"""The deployment `wifi-a-maxpsdu-8s` on the served path at toy width
(ISSUE 43): the rehearsal twin's population (every frame one length,
all eight rates, the 6 and 9 Mbit/s frames longer than half the
window) through `ServeRuntime`, every frame held to what was sent and
to the benchmark's plain numpy receiver on the same samples.

Two sessions of the twin come from the benchmark's own generator
(`load.synth_laps`) and are served twice: at the twin's window, which
holds every frame, and at half of it (as 65 536 is to the real
deployment's 131 072), which cannot hold the two longest: those come
back as truncated results, named by rate and length, and are counted;
the rest come out right. The ingress bounds that follow the geometry
are held on a stub receiver, at the real sizes. A CPU run: results and
counts, never speeds.
"""

import json
import os

import numpy as np
import pytest

from benchmark import lap_check
from benchmark.harness import checks, counts, load
from ziria_tpu.backend import framebatch
from ziria_tpu.phy.wifi import rx
from ziria_tpu.runtime import serve
from ziria_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/tests/rehearse/wifi-a-maxpsdu-8s.json")) as _f:
    TWIN = json.load(_f)
GEO, POP = TWIN["geometry"], TWIN["population"]
CHUNK, FRAME_LEN, K = (GEO["chunk_len"], GEO["frame_len"],
                       GEO["max_frames_per_chunk"])
S, SEED = 2, 43
ALL = (6, 9, 12, 18, 24, 36, 48, 54)
LONG = (6, 9)                   # longer than half the twin's window
PSDU = POP["psdu_bytes"][0] + 4


def _runtime(chunk_len: int, frame_len: int):
    return serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=chunk_len, frame_len=frame_len,
        max_frames_per_chunk=K, check_fcs=True))


def _serve(srv, streams):
    """A stride a session a tick until every stream is through, then
    the steps in flight. Returns the frames per session, as emitted."""
    stride = srv.cfg.chunk_len - srv.cfg.frame_len
    with telemetry.collect(srv.registry):
        for i in range(len(streams)):
            assert srv.connect(f"s{i}").admitted
        out, pos = [], 0
        while pos < max(len(st) for st in streams) + srv.cfg.chunk_len:
            for i, st in enumerate(streams):
                slab = np.zeros((stride, 2), np.float32)
                part = st[pos: pos + stride]
                slab[:len(part)] = part
                assert srv.submit(f"s{i}", slab).accepted
            out += srv.step()
            pos += stride
        out += [(srv._lane_sid[ln], fr)
                for ln, fr in srv._rx.drain_pending()]
    return [[fr for sid, fr in out if sid == f"s{i}"]
            for i in range(len(streams))]


def _agrees(fr, stream, mbps, psdu) -> bool:
    """One served frame against what was sent and against the plain
    numpy receiver on the same samples: rate, length, bytes."""
    res = fr.result
    sent = res.ok and res.rate_mbps == mbps and res.crc_ok is True \
        and res.length_bytes == psdu.size + 4 \
        and np.array_equal(checks._bytes(res.psdu_bits)[: psdu.size],
                           psdu)
    return bool(sent and lap_check.reference_agrees(
        res, stream[fr.start: fr.start + FRAME_LEN]))


def _spans(tr, name):
    return [e["args"] for e in tr.events()
            if e["ph"] == "X" and e["name"] == name]


@pytest.fixture(scope="module")
def laps():
    return load.synth_laps(dict(TWIN, sessions=S), SEED)


@pytest.fixture(scope="module")
def served(laps):
    """The twin's own window: it holds every frame."""
    srv = _runtime(CHUNK, FRAME_LEN)
    with telemetry.tracing() as tr:
        frames = _serve(srv, [lap.stream for lap in laps])
    return srv, frames, tr


@pytest.fixture(scope="module")
def halved(laps):
    """Half the window: the 6 and 9 Mbit/s frames do not fit it."""
    srv = _runtime(CHUNK // 2, FRAME_LEN // 2)
    with telemetry.tracing() as tr:
        frames = _serve(srv, [lap.stream for lap in laps])
    return srv, frames, tr


def test_the_twin_is_the_deployment_in_small():
    by_rate = {m: counts.frame_samples(PSDU, m) for m in ALL}
    assert {m for m, n in by_rate.items() if n > FRAME_LEN // 2} \
        == set(LONG)
    assert max(by_rate.values()) <= FRAME_LEN


@pytest.mark.parametrize("mbps", ALL)
def test_every_frame_of_each_rate_at_the_twins_window(served, laps,
                                                      mbps):
    _srv, frames, _tr = served
    n = 0
    for lap, mine in zip(laps, frames):
        assert [fr.start for fr in mine] == lap.starts.tolist()
        for j, m in enumerate(lap.rates):
            if m == mbps:
                n += 1
                assert _agrees(mine[j], lap.stream, m, lap.psdus[j]), j
    assert n == 2 * S           # each rate twice a lap


def test_nothing_is_truncated_hidden_or_refused_at_the_twins_window(
        served, laps):
    srv, _frames, tr = served
    st = srv._rx.stats
    assert st.truncated_frames == 0
    assert srv.registry.find("rx.stream_frames_truncated") is None
    assert srv.registry.find("serve.rejected_slabs",
                             reason="oversized") is None
    rows = checks.check_hidden(st, srv.registry.snapshot(), {}, 0, 0,
                               st.chunk_steps)
    assert all(r.ok for r in rows), [r for r in rows if not r.ok]
    n_frames = sum(len(lap.starts) for lap in laps)
    emits = _spans(tr, "rx.fleet.emit")
    assert all(a["truncated"] == 0 for a in emits)
    assert sum(a["acquired"] for a in emits) \
        == sum(a["frames"] for a in emits) == n_frames


def test_the_decode_span_weighs_the_frames_against_their_windows(
        served, laps):
    """`frame_samples` / `window_samples` (the benchmark's
    `window_fill_share`): each frame's own length on air, preamble and
    SIGNAL included, over the whole window every one of the S x K
    slots was cut at."""
    srv, _frames, tr = served
    dec = _spans(tr, "rx.fleet.decode")
    assert dec and all(a["window_samples"] == S * K * FRAME_LEN
                       for a in dec)
    assert all(a["frame_samples"]
               == a["lanes"] * rx.FRAME_DATA_START
               + 80 * a["useful_symbols"] for a in dec)
    assert sum(a["frame_samples"] for a in dec) \
        == sum(counts.frame_samples(PSDU, m)
               for lap in laps for m in lap.rates)
    assert all(0 < a["frame_samples"] <= a["window_samples"]
               for a in dec)


@pytest.mark.parametrize("mbps", ALL)
def test_half_the_window_truncates_the_two_long_rates_and_no_other(
        halved, laps, mbps):
    _srv, frames, _tr = halved
    for lap, mine in zip(laps, frames):
        # every frame is still reported, once, where it was sent
        assert [fr.start for fr in mine] == lap.starts.tolist()
        for j, m in enumerate(lap.rates):
            if m != mbps:
                continue
            res = mine[j].result
            if mbps in LONG:
                # not delivered, and named: SIGNAL's rate and length
                assert not res.ok and res.psdu_bits.size == 0
                assert (res.rate_mbps, res.length_bytes) == (m, PSDU)
            else:
                assert _agrees(mine[j], lap.stream, m, lap.psdus[j])


def test_the_truncated_are_counted_exactly(halved, laps):
    srv, frames, tr = halved
    want = sum(m in LONG for lap in laps for m in lap.rates)
    assert want == 4 * S
    st = srv._rx.stats
    assert st.truncated_frames == want
    assert st.frames == sum(len(lap.starts) for lap in laps)
    assert st.overflow_chunks == 0 and not st.degraded
    assert srv.registry.find("rx.stream_frames_truncated").value == want
    assert "rx_stream_frames_truncated" in srv.scrape()
    # the trace's counter track ends at the running total
    track = [e for e in tr.events() if e["ph"] == "C"
             and e["name"] == "rx.stream_frames_truncated"]
    assert track and list(track[-1]["args"].values()) == [want]
    emits = _spans(tr, "rx.fleet.emit")
    assert sum(a["truncated"] for a in emits) == want
    # a truncated frame was acquired (its SIGNAL parsed): it is in
    # `acquired`, as on `rx.fleet.classify`, and not in the decode
    by_step = {a["step"]: a["acquired"]
               for a in _spans(tr, "rx.fleet.classify")}
    assert all(a["acquired"] == by_step[a["step"]] for a in emits)
    assert sum(a["acquired"] for a in emits) == st.frames
    assert sum(a["lanes"] for a in _spans(tr, "rx.fleet.decode")) \
        == st.frames - want


def test_the_oracle_path_counts_them_too(laps):
    """``streaming=False`` (and the degraded mode that shares it):
    the same windows through per-capture `rx.receive`, the same
    count."""
    lap = laps[0]
    n = int(lap.starts[3])      # the first three frames: 6, 9, 12
    assert lap.rates[:3] == [6, 9, 12]
    rcv = framebatch.MultiStreamReceiver(
        n_streams=S, chunk_len=CHUNK // 2, frame_len=FRAME_LEN // 2,
        max_frames_per_chunk=K, check_fcs=True, streaming=False)
    reg = telemetry.MetricsRegistry()
    with telemetry.collect(reg):
        out = rcv.push(0, lap.stream[:n]) + rcv.flush()
    assert [(fr.result.ok, fr.result.rate_mbps) for _i, fr in out] \
        == [(False, 6), (False, 9), (True, 12)]
    assert rcv.stats.truncated_frames == 2
    assert reg.find("rx.stream_frames_truncated").value == 2


def test_a_stream_cut_mid_frame_at_flush_counts_as_truncated(laps):
    """A frame whose stream ends inside its DATA field: the window had
    room, the stream did not; it is reported and counted the same."""
    lap = laps[0]
    cut = int(lap.starts[2]) + 800      # 12 Mbit/s: 1840 samples
    rcv = framebatch.MultiStreamReceiver(
        n_streams=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True)
    out = rcv.push(0, lap.stream[:cut]) + rcv.flush()
    assert [(fr.result.ok, fr.result.rate_mbps) for _i, fr in out] \
        == [(True, 6), (True, 9), (False, 12)]
    assert rcv.stats.truncated_frames == 1


def test_truncated_is_the_failure_that_names_its_rate():
    def res(ok, mbps):
        return rx.RxResult(ok, mbps, 100 if mbps else 0,
                           np.zeros(0, np.uint8), None)

    fail, _ = rx._classify_acquire(False, 0, 0, 0, False)
    cut, _ = rx._classify_acquire(True, 3000, 0b1101, 100, True)
    assert (cut.ok, cut.rate_mbps, cut.length_bytes) == (False, 6, 100)
    assert framebatch._truncated([fail, cut, res(True, 6), cut]) == 2
    assert framebatch._truncated([]) == 0


# ------------------------------------- the bounds follow the geometry

class _Stub:
    """As much receiver as `submit` and one `step` need."""

    def __init__(self, cfg):
        self.s, self.chunk_len = cfg.n_lanes, cfg.chunk_len

    def push_many(self, slabs):
        self.pushed = {i: len(a) for i, a in slabs.items()}
        return []

    def quarantined(self, _lane):
        return False

    class stats:        # noqa: N801 - read as attributes
        chunk_steps = frames = 0


def _stub_runtime(**kw):
    cfg = serve.ServeConfig(**kw)
    srv = serve.ServeRuntime(cfg, receiver=_Stub(cfg))
    assert srv.connect("a").admitted
    return srv


@pytest.mark.parametrize("kw,want", [
    ({}, (1 << 16, 1 << 18)),
    (dict(chunk_len=131072, frame_len=65536), (65536, 262144)),
    (dict(chunk_len=8192, frame_len=4096), (65536, 262144)),
    (dict(chunk_len=262144, frame_len=131072), (131072, 524288)),
    (dict(chunk_len=262144, frame_len=131072, max_slab_samples=4096),
     (4096, 524288)),
    (dict(chunk_len=262144, frame_len=131072,
          max_backlog_samples=1 << 17), (131072, 1 << 17)),
    (dict(max_slab_samples=512, max_backlog_samples=1024), (512, 1024)),
], ids=["default", "accepted-cells", "twin", "maxpsdu", "slab-given",
        "backlog-given", "both-given"])
def test_ingress_bounds_by_geometry(kw, want):
    """Today's 65 536 / 262 144 wherever a stride and two chunks are
    no larger; a stride and two chunks beyond; the caller's word
    first."""
    assert serve.ServeConfig(**kw).ingress_bounds() == want


def test_a_stride_is_admitted_and_two_chunks_stage_at_the_real_sizes():
    srv = _stub_runtime(n_lanes=1, chunk_len=262144, frame_len=131072)
    stride = np.zeros((131072, 2), np.float32)
    for _ in range(4):                  # two chunks staged
        assert srv.submit("a", stride).accepted
    r = srv.submit("a", stride)         # a fifth stride is over them
    assert (r.accepted, r.reason) == (False, "backlog_full")
    r = srv.submit("a", np.zeros((131073, 2), np.float32))
    assert (r.accepted, r.reason) == (False, "oversized")
    srv.step()                          # takes one chunk's worth
    assert srv._rx.pushed == {0: 262144}
    assert srv.submit("a", stride).accepted
    # what the caller left unset stays unset in the config it reads
    # back (and in a snapshot's body)
    assert srv.cfg.max_slab_samples is None


def test_an_explicit_bound_still_refuses_a_stride():
    srv = _stub_runtime(n_lanes=1, chunk_len=262144, frame_len=131072,
                        max_slab_samples=65536)
    r = srv.submit("a", np.zeros((131072, 2), np.float32))
    assert (r.accepted, r.reason) == (False, "oversized")
    assert srv.submit("a", np.zeros((65536, 2), np.float32)).accepted
