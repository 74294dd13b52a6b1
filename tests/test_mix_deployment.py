"""The deployment `wifi-a-mix-8s` on the served path at toy width
(ISSUE 33): the rehearsal twin's population (DATA, ACK, DATA, ACK,
TCP-ACK, ACK over the sixteen-entry rate list, gaps in [320, 680),
K = 16) through `ServeRuntime`, every frame held to what was sent and
to the benchmark's plain numpy receiver on the same samples.

Two sessions of the twin (0 opens its lap with a data frame, 1 with an
ACK) come from the benchmark's own generator (`load.synth_laps`) and
are served once; the parametrised cases read that one run. The seam
cases cut one densest-legal stream (every gap exactly aSIFSTime) so
that a chunk's ownership boundary falls at chosen points of a
560-sample ACK and the frame 320 samples after it. A CPU run: results
and counts, never speeds.
"""

import json
import os

import numpy as np
import pytest

from benchmark import lap_check
from benchmark.harness import checks, counts, load
from ziria_tpu.backend import framebatch
from ziria_tpu.phy import link
from ziria_tpu.phy.wifi import rx
from ziria_tpu.runtime import serve
from ziria_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/tests/rehearse/wifi-a-mix-8s.json")) as _f:
    TWIN = json.load(_f)
GEO, POP, CHAN = TWIN["geometry"], TWIN["population"], TWIN["channel"]
CHUNK, FRAME_LEN, K = (GEO["chunk_len"], GEO["frame_len"],
                       GEO["max_frames_per_chunk"])
STRIDE = CHUNK - FRAME_LEN
S, SEED = 2, 33
ACK, TCP_ACK, DATA = sorted(set(POP["psdu_bytes"]))
BASIC = (6, 12, 24)
ALL = (6, 9, 12, 18, 24, 36, 48, 54)
CASES = [("ack", ACK, m) for m in BASIC] \
    + [("tcp_ack", TCP_ACK, m) for m in ALL] \
    + [("data", DATA, m) for m in ALL]


def _runtime(k: int):
    return serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=k, check_fcs=True))


def _serve(srv, streams):
    """A stride a session a tick until every stream is through, then
    the step in flight. Returns the frames per session, as emitted."""
    with telemetry.collect(srv.registry):
        for i in range(len(streams)):
            assert srv.connect(f"s{i}").admitted
        out, pos = [], 0
        while pos < max(len(st) for st in streams) + CHUNK:
            for i, st in enumerate(streams):
                slab = np.zeros((STRIDE, 2), np.float32)
                part = st[pos: pos + STRIDE]
                slab[:len(part)] = part
                srv.submit(f"s{i}", slab)
            out += srv.step()
            pos += STRIDE
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


@pytest.fixture(scope="module")
def laps():
    return load.synth_laps(dict(TWIN, sessions=S), SEED)


@pytest.fixture(scope="module")
def served(laps):
    srv = _runtime(K)
    with telemetry.tracing() as tr:
        frames = _serve(srv, [lap.stream for lap in laps])
    spans = [e for e in tr.events() if e["ph"] == "X"]
    good = [[_agrees(fr, lap.stream, lap.rates[j], lap.psdus[j])
             for j, fr in enumerate(mine[: len(lap.starts)])]
            for lap, mine in zip(laps, frames)]
    return srv, frames, good, spans


def test_every_frame_once_in_order_where_it_was_sent(served, laps):
    srv, frames, good, _spans = served
    for lap, mine, ok in zip(laps, frames, good):
        assert [fr.start for fr in mine] == lap.starts.tolist()
        assert all(ok)
    st = srv._rx.stats
    assert (st.overflow_chunks, st.quarantines, st.sanitized,
            st.lane_blowups) == (0, 0, 0, 0) and not st.degraded
    steps = st.chunk_steps
    rows = checks.check_hidden(st, srv.registry.snapshot(), {}, 0, 0,
                               steps)
    assert all(r.ok for r in rows), [r for r in rows if not r.ok]


@pytest.mark.parametrize("kind,size,mbps", CASES,
                         ids=[f"{k}@{m}" for k, _b, m in CASES])
def test_each_size_class_at_each_of_its_rates(served, laps, kind, size,
                                              mbps):
    _srv, frames, good, _spans = served
    n = 0
    for lap, mine, ok in zip(laps, frames, good):
        for j, (m, p) in enumerate(zip(lap.rates, lap.psdus)):
            if (p.size, m) != (size, mbps):
                continue
            n += 1
            assert ok[j], (kind, mbps, j)
            assert mine[j].result.length_bytes == size + 4
    # a lap holds 16 data frames, 8 TCP ACKs and 24 ACKs, in every
    # session
    want = {"data": 2, "tcp_ack": 1}.get(kind) or {6: 6, 12: 6, 24: 12}[mbps]
    assert n == S * want


def test_spans_count_the_slots_and_the_registry_the_classes(served, laps):
    srv, frames, _good, spans = served
    dec = [e for e in spans if e["name"] == "rx.fleet.decode"]
    # the slots the decode fronted: the program's own rule for the
    # step's lanes (whole groups), at most the S x K it was given
    assert dec and all(
        e["args"]["slots"]
        == min(rx.decode_walk(e["args"]["lanes"], S * K)[0], S * K)
        for e in dec)
    n_frames = sum(len(lap.starts) for lap in laps)
    assert sum(e["args"]["lanes"] for e in dec) == n_frames
    assert all(e["args"]["lanes"] <= e["args"]["slots"] for e in dec)
    assert sum(e["args"]["frames"] for e in spans
               if e["name"] == "rx.fleet.emit") == n_frames
    cls = [e for e in spans if e["name"] == "rx.fleet.classify"]
    assert all(e["args"]["candidates"] == e["args"]["acquired"]
               for e in cls)
    reg = srv.registry
    n_ack = sum(p.size == ACK for lap in laps for p in lap.psdus)
    assert n_ack == n_frames // 2
    assert reg.find("rx.stream_frames_by_length", psdu="ack").value \
        == n_ack
    # at toy width the data frames (28 and 20 bytes) are "short" too
    assert reg.find("rx.stream_frames_by_length", psdu="short").value \
        == n_frames - n_ack
    assert reg.find("rx.stream_frames_by_length", psdu="long") is None
    assert reg.find("rx.stream_frames").value == n_frames
    assert 'rx_stream_frames_by_length{psdu="ack"}' in srv.scrape()


def test_length_classes_are_14_bytes_a_tcp_ack_and_the_rest():
    """The three classes at the real sizes, with no device: the
    counter's own arithmetic on results shaped like the receiver's."""
    from ziria_tpu.phy.wifi import rx

    def frame(ok, nbytes):
        return 0, framebatch.StreamFrame(0, rx.RxResult(
            ok, 6, nbytes, np.zeros(0, np.uint8), ok or None))

    out = [frame(True, 14)] * 3 + [frame(True, 76)] * 2 \
        + [frame(True, 1504), frame(True, 16), frame(True, 128),
           frame(True, 129), frame(False, 0), frame(False, 1504)]
    reg = telemetry.MetricsRegistry()
    framebatch._count_emitted(out, len(out))    # nothing collecting
    with telemetry.collect(reg):
        framebatch._count_emitted(out, len(out))
        framebatch._count_emitted([], len(out))
    got = {c: reg.find("rx.stream_frames_by_length", psdu=c).value
           for c in ("ack", "short", "long")}
    assert got == {"ack": 4, "short": 3, "long": 2}
    assert reg.find("rx.stream_frames").value == len(out)


# ------------------------------------------------- across a chunk seam

SEAM_LEAD = STRIDE + 64         # a stride of noise to shift the cut by


@pytest.fixture(scope="module")
def dense():
    """Session 0's frames at the densest legal spacing: every gap
    exactly aSIFSTime, after a lead of more than a stride of noise."""
    rates, psdus, _lead, gaps, _starts, _tail = load.plan_lap(
        POP, SEED, 0)
    stream, starts = link.stream_many(
        psdus, rates, gaps=np.full_like(gaps, 320),
        snr_db=CHAN["snr_db"], cfo=CHAN["cfo_rad_per_sample"],
        delay=SEAM_LEAD, seed=SEED, add_fcs=True, tail=FRAME_LEN,
        channel_profile="flat")
    lens = [counts.frame_samples(p.size + 4, m)
            for p, m in zip(psdus, rates)]
    # the first 560-sample ACK with two frames on either side
    j = next(j for j in range(2, len(rates) - 2)
             if lens[j] == 560 and psdus[j].size == ACK)
    assert starts[j + 1] - starts[j] == 560 + 320
    return stream, starts, rates, psdus, lens, j


@pytest.mark.parametrize("seam_at", [0, 1, 560, 720, 880, 881])
def test_a_frame_a_sifs_after_a_560_sample_ack_across_the_seam(
        dense, seam_at):
    """The chunk's ownership boundary `seam_at` samples after the
    ACK's first: at it, one past it, at its end, in the gap, at the
    next frame's first sample and one past that. Both frames and
    their neighbours come out once, in order, right."""
    stream, starts, rates, psdus, lens, j = dense
    a = int(starts[j - 2]) - 160             # inside the gap before
    b = int(starts[j + 2]) + lens[j + 2] + 160
    z = STRIDE - seam_at - (int(starts[j]) - a)
    assert 0 <= z <= SEAM_LEAD - 64
    sub = np.concatenate([stream[:z], stream[a:b],
                          stream[:FRAME_LEN]])   # noise either side
    want = [z + int(starts[t]) - a for t in range(j - 2, j + 3)]
    assert want[2] + seam_at == STRIDE
    srv = _runtime(K)
    (mine,) = _serve(srv, [sub])
    assert [fr.start for fr in mine] == want
    for fr, t in zip(mine, range(j - 2, j + 3)):
        assert _agrees(fr, sub, rates[t], psdus[t]), (seam_at, t)
    assert srv._rx.stats.overflow_chunks == 0


# ---------------------------------------------------- a K too small

def test_a_k_too_small_is_counted_and_reported_never_silent(laps):
    """The same population at K = 4, where an owned window holds up to
    seven starts: the chunks that dropped frames are counted, the
    benchmark's row reads them, and what does come out is right."""
    srv = _runtime(4)
    n = 3 * STRIDE
    frames = _serve(srv, [lap.stream[:n] for lap in laps])
    st = srv._rx.stats
    assert st.overflow_chunks >= 1
    row = checks.check_hidden(st, {}, {}, 0, 0, st.chunk_steps)[0]
    assert row.name == "overflow_chunks" and not row.ok
    lost = 0
    for lap, mine in zip(laps, frames):
        sent = {int(s): t for t, s in enumerate(lap.starts)}
        whole = [int(s) for s in lap.starts if s + FRAME_LEN <= n]
        assert all(fr.start in sent for fr in mine)
        for fr in mine:
            if fr.start in whole:
                t = sent[fr.start]
                assert _agrees(fr, lap.stream, lap.rates[t],
                               lap.psdus[t])
        lost += len(set(whole) - {fr.start for fr in mine})
    assert lost >= 1            # the overflow was real, and was flagged
