"""Where a served fleet lies (ISSUE 37): the runtime's own placement
rule, and a 32-lane fleet over four of the suite's forced CPU devices
against the same fleet on one and against the plain numpy receiver.

`ServeConfig.shard` left at None places by rule: the widest dp mesh
that divides the lanes, fits the visible devices and leaves every
device at least the tuned fleet width (`Geometry.n_streams`, 8). The
suite geometry (chunk 4096, window 1024, K = 8) keeps the compiles
small; a CPU run gives frames and placements, never speeds.
"""

import numpy as np
import pytest

from benchmark.reference.wifi_rx_ref import np_receive
from ziria_tpu.backend import framebatch
from ziria_tpu.parallel import batch as pbatch
from ziria_tpu.phy import link
from ziria_tpu.runtime import serve
from ziria_tpu.utils import geometry, telemetry
from ziria_tpu.utils.bits import np_bits_to_bytes

N_BYTES = 12                     # +4 FCS = 16 bytes on air
CHUNK, FRAME_LEN, K = 4096, 1024, 8
SLAB = CHUNK - FRAME_LEN         # the chunk stride: a chunk-step a tick
S = 32
RATES = (6, 9, 12, 18, 24, 36, 48, 54)
WIDTH = geometry.DEFAULT.n_streams


@pytest.mark.parametrize("lanes,devices,want", [
    (8, 1, 1), (8, 4, 1), (8, 8, 1), (16, 4, 2), (32, 4, 4), (32, 2, 2),
    (32, 1, 1), (24, 4, 3)])
def test_the_rule_gives_every_chip_the_tuned_width(lanes, devices, want):
    assert WIDTH == 8
    mesh = pbatch.elastic_mesh(lanes, n_devices=devices, min_lanes=WIDTH)
    assert (1 if mesh is None else mesh.size) == want
    if mesh is not None:
        assert lanes % mesh.size == 0 and lanes // mesh.size >= WIDTH
        assert mesh.axis_names == ("dp",)


def test_the_floor_is_at_least_a_lane():
    with pytest.raises(ValueError):
        pbatch.elastic_mesh(8, min_lanes=0)
    # without a floor the rule is the recovery's: every device it divides
    assert pbatch.elastic_mesh(8, n_devices=4).size == 4


def _mesh_size(**cfg) -> int:
    srv = serve.ServeRuntime(serve.ServeConfig(
        chunk_len=CHUNK, frame_len=FRAME_LEN, max_frames_per_chunk=K,
        **cfg))
    return 1 if srv._rx.mesh is None else srv._rx.mesh.size


@pytest.mark.parametrize("cfg,want", [
    (dict(n_lanes=8), 1), (dict(n_lanes=16), 2), (dict(n_lanes=32), 4),
    (dict(n_lanes=12), 1), (dict(n_lanes=64), 8),
    (dict(n_lanes=8, shard=True), 8), (dict(n_lanes=32, shard=True), 8),
    (dict(n_lanes=32, shard=False), 1), (dict(n_lanes=64, shard=False), 1)])
def test_the_runtime_places_by_the_rule_unless_told(cfg, want):
    """Under the suite's eight devices: None follows the rule, True takes
    every device the lanes divide over, False none."""
    assert serve.ServeConfig().shard is None
    assert _mesh_size(**cfg) == want


# --------------------------------- 32 lanes over four devices, served


def _streams():
    rng = np.random.default_rng(20260929)
    streams, sent = [], []
    for i in range(S):
        rates = [RATES[(i + j) % 8] for j in range(1 + i % 2)]
        psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
                 for _ in rates]
        st, starts = link.stream_many(
            psdus, rates, snr_db=30.0, cfo=1e-4, delay=60 + 37 * i,
            seed=500 + i, add_fcs=True, tail=FRAME_LEN)
        streams.append(st)
        sent.append(list(zip(starts, rates, psdus)))
    return streams, sent


def _serve(streams, **cfg):
    """Every stream through a fresh ServeRuntime, a stride a session a
    tick. Returns (runtime, frames per session, the devices each
    chunk scan's first output lay on)."""
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True, queue_cap=S, **cfg))
    got = {f"s{i}": [] for i in range(S)}
    placed = []
    with srv:
        for i in range(S):
            assert srv.connect(f"s{i}").admitted
        pos = 0
        while pos < max(len(s) for s in streams) + CHUNK:
            for i, st in enumerate(streams):
                slab = np.zeros((SLAB, 2), np.float32)
                part = st[pos: pos + SLAB]
                slab[:len(part)] = part
                assert srv.submit(f"s{i}", slab).accepted
            for sid, fr in srv.step():
                got[sid].append(fr)
            pend = srv._rx._pending
            if pend is not None:
                placed.append(sorted(
                    str(sh.device)
                    for sh in pend[-1][0].addressable_shards))
            pos += SLAB
        for sid, fr in srv.drain():
            got[sid].append(fr)
    return srv, got, placed


STARTED = {}        # placement -> arrays per `_start_pull` call, in order


@pytest.fixture(scope="module")
def served():
    streams, sent = _streams()
    started = []                    # arrays a `_start_pull` call sent off
    real = framebatch._start_pull

    def recording(arrays):
        started.append(len(arrays))
        return real(arrays)

    framebatch._start_pull = recording
    try:
        with telemetry.tracing() as tr:
            srv4, got4, placed4 = _serve(streams)      # the default
        STARTED["sharded"] = list(started)
        del started[:]
        srv1, got1, placed1 = _serve(streams, shard=False)
        STARTED["one device"] = list(started)
    finally:
        framebatch._start_pull = real
    spans = [e for e in tr.events() if e["ph"] == "X"]
    return streams, sent, (srv4, got4, placed4), (srv1, got1, placed1), \
        spans


def test_32_lanes_lie_over_four_devices_with_no_argument(served):
    _streams_, _sent, (srv4, _g, placed4), (srv1, _g1, placed1), _sp \
        = served
    assert srv4.cfg.shard is None
    assert srv4._rx.mesh is not None and srv4._rx.mesh.size == 4
    assert placed4 and all(len(set(p)) == 4 for p in placed4)
    assert srv1._rx.mesh is None
    assert placed1 and all(len(set(p)) == 1 for p in placed1)
    for srv in (srv4, srv1):
        st = srv._rx.stats
        assert not st.degraded and st.overflow_chunks == 0 \
            and st.quarantines == 0 and st.lane_blowups == 0


def test_every_session_gets_what_it_sent_on_both_placements(served):
    _streams_, sent, (_s4, got4, _p4), (_s1, got1, _p1), _sp = served
    for got in (got4, got1):
        for i, want in enumerate(sent):
            frames = sorted(got[f"s{i}"], key=lambda f: f.start)
            assert [f.start for f in frames] == [w[0] for w in want]
            for fr, (_start, mbps, psdu) in zip(frames, want):
                r = fr.result
                assert r.ok and r.crc_ok is True
                assert r.rate_mbps == mbps
                assert r.length_bytes == N_BYTES + 4
                assert np.array_equal(
                    np_bits_to_bytes(np.asarray(r.psdu_bits))[:N_BYTES],
                    psdu)


def test_sharded_equals_unsharded_byte_for_byte(served):
    _streams_, _sent, (_s4, got4, _p4), (_s1, got1, _p1), _sp = served
    assert got4.keys() == got1.keys()
    n = 0
    for sid in got4:
        a = sorted(got4[sid], key=lambda f: f.start)
        b = sorted(got1[sid], key=lambda f: f.start)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.start == y.start
            assert x.result.ok == y.result.ok
            assert x.result.rate_mbps == y.result.rate_mbps
            assert x.result.length_bytes == y.result.length_bytes
            assert x.result.crc_ok == y.result.crc_ok
            assert np.array_equal(x.result.psdu_bits, y.result.psdu_bits)
            n += 1
    assert n == sum(1 + i % 2 for i in range(S))


def test_the_plain_reference_reads_the_same_bytes(served):
    """`benchmark/reference/wifi_rx_ref.np_receive` on the capture of
    every frame the sharded fleet emitted: the same rate, length and
    PSDU bytes, FCS included."""
    streams, _sent, (_s4, got4, _p4), _one, _sp = served
    for i, st in enumerate(streams):
        for fr in got4[f"s{i}"]:
            ref = np_receive(st[fr.start: fr.start + FRAME_LEN])
            assert ref is not None, (i, fr.start)
            assert ref.rate_mbps == fr.result.rate_mbps
            assert ref.length_bytes == fr.result.length_bytes
            assert np.array_equal(
                ref.psdu,
                np_bits_to_bytes(np.asarray(fr.result.psdu_bits)))


def test_spans_and_gauge_name_the_four_devices(served):
    """The args a per-layer metric reads (`lanes_per_chip`: `lanes` over
    `devices` on `rx.fleet.put`) and the transfers a pull issues, on
    the sharded fleet; the bytes are the whole fleet's."""
    _streams_, _sent, (srv4, _g4, _p4), (srv1, _g1, _p1), spans = served
    puts = [e["args"] for e in spans if e["name"] == "rx.fleet.put"]
    assert puts and all(a["devices"] == 4 and a["lanes"] == S
                        for a in puts)
    assert {a["bytes"] for a in puts} == {S * CHUNK * 2 * 4 + 3 * S * 4}
    # 8 lanes a device at this chunk: 8 x ceil((4096 - 63) / 512)
    assert {a["locate_rows"] for a in puts} == {8 * 8}
    scans = [e["args"] for e in spans if e["name"] == "rx.fleet.pull_scan"]
    assert scans and {a["shards"] for a in scans} == {9 * 4}
    assert {a["bytes"] for a in scans} == {S * K * (3 * 1 + 5 * 4) + S}
    decs = [e["args"] for e in spans
            if e["name"] == "rx.fleet.pull_decode"]
    assert decs and {a["shards"] for a in decs} == {2 * 4}
    for srv, want in ((srv4, 4.0), (srv1, 1.0)):
        g = srv.registry.find(telemetry.GAUGE_METRIC,
                              site="rx.mesh_devices")
        assert g.last == want and len(g.samples) == 1


def test_a_step_starts_every_copy_before_it_blocks(served):
    """On a mesh and on one device alike: the nine scan scalars leave
    their devices as the step is launched, the decode's two arrays as
    it is dispatched, each a launch before the host reads them."""
    _streams_, _sent, (srv4, _g4, _p4), (srv1, _g1, _p1), spans = served
    decodes = sum(1 for e in spans if e["name"] == "rx.fleet.pull_decode")
    for got, srv in ((STARTED["sharded"], srv4),
                     (STARTED["one device"], srv1)):
        assert got.count(9) == srv._rx.stats.chunk_steps
        assert got.count(2) == decodes >= 1
        assert set(got) == {9, 2}
        # launched first: a step's scalars are on their way a tick early
        assert got[0] == 9
