"""Multi-stream fleet receiver (backend/framebatch.receive_streams +
MultiStreamReceiver + rx._jit_stream_chunk_multi/_jit_stream_decode_multi):
S concurrent I/Q streams' chunks stacked on a leading stream axis
through the two compiled streaming programs — <= 2 device dispatches
per CHUNK-STEP independent of S — with every emitted frame
bit-identical, lane for lane and RxResult field for field, to that
stream received ALONE (`receive_stream`: a fleet of one) and hence,
transitively, to per-capture `rx.receive` over the slice — the PR 5
contract.

Budget discipline (the tier-1 870 s cutoff is real): ONE module
fixture pays the S=8 fleet compiles at the suite-shared streaming
geometry (chunk 4096, window 1024, K=8, 8-symbol bucket — the same
keys test_rx_stream and test_programs share), covering mixed rates
(all 8 across the fleet), a chunk-boundary-straddling frame, an
all-noise stream, an EMPTY stream, and ragged lengths. The sharded
run (frame_mesh(8), one stream per virtual device) and the S=1 pin
compile their own (small) programs; everything else re-dispatches.
"""

import numpy as np
import pytest

from ziria_tpu.backend import framebatch
from ziria_tpu.phy import link
from ziria_tpu.phy.wifi import rx
from ziria_tpu.utils import dispatch

N_BYTES = 12     # +4 FCS = the suite's standard 16-byte on-air PSDU
CHUNK, FRAME_LEN, K, S = 4096, 1024, 8, 8
GEO = dict(chunk_len=CHUNK, frame_len=FRAME_LEN,
           max_frames_per_chunk=K, check_fcs=True)


def _same_result(a, b) -> bool:
    return (a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)


def _same_frames(got, want) -> None:
    assert [f.start for f in got] == [f.start for f in want]
    for a, b in zip(got, want):
        assert _same_result(a.result, b.result)


@pytest.fixture(scope="module")
def corpus():
    """An 8-stream fleet load: all 8 rates spread across the streams,
    one stream whose second frame straddles its chunk boundary, one
    all-noise stream, one EMPTY stream, ragged lengths — plus one
    fleet pass and one pass of every stream alone (S fleets of one),
    both under dispatch counters."""
    rng = np.random.default_rng(20260803)

    def psdus(n):
        return [rng.integers(0, 256, N_BYTES).astype(np.uint8)
                for _ in range(n)]

    per_psdus = [psdus(2), psdus(2), [], psdus(3), psdus(2),
                 psdus(1), [], psdus(2)]
    per_rates = [[6, 54], [54, 54], [], [24, 36, 48], [9, 12],
                 [18], [], [48, 6]]
    per_gaps = [None, [3260], None, None, None, None, None, None]
    per_delay = [60, 60, 0, 500, 1500, 30, 0, 100]
    streams, starts = [], []
    for i in range(S):
        if not per_psdus[i]:
            streams.append(np.zeros((0, 2), np.float32))
            starts.append(np.zeros((0,), np.int64))
            continue
        st, sts = link.stream_many(
            per_psdus[i], per_rates[i], gaps=per_gaps[i],
            snr_db=30.0, cfo=1e-4, delay=per_delay[i],
            seed=40 + i, add_fcs=True, tail=FRAME_LEN)
        streams.append(st)
        starts.append(sts)
    # stream 2: noise, no frames (long enough to own a full chunk)
    streams[2] = rng.normal(scale=0.05, size=(CHUNK + 2000, 2)) \
        .astype(np.float32)
    # the straddle stream really straddles: frame 1 starts inside
    # chunk 0's overlap and crosses the 4096 boundary (the
    # test_rx_stream recipe, here as ONE lane of the fleet)
    assert starts[1][1] == 3800 and starts[1][1] + 480 > CHUNK

    with dispatch.count_dispatches() as d_m:
        res_m, st_m = framebatch.receive_streams(streams, **GEO)
    # the reference: every stream ALONE through `receive_stream`
    with dispatch.count_dispatches() as d_o:
        lone = [framebatch.receive_stream(st, **GEO) for st in streams]
    res_o, st_o = [r for r, _ in lone], [st for _, st in lone]
    return streams, starts, res_m, st_m, d_m, res_o, st_o, d_o


def test_fleet_bit_identical_to_s_independent_receivers(corpus):
    # THE fleet contract: per stream, frame for frame, every emitted
    # start and RxResult (crc_ok included) equals what that stream
    # received alone emits — mixed rates, straddle, noise,
    # empty, and ragged lengths all riding one stream axis
    streams, starts, res_m, _st, _d, res_o, _so, _do = corpus
    assert len(res_m) == len(res_o) == S
    for i in range(S):
        _same_frames(res_m[i], res_o[i])
        assert [f.start for f in res_m[i]] == list(starts[i])
    # all 8 rates decoded somewhere in the fleet
    got_rates = sorted(f.result.rate_mbps
                       for r in res_m for f in r if f.result.ok)
    assert set(got_rates) == {6, 9, 12, 18, 24, 36, 48, 54}
    # noise and empty streams emit nothing, in both paths
    assert res_m[2] == [] and res_m[6] == []


def test_lanes_the_chunk_does_not_own_are_masked_host_side(
        corpus, monkeypatch):
    """Whatever the window acquisition says of a lane the chunk scan
    did not own (a pad lane, a deferred or a previous chunk's frame:
    since PR 30 it reads only the window's head there, and garbage is
    garbage), the host never looks: every such lane made to report a
    found, parity-clean 16-byte frame at 6 Mbit/s, and the fleet
    emits what it emitted."""
    import jax.numpy as jnp

    from ziria_tpu.phy.wifi.params import RATES

    streams, _starts, res_m, _st, _d, _ro, _so, _do = corpus
    real = framebatch.MultiStreamReceiver._front
    forged = []

    def front(self, st):
        outs = list(st.outs)
        own = np.asarray(outs[0])
        forged.append(int((~own).sum()))
        for at, val in ((3, True), (4, 0), (6, RATES[6].signal_bits),
                        (7, N_BYTES + 4), (8, True)):
            a = np.asarray(outs[at])
            outs[at] = jnp.asarray(np.where(own, a, val).astype(a.dtype))
        st.outs = tuple(outs)
        return real(self, st)

    monkeypatch.setattr(framebatch.MultiStreamReceiver, "_front", front)
    res, _stats = framebatch.receive_streams(streams, **GEO)
    assert sum(forged) > 0
    for i in range(S):
        _same_frames(res[i], res_m[i])


def test_straddling_frame_decoded_exactly_once_in_fleet(corpus):
    streams, starts, res_m, _st, _d, _ro, _so, _do = corpus
    assert [f.start for f in res_m[1]] == list(starts[1])
    for f in res_m[1]:
        assert f.result.ok and f.result.crc_ok
        ref = rx.receive(streams[1][f.start: f.start + FRAME_LEN],
                         check_fcs=True)
        assert _same_result(f.result, ref)


def test_dispatches_per_chunk_step_independent_of_s(corpus):
    # the tentpole number at S=8: <= 2 dispatches per CHUNK-STEP
    # (one stacked scan + at most one flattened decode), however many
    # streams ride the step — vs the per-stream chunk costs of S
    # fleets of one
    _s, _starts, _rm, st_m, d_m, _ro, st_o, d_o = corpus
    assert st_m.streams == S and st_m.chunk_steps >= 2
    assert d_m.total <= 2 * st_m.chunk_steps, dict(d_m.counts)
    assert d_m.counts["rx.stream_chunk_multi"] == st_m.chunk_steps
    assert d_m.counts["rx.stream_decode_multi"] <= st_m.chunk_steps
    # alone, every stream pays one scan per chunk of its own: strictly
    # more scans than the fleet's chunk-steps (7 non-empty streams)
    lone_chunks = sum(st.chunks for st in st_o)
    assert d_o.counts["rx.stream_chunk_multi"] == lone_chunks
    assert lone_chunks > st_m.chunk_steps
    assert st_m.frames == sum(st.frames for st in st_o)
    # the three-deep pipeline still overlaps at fleet scale
    assert d_m.gauges["rx.stream_inflight"] == st_m.max_in_flight \
        == min(3, st_m.chunk_steps)
    assert st_m.overflow_chunks == 0


def test_active_streams_gauge_and_per_stream_carry_rows(corpus):
    # the telemetry satellite: the fleet records an rx.active_streams
    # level per chunk-step and the aggregate carry depth; the per-lane
    # carry rows went with PR 41 (S formatted names and samples a
    # chunk-step that nothing read: `rx.fleet.stack`'s `active` says
    # how many lanes rode)
    _s, _starts, _rm, st_m, d_m, _ro, _so, _do = corpus
    assert d_m.gauges["rx.active_streams"] == st_m.max_active_streams
    assert 2 <= st_m.max_active_streams <= S
    assert "rx.stream_carry_depth" in d_m.gauges
    assert not [k for k in d_m.gauges
                if k.startswith("rx.stream_carry_depth[")]


def test_dispatch_pin_at_s1(corpus):
    # S=1 is the degenerate fleet: same <= 2-per-chunk-step pin, and
    # what `receive_stream` (which unwraps its lane 0) emits
    streams, _starts, _rm, _st, _d, res_o, _so, _do = corpus
    with dispatch.count_dispatches() as d1:
        res_1, st_1 = framebatch.receive_streams(streams[:1], **GEO)
    assert st_1.streams == 1 and st_1.chunk_steps >= 1
    assert d1.total <= 2 * st_1.chunk_steps, dict(d1.counts)
    _same_frames(res_1[0], res_o[0])


def test_sharded_fleet_on_suite_mesh_bit_identical(corpus):
    # the dp-mesh path: the SAME fleet with its stream axis sharded
    # over the suite's 8 virtual devices (one stream per device,
    # jax.shard_map) — identical per-device program,
    # streams independent, so results are bit-identical lane for lane
    # and the dispatch pin is unchanged
    from ziria_tpu.parallel.batch import frame_mesh

    streams, starts, res_m, _st, _d, _ro, _so, _do = corpus
    mesh = frame_mesh(8)
    with dispatch.count_dispatches() as d_sh:
        res_s, st_s = framebatch.receive_streams(
            streams, mesh=mesh, **GEO)
    assert d_sh.total <= 2 * st_s.chunk_steps, dict(d_sh.counts)
    for i in range(S):
        _same_frames(res_s[i], res_m[i])
        assert [f.start for f in res_s[i]] == list(starts[i])


@pytest.mark.parametrize("lanes", [8, 32])
def test_sharded_scan_outputs_equal_unsharded(corpus, lanes):
    """The chunk scan itself over a dp mesh of four of the suite's
    devices (`chip_smoke.py --four-chips`' two placements: 8 lanes
    forced over four, and 32 at 8 a device) against the same program
    on one device: all eleven outputs, every lane owned or not, bit
    for bit — `segs` too, which since ISSUE 44 is sliced from a
    chunk each shard pads for itself, an idle lane (`valid == 0`)
    and a noise lane among them."""
    import jax.numpy as jnp

    from ziria_tpu.parallel.batch import frame_mesh

    streams = corpus[0]
    chunks = np.zeros((lanes, CHUNK, 2), np.float32)
    valid = np.zeros((lanes,), np.int32)
    for i in range(lanes):
        st = streams[i % S]
        # the fleet's later copies start further into their stream,
        # so no two lanes of the 32 carry the same chunk
        st = st[(i // S) * 37:][:CHUNK]
        chunks[i, :len(st)] = st
        valid[i] = len(st)
    assert (valid == 0).sum() == lanes // S and (valid == CHUNK).any()
    own_lo = np.where(np.arange(lanes) < S, -192, 0).astype(np.int32)
    args = tuple(jnp.asarray(a) for a in (chunks, valid, own_lo, valid))
    # the receivers' own compiled scans (`_jit1`: at 8 lanes the
    # fixture's program, no new compile)
    one = framebatch.MultiStreamReceiver(lanes, **GEO)._jit1(*args)
    four = framebatch.MultiStreamReceiver(
        lanes, mesh=frame_mesh(4), **GEO)._jit1(*args)
    assert len(one) == len(four) == 11
    assert np.asarray(one[0]).sum() >= 10 * (lanes // S)     # owned
    assert len(four[10].sharding.device_set) == 4
    for i, (a, b) in enumerate(zip(one, four)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, i
        assert a.tobytes() == b.tobytes(), i


def test_all_noise_fleet_costs_one_dispatch_per_step(corpus):
    # the noise fast path survives the fleet: a chunk-step with zero
    # decodable lanes across ALL streams skips the decode dispatch
    # entirely (geometry shared with the fixture: zero new compiles)
    rng = np.random.default_rng(31)
    noise = [rng.normal(scale=0.05, size=(2 * CHUNK, 2))
             .astype(np.float32) for _ in range(S)]
    with dispatch.count_dispatches() as d:
        res, stats = framebatch.receive_streams(noise, **GEO)
    assert all(r == [] for r in res)
    assert stats.frames == 0 and stats.overflow_chunks == 0
    assert d.total == stats.chunk_steps
    assert d.counts.get("rx.stream_decode_multi", 0) == 0


def test_ragged_pushes_thread_carries_no_recompile(corpus):
    """The push-driven fleet surface: the same 8 streams fed in
    ragged per-stream slabs through ONE MultiStreamReceiver emit the
    same frames as the one-shot call, per-stream (tail, offset,
    emitted, watermark) carries threading across chunk-steps. The
    whole steady state runs under dispatch.no_recompile: at the
    fixture's already-compiled geometry, ragged arrival may only
    RE-DISPATCH the two compiled fleet programs."""
    streams, _starts, res_m, _st, _d, _ro, _so, _do = corpus
    with dispatch.no_recompile(rx._jit_stream_chunk_multi,
                               rx._jit_stream_decode_multi):
        msr = framebatch.MultiStreamReceiver(S, **GEO)
        got = []
        for a, b in [(0, 500), (500, 3500), (3500, 4200),
                     (4200, 7000), (7000, None)]:
            for i in range(S):
                got += msr.push(i, streams[i][a:b])
        got += msr.flush()
    per = [[] for _ in range(S)]
    for i, fr in got:
        per[i].append(fr)
    for i in range(S):
        _same_frames(per[i], res_m[i])
        c = msr.carry(i)
        assert c.offset + c.tail.shape[0] == streams[i].shape[0]
        assert c.emitted == len(res_m[i])
    # the dedupe watermark is per stream: streams that drained a
    # chunk-step carry the prune bound forward
    assert msr.carry(1).watermark > 0
    assert msr.carry(6).watermark == 0          # empty stream
    with pytest.raises(RuntimeError):
        msr.push(0, streams[0][:8])             # closed fleet
    with pytest.raises(RuntimeError):
        msr.push_many([s[:0] for s in streams])


def test_single_stream_carry_exposes_watermark(corpus):
    # the StreamCarry watermark satellite reaches the single-stream
    # receiver too (same fixture geometry: re-dispatch only)
    streams, _starts, _rm, _st, _d, _ro, _so, _do = corpus
    sr = framebatch.StreamReceiver(**GEO)
    sr.push(streams[1])
    sr.flush()
    assert sr.carry.watermark > 0
    assert sr.carry.emitted == 2


def test_bad_geometry_and_mesh_divisibility_rejected():
    with pytest.raises(ValueError):
        framebatch.MultiStreamReceiver(0, **GEO)
    with pytest.raises(ValueError):
        framebatch.MultiStreamReceiver(2, chunk_len=4096,
                                       frame_len=1000)
    with pytest.raises(ValueError):
        framebatch.MultiStreamReceiver(2, chunk_len=1024,
                                       frame_len=1024)
    from ziria_tpu.parallel.batch import frame_mesh
    with pytest.raises(ValueError):
        framebatch.MultiStreamReceiver(5, mesh=frame_mesh(8), **GEO)
    msr = framebatch.MultiStreamReceiver(2, **GEO)
    with pytest.raises(IndexError):
        msr.push(2, np.zeros((4, 2), np.float32))
    with pytest.raises(ValueError):
        msr.push_many([np.zeros((4, 2), np.float32)])
    per, stats = framebatch.receive_streams([], **GEO)
    assert per == [] and stats.streams == 0


def test_stream_many_multi_synthesizer_contract():
    # per-stream folded seeds: independent reproducible lanes, no
    # aliasing of the base seed; broadcast per-stream channel params;
    # shape errors loud
    rng = np.random.default_rng(7)
    pp = [[rng.integers(0, 256, N_BYTES).astype(np.uint8)],
          [rng.integers(0, 256, N_BYTES).astype(np.uint8)]]
    streams, starts = link.stream_many_multi(
        pp, [[6], [54]], snr_db=30.0, cfo=[1e-4, -1e-4],
        delay=[60, 90], seed=3, add_fcs=True, tail=FRAME_LEN)
    assert len(streams) == len(starts) == 2
    assert starts[0][0] == 60 and starts[1][0] == 90
    # deterministic: the same call reproduces bit-identical streams
    streams2, _ = link.stream_many_multi(
        pp, [[6], [54]], snr_db=30.0, cfo=[1e-4, -1e-4],
        delay=[60, 90], seed=3, add_fcs=True, tail=FRAME_LEN)
    assert all(np.array_equal(a, b)
               for a, b in zip(streams, streams2))
    # stream i's draws differ from the base-seed single-stream call
    solo, _ = link.stream_many(pp[0], [6], snr_db=30.0, cfo=1e-4,
                               delay=60, seed=3, add_fcs=True,
                               tail=FRAME_LEN)
    assert not np.array_equal(streams[0], solo)
    with pytest.raises(ValueError):
        link.stream_many_multi(pp, [[6]])
    with pytest.raises(ValueError):
        link.stream_many_multi(pp, [[6], [54]], gaps=[[1]])


# ---- the decode walks the slots that hold a frame (PR 46) ----------
#
# ONE compiled toy program (`rx.stream_decode_graph` with its trip
# counts as a third output) at 2 x 72 slots of the suite's 8-symbol
# bucket — two ACS tiles, so its loop turns — and one
# reference: `decode_data_mixed` + `crc_psdu_many_graph` over the
# unpacked batch with EVERY slot live, run once. Lane values do not
# depend on the batch (the pinned `receive_many` contract), so a case
# is data: which slots of that one table keep their `nbits`.
#
# Since PR 53 a tile's ACS and traceback stop where the tile's longest
# frame stops (`rx.decode_bound`), so a live slot equals the reference
# BEFORE ITS OWN `nbits` (the PSDU, the tail, the pad of its last
# symbol) and in its FCS flag; from there to its tile's bound it holds
# a decode of erasures, and past the bound the descrambled zeros.

WS, WK, WBUCKET = 2, 72, 8
G = rx.DECODE_GROUP
#: live slots a stream (stream 0, stream 1): the counts ISSUE 46 names
#: around a group and a tile, and lanes spread unevenly over streams
WALK_CASES = [(0, 0), (0, 1), (G - 1, 0), (G // 2, G - G // 2), (G, 1),
              (2 * G, 0), (2 * G, 1), (WK, 0), (0, WK), (WK, 128 - WK),
              (WK, 129 - WK), (WK, WK - 1), (WK, WK),
              # PR 53, by name (`_live`): two tiles whose longest lanes
              # differ, and one live lane that ends ON its bound
              "tiles-differ", "one-lane-on-its-bound"]


def _live(case, nbits):
    """The (WS, WK) mask of a case's live slots: counts a stream (the
    host's tables: a stream's live lanes first), or a pattern by name
    (the program packs whatever slots state ``nbits > 0``)."""
    if case == "tiles-differ":
        # the first tile mixed, the second short lanes alone
        flat = np.arange(WS * WK).reshape(WS, WK)
        return (flat < 128) | (nbits <= 7 * 64)
    if case == "one-lane-on-its-bound":
        on = (nbits % 64 == 0) & (nbits < nbits.max())
        return np.arange(WS * WK).reshape(WS, WK) == np.flatnonzero(on)[0]
    return np.arange(WK)[None, :] < np.array(case)[:, None]


@pytest.fixture(scope="module")
def walk_toy():
    import jax
    from ziria_tpu.phy.wifi.params import RATES
    rng = np.random.default_rng(46)
    need = rx.FRAME_DATA_START + 80 * WBUCKET
    segs = rng.standard_normal((WS, WK, need, 2)).astype(np.float32)
    rows = rng.integers(0, WK, (WS, WK)).astype(np.int32)
    ridx = rng.integers(0, 8, (WS, WK)).astype(np.int32)   # mixed rates
    dbps = np.array([RATES[m].n_dbps for m in rx.RATE_MBPS_ORDER])
    nbits = (rng.integers(1, WBUCKET + 1, (WS, WK))
             * dbps[ridx]).astype(np.int32)
    npsdu = ((nbits - 22) // 8 * 8).astype(np.int32)
    # traced and compiled ONCE: the cases below run the executable,
    # the last test reads the trace
    tables = (segs, rows, ridx, nbits, npsdu)
    traced = jax.jit(
        lambda *a: rx.stream_decode_graph(*a, WBUCKET)).trace(*tables)
    walk = traced.lower().compile()

    def ref(frames, r, b, p):
        # `decode_data_mixed`, its three stages apart: the decoded row
        # is also put through the back with every bit past its first
        # seven (the seed's) ZERO: the descrambled zeros
        front, trellis, back = rx._mixed_stages(
            WBUCKET, None, None, None, None, False, False)
        raw = trellis(front(frames, r, b), r, b)
        clear = back(raw)
        return (clear, rx.crc_psdu_many_graph(clear, p),
                back(raw.at[:, 7:].set(0)))

    sel = np.stack([segs[i][rows[i]] for i in range(WS)])
    clear, crc, zeros = jax.jit(ref)(
        sel.reshape(WS * WK, need, 2), ridx.reshape(-1),
        nbits.reshape(-1), npsdu.reshape(-1))
    return (walk, tables, np.asarray(clear).reshape(WS, WK, -1),
            np.asarray(crc).reshape(WS, WK), traced.jaxpr,
            np.asarray(zeros).reshape(WS, WK, -1))


@pytest.mark.parametrize("counts", WALK_CASES,
                         ids=[c if isinstance(c, str) else
                              f"live{c[0] + c[1]}of{WS * WK}-{c[0]}+{c[1]}"
                              for c in WALK_CASES])
def test_decode_walks_the_live_slots_bit_identical(walk_toy, counts):
    (walk, (segs, rows, ridx, nbits, npsdu), want_clear, want_crc, _,
     zeros) = walk_toy
    # the host's tables: a stream's live lanes first, `nbits` 0 after
    live = _live(counts, nbits)
    table = np.where(live, nbits, 0)
    clear, crc, trips = walk(segs, rows, ridx, table, npsdu)
    clear, crc = np.asarray(clear), np.asarray(crc)
    assert clear.shape == want_clear.shape and crc.shape == (WS, WK)
    # every live slot: the whole-trellis mixed decode's own bits before
    # its `nbits`, and its FCS flag
    real = np.arange(clear.shape[-1]) < table[..., None]
    assert np.array_equal(clear[real], want_clear[real])
    assert np.array_equal(crc[live], want_crc[live])
    # a slot that holds no frame is not decoded: zero, both outputs
    assert not clear[~live].any() and not crc[~live].any()
    # the slots the program's trips ran are the python rule's, and so
    # are the trellis steps each tile's kernels ran: the bound of the
    # tile's longest LIVE lane, in packed stream order
    n = int(live.sum())
    fronted, decoded = rx.decode_walk(n, WS * WK)
    assert tuple(int(t) for t in trips[:2]) == (fronted, decoded)
    steps = np.asarray(trips[2])
    packed = np.zeros(steps.size * 128, np.int32)
    packed[:n] = table[live]
    want_steps = rx.decode_bound(packed.reshape(-1, 128).max(axis=1),
                                 clear.shape[-1])[1]
    want_steps[decoded // 128:] = 0          # a tile no trip went to
    assert steps.tolist() == want_steps.tolist()
    if counts == "tiles-differ":
        assert steps[0] == clear.shape[-1] and 0 < steps[1] <= 7 * 64
    if counts == "one-lane-on-its-bound":
        assert steps.tolist() == [table.max(), 0]
    assert rx.decode_steps(table.reshape(1, -1), WBUCKET) == int(
        (np.minimum(np.arange(128, decoded + 1, 128), WS * WK)
         - np.arange(0, decoded, 128)) @ steps[:decoded // 128])
    # (b) a row at or past its tile's bound: the descrambled zeros (no
    # block the kernels left unwritten reaches an output), the same
    # bytes in a second run
    place = np.cumsum(live.reshape(-1)) - 1
    bound = steps[place // 128].reshape(WS, WK)
    past = live[..., None] & (np.arange(clear.shape[-1]) >= bound[..., None])
    assert np.array_equal(clear[past], zeros[past])
    again = np.asarray(walk(segs, rows, ridx, table, npsdu)[0])
    assert np.array_equal(again, clear)
    # the rule, said again: 128-lane tiles up to the last live slot,
    # each fronted whole but the last, which fronts one group where
    # one holds what is left
    tiles = max(1, -(-n // 128))
    left = max(n, 1) - 128 * (tiles - 1)
    assert decoded == 128 * tiles
    assert fronted == 128 * (tiles - 1) + (G if left <= G else 128) >= n


@pytest.mark.parametrize("n_slots, n_live, want", [
    (256, 78, (128, 128)),        # the mix cell's step: one tile, whole
    (256, 32, (32, 128)), (256, 129, (160, 256)), (256, 161, (256, 256)),
    (128, 24, (32, 128)), (128, 95, (128, 128)),     # maxpsdu, dense54
    (64, 30, (32, 64)), (64, 33, (64, 64)),          # the MTU cells
    (8, 3, (32, 32)),             # a batch under a group is padded to one
    (96, 40, (96, 96)), (144, 0, (32, 128))])
def test_decode_walk_rule(n_slots, n_live, want):
    assert rx.decode_walk(n_live, n_slots) == want
    # the same rule over an array of counts, a device each (the
    # host's account under a mesh)
    both = rx.decode_walk(np.array([n_live, n_live]), n_slots)
    assert [w.tolist() for w in both] == [[v, v] for v in want]


def test_decode_walk_loops_to_a_bound_that_is_data(walk_toy):
    """At two ACS tiles the traced program holds ONE loop of its own,
    over the tiles that hold a live slot, and its bound is data: a
    `fori_loop` to a static bound traces to a `scan`, one to a traced
    bound to a `while`; the front inside it is a `cond` (one group, or
    the whole tile). The Pallas kernels are calls, not loops, here."""
    import jax

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            if e.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from eqns(sub)

    loops = [e for e in eqns(walk_toy[4].jaxpr)
             if e.primitive.name == "while"]
    assert len(loops) == 1
    body = loops[0].params["body_jaxpr"].jaxpr
    assert [e.primitive.name for e in body.eqns].count("cond") == 1
    assert sum(e.primitive.name == "pallas_call" for e in eqns(body)) >= 2
