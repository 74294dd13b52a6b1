"""Device-resident streaming receiver (backend/framebatch.receive_stream
+ rx.stream_chunk_graph + ops/sync.locate_frames): a long multi-frame
I/Q stream decoded in O(chunks) device dispatches (<= 2 per chunk),
with every emitted frame bit-identical — RxResult field for field,
FCS status included — to slicing `stream[start : start + frame_len]`
out and calling per-capture `rx.receive` on it, and every emitted
start hitting the synthesizer's ground truth.

Budget discipline (the tier-1 870 s cutoff is real): ONE module
fixture pays the streaming geometry compiles — chunk 4096, window
1024, K=8 candidate lanes, 8-symbol decode bucket, the same
1024-sample capture bucket / 8-symbol geometry the batched-acquire
and mixed-dispatch suites share — and every test is a cheap
re-dispatch. The edge-case streams (straddle, minimum gap, overflow,
all-noise) are all constructed AT the fixture geometry so no test
compiles a second chunk graph.
"""

import numpy as np
import pytest

from ziria_tpu.backend import framebatch
from ziria_tpu.phy import link
from ziria_tpu.phy.wifi import rx
from ziria_tpu.phy.wifi.params import RATES
from ziria_tpu.utils import dispatch

N_BYTES = 12     # +4 FCS = the suite's standard 16-byte on-air PSDU:
                 # every frame fits the 1024-sample window (6 Mbps =
                 # 960 samples) and the decode bucket stays 8 symbols
CHUNK, FRAME_LEN, K = 4096, 1024, 8
GEO = dict(chunk_len=CHUNK, frame_len=FRAME_LEN, max_frames_per_chunk=K,
           check_fcs=True)


def _same_result(a, b) -> bool:
    return (a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)


def _oracle(stream, start):
    """THE identity contract: per-capture receive over the stream
    sliced at the (true/reported) frame start."""
    return rx.receive(stream[start: start + FRAME_LEN], check_fcs=True)


@pytest.fixture(scope="module")
def corpus():
    """All 8 rates on one continuous stream — random gaps, CFO,
    initial delay, AWGN, FCS appended — plus one streaming and one
    per-capture-mode pass with dispatch counters."""
    rng = np.random.default_rng(20260804)
    mbps = sorted(RATES)
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in mbps]
    stream, starts = link.stream_many(
        psdus, mbps, snr_db=30.0, cfo=1e-4, delay=60, seed=5,
        add_fcs=True, tail=FRAME_LEN)
    with dispatch.count_dispatches() as d_st:
        got_s, st_s = framebatch.receive_stream(stream, streaming=True,
                                                **GEO)
    with dispatch.count_dispatches() as d_pc:
        got_p, st_p = framebatch.receive_stream(stream, streaming=False,
                                                **GEO)
    return stream, starts, got_s, st_s, d_st, got_p, st_p, d_pc


def test_all_8_rates_bit_identical_to_sliced_receive(corpus):
    # the acceptance contract: reported starts == the synthesizer's
    # TRUE frame starts, and every RxResult (crc_ok included) equals
    # per-capture receive over the stream sliced at that start
    stream, starts, got_s, _st, _d, _gp, _sp, _dp = corpus
    assert [f.start for f in got_s] == list(starts)
    for f in got_s:
        ref = _oracle(stream, f.start)
        assert f.result.ok and ref.ok and f.result.crc_ok
        assert _same_result(f.result, ref)
    assert sorted(f.result.rate_mbps for f in got_s) == sorted(RATES)


def test_percapture_mode_is_the_same_oracle(corpus):
    # the opt-out path (--no-streaming-rx) stays available and stays
    # exact: same detected windows, per-capture receive per frame
    _s, _starts, got_s, _st, _d, got_p, _sp, _dp = corpus
    assert [f.start for f in got_p] == [f.start for f in got_s]
    for a, b in zip(got_p, got_s):
        assert _same_result(a.result, b.result)


def test_o_chunks_dispatches_vs_o_frames(corpus):
    # the tentpole number: <= 2 dispatches per CHUNK (scan + decode)
    # however many frames ride the stream, vs >= 3 per FRAME (+ the
    # per-chunk scan) for the per-capture path
    _s, starts, _gs, st_s, d_st, _gp, st_p, d_pc = corpus
    n = len(starts)
    assert st_s.chunks >= 2                   # the stream really chunks
    assert d_st.total <= 2 * st_s.chunks, dict(d_st.counts)
    assert d_st.counts["rx.stream_chunk_multi"] == st_s.chunks
    assert d_st.counts["rx.stream_decode_multi"] <= st_s.chunks
    assert d_pc.total >= 3 * n + 1, dict(d_pc.counts)
    # the pipeline really overlapped: chunks i+1 and i+2 were in
    # flight before chunk i drained (the utils/dispatch gauge)
    assert d_st.gauges["rx.stream_inflight"] == 3
    assert st_s.max_in_flight == 3
    assert st_s.overflow_chunks == 0


def test_boundary_straddling_frame_decoded_exactly_once(corpus):
    """A frame whose samples cross the chunk boundary is owned by
    exactly one chunk (the next one, which contains it fully inside
    the overlap) — decoded once, bit-identically."""
    stream0, _starts, _gs, _st, _d, _gp, _sp, _dp = corpus
    rng = np.random.default_rng(9)
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in range(2)]
    # 54 Mbps frames are 480 samples on air; gap 3260 puts frame 1 at
    # 60 + 480 + 3260 = 3800: inside chunk 0's overlap region
    # [3072, 4096) and spanning the 4096 boundary into chunk 1
    stream, starts = link.stream_many(
        psdus, [54, 54], gaps=[3260], snr_db=30.0, cfo=1e-4, delay=60,
        seed=6, add_fcs=True, tail=FRAME_LEN)
    assert starts[1] == 3800 and starts[1] + 480 > CHUNK
    got, stats = framebatch.receive_stream(stream, **GEO)
    assert [f.start for f in got] == list(starts)     # exactly once
    for f in got:
        assert f.result.ok and f.result.crc_ok
        assert _same_result(f.result, _oracle(stream, f.start))
    assert stats.chunks == 2


def test_back_to_back_frames_at_minimum_gap(corpus):
    """Two longest frames nose to tail (10-sample gap): the dead-zone
    suppression must not eat the second frame, and each window must
    time onto its OWN preamble."""
    rng = np.random.default_rng(10)
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in range(2)]
    stream, starts = link.stream_many(
        psdus, [6, 6], gaps=[10], snr_db=30.0, cfo=1e-4, delay=60,
        seed=7, add_fcs=True, tail=FRAME_LEN)
    assert starts[1] - starts[0] == 970       # 960-sample frame + 10
    got, _stats = framebatch.receive_stream(stream, **GEO)
    assert [f.start for f in got] == list(starts)
    for f in got:
        assert f.result.ok and f.result.crc_ok
        assert _same_result(f.result, _oracle(stream, f.start))


def test_overflow_reported_not_silently_dropped(corpus):
    """More than K eligible plateaus in one chunk's owned region:
    the K extracted lanes emit, the surplus raises the chunk's
    overflow flag (StreamStats.overflow_chunks) — never a silent
    drop. Built from bare 320-sample preambles at the FIXTURE
    geometry so no new graph compiles."""
    stream0, starts0, _gs, _st, _d, _gp, _sp, _dp = corpus
    pre = stream0[int(starts0[0]): int(starts0[0]) + 320]   # STS+LTS
    rng = np.random.default_rng(11)
    stream = rng.normal(scale=0.01, size=(CHUNK + 512, 2)) \
        .astype(np.float32)
    for i in range(9):                        # 9 plateaus, K = 8
        stream[i * 360: i * 360 + 320] += pre
    got, stats = framebatch.receive_stream(stream, **GEO)
    assert stats.overflow_chunks >= 1
    assert len(got) <= K
    # the K extracted lanes still honor the identity contract
    for f in got:
        assert _same_result(f.result, _oracle(stream, f.start))


def test_failure_lanes_bit_identical(corpus):
    """Failure lanes on the STREAM honor the identity contract too:
    a frame whose SIGNAL parity is corrupted (detected, then
    classified ACQ_FAIL) and a frame the stream ends in the middle of
    (ACQ_TRUNCATED through the final chunk's traced own-bucket cap)
    both emit the exact fail RxResult per-capture receive returns."""
    import jax.numpy as jnp

    from ziria_tpu.ops import coding, interleave, modulate, ofdm
    from ziria_tpu.phy.wifi import tx

    rng = np.random.default_rng(13)
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in range(3)]
    # no noise/CFO so the SIGNAL patch below is sample-exact
    stream, starts = link.stream_many(
        psdus, [24, 24, 24], gaps=[400, 400], snr_db=np.inf, cfo=0.0,
        delay=60, seed=14, add_fcs=True, tail=FRAME_LEN)
    # frame 1's SIGNAL re-encoded with its even-parity bit flipped
    # (the test_rx_batched_acquire recipe), patched over the stream
    sig_bits = np.array(tx.signal_field_bits(RATES[24], N_BYTES + 4))
    sig_bits[17] ^= 1
    coded = coding.conv_encode(jnp.asarray(sig_bits))
    syms = modulate.modulate(interleave.interleave(coded, 48, 1), 1)
    bins = ofdm.map_subcarriers(syms[None, :, :], symbol_index0=0)
    s1 = int(starts[1])
    stream[s1 + 320: s1 + 400] = np.asarray(ofdm.ofdm_modulate(bins)[0])
    # ...and the stream ends 500 samples into frame 2 (mid-DATA)
    stream = stream[: int(starts[2]) + 500]

    got, _stats = framebatch.receive_stream(stream, **GEO)
    assert [f.start for f in got] == list(starts)
    for f in got:
        assert _same_result(f.result, _oracle(stream, f.start))
    assert got[0].result.ok and got[0].result.crc_ok
    assert not got[1].result.ok and got[1].result.rate_mbps == 0
    assert not got[2].result.ok and got[2].result.rate_mbps == 24 \
        and got[2].result.length_bytes == N_BYTES + 4      # truncated


def test_stream_head_truncated_preamble_not_silently_dropped(corpus):
    """A stream that begins mid-preamble: the LTS alignment lands
    BELOW 0, which on any later chunk means 'previous chunk's frame'
    — but on the stream's FIRST chunk there is no previous chunk, so
    the start clamps to 0 (exactly per-capture locate_frame's
    max(lts1-192, 0) clamp) and a result is emitted, identical to
    receive over the stream head. Never a silent drop."""
    rng = np.random.default_rng(15)
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in range(2)]
    full, starts = link.stream_many(
        psdus, [24, 54], gaps=[400], snr_db=30.0, cfo=1e-4, delay=0,
        seed=16, add_fcs=True, tail=FRAME_LEN)
    stream = full[40:]                 # first 40 preamble samples lost
    got, _stats = framebatch.receive_stream(stream, **GEO)
    # the head-truncated frame emits at the clamped start 0; frame 1
    # is intact at its shifted true start
    assert [f.start for f in got] == [0, int(starts[1]) - 40]
    for f in got:
        assert _same_result(f.result, _oracle(stream, f.start))
    assert got[1].result.ok and got[1].result.crc_ok


def test_deferred_overlap_plateau_is_not_overflow(corpus):
    """K plateaus owned by the chunk plus one more in the DEFERRED
    overlap region: the leftover is the next chunk's frame, not a
    drop, so the overflow flag must stay clear (the overflow scan is
    capped at the ownership bound) — and the deferred plateau still
    gets its own candidate in the next chunk."""
    stream0, starts0, _gs, _st, _d, _gp, _sp, _dp = corpus
    pre = stream0[int(starts0[0]): int(starts0[0]) + 320]
    rng = np.random.default_rng(16)
    stream = rng.normal(scale=0.01, size=(CHUNK + 2048, 2)) \
        .astype(np.float32)
    for i in range(8):                        # exactly K owned
        stream[i * 360: i * 360 + 320] += pre
    # deferred plateau, past the stride AND past the overflow scan's
    # 224-sample alignment-slack sliver (which stays conservative:
    # a surplus frame THIS chunk owns must always flag)
    stream[3400: 3720] += pre
    got, stats = framebatch.receive_stream(stream, **GEO)
    assert stats.overflow_chunks == 0
    assert any(f.start >= 3072 for f in got)  # next chunk took it
    for f in got:
        assert _same_result(f.result, _oracle(stream, f.start))


def test_all_noise_chunks_cost_one_dispatch_each(corpus):
    rng = np.random.default_rng(12)
    stream = rng.normal(scale=0.05, size=(2 * CHUNK, 2)) \
        .astype(np.float32)
    with dispatch.count_dispatches() as d:
        got, stats = framebatch.receive_stream(stream, **GEO)
    assert got == []
    assert stats.frames == 0 and stats.overflow_chunks == 0
    # no decodable lane -> the decode dispatch never fires
    assert d.total == stats.chunks
    assert d.counts.get("rx.stream_decode_multi", 0) == 0


def test_push_flush_carry_threads_across_slabs(corpus):
    """The push-driven surface: the same stream fed in ragged slabs
    through StreamReceiver emits the same frames as the one-shot
    call, with the (tail, offset, emitted) carry threading across
    chunk boundaries. The whole steady state runs under
    dispatch.no_recompile — the runtime twin of the jaxlint R1
    cache-key rule: at the fixture's already-compiled geometry, ragged
    pushes may only RE-DISPATCH the two compiled chunk programs, never
    mint a fresh compile-cache entry."""
    stream, starts, got_s, _st, _d, _gp, _sp, _dp = corpus
    with dispatch.no_recompile(rx._jit_stream_chunk_multi,
                               rx._jit_stream_decode_multi):
        sr = framebatch.StreamReceiver(**GEO)
        got = []
        cuts = [0, 777, 3000, 4100, 9001, stream.shape[0]]
        for a, b in zip(cuts, cuts[1:]):
            got += sr.push(stream[a:b])
        assert sr.carry.offset + sr.carry.tail.shape[0] \
            == stream.shape[0]
        got += sr.flush()
    assert sr.carry.emitted == len(got)
    assert [f.start for f in got] == [f.start for f in got_s]
    for a, b in zip(got, got_s):
        assert _same_result(a.result, b.result)
    with pytest.raises(RuntimeError):
        sr.push(stream[:8])                   # closed stream


def test_stream_receiver_is_a_fleet_of_one(corpus):
    """A lone stream is lane 0 of a one-lane fleet: `StreamReceiver`
    holds ONE `MultiStreamReceiver(n_streams=1)` and no chunk
    lifecycle, compiled program or pending state of its own — what it
    dispatches are the fleet's two sites, and `rx` has no other
    streaming programs to dispatch."""
    stream, _starts, got_s, _st, _d, _gp, _sp, _dp = corpus
    with dispatch.count_dispatches() as d:
        sr = framebatch.StreamReceiver(**GEO)
        got = sr.push(stream) + sr.flush()
    assert set(d.counts) == {"rx.stream_chunk_multi",
                             "rx.stream_decode_multi"}
    assert d.counts["rx.stream_chunk_multi"] == sr.stats.chunks \
        == sr.fleet.stats.chunk_steps
    assert type(sr.fleet) is framebatch.MultiStreamReceiver
    assert sr.fleet.s == 1 and sr.fleet.mesh is None
    assert [f.start for f in got] == [f.start for f in got_s]
    for a, b in zip(got, got_s):
        assert _same_result(a.result, b.result)
    for gone in ("chunk", "decode"):     # the fleet's two are `_multi`
        assert not hasattr(rx, "_jit_stream_" + gone)
    for name in ("_launch", "_scan_dispatch", "_rescan", "_front",
                 "_drain", "_settle", "_decode_oracle", "_eager_chunk",
                 "_mark_degraded", "_jit1", "_pending", "_health"):
        assert hasattr(sr.fleet, name) and not hasattr(sr, name), name
    for name in ("_note_emitted", "_runtime_state"):
        assert not hasattr(sr, name), name


def test_stream_bucket_graph_matches_host_rule():
    # the traced per-lane detector cap must be THE _stream_bucket rule
    # (the acquire_many limit contract hangs off it)
    import jax.numpy as jnp
    nv = np.arange(1, FRAME_LEN + 1, dtype=np.int32)
    got = np.asarray(rx._stream_bucket_graph(jnp.asarray(nv), FRAME_LEN))
    want = np.asarray([rx._stream_bucket(int(v)) for v in nv])
    np.testing.assert_array_equal(got, want)


def test_locate_frames_k1_matches_single_frame_oracle(corpus):
    # the K=1 oracle relationship the sync docstrings name: one frame
    # per capture -> locate_frames' first lane finds the exact start
    # locate_frame's global peak-pick reports
    stream, starts, _gs, _st, _d, _gp, _sp, _dp = corpus
    from ziria_tpu.ops import sync
    cap = stream[int(starts[0]) - 40: int(starts[0]) - 40 + FRAME_LEN]
    d1, s1, _e = sync.locate_frame(cap)
    fk, sk, ovf = sync.locate_frames(cap, 1)
    assert bool(d1) and bool(np.asarray(fk)[0])
    assert int(np.asarray(sk)[0]) == int(s1) == 40
    assert not bool(ovf)


def test_streaming_rx_env_knob(monkeypatch):
    # the CLI's scoped-env pattern: default ON, ZIRIA_STREAMING_RX=0
    # forces the per-capture oracle, an explicit argument wins
    monkeypatch.delenv("ZIRIA_STREAMING_RX", raising=False)
    assert framebatch.streaming_rx_enabled(None)
    monkeypatch.setenv("ZIRIA_STREAMING_RX", "0")
    assert not framebatch.streaming_rx_enabled(None)
    assert framebatch.streaming_rx_enabled(True)
    monkeypatch.setenv("ZIRIA_STREAMING_RX", "1")
    assert framebatch.streaming_rx_enabled(None)
    assert not framebatch.streaming_rx_enabled(False)


def test_bad_geometry_rejected():
    with pytest.raises(ValueError):
        framebatch.StreamReceiver(chunk_len=4096, frame_len=1000)
    with pytest.raises(ValueError):
        framebatch.StreamReceiver(chunk_len=1024, frame_len=1024)
    # zero frames + finite SNR: no frame power to reference — an
    # explicit error, never a silent all-zero "noise" stream
    with pytest.raises(ValueError):
        link.stream_many([], [], snr_db=10.0)
    stream, starts = link.stream_many([], [], tail=600)
    assert stream.shape == (600, 2) and starts.size == 0


# ------------------------------ windows that hold more than one frame
#
# PR 22's first chip run: at the MTU capture bucket (65 536 samples) a
# window holds up to a dozen short high-rate frames, and the
# per-window acquisition decoded whichever frame's LTS correlated
# best — every geometry above holds ONE frame per window, so nothing
# here had ever seen it. The window's frame is the one it starts at.


def test_locate_frame_takes_the_first_frame_not_the_loudest():
    import jax.numpy as jnp

    from ziria_tpu.ops import sync

    rng = np.random.default_rng(7)
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in range(2)]
    stream, starts = link.stream_many(
        psdus, [54, 6], snr_db=30.0, cfo=1e-4, delay=60, seed=9,
        add_fcs=True, tail=FRAME_LEN)
    cap = np.array(stream[starts[0]: starts[0] + 4096], copy=True)
    second = starts[1] - starts[0]
    assert second + 400 < 4096          # both preambles in the capture
    cap[second:] *= 2.0                 # ...and the second one louder
    found, start, _eps = sync.locate_frame(jnp.asarray(cap))
    assert bool(found) and int(start) == 0


def test_chunk_scan_reads_every_frame_of_a_many_frame_window(corpus):
    """The chunk scan at a window four times the frame: each owned
    window's acquisition locks to ITS frame (offset 0 in the window)
    and reads that frame's RATE and LENGTH — the eight rates, in the
    order sent. XLA only (SIGNAL is decoded in the scan), no Pallas."""
    import jax
    import jax.numpy as jnp

    stream, starts = corpus[0], corpus[1]
    win, chunk = 4 * FRAME_LEN, 16 * FRAME_LEN
    assert stream.shape[0] <= chunk and len(starts) <= 8
    scan = jax.jit(lambda c, v: rx.stream_chunk_graph(
        c, v, jnp.int32(-192), v, 8, win, 8)[:10])
    padded = np.zeros((chunk, 2), np.float32)
    padded[:stream.shape[0]] = stream
    own, got, overflow, found, fstart, _eps, rb, ln, pk, _nv = (
        np.asarray(o) for o in scan(jnp.asarray(padded),
                                    jnp.int32(stream.shape[0])))
    assert not overflow and own.sum() == len(starts)
    assert list(got[own]) == list(starts)
    assert found[own].all() and pk[own].all()
    assert list(fstart[own]) == [0] * len(starts)
    # the rate word: RATE bits, the lane's CFO estimate above them
    assert [int(b) for b in rx.unpack_rate_word(rb)[0][own]] == \
        [RATES[m].signal_bits for m in sorted(RATES)]
    assert set(ln[own]) == {N_BYTES + 4}
