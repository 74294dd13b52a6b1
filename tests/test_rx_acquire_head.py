"""The chunk scan's per-window acquisition reads the window's HEAD
(`rx._acquire_head`), not all `win_len` samples of it (ISSUE 30): the
chunk-level `sync.locate_frames` found the frame already, the window
is cut AT that start, and `acquire_frame_graph`'s first-crossing
`argmax` and local peak mask discard everything further in.

The identity contract here: on the CPU, `stream_chunk_graph`'s eleven
outputs are bit-identical, on every lane the chunk owns, to an oracle
built in this file from `sync.locate_frames` + the window cut + the
UNCHANGED `acquire_frame_graph` vmapped over each WHOLE window. Two
geometries (a toy one whose window is twice the head, and the served
MTU one), K=16 so a dozen frames fit one chunk; each geometry compiles
its two scans once and every case re-dispatches them.

Since ISSUE 44 the scan under test cuts no window at all: the head and
the data region are sliced from the padded chunk. The oracle still
writes the S x K x `win_len` window array out, and a second set of
cases at the toy window runs the relation the served geometries have
(a symbol bucket whose segment is LONGER than the window: 82 320 >
65 536, 164 240 > 131 072), where a gather that ran on past the
window's bound would read the chunk's next frame.
"""

import inspect
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ziria_tpu.ops import ofdm, sync
from ziria_tpu.phy import link
from ziria_tpu.phy.wifi import rx
from ziria_tpu.phy.wifi.params import RATES

K, BUCKET = 16, 8
GEOS = {"toy": (16384, 2048), "mtu": (131072, 65536)}
OUTPUTS = ("own", "starts", "overflow", "found", "fstart", "eps",
           "rate_bits", "length", "parity_ok", "n_valid", "segs")


def _whole_window_scan(chunk, chunk_valid, own_lo, own_hi, win_len,
                       bucket=BUCKET):
    """The oracle: `stream_chunk_graph`'s five steps written out, with
    the acquisition over every whole window (the parent's form)."""
    found, starts, overflow = sync.locate_frames(
        chunk, K, limit=chunk_valid, overflow_limit=own_hi + 224)
    own = found & (starts >= own_lo) & (starts < own_hi)
    starts = jnp.where(own, jnp.maximum(starts, 0), starts)
    safe = jnp.clip(starts, 0, chunk.shape[0])
    chunk_pad = jnp.pad(chunk, ((0, win_len), (0, 0)))
    wins = jax.vmap(lambda s: jax.lax.dynamic_slice(
        chunk_pad, (s, jnp.int32(0)), (win_len, 2)))(safe)
    nv = jnp.clip(chunk_valid - safe, 0, win_len).astype(jnp.int32)
    lim = rx._stream_bucket_graph(nv, win_len)
    f2, fstart, eps, rb, ln, pk = jax.vmap(rx.acquire_frame_graph)(
        wins, nv, lim)
    need_b = rx.FRAME_DATA_START + 80 * bucket
    wins_pad = jnp.pad(wins, ((0, 0), (0, need_b), (0, 0)))
    segs = jax.vmap(lambda xi, s, e, a: rx.gather_segment_graph(
        xi, s, e, a, bucket))(wins_pad, fstart, eps, nv - fstart)
    return (own, starts, overflow, f2, fstart, eps,
            rx.pack_rate_word(rb, eps), ln, pk, nv, segs)


@lru_cache(maxsize=None)
def _scans(win_len: int, bucket: int = BUCKET):
    """(the scan under test, the whole-window oracle), jitted once a
    geometry (chunk length retraces by shape)."""
    return (
        jax.jit(lambda c, v, lo, hi: rx.stream_chunk_graph(
            c, v, lo, hi, K, win_len, bucket)),
        jax.jit(lambda c, v, lo, hi: _whole_window_scan(
            c, v, lo, hi, win_len, bucket)))


def _psdus(rng, n, n_bytes=12):
    return [rng.integers(0, 256, n_bytes).astype(np.uint8)
            for _ in range(n)]


def _eight_rates(snr_db, cfo, seed):
    rng = np.random.default_rng(seed)
    stream, starts = link.stream_many(
        _psdus(rng, 8), sorted(RATES), snr_db=snr_db, cfo=cfo, delay=60,
        seed=seed, add_fcs=True, tail=2048)
    return stream, len(starts)


def _latest_crossing_stream():
    """A window whose FIRST threshold crossing sits at the latest
    position the alignment rule admits, start + 224: an LTS pair at P
    (so the start is P - 192) under a louder 16-periodic tone that
    brings the STS metric over the threshold at P + 32 exactly — the
    peak then lies on the alignment window's lowest position."""
    p = 1000
    x = np.zeros((4096, 2), np.float32)
    lts = np.asarray(ofdm.lts_time_symbol())
    x[p: p + 64] += lts
    x[p + 64: p + 128] += lts
    tone = np.tile(np.asarray(ofdm.preamble())[:16], (20, 1))
    x[p + 40: p + 40 + 320] += 2.0 * tone
    win = jnp.asarray(x[p - 192:])
    detected, crossing = sync.detect_packet(win)
    assert bool(detected) and int(crossing) == 224
    assert int(sync.locate_frame(win)[1]) == 0
    return x


def _late_peak_stream():
    """A window whose acquisition reads further in than a clean
    frame's does (320 for the timing, 400 for SIGNAL): a 300-sample
    STS run, so the chunk's crossing lies 150 BELOW the start it
    aligns, and a second, louder LTS pair 191 after the first — past
    the chunk's alignment window, inside the window's own, whose pick
    (start 191) then reads up to 591. What a shorter head would cut."""
    r0, run = 1000, 300
    x = np.zeros((4096, 2), np.float32)
    lts = np.asarray(ofdm.lts_time_symbol())
    x[r0: r0 + run] = np.tile(np.asarray(ofdm.preamble())[:16],
                              (run // 16 + 1, 1))[:run]
    l0 = r0 + run + 32
    x[l0 - 32: l0] = lts[32:]
    for at, amp in ((l0, 1.0), (l0 + 191, 2.0)):
        x[at: at + 64] += amp * lts
        x[at + 64: at + 128] += amp * lts
    return x


def _case(name: str):
    """(stream, own_lo, owned lanes expected, all found?) of a case;
    every case is a stream's first AND final chunk, so it owns all it
    locates."""
    rng = np.random.default_rng(30)
    if name == "eight_rates":
        stream, n = _eight_rates(np.inf, 0.0, 31)
        return stream, -192, n, True
    if name == "cfo_awgn":
        stream, n = _eight_rates(30.0, 1e-4, 32)
        return stream, -192, n, True
    if name == "dozen_back_to_back":
        # 14 bytes + FCS: one symbol at 54/48, two at 36/24, so a
        # 6 000-sample run holds twelve frames and an MTU window all
        # of them (PR 22: a global peak-pick decoded the wrong one)
        rates = [54, 48, 36, 24] * 3
        stream, starts = link.stream_many(
            _psdus(rng, 12, 14), rates, gaps=[10] * 11, snr_db=30.0,
            cfo=1e-4, delay=60, seed=33, add_fcs=True, tail=2048)
        assert starts[-1] - starts[0] < 6200
        return stream, -192, 12, True
    if name == "head_truncated":
        full, _starts = link.stream_many(
            _psdus(rng, 2), [24, 54], gaps=[400], snr_db=30.0, cfo=1e-4,
            delay=0, seed=34, add_fcs=True, tail=2048)
        return full[40:], -192, 2, True
    if name == "final_partial":
        # the stream ends 500 samples into frame 2 and 300 would be
        # too few: nv < head on the last lane, its own bucket (512)
        # caps the detector below the head, and `found` still reads
        # the window's true count
        stream, starts = link.stream_many(
            _psdus(rng, 3), [24, 6, 12], gaps=[400, 400], snr_db=30.0,
            cfo=1e-4, delay=60, seed=35, add_fcs=True, tail=2048)
        return stream[: int(starts[2]) + 500], 0, 3, True
    if name == "final_partial_short":
        stream, starts = link.stream_many(
            _psdus(rng, 2), [24, 6], gaps=[400], snr_db=30.0, cfo=1e-4,
            delay=60, seed=36, add_fcs=True, tail=2048)
        return stream[: int(starts[1]) + 380], 0, 2, False
    if name == "late_peak":
        return _late_peak_stream(), 0, 1, True
    assert name == "latest_crossing"
    return _latest_crossing_stream(), 0, 1, True


CASES = ("eight_rates", "cfo_awgn", "dozen_back_to_back",
         "head_truncated", "final_partial", "final_partial_short",
         "latest_crossing", "late_peak")


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def _scan_args(stream, own_lo, chunk_len):
    """The stream as one zero-padded chunk that owns up to its end."""
    valid = stream.shape[0]
    assert valid <= chunk_len
    chunk = np.zeros((chunk_len, 2), np.float32)
    chunk[:valid] = stream
    return (jnp.asarray(chunk), jnp.int32(valid), jnp.int32(own_lo),
            jnp.int32(valid))


def _assert_owned_lanes_identical(got, want):
    own = want[0]
    for name, g, w in zip(OUTPUTS, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        if name in ("own", "starts", "overflow"):
            np.testing.assert_array_equal(g, w, err_msg=name)
        else:
            np.testing.assert_array_equal(_bits(g[own]), _bits(w[own]),
                                          err_msg=name)
    # the rate word (PR 45): the RATE bits, and above them the lane's
    # own `eps` (output 5) to half a step of 2**-17 rad/sample
    rate, urad = rx.unpack_rate_word(got[6])
    assert (rate[own] < 16).all()
    assert np.abs(urad[own] - got[5][own].astype(np.float64) * 1e6
                  ).max(initial=0) <= 0.5 * 1e6 / rx.CFO_WORD_SCALE + 0.5


@pytest.mark.parametrize("geo", sorted(GEOS))
@pytest.mark.parametrize("case", CASES)
def test_head_scan_bit_identical_to_whole_window_oracle(case, geo):
    chunk_len, win_len = GEOS[geo]
    assert rx._acquire_head(win_len) < win_len   # the head really cuts
    stream, own_lo, n_owned, all_found = _case(case)
    args = _scan_args(stream, own_lo, chunk_len)
    head_scan, whole_scan = _scans(win_len)
    got = [np.asarray(o) for o in head_scan(*args)]
    want = [np.asarray(o) for o in whole_scan(*args)]
    own = want[0]
    assert own.sum() == n_owned and not want[2]
    assert want[3][own].all() == all_found
    if case == "final_partial":
        assert want[9][own].min() == 500 < rx._acquire_head(win_len)
    if case == "head_truncated":
        assert want[1][own][0] == 0              # clamped, and owned
    if case == "late_peak":
        assert want[1][own][0] == 1140 and want[4][own][0] == 191
    if case == "dozen_back_to_back":
        # each window reads ITS frame's RATE, in the order sent
        assert [int(b) for b in rx.unpack_rate_word(want[6])[0][own]] == \
            [RATES[m].signal_bits for m in [54, 48, 36, 24] * 3]
    _assert_owned_lanes_identical(got, want)


#: a symbol bucket whose segment outruns the toy window, as the served
#: ones do: 400 + 80 x 32 = 2960 > 2048
LONG_BUCKET = 32


def _chunk_case(name: str):
    """(stream, valid or None for all of it, own_lo) of a case of the
    chunk-sliced scan; each is a stream's final chunk."""
    rng = np.random.default_rng(44)
    if name == "eight_rates":
        stream, _n = _eight_rates(30.0, 1e-4, 441)
        return stream, None, -192
    if name in ("last_start_within_window", "last_start_within_head"):
        # the stream ends 1500 (under win_len) or 700 (under the head,
        # over the 400 `found` asks) samples into its last frame
        keep = 1500 if name == "last_start_within_window" else 700
        stream, starts = link.stream_many(
            _psdus(rng, 3, 100), [24, 54, 6], gaps=[400, 400],
            snr_db=30.0, cfo=1e-4, delay=60, seed=442, add_fcs=True,
            tail=2048)
        return stream[: int(starts[2]) + keep], None, 0
    if name == "frame_longer_than_window":
        # 100 bytes + FCS at 6 Mbit/s: 36 symbols, 3280 samples on air,
        # and a second frame close behind it, inside what a gather of
        # 2960 samples reaches and outside the 2048-sample window
        stream, starts = link.stream_many(
            _psdus(rng, 2, 100), [6, 54], gaps=[10], snr_db=30.0,
            cfo=1e-4, delay=60, seed=443, add_fcs=True, tail=4096)
        assert starts[1] - starts[0] > GEOS["toy"][1]
        return stream, None, 0
    if name == "first_chunk_head_truncated":
        full, _starts = link.stream_many(
            _psdus(rng, 2, 100), [12, 36], gaps=[400], snr_db=30.0,
            cfo=1e-4, delay=0, seed=444, add_fcs=True, tail=2048)
        return full[40:], None, -192
    if name == "idle_lane":
        # the packer zeroes an idle lane, but the graph may not lean
        # on that: samples of an earlier step under `valid == 0`
        stream, _n = _eight_rates(30.0, 1e-4, 445)
        return stream, 0, 0
    assert name == "more_than_k_plateaus"
    rates = [54, 48, 36, 24] * 5
    stream, starts = link.stream_many(
        _psdus(rng, 20, 14), rates, gaps=[10] * 19, snr_db=30.0,
        cfo=1e-4, delay=60, seed=446, add_fcs=True, tail=2048)
    return stream, None, -192


CHUNK_CASES = ("eight_rates", "last_start_within_window",
               "last_start_within_head", "frame_longer_than_window",
               "first_chunk_head_truncated", "idle_lane",
               "more_than_k_plateaus")


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_chunk_sliced_scan_equals_the_window_array_oracle(case):
    """ISSUE 44: steps 4 and 5 slice the padded chunk where the oracle
    slices the window array it cut, at a bucket whose segment is longer
    than the window; `segs` bit for bit."""
    chunk_len, win_len = GEOS["toy"]
    head = rx._acquire_head(win_len)
    need_b = rx.FRAME_DATA_START + 80 * LONG_BUCKET
    assert head < win_len < need_b
    stream, valid, own_lo = _chunk_case(case)
    chunk, n, lo, hi = _scan_args(stream, own_lo, chunk_len)
    if valid is not None:
        n = hi = jnp.int32(valid)
    chunk_scan, window_scan = _scans(win_len, LONG_BUCKET)
    got = [np.asarray(o) for o in chunk_scan(chunk, n, lo, hi)]
    want = [np.asarray(o) for o in window_scan(chunk, n, lo, hi)]
    own, found, fstart, nv, segs = (want[i] for i in (0, 3, 4, 9, 10))
    assert segs.shape == (K, need_b, 2)
    _assert_owned_lanes_identical(got, want)      # `segs` bit for bit
    if case == "idle_lane":
        # nothing is owned, so hold every lane to the oracle: whatever
        # the detector's cap leaves in `starts`, no sample comes out
        assert not own.any() and not want[2] and not nv.any()
        for name, g, w in zip(OUTPUTS, got, want):
            np.testing.assert_array_equal(_bits(g), _bits(w),
                                          err_msg=name)
        assert not segs.any()
        return
    assert found[own].all()
    if case == "more_than_k_plateaus":
        assert own.sum() == K and want[2] > 0     # overflow: K is full
        return
    assert not want[2]
    last = np.flatnonzero(own)[-1]
    if case == "eight_rates":
        assert own.sum() == 8
    if case == "last_start_within_window":
        assert own.sum() == 3 and head < nv[last] == 1500 < win_len
    if case == "last_start_within_head":
        assert own.sum() == 3 and 400 <= nv[last] == 700 < head
    if case == "first_chunk_head_truncated":
        assert want[1][own][0] == 0              # clamped, and owned
    if case == "frame_longer_than_window":
        # the segment ends where the WINDOW does, though the chunk
        # goes on with the frame's own tail and the next frame
        first = np.flatnonzero(own)[0]
        cut = int(nv[first] - fstart[first])
        assert nv[first] == win_len and cut < need_b
        a = int(want[1][first]) + win_len
        assert np.abs(np.asarray(chunk)[a: a + need_b - cut]).max() > 0.1
        assert got[10][first, cut - 1].any()
        assert not got[10][first, cut:].any()


def test_head_is_derived_from_syncs_alignment_constants(monkeypatch):
    # the defaults the locators run with ARE the named constants
    for fn in (sync._align_lts, sync.locate_frames):
        par = inspect.signature(fn).parameters
        assert par["align_back"].default == sync.ALIGN_BACK
        assert par["align_span"].default == sync.ALIGN_SPAN
    back, span = sync.ALIGN_BACK, sync.ALIGN_SPAN
    crossing = sync.LTS_OFFSET + back        # 224: rx.py's overflow cap
    assert (crossing, sync.LTS_OFFSET, sync.LTS_PAIR_SPAN) \
        == (224, 192, 128)
    head = rx._acquire_head(1 << 16)
    # timing: the peak-pick's last position and the samples it reads
    assert head >= crossing + span - back + sync.LTS_PAIR_SPAN == 736
    # the latest start, and the SIGNAL head sliced there
    assert head >= crossing + span - back - sync.LTS_OFFSET \
        + rx.FRAME_DATA_START == 816
    assert head == 1024
    # clipped to the window, one rule for every geometry
    assert [rx._acquire_head(w) for w in (512, 1024, 2048)] \
        == [512, 1024, 1024]
    # ...and it FOLLOWS the constants: a wider alignment span widens
    # the head instead of truncating the search
    monkeypatch.setattr(sync, "ALIGN_SPAN", 700)
    assert rx._acquire_head(1 << 16) == 2048
    monkeypatch.setattr(sync, "ALIGN_BACK", 400)
    assert rx._acquire_head(1 << 16) == 2048
    assert rx._acquire_head(1024) == 1024


def test_a_head_cut_too_short_is_seen(monkeypatch):
    # the cases above can tell: at half the derived head the late-peak
    # window's SIGNAL head no longer fits and its lane differs
    chunk_len, win_len = GEOS["toy"]
    stream, own_lo, _n, _f = _case("late_peak")
    args = _scan_args(stream, own_lo, chunk_len)
    want = [np.asarray(o) for o in _scans(win_len)[1](*args)]
    monkeypatch.setattr(rx, "_acquire_head", lambda w: 512)
    short = jax.jit(lambda c, v, lo, hi: rx.stream_chunk_graph(
        c, v, lo, hi, K, win_len, BUCKET))
    got = [np.asarray(o) for o in short(*args)]
    with pytest.raises(AssertionError):
        _assert_owned_lanes_identical(got, want)


@pytest.mark.parametrize("k", [1, 3, 8])
def test_the_gather_in_groups_is_the_vmap_bit_for_bit(k):
    """`rx._gather_in_groups` takes a lane's K candidates
    `rx.GATHER_GROUP` at a time and writes each group's segments in
    place (PR 45: the mask's and the derotation's temporaries are then
    a group's, not the batch's): the values are the vmap's over all K,
    a K the group does not divide and K = 1 included."""
    rng = np.random.default_rng(45 + k)
    bucket = 4
    need_b = rx.FRAME_DATA_START + 80 * bucket
    x = jnp.asarray(rng.standard_normal((2048 + need_b, 2)),
                    jnp.float32)
    args = (jnp.asarray(rng.integers(0, 2048, k), jnp.int32),
            jnp.asarray(rng.uniform(-0.05, 0.05, k), jnp.float32),
            jnp.asarray(rng.integers(0, need_b + 50, k), jnp.int32))

    def one(s, e, a):
        return rx.gather_segment_graph(x, s, e, a, bucket)

    got = jax.jit(lambda *a: rx._gather_in_groups(one, a, k, need_b))(
        *args)
    want = jax.jit(jax.vmap(one))(*args)
    assert got.shape == (k, need_b, 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
