"""The mixed decode's trellis stops at the longest frame SIGNAL can
announce (`params.mixed_trellis_steps`, ISSUE 32).

LENGTH has 12 bits, so no DATA field exceeds 16 + 8 * 4095 + 6 bits:
152 symbols at 54 Mbit/s and at most 152 x 216 = 32 832 trellis steps
at any rate. `rx.decode_data_mixed` runs that many steps a lane at the
served 1024-symbol bucket (not 1024 x 216) and as many as the bucket
holds at every bucket of 152 symbols or fewer. Rows past the bound are
zero-LLR erasures by construction, so dropping them changes no bit of
any lane's real prefix: pinned here at toy width with the bound patched
down, and once (``-m slow``) at the served bucket against the whole
trellis. `decode_data_mixed` is called directly, never through the
``lru_cache``d factories: a patched bound must not land in a cache
another test file shares.
"""

import jax
import numpy as np
import pytest

from ziria_tpu.phy.wifi import params, rx, tx
from ziria_tpu.phy.wifi.params import (MAX_DBPS, N_SERVICE_BITS, RATES,
                                       mixed_trellis_steps, n_symbols)

BOUND_SYMS = params.MAX_SYM_AT_MAX_DBPS


def test_the_bound_is_derived_from_the_length_field():
    assert params.MAX_PSDU_BYTES == 4095
    assert params.MAX_DATA_BITS == 16 + 8 * 4095 + 6
    assert BOUND_SYMS == 152
    assert mixed_trellis_steps(1024) == 152 * 216 == 32832
    # no kernel pads: the ACS block, the mixed-fused block and a
    # 54 Mbit/s symbol all divide it
    from ziria_tpu.ops import viterbi_pallas as vp
    for block in (vp.UNROLL, vp.MIXED_UNROLL, MAX_DBPS):
        assert mixed_trellis_steps(1024) % block == 0


@pytest.mark.parametrize("mbps", sorted(RATES))
def test_no_legal_frame_fills_more_than_the_bound(mbps):
    """Every LENGTH 1..4095 at every rate: whole symbols' worth of
    data bits never pass 152 x 216, and the longest frame's are the
    most (32 832 at five rates, 32 784 and 32 796 at 6, 12 and 9)."""
    rate = RATES[mbps]
    steps = np.array([n_symbols(n, rate) * rate.n_dbps
                      for n in range(1, params.MAX_PSDU_BYTES + 1)])
    assert steps.max() <= BOUND_SYMS * MAX_DBPS
    assert steps.max() == steps[-1] \
        == -(-params.MAX_DATA_BITS // rate.n_dbps) * rate.n_dbps
    assert np.all(np.diff(steps) >= 0)


def test_the_bound_is_the_identity_up_to_152_symbols():
    for b in range(1, BOUND_SYMS + 1):
        assert mixed_trellis_steps(b) == b * MAX_DBPS
    for b in (153, 256, 512, 1024, 4096):
        assert mixed_trellis_steps(b) == BOUND_SYMS * MAX_DBPS


def test_each_branch_stops_at_the_symbols_that_hold_the_bound():
    """What `decode_data_mixed` demaps per rate at the served bucket:
    1024, 912, 684, ... 152 symbols, 3 969 of the 8 192 a lane."""
    t_max = mixed_trellis_steps(1024)
    syms = [params.mixed_branch_symbols(1024, RATES[m])
            for m in sorted(RATES)]
    assert syms == [1024, 912, 684, 456, 342, 228, 171, 152]
    assert sum(syms) == 3969
    assert all(s * RATES[m].n_dbps >= t_max or s == 1024
               for s, m in zip(syms, sorted(RATES)))
    # and every symbol of the bucket wherever the bound is the identity
    assert all(params.mixed_branch_symbols(b, r) == b
               for b in (1, 8, 64, 152) for r in RATES.values())


# --------------------------------------------- the mechanism, bound patched


def _frames(rng, n_sym_bucket, lengths):
    """One aligned, noiseless frame per rate (PSDU + FCS of
    ``lengths[mbps]`` bytes in all) padded to the symbol bucket.
    Returns (frames, rate_idx, n_bits_real, n_psdu_bits, sent bits)."""
    need = rx.FRAME_DATA_START + 80 * n_sym_bucket
    frames, ridx, nbits, npsdu, sent = [], [], [], [], []
    for m in sorted(RATES):
        body = rng.integers(0, 256, lengths[m] - 4).astype(np.uint8)
        bits = tx._host_psdu_bits(body, add_fcs=True)
        s = np.asarray(tx.encode_frame(body, m, add_fcs=True), np.float32)
        assert s.shape[0] <= need
        frames.append(np.pad(s, ((0, need - s.shape[0]), (0, 0))))
        ridx.append(rx.RATE_INDEX[m])
        nbits.append(n_symbols(lengths[m], RATES[m]) * RATES[m].n_dbps)
        npsdu.append(8 * lengths[m])
        sent.append(bits)
    return (np.stack(frames), np.asarray(ridx, np.int32),
            np.asarray(nbits, np.int32), np.asarray(npsdu, np.int32), sent)


def _decode(monkeypatch, bound, n_sym_bucket, frames, ridx, nbits, npsdu,
            **mode):
    """A FRESH jit of the mixed decode + FCS check with the trellis
    bound patched to ``bound`` symbols."""
    monkeypatch.setattr(
        rx, "mixed_trellis_steps",
        lambda b: min(b, bound) * MAX_DBPS)

    def f(fr, r, n, p):
        clear = rx.decode_data_mixed(fr, r, n, n_sym_bucket, **mode)
        return clear, rx.crc_psdu_many_graph(clear, p)

    clear, ok = jax.jit(f)(frames, ridx, nbits, npsdu)
    return np.asarray(clear), np.asarray(ok)


def _assert_same_real_bits(short, whole, nbits, npsdu, sent):
    (c_s, ok_s), (c_w, ok_w) = short, whole
    assert c_s.shape[1] < c_w.shape[1]
    np.testing.assert_array_equal(ok_s, ok_w)
    assert ok_s.all()
    for lane, (n, p, bits) in enumerate(zip(nbits, npsdu, sent)):
        np.testing.assert_array_equal(c_s[lane, :n], c_w[lane, :n])
        np.testing.assert_array_equal(
            c_s[lane, N_SERVICE_BITS: N_SERVICE_BITS + p], bits)


#: PSDU + FCS bytes by rate: every lane fits an 8-symbol bucket, and
#: 18 / 36 / 54 Mbit/s fill a 2-symbol bound to its last step (432)
TOY_LENGTHS = {6: 21, 9: 33, 12: 45, 18: 50, 24: 40, 36: 50, 48: 45,
               54: 50}


@pytest.mark.parametrize("mode", [{}, {"fused_demap": True}],
                         ids=["unfused", "fused"])
def test_a_patched_bound_changes_no_real_bit_at_any_rate(monkeypatch,
                                                         mode):
    """8-symbol bucket, bound patched to 2 symbols (432 steps of
    1728): all eight rates in one batch, three lanes ending on the
    bound's last step, two branches (24 and 48 Mbit/s) slicing a
    symbol in half. Each lane's [0, n_bits_real) and every CRC flag
    equal the whole-bucket decode's."""
    rng = np.random.default_rng(3202)
    frames, ridx, nbits, npsdu, sent = _frames(rng, 8, TOY_LENGTHS)
    assert nbits.max() == 2 * MAX_DBPS and (nbits <= 2 * MAX_DBPS).all()
    short = _decode(monkeypatch, 2, 8, frames, ridx, nbits, npsdu, **mode)
    whole = _decode(monkeypatch, 8, 8, frames, ridx, nbits, npsdu, **mode)
    assert short[0].shape == (8, 2 * MAX_DBPS)
    assert whole[0].shape == (8, 8 * MAX_DBPS)
    _assert_same_real_bits(short, whole, nbits, npsdu, sent)


def test_a_corrupted_frame_fails_its_fcs_under_both_bounds(monkeypatch):
    rng = np.random.default_rng(3203)
    frames, ridx, nbits, npsdu, _sent = _frames(rng, 8, TOY_LENGTHS)
    npsdu = npsdu.copy()
    npsdu[::2] -= 8               # claim a byte less: the FCS moves
    _c, ok_s = _decode(monkeypatch, 2, 8, frames, ridx, nbits, npsdu)
    _c, ok_w = _decode(monkeypatch, 8, 8, frames, ridx, nbits, npsdu)
    np.testing.assert_array_equal(ok_s, ok_w)
    np.testing.assert_array_equal(ok_s, np.arange(8) % 2 == 1)


#: the longest legal PSDU wherever the served bucket holds it (9 Mbit/s
#: up: five of these lanes end on the bound's last step), 3000 bytes at
#: 6 Mbit/s (1001 of the 1024 symbols)
SERVED_LENGTHS = {6: 3000, 9: 4095, 12: 4095, 18: 4095, 24: 4095,
                  36: 4095, 48: 4095, 54: 4095}


@pytest.mark.slow
def test_served_bucket_matches_the_whole_trellis(monkeypatch):
    """The served 1024-symbol bucket: 32 832 steps against the
    parent's 221 184, frames as long as the standard allows."""
    rng = np.random.default_rng(3204)
    frames, ridx, nbits, npsdu, sent = _frames(rng, 1024, SERVED_LENGTHS)
    assert nbits.max() == mixed_trellis_steps(1024)
    short = _decode(monkeypatch, BOUND_SYMS, 1024, frames, ridx, nbits,
                    npsdu)
    whole = _decode(monkeypatch, 1024, 1024, frames, ridx, nbits, npsdu)
    assert short[0].shape == (8, 32832)
    assert whole[0].shape == (8, 221184)
    _assert_same_real_bits(short, whole, nbits, npsdu, sent)


# ------------------------------- the bound that is data (ISSUE 53, S5(e))
#
# Since PR 53 the ACS and the traceback stop after the block that holds
# the last data bit of the tile's LONGEST lane (`rx.decode_bound`, a
# traced count), and no longer at the bucket's whole trellis. Every row
# past that is an erasure in every lane of the tile, so each lane's
# [0, n_bits_real), its PSDU and its FCS flag are the whole trellis's.
#
# ONE batch of real frames at the 64-symbol bucket (13 824 steps, 216
# blocks) holds every lane the cases need; the whole-trellis decode of
# it runs once a mode, and a case is DATA for the one bounded program:
# which lanes are live (the rest ride as erasures, ``nbits`` 0) and the
# bound of the longest among them. Lane values do not depend on the
# batch (the pinned `receive_many` contract).

BUCKET = 64
#: (rate, PSDU + FCS bytes): one short frame a rate; an ACK, a TCP ACK
#: and an MTU frame (the mix cell's three lengths); a 54 Mbit/s frame
#: of whole blocks (8 symbols x 216 = 27 x 64: it ends ON its bound)
LANES = [(6, 21), (9, 33), (12, 45), (18, 50), (24, 40), (36, 50),
         (48, 45), (54, 50), (24, 14), (12, 76), (54, 1504), (54, 200)]
BOUND_CASES = {f"{m}mbps": [i] for i, (m, _n) in enumerate(LANES[:8])}
BOUND_CASES.update({
    "all-eight-rates": list(range(8)),
    "ack-tcpack-mtu": [8, 9, 10],
    "a-lane-on-the-bounds-last-step": [11, 8],
    "one-live-lane-the-mtu-frame": [10],
    "every-lane": list(range(len(LANES)))})
BOUND_MODES = {"f32-radix2": {}, "f32-radix4": {"viterbi_radix": 4},
               "int16": {"viterbi_metric": "int16"}}


@pytest.fixture(scope="module")
def bound_batch():
    rng = np.random.default_rng(5300)
    need = rx.FRAME_DATA_START + 80 * BUCKET
    frames, ridx, nbits, npsdu, sent = [], [], [], [], []
    for m, n in LANES:
        body = rng.integers(0, 256, n - 4).astype(np.uint8)
        s = np.asarray(tx.encode_frame(body, m, add_fcs=True), np.float32)
        s = s + rng.normal(0, 0.02, s.shape).astype(np.float32)
        frames.append(np.pad(s, ((0, need - s.shape[0]), (0, 0))))
        ridx.append(rx.RATE_INDEX[m])
        nbits.append(n_symbols(n, RATES[m]) * RATES[m].n_dbps)
        npsdu.append(8 * n)
        sent.append(tx._host_psdu_bits(body, add_fcs=True))
    return (np.stack(frames), np.asarray(ridx, np.int32),
            np.asarray(nbits, np.int32), np.asarray(npsdu, np.int32), sent)


@pytest.fixture(scope="module", params=list(BOUND_MODES),
                ids=list(BOUND_MODES))
def bound_mode(request, bound_batch):
    """(mode, the bounded program compiled once, the whole trellis's
    clear rows and FCS flags of the batch, run once)."""
    frames, ridx, nbits, npsdu, _sent = bound_batch
    front, trellis, back = rx._mixed_stages(
        BUCKET, None, BOUND_MODES[request.param].get("viterbi_metric"),
        BOUND_MODES[request.param].get("viterbi_radix"), None, False,
        False)

    def decode(fr, r, n, p, blocks=None):
        raw = trellis(front(fr, r, n), r, n,
                      None if blocks is None else blocks.reshape(1))
        clear = back(raw)
        return (clear, rx.crc_psdu_many_graph(clear, p),
                back(raw.at[:, 7:].set(0)))

    whole = jax.jit(decode)(frames, ridx, nbits, npsdu)
    bounded = jax.jit(decode).lower(
        frames, ridx, nbits, npsdu, np.int32(1)).compile()
    return request.param, bounded, [np.asarray(w) for w in whole[:2]]


@pytest.mark.parametrize("case", list(BOUND_CASES))
def test_the_bounded_decode_is_the_whole_trellis_on_every_real_bit(
        bound_batch, bound_mode, case):
    frames, ridx, nbits, npsdu, sent = bound_batch
    _mode, bounded, (want_clear, want_crc) = bound_mode
    live = np.isin(np.arange(len(LANES)), BOUND_CASES[case])
    table = np.where(live, nbits, 0).astype(np.int32)
    t_max = mixed_trellis_steps(BUCKET)
    blocks, steps = rx.decode_bound(int(table.max()), t_max)
    assert steps < t_max and steps - table.max() < 64
    if case == "a-lane-on-the-bounds-last-step":
        assert steps == table.max() == 27 * 64
    clear, crc, zeros = (np.asarray(o) for o in bounded(
        frames, ridx, table, npsdu, np.int32(blocks)))
    for lane in np.flatnonzero(live):
        n, p = table[lane], npsdu[lane]
        np.testing.assert_array_equal(clear[lane, :n],
                                      want_clear[lane, :n])
        np.testing.assert_array_equal(
            clear[lane, N_SERVICE_BITS: N_SERVICE_BITS + p], sent[lane])
    np.testing.assert_array_equal(crc[live], want_crc[live])
    assert crc[live].all()
    # at and past the bound: the descrambled zeros, whatever the
    # kernels' unwritten blocks held, and the same bytes twice
    np.testing.assert_array_equal(clear[:, steps:], zeros[:, steps:])
    again = np.asarray(bounded(frames, ridx, table, npsdu,
                               np.int32(blocks))[0])
    np.testing.assert_array_equal(again, clear)


@pytest.mark.parametrize("longest, t_max, want", [
    (12096, 32832, (189, 12096)),    # a 1504-byte frame at 54 Mbit/s
    (12054, 32832, (189, 12096)),    # its data bits before whole symbols
    (12097, 32832, (190, 12160)),
    (32832, 32832, (513, 32832)),    # clause 18's longest: the whole
    (32784, 32832, (513, 32832)),    # the same at 6 and 12 Mbit/s
    (99999, 32832, (513, 32832)),    # never past the trellis there is
    (134, 32832, (3, 192)),          # an ACK at 6 Mbit/s: 6 x 24 = 144
    (64, 32832, (1, 64)), (65, 32832, (2, 128)),
    (1, 32832, (1, 64)), (0, 32832, (1, 64)),    # at least one block
    (1728, 1728, (27, 1728)),        # 8 symbols: 27 whole blocks
    (200, 216, (4, 216)), (216, 216, (4, 216)),  # a trellis of 3.4 blocks
    (10, 216, (1, 64))])
def test_decode_bound_rule(longest, t_max, want):
    """The ONE rule: whole blocks of UNROLL up to the longest lane's
    last bit, at least one, never past `t_max`; on ints, on an array a
    tile (the host's account), and traced (the program)."""
    assert rx.decode_bound(longest, t_max) == want
    both = rx.decode_bound(np.array([longest, longest]), t_max)
    assert [b.tolist() for b in both] == [[v, v] for v in want]
    traced = jax.jit(lambda n: rx.decode_bound(n, t_max))(np.int32(longest))
    assert tuple(int(v) for v in traced) == want


@pytest.mark.parametrize("s, k, live, lengths, want", [
    # the MTU cells' step: 64 slots, one tile of 64, every lane 12 096
    (8, 8, 8, [(54, 1504)], 64 * 12096),
    # half of them live: the tile runs all its 64 lanes all the same
    (8, 8, 4, [(54, 1504)], 64 * 12096),
    # 6 Mbit/s: 12 054 bits are 503 symbols of 24 = 12 072 -> 189 blocks
    (8, 8, 8, [(6, 1504)], 64 * 12096),
    # beacons: 204 bytes at 6 Mbit/s = 69 symbols = 1 656 bits, 26 blocks
    (8, 8, 1, [(6, 204)], 64 * 26 * 64),
    # the mix cell: 256 slots, two tiles; 80 live ride one
    (8, 32, 10, [(54, 1504), (24, 14), (12, 76)], 128 * 12096),
    # 136 live: the second tile holds 8 lanes, ACKs alone (stream order)
    (8, 32, 17, [(24, 14)], 256 * 3 * 64),
    # the longest frame fills the trellis
    (8, 16, 3, [(54, 4095)], 128 * 32832),
    # nothing live walks as one slot would: a tile, one block
    (8, 8, 0, [(54, 1504)], 64 * 64)])
def test_decode_steps_is_lanes_times_each_tiles_bound(s, k, live, lengths,
                                                     want):
    nbits = np.zeros((s, k), np.int32)
    for i in range(s):
        for j in range(live):
            m, n = lengths[(i * k + j) % len(lengths)]
            nbits[i, j] = n_symbols(n, RATES[m]) * RATES[m].n_dbps
    one = nbits.reshape(1, -1)         # one device holds every stream
    assert rx.decode_steps(one, 1024) == want
    # a decode mode whose kernels take no bound runs the whole trellis
    tile = rx.decode_walk(1, s * k)[1]
    assert rx.decode_steps(one, 1024, bounded=False) \
        == min(rx.decode_walk(int((nbits > 0).sum()), s * k)[1], s * k) \
        * 32832
    assert want % tile == 0
    # over a mesh each device walks and bounds its own streams' slots
    four = nbits.reshape(4, -1)
    assert rx.decode_steps(four, 1024) \
        == sum(rx.decode_steps(d[None], 1024) for d in four)


def test_without_a_bound_the_mixed_decode_lowers_as_on_the_parent():
    """`decode_data_mixed` (`receive_many`, the link) passes no bound:
    its lowered text at three decode modes is, to the byte, what the
    parent of PR 53 lowered (digests taken on that commit in this
    container, the interpreter's lowering), and its kernels keep a
    static grid with no prefetch operand."""
    import hashlib
    need = rx.FRAME_DATA_START + 80 * 8
    fr = jax.ShapeDtypeStruct((4, need, 2), np.float32)
    i4 = jax.ShapeDtypeStruct((4,), np.int32)
    pinned = [({}, "ec73f58ab36358c5"),
              ({"viterbi_radix": 4}, "34c3eb72cf2ff8c5"),
              ({"viterbi_metric": "int16"}, "b65316c060cdb822")]
    for mode, want in pinned:
        traced = jax.jit(lambda a, b, c: rx.decode_data_mixed(
            a, b, c, 8, interpret=True, **mode)).trace(fr, i4, i4)
        text = traced.lower().as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, mode
        calls = [e for e in _eqns(traced.jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 2
        assert all(e.params["grid_mapping"].num_index_operands == 0
                   for e in calls)


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from _eqns(sub)
