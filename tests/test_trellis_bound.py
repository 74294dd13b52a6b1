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
