"""DSP op library vs independent numpy oracles (golden-file pattern,
SURVEY.md §4: generate ground truth from an obvious loop implementation,
compare the vectorized TPU path against it)."""

import jax
import numpy as np
import pytest

from ziria_tpu.utils.bits import (bytes_to_bits, bits_to_bytes,
                                  bits_to_uint, uint_to_bits)
from ziria_tpu.ops import (crc, scramble, coding, interleave, modulate,
                           ofdm, cplx)
from ziria_tpu.utils.diff import assert_stream_eq

RNG = np.random.default_rng(42)


def rand_bits(n):
    return RNG.integers(0, 2, n).astype(np.uint8)


# ---------------------------------------------------------------- bits

def test_bits_bytes_roundtrip():
    data = RNG.integers(0, 256, 33).astype(np.uint8)
    bits = bytes_to_bits(data)
    assert bits.shape == (33 * 8,)
    back = bits_to_bytes(bits)
    assert_stream_eq(np.asarray(back), data)


def test_bit_order_lsb_first():
    bits = np.asarray(bytes_to_bits(np.array([0b00000001], np.uint8)))
    assert bits[0] == 1 and bits[1:].sum() == 0


def test_uint_roundtrip():
    v = np.asarray(bits_to_uint(uint_to_bits(np.uint32(0xDEADBEEF), 32)))
    assert v == 0xDEADBEEF


# ---------------------------------------------------------------- crc

def test_crc32_check_value():
    # classic CRC-32 check: crc32(b"123456789") == 0xCBF43926
    data = np.frombuffer(b"123456789", np.uint8)
    assert int(np.asarray(crc.crc32_bytes(data))) == 0xCBF43926


def test_crc32_bits_vs_oracle():
    bits = rand_bits(8 * 41)
    got = np.asarray(crc.crc32_bits(bits))
    want = crc.np_crc32_bits_ref(bits)
    assert_stream_eq(got, want)


def test_crc32_append_check_roundtrip():
    bits = rand_bits(8 * 17)
    with_fcs = crc.append_crc32(bits)
    assert bool(np.asarray(crc.check_crc32(with_fcs)))
    corrupted = np.asarray(with_fcs).copy()
    corrupted[5] ^= 1
    assert not bool(np.asarray(crc.check_crc32(corrupted)))


# ---------------------------------------------------------------- scrambler

def test_scramble_vs_oracle():
    bits = rand_bits(300)
    seed = uint_to_bits(np.uint32(0b1011101), 7)
    got = np.asarray(scramble.scramble_bits(bits, seed))
    want = scramble.np_scramble_ref(bits, np.asarray(seed))
    assert_stream_eq(got, want)


def test_scramble_involution():
    bits = rand_bits(500)
    seed = uint_to_bits(np.uint32(0x5B), 7)
    twice = scramble.descramble_bits(scramble.scramble_bits(bits, seed), seed)
    assert_stream_eq(np.asarray(twice), bits)


def test_scrambler_sequence_period_127_and_balance():
    seq = np.asarray(scramble.lfsr_sequence_127(np.ones(7, np.uint8)))
    assert seq.shape == (127,)
    # maximal-length sequence: 64 ones, 63 zeros
    assert seq.sum() == 64


def test_scrambler_sequence_of_every_seed_is_the_lfsrs():
    """One period is an XOR of unit-seed periods (no loop): all 128
    seeds, one at a time and under vmap, against the bit-serial LFSR."""
    seeds = np.array([[(s >> k) & 1 for k in range(7)]
                      for s in range(128)], np.uint8)
    want = np.stack([scramble.np_lfsr_sequence_127(s) for s in seeds])
    got = np.asarray(jax.vmap(scramble.lfsr_sequence_127)(seeds))
    assert_stream_eq(got, want)
    one = jax.jit(scramble.lfsr_sequence_127)
    for s in (0, 1, 0x5D, 0x7F):
        assert_stream_eq(np.asarray(one(seeds[s])), want[s])
    assert "scan" not in str(jax.make_jaxpr(scramble.lfsr_sequence_127)(
        seeds[1]))


def test_seed_recovery():
    for seed_val in [1, 0b1011101, 0x7F, 0x2A]:
        seed = uint_to_bits(np.uint32(seed_val), 7)
        zeros = np.zeros(7, np.uint8)
        first7 = np.asarray(scramble.scramble_bits(zeros, seed))
        rec = np.asarray(scramble.recover_seed(first7))
        assert_stream_eq(rec, np.asarray(seed))


# ---------------------------------------------------------------- coding

def test_conv_encode_vs_oracle():
    bits = rand_bits(200)
    got = np.asarray(coding.conv_encode(bits))
    want = coding.np_conv_encode_ref(bits)
    assert_stream_eq(got, want)


def test_conv_encode_impulse_generators():
    # impulse response = generator taps interleaved
    x = np.zeros(7, np.uint8)
    x[0] = 1
    out = np.asarray(coding.conv_encode(x)).reshape(-1, 2)
    assert_stream_eq(out[:, 0], coding.G0.astype(np.uint8))
    assert_stream_eq(out[:, 1], coding.G1.astype(np.uint8))


@pytest.mark.parametrize("rate,period,kept", [("1/2", 2, 2), ("2/3", 4, 3),
                                              ("3/4", 6, 4)])
def test_puncture_lengths(rate, period, kept):
    coded = rand_bits(12 * period)
    p = np.asarray(coding.puncture(coded, rate))
    assert p.size == 12 * kept


@pytest.mark.parametrize("rate", ["1/2", "2/3", "3/4"])
def test_depuncture_inverse_on_kept_positions(rate):
    coded = rand_bits(24).astype(np.float32)
    p = coding.puncture(coded.astype(np.uint8), rate)
    d = np.asarray(coding.depuncture(np.asarray(p, np.float32), rate,
                                     fill=-1.0))
    keep = np.tile(coding.PUNCTURE_KEEP[rate], 24 // coding.PUNCTURE_KEEP[rate].size)
    assert_stream_eq(d[keep], coded[keep], atol=0)
    assert (d[~keep] == -1.0).all()


# ---------------------------------------------------------------- interleaver

@pytest.mark.parametrize("n_cbps,n_bpsc", [(48, 1), (96, 2), (192, 4),
                                           (288, 6)])
def test_interleave_vs_oracle(n_cbps, n_bpsc):
    bits = rand_bits(n_cbps * 3)
    got = np.asarray(interleave.interleave(bits, n_cbps, n_bpsc))
    want = interleave.np_interleave_ref(bits, n_cbps, n_bpsc)
    assert_stream_eq(got, want)


@pytest.mark.parametrize("n_cbps,n_bpsc", [(48, 1), (96, 2), (192, 4),
                                           (288, 6)])
def test_deinterleave_inverse(n_cbps, n_bpsc):
    bits = rand_bits(n_cbps * 2)
    round_trip = interleave.deinterleave(
        interleave.interleave(bits, n_cbps, n_bpsc), n_cbps, n_bpsc)
    assert_stream_eq(np.asarray(round_trip), bits)


# ---------------------------------------------------------------- modulation

@pytest.mark.parametrize("n_bpsc", [1, 2, 4, 6])
def test_modulate_vs_oracle(n_bpsc):
    bits = rand_bits(n_bpsc * 96)
    got = cplx.to_complex(np.asarray(modulate.modulate(bits, n_bpsc)))
    want = modulate.np_modulate_ref(bits, n_bpsc)
    assert_stream_eq(got, want, atol=1e-6)


@pytest.mark.parametrize("n_bpsc", [1, 2, 4, 6])
def test_modulate_unit_average_power(n_bpsc):
    # over all bit patterns, constellation has unit average energy
    n_sym = 1 << n_bpsc
    patterns = np.asarray(
        [[(v >> k) & 1 for k in range(n_bpsc)][::-1] for v in range(n_sym)],
        np.uint8).reshape(-1)
    syms = cplx.to_complex(np.asarray(modulate.modulate(patterns, n_bpsc)))
    assert abs(np.mean(np.abs(syms) ** 2) - 1.0) < 1e-6


# ---------------------------------------------------------------- ofdm

def test_map_extract_roundtrip():
    syms_c = (RNG.standard_normal((5, 48))
              + 1j * RNG.standard_normal((5, 48))).astype(np.complex64)
    syms = cplx.from_complex(syms_c)
    bins = ofdm.map_subcarriers(syms, symbol_index0=1)
    data, pilots = ofdm.extract_subcarriers(bins)
    assert_stream_eq(cplx.to_complex(np.asarray(data)), syms_c, atol=1e-6)
    # pilot polarity follows the 127-sequence
    pol = ofdm.PILOT_POLARITY[1:6]
    want_p = ofdm.PILOT_VALS[None, :] * pol[:, None]
    assert_stream_eq(cplx.to_complex(np.asarray(pilots)),
                     want_p.astype(np.complex64), atol=1e-6)


def test_ofdm_modulate_demodulate_roundtrip():
    syms = cplx.from_complex(
        (RNG.standard_normal((4, 48)) + 1j * RNG.standard_normal((4, 48))
         ).astype(np.complex64))
    bins = ofdm.map_subcarriers(syms)
    t = ofdm.ofdm_modulate(bins)
    assert t.shape == (4, 80, 2)
    # cyclic prefix is a copy of the tail
    assert_stream_eq(np.asarray(t[:, :16]), np.asarray(t[:, -16:]),
                     atol=1e-6)
    back = ofdm.ofdm_demodulate(t)
    assert_stream_eq(np.asarray(back), np.asarray(bins), atol=1e-4)


def test_dft_pair_matches_numpy_fft():
    x = (RNG.standard_normal((3, 64)) + 1j * RNG.standard_normal((3, 64))
         ).astype(np.complex64)
    p = cplx.from_complex(x)
    fwd = cplx.to_complex(np.asarray(cplx.fft_pair(p)))
    assert_stream_eq(fwd, np.fft.fft(x, axis=-1).astype(np.complex64),
                     atol=1e-3)
    inv = cplx.to_complex(np.asarray(cplx.ifft_pair(p)))
    assert_stream_eq(inv, np.fft.ifft(x, axis=-1).astype(np.complex64),
                     atol=1e-4)


def test_preamble_shape_and_sts_periodicity():
    p = cplx.to_complex(np.asarray(ofdm.preamble()))
    assert p.shape == (320,)
    # short training: 16-sample periodicity over the first 160 samples
    assert np.allclose(p[:144], p[16:160], atol=1e-5)
    # long training: the two 64-sample symbols are identical
    assert np.allclose(p[192:256], p[256:320], atol=1e-5)
    # GI2 is the tail of the long symbol
    assert np.allclose(p[160:192], p[224:256], atol=1e-5)
