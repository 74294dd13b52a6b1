"""Viterbi decoder: roundtrip through the encoder, oracle equivalence,
puncturing with erasures, and noise tolerance."""

import numpy as np
import pytest

from ziria_tpu.ops import coding, viterbi
from ziria_tpu.utils.diff import assert_stream_eq

RNG = np.random.default_rng(11)


def tailed_bits(n):
    """random bits with 6 zero tail bits (zero-terminates the trellis)."""
    b = RNG.integers(0, 2, n).astype(np.uint8)
    b[-6:] = 0
    return b


def test_hard_decision_roundtrip():
    bits = tailed_bits(120)
    coded = np.asarray(coding.conv_encode(bits))
    dec = np.asarray(viterbi.viterbi_decode_bits(coded))
    assert_stream_eq(dec, bits)


def test_vs_oracle_on_noisy_llrs():
    bits = tailed_bits(40)
    coded = np.asarray(coding.conv_encode(bits)).astype(np.float64)
    llr = (2 * coded - 1) + 0.6 * RNG.standard_normal(coded.size)
    got = np.asarray(viterbi.viterbi_decode(llr.astype(np.float32)))
    want = viterbi.np_viterbi_ref(llr)
    assert_stream_eq(got, want)


def test_soft_decode_corrects_errors():
    bits = tailed_bits(200)
    coded = np.asarray(coding.conv_encode(bits)).astype(np.float64)
    tx = 2 * coded - 1
    rx = tx + 0.6 * RNG.standard_normal(tx.size)  # ~7 dB Eb/N0
    dec = np.asarray(viterbi.viterbi_decode(rx.astype(np.float32)))
    # rate-1/2 K=7 at this Eb/N0 decodes 200 bits error-free
    assert_stream_eq(dec, bits)


@pytest.mark.parametrize("rate", ["2/3", "3/4"])
def test_punctured_roundtrip(rate):
    n = 216  # multiple of both puncture periods after encoding
    bits = tailed_bits(n)
    coded = coding.conv_encode(bits)
    punct = coding.puncture(coded, rate)
    llr = 2.0 * np.asarray(punct, np.float32) - 1.0
    depunct = coding.depuncture(llr, rate, fill=0.0)
    dec = np.asarray(viterbi.viterbi_decode(depunct))
    assert_stream_eq(dec, bits)


def test_batched_vmap_frames():
    import jax
    frames = np.stack([tailed_bits(64) for _ in range(8)])
    coded = np.stack([np.asarray(coding.conv_encode(f)) for f in frames])
    llrs = 2.0 * coded.astype(np.float32) - 1.0
    dec = np.asarray(jax.jit(jax.vmap(viterbi.viterbi_decode))(llrs))
    assert_stream_eq(dec.astype(np.uint8), frames)


def test_n_bits_slice():
    bits = tailed_bits(50)
    coded = np.asarray(coding.conv_encode(bits))
    dec = np.asarray(viterbi.viterbi_decode_bits(coded, n_bits=30))
    assert dec.shape == (30,)
    assert_stream_eq(dec, bits[:30])


def test_native_c_viterbi_matches_jax():
    from ziria_tpu.runtime.native_lib import load, viterbi_decode_native
    if load() is None:
        pytest.skip("no native toolchain")
    bits = tailed_bits(300)
    coded = np.asarray(coding.conv_encode(bits)).astype(np.float64)
    llr = (2 * coded - 1) + 0.5 * RNG.standard_normal(coded.size)
    llr = llr.astype(np.float32)
    got_c = viterbi_decode_native(llr)
    got_jax = np.asarray(viterbi.viterbi_decode(llr))
    assert_stream_eq(got_c, got_jax)
    assert_stream_eq(got_c, bits)


def test_native_simd_acs_bit_exact_with_scalar():
    # the AVX2 ACS (runtime/native/viterbi.c, the SORA-SSE-class
    # baseline kernel) must match the portable scalar path bit-for-bit
    # on random soft values — same op order, same tie-breaks, same
    # per-step renorm
    import ctypes

    from ziria_tpu.runtime.native_lib import load, viterbi_decode_native
    lib = load()
    if lib is None:
        pytest.skip("no native toolchain")
    if not hasattr(lib, "ziria_viterbi_decode_scalar"):
        pytest.skip("old native build without the scalar hook")
    rng = np.random.default_rng(42)
    for T in (64, 1000, 8208):
        llrs = rng.normal(size=(T, 2)).astype(np.float32)
        fast = viterbi_decode_native(llrs)
        ref = np.zeros(T, np.uint8)
        lib.ziria_viterbi_decode_scalar(
            llrs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_int64(T),
            ref.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        np.testing.assert_array_equal(fast, ref, err_msg=f"T={T}")
        oracle = np.asarray(viterbi.viterbi_decode(llrs.reshape(-1)))
        np.testing.assert_array_equal(fast, oracle, err_msg=f"T={T}")
