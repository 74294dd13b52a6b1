"""The loop-free FCS check (`ops/crc.check_crc32_masked`).

CRC-32 is affine over GF(2), so the served check is two XOR-reductions
against constants and a table look-up instead of a byte-serial scan.
Everything here holds it to the two things it replaced or restates:
`zlib.crc32` (the definition) and `crc32_bytes_masked` against the
sliced-out FCS (the table scan it took over from, kept as the serial
oracle), verdict for verdict.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ziria_tpu.ops import crc
from ziria_tpu.phy.wifi import rx
from ziria_tpu.phy.wifi.params import N_SERVICE_BITS, mixed_trellis_steps
from ziria_tpu.utils.bits import bits_to_bytes, np_bytes_to_bits, uint_to_bits

#: the served row: the 1024-symbol MTU bucket's bound trellis, 152 x
#: 216 = 32 832 bits (the longest frame LENGTH can announce), SERVICE
#: in front; 32 blocks of 1024 and 64 bits of a 33rd
MTU_ROW = mixed_trellis_steps(1024)
#: a toy row (4 symbols), not a whole number of 1024-bit blocks
TOY_ROW = 4 * 216


def _serial_check(bits, n_bits):
    """`check_crc32_masked` as it was before the affine form: the
    masked table scan over the body, against the FCS sliced out."""
    bits = jnp.asarray(bits, jnp.uint8)
    n_bits = jnp.asarray(n_bits, jnp.int32)
    reg = crc.crc32_bytes_masked(bits_to_bytes(bits),
                                 jnp.maximum(n_bits - 32, 0) // 8)
    fcs = jax.lax.dynamic_slice(
        bits, (jnp.maximum(n_bits - 32, 0),), (32,))
    return jnp.logical_and(n_bits >= 32,
                           jnp.all(uint_to_bits(reg, 32) == fcs))


def _frame_bits(rng, n_body: int) -> np.ndarray:
    """body + FCS as zlib computes it, in transmission bit order."""
    body = rng.integers(0, 256, n_body, dtype=np.uint8).tobytes()
    fcs = zlib.crc32(body).to_bytes(4, "little")
    return np_bytes_to_bits(np.frombuffer(body + fcs, np.uint8))


def _rows(rng, row_bits: int, lo: int, n_bytes, garbage: bool = True):
    """One padded row per message length (bytes, FCS included >= 4):
    the message at [lo, lo + 8 n), random bits everywhere else."""
    rows = (rng.integers(0, 2, (len(n_bytes), row_bits), dtype=np.uint8)
            if garbage else
            np.zeros((len(n_bytes), row_bits), np.uint8))
    for r, n in zip(rows, n_bytes):
        if n >= 4:
            r[lo: lo + 8 * n] = _frame_bits(rng, n - 4)
    return rows


def _both(rows, n_bytes, lo):
    n_bits = jnp.asarray(8 * np.asarray(n_bytes), jnp.int32)
    got = jax.jit(jax.vmap(
        lambda b, n: crc.check_crc32_masked(b, n, lo=lo)))(rows, n_bits)
    old = jax.jit(jax.vmap(_serial_check))(rows[:, lo:], n_bits)
    return np.asarray(got), np.asarray(old)


@pytest.mark.parametrize("lo", [0, N_SERVICE_BITS])
def test_every_byte_length_of_a_toy_bucket(lo):
    """Every length the bucket can hold, 0 bytes to full: zlib's FCS
    is accepted (4 bytes up), shorter is False, and the serial oracle
    agrees on every row."""
    rng = np.random.default_rng(27 + lo)
    n_bytes = np.arange((TOY_ROW - lo) // 8 + 1)
    got, old = _both(_rows(rng, TOY_ROW, lo, n_bytes), n_bytes, lo)
    np.testing.assert_array_equal(got, n_bytes >= 4)
    np.testing.assert_array_equal(got, old)


@pytest.mark.parametrize("where", ["first", "last", "seeded"])
def test_one_bit_corruption_reports_false_at_every_length(where):
    rng = np.random.default_rng(271)
    lo = N_SERVICE_BITS
    n_bytes = np.arange(4, (TOY_ROW - lo) // 8 + 1)
    rows = _rows(rng, TOY_ROW, lo, n_bytes)
    for r, n in zip(rows, n_bytes):
        at = {"first": 0, "last": 8 * n - 1,
              "seeded": int(rng.integers(8 * n))}[where]
        r[lo + at] ^= 1
    got, old = _both(rows, n_bytes, lo)
    assert not got.any()
    np.testing.assert_array_equal(got, old)


def _mtu_lengths(rng):
    full = (MTU_ROW - N_SERVICE_BITS) // 8
    return np.concatenate([[4, 5, 1504, 4095, full - 1, full],
                           rng.integers(4, full + 1, 7)])


def test_seeded_lengths_at_the_mtu_bucket():
    """Up to 32 816 message bits in a 32 832-bit row (a 4095-byte
    PSDU among them: the longest legal one): the served shape, against
    zlib and against the byte-serial scan."""
    rng = np.random.default_rng(2027)
    n_bytes = _mtu_lengths(rng)
    rows = _rows(rng, MTU_ROW, N_SERVICE_BITS, n_bytes)
    got, old = _both(rows, n_bytes, N_SERVICE_BITS)
    assert got.all()
    np.testing.assert_array_equal(got, old)
    for r, n in zip(rows, n_bytes):
        for at in (0, 8 * n - 1, int(rng.integers(8 * n))):
            r[N_SERVICE_BITS + at] ^= 1
    got, old = _both(rows, n_bytes, N_SERVICE_BITS)
    assert not got.any()
    np.testing.assert_array_equal(got, old)


@pytest.mark.parametrize("n_bits", [0, 8, 24, 32])
def test_streams_around_the_shortest_fcs(n_bits):
    """Under 32 bits no FCS fits: False whatever the bits. At 32 the
    body is empty and its FCS is zlib.crc32(b"") = 0: 32 zero bits."""
    rng = np.random.default_rng(n_bits)
    f = jax.jit(crc.check_crc32_masked)
    noise = rng.integers(0, 2, TOY_ROW, dtype=np.uint8)
    zeros = np.zeros(TOY_ROW, np.uint8)
    assert bool(f(zeros, n_bits)) == (n_bits == 32)
    assert bool(f(zeros, n_bits)) == bool(_serial_check(zeros, n_bits))
    noise[:32] = [1] + [0] * 31
    assert not bool(f(noise, n_bits))
    assert not bool(_serial_check(noise, n_bits))


def test_a_message_past_the_streams_end_is_false():
    f = jax.jit(crc.check_crc32_masked, static_argnames="lo")
    bits = _frame_bits(np.random.default_rng(5), 20)
    assert bool(f(bits, bits.size))
    assert not bool(f(bits, bits.size + 8))
    assert not bool(f(bits, bits.size, lo=8))


def test_garbage_outside_the_message_does_not_change_the_verdict():
    rng = np.random.default_rng(99)
    lo = N_SERVICE_BITS
    n_bytes = rng.integers(0, (TOY_ROW - lo) // 8 + 1, 24)
    clean = _rows(np.random.default_rng(1), TOY_ROW, lo, n_bytes,
                  garbage=False)
    dirty = clean.copy()
    for r, n in zip(dirty, n_bytes):
        r[:lo] = rng.integers(0, 2, lo)
        r[lo + 8 * n:] = rng.integers(0, 2, TOY_ROW - lo - 8 * n)
    # half the rows wrong, so both verdicts are exercised
    for rows in (clean, dirty):
        for r, n in list(zip(rows, n_bytes))[::2]:
            if n >= 4:
                r[lo + 8 * n - 9] ^= 1
    got_c, _ = _both(clean, n_bytes, lo)
    got_d, old_d = _both(dirty, n_bytes, lo)
    np.testing.assert_array_equal(got_c, got_d)
    np.testing.assert_array_equal(got_d, old_d)
    assert got_c.any() and not got_c.all()


def test_vmap_over_64_mixed_lengths_equals_lane_by_lane():
    rng = np.random.default_rng(64)
    lo = N_SERVICE_BITS
    n_bytes = rng.integers(0, (TOY_ROW - lo) // 8 + 1, 64)
    rows = _rows(rng, TOY_ROW, lo, n_bytes)
    for r, n in list(zip(rows, n_bytes))[::3]:
        r[lo + int(rng.integers(max(1, 8 * n)))] ^= 1
    got, old = _both(rows, n_bytes, lo)
    one = jax.jit(crc.check_crc32_masked, static_argnames="lo")
    lanes = [bool(one(r, 8 * int(n), lo=lo))
             for r, n in zip(rows, n_bytes)]
    np.testing.assert_array_equal(got, lanes)
    np.testing.assert_array_equal(got, old)
    assert got.any() and not got.all()


@pytest.mark.parametrize("row_bits", [32, 1000, 1024, 1032, 2504, 3 * 1024])
def test_buckets_that_are_and_are_not_whole_blocks(row_bits):
    """The stream is padded up to whole 1024-bit blocks when it does
    not factor; the verdicts do not depend on where the blocks cut."""
    rng = np.random.default_rng(row_bits)
    n_bytes = np.unique(np.concatenate(
        [[0, row_bits // 8], rng.integers(0, row_bits // 8 + 1, 12)]))
    rows = _rows(rng, row_bits, 0, n_bytes)
    got, old = _both(rows, n_bytes, 0)
    np.testing.assert_array_equal(got, n_bytes >= 4)
    np.testing.assert_array_equal(got, old)
    for r in rows:
        r[int(rng.integers(row_bits))] ^= 1     # in or out of the message
    got, old = _both(rows, n_bytes, 0)
    np.testing.assert_array_equal(got, old)


def test_tables_restate_the_definition():
    """The three constants against the bit-serial definition
    (`np_crc32_bits_ref`'s register): w1 and w2 are powers of the
    one-bit step, want[j] is what a correct j-byte message leaves."""
    w1, w2, want = crc._affine_tables(2, 16)

    def steps(reg, bits):
        for b in bits:
            fb = (reg ^ int(b)) & 1
            reg = (reg >> 1) ^ (crc._POLY if fb else 0)
        return reg

    lb = crc._BLOCK_BITS
    for j in (0, 1, 517, lb - 1):
        assert w1[j] == steps(0, [1] + [0] * (lb - 1 - j))
    for k in (0, 13, 31):
        assert w2[1, k] == 1 << k
        assert w2[0, k] == steps(1 << k, [0] * lb)
    assert want.shape == ((2 * lb - 16) // 8 + 1,)
    rng = np.random.default_rng(3)
    for j in (4, 40, want.size - 1):
        msg = _frame_bits(rng, j - 4)
        tail = [0] * (2 * lb - 16 - msg.size)
        assert want[j] == steps(steps(0, msg), tail)


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


def test_the_batched_check_traces_to_no_loop():
    clear = jax.ShapeDtypeStruct((64, MTU_ROW), jnp.uint8)
    npsdu = jax.ShapeDtypeStruct((64,), jnp.int32)
    prims = _primitives(
        jax.make_jaxpr(rx.crc_psdu_many_graph)(clear, npsdu).jaxpr, set())
    assert "reduce_xor" in prims, prims
    assert not prims & {"while", "scan", "fori_loop"}, prims
    # the detector does see the loop of the scan this replaced
    old = jax.make_jaxpr(jax.vmap(_serial_check))(
        jax.ShapeDtypeStruct((2, 64), jnp.uint8),
        jax.ShapeDtypeStruct((2,), jnp.int32))
    assert "scan" in _primitives(old.jaxpr, set())
