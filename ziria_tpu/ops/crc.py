"""CRC-32 (the 802.11 FCS) over bit streams.

Counterpart of the reference's `crc.blk` in the TX chain (SURVEY.md
§2.3). Parameters are the standard FCS ones: polynomial 0x04C11DB7,
init all-ones, LSB-first bit order, final complement.

Two designs, by direction. The TX (`crc32_bytes`, `append_crc32`)
groups bits into bytes and drives a ``lax.scan`` over them with a
256-entry lookup table — the role of the reference's AutoLUT-generated
tables (SURVEY.md §2.1 AutoLUT), precomputed at module load. The
served RX check (`check_crc32_masked`) walks nothing: CRC-32 is affine
over GF(2), so the verdict is two bit-matrix products (XOR-reductions
against constants) and one table look-up, with no step that depends
on the one before it. It replaced the masked byte scan
(`crc32_bytes_masked`, kept as the serial oracle of the tests), whose
27 646 dependent steps at the MTU bucket were 35.9 ms of the 83 ms
decode on a TPU v5e — more than both Viterbi kernels (ledger, PR 25).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ziria_tpu.utils.bits import bits_to_bytes, uint_to_bits, xor_reduce

_POLY = 0xEDB88320  # 0x04C11DB7 bit-reflected (LSB-first algorithm)


def _make_table() -> np.ndarray:
    tab = np.zeros(256, np.uint32)
    for b in range(256):
        c = b
        for _ in range(8):
            c = (c >> 1) ^ (_POLY if (c & 1) else 0)
        tab[b] = c
    return tab


_TABLE = _make_table()

_INIT = 0xFFFFFFFF
#: register (before the final complement) after body + FCS of any
#: correct frame: ~zlib.crc32(body + fcs) for every body
_RESIDUE = 0xDEBB20E3
#: bits per block of the first product; the second combines the blocks
_BLOCK_BITS = 1024


def _np_zero_steps(reg: np.ndarray, n_bits: int) -> np.ndarray:
    """``A^n_bits reg`` for every uint32 of ``reg``: the register
    stepped over ``n_bits`` zero input bits (bytes through the table,
    then the odd bits one at a time)."""
    reg = np.asarray(reg, np.uint32).copy()
    for _ in range(n_bits // 8):
        reg = (reg >> np.uint32(8)) ^ _TABLE[reg & np.uint32(0xFF)]
    for _ in range(n_bits % 8):
        reg = (reg >> np.uint32(1)) ^ np.where(
            reg & np.uint32(1), np.uint32(_POLY), np.uint32(0))
    return reg


@lru_cache(maxsize=None)
def _affine_tables(n_blocks: int, lo: int):
    """The three constants of :func:`check_crc32_masked` for a stream
    of ``N = n_blocks * _BLOCK_BITS`` bits whose message starts at bit
    ``lo`` (numpy, built once per geometry). With ``A`` the one-bit
    step of the register on a zero input bit and ``b = A e_0`` what a
    one input bit adds (the reflected polynomial):

    - ``w1[j] = A^(Lb-1-j) b``, (Lb,): what bit ``j`` of a block
      leaves in the register at the block's end;
    - ``w2[blk, k] = A^(Lb (n_blocks-1-blk)) e_k``, (n_blocks, 32):
      what bit ``k`` of block ``blk``'s remainder leaves at the
      stream's end;
    - ``want[j] = A^(N-lo-8j) Z + A^(N-lo) I``, ((N-lo)//8 + 1,): the
      end-of-stream register of a correct message of ``j`` bytes —
      ``A^(N-e) (Z + A^n I)`` with ``n = 8j`` and ``e = lo + n``.
    """
    lb = _BLOCK_BITS
    w1 = np.empty(lb, np.uint32)
    reg = np.array([_POLY], np.uint32)
    for j in range(lb - 1, -1, -1):
        w1[j] = reg[0]
        reg = _np_zero_steps(reg, 1)
    w2 = np.empty((n_blocks, 32), np.uint32)
    cols = np.uint32(1) << np.arange(32, dtype=np.uint32)
    for blk in range(n_blocks - 1, -1, -1):
        w2[blk] = cols
        cols = _np_zero_steps(cols, lb)
    m = n_blocks * lb - lo
    want = np.empty(m // 8 + 1, np.uint32)
    # Z and I stepped together: m bits in all by the time j reaches 0
    regs = _np_zero_steps(np.array([_RESIDUE, _INIT], np.uint32), m % 8)
    for j in range(m // 8, -1, -1):
        want[j] = regs[0]
        if j:
            regs = _np_zero_steps(regs, 8)
    want ^= regs[1]
    for tab in (w1, w2, want):      # cached: shared by every caller
        tab.setflags(write=False)
    return w1, w2, want


def crc32_bytes(data) -> jnp.ndarray:
    """CRC-32 of a uint8 byte array; returns uint32 scalar."""
    data = jnp.asarray(data, jnp.uint8)
    tab = jnp.asarray(_TABLE)

    def step(crc, byte):
        idx = (crc ^ byte.astype(jnp.uint32)) & 0xFF
        return (crc >> 8) ^ tab[idx], None

    crc, _ = jax.lax.scan(step, jnp.uint32(0xFFFFFFFF), data)
    return crc ^ jnp.uint32(0xFFFFFFFF)


def crc32_bits(bits) -> jnp.ndarray:
    """CRC-32 of a bit stream (multiple of 8 bits, LSB-first per byte);
    returns the 32 FCS bits in transmission order (LSB-first)."""
    crc = crc32_bytes(bits_to_bytes(bits))
    return uint_to_bits(crc, 32)


def append_crc32(bits) -> jnp.ndarray:
    """Append the 32-bit FCS to a bit stream (the TX `crc` block)."""
    bits = jnp.asarray(bits, jnp.uint8)
    return jnp.concatenate([bits, crc32_bits(bits)])


def check_crc32(bits) -> jnp.ndarray:
    """True iff the trailing 32 bits are the correct FCS of the rest."""
    bits = jnp.asarray(bits, jnp.uint8)
    body, fcs = bits[:-32], bits[-32:]
    return jnp.all(crc32_bits(body) == fcs)


def crc32_bytes_masked(data, n_bytes) -> jnp.ndarray:
    """CRC-32 of the first ``n_bytes`` (TRACED int32) of a padded uint8
    byte array: the same table-driven ``lax.scan`` as
    :func:`crc32_bytes`, with steps at or past ``n_bytes`` leaving the
    register untouched — so one fixed-length compiled scan serves every
    true length, and a batch of mixed-length streams rides one ``vmap``
    (the batched-FCS dispatch of ``framebatch._mixed_decode_tail`` and
    the fused loopback link). Bit-identical to ``crc32_bytes`` of the
    unpadded prefix."""
    data = jnp.asarray(data, jnp.uint8)
    tab = jnp.asarray(_TABLE)
    n_bytes = jnp.asarray(n_bytes, jnp.int32)

    def step(crc, ji):
        j, byte = ji
        idx = (crc ^ byte.astype(jnp.uint32)) & 0xFF
        nxt = (crc >> 8) ^ tab[idx]
        return jnp.where(j < n_bytes, nxt, crc), None

    crc, _ = jax.lax.scan(
        step, jnp.uint32(0xFFFFFFFF),
        (jnp.arange(data.shape[0], dtype=jnp.int32), data))
    return crc ^ jnp.uint32(0xFFFFFFFF)


def check_crc32_masked(bits, n_bits, lo: int = 0) -> jnp.ndarray:
    """Traced-length twin of :func:`check_crc32`: ``bits`` is a padded
    bit stream whose ``n_bits`` (TRACED int32, a multiple of 8) bits
    from position ``lo`` (static) on are body+FCS; returns True iff
    bits[lo+n_bits-32 : lo+n_bits] is the FCS of bits[lo : lo+n_bits-32].
    What lies before ``lo`` or after the message is ignored. Fixed
    shapes — one compile per padded length, every true length and
    (under ``vmap``) every lane of a mixed-length batch served by it.

    No loop: the register is affine in the message over GF(2),
    ``A^n I + sum_i A^(n-1-i) b m_i``, and zero bits ahead of the
    message leave a zero register at zero. So with everything outside
    the message masked to 0, the zero-init register at the END of the
    padded stream is one XOR-reduction of constants per block
    (``w1``), a second over the blocks' remainders (``w2``), and it
    equals ``A^(N-e)`` times the message's own. Over body + FCS a
    correct frame leaves the fixed residue, so the verdict is one
    comparison with ``want[n_bits // 8]`` (:func:`_affine_tables`):
    nothing is un-shifted and the FCS is never sliced out. Verdicts
    are those of :func:`crc32_bytes_masked` against the sliced FCS.

    A stream too short to even hold the 32-bit FCS (n_bits < 32 — a
    noise-corrupted SIGNAL claiming a 1..3-byte PSDU) reports False:
    no valid FCS can exist. (The eager :func:`check_crc32` cannot
    classify that case at all — its fixed slices raise a shape error —
    so this is the one place the masked twin is defined on strictly
    more inputs rather than bit-identical.) So does a message that
    claims to run past the stream's end."""
    bits = jnp.asarray(bits, jnp.uint8)
    n_bits = jnp.asarray(n_bits, jnp.int32)
    n = bits.shape[0]
    n_blocks = -(-n // _BLOCK_BITS)
    w1, w2, want = _affine_tables(n_blocks, lo)
    bits = jnp.pad(bits, (0, n_blocks * _BLOCK_BITS - n))
    pos = jnp.arange(n_blocks * _BLOCK_BITS, dtype=jnp.int32)
    live = (bits != 0) & (pos >= lo) & (pos < lo + n_bits)
    rem = xor_reduce(
        jnp.where(live.reshape(n_blocks, _BLOCK_BITS), w1, np.uint32(0)),
        (1,))
    reg = xor_reduce(
        jnp.where(uint_to_bits(rem, 32) != 0, w2, np.uint32(0)), (0, 1))
    ok = reg == jnp.asarray(want)[
        jnp.clip(n_bits // 8, 0, want.shape[0] - 1)]
    return ok & (n_bits >= 32) & (lo + n_bits <= n)


def np_crc32_bits_ref(bits: np.ndarray) -> np.ndarray:
    """Independent oracle: per-bit LFSR, straight from the CRC definition.
    Used only by tests."""
    reg = 0xFFFFFFFF
    for bit in np.asarray(bits, np.uint8):
        fb = (reg ^ int(bit)) & 1
        reg >>= 1
        if fb:
            reg ^= _POLY
    reg ^= 0xFFFFFFFF
    return np.array([(reg >> k) & 1 for k in range(32)], np.uint8)
