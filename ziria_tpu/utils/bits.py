"""Packed-bit utilities.

The reference carries `bit` streams through bit-packed C buffers
(`csrc/bit.c`, `buf_bit.c` — SURVEY.md §2.2). On TPU the working
representation is one bit per int8 lane (vector-friendly, XOR/AND are
native VPU ops); packing to real bytes exists for file I/O and hashing.
Bit order follows the reference's wire convention: within a byte, bit 0
(LSB) is first on the stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BIT_DTYPE = jnp.uint8


def bytes_to_bits(data, xp=jnp):
    """uint8 bytes (..., N) -> bits (..., 8N), LSB-first per byte."""
    data = xp.asarray(data, dtype=xp.uint8)
    shifts = xp.arange(8, dtype=xp.uint8)
    bits = (data[..., :, None] >> shifts[None, :]) & 1
    return bits.reshape(data.shape[:-1] + (data.shape[-1] * 8,))


def bits_to_bytes(bits, xp=jnp):
    """bits (..., 8N) -> uint8 bytes (..., N), LSB-first per byte."""
    bits = xp.asarray(bits, dtype=xp.uint8)
    n = bits.shape[-1]
    if n % 8:
        raise ValueError(f"bit count {n} not a multiple of 8")
    b = bits.reshape(bits.shape[:-1] + (n // 8, 8))
    weights = (xp.asarray(1, dtype=xp.uint8) << xp.arange(8, dtype=xp.uint8))
    return (b * weights).sum(axis=-1).astype(xp.uint8)


def bits_to_uint(bits, xp=jnp, msb_first: bool = False):
    """bits (..., K) -> integer (...,), K <= 32. LSB-first by default."""
    bits = xp.asarray(bits, dtype=xp.uint32)
    k = bits.shape[-1]
    idx = xp.arange(k, dtype=xp.uint32)
    if msb_first:
        idx = idx[::-1]
    return (bits << idx).sum(axis=-1)


def uint_to_bits(vals, k: int, xp=jnp, msb_first: bool = False):
    """integers (...,) -> bits (..., k). LSB-first by default."""
    vals = xp.asarray(vals, dtype=xp.uint32)
    idx = xp.arange(k, dtype=xp.uint32)
    if msb_first:
        idx = idx[::-1]
    return ((vals[..., None] >> idx) & 1).astype(xp.uint8)


def xor_reduce(x, axes):
    """XOR of an unsigned integer array over ``axes``: the sum of a
    GF(2) product whose rows are packed into words."""
    return jax.lax.reduce(x, np.zeros((), x.dtype), jax.lax.bitwise_xor,
                          axes)


def np_bytes_to_bits(data):
    return np.asarray(bytes_to_bits(np.asarray(data, np.uint8), xp=np),
                      np.uint8)


def np_bits_to_bytes(bits):
    return np.asarray(bits_to_bytes(np.asarray(bits, np.uint8), xp=np),
                      np.uint8)
