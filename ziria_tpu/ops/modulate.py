"""Constellation mapping (BPSK/QPSK/16-QAM/64-QAM, 802.11 Gray labels).

Counterpart of the reference's `modulating.blk` (SURVEY.md §2.3).
TPU-native: bits group into per-axis Gray indices, then one LUT gather
per I/Q axis — no per-symbol branching; the constellation tables are the
AutoLUT analogue, precomputed in numpy.

Dtype policy: symbols are real pairs (..., 2) float32 (see ops/cplx —
the device path carries no complex dtype, and the reference's SORA
likewise carries complex16 as integer pairs). The numpy oracle
(np_modulate_ref) speaks complex64 for test readability.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ziria_tpu.utils.bits import bits_to_uint

# per-axis Gray maps: bits (LSB..MSB along axis) -> amplitude level
_GRAY2 = np.array([-3.0, -1.0, 3.0, 1.0])  # b0 b1 -> level, 16-QAM axis
# 64-QAM axis, 3 bits b0b1b2 (b0 most significant per standard order):
# 000->-7 001->-5 011->-3 010->-1 110->1 111->3 101->5 100->7
_GRAY3 = np.zeros(8)
for _bits, _lvl in [((0, 0, 0), -7), ((0, 0, 1), -5), ((0, 1, 1), -3),
                    ((0, 1, 0), -1), ((1, 1, 0), 1), ((1, 1, 1), 3),
                    ((1, 0, 1), 5), ((1, 0, 0), 7)]:
    _GRAY3[(_bits[0] << 2) | (_bits[1] << 1) | _bits[2]] = _lvl

_KMOD = {1: 1.0, 2: 1.0 / np.sqrt(2.0), 4: 1.0 / np.sqrt(10.0),
         6: 1.0 / np.sqrt(42.0)}


def modulate(bits, n_bpsc: int) -> jnp.ndarray:
    """bits (..., m*n_bpsc) -> pair symbols (..., m, 2) float32.

    Bit order within a symbol follows the standard: first bits map to I,
    remaining to Q, most-significant first.
    """
    bits = jnp.asarray(bits, jnp.uint8)
    n = bits.shape[-1]
    if n % n_bpsc:
        raise ValueError(f"bit count {n} not a multiple of n_bpsc={n_bpsc}")
    g = bits.reshape(bits.shape[:-1] + (n // n_bpsc, n_bpsc))
    if n_bpsc == 1:
        i = 2.0 * g[..., 0] - 1.0
        q = jnp.zeros_like(i)
    elif n_bpsc == 2:
        i = 2.0 * g[..., 0] - 1.0
        q = 2.0 * g[..., 1] - 1.0
    elif n_bpsc == 4:
        lut = jnp.asarray(_GRAY2)
        i = lut[bits_to_uint(g[..., 0:2], msb_first=True)]
        q = lut[bits_to_uint(g[..., 2:4], msb_first=True)]
    elif n_bpsc == 6:
        lut = jnp.asarray(_GRAY3)
        i = lut[bits_to_uint(g[..., 0:3], msb_first=True)]
        q = lut[bits_to_uint(g[..., 3:6], msb_first=True)]
    else:
        raise ValueError(f"unsupported n_bpsc {n_bpsc}")
    sym = jnp.stack([i, q], axis=-1) * _KMOD[n_bpsc]
    return sym.astype(jnp.float32)


def np_modulate_ref(bits: np.ndarray, n_bpsc: int) -> np.ndarray:
    """Independent oracle: per-symbol python loop over the standard's
    Gray tables. Tests only."""
    bits = np.asarray(bits, np.uint8).reshape(-1, n_bpsc)
    out = np.empty(bits.shape[0], np.complex64)
    kmod = _KMOD[n_bpsc]
    for s, b in enumerate(bits):
        if n_bpsc == 1:
            out[s] = kmod * (2 * int(b[0]) - 1)
        elif n_bpsc == 2:
            out[s] = kmod * ((2 * int(b[0]) - 1) + 1j * (2 * int(b[1]) - 1))
        elif n_bpsc == 4:
            i = _GRAY2[(int(b[0]) << 1) | int(b[1])]
            q = _GRAY2[(int(b[2]) << 1) | int(b[3])]
            out[s] = kmod * (i + 1j * q)
        else:
            i = _GRAY3[(int(b[0]) << 2) | (int(b[1]) << 1) | int(b[2])]
            q = _GRAY3[(int(b[3]) << 2) | (int(b[4]) << 1) | int(b[5])]
            out[s] = kmod * (i + 1j * q)
    return out
