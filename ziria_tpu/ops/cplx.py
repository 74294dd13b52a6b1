"""Real-pair complex arithmetic and matmul DFTs — the framework's
canonical sample representation.

Two reasons this exists:

1. It mirrors the reference: SORA carries `complex16`/`complex32` as
   integer re/im pairs, never a hardware complex type (SURVEY.md §2.2
   `numerics.c`). The TPU analogue is a trailing axis of size 2 over
   f32/bf16 (or int16 for the fixed-point path).
2. The device path carries **no complex dtype at all** (the first
   backend it ran on had none: every complex op failed
   `UNIMPLEMENTED`) — jnp.complex64 may appear only in CPU-side test
   oracles, never on the device path.

FFTs on this representation are DFT matrix multiplies: at n=64 (the
802.11 symbol size) a pair of 64x64 f32 matmuls per re/im component is
exactly the MXU's shape, and batching over symbols/frames makes it one
big GEMM — faster than a generic small-FFT on TPU and the reason the
reference's SSE FFT brick maps so well here.

Convention: ``p[..., 0]`` = real, ``p[..., 1]`` = imag.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np


def cpack(re, im):
    return jnp.stack([re, im], axis=-1)


def cre(p):
    return p[..., 0]


def cim(p):
    return p[..., 1]


def conj(p):
    return jnp.stack([p[..., 0], -p[..., 1]], axis=-1)


def cmul(a, b):
    """Elementwise complex multiply of pair arrays."""
    ar, ai = a[..., 0], a[..., 1]
    br, bi = b[..., 0], b[..., 1]
    return jnp.stack([ar * br - ai * bi, ar * bi + ai * br], axis=-1)


def cmul_conj(a, b):
    """a * conj(b)."""
    ar, ai = a[..., 0], a[..., 1]
    br, bi = b[..., 0], b[..., 1]
    return jnp.stack([ar * br + ai * bi, ai * br - ar * bi], axis=-1)


def cscale(p, s):
    return p * jnp.asarray(s)[..., None]

def cabs2(p):
    return p[..., 0] ** 2 + p[..., 1] ** 2


def cdiv(a, b, eps: float = 1e-12):
    """a / b (pairwise); eps regularizes |b|^2 so a zero divisor (e.g. a
    dead subcarrier in an estimated channel) yields 0, not NaN."""
    num = cmul_conj(a, b)
    den = cabs2(b) + eps
    return num / den[..., None]


def cexp(theta):
    """unit phasor pair from angle(s)."""
    return jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)


def cangle(p):
    return jnp.arctan2(p[..., 1], p[..., 0])


# `cexp_ramp`'s block, and 2*pi in three parts (Cody-Waite): the first
# has 8 significant bits and the second 11, so a whole number of turns
# below 2**13 times either is an exact float32 product.
_RAMP_SHIFT = 9
RAMP_BLOCK = 1 << _RAMP_SHIFT
_TURN_1 = 6.28125
_TURN_2 = 0.0019350051879882812
_TURN_3 = 3.019916050561733e-07


def cexp_ramp(eps, n: int):
    """Unit phasors e^{j*eps*m} for m = 0..n-1 as an (n, 2) pair array
    (`eps` a float32 scalar, traced or not): THE carrier rotation, for
    the receiver's derotation (`ops/sync.correct_cfo`) and the
    channel's offset (`phy/channel.apply_cfo`) alike.

    The float32 product ``eps * m`` carries half an ulp of its own
    size, so a ramp formed from it is wrong by 1.2e-4 rad once the
    phase passes 2048 rad (sample 55 960 at 20 ppm of a 5.8 GHz
    carrier, 0.0366 rad/sample) and loses a bit more with every
    doubling. Here the error does not grow with ``m``: with ``m = 512
    j + i``, the phase of block ``j``, ``j * (512 * eps)`` modulo a
    turn, is formed without rounding until it is small (``512 * eps``
    is split into a 12-bit head and its exact remainder, so both
    products with ``j``, below 2**12, are exact, and the head's whole
    turns come off in three parts of 2*pi), and ``i * eps``, below
    ``512 * eps``, is added to it. What is left is the rounding of
    that last product and sum, an ulp of ``512 * eps``: within 1.5e-6
    of a float64 ramp by the same float32 `eps` for ``|eps| <= pi /
    64`` (the fine estimator's range) at every served length
    (tests/test_derotate_precision.py), within 1.5e-5 for ``|eps| <=
    pi / 16`` (the coarse estimator's) at any ``n`` up to 2**21. One
    block and less (``n <= 512``: the acquisition's heads) is the
    plain product, bit for bit.

    A dozen integer and float operations a sample beside the sine and
    the cosine, in the same elementwise pass. Not a two-level table
    (``coarse[j] * fine[i]``, two transcendentals a BLOCK): its blocked
    product has to be laid out again as the segment is, which costs
    the scan more than the transcendentals do (PERF.md, PR 45)."""
    if n > RAMP_BLOCK << 12:
        raise ValueError(
            f"cexp_ramp: {n} samples exceed {RAMP_BLOCK << 12}, the "
            f"longest ramp whose block phases are formed exactly")
    eps = jnp.asarray(eps, jnp.float32)
    m = jnp.arange(n, dtype=jnp.int32)
    j = (m >> _RAMP_SHIFT).astype(jnp.float32)
    i = (m & (RAMP_BLOCK - 1)).astype(jnp.float32)
    step = eps * RAMP_BLOCK                     # exact: a power of two
    head = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(step, jnp.uint32)
        & jnp.uint32(0xFFFFF000), jnp.float32)
    tail = step - head                          # exact, 12 bits
    phase = j * head                            # exact, 24 bits
    turns = jnp.round(phase * float(0.5 / np.pi))
    phase = ((phase - turns * _TURN_1) - turns * _TURN_2) \
        - turns * _TURN_3
    return cexp(phase + (j * tail + i * eps))


# ----------------------------------------------------------------- dft

@lru_cache(maxsize=None)
def _dft_mats(n: int, inverse: bool):
    k = np.arange(n)
    ang = 2.0 * np.pi * np.outer(k, k) / n
    sign = 1.0 if inverse else -1.0
    c = np.cos(ang).astype(np.float32)
    s = (sign * np.sin(ang)).astype(np.float32)
    if inverse:
        c /= n
        s /= n
    return c, s


def dft_pair(p, inverse: bool = False, axis: int = -2):
    """DFT along `axis` of a pair array (axis counts among the non-pair
    dims; default: the axis right before the re/im axis). numpy-fft
    convention: forward unscaled, inverse scaled by 1/n."""
    p = jnp.asarray(p)
    if axis != -2:
        p = jnp.moveaxis(p, axis, -2)
    n = p.shape[-2]
    c, s = _dft_mats(n, inverse)
    c = jnp.asarray(c)
    s = jnp.asarray(s)
    xr, xi = p[..., 0], p[..., 1]

    # full f32 products: a TPU's DEFAULT matmul precision rounds f32
    # operands to bfloat16 (three significant digits), which on the
    # chip cost the FFT the SIGNAL field's RATE bits at 30 dB (PR 22)
    def mm(x, w):
        return jnp.matmul(x, w.T, precision=jax.lax.Precision.HIGHEST)

    # W = C + iS; y = W x
    yr = mm(xr, c) - mm(xi, s)
    yi = mm(xr, s) + mm(xi, c)
    out = jnp.stack([yr, yi], axis=-1)
    if axis != -2:
        out = jnp.moveaxis(out, -2, axis)
    return out


def fft_pair(p, axis: int = -2):
    return dft_pair(p, inverse=False, axis=axis)


def ifft_pair(p, axis: int = -2):
    return dft_pair(p, inverse=True, axis=axis)


# ------------------------------------------------- host-side conversion

def from_complex(c, xp=np):
    """complex array -> pair array (host/test use)."""
    c = xp.asarray(c)
    return xp.stack([c.real, c.imag], axis=-1).astype(xp.float32)


def to_complex(p, xp=np):
    """pair array -> complex array (host/test use)."""
    p = xp.asarray(p)
    return (p[..., 0] + 1j * p[..., 1]).astype(xp.complex64)
