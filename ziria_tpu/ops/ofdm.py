"""OFDM symbol assembly: subcarrier mapping, pilots, DFT/IDFT, cyclic
prefix, and the PLCP preamble (STS/LTS).

Counterpart of the reference's `map_ofdm.blk` + `ifft.blk` + preamble
generation (SURVEY.md §2.3), with MXU matmul-DFTs (ops/cplx.dft_pair)
replacing the SORA SSE FFT bricks (§2.2).

All sample data uses the framework's pair representation
(`(..., 2) float32`, ops/cplx): the device path carries no complex
dtype, and the reference likewise carries complex as integer pairs.
Everything is batched over leading symbol/frame axes — a whole frame of
symbols is one (n_sym, 64) x (64, 64) GEMM per re/im component.

Constants follow IEEE 802.11a-1999 §17.3 (values reproduced from
standard knowledge; the reference mount was empty so no file:line
citations are possible — see SURVEY.md evidence note).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ziria_tpu.ops import cplx
from ziria_tpu.ops.scramble import np_lfsr_sequence_127

N_FFT = 64
N_CP = 16
N_DATA = 48

# subcarrier indices (FFT bin, negative = N_FFT + k)
PILOT_SC = np.array([-21, -7, 7, 21])
PILOT_VALS = np.array([1.0, 1.0, 1.0, -1.0])
_used = [k for k in range(-26, 27) if k != 0]
DATA_SC = np.array([k for k in _used if k not in set(PILOT_SC.tolist())])
assert DATA_SC.size == N_DATA

DATA_BINS = np.where(DATA_SC < 0, DATA_SC + N_FFT, DATA_SC)
PILOT_BINS = np.where(PILOT_SC < 0, PILOT_SC + N_FFT, PILOT_SC)

# pilot polarity sequence p_0..p_126: scrambler sequence with all-ones
# seed, mapped 0 -> +1, 1 -> -1 (host-side constant, no JAX at import)
_seq = np_lfsr_sequence_127(np.ones(7, np.uint8))
PILOT_POLARITY = (1.0 - 2.0 * _seq.astype(np.float64))

# long training symbol, subcarriers -26..26 (0 at DC)
LTS_FREQ = np.array(
    [1, 1, -1, -1, 1, 1, -1, 1, -1, 1, 1, 1, 1, 1, 1, -1, -1, 1, 1, -1,
     1, -1, 1, 1, 1, 1,
     0,
     1, -1, -1, 1, 1, -1, 1, -1, 1, -1, -1, -1, -1, -1, 1, 1, -1, -1, 1,
     -1, 1, -1, 1, 1, 1, 1], np.float64)

# short training symbol: nonzero every 4th subcarrier in -24..24
STS_SC = np.array([-24, -20, -16, -12, -8, -4, 4, 8, 12, 16, 20, 24])
STS_VALS = np.sqrt(13.0 / 6.0) * np.array(
    [1 + 1j, -1 - 1j, 1 + 1j, -1 - 1j, -1 - 1j, 1 + 1j,
     -1 - 1j, -1 - 1j, 1 + 1j, 1 + 1j, 1 + 1j, 1 + 1j])

# TX time-domain scaling: unit average sample power over 52 used tones
TIME_SCALE = N_FFT / np.sqrt(52.0)


def map_subcarriers(data_syms, symbol_index0: int = 1) -> jnp.ndarray:
    """(..., n_sym, 48, 2) data symbols -> (..., n_sym, 64, 2) frequency
    bins with pilots inserted. ``symbol_index0`` is the polarity index of
    the first symbol (SIGNAL uses 0; DATA symbols start at 1)."""
    syms = jnp.asarray(data_syms, jnp.float32)
    n_sym = syms.shape[-3]
    bins = jnp.zeros(syms.shape[:-2] + (N_FFT, 2), jnp.float32)
    bins = bins.at[..., jnp.asarray(DATA_BINS), :].set(syms)
    pol = jnp.asarray(PILOT_POLARITY, jnp.float32)[
        (jnp.arange(n_sym) + symbol_index0) % 127]
    pilots_re = jnp.asarray(PILOT_VALS, jnp.float32)[None, :] * pol[:, None]
    pilots = jnp.stack([pilots_re, jnp.zeros_like(pilots_re)], axis=-1)
    bins = bins.at[..., jnp.asarray(PILOT_BINS), :].set(pilots)
    return bins


def extract_subcarriers(bins):
    """(..., 64, 2) bins -> ((..., 48, 2) data, (..., 4, 2) pilots)."""
    bins = jnp.asarray(bins)
    return (bins[..., jnp.asarray(DATA_BINS), :],
            bins[..., jnp.asarray(PILOT_BINS), :])


def ofdm_modulate(bins) -> jnp.ndarray:
    """(..., 64, 2) frequency bins -> (..., 80, 2) time samples (CP +
    symbol), via the IDFT matmul; scaled for unit average power."""
    t = cplx.ifft_pair(jnp.asarray(bins, jnp.float32)) * TIME_SCALE
    return jnp.concatenate([t[..., N_FFT - N_CP:, :], t], axis=-2)


def ofdm_demodulate(samples) -> jnp.ndarray:
    """(..., 80, 2) time samples (CP + symbol) -> (..., 64, 2) bins."""
    sym = jnp.asarray(samples)[..., N_CP:, :]
    return cplx.fft_pair(sym) / TIME_SCALE


def _freq_to_bins(sc: np.ndarray, vals: np.ndarray) -> np.ndarray:
    bins = np.zeros(N_FFT, np.complex128)
    bins[np.where(sc < 0, sc + N_FFT, sc)] = vals
    return bins


def _preamble_np() -> np.ndarray:
    """numpy complex build (host-side constant), converted to pairs."""
    sts_bins = _freq_to_bins(STS_SC, STS_VALS)
    sts_time = (np.fft.ifft(sts_bins) * N_FFT / np.sqrt(12.0)
                / np.sqrt(13.0 / 6.0))
    short = np.tile(sts_time[:16], 10)

    lts_bins = _freq_to_bins(np.arange(-26, 27), LTS_FREQ)
    lts_time = np.fft.ifft(lts_bins) * N_FFT / np.sqrt(52.0)
    long = np.concatenate([lts_time[-32:], lts_time, lts_time])
    return np.concatenate([short, long])


_PREAMBLE = cplx.from_complex(_preamble_np())


def preamble() -> jnp.ndarray:
    """The 320-sample PLCP preamble as pairs (320, 2): 10 short symbols
    (160) + GI2 + 2 long symbols (160)."""
    return jnp.asarray(_PREAMBLE)


_LTS_TIME = cplx.from_complex(
    np.fft.ifft(_freq_to_bins(np.arange(-26, 27), LTS_FREQ))
    * N_FFT / np.sqrt(52.0))


def lts_time_symbol() -> np.ndarray:
    """One 64-sample long-training symbol as pairs (64, 2) (for RX
    channel estimation)."""
    return _LTS_TIME
