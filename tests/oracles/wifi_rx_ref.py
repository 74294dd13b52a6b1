"""Plain numpy 802.11a receiver — the reference the served path's
frames are compared with (chip_smoke.py; bytes, never float samples).

One capture in, one PSDU out, straight down the standard's receive
chain with no batching, bucketing or padding: fine CFO from the LTS
repetition, two-LTS channel estimate, SIGNAL decode (rate, length,
parity), then per-symbol FFT / zero-forcing equalize / pilot common-
phase / max-log demap / deinterleave / depuncture / Viterbi /
descramble at whatever rate the SIGNAL names. It shares the standard's
constant tables (subcarrier maps, interleaver permutation, puncture
pattern, trellis) with the package and nothing of its receive code.
Grown from the 54 Mbit/s-only ``np_rx_decode`` of the old benchmark.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from ziria_tpu.ops.coding import PUNCTURE_KEEP
from ziria_tpu.ops.interleave import deinterleave_perm
from ziria_tpu.ops.ofdm import (DATA_BINS, LTS_FREQ, PILOT_BINS,
                                PILOT_POLARITY, PILOT_VALS, TIME_SCALE)
from ziria_tpu.ops.scramble import np_lfsr_sequence_127
from ziria_tpu.ops.viterbi import np_viterbi_decode
from ziria_tpu.phy.wifi.params import (N_SERVICE_BITS, N_TAIL_BITS, RATES,
                                       SIGNAL_BITS_TO_MBPS)

_NORM = {1: 1.0, 2: np.sqrt(2.0), 4: np.sqrt(10.0), 6: np.sqrt(42.0)}


class RefFrame(NamedTuple):
    rate_mbps: int
    length_bytes: int
    psdu: np.ndarray          # (length_bytes,) uint8


def _demap(data: np.ndarray, n_bpsc: int, gain: np.ndarray) -> np.ndarray:
    """(n_sym, 48) complex equalized subcarriers -> (n_sym, 48*n_bpsc)
    max-log LLRs (positive = bit 1), |H|^2-weighted."""
    i = data.real * _NORM[n_bpsc]
    q = data.imag * _NORM[n_bpsc]
    if n_bpsc == 1:
        per = [i]
    elif n_bpsc == 2:
        per = [i, q]
    elif n_bpsc == 4:
        per = [i, 2 - np.abs(i), q, 2 - np.abs(q)]
    else:
        per = [i, 4 - np.abs(i), 2 - np.abs(np.abs(i) - 4),
               q, 4 - np.abs(q), 2 - np.abs(np.abs(q) - 4)]
    llr = np.stack(per, axis=-1) * gain[None, :, None]
    return llr.reshape(data.shape[0], -1)


def _symbols(x: np.ndarray, at: int, n_sym: int, H: np.ndarray,
             index0: int) -> np.ndarray:
    """n_sym OFDM symbols from sample ``at``: strip CP, FFT, equalize,
    derotate each by its pilots' common phase -> (n_sym, 48)."""
    syms = x[at: at + 80 * n_sym].reshape(n_sym, 80)[:, 16:]
    eq = np.fft.fft(syms, axis=-1) / TIME_SCALE \
        / np.where(H == 0, 1.0, H)[None, :]
    pol = PILOT_POLARITY[(np.arange(n_sym) + index0) % 127]
    expect = PILOT_VALS[None, :] * pol[:, None]
    ph = np.angle((eq[:, PILOT_BINS] * expect).sum(-1))
    return eq[:, DATA_BINS] * np.exp(-1j * ph)[:, None]


def _decode(data: np.ndarray, gain: np.ndarray, rate) -> np.ndarray:
    llr = _demap(data, rate.n_bpsc, gain)
    deint = llr[:, deinterleave_perm(rate.n_cbps, rate.n_bpsc)]
    keep = PUNCTURE_KEEP[rate.coding]
    dep = np.zeros((deint.size // keep.sum(), keep.size), np.float32)
    dep[:, np.flatnonzero(keep)] = deint.reshape(-1, keep.sum())
    return np_viterbi_decode(dep.reshape(-1, 2))


def np_receive(capture: np.ndarray) -> Optional[RefFrame]:
    """Decode the frame whose short preamble starts at sample 0 of
    ``capture`` ((n, 2) float I/Q). None when the SIGNAL field is not
    a valid header or the capture ends before the frame does."""
    cap = np.asarray(capture, np.float64)
    x = cap[:, 0] + 1j * cap[:, 1]
    if x.shape[0] < 400:
        return None
    # fine CFO: the two LTS repetitions are 64 samples apart
    eps = np.angle(np.vdot(x[192:256], x[256:320])) / 64.0
    x = x * np.exp(-1j * eps * np.arange(x.shape[0]))
    ref = np.zeros(64)
    ref[np.arange(-26, 27) % 64] = LTS_FREQ
    H = (np.fft.fft(x[192:256]) + np.fft.fft(x[256:320])) * 0.5 \
        / TIME_SCALE * ref
    gain = np.abs(H[DATA_BINS]) ** 2

    sig = _decode(_symbols(x, 320, 1, H, 0), gain, RATES[6])[:24]
    rate_bits = int("".join(str(b) for b in sig[0:4]), 2)
    length = int(sum(int(b) << k for k, b in enumerate(sig[5:17])))
    if sig[:18].sum() % 2 or rate_bits not in SIGNAL_BITS_TO_MBPS \
            or length == 0:
        return None
    rate = RATES[SIGNAL_BITS_TO_MBPS[rate_bits]]
    n_bits = N_SERVICE_BITS + 8 * length + N_TAIL_BITS
    n_sym = -(-n_bits // rate.n_dbps)
    if x.shape[0] < 400 + 80 * n_sym:
        return None

    bits = _decode(_symbols(x, 400, n_sym, H, 1), gain, rate)
    # the 7 scrambled SERVICE zeros ARE the scrambler's first 7
    # outputs: find the seed that produces them
    for seed in range(1, 128):
        seq = np_lfsr_sequence_127(
            np.array([(seed >> k) & 1 for k in range(7)], np.uint8))
        if np.array_equal(seq[:7], bits[:7]):
            break
    else:
        return None
    clear = bits ^ np.resize(seq, bits.size)
    psdu_bits = clear[N_SERVICE_BITS: N_SERVICE_BITS + 8 * length]
    psdu = np.packbits(psdu_bits.reshape(-1, 8), axis=1,
                       bitorder="little").reshape(-1)
    return RefFrame(rate.mbps, length, psdu)
