"""Plain numpy 802.11a receiver: the benchmark's reference.

One capture in, one PSDU out, straight down IEEE 802.11-2012 clause 18's
receive chain in float64, with no batching, bucketing or padding: fine CFO
from the LTS repetition, two-LTS channel estimate, SIGNAL decode (rate,
length, parity), then per-symbol FFT / zero-forcing equalize / pilot
common phase / max-log demap / deinterleave / depuncture / Viterbi /
descramble at whatever rate the SIGNAL names.

Copied from tests/oracles/wifi_rx_ref.py so that later PRs cannot move the
yardstick, and made self-contained: it imports nothing of ziria_tpu. The
standard's tables (subcarrier maps, interleaver, puncture patterns,
trellis, scrambler) are written out here from the standard.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

N_FFT = 64
N_SERVICE_BITS = 16
N_TAIL_BITS = 6

#: mbps -> (bits per subcarrier, coded bits per symbol, data bits per
#: symbol, coding, RATE field with R1 as MSB): Table 18-4 and 18-6
RATES = {
    6: (1, 48, 24, "1/2", 0b1101),
    9: (1, 48, 36, "3/4", 0b1111),
    12: (2, 96, 48, "1/2", 0b0101),
    18: (2, 96, 72, "3/4", 0b0111),
    24: (4, 192, 96, "1/2", 0b1001),
    36: (4, 192, 144, "3/4", 0b1011),
    48: (6, 288, 192, "2/3", 0b0001),
    54: (6, 288, 216, "3/4", 0b0011),
}
SIGNAL_BITS_TO_MBPS = {v[4]: m for m, v in RATES.items()}

PUNCTURE_KEEP = {
    "1/2": np.array([1, 1], bool),
    "2/3": np.array([1, 1, 1, 0], bool),
    "3/4": np.array([1, 1, 1, 0, 0, 1], bool),
}

_PILOT_SC = np.array([-21, -7, 7, 21])
PILOT_VALS = np.array([1.0, 1.0, 1.0, -1.0])
_DATA_SC = np.array([k for k in range(-26, 27)
                     if k != 0 and k not in (-21, -7, 7, 21)])
DATA_BINS = _DATA_SC % N_FFT
PILOT_BINS = _PILOT_SC % N_FFT
LTS_FREQ = np.array(
    [1, 1, -1, -1, 1, 1, -1, 1, -1, 1, 1, 1, 1, 1, 1, -1, -1, 1, 1, -1,
     1, -1, 1, 1, 1, 1,
     0,
     1, -1, -1, 1, 1, -1, 1, -1, 1, -1, -1, -1, -1, -1, 1, 1, -1, -1, 1,
     -1, 1, -1, 1, 1, 1, 1], np.float64)
#: the transmitter's IFFT scale for unit average power over 52 carriers
TIME_SCALE = N_FFT / np.sqrt(52.0)
_NORM = {1: 1.0, 2: np.sqrt(2.0), 4: np.sqrt(10.0), 6: np.sqrt(42.0)}


def lfsr_127(seed_bits) -> np.ndarray:
    """One period of the x^7 + x^4 + 1 scrambler from a 7-bit state
    (seed_bits[k] = x_{k+1})."""
    s = [int(b) for b in seed_bits]
    out = []
    for _ in range(127):
        fb = s[6] ^ s[3]
        out.append(fb)
        s = [fb] + s[:6]
    return np.array(out, np.uint8)


PILOT_POLARITY = 1.0 - 2.0 * lfsr_127([1] * 7).astype(np.float64)


def deinterleave_perm(n_cbps: int, n_bpsc: int) -> np.ndarray:
    """out[k] = in[j(k)]: undo the two permutations of 18.3.5.7."""
    s = max(n_bpsc // 2, 1)
    k = np.arange(n_cbps)
    i = (n_cbps // 16) * (k % 16) + k // 16
    j = s * (i // s) + (i + n_cbps - (16 * i // n_cbps)) % s
    return j


def _trellis():
    """K=7, g0=133o, g1=171o. For next state t and decision d: the
    predecessor and the two coded bits on the edge, as +-1."""
    g0 = (1, 0, 1, 1, 0, 1, 1)
    g1 = (1, 1, 1, 1, 0, 0, 1)
    pred = np.zeros((64, 2), np.int64)
    out_a = np.zeros((64, 2))
    out_b = np.zeros((64, 2))
    for t in range(64):
        b = t >> 5
        for d in range(2):
            s = ((t & 31) << 1) | d
            w = [b] + [(s >> (5 - i)) & 1 for i in range(6)]
            pred[t, d] = s
            out_a[t, d] = 2.0 * (sum(g * x for g, x in zip(g0, w)) % 2) - 1
            out_b[t, d] = 2.0 * (sum(g * x for g, x in zip(g1, w)) % 2) - 1
    return pred, out_a, out_b


_PRED, _OUT_A, _OUT_B = _trellis()


def viterbi(llrs: np.ndarray) -> np.ndarray:
    """Soft-decision Viterbi over (T, 2) LLR pairs (positive = bit 1,
    0 = erasure), starting in state 0, best final state."""
    dep = np.asarray(llrs, np.float64).reshape(-1, 2)
    T = dep.shape[0]
    metrics = np.full(64, -1e30)
    metrics[0] = 0.0
    decisions = np.zeros((T, 64), np.uint8)
    for k in range(T):
        cand = metrics[_PRED] + _OUT_A * dep[k, 0] + _OUT_B * dep[k, 1]
        decisions[k] = np.argmax(cand, 1)
        metrics = cand.max(1)
        metrics -= metrics.max()
    state = int(np.argmax(metrics))
    bits = np.zeros(T, np.uint8)
    for k in range(T - 1, -1, -1):
        bits[k] = state >> 5
        state = _PRED[state, decisions[k, state]]
    return bits


class RefFrame(NamedTuple):
    rate_mbps: int
    length_bytes: int
    psdu: np.ndarray          # (length_bytes,) uint8
    eps: float                # CFO estimate, rad/sample


def lts_cfo(x: np.ndarray) -> float:
    """Fine CFO (rad/sample) of a complex capture whose short preamble
    starts at sample 0: the two LTS repetitions are 64 samples apart."""
    return float(np.angle(np.vdot(x[192:256], x[256:320])) / 64.0)


def _demap(data: np.ndarray, n_bpsc: int, gain: np.ndarray) -> np.ndarray:
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


def _symbols(x, at: int, n_sym: int, H, index0: int) -> np.ndarray:
    syms = x[at: at + 80 * n_sym].reshape(n_sym, 80)[:, 16:]
    eq = np.fft.fft(syms, axis=-1) / TIME_SCALE \
        / np.where(H == 0, 1.0, H)[None, :]
    pol = PILOT_POLARITY[(np.arange(n_sym) + index0) % 127]
    expect = PILOT_VALS[None, :] * pol[:, None]
    ph = np.angle((eq[:, PILOT_BINS] * expect).sum(-1))
    return eq[:, DATA_BINS] * np.exp(-1j * ph)[:, None]


def _decode(data, gain, mbps: int) -> np.ndarray:
    n_bpsc, n_cbps, _dbps, coding, _sig = RATES[mbps]
    llr = _demap(data, n_bpsc, gain)
    deint = llr[:, deinterleave_perm(n_cbps, n_bpsc)]
    keep = PUNCTURE_KEEP[coding]
    dep = np.zeros((deint.size // keep.sum(), keep.size))
    dep[:, np.flatnonzero(keep)] = deint.reshape(-1, keep.sum())
    return viterbi(dep.reshape(-1, 2))


def np_receive(capture: np.ndarray) -> Optional[RefFrame]:
    """Decode the frame whose short preamble starts at sample 0 of
    ``capture`` ((n, 2) float I/Q). None when the SIGNAL field is not a
    valid header or the capture ends before the frame does."""
    cap = np.asarray(capture, np.float64)
    x = cap[:, 0] + 1j * cap[:, 1]
    if x.shape[0] < 400:
        return None
    eps = lts_cfo(x)
    x = x * np.exp(-1j * eps * np.arange(x.shape[0]))
    ref = np.zeros(64)
    ref[np.arange(-26, 27) % 64] = LTS_FREQ
    H = (np.fft.fft(x[192:256]) + np.fft.fft(x[256:320])) * 0.5 \
        / TIME_SCALE * ref
    gain = np.abs(H[DATA_BINS]) ** 2

    sig = _decode(_symbols(x, 320, 1, H, 0), gain, 6)[:24]
    rate_bits = int("".join(str(b) for b in sig[0:4]), 2)
    length = int(sum(int(b) << k for k, b in enumerate(sig[5:17])))
    if sig[:18].sum() % 2 or rate_bits not in SIGNAL_BITS_TO_MBPS \
            or length == 0:
        return None
    mbps = SIGNAL_BITS_TO_MBPS[rate_bits]
    n_bits = N_SERVICE_BITS + 8 * length + N_TAIL_BITS
    n_sym = -(-n_bits // RATES[mbps][2])
    if x.shape[0] < 400 + 80 * n_sym:
        return None

    bits = _decode(_symbols(x, 400, n_sym, H, 1), gain, mbps)
    # the 7 scrambled SERVICE zeros ARE the scrambler's first 7 outputs
    for seed in range(1, 128):
        seq = lfsr_127([(seed >> k) & 1 for k in range(7)])
        if np.array_equal(seq[:7], bits[:7]):
            break
    else:
        return None
    clear = bits ^ np.resize(seq, bits.size)
    psdu_bits = clear[N_SERVICE_BITS: N_SERVICE_BITS + 8 * length]
    psdu = np.packbits(psdu_bits.reshape(-1, 8), axis=1,
                       bitorder="little").reshape(-1)
    return RefFrame(mbps, length, psdu, eps)


def derotate(capture: np.ndarray, eps: float, n: int,
             avail: int) -> np.ndarray:
    """The first ``n`` samples of ``capture`` ((m, 2) I/Q, zero past
    ``avail``) times e^{-j eps k}, in float64: what the receiver hands
    its decoder for a frame starting at sample 0."""
    cap = np.zeros((n, 2))
    m = max(0, min(n, avail, capture.shape[0]))
    cap[:m] = capture[:m]
    k = np.arange(n, dtype=np.float64)
    c, s = np.cos(eps * k), np.sin(eps * k)
    return np.stack([cap[:, 0] * c + cap[:, 1] * s,
                     cap[:, 1] * c - cap[:, 0] * s], axis=-1)
