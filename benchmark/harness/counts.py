"""Operation and byte counts from shapes: the benchmark's own arithmetic,
kept here so that no later PR can move it.

Frame sizes follow IEEE 802.11-2012 clause 18 (equation 18-11): a PPDU is
a 320-sample preamble, one 80-sample SIGNAL symbol and N_SYM 80-sample
DATA symbols carrying 16 SERVICE bits, the PSDU and 6 tail bits.
"""

from __future__ import annotations

from ..reference.wifi_rx_ref import RATES

#: data bits per OFDM symbol by rate (Table 18-4)
N_DBPS = {mbps: row[2] for mbps, row in RATES.items()}
FRAME_DATA_START = 400      # preamble + SIGNAL, samples


def n_symbols(psdu_bytes: int, mbps: int) -> int:
    return -(-(16 + 8 * psdu_bytes + 6) // N_DBPS[mbps])


def frame_samples(psdu_bytes: int, mbps: int) -> int:
    """Samples on air of one PPDU whose PSDU (FCS included) has
    ``psdu_bytes`` bytes."""
    return FRAME_DATA_START + 80 * n_symbols(psdu_bytes, mbps)


def scan_h2d_bytes(s: int, chunk_len: int) -> int:
    """Host to device for one chunk scan: the (S, chunk, 2) f32 slab
    and three (S,) int32 vectors (valid, own_lo, own_hi)."""
    return s * chunk_len * 2 * 4 + 3 * s * 4


def decode_h2d_bytes(s: int, k: int) -> int:
    """Four (S, K) int32 tables (row, rate index, bits, PSDU bits)."""
    return 4 * s * k * 4


def scan_d2h_bytes(s: int, k: int) -> int:
    """The per-lane scalars `_pull_chunk` brings back: own, found,
    parity (bool) and starts, fstart, rate bits, length, n_valid
    (int32), each (S, K), and overflow (S,) bool."""
    return s * k * (3 * 1 + 5 * 4) + s


def decode_d2h_bytes(s: int, k: int, symbol_bucket: int) -> int:
    """The decode's pull: (S, K, T) uint8 clear bits, T the symbol
    bucket at 216 data bits a symbol, and (S, K) bool CRC flags."""
    return s * k * symbol_bucket * 216 + s * k


def acs_min_bytes(lanes: int, symbol_bucket: int) -> int:
    """The least HBM traffic the add-compare-select kernel needs for
    ``lanes`` decode lanes (S x K; the padding up to a whole 128-lane
    tile is not counted as useful): every LLR pair in once (f32, 2 per
    trellis step per lane) and every survivor decision out once (64
    states packed 8 to a byte = 8 bytes per step per lane), over
    T = symbol bucket x 216 trellis steps."""
    return lanes * symbol_bucket * 216 * (2 * 4 + 8)
