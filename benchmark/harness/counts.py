"""Operation and byte counts from shapes: the benchmark's own arithmetic,
kept here so that no later PR can move it.

Frame sizes follow IEEE 802.11-2012 clause 18 (equation 18-11): a PPDU is
a 320-sample preamble, one 80-sample SIGNAL symbol and N_SYM 80-sample
DATA symbols carrying 16 SERVICE bits, the PSDU and 6 tail bits.
"""

from __future__ import annotations

from typing import List

from ..reference.wifi_rx_ref import RATES

#: data bits per OFDM symbol by rate (Table 18-4)
N_DBPS = {mbps: row[2] for mbps, row in RATES.items()}
FRAME_DATA_START = 400      # preamble + SIGNAL, samples
#: the longest PSDU SIGNAL's 12-bit LENGTH field can announce (18.3.4.4)
MAX_PSDU_BYTES = 4095


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


def trellis_steps(symbol_bucket: int) -> int:
    """Trellis steps a decode lane needs: the symbol bucket at 54
    Mbit/s (216 data bits a symbol, the most), bound by the longest
    DATA field the standard allows, that of a 4095-byte PSDU: 152
    symbols, 32 832 steps. Derived from clause 18 and not from the
    program, which has held the same rule since PR 32
    (``params.mixed_trellis_steps``; benchmark/tests/test_counts.py
    holds the two equal, and a traced run checks ``stale`` below)."""
    widest = max(N_DBPS)
    return min(symbol_bucket, n_symbols(MAX_PSDU_BYTES, widest)) \
        * N_DBPS[widest]


def decode_d2h_bytes(s: int, k: int, symbol_bucket: int) -> int:
    """The decode's pull: (S, K, T) uint8 clear bits, T the trellis
    steps of a lane, and (S, K) bool CRC flags."""
    return s * k * trellis_steps(symbol_bucket) + s * k


def acs_min_bytes(lanes: int, symbol_bucket: int) -> int:
    """The least HBM traffic the add-compare-select kernel needs for
    ``lanes`` decode lanes (S x K; the padding up to a whole 128-lane
    tile is not counted as useful): every LLR pair in once (f32, 2 per
    trellis step per lane) and every survivor decision out once (64
    states packed 8 to a byte = 8 bytes per step per lane), over
    ``trellis_steps`` of the symbol bucket."""
    return lanes * trellis_steps(symbol_bucket) * (2 * 4 + 8)


def stale(spans, s: int, k: int, symbol_bucket: int) -> List[str]:
    """What the program's own spans say against the counts above, one
    line for each span arg that differs (``spans``: anything with
    ``.name`` and ``.args``, as harness/annotations.py reads them out
    of a traced run). The counts are the benchmark's arithmetic; the
    args are the program's report of what it ran and pulled. Where the
    two part, a metric built on the count reads wrong (PR 32 to PR 33:
    ``acs_roofline`` 6.74 times high), so a traced run stops on it."""
    want = {("rx.fleet.decode", "trellis_steps"):
            s * k * trellis_steps(symbol_bucket),
            ("rx.fleet.pull_decode", "bytes"):
            decode_d2h_bytes(s, k, symbol_bucket),
            ("rx.fleet.pull_scan", "bytes"): scan_d2h_bytes(s, k)}
    seen = {(sp.name, key, sp.args[key]) for sp in spans
            for (name, key) in want
            if sp.name == name and key in sp.args}
    return [f"{name} reports {key} {got}, harness/counts.py counts "
            f"{want[name, key]}" for name, key, got in sorted(seen)
            if got != want[name, key]]
