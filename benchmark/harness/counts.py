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


#: an LLR pair in (two f32) and 64 survivor decisions out (eight to a
#: byte) for every trellis step of every lane
ACS_BYTES_PER_STEP = 2 * 4 + 8


def acs_min_bytes(lanes: int, symbol_bucket: int) -> int:
    """The least HBM traffic the add-compare-select kernel needs for
    ``lanes`` decode lanes (S x K; the padding up to a whole 128-lane
    tile is not counted as useful): every LLR pair in once (f32, 2 per
    trellis step per lane) and every survivor decision out once (64
    states packed 8 to a byte = 8 bytes per step per lane), over
    ``trellis_steps`` of the symbol bucket."""
    return lanes * trellis_steps(symbol_bucket) * ACS_BYTES_PER_STEP


#: the span args a count stands for, and the count each may not pass:
#: clause 18's longest frame in every slot (``ceilings`` below)
TRELLIS = ("rx.fleet.decode", "trellis_steps")
PULL_DECODE = ("rx.fleet.pull_decode", "bytes")
PULL_SCAN = ("rx.fleet.pull_scan", "bytes")
REPORTED = (TRELLIS, PULL_DECODE, PULL_SCAN)


def ceilings(s: int, k: int, symbol_bucket: int) -> dict:
    """The most a program that decodes and pulls nothing it cannot need
    may report under each of ``REPORTED``: the counts above."""
    return {TRELLIS: s * k * trellis_steps(symbol_bucket),
            PULL_DECODE: decode_d2h_bytes(s, k, symbol_bucket),
            PULL_SCAN: scan_d2h_bytes(s, k)}


def _floors(spans) -> dict:
    """The least each reported number can be, from what the program
    states beside it: (name, key, step) -> floor. A trellis runs at
    least the ``useful_bits`` its own span states; the decode's pull
    of that ``step`` brings back at least those bits packed eight to a
    byte and a CRC flag for every slot that held a frame (``lanes``);
    the scan's pull a byte a lane at the very least."""
    out = {}
    for sp in spans:
        a = sp.args
        if sp.name == "rx.fleet.decode" and "useful_bits" in a:
            out[TRELLIS + (a.get("step"),)] = a["useful_bits"]
            out[PULL_DECODE + (a.get("step"),)] = \
                -(-a["useful_bits"] // 8) + a.get("lanes", 0)
        elif sp.name == "rx.fleet.stack" and "active" in a:
            out[PULL_SCAN + (a.get("step"),)] = a["active"]
    return out


def _reports(spans):
    return [(sp.name, key, sp.args.get("step"), sp.args[key])
            for sp in spans for name, key in REPORTED
            if sp.name == name and key in sp.args]


def stale(spans, s: int, k: int, symbol_bucket: int) -> List[str]:
    """What the program's own spans say that cannot be right, one line
    for each distinct case (``spans``: anything with ``.name`` and
    ``.args``, as harness/annotations.py reads them out of a traced
    run). A reported number is wrong ABOVE its ceiling (the benchmark's
    own count: clause 18's longest frame in every slot; PR 32 to PR 33
    the program ran 6.74 times that and ``acs_roofline`` read as much
    too high) and BELOW the floor the program states beside it
    (``_floors``). Between the two the program decodes or pulls less
    than the ceiling, which is what S3's packed pull and S5(c), S5(e)
    are for: the counts then follow the spans (``reported``) and the
    run goes on (PR 36; until then any difference stopped it)."""
    top, low = ceilings(s, k, symbol_bucket), _floors(spans)
    out = set()
    for name, key, step, got in _reports(spans):
        if got > top[name, key]:
            out.add(f"{name} reports {key} {got}, above the ceiling "
                    f"harness/counts.py counts: {top[name, key]}")
        floor = low.get((name, key, step))
        if floor is not None and got < floor:
            out.add(f"{name} reports {key} {got}, below the floor its "
                    f"step's spans state: {floor}")
    return sorted(out)


def reported(spans, s: int, k: int, symbol_bucket: int) -> dict:
    """Per call, what the program reports under each of ``REPORTED``
    (the mean over the traced calls), and the ceiling where no span
    reports it: what ``d2h_bytes_per_step`` and ``acs_roofline``'s
    least bytes are built on. Today every report equals its ceiling."""
    out, got = ceilings(s, k, symbol_bucket), {}
    for name, key, _step, value in _reports(spans):
        got.setdefault((name, key), []).append(value)
    out.update({nk: sum(v) / len(v) for nk, v in got.items()})
    return out
