"""What decides ``correct``: every number compared, beside its limit.

Copied in spirit from chip_smoke.py (frames back once and identical,
nothing hidden, the numpy receiver agrees) and made to work on a lap
that is replayed for as long as the window lasts. Every check returns
``Compared`` rows; a run is correct when no row is over its limit.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, NamedTuple

import numpy as np

from ..reference import wifi_rx_ref as ref
from . import load

#: a served frame's start is LTS-aligned; it lands within a few samples
#: of the true start (0 in every rehearsal), so a quarter preamble is a
#: wide net that still cannot reach a neighbouring frame (>= 300 apart)
START_TOL = 80
#: a frame this close to the edge of what was consumed may belong to
#: either side of it: it is not expected, and counted if it came
EDGE = 640

RESILIENCE_COUNTERS = ("resilience.fatal", "resilience.fallbacks",
                       "resilience.degraded", "resilience.retries",
                       "resilience.async_rescans")
SITES = ("rx.stream_chunk_multi", "rx.stream_decode_multi")


class Compared(NamedTuple):
    name: str
    value: float
    limit: float
    how: str = "<="         # value <= limit, or ">=" for a floor

    @property
    def ok(self) -> bool:
        if self.value != self.value:        # NaN never passes
            return False
        return self.value >= self.limit if self.how == ">=" \
            else self.value <= self.limit


def plain(x):
    """A compared value as the result line can carry it: a python
    number, and None for a NaN (which JSON has no word for)."""
    x = float(x)
    return None if x != x else int(x) if x == int(x) else x


def report(rows: List["Compared"]) -> Dict[str, dict]:
    """The result line's last two keys. ``not_ok``: the rows that
    failed as ``{name: [value, limit]}``, ``{}`` when the run is
    correct. ``compared``: every number compared beside its limit and
    its rule, the failed ones first: the ledger kept the first 13 of
    PR 48's 24 rows, and the one that had refused it was the 16th."""
    return {"not_ok": {r.name: [plain(r.value), r.limit]
                       for r in rows if not r.ok},
            "compared": {r.name: [plain(r.value), r.limit, r.how]
                         for r in sorted(rows, key=lambda r: r.ok)}}


def limits() -> Dict[str, float]:
    with open(os.path.join(os.path.dirname(__file__), "limits.json")) as f:
        return json.load(f)["limits"]


def _bytes(bits) -> np.ndarray:
    b = np.asarray(bits, np.uint8)
    return np.packbits(b[: b.size // 8 * 8].reshape(-1, 8), axis=1,
                       bitorder="little").reshape(-1)


class FrameReport(NamedTuple):
    attempted: int
    failed: int
    why: Dict[str, int]         # failure kind -> count
    matched: List               # (session, lap, j, Emitted), sound ones


def check_frames(emitted, laps, consumed: List[int]) -> FrameReport:
    """Every frame sent whose capture was consumed comes back exactly
    once, in order, with its rate, length, FCS and bytes; nothing comes
    back that was not sent. ``consumed[i]`` is the stream coordinate up
    to which session ``i``'s starts are owned by scanned chunks."""
    why: Dict[str, int] = {}
    seen = {}
    matched = []
    spurious = 0

    def fail(kind):
        why[kind] = why.get(kind, 0) + 1

    last_start = {}
    for em in emitted:
        i, fr = em.session, em.frame
        lap = laps[i]
        L = lap.stream.shape[0]
        if fr.start < last_start.get(i, -1):
            fail("out of order")
        last_start[i] = fr.start
        k, r = divmod(int(fr.start), L)
        if r > L - START_TOL:               # aligned just before a lap
            k, r = k + 1, r - L
        near = np.flatnonzero(np.abs(lap.starts - r) <= START_TOL)
        if near.size != 1:
            spurious += 1
            fail("not sent")
            continue
        j = int(near[0])
        key = (i, k, j)
        if key in seen:
            fail("delivered twice")
            continue
        res, psdu = fr.result, lap.psdus[j]
        if not (res.ok and res.rate_mbps == lap.rates[j]
                and res.length_bytes == psdu.size + 4):
            seen[key] = False
            fail("header")
        elif res.crc_ok is not True:
            seen[key] = False
            fail("FCS")
        elif not np.array_equal(_bytes(res.psdu_bits)[: psdu.size], psdu):
            seen[key] = False
            fail("bytes")
        else:
            seen[key] = True
            matched.append((i, k, j, em))
    expected = 0
    for i, lap in enumerate(laps):
        L = lap.stream.shape[0]
        for k in range(consumed[i] // L + 1):
            for j, s in enumerate(lap.starts):
                if k * L + int(s) + EDGE <= consumed[i]:
                    expected += 1
                    if (i, k, j) not in seen:
                        fail("missing")
                elif (i, k, j) in seen:
                    expected += 1
    failed = sum(why.values())
    return FrameReport(expected + spurious, failed, why, matched)


def check_reference(matched, laps, frame_len: int, seed: int,
                    n: int = 2) -> List[Compared]:
    """``n`` of the sound frames, their rates rotating with the seed,
    through the plain numpy receiver: same rate, length and bytes."""
    rng = np.random.default_rng([int(seed), 41])
    rates = sorted({laps[i].rates[j] for i, _k, j, _e in matched})
    bad = tried = 0
    for t in range(n if rates else 0):
        want = rates[(int(seed) + t) % len(rates)]
        pool = [m for m in matched if laps[m[0]].rates[m[2]] == want]
        i, _k, _j, em = pool[int(rng.integers(len(pool)))]
        cap = load.lap_slice(laps[i], int(em.frame.start), frame_len)
        got = ref.np_receive(cap)
        res = em.frame.result
        tried += 1
        if got is None or got.rate_mbps != res.rate_mbps \
                or got.length_bytes != res.length_bytes \
                or not np.array_equal(got.psdu, _bytes(res.psdu_bits)):
            bad += 1
    return [Compared("reference_captures_compared", tried, min(n, 1),
                     ">="),
            Compared("reference_disagreements", bad, 0)]


def check_hidden(stats, counters: Dict[str, int], dispatches: Dict[str, int],
                 compiles: int, cache_growth: int,
                 chunk_steps: int) -> List[Compared]:
    """Nothing was hidden: no overflow, degrade, quarantine, retry,
    fallback, rescan or compile inside the window, and at most two
    dispatches per chunk-step, all at the two served sites.
    ``dispatches`` are those of the ``chunk_steps`` the window
    launched, scan and decode: both loops drain the fleet before they
    open and before they hand the window back, so a decode is counted
    with the step it belongs to whichever call dispatched it, and a
    third dispatch in any step reads over 2.0 where every step
    decodes."""
    off_path = sum(n for s, n in dispatches.items() if s not in SITES)
    n_disp = sum(dispatches.get(s, 0) for s in SITES)
    rows = [Compared("overflow_chunks", stats.overflow_chunks, 0),
            Compared("degraded", int(bool(stats.degraded)), 0),
            Compared("quarantines", stats.quarantines, 0),
            Compared("sanitized", stats.sanitized, 0),
            Compared("lane_blowups", stats.lane_blowups, 0)]
    rows += [Compared(c, int(counters.get(c, 0)), 0)
             for c in RESILIENCE_COUNTERS]
    rows += [Compared("compiles_in_window", compiles, 0),
             Compared("jit_cache_growth_in_window", cache_growth, 0),
             Compared("dispatches_off_the_served_sites", off_path, 0),
             Compared("dispatches_per_chunk_step",
                      n_disp / max(1, chunk_steps), 2.0)]
    return rows


_CONTRACTION = re.compile(r"stablehlo\.(dot_general|dot|convolution)\b")


def loose_contractions(stablehlo_text: str) -> int:
    """Float contractions not lowered at HIGHEST precision (the check of
    tests/test_tpu_compile.py, copied): on a TPU such an op rounds its
    f32 operands to bfloat16."""
    return sum(1 for ln in stablehlo_text.splitlines()
               if _CONTRACTION.search(ln) and "f32" in ln
               and "HIGHEST" not in ln)


def owned_frames(host_step, outs, win_len: int):
    """(lane, row, capture from the frame's start as (n, 2) float64,
    samples available) for every owned, found frame of one chunk-step:
    the window the scan cut at its own start, from the host's copy of
    the step's samples."""
    _offs, active, arrs, _valid, _lo, _hi = host_step
    own, starts, found, fstart, nv = (np.asarray(outs[t])
                                      for t in (0, 1, 3, 4, 9))
    for i in active:
        for j in range(own.shape[1]):
            if not (own[i, j] and found[i, j]):
                continue
            s = int(starts[i, j])
            win = np.zeros((win_len, 2), np.float64)
            got = arrs[i, s: s + win_len]
            win[: got.shape[0]] = got
            yield i, j, win[int(fstart[i, j]):], \
                int(nv[i, j]) - int(fstart[i, j])


def float_gaps(host_step, outs, win_len: int, need_b: int):
    """The chunk scan's float outputs for one chunk-step of the window
    against float64: for every owned, found frame the widest gap of its
    CFO estimate from the plain LTS estimator's (rad/sample), and of its
    derotated segment from the same samples derotated in float64 by the
    program's own estimate, relative to the preamble's RMS. Returns
    (frames compared, eps gap, segment gap)."""
    eps, segs = np.asarray(outs[5]), None
    n = 0
    eps_gap = seg_gap = 0.0
    for i, j, cap, avail in owned_frames(host_step, outs, win_len):
        if segs is None:
            segs = np.asarray(outs[10])
        n += 1
        e = float(eps[i, j])
        eps_gap = max(eps_gap, abs(e - ref.lts_cfo(
            cap[:, 0] + 1j * cap[:, 1])))
        want = ref.derotate(cap, e, need_b, avail)
        rms = float(np.sqrt(np.mean(want[:400] ** 2) * 2.0))
        seg_gap = max(seg_gap, float(np.max(np.abs(
            segs[i, j].astype(np.float64) - want))) / rms)
    return n, eps_gap, seg_gap
