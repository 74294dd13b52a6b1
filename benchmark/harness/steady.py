"""Where a run's spread comes from, from its own ticks, and the two
measures of a set's spread: arithmetic only, shared by every run's
``[steady]`` line, the bounds' derivation (``bounds.py``) and their tests.

PR 36: since the tick is 26 ms and two fifths of it the host's, a
cell's runs differ by a per-process offset (the runs' median ticks
spread), by the time spent in ticks far longer than the rest
(collections, stalls), or by a cold start (the first half of the window
against the second). A longer window cures only the second.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence


def tick_summary(step_ms: Sequence[float]) -> Dict[str, float]:
    """Of one run's ``bench.step`` durations in window order: the
    median, the share of their time in steps longer than twice the
    median, and the mean of the first half over the second's."""
    if len(step_ms) < 4:
        return {}
    med = statistics.median(step_ms)
    half = len(step_ms) // 2
    return {"tick_median_ms": med,
            "long_tick_share": sum(x for x in step_ms if x > 2 * med)
            / sum(step_ms),
            "first_over_second": statistics.fmean(step_ms[:half])
            / statistics.fmean(step_ms[half:])}


def quartile_spread(values: Sequence[float]) -> float:
    """The distance between the first and the third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, over the median:
    the spread the benchmark's contract sets bounds from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values: Sequence[float]) -> List[float]:
    """The set with the run farthest from its median left out, as the
    driver's check leaves it out of a set before it holds the spread
    against half a bound: one far-off run (a stall) does no harm."""
    med = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - med))[:-1]


def trimmed_range(values: Sequence[float]) -> float:
    """(max - min) over the median once the run farthest from the
    median is left out: the spread the driver's notes hold against
    half a bound (ISSUE 36, D)."""
    kept = without_farthest(values)
    return (max(kept) - min(kept)) / statistics.median(kept)


def time_weighted_median(durations: Sequence[float]) -> float:
    """The duration such that half of all the time lies in shorter
    ones: the step a moment spent stepping most likely falls in. In the
    open loop most ``step()`` calls launch nothing and return at once,
    so their plain median says nothing of how long a blocking one
    keeps the loop's one thread from its slabs."""
    d = sorted(durations)
    half, run = 0.5 * sum(d), 0.0
    for x in d:
        run += x
        if run >= half:
            return x
    return 0.0
