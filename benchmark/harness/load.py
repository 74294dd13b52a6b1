"""The one general traffic generator: seeded payloads, one lap of
waveform per session from the program's transmitter, and the cutting of
a replayed lap into slabs with due times.

A configuration file's ``population`` and ``channel`` and a traffic
file's parameters are all it reads; a new mix or configuration is a new
data file. The payload draw, the gaps, the lead, the slab sizes and the
phases are the benchmark's own; only the waveform (TX, CFO, AWGN) comes
from the program (``link.stream_many``), which PERF.md lists as an open
question.
"""

from __future__ import annotations

import bisect
from typing import List, NamedTuple

import numpy as np

from . import counts


class Lap(NamedTuple):
    """One session's replayed stream."""
    stream: np.ndarray      # (lap_samples, 2) float32
    starts: np.ndarray      # true frame starts inside the lap
    rates: List[int]        # Mbit/s per frame
    psdus: List[np.ndarray]  # payload bytes per frame, FCS excluded


def _session_seed(seed: int, i: int) -> int:
    return (int(seed) * 1000003 + 7919 * (i + 1)) % (2 ** 31 - 1)


def plan_lap(pop: dict, seed: int, i: int):
    """Everything about session ``i``'s lap that needs no transmitter:
    rates, payloads, lead, gaps, starts and the tail that pads the lap
    to ``lap_samples``. The same sizes for every seed, in the same
    cyclic order; the seed moves payload bytes, gaps and lead."""
    rng = np.random.default_rng([int(seed), i, 17])
    rates_all, sizes = pop["rates_mbps"], pop["psdu_bytes"]
    n = pop["frames_per_lap"]
    rates = [rates_all[(i + j) % len(rates_all)] for j in range(n)]
    nbytes = [sizes[(i + j) % len(sizes)] for j in range(n)]
    psdus = [rng.integers(0, 256, b).astype(np.uint8) for b in nbytes]
    lo, hi = pop["lead_samples"]
    if pop.get("lead_draw") == "slots":
        # the same set of phases for every seed, dealt out in another
        # order: session i leads by slot perm[i] of `sessions` even ones
        n_s = pop["lead_slots"]
        perm = np.random.default_rng([int(seed), 19]).permutation(n_s)
        lead = lo + int(perm[i % n_s]) * ((hi - lo) // n_s)
    else:
        lead = int(rng.integers(lo, hi))
    glo, ghi = pop["gap_samples"]
    gaps = rng.integers(glo, ghi, size=max(n - 1, 0))
    fcs = 4 if pop["add_fcs"] else 0
    lens = [counts.frame_samples(b + fcs, m) for b, m in zip(nbytes, rates)]
    starts, pos = [], lead
    for j, ln in enumerate(lens):
        starts.append(pos)
        pos += ln + (int(gaps[j]) if j < n - 1 else 0)
    tail = pop["lap_samples"] - pos
    if tail < glo:
        raise ValueError(f"lap_samples {pop['lap_samples']} leaves a "
                         f"tail of {tail} < {glo} after session {i}'s "
                         f"frames")
    return rates, psdus, lead, gaps, np.asarray(starts, np.int64), tail


def synth_laps(cfg: dict, seed: int) -> List[Lap]:
    """One lap per session through the program's transmitter and
    channel. Every session's lap has the same shape for every seed, so
    the programs synthesis compiles are in the cache after a first
    run."""
    from ziria_tpu.phy import link

    pop, chan = cfg["population"], cfg["channel"]
    laps = []
    for i in range(cfg["sessions"]):
        rates, psdus, lead, gaps, starts, tail = plan_lap(pop, seed, i)
        stream, true_starts = link.stream_many(
            psdus, rates, gaps=gaps, snr_db=chan["snr_db"],
            cfo=chan["cfo_rad_per_sample"], delay=lead,
            seed=_session_seed(seed, i), add_fcs=pop["add_fcs"],
            tail=tail, channel_profile="flat")
        if stream.shape[0] != pop["lap_samples"] \
                or not np.array_equal(true_starts, starts):
            raise RuntimeError(
                f"session {i}: the transmitter's lap ({stream.shape[0]} "
                f"samples, starts {true_starts[:3]}...) is not the "
                f"planned one ({pop['lap_samples']}, {starts[:3]}...)")
        laps.append(Lap(np.ascontiguousarray(stream, np.float32), starts,
                        rates, psdus))
    return laps


def lap_slice(lap: Lap, pos: int, n: int) -> np.ndarray:
    """Samples [pos, pos + n) of the lap replayed end to end."""
    L = lap.stream.shape[0]
    a = pos % L
    if a + n <= L:
        return lap.stream[a: a + n]
    parts, left = [], n
    while left:
        take = min(left, L - a)
        parts.append(lap.stream[a: a + take])
        left -= take
        a = 0
    return np.concatenate(parts)


def phases(seed: int, n: int, period_s: float) -> List[float]:
    """When each of ``n`` sessions' streams begins: the n even parts of
    one fill period (a stride at a session's rate), dealt to the
    sessions by the seed, as ``wifi-a-beacon-8s`` deals its beacons.
    Every seed then sees the same interleaving of lane fills, with
    other payloads and slab cuts. Until PR 36 each phase was seeded
    uniform within the period, and the delay's median followed the
    draw: 66-126 ms by the seed at a launch a fill, and still 2.8%
    across six seeds against 1.5% at 9.6 M samples/s."""
    perm = np.random.default_rng([int(seed), 31]).permutation(n)
    return [float(j) * period_s / n for j in perm]


class Arrivals:
    """Open-loop arrivals of one session: slab sizes seeded uniform in
    [slab_lo, slab_hi), slab k due at ``phase_s + samples_before_k /
    rate``. Generated lazily (the window's length is not known to it)
    and remembered, so that the due time of any sample already handed
    out can be looked up."""

    def __init__(self, seed: int, i: int, slab_lo: int, slab_hi: int,
                 rate: float, phase_s: float):
        self._rng = np.random.default_rng([int(seed), i, 29])
        self._lo, self._hi = int(slab_lo), int(slab_hi)
        self.rate = float(rate)
        self.phase = float(phase_s)
        self.first = [0]        # first sample of slab k
        self.size = []

    def _grow(self) -> None:
        k = int(self._rng.integers(self._lo, self._hi))
        self.size.append(k)
        self.first.append(self.first[-1] + k)

    def slab(self, k: int):
        """(first sample, size, due time) of slab ``k``."""
        while len(self.size) <= k:
            self._grow()
        return self.first[k], self.size[k], \
            self.phase + self.first[k] / self.rate

    def due_of_sample(self, s: int) -> float:
        """Due time of the slab carrying sample ``s``."""
        while self.first[-1] <= s:
            self._grow()
        k = bisect.bisect_right(self.first, s) - 1
        return self.phase + self.first[k] / self.rate
