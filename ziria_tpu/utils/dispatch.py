"""Device-dispatch observability: count the compiled calls a code
path fires, and the compile-cache growth it causes.

The frame-batching work lives and dies by TWO integers the profiler
does not hand you: how many *device dispatches* a receive path costs
(each one pays the host link round trip — ~68 ms on the remote link
the first rounds measured through) and how many *fresh compiles* it
triggered (tens of seconds each on first contact). This module gives
both a first-class seam:

- :func:`count_dispatches` — a context manager; every instrumented
  call site inside the ``with`` block increments a labelled counter.
  Sites are instrumented explicitly with :func:`record` (the same
  own-call-site discipline as ``backend.chunked.STATS`` — JAX has no
  stable public hook for "a compiled program ran", so we count where
  WE launch device work; eager jnp call sites count as one dispatch
  however many primitives they fan into, making every reported bound
  a LOWER bound on real device calls). Sites wrapped with
  :func:`timed` additionally accumulate per-site *wall time*
  (``DispatchCount.times``, seconds): the host-side time spent in the
  instrumented call — dispatch plus any blocking the call does. On a
  synchronous backend (CPU) that is the stage's real wall time; on an
  async one it is a lower bound (the dispatch tax itself), which is
  exactly the number the host-link analyses need.
- :func:`cache_growth` — lru-delta measurement for the jit-factory
  caches (``rx._jit_decode_data_mixed`` etc.): the compile-count
  proxy `tests/test_rx_mixed_dispatch.py` used to hand-roll. Deltas,
  never ``cache_clear`` — the caches are process-wide shared state.

Both are reentrant and thread-safe: nested/overlapping counters each
see every event recorded while they are active (frame threads under
``framebatch.run_many`` all report into the same active counters).
Each :class:`DispatchCount` owns its OWN lock — concurrent
instrumented sites (the double-buffered streaming loop, ``run_many``
frame threads) update counters without contending on one global
mutex; the module lock only guards (de)activation.

The sites are also the emission points of the runtime telemetry layer
(:mod:`ziria_tpu.utils.telemetry`): when a trace or metrics registry
is active, :func:`timed` records a span plus a latency-histogram
observation, :func:`record` a labelled counter increment, and
:func:`record_gauge` a time-series gauge sample and a trace
counter-track point — so every instrumented surface gets
distribution-level (p50/p99) latency and plottable gauge levels with
no changes at the call sites. All of it stays free when nothing is
active (the same one-truthiness-check fast path).

The module also owns the *dispatch geometry* helpers every batched
path shares (:func:`pow2_ceil`, :func:`pow2_bucket`,
:func:`pad_lanes`): lane counts and padded sizes round up to powers
of two so XLA compiles O(log N) batch variants, not one per size —
the single padding rule behind the O(log buckets) compile-count
contracts the counters above measure. They were hoisted here from
three drifting copies (``backend/framebatch``, ``rx.acquire_many``,
and the TX batch path).
"""

from __future__ import annotations

import threading
import time
from collections import Counter
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

from ziria_tpu.utils import telemetry as _tm

_LOCK = threading.Lock()          # guards _ACTIVE mutation only
_ACTIVE: List["DispatchCount"] = []


def _idle() -> bool:
    """True when no counter, trace, or registry is collecting — the
    one check every emitter's disabled fast path takes."""
    return not (_ACTIVE or _tm._TRACES or _tm._REGISTRIES)


# ------------------------------------------------------ dispatch geometry


def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (and >= 1)."""
    return 1 << max(0, (int(n) - 1).bit_length())


def pow2_bucket(n: int, min_bucket: int) -> int:
    """Power-of-two size bucket with a floor: the one padding formula
    every batched path uses (symbol buckets floor at 4, capture
    buckets at 512, TX bit buckets at 128) so tiny inputs share one
    compile class instead of fragmenting the jit caches."""
    return max(int(min_bucket), pow2_ceil(n))


def pad_lanes(lanes: Sequence) -> list:
    """Pad a non-empty lane list to the next power-of-two count by
    repeating lane 0 — the shared lane-count rule of every vmapped
    batch here (XLA compiles O(log N) lane-count variants; repeated
    lane 0 is discarded by the caller, which only reads the first
    ``len(lanes)`` results)."""
    lanes = list(lanes)
    return lanes + [lanes[0]] * (pow2_ceil(len(lanes)) - len(lanes))


class DispatchCount:
    """Labelled dispatch tally filled in by :func:`record` while its
    :func:`count_dispatches` block is active. ``counts`` holds the
    per-site dispatch counts; ``times`` the per-site accumulated wall
    seconds from :func:`timed` sites (sites instrumented with bare
    :func:`record` contribute counts only); ``gauges`` the per-label
    high-water marks from :func:`record_gauge` sites (e.g. the
    streaming receiver's in-flight chunk depth — a *level*, not an
    event count, so it maxes rather than sums). Updates go through the
    instance's OWN lock, so two counters active at once (or many
    threads reporting into one) never serialize on a shared mutex."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counts: Counter = Counter()
        self.times: Counter = Counter()      # label -> wall seconds
        self.gauges: Dict[str, float] = {}   # label -> max level seen

    def _add(self, label: str, n: int, seconds: Optional[float]) -> None:
        with self._lock:
            self.counts[label] += n
            if seconds is not None:
                self.times[label] += seconds

    def _gauge(self, label: str, value: float) -> None:
        with self._lock:
            if value > self.gauges.get(label, float("-inf")):
                self.gauges[label] = value

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    @property
    def total_time(self) -> float:
        return float(sum(self.times.values()))

    def times_ms(self) -> Dict[str, float]:
        """Per-site wall time in milliseconds, rounded for reports."""
        return {k: round(v * 1e3, 3) for k, v in sorted(
            self.times.items())}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        inner = ", ".join(f"{k}={v}" for k, v in sorted(
            self.counts.items()))
        return f"DispatchCount(total={self.total}, {inner})"


def record(label: str = "dispatch", n: int = 1,
           seconds: Optional[float] = None) -> None:
    """Report ``n`` device dispatches at an instrumented call site,
    optionally with the wall time the call took (``seconds``; the
    :func:`timed` wrapper measures and passes it). Also increments the
    per-site dispatch counter (and, when timed, the latency histogram)
    of every active telemetry registry.

    Free when nothing is collecting (one truthiness check), so the
    hot paths carry their instrumentation permanently. Active counters
    update under their own per-instance locks — no shared mutex on
    the instrumented fast path (``tuple(_ACTIVE)`` is an atomic
    snapshot under the GIL).
    """
    if _idle():
        return
    for c in tuple(_ACTIVE):
        c._add(label, n, seconds)
    if _tm._REGISTRIES:
        _tm.dispatch_event(label, n, seconds)


def record_gauge(label: str, value: float) -> None:
    """Report the current *level* of an instrumented quantity (the
    streaming receiver's in-flight dispatch depth). Active counters
    keep the maximum level observed, so ``d.gauges["..."]`` after a
    :func:`count_dispatches` block is the high-water mark — the number
    that shows whether double-buffered overlap actually overlapped.
    Active telemetry sinks additionally get EVERY sample: a
    time-series point per registry and a counter-track event per trace
    — the level over time, so a chart shows *how long* the level was
    sustained, not just that it was reached.
    Free when nothing is collecting (one truthiness check)."""
    if _idle():
        return
    for c in tuple(_ACTIVE):
        c._gauge(label, value)
    _tm.gauge_sample(label, value)


@contextmanager
def timed(label: str = "dispatch", args: Optional[dict] = None):
    """``with timed("rx.sync"): ...`` — record ONE dispatch at the
    site plus the wall time of the block. The preferred form for
    instrumented call sites: dispatch *time*, not just count, becomes
    observable per stage (`tools/rx_dispatch_bench.py` stats blocks
    report both). With telemetry active the block is additionally a
    trace span (carrying ``args``, as `telemetry.span` does: the served
    path's two dispatches give their chunk-step's id) and a
    latency-histogram observation — p50/p99 per site for free.
    Near-free when nothing is collecting (one truthiness check)."""
    if _idle():
        yield
        return
    with _tm.span(label, args):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            record(label, seconds=time.perf_counter() - t0)


@contextmanager
def count_dispatches():
    """``with count_dispatches() as d:`` — afterwards ``d.total`` is
    the number of instrumented device dispatches the block performed
    and ``d.counts`` the per-label breakdown."""
    c = DispatchCount()
    with _LOCK:
        _ACTIVE.append(c)
    try:
        yield c
    finally:
        with _LOCK:
            _ACTIVE.remove(c)


class CacheGrowth:
    """Per-cache ``currsize`` deltas captured on context exit. With
    telemetry active, nonzero deltas are reported as compile events
    (`telemetry.record_compile`) — fresh jit-factory entries show up
    in the trace as compile markers instead of masquerading as slow
    dispatches."""

    def __init__(self, caches: Tuple) -> None:
        self._caches = caches
        self._before = [c.cache_info().currsize for c in caches]
        self.growth: Dict = {}

    def _finish(self) -> None:
        self.growth = {
            c: c.cache_info().currsize - b
            for c, b in zip(self._caches, self._before)}
        if _tm.active():
            for c, g in self.growth.items():
                if g:
                    name = getattr(c, "__name__", None) or repr(c)
                    _tm.record_compile(f"cache_growth:{name}", n=g,
                                       args={"new_entries": g})

    @property
    def total(self) -> int:
        return sum(self.growth.values())

    def __getitem__(self, cache) -> int:
        return self.growth[cache]


@contextmanager
def cache_growth(*caches):
    """``with cache_growth(rx._jit_decode_data_mixed) as g:`` — after
    the block, ``g[cache]`` / ``g.total`` give how many NEW entries
    (fresh compiled callables) the block added to each ``lru_cache``.
    Measures deltas without ever clearing: safe inside a shared-cache
    process (a full pytest run, an embedder)."""
    g = CacheGrowth(caches)
    try:
        yield g
    finally:
        g._finish()


@contextmanager
def no_recompile(*caches):
    """``with no_recompile(rx._jit_stream_chunk_multi): ...`` — assert
    the block added ZERO entries to each jit-factory ``lru_cache``: the
    runtime twin of the jaxlint R1 cache-key rule
    (docs/static_analysis.md). The static rule proves every knob IS in
    the key; this proves a steady-state path never mints a fresh key —
    i.e. re-dispatches compiled programs instead of recompiling.
    Raises AssertionError naming the grown caches on a clean exit; an
    exception from the block propagates unmasked (growth is not
    checked — the block didn't finish its steady state)."""
    with cache_growth(*caches) as g:
        yield g
    # only reached on a clean block exit: an exception propagates
    # through the yield and skips the growth assertion
    grown = {}
    for c, n in g.growth.items():
        if n:
            name = getattr(c, "__name__", None) or repr(c)
            mod = getattr(c, "__module__", None)
            grown[f"{mod}.{name}" if mod else name] = n
    if grown:
        raise AssertionError(
            f"no_recompile: block minted fresh compile-cache entries "
            f"{grown} — a knob or geometry is reaching the jit "
            f"factory without riding its cache key")
