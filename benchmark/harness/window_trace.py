"""The program's own trace of the WHOLE measured window, for the readers
that want every chunk-step of it and not the five the profiler catches.

In a traced run ``cell.py`` opens ``telemetry.tracing(annotate_device=
True)`` around the loop, so the program keeps an in-memory ``Trace`` of
every ``serve.*`` and ``rx.*`` span of all 30 s, with ``step`` ids and
``args``, on ``time.perf_counter``: the clock of ``Reduction.window``.
The program hands it over as ``telemetry.last_trace()`` (found the way
``annotations.for_ctx`` finds the profiler's file: in a place it knows,
not in ``ctx``), and ``Trace.epoch`` says where its ``ts`` start.

One clock with the device trace. Every span is also a
``TraceAnnotation`` on the profiler's clock while the profiler runs, so
the spans that are in both, matched on (name, ``step``), give the
offset between the two clocks; where they disagree on it by more than
``CLOCK_TOLERANCE_NS`` nothing is reported. The offset lays the
profiled interval (``ctx.device.window``) on the window's clock, and
the spans that overlap it, widened by a median tick on both sides, are
left out: there the python tracer slows the host, which is what the
whole-window readings are meant to be free of.

Needs no device op, so a CPU rehearsal (``--rehearse --trace 1``)
reports too. A program without ``last_trace`` (the parent of the PR
that added it) gives None, and every reader on this module nothing.
"""

from __future__ import annotations

import bisect
import statistics
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import annotations, xplane
from .annotations import Span

#: matched spans may disagree on the offset between the two clocks by
#: this much: a thirtieth of the shortest tick. The two clock reads of
#: a span are adjacent statements, and lie tens of microseconds apart
#: under the python tracer
CLOCK_TOLERANCE_NS = 5e5
#: one of these a chunk-step, as in ``annotations.chunk_steps``
STEP_SPAN = "rx.fleet.stack"


class WindowTrace(NamedTuple):
    window: Tuple[float, float]     # ns on perf_counter, as ctx.window
    profiled: Tuple[float, float]   # the interval left out, same clock
    spans: List[Span]               # every span of the trace, by start
    kept: List[Span]                # began in the window, outside profiled
    steps: int                      # chunk-steps among ``kept``
    seconds: float                  # the window less the part left out
    residual_ns: float              # widest disagreement of the matches
    matched: int


def trace_spans(trace) -> List[Span]:
    """A ``telemetry.Trace``'s complete events as ``Span``s on
    perf_counter nanoseconds, by start."""
    base = trace.epoch * 1e9
    out = [Span(e["name"], base + e["ts"] * 1e3,
                base + (e["ts"] + e["dur"]) * 1e3, e.get("args") or {})
           for e in trace.events() if e.get("ph") == "X"]
    out.sort(key=lambda s: s.start)
    return out


def _by_key(spans: Sequence[Span]) -> Dict[tuple, Span]:
    """(name, step) -> span, for the keys that occur once."""
    seen: Dict[tuple, Optional[Span]] = {}
    for s in spans:
        if "step" in s.args:
            key = (s.name, s.args["step"])
            seen[key] = None if key in seen else s
    return {k: s for k, s in seen.items() if s is not None}


def clock_offset(mine: Sequence[Span], profiled: Sequence[Span]
                 ) -> Optional[Tuple[float, float, int]]:
    """(offset, residual, pairs): nanoseconds to ADD to a perf_counter
    time for the profiler's clock (the median over the spans both sides
    hold once under one (name, ``step``), taken at their starts), and
    how far the farthest pair lies from it. None with nothing
    matched."""
    a, b = _by_key(mine), _by_key(profiled)
    diffs = [b[k].start - a[k].start for k in a if k in b]
    if not diffs:
        return None
    off = statistics.median(diffs)
    return off, max(abs(d - off) for d in diffs), len(diffs)


def build(trace, window_s: Tuple[float, float],
          profile_spans: Sequence[Span],
          profile_window: Tuple[float, float],
          tick_ns: float) -> Optional[WindowTrace]:
    """``window_s`` is ``ctx.window`` (seconds), ``profile_window`` the
    profiled interval on the profiler's clock (ns), ``tick_ns`` what it
    is widened by on both sides."""
    spans = trace_spans(trace)
    lo, hi = window_s[0] * 1e9, window_s[1] * 1e9
    inside = [s for s in spans if lo <= s.start < hi]
    if not inside:
        return None             # another run's trace
    found = clock_offset(inside, profile_spans)
    if found is None:
        print("window_trace: no span of the window is in the profile "
              "too: the two clocks cannot be laid together",
              file=sys.stderr)
        return None
    off, residual, pairs = found
    if residual > CLOCK_TOLERANCE_NS:
        print(f"window_trace: {pairs} matched spans disagree on the "
              f"clock offset by {residual / 1e6:.3f} ms, over "
              f"{CLOCK_TOLERANCE_NS / 1e6} ms: nothing reported",
              file=sys.stderr)
        return None
    p_lo = profile_window[0] - off - tick_ns
    p_hi = profile_window[1] - off + tick_ns
    kept = [s for s in inside if s.end <= p_lo or s.start >= p_hi]
    left_out = max(0.0, min(p_hi, hi) - max(p_lo, lo))
    return WindowTrace((lo, hi), (p_lo, p_hi), spans, kept,
                       sum(1 for s in kept if s.name == STEP_SPAN),
                       (hi - lo - left_out) / 1e9, residual, pairs)


def profile_spans(ctx) -> List[Span]:
    """The annotation spans of the run's profile, with their stats."""
    an = annotations.for_ctx(ctx)
    if an is not None:
        return an.spans
    path = xplane.find_xplane(annotations.TRACE_DIR)
    return annotations.host_spans(path) if path else []


_LOADED: Dict[tuple, Optional[WindowTrace]] = {}


def for_ctx(ctx) -> Optional[WindowTrace]:
    """The window trace of the run ``ctx`` is of, built once; None
    where the program keeps none, where ``ctx`` has no profile to leave
    out, or where the clock check fails (one line on standard error)."""
    try:
        from ziria_tpu.utils import telemetry
    except ImportError:
        return None
    last = getattr(telemetry, "last_trace", None)
    trace = last() if last is not None else None
    if getattr(trace, "epoch", None) is None or ctx.device is None:
        return None
    key = (id(trace), tuple(ctx.window))
    if key not in _LOADED:
        _LOADED.clear()
        ticks = ctx.spans.durations(xplane.WINDOW_SPAN, *ctx.window)
        wt = build(trace, ctx.window, profile_spans(ctx),
                   ctx.device.window,
                   1e9 * statistics.median(ticks) if ticks else 0.0)
        if wt is not None:
            whole = (wt.window[1] - wt.window[0]) / 1e9
            print(f"[window_trace] chunk_steps={wt.steps} "
                  f"spans={len(wt.kept)} seconds={wt.seconds:.3f} "
                  f"left_out_s={whole - wt.seconds:.3f} "
                  f"matched={wt.matched} "
                  f"clock_residual_ms={wt.residual_ns / 1e6:.4f}",
                  flush=True)
        _LOADED[key] = wt
    return _LOADED[key]


# ------------------------------------------- what the readers share


def named(wt: WindowTrace, name: str) -> List[Span]:
    return [s for s in wt.kept if s.name == name]


def own_ns(parents: Sequence[Span], kids: Sequence[Span]) -> List[float]:
    """Each parent's duration less what ``kids`` cover of it
    (overlapping kids counted once)."""
    kids = sorted(kids, key=lambda s: s.start)
    starts = [k.start for k in kids]
    reach, far = [], float("-inf")      # the farthest end so far
    for k in kids:
        far = max(far, k.end)
        reach.append(far)
    out = []
    for p in parents:
        j = bisect.bisect_left(starts, p.end)
        i = j
        while i > 0 and reach[i - 1] > p.start:
            i -= 1
        covered = sum(b - a for a, b in xplane.union_ns(
            [(max(k.start, p.start), min(k.end, p.end))
             for k in kids[i:j] if k.end > p.start]))
        out.append((p.end - p.start) - covered)
    return out

