"""Median time, in milliseconds, from the start of a span named
``first`` to the end of the span named ``last`` that carries the same
value of ``key`` (the program's ``step``: a chunk-step is launched in
one tick and drained in the next). Only pairs wholly inside the
profiled window count, and fewer than ``at_least`` give nothing: a
pair that straddles the window's edge was cut by the profiler."""

import statistics

from ..harness import annotations


def reduce(ctx, first: str, last: str, key: str = "step",
           at_least: int = 3):
    an = annotations.for_ctx(ctx)
    if an is None:
        return None
    lo, hi = an.window
    inside = [s for s in an.spans
              if key in s.args and lo <= s.start and s.end <= hi]
    began = {s.args[key]: s.start for s in inside if s.name == first}
    pairs = [s.end - began[s.args[key]] for s in inside
             if s.name == last and s.args[key] in began]
    if len(pairs) < at_least:
        return None
    return statistics.median(pairs) / 1e6
