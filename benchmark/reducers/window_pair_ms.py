"""``span_pair_ms`` over the WHOLE measured window
(``harness/window_trace.py``): the median time, in milliseconds, from
the start of a span named ``first`` to the end of the span named
``last`` that carries the same value of ``key`` (the program's
``step``). A pair counts where both spans were kept and the profiled
interval does not lie between them; fewer than ``at_least`` give
nothing."""

import statistics

from ..harness import window_trace


def reduce(ctx, first: str, last: str, key: str = "step",
           at_least: int = 3):
    wt = window_trace.for_ctx(ctx)
    if wt is None:
        return None
    p_lo, p_hi = wt.profiled
    began = {s.args[key]: s.start for s in window_trace.named(wt, first)
             if key in s.args}
    pairs = [s.end - began[s.args[key]]
             for s in window_trace.named(wt, last)
             if s.args.get(key) in began
             and not (began[s.args[key]] < p_lo and s.end > p_hi)]
    if len(pairs) < at_least:
        return None
    return statistics.median(pairs) / 1e6
