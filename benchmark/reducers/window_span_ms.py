"""Own time of the spans of one name over the WHOLE measured window
(``harness/window_trace.py``: the program's in-memory trace, the
profiled interval left out), in milliseconds: their duration less what
the named child spans cover of it. ``stat`` ``mean`` sums it and
divides by the chunk-steps of the window (``per`` ``step``: one
``rx.fleet.stack`` span each) or by its seconds (``per`` ``second``);
``median`` is the median span, for a span that comes once a chunk-step.
``empty`` is what a trace without the span reads (nothing, or 0 for a
span that records a pause)."""

import statistics

from ..harness import window_trace


def reduce(ctx, span: str, children=(), stat: str = "mean",
           per: str = "step", empty=None):
    wt = window_trace.for_ctx(ctx)
    if wt is None:
        return None
    mine = window_trace.named(wt, span)
    if not mine:
        return empty
    own = window_trace.own_ns(
        mine, [s for s in wt.spans if s.name in children])
    if stat == "median":
        return statistics.median(own) / 1e6
    if stat != "mean" or per not in ("step", "second"):
        raise ValueError(f"stat {stat!r} per {per!r}")
    over = wt.steps if per == "step" else wt.seconds
    return sum(own) / over / 1e6 if over else None
