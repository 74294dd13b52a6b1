"""Sum of one arg over the sum of another, over the spans of one name
in the WHOLE measured window (``harness/window_trace.py``), times
``scale``: ``span_arg_ratio`` with every chunk-step under it."""

from ..harness import window_trace


def reduce(ctx, span: str, numerator: str, denominator: str,
           scale: float = 1.0):
    wt = window_trace.for_ctx(ctx)
    if wt is None:
        return None
    mine = [s.args for s in window_trace.named(wt, span)
            if numerator in s.args and denominator in s.args]
    den = sum(a[denominator] for a in mine)
    if not den:
        return None
    return scale * sum(a[numerator] for a in mine) / den
