"""Sum of one arg over the sum of another, over the annotation spans of
one name that began inside the profiled window, times ``scale``: a
ratio of two counts taken where the work happens."""

from ..harness import annotations


def reduce(ctx, span: str, numerator: str, denominator: str,
           scale: float = 1.0):
    an = annotations.for_ctx(ctx)
    if an is None:
        return None
    lo, hi = an.window
    mine = [s.args for s in an.spans if s.name == span
            and lo <= s.start < hi
            and numerator in s.args and denominator in s.args]
    den = sum(a[denominator] for a in mine)
    if not den:
        return None
    return scale * sum(a[numerator] for a in mine) / den
