"""Seconds spent in spans of one name inside the window, per unit of a
counter (``per``), in milliseconds."""


def reduce(ctx, span: str, per: str):
    d = ctx.spans.durations(span, *ctx.window)
    n = ctx.counters.get(per, 0)
    if not d or not n:
        return None
    return 1e3 * sum(d) / n
