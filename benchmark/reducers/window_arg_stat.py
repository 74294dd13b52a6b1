"""Mean or median (``stat``) of one arg over the spans of one name that
carry it, in the WHOLE measured window (``harness/window_trace.py``): a
level or a wait the program states where the work happens."""

import statistics

from ..harness import window_trace

STATS = {"mean": statistics.fmean, "median": statistics.median}


def reduce(ctx, span: str, arg: str, stat: str = "mean"):
    wt = window_trace.for_ctx(ctx)
    if wt is None:
        return None
    values = [s.args[arg] for s in window_trace.named(wt, span)
              if arg in s.args]
    return STATS[stat](values) if values else None
