"""Summed device time of the ops whose name matches ``pattern`` (a
Pallas kernel shows as ``%<kernel>.<n> = ... custom-call(...)``) per run
of the program of kind ``calls`` inside the profiled window, in
milliseconds."""

import re


def kernel_seconds(tr, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(o.end - o.start for o in tr.ops if rx.search(o.name)) / 1e9


def reduce(ctx, pattern: str, calls: str):
    tr = ctx.device
    if tr is None or not tr.modules[calls]:
        return None
    s = kernel_seconds(tr, pattern)
    return 1e3 * s / len(tr.modules[calls]) if s else None
