"""Median device time of one kind of program run (``scan`` or ``decode``,
as harness/xplane.py tells them apart) inside the profiled window, in
milliseconds. The median, because the profiler cuts the runs in flight
when it starts and stops."""

import statistics


def reduce(ctx, module: str):
    tr = ctx.device
    if tr is None or not tr.modules[module]:
        return None
    return statistics.median(
        e.end - e.start for e in tr.modules[module]) / 1e6
