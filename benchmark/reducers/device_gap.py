"""Mean device-idle gap, in milliseconds, from the end of a program run
of kind ``after`` to the start of the next run, when that next run is
of kind ``before``: ``scan`` -> ``decode`` is what the host's classify
holds the device back by, ``any`` -> ``scan`` is the host's turnaround
between chunk-steps."""


def reduce(ctx, after: str, before: str):
    tr = ctx.device
    if tr is None:
        return None
    runs = sorted(((e.start, e.end, kind)
                   for kind, evs in tr.modules.items() for e in evs))
    gaps = [max(0.0, b[0] - a[1]) for a, b in zip(runs, runs[1:])
            if after in ("any", a[2]) and before == b[2]]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
