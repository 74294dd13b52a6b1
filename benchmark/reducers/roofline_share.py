"""A kernel's share of its roofline, in percent: the least time the
chip could take for the bytes the algorithm needs (``bytes`` names the
counter, from harness/counts.py; ``peak`` the entry of harness/peaks.py)
over the kernel's device time per call. Bytes bound it: the kernel does
a few dozen adds and compares per byte it moves."""

from .kernel_time import kernel_seconds


def reduce(ctx, pattern: str, bytes: str, peak: str, calls: str):
    tr = ctx.device
    if tr is None or not ctx.peaks:
        return None
    s = kernel_seconds(tr, pattern)
    n = len(tr.modules[calls])
    if not s or not n:
        return None
    return 100.0 * (ctx.counters[bytes] / ctx.peaks[peak]) / (s / n)
