"""Device-idle time inside the profiled window while one of the named
annotation spans was the deepest one open on the main thread, per
chunk-step (one ``rx.fleet.stack`` span each), in milliseconds. Idle is
what ``harness/xplane.py`` calls idle: the gaps between device 0's busy
intervals, those over 50 us (a shorter one lies between two ops of one
program). A gap is cut at every span boundary inside it and each piece
goes to the span that started last among those open then, so the
pieces of all spans add up to the idle time and none is counted twice.
What went to other spans is printed, by span, in an ``[idle]`` line."""

from ..harness import annotations


def idle_by_span(an, busy):
    """{span name or '(no span open)': idle ns} over the window."""
    lo, hi = an.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a <= annotations.HOST_GAP_NS:
            continue
        inside = [s for s in an.spans if s.end > a and s.start < b]
        cuts = sorted({a, b} | {t for s in inside
                                for t in (s.start, s.end) if a < t < b})
        for c, d in zip(cuts, cuts[1:]):
            mid = 0.5 * (c + d)
            open_ = [s for s in inside if s.start <= mid < s.end]
            name = max(open_, key=lambda s: s.start).name if open_ \
                else "(no span open)"
            out[name] = out.get(name, 0.0) + (d - c)
    return out


def reduce(ctx, spans):
    an = annotations.for_ctx(ctx)
    if an is None:
        return None
    steps = annotations.chunk_steps(an)
    if not steps:
        return None             # a program without the fleet's spans
    by = idle_by_span(an, ctx.device.busy)
    ms = sum(by.get(n, 0.0) for n in spans) / steps / 1e6
    other = {n: round(v / steps / 1e6, 3) for n, v in sorted(
        by.items(), key=lambda kv: -kv[1]) if n not in spans}
    print(f"[idle] spans={','.join(spans)} ms_per_step={ms:.3f} "
          f"steps={steps} other_ms_per_step={other}", flush=True)
    return ms
