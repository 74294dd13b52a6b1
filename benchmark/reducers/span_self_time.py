"""Self time of the annotation spans of one name that began inside the
profiled window: their duration less what the named child spans cover
of it (overlapping children counted once), per chunk-step (one
``rx.fleet.stack`` span each), in milliseconds. With no children it is
the spans' own time."""

from ..harness import annotations, xplane


def covered_ns(lo: float, hi: float, spans) -> float:
    """How much of [lo, hi) the spans cover, overlaps counted once."""
    return sum(b - a for a, b in xplane.union_ns(
        [(max(s.start, lo), min(s.end, hi)) for s in spans
         if s.end > lo and s.start < hi]))


def reduce(ctx, span: str, children=()):
    an = annotations.for_ctx(ctx)
    if an is None:
        return None
    lo, hi = an.window
    steps = annotations.chunk_steps(an)
    mine = [s for s in an.spans if s.name == span and lo <= s.start < hi]
    if not steps or not mine:
        return None
    kids = [s for s in an.spans if s.name in children]
    self_ns = sum((s.end - s.start)
                  - covered_ns(s.start, s.end, kids)
                  for s in mine)
    return self_ns / steps / 1e6
