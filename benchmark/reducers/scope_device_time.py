"""Device time of the ops that ran under a ``jax.named_scope`` matching
``pattern``, per run of the program of kind ``module`` (``scan`` or
``decode``), in milliseconds: self times (harness/annotations.py), so a
``while`` and its body are counted once; the median over the runs that
began inside the profiled window, because the profiler cuts the runs in
flight when it starts and stops. ``family`` matches every scope the
program names: what ran under none of them is the unscoped remainder,
printed beside the run's whole time in a ``[scopes]`` line, with how
much of the run was ops the compiler made, which have no scope of their
own and are charged to the op before them (``unnamed_ms``)."""

import re
import statistics

from ..harness import annotations


def reduce(ctx, pattern: str, family: str, module: str):
    an = annotations.for_ctx(ctx)
    if an is None or not an.runs[module]:
        return None
    want, named = re.compile(pattern), re.compile(family)
    runs = an.runs[module]
    mine = [sum(ns for scope, ns in r.by_scope.items()
                if want.search(scope)) for r in runs]
    if not any(mine):
        return None             # a program that names no such scope
    unscoped = [sum(ns for scope, ns in r.by_scope.items()
                    if not named.search(scope)) for r in runs]
    ms = statistics.median(mine) / 1e6
    print(f"[scopes] module={module} pattern={pattern} ms={ms:.3f} "
          f"unscoped_ms={statistics.median(unscoped) / 1e6:.3f} "
          f"unnamed_ms="
          f"{statistics.median(r.unnamed_ns for r in runs) / 1e6:.3f} "
          f"module_ms="
          f"{statistics.median(r.end - r.start for r in runs) / 1e6:.3f} "
          f"runs={len(runs)}", flush=True)
    return ms
