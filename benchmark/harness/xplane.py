"""The reduction from a JAX profiler trace (``*.xplane.pb``) to what the
per-layer readers and the result line need: device modules and ops,
busy and idle time, and idle gaps by what the host was doing.

Read with nothing but ``jax.profiler.ProfileData``. What the planes of a
TPU v5e trace hold was read by hand first (PERF.md, findings of PR 24):
device planes are named ``/device:TPU:<n>``; their line ``XLA Modules``
has one event per program run, named ``jit_<fn>(<fingerprint>)``; the
line ``XLA Ops`` has one event per HLO op, named with the op's HLO text
(a Pallas kernel reads ``%<kernel>.<n> = ... custom-call(...)``);
``Async XLA Ops`` has the copies. ``/host:CPU`` has one line per thread;
the main thread's carries ``TraceAnnotation`` names as they are and
python frames as ``$file.py:line function``. Host and device events
share one clock.

The two served programs both jit a function called ``f``. They are told
apart from this side: module runs are grouped by full name (the
fingerprint differs), ``decode`` is the group inside whose runs a
Viterbi kernel op ran, and ``scan`` is the busiest other group.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

KERNEL = re.compile(r"^%_(acs|traceback)_tiles\b")
#: host events an idle gap may be charged to: the benchmark's own
#: annotations and the program's spans (``serve.*``, ``rx.*``). Python
#: frames are not among them: ``$framebatch.py:1014 _ingest`` names a
#: line that moves with every edit, and sits deeper than the span that
#: says the same thing (PR 36; ROADMAP D13)
HOST_LABEL = re.compile(r"^(bench|serve|rx)\.")
WINDOW_SPAN = "bench.tick"


class Ev(NamedTuple):
    name: str
    start: float        # ns on the trace's clock
    end: float


class DeviceTrace(NamedTuple):
    window: Tuple[float, float]         # ns: first tick start, last end
    devices: int
    modules: Dict[str, List[Ev]]        # kind -> runs inside the window
    ops: List[Ev]                       # device 0's ops inside the window
    busy: List[List[float]]             # device 0's busy intervals there
    busy_s: float                       # mean over devices
    window_s: float
    host: List[Ev]                      # main-thread events of interest


def find_xplane(logdir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _events(line) -> List[Ev]:
    return [Ev(e.name, float(e.start_ns),
               float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def union_ns(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(evs: List[Ev], lo: float, hi: float):
    return [(max(e.start, lo), min(e.end, hi)) for e in evs
            if e.end > lo and e.start < hi]


def classify_modules(modules: List[Ev], ops: List[Ev]) -> Dict[str, List[Ev]]:
    """Group module runs by name; ``decode`` holds a Viterbi kernel op,
    ``scan`` is the busiest other group, the rest is ``other``."""
    groups: Dict[str, List[Ev]] = {}
    for m in modules:
        groups.setdefault(m.name, []).append(m)
    kernels = [o for o in ops if KERNEL.match(o.name)]
    decode = {name for name, runs in groups.items()
              if any(r.start <= k.start < r.end for r in runs
                     for k in kernels)}
    rest = sorted((n for n in groups if n not in decode),
                  key=lambda n: -sum(r.end - r.start for r in groups[n]))
    out: Dict[str, List[Ev]] = {"scan": [], "decode": [], "other": []}
    for name, runs in groups.items():
        kind = "decode" if name in decode else \
            "scan" if rest and name == rest[0] else "other"
        out[kind] += runs
    for runs in out.values():
        runs.sort(key=lambda e: e.start)
    return out


def read(path: str, need_device: bool = True) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev_planes, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            dev_planes.append((
                _events(lines["XLA Modules"]) if "XLA Modules" in lines
                else [],
                _events(lines["XLA Ops"]) if "XLA Ops" in lines else [],
                _events(lines["Async XLA Ops"])
                if "Async XLA Ops" in lines else []))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                evs = _events(ln)
                if any(e.name == WINDOW_SPAN for e in evs):
                    host = [e for e in evs if e.name == WINDOW_SPAN
                            or HOST_LABEL.match(e.name)]
    if not dev_planes and not need_device:      # a CPU rehearsal
        dev_planes = [([], [], [])]
    if not dev_planes:
        raise ValueError(f"{path}: no /device:TPU:<n> plane: nothing ran "
                         f"on a device while the profiler was on")
    ticks = [e for e in host if e.name == WINDOW_SPAN]
    if not ticks:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} annotation on any "
                         f"host line: the profiled window is unknown")
    lo, hi = min(e.start for e in ticks), max(e.end for e in ticks)
    unions = [union_ns(_clip(ops + async_ops, lo, hi))
              for _mods, ops, async_ops in dev_planes]
    busy = [sum(b - a for a, b in u) for u in unions]
    mods0, ops0, _ = dev_planes[0]
    inside = [m for m in mods0 if lo <= m.start < hi]
    ops_in = [o for o in ops0 if lo <= o.start < hi]
    return DeviceTrace(
        window=(lo, hi), devices=len(dev_planes),
        modules=classify_modules(inside, ops_in), ops=ops_in,
        busy=unions[0],
        busy_s=sum(busy) / len(busy) / 1e9, window_s=(hi - lo) / 1e9,
        host=sorted(host, key=lambda e: e.start))


def top_device_ops(tr: DeviceTrace, n: int = 10):
    """[[name, seconds], ...]: the ops that took most device time,
    largest first, named by the head of their HLO text (the op's own
    name and its result shape: ``%fusion.12 = f32[8,1,131135]``)."""
    by: Dict[str, float] = {}
    for o in tr.ops:
        key = o.name.split("{")[0][:96]
        by[key] = by.get(key, 0.0) + (o.end - o.start) / 1e9
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def _label(host: List[Ev], t: float) -> str:
    """The deepest host event of interest open at time ``t``."""
    best = None
    for e in host:
        if e.start > t:
            break
        if e.end >= t and e.name != WINDOW_SPAN \
                and (best is None or e.start >= best.start):
            best = e
    return best.name if best is not None else "(no span open)"


def idle_gaps(tr: DeviceTrace, n: int = 10):
    """[[host label, seconds], ...]: device-idle time inside the window,
    charged to the deepest host event open at each gap's middle, summed
    by label, longest first."""
    lo, hi = tr.window
    edges = [lo] + [x for iv in tr.busy for x in iv] + [hi]
    by: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        # between two ops of one program: not the host's doing
        lab = _label(tr.host, 0.5 * (a + b)) if b - a > 5e4 \
            else "(gaps under 50 us)"
        by[lab] = by.get(lab, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]
