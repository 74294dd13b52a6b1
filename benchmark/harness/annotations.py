"""What ``xplane.read`` leaves out of a profiler trace, for the readers
of the program's own tracing: the main thread's annotation events WITH
their stats, and device 0's op events with the scope they ran under.

Annotations. ``telemetry.span(name, args)`` builds
``TraceAnnotation(name, **args)``; the profile holds ``args`` as the
event's stats, which ``jax.profiler.ProfileData`` gives (an int comes
back an int, a float a float, a string a string), and the event's name
stays clean. Taken from the host line that holds ``bench.tick``: the
benchmark's own spans (``bench.*``) and the program's (``rx.*``,
``serve.*``).

Scopes. What a TPU v5e trace holds for an op's scope was read by hand
(PR 25, on the traces PR 24 left and on this PR's): the events of
``XLA Ops`` carry ``device_offset_ps``, ``device_duration_ps`` and
nothing else, but the plane's event METADATA carries, per HLO
instruction, ``tf_op`` (the instruction's ``op_name``, as
``jit(stream_chunk_multi)/vmap(rx.scan.locate)/.../conv_general_dilated:``;
a fusion has its root's), ``program_id`` (the number in the module
event's name), ``hlo_category``, ``flops``, ``bytes_accessed`` and
``source``. ``ProfileData`` does not give event metadata, so that one
map is read from the file's protobuf wire format (``XSpace.planes[]
.event_metadata`` and ``.stat_metadata``; tsl/profiler/protobuf/
xplane.proto) with the few lines below. Metadata names repeat across
the two programs (``%fusion.67`` is in both), so an op is looked up by
(program of the module run it started in, name). A ``while`` has no
``tf_op``; its body's ops lie inside it on the same line and have
one, so an op with none takes the scope most of the time nested in it
ran under, and ``self_ns`` is an op's time less what is nested in it:
self times add up to device time with nothing counted twice. Ops the
compiler made itself (layout copies, ``copy-done``, a conversion fused
with a slice: 0.1% of the scan and 2.2% of the decode at MTU width)
have no ``tf_op`` and no ``source`` and nothing nested in them; each is
charged to the scope of the op that ran just before it in the same
program run, and keeps ``named`` false so that a reader can say how
much was charged that way.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from . import manifest, xplane

#: where harness/cell.py has the profiler write
TRACE_DIR = os.path.join(manifest.ROOT, ".bench_scratch", "trace")
ANNOTATION = re.compile(r"^(bench|rx|serve)\.")
PROGRAM_ID = re.compile(r"\((\d+)\)$")
#: the same floor harness/xplane.py's idle_gaps uses: a shorter gap
#: lies between two ops of one program and is not the host's doing
HOST_GAP_NS = 5e4


class Span(NamedTuple):
    name: str
    start: float        # ns on the trace's clock
    end: float
    args: dict


class Op(NamedTuple):
    name: str
    start: float
    end: float
    self_ns: float      # its time less the ops nested in it
    scope: str          # op_name it ran under ("" when the trace has none)
    named: bool = True  # False: the scope is its predecessor's, not its own


class Run(NamedTuple):
    """One run of a program inside the window, its ops' self times
    summed by scope."""
    start: float
    end: float
    by_scope: Dict[str, float]      # scope ("" for none) -> self ns
    unnamed_ns: float               # of it, ops charged to a neighbour


class Annotations(NamedTuple):
    window: Tuple[float, float]     # first bench.tick start, last end
    spans: List[Span]               # main-thread annotations, by start
    ops: List[Op]                   # device 0's ops, by start
    runs: Dict[str, List[Run]]      # module kind -> its runs, by start


# ------------------------------------------------- protobuf wire format


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of one message: a varint's value, or the
    (start, end) of a length-delimited field. Fixed-width fields are
    skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, entry):
    """The value (field 2) of one protobuf map entry."""
    for f, v in _fields(buf, *entry):
        if f == 2:
            return v
    return None


def op_scopes(path: str, plane_name: str = "/device:TPU:0"
              ) -> Dict[Tuple[str, str], str]:
    """{(program id, instruction name as the op events carry it):
    ``tf_op``} from one plane's event metadata."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[Tuple[str, str], str] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:                                  # XSpace.planes
            continue
        name, ev_md, stat_md = "", [], []
        for f2, v in _fields(buf, *plane):
            if f2 == 2:
                name = _text(buf, v)
            elif f2 == 4:                           # event_metadata
                ev_md.append(v)
            elif f2 == 5:                           # stat_metadata
                stat_md.append(v)
        if name != plane_name:
            continue
        stat_name: Dict[int, str] = {}
        for entry in stat_md:
            md = _map_value(buf, entry)
            sid, sname = 0, ""
            for f3, v in _fields(buf, *md):
                if f3 == 1:
                    sid = v
                elif f3 == 2:
                    sname = _text(buf, v)
            stat_name[sid] = sname
        for entry in ev_md:
            md = _map_value(buf, entry)
            op, stats = "", {}
            for f3, v in _fields(buf, *md):
                if f3 == 2:                         # XEventMetadata.name
                    op = _text(buf, v)
                elif f3 == 5:                       # .stats
                    key, val = None, None
                    for f4, v4 in _fields(buf, *v):
                        if f4 == 1:
                            key = stat_name.get(v4)
                        elif f4 in (3, 4):          # uint64, int64
                            val = str(v4)
                        elif f4 == 5:               # str_value
                            val = _text(buf, v4)
                        elif f4 == 7:               # ref_value
                            val = stat_name.get(v4, "")
                    stats[key] = val
            if stats.get("tf_op"):
                out[(stats.get("program_id") or "", op)] = stats["tf_op"]
    return out


# ------------------------------------------------------------- the read


def nest(ops: List[Tuple[str, float, float, str]]) -> List[Op]:
    """(name, start, end, scope) -> ``Op``s by start, each with its
    self time, and a scope inherited from what is nested in it where
    it has none of its own."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child_ns = [0.0] * len(ops)
    by_scope: Dict[int, Dict[str, float]] = {}
    scope = [o[3] for o in ops]
    stack: List[int] = []

    def close(i: int) -> None:
        if not scope[i] and i in by_scope:
            scope[i] = max(by_scope[i], key=by_scope[i].get)
        if stack:
            p, dur = stack[-1], ops[i][2] - ops[i][1]
            child_ns[p] += dur
            if scope[i] and not scope[p]:
                tally = by_scope.setdefault(p, {})
                tally[scope[i]] = tally.get(scope[i], 0.0) + dur

    for i in order:
        while stack and ops[stack[-1]][2] <= ops[i][1]:
            close(stack.pop())
        stack.append(i)
    while stack:
        close(stack.pop())
    return [Op(ops[i][0], ops[i][1], ops[i][2],
               max(0.0, ops[i][2] - ops[i][1] - child_ns[i]), scope[i])
            for i in order]


def host_spans(path: str) -> List[Span]:
    """The main thread's annotation events with their stats, by start:
    all a trace with no device op in it (a CPU rehearsal) has to read."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    for ln in (plane.lines if plane is not None else ()):
        evs = [e for e in ln.events if ANNOTATION.match(e.name)]
        if any(e.name == xplane.WINDOW_SPAN for e in evs):
            spans = [Span(e.name, float(e.start_ns),
                          float(e.start_ns) + float(e.duration_ns),
                          dict(e.stats)) for e in evs]
    return sorted(spans, key=lambda s: s.start)


def read(path: str, tr: xplane.DeviceTrace) -> Annotations:
    """``tr`` is ``xplane.read(path)``: its window, module runs and
    device 0's ops are taken as they are; this adds the annotations'
    stats and the ops' scopes."""
    spans = host_spans(path)
    scopes = op_scopes(path) if tr.ops else {}
    runs = sorted(((m, kind) for kind, evs in tr.modules.items()
                   for m in evs), key=lambda mk: mk[0].start)
    raw, r = [], 0
    for o in tr.ops:                        # in the line's order: by start
        while r < len(runs) and runs[r][0].end <= o.start:
            r += 1
        scope = ""
        if r < len(runs) and runs[r][0].start <= o.start:
            found = PROGRAM_ID.search(runs[r][0].name)
            scope = scopes.get((found.group(1) if found else "", o.name),
                               "")
        raw.append((o.name, o.start, o.end, scope))
    ops = charge_unnamed(nest(raw), [m for m, _kind in runs])
    return Annotations(tr.window, spans, ops, sum_runs(ops, tr.modules))


def sum_runs(ops: List[Op], modules: Dict[str, List[xplane.Ev]]
             ) -> Dict[str, List[Run]]:
    """Per kind of program, each run with the self time of the ops
    (by start) that began inside it, summed by scope."""
    out: Dict[str, List[Run]] = {kind: [] for kind in modules}
    i = 0
    for m, kind in sorted(((m, kind) for kind, evs in modules.items()
                           for m in evs), key=lambda mk: mk[0].start):
        by_scope: Dict[str, float] = {}
        unnamed = 0.0
        while i < len(ops) and ops[i].start < m.end:
            o, i = ops[i], i + 1
            if o.start >= m.start:
                by_scope[o.scope] = by_scope.get(o.scope, 0.0) + o.self_ns
                unnamed += 0.0 if o.named else o.self_ns
        out[kind].append(Run(m.start, m.end, by_scope, unnamed))
    return out


def charge_unnamed(ops: List[Op], runs: List[xplane.Ev]) -> List[Op]:
    """Ops (by start) that still have no scope take the scope of the op
    before them in the same program run (``runs`` by start), marked
    ``named=False``; the first ops of a run and ops outside every run
    keep none."""
    out, r, last = [], 0, ""
    for o in ops:
        while r < len(runs) and runs[r].end <= o.start:
            r, last = r + 1, ""
        if r == len(runs) or o.start < runs[r].start:
            last = ""
        elif o.scope:
            last = o.scope
        elif last:
            o = o._replace(scope=last, named=False)
        out.append(o)
    return out


_LOADED: Dict[str, Annotations] = {}


def for_ctx(ctx, trace_dir: str = TRACE_DIR) -> Optional[Annotations]:
    """The newest trace under the directory cell.py has the profiler
    write to (the file ``ctx.device`` was read from), read once a
    process; None where the run's trace has no device op (a CPU
    rehearsal), so that every reader reports nothing there, as the
    readers of ``ctx.device`` do."""
    if ctx.device is None or not ctx.device.ops:
        return None
    path = xplane.find_xplane(trace_dir)
    if path is None:
        return None
    if path not in _LOADED:
        _LOADED.clear()
        _LOADED[path] = read(path, ctx.device)
    return _LOADED[path]


# ------------------------------------------- what two readers share


def chunk_steps(an: Annotations) -> int:
    """Chunk-steps launched inside the window: one ``rx.fleet.stack``
    span each."""
    lo, hi = an.window
    return sum(1 for s in an.spans
               if s.name == "rx.fleet.stack" and lo <= s.start < hi)
