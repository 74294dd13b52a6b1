"""Process-wide runtime telemetry: span traces, latency histograms,
and a metrics registry behind every dispatch surface.

The dispatch observability this repo grew PR by PR (`utils/dispatch`)
answers *how many* device calls a path fires and *how much total wall
time* they took — two integers that cannot answer the questions the
serving work asks: what is the p99 chunk latency, how long was the
double-buffer overlap sustained, and was that slow dispatch a dispatch
at all or a first-contact XLA compile. This module is the
distribution-level, exportable layer those questions need. Three
cooperating pieces, each thread-safe and each *free when inactive*
(the hot paths carry their instrumentation permanently; the disabled
cost is one tuple truthiness check, pinned by
``tests/test_telemetry.py``):

- **Span tracing** — :func:`tracing` activates a :class:`Trace`;
  :func:`span` (and every ``dispatch.timed`` site) records nested,
  per-thread spans with monotonic timestamps. :meth:`Trace.export`
  writes Chrome trace-event JSON, loadable in Perfetto /
  ``chrome://tracing``.
  ``Trace(annotate_device=True)`` passes each span through
  ``jax.profiler.TraceAnnotation`` so host spans line up with device
  traces when a ``jax.profiler`` capture runs concurrently.
  The trace most recently activated stays reachable
  (:func:`last_trace`) and says its clock (``Trace.epoch``); while
  one is active each collection of the interpreter's is a span too
  (``rx.pause.gc``), so a pause of the host has a name in it.
- **Metrics** — :func:`collect` activates a :class:`MetricsRegistry`
  of :class:`CounterMetric`\\ s, time-series :class:`Gauge`\\ s (every
  sample kept, not just the high-water mark), and power-of-two
  log-bucket :class:`Histogram`\\ s whose quantiles are exact *bounds*:
  ``quantile(q)`` returns the upper edge of the bucket holding the
  rank-⌈qN⌉ sample, so the true quantile is always in
  ``(bound/2, bound]``. :meth:`MetricsRegistry.snapshot` gives plain
  dicts for JSON artifacts; :meth:`MetricsRegistry.exposition` a
  Prometheus-style text page (``--metrics-dump``).
- **Compile events** — a ``jax.monitoring`` duration listener
  (installed on first activation, dormant otherwise) surfaces XLA
  compile stalls as trace spans in the ``compile`` category, and
  ``dispatch.cache_growth`` reports fresh jit-cache entries through
  :func:`record_compile` — so a 20 s first-contact compile shows up AS
  a compile, not as a mysteriously slow dispatch span.

`utils/dispatch.record()/timed()/record_gauge()` are thin emitters
into whatever is active here, so every instrumented site of the last
six PRs (``rx.stream_chunk_multi``, ``link.fused``, ``tx.encode_many``, the
in-flight gauge, ...) inherits tracing and histograms with no changes
at the site. Activation nests and overlaps freely: each active trace
and registry sees every event recorded while it is active (the same
reentrancy contract as ``dispatch.count_dispatches``).
"""

from __future__ import annotations

import gc
import json
import math
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

_LOCK = threading.Lock()      # guards (de)activation; never the hot path
# active sinks as immutable tuples: readers (the per-dispatch fast
# path) take a plain attribute read and a truthiness check — no lock
_TRACES: Tuple["Trace", ...] = ()
_REGISTRIES: Tuple["MetricsRegistry", ...] = ()


def active() -> bool:
    """True when any trace or registry is collecting (the slow path of
    every emitter is gated on this)."""
    return bool(_TRACES or _REGISTRIES)


def traced() -> bool:
    """True when a trace is active: the gate of what is recorded for a
    trace alone (a clock read kept for a span's arg, a counter-track
    sample), which a registry that always collects must not pay for."""
    return bool(_TRACES)


# ------------------------------------------------------------- histograms


def _bucket_exp(v: float) -> int:
    """The power-of-two bucket of ``v > 0``: the exponent ``e`` with
    ``v`` in ``(2**(e-1), 2**e]`` (exact powers land in their own
    bucket's upper edge, not the next one up)."""
    m, e = math.frexp(v)          # v = m * 2**e, m in [0.5, 1)
    if m == 0.5:
        e -= 1
    return e


class Histogram:
    """Fixed power-of-two log-bucket histogram with exact quantile
    *bounds*. Bucket ``e`` holds observations in ``(2**(e-1), 2**e]``
    (non-positive values get their own underflow bucket), so the full
    float range needs ~60 sparse buckets, recording is O(1), and
    ``quantile(q)`` is an upper bound on the true q-quantile that is
    never more than 2x above it — the resolution the power-of-two
    bucket family buys. Exact ``count``/``sum``/``min``/``max`` ride
    along, so ``max`` and ``mean`` are exact, not bounds."""

    __slots__ = ("_lock", "_buckets", "count", "sum", "min", "max")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._buckets: Dict[Optional[int], int] = {}  # exp -> count
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, value: float) -> None:
        v = float(value)
        e = _bucket_exp(v) if v > 0.0 else None       # None: v <= 0
        with self._lock:
            self._buckets[e] = self._buckets.get(e, 0) + 1
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v

    def _sorted_buckets(self) -> List[Tuple[Optional[int], int]]:
        return sorted(self._buckets.items(),
                      key=lambda kv: -math.inf if kv[0] is None
                      else kv[0])

    def quantile(self, q: float) -> Optional[float]:
        """Upper BOUND on the q-quantile: the upper edge of the bucket
        holding the rank-⌈qN⌉ observation (capped at the exact max).
        The true quantile lies in ``(bound/2, bound]``. None when
        empty."""
        with self._lock:
            n = self.count
            if not n:
                return None
            rank = min(n, max(1, math.ceil(q * n)))
            c = 0
            for e, k in self._sorted_buckets():
                c += k
                if c >= rank:
                    if e is None:
                        return min(0.0, self.max)
                    return min(math.ldexp(1.0, e), self.max)
        return self.max           # pragma: no cover - loop covers n>0

    def summary(self, scale: float = 1.0,
                ndigits: int = 6) -> Dict[str, Any]:
        """The artifact block: count + exact mean/max + p50/p90/p99
        quantile bounds, all scaled (pass ``scale=1e3`` for ms)."""
        if not self.count:
            return {"count": 0}
        r = lambda v: round(v * scale, ndigits)  # noqa: E731
        return {"count": self.count,
                "mean": r(self.sum / self.count),
                "p50": r(self.quantile(0.50)),
                "p90": r(self.quantile(0.90)),
                "p99": r(self.quantile(0.99)),
                "max": r(self.max)}

    def bucket_counts(self) -> List[Tuple[float, int]]:
        """(upper_edge, count) per occupied bucket, ascending — the
        exposition's cumulative-``le`` series is built from this."""
        with self._lock:
            return [(0.0 if e is None else math.ldexp(1.0, e), k)
                    for e, k in self._sorted_buckets()]


class CounterMetric:
    """Monotonic event counter."""

    __slots__ = ("_lock", "value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


class Gauge:
    """Time-series gauge: every ``set`` keeps a (monotonic seconds,
    value) sample — bounded by ``maxlen`` so an unbounded stream holds
    a window, not the full history — plus the exact last and max. The
    upgrade over ``DispatchCount.gauges``' high-water mark: the series
    shows *how long* a level (the streaming receiver's overlap depth)
    was sustained, not just that it was reached once."""

    __slots__ = ("_lock", "samples", "last", "max")

    def __init__(self, maxlen: int = 4096) -> None:
        self._lock = threading.Lock()
        self.samples: deque = deque(maxlen=maxlen)
        self.last: Optional[float] = None
        self.max = -math.inf

    def set(self, value: float, t: Optional[float] = None) -> None:
        v = float(value)
        with self._lock:
            self.samples.append(
                (time.perf_counter() if t is None else t, v))
            self.last = v
            if v > self.max:
                self.max = v


def _metric_key(name: str, labels: Dict[str, str]):
    return (name, tuple(sorted(labels.items())))


def _label_str(labels: Tuple[Tuple[str, str], ...]) -> str:
    return ",".join(f'{k}="{v}"' for k, v in labels)


def _sanitize(name: str) -> str:
    """Prometheus metric-name charset ([a-zA-Z0-9_:])."""
    return "".join(c if c.isalnum() or c in "_:" else "_"
                   for c in name)


class MetricsRegistry:
    """Thread-safe name+labels -> metric map. Metrics are get-or-create
    (:meth:`counter` / :meth:`gauge` / :meth:`histogram`), readable as
    a plain dict (:meth:`snapshot`, for JSON artifacts) or as a
    Prometheus-style text page (:meth:`exposition`, the
    ``--metrics-dump`` output)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple, Any] = {}

    def _get(self, cls, name: str, labels: Dict[str, str]):
        key = _metric_key(name, labels)
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls()
                self._metrics[key] = m
        if not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r}{dict(labels)} already registered as "
                f"{type(m).__name__}, requested {cls.__name__}")
        return m

    def counter(self, name: str, **labels: str) -> CounterMetric:
        return self._get(CounterMetric, name, labels)

    def gauge(self, name: str, **labels: str) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, **labels: str) -> Histogram:
        return self._get(Histogram, name, labels)

    def metrics(self) -> List[Tuple[Tuple[str, Tuple], Any]]:
        """[(name, labels), metric] pairs, stable-sorted — the raw
        iteration surface bench tooling reads percentile blocks off."""
        with self._lock:
            return sorted(self._metrics.items(), key=lambda kv: kv[0])

    def find(self, name: str, **labels: str):
        """The metric at name+labels, or None (never creates)."""
        return self._metrics.get(_metric_key(name, labels))

    def snapshot(self) -> Dict[str, Any]:
        """Plain-dict view: ``{name{labels}: value}`` for counters and
        gauges (gauges as {last, max, samples}), histogram summaries
        for histograms. JSON-serializable as-is."""
        out: Dict[str, Any] = {}
        for (name, labels), m in self.metrics():
            key = name + ("{%s}" % _label_str(labels) if labels else "")
            if isinstance(m, CounterMetric):
                out[key] = m.value
            elif isinstance(m, Gauge):
                with m._lock:
                    out[key] = {"last": m.last, "max": m.max,
                                "samples": [[round(t, 6), v]
                                            for t, v in m.samples]}
            else:
                out[key] = m.summary()
        return out

    def exposition(self) -> str:
        """Prometheus text exposition: counters and gauges as single
        samples, histograms as the standard cumulative ``_bucket{le=}``
        / ``_sum`` / ``_count`` series (bucket edges are this module's
        powers of two)."""
        by_name: Dict[str, List[Tuple[Tuple, Any]]] = {}
        for (name, labels), m in self.metrics():
            by_name.setdefault(name, []).append((labels, m))
        lines: List[str] = []
        for name, entries in sorted(by_name.items()):
            pname = _sanitize(name)
            kind = entries[0][1]
            typ = ("counter" if isinstance(kind, CounterMetric)
                   else "gauge" if isinstance(kind, Gauge)
                   else "histogram")
            lines.append(f"# TYPE {pname} {typ}")
            for labels, m in entries:
                ls = _label_str(labels)
                if isinstance(m, CounterMetric):
                    lines.append(f"{pname}{{{ls}}} {m.value}" if ls
                                 else f"{pname} {m.value}")
                elif isinstance(m, Gauge):
                    v = m.last if m.last is not None else "NaN"
                    lines.append(f"{pname}{{{ls}}} {v}" if ls
                                 else f"{pname} {v}")
                else:
                    cum = 0
                    for edge, k in m.bucket_counts():
                        cum += k
                        le = f'le="{edge!r}"'
                        full = f"{ls},{le}" if ls else le
                        lines.append(f"{pname}_bucket{{{full}}} {cum}")
                    full = f"{ls},le=\"+Inf\"" if ls else 'le="+Inf"'
                    lines.append(f"{pname}_bucket{{{full}}} {m.count}")
                    sfx = f"{{{ls}}}" if ls else ""
                    lines.append(f"{pname}_sum{sfx} {m.sum!r}")
                    lines.append(f"{pname}_count{sfx} {m.count}")
        return "\n".join(lines) + ("\n" if lines else "")


# ------------------------------------------------------------------ traces


class Trace:
    """Chrome trace-event collector. Spans land as complete ("X")
    events with microsecond timestamps relative to the trace's own
    monotonic epoch; gauges as counter ("C") tracks; compile events in
    the ``compile`` category. :meth:`export` writes the standard
    ``{"traceEvents": [...]}`` JSON object (Perfetto /
    ``chrome://tracing``).

    ``epoch`` is the ``time.perf_counter()`` value every event's ``ts``
    is relative to: an event began at ``epoch + ts / 1e6`` seconds on
    that clock, which is how a reader lays the trace against anything
    else timed with ``perf_counter`` (the benchmark's window) or, by
    the spans both hold, against a profiler's trace."""

    def __init__(self, annotate_device: bool = False) -> None:
        self.annotate_device = annotate_device
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self.epoch = time.perf_counter()
        self._pid = os.getpid()

    def _ts(self, t: float) -> float:
        return (t - self.epoch) * 1e6           # µs, trace-relative

    def add_event(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(ev)

    def complete(self, name: str, t0: float, dur_s: float,
                 tid: Optional[int] = None, args: Optional[dict] = None,
                 cat: str = "host") -> None:
        """A finished span: began at monotonic ``t0``, ran ``dur_s``."""
        ev = {"name": name, "ph": "X", "cat": cat,
              "ts": self._ts(t0), "dur": dur_s * 1e6,
              "pid": self._pid,
              "tid": threading.get_ident() if tid is None else tid}
        if args:
            ev["args"] = args
        self.add_event(ev)

    def instant(self, name: str, args: Optional[dict] = None,
                cat: str = "host") -> None:
        ev = {"name": name, "ph": "i", "s": "t", "cat": cat,
              "ts": self._ts(time.perf_counter()), "pid": self._pid,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self.add_event(ev)

    def counter(self, name: str, value: float) -> None:
        """One sample of a counter track — gauge levels plotted over
        time (the in-flight depth, the carry depth, frames emitted)."""
        self.add_event({"name": name, "ph": "C",
                        "ts": self._ts(time.perf_counter()),
                        "pid": self._pid, "args": {"value": value}})

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def to_json(self) -> Dict[str, Any]:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: Optional[str] = None) -> Dict[str, Any]:
        """The trace as a Chrome trace-event JSON object; written to
        ``path`` when given. Returns the object either way."""
        obj = self.to_json()
        if path:
            with open(path, "w") as f:
                json.dump(obj, f)
        return obj


_ANN_CLS: Any = None       # cached jax.profiler.TraceAnnotation


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` resolved once, lazily — jax is
    deliberately not imported at module load (telemetry must stay
    importable in jax-free tooling) and unavailable annotations
    degrade to plain host spans."""
    global _ANN_CLS
    if _ANN_CLS is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANN_CLS = TraceAnnotation
        except Exception:          # pragma: no cover - jax-free env
            _ANN_CLS = False
    return _ANN_CLS or None


@contextmanager
def span(name: str, args: Optional[dict] = None):
    """``with span("rx.stream_chunk_multi"): ...`` — record the block as one
    trace span in every active trace (nesting and thread identity come
    from timestamps + tid, the Chrome trace model). Free when no trace
    is active. When an active trace was built with
    ``annotate_device=True``, the block also runs under
    ``jax.profiler.TraceAnnotation(name, **args)`` so a concurrent
    device profile shows the same label, with ``args`` (ints, floats
    or short strings, all known when the span opens) as the event's
    stats."""
    traces = _TRACES
    if not traces:
        yield
        return
    if not _listener_installed:
        # activation may have preceded the jax import (the CLI shell
        # activates before _run_cmd imports jax): retry here, BEFORE
        # the traced call — dispatch.timed enters this span ahead of
        # the jit call, so even the first compile is captured
        _install_compile_listener()
    ann = None
    if any(t.annotate_device for t in traces):
        cls = _annotation_cls()
        if cls is not None:
            ann = cls(name, **args) if args else cls(name)
            ann.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dur = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        for t in traces:
            t.complete(name, t0, dur, args=args)


# ----------------------------------------------------------------- pauses

#: the span a collection of the interpreter's is recorded under: named
#: as the served path's spans are, so that a reader of device-idle gaps
#: (which labels a gap by the deepest ``rx.*`` span open in it) names a
#: gap a collection made
GC_SPAN = "rx.pause.gc"
_gc_open: Optional[Tuple[float, Any]] = None    # (start, annotation)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """The ``gc.callbacks`` entry of an active trace: each collection
    becomes one span from its ``start`` to its ``stop`` phase, with
    ``generation`` and ``collected`` (the annotation, entered at the
    start, carries the generation alone). Collections do not nest and
    run under the interpreter lock, so one open slot serves every
    thread."""
    global _gc_open
    traces = _TRACES
    if phase == "start":
        ann = None
        if any(t.annotate_device for t in traces):
            cls = _annotation_cls()
            if cls is not None:
                ann = cls(GC_SPAN, generation=info["generation"])
                ann.__enter__()
        _gc_open = (time.perf_counter(), ann)
    elif _gc_open is not None:
        (t0, ann), _gc_open = _gc_open, None
        dur = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        args = {"generation": info["generation"],
                "collected": info["collected"]}
        for t in traces:
            t.complete(GC_SPAN, t0, dur, args=args)


# ------------------------------------------------- activation / lifecycle

_LAST_TRACE: Optional[Trace] = None


def last_trace() -> Optional[Trace]:
    """The :class:`Trace` most recently activated by :func:`tracing`
    in this process, still there after its block has closed (one
    reference, replaced at the next activation; None before the
    first): how a reader that was not handed the object gets it — the
    benchmark's whole-window readers, or an operator after a traced
    run raised."""
    return _LAST_TRACE



def _without_last(sinks: Tuple, x) -> Tuple:
    """``sinks`` minus ONE occurrence of ``x`` (the last) — so
    activating the same Trace/MetricsRegistry object in nested blocks
    stays balanced: the inner exit removes one activation, not all of
    them."""
    for i in range(len(sinks) - 1, -1, -1):
        if sinks[i] is x:
            return sinks[:i] + sinks[i + 1:]
    return sinks


@contextmanager
def tracing(path: Optional[str] = None, annotate_device: bool = False,
            trace: Optional[Trace] = None):
    """Activate a :class:`Trace` for the block (a fresh one, or the
    one passed in); on exit deactivate and — when ``path`` is given —
    export the Chrome trace JSON there (export runs even when the
    block raises: a crashed run's trace is the one you want most).
    The trace stays reachable as :func:`last_trace`. While any trace
    is active the interpreter's collections are recorded as
    ``rx.pause.gc`` spans (one ``gc.callbacks`` entry, installed by
    the first activation and removed when the last closes)."""
    global _TRACES, _LAST_TRACE, _gc_open
    t = trace if trace is not None else Trace(
        annotate_device=annotate_device)
    with _LOCK:
        if not _TRACES:
            _gc_open = None
            gc.callbacks.append(_on_gc)
        _TRACES = _TRACES + (t,)
        _LAST_TRACE = t
    _install_compile_listener()
    try:
        yield t
    finally:
        with _LOCK:
            _TRACES = _without_last(_TRACES, t)
            if not _TRACES:
                gc.callbacks.remove(_on_gc)
        if path:
            t.export(path)


@contextmanager
def collect(registry: Optional[MetricsRegistry] = None):
    """Activate a :class:`MetricsRegistry` for the block; yields it.
    Every emitter sample recorded while active lands in it."""
    global _REGISTRIES
    r = registry if registry is not None else MetricsRegistry()
    with _LOCK:
        _REGISTRIES = _REGISTRIES + (r,)
    _install_compile_listener()
    try:
        yield r
    finally:
        with _LOCK:
            _REGISTRIES = _without_last(_REGISTRIES, r)


def env_trace_path() -> Optional[str]:
    """The ONE reading of the ZIRIA_TRACE knob (the CLI's ``--trace``
    writes it via the scoped-env pattern; exporting it directly works
    for any invocation): a path means 'trace this run and export the
    Chrome trace JSON there'."""
    return os.environ.get("ZIRIA_TRACE") or None


# -------------------------------------------------------------- emitters
#
# Thin, fixed-name funnels `utils/dispatch` (and the streaming
# receiver) pour into. All are free when nothing is active.

DISPATCH_COUNTER = "ziria_dispatches_total"
DISPATCH_HISTOGRAM = "ziria_dispatch_seconds"
GAUGE_METRIC = "ziria_gauge"
COMPILE_COUNTER = "ziria_compile_events_total"
COMPILE_HISTOGRAM = "ziria_compile_seconds"


def dispatch_event(label: str, n: int = 1,
                   seconds: Optional[float] = None) -> None:
    """One instrumented dispatch site firing: counter always,
    histogram observation when the site is timed."""
    if _REGISTRIES and not _listener_installed:
        _install_compile_listener()   # activation preceded jax import
    for r in _REGISTRIES:
        r.counter(DISPATCH_COUNTER, site=label).inc(n)
        if seconds is not None:
            r.histogram(DISPATCH_HISTOGRAM, site=label).observe(seconds)


def gauge_sample(label: str, value: float,
                 labels: Optional[Dict[str, str]] = None) -> None:
    """One level sample: a time-series point in every active registry
    AND a counter-track event in every active trace — the level is
    plottable over time, not just a high-water mark. ``labels`` split
    the series (a level a lane: ``{"lane": "3"}``; from a bounded set,
    since a registry keeps every series it was ever given), in the
    registry beside ``site`` and in the trace as a track of its own."""
    if not (_TRACES or _REGISTRIES):
        return
    t = time.perf_counter()
    labels = labels or {}
    for r in _REGISTRIES:
        r.gauge(GAUGE_METRIC, site=label, **labels).set(value, t)
    track_name = label + "".join(f"[{k}={v}]" for k, v in labels.items())
    for tr in _TRACES:
        tr.counter(track_name, value)


def track(name: str, value: float) -> None:
    """One counter-track sample in every active trace and nowhere
    else: a level that is worth a time beside the spans (the device's
    bytes in use at a launch) and not a series in every registry."""
    for tr in _TRACES:
        tr.counter(name, value)


def observe(name: str, value: float,
            labels: Optional[Dict[str, str]] = None) -> None:
    """One free-standing histogram observation (the resilience
    layer's backoff delays, the serving runtime's per-chunk-step
    latency): lands in every active registry's ``name`` histogram
    (label-partitioned when ``labels`` is given). Free when nothing
    is collecting."""
    if not _REGISTRIES:
        return
    for r in _REGISTRIES:
        r.histogram(name, **(labels or {})).observe(value)


def count(name: str, n: int = 1,
          total: Optional[float] = None,
          labels: Optional[Dict[str, str]] = None) -> None:
    """An event counter (frames emitted, sessions admitted):
    increments every active registry; when the caller passes its
    cumulative ``total``, active traces get a counter-track sample so
    the count is plottable over the run. ``labels`` partitions the
    counter per label set (the serving runtime's attributable
    ``serve.shed{reason=...}`` discipline) — the exposition carries
    each label series separately."""
    if not (_TRACES or _REGISTRIES):
        return
    for r in _REGISTRIES:
        r.counter(name, **(labels or {})).inc(n)
    if total is not None:
        for tr in _TRACES:
            tr.counter(name, total)


def record_compile(label: str, seconds: Optional[float] = None,
                   n: int = 1, args: Optional[dict] = None) -> None:
    """A compile-ish event. With ``seconds`` (an XLA compile stall's
    measured duration) it lands as a trace span in the ``compile``
    category ending now; without (a jit-cache growth delta) as an
    instant marker. Registries get the counter and — when timed — the
    compile-latency histogram."""
    if not (_TRACES or _REGISTRIES):
        return
    now = time.perf_counter()
    for t in _TRACES:
        if seconds:
            t.complete(label, now - seconds, seconds, cat="compile",
                       args=args)
        else:
            a = dict(args or {})
            a.setdefault("count", n)   # the marker carries its weight
            t.instant(label, args=a, cat="compile")
    for r in _REGISTRIES:
        r.counter(COMPILE_COUNTER, event=label).inc(n)
        if seconds:
            r.histogram(COMPILE_HISTOGRAM, event=label).observe(seconds)


# ------------------------------------------------- XLA compile listener

_listener_installed = False


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    """jax.monitoring duration callback: surface compile-flavored
    events (backend_compile, trace/lowering stalls) into whatever is
    active. Fast no-op otherwise — the listener stays registered for
    the life of the process once installed."""
    if not (_TRACES or _REGISTRIES):
        return
    if "compile" not in event and "trace" not in event:
        return
    record_compile(f"xla:{event.strip('/')}", seconds=float(duration))


def _install_compile_listener() -> None:
    """Register the jax.monitoring duration listener once, lazily, on
    the first activation AFTER jax is in play — importing jax (or
    running without it) before any telemetry is used costs nothing,
    and a deliberately jax-free process (the serving smoke, the trace
    tooling) activating telemetry must never drag jax in: when jax is
    absent the install is deferred, and the next activation — or the
    first span/dispatch emission after a jax import (the CLI shell
    activates before its command imports jax) — picks it up."""
    global _listener_installed
    if _listener_installed:
        return
    if "jax" not in sys.modules:
        return
    _listener_installed = True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
    except Exception:              # pragma: no cover - jax-free env
        pass
