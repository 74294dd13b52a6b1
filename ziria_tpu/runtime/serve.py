"""Continuous-batching serving runtime: N client sessions multiplexed
onto one fixed (S, K, chunk) compiled fleet geometry.

The ROADMAP's last missing layer between the device-side fleet
(`backend/framebatch.MultiStreamReceiver`, PR 11) and "heavy traffic
from millions of users": production traffic is many clients pushing
ragged I/Q slabs concurrently under latency SLOs, and the device side
must never see that raggedness — Ziria's ``|>>>|`` discipline keeps
the steady-state stream on the engine with the host touched only at
control points, and this scheduler IS that host control point (Sora's
dedicated-core streaming lineage: admission/eviction happen off the
hot dispatch loop). The compiled geometry never changes:

- **Admission** is a bounded queue with explicit backpressure. A
  session gets a free lane immediately, waits in the queue, or is
  REJECTED with a deterministic ``retry_after_s`` hint — never
  unbounded buffering, never a silent stall.
- **Scheduling** is continuous batching: each :meth:`ServeRuntime.step`
  moves at most one chunk's worth of each session's staged samples
  into its lane and fires ``push_many`` — the fleet packer dispatches
  one chunk-step for whichever lanes filled a chunk, idle lanes ride
  the existing valid-mask. Session count never enters the dispatch
  budget (≤ 2 dispatches per chunk-step, the PR 11 pin).
- **Deadlines + load shedding**: a session past its SLO deadline is
  SHED — removed, counted, and attributed in the shed log — not
  silently stalled. Shedding is deterministic: every decision reads
  the injectable ``clock`` at step boundaries, so a replay sheds the
  identical sessions at the identical steps.
- **Fault containment** rides PR 12's machinery unchanged: NaN slabs
  quarantine ONE lane behind the valid-mask (healthy sessions stay
  bit-identical to independent receivers, pinned), dispatch faults
  retry/degrade through `runtime/resilience.guarded`.
- **Eviction + recovery**: :meth:`ServeRuntime.evict` checkpoints a
  session's lane (`resilience.checkpoint_carry` blob, quarantine
  rider included); ``connect(sid, checkpoint=blob)`` restores it into
  a fresh lane with bit-identical subsequent emissions (the
  `restore_stream` contract).
- **Graceful drain**: :meth:`ServeRuntime.drain` stops admitting,
  flushes every in-flight chunk and session tail, and leaves the
  final stats — the SIGINT path of the ``python -m ziria_tpu serve``
  demo.
- **Crash durability** (ISSUE 14, docs/robustness.md): with
  ``snapshot_dir`` set, every state transition journals
  (runtime/durability write-ahead log) and the fleet snapshots
  atomically every ``snapshot_every`` chunk-steps —
  :meth:`ServeRuntime.recover` rebuilds the whole fleet after a
  ``kill -9`` with bit-identical emissions (at-least-once, deduped
  against the journaled delivery watermarks), elastically repacking
  onto fewer lanes when devices shrank.

All SLO metrics report through the PR 7 `utils/telemetry` registry —
:meth:`ServeRuntime.scrape` is the registry's Prometheus-style
``exposition()``, not a parallel stats path: ``serve.*`` counters
(admitted/queued/rejected/shed/evicted/restored/closed/frames, shed
reasons as labels), ``serve.active_sessions`` / ``serve.queue_depth``
gauges, and the ``serve.chunk_seconds`` latency histogram (the time
of the ``push_many`` call a chunk-step was LAUNCHED in: since the
receiver keeps three steps in flight that is a tick of the loop, not
the step's flight from its launch to its frames, which spans three
calls and is the benchmark's ``chunk_flight_ms.window``), next to the
per-dispatch
``ziria_dispatch_seconds{site="rx.stream_chunk_multi"}`` series the
receiver already emits. Use the runtime as a context manager — it
activates its registry for its lifetime and drains on exit.

The module imports no jax: the receiver is injectable (the default
builds a `MultiStreamReceiver` lazily), so `tools/serve_smoke.py`
exercises the whole admission/shed/evict/drain state machine against
a stub receiver in milliseconds, through TPU probe hangs.
"""

from __future__ import annotations

import base64
import bisect
import os
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional, \
    Tuple

import numpy as np

from ziria_tpu.runtime import durability, resilience
from ziria_tpu.utils import dispatch, faults, geometry as _geometry, \
    telemetry

# the single source of the fleet-geometry defaults below (jax-free,
# like this module) — ServeConfig() and MultiStreamReceiver() can
# never drift apart on chunk_len/frame_len/K/S
_GEO = _geometry.DEFAULT


class ServeConfig(NamedTuple):
    """The server's fixed shape. The first five fields are the
    compiled fleet geometry (`MultiStreamReceiver`'s, defaults
    inherited from :data:`ziria_tpu.utils.geometry.DEFAULT` —
    admission churn never changes them, so the two fleet programs
    compile once); the rest are host-side protocol bounds. Build
    from a tuned geometry with :meth:`from_geometry`. The two ingress
    bounds left None follow the geometry (:meth:`ingress_bounds`)."""
    n_lanes: int = _GEO.n_streams    # S: concurrent sessions on device
    chunk_len: int = _GEO.chunk_len
    frame_len: int = _GEO.frame_len
    max_frames_per_chunk: int = _GEO.max_frames_per_chunk
    check_fcs: bool = False
    queue_cap: int = 16              # admission queue bound
    max_slab_samples: Optional[int] = None   # oversized-slab bound
    max_backlog_samples: Optional[int] = None    # per-session staged
    default_slo_s: Optional[float] = None  # deadline = connect + slo
    retry_after_s: float = 0.05      # base backpressure hint
    sanitize: bool = True            # NaN slabs quarantine, not crash
    max_retries: Optional[int] = None    # guarded-dispatch budget
    watchdog_s: Optional[float] = None   # hang-cut timeout
    blowup_limit: int = 2
    rejoin_after: int = 3
    # durability (ISSUE 14): a snapshot_dir activates the write-ahead
    # journal; snapshot_every > 0 adds automatic fleet snapshots every
    # N chunk-steps (ServeRuntime.recover(dir) resumes after a crash)
    snapshot_dir: Optional[str] = None
    snapshot_every: int = 0
    snapshot_keep: int = 2
    journal_segment_records: int = 256
    jitter_seed: int = 0             # retry-after hint jitter seed
    # the lane axis over a dp mesh: None places by rule (the widest
    # mesh that leaves every chip the tuned fleet width), True takes
    # every chip the lanes divide over, False none
    shard: Optional[bool] = None

    @classmethod
    def from_geometry(cls, geo: "_geometry.Geometry",
                      **overrides: Any) -> "ServeConfig":
        """Config whose fleet-geometry fields come from ``geo`` (e.g.
        ``Geometry.tuned(device_kind)``); host-protocol fields keep
        their defaults unless overridden."""
        fields = dict(n_lanes=geo.n_streams, chunk_len=geo.chunk_len,
                      frame_len=geo.frame_len,
                      max_frames_per_chunk=geo.max_frames_per_chunk)
        fields.update(overrides)
        return cls(**fields)

    def ingress_bounds(self) -> Tuple[int, int]:
        """``(max_slab_samples, max_backlog_samples)`` as `submit`
        holds them: the caller's where given; left None, a slab of one
        stride (``chunk_len - frame_len``, what a session advances by
        a chunk-step) is always admitted and a session stages two
        chunks, and never less than the 65 536 / 262 144 samples the
        two were fixed at while every served window was 65 536."""
        slab, backlog = self.max_slab_samples, self.max_backlog_samples
        if slab is None:
            slab = max(1 << 16, self.chunk_len - self.frame_len)
        if backlog is None:
            backlog = max(1 << 18, 2 * self.chunk_len)
        return slab, backlog


class AdmitResult(NamedTuple):
    """:meth:`ServeRuntime.connect`'s answer. Exactly one of
    ``admitted``/``queued`` is True on success; both False means the
    client should retry after ``retry_after_s`` (``reason`` says
    why: ``queue_full`` / ``draining`` / ``duplicate``)."""
    sid: Any
    admitted: bool
    queued: bool = False
    retry_after_s: float = 0.0
    reason: str = ""


class SubmitResult(NamedTuple):
    """:meth:`ServeRuntime.submit`'s answer. ``accepted=False`` with
    a ``retry_after_s`` is backpressure (``backlog_full``); with
    ``reason`` ``oversized`` the slab violated the protocol bound;
    a terminal reason (``shed:deadline`` / ``evicted`` / ``closed`` /
    ``draining``) means the session is gone — reconnect or move on.
    Backpressure and shedding are protocol results, not exceptions:
    only a malformed slab or an unknown session id raises."""
    sid: Any
    accepted: bool
    retry_after_s: float = 0.0
    reason: str = ""


class ServeStats(NamedTuple):
    """The final report (:meth:`ServeRuntime.stats`): exact session
    accounting read back FROM the telemetry registry (the counters
    ARE the record — ``admitted == closed + shed_active + evicted +
    active`` by construction; a still-queued session that closes or
    evicts lands on the separate ``serve.closed_queued`` /
    ``serve.evicted_queued`` counters, visible in the scrape, so the
    balance holds) plus the receiver's dispatch-side numbers."""
    admitted: int
    queued: int
    rejected_admissions: int
    rejected_slabs: int
    shed: int
    evicted: int
    restored: int
    closed: int
    frames: int
    chunk_steps: int
    active_sessions: int
    queue_depth: int
    quarantined_sessions: int
    shed_log: Tuple
    snapshots: int = 0
    restarts: int = 0
    deduped: int = 0
    journal_errors: int = 0


class _Session:
    __slots__ = ("sid", "lane", "staged", "staged_samples", "deadline",
                 "connected_t", "frames", "restore_blob", "slo_s",
                 "dedupe_until", "acked", "unacked")

    def __init__(self, sid, now: float, slo_s: Optional[float],
                 restore_blob: Optional[bytes]):
        self.sid = sid
        self.lane: Optional[int] = None
        self.staged: deque = deque()      # accepted, not yet scheduled
        self.staged_samples = 0
        self.connected_t = now
        self.slo_s = None if slo_s is None else float(slo_s)
        self.deadline = None if slo_s is None else now + float(slo_s)
        self.frames = 0                   # per-session emission index
        self.restore_blob = restore_blob
        # durability bookkeeping (ISSUE 14): re-emissions with index
        # <= dedupe_until were already delivered before a crash and
        # are suppressed on recovery; `acked` is the stream coordinate
        # durably consumed (the client resubmits from it); `unacked`
        # holds (index, frame) pairs emitted but not yet journal-
        # marked — they ride the next snapshot as the rider
        self.dedupe_until = 0
        self.acked = 0
        self.unacked: List[Tuple[int, Any]] = []


def _slab(samples, sid) -> np.ndarray:
    """The ingress shape gate (the receiver's `_slab_array` rule,
    jax-free): coerce to (n, 2) float32 I/Q pairs or raise a
    ValueError NAMING the session — malformed input fails at the
    front door, never inside the scheduler."""
    try:
        arr = np.asarray(samples, np.float32)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"session {sid!r}: submitted slab is not "
            f"float-convertible ((n, 2) I/Q sample pairs expected): "
            f"{e}") from None
    if arr.size == 0:
        return arr.reshape(0, 2)
    if arr.ndim != 2 or arr.shape[1] != 2:
        raise ValueError(
            f"session {sid!r}: submitted slab has shape {arr.shape}, "
            f"want (n, 2) I/Q sample pairs")
    return arr


def _known(ids, cap: int = 16) -> str:
    ids = sorted(ids, key=repr)
    shown = ", ".join(repr(i) for i in ids[:cap])
    more = f", ... {len(ids) - cap} more" if len(ids) > cap else ""
    return f"[{shown}{more}]" if ids else "[] (none connected)"


class ServeRuntime:
    """The continuous-batching server. Single-threaded and
    deterministic by design: every admission/shed/evict decision is a
    pure function of the call sequence and the injectable ``clock``,
    so a chaos replay reproduces the run decision for decision.

    Use as a context manager::

        with ServeRuntime(ServeConfig(n_lanes=8, ...)) as srv:
            srv.connect("alice", slo_s=2.0)
            srv.submit("alice", slab)
            frames = srv.step()        # the scheduler tick
            ...
            final = srv.drain()        # or leave the block: auto-drain
        print(srv.scrape())            # Prometheus exposition

    ``receiver`` injects a duck-typed fleet (tests, the jax-free
    smoke); the default builds a `MultiStreamReceiver` at the config
    geometry on first use."""

    def __init__(self, config: Optional[ServeConfig] = None,
                 receiver=None,
                 clock: Callable[[], float] = time.monotonic,
                 registry: Optional[telemetry.MetricsRegistry] = None):
        self.cfg = config if config is not None else ServeConfig()
        if self.cfg.n_lanes < 1:
            raise ValueError(f"n_lanes {self.cfg.n_lanes} must be >= 1")
        self._max_slab, self._max_backlog = self.cfg.ingress_bounds()
        self.clock = clock
        self.registry = registry if registry is not None \
            else telemetry.MetricsRegistry()
        if receiver is not None:
            self._rx = receiver
        else:
            # what the receiver reports once, as it is built, lands
            # in this runtime's registry (`rx.mesh_devices`)
            with telemetry.collect(self.registry):
                self._rx = self._default_receiver()
        self._free = list(range(self.cfg.n_lanes))
        self._lane_sid: Dict[int, Any] = {}
        self._sessions: Dict[Any, _Session] = {}
        self._queue: deque = deque()
        self._gone: Dict[Any, str] = {}   # sid -> terminal reason
        self._spill: List = []            # (lane, frame) off-step
        self._shed_log: List[Tuple] = []
        self._steps_seen = 0
        self._draining = False
        self._drained = False
        self._cm = None
        self._rejects: Dict[Any, int] = {}   # sid -> reject attempts
        # durability (ISSUE 14): the write-ahead journal + snapshot
        # cadence; recovery state lives on `recovered`/`replayed`
        self._journal: Optional[durability.Journal] = None
        if self.cfg.snapshot_dir:
            self._journal = durability.Journal(
                os.path.join(self.cfg.snapshot_dir, "journal"),
                segment_records=self.cfg.journal_segment_records)
        self._marked: Dict[Any, int] = {}      # sid -> journaled mark
        self._pending_marks: Dict[Any, int] = {}
        # snapshot steps are ABSOLUTE across restarts: a recovered
        # runtime's receiver restarts chunk_steps at 0, so recover()
        # sets _step_base to the recovered snapshot's step — without
        # it, post-recovery snapshots would be numbered BELOW the
        # pre-crash ones and pruned as "oldest" (second-crash rollback)
        self._step_base = 0
        self._last_snap_step = 0
        self._last_snap_t: Optional[float] = None
        self.recovered: Dict[Any, dict] = {}   # recovery info per sid
        self.replayed: List[Tuple[Any, Any]] = []  # rider re-delivery

    def _default_receiver(self):
        # lazy: jax (through framebatch) is only imported when the
        # real fleet is wanted — the smoke's stub path never pays it
        from ziria_tpu.backend import framebatch
        c = self.cfg
        mesh = None
        if c.shard is not False:
            # the ELASTIC placement rule: shard the lane axis over
            # the widest S-divisible mesh the surviving devices
            # support — a recovery onto fewer chips rebuilds the
            # fleet instead of refusing to start (ISSUE 14). Left to
            # itself (``shard=None``) the runtime takes a further chip
            # only where each keeps the tuned fleet width: 8 lanes
            # stay on one chip on any host, 32 lie over four
            from ziria_tpu.parallel import batch as pbatch
            mesh = pbatch.elastic_mesh(
                c.n_lanes,
                min_lanes=1 if c.shard else _GEO.n_streams)
        return framebatch.MultiStreamReceiver(
            c.n_lanes, chunk_len=c.chunk_len, frame_len=c.frame_len,
            max_frames_per_chunk=c.max_frames_per_chunk,
            check_fcs=c.check_fcs, sanitize=c.sanitize,
            max_retries=c.max_retries, watchdog_s=c.watchdog_s,
            blowup_limit=c.blowup_limit,
            rejoin_after=c.rejoin_after, mesh=mesh)

    # -- lifecycle -------------------------------------------------------

    def __enter__(self) -> "ServeRuntime":
        self._cm = telemetry.collect(self.registry)
        self._cm.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        try:
            if not self._drained:
                self.drain()
        finally:
            cm, self._cm = self._cm, None
            cm.__exit__(*exc)

    # -- telemetry helpers ----------------------------------------------

    def _count(self, name: str, n: int = 1,
               labels: Optional[dict] = None) -> None:
        telemetry.count(name, n, labels=labels)

    def _counter_total(self, name: str) -> int:
        return sum(m.value for (n, _l), m in self.registry.metrics()
                   if n == name
                   and isinstance(m, telemetry.CounterMetric))

    def _gauges(self) -> None:
        dispatch.record_gauge("serve.active_sessions",
                              len(self._lane_sid))
        dispatch.record_gauge("serve.queue_depth", len(self._queue))
        dispatch.record_gauge(
            "serve.quarantined_sessions",
            sum(1 for ln in self._lane_sid
                if self._rx.quarantined(ln)))

    def _retry_after(self, sid=None) -> float:
        """Deterministic backpressure hint, scaled by the queue the
        rejected client would have stood behind — with PER-SESSION
        HASHED JITTER (ISSUE 14 satellite): an unjittered hint is the
        same for every client at the same depth, so a flood of
        synchronized rejects re-arrives in lockstep and floods again.
        The jitter is the resilience backoff discipline — a unit hash
        of (label, seed, attempt), never drawn — so a replay hints
        identically: hint = base * (1 + depth) * (0.5 + 0.5 * u)."""
        base = self.cfg.retry_after_s * (1 + len(self._queue))
        attempt = self._rejects.get(sid, 0)
        self._rejects[sid] = attempt + 1
        # bound the attempt table: a flood of unique-sid rejects is
        # exactly the overload this hint exists for, and must not
        # leak memory — an evicted entry just restarts that client's
        # jitter sequence (harmless)
        while len(self._rejects) > 4096:
            self._rejects.pop(next(iter(self._rejects)))
        u = faults._unit(f"{sid!r}", self.cfg.jitter_seed, attempt)
        return base * (0.5 + 0.5 * u)

    # -- durability: the write-ahead journal --------------------------

    def _j(self, ev: dict) -> None:
        """Best-effort durable journal append: a failed write (a full
        disk, an injected ``io_enospc``) is counted and contained —
        the fleet keeps serving; the lost record only WIDENS the
        recovery dedupe window (at-least-once, never a crash)."""
        if self._journal is None:
            return
        try:
            self._journal.append(ev)
        except OSError:
            self._count("serve.journal_errors")

    def _flush_marks(self) -> None:
        """Journal the delivery watermarks of everything returned by
        the PREVIOUS public call. Marks are deferred one call on
        purpose: a mark written before the caller actually received
        the frames would, after a crash in between, dedupe away
        frames nobody ever got (silent loss). Deferred, the crash
        window yields a re-delivery instead (at-least-once; the
        (sid, frame.start) pair is the idempotency key)."""
        if not self._pending_marks:
            return
        marks, self._pending_marks = self._pending_marks, {}
        self._j({"ev": "mark",
                 "d": {str(sid): n for sid, n in marks.items()}})
        for sid, n in marks.items():
            self._marked[sid] = n
            s = self._sessions.get(sid)
            if s is not None:
                while s.unacked and s.unacked[0][0] <= n:
                    s.unacked.pop(0)

    @staticmethod
    def _b64(blob: Optional[bytes]) -> Optional[str]:
        return None if blob is None \
            else base64.b64encode(blob).decode()

    def scrape(self) -> str:
        """The server's Prometheus-style scrape page — the PR 7
        registry exposition, serve.* series next to the receiver's
        dispatch/latency series. No parallel stats path."""
        return self.registry.exposition()

    def stats(self) -> ServeStats:
        ct = self._counter_total
        return ServeStats(
            admitted=ct("serve.admitted"),
            queued=ct("serve.queued"),
            rejected_admissions=ct("serve.rejected_admissions"),
            rejected_slabs=ct("serve.rejected_slabs"),
            shed=ct("serve.shed"),
            evicted=ct("serve.evicted"),
            restored=ct("serve.restored"),
            closed=ct("serve.closed"),
            frames=ct("serve.frames"),
            chunk_steps=int(self._rx.stats.chunk_steps),
            active_sessions=len(self._lane_sid),
            queue_depth=len(self._queue),
            quarantined_sessions=sum(
                1 for ln in self._lane_sid
                if self._rx.quarantined(ln)),
            shed_log=tuple(self._shed_log),
            snapshots=ct("serve.snapshots"),
            restarts=ct("serve.restarts"),
            deduped=ct("serve.deduped"),
            journal_errors=ct("serve.journal_errors"))

    # -- admission -------------------------------------------------------

    def connect(self, sid, slo_s: Optional[float] = None,
                checkpoint: Optional[bytes] = None) -> AdmitResult:
        """Admit a session: a free lane immediately, the bounded
        queue, or an explicit reject with a retry hint — never
        unbounded buffering. ``slo_s`` sets the deadline (connect
        time + slo; the config default applies when None);
        ``checkpoint`` restores an evicted session's blob into the
        granted lane (`restore_stream` — bit-identical resumption,
        quarantine rider included)."""
        self._flush_marks()
        if self._draining or self._drained:
            self._count("serve.rejected_admissions",
                        labels={"reason": "draining"})
            return AdmitResult(sid, False, False,
                               self._retry_after(sid), "draining")
        if sid in self._sessions:
            return AdmitResult(sid, False, False, 0.0, "duplicate")
        now = self.clock()
        slo = slo_s if slo_s is not None else self.cfg.default_slo_s
        s = _Session(sid, now, slo, checkpoint)
        if self._free:
            self._gone.pop(sid, None)  # reconnect after shed/evict
            self._sessions[sid] = s
            self._admit(s)
            self._j({"ev": "admit", "sid": sid, "slo": slo,
                     "ckpt": self._b64(checkpoint)})
            self._rejects.pop(sid, None)
            self._gauges()
            return AdmitResult(sid, True)
        if len(self._queue) >= self.cfg.queue_cap:
            # a REJECTED reconnect keeps its terminal _gone record:
            # submits keep answering with the old reason, not a raise
            self._count("serve.rejected_admissions",
                        labels={"reason": "queue_full"})
            return AdmitResult(sid, False, False,
                               self._retry_after(sid), "queue_full")
        self._gone.pop(sid, None)      # reconnect after shed/evict
        self._sessions[sid] = s
        self._queue.append(sid)
        self._count("serve.queued")
        self._j({"ev": "admit", "sid": sid, "slo": slo,
                 "ckpt": self._b64(checkpoint)})
        self._rejects.pop(sid, None)
        self._gauges()
        return AdmitResult(sid, False, True, 0.0, "queued")

    def _admit(self, s: _Session) -> None:
        lane = self._free.pop(0)
        s.lane = lane
        self._lane_sid[lane] = s.sid
        if s.restore_blob is not None:
            blob = s.restore_blob
            self._spill += self._rx.restore_stream(lane, blob)
            s.restore_blob = None
            try:
                st = resilience.restore_carry(blob)
                # the session's emission index resumes at the lane's
                # (the 1:1 emit rule), and `acked` names the stream
                # coordinate the blob durably consumed — the client
                # resubmits from there
                s.frames = int(st.emitted)
                s.acked = int(st.offset) + int(st.tail.shape[0])
            except resilience.CarryCheckpointError:
                pass    # duck-typed stub blob: counters stay fresh
            self._count("serve.restored")
        self._marked.setdefault(s.sid, s.frames)
        self._count("serve.admitted")

    def _admit_waiting(self) -> None:
        while self._free and self._queue:
            sid = self._queue.popleft()
            self._admit(self._sessions[sid])

    # -- ingress ---------------------------------------------------------

    def is_active(self, sid) -> bool:
        """True while ``sid`` holds a lane (admitted, not yet
        closed/shed/evicted) — the client-visible promotion signal:
        a queued session becomes active when a lane frees. Closing a
        session before it is active discards its staged data (it was
        never served), so well-behaved clients close active sessions
        only."""
        s = self._sessions.get(sid)
        return s is not None and s.lane is not None

    def _get_session(self, sid) -> _Session:
        s = self._sessions.get(sid)
        if s is None:
            raise KeyError(
                f"unknown session {sid!r}: known sessions are "
                f"{_known(self._sessions)}")
        return s

    def submit(self, sid, samples) -> SubmitResult:
        """Stage one slab of samples for ``sid``. Bounded end to end:
        an oversized slab is rejected (``max_slab_samples``), a slab
        that would overflow the session's staging bound is rejected
        with a retry hint (``max_backlog_samples`` — the per-session
        backpressure that contains floods). A slab for a shed/
        evicted/closed session returns its terminal reason; a truly
        unknown session raises a KeyError naming the known ones."""
        self._flush_marks()
        s = self._sessions.get(sid)
        if s is None:
            reason = self._gone.get(sid)
            if reason is not None:
                return SubmitResult(sid, False, 0.0, reason)
            self._get_session(sid)     # raises the named KeyError
        arr = _slab(samples, sid)
        n = int(arr.shape[0])
        if n > self._max_slab:
            self._count("serve.rejected_slabs",
                        labels={"reason": "oversized"})
            return SubmitResult(sid, False, 0.0, "oversized")
        if s.staged_samples + n > self._max_backlog:
            self._count("serve.rejected_slabs",
                        labels={"reason": "backlog_full"})
            return SubmitResult(sid, False, self._retry_after(sid),
                                "backlog_full")
        if n:
            s.staged.append(arr)
            s.staged_samples += n
        return SubmitResult(sid, True)

    # -- the scheduler tick ---------------------------------------------

    def _take_staged(self, s: _Session,
                     budget: int) -> Optional[np.ndarray]:
        """Pop exactly up to one chunk's worth of staged samples —
        the continuous-batching rate limit: a flooding client
        advances at MOST one chunk per tick (a slab crossing the
        budget is split, its tail pushed back), its excess held
        (bounded) in staging. Push-boundary invariance (the
        ragged-push pin) makes the re-slabbing bit-invisible to the
        receiver."""
        if not s.staged:
            return None
        take, got = [], 0
        while s.staged and got < budget:
            a = s.staged.popleft()
            need = budget - got
            if a.shape[0] > need:
                s.staged.appendleft(a[need:])
                a = a[:need]
            take.append(a)
            got += a.shape[0]
        s.staged_samples -= got
        return take[0] if len(take) == 1 else np.concatenate(take)

    def _emit(self, pairs) -> List[Tuple[Any, Any]]:
        """Map receiver (lane, frame) emissions back to sessions.
        Re-emissions already delivered before a crash (index at or
        below the session's journaled dedupe watermark) are SUPPRESSED
        and counted — the recovery dedupe window, docs/robustness.md.
        Delivered frames ride ``unacked`` until their mark is durably
        journaled (the next public call), so a snapshot in between
        can carry them as the rider."""
        out = []
        with telemetry.span("serve.emit", {"frames": len(pairs)}):
            for lane, fr in pairs:
                sid = self._lane_sid.get(lane)
                if sid is None:        # pragma: no cover - drained
                    continue           # lanes are emptied before free
                s = self._sessions[sid]
                s.frames += 1
                if s.frames <= s.dedupe_until:
                    self._count("serve.deduped")
                    continue
                s.unacked.append((s.frames, fr))
                self._pending_marks[sid] = s.frames
                out.append((sid, fr))
        if out:
            self._count("serve.frames", len(out))
        return out

    def _take_spill(self) -> List[Tuple[Any, Any]]:
        if not self._spill:
            return []
        spill, self._spill = self._spill, []
        return self._emit(spill)

    def _note_steps(self, dt: float) -> None:
        d = int(self._rx.stats.chunk_steps) - self._steps_seen
        if d <= 0:
            return
        self._steps_seen += d
        per = dt / d
        for _ in range(d):
            telemetry.observe("serve.chunk_seconds", per)

    def _push(self, push: Dict[int, np.ndarray]) -> List:
        t0 = time.perf_counter()
        got = self._rx.push_many(push)
        self._note_steps(time.perf_counter() - t0)
        return self._emit(got)

    def step(self) -> List[Tuple[Any, Any]]:
        """One scheduler tick: shed expired sessions, admit from the
        queue into freed lanes, move up to one chunk's worth of each
        session's staged samples into its lane, and fire the fleet
        packer (one ``push_many`` — chunk-steps dispatch for
        whichever lanes filled, idle lanes ride the valid-mask).
        Returns the ``(sid, StreamFrame)`` pairs that became
        decodable this tick: a tick that launches hands back the
        frames of the chunk-step two launches before it, and a tick
        that launches nothing (nothing staged included) those of
        every older step the device has finished meanwhile, without
        waiting for one it has not. :meth:`drain`, :meth:`close`,
        :meth:`evict` and :meth:`snapshot` wait for all of them."""
        if self._drained:
            raise RuntimeError("step after drain")
        with telemetry.span("serve.step",
                            {"sessions": len(self._sessions)}):
            self._flush_marks()
            out = self._take_spill()
            out += self._shed_expired()
            self._admit_waiting()
            push = {}
            with telemetry.span("serve.stage"):
                for lane, sid in self._lane_sid.items():
                    take = self._take_staged(self._sessions[sid],
                                             self.cfg.chunk_len)
                    if take is not None:
                        push[lane] = take
            # an empty push launches nothing and still hands back
            # what the device has finished
            out += self._push(push)
            out += self._maybe_snapshot()
            self._gauges()
        return out

    # -- durability: snapshots + recovery -------------------------------

    def _maybe_snapshot(self) -> List[Tuple[Any, Any]]:
        """The automatic cadence: every ``snapshot_every`` chunk-steps
        the whole fleet snapshots (ISSUE 14 tentpole). Between
        snapshots the age gauges keep the staleness visible."""
        if self._journal is None or self.cfg.snapshot_every <= 0:
            return []
        steps = self._step_base + int(self._rx.stats.chunk_steps)
        if steps - self._last_snap_step < self.cfg.snapshot_every:
            if self._last_snap_t is not None:
                dispatch.record_gauge("serve.snapshot_age_s",
                                      self.clock()
                                      - self._last_snap_t)
                dispatch.record_gauge("serve.snapshot_age_steps",
                                      steps - self._last_snap_step)
            return []
        return self.snapshot()

    def snapshot(self) -> List[Tuple[Any, Any]]:
        """Write one atomic fleet snapshot: drain the in-flight
        chunk-step (its emissions are returned — they belong to the
        caller, never to the snapshot alone), then persist every
        occupied lane's checkpoint blob, the session table (SLO
        remainders, delivery watermarks, queued sessions' restore
        blobs), the terminal-reason map, the undelivered-frame rider,
        and the journal watermark — one atomic directory rename
        (runtime/durability.py). A failed write (full disk, injected
        ``io_enospc``) is contained: counted, the previous snapshot
        stays authoritative, serving continues."""
        if self._journal is None:
            raise RuntimeError(
                "snapshot without a snapshot_dir (set "
                "ServeConfig.snapshot_dir)")
        lanes, got = self._rx.checkpoint_fleet(
            sorted(self._lane_sid))
        out = self._emit(got)
        now = self.clock()
        step = self._step_base + int(self._rx.stats.chunk_steps)
        sessions = []
        for sid in ([self._lane_sid[ln]
                     for ln in sorted(self._lane_sid)]
                    + list(self._queue)):
            s = self._sessions[sid]
            sessions.append({
                "sid": sid, "lane": s.lane, "slo": s.slo_s,
                "slo_rem": None if s.deadline is None
                else max(0.0, s.deadline - now),
                "delivered": self._marked.get(sid, 0),
                "ckpt": self._b64(s.restore_blob)})
        rider, skipped = [], 0
        for sid, s in self._sessions.items():
            for idx, fr in s.unacked:
                try:
                    rider.append({"sid": sid, "idx": idx,
                                  "frame": durability.encode_frame(
                                      fr)})
                except Exception:    # noqa: BLE001 - duck-typed stub
                    skipped += 1
        if skipped:
            self._count("serve.rider_skipped", skipped)
        body = {"config": dict(self.cfg._asdict()),
                "jseq": int(self._journal.seq),
                "sessions": sessions,
                "gone": [[sid, r] for sid, r in self._gone.items()],
                "rider": rider}
        try:
            durability.write_snapshot(
                self.cfg.snapshot_dir, step, lanes, body,
                keep=self.cfg.snapshot_keep)
        except OSError:
            self._count("serve.snapshot_errors")
            return out
        self._journal.prune(body["jseq"])
        self._last_snap_step = step
        self._last_snap_t = now
        self._count("serve.snapshots")
        dispatch.record_gauge("serve.snapshot_age_s", 0.0)
        dispatch.record_gauge("serve.snapshot_age_steps", 0)
        return out

    def acked(self, sid) -> int:
        """The stream coordinate durably consumed for ``sid`` — after
        :meth:`recover`, the client resubmits its stream from here
        (everything before it is inside the restored lane state;
        everything after was lost with the process and must be pushed
        again)."""
        return self._get_session(sid).acked

    @classmethod
    def recover(cls, snapshot_dir: str,
                config: Optional[ServeConfig] = None,
                receiver=None,
                clock: Callable[[], float] = time.monotonic,
                registry: Optional[telemetry.MetricsRegistry] = None
                ) -> "ServeRuntime":
        """Rebuild a crashed server from its durability directory —
        the ISSUE 14 acceptance path: load the newest VALID snapshot,
        replay journal records past its watermark to reconstruct the
        session table exactly (admissions after the snapshot restore
        as fresh sessions; shed/evicted/closed sessions stay gone
        with their terminal reasons; delivery watermarks advance to
        the last durable mark), restore every lane blob into the new
        fleet, and re-deliver the snapshot's undelivered-frame rider
        (``.replayed``) — at-least-once, deduped against the
        journaled watermarks.

        ``config`` overrides the snapshot's recorded config — the
        ELASTIC failover lever: recover with a smaller ``n_lanes``
        (devices shrank) and sessions beyond the surviving lanes are
        repacked into the admission queue, restoring as lanes free
        (zero recompiles beyond the new geometry's two programs).
        ``.recovered`` maps every live session to its ``acked``
        resubmission coordinate and dedupe watermark."""
        snap = durability.load_snapshot(snapshot_dir)
        base_seq = int(snap.body.get("jseq", 0)) if snap else 0
        events, rstats = durability.replay(
            os.path.join(snapshot_dir, "journal"),
            after_seq=base_seq)
        if config is None:
            if snap is None:
                raise ValueError(
                    f"{snapshot_dir}: no usable snapshot — journal-"
                    f"only recovery needs an explicit config")
            config = ServeConfig(**snap.body["config"])
        config = config._replace(snapshot_dir=snapshot_dir)

        # reduce snapshot + journal into the final session table
        live: Dict[Any, dict] = {}
        delivered: Dict[Any, int] = {}
        order: List[Any] = []
        by_str: Dict[str, Any] = {}
        gone: Dict[Any, str] = {}

        def note(sid):
            by_str[str(sid)] = sid
            if sid not in order:
                order.append(sid)

        if snap is not None:
            for ent in snap.body.get("sessions", []):
                sid = ent["sid"]
                blob = None
                if ent.get("lane") is not None:
                    blob = snap.lanes.get(int(ent["lane"]))
                elif ent.get("ckpt"):
                    blob = base64.b64decode(ent["ckpt"])
                live[sid] = {"slo": ent.get("slo"),
                             "slo_rem": ent.get("slo_rem"),
                             "blob": blob}
                delivered[sid] = int(ent.get("delivered", 0))
                note(sid)
            gone.update({sid: r
                         for sid, r in snap.body.get("gone", [])})
        for ev in events:
            k = ev.get("ev")
            if k == "admit":
                sid = ev["sid"]
                blob = base64.b64decode(ev["ckpt"]) \
                    if ev.get("ckpt") else None
                live[sid] = {"slo": ev.get("slo"), "slo_rem": None,
                             "blob": blob}
                delivered[sid] = max(delivered.get(sid, 0),
                                     int(ev.get("delivered", 0)))
                gone.pop(sid, None)
                note(sid)
            elif k == "mark":
                for key, n in ev.get("d", {}).items():
                    sid = by_str.get(key, key)
                    delivered[sid] = max(delivered.get(sid, 0),
                                         int(n))
            elif k in ("shed", "close", "evict"):
                sid = ev["sid"]
                live.pop(sid, None)
                gone[sid] = ev.get("reason",
                                   "closed" if k == "close"
                                   else "evicted")

        srv = cls(config, receiver=receiver, clock=clock,
                  registry=registry)
        if snap is not None:
            # continue the ABSOLUTE step/sequence lines: the fresh
            # receiver restarts chunk_steps at 0 and a fully-pruned
            # journal restarts seq at 0 — both must resume past the
            # recovered snapshot or a SECOND crash rolls back to it
            srv._step_base = int(snap.step)
            srv._last_snap_step = int(snap.step)
            if srv._journal is not None:
                srv._journal.bump_seq(base_seq)
        now = srv.clock()
        with telemetry.collect(srv.registry):
            srv._count("serve.restarts")
            if rstats.dropped:
                srv._count("serve.journal_torn_drops",
                           rstats.dropped)
            srv._gone.update(gone)
            marks: Dict[str, int] = {}
            for sid in order:
                ent = live.get(sid)
                if ent is None:
                    continue
                slo = ent["slo_rem"] if ent["slo_rem"] is not None \
                    else ent["slo"]
                s = _Session(sid, now, slo, ent["blob"])
                s.dedupe_until = delivered.get(sid, 0)
                if ent["blob"] is not None:
                    try:
                        st = resilience.restore_carry(ent["blob"])
                        s.acked = int(st.offset) \
                            + int(st.tail.shape[0])
                    except resilience.CarryCheckpointError:
                        pass
                srv._sessions[sid] = s
                srv._marked[sid] = delivered.get(sid, 0)
                if srv._free:
                    srv._admit(s)
                else:
                    # elastic repack: more live sessions than
                    # surviving lanes — the scheduler's queue takes
                    # the rest, restoring as lanes free
                    srv._queue.append(sid)
                    srv._count("serve.queued")
                srv._j({"ev": "admit", "sid": sid, "slo": slo,
                        "ckpt": srv._b64(
                            ent["blob"]),
                        "delivered": delivered.get(sid, 0)})
                marks[str(sid)] = delivered.get(sid, 0)
                srv.recovered[sid] = {
                    "acked": s.acked,
                    "dedupe_until": s.dedupe_until,
                    "active": s.lane is not None}
            if marks:
                srv._j({"ev": "mark", "d": marks})
            # rider replay: frames emitted before the crash but never
            # durably marked delivered — re-delivered at-least-once
            for entry in (snap.body.get("rider", [])
                          if snap else []):
                sid = entry["sid"]
                if sid not in srv._sessions:
                    continue
                idx = int(entry["idx"])
                if idx <= delivered.get(sid, 0):
                    continue
                fr = durability.decode_frame(entry["frame"])
                srv.replayed.append((sid, fr))
                srv._pending_marks[sid] = max(
                    srv._pending_marks.get(sid, 0), idx)
            if srv.replayed:
                srv._count("serve.replayed", len(srv.replayed))
            srv._gauges()
        return srv

    # -- deadlines / shedding -------------------------------------------

    def _shed_expired(self) -> List[Tuple[Any, Any]]:
        """SLO-aware load shedding, deterministic and attributable:
        every session past its deadline — queued or active — is
        removed NOW, counted under its reason label, and logged
        ``(sid, reason, t)``. Never a silent stall."""
        now = self.clock()
        out: List[Tuple[Any, Any]] = []
        for sid in [q for q in self._queue
                    if self._expired(q, now)]:
            self._queue.remove(sid)
            del self._sessions[sid]
            self._shed(sid, "deadline_queued", now)
        for lane in [ln for ln, sid in self._lane_sid.items()
                     if self._expired(sid, now)]:
            sid = self._lane_sid[lane]
            out += self._release(sid, shed_reason="deadline", t=now)
        return out

    def _expired(self, sid, now: float) -> bool:
        d = self._sessions[sid].deadline
        return d is not None and now > d

    def _shed(self, sid, reason: str, t: float) -> None:
        self._gone[sid] = f"shed:{reason}"
        self._shed_log.append((sid, reason, t))
        self._j({"ev": "shed", "sid": sid,
                 "reason": f"shed:{reason}"})
        self._count("serve.shed", labels={"reason": reason})

    def _release(self, sid, shed_reason: Optional[str] = None,
                 t: Optional[float] = None,
                 counted: Optional[str] = None) -> List:
        """Free a session's lane: drain anything it still rides in
        the in-flight step (attributed before the mapping goes away),
        reset the lane for recycling, and unmap."""
        s = self._sessions[sid]
        lane = s.lane
        out = self._emit(self._rx.reset_stream(lane))
        del self._lane_sid[lane]
        bisect.insort(self._free, lane)
        del self._sessions[sid]
        if shed_reason is not None:
            self._shed(sid, shed_reason, t)
        elif counted is not None:
            self._gone[sid] = counted
            self._j({"ev": "close" if counted == "closed"
                     else "evict", "sid": sid, "reason": counted})
            self._count(f"serve.{counted}")
        return out

    # -- close / evict / drain ------------------------------------------

    def close(self, sid) -> List[Tuple[Any, Any]]:
        """Graceful per-session end: push everything the session
        still has staged, flush its lane (the final zero-padded
        chunk), free the lane, and admit the next queued session.
        Returns the emissions (any session may ride along — the
        in-flight step drains)."""
        self._flush_marks()
        s = self._get_session(sid)
        if s.lane is None:
            # closing a still-QUEUED session: it was never admitted,
            # so it gets its own counter — serve.closed stays in the
            # admitted == closed + evicted + shed_active balance
            self._queue.remove(sid)
            del self._sessions[sid]
            self._gone[sid] = "closed"
            self._j({"ev": "close", "sid": sid, "reason": "closed"})
            self._count("serve.closed_queued")
            return []
        out = []
        while True:
            take = self._take_staged(s, self.cfg.chunk_len)
            if take is None:
                break
            out += self._push({s.lane: take})
        t0 = time.perf_counter()
        got = self._rx.flush_stream(s.lane)
        self._note_steps(time.perf_counter() - t0)
        out += self._emit(got)
        out += self._release(sid, counted="closed")
        self._admit_waiting()
        self._gauges()
        return out

    def evict(self, sid) -> Tuple[Optional[bytes], List, List]:
        """Evict a session, preserving it: checkpoint its lane (the
        in-flight step drains; quarantine rider travels in the blob),
        free the lane, and return ``(blob, emissions,
        staged_slabs)`` — the staged-but-unscheduled slabs hand back
        so the recovering client resubmits them after
        ``connect(sid, checkpoint=blob)``. Evicting a still-QUEUED
        session returns ``(None, [], staged)`` (no lane state
        exists yet)."""
        self._flush_marks()
        s = self._get_session(sid)
        staged = list(s.staged)
        s.staged.clear()
        s.staged_samples = 0
        if s.lane is None:
            # evicting a still-QUEUED session: never admitted, no
            # lane state — own counter, same balance rule as close
            self._queue.remove(sid)
            del self._sessions[sid]
            self._gone[sid] = "evicted"
            self._j({"ev": "evict", "sid": sid, "reason": "evicted"})
            self._count("serve.evicted_queued")
            return None, [], staged
        blob, got = self._rx.checkpoint(s.lane)
        out = self._emit(got)
        out += self._release(sid, counted="evicted")
        self._admit_waiting()
        self._gauges()
        return blob, out, staged

    def drain(self) -> List[Tuple[Any, Any]]:
        """Graceful shutdown: stop admitting (queued sessions are
        shed with reason ``draining`` — they never held device
        state), flush every active session's staged samples and lane,
        drain the in-flight chunk, and close the fleet. Idempotent;
        the final :meth:`stats`/:meth:`scrape` survive it."""
        if self._drained:
            return []
        self._flush_marks()
        self._draining = True
        out = self._take_spill()
        now = self.clock()
        while self._queue:
            sid = self._queue.popleft()
            del self._sessions[sid]
            self._shed(sid, "draining", now)
        for sid in [self._lane_sid[ln]
                    for ln in sorted(self._lane_sid)]:
            out += self.close(sid)
        got = self._rx.flush()
        # the fleet is closed: anything still pending drained above
        out += self._emit(got)
        self._drained = True
        if self._journal is not None:
            # every session closed above; seal the active segment so
            # the directory holds only sealed, replay-clean files
            self._flush_marks()
            self._journal.close()
        self._gauges()
        return out


# ---------------------------------------------------------- load generator


class ClientSpec(NamedTuple):
    """One synthetic client of the load generator: an id, a seeded
    arrival schedule (``[(tick, slab), ...]``), the ground-truth
    stream it was cut from, an optional SLO, and a misbehavior mode
    (``"ok"`` / ``"nan"`` poisoned slab / ``"flood"`` everything at
    tick 0 / ``"stall"`` delivers only the first half then goes
    silent / ``"oversize"`` one protocol-violating giant slab)."""
    sid: Any
    schedule: List
    stream: np.ndarray
    slo_s: Optional[float] = None
    mode: str = "ok"


def synth_payloads(n_sessions: int, frames_per_session: int,
                   n_bytes: int, seed: int) -> Tuple[List, List]:
    """What :func:`synth_load` transmits: ``(psdus_per, rates_per)``,
    per session the seeded PSDU byte arrays and their rates (session
    *i*, frame *j* rides the ``(i + j)``-th of the eight rates). Its
    own function so a checker can compare the frames a server hands
    back with the bytes that were sent (chip_smoke.py)."""
    from ziria_tpu.phy.wifi.params import RATES

    rng = np.random.default_rng(seed)
    rates_all = sorted(RATES)
    psdus_per, rates_per = [], []
    for i in range(n_sessions):
        rates = [rates_all[(i + j) % len(rates_all)]
                 for j in range(frames_per_session)]
        rates_per.append(rates)
        psdus_per.append([rng.integers(0, 256, n_bytes)
                          .astype(np.uint8) for _ in rates])
    return psdus_per, rates_per


def synth_load(n_sessions: int, frames_per_session: int = 3,
               n_bytes: int = 12, snr_db: float = 30.0,
               seed: int = 0, add_fcs: bool = True,
               tail: int = 1024, arrival=None,
               misbehave: Optional[Dict[int, str]] = None,
               slo_s: Optional[float] = None,
               channel_profile=None) -> List[ClientSpec]:
    """The many-client load generator (built on
    `link.stream_many_multi`'s arrival schedules): ``n_sessions``
    independent mixed-rate streams cut into seeded ragged slab
    schedules, with ``misbehave`` marking sessions by int index —
    ``{3: "nan"}``-style modes rewrite that session's schedule into
    the corresponding bad-client behavior. Fully deterministic per
    seed. Imports jax (through the PHY) — the jax-free smoke uses its
    own stub traffic instead."""
    from ziria_tpu.phy import link

    if arrival is None:
        arrival = link.ArrivalSpec()
    misbehave = dict(misbehave or {})
    psdus_per, rates_per = synth_payloads(n_sessions,
                                          frames_per_session, n_bytes,
                                          seed)
    # channel_profile (name / per-stream list / None -> the
    # ZIRIA_CHANNEL_PROFILE default) rides stream_many_multi's
    # per-stream physical channel: the serving load generator can
    # campaign multipath/SCO/Doppler/burst clients alongside the
    # misbehave modes (the soak harness's multipath-active rounds)
    streams, _starts, schedules = link.stream_many_multi(
        psdus_per, rates_per, snr_db=snr_db, cfo=1e-4, delay=60,
        seed=seed, add_fcs=add_fcs, tail=tail, arrival=arrival,
        channel_profile=channel_profile)

    out = []
    for i in range(n_sessions):
        mode = misbehave.get(i, "ok")
        sched = schedules[i]
        if mode == "flood":
            # everything at once, one giant burst of max-size slabs
            whole = streams[i]
            sched = [(0, whole[a: a + (1 << 14)])
                     for a in range(0, whole.shape[0], 1 << 14)]
        elif mode == "stall":
            sched = sched[: max(1, len(sched) // 2)]
        elif mode == "nan":
            # poison a deterministic slab mid-schedule
            j = len(sched) // 2
            t, bad = sched[j]
            bad = np.array(bad, copy=True)
            bad[:: 7] = np.nan
            sched = sched[:j] + [(t, bad)] + sched[j + 1:]
        elif mode == "oversize":
            t0 = sched[0][0] if sched else 0
            sched = [(t0, np.zeros((1 << 20, 2), np.float32))] + sched
        elif mode != "ok":
            raise ValueError(f"unknown misbehave mode {mode!r}")
        out.append(ClientSpec(f"s{i}", sched, streams[i], slo_s,
                              mode))
    return out


def run_clients(srv: ServeRuntime, clients: List[ClientSpec],
                max_ticks: int = 10000) -> Dict[Any, List]:
    """Drive a client set against a server, tick by tick: connect
    everyone up front (rejected clients retry each tick — the
    backpressure protocol), deliver each schedule's due slabs
    (resubmitting on backpressure), step the scheduler, close
    clients whose schedule is done (stalled clients never close —
    the deadline shed or the drain collects them), then drain.
    Returns ``{sid: [StreamFrame, ...]}`` per session. Deterministic
    for a deterministic server clock."""
    frames: Dict[Any, List] = {c.sid: [] for c in clients}

    def collect(pairs):
        for sid, fr in pairs:
            frames[sid].append(fr)

    # a recovered runtime re-delivers its snapshot rider up front
    # (at-least-once; dedupe by frame.start if exactness matters)
    collect((sid, fr) for sid, fr in srv.replayed
            if sid in frames)

    todo = {c.sid: deque(c.schedule) for c in clients}
    pending = {c.sid: c for c in clients}       # not yet connected
    unclosed = {c.sid: c for c in clients}

    def fast_forward(sid):
        """A RECOVERED session is already live ('duplicate'): resume
        its schedule from the server's acked coordinate — everything
        below it is inside the restored lane state (the documented
        resubmission protocol, docs/robustness.md)."""
        skip = srv.acked(sid)
        q = todo[sid]
        while q and skip > 0:
            t, slab = q[0]
            n = slab.shape[0]
            if n <= skip:
                q.popleft()
                skip -= n
            else:
                q[0] = (t, slab[skip:])
                skip = 0

    tick = 0
    while tick <= max_ticks:
        for sid in list(pending):
            r = srv.connect(sid, slo_s=pending[sid].slo_s)
            if r.admitted or r.queued:
                del pending[sid]
            elif r.reason == "duplicate":
                # recovered session (active, or queued behind the
                # elastic repack): resume, don't re-stream
                fast_forward(sid)
                del pending[sid]
        for c in clients:
            if c.sid in pending:
                continue
            q = todo[c.sid]
            while q and q[0][0] <= tick:
                t, slab = q[0]
                r = srv.submit(c.sid, slab)
                if r.accepted or not r.retry_after_s:
                    q.popleft()     # accepted, or terminally refused
                else:
                    break           # backpressure: retry next tick
        collect(srv.step())
        for done in [s for s, c in unclosed.items()
                     if c.mode != "stall" and not todo[s]
                     and s not in pending]:
            if srv.is_active(done):
                collect(srv.close(done))
                del unclosed[done]
            elif done in srv._gone:
                del unclosed[done]   # shed/evicted — accounted there
            # else: still queued — close once a lane frees it in
        tick += 1
        if not unclosed and not any(todo.values()):
            break
        if all(c.mode == "stall" for c in unclosed.values()) \
                and not any(todo[s] for s in unclosed) \
                and not pending:
            break
    collect(srv.drain())
    return frames


# ------------------------------------------------------------------- CLI


def main(argv=None) -> int:
    """``python -m ziria_tpu serve`` — the serving demo: a synthetic
    many-client load (misbehaving clients included) through the real
    fleet, SIGINT-safe (a ^C drains gracefully and still prints the
    final stats + exposition), chaos-injectable via ``--chaos``."""
    import argparse
    import json
    import sys

    from ziria_tpu.utils import faults

    p = argparse.ArgumentParser(
        prog="ziria_tpu serve",
        description="continuous-batching serving demo "
                    "(docs/serving.md)")
    p.add_argument("--lanes", type=int, default=4,
                   help="device lanes S (compiled fleet width)")
    p.add_argument("--sessions", type=int, default=6,
                   help="client sessions to serve")
    p.add_argument("--frames", type=int, default=2,
                   help="frames per session")
    p.add_argument("--chunk-len", type=int, default=4096)
    p.add_argument("--frame-len", type=int, default=1024)
    p.add_argument("--slo", type=float, default=None,
                   help="per-session deadline seconds (default none)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--nan-client", action="store_true",
                   help="make session 0 push a NaN-poisoned slab "
                        "(quarantine demo)")
    p.add_argument("--chaos", metavar="SPEC", default=None,
                   help="fault-injection spec (utils/faults grammar)")
    p.add_argument("--channel-profile", metavar="NAME[,NAME...]",
                   default=None,
                   help="physical-channel profile(s) for the client "
                        "load (phy/profiles; comma lists cycle per "
                        "session — the multipath/SCO/Doppler/burst "
                        "campaign stimulus, docs/robustness.md)")
    p.add_argument("--metrics-dump", action="store_true",
                   help="print the Prometheus exposition to stderr "
                        "at exit")
    p.add_argument("--snapshot-dir", metavar="DIR", default=None,
                   help="durability directory: write-ahead journal + "
                        "automatic fleet snapshots (docs/robustness.md"
                        "; ServeRuntime.recover(DIR) resumes a "
                        "crashed run)")
    p.add_argument("--snapshot-every", type=int, default=8,
                   metavar="N",
                   help="chunk-steps between automatic snapshots "
                        "(with --snapshot-dir; default 8)")
    p.add_argument("--recover", action="store_true",
                   help="recover the fleet from --snapshot-dir "
                        "instead of starting fresh")
    args = p.parse_args(argv)

    if args.recover and not args.snapshot_dir:
        raise SystemExit("--recover needs --snapshot-dir")
    cfg = ServeConfig(n_lanes=args.lanes, chunk_len=args.chunk_len,
                      frame_len=args.frame_len, check_fcs=True,
                      default_slo_s=args.slo,
                      snapshot_dir=args.snapshot_dir,
                      snapshot_every=args.snapshot_every)
    misbehave = {0: "nan"} if args.nan_client else {}
    if args.channel_profile is not None:
        from ziria_tpu.phy.profiles import parse_profile_spec
        try:
            parse_profile_spec(args.channel_profile)
        except ValueError as e:
            raise SystemExit(f"--channel-profile: {e}")
    from ziria_tpu.utils import compile_cache
    compile_cache.place()       # before the first compile (the load's TX)
    clients = synth_load(args.sessions, args.frames, seed=args.seed,
                         misbehave=misbehave, tail=args.frame_len,
                         channel_profile=args.channel_profile)
    chaos = None
    if args.chaos is not None:
        try:
            chaos = faults.parse_chaos_spec(args.chaos)
        except ValueError as e:
            raise SystemExit(f"--chaos: {e}")

    srv = ServeRuntime.recover(args.snapshot_dir, config=cfg) \
        if args.recover else ServeRuntime(cfg)
    frames: Dict[Any, List] = {}
    import contextlib
    try:
        with contextlib.ExitStack() as stack:
            if chaos is not None:
                specs, seed = chaos
                stack.enter_context(faults.inject(*specs, seed=seed))
            stack.enter_context(srv)
            try:
                frames = run_clients(srv, clients)
            except KeyboardInterrupt:
                # SIGINT-safe drain: stop admitting, flush in-flight
                # chunks, fall through to the final stats
                srv.drain()
                frames = {}
    finally:
        st = srv.stats()
        lat = srv.registry.find("serve.chunk_seconds")
        report = {
            "sessions": args.sessions, "lanes": args.lanes,
            "frames": sum(len(v) for v in frames.values()),
            "stats": {k: (list(v) if isinstance(v, tuple) else v)
                      for k, v in st._asdict().items()},
            "chunk_latency_ms": lat.summary(scale=1e3)
            if lat is not None else {"count": 0},
        }
        print(json.dumps(report))
        if args.metrics_dump:
            print("metrics exposition (utils/telemetry):",
                  file=sys.stderr)
            print(srv.scrape(), file=sys.stderr, end="")
    return 0
