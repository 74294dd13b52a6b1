"""Fault-tolerant dispatch runtime: guarded dispatch with watchdog +
retry/backoff, transient-vs-fatal classification, and stream-carry
checkpoint/restore (docs/robustness.md).

The streaming hot path (PR 5/11) keeps the steady state on the device
with the host at data-dependent control points — Ziria's placement
discipline. Those control points are also the *containment* points:
when a compiled dispatch fails, the host is the only layer that can
classify the failure, retry it, or swap in a degraded twin without
poisoning the rest of the fleet. This module is that layer:

- :func:`guarded` wraps a compiled-program call site. Each attempt
  runs inside ``dispatch.timed(label)`` (so per-attempt latency keeps
  feeding the telemetry histograms and the jaxlint R3 contract —
  instrumented sites stay inside ``timed()``), behind the chaos seam
  (``faults.maybe_fail``) and, when a watchdog timeout is set, on a
  watchdog thread whose abandonment contains a *hung* dispatch.
  Transient failures retry with exponential backoff and
  **deterministic jitter** (hashed from (label, seed, attempt) — a
  chaos replay backs off identically); fatal failures (and exhausted
  retries) raise :class:`DispatchFailed` — or return ``fallback()``
  when the caller has a degraded twin (the fused link's staged oracle,
  the streaming decode's per-capture path).
- :func:`classify_error` is the transient/fatal split: retry only
  what may heal. Retryable = injected transients, watchdog timeouts,
  and runtime errors carrying a retryable status marker
  (``UNAVAILABLE``, ``RESOURCE_EXHAUSTED``, ...); everything else —
  including an ``XlaRuntimeError`` with ``INVALID_ARGUMENT`` — is
  fatal (recompiling the same wrong program cannot help).
- :func:`compile_ahead` keeps the compiler OUT of the guard: a jitted
  program is traced, lowered and compiled before its first guarded
  attempt, so a program that does not trace, a kernel Mosaic refuses
  or one over its fast-memory limit raises to the caller — never
  retried, never classified, never answered with a twin. The guard
  contains what can go wrong while a program that compiled RUNS.
- :func:`checkpoint_carry` / :func:`restore_carry` serialize a
  streaming receiver's :class:`~ziria_tpu.backend.framebatch.StreamCarry`
  (tail samples, offset, emitted count, dedupe watermark — plus the
  live dedupe set and a geometry fingerprint) so a crashed or
  restarted receiver resumes mid-stream with bit-identical subsequent
  emissions — into a fleet lane via
  ``MultiStreamReceiver.restore_stream(i, blob)``, a lone
  ``StreamReceiver(checkpoint=...)`` being lane 0 of a fleet of one
  (the serving runtime's eviction-recovery path,
  docs/serving.md: ``ServeRuntime.evict`` checkpoints a session out,
  ``connect(sid, checkpoint=blob)`` restores it into whatever lane
  frees next).

Telemetry rides throughout (free when idle): ``resilience.retries`` /
``resilience.recovered`` / ``resilience.fallbacks`` /
``resilience.fatal`` counters, a ``resilience.backoff_seconds``
histogram, and the receivers' ``rx.degraded_mode`` /
``rx.quarantined_streams`` gauges — all visible in ``trace_report``.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import threading
import time
import zlib
from collections import Counter
from typing import Any, Callable, NamedTuple, Optional, Tuple

import numpy as np

from ziria_tpu.utils import dispatch, faults, telemetry

#: status markers that mean "the failure may heal on retry" — the
#: retryable gRPC/absl status families an XlaRuntimeError-shaped
#: message leads with, plus transport flaps of a remote device link
TRANSIENT_MARKERS = ("UNAVAILABLE", "RESOURCE_EXHAUSTED",
                     "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED",
                     "connection reset", "socket closed")


class DispatchTimeout(TimeoutError):
    """A guarded dispatch exceeded its watchdog timeout. Transient by
    classification: a hung device link often heals, and the watchdog
    thread holding the hung call is abandoned (daemon), never
    joined."""


class DispatchFailed(RuntimeError):
    """A guarded dispatch failed past its retry budget (or fatally).
    Carries the site label, attempts spent, the classification, and
    the last underlying error (also the ``__cause__``)."""

    def __init__(self, label: str, attempts: int, kind: str,
                 last: BaseException):
        super().__init__(
            f"guarded dispatch '{label}' failed ({kind}) after "
            f"{attempts} attempt(s): {type(last).__name__}: {last}")
        self.label = label
        self.attempts = attempts
        self.kind = kind
        self.last = last


class FaultPolicy(NamedTuple):
    """The retry/backoff/watchdog policy of a guarded site.
    ``max_retries`` transient retries follow the first attempt;
    backoff for attempt ``a`` is ``min(base * 2**a, max) * (0.5 +
    0.5 * u)`` with ``u`` the deterministic unit hash of
    (label, seed, a). ``timeout_s = None`` disables the watchdog
    thread (the production default — zero thread overhead); a value
    bounds every attempt and converts a hang into a retryable
    :class:`DispatchTimeout`."""
    max_retries: int = 2
    backoff_base_s: float = 0.05
    backoff_max_s: float = 2.0
    timeout_s: Optional[float] = None
    seed: int = 0


def env_max_retries() -> Optional[int]:
    """The ONE reading of the ``ZIRIA_MAX_RETRIES`` knob (the CLI's
    ``--max-retries`` writes it via the scoped-env pattern): the
    transient retry budget of every guarded dispatch site."""
    import os

    v = os.environ.get("ZIRIA_MAX_RETRIES")
    if v is None or v == "":
        return None
    return int(v)


def default_policy(max_retries: Optional[int] = None,
                   timeout_s: Optional[float] = None,
                   seed: int = 0) -> FaultPolicy:
    """The resolved site policy: an explicit ``max_retries`` wins,
    else ``ZIRIA_MAX_RETRIES``, else the 2-retry default."""
    if max_retries is None:
        max_retries = env_max_retries()
    if max_retries is None:
        max_retries = FaultPolicy._field_defaults["max_retries"]
    if max_retries < 0:
        raise ValueError(f"max_retries {max_retries} must be >= 0")
    return FaultPolicy(max_retries=int(max_retries),
                       timeout_s=timeout_s, seed=seed)


def classify_error(e: BaseException) -> str:
    """``"transient"`` (retry may heal it) or ``"fatal"`` (it will
    not). Injected faults classify by their class; timeouts are
    transient (the watchdog cut a hang); runtime errors classify by
    the retryable status markers their message leads with —
    an ``XlaRuntimeError`` saying ``INVALID_ARGUMENT`` is fatal, one
    saying ``UNAVAILABLE`` is not."""
    if isinstance(e, faults.InjectedFatalError):
        return "fatal"
    if isinstance(e, (faults.InjectedTransientError, TimeoutError)):
        return "transient"
    msg = str(e)
    if any(m in msg for m in TRANSIENT_MARKERS):
        return "transient"
    return "fatal"


def backoff_delay(label: str, attempt: int,
                  policy: FaultPolicy) -> float:
    """Attempt ``attempt``'s backoff: exponential with deterministic
    jitter in [0.5, 1.0) of the exponential value — hashed, never
    drawn, so a chaos replay waits the identical schedule."""
    base = min(policy.backoff_base_s * (2 ** attempt),
               policy.backoff_max_s)
    h = hashlib.sha256(
        f"{label}\x00{policy.seed}\x00{attempt}".encode()).digest()
    u = int.from_bytes(h[:8], "big") / float(1 << 64)
    return base * (0.5 + 0.5 * u)


# process-wide counter totals: telemetry counters are per-registry,
# but the trace counter tracks want cumulative levels
_COUNTS: Counter = Counter()
_CLOCK = threading.Lock()


def _count(name: str, n: int = 1) -> None:
    if not telemetry.active():
        return
    with _CLOCK:
        _COUNTS[name] += n
        tot = _COUNTS[name]
    telemetry.count(name, n, total=tot)


def _call_with_watchdog(label: str, call: Callable[[], Any],
                        timeout_s: float) -> Any:
    """Run ``call`` on a watchdog thread; on timeout abandon the
    thread (daemon — a genuinely hung dispatch never blocks the
    caller again) and raise :class:`DispatchTimeout`. The abandoned
    runner checks the flag after the chaos seam so an injected hang
    never fires a stray late dispatch on wake."""
    box: dict = {}
    done = threading.Event()
    abandoned = threading.Event()

    def run():
        try:
            box["out"] = call(abandoned)
        except BaseException as e:   # noqa: BLE001 - relayed below
            box["exc"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, daemon=True,
                         name=f"ziria-watchdog-{label}")
    t.start()
    if not done.wait(timeout_s):
        abandoned.set()
        raise DispatchTimeout(
            f"DEADLINE_EXCEEDED: dispatch '{label}' exceeded its "
            f"{timeout_s}s watchdog")
    if "exc" in box:
        raise box["exc"]
    return box.get("out")


#: (jitted callable, argument signature) pairs already compiled ahead
_COMPILED: set = set()


def _arg_sig(a: Any) -> Tuple:
    return (getattr(a, "shape", None), getattr(a, "dtype", type(a)),
            getattr(a, "sharding", None),
            bool(getattr(a, "weak_type", False)))


def compile_ahead(fn: Callable, *args) -> None:
    """Trace, lower and compile jitted ``fn`` for these arguments
    HERE, outside every guard, so whatever the tracer, Mosaic or XLA
    raises reaches the caller as itself. Once per (callable, argument
    shapes/dtypes/placement): the compiled executable lands in the
    callable's own lowering cache, so the ``fn(*args)`` that follows
    re-dispatches it and never compiles (pinned by
    tests/test_resilience.py). A callable with no ``lower`` (a plain
    function, a test stub) has nothing to compile."""
    if not hasattr(fn, "lower"):
        return
    key = (fn, tuple(_arg_sig(a) for a in args))
    if key in _COMPILED:
        return
    fn.lower(*args).compile()
    _COMPILED.add(key)


def guarded(label: str, fn: Callable, *args,
            policy: Optional[FaultPolicy] = None,
            fallback: Optional[Callable[[], Any]] = None,
            span_args: Optional[dict] = None,
            _sleep: Callable[[float], None] = time.sleep) -> Any:
    """Fire ``fn(*args)`` as a guarded dispatch at site ``label``.

    Every attempt runs inside ``dispatch.timed(label, span_args)`` (the
    per-attempt latency lands in the site's telemetry histogram, its
    trace span carries ``span_args``, and
    retries count as the extra dispatches they are) behind the chaos
    seam (``faults.maybe_fail(label)``). Transient failures retry up
    to ``policy.max_retries`` times with deterministic-jitter
    exponential backoff; a fatal failure (or exhaustion) returns
    ``fallback()`` when given — the degraded-twin hook — else raises
    :class:`DispatchFailed` with the last error chained.

    A jitted ``fn`` is compiled first, outside all of that
    (:func:`compile_ahead`): an error from tracing, lowering or
    compiling it propagates unchanged and untimed."""
    compile_ahead(fn, *args)
    policy = policy if policy is not None else default_policy()
    last: Optional[BaseException] = None
    kind = "fatal"
    attempt = 0
    for attempt in range(policy.max_retries + 1):
        try:
            with dispatch.timed(label, span_args):
                if policy.timeout_s is not None:
                    def call(abandoned):
                        faults.maybe_fail(label)
                        if abandoned.is_set():
                            return None   # hang cut: no stray dispatch
                        return fn(*args)
                    out = _call_with_watchdog(label, call,
                                              policy.timeout_s)
                else:
                    faults.maybe_fail(label)
                    out = fn(*args)
            if attempt:
                _count("resilience.recovered")
            return out
        except Exception as e:    # noqa: BLE001 - classified below
            last = e
            kind = classify_error(e)
            if kind == "transient" and attempt < policy.max_retries:
                d = backoff_delay(label, attempt, policy)
                _count("resilience.retries")
                telemetry.observe("resilience.backoff_seconds", d)
                _sleep(d)
                continue
            break
    _count("resilience.fatal")
    if fallback is not None:
        _count("resilience.fallbacks")
        return fallback()
    raise DispatchFailed(label, attempt + 1, kind, last) from last


# ------------------------------------------------ carry checkpoint/restore

#: checkpoint container format tag (bump on incompatible layout change)
CARRY_FORMAT = "ziria-stream-carry-v1"


class CarryCheckpointError(ValueError):
    """A checkpoint blob failed validation (wrong format tag, missing
    field, geometry mismatch surfaced by the restoring receiver)."""


class CarryState(NamedTuple):
    """A deserialized stream checkpoint: the :class:`StreamCarry`
    fields plus the live dedupe set, the geometry fingerprint the
    restoring receiver must match, and the receiver's runtime state
    (quarantine health, degraded flags, counters) — without which a
    quarantined receiver would restore un-quarantined and diverge
    from the uninterrupted run."""
    tail: np.ndarray          # (n, 2) float32 not-yet-owned samples
    offset: int               # stream coordinate of tail[0]
    emitted: int              # frames emitted so far
    watermark: int            # dedupe prune bound
    seen: frozenset           # live dedupe starts (>= watermark)
    geometry: dict            # receiver geometry fingerprint
    state: dict               # health/degraded runtime state


def _carry_crc(tail: np.ndarray, scalars: np.ndarray,
               seen: np.ndarray, geo: bytes, state: bytes) -> int:
    """CRC32 over the checkpoint's canonical payload bytes — the
    integrity field a torn or bit-rotted blob fails against at
    restore time (docs/robustness.md durability section)."""
    c = zlib.crc32(tail.tobytes())
    c = zlib.crc32(scalars.tobytes(), c)
    c = zlib.crc32(seen.tobytes(), c)
    c = zlib.crc32(geo, c)
    return zlib.crc32(state, c) & 0xFFFFFFFF


def checkpoint_carry(carry, seen=(), geometry: Optional[dict] = None,
                     state: Optional[dict] = None) -> bytes:
    """Serialize a stream carry (anything with ``tail`` / ``offset`` /
    ``emitted`` / ``watermark`` fields — a receiver's ``carry(i)``)
    plus the dedupe set, a geometry fingerprint, and the receiver's
    runtime ``state`` dict into a compact npz-container blob with a
    CRC32 integrity field over the payload (a torn write fails
    loudly at restore; pre-integrity blobs still load, counted on
    ``resilience.checkpoint_legacy``).
    ``MultiStreamReceiver.checkpoint(i)`` is the receiver-level
    wrapper (a lone ``StreamReceiver`` checkpoints through it): it
    drains the in-flight chunk-step first, so the blob never silently
    drops a launched chunk's frames, and fills ``state`` so
    quarantine/degraded status survives the restart."""
    tail = np.asarray(carry.tail, np.float32).reshape(-1, 2)
    scalars = np.asarray([int(carry.offset), int(carry.emitted),
                          int(carry.watermark)], np.int64)
    seen_a = np.asarray(sorted(int(s) for s in seen), np.int64)
    geo = json.dumps(geometry or {}, sort_keys=True).encode()
    state_b = json.dumps(state or {}, sort_keys=True).encode()
    buf = io.BytesIO()
    np.savez(
        buf,
        fmt=np.frombuffer(CARRY_FORMAT.encode(), np.uint8),
        tail=tail,
        scalars=scalars,
        seen=seen_a,
        geometry=np.frombuffer(geo, np.uint8),
        state=np.frombuffer(state_b, np.uint8),
        crc=np.asarray(
            [_carry_crc(tail, scalars, seen_a, geo, state_b)],
            np.uint32))
    return buf.getvalue()


def restore_carry(data: bytes) -> CarryState:
    """Deserialize a :func:`checkpoint_carry` blob. Raises
    :class:`CarryCheckpointError` on a malformed or wrong-format blob
    — a truncated file must fail loudly, never resume at garbage
    state."""
    try:
        z = np.load(io.BytesIO(bytes(data)), allow_pickle=False)
        fmt = bytes(z["fmt"]).decode()
        if fmt != CARRY_FORMAT:
            raise CarryCheckpointError(
                f"checkpoint format {fmt!r} != {CARRY_FORMAT!r}")
        tail = np.asarray(z["tail"], np.float32).reshape(-1, 2)
        scalars = np.asarray(z["scalars"], np.int64)
        off, emitted, watermark = (int(v) for v in scalars)
        seen_a = np.asarray(z["seen"], np.int64)
        seen = frozenset(int(s) for s in seen_a)
        geo_b = bytes(z["geometry"])
        geometry = json.loads(geo_b.decode() or "{}")
        state_b = bytes(z["state"]) if "state" in z.files else b"{}"
        state = json.loads(state_b.decode() or "{}")
        if "crc" in z.files:
            want = int(np.asarray(z["crc"], np.uint32)[0])
            got = _carry_crc(tail, scalars, seen_a, geo_b, state_b)
            if got != want:
                raise CarryCheckpointError(
                    f"checkpoint integrity failure: payload CRC32 "
                    f"{got:#010x} != recorded {want:#010x} (torn or "
                    f"corrupted blob)")
        else:
            # pre-integrity blob (ISSUE 14 satellite): still loads —
            # format tag unchanged — but the gap is counted so a fleet
            # quietly running CRC-less checkpoints is visible
            telemetry.count("resilience.checkpoint_legacy")
    except CarryCheckpointError:
        raise
    except Exception as e:
        raise CarryCheckpointError(
            f"unreadable stream checkpoint: {type(e).__name__}: {e}"
        ) from e
    return CarryState(tail, off, emitted, watermark, seen, geometry,
                      state)


def save_checkpoint(path: str, blob: bytes,
                    io_site: str = "checkpoint.write") -> None:
    """Write a checkpoint blob to ``path`` ATOMICALLY — tmp + fsync +
    rename (ISSUE 14 satellite: the direct write left a torn file on
    a crash mid-write, which `restore_carry` then reported as
    garbage). A reader never observes a partial file: it sees the old
    content or the new, nothing between. The payload passes the
    durability chaos seam (``faults.io_fault``) so soak campaigns can
    inject torn/ENOSPC writes here; a torn injected payload still
    lands atomically and fails loudly at restore via the CRC field."""
    from ziria_tpu.runtime.durability import _fsync_dir

    data = faults.io_fault(io_site, bytes(blob))
    d = os.path.dirname(os.path.abspath(path)) or "."
    tmp = os.path.join(
        d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(d)


def load_checkpoint(path: str) -> CarryState:
    """Read + validate a checkpoint file written by
    :func:`save_checkpoint` (or any `checkpoint_carry` blob on disk).
    Raises :class:`CarryCheckpointError` on torn/corrupt content —
    the CRC integrity field catches what atomicity cannot (bit rot,
    an injected torn payload)."""
    with open(path, "rb") as f:
        return restore_carry(f.read())


