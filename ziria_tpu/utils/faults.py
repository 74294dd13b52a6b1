"""Seeded, scoped fault injection — the chaos layer of the streaming
runtime (docs/robustness.md).

A streaming fleet that must survive "millions of users" meets bad
input and flaky devices as a matter of course: a NaN slab from a
misbehaving client, a truncated push from a dropped socket, a
transient ``XlaRuntimeError`` when the device link flaps, a dispatch
that simply hangs. None of those are reproducible on demand — so this
module makes them reproducible: :func:`inject` activates a
:class:`FaultPlan` for a scope (telemetry-style activation: a module
tuple of active plans, one truthiness check per seam when nothing is
active — the same free-when-idle discipline as
:mod:`ziria_tpu.utils.telemetry`, pinned by
``tests/test_resilience.py``), and every decision is **deterministic
by (site, seed, call-index)**: the same plan over the same workload
injects the same faults at the same calls, so every chaos test
replays exactly.

Two seam families consume the plan:

- **dispatch seams** call :func:`maybe_fail(site)
  <maybe_fail>` just before firing a compiled program
  (``resilience.guarded`` does this for every guarded site): a
  matching spec raises :class:`InjectedTransientError` /
  :class:`InjectedFatalError` (status-prefixed messages shaped like
  ``XlaRuntimeError`` text, so the retry classifier exercises its real
  matching), or sleeps ``delay_s`` (``delay`` — added latency; a
  ``hang`` is the same sleep, long enough that only the guarded
  watchdog can cut it).
- **data seams** call :func:`corrupt_slab(site, arr) <corrupt_slab>`
  on an incoming sample slab (the receivers' push paths): ``nan_slab``
  NaN-poisons a deterministic fraction of the rows, ``truncate`` drops
  a deterministic tail fraction — the two input-poisoning faults the
  quarantine machinery exists to contain.
- **io seams** call :func:`io_fault(site, data) <io_fault>` on every
  byte payload the durability layer (``runtime/durability.py``) is
  about to write: ``io_torn`` truncates the write (the torn-record
  crash the CRC framing catches on replay), ``io_enospc`` raises
  ``OSError(ENOSPC)`` like a full disk.

Sites are matched by :mod:`fnmatch` pattern, so one spec can cover a
family (``"rx.push.s*"`` — note fnmatch treats ``[...]`` as a
character class, which is why the per-stream sites are dot-named)
while the per-site call counters keep every concrete site's schedule
independent.

The CLI exposes the layer as ``--chaos SPEC`` / ``ZIRIA_CHAOS``
(scoped-env pattern; :func:`env_chaos` is the single reader, jaxlint
R4). Spec grammar, semicolon-separated::

    [seed=N;]site:kind[:key=val[,key=val...]][;site:kind...]

with keys ``every=N`` (fire every Nth call), ``calls=i+j+k`` (explicit
0-based call indices), ``p=F`` (probability, hashed from
(site, seed, index)), ``count=N`` (max firings), ``delay=F`` (seconds,
for delay/hang), ``frac=F`` (slab fraction, for nan_slab/truncate),
``profile=NAME`` (a phy/profiles channel-profile name, for the
``channel`` kind — default ``hostile``).
Examples: ``ZIRIA_CHAOS="seed=3;rx.stream_chunk*:transient:every=7"``,
``ZIRIA_CHAOS="rx.push.s*:channel:profile=severe,every=2"``.
"""

from __future__ import annotations

import fnmatch
import hashlib
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

_LOCK = threading.Lock()            # guards (de)activation only
_PLANS: Tuple["FaultPlan", ...] = ()

#: the injectable fault classes (the docs/robustness.md table)
KINDS = ("nan_slab", "truncate", "transient", "fatal", "delay", "hang",
         "io_torn", "io_enospc", "channel")

#: kinds that act at data (push) seams vs dispatch seams vs the
#: durability write seams (journal append / snapshot file writes).
#: ``channel`` is a data kind: it passes the slab through a named
#: physical-channel profile (phy/profiles — multipath FIR, SCO
#: resample, drift phase, interference bursts) in PURE NUMPY, so the
#: chaos layer stays jax-free (tools/chaos_smoke.py's no-jax pin).
#: Applied per-slab it is a chaos corruption, not stream physics —
#: frames straddling slab boundaries see filter seams, exactly the
#: kind of hostile input the quarantine/CRC machinery must absorb
#: without crashing; the physically-continuous stimulus lives in
#: link.stream_many(channel_profile=...).
DATA_KINDS = ("nan_slab", "truncate", "channel")
DISPATCH_KINDS = ("transient", "fatal", "delay", "hang")
IO_KINDS = ("io_torn", "io_enospc")


class InjectedFault(Exception):
    """Base of the injected error classes (never raised itself)."""


class InjectedTransientError(InjectedFault):
    """An injected *transient* dispatch failure — message styled like
    a retryable ``XlaRuntimeError`` (``UNAVAILABLE: ...``) so the
    guarded-dispatch classifier exercises its real marker matching."""


class InjectedFatalError(InjectedFault):
    """An injected *fatal* dispatch failure — a non-retryable status
    (``INVALID_ARGUMENT: ...``): retrying cannot heal it, the guarded
    site must degrade or raise."""


class FaultSpec(NamedTuple):
    """One injectable fault: fire ``kind`` at sites matching the
    fnmatch pattern ``site`` on the calls selected by exactly one of
    ``calls`` (explicit 0-based per-site call indices), ``every``
    (every Nth call), or ``p`` (probability, decided by a hash of
    (site, seed, call-index) — still fully deterministic). ``count``
    bounds total firings (0 = unbounded); ``delay_s`` is the sleep of
    delay/hang kinds; ``fraction`` the slab share nan_slab/truncate
    touch."""
    site: str
    kind: str
    calls: Tuple[int, ...] = ()
    every: int = 0
    p: float = 0.0
    count: int = 0
    delay_s: float = 0.01
    fraction: float = 0.25
    #: channel-profile name for the ``channel`` kind (grammar key
    #: ``profile=NAME``; default ``hostile`` — validated against
    #: phy/profiles.CHANNEL_PROFILES at plan construction)
    profile: str = "hostile"


def _unit(site: str, seed: int, idx: int) -> float:
    """Deterministic uniform in [0, 1) from (site, seed, call-index):
    the probabilistic specs' coin, identical on every replay."""
    h = hashlib.sha256(f"{site}\x00{seed}\x00{idx}".encode()).digest()
    return int.from_bytes(h[:8], "big") / float(1 << 64)


class FaultPlan:
    """The active decision state of one :func:`inject` scope: per-site
    call counters (thread-safe), per-spec firing counts, and a log of
    every fired fault (``fired``: (site, kind, call-index) tuples, the
    attribution record chaos benches assert against)."""

    def __init__(self, specs, seed: int = 0):
        specs = tuple(specs)
        for sp in specs:
            if sp.kind not in KINDS:
                raise ValueError(
                    f"unknown fault kind {sp.kind!r} (known: {KINDS})")
            if sum((len(sp.calls) > 0, sp.every > 0, sp.p > 0)) != 1:
                raise ValueError(
                    f"spec {sp.site}:{sp.kind} needs exactly one of "
                    f"calls=/every=/p= to select its firing calls")
            if sp.kind == "channel":
                # jax-free import (phy/profiles is plain data) —
                # unknown profile names fail at plan construction
                # with the registry's own known-names message
                from ziria_tpu.phy.profiles import get_profile
                get_profile(sp.profile)
        self.specs = specs
        self.seed = int(seed)
        self._lock = threading.Lock()
        self._idx: Dict[str, int] = {}       # concrete site -> calls
        self._spec_fired = [0] * len(specs)
        self.fired: List[Tuple[str, str, int]] = []

    def decide(self, site: str, kinds) -> Optional[Tuple[FaultSpec, int]]:
        """Advance ``site``'s call counter and return the first
        matching spec (restricted to ``kinds``) that fires at this
        call, with the call index — or None. One counter per concrete
        site string: determinism is per (site, seed, call-index)."""
        with self._lock:
            idx = self._idx.get(site, 0)
            self._idx[site] = idx + 1
            for j, sp in enumerate(self.specs):
                if sp.kind not in kinds:
                    continue
                if sp.count and self._spec_fired[j] >= sp.count:
                    continue
                if not fnmatch.fnmatchcase(site, sp.site):
                    continue
                if sp.calls:
                    hit = idx in sp.calls
                elif sp.every:
                    hit = (idx + 1) % sp.every == 0
                else:
                    # fold the spec position in so two p-specs on one
                    # site draw independent coins
                    hit = _unit(f"{site}#{j}", self.seed, idx) < sp.p
                if hit:
                    self._spec_fired[j] += 1
                    self.fired.append((site, sp.kind, idx))
                    return sp, idx
        return None

    @property
    def total_fired(self) -> int:
        with self._lock:
            return len(self.fired)

    def fired_sites(self) -> Dict[str, int]:
        """site -> fired count (the per-stream attribution record)."""
        out: Dict[str, int] = {}
        with self._lock:
            for s, _k, _i in self.fired:
                out[s] = out.get(s, 0) + 1
        return out


def active() -> bool:
    """True when any fault plan is injecting (every seam's slow path
    gates on this; the fast path is one tuple truthiness check)."""
    return bool(_PLANS)


@contextmanager
def inject(*specs: FaultSpec, seed: int = 0,
           plan: Optional[FaultPlan] = None):
    """Activate a :class:`FaultPlan` for the block (a fresh one from
    ``specs`` + ``seed``, or the one passed in); yields the plan so
    the caller can read its firing log afterwards. Nests and overlaps
    freely — every active plan sees every seam call (the telemetry
    activation contract)."""
    global _PLANS
    p = plan if plan is not None else FaultPlan(specs, seed=seed)
    with _LOCK:
        _PLANS = _PLANS + (p,)
    try:
        yield p
    finally:
        with _LOCK:
            lst = list(_PLANS)
            for i in range(len(lst) - 1, -1, -1):
                if lst[i] is p:      # remove ONE occurrence (nesting)
                    del lst[i]
                    break
            _PLANS = tuple(lst)


def maybe_fail(site: str) -> None:
    """The dispatch seam: called just before a guarded compiled
    program fires. A matching ``delay``/``hang`` spec sleeps
    ``delay_s`` (a hang is contained only by the guarded watchdog); a
    ``transient``/``fatal`` spec raises the corresponding injected
    error. Free when no plan is active (one truthiness check)."""
    if not _PLANS:
        return
    for plan in _PLANS:
        got = plan.decide(site, DISPATCH_KINDS)
        if got is None:
            continue
        sp, idx = got
        if sp.kind in ("delay", "hang"):
            time.sleep(sp.delay_s)
        elif sp.kind == "transient":
            raise InjectedTransientError(
                f"UNAVAILABLE: injected transient fault at {site} "
                f"(call {idx})")
        else:
            raise InjectedFatalError(
                f"INVALID_ARGUMENT: injected fatal fault at {site} "
                f"(call {idx})")


def _channel_slab(arr: np.ndarray, profile: str, seed: int,
                  idx: int) -> np.ndarray:
    """The ``channel`` data kind: pass a slab through a named
    physical-channel profile in pure numpy — multipath FIR + SCO
    resample (the jax-free host twins in phy/profiles), a drift phase
    ramp from the slab's own origin, and seeded interference bursts
    (numpy RNG keyed by the plan's (site, seed, call-index) hash, so
    every replay corrupts identically). Per-slab application is a
    deterministic hostile-input FAULT (boundary seams included), not
    continuous stream physics."""
    from ziria_tpu.phy.profiles import get_profile, np_apply_drift, \
        np_apply_sco, np_apply_taps, np_burst_amp, np_burst_mask

    prof = get_profile(profile)
    x = np_apply_taps(np.asarray(arr, np.float32), prof)
    x = np_apply_sco(x, prof.sco)
    x = np_apply_drift(x, prof.drift)
    n = x.shape[0]
    if prof.burst_every and n:
        rs = np.random.default_rng(int(_unit(f"chan:{profile}", seed,
                                             idx) * (1 << 53)))
        off = int(rs.integers(0, prof.burst_every))
        in_burst = np_burst_mask(n, prof, off)
        p_sig = float(np.mean(np.square(x.astype(np.float64)))) * 2.0
        amp = np_burst_amp(p_sig, prof)
        x = (x + rs.normal(size=x.shape)
             * (amp * in_burst.astype(np.float64))[:, None]) \
            .astype(np.float32)
    return x


def corrupt_slab(site: str, arr: np.ndarray):
    """The data seam: called on an incoming (n, 2) sample slab at the
    push surfaces. A matching ``nan_slab`` spec NaN-poisons a
    deterministic ``fraction`` of the rows (row choice seeded by
    (site, seed, call-index)); ``truncate`` drops the tail
    ``fraction``; ``channel`` passes the slab through its named
    physical-channel profile (`_channel_slab` — multipath/SCO/drift/
    bursts, pure numpy). Returns ``(slab, kinds)`` — the (possibly
    copied) slab and the tuple of injected kinds (empty when nothing
    fired). Free when no plan is active."""
    if not _PLANS:
        return arr, ()
    kinds: List[str] = []
    for plan in _PLANS:
        got = plan.decide(site, DATA_KINDS)
        if got is None:
            continue
        sp, idx = got
        n = int(arr.shape[0]) if arr.ndim else 0
        if sp.kind == "nan_slab" and n:
            arr = np.array(arr, copy=True)
            k = max(1, int(n * sp.fraction))
            rs = np.random.default_rng(
                int(_unit(site, plan.seed, idx) * (1 << 53)))
            rows = rs.choice(n, size=min(k, n), replace=False)
            arr[rows] = np.nan
        elif sp.kind == "truncate" and n > 1:
            keep = max(1, n - max(1, int(n * sp.fraction)))
            arr = arr[:keep]
        elif sp.kind == "channel" and n:
            arr = _channel_slab(arr, sp.profile, plan.seed, idx)
        kinds.append(sp.kind)
    return arr, tuple(kinds)


def io_fault(site: str, data: bytes) -> bytes:
    """The durability write seam (runtime/durability.py calls this on
    every byte payload it is about to put on disk — journal record
    frames and snapshot files alike). A matching ``io_torn`` spec
    returns a TRUNCATED prefix of ``data`` (at least one byte dropped
    — the torn-write crash the CRC framing exists to catch); an
    ``io_enospc`` spec raises ``OSError(ENOSPC)`` exactly as a full
    disk would. Free when no plan is active (one truthiness check)."""
    if not _PLANS:
        return data
    import errno

    for plan in _PLANS:
        got = plan.decide(site, IO_KINDS)
        if got is None:
            continue
        sp, idx = got
        if sp.kind == "io_enospc":
            raise OSError(
                errno.ENOSPC,
                f"No space left on device (injected at {site}, "
                f"call {idx})")
        keep = min(len(data) - 1,
                   int(len(data) * (1.0 - sp.fraction)))
        data = data[: max(0, keep)]
    return data


# ----------------------------------------------------------- env knob


def parse_chaos_spec(text: str) -> Tuple[Tuple[FaultSpec, ...], int]:
    """Parse the ``--chaos`` / ``ZIRIA_CHAOS`` grammar into
    ``(specs, seed)``. Raises ValueError on malformed specs (the CLI
    surfaces it as a flag error, never a silent no-chaos run)."""
    specs: List[FaultSpec] = []
    seed = 0
    for item in (s.strip() for s in text.split(";")):
        if not item:
            continue
        if item.startswith("seed="):
            seed = int(item[5:])
            continue
        parts = item.split(":")
        if len(parts) < 2:
            raise ValueError(
                f"chaos spec {item!r}: want site:kind[:key=val,...]")
        site, kind = parts[0], parts[1]
        kw: Dict[str, object] = {}
        for opt in ":".join(parts[2:]).split(","):
            opt = opt.strip()
            if not opt:
                continue
            if "=" not in opt:
                raise ValueError(f"chaos option {opt!r}: want key=val")
            k, v = opt.split("=", 1)
            if k == "every":
                kw["every"] = int(v)
            elif k == "calls":
                kw["calls"] = tuple(int(c) for c in v.split("+"))
            elif k == "p":
                kw["p"] = float(v)
            elif k == "count":
                kw["count"] = int(v)
            elif k == "delay":
                kw["delay_s"] = float(v)
            elif k == "frac":
                kw["fraction"] = float(v)
            elif k == "profile":
                kw["profile"] = v
            else:
                raise ValueError(f"unknown chaos option {k!r}")
        if not (kw.get("calls") or kw.get("every") or kw.get("p")):
            kw["every"] = 1          # bare spec: fire every call
        specs.append(FaultSpec(site=site, kind=kind, **kw))
    # self-validate (kinds, selector combos) so EVERY consumer of the
    # grammar — the CLI flag path and a directly-exported ZIRIA_CHAOS
    # alike — fails at parse time with one clear message
    FaultPlan(specs, seed=seed)
    return tuple(specs), seed


def env_chaos() -> Optional[Tuple[Tuple[FaultSpec, ...], int]]:
    """The ONE reading of the ``ZIRIA_CHAOS`` knob (the CLI's
    ``--chaos`` writes it via the scoped-env pattern): a spec string
    means 'run this invocation under the described fault plan'.
    Returns ``(specs, seed)`` or None when unset/empty."""
    import os

    text = os.environ.get("ZIRIA_CHAOS")
    if not text:
        return None
    return parse_chaos_spec(text)
