#!/usr/bin/env python
"""Chaos-soak harness for the DURABLE serving runtime (ISSUE 14).

A seeded randomized fault campaign over the crash-tolerant server:
every PR 12 fault kind (nan_slab / truncate at the push seams,
transient / fatal / delay / hang at the dispatch seams), the new
``io_torn`` / ``io_enospc`` kinds at the durability write seams
(journal appends, snapshot files), plus REAL process death —
subprocess rounds SIGKILLed mid-chunk-step — each round ending in a
crash and a ``ServeRuntime.recover``. Gates:

- **zero crashes**: no round may raise out of the serving loop or the
  recovery; injected faults are contained, retried, degraded, or
  journaled — never fatal to the harness.
- **bit-identity**: every delivered frame equals the uninterrupted
  oracle's frame at the same (session, start) — delivery is
  at-least-once (duplicates allowed and counted; (sid, start) is the
  idempotency key), and sessions untouched by data-poisoning faults
  must deliver the COMPLETE oracle set. NaN-poisoned sessions gate as
  subsets (quarantine drops, never corrupts); truncate-poisoned
  sessions gate on no-crash only (their stream genuinely differs).
- **recovery latency SLO**: ``recover()`` wall time per round, gated
  at p99 (``recovery_p99_s``, lower is better).
- **dispatch budget after recovery**: <= 2 dispatches per chunk-step
  on the recovered fleet, under ``dispatch.no_recompile`` for the
  unchanged-geometry case — recovery must not cost the compiled
  programs their one-compile contract.

:func:`soak_stats` is the campaign (smoke-sized on CPU); ``--child``
is the subprocess serving loop the SIGKILL rounds shoot. The jax-free protocol canary is
tools/durability_smoke.py — this harness is the full-device proof.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

N_BYTES = 12
GEO = dict(chunk_len=4096, frame_len=1024, max_frames_per_chunk=8,
           check_fcs=True)

#: the full kind menu a campaign round draws from (site, kind, kwargs)
DISPATCH_MENU = [
    ("rx.stream_chunk_multi", "transient", {"every": 4}),
    ("rx.stream_decode_multi", "transient", {"every": 3}),
    ("rx.stream_decode_multi", "fatal", {"calls": (2,), "count": 1}),
    ("rx.stream_chunk_multi", "delay", {"every": 5, "delay_s": 0.02}),
    ("rx.stream_chunk_multi", "hang",
     {"calls": (3,), "count": 1, "delay_s": 8.0}),
]
DATA_MENU = [
    ("rx.push.s*", "nan_slab", {"every": 7, "fraction": 0.2}),
    ("rx.push.s*", "truncate", {"every": 9, "fraction": 0.2}),
]
IO_MENU = [
    ("journal.append", "io_torn", {"every": 6, "fraction": 0.5}),
    ("journal.append", "io_enospc", {"every": 11}),
    ("snapshot.lane", "io_enospc", {"calls": (1,), "count": 1}),
    ("snapshot.meta", "io_torn", {"calls": (0,), "count": 1,
                                  "fraction": 0.3}),
]


def _same(a, b) -> bool:
    return (a.start == b.start and a.result.ok == b.result.ok
            and a.result.rate_mbps == b.result.rate_mbps
            and a.result.length_bytes == b.result.length_bytes
            and np.array_equal(np.asarray(a.result.psdu_bits),
                               np.asarray(b.result.psdu_bits))
            and a.result.crc_ok == b.result.crc_ok)


def _clients(n_sessions: int, frames_per_session: int, seed: int,
             channel_profile=None):
    from ziria_tpu.runtime import serve
    return serve.synth_load(n_sessions, frames_per_session,
                            n_bytes=N_BYTES, snr_db=30.0, seed=seed,
                            tail=GEO["frame_len"],
                            channel_profile=channel_profile)


def _oracle(clients):
    from ziria_tpu.backend import framebatch
    return {c.sid: framebatch.receive_stream(c.stream, **GEO)[0]
            for c in clients}


def _serve_until_crash(cfg, clients, crash_after: int, got):
    """Run a fresh server, pushing each client's stream in ragged
    slabs, until ``crash_after`` frames were delivered (or the input
    is exhausted) — then ABANDON the runtime mid-flight: no drain, no
    close, exactly what a SIGKILL leaves behind, minus the process.
    Returns the abandoned runtime (for accounting reads only)."""
    from ziria_tpu.runtime import serve

    srv = serve.ServeRuntime(cfg)
    delivered = 0
    with srv:
        for c in clients:
            srv.connect(c.sid)
        pos = {c.sid: 0 for c in clients}
        idle = 0
        while idle < 3:
            moved = False
            for c in clients:
                lo = pos[c.sid]
                hi = min(lo + 1700, c.stream.shape[0])
                if lo < hi:
                    if srv.submit(c.sid, c.stream[lo:hi]).accepted:
                        pos[c.sid] = hi
                    moved = True
            frames = srv.step()
            for sid, f in frames:
                got[sid].append(f)
                delivered += 1
            if delivered >= crash_after:
                break
            idle = 0 if (moved or frames) else idle + 1
        srv._drained = True          # the crash: nothing cleans up
    return srv


def _finish_recovered(srv2, clients, got):
    """The documented client recovery protocol: take the replayed
    rider frames, resubmit every live session's stream from its
    ``acked`` coordinate (a session the journal lost entirely —
    ENOSPC ate its admit record — reconnects fresh and resubmits from
    zero; the dedupe key (sid, start) absorbs any re-delivery), drive
    to quiescence, drain."""
    with srv2:
        for sid, f in srv2.replayed:
            got[sid].append(f)
        for c in clients:
            if c.sid not in srv2._sessions:
                if c.sid in srv2._gone:
                    continue             # terminally accounted
                srv2.connect(c.sid)      # journal-lost: fresh session
            if not (c.sid in srv2._sessions):
                continue                 # queue full: give up politely
            acked = srv2.acked(c.sid)
            for lo in range(acked, c.stream.shape[0], 1 << 14):
                srv2.submit(c.sid,
                            c.stream[lo: lo + (1 << 14)])
        idle = 0
        while idle < 3:
            frames = srv2.step()
            for sid, f in frames:
                got[sid].append(f)
            idle = 0 if frames else idle + 1
        for sid, f in srv2.drain():
            got[sid].append(f)


def _verify(clients, oracle, got, nan_sids, trunc_sids):
    """The identity gate. Returns (duplicates, frames_checked)."""
    dups = 0
    checked = 0
    for c in clients:
        if c.sid in trunc_sids:
            continue          # stream genuinely differs: no-crash only
        by_start = {}
        for f in got[c.sid]:
            if f.start in by_start:
                assert _same(f, by_start[f.start]), \
                    f"{c.sid}: duplicate at {f.start} differs"
                dups += 1
                continue
            by_start[f.start] = f
        want = {f.start: f for f in oracle[c.sid]}
        for start, f in by_start.items():
            assert start in want, \
                f"{c.sid}: unexpected frame at {start}"
            assert _same(f, want[start]), \
                f"{c.sid}: frame at {start} differs from oracle"
            checked += 1
        if c.sid not in nan_sids:
            missing = sorted(set(want) - set(by_start))
            assert not missing, \
                f"{c.sid}: frames missing after recovery: {missing}"
    return dups, checked


def _affected_sids(plan, lane_sid):
    """Map fired data-seam sites (rx.push.s<lane>) back to sessions."""
    nan_s, trunc_s = set(), set()
    for site, kind, _idx in plan.fired:
        if not site.startswith("rx.push.s"):
            continue
        lane = int(site[len("rx.push.s"):])
        sid = lane_sid.get(lane)
        if sid is None:
            continue
        (nan_s if kind == "nan_slab" else trunc_s).add(sid)
    return nan_s, trunc_s


def _round_specs(rng, dirty: bool):
    """Draw a seeded spec set for one round: always >= 1 dispatch
    kind and >= 1 io kind; data-poisoning kinds only on dirty
    rounds (their sessions cannot gate completeness)."""
    from ziria_tpu.utils import faults
    picks = [DISPATCH_MENU[i] for i in
             rng.choice(len(DISPATCH_MENU),
                        size=1 + int(rng.integers(0, 3)),
                        replace=False)]
    picks += [IO_MENU[i] for i in
              rng.choice(len(IO_MENU), size=1 + int(rng.integers(0, 2)),
                         replace=False)]
    if dirty:
        picks += [DATA_MENU[int(rng.integers(0, len(DATA_MENU)))]]
    return [faults.FaultSpec(site, kind, **kw)
            for site, kind, kw in picks]


def run_round(clients, oracle, cfg, seed: int, dirty: bool,
              budget: bool = False) -> dict:
    """One in-process campaign round: serve under a seeded fault plan
    (dispatch + io kinds, push kinds on dirty rounds), crash, recover
    with the fault plan GONE (the chaos died with the process),
    verify, time the recovery. ``budget=True`` additionally pins the
    POST-RECOVERY dispatch budget — <= 2 dispatches per chunk-step on
    the recovered fleet, zero recompiles (the unchanged-geometry
    acceptance gate; the pre-crash phase is excluded because injected
    transients legitimately retry as extra dispatches)."""
    from ziria_tpu.runtime import serve
    from ziria_tpu.utils import faults

    rng = np.random.default_rng(seed)
    specs = _round_specs(rng, dirty)
    got = {c.sid: [] for c in clients}
    crash_after = 1 + int(rng.integers(0, 3))
    with faults.inject(*specs, seed=seed) as plan:
        srv = _serve_until_crash(cfg, clients, crash_after, got)
        lane_sid = dict(srv._lane_sid)
    nan_s, trunc_s = _affected_sids(plan, lane_sid)
    st = srv.stats()

    t0 = time.perf_counter()
    srv2 = serve.ServeRuntime.recover(cfg.snapshot_dir, config=cfg)
    recovery_s = time.perf_counter() - t0
    dpcs = None
    if budget:
        from ziria_tpu.phy.wifi import rx as _rx
        from ziria_tpu.utils import dispatch
        with dispatch.no_recompile(_rx._jit_stream_chunk_multi,
                                   _rx._jit_stream_decode_multi):
            with dispatch.count_dispatches() as dc:
                _finish_recovered(srv2, clients, got)
        steps = int(srv2.stats().chunk_steps)
        if steps:
            dpcs = round(dc.total / steps, 2)
            assert dpcs <= 2.0 + 1e-9, \
                (f"dispatch budget broken after recovery: "
                 f"{dc.total} dispatches / {steps} chunk-steps")
    else:
        _finish_recovered(srv2, clients, got)
    dups, checked = _verify(clients, oracle, got, nan_s, trunc_s)
    st2 = srv2.stats()
    return {"recovery_s": recovery_s, "faults": len(plan.fired),
            "by_kind": _by_kind(plan), "duplicates": dups,
            "frames_checked": checked, "deduped": st2.deduped,
            "snapshots": st.snapshots + st2.snapshots,
            "journal_errors": st.journal_errors
            + st2.journal_errors,
            "dpcs": dpcs,
            "nan_sessions": sorted(map(str, nan_s)),
            "trunc_sessions": sorted(map(str, trunc_s))}


def _by_kind(plan) -> dict:
    out: dict = {}
    for _site, kind, _idx in plan.fired:
        out[kind] = out.get(kind, 0) + 1
    return out


# ----------------------------------------------------- SIGKILL rounds


def _child_main(args) -> int:
    """``--child``: the serving loop the SIGKILL rounds shoot. Builds
    the SAME seeded client set as the parent, serves with journaling
    + per-step snapshots, prints one flushed JSON line per delivered
    frame (delivery-before-mark: the parent's record of what the dead
    process delivered), and sleeps a little each tick so the parent
    can reliably land the kill mid-run."""
    from ziria_tpu.runtime import durability, serve

    clients = _clients(args.sessions, args.frames, args.seed)
    cfg = serve.ServeConfig(n_lanes=args.lanes, queue_cap=16,
                            sanitize=True,
                            snapshot_dir=args.dir, snapshot_every=1,
                            **GEO)
    got_n = 0
    srv = serve.ServeRuntime(cfg)
    with srv:
        for c in clients:
            srv.connect(c.sid)
        pos = {c.sid: 0 for c in clients}
        idle = 0
        while idle < 3:
            moved = False
            for c in clients:
                lo = pos[c.sid]
                hi = min(lo + 1500, c.stream.shape[0])
                if lo < hi:
                    if srv.submit(c.sid, c.stream[lo:hi]).accepted:
                        pos[c.sid] = hi
                    moved = True
            frames = srv.step()
            for sid, f in frames:
                print(json.dumps({"sid": sid,
                                  "f": durability.encode_frame(f)}),
                      flush=True)
                got_n += 1
            idle = 0 if (moved or frames) else idle + 1
            time.sleep(args.tick_sleep)
        for sid, f in srv.drain():
            print(json.dumps({"sid": sid,
                              "f": durability.encode_frame(f)}),
                  flush=True)
            got_n += 1
    print(json.dumps({"done": got_n}), flush=True)
    return 0


def run_sigkill_round(clients, oracle, workdir: str, seed: int,
                      n_lanes: int, frames_per_session: int,
                      tick_sleep: float = 0.05) -> dict:
    """One REAL process-death round: spawn the ``--child`` serving
    subprocess, SIGKILL it once frames are flowing (mid-chunk-step —
    the child sleeps between ticks, so the kill lands inside live
    journal/snapshot traffic), then recover the fleet IN THIS PROCESS
    from the directory the corpse left behind and finish the streams.
    The child's flushed stdout lines are the delivered-frame record a
    real client would hold; a torn last line is dropped exactly like
    a torn journal tail."""
    from ziria_tpu.runtime import durability, serve

    rng = np.random.default_rng(seed)
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--dir", workdir, "--seed", str(seed),
         "--sessions", str(len(clients)), "--lanes", str(n_lanes),
         "--frames", str(frames_per_session),
         "--tick-sleep", str(tick_sleep)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))),
        env={**os.environ, "JAX_PLATFORMS":
             os.environ.get("JAX_PLATFORMS", "cpu")})
    lines: list = []
    kill_after = 1 + int(rng.integers(0, 2))
    killed = False

    def reader():
        for raw in child.stdout:
            lines.append(raw)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    deadline = time.time() + 600
    while child.poll() is None and time.time() < deadline:
        n_frames = sum(1 for ln in lines if b'"sid"' in ln)
        if n_frames >= kill_after:
            time.sleep(float(rng.uniform(0.0, 2 * tick_sleep)))
            try:
                os.kill(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            killed = True
            break
        time.sleep(0.01)
    child.wait(timeout=60)
    t.join(timeout=10)

    got = {c.sid: [] for c in clients}
    done = False
    for raw in lines:
        try:
            d = json.loads(raw.decode())
        except Exception:
            continue        # torn final line: dropped like a torn tail
        if "done" in d:
            done = True
            continue
        got[d["sid"]].append(durability.decode_frame(d["f"]))

    recovery_s = 0.0
    if not done:
        cfg = serve.ServeConfig(n_lanes=n_lanes, queue_cap=16,
                                sanitize=True, snapshot_dir=workdir,
                                snapshot_every=1, **GEO)
        t0 = time.perf_counter()
        srv2 = serve.ServeRuntime.recover(workdir, config=cfg)
        recovery_s = time.perf_counter() - t0
        _finish_recovered(srv2, clients, got)
    dups, checked = _verify(clients, oracle, got, set(), set())
    return {"recovery_s": recovery_s, "killed": killed,
            "kill_missed": done, "duplicates": dups,
            "frames_checked": checked,
            "pre_kill_frames": sum(
                1 for ln in lines if b'"sid"' in ln)}


# --------------------------------------------------------- the harness


def soak_stats(n_sessions: int = 3, n_lanes: int = 4,
               frames_per_session: int = 4, rounds: int = 3,
               sigkill_rounds: int = 1, seed: int = 20260804,
               recovery_slo_s: float = 30.0,
               tick_sleep: float = 0.05,
               channel_profile: str = "urban") -> dict:
    """The campaign: in-process
    fault rounds (alternating clean-data / dirty-data spec draws) +
    real SIGKILL subprocess rounds, all gated, recovery latencies
    aggregated to ``recovery_p99_s``. The campaign
    additionally runs ONE multipath-active round (ISSUE 15): every
    client's stream rides the named physical-channel profile
    (phy/profiles; an equalizable tap set, so the oracle is complete)
    while the usual dispatch/io faults fire and the server crashes
    and recovers — physical faults and software faults campaigned
    TOGETHER, gated on zero crashes and the same per-session
    bit-identity vs the profiled oracle."""
    from ziria_tpu.runtime import serve

    clients = _clients(n_sessions, frames_per_session, seed)
    oracle = _oracle(clients)
    n_oracle = sum(len(v) for v in oracle.values())
    chan_clients = _clients(n_sessions, frames_per_session, seed + 1,
                            channel_profile=channel_profile)
    chan_oracle = _oracle(chan_clients)

    times: list = []
    by_kind: dict = {}
    totals = {"faults": 0, "duplicates": 0, "deduped": 0,
              "snapshots": 0, "journal_errors": 0}
    budget_checked = False
    dpcs = None

    with tempfile.TemporaryDirectory(prefix="ziria-soak-") as root:
        # warm pass: the fleet programs compile ONCE here, so the
        # chaos rounds' watchdogs never mistake a cold compile for a
        # hang, and the budget round can pin no_recompile
        warm_cfg = serve.ServeConfig(
            n_lanes=n_lanes, queue_cap=16, sanitize=True,
            watchdog_s=None,
            snapshot_dir=os.path.join(root, "warm"),
            snapshot_every=4, **GEO)
        got = {c.sid: [] for c in clients}
        _serve_until_crash(warm_cfg, clients, 10 ** 9, got)

        for r in range(rounds):
            d = os.path.join(root, f"round-{r}")
            cfg = serve.ServeConfig(
                n_lanes=n_lanes, queue_cap=16, sanitize=True,
                watchdog_s=2.0, snapshot_dir=d, snapshot_every=1,
                **GEO)
            # the LAST round is the unchanged-geometry budget gate:
            # <= 2 dispatches/chunk-step on the recovered fleet
            # under dispatch.no_recompile
            ev = run_round(clients, oracle, cfg, seed + 17 * r,
                           dirty=bool(r % 2),
                           budget=(r == rounds - 1))
            if ev["dpcs"] is not None:
                dpcs = ev["dpcs"]
                budget_checked = True
            times.append(ev["recovery_s"])
            for k, v in ev["by_kind"].items():
                by_kind[k] = by_kind.get(k, 0) + v
            for k in totals:
                totals[k] += ev[k]

        # the multipath-active crash->recover round: profiled client
        # streams (same geometry, own oracle) under a DIRTY-round
        # fault draw (dispatch + io + data-poisoning kinds) —
        # physical chaos UNDER software chaos, one campaign
        d = os.path.join(root, "round-channel")
        cfg = serve.ServeConfig(
            n_lanes=n_lanes, queue_cap=16, sanitize=True,
            watchdog_s=2.0, snapshot_dir=d, snapshot_every=1,
            **GEO)
        chan_ev = run_round(chan_clients, chan_oracle, cfg,
                            seed + 991, dirty=True)
        times.append(chan_ev["recovery_s"])
        for k, v in chan_ev["by_kind"].items():
            by_kind[k] = by_kind.get(k, 0) + v
        for k in totals:
            totals[k] += chan_ev[k]

        kills = {"killed": 0, "kill_missed": 0}
        for r in range(sigkill_rounds):
            d = os.path.join(root, f"kill-{r}")
            ev = run_sigkill_round(clients, oracle, d,
                                   seed, n_lanes,
                                   frames_per_session,
                                   tick_sleep=tick_sleep)
            if ev["recovery_s"]:
                times.append(ev["recovery_s"])
            totals["duplicates"] += ev["duplicates"]
            kills["killed"] += int(ev["killed"])
            kills["kill_missed"] += int(ev["kill_missed"])

    p50 = float(np.percentile(times, 50)) if times else 0.0
    p99 = float(np.percentile(times, 99)) if times else 0.0
    assert p99 <= recovery_slo_s, \
        f"recovery p99 {p99:.2f}s exceeds the {recovery_slo_s}s SLO"
    return {"sessions": n_sessions, "lanes": n_lanes,
            "rounds": rounds, "sigkill_rounds": sigkill_rounds,
            "oracle_frames": n_oracle,
            "faults_injected": totals["faults"],
            "faults_by_kind": by_kind,
            "recovery_p50_s": round(p50, 4),
            "recovery_p99_s": round(p99, 4),
            "recovery_rounds_timed": len(times),
            "duplicates": totals["duplicates"],
            "deduped": totals["deduped"],
            "snapshots": totals["snapshots"],
            "journal_errors": totals["journal_errors"],
            "dispatches_per_chunk_step_post_recovery": dpcs,
            "budget_checked": budget_checked,
            "kills": kills, "identity": "bit_identical",
            "channel_profile": channel_profile,
            "channel_round_frames": chan_ev["frames_checked"],
            "channel_round_faults": chan_ev["faults"],
            "zero_crashes": True}


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="soak", description="chaos-soak the durable serving "
                                 "runtime (docs/robustness.md)")
    p.add_argument("--child", action="store_true",
                   help="internal: the SIGKILL target serving loop")
    p.add_argument("--dir", default=None)
    p.add_argument("--seed", type=int, default=20260804)
    p.add_argument("--sessions", type=int, default=3)
    p.add_argument("--lanes", type=int, default=4)
    p.add_argument("--frames", type=int, default=4)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--sigkill-rounds", type=int, default=1)
    p.add_argument("--tick-sleep", type=float, default=0.05)
    p.add_argument("--recovery-slo", type=float, default=30.0)
    args = p.parse_args(argv)
    if args.child:
        if not args.dir:
            raise SystemExit("--child needs --dir")
        return _child_main(args)
    ev = soak_stats(args.sessions, args.lanes, args.frames,
                    args.rounds, args.sigkill_rounds, args.seed,
                    recovery_slo_s=args.recovery_slo,
                    tick_sleep=args.tick_sleep)
    print(json.dumps(ev, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
