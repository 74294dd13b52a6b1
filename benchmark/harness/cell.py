"""One run of one cell in one process: load, warm, measure, check, print.

Lifted from chip_smoke.py: the same objects ``python -m ziria_tpu serve``
uses (``ServeRuntime`` -> ``MultiStreamReceiver`` -> the two compiled
programs), warmed on idle input, then driven by the benchmark's own loop
and clock. The last line of standard output is the one contract line;
a rehearsal (``--rehearse``) never prints it.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

from . import annotations, checks, counts, load, loop, manifest, peaks, \
    spans, steady, xplane

#: closed-loop ticks before the window: the first fills the lanes, the
#: second launches the first chunk-step, the third drains it through
#: the decode, the fourth is the first in steady state
WARM_TICKS = 4
#: chunk-steps the fleet launches under the profiler in a traced run:
#: a step is launched in one and drained in the next, so five hold the
#: four whole flights of the first four
PROFILED_STEPS = 5
#: and the seconds after which the profiler stops whatever was launched:
#: a fleet that launches less than once a second is traced for these
#: and no longer (today's five chunk-steps take 2.2 s)
PROFILED_SECONDS = 6.0
#: a tick this long (a chunk-step is about half a second) has its
#: whereabouts noted by the stall watch
STALL_S = 2.0


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's record
    of it: imports done before the benchmark's first line count."""
    with open("/proc/self/stat") as f:
        start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


def chunk_shapes(rx):
    """The chunk scan's arguments as `_launch` builds them, all idle."""
    idle = np.zeros(rx.s, np.int32)
    return (rx._put(np.zeros((rx.s, rx.chunk_len, 2), np.float32)),
            rx._put(idle), rx._put(idle), rx._put(idle))


def decode_program(rx):
    from ziria_tpu.phy.wifi import rx as _rx
    return _rx._jit_stream_decode_multi(
        rx.n_sym_bucket, rx.viterbi_window, rx.viterbi_metric,
        rx.viterbi_radix, rx.mesh, rx.axis, rx.sco_track, rx.fused_demap)


def warm(rx, on_tpu: bool):
    """Compile the receiver's two programs ahead of traffic on all-idle
    input and run each once. Returns the arguments they were lowered
    for, for the StableHLO check after the window."""
    import jax

    from ziria_tpu.runtime import resilience

    chunk_args = chunk_shapes(rx)
    resilience.compile_ahead(rx._jit1, *chunk_args)
    outs = rx._jit1(*chunk_args)
    dec = decode_program(rx)
    table = np.zeros((rx.s, rx.k), np.int32)
    dec_args = (outs[-1],) + tuple(rx._put(table) for _ in range(4))
    resilience.compile_ahead(dec, *dec_args)
    jax.block_until_ready(dec(*dec_args))
    n_mosaic = dec.lower(*dec_args).compile().as_text() \
        .count("tpu_custom_call")
    if on_tpu and n_mosaic < 2:
        raise SystemExit(f"the decode executable holds {n_mosaic} "
                         f"tpu_custom_call(s): a Viterbi kernel lowered "
                         f"in interpret mode on the chip")
    return chunk_args, dec_args, n_mosaic


class Profiler:
    """Starts the JAX profiler at the first tick at or after ``at_s``
    into the window and stops it at the first tick by which the fleet
    has launched ``PROFILED_STEPS`` more chunk-steps (``launched()`` is
    its count so far), or ``PROFILED_SECONDS`` later where that comes
    first. A tick is one call of ``on_tick``: in the open loop most
    launch nothing once a tick is shorter than the gap between lane
    fills, so ticks say nothing of how much work the trace holds."""

    def __init__(self, logdir: str, at_s: float,
                 launched: Callable[[], int]):
        self.logdir, self.at_s, self.launched = logdir, at_s, launched
        self.started: Optional[Tuple[float, int]] = None  # (t, launched)
        self.done = False

    def on_tick(self, tick: int, t: float) -> None:
        import jax
        if self.done:
            return
        if self.started is None:
            if t >= self.at_s:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 1
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.logdir,
                                         profiler_options=opts)
                self.started = (t, self.launched())
        elif self.launched() - self.started[1] >= PROFILED_STEPS \
                or t - self.started[0] >= PROFILED_SECONDS:
            self.close()

    def close(self) -> None:
        import jax
        if self.started is not None and not self.done:
            jax.profiler.stop_trace()
        self.done = True


class SampledStep:
    """Wraps ``srv.step`` to remember the host side (offsets, lanes,
    samples, valid, own_lo, own_hi) of the newest chunk-step whose
    frames the loop has been handed, for the float comparison after the
    window. References only: nothing is pulled inside the window."""

    def __init__(self, srv):
        self._rx, self._step = srv._rx, srv.step
        self._last = None
        self.kept = None
        srv.step = self

    def __call__(self):
        out = self._step()
        if out and self._last is not None:
            self.kept = self._last
        pend = self._rx._pending
        self._last = None if pend is None else tuple(pend[:6])
        return out


class Reduction(NamedTuple):
    """What a per-layer reader may read."""
    spans: spans.Recorder
    window: tuple                       # (open, close) on perf_counter
    counters: Dict[str, float]
    device: Optional[xplane.DeviceTrace]
    peaks: dict


def refuse(why: str):
    """No result line, and an exit code that is not 0."""
    print(f"benchmark: {why}", file=sys.stderr)
    raise SystemExit(2)


def measure(args, inspect=None, traffic=None):
    """Everything but the printing of the result line: returns it, and
    the value of every number compared by name (control.py, the tests).
    ``inspect(rx, host_step, outs, chunk_args, dec_args)`` sees the
    chunk-step the float comparison was made on (control.py);
    ``traffic`` stands in for the cell's traffic file (sweep.py)."""
    cell = manifest.load_cell(args.workload, rehearse=args.rehearse)
    try:
        import jax
        import ziria_tpu  # noqa: F401
    except ImportError as e:
        refuse(f"the program is not here: {e}")

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    if not on_tpu and not args.rehearse:
        refuse(f"jax.default_backend() is {backend!r}, not 'tpu': no "
               f"accelerator, no result")
    devs = jax.devices()
    if len(devs) < cell.chips and not args.rehearse:
        refuse(f"cell {cell.name} asks for {cell.chips} chip(s), JAX "
               f"reports {len(devs)}")

    from ziria_tpu.ops import viterbi_pallas
    from ziria_tpu.phy.wifi import rx as _rx
    from ziria_tpu.runtime import serve
    from ziria_tpu.utils import compile_cache, dispatch, telemetry

    cache = compile_cache.place()
    # every program, however small: a run after the first compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    say("device", **device, jax=jax.__version__, compile_cache=cache,
        rehearsal=args.rehearse, cell=cell.name, seed=args.seed)
    if viterbi_pallas._interpret_default() != (not on_tpu):
        raise SystemExit("interpret mode must follow the backend: "
                         "Mosaic on tpu, the interpreter elsewhere")
    mark = {"import": process_age_s()}

    cfg = cell.config
    traffic = cell.traffic if traffic is None else traffic
    geo = cfg["geometry"]
    laps = load.synth_laps(cfg, args.seed)
    mark["synthesis"] = process_age_s()

    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=geo["n_lanes"], chunk_len=geo["chunk_len"],
        frame_len=geo["frame_len"],
        max_frames_per_chunk=geo["max_frames_per_chunk"],
        check_fcs=geo["check_fcs"]))
    rx = srv._rx
    if rx.n_sym_bucket != geo["symbol_bucket"]:
        raise SystemExit(f"the configuration states symbol bucket "
                         f"{geo['symbol_bucket']}, the receiver built "
                         f"{rx.n_sym_bucket}")
    chunk_args, dec_args, n_mosaic = warm(rx, on_tpu)
    mark["compile_or_cache"] = process_age_s()

    sids = [f"s{i}" for i in range(cfg["sessions"])]
    session_of = {s: i for i, s in enumerate(sids)}.__getitem__
    lane_of: Dict[str, int] = {}
    stride, chunk_len = rx.stride, rx.chunk_len
    jits = (_rx._jit_stream_chunk_multi, _rx._jit_stream_decode_multi)

    def consumed() -> int:
        return sum(rx.carry(lane_of[s]).offset for s in sids)

    def in_flight() -> list:
        """Blocks on the chunk-steps in flight, if any: their frames.
        Both loops call it before the window opens and as it closes."""
        return [(srv._lane_sid[ln], fr) for ln, fr in rx.drain_pending()]

    # every XLA compile fires this event; the listener cannot be
    # removed, so it counts only while the window is open
    seen = {"live": False, "compiles": 0}

    def on_event(name, _secs, **_kw):
        if seen["live"] and name.endswith("backend_compile_duration"):
            seen["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    rec = spans.Recorder(annotate=bool(args.trace))
    sampled = SampledStep(srv)
    trace_dir = os.path.join(manifest.ROOT, ".bench_scratch", "trace")
    prof = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        prof = Profiler(trace_dir, 0.4 * args.seconds,
                        lambda: rx.stats.chunk_steps)
    at_open: Dict[str, object] = {}

    def on_open() -> None:
        at_open["setup_s"] = process_age_s()
        at_open["chunk_steps"] = rx.stats.chunk_steps
        at_open["dispatches"] = dict(d.counts)
        at_open["jit_entries"] = sum(c.cache_info().currsize for c in jits)
        seen["live"] = True

    with telemetry.collect(srv.registry), \
            dispatch.count_dispatches() as d, \
            spans.StallWatch(rec, STALL_S) as stalls:
        for s in sids:
            r = srv.connect(s)
            if not r.admitted:
                raise SystemExit(f"session {s} was not admitted: {r}")
        lane_of.update({s: ln for ln, s in srv._lane_sid.items()})
        tracing = telemetry.tracing(annotate_device=True) if args.trace \
            else contextlib.nullcontext()
        with tracing:
            try:
                common = dict(consumed=consumed, clock=time.perf_counter,
                              rec=rec, session_of=session_of, on_open=on_open,
                              on_tick=prof.on_tick if prof else None)
                if traffic["loop"] == "closed":
                    slab = traffic["slab_samples"]
                    win = loop.run_closed(
                        srv, sids, laps, stride if slab == "stride" else slab,
                        args.seconds, WARM_TICKS, drain=in_flight,
                        **common)
                elif traffic["loop"] == "open":
                    rate = traffic["rate_samples_per_s"] / len(sids)
                    phases = load.phases(args.seed, len(sids), stride / rate)
                    arrivals = [load.Arrivals(
                        args.seed, i, traffic["slab_lo"], traffic["slab_hi"],
                        rate, phases[i]) for i in range(len(sids))]
                    win = loop.run_open(srv, sids, laps, arrivals, WARM_TICKS,
                                        args.seconds, stride, chunk_len,
                                        sleep=time.sleep, drain=in_flight,
                                        **common)
                else:
                    raise SystemExit(f"traffic loop {traffic['loop']!r}: "
                                     f"'closed' or 'open'")
            finally:
                seen["live"] = False
                if prof is not None:
                    prof.close()
        t_close = win.t_open + win.elapsed_s
        steps = rx.stats.chunk_steps - at_open["chunk_steps"]
        # both loops have drained the fleet at both edges: these are
        # the dispatches of the chunk-steps the window launched
        disp = {k: v - at_open["dispatches"].get(k, 0)
                for k, v in d.counts.items()}
        growth = sum(c.cache_info().currsize for c in jits) \
            - at_open["jit_entries"]
        stats = rx.stats
        snap = srv.registry.snapshot()

    emitted = win.emitted
    offsets = [rx.carry(lane_of[s]).offset for s in sids]
    frames = checks.check_frames(emitted, laps, offsets)
    rows = [checks.Compared("frames_attempted", frames.attempted, 1, ">="),
            checks.Compared("frames_failed", frames.failed, 0)]
    if cfg["no_frame_unsent"]:
        rows.append(checks.Compared("frames_not_sent",
                                    frames.why.get("not sent", 0), 0))
    rows += checks.check_hidden(stats, snap, disp, seen["compiles"],
                                growth, steps)
    rows.append(checks.Compared("decode_tpu_custom_calls", n_mosaic,
                                2 if on_tpu else 0, ">="))
    rows += checks.check_reference(frames.matched, laps, rx.frame_len,
                                   args.seed)
    text = rx._jit1.lower(*chunk_args).as_text() \
        + decode_program(rx).lower(*dec_args).as_text()
    rows.append(checks.Compared("contractions_below_highest",
                                checks.loose_contractions(text), 0))
    lim = checks.limits()
    if sampled.kept is not None:
        step = sampled.kept
        outs = rx._jit1(rx._put(step[2]), rx._put(step[3]),
                        rx._put(step[4]), rx._put(step[5]))
        n_cmp, eps_gap, seg_gap = checks.float_gaps(
            step, outs, rx.frame_len,
            counts.FRAME_DATA_START + 80 * rx.n_sym_bucket)
        if inspect is not None:
            inspect(rx, step, outs, chunk_args, dec_args)
        del outs
    else:
        n_cmp, eps_gap, seg_gap = 0, float("nan"), float("nan")
    rows += [checks.Compared("float_frames_compared", n_cmp, 1, ">="),
             checks.Compared("cfo_gap_rad_per_sample", eps_gap,
                             lim["cfo_gap_rad_per_sample"]),
             checks.Compared("segment_gap_rel", seg_gap,
                             lim["segment_gap_rel"])]
    if not (traffic["loop"] == "closed"):
        rows.append(checks.Compared(
            "negative_delays", sum(1 for x in win.delays_s if x < 0), 0))
    for r in rows:
        say("compared", name=r.name, value=r.value, limit=r.limit,
            rule=r.how, ok=r.ok)
    correct = all(r.ok for r in rows)
    if frames.why:
        say("frames", failed_by_kind=frames.why)

    ms = dev.memory_stats() or {}
    peak = int(ms.get("peak_bytes_in_use", 0))
    setup_s = float(at_open["setup_s"])
    say("setup", total_s=setup_s, import_s=mark["import"],
        synthesis_s=mark["synthesis"] - mark["import"],
        compile_or_cache_s=mark["compile_or_cache"] - mark["synthesis"],
        warm_ticks_s=setup_s - mark["compile_or_cache"])
    rate = win.consumed / win.elapsed_s
    say("window", elapsed_s=win.elapsed_s, ticks=win.ticks,
        chunk_steps=steps, samples=win.consumed, samples_per_s=rate,
        realtime_sessions=rate / cfg["sample_rate_hz"],
        frames=len(emitted), frames_attempted=frames.attempted,
        dispatches=disp, peak_device_bytes=peak)
    step_ms = [1e3 * x for x in rec.durations("bench.step", win.t_open,
                                              t_close)]
    say("ticks", step_ms=[round(x, 1) for x in step_ms])
    say("steady", **steady.tick_summary(step_ms))
    for began, where in stalls.seen:
        say("stall", at_s=began - win.t_open, where=where)
    e2e = {"setup_s": setup_s, "samples_per_s": rate}
    if win.delays_s:
        dl = np.asarray(win.delays_s) * 1e3
        lt = np.asarray(win.late_s) * 1e3
        half = len(dl) // 2
        e2e["emit_delay_p50_ms"] = float(np.percentile(dl, 50))
        e2e["emit_delay_p90_ms"] = float(np.percentile(dl, 90))
        paced = dict(
            delay_samples=len(dl), delay_p50_ms=e2e["emit_delay_p50_ms"],
            delay_p90_ms=e2e["emit_delay_p90_ms"],
            delay_p99_ms=float(np.percentile(dl, 99)),
            delay_p50_first_half_ms=float(np.median(dl[:half])),
            delay_p50_second_half_ms=float(np.median(dl[half:])),
            slabs=len(lt), late_p50_ms=float(np.median(lt)),
            late_max_ms=float(lt.max()), refused=win.refused,
            staged_at_close=sum(s.staged_samples
                                for s in srv._sessions.values()),
            step_busy_p50_ms=steady.time_weighted_median(step_ms),
            step_samples=rx.s * stride,
            stalls=sum(1 for began, _ in stalls.seen if began >= win.t_open))
        say("paced", **paced)

    metrics = {}
    if args.trace:
        path = xplane.find_xplane(trace_dir)
        if path is None:
            raise SystemExit(f"no *.xplane.pb under {trace_dir}: the "
                             f"window closed before the profiler ran")
        tr = xplane.read(path, need_device=on_tpu)
        n_dec = disp.get(checks.SITES[1], 0)
        tallies = {
            "samples_consumed": win.consumed, "chunk_steps": steps,
            "lanes": rx.s, "stride": stride, "ticks": win.ticks,
            "peak_device_bytes": peak,
            "h2d_bytes": steps * counts.scan_h2d_bytes(rx.s, chunk_len)
            + n_dec * counts.decode_h2d_bytes(rx.s, rx.k)}
        ctx = Reduction(rec, (win.t_open, t_close), tallies, tr,
                        peaks.peaks_for(dev.device_kind) if on_tpu else {})
        an = annotations.for_ctx(ctx)
        prog_spans = an.spans if an is not None \
            else annotations.host_spans(path)
        shape = (rx.s, rx.k, rx.n_sym_bucket)
        stale = counts.stale(prog_spans, *shape)
        if stale:
            raise SystemExit("the program's spans report what cannot "
                             "be: " + "; ".join(stale))
        # what was pulled and decoded, as the program reports it per
        # call between its floor and the benchmark's ceiling
        said = counts.reported(prog_spans, *shape)
        tallies["d2h_bytes"] = \
            steps * said[counts.PULL_SCAN] \
            + n_dec * said[counts.PULL_DECODE]
        tallies["acs_min_bytes"] = counts.ACS_BYTES_PER_STEP \
            * said[counts.TRELLIS]
        for m in cell.per_layer:
            v = m.reduce(ctx, **m.args)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        breakdown = {"device_ops": xplane.top_device_ops(tr),
                     "idle_gaps": xplane.idle_gaps(tr)}
        say("trace", file=path, busy_s=tr.busy_s, window_s=tr.window_s,
            idle_share=1 - tr.busy_s / tr.window_s,
            runs={k: len(v) for k, v in tr.modules.items()},
            spans={n: sum(1 for sp in prog_spans if sp.name == n)
                   for n in (xplane.WINDOW_SPAN, "serve.step",
                             "rx.fleet.stack", "rx.fleet.emit")},
            scan_ms=[round((e.end - e.start) / 1e6, 2)
                     for e in tr.modules["scan"]])
    else:
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise SystemExit(f"cell {cell.name} lists {m['name']}, "
                                 f"which this run did not measure")
            metrics[m["name"]] = {"value": e2e[m["name"]],
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    line = {"correct": bool(correct), "attempted": int(frames.attempted),
            "failed": int(frames.failed), "metrics": metrics,
            "device": device}
    if args.trace:
        line["breakdown"] = breakdown
    if win.delays_s:
        line["paced"] = paced
    # why a run is not correct, then every number compared beside its
    # limit: the line's last key, and the last lines of standard error
    # (of which the record keeps the end: the failed rows come last)
    line.update(checks.report(rows))
    for r in sorted(rows, key=lambda r: not r.ok):
        print(f"compared {r.name} {r.value} {r.how} {r.limit} "
              f"{'ok' if r.ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    return line, {r.name: r.value for r in rows}


def run(args) -> int:
    line, _compared = measure(args)
    say("done", process_age_s=process_age_s())
    if args.rehearse:
        say("rehearsal", would_print=json.dumps(line))
        print(json.dumps({"rehearsal": True, "device": line["device"]}))
        return 0 if line["correct"] else 1
    print(json.dumps(line), flush=True)
    return 0
