"""The benchmark's whole-window readers (ISSUE 41) on hand-built
traces: `benchmark/harness/window_trace.py` takes the program's own
in-memory `Trace` of the measured window (`telemetry.last_trace()`),
lays it against the profile by the spans both hold, leaves the profiled
interval out, and four reducers read it over every chunk-step.

Times below are milliseconds into a window that opens `T0` seconds
into `perf_counter`; the profile's clock runs `OFFSET_NS` ahead."""

import json
import os
import types

import pytest

from benchmark.harness import manifest, window_trace
from benchmark.harness.annotations import Span
from benchmark.reducers import (window_arg_ratio, window_arg_stat,
                                window_pair_ms, window_span_ms)
from ziria_tpu.utils import telemetry

T0 = 1000.0
OFFSET_NS = 7.5e15
WINDOW_MS = 1000.0
TICK_MS = 10.0
#: the profiler's interval, on the window's clock: with a tick on both
#: sides, [390, 460) is left out
PROFILED_MS = (400.0, 450.0)
NEW = ["host_work_ms_per_step.window", "decode_wait_ms_per_step",
       "decode_ready_share.window", "stack_ms_per_step",
       "ingest_ms_per_step", "launch_wait_ms", "chunk_flight_ms.window",
       "in_flight_mean.sat", "in_flight_mean.paced", "gc_pause_ms_per_s"]


class Run:
    """A hand-built trace, its twin in the profile, and the `ctx` the
    harness would hand a reducer."""

    def __init__(self):
        self.trace = telemetry.Trace()
        self.trace.epoch = T0 - 5.0     # activated before the window
        self.profile = []
        self.skew_ns = {}               # (name, step) -> extra offset

    def span(self, name, start_ms, end_ms, **args):
        self.trace.complete(name, T0 + start_ms / 1e3,
                            (end_ms - start_ms) / 1e3, args=args)
        if PROFILED_MS[0] <= start_ms < PROFILED_MS[1]:
            off = OFFSET_NS + self.skew_ns.get((name, args.get("step")),
                                               0.0)
            self.profile.append(Span(
                name, T0 * 1e9 + start_ms * 1e6 + off,
                T0 * 1e9 + end_ms * 1e6 + off, dict(args)))

    def ctx(self, monkeypatch):
        with telemetry.tracing(trace=self.trace):
            pass                        # what last_trace() now returns
        monkeypatch.setattr(window_trace, "profile_spans",
                            lambda ctx: self.profile)
        window_trace._LOADED.clear()
        device = types.SimpleNamespace(window=tuple(
            T0 * 1e9 + ms * 1e6 + OFFSET_NS for ms in PROFILED_MS))
        spans = types.SimpleNamespace(
            durations=lambda name, lo, hi: [TICK_MS / 1e3] * 3)
        return types.SimpleNamespace(
            window=(T0, T0 + WINDOW_MS / 1e3), device=device,
            spans=spans)


def chunk_step(run, step, at, ready=0, in_flight=3, emit=True):
    """One closed-loop tick of 10 ms at `at`: a `serve.step` of 9 that
    ingests 1, stacks 2, puts 1, waits 3 in the decode's pull (for
    step - 2) and emits."""
    run.span("serve.step", at, at + 9, sessions=8)
    run.span("rx.fleet.ingest", at, at + 1, step=step, lanes=8)
    run.span("rx.fleet.stack", at + 1, at + 3, step=step, active=8,
             ready_ms=0.5 + step)
    run.span("rx.fleet.put", at + 3, at + 4, step=step,
             in_flight=in_flight)
    run.span("rx.fleet.pull_scan", at + 4, at + 4.5, step=step - 1,
             reads=1, ready=1, how="launch")
    run.span("rx.fleet.pull_decode", at + 5, at + 8, step=step - 2,
             reads=1, ready=ready, how="launch")
    if emit:
        run.span("rx.fleet.emit", at + 8, at + 8.5, step=step - 2,
                 frames=16)


@pytest.fixture
def run():
    """Ten chunk-steps: 0-3 before the profiled interval, 4 and 5
    inside it (at 400 and 420 ms), 6-9 after."""
    r = Run()
    for step, at in enumerate((100, 200, 300, 370, 400, 420, 470, 600,
                               700, 800)):
        chunk_step(r, step, at, ready=int(step % 2 == 0))
    return r


def test_the_profiled_interval_is_left_out_a_tick_wide(run, monkeypatch):
    wt = window_trace.for_ctx(run.ctx(monkeypatch))
    assert wt.profiled == pytest.approx(
        ((T0 + 0.390) * 1e9, (T0 + 0.460) * 1e9), abs=10)
    stacks = [s.args["step"] for s in window_trace.named(
        wt, "rx.fleet.stack")]
    # steps 4 and 5 ran under the profiler; 3 and 6 are a tick clear
    assert stacks == [0, 1, 2, 3, 6, 7, 8, 9] and wt.steps == 8
    assert wt.seconds == pytest.approx(1.0 - 0.070)
    assert wt.matched >= 10 and wt.residual_ns < 1.0
    # a span that reaches into the widened interval goes too
    run.span("serve.step", 385, 392, sessions=8)
    run.span("serve.step", 1200, 1209, sessions=8)  # after the window
    wt = window_trace.for_ctx(run.ctx(monkeypatch))
    assert len(window_trace.named(wt, "serve.step")) == 8


def test_own_time_is_the_span_less_its_named_children(run, monkeypatch):
    ctx = run.ctx(monkeypatch)
    # 9 ms a serve.step less 0.5 (pull_scan) and 3 (pull_decode)
    assert window_span_ms.reduce(
        ctx, "serve.step", children=["rx.fleet.pull_scan",
                                     "rx.fleet.pull_decode"]) \
        == pytest.approx(5.5)
    assert window_span_ms.reduce(ctx, "serve.step") == pytest.approx(9.0)
    assert window_span_ms.reduce(ctx, "rx.fleet.pull_decode") \
        == pytest.approx(3.0)
    # children that overlap each other are counted once
    run.span("rx.fleet.pull_scan", 104.2, 106, step=99)
    assert window_span_ms.reduce(
        run.ctx(monkeypatch), "serve.step",
        children=["rx.fleet.pull_scan", "rx.fleet.pull_decode"]) \
        == pytest.approx((8 * 5.5 - 0.5) / 8)


@pytest.mark.parametrize("stat,per,want", [
    ("median", "step", 2.0), ("mean", "step", 23.0 / 10),
    ("mean", "second", 23.0 / 0.930)])
def test_a_spans_median_and_its_mean_a_step_or_a_second(
        run, monkeypatch, stat, per, want):
    # eight kept stacks of 2 ms, a slow one of 6 and a short one of 1
    run.span("rx.fleet.stack", 900, 906, step=10, active=8)
    run.span("rx.fleet.stack", 910, 911, step=11, active=1)
    ctx = run.ctx(monkeypatch)
    assert window_trace.for_ctx(ctx).steps == 10
    assert window_span_ms.reduce(ctx, "rx.fleet.stack", stat=stat,
                                 per=per) == pytest.approx(want)
    with pytest.raises(ValueError):
        window_span_ms.reduce(ctx, "rx.fleet.stack", stat="p99")


def test_a_pause_reads_zero_where_none_was_recorded(run, monkeypatch):
    ctx = run.ctx(monkeypatch)
    args = dict(span="rx.pause.gc", stat="mean", per="second")
    assert window_span_ms.reduce(ctx, **args) is None
    assert window_span_ms.reduce(ctx, empty=0.0, **args) == 0.0
    run.span("rx.pause.gc", 150, 270, generation=2, collected=5)
    run.span("rx.pause.gc", 410, 411, generation=0, collected=0)  # left out
    assert window_span_ms.reduce(run.ctx(monkeypatch), empty=0.0,
                                 **args) == pytest.approx(120 / 0.930)


def test_ratio_and_stat_of_an_arg_over_every_kept_span(run, monkeypatch):
    run.span("rx.fleet.stack", 950, 951, step=10, active=1)  # no ready_ms
    ctx = run.ctx(monkeypatch)
    # ready on the even steps: 0, 2, 6, 8 of the eight kept
    assert window_arg_ratio.reduce(
        ctx, "rx.fleet.pull_decode", "ready", "reads", scale=100.0) \
        == pytest.approx(50.0)
    assert window_arg_ratio.reduce(ctx, "rx.fleet.put", "ready",
                                   "reads") is None
    waits = [0.5 + s for s in (0, 1, 2, 3, 6, 7, 8, 9)]
    assert window_arg_stat.reduce(ctx, "rx.fleet.stack", "ready_ms",
                                  stat="median") \
        == pytest.approx(sorted(waits)[3] / 2 + sorted(waits)[4] / 2)
    assert window_arg_stat.reduce(ctx, "rx.fleet.stack", "ready_ms") \
        == pytest.approx(sum(waits) / 8)
    assert window_arg_stat.reduce(ctx, "rx.fleet.put", "in_flight") == 3.0
    assert window_arg_stat.reduce(ctx, "rx.fleet.put", "depth") is None


def test_a_flight_needs_both_ends_and_no_profiler_between(monkeypatch):
    run = Run()
    # stack of step n at 100 n, its emit 230 ms later; step 7's is lost
    for step in range(9):
        at = 100.0 * step
        run.span("rx.fleet.stack", at + 1, at + 3, step=step)
        if step != 7:
            run.span("rx.fleet.emit", at + 225 + step, at + 231 + step,
                     step=step)
    ctx = run.ctx(monkeypatch)
    wt = window_trace.for_ctx(ctx)
    assert [s.args["step"] for s in window_trace.named(
        wt, "rx.fleet.emit")] == [0, 1, 3, 4, 5, 6]     # 2 in it, 8 late
    # steps 2, 3 and 4 had the profiled interval in their flight or
    # their stack in it (4: 401-403); 7 never came out
    flights = {0: 230, 1: 231, 5: 235, 6: 236}
    assert window_pair_ms.reduce(ctx, "rx.fleet.stack", "rx.fleet.emit") \
        == pytest.approx(sum(sorted(flights.values())[1:3]) / 2)
    assert window_pair_ms.reduce(ctx, "rx.fleet.stack", "rx.fleet.emit",
                                 at_least=5) is None


@pytest.mark.parametrize("skew_ms,reports", [(0.4, True), (0.6, False)])
def test_nothing_is_reported_where_the_clocks_disagree(
        run, monkeypatch, capsys, skew_ms, reports):
    run = Run()
    run.skew_ns[("rx.fleet.put", 4)] = skew_ms * 1e6
    for step, at in enumerate((100, 200, 300, 370, 400, 420, 470, 600)):
        chunk_step(run, step, at)
    ctx = run.ctx(monkeypatch)
    wt = window_trace.for_ctx(ctx)
    err = capsys.readouterr().err
    if reports:
        assert wt.residual_ns == pytest.approx(skew_ms * 1e6, rel=1e-3)
        assert err == ""
    else:
        assert wt is None and f"{skew_ms:.3f} ms" in err
        assert window_span_ms.reduce(ctx, "serve.step") is None
        assert window_pair_ms.reduce(ctx, "rx.fleet.stack",
                                     "rx.fleet.emit") is None
        assert window_arg_stat.reduce(ctx, "rx.fleet.put",
                                      "in_flight") is None
        # said once, however many readers ask
        assert capsys.readouterr().err == ""


def test_another_runs_trace_or_a_program_without_one_gives_nothing(
        run, monkeypatch, capsys):
    ctx = run.ctx(monkeypatch)
    late = types.SimpleNamespace(window=(T0 + 50.0, T0 + 51.0),
                                 device=ctx.device, spans=ctx.spans)
    assert window_trace.for_ctx(late) is None
    # no span of the window in the profile: the clocks cannot be laid
    run.profile.clear()
    assert window_trace.for_ctx(run.ctx(monkeypatch)) is None
    assert "cannot be laid together" in capsys.readouterr().err
    # a run with no profile at all, as every reader of ctx.device
    bare = types.SimpleNamespace(window=ctx.window, device=None,
                                 spans=ctx.spans)
    assert window_trace.for_ctx(bare) is None
    # the parent of PR 41: no last_trace(), or a Trace with no epoch
    monkeypatch.setattr(telemetry, "_LAST_TRACE",
                        types.SimpleNamespace(_epoch=T0))
    assert window_trace.for_ctx(ctx) is None
    monkeypatch.delattr(telemetry, "last_trace")
    assert window_trace.for_ctx(ctx) is None
    assert window_span_ms.reduce(ctx, "serve.step") is None


def test_the_ten_entries_resolve_and_only_follow_what_was_there():
    assert manifest.problems() == []
    man = manifest.manifest()
    names = [m["name"] for m in man["per_layer"]]
    # appended in one block; what later PRs append follows it
    first = names.index(NEW[0])
    assert names[first: first + 10] == NEW
    assert len(set(names)) == len(names)
    by = {m["name"]: m for m in man["per_layer"]}
    sat = ["mtu8.saturated", "beacon8.saturated", "mix8.saturated",
           "mtu32x4.saturated", "maxpsdu8.saturated",
           "dense54.saturated"]
    for n in NEW:
        with open(os.path.join(manifest.HERE, "layer_metrics",
                               n + ".json")) as f:
            assert json.load(f)["reducer"].startswith("window_"), n
        paced = by[n]["workloads"] == ["mtu8.paced"]
        assert paced or set(by[n]["workloads"]) <= set(sat), n
        assert by[n]["moves"] == ("emit_delay_p50_ms" if paced
                                  else "samples_per_s")
    # the five-step twins stay as they were, for a `benchmark` issue
    for twin in ("host_work_ms_per_step", "decode_ready_share",
                 "chunk_flight_ms"):
        assert by[twin]["workloads"] == by[twin + ".window"]["workloads"]
    for cell, n_new in (("mtu8.saturated", 7), ("beacon8.saturated", 5),
                        ("mtu8.paced", 3)):
        have = [m.name for m in manifest.load_cell(cell).per_layer]
        assert len([n for n in have if n in NEW]) == n_new, cell
