"""The readers of the program's own tracing (PR 25): annotation stats
and op scopes out of a profiler trace, and the five reducers on
synthetic events."""

import os

import pytest

from benchmark.harness import annotations, manifest, xplane
from benchmark.harness.annotations import Annotations, Op, Span
from benchmark.reducers import idle_under_span, scope_device_time, \
    span_arg_ratio, span_pair_ms, span_self_time

MS = 1e6          # ns


# ------------------------------------ a trace recorded here, on the CPU


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """Two ticks of annotated host work under the JAX profiler: the
    program's ``telemetry.span`` inside the benchmark's ``bench.tick``,
    as harness/cell.py nests them."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import spans
    from ziria_tpu.utils import telemetry

    logdir = str(tmp_path_factory.mktemp("trace"))
    rec = spans.Recorder(annotate=True)
    jax.profiler.start_trace(logdir)
    try:
        with telemetry.tracing(annotate_device=True):
            for step in (4, 5):
                with rec.span("bench.tick"):
                    with telemetry.span("rx.fleet.stack", {
                            "step": step, "active": 8, "samples": 4096}):
                        x = jnp.ones((64, 64)) * step
                    with telemetry.span("rx.fleet.decode", {
                            "step": step - 1, "lanes": 3,
                            "useful_symbols": 10 * step,
                            "padded_symbols": 512, "note": "text"}):
                        jax.block_until_ready(x @ x)
                    with telemetry.span("serve.stage"):
                        pass
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(logdir)
    assert path is not None
    return path


MEANT = ("bench.tick", "rx.fleet.stack", "rx.fleet.decode", "serve.stage")


def annotations_with_their_stats(cpu_trace, meant):
    """The traced block's annotations as they were written, in order;
    ``meant`` picks the ones the case is about out of whatever else the
    program annotates inside the block."""
    tr = xplane.read(cpu_trace, need_device=False)
    an = annotations.read(cpu_trace, tr)
    assert an.window == tr.window and an.ops == []
    assert an.runs == {"scan": [], "decode": [], "other": []}
    names = [s.name for s in an.spans if meant(s.name)]
    assert names == list(MEANT) * 2
    stacks = [s for s in an.spans if s.name == "rx.fleet.stack"]
    assert [s.args for s in stacks] == [
        {"step": 4, "active": 8, "samples": 4096},
        {"step": 5, "active": 8, "samples": 4096}]
    dec = [s for s in an.spans if s.name == "rx.fleet.decode"]
    assert dec[1].args == {"step": 4, "lanes": 3, "useful_symbols": 50,
                           "padded_symbols": 512, "note": "text"}
    assert all(s.args == {} for s in an.spans
               if s.name in ("bench.tick", "serve.stage"))
    for s in an.spans:
        assert an.window[0] <= s.start <= s.end <= an.window[1]
    assert annotations.chunk_steps(an) == 2


def test_annotations_come_back_with_their_stats(cpu_trace):
    """Pins the exact list of the block's annotations, and fails since
    PR 41 made a collection inside it one (`rx.pause.gc`):
    `tests/test_benchmark_harness.py` expects that (KNOWN_FAILURES,
    strict), and this PR's kind may not edit that file. The case below
    is this one repaired; the PR that strikes the line there deletes
    this one."""
    annotations_with_their_stats(cpu_trace, lambda name: True)


def test_the_annotations_it_means_come_back_with_their_stats(cpu_trace):
    annotations_with_their_stats(cpu_trace, MEANT.__contains__)


def test_no_device_op_means_every_reader_reports_nothing(cpu_trace):
    tr = xplane.read(cpu_trace, need_device=False)

    class Ctx:
        device = tr
    assert annotations.for_ctx(Ctx, os.path.dirname(cpu_trace)) is None
    assert scope_device_time.reduce(Ctx, "rx", "rx", "scan") is None
    assert idle_under_span.reduce(Ctx, ["rx.fleet.stack"]) is None
    assert span_self_time.reduce(Ctx, "serve.step") is None
    assert span_arg_ratio.reduce(Ctx, "rx.fleet.decode", "a", "b") is None
    assert span_pair_ms.reduce(Ctx, "rx.fleet.stack",
                               "rx.fleet.emit") is None


# ---------------------------- op scopes out of the recorded v5e trace


V5E = os.path.join(os.path.dirname(__file__), "data",
                   "tiny_v5e_scoped.xplane.pb")


@pytest.fixture(scope="module")
def v5e():
    tr = xplane.read(V5E)
    return tr, annotations.read(V5E, tr)


def test_v5e_modules_carry_the_programs_names(v5e):
    tr, _an = v5e
    names = {k: {m.name.split("(")[0] for m in v}
             for k, v in tr.modules.items() if v}
    assert names == {"scan": {"jit_stream_chunk_multi"},
                     "decode": {"jit_stream_decode_multi"}}


def test_v5e_ops_are_looked_up_by_program_and_name(v5e):
    _tr, an = v5e
    scopes = annotations.op_scopes(V5E)
    assert scopes and all(len(k) == 2 for k in scopes)
    programs = {p for p, _n in scopes}
    assert len(programs) == 2               # the scan and the decode
    assert any("rx.scan.locate" in v for v in scopes.values())
    assert any("rx.decode.viterbi" in v for v in scopes.values())
    scoped = [o for o in an.ops if o.scope]
    assert len(scoped) > 0.9 * len(an.ops)
    # a while has no op_name of its own and inherits its body's
    whiles = [o for o in an.ops if o.name.startswith("%while")]
    assert whiles and all(o.scope for o in whiles)


def test_v5e_self_times_add_up_to_each_run(v5e):
    tr, an = v5e
    for kind in ("scan", "decode"):
        for run in tr.modules[kind]:
            ops = [o for o in an.ops if run.start <= o.start < run.end]
            if not ops or ops[-1].end > run.end:
                continue                    # cut by the profiler
            total = sum(o.self_ns for o in ops)
            assert total <= run.end - run.start
            assert total > 0.97 * (run.end - run.start)


def test_v5e_scopes_split_both_programs(v5e, capsys, monkeypatch):
    tr, an = v5e

    class Ctx:
        device = tr
    monkeypatch.setattr(annotations, "for_ctx", lambda ctx: an)
    parts = [scope_device_time.reduce(Ctx, p, r"rx\.scan\.", "scan")
             for p in (r"rx\.scan\.locate", r"rx\.scan\.(window|acquire)",
                       r"rx\.scan\.gather")]
    assert all(p is not None and p > 0 for p in parts)
    whole = sorted(m.end - m.start for m in tr.modules["scan"])
    whole = whole[len(whole) // 2] / MS
    assert 0.97 * whole < sum(parts) <= whole
    dec = [scope_device_time.reduce(Ctx, p, r"rx\.decode\.", "decode")
           for p in (r"rx\.decode\.(select|front)", r"rx\.decode\.viterbi",
                     r"rx\.decode\.back")]
    assert all(p is not None and p > 0 for p in dec)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[scopes] ")]
    assert len(lines) == 6 and all("unscoped_ms=" in ln for ln in lines)
    # a scope the programs do not name reads as nothing, and says nothing
    assert scope_device_time.reduce(Ctx, r"rx\.nowhere", r"rx\.scan\.",
                                    "scan") is None
    assert "[scopes]" not in capsys.readouterr().out


def test_v5e_idle_by_span_adds_up_to_the_idle_time(v5e, monkeypatch):
    tr, an = v5e
    assert annotations.chunk_steps(an) == 5
    by = idle_under_span.idle_by_span(an, tr.busy)
    short = dict(xplane.idle_gaps(tr))["(gaps under 50 us)"]
    assert sum(by.values()) / 1e9 + short \
        == pytest.approx(tr.window_s - tr.busy_s)
    assert max(by, key=by.get) == "rx.fleet.pull_decode"
    # the step id ties a launch to its drain one tick later: four of
    # the five steps launched in the window are drained inside it
    monkeypatch.setattr(annotations, "for_ctx", lambda ctx: an)

    class Ctx:
        device = tr
    flight = span_pair_ms.reduce(Ctx, "rx.fleet.stack", "rx.fleet.emit",
                                 at_least=4)
    tick = tr.window_s / 5 * 1e3
    assert tick < flight < 2.5 * tick
    assert span_pair_ms.reduce(Ctx, "rx.fleet.stack", "rx.fleet.emit",
                               at_least=5) is None
    assert 0 < span_arg_ratio.reduce(
        Ctx, "rx.fleet.decode", "useful_symbols", "padded_symbols",
        100.0) < 100
    work = span_self_time.reduce(Ctx, "serve.step", [
        "rx.fleet.pull_scan", "rx.fleet.pull_decode"])
    assert 0 < work < tick


# ------------------------------------------ reducers, synthetic events


class Dev:
    def __init__(self, busy, modules=None, ops=(1,)):
        self.busy, self.ops = busy, list(ops)
        self.modules = modules or {"scan": [], "decode": [], "other": []}


class Ctx:
    def __init__(self, dev):
        self.device = dev


def _patch(monkeypatch, an):
    monkeypatch.setattr(annotations, "for_ctx", lambda ctx: an)


def sp(name, a_ms, b_ms, **args):
    return Span(name, a_ms * MS, b_ms * MS, args)


def test_nest_self_time_and_inherited_scope():
    ops = annotations.nest([
        ("%fusion.1", 0, 10, "jit(f)/rx.scan.locate/conv"),
        ("%while.2", 10, 50, ""),               # no op_name of its own
        ("%body.3", 12, 20, "jit(f)/rx.scan.acquire/gather"),
        ("%body.3", 20, 44, "jit(f)/rx.scan.acquire/gather"),
        ("%body.4", 44, 48, "jit(f)/rx.scan.window/slice"),
        ("%copy.5", 50, 55, "")])
    assert [o.name for o in ops] == ["%fusion.1", "%while.2", "%body.3",
                                     "%body.3", "%body.4", "%copy.5"]
    assert [o.self_ns for o in ops] == [10, 4, 8, 24, 4, 5]
    assert sum(o.self_ns for o in ops) == 55    # nothing counted twice
    assert ops[1].scope == "jit(f)/rx.scan.acquire/gather"
    assert ops[5].scope == ""


def test_compiler_made_ops_are_charged_to_the_op_before_them():
    runs = [xplane.Ev("jit_a(1)", 0, 50), xplane.Ev("jit_b(2)", 60, 90)]
    ops = [Op("%copy.0", 0, 5, 5, ""),             # first of its run
           Op("%fusion.1", 5, 20, 15, "jit(a)/rx.scan.locate/conv"),
           Op("%copy.2", 20, 30, 10, ""),
           Op("%copy-done.3", 30, 35, 5, ""),
           Op("%fusion.4", 35, 50, 15, "jit(a)/rx.scan.gather/mul"),
           Op("%copy.5", 52, 55, 3, ""),           # outside every run
           Op("%copy.6", 60, 70, 10, ""),          # first of the next run
           Op("%fusion.7", 70, 90, 20, "jit(b)/rx.decode.back/crc")]
    got = annotations.charge_unnamed(ops, runs)
    assert [(o.scope.split("/")[1] if o.scope else "", o.named)
            for o in got] == [
        ("", True), ("rx.scan.locate", True), ("rx.scan.locate", False),
        ("rx.scan.locate", False), ("rx.scan.gather", True), ("", True),
        ("", True), ("rx.decode.back", True)]
    assert [o.self_ns for o in got] == [o.self_ns for o in ops]


def test_scope_device_time_is_a_median_over_runs(monkeypatch, capsys):
    def run_ops(t0, locate):
        return [("%a", t0, t0 + locate, "jit(x)/vmap(rx.scan.locate)/conv"),
                ("%b", t0 + locate, t0 + locate + 2 * MS,
                 "jit(x)/rx.scan.gather/mul"),
                ("%c", t0 + locate + 2 * MS, t0 + locate + 3 * MS, "")]
    raw = run_ops(0, 10 * MS) + run_ops(100 * MS, 12 * MS) \
        + run_ops(200 * MS, 5 * MS)             # the last: cut short
    modules = {"scan": [xplane.Ev("jit_x(1)", 0, 13 * MS),
                        xplane.Ev("jit_x(1)", 100 * MS, 115 * MS),
                        xplane.Ev("jit_x(1)", 200 * MS, 208 * MS)],
               "decode": [], "other": []}
    ops = annotations.nest(raw)
    an = Annotations((0, 300 * MS), [], ops,
                     annotations.sum_runs(ops, modules))
    ctx = Ctx(Dev([], modules))
    _patch(monkeypatch, an)
    got = scope_device_time.reduce(ctx, r"rx\.scan\.locate",
                                   r"rx\.scan\.", "scan")
    assert got == pytest.approx(10.0)
    out = capsys.readouterr().out
    assert "[scopes] module=scan" in out and "unscoped_ms=1.000" in out \
        and "module_ms=13.000" in out
    assert scope_device_time.reduce(ctx, r"rx\.scan\.gather",
                                    r"rx\.scan\.", "scan") == 2.0
    assert scope_device_time.reduce(ctx, r"rx\.scan\.locate",
                                    r"rx\.scan\.", "decode") is None


def test_idle_gap_is_split_between_the_spans_open_in_it(monkeypatch,
                                                        capsys):
    # device busy 0-100 and 130-200 ms; the 30 ms gap starts under
    # pull_decode (10 ms of it), goes on under emit (4 ms), between the
    # ticks, and ends under the next tick's stack and put (1 + 13 ms)
    spans = [sp("bench.tick", 0, 115), sp("serve.step", 1, 114),
             sp("rx.fleet.stack", 2, 3, step=1),
             sp("rx.fleet.pull_decode", 90, 110, step=0),
             sp("rx.fleet.emit", 110, 114, step=0),
             sp("bench.tick", 115, 200), sp("serve.step", 115.5, 199),
             sp("rx.fleet.stack", 116, 117, step=2),
             sp("rx.fleet.put", 117, 140, step=2)]
    an = Annotations((0, 200 * MS), sorted(spans, key=lambda s: s.start),
                     [], {})
    busy = [[0, 100 * MS], [130 * MS, 200 * MS - 2e4]]   # + a 20 us gap
    ctx = Ctx(Dev(busy))
    _patch(monkeypatch, an)
    by = idle_under_span.idle_by_span(an, busy)
    assert {k: v / MS for k, v in by.items()} == pytest.approx({
        "rx.fleet.pull_decode": 10, "rx.fleet.emit": 4, "bench.tick": 1.5,
        "serve.step": 0.5, "rx.fleet.stack": 1, "rx.fleet.put": 13})
    assert sum(by.values()) == pytest.approx(30 * MS)   # not the 20 us
    pull = idle_under_span.reduce(ctx, ["rx.fleet.pull_scan",
                                        "rx.fleet.pull_decode"])
    assert pull == pytest.approx(10 / 2)        # two chunk-steps
    launch = idle_under_span.reduce(ctx, ["rx.fleet.stack",
                                          "rx.fleet.put"])
    assert launch == pytest.approx(14 / 2)
    out = capsys.readouterr().out
    assert out.count("[idle] ") == 2 and "'rx.fleet.emit': 2.0" in out


def test_span_self_time_takes_the_children_out_once(monkeypatch):
    spans = [sp("bench.tick", 0, 100),
             sp("serve.step", 0, 100), sp("rx.fleet.stack", 1, 2, step=0),
             sp("rx.fleet.pull_scan", 10, 30, step=0),
             sp("rx.fleet.decode", 40, 95, step=0),
             sp("rx.fleet.pull_decode", 50, 90, step=0),
             sp("rx.fleet.pull_decode", 60, 95, step=0),  # overlapping
             sp("bench.tick", 100, 200),
             sp("serve.step", 100, 150),
             sp("rx.fleet.stack", 101, 102, step=1)]
    an = Annotations((0, 200 * MS), spans, [], {})
    _patch(monkeypatch, an)
    ctx = Ctx(Dev([]))
    got = span_self_time.reduce(ctx, "serve.step", [
        "rx.fleet.pull_scan", "rx.fleet.pull_decode"])
    # (100 - 20 - 45) + 50 over two chunk-steps
    assert got == pytest.approx((35 + 50) / 2)
    assert span_self_time.reduce(ctx, "rx.fleet.decode") \
        == pytest.approx(55 / 2)
    assert span_self_time.reduce(ctx, "rx.fleet.classify") is None


def test_span_arg_ratio_sums_before_it_divides(monkeypatch):
    spans = [sp("bench.tick", 0, 100),
             sp("rx.fleet.decode", 1, 2, step=0, useful_symbols=100,
                padded_symbols=1000),
             sp("rx.fleet.decode", 3, 4, step=1, useful_symbols=50,
                padded_symbols=1000),
             sp("rx.fleet.decode", 150, 160, step=2, useful_symbols=999,
                padded_symbols=1000)]           # outside the window
    an = Annotations((0, 100 * MS), spans, [], {})
    _patch(monkeypatch, an)
    ctx = Ctx(Dev([]))
    assert span_arg_ratio.reduce(ctx, "rx.fleet.decode", "useful_symbols",
                                 "padded_symbols", 100.0) \
        == pytest.approx(7.5)
    assert span_arg_ratio.reduce(ctx, "rx.fleet.decode", "useful_symbols",
                                 "no_such_arg") is None


def test_a_step_pair_that_straddles_the_window_is_left_out(monkeypatch):
    spans = [sp("rx.fleet.emit", 5, 6, step=0),          # stack: before
             sp("rx.fleet.stack", 10, 11, step=1),
             sp("rx.fleet.emit", 1010, 1011, step=1),
             sp("rx.fleet.stack", 500, 501, step=2),
             sp("rx.fleet.emit", 1700, 1701, step=2),
             sp("rx.fleet.stack", 1000, 1001, step=3),
             sp("rx.fleet.emit", 2101, 2103, step=3),
             sp("rx.fleet.stack", 1500, 1501, step=4),
             sp("rx.fleet.emit", 2990, 3005, step=4)]    # ends after it
    an = Annotations((0, 3000 * MS), spans, [], {})
    _patch(monkeypatch, an)
    ctx = Ctx(Dev([]))
    got = span_pair_ms.reduce(ctx, "rx.fleet.stack", "rx.fleet.emit")
    assert got == pytest.approx(1103.0)         # median of 1001, 1201, 1103
    assert span_pair_ms.reduce(ctx, "rx.fleet.stack", "rx.fleet.emit",
                               at_least=4) is None


def test_the_new_entries_resolve_and_only_follow_the_old_ones():
    assert manifest.problems() == []
    man = manifest.manifest()
    names = [m["name"] for m in man["per_layer"]]
    old = ["submit_ms_per_step", "step_ms.sat", "step_ms.paced",
           "lane_fill_share", "h2d_bytes_per_step", "d2h_bytes_per_step",
           "scan_device_ms", "decode_device_ms", "classify_gap_ms",
           "host_gap_ms", "viterbi_kernel_ms", "acs_roofline",
           "peak_device_bytes.sat", "peak_device_bytes.paced"]
    assert names[:len(old)] == old
    new = set(names[len(old):])
    assert {"scan_locate_ms", "scan_acquire_ms", "scan_gather_ms",
            "decode_viterbi_ms", "decode_front_ms", "idle_pull_ms",
            "host_work_ms_per_step"} <= new
    for cell in ("mtu8.saturated", "beacon8.saturated", "mtu8.paced"):
        have = {m.name for m in manifest.load_cell(cell).per_layer}
        assert have & new, cell
