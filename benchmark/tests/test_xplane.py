"""The reduction from a profiler trace to metrics, on a small trace
recorded on the chip: five chunk-steps of ``mtu8.saturated --rehearse``
(tiny geometry) on one TPU v5e (PR 24), trimmed to the planes and lines
the reduction reads."""

import os

import pytest

from benchmark.harness import xplane
from benchmark.reducers import device_gap, device_module_time, \
    kernel_time, roofline_share

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tiny_v5e.xplane.pb")


class Ctx:
    def __init__(self, tr):
        self.device = tr
        self.counters = {"acs_min_bytes": 64 * 8 * 216 * 16}
        self.peaks = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tr():
    return xplane.read(TRACE)


def test_the_two_programs_named_jit_f_are_told_apart(tr):
    assert tr.devices == 1
    assert {k: len(v) for k, v in tr.modules.items()} == \
        {"scan": 5, "decode": 5, "other": 0}
    # as recorded: the scan 15.16 ms a run, the decode 0.99 ms
    scan = [e.end - e.start for e in tr.modules["scan"]]
    dec = [e.end - e.start for e in tr.modules["decode"]]
    assert 15.1e6 < min(scan) <= max(scan) < 15.2e6
    assert 0.95e6 < min(dec) <= max(dec) < 1.05e6
    # every decode run holds a Viterbi kernel op, no scan run does
    kernels = [o for o in tr.ops if xplane.KERNEL.match(o.name)]
    assert len(kernels) == 10
    for k in kernels:
        assert any(d.start <= k.start < d.end for d in tr.modules["decode"])
        assert not any(s.start <= k.start < s.end
                       for s in tr.modules["scan"])


def test_busy_time_is_the_union_of_op_intervals_inside_the_window(tr):
    assert tr.window_s == pytest.approx(0.105903138)
    assert tr.busy_s == pytest.approx(0.080741168)
    assert 0 < tr.busy_s < tr.window_s
    # the union never counts overlapping ops twice
    total = sum(o.end - o.start for o in tr.ops) / 1e9
    assert tr.busy_s <= total + 1e-9
    assert xplane.union_ns([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]


def test_readers(tr):
    ctx = Ctx(tr)
    assert device_module_time.reduce(ctx, module="scan") == \
        pytest.approx(15.163268)
    assert device_module_time.reduce(ctx, module="decode") == \
        pytest.approx(0.98629, rel=1e-3)
    # the decode is queued behind the next scan: no gap before it; the
    # host's turnaround shows before each scan
    assert device_gap.reduce(ctx, after="scan", before="decode") < 0.1
    assert device_gap.reduce(ctx, after="any", before="scan") > 1.0
    acs = kernel_time.reduce(ctx, pattern=r"^%_acs_tiles\b", calls="decode")
    both = kernel_time.reduce(ctx, pattern=r"^%_(acs|traceback)_tiles\b",
                              calls="decode")
    assert 0 < acs < both < 0.986194
    share = roofline_share.reduce(ctx, pattern=r"^%_acs_tiles\b",
                                  bytes="acs_min_bytes",
                                  peak="hbm_bytes_per_s", calls="decode")
    assert 0 < share < 100
    assert kernel_time.reduce(ctx, pattern="^%no_such_kernel",
                              calls="decode") is None


def test_breakdown(tr):
    ops = xplane.top_device_ops(tr)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    assert ops[0][0].startswith("%") and " = " in ops[0][0]
    gaps = xplane.idle_gaps(tr)
    assert gaps and all(s > 0 for _n, s in gaps)
    idle = tr.window_s - tr.busy_s
    assert sum(s for _n, s in gaps) <= idle + 1e-9
    # charged to spans, ours here (PR 24's trace: the program had
    # none yet), never to a python frame, whose line moves with every
    # edit (PR 36)
    assert {n for n, _s in gaps} == {"bench.step", "(gaps under 50 us)"}


def test_idle_gaps_are_charged_to_the_programs_spans_serve_among_them():
    """The trace of PR 25 (the program's spans on): ``serve.*`` are
    among the host events a gap may be charged to, and the deepest one
    open takes it."""
    tr = xplane.read(os.path.join(os.path.dirname(TRACE),
                                  "tiny_v5e_scoped.xplane.pb"))
    names = {e.name for e in tr.host}
    assert {"serve.step", "serve.stage", "serve.emit",
            "rx.fleet.pull_decode", "bench.tick"} <= names
    assert not any(n.startswith("$") for n in names)
    gaps = dict(xplane.idle_gaps(tr))
    assert max(gaps, key=gaps.get) == "rx.fleet.pull_decode"
    assert all(xplane.HOST_LABEL.match(n) or n.startswith("(")
               for n in gaps)
    # a gap outside every rx.* span but inside step() goes to serve.step
    step = next(e for e in tr.host if e.name == "serve.step")
    inner = [e for e in tr.host if e.name != "serve.step"
             and step.start <= e.start and e.end <= step.end
             and not e.name.startswith("bench.")]
    free = step.start + 1.0
    assert not any(e.start <= free <= e.end for e in inner)
    assert xplane._label(tr.host, free) == "serve.step"


def test_a_trace_without_a_device_is_refused(tmp_path):
    from jax.profiler import ProfileData
    blob = ProfileData.text_proto_to_serialized_xspace(
        'planes { name: "/host:CPU" }')
    p = tmp_path / "empty.xplane.pb"
    p.write_bytes(blob)
    with pytest.raises(ValueError, match="no /device:TPU"):
        xplane.read(str(p))
