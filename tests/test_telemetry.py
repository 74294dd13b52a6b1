"""The runtime telemetry layer (utils/telemetry) and its dispatch
emitters (ISSUE 7): span nesting and thread-safety, histogram
quantile bounds vs exact sorted percentiles, Chrome trace-event JSON
schema validity, the Prometheus-style exposition, the
dispatch/gauge/compile emitter wiring, and — because
the hot paths carry their instrumentation permanently — a pinned
near-zero-overhead check for the disabled path."""

import gc
import json
import math
import os
import threading
import time

import numpy as np
import pytest

from ziria_tpu.utils import dispatch, telemetry


@pytest.fixture(autouse=True)
def collector_off():
    """The tests below count a trace's events exactly, and since
    ISSUE 41 a collection that strikes inside an active trace is an
    event of its own (`rx.pause.gc`; tests/test_fleet_spans.py has
    its test): eight threads appending 400 event dicts are enough to
    bring one on. So the collector rests while each test runs."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


# ------------------------------------------------------------------ spans


def test_span_nesting_contained_and_labelled():
    with telemetry.tracing() as tr:
        with telemetry.span("outer"):
            time.sleep(0.002)
            with telemetry.span("inner"):
                time.sleep(0.001)
    evs = {e["name"]: e for e in tr.events()}
    assert set(evs) == {"outer", "inner"}
    outer, inner = evs["outer"], evs["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    # same thread, and the inner span's [ts, ts+dur) lies inside the
    # outer's — the containment Chrome's nesting model is built on
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert inner["dur"] >= 1000 and outer["dur"] >= inner["dur"]


def test_spans_threadsafe_none_lost():
    """Concurrent spans from many threads: no lost events, and each
    worker's spans all carry that worker's tid (thread idents may be
    REUSED across workers whose lifetimes don't overlap — that is OS
    behavior, not a trace defect — so cross-worker distinctness is
    deliberately not asserted; a gate barrier keeps them overlapping
    enough to exercise real contention)."""
    n_threads, n_spans = 8, 50
    gate = threading.Barrier(n_threads)
    with telemetry.tracing() as tr:
        def worker(i):
            gate.wait()
            for _k in range(n_spans):
                with telemetry.span(f"t{i}"):
                    pass
        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    evs = tr.events()
    assert len(evs) == n_threads * n_spans
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e["tid"])
    for i in range(n_threads):
        assert len(by_name[f"t{i}"]) == n_spans
        assert len(set(by_name[f"t{i}"])) == 1


def test_nested_same_object_activation_stays_balanced():
    """Activating the SAME Trace/MetricsRegistry object in nested
    blocks must deactivate one level per exit, not all of them — the
    outer block keeps collecting after the inner one closes."""
    r = telemetry.MetricsRegistry()
    with telemetry.collect(r):
        with telemetry.collect(r):
            pass
        telemetry.count("after_inner")
    assert r.find("after_inner").value == 1
    assert not telemetry.active()
    t = telemetry.Trace()
    with telemetry.tracing(trace=t):
        with telemetry.tracing(trace=t):
            pass
        with telemetry.span("after"):
            pass
    assert not telemetry.active()
    assert [e["name"] for e in t.events() if e["ph"] == "X"] == ["after"]


def test_overlapping_traces_each_see_their_window():
    with telemetry.tracing() as a:
        with telemetry.span("one"):
            pass
        with telemetry.tracing() as b:
            with telemetry.span("two"):
                pass
        with telemetry.span("three"):
            pass
    assert [e["name"] for e in a.events()] == ["one", "two", "three"]
    assert [e["name"] for e in b.events()] == ["two"]


def test_last_trace_before_inside_and_after_nested(monkeypatch):
    """`last_trace()` (ISSUE 41): the trace most recently ACTIVATED,
    still there once its block has closed, so a reader that was not
    handed the object finds it; None before the first."""
    monkeypatch.setattr(telemetry, "_LAST_TRACE", None)
    assert telemetry.last_trace() is None
    with telemetry.tracing() as a:
        assert telemetry.last_trace() is a
        with telemetry.tracing() as b:
            assert telemetry.last_trace() is b
        # closing the inner block re-activates nothing
        assert telemetry.last_trace() is b
    assert telemetry.last_trace() is b and not telemetry.traced()
    mine = telemetry.Trace()
    with telemetry.tracing(trace=mine):
        assert telemetry.traced()
    assert telemetry.last_trace() is mine
    # building a trace activates nothing
    telemetry.Trace()
    assert telemetry.last_trace() is mine


def test_epoch_lays_a_span_on_perf_counter():
    """`Trace.epoch` is the `perf_counter` value `ts` is relative to:
    `epoch + ts / 1e6` is when the span began on that clock."""
    t_made = time.perf_counter()
    with telemetry.tracing() as tr:
        assert t_made <= tr.epoch <= time.perf_counter()
        time.sleep(0.01)
        before = time.perf_counter()
        with telemetry.span("one", {"step": 1}):
            inside = time.perf_counter()
        after = time.perf_counter()
        tr.complete("by_hand", 123.25, 0.5)
    one, by_hand = tr.events()
    began = tr.epoch + one["ts"] / 1e6
    assert before <= began <= inside
    assert inside <= began + one["dur"] / 1e6 <= after
    assert tr.epoch + by_hand["ts"] / 1e6 == pytest.approx(123.25,
                                                            abs=1e-9)


def test_track_is_a_counter_sample_in_traces_alone():
    with telemetry.collect() as reg, telemetry.tracing() as tr:
        telemetry.track("rx.device_bytes_in_use", 7.0)
    assert [(e["name"], e["ph"], e["args"]) for e in tr.events()] \
        == [("rx.device_bytes_in_use", "C", {"value": 7.0})]
    assert reg.snapshot() == {}
    telemetry.track("rx.device_bytes_in_use", 8.0)      # nothing active


# ------------------------------------------------------------- histograms


def test_histogram_quantile_bounds_vs_exact_percentiles():
    rng = np.random.default_rng(7)
    # log-uniform over ~6 decades: every bucket family gets exercised
    vals = np.exp(rng.uniform(np.log(1e-6), np.log(1.0), 5000))
    h = telemetry.Histogram()
    for v in vals:
        h.observe(float(v))
    s = np.sort(vals)
    for q in (0.5, 0.9, 0.99):
        exact = s[max(1, math.ceil(q * len(s))) - 1]   # nearest rank
        bound = h.quantile(q)
        # the contract: an upper bound never more than 2x above truth
        assert exact <= bound <= 2.0 * exact, (q, exact, bound)
    assert h.max == pytest.approx(float(s[-1]))
    assert h.min == pytest.approx(float(s[0]))
    assert h.sum == pytest.approx(float(vals.sum()), rel=1e-9)
    assert h.count == len(vals)


def test_histogram_exact_powers_and_edge_cases():
    h = telemetry.Histogram()
    assert h.quantile(0.5) is None               # empty
    for v in (0.25, 0.5, 1.0, 2.0):
        h.observe(v)
    # exact powers of two sit at their own bucket's UPPER edge: the
    # p-quantile bound of a single-value bucket is the value itself
    assert h.quantile(0.01) == 0.25
    assert h.quantile(1.0) == 2.0
    h2 = telemetry.Histogram()
    h2.observe(0.0)
    h2.observe(-1.0)
    assert h2.quantile(0.5) <= 0.0               # underflow bucket
    assert h2.count == 2


def test_histogram_summary_block():
    h = telemetry.Histogram()
    for v in (0.001, 0.002, 0.004):
        h.observe(v)
    s = h.summary(scale=1e3, ndigits=4)
    assert s["count"] == 3
    assert s["mean"] == pytest.approx(7.0 / 3, rel=1e-3)
    assert s["max"] == pytest.approx(4.0)
    assert s["p50"] >= 2.0 and s["p99"] >= 4.0
    assert telemetry.Histogram().summary() == {"count": 0}


# ------------------------------------------------------ registry/metrics


def test_registry_counters_gauges_and_snapshot():
    r = telemetry.MetricsRegistry()
    r.counter("frames", kind="data").inc(3)
    r.counter("frames", kind="data").inc(2)     # get-or-create: same
    g = r.gauge("depth")
    g.set(1.0, t=10.0)
    g.set(3.0, t=11.0)
    g.set(2.0, t=12.0)
    snap = r.snapshot()
    assert snap['frames{kind="data"}'] == 5
    assert snap["depth"]["last"] == 2.0
    assert snap["depth"]["max"] == 3.0          # series, not just max
    assert [v for _t, v in snap["depth"]["samples"]] == [1.0, 3.0, 2.0]
    json.dumps(snap)                             # JSON-serializable
    with pytest.raises(TypeError):
        r.gauge("frames", kind="data")           # type collision


def test_registry_prometheus_exposition():
    r = telemetry.MetricsRegistry()
    r.counter("ziria_dispatches_total", site="rx.sync").inc(4)
    r.gauge("ziria_gauge", site="rx.stream_inflight").set(2.0)
    h = r.histogram("ziria_dispatch_seconds", site="rx.sync")
    h.observe(0.001)
    h.observe(0.003)
    text = r.exposition()
    assert "# TYPE ziria_dispatches_total counter" in text
    assert 'ziria_dispatches_total{site="rx.sync"} 4' in text
    assert "# TYPE ziria_gauge gauge" in text
    assert 'ziria_gauge{site="rx.stream_inflight"} 2.0' in text
    assert "# TYPE ziria_dispatch_seconds histogram" in text
    assert 'le="+Inf"' in text
    assert 'ziria_dispatch_seconds_count{site="rx.sync"} 2' in text
    # cumulative bucket discipline: counts never decrease with le
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("ziria_dispatch_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 2


# ------------------------------------------------------ trace JSON schema


def test_chrome_trace_json_schema(tmp_path):
    path = tmp_path / "trace.json"
    with telemetry.tracing(str(path)) as tr:
        with telemetry.span("a", args={"k": 1}):
            pass
        tr.counter("lvl", 2.0)
        telemetry.record_compile("cache_growth:test", n=3,
                                 args={"new_entries": 3})
        telemetry.record_compile("xla:fake_compile", seconds=0.01)
    obj = json.loads(path.read_text())
    assert isinstance(obj["traceEvents"], list)
    assert obj["displayTimeUnit"] == "ms"
    by_ph = {}
    for e in obj["traceEvents"]:
        assert isinstance(e["name"], str)
        assert "ts" in e and "pid" in e and "ph" in e
        by_ph.setdefault(e["ph"], []).append(e)
    assert all("dur" in e and "tid" in e for e in by_ph["X"])
    # the compile span sits in the compile category with its duration
    comp = [e for e in by_ph["X"] if e["cat"] == "compile"]
    assert comp and comp[0]["name"] == "xla:fake_compile" \
        and comp[0]["dur"] == pytest.approx(1e4, rel=1e-3)
    # the cache-growth delta is an instant marker carrying the delta
    inst = by_ph["i"][0]
    assert inst["name"] == "cache_growth:test" \
        and inst["args"]["new_entries"] == 3
    # counter samples carry {"value": v}
    assert by_ph["C"][0]["args"]["value"] == 2.0


# ------------------------------------------------------ dispatch emitters


def test_dispatch_sites_emit_spans_histograms_counters():
    with telemetry.tracing() as tr, telemetry.collect() as reg:
        with dispatch.count_dispatches() as d:
            for _ in range(5):
                with dispatch.timed("rx.fake_site"):
                    pass
            dispatch.record("rx.bare", 2)
    # DispatchCount API unchanged
    assert d.counts["rx.fake_site"] == 5 and d.counts["rx.bare"] == 2
    # trace got one span per timed() block
    assert [e["name"] for e in tr.events()].count("rx.fake_site") == 5
    # registry got the counter and the latency histogram
    assert reg.find(telemetry.DISPATCH_COUNTER,
                    site="rx.fake_site").value == 5
    assert reg.find(telemetry.DISPATCH_COUNTER, site="rx.bare").value \
        == 2
    h = reg.find(telemetry.DISPATCH_HISTOGRAM, site="rx.fake_site")
    assert h.count == 5 and h.quantile(0.99) is not None
    # bare record() carries no duration: counter only
    assert reg.find(telemetry.DISPATCH_HISTOGRAM, site="rx.bare") is None


def test_record_gauge_emits_timeseries_and_counter_track():
    with telemetry.tracing() as tr, telemetry.collect() as reg:
        with dispatch.count_dispatches() as d:
            for v in (1, 2, 1):
                dispatch.record_gauge("rx.fake_inflight", v)
    assert d.gauges["rx.fake_inflight"] == 2        # max, as before
    g = reg.find(telemetry.GAUGE_METRIC, site="rx.fake_inflight")
    assert [v for _t, v in g.samples] == [1.0, 2.0, 1.0]  # the series
    cs = [e for e in tr.events() if e["ph"] == "C"]
    assert [e["args"]["value"] for e in cs] == [1.0, 2.0, 1.0]


def test_telemetry_without_dispatch_counter_active():
    # a trace alone (no count_dispatches) still sees the sites — the
    # CLI --trace path runs exactly this shape
    with telemetry.tracing() as tr:
        with dispatch.timed("rx.solo"):
            pass
    assert [e["name"] for e in tr.events()] == ["rx.solo"]


def test_cache_growth_reports_compile_delta():
    import functools

    @functools.lru_cache(maxsize=None)
    def _jit_fake(n):
        return object()

    with telemetry.tracing() as tr:
        with dispatch.cache_growth(_jit_fake) as g:
            _jit_fake(1)
            _jit_fake(2)
    assert g.total == 2
    evs = [e for e in tr.events()
           if e["name"] == "cache_growth:_jit_fake"]
    assert len(evs) == 1 and evs[0]["args"]["new_entries"] == 2
    # no delta -> no event
    with telemetry.tracing() as tr2:
        with dispatch.cache_growth(_jit_fake):
            _jit_fake(1)
    assert tr2.events() == []


def test_dispatchcount_concurrent_per_instance_locks():
    n_threads, n_each = 8, 300
    with dispatch.count_dispatches() as outer:
        with dispatch.count_dispatches() as inner:
            def worker(i):
                for _ in range(n_each):
                    dispatch.record(f"site{i % 2}",
                                    seconds=1e-6)
                    dispatch.record_gauge("lvl", i)
            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    # no lost updates under per-instance locking, and BOTH active
    # counters (nested) saw every event
    for d in (outer, inner):
        assert d.total == n_threads * n_each
        assert d.counts["site0"] == d.counts["site1"] \
            == n_threads * n_each // 2
        assert d.gauges["lvl"] == n_threads - 1
        assert d.total_time == pytest.approx(
            n_threads * n_each * 1e-6, rel=0.5)


# ------------------------------------------------------- disabled path


def test_disabled_path_overhead_pinned():
    """The hot paths carry record()/timed()/record_gauge()/span()/
    span(name, args) permanently; with nothing active each call must stay in the
    no-allocation fast path. Pinned as a generous wall bound (CI boxes
    are noisy): 50k disabled calls in well under a second — a
    regression to lock-taking or event building blows this by orders
    of magnitude."""
    assert not telemetry.active() and not dispatch._ACTIVE
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        dispatch.record("x")
    t_record = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        dispatch.record_gauge("x", 1.0)
    t_gauge = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        with dispatch.timed("x"):
            pass
    t_timed = time.perf_counter() - t0
    # the fleet receiver's step-keyed spans (ISSUE 25) build their
    # args at the site: a small dict, one truthiness check, a generator
    t0 = time.perf_counter()
    for i in range(n):
        with telemetry.span("x", {"step": i, "bytes": 8}):
            pass
    t_span = time.perf_counter() - t0
    # ~0.1-0.3 µs/call measured; the pin is 20x that
    assert t_record / n < 5e-6, f"record() disabled: {t_record/n:.2e}s"
    assert t_gauge / n < 5e-6, f"record_gauge() disabled: {t_gauge/n:.2e}s"
    assert t_timed / n < 2e-5, f"timed() disabled: {t_timed/n:.2e}s"
    assert t_span / n < 2e-5, f"span(args) disabled: {t_span/n:.2e}s"


# ------------------------------------------------------------- CLI knob


def test_cli_trace_and_metrics_dump(tmp_path, capsys):
    """--trace exports a parseable Chrome trace via the scoped
    ZIRIA_TRACE env (cleared after the invocation); --metrics-dump
    prints the Prometheus exposition."""
    from ziria_tpu.runtime.buffers import StreamSpec, write_stream
    from ziria_tpu.runtime.cli import main as cli_main

    inf, outf = tmp_path / "in.dbg", tmp_path / "out.dbg"
    tracef = tmp_path / "trace.json"
    rng = np.random.default_rng(0)
    write_stream(StreamSpec(ty="bit", path=str(inf), mode="dbg"),
                 rng.integers(0, 2, 64).astype(np.uint8))
    rc = cli_main([
        "--prog=scramble",
        "--input=file", f"--input-file-name={inf}",
        "--input-file-mode=dbg", "--input-type=bit",
        "--output=file", f"--output-file-name={outf}",
        "--output-file-mode=dbg", "--output-type=bit",
        "--backend=jit", f"--trace={tracef}", "--metrics-dump",
    ])
    assert rc == 0
    assert os.environ.get("ZIRIA_TRACE") is None     # scoped, restored
    obj = json.loads(tracef.read_text())
    assert isinstance(obj["traceEvents"], list)
    err = capsys.readouterr().err
    assert "telemetry trace written to" in err
    # the exposition dump ran (its marker line always prints; the
    # metric families below it depend on what the warm caches skipped)
    assert "metrics exposition" in err
