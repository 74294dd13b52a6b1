"""The served path's own tracing (ISSUE 25): step-keyed spans with
counts in `MultiStreamReceiver` / `ServeRuntime`, every one through
`telemetry.span`, and the names inside the two compiled programs.

One small fleet load at the suite-shared streaming geometry (chunk
4096, window 1024, K=8, S=8: test_rx_multistream's compile keys) is
served twice through `ServeRuntime`, once under `telemetry.tracing()`
and once with nothing active. The program half lowers the two fleet
programs (no compile) and reads module names and scopes off the text.
"""

import jax
import numpy as np
import pytest

from ziria_tpu.backend import framebatch
from ziria_tpu.phy import link
from ziria_tpu.phy.wifi import rx
from ziria_tpu.phy.wifi.params import RATES, mixed_trellis_steps
from ziria_tpu.runtime import serve
from ziria_tpu.utils import telemetry

N_BYTES = 12                     # +4 FCS = 16 bytes on air
CHUNK, FRAME_LEN, K, S = 4096, 1024, 8, 8
SLAB = 3072                      # the chunk stride: a chunk-step a tick
RATE_SETS = [[6, 24, 54], [9, 12], [18, 36, 48], [54], [6, 6], [48, 9],
             [12, 24, 36], [18]]

FLEET_SPANS = {"serve.step", "serve.stage", "serve.emit",
               "rx.fleet.ingest", "rx.fleet.stack", "rx.fleet.put",
               "rx.fleet.pull_scan", "rx.fleet.classify",
               "rx.fleet.decode", "rx.fleet.pull_decode",
               "rx.fleet.emit"}
STEP_KEYED = {n for n in FLEET_SPANS if n.startswith("rx.fleet.")} \
    - {"rx.fleet.ingest"}
SCAN_SCOPES = ("rx.scan.locate", "rx.scan.window", "rx.scan.acquire",
               "rx.scan.gather")
DECODE_SCOPES = ("rx.decode.select", "rx.decode.front",
                 "rx.decode.viterbi", "rx.decode.back")


def _n_sym(mbps: int) -> int:
    return -(-(16 + 8 * (N_BYTES + 4) + 6) // RATES[mbps].n_dbps)


def _serve(streams):
    """Every stream through a fresh ServeRuntime, a stride a session a
    tick, then the steps in flight. Returns (runtime, emitted pairs)."""
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True))
    with telemetry.collect(srv.registry):
        for i in range(S):
            assert srv.connect(f"s{i}").admitted
        out, pos = [], 0
        while pos < max(len(s) for s in streams) + CHUNK:
            for i, st in enumerate(streams):
                slab = np.zeros((SLAB, 2), np.float32)
                part = st[pos: pos + SLAB]
                slab[:len(part)] = part
                srv.submit(f"s{i}", slab)
            out += srv.step()
            pos += SLAB
        # the two chunk-steps still in flight, through the runtime's
        # own emit (`serve.emit` counts them like any other)
        out += srv._emit(srv._rx.drain_pending())
    return srv, out


@pytest.fixture(scope="module")
def runs():
    rng = np.random.default_rng(20260927)
    streams = []
    for i, rates in enumerate(RATE_SETS):
        psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
                 for _ in rates]
        st, _starts = link.stream_many(
            psdus, rates, snr_db=30.0, cfo=1e-4, delay=60 + 100 * i,
            seed=70 + i, add_fcs=True, tail=FRAME_LEN)
        streams.append(st)
    built = []
    real = telemetry._annotation_cls

    def counting():
        built.append(1)
        return real()

    # nothing active: no annotation class is even looked up
    telemetry._annotation_cls = counting
    try:
        _srv0, plain = _serve(streams)
    finally:
        telemetry._annotation_cls = real
    with telemetry.tracing() as tr:
        srv, traced = _serve(streams)
    spans = [e for e in tr.events() if e["ph"] == "X"
             and e["cat"] == "host"]
    return srv, spans, traced, plain, len(built)


def _named(spans, name):
    return [e for e in spans if e["name"] == name]


def test_every_span_of_the_table_is_recorded(runs):
    _srv, spans, traced, _plain, _built = runs
    assert len(traced) == sum(len(r) for r in RATE_SETS)
    names = {e["name"] for e in spans}
    assert FLEET_SPANS <= names
    # the dispatch spans of resilience.guarded stay as they were
    assert {"rx.stream_chunk_multi", "rx.stream_decode_multi"} <= names
    for e in spans:
        if e["name"] in STEP_KEYED:
            assert isinstance(e["args"]["step"], int), e
        elif e["name"] in FLEET_SPANS:
            assert "step" not in (e.get("args") or {}), e
    assert all(e["args"] == {"sessions": S}
               for e in _named(spans, "serve.step"))
    assert all(e["args"] == {"lanes": S}
               for e in _named(spans, "rx.fleet.ingest"))


def test_step_pairs_each_stack_with_one_emit_two_ticks_later(runs):
    srv, spans, _traced, _plain, _built = runs
    stacks = {e["args"]["step"]: e for e in _named(spans, "rx.fleet.stack")}
    emits = [e["args"]["step"] for e in _named(spans, "rx.fleet.emit")]
    assert sorted(emits) == sorted(stacks) \
        == list(range(srv._rx.stats.chunk_steps))
    ticks = sorted(_named(spans, "serve.step"), key=lambda e: e["ts"])

    def tick_of(e):
        return max(i for i, t in enumerate(ticks) if t["ts"] <= e["ts"])

    # every tick here launches, so a step's frames come out of the
    # second launch after its own; the last two are the final drain's
    for e in _named(spans, "rx.fleet.emit")[:-2]:
        assert tick_of(e) == tick_of(stacks[e["args"]["step"]]) + 2
    # its front half (the decode's dispatch) runs a tick before that
    for e in _named(spans, "rx.fleet.classify")[:-1]:
        assert tick_of(e) == tick_of(stacks[e["args"]["step"]]) + 1
    # every other step-keyed span of a step lies between the two
    for e in spans:
        if e["name"] in STEP_KEYED:
            st = stacks[e["args"]["step"]]
            assert e["ts"] >= st["ts"]
    for e in _named(spans, "rx.fleet.stack"):
        assert e["args"]["active"] == S
        assert e["args"]["samples"] == S * CHUNK
    # the id rides beside the pending tuple, never inside it
    assert srv._rx._pending is None and srv._rx._pending_step is None
    assert srv._rx.stats.max_in_flight == min(3, srv._rx.stats.chunk_steps)


def test_useful_and_padded_symbols_from_the_frames_sent(runs):
    srv, spans, _traced, _plain, _built = runs
    decodes = _named(spans, "rx.fleet.decode")
    want = sum(_n_sym(m) for rates in RATE_SETS for m in rates)
    assert sum(e["args"]["useful_symbols"] for e in decodes) == want
    bucket = srv._rx.n_sym_bucket
    assert all(e["args"]["padded_symbols"] == S * K * bucket
               for e in decodes)
    assert sum(e["args"]["lanes"] for e in decodes) \
        == sum(len(r) for r in RATE_SETS)
    # the lanes filled of the S x K the program runs whatever they hold
    assert all(e["args"]["slots"] == S * K for e in decodes)
    # and what the bound trellis ran against the bits that filled it
    assert sum(e["args"]["useful_bits"] for e in decodes) \
        == sum(_n_sym(m) * RATES[m].n_dbps
               for rates in RATE_SETS for m in rates)
    assert all(e["args"]["trellis_steps"]
               == S * K * mixed_trellis_steps(bucket) for e in decodes)
    # the same two counts in the registry, for scrape()
    reg = srv.registry
    assert reg.find("rx.decode_symbols", kind="useful").value == want
    assert reg.find("rx.decode_symbols", kind="padded").value \
        == len(decodes) * S * K * bucket
    assert 'rx_decode_symbols{kind="useful"}' in srv.scrape()
    assert sum(e["args"]["frames"]
               for e in _named(spans, "rx.fleet.emit")) \
        == sum(e["args"]["frames"] for e in _named(spans, "serve.emit")) \
        == sum(len(r) for r in RATE_SETS)


def test_classify_counts_the_lanes_owned_and_those_acquired(runs):
    _srv, spans, _traced, _plain, _built = runs
    cls = _named(spans, "rx.fleet.classify")
    assert all(set(e["args"]) == {"step", "candidates", "acquired"}
               and all(isinstance(v, int) for v in e["args"].values())
               for e in cls)
    # a clean load: every lane the scan owned, its window acquired
    assert all(e["args"]["candidates"] == e["args"]["acquired"]
               for e in cls)
    assert sum(e["args"]["candidates"] for e in cls) \
        == sum(len(r) for r in RATE_SETS)
    # the same lanes the decode and the emit of that step then count
    lanes = {e["args"]["step"]: e["args"]["lanes"]
             for e in _named(spans, "rx.fleet.decode")}
    assert all(e["args"]["acquired"] == lanes.get(e["args"]["step"], 0)
               for e in cls)


def test_window_wider_than_the_acquisition_head_loses_no_lane():
    """The same count where it says something: a window twice
    `rx._acquire_head` (the suite geometry's window IS the head), all
    eight rates over two streams with CFO and noise. Every owned lane
    is acquired from its window's head and decodes clean."""
    frame_len = 2 * rx._acquire_head(1 << 16)
    rng = np.random.default_rng(20260928)
    streams, want = [], []
    for i, rates in enumerate(([6, 12, 24, 48], [9, 18, 36, 54])):
        psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
                 for _ in rates]
        st, starts = link.stream_many(
            psdus, rates, snr_db=30.0, cfo=1e-4, delay=60 + 500 * i,
            seed=90 + i, add_fcs=True, tail=frame_len)
        streams.append(st)
        want.append(list(starts))
    with telemetry.tracing() as tr:
        got, stats = framebatch.receive_streams(
            streams, chunk_len=4 * frame_len,
            frame_len=frame_len, max_frames_per_chunk=K, check_fcs=True)
    assert [[f.start for f in r] for r in got] == want
    assert all(f.result.ok and f.result.crc_ok for r in got for f in r)
    cls = [e["args"] for e in tr.events()
           if e["name"] == "rx.fleet.classify"]
    assert len(cls) == stats.chunk_steps >= 1
    assert all(a["candidates"] == a["acquired"] for a in cls)
    assert sum(a["candidates"] for a in cls) == 8


def test_bytes_on_put_and_pulls_redo_the_shape_arithmetic(runs):
    srv, spans, _traced, _plain, _built = runs
    bucket = srv._rx.n_sym_bucket
    # (S, chunk, 2) f32 slab + three (S,) int32 vectors
    assert {e["args"]["bytes"] for e in _named(spans, "rx.fleet.put")} \
        == {S * CHUNK * 2 * 4 + 3 * S * 4}
    # three bool and five int32 (S, K) tables, and overflow (S,) bool
    assert {e["args"]["bytes"]
            for e in _named(spans, "rx.fleet.pull_scan")} \
        == {S * K * (3 * 1 + 5 * 4) + S}
    # (S, K, T) uint8 clear bits, T the bound trellis (216 bits a
    # symbol at this bucket: it is under 152 symbols) + (S, K) bool
    assert mixed_trellis_steps(bucket) == bucket * 216
    assert {e["args"]["bytes"]
            for e in _named(spans, "rx.fleet.pull_decode")} \
        == {S * K * mixed_trellis_steps(bucket) + S * K}


def test_put_and_pulls_say_how_many_devices_they_touch(runs):
    """ISSUE 37: `rx.fleet.put` carries the mesh size and the fleet
    width (a per-layer metric divides them: lanes a chip), the two
    pulls the device-to-host transfers they issue (nine scan scalars,
    the decode's clear bits and FCS flags, from every device), and the
    gauge `rx.mesh_devices` is set once, as the receiver is built, in
    the runtime's own registry. One device here: 8 lanes stay on one
    chip on any host (tests/test_fleet_placement.py has four)."""
    srv, spans, _traced, _plain, _built = runs
    assert srv._rx.mesh is None
    puts = _named(spans, "rx.fleet.put")
    assert puts and all(e["args"]["devices"] == 1
                        and e["args"]["lanes"] == S for e in puts)
    assert {e["args"]["shards"]
            for e in _named(spans, "rx.fleet.pull_scan")} == {9}
    assert {e["args"]["shards"]
            for e in _named(spans, "rx.fleet.pull_decode")} == {2}
    g = srv.registry.find(telemetry.GAUGE_METRIC, site="rx.mesh_devices")
    assert g is not None and g.last == 1.0 and len(g.samples) == 1
    assert 'ziria_gauge{site="rx.mesh_devices"} 1.0' in srv.scrape()


def test_put_and_pulls_say_how_the_pipeline_ran(runs):
    """ISSUE 40: each pull says whether the device had finished before
    the host asked (`ready` of `reads`: `decode_ready_share` divides
    them), each put how many chunk-steps are in flight once its own is
    launched, the counter `rx.pipeline_advances` which way each half of
    a step's drain ran, the gauge the depth."""
    srv, spans, _traced, _plain, _built = runs
    steps = srv._rx.stats.chunk_steps
    for name in ("rx.fleet.pull_scan", "rx.fleet.pull_decode"):
        args = [e["args"] for e in _named(spans, name)]
        assert args and all(a["reads"] == 1 and a["ready"] in (0, 1)
                            for a in args)
    assert sorted(e["args"]["in_flight"]
                  for e in _named(spans, "rx.fleet.put")) \
        == [min(3, n + 1) for n in range(steps)]
    by_how = [srv.registry.find("rx.pipeline_advances", how=how)
              for how in ("launch", "ready", "drain")]
    assert sum(c.value for c in by_how if c is not None) == 2 * steps
    g = srv.registry.find(telemetry.GAUGE_METRIC, site="rx.stream_inflight")
    assert max(v for _t, v in g.samples) == min(3, steps)
    assert 'rx_pipeline_advances{how="' in srv.scrape()


def test_put_names_the_batch_the_detector_convolves_over(runs):
    """`locate_rows` (PR 35): lanes a device x the blocks
    `sync.correlate_valid` cuts a chunk-long row into, from the
    function that picks the fold; static, so the same on every
    step."""
    from ziria_tpu.ops import sync
    _srv, spans, _traced, _plain, _built = runs
    blocks = sync.fold_blocks(CHUNK - 63)
    assert blocks == -(-(CHUNK - 63) // sync.FOLD_BLOCK) == 8
    assert {e["args"]["locate_rows"]
            for e in _named(spans, "rx.fleet.put")} == {S * blocks}


@pytest.mark.parametrize("s,chunk_len,devices,want", [
    (8, 131072, 1, 2048), (1, 131072, 1, 256), (32, 131072, 1, 8192),
    (32, 131072, 4, 2048), (8, 1056, 1, 8)])
def test_locate_rows_by_geometry(s, chunk_len, devices, want):
    """The served geometry, the lone stream, the 32-lane fleet on one
    chip and sharded over four (8 lanes a chip: the shape PR 28 lost
    to), and a chunk too short to fold."""
    mesh = None
    if devices > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    rcv = framebatch.MultiStreamReceiver(
        n_streams=s, chunk_len=chunk_len, frame_len=1024, mesh=mesh)
    assert rcv._locate_rows == want


def test_with_no_trace_same_frames_and_nothing_built(runs):
    _srv, _spans, traced, plain, built = runs
    assert built == 0
    assert [(sid, f.start) for sid, f in plain] \
        == [(sid, f.start) for sid, f in traced]
    for (_s, a), (_t, b) in zip(plain, traced):
        assert a.result.ok and a.result.crc_ok
        assert a.result.rate_mbps == b.result.rate_mbps
        assert np.array_equal(a.result.psdu_bits, b.result.psdu_bits)
    idle = telemetry.Trace()            # built, never activated
    with telemetry.span("rx.fleet.put", {"step": 0, "bytes": 1}):
        pass
    assert idle.events() == []


def test_annotation_takes_args_as_keywords(monkeypatch):
    seen = []

    class Ann:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(telemetry, "_ANN_CLS", Ann)
    with telemetry.tracing(annotate_device=True) as tr:
        with telemetry.span("rx.fleet.put", {"step": 3, "bytes": 8}):
            pass
        with telemetry.span("serve.stage"):
            pass
    assert seen == [("rx.fleet.put", {"step": 3, "bytes": 8}),
                    ("serve.stage", {})]
    assert [e.get("args") for e in tr.events()] \
        == [{"step": 3, "bytes": 8}, None]
    # without annotate_device no annotation is built
    seen.clear()
    with telemetry.tracing():
        with telemetry.span("rx.fleet.put", {"step": 3}):
            pass
    assert seen == []


def test_single_stream_receiver_names_the_fleets_spans():
    """A lone stream is a fleet of one: `receive_stream` runs the
    same named steps, one set per chunk-step, keyed by step id."""
    rng = np.random.default_rng(5)
    st, _ = link.stream_many(
        [rng.integers(0, 256, N_BYTES).astype(np.uint8)], [24],
        snr_db=30.0, cfo=1e-4, delay=60, seed=9, add_fcs=True,
        tail=FRAME_LEN)
    with telemetry.tracing() as tr:
        frames, stats = framebatch.receive_stream(
            st, chunk_len=CHUNK, frame_len=FRAME_LEN,
            max_frames_per_chunk=K, check_fcs=True)
    assert [f.result.ok for f in frames] == [True]
    steps = {}
    for e in tr.events():
        if e["name"].startswith("rx.fleet."):
            steps.setdefault(e["name"], []).append(e["args"]["step"])
    want = list(range(stats.chunks))
    for name in ("rx.fleet.stack", "rx.fleet.put", "rx.fleet.pull_scan",
                 "rx.fleet.classify", "rx.fleet.emit"):
        assert steps[name] == want, (name, steps)
    assert len(steps["rx.fleet.decode"]) == 1     # the one frame


# ------------------------------------------- names inside the programs


def _programs(mesh=None):
    """(name, jitted program, argument shapes) of the two served fleet
    programs at the suite geometry."""
    bucket = 8
    need_b = rx.FRAME_DATA_START + 80 * bucket
    f32, i32 = np.float32, np.int32
    sds = jax.ShapeDtypeStruct
    chunk = rx._jit_stream_chunk_multi(K, FRAME_LEN, bucket, 0.75, 33,
                                       320, mesh, "dp")
    dec = rx._jit_stream_decode_multi(bucket, None, None, 2, mesh, "dp",
                                      False, False)
    return {
        "stream_chunk_multi": (chunk, (
            sds((S, CHUNK, 2), f32), sds((S,), i32), sds((S,), i32),
            sds((S,), i32))),
        "stream_decode_multi": (dec, (
            sds((S, K, need_b, 2), f32),) + (sds((S, K), i32),) * 4),
    }


@pytest.mark.parametrize("name,scopes", [
    ("stream_chunk_multi", SCAN_SCOPES),
    ("stream_decode_multi", DECODE_SCOPES)])
def test_program_carries_its_name_and_every_scope(name, scopes):
    prog, shapes = _programs()[name]
    low = prog.lower(*shapes)
    assert f"module @jit_{name} " in low.as_text()
    text = low.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    # the other program's scopes are not here
    other = set(SCAN_SCOPES + DECODE_SCOPES) - set(scopes)
    assert not any(s in text for s in other)


@pytest.mark.parametrize("name", ["stream_chunk_multi",
                                  "stream_decode_multi"])
def test_sharded_program_carries_the_same_name(name):
    from ziria_tpu.parallel import batch as pbatch
    mesh = pbatch.frame_mesh(8)
    prog, shapes = _programs(mesh)[name]
    assert f"module @jit_{name} " in prog.lower(*shapes).as_text()


@pytest.mark.parametrize("name", ["stream_chunk_multi",
                                  "stream_decode_multi"])
def test_scopes_are_metadata_the_program_is_unchanged(name, monkeypatch):
    import contextlib

    prog, shapes = _programs()[name]
    with_scopes = prog.lower(*shapes).as_text()     # locations stripped
    factories = (rx._jit_stream_chunk_multi, rx._jit_stream_decode_multi)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    for f in factories:
        f.cache_clear()
    try:
        bare_prog, _ = _programs()[name]
        assert bare_prog is not prog
        low = bare_prog.lower(*shapes)
        assert not any(s in low.as_text(debug_info=True)
                       for s in SCAN_SCOPES + DECODE_SCOPES)
        assert low.as_text() == with_scopes
    finally:
        for f in factories:
            f.cache_clear()
