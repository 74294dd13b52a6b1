"""The served path's own tracing (ISSUE 25): step-keyed spans with
counts in `MultiStreamReceiver` / `ServeRuntime`, every one through
`telemetry.span`, and the names inside the two compiled programs.

One small fleet load at the suite-shared streaming geometry (chunk
4096, window 1024, K=8, S=8: test_rx_multistream's compile keys) is
served twice through `ServeRuntime`, once under `telemetry.tracing()`
and once with nothing active. The program half lowers the two fleet
programs (no compile) and reads module names and scopes off the text.
"""

import gc
import time

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
#: the two dispatch spans of `resilience.guarded` carry the id too
DISPATCH_SPANS = {"rx.stream_chunk_multi", "rx.stream_decode_multi"}
STEP_KEYED = {n for n in FLEET_SPANS if n.startswith("rx.fleet.")} \
    | DISPATCH_SPANS
SCAN_SCOPES = ("rx.scan.locate", "rx.scan.window", "rx.scan.acquire",
               "rx.scan.gather", "rx.scan.gather.derotate")
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

    # nothing active: no annotation class is even looked up, no
    # collection is listened for, no lane's fill is timed
    telemetry._annotation_cls = counting
    try:
        srv0, plain = _serve(streams)
    finally:
        telemetry._annotation_cls = real
    idle = {"built": len(built),
            "gc_callbacks": telemetry._on_gc in gc.callbacks,
            "full_since": srv0._rx._full_since}
    with telemetry.tracing() as tr:
        srv, traced = _serve(streams)
    spans = [e for e in tr.events() if e["ph"] == "X"
             and e["cat"] == "host"]
    return srv, spans, traced, plain, idle


def _named(spans, name):
    return [e for e in spans if e["name"] == name]


def test_every_span_of_the_table_is_recorded(runs):
    _srv, spans, traced, _plain, _built = runs
    assert len(traced) == sum(len(r) for r in RATE_SETS)
    names = {e["name"] for e in spans}
    assert FLEET_SPANS <= names
    assert DISPATCH_SPANS <= names
    for e in spans:
        if e["name"] in STEP_KEYED:
            assert isinstance(e["args"]["step"], int), e
        elif e["name"] in FLEET_SPANS:
            assert "step" not in (e.get("args") or {}), e
    assert all(e["args"] == {"sessions": S}
               for e in _named(spans, "serve.step"))
    assert all(set(e["args"]) == {"step", "lanes", "written"}
               and e["args"]["lanes"] == S
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
    # every other step-keyed span of a step lies between the two (but
    # the ingest that fills its lanes, which comes before the stack)
    for e in spans:
        if e["name"] in STEP_KEYED - {"rx.fleet.ingest"}:
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
    # the slots the program fronts for the step's lanes, whole groups
    # of them, and the lanes of the tiles its trellis runs
    # (`rx.decode_walk`, the program's own rule; the values and the
    # trip counts: test_rx_multistream), each at most all S x K
    walked, decoded = zip(*(
        [min(w, S * K) for w in rx.decode_walk(e["args"]["lanes"], S * K)]
        for e in decodes))
    assert all(e["args"]["lanes"] <= w <= d
               for e, w, d in zip(decodes, walked, decoded))
    assert [e["args"]["padded_symbols"] for e in decodes] \
        == [w * bucket for w in walked]
    assert sum(e["args"]["lanes"] for e in decodes) \
        == sum(len(r) for r in RATE_SETS)
    assert [e["args"]["slots"] for e in decodes] == list(walked)
    assert all(e["args"]["window_samples"] == S * K * FRAME_LEN
               for e in decodes)
    # and what the trellis ran against the bits that filled it: the
    # lanes of the tiles x the steps up to the tile's longest frame
    # (`rx.decode_bound`, PR 53; the values a table: test_trellis_bound,
    # what the program ran: test_rx_multistream). A 16-byte frame holds
    # 168 to 288 data bits by its rate: 3, 4 or 5 blocks of 64, where
    # the bucket's whole trellis is 27
    assert sum(e["args"]["useful_bits"] for e in decodes) \
        == sum(_n_sym(m) * RATES[m].n_dbps
               for rates in RATE_SETS for m in rates)
    bits = sorted({_n_sym(m) * RATES[m].n_dbps for m in RATES})
    assert (bits[0], bits[-1], mixed_trellis_steps(bucket)) \
        == (168, 288, 27 * 64)
    for e, d in zip(decodes, decoded):
        steps, left = divmod(e["args"]["trellis_steps"], d)
        assert left == 0 and steps in (192, 256, 320)
        # the longest lane is at least the mean one
        assert steps * e["args"]["lanes"] >= e["args"]["useful_bits"]
    # the benchmark's two-sided check of the same spans finds nothing
    # (`counts.stale`: at or above `useful_bits`, at or below the
    # whole trellis in every slot)
    from types import SimpleNamespace

    from benchmark.harness import counts
    assert counts.stale(
        [SimpleNamespace(name=e["name"], args=e["args"]) for e in decodes],
        S, K, bucket) == []
    # the same counts in the registry, for scrape()
    reg = srv.registry
    assert reg.find("rx.decode_symbols", kind="useful").value == want
    assert reg.find("rx.decode_symbols", kind="padded").value \
        == sum(walked) * bucket
    assert reg.find("rx.decode_slots", kind="live").value \
        == sum(len(r) for r in RATE_SETS)
    assert reg.find("rx.decode_slots", kind="walked").value == sum(walked)
    assert 'rx_decode_symbols{kind="useful"}' in srv.scrape()
    assert 'rx_decode_slots{kind="walked"}' in srv.scrape()
    assert sum(e["args"]["frames"]
               for e in _named(spans, "rx.fleet.emit")) \
        == sum(e["args"]["frames"] for e in _named(spans, "serve.emit")) \
        == sum(len(r) for r in RATE_SETS)


def test_classify_counts_the_lanes_owned_and_those_acquired(runs):
    _srv, spans, _traced, _plain, _built = runs
    cls = _named(spans, "rx.fleet.classify")
    assert all(set(e["args"]) == {"step", "candidates", "acquired",
                                  "cfo_abs_max_urad", "cfo_abs_sum_urad"}
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


def test_classify_says_how_far_off_carrier_the_frames_were(runs):
    """ISSUE 45: the scan's estimate comes to the host inside the rate
    word (`rx.pack_rate_word`: to 7.6 micro-radians a sample, in the
    bytes the pull already had), and `rx.fleet.classify` carries the
    step's widest and the sum over its acquired frames: every stream
    here was sent 1e-4 rad/sample off, and the estimators' scatter at
    30 dB over a 320-sample preamble is under 1e-4. The fleet sets
    the level a LANE for `scrape()`: S series however many sessions
    come and go, a sample only when a lane's level moved."""
    srv, spans, _traced, _plain, _built = runs
    cls = [e["args"] for e in _named(spans, "rx.fleet.classify")
           if e["args"]["acquired"]]
    assert cls
    for a in cls:
        assert 20 <= a["cfo_abs_max_urad"] <= 220
        assert a["cfo_abs_max_urad"] <= a["cfo_abs_sum_urad"] \
            <= a["acquired"] * a["cfo_abs_max_urad"]
    mean = sum(a["cfo_abs_sum_urad"] for a in cls) \
        / sum(a["acquired"] for a in cls)
    assert 70 <= mean <= 130
    idle = [e["args"] for e in _named(spans, "rx.fleet.classify")
            if not e["args"]["acquired"]]
    assert all(a["cfo_abs_max_urad"] == a["cfo_abs_sum_urad"] == 0
               for a in idle)
    lines = [ln for ln in srv.scrape().splitlines()
             if 'site="rx.stream_cfo_abs_max_urad"' in ln]
    assert len(lines) == S
    for i in range(S):
        (line,) = [ln for ln in lines if f'lane="{i}"' in ln]
        assert 20 <= float(line.split()[-1]) <= 220
        assert srv._rx._cfo_urad[i] == int(float(line.split()[-1]))


def test_the_offset_gauge_is_a_series_a_lane_and_samples_only_moves():
    """A registry never drops a series, so the gauge is labelled from
    a set the configuration bounds (the lane), a level that did not
    move takes no sample, and a lane whose stream is reset (a session
    closed or evicted) reads 0 again and not the last radio's."""
    rcv = object.__new__(framebatch.MultiStreamReceiver)
    rcv._cfo_urad = [0, 0]
    reg = telemetry.MetricsRegistry()
    with telemetry.collect(reg), telemetry.tracing() as tr:
        for urad in (36600, 36600, 36608, 36608):
            rcv._note_cfo(1, urad)
        rcv._note_cfo(0, 0)               # never moved: no series yet
        rcv._note_cfo(1, 0)               # what `reset_stream` does
    g = reg.find(telemetry.GAUGE_METRIC,
                 site="rx.stream_cfo_abs_max_urad", lane="1")
    assert [v for _t, v in g.samples] == [36600.0, 36608.0, 0.0]
    assert [k for k, _m in reg.metrics()] == [
        (telemetry.GAUGE_METRIC,
         (("lane", "1"), ("site", "rx.stream_cfo_abs_max_urad")))]
    assert [(e["name"], list(e["args"].values())) for e in tr.events()
            if e.get("ph") == "C"] \
        == [("rx.stream_cfo_abs_max_urad[lane=1]", [v])
            for v in (36600, 36608, 0)]


def test_the_rate_word_round_trips_the_offset_to_24_hz():
    eps = np.array([0.0366, -0.0366, 1e-4, 0.0, 0.19634, -0.3, 0.3],
                   np.float32)
    rate = np.arange(7, dtype=np.uint32) + 3
    word = np.asarray(rx.pack_rate_word(rate, eps))
    assert word.dtype == np.uint32
    got_rate, urad = rx.unpack_rate_word(word)
    assert list(got_rate) == list(rate)
    # clipped at the int16's ends, +-0.25 rad/sample, past pi / 16
    want = np.clip(eps.astype(np.float64), -0.25, 0.25 - 2.0 ** -17)
    assert np.abs(urad - want * 1e6).max() <= 0.5 * 1e6 / 2 ** 17 + 0.5
    assert 36000 <= urad[0] <= 37200 and urad[1] == -urad[0]


def test_emit_and_decode_say_what_the_window_held(runs):
    """ISSUE 43: `rx.fleet.emit` carries the step's `acquired` again
    beside `truncated` (the frames its windows could not hold: none on
    this load; tests/test_maxpsdu_deployment.py has some), and
    `rx.fleet.decode` each frame's samples on air against the whole
    window every slot was cut at."""
    srv, spans, _traced, _plain, _built = runs
    acquired = {e["args"]["step"]: e["args"]["acquired"]
                for e in _named(spans, "rx.fleet.classify")}
    emits = _named(spans, "rx.fleet.emit")
    assert all(set(e["args"]) == {"step", "frames", "acquired",
                                  "truncated"} for e in emits)
    assert all(e["args"]["truncated"] == 0
               and e["args"]["acquired"] == acquired[e["args"]["step"]]
               == e["args"]["frames"] for e in emits)
    assert srv._rx.stats.truncated_frames == 0
    assert srv.registry.find("rx.stream_frames_truncated") is None
    decodes = _named(spans, "rx.fleet.decode")
    assert all(e["args"]["window_samples"] == S * K * FRAME_LEN
               for e in decodes)
    assert sum(e["args"]["frame_samples"] for e in decodes) \
        == sum(rx.FRAME_DATA_START + 80 * _n_sym(m)
               for rates in RATE_SETS for m in rates)


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
    # three bool and five int32 (S, K) tables, and overflow (S,) bool:
    # the benchmark's ceiling, to the byte, with the carrier offset
    # inside the rate word (ISSUE 45)
    from benchmark.harness import counts
    assert {e["args"]["bytes"]
            for e in _named(spans, "rx.fleet.pull_scan")} \
        == {S * K * (3 * 1 + 5 * 4) + S} == {counts.scan_d2h_bytes(S, K)}
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
    `sync.ccorrelate_valid` cuts a chunk-long row into, from the
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
    _srv, _spans, traced, plain, idle = runs
    assert idle == {"built": 0, "gc_callbacks": False,
                    "full_since": None}
    assert telemetry._on_gc not in gc.callbacks     # nor after a trace
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


def test_one_step_id_from_a_samples_arrival_to_its_frames_emission(runs):
    """ISSUE 41: `rx.fleet.ingest` carries the id of the NEXT launch
    and the two dispatch spans their chunk-step's, so every span of a
    flight shares one identifier, in whichever call it runs."""
    srv, spans, _traced, _plain, _idle = runs
    steps = srv._rx.stats.chunk_steps
    by_step = {}
    for e in spans:
        if e["name"] in STEP_KEYED:
            by_step.setdefault(e["args"]["step"], []).append(e)
    # the ingests after the last launch name a step that never came
    assert set(range(steps)) <= set(by_step)
    always = {"rx.fleet.ingest", "rx.fleet.stack", "rx.fleet.put",
              "rx.stream_chunk_multi", "rx.fleet.pull_scan",
              "rx.fleet.classify", "rx.fleet.emit"}
    decoded = {"rx.fleet.decode", "rx.stream_decode_multi",
               "rx.fleet.pull_decode"}
    for step in range(steps):
        evs = sorted(by_step[step], key=lambda e: e["ts"])
        names = [e["name"] for e in evs]
        assert always <= set(names), (step, names)
        assert set(names) - always in (set(), decoded), (step, names)
        # the ingest that filled the lanes comes first, the emit last
        assert names[0] == "rx.fleet.ingest"
        assert names[-1] == "rx.fleet.emit"
        # and each dispatch span lies inside its step's launch or decode
        outer = {"rx.stream_chunk_multi": "rx.fleet.stack",
                 "rx.stream_decode_multi": "rx.fleet.decode"}
        at = {e["name"]: e for e in evs}
        for inner, first in outer.items():
            if inner in at:
                assert at[inner]["ts"] >= at[first]["ts"]


@pytest.fixture(scope="module")
def busy():
    """One stream with a frame in every chunk of its first five, the
    same on every lane of a bare fleet: what the `how` and `ready_ms`
    cases drive by hand."""
    rng = np.random.default_rng(20260930)
    rates = [6, 24, 54] * 3
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in rates]
    st, _starts = link.stream_many(
        psdus, rates, gaps=[1500] * (len(rates) - 1), snr_db=30.0,
        cfo=1e-4, delay=60, seed=41, add_fcs=True, tail=FRAME_LEN)
    assert len(st) >= CHUNK + 3 * SLAB
    return st


def _fleet():
    return framebatch.MultiStreamReceiver(
        n_streams=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True)


def _hows(tr, name):
    return {e["args"]["step"]: e["args"]["how"] for e in tr.events()
            if e["name"] == name}


def test_a_pull_says_how_its_half_was_reached(busy):
    """`how` on the two pulls (ISSUE 41): `launch` behind a launch,
    `ready` from a call that launched nothing, `drain` at a drain
    point: the value `rx.pipeline_advances` is labelled with."""
    rx_ = _fleet()
    cuts = [0, CHUNK, CHUNK + SLAB, CHUNK + 2 * SLAB, CHUNK + 3 * SLAB]
    with telemetry.tracing() as tr:
        for a, b in zip(cuts[:3], cuts[1:4]):       # steps 0, 1, 2
            rx_.push_many([busy[a:b]] * S)
        # a closed loop: step 0 went through both halves behind
        # launches, step 1 through its front half
        assert _hows(tr, "rx.fleet.pull_scan") == {0: "launch",
                                                   1: "launch"}
        assert _hows(tr, "rx.fleet.pull_decode") == {0: "launch"}
        out = rx_.drain_pending()
        assert out and rx_._pending is None
        assert _hows(tr, "rx.fleet.pull_scan")[2] == "drain"
        assert {s: h for s, h in _hows(
            tr, "rx.fleet.pull_decode").items() if s} \
            == {1: "drain", 2: "drain"}
        # a launch, then calls that launch nothing once the device is
        # done: each half is found ready
        rx_.push_many([busy[cuts[3]:cuts[4]]] * S)  # step 3
        jax.block_until_ready(rx_._flight[-1].outs)
        got = rx_.push_many({})
        st = rx_._flight[0] if rx_._flight else None
        if st is not None:      # its decode, dispatched just now
            jax.block_until_ready(st.dec_out)
            got += rx_.push_many({})
        assert got and rx_._pending is None
        assert _hows(tr, "rx.fleet.pull_scan")[3] == "ready"
        assert _hows(tr, "rx.fleet.pull_decode")[3] == "ready"
    adv = {how: sum(1 for e in tr.events() if e["ph"] == "X"
                    and (e.get("args") or {}).get("how") == how)
           for how in ("launch", "ready", "drain")}
    assert adv == {"launch": 3, "ready": 2, "drain": 3}


def test_ready_ms_is_the_wait_from_a_full_lane_to_its_launch(busy):
    """`ready_ms` on `rx.fleet.stack` (ISSUE 41): next to nothing in a
    lockstep `push_many` (the rest of that call's ingest), and longer
    by whatever passes between a lane's fill and the push that
    launches."""
    rx_ = _fleet()
    nap = 0.2
    with telemetry.tracing() as tr:
        rx_.push_many([busy[:CHUNK]] * S)                   # step 0
        rx_._ingest(0, busy[CHUNK:CHUNK + SLAB])            # lane 0 full
        assert rx_._full_since is not None
        time.sleep(nap)
        rx_.push_many({i: busy[CHUNK:CHUNK + SLAB]
                       for i in range(1, S)})               # step 1
        assert rx_._full_since is None
        rx_.drain_pending()
    waits = {e["args"]["step"]: e["args"]["ready_ms"]
             for e in tr.events() if e["name"] == "rx.fleet.stack"}
    assert 0.0 <= waits[0] < 1e3 * nap / 2
    assert waits[1] >= 1e3 * nap > waits[0]
    # with no trace no lane's fill is timed
    rx_.push_many([busy[CHUNK + SLAB:CHUNK + 2 * SLAB]] * S)
    assert rx_._full_since is None
    rx_.drain_pending()


def test_a_collection_inside_a_trace_is_a_span(monkeypatch):
    """`rx.pause.gc` (ISSUE 41): one `gc.callbacks` entry while any
    trace is active, gone when the last closes; each collection a span
    with its `generation` and what it `collected`, and an annotation
    (the generation alone: it is entered at the start) when the trace
    annotates the device."""
    seen = []

    class Ann:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(telemetry, "_ANN_CLS", Ann)
    assert telemetry._on_gc not in gc.callbacks
    with telemetry.tracing() as outer:
        with telemetry.tracing(annotate_device=True) as inner:
            assert gc.callbacks.count(telemetry._on_gc) == 1
            with telemetry.span("rx.fleet.stack", {"step": 0}):
                gc.collect()
        assert gc.callbacks.count(telemetry._on_gc) == 1
        gc.collect(0)
    assert telemetry._on_gc not in gc.callbacks
    full = [e for e in inner.events() if e["name"] == telemetry.GC_SPAN
            and e["args"]["generation"] == 2]
    assert len(full) == 1 and full[0]["args"]["collected"] >= 0
    stack = next(e for e in inner.events()
                 if e["name"] == "rx.fleet.stack")
    assert stack["ts"] <= full[0]["ts"] and full[0]["ts"] \
        + full[0]["dur"] <= stack["ts"] + stack["dur"]
    assert (telemetry.GC_SPAN, {"generation": 2}) in seen
    # the outer trace saw both, the inner one only its own
    gens = [e["args"]["generation"] for e in outer.events()
            if e["name"] == telemetry.GC_SPAN]
    assert 2 in gens and gens[-1] == 0
    assert len([e for e in inner.events()
                if e["name"] == telemetry.GC_SPAN]) < len(gens)


def test_annotation_takes_args_as_keywords(monkeypatch):
    seen = []
    # every annotation is listed below: none for a collection
    monkeypatch.setattr(telemetry, "_on_gc", lambda phase, info: None)

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
