"""The receiver's pipeline (ISSUE 40): three chunk-steps in flight, a
launch that blocks only on the oldest, a push that launches nothing and
hands back what is ready.

What comes out never depends on when: over seeded streams, lockstep and
ragged pushes, one lane and eight, one device and the suite's mesh, the
pipelined receiver's emissions equal, frame for frame, byte for byte
and in order, those of the per-capture oracle (``streaming=False``)
and of the plain numpy reference, wherever `drain_pending`,
`flush_stream`, `reset_stream` or `checkpoint` / `restore_stream` fall
between two steps in flight. WHEN is pinned where it is a rule: a
launch hands back the frames of `_pending`'s chunk-step, the oldest.
And WHAT `_pending` keeps (ISSUE 42): a step's stacked host array comes
from the receiver's own store and is written again only once nothing
but that store holds it (the last section).
"""

import os
import re

import numpy as np
import pytest

from benchmark.reference.wifi_rx_ref import np_receive
from ziria_tpu.backend import framebatch
from ziria_tpu.phy import link
from ziria_tpu.runtime import serve
from ziria_tpu.utils import dispatch, telemetry
from ziria_tpu.utils.bits import np_bits_to_bytes

N_BYTES = 12     # +4 FCS = the suite's standard 16-byte on-air PSDU
CHUNK, FRAME_LEN, K, S = 4096, 1024, 8, 8
STRIDE = CHUNK - FRAME_LEN
GEO = dict(chunk_len=CHUNK, frame_len=FRAME_LEN,
           max_frames_per_chunk=K, check_fcs=True)
RATES_OF = [6, 9, 12, 18, 24, 36, 48, 54]
N_FRAMES = 10
#: samples of every stream pushed before the drain point: three chunks,
#: the last completed by the last sample, so the push that ends there
#: launches and leaves two chunk-steps in flight
P = CHUNK + 2 * STRIDE
SLAB = 1024
SCENARIOS = ("none", "drain", "checkpoint", "flush_stream",
             "reset_stream")


def _stream(rng, seed):
    """One seeded stream of N_FRAMES frames behind a stretch of idle
    air that keeps every frame off sample P (a lane flushed there
    closes between two frames)."""
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in range(N_FRAMES)]
    rates = [RATES_OF[(seed + n) % 8] for n in range(N_FRAMES)]
    st, starts = link.stream_many(
        psdus, rates, gaps=rng.integers(1000, 1600, N_FRAMES - 1),
        snr_db=30.0, cfo=1e-4, delay=60, seed=seed, add_fcs=True,
        tail=FRAME_LEN)
    idle = next(d for d in range(0, 3000, 97) if not any(
        P - FRAME_LEN - 100 < s + d < P + 64 for s in starts))
    st = np.concatenate([np.zeros((idle, 2), np.float32), st])
    assert len(st) > P + 2 * STRIDE        # five chunk-steps and a tail
    return st, [int(s) + idle for s in starts]


@pytest.fixture(scope="module")
def corpus():
    """Eight seeded streams, the second stream lane S-1 (or 0) takes
    after a `reset_stream`, and every stream's true frame starts."""
    rng = np.random.default_rng(20260930)
    made = [_stream(rng, 400 + i) for i in range(S + 1)]
    return ([st for st, _ in made[:S]], [sts for _, sts in made[:S]],
            made[S][0], made[S][1])


def _cuts(rng, lo, hi, ragged):
    """Slab edges of one stream from sample ``lo`` to ``hi``."""
    if hi <= lo:
        return [lo]
    if not ragged:
        return list(range(lo, hi, SLAB)) + [hi]
    n = max(1, (hi - lo) // SLAB)
    inner = sorted(set(int(x) for x in rng.integers(lo + 1, hi, n)))
    return [lo] + inner + [hi]


def _rounds(rng, streams, lo, his, ragged):
    """Push rounds from ``lo`` to each stream's ``his[i]``: a dict of
    slabs a round, the LAST round carrying every stream's last slab."""
    cuts = [_cuts(rng, lo, hi, ragged) for hi in his]
    n = max((len(c) - 1 for c in cuts), default=0)
    rounds = []
    for r in range(n):
        push = {}
        for i, c in enumerate(cuts):
            k = r - (n - (len(c) - 1))     # right-aligned: all end at n
            if k >= 0:
                push[i] = streams[i][c[k]: c[k + 1]]
        rounds.append(push)
    return rounds


def _drive(rx, streams, alt, scenario, ragged, seed, at_point=None):
    """Phase one (``P`` samples a stream), the scenario's drain point on
    lane j = S-1, phase two (the rest; lane j's second stream after a
    reset, nothing after its flush), `flush`. Returns the emissions
    before the reset took effect and after it."""
    rng = np.random.default_rng(seed)
    s = len(streams)
    j = s - 1
    before = []
    for push in _rounds(rng, streams, 0, [P] * s, ragged):
        before += rx.push_many(push)
    if at_point is not None:
        at_point(rx, j)
    rest = list(streams)
    his = [len(st) for st in streams]
    lo = P
    if scenario == "drain":
        before += rx.drain_pending()
        assert rx._pending is None and rx._pending_step is None
    elif scenario == "checkpoint":
        blob, out = rx.checkpoint(j)
        before += out
        assert rx._pending is None
        before += rx.restore_stream(j, blob)
    elif scenario == "flush_stream":
        before += rx.flush_stream(j)
        assert rx._pending is None
        his[j] = P                          # nothing more for lane j
    elif scenario == "reset_stream":
        before += rx.reset_stream(j)
        assert not rx._pending_touches(j)
    after = []
    if scenario == "reset_stream":
        # lane j starts over at sample 0 of its second stream, the
        # others go on from P: two schedules, interleaved round by round
        a = _rounds(rng, rest[:j], lo, his[:j], ragged)
        b = _rounds(rng, [alt], 0, [len(alt)], ragged)
        for r in range(max(len(a), len(b))):
            push = dict(a[r]) if r < len(a) else {}
            if r < len(b):
                push[j] = b[r][0]
            after += rx.push_many(push)
    else:
        for push in _rounds(rng, rest, lo, his, ragged):
            after += rx.push_many({i: x for i, x in push.items()
                                   if len(x)})
    after += rx.flush()
    return before, after


def _per_stream(pairs, s):
    per = [[] for _ in range(s)]
    for i, fr in pairs:
        per[i].append(fr)
    return per


def _same_result(a, b) -> bool:
    return (a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)


ORACLE = {}


def _oracle(streams, alt):
    """The per-capture oracle (``streaming=False``: every owned window
    through `rx.receive`) over the whole of every stream, on one
    device, once a fleet width; and over lane j's second stream."""
    key = len(streams)
    if key not in ORACLE:
        rx = framebatch.MultiStreamReceiver(
            len(streams), streaming=False, **GEO)
        out = rx.push_many(list(streams)) + rx.flush()
        ORACLE[key] = _per_stream(out, len(streams))
    if "alt" not in ORACLE:
        ORACLE["alt"], _st = framebatch.receive_stream(
            alt, streaming=False, **GEO)
    return ORACLE[key], ORACLE["alt"]


def _expected(whole, second, i, j, scenario):
    """What lane i hands back before lane j's drain point took effect
    and after, from the oracle of the whole streams: a lane flushed at
    P keeps the frames before it, a lane reset there those its three
    launched chunks own and then its second stream's."""
    if i != j or scenario in ("none", "drain", "checkpoint"):
        return whole[i], []
    if scenario == "flush_stream":
        return [f for f in whole[i] if f.start < P], []
    return [f for f in whole[i] if f.start < 3 * STRIDE], second


def _mesh(placement):
    if placement == "one device":
        return None
    from ziria_tpu.parallel.batch import frame_mesh
    return frame_mesh(8)


#: every fleet x cut x drain point, but for the mesh under ragged pushes,
#: which keeps the two that say most (none, and a lane reset mid-flight)
CASES = [(lanes, placement, cut, scenario)
         for lanes, placement in ((1, "one device"), (S, "one device"),
                                  (S, "mesh of 8"))
         for cut in ("lockstep", "ragged") for scenario in SCENARIOS
         if not (placement == "mesh of 8" and cut == "ragged"
                 and scenario in ("drain", "checkpoint", "flush_stream"))]


@pytest.mark.parametrize("lanes,placement,cut,scenario", CASES)
def test_emissions_equal_the_oracle_and_the_reference(
        corpus, monkeypatch, lanes, placement, cut, scenario):
    all_streams, all_starts, alt, alt_starts = corpus
    streams, starts = all_streams[:lanes], all_starts[:lanes]
    j = lanes - 1
    at_point = None
    if cut == "lockstep":
        # nothing counts as ready: only launches advance the pipeline,
        # so the drain point falls between two steps in flight, lane j
        # riding in both
        monkeypatch.setattr(framebatch, "_ready", lambda arrays: False)

        def at_point(rx, lane):
            assert [st.fronted for st in rx._flight] == [True, False]
            assert all(lane in st.active for st in rx._flight)
            assert rx.stats.max_in_flight == 3
    rx = framebatch.MultiStreamReceiver(lanes, mesh=_mesh(placement),
                                        **GEO)
    before, after = _drive(rx, streams, alt, scenario,
                           cut == "ragged", 7 + lanes, at_point)
    assert not rx.stats.degraded and rx.stats.overflow_chunks == 0
    got_b, got_a = _per_stream(before, lanes), _per_stream(after, lanes)
    whole, second = _oracle(streams, alt)

    # the per-capture oracle: frame for frame, byte for byte, in order
    # (WHICH call hands a frame back may differ, so only the lane that
    # was reset is held to what came before its reset and what after)
    for i in range(lanes):
        want_b, want_a = _expected(whole, second, i, j, scenario)
        if i == j and scenario == "reset_stream":
            pairs = [(got_b[i], want_b), (got_a[i], want_a)]
        else:
            pairs = [(got_b[i] + got_a[i], want_b + want_a)]
        for got, want in pairs:
            assert [f.start for f in got] \
                == [f.start for f in want], (i, scenario)
            for a, b in zip(got, want):
                assert _same_result(a.result, b.result)

    # every frame sent, exactly once and in order
    for i in range(lanes):
        seen = [f.start for f in got_b[i] + got_a[i]]
        if i == j and scenario == "flush_stream":
            assert seen == [s for s in starts[i] if s < P]
        elif i == j and scenario == "reset_stream":
            old = [f.start for f in got_b[i]]
            assert old == [s for s in starts[i] if s < 3 * STRIDE]
            assert [f.start for f in got_a[i]] == alt_starts
        else:
            assert seen == starts[i]

    # the plain numpy reference on every emitted frame's own capture
    for i in range(lanes):
        for src, frames in ((streams[i], got_b[i]),
                            (alt if i == j and scenario == "reset_stream"
                             else streams[i], got_a[i])):
            for fr in frames:
                ref = np_receive(src[fr.start: fr.start + FRAME_LEN])
                assert ref is not None, (i, fr.start)
                assert fr.result.ok and fr.result.crc_ok is True
                assert ref.rate_mbps == fr.result.rate_mbps
                assert ref.length_bytes == fr.result.length_bytes
                assert np.array_equal(
                    ref.psdu,
                    np_bits_to_bytes(np.asarray(fr.result.psdu_bits)))


# ------------------------------------------- `_pending`'s contract (WHEN)


def _owned_by(host_side, lane, start) -> bool:
    offs, active, _arrs, _valid, own_lo, own_hi = host_side
    return lane in active and \
        offs[lane] + own_lo[lane] <= start < offs[lane] + own_hi[lane]


@pytest.fixture(scope="module")
def closed_loop(corpus):
    """The benchmark's closed loop through `ServeRuntime`: a stride a
    session a tick, so every tick after the first launches. After every
    `step()`: what it returned, and `_pending[:6]` as
    `benchmark/harness/cell.py`'s `SampledStep` reads it."""
    streams, _starts, _alt, _as = corpus
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True, shard=False))
    ticks = []
    with telemetry.tracing() as tr, telemetry.collect(srv.registry):
        for i in range(S):
            assert srv.connect(f"s{i}").admitted
        lane_of = {sid: ln for ln, sid in srv._lane_sid.items()}
        pos = 0
        while pos + STRIDE <= min(len(st) for st in streams):
            for i, st in enumerate(streams):
                assert srv.submit(f"s{i}",
                                  st[pos: pos + STRIDE]).accepted
            steps = srv._rx.stats.chunk_steps
            out = srv.step()
            pend = srv._rx._pending
            ticks.append((out, srv._rx.stats.chunk_steps - steps,
                          None if pend is None else tuple(pend[:6]),
                          srv._rx._pending_step))
            pos += STRIDE
        tail = srv._emit(srv._rx.drain_pending())
        drained = (srv._rx._pending, srv._rx._pending_step)
    spans = [e for e in tr.events() if e["ph"] == "X"
             and e["cat"] == "host"]
    return srv, ticks, tail, drained, lane_of, spans


def test_a_launch_hands_back_the_frames_of_the_pending_step(closed_loop):
    srv, ticks, tail, drained, lane_of, _spans = closed_loop
    assert sum(n for _o, n, _p, _s in ticks) >= 5
    named, handed, oldest = None, 0, []
    for out, launched, pend, step in ticks:
        if launched:
            assert launched == 1
            oldest.append(step)
        # the frames of exactly the chunk-step `_pending` named after
        # the call before: SampledStep's rule
        for sid, fr in out:
            assert launched and named is not None
            assert _owned_by(named, lane_of[sid], fr.start)
            handed += 1
        named = pend
    assert handed >= S
    # the oldest step in flight once launch t has returned: t-1
    assert oldest == [max(0, t - 1) for t in range(len(oldest))]
    # the two steps still in flight come back from the drain, and then
    # nothing is pending
    assert len(tail) >= 1 and drained == (None, None)
    assert srv._rx.stats.max_in_flight == 3
    assert srv.registry.find(telemetry.GAUGE_METRIC,
                             site="rx.stream_inflight").last == 3


def test_a_steps_frames_come_out_two_launches_later(closed_loop):
    srv, ticks, _tail, _drained, _lane_of, spans = closed_loop
    stacks = {e["args"]["step"]: e for e in spans
              if e["name"] == "rx.fleet.stack"}
    steps = sorted((e["ts"] for e in spans if e["name"] == "serve.step"))
    assert len(stacks) == srv._rx.stats.chunk_steps

    def tick_of(e):
        return max(n for n, ts in enumerate(steps) if ts <= e["ts"])

    def named(name):
        return sorted((e for e in spans if e["name"] == name),
                      key=lambda e: e["args"]["step"])

    for name, later, left in (("rx.fleet.pull_scan", 1, 1),
                              ("rx.fleet.classify", 1, 1),
                              ("rx.fleet.decode", 1, 1),
                              ("rx.fleet.pull_decode", 2, 2),
                              ("rx.fleet.emit", 2, 2)):
        mine = named(name)
        assert len(mine) >= 3
        for e in mine:
            if e["args"]["step"] < len(stacks) - left:   # not the drain's
                assert tick_of(e) \
                    == tick_of(stacks[e["args"]["step"]]) + later, name


def test_spans_counter_and_gauge_say_how_the_pipeline_ran(closed_loop):
    srv, _ticks, _tail, _drained, _lane_of, spans = closed_loop
    steps = srv._rx.stats.chunk_steps
    for name in ("rx.fleet.pull_scan", "rx.fleet.pull_decode"):
        mine = [e["args"] for e in spans if e["name"] == name]
        assert mine and all(a["reads"] == 1 and a["ready"] in (0, 1)
                            for a in mine)
    puts = sorted((e["args"] for e in spans
                   if e["name"] == "rx.fleet.put"),
                  key=lambda a: a["step"])
    assert [a["in_flight"] for a in puts] \
        == [min(3, n + 1) for n in range(steps)]
    reg = srv.registry
    by_how = {how: reg.find("rx.pipeline_advances", how=how)
              for how in ("launch", "ready", "drain")}
    # two halves a chunk-step; a closed loop runs none of them early,
    # and the final drain ran three (one front half, two back halves)
    assert by_how["ready"] is None
    assert by_how["drain"].value == 3
    assert by_how["launch"].value == 2 * steps - 3


# --------------------------- the names the benchmark's harness reads (D13)

#: the two files of the benchmark that take the fleet's attributes by
#: name; a program PR that renames one breaks every cell and has no
#: other test to tell it (tier-1 never runs `cell.measure`)
HARNESS = ("benchmark/harness/cell.py", "benchmark/control.py")


def _read_by_name(var):
    """Every attribute the harness reads off the variable ``var``
    (`rx`: the receiver, `srv`: the runtime, `_rx`: the module
    `phy/wifi/rx`), from the two files' source; a span's name in a
    string (`"rx.fleet.put"`) is none."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = set()
    for path in HARNESS:
        with open(os.path.join(root, path)) as f:
            names |= set(re.findall(
                r"(?<![\w.\"'`])" + var + r"\.(\w+)", f.read()))
    return names


def test_the_names_the_harness_reads_are_there_at_the_shapes_it_uses(
        corpus):
    """At the smallest twin's geometry (`wifi-a-mtu-8s`'s: this file's),
    one chunk-step launched and none yet handed back."""
    from benchmark.harness import cell
    from ziria_tpu.phy.wifi import rx as _rx

    streams, _starts, _alt, _as = corpus
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True, shard=False))
    rx = srv._rx
    of_rx, of_srv, of_mod = (_read_by_name(v)
                             for v in ("rx", "srv", "_rx"))
    # the expression still finds what the harness is known to read
    assert {"_put", "_jit1", "s", "k", "chunk_len", "frame_len",
            "n_sym_bucket", "stride", "mesh", "axis", "stats", "carry",
            "drain_pending"} <= of_rx
    assert {"_rx", "_lane_sid", "_sessions", "step"} <= of_srv
    assert of_mod == {"_jit_stream_chunk_multi",
                      "_jit_stream_decode_multi"}
    assert [n for n in sorted(of_rx) if not hasattr(rx, n)] == []
    assert [n for n in sorted(of_srv) if not hasattr(srv, n)] == []
    for name in of_mod:
        factory = getattr(_rx, name)
        assert factory.cache_info().currsize >= 0
        assert callable(factory.__wrapped__)
    # the shapes: geometry as integers, the two programs' arguments as
    # `cell.warm` builds them
    assert (rx.s, rx.k, rx.chunk_len, rx.frame_len, rx.stride) \
        == (S, K, CHUNK, FRAME_LEN, STRIDE)
    assert rx.n_sym_bucket == 8 and rx.mesh is None
    assert [tuple(a.shape) for a in cell.chunk_shapes(rx)] \
        == [(S, CHUNK, 2), (S,), (S,), (S,)]
    assert cell.decode_program(rx) is not None
    assert rx._pending is None and rx._pending_step is None
    sampled = cell.SampledStep(srv)
    for i in range(S):
        assert srv.connect(f"s{i}").admitted
        assert srv.submit(f"s{i}", streams[i][:CHUNK + 7]).accepted
    assert sorted(srv._lane_sid) == list(range(S))
    assert srv.step() == [] and sampled.kept is None
    assert rx.stats.chunk_steps == 1 and rx._pending_step == 0
    assert len(rx._pending) == 7
    offs, active, arrs, valid, own_lo, own_hi = rx._pending[:6]
    assert len(offs) == S and sorted(active) == list(range(S))
    assert arrs.shape == (S, CHUNK, 2) and arrs.dtype == np.float32
    assert valid.shape == own_lo.shape == own_hi.shape == (S,)
    assert [rx.carry(i).offset for i in range(S)] == [STRIDE] * S
    # what the open loop's `staged_at_close` sums: the 7 samples a
    # session that the step left staged
    assert [s.staged_samples for s in srv._sessions.values()] == [7] * S
    assert rx.drain_pending()
    assert rx._pending is None
    srv.drain()


# ------------------------------- a push that launches nothing (the ready path)


def _fleet_with_one_step_launched(streams, monkeypatch, ready):
    monkeypatch.setattr(framebatch, "_ready", ready)
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    assert rx.push_many([st[:CHUNK] for st in streams]) == []
    assert [st.fronted for st in rx._flight] == [False]
    return rx


def test_a_push_that_launches_nothing_never_blocks(corpus, monkeypatch):
    streams, _starts, _alt, _as = corpus
    rx = _fleet_with_one_step_launched(streams, monkeypatch,
                                       lambda arrays: False)
    monkeypatch.setattr(framebatch, "_pull_chunk", None)   # would raise
    monkeypatch.setattr(framebatch, "_pull_decode", None)
    with telemetry.collect() as reg:
        for n in range(3):
            assert rx.push(0, streams[0][CHUNK + n: CHUNK + n + 1]) == []
            assert rx.push_many({}) == []
    assert [st.fronted for st in rx._flight] == [False]
    assert reg.find("rx.pipeline_advances", how="ready") is None
    assert rx.stats.chunk_steps == 1


def test_a_push_that_launches_nothing_hands_back_what_is_ready(
        corpus, monkeypatch):
    streams, starts, _alt, _as = corpus
    asked = []

    def ready(arrays):
        # the scan's nine scalars are; the decode's two are not yet
        asked.append(len(arrays))
        return len(arrays) == 9 or decode_done[0]

    decode_done = [False]
    rx = _fleet_with_one_step_launched(streams, monkeypatch, ready)
    with telemetry.collect() as reg:
        # the front half runs (the decode is dispatched), the back does
        # not: the step stays in flight and nothing comes out
        assert rx.push(0, streams[0][CHUNK: CHUNK + 1]) == []
        assert [st.fronted for st in rx._flight] == [True]
        assert rx._flight[0].dec_out is not None
        assert reg.find("rx.pipeline_advances", how="ready").value == 1
        decode_done[0] = True
        out = rx.push(0, streams[0][CHUNK + 1: CHUNK + 2])
    assert rx._pending is None
    assert reg.find("rx.pipeline_advances", how="ready").value == 2
    assert reg.find("rx.pipeline_advances", how="launch") is None
    assert set(asked) == {9, 2}
    # chunk 0 owns the starts below one stride, of every stream
    per = _per_stream(out, S)
    for i in range(S):
        assert [f.start for f in per[i]] \
            == [s for s in starts[i] if s < STRIDE]
    assert sum(len(p) for p in per) >= S


def test_a_runtime_step_with_nothing_staged_hands_back_what_is_ready(
        corpus, monkeypatch):
    streams, starts, _alt, _as = corpus
    monkeypatch.setattr(framebatch, "_ready", lambda arrays: False)
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True, shard=False))
    for i in range(S):
        assert srv.connect(f"s{i}").admitted
        assert srv.submit(f"s{i}", streams[i][:CHUNK]).accepted
    assert srv.step() == [] and srv._rx.stats.chunk_steps == 1
    assert srv.step() == []                 # nothing staged, not ready
    monkeypatch.setattr(framebatch, "_ready", lambda arrays: True)
    out = srv.step()                        # nothing staged, ready
    assert srv._rx._pending is None and srv._rx.stats.chunk_steps == 1
    lane_of = {sid: ln for ln, sid in srv._lane_sid.items()}
    got = sorted((lane_of[sid], fr.start) for sid, fr in out)
    assert got == sorted((i, s) for i in range(S) for s in starts[i]
                         if s < STRIDE)
    srv.drain()


# ------------------------------- what a counter over a window must allow


def _window(streams, closes_on):
    """Two launches of warm-up, then a counted interval of three
    launches that closes straight after the last of them
    (``"launch"``) or after one more `push_many` that launches nothing
    and finds the newest scan done (``"ready"``: blocked on here, where
    a chip's timing would decide). Returns the interval's counts and
    every frame the receiver handed back, the flush's among them."""
    import jax

    n = min(len(st) for st in streams)
    cuts = [0, CHUNK] + list(range(CHUNK + STRIDE, n + 1, STRIDE))
    rounds = [{i: st[a:b] for i, st in enumerate(streams)}
              for a, b in zip(cuts, cuts[1:])]
    assert len(rounds) >= 5
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    out = []
    for push in rounds[:2]:
        out += rx.push_many(push)
    # the interval opens after a launch: its scan is queued, its front
    # half (the decode's dispatch) still to come
    assert [st.fronted for st in rx._flight] == [True, False]
    with dispatch.count_dispatches() as d:
        for push in rounds[2:5]:
            out += rx.push_many(push)
        if closes_on == "ready":
            jax.block_until_ready(
                framebatch._chunk_scalars(rx._flight[-1].outs))
            out += rx.push_many({})
            assert rx._flight[-1].fronted
    assert rx.stats.chunk_steps == 5
    for push in rounds[5:]:
        out += rx.push_many(push)
    out += rx.push_many({i: st[cuts[-1]:]
                         for i, st in enumerate(streams)})
    return dict(d.counts), out + rx.flush()


def test_a_window_that_closes_on_a_ready_step_counts_one_decode_more(
        corpus):
    """What the pipeline does by design (PR 40) and what a counter of
    dispatches over a window of it must allow: a step's decode is
    dispatched a launch after its scan, or by an earlier call that
    launches nothing and finds the scan done, so a window's decodes are
    its chunk-steps plus the steps unfronted when it opened less those
    unfronted when it closed. The benchmark's open loop opens after a
    launch (one unfronted) and closes on whichever call passes its
    time: `dispatches_per_chunk_step` reads (2n + 1) / n against a
    limit of 2.0 where that call launched nothing (ROADMAP B2(i))."""
    streams, starts, _alt, _as = corpus
    on_launch, a = _window(streams, "launch")
    on_ready, b = _window(streams, "ready")
    assert on_launch["rx.stream_chunk_multi"] == 3
    assert on_launch["rx.stream_decode_multi"] == 3
    assert on_ready["rx.stream_chunk_multi"] == 3
    assert on_ready["rx.stream_decode_multi"] == 3 + 1
    # which call handed a frame back differs; what came out does not
    per_a, per_b = _per_stream(a, S), _per_stream(b, S)
    for i in range(S):
        assert [f.start for f in per_a[i]] == starts[i]
        assert [f.start for f in per_b[i]] == starts[i]
        assert all(_same_result(x.result, y.result)
                   for x, y in zip(per_a[i], per_b[i]))


# ------------------------------------ containment across the split (late)


class _Unpullable:
    """A device handle whose async computation failed: the error
    surfaces at the host read, a launch after the dispatch."""
    nbytes = 0

    def __array__(self, *a, **k):
        raise RuntimeError("UNAVAILABLE: link died mid-execution")


def _two_in_flight(streams, monkeypatch):
    monkeypatch.setattr(framebatch, "_ready", lambda arrays: False)
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    out = rx.push_many([st[:CHUNK + STRIDE] for st in streams])
    assert [st.fronted for st in rx._flight] == [True, False]
    assert rx._flight[0].dec_out is not None
    return rx, out


def _check_all_frames(out, streams, starts):
    per = _per_stream(out, S)
    for i in range(S):
        assert [f.start for f in per[i]] == starts[i]
        for fr in per[i]:
            ref = np_receive(streams[i][fr.start: fr.start + FRAME_LEN])
            assert ref is not None and fr.result.crc_ok is True
            assert np.array_equal(
                ref.psdu,
                np_bits_to_bytes(np.asarray(fr.result.psdu_bits)))


def test_a_decode_pull_that_fails_a_launch_late_is_redispatched_once(
        corpus, monkeypatch):
    streams, starts, _alt, _as = corpus
    with telemetry.collect() as reg:
        rx, out = _two_in_flight(streams, monkeypatch)
        dispatched = []
        real = framebatch._dispatch_decode
        monkeypatch.setattr(
            framebatch, "_dispatch_decode",
            lambda r, st: (dispatched.append(st.step), real(r, st))[1])
        # the decode in flight loses its device handles AFTER its
        # dispatch returned: the read, one launch later, finds out
        lost = rx._flight[0]
        lost.dec_out = (_Unpullable(), _Unpullable())
        out += rx.push_many([st[CHUNK + STRIDE:] for st in streams])
        out += rx.flush()
    # from the segs and tables the step kept: once, and only that step
    assert dispatched.count(lost.step) == 1
    assert reg.snapshot()["resilience.async_rescans"] == 1
    assert not rx.stats.degraded and rx.stats.lane_blowups == 0
    _check_all_frames(out, streams, starts)


def test_a_decode_lost_twice_degrades_to_the_oracle_and_loses_no_frame(
        corpus, monkeypatch):
    streams, starts, _alt, _as = corpus
    with telemetry.collect() as reg:
        rx, out = _two_in_flight(streams, monkeypatch)

        real = framebatch._dispatch_decode

        def lost_again(r, st):
            real(r, st)
            if st is lost:
                st.dec_out = (_Unpullable(), _Unpullable())

        lost = rx._flight[0]
        lost.dec_out = (_Unpullable(), _Unpullable())
        monkeypatch.setattr(framebatch, "_dispatch_decode", lost_again)
        out += rx.push_many([st[CHUNK + STRIDE: CHUNK + 2 * STRIDE]
                             for st in streams])
        monkeypatch.undo()
        monkeypatch.setattr(framebatch, "_ready", lambda arrays: False)
        # degraded: that step through the oracle with its own host
        # arrays, and every later step too; the step whose decode was
        # already on the device when the fleet degraded reads it back
        assert rx.stats.degraded
        out += rx.push_many([st[CHUNK + 2 * STRIDE:] for st in streams])
        out += rx.flush()
    assert reg.snapshot()["resilience.async_rescans"] == 1
    assert reg.snapshot()["resilience.degraded"] == 1
    assert rx.stats.lane_blowups == 0
    _check_all_frames(out, streams, starts)


def test_a_scan_lost_behind_a_decode_is_rescanned_from_its_own_arrays(
        corpus, monkeypatch):
    streams, starts, _alt, _as = corpus
    with telemetry.collect() as reg:
        rx, out = _two_in_flight(streams, monkeypatch)
        # the NEWER step's scan and the OLDER step's decode both lost
        rx._flight[1].outs = tuple(_Unpullable() for _ in range(11))
        rx._flight[0].dec_out = (_Unpullable(), _Unpullable())
        out += rx.drain_pending()
        assert rx._pending is None
        out += rx.push_many([st[CHUNK + STRIDE:] for st in streams])
        out += rx.flush()
    assert reg.snapshot()["resilience.async_rescans"] == 2
    assert not rx.stats.degraded
    _check_all_frames(out, streams, starts)


# ------------------------- `_pending`'s contract (WHAT): the staging arrays
#
# ISSUE 42: a chunk-step's stacked host array comes from the receiver's
# store (`framebatch._Staging`) and is written again only once nothing
# but the store holds it. These cases run on the programs the cases
# above compiled, and the oracle they ran.

ARRAY_BYTES = S * CHUNK * 2 * 4


def _assert_same_frames(got, want):
    """Per stream: the same starts in the same order, equal results."""
    for i in range(S):
        assert [f.start for f in got[i]] == [f.start for f in want[i]]
        for a, b in zip(got[i], want[i]):
            assert _same_result(a.result, b.result)


def _stacks(trace):
    return sorted((e["args"] for e in trace.events()
                   if e["ph"] == "X" and e["name"] == "rx.fleet.stack"),
                  key=lambda a: a["step"])


def test_the_store_hands_out_only_what_nobody_holds():
    store = framebatch._Staging(2, 8)
    a, stale, fresh = store.take()
    assert fresh and a.shape == (2, 8, 2) and not a.any() \
        and not stale.any()
    ident = id(a)
    del a, stale
    b, _stale, fresh = store.take()         # let go: handed out again
    assert not fresh and id(b) == ident
    c, _stale, fresh = store.take()         # held: passed over
    assert fresh and c is not b
    lane = b[1]                              # a view holds its base
    del b, c
    d, _stale, fresh = store.take()
    assert not fresh and d.base is None and d is not lane.base
    del d
    # one array more for every reference somebody keeps, and no more
    e, _stale, fresh = store.take()
    assert not fresh and e is not lane.base
    assert store.nbytes == 2 * lane.base.nbytes
    del lane, e
    assert id(store.take()[0]) == ident and len(store._arrays) == 2


@pytest.fixture(scope="module")
def sampled_loop(corpus):
    """The benchmark's closed loop through `ServeRuntime`, a stride a
    session a tick, with `benchmark/harness/cell.py`'s `SampledStep`
    beside it: `_pending[:6]` kept by reference after every `step()`
    (``last``, replaced every call; ``kept``, the newest whose frames
    came back), and the first one held to the end. Every reference is
    paired with the copy taken when it was."""
    streams, _starts, _alt, _as = corpus
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True, shard=False))
    # the corpus twice over: one launch and ten further ones
    streams = [np.concatenate([st, st])[:CHUNK + 10 * STRIDE]
               for st in streams]
    first = last = kept = None
    with telemetry.tracing() as tr, telemetry.collect(srv.registry):
        for i in range(S):
            assert srv.connect(f"s{i}").admitted
        for pos in range(0, len(streams[0]) - STRIDE + 1, STRIDE):
            for i, st in enumerate(streams):
                assert srv.submit(f"s{i}",
                                  st[pos: pos + STRIDE]).accepted
            out = srv.step()
            if out and last is not None:
                kept = last
            pend = srv._rx._pending
            last = None if pend is None else (
                tuple(pend[:6]), pend[2].copy(), srv._rx._pending_step)
            if first is None:
                first = last
        srv._rx.drain_pending()
    return srv, tr, first, kept


def test_an_array_somebody_holds_stays_that_steps_samples(sampled_loop):
    srv, _tr, first, kept = sampled_loop
    assert srv._rx.stats.chunk_steps >= 10
    # the one replaced every call, as `SampledStep.kept` is read after
    # the window: the samples of the step it was taken from
    assert kept is not None and kept[1].any()
    assert np.array_equal(kept[0][2], kept[1])
    # the one held throughout: eight further launches and more, and a
    # `drain_pending`
    assert first[2] == 0 and srv._rx.stats.chunk_steps >= 1 + 8
    assert kept[2] > first[2]
    assert np.array_equal(first[0][2], first[1])
    assert first[0][2] is not kept[0][2]


def test_the_store_settles_and_the_counter_and_gauge_say_so(
        sampled_loop):
    srv, tr, _first, _kept = sampled_loop
    rx = srv._rx
    fresh = [a["fresh"] for a in _stacks(tr)]
    assert len(fresh) == rx.stats.chunk_steps
    # two steps in flight as a third is filled, `last`, `kept`, and the
    # first held to the end: no array is made once each of them has
    # one. (`last` counts since ISSUE 52: a slab that straddles a chunk
    # is written on behind the launch it completes, inside the call,
    # while the caller still holds the step that launch drained.)
    made = sum(fresh)
    assert made <= rx.stats.max_in_flight + 3
    assert fresh == [1] * made + [0] * (len(fresh) - made)
    assert len(rx._staging._arrays) == made
    reg = srv.registry
    assert reg.find("rx.stage_arrays", how="fresh").value == made
    assert reg.find("rx.stage_arrays", how="reused").value \
        == len(fresh) - made
    gauge = reg.find(telemetry.GAUGE_METRIC, site="rx.stage_bytes")
    assert gauge.last == made * ARRAY_BYTES == rx._staging.nbytes


#: lanes that get a stride in each round (every lane holds a chunk
#: less a stride first): lanes idle, then carried, then idle again,
#: over more launches than the store has arrays
ROUNDS = [(0, 1, 2, 3), tuple(range(S)), (4, 5, 6, 7), (0, 2, 4, 6),
          (1, 3, 5, 7), (0, 7), (1, 2, 3, 4, 5, 6)]


def _ragged(rx, streams, checked):
    """Drive ``rx`` through ROUNDS, the rest of every stream and a
    flush of ragged tails. Every array put is compared with the array a
    new `np.zeros` and the streams would have made, from the samples
    this function has pushed (``at``) and the chunk-steps it has seen
    each lane ride (``off``), and the verdict appended (no reference to
    a staging array is kept)."""
    step, launch = rx._step, rx._launch
    want = []
    at, off = [0] * S, [0] * S

    def _step(active, flushing):
        new = np.zeros((S, CHUNK, 2), np.float32)
        for i in active:
            if flushing:
                new[i, :at[i] - off[i]] = streams[i][off[i]: at[i]]
                off[i] = at[i]
            else:
                new[i] = streams[i][off[i]: off[i] + CHUNK]
                off[i] += STRIDE
        want.append(new)
        return step(active, flushing)

    def _launch(arrs, *rest):
        checked.append(np.array_equal(arrs, want.pop()))
        return launch(arrs, *rest)

    rx._step, rx._launch = _step, _launch

    def push(ends):
        slabs = {i: streams[i][at[i]: hi] for i, hi in ends.items()}
        at[:] = [ends.get(i, at[i]) for i in range(S)]
        return rx.push_many(slabs)

    out = push({i: CHUNK - STRIDE for i in range(S)})
    for lanes in ROUNDS:
        out += push({i: at[i] + STRIDE for i in lanes})
    out += push({i: len(st) for i, st in enumerate(streams)})
    return out + rx.flush()


@pytest.mark.parametrize("placement", ["one device", "mesh of 8"])
def test_a_reused_array_reads_as_a_new_one_would(corpus, placement):
    streams, _starts, alt, _as = corpus
    rx = framebatch.MultiStreamReceiver(S, mesh=_mesh(placement), **GEO)
    checked = []
    with telemetry.tracing() as tr:
        got = _per_stream(_ragged(rx, streams, checked), S)
    assert len(checked) == rx.stats.chunk_steps > len(ROUNDS)
    assert all(checked)
    # nobody holds an array: the steps in flight have theirs, and
    # every later one is used again, idle lanes and short tails and all
    fresh = [a["fresh"] for a in _stacks(tr)]
    made = sum(fresh)
    assert made <= rx.stats.max_in_flight + 1 < len(fresh)
    assert fresh == [1] * made + [0] * (len(fresh) - made)
    whole, _second = _oracle(streams, alt)
    _assert_same_frames(got, whole)


def test_a_scan_lost_a_launch_ago_is_rescanned_from_intact_samples(
        corpus, monkeypatch):
    streams, _starts, alt, _as = corpus
    monkeypatch.setattr(framebatch, "_ready", lambda arrays: False)
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    rescanned = []
    rescan = rx._rescan

    def _rescan(st):
        rescanned.append((st.step, np.array_equal(st.arrs, sent)))
        return rescan(st)

    rx._rescan = _rescan
    with telemetry.collect() as reg, telemetry.tracing() as tr:
        out = rx.push_many([st[:CHUNK] for st in streams])
        # one reference held for the whole run
        held = rx._pending[2]
        held_copy = held.copy()
        # enough launches that every array of the store has been used
        out += rx.push_many([st[CHUNK: CHUNK + 4 * STRIDE]
                             for st in streams])
        lost = rx._flight[-1]
        assert lost.step == 4 and not lost.fronted
        sent = lost.arrs.copy()
        lost.outs = tuple(_Unpullable() for _ in range(11))
        del lost
        # the loss is found a launch later, after that launch stacked
        # and put its own samples
        out += rx.push_many([st[CHUNK + 4 * STRIDE:] for st in streams])
        out += rx.flush()
    assert rescanned == [(4, True)]
    assert reg.snapshot()["resilience.async_rescans"] == 1
    assert not rx.stats.degraded
    # two in flight as a third is filled, and the one held: one more
    # than a run in which nobody holds one. The streams end raggedly,
    # and a step that some lane waits through takes a second array
    # before its launch (ISSUE 52): one more at most, at the end
    fresh = [a["fresh"] for a in _stacks(tr)]
    assert fresh[:6] == [1] * 4 + [0] * 2 and len(fresh) > 6
    assert sum(fresh) == len(rx._staging._arrays) <= 5
    assert np.array_equal(held, held_copy)
    got = _per_stream(out, S)
    whole, _second = _oracle(streams, alt)
    _assert_same_frames(got, whole)


def test_a_step_that_leaves_by_an_exception_gives_its_array_back(
        corpus, monkeypatch):
    streams, _starts, _alt, _as = corpus
    # of one length: every step carries all the lanes or none (a step
    # some lane waits through takes a second array: ISSUE 52)
    n = 2 * min(len(st) for st in streams)
    streams = [np.concatenate([st, st])[:n] for st in streams]
    monkeypatch.setattr(framebatch, "_ready", lambda arrays: False)
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    real = rx._scan_to_decode

    def _scan_to_decode(st):
        if st.step == 3:
            raise RuntimeError("the front half of step 3")
        return real(st)

    rx._scan_to_decode = _scan_to_decode
    with telemetry.tracing() as tr:
        rx.push_many([st[:CHUNK + 3 * STRIDE] for st in streams])
        assert [st.step for st in rx._flight] == [2, 3]
        try:
            rx.push_many([st[CHUNK + 3 * STRIDE: CHUNK + 4 * STRIDE]
                          for st in streams])
        except RuntimeError as exc:
            assert "step 3" in str(exc)
        else:
            raise AssertionError("the front half did not raise")
        # `_front` took step 3 out of flight; nobody released anything
        assert [st.step for st in rx._flight] == [2, 4]
        rx.push_many([st[CHUNK + 4 * STRIDE:] for st in streams])
        rx.flush()
    # step 3 left before its scan was read: while that scan still runs
    # the runtime holds the array it reads (on this backend the device
    # array may be the host's memory), so the launch behind the raise
    # may find it held and make one; by the launch after it is back.
    # Step 4 was whole and queued when the raise came: its lanes had
    # moved on, and nothing launches it a second time (ISSUE 52)
    fresh = [a["fresh"] for a in _stacks(tr)]
    assert fresh[:5] == [1, 1, 1, 0, 0] and len(fresh) > 6
    assert not any(fresh[6:])
    assert len(fresh) == rx.stats.chunk_steps \
        == (n - CHUNK) // STRIDE + 2
    assert len(rx._staging._arrays) == sum(fresh) <= 4


# ------------------------------------ a sample is written once (ISSUE 52)
#
# A lane's pending samples live in the staging array of the step they
# will ride: a slab is written there as it is pushed, and the
# `frame_len` overlap is copied forward once, from the array of the
# launch before. `written` on `rx.fleet.ingest`, `carried` and `moved`
# on `rx.fleet.stack` and the counter `rx.stage_samples{how}` say how
# often each happens. These cases run on the programs compiled above.


def _launched(rx):
    """Copies of ``(arrs, active)`` of every launch of ``rx`` from here
    on, oldest first."""
    seen, launch = [], rx._launch

    def _launch(arrs, valid, own_lo, own_hi, active, offs):
        seen.append((arrs.copy(), list(active)))
        return launch(arrs, valid, own_lo, own_hi, active, offs)

    rx._launch = _launch
    return seen


def _tail_is_the_streams(rx, streams, pushed):
    for i, st in enumerate(streams):
        c = rx.carry(i)
        assert np.array_equal(c.tail, st[c.offset: pushed[i]]), i


def test_a_closed_loop_writes_a_sample_once_and_carries_the_overlap_once(
        closed_loop):
    srv, _ticks, _tail, _drained, _lane_of, spans = closed_loop
    steps = srv._rx.stats.chunk_steps
    written = {}
    for e in spans:
        if e["name"] == "rx.fleet.ingest":
            step = e["args"]["step"]
            written[step] = written.get(step, 0) + e["args"]["written"]
    stacks = {e["args"]["step"]: e["args"] for e in spans
              if e["name"] == "rx.fleet.stack"}
    assert sorted(stacks) == list(range(steps)) and steps >= 5
    for step, a in stacks.items():
        assert a["active"] == S and a["moved"] == 0
        assert a["carried"] == (S * FRAME_LEN if step else 0)
        assert written[step] + a["carried"] == S * CHUNK
    reg = srv.registry
    assert reg.find("rx.stage_samples", how="written").value \
        == sum(written.values())
    assert reg.find("rx.stage_samples", how="carried").value \
        == (steps - 1) * S * FRAME_LEN
    assert reg.find("rx.stage_samples", how="moved").value == 0
    # twice a sample's share of a chunk, where the tails cost four times
    assert sum(written[s] for s in range(1, steps)) \
        == (steps - 1) * S * STRIDE


#: what each lane but the first holds when the first fills its chunk:
#: little (they move on to the next array) or much (lane 0 is copied out)
SPARSE = {"the waiting lanes move on": 100,
          "the riding lane is copied out": 3000}


@pytest.mark.parametrize("regime", list(SPARSE))
def test_a_sparse_step_carries_zeros_and_loses_no_sample(corpus, regime):
    streams, _starts, alt, _as = corpus
    part = SPARSE[regime]
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    seen = _launched(rx)
    pushed = [CHUNK] + [part] * (S - 1)
    with telemetry.tracing() as tr:
        out = rx.push_many([st[:n] for st, n in zip(streams, pushed)])
    arrs, active = seen[0]
    assert active == [0] and len(seen) == 1
    assert np.array_equal(arrs[0], streams[0][:CHUNK])
    assert not arrs[1:].any()
    (stack,) = _stacks(tr)
    # the rule: whichever moves fewer samples, by the levels held
    assert stack["moved"] == min((S - 1) * part, CHUNK)
    assert stack["active"] == 1 and stack["carried"] == 0
    _tail_is_the_streams(rx, streams, pushed)
    # a second sparse step, lane 0's overlap still owed; then the rest
    out += rx.push_many({0: streams[0][CHUNK: CHUNK + STRIDE]})
    pushed[0] += STRIDE
    assert seen[1][1] == [0] and not seen[1][0][1:].any()
    assert np.array_equal(seen[1][0][0], streams[0][STRIDE: STRIDE + CHUNK])
    _tail_is_the_streams(rx, streams, pushed)
    out += rx.push_many([st[n:] for st, n in zip(streams, pushed)])
    out += rx.flush()
    whole, _second = _oracle(streams, alt)
    _assert_same_frames(_per_stream(out, S), whole)


def test_a_slab_of_many_chunks_is_consumed_inside_the_call(corpus):
    streams, _starts, alt, _as = corpus
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    slabs = [st.copy() for st in streams]
    assert min(len(st) for st in slabs) > CHUNK + 3 * STRIDE
    out = rx.push_many(slabs)
    assert rx.stats.chunk_steps >= 4 and rx._rest == {}
    # the caller's buffers are its own again: nothing pending reads them
    for st in slabs:
        st[:] = np.nan
    _tail_is_the_streams(rx, streams, [len(st) for st in streams])
    out += rx.flush()
    whole, _second = _oracle(streams, alt)
    _assert_same_frames(_per_stream(out, S), whole)


def test_a_slab_the_gate_refuses_writes_nothing(corpus):
    streams, _starts, alt, _as = corpus
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    cut = CHUNK + 1500
    out = rx.push_many([st[:cut] for st in streams])
    was = (rx._fill.copy(), list(rx._level), list(rx._offsets),
           list(rx._owed))
    bad = streams[3][cut: cut + 2 * CHUNK].copy()
    bad[-1, 0] = np.inf
    with pytest.raises(ValueError, match="stream 3.*non-finite"):
        rx.push(3, bad)
    with pytest.raises(ValueError, match="stream 3.*shape"):
        rx.push(3, np.zeros((4, 3), np.float32))
    assert np.array_equal(rx._fill, was[0]) and rx._rest == {}
    assert (rx._level, rx._offsets, rx._owed) == was[1:]
    out += rx.push_many([st[cut:] for st in streams])
    out += rx.flush()
    whole, _second = _oracle(streams, alt)
    _assert_same_frames(_per_stream(out, S), whole)


def test_a_lane_restored_mid_chunk_goes_on_as_the_unbroken_one(corpus):
    streams, _starts, alt, _as = corpus
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    cut = P + 1500                     # an owed overlap and 1500 written
    j = 2
    out = rx.push_many([st[:cut] for st in streams])
    assert rx._owed[j] and rx._level[j] == FRAME_LEN + 1500
    blob, got = rx.checkpoint(j)
    out += got
    tail = rx.carry(j).tail
    assert np.array_equal(tail, streams[j][3 * STRIDE: cut])
    out += rx.restore_stream(j, blob)
    # written as a slab is: no overlap is owed, the level is the tail's
    assert not rx._owed[j] and rx._level[j] == len(tail)
    assert np.array_equal(rx.carry(j).tail, tail)
    # and a lone receiver takes the same blob up where the lane stood
    lone = framebatch.StreamReceiver(checkpoint=blob, **GEO)
    alone = lone.push(streams[j][cut:]) + lone.flush()
    out += rx.push_many([st[cut:] for st in streams])
    out += rx.flush()
    whole, _second = _oracle(streams, alt)
    got = _per_stream(out, S)
    _assert_same_frames(got, whole)
    rest = [f for f in whole[j] if f.start >= 3 * STRIDE]
    assert [f.start for f in alone] == [f.start for f in rest]
    assert all(_same_result(a.result, b.result)
               for a, b in zip(alone, rest))


def test_flushing_one_lane_leaves_the_part_full_others_intact(corpus):
    streams, _starts, alt, _as = corpus
    rx = framebatch.MultiStreamReceiver(S, **GEO)
    seen = _launched(rx)
    cut = P + 1500
    j = S - 1
    out = rx.push_many([st[:cut] for st in streams])
    n = len(seen)
    out += rx.flush_stream(j)
    arrs, active = seen[n]
    assert active == [j] and len(seen) == n + 1
    assert np.array_equal(arrs[j, :cut - 3 * STRIDE],
                          streams[j][3 * STRIDE: cut])
    assert not arrs[j, cut - 3 * STRIDE:].any() and not arrs[:j].any()
    assert rx._level[j] == 0 and rx.carry(j).offset == cut
    _tail_is_the_streams(rx, streams[:j], [cut] * j)
    out += rx.push_many({i: streams[i][cut:] for i in range(j)})
    out += rx.flush()
    whole, _second = _oracle(streams, alt)
    got = _per_stream(out, S)
    for i in range(j):
        assert [f.start for f in got[i]] == [f.start for f in whole[i]]
        assert all(_same_result(a.result, b.result)
                   for a, b in zip(got[i], whole[i]))
    # lane j: every frame that ended before its stream was cut
    kept = [f for f in whole[j] if f.start + FRAME_LEN <= cut]
    assert kept and [f.start for f in got[j]][:len(kept)] \
        == [f.start for f in kept]


def test_the_receiver_keeps_no_array_a_lane_between_calls(corpus):
    streams, _starts, _alt, _as = corpus
    rx = framebatch.MultiStreamReceiver(S, **GEO)

    def a_lane_arrays():
        found = []
        for name, val in vars(rx).items():
            items = val.values() if isinstance(val, dict) else \
                val if isinstance(val, (list, tuple)) else ()
            found += [name for x in items if isinstance(x, np.ndarray)
                      and x.ndim == 2 and x.shape[1] == 2]
        return found

    pushed = [0] * S
    rng = np.random.default_rng(52)
    for _ in range(12):
        ends = {int(i): pushed[i] + int(rng.integers(1, 2 * CHUNK))
                for i in rng.choice(S, 5, replace=False)}
        rx.push_many({i: streams[i][pushed[i]: hi]
                      for i, hi in ends.items()})
        for i, hi in ends.items():
            pushed[i] = min(hi, len(streams[i]))
        assert not hasattr(rx, "_tails") and a_lane_arrays() == []
        assert rx._rest == {}
        _tail_is_the_streams(rx, streams, pushed)
    assert rx.stats.chunk_steps >= 3
    # what the lanes hold is in ONE array of the store, or still in
    # the array launched last
    assert sum(rx._level) == sum(rx.carry(i).tail.shape[0]
                                 for i in range(S))
    for held in (rx._fill, rx._prev):
        assert held is None or any(held is a
                                   for a in rx._staging._arrays)
    rx.flush()
