"""Fault-tolerant streaming runtime (utils/faults + runtime/resilience
+ the guarded framebatch/link surfaces; docs/robustness.md):

- the chaos layer: deterministic replay by (site, seed, call-index),
  scoped activation, spec validation, the ``--chaos`` grammar, and the
  pinned free-when-idle seam overhead (the PR 7 discipline extended to
  the fault seams);
- guarded dispatch: transient retry with deterministic-jitter backoff,
  fatal/exhausted classification, the watchdog cutting a hung
  dispatch, and fallback wiring;
- push-seam input validation: malformed/non-finite slabs rejected
  with the stream NAMED, ``sanitize=True`` zero-and-quarantine, fleet
  ``push_many`` dict form with a named unknown-id error;
- lane quarantine: a poisoned fleet stream rides behind the
  valid-mask, healthy lanes stay LANE-FOR-LANE BIT-IDENTICAL to an
  unquarantined run, and the stream rejoins after N clean chunks;
- chaos matrix over the compiled streaming programs: a transient
  fault inside the chunk scan retries to identical frames; a fatal
  decode fault degrades to the per-capture oracle (bit-identical by
  the pinned contract) with the degraded gauge recorded; a fatal scan
  fault degrades to the eager twin; an injected hang is cut by the
  watchdog and retried;
- the fused-link and sweep surfaces under injection (transient →
  identical result, fatal → staged-oracle / loop degrade, never a
  silent wrong answer);
- carry checkpoint/restore: a receiver restarted from a checkpoint
  emits bit-identical subsequent frames vs an uninterrupted run.

Budget discipline: the streaming tests ride the suite-shared
geometry (chunk 4096 / window 1024 / K=8 / 12-byte+FCS PSDUs — the
test_rx_stream keys) and the fused-link/sweep tests reuse
test_link_fused's exact LENS/MBPS/sweep geometry, so in one tier-1
process every compiled program here is a jit-cache hit.
"""

import time

import numpy as np
import pytest

from ziria_tpu.backend import framebatch
from ziria_tpu.phy import link
from ziria_tpu.runtime import resilience
from ziria_tpu.utils import dispatch, faults, telemetry

N_BYTES = 12
CHUNK, FRAME_LEN, K = 4096, 1024, 8
GEO = dict(chunk_len=CHUNK, frame_len=FRAME_LEN,
           max_frames_per_chunk=K, check_fcs=True)

# test_link_fused's exact fused-graph geometry: shared compile class
LENS = (16, 10, 16, 5, 16, 12, 9, 16)
MBPS_ALL = (6, 9, 12, 18, 24, 36, 48, 54)
CFO = tuple((-1) ** k * 1e-4 * (k + 1) for k in range(8))
DELAY = tuple(20 + 17 * k for k in range(8))
SNRS = (25.0, 30.0, -25.0, 28.0, 25.0, 30.0, 27.0, 26.0)


def _same_result(a, b) -> bool:
    return (a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)


def _same_frames(got, want) -> None:
    assert [f.start for f in got] == [f.start for f in want]
    for a, b in zip(got, want):
        assert _same_result(a.result, b.result)


@pytest.fixture(scope="module")
def corpus():
    """One mixed-rate single stream + its clean streaming run, and an
    S=4 fleet + its clean run — every chaos test replays against
    these references at the suite-shared geometry."""
    rng = np.random.default_rng(20260804)
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in range(4)]
    stream, starts = link.stream_many(
        psdus, [6, 54, 24, 54], snr_db=30.0, cfo=1e-4, delay=80,
        seed=21, add_fcs=True, tail=FRAME_LEN)
    frames_c, stats_c = framebatch.receive_stream(stream, **GEO)
    assert [f.start for f in frames_c] == list(starts)
    assert all(f.result.ok and f.result.crc_ok for f in frames_c)

    s_psdus = [[rng.integers(0, 256, N_BYTES).astype(np.uint8)
                for _ in range(2)] for _ in range(4)]
    s_rates = [[6, 54], [12, 24], [36, 48], [9, 18]]
    # stream 0's second frame sits ~3 chunks downstream (gap 9000):
    # the quarantine test needs frames BOTH before poisoning and
    # after the rejoin point, several chunk-steps apart
    streams, fstarts = link.stream_many_multi(
        s_psdus, s_rates, snr_db=30.0, cfo=1e-4, delay=60, seed=33,
        add_fcs=True, tail=FRAME_LEN,
        gaps=[[9000], None, None, None])
    res_c, st_c = framebatch.receive_streams(streams, **GEO)
    for i in range(4):
        assert [f.start for f in res_c[i]] == list(fstarts[i])
    return stream, starts, frames_c, streams, fstarts, res_c


# ------------------------------------------------------------ chaos layer


def test_fault_plan_deterministic_replay():
    specs = (faults.FaultSpec("rx.stream_chunk_multi", "transient",
                              every=3),
             faults.FaultSpec("rx.push.s*", "nan_slab", calls=(1,)))

    def run():
        fired, slabs = [], []
        with faults.inject(*specs, seed=7) as plan:
            for i in range(9):
                try:
                    faults.maybe_fail("rx.stream_chunk_multi")
                except faults.InjectedTransientError:
                    fired.append(i)
            a = np.ones((16, 2), np.float32)
            for _ in range(3):
                slab, _k = faults.corrupt_slab("rx.push.s0", a)
                slabs.append(slab)
        return fired, slabs, list(plan.fired)

    f1, s1, log1 = run()
    f2, s2, log2 = run()
    assert f1 == f2 == [2, 5, 8]
    assert log1 == log2
    # the nan_slab fired on call 1 only, same rows both replays
    assert not np.isnan(s1[0]).any() and not np.isnan(s1[2]).any()
    assert np.isnan(s1[1]).any()
    assert np.array_equal(np.isnan(s1[1]), np.isnan(s2[1]))
    # inactive outside the scope
    assert not faults.active()
    faults.maybe_fail("rx.stream_chunk_multi")  # no-op, no raise


def test_fault_spec_validation_and_truncate():
    with pytest.raises(ValueError):
        faults.FaultPlan((faults.FaultSpec("x", "explode", every=1),))
    with pytest.raises(ValueError):      # zero selectors
        faults.FaultPlan((faults.FaultSpec("x", "transient"),))
    with pytest.raises(ValueError):      # two selectors
        faults.FaultPlan((faults.FaultSpec("x", "transient", every=2,
                                           p=0.5),))
    a = np.ones((16, 2), np.float32)
    with faults.inject(faults.FaultSpec("rx.push*", "truncate",
                                        every=1, fraction=0.25)):
        t, kinds = faults.corrupt_slab("rx.push.s3", a)
    assert t.shape[0] == 12 and kinds == ("truncate",)
    # count= bounds total firings
    with faults.inject(faults.FaultSpec("s", "transient", every=1,
                                        count=1)) as plan:
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fail("s")
        faults.maybe_fail("s")           # budget spent: no raise
    assert plan.total_fired == 1


def test_parse_chaos_spec_and_env(monkeypatch):
    specs, seed = faults.parse_chaos_spec(
        "seed=3;rx.stream_chunk_multi:transient:every=7;"
        "rx.push.s*:nan_slab:calls=1+4,frac=0.5")
    assert seed == 3
    assert specs[0] == faults.FaultSpec("rx.stream_chunk_multi",
                                        "transient", every=7)
    assert specs[1].calls == (1, 4) and specs[1].fraction == 0.5
    # a bare spec fires every call
    (sp,), _ = faults.parse_chaos_spec("link.fused:fatal")
    assert sp.every == 1
    with pytest.raises(ValueError):
        faults.parse_chaos_spec("justasite")
    with pytest.raises(ValueError):
        faults.parse_chaos_spec("s:transient:bogus=1")
    monkeypatch.delenv("ZIRIA_CHAOS", raising=False)
    assert faults.env_chaos() is None
    monkeypatch.setenv("ZIRIA_CHAOS", "s:transient:every=2")
    specs, seed = faults.env_chaos()
    assert specs[0].every == 2 and seed == 0


# -------------------------------------------------------- guarded dispatch


def test_guarded_retries_transient_then_recovers():
    calls = []
    slept = []

    def fn(x):
        calls.append(x)
        return x * 2

    pol = resilience.FaultPolicy(max_retries=2, backoff_base_s=1e-4)
    with telemetry.collect() as reg:
        with faults.inject(faults.FaultSpec("site", "transient",
                                            calls=(0, 1))):
            out = resilience.guarded("site", fn, 21, policy=pol,
                                     _sleep=slept.append)
    assert out == 42 and calls == [21]
    assert len(slept) == 2
    # deterministic-jitter backoff: exact replay values, exponential
    assert slept[0] == resilience.backoff_delay("site", 0, pol)
    assert slept[1] == resilience.backoff_delay("site", 1, pol)
    assert 0.5 * 1e-4 <= slept[0] <= 1e-4 < slept[1]
    # telemetry: retries counted, backoff histogram fed, recovery noted
    snap = reg.snapshot()
    assert snap["resilience.retries"] == 2
    assert snap["resilience.recovered"] == 1
    assert snap["resilience.backoff_seconds"]["count"] == 2


def test_guarded_fatal_and_exhaustion():
    def fn():
        return "fine"

    # fatal: no retries, fallback taken immediately
    with faults.inject(faults.FaultSpec("s2", "fatal", every=1)):
        out = resilience.guarded("s2", fn, fallback=lambda: "twin",
                                 _sleep=lambda s: None)
    assert out == "twin"
    # exhausted transients raise DispatchFailed with the cause chained
    with faults.inject(faults.FaultSpec("s3", "transient", every=1)):
        with pytest.raises(resilience.DispatchFailed) as ei:
            resilience.guarded(
                "s3", fn,
                policy=resilience.FaultPolicy(max_retries=1,
                                              backoff_base_s=1e-5),
                _sleep=lambda s: None)
    assert ei.value.attempts == 2 and ei.value.kind == "transient"
    assert isinstance(ei.value.last, faults.InjectedTransientError)
    # every guarded attempt is a timed dispatch at the site
    with dispatch.count_dispatches() as d:
        with faults.inject(faults.FaultSpec("s4", "transient",
                                            calls=(0,))):
            resilience.guarded("s4", fn, _sleep=lambda s: None)
    assert d.counts["s4"] == 2


def test_guarded_watchdog_cuts_hang_and_retries():
    t0 = time.perf_counter()
    with faults.inject(faults.FaultSpec("hang", "hang", calls=(0,),
                                        delay_s=5.0)):
        out = resilience.guarded(
            "hang", lambda: "ok",
            policy=resilience.FaultPolicy(max_retries=1,
                                          backoff_base_s=1e-4,
                                          timeout_s=0.1),
            _sleep=lambda s: None)
    assert out == "ok"
    assert time.perf_counter() - t0 < 3.0       # the 5s hang was cut


def test_classify_error():
    assert resilience.classify_error(ValueError("nope")) == "fatal"
    assert resilience.classify_error(
        RuntimeError("UNAVAILABLE: link flap")) == "transient"
    assert resilience.classify_error(
        RuntimeError("RESOURCE_EXHAUSTED: hbm")) == "transient"
    assert resilience.classify_error(
        RuntimeError("INVALID_ARGUMENT: shape")) == "fatal"
    assert resilience.classify_error(
        resilience.DispatchTimeout("t")) == "transient"
    assert resilience.classify_error(
        faults.InjectedFatalError("INVALID_ARGUMENT: x")) == "fatal"


def test_env_max_retries(monkeypatch):
    monkeypatch.delenv("ZIRIA_MAX_RETRIES", raising=False)
    assert resilience.env_max_retries() is None
    assert resilience.default_policy().max_retries == 2
    monkeypatch.setenv("ZIRIA_MAX_RETRIES", "5")
    assert resilience.default_policy().max_retries == 5
    assert resilience.default_policy(max_retries=1).max_retries == 1
    with pytest.raises(ValueError):
        resilience.default_policy(max_retries=-1)


def test_disabled_path_overhead_pinned():
    """The PR 7 discipline extended to the fault seams: with no plan
    active, every seam is one truthiness check (< 5µs/call, generous
    CI bound ~20x measured)."""
    assert not faults.active()
    n = 20000
    arr = np.ones((4, 2), np.float32)
    t0 = time.perf_counter()
    for _ in range(n):
        faults.maybe_fail("rx.stream_chunk_multi")
    t_fail = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        faults.corrupt_slab("rx.push", arr)
    t_slab = time.perf_counter() - t0
    assert t_fail / n < 5e-6, f"maybe_fail disabled: {t_fail/n:.2e}s"
    assert t_slab / n < 5e-6, f"corrupt_slab disabled: {t_slab/n:.2e}s"


# ------------------------------------------------- push-seam validation


def test_push_rejects_malformed_and_nonfinite():
    sr = framebatch.StreamReceiver(**GEO)
    with pytest.raises(ValueError, match="stream.*shape"):
        sr.push(np.zeros((8, 3), np.float32))
    with pytest.raises(ValueError, match="not float-convertible"):
        sr.push(["not", "samples"])
    bad = np.zeros((8, 2), np.float32)
    bad[3, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        sr.push(bad)
    # empty and 0-row slabs stay fine
    assert sr.push(np.zeros((0, 2), np.float32)) == []
    assert sr.push([]) == []


def test_push_many_dict_and_unknown_stream_id():
    msr = framebatch.MultiStreamReceiver(2, **GEO)
    with pytest.raises(KeyError, match="unknown stream id 7"):
        msr.push_many({7: np.zeros((4, 2), np.float32)})
    with pytest.raises(ValueError):
        msr.push_many([np.zeros((4, 2), np.float32)])   # wrong count
    bad = np.zeros((4, 2), np.float32)
    bad[0, 1] = np.inf
    with pytest.raises(ValueError, match="stream 1.*non-finite"):
        msr.push_many({1: bad})
    assert msr.push_many({0: np.zeros((4, 2), np.float32)}) == []


def test_sanitize_counts_and_quarantines():
    sr = framebatch.StreamReceiver(sanitize=True, **GEO)
    bad = np.zeros((16, 2), np.float32)
    bad[2] = np.nan
    bad[5, 0] = np.inf
    sr.push(bad)
    assert sr.stats.sanitized == 2 and sr.stats.quarantines == 1
    assert sr.fleet.quarantined(0)


# ----------------------------------------------------- lane quarantine


def test_quarantine_keeps_healthy_lanes_bit_identical(corpus):
    """THE containment contract: one stream's slab NaN-poisoned
    mid-feed (sanitize=True) → that stream quarantines behind the
    valid-mask and rejoins after N clean chunks, healthy lanes stay
    lane-for-lane bit-identical to the clean fleet run, zero crashes,
    and every frame the poisoned lane does emit matches the clean run
    (dropped-while-quarantined, never garbage)."""
    _stream, _starts, _fc, streams, fstarts, res_c = corpus
    spec = faults.FaultSpec("rx.push.s0", "nan_slab", calls=(1,),
                            fraction=0.2)
    with telemetry.collect() as reg:
        with dispatch.count_dispatches() as d:
            with faults.inject(spec, seed=5) as plan:
                msr = framebatch.MultiStreamReceiver(
                    4, sanitize=True, rejoin_after=2, **GEO)
                got = []
                step = 1500
                hi = max(s.shape[0] for s in streams)
                for a in range(0, hi, step):
                    got += msr.push_many(
                        [s[a: a + step] for s in streams])
                got += msr.flush()
    assert plan.total_fired == 1
    per = [[] for _ in range(4)]
    for i, fr in got:
        per[i].append(fr)
    # healthy lanes: bit-identical to the clean fleet run
    for i in (1, 2, 3):
        _same_frames(per[i], res_c[i])
    # the poisoned lane: a strict subset of its clean frames — the
    # frame in the quarantined window dropped, each surviving frame
    # bit-identical (zero garbage emissions)
    clean_by_start = {f.start: f for f in res_c[0]}
    for f in per[0]:
        assert f.start in clean_by_start
        assert _same_result(f.result, clean_by_start[f.start].result)
    assert len(per[0]) < len(res_c[0])
    # ... and the stream REJOINED: its post-rejoin frame (3 chunks
    # past the poisoned slab) decoded normally
    assert per[0] and per[0][-1].start == res_c[0][-1].start
    st = msr.stats
    assert st.sanitized > 0 and st.quarantines == 1
    assert st.quarantined_streams == 0      # rejoined by stream end
    assert not msr.quarantined(0)
    assert not st.degraded
    # the fleet budget held: <= 2 dispatches per chunk-step under chaos
    assert d.total <= 2 * st.chunk_steps, dict(d.counts)
    # observability: quarantine gauge + sanitized counter visible
    assert d.gauges["rx.quarantined_streams"] >= 1
    snap = reg.snapshot()
    assert snap["resilience.sanitized"] == st.sanitized
    assert snap["resilience.quarantines"] == 1


def test_quarantine_rejoin_after_clean_chunks():
    h = framebatch._LaneHealth(blowup_limit=2, rejoin_after=2)
    assert not h.step(dirty=False)
    h.poison()
    assert h.quarantined and h.quarantines == 1
    assert h.step(dirty=False)       # clean 1/2, still quarantined
    assert h.step(dirty=False)       # clean 2/2: rejoin AFTER this
    assert not h.quarantined
    assert not h.step(dirty=False)
    # repeated blowups quarantine too
    h.blowup()
    assert not h.quarantined
    h.blowup()
    assert h.quarantined and h.quarantines == 2
    # a dirty chunk resets the clean streak
    assert h.step(dirty=True) and h.clean == 0
    # blowups accumulate ACROSS chunks (a chunk's blowups are
    # delivered one drain after its step — the double buffer — so a
    # per-step reset could never see two in a row)
    h2 = framebatch._LaneHealth(blowup_limit=2, rejoin_after=2)
    h2.blowup()
    assert not h2.step(dirty=False) and not h2.quarantined
    h2.blowup()
    assert h2.quarantined


# --------------------------------------------- chaos over compiled paths


def test_transient_scan_fault_retries_to_identical_frames(corpus):
    stream, starts, frames_c, *_ = corpus
    spec = faults.FaultSpec("rx.stream_chunk_multi", "transient",
                            every=2)
    with telemetry.collect() as reg:
        with faults.inject(spec) as plan:
            frames, stats = framebatch.receive_stream(stream, **GEO)
    assert plan.total_fired >= 1
    _same_frames(frames, frames_c)
    assert not stats.degraded
    snap = reg.snapshot()
    assert snap["resilience.retries"] == plan.total_fired
    assert snap["resilience.recovered"] == plan.total_fired


def test_fatal_decode_fault_degrades_to_oracle_identical(corpus):
    stream, starts, frames_c, *_ = corpus
    spec = faults.FaultSpec("rx.stream_decode_multi", "fatal",
                            every=1)
    with telemetry.collect() as reg:
        with dispatch.count_dispatches() as d:
            with faults.inject(spec) as plan:
                frames, stats = framebatch.receive_stream(stream,
                                                          **GEO)
    assert plan.total_fired >= 1
    # the oracle twin is bit-identical by the pinned contract: a
    # degraded fleet NEVER silently diverges
    _same_frames(frames, frames_c)
    assert stats.degraded
    assert d.gauges["rx.degraded_mode"] == 1.0
    snap = reg.snapshot()
    assert snap["resilience.degraded"] == 1
    assert snap["resilience.fatal"] >= 1


def test_fatal_scan_fault_degrades_to_eager_identical(corpus):
    stream, starts, frames_c, *_ = corpus
    spec = faults.FaultSpec("rx.stream_chunk_multi", "fatal",
                            calls=(1,))
    with dispatch.count_dispatches() as d:
        with faults.inject(spec) as plan:
            frames, stats = framebatch.receive_stream(stream, **GEO)
    assert plan.total_fired == 1
    _same_frames(frames, frames_c)
    assert stats.degraded
    # the eager twin is its own instrumented site
    assert d.counts["rx.stream_chunk_multi.eager"] >= 1


def test_injected_hang_cut_by_watchdog_identical(corpus):
    stream, starts, frames_c, *_ = corpus
    spec = faults.FaultSpec("rx.stream_chunk_multi", "hang", calls=(1,),
                            delay_s=5.0)
    t0 = time.perf_counter()
    with faults.inject(spec):
        sr = framebatch.StreamReceiver(watchdog_s=1.0, **GEO)
        frames = sr.push(stream)
        frames += sr.flush()
    assert time.perf_counter() - t0 < 20.0
    _same_frames(frames, frames_c)
    assert not sr.stats.degraded


class _Unpullable:
    """A device-handle stand-in whose host pull raises the way a LOST
    async dispatch does: guarded() already returned, the failure
    surfaces at np.asarray."""

    def __array__(self, *a, **k):
        raise RuntimeError("UNAVAILABLE: link died mid-execution")


def _lose_scans(fleet) -> None:
    """Every chunk-step in flight whose scan the host has not read
    yet loses its device handles."""
    for st in fleet._flight:
        if not st.fronted:
            st.outs = tuple(_Unpullable() for _ in range(11))


def test_async_pull_failure_rescans_chunk(corpus):
    """On an async backend a runtime failure surfaces at the host
    pull, AFTER the guarded dispatch returned — the receiver must
    re-dispatch the chunk (results are lost) instead of crashing,
    and the emitted frames stay bit-identical."""
    stream, starts, frames_c, *_ = corpus
    with telemetry.collect() as reg:
        sr = framebatch.StreamReceiver(**GEO)
        frames = sr.push(stream)
        # sabotage the device handles of the scan still in flight
        _lose_scans(sr.fleet)
        frames += sr.flush()
    _same_frames(frames, frames_c)
    assert not sr.stats.degraded     # the rescan's compiled path won
    assert reg.snapshot()["resilience.async_rescans"] == 1


def test_async_pull_failure_rescans_fleet_step(corpus):
    _s, _st, _fc, streams, fstarts, res_c = corpus
    with telemetry.collect() as reg:
        msr = framebatch.MultiStreamReceiver(4, **GEO)
        got = msr.push_many([s for s in streams])
        _lose_scans(msr)
        got += msr.flush()
    per = [[] for _ in range(4)]
    for i, fr in got:
        per[i].append(fr)
    for i in range(4):
        _same_frames(per[i], res_c[i])
    assert not msr.stats.degraded
    assert reg.snapshot()["resilience.async_rescans"] >= 1


def test_multi_transient_and_fatal_fleet_recovery(corpus):
    _s, _st, _fc, streams, fstarts, res_c = corpus
    specs = (faults.FaultSpec("rx.stream_chunk_multi", "transient",
                              calls=(0,)),
             faults.FaultSpec("rx.stream_decode_multi", "fatal",
                              calls=(0,)))
    with faults.inject(*specs) as plan:
        res, stats = framebatch.receive_streams(streams, **GEO)
    assert plan.total_fired == 2
    for i in range(4):
        _same_frames(res[i], res_c[i])
    assert stats.degraded and stats.frames == sum(
        len(r) for r in res_c)


# ------------------------------------------- fused link + sweep chaos


@pytest.fixture(scope="module")
def fused_corpus():
    rng = np.random.default_rng(20260803)    # test_link_fused's seed
    psdus = [rng.integers(0, 256, n).astype(np.uint8) for n in LENS]
    kw = dict(snr_db=SNRS, cfo=CFO, delay=DELAY, seed=11,
              add_fcs=True, check_fcs=True)
    clean = link.loopback_many(psdus, MBPS_ALL, fused=True, **kw)
    return psdus, kw, clean


def test_fused_link_transient_retries_identical(fused_corpus):
    psdus, kw, clean = fused_corpus
    with telemetry.collect() as reg:
        with faults.inject(faults.FaultSpec("link.fused", "transient",
                                            calls=(0,))) as plan:
            got = link.loopback_many(psdus, MBPS_ALL, fused=True, **kw)
    assert plan.total_fired == 1
    for a, b in zip(got, clean):
        assert _same_result(a, b)
    assert reg.snapshot()["resilience.retries"] == 1


def test_fused_link_fatal_degrades_to_staged_identical(fused_corpus):
    psdus, kw, clean = fused_corpus
    with telemetry.collect() as reg:
        with dispatch.count_dispatches() as d:
            with faults.inject(faults.FaultSpec(
                    "link.fused", "fatal", every=1)) as plan:
                got = link.loopback_many(psdus, MBPS_ALL, fused=True,
                                         **kw)
    assert plan.total_fired == 1
    # the staged oracle result, bit-identical — with the degrade
    # RECORDED (gauge + counter), never a silent wrong answer
    for a, b in zip(got, clean):
        assert _same_result(a, b)
    assert d.gauges["link.degraded_mode"] == 1.0
    assert reg.snapshot()["link.fused_degraded"] == 1
    # the staged twin actually ran (its sites dispatched)
    assert d.counts.get("tx.encode_many", 0) >= 1


B_SWEEP, NB_SWEEP = 8, 24                  # test_link_fused geometry
SWEEP_RATES = (6, 54)


@pytest.fixture(scope="module")
def sweep_corpus():
    rng = np.random.default_rng(9)
    psdus = rng.integers(0, 256, (B_SWEEP, NB_SWEEP)).astype(np.uint8)
    snrs, seeds = (-2.0, 8.0), (7,)
    errs = link.sweep_ber(psdus, SWEEP_RATES, snrs, seeds)
    return psdus, snrs, seeds, errs


def test_sweep_transient_retries_identical(sweep_corpus):
    psdus, snrs, seeds, errs = sweep_corpus
    with faults.inject(faults.FaultSpec("link.sweep", "transient",
                                        calls=(0,))) as plan:
        got = link.sweep_ber(psdus, SWEEP_RATES, snrs, seeds)
    assert plan.total_fired == 1
    assert np.array_equal(got, errs)


def test_sweep_fatal_degrades_to_loop_identical(sweep_corpus):
    psdus, snrs, seeds, errs = sweep_corpus
    with dispatch.count_dispatches() as d:
        with faults.inject(faults.FaultSpec("link.sweep", "fatal",
                                            every=1)) as plan:
            got = link.sweep_ber(psdus, SWEEP_RATES, snrs, seeds)
    assert plan.total_fired == 1
    # integer-identical error counts via the per-batch loop twin
    assert np.array_equal(got, errs)
    assert d.gauges["link.degraded_mode"] == 1.0
    assert d.counts.get("rx.decode_batch", 0) >= 1
    # the gauge is a LEVEL, not a latch: a later healthy sweep
    # re-records 0.0 (dashboards recover)
    with telemetry.collect() as reg:
        link.sweep_ber(psdus, SWEEP_RATES, snrs, seeds)
    g = reg.find(telemetry.GAUGE_METRIC, site="link.degraded_mode")
    assert g is not None and g.last == 0.0


# ------------------------------------------- checkpoint / restore


def test_checkpoint_restore_bit_identical(corpus):
    """A receiver restarted mid-stream from its checkpoint emits
    bit-identical subsequent frames vs the uninterrupted run — the
    crash-recovery contract."""
    stream, starts, frames_c, *_ = corpus
    cut = stream.shape[0] // 2
    sr1 = framebatch.StreamReceiver(**GEO)
    first = sr1.push(stream[:cut])
    state, drained = sr1.checkpoint()
    first += drained
    # "crash": sr1 is abandoned; a NEW receiver restores and resumes
    sr2 = framebatch.StreamReceiver(checkpoint=state, **GEO)
    assert sr2.carry.offset == sr1.carry.offset
    assert np.array_equal(sr2.carry.tail, sr1.carry.tail)
    rest = sr2.push(stream[cut:])
    rest += sr2.flush()
    _same_frames(first + rest, frames_c)
    assert sr2.stats.frames + len(first) == len(frames_c)


def test_checkpoint_preserves_quarantine_and_degraded_state():
    """A quarantined/degraded receiver must RESUME quarantined and
    degraded — restoring fresh health would diverge from the
    uninterrupted run (the bit-identical-resumption contract)."""
    sr = framebatch.StreamReceiver(sanitize=True, **GEO)
    bad = np.zeros((16, 2), np.float32)
    bad[3] = np.nan
    sr.push(bad)
    sr.fleet._mark_degraded(scan=False)
    state, _ = sr.checkpoint()
    sr2 = framebatch.StreamReceiver(sanitize=True, checkpoint=state,
                                    **GEO)
    assert sr2.fleet.quarantined(0) and sr2.fleet._dirty[0]
    assert sr2.stats.quarantines == 1
    assert sr2.stats.sanitized == sr.stats.sanitized == 1
    assert sr2.stats.degraded and sr2.fleet._degraded


def test_raw_carry_without_geometry_refuses_restore(corpus):
    """A blob made by hand-calling checkpoint_carry WITHOUT the
    geometry fingerprint must not restore into an arbitrary receiver
    — the mismatch gate refuses to guess."""
    stream, *_ = corpus
    sr = framebatch.StreamReceiver(**GEO)
    sr.push(stream[:CHUNK // 2])
    blob = resilience.checkpoint_carry(sr.carry, seen=sr.fleet._seen[0])
    with pytest.raises(resilience.CarryCheckpointError,
                       match="lacks geometry fields"):
        framebatch.StreamReceiver(checkpoint=blob, **GEO)


def test_plain_oracle_propagates_decode_blowups(corpus, monkeypatch):
    """The containment opt-in boundary: in the PLAIN streaming=False
    oracle (no sanitize, not degraded) a decode blowup propagates —
    a genuine decoder defect must surface, never masquerade as frame
    loss. With sanitize=True the same blowup is contained, counted,
    and charged to the stream's health."""
    stream, *_ = corpus
    from ziria_tpu.phy.wifi import rx as _rx

    def boom(*a, **k):
        raise RuntimeError("genuine decoder defect")

    monkeypatch.setattr(_rx, "receive", boom)
    sr = framebatch.StreamReceiver(streaming=False, **GEO)
    with pytest.raises(RuntimeError, match="genuine decoder defect"):
        sr.push(stream)
        sr.flush()
    sr2 = framebatch.StreamReceiver(streaming=False, sanitize=True,
                                    **GEO)
    frames = sr2.push(stream)
    frames += sr2.flush()
    assert frames == []                     # dropped, loudly counted
    assert sr2.stats.lane_blowups >= 2
    assert sr2.stats.quarantines >= 1       # blowup_limit=2 reached


def test_fleet_plain_oracle_propagates_and_contains(corpus,
                                                    monkeypatch):
    """The same boundary, per lane of the fleet (where the rule
    lives): ``streaming=False`` decodes the same owned windows through
    per-capture `rx.receive` — identical frames, no compiled decode
    dispatched — a blowup there propagates in the plain oracle, and
    under sanitize=True is dropped, counted and charged to ITS lane's
    health while the lane-mate keeps its frames."""
    _s, _st, _fc, streams, fstarts, res_c = corpus
    from ziria_tpu.phy.wifi import rx as _rx

    def run(**kw):
        msr = framebatch.MultiStreamReceiver(2, **kw, **GEO)
        got = msr.push_many(list(streams[:2])) + msr.flush()
        return [[f for i, f in got if i == lane]
                for lane in range(2)], msr

    with dispatch.count_dispatches() as d:
        per, _msr = run(streaming=False)
    assert "rx.stream_decode_multi" not in d.counts
    assert d.counts["rx.sync"] >= sum(len(p) for p in per) >= 4
    for lane in range(2):
        _same_frames(per[lane], res_c[lane])    # == streaming=True

    real = _rx.receive
    heads = [streams[1][s: s + 64] for s in fstarts[1]]

    def boom(win, **kw):                        # lane 1's frames only
        if any(np.array_equal(win[:64], h) for h in heads):
            raise RuntimeError("genuine decoder defect")
        return real(win, **kw)

    monkeypatch.setattr(_rx, "receive", boom)
    with pytest.raises(RuntimeError, match="genuine decoder defect"):
        run(streaming=False)
    per, msr = run(streaming=False, sanitize=True)
    _same_frames(per[0], res_c[0])              # lane-mate untouched
    assert per[1] == []                         # dropped, loudly
    assert msr.stats.lane_blowups == len(heads) == 2
    assert msr._health[1].quarantines == 1      # blowup_limit=2
    assert msr._health[0].quarantines == 0
    assert not msr.stats.degraded


def test_parent_format_checkpoint_restores_into_the_face(corpus):
    """`StreamReceiver(checkpoint=...)` takes up the WHOLE single-
    stream rider — `restore_stream` alone leaves the degraded flags,
    the containment counters and the emitted count to the old runtime
    — so a blob in the format every single-stream receiver has
    written restores with those values in `stats`, resumes degraded
    (the oracle twin decodes the rest) and emits bit-identical
    subsequent frames; and what `checkpoint()` writes is that same
    nine-key rider."""
    stream, _starts, frames_c, *_ = corpus
    cut = stream.shape[0] // 2
    sr1 = framebatch.StreamReceiver(**GEO)
    first = sr1.push(stream[:cut])
    blob, drained = sr1.checkpoint()
    first += drained
    st = resilience.restore_carry(blob)
    assert set(st.state) == {
        "quarantined", "clean", "blowups", "quarantines", "dirty",
        "sanitized", "lane_blowups", "degraded", "scan_degraded"}
    assert st.emitted == len(first)
    parent_blob = resilience.checkpoint_carry(
        st, seen=st.seen, geometry=st.geometry,
        state=dict(st.state, sanitized=5, lane_blowups=3,
                   degraded=True))
    sr2 = framebatch.StreamReceiver(checkpoint=parent_blob, **GEO)
    assert sr2.stats.sanitized == 5 and sr2.stats.lane_blowups == 3
    assert sr2.stats.degraded and sr2.fleet._degraded
    assert not sr2.fleet._scan_degraded
    assert sr2.stats.frames == sr2.carry.emitted == len(first)
    # the face's own checkpoint carries the counters on
    st2 = resilience.restore_carry(sr2.checkpoint()[0]).state
    assert (st2["sanitized"], st2["lane_blowups"], st2["degraded"]) \
        == (5, 3, True)
    with dispatch.count_dispatches() as d:
        rest = sr2.push(stream[cut:]) + sr2.flush()
    assert d.counts["rx.stream_chunk_multi"] >= 1
    assert "rx.stream_decode_multi" not in d.counts
    _same_frames(first + rest, frames_c)
    assert sr2.stats.frames == len(frames_c) and len(rest) >= 1


def test_checkpoint_geometry_mismatch_rejected(corpus):
    stream, *_ = corpus
    sr = framebatch.StreamReceiver(**GEO)
    sr.push(stream[:CHUNK // 2])
    state, _ = sr.checkpoint()
    with pytest.raises(resilience.CarryCheckpointError,
                       match="geometry mismatch"):
        framebatch.StreamReceiver(
            checkpoint=state, chunk_len=2 * CHUNK,
            frame_len=FRAME_LEN, max_frames_per_chunk=K,
            check_fcs=True)
    # detector params are part of the fingerprint: a different
    # threshold detects different starts, so it must refuse too
    with pytest.raises(resilience.CarryCheckpointError,
                       match="geometry mismatch"):
        framebatch.StreamReceiver(checkpoint=state, threshold=0.95,
                                  **GEO)
    with pytest.raises(resilience.CarryCheckpointError):
        framebatch.StreamReceiver(checkpoint=b"garbage", **GEO)


def test_checkpoint_restore_quarantined_and_degraded_emissions(corpus):
    """The CROSS-PRODUCT rider restore (ISSUE 13 satellite): PR 12
    pins each rider field separately; this pins the behavior of a
    receiver that is simultaneously QUARANTINED and DEGRADED at
    checkpoint time — the restored receiver's subsequent emissions
    (quarantine drops, rejoin timing, oracle-twin decodes) are
    bit-identical to the uninterrupted quarantined+degraded run."""
    _s, _st, _fc, streams, _fs, _rc = corpus
    stream = streams[0]          # 2nd frame ~3 chunks downstream:
    #                              frames exist on BOTH sides of the
    #                              quarantine rejoin

    def run(split):
        sr = framebatch.StreamReceiver(sanitize=True, rejoin_after=2,
                                       **GEO)
        bad = np.zeros((16, 2), np.float32)
        bad[3] = np.nan
        out = sr.push(bad)                   # -> quarantined
        sr.fleet._mark_degraded(scan=False)  # -> decode oracle twin
        if split is None:
            out += sr.push(stream)
        else:
            out += sr.push(stream[:split])
            blob, drained = sr.checkpoint()
            out += drained
            sr = framebatch.StreamReceiver(
                sanitize=True, rejoin_after=2, checkpoint=blob,
                **GEO)
            assert sr.fleet.quarantined(0) and sr.fleet._degraded
            out += sr.push(stream[split:])
        out += sr.flush()
        return out, sr.stats

    want, stats_c = run(None)
    got, stats_r = run(stream.shape[0] // 2)
    _same_frames(got, want)
    # the rejoined tail really decoded through the oracle twin, and
    # the quarantine dropped the head identically in both runs
    assert stats_r.degraded and stats_c.degraded
    assert stats_r.quarantines == stats_c.quarantines == 1
    assert len(want) < len(_rc[0])     # quarantine dropped something
    assert len(want) >= 1              # and the rejoin re-emitted


def test_cross_product_blob_restores_into_fleet_lane(corpus):
    """A quarantined+degraded session's blob restored into a FLEET
    lane (`restore_stream`, the serving runtime's recovery path): the
    quarantine rider restores per-lane, the degraded flags
    deliberately do NOT transfer (they describe the old runtime's
    compiled-program health; the degraded twin is bit-identical by
    the pinned contract, so emissions cannot diverge), and the
    lane-mate stays untouched."""
    _s, _st, _fc, streams, _fs, res_c = corpus
    stream = streams[0]
    cut = stream.shape[0] // 2

    def lone(split):
        sr = framebatch.StreamReceiver(sanitize=True, rejoin_after=2,
                                       **GEO)
        bad = np.zeros((16, 2), np.float32)
        bad[3] = np.nan
        out = sr.push(bad)
        sr.fleet._mark_degraded(scan=False)
        out += sr.push(stream[:split] if split else stream)
        return sr, out

    sr_c, want = lone(None)
    want += sr_c.flush()
    sr, first = lone(cut)
    blob, drained = sr.checkpoint()
    first += drained

    msr = framebatch.MultiStreamReceiver(2, sanitize=True,
                                         rejoin_after=2, **GEO)
    rest = msr.restore_stream(0, blob)
    assert msr._health[0].quarantined          # rider restored
    assert not msr._degraded and not msr._scan_degraded
    assert not msr._health[1].quarantined      # lane-mate untouched
    got2 = msr.push_many({0: stream[cut:], 1: streams[1]})
    got2 += msr.flush()
    rest += [f for i, f in got2 if i == 0]
    _same_frames(first + rest, want)
    # the healthy lane-mate is bit-identical to its clean fleet run
    _same_frames([f for i, f in got2 if i == 1], res_c[1])


def test_fleet_lane_checkpoint_restores_into_lone_receiver(corpus):
    _s, _st, _fc, streams, fstarts, res_c = corpus
    msr = framebatch.MultiStreamReceiver(4, **GEO)
    cut = streams[1].shape[0] // 2
    got = msr.push_many([s[:cut] for s in streams])
    state, drained = msr.checkpoint(1)
    got += drained
    first = [f for i, f in got if i == 1]
    sr = framebatch.StreamReceiver(checkpoint=state, **GEO)
    rest = sr.push(streams[1][cut:])
    rest += sr.flush()
    _same_frames(first + rest, res_c[1])


# --------------------------------------------------------------- CLI


def test_cli_chaos_flags_scope_env(tmp_path, monkeypatch):
    """--chaos / --max-retries write ZIRIA_CHAOS / ZIRIA_MAX_RETRIES
    for the invocation only (the scoped-env pattern): pre-existing
    values restore after main() returns."""
    import os

    from ziria_tpu.runtime.buffers import StreamSpec, write_stream
    from ziria_tpu.runtime.cli import build_parser, main as cli_main

    args = build_parser().parse_args(
        ["--chaos", "rx.push:nan_slab:every=2", "--max-retries", "4"])
    assert args.chaos == "rx.push:nan_slab:every=2"
    assert args.max_retries == 4

    inf, outf = tmp_path / "in.dbg", tmp_path / "out.dbg"
    rng = np.random.default_rng(0)
    write_stream(StreamSpec(ty="bit", path=str(inf), mode="dbg"),
                 rng.integers(0, 2, 16).astype(np.uint8))
    monkeypatch.setenv("ZIRIA_CHAOS", "keep:transient:every=9")
    monkeypatch.delenv("ZIRIA_MAX_RETRIES", raising=False)
    rc = cli_main([
        "--prog=scramble",
        "--input=file", f"--input-file-name={inf}",
        "--input-file-mode=dbg", "--input-type=bit",
        "--output=file", f"--output-file-name={outf}",
        "--output-file-mode=dbg", "--output-type=bit",
        "--backend=interp",
        "--chaos", "other:transient:every=3", "--max-retries", "1",
    ])
    assert rc == 0
    assert os.environ.get("ZIRIA_CHAOS") == "keep:transient:every=9"
    assert os.environ.get("ZIRIA_MAX_RETRIES") is None
    assert not faults.active()          # plan deactivated on exit
    # a malformed spec is a FLAG error at parse time, not a traceback
    # from deep inside the run
    with pytest.raises(SystemExit, match="--chaos"):
        cli_main(["--prog=scramble", "--chaos", "justasite"])
    with pytest.raises(SystemExit, match="--chaos"):
        cli_main(["--prog=scramble", "--chaos", "s:explode:every=2"])


# ------------------------------- the compiler is not a run-time fault
#
# ISSUE 22: an error raised while a program is traced, lowered or
# compiled is a defect of the program, on this backend, every time.
# It propagates out of every guarded site as itself — never retried,
# never classified transient, never answered with the eager/oracle/
# staged twin — while a fault of a program that DID compile still
# retries and degrades as above. The guard tells them apart by WHEN,
# not by type: `resilience.compile_ahead` compiles a jitted program
# before its first guarded attempt.

import jax  # noqa: E402

from ziria_tpu.phy.wifi import rx as _rx  # noqa: E402
from ziria_tpu.runtime import serve  # noqa: E402


def _untraceable():
    """A jitted program that fails the way rx.py's shard_map did
    under jax 0.9: a TypeError out of the tracer."""
    def f(*_a):
        raise TypeError("scan body carry input and output must have "
                        "equal types")
    return jax.jit(f)


class _Refused:
    """A jitted program the chip's compiler refuses: it lowers, then
    compile() raises what XLA/Mosaic raise — a JaxRuntimeError whose
    status reads like a retryable one (a kernel over its fast-memory
    limit says RESOURCE_EXHAUSTED)."""

    def lower(self, *_a, **_k):
        return self

    def compile(self):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space "
            "vmem while compiling the kernel")

    def __call__(self, *_a):
        raise AssertionError("a refused program was dispatched")


class _RunFails:
    """A program that compiles and then fails when it RUNS, with the
    very error type a compile failure carries."""

    def lower(self, *_a, **_k):
        return self

    def compile(self):
        return self

    def __call__(self, *_a):
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: device halted mid-execution")


BROKEN = {"trace": (_untraceable, TypeError),
          "compile": (_Refused, jax.errors.JaxRuntimeError)}


def _site_single_scan(corpus, bad, _mp):
    sr = framebatch.StreamReceiver(**GEO)
    sr.fleet._jit1 = bad
    try:
        sr.push(corpus[0])
        sr.flush()
    finally:
        assert not sr.stats.degraded


def _site_single_decode(corpus, bad, mp):
    mp.setattr(_rx, "_jit_stream_decode_multi", lambda *a, **k: bad)
    sr = framebatch.StreamReceiver(**GEO)
    try:
        sr.push(corpus[0])
        sr.flush()
    finally:
        assert not sr.stats.degraded


def _site_fleet_scan(corpus, bad, _mp):
    msr = framebatch.MultiStreamReceiver(4, **GEO)
    msr._jit1 = bad
    try:
        msr.push_many(list(corpus[3]))
        msr.flush()
    finally:
        assert not msr.stats.degraded


def _site_fleet_decode(corpus, bad, mp):
    mp.setattr(_rx, "_jit_stream_decode_multi", lambda *a, **k: bad)
    msr = framebatch.MultiStreamReceiver(4, **GEO)
    try:
        msr.push_many(list(corpus[3]))
        msr.flush()
    finally:
        assert not msr.stats.degraded


def _site_serve_step(corpus, bad, _mp):
    """The acceptance surface: it raises out of ServeRuntime.step."""
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=4, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True))
    srv._rx._jit1 = bad
    assert srv.connect("c0").admitted
    assert srv.submit("c0", corpus[3][0][:CHUNK]).accepted
    try:
        srv.step()
    finally:
        assert not srv._rx.stats.degraded


def _site_link_fused(_corpus, bad, mp):
    mp.setattr(link, "_jit_fused_link", lambda *a, **k: bad)
    rng = np.random.default_rng(77)
    psdus = [rng.integers(0, 256, n).astype(np.uint8) for n in LENS]
    link.loopback_many(psdus, MBPS_ALL, snr_db=SNRS, cfo=CFO,
                       delay=DELAY, seed=11, fused=True)


def _site_link_sweep(_corpus, bad, mp):
    mp.setattr(link, "_jit_sweep_ber", lambda *a, **k: bad)
    rng = np.random.default_rng(9)
    psdus = rng.integers(0, 256, (B_SWEEP, NB_SWEEP)).astype(np.uint8)
    link.sweep_ber(psdus, SWEEP_RATES, (-2.0, 8.0), (7,))


GUARDED_SITES = {
    "face:rx.stream_chunk_multi": _site_single_scan,
    "face:rx.stream_decode_multi": _site_single_decode,
    "rx.stream_chunk_multi": _site_fleet_scan,
    "rx.stream_decode_multi": _site_fleet_decode,
    "serve.step": _site_serve_step,
    "link.fused": _site_link_fused,
    "link.sweep": _site_link_sweep,
}


@pytest.mark.parametrize("site", sorted(GUARDED_SITES))
@pytest.mark.parametrize("kind", sorted(BROKEN))
def test_trace_and_compile_errors_propagate(corpus, monkeypatch,
                                            kind, site):
    make, exc = BROKEN[kind]
    with telemetry.collect() as reg:
        with dispatch.count_dispatches() as d:
            with pytest.raises(exc):
                GUARDED_SITES[site](corpus, make(), monkeypatch)
    snap = reg.snapshot()
    # not retried, not counted fatal, not fallen back, not degraded
    for c in ("resilience.retries", "resilience.fatal",
              "resilience.fallbacks", "resilience.degraded",
              "resilience.async_rescans", "link.fused_degraded",
              "link.sweep_degraded"):
        assert snap.get(c, 0) == 0, (c, snap[c])
    # and no twin ran in its place
    assert not [s for s in d.counts if s.endswith(".eager")], d.counts


def test_runtime_failure_of_a_compiled_program_still_degrades(corpus):
    """Same exception TYPE as a compile failure, raised when the
    compiled program runs: contained exactly as before — the fleet
    degrades to its eager twin and the frames stay bit-identical."""
    _s, _st, _fc, streams, _fs, res_c = corpus
    with telemetry.collect() as reg:
        msr = framebatch.MultiStreamReceiver(4, **GEO)
        msr._jit1 = _RunFails()
        got = msr.push_many(list(streams)) + msr.flush()
    per = [[] for _ in range(4)]
    for i, fr in got:
        per[i].append(fr)
    for i in range(4):
        _same_frames(per[i], res_c[i])
    assert msr.stats.degraded
    snap = reg.snapshot()
    assert snap["resilience.fatal"] >= 1
    assert snap["resilience.degraded"] == 1


def test_compile_ahead_compiles_once_and_the_dispatch_reuses_it():
    """`compile_ahead` pays the ONE compile: the guarded jit call that
    follows finds the executable in the callable's own cache (no
    second XLA compile), and a second compile_ahead is a memo hit."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _d, **_k: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)

    @jax.jit
    def prog(x, n):
        return (x * 3 + n).sum()

    x, n = np.arange(7, dtype=np.float32), np.int32(2)
    resilience.compile_ahead(prog, x, n)
    assert len(compiles) == 1
    with dispatch.count_dispatches() as d:
        out = resilience.guarded("once", prog, x, n)
    assert float(out) == float((x * 3 + 2).sum())
    resilience.compile_ahead(prog, x, n)
    assert len(compiles) == 1, "the dispatch or the memo re-compiled"
    assert d.counts["once"] == 1
    # a new argument geometry is a new program
    resilience.compile_ahead(prog, np.arange(9, dtype=np.float32), n)
    assert len(compiles) == 2
    # a plain callable has nothing to compile
    resilience.compile_ahead(lambda: None)
