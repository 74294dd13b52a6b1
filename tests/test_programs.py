"""Compiled-program observatory (ziria_tpu/utils/programs.py): XLA
cost/memory attribution per jit-factory program, CPU-only (ISSUE 9).

Budget discipline: ONE module fixture drives the receive / batched /
streaming surfaces at the suite's shared tiny geometry (the same
12-byte-PSDU, K=8/4096-chunk/1024-window/8-symbol keys as
test_rx_stream) and analyzes every noted program once; each test then
reads the report. The FULL driver — fused link, BER sweep, channel
oracle — rides the tier-2 ``slow`` marker (the CLI path
``python -m ziria_tpu programs`` runs it; its per-program compiles
are real money on a cold cache).

The two cost-pin tests are the ISSUE 9 satellite: the streaming
chunk-scan and stream-decode programs' FLOPs / bytes-accessed pinned
within a generous factor of today's values, so an accidental
recompute (e.g. a dropped ``lax.scan`` carry re-evaluating the chunk)
fails tier-1 loudly instead of halving throughput silently.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ziria_tpu.phy.wifi import rx
from ziria_tpu.utils import programs as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_BYTES = 12                     # the suite's standard on-air PSDU
CHUNK, FRAME_LEN, K, SYM_B = 4096, 1024, 8, 8

# Today's XLA cost-analysis values for the two streaming programs at
# the canonical geometry (jax 0.4.37, CPU backend — the backend the
# tier-1 gate runs on). Bounds are deliberately generous (a jax
# version bump may reshuffle fusion a bit) but tight enough that a
# doubled chunk evaluation (~2x flops AND bytes) fails:
#   lower = pin / 3, upper = pin * 1.8
STREAM_CHUNK_PIN = {"flops": 11732372.0, "bytes_accessed": 3172926.0}
STREAM_DECODE_PIN = {"flops": 30006368.0, "bytes_accessed": 72476368.0}
# the ISSUE 20 fused twin: the same stream-decode program with the
# rate-switched fused front (fused_demap=True) — LLRs produced and
# consumed in VMEM, so bytes_accessed drops to ~0.58x the unfused pin
# (the fori-loop kernel body is also what the analytical model
# bills, one sub-block not MIXED_UNROLL straight-line steps)
STREAM_DECODE_FUSED_PIN = {"flops": 31700852.0,
                           "bytes_accessed": 42078772.0}


def _tier1_driver():
    """The cheap subset of programs.run_driver: per-frame receive,
    batched receive (+CRC), one streaming pass, and one multi-stream
    fleet pass — 13 dispatch-site labels, all at geometries other
    tier-1 suites also compile (the fleet pass rides
    test_rx_multistream's S=4 shape)."""
    from ziria_tpu.backend import framebatch
    from ziria_tpu.phy import link
    from ziria_tpu.phy.wifi import tx

    rng = np.random.default_rng(23)
    rates = [6, 54]
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in rates]
    cap = np.concatenate(
        [np.zeros((50, 2), np.float32),
         np.asarray(tx.encode_frame(psdus[0], rates[0]))], axis=0)
    rx.receive(cap)
    caps = [np.concatenate(
        [np.zeros((50, 2), np.float32),
         np.asarray(tx.encode_frame(p, m, add_fcs=True))], axis=0)
        for p, m in zip(psdus, rates)]
    framebatch.receive_many(caps, check_fcs=True, batched_acquire=True)
    stream, _ = link.stream_many(
        psdus, rates, snr_db=30.0, cfo=1e-4, delay=60, seed=8,
        add_fcs=True, tail=FRAME_LEN)
    framebatch.receive_stream(stream, chunk_len=CHUNK,
                              frame_len=FRAME_LEN,
                              max_frames_per_chunk=K, check_fcs=True,
                              streaming=True)
    streams, _st = link.stream_many_multi(
        [psdus[:1], psdus[1:], [], psdus[:1]],
        [rates[:1], rates[1:], [], rates[:1]],
        snr_db=np.inf, cfo=1e-4, delay=60, seed=9, add_fcs=True,
        tail=FRAME_LEN)
    framebatch.receive_streams(streams, chunk_len=CHUNK,
                               frame_len=FRAME_LEN,
                               max_frames_per_chunk=K, check_fcs=True)


@pytest.fixture(scope="module")
def report():
    return P.collect_programs(driver=_tier1_driver)


# ------------------------------------------------------------- acceptance


def test_lists_at_least_10_programs_with_nonzero_cost(report):
    # the ISSUE 9 acceptance shape: >= 10 compiled programs, every one
    # with nonzero flops AND bytes_accessed from XLA cost analysis
    ok = [r for r in report["programs"] if not r.get("error")]
    assert len(ok) >= 10, [r["label"] for r in report["programs"]]
    for r in ok:
        assert r["flops"] > 0, r
        assert r["bytes_accessed"] > 0, r


def test_memory_analysis_fields_present(report):
    ok = [r for r in report["programs"] if not r.get("error")]
    for r in ok:
        assert r["peak_bytes"] >= r["argument_bytes"] >= 0, r
        assert r["output_bytes"] > 0, r


def test_driver_covers_the_streaming_and_batched_factories(report):
    # factories the tier-1 driver exercises must all map back to a
    # noted program; the full-driver CLI covers the rest (slow test)
    uncovered = set(report["uncovered"])
    for fq in ("ziria_tpu.phy.wifi.rx._jit_stream_chunk_multi",
               "ziria_tpu.phy.wifi.rx._jit_stream_decode_multi",
               "ziria_tpu.phy.wifi.rx._jit_decode_data_mixed",
               "ziria_tpu.phy.wifi.rx._jit_acquire_many",
               "ziria_tpu.phy.wifi.rx._jit_sync_fn",
               "ziria_tpu.phy.wifi.rx._jit_crc_many",
               "ziria_tpu.phy.wifi.tx._jit_encode_many"):
        assert fq not in uncovered, (fq, sorted(uncovered))
    # the reduced driver legitimately skips only these surfaces
    assert uncovered <= {
        "ziria_tpu.phy.channel._jit_impair_many",
        "ziria_tpu.phy.channel._jit_impair_one",
        "ziria_tpu.phy.link._jit_fused_link",
        "ziria_tpu.phy.link._jit_sweep_ber",
        "ziria_tpu.phy.wifi.tx._jit_encode_batch",
    }, sorted(uncovered)


def test_factory_discovery_is_ast_driven():
    facs = P.discovered_factories()
    names = {f"{f['module']}.{f['name']}" for f in facs}
    # the jit factories of the tree are found by the R1 convention —
    # and table/kernel lru_caches (no jit in the body) are NOT
    assert "ziria_tpu.phy.wifi.rx._jit_stream_chunk_multi" in names
    assert "ziria_tpu.phy.wifi.rx._jit_stream_decode_multi" in names
    assert "ziria_tpu.phy.link._jit_fused_link" in names
    assert "ziria_tpu.ops.interleave.interleave_perm" not in names
    assert len(facs) >= 16


# ------------------------------------------------------------- cost pins


def _pin_check(cost, pin):
    for k, v in pin.items():
        assert v / 3 <= cost[k] <= v * 1.8, (
            f"{k}={cost[k]:.4g} outside [{v / 3:.4g}, {v * 1.8:.4g}] "
            f"— the compiled program's work changed materially "
            f"(accidental recompute, dropped fusion, or a real "
            f"optimization: re-pin deliberately)")


def test_stream_chunk_cost_pinned():
    # rx.stream_chunk_graph behind _jit_stream_chunk_multi at the
    # canonical (K=8, 1024-window, 8-symbol) geometry on the
    # 4096-sample chunk, one stream wide
    fn = rx._jit_stream_chunk_multi(K, FRAME_LEN, SYM_B)
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    cost = P.cost_of(fn, S((1, CHUNK, 2), jnp.float32), S((1,), i32),
                     S((1,), i32), S((1,), i32))
    _pin_check(cost, STREAM_CHUNK_PIN)


def test_stream_decode_cost_pinned():
    # _jit_stream_decode_multi (row-select + mixed decode + masked
    # CRC) at the same geometry; a dropped carry re-evaluating the
    # decode would ~double both pinned numbers
    need_b = rx.FRAME_DATA_START + 80 * SYM_B
    fn = rx._jit_stream_decode_multi(SYM_B, None, None, 2)
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    cost = P.cost_of(fn, S((1, K, need_b, 2), jnp.float32),
                     S((1, K), i32), S((1, K), i32), S((1, K), i32),
                     S((1, K), i32))
    _pin_check(cost, STREAM_DECODE_PIN)


def test_stream_decode_fused_cost_pinned_below_unfused():
    # the ISSUE 20 acceptance gate: at the suite-shared geometry the
    # fused stream decode must bill STRICTLY fewer bytes than the
    # unfused program it replaces (the whole point of keeping LLRs in
    # VMEM), and its own cost stays pinned so a wrapper regression
    # (e.g. a bank re-materialized per chunk) fails tier-1 loudly
    need_b = rx.FRAME_DATA_START + 80 * SYM_B
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    avals = (S((1, K, need_b, 2), jnp.float32), S((1, K), i32),
             S((1, K), i32), S((1, K), i32), S((1, K), i32))
    cost_u = P.cost_of(
        rx._jit_stream_decode_multi(SYM_B, None, None, 2), *avals)
    cost_f = P.cost_of(
        rx._jit_stream_decode_multi(SYM_B, None, None, 2,
                                    fused_demap=True), *avals)
    _pin_check(cost_f, STREAM_DECODE_FUSED_PIN)
    assert cost_f["bytes_accessed"] < cost_u["bytes_accessed"], (
        cost_f, cost_u)


# ----------------------------------------------------------- observatory


def test_note_site_is_free_when_idle():
    # no active observatory: note_site returns before any aval work,
    # and nothing is recorded anywhere
    obs = P.Observatory()
    P.note_site("nope", None, object())
    assert obs.notes == {}


def test_site_costs_join_on_dispatch_labels(report):
    labels = {r["label"] for r in report["programs"]}
    for lbl in ("rx.stream_chunk_multi", "rx.stream_decode_multi",
                "rx.decode_mixed", "rx.crc_many", "rx.acquire_many",
                "tx.encode_many"):
        assert lbl in labels, sorted(labels)


def test_roofline_math_and_peaks_table():
    # 1 GB in 1 ms = 1000 GB/s; v5e peak 819 GB/s
    r = P.roofline(1e-3, bytes_accessed=1e9, flops=2e9,
                   device_kind="TPU v5 lite")
    assert r["achieved_gbps"] == pytest.approx(1000.0)
    assert r["pct_hbm_peak"] == pytest.approx(100 * 1000 / 819.0,
                                              rel=1e-3)
    assert r["achieved_gflops"] == pytest.approx(2000.0)
    assert r["pct_flops_peak"] == pytest.approx(
        100 * 2.0 / 197.0, rel=1e-3)


def test_unknown_device_kind_reports_absolutes_without_pct():
    r = P.roofline(1e-3, bytes_accessed=1e9, flops=1e9,
                   device_kind="TPU v9 hypothetical")
    assert "achieved_gbps" in r and "achieved_gflops" in r
    assert "pct_hbm_peak" not in r and "pct_flops_peak" not in r
    assert P.peaks_for("cpu") is None
    assert P.peaks_for(None) is None
    assert P.peaks_for("v5e") == {"hbm_gbps": 819.0,
                                  "peak_tflops": 197.0}


def test_hlo_dump_writes_program_text(tmp_path):
    obs = P.Observatory()
    f = jax.jit(lambda x: (x * 2.0).sum())
    with P.observing(obs):
        x = jnp.ones((16,), jnp.float32)
        P.note_site("toy.sum", f, x)
        f(x)
    recs = obs.analyze(hlo_dump=str(tmp_path))
    assert len(recs) == 1 and recs[0]["label"] == "toy.sum"
    assert os.path.exists(recs[0]["hlo_path"])
    text = open(recs[0]["hlo_path"]).read()
    assert "HloModule" in text or "module" in text


def test_observatory_dedupes_geometry_and_counts_calls():
    obs = P.Observatory()
    f = jax.jit(lambda x: x + 1)
    with P.observing(obs):
        for _ in range(3):
            P.note_site("toy.add", f, jnp.ones((4,), jnp.float32))
        P.note_site("toy.add", f, jnp.ones((8,), jnp.float32))
    assert len(obs.notes) == 2
    counts = sorted(n.calls for n in obs.notes.values())
    assert counts == [1, 3]


# ------------------------------------------------------------ full driver


@pytest.mark.slow
def test_full_driver_covers_every_factory():
    rep = P.collect_programs()
    assert rep["uncovered"] == [], rep["uncovered"]
    assert rep["factories_covered"] == rep["factories_discovered"]
    assert rep["programs_analyzed"] >= 10


@pytest.mark.slow
def test_cli_programs_json_subprocess():
    # the acceptance surface end to end: `python -m ziria_tpu programs
    # --json` on a box whose default backend may even be a hung TPU
    # probe — the subcommand pins CPU itself
    out = subprocess.run(
        [sys.executable, "-m", "ziria_tpu", "programs", "--json"],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 0, out.stderr[-800:]
    j = json.loads(out.stdout.strip().splitlines()[-1])
    assert j["platform"] == "cpu"
    ok = [r for r in j["programs"] if not r.get("error")
          and r.get("flops") and r.get("bytes_accessed")]
    assert len(ok) >= 10
