"""Benchmark entry point — prints ONE JSON line.

Flagship metric (BASELINE.json): **802.11a OFDM RX samples/sec/chip** —
the batched steady-state DATA decode (channel est + matmul-FFT +
equalize + pilot tracking + soft demap + deinterleave + Viterbi +
descramble) at 54 Mbps, frames batched on one chip.

Baseline (BASELINE.md self-measured policy — the reference mount was
empty): the same receiver chain implemented in straightforward
vectorized numpy on the host CPU with the native C Viterbi
(a stand-in for the reference's single-core C backend). The correctness
gate requires the decoded PSDU to equal the transmitted bits before any
number is printed.

One process, on the chip or not at all: ``main()`` runs the stages
in this process and exits non-zero when JAX finds no accelerator —
nothing is measured on the host under a chip metric's name, and no
earlier capture is reprinted. Each completed stage is appended to
``BENCH_PARTIAL.jsonl`` and mirrored into ``BENCH_TRAJECTORY.jsonl``.
(ROADMAP D1 replaces this file; PR 22 only took the parent/child,
probe and stale-capture machinery out of it.)
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
PARTIAL_PATH = os.path.join(REPO, "BENCH_PARTIAL.jsonl")
BASELINE_PATH = os.path.join(REPO, "BASELINE.json")

# Stage-record schema version: bump whenever a stage's semantics change
# so resume (below) can never reuse a measurement whose meaning moved.
BENCH_STAGE_VERSION = 5
# A completed stage this recent (and this code version, same platform)
# is reused instead of re-measured (VERDICT r4 missing #1: two chip
# windows were lost re-burning already-captured stages from zero).
RESUME_WINDOW_DEFAULT = 21600.0

STAGE_BUDGET_S = 700      # the run's own wall budget the stage guards
#                           are fractions of (env BENCH_CHILD_BUDGET)

# Perf-ledger trajectory (ISSUE 9): ONE normalized flat record per
# completed stage, appended here by every run (the BENCH_r*.json
# "tail"-wrapped artifacts were unreadable by tooling; this file is
# what tools/perf_report.py diffs and gates on). BENCH_TRAJECTORY env
# overrides the path (tests, smoke runs that must not touch the
# committed ledger).
TRAJECTORY_PATH = os.path.join(REPO, "BENCH_TRAJECTORY.jsonl")

# stage -> (payload key of the stage's primary metric, direction a
# BETTER value moves). The trajectory carries direction per record so
# perf_report never needs this table.
STAGE_METRICS = {
    "headline": ("tpu_sps", "higher"),
    "batch_sweep": ("tpu_sps", "higher"),
    "windowed": ("tpu_sps", "higher"),
    "decompose": ("t_full_step_s", "lower"),
    "framebatch": ("dsl_sps_batched", "higher"),
    "fxp_interior": ("sps", "higher"),
    "tx_chain": ("tx_sps", "higher"),
    "micro_fir": ("items_per_s", "higher"),
    "micro_fft64": ("items_per_s", "higher"),
    "quantized_viterbi": ("sps_i16", "higher"),
    "viterbi_breakdown": ("t_full_s", "lower"),
    "viterbi_kernel_stats": ("sps_base", "higher"),
    "mixed_dispatch": ("sps_mixed", "higher"),
    "fused_mixed": ("sps_fused_mixed", "higher"),
    "batched_acquire": ("sps_batched_acquire", "higher"),
    "link_loopback": ("fps_batched", "higher"),
    "fused_link": ("fps_fused", "higher"),
    "ber_sweep": ("points_per_s_sweep", "higher"),
    "channel_sweep": ("ber_floor_severe", "lower"),
    "streaming_rx": ("sps_streaming", "higher"),
    "multi_stream": ("sps_multi", "higher"),
    "resilience": ("faults_recovered", "higher"),
    "serving": ("sps_serving", "higher"),
    "soak": ("recovery_p99_s", "lower"),
    "autotune": ("sps_tuned", "higher"),
    "lint": ("findings_total", "lower"),
    "programs": ("programs_analyzed", "higher"),
    "numpy_baseline": ("sps", "higher"),
    "result": ("rx_sps", "higher"),
}


def _block(out):
    """Force completion of everything queued before `out`.

    block_until_ready() over a remote device link has been observed to
    return before the device is actually done (it reported rates
    exceeding HBM bandwidth); a tiny device->host copy of the result is
    an honest fence because transfers are ordered after the producing
    computation. The child also measures a chained matmul with both
    fences and reports the ratio as ``fence_audit_bur_over_copy`` so
    the workaround is inspectable rather than folklore (a ratio well
    below 1 = bur returned early).
    """
    import jax
    leaves = [a for a in jax.tree.leaves(out) if hasattr(a, "ndim")]
    for a in leaves[-1:]:
        np.asarray(a.ravel()[:1] if a.ndim else a)


def _time(fn, *args, reps=5, fence=_block):
    """Average seconds per call: queue `reps` async calls, fence once.

    reps amortizes the host<->device round trip, which would otherwise
    dominate millisecond-scale kernels.
    """
    fence(fn(*args))  # warm-up / compile, fully drained before timing
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / reps


# ------------------------------------------------------------------ numpy RX

def np_rx_decode(frame, rate, n_sym, n_psdu_bits):
    """Host-CPU receiver chain (numpy), the perf baseline."""
    from ziria_tpu.ops.coding import PUNCTURE_KEEP
    from ziria_tpu.ops.interleave import deinterleave_perm
    from ziria_tpu.ops.ofdm import (DATA_BINS, LTS_FREQ, PILOT_BINS,
                                    PILOT_POLARITY, PILOT_VALS, TIME_SCALE)
    from ziria_tpu.ops.scramble import np_lfsr_sequence_127
    x = frame[..., 0] + 1j * frame[..., 1]
    # channel estimate from LTS
    ref = np.zeros(64, np.float32)
    ref[np.arange(-26, 27) % 64] = LTS_FREQ
    H = ((np.fft.fft(x[192:256]) + np.fft.fft(x[256:320])) * 0.5
         / TIME_SCALE) * ref
    Hd = H[DATA_BINS]
    gain = np.abs(Hd) ** 2

    syms = x[400: 400 + 80 * n_sym].reshape(n_sym, 80)[:, 16:]
    bins = np.fft.fft(syms, axis=-1) / TIME_SCALE
    eq = bins / np.where(H == 0, 1.0, H)[None, :]
    data = eq[:, DATA_BINS]
    pilots = eq[:, PILOT_BINS]
    pol = PILOT_POLARITY[(np.arange(n_sym) + 1) % 127]
    expect = PILOT_VALS[None, :] * pol[:, None]
    ph = np.angle((pilots * expect).sum(-1))
    data = data * np.exp(-1j * ph)[:, None]

    # 64-QAM demap
    i = data.real * np.sqrt(42.0)
    q = data.imag * np.sqrt(42.0)
    llr = np.stack([i, 4 - np.abs(i), 2 - np.abs(np.abs(i) - 4),
                    q, 4 - np.abs(q), 2 - np.abs(np.abs(q) - 4)],
                   axis=-1) * gain[None, :, None]
    llr = llr.reshape(n_sym, -1)
    perm = deinterleave_perm(rate.n_cbps, rate.n_bpsc)
    deint = llr[:, perm].reshape(-1)

    keep = PUNCTURE_KEEP[rate.coding]
    nblk = deint.size // keep.sum()
    dep = np.zeros((nblk, keep.size), np.float32)
    dep[:, np.flatnonzero(keep)] = deint.reshape(nblk, keep.sum())
    dep = dep.reshape(-1, 2)

    # Viterbi: native C decoder (the honest C-backend stand-in; the
    # reference's hot kernel is a C SORA brick). Fall back to the shared
    # numpy ACS (ops/viterbi.np_viterbi_decode) only if no toolchain
    # exists — that fallback is NOT a fair baseline and the ratio should
    # be read accordingly.
    from ziria_tpu.runtime.native_lib import load, viterbi_decode_native
    if load() is not None:
        bits = viterbi_decode_native(dep)
    else:
        from ziria_tpu.ops.viterbi import np_viterbi_decode
        bits = np_viterbi_decode(dep)

    from ziria_tpu.phy.wifi.tx import DEFAULT_SCRAMBLER_SEED, _seed_bits_np
    seq = np.resize(
        np_lfsr_sequence_127(_seed_bits_np(DEFAULT_SCRAMBLER_SEED)),
        bits.size)
    clear = bits ^ seq  # descramble with the frame's actual seed
    return clear[16: 16 + n_psdu_bits]  # 16 SERVICE bits, then the PSDU


# ------------------------------------------------------------ shared setup

def _setup():
    """Build the bench frame + expected bits (backend-agnostic)."""
    from ziria_tpu.phy.wifi import tx
    from ziria_tpu.phy.wifi.params import RATES, n_symbols
    from ziria_tpu.utils.bits import bytes_to_bits

    rate = RATES[54]
    # ZIRIA_BENCH_NBYTES shrinks the frame for CPU smoke tests of the
    # child path; outside smoke mode a leaked override must not
    # silently change the workload the published number is computed on
    n_bytes = int(os.environ.get("ZIRIA_BENCH_NBYTES", "1000"))
    if n_bytes != 1000 and os.environ.get("ZIRIA_BENCH_ALLOW_CPU") != "1":
        raise RuntimeError(
            f"ZIRIA_BENCH_NBYTES={n_bytes} is only valid in smoke mode "
            "(ZIRIA_BENCH_ALLOW_CPU=1): the headline metric is defined "
            "on the 1000-byte frame")
    n_sym = n_symbols(n_bytes, rate)
    n_psdu_bits = 8 * n_bytes
    frame_len = 400 + 80 * n_sym

    rng = np.random.default_rng(0)
    psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
    frame = np.asarray(tx.encode_frame(psdu, 54))
    want = np.asarray(bytes_to_bits(psdu))
    return rate, n_sym, n_psdu_bits, frame_len, frame, want


def _roofline(B, frame_len, n_sym, n_psdu_bits, t,
              device_kind=None, cost=None):
    """Achieved GB/s / TFLOP/s for one decode step → % of the chip's
    single-chip peaks (per-``device_kind`` table in
    ``ziria_tpu.utils.programs.DEVICE_PEAKS``; unknown kinds report
    absolutes with the pct_* fields omitted — absent, not wrong).

    ``cost`` — XLA's own ``cost_analysis()`` numbers for the batch
    decode program (``{"flops", "bytes_accessed"}`` per dispatch) —
    is the preferred accounting (``source: xla_cost_analysis``); the
    hand-derived per-frame formula that carried rounds 3-8 stays as a
    cross-check column (``hand_gbps``/``hand_tflops``). Without a
    cost dict the hand formula is the estimate, labelled as such.
    """
    bytes_per_frame = (
        frame_len * 8                 # input samples f32 (re, im)
        + n_sym * 64 * 8 * 3          # FFT in/out + equalize traffic
        + n_sym * 48 * 6 * 4 * 2      # LLRs write+read
        + n_psdu_bits * 1)            # output bits
    flops_per_frame = (
        n_sym * 64 * 6 * 5 * 2        # FFT (radix-2 estimate, complex)
        + n_sym * 48 * 40             # equalize + pilot track + demap
        + (n_psdu_bits + 16 + 6) * 64 * 4)  # Viterbi ACS add/compare/sel
    hand_gbps = B * bytes_per_frame / t / 1e9
    hand_tflops = B * flops_per_frame / t / 1e12
    if cost and cost.get("bytes_accessed") and cost.get("flops"):
        gbps = cost["bytes_accessed"] / t / 1e9
        tflops = cost["flops"] / t / 1e12
        out = {
            "achieved_gbps": round(gbps, 2),
            "achieved_tflops": round(tflops, 3),
            "source": "xla_cost_analysis",
            "hand_gbps": round(hand_gbps, 2),
            "hand_tflops": round(hand_tflops, 3),
        }
    else:
        gbps, tflops = hand_gbps, hand_tflops
        out = {
            "achieved_gbps": round(gbps, 2),
            "achieved_tflops": round(tflops, 3),
            "source": "hand_estimate",
        }
    from ziria_tpu.utils.programs import peaks_for
    peaks = peaks_for(device_kind)
    if peaks:
        out["pct_hbm_peak"] = round(100 * gbps / peaks["hbm_gbps"], 2)
        out["pct_flops_peak"] = round(
            100 * tflops / peaks["peak_tflops"], 3)
    return out


# ---------------------------------------------------------------- stages

def _traj_path():
    """The ONE reading of the BENCH_TRAJECTORY path override (tests
    and smoke harnesses point it at a scratch file so the committed
    ledger only accumulates real runs)."""
    return os.environ.get("BENCH_TRAJECTORY") or TRAJECTORY_PATH


def _traj_append(stage, metric, value, run_id, platform,
                 direction="higher", partial=False, resumed=False,
                 unit=None, source="bench", t=None, extra=None):
    """Append ONE normalized flat record to the perf-ledger trajectory
    (BENCH_TRAJECTORY.jsonl) — the canonical machine-readable form the
    BENCH_r*.json "tail" wrapper never was. ``extra`` carries
    stage-specific rider fields (the autotune stage's device_kind +
    winning geometry, which Geometry.tuned() and perf_report's
    device_kind matching read back). Best-effort: an unwritable
    ledger never blocks a bench run."""
    rec = {"run_id": run_id, "unix": round(
               time.time() if t is None else t, 1),
           "stage": stage, "metric": metric, "value": value,
           "platform": platform, "partial": bool(partial),
           "direction": direction, "source": source}
    if resumed:
        rec["resumed"] = True
    if unit:
        rec["unit"] = unit
    if extra:
        rec.update(extra)
    try:
        with open(_traj_path(), "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass


def _traj_from_stage(run_id, stage, rec):
    """Mirror a completed stage record into the trajectory when the
    stage has a primary metric and the record carries it (error and
    bookkeeping records don't)."""
    spec = STAGE_METRICS.get(stage)
    if spec is None or rec.get("error"):
        return
    key, direction = spec
    v = rec.get(key)
    if v is None:
        return
    # sweep probes are per-width measurements: key them per width
    # (mirroring _load_resume) so a run that probed B=1024 and a run
    # whose budget stopped at B=256 never compare as one series —
    # that aliasing would fake a 2-4x "regression" in the gate
    if stage == "batch_sweep" and rec.get("batch") is not None:
        stage = f"batch_sweep:{rec['batch']}"
    # the autotune stage's winner rides the ledger record so
    # Geometry.tuned(device_kind) can reconstruct it later, and so
    # perf_report's device_kind matching scopes the gate correctly
    extra = None
    if stage == "autotune":
        extra = {k: rec[k] for k in ("device_kind", "geometry")
                 if k in rec}
    _traj_append(stage, key, v, run_id, rec.get("platform"),
                 direction=direction,
                 resumed=bool(rec.get("resumed_from")),
                 t=rec.get("t"), extra=extra)


def _partial(run_id, stage, **kv):
    """Append one completed stage to BENCH_PARTIAL.jsonl (crash-proof
    evidence: the parent recovers the headline number from here if the
    child is later killed by a timeout) — and its normalized primary
    metric to the perf-ledger trajectory."""
    rec = {"run_id": run_id, "stage": stage, "t": time.time(),
           "ver": BENCH_STAGE_VERSION, **kv}
    with open(PARTIAL_PATH, "a") as f:
        f.write(json.dumps(rec) + "\n")
    _traj_from_stage(run_id, stage, rec)


def _load_resume(platform, window_s, now=None, path=PARTIAL_PATH,
                 workload_bytes=1000):
    """Most recent completed stage records eligible for reuse.

    Eligible = same schema version, same platform, younger than the
    resume window, and not an error record. Batch-sweep probes are
    keyed per width so each width resumes independently. This is what
    makes the child *stage-resumable*: a flapping 480 s window
    accumulates stages across invocations instead of re-burning the
    ones already measured (VERDICT r4 missing #1 / next #1).
    """
    now = time.time() if now is None else now
    out = {}
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                # the window is gated on the ORIGINAL capture time:
                # a resumed re-emission carries captured_t forward so
                # chained resumes cannot keep a measurement alive past
                # the window it was actually taken in
                t_cap = rec.get("captured_t", rec.get("t", 0))
                if (rec.get("ver") != BENCH_STAGE_VERSION
                        or rec.get("platform") != platform
                        or rec.get("workload_bytes") != workload_bytes
                        or t_cap < now - window_s
                        or rec.get("error")):
                    continue
                keys = [rec.get("stage")]
                if keys[0] == "batch_sweep":
                    keys = [f"batch_sweep:{rec.get('batch')}"]
                elif keys[0] == "headline" and rec.get("windowed"):
                    # a windowed-Viterbi promotion is a different
                    # decode method: it must never shadow the exact
                    # step at its width (the "windowed" stage record
                    # is what resumes the measurement itself)
                    keys = ["headline_windowed"]
                elif keys[0] == "headline":
                    # a run emits headline at B=128 and again when the
                    # sweep promotes a wider B — keep each width's
                    # measurement as well as the latest promotion
                    keys.append(f"headline:{rec.get('batch')}")
                for key in keys:
                    if key not in out or rec["t"] > out[key]["t"]:
                        out[key] = rec
    except OSError:
        pass
    return out


_RESUME_META = ("run_id", "stage", "t", "ver", "resumed_from",
                "captured_t", "platform", "workload_bytes")


def _stage_payload(rec):
    """A resumed record's measurement fields, minus bookkeeping
    (platform is re-stamped by the emitting child, not carried)."""
    return {k: v for k, v in rec.items() if k not in _RESUME_META}


def _child_main(run_id):
    """The measurement, in this process, on the chip.

    Prints progress to stderr and exactly one JSON object to stdout.
    Stage order is headline-first: the samples/sec/chip measurement is
    recorded to BENCH_PARTIAL.jsonl before the auxiliary proofs.
    """
    def note(msg):
        print(f"[bench-child] +{time.time() - t0:.1f}s {msg}",
              file=sys.stderr, flush=True)

    t0 = time.time()
    # the wall budget of this run — stage guards below are fractions
    # of it
    budget = float(os.environ.get("BENCH_CHILD_BUDGET",
                                  str(STAGE_BUDGET_S)))
    import jax
    import jax.numpy as jnp
    if os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1":
        # smoke mode: pin the CPU before backend init (same mechanism
        # as tests/conftest.py)
        jax.config.update("jax_platforms", "cpu")
    from ziria_tpu.utils import compile_cache
    compile_cache.place()
    note("jax imported; touching backend")
    devs = jax.devices()
    dev = devs[0]
    note(f"backend up: {dev.platform} / {getattr(dev, 'device_kind', '?')}"
         f" x{len(devs)}")
    if dev.platform == "cpu":
        if os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1":
            # smoke-test mode: exercises every stage's control flow on
            # the CPU; every record it writes carries platform "cpu"
            note("CPU allowed for smoke test (ZIRIA_BENCH_ALLOW_CPU=1)")
        else:
            # a CPU run must NOT be reported as a per-chip number
            note("backend is CPU, not a TPU — no accelerator, no result")
            sys.exit(3)
    _partial(run_id, "backend_up", platform=dev.platform,
             device_kind=getattr(dev, "device_kind", "?"))

    from ziria_tpu.phy.wifi import rx

    rate, n_sym, n_psdu_bits, frame_len, frame, want = _setup()
    note("frame encoded")

    def part(stage, **kv):
        kv.setdefault("platform", dev.platform)
        kv.setdefault("workload_bytes", n_psdu_bits // 8)
        _partial(run_id, stage, **kv)

    # stage resume: reuse measurements a recent same-version,
    # same-platform, same-workload child already recorded, re-emitting
    # them under THIS run_id (tagged resumed_from) so partial recovery
    # and the ledger both see what this run published
    resume = {}
    if os.environ.get("ZIRIA_BENCH_RESUME", "1") != "0":
        window = float(os.environ.get("BENCH_RESUME_WINDOW",
                                      str(RESUME_WINDOW_DEFAULT)))
        resume = _load_resume(dev.platform, window,
                              workload_bytes=n_psdu_bits // 8)
        resume.pop("backend_up", None)   # always re-proven above
        resume.pop("complete", None)     # always re-merged below
        if resume:
            note(f"resume: reusable stages {sorted(resume)}")
    resumed_stages = []

    def reuse(rec):
        resumed_stages.append(rec["stage"])
        part(rec["stage"], **_stage_payload(rec),
             resumed_from=rec.get("resumed_from", rec["run_id"]),
             captured_t=rec.get("captured_t", rec["t"]))
        return _stage_payload(rec)

    # seed the batch-width table from resumable measurements: the
    # headline record carries the B it was promoted at, sweep probes
    # carry theirs — each width already measured is not re-burned
    sweep = {}
    width_cap = {}   # batch -> original capture time (resume provenance)
    for key, rec in resume.items():
        # windowed-Viterbi headline promotions are a different decode
        # method — they resume via the "windowed" stage and must not
        # seed the EXACT-decode width table
        if (key.startswith("headline:") or key.startswith("batch_sweep:")) \
                and "t_step_s" in rec and "batch" in rec \
                and not rec.get("windowed"):
            sweep.setdefault(rec["batch"], rec["t_step_s"])
            width_cap.setdefault(rec["batch"],
                                 rec.get("captured_t", rec["t"]))
    fresh_widths = set()   # widths actually measured by THIS child

    B = 128
    frames = jnp.asarray(np.broadcast_to(frame, (B,) + frame.shape).copy())
    decode = jax.jit(
        lambda f: rx.decode_data_batch(f, rate, n_sym, n_psdu_bits)[0])
    dev_kind = getattr(dev, "device_kind", "?")

    _cost_memo = {}

    def _decode_cost(b):
        """XLA's own cost analysis for the batch decode at width b —
        the compiled-graph accounting the roofline block now prefers
        over the hand formula (ISSUE 9). Never fatal and budget-
        guarded (lower+compile off the jit fast path costs a compile
        per width); None falls back to the hand estimate."""
        if b in _cost_memo:
            return _cost_memo[b]
        cost = None
        try:
            if time.time() - t0 < 0.80 * budget:
                from ziria_tpu.utils import programs as _prog
                cost = _prog.cost_of(decode, jax.ShapeDtypeStruct(
                    (b,) + frame.shape, jnp.float32))
        except Exception as e:
            note(f"decode cost analysis failed at B={b}: {e!r}")
        _cost_memo[b] = cost
        return cost
    if B in sweep and "correctness" in resume:
        reuse(resume["correctness"])
        note("correctness + B=128 timing resumed from prior window")
    else:
        # batched correctness gate (also the single-frame gate: row 0)
        got_b = np.asarray(decode(frames))
        assert np.array_equal(got_b[0], want) \
            and np.array_equal(got_b[-1], want)
        note("batched correctness gate passed; timing")
        part("correctness", batch=B)

    # Steady-state throughput, amortized ON DEVICE. Measured r2: the
    # old remote device link cost ~70 ms per host round-trip and ~2-4 ms per
    # queued call (50 queued 4k matmuls time at 14 TFLOP/s; a device-
    # side chain of the same matmul runs at 213 TFLOP/s ~ peak), so
    # per-call timing measures the link, not the chip. A streaming
    # receiver runs the decode in a device-side loop anyway, so the
    # honest samples/sec/chip is the *marginal* time of one decode step
    # inside a jitted fori_loop, taken between two loop lengths to
    # cancel the fixed round-trip.
    # integrity checksum folded into the timed loop: a lane- and
    # bit-position-weighted reduction of the decoded bits, masked to 20
    # bits so the accumulator cannot overflow. Catches decode
    # corruption in ANY lane/bit at ANY width (the earlier ride-along
    # watched a single bit of lane 0), at a cost negligible relative to
    # the decode — and identical across widths, keeping the sweep fair.
    CHK_MASK = (1 << 20) - 1

    def _chk_expected(b, k):
        i = np.arange(b, dtype=np.int64)[:, None]
        j = np.arange(want.size, dtype=np.int64)[None, :]
        w = (i * 131 + j * 7) % 17 - 8
        one = int((w * want.astype(np.int64)).sum())
        # k masked additions == multiplication mod 2^20 (Python's &
        # on negative ints is two's complement, matching the device)
        return (k * one) & CHK_MASK

    def make_decode_k(decode_rows):
        """Jitted K-step device loop around `decode_rows` ((B, len, 2)
        -> (B, n_psdu_bits) bits) with the integrity checksum — ONE
        definition shared by the f32 and fxp paths so their timing
        methodology and corruption detection cannot drift apart."""
        @jax.jit
        def dk(f, k):
            # traced loop bound -> ONE compile serves every K
            i = jnp.arange(f.shape[0], dtype=jnp.int32)[:, None]
            j = jnp.arange(n_psdu_bits, dtype=jnp.int32)[None, :]
            chk_w = (i * 131 + j * 7) % 17 - 8

            def body(_i, carry):
                s, acc = carry
                bits = decode_rows(f + s)    # s is 0 at runtime but
                chk = (bits.astype(jnp.int32) * chk_w).sum()
                # bits are 0/1 so b>>1 == 0, yet data-dependent: the
                # next iteration's input cannot be hoisted
                return (bits[0, 0].astype(jnp.int32) >> 1,
                        (acc + chk) & CHK_MASK)
            return jax.lax.fori_loop(
                0, k, body, (jnp.int32(0), jnp.int32(0)))[1]
        return dk

    decode_k = make_decode_k(
        lambda x: rx.decode_data_batch(x, rate, n_sym, n_psdu_bits)[0])

    def timed_k(dk, f, k, tries=3):
        best = float("inf")
        _block(dk(f, jnp.int32(k)))            # compile + warm
        for _ in range(tries):
            ts = time.perf_counter()
            _block(dk(f, jnp.int32(k)))
            best = min(best, time.perf_counter() - ts)
        return best

    def emit_headline(stage, b, t, method, **fields):
        """One definition of a measured-throughput partial record, so
        the headline, sweep probes, and promotion can't drift apart.
        A record whose width was NOT measured by this child carries the
        original capture time so chained resumes age out honestly."""
        extra = dict(fields)
        if b not in fresh_widths and b in width_cap:
            extra.setdefault("captured_t", width_cap[b])
        # the cost analysis describes the EXACT batch decode program;
        # a windowed-Viterbi promotion is a different program, so its
        # roofline keeps the hand formula (labelled hand_estimate)
        cost = None if extra.get("windowed") else _decode_cost(b)
        part(stage, tpu_sps=b * frame_len / t, t_step_s=t, batch=b,
             device_kind=dev_kind,
             timing_method=method,
             roofline=_roofline(b, frame_len, n_sym, n_psdu_bits, t,
                                device_kind=dev_kind, cost=cost),
             **extra)

    K1, K2 = 32, 160
    if f"headline:{B}" in resume:
        # resumed: the base-width step was measured by a recent child
        # on this platform (checksum-gated before it was recorded)
        hl = reuse(resume[f"headline:{B}"])
        t_tpu = hl["t_step_s"]
        sweep[B] = t_tpu
        timing_method = (f"marginal device-loop step (K={K1} vs {K2}), "
                         f"resumed from prior window")
        note(f"device-loop: B={B} step {t_tpu*1e3:.3f} ms (resumed)")
    else:
        t1, t2 = timed_k(decode_k, frames, K1), timed_k(decode_k, frames, K2)
        t_tpu = (t2 - t1) / (K2 - K1)
        timing_method = f"marginal device-loop step (K={K1} vs {K2})"
        note(f"device-loop: K={K1}: {t1*1e3:.1f} ms, K={K2}: {t2*1e3:.1f} ms"
             f" -> marginal {t_tpu*1e3:.3f} ms/step")
        # verify the loop body's decode BEFORE the record exists: a
        # failed checksum must leave nothing for partial recovery
        a128 = int(decode_k(frames, jnp.int32(2)))
        assert a128 == _chk_expected(B, 2), (a128, _chk_expected(B, 2))
        fresh_widths.add(B)
        emit_headline("headline", B, t_tpu, timing_method)
        sweep[B] = t_tpu
    sps = B * frame_len / t_tpu

    # Pallas-on-Mosaic proof: decode with interpret=False explicitly and
    # compare to the lax.scan oracle. On a real TPU this compiles the
    # kernels with Mosaic; any Mosaic rejection fails loudly here.
    # Ordered BEFORE the batch sweep: this is load-bearing round
    # evidence and must land even if the sweep eats the remaining
    # child budget.
    if "pallas_mosaic" in resume:
        pallas_mosaic = bool(resume["pallas_mosaic"].get("pallas_mosaic"))
        reuse(resume["pallas_mosaic"])
        note("Pallas-Mosaic proof resumed from prior window")
    else:
        from ziria_tpu.ops import viterbi, viterbi_pallas
        rng = np.random.default_rng(1)
        llrs = jnp.asarray(rng.normal(size=(4, 1024, 2)).astype(np.float32))
        # interpret=False means Mosaic — except in the CPU smoke mode,
        # where Pallas has no backend and interpret mode stands in
        hard = viterbi_pallas.viterbi_decode_batch(
            llrs, interpret=(dev.platform == "cpu"))
        oracle = jax.vmap(viterbi.viterbi_decode)(llrs)
        assert np.array_equal(np.asarray(hard), np.asarray(oracle)), \
            "Pallas (Mosaic) Viterbi != lax.scan oracle"
        pallas_mosaic = dev.platform != "cpu"
        note("Pallas kernels compiled by Mosaic, match oracle"
             if pallas_mosaic else "Pallas kernels in interpret mode (smoke)")
        part("pallas_mosaic", pallas_mosaic=pallas_mosaic)

    # Batch-width sweep: the B=128 headline leaves the chip ~96% idle
    # (roofline above) — the decode is dependency-chain-bound, so wider
    # batches are nearly free until a VMEM/HBM cliff. Measure wider
    # widths with the same marginal methodology and promote the best
    # to the headline. Each width is one fresh compile of decode_k;
    # its result is recorded as a partial before the next compile
    # starts, so an interrupted run keeps whatever was measured.
    # ZIRIA_BENCH_SWEEP=0 pins the headline at B=128. Widths already
    # seeded from a resumed window are skipped, so re-entry spends the
    # budget on the widths still missing (B=1024 never ran in r4).
    if os.environ.get("ZIRIA_BENCH_SWEEP", "1") != "0":
        Ks1, Ks2 = 8, 40
        for Bs in (256, 512, 1024):
            if Bs in sweep:
                note(f"sweep: B={Bs} resumed "
                     f"({sweep[Bs]*1e3:.3f} ms/step)")
                continue
            # guard on the REAL kill budget the parent runs us under
            # (review: a constant above the parent's hard timeout can
            # never fire and every harvest died mid-aux as a partial)
            if time.time() - t0 > 0.55 * budget:
                note(f"sweep: out of time budget before B={Bs}")
                break
            try:
                fs = jnp.asarray(
                    np.broadcast_to(frame, (Bs,) + frame.shape).copy())
                # integrity ride-along at this width: the weighted
                # whole-batch checksum, not one bit of lane 0
                acc = int(decode_k(fs, jnp.int32(4)))
                assert acc == _chk_expected(Bs, 4), \
                    (acc, _chk_expected(Bs, 4))
                ts1, ts2 = (timed_k(decode_k, fs, Ks1),
                            timed_k(decode_k, fs, Ks2))
                t_b = (ts2 - ts1) / (Ks2 - Ks1)
                # plausibility: a step over MORE frames cannot take
                # less absolute time than the B=128 step (80% slack
                # for noise) — the sweep's K-spread is only 32 steps,
                # and a congested-window glitch there must not
                # publish an inflated headline
                if t_b < 0.8 * t_tpu:
                    note(f"sweep: B={Bs} marginal {t_b*1e3:.3f} ms "
                         f"implausible (< B=128's {t_tpu*1e3:.3f} ms)"
                         f" — discarded")
                    continue
                fresh_widths.add(Bs)
                sweep[Bs] = t_b
                note(f"sweep: B={Bs} marginal {t_b*1e3:.3f} ms/step"
                     f" ({Bs * frame_len / t_b / 1e6:.0f} M sps)")
                emit_headline(
                    "batch_sweep", Bs, t_b,
                    f"marginal device-loop step (K={Ks1} vs {Ks2}), "
                    f"batch sweep probe")
            except Exception as e:
                note(f"sweep: B={Bs} failed: {e!r}")
                break
        B_best = max(sweep, key=lambda b: b * frame_len / sweep[b])
        if B_best != B:
            B, t_tpu = B_best, sweep[B_best]
            sps = B * frame_len / t_tpu
            timing_method = (f"marginal device-loop step (K={Ks1} vs "
                             f"{Ks2}), best of batch sweep "
                             f"{sorted(sweep)}")
            if B_best not in fresh_widths:
                # the winning width's measurement came from a prior
                # window — the published result must say so, not just
                # the buried partial record (review finding)
                timing_method += ", width resumed from prior window"
                resumed_stages.append("headline")
            note(f"sweep: promoting B={B} to headline"
                 f" ({sps/1e6:.0f} M sps)")
            emit_headline("headline", B, t_tpu, timing_method)

    # Sliding-window parallel Viterbi (r5): the exact decode's ~8k-step
    # trellis chain is the suspected bound (see decompose below);
    # windowing converts that serial depth into batch lanes — the
    # truncated-traceback trade the reference's own SORA decoder makes,
    # bit-identical at operating SNR (tests/test_viterbi_windowed.py).
    # The integrity checksum gates it on-chip before any timing is
    # recorded; if it beats the exact headline it is promoted with the
    # method stated in timing_method. ZIRIA_BENCH_WINDOWED=0 disables.
    def _windowed_stage():
        if time.time() - t0 > 0.65 * budget:
            raise TimeoutError("skipped: child time budget")
        win, ov = 1024, 96
        if n_sym * rate.n_dbps <= win + 2 * ov:
            # too short to window (smoke frames): the decoder would
            # fall back to the exact path and any "win" would be noise
            raise TimeoutError("skipped: frame too short to window")
        # measure at the CURRENT headline width: after the sweep this
        # is the best exact-decode batch, so windowed x best-B stack
        Bw = B
        fw = frames if Bw == 128 else jnp.asarray(
            np.broadcast_to(frame, (Bw,) + frame.shape).copy())
        dkw = make_decode_k(lambda x: rx.decode_data_batch(
            x, rate, n_sym, n_psdu_bits, viterbi_window=win)[0])
        acc = int(dkw(fw, jnp.int32(2)))
        assert acc == _chk_expected(Bw, 2), (acc, _chk_expected(Bw, 2))
        tw1, tw2 = timed_k(dkw, fw, 8), timed_k(dkw, fw, 40)
        t_w = (tw2 - tw1) / 32
        t_ex = sweep.get(Bw, t_tpu)
        # same glitch guard as the sweep: a marginal step implausibly
        # below 1/50 of the exact step is a timing artifact
        if not t_w > 0.02 * t_ex:
            raise RuntimeError(
                f"implausible windowed marginal {t_w*1e3:.4f} ms "
                f"(exact step {t_ex*1e3:.3f} ms) — timing glitch")
        rec = {"batch": Bw, "window": win, "overlap": ov,
               "t_step_s": round(t_w, 6),
               "tpu_sps": round(Bw * frame_len / t_w, 1),
               "vs_exact_step": round(t_w / t_ex, 3)}
        note(f"windowed viterbi: B={Bw} {t_w*1e3:.3f} ms/step "
             f"({rec['tpu_sps']/1e6:.0f} M sps, "
             f"{rec['vs_exact_step']:.2f}x the exact step)")
        part("windowed", **rec)
        return rec

    windowed_captured_t = None
    can_window = n_sym * rate.n_dbps > 1024 + 2 * 96
    if "windowed" in resume and can_window:
        rec_w = resume["windowed"]
        windowed_captured_t = rec_w.get("captured_t", rec_w["t"])
        winrec = reuse(rec_w)
        note("windowed stage resumed from prior window")
    elif not can_window:
        winrec = {"skipped": "frame too short to window"}
    elif os.environ.get("ZIRIA_BENCH_WINDOWED", "1") == "0":
        winrec = {"skipped": "ZIRIA_BENCH_WINDOWED=0"}
    else:
        try:
            winrec = _windowed_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"windowed stage failed: {e!r}")
            winrec = {"error": repr(e)}

    headline_is_windowed = False
    if (winrec.get("tpu_sps") and
            winrec["tpu_sps"] > B * frame_len / t_tpu):
        B, t_tpu = winrec["batch"], winrec["t_step_s"]
        sps = winrec["tpu_sps"]
        headline_is_windowed = True
        timing_method = (
            f"marginal device-loop step (K=8 vs 40), windowed "
            f"Viterbi (window={winrec['window']}, "
            f"overlap={winrec['overlap']}; truncated-traceback "
            f"parallel decode, checksum-gated on-chip)")
        extra = {"windowed": True, "window": winrec.get("window"),
                 "overlap": winrec.get("overlap")}
        if windowed_captured_t is not None:
            # promotion of a RESUMED windowed measurement: say so and
            # carry the original capture time so chained resumes age
            # it out honestly (review finding)
            timing_method += ", resumed from prior window"
            extra["captured_t"] = windowed_captured_t
        else:
            # freshly measured this run (even when the exact step at
            # this width was resumed)
            fresh_widths.add(B)
        note(f"windowed decode promoted to headline "
             f"({sps/1e6:.0f} M sps)")
        emit_headline("headline", B, t_tpu, timing_method, **extra)

    # Step decomposition (VERDICT r4 next #3): the B=128 step runs at
    # ~4% of HBM peak — dependency-chain-bound, but WHERE? Time the
    # vmapped front end (channel est + matmul-FFT + equalize + demap +
    # deinterleave + depuncture) and the Pallas Viterbi kernel
    # separately with the same marginal-K method, so the round closes
    # with a measured bound decomposition even if nothing else lands.
    def _decompose_stage():
        if time.time() - t0 > 0.70 * budget:
            raise TimeoutError("skipped: child time budget")
        from ziria_tpu.ops import viterbi_pallas
        from ziria_tpu.phy.wifi.rx import _decode_front

        @jax.jit
        def front_k(f, k):
            def body(_i, carry):
                s, acc = carry
                dep = jax.vmap(
                    lambda x: _decode_front(x, rate, n_sym))(f + s)
                # tiny data-dependent feedback: the next iteration's
                # input depends on this one's output, so XLA cannot
                # hoist the body out of the loop
                return (dep[0, 0, 0] * 1e-30, acc + dep.sum() * 1e-30)
            return jax.lax.fori_loop(
                0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

        dep0 = jax.jit(jax.vmap(
            lambda x: _decode_front(x, rate, n_sym)))(frames)
        n_bits = n_sym * rate.n_dbps

        @jax.jit
        def vit_k(d, k):
            def body(_i, carry):
                s, acc = carry
                bits = viterbi_pallas.viterbi_decode_batch(
                    d + s, n_bits=n_bits,
                    interpret=(dev.platform == "cpu"))
                return (bits[0, 0].astype(jnp.float32) * 1e-30,
                        acc + bits.sum().astype(jnp.float32) * 1e-30)
            return jax.lax.fori_loop(
                0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

        Kd1, Kd2 = 8, 40
        tf = (timed_k(front_k, frames, Kd2) -
              timed_k(front_k, frames, Kd1)) / (Kd2 - Kd1)
        tv = (timed_k(vit_k, dep0, Kd2) -
              timed_k(vit_k, dep0, Kd1)) / (Kd2 - Kd1)
        t_full = sweep.get(128, t_tpu)
        dec = {"batch": 128,
               "t_front_s": round(tf, 6), "t_viterbi_s": round(tv, 6),
               "t_full_step_s": round(t_full, 6),
               "front_frac": round(tf / t_full, 3),
               "viterbi_frac": round(tv / t_full, 3)}
        note(f"decompose: front {tf*1e3:.3f} ms "
             f"({dec['front_frac']:.0%}) + viterbi {tv*1e3:.3f} ms "
             f"({dec['viterbi_frac']:.0%}) of {t_full*1e3:.3f} ms step")
        part("decompose", **dec)
        return dec

    if "decompose" in resume:
        decomp = reuse(resume["decompose"])
        note("decompose resumed from prior window")
    else:
        try:
            decomp = _decompose_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"decompose stage failed: {e!r}")
            decomp = {"error": repr(e)}

    # Frame batching on-chip (r4): any compiled .zir program amortizes
    # the host link across frames — 16 captures through the in-language
    # receiver should ride ~the single-frame device-call count. Timed
    # here because the win is exactly the per-call link cost the
    # marginal-step methodology above factors out.
    def _framebatch_stage():
        if time.time() - t0 > 0.75 * budget:
            raise TimeoutError("skipped: child time budget")
        from ziria_tpu.backend import chunked as CH
        from ziria_tpu.backend import hybrid as HY
        from ziria_tpu.backend.framebatch import StepBatcher, run_many
        from ziria_tpu.frontend import compile_file
        from ziria_tpu.interp.interp import run as interp_run
        from ziria_tpu.phy import channel

        hyb = HY.hybridize(compile_file(
            os.path.join(REPO, "examples", "wifi_rx.zir")).comp)
        caps = [channel.impaired_capture(24, 60, seed=100 + k,
                                         add_fcs=True)
                for k in range(16)]
        streams = [[p for p in xi] for _ps, xi in caps]
        interp_run(hyb, streams[0])              # compile single path
        CH.STATS["device_calls"] = 0
        ts = time.perf_counter()
        for s in streams:
            interp_run(hyb, s)
        t_seq = time.perf_counter() - ts
        calls_seq = CH.STATS["device_calls"]
        run_many(hyb, streams,
                 batcher=StepBatcher(len(streams)))  # compile vmap path
        b2 = StepBatcher(len(streams))
        ts = time.perf_counter()
        run_many(hyb, streams, batcher=b2)
        t_bat = time.perf_counter() - ts
        samples_total = sum(len(s) for s in streams)
        fb = {"frames": len(streams), "calls_sequential": calls_seq,
              "calls_batched": b2.device_calls,
              "t_sequential_s": round(t_seq, 3),
              "t_batched_s": round(t_bat, 3),
              # compiled-DSL throughput, comparable (roughly — 24 Mbps
              # short captures vs the headline's 54 Mbps frames) with
              # the library receiver's headline: the DSL-vs-library
              # gap factor VERDICT r4 #5 asks to state
              "samples_total": samples_total,
              "dsl_sps_batched": round(samples_total / t_bat, 1),
              "dsl_sps_sequential": round(samples_total / t_seq, 1)}
        note(f"framebatch: {calls_seq} calls / {t_seq:.2f}s sequential"
             f" -> {b2.device_calls} calls / {t_bat:.2f}s batched")
        part("framebatch", **fb)
        return fb

    if "framebatch" in resume:
        fb = reuse(resume["framebatch"])
        note("framebatch resumed from prior window")
    else:
        try:
            fb = _framebatch_stage()
        except Exception as e:        # evidence stage: never fatal
            note(f"framebatch stage failed: {e!r}")
            fb = {"error": repr(e)}

    # Fixed-point interior on-chip (r4 session 3): the Q15 integer
    # decode (phy/wifi/rx_fxp.py) timed with the same marginal-step
    # methodology at B=128 — evidence of what the reference's int16
    # discipline costs/earns on the VPU vs the f32 fast path.
    # Non-fatal, budget-guarded.
    def _fxp_stage():
        if time.time() - t0 > 0.85 * budget:
            raise TimeoutError("skipped: child time budget")
        from ziria_tpu.phy.wifi import rx_fxp
        fq = rx_fxp.quantize_frame(jnp.asarray(frame))
        fqs = jnp.broadcast_to(fq, (128,) + fq.shape)
        decode_k_fxp = make_decode_k(
            lambda x: rx_fxp.decode_data_batch_fxp(
                x, rate, n_sym, n_psdu_bits)[0])

        acc = int(decode_k_fxp(fqs, jnp.int32(2)))
        assert acc == _chk_expected(128, 2), \
            (acc, _chk_expected(128, 2))

        tf1 = timed_k(decode_k_fxp, fqs, 8)
        tf2 = timed_k(decode_k_fxp, fqs, 40)
        t_fxp = (tf2 - tf1) / 32
        t128 = sweep.get(128, t_tpu)
        # plausibility (same reasoning as the sweep's guard): an fxp
        # step 5x faster than the f32 step is a timing glitch on the
        # 32-step K-spread, not physics
        if not t_fxp > 0.2 * t128:
            raise RuntimeError(
                f"implausible fxp marginal {t_fxp*1e3:.3f} ms "
                f"(f32 step {t128*1e3:.3f} ms) — timing glitch")
        fxp_ev = {"t_step_s": round(t_fxp, 6), "batch": 128,
                  "sps": round(128 * frame_len / t_fxp, 1),
                  "vs_f32_interior": round(t_fxp / t128, 3)}
        note(f"fxp interior: {t_fxp*1e3:.3f} ms/step "
             f"({fxp_ev['sps']/1e6:.0f} M sps, "
             f"{fxp_ev['vs_f32_interior']:.2f}x the f32 step)")
        part("fxp_interior", **fxp_ev)
        return fxp_ev

    if "fxp_interior" in resume:
        fxp_ev = reuse(resume["fxp_interior"])
        note("fxp interior resumed from prior window")
    else:
        try:
            fxp_ev = _fxp_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"fxp stage failed: {e!r}")
            fxp_ev = {"error": repr(e)}

    # TX chain on-chip (r5; BASELINE config #3): the batched transmit
    # encode (scramble + conv + interleave + modulate + matmul-IFFT +
    # preamble/SIGNAL assembly) with the same marginal-step method.
    # All-parallel work — the counterpoint to the trellis-bound RX.
    def _tx_stage():
        if time.time() - t0 > 0.88 * budget:
            raise TimeoutError("skipped: child time budget")
        from ziria_tpu.phy.wifi import tx as txm
        Bt = 128
        bits = jnp.asarray(np.broadcast_to(
            np.asarray(want, np.uint8), (Bt, want.size)).copy())
        enc = jax.jit(jax.vmap(
            lambda b: txm.encode_frame_bits(b, rate)))
        got0 = np.asarray(enc(bits))
        # correctness gate: every encoded row equals the committed
        # reference frame (the same PSDU _setup encoded)
        assert np.allclose(got0[0], frame, atol=1e-4) \
            and np.allclose(got0[-1], frame, atol=1e-4)

        @jax.jit
        def tx_k(bb, k):
            def body(_i, carry):
                s, acc = carry
                out = jax.vmap(
                    lambda b: txm.encode_frame_bits(b, rate)
                )(jnp.bitwise_xor(bb, s))
                # runtime-zero, data-dependent feedback (cf. the RX
                # loop): the next iteration's input depends on this
                # one's output, so the body cannot be hoisted
                s2 = (out[0, 0, 0] * 1e-30).astype(jnp.uint8)
                return (jnp.broadcast_to(s2, bb.shape),
                        acc + out.sum() * 1e-30)
            z0 = jnp.zeros_like(bits)
            return jax.lax.fori_loop(
                0, k, body, (z0, jnp.float32(0)))[1]

        tt1, tt2 = timed_k(tx_k, bits, 8), timed_k(tx_k, bits, 40)
        t_tx = (tt2 - tt1) / 32
        # plausibility (cf. the fxp stage's guard): the marginal step
        # can't be negative or far below the K=40 run's average step —
        # that's scheduler noise on the K-spread, not physics, and it
        # must not persist as a resumable record
        if not t_tx > 0.02 * (tt2 / 40):
            raise RuntimeError(
                f"implausible tx marginal {t_tx*1e3:.4f} ms "
                f"(K=40 avg {tt2/40*1e3:.3f} ms) — timing glitch")
        rec = {"batch": Bt, "t_step_s": round(t_tx, 6),
               "tx_sps": round(Bt * frame_len / t_tx, 1)}
        note(f"tx chain: {t_tx*1e3:.3f} ms/step "
             f"({rec['tx_sps']/1e6:.0f} M samples/s generated)")
        part("tx_chain", **rec)
        return rec

    if "tx_chain" in resume:
        tx_ev = reuse(resume["tx_chain"])
        note("tx chain resumed from prior window")
    else:
        try:
            tx_ev = _tx_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"tx stage failed: {e!r}")
            tx_ev = {"error": repr(e)}

    # Micro configs on-chip (r5; BASELINE configs #1/#2): the FIR
    # pipeline and the registered 64-pt FFT-block pipeline, each at
    # the vectorizer's chosen width, timed with the calibration tool's
    # own device-loop method (imported, not re-implemented, so the two
    # cannot drift). Two independently resumable stages: a window that
    # dies between them keeps the finished half.
    def _micro_config(prog_name):
        if time.time() - t0 > 0.92 * budget:
            raise TimeoutError("skipped: child time budget")
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "calibrate_vect", os.path.join(REPO, "tools",
                                           "calibrate_vect.py"))
        cv = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cv)

        from ziria_tpu.core.vectorize import vectorize
        from ziria_tpu.runtime.cli import PROGS
        comp = PROGS[prog_name]()
        W = vectorize(comp).segments[0].width
        shape = {"fir": (), "fft64": (2,)}[prog_name]  # complex pairs
        # _time_width clamps the marginal >= 1e-9 (no glitch records)
        t_s, take = cv._time_width(comp, W, item_shape=shape)
        ev = {"config": prog_name, "width": W,
              "s_per_step": round(t_s, 9),
              "items_per_s": round(take / t_s, 1)}
        note(f"micro: {prog_name} W={W} "
             f"{take / t_s / 1e6:.2f} M items/s")
        part(f"micro_{prog_name}", **ev)
        return ev

    micro_ev = {}
    for prog_name in ("fir", "fft64"):
        key = f"micro_{prog_name}"
        if key in resume:
            micro_ev[prog_name] = reuse(resume[key])
            note(f"micro {prog_name} resumed from prior window")
        else:
            try:
                micro_ev[prog_name] = _micro_config(prog_name)
            except Exception as e:      # evidence stage: never fatal
                note(f"micro {prog_name} failed: {e!r}")
                micro_ev[prog_name] = {"error": repr(e)}

    # RX hot-path levers (ISSUE 1): the quantized-metric Viterbi and
    # the one-dispatch mixed-rate decode, measured by the shared tools
    # module (tools/rx_dispatch_bench.py — imported, not re-implemented,
    # per the VERDICT #9 tools-not-monolith discipline). Two
    # independently resumable, never-fatal stages so the next chip
    # window captures both levers without a code change.
    def _load_rx_dispatch_bench():
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "rx_dispatch_bench", os.path.join(REPO, "tools",
                                              "rx_dispatch_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _quantized_stage():
        if time.time() - t0 > 0.90 * budget:
            raise TimeoutError("skipped: child time budget")
        # smoke mode shrinks the batch with the frame: the point there
        # is path coverage, and B=128 interpret-mode Pallas on a CPU
        # child would eat the whole budget
        smoke = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().quantized_sweep(
            B=8 if smoke else 128, n_bytes=n_psdu_bits // 8,
            k1=2 if smoke else 4, k2=4 if smoke else 12)
        note(f"quantized viterbi: f32 {ev['t_step_f32_s']*1e3:.3f} ms "
             f"-> i16 {ev['t_step_i16_s']*1e3:.3f} ms/step "
             f"({ev['i16_over_f32']:.2f}x, bit-match="
             f"{ev['i16_matches_f32']})")
        part("quantized_viterbi", **ev)
        return ev

    if "quantized_viterbi" in resume:
        quant_ev = reuse(resume["quantized_viterbi"])
        note("quantized viterbi resumed from prior window")
    else:
        try:
            quant_ev = _quantized_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"quantized viterbi stage failed: {e!r}")
            quant_ev = {"error": repr(e)}

    # ISSUE 6 satellite: the decode step split into front-end / ACS /
    # traceback / full (the measured answer to the decompose stage's
    # "dependency-chain-bound, but WHERE?"), emitted alongside the
    # roofline block. Resumable, never-fatal.
    def _viterbi_breakdown_stage():
        if time.time() - t0 > 0.91 * budget:
            raise TimeoutError("skipped: child time budget")
        smoke = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().viterbi_breakdown(
            B=8 if smoke else 128, n_bytes=n_psdu_bits // 8,
            k1=2 if smoke else 4, k2=4 if smoke else 12)
        note(f"viterbi breakdown: front {ev['t_front_s']*1e3:.3f} ms "
             f"({ev['front_frac']:.0%}) + acs {ev['t_acs_s']*1e3:.3f} "
             f"ms ({ev['acs_frac']:.0%}) + traceback "
             f"{ev['t_traceback_s']*1e3:.3f} ms "
             f"({ev['traceback_frac']:.0%}) of {ev['t_full_s']*1e3:.3f}"
             f" ms full step")
        part("viterbi_breakdown", **ev)
        return ev

    if "viterbi_breakdown" in resume:
        vbrk_ev = reuse(resume["viterbi_breakdown"])
        note("viterbi breakdown resumed from prior window")
    else:
        try:
            vbrk_ev = _viterbi_breakdown_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"viterbi breakdown stage failed: {e!r}")
            vbrk_ev = {"error": repr(e)}

    # ISSUE 6 tentpole evidence: per-lever decode-core samples/s for
    # the rebuilt ACS (radix-4 / int16 / int8+LUT / fused demap /
    # stacked), identity-gated, with the ROOFLINE percentage each
    # lever achieves annotated from the same accounting as the
    # headline's roofline block — the per-lever deltas the issue asks
    # the roofline reporting to carry.
    def _viterbi_kernel_stats_stage():
        if time.time() - t0 > 0.92 * budget:
            raise TimeoutError("skipped: child time budget")
        smoke = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        rdb = _load_rx_dispatch_bench()
        # smoke mode drops the fused levers: their per-rate unrolled
        # kernels take minutes in interpret mode on CPU (milliseconds
        # of Mosaic compile on the chip); the fused identity is
        # covered by tier-1 pytest at a cheap rate either way
        levers = rdb.VITERBI_LEVERS[:5] if smoke else rdb.VITERBI_LEVERS
        ev = rdb.viterbi_kernel_stats(
            B=8 if smoke else 128, n_bytes=n_psdu_bits // 8,
            k1=2 if smoke else 4, k2=4 if smoke else 12,
            levers=levers)
        lever_roofline = {}
        for name, _kw in levers:
            t_l = ev.get(f"t_step_{name}_s")
            if t_l:
                lever_roofline[name] = _roofline(
                    ev["batch"], ev["frame_len"], n_sym, n_psdu_bits,
                    t_l, device_kind=dev_kind)
        ev["roofline_by_lever"] = lever_roofline
        best = max((ev[f"sps_{n}"], n) for n, _k in levers)
        note(f"viterbi levers: base {ev['sps_base']/1e6:.0f} M sps -> "
             f"best {best[1]} {best[0]/1e6:.0f} M sps "
             f"(i8 ber delta {ev.get('ber_int8_delta', 0):+.4f}, "
             f"gates green)")
        part("viterbi_kernel_stats", **ev)
        return ev

    if "viterbi_kernel_stats" in resume:
        vlev_ev = reuse(resume["viterbi_kernel_stats"])
        note("viterbi kernel stats resumed from prior window")
    else:
        try:
            vlev_ev = _viterbi_kernel_stats_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"viterbi kernel stats stage failed: {e!r}")
            vlev_ev = {"error": repr(e)}

    def _mixed_dispatch_stage():
        if time.time() - t0 > 0.93 * budget:
            raise TimeoutError("skipped: child time budget")
        ev = _load_rx_dispatch_bench().mixed_dispatch_stats(
            n_bytes=24 if os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
            else 100)
        note(f"mixed dispatch: {ev['compiles_bucketed']} bucketed "
             f"compiles / {ev['t_bucketed_s']:.3f}s -> "
             f"{ev['compiles_mixed']} compile / {ev['t_mixed_s']:.3f}s")
        part("mixed_dispatch", **ev)
        return ev

    if "mixed_dispatch" in resume:
        mixed_ev = reuse(resume["mixed_dispatch"])
        note("mixed dispatch resumed from prior window")
    else:
        try:
            mixed_ev = _mixed_dispatch_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"mixed dispatch stage failed: {e!r}")
            mixed_ev = {"error": repr(e)}

    # ISSUE 20 tentpole evidence: the rate-switched fused decode on
    # the mixed/stream path — identity-gated (lane-for-lane vs the
    # unfused mixed trellis, radix 2 and 4) with the analytical
    # cost_of(_jit_stream_decode_multi) bytes_accessed delta fused vs
    # unfused at the suite-shared geometry. On CPU the fused sps pays
    # interpret-mode dispatch overhead for the in-kernel 8-rate front
    # (the win is priced by the bytes delta until a chip run
    # lands); the stage records both sides either way. Same
    # resumable, never-fatal discipline as mixed_dispatch above.
    def _fused_mixed_stage():
        if time.time() - t0 > 0.935 * budget:
            raise TimeoutError("skipped: child time budget")
        smoke = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().fused_mixed_stats(
            B=8 if smoke else 64, n_bytes=24 if smoke else 100,
            k1=2, k2=4 if smoke else 6)
        note(f"fused mixed: identity "
             f"{ev['fused_mixed_bit_identical']}, stream decode bytes "
             f"{ev['stream_decode_bytes_unfused']/1e6:.1f}M -> "
             f"{ev['stream_decode_bytes_fused']/1e6:.1f}M "
             f"({ev['stream_decode_bytes_ratio']:.2f}x), "
             f"sps {ev['sps_unfused_mixed']/1e3:.0f}k -> "
             f"{ev['sps_fused_mixed']/1e3:.0f}k")
        part("fused_mixed", **ev)
        return ev

    if "fused_mixed" in resume:
        fused_mixed_ev = reuse(resume["fused_mixed"])
        note("fused mixed resumed from prior window")
    else:
        try:
            fused_mixed_ev = _fused_mixed_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"fused mixed stage failed: {e!r}")
            fused_mixed_ev = {"error": repr(e)}

    # ISSUE 2 tentpole evidence: the acquisition front end's
    # O(N) -> O(1) dispatch collapse (receive_many batched_acquire),
    # measured by the instrumented dispatch counter. Same resumable,
    # never-fatal stage discipline as mixed_dispatch above.
    def _batched_acquire_stage():
        if time.time() - t0 > 0.95 * budget:
            raise TimeoutError("skipped: child time budget")
        ev = _load_rx_dispatch_bench().batched_acquire_stats(
            n_bytes=24 if os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
            else 100)
        note(f"batched acquire: {ev['dispatches_host_acquire']} "
             f"dispatches / {ev['t_host_acquire_s']:.3f}s -> "
             f"{ev['dispatches_batched_acquire']} dispatches / "
             f"{ev['t_batched_acquire_s']:.3f}s")
        part("batched_acquire", **ev)
        return ev

    if "batched_acquire" in resume:
        acq_ev = reuse(resume["batched_acquire"])
        note("batched acquire resumed from prior window")
    else:
        try:
            acq_ev = _batched_acquire_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"batched acquire stage failed: {e!r}")
            acq_ev = {"error": repr(e)}

    # ISSUE 3 tentpole evidence: the closed TX -> channel -> RX
    # loopback's dispatch collapse (per-frame >= 5N vs batched <= 5)
    # and frames/s, measured by the instrumented counter through the
    # shared tools module. Same resumable, never-fatal discipline.
    def _link_loopback_stage():
        if time.time() - t0 > 0.96 * budget:
            raise TimeoutError("skipped: child time budget")
        ev = _load_rx_dispatch_bench().link_loopback_stats(
            n_bytes=24 if os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
            else 100)
        note(f"link loopback: {ev['dispatches_perframe']} dispatches / "
             f"{ev['fps_perframe']:.1f} fps -> "
             f"{ev['dispatches_batched']} dispatches / "
             f"{ev['fps_batched']:.1f} fps")
        part("link_loopback", **ev)
        return ev

    if "link_loopback" in resume:
        link_ev = reuse(resume["link_loopback"])
        note("link loopback resumed from prior window")
    else:
        try:
            link_ev = _link_loopback_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"link loopback stage failed: {e!r}")
            link_ev = {"error": repr(e)}

    # ISSUE 4 tentpole evidence: the fused ONE-dispatch loopback graph
    # vs the staged path (counts, per-site dispatch times, identity
    # gate incl. batched CRC), and the one-scan BER sweep's points/s
    # vs the per-batch python loop. Same resumable never-fatal stage
    # discipline: the BENCH_* trajectory stays populated even when the
    # backend flakes.
    def _fused_link_stage():
        if time.time() - t0 > 0.96 * budget:
            raise TimeoutError("skipped: child time budget")
        ev = _load_rx_dispatch_bench().fused_link_stats(
            n_bytes=24 if os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
            else 100)
        note(f"fused link: {ev['dispatches_staged']} dispatches / "
             f"{ev['fps_staged']:.1f} fps -> "
             f"{ev['dispatches_fused']} dispatch / "
             f"{ev['fps_fused']:.1f} fps")
        part("fused_link", **ev)
        return ev

    if "fused_link" in resume:
        fused_ev = reuse(resume["fused_link"])
        note("fused link resumed from prior window")
    else:
        try:
            fused_ev = _fused_link_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"fused link stage failed: {e!r}")
            fused_ev = {"error": repr(e)}

    def _ber_sweep_stage():
        if time.time() - t0 > 0.97 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().ber_sweep_stats(
            n_frames=8 if cpu else 16,
            n_bytes=24 if cpu else 50,
            rates=(6, 54) if cpu else (6, 24, 54))
        note(f"ber sweep: {ev['points']} points, "
             f"{ev['dispatches_loop']} loop dispatches -> "
             f"{ev['dispatches_sweep']} "
             f"({ev['points_per_s_sweep']:.2f} points/s, "
             f"{ev['sweep_sps']:.0f} bit/s)")
        part("ber_sweep", **ev)
        return ev

    if "ber_sweep" in resume:
        sweep_ev = reuse(resume["ber_sweep"])
        note("ber sweep resumed from prior window")
    else:
        try:
            sweep_ev = _ber_sweep_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"ber sweep stage failed: {e!r}")
            sweep_ev = {"error": repr(e)}

    # ISSUE 15 tentpole evidence: the channel-hostile BER gate — a
    # rates x SNR x PROFILE waterfall (named multipath/SCO/Doppler/
    # burst profiles, phy/profiles) through sweep_ber's profile axis,
    # STILL one lax.scan dispatch, asserting the flat column is
    # bit-identical to the unprofiled sweep and every hostile
    # profile's high-SNR error floor stays inside its envelope
    # (tools/rx_dispatch_bench.channel_sweep_stats). The per-profile
    # ber_floor_* values land in BENCH_TRAJECTORY (severe is the
    # ledger's gated metric, lower = better). Same resumable
    # never-fatal stage discipline.
    def _channel_sweep_stage():
        if time.time() - t0 > 0.97 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().channel_sweep_stats(
            n_frames=4 if cpu else 8,
            n_bytes=24 if cpu else 50,
            rates=(6, 54) if cpu else (6, 24, 54),
            profiles=(("flat", "severe", "sco", "bursty", "hostile")
                      if cpu else
                      ("flat", "mild", "urban", "severe", "sco",
                       "doppler", "bursty", "hostile")))
        floors = {p: ev[f"ber_floor_{p}"] for p in ev["profiles"]}
        note(f"channel sweep: {ev['points']} points over "
             f"{len(ev['profiles'])} profiles in "
             f"{ev['dispatches_sweep']} dispatch(es), flat column "
             f"bit-identical, floors {floors} all inside envelopes")
        part("channel_sweep", **ev)
        return ev

    if "channel_sweep" in resume:
        chan_ev = reuse(resume["channel_sweep"])
        note("channel sweep resumed from prior window")
    else:
        try:
            chan_ev = _channel_sweep_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"channel sweep stage failed: {e!r}")
            chan_ev = {"error": repr(e)}

    # ISSUE 5 tentpole evidence: the streaming receiver's O(chunks)
    # dispatch count vs the per-capture path's O(frames) over the same
    # multi-frame stream, identity-gated, with the double-buffer
    # in-flight gauge. Since ISSUE 7 the stage also reports per-chunk
    # p50/p99 latency from the telemetry histogram layer and leaves a
    # Chrome trace (BENCH_TRACE_streaming.json) plus its
    # tools/trace_report.py summary next to the JSON artifacts, so
    # every bench run ships a readable timeline of the streaming loop.
    # Same resumable never-fatal stage discipline.
    def _streaming_rx_stage():
        if time.time() - t0 > 0.97 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        trace_path = os.path.join(REPO, "BENCH_TRACE_streaming.json")
        ev = _load_rx_dispatch_bench().streaming_stats(
            n_frames=8 if cpu else 16, trace_path=trace_path)
        chunk_lat = ev.get("latency_ms_streaming", {}).get(
            "rx.stream_chunk_multi", {})
        note(f"streaming rx: {ev['frames']} frames / "
             f"{ev['chunks']} chunks, "
             f"{ev['dispatches_percapture']} dispatches -> "
             f"{ev['dispatches_streaming']} "
             f"({ev['sps_streaming']:.0f} sps, in-flight "
             f"{ev['max_in_flight']}, chunk p50/p99 "
             f"{chunk_lat.get('p50', '?')}/{chunk_lat.get('p99', '?')}"
             f" ms)")
        # trace summary smoke: the trace the stage just wrote must
        # parse; its table rides the artifact so the timeline is
        # readable without loading Perfetto
        try:
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "trace_report", os.path.join(REPO, "tools",
                                             "trace_report.py"))
            tr = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(tr)
            _summary, table = tr.summarize_file(trace_path)
            ev["trace_summary"] = table
            note("trace summary:\n" + table)
        except Exception as e:          # summary is evidence, not a gate
            ev["trace_summary_error"] = repr(e)
        part("streaming_rx", **ev)
        return ev

    if "streaming_rx" in resume:
        stream_ev = reuse(resume["streaming_rx"])
        note("streaming rx resumed from prior window")
    else:
        try:
            stream_ev = _streaming_rx_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"streaming rx stage failed: {e!r}")
            stream_ev = {"error": repr(e)}

    # ISSUE 11 tentpole evidence: S concurrent streams through the
    # stream-axis fleet receiver vs S independent single-stream
    # receivers — dispatches per chunk-step pinned <= 2 independent
    # of S, lane-for-lane bit-identity gate, and aggregate samples/s
    # vs dp device count (sps_by_devices — the mesh-scaling record).
    # Same resumable never-fatal stage discipline.
    def _multi_stream_stage():
        if time.time() - t0 > 0.97 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().multi_stream_stats(
            n_streams=4 if cpu else 8,
            frames_per_stream=2 if cpu else 4)
        if len(ev.get("sps_by_devices", {})) <= 1:
            # a single visible device (the CPU smoke child) has no
            # in-process mesh point; measure it in a subprocess with
            # virtual devices — the dryrun_multichip mechanism, via
            # the tool's --multi-stream-mesh mode. Never fatal, and
            # genuinely bounded by the child's remaining budget:
            # under a minimum window the probe is SKIPPED, never
            # granted time the later stages no longer have.
            remaining = budget - (time.time() - t0) - 30.0
            if remaining < 60.0:
                ev["mesh_probe_error"] = "skipped: child time budget"
            else:
                env = dict(os.environ)
                n_dev = 4 if cpu else 8
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "") +
                    f" --xla_force_host_platform_device_count={n_dev}"
                ).strip()
                env["ZIRIA_TOOL_ALLOW_CPU"] = "1"
                try:
                    probe = subprocess.run(
                        [sys.executable,
                         os.path.join(REPO, "tools",
                                      "rx_dispatch_bench.py"),
                         "--multi-stream-mesh", str(n_dev)],
                        capture_output=True, text=True,
                        timeout=min(300.0, remaining), env=env,
                        cwd=REPO)
                    j = json.loads(
                        probe.stdout.strip().splitlines()[-1])
                    if "error" in j:
                        raise RuntimeError(j["error"])
                    ev["sps_by_devices_virtual"] = j["sps_by_devices"]
                    ev["mesh_scaling_virtual"] = j.get("mesh_scaling")
                    ev["mesh_virtual_devices"] = n_dev
                    note(f"multi stream mesh probe ({n_dev} virtual "
                         f"devices): sps by devices "
                         f"{j['sps_by_devices']} "
                         f"(x{j.get('mesh_scaling', '?')})")
                except Exception as e:  # probe: evidence, not a gate
                    ev["mesh_probe_error"] = repr(e)
        note(f"multi stream: {ev['streams']} streams / "
             f"{ev['chunk_steps']} chunk-steps, "
             f"{ev['dispatches_oracle']} dispatches -> "
             f"{ev['dispatches_multi']} "
             f"({ev['dispatches_per_chunk_step']}/step, "
             f"{ev['sps_multi']:.0f} sps aggregate, by devices "
             f"{ev['sps_by_devices']})")
        part("multi_stream", **ev)
        return ev

    if "multi_stream" in resume:
        multi_ev = reuse(resume["multi_stream"])
        note("multi stream resumed from prior window")
    else:
        try:
            multi_ev = _multi_stream_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"multi stream stage failed: {e!r}")
            multi_ev = {"error": repr(e)}

    # ISSUE 12 tentpole evidence: the chaos run of the multi-stream
    # fleet (tools/rx_dispatch_bench.resilience_stats) — injected
    # transient/fatal/latency/NaN-slab faults over the chunk-steps,
    # asserting ZERO crashes, healthy-lane bit-identity, quarantine
    # rejoin, and checkpoint/restore resumption; retries/fallbacks/
    # quarantines recorded. Same resumable never-fatal discipline.
    def _resilience_stage():
        if time.time() - t0 > 0.95 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().resilience_stats(
            n_streams=4 if cpu else 8,
            frames_per_stream=2 if cpu else 3)
        note(f"resilience: {ev['faults_injected']} fault(s) injected "
             f"over {ev['chunk_steps']} chunk-steps "
             f"({ev['faults_per_100_steps']}/100 steps, by kind "
             f"{ev['faults_by_kind']}): {ev['retries']} retried, "
             f"degraded={ev['degraded']}, "
             f"{ev['quarantines']} quarantine(s) "
             f"({ev['frames_dropped_quarantined']} frame(s) dropped, "
             f"rejoined), healthy lanes bit-identical, "
             f"checkpoint roundtrip bit-identical, zero crashes")
        part("resilience", **ev)
        return ev

    if "resilience" in resume:
        res_ev = reuse(resume["resilience"])
        note("resilience resumed from prior window")
    else:
        try:
            res_ev = _resilience_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"resilience stage failed: {e!r}")
            res_ev = {"error": repr(e)}

    # ISSUE 13 tentpole evidence: the chaos SLO run of the
    # continuous-batching SERVER (tools/rx_dispatch_bench
    # .serving_stats) — N client sessions (NaN/flood/stall/oversize
    # misbehavers included) over S lanes under injected
    # transient+fatal+hang+delay dispatch faults, gating zero
    # crashes, healthy-session bit-identity, the evict→restore
    # round trip, exact shed/evict/admit accounting, and the
    # ≤ 2-dispatches-per-chunk-step budget under admission churn;
    # p50/p99 chunk latency and sustained aggregate samples/s land
    # in the artifact. Same resumable never-fatal discipline.
    def _serving_stage():
        if time.time() - t0 > 0.95 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_rx_dispatch_bench().serving_stats(
            n_sessions=6 if cpu else 12,
            n_lanes=4 if cpu else 8,
            frames_per_session=2 if cpu else 3)
        note(f"serving: {ev['sessions']} sessions / {ev['lanes']} "
             f"lanes, {ev['dispatches_per_chunk_step']} "
             f"dispatches/chunk-step, {ev['sps_serving']:.0f} sps "
             f"sustained, p50/p99 chunk "
             f"{ev['chunk_latency_ms'].get('p50')}/"
             f"{ev['chunk_latency_ms'].get('p99')} ms, "
             f"{ev['faults_injected']} fault(s) injected, "
             f"shed={ev['shed']} evicted={ev['evicted']} "
             f"restored={ev['restored']}, healthy sessions "
             f"bit-identical, zero crashes")
        part("serving", **ev)
        return ev

    if "serving" in resume:
        serving_ev = reuse(resume["serving"])
        note("serving resumed from prior window")
    else:
        try:
            serving_ev = _serving_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"serving stage failed: {e!r}")
            serving_ev = {"error": repr(e)}

    # ISSUE 14 tentpole evidence: the chaos-SOAK of the DURABLE
    # serving runtime (tools/soak.py) — seeded fault campaign over
    # every fault kind (dispatch + push + the new io_torn/io_enospc
    # durability seams) plus a real subprocess SIGKILL mid-chunk-step,
    # each round crash -> ServeRuntime.recover(), gating zero crashes,
    # per-session bit-identity vs the uninterrupted oracle, the
    # <= 2-dispatches-per-chunk-step budget under no_recompile after
    # recovery, and the recovery-latency SLO; recovery_p99_s (lower is
    # better) lands in the trajectory. Same resumable never-fatal
    # stage discipline.
    def _load_soak():
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "soak", os.path.join(REPO, "tools", "soak.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _soak_stage():
        if time.time() - t0 > 0.95 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        ev = _load_soak().soak_stats(
            n_sessions=3 if cpu else 6,
            n_lanes=4 if cpu else 8,
            frames_per_session=3 if cpu else 4,
            rounds=2 if cpu else 4,
            sigkill_rounds=1 if cpu else 2)
        note(f"soak: {ev['faults_injected']} fault(s) "
             f"({ev['faults_by_kind']}) over {ev['rounds']} crash "
             f"round(s) + {ev['sigkill_rounds']} SIGKILL round(s) "
             f"(killed={ev['kills']['killed']}), recovery p50/p99 "
             f"{ev['recovery_p50_s']}/{ev['recovery_p99_s']} s, "
             f"{ev['dispatches_per_chunk_step_post_recovery']} "
             f"dispatches/chunk-step after recovery, "
             f"{ev['duplicates']} at-least-once duplicate(s) "
             f"deduped by (sid, start), bit-identical, zero crashes")
        part("soak", **ev)
        return ev

    if "soak" in resume:
        soak_ev = reuse(resume["soak"])
        note("soak resumed from prior window")
    else:
        try:
            soak_ev = _soak_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"soak stage failed: {e!r}")
            soak_ev = {"error": repr(e)}

    # ISSUE 16 tentpole evidence: the geometry autotuner
    # (utils/autotune) — candidates around the default Geometry,
    # cost-pruned through the PR 9 observatory's analytical model,
    # survivors measured on the streaming + fused-link surfaces under
    # the identity gates, best-vs-default speedup recorded. The ledger
    # record (sps_tuned, higher = better) rides this stage's part()
    # with device_kind + winning geometry attached, so
    # Geometry.tuned() reconstructs it. Same resumable never-fatal
    # stage discipline.
    def _autotune_stage():
        if time.time() - t0 > 0.90 * budget:
            raise TimeoutError("skipped: child time budget")
        cpu = os.environ.get("ZIRIA_BENCH_ALLOW_CPU") == "1"
        from ziria_tpu.utils import autotune as at
        ev_full = at.run(n_frames=4 if cpu else 12,
                         n_bytes=16 if cpu else 50,
                         reps=1 if cpu else 3,
                         record=False, log=note)
        ev = {k: ev_full[k] for k in (
            "winner", "geometry", "sps_tuned", "baseline_sps",
            "speedup", "device_kind", "platform", "candidates",
            "pruned", "identity_rejected", "measured")}
        note(f"autotune: winner '{ev['winner']}' "
             f"{ev['sps_tuned']:.0f} sps ({ev['speedup']}x default), "
             f"{len(ev['pruned'])} cost-pruned, "
             f"{len(ev['identity_rejected'])} identity-rejected")
        part("autotune", **ev)
        return ev

    if "autotune" in resume:
        tune_ev = reuse(resume["autotune"])
        note("autotune resumed from prior window")
    else:
        try:
            tune_ev = _autotune_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"autotune stage failed: {e!r}")
            tune_ev = {"error": repr(e)}

    # ISSUE 8 tentpole evidence: the jaxlint static-analysis sweep —
    # per-rule finding counts (and the suppression count) over
    # ziria_tpu/, recorded in the artifact so the trend — and any
    # suppression creep — stays visible across PRs. Pure AST, never
    # touches the backend (it cannot flake with the device), but it
    # rides the same resumable never-fatal stage discipline anyway.
    def _lint_stage():
        from ziria_tpu.analysis import lint_paths
        t_l = time.perf_counter()
        res = lint_paths([os.path.join(REPO, "ziria_tpu")])
        ev = {"files": res.files,
              "findings_total": len(res.findings),
              "findings_by_rule": res.counts,
              "suppressed": res.suppressed,
              "t_lint_s": round(time.perf_counter() - t_l, 3)}
        note(f"lint: {ev['findings_total']} finding(s) over "
             f"{ev['files']} file(s), {ev['suppressed']} suppressed, "
             f"{ev['t_lint_s']}s")
        part("lint", **ev)
        return ev

    if "lint" in resume:
        lint_ev = reuse(resume["lint"])
        note("lint resumed from prior window")
    else:
        try:
            lint_ev = _lint_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"lint stage failed: {e!r}")
            lint_ev = {"error": repr(e)}

    # ISSUE 9 tentpole evidence: the compiled-program observatory —
    # XLA cost/memory attribution for every live jit-factory program
    # (utils/programs), with the factory-coverage cross-check. Runs on
    # whatever backend this child has (CPU-only safe by design: the
    # observatory is exactly the attribution that needs no chip).
    # Resumable, never-fatal, budget-guarded.
    def _programs_stage():
        if time.time() - t0 > 0.90 * budget:
            raise TimeoutError("skipped: child time budget")
        from ziria_tpu.utils import programs as P
        t_p = time.perf_counter()
        rep = P.collect_programs()
        ev = {"programs_analyzed": rep["programs_analyzed"],
              "factories_discovered": rep["factories_discovered"],
              "factories_covered": rep["factories_covered"],
              "uncovered": rep["uncovered"],
              "total_flops": rep["total_flops"],
              "total_bytes_accessed": rep["total_bytes_accessed"],
              "programs": [
                  {k: r.get(k) for k in ("label", "in_avals", "flops",
                                         "bytes_accessed", "peak_bytes",
                                         "error") if r.get(k) is not None}
                  for r in rep["programs"]],
              "t_programs_s": round(time.perf_counter() - t_p, 3)}
        note(f"programs: {ev['programs_analyzed']} analyzed, "
             f"{ev['factories_covered']}/{ev['factories_discovered']} "
             f"factories covered, {ev['t_programs_s']}s")
        part("programs", **ev)
        return ev

    if "programs" in resume:
        prog_ev = reuse(resume["programs"])
        note("programs resumed from prior window")
    else:
        try:
            prog_ev = _programs_stage()
        except Exception as e:          # evidence stage: never fatal
            note(f"programs stage failed: {e!r}")
            prog_ev = {"error": repr(e)}

    def _percall_fence_stage():
        # per-call diagnostic (link-dispatch-bound upper bound on
        # latency) — always taken at the base batch of 128, which may
        # differ from the promoted headline batch; recorded as such
        t_percall = _time(decode, frames, reps=50)
        note(f"t_marginal={t_tpu*1e3:.3f} ms "
             f"t_percall={t_percall*1e3:.3f} ms")

        # fence audit (VERDICT r1 weak #8): block_until_ready has been
        # observed to return before the device drains over a remote
        # link. Time a chained 2k matmul with both fences; a bur/copy
        # ratio well below 1 proves the copy fence is load-bearing, ~1
        # means bur is currently honest. Recorded every run so the
        # workaround is evidence, not folklore.
        a = jnp.asarray(np.random.default_rng(3).normal(
            size=(2048, 2048)).astype(np.float32))
        mm = jax.jit(lambda x: x @ x * 1e-3)

        def chain(fence_fn, reps=10):
            o = mm(a)
            fence_fn(o)
            ts = time.perf_counter()
            for _ in range(reps):
                o = mm(o)
            fence_fn(o)
            return (time.perf_counter() - ts) / reps

        t_copy = chain(_block)
        t_bur = chain(jax.block_until_ready)
        fence_audit = round(t_bur / t_copy, 3)
        note(f"fence audit: bur/copy = {fence_audit} "
             f"({'bur returns early — copy fence required' if fence_audit < 0.8 else 'bur honest here'})")
        pf = {"t_percall_s": t_percall, "t_percall_batch": 128,
              "fence_audit_bur_over_copy": fence_audit}
        part("percall_fence", **pf)
        return pf

    if "percall_fence" in resume:
        pf = reuse(resume["percall_fence"])
        note("per-call + fence audit resumed from prior window")
    else:
        try:
            pf = _percall_fence_stage()
        except Exception as e:          # diagnostic: never fatal
            note(f"percall/fence stage failed: {e!r}")
            pf = {"error": repr(e)}

    out = {
        "tpu_sps": sps,
        "t_step_s": t_tpu,
        "timing_method": timing_method,
        "batch": B,
        "frame_bytes": n_psdu_bits // 8,
        "batch_sweep": {str(b): round(t, 6) for b, t in sorted(sweep.items())},
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "pallas_mosaic": pallas_mosaic,
        "windowed": winrec,
        "decompose": decomp,
        "framebatch": fb,
        "fxp_interior": fxp_ev,
        "tx_chain": tx_ev,
        "micro": micro_ev,
        "quantized_viterbi": quant_ev,
        "viterbi_breakdown": vbrk_ev,
        "viterbi_kernel_stats": vlev_ev,
        "mixed_dispatch": mixed_ev,
        "batched_acquire": acq_ev,
        "link_loopback": link_ev,
        "fused_link": fused_ev,
        "ber_sweep": sweep_ev,
        "channel_sweep": chan_ev,
        "streaming_rx": stream_ev,
        "multi_stream": multi_ev,
        "resilience": res_ev,
        "serving": serving_ev,
        "soak": soak_ev,
        "autotune": tune_ev,
        "lint": lint_ev,
        "programs": prog_ev,
        "roofline": _roofline(
            B, frame_len, n_sym, n_psdu_bits, t_tpu,
            device_kind=dev_kind,
            cost=None if headline_is_windowed else _decode_cost(B)),
        "resumed_stages": sorted(set(resumed_stages)),
    }
    for k in ("t_percall_s", "t_percall_batch",
              "fence_audit_bur_over_copy"):
        if k in pf:
            out[k] = pf[k]
    _partial(run_id, "complete", **out)
    print(json.dumps(out), flush=True)


def _pinned_baseline():
    """The committed, load-isolated baseline denominator (VERDICT r4
    missing #2): BASELINE.json's ``pinned_baseline`` entry, written by
    ``bench.py --pin-baseline`` on an idle box. Every published chip
    multiple divides by THIS number so the flagship claim cannot float
    with whatever else the host happens to be running."""
    try:
        with open(BASELINE_PATH) as f:
            pin = json.load(f).get("pinned_baseline")
        if pin and pin.get("sps"):
            return pin
    except (OSError, json.JSONDecodeError):
        pass
    return None


def _pin_baseline_main(n_runs):
    """Measure the numpy+C-AVX2 baseline N times and pin the max.

    The denominator must not swing with host load (r4 saw 4.08-6.40 M
    sps for the same code depending on what else was running), and it
    must be the number most favorable to the BASELINE: concurrent load
    can only slow the baseline down, so the fastest of N runs is the
    closest observation of the uncontended machine — and dividing by
    it yields the SMALLEST (most conservative) chip multiple. The max,
    the median, and every raw run are committed so the spread is
    inspectable.
    """
    import jax
    jax.config.update("jax_platforms", "cpu")
    rate, n_sym, n_psdu_bits, frame_len, frame, want = _setup()
    got = np_rx_decode(frame, rate, n_sym, n_psdu_bits)
    assert np.array_equal(got, want), "baseline decode mismatch"

    sps_runs, vit_runs = [], []
    from ziria_tpu.runtime.native_lib import load, viterbi_decode_native
    have_native = load() is not None
    nb = n_psdu_bits + 16 + 6
    dep = np.random.default_rng(2).normal(size=(nb, 2)).astype(np.float32)
    for i in range(n_runs):
        t_np = _time(np_rx_decode, frame, rate, n_sym, n_psdu_bits,
                     reps=3, fence=lambda o: None)
        sps_runs.append(frame_len / t_np)
        if have_native:
            t_v = _time(viterbi_decode_native, dep, reps=5,
                        fence=lambda o: None)
            vit_runs.append(nb / t_v / 1e6)
        print(f"[pin-baseline] run {i + 1}/{n_runs}: "
              f"{sps_runs[-1] / 1e6:.2f} M sps"
              + (f", viterbi {vit_runs[-1]:.1f} Mb/s"
                 if vit_runs else ""), file=sys.stderr, flush=True)
        time.sleep(1)

    # historical observations are REPORTED CONTEXT ONLY, never
    # denominator inputs (ADVICE r5 #3): folding every committed
    # BENCH_r0*.json into the max made the pin a one-way upward
    # ratchet — a single noisy-high historical point permanently
    # deflated all future chip multiples and no re-pin could revise it
    # down. The pin now comes from THIS pin's controlled runs alone.
    import glob
    hist = {}
    for p in sorted(glob.glob(os.path.join(REPO, "BENCH_r0*.json"))):
        try:
            with open(p) as f:
                j = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        j = (j.get("parsed") or j) if isinstance(j, dict) else {}
        for node in (j, j.get("last_good") or {}):
            v = node.get("numpy_baseline_sps")
            if v:
                hist[os.path.basename(p)] = max(
                    hist.get(os.path.basename(p), 0.0), float(v))

    # trimmed max of the current runs: with >= 4 runs the single
    # highest observation is dropped before taking the max, so one
    # spurious timer glitch cannot set the denominator; below that
    # there is no headroom to trim and the plain max stands
    srt = sorted(sps_runs)
    trimmed = srt[:-1] if n_runs >= 4 else srt
    pin = {
        "sps": round(max(trimmed), 1),
        "sps_max_this_pin": round(max(sps_runs), 1),
        "sps_historical": {k: round(v, 1) for k, v in hist.items()},
        "sps_median": round(float(np.median(sps_runs)), 1),
        "sps_runs": [round(s, 1) for s in sps_runs],
        "viterbi_c_simd_mbps": (round(max(vit_runs), 2)
                                if vit_runs else None),
        "n_runs": n_runs,
        "pinned_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "recipe": ("python bench.py --pin-baseline: numpy RX chain + C "
                   "AVX2 Viterbi, 1000-byte 54 Mbps frame, N runs of "
                   "_time(reps=3); pinned value = TRIMMED MAX over "
                   "these controlled runs only (top run dropped when "
                   "N >= 4 — one timer glitch must not set the "
                   "denominator); committed BENCH_r0*.json "
                   "observations are recorded as sps_historical "
                   "context and do NOT enter the denominator, so a "
                   "legitimate re-pin can revise it in either "
                   "direction (ADVICE r5 #3)"),
        "spread_pct": round(100 * (max(sps_runs) - min(sps_runs))
                            / float(np.median(sps_runs)), 1),
    }
    try:
        with open(BASELINE_PATH) as f:
            base = json.load(f)
    except (OSError, json.JSONDecodeError):
        base = {}
    base["pinned_baseline"] = pin
    tmp = BASELINE_PATH + ".pin.tmp"
    with open(tmp, "w") as f:
        json.dump(base, f, indent=2)
        f.write("\n")
    os.replace(tmp, BASELINE_PATH)
    print(json.dumps(pin))


# -------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-id", default=None)
    ap.add_argument("--pin-baseline", nargs="?", const=7, type=int,
                    default=None, metavar="N",
                    help="measure the CPU baseline N times and pin the "
                         "trimmed max into BASELINE.json")
    args = ap.parse_args()

    if args.pin_baseline is not None:
        _pin_baseline_main(max(3, args.pin_baseline))
        return
    # one process: the stages run here, and exit non-zero (3) when
    # JAX finds no accelerator
    _child_main(args.run_id or f"r{int(time.time())}")


if __name__ == "__main__":
    main()
