"""CLI driver: the reference's params.c + driver.c, re-designed.

The reference's compiled executables all share one CLI
(`csrc/params.c`, SURVEY.md §2.2): ``--input=file --input-file-name=X
--input-file-mode=dbg|bin --output=...``. This driver keeps that flag
surface (so reference muscle-memory transfers) and adds the compiler
flags that in the reference live on `wplc` (`src/Opts.hs`): backend
selection (``--backend=interp|jit`` — the codegen-backend switch the
north star pins), vectorization width, ``--fold``/``--autolut``, and
pass-dump flags.

The program to run is a named pipeline from the registry
(``--prog=NAME``; `--list-progs` enumerates) — the analogue of picking
a compiled .blk executable. A textual frontend (.zir source via
``--src``) plugs in here when the parser lands.

Example:

    python -m ziria_tpu --prog=wifi_tx_sym_6 \
        --input=file --input-file-name=bits.dbg --input-file-mode=dbg \
        --input-type=bit \
        --output=file --output-file-name=out.bin --output-file-mode=bin \
        --output-type=complex16 --backend=jit
"""

from __future__ import annotations

# ziria: lint-ignore-file[R4] this module OWNS the scoped-env pattern:
# its flag writes are paired with the finally-restore in main(), and its
# reads mirror argparse defaults for the same invocation-scoped knobs
import argparse
import os
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np

from ziria_tpu.runtime.buffers import ITEM_TYPES, StreamSpec, read_stream, \
    write_stream


# --------------------------------------------------------------------------
# Program registry
# --------------------------------------------------------------------------


def _prog_fir():
    """BASELINE config #1: FIR low-pass over a scalar float stream."""
    import jax.numpy as jnp
    import ziria_tpu as z

    taps = np.array([0.0625, 0.25, 0.375, 0.25, 0.0625], np.float32)

    def fir_step(state, x):
        state = jnp.roll(state, 1).at[0].set(x)
        return state, (state * jnp.asarray(taps)).sum()

    return z.map_accum(fir_step, np.zeros(5, np.float32), name="fir5")


def _prog_fft64():
    """BASELINE config #2: 64-point FFT blocks over complex16 pairs."""
    import jax.numpy as jnp
    import ziria_tpu as z
    from ziria_tpu.ops import cplx

    def fft_block(v):
        return cplx.fft_pair(jnp.asarray(v, jnp.float32))

    return z.zmap(fft_block, in_arity=64, out_arity=64, name="fft64")


def _prog_ifft64():
    import jax.numpy as jnp
    import ziria_tpu as z
    from ziria_tpu.ops import cplx

    def ifft_block(v):
        return cplx.ifft_pair(jnp.asarray(v, jnp.float32))

    return z.zmap(ifft_block, in_arity=64, out_arity=64, name="ifft64")


def _prog_scramble():
    """802.11 LFSR scrambler over a bit stream (default seed)."""
    import jax.numpy as jnp
    import ziria_tpu as z
    from ziria_tpu.ops import scramble
    from ziria_tpu.phy.wifi.tx import DEFAULT_SCRAMBLER_SEED, _seed_bits_np

    seq_np = scramble.np_lfsr_sequence_127(
        _seed_bits_np(DEFAULT_SCRAMBLER_SEED))

    def step(phase, b):
        out = jnp.asarray(b, jnp.uint8) ^ jnp.asarray(seq_np)[phase % 127]
        return phase + 1, out

    return z.map_accum(step, 0, name="scramble")


def _wifi_tx_sym(rate_mbps: int):
    def build():
        from ziria_tpu.phy.wifi.tx import tx_symbol_pipeline
        return tx_symbol_pipeline(rate_mbps)
    return build


PROGS: Dict[str, Callable] = {
    "fir": _prog_fir,
    "fft64": _prog_fft64,
    "ifft64": _prog_ifft64,
    "scramble": _prog_scramble,
}
for _r in (6, 9, 12, 18, 24, 36, 48, 54):
    PROGS[f"wifi_tx_sym_{_r}"] = _wifi_tx_sym(_r)


# --------------------------------------------------------------------------
# Arg parsing (reference params.c flag names)
# --------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ziria_tpu",
        description="TPU-native stream pipeline driver "
                    "(reference-style params)",
        epilog="subcommands: `python -m ziria_tpu lint [paths...]` runs "
               "the jaxlint static analysis (pure AST, no jax import; "
               "docs/static_analysis.md); `python -m ziria_tpu programs "
               "[--json] [--hlo-dump DIR]` runs the compiled-program "
               "observatory (CPU-pinned XLA cost/memory attribution; "
               "docs/observability.md); `python -m ziria_tpu serve "
               "[--sessions N] [--chaos SPEC]` runs the "
               "continuous-batching serving demo (docs/serving.md)")
    p.add_argument("--prog", help="registered pipeline name")
    p.add_argument("--src", help="Ziria-like source file (.zir) to compile")
    p.add_argument("--list-progs", action="store_true")

    # `memory` streams are the programmatic API (StreamSpec(data=...));
    # argv has no way to carry an array, so the CLI offers file|dummy only
    p.add_argument("--input", default="file", choices=["file", "dummy"])
    p.add_argument("--input-file-name")
    p.add_argument("--input-file-mode", default="dbg",
                   choices=["dbg", "bin"])
    p.add_argument("--input-type", default=None, choices=ITEM_TYPES,
                   help="item type (default: from the program's read[t], "
                        "else int32)")
    p.add_argument("--dummy-samples", type=int, default=0)

    p.add_argument("--output", default="file", choices=["file", "dummy"])
    p.add_argument("--output-file-name")
    p.add_argument("--output-file-mode", default="dbg",
                   choices=["dbg", "bin"])
    p.add_argument("--output-type", default=None, choices=ITEM_TYPES,
                   help="item type (default: from the program's write[t], "
                        "else int32)")

    p.add_argument("--scan", action="store_true",
                   help="treat the input as one LONG capture: find "
                        "every packet (sp-sharded STS metric when "
                        "--sp=N is given) and decode them all as one "
                        "frame batch through the in-language receiver "
                        "(phy/search.scan_and_decode); the output "
                        "stream is the concatenated validated "
                        "payloads, packet starts print with --verbose")
    p.add_argument("--batch-input-files", metavar="F1,F2,...",
                   help="decode N independent input streams in ONE "
                        "process, batching the compiled program's "
                        "device steps across them (backend/framebatch; "
                        "implies --backend=hybrid); pairs with "
                        "--batch-output-files")
    p.add_argument("--batch-output-files", metavar="F1,F2,...",
                   help="per-stream output files for "
                        "--batch-input-files (same count)")

    p.add_argument("--backend", default="jit",
                   choices=["interp", "jit", "hybrid"])
    p.add_argument("--width", type=int, default=None,
                   help="vectorization width (default: planner)")
    p.add_argument("--sp", type=int, default=None, metavar="N",
                   help="split the stream over N devices (sequence "
                        "parallelism; jit backend, stateless or "
                        "fast-forwardable pipelines)")
    p.add_argument("--pp", type=int, default=None, metavar="N",
                   help="auto-pipeline the stages across N devices "
                        "(balanced |>>>| placement decided by the "
                        "compiler; jit backend)")
    p.add_argument("--pp-costs", choices=("proxy", "measured"),
                   default="proxy",
                   help="stage-cost model for --pp placement: 'proxy' "
                        "(items moved per steady-state iteration) or "
                        "'measured' (time each stage on a sample of "
                        "the real input before deciding)")
    p.add_argument("--fold", action="store_true", default=True)
    p.add_argument("--no-fold", dest="fold", action="store_false")
    p.add_argument("--autolut", action="store_true")
    p.add_argument("--fxp-complex16", action="store_true",
                   help="int16 fixed-point complex16 policy: stream "
                        "items and arithmetic are integer IQ pairs "
                        "with C shorts semantics (wrap at store); "
                        "f32 is retained only inside explicitly "
                        "complex-typed ext calls such as v_fft")
    p.add_argument("--ddump-fold", action="store_true",
                   help="dump the IR after folding")
    p.add_argument("--ddump-vect", action="store_true",
                   help="dump the vectorizer's scored candidate table")
    p.add_argument("--ddump-hybrid", action="store_true",
                   help="dump the hybrid executor's per-do-block "
                        "decisions (weight, jit/effects/below-threshold)")
    p.add_argument("--stats", action="store_true",
                   help="print the fused plan: per-stage firing counts, "
                        "rates, width (jit backend)")
    p.add_argument("--profile", action="store_true",
                   help="per-stage wall time + item counts: each top-"
                        "level pipeline stage runs separately (warm-up "
                        "+ timed pass); totals differ from the fused run")
    p.add_argument("--profile-trace", metavar="DIR",
                   help="write a jax.profiler trace of the run to DIR "
                        "(view with TensorBoard / xprof)")
    p.add_argument("--trace", metavar="PATH",
                   help="write a Chrome trace-event JSON of this "
                        "invocation's instrumented host spans — every "
                        "dispatch site, gauge counter track, and "
                        "compile event — to PATH "
                        "(utils/telemetry; load in Perfetto / "
                        "chrome://tracing); also via ZIRIA_TRACE")
    p.add_argument("--metrics-dump", action="store_true",
                   help="print a Prometheus-style text exposition of "
                        "the invocation's metrics registry — dispatch "
                        "counters, per-site latency histograms "
                        "(power-of-two buckets, p50/p99 bounds), "
                        "gauges — to stderr at exit (utils/telemetry; "
                        "docs/observability.md)")
    p.add_argument("--chaos", metavar="SPEC",
                   help="run this invocation under a seeded fault-"
                        "injection plan (utils/faults; "
                        "docs/robustness.md): semicolon-separated "
                        "'[seed=N;]site:kind[:key=val,...]' specs — "
                        "kinds nan_slab/truncate (push seams), "
                        "transient/fatal/delay/hang (dispatch "
                        "seams); selectors every=N / calls=i+j / "
                        "p=F; deterministic by (site, seed, "
                        "call-index) so every chaos run replays "
                        "exactly. Also via ZIRIA_CHAOS")
    p.add_argument("--max-retries", type=int, default=None,
                   metavar="N",
                   help="transient-failure retry budget of every "
                        "guarded dispatch site (runtime/resilience "
                        "guarded dispatch: watchdog + exponential "
                        "backoff with deterministic jitter; default "
                        "2). Also via ZIRIA_MAX_RETRIES")
    p.add_argument("--channel-profile", metavar="NAME[,NAME...]",
                   help="default physical-channel profile of the "
                        "stimulus surfaces (phy/profiles; "
                        "docs/robustness.md): named multipath / "
                        "sampling-clock-offset / Doppler-drift / "
                        "interference-burst parameter sets — flat, "
                        "mild, urban, severe, sco, doppler, bursty, "
                        "hostile — applied as vmapped per-lane taps "
                        "inside the existing channel dispatches "
                        "('flat' IS the unprofiled channel, bit-"
                        "identical by construction; a comma list "
                        "assigns per lane/stream, cycling). Also via "
                        "ZIRIA_CHANNEL_PROFILE")
    p.add_argument("--rx-sco-track", dest="rx_sco_track",
                   action="store_true", default=None,
                   help="pilot phase-RAMP tracking in the RX DATA "
                        "decode (the sampling-clock-offset hardening; "
                        "docs/robustness.md). Default off — the flat-"
                        "channel decode is pinned bit-identical and "
                        "a fitted slope is never exactly zero. Also "
                        "via ZIRIA_RX_SCO_TRACK=1")
    p.add_argument("--no-rx-sco-track", dest="rx_sco_track",
                   action="store_false",
                   help="force SCO tracking off (overrides an "
                        "exported ZIRIA_RX_SCO_TRACK=1)")
    p.add_argument("--state-in",
                   help="resume stream state from this checkpoint "
                        "(runtime/state.py; jit backend)")
    p.add_argument("--state-out",
                   help="write final stream state to this checkpoint")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--platform", default=None,
                   help="pin the JAX platform (e.g. cpu, tpu) before "
                        "backend init (jax_platforms)")
    p.add_argument("--viterbi-window", type=int, default=None,
                   metavar="N",
                   help="decode every staged viterbi_soft ext with the "
                        "sliding-window PARALLEL Pallas Viterbi "
                        "(window N, e.g. 1024): ~T/N less sequential "
                        "trellis depth on chip, same result at "
                        "operating SNR; also via ZIRIA_VITERBI_WINDOW")
    # choices mirror ops.viterbi.METRIC_DTYPES (asserted by
    # tests/test_viterbi_int16.py::test_cli_choices_mirror_metric_dtypes)
    # — not imported here so --help stays cheap
    p.add_argument("--viterbi-metric", default=None,
                   choices=["float32", "int16", "int8"],
                   help="path-metric dtype for every staged "
                        "viterbi_soft ext: int16 runs the quantized "
                        "saturating-metric Pallas kernel (the SORA "
                        "trade — half the LLR stream and metric "
                        "footprint; docs/quantized_viterbi.md), int8 "
                        "the 4-bit-soft LUT-branch-metric kernel "
                        "below it (half the resident metric state "
                        "again; BER-envelope accuracy, not bit "
                        "identity), float32 the exact oracle "
                        "(default); also via ZIRIA_VITERBI_METRIC")
    # choices mirror ops.viterbi.RADIXES (same pinned-mirror rule)
    p.add_argument("--viterbi-radix", type=int, default=None,
                   choices=[2, 4],
                   help="trellis steps per Pallas ACS iteration for "
                        "every staged viterbi_soft ext and library "
                        "decode surface: 4 collapses butterfly pairs "
                        "into one 4-way compare — half the sequential "
                        "dependency chain of the decode core's "
                        "hottest kernel, bit-identical to 2 (the "
                        "default/oracle) at float32 and int16; also "
                        "via ZIRIA_VITERBI_RADIX")
    p.add_argument("--fused-demap", dest="fused_demap",
                   action="store_true", default=None,
                   help="run demap + deinterleave + depuncture as an "
                        "in-kernel prologue of the Pallas Viterbi on "
                        "the known-rate DATA decodes (receive / "
                        "decode_data_batch): LLRs are produced and "
                        "consumed in VMEM and never round-trip HBM "
                        "between the front end and the ACS "
                        "(docs/architecture.md decode-roofline "
                        "section; the mixed-rate switch decode keeps "
                        "the XLA front end). Also via "
                        "ZIRIA_FUSED_DEMAP=1")
    p.add_argument("--no-fused-demap", dest="fused_demap",
                   action="store_false",
                   help="force the XLA front end (the fused "
                        "prologue's bit-identical oracle; the "
                        "default); also via ZIRIA_FUSED_DEMAP=0")
    p.add_argument("--batched-acquire", dest="batched_acquire",
                   action="store_true", default=None,
                   help="one-dispatch batched acquisition for the "
                        "frame-batched library receiver "
                        "(framebatch.receive_many): detect + align + "
                        "CFO + SIGNAL parse for ALL captures as ONE "
                        "vmapped device call, then gather+derotate "
                        "and the mixed-rate decode — O(1) dispatches "
                        "per batch instead of ~3 per capture (the "
                        "default; docs/architecture.md). Also via "
                        "ZIRIA_BATCHED_ACQUIRE=1")
    p.add_argument("--no-batched-acquire", dest="batched_acquire",
                   action="store_false",
                   help="force the host-driven per-capture "
                        "acquisition loop (the batched path's "
                        "bit-identical oracle); also via "
                        "ZIRIA_BATCHED_ACQUIRE=0")
    p.add_argument("--batched-tx", dest="batched_tx",
                   action="store_true", default=None,
                   help="one-dispatch batched TX for the frame-batch "
                        "surfaces (tx.encode_many / link.loopback_many "
                        "/ framebatch.transmit_many): an N-frame "
                        "mixed-rate, mixed-length batch encodes as "
                        "ONE vmapped lax.switch device call, and the "
                        "loopback link runs TX->channel->RX in ~5 "
                        "dispatches total (the default; "
                        "docs/architecture.md). Also via "
                        "ZIRIA_BATCHED_TX=1")
    p.add_argument("--no-batched-tx", dest="batched_tx",
                   action="store_false",
                   help="force the per-frame encode/loopback loop "
                        "(the batched TX path's bit-identical "
                        "oracle); also via ZIRIA_BATCHED_TX=0")
    p.add_argument("--streaming-rx", dest="streaming_rx",
                   action="store_true", default=None,
                   help="chunked one-dispatch streaming receiver for "
                        "the library stream surface "
                        "(framebatch.receive_stream): a long multi-"
                        "frame capture is scanned in fixed overlapping "
                        "chunks, each chunk costing <= 2 device "
                        "dispatches (multi-peak detect + align + "
                        "acquire + gather fused, then one mixed-rate "
                        "decode), with the host<->device transfer "
                        "double-buffered behind compute (the default; "
                        "docs/architecture.md). Also via "
                        "ZIRIA_STREAMING_RX=1")
    p.add_argument("--no-streaming-rx", dest="streaming_rx",
                   action="store_false",
                   help="force the per-capture oracle over the same "
                        "detected windows (>= 3 dispatches per frame "
                        "— the streaming path's bit-identical "
                        "contract); also via ZIRIA_STREAMING_RX=0")
    p.add_argument("--fused-link", dest="fused_link",
                   action="store_true", default=None,
                   help="ONE-dispatch fused loopback link "
                        "(phy/link.loopback_many): the whole "
                        "TX -> channel -> acquire -> classify -> "
                        "gather -> mixed decode -> batched-CRC chain "
                        "as a single jitted device program — the "
                        "acquisition decision tree traced on-device, "
                        "1 dispatch per N-frame all-rates multi-SNR "
                        "batch (the default; docs/architecture.md). "
                        "Also via ZIRIA_FUSED_LINK=1")
    p.add_argument("--no-fused-link", dest="fused_link",
                   action="store_false",
                   help="force the staged ~5-dispatch loopback "
                        "(encode_many + impair_many + acquire/gather/"
                        "decode — the fused graph's bit-identical "
                        "oracle); also via ZIRIA_FUSED_LINK=0")
    return p


def _resolve_prog(args):
    """Returns (comp, default_in_ty, default_out_ty)."""
    if args.src:
        from ziria_tpu.frontend import compile_file
        prog = compile_file(args.src,
                            fxp_complex16=args.fxp_complex16,
                            autolut=args.autolut)
        return prog.comp, prog.in_ty, prog.out_ty
    if not args.prog:
        raise SystemExit("need --prog=NAME or --src=FILE "
                         "(--list-progs to enumerate)")
    if args.prog not in PROGS:
        raise SystemExit(
            f"unknown prog {args.prog!r}; known: {', '.join(sorted(PROGS))}")
    return PROGS[args.prog](), None, None


def _run_profiled(comp, xs, args):
    """Per-stage observability (SURVEY.md §5 tracing row): run each
    top-level pipeline stage separately — one warm-up pass (compile),
    one timed pass — reporting wall time and item counts per stage.
    Stages are composition-independent (their state is internal), so
    the final output equals the fused run's; only the *timing* loses
    cross-stage fusion, which is the point of a per-stage breakdown."""
    from ziria_tpu.core.ir import pipeline_stages

    stages = list(pipeline_stages(comp))
    rows = []
    cur = np.asarray(xs)
    for st in stages:
        if args.backend == "interp":
            from ziria_tpu.interp.interp import run

            def go(_st=st, _cur=cur):
                return np.asarray(run(_st, list(_cur)).out_array())
        else:
            # jit when the stage lowers, hybrid otherwise — the shared
            # stage-timing discipline (autosplit.stage_runner, also
            # behind --pp-costs=measured)
            from ziria_tpu.parallel.autosplit import stage_runner
            go = stage_runner(st, cur, width=args.width)

        go()                                   # warm-up / compile
        t0 = time.perf_counter()
        out = go()
        dt = time.perf_counter() - t0
        rows.append((st.label(), cur.shape[0], out.shape[0], dt))
        cur = out

    total = sum(r[3] for r in rows) or 1e-12
    print(f"profile: {len(rows)} stage(s), backend={args.backend} "
          f"(stages timed unfused)", file=sys.stderr)
    for lbl, n_in, n_out, dt in rows:
        print(f"  stage {lbl:<28s} {n_in:>8d} -> {n_out:>8d} items  "
              f"{dt * 1e3:>9.3f} ms  {100 * dt / total:>5.1f}%  "
              f"({n_in / max(dt, 1e-12):,.0f} items/s)", file=sys.stderr)
    return cur


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "lint":
        # jaxlint subcommand: pure-AST static analysis of the jit
        # disciplines (docs/static_analysis.md). Dispatched BEFORE
        # argparse and without touching jax, so the gate needs no
        # backend at all.
        from ziria_tpu.analysis.__main__ import main as lint_main
        return lint_main(argv[1:])
    if argv and argv[0] == "programs":
        # compiled-program observatory subcommand: XLA cost/memory
        # attribution per jit factory. Dispatched BEFORE argparse,
        # mirroring `lint`; the observatory pins the CPU backend
        # itself, so cost attribution needs no chip.
        from ziria_tpu.utils.programs import main as programs_main
        return programs_main(argv[1:])
    if argv and argv[0] == "serve":
        # continuous-batching serving demo (runtime/serve,
        # docs/serving.md): synthetic many-client load through the
        # real fleet, SIGINT-safe drain + final stats/exposition,
        # chaos-injectable. Own arg surface, dispatched BEFORE
        # argparse like `lint`/`programs`.
        from ziria_tpu.runtime.serve import main as serve_main
        return serve_main(argv[1:])
    args = build_parser().parse_args(argv)
    if args.list_progs:
        for name in sorted(PROGS):
            print(name)
        return 0
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    from ziria_tpu.utils import compile_cache
    compile_cache.place()

    # the staged viterbi_soft ext reads the env pair at trace time
    # (frontend/externals.viterbi_mode, folded into the backend's
    # compile cache keys); scope the writes to this invocation so
    # in-process callers (tests, embedders) never inherit them, and
    # let --viterbi-window=0 / --viterbi-metric=float32 force-disable
    # an exported env value (review r5)
    overrides = {}
    if args.viterbi_window is not None:
        overrides["ZIRIA_VITERBI_WINDOW"] = str(args.viterbi_window)
    if args.viterbi_metric is not None:
        overrides["ZIRIA_VITERBI_METRIC"] = args.viterbi_metric
    if args.viterbi_radix is not None:
        # --viterbi-radix=2 force-disables an exported env value, the
        # same force-off semantics as --viterbi-metric=float32
        overrides["ZIRIA_VITERBI_RADIX"] = str(args.viterbi_radix)
    if args.fused_demap is not None:
        overrides["ZIRIA_FUSED_DEMAP"] = \
            "1" if args.fused_demap else "0"
    if args.batched_acquire is not None:
        # receive_many reads this at call time; scoping the write
        # keeps in-process callers from inheriting the flag, same as
        # the viterbi pair above
        overrides["ZIRIA_BATCHED_ACQUIRE"] = \
            "1" if args.batched_acquire else "0"
    if args.batched_tx is not None:
        # link.batched_tx_enabled reads this at call time (the TX
        # twin of the batched-acquire knob)
        overrides["ZIRIA_BATCHED_TX"] = \
            "1" if args.batched_tx else "0"
    if args.fused_link is not None:
        # link.fused_link_enabled reads this at call time (the
        # one-dispatch loopback vs its staged 5-dispatch oracle)
        overrides["ZIRIA_FUSED_LINK"] = \
            "1" if args.fused_link else "0"
    if args.streaming_rx is not None:
        # framebatch.streaming_rx_enabled reads this at call time
        # (the chunked streaming receiver vs its per-capture oracle)
        overrides["ZIRIA_STREAMING_RX"] = \
            "1" if args.streaming_rx else "0"
    if args.chaos is not None:
        # faults.env_chaos reads this inside _main_run's shell; the
        # scoped write keeps in-process callers from inheriting a
        # fault plan, same as every knob above. Validate NOW so a
        # malformed spec is a flag error, not a traceback from deep
        # inside the run (parse_chaos_spec self-validates kinds and
        # selectors)
        from ziria_tpu.utils import faults as _faults
        try:
            _faults.parse_chaos_spec(args.chaos)
        except ValueError as e:
            raise SystemExit(f"--chaos: {e}")
        overrides["ZIRIA_CHAOS"] = args.chaos
    if args.max_retries is not None:
        # resilience.env_max_retries reads this at guarded-site
        # policy resolution time
        if args.max_retries < 0:
            raise SystemExit(
                f"--max-retries: {args.max_retries} must be >= 0")
        overrides["ZIRIA_MAX_RETRIES"] = str(args.max_retries)
    if args.channel_profile is not None:
        # profiles.env_channel_profile reads this at the stimulus
        # surfaces (link.stream_many[_multi], loopback_many). Validate
        # NOW so an unknown profile is a flag error naming the known
        # registry, not a traceback from deep inside the run
        from ziria_tpu.phy import profiles as _profiles
        try:
            _profiles.parse_profile_spec(args.channel_profile)
        except ValueError as e:
            raise SystemExit(f"--channel-profile: {e}")
        overrides["ZIRIA_CHANNEL_PROFILE"] = args.channel_profile
    if args.rx_sco_track is not None:
        # rx.sco_track_enabled reads this at decode-surface entry
        # (resolved once, part of every decode factory's cache key);
        # --no-rx-sco-track force-disables an exported env value
        overrides["ZIRIA_RX_SCO_TRACK"] = \
            "1" if args.rx_sco_track else "0"
    if args.trace:
        # telemetry.env_trace_path reads this inside _main_run; the
        # scoped write keeps in-process callers from inheriting an
        # always-on trace, same as every knob above
        overrides["ZIRIA_TRACE"] = args.trace
    if not overrides:
        return _main_run(args)
    prev = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        return _main_run(args)
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _main_run(args) -> int:
    """The telemetry shell around every command path: when --trace /
    ZIRIA_TRACE names a path, the whole run is recorded as a Chrome
    trace and exported there (even on failure — a crashed run's trace
    is the one you want most); --metrics-dump collects the run's
    metrics registry and prints its Prometheus-style exposition to
    stderr at exit."""
    from ziria_tpu.utils import faults, telemetry

    tpath = telemetry.env_trace_path()
    try:
        chaos = faults.env_chaos()
    except ValueError as e:
        # a directly-exported malformed ZIRIA_CHAOS must be a clean
        # error, never a silent no-chaos run or a raw traceback
        raise SystemExit(f"ZIRIA_CHAOS: {e}")
    if not tpath and not args.metrics_dump and chaos is None:
        return _run_cmd(args)
    import contextlib
    reg = None
    try:
        with contextlib.ExitStack() as stack:
            if tpath:
                stack.enter_context(telemetry.tracing(tpath))
            if args.metrics_dump:
                reg = stack.enter_context(telemetry.collect())
            if chaos is not None:
                # the whole invocation runs under the described fault
                # plan (utils/faults; --chaos / ZIRIA_CHAOS)
                specs, seed = chaos
                stack.enter_context(faults.inject(*specs, seed=seed))
            return _run_cmd(args)
    finally:
        # the crashed run's telemetry is the telemetry you want most:
        # tracing() exports in its own finally, and the exposition /
        # hint print here so ^C or a failing command still reports
        if tpath:
            print(f"telemetry trace written to {tpath}",
                  file=sys.stderr)
        if reg is not None:
            print("metrics exposition (utils/telemetry):",
                  file=sys.stderr)
            print(reg.exposition(), file=sys.stderr, end="")


def _run_cmd(args) -> int:
    if args.scan:
        return _run_scan(args)

    comp, src_in_ty, src_out_ty = _resolve_prog(args)
    in_ty = args.input_type or src_in_ty or "int32"
    out_ty = args.output_type or src_out_ty or "int32"

    pre_read = None      # input parsed early by --pp-costs=measured
    # autolut first: fold's map-map fusion erases in_domain declarations,
    # so the LUT rewrite must see the maps before they fuse
    if args.autolut:
        from ziria_tpu.core.autolut import autolut
        comp = autolut(comp)
    if args.pp is not None and args.pp >= 1:
        # decide |>>>| placement BEFORE folding: fold fuses across >>>
        # (collapsing the stages we want to distribute) but respects
        # ParPipe boundaries, so each decided segment still fuses
        # internally. --pp=1 also goes through the pass: any existing
        # |>>>| annotations are flattened onto the single device
        from ziria_tpu.parallel.autosplit import (AutoSplitError,
                                                  auto_pipeline)
        sample = None
        if args.pp_costs == "measured":
            # validate flag compatibility BEFORE spending seconds of
            # per-stage sampling that _run_backend would reject anyway
            if args.backend != "jit" or args.profile:
                raise SystemExit("--pp needs --backend=jit and cannot "
                                 "combine with --profile")
            # time each stage on (a slice of) the real input instead
            # of the items-moved proxy; the full array is kept so the
            # run below does not parse the file a second time
            spec = StreamSpec(kind=args.input, ty=in_ty,
                              path=args.input_file_name,
                              mode=args.input_file_mode,
                              dummy_items=args.dummy_samples)
            pre_read = read_stream(spec)
            if pre_read.shape[0] == 0:
                raise SystemExit("--pp-costs=measured: input sample is "
                                 "empty (nothing to time)")
            sample = pre_read[: 1 << 15]
        try:
            comp = auto_pipeline(comp, args.pp, sample=sample,
                                 width=args.width or 1)
        except AutoSplitError as e:
            raise SystemExit(f"--pp={args.pp}: {e}")
    if args.fold:
        from ziria_tpu.core.opt import fold
        comp = fold(comp)
    if args.ddump_fold:
        print(comp, file=sys.stderr)
    if args.ddump_vect:
        from ziria_tpu.core.vectorize import vectorize
        print(vectorize(comp).dump(), file=sys.stderr)
    if args.ddump_hybrid:
        from ziria_tpu.backend.hybrid import hybridize
        print("hybrid plan:", file=sys.stderr)
        hybridize(comp, dump=lambda s: print(s, file=sys.stderr))

    if args.batch_input_files or args.batch_output_files:
        return _run_batch_files(comp, args, in_ty, out_ty)

    in_spec = StreamSpec(kind=args.input, ty=in_ty,
                         path=args.input_file_name,
                         mode=args.input_file_mode,
                         dummy_items=args.dummy_samples)
    out_spec = StreamSpec(kind=args.output, ty=out_ty,
                          path=args.output_file_name,
                          mode=args.output_file_mode)

    if args.profile and (args.state_in or args.state_out):
        raise SystemExit("--profile runs stages separately and "
                         "cannot combine with --state-in/--state-out")
    xs = pre_read if pre_read is not None else read_stream(in_spec)
    tracing = False
    if args.profile_trace:
        import jax
        jax.profiler.start_trace(args.profile_trace)
        tracing = True
    t0 = time.perf_counter()
    try:
        ys, dt = _run_backend(comp, xs, args, t0)
    finally:
        if tracing:
            import jax
            jax.profiler.stop_trace()
            print(f"profiler trace written to {args.profile_trace}",
                  file=sys.stderr)

    write_stream(out_spec, ys)
    if args.verbose:
        print(f"items in: {xs.shape[0]}, items out: {ys.shape[0]}, "
              f"time: {dt:.4f}s "
              f"({xs.shape[0] / max(dt, 1e-12):,.0f} items/s)",
              file=sys.stderr)
    return 0


def _seq_of(comp):
    """ParPipe pipeline -> plain Pipe of the same segments (the fused
    single-device equivalent, sharing carry structure stage-for-stage)."""
    from ziria_tpu.core import ir as _ir
    return _ir.pipe(*_ir.par_segments(comp))


def _run_auto_pp(comp, xs, args, t0):
    """--pp=N: compiler-decided stage placement across N devices (the
    reference's auto-pipelining pass, minus the hand-written |>>>|)."""
    import jax

    from ziria_tpu.backend.lower import LowerError
    from ziria_tpu.parallel.stages import lower_stage_parallel
    from ziria_tpu.parallel.streampar import (StreamParError,
                                              stream_mesh)

    if args.stats:
        print("note: --stats reports the fused single-device plan and "
              "is unavailable under --pp", file=sys.stderr)
    if args.width is not None and args.width < 1:
        raise SystemExit(f"--width={args.width}: must be >= 1")
    if args.width is None:
        print("note: --pp segments run at width 1; pass --width=W to "
              "vectorize each segment (widths multiply the macro "
              "chunk the input length must divide)", file=sys.stderr)
    try:
        mesh = stream_mesh(args.pp, axis="pp")
        # main() already decided the ParPipe placement (pre-fold)
        pp = lower_stage_parallel(
            comp, mesh, width=args.width if args.width else 1,
            in_item=jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype))
    except (LowerError, StreamParError) as e:
        raise SystemExit(f"--pp={args.pp}: {e}")
    m = xs.shape[0] // pp.take
    r = xs.shape[0] - m * pp.take
    if r == 0:
        ys = np.asarray(pp.run(xs.reshape((m, pp.take) + xs.shape[1:])))
        return (ys.reshape((m * pp.emit,) + ys.shape[2:]),
                time.perf_counter() - t0)
    # remainder path: the reference's queues had no length restriction
    # (SURVEY.md §2.2 TS queues). Run the whole macro chunks through
    # the pipeline, then continue the tail on the fused single-device
    # path seeded with the segments' exit carries — exact vs run_jit
    # for any length.
    from ziria_tpu.backend.execute import run_jit_carry
    seq = _seq_of(comp)
    outs = []
    carry = None
    if m:
        ys, carry = pp.run_carry(
            xs[: m * pp.take].reshape((m, pp.take) + xs.shape[1:]))
        ys = np.asarray(ys)
        outs.append(ys.reshape((m * pp.emit,) + ys.shape[2:]))
    tail, _ = run_jit_carry(seq, xs[m * pp.take:], carry=carry,
                            width=args.width)
    tail = np.asarray(tail)
    if tail.shape[0]:
        outs.append(tail)
    ys = (np.concatenate(outs, axis=0) if outs
          else np.empty((0,) + xs.shape[1:], xs.dtype))
    return ys, time.perf_counter() - t0


def _run_scan(args) -> int:
    """--scan: long-capture workflow — sp-shardable packet search +
    frame-batched decode of every hit (phy/search.scan_and_decode).
    The program is fixed (the in-language receiver); --src/--prog are
    rejected so a mismatch cannot pass silently."""
    if args.src or args.prog:
        raise SystemExit("--scan uses the in-language receiver; drop "
                         "--src/--prog")
    if args.profile or args.profile_trace or args.stats \
            or args.pp is not None or args.state_in \
            or args.state_out or args.batch_input_files \
            or args.batch_output_files:
        raise SystemExit("--scan cannot combine with --pp/--profile/"
                         "--profile-trace/--stats/--state-*/--batch-*")
    if args.input != "file" or not args.input_file_name:
        raise SystemExit("--scan needs --input=file with "
                         "--input-file-name (a complex16 capture)")
    if args.sp is not None and args.sp < 1:
        raise SystemExit(f"--sp={args.sp}: need at least 1 device")
    # fail on a bad output spec BEFORE the scan spends minutes
    out_spec = StreamSpec(kind=args.output, ty="bit",
                          path=args.output_file_name,
                          mode=args.output_file_mode)
    from ziria_tpu.parallel.streampar import StreamParError
    from ziria_tpu.phy.search import scan_and_decode

    xs = read_stream(StreamSpec(kind="file", ty="complex16",
                                path=args.input_file_name,
                                mode=args.input_file_mode))
    try:
        mesh = None
        if args.sp is not None:
            from ziria_tpu.parallel.streampar import stream_mesh
            mesh = stream_mesh(args.sp)
        t0 = time.perf_counter()
        hits = scan_and_decode(xs, mesh=mesh)
    except StreamParError as e:
        raise SystemExit(f"--sp={args.sp}: {e}")
    dt = time.perf_counter() - t0
    payload = (np.concatenate([b for _s, b in hits])
               if hits else np.empty((0,), np.uint8))
    write_stream(out_spec, payload)
    if args.verbose:
        print(f"scan: {xs.shape[0]} samples, {len(hits)} packet(s) "
              f"validated at {[s for s, _b in hits]}, "
              f"{payload.shape[0]} payload bits, time: {dt:.3f}s",
              file=sys.stderr)
    return 0


def _run_batch_files(comp, args, in_ty, out_ty) -> int:
    """--batch-input-files: N independent streams through one
    hybridized program, chunk-machine device steps batched across them
    (backend/framebatch.py) — the driver surface of frame batching.
    Each stream's output goes to the matching --batch-output-files
    entry, bit-identical to N separate runs."""
    if not (args.batch_input_files and args.batch_output_files):
        raise SystemExit("--batch-input-files and --batch-output-files "
                         "must be given together")
    ins = [f for f in args.batch_input_files.split(",") if f]
    outs = [f for f in args.batch_output_files.split(",") if f]
    if len(ins) != len(outs):
        raise SystemExit(
            f"--batch-*: {len(ins)} inputs but {len(outs)} outputs")
    if args.backend == "jit":
        args.backend = "hybrid"           # the documented implication
    if args.backend != "hybrid" or args.profile or args.profile_trace \
            or args.stats or args.sp is not None \
            or args.pp is not None or args.state_in or args.state_out:
        raise SystemExit("--batch-input-files runs the hybrid backend "
                         "and cannot combine with --sp/--pp/--profile/"
                         "--profile-trace/--stats/--state-*")

    from ziria_tpu.backend.framebatch import StepBatcher, run_many
    from ziria_tpu.backend.hybrid import hybridize

    frames = [read_stream(StreamSpec(kind="file", ty=in_ty, path=f,
                                     mode=args.input_file_mode))
              for f in ins]
    hyb = hybridize(comp)
    t0 = time.perf_counter()
    b = StepBatcher(len(frames))
    results = run_many(hyb, [list(x) for x in frames], batcher=b)
    dt = time.perf_counter() - t0
    for f, res in zip(outs, results):
        write_stream(StreamSpec(kind="file", ty=out_ty, path=f,
                                mode=args.output_file_mode),
                     np.asarray(res.out_array()))
    if args.verbose:
        n_in = sum(x.shape[0] for x in frames)
        n_out = sum(len(r.outputs) for r in results)
        print(f"batch: {len(frames)} streams, items in: {n_in}, "
              f"items out: {n_out}, device calls: {b.device_calls} "
              f"(group sizes {b.group_sizes}), time: {dt:.4f}s",
              file=sys.stderr)
    return 0


def _run_backend(comp, xs, args, t0):
    """Dispatch to --profile / interp / jit; returns (ys, seconds)."""
    if args.sp is not None:
        # validate up front so the flag can never be silently ignored
        if args.sp < 1:
            raise SystemExit(f"--sp={args.sp}: need at least 1 device")
        if args.backend != "jit" or args.profile:
            raise SystemExit("--sp needs --backend=jit (sequence "
                             "parallelism shards the fused pipeline) "
                             "and cannot combine with --profile")
    if args.pp is not None:
        if args.pp < 1:
            raise SystemExit(f"--pp={args.pp}: need at least 1 device")
        if args.backend != "jit" or args.profile or args.sp is not None \
                or args.state_in or args.state_out:
            raise SystemExit("--pp needs --backend=jit and cannot "
                             "combine with --sp/--profile/--state-*")
        return _run_auto_pp(comp, xs, args, t0)
    if args.profile:
        ys = _run_profiled(comp, xs, args)
        return ys, time.perf_counter() - t0
    if args.backend in ("interp", "hybrid"):
        if args.state_in or args.state_out:
            raise SystemExit("--state-in/--state-out need --backend=jit "
                             "(stream state is the jit carry pytree)")
        if args.backend == "hybrid":
            # interpreter-driven control, jit-compiled heavy do-blocks
            # (backend/hybrid.py) — for dynamic-control programs like
            # the flagship receiver that the fused jit path refuses
            from ziria_tpu.backend.hybrid import hybridize
            comp = hybridize(comp)
        from ziria_tpu.interp.interp import run
        res = run(comp, list(xs))
        ys = np.asarray(res.out_array())
    else:
        from ziria_tpu.backend.execute import lower, run_jit_carry
        from ziria_tpu.backend.lower import LowerError
        if args.sp is not None:
            if args.state_in or args.state_out:
                raise SystemExit("--sp cannot combine with "
                                 "--state-in/--state-out (the sharded "
                                 "run has no single carry)")
            from ziria_tpu.parallel.streampar import (StreamParError,
                                                      stream_mesh,
                                                      stream_parallel)
            if args.stats:
                print("note: --stats reports the single-device fused "
                      "plan and is unavailable under --sp",
                      file=sys.stderr)
            try:
                ys = stream_parallel(comp, xs, stream_mesh(args.sp),
                                     width=args.width)
            except (StreamParError, LowerError) as e:
                raise SystemExit(f"--sp={args.sp}: {e}")
            return np.asarray(ys), time.perf_counter() - t0
        stats: Optional[dict] = {} if args.stats else None
        try:
            carry = None
            if args.state_in:
                from ziria_tpu.runtime.state import (load_state,
                                                     program_fingerprint)
                carry = load_state(args.state_in,
                                   like=lower(comp, width=args.width)
                                   .init_carry,
                                   fingerprint=program_fingerprint(comp))
            ys, carry = run_jit_carry(comp, xs, carry=carry,
                                      width=args.width, stats_out=stats)
        except LowerError as e:
            # dynamic-control programs can't fuse; instead of refusing
            # (the reference's compiler compiles everything), fall back
            # to the hybrid executor — same results, control on the
            # host, heavy blocks still jit-compiled. (LowerError is
            # raised before any execution, so nothing ran twice.)
            if args.state_in or args.state_out:
                raise SystemExit(
                    f"--state-in/--state-out need a fusable pipeline "
                    f"({e})")
            print(f"note: program has dynamic control "
                  f"({e}); falling back to --backend=hybrid",
                  file=sys.stderr)
            if args.stats:
                print("note: --stats reports the fused plan and is "
                      "unavailable under the hybrid fallback "
                      "(try --ddump-hybrid)", file=sys.stderr)
            from ziria_tpu.backend.hybrid import hybridize
            from ziria_tpu.interp.interp import run
            res = run(hybridize(comp), list(xs))
            return (np.asarray(res.out_array()),
                    time.perf_counter() - t0)
        ys = np.asarray(ys)
        if args.state_out:
            from ziria_tpu.runtime.state import (program_fingerprint,
                                                 save_state)
            save_state(args.state_out, carry,
                       fingerprint=program_fingerprint(comp))
        if args.stats:
            # printed straight from the executor's own split arithmetic
            print(f"plan: width={stats['width']} take={stats['take']} "
                  f"emit={stats['emit']} "
                  f"bulk_steps={stats['bulk_steps']} "
                  f"remainder_iters={stats['remainder_iters']}",
                  file=sys.stderr)
            for lbl, reps in zip(stats["labels"], stats["reps"]):
                print(f"  stage {lbl:<28s} {reps:>6d} firings/iter "
                      f"({reps * stats['width']} per bulk step)",
                      file=sys.stderr)
    return ys, time.perf_counter() - t0


if __name__ == "__main__":
    sys.exit(main())
