"""Geometry autotuner: cost-pruned measured search over the tunables.

The search loop the ISSUE 16 tentpole adds on top of
:mod:`ziria_tpu.utils.geometry` — three stages, each riding machinery
an earlier PR already shipped:

1. **Enumerate** candidate geometries around the default
   (:func:`default_candidates`): the chunk-length ladder (halving
   raises the overlap fraction, doubling amortizes it) and the
   radix-4 Viterbi ACS (bit-identical to radix-2 at float32 by
   construction — ops/viterbi's pinned contract — so it is a legal
   identity-preserving candidate).
2. **Prune analytically** (:func:`stream_chunk_cost`): XLA's own
   ``cost_analysis`` for the candidate's chunk-scan program (the PR 9
   observatory's `programs.cost_of` — aval-lowered, no hardware, no
   data) normalized per OWNED stream sample. A candidate whose
   analytical bytes/flops per sample regress past the default never
   reaches a device: the halved chunk pays double the overlap
   fraction and dies here, by arithmetic instead of by stopwatch.
3. **Measure survivors** (:class:`Measurer`): the PR 7 telemetry
   harness on the two hot surfaces — the streaming receiver over a
   synthesized multi-frame stream (aggregate samples/s + per-chunk
   p50/p99 off the dispatch histograms) and the fused link (frames/s)
   — under the existing identity gates: a candidate's emissions must
   be bit-identical to the default's, field for field, or it is
   rejected no matter how fast it ran.

The winner (best streaming samples/s among identity-clean survivors;
the default itself competes) lands in the bench trajectory ledger
(``BENCH_TRAJECTORY.jsonl``, the ``BENCH_TRAJECTORY`` env override
honored via geometry's designated reader) as a ``stage="autotune"``
record keyed by ``device_kind`` — the record
:meth:`ziria_tpu.utils.geometry.Geometry.tuned` reconstructs, and
``tools/perf_report.py --check`` gates (device_kind-matched, so a v5e
winner never gates a CPU smoke). ``cost_fn`` / ``measure_fn`` are
injectable, so tests drive the whole pipeline deterministically with
fakes (tests/test_geometry.py).

Run it as ``python -m ziria_tpu autotune`` (pre-argparse dispatch,
like ``lint`` and ``programs``) or through bench.py's never-fatal
``autotune`` stage. docs/autotune.md walks the record format.
"""

from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ziria_tpu.utils.geometry import (Geometry, detect_device_kind,
                                      env_trajectory_path)

#: analytical slack: a candidate may cost up to this fraction MORE
#: bytes/flops per owned sample than the default before the prune
#: rejects it (keeps exact-cost rewrites like radix-4 alive through
#: cost-model noise)
PRUNE_SLACK = 0.02

Candidate = Tuple[str, Geometry]


# ------------------------------------------------------------ enumeration


def default_candidates(base: Geometry) -> List[Candidate]:
    """The search neighborhood around ``base`` (which must be
    resolved): the chunk-length ladder, the radix-4 ACS, the fused
    demap front end (now a MEASURED axis — the rate-switched fused
    mixed decode covers the streaming surfaces this harness times),
    and the joint ``chunk_len x fused_demap`` move (the fused
    decode's VMEM residency shifts the scan/decode balance, so the
    chunk length that wins unfused need not win fused). Every
    candidate keeps ``frame_len``/detector params fixed — those are
    part of the identity contract's geometry, not throughput
    tunables."""
    out: List[Candidate] = []
    for cl in (base.chunk_len // 2, base.chunk_len * 2,
               base.chunk_len * 4):
        if cl > base.frame_len:
            out.append((f"chunk{cl}", base.replace(chunk_len=cl)))
    if base.viterbi_radix != 4:
        out.append(("radix4", base.replace(viterbi_radix=4)))
    if not base.fused_demap:
        out.append(("fused_demap", base.replace(fused_demap=True)))
        cl2 = base.chunk_len * 2
        if cl2 > base.frame_len:
            out.append((f"chunk{cl2}_fused",
                        base.replace(chunk_len=cl2, fused_demap=True)))
    return out


# ---------------------------------------------------------- analytical cost


def stream_chunk_cost(geo: Geometry) -> Dict[str, float]:
    """Analytical cost of the candidate's chunk-scan program per OWNED
    stream sample (a chunk re-reads ``frame_len`` overlap samples, so
    the honest denominator is ``chunk_len - frame_len``). Pure
    lowering through the PR 9 observatory — no dispatch, no data, no
    accelerator required."""
    import jax

    from ziria_tpu.phy.wifi import rx as _rx
    from ziria_tpu.utils import programs

    n_sym_bucket = geo.sym_bucket(
        max(1, (geo.frame_len - _rx.FRAME_DATA_START) // 80))
    fn = _rx._jit_stream_chunk_multi(
        geo.max_frames_per_chunk, geo.frame_len, n_sym_bucket,
        float(geo.threshold), int(geo.min_run), int(geo.dead_zone))
    # a fleet of one: what `receive_stream` dispatches
    chunk = jax.ShapeDtypeStruct((1, geo.chunk_len, 2), np.float32)
    scalar = jax.ShapeDtypeStruct((1,), np.int32)
    c = programs.cost_of(fn, chunk, scalar, scalar, scalar)
    owned = geo.chunk_len - geo.frame_len
    return {
        "bytes_per_sample": c.get("bytes_accessed", 0.0) / owned,
        "flops_per_sample": c.get("flops", 0.0) / owned,
    }


def prune(candidates: Sequence[Candidate], base_cost: Dict[str, float],
          cost_fn: Callable[[Geometry], Dict[str, float]],
          slack: float = PRUNE_SLACK):
    """Split ``candidates`` into (survivors, rejected) on the
    analytical cost model: a candidate whose bytes/sample OR
    flops/sample regress past ``slack`` over the default is rejected
    before any hardware time is spent on it."""
    survivors: List[Tuple[str, Geometry, Dict[str, float]]] = []
    rejected: List[Dict[str, Any]] = []
    for label, geo in candidates:
        c = cost_fn(geo)
        worse_bytes = c["bytes_per_sample"] > \
            base_cost["bytes_per_sample"] * (1.0 + slack)
        worse_flops = c["flops_per_sample"] > \
            base_cost["flops_per_sample"] * (1.0 + slack)
        if worse_bytes or worse_flops:
            rejected.append({
                "label": label, "reason": "cost",
                "bytes_per_sample": round(c["bytes_per_sample"], 3),
                "flops_per_sample": round(c["flops_per_sample"], 3),
            })
        else:
            survivors.append((label, geo, c))
    return survivors, rejected


# ------------------------------------------------------------- measurement


def _stream_fingerprint(frames) -> Tuple:
    """Field-for-field emission fingerprint of a streaming run — the
    identity gate's comparand (failures included: a lane failing
    identically in both geometries is identity, not divergence)."""
    return tuple(
        (int(f.start), bool(f.result.ok), bool(f.result.crc_ok),
         int(f.result.rate_mbps), int(f.result.length_bytes),
         np.asarray(f.result.psdu_bits).tobytes())
        for f in frames)


def _link_fingerprint(results) -> Tuple:
    return tuple(
        (bool(r.ok), bool(r.crc_ok), int(r.rate_mbps),
         int(r.length_bytes), np.asarray(r.psdu_bits).tobytes())
        for r in results)


def _chunk_latency_ms(reg) -> Dict[str, float]:
    """p50/p99 of the streaming chunk-scan dispatch site off the
    telemetry registry's histogram layer (upper-bound bucket
    quantiles — the PR 7 numbers, not summed means)."""
    from ziria_tpu.utils import telemetry

    for (name, labels), m in reg.metrics():
        if name == telemetry.DISPATCH_HISTOGRAM and \
                dict(labels).get("site") == "rx.stream_chunk_multi":
            s = m.summary(scale=1e3, ndigits=4)
            return {"p50_ms": s.get("p50"), "p99_ms": s.get("p99")}
    return {}


class Measurer:
    """The default (hardware) measurer: one shared stimulus, then per
    candidate a warmed+timed streaming pass and fused-link pass with
    telemetry latency capture and emission fingerprints. Callable so
    tests can swap in a deterministic fake with the same signature."""

    def __init__(self, n_frames: int = 8, n_bytes: int = 24,
                 seed: int = 8, reps: int = 2):
        self.n_frames = int(n_frames)
        self.n_bytes = int(n_bytes)
        self.seed = int(seed)
        self.reps = max(1, int(reps))
        self._stim = None

    def _stimulus(self):
        if self._stim is None:
            from ziria_tpu.phy import link
            from ziria_tpu.phy.wifi.params import RATES

            rng = np.random.default_rng(self.seed)
            rates = (sorted(RATES)
                     * (-(-self.n_frames // len(RATES))))[:self.n_frames]
            psdus = [rng.integers(0, 256, self.n_bytes).astype(np.uint8)
                     for _ in range(self.n_frames)]
            stream, starts = link.stream_many(
                psdus, rates, snr_db=30.0, cfo=1e-4, delay=60,
                seed=self.seed, add_fcs=True, tail=2048)
            self._stim = (stream, starts, psdus, rates)
        return self._stim

    def __call__(self, geo: Geometry) -> Dict[str, Any]:
        from ziria_tpu.backend import framebatch
        from ziria_tpu.phy import link
        from ziria_tpu.utils import telemetry

        stream, _starts, psdus, rates = self._stimulus()
        kw = dict(geometry=geo, check_fcs=True, streaming=True)
        frames, _ = framebatch.receive_stream(stream, **kw)  # warm
        with telemetry.collect() as reg:
            t0 = time.perf_counter()
            for _ in range(self.reps):
                frames, _ = framebatch.receive_stream(stream, **kw)
            dt = time.perf_counter() - t0
        sps = stream.shape[0] * self.reps / dt if dt > 0 else 0.0

        res = link.loopback_many(psdus, rates, add_fcs=True,
                                 check_fcs=True, geometry=geo)  # warm
        t0 = time.perf_counter()
        for _ in range(self.reps):
            res = link.loopback_many(psdus, rates, add_fcs=True,
                                     check_fcs=True, geometry=geo)
        dt = time.perf_counter() - t0
        fps = len(psdus) * self.reps / dt if dt > 0 else 0.0

        out: Dict[str, Any] = {
            "sps": sps, "fps": fps,
            "fingerprint": (_stream_fingerprint(frames),
                            _link_fingerprint(res)),
        }
        out.update(_chunk_latency_ms(reg))
        return out


# -------------------------------------------------------------- the search


def run(base: Optional[Geometry] = None,
        candidates: Optional[Sequence[Candidate]] = None,
        cost_fn: Optional[Callable] = None,
        measure_fn: Optional[Callable] = None,
        n_frames: int = 8, n_bytes: int = 24, seed: int = 8,
        reps: int = 2, slack: float = PRUNE_SLACK,
        record: bool = True, path: Optional[str] = None,
        device_kind: Optional[str] = None,
        platform: Optional[str] = None,
        log: Callable[[str], None] = print) -> Dict[str, Any]:
    """The whole pipeline: enumerate -> cost-prune -> measure ->
    identity-gate -> pick winner -> (optionally) record. Deterministic
    given injected ``cost_fn``/``measure_fn``; the returned dict is
    the bench stage's evidence record."""
    base = (base if base is not None else Geometry()).resolve()
    cands = list(candidates if candidates is not None
                 else default_candidates(base))
    cost_fn = cost_fn or stream_chunk_cost
    measure_fn = measure_fn or Measurer(n_frames=n_frames,
                                        n_bytes=n_bytes, seed=seed,
                                        reps=reps)

    base_cost = cost_fn(base)
    survivors, pruned = prune(cands, base_cost, cost_fn, slack)
    log(f"autotune: {len(cands)} candidate(s), cost-pruned "
        f"{len(pruned)} ({', '.join(r['label'] for r in pruned) or '-'})"
        f", measuring {len(survivors)} + default")

    base_m = measure_fn(base)
    base_fp = base_m.get("fingerprint")
    measured = [{"label": "default", "sps": base_m["sps"],
                 "fps": base_m.get("fps"),
                 "p50_ms": base_m.get("p50_ms"),
                 "p99_ms": base_m.get("p99_ms")}]
    best_label, best_geo, best_sps = "default", base, base_m["sps"]
    identity_rejected: List[str] = []
    for label, geo, _cost in survivors:
        m = measure_fn(geo)
        if base_fp is not None and m.get("fingerprint") != base_fp:
            identity_rejected.append(label)
            log(f"autotune: {label} REJECTED — emissions diverge from "
                f"the default geometry (identity gate)")
            continue
        measured.append({"label": label, "sps": m["sps"],
                         "fps": m.get("fps"), "p50_ms": m.get("p50_ms"),
                         "p99_ms": m.get("p99_ms")})
        log(f"autotune: {label}: {m['sps']:.0f} sps "
            f"({m['sps'] / base_m['sps']:.2f}x default)")
        if m["sps"] > best_sps:
            best_label, best_geo, best_sps = label, geo, m["sps"]

    speedup = best_sps / base_m["sps"] if base_m["sps"] else 1.0
    if device_kind is None:
        device_kind = detect_device_kind()
    if platform is None:
        platform = _platform()
    rec = {
        "run_id": f"autotune-{int(time.time())}",
        "unix": round(time.time(), 1),
        "stage": "autotune", "metric": "sps_tuned",
        "value": best_sps, "platform": platform, "partial": False,
        "direction": "higher", "source": "autotune",
        "device_kind": device_kind,
        "geometry": best_geo.as_dict(),
        "winner": best_label,
        "baseline_sps": base_m["sps"],
        "speedup": round(speedup, 4),
    }
    out = {
        "winner": best_label, "geometry": best_geo.as_dict(),
        "sps_tuned": best_sps, "baseline_sps": base_m["sps"],
        "speedup": round(speedup, 4), "device_kind": device_kind,
        "platform": platform, "candidates": len(cands),
        "pruned": pruned, "identity_rejected": identity_rejected,
        "measured": measured, "record": rec,
    }
    if record:
        p = path or env_trajectory_path()
        try:
            with open(p, "a", encoding="utf-8") as fh:
                fh.write(json.dumps(rec) + "\n")
            out["recorded_to"] = p
            log(f"autotune: winner '{best_label}' "
                f"({speedup:.2f}x default) recorded for "
                f"device_kind={device_kind!r} -> {p}")
        except OSError as e:   # an unwritable ledger never fails a run
            out["record_error"] = repr(e)
            log(f"autotune: ledger unwritable ({e!r}); winner not "
                f"recorded")
    return out


def _platform() -> Optional[str]:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return None


# -------------------------------------------------------------------- cli


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m ziria_tpu autotune``: the measured search, sized
    for a smoke by default (a handful of frames; pass --frames/--reps
    up for a real tuning run on hardware)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m ziria_tpu autotune",
        description="cost-pruned measured geometry search; winners "
                    "land per-device in the bench trajectory ledger "
                    "(BENCH_TRAJECTORY.jsonl) for Geometry.tuned()")
    ap.add_argument("--frames", type=int, default=8,
                    help="stimulus frames per measurement (default 8)")
    ap.add_argument("--bytes", type=int, default=24, dest="n_bytes",
                    help="PSDU bytes per stimulus frame (default 24)")
    ap.add_argument("--reps", type=int, default=2,
                    help="timed repetitions per candidate (default 2)")
    ap.add_argument("--seed", type=int, default=8)
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: BENCH_TRAJECTORY env "
                         "or the repo-root BENCH_TRAJECTORY.jsonl)")
    ap.add_argument("--dry-run", action="store_true",
                    help="search and report but do not record")
    args = ap.parse_args(argv)

    out = run(n_frames=args.frames, n_bytes=args.n_bytes,
              reps=args.reps, seed=args.seed,
              record=not args.dry_run, path=args.ledger)
    tuned = Geometry.tuned(out["device_kind"],
                           path=None if args.dry_run else args.ledger)
    print(json.dumps({k: out[k] for k in
                      ("winner", "sps_tuned", "baseline_sps",
                       "speedup", "device_kind", "platform")},
                     default=str))
    if not args.dry_run and out.get("recorded_to"):
        ok = tuned.as_dict() == out["geometry"]
        print(f"Geometry.tuned({out['device_kind']!r}) "
              f"{'reproduces the winner' if ok else 'MISMATCH'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":   # pragma: no cover - python -m entry
    raise SystemExit(main())
