"""Validate the vectorizer's utility model against TPU measurement.

VERDICT r1 next-round #5: the model (core/vectorize.py STEP_OVERHEAD /
VPU_PARALLEL) picked widths no measurement had ever contacted. This
harness times representative pipelines at W in {pick/4, pick, 4*pick}
on the real chip using the device-loop marginal method (per-call
timing measures the host link, not the chip) and reports
whether the model's pick is within tolerance of the measured best.

    python tools/calibrate_vect.py            # needs the TPU reachable
    python tools/calibrate_vect.py --cpu      # smoke-test the harness
                                              # (mechanics only: the
                                              # constants are TPU-tuned,
                                              # so a CPU verdict of
                                              # MODEL OFF is expected)

Emits one JSON object: per-pipeline tables of (W, steps/s, items/s)
plus the model's pick and the measured best. If the pick is >10% off
the best W's throughput, recalibrate STEP_OVERHEAD (raise it if the
model picks too-small W; lower if too-large) and re-run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))


def _pipelines():
    """(name, comp, item dtype) — one stateless-wide, one stateful-
    scan-bound, one mixed (the three regimes the model trades off)."""
    import ziria_tpu as z

    def fir_step(s, x):
        import jax.numpy as jnp
        s = jnp.roll(s, 1).at[0].set(x)
        return s, (s * jnp.arange(1.0, 6.0)).sum()

    stateless = z.pipe(z.zmap(lambda x: x * 2.0 + 1.0, name="axpy"),
                       z.zmap(lambda x: x * x, name="sq"))
    stateful = z.pipe(z.map_accum(fir_step, np.zeros(5, np.float32),
                                  name="fir5"))
    mixed = z.pipe(z.zmap(lambda x: x * 0.5, name="pre"),
                   z.map_accum(lambda s, x: (s + x, s + x), 0.0,
                               name="cumsum"),
                   z.zmap(lambda x: x + 3.0, name="post"))
    return [("stateless", stateless), ("stateful", stateful),
            ("mixed", mixed)]


def _fence(x):
    np.asarray(x.ravel()[:1])


def _time_width(comp, W: int, item_shape: tuple = ()):
    """(marginal seconds per fused step at width W, items per step) —
    timed via a device-side chain of K steps (cancels the host
    round-trip). ``item_shape`` is the per-item trailing shape (() for
    scalar streams, (2,) for complex16 pair streams)."""
    import jax
    import jax.numpy as jnp

    from ziria_tpu.backend.lower import lower

    lowered = lower(comp, width=W)
    take = lowered.take
    xs = jnp.asarray(np.random.default_rng(0).normal(
        size=(take,) + tuple(item_shape)).astype(np.float32))

    @jax.jit
    def step_k(x0, k):
        def body(i, carry):
            s, x, acc = carry
            st, y = lowered.step(s, x)
            # feed a perturbed copy of the same chunk back: keeps the
            # loop data-dependent so XLA cannot hoist the body
            return (st, x0 + acc * 1e-30, acc + y.sum())
        return jax.lax.fori_loop(
            0, k, body, (lowered.init_carry, x0, jnp.float32(0)))[2]

    K1, K2 = 16, 80
    def run(k):
        best = float("inf")
        _fence(step_k(xs, jnp.int32(k)))
        for _ in range(3):
            t0 = time.perf_counter()
            _fence(step_k(xs, jnp.int32(k)))
            best = min(best, time.perf_counter() - t0)
        return best
    t1, t2 = run(K1), run(K2)
    return max((t2 - t1) / (K2 - K1), 1e-9), take


def _fit_constants(pipelines: dict) -> dict:
    """Fit the utility model's two constants from the probe tables.

    Model: s_per_step(W) = a + b_par*(parallel items) + b_seq*(seq
    items). The stateless pipeline (2 vmapped stages -> 2W parallel
    items/step) yields b_par from its lstsq slope; the stateful one
    (1 scan -> W sequential items/step) yields b_seq; the stateless
    intercept estimates the fixed per-step cost a. Then, in the
    model's own units (a sequential item costs 1):

        VPU_PARALLEL  = b_seq / b_par   (parallel items per seq-item)
        STEP_OVERHEAD = a / b_seq       (seq-item-equivalents)

    Per-regime fits are used instead of one global lstsq because the
    captures are noisy (host load, cache cliffs at multi-MB widths) —
    a shared intercept fits nothing well. Treat results as
    2-significant-figure estimates.
    """
    def slope_intercept(name):
        tab = pipelines[name]["table"]
        W = np.array([r["W"] for r in tab], float)
        t = np.array([r["s_per_step"] for r in tab], float)
        b, a = np.polyfit(W, t, 1)
        return b, max(a, 0.0)

    b_sl, a_sl = slope_intercept("stateless")   # slope = 2*b_par
    b_sf, _ = slope_intercept("stateful")       # slope = b_seq
    #                         (its intercept is unused: STEP_OVERHEAD
    #                          derives from the stateless fit's a_sl)
    b_par = max(b_sl / 2.0, 1e-15)
    b_seq = max(b_sf, 1e-15)
    return {
        "a_s": round(float(a_sl), 9),
        "b_par_s": round(float(b_par), 12),
        "b_seq_s": round(float(b_seq), 12),
        "VPU_PARALLEL": round(float(b_seq / b_par), 1),
        "STEP_OVERHEAD": round(float(a_sl / b_seq), 1),
        "method": "per-regime lstsq (see _fit_constants docstring)",
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="harness smoke test on CPU")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]

    from ziria_tpu.core.vectorize import vectorize

    # per-pipeline resume across window flaps (tools/_bank.py): each
    # finished pipeline is banked in the scratch
    # dir with its own capture time; a re-entering run on the same
    # platform reuses the still-fresh ones and spends the (possibly
    # short) window on what is missing.
    import _bank
    bank = _bank.load_bank("vect_calib", dev.platform)
    if bank:
        print(f"[calibrate] resuming {sorted(bank)} from the scratch "
              f"bank", file=sys.stderr, flush=True)

    report = {"device": str(dev), "platform": dev.platform,
              "pipelines": {}}
    for name, comp in _pipelines():
        if name in bank:
            report["pipelines"][name] = _bank.strip(bank[name])
            continue
        plan = vectorize(comp)
        pick = plan.segments[0].width if plan.segments else 1
        table = []
        for W in sorted({max(1, pick // 4), pick, pick * 4}):
            t, take = _time_width(comp, W)
            table.append({"W": W, "s_per_step": round(t, 9),
                          "items_per_s": round(take / t, 1)})
        best = max(table, key=lambda r: r["items_per_s"])
        pick_row = next(r for r in table if r["W"] == pick)
        report["pipelines"][name] = {
            "model_pick": pick,
            "table": table,
            "best_W": best["W"],
            "pick_within_10pct":
                pick_row["items_per_s"] >= 0.9 * best["items_per_s"],
        }
        _bank.save_entry("vect_calib", dev.platform, name,
                         report["pipelines"][name])
        print(f"[calibrate] banked {name}", file=sys.stderr, flush=True)
    try:
        report["fitted_constants"] = _fit_constants(report["pipelines"])
    except Exception as e:        # fit is best-effort; tables are the data
        report["fitted_constants"] = {"error": repr(e)}
    print(json.dumps(report, indent=2))
    ok = all(p["pick_within_10pct"]
             for p in report["pipelines"].values())
    print(("MODEL OK: every pick within 10% of measured best"
           if ok else
           "MODEL OFF: recalibrate STEP_OVERHEAD/VPU_PARALLEL "
           "(core/vectorize.py)"), file=sys.stderr)
    # --cpu is a mechanics smoke test: the constants are TPU-tuned, so
    # its verdict is expected to be OFF and must not fail the exit code
    return 0 if (ok or args.cpu) else 1


if __name__ == "__main__":
    sys.exit(main())
