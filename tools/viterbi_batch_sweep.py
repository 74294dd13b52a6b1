"""Viterbi Pallas batch sweep (VERDICT r2 weak #6): measure the kernel
across batch sizes on the real chip and attribute the r2 "B=512
regressed" observation. Emits ONE JSON object.

Static working-set arithmetic first (independent of the chip):

per grid step (one 128-lane batch tile x one UNROLL=64 time block)
  llr in      (1, 64, 2, 128) f32   64 KiB   } x2 with pipeline
  dec out     (1, 64, 8, 128) u8    64 KiB   } double-buffering
  metrics out (64, 128) f32         32 KiB
  m scratch   (64, 128) f32         32 KiB
  total VMEM  ~0.4 MiB  — far under a v5e core's VMEM, so VMEM
  pressure inside the kernel does NOT scale with B (batch enters as
  extra GRID tiles, not bigger blocks).

What DOES scale with B:
  - the lane-transpose pre/post passes ((B,T,2) <-> (nb,T,2,128)):
    pure HBM traffic, ~8 B x T x B bytes round-tripped;
  - the packed decision stream (T x 8 x 128 B per tile) read back by
    the traceback kernel: 2 x 8.2 MB of HBM per tile at T=8208.

The sweep times (a) the full decode, (b) the ACS+traceback kernels
alone (pre-transposed inputs), per frame, so the regression's locus
(kernel vs layout passes) is measured, not guessed.
"""

import json
import os
import sys
import time

import numpy as np

# run as a script from tools/: only tools/ lands on sys.path, the repo
# root is not — same bootstrap as hybrid_tpu_check.py (this exact miss
# cost the first successful TPU window its sweep artifact, r4)
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))


def main():
    import jax

    # ZIRIA_TOOL_ALLOW_CPU=1: smoke-test the whole sweep body on CPU
    # (interpret-mode kernels, shrunk sizes) so a broken tool cannot
    # waste a real TPU window — the sys.path bug above already cost
    # one. Results are labelled platform=cpu and never mistakable for
    # chip evidence.
    smoke = os.environ.get("ZIRIA_TOOL_ALLOW_CPU") == "1"
    if smoke:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from ziria_tpu.ops import viterbi_pallas as vp

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not smoke:
        print(json.dumps({"error": "no TPU visible"}))
        return 1
    interp = dev.platform == "cpu"

    T = 1040 if smoke else 8208
    rng = np.random.default_rng(0)
    out = {"platform": dev.platform,
           "device_kind": getattr(dev, "device_kind", "?"),
           "T": T, "unroll": vp.UNROLL, "points": []}

    def fence(x):
        np.asarray(x.ravel()[:1])

    # per-point resume across window flaps (tools/_bank.py):
    # finished B points are banked in the scratch dir
    # keyed by platform+T with per-point capture times, so a window
    # that dies after B=256 spends its successor on 512/1024.
    import _bank
    bank = _bank.load_bank("vit_sweep", dev.platform, match={"T": T})
    if bank:
        print(f"[sweep] resuming B={sorted(bank)} from the scratch "
              f"bank", file=sys.stderr, flush=True)

    for B in ((128, 256) if smoke else (128, 256, 512, 1024)):
        if str(B) in bank:
            out["points"].append(_bank.strip(bank[str(B)]))
            continue
        llrs = jnp.asarray(rng.normal(size=(B, T, 2)).astype(np.float32))
        full = jax.jit(lambda x: vp.viterbi_decode_batch(
            x, interpret=interp))
        # kernel-only: pre-tiled input, no lane transposes in the timed
        # region
        x = jnp.transpose(llrs, (1, 2, 0)).reshape(
            T, 2, B // 128, 128).transpose(2, 0, 1, 3)
        kern = jax.jit(lambda t: vp._decode_tiles(t, interp))

        def timed(fn, arg, reps=8):
            fence(fn(arg))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                o = None
                for _ in range(reps):
                    o = fn(arg)
                fence(o)
                best = min(best, (time.perf_counter() - t0) / reps)
            return best

        t_full = timed(full, llrs)
        t_kern = timed(kern, x)
        point = {
            "B": B,
            "t_full_ms": round(t_full * 1e3, 3),
            "t_kernel_ms": round(t_kern * 1e3, 3),
            "t_layout_ms": round((t_full - t_kern) * 1e3, 3),
            "mbit_per_s_full": round(B * T / t_full / 1e6, 1),
            "mbit_per_s_kernel": round(B * T / t_kern / 1e6, 1),
        }
        out["points"].append(point)
        _bank.save_entry("vit_sweep", dev.platform, str(B), point,
                         match={"T": T})
        print(f"[sweep] B={B}: full {t_full*1e3:.2f} ms, kernel "
              f"{t_kern*1e3:.2f} ms", file=sys.stderr, flush=True)

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
