#!/usr/bin/env python
"""jax-free smoke of the declarative geometry layer (ISSUE 16).

Constructs, resolves and serializes
`ziria_tpu.utils.geometry.Geometry` WITHOUT importing jax — the same
through-TPU-probe-hangs discipline as chaos/serve/durability smokes —
and pins that the default Geometry still resolves to the tree's
historical constants (the zero-new-programs / bit-identity guarantee
rests on exactly these values; tests/test_geometry.py pins the
compiled side). Wired into tools/precommit.sh. Sub-second.
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

from ziria_tpu.utils.geometry import Geometry  # noqa: E402

checks = 0


def ok(cond, what):
    global checks
    checks += 1
    if not cond:
        print(f"geometry_smoke: FAIL — {what}", file=sys.stderr)
        raise SystemExit(1)


def main():
    ok("jax" not in sys.modules,
       "importing utils.geometry pulled in jax (the smoke must run "
       "through TPU probe hangs)")

    # the default IS the tree's historical constants — drift here
    # breaks the no-op-by-construction guarantee
    g = Geometry()
    ok(g.chunk_len == 8192 and g.frame_len == 2048
       and g.max_frames_per_chunk == 8 and g.n_streams == 8,
       f"default fleet geometry drifted: {g}")
    ok(g.sym_bucket_min == 4 and g.capture_bucket_min == 512
       and g.bit_bucket_min == 128,
       f"default bucket floors drifted: {g}")
    ok((g.threshold, g.min_run, g.dead_zone) == (0.75, 33, 320),
       f"default detector params drifted: {g}")
    ok(g.sym_bucket(3) == 4 and g.sym_bucket(21) == 32
       and g.capture_bucket(100) == 512 and g.bit_bucket(1) == 128,
       "bucket rules diverged from pow2_bucket floors")

    # frozen + hashable: Geometry is a dict key / part of cache keys
    ok(hash(g) == hash(Geometry()), "equal geometries hash unequal")
    try:
        g.chunk_len = 1
        ok(False, "frozen dataclass accepted a field write")
    except Exception:
        pass

    # resolve() folds env exactly once, under a scoped set+restore
    old = {k: os.environ.get(k) for k in
           ("ZIRIA_VITERBI_RADIX", "ZIRIA_RX_SCO_TRACK")}
    try:
        os.environ["ZIRIA_VITERBI_RADIX"] = "4"
        os.environ["ZIRIA_RX_SCO_TRACK"] = "1"
        r = g.resolve()
        ok(r.viterbi_radix == 4 and r.sco_track is True,
           f"resolve() missed the env knobs: {r}")
        ok(g.viterbi_radix is None,
           "resolve() mutated the source geometry")
        explicit = dataclasses.replace(g, viterbi_radix=2).resolve()
        ok(explicit.viterbi_radix == 2,
           "an explicit field lost to the env default")
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    r = g.resolve()
    ok((r.viterbi_window, r.viterbi_metric, r.viterbi_radix,
        r.fused_demap, r.sco_track) == (0, "float32", 2, False, False),
       f"clean-env resolve() drifted from the historical defaults: {r}")
    ok(r.resolve() == r, "resolve() is not idempotent")

    # serialization round-trips, strictly
    ok(Geometry.from_json(r.to_json()) == r,
       "to_json/from_json round trip lost a field")
    try:
        Geometry.from_dict({"chunk_len": 4096, "warp_factor": 9})
        ok(False, "from_dict accepted an unknown field")
    except ValueError:
        pass

    ok("jax" not in sys.modules,
       "a geometry code path imported jax")
    print(f"geometry_smoke: OK ({checks} checks, no jax)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
