#!/usr/bin/env python3
"""The controls of ``correct``: readings that have to come out as NOT
correct. Not part of a benchmark run; PR 24 ran it on the chip to set
the limits in harness/limits.json, and benchmark/tests keeps it at a
size a test run can hold.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3 --seconds 5

In ONE process, for each seed: a short window of the cell at its own
load (the program's sound readings of every number compared), then the
control's readings on the same chunk-step:

- the plain reference put in the program's place and computed in
  bfloat16, the nearest precision below the float32 the configurations
  state: its CFO estimates and derotated segments against float64;
- once, the two served programs lowered with the precision argument of
  every ``matmul`` and ``convolve`` dropped, which is what a TPU's
  DEFAULT precision is: the count of float contractions below HIGHEST.
"""

import argparse
import contextlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def reference_in_place(host_step, outs, win_len: int, need_b: int, dtype):
    """The chunk scan's outputs with its two float ones (CFO estimate,
    derotated segments) replaced by the plain reference's, computed in
    ``dtype`` from the same samples at the same starts."""
    from benchmark.harness import checks
    from benchmark.reference import wifi_rx_ref as ref

    outs = [np.array(o) for o in outs]

    def rnd(a):
        return np.asarray(a).astype(dtype).astype(np.float64)

    for i, j, cap, avail in checks.owned_frames(host_step, outs, win_len):
        cap = rnd(cap)
        eps = float(rnd(ref.lts_cfo(cap[:, 0] + 1j * cap[:, 1])))
        outs[5][i, j] = eps
        outs[10][i, j] = rnd(ref.derotate(cap, eps, need_b, avail))
    return outs


@contextlib.contextmanager
def default_precision():
    """Inside, ``jnp.matmul`` and ``jnp.convolve`` drop their
    ``precision`` argument: every contraction traces at DEFAULT."""
    import jax.numpy as jnp
    mm, cv = jnp.matmul, jnp.convolve
    jnp.matmul = lambda *a, precision=None, **k: mm(*a, **k)
    jnp.convolve = lambda *a, precision=None, **k: cv(*a, **k)
    try:
        yield
    finally:
        jnp.matmul, jnp.convolve = mm, cv


def contractions_at_default(rx, chunk_args, dec_args) -> int:
    """Fresh traces of the two served programs (through the factories'
    ``__wrapped__``, so no cache is touched) under DEFAULT precision."""
    from benchmark.harness import checks
    from ziria_tpu.phy.wifi import rx as _rx

    with default_precision():
        scan = _rx._jit_stream_chunk_multi.__wrapped__(
            rx.k, rx.frame_len, rx.n_sym_bucket, rx._threshold,
            rx._min_run, rx._dead_zone, rx.mesh, rx.axis)
        dec = _rx._jit_stream_decode_multi.__wrapped__(
            rx.n_sym_bucket, rx.viterbi_window, rx.viterbi_metric,
            rx.viterbi_radix, rx.mesh, rx.axis, rx.sco_track,
            rx.fused_demap)
        text = scan.lower(*chunk_args).as_text() \
            + dec.lower(*dec_args).as_text()
    return checks.loose_contractions(text)


def main(argv=None) -> int:
    import ml_dtypes

    from benchmark.harness import cell, checks, counts

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    for n, seed in enumerate(args.seeds):
        got = {}

        def inspect(rx, host_step, outs, chunk_args, dec_args):
            need_b = counts.FRAME_DATA_START + 80 * rx.n_sym_bucket
            ctrl = reference_in_place(host_step, outs, rx.frame_len,
                                      need_b, ml_dtypes.bfloat16)
            got["control"] = checks.float_gaps(host_step, ctrl,
                                               rx.frame_len, need_b)
            if n == 0:
                got["default"] = contractions_at_default(rx, chunk_args,
                                                         dec_args)

        line, compared = cell.measure(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=0, rehearse=args.rehearse), inspect=inspect)
        print("[control] " + json.dumps(
            {"seed": seed, "correct": line["correct"],
             "failed": line["failed"], "attempted": line["attempted"],
             "sound": compared, "control": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
