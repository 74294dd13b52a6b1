#!/usr/bin/env python3
"""The rate sweep that fixed ``traffic/paced.json``'s rate: one process,
one cell, a short window at each of a few offered rates. Not part of a
benchmark run. A rate is sustained when the generator is not refused,
the staged backlog at the window's close is no more than a step's worth
and the delay's median does not grow from the window's first half to
its second.

    python3 benchmark/sweep.py --workload mtu8.paced --seed 1 --seconds 25 \\
        --rates 300000 500000 700000 900000

``--trace 1`` makes each of them a traced run, whose line holds the
cell's per-layer metrics: a rate far under the sustained one stands in
for a program whose tick is shorter than the gap between lane fills
(PR 34: 100000 samples/s, a fill every 655 ms against a 443 ms tick).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark.harness import cell, manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    base = manifest.load_cell(args.workload, args.rehearse).traffic
    for rate in args.rates:
        line, _compared = cell.measure(
            argparse.Namespace(workload=args.workload, seed=args.seed,
                               seconds=args.seconds, trace=args.trace,
                               rehearse=args.rehearse),
            traffic=dict(base, rate_samples_per_s=rate))
        print("[sweep] " + json.dumps(
            {"rate": rate, "correct": line["correct"],
             "metrics": line["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
