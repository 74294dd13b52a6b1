#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms, measures for ``--seconds``, checks, and prints one JSON
object as the last line of standard output. Exits non-zero, with no
result line, when JAX has no TPU or fewer chips than the cell asks for,
or when the program is not in the checkout. ``--rehearse`` runs the same
control flow at a tiny geometry on whatever backend JAX has and never
prints a result line. BENCH_RUN in the environment is not read.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny geometry, any backend, no result line")
    args = ap.parse_args(argv)

    from benchmark.harness import cell
    return cell.run(args)


if __name__ == "__main__":
    sys.exit(main())
