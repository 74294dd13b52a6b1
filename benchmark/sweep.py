#!/usr/bin/env python3
"""The rate sweep that fixes ``traffic/paced.json``'s rate: one process,
one cell, a short window at each of a few offered rates. Not part of a
benchmark run. A rate is sustained (``sustained`` below) when no slab
was refused, the staged backlog at the window's close is no more than
a chunk-step's worth, the delay's median does not grow from the
window's first half to its second, and the generator itself kept up:
the median lateness of its slabs is under one blocking ``step()``
(``step_busy_p50_ms``). The loop has one thread, so a slab that falls
due while ``step()`` blocks waits for it: at every rate from 2 M to
12 M samples/s the median slab was 8-14 ms late against a 25 ms step
(PR 36; the delay is timed from DUE, so the server is charged for
it). A generator a whole step late at the median is behind, and
offers less than the rate says.

One sweep is not a knee: a pause of the host (PR 36: 0.8-8 s blocked
in a pull, one window in twenty; ``stalls`` in a row counts those of
2 s and more its window held) breaks whatever rate it strikes, and
between 11 M and 14 M a 20 s window breaks on one of under a second
(three sweeps read 12.5, 10 and 10 M alone). ``paced.json`` keeps the
rows of several sweeps and ``bounds.knee`` calls a rate broken where
it broke in two of them; the cell offers ``share_of_knee`` of the
highest rate under which none is. ``[knee]`` prints this sweep's knee
alone and the one it gives with the sweeps the traffic file records.

    python3 benchmark/sweep.py --workload mtu8.paced --seed 1 --seconds 20 \\
        --rates 2e6 6e6 10e6 12e6 14e6 16e6 18e6 20e6

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

#: by how much the second half's median delay may pass the first's
#: before the delay counts as growing: a tenth, and a millisecond (the
#: host clock's grain)
GROWTH = 1.1
GRAIN_MS = 1.0


def sustained(paced: dict) -> list:
    """The rules a rate broke, by name: empty when it is sustained.
    ``paced`` is the ``[paced]`` line of one run (cell.measure)."""
    broke = []
    if paced["refused"]:
        broke.append("refused")
    if paced["staged_at_close"] > paced["step_samples"]:
        broke.append("staged")
    if paced["delay_p50_second_half_ms"] > \
            GROWTH * paced["delay_p50_first_half_ms"] + GRAIN_MS:
        broke.append("growing")
    if paced["late_p50_ms"] >= paced["step_busy_p50_ms"]:
        broke.append("late")
    return broke


def main(argv=None) -> int:
    from benchmark.harness import bounds, cell, manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    base = manifest.load_cell(args.workload, args.rehearse).traffic
    rows = []
    for rate in sorted(args.rates):
        line, _compared = cell.measure(
            argparse.Namespace(workload=args.workload, seed=args.seed,
                               seconds=args.seconds, trace=args.trace,
                               rehearse=args.rehearse),
            traffic=dict(base, rate_samples_per_s=rate))
        paced = line.get("paced", {})
        broke = sustained(paced) if paced else ["no delay sample"]
        if not line["correct"]:
            broke.append("not correct")
        rows.append({"rate": rate, "sustained": not broke})
        print("[sweep] " + json.dumps(
            {"rate": rate, "correct": line["correct"],
             "sustained": not broke, "broke": broke,
             "p50_ms": paced.get("delay_p50_ms"),
             "p90_ms": paced.get("delay_p90_ms"),
             "late_p50_ms": paced.get("late_p50_ms"),
             "refused": paced.get("refused"),
             "staged_at_close": paced.get("staged_at_close"),
             "step_busy_p50_ms": paced.get("step_busy_p50_ms"),
             "stalls": paced.get("stalls"),
             "metrics": line["metrics"]}), flush=True)
    recorded = base.get("sweeps", [])
    knee = bounds.knee(recorded + [{"rows": rows}])
    print("[knee] " + json.dumps(
        {"alone": bounds.knee([{"rows": rows}], 1),
         "with_recorded": knee, "recorded_sweeps": len(recorded),
         "broken_in": bounds.BROKEN_IN, "rates": sorted(args.rates),
         "paced_rate_at": {str(sh): bounds.paced_rate(knee, sh)
                           for sh in (0.8, 0.7, 0.6)} if knee else None}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
