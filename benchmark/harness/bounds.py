"""The bounds of ``BENCHMARK.json`` as data with their derivation, in
files a later PR can add to and need not edit (``benchmark/bounds/``):

- ``rule.json``: the rule, for every metric;
- ``metrics/<metric>.json``: a metric's ceiling, and the cells its
  bound is ``derived_from`` (or a ``fixed`` bound: ``setup_s``, the
  contract's 25%). A PR that adds an end-to-end metric adds its file;
- ``cells/<cell>.json``: the runs of one cell, two sets of the same
  seeds, one value a seed for each end-to-end metric it reports (one
  TPU v5e, the tree named there).

The rule (PERF.md section 2): a metric's bound is ``times`` (5) times
the widest quartile spread (``steady.quartile_spread``) over both sets
of every cell it is derived from, each set's run farthest from its
median left out (a run that holds a stall is not what a bound is for),
rounded to ``round_to`` (half a per cent), never under ``floor`` (1%);
where that passes the metric's ceiling the bound is the ceiling, and the
metric's file has to say so (``over_ceiling``). What a bound has to hold
before a cell can be admitted or measured anew: each recorded set's
trimmed range (``steady.trimmed_range``, the driver's notes' measure)
is at most half of it.

A bound moves only when a ``benchmark`` PR rewrites ``derived_from`` or
the runs of a cell named there. A PR that adds a cell need add nothing
here; where it records its cell's runs (``cells/<its cell>.json``, a
new file) they are held to the half rule and leave every bound alone.

The same for an open-loop mix's rate (``rate_problems``): a share of
the knee of the sweeps its traffic file records.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Optional, Sequence

from . import steady

DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bounds")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(path: str) -> Dict[str, dict]:
    """name -> the JSON of ``<path>/<name>.json``, whatever is there."""
    if not os.path.isdir(path):
        return {}
    return {f[:-len(".json")]: _json(os.path.join(path, f))
            for f in sorted(os.listdir(path)) if f.endswith(".json")}


def load(path: str = DIR) -> dict:
    return {"rule": _json(os.path.join(path, "rule.json")),
            "metrics": _named(os.path.join(path, "metrics")),
            "cells": _named(os.path.join(path, "cells"))}


def spreads(sets: Sequence[Sequence[float]]) -> List[dict]:
    """Of each set of a cell's runs: the quartile spread and the range
    of its runs without the farthest."""
    return [{"quartile_trimmed": steady.quartile_spread(
                steady.without_farthest(v)),
             "trimmed_range": steady.trimmed_range(v)} for v in sets]


def rule_asks(name: str, rec: dict) -> float:
    """What the rule gives a metric from the sets of the cells it is
    derived from, before its ceiling."""
    rule = rec["rule"]
    widest = max(s["quartile_trimmed"]
                 for cell in rec["metrics"][name]["derived_from"]
                 for s in spreads(rec["cells"][cell]["metrics"][name]))
    step = rule["round_to"]
    return max(math.floor(rule["times"] * widest / step + 0.5) * step,
               rule["floor"])


def derive(name: str, rec: dict) -> float:
    """The bound of a metric: fixed, or the rule's under its ceiling."""
    metric = rec["metrics"][name]
    if "fixed" in metric:
        return metric["fixed"]
    return round(min(rule_asks(name, rec), metric["ceiling"]), 6)


def problems(manifest: dict, rec: Optional[dict] = None) -> List[str]:
    """Where ``BENCHMARK.json``'s bounds part from what
    ``benchmark/bounds/`` records and derives. Empty when they agree.
    A cell with no record is no problem: only the cells a bound is
    derived from must have one."""
    rec = load() if rec is None else rec
    out = []
    cells = [w["name"] for w in manifest["workloads"]]
    reports = {m["name"]: m.get("workloads", cells)
               for m in manifest["end_to_end"]}
    for cell, c in rec["cells"].items():
        if cell not in cells:
            out.append(f"bounds/cells/{cell}.json: no such cell")
        for name, sets in c["metrics"].items():
            if cell not in reports.get(name, []):
                out.append(f"{name} in {cell}: recorded, and "
                           f"BENCHMARK.json does not report it there")
            if len(sets) != 2 or any(len(v) != len(c["seeds"])
                                     for v in sets):
                out.append(f"{name} in {cell}: two sets of one value a "
                           f"seed are recorded")
    if out:
        return out
    for m in manifest["end_to_end"]:
        name = m["name"]
        if name not in rec["metrics"]:
            out.append(f"{name}: no benchmark/bounds/metrics/{name}.json")
            continue
        got = rec["metrics"][name]
        missing = [c for c in got.get("derived_from", [])
                   if name not in rec["cells"].get(c, {}).get("metrics", {})]
        if missing:
            out.append(f"{name}: derived from {missing}, whose runs are "
                       f"not recorded")
            continue
        want = derive(name, rec)
        if abs(m["bound"] - want) > 1e-9:
            out.append(f"{name}: BENCHMARK.json's bound {m['bound']} is "
                       f"not the {want} benchmark/bounds derives")
        if "fixed" in got:
            continue
        asks = rule_asks(name, rec)
        if (asks > got["ceiling"]) != ("over_ceiling" in got):
            out.append(f"{name}: the rule asks for {asks:.3f}, the "
                       f"ceiling is {got['ceiling']}, and metrics/"
                       f"{name}.json has to say so under over_ceiling, "
                       f"or not")
        for cell, c in rec["cells"].items():
            for i, s in enumerate(spreads(c["metrics"].get(name, []))):
                if s["trimmed_range"] > 0.5 * want:
                    out.append(
                        f"{name} in {cell}, set {i + 1}: spread "
                        f"{s['trimmed_range']:.4f} is over half the "
                        f"bound {want}")
    return out


def paced_rate(knee: float, share: float, grain: int = 100000) -> int:
    """``share`` of the knee, rounded to ``grain`` samples/s."""
    return int(round(share * knee / grain)) * grain


#: in how many of a mix's recorded sweeps a rate has to have broken a
#: rule before it counts as broken: a stall (PR 36: 2-4 s of the host
#: blocked in a pull, one window in thirty) strikes one sweep's row,
#: a rate the fleet cannot carry breaks in every sweep
BROKEN_IN = 2


def knee(sweeps: Sequence[dict], need: int = BROKEN_IN):
    """The highest offered rate under which no rate is broken, a rate
    being broken where it broke a rule in ``need`` or more of the
    sweeps (each ``rows``: ``rate`` and ``sustained``; ``need`` is 1
    only for one sweep read alone). None where the lowest rate is
    broken."""
    broke: Dict[float, int] = {}
    for sw in sweeps:
        for r in sw["rows"]:
            broke[r["rate"]] = broke.get(r["rate"], 0) \
                + (not r["sustained"])
    best = None
    for rate in sorted(broke):
        if broke[rate] >= need:
            break
        best = rate
    return best


def rate_problems(mix: str, traffic: dict) -> List[str]:
    """An open-loop mix offers a share of the knee its own sweeps found
    (``benchmark/sweep.py``): the rate, the share, the knee and the
    sweeps' rows are all in the traffic file, and have to agree."""
    if traffic.get("loop") != "open":
        return []
    out = []
    if len(traffic["sweeps"]) < BROKEN_IN:
        out.append(f"traffic {mix}: {BROKEN_IN} sweeps at the least")
    if traffic["knee_samples_per_s"] != knee(traffic["sweeps"]):
        out.append(f"traffic {mix}: the knee is not the highest rate of "
                   f"its sweeps under which no rate broke in {BROKEN_IN}")
    want = paced_rate(traffic["knee_samples_per_s"],
                      traffic["share_of_knee"])
    if traffic["rate_samples_per_s"] != want:
        out.append(f"traffic {mix}: rate {traffic['rate_samples_per_s']} "
                   f"is not {traffic['share_of_knee']} of the knee, "
                   f"{want}")
    return out
