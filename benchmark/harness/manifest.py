"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: it names a configuration (whose
entry under ``configs`` names its file) and a traffic mix
(``traffic/<mix>.json``). A per-layer metric is one entry of
``per_layer`` plus ``layer_metrics/<name>.json``, which names a reader
under ``reducers/`` and its arguments. A name that resolves to nothing
is an error at start, never a row left out.
"""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Dict, List, NamedTuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class LayerMetric(NamedTuple):
    name: str
    unit: str
    reduce: object          # reducers.<kind>.reduce(ctx, **args)
    args: dict


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the metrics this cell reports
    per_layer: List[LayerMetric]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str) -> bool:
    """An end-to-end metric is in every cell unless it lists some."""
    return cell in metric.get("workloads", [cell])


def _reads(metric: dict, cell: str, reported: set) -> bool:
    """A per-layer metric is read in the cells it lists or, with no
    list, in every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def load_cell(name: str, rehearse: bool = False,
              root: str = ROOT) -> Cell:
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}: BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    if w["config"] not in configs:
        raise SystemExit(f"cell {name}: no configuration "
                         f"{w['config']!r} in BENCHMARK.json")
    cfg_path = os.path.join(root, configs[w["config"]]["file"])
    if rehearse:        # the tiny twin: control flow only, never a result
        cfg_path = os.path.join(HERE, "tests", "rehearse",
                                os.path.basename(cfg_path))
    traffic_path = os.path.join(HERE, "traffic", w["traffic"] + ".json")
    for p in (cfg_path, traffic_path):
        if not os.path.isfile(p):
            raise SystemExit(f"cell {name}: {p} is missing")
    e2e = [m for m in man["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    layers = []
    for m in man["per_layer"]:
        if not _reads(m, name, reported):
            continue
        spec_path = os.path.join(HERE, "layer_metrics",
                                 m["name"] + ".json")
        if not os.path.isfile(spec_path):
            raise SystemExit(f"per-layer metric {m['name']}: "
                             f"{spec_path} is missing")
        spec = _json(spec_path)
        try:
            mod = importlib.import_module(
                f"benchmark.reducers.{spec['reducer']}")
        except ImportError as e:
            raise SystemExit(f"per-layer metric {m['name']}: unknown "
                             f"reducer kind {spec['reducer']!r}: {e}")
        layers.append(LayerMetric(m["name"], m["unit"], mod.reduce,
                                  spec.get("args", {})))
    return Cell(name, int(w["chips"]), _json(cfg_path),
                _json(traffic_path), e2e, layers)


def problems(root: str = ROOT) -> List[str]:
    """Everything about ``BENCHMARK.json`` and the files it names that
    the harness can check without running: allowed characters, unique
    names, every file there, every metric's cells reporting what it
    moves. Empty when sound."""
    man = manifest(root)
    out: List[str] = []
    names: Dict[str, str] = {}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for m in man[kind]:
            if not NAME.match(m["name"]):
                out.append(f"{kind}: name {m['name']!r} has a character "
                           f"that is not allowed")
            key = ("metric" if kind in ("end_to_end", "per_layer")
                   else kind) + ":" + m["name"]
            if key in names:
                out.append(f"{key} appears twice")
            names[key] = kind
            if "unit" in m and not UNIT.match(m["unit"]):
                out.append(f"{m['name']}: unit {m['unit']!r}")
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = [w["name"] for w in man["workloads"]]
    for w in man["workloads"]:
        for key in ("config", "traffic"):
            if not NAME.match(w[key]):
                out.append(f"{w['name']}: {key} {w[key]!r}")
        try:
            cell = load_cell(w["name"], root=root)
            load_cell(w["name"], rehearse=True, root=root)
        except SystemExit as e:
            out.append(str(e))
            continue
        if len(cell.end_to_end) < 2 or not cell.per_layer:
            out.append(f"{w['name']}: needs setup_s, another end-to-end "
                       f"metric and a per-layer metric")
    for m in man["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves {m['moves']!r}, which is no "
                       f"end-to-end metric")
            continue
        for c in m.get("workloads", cells):
            if c not in cells:
                out.append(f"{m['name']}: unknown cell {c!r}")
            elif not _reports(e2e[m["moves"]], c):
                out.append(f"{m['name']}: cell {c} does not report "
                           f"{m['moves']}")
    # the bounds against the runs they were derived from
    # (benchmark/bounds/: a new cell needs no record there), and an
    # open-loop mix's rate against the sweeps it records (PR 36)
    from . import bounds
    out += bounds.problems(man)
    for mix in sorted({w["traffic"] for w in man["workloads"]}):
        path = os.path.join(HERE, "traffic", mix + ".json")
        if os.path.isfile(path):
            out += bounds.rate_problems(mix, _json(path))
    return out
