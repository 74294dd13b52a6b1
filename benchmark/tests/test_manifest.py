"""BENCHMARK.json against the contract's limits on names and units, and
every file it names resolving."""

import json
import os
import re

from benchmark.harness import manifest

NAME, UNIT = manifest.NAME, manifest.UNIT
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_keys_names_and_units():
    man = manifest.manifest()
    assert set(man) == KEYS
    for kind, keys in ENTRY_KEYS.items():
        for e in man[kind]:
            assert set(e) - {"workloads"} == keys, (kind, e["name"])
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k], (e["name"], k)
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) < 64 << 10
    assert "setup_s" in {m["name"] for m in man["end_to_end"]}
    assert 1 <= man["run_seconds"] <= 51


def test_every_file_resolves_and_every_metric_has_its_cells():
    assert manifest.problems() == []


def test_files_under_paths_are_named_from_allowed_characters():
    for base, _dirs, files in os.walk(manifest.HERE):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_configs_state_what_the_contract_asks():
    man = manifest.manifest()
    for c in man["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and cfg["assumed"]
