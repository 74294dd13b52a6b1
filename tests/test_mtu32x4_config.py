"""The deployment `wifi-a-mtu-32s-dp4` and its cell `mtu32x4.saturated`
(ISSUE 37) as files: arithmetic on the real configuration, its tiny
twin and `BENCHMARK.json`, no JAX.

What the deployment fixes is how the fleet maps onto the chips of a
host (32 sessions, 8 lanes a chip, placed by the runtime's own rule:
tests/test_fleet_placement.py). Every width, the population, the
channel and the guarantees are `wifi-a-mtu-8s`'s, letter for letter,
and the harness builds its `ServeConfig` from the same five geometry
keys: nothing in the file names the placement.
"""

import json
import os

import pytest

from benchmark.harness import counts, load, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME, CELL = "wifi-a-mtu-32s-dp4", "mtu32x4.saturated"
NEW_METRICS = {"lanes_per_chip": ("fleet placement", "program_counter"),
               "put_ms_per_step": ("host to device transfer",
                                   "program_span"),
               "pull_scan_ms_per_step": ("device to host pull and emit",
                                         "program_span"),
               "pull_decode_ms_per_step": ("device to host pull and emit",
                                           "program_span")}


def _cfg(name: str, twin: bool = False) -> dict:
    path = "benchmark/tests/rehearse" if twin else "benchmark/configs"
    with open(os.path.join(ROOT, path, name + ".json")) as f:
        return json.load(f)


CFG, MTU = _cfg(NAME), _cfg("wifi-a-mtu-8s")
MAN = manifest.manifest()


def test_the_manifest_is_sound_with_the_four_chip_cell():
    assert manifest.problems() == []
    cell = manifest.load_cell(CELL)
    assert cell.config["name"] == NAME and cell.chips == 4
    assert cell.traffic["loop"] == "closed"
    assert {m["name"] for m in cell.end_to_end} \
        == {"samples_per_s", "setup_s"}
    assert len(MAN["workloads"]) >= 5
    assert [w["name"] for w in MAN["workloads"] if w["chips"] == 4] \
        == [CELL]
    # appended behind the four of its day, not put in; later cells
    # (PR 43's `maxpsdu8.saturated`) follow it
    assert MAN["workloads"][4]["name"] == CELL


def test_the_cell_reads_what_mtu8_saturated_reads_but_one_and_four_more():
    """Every per-layer metric of `mtu8.saturated` but `acs_roofline`:
    its operations are counted for all 32 lanes and its kernel time is
    device 0's 8, so it would read four times too high here (left to a
    `benchmark` issue). The four new ones are this cell's alone; the
    one PR 40 appended behind them (`decode_ready_share`) it shares,
    and so it does the whole-window readings PR 41 appended."""
    names = [m.name for m in manifest.load_cell(CELL).per_layer]
    mtu8 = [m.name for m in manifest.load_cell("mtu8.saturated").per_layer]
    assert "acs_roofline" in mtu8 and "acs_roofline" not in names
    # ... and but `scan_derotate_ms` (PR 45), which lists the one-chip
    # cells PR 44 measured the fusion in
    assert [n for n in names if n not in NEW_METRICS] \
        == [n for n in mtu8
            if n not in ("acs_roofline", "scan_derotate_ms")]
    at = names.index("decode_ready_share")
    assert names[at - 4:at + 1] \
        == list(NEW_METRICS) + ["decode_ready_share"]
    by = {m["name"]: m for m in MAN["per_layer"]}
    for n, (layer, source) in NEW_METRICS.items():
        assert by[n]["workloads"] == [CELL], n
        assert (by[n]["layer"], by[n]["source"]) == (layer, source)
        assert by[n]["moves"] == "samples_per_s"
    # a new reading rides a reducer the benchmark already had
    for n in NEW_METRICS:
        with open(os.path.join(ROOT, "benchmark/layer_metrics",
                               n + ".json")) as f:
            spec = json.load(f)
        assert spec["reducer"] in ("span_arg_ratio", "span_self_time")
    with open(os.path.join(ROOT, "benchmark/layer_metrics",
                           "lanes_per_chip.json")) as f:
        assert json.load(f)["args"] == {
            "span": "rx.fleet.put", "numerator": "lanes",
            "denominator": "devices"}


def test_source_matches_the_manifest_and_nothing_is_cut():
    entry = {c["name"]: c for c in MAN["configs"]}[NAME]
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == CFG["reduced"] == []
    # two deployments from one standard: sources that differ
    assert CFG["source"] != MTU["source"]
    assert "Annex E" in CFG["source"] and "cl.18" in CFG["source"]
    assert len({c["source"] for c in MAN["configs"]}) \
        == len(MAN["configs"])


def test_everything_but_the_fleet_width_is_wifi_a_mtu_8s():
    assert set(CFG) == set(MTU)
    assert set(CFG["geometry"]) == set(MTU["geometry"])
    assert CFG["sessions"] == CFG["geometry"]["n_lanes"] == 32
    assert MTU["sessions"] == MTU["geometry"]["n_lanes"] == 8
    assert {k: v for k, v in CFG["geometry"].items() if k != "n_lanes"} \
        == {k: v for k, v in MTU["geometry"].items() if k != "n_lanes"}
    same = set(CFG) - {"name", "source", "deployment", "sessions",
                       "geometry"}
    assert {"population", "channel", "guarantees", "precision",
            "assumed", "sample_rate_hz", "no_frame_unsent"} <= same
    for key in same:
        assert CFG[key] == MTU[key], key
    assert "sessions" in CFG["assumed"]
    for word in ("32 sessions", "4 chips", "8 lanes a chip",
                 "runtime's rule"):
        assert word in CFG["deployment"], word


def test_the_twin_is_the_mtu_twin_at_32_lanes():
    twin, mtu_twin = _cfg(NAME, twin=True), _cfg("wifi-a-mtu-8s", twin=True)
    assert set(twin) == set(mtu_twin)
    assert twin["name"] == NAME
    assert twin["sessions"] == twin["geometry"]["n_lanes"] == 32
    for key in set(twin) - {"name", "sessions", "geometry"}:
        assert twin[key] == mtu_twin[key], key
    assert {k: v for k, v in twin["geometry"].items() if k != "n_lanes"} \
        == {k: v for k, v in mtu_twin["geometry"].items()
            if k != "n_lanes"}


@pytest.mark.parametrize("seed", [0, 3700000001, 2 ** 31 + 5])
def test_every_session_has_a_lap_of_its_own(seed):
    """32 sessions on 8 rate orders: session i and i + 8 send the same
    rates in the same order and differ in every seeded draw."""
    pop = CFG["population"]
    plans = [load.plan_lap(pop, seed, i) for i in range(CFG["sessions"])]
    for i, (rates, psdus, _lead, gaps, starts, tail) in enumerate(plans):
        assert rates == [pop["rates_mbps"][(i + j) % 8] for j in range(16)]
        assert all(p.size == 1500 for p in psdus)
        assert tail >= pop["gap_samples"][0]
        assert starts[-1] + counts.frame_samples(1504, rates[-1]) + tail \
            == pop["lap_samples"]
        assert gaps.min() >= 300 and gaps.max() < 600
    for i in range(8):
        for j in (8, 16, 24):
            assert plans[i][0] == plans[i + j][0]
            assert not (plans[i][1][0] == plans[i + j][1][0]).all()
            assert not (plans[i][3] == plans[i + j][3]).all()


def test_the_stream_is_what_the_cell_says_it_is():
    """2 097 152 samples a tick, 33.56 MB up a chunk-step and 8.41 MB
    down at the ceiling: four times `mtu8.saturated`'s."""
    g = CFG["geometry"]
    stride = g["chunk_len"] - g["frame_len"]
    assert CFG["sessions"] * stride == 2_097_152
    up = counts.scan_h2d_bytes(g["n_lanes"], g["chunk_len"]) \
        + counts.decode_h2d_bytes(g["n_lanes"], g["max_frames_per_chunk"])
    assert up == 4 * (counts.scan_h2d_bytes(8, g["chunk_len"])
                      + counts.decode_h2d_bytes(8, 8)) == 33_558_912
    why = {w["name"]: w["why"] for w in MAN["workloads"]}[CELL]
    assert "33.6 MB" in why and "chip 0" in why and len(why) <= 200
