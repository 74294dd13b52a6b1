"""The deployment `wifi-a-dense54-8s` as the benchmark's generator plans
it (ISSUE 45): arithmetic on the real configuration file and its tiny
twin, no JAX.

Back-to-back 1500-byte bodies at the top rate, a SIFS to a DIFS apart,
every station at the edge of the standard's carrier tolerance: 20 ppm
of channel 165's 5.825 GHz, 116.5 kHz, 0.0366 rad/sample, 366 times
what every other configuration states. What makes the deployment is
the channel; the geometry is `wifi-a-mtu-8s`'s at K = 16.
"""

import json
import math
import os

import numpy as np
import pytest

from benchmark.harness import counts, load, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME, CELL = "wifi-a-dense54-8s", "dense54.saturated"
SEEDS = list(range(4500000001, 4500000013))


def _cfg(twin: bool = False, name: str = NAME):
    path = ("benchmark/tests/rehearse" if twin else "benchmark/configs")
    with open(os.path.join(ROOT, path, name + ".json")) as f:
        return json.load(f)


CFG = _cfg()
POP, GEO = CFG["population"], CFG["geometry"]
KINDS = pytest.mark.parametrize("twin", [False, True],
                                ids=["real", "twin"])


def test_the_manifest_is_sound_with_the_new_cell():
    assert manifest.problems() == []
    cell = manifest.load_cell(CELL)
    assert cell.config["name"] == NAME and cell.chips == 1
    assert cell.traffic == manifest.load_cell("mtu8.saturated").traffic
    assert cell.traffic["slab_samples"] == "stride"
    assert {m["name"] for m in cell.end_to_end} \
        == {"samples_per_s", "setup_s"}


def test_the_cell_reads_what_maxpsdu8_reads_but_the_window_and_one_more():
    per_layer = manifest.manifest()["per_layer"]
    mine = {m["name"] for m in per_layer if CELL in m["workloads"]}
    maxpsdu = {m["name"] for m in per_layer
               if "maxpsdu8.saturated" in m["workloads"]}
    assert maxpsdu - mine == {"window_fill_share", "truncated_share"}
    assert mine - maxpsdu == set()
    assert {"acs_roofline", "d2h_bytes_per_step",
            "scan_derotate_ms"} <= mine
    (new,) = [m for m in per_layer if m["name"] == "scan_derotate_ms"]
    assert new == per_layer[-1]         # appended, not inserted
    assert new["workloads"] == [CELL, "mtu8.saturated", "mix8.saturated",
                                "maxpsdu8.saturated"]
    (gather,) = [m for m in per_layer if m["name"] == "scan_gather_ms"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert new[key] == gather[key], key
    with open(os.path.join(ROOT, "benchmark/layer_metrics",
                           "scan_derotate_ms.json")) as f:
        mine_file = json.load(f)
    with open(os.path.join(ROOT, "benchmark/layer_metrics",
                           "scan_gather_ms.json")) as f:
        gather_file = json.load(f)
    # a data file over a reducer the benchmark had, and a name the
    # gather's pattern still matches
    assert mine_file["reducer"] == gather_file["reducer"] \
        == "scope_device_time"
    import re
    assert re.search(gather_file["args"]["pattern"],
                     "rx.scan.gather/rx.scan.gather.derotate/mul")
    assert not re.search(mine_file["args"]["pattern"],
                         "rx.scan.gather/select_n")
    assert {m.name for m in manifest.load_cell(CELL).per_layer} == mine


def test_source_matches_the_manifest_and_nothing_is_cut():
    entry = {c["name"]: c for c in manifest.manifest()["configs"]}[NAME]
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    for word in ("Table 18-4", "18.3.9.5", "165"):
        assert word in CFG["source"], word
    assert entry["reduced"] == CFG["reduced"] == []
    mtu = _cfg(name="wifi-a-mtu-8s")
    assert set(CFG) == set(mtu)
    assert set(GEO) == set(mtu["geometry"])
    assert set(POP) == set(mtu["population"])
    for key in ("guarantees", "precision", "sample_rate_hz", "sessions",
                "no_frame_unsent"):
        assert CFG[key] == mtu[key], key
    # the geometry is the MTU deployment's but for K
    assert {k: v for k, v in GEO.items()
            if k != "max_frames_per_chunk"} \
        == {k: v for k, v in mtu["geometry"].items()
            if k != "max_frames_per_chunk"}
    assert GEO["max_frames_per_chunk"] == 16
    assert POP["psdu_bytes"] == mtu["population"]["psdu_bytes"] == [1500]
    assert POP["rates_mbps"] == [54] and POP["add_fcs"] is True
    # aSIFSTime 16 us and DIFS 34 us at 20 MS/s, as wifi-a-mix-8s
    assert POP["gap_samples"] == [320, 680] \
        == _cfg(name="wifi-a-mix-8s")["population"]["gap_samples"]
    assert CFG["channel"]["snr_db"] == mtu["channel"]["snr_db"]
    listed = " ".join(CFG["assumed"])
    for word in ("sessions", "snr_db", "payload", "one offset",
                 "symbol-clock", "backoff", "lap_samples",
                 "frames_per_lap", "max_frames_per_chunk", "recalled"):
        assert word in listed, word


def test_the_offset_is_20_ppm_of_channel_165_and_inside_the_reference():
    eps = CFG["channel"]["cfo_rad_per_sample"]
    exact = 2 * math.pi * 20e-6 * 5.825e9 / CFG["sample_rate_hz"]
    assert abs(exact * CFG["sample_rate_hz"] / (2 * math.pi)
               - 116.5e3) < 1
    assert float(f"{exact:.3g}") == eps == 0.0366
    # inside the plain reference's range (LTS alone, lag 64) with the
    # estimate's scatter at 30 dB to spare, and a fifth of the way to
    # the receiver's own coarse stage's (STS, lag 16)
    assert eps + 1e-3 < math.pi / 64 < 2 * eps
    assert eps < math.pi / 16 / 5
    # 366 times every other configuration's
    others = [_cfg(name=os.path.splitext(f)[0])
              for f in sorted(os.listdir(
                  os.path.join(ROOT, "benchmark/configs")))
              if f != NAME + ".json"]
    assert len(others) == 5
    assert {c["channel"]["cfo_rad_per_sample"] for c in others} == {1e-4}
    # the phase of a slot's derotation passes 2048 rad, where a float32
    # product's half ulp is 1.2e-4 rad, inside the segment
    need_b = counts.FRAME_DATA_START + 80 * GEO["symbol_bucket"]
    assert 2048 / eps < need_b == 82320


@KINDS
def test_the_frame_and_the_window(twin):
    cfg = _cfg(twin)
    pop, geo = cfg["population"], cfg["geometry"]
    (body,) = pop["psdu_bytes"]
    n = counts.frame_samples(body + 4, 54)
    if not twin:
        # 16 service + 8 x 1504 + 6 tail bits at 216 a symbol
        assert counts.n_symbols(body + 4, 54) == 56 and n == 4880
        assert geo["frame_len"] == 65536 and geo["chunk_len"] == 131072
        assert geo["symbol_bucket"] == 1024
        assert geo["n_lanes"] * geo["max_frames_per_chunk"] == 128
    assert n <= geo["frame_len"] // 4
    assert geo["chunk_len"] == 2 * geo["frame_len"]
    assert cfg["channel"] == CFG["channel"]
    assert pop["rates_mbps"] == [54] and pop["gap_samples"] == [320, 680]
    assert geo["max_frames_per_chunk"] == 16


@pytest.mark.parametrize("i", range(CFG["sessions"]))
def test_every_lap_is_51_top_rate_mtu_frames(i):
    rates, psdus, lead, gaps, starts, tail = load.plan_lap(POP, 0, i)
    assert rates == [54] * 51 and {p.size for p in psdus} == {1500}
    assert lead == 60 and gaps.min() >= 320 and gaps.max() < 680
    assert np.array_equal(np.diff(starts), 4880 + gaps)


@KINDS
@pytest.mark.parametrize("seed", SEEDS)
def test_every_lap_fits_with_its_tail(seed, twin):
    cfg = _cfg(twin)
    pop, geo = cfg["population"], cfg["geometry"]
    stride = geo["chunk_len"] - geo["frame_len"]
    # sixteen phases: boundaries walk the lap
    assert (pop["lap_samples"] * 16) % stride == 0
    assert pop["lap_samples"] % stride
    tails = [load.plan_lap(pop, seed, i)[5]
             for i in range(cfg["sessions"])]
    assert min(tails) >= pop["gap_samples"][0]
    if not twin:
        assert pop["lap_samples"] == 282624 == int(4.3125 * stride)
        assert 6000 <= min(tails) and max(tails) <= 11000


def _owned(cfg, seed):
    """(most, mean) frame starts per owned window (the stride; the most
    over the stride plus the 224-sample sliver the overflow scan also
    counts) over sixteen replayed laps of every session."""
    pop, geo = cfg["population"], cfg["geometry"]
    stride = geo["chunk_len"] - geo["frame_len"]
    L = pop["lap_samples"]
    worst, means = 0, []
    for i in range(cfg["sessions"]):
        starts = load.plan_lap(pop, seed, i)[4]
        every = np.concatenate([starts + k * L for k in range(16)])
        lo = np.arange(0, every[-1] - stride, stride)
        at = np.searchsorted(every, lo)
        worst = max(worst, int((np.searchsorted(
            every, lo + stride + 224) - at).max()))
        means.append((np.searchsorted(every, lo + stride) - at).mean())
    return worst, float(np.mean(means))


@KINDS
def test_k_leaves_three_slots_over_the_densest_owned_window(twin):
    cfg = _cfg(twin)
    got = [_owned(cfg, s) for s in SEEDS]
    worst = max(w for w, _ in got)
    assert worst + 3 <= cfg["geometry"]["max_frames_per_chunk"]
    if not twin:
        # ISSUE 45's count, and 95 of 128 slots filled a chunk-step
        assert worst == 13
        mean = float(np.mean([m for _, m in got]))
        assert 11.7 <= mean <= 12.0
        assert 93 <= 8 * mean <= 96


def test_the_tails_over_issue_45s_seeds():
    tails = [load.plan_lap(POP, s, i)[5] for s in SEEDS
             for i in range(CFG["sessions"])]
    assert (min(tails), max(tails)) == (6404, 10630)


def test_the_recorded_runs_spread_by_under_half_the_bound():
    """`benchmark/bounds/cells/dense54.saturated.json`: two sets of
    six from the final tree on the chip, each set's trimmed range at
    most half of `samples_per_s`'s bound; the record moves no bound
    (the cell is in no metric's `derived_from`)."""
    from benchmark.harness import bounds, steady

    rec = bounds.load()
    mine = rec["cells"][CELL]
    assert mine["cell"] == CELL and len(mine["seeds"]) == 6
    (bound,) = [m["bound"] for m in manifest.manifest()["end_to_end"]
                if m["name"] == "samples_per_s"]
    sets = mine["metrics"]["samples_per_s"]
    assert [len(v) for v in sets] == [6, 6]
    assert all(steady.trimmed_range(v) <= bound / 2 for v in sets)
    assert [len(v) for v in mine["metrics"]["setup_s"]] == [6, 6]
    assert all(CELL not in m.get("derived_from", [])
               for m in rec["metrics"].values())
