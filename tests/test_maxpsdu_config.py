"""The deployment `wifi-a-maxpsdu-8s` as the benchmark's generator plans
it (ISSUE 43): arithmetic on the real configuration file and its tiny
twin, no JAX.

Every frame is the longest PSDU the 12-bit LENGTH field announces
(4095 bytes, FCS included) at each of the eight rates. What makes the
deployment is the window: its 6 and 9 Mbit/s frames are longer than
the 65 536 samples every other cell's window holds, and every frame
fits the 131 072 this one compiles. The twin keeps that property at
its own sizes.
"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import counts, load, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME, CELL = "wifi-a-maxpsdu-8s", "maxpsdu8.saturated"
ALL = [6, 9, 12, 18, 24, 36, 48, 54]
SEEDS = list(range(10)) + [4300000001, 2 ** 31 + 5]


def _cfg(twin: bool = False, name: str = NAME):
    path = ("benchmark/tests/rehearse" if twin else "benchmark/configs")
    with open(os.path.join(ROOT, path, name + ".json")) as f:
        return json.load(f)


CFG = _cfg()
POP, GEO = CFG["population"], CFG["geometry"]
SESSIONS = range(CFG["sessions"])
KINDS = pytest.mark.parametrize("twin", [False, True],
                                ids=["real", "twin"])


def _frame_samples(pop):
    (body,) = pop["psdu_bytes"]
    return {m: counts.frame_samples(body + 4, m)
            for m in pop["rates_mbps"]}


def test_the_manifest_is_sound_with_the_new_cell():
    assert manifest.problems() == []
    cell = manifest.load_cell(CELL)
    assert cell.config["name"] == NAME and cell.chips == 1
    assert cell.traffic == manifest.load_cell("mtu8.saturated").traffic
    assert cell.traffic["slab_samples"] == "stride"
    assert {m["name"] for m in cell.end_to_end} \
        == {"samples_per_s", "setup_s"}


def test_the_cell_reads_what_mtu8_saturated_reads_and_two_more():
    per_layer = manifest.manifest()["per_layer"]
    mine = {m["name"] for m in per_layer if CELL in m["workloads"]}
    mtu = {m["name"] for m in per_layer
           if "mtu8.saturated" in m["workloads"]}
    new = {"window_fill_share", "truncated_share"}
    assert mine == mtu | new and not mtu & new
    assert {"acs_roofline", "d2h_bytes_per_step"} <= mine
    for m in per_layer:
        if m["name"] in new:
            # listed for this cell alone, and read over the whole
            # window by a reducer the benchmark already had
            assert m["workloads"] == [CELL]
            assert m["moves"] == "samples_per_s" and m["unit"] == "%"
            with open(os.path.join(ROOT, "benchmark/layer_metrics",
                                   m["name"] + ".json")) as f:
                assert json.load(f)["reducer"] == "window_arg_ratio"
    assert {m.name for m in manifest.load_cell(CELL).per_layer} == mine


def test_source_matches_the_manifest_and_nothing_is_cut():
    entry = {c["name"]: c for c in manifest.manifest()["configs"]}[NAME]
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert "4095" in CFG["source"] and "LENGTH" in CFG["source"]
    assert entry["reduced"] == CFG["reduced"] == []
    mtu = _cfg(name="wifi-a-mtu-8s")
    assert set(CFG) == set(mtu)
    assert set(GEO) == set(mtu["geometry"])
    assert set(POP) == set(mtu["population"])
    for key in ("guarantees", "channel", "precision", "sample_rate_hz",
                "sessions", "no_frame_unsent"):
        assert CFG[key] == mtu[key], key
    for key in ("rates_mbps", "rule", "gap_samples", "lead_samples",
                "frames_per_lap", "add_fcs"):
        assert POP[key] == mtu["population"][key], key
    assert POP["rates_mbps"] == ALL
    # the longest PSDU the LENGTH field announces, FCS included
    assert [b + 4 for b in POP["psdu_bytes"]] == [counts.MAX_PSDU_BYTES]


def test_the_window_is_the_power_of_two_the_longest_frame_needs():
    """The receiver's own rules (`utils/geometry.py`) at this
    deployment: the capture bucket of the 6 Mbit/s frame is the
    window, the symbol bucket that of the symbols the window holds,
    and the chunk twice the window as in every cell."""
    from ziria_tpu.utils.geometry import DEFAULT

    longest = max(_frame_samples(POP).values())
    assert longest == 400 + 80 * 1366 == 109680
    assert GEO["frame_len"] == DEFAULT.capture_bucket(longest) == 131072
    assert GEO["chunk_len"] == 2 * GEO["frame_len"]
    assert GEO["symbol_bucket"] == DEFAULT.sym_bucket(
        (GEO["frame_len"] - counts.FRAME_DATA_START) // 80) == 2048
    # one whole ACS tile of decode slots, each at the bound trellis
    assert GEO["n_lanes"] * GEO["max_frames_per_chunk"] == 128
    assert counts.trellis_steps(GEO["symbol_bucket"]) == 152 * 216


@KINDS
def test_two_rates_outgrow_half_the_window_and_all_fit_it(twin):
    cfg = _cfg(twin)
    win = cfg["geometry"]["frame_len"]
    by_rate = _frame_samples(cfg["population"])
    assert sorted(by_rate) == ALL
    assert {m for m, n in by_rate.items() if n > win // 2} == {6, 9}
    assert all(n <= win for n in by_rate.values())
    if not twin:
        assert win // 2 == 65536        # every other cell's window
        assert by_rate == {6: 109680, 9: 73280, 12: 55040, 18: 36880,
                           24: 27760, 36: 18640, 48: 14080, 54: 12560}
    # the symbol bucket the window's rule gives holds the longest
    assert counts.n_symbols(cfg["population"]["psdu_bytes"][0] + 4, 6) \
        <= cfg["geometry"]["symbol_bucket"]


@pytest.mark.parametrize("i", SESSIONS)
def test_every_lap_holds_each_rate_twice(i):
    rates, psdus, *_ = load.plan_lap(POP, 0, i)
    assert sorted(rates) == sorted(ALL * 2)
    assert {p.size for p in psdus} == {4091}
    assert rates[0] == ALL[i % 8]       # session i opens at rate i


@KINDS
@pytest.mark.parametrize("seed", SEEDS)
def test_every_lap_fits_with_its_tail(seed, twin):
    cfg = _cfg(twin)
    pop, geo = cfg["population"], cfg["geometry"]
    stride = geo["chunk_len"] - geo["frame_len"]
    assert pop["lap_samples"] % stride       # boundaries walk the lap
    for i in range(cfg["sessions"]):
        _r, _p, lead, gaps, starts, tail = load.plan_lap(pop, seed, i)
        assert tail >= pop["gap_samples"][0]
        assert lead == 60
        assert gaps.min() >= 300 and gaps.max() < 600
        assert starts[-1] < pop["lap_samples"]


def _owned_counts(cfg, seed):
    """Frame starts per owned window (the stride, plus the 224-sample
    sliver the overflow scan also counts) over sixteen replayed laps
    of every session."""
    pop, geo = cfg["population"], cfg["geometry"]
    stride = geo["chunk_len"] - geo["frame_len"]
    L = pop["lap_samples"]
    worst = 0
    for i in range(cfg["sessions"]):
        starts = load.plan_lap(pop, seed, i)[4]
        every = np.concatenate([starts + k * L for k in range(16)])
        lo = np.arange(0, every[-1], stride)
        n = np.searchsorted(every, lo + stride + 224) \
            - np.searchsorted(every, lo)
        worst = max(worst, int(n.max()))
    return worst


@KINDS
def test_k_is_at_least_twice_the_densest_owned_window(twin):
    cfg = _cfg(twin)
    worst = max(_owned_counts(cfg, s) for s in SEEDS[:4])
    assert 2 * worst <= cfg["geometry"]["max_frames_per_chunk"]
    if not twin:
        assert worst == 6


def test_the_recorded_runs_spread_by_under_half_the_bound():
    """`benchmark/bounds/cells/maxpsdu8.saturated.json`: two sets of
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
