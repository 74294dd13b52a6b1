"""``BENCHMARK.json``'s bounds against the runs ``benchmark/bounds/``
records and the rule it states; ``traffic/paced.json``'s rate against
the sweeps it records. Arithmetic on data files: no JAX, no chip."""

import json
import os
import shutil

import pytest

from benchmark import sweep
from benchmark.harness import bounds, manifest, steady

MAN = manifest.manifest()
REC = bounds.load()
RULE = REC["rule"]
E2E = {m["name"]: m for m in MAN["end_to_end"]}
DERIVED = [(name, cell) for name, m in sorted(REC["metrics"].items())
           for cell in m.get("derived_from", [])]
with open(os.path.join(manifest.HERE, "traffic", "paced.json")) as _f:
    PACED = json.load(_f)


def sets_of(name, cell):
    return REC["cells"][cell]["metrics"][name]


def test_the_two_measures_of_a_sets_spread():
    v = [100.0, 101.0, 102.0, 103.0, 104.0, 120.0]
    # statistics.quantiles, exclusive: Q1 = 100.75, Q3 = 108.0
    assert steady.quartile_spread(v) == pytest.approx(7.25 / 102.5)
    # the farthest run (120) left out: 100 to 104 over their median
    assert steady.without_farthest(v) == [102.0, 103.0, 101.0, 104.0, 100.0]
    assert steady.trimmed_range(v) == pytest.approx(4.0 / 102.0)
    assert steady.trimmed_range([5.0] * 6) == 0.0


def test_tick_summary_finds_long_ticks_and_a_cold_start():
    ticks = [30.0] * 50 + [26.0] * 49 + [130.0]
    got = steady.tick_summary(ticks)
    assert got["tick_median_ms"] == 30.0
    assert got["long_tick_share"] == pytest.approx(130.0 / sum(ticks))
    assert got["first_over_second"] == pytest.approx(
        30.0 / ((49 * 26.0 + 130.0) / 50))
    assert steady.tick_summary([1.0, 2.0]) == {}
    # nine steps in ten launch nothing: the plain median is theirs,
    # the time is the blocking ones'
    steps = [0.2] * 90 + [25.0] * 10
    assert steady.time_weighted_median(steps) == 25.0
    assert steady.time_weighted_median([]) == 0.0


def test_benchmark_json_holds_what_the_recorded_runs_derive():
    assert bounds.problems(MAN) == []
    assert set(REC["metrics"]) == set(E2E)
    assert REC["metrics"]["setup_s"]["fixed"] == E2E["setup_s"]["bound"] \
        == 0.25
    assert RULE["run_seconds"] == MAN["run_seconds"]
    # every cell the benchmark has today is recorded, in every
    # end-to-end metric it reports
    cells = [w["name"] for w in MAN["workloads"]]
    assert sorted(REC["cells"]) == sorted(cells)
    for m in MAN["end_to_end"]:
        for cell in m.get("workloads", cells):
            assert m["name"] in REC["cells"][cell]["metrics"]


@pytest.mark.parametrize("name", sorted({n for n, _c in DERIVED}))
def test_a_bound_is_the_rules_result_under_its_ceiling(name):
    got = REC["metrics"][name]
    bound = E2E[name]["bound"]
    asks = bounds.rule_asks(name, REC)
    assert bound == bounds.derive(name, REC) == min(asks, got["ceiling"])
    assert RULE["floor"] <= bound <= got["ceiling"] <= 0.25
    # a ceiling that cuts the rule's result is said, never silent
    assert ("over_ceiling" in got) == (asks > got["ceiling"])
    sets = [v for c in got["derived_from"] for v in sets_of(name, c)]
    # the driver's own limits: not under twice a set's spread with its
    # farthest run left out, not over eight times the widest spread of
    # all the runs (a bound of 1% is never too loose)
    assert bound >= 2 * max(s["quartile_trimmed"]
                            for s in bounds.spreads(sets))
    assert bound <= 8 * max(steady.quartile_spread(v) for v in sets) \
        or bound == RULE["floor"]


@pytest.mark.parametrize("name, cell", DERIVED)
def test_every_recorded_set_spreads_by_at_most_half_the_bound(name, cell):
    rec = REC["cells"][cell]
    assert len(sets_of(name, cell)) == 2 and len(rec["seeds"]) >= 6
    assert len(set(rec["seeds"])) == len(rec["seeds"])
    for s in bounds.spreads(sets_of(name, cell)):
        assert 2 * s["trimmed_range"] <= E2E[name]["bound"]
        assert 2 * s["quartile_trimmed"] <= E2E[name]["bound"]


def test_a_bound_that_parts_from_its_record_is_a_problem():
    man = json.loads(json.dumps(MAN))
    next(m for m in man["end_to_end"]
         if m["name"] == "samples_per_s")["bound"] = 0.011
    assert any("samples_per_s" in p for p in bounds.problems(man))
    rec = json.loads(json.dumps(REC))
    cell = REC["metrics"]["emit_delay_p50_ms"]["derived_from"][0]
    sets = rec["cells"][cell]["metrics"]["emit_delay_p50_ms"]
    sets[0] = [v * (1 + 0.02 * i) for i, v in enumerate(sets[0])]
    assert any("emit_delay_p50_ms" in p for p in bounds.problems(MAN, rec))
    rec = json.loads(json.dumps(REC))
    del rec["cells"][cell]
    assert any("not recorded" in p for p in bounds.problems(MAN, rec))


NEW = "mtu32x4.saturated"


def with_new_cell(new=NEW, config="wifi-a-mtu-32s-dp4", chips=4):
    """BENCHMARK.json as the next ``model_config`` PR leaves it: one
    more cell, reporting ``samples_per_s`` and ``setup_s``."""
    man = json.loads(json.dumps(MAN))
    man["workloads"].append({"name": new, "config": config,
                             "traffic": "saturated", "chips": chips,
                             "why": "x"})
    next(m for m in man["end_to_end"]
         if m["name"] == "samples_per_s")["workloads"].append(new)
    return man


def adding_a_cell(new, man, tmp_path):
    """A PR that adds a cell may add files and entries and may not edit
    a file the benchmark has: with nothing added under ``bounds/`` the
    bounds hold as they are."""
    assert bounds.problems(man) == []
    assert bounds.derive("samples_per_s", REC) \
        == E2E["samples_per_s"]["bound"]
    # and where it records its runs, it adds ONE file, which is held
    # to the half rule and moves no bound however widely it spreads
    there = str(tmp_path / "bounds")
    shutil.copytree(bounds.DIR, there)
    before = {f: open(os.path.join(d, f)).read()
              for d, _s, fs in os.walk(there) for f in fs}
    half = 0.5 * E2E["samples_per_s"]["bound"]
    for width, ok in ((0.8 * half, True), (1.6 * half, False)):
        runs = [4.0e7 * (1 + width * (i / 4 - 0.5)) for i in range(5)]
        with open(os.path.join(there, "cells", new + ".json"), "w") as f:
            json.dump({"cell": new, "seeds": [1, 2, 3, 4, 5, 6],
                       "metrics": {"samples_per_s": [runs + [3.0e7]] * 2,
                                   "setup_s": [[40.0] * 6] * 2}}, f)
        rec = bounds.load(there)
        assert bounds.derive("samples_per_s", rec) \
            == E2E["samples_per_s"]["bound"]
        got = bounds.problems(man, rec)
        assert (got == []) == ok
        assert all(new in p and "half" in p for p in got)
    after = {f: open(os.path.join(d, f)).read()
             for d, _s, fs in os.walk(there) for f in fs
             if f != new + ".json"}
    assert after == before
    # a record of a cell BENCHMARK.json does not have is a problem
    assert any(new in p for p in bounds.problems(MAN, rec))


def test_a_new_cell_needs_no_edit_of_a_file_that_is_there(tmp_path):
    """Names a cell the benchmark has had since PR 37, and fails on it:
    `tests/test_benchmark_harness.py` expects that (KNOWN_FAILURES,
    strict), and this PR's kind may not edit that file. The case below
    is this one repaired; the PR that strikes the line there deletes
    this one."""
    adding_a_cell(NEW, with_new_cell(), tmp_path)


def test_a_cell_the_benchmark_lacks_needs_no_edit_of_a_file_that_is_there(
        tmp_path):
    new = "mtu8.overload"
    assert new not in [w["name"] for w in MAN["workloads"]]
    adding_a_cell(new, with_new_cell(new, "wifi-a-mtu-8s", 1), tmp_path)


# ------------------------------------- the paced rate and its sweep


def test_the_paced_rate_is_its_share_of_the_recorded_knee():
    assert PACED["share_of_knee"] in (0.8, 0.7, 0.6)
    assert PACED["rate_samples_per_s"] == bounds.paced_rate(
        PACED["knee_samples_per_s"], PACED["share_of_knee"])
    assert PACED["rate_samples_per_s"] % 100000 == 0
    assert bounds.paced_rate(14.4e6, 0.8) == 11500000
    assert str(PACED["knee_samples_per_s"]) in PACED["rate_is"] \
        or f"{PACED['knee_samples_per_s'] / 1e6:g} M" in PACED["rate_is"]


def rows(*held):
    return {"rows": [{"rate": float(i + 1), "sustained": h}
                     for i, h in enumerate(held)]}


def test_the_knee_is_under_the_lowest_rate_that_broke_in_two_sweeps():
    sweeps = PACED["sweeps"]
    need = bounds.BROKEN_IN
    assert need == 2 and len(sweeps) >= need
    for sw in sweeps:
        assert len(sw["rows"]) >= 8
        assert min(r["rate"] for r in sw["rows"]) <= 2e6 \
            and max(r["rate"] for r in sw["rows"]) >= 20e6
        for r in sw["rows"]:
            assert r["sustained"] == (not r["broke"])
    # the newest is the committed harness's own
    assert "committed harness" in sweeps[-1]["harness"]
    knee = PACED["knee_samples_per_s"]
    assert knee == bounds.knee(sweeps)
    times_broken = {}
    for sw in sweeps:
        for r in sw["rows"]:
            times_broken[r["rate"]] = times_broken.get(r["rate"], 0) \
                + (not r["sustained"])
    assert all(n < need for r, n in times_broken.items() if r <= knee)
    assert times_broken[min(r for r in times_broken if r > knee)] >= need
    assert 256 <= PACED["slab_lo"] < PACED["slab_hi"] <= 2048
    assert bounds.rate_problems("paced", PACED) == []
    assert bounds.rate_problems("paced", dict(PACED, sweeps=sweeps[:1]))


@pytest.mark.parametrize("sweeps, need, knee", [
    ([rows(1, 1, 0, 1)], 1, 2.0),                   # one sweep alone
    ([rows(0, 1)], 1, None),                        # the lowest broke
    ([rows(1, 1, 0, 1), rows(1, 1, 1, 1)], 2, 4.0),  # a stall in one
    ([rows(1, 0, 1, 0), rows(1, 1, 1, 0)], 2, 3.0),  # both: a real break
    ([rows(1, 0, 1, 1), rows(1, 1, 0, 1), rows(1, 0, 1, 0)], 2, 1.0),
    ([rows(1, 1), rows(1, 1, 1)], 2, 3.0),          # other rates offered
], ids=["alone", "lowest-broke", "stall-in-one", "broke-in-both",
        "two-of-three", "ragged"])
def test_a_rate_is_broken_where_it_broke_in_enough_sweeps(
        sweeps, need, knee):
    assert bounds.knee(sweeps, need) == knee


BASE = dict(refused=0, staged_at_close=0, step_samples=524288,
            delay_p50_first_half_ms=60.0, delay_p50_second_half_ms=60.5,
            late_p50_ms=13.0, step_busy_p50_ms=26.0)


@pytest.mark.parametrize("change, broke", [
    ({}, []),
    ({"refused": 1}, ["refused"]),
    ({"staged_at_close": 524289}, ["staged"]),
    ({"staged_at_close": 524288}, []),
    ({"delay_p50_second_half_ms": 67.1}, ["growing"]),
    ({"delay_p50_second_half_ms": 66.9}, []),
    ({"late_p50_ms": 26.0}, ["late"]),
    ({"late_p50_ms": 25.9}, []),
    ({"refused": 3, "late_p50_ms": 90.0}, ["refused", "late"]),
], ids=lambda x: "-".join(x) if isinstance(x, list) else
    ",".join(x) or "sound")
def test_the_sweeps_rule_names_what_a_rate_broke(change, broke):
    assert sweep.sustained(dict(BASE, **change)) == broke
