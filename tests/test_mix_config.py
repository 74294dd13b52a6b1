"""The deployment `wifi-a-mix-8s` as the benchmark's generator plans it
(ISSUE 33): arithmetic on the real configuration file, no JAX.

The file states an exchange (DATA, ACK, DATA, ACK, TCP-ACK, ACK, each
802.11 ACK at the highest basic rate not above the rate of the frame
it answers) as two cyclic lists that `load.plan_lap` indexes by
``i + j``. These tests hold the lists to the standard's rule for every
session, and the lap to its length for every seed the builder ran.
"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import counts, load, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASIC = (6, 12, 24)             # the mandatory clause-18 rates
SIFS = 320                      # aSIFSTime, 16 us at 20 MS/s
SEEDS = list(range(10)) + [3300000001, 2 ** 31 + 5]


def _cfg(twin: bool = False):
    path = ("benchmark/tests/rehearse" if twin else "benchmark/configs")
    with open(os.path.join(ROOT, path, "wifi-a-mix-8s.json")) as f:
        return json.load(f)


CFG = _cfg()
POP = CFG["population"]
SESSIONS = range(CFG["sessions"])


def _ack_rate(mbps: int) -> int:
    return max(r for r in BASIC if r <= mbps)


def _sizes(pop, i):
    n = len(pop["psdu_bytes"])
    return [pop["psdu_bytes"][(i + j) % n]
            for j in range(pop["frames_per_lap"])]


def test_the_manifest_is_sound_with_the_new_cell():
    assert manifest.problems() == []
    cell = manifest.load_cell("mix8.saturated")
    assert cell.config["name"] == "wifi-a-mix-8s" and cell.chips == 1
    assert cell.traffic["loop"] == "closed"
    names = {m.name for m in cell.per_layer}
    assert {"trellis_fill_share", "slot_fill_share",
            "emit_ms_per_step", "decode_ready_share"} <= names
    # the two counts that read stale since PR 32 get no new cell
    assert not {"acs_roofline", "d2h_bytes_per_step"} & names
    assert {m["name"] for m in cell.end_to_end} \
        == {"samples_per_s", "setup_s"}


def test_source_matches_the_manifest_and_no_width_is_cut():
    entry = {c["name"]: c for c in manifest.manifest()["configs"]}[
        "wifi-a-mix-8s"]
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert entry["reduced"] == CFG["reduced"] == []
    mtu = json.load(open(os.path.join(
        ROOT, "benchmark/configs/wifi-a-mtu-8s.json")))
    assert set(CFG) == set(mtu)
    assert set(CFG["geometry"]) == set(mtu["geometry"])
    # the same widths as every accepted cell; only K differs
    assert {k: v for k, v in CFG["geometry"].items()
            if k != "max_frames_per_chunk"} \
        == {k: v for k, v in mtu["geometry"].items()
            if k != "max_frames_per_chunk"}
    assert CFG["guarantees"] == mtu["guarantees"]
    assert CFG["channel"] == mtu["channel"]
    assert CFG["no_frame_unsent"] is False
    # the 1500-byte convention, said in words where the two files meet
    assert max(POP["psdu_bytes"]) == max(mtu["population"]["psdu_bytes"])
    assert "1500" in CFG["deployment"] and "FCS" in CFG["deployment"]


@pytest.mark.parametrize("twin", [False, True], ids=["real", "twin"])
@pytest.mark.parametrize("i", SESSIONS)
def test_every_ack_answers_at_the_highest_basic_rate_below(i, twin):
    pop = _cfg(twin)["population"]
    rates, psdus, _lead, _gaps, _starts, _tail = load.plan_lap(pop, 0, i)
    sizes = _sizes(pop, i)
    assert [p.size for p in psdus] == sizes
    ack = min(pop["psdu_bytes"])
    assert ack + 4 == 14
    n_acks = 0
    for j in range(1, len(rates)):
        if sizes[j] != ack:
            continue
        n_acks += 1
        assert sizes[j - 1] != ack          # it answers a data frame
        assert rates[j] == _ack_rate(rates[j - 1]), (i, j)
    # half of all frames; one that opens the lap answers the frame
    # that closed the lap before it
    assert n_acks + (sizes[0] == ack) == len(rates) // 2
    if sizes[0] == ack:
        assert rates[0] == _ack_rate(rates[-1])


@pytest.mark.parametrize("i", SESSIONS)
def test_every_lap_holds_all_eight_rates_and_three_sizes(i):
    rates, psdus, *_ = load.plan_lap(POP, 0, i)
    sizes = [p.size for p in psdus]
    assert sorted(set(rates)) == [6, 9, 12, 18, 24, 36, 48, 54]
    assert sorted(set(sizes)) == [10, 72, 1500]
    assert sizes.count(10) == 24 and sizes.count(72) == 8 \
        and sizes.count(1500) == 16
    # every session's lap is the same 48 (size, rate) pairs, rotated
    pairs = sorted(zip(sizes, rates))
    r0, p0, *_ = load.plan_lap(POP, 0, 0)
    assert pairs == sorted(zip([p.size for p in p0], r0))
    # each data size at each of the eight rates, the ACK at the three
    by_size = {b: {m for s, m in pairs if s == b} for b in (10, 72, 1500)}
    assert by_size[1500] == by_size[72] == set(rates)
    assert by_size[10] == set(BASIC)
    # a 14-byte ACK is 2 to 6 symbols on air
    assert {counts.frame_samples(14, m) for m in BASIC} \
        == {560, 640, 880}


@pytest.mark.parametrize("seed", SEEDS)
def test_every_lap_fits_with_a_tail_and_sifs_gaps(seed):
    stride = CFG["geometry"]["chunk_len"] - CFG["geometry"]["frame_len"]
    assert POP["lap_samples"] % stride       # boundaries walk the lap
    for i in SESSIONS:
        _r, _p, lead, gaps, starts, tail = load.plan_lap(POP, seed, i)
        assert tail >= SIFS
        assert lead == 60
        assert gaps.min() >= SIFS and gaps.max() < 680
        assert starts[-1] < POP["lap_samples"]


def _owned_counts(pop, geo, seed):
    """Frame starts per owned window (the stride, plus the 224-sample
    sliver the overflow scan also counts) over ten replayed laps of
    every session."""
    stride = geo["chunk_len"] - geo["frame_len"]
    L = pop["lap_samples"]
    worst = 0
    for i in SESSIONS:
        starts = load.plan_lap(pop, seed, i)[4]
        every = np.concatenate([starts + k * L for k in range(10)])
        lo = np.arange(0, every[-1], stride)
        n = np.searchsorted(every, lo + stride + 224) \
            - np.searchsorted(every, lo)
        worst = max(worst, int(n.max()))
    return worst


@pytest.mark.parametrize("twin", [False, True], ids=["real", "twin"])
def test_k_holds_the_densest_owned_window(twin):
    cfg = _cfg(twin)
    k = cfg["geometry"]["max_frames_per_chunk"]
    worst = max(_owned_counts(cfg["population"], cfg["geometry"], s)
                for s in SEEDS[:4])
    # the real width: 16 in the densest window, so K = 16 has no room
    # for one false plateau; the file states 32
    assert worst <= k // 2
    if not twin:
        assert worst == 16 and k == 32
