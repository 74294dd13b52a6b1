"""The rehearsal run of ``dense54.saturated`` (ISSUE 45): the cell's
control flow at its tiny twin (tests/rehearse/wifi-a-dense54-8s.json:
96-byte PSDUs at 54 Mbit/s a SIFS to a DIFS apart, every frame 0.0366
rad/sample off carrier), on any backend, would print ``correct`` true:
the two float comparisons included, at the offset the real cell runs.
A minute on a CPU, which is why it is here and not under tier-1's
``tests/``."""

import argparse

from benchmark.harness import cell


def test_the_rehearsal_of_dense54_saturated_is_correct():
    args = argparse.Namespace(workload="dense54.saturated",
                              seed=2 ** 31 + 45, seconds=3.0, trace=0,
                              rehearse=True)
    line, compared = cell.measure(args)
    assert line["correct"] and line["failed"] == 0
    # eight sessions, about a frame every 1220 samples of a 4096 stride
    assert line["attempted"] > 100
    for name in ("overflow_chunks", "reference_disagreements",
                 "degraded", "quarantines", "compiles_in_window",
                 "contractions_below_highest"):
        assert compared[name] == 0, name
    assert compared["reference_captures_compared"] == 2
    assert compared["float_frames_compared"] >= 1
    assert compared["dispatches_per_chunk_step"] <= 2.0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
