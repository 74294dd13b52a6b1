"""The rehearsal run of ``maxpsdu8.saturated`` (ISSUE 43): the cell's
control flow at its tiny twin (tests/rehearse/wifi-a-maxpsdu-8s.json:
every frame one length at all eight rates, the 6 and 9 Mbit/s frames
longer than half the window), on any backend, would print ``correct``
true. A minute on a CPU, which is why it is here and not under
tier-1's ``tests/``."""

import argparse

from benchmark.harness import cell


def test_the_rehearsal_of_maxpsdu8_saturated_is_correct():
    args = argparse.Namespace(workload="maxpsdu8.saturated",
                              seed=2 ** 31 + 43, seconds=3.0, trace=0,
                              rehearse=True)
    line, compared = cell.measure(args)
    assert line["correct"] and line["failed"] == 0
    # eight sessions, about a frame every 2100 samples of a 4096 stride
    assert line["attempted"] > 60
    for name in ("overflow_chunks", "reference_disagreements",
                 "degraded", "quarantines", "compiles_in_window",
                 "contractions_below_highest"):
        assert compared[name] == 0, name
    assert compared["reference_captures_compared"] == 2
    assert compared["float_frames_compared"] >= 1
    assert compared["dispatches_per_chunk_step"] <= 2.0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
