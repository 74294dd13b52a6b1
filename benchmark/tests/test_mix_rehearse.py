"""The rehearsal run of ``mix8.saturated`` (ISSUE 33): the cell's
control flow at its tiny twin (tests/rehearse/wifi-a-mix-8s.json: the
same six-frame exchange and sixteen-entry rate list, K = 16), on any
backend, would print ``correct`` true. Half a minute on a CPU, which
is why it is here and not under tier-1's ``tests/``."""

import argparse

from benchmark.harness import cell


def test_the_rehearsal_of_mix8_saturated_is_correct():
    args = argparse.Namespace(workload="mix8.saturated",
                              seed=2 ** 31 + 33, seconds=3.0, trace=0,
                              rehearse=True)
    line, compared = cell.measure(args)
    assert line["correct"] and line["failed"] == 0
    # eight sessions, about a frame every 1200 samples of a 6144 stride
    assert line["attempted"] > 150
    for name in ("overflow_chunks", "reference_disagreements",
                 "degraded", "quarantines", "compiles_in_window",
                 "contractions_below_highest"):
        assert compared[name] == 0, name
    assert compared["reference_captures_compared"] == 2
    assert compared["float_frames_compared"] >= 1
    assert compared["dispatches_per_chunk_step"] <= 2.0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
