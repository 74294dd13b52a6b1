"""A traced rehearsal (``--rehearse --trace 1``) reports the
whole-window metrics (ISSUE 41) on the CPU: ``harness/window_trace.py``
needs the program's in-memory trace and the profile's host spans, no
device op. One saturated and the paced cell at their tiny twins; about
half a minute each, which is why they are here and not under tier-1's
``tests/``."""

import argparse

import pytest

from benchmark.harness import cell

SAT = {"host_work_ms_per_step.window", "decode_wait_ms_per_step",
       "decode_ready_share.window", "stack_ms_per_step",
       "ingest_ms_per_step", "in_flight_mean.sat", "gc_pause_ms_per_s"}
PACED = {"launch_wait_ms", "chunk_flight_ms.window",
         "in_flight_mean.paced"}


@pytest.mark.parametrize("workload,new,seconds", [
    ("mtu8.saturated", SAT, 4.0), ("mtu8.paced", PACED, 6.0)])
def test_a_traced_rehearsal_reports_the_window_metrics(
        workload, new, seconds, capsys):
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 41,
                              seconds=seconds, trace=1, rehearse=True)
    line, _compared = cell.measure(args)
    assert line["correct"] and line["failed"] == 0
    got = line["metrics"]
    assert new <= set(got), sorted(new - set(got))
    out = capsys.readouterr()
    assert "[window_trace] chunk_steps=" in out.out
    assert "window_trace:" not in out.err       # the clock check passed
    assert 1.0 <= got["in_flight_mean." + (
        "sat" if workload.endswith("saturated") else "paced")]["value"] \
        <= 3.0
    if workload == "mtu8.saturated":
        assert 0.0 <= got["decode_ready_share.window"]["value"] <= 100.0
        assert got["host_work_ms_per_step.window"]["value"] > \
            got["stack_ms_per_step"]["value"] > 0.0
        assert got["gc_pause_ms_per_s"]["value"] >= 0.0
    else:
        assert got["launch_wait_ms"]["value"] >= 0.0
        assert got["chunk_flight_ms.window"]["value"] > 0.0
        assert line["not_ok"] == {}
        assert list(line)[-2:] == ["not_ok", "compared"]
