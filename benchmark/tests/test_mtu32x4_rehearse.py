"""The rehearsal run of ``mtu32x4.saturated`` (ISSUE 37): the cell's
control flow at its tiny twin (tests/rehearse/wifi-a-mtu-32s-dp4.json:
``wifi-a-mtu-8s``'s twin at 32 sessions and lanes), on any backend,
would print ``correct`` true; and where the backend shows four devices
or more the harness, which names no placement, gets a fleet that lies
over four. Under a minute on a CPU, which is why it is here and not
under tier-1's ``tests/``. Four devices on a CPU:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""

import argparse

import jax

from benchmark.harness import cell, manifest
from ziria_tpu.runtime import serve


def test_the_rehearsal_of_mtu32x4_saturated_is_correct():
    args = argparse.Namespace(workload="mtu32x4.saturated",
                              seed=2 ** 31 + 37, seconds=3.0, trace=0,
                              rehearse=True)
    line, compared = cell.measure(args)
    assert line["correct"] and line["failed"] == 0
    # 32 sessions, a frame every 1300 samples of a 3072 stride
    assert line["attempted"] > 300
    for name in ("overflow_chunks", "reference_disagreements",
                 "degraded", "quarantines", "compiles_in_window",
                 "contractions_below_highest"):
        assert compared[name] == 0, name
    assert compared["reference_captures_compared"] == 2
    assert compared["float_frames_compared"] >= 1
    assert compared["dispatches_per_chunk_step"] <= 2.0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}


def test_the_harness_names_no_placement_and_gets_four_devices():
    """What `cell.measure` builds, built the same way: the five
    geometry keys of the twin and nothing else."""
    geo = manifest.load_cell("mtu32x4.saturated",
                             rehearse=True).config["geometry"]
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=geo["n_lanes"], chunk_len=geo["chunk_len"],
        frame_len=geo["frame_len"],
        max_frames_per_chunk=geo["max_frames_per_chunk"],
        check_fcs=geo["check_fcs"]))
    want = {1: 1, 2: 2, 3: 2}.get(len(jax.devices()), 4)
    mesh = srv._rx.mesh
    assert (1 if mesh is None else mesh.size) == want
    outs = srv._rx._jit1(*cell.chunk_shapes(srv._rx))
    assert {len(o.addressable_shards) for o in outs} == {want}
