"""chip_smoke.py's contract, as far as a machine with no chip can
hold it (the chip side is the builder's and the driver's run):
without an accelerator it exits non-zero and prints no result — from
the checkout and from a directory holding nothing but the script —
and its control flow, every check included, passes end to end at the
rehearsal geometry without ever printing a result line.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, script=SMOKE, **env):
    e = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=e, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("args", [[], ["--four-chips"]],
                         ids=["default", "four-chips"])
def test_refuses_to_run_without_a_chip(args):
    r = _run(args, REPO)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and r.stdout.strip() == ""
    assert "not 'tpu'" in r.stderr


def test_fails_alone_in_an_empty_directory(tmp_path):
    lone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    for args in ([], ["--rehearse"]):
        r = _run(args, str(tmp_path), script=str(lone))
        assert r.returncode != 0
        assert '"ok"' not in r.stdout


def test_rehearsal_passes_every_phase_and_prints_no_result():
    r = _run(["--rehearse"], REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    phases = [ln.split("]")[0].lstrip("[") for ln in lines[:-1]]
    assert phases == ["device", "load", "compile", "served", "frames",
                      "reference", "bounded-decode", "memory"]
    # PR 53: a tile of mixed lengths decodes to the same bits with its
    # kernels stopped at its longest frame and run whole
    bound = [ln for ln in lines if ln.startswith("[bounded-decode]")][0]
    assert "same_bits_psdu_and_fcs=True" in bound
    run, whole = (int(bound.split(f"{k}=")[1].split()[0])
                  for k in ("steps_run", "steps_whole"))
    assert 0 < run < whole
    assert lines[-1].startswith('{"rehearsal": true')
    assert '"ok"' not in r.stdout


def test_four_chip_rehearsal_on_virtual_devices():
    r = _run(["--rehearse", "--four-chips"], REPO,
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    out = r.stdout
    assert "[placement] sharded_equals_unsharded=True" in out
    assert "TFRT_CPU_0|TFRT_CPU_1|TFRT_CPU_2|TFRT_CPU_3" in out
    # and the 32-lane fleet the runtime places with no argument
    assert "[placement-by-rule] lanes=32 lanes_per_chip=8 " \
        "sharded_equals_unsharded=True" in out
    assert out.count("TFRT_CPU_0|TFRT_CPU_1|TFRT_CPU_2|TFRT_CPU_3") == 2
    assert '"ok"' not in out and '"count": 4' in out
