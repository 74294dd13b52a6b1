"""Stage-resume logic of the bench harness (bench.py).

Round 4 lost two chip windows because every run re-measured
already-captured stages from zero before its budget ran out (VERDICT
r4 missing #1). bench.py therefore reuses stage records from BENCH_PARTIAL.jsonl when they are recent,
same-schema-version, and same-platform. These tests pin the eligibility
rules — reusing a stale, foreign-platform, or error record would
publish a wrong number, so the filter is load-bearing.
"""

import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench = _bench()
NOW = 1_000_000.0
VER = bench.BENCH_STAGE_VERSION


def _write(tmp_path, recs):
    p = tmp_path / "partial.jsonl"
    with open(p, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(p)


def rec(stage, t=NOW - 100, ver=VER, platform="tpu",
        workload_bytes=1000, **kv):
    return {"run_id": "rX", "stage": stage, "t": t, "ver": ver,
            "platform": platform, "workload_bytes": workload_bytes, **kv}


def test_eligibility_filters(tmp_path):
    path = _write(tmp_path, [
        rec("headline", batch=128, t_step_s=1e-3, tpu_sps=1.0),
        rec("pallas_mosaic", ver=VER - 1, pallas_mosaic=True),   # old schema
        rec("fxp_interior", platform="cpu", t_step_s=2e-3),      # wrong plat
        rec("framebatch", t=NOW - 99999, frames=16),             # too old
        rec("percall_fence", error="boom"),                      # error rec
        rec("correctness", workload_bytes=100),              # smoke workload
    ])
    out = bench._load_resume("tpu", 3600, now=NOW, path=path)
    assert "headline" in out and "headline:128" in out
    assert "pallas_mosaic" not in out
    assert "fxp_interior" not in out
    assert "framebatch" not in out
    assert "percall_fence" not in out
    assert "correctness" not in out


def test_chained_resume_ages_on_original_capture(tmp_path):
    # a re-emitted record carries captured_t of the ORIGINAL
    # measurement; the window gates on that, not the re-emission time
    path = _write(tmp_path, [
        rec("headline", t=NOW - 10, captured_t=NOW - 99999,
            batch=128, t_step_s=1e-3),
    ])
    assert bench._load_resume("tpu", 3600, now=NOW, path=path) == {}
    # but a fresh record the same age IS eligible
    path2 = _write(tmp_path, [
        rec("headline", t=NOW - 10, batch=128, t_step_s=1e-3)])
    assert "headline" in bench._load_resume("tpu", 3600, now=NOW,
                                            path=path2)


def test_sweep_widths_keyed_independently(tmp_path):
    path = _write(tmp_path, [
        rec("batch_sweep", batch=256, t_step_s=2e-3),
        rec("batch_sweep", batch=512, t_step_s=3e-3),
        rec("batch_sweep", batch=512, t=NOW - 50, t_step_s=4e-3),
    ])
    out = bench._load_resume("tpu", 3600, now=NOW, path=path)
    assert out["batch_sweep:256"]["t_step_s"] == 2e-3
    # most recent record wins per width
    assert out["batch_sweep:512"]["t_step_s"] == 4e-3
    assert "batch_sweep" not in out


def test_headline_keeps_per_width_and_latest(tmp_path):
    # a run emits headline at B=128 then re-emits at the promoted
    # width: both widths stay resumable, "headline" = the promotion
    path = _write(tmp_path, [
        rec("headline", t=NOW - 200, batch=128, t_step_s=1e-3),
        rec("headline", t=NOW - 100, batch=512, t_step_s=2e-3),
    ])
    out = bench._load_resume("tpu", 3600, now=NOW, path=path)
    assert out["headline"]["batch"] == 512
    assert out["headline:128"]["t_step_s"] == 1e-3
    assert out["headline:512"]["t_step_s"] == 2e-3


def test_stage_payload_strips_bookkeeping():
    r = rec("fxp_interior", t_step_s=1e-3, sps=5.0,
            captured_t=NOW - 5, resumed_from="r0")
    payload = bench._stage_payload(r)
    assert payload == {"t_step_s": 1e-3, "sps": 5.0}


def test_garbage_lines_ignored(tmp_path):
    p = tmp_path / "partial.jsonl"
    with open(p, "w") as f:
        f.write("not json at all\n")
        f.write(json.dumps(rec("headline", batch=128, t_step_s=1e-3))
                + "\n")
    out = bench._load_resume("tpu", 3600, now=NOW, path=str(p))
    assert "headline" in out


def test_missing_file_is_empty(tmp_path):
    out = bench._load_resume("tpu", 3600, now=NOW,
                             path=str(tmp_path / "nope.jsonl"))
    assert out == {}


def test_windowed_headline_never_seeds_exact_width_table(tmp_path):
    # a windowed-Viterbi promotion is a different decode method: it
    # must resume under its own key, never shadowing the exact step
    # at its width — even when it is the LATEST headline record
    path = _write(tmp_path, [
        rec("headline", t=NOW - 200, batch=128, t_step_s=1e-3),
        rec("headline", t=NOW - 100, batch=128, t_step_s=2e-4,
            windowed=True, window=1024, overlap=96),
        rec("batch_sweep", batch=256, t_step_s=2e-3),
    ])
    out = bench._load_resume("tpu", 3600, now=NOW, path=path)
    # the exact record survives at its width key...
    assert out["headline:128"]["t_step_s"] == 1e-3
    assert "windowed" not in out["headline:128"]
    # ...and the windowed promotion lives under its own key
    assert out["headline_windowed"]["windowed"] is True
    assert out["headline"]["t_step_s"] == 1e-3   # latest EXACT headline


def test_pinned_baseline_reader(tmp_path, monkeypatch):
    base = tmp_path / "BASELINE.json"
    monkeypatch.setattr(bench, "BASELINE_PATH", str(base))
    assert bench._pinned_baseline() is None          # missing file
    base.write_text(json.dumps({"pinned_baseline": {"sps": 0}}))
    assert bench._pinned_baseline() is None          # zero = unset
    base.write_text(json.dumps(
        {"pinned_baseline": {"sps": 6401460.9,
                             "pinned_at": "2026-07-31"}}))
    pin = bench._pinned_baseline()
    assert pin["sps"] == 6401460.9
