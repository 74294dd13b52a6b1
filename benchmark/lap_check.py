#!/usr/bin/env python3
"""Every frame of one lap through the plain numpy receiver, once.

    python3 benchmark/lap_check.py --workload mix8.saturated --seed 7

Not part of a benchmark run and not wired into ``correct``: a run's
``reference_disagreements`` compares two captures drawn by rate, which
in a configuration of several frame sizes may both be of one size.
This serves the cell's sessions through the same ``ServeRuntime`` the
cell builds, closed loop, until every session's first lap has been
consumed, and puts EVERY frame of that lap (of every session, or of
``--session``) through ``reference/wifi_rx_ref.np_receive`` on the
samples the program was given: rate, length and bytes, counted by
PSDU size. It also reads, from the program's own output, the largest
number of frames one owned window held (what K has to cover). Nothing
is timed. Needs a TPU unless ``--rehearse``; exits 1 on a disagreement.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def reference_agrees(result, capture) -> bool:
    """The plain numpy receiver on ``capture`` (the samples from the
    frame's start) against one served ``RxResult``: rate, length and
    every PSDU byte, FCS included."""
    import numpy as np

    from benchmark.harness import checks
    from benchmark.reference import wifi_rx_ref as ref

    got = ref.np_receive(capture)
    return got is not None and got.rate_mbps == result.rate_mbps \
        and got.length_bytes == result.length_bytes \
        and np.array_equal(got.psdu, checks._bytes(result.psdu_bits))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--session", type=int, default=None,
                    help="one session's lap (default: every session's)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark.harness import (cell, checks, load, loop, manifest,
                                   spans)

    cfg = manifest.load_cell(args.workload, args.rehearse).config
    import jax
    from ziria_tpu.runtime import serve
    from ziria_tpu.utils import compile_cache

    if jax.default_backend() != "tpu" and not args.rehearse:
        cell.refuse(f"jax.default_backend() is "
                    f"{jax.default_backend()!r}, not 'tpu'")
    compile_cache.place()
    dev = jax.devices()[0]
    geo = cfg["geometry"]
    laps = load.synth_laps(cfg, args.seed)
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=geo["n_lanes"], chunk_len=geo["chunk_len"],
        frame_len=geo["frame_len"],
        max_frames_per_chunk=geo["max_frames_per_chunk"],
        check_fcs=geo["check_fcs"]))
    rx = srv._rx
    sids = [f"s{i}" for i in range(cfg["sessions"])]
    for s in sids:
        if not srv.connect(s).admitted:
            raise SystemExit(f"session {s} was not admitted")
    lane_of = {s: ln for ln, s in srv._lane_sid.items()}
    L = cfg["population"]["lap_samples"]
    rec, pos, out = spans.Recorder(annotate=False), [0] * len(sids), []
    while min(rx.carry(lane_of[s]).offset for s in sids) \
            < L + rx.chunk_len:
        out += loop.closed_tick(srv, sids, laps, pos, rx.stride, rec)
    out += [(srv._lane_sid[ln], fr) for ln, fr in rx.drain_pending()]
    emitted = [loop.Emitted(0.0, sids.index(sid), fr) for sid, fr in out]
    consumed = [rx.carry(lane_of[s]).offset for s in sids]
    frames = checks.check_frames(emitted, laps, consumed)

    want = range(len(sids)) if args.session is None else [args.session]
    by_size, bad, seen = {}, [], set()
    for i, k, j, em in frames.matched:
        if k != 0 or i not in want:
            continue
        seen.add((i, j))
        res = em.frame.result
        size = int(res.length_bytes)
        n = by_size.setdefault(size, [0, 0])
        n[0] += 1
        if not reference_agrees(res, load.lap_slice(
                laps[i], int(em.frame.start), rx.frame_len)):
            n[1] += 1
            bad.append((i, j, laps[i].rates[j], size))
    missing = [(i, j) for i in want for j in range(len(laps[i].starts))
               if (i, j) not in seen]
    # frames per owned window, from the starts the program reported
    worst = 0
    for i in range(len(sids)):
        starts = np.asarray([max(int(e.frame.start), 0) for e in emitted
                             if e.session == i]) // rx.stride
        if starts.size:
            worst = max(worst, int(np.bincount(starts).max()))
    line = {
        "workload": args.workload, "seed": args.seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "sessions_checked": list(want),
        "frames_compared": sum(n for n, _b in by_size.values()),
        "by_psdu_bytes": {str(b): {"compared": n, "disagree": d}
                          for b, (n, d) in sorted(by_size.items())},
        "disagreements": len(bad), "first_disagreements": bad[:8],
        "missing_from_the_lap": missing[:8],
        "served_frames_failed": frames.failed,
        "failed_by_kind": frames.why,
        "most_frames_in_an_owned_window": worst,
        "k": rx.k, "overflow_chunks": rx.stats.overflow_chunks,
        "chunk_steps": rx.stats.chunk_steps,
    }
    print("[lap_check] " + json.dumps(line), flush=True)
    ok = not bad and not missing and frames.failed == 0 \
        and rx.stats.overflow_chunks == 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
