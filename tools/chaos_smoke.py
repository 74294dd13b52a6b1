#!/usr/bin/env python
"""Sub-10s CPU chaos smoke for tools/precommit.sh (ISSUE 12).

Exercises the fault-injection + guarded-dispatch machinery
(utils/faults, runtime/resilience) against stub dispatch functions —
deterministic replay, retry/backoff, watchdog hang containment,
fallback degrade, checkpoint roundtrip — WITHOUT importing jax or
compiling anything, so the gate stays sub-second and works while the
TPU probe hangs (the jaxlint-subcommand discipline). The full
device-path chaos matrix lives in tests/test_resilience.py and the
bench `resilience` stage; this is the commit-time canary.

Exit 0 = all checks passed; nonzero = the resilience layer itself is
broken (precommit refuses the commit).
"""

import os
import sys
import time

# run as a script from tools/: only tools/ lands on sys.path, the repo
# root is not
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))


def main() -> int:
    t_start = time.perf_counter()
    import numpy as np

    from ziria_tpu.runtime import resilience as rz
    from ziria_tpu.utils import faults

    # jax must NOT have been imported by the above (the no-jax pin)
    assert "jax" not in sys.modules, \
        "chaos_smoke imported jax — the smoke must stay host-only"

    # 1. deterministic replay: same plan, same workload, same faults
    def run_once():
        fired = []
        with faults.inject(
                faults.FaultSpec("rx.stream_chunk_multi", "transient",
                                 every=3),
                faults.FaultSpec("rx.push.s*", "nan_slab",
                                 calls=(1,)), seed=7) as plan:
            for i in range(9):
                try:
                    faults.maybe_fail("rx.stream_chunk_multi")
                except faults.InjectedTransientError:
                    fired.append(i)
            a = np.ones((16, 2), np.float32)
            slabs = [faults.corrupt_slab("rx.push.s0", a)[0]
                     for _ in range(3)]
        return fired, slabs, list(plan.fired)

    f1, s1, log1 = run_once()
    f2, s2, log2 = run_once()
    assert f1 == f2 == [2, 5, 8], (f1, f2)
    assert log1 == log2
    assert np.array_equal(np.isnan(s1[1]), np.isnan(s2[1]))
    assert np.isnan(s1[1]).any() and not np.isnan(s1[0]).any()

    # 2. guarded: transient retries recover; backoff is deterministic
    calls, slept = [], []
    pol = rz.FaultPolicy(max_retries=2, backoff_base_s=1e-4)
    with faults.inject(faults.FaultSpec("site", "transient",
                                        calls=(0, 1))):
        out = rz.guarded(
            "site", lambda x: calls.append(x) or x * 2, 21,
            policy=pol, _sleep=slept.append)
    assert out == 42 and calls == [21] and len(slept) == 2
    assert slept[0] == rz.backoff_delay("site", 0, pol)
    assert slept[1] == rz.backoff_delay("site", 1, pol) > slept[0]

    # 3. fatal: immediate degrade to the fallback twin
    with faults.inject(faults.FaultSpec("s2", "fatal", every=1)):
        out = rz.guarded("s2", lambda: "compiled",
                         fallback=lambda: "twin")
    assert out == "twin"

    # 4. a hang is cut by the watchdog and the retry succeeds
    t0 = time.perf_counter()
    with faults.inject(faults.FaultSpec("hang", "hang", calls=(0,),
                                        delay_s=30.0)):
        out = rz.guarded(
            "hang", lambda: "ok",
            policy=rz.FaultPolicy(max_retries=1, backoff_base_s=1e-4,
                                  timeout_s=0.05),
            _sleep=lambda s: None)
    assert out == "ok" and time.perf_counter() - t0 < 5.0

    # 5. classification: retry only what may heal
    assert rz.classify_error(
        RuntimeError("UNAVAILABLE: link")) == "transient"
    assert rz.classify_error(
        RuntimeError("INVALID_ARGUMENT: shape")) == "fatal"

    # 6. carry checkpoint roundtrip (the npz blob, format-gated)
    class Carry:
        tail = np.arange(8, dtype=np.float32).reshape(4, 2)
        offset, emitted, watermark = 4096, 3, 4000
    blob = rz.checkpoint_carry(Carry, seen=(4100, 4200),
                               geometry={"chunk_len": 4096})
    st = rz.restore_carry(blob)
    assert np.array_equal(st.tail, Carry.tail)
    assert (st.offset, st.emitted, st.watermark) == (4096, 3, 4000)
    assert st.seen == frozenset((4100, 4200))
    try:
        rz.restore_carry(b"garbage")
        raise AssertionError("garbage checkpoint must not restore")
    except rz.CarryCheckpointError:
        pass

    # 7. the channel-profile grammar (ISSUE 15): parses jax-free,
    # validates names against the profile registry, and the `channel`
    # data kind corrupts slabs deterministically in pure numpy — the
    # precommit gate keeps working through TPU probe hangs
    from ziria_tpu.phy import profiles as chp

    assert "jax" not in sys.modules, \
        "phy/profiles imported jax — the registry must stay host-only"
    assert chp.parse_profile_spec("flat,severe") == ("flat", "severe")
    assert chp.resolve_profiles("flat", 4) is None, \
        "flat must resolve to the unprofiled path"
    assert chp.resolve_profiles(("flat", "severe"), 4) == \
        ("flat", "severe", "flat", "severe")
    for name, prof in chp.CHANNEL_PROFILES.items():
        e = sum(r * r + i * i for r, i in prof.taps)
        assert abs(e - 1.0) < 1e-6, f"{name} taps not unit-energy"
    try:
        chp.parse_profile_spec("nope")
        raise AssertionError("unknown profile must not parse")
    except ValueError:
        pass
    specs, cseed = faults.parse_chaos_spec(
        "seed=5;rx.push.s*:channel:profile=severe,every=2")
    assert specs[0].profile == "severe" and specs[0].every == 2
    slab = np.ones((64, 2), np.float32)
    outs = []
    for _ in range(2):
        with faults.inject(*specs, seed=cseed):
            a0, k0 = faults.corrupt_slab("rx.push.s0", slab)
            a1, k1 = faults.corrupt_slab("rx.push.s0", slab)
        assert k0 == () and k1 == ("channel",)
        outs.append(a1)
    assert np.array_equal(outs[0], outs[1]), "channel kind must replay"
    assert not np.array_equal(outs[0], slab), "channel kind must act"
    assert outs[0].shape == slab.shape
    try:
        faults.parse_chaos_spec("x:channel:profile=nope")
        raise AssertionError("bad channel profile must not parse")
    except ValueError:
        pass
    assert "jax" not in sys.modules, \
        "channel-kind corruption imported jax — must stay host-only"

    # 8. disabled-path pin: the seams are free when no plan is active
    assert not faults.active()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        faults.maybe_fail("x")
    per = (time.perf_counter() - t0) / n
    assert per < 5e-6, f"disabled maybe_fail: {per:.2e}s/call"

    dt = time.perf_counter() - t_start
    print(f"chaos smoke OK ({dt:.2f}s, no jax, "
          f"disabled-seam {per * 1e9:.0f}ns/call)")
    assert dt < 10.0, f"chaos smoke exceeded its 10s budget: {dt:.1f}s"
    return 0


if __name__ == "__main__":
    sys.exit(main())
