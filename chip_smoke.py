#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served receive path
still starts, and is still right, on the chip.

    python chip_smoke.py                one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips   the sharded path on a 4-chip host
    python chip_smoke.py --rehearse     control-flow rehearsal at a tiny
                                        geometry on whatever backend JAX
                                        has (the CPU here); never a result

One process; it starts no child. Without ``--rehearse`` it refuses to
run unless ``jax.default_backend()`` is ``tpu``. Every phase prints
one line and a phase that fails ends the run non-zero at once. The
LAST line of a passing chip run — and of no other run — is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Default run: 8 sessions of 24 MTU-sized (1500-byte + FCS) frames at
all eight 802.11a rates, 20 MS/s I/Q, through the objects ``python -m
ziria_tpu serve`` uses — ``serve.synth_load`` -> ``serve.run_clients``
-> ``ServeRuntime`` -> ``MultiStreamReceiver`` -> the two compiled
programs — at S=8, K=8, chunk 131072, frame bucket 65536: the full
width of the one deployment the repo supports. Checked: every frame
sent comes back once, byte-identical, FCS good; a seeded sample of
the captures decodes to the same bytes through the plain numpy
receiver (tests/oracles/wifi_rx_ref.py); and nothing was hidden — no
degrade, retry, fallback, rescan, quarantine, overflow, recompile or
interpret-mode kernel, at most two dispatches per chunk-step.

``--four-chips`` runs the path across chips and what it is compared
with, and nothing else: the same seeded load through a sharded
(``ServeConfig(shard=True)``, 8 lanes over 4 devices) and an unsharded
runtime at the default ``Geometry()``, frames identical, the chunk
scan's outputs on four distinct devices, the same nothing-hidden
checks on both. Then the fleet the runtime places by itself: 32
sessions on 32 lanes with NO ``shard`` argument (the rule gives 8
lanes a chip: a 4-device mesh) against ``shard=False``, same checks.

The printed seconds and bytes are observations for PERF.md, not
metrics; a CPU rehearsal's are not even that.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

MTU = dict(n_lanes=8, chunk_len=131072, frame_len=65536,
           max_frames_per_chunk=8)
MTU_LOAD = dict(n_sessions=8, frames_per_session=24, n_bytes=1500)
#: the repo's default Geometry() (S=8, K=8, chunk 8192, frame 2048):
#: 48-byte frames fit its 20-symbol capture bucket at 6 Mbit/s
DEFAULT_LOAD = dict(n_sessions=8, frames_per_session=16, n_bytes=48)
#: control-flow rehearsal: the test suite's shared tiny geometry
TINY = dict(n_lanes=8, chunk_len=4096, frame_len=1024,
            max_frames_per_chunk=8)
TINY_LOAD = dict(n_sessions=8, frames_per_session=16, n_bytes=12)
#: the fleet `--four-chips` lets the runtime place by itself: four
#: chips x the tuned 8 lanes (`mtu32x4.saturated`'s width)
WIDE_LANES = 32

RESILIENCE_COUNTERS = ("resilience.fatal", "resilience.fallbacks",
                       "resilience.degraded", "resilience.retries",
                       "resilience.async_rescans")
SITES = ("rx.stream_chunk_multi", "rx.stream_decode_multi")
SEED = 0        # load and reference sample are made from it


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


def sent_frames(load: dict):
    """What ``serve.synth_load(**load, seed=SEED)`` transmits, per
    session: ``[(rate_mbps, psdu bytes), ...]``."""
    from ziria_tpu.runtime import serve

    psdus_per, rates_per = serve.synth_payloads(seed=SEED, **load)
    return [list(zip(rates, psdus))
            for rates, psdus in zip(rates_per, psdus_per)]


def warm(rx, on_tpu: bool):
    """Compile the receiver's two programs ahead of traffic, on
    all-idle inputs built exactly as `_launch`/`_drain` build them,
    and time them: cold compile seconds each, then warm seconds per
    dispatch around block_until_ready. Returns the chunk scan's
    outputs (for the placement check)."""
    import jax
    import numpy as np

    from ziria_tpu.phy.wifi import rx as _rx
    from ziria_tpu.runtime import resilience

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    idle = np.zeros(rx.s, np.int32)
    chunk_args = (rx._put(np.zeros((rx.s, rx.chunk_len, 2), np.float32)),
                  rx._put(idle), rx._put(idle), rx._put(idle))
    _, c_chunk = timed(resilience.compile_ahead, rx._jit1, *chunk_args)
    outs, _first = timed(rx._jit1, *chunk_args)
    w_chunk = sorted(timed(rx._jit1, *chunk_args)[1] for _ in range(5))

    dec = _rx._jit_stream_decode_multi(
        rx.n_sym_bucket, rx.viterbi_window, rx.viterbi_metric,
        rx.viterbi_radix, rx.mesh, rx.axis, rx.sco_track,
        rx.fused_demap)
    table = np.zeros((rx.s, rx.k), np.int32)
    dec_args = (outs[-1],) + tuple(rx._put(table) for _ in range(4))
    _, c_dec = timed(resilience.compile_ahead, dec, *dec_args)
    _, _first = timed(dec, *dec_args)
    w_dec = sorted(timed(dec, *dec_args)[1] for _ in range(5))

    # the decode executable the served path will dispatch (lowering
    # and executable are cached on the jitted callable: no recompile)
    n_mosaic = dec.lower(*dec_args).compile().as_text() \
        .count("tpu_custom_call")
    say("compile", chunk_scan_cold_s=round(c_chunk, 2),
        decode_cold_s=round(c_dec, 2),
        chunk_scan_warm_s=w_chunk[2], decode_warm_s=w_dec[2],
        decode_tpu_custom_calls=n_mosaic)
    if on_tpu:
        check(n_mosaic >= 2, f"decode executable holds {n_mosaic} "
              f"tpu_custom_call(s), wanted the ACS and the traceback")
    return outs


def serve_once(cfg, clients, on_tpu: bool, tag: str):
    """One ServeRuntime over the client set, warm-up first, with every
    nothing-hidden check. Returns (frames per sid, chunk-scan outs of
    the warm-up, receiver stats)."""
    import jax

    from ziria_tpu.phy.wifi import rx as _rx
    from ziria_tpu.runtime import serve
    from ziria_tpu.utils import dispatch

    srv = serve.ServeRuntime(cfg)
    outs = warm(srv._rx, on_tpu)

    # every XLA compile fires this event; the listener cannot be
    # removed, so it counts only while its phase is live
    seen = {"live": True, "compiles": 0}

    def on_event(name, _secs, **_kw):
        if seen["live"] and name.endswith("backend_compile_duration"):
            seen["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    with dispatch.count_dispatches() as d, \
            dispatch.no_recompile(_rx._jit_stream_chunk_multi,
                                  _rx._jit_stream_decode_multi):
        with srv:
            frames = serve.run_clients(srv, clients)
    wall = time.perf_counter() - t0
    seen["live"] = False
    n_compiles = seen["compiles"]

    st = srv._rx.stats
    snap = srv.registry.snapshot()
    counters = {c: int(snap.get(c, 0)) for c in RESILIENCE_COUNTERS}
    n_disp = sum(d.counts[s] for s in SITES)
    say(f"served{tag}", chunk_steps=st.chunk_steps, frames=st.frames,
        dispatches=n_disp, overflow_chunks=st.overflow_chunks,
        degraded=st.degraded, quarantines=st.quarantines,
        max_in_flight=st.max_in_flight,
        compiles_after_warmup=n_compiles, wall_s=round(wall, 2),
        s_per_chunk_step=wall / max(1, st.chunk_steps),
        **{c.replace(".", "_"): v for c, v in counters.items()})
    check(st.overflow_chunks == 0,
          f"{st.overflow_chunks} chunk(s) overflowed K="
          f"{cfg.max_frames_per_chunk}: frames were dropped — widen K")
    check(not st.degraded, "a compiled fleet program degraded to its "
          "twin")
    check(st.quarantines == 0 and st.sanitized == 0
          and st.lane_blowups == 0,
          f"containment fired: {st}")
    check(not any(counters.values()), f"resilience counters: {counters}")
    check(set(d.counts) <= set(SITES),
          f"dispatch sites off the two-program path: {dict(d.counts)}")
    check(n_disp <= 2 * st.chunk_steps,
          f"{n_disp} dispatches over {st.chunk_steps} chunk-steps")
    check(n_compiles == 0,
          f"{n_compiles} XLA compile(s) after warm-up")
    return frames, outs, st


def check_frames(frames, sent, clients, cfg, tag: str) -> None:
    """Every frame sent comes back once: same order, rate, length,
    PSDU bytes, FCS good. All frames are compared before the phase
    fails, and the first few bad captures go through the numpy
    receiver, so ONE failing chip run says how many frames, at which
    rates, and whether the captures or the receiver are at fault."""
    import numpy as np

    from tests.oracles.wifi_rx_ref import np_receive
    from ziria_tpu.utils.bits import np_bits_to_bytes

    n, bad = 0, []          # bad: (session, frame, start, why, sent)
    for i, want in enumerate(sent):
        got = sorted(frames[f"s{i}"], key=lambda f: f.start)
        check(len(got) == len(want),
              f"session s{i}: {len(got)} frames back, {len(want)} sent")
        for j, (fr, (mbps, psdu)) in enumerate(zip(got, want)):
            r, n = fr.result, n + 1
            if not (r.ok and r.rate_mbps == mbps
                    and r.length_bytes == psdu.size + 4):
                why = (f"header ok={r.ok} rate={r.rate_mbps} "
                       f"len={r.length_bytes}")
            elif r.crc_ok is not True:
                why = "FCS bad"
            elif not np.array_equal(
                    np_bits_to_bytes(np.asarray(r.psdu_bits))
                    [:psdu.size], psdu):
                why = "PSDU bytes differ"
            else:
                continue
            bad.append((i, j, fr.start, why, mbps, psdu))
    say(f"frames{tag}", sent=n, returned_once_identical_fcs_ok=n - len(bad),
        bad=len(bad), bad_by_rate=dict(
            (m, sum(1 for b in bad if b[4] == m))
            for m in sorted({b[4] for b in bad})))
    if bad:
        # the bad captures, for a post-mortem off the chip
        import os
        os.makedirs("chiprun_out", exist_ok=True)
        np.savez_compressed(
            "chiprun_out/chip_smoke_bad_captures.npz",
            where=np.array([b[:3] for b in bad[:4]]),
            rate=np.array([b[4] for b in bad[:4]]),
            psdu=np.stack([b[5] for b in bad[:4]]),
            capture=np.stack([
                np.resize(clients[b[0]].stream[b[2]:
                                               b[2] + cfg.frame_len],
                          (cfg.frame_len, 2)) for b in bad[:4]]))
    for i, j, start, why, mbps, psdu in bad[:6]:
        ref = np_receive(clients[i].stream[start: start + cfg.frame_len])
        ref_ok = (ref is not None and ref.rate_mbps == mbps
                  and np.array_equal(ref.psdu[:psdu.size], psdu))
        say("diagnosis", where=f"s{i}/frame{j}@{start}",
            sent_rate=mbps, served=repr(why),
            numpy_receiver_decodes_the_capture=ref_ok)
    check(not bad, f"{len(bad)} of {n} frames came back wrong "
          f"(first: s{bad[0][0]} frame {bad[0][1]} @ {bad[0][2]}: "
          f"{bad[0][3]}, sent {bad[0][4]} Mbit/s)" if bad else "")


def check_reference(clients, frames, cfg) -> None:
    """A seeded sample of the captures — one per rate — through the
    plain numpy receiver: same bytes as the served path."""
    import numpy as np

    from tests.oracles.wifi_rx_ref import np_receive
    from ziria_tpu.utils.bits import np_bits_to_bytes

    rng = np.random.default_rng(SEED + 1)
    pool = [(c, fr) for c in clients for fr in frames[c.sid]]
    rates = sorted({fr.result.rate_mbps for _c, fr in pool})
    for m in rates:
        cands = [p for p in pool if p[1].result.rate_mbps == m]
        c, fr = cands[int(rng.integers(len(cands)))]
        ref = np_receive(c.stream[fr.start: fr.start + cfg.frame_len])
        check(ref is not None, f"numpy receiver found no frame at "
              f"{c.sid} @ {fr.start}")
        back = np_bits_to_bytes(np.asarray(fr.result.psdu_bits))
        check(ref.rate_mbps == m
              and ref.length_bytes == fr.result.length_bytes
              and np.array_equal(ref.psdu, back),
              f"numpy receiver disagrees at {c.sid} @ {fr.start} "
              f"({m} Mbit/s)")
    say("reference", numpy_receiver_agrees_on=len(rates),
        rates=",".join(str(m) for m in rates))


#: the bounded decode's check: (rate, PSDU + FCS bytes) a lane. An ACK,
#: a TCP ACK and MTU frames ride ONE tile (the mix cell's lengths), so
#: the short lanes are erasures for most of what the tile runs
BOUND_LANES = ((24, 14), (12, 76), (54, 1504), (6, 1504), (36, 1504),
               (6, 14))
TINY_BOUND_LANES = ((24, 14), (12, 16), (54, 50), (6, 16), (36, 40),
                    (6, 14))


def check_bounded_decode(cfg, lanes) -> None:
    """PR 53: one tile of mixed lengths through the decode's own stages
    (`rx._mixed_stages`) at the served symbol bucket, its ACS and
    traceback stopped at the tile's longest frame (`rx.decode_bound`)
    and run whole: every lane's bits before its own count, its PSDU and
    its FCS flag are the same. On the chip both are Mosaic kernels (the
    tests run the interpreter)."""
    import jax
    import numpy as np

    from ziria_tpu.phy.wifi import rx, tx
    from ziria_tpu.phy.wifi.params import (N_SERVICE_BITS, RATES,
                                           mixed_trellis_steps, n_symbols)
    from ziria_tpu.utils.geometry import DEFAULT

    nsb = DEFAULT.sym_bucket(
        max(1, (cfg.frame_len - rx.FRAME_DATA_START) // 80))
    need = rx.FRAME_DATA_START + 80 * nsb
    rng = np.random.default_rng(SEED + 53)
    frames, sent = [], []
    for m, n in lanes:
        body = rng.integers(0, 256, n - 4).astype(np.uint8)
        s = np.asarray(tx.encode_frame(body, m, add_fcs=True), np.float32)
        s = s + rng.normal(0, 0.02, s.shape).astype(np.float32)
        frames.append(np.pad(s, ((0, need - s.shape[0]), (0, 0))))
        sent.append(np.asarray(tx._host_psdu_bits(body, add_fcs=True)))
    ridx = np.array([rx.RATE_INDEX[m] for m, _n in lanes], np.int32)
    nbits = np.array([n_symbols(n, RATES[m]) * RATES[m].n_dbps
                      for m, n in lanes], np.int32)
    npsdu = np.array([8 * n for _m, n in lanes], np.int32)
    front, trellis, back = rx._mixed_stages(nsb, None, None, None, None,
                                            False, False)

    def decode(fr, r, n, p, blocks=None):
        clear = back(trellis(front(fr, r, n), r, n, blocks))
        return clear, rx.crc_psdu_many_graph(clear, p)

    blocks, steps = rx.decode_bound(int(nbits.max()),
                                    mixed_trellis_steps(nsb))
    check(steps < mixed_trellis_steps(nsb),
          f"the tile's bound ({steps} steps) is the whole trellis")
    args = (np.stack(frames), ridx, nbits, npsdu)
    whole = [np.asarray(o) for o in jax.jit(decode)(*args)]
    got = [np.asarray(o) for o in jax.jit(decode)(
        *args, np.full((1,), blocks, np.int32))]
    for i, (n, p) in enumerate(zip(nbits, npsdu)):
        check(np.array_equal(got[0][i, :n], whole[0][i, :n]),
              f"bounded decode, lane {i} {lanes[i]}: a bit before its "
              f"{n} differs from the whole trellis's")
        check(np.array_equal(
            got[0][i, N_SERVICE_BITS: N_SERVICE_BITS + p], sent[i]),
            f"bounded decode, lane {i} {lanes[i]}: not the PSDU sent")
    check(got[1].all() and whole[1].all(),
          f"bounded decode: FCS flags {got[1].tolist()} "
          f"(whole: {whole[1].tolist()})")
    say("bounded-decode", lanes=len(lanes), symbol_bucket=nsb,
        steps_run=int(steps), steps_whole=mixed_trellis_steps(nsb),
        same_bits_psdu_and_fcs=True)


def same_frames(a, b) -> None:
    import numpy as np

    check(a.keys() == b.keys(), "session sets differ")
    for sid in a:
        fa = sorted(a[sid], key=lambda f: f.start)
        fb = sorted(b[sid], key=lambda f: f.start)
        check(len(fa) == len(fb), f"{sid}: {len(fa)} vs {len(fb)} frames")
        for x, y in zip(fa, fb):
            rx_, ry = x.result, y.result
            check(x.start == y.start and rx_.ok == ry.ok
                  and rx_.rate_mbps == ry.rate_mbps
                  and rx_.length_bytes == ry.length_bytes
                  and rx_.crc_ok == ry.crc_ok
                  and np.array_equal(rx_.psdu_bits, ry.psdu_bits),
                  f"{sid} @ {x.start}: sharded and unsharded differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the sharded path and its unsharded twin "
                         "on a 4-chip host, and nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny geometry, any backend, no result line")
    args = ap.parse_args(argv)

    import jax

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: jax.default_backend() is {backend!r}, not "
              f"'tpu' — no accelerator, no result", file=sys.stderr)
        return 2

    import jaxlib

    from ziria_tpu.ops import viterbi_pallas
    from ziria_tpu.runtime import serve
    from ziria_tpu.utils import compile_cache

    cache = compile_cache.place()
    devs = jax.devices()
    dev = devs[0]
    try:
        import libtpu
        libtpu_v = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_v = "absent"
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devs), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_v, compile_cache=cache, rehearsal=args.rehearse)
    check(viterbi_pallas._interpret_default() == (not on_tpu),
          "interpret mode must follow the backend: Mosaic on tpu, "
          "interpreter elsewhere")

    if args.four_chips:
        check(len(devs) == 4, f"--four-chips needs 4 devices, JAX "
              f"reports {len(devs)}")
        geo = TINY if args.rehearse else {}
        load = TINY_LOAD if args.rehearse else DEFAULT_LOAD
        base = serve.ServeConfig(check_fcs=True, **geo)
        clients = serve.synth_load(seed=SEED, tail=base.frame_len,
                                   **load)
        sent = sent_frames(load)
        f1, _o, _s = serve_once(base, clients, on_tpu, "[1-device]")
        check_frames(f1, sent, clients, base, "[1-device]")
        f4, outs, _s = serve_once(base._replace(shard=True), clients,
                                  on_tpu, "[4-device]")
        check_frames(f4, sent, clients, base, "[4-device]")
        same_frames(f1, f4)
        placed = [sorted(str(s.device) for s in o.addressable_shards)
                  for o in outs]
        check(all(len(set(p)) == 4 for p in placed),
              f"chunk-scan outputs not on four distinct devices: "
              f"{placed}")
        say("placement", sharded_equals_unsharded=True,
            chunk_scan_output_devices="|".join(placed[-1]))
        # 32 lanes, placed by the runtime's own rule: no argument
        wide = base._replace(n_lanes=WIDE_LANES)
        check(wide.shard is None, "ServeConfig.shard defaults to None")
        load = dict(load, n_sessions=WIDE_LANES, frames_per_session=2)
        clients = serve.synth_load(seed=SEED, tail=base.frame_len,
                                   **load)
        sent = sent_frames(load)
        f32, outs, st32 = serve_once(wide, clients, on_tpu,
                                     "[32-lanes-by-rule]")
        check_frames(f32, sent, clients, wide, "[32-lanes-by-rule]")
        placed = [sorted(str(s.device) for s in o.addressable_shards)
                  for o in outs]
        check(all(len(set(p)) == 4 for p in placed),
              f"32 lanes by the rule: chunk-scan outputs not on four "
              f"distinct devices: {placed}")
        f32one, outs1, _s = serve_once(wide._replace(shard=False),
                                       clients, on_tpu,
                                       "[32-lanes-1-device]")
        check(all(len(o.addressable_shards) == 1 for o in outs1),
              "shard=False left the fleet on more than one device")
        same_frames(f32, f32one)
        say("placement-by-rule", lanes=WIDE_LANES,
            lanes_per_chip=WIDE_LANES // 4,
            sharded_equals_unsharded=True, chunk_steps=st32.chunk_steps,
            chunk_scan_output_devices="|".join(placed[-1]))
    else:
        geo = TINY if args.rehearse else MTU
        load = TINY_LOAD if args.rehearse else MTU_LOAD
        cfg = serve.ServeConfig(check_fcs=True, **geo)
        t0 = time.perf_counter()
        clients = serve.synth_load(seed=SEED, tail=cfg.frame_len,
                                   **load)
        say("load", sessions=len(clients),
            samples_per_session=clients[0].stream.shape[0],
            seed=SEED, synth_s=round(time.perf_counter() - t0, 2))
        frames, _outs, st = serve_once(cfg, clients, on_tpu, "")
        stride = cfg.chunk_len - cfg.frame_len
        per_lane = min(-(-c.stream.shape[0] // stride) for c in clients)
        check(per_lane >= 5 and st.chunk_steps >= per_lane,
              f"{per_lane} chunk-steps per lane ({st.chunk_steps} fleet "
              f"steps) is under five")
        # eight lanes outrun their device: the pipeline fills. (The
        # --four-chips fleets print the depth and are not held to it: a
        # host slower than its devices hands every step back from a
        # call that launches nothing, before the next launch.)
        check(st.max_in_flight == 3,
              f"the pipeline held {st.max_in_flight} chunk-step(s) in "
              f"flight at most, not 3: a scan and a decode were never "
              f"queued behind the step the host waited for")
        check_frames(frames, sent_frames(load), clients, cfg, "")
        check_reference(clients, frames, cfg)
        check_bounded_decode(
            cfg, TINY_BOUND_LANES if args.rehearse else BOUND_LANES)

    ms = dev.memory_stats() or {}
    say("memory", peak_device_bytes=ms.get("peak_bytes_in_use", "n/a"),
        bytes_limit=ms.get("bytes_limit", "n/a"))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
