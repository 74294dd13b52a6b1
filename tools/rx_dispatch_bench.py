"""RX hot-path lever bench: quantized Viterbi metrics + one-dispatch
mixed-rate decode (ISSUE 1 tentpole; VERDICT r5 "Next round" #2/#5).

Two measurements, each importable by bench.py as a resumable child
stage (the tools-module discipline of VERDICT #9 — bench.py loads this
file, it does not re-implement it) and runnable standalone for a CPU
smoke or a manual chip window:

- ``quantized_sweep``: marginal per-step time of the batched DATA
  decode at the bench shape with float32 vs int16 path metrics — the
  SORA trade (half the LLR HBM stream, half the metric VMEM footprint)
  measured, not asserted. The marginal time comes from a jitted
  fori_loop K-spread (t(K2)-t(K1))/(K2-K1) with runtime-zero data
  feedback, the same round-trip-cancelling method as bench.py's headline.

- ``mixed_dispatch_stats``: the DATA-stage compile count and decode
  wall time for an all-8-rates corpus through (a) the host-side
  bucketed dispatch (one jit per (rate, symbol bucket) — O(rates x
  log lengths) compiles) and (b) the one-``lax.switch`` mixed-rate
  dispatch (one jit per symbol bucket — O(log lengths)), asserting
  the two decode bit-identically lane for lane. Compile counts are
  read off the real lru_cache entry counts after clearing them, so
  the artifact records measured cache growth, not arithmetic.

- ``batched_acquire_stats`` (ISSUE 2 tentpole): acquisition dispatch
  count and wall time of ``receive_many`` with the host-driven
  per-capture loop (>= 3N+1 dispatches) vs the one-dispatch batched
  acquisition (acquire -> gather -> mixed decode, <= 3 dispatches),
  measured by the instrumented utils/dispatch counter and
  identity-gated lane for lane.

- ``link_loopback_stats`` (ISSUE 3 tentpole): the full device-resident
  TX -> channel -> RX loopback (phy/link.loopback_many) over an
  all-8-rates mixed-length batch — <= 5 dispatches and frames/s for
  the batched link vs >= 5N for the per-frame encode/impair/receive
  loop, identity-gated lane for lane; dispatch counts from the
  instrumented counter, so the artifact records the measured
  O(N) -> O(1) collapse of the transmit side too. Pins
  ``fused=False`` so this artifact keeps measuring the staging lever
  alone, comparable with prior rounds; the fused graph is
  ``fused_link_stats``'s job.

- ``fused_link_stats`` (ISSUE 4 tentpole): the staged ~5-dispatch
  loopback vs the ONE-dispatch fused graph (encode -> channel ->
  acquire -> classify -> gather -> decode -> batched CRC in a single
  jitted program), with ``check_fcs=True`` so the batched-CRC
  satellite is measured too; per-site dispatch wall times from the
  extended utils/dispatch counter, identity-gated lane for lane.

- ``ber_sweep_stats`` (ISSUE 4 tentpole): an n-rates x K-SNR BER
  sweep through ``link.sweep_ber`` (ONE lax.scan dispatch) vs the
  python loop of per-batch ``loopback_ber_bits`` points (~3 dispatches
  per point), error counts gated integer-identical, sweep points/s
  and samples/s recorded.

- ``viterbi_breakdown`` (ISSUE 6 satellite): the decode step cut into
  front-end-only / ACS-only / traceback-only / full with the marginal-K
  method — the measured answer to "dependency-chain-bound, but WHERE?".

- ``viterbi_kernel_stats`` (ISSUE 6 tentpole): per-lever decode-core
  samples/s for the rebuilt ACS (radix-4, int16, int8+LUT, fused
  demap front end, stacked), dispatch counts + per-site times from
  utils/dispatch, identity-gated: radix-4 exactly bit-identical vs
  the float32 radix-2 oracle on noisy inputs, the fused levers within
  a vanishing mismatch budget (their renorm cadence differs), int8
  gated on its BER envelope.

- ``streaming_stats`` (ISSUE 5 tentpole): a long multi-frame I/Q
  stream (``link.stream_many``: all 8 rates, random gaps, CFO, delay,
  AWGN) through ``framebatch.receive_stream`` — <= 2 dispatches per
  CHUNK (O(chunks), frame count free) vs >= 3 per FRAME for the
  per-capture path over the same detected windows — identity-gated
  frame for frame (results AND starts vs ground truth), samples/s,
  dispatch counts, and the double-buffer in-flight depth gauge.
  Since ISSUE 7, ``streaming_stats`` and ``fused_link_stats`` also
  report per-site latency DISTRIBUTIONS (p50/p90/p99/max ms) off the
  utils/telemetry histogram layer (``latency_ms_*`` blocks), and
  ``streaming_stats(trace_path=...)`` leaves a Chrome trace of one
  streaming pass for tools/trace_report.py. Since ISSUE 9 both blocks
  additionally report ``roofline_by_site`` — achieved GB/s / GFLOP/s
  and %-of-peak per dispatch site from XLA's own cost analysis
  (utils/programs observatory) x the measured p50, replacing hand
  byte/FLOP formulas with compiled-graph truth — and the exported
  trace embeds the ``siteCosts``/``devicePeaks`` riders so
  trace_report prints GB/s per span label.

- ``multi_stream_stats`` (ISSUE 11 tentpole): S concurrent streams
  through the stream-axis fleet receiver
  (``framebatch.receive_streams``) vs S independent single-stream
  receivers — <= 2 dispatches per CHUNK-STEP independent of S
  (asserted), lane-for-lane bit-identity per stream, aggregate
  samples/s per dp mesh size (``sps_by_devices`` — the scaling
  record the ROADMAP's "many streams, one device fleet" item asks
  for), active-streams gauge, latency + roofline blocks.

Standalone: ``ZIRIA_TOOL_ALLOW_CPU=1 python tools/rx_dispatch_bench.py``
runs all at shrunk sizes on CPU (results labelled platform=cpu,
never mistakable for chip evidence). Emits ONE JSON object.
"""

import json
import os
import sys
import time

import numpy as np

# run as a script from tools/: only tools/ lands on sys.path, the repo
# root is not — same bootstrap as viterbi_batch_sweep.py
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))


def _fence(x):
    # device arrays need a copy-out fence; host-complete results (the
    # receive paths return numpy-backed RxResult lists) do not
    if hasattr(x, "ravel"):
        np.asarray(np.ravel(x)[:1])


def _timed(fn, *args, reps=1, tries=3):
    fn(*args)                       # warm (compile)
    best = float("inf")
    for _ in range(tries):
        t0 = time.perf_counter()
        o = None
        for _ in range(reps):
            o = fn(*args)
        _fence(o)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def _roofline_by_site(obs, lat_blocks, device_kind):
    """Per-site achieved GB/s / GFLOP/s (ISSUE 9): XLA's own cost
    analysis for the site's compiled program (utils/programs — the
    observatory noted fn+avals at the dispatch site) divided by the
    site's measured p50 latency from the telemetry histograms. The
    p50 is the histogram's power-of-two bucket UPPER bound (<= 2x the
    true p50), so the achieved numbers are conservative lower bounds.
    ``pct_hbm_peak``/``pct_flops_peak`` appear only for device kinds
    in the peaks table (utils/programs.DEVICE_PEAKS) — unknown kinds
    report absolutes, never a percentage of the wrong ceiling."""
    from ziria_tpu.utils import programs

    lat = {}
    for b in lat_blocks:
        lat.update({k: v for k, v in b.items() if v})
    out = {}
    for site, c in sorted(obs.site_costs().items()):
        row = {"flops": c["flops"],
               "bytes_accessed": c["bytes_accessed"]}
        if c.get("peak_bytes"):
            row["peak_bytes"] = c["peak_bytes"]
        p50_ms = (lat.get(site) or {}).get("p50")
        if p50_ms:
            row["p50_ms"] = p50_ms
            row.update(programs.roofline(
                p50_ms / 1e3, bytes_accessed=c["bytes_accessed"],
                flops=c["flops"], device_kind=device_kind))
        out[site] = row
    return out


def _device_kind():
    import jax
    return getattr(jax.devices()[0], "device_kind", "?")


def _latency_block(reg):
    """Per-site latency summaries (ms) off a telemetry registry's
    dispatch histograms: {site: {count, mean, p50, p90, p99, max}} —
    distribution-level numbers from the histogram layer, NOT summed
    means (p50/p99 are the power-of-two bucket quantile bounds, max
    and mean exact)."""
    from ziria_tpu.utils import telemetry

    out = {}
    for (name, labels), m in reg.metrics():
        if name == telemetry.DISPATCH_HISTOGRAM:
            out[dict(labels).get("site", "")] = m.summary(
                scale=1e3, ndigits=4)
    return out


def quantized_sweep(B=128, n_bytes=1000, rate_mbps=54,
                    k1=4, k2=12):
    """float32 vs int16 saturating path metrics on the batched DATA
    decode: correctness gate + marginal step time for each. Returns a
    flat dict (bench.py stages store it verbatim)."""
    import jax
    import jax.numpy as jnp

    from ziria_tpu.phy.wifi import rx, tx
    from ziria_tpu.phy.wifi.params import RATES, n_symbols
    from ziria_tpu.utils.bits import bytes_to_bits

    rate = RATES[rate_mbps]
    n_sym = n_symbols(n_bytes, rate)
    n_psdu_bits = 8 * n_bytes
    rng = np.random.default_rng(11)
    psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
    frame = np.asarray(tx.encode_frame(psdu, rate_mbps))
    want = np.asarray(bytes_to_bits(psdu))
    frames = jnp.asarray(np.broadcast_to(
        frame, (B,) + frame.shape).copy())

    out = {"batch": B, "frame_bytes": n_bytes, "rate_mbps": rate_mbps,
           "frame_len": int(frame.shape[0])}
    bits_by_md = {}
    for md in ("float32", "int16"):
        def decode(f, _md=md):
            return rx.decode_data_batch(
                f, rate, n_sym, n_psdu_bits, viterbi_metric=_md)[0]

        got = np.asarray(jax.jit(decode)(frames))
        assert np.array_equal(got[0], want) \
            and np.array_equal(got[-1], want), f"{md} decode mismatch"
        bits_by_md[md] = got

        # marginal step: K-spread of a jitted device-side loop with
        # runtime-zero feedback (the next input depends on the last
        # output, so the body cannot be hoisted), cancelling the fixed
        # per-call dispatch/link cost
        @jax.jit
        def loop(x, k, _md=md):
            def body(_i, carry):
                s, acc = carry
                bits = rx.decode_data_batch(
                    x + s, rate, n_sym, n_psdu_bits,
                    viterbi_metric=_md)[0]
                s2 = bits[0, 0].astype(jnp.float32) * 1e-30
                return s2, acc + bits.sum() * 1e-30
            return jax.lax.fori_loop(
                0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

        t_k1 = _timed(loop, frames, jnp.int32(k1))
        t_k2 = _timed(loop, frames, jnp.int32(k2))
        t_step = max((t_k2 - t_k1) / (k2 - k1), 1e-9)
        short = "f32" if md == "float32" else "i16"
        out[f"t_step_{short}_s"] = round(t_step, 6)
        out[f"sps_{short}"] = round(B * frame.shape[0] / t_step, 1)
    out["i16_matches_f32"] = bool(
        np.array_equal(bits_by_md["int16"], bits_by_md["float32"]))
    out["i16_over_f32"] = round(
        out["t_step_i16_s"] / max(out["t_step_f32_s"], 1e-12), 3)
    return out


def mixed_dispatch_stats(n_bytes=100, viterbi_metric=None):
    """All-8-rates corpus through the bucketed host dispatch vs the
    one-``lax.switch`` mixed dispatch: DATA-stage compile counts
    (measured lru_cache growth), wall times, and a lane-for-lane
    bit-identity gate. Returns a flat dict."""
    from ziria_tpu.backend import framebatch
    from ziria_tpu.phy.wifi import rx, tx
    from ziria_tpu.phy.wifi.params import RATES

    rng = np.random.default_rng(12)
    caps = []
    for m in sorted(RATES):
        psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
        s = np.asarray(tx.encode_frame(psdu, m))
        caps.append(np.concatenate(
            [np.zeros((50, 2), np.float32), s], axis=0))

    # -- before: host-side bucketed dispatch, one jit per (rate, bucket)
    rx._jit_decode_data_bucketed.cache_clear()
    res_b = [rx.receive(c, viterbi_metric=viterbi_metric) for c in caps]
    compiles_bucketed = rx._jit_decode_data_bucketed.cache_info().currsize
    t_bucketed = _timed(
        lambda: [rx.receive(c, viterbi_metric=viterbi_metric)
                 for c in caps])

    # -- after: ONE jitted lax.switch serving every rate in the batch.
    # batched_acquire is pinned OFF so this artifact keeps measuring
    # the mixed-dispatch lever alone, comparable with prior rounds;
    # the acquisition before/after is batched_acquire_stats's job
    rx._jit_decode_data_mixed.cache_clear()
    res_m = framebatch.receive_many(caps, viterbi_metric=viterbi_metric,
                                    batched_acquire=False)
    compiles_mixed = rx._jit_decode_data_mixed.cache_info().currsize
    t_mixed = _timed(
        lambda: framebatch.receive_many(
            caps, viterbi_metric=viterbi_metric, batched_acquire=False))

    assert all(a.ok and b.ok for a, b in zip(res_b, res_m))
    assert all(np.array_equal(a.psdu_bits, b.psdu_bits)
               for a, b in zip(res_b, res_m)), \
        "mixed dispatch diverged from the bucketed path"

    samples = sum(c.shape[0] for c in caps)
    return {
        "rates": len(caps), "frame_bytes": n_bytes,
        "viterbi_metric": viterbi_metric or "float32",
        "compiles_bucketed": compiles_bucketed,
        "compiles_mixed": compiles_mixed,
        # the DATA stage's device dispatch count per mixed batch:
        # one bucketed jit call per decodable frame vs one switch call
        "data_dispatches_bucketed": len(caps),
        "data_dispatches_mixed": 1,
        "t_bucketed_s": round(t_bucketed, 4),
        "t_mixed_s": round(t_mixed, 4),
        "sps_bucketed": round(samples / t_bucketed, 1),
        "sps_mixed": round(samples / t_mixed, 1),
        "bit_identical": True,
    }


def batched_acquire_stats(n_bytes=100, viterbi_metric=None):
    """Acquisition dispatch count + wall time of `receive_many` over
    an all-8-rates corpus, host-driven per-capture acquisition vs the
    one-dispatch batched path (acquire -> gather -> mixed decode),
    identity-gated lane for lane. Dispatches are measured with the
    instrumented counter (utils/dispatch.count_dispatches), so the
    artifact records the real before/after O(N) -> O(1) collapse, not
    arithmetic."""
    from ziria_tpu.backend import framebatch
    from ziria_tpu.phy.wifi import tx
    from ziria_tpu.phy.wifi.params import RATES
    from ziria_tpu.utils.dispatch import count_dispatches

    rng = np.random.default_rng(13)
    caps = []
    for m in sorted(RATES):
        psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
        s = np.asarray(tx.encode_frame(psdu, m))
        caps.append(np.concatenate(
            [np.zeros((50, 2), np.float32), s], axis=0))

    # -- before: host loop — sync + head CFO + SIGNAL per capture,
    #    a per-lane segment CFO, then the one mixed decode
    with count_dispatches() as d_host:
        res_h = framebatch.receive_many(
            caps, viterbi_metric=viterbi_metric, batched_acquire=False)
    t_host = _timed(lambda: framebatch.receive_many(
        caps, viterbi_metric=viterbi_metric, batched_acquire=False))

    # -- after: acquire -> gather -> decode, three dispatches total
    with count_dispatches() as d_bat:
        res_b = framebatch.receive_many(
            caps, viterbi_metric=viterbi_metric, batched_acquire=True)
    t_bat = _timed(lambda: framebatch.receive_many(
        caps, viterbi_metric=viterbi_metric, batched_acquire=True))

    assert all(a.ok and b.ok for a, b in zip(res_h, res_b))
    assert all(np.array_equal(a.psdu_bits, b.psdu_bits)
               for a, b in zip(res_h, res_b)), \
        "batched acquisition diverged from the host-acquire path"

    samples = sum(c.shape[0] for c in caps)
    return {
        "rates": len(caps), "frame_bytes": n_bytes,
        "viterbi_metric": viterbi_metric or "float32",
        "dispatches_host_acquire": d_host.total,
        "dispatches_batched_acquire": d_bat.total,
        "dispatch_breakdown_batched": dict(d_bat.counts),
        "dispatch_times_ms_host": d_host.times_ms(),
        "dispatch_times_ms_batched": d_bat.times_ms(),
        "t_host_acquire_s": round(t_host, 4),
        "t_batched_acquire_s": round(t_bat, 4),
        "sps_host_acquire": round(samples / t_host, 1),
        "sps_batched_acquire": round(samples / t_bat, 1),
        "bit_identical": True,
    }


def link_loopback_stats(n_frames=8, n_bytes=100, snr_db=28.0):
    """The closed TX -> channel -> RX loop, batched vs per-frame:
    dispatch counts (instrumented counter), wall times, frames/s, and
    a lane-for-lane identity gate. All 8 rates with mixed lengths ride
    one batch; the channel applies per-lane CFO + delay + AWGN with
    counter-derived keys, identical in both paths. Returns a flat
    dict."""
    from ziria_tpu.phy import link
    from ziria_tpu.phy.wifi.params import RATES
    from ziria_tpu.utils.dispatch import count_dispatches

    rng = np.random.default_rng(14)
    mbps = sorted(RATES) * (-(-n_frames // len(RATES)))
    mbps = mbps[:n_frames]
    lens = [max(5, n_bytes - 7 * (k % 5)) for k in range(n_frames)]
    psdus = [rng.integers(0, 256, n).astype(np.uint8) for n in lens]
    cfo = [(-1) ** k * 1e-4 * (k % 7 + 1) for k in range(n_frames)]
    delay = [20 + 13 * k for k in range(n_frames)]
    # fused=False: this artifact measures the STAGING lever alone
    # (comparable with prior rounds); fused_link_stats owns the fused
    # graph's numbers
    kw = dict(snr_db=snr_db, cfo=cfo, delay=delay, seed=6, fused=False)

    with count_dispatches() as d_pf:
        res_f = link.loopback_many(psdus, mbps, batched_tx=False, **kw)
    t_pf = _timed(lambda: link.loopback_many(
        psdus, mbps, batched_tx=False, **kw))

    with count_dispatches() as d_bat:
        res_b = link.loopback_many(psdus, mbps, batched_tx=True, **kw)
    t_bat = _timed(lambda: link.loopback_many(
        psdus, mbps, batched_tx=True, **kw))

    assert all(a.ok and b.ok for a, b in zip(res_f, res_b))
    assert all(np.array_equal(a.psdu_bits, b.psdu_bits)
               for a, b in zip(res_f, res_b)), \
        "batched loopback diverged from the per-frame path"

    return {
        "frames": n_frames, "max_frame_bytes": max(lens),
        "rates": sorted(set(mbps)), "snr_db": snr_db,
        "dispatches_perframe": d_pf.total,
        "dispatches_batched": d_bat.total,
        "dispatch_breakdown_batched": dict(d_bat.counts),
        "dispatch_times_ms_batched": d_bat.times_ms(),
        "t_perframe_s": round(t_pf, 4),
        "t_batched_s": round(t_bat, 4),
        "fps_perframe": round(n_frames / t_pf, 1),
        "fps_batched": round(n_frames / t_bat, 1),
        "bit_identical": True,
    }


def fused_link_stats(n_frames=8, n_bytes=100, snr_db=28.0):
    """The ONE-dispatch fused loopback graph vs its staged ~5-dispatch
    oracle: dispatch counts AND per-site wall times (the extended
    utils/dispatch counter), wall times, frames/s, and a lane-for-lane
    identity gate — with ``check_fcs=True`` so the batched-CRC tail
    (one vmapped dispatch instead of a host check per lane) is in the
    measurement. Returns a flat dict."""
    from ziria_tpu.phy import link
    from ziria_tpu.phy.wifi.params import RATES
    from ziria_tpu.utils.dispatch import count_dispatches

    rng = np.random.default_rng(15)
    mbps = (sorted(RATES) * (-(-n_frames // len(RATES))))[:n_frames]
    lens = [max(5, n_bytes - 7 * (k % 5)) for k in range(n_frames)]
    psdus = [rng.integers(0, 256, n).astype(np.uint8) for n in lens]
    cfo = [(-1) ** k * 1e-4 * (k % 7 + 1) for k in range(n_frames)]
    delay = [20 + 13 * k for k in range(n_frames)]
    kw = dict(snr_db=snr_db, cfo=cfo, delay=delay, seed=6,
              add_fcs=True, check_fcs=True)

    from ziria_tpu.utils import programs, telemetry

    # collect() around BOTH the counted run and the timed repeats so
    # the per-site latency histograms hold enough samples for the
    # p50/p99 bounds to mean something; the observatory wraps both
    # variants so every fired site contributes its compiled program's
    # analytical cost to the per-site roofline block
    with programs.observing() as obs:
        with telemetry.collect() as reg_st:
            with count_dispatches() as d_st:
                res_s = link.loopback_many(psdus, mbps, fused=False,
                                           **kw)
            t_st = _timed(lambda: link.loopback_many(
                psdus, mbps, fused=False, **kw))

        with telemetry.collect() as reg_fu:
            with count_dispatches() as d_fu:
                res_f = link.loopback_many(psdus, mbps, fused=True,
                                           **kw)
            t_fu = _timed(lambda: link.loopback_many(
                psdus, mbps, fused=True, **kw))

    assert all(a.ok == b.ok and a.crc_ok == b.crc_ok
               and a.rate_mbps == b.rate_mbps
               and a.length_bytes == b.length_bytes
               and np.array_equal(a.psdu_bits, b.psdu_bits)
               for a, b in zip(res_s, res_f)), \
        "fused loopback diverged from the staged path"

    return {
        "frames": n_frames, "max_frame_bytes": max(lens),
        "rates": sorted(set(mbps)), "snr_db": snr_db,
        "check_fcs": True,
        "dispatches_staged": d_st.total,
        "dispatches_fused": d_fu.total,
        "dispatch_breakdown_staged": dict(d_st.counts),
        "dispatch_times_ms_staged": d_st.times_ms(),
        "dispatch_times_ms_fused": d_fu.times_ms(),
        # per-dispatch latency DISTRIBUTIONS (telemetry histograms):
        # the fused block's "link.fused" row is the per-dispatch
        # p50/p99 the serving work asks for
        "latency_ms_staged": _latency_block(reg_st),
        "latency_ms_fused": _latency_block(reg_fu),
        # per-site achieved GB/s / GFLOP/s and %-of-peak from XLA
        # cost analysis x measured p50 — the "link.fused" row is the
        # fused dispatch's distance to the roofline (compiled-graph
        # truth, not bench.py's hand formulas)
        "roofline_by_site": _roofline_by_site(
            obs, [_latency_block(reg_st), _latency_block(reg_fu)],
            _device_kind()),
        "t_staged_s": round(t_st, 4),
        "t_fused_s": round(t_fu, 4),
        "fps_staged": round(n_frames / t_st, 1),
        "fps_fused": round(n_frames / t_fu, 1),
        "bit_identical": True,
    }


def ber_sweep_stats(n_frames=16, n_bytes=50, rates=(6, 24, 54),
                    snrs=(2.0, 5.0, 8.0), seeds=(7,)):
    """A rates x SNR x seeds BER sweep through `link.sweep_ber` (ONE
    lax.scan dispatch) vs the python loop of per-batch
    `loopback_ber_bits` points (~3 instrumented dispatches per
    rate-point), error counts gated integer-identical. Records sweep
    points/s and samples/s. Returns a flat dict."""
    from ziria_tpu.phy import link
    from ziria_tpu.utils.bits import np_bytes_to_bits
    from ziria_tpu.utils.dispatch import count_dispatches

    rng = np.random.default_rng(16)
    psdus = rng.integers(0, 256, (n_frames, n_bytes)).astype(np.uint8)
    want = np.stack([np_bytes_to_bits(p) for p in psdus])

    with count_dispatches() as d_sw:
        errs = link.sweep_ber(psdus, rates, snrs, seeds)
    t_sw = _timed(lambda: link.sweep_ber(psdus, rates, snrs, seeds))

    with count_dispatches() as d_lp:
        for ri, m in enumerate(rates):
            for si, s in enumerate(snrs):
                for ki, sd in enumerate(seeds):
                    got = link.loopback_ber_bits(psdus, m, s, sd)
                    e = int(np.sum(got != want))
                    assert e == int(errs[ri, si, ki]), \
                        "sweep diverged from the per-batch loop"
    t_lp = _timed(lambda: [
        link.loopback_ber_bits(psdus, m, s, sd)
        for m in rates for s in snrs for sd in seeds])

    n_points = len(rates) * len(snrs) * len(seeds)
    bits_per_point = n_frames * 8 * n_bytes
    return {
        "frames": n_frames, "frame_bytes": n_bytes,
        "rates": list(rates), "snrs": list(snrs),
        "seeds": list(seeds), "points": n_points,
        "dispatches_sweep": d_sw.total,
        "dispatches_loop": d_lp.total,
        "dispatch_times_ms_sweep": d_sw.times_ms(),
        "t_sweep_s": round(t_sw, 4),
        "t_loop_s": round(t_lp, 4),
        "points_per_s_sweep": round(n_points / t_sw, 2),
        "points_per_s_loop": round(n_points / t_lp, 2),
        "bits_per_point": bits_per_point,
        "sweep_sps": round(
            n_points * bits_per_point / max(t_sw, 1e-9), 1),
        "counts_identical": True,
    }


#: per-profile BER-envelope bounds at the TOP of the sweep's SNR grid
#: (the "bounded error floor at high SNR" acceptance gates of ISSUE
#: 15). flat must be error-free at high SNR; the equalizable profiles
#: (multipath-only) must stay near-clean through the LTS/ZF front
#: end; the burst/SCO/drift profiles are ALLOWED a floor — bounded,
#: never unbounded garbage. Calibrated with >= 3x margin over
#: measured CPU values at the bench geometry.
CHANNEL_BER_ENVELOPES = {
    "flat": 0.0, "mild": 0.02, "urban": 0.05, "severe": 0.15,
    "sco": 0.10, "doppler": 0.10, "bursty": 0.30, "hostile": 0.30,
}


def channel_sweep_stats(n_frames=8, n_bytes=24, rates=(6, 24, 54),
                        snrs=(12.0, 30.0), seeds=(7,),
                        profiles=("flat", "mild", "urban", "severe",
                                  "sco", "doppler", "bursty",
                                  "hostile")):
    """The channel-hostile BER gate (ISSUE 15): a rates x SNR x
    PROFILE waterfall through `link.sweep_ber`'s profile axis — STILL
    one `lax.scan` dispatch — gated three ways:

    - the ``flat`` column's error counts are bit-identical to the
      profile-less sweep (flat IS the unprofiled channel);
    - every profile's BER at the TOP SNR point stays under its
      `CHANNEL_BER_ENVELOPES` bound (bounded error floors — a deep
      fade degrades, it never explodes);
    - BER is non-increasing in SNR per profile within counting noise
      (the waterfall actually falls).

    Records ``ber_floor_<profile>`` per profile (the BENCH_TRAJECTORY
    metrics; lower is better) plus sweep timing. Returns a flat
    dict."""
    from ziria_tpu.phy import link
    from ziria_tpu.utils.dispatch import count_dispatches

    if "flat" not in profiles:
        # the stage IS the flat-identity gate: without the anchor
        # column the base-sweep comparison would be vacuous and the
        # ledger would record a gate that never ran
        raise ValueError("channel_sweep_stats needs 'flat' in "
                         "profiles (the identity-anchor column)")
    rng = np.random.default_rng(15)
    psdus = rng.integers(0, 256, (n_frames, n_bytes)).astype(np.uint8)
    bits_total = n_frames * 8 * n_bytes

    base = link.sweep_ber(psdus, rates, snrs, seeds)
    with count_dispatches() as d_sw:
        errs = link.sweep_ber(psdus, rates, snrs, seeds,
                              profiles=profiles)
    t_sw = _timed(lambda: link.sweep_ber(psdus, rates, snrs, seeds,
                                         profiles=profiles))
    assert errs.shape == (len(rates), len(profiles), len(snrs),
                          len(seeds)), errs.shape

    flat_cols = [pi for pi, p in enumerate(profiles) if p == "flat"]
    flat_identical = all(
        np.array_equal(errs[:, pi], base) for pi in flat_cols)
    assert flat_identical, \
        "flat profile column diverged from the unprofiled sweep"

    floors, monotone = {}, {}
    for pi, p in enumerate(profiles):
        # BER per SNR point, averaged over rates and seeds
        ber = errs[:, pi].sum(axis=(0, 2)) \
            / (len(rates) * len(seeds) * bits_total)
        floors[p] = float(ber[-1])
        bound = CHANNEL_BER_ENVELOPES[p]
        assert ber[-1] <= bound, \
            (f"profile {p}: BER floor {ber[-1]:.4f} at "
             f"{snrs[-1]} dB exceeds its {bound} envelope")
        # counting noise on a small smoke grid: allow a 2e-3 rise
        monotone[p] = bool(np.all(np.diff(ber) <= 2e-3))
        assert monotone[p], f"profile {p}: BER rose with SNR: {ber}"

    n_points = len(rates) * len(snrs) * len(seeds) * len(profiles)
    out = {
        "frames": n_frames, "frame_bytes": n_bytes,
        "rates": list(rates), "snrs": list(snrs),
        "seeds": list(seeds), "profiles": list(profiles),
        "points": n_points,
        "dispatches_sweep": d_sw.total,
        "dispatch_times_ms_sweep": d_sw.times_ms(),
        "t_sweep_s": round(t_sw, 4),
        "points_per_s_sweep": round(n_points / t_sw, 2),
        "flat_identical": flat_identical,
        "envelopes": {p: CHANNEL_BER_ENVELOPES[p] for p in profiles},
    }
    for p, v in floors.items():
        out[f"ber_floor_{p}"] = round(v, 6)
    return out


def streaming_stats(n_frames=16, n_bytes=12, snr_db=30.0,
                    chunk_len=4096, frame_len=1024, k=8,
                    trace_path=None):
    """An N-frame continuous stream through the chunked streaming
    receiver vs the per-capture oracle over the same detected windows:
    dispatch counts (instrumented counter — the O(chunks) vs O(frames)
    collapse), wall times, samples/s, the in-flight depth gauge, and
    a frame-for-frame identity gate (every emitted start must hit the
    synthesizer's ground truth; every RxResult must be bit-identical
    to the oracle's). ``check_fcs=True`` so the masked-CRC tail rides
    the measurement. Per-chunk/per-dispatch latency lands as p50/p99
    blocks from the telemetry histogram layer (``latency_ms_*``), and
    ``trace_path`` — when given — additionally records one streaming
    pass as a Chrome trace there (chunk/decode spans, in-flight and
    carry-depth counter tracks, compile events; summarize with
    tools/trace_report.py). Returns a flat dict."""
    from ziria_tpu.backend import framebatch
    from ziria_tpu.phy import link
    from ziria_tpu.phy.wifi.params import RATES
    from ziria_tpu.utils import telemetry
    from ziria_tpu.utils.dispatch import count_dispatches

    rng = np.random.default_rng(17)
    mbps = (sorted(RATES) * (-(-n_frames // len(RATES))))[:n_frames]
    psdus = [rng.integers(0, 256, n_bytes).astype(np.uint8)
             for _ in range(n_frames)]
    stream, starts = link.stream_many(
        psdus, mbps, snr_db=snr_db, cfo=1e-4, delay=60, seed=8,
        add_fcs=True, tail=frame_len)
    kw = dict(chunk_len=chunk_len, frame_len=frame_len,
              max_frames_per_chunk=k, check_fcs=True)

    from ziria_tpu.utils import programs

    # collect() spans the counted run AND the timed repeats: the
    # per-chunk latency histograms see chunks x repeats samples; the
    # observatory wraps both paths so the chunk-scan and decode
    # programs contribute their compiled cost to the per-site roofline
    with programs.observing() as obs:
        with telemetry.collect() as reg_pc:
            with count_dispatches() as d_pc:
                res_p, st_p = framebatch.receive_stream(
                    stream, streaming=False, **kw)
            t_pc = _timed(lambda: framebatch.receive_stream(
                stream, streaming=False, **kw))

        with telemetry.collect() as reg_st:
            with count_dispatches() as d_st:
                res_s, st_s = framebatch.receive_stream(
                    stream, streaming=True, **kw)
            t_st = _timed(lambda: framebatch.receive_stream(
                stream, streaming=True, **kw))

    roofline_by_site = _roofline_by_site(
        obs, [_latency_block(reg_pc), _latency_block(reg_st)],
        _device_kind())

    if trace_path:
        # one warm streaming pass under an exporting trace: spans +
        # counter tracks + (warm, so few) compile events — plus the
        # observatory's analytical site costs and the device peaks as
        # trace metadata, so tools/trace_report.py can print achieved
        # GB/s per span label straight off the file
        with telemetry.tracing(trace_path) as tr:
            framebatch.receive_stream(stream, streaming=True, **kw)
            tr.set_metadata("siteCosts", {
                s: {"flops": r["flops"],
                    "bytes_accessed": r["bytes_accessed"]}
                for s, r in roofline_by_site.items()})
            tr.set_metadata("deviceKind", _device_kind())
            tr.set_metadata("devicePeaks",
                            programs.peaks_for(_device_kind()))

    assert [f.start for f in res_s] == list(starts), \
        "streaming starts diverged from the synthesizer ground truth"
    # identity first (field for field, failures included), THEN the
    # all-decoded gate — a lane failing identically in both paths is
    # not a divergence and must not be reported as one
    assert len(res_p) == len(res_s) and all(
        a.start == b.start and a.result.ok == b.result.ok
        and a.result.crc_ok == b.result.crc_ok
        and a.result.rate_mbps == b.result.rate_mbps
        and a.result.length_bytes == b.result.length_bytes
        and np.array_equal(a.result.psdu_bits, b.result.psdu_bits)
        for a, b in zip(res_p, res_s)), \
        "streaming receive diverged from the per-capture path"
    assert all(f.result.ok and f.result.crc_ok for f in res_s), \
        "a stimulus frame failed to decode (identically in both paths)"

    n_samples = stream.shape[0]
    return {
        "frames": n_frames, "frame_bytes": n_bytes, "snr_db": snr_db,
        "stream_samples": n_samples, "chunks": st_s.chunks,
        "chunk_len": chunk_len, "frame_len": frame_len,
        "dispatches_percapture": d_pc.total,
        "dispatches_streaming": d_st.total,
        "dispatch_breakdown_streaming": dict(d_st.counts),
        "dispatch_times_ms_streaming": d_st.times_ms(),
        "dispatch_times_ms_percapture": d_pc.times_ms(),
        # distribution-level per-site latency (telemetry histograms):
        # "rx.stream_chunk_multi" is the per-chunk p50/p99 the serving
        # harness will report against SLOs — not a summed mean
        "latency_ms_streaming": _latency_block(reg_st),
        "latency_ms_percapture": _latency_block(reg_pc),
        # per-site roofline from the compiled graphs: achieved GB/s /
        # GFLOP/s per dispatch site (rx.stream_chunk_multi: the number the
        # serving work reports against the hardware ceiling)
        "roofline_by_site": roofline_by_site,
        "trace_path": trace_path,
        "max_in_flight": st_s.max_in_flight,
        "overflow_chunks": st_s.overflow_chunks,
        "t_percapture_s": round(t_pc, 4),
        "t_streaming_s": round(t_st, 4),
        "sps_percapture": round(n_samples / t_pc, 1),
        "sps_streaming": round(n_samples / t_st, 1),
        "bit_identical": True,
    }


def multi_stream_stats(n_streams=8, frames_per_stream=4, n_bytes=12,
                       snr_db=30.0, chunk_len=4096, frame_len=1024,
                       k=8, mesh_sizes=None):
    """S concurrent I/Q streams through the stream-axis fleet receiver
    (``framebatch.receive_streams`` + ``MultiStreamReceiver``) vs S
    independent single-stream receivers (the oracle): dispatch counts
    per chunk-step (<= 2 *independent of S* — asserted), aggregate
    samples/s, the active-streams gauge, lane-for-lane bit-identity
    per stream (results AND starts vs the synthesizer's ground
    truth), per-site latency distributions and roofline blocks, and
    — the scaling record — aggregate samples/s per dp mesh size
    (``sps_by_devices``: the unsharded run is the 1-device point,
    then ``frame_mesh(n)``-sharded fleets for every usable n in
    ``mesh_sizes``; identical per-device program, streams
    independent, so the sharded results are gated bit-identical
    too). Returns a flat dict."""
    import jax

    from ziria_tpu.backend import framebatch
    from ziria_tpu.parallel import batch as pbatch
    from ziria_tpu.phy import link
    from ziria_tpu.phy.wifi.params import RATES
    from ziria_tpu.utils import programs, telemetry
    from ziria_tpu.utils.dispatch import count_dispatches

    rng = np.random.default_rng(23)
    rates_all = sorted(RATES)
    psdus_per, rates_per = [], []
    for i in range(n_streams):
        rates = [rates_all[(i + j) % len(rates_all)]
                 for j in range(frames_per_stream)]
        rates_per.append(rates)
        psdus_per.append([rng.integers(0, 256, n_bytes)
                          .astype(np.uint8) for _ in rates])
    streams, starts = link.stream_many_multi(
        psdus_per, rates_per, snr_db=snr_db, cfo=1e-4, delay=60,
        seed=9, add_fcs=True, tail=frame_len)
    kw = dict(chunk_len=chunk_len, frame_len=frame_len,
              max_frames_per_chunk=k, check_fcs=True)
    n_samples = sum(int(s.shape[0]) for s in streams)

    def gate(res_a, res_b, what):
        assert [len(r) for r in res_a] == [len(r) for r in res_b], what
        for i in range(n_streams):
            assert [f.start for f in res_a[i]] == list(starts[i]), \
                f"{what}: stream {i} starts diverged from ground truth"
            for a, b in zip(res_a[i], res_b[i]):
                assert (a.start == b.start
                        and a.result.ok == b.result.ok
                        and a.result.crc_ok == b.result.crc_ok
                        and a.result.rate_mbps == b.result.rate_mbps
                        and a.result.length_bytes == b.result.length_bytes
                        and np.array_equal(a.result.psdu_bits,
                                           b.result.psdu_bits)), \
                    f"{what}: stream {i} diverged lane for lane"

    with programs.observing() as obs:
        with telemetry.collect() as reg_or:
            def lone():
                return [framebatch.receive_stream(s, **kw)[0]
                        for s in streams]

            with count_dispatches() as d_or:
                res_o = lone()
            t_or = _timed(lone)

        with telemetry.collect() as reg_ml:
            with count_dispatches() as d_ml:
                res_m, st_m = framebatch.receive_streams(
                    streams, **kw)
            t_ml = _timed(lambda: framebatch.receive_streams(
                streams, **kw))

    gate(res_m, res_o, "fleet vs S independent receivers")
    assert all(f.result.ok and f.result.crc_ok
               for r in res_m for f in r), \
        "a stimulus frame failed to decode (identically in both paths)"
    # the tentpole pin: <= 2 dispatches per chunk-step, S-free
    assert d_ml.total <= 2 * st_m.chunk_steps, \
        (dict(d_ml.counts), st_m)

    # aggregate samples/s per device count: the unsharded fleet is the
    # 1-device point; each usable mesh size reruns the SAME fleet with
    # the stream axis sharded over frame_mesh(n) and gates identity
    sps_by_devices = {"1": round(n_samples / t_ml, 1)}
    devs = jax.devices()
    if mesh_sizes is None:
        # the largest mesh the fleet can shard evenly over — on the
        # 8-virtual-device CPU box that is 8 for S=8 and 4 for the
        # smoke's S=4 (never silently no mesh point at all)
        usable = [n for n in range(2, len(devs) + 1)
                  if n_streams % n == 0]
        sizes = [max(usable)] if usable else []
    else:
        sizes = sorted(set(mesh_sizes))
    for n in sizes:
        if n <= 1 or n > len(devs) or n_streams % n:
            continue
        mesh = pbatch.frame_mesh(n)
        res_s, _st_s = framebatch.receive_streams(
            streams, mesh=mesh, **kw)
        gate(res_s, res_m, f"sharded fleet (dp={n})")
        t_n = _timed(lambda _m=mesh: framebatch.receive_streams(
            streams, mesh=_m, **kw))
        sps_by_devices[str(n)] = round(n_samples / t_n, 1)

    out = {
        "streams": n_streams, "frames_per_stream": frames_per_stream,
        "frame_bytes": n_bytes, "snr_db": snr_db,
        "stream_samples_total": n_samples,
        "chunk_steps": st_m.chunk_steps,
        "chunk_len": chunk_len, "frame_len": frame_len,
        "dispatches_oracle": d_or.total,
        "dispatches_multi": d_ml.total,
        "dispatch_breakdown_multi": dict(d_ml.counts),
        "dispatch_times_ms_multi": d_ml.times_ms(),
        "dispatch_times_ms_oracle": d_or.times_ms(),
        # the S-independence record, machine-checkable: dispatches per
        # chunk-step for THIS S (pinned <= 2 above)
        "dispatches_per_chunk_step": round(
            d_ml.total / max(st_m.chunk_steps, 1), 3),
        "max_active_streams": st_m.max_active_streams,
        "max_in_flight": st_m.max_in_flight,
        "overflow_chunks": st_m.overflow_chunks,
        "latency_ms_multi": _latency_block(reg_ml),
        "latency_ms_oracle": _latency_block(reg_or),
        "roofline_by_site": _roofline_by_site(
            obs, [_latency_block(reg_or), _latency_block(reg_ml)],
            _device_kind()),
        "t_oracle_s": round(t_or, 4),
        "t_multi_s": round(t_ml, 4),
        "sps_oracle": round(n_samples / t_or, 1),
        "sps_multi": round(n_samples / t_ml, 1),
        "sps_by_devices": sps_by_devices,
        "bit_identical": True,
    }
    ks = sorted(sps_by_devices, key=int)
    if len(ks) > 1:
        out["mesh_scaling"] = round(
            sps_by_devices[ks[-1]] / max(sps_by_devices["1"], 1e-9), 3)
        out["mesh_devices_max"] = int(ks[-1])
    return out


def resilience_stats(n_streams=4, frames_per_stream=3, n_bytes=12,
                     snr_db=30.0, chunk_len=4096, frame_len=1024,
                     k=8, seed=12):
    """Chaos run of the multi-stream fleet (ISSUE 12): the fleet is
    fed push-driven under an injected fault plan — transient scan and
    decode faults (retried), a dispatch-latency fault, a NaN slab into
    stream 0 (sanitize=True zero-and-quarantine, rejoin after 2 clean
    chunks), and a one-shot FATAL decode fault (degrade to the
    per-capture oracle) — asserting ZERO crashes, healthy-lane
    lane-for-lane bit-identity vs a fault-free run, no garbage
    emissions from the poisoned lane, full quarantine recovery
    (rejoined by stream end), and a checkpoint/restore roundtrip
    bit-identical to an uninterrupted receiver. Records
    retries/fallbacks/quarantines/sanitized counts and the fault rate
    per 100 chunk-steps. Returns a flat dict (metric:
    ``faults_recovered``)."""
    from ziria_tpu.backend import framebatch
    from ziria_tpu.phy import link
    from ziria_tpu.phy.wifi.params import RATES
    from ziria_tpu.utils import faults, telemetry
    from ziria_tpu.utils.dispatch import count_dispatches

    rng = np.random.default_rng(29)
    rates_all = sorted(RATES)
    psdus_per, rates_per = [], []
    for i in range(n_streams):
        rates = [rates_all[(i + j) % len(rates_all)]
                 for j in range(frames_per_stream)]
        rates_per.append(rates)
        psdus_per.append([rng.integers(0, 256, n_bytes)
                          .astype(np.uint8) for _ in rates])
    # every stream spreads its frames ~3 chunks apart so the workload
    # spans several chunk-steps AND several decode dispatches: the
    # quarantine (on stream 0) gets clean chunks to rejoin across,
    # and the one-shot fatal decode fault has a later decode to hit
    streams, starts = link.stream_many_multi(
        psdus_per, rates_per, snr_db=snr_db, cfo=1e-4, delay=60,
        seed=11, add_fcs=True, tail=frame_len,
        gaps=[[9000] * (frames_per_stream - 1)] * n_streams)
    kw = dict(chunk_len=chunk_len, frame_len=frame_len,
              max_frames_per_chunk=k, check_fcs=True)

    # fault-free reference (also pre-compiles both fleet programs so
    # the chaos pass times recovery, not first-contact compiles)
    res_c, st_c = framebatch.receive_streams(streams, **kw)
    per_c = res_c

    specs = (
        faults.FaultSpec("rx.stream_chunk_multi", "transient",
                         every=3),
        faults.FaultSpec("rx.stream_decode_multi", "transient",
                         every=4),
        faults.FaultSpec("rx.stream_chunk_multi", "delay",
                         calls=(4,), delay_s=0.02),
        faults.FaultSpec("rx.push.s0", "nan_slab", calls=(1,),
                         fraction=0.2),
        faults.FaultSpec("rx.stream_decode_multi", "fatal",
                         calls=(1,), count=1),
    )
    t0 = time.perf_counter()
    with telemetry.collect() as reg:
        with count_dispatches() as d:
            with faults.inject(*specs, seed=seed) as plan:
                msr = framebatch.MultiStreamReceiver(
                    n_streams, sanitize=True, rejoin_after=2, **kw)
                got = []
                step = chunk_len // 2
                hi = max(int(s.shape[0]) for s in streams)
                for a in range(0, hi, step):
                    got += msr.push_many(
                        [s[a: a + step] for s in streams])
                got += msr.flush()
    t_chaos = time.perf_counter() - t0
    # reaching here IS the first gate: zero process crashes
    per = [[] for _ in range(n_streams)]
    for i, fr in got:
        per[i].append(fr)

    # attribution: streams whose push seam a data fault actually hit
    corrupted = set()
    for site, kind, _idx in plan.fired:
        if site.startswith("rx.push.s"):
            corrupted.add(int(site[len("rx.push.s"):]))
    same = (lambda a, b: a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)
    for i in range(n_streams):
        if i in corrupted:
            # poisoned lane: every surviving frame must match the
            # clean run (dropped-while-quarantined, never garbage)
            clean_by_start = {f.start: f for f in per_c[i]}
            for f in per[i]:
                assert f.start in clean_by_start and same(
                    f.result, clean_by_start[f.start].result), \
                    f"stream {i} emitted garbage under chaos"
        else:
            # healthy lanes: lane-for-lane bit-identical
            assert [f.start for f in per[i]] == \
                [f.start for f in per_c[i]], \
                f"healthy stream {i} diverged under chaos"
            for a, b in zip(per[i], per_c[i]):
                assert same(a.result, b.result), \
                    f"healthy stream {i} diverged under chaos"
    stats = msr.stats
    assert stats.quarantined_streams == 0, \
        "a quarantined stream failed to rejoin"
    dropped = sum(len(per_c[i]) - len(per[i]) for i in corrupted)

    # checkpoint/restore roundtrip: bit-identical resumption
    sr1 = framebatch.StreamReceiver(**kw)
    cut = int(streams[1].shape[0]) // 2
    first = sr1.push(streams[1][:cut])
    state, drained = sr1.checkpoint()
    first += drained
    sr2 = framebatch.StreamReceiver(checkpoint=state, **kw)
    rest = sr2.push(streams[1][cut:])
    rest += sr2.flush()
    resumed = first + rest
    assert [f.start for f in resumed] == \
        [f.start for f in per_c[1]] and all(
            same(a.result, b.result)
            for a, b in zip(resumed, per_c[1])), \
        "checkpoint/restore resumption diverged"

    snap = reg.snapshot()
    fired_by_kind = {}
    for _s, kind, _i in plan.fired:
        fired_by_kind[kind] = fired_by_kind.get(kind, 0) + 1
    return {
        "streams": n_streams, "frames_per_stream": frames_per_stream,
        "frame_bytes": n_bytes,
        "chunk_steps": stats.chunk_steps,
        "faults_injected": plan.total_fired,
        "faults_recovered": plan.total_fired,   # zero crashes gated
        "faults_by_kind": fired_by_kind,
        "faults_per_100_steps": round(
            100.0 * plan.total_fired / max(stats.chunk_steps, 1), 1),
        "retries": snap.get("resilience.retries", 0),
        "recovered": snap.get("resilience.recovered", 0),
        "fallbacks": snap.get("resilience.fallbacks", 0),
        "sanitized": stats.sanitized,
        "quarantines": stats.quarantines,
        "quarantined_at_end": stats.quarantined_streams,
        "lane_blowups": stats.lane_blowups,
        "degraded": bool(stats.degraded),
        "frames_clean": sum(len(r) for r in per_c),
        "frames_chaos": sum(len(r) for r in per),
        "frames_dropped_quarantined": dropped,
        "corrupted_streams": sorted(corrupted),
        "dispatch_breakdown_chaos": dict(d.counts),
        "backoff_s": snap.get("resilience.backoff_seconds",
                              {"count": 0}),
        "t_chaos_s": round(t_chaos, 4),
        "healthy_bit_identical": True,
        "checkpoint_bit_identical": True,
        "zero_crashes": True,
    }


def serving_stats(n_sessions=12, n_lanes=8, frames_per_session=3,
                  n_bytes=12, snr_db=30.0, chunk_len=4096,
                  frame_len=1024, k=8, seed=17):
    """Chaos SLO run of the continuous-batching server (ISSUE 13):
    ``n_sessions`` clients (misbehaving ones included: a NaN-slab
    poisoner, a flood, a stall, an oversized-slab violator) served
    over ``n_lanes`` device lanes under a deterministic fake clock —
    three passes, all gated:

    1. **budget pass** (all-healthy): dispatches ≤ 2 per chunk-step
       independent of session count, pinned under
       ``dispatch.no_recompile`` across admission/close churn;
       sustained aggregate samples/s measured here.
    2. **SLO pass** (misbehaving clients, no chaos): the stall
       session is DEADLINE-SHED (counted, attributed), session 0 is
       EVICTED mid-stream and restored from its checkpoint into a
       fresh lane (bit-identical resumption — the acceptance round
       trip), the NaN session quarantines without garbage, and every
       healthy session's frames are bit-identical to a lone
       single-stream receiver.
    3. **chaos pass**: the same load under injected transient+fatal+
       hang+delay dispatch faults — ZERO crashes, healthy sessions
       still bit-identical, every shed/evict/restore accounted
       exactly in the telemetry counters.

    p50/p99 chunk latency (the SLO numbers) come off the server's own
    registry (``serve.chunk_seconds`` + the per-dispatch site
    histograms). Returns a flat dict (metric: ``sps_serving``)."""
    import contextlib

    from ziria_tpu.backend import framebatch
    from ziria_tpu.phy.wifi import rx as _rx
    from ziria_tpu.runtime import serve
    from ziria_tpu.utils import dispatch, faults
    from ziria_tpu.utils.dispatch import count_dispatches

    misbehave = {1: "nan", 2: "flood", 3: "stall", 4: "oversize"}
    clients = serve.synth_load(
        n_sessions, frames_per_session, n_bytes=n_bytes,
        snr_db=snr_db, seed=seed, tail=frame_len,
        misbehave=misbehave)
    geo = dict(chunk_len=chunk_len, frame_len=frame_len,
               max_frames_per_chunk=k, check_fcs=True)
    oracle = {}
    for c in clients:
        oracle[c.sid], _ = framebatch.receive_stream(c.stream, **geo)

    def same(a, b):
        return (a.start == b.start and a.result.ok == b.result.ok
                and a.result.rate_mbps == b.result.rate_mbps
                and a.result.length_bytes == b.result.length_bytes
                and np.array_equal(a.result.psdu_bits,
                                   b.result.psdu_bits)
                and a.result.crc_ok == b.result.crc_ok)

    stall_slo = 8.0
    evict_sid = clients[0].sid

    def drive(cs, specs=None, chaos_seed=seed, stall=True,
              evict=True, watchdog=None):
        # the watchdog is only armed for the chaos pass (its hang
        # spec needs cutting): on a cold CPU cache a first-contact
        # XLA compile legitimately exceeds any hang-scale timeout,
        # and the earlier passes warm the caches
        cfg = serve.ServeConfig(
            n_lanes=n_lanes, queue_cap=n_sessions, sanitize=True,
            default_slo_s=None, watchdog_s=watchdog, **geo)
        clock = [0.0]
        srv = serve.ServeRuntime(cfg, clock=lambda: clock[0])
        frames = {c.sid: [] for c in cs}

        def collect(pairs):
            for sid, f in pairs:
                frames[sid].append(f)

        restored = not evict
        closed = set()
        todo = {c.sid: list(c.schedule) for c in cs}
        pending = {c.sid: c for c in cs}
        t0 = time.perf_counter()
        with contextlib.ExitStack() as stack:
            plan = stack.enter_context(
                faults.inject(*specs, seed=chaos_seed)) \
                if specs else None
            stack.enter_context(srv)
            for tick in range(400):
                for sid in list(pending):
                    c = pending[sid]
                    slo = stall_slo if (stall and c.mode == "stall") \
                        else None
                    r = srv.connect(sid, slo_s=slo)
                    if r.admitted or r.queued:
                        del pending[sid]
                for c in cs:
                    if c.sid in pending or c.sid in closed:
                        continue
                    q = todo[c.sid]
                    while q and q[0][0] <= tick:
                        r_ = srv.submit(c.sid, q[0][1])
                        if r_.accepted or not r_.retry_after_s:
                            q.pop(0)
                        else:
                            break
                collect(srv.step())
                if evict and not restored and tick >= 2 \
                        and evict_sid not in pending:
                    blob, ems, staged = srv.evict(evict_sid)
                    collect(ems)
                    r = srv.connect(evict_sid, checkpoint=blob)
                    assert r.admitted or r.queued, r
                    for s_ in staged:
                        srv.submit(evict_sid, s_)
                    restored = True
                for c in cs:
                    if (c.sid not in pending and c.sid not in closed
                            and not todo[c.sid] and c.mode != "stall"
                            and (c.sid != evict_sid or restored)):
                        if srv.is_active(c.sid):
                            collect(srv.close(c.sid))
                            closed.add(c.sid)
                        elif c.sid in srv._gone:
                            closed.add(c.sid)  # shed — accounted
                clock[0] += 1.0
                if (not pending and not any(todo.values())
                        and all(c.sid in closed or c.mode == "stall"
                                for c in cs)
                        and (not stall or clock[0] > stall_slo + 2)):
                    break
            collect(srv.drain())
        return srv, frames, time.perf_counter() - t0, plan

    def gate(frames, chaos=False):
        for c in clients:
            got, want = frames[c.sid], oracle[c.sid]
            if c.mode in ("nan", "stall"):
                # poisoned/shed sessions: surviving frames match the
                # clean run at their start — dropped, never garbage
                by_start = {f.start: f for f in want}
                for f in got:
                    assert f.start in by_start and same(
                        f, by_start[f.start]), \
                        f"{c.sid} emitted garbage ({c.mode})"
            else:
                assert len(got) == len(want) and all(
                    same(a, b) for a, b in zip(got, want)), \
                    f"healthy session {c.sid} diverged" \
                    f"{' under chaos' if chaos else ''}"

    # -- pass 1: SLO run (misbehaving clients, shed + evict/restore).
    # Runs first: it also pays the two fleet compiles, so the budget
    # pass below genuinely pins zero cache growth
    srv_s, frames_s, _t_slo, _ = drive(clients)
    st_s = srv_s.stats()
    gate(frames_s)
    shed_sids = {s for s, _r, _t in st_s.shed_log}
    assert clients[3].sid in shed_sids, "stall session was not shed"
    assert st_s.evicted == 1 and st_s.restored == 1
    assert st_s.rejected_slabs >= 1, "oversized slab not rejected"
    assert st_s.admitted == st_s.closed + st_s.evicted + len(
        [1 for _s, r, _t in st_s.shed_log if r == "deadline"]), \
        "session accounting does not balance"

    # -- pass 2: all-healthy dispatch-budget pin ------------------------
    # the raw arrival schedules (no misbehavior rewrite), same sids,
    # same streams: admission/close churn with every lane healthy,
    # and the caches warmed by pass 1 — zero growth is the pin
    healthy = serve.synth_load(
        n_sessions, frames_per_session, n_bytes=n_bytes,
        snr_db=snr_db, seed=seed, tail=frame_len)
    total_samples = sum(int(c.stream.shape[0]) for c in clients)
    with dispatch.no_recompile(_rx._jit_stream_chunk_multi,
                               _rx._jit_stream_decode_multi):
        with count_dispatches() as d_b:
            srv_b, frames_b, t_budget, _ = drive(
                healthy, stall=False, evict=False)
    st_b = srv_b.stats()
    assert d_b.total <= 2 * st_b.chunk_steps, \
        (dict(d_b.counts), st_b.chunk_steps)
    for c in healthy:
        got, want = frames_b[c.sid], oracle[c.sid]
        assert len(got) == len(want) and all(
            same(a, b) for a, b in zip(got, want)), \
            f"budget pass: session {c.sid} diverged"

    # -- pass 3: chaos --------------------------------------------------
    specs = (
        faults.FaultSpec("rx.stream_chunk_multi", "transient",
                         every=5),
        faults.FaultSpec("rx.stream_decode_multi", "transient",
                         every=4),
        faults.FaultSpec("rx.stream_chunk_multi", "delay",
                         calls=(3,), delay_s=0.02),
        faults.FaultSpec("rx.stream_chunk_multi", "hang",
                         calls=(6,), delay_s=10.0),
        faults.FaultSpec("rx.stream_decode_multi", "fatal",
                         calls=(2,), count=1),
    )
    srv_c, frames_c, _t_chaos, plan = drive(clients, specs=specs,
                                            watchdog=2.0)
    st_c = srv_c.stats()
    gate(frames_c, chaos=True)       # zero crashes = reaching here
    assert plan.total_fired > 0
    fired_by_kind = {}
    for _s, kind, _i in plan.fired:
        fired_by_kind[kind] = fired_by_kind.get(kind, 0) + 1
    snap = srv_c.registry.snapshot()
    lat = srv_c.registry.find("serve.chunk_seconds")

    return {
        "sessions": n_sessions, "lanes": n_lanes,
        "frames_per_session": frames_per_session,
        "frame_bytes": n_bytes, "snr_db": snr_db,
        "chunk_len": chunk_len, "frame_len": frame_len,
        "stream_samples_total": total_samples,
        "chunk_steps_budget": st_b.chunk_steps,
        "dispatches_budget": d_b.total,
        "dispatches_per_chunk_step": round(
            d_b.total / max(st_b.chunk_steps, 1), 3),
        "sps_serving": round(total_samples / t_budget, 1),
        "t_serve_s": round(t_budget, 4),
        "chunk_latency_ms": lat.summary(scale=1e3, ndigits=4)
        if lat is not None else {"count": 0},
        "p99_chunk_ms": (lat.summary(scale=1e3, ndigits=4)
                         .get("p99") if lat is not None else None),
        "latency_ms_sites": _latency_block(srv_c.registry),
        "admitted": st_c.admitted, "closed": st_c.closed,
        "shed": st_s.shed, "evicted": st_s.evicted,
        "restored": st_s.restored,
        "rejected_slabs": st_s.rejected_slabs,
        "shed_log": [[s, r, t] for s, r, t in st_s.shed_log],
        "frames_served": st_c.frames,
        "faults_injected": plan.total_fired,
        "faults_by_kind": fired_by_kind,
        "retries": snap.get("resilience.retries", 0),
        "recovered": snap.get("resilience.recovered", 0),
        "degraded": bool(srv_c._rx.stats.degraded),
        # from the registry, not the recycled lane health (a closed
        # session's lane resets; the counter is the durable record)
        "quarantines": snap.get("resilience.quarantines", 0),
        "healthy_bit_identical": True,
        "evict_restore_bit_identical": True,
        "zero_crashes": True,
    }


def viterbi_breakdown(B=128, n_bytes=1000, rate_mbps=54, k1=4, k2=12):
    """ACS-only vs traceback-only vs front-end-only vs full decode at
    the bench shape — the answer to bench.py's open question ("the
    decode is dependency-chain-bound, but WHERE?"): the decompose
    stage bounds front end vs Viterbi; this splits the Viterbi into
    its two Pallas kernels. Each piece is timed with the same
    marginal-K device-loop method as the headline (runtime-zero data
    feedback so the body cannot be hoisted), so the four numbers are
    directly comparable. Returns a flat dict."""
    import jax
    import jax.numpy as jnp

    from ziria_tpu.ops import viterbi_pallas as vp
    from ziria_tpu.phy.wifi import rx, tx
    from ziria_tpu.phy.wifi.params import RATES, n_symbols

    rate = RATES[rate_mbps]
    n_sym = n_symbols(n_bytes, rate)
    rng = np.random.default_rng(19)
    psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
    frame = np.asarray(tx.encode_frame(psdu, rate_mbps))
    frames = jnp.asarray(np.broadcast_to(
        frame, (B,) + frame.shape).copy())
    interpret = jax.default_backend() != "tpu"
    n_bits = n_sym * rate.n_dbps

    def marginal(loop, *args):
        t1 = _timed(loop, *args, jnp.int32(k1))
        t2 = _timed(loop, *args, jnp.int32(k2))
        return max((t2 - t1) / (k2 - k1), 1e-9)

    @jax.jit
    def front_k(f, k):
        def body(_i, carry):
            s, acc = carry
            dep = jax.vmap(
                lambda x: rx._decode_front(x, rate, n_sym))(f + s)
            return dep[0, 0, 0] * 1e-30, acc + dep.sum() * 1e-30
        return jax.lax.fori_loop(
            0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

    dep0 = jax.jit(jax.vmap(
        lambda x: rx._decode_front(x, rate, n_sym)))(frames)
    # the ACS kernel's real input: lane tiles at the UNROLL multiple
    tiles, _b = vp._to_tiles(jnp.asarray(dep0))
    T = tiles.shape[1]
    Tp = -(-T // vp.UNROLL) * vp.UNROLL
    tiles = jnp.pad(tiles, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))

    @jax.jit
    def acs_k(x, k):
        def body(_i, carry):
            s, acc = carry
            _dec, metrics = vp._acs_tiles(x + s, interpret)
            return metrics[0, 0, 0] * 1e-30, acc + metrics.sum() * 1e-30
        return jax.lax.fori_loop(
            0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

    dec0, met0 = jax.jit(
        lambda x: vp._acs_tiles(x, interpret))(tiles)

    @jax.jit
    def tb_k(d, m, k):
        def body(_i, carry):
            s, acc = carry
            bits = vp._traceback_tiles(d, m + s, interpret)
            f = bits[0, 0, 0, 0].astype(jnp.float32)
            return f * 1e-30, acc + f * 1e-30
        return jax.lax.fori_loop(
            0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

    @jax.jit
    def full_k(f, k):
        def body(_i, carry):
            s, acc = carry
            bits = rx.decode_data_batch(
                f + s, rate, n_sym, 8 * n_bytes)[0]
            s2 = bits[0, 0].astype(jnp.float32) * 1e-30
            return s2, acc + bits.sum() * 1e-30
        return jax.lax.fori_loop(
            0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

    t_front = marginal(front_k, frames)
    t_acs = marginal(acs_k, tiles)
    t_tb = marginal(tb_k, dec0, met0)
    t_full = marginal(full_k, frames)
    return {
        "batch": B, "frame_bytes": n_bytes, "rate_mbps": rate_mbps,
        "frame_len": int(frame.shape[0]), "trellis_steps": int(n_bits),
        "t_front_s": round(t_front, 6),
        "t_acs_s": round(t_acs, 6),
        "t_traceback_s": round(t_tb, 6),
        "t_full_s": round(t_full, 6),
        "front_frac": round(t_front / t_full, 3),
        "acs_frac": round(t_acs / t_full, 3),
        "traceback_frac": round(t_tb / t_full, 3),
    }


# the decode-core lever matrix viterbi_kernel_stats measures: kwargs
# for rx.decode_data_batch per lever (radix-4 ACS, quantized metrics,
# the fused in-kernel front end, and the stack)
VITERBI_LEVERS = (
    ("base", {}),
    ("radix4", {"viterbi_radix": 4}),
    ("int16", {"viterbi_metric": "int16"}),
    ("int16_radix4", {"viterbi_metric": "int16", "viterbi_radix": 4}),
    ("int8_lut", {"viterbi_metric": "int8"}),
    ("fused_demap", {"fused_demap": True}),
    ("fused_demap_radix4", {"fused_demap": True, "viterbi_radix": 4}),
)


def viterbi_kernel_stats(B=128, n_bytes=1000, rate_mbps=54,
                         k1=4, k2=12, noise_sigma=0.35,
                         levers=VITERBI_LEVERS):
    """Per-lever decode-core stats (ISSUE 6): samples/s + marginal
    step time for each lever of the rebuilt ACS (radix-4, int16,
    int8+LUT, fused demap front end, and the radix-4+fused stack),
    with dispatch counts and per-site wall times from utils/dispatch
    and the identity gates the levers promise:

    - every lever decodes the clean corpus to the TX bits (the bench
      correctness gate, green for int8 too);
    - on NOISY inputs, radix-4 / fused levers are gated BIT-IDENTICAL
      against the float32 radix-2 oracle's output (their contract),
      int16 against its own radix-2 twin, and int8 — whose contract is
      statistical — against the f32 oracle's BER (delta recorded).

    Returns a flat dict (bench.py's viterbi_kernel_stats stage stores
    it verbatim and annotates roofline percentages per lever)."""
    import jax
    import jax.numpy as jnp

    from ziria_tpu.phy.wifi import rx, tx
    from ziria_tpu.phy.wifi.params import RATES, n_symbols
    from ziria_tpu.utils.dispatch import count_dispatches, timed

    rate = RATES[rate_mbps]
    n_sym = n_symbols(n_bytes, rate)
    n_psdu_bits = 8 * n_bytes
    rng = np.random.default_rng(21)
    psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
    frame = np.asarray(tx.encode_frame(psdu, rate_mbps))
    from ziria_tpu.utils.bits import bytes_to_bits
    want = np.asarray(bytes_to_bits(psdu))
    frames = jnp.asarray(np.broadcast_to(
        frame, (B,) + frame.shape).copy())
    # a small noisy batch at operating SNR for the oracle gates (the
    # clean batch decodes perfectly under EVERY lever, which gates
    # correctness but cannot distinguish bit-identity from luck)
    Bn = min(B, 8)
    noisy = (np.broadcast_to(frame, (Bn,) + frame.shape)
             + rng.normal(0, noise_sigma, (Bn,) + frame.shape)
             ).astype(np.float32)
    noisy = jnp.asarray(noisy)

    def decode(f, **kw):
        return rx.decode_data_batch(f, rate, n_sym, n_psdu_bits,
                                    **kw)[0]

    out = {"batch": B, "frame_bytes": n_bytes, "rate_mbps": rate_mbps,
           "frame_len": int(frame.shape[0]),
           "noise_sigma": noise_sigma}
    noisy_bits = {}
    with count_dispatches() as d:
        for name, kw in levers:
            with timed(f"viterbi.{name}"):
                got = np.asarray(jax.jit(
                    lambda f, _kw=kw: decode(f, **_kw))(frames))
            assert np.array_equal(got[0], want) \
                and np.array_equal(got[-1], want), \
                f"{name} failed the clean correctness gate"
            noisy_bits[name] = np.asarray(jax.jit(
                lambda f, _kw=kw: decode(f, **_kw))(noisy))
    out["dispatch_times_ms"] = d.times_ms()
    out["dispatches"] = d.total

    # identity gates on the noisy corpus. radix4 is PROVABLY identical
    # to the oracle (same renorm cadence, same expression trees), so
    # its gate is exact. The fused levers share the expression trees
    # but renorm at the symbol-block cadence instead of every UNROLL
    # steps — f32 renorm rounding can in principle flip a sub-epsilon
    # near-tie at operating noise, so their gate records the mismatch
    # fraction and asserts it stays within a vanishing budget instead
    # of erroring the whole stage on one flipped razor-edge bit.
    base = noisy_bits["base"]
    for name in ("radix4",):
        if name not in noisy_bits:
            continue                   # lever not in this run's matrix
        same = bool(np.array_equal(noisy_bits[name], base))
        out[f"{name}_bit_identical"] = same
        assert same, f"{name} diverged from the float32 radix-2 oracle"
    for name in ("fused_demap", "fused_demap_radix4"):
        if name not in noisy_bits:
            continue
        frac = float((noisy_bits[name] != base).mean())
        out[f"{name}_bit_identical"] = frac == 0.0
        out[f"{name}_mismatch_frac"] = round(frac, 8)
        assert frac <= 1e-3, \
            f"{name} diverged from the unfused front end ({frac:.2e})"
    if "int16_radix4" in noisy_bits and "int16" in noisy_bits:
        same16 = bool(np.array_equal(noisy_bits["int16_radix4"],
                                     noisy_bits["int16"]))
        out["int16_radix4_bit_identical"] = same16
        assert same16, "int16 radix-4 diverged from its radix-2 twin"
    ber_f32 = float((base != want[None]).mean())
    out["ber_f32"] = round(ber_f32, 6)
    if "int8_lut" in noisy_bits:
        ber_i8 = float((noisy_bits["int8_lut"] != want[None]).mean())
        out["ber_int8"] = round(ber_i8, 6)
        out["ber_int8_delta"] = round(ber_i8 - ber_f32, 6)
        # the int8 contract is its BER ENVELOPE (same bound as
        # tests/test_viterbi_radix4.test_int8_ber_guard): a saturation
        # or LUT regression must fail the stage, not report green
        assert abs(ber_i8 - ber_f32) < 0.05 * max(ber_f32, 1e-9) + 4e-3, \
            f"int8 BER {ber_i8:.4f} outside envelope vs f32 {ber_f32:.4f}"
        out["int8_ber_gate"] = True

    # per-lever marginal step time (the headline's round-trip-cancelling
    # K-spread method)
    for name, kw in levers:
        @jax.jit
        def loop(x, k, _kw=kw):
            def body(_i, carry):
                s, acc = carry
                bits = decode(x + s, **_kw)
                s2 = bits[0, 0].astype(jnp.float32) * 1e-30
                return s2, acc + bits.sum() * 1e-30
            return jax.lax.fori_loop(
                0, k, body, (jnp.float32(0), jnp.float32(0)))[1]

        t_1 = _timed(loop, frames, jnp.int32(k1))
        t_2 = _timed(loop, frames, jnp.int32(k2))
        t_step = max((t_2 - t_1) / (k2 - k1), 1e-9)
        out[f"t_step_{name}_s"] = round(t_step, 6)
        out[f"sps_{name}"] = round(B * frame.shape[0] / t_step, 1)
    for name, _kw in levers[1:]:
        out[f"{name}_over_base"] = round(
            out[f"t_step_{name}_s"] / out["t_step_base_s"], 3)
    return out


def fused_mixed_stats(B=64, n_bytes=100, noise_sigma=0.3, k1=2, k2=6,
                      frame_len=1024, stream_k=8):
    """The rate-SWITCHED fused-demap lever (ISSUE 20) — the mixed
    `lax.switch` decode every streaming/fleet surface runs, with the
    8-rate stacked constant bank row-selected in-kernel:

    - identity gate: `rx.decode_data_mixed(fused_demap=True)` vs the
      unfused mixed oracle on a noisy all-8-rates batch, per-lane
      real-prefix mismatch fraction recorded and asserted vanishing
      (the radix-4 stack too — same budget as the known-rate fused
      levers in `viterbi_kernel_stats`);
    - marginal step time (K-spread) for the unfused and fused mixed
      decode -> `sps_fused_mixed` / `sps_unfused_mixed` (bench.py's
      fused_mixed stage headline);
    - the observatory's before/after on `rx._jit_stream_decode_multi` at
      the suite-shared geometry: compiled `bytes_accessed` unfused vs
      fused, asserted STRICTLY lower fused (the roofline claim — the
      LLR round-trip leaves the program, the constant bank it buys is
      smaller).

    Returns a flat dict (bench.py stores it verbatim)."""
    import jax
    import jax.numpy as jnp

    from ziria_tpu.phy.wifi import rx, tx
    from ziria_tpu.phy.wifi.params import (RATE_MBPS_ORDER, RATES,
                                           n_symbols)
    from ziria_tpu.utils import programs

    rng = np.random.default_rng(33)
    mbps = (list(RATE_MBPS_ORDER) * (-(-B // 8)))[:B]
    n_sym_b = rx._sym_bucket(max(n_symbols(n_bytes, RATES[m])
                                 for m in mbps))
    need = rx.FRAME_DATA_START + 80 * n_sym_b
    frames = np.zeros((B, need, 2), np.float32)
    for i, m in enumerate(mbps):
        psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
        s = np.asarray(tx.encode_frame(psdu, m))
        ln = min(len(s), need)
        frames[i, :ln] = s[:ln]
    frames = jnp.asarray(
        frames + rng.normal(0, noise_sigma, frames.shape)
        .astype(np.float32))
    ridx = jnp.asarray([rx.RATE_INDEX[m] for m in mbps], jnp.int32)
    nb_host = np.asarray([n_symbols(n_bytes, RATES[m])
                          * RATES[m].n_dbps for m in mbps], np.int32)
    nbits = jnp.asarray(nb_host)

    def dec(fused, **kw):
        return np.asarray(jax.jit(lambda f: rx.decode_data_mixed(
            f, ridx, nbits, n_sym_b, fused_demap=fused, **kw))(frames))

    base = dec(False)
    # compare the real prefix per lane: past nbits both paths decode
    # zero-LLR erasures whose tie-broken bits carry no contract
    mask = np.arange(base.shape[1])[None, :] < nb_host[:, None]
    out = {"batch": B, "frame_bytes": n_bytes,
           "n_sym_bucket": n_sym_b, "noise_sigma": noise_sigma,
           "rates": sorted(set(mbps))}
    for name, kw in (("fused_mixed", {}),
                     ("fused_mixed_radix4", {"viterbi_radix": 4})):
        got = dec(True, **kw)
        frac = float((got != base)[mask].mean())
        out[f"{name}_bit_identical"] = frac == 0.0
        out[f"{name}_mismatch_frac"] = round(frac, 8)
        assert frac <= 1e-3, \
            f"{name} diverged from the unfused mixed decode ({frac:.2e})"

    # marginal mixed-decode step time, fused vs unfused (the
    # K-spread method of viterbi_kernel_stats)
    for name, fused in (("unfused_mixed", False),
                        ("fused_mixed", True)):
        @jax.jit
        def loop(x, kk, _f=fused):
            def body(_i, carry):
                s, acc = carry
                bits = rx.decode_data_mixed(x + s, ridx, nbits,
                                            n_sym_b, fused_demap=_f)
                s2 = bits[0, 0].astype(jnp.float32) * 1e-30
                return s2, acc + bits.sum() * 1e-30
            return jax.lax.fori_loop(
                0, kk, body, (jnp.float32(0), jnp.float32(0)))[1]

        t_1 = _timed(loop, frames, jnp.int32(k1))
        t_2 = _timed(loop, frames, jnp.int32(k2))
        t_step = max((t_2 - t_1) / (k2 - k1), 1e-9)
        out[f"t_step_{name}_s"] = round(t_step, 6)
        out[f"sps_{name}"] = round(B * need / t_step, 1)
    out["fused_over_unfused"] = round(
        out["t_step_fused_mixed_s"] / out["t_step_unfused_mixed_s"], 3)

    # before/after compiled bytes on THE streaming decode program at
    # the suite-shared geometry (tests/test_programs.py's pinned
    # site): the acceptance claim is strictly-lower fused
    sym_b = rx._sym_bucket(
        max(1, (frame_len - rx.FRAME_DATA_START) // 80))
    need_b = rx.FRAME_DATA_START + 80 * sym_b
    S = jax.ShapeDtypeStruct
    segs = S((1, stream_k, need_b, 2), np.float32)
    row = S((1, stream_k), np.int32)
    for name, fused in (("unfused", False), ("fused", True)):
        c = programs.cost_of(
            rx._jit_stream_decode_multi(sym_b, None, None, 2,
                                        fused_demap=fused),
            segs, row, row, row, row)
        out[f"stream_decode_bytes_{name}"] = c.get("bytes_accessed")
        out[f"stream_decode_flops_{name}"] = c.get("flops")
    b_un = out["stream_decode_bytes_unfused"]
    b_fu = out["stream_decode_bytes_fused"]
    out["stream_decode_bytes_delta"] = round(b_un - b_fu, 1)
    out["stream_decode_bytes_ratio"] = round(b_fu / b_un, 4)
    assert b_fu < b_un, \
        (f"fused stream decode bytes_accessed {b_fu} not below "
         f"unfused {b_un}")
    return out


def _multi_stream_mesh_main(argv):
    """``rx_dispatch_bench.py --multi-stream-mesh N [S]``: the mesh
    point of `multi_stream_stats` alone, in a process whose caller
    exported ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (virtual devices must exist BEFORE jax initializes — the
    `dryrun_multichip` mechanism). bench.py's multi_stream stage
    spawns this when its own process sees a single device, so the
    CPU smoke child still records aggregate samples/s vs mesh size.
    Prints ONE JSON object with `sps_by_devices`/`mesh_scaling`."""
    import jax

    n = int(argv[0]) if argv else 4
    n_streams = int(argv[1]) if len(argv) > 1 else n
    if os.environ.get("ZIRIA_TOOL_ALLOW_CPU") == "1":
        jax.config.update("jax_platforms", "cpu")
    from ziria_tpu.utils import compile_cache
    compile_cache.place()   # the probe's compiles are bench compiles
    if len(jax.devices()) < n:
        print(json.dumps({"error": f"{len(jax.devices())} device(s) "
                          f"visible, need {n} (export XLA_FLAGS="
                          f"--xla_force_host_platform_device_count="
                          f"{n})"}))
        return 1
    out = multi_stream_stats(n_streams=n_streams, frames_per_stream=2,
                             mesh_sizes=[n])
    print(json.dumps({k: out[k] for k in
                      ("streams", "sps_by_devices", "mesh_scaling",
                       "mesh_devices_max", "bit_identical",
                       "dispatches_per_chunk_step") if k in out}))
    return 0


def main():
    import jax

    if sys.argv[1:2] == ["--multi-stream-mesh"]:
        return _multi_stream_mesh_main(sys.argv[2:])
    smoke = os.environ.get("ZIRIA_TOOL_ALLOW_CPU") == "1"
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not smoke:
        print(json.dumps({"error": "no TPU visible"}))
        return 1

    out = {"platform": dev.platform,
           "device_kind": getattr(dev, "device_kind", "?")}
    if smoke:     # shrunk sizes: prove the path, not the number
        out["quantized"] = quantized_sweep(B=8, n_bytes=100, k1=2, k2=4)
        out["viterbi_breakdown"] = viterbi_breakdown(
            B=8, n_bytes=100, k1=2, k2=4)
        # fused levers dropped on CPU like bench.py's smoke stage: the
        # rate-54 fused kernel is a 216-step unrolled interpret-mode
        # program (minutes on CPU, milliseconds of Mosaic on chip)
        out["viterbi_kernel_stats"] = viterbi_kernel_stats(
            B=8, n_bytes=100, k1=2, k2=4, levers=VITERBI_LEVERS[:5])
        out["fused_mixed"] = fused_mixed_stats(
            B=8, n_bytes=24, k1=2, k2=4)
        out["mixed_dispatch"] = mixed_dispatch_stats(n_bytes=60)
        out["batched_acquire"] = batched_acquire_stats(n_bytes=60)
        out["link_loopback"] = link_loopback_stats(n_bytes=24)
        out["fused_link"] = fused_link_stats(n_bytes=24)
        out["ber_sweep"] = ber_sweep_stats(
            n_frames=8, n_bytes=24, rates=(6, 54), snrs=(3.0, 8.0))
        out["channel_sweep"] = channel_sweep_stats(
            n_frames=4, n_bytes=24, rates=(6, 54),
            profiles=("flat", "severe", "sco", "bursty", "hostile"))
        out["streaming_rx"] = streaming_stats(n_frames=8)
        out["multi_stream"] = multi_stream_stats(
            n_streams=4, frames_per_stream=2)
        out["resilience"] = resilience_stats(
            n_streams=4, frames_per_stream=2)
        out["serving"] = serving_stats(
            n_sessions=6, n_lanes=4, frames_per_session=2)
    else:
        out["quantized"] = quantized_sweep()
        out["viterbi_breakdown"] = viterbi_breakdown()
        out["viterbi_kernel_stats"] = viterbi_kernel_stats()
        out["fused_mixed"] = fused_mixed_stats()
        out["mixed_dispatch"] = mixed_dispatch_stats()
        out["mixed_dispatch_i16"] = mixed_dispatch_stats(
            viterbi_metric="int16")
        out["batched_acquire"] = batched_acquire_stats()
        out["link_loopback"] = link_loopback_stats()
        out["fused_link"] = fused_link_stats()
        out["ber_sweep"] = ber_sweep_stats()
        out["channel_sweep"] = channel_sweep_stats()
        out["streaming_rx"] = streaming_stats()
        out["multi_stream"] = multi_stream_stats()
        out["resilience"] = resilience_stats()
        out["serving"] = serving_stats()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
