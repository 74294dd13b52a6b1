"""Channel-hostile robustness (ISSUE 15): the seeded physical-layer
profile subsystem — named multipath/SCO/Doppler/burst parameter sets
(phy/profiles) applied as vmapped per-lane taps through the impair
graphs — and the RX front-end hardening it exercises (bounded-|H|
null-subcarrier guard, pilot SCO phase-ramp tracking).

Contracts pinned here:

- `channel.multipath` vs a host numpy complex-FIR oracle (the helper
  had zero callers and zero tests before this PR);
- the profiled graph at NEUTRAL parameters is BIT-IDENTICAL to the
  unprofiled `impair_graph` (one-hot taps, zero-fraction resample,
  zero phase, zero burst amplitude are exact identities and the AWGN
  consumes the same lane key) — the flat-lane contract of mixed
  profiled batches;
- ``profile="flat"`` resolves to the UNPROFILED code path by
  construction: bit-identical streams/captures and ZERO new compiled
  programs, pinned across the loopback link (fused + staged), the
  streaming receiver, and the S=8 fleet at the suite-shared
  4096/1024/K=8 geometry under ``dispatch.no_recompile``;
- `impair_stream`'s noise draws follow the SAME per-lane fold-in key
  schedule as the batched graphs (the stream/batch seeding symmetry
  satellite);
- `sweep_ber`'s rates x SNR x PROFILE waterfall stays ONE `lax.scan`
  dispatch, its flat column is integer-identical to the unprofiled
  sweep, and the hostile profiles hold their BER envelopes at high
  SNR;
- the hostile-profile loopback agrees lane for lane across the
  staged / per-frame (and, slow, fused) modes;
- the bounded-|H| guard zeroes null bins exactly and is value-inert
  on healthy channels; `pilot_phase_correct(sco_track=True)` removes
  a synthetic phase ramp and measurably improves a strong-SCO decode.

Loopback geometry mirrors test_link_fused's exactly (same LENS/MBPS/
CFO/DELAY/SNRS, same B_SWEEP/NB_SWEEP sweep shape) so the unprofiled
programs are one compile class with that suite.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ziria_tpu.backend import framebatch
from ziria_tpu.phy import channel, link
from ziria_tpu.phy import profiles as chanprof
from ziria_tpu.phy.wifi import rx, tx
from ziria_tpu.phy.wifi.params import RATES, n_symbols
from ziria_tpu.utils import dispatch, faults
from ziria_tpu.utils.bits import np_bytes_to_bits

# test_link_fused's exact loopback geometry: shared compile class
LENS = (16, 10, 16, 5, 16, 12, 9, 16)
MBPS = tuple(sorted(RATES))
CFO = tuple((-1) ** k * 1e-4 * (k + 1) for k in range(8))
DELAY = tuple(20 + 17 * k for k in range(8))
SNRS = (25.0, 30.0, -25.0, 28.0, 25.0, 30.0, 27.0, 26.0)

B_SWEEP, NB_SWEEP = 8, 24                  # test_link_fused geometry
SWEEP_RATES = (6, 54)

# the suite-shared streaming geometry (test_rx_stream / multistream)
CHUNK, FRAME_LEN, K = 4096, 1024, 8


# ------------------------------------------------------- registry/oracle


def test_multipath_matches_numpy_fir_oracle():
    # satellite 1: the orphaned helper, pinned against a float64
    # numpy complex FIR before anything builds on it
    rng = np.random.default_rng(3)
    x = rng.normal(size=(256, 2)).astype(np.float32)
    taps = rng.normal(size=(7, 2)).astype(np.float32)
    got = np.asarray(channel.multipath(x, taps))
    xc = x[:, 0].astype(np.float64) + 1j * x[:, 1].astype(np.float64)
    tc = taps[:, 0].astype(np.float64) + 1j * taps[:, 1] \
        .astype(np.float64)
    ref = np.convolve(xc, tc)[:256]
    np.testing.assert_allclose(got[:, 0], ref.real, atol=2e-4)
    np.testing.assert_allclose(got[:, 1], ref.imag, atol=2e-4)
    # one-hot taps are an exact identity (the flat-lane hinge)
    hot = np.zeros((5, 2), np.float32)
    hot[0, 0] = 1.0
    assert np.array_equal(np.asarray(channel.multipath(x, hot)), x)
    # and the host twin agrees with the device graph
    prof = chanprof.ChannelProfile(
        "t", taps=tuple((float(a), float(b)) for a, b in taps))
    np.testing.assert_allclose(chanprof.np_apply_taps(x, prof), got,
                               atol=2e-4)


def test_profile_registry_and_grammar():
    for name, prof in chanprof.CHANNEL_PROFILES.items():
        e = sum(r * r + i * i for r, i in prof.taps)
        assert abs(e - 1.0) < 1e-6, f"{name} taps not unit energy"
        assert len(prof.taps) <= 16, \
            f"{name} delay spread exceeds the cyclic prefix"
        assert prof.name == name
    assert chanprof.get_profile("flat").is_flat
    assert not chanprof.get_profile("severe").is_flat
    with pytest.raises(ValueError, match="known:"):
        chanprof.get_profile("nope")
    assert chanprof.parse_profile_spec(" flat , severe ") == \
        ("flat", "severe")
    with pytest.raises(ValueError):
        chanprof.parse_profile_spec("flat,nope")
    # flat resolves to the UNPROFILED path; mixes cycle per lane
    assert chanprof.resolve_profiles("flat", 4) is None
    assert chanprof.resolve_profiles(None, 4, use_env=False) is None
    assert chanprof.resolve_profiles(("mild", "severe"), 4) == \
        ("mild", "severe", "mild", "severe")


def test_env_knob_scoping(monkeypatch):
    psdus = [np.arange(12, dtype=np.uint8)] * 2
    base, _ = link.stream_many(psdus, [6, 24], gaps=[400],
                               snr_db=np.inf, seed=4, add_fcs=True)
    monkeypatch.setenv("ZIRIA_CHANNEL_PROFILE", "severe")
    via_env, _ = link.stream_many(psdus, [6, 24], gaps=[400],
                                  snr_db=np.inf, seed=4, add_fcs=True)
    explicit, _ = link.stream_many(psdus, [6, 24], gaps=[400],
                                   snr_db=np.inf, seed=4,
                                   add_fcs=True,
                                   channel_profile="severe")
    # env default == explicit request; explicit "flat" OVERRIDES the
    # env (the resolve-once precedence rule — a lower layer must not
    # resurrect the env default a surface already consumed)
    assert np.array_equal(via_env, explicit)
    assert not np.array_equal(via_env, base)
    flat, _ = link.stream_many(psdus, [6, 24], gaps=[400],
                               snr_db=np.inf, seed=4, add_fcs=True,
                               channel_profile="flat")
    assert np.array_equal(flat, base)
    monkeypatch.delenv("ZIRIA_CHANNEL_PROFILE")
    assert np.array_equal(
        link.stream_many(psdus, [6, 24], gaps=[400], snr_db=np.inf,
                         seed=4, add_fcs=True)[0], base)


# ------------------------------------------- graph neutral-identity


def test_neutral_profile_graph_bit_identical():
    # the flat-lane contract: the PROFILED graph at neutral
    # parameters reproduces impair_graph BITWISE (every added op is
    # an exact identity; the AWGN consumes the same lane key)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(512, 2)).astype(np.float32)
    key = channel.lane_key(3, 0)
    a = np.asarray(channel.impair_graph(x, 400, 20.0, 1e-3, 30, key))
    arrs = [jnp.asarray(v) for v in chanprof.lane_arrays(("flat",))]
    b = np.asarray(channel.impair_profile_graph(
        x, 400, 20.0, 1e-3, 30, key, *[v[0] for v in arrs]))
    assert np.array_equal(a, b)


def test_mixed_batch_flat_lane_and_per_frame_oracle():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 400, 2)).astype(np.float32)
    xb = jnp.asarray(x)
    plain = np.asarray(channel.impair_many(xb, 400, 20.0, 1e-3, 16,
                                           seed=9, out_len=512))
    mixed = np.asarray(channel.impair_many(
        xb, 400, 20.0, 1e-3, 16, seed=9, out_len=512,
        profile=("flat", "severe")))
    # the flat lane of a MIXED profiled batch: the neutral ops are
    # EXACT identities and the AWGN key is the same (the eager graph
    # is pinned bitwise above), but the profiled batch is a
    # separately-COMPILED program and XLA's FMA contraction may round
    # the shared ops differently — so the cross-program pin is one
    # float32 ulp, while the severe lane genuinely differs
    np.testing.assert_allclose(mixed[0], plain[0], atol=3e-7,
                               rtol=0.0)
    assert not np.allclose(mixed[1], plain[1], atol=1e-3)
    # per-frame oracle == its batched lane, profile included (same
    # ulp rule: single-lane and vmapped programs compile separately)
    one = np.asarray(channel.impair_one(x[1], 20.0, 1e-3, 16, 9, 1,
                                        512, profile="severe"))
    np.testing.assert_allclose(one, mixed[1], atol=3e-7, rtol=0.0)
    # determinism: the same profiled batch replays bitwise
    again = np.asarray(channel.impair_many(
        xb, 400, 20.0, 1e-3, 16, seed=9, out_len=512,
        profile=("flat", "severe")))
    assert np.array_equal(again, mixed)


def test_impair_stream_seeding_symmetry():
    # satellite 2: the stream AWGN follows the SAME per-lane fold-in
    # schedule as the batched graphs — jax.random.normal off
    # lane_key(seed, lane), element-identical at equal geometry
    rng = np.random.default_rng(2)
    x = rng.normal(size=(512, 2)).astype(np.float32)
    got = channel.impair_stream(x, x.shape[0], 20.0, 0.0, seed=7)
    p_sig = float(np.sum(x.astype(np.float64) ** 2) / x.shape[0])
    scale = np.sqrt(p_sig / 10.0 ** 2 / 2.0)
    for lane, out in ((0, got),
                      (3, channel.impair_stream(x, x.shape[0], 20.0,
                                                0.0, seed=7,
                                                lane=3))):
        want = (x + np.asarray(
            jax.random.normal(channel.lane_key(7, lane), x.shape),
            np.float64) * scale).astype(np.float32)
        assert np.array_equal(out, want), f"lane {lane}"
    assert not np.array_equal(
        got, channel.impair_stream(x, x.shape[0], 20.0, 0.0, seed=7,
                                   lane=3))


# -------------------------------------------------- loopback identity


def _loop(profile=None, **kw):
    rng = np.random.default_rng(20260803)
    psdus = [rng.integers(0, 256, n).astype(np.uint8) for n in LENS]
    got = link.loopback_many(psdus, MBPS, snr_db=SNRS, cfo=CFO,
                             delay=DELAY, seed=11, add_fcs=True,
                             check_fcs=True, channel_profile=profile,
                             **kw)
    return psdus, got


def _same_result(a, b) -> bool:
    return (a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)


def test_loopback_flat_identity_zero_new_programs():
    # profile="flat" IS the unprofiled link: bit-identical results
    # AND zero new compiled programs, fused and staged alike
    _p, base_fu = _loop(fused=True)
    _p, base_st = _loop(fused=False)
    with dispatch.no_recompile(link._jit_fused_link,
                               channel._jit_impair_many,
                               rx._jit_decode_data_mixed,
                               rx._jit_acquire_many):
        _p, flat_fu = _loop(profile="flat", fused=True)
        _p, flat_st = _loop(profile="flat", fused=False)
    for a, b in zip(flat_fu, base_fu):
        assert _same_result(a, b)
    for a, b in zip(flat_st, base_st):
        assert _same_result(a, b)


def test_loopback_hostile_staged_equals_per_frame():
    # per-lane MIXED profiles through the staged batch vs the
    # per-frame oracle loop: lane-for-lane identical RxResults (the
    # profiled channel is the same graph with the same fold-in keys
    # either way; the decode programs are the already-compiled ones)
    profs = ("severe", "urban", "flat", "mild", "severe", "urban",
             "mild", "flat")
    psdus, staged = _loop(profile=profs, fused=False)
    _p, perframe = _loop(profile=profs, batched_tx=False)
    assert len(staged) == len(perframe) == len(psdus)
    for a, b in zip(staged, perframe):
        assert _same_result(a, b)
    # the equalizable profiles decode clean at these SNRs (lane 2 is
    # the swamped -25 dB lane, failed in BOTH paths by construction)
    for k in (0, 1, 3, 4, 5, 6, 7):
        assert staged[k].ok and staged[k].crc_ok, k


@pytest.mark.slow
def test_loopback_hostile_fused_equals_staged():
    # the profiled FUSED graph (one dispatch, profile constants baked
    # in) against the staged oracle — heavy compile, tier-2
    profs = ("severe", "urban", "flat", "mild", "severe", "urban",
             "mild", "flat")
    _p, fused = _loop(profile=profs, fused=True)
    _p, staged = _loop(profile=profs, fused=False)
    for a, b in zip(fused, staged):
        assert _same_result(a, b)


# ------------------------------------------------------- sweep profile axis


@pytest.fixture(scope="module")
def sweep_corpus():
    rng = np.random.default_rng(9)
    psdus = rng.integers(0, 256, (B_SWEEP, NB_SWEEP)).astype(np.uint8)
    snrs, seeds = (8.0, 30.0), (7,)
    profiles = ("flat", "severe", "bursty")
    base = link.sweep_ber(psdus, SWEEP_RATES, snrs, seeds)
    with dispatch.count_dispatches() as d_sw:
        errs = link.sweep_ber(psdus, SWEEP_RATES, snrs, seeds,
                              profiles=profiles)
    return psdus, snrs, seeds, profiles, base, errs, d_sw


def test_sweep_profile_axis_one_dispatch(sweep_corpus):
    _p, snrs, seeds, profiles, _b, errs, d_sw = sweep_corpus
    assert errs.shape == (len(SWEEP_RATES), len(profiles), len(snrs),
                          len(seeds))
    assert d_sw.total <= 1, dict(d_sw.counts)
    assert d_sw.counts["link.sweep"] == 1


def test_sweep_flat_column_identical(sweep_corpus):
    # the flat column IS the unprofiled sweep — integer-identical
    _p, _s, _k, profiles, base, errs, _d = sweep_corpus
    assert np.array_equal(errs[:, profiles.index("flat")], base)


def test_sweep_hostile_envelopes(sweep_corpus):
    # bounded error floors at the 30 dB point (the acceptance gate;
    # the bench channel_sweep stage runs the full profile set)
    psdus, _s, seeds, profiles, _b, errs, _d = sweep_corpus
    bits = B_SWEEP * 8 * NB_SWEEP * len(SWEEP_RATES) * len(seeds)
    floor = {p: float(errs[:, i, -1, :].sum()) / bits
             for i, p in enumerate(profiles)}
    assert floor["flat"] == 0.0, floor
    assert floor["severe"] <= 0.15, floor
    assert floor["bursty"] <= 0.30, floor
    # and the waterfall falls: no profile's BER rises with SNR
    for i, p in enumerate(profiles):
        ber = errs[:, i].sum(axis=(0, 2)) / bits
        assert ber[1] <= ber[0] + 2e-3, (p, ber)


@pytest.mark.slow
def test_sweep_profiled_equals_perbatch_loop(sweep_corpus):
    # the degraded twin stays integer-identical under the profile
    # axis: loopback_ber_bits(profile=...) applies the same point
    # graph at the same split keys
    psdus, snrs, seeds, profiles, _b, errs, _d = sweep_corpus
    bits = np.stack([np_bytes_to_bits(p) for p in psdus])
    for pi, pname in enumerate(profiles):
        for si, s in enumerate(snrs):
            for ki, sd in enumerate(seeds):
                for ri, m in enumerate(SWEEP_RATES):
                    got = link.loopback_ber_bits(
                        psdus, m, float(s), int(sd), profile=pname)
                    assert int((got != bits).sum()) == \
                        int(errs[ri, pi, si, ki]), (pname, m, s)


# ----------------------------------------------------- RX hardening


def test_h_guard_nulls_exactly_and_is_inert_when_healthy():
    rng = np.random.default_rng(6)
    data = rng.normal(size=(2, 48, 2)).astype(np.float32)
    pilots = rng.normal(size=(2, 4, 2)).astype(np.float32)
    h = np.ones((64, 2), np.float32)
    # healthy flat channel: everything passes through BITWISE
    d2, p2, g2 = rx.guard_subcarriers(jnp.asarray(data),
                                      jnp.asarray(pilots),
                                      jnp.asarray(h))
    assert np.array_equal(np.asarray(d2), data)
    assert np.array_equal(np.asarray(p2), pilots)
    # null one data bin and one pilot bin: exact-zero erasures there,
    # every other value untouched
    from ziria_tpu.ops import ofdm
    hn = h.copy()
    hn[ofdm.DATA_BINS[5]] = 1e-6
    hn[ofdm.PILOT_BINS[2]] = 0.0
    d3, p3, g3 = rx.guard_subcarriers(jnp.asarray(data),
                                      jnp.asarray(pilots),
                                      jnp.asarray(hn))
    d3, p3, g3 = np.asarray(d3), np.asarray(p3), np.asarray(g3)
    assert np.all(d3[:, 5] == 0.0) and g3[5] == 0.0
    assert np.all(p3[:, 2] == 0.0)
    keep = [i for i in range(48) if i != 5]
    assert np.array_equal(d3[:, keep], data[:, keep])
    assert np.array_equal(p3[:, [0, 1, 3]], pilots[:, [0, 1, 3]])
    assert np.all(g3[keep] > 0.0)


def test_pilot_sco_track_removes_phase_ramp():
    from ziria_tpu.ops import ofdm
    rng = np.random.default_rng(8)
    n_sym = 4
    syms = (rng.integers(0, 2, (n_sym, 48, 2)) * 2 - 1) \
        .astype(np.float32) / np.sqrt(2.0)
    pol = ofdm.PILOT_POLARITY[(np.arange(n_sym) + 1) % 127]
    pilots_re = (ofdm.PILOT_VALS[None, :] * pol[:, None]) \
        .astype(np.float32)
    pilots = np.stack([pilots_re, np.zeros_like(pilots_re)], axis=-1)
    # apply a per-subcarrier phase ramp growing over the symbols (the
    # SCO signature) to data AND pilots
    slope = 0.004 * (1.0 + np.arange(n_sym))            # rad/subcarrier
    def rot(x, k):
        th = slope[:, None] * k[None, :]
        c, s = np.cos(th), np.sin(th)
        return np.stack([x[..., 0] * c - x[..., 1] * s,
                         x[..., 0] * s + x[..., 1] * c], axis=-1) \
            .astype(np.float32)
    data_r = rot(syms, ofdm.DATA_SC.astype(np.float64))
    pilots_r = rot(pilots, ofdm.PILOT_SC.astype(np.float64))
    off = np.asarray(rx.pilot_phase_correct(
        jnp.asarray(data_r), jnp.asarray(pilots_r), 1,
        sco_track=False))
    on = np.asarray(rx.pilot_phase_correct(
        jnp.asarray(data_r), jnp.asarray(pilots_r), 1,
        sco_track=True))
    def worst(x):
        ph = np.abs(np.arctan2(
            (x[..., 0] * syms[..., 1] - x[..., 1] * syms[..., 0]),
            (x[..., 0] * syms[..., 0] + x[..., 1] * syms[..., 1])))
        return float(ph.max())
    # tracking removes the ramp (residual < 10% of the edge phase);
    # without it the band edge keeps ~slope * 26 of error
    assert worst(on) < 0.1 * worst(off)
    assert worst(off) > 0.2


def test_sco_track_improves_strong_sco_decode():
    # end-to-end: a 400 ppm clock offset at 54 Mbps (64-QAM) — the
    # phase ramp at the band edge breaks the untracked decode, the
    # tracked one recovers most of it
    rng = np.random.default_rng(5)
    b, n_bytes, m = 2, 60, 54
    psdus = rng.integers(0, 256, (b, n_bytes)).astype(np.uint8)
    want = np.stack([np_bytes_to_bits(p) for p in psdus])
    frames = jnp.asarray(np.asarray(tx.encode_batch(psdus, m)))
    n_sym = n_symbols(n_bytes, RATES[m])
    x = jax.vmap(lambda f: channel.sco_resample_graph(f, 4e-4))(
        frames)
    errs = {}
    for st in (False, True):
        got, _ = rx.decode_data_batch(x, RATES[m], n_sym,
                                      8 * n_bytes, sco_track=st)
        errs[st] = int(np.sum(np.asarray(got) != want))
    assert errs[False] > 50, errs       # the fault is real
    assert errs[True] < errs[False] // 4, errs


# ------------------------------------------- streaming / fleet / chaos


def _std_streams(s, profile, seed=31):
    rng = np.random.default_rng(seed)
    psdus = [[rng.integers(0, 256, 12).astype(np.uint8)
              for _ in range(2)] for _ in range(s)]
    rates = [[MBPS[(i + j) % 8] for j in range(2)] for i in range(s)]
    return link.stream_many_multi(
        psdus, rates, snr_db=30.0, cfo=1e-4, delay=60, seed=seed,
        add_fcs=True, tail=FRAME_LEN, channel_profile=profile)


def test_fleet_flat_identity_no_recompile():
    # S=8 fleet at the suite-shared geometry: flat-profile streams
    # are bitwise the unprofiled streams, and decoding them mints no
    # new compiled programs (warm pass first — the fleet programs are
    # the suite-shared compile class)
    streams, starts = _std_streams(8, None)
    flat_streams, fstarts = _std_streams(8, "flat")
    for a, b in zip(streams, flat_streams):
        assert np.array_equal(a, b)
    for a, b in zip(starts, fstarts):
        assert np.array_equal(a, b)
    kw = dict(chunk_len=CHUNK, frame_len=FRAME_LEN,
              max_frames_per_chunk=K, check_fcs=True)
    base, _stats = framebatch.receive_streams(streams, **kw)
    with dispatch.no_recompile(rx._jit_stream_chunk_multi,
                               rx._jit_stream_decode_multi):
        got, stats = framebatch.receive_streams(flat_streams, **kw)
    assert sum(len(v) for v in got) == sum(len(v) for v in base) > 0
    for gs, bs in zip(got, base):
        for a, b in zip(gs, bs):
            assert a.start == b.start
            assert _same_result(a.result, b.result)


def test_hostile_stream_and_channel_chaos_contained():
    # a hostile-profile stream AND chaos channel-kind slab corruption
    # through the streaming receiver: frames may fail, the receiver
    # may not crash, healthy runs stay healthy (docs/robustness.md)
    (stream,), (starts,) = _std_streams(1, "hostile", seed=33)
    sr = framebatch.StreamReceiver(chunk_len=CHUNK,
                                   frame_len=FRAME_LEN,
                                   max_frames_per_chunk=K,
                                   check_fcs=True, sanitize=True)
    got = sr.push(stream)
    got += sr.flush()
    assert sr.stats.chunks > 0          # it ran, it did not crash
    # chaos grammar: per-slab channel corruption at the push seam
    (clean,), _ = _std_streams(1, None, seed=33)
    specs, cseed = faults.parse_chaos_spec(
        "seed=5;rx.push.s0:channel:profile=severe,every=2")
    sr2 = framebatch.StreamReceiver(chunk_len=CHUNK,
                                    frame_len=FRAME_LEN,
                                    max_frames_per_chunk=K,
                                    check_fcs=True, sanitize=True)
    with faults.inject(*specs, seed=cseed) as plan:
        out = []
        for lo in range(0, clean.shape[0], 1500):
            out += sr2.push(clean[lo: lo + 1500])
        out += sr2.flush()
    assert plan.total_fired > 0
    assert sr2.stats.chunks > 0         # corrupted input, no crash
