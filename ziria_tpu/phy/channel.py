"""Channel impairment models for loopback testing (pair format).

The reference tests its RX against TX output passed through file-based
golden streams (SURVEY.md §4); real-channel impairments came from
SORA/BladeRF hardware. Here the channel is synthetic and explicit: AWGN,
carrier frequency offset, integer delay (with noise padding), phase
offset, and multipath FIR — everything jax, batchable over frames.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ziria_tpu.ops import cplx
from ziria_tpu.phy import profiles as chanprof


def awgn(key, samples, snr_db: float) -> jnp.ndarray:
    """Add complex white noise at the given SNR (dB) relative to the
    average sample power."""
    x = jnp.asarray(samples, jnp.float32)
    p_sig = jnp.mean(cplx.cabs2(x))
    p_noise = p_sig / (10.0 ** (snr_db / 10.0))
    noise = jax.random.normal(key, x.shape) * jnp.sqrt(p_noise / 2.0)
    return x + noise


def apply_cfo(samples, eps: float) -> jnp.ndarray:
    """Rotate samples by e^{+j*eps*n} (eps radians/sample)."""
    x = jnp.asarray(samples, jnp.float32)
    return cplx.cmul(x, cplx.cexp_ramp(eps, x.shape[0]))


def apply_phase(samples, theta: float) -> jnp.ndarray:
    x = jnp.asarray(samples, jnp.float32)
    return cplx.cmul(x, jnp.broadcast_to(cplx.cexp(jnp.float32(theta)),
                                         x.shape))


def delay(key, samples, n_before: int, n_after: int = 0,
          noise_db: float = -30.0) -> jnp.ndarray:
    """Pad the frame with low-level noise before/after (models idle air
    time around a detected packet)."""
    x = jnp.asarray(samples, jnp.float32)
    p_sig = jnp.mean(cplx.cabs2(x))
    amp = jnp.sqrt(p_sig * 10.0 ** (noise_db / 10.0) / 2.0)
    pad = jax.random.normal(key, (n_before + n_after, 2)) * amp
    return jnp.concatenate([pad[:n_before], x, pad[n_before:]], axis=0)


# ------------------------------------------------- batched link channel
#
# The device-resident loopback link (phy/link.py) needs the channel as
# ONE vmapped dispatch over a frame batch with PER-LANE parameters —
# the composable helpers above are host-loop shaped (python-scalar
# params, shape-changing delay). `impair_graph` is the same physics at
# a fixed geometry: CFO rotation, integer delay as a roll into the
# zero tail, and AWGN at the lane's own SNR, every parameter a traced
# per-lane scalar. Keys derive from one seed by lane-counter fold-in,
# so lane i's noise never depends on the batch composition.


def impair_graph(x, n_valid, snr_db, eps, delay, key) -> jnp.ndarray:
    """One lane of the batched link channel, all shapes static.

    x: (L, 2) TX samples, only the first `n_valid` (traced int32) of
    which are the frame — anything past is masked to zero HERE (an
    encode_many lane's bucket pad carries garbage symbols, which must
    neither transmit nor count as signal power); snr_db/eps/delay
    (traced scalars): the lane's own AWGN SNR (``inf`` disables noise
    exactly — the noise term multiplies to 0), CFO in rad/sample, and
    integer sample delay (must satisfy delay + n_valid <= L, or the
    frame tail wraps around). Returns (L, 2). Under ``vmap`` this is
    the whole channel of an N-frame batch in one dispatch;
    single-lane calls are the per-frame oracle the batched path is
    judged against — the mask makes the two agree bit-for-bit
    whatever the caller's pad region holds (the select passes real
    samples through untouched)."""
    x = jnp.asarray(x, jnp.float32)
    idx = jnp.arange(x.shape[0])
    x = jnp.where((idx < n_valid)[:, None], x, 0.0)
    n = idx.astype(jnp.float32)
    x = cplx.cmul(x, cplx.cexp(jnp.float32(eps) * n))   # zeros stay 0
    x = jnp.roll(x, delay, axis=0)     # circular, but the zero tail
    #                                    makes it a pure shift
    p_sig = jnp.sum(cplx.cabs2(x)) / jnp.maximum(
        jnp.asarray(n_valid, jnp.float32), 1.0)
    p_noise = p_sig / (10.0 ** (jnp.asarray(snr_db, jnp.float32) / 10.0))
    noise = jax.random.normal(key, x.shape) * jnp.sqrt(p_noise / 2.0)
    return x + noise


def lane_key(seed, i):
    """Counter-derived per-lane PRNG key: fold the lane index into the
    batch seed. The same key reaches lane i whether the channel runs
    batched, per-frame, or as a whole stream (`impair_stream`) — the
    bit-identity hinge of the link tests AND the stream/batch seeding
    contract: every noise consumer folds off THIS key, so a lane's
    draws never depend on which surface applies the channel."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), i)


# salts folding the per-lane key into independent draw streams: the
# AWGN consumes the bare lane key (so the profiled and unprofiled
# graphs draw IDENTICAL noise — the flat bit-identity hinge), bursts
# fold these in (position, then the burst noise field)
_BURST_POS_SALT = 0x6B01
_BURST_NOISE_SALT = 0x6B02


def sco_resample_graph(x, sco):
    """Sampling-clock-offset resample, traced: linear interpolation
    at positions ``n * (1 + sco)`` — the RX ADC ticking `sco` faster
    than the TX DAC, a slowly growing timing drift. ``sco == 0``
    reproduces ``x`` exactly (positions are exact integers, the
    interpolation weights collapse to 1/0), the profiled-graph
    neutral-identity argument. Positions past the end extend the last
    sample (the tail is the capture's zero pad anyway)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    pos = jnp.arange(n, dtype=jnp.float32) \
        * (1.0 + jnp.asarray(sco, jnp.float32))
    i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n - 1)
    i1 = jnp.clip(i0 + 1, 0, n - 1)
    frac = (pos - i0.astype(jnp.float32))[:, None]
    return x[i0] * (1.0 - frac) + x[i1] * frac


def _burst_graph(x, p_sig, every, blen, bdb, key):
    """Seeded interference bursts, traced: a `blen`-sample wideband
    noise burst every `every` samples at `bdb` dB relative to the
    lane's signal power, burst phase offset drawn from the lane key's
    burst fold-in (deterministic per (seed, lane), independent of the
    AWGN draw). ``every == 0`` adds exactly zero (amp masks to 0.0 —
    finite noise times zero), the neutral-identity argument."""
    every = jnp.asarray(every, jnp.int32)
    on = every > 0
    safe = jnp.maximum(every, 1)
    off = jax.random.randint(
        jax.random.fold_in(key, _BURST_POS_SALT), (), 0, safe)
    idx = jnp.arange(x.shape[0], dtype=jnp.int32)
    in_burst = on & (((idx - off) % safe) < jnp.asarray(blen, jnp.int32))
    amp = jnp.where(
        on,
        jnp.sqrt(p_sig * 10.0 ** (jnp.asarray(bdb, jnp.float32) / 10.0)
                 / 2.0),
        0.0)
    noise = jax.random.normal(
        jax.random.fold_in(key, _BURST_NOISE_SALT), x.shape)
    return x + noise * (amp * in_burst.astype(jnp.float32))[:, None]


def impair_profile_graph(x, n_valid, snr_db, eps, delay, key,
                         taps, sco, drift, burst_every, burst_len,
                         burst_db,
                         with_bursts: bool = True) -> jnp.ndarray:
    """One lane of the PROFILED batched channel — `impair_graph` with
    the physical-layer faults composed in (docs/robustness.md):

        mask pad -> multipath FIR (`taps`) -> SCO resample ->
        CFO + drift phase (theta = eps*n + drift*n^2/2) ->
        integer delay -> AWGN (the lane key, UNCHANGED) ->
        seeded interference bursts (key fold-ins)

    Every profile parameter is a traced per-lane value, so ONE
    compiled graph (per tap count) serves a batch of mixed profiles
    under ``vmap``. At the neutral parameters (one-hot taps, sco =
    drift = 0, burst_every = 0) every added op is an exact identity
    and the AWGN consumes the same key, so a neutral lane is
    BIT-IDENTICAL to `impair_graph` at the op level (pinned eager by
    tests/test_channel_profiles.py; ACROSS separately compiled
    programs XLA's FMA contraction may differ by one float32 ulp —
    the bit-exact ``flat`` guarantee is `resolve_profiles`' collapse
    to the unprofiled path, not this graph). The FIR rings
    `len(taps) - 1` samples past `n_valid`; callers keep
    ``delay + n_valid + len(taps) - 1 <= L`` or the tail wraps."""
    x = jnp.asarray(x, jnp.float32)
    idx = jnp.arange(x.shape[0])
    x = jnp.where((idx < n_valid)[:, None], x, 0.0)
    x = multipath(x, taps)
    x = sco_resample_graph(x, sco)
    n = idx.astype(jnp.float32)
    theta = jnp.asarray(eps, jnp.float32) * n \
        + 0.5 * jnp.asarray(drift, jnp.float32) * n * n
    x = cplx.cmul(x, cplx.cexp(theta))
    x = jnp.roll(x, delay, axis=0)
    p_sig = jnp.sum(cplx.cabs2(x)) / jnp.maximum(
        jnp.asarray(n_valid, jnp.float32), 1.0)
    p_noise = p_sig / (10.0 ** (jnp.asarray(snr_db, jnp.float32) / 10.0))
    noise = jax.random.normal(key, x.shape) * jnp.sqrt(p_noise / 2.0)
    x = x + noise
    if not with_bursts:
        # STATIC skip (callers pass the host-known "no lane bursts"
        # fact): the burst amp is a traced per-lane value, so without
        # this XLA cannot DCE the full-capture normal draw a
        # burst-free profile would multiply by zero
        return x
    return _burst_graph(x, p_sig, burst_every, burst_len, burst_db,
                        key)


def _profile_consts(profile_key):
    """Per-lane profile names -> jnp constant parameter arrays for the
    vmapped profiled graph (None passes through). Host-side: the
    arrays bake into whichever jit closes over them, keyed by the name
    tuple — a handful of name combinations, not one compile per
    parameter value."""
    if profile_key is None:
        return None
    arrs = chanprof.lane_arrays(profile_key)
    return tuple(jnp.asarray(a) for a in arrs)


def impair_many_graph(x_b, n_valid, snr_db, eps, delay, seed,
                      out_len: int, profile_key=None) -> jnp.ndarray:
    """The traced batched channel: pad the TX batch to `out_len`,
    derive per-lane keys from `seed` by counter fold-in, and apply
    every lane's own impairments under one ``vmap`` — the graph
    `_jit_impair_many` jits, exposed as a plain function so larger
    programs can FUSE it (the one-dispatch loopback link traces it
    between the batch encode and the batched receiver).

    ``profile_key`` (a per-lane tuple of channel-profile names, or
    None) routes through `impair_profile_graph` with the profiles'
    taps/SCO/drift/burst parameters as per-lane constants — still ONE
    vmapped graph, same dispatch count; None is today's unprofiled
    graph, untouched."""
    pad = out_len - x_b.shape[1]
    x = jnp.pad(jnp.asarray(x_b, jnp.float32),
                ((0, 0), (0, pad), (0, 0)))
    keys = jax.vmap(lambda i: lane_key(seed, i))(
        jnp.arange(x.shape[0]))
    if profile_key is None:
        return jax.vmap(impair_graph)(x, n_valid, snr_db, eps, delay,
                                      keys)
    taps, sco, drift, b_ev, b_ln, b_db = _profile_consts(profile_key)
    wb = any(chanprof.get_profile(n).burst_every for n in profile_key)
    return jax.vmap(
        lambda xi, nv, s, e, d, k, t, sc, dr, be, bl, bd:
        impair_profile_graph(xi, nv, s, e, d, k, t, sc, dr, be, bl,
                             bd, with_bursts=wb))(
        x, n_valid, snr_db, eps, delay, keys, taps, sco, drift,
        b_ev, b_ln, b_db)


@lru_cache(maxsize=None)
def _jit_impair_many(out_len: int, profile_key=None):
    """ONE jitted `impair_many_graph` per (output length, per-lane
    profile-name tuple) — jit retraces per input shape; the profile
    constants bake into the graph, so the cache key IS the name tuple
    (resolved by the caller, never env-read here — jaxlint R1)."""
    def f(x_b, n_valid, snr_db, eps, delay, seed):
        return impair_many_graph(x_b, n_valid, snr_db, eps, delay,
                                 seed, out_len, profile_key)
    return jax.jit(f)


def impair_many(x_b, n_valid, snr_db, eps, delay, seed,
                out_len: int = None, profile=None) -> jnp.ndarray:
    """Batched per-lane channel: (R, L, 2) device-resident TX batch ->
    (R, out_len, 2) impaired captures in ONE dispatch, staying on
    device for the receiver. Per-lane arrays for n_valid/snr_db/eps/
    delay (scalars broadcast); `seed` one int — lane keys derive by
    counter fold-in (`lane_key`). Bit-identical per lane to a
    single-lane `impair_graph` call with the same key.

    ``profile`` is a channel-profile name, per-lane sequence, or None
    — None means UNPROFILED here: the ``ZIRIA_CHANNEL_PROFILE`` env
    default is deliberately NOT consulted at this low-level surface
    (``use_env=False`` — the top-level surfaces resolve it once, and
    an explicit "flat" there must not have the env resurrected
    underneath; `profiles.resolve_profiles`; all-flat resolves to
    the unprofiled graph by construction). Profiled batches stay ONE
    dispatch; lane i matches `impair_one` at the same profile name
    to within one float32 ulp (separately compiled programs — the
    FMA-contraction rule), exactly when unprofiled."""
    from ziria_tpu.utils import dispatch, programs

    r = int(x_b.shape[0])
    if out_len is None:
        out_len = int(x_b.shape[1])
    profile_key = chanprof.resolve_profiles(profile, r, use_env=False)

    def _vec(v, dtype):
        a = np.broadcast_to(np.asarray(v, dtype), (r,))
        return jnp.asarray(a)

    imp_fn = _jit_impair_many(int(out_len), profile_key)
    imp_args = (x_b, _vec(n_valid, np.int32), _vec(snr_db, np.float32),
                _vec(eps, np.float32), _vec(delay, np.int32),
                jnp.uint32(seed))
    programs.note_site("channel.impair_many", imp_fn, *imp_args)
    with dispatch.timed("channel.impair_many"):
        return imp_fn(*imp_args)


@lru_cache(maxsize=None)
def _jit_impair_one(profile_key=None):
    """ONE jitted single-lane channel per profile name (None = the
    unprofiled `impair_graph`, exactly as before; the name's profile
    constants bake in, resolved by the caller — jaxlint R1)."""
    if profile_key is None:
        return jax.jit(impair_graph)
    taps, sco, drift, b_ev, b_ln, b_db = _profile_consts(
        (profile_key,))
    wb = bool(chanprof.get_profile(profile_key).burst_every)

    def f(x, n_valid, snr_db, eps, delay, key):
        return impair_profile_graph(
            x, n_valid, snr_db, eps, delay, key, taps[0], sco[0],
            drift[0], b_ev[0], b_ln[0], b_db[0], with_bursts=wb)
    return jax.jit(f)


def impair_one(samples, snr_db, eps, delay, seed, lane: int,
               out_len: int, profile=None) -> jnp.ndarray:
    """The per-frame oracle of `impair_many`: one lane's impairments
    through the SAME graph with the SAME counter-derived key
    (`lane_key(seed, lane)`), the frame zero-padded to `out_len`
    host-side. Bit-identical to row `lane` of the batched dispatch;
    with ``profile`` (this LANE's profile name or None) it matches
    row `lane` of the batched dispatch at the same per-lane profile
    to within one float32 ulp (separately compiled programs — the
    FMA-contraction rule tests/test_channel_profiles.py documents)."""
    from ziria_tpu.utils import dispatch, programs

    names = chanprof.resolve_profiles(profile, 1, use_env=False)
    x = np.zeros((int(out_len), 2), np.float32)
    s = np.asarray(samples, np.float32)
    x[:s.shape[0]] = s
    imp_fn = _jit_impair_one(None if names is None else names[0])
    imp_args = (jnp.asarray(x), jnp.int32(s.shape[0]),
                jnp.float32(snr_db), jnp.float32(eps),
                jnp.int32(delay), lane_key(seed, lane))
    programs.note_site("channel.impair", imp_fn, *imp_args)
    with dispatch.timed("channel.impair"):
        return imp_fn(*imp_args)


def impair_stream(stream, n_signal: int, snr_db, eps, seed,
                  profile=None, lane: int = 0) -> np.ndarray:
    """Whole-stream impairments for the streaming-receiver stimulus
    (`phy/link.stream_many`): the channel profile's multipath FIR and
    SCO resample (host numpy twins of the vmapped graph ops —
    `profiles.np_apply_taps` / `np_apply_sco`), one CFO(+drift)
    rotation over the FULL stream (a single oscillator — every frame
    sees the same eps, at its own carrier phase; a profile's `drift`
    adds the quadratic term), AWGN at `snr_db` relative to the
    average *frame* power, then the profile's seeded interference
    bursts. `n_signal` is the count of real signal samples in the
    stream — the inter-frame gaps are idle air and must not deflate
    the reference power the way a whole-stream mean would. ``np.inf``
    disables noise exactly.

    SEEDING CONTRACT (the stream/batch symmetry the batched channel
    already had): every draw folds off ``lane_key(seed, lane)`` —
    the AWGN consumes the bare lane key via ``jax.random.normal``
    (the SAME per-lane fold-in schedule as `impair_many_graph`, so at
    equal geometry — same (seed, lane), same array shape — the
    standard-normal field is element-identical to the batched lane's)
    and bursts fold the same salts the graph folds. Host numpy keeps
    the float64 trig / power math (deterministic test/bench stimulus;
    the receiver under test only ever sees the returned f32 stream)."""
    prof = None
    names = chanprof.resolve_profiles(profile, 1, use_env=False)
    if names is not None:
        prof = chanprof.get_profile(names[0])
    x = np.asarray(stream, np.float32)
    drift = 0.0
    if prof is not None:
        x = chanprof.np_apply_taps(x, prof)
        x = chanprof.np_apply_sco(x, prof.sco)
        drift = float(prof.drift)
    if eps or drift:
        n = np.arange(x.shape[0], dtype=np.float64)
        theta = float(eps) * n + 0.5 * drift * n * n
        c = np.cos(theta)
        s = np.sin(theta)
        x = np.stack([x[:, 0] * c - x[:, 1] * s,
                      x[:, 0] * s + x[:, 1] * c], axis=-1)
        x = x.astype(np.float32)
    # the O(n) power reduction and the jax key are only needed when
    # something will draw (finite-SNR noise or profile bursts): the
    # common snr=inf unprofiled stimulus stays draw-free and cheap
    need_draws = np.isfinite(snr_db) or (prof is not None
                                         and prof.burst_every)
    key = lane_key(seed, lane) if need_draws else None
    p_sig = (float(np.sum(x.astype(np.float64) ** 2)
                   / max(int(n_signal), 1)) if need_draws else 0.0)
    if np.isfinite(snr_db):
        p_noise = p_sig / (10.0 ** (float(snr_db) / 10.0))
        noise = np.asarray(jax.random.normal(key, x.shape), np.float64)
        x = (x + noise * np.sqrt(p_noise / 2.0)).astype(np.float32)
    if prof is not None and prof.burst_every:
        off = int(jax.random.randint(
            jax.random.fold_in(key, _BURST_POS_SALT), (), 0,
            prof.burst_every))
        in_burst = chanprof.np_burst_mask(x.shape[0], prof, off)
        amp = chanprof.np_burst_amp(p_sig, prof)
        bn = np.asarray(jax.random.normal(
            jax.random.fold_in(key, _BURST_NOISE_SALT), x.shape),
            np.float64)
        x = (x + bn * (amp * in_burst.astype(np.float64))[:, None]) \
            .astype(np.float32)
    return x


def impair_profile_point_graph(frames, keys, snr_db,
                               profile_key: str) -> jnp.ndarray:
    """Perfect-sync profiled channel for the BER surfaces
    (`link.loopback_ber_bits` / `link.sweep_ber`'s profile axis),
    traced: per-lane multipath + SCO resample + drift phase (the
    deterministic profile ops — no CFO/delay here, the BER lane is
    perfect-sync by design), AWGN at `snr_db` through `awgn` with the
    caller's split keys (loopback_ber_bits' key schedule, NOT the
    framed link's fold-in lane keys), then seeded bursts off each
    lane's key fold-ins. ``profile_key`` is ONE static profile name —
    its constants bake into the graph. The sweep's flat column skips
    this entirely (flat IS the unprofiled expression), so the
    profiled sweep's flat counts are bit-identical to the unprofiled
    sweep by construction."""
    taps, sco, drift, b_ev, b_ln, b_db = _profile_consts(
        (profile_key,))
    wb = bool(chanprof.get_profile(profile_key).burst_every)

    def lane(f, k):
        x = multipath(jnp.asarray(f, jnp.float32), taps[0])
        x = sco_resample_graph(x, sco[0])
        n = jnp.arange(x.shape[0], dtype=jnp.float32)
        x = cplx.cmul(x, cplx.cexp(0.5 * drift[0] * n * n))
        p_sig = jnp.mean(cplx.cabs2(x))
        x = awgn(k, x, snr_db)
        if not wb:       # static: no wasted full-length burst draw
            return x
        return _burst_graph(x, p_sig, b_ev[0], b_ln[0], b_db[0], k)

    return jax.vmap(lane)(frames, keys)


def multipath(samples, taps_pair) -> jnp.ndarray:
    """Complex FIR channel: taps_pair (L, 2). Causal, same length out.

    The frequency-selective core of the profiled channel
    (`impair_profile_graph` applies it per lane under vmap; the named
    tap sets live in `phy/profiles.CHANNEL_PROFILES`). Pinned against
    a host numpy complex-FIR oracle by
    tests/test_channel_profiles.py. A one-hot tap vector is an exact
    identity (the flat-lane neutral-identity argument)."""
    x = jnp.asarray(samples, jnp.float32)
    t = jnp.asarray(taps_pair, jnp.float32)
    n = x.shape[0]

    def conv(u, v):
        return jnp.convolve(u, v, precision="highest")[:n]

    re = conv(x[:, 0], t[:, 0]) - conv(x[:, 1], t[:, 1])
    im = conv(x[:, 0], t[:, 1]) + conv(x[:, 1], t[:, 0])
    return jnp.stack([re, im], axis=-1)


def impaired_capture(mbps: int, n_bytes: int, seed: int,
                     cfo: float = 0.002, pre: int = 60, post: int = 40,
                     noise: float = 0.03, floor: float = 0.02,
                     scale: float = 1024.0, add_fcs: bool = False):
    """A deterministic receiver test vector: one TX frame with CFO,
    surrounded by noise, plus AWGN, quantized to the complex16 wire
    format (int16 IQ pairs). Returns (psdu_bytes, samples).

    The single source of truth for the capture recipe the receiver
    tests AND the checked-in wifi_rx golden use — three copies of this
    pipeline had already appeared before it was hoisted here.
    """
    import numpy as np

    from ziria_tpu.phy.wifi import tx

    rng = np.random.default_rng(seed)
    psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
    frame = np.asarray(tx.encode_frame(psdu, mbps, add_fcs=add_fcs))
    x = np.concatenate([
        rng.normal(scale=floor, size=(pre, 2)).astype(np.float32),
        np.asarray(apply_cfo(jnp.asarray(frame), cfo)),
        rng.normal(scale=floor, size=(post, 2)).astype(np.float32)])
    x = (x + rng.normal(scale=noise, size=x.shape)).astype(np.float32)
    xi = np.clip(np.round(x * scale), -32768, 32767).astype(np.int16)
    return psdu, xi
