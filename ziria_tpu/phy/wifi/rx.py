"""802.11a/g OFDM receiver chain.

Counterpart of the reference's `code/WiFi/receiver/` top-level `rx.blk`
(SURVEY.md §2.3, §3.4): packet detect (STS autocorr) ; CFO est/correct ;
channel est (LTS) ; PLCP header parse ; then per-rate FFT >>> pilot
tracking >>> soft demap >>> deinterleave >>> Viterbi >>> descramble >>>
CRC.

TPU-first structure: the steady-state DATA decode is one traced graph
over ALL symbols of a frame at once — (n_sym, 64) matmul-FFTs, batched
pilot tracking, one Viterbi scan — and batches over frames with vmap.
The data-dependent part (header-derived rate/length — the motivating
example for the reference's computers-returning-values, §3.4) is a
two-phase dispatch: decode SIGNAL (fixed shape), then select the
per-rate compiled decoder — the jit analogue of `parsePLCPHeader ;
per-rate loop`. ``receive()`` drives the whole thing host-side;
``decode_data_static`` is the fully-jitted flagship used by the bench.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ziria_tpu.ops import cplx, coding, demap as demap_mod, interleave, ofdm, \
    scramble, sync, viterbi, viterbi_pallas
from ziria_tpu.ops.crc import check_crc32
# MAX_DBPS / RATE_INDEX / RATE_MBPS_ORDER: the lax.switch branch
# order shared with TX encode_many (hoisted to params so both sides
# of the link agree by construction), re-exported here because this
# module is where the switch-order contract is consumed
from ziria_tpu.phy.wifi.params import (MAX_DBPS, N_SERVICE_BITS,
                                       N_TAIL_BITS, RATE_INDEX,
                                       RATE_MBPS_ORDER, RateParams,
                                       RATES, SIGNAL_BITS_TO_MBPS,
                                       mixed_branch_symbols,
                                       mixed_trellis_steps, n_symbols)
from ziria_tpu.utils.bits import bits_to_uint

FRAME_DATA_START = 400  # 320 preamble + 80 SIGNAL


def equalize(bins, H):
    """Zero-forcing equalization of (..., 64, 2) bins by H (64, 2)."""
    return cplx.cdiv(bins, jnp.broadcast_to(H, bins.shape))


#: bounded-|H| equalizer guard: a used subcarrier whose estimated
#: channel gain |H|^2 falls below this fraction of the MEAN used-bin
#: gain is treated as a NULL — its equalized symbols, its demap gain,
#: and (crucially) its pilot contribution zero out EXACTLY, so a deep
#: multipath fade degrades to zero-LLR erasures instead of feeding
#: noise amplified by 1/|H| into the demapper, and a nulled PILOT
#: stops poisoning the common-phase estimate of every other
#: subcarrier in its symbol. 1e-3 sits far below any healthy gain
#: (flat channels estimate |H|^2 ~ 1 +- noise), so on flat channels
#: the guard never trips and the select ops pass values through
#: bitwise — the flat-profile identity contract holds through it.
H_GUARD_REL = 1e-3


def guard_subcarriers(data, pilots, H):
    """The bounded-|H| null-subcarrier guard (docs/robustness.md):
    given extracted data (..., n_sym, 48, 2) and pilots
    (..., n_sym, 4, 2) plus the channel estimate H (64, 2), zero the
    bins whose gain is under ``H_GUARD_REL`` x the mean used-bin gain
    and return ``(data, pilots, gain)`` with `gain` the (48,)
    demap weight, zeroed at nulls (an exact-zero equalized symbol
    times an exact-zero gain = a true erasure LLR, the same
    adds-no-likelihood argument as the bucket padding)."""
    g = cplx.cabs2(H)                                     # (64,)
    gd = g[jnp.asarray(ofdm.DATA_BINS)]                   # (48,)
    gp = g[jnp.asarray(ofdm.PILOT_BINS)]                  # (4,)
    floor = H_GUARD_REL * jnp.mean(jnp.concatenate([gd, gp]))
    data = jnp.where((gd < floor)[:, None], 0.0, data)
    pilots = jnp.where((gp < floor)[:, None], 0.0, pilots)
    gain = jnp.where(gd < floor, 0.0, gd)
    return data, pilots, gain


def sco_track_enabled(sco_track=None) -> bool:
    """The ONE reading of the --rx-sco-track / ZIRIA_RX_SCO_TRACK
    knob (default OFF — the flat-profile bit-identity contract pins
    the default DATA decode bitwise, and a fitted slope is never
    exactly zero): whether `pilot_phase_correct` additionally fits
    and removes the per-subcarrier phase RAMP a sampling-clock
    offset induces (docs/robustness.md). Callers resolve once and
    pass the bool into the decode jit factories' cache keys. The env
    read itself lives with the geometry object's designated reader
    (utils/geometry.env_sco_track)."""
    if sco_track is not None:
        return bool(sco_track)
    from ziria_tpu.utils.geometry import env_sco_track
    return env_sco_track()


def pilot_phase_correct(data, pilots, symbol_index0: int,
                        sco_track: bool = False):
    """Common-phase derotation per symbol from the 4 pilots.

    data (..., n_sym, 48, 2), pilots (..., n_sym, 4, 2); pilot polarity
    index starts at symbol_index0.

    ``sco_track=True`` additionally fits the per-subcarrier phase
    RAMP across the pilots and derotates the data by it: a
    sampling-clock offset is a timing drift tau(t), which in the
    frequency domain is a phase slope ~ k * tau growing over the
    frame — the common phase tracks its mean, the ramp is what is
    left. Slope per symbol by least squares through the origin over
    the pilot subcarrier indices (-21, -7, 7, 21), weighted by pilot
    energy so a guarded-out null pilot carries zero weight. Off by
    default: the flat-path decode must stay bit-identical, and a
    fitted slope is never exactly zero."""
    n_sym = data.shape[-3]
    pol = jnp.asarray(ofdm.PILOT_POLARITY, jnp.float32)[
        (jnp.arange(n_sym) + symbol_index0) % 127]
    expect_re = jnp.asarray(ofdm.PILOT_VALS, jnp.float32)[None, :] * \
        pol[:, None]                                   # (n_sym, 4)
    # phase of sum_k pilots_k * expected_k (expected is real)
    weighted = pilots * expect_re[..., :, None]
    ph = jnp.arctan2(weighted[..., 1].sum(-1), weighted[..., 0].sum(-1))
    derot = cplx.cexp(-ph)                             # (..., n_sym, 2)
    data = cplx.cmul(data, derot[..., None, :])
    if not sco_track:
        return data
    w = cplx.cmul(weighted, derot[..., None, :])   # common phase out
    res = jnp.arctan2(w[..., 1], w[..., 0])        # (..., n_sym, 4)
    k_p = jnp.asarray(ofdm.PILOT_SC, jnp.float32)
    e = cplx.cabs2(w)
    num = jnp.sum(e * k_p * res, axis=-1)
    den = jnp.sum(e * k_p * k_p, axis=-1)
    slope = num / jnp.maximum(den, 1e-12)          # rad / subcarrier
    k_d = jnp.asarray(ofdm.DATA_SC, jnp.float32)
    ramp = cplx.cexp(-slope[..., None] * k_d)      # (..., n_sym, 48, 2)
    return cplx.cmul(data, ramp)


def decode_signal(frame):
    """Decode the SIGNAL symbol of an aligned, CFO-corrected frame.

    Returns (rate_bits_uint (traced), length (traced), parity_ok
    (traced)). Fixed shapes — jits once."""
    H = sync.estimate_channel(frame)
    bins = ofdm.ofdm_demodulate(frame[320:400][None])  # (1, 64, 2)
    eq = equalize(bins, H)
    data, pilots = ofdm.extract_subcarriers(eq)
    data, pilots, gain = guard_subcarriers(data, pilots, H)
    data = pilot_phase_correct(data, pilots, symbol_index0=0)
    llr = demap_mod.demap(data, 1, gain=gain[None])[0]
    deint = interleave.deinterleave(llr, 48, 1)
    bits = viterbi.viterbi_decode(deint, n_bits=24)
    rate_bits = bits_to_uint(bits[0:4], msb_first=True)
    length = bits_to_uint(bits[5:17])
    parity_ok = (bits[:18].astype(jnp.uint32).sum() % 2) == 0
    return rate_bits, length, parity_ok


def _front_symbols(frame, n_sym: int, sco_track: bool = False):
    """Aligned frame -> (data (n_sym, 48, 2), gain (48,)): channel est
    (two-repeat LTS average) + (n_sym x 64) matmul-FFT + equalize +
    bounded-|H| guard + pilot track — the shared pre-demap front.
    Split out so the fused-demap decode can hand the raw equalized
    subcarriers straight to the Pallas kernel
    (ops/viterbi_pallas.viterbi_decode_batch_fused) while the XLA
    demap path keeps consuming the identical values. ``sco_track``
    adds the pilot phase-ramp fit (resolved by the caller — part of
    every decode factory's cache key)."""
    H = sync.estimate_channel(frame)
    syms = frame[FRAME_DATA_START: FRAME_DATA_START + 80 * n_sym]
    bins = ofdm.ofdm_demodulate(syms.reshape(n_sym, 80, 2))
    eq = equalize(bins, H)
    data, pilots = ofdm.extract_subcarriers(eq)
    data, pilots, gain = guard_subcarriers(data, pilots, H)
    data = pilot_phase_correct(data, pilots, symbol_index0=1,
                               sco_track=sco_track)
    return data, gain


def _decode_front(frame, rate: RateParams, n_sym: int,
                  sco_track: bool = False):
    """Aligned frame -> depunctured soft LLR pairs (T, 2): channel est +
    (n_sym x 64) matmul-FFT + equalize + pilot track + demap +
    deinterleave + depuncture — everything before the Viterbi."""
    data, gain = _front_symbols(frame, n_sym, sco_track)
    return _demap_symbols(data, gain, rate)


def _demap_symbols(data, gain, rate: RateParams):
    """`_front_symbols`' (n_sym, 48, 2) symbols and (48,) gains ->
    depunctured soft LLR pairs (n_sym * n_dbps, 2): demap + deinterleave
    + depuncture, the rate-dependent half of `_decode_front`. Symbol-
    local, like the half before it: a prefix of the symbols gives that
    prefix of the rows."""
    llrs = demap_mod.demap(data, rate.n_bpsc,
                           gain=jnp.broadcast_to(gain, data.shape[:-1]))
    deint = interleave.deinterleave(
        llrs.reshape(-1), rate.n_cbps, rate.n_bpsc)
    return coding.depuncture(deint, rate.coding, fill=0.0).reshape(-1, 2)


def fused_demap_enabled(fused_demap=None) -> bool:
    """The ONE reading of the --fused-demap / ZIRIA_FUSED_DEMAP knob
    (default OFF — the XLA front end is the oracle): whether the
    known-rate DATA decodes run demap + deinterleave + depuncture as
    an in-kernel prologue of the Pallas ACS (LLRs produced and
    consumed in VMEM, never round-tripping HBM). The env read itself
    lives with the geometry object's designated reader
    (utils/geometry.env_fused_demap)."""
    if fused_demap is not None:
        return fused_demap
    from ziria_tpu.utils.geometry import env_fused_demap
    return env_fused_demap()


def _fused_front_applies(viterbi_window, viterbi_metric) -> bool:
    """Where the fused front end composes: full-frame decodes at f32
    metrics. The windowed decode cuts LLR-domain windows the symbol
    tile cannot express, and the quantized metrics scale by the whole
    frame's LLR peak before the first ACS step — both fall back to
    the (bit-identical) unfused front, documented in
    docs/architecture.md's decode-roofline section."""
    return not viterbi_window and (viterbi_metric or "float32") == "float32"


def trellis_takes_bound(viterbi_window, viterbi_metric,
                        fused_demap) -> bool:
    """Whether the mixed decode's kernels stop at a bound that is data
    (`_mixed_stages`): the exact lane-tile ACS and traceback do, at
    every metric and radix; the windowed decode and the fused-demap
    kernels run their whole trellis (no served configuration runs
    them: ROADMAP S2, S6). One reading for the program and for the
    host's account of it (`decode_steps`)."""
    return not viterbi_window and not (
        fused_demap_enabled(fused_demap)
        and _fused_front_applies(viterbi_window, viterbi_metric))


def _decode_back(bits, n_psdu_bits: int):
    """Decoded bits -> (psdu_bits, descrambled service bits)."""
    seed = scramble.recover_seed(bits[:7])
    clear = scramble.descramble_bits(bits, seed)
    psdu = clear[N_SERVICE_BITS: N_SERVICE_BITS + n_psdu_bits]
    return psdu, clear[:N_SERVICE_BITS]


def decode_data_static(frame, rate: RateParams, n_sym: int,
                       n_psdu_bits: int, sco_track: bool = False):
    """Fully-jitted DATA decode for a known rate/symbol count: aligned
    CFO-corrected frame -> (psdu_bits, descrambled service bits).

    The flagship fused graph: channel est + (n_sym x 64) matmul-FFT +
    equalize + pilot track + demap + deinterleave + depuncture + Viterbi
    + descramble in one jit."""
    depunct = _decode_front(frame, rate, n_sym, sco_track)
    bits = viterbi.viterbi_decode(depunct, n_bits=n_sym * rate.n_dbps)
    return _decode_back(bits, n_psdu_bits)


def decode_data_batch(frames, rate: RateParams, n_sym: int,
                      n_psdu_bits: int, interpret: bool = None,
                      viterbi_window: int = None,
                      viterbi_metric: str = None,
                      viterbi_radix: int = None,
                      fused_demap: bool = None,
                      sco_track: bool = False):
    """Batched DATA decode: (B, frame_len, 2) -> ((B, n_psdu_bits),
    (B, 16)).

    The TPU fast path: the per-frame front end (FFT/equalize/demap/...)
    runs under vmap, then the whole batch hits the Pallas Viterbi kernel
    with frames laid out across the 128 VPU lanes (~8x the vmapped
    lax.scan ACS; see ops/viterbi_pallas.py).

    ``viterbi_window`` opts into the sliding-window PARALLEL Viterbi
    (viterbi_decode_batch_windowed): the ~8k-step sequential trellis is
    cut into overlapping windows decoded as extra batch lanes — the
    standard truncated-traceback trade every production decoder
    (including the reference's SORA brick) makes, bit-identical to the
    exact decode at operating SNR (tests/test_viterbi_windowed.py).

    ``viterbi_metric="int16"`` opts into the quantized saturating-
    metric kernel (the SORA int16 discipline; docs/quantized_viterbi.md
    — the other half of the device-residency trade); ``"int8"`` into
    the int8+LUT kernel below it (BER-envelope accuracy).

    ``viterbi_radix=4`` runs two trellis steps per ACS iteration
    (bit-identical at f32/int16); ``fused_demap=True`` moves demap +
    deinterleave + depuncture into the Pallas kernel (known-rate
    surfaces only; composes with radix, falls back to the unfused
    front under windowed/quantized modes)."""
    if fused_demap_enabled(fused_demap) \
            and _fused_front_applies(viterbi_window, viterbi_metric):
        data, gain = jax.vmap(
            lambda f: _front_symbols(f, n_sym, sco_track))(frames)
        bits = viterbi_pallas.viterbi_decode_batch_fused(
            data, gain, rate, n_bits=n_sym * rate.n_dbps,
            radix=viterbi_radix, interpret=interpret)
    else:
        dep = jax.vmap(
            lambda f: _decode_front(f, rate, n_sym, sco_track))(frames)
        bits = viterbi_pallas.viterbi_decode_batch_opt(
            dep, n_bits=n_sym * rate.n_dbps, window=viterbi_window,
            interpret=interpret, metric_dtype=viterbi_metric,
            radix=viterbi_radix)
    return jax.vmap(lambda b: _decode_back(b, n_psdu_bits))(bits)


def sync_frame(samples):
    """Locate and align ONE frame in a pre-segmented capture: STS
    detection gate, LTS cross-correlation timing, coarse+fine CFO.
    Returns (found, frame_start_index, cfo_estimate). Fixed shapes ->
    jits.

    The graph itself lives in ``ops/sync.locate_frame`` (vmap-ready so
    ``acquire_many`` can batch it); this name is the receiver-side
    oracle entry the per-capture path and tests use. It is the K=1
    special case of the streaming front end — first crossing, global
    peak-pick — that ``ops/sync.locate_frames``' multi-peak chunk scan
    (the ``receive_stream`` detector) generalizes and is judged
    against; a one-frame capture gives identical (found, start) either
    way."""
    return sync.locate_frame(samples)


class RxResult(NamedTuple):
    ok: bool
    rate_mbps: int
    length_bytes: int
    psdu_bits: np.ndarray
    crc_ok: Optional[bool]


def decode_data_bucketed(frame, rate: RateParams, n_sym_bucket: int,
                         n_bits_real, viterbi_window: int = None,
                         viterbi_metric: str = None,
                         viterbi_radix: int = None,
                         fused_demap: bool = None,
                         sco_track: bool = False):
    """DATA decode over a *bucketed* symbol count: `frame` is padded to
    FRAME_DATA_START + 80*n_sym_bucket samples, `n_bits_real` is the
    true data-bit count as a TRACED scalar. Returns the full descrambled
    bit stream (n_sym_bucket * n_dbps); the caller slices the PSDU.

    This is what makes `receive()` streaming-grade (VERDICT r1 weak #3):
    one compile per (rate, power-of-two bucket) instead of one per PSDU
    length. LLR rows at or beyond `n_bits_real` are zeroed — true
    erasures — so the pad region adds no likelihood and the Viterbi path
    over the real prefix is exactly the unpadded ML path (the tail bits
    still steer it into state 0 before the pad)."""
    if fused_demap_enabled(fused_demap) \
            and _fused_front_applies(viterbi_window, viterbi_metric):
        # the fused kernel applies the SAME n_bits_real erasure mask
        # in its prologue; this single frame rides one pad-to-128 lane
        # tile of the fused Pallas decode
        data, gain = _front_symbols(frame, n_sym_bucket, sco_track)
        bits = viterbi_pallas.viterbi_decode_batch_fused(
            data[None], gain[None], rate,
            n_bits=n_sym_bucket * rate.n_dbps,
            nbits_real=jnp.asarray(n_bits_real, jnp.int32)[None],
            radix=viterbi_radix)[0]
    else:
        bits = _decode_data_bits_unfused(
            frame, rate, n_sym_bucket, n_bits_real,
            viterbi_window, viterbi_metric, viterbi_radix, sco_track)
    seed = scramble.recover_seed(bits[:7])
    return scramble.descramble_bits(bits, seed)


def _decode_data_bits_unfused(frame, rate, n_sym_bucket, n_bits_real,
                              viterbi_window, viterbi_metric,
                              viterbi_radix, sco_track=False):
    """The XLA-front-end decode body of `decode_data_bucketed`: demap
    front end, traced erasure mask, then whichever Viterbi engine the
    (window, metric, radix) mode selects. Raw coded bits out — the
    caller owns the descramble tail."""
    depunct = _decode_front(frame, rate, n_sym_bucket,
                            sco_track)                    # (T_b, 2)
    t = jnp.arange(depunct.shape[0])
    depunct = jnp.where((t < n_bits_real)[:, None], depunct, 0.0)
    if viterbi_window:
        # the windowed PARALLEL decoder: this single frame's windows
        # become a small batch through the Pallas kernel, cutting the
        # sequential trellis depth ~T/window-fold (see
        # ops/viterbi_pallas.viterbi_decode_batch_windowed)
        bits = viterbi_pallas.viterbi_decode_batch_windowed(
            depunct[None], n_bits=n_sym_bucket * rate.n_dbps,
            window=viterbi_window, metric_dtype=viterbi_metric,
            radix=viterbi_radix)[0]
    elif (viterbi._check_radix(viterbi_radix) != 2
          or (viterbi_metric or "float32") == "int8"):
        # the radix knob (and the int8 kernel) live in the Pallas
        # batch decode; ride it as a single-lane batch so the bucketed
        # per-capture path inherits the faster core too
        bits = viterbi_pallas.viterbi_decode_batch(
            depunct[None], n_bits=n_sym_bucket * rate.n_dbps,
            metric_dtype=viterbi_metric, radix=viterbi_radix)[0]
    else:
        bits = viterbi.viterbi_decode(
            depunct, n_bits=n_sym_bucket * rate.n_dbps,
            metric_dtype=viterbi_metric)
    return bits


@lru_cache(maxsize=None)
def _jit_decode_data_bucketed(rate_mbps: int, n_sym_bucket: int,
                              fxp: bool = False,
                              viterbi_window: int = None,
                              viterbi_metric: str = None,
                              viterbi_radix: int = None,
                              sco_track: bool = False,
                              fused_demap: bool = None):
    """Callers pass RESOLVED radix/sco/fused values (never None-
    meaning-env): the decode mode is part of the compile-cache key, so
    an in-process env change must re-trace (ADVICE r5 #1 discipline).
    ``fused_demap`` stays the LAST parameter — tests/test_lint.py's R1
    acceptance demo AST-drops it by position."""
    rate = RATES[rate_mbps]

    if fxp:
        from ziria_tpu.phy.wifi import rx_fxp

        def f(frame_q, n_bits_real):
            return rx_fxp.decode_data_bucketed_fxp(
                frame_q, rate, n_sym_bucket, n_bits_real)
    else:
        def f(frame, n_bits_real):
            return decode_data_bucketed(frame, rate, n_sym_bucket,
                                        n_bits_real, viterbi_window,
                                        viterbi_metric, viterbi_radix,
                                        fused_demap, sco_track)

    return jax.jit(f)


def _sym_bucket(n_sym: int) -> int:
    """Power-of-two symbol bucket (the floor keeps tiny frames in one
    compile class). Shared with the TX batch path (tx.encode_many
    buckets its symbol counts with the same rule, so a loopback's
    encode and decode geometries agree) — the rule itself lives on the
    Geometry object (utils/geometry; jaxlint R6 flags literal
    floors)."""
    from ziria_tpu.utils.geometry import DEFAULT
    return DEFAULT.sym_bucket(n_sym)


# ------------------------------------------------------- mixed-rate dispatch


def decode_data_mixed(frames, rate_idx, n_bits_real, n_sym_bucket: int,
                      viterbi_window: int = None,
                      viterbi_metric: str = None,
                      viterbi_radix: int = None,
                      interpret: bool = None,
                      sco_track: bool = False,
                      fused_demap: bool = None):
    """Mixed-rate batched DATA decode in ONE device dispatch — the
    compiled-program analogue of Ziria's in-language rate dispatch
    (the reference's `parsePLCPHeader ; per-rate loop` runs INSIDE the
    compiled receiver; SURVEY.md §3.4, §7 step 6).

    frames: (B, FRAME_DATA_START + 80*n_sym_bucket, 2) aligned,
    CFO-corrected frames padded to ONE common symbol bucket;
    rate_idx: (B,) int32 indices into RATE_MBPS_ORDER (traced);
    n_bits_real: (B,) int32 true data-bit counts (traced).
    Returns (B, t_max) descrambled bit streams, ``t_max =
    params.mixed_trellis_steps(n_sym_bucket)``; the caller slices each
    lane's PSDU out by its length.

    Geometry trick that makes one `lax.switch` serve all 8 rates: each
    per-rate branch runs only the CHEAP front end (FFT/equalize/demap/
    deinterleave/depuncture) at its own rate and pads the depunctured
    LLRs to the bucket's maximal trellis with zero-LLR erasures — the
    same "adds no likelihood" argument as the symbol-bucket padding,
    so the surviving path over each lane's real prefix is exactly its
    unpadded ML path. That trellis is the bucket at 54 Mbit/s
    (n_sym_bucket * MAX_DBPS) up to 152 symbols and stops there: the
    LENGTH field's 12 bits let no frame fill more than 152 x 216 =
    32 832 steps at any rate, so past that every row of every lane is
    an erasure by construction, and dropping erasures after the tail
    bits changes no bit before them. Each branch likewise demaps only
    the symbols that can hold t_max steps at its rate
    (`params.mixed_branch_symbols`), off ONE rate-independent
    `_front_symbols` over the whole bucket (on the chip 3.2 ms where
    eight fronts cut to their own symbols read 4.9 and whole-bucket
    rows sliced after the demap 15.2; PERF.md, PR 32). The EXPENSIVE
    Viterbi then runs once, rate-agnostic, over the whole mixed batch
    through the Pallas kernel, every lane riding the same 128-lane tiles:
    mixed traffic no longer fragments the hot kernel's batch. Under
    vmap the switch lowers to a select over the (cheap) front-end
    branches; the per-lane trellis work is never duplicated.

    vs the host-side bucketed path (`receive`): compile count for the
    DATA stage drops from O(rates x log lengths) to O(log lengths),
    and a mixed-rate batch costs ONE device call instead of one per
    rate group.

    ``viterbi_radix``/``viterbi_metric`` reach the shared Pallas ACS,
    so every mixed surface (receive_many, the streaming receiver, the
    fused link) inherits the faster core. ``fused_demap=True`` moves
    demap + deinterleave + depuncture into the kernel here too
    (ISSUE 20): the rate-SWITCHED fused prologue row-selects each
    lane's slot tables from one stacked all-rates constant bank
    (ops/viterbi_pallas.viterbi_decode_mixed_fused), the XLA front
    collapses from 8 per-rate branches to ONE rate-independent
    `_front_symbols` vmap, and the LLRs are produced and consumed in
    VMEM — the one rate-agnostic Viterbi this dispatch exists to
    share stays one kernel. Windowed/quantized modes fall back to the
    (bit-identical) unfused front, exactly like the known-rate path.
    """
    front, trellis, back = _mixed_stages(
        n_sym_bucket, viterbi_window, viterbi_metric, viterbi_radix,
        interpret, sco_track, fused_demap)
    rate_idx = jnp.asarray(rate_idx, jnp.int32)
    n_bits_real = jnp.asarray(n_bits_real, jnp.int32)
    return back(trellis(front(frames, rate_idx, n_bits_real),
                        rate_idx, n_bits_real))


def _mixed_stages(n_sym_bucket: int, viterbi_window, viterbi_metric,
                  viterbi_radix, interpret, sco_track, fused_demap):
    """`decode_data_mixed`'s three stages as functions of a batch of
    lanes, each under its scope: ``front(frames, rate_idx, n_bits_real)``
    -> what the trellis reads, a tuple of arrays that lead with the
    lane axis (the depunctured LLRs; under the fused front the
    equalized symbols and their gains); ``trellis(soft, rate_idx,
    n_bits_real, n_blocks=None)`` -> (B, t_max) decoded bits;
    ``back(bits)`` -> the descrambled rows. Every stage is lane-local,
    so a lane's values do not depend on the batch it rides in: the
    mixed decode runs the three over one batch, the streaming decode
    (`stream_decode_graph`) over the groups and tiles that hold a
    frame.

    ``n_blocks`` is a BOUND on the trellis, one count for each 128
    lanes of the batch, traced (`decode_bound`'s first value): the ACS
    and the traceback run that many blocks of `viterbi_pallas.UNROLL`
    steps and a lane's bits from there on read zero. The caller states
    that every lane under it is an erasure from there on (no
    ``n_bits_real`` of the 128 passes it); then every bit before a
    lane's ``n_bits_real`` is what the whole trellis gives, because the
    rows left out add no likelihood and the survivor through them
    leads back to the best state at the bound (`decode_data_mixed`'s
    argument for stopping at clause 18's longest frame, taken to the
    longest frame the tile holds). Absent, the whole trellis: the
    program the mixed decode has always traced. The windowed and the
    fused-demap kernels take no bound and run theirs whole."""
    t_max = mixed_trellis_steps(n_sym_bucket)
    # `rx.decode.front` / `.viterbi` / `.back` name the stages in the
    # device trace (docs/observability.md): metadata, no program change
    if fused_demap_enabled(fused_demap) \
            and _fused_front_applies(viterbi_window, viterbi_metric):
        def front(frames, rate_idx, n_bits_real):
            with jax.named_scope("rx.decode.front"):
                return jax.vmap(
                    lambda f: _front_symbols(f, n_sym_bucket,
                                             sco_track))(frames)

        def trellis(soft, rate_idx, n_bits_real, n_blocks=None):
            data, gain = soft
            with jax.named_scope("rx.decode.viterbi"):
                # the fused kernel still runs the bucket's whole trellis
                # (ROADMAP S2); its rows past t_max are the same erasures
                return viterbi_pallas.viterbi_decode_mixed_fused(
                    data, gain, rate_idx, n_bits_real,
                    radix=viterbi_radix, interpret=interpret)[:, :t_max]
    else:
        def _branch(rate):
            n_sym = mixed_branch_symbols(n_sym_bucket, rate)

            def f(frame):
                # the SAME whole-bucket expression in all eight
                # branches, so XLA computes it once a lane; what
                # differs by rate starts at the demap, over the
                # symbols that can hold t_max steps at this rate
                data, gain = _front_symbols(frame, n_sym_bucket,
                                            sco_track)
                dep = _demap_symbols(data[:n_sym], gain, rate)[:t_max]
                return jnp.pad(dep, ((0, t_max - dep.shape[0]), (0, 0)))
            return f

        def front(frames, rate_idx, n_bits_real):
            with jax.named_scope("rx.decode.front"):
                branches = [_branch(RATES[m]) for m in RATE_MBPS_ORDER]
                dep = jax.vmap(
                    lambda f, r: jax.lax.switch(r, branches, f))(
                        frames, rate_idx)
                # rows at/after each lane's true bit count become
                # erasures (covers both the in-rate bucket pad and the
                # cross-rate pad to MAX_DBPS)
                t = jnp.arange(t_max)
                return (jnp.where(
                    (t[None, :] < n_bits_real[:, None])[..., None],
                    dep, 0.0),)

        def trellis(soft, rate_idx, n_bits_real, n_blocks=None):
            with jax.named_scope("rx.decode.viterbi"):
                return viterbi_pallas.viterbi_decode_batch_opt(
                    soft[0], window=viterbi_window,
                    metric_dtype=viterbi_metric, radix=viterbi_radix,
                    interpret=interpret, n_blocks=n_blocks)

    def _descramble(b):
        seed = scramble.recover_seed(b[:7])
        return scramble.descramble_bits(b, seed)

    def back(bits):
        with jax.named_scope("rx.decode.back"):
            return jax.vmap(_descramble)(bits)

    return front, trellis, back


def crc_psdu_many_graph(clear_b, n_psdu_bits):
    """Batched FCS check over the mixed decode's output: for each lane
    of `clear_b` (B, mixed_trellis_steps(n_sym_bucket) descrambled bits)
    with `n_psdu_bits` (B,) traced true PSDU bit counts, True iff the
    PSDU's trailing 32 bits are the CRC-32 of the rest — ONE vmapped
    loop-free check at the common bucket (`ops/crc.check_crc32_masked`:
    two GF(2) products and a table look-up), boolean-identical lane
    for lane to a host `check_crc32` per lane. The whole row goes in,
    SERVICE bits masked by position; the check pads it to whole blocks
    of its own (the served row, 152 x 216 = 32 832 bits, to 33 blocks
    of 1024). Traced, so the fused loopback link inlines it after the
    decode."""
    from ziria_tpu.ops.crc import check_crc32_masked

    with jax.named_scope("rx.decode.back"):
        return jax.vmap(
            lambda b, n: check_crc32_masked(b, n, lo=N_SERVICE_BITS))(
                clear_b, jnp.asarray(n_psdu_bits, jnp.int32))


@lru_cache(maxsize=None)
def _jit_crc_many():
    """ONE jitted batched FCS check serving every (lane count, bucket)
    geometry (jit retraces per shape)."""
    return jax.jit(crc_psdu_many_graph)


@lru_cache(maxsize=None)
def _jit_decode_data_mixed(n_sym_bucket: int, viterbi_window: int = None,
                           viterbi_metric: str = None,
                           viterbi_radix: int = None,
                           sco_track: bool = False,
                           fused_demap: bool = False):
    """ONE jit per (symbol bucket, decode mode) serving ALL rates —
    the decode-mode knobs (window, metric, radix, sco_track,
    fused_demap) are part of the cache key, so an in-process change
    can never silently reuse the other mode's trace (ADVICE r5 #1
    discipline; callers pass RESOLVED radix/sco/fused values, never
    None-meaning-env). ``fused_demap`` stays the LAST parameter —
    tests/test_lint.py's R1 acceptance demo AST-drops it by
    position."""
    def f(frames, rate_idx, n_bits_real):
        return decode_data_mixed(frames, rate_idx, n_bits_real,
                                 n_sym_bucket, viterbi_window,
                                 viterbi_metric, viterbi_radix,
                                 sco_track=sco_track,
                                 fused_demap=fused_demap)
    return jax.jit(f)


# ------------------------------------------------------ frame acquisition
#
# Two structurally-identical paths share one decision tree:
#  - `_acquire_frame`: the per-capture oracle (host-driven, 2 fixed-
#    shape jits + one eager CFO rotation per capture);
#  - `acquire_many`: the whole front end for N captures as ONE vmapped
#    dispatch (`acquire_frame_graph` under vmap), the host reduced to
#    integer header parsing between dispatches.
# Lane-for-lane bit-identity between them is the pinned contract
# (tests/test_rx_batched_acquire.py).


@lru_cache(maxsize=None)
def _jit_sync_fn():
    """jit(sync_frame), built once. `lru_cache` (not a checked global)
    so concurrent first calls from `framebatch` worker threads can
    never observe a half-initialized pair; a racing duplicate build is
    harmless — one value wins the cache and both are valid."""
    return jax.jit(sync_frame)


@lru_cache(maxsize=None)
def _jit_signal_fn():
    return jax.jit(decode_signal)


class _Acquired(NamedTuple):
    """A detected, SIGNAL-parsed capture, ready for a DATA decode."""
    frame_np: np.ndarray        # samples from the frame start (f32)
    avail: int                  # true capture samples past the start
    eps: float                  # CFO estimate
    rate_mbps: int
    length_bytes: int
    n_sym: int


def _stream_bucket(n: int) -> int:
    """Power-of-two capture bucket: the ONE padding formula the
    per-capture and batched acquisition paths share — their
    bit-identity contract assumes identical padded geometry rules.
    The rule (and its floor) lives on the Geometry object
    (utils/geometry; jaxlint R6 flags literal floors)."""
    from ziria_tpu.utils.geometry import DEFAULT
    return DEFAULT.capture_bucket(n)


def _bucket_pad(x: np.ndarray):
    """Pad a capture to its power-of-two bucket so the sync/acquire
    jits compile once per bucket, not once per stream length (zeros
    are inert to detection). Returns (padded, n_valid)."""
    n_valid = x.shape[0]
    bucket = _stream_bucket(n_valid)
    if bucket != n_valid:
        x = np.concatenate(
            [x, np.zeros((bucket - n_valid, 2), np.float32)], axis=0)
    return x, n_valid


def _classify_acquire(found: bool, avail: int, rate_bits: int,
                      length_bytes: int, parity_ok: bool):
    """The shared host decision tree over acquisition outputs — all
    integer/bool parsing, no device work. Returns (RxResult, None) on
    any failure, (None, (rate_mbps, n_sym)) for a decodable frame.

    All length checks use the true capture length — decoding padding
    zeros as DATA must fail, not silently "succeed".
    `classify_acquire_graph` is the traced twin the fused loopback
    link runs on-device; their branch-for-branch agreement is pinned
    by tests/test_link_fused.py."""
    fail = RxResult(False, 0, 0, np.zeros(0, np.uint8), None)
    if not found or avail < 400 or not parity_ok:
        return fail, None
    rate_mbps = SIGNAL_BITS_TO_MBPS.get(rate_bits)
    if rate_mbps is None:
        return fail, None
    n_sym = n_symbols(length_bytes, RATES[rate_mbps])
    if avail < FRAME_DATA_START + 80 * n_sym:
        return RxResult(False, rate_mbps, length_bytes,
                        np.zeros(0, np.uint8), None), None
    return None, (rate_mbps, n_sym)


# 16-entry lookup tables over the 4-bit SIGNAL RATE field: mbps (0 for
# the 8 invalid codes) and n_dbps — what lets `classify_acquire_graph`
# run `SIGNAL_BITS_TO_MBPS.get` + `n_symbols` as traced integer ops
_RB_TO_MBPS = np.zeros(16, np.int32)
_RB_TO_DBPS = np.zeros(16, np.int32)
for _rb, _m in SIGNAL_BITS_TO_MBPS.items():
    _RB_TO_MBPS[_rb] = _m
    _RB_TO_DBPS[_rb] = RATES[_m].n_dbps

# classification codes shared by the traced tree and its host readers
ACQ_FAIL, ACQ_TRUNCATED, ACQ_DECODABLE = 0, 1, 2


def classify_acquire_graph(found, avail, rate_bits, length_bytes,
                           parity_ok):
    """The traced twin of `_classify_acquire` — the same pure-integer
    decision tree as jnp ops, so the fused loopback link keeps it
    on-device (no acquisition metadata crosses the host link mid-
    batch). All inputs traced, elementwise over any batch shape.

    Returns ``(status, rate_mbps, length_bytes, n_sym)``:
    status `ACQ_FAIL` (no detect / short capture / bad parity /
    unknown rate; rate/length forced 0 exactly as the host tree's fail
    RxResult), `ACQ_TRUNCATED` (SIGNAL parsed but the capture can't
    hold the claimed DATA field; rate/length are the parsed values),
    or `ACQ_DECODABLE`."""
    rb = jnp.asarray(rate_bits, jnp.uint32) & 15
    mbps = jnp.asarray(_RB_TO_MBPS)[rb]
    dbps = jnp.asarray(_RB_TO_DBPS)[rb]
    avail = jnp.asarray(avail, jnp.int32)
    length_bytes = jnp.asarray(length_bytes, jnp.int32)
    known = (jnp.asarray(found, bool) & (avail >= 400)
             & jnp.asarray(parity_ok, bool) & (mbps > 0))
    n_bits = N_SERVICE_BITS + 8 * length_bytes + N_TAIL_BITS
    n_sym = (n_bits + dbps - 1) // jnp.maximum(dbps, 1)
    fits = avail >= FRAME_DATA_START + 80 * n_sym
    status = jnp.where(known,
                       jnp.where(fits, ACQ_DECODABLE, ACQ_TRUNCATED),
                       ACQ_FAIL)
    zero = jnp.zeros_like(mbps)
    return (jnp.asarray(status, jnp.int32),
            jnp.where(known, mbps, zero),
            jnp.where(known, length_bytes, zero),
            jnp.where(known, n_sym, zero))


def _acquire_frame(samples, max_samples: int = 1 << 16):
    """Detect/align/CFO-correct a capture and parse its SIGNAL field:
    the per-capture acquisition front of `receive` — and the single-
    lane oracle of the batched `acquire_many`. Returns (RxResult,
    None) on any failure, (None, _Acquired) on success."""
    from ziria_tpu.utils import dispatch, programs

    x, n_valid = _bucket_pad(
        np.asarray(samples, np.float32)[:max_samples])
    sync_fn = _jit_sync_fn()
    programs.note_site("rx.sync", sync_fn, x)
    with dispatch.timed("rx.sync"):
        found, start, eps = sync_fn(x)
    found = bool(np.asarray(found))
    start = int(np.asarray(start))
    eps = float(np.asarray(eps))
    avail = n_valid - start
    rate_bits = length_bytes = 0
    parity_ok = False
    if found and avail >= 400:
        # CFO-correct only fixed-size regions so device code caches:
        # the 400-sample head now, the (rate, n_sym)-sized data region
        # after the SIGNAL parse (both slices start at the frame
        # start, keeping the rotation phase-continuous)
        with dispatch.timed("rx.cfo_head"):
            head = sync.correct_cfo(jnp.asarray(x[start:start + 400]),
                                    eps)
        sig_fn = _jit_signal_fn()
        programs.note_site("rx.signal", sig_fn, head)
        with dispatch.timed("rx.signal"):
            rb, ln, pk = sig_fn(head)
        rate_bits = int(np.asarray(rb))
        length_bytes = int(np.asarray(ln))
        parity_ok = bool(np.asarray(pk))
    res, ok = _classify_acquire(found, avail, rate_bits, length_bytes,
                                parity_ok)
    if ok is None:
        return res, None
    rate_mbps, n_sym = ok
    return None, _Acquired(x[start:], avail, eps, rate_mbps,
                           length_bytes, n_sym)


def acquire_frame_graph(x, n_valid, limit):
    """Fully-traceable single-capture acquisition: STS detect, LTS
    peak-pick, coarse+fine CFO, on-device frame alignment
    (`lax.dynamic_slice` at the traced start), CFO rotation of the
    400-sample head, and the SIGNAL decode — fused into ONE graph.

    x: (L, 2) bucket-padded capture; n_valid: true capture length
    (traced int32); limit: the lane's OWN power-of-two bucket (traced
    int32) — caps detection/peak-pick positions so a lane padded past
    its own bucket to the batch's common one evaluates exactly the
    positions the per-capture path does (sync.locate_frame). Returns
    per-lane (found, start, eps, rate_bits, length, parity_ok) —
    `found` already folds in the >= 400-sample availability gate, so
    every downstream field of a not-found lane is garbage-by-
    construction and masked by the host decision tree. Under `vmap`
    this is the whole acquisition front end of a batch in one
    dispatch."""
    detected, start, eps = sync.locate_frame(x, limit=limit)
    avail = n_valid - start
    head = jax.lax.dynamic_slice(x, (start, jnp.int32(0)), (400, 2))
    head = sync.correct_cfo(head, eps)
    rate_bits, length, parity_ok = decode_signal(head)
    found = jnp.logical_and(detected, avail >= 400)
    return found, start, eps, rate_bits, length, parity_ok


@lru_cache(maxsize=None)
def _jit_acquire_many():
    """ONE jitted vmap of the acquisition graph serves every
    (lane count, bucket) geometry (jit retraces per shape)."""
    return jax.jit(jax.vmap(acquire_frame_graph))


class _LaneAcq(NamedTuple):
    """A decodable lane of a batched acquisition: everything the
    gather+decode dispatches need, as host integers/floats."""
    row: int                    # row in the padded capture batch
    start: int
    eps: float
    avail: int
    rate_mbps: int
    length_bytes: int
    n_sym: int


def acquire_batch(x_dev, n_valid, limits, n_lanes: int):
    """Batched acquisition over an ALREADY device-resident capture
    batch: ONE vmapped dispatch + the host integer decision tree.

    x_dev: (R, L, 2) device array, R a power-of-two lane count and L
    a power-of-two capture bucket, rows past the real lanes repeating
    row 0 (the `utils/dispatch.pad_lanes` rule); n_valid/limits: (R,)
    int arrays (true capture lengths and per-lane own-bucket caps for
    the detector). The first `n_lanes` rows are real. Returns
    (results, lanes) as `acquire_many` does. This is the entry the
    device-resident loopback link uses — the TX/channel output feeds
    acquisition without ever crossing the host link."""
    from ziria_tpu.utils import dispatch, programs

    acq_fn = _jit_acquire_many()
    acq_args = (x_dev, jnp.asarray(n_valid, jnp.int32),
                jnp.asarray(limits, jnp.int32))
    programs.note_site("rx.acquire_many", acq_fn, *acq_args)
    with dispatch.timed("rx.acquire_many"):
        found_b, start_b, eps_b, rb_b, ln_b, pk_b = acq_fn(*acq_args)
    found_b = np.asarray(found_b)
    start_b = np.asarray(start_b)
    eps_b = np.asarray(eps_b)
    rb_b = np.asarray(rb_b)
    ln_b = np.asarray(ln_b)
    pk_b = np.asarray(pk_b)
    n_valid = np.asarray(n_valid)

    results = [None] * n_lanes
    lanes = []
    for i in range(n_lanes):
        start = int(start_b[i])
        avail = int(n_valid[i]) - start
        res, ok = _classify_acquire(bool(found_b[i]), avail,
                                    int(rb_b[i]), int(ln_b[i]),
                                    bool(pk_b[i]))
        if ok is None:
            results[i] = res
            continue
        rate_mbps, n_sym = ok
        lanes.append((i, _LaneAcq(i, start, float(eps_b[i]), avail,
                                  rate_mbps, int(ln_b[i]), n_sym)))
    return results, lanes


def acquire_many(captures, max_samples: int = 1 << 16):
    """Batched acquisition front end: N captures -> per-lane
    (found, start, eps, rate_bits, length, parity_ok) in ONE device
    dispatch, then the host decision tree (integer parsing only).

    Returns (results, x_dev, lanes): `results[i]` is the failure
    RxResult for undecodable lanes and None for decodable ones,
    `x_dev` is the (N_pow2, L, 2) bucket-padded capture batch as the
    DEVICE array the acquire dispatch already uploaded (kept resident
    so the gather dispatch slices data regions without a second trip
    through the host link), `lanes` is [(i, _LaneAcq)] for the
    decodable lanes. Lane-for-lane, the classification and every
    parsed field are bit-identical to per-capture `_acquire_frame`."""
    from ziria_tpu.utils.dispatch import pow2_ceil

    if not len(captures):
        return [], jnp.zeros((0, 0, 2), jnp.float32), []
    xs = [np.asarray(s, np.float32)[:max_samples] for s in captures]
    n_valid = np.asarray([x.shape[0] for x in xs], np.int32)
    # ONE common bucket for the whole batch (zeros are inert to the
    # detector and to the conv outputs at real-sample positions, so a
    # longer pad does not change any lane's values), and lane counts
    # pad to a power of two (lane 0 repeated) so XLA compiles O(log N)
    # batch variants
    bucket = _stream_bucket(int(n_valid.max()))
    n_lanes = len(xs)
    n_rows = pow2_ceil(n_lanes)
    x_pad = np.zeros((n_rows, bucket, 2), np.float32)
    for i, x in enumerate(xs):
        x_pad[i, :x.shape[0]] = x
    if n_lanes < n_rows:
        x_pad[n_lanes:] = x_pad[0]
    nv_pad = np.full((n_rows,), n_valid[0], np.int32)
    nv_pad[:n_lanes] = n_valid
    # each lane's OWN bucket caps its detect/peak-pick positions so
    # sharing a longer common bucket cannot expose tail windows the
    # per-capture path never evaluates (sync.locate_frame's limit)
    limits = np.asarray([_stream_bucket(int(v)) for v in nv_pad],
                        np.int32)

    x_dev = jnp.asarray(x_pad)
    results, lanes = acquire_batch(x_dev, nv_pad, limits, n_lanes)
    return results, x_dev, lanes


def gather_segment_graph(x, start, eps, avail, n_sym_bucket: int):
    """One lane of the batched "gather+derotate" graph: slice the
    frame region at the lane's own (traced) start, zero everything
    past its true available samples, and apply its own CFO phase —
    the traced twin of `_padded_segment`, fused for the whole batch
    under vmap. `x` must be padded so start + need_b never clamps."""
    need_b = FRAME_DATA_START + 80 * n_sym_bucket
    seg = jax.lax.dynamic_slice(x, (start, jnp.int32(0)), (need_b, 2))
    n = jnp.minimum(avail, need_b)
    seg = jnp.where((jnp.arange(need_b) < n)[:, None], seg, 0.0)
    with jax.named_scope("rx.scan.gather.derotate"):
        return sync.correct_cfo(seg, eps)


@lru_cache(maxsize=None)
def _jit_gather_segments(n_sym_bucket: int):
    """ONE jitted gather per symbol bucket (shapes retrace per
    (lane count, capture bucket) pair). The row gather and the tail
    pad both happen INSIDE the jit, on the device-resident capture
    batch the acquire dispatch uploaded — the batch never crosses the
    host link a second time."""
    need_b = FRAME_DATA_START + 80 * n_sym_bucket

    def f(x_all, rows, start, eps, avail):
        # tail-pad so start + need_b is always in bounds:
        # dynamic_slice clamps out-of-range starts, which would
        # silently shift a lane
        x = jnp.pad(x_all[rows], ((0, 0), (0, need_b), (0, 0)))
        return jax.vmap(
            lambda xi, s, e, a: gather_segment_graph(
                xi, s, e, a, n_sym_bucket))(x, start, eps, avail)

    return jax.jit(f)


def gather_segments_many(x_dev, lanes, n_sym_bucket: int):
    """Slice every decodable lane's data region at its own offset and
    apply its own CFO rotation at the common symbol bucket — ONE
    device dispatch over the device-resident capture batch from
    `acquire_many`; output stays on device for the mixed-rate decode.
    `lanes` rows must already be padded to the target lane count
    (repeat the first entry, like every batch path here)."""
    from ziria_tpu.utils import dispatch, programs

    gather_fn = _jit_gather_segments(n_sym_bucket)
    gather_args = (
        x_dev,
        jnp.asarray([la.row for la in lanes], jnp.int32),
        jnp.asarray([la.start for la in lanes], jnp.int32),
        jnp.asarray([la.eps for la in lanes], jnp.float32),
        jnp.asarray([la.avail for la in lanes], jnp.int32))
    programs.note_site("rx.gather", gather_fn, *gather_args)
    with dispatch.timed("rx.gather"):
        return gather_fn(*gather_args)


def _padded_segment(acq: _Acquired, n_sym_bucket: int):
    """The acquired frame's data region padded to `n_sym_bucket`
    symbols and CFO-corrected: the fixed-geometry device input of the
    bucketed and mixed-rate DATA decodes. Per-lane host path — the
    batched `gather_segments_many` produces the identical values for
    a whole batch in one dispatch."""
    from ziria_tpu.utils import dispatch

    need_b = FRAME_DATA_START + 80 * n_sym_bucket
    frame_pad = np.zeros((need_b, 2), np.float32)
    n = min(acq.avail, need_b)
    frame_pad[:n] = acq.frame_np[:n]
    with dispatch.timed("rx.cfo_segment"):
        return sync.correct_cfo(jnp.asarray(frame_pad), acq.eps)


# ------------------------------------------------------ streaming receiver
#
# The per-chunk device half of `backend/framebatch.receive_stream`:
# ONE jitted graph turns a long multi-frame chunk into K dense
# candidate lanes — multi-peak detect (`ops/sync.locate_frames`),
# per-candidate windows at the traced aligned starts (a bound each,
# sliced from the chunk where they are read and never cut out), the
# vmapped per-window acquisition (`acquire_frame_graph`, the SAME
# graph the batched per-capture path runs, so every window decodes
# bit-identically to `receive` over that window), and the
# gather+derotate at ONE fixed symbol bucket. A second fixed-geometry
# jit decodes the chunk's decodable lanes (mixed-rate switch + masked
# CRC). Between the two sits only the integer `_classify_acquire`
# tree — the blind receive's genuinely data-dependent step.


def _stream_bucket_graph(n_valid, cap: int):
    """Traced twin of `_stream_bucket` (power-of-two capture bucket,
    floor 512) for per-lane true sample counts up to the static window
    length `cap` — the streaming windows share one common buffer, so
    each lane's detector cap must be ITS OWN bucket for bit-identity
    with per-capture `receive` (the `acquire_many` limit rule). The
    unrolled compare ladder is exact where float log2 would not be;
    `tests/test_rx_stream.py` pins it against the host rule."""
    b = jnp.full(jnp.shape(n_valid), 512, jnp.int32)
    m = 512
    while m < cap:
        m *= 2
        b = jnp.where(jnp.asarray(n_valid) > m // 2, m, b)
    return b


def _acquire_head(win_len: int) -> int:
    """How many samples of a window that begins AT an aligned frame start
    its acquisition reads (`stream_chunk_graph` step 4): the chunk scan
    found the start already, so `acquire_frame_graph` re-derives it
    from the window's head and everything it computes further in is
    discarded by its own first-crossing `argmax` and local peak mask.

    `sync._align_lts` puts the start at most LTS_OFFSET + ALIGN_BACK
    (224) below the plateau crossing it was given, so the window's
    FIRST crossing lies at or below 224. Its peak-pick then reads
    `pair` below crossing - ALIGN_BACK + ALIGN_SPAN (608: ALIGN_BACK
    cancels), each value from LTS_PAIR_SPAN samples: 736 for the
    timing. The start it finds is below 608 - LTS_OFFSET (416), and
    the CFO head (320) and SIGNAL head (FRAME_DATA_START) are sliced
    there: 816. The larger, as a power of two (1024 today), clipped to
    the window: one line for every geometry, and
    `tests/test_rx_acquire_head.py` pins it against `sync`'s
    constants. A lane whose first crossing lies further in (a false
    plateau that ended before the start it aligned) reads as not
    found, where a whole-window scan locked onto a later frame."""
    from ziria_tpu.utils.dispatch import pow2_ceil

    pick_end = sync.LTS_OFFSET + sync.ALIGN_SPAN
    timing = pick_end + sync.LTS_PAIR_SPAN
    heads = pick_end - sync.LTS_OFFSET + FRAME_DATA_START
    return min(pow2_ceil(max(timing, heads)), win_len)


#: A candidate's carrier offset rides to the host in the 16 bits above
#: its SIGNAL RATE field (four bits of a uint32), so that an operator
#: can see a session's radio drift toward the estimators' ranges without
#: a byte more in the scan's pull: an int16 in units of 2**-17
#: rad/sample (24 Hz at 20 MS/s), range +-0.25, past the coarse
#: estimator's pi / 16.
CFO_WORD_SCALE = float(1 << 17)


def pack_rate_word(rate_bits, eps):
    """The scan's rate word: `rate_bits` (uint32, below 16) with `eps`
    quantized into its upper half (traced; `unpack_rate_word` is the
    host's reading of it)."""
    q = jnp.clip(jnp.round(eps * CFO_WORD_SCALE), -32768.0, 32767.0)
    return rate_bits | (jax.lax.bitcast_convert_type(
        q.astype(jnp.int32), jnp.uint32) << 16)


def unpack_rate_word(word):
    """Host side of `pack_rate_word`: ``(rate_bits, cfo_urad)`` as
    int32 arrays, the offset in micro-radians a sample (rounded from
    the word's 7.6 a step)."""
    word = np.asarray(word, np.uint32)
    q = (word >> 16).astype(np.uint16).view(np.int16)
    return (word & 15).astype(np.int32), np.rint(
        q * (1e6 / CFO_WORD_SCALE)).astype(np.int32)


#: Candidates a lane that `_gather_in_groups` takes at a time. Two: the
#: loop that slices a group's segments then writes 2 x S rows, which
#: the chip's compiler lays out as the chunk is (`{1,2,0:T(2,128)}`,
#: 0.15 ms for 64 slots); one candidate a lane is S rows, a lane each,
#: which it lays out as planar rows written a sublane at a time (1.27
#: ms for the same 64: PERF.md, PR 45).
GATHER_GROUP = 2


def _gather_in_groups(one, args, k: int, need_b: int):
    """``jax.vmap(one)(*args)`` over K candidates, `GATHER_GROUP` at a
    time, each group's segments written in place into the (K, need_b,
    2) batch: what the mask and the derotation hold between the slice
    and the batch is then a group's, which the chip keeps in its fast
    memory, and not a second array of the batch's size in HBM (168 MB
    at K = 32, and at K = 16 with the window of 131 072). Values are
    the vmap's, bit for bit."""
    g = GATHER_GROUP
    groups = -(-k // g)
    args = [jnp.pad(a, (0, groups * g - k)) for a in args]

    def body(j, batch):
        part = jax.vmap(one)(*[
            jax.lax.dynamic_slice(a, (j * g,), (g,)) for a in args])
        return jax.lax.dynamic_update_slice(batch, part, (j * g, 0, 0))

    batch = jnp.zeros((groups * g, need_b, 2), jnp.float32)
    return jax.lax.fori_loop(0, groups, body, batch)[:k]


def stream_chunk_graph(chunk, chunk_valid, own_lo, own_hi, k: int,
                       win_len: int, n_sym_bucket: int,
                       threshold: float = 0.75, min_run: int = 33,
                       dead_zone: int = 320):
    """One streaming chunk, fully traced (dispatch 1 of 2 per chunk):

    1. `sync.locate_frames`: up to `k` exact frame starts (plateau
       gate, dead-zone suppression, local LTS alignment) over the
       chunk's `chunk_valid` real samples.
    2. ownership mask: only starts in ``[own_lo, own_hi)`` are this
       chunk's (`own_hi` = the chunk stride, or the valid length on
       the final chunk; `own_lo` = 0 except on the STREAM's first
       chunk, where -192 admits a head-truncated preamble whose LTS
       peak-pick lands below the 192-sample offset — per-capture
       `locate_frame` clamps such a start to 0 and still reports,
       and so must we; on later chunks a negative start is a frame
       owned by the PREVIOUS chunk). Boundary-straddling frames
       re-detect fully inside the NEXT chunk's overlap and are owned
       exactly once.
    3. the per-candidate window, as a bound and not as an array: the
       traced starts clamped to 0 exactly as `locate_frame` clamps,
       and each candidate's true count `n_valid` = the chunk's real
       samples from its start on, at most `win_len` (the window IS
       the capture the per-capture oracle would see for
       `stream[max(start,0) : +win_len]`; `win_len` bounds what a
       slot may hold, and the truncation verdict reads that count).
       Nothing is cut: every sample steps 4 and 5 keep is a sample of
       the chunk, so they slice the chunk, tail-padded once by what
       the two reads reach past its end (a window array is S x K x
       `win_len` samples written and read back a step: 134 MB at 256
       x 65 536, a third of that scan's time on the chip; PR 44).
    4. the vmapped per-window acquisition (detect gate, LTS timing,
       CFO, SIGNAL decode) with per-lane true counts and own-bucket
       detector caps, over the window's first `_acquire_head` samples
       (`dynamic_slice` of the chunk at the start) — all it reads of
       a window that starts at its frame; a whole-window scan there
       located every frame a second time, 36 ms of a chunk-step at 64
       windows x 65 536 (chip runs, PR 30) — and
    5. gather+derotate of every candidate's data region at the ONE
       static symbol bucket, sliced from the chunk at start + the
       frame start step 4 found and zeroed from the window's true
       count on (garbage on failed lanes, masked host-side).

    Returns ``(own, starts, overflow, found, fstart, eps, rate_word,
    length, parity_ok, n_valid, segs)`` — everything before `segs` is
    K scalars per lane (one host transfer; `starts` already clamped;
    `rate_word` is `pack_rate_word`'s: the RATE bits, and `eps` to 24
    Hz above them for the host, which pulls every scalar but the
    float `eps`), `segs` stays device-resident for the decode
    dispatch."""
    # overflow scan cap: the scan sees plateau CROSSING indices, and a
    # frame aligned at start s can cross as late as s + 224 (the
    # alignment window spans [d-32, d+384) and start = peak - 192, so
    # s >= d - 224). Capping at own_hi + 224 therefore counts every
    # surplus frame THIS chunk owns (never a silent drop), at the cost
    # of flagging deferred frames in a 224-sample sliver past the
    # bound — the conservative side for a widen-K diagnostic.
    #
    # The four `rx.scan.*` scopes name the stages in the device trace
    # (docs/observability.md); they are metadata and change no program.
    with jax.named_scope("rx.scan.locate"):
        found, starts, overflow = sync.locate_frames(
            chunk, k, limit=chunk_valid, threshold=threshold,
            min_run=min_run, dead_zone=dead_zone,
            overflow_limit=own_hi + sync.LTS_OFFSET + sync.ALIGN_BACK)
    with jax.named_scope("rx.scan.window"):
        own = found & (starts >= own_lo) & (starts < own_hi)
        starts = jnp.where(own, jnp.maximum(starts, 0), starts)
        # a candidate's window is chunk[safe : safe + win_len], and it
        # is never cut: steps 4 and 5 slice what they read of it from
        # the chunk. Tail-pad once so that neither slice clamps (a
        # final-chunk start may sit at the chunk's very end, fstart <
        # head, and the stream genuinely ends there: the zero tail is
        # exactly the oracle slice's bucket pad); clamping a slice
        # instead would silently shift the lane
        safe = jnp.clip(starts, 0, chunk.shape[0])
        head = _acquire_head(win_len)
        need_b = FRAME_DATA_START + 80 * n_sym_bucket
        chunk_pad = jnp.pad(chunk, ((0, head + need_b), (0, 0)))
        nv = jnp.clip(jnp.asarray(chunk_valid, jnp.int32) - safe,
                      0, win_len).astype(jnp.int32)
        lim = _stream_bucket_graph(nv, win_len)
    with jax.named_scope("rx.scan.acquire"):
        # nv stays the window's true count: `found`'s avail gate reads it
        heads = jax.vmap(lambda s: jax.lax.dynamic_slice(
            chunk_pad, (s, jnp.int32(0)), (head, 2)))(safe)
        f2, fstart, eps, rb, ln, pk = jax.vmap(acquire_frame_graph)(
            heads, nv, jnp.minimum(lim, head))
        rb = pack_rate_word(rb, eps)
    with jax.named_scope("rx.scan.gather"):
        # avail <= win_len - fstart, so the segment's mask ends at the
        # window's bound wherever the chunk goes on past it
        segs = _gather_in_groups(
            lambda s, e, a: gather_segment_graph(chunk_pad, s, e, a,
                                                 n_sym_bucket),
            (safe + fstart, eps, nv - fstart), k, need_b)
    return own, starts, overflow, f2, fstart, eps, rb, ln, pk, nv, segs


# ---------------------------------------------- the streaming programs
#
# The two compiled programs of the streaming receiver
# (backend/framebatch.MultiStreamReceiver; a lone stream is S = 1): S
# independent I/Q streams' chunks ride a LEADING STREAM AXIS through
# the per-lane graphs (`stream_chunk_graph` under one vmap; the mixed
# decode over the flattened (S*K) lane axis), so an entire fleet of
# streams runs on TWO compiled programs and <= 2 dispatches per
# chunk-step — Ziria's `|>>>|` stage placement re-expressed as a mesh
# axis. With a `mesh`, both programs wrap in `jax.shard_map` over the
# dp stream axis: an identical per-device
# program per shard of streams, no collectives (streams are
# independent), multihost-ready through parallel/multihost.build_mesh.


def multi_stream_chunk_graph(chunks, valid, own_lo, own_hi, k: int,
                             win_len: int, n_sym_bucket: int,
                             threshold: float = 0.75, min_run: int = 33,
                             dead_zone: int = 320):
    """`stream_chunk_graph` over a leading stream axis: `chunks`
    (S, chunk_len, 2) stacked per-stream windows, `valid`/`own_lo`/
    `own_hi` (S,) per-stream scalars (an idle lane rides `valid == 0`
    — the detector's position cap masks it to zero candidates, the
    valid-mask of the host packer). Per lane, values are the one-
    stream graph's values by construction — the vmap adds the stream
    axis, nothing else — which is what makes a fleet lane bit-
    identical to that stream received alone."""
    return jax.vmap(
        lambda c, v, lo, hi: stream_chunk_graph(
            c, v, lo, hi, k, win_len, n_sym_bucket, threshold,
            min_run, dead_zone))(chunks, valid, own_lo, own_hi)


@lru_cache(maxsize=None)
def _jit_stream_chunk_multi(k: int, win_len: int, n_sym_bucket: int,
                            threshold: float = 0.75, min_run: int = 33,
                            dead_zone: int = 320, mesh=None,
                            axis: str = "dp"):
    """ONE compiled S-stream chunk scan per (K, window, symbol bucket,
    detector params, mesh) — stream count and chunk length retrace per
    shape, so a fleet of uniform chunk-steps compiles ONCE. With a
    `mesh`, the graph wraps in shard_map over the leading stream axis
    (`parallel/batch.stream_specs` placement): each
    device runs the identical per-shard program over its S/n streams.
    `mesh` is part of the lru key (a Mesh hashes by device layout), so
    sharded and unsharded fleets never share a trace."""
    def stream_chunk_multi(chunks, valid, own_lo, own_hi):
        return multi_stream_chunk_graph(chunks, valid, own_lo, own_hi,
                                        k, win_len, n_sym_bucket,
                                        threshold, min_run, dead_zone)

    if mesh is None:
        return jax.jit(stream_chunk_multi)
    from ziria_tpu.parallel.batch import stream_specs
    # outputs: own/starts (S,K), overflow (S,), 7x per-lane (S,K)
    # scalars, segs (S,K,need_b,2) — every one leads with the stream
    # axis, so the specs are rank-driven. check_vma=False: the
    # detector's scan starts its carry from an unvarying constant and
    # returns it varying over dp, which the varying-axes check refuses
    # to trace; nothing here is replicated, as in the decode twin
    return jax.jit(jax.shard_map(
        stream_chunk_multi, mesh=mesh,
        in_specs=stream_specs((3, 1, 1, 1), axis),
        out_specs=stream_specs((2, 2, 1) + (2,) * 7 + (4,), axis),
        check_vma=False))


#: Live slots in a group of the streaming decode's walk (`stream_
#: decode_graph`): the unit it accounts in, and the least it fronts. A
#: tile of the walk fronts one group or all of its groups at once,
#: because on the chip a front is some eighty small ops whose cost
#: hardly falls under 32 slots (1.7, 2.1, 2.9 ms for 8, 16, 32 at the
#: 1024-symbol bucket; 6.9 for 128), so one trip at a size that holds
#: the live slots beats several small ones, and every size is a front
#: the program compiles (PERF.md, PR 46: the sweep).
DECODE_GROUP = 32


def decode_walk(n_live, n_slots: int):
    """What the streaming decode computes for `n_live` live slots of a
    batch of `n_slots`, in slots: ``(fronted, decoded)``. It takes the
    packed live slots a tile at a time, a tile the ACS kernel's 128
    lanes or the whole groups of `DECODE_GROUP` that already hold the
    batch, and stops at the last tile that holds one. `decoded`: the
    lanes of those tiles, which the ACS, the traceback, the descrambler
    and the FCS check run whole. `fronted`: the slots it selects and
    fronts in them: every tile but the last whole, the last ONE group
    where one holds what is left of the live slots and whole where it
    does not. A batch with no live slot (no step dispatches one; a
    warm-up does) walks as a batch with one. ONE rule for the program
    (`n_live` traced: its loop's bound and each trip's front) and for
    the host's account of it (ints, or an array of them a device); a
    batch that is no whole number of groups or tiles is padded to
    one, and the pad is no slot: the account cuts both at `n_slots`."""
    tile = min(viterbi_pallas.LANES,
               -(-n_slots // DECODE_GROUP) * DECODE_GROUP)
    n = n_live + (n_live == 0)
    full = (n - 1) // tile * tile        # the tiles before the last
    whole = n - full > DECODE_GROUP
    return (full + DECODE_GROUP + whole * (tile - DECODE_GROUP),
            full + tile)


def decode_bound(longest, t_max: int):
    """What the streaming decode runs of the trellis for a tile whose
    longest lane holds `longest` data bits: ``(blocks, steps)``. The
    ACS and the traceback take the trellis in blocks of
    `viterbi_pallas.UNROLL` steps and stop after the block that holds
    that lane's last bit: at least one block, at most the `t_max`
    steps there are (`params.mixed_trellis_steps`). Every row past it
    is an erasure in every lane of the tile, so the bits before each
    lane's own count are the whole trellis's (`_mixed_stages`). ONE
    rule for the program (`longest` traced: the kernels' prefetched
    count) and for the host's account of it (ints, or an array of them
    a tile: `decode_steps`)."""
    unroll = viterbi_pallas.UNROLL
    blocks = -(-longest // unroll)
    blocks = blocks + (blocks == 0)
    steps = blocks * unroll
    steps = steps - (steps > t_max) * (steps - t_max)
    return -(-steps // unroll), steps


def decode_steps(nbits, n_sym_bucket: int, bounded: bool = True) -> int:
    """The host's account of the walk: the trellis steps the ACS and
    the traceback run for the (devices, slots) table `nbits` (data
    bits a slot in stream order, zero where a slot holds no frame),
    lanes x steps summed over the tiles `decode_walk` says each device
    decodes. The program packs a device's live slots in that order a
    tile at a time, a tile runs all its lanes (cut at the slots there
    are) to `decode_bound`'s steps for its longest (``bounded`` False,
    a decode mode whose kernels take no bound, `trellis_takes_bound`:
    the whole trellis), and that is what this counts: plain ints on
    the host (a few hundred at most: quicker than arrays), no device."""
    nbits = np.atleast_2d(nbits)
    n, t_max = nbits.shape[1], mixed_trellis_steps(n_sym_bucket)
    tile = decode_walk(1, n)[1]
    total = 0
    for row in nbits.tolist():
        live = [b for b in row if b]
        for j in range(0, decode_walk(len(live), n)[1], tile):
            longest = max(live[j:j + tile], default=0) if bounded else t_max
            total += (min(j + tile, n) - j) * decode_bound(longest, t_max)[1]
    return total


def stream_decode_graph(segs, rows, ridx, nbits, npsdu,
                        n_sym_bucket: int, viterbi_window: int = None,
                        viterbi_metric: str = None,
                        viterbi_radix: int = None,
                        sco_track: bool = False,
                        fused_demap: bool = False):
    """The streaming decode over the slots that hold a frame. `segs`
    (S, K, need_b, 2) is the scan's segment batch; `rows`, `ridx`,
    `nbits`, `npsdu` the host's four (S, K) tables (segment row, rate
    index, data bits, PSDU bits), a stream's decodable lanes first
    and ``nbits == 0`` in every slot past them. Returns ``(clear (S,
    K, t_max), crc (S, K), (fronted, decoded, steps))``, the last the
    slots its trips ran (`decode_walk`'s pair) and the trellis steps
    each tile's kernels ran (`decode_bound`'s, zero for a tile no trip
    went to), for the tests.

    A slot is live where ``nbits > 0``. A stable partition packs the
    live slots of the flattened (S*K) batch to its front, stream
    order kept, and ONE loop whose trip count is data (`decode_walk`)
    takes them a tile of 128 at a time and stops at the last tile
    that holds one: a trip selects the segments of its tile's live
    slots from `segs` and fronts them (one group of `DECODE_GROUP`
    where one holds what is left, else the whole tile: a `lax.cond`),
    then runs the ACS, the traceback, the descrambler and the FCS
    check on the tile. The two kernels run the trellis as far as the
    tile's LONGEST frame reaches and no further (PR 53): the bound is
    `decode_bound` of the largest ``nbits`` among the tile's live
    places, a traced count a tile (under a mesh each device bounds its
    own tiles; no collective), and a tile of 1500-byte frames runs 189
    of the 513 blocks that clause 18's longest frame fills. The stages
    are `decode_data_mixed`'s own (`_mixed_stages`) and lane-local, and
    the rows a bound leaves out are erasures in every lane under it,
    so every live slot's `crc` and its `clear` BEFORE ITS OWN
    ``nbits`` are, bit for bit, what the mixed decode over all S*K
    slots and the whole trellis gives it. From a slot's ``nbits`` on
    its `clear` held the decode of erasures, and holds that up to its
    tile's bound and the descrambled zeros past it; a slot that holds
    no frame is not decoded and reads ZERO in both outputs (until PR
    46 it came back as a decoded erasure). The host reads neither."""
    front, trellis, back = _mixed_stages(
        n_sym_bucket, viterbi_window, viterbi_metric, viterbi_radix,
        None, sco_track, fused_demap)
    bounded = trellis_takes_bound(viterbi_window, viterbi_metric,
                                  fused_demap)
    s, kk = rows.shape
    n, g = s * kk, DECODE_GROUP
    t_max = mixed_trellis_steps(n_sym_bucket)
    # a tile: what one live slot decodes; the packed batch: whole tiles
    tile, n_buf = decode_walk(1, n)[1], decode_walk(n, n)[1]
    ridx, nbits, npsdu = (t.reshape(-1) for t in (ridx, nbits, npsdu))
    with jax.named_scope("rx.decode.select"):
        live = nbits > 0
        n_live = live.sum(dtype=jnp.int32)
        fronted, decoded = decode_walk(n_live, n)
        # a live slot's place in the packed order, and the slot at
        # each place; places past the last live slot keep slot 0,
        # whose rows are computed with the last group and never read
        place = jnp.cumsum(live, dtype=jnp.int32) - 1
        order = jnp.zeros((n_buf,), jnp.int32).at[
            jnp.where(live, place, n_buf)].set(
                jnp.arange(n, dtype=jnp.int32), mode="drop")
        segs = segs.reshape((n,) + segs.shape[2:])
        src = jnp.arange(n, dtype=jnp.int32) // kk * kk \
            + rows.reshape(-1)

    def front_rows(size):
        """The front over a tile's first `size` packed slots, as the
        tile's rows (zero, an erasure, past them)."""
        def rows_of(j):
            idx = jax.lax.dynamic_slice(order, (j * tile,), (size,))
            with jax.named_scope("rx.decode.select"):
                # ONE gather a trip: a slice a slot, stacked, cost the
                # chip's compiler 10.9 GB of temporaries at 128 slots
                frames = jnp.take(segs, src[idx], axis=0, mode="clip")
            return tuple(
                jnp.pad(o, ((0, tile - size),) + ((0, 0),) * (o.ndim - 1))
                for o in front(frames, ridx[idx], nbits[idx]))
        return rows_of

    def decode_tile(j, out):
        # what `decode_walk` fronts of this tile: one group (the
        # last tile, where one holds what is left) or all of it
        soft = front_rows(g)(j) if tile == g else jax.lax.cond(
            fronted - j * tile > g, front_rows(tile), front_rows(g), j)
        idx = jax.lax.dynamic_slice(order, (j * tile,), (tile,))
        with jax.named_scope("rx.decode.select"):
            # the tile's bound: its longest frame, over its LIVE places
            # (a place past the last live slot reads slot 0's tables)
            here = j * tile + jnp.arange(tile, dtype=jnp.int32) < n_live
            blocks, steps = decode_bound(
                jnp.max(jnp.where(here, nbits[idx], 0)) if bounded
                else jnp.int32(t_max), t_max)
        clear = back(trellis(soft, ridx[idx], nbits[idx],
                             blocks.reshape(1) if bounded else None))
        crc = crc_psdu_many_graph(clear, npsdu[idx])
        with jax.named_scope("rx.decode.back"):
            # a tile is a leading index of the packed outputs, so the
            # write is the tile's own bytes (zero where no trip went)
            return tuple(jax.lax.dynamic_update_index_in_dim(b, p, j, 0)
                         for b, p in zip(out, (clear, crc, steps)))

    out = (jnp.zeros((n_buf // tile, tile, t_max), jnp.uint8),
           jnp.zeros((n_buf // tile, tile), bool),
           jnp.zeros((n_buf // tile,), jnp.int32))
    # one tile is no loop: a body that does not read its counter is
    # hoisted out piecemeal by the chip's compiler, which then ran
    # the ACS twice
    clear, crc, steps = (
        decode_tile(0, out) if n_buf == tile
        else jax.lax.fori_loop(0, decoded // tile, decode_tile, out))
    clear, crc = (b.reshape((n_buf,) + b.shape[2:]) for b in (clear, crc))
    with jax.named_scope("rx.decode.back"):
        # packed places back to the slots' own
        clear = jnp.where(live[:, None], clear[place], 0)
        crc = live & crc[place]
    return (clear.reshape(s, kk, -1), crc.reshape(s, kk),
            (fronted, decoded, steps))


@lru_cache(maxsize=None)
def _jit_stream_decode_multi(n_sym_bucket: int, viterbi_window: int = None,
                             viterbi_metric: str = None,
                             viterbi_radix: int = None, mesh=None,
                             axis: str = "dp",
                             sco_track: bool = False,
                             fused_demap: bool = False):
    """Dispatch 2 of the chunk-step: the mixed-rate decode + masked
    CRC of the slots that hold a frame (`stream_decode_graph`: all
    inside the jit — the still device-resident (S, K, ...) segment
    batch never re-crosses the host link) — one rate-agnostic Pallas
    Viterbi for the whole fleet, every live lane riding the same
    128-lane tiles (lane values are batch-independent, the pinned
    receive_many contract, so each lane is bit-identical to its
    stream's own K-lane decode). With a `mesh` each device packs and
    walks its own streams' slots; no collective. The CRC flags
    are always computed, so one compile serves both `check_fcs`
    modes: two XOR-reductions and a look-up, 0.4 ms at the MTU
    bucket (as a byte-serial scan the check was 35.9 ms of the 83 ms
    decode, more than the Viterbi; ledger PR 25, chip runs PR 27).
    Decode-mode knobs (including the resolved ``fused_demap``, LAST
    for the R1 lint demo) and the mesh are cache keys, as in every
    jit factory here."""
    def stream_decode_multi(segs, rows, ridx, nbits, npsdu):
        return stream_decode_graph(
            segs, rows, ridx, nbits, npsdu, n_sym_bucket,
            viterbi_window, viterbi_metric, viterbi_radix,
            sco_track=sco_track, fused_demap=fused_demap)[:2]

    if mesh is None:
        return jax.jit(stream_decode_multi)
    from ziria_tpu.parallel.batch import stream_specs
    # check_vma=False: the Pallas ACS inside the decode has no
    # replication rule; nothing here is replicated anyway — every
    # operand leads with the sharded stream axis
    return jax.jit(jax.shard_map(
        stream_decode_multi, mesh=mesh,
        in_specs=stream_specs((4, 2, 2, 2, 2), axis),
        out_specs=stream_specs((3, 2), axis), check_vma=False))


def receive(samples, check_fcs: bool = False,
            max_samples: int = 1 << 16, fxp: bool = False,
            viterbi_window: int = None,
            viterbi_metric: str = None,
            viterbi_radix: int = None,
            fused_demap: bool = None,
            sco_track: bool = None,
            geometry=None) -> RxResult:
    """Host-side receiver driver: detect, align, CFO-correct, parse
    SIGNAL, dispatch the per-rate decoder — the jit analogue of the
    reference's header-driven rate dispatch. The data decode compiles
    once per (rate, power-of-two symbol bucket) with the true bit count
    traced (see decode_data_bucketed), so varied traffic stays within
    O(rates x log lengths) compiles.

    fxp=True routes the DATA decode through the Q15 integer interior
    (phy/wifi/rx_fxp.py — the reference's fixed-point discipline):
    acquisition and SIGNAL stay f32; the aligned data region is
    AGC-normalized by the preamble RMS and quantized to Q11 at the
    fixed-point boundary, after which every decode op is exact integer
    arithmetic (bit-identical across backends for identical quantized
    input).

    viterbi_window opts the (float) DATA decode into the sliding-
    window parallel Viterbi — same result at operating SNR, ~T/window
    less sequential trellis depth on the chip; viterbi_metric="int16"
    opts it into the quantized saturating-metric kernel and "int8"
    into the int8+LUT kernel below it; viterbi_radix=4 runs two
    trellis steps per ACS iteration and fused_demap=True moves the
    demap/deinterleave/depuncture front end into the decode kernel
    (all ignored under fxp, whose decode keeps the exact scan).

    sco_track=True (--rx-sco-track / ZIRIA_RX_SCO_TRACK) adds the
    pilot phase-RAMP tracking for sampling-clock-offset channels
    (docs/robustness.md; default off — the flat-path decode is
    pinned bit-identical and a fitted slope is never exactly zero);
    the bounded-|H| null-subcarrier guard is always on and value-
    inert on flat channels. Both ignored under fxp.

    ``geometry`` (a utils/geometry.Geometry) supplies the default for
    every decode-mode knob the caller leaves None — one declarative
    object instead of five threaded parameters; explicit per-knob
    arguments still win. The default Geometry reproduces the legacy
    env-resolution path exactly (same compiled programs, same bits).
    """
    if geometry is not None:
        viterbi_window = (geometry.viterbi_window
                          if viterbi_window is None else viterbi_window)
        viterbi_metric = (geometry.viterbi_metric
                          if viterbi_metric is None else viterbi_metric)
        viterbi_radix = (geometry.viterbi_radix
                         if viterbi_radix is None else viterbi_radix)
        fused_demap = (geometry.fused_demap
                       if fused_demap is None else fused_demap)
        sco_track = (geometry.sco_track
                     if sco_track is None else sco_track)
    res, acq = _acquire_frame(samples, max_samples)
    if acq is None:
        return res
    rate = RATES[acq.rate_mbps]

    # bucketed dispatch: pad the frame to a power-of-two symbol count so
    # the decode jit-caches O(rates x log lengths), not once per PSDU
    # length; the true bit count flows in as a traced scalar
    n_sym_b = _sym_bucket(acq.n_sym)
    seg = _padded_segment(acq, n_sym_b)
    if fxp:
        from ziria_tpu.phy.wifi import rx_fxp
        # AGC at the fixed-point boundary: unit average power over the
        # real preamble (numpy host math — stable for a given capture)
        rms = float(np.sqrt(np.mean(acq.frame_np[:320].astype(np.float64)
                                    ** 2) * 2.0))
        seg = rx_fxp.quantize_frame(np.asarray(seg) / max(rms, 1e-12))
    dec = _jit_decode_data_bucketed(
        acq.rate_mbps, n_sym_b, fxp,
        None if fxp else viterbi_window,
        None if fxp else viterbi_metric,
        None if fxp else viterbi._check_radix(viterbi_radix),
        False if fxp else sco_track_enabled(sco_track),
        None if fxp else fused_demap_enabled(fused_demap))
    from ziria_tpu.utils import dispatch, programs
    programs.note_site("rx.decode_bucketed", dec, seg,
                       jnp.int32(acq.n_sym * rate.n_dbps))
    # the host pull stays OUTSIDE the timed block: the site times the
    # dispatch, not the device wait (jaxlint R2 — docs/static_analysis.md)
    with dispatch.timed("rx.decode_bucketed"):
        clear_dev = dec(seg, jnp.int32(acq.n_sym * rate.n_dbps))
    clear = np.asarray(clear_dev, np.uint8)
    psdu = clear[N_SERVICE_BITS: N_SERVICE_BITS + 8 * acq.length_bytes]
    crc = bool(np.asarray(check_crc32(psdu))) if check_fcs else None
    return RxResult(True, acq.rate_mbps, acq.length_bytes, psdu, crc)
