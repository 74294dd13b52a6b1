"""Device-resident TX → channel → RX loopback link.

The closed loop the reference ran over SORA/BladeRF hardware (Sora's
NSDI 2009 real-time link; the Ziria transceiver demo drives it
in-language) — here the "air" is the batched synthetic channel and the
whole N-frame round trip compiles to ONE device program:

    link.loopback_fused     encode_many → impair_many → acquire →
                            classify → gather → mixed decode →
                            batched CRC, fused into ONE jitted graph

— 1 device dispatch for any N-frame, all-rates, multi-SNR batch. The
host `_classify_acquire` decision tree is pure integer logic, so in
the loopback — where the frame geometry is already known from the TX
side and the SIGNAL parse is therefore NOT data-dependent — it traces
(`rx.classify_acquire_graph`) and no acquisition metadata crosses the
host link mid-batch; the decoded SIGNAL fields come back as device-
side validity flags, so no-detect / bad-parity / truncated lanes keep
their exact staged-path classification.

``fused=False`` (or ``--no-fused-link`` / ``ZIRIA_FUSED_LINK=0``) runs
the STAGED 5-dispatch path — encode_many, impair_many, then the
acquire → gather → mixed-decode triple — the fused graph's
bit-identical oracle (same capture bucket, so the noise draws agree);
``batched_tx=False`` (``--no-batched-tx`` / ``ZIRIA_BATCHED_TX=0``)
drops further to the per-frame loop: encode_frame + single-lane
channel + rx.receive per frame, >= 5 dispatches per lane. All three
agree lane for lane (tests/test_link_fused.py, test_tx_batched.py;
tools/rx_dispatch_bench.py ``fused_link_stats`` measures it).

On top of the fused step, ``sweep_ber`` runs an entire BER waterfall —
(rate grid) x (SNR grid x seeds) — as ONE ``lax.scan`` dispatch with a
donated error-count carry, and ``sweep_ber_sharded`` shards its frame
lanes over ``parallel/batch.frame_mesh``'s dp axis so the sweep scales
across chips (integer error counts, so the numbers are identical on 1
device and any mesh).
"""

from __future__ import annotations

import os
from functools import lru_cache
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ziria_tpu.backend import framebatch
from ziria_tpu.ops.viterbi import _check_radix
from ziria_tpu.phy import channel
from ziria_tpu.phy import profiles as chanprof
from ziria_tpu.phy.wifi import rx, tx
from ziria_tpu.phy.wifi.params import N_SERVICE_BITS, \
    RATE_MBPS_ORDER, RATES, n_symbols
from ziria_tpu.utils import dispatch, programs
from ziria_tpu.utils.dispatch import pad_lanes, pow2_ceil


def _note_link_degraded(counter: str) -> None:
    """The ONE link-side degrade-visibility ritual (the fused link
    and the sweep share it, so the recording can never drift): the
    ``link.degraded_mode`` gauge plus the per-site degrade counter."""
    from ziria_tpu.utils import telemetry
    dispatch.record_gauge("link.degraded_mode", 1.0)
    telemetry.count(counter)


def batched_tx_enabled(batched_tx: Optional[bool] = None) -> bool:
    """The ONE reading of the --batched-tx / ZIRIA_BATCHED_TX knob
    (default ON), shared by every TX-batch surface."""
    if batched_tx is not None:
        return batched_tx
    return os.environ.get("ZIRIA_BATCHED_TX", "1") != "0"


def fused_link_enabled(fused: Optional[bool] = None) -> bool:
    """The ONE reading of the --fused-link / ZIRIA_FUSED_LINK knob
    (default ON): whether `loopback_many` routes through the
    one-dispatch fused graph or the staged 5-dispatch oracle."""
    if fused is not None:
        return fused
    return os.environ.get("ZIRIA_FUSED_LINK", "1") != "0"


def transmit_many(psdus: Sequence, rates_mbps: Sequence[int],
                  add_fcs: bool = False,
                  batched_tx: Optional[bool] = None) -> List[np.ndarray]:
    """N mixed-rate, mixed-length frames -> per-frame sample arrays at
    their true lengths: ONE encode_many dispatch plus one batched
    copy-out (default), or the per-frame encode_frame oracle loop
    (``ZIRIA_BATCHED_TX=0``). Bit-identical either way — including
    the empty batch, which is [] in both modes (receive_many's
    convention), never a mode-dependent raise."""
    if not len(psdus):
        return []
    if not batched_tx_enabled(batched_tx):
        return [np.asarray(tx.encode_frame(p, m, add_fcs=add_fcs))
                for p, m in zip(psdus, rates_mbps)]
    txb = tx.encode_many(psdus, rates_mbps, add_fcs=add_fcs)
    arr = np.asarray(txb.samples[:len(psdus)])   # pad rows never move
    return [arr[i, :int(v)] for i, v in enumerate(txb.n_valid)]


def _lane_param(v, n: int, dtype) -> np.ndarray:
    return np.broadcast_to(np.asarray(v, dtype), (n,)).copy()


def _link_buckets(psdus, rates_mbps, add_fcs: bool, dly_max: int,
                  tap_pad: int = 0):
    """The ONE derivation of the link's (symbol bucket, capture
    bucket): the common symbol bucket's frame length plus the worst
    delay, at the receiver's capture-bucket rule. ``tap_pad`` is the
    profiled channel's FIR ring headroom (max tap count - 1, zero for
    the unprofiled/flat link so those buckets are untouched): the
    multipath tail smears that many samples past the frame, and
    without the margin a lane whose delay + frame length lands
    exactly on the power-of-two bucket would wrap the ring onto the
    capture HEAD via the delay roll. Every loopback mode — fused,
    staged, per-frame — calls this, because a lane's noise field is
    drawn over the whole capture buffer: buffer sizes ARE semantics,
    and a drift here would silently break the lane-for-lane
    bit-identity contract."""
    fcs_bytes = 4 if add_fcs else 0
    sym_b = max(tx._sym_bucket(n_symbols(
        int(np.asarray(p).size) + fcs_bytes, RATES[m]))
        for p, m in zip(psdus, rates_mbps))
    return sym_b, rx._stream_bucket(400 + 80 * sym_b + int(dly_max)
                                    + int(tap_pad))


class _LinkGeometry:
    """The host-known batch geometry of the staged/fused loopback: the
    shared TX batch prep (`tx.batch_host_prep` — the SAME padded-batch
    rule `encode_many` consumes, so the link can never drift from the
    transmit surfaces) plus the link-side row tables (channel params,
    capture bucket, per-lane decode bit counts)."""

    def __init__(self, psdus, rates_mbps, snr, eps, dly, add_fcs,
                 tap_pad: int = 0):
        n = len(psdus)
        self.n = n
        prep = tx.batch_host_prep(psdus, rates_mbps, add_fcs)
        self.n_sym = prep.n_sym
        self.sym_b = prep.n_sym_bucket
        self.bit_b = prep.bit_bucket
        self.bits_b = prep.bits_b
        self.nbits_b = prep.nbits_b
        self.ridx_b = prep.ridx_b
        _sym_b2, self.l_cap = _link_buckets(psdus, rates_mbps,
                                            add_fcs, int(dly.max()),
                                            tap_pad)
        if _sym_b2 != self.sym_b:       # one rule, two call shapes
            raise AssertionError(
                f"link bucket rule drifted: {_sym_b2} != {self.sym_b}")
        self.rows = pow2_ceil(n)
        lanes = pad_lanes(list(range(n)))
        self.nv_tx = np.zeros(self.rows, np.int32)
        self.ndata_b = np.zeros(self.rows, np.int32)
        for row, i in enumerate(lanes):
            self.nv_tx[row] = 400 + 80 * int(self.n_sym[i])
            self.ndata_b[row] = int(self.n_sym[i]) * \
                RATES[rates_mbps[i]].n_dbps

        def _pad_rows(a):
            return np.concatenate(
                [a, np.broadcast_to(a[0], (self.rows - n,)
                                    + a.shape[1:])])
        self.snr = _pad_rows(snr)
        self.eps = _pad_rows(eps)
        self.dly = _pad_rows(dly)


def loopback_many(psdus, rates_mbps: Sequence[int],
                  snr_db=np.inf, cfo=0.0, delay=0, seed: int = 0,
                  add_fcs: bool = False, check_fcs: bool = False,
                  batched_tx: Optional[bool] = None,
                  fused: Optional[bool] = None,
                  viterbi_window: int = None,
                  viterbi_metric: str = None,
                  viterbi_radix: int = None,
                  channel_profile=None,
                  sco_track: Optional[bool] = None,
                  fused_demap: Optional[bool] = None,
                  geometry=None) -> List:
    """The full N-frame mixed-rate loopback. Default: the FUSED path —
    encode → per-lane channel impairments → acquire → classify →
    gather → mixed-rate decode → batched CRC as ONE jitted device
    program (1 dispatch). ``fused=False`` / ``ZIRIA_FUSED_LINK=0``:
    the staged ~5-dispatch path (encode_many + impair_many + the
    acquire/gather/decode triple), the fused graph's bit-identical
    oracle. ``batched_tx=False``: the per-frame loop (>= 5 dispatches
    per lane), the staged path's oracle in turn.

    ``snr_db``/``cfo``/``delay`` are scalars or per-lane sequences
    (``np.inf`` SNR disables noise exactly); lane noise keys derive
    from ``seed`` by counter fold-in, so lane i sees the same channel
    whether it runs fused, staged, or alone. ``channel_profile`` is a
    profile name / per-lane sequence / None (-> the
    ``ZIRIA_CHANNEL_PROFILE`` default; `profiles.resolve_profiles` —
    all-flat IS the unprofiled channel by construction), applied as
    vmapped per-lane taps/SCO/drift/bursts inside the SAME dispatches;
    ``sco_track`` opts the decode into the pilot phase-ramp tracking
    (``ZIRIA_RX_SCO_TRACK``). Returns per-frame :class:`rx.RxResult`,
    lane-for-lane bit-identical across all three modes — including
    no-detect / bad-parity / truncated lanes and ``check_fcs=True``.
    (Profiled lanes' channel SAMPLES may differ by one float32 ulp
    between the separately compiled mode programs — the
    FMA-contraction rule — but the decoded RxResults are pinned
    equal lane for lane: tests/test_channel_profiles.py.)"""
    n = len(psdus)
    if len(rates_mbps) != n:
        raise ValueError(f"{n} PSDUs but {len(rates_mbps)} rates")
    if n == 0:
        return []          # match receive_many's empty-batch behavior
    snr = _lane_param(snr_db, n, np.float32)
    eps = _lane_param(cfo, n, np.float32)
    dly = _lane_param(delay, n, np.int32)
    if (dly < 0).any():
        raise ValueError("negative delay")
    # a Geometry fills only the knobs the caller left at None — explicit
    # per-call arguments still win (utils/geometry contract)
    if geometry is not None:
        viterbi_window = (geometry.viterbi_window
                          if viterbi_window is None else viterbi_window)
        viterbi_metric = (geometry.viterbi_metric
                          if viterbi_metric is None else viterbi_metric)
        viterbi_radix = (geometry.viterbi_radix
                         if viterbi_radix is None else viterbi_radix)
        sco_track = (geometry.sco_track
                     if sco_track is None else sco_track)
        fused_demap = (geometry.fused_demap
                       if fused_demap is None else fused_demap)
    # resolved ONCE here so the per-frame oracle, the staged path, and
    # the fused graph's compile-cache key all see the same radix,
    # per-lane profile names, sco_track, and fused_demap values
    viterbi_radix = _check_radix(viterbi_radix)
    prof_key = chanprof.resolve_profiles(channel_profile, n)
    sco_track = rx.sco_track_enabled(sco_track)
    fused_demap = rx.fused_demap_enabled(fused_demap)
    # profiled links reserve FIR-ring headroom in the capture bucket
    # (max taps - 1; zero for flat/None, so those buckets — and their
    # noise-draw geometry — are byte-for-byte today's)
    tap_pad = 0 if prof_key is None else max(
        len(chanprof.get_profile(nm).taps) for nm in prof_key) - 1
    # the shared bucket rule, from byte counts alone — the per-frame
    # oracle never pays the padded-batch construction
    _sym_b, l_cap = _link_buckets(psdus, rates_mbps, add_fcs,
                                  int(dly.max()), tap_pad)
    if not batched_tx_enabled(batched_tx):
        # the per-frame oracle: same channel physics, one frame at a
        # time, through the per-capture receiver
        results = []
        for i in range(n):
            s = np.asarray(tx.encode_frame(psdus[i], rates_mbps[i],
                                           add_fcs=add_fcs))
            cap = channel.impair_one(
                s, snr[i], eps[i], int(dly[i]), seed, i, l_cap,
                profile=None if prof_key is None else prof_key[i])
            results.append(rx.receive(np.asarray(cap),
                                      check_fcs=check_fcs,
                                      viterbi_window=viterbi_window,
                                      viterbi_metric=viterbi_metric,
                                      viterbi_radix=viterbi_radix,
                                      fused_demap=fused_demap,
                                      sco_track=sco_track))
        return results

    geo = _LinkGeometry(psdus, rates_mbps, snr, eps, dly, add_fcs,
                        tap_pad)
    # lane-pad the profile names exactly as every other row table
    # (lane 0 repeated), so pad rows ride lane 0's channel
    prof_rows = None if prof_key is None else tuple(
        prof_key[i] for i in pad_lanes(list(range(n))))
    if fused_link_enabled(fused):
        return _loopback_fused(geo, seed, check_fcs,
                               viterbi_window, viterbi_metric,
                               viterbi_radix, prof_rows, sco_track,
                               fused_demap)
    return _loopback_staged(geo, seed, check_fcs, viterbi_window,
                            viterbi_metric, viterbi_radix, prof_rows,
                            sco_track, fused_demap)


def _loopback_staged(geo: _LinkGeometry, seed, check_fcs,
                     viterbi_window, viterbi_metric,
                     viterbi_radix=None, prof_rows=None,
                     sco_track: bool = False,
                     fused_demap: bool = False) -> List:
    """The staged ~5-dispatch batched loopback (the fused graph's
    bit-identical oracle): one encode_many dispatch, one impair_many
    dispatch, then receive_many_device's acquire → gather → decode
    (+ CRC) over the device-resident capture batch."""
    enc_fn = tx._jit_encode_many(geo.bit_b, geo.sym_b)
    enc_args = (jnp.asarray(geo.bits_b), jnp.asarray(geo.nbits_b),
                jnp.asarray(geo.ridx_b))
    programs.note_site("tx.encode_many", enc_fn, *enc_args)
    with dispatch.timed("tx.encode_many"):
        samples = enc_fn(*enc_args)
    caps = channel.impair_many(
        samples, geo.nv_tx, geo.snr, geo.eps, geo.dly, seed,
        out_len=geo.l_cap, profile=prof_rows)
    return framebatch.receive_many_device(
        caps, geo.n, check_fcs=check_fcs,
        viterbi_window=viterbi_window, viterbi_metric=viterbi_metric,
        viterbi_radix=viterbi_radix, sco_track=sco_track,
        fused_demap=fused_demap)


@lru_cache(maxsize=None)
def _jit_fused_link(rows: int, bit_bucket: int, sym_bucket: int,
                    l_cap: int, viterbi_window: int = None,
                    viterbi_metric: str = None,
                    viterbi_radix: int = None, profile_key=None,
                    sco_track: bool = False,
                    fused_demap: bool = False):
    """ONE compiled loopback link per (lane count, bit bucket, symbol
    bucket, capture bucket, decode mode, per-lane channel-profile
    names): the whole TX → channel → RX chain — including the
    acquisition classify tree and the batched FCS check — as a single
    XLA program. A profiled link is STILL one dispatch: the profile's
    taps/SCO/drift/bursts trace into the channel stage as per-lane
    constants (callers pass RESOLVED names — jaxlint R1). The CRC
    flags are always computed (`ops/crc.check_crc32_masked`: two
    XOR-reductions and a look-up per lane), so one compile serves both
    ``check_fcs`` modes."""
    need_b = rx.FRAME_DATA_START + 80 * sym_bucket

    def f(bits_b, nbits_b, ridx_b, nv_tx, snr, eps, dly, seed,
          ndata_b):
        # 1. mixed-rate encode at the common bucketed geometry
        samples = tx.encode_many_graph(bits_b, nbits_b, ridx_b,
                                       sym_bucket)
        # 2. per-lane channel impairments (counter fold-in keys:
        #    lane i's noise is the same fused, staged, or alone —
        #    profiled lanes included)
        caps = channel.impair_many_graph(samples, nv_tx, snr, eps,
                                         dly, seed, l_cap,
                                         profile_key)
        # 3. batched acquisition: detect / LTS timing / CFO / SIGNAL
        #    (the whole capture is the lane's buffer, so n_valid and
        #    the detector's position cap are both l_cap — exactly what
        #    receive_many_device passes)
        nv = jnp.full((caps.shape[0],), l_cap, jnp.int32)
        found, start, eps_hat, rate_bits, length, parity_ok = \
            jax.vmap(rx.acquire_frame_graph)(caps, nv, nv)
        # 4. the classify tree, traced — the host decision that used
        #    to force a sync point stays on-device
        status, mbps_sig, len_sig, nsym_sig = rx.classify_acquire_graph(
            found, nv - start, rate_bits, length, parity_ok)
        # 5. gather+derotate EVERY lane at the common symbol bucket
        #    (failed lanes produce garbage segments, masked by status
        #    host-side; per-lane values are batch-independent)
        caps_pad = jnp.pad(caps, ((0, 0), (0, need_b), (0, 0)))
        segs = jax.vmap(
            lambda xi, s, e, a: rx.gather_segment_graph(
                xi, s, e, a, sym_bucket))(caps_pad, start, eps_hat,
                                          nv - start)
        # 6. mixed-rate DATA decode at the TX-known geometry (the
        #    loopback's SIGNAL parse is not data-dependent: rate and
        #    bit count per lane are known a priori; the decoded
        #    SIGNAL only gates validity via `status`)
        clear = rx.decode_data_mixed(segs, ridx_b, ndata_b, sym_bucket,
                                     viterbi_window, viterbi_metric,
                                     viterbi_radix,
                                     sco_track=sco_track,
                                     fused_demap=fused_demap)
        # 7. batched FCS check over the decoded PSDUs
        crc_ok = rx.crc_psdu_many_graph(clear, nbits_b)
        return status, mbps_sig, len_sig, nsym_sig, clear, crc_ok

    return jax.jit(f)


def _loopback_fused(geo: _LinkGeometry, seed, check_fcs,
                    viterbi_window, viterbi_metric,
                    viterbi_radix=None, prof_rows=None,
                    sco_track: bool = False,
                    fused_demap: bool = False) -> List:
    """Host wrapper of the fused graph: ONE device dispatch, then the
    per-lane RxResult assembly from the returned validity flags —
    integer reads only, exactly mirroring `_classify_acquire`'s
    outcomes. If a decodable lane's decoded SIGNAL disagrees with the
    TX-side geometry (possible only when noise corrupts the SIGNAL
    into a *different valid* header — a 1-in-2^~16 parity escape), the
    fused decode geometry would diverge from the staged one, so the
    whole batch falls back to the staged oracle; the common case pays
    nothing for the guard."""
    from ziria_tpu.runtime import resilience

    fn = _jit_fused_link(geo.rows, geo.bit_b, geo.sym_b, geo.l_cap,
                         viterbi_window, viterbi_metric, viterbi_radix,
                         prof_rows, sco_track, fused_demap)
    fused_args = (
        jnp.asarray(geo.bits_b), jnp.asarray(geo.nbits_b),
        jnp.asarray(geo.ridx_b), jnp.asarray(geo.nv_tx),
        jnp.asarray(geo.snr), jnp.asarray(geo.eps),
        jnp.asarray(geo.dly), jnp.uint32(seed),
        jnp.asarray(geo.ndata_b))
    programs.note_site("link.fused", fn, *fused_args)
    try:
        # guarded dispatch (runtime/resilience): a transient failure
        # retries with backoff to the identical result (the graph is
        # pure); a fatal or retry-exhausted one degrades the batch to
        # the staged oracle below — bit-identical by the pinned
        # fused-vs-staged contract, recorded, never a crash
        status, mbps_sig, len_sig, nsym_sig, clear, crc_ok = \
            resilience.guarded("link.fused", fn, *fused_args)
    except resilience.DispatchFailed:
        _note_link_degraded("link.fused_degraded")
        return _loopback_staged(geo, seed, check_fcs, viterbi_window,
                                viterbi_metric, viterbi_radix,
                                prof_rows, sco_track, fused_demap)
    try:
        # on an async backend a mid-execution runtime failure
        # surfaces HERE at the host pull, after the guarded dispatch
        # already returned — the fused batch is lost, so degrade
        # exactly as for a fatal dispatch
        status = np.asarray(status)
        mbps_sig = np.asarray(mbps_sig)
        len_sig = np.asarray(len_sig)
        nsym_sig = np.asarray(nsym_sig)
    except Exception:        # noqa: BLE001 - async loss, degrade
        _note_link_degraded("link.fused_degraded")
        return _loopback_staged(geo, seed, check_fcs, viterbi_window,
                                viterbi_metric, viterbi_radix,
                                prof_rows, sco_track, fused_demap)
    # healthy pass: re-record the gauge LEVEL so a past degrade does
    # not latch forever on dashboards (the rx receivers' per-chunk
    # level discipline)
    dispatch.record_gauge("link.degraded_mode", 0.0)

    results: List = [None] * geo.n
    clear_np = None
    crc_np = None
    for i in range(geo.n):
        st = int(status[i])
        if st == rx.ACQ_FAIL:
            results[i] = rx.RxResult(False, 0, 0,
                                     np.zeros(0, np.uint8), None)
            continue
        m, ln = int(mbps_sig[i]), int(len_sig[i])
        if st == rx.ACQ_TRUNCATED:
            results[i] = rx.RxResult(False, m, ln,
                                     np.zeros(0, np.uint8), None)
            continue
        if (m != RATE_MBPS_ORDER[int(geo.ridx_b[i])]
                or 8 * ln != int(geo.nbits_b[i])
                or int(nsym_sig[i]) != int(geo.n_sym[i])):
            # SIGNAL decoded to a different valid header than the one
            # TX sent: the staged path would decode at ITS claimed
            # geometry — replay the batch through the oracle
            return _loopback_staged(geo, seed, check_fcs,
                                    viterbi_window, viterbi_metric,
                                    viterbi_radix, prof_rows,
                                    sco_track, fused_demap)
        if clear_np is None:
            try:
                clear_np = np.asarray(clear, np.uint8)
                crc_np = np.asarray(crc_ok) if check_fcs else None
            except Exception:    # noqa: BLE001 - async loss, degrade
                _note_link_degraded("link.fused_degraded")
                return _loopback_staged(geo, seed, check_fcs,
                                        viterbi_window, viterbi_metric,
                                        viterbi_radix, prof_rows,
                                        sco_track, fused_demap)
        psdu = clear_np[i][N_SERVICE_BITS: N_SERVICE_BITS + 8 * ln]
        crc = bool(crc_np[i]) if check_fcs else None
        results[i] = rx.RxResult(True, m, ln, psdu, crc)
    return results


def stream_many(psdus, rates_mbps: Sequence[int], gaps=None,
                snr_db=np.inf, cfo: float = 0.0, delay: int = 0,
                seed: int = 0, add_fcs: bool = False,
                tail: int = 2048,
                batched_tx: Optional[bool] = None,
                channel_profile=None, _lane: int = 0):
    """Synthesize a continuous multi-frame I/Q stream — the stimulus
    of the streaming receiver (`framebatch.receive_stream`) and its
    bench: N mixed-rate frames at random (or given) inter-frame gaps,
    an initial `delay` of idle air, whole-stream CFO, and AWGN over
    everything (`channel.impair_stream` — SNR referenced to frame
    power, so gap length never changes the noise level). Frames ride
    the ONE-dispatch batched TX (`transmit_many`; per-frame oracle
    under ``batched_tx=False``, bit-identical).

    Returns ``(stream, starts)``: the (n, 2) f32 stream and the TRUE
    frame-start indices — the ground truth the streaming identity
    contract slices at. `gaps` is a length-(N-1) sequence of samples
    between a frame's end and the next frame's start; default: seeded
    random in [300, 600) — wide enough that a `frame_len`-tight
    receive window over one frame never also spans the NEXT frame's
    long preamble (per-capture `receive`'s global LTS peak-pick could
    otherwise time onto the stronger neighbor; identity would hold,
    per-frame decode would not). `tail` idle samples close the stream
    so the last frame's window is full-length.

    ``channel_profile`` (a profile name or None -> the
    ``ZIRIA_CHANNEL_PROFILE`` default; flat IS the unprofiled stream)
    applies the profile's multipath/SCO/drift/bursts over the WHOLE
    stream via `channel.impair_stream` — the streaming fleet's
    physical-fault campaign stimulus. Under an ``sco`` profile the
    returned `starts` are the PRE-resample positions (true positions
    drift by up to ``sco * len(stream)`` samples — slice-at-truth
    identity contracts should use flat-tap profiles)."""
    n = len(psdus)
    prof_names = chanprof.resolve_profiles(channel_profile, 1)
    prof_name = None if prof_names is None else prof_names[0]
    if len(rates_mbps) != n:
        raise ValueError(f"{n} PSDUs but {len(rates_mbps)} rates")
    if n == 0:
        if np.isfinite(snr_db):
            # SNR is referenced to frame power; with no frames there
            # is nothing to reference, and silently returning zeros
            # would masquerade as a noise stimulus
            raise ValueError("stream_many with zero frames has no "
                             "frame power to reference snr_db against;"
                             " synthesize noise directly")
        return (np.zeros((int(tail), 2), np.float32),
                np.zeros((0,), np.int64))
    frames = transmit_many(psdus, rates_mbps, add_fcs=add_fcs,
                           batched_tx=batched_tx)
    rng = np.random.default_rng(seed)
    if gaps is None:
        gaps = rng.integers(300, 600, size=max(n - 1, 0))
    gaps = np.asarray(gaps, np.int64)
    if gaps.shape[0] != n - 1:
        raise ValueError(f"{n} frames need {n - 1} gaps, "
                         f"got {gaps.shape[0]}")
    if n > 1 and (gaps < 0).any():
        raise ValueError("negative gap")
    if int(delay) < 0:
        raise ValueError("negative delay")

    starts = np.zeros(n, np.int64)
    pos = int(delay)
    for i, f in enumerate(frames):
        starts[i] = pos
        pos += f.shape[0] + (int(gaps[i]) if i < n - 1 else 0)
    stream = np.zeros((pos + int(tail), 2), np.float32)
    n_signal = 0
    for s, f in zip(starts, frames):
        stream[s: s + f.shape[0]] = f
        n_signal += f.shape[0]
    return (channel.impair_stream(stream, n_signal, snr_db, cfo, seed,
                                  profile=prof_name, lane=_lane),
            starts)


class ArrivalSpec(NamedTuple):
    """A seeded ragged-arrival shape for `stream_many_multi`: slab
    sizes drawn uniformly in ``[slab_lo, slab_hi)`` samples and
    inter-arrival gaps in ``[gap_lo, gap_hi]`` scheduler ticks (gap 0
    = the next slab lands on the same tick — a burst). One spec
    describes the whole fleet; each stream draws its OWN schedule
    from its folded seed, so the traffic is ragged ACROSS streams
    too, and every replay is identical."""
    slab_lo: int = 256
    slab_hi: int = 2048
    gap_lo: int = 0
    gap_hi: int = 2


def arrival_schedule(stream: np.ndarray, spec: ArrivalSpec,
                     seed: int) -> List:
    """Cut one synthesized stream into a seeded arrival schedule:
    ``[(tick, slab), ...]`` with ticks non-decreasing and the slabs
    concatenating back to the stream EXACTLY (the load generator
    replays real ragged traffic, it never invents or drops samples).
    Deterministic per (stream length, spec, seed)."""
    if spec.slab_lo < 1 or spec.slab_hi <= spec.slab_lo:
        raise ValueError(
            f"arrival slab range [{spec.slab_lo}, {spec.slab_hi}) "
            f"is empty or non-positive")
    if spec.gap_lo < 0 or spec.gap_hi < spec.gap_lo:
        raise ValueError(
            f"arrival gap range [{spec.gap_lo}, {spec.gap_hi}] "
            f"is empty or negative")
    rng = np.random.default_rng(seed)
    out, pos, tick, n = [], 0, 0, int(stream.shape[0])
    while pos < n:
        k = int(rng.integers(spec.slab_lo, spec.slab_hi))
        out.append((tick, stream[pos: pos + k]))
        pos += k
        tick += int(rng.integers(spec.gap_lo, spec.gap_hi + 1))
    return out


def _stream_seed(seed: int, i: int) -> int:
    """Per-stream seed fold-in for `stream_many_multi`: deterministic
    and collision-free across the fleet for any base seed (the affine
    map is injective mod the prime, so stream i's gap and noise draws
    never depend on which other streams ride the load). The offset
    also keeps lanes off the bare base seed for ordinary seeds — not
    a universal guarantee (every lane's affine map has one fixed
    point mod 2^31-1); callers needing a lane provably disjoint from
    a `stream_many(seed=seed)` stimulus should pick a different base
    seed."""
    return (int(seed) * 1000003 + 7919 * (int(i) + 1)) % (2 ** 31 - 1)


def stream_many_multi(psdus_per_stream, rates_per_stream, snr_db=np.inf,
                      cfo=0.0, delay=0, seed: int = 0,
                      add_fcs: bool = False, tail: int = 2048,
                      gaps=None, batched_tx: Optional[bool] = None,
                      arrival: Optional[ArrivalSpec] = None,
                      channel_profile=None):
    """The S-stream load synthesizer — the stimulus of the multi-
    stream receiver (`framebatch.receive_streams`) and its bench:
    stream i is exactly ``stream_many(psdus_per_stream[i],
    rates_per_stream[i], ...)`` at the per-stream folded seed
    (`_stream_seed`), so every stream carries its own frames, gaps,
    CFO rotation, and noise draws, mutually independent and
    reproducible per lane. ``snr_db``/``cfo``/``delay`` broadcast
    scalar-or-per-stream (the `loopback_many` rule); ``gaps`` is
    None or a length-S sequence of per-stream gap sequences.

    Returns ``(streams, starts_per_stream)``: S (n_i, 2) f32 streams
    (lengths ragged — the receiver's packer handles that) and each
    stream's TRUE frame-start indices, the ground truth the fleet
    identity contract slices at.

    ``arrival`` (an :class:`ArrivalSpec`) additionally returns a
    third element: per-stream seeded arrival SCHEDULES —
    ``schedules[i]`` is ``[(tick, slab), ...]`` cutting stream *i*
    into ragged slabs with inter-arrival gaps (the serving load
    generator's replayable traffic shape, `runtime/serve.py`); the
    slabs concatenate back to the stream exactly, so pushing a
    schedule through a receiver emits bit-identically to pushing the
    whole stream. Default ``None`` keeps the two-element return —
    existing call sites unchanged.

    ``channel_profile`` is a name or per-STREAM sequence (cycling, the
    `profiles.resolve_profiles` rule; None -> the env default): each
    stream rides its own physical channel — the fleet-scale
    physical-fault campaign stimulus of the soak harness."""
    s = len(psdus_per_stream)
    if len(rates_per_stream) != s:
        raise ValueError(f"{s} streams of PSDUs but "
                         f"{len(rates_per_stream)} of rates")
    if gaps is not None and len(gaps) != s:
        raise ValueError(f"{s} streams need {s} gap sequences, "
                         f"got {len(gaps)}")
    prof_key = chanprof.resolve_profiles(channel_profile, s)
    snr = _lane_param(snr_db, s, np.float64)
    eps = _lane_param(cfo, s, np.float64)
    dly = _lane_param(delay, s, np.int64)
    streams, starts = [], []
    for i in range(s):
        st, sts = stream_many(
            psdus_per_stream[i], rates_per_stream[i],
            gaps=None if gaps is None else gaps[i],
            snr_db=float(snr[i]), cfo=float(eps[i]),
            delay=int(dly[i]), seed=_stream_seed(seed, i),
            add_fcs=add_fcs, tail=tail, batched_tx=batched_tx,
            # "flat" (not None) when the fleet resolved to no profile:
            # the per-stream call must not resurrect the env default
            # the fleet-level resolution already consumed
            channel_profile=("flat" if prof_key is None
                             else prof_key[i]))
        streams.append(st)
        starts.append(sts)
    if arrival is None:
        return streams, starts
    schedules = [arrival_schedule(streams[i], arrival,
                                  _stream_seed(seed, i) + 1)
                 for i in range(s)]
    return streams, starts, schedules


def loopback_ber_bits(psdus, rate_mbps: int, snr_db: float, seed: int,
                      batched_tx: Optional[bool] = None,
                      profile=None,
                      sco_track: Optional[bool] = None) -> np.ndarray:
    """Perfect-sync single-rate BER loopback — the statistical lane of
    the link (BER waterfalls measure the equalize/demap/Viterbi chain,
    not packet detection): (B, n_bytes) PSDUs encode in ONE dispatch
    (`tx.encode_batch`; per-frame `encode_frame` loop when batched TX
    is off — bit-identical), AWGN rides one vmapped dispatch with
    per-lane split keys, and the batched DATA decode returns the
    decoded PSDU bits (B, 8*n_bytes). `sweep_ber` is the ONE-dispatch
    sweep of exactly this step over a (SNR x seed x profile) grid —
    equal error counts point for point.

    ``profile`` (one name; None/"flat" = today's AWGN path, exactly)
    routes the batch through `channel.impair_profile_point_graph` —
    multipath/SCO/drift before the SAME awgn expression at the SAME
    split keys, seeded bursts after — so the profiled sweep's loop
    twin stays integer-identical. ``sco_track`` is the RX knob."""
    psdus = np.asarray(psdus, np.uint8)
    rate = RATES[rate_mbps]
    n_bytes = psdus.shape[1]
    n_sym = n_symbols(n_bytes, rate)
    names = chanprof.resolve_profiles(profile, 1, use_env=False)
    sco_track = rx.sco_track_enabled(sco_track)
    if batched_tx_enabled(batched_tx):
        frames = tx.encode_batch(psdus, rate_mbps)
    else:
        frames = jnp.stack([jnp.asarray(tx.encode_frame(p, rate_mbps))
                            for p in psdus])
    keys = jax.random.split(jax.random.PRNGKey(seed), psdus.shape[0])
    with dispatch.timed("channel.awgn_batch"):
        if names is None:
            noisy = jax.vmap(
                lambda k, f: channel.awgn(k, f, snr_db))(keys, frames)
        else:
            noisy = channel.impair_profile_point_graph(
                frames, keys, snr_db, names[0])
    with dispatch.timed("rx.decode_batch"):
        got, _ = rx.decode_data_batch(noisy, rate, n_sym, 8 * n_bytes,
                                      sco_track=sco_track)
    return np.asarray(got)


# ------------------------------------------------- device-resident sweeps
#
# The serving workload: BER / waterfall studies over (rate, SNR, seed)
# grids. Point-by-point through the per-batch path every point pays
# the host round trips; here the whole grid rides ONE compiled
# `lax.scan` whose carry — the error-count buffer — is donated, and
# whose per-point body is the same perfect-sync step as
# `loopback_ber_bits` (same split keys, same ops), so the counts agree
# integer-for-integer with a python loop of batches.


def _sweep_point_graph(frames_by_rate, want_bits, rate_list, snr, seed,
                       profiles_key=None, sco_track: bool = False):
    """One sweep point, traced: AWGN at `snr` with keys split from
    `seed` (the SAME key schedule as loopback_ber_bits — lane i's
    noise never depends on which rates ride the sweep), the batched
    DATA decode per rate, and integer error counts vs the known TX
    bits. Returns (n_rates,) int32 — or, with ``profiles_key`` (a
    tuple of profile names), (n_profiles * n_rates,) profile-major:
    each profile column applies its taps/SCO/drift before the SAME
    awgn expression at the SAME keys and its bursts after
    (`channel.impair_profile_point_graph`), while a ``flat`` column
    skips the profile ops entirely — it IS the unprofiled expression,
    so its counts are bit-identical to the profile-less sweep."""
    errs = []
    for pname in (profiles_key or (None,)):
        prof = None if pname is None else chanprof.get_profile(pname)
        for frames, (m, n_sym, n_psdu_bits) in zip(frames_by_rate,
                                                   rate_list):
            keys = jax.random.split(jax.random.PRNGKey(seed),
                                    frames.shape[0])
            if prof is None or prof.is_flat:
                noisy = jax.vmap(
                    lambda k, f, _s=snr: channel.awgn(k, f, _s))(
                        keys, frames)
            else:
                noisy = channel.impair_profile_point_graph(
                    frames, keys, snr, prof.name)
            got, _ = rx.decode_data_batch(noisy, RATES[m], n_sym,
                                          n_psdu_bits,
                                          sco_track=sco_track)
            errs.append(jnp.sum(got != want_bits, dtype=jnp.int32))
    return jnp.stack(errs)


@lru_cache(maxsize=None)
def _jit_sweep_ber(rates_key: tuple, n_bytes: int, donate: bool,
                   profiles_key=None, sco_track: bool = False):
    """ONE compiled sweep per (rate tuple, frame bytes, profile
    tuple, sco_track): encode every rate's frame batch once
    (scan-invariant — XLA hoists it), then `lax.scan` the point step
    over the (snr, seed) grid, writing each point's error counts —
    (n_profiles x n_rates) wide under a profile axis — into the
    carried buffer. STILL one dispatch for the whole rates x SNR x
    profile waterfall. The buffer is DONATED (where the backend
    supports donation), so repeated sweeps reuse its pages instead of
    allocating per call."""
    rate_list = tuple(
        (m, n_symbols(n_bytes, RATES[m]), 8 * n_bytes)
        for m in rates_key)

    def f(bits_b, snr_flat, seed_flat, errbuf):
        # bits_b doubles as the decode's expected output: the TX bits
        # ARE the truth the decoded PSDU is scored against (one upload,
        # one traced operand)
        frames_by_rate = []
        for m, n_sym, _nb in rate_list:
            rate = RATES[m]
            full = jax.vmap(
                lambda b, _r=rate, _sb=tx._sym_bucket(n_sym):
                tx.encode_frame_bits_bucketed(
                    b, jnp.int32(8 * n_bytes), _r, _sb))(bits_b)
            frames_by_rate.append(full[:, :400 + 80 * n_sym])

        def body(carry, xs):
            i, buf = carry
            snr, seed = xs
            e = _sweep_point_graph(frames_by_rate, bits_b,
                                   rate_list, snr, seed,
                                   profiles_key, sco_track)
            buf = jax.lax.dynamic_update_slice(
                buf, e[None], (i, jnp.int32(0)))
            return (i + 1, buf), None

        (_, buf), _ = jax.lax.scan(
            body, (jnp.int32(0), errbuf), (snr_flat, seed_flat))
        return buf

    return jax.jit(f, donate_argnums=(3,) if donate else ())


def _sweep_dispatch(sweep_fn, bits_d, snr_d, seed_d, n_points: int,
                    n_rates: int):
    """One guarded sweep attempt. The error-count carry is DONATED on
    non-CPU backends, so it must be allocated fresh per attempt — a
    retry after a mid-execution transient would otherwise re-pass a
    donated (hence deleted) buffer and turn every retryable failure
    fatal."""
    errbuf = jnp.zeros((n_points, n_rates), jnp.int32)
    return sweep_fn(bits_d, snr_d, seed_d, errbuf)


def sweep_ber(psdus, rates_mbps: Sequence[int],
              snr_grid: Sequence[float], seeds: Sequence[int],
              profiles: Optional[Sequence] = None,
              sco_track: Optional[bool] = None,
              _shard=None) -> np.ndarray:
    """An entire BER waterfall in ONE device dispatch: every rate in
    `rates_mbps` over every (snr, seed) point of the grid, via one
    `lax.scan` of the perfect-sync link step. Returns int64 error
    counts shaped (len(rates), len(snr_grid), len(seeds)); divide by
    ``psdus.shape[0] * 8 * psdus.shape[1]`` for BER. Counts are
    IDENTICAL to a python loop of `loopback_ber_bits` batches over the
    same points (pinned by tests/test_link_fused.py) — vs ~3 host
    round trips per point through that loop and ~5 per point through
    the staged full link.

    ``profiles`` (a sequence of channel-profile names) grows the
    waterfall a PROFILE axis — rates x profiles x SNR x seeds, STILL
    one `lax.scan` dispatch — returning (len(rates), len(profiles),
    len(snr_grid), len(seeds)); the ``"flat"`` column's counts are
    bit-identical to the profile-less sweep by construction (it IS
    the unprofiled expression — tests/test_channel_profiles.py), and
    hostile columns gate the BER envelopes the channel_sweep bench
    stage records. ``sco_track`` opts every column's decode into the
    pilot phase-ramp tracking (one more cache-key bit). None keeps
    today's 3-axis return exactly.

    `_shard` (internal — `sweep_ber_sharded` passes it) is a callable
    placing the lane-axis arrays on a device mesh before the call."""
    psdus = np.asarray(psdus, np.uint8)
    if psdus.ndim != 2:
        raise ValueError("psdus must be (B, n_bytes)")
    b, n_bytes = psdus.shape
    rates_key = tuple(int(m) for m in rates_mbps)
    profiles_key = None if profiles is None else tuple(
        chanprof.get_profile(p).name for p in profiles)
    if profiles_key == ():
        # a zero-width profile axis would compile a zero-column error
        # buffer and die deep in the reshape — a caller bug, not a
        # backend fault, so fail HERE with the fix in the message
        raise ValueError("profiles must be a non-empty sequence of "
                         "profile names, or None for the unprofiled "
                         "3-axis sweep")
    n_prof = 1 if profiles_key is None else len(profiles_key)
    sco_track = rx.sco_track_enabled(sco_track)
    bits = np.stack([tx._host_psdu_bits(p, False) for p in psdus])
    snrs = np.asarray(snr_grid, np.float32)
    seed_arr = np.asarray(seeds, np.int32)
    # the scanned point order is (snr major, seed minor)
    snr_flat = np.repeat(snrs, seed_arr.shape[0])
    seed_flat = np.tile(seed_arr, snrs.shape[0])
    n_points = snr_flat.shape[0]
    # shape/dtype witness for note_site only (the REAL donated carry
    # is allocated fresh per attempt inside _sweep_dispatch): a host
    # array carries the aval without a wasted device allocation
    errbuf = np.zeros((n_points, n_prof * len(rates_key)), np.int32)
    bits_d = jnp.asarray(bits)
    if _shard is not None:
        bits_d = _shard(bits_d)
    donate = jax.devices()[0].platform != "cpu"   # no-op (+warn) on CPU
    sweep_fn = _jit_sweep_ber(rates_key, n_bytes, donate,
                              profiles_key, sco_track)
    snr_d = jnp.asarray(snr_flat)
    seed_d = jnp.asarray(seed_flat)
    programs.note_site("link.sweep", sweep_fn, bits_d, snr_d, seed_d,
                       errbuf)

    def _shape(errs):
        # (points, P*R) profile-major -> (R, S, K) or (R, P, S, K)
        errs = errs.reshape(snrs.shape[0], seed_arr.shape[0], n_prof,
                            len(rates_key))
        out = np.transpose(errs, (3, 2, 0, 1))
        return out[:, 0] if profiles_key is None else out

    from ziria_tpu.runtime import resilience
    # the sweep compiles OUTSIDE the guard (the dispatch wrapper hides
    # the jitted callable from guarded()): a program the compiler
    # refuses raises here, it never degrades to the loop
    resilience.compile_ahead(sweep_fn, bits_d, snr_d, seed_d, errbuf)
    try:
        # guarded (runtime/resilience): transient failures retry to
        # the identical counts (pure graph, fixed keys); a fatal one
        # degrades to the python loop of per-batch link steps — the
        # pinned integer-identical twin (test_link_fused), recorded.
        # The dispatch wrapper allocates the DONATED carry buffer
        # fresh per attempt: a retry after a mid-execution failure
        # must not re-pass a donated (hence deleted) buffer
        out = resilience.guarded(
            "link.sweep", _sweep_dispatch, sweep_fn, bits_d, snr_d,
            seed_d, n_points, n_prof * len(rates_key))
    except resilience.DispatchFailed:
        _note_link_degraded("link.sweep_degraded")
        return _shape(_sweep_ber_loop(psdus, rates_key, snr_flat,
                                      seed_flat, bits, profiles_key,
                                      sco_track))
    # host pull outside the timed block (jaxlint R2): the site times
    # the dispatch, not the device wait. On an async backend a
    # mid-execution failure surfaces at THIS pull — one guarded
    # re-dispatch (fresh donated buffer), then the loop twin
    try:
        errs = np.asarray(out, np.int64)
    except Exception:            # noqa: BLE001 - async loss
        try:
            out = resilience.guarded(
                "link.sweep", _sweep_dispatch, sweep_fn, bits_d,
                snr_d, seed_d, n_points, n_prof * len(rates_key))
            errs = np.asarray(out, np.int64)
        except Exception:        # noqa: BLE001 - degrade to the loop
            _note_link_degraded("link.sweep_degraded")
            return _shape(_sweep_ber_loop(psdus, rates_key, snr_flat,
                                          seed_flat, bits,
                                          profiles_key, sco_track))
    dispatch.record_gauge("link.degraded_mode", 0.0)   # healthy pass
    return _shape(errs)


def _sweep_ber_loop(psdus, rates_key, snr_flat, seed_flat, bits,
                    profiles_key=None,
                    sco_track: bool = False) -> np.ndarray:
    """The sweep's degraded twin: the python loop of per-batch
    `loopback_ber_bits` steps over the same (snr, seed[, profile])
    points — the exact loop `sweep_ber` is pinned integer-identical
    against (loopback_ber_bits applies a point's profile through the
    SAME `impair_profile_point_graph` at the SAME split keys). ~3
    host round trips per point instead of one total, but counts are
    bit-identical; used only when the compiled sweep fails for good.
    Returns flat (points, n_prof * n_rates) counts, profile-major —
    the caller owns the waterfall reshape."""
    n_rates = len(rates_key)
    profs = profiles_key or (None,)
    errs = np.zeros((len(snr_flat), len(profs) * n_rates), np.int64)
    for p, (snr, seed) in enumerate(zip(snr_flat, seed_flat)):
        for pi, pname in enumerate(profs):
            for r, m in enumerate(rates_key):
                got = loopback_ber_bits(psdus, m, float(snr),
                                        int(seed), profile=pname,
                                        sco_track=sco_track)
                errs[p, pi * n_rates + r] = int((got != bits).sum())
    return errs


def sweep_ber_sharded(psdus, rates_mbps: Sequence[int],
                      snr_grid: Sequence[float], seeds: Sequence[int],
                      mesh=None, axis: str = "dp",
                      profiles: Optional[Sequence] = None,
                      sco_track: Optional[bool] = None) -> np.ndarray:
    """`sweep_ber` with the frame-lane axis sharded over a device mesh
    (`parallel/batch.frame_mesh()` by default — every visible chip):
    each device encodes/impairs/decodes its shard of lanes, XLA
    inserts the error-count reduction. Error counts are exact integer
    sums, so the result is bit-identical to the single-device sweep on
    ANY mesh shape — on 1 device this IS `sweep_ber` — and the frame
    batch must divide the mesh (`shard_batch`'s rule). The MULTICHIP
    dryrun (`__graft_entry__.dryrun_multichip`) pins the multi-device
    path; `parallel/batch.data_parallel` is the same placement pattern
    this reuses. The profile axis shards with it (per-lane profile
    ops are lane-local — no new collectives)."""
    from ziria_tpu.parallel import batch as pbatch

    if mesh is None:
        mesh = pbatch.frame_mesh()
    return sweep_ber(psdus, rates_mbps, snr_grid, seeds,
                     profiles=profiles, sco_track=sco_track,
                     _shard=lambda x: pbatch.shard_batch(mesh, x, axis))
