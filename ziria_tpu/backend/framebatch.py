"""Frame batching for chunked state machines: N independent streams,
one device call per step.

The reference ran one PHY pipeline per thread and scaled frames by
adding threads (SURVEY.md §2.2 thread separators); a TPU behind a host
link scales the other way — batch the *device work* of many frames into
single calls so the per-call round-trip (tens of ms over a remote
host link) amortizes across frames. The library receiver already does this
with a leading frame axis (phy/wifi/rx.py). This module gives the same
economics to ANY compiled `.zir` program (VERDICT r3 next #3): a
1000-byte DSL receive costs ~8 device calls; 16 frames through
`run_many` cost ~the same 8 vmapped calls, not 128.

Design — continuation batching over the interpreter:

- each frame runs the normal interpreter/hybrid executor in its own
  thread (host control flow stays per-frame Python: divergent rates,
  ragged lengths, interpreter EOF tails all Just Work);
- when a frame's `_ChunkLoop` needs a device step it *parks* its
  request in the shared :class:`StepBatcher` (`chunked._step_call`
  routes here via a thread-local);
- when every unfinished frame is parked, the quorum thread fires:
  requests are grouped by (machine, jit key, operand shapes), each
  group's operands are stacked and run through ONE `jax.vmap`-ped step
  — JAX's `lax.while_loop` batching rule executes while ANY lane's
  guard holds and `select`s per-lane carries, so lanes consume their
  own cursors/iteration counts and bit-exactness per lane is preserved
  — and every parked frame resumes with its lane of the result.

Frames that drift to different program points simply land in different
groups (two smaller calls); frames in lockstep — the common case for
same-shape captures — ride one call. Lane counts are padded to the
next power of two (lane 0 repeated) so XLA compiles O(log N) batched
variants, not one per group size.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Any, List, NamedTuple, Optional, Sequence

import numpy as np

from ziria_tpu.backend import chunked as C
from ziria_tpu.core import ir
from ziria_tpu.utils import geometry as _geometry
from ziria_tpu.utils.dispatch import pad_lanes, pow2_ceil


def _shape_sig(args):
    import jax
    return tuple(
        (tuple(np.shape(x)), np.asarray(x).dtype.str) if not hasattr(
            x, "aval") else (tuple(x.shape), x.dtype.str)
        for x in jax.tree_util.tree_leaves(args))


class _Req:
    __slots__ = ("node", "key", "args", "done", "result", "exc")

    def __init__(self, node, key, args):
        self.node = node
        self.key = key
        self.args = args
        self.done = False
        self.result = None
        self.exc: Optional[BaseException] = None


class StepBatcher:
    """Collects concurrent chunk-step requests from frame threads and
    services them in vmapped groups. `device_calls` counts actual
    device dispatches (one per fired group) — the number the frame-
    batching contract is about."""

    def __init__(self, n_frames: int):
        self._cv = threading.Condition()
        self._active = n_frames
        self._parked: List[_Req] = []
        self._vfns = {}
        self.device_calls = 0
        self.group_sizes: List[int] = []   # fired lane counts (stats)

    # -- frame lifecycle ------------------------------------------------

    def frame_finished(self) -> None:
        with self._cv:
            self._active -= 1
            if self._parked and len(self._parked) >= self._active:
                self._fire_locked()

    # -- the park point (called from chunked._step_call) ---------------

    def call(self, node, key, args):
        req = _Req(node, key, args)
        with self._cv:
            self._parked.append(req)
            if len(self._parked) >= self._active:
                self._fire_locked()
            while not req.done:
                self._cv.wait()
        if req.exc is not None:
            raise req.exc
        return req.result

    # -- firing ---------------------------------------------------------

    def _vfn(self, node, key):
        import jax
        k = (id(node), key)
        f = self._vfns.get(k)
        if f is None:
            f = jax.jit(jax.vmap(node._steps[key]))
            self._vfns[k] = f
        return f

    def _fire_locked(self) -> None:
        batch, self._parked = self._parked, []
        try:
            self._service(batch)
        finally:
            # every parked thread MUST wake whatever happened above —
            # a request left done=False would wait forever
            for r in batch:
                if not r.done:
                    if r.exc is None and r.result is None:
                        r.exc = RuntimeError(
                            "step batch aborted before this lane ran")
                    r.done = True
            self._cv.notify_all()

    def _service(self, batch: List[_Req]) -> None:
        import jax
        import jax.numpy as jnp

        groups = {}
        for r in batch:
            sig = (id(r.node), r.key, _shape_sig(r.args))
            groups.setdefault(sig, []).append(r)
        for reqs in groups.values():
            try:
                if len(reqs) == 1:
                    r = reqs[0]
                    r.result = r.node._fns[r.key](*r.args)
                else:
                    lanes = len(reqs)
                    padded = pad_lanes(reqs)
                    stacked = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs),
                        *[r.args for r in padded])
                    it_b, pos_b, out_n_b, out_buf_b, rvals_b = \
                        self._vfn(reqs[0].node, reqs[0].key)(*stacked)
                    # every lane's (it, pos, out_n) in ONE transfer,
                    # and every lane's emitted prefix in one more: per
                    # -lane scalar reads and per-lane buffer flushes
                    # through a high-latency host link would cost a
                    # round trip each and dwarf the batched call
                    metas = np.asarray(jnp.stack(
                        [it_b, pos_b, out_n_b], axis=1))
                    bufs = None
                    if getattr(out_buf_b, "ndim", 0) >= 2:
                        max_k = int(metas[:lanes, 2].max())
                        if max_k:
                            bufs = np.asarray(
                                out_buf_b[:lanes, :max_k])
                    for i, r in enumerate(reqs):
                        ob = bufs[i] if bufs is not None \
                            else out_buf_b[i]
                        r.result = (metas[i, 0], metas[i, 1],
                                    metas[i, 2], ob,
                                    jax.tree_util.tree_map(
                                        lambda x, i=i: x[i], rvals_b))
                C.STATS["device_calls"] += 1
                self.device_calls += 1
                from ziria_tpu.utils import dispatch
                dispatch.record("framebatch.step")
                self.group_sizes.append(len(reqs))
            except Exception:
                # a vmap-only failure must not abort frames whose
                # per-frame step is fine (or worse, mark the shared
                # machine broken): retry each lane unbatched; only a
                # lane whose OWN direct call fails gets the exception
                for r in reqs:
                    try:
                        r.result = r.node._fns[r.key](*r.args)
                        C.STATS["device_calls"] += 1
                        self.device_calls += 1
                        from ziria_tpu.utils import dispatch
                        dispatch.record("framebatch.step")
                        self.group_sizes.append(1)
                    except Exception as le:
                        r.exc = le
            for r in reqs:
                r.done = True


def batched_acquire_enabled(batched_acquire: Optional[bool] = None) -> bool:
    """The ONE reading of the --batched-acquire / ZIRIA_BATCHED_ACQUIRE
    knob (default ON): whether `receive_many` runs the one-dispatch
    vmapped acquisition front end or the host-driven per-capture loop.
    Hoisted out of `receive_many`'s body by the jaxlint R4 audit — the
    single-reader discipline every other knob here already follows."""
    import os

    if batched_acquire is not None:
        return batched_acquire
    return os.environ.get("ZIRIA_BATCHED_ACQUIRE", "1") != "0"


def receive_many(captures: Sequence[Any], check_fcs: bool = False,
                 max_samples: int = 1 << 16,
                 viterbi_window: int = None,
                 viterbi_metric: str = None,
                 viterbi_radix: int = None,
                 batched_acquire: Optional[bool] = None,
                 sco_track: Optional[bool] = None,
                 fused_demap: Optional[bool] = None) -> List[Any]:
    """Frame-batched library receiver: N independent captures -> N
    :class:`rx.RxResult`s in O(1) device dispatches — acquire ->
    gather -> mixed-rate decode:

    1. **acquire** (`rx.acquire_many`): STS detect, LTS peak-pick,
       CFO, on-device alignment, and SIGNAL decode for ALL lanes as
       ONE vmapped dispatch; the host does only the integer header
       parsing and the symbol-bucket choice.
    2. **gather** (`rx.gather_segments_many`): every decodable lane's
       data region sliced at its own offset and derotated by its own
       CFO phase at ONE common symbol bucket — one dispatch, output
       device-resident.
    3. **decode** (`rx.decode_data_mixed`): the one-``lax.switch``
       mixed-rate DATA decode — lanes with DIFFERENT rates share the
       same device call and the same Pallas Viterbi batch; each row
       it returns is `params.mixed_trellis_steps(bucket)` bits long
       (the bucket at 54 Mbit/s, bound by the longest legal frame).

    ``batched_acquire=False`` (or env ``ZIRIA_BATCHED_ACQUIRE=0``)
    falls back to the host-driven per-capture acquisition loop (~3
    round trips per capture — the pre-batched oracle). Either way,
    results are bit-identical to per-capture ``rx.receive`` lane for
    lane, including no-detect / bad-parity / truncated lanes; lane
    counts pad to the next power of two (lane 0 repeated) so XLA
    compiles O(log N) batch variants.

    ``viterbi_radix=4`` runs the mixed decode's Pallas ACS two trellis
    steps per iteration (bit-identical); ``fused_demap=True`` (env
    ``ZIRIA_FUSED_DEMAP``) runs the rate-SWITCHED fused front end —
    the stacked 8-rate constant bank row-selected in-kernel, LLRs
    never leaving VMEM (rx.viterbi_decode_mixed_fused) — on the same
    one-dispatch mixed decode, bit-identical lane for lane.
    """
    import jax.numpy as jnp

    from ziria_tpu.phy.wifi import rx as _rx

    batched_acquire = batched_acquire_enabled(batched_acquire)
    sco_track = _rx.sco_track_enabled(sco_track)
    fused_demap = _rx.fused_demap_enabled(fused_demap)

    results: List[Any] = [None] * len(captures)
    if batched_acquire:
        results, x_dev, acqs = _rx.acquire_many(captures, max_samples)
    else:
        acqs = []
        for i, s in enumerate(captures):
            res, acq = _rx._acquire_frame(s, max_samples)
            if acq is None:
                results[i] = res
            else:
                acqs.append((i, acq))
    if not acqs:
        return results

    # one common bucket = one compiled geometry for the whole batch;
    # smaller frames pay pad symbols (zero-LLR erasures), not a second
    # compile or a second dispatch
    n_sym_b = max(_rx._sym_bucket(a.n_sym) for _i, a in acqs)
    padded = pad_lanes(acqs)
    if batched_acquire:
        segs = _rx.gather_segments_many(
            x_dev, [a for _i, a in padded], n_sym_b)
    else:
        segs = jnp.stack([_rx._padded_segment(a, n_sym_b)
                          for _i, a in padded])
    return _mixed_decode_tail(acqs, padded, segs, n_sym_b, results,
                              check_fcs, viterbi_window, viterbi_metric,
                              viterbi_radix, sco_track, fused_demap)


def _mixed_decode_tail(acqs, padded, segs, n_sym_b: int,
                       results: List[Any], check_fcs: bool,
                       viterbi_window, viterbi_metric,
                       viterbi_radix=None, sco_track: bool = False,
                       fused_demap: bool = False):
    """The shared tail of every batched receive surface: ONE
    mixed-rate decode dispatch over the lane-padded segments, plus —
    when FCS checking is on — ONE vmapped masked-CRC dispatch at the
    common bucket over the still-device-resident decode output
    (previously a hidden host `check_crc32` dispatch PER LANE), then
    the per-lane PSDU slice. CRC booleans are bit-identical to the
    per-lane path (`ops/crc.check_crc32_masked`, loop-free, gives the
    table scan's verdicts). `acqs` is [(i, acq)] for the real lanes
    (acq needs .rate_mbps/.n_sym/.length_bytes — both the host
    `_Acquired` and batched `_LaneAcq` shapes qualify); `padded` is
    THE pad_lanes list the caller built `segs` from — passed in, not
    recomputed, so the ridx/nbits rows can never disagree with the
    segment rows."""
    import jax.numpy as jnp

    from ziria_tpu.ops.viterbi import _check_radix
    from ziria_tpu.phy.wifi import rx as _rx
    from ziria_tpu.phy.wifi.params import N_SERVICE_BITS, RATES
    from ziria_tpu.utils import dispatch, programs

    ridx = jnp.asarray([_rx.RATE_INDEX[a.rate_mbps] for _i, a in padded],
                       jnp.int32)
    nbits = jnp.asarray(
        [a.n_sym * RATES[a.rate_mbps].n_dbps for _i, a in padded],
        jnp.int32)
    dec = _rx._jit_decode_data_mixed(n_sym_b, viterbi_window,
                                     viterbi_metric,
                                     _check_radix(viterbi_radix),
                                     sco_track, fused_demap)
    programs.note_site("rx.decode_mixed", dec, segs, ridx, nbits)
    with dispatch.timed("rx.decode_mixed"):
        clear_dev = dec(segs, ridx, nbits)
    crc_b = None
    if check_fcs:
        npsdu = jnp.asarray([8 * a.length_bytes for _i, a in padded],
                            jnp.int32)
        crc_fn = _rx._jit_crc_many()
        programs.note_site("rx.crc_many", crc_fn, clear_dev, npsdu)
        # host pull outside the timed block (jaxlint R2): the site
        # times the dispatch, not the device wait
        with dispatch.timed("rx.crc_many"):
            crc_dev = crc_fn(clear_dev, npsdu)
        crc_b = np.asarray(crc_dev)
    clear = np.asarray(clear_dev, np.uint8)
    for k, (i, a) in enumerate(acqs):
        psdu = clear[k][N_SERVICE_BITS: N_SERVICE_BITS
                        + 8 * a.length_bytes]
        crc = bool(crc_b[k]) if check_fcs else None
        results[i] = _rx.RxResult(True, a.rate_mbps, a.length_bytes,
                                  psdu, crc)
    return results


def receive_many_device(x_dev, n_lanes: int, check_fcs: bool = False,
                        viterbi_window: int = None,
                        viterbi_metric: str = None,
                        viterbi_radix: int = None,
                        sco_track: Optional[bool] = None,
                        fused_demap: Optional[bool] = None) -> List[Any]:
    """Batched receive over an ALREADY device-resident capture batch —
    the RX side of the loopback link (phy/link.py): the channel's
    output feeds acquisition without the samples ever crossing the
    host link.

    x_dev: (R, L, 2) device array, R a power-of-two lane count (rows
    past `n_lanes` repeating row 0 — the pad_lanes rule) and L a
    power-of-two >= 512 capture bucket; the WHOLE buffer of every lane
    is its capture (n_valid = L: the batched channel fills it with
    real air samples). Three dispatches — acquire -> gather -> mixed
    decode — with results bit-identical to per-capture `rx.receive`
    over `np.asarray(x_dev[i])`."""
    from ziria_tpu.phy.wifi import rx as _rx

    l_cap = int(x_dev.shape[1])
    if l_cap != _rx._stream_bucket(l_cap):
        raise ValueError(
            f"capture length {l_cap} is not a power-of-two >= 512 "
            f"bucket; per-capture receive would pad to "
            f"{_rx._stream_bucket(l_cap)} and the identity contract "
            f"needs identical geometry")
    nv = np.full((int(x_dev.shape[0]),), l_cap, np.int32)
    results, lanes = _rx.acquire_batch(x_dev, nv, nv, n_lanes)
    if not lanes:
        return results
    n_sym_b = max(_rx._sym_bucket(a.n_sym) for _i, a in lanes)
    padded = pad_lanes(lanes)
    segs = _rx.gather_segments_many(
        x_dev, [a for _i, a in padded], n_sym_b)
    return _mixed_decode_tail(lanes, padded, segs, n_sym_b, results,
                              check_fcs, viterbi_window, viterbi_metric,
                              viterbi_radix,
                              _rx.sco_track_enabled(sco_track),
                              _rx.fused_demap_enabled(fused_demap))


# ------------------------------------------------------ streaming receiver
#
# `receive_many` serves a *batch of pre-segmented captures*; the
# reference runtime serves *streams* — unbounded I/Q sample flows with
# many frames at unknown offsets, and "millions of users" is MANY of
# them on one device fleet. ONE receiver closes that gap: the push-
# driven `MultiStreamReceiver` (`receive_streams` its whole-stream
# wrapper) cuts each of S independent streams into fixed-size
# overlapping chunks, stacks one chunk per stream on a leading STREAM
# AXIS, and runs each stacked chunk-step through the two compiled
# streaming programs (`rx._jit_stream_chunk_multi`, the fused
# multi-peak scan `rx.stream_chunk_graph` under one vmap, then
# `rx._jit_stream_decode_multi`, the fixed-geometry mixed-rate decode
# over the flattened S*K lanes) — <= 2 dispatches per CHUNK-STEP,
# independent of S. A carried (tail samples, sample offset, frames
# emitted) state threads across each stream's chunks, so every frame
# is owned by exactly one chunk and decodes bit-identically to slicing
# `stream[start:start+frame_len]` out and calling per-capture
# `rx.receive` on it. Ragged arrival is handled host-side by a packer:
# a chunk-step fires only when at least one stream has a full chunk,
# streams without one ride the step as idle lanes behind a valid-mask
# (`valid == 0` → the detector caps their positions to nothing), and
# the all-noise fast path is preserved (a step with zero decodable
# lanes across the WHOLE fleet skips the decode dispatch entirely).
# The dispatch loop is a pipeline three chunk-steps deep (`_InFlight`):
# a launch uploads and dispatches scan t, dispatches decode t-1 behind
# it, and blocks only on decode t-2, so the device holds a scan and a
# decode while the host stacks the next step (in-flight depth on the
# `utils/dispatch.record_gauge("rx.stream_inflight")` gauge). A step's
# stacked host array comes from the receiver's own store and is written
# again only once nothing else holds it (`_Staging`); a pushed slab is
# written ONCE, straight into the array of the step it will ride, and
# the `frame_len` overlap is copied forward once (`_write`, `_step`). The
# stream axis shards over the dp mesh (`parallel/batch.frame_mesh` /
# `lane_sharding`, `jax.shard_map` — multihost-ready through
# `parallel/multihost.build_mesh`, dp being the axis with no
# steady-state collectives). A single stream is a fleet of one:
# `StreamReceiver` / `receive_stream` are that fleet's lane 0, unwrapped.


def streaming_rx_enabled(streaming: Optional[bool] = None) -> bool:
    """The ONE reading of the --streaming-rx / ZIRIA_STREAMING_RX knob
    (default ON): whether `receive_stream` runs the two-dispatch
    chunk path or the per-capture oracle (same detected windows, each
    sliced to the host and fed through `rx.receive` — >= 3 dispatches
    per frame, the identity contract made runnable)."""
    import os

    if streaming is not None:
        return streaming
    return os.environ.get("ZIRIA_STREAMING_RX", "1") != "0"


class StreamFrame(NamedTuple):
    """One emitted frame of a streamed receive: `start` is the
    stream-coordinate window start (the LTS-aligned frame start for
    clean frames), `result` the `rx.RxResult` of per-capture
    `rx.receive(stream[start : start + frame_len])` — bit-identical
    by construction, failures included."""
    start: int
    result: Any


class StreamCarry(NamedTuple):
    """The cross-chunk carry the receiver threads internally: the
    not-yet-owned tail samples (a copy, gathered from the staging
    arrays they wait in), the stream coordinate of their first
    sample, the frames emitted so far, and the dedupe watermark (the
    offset below which no future chunk can re-own a start — the
    `_seen` set holds only entries at or above it, O(K) per stream).
    Exposed read-only per lane via :meth:`MultiStreamReceiver.carry`
    (and :attr:`StreamReceiver.carry`) for observability and
    tests — to continue a stream across slabs, keep pushing into the
    SAME receiver (the carry is its live state, not a detached resume
    token)."""
    tail: np.ndarray
    offset: int
    emitted: int
    watermark: int = 0


def _chunk_candidates(seen, off, own, starts, k: int):
    """The dedupe/ownership core of a lane's drain (the trickiest
    host logic, kept apart so it reads alone): prune `seen` to the
    watermark `off` (starts are non-decreasing across chunks, so no
    future chunk can re-own a start below it — the receiver holds
    O(K) entries, not one per frame ever emitted), then collect the
    chunk's owned, unseen (abs_start, lane row) candidates in stream
    order. Returns (pruned seen, candidates); the caller stores the
    pruned set and records `off` as the carry's watermark."""
    seen = {s for s in seen if s >= off}
    cands = []
    for j in range(k):
        if not own[j]:
            continue
        abs_start = off + int(starts[j])
        if abs_start in seen:
            continue             # safety net; ownership + dead
        seen.add(abs_start)      # zone already make starts unique
        cands.append((abs_start, j))
    cands.sort()
    return seen, cands


def _slab_array(samples, name: str) -> np.ndarray:
    """The push-seam shape/dtype gate (docs/robustness.md): coerce a
    pushed slab to (n, 2) float32 I/Q pairs or raise a ValueError
    NAMING the stream — malformed input fails at the seam, never as
    garbage inside the detector."""
    try:
        arr = np.asarray(samples, np.float32)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"{name}: pushed slab is not float-convertible "
            f"((n, 2) I/Q sample pairs expected): {e}") from None
    if arr.size == 0:
        return arr.reshape(0, 2)
    if arr.ndim != 2 or arr.shape[1] != 2:
        raise ValueError(
            f"{name}: pushed slab has shape {arr.shape}, want (n, 2) "
            f"I/Q sample pairs")
    return arr


class _LaneHealth:
    """Per-stream quarantine state: non-finite input poisons the lane
    immediately; ``blowup_limit`` repeated per-lane
    decode blowups poison it too; a poisoned lane rides behind the
    valid-mask (``valid == 0`` — its chunks scan to nothing, healthy
    lanes untouched by construction) and rejoins after
    ``rejoin_after`` consecutive clean chunks."""

    __slots__ = ("blowup_limit", "rejoin_after", "quarantined",
                 "clean", "blowups", "quarantines")

    def __init__(self, blowup_limit: int = 2, rejoin_after: int = 3):
        self.blowup_limit = max(1, int(blowup_limit))
        self.rejoin_after = max(1, int(rejoin_after))
        self.quarantined = False
        self.clean = 0          # consecutive clean chunks in quarantine
        self.blowups = 0        # consecutive per-lane decode blowups
        self.quarantines = 0    # times this lane entered quarantine

    def poison(self) -> None:
        if not self.quarantined:
            self.quarantines += 1
            from ziria_tpu.utils import telemetry
            telemetry.count("resilience.quarantines")
        self.quarantined = True
        self.clean = 0

    def blowup(self) -> None:
        self.blowups += 1
        if self.blowups >= self.blowup_limit:
            self.poison()
            self.blowups = 0

    def step(self, dirty: bool) -> bool:
        """Advance one consumed chunk; True = this chunk rides
        quarantined (valid 0). A dirty chunk resets the clean streak;
        rejoin takes effect from the chunk AFTER the streak fills.
        Blowups are NOT reset here: a chunk's blowups are delivered
        with its back half, up to two launches later than its step
        (the pipeline), so a per-step reset could never see two in a
        row — the count accumulates until the lane is poisoned or
        rejoins."""
        if dirty:
            self.clean = 0
            return self.quarantined
        if self.quarantined:
            self.clean += 1
            if self.clean >= self.rejoin_after:
                self.quarantined = False
                self.clean = 0
                self.blowups = 0
            return True
        return False


#: length classes of `rx.stream_frames_by_length` (PSDU bytes, FCS
#: included): an 802.11 ACK or CTS is 14, a TCP ACK under a hundred
_ACK_BYTES, _SHORT_BYTES = 16, 128


def _count_emitted(out, total: int) -> None:
    """The registry's view of the frames a chunk-step emitted: their
    number (`total` the receiver's running count, for the trace's
    counter track), and the decoded ones by length class (a PSDU, FCS
    included, of at most `_ACK_BYTES`, of at most `_SHORT_BYTES`, or
    longer), so that a class of frames that stopped coming out shows
    as a count and not as a rate."""
    from ziria_tpu.utils import telemetry

    if not out or not telemetry.active():
        return
    telemetry.count("rx.stream_frames", len(out), total=total)
    by_class = collections.Counter(
        "ack" if fr.result.length_bytes <= _ACK_BYTES
        else "short" if fr.result.length_bytes <= _SHORT_BYTES
        else "long" for _i, fr in out if fr.result.ok)
    for c, n in by_class.items():
        telemetry.count("rx.stream_frames_by_length", n,
                        labels={"psdu": c})


def _truncated(results) -> int:
    """How many of a chunk-step's results are frames the window could
    not hold: SIGNAL parsed, and the DATA field it announces runs past
    the samples the window (or, at a flush, the stream) has left
    (`rx.ACQ_TRUNCATED`; the one failure that still names its rate)."""
    return sum(1 for r in results if not r.ok and r.rate_mbps)


#: geometry keys that postdate shipped checkpoint blobs, mapped to
#: the behavior the pre-key code had (see _validate_checkpoint)
_LEGACY_GEOMETRY_DEFAULTS = {"sco_track": False, "fused_demap": False}


def _validate_checkpoint(st, mine: dict) -> None:
    """The checkpoint-geometry gate of ``restore_stream`` (and so of
    ``StreamReceiver(checkpoint=...)``, which restores through it):
    refuse a blob whose fingerprint is partial/absent (a raw
    ``checkpoint_carry`` without geometry must not restore into an
    arbitrary receiver) or disagrees with the restoring receiver."""
    from ziria_tpu.runtime import resilience

    # geometry fields added AFTER a blob format shipped, with the
    # value the old code behaved as: a legacy blob missing one of
    # these restores as that default instead of refusing — the old
    # decode program IS the default-mode program, so refusing would
    # throw away valid saved state on every deploy of a new knob
    geo = dict(st.geometry)
    for k_, v_ in _LEGACY_GEOMETRY_DEFAULTS.items():
        geo.setdefault(k_, v_)
    missing = [k_ for k_ in mine if k_ not in geo]
    if missing:
        raise resilience.CarryCheckpointError(
            f"checkpoint lacks geometry fields {missing}; "
            f"use StreamReceiver.checkpoint() (or pass the "
            f"receiver geometry to checkpoint_carry) so the "
            f"restore can be validated")
    bad = {k_: (geo[k_], mine[k_]) for k_ in mine
           if geo[k_] != mine[k_]}
    if bad:
        raise resilience.CarryCheckpointError(
            f"checkpoint geometry mismatch (checkpoint, "
            f"receiver): {bad}")


def _stream_geometry(r) -> dict:
    """The checkpoint geometry fingerprint — the fleet width is NOT
    part of it, so a lane's checkpoint restores into any fleet, a
    lone `StreamReceiver` included: everything a restoring receiver
    must match
    for bit-identical resumption — the detector parameters included,
    since different thresholds detect different frame starts."""
    return {"chunk_len": r.chunk_len, "frame_len": r.frame_len,
            "k": r.k, "n_sym_bucket": r.n_sym_bucket,
            "check_fcs": bool(r.check_fcs),
            "threshold": r._threshold, "min_run": r._min_run,
            "dead_zone": r._dead_zone,
            "viterbi_window": r.viterbi_window,
            "viterbi_metric": r.viterbi_metric,
            "viterbi_radix": r.viterbi_radix,
            "sco_track": bool(r.sco_track),
            "fused_demap": bool(r.fused_demap)}


def _chunk_scalars(outs):
    """The nine per-lane arrays the host pulls of a chunk scan's
    eleven outputs: all but the float CFO estimate and the segments,
    which stay on the device for the decode (the estimate comes to
    the host to 24 Hz inside the rate word: `rx.pack_rate_word`)."""
    return outs[:5] + outs[6:10]


def _start_pull(arrays) -> None:
    """Send every shard of every array on its way to the host without
    blocking on any: the read that follows, a launch or two later,
    finds the bytes on the host, and on a mesh waits for the slowest
    device once, not for each transfer in turn (a sharded fleet's nine
    scan scalars are 36 transfers, its decode's pull 8)."""
    for x in arrays:
        x.copy_to_host_async()


def _ready(arrays) -> bool:
    """Whether a read of every array would return without waiting for
    the device. What has no ``is_ready`` (a host array of the eager
    or oracle twin) is ready; one whose answer raises is too, so that
    the guarded read that follows meets the failure."""
    try:
        return all(x.is_ready() for x in arrays
                   if hasattr(x, "is_ready"))
    except Exception:        # noqa: BLE001 - the read will say why
        return True


def _host_lanes(x):
    """A decode output on the host, indexable by lane. Read whole
    (`np.asarray`), an array that lies over a mesh is built anew on
    the host and every shard copied into it (8.4 MB a chunk-step at
    32 lanes, 7 ms of the one thread that paces that fleet); each
    shard's own host copy, on its way since the dispatch, is read
    where it lies, and a lane is a row of one of them."""
    shards = getattr(x, "addressable_shards", ())
    if len(shards) < 2:
        return np.asarray(x)
    # by first lane: in order, and a replica read once
    first = {sh.index[0].start or 0: sh.data for sh in shards}
    return [row for at in sorted(first) for row in np.asarray(first[at])]


def _pull_chunk(outs, span):
    """Materialize a chunk scan's per-lane scalars on the host. On an
    ASYNC backend a runtime failure mid-execution surfaces HERE, at
    the first host pull, not inside the guarded dispatch — callers
    wrap this and re-run the chunk through the guarded path when it
    throws (the launched results are lost either way). `segs` stays
    device-resident for the decode dispatch. ``span`` (name, args)
    is opened around the blocking pulls alone."""
    from ziria_tpu.utils import telemetry

    scalars, segs = _chunk_scalars(outs), outs[10]
    with telemetry.span(*span):
        return tuple(np.asarray(x) for x in scalars) + (segs,)


def _record_degraded(entered: bool) -> None:
    """The degrade-visibility ritual: the rx.degraded_mode gauge level
    plus — on entry — the resilience.degraded counter. A fleet quietly
    running its slow twin must be visible in a trace, not discovered
    in a latency graph."""
    from ziria_tpu.utils import dispatch, telemetry
    dispatch.record_gauge("rx.degraded_mode", 1.0 if entered else 0.0)
    if entered:
        telemetry.count("resilience.degraded")


def _dispatch_decode(r, st) -> None:
    """The guarded decode dispatch of a chunk-step's front half, its
    two outputs sent on their way to the host at once: ``st.dec_out``
    is (clear, crc) on the device, or None with the receiver marked
    degraded when the compiled program failed for good (a decode that
    does not trace or compile raises out of guarded() as itself,
    `resilience.compile_ahead`: only a RUN-time failure degrades)."""
    from ziria_tpu.runtime import resilience

    try:
        st.dec_out = resilience.guarded(
            "rx.stream_decode_multi", st.dec, *st.dec_args,
            policy=r._policy, span_args={"step": st.step})
        _start_pull(st.dec_out)
    except resilience.DispatchFailed:
        st.dec_out = None
        r._mark_degraded(scan=False)


def _pull_decode(r, st):
    """The host read of a chunk-step's decode, in its back half: an
    async runtime failure surfaces HERE, a launch after the dispatch
    returned, so the read lives inside the same containment — one
    guarded re-dispatch from the ``segs`` and tables the step kept,
    then None, with the receiver marked degraded so the caller (and
    the rest of the stream) runs the oracle twin. Returns (clear, crc)
    as host arrays, or None. `rx.fleet.pull_decode` is opened around
    the blocking read alone."""
    from ziria_tpu.utils import telemetry

    for attempt in (0, 1):
        if st.dec_out is None:       # the dispatch failed for good
            return None
        clear, crc = st.dec_out
        try:
            with telemetry.span("rx.fleet.pull_decode", {
                    "step": st.step, "shards": 2 * r._n_devices,
                    "bytes": int(clear.nbytes + crc.nbytes),
                    "reads": 1, "ready": int(_ready(st.dec_out)),
                    "how": st.how}):
                return _host_lanes(clear), np.asarray(crc)
        except Exception:        # noqa: BLE001 - async pull loss
            if attempt:
                break
            telemetry.count("resilience.async_rescans")
            _dispatch_decode(r, st)
    r._mark_degraded(scan=False)
    return None


class _InFlight:
    """One chunk-step between its launch and its emission. The FRONT
    half of its drain (`MultiStreamReceiver._front`) reads the scan's
    scalars, classifies and dispatches the decode; the BACK half
    (`_drain`) reads the decode and emits. Each half keeps here what
    the next one, or its containment, needs: the host arrays of the
    step (a lost scan is rescanned from them, the oracle twin slices
    its windows out of them, the next step's carry reads its lanes'
    overlap from them; ``arrs`` is the store's, `_Staging`, and
    holding it here is what keeps it this step's), the scan's outputs
    (`segs` among them, the decode's input and its re-dispatch's), and
    the decode's tables and outputs. ``step`` tags every span of either half, and
    ``how`` says which way the half now running was reached (behind a
    ``"launch"``, by a call that launched nothing and found it
    ``"ready"``, at a ``"drain"`` point): the two pull spans carry it,
    so that a read that waited is told from one that did not have to."""

    __slots__ = ("step", "offs", "active", "arrs", "valid", "own_lo",
                 "own_hi", "outs", "fronted", "allcands", "starts",
                 "oracle", "emit", "lanes", "slots", "dec", "dec_args",
                 "dec_out", "how", "acquired")

    def __init__(self, step, offs, active, arrs, valid, own_lo, own_hi,
                 outs):
        self.step, self.offs, self.active = step, offs, active
        self.arrs, self.valid = arrs, valid
        self.own_lo, self.own_hi, self.outs = own_lo, own_hi, outs
        self.fronted = False
        self.allcands = self.starts = None
        self.oracle = False
        self.emit = self.lanes = self.slots = None
        self.dec = self.dec_args = self.dec_out = None
        self.how = "launch"
        self.acquired = 0


def _unheld_refs() -> int:
    """What `sys.getrefcount` reads of an object that one list holds
    and nothing else, asked the way `_Staging.take` asks: measured, so
    that no interpreter's calling convention is assumed."""
    probe = [object()]
    return sys.getrefcount(probe[0])


class _Staging:
    """The host arrays a receiver stacks its chunk-steps in: made once,
    used again. ONE rule says when: an array is written again only when
    nothing but this store holds it, read from the interpreter's own
    count of references as an array is asked for. The receiver holds
    the array it is FILLING (`MultiStreamReceiver._fill`: pushed slabs
    are written into it as they come) and the one it launched last
    while a lane's overlap still waits in it (`_prev`), a step in
    flight holds its array (`_InFlight.arrs`: a lost scan is re-put
    from it, the oracle twin slices its windows out of it), a put whose
    copy has not finished holds it (the runtime keeps the array, or the
    shard views whose base it is, until it is done with the memory),
    and so does a caller that kept what `_pending` showed it; each lets
    go by
    dropping its reference, a step that leaves flight by an exception
    too, and there is no release call to forget. An array somebody
    still holds is passed over and stays theirs for as long as they
    keep it; where every array is held a new one is made, so the store
    grows to the most that were ever held at once, and one.

    ``stale[i]`` beside an array says lane i still holds samples of
    an earlier use beyond what has been written into it since: `_step`
    zeroes such a lane where the new step leaves it idle, so idle lanes
    ride zeros as they do in a new array, and nothing is zeroed where
    every lane is carried."""

    _UNHELD = _unheld_refs()

    def __init__(self, n_lanes: int, chunk_len: int):
        self._shape = (n_lanes, chunk_len, 2)
        self._arrays: List[np.ndarray] = []
        self._stale: List[np.ndarray] = []

    @property
    def nbytes(self) -> int:
        """Host memory the store keeps."""
        return sum(a.nbytes for a in self._arrays)

    def take(self):
        """``(array, stale, fresh)`` for a step to be filled: the
        first array nobody holds, or a new one of zeros (``fresh``)."""
        for k in range(len(self._arrays)):
            if sys.getrefcount(self._arrays[k]) == self._UNHELD:
                return self._arrays[k], self._stale[k], False
        self._arrays.append(np.zeros(self._shape, np.float32))
        self._stale.append(np.zeros(self._shape[0], bool))
        return self._arrays[-1], self._stale[-1], True


def _gate_finite(arr: np.ndarray, name: str, sanitize: bool,
                 health: "_LaneHealth"):
    """The non-finite gate behind the shape gate of the push seam:
    reject with an error NAMING the stream — or, under
    ``sanitize=True``, zero the poisoned samples and quarantine the
    lane. Returns ``(arr, n_bad)``; the caller owns its own dirty
    flag and sanitized counter."""
    if arr.size == 0:
        return arr, 0
    bad = ~np.isfinite(arr)
    if not bad.any():
        return arr, 0
    n_bad = int(bad.any(axis=-1).sum())
    if not sanitize:
        raise ValueError(
            f"{name}: pushed slab carries {n_bad} non-finite "
            f"sample(s); reject at the source or construct the "
            f"receiver with sanitize=True to zero-and-quarantine")
    arr = np.where(bad, np.float32(0), arr)
    health.poison()
    from ziria_tpu.utils import telemetry
    telemetry.count("resilience.sanitized", n_bad)
    return arr, n_bad


class MultiStreamStats(NamedTuple):
    streams: int               # S, the fleet width
    chunk_steps: int           # fleet scan dispatches issued
    frames: int                # StreamFrames emitted, all streams
    overflow_chunks: int       # per-stream chunk overflow flags raised
    max_in_flight: int         # high-water chunk-steps in flight
    max_active_streams: int    # high-water active lanes in one step
    sanitized: int = 0         # non-finite samples zeroed, fleet-wide
    quarantines: int = 0       # quarantine entries, fleet-wide
    quarantined_streams: int = 0   # streams quarantined RIGHT NOW
    lane_blowups: int = 0      # per-window oracle blowups caught
    degraded: bool = False     # a compiled fleet program degraded
    truncated_frames: int = 0  # owned frames longer than the window


class MultiStreamReceiver:
    """Push-driven S-stream receiver: feed per-stream sample slabs
    with :meth:`push` (one stream) or :meth:`push_many` (a slab per
    stream), close with :meth:`flush`; all return the
    ``(stream, StreamFrame)`` pairs that became decodable.

    Geometry, PER STREAM: `chunk_len` samples per scan with
    `frame_len` of overlap between consecutive chunks (`frame_len`
    must be a power-of-two >= 512 capture bucket covering the longest
    frame a stream may carry, so a frame starting anywhere in a
    chunk's OWNED region — the first `chunk_len - frame_len` samples —
    lies fully inside that chunk). Starts detected in the overlap
    re-detect fully inside the next chunk and are owned there: every
    frame is decoded exactly once. Up to `max_frames_per_chunk` frames
    are extracted per chunk per stream; more raises the chunk's
    overflow flag (counted in :attr:`stats` — reported, never silently
    dropped; widen K or shorten the chunk). An owned frame LONGER than
    `frame_len` (or cut by the stream's end at a flush) is reported
    the same way: emitted where it started as a failed result that
    names its rate and length, and counted (`stats.truncated_frames`,
    the counter `rx.stream_frames_truncated`, `truncated` on
    `rx.fleet.emit`; widen the window). Each stream steps through
    its own chunk boundaries whatever its lane-mates do, and per-lane
    graphs under vmap are the one-stream graphs, so a lane's frames
    are bit-identical to that stream received alone — by
    construction. One chunk-step = one stacked (S, chunk_len, 2)
    upload + ONE vmapped scan dispatch (+ ONE flattened decode
    dispatch when any stream has a decodable frame), pipelined three
    deep: a chunk-step's frames come out of the second launch after
    its own, or of an earlier call that launches nothing and finds
    them ready; :meth:`drain_pending` hands them over now.
    ``streaming=False`` runs the per-capture oracle over the same
    detected windows in place of the compiled decode. `mesh` shards the
    stream axis over dp (`S % mesh.size == 0`); per-stream carries
    (:class:`StreamCarry`, dedupe watermark included) are visible via
    :meth:`carry`/:attr:`carries`."""

    def __init__(self, n_streams: Optional[int] = None,
                 chunk_len: Optional[int] = None,
                 frame_len: Optional[int] = None,
                 max_frames_per_chunk: Optional[int] = None,
                 check_fcs: bool = False,
                 threshold: Optional[float] = None,
                 min_run: Optional[int] = None,
                 dead_zone: Optional[int] = None,
                 viterbi_window: int = None, viterbi_metric: str = None,
                 viterbi_radix: int = None, mesh=None,
                 axis: str = "dp", sanitize: bool = False,
                 max_retries: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 blowup_limit: int = 2, rejoin_after: int = 3,
                 sco_track: Optional[bool] = None,
                 fused_demap: Optional[bool] = None,
                 geometry: Optional[_geometry.Geometry] = None,
                 streaming: bool = True):
        from ziria_tpu.ops import sync as _sync
        from ziria_tpu.ops.viterbi import _check_radix
        from ziria_tpu.phy.wifi import rx as _rx
        from ziria_tpu.runtime import resilience
        from ziria_tpu.utils import dispatch

        # ONE declarative geometry supplies every default the caller
        # leaves None (explicit per-knob args still win); the default
        # Geometry IS the historical constants — same compiled
        # programs, same checkpoint fingerprint, same bits. The fleet
        # width S rides the same object as the chunk geometry, so
        # MultiStreamReceiver(geometry=g) builds the whole fleet
        geo = geometry if geometry is not None else _geometry.DEFAULT
        n_streams = geo.n_streams if n_streams is None else n_streams
        chunk_len = geo.chunk_len if chunk_len is None else chunk_len
        frame_len = geo.frame_len if frame_len is None else frame_len
        max_frames_per_chunk = (geo.max_frames_per_chunk
                                if max_frames_per_chunk is None
                                else max_frames_per_chunk)
        threshold = geo.threshold if threshold is None else threshold
        min_run = geo.min_run if min_run is None else min_run
        dead_zone = geo.dead_zone if dead_zone is None else dead_zone
        viterbi_window = (geo.viterbi_window if viterbi_window is None
                          else viterbi_window)
        viterbi_metric = (geo.viterbi_metric if viterbi_metric is None
                          else viterbi_metric)
        viterbi_radix = (geo.viterbi_radix if viterbi_radix is None
                         else viterbi_radix)
        sco_track = geo.sco_track if sco_track is None else sco_track
        fused_demap = (geo.fused_demap if fused_demap is None
                       else fused_demap)

        if n_streams < 1:
            raise ValueError(f"n_streams {n_streams} must be >= 1")
        if frame_len != geo.capture_bucket(frame_len):
            raise ValueError(
                f"frame_len {frame_len} is not a power-of-two >= "
                f"{geo.capture_bucket_min} capture bucket; per-capture "
                f"receive would pad to {geo.capture_bucket(frame_len)} "
                f"and the identity contract needs identical geometry")
        if chunk_len <= frame_len:
            raise ValueError(
                f"chunk_len {chunk_len} must exceed the frame_len "
                f"{frame_len} overlap (the owned region would be empty)")
        if mesh is not None and n_streams % mesh.size:
            raise ValueError(
                f"n_streams {n_streams} must divide the mesh "
                f"({mesh.size} devices): the stream axis shards evenly "
                f"(shard_batch's rule)")
        self.s = int(n_streams)
        self.chunk_len = int(chunk_len)
        self.frame_len = int(frame_len)
        self.stride = self.chunk_len - self.frame_len
        self.k = int(max_frames_per_chunk)
        # the largest DATA field a frame_len window can hold, bucketed:
        # the fleet's ONE fixed decode geometry (longer frames are
        # ACQ_TRUNCATED in both paths — the window cannot hold them —
        # and counted: `_count_truncated`)
        self.n_sym_bucket = geo.sym_bucket(
            max(1, (self.frame_len - _rx.FRAME_DATA_START) // 80))
        self.check_fcs = check_fcs
        self.viterbi_window = viterbi_window
        self.viterbi_metric = viterbi_metric
        # resolved ONCE at construction: the radix, sco_track, and
        # fused_demap are part of the fixed compiled geometry (decode
        # jit cache key AND the checkpoint fingerprint — a different
        # decode program emits different bits)
        self.viterbi_radix = _check_radix(viterbi_radix)
        self.sco_track = _rx.sco_track_enabled(sco_track)
        self.fused_demap = _rx.fused_demap_enabled(fused_demap)
        # False = every owned window through per-capture `rx.receive`
        # (`_decode_oracle`) in place of the compiled decode. The
        # caller's word, never the environment's: the served path does
        # not read ZIRIA_STREAMING_RX (StreamReceiver resolves it)
        self.streaming = bool(streaming)
        self.mesh = mesh
        self.axis = axis
        # detector params kept for the degraded eager twin (the same
        # chunk graph run op-by-op when the compiled program fails)
        self._threshold = float(threshold)
        self._min_run = int(min_run)
        self._dead_zone = int(dead_zone)
        self._jit1 = _rx._jit_stream_chunk_multi(
            self.k, self.frame_len, self.n_sym_bucket,
            float(threshold), int(min_run), int(dead_zone), mesh, axis)
        # the batch the detector's LTS convolutions run over at this
        # geometry on each device (`rx.fleet.put`'s `locate_rows`):
        # from the function that picks the fold, so it cannot drift
        self._n_devices = mesh.size if mesh is not None else 1
        self._locate_rows = _sync.fold_rows(
            self.s // self._n_devices, self.chunk_len)
        # how many devices the lane axis lies over: set once, the
        # placement is fixed for the receiver's life
        dispatch.record_gauge("rx.mesh_devices", self._n_devices)
        self.sanitize = bool(sanitize)
        self._policy = resilience.default_policy(
            max_retries=max_retries, timeout_s=watchdog_s)
        self._health = [_LaneHealth(blowup_limit, rejoin_after)
                        for _ in range(self.s)]
        self._dirty = [False] * self.s
        self._sanitized = 0
        self._lane_blowups = 0
        self._degraded = False        # fleet decode -> oracle twin
        self._scan_degraded = False   # fleet scan -> eager twin
        # a lane's pending samples live in the staging array of the
        # step they will ride: `_level[i]` of them from sample
        # `_offsets[i]` of the stream on, at `_fill[i, :_level[i]]`
        # (`_write`), but for the `frame_len` overlap of a lane that
        # rode the last launch, which waits in `_prev[i, stride:]` until
        # the next step copies it forward (`_owed[i]`, `_step`). What a
        # slab holds beyond its lane's chunk waits in `_rest` for the
        # launch that makes room, inside the call that brought it
        self._level = [0] * self.s
        self._owed = [False] * self.s
        self._fill: Optional[np.ndarray] = None
        self._fill_stale: Optional[np.ndarray] = None
        self._fill_fresh = False
        self._prev: Optional[np.ndarray] = None
        self._rest: dict = {}
        self._offsets = [0] * self.s
        self._emitted = [0] * self.s
        self._watermarks = [0] * self.s
        self._seen = [set() for _ in range(self.s)]
        self._cfo_urad = [0] * self.s
        # the chunk-steps in flight, oldest first: at most three, of
        # which the newest alone still waits for its front half once a
        # launch has returned (`_InFlight`, `_settle`)
        self._flight: List[_InFlight] = []
        self._staging = _Staging(self.s, self.chunk_len)
        self._chunk_steps = 0
        # since when a lane has held a full chunk that no launch has
        # taken (`perf_counter`; kept only while a trace is active:
        # `rx.fleet.stack`'s `ready_ms`)
        self._full_since: Optional[float] = None
        self._overflow_chunks = 0
        self._truncated = 0    # owned frames the window could not hold
        self._max_in_flight = 0
        self._max_active = 0
        self._retired = 0      # frames credited to recycled lanes
        self._flushed = False

    # -- state ----------------------------------------------------------

    def _check_stream(self, stream, exc=IndexError) -> int:
        """The ONE unknown-stream-id gate of every per-lane surface:
        at S=64 an error naming only the bad id is useless — every
        raise here names the fleet's known id range too."""
        if not (isinstance(stream, (int, np.integer))
                and 0 <= int(stream) < self.s):
            raise exc(
                f"unknown stream id {stream!r}: this fleet's known "
                f"ids are 0..{self.s - 1} ({self.s} streams)")
        return int(stream)

    def carry(self, stream: int) -> StreamCarry:
        """Stream `stream`'s live :class:`StreamCarry` (tail, offset,
        emitted, dedupe watermark) — read-only observability."""
        stream = self._check_stream(stream)
        return StreamCarry(self._tail(stream), self._offsets[stream],
                           self._emitted[stream],
                           self._watermarks[stream])

    def _held(self, lane: int) -> int:
        """Samples of ``lane`` that lie in the array being filled
        (its level less an overlap that is still owed)."""
        return self._level[lane] - (self.frame_len if self._owed[lane]
                                    else 0)

    def _depth(self) -> int:
        """Samples the fleet holds for steps to come."""
        return sum(self._level) + sum(
            a.shape[0] for slabs in self._rest.values() for a in slabs)

    def _tail(self, lane: int) -> np.ndarray:
        """A lane's pending samples gathered into an array of their
        own: what `carry` shows and a checkpoint keeps (a copy, off
        the push path)."""
        v = self._level[lane]
        rest = self._rest.get(lane, ())
        out = np.empty((v + sum(a.shape[0] for a in rest), 2),
                       np.float32)
        lo = 0
        if self._owed[lane]:
            lo = self.frame_len
            out[:lo] = self._prev[lane, self.stride:]
        if v > lo:
            out[lo:v] = self._fill[lane, lo:v]
        for a in rest:
            out[v:v + a.shape[0]] = a
            v += a.shape[0]
        return out

    @property
    def carries(self) -> List[StreamCarry]:
        return [self.carry(i) for i in range(self.s)]

    @property
    def stats(self) -> MultiStreamStats:
        return MultiStreamStats(
            self.s, self._chunk_steps,
            sum(self._emitted) + self._retired,
            self._overflow_chunks, self._max_in_flight,
            self._max_active, self._sanitized,
            sum(h.quarantines for h in self._health),
            sum(1 for h in self._health if h.quarantined),
            self._lane_blowups,
            self._degraded or self._scan_degraded, self._truncated)

    def _note_cfo(self, lane: int, urad: int) -> None:
        """The gauge ``rx.stream_cfo_abs_max_urad{lane}``: the widest
        carrier offset among the frames of the lane's newest chunk-step
        that acquired any (micro-radians a sample, to the 7.6 of the
        scan's rate word; 0 once the lane's stream is reset), to be
        read against the estimators' ranges, pi / 64 = 49 087 fine and
        pi / 16 = 196 350 coarse (docs/observability.md). A series a
        lane, so at most S of them however sessions come and go, and
        a sample only when the level moved."""
        from ziria_tpu.utils import telemetry
        if urad != self._cfo_urad[lane]:
            self._cfo_urad[lane] = urad
            telemetry.gauge_sample("rx.stream_cfo_abs_max_urad", urad,
                                   {"lane": str(lane)})

    def quarantined(self, stream: int) -> bool:
        """True while `stream` rides behind the valid-mask (poisoned
        input or repeated decode blowups; docs/robustness.md)."""
        return self._health[self._check_stream(stream)].quarantined

    def _geometry(self) -> dict:
        return _stream_geometry(self)

    def _lane_state(self, stream: int) -> dict:
        """The checkpoint runtime-state rider of one lane (quarantine
        health + fleet degraded flags), shared by the per-lane and
        whole-fleet checkpoint surfaces so the two can never drift."""
        h = self._health[stream]
        return {"quarantined": h.quarantined, "clean": h.clean,
                "blowups": h.blowups, "quarantines": h.quarantines,
                "dirty": self._dirty[stream],
                "degraded": self._degraded,
                "scan_degraded": self._scan_degraded}

    def _lane_blob(self, stream: int, **rider) -> bytes:
        """One lane's checkpoint blob; ``rider`` adds runtime-state
        keys only the fleet's owner can vouch for per lane."""
        from ziria_tpu.runtime import resilience
        return resilience.checkpoint_carry(
            self.carry(stream), seen=self._seen[stream],
            geometry=self._geometry(),
            state=dict(self._lane_state(stream), **rider))

    def checkpoint(self, stream: int):
        """Serialize one fleet lane's live stream state (every
        chunk-step in flight is drained first; their fleet-wide
        emissions return alongside). The blob restores into a lone
        ``StreamReceiver(checkpoint=...)`` at the same geometry —
        a crashed fleet lane resumes on its own receiver with
        bit-identical subsequent emissions. Returns
        ``(state_bytes, (stream, frame) pairs)``."""
        if self._flushed:
            raise RuntimeError("checkpoint after flush")
        stream = self._check_stream(stream)
        out = self.drain_pending()
        return self._lane_blob(stream), out

    def checkpoint_fleet(self, lanes=None):
        """Serialize the fleet's live stream state in one pass — the
        serving runtime's automatic-snapshot surface (ISSUE 14): the
        chunk-steps in flight are drained ONCE (their emissions returned
        alongside — they belong to the pre-snapshot past and must
        reach the caller, never be silently dropped), then the lane
        blobs are taken against the now-quiescent state. ``lanes``
        restricts serialization to a subset (the server passes its
        OCCUPIED lanes — idle lanes' blobs would be built only to be
        discarded); None means all S. Returns ``({stream:
        state_bytes}, (stream, frame) pairs)``; each blob is exactly
        what :meth:`checkpoint` would produce, so any lane restores
        into a lone receiver or another fleet's :meth:`restore_stream`
        at the same geometry."""
        if self._flushed:
            raise RuntimeError("checkpoint after flush")
        out = self.drain_pending()
        which = range(self.s) if lanes is None \
            else [self._check_stream(i) for i in lanes]
        return {i: self._lane_blob(i) for i in which}, out

    # -- the push surface -----------------------------------------------

    def _filling(self) -> np.ndarray:
        """The staging array being filled, taken from the store when
        the first sample of a step has to be written: by then the
        launch before has drained its oldest step, so that step's
        array is free to be used again."""
        if self._fill is None:
            self._fill, self._fill_stale, self._fill_fresh = \
                self._staging.take()
        return self._fill

    def _write(self, lane: int, arr: np.ndarray) -> int:
        """Write a gated slab at the lane's level in the array being
        filled: the ONE copy a pushed sample gets on the host. What the
        lane's chunk has no room for waits in `_rest`, a view of the
        slab, for `_feed`. Returns the samples written."""
        if lane in self._rest:          # behind what already waits
            self._rest[lane].append(arr)
            return 0
        at = self._level[lane]
        n = min(self.chunk_len - at, arr.shape[0])
        if n:
            self._filling()[lane, at:at + n] = arr[:n]
            self._level[lane] = at + n
            if at + n == self.chunk_len and self._full_since is None:
                from ziria_tpu.utils import telemetry
                if telemetry.traced():
                    self._full_since = time.perf_counter()
        if n < arr.shape[0]:
            self._rest[lane] = [arr[n:]]
        return n

    def _feed(self) -> None:
        """Write on, behind a launch that made room, what the pushed
        slabs held beyond their lanes' chunks: a lane's level reaches
        `chunk_len` again for as long as any of it waits, so `_pump`
        runs until `_rest` is empty."""
        rest, self._rest = self._rest, {}
        self._ingest_span(len(rest), (self._write(i, a)
                                      for i, slabs in rest.items()
                                      for a in slabs))

    def _ingest_span(self, lanes: int, writes) -> None:
        """Run ``writes`` (an iterator, each item the samples one write
        put into staging) under ONE `rx.fleet.ingest` span that names
        the next launch, and count what they wrote."""
        from ziria_tpu.utils import telemetry

        args = {"step": self._chunk_steps, "lanes": lanes}
        with telemetry.span("rx.fleet.ingest", args):
            # known as the span closes: the trace's event carries it,
            # the device profile's annotation does not
            args["written"] = sum(writes)
        telemetry.count("rx.stage_samples", args["written"],
                        labels={"how": "written"})

    def _own_rest(self) -> None:
        """A call that leaves by an exception keeps what it could not
        write in arrays of the receiver's own: no reference to a
        caller's buffer outlives the call that brought it."""
        self._rest = {i: [np.array(a) for a in slabs]
                      for i, slabs in self._rest.items()}

    def _ingest(self, stream: int, samples) -> int:
        """The per-stream push seam: shape gate, chaos corruption
        seam (site ``rx.push.s<i>``), non-finite gate (reject, or
        ``sanitize=True`` zero-and-quarantine), then the write into
        the staging array (a slab that is refused has written
        nothing). Returns the samples written."""
        from ziria_tpu.utils import faults

        name = f"stream {stream}"
        arr = _slab_array(samples, name)
        arr, _kinds = faults.corrupt_slab(f"rx.push.s{stream}", arr)
        arr, n_bad = _gate_finite(arr, name, self.sanitize,
                                  self._health[stream])
        if n_bad:
            self._sanitized += n_bad
            self._dirty[stream] = True
        return self._write(stream, arr) if arr.size else 0

    def push(self, stream: int, samples) -> List:
        """Append samples ((n, 2) float pairs) to one stream; fire
        every chunk-step that completes. Returns the emitted
        ``(stream, StreamFrame)`` pairs (any stream may emit: a
        launch hands back the frames of the chunk-step two launches
        before it, and a push that launches nothing those of every
        older step the device has finished, without waiting for one
        it has not; :meth:`drain_pending` waits for all of them).
        Malformed slabs and non-finite samples fail loudly at the
        seam, naming the stream (or quarantine under
        ``sanitize=True``; docs/robustness.md). The slab is consumed
        inside the call: the caller may write its buffer again."""
        from ziria_tpu.utils import telemetry

        if self._flushed:
            raise RuntimeError("push after flush")
        try:
            telemetry.count(
                "rx.stage_samples",
                self._ingest(self._check_stream(stream), samples),
                labels={"how": "written"})
            return self._pump()
        except BaseException:
            self._own_rest()
            raise

    def push_many(self, slabs) -> List:
        """Append one slab per stream (empty slabs fine), THEN pump:
        streams that filled a chunk together ride the same chunk-step
        — the packer's lockstep fast path for synchronized feeds.
        Frames come out as :meth:`push` says: two launches after their
        chunk-step's own, or from a call that launches nothing and
        finds them ready. ``slabs`` is a length-S sequence, or a
        ``{stream_id: slab}`` dict for sparse arrival; an unknown
        stream id raises a named KeyError. Every slab is consumed
        inside the call, however many chunks it holds."""
        if self._flushed:
            raise RuntimeError("push after flush")
        if isinstance(slabs, dict):
            items = [(self._check_stream(i, KeyError), s)
                     for i, s in slabs.items()]
        else:
            if len(slabs) != self.s:
                raise ValueError(
                    f"{self.s} streams need {self.s} slabs, "
                    f"got {len(slabs)}")
            items = list(enumerate(slabs))
        try:
            if items:
                self._ingest_span(len(items), (self._ingest(i, s)
                                               for i, s in items))
            return self._pump()
        except BaseException:
            self._own_rest()
            raise

    def flush(self) -> List:
        """Close every stream: scan what each still holds (zero-padded
        to the chunk geometry, each stream owning every remaining
        start) as one final chunk-step, then drain every step in
        flight. Idempotent."""
        if self._flushed:
            return []
        out = self._pump()
        self._flushed = True
        active = [i for i in range(self.s) if self._level[i]]
        if active:
            out += self._step(active, flushing=True)
        return out + self.drain_pending()

    # -- per-lane lifecycle (the serving runtime's lane recycle) --------
    #
    # runtime/serve.py maps client SESSIONS onto this fleet's fixed S
    # lanes: a closing session flushes ITS lane (`flush_stream`), an
    # evicted one checkpoints it (`checkpoint`), and the freed lane is
    # recycled for the next admitted session (`reset_stream`) or a
    # recovering one (`restore_stream`). None of these disturb the
    # other lanes: every piece of stream state is per lane, and the
    # chunk-steps in flight are drained first only when the touched
    # lane actually rides in one of them — an idle lane's recycle
    # preserves the pipeline.

    @property
    def _pending(self):
        """The OLDEST chunk-step in flight as ``(offs, active, arrs,
        valid, own_lo, own_hi, outs)``, None when there is none: the
        one whose frames the next launch hands back (its first six
        are what the benchmark's float comparison keeps of it: for as
        long as a caller keeps ``arrs`` it stays this step's samples,
        `_Staging`)."""
        if not self._flight:
            return None
        st = self._flight[0]
        return (st.offs, st.active, st.arrs, st.valid, st.own_lo,
                st.own_hi, st.outs)

    @property
    def _pending_step(self):
        """The id of `_pending`'s chunk-step."""
        return self._flight[0].step if self._flight else None

    def drain_pending(self) -> List:
        """Block on every chunk-step in flight and emit them, oldest
        first — the pipeline's explicit drain point, and the way to
        have a step's frames NOW rather than two launches later.
        Returns the ``(stream, StreamFrame)`` pairs; safe to call any
        time."""
        return self._settle(0, 0, "drain")

    def _pending_touches(self, stream: int) -> bool:
        return any(stream in st.active for st in self._flight)

    def flush_stream(self, stream: int) -> List:
        """Close ONE stream: scan what it still holds (zero-padded, the
        lane owning every remaining start — the per-lane twin of
        :meth:`flush`) and drain through it, leaving every other lane
        live, its pending samples where they were or moved on whole
        (`_step`). Returns the emitted ``(stream, frame)`` pairs (any lane
        may emit — the steps in flight drain first). The lane's state
        is NOT reset; :meth:`reset_stream` recycles it."""
        stream = self._check_stream(stream)
        if self._flushed:
            raise RuntimeError("flush_stream after flush")
        out = self.drain_pending()
        if self._level[stream]:
            out += self._step([stream], flushing=True)
            out += self.drain_pending()
        return out

    def reset_stream(self, stream: int) -> List:
        """Return one lane to the fresh-stream state (offset 0, empty
        tail/dedupe, clean health) so a NEW session can ride it —
        after :meth:`flush_stream` or an eviction's :meth:`checkpoint`.
        Frames the lane emitted stay credited in :attr:`stats` (the
        ``retired`` accounting). Drains the steps in flight first ONLY
        when this lane rides in one of them, so recycling an idle lane
        never costs the fleet its overlap. Returns the drained
        ``(stream, frame)`` pairs."""
        stream = self._check_stream(stream)
        out = self.drain_pending() if self._pending_touches(stream) \
            else []
        h = self._health[stream]
        self._health[stream] = _LaneHealth(h.blowup_limit,
                                           h.rejoin_after)
        self._dirty[stream] = False
        self._retired += self._emitted[stream]
        if self._held(stream):
            # what the lane wrote stays behind in the array: zeroed
            # where a step leaves the lane idle, written over otherwise
            self._fill_stale[stream] = True
        self._level[stream] = 0
        self._owed[stream] = False
        self._rest.pop(stream, None)
        if not any(self._owed):
            self._prev = None
        self._offsets[stream] = 0
        self._emitted[stream] = 0
        self._watermarks[stream] = 0
        self._seen[stream] = set()
        self._note_cfo(stream, 0)
        return out

    def restore_stream(self, stream: int, checkpoint: bytes) -> List:
        """Restore a checkpointed session into lane ``stream`` — the
        eviction-recovery path: a blob from ``checkpoint(i)`` (or a
        lone ``StreamReceiver.checkpoint()``) at the same geometry
        resumes on this lane with bit-identical subsequent emissions
        (per-lane graphs under vmap ARE the one-stream graphs — the
        pinned fleet contract). The quarantine rider restores
        per-lane: a session checkpointed quarantined RESUMES
        quarantined, its lane-mates untouched. The blob's
        degraded/scan_degraded flags deliberately do NOT transfer —
        they describe the OLD runtime's compiled-program health, the
        degraded twins are bit-identical by the pinned contracts (so
        emissions cannot diverge), and importing them would punish
        this fleet's healthy lane-mates with the slow twin. Returns
        the drained ``(stream, frame)`` pairs (the reset's rule)."""
        from ziria_tpu.runtime import resilience
        from ziria_tpu.utils import telemetry

        stream = self._check_stream(stream)
        st = resilience.restore_carry(checkpoint)
        _validate_checkpoint(st, self._geometry())
        out = self.reset_stream(stream)
        # the restored tail is written as a slab is (it is the blob's
        # own array, nobody else's)
        tail = np.asarray(st.tail, np.float32)
        telemetry.count("rx.stage_samples", self._write(stream, tail),
                        labels={"how": "written"})
        self._offsets[stream] = int(st.offset)
        self._emitted[stream] = int(st.emitted)
        # the restored frames were emitted elsewhere: keep this
        # fleet's stats.frames counting ITS emissions only
        self._retired -= int(st.emitted)
        self._watermarks[stream] = int(st.watermark)
        self._seen[stream] = set(st.seen)
        rs = st.state
        h = self._health[stream]
        h.quarantined = bool(rs.get("quarantined", False))
        h.clean = int(rs.get("clean", 0))
        h.blowups = int(rs.get("blowups", 0))
        h.quarantines = int(rs.get("quarantines", 0))
        self._dirty[stream] = bool(rs.get("dirty", False))
        return out

    # -- chunk-step lifecycle -------------------------------------------

    def _pump(self) -> List:
        """Launch every chunk-step the lanes have filled, writing on
        behind each launch what the pushed slabs still hold; a call
        that fills none hands back what the device has finished
        meanwhile."""
        out: List = []
        launched = False
        while True:
            active = [i for i, n in enumerate(self._level)
                      if n >= self.chunk_len]
            if not active:
                return out if launched else self._advance_ready()
            out += self._step(active, flushing=False)
            launched = True
            if self._rest:
                self._feed()

    def _step(self, active, flushing: bool) -> List:
        """Make one stacked chunk-step over the `active` streams whole
        (idle lanes ride zeros behind `valid == 0`), advance the
        active streams' host carries, and launch it.

        The array launched is the one the pushes filled, AS IT IS:
        what is left to do is the ONE carry, the last `frame_len`
        samples of every lane that rode the launch before, copied
        from that launch's array (`_prev`, still in flight) to the
        front of this one. Where some lane holds samples and does not
        ride (a sparse step: paced arrivals, ragged streams,
        `flush_stream`), it must ride zeros and keep its samples, and
        either the waiting lanes move on to the next array and are
        zeroed behind, or the riding lanes are copied out to an array
        of their own; the step takes whichever moves fewer samples,
        by the levels it holds. Either way needs a second array of
        the store before the launch; a step every holding lane rides
        needs none, and the next is taken once this launch has
        drained its oldest step (`_filling`)."""
        from ziria_tpu.utils import dispatch, telemetry

        level, owed = self._level, self._owed
        fl, st = self.frame_len, self.stride
        riding = [False] * self.s
        for i in active:
            riding[i] = True
        waiting = [i for i, n in enumerate(level) if n and not riding[i]]
        carried = fl * sum(owed)
        move_on = sum(self._held(i) for i in waiting)
        move_out = sum(self._held(i) for i in active)
        # ``src`` holds the pushed samples, ``arrs`` is launched,
        # ``nxt`` is filled from here on
        if waiting and move_on > move_out:
            src, src_stale = self._fill, self._fill_stale
            nxt = (src, src_stale, self._fill_fresh)
            arrs, stale, fresh = self._staging.take()
        else:
            src = arrs = self._filling()
            src_stale = stale = self._fill_stale
            fresh = self._fill_fresh
            nxt = self._staging.take() if waiting else (None, None, False)
        telemetry.count("rx.stage_arrays", labels={
            "how": "fresh" if fresh else "reused"})
        dispatch.record_gauge("rx.stage_bytes", self._staging.nbytes)
        args = {"step": self._chunk_steps, "active": len(active),
                "samples": sum(level[i] if flushing
                               else self.chunk_len for i in active),
                "fresh": int(fresh), "carried": carried,
                "moved": min(move_on, move_out) if waiting else 0}
        traced = telemetry.traced()
        full_since, self._full_since = self._full_since, None
        if traced and full_since is not None:
            # how long the first lane to fill waited for this launch
            args["ready_ms"] = 1e3 * (time.perf_counter() - full_since)
        with telemetry.span("rx.fleet.stack", args):
            valid = np.zeros(self.s, np.int32)
            own_lo = np.zeros(self.s, np.int32)
            own_hi = np.zeros(self.s, np.int32)
            prev = self._prev
            for i in active:
                v, lo = level[i], 0
                if owed[i]:
                    arrs[i, :fl] = prev[i, st:]
                    lo = fl
                if arrs is not src and v > lo:
                    arrs[i, lo:v] = src[i, lo:v]
                    src_stale[i] = True
                if flushing:
                    if stale[i]:
                        arrs[i, v:] = 0
                    valid[i] = own_hi[i] = v
                else:
                    valid[i] = self.chunk_len
                    own_hi[i] = st
                # a quarantined stream rides behind the existing
                # valid-mask: its chunk advances (samples consumed)
                # but the detector sees zero valid samples — healthy
                # lanes are untouched by construction (per-lane graphs
                # under vmap), and the <= 2-dispatch budget is
                # preserved
                if self._health[i].step(self._dirty[i]):
                    valid[i] = 0
                self._dirty[i] = False
                # the stream's FIRST chunk owns head-truncated
                # preambles whose LTS alignment lands below 0 (clamped
                # to 0 on device, exactly as per-capture locate_frame
                # clamps); on any later chunk a negative start is the
                # previous chunk's frame
                own_lo[i] = -192 if self._offsets[i] == 0 else 0
            dst = nxt[0]
            for i in waiting:
                v, lo = level[i], 0
                if owed[i]:
                    dst[i, :fl] = prev[i, st:]
                    lo = fl
                if dst is not src and v > lo:
                    dst[i, lo:v] = src[i, lo:v]
                    if not stale[i]:
                        src[i, lo:v] = 0
            # an idle lane rides zeros: one that still holds the
            # samples of an earlier use is zeroed, once
            stale[active] = False
            arrs[stale] = 0
            stale[:] = False
            stale[active] = True
        telemetry.count("rx.stage_samples", carried,
                        labels={"how": "carried"})
        telemetry.count("rx.stage_samples", args["moved"],
                        labels={"how": "moved"})
        # the step is whole: the receiver's state is the next step's
        # from here on, whatever becomes of the launch (one that raises
        # once the scan is queued has its step in flight all the same)
        offs = list(self._offsets)
        owed[:] = [r and not flushing for r in riding]
        for i in active:
            self._offsets[i] += level[i] if flushing else st
            level[i] = 0 if flushing else fl
        self._prev = None if flushing else arrs
        self._fill, self._fill_stale, self._fill_fresh = nxt
        res = self._launch(arrs, valid, own_lo, own_hi, active, offs)
        dispatch.record_gauge("rx.stream_carry_depth", self._depth())
        return res

    def _put(self, x):
        """Host array -> device, stream axis sharded when a mesh is
        set (the `sweep_ber_sharded` placement rule)."""
        import jax

        if self.mesh is None:
            return jax.device_put(x)
        from ziria_tpu.parallel import batch as pbatch
        return pbatch.shard_batch(self.mesh, x, self.axis)

    def _launch(self, arrs, valid, own_lo, own_hi, active, offs) -> List:
        """Issue the stacked upload + scan dispatch of chunk-step t,
        THEN the front half of t-1 (its decode goes behind scan t) and
        the back half of t-2, the one read that waits for the device:
        when this returns, scan t and decode t-1 are queued there and
        the host stacks step t+1 under them. Returns step t-2's
        emissions (nothing, where an earlier call found them ready)."""
        from ziria_tpu.utils import dispatch, programs, telemetry

        step = self._chunk_steps
        with telemetry.span("rx.fleet.put", {
                "step": step, "bytes": arrs.nbytes + valid.nbytes
                + own_lo.nbytes + own_hi.nbytes,
                "locate_rows": self._locate_rows,
                "devices": self._n_devices, "lanes": self.s,
                "in_flight": len(self._flight) + 1}):
            chunk_args = (self._put(arrs), self._put(valid),
                          self._put(own_lo), self._put(own_hi))
        programs.note_site("rx.stream_chunk_multi", self._jit1,
                           *chunk_args)
        outs = self._scan_dispatch(chunk_args, step)
        self._chunk_steps += 1
        self._flight.append(_InFlight(
            step, offs, list(active), arrs, valid.copy(), own_lo.copy(),
            own_hi.copy(), outs))
        self._max_in_flight = max(self._max_in_flight, len(self._flight))
        self._max_active = max(self._max_active, len(active))
        dispatch.record_gauge("rx.stream_inflight", len(self._flight))
        if telemetry.traced():
            # the stall's one known signature (PERF.md section 7),
            # with a time beside the spans it struck
            import jax
            dev = jax.devices()[0] if self.mesh is None \
                else self.mesh.devices.flat[0]
            in_use = (dev.memory_stats() or {}).get("bytes_in_use")
            if in_use is not None:
                telemetry.track("rx.device_bytes_in_use", in_use)
        # the fleet-level time series: how many lanes carried real
        # samples this step (idle lanes are the valid-mask riders)
        dispatch.record_gauge("rx.active_streams", len(active))
        dispatch.record_gauge(
            "rx.quarantined_streams",
            float(sum(1 for h in self._health if h.quarantined)))
        dispatch.record_gauge(
            "rx.degraded_mode",
            1.0 if (self._degraded or self._scan_degraded) else 0.0)
        return self._settle(1, 2, "launch")

    def _settle(self, scans: int, depth: int, how: str) -> List:
        """Block, oldest first, until at most ``scans`` chunk-steps
        still wait for their front half and at most ``depth`` are in
        flight: (1, 2) behind a launch, (0, 0) at a drain point. Every
        front half due runs before the first back half, so that each
        decode is queued on the device before the host waits for an
        older one. Returns the emissions of the steps that left."""
        from ziria_tpu.utils import telemetry

        out: List = []
        halves = 0
        for st in self._flight[:len(self._flight) - scans]:
            if not st.fronted:
                st.how = how
                self._front(st)
                halves += 1
        while len(self._flight) > depth:
            self._flight[0].how = how
            out += self._drain(self._flight[0])
            halves += 1
        if halves:
            telemetry.count("rx.pipeline_advances", halves,
                            labels={"how": how})
        return out

    def _advance_ready(self) -> List:
        """Run every half whose arrays the device has finished, in the
        order a launch would and without waiting for any: the front
        half of the oldest step that lacks it, the back half of the
        oldest step. WHICH call hands a frame back depends on timing
        here and nowhere else; what comes out, and in what order,
        never does."""
        from ziria_tpu.utils import telemetry

        out: List = []
        halves = 0
        while True:
            st = next((x for x in self._flight if not x.fronted), None)
            if st is not None and _ready(_chunk_scalars(st.outs)):
                st.how = "ready"
                self._front(st)
            elif self._flight and self._flight[0].fronted \
                    and _ready(self._flight[0].dec_out or ()):
                self._flight[0].how = "ready"
                out += self._drain(self._flight[0])
            else:
                break
            halves += 1
        if halves:
            telemetry.count("rx.pipeline_advances", halves,
                            labels={"how": "ready"})
        return out

    def _scan_dispatch(self, chunk_args, step: int):
        """The ONE guarded fleet-scan dispatch (shared by `_launch`
        and the async-rescan path), degrading to the eager twin when
        the compiled program fails for good. The nine scalars leave
        each device as its scan ends, a launch before `_front` reads
        them. Its span carries the chunk-step's id."""
        from ziria_tpu.runtime import resilience

        outs = None
        if not self._scan_degraded:
            try:
                outs = resilience.guarded(
                    "rx.stream_chunk_multi", self._jit1, *chunk_args,
                    policy=self._policy, span_args={"step": step})
            except resilience.DispatchFailed:
                self._mark_degraded(scan=True)
        if outs is None:
            outs = self._eager_chunk(*chunk_args)
        _start_pull(_chunk_scalars(outs))
        return outs

    def _rescan(self, st):
        """Re-run a chunk-step whose ASYNC results were lost: a
        runtime failure mid-execution surfaces at the host pull in
        `_front`, after the guarded dispatch already returned — the
        launched results are gone, so the step re-dispatches from its
        own host arrays through the same guarded/degraded path
        (counted as an async rescan)."""
        from ziria_tpu.utils import telemetry

        telemetry.count("resilience.async_rescans")
        return self._scan_dispatch(
            (self._put(st.arrs), self._put(st.valid),
             self._put(st.own_lo), self._put(st.own_hi)), st.step)

    def _front(self, st) -> None:
        """Run the front half of a launched chunk-step's drain
        (`_scan_to_decode`); one that raises takes its step out of
        flight with it, as a drain that raised always has."""
        try:
            self._scan_to_decode(st)
        except BaseException:
            self._flight.remove(st)
            raise
        st.fronted = True

    def _scan_to_decode(self, st) -> None:
        """The front half: block on a launched chunk-step's per-lane
        scalars (done a launch ago, where the device keeps up), run
        the host integer decision tree per active stream, and dispatch
        the step's ONE flattened fleet decode when ANY stream has a
        decodable lane (the all-noise fast path skips it for the whole
        fleet), its outputs sent on their way to the host. Every span
        carries the step's own id."""
        from ziria_tpu.phy.wifi import rx as _rx
        from ziria_tpu.utils import programs, telemetry

        step, active = st.step, st.active

        def pull(o):
            pulled = _chunk_scalars(o)
            return _pull_chunk(o, ("rx.fleet.pull_scan", {
                "step": step,
                "bytes": sum(int(x.nbytes) for x in pulled),
                "shards": len(pulled) * self._n_devices,
                "reads": 1, "ready": int(_ready(pulled)),
                "how": st.how}))
        try:
            (own, starts, overflow, found, fstart, rb, ln, pk, nv,
             segs) = pull(st.outs)
        except Exception:    # noqa: BLE001 - async loss, re-dispatch
            st.outs = self._rescan(st)
            (own, starts, overflow, found, fstart, rb, ln, pk, nv,
             segs) = pull(st.outs)
        self._overflow_chunks += int(overflow[active].sum())
        rb, cfo = _rx.unpack_rate_word(rb)

        # what the scan owned against what its window acquisition found
        # there: equal on a clean stream (the acquisition reads only
        # each window's head, `rx._acquire_head`, and loses nothing),
        # and how far off carrier the frames found were
        got = own & found
        st.acquired = int(got[active].sum())
        cfo = np.where(got, np.abs(cfo), 0)
        for i in active:
            if got[i].any():
                self._note_cfo(i, int(cfo[i].max()))
        with telemetry.span("rx.fleet.classify", {
                "step": step, "candidates": int(own[active].sum()),
                "acquired": st.acquired,
                "cfo_abs_max_urad": int(cfo[active].max(initial=0)),
                "cfo_abs_sum_urad": int(cfo[active].sum())}):
            allcands = []    # (stream, abs_start, row j) in emit order
            for i in active:
                off = st.offs[i]
                self._watermarks[i] = off
                self._seen[i], cands = _chunk_candidates(
                    self._seen[i], off, own[i], starts[i], self.k)
                allcands += [(i, abs_start, j) for abs_start, j in cands]
            # the per-capture oracle serves every window: asked for
            # (``streaming=False``), or the compiled fleet decode
            # already failed for good
            st.oracle = not self.streaming or self._degraded
            if not st.oracle:
                st.emit, st.lanes, st.slots, tables = self._classify(
                    allcands, found, fstart, rb, ln, pk, nv)
        st.allcands, st.starts = allcands, starts
        if st.oracle or not st.lanes:
            return
        # what the decode is asked for against what it computes: on
        # every device the program fronts the slots that hold a frame,
        # whole groups of them, each gathered at the whole symbol
        # bucket (`rx.decode_walk`, its own rule, cut at the slots a
        # device has), and runs the tiles that hold them, every lane,
        # as far into the trellis as the tile's longest frame reaches
        # (`rx.decode_steps`, over `rx.decode_bound`, the kernels' own
        # rule); the scan cut every one of the S x K from the chunk at
        # the whole window, whatever it holds
        useful = sum(lane[4] for lane in st.lanes)
        dev_slots = self.s * self.k // self._n_devices
        dev_nbits = tables[2].reshape(self._n_devices, -1)
        n_slots = int(np.minimum(_rx.decode_walk(
            (dev_nbits > 0).sum(axis=1), dev_slots)[0], dev_slots).sum())
        bounded = _rx.trellis_takes_bound(
            self.viterbi_window, self.viterbi_metric, self.fused_demap)
        padded = n_slots * self.n_sym_bucket
        telemetry.count("rx.decode_symbols", useful,
                        labels={"kind": "useful"})
        telemetry.count("rx.decode_symbols", padded,
                        labels={"kind": "padded"})
        telemetry.count("rx.decode_slots", len(st.lanes),
                        labels={"kind": "live"})
        telemetry.count("rx.decode_slots", n_slots,
                        labels={"kind": "walked"})
        with telemetry.span("rx.fleet.decode", {
                "step": step, "lanes": len(st.lanes),
                "slots": n_slots,
                "useful_symbols": useful,
                "padded_symbols": padded,
                "useful_bits": int(tables[2].sum()),
                "trellis_steps": _rx.decode_steps(
                    dev_nbits, self.n_sym_bucket, bounded),
                "frame_samples": len(st.lanes) * _rx.FRAME_DATA_START
                + 80 * useful,
                "window_samples": self.s * self.k * self.frame_len}):
            st.dec = _rx._jit_stream_decode_multi(
                self.n_sym_bucket, self.viterbi_window,
                self.viterbi_metric, self.viterbi_radix,
                self.mesh, self.axis, self.sco_track,
                self.fused_demap)
            st.dec_args = (segs,) + tuple(self._put(t) for t in tables)
            programs.note_site("rx.stream_decode_multi", st.dec,
                               *st.dec_args)
            _dispatch_decode(self, st)

    def _drain(self, st) -> List:
        """The back half of a chunk-step's drain, and the one place its
        frames come out of: take the oldest chunk-step out of flight,
        block on its decode's two arrays (on their way to the host
        since `_front` dispatched it) and emit. A decode that failed
        for good, at its dispatch or at this read, degrades the WHOLE
        fleet's decode to the per-capture oracle (bit-identical by the
        pinned contract), this chunk-step included — healthy lanes
        keep flowing."""
        from ziria_tpu.phy.wifi import rx as _rx
        from ziria_tpu.phy.wifi.params import N_SERVICE_BITS
        from ziria_tpu.utils import telemetry

        self._flight.remove(st)
        if st.oracle:
            return self._decode_oracle(st)
        got = None
        if st.lanes:
            got = _pull_decode(self, st)
            if got is None:
                return self._decode_oracle(st)
        emit = st.emit
        # final since `_classify`: the frames this step's windows
        # could not hold, beside those it acquired
        n_trunc = _truncated(emit.values())
        with telemetry.span("rx.fleet.emit", {
                "step": st.step, "frames": len(emit) + len(st.lanes),
                "acquired": st.acquired, "truncated": n_trunc}):
            if got is not None:
                clear, crc = got
                for i, sl in st.slots.items():
                    for pos, (abs_start, m, lb) in enumerate(sl):
                        psdu = clear[i][pos][
                            N_SERVICE_BITS: N_SERVICE_BITS + 8 * lb]
                        emit[(i, abs_start)] = _rx.RxResult(
                            True, m, lb, psdu,
                            bool(crc[i, pos]) if self.check_fcs
                            else None)
            out = []
            for key in sorted(emit):
                i, abs_start = key
                out.append((i, StreamFrame(abs_start, emit[key])))
                self._emitted[i] += 1
        _count_emitted(out, sum(self._emitted))
        self._count_truncated(n_trunc)
        return out

    def _count_truncated(self, n: int) -> None:
        """`n` more owned frames the window could not hold: the stat,
        and the registry's view beside `_count_emitted`'s (the running
        count for the trace's counter track; `telemetry.count` is free
        when nothing collects)."""
        from ziria_tpu.utils import telemetry

        self._truncated += n
        if n:
            telemetry.count("rx.stream_frames_truncated", n,
                            total=self._truncated)

    def _classify(self, allcands, found, fstart, rb, ln, pk, nv):
        """The host integer decision tree over a chunk-step's owned
        candidates. Returns ``(emit, lanes, slots, tables)``: results
        already final keyed (stream, abs_start); the decodable lanes
        as (stream, abs_start, row j, rate, n_sym, length); per stream
        their (abs_start, rate, length) in table order; and the four
        (S, K) int32 tables the decode takes (row, rate index, data
        bits, PSDU bits), None when nothing decodes."""
        from ziria_tpu.phy.wifi import rx as _rx
        from ziria_tpu.phy.wifi.params import RATES

        emit, lanes, slots = {}, [], {}
        for i, abs_start, j in allcands:
            avail = int(nv[i, j]) - int(fstart[i, j])
            res, ok = _rx._classify_acquire(
                bool(found[i, j]), avail, int(rb[i, j]),
                int(ln[i, j]), bool(pk[i, j]))
            if ok is None:
                emit[(i, abs_start)] = res
            else:
                lanes.append((i, abs_start, j, ok[0], ok[1],
                              int(ln[i, j])))
        if not lanes:
            return emit, lanes, slots, None
        # (S, K) row tables, zero-filled past each stream's real
        # lanes (ridx 0 / nbits 0 = a full-erasure pad decode —
        # discarded, like every pad lane here); row 0 is safe for
        # idle streams because segs always holds K rows per stream
        rows = np.zeros((self.s, self.k), np.int32)
        ridx = np.zeros((self.s, self.k), np.int32)
        nbits = np.zeros((self.s, self.k), np.int32)
        npsdu = np.zeros((self.s, self.k), np.int32)
        for i, abs_start, j, m, n_sym, lb in lanes:
            sl = slots.setdefault(i, [])
            pos = len(sl)
            sl.append((abs_start, m, lb))
            rows[i, pos] = j
            ridx[i, pos] = _rx.RATE_INDEX[m]
            nbits[i, pos] = n_sym * RATES[m].n_dbps
            npsdu[i, pos] = 8 * lb
        return emit, lanes, slots, (rows, ridx, nbits, npsdu)

    def _decode_oracle(self, st) -> List:
        """The per-capture decode twin — the ``streaming=False``
        oracle AND the degraded mode the compiled decode falls back
        to: each owned window sliced from its stream's host chunk and
        pushed through per-capture `rx.receive` (the same detected
        windows, >= 3 dispatches per frame: the identity contract made
        runnable). Under the resilience opt-ins (``sanitize=True`` or
        degraded mode) a window whose receive blows up is counted
        (`resilience.lane_blowups`), dropped loudly, and charged to
        ITS stream's health (repeated blowups quarantine that stream;
        the rest of the fleet keeps flowing) — never a crash, never a
        silent wrong answer. In the PLAIN ``streaming=False`` oracle
        (no opt-in) exceptions propagate unchanged: a genuine decoder
        defect must surface, not masquerade as frame loss."""
        from ziria_tpu.phy.wifi import rx as _rx
        from ziria_tpu.utils import telemetry

        contain = (self.sanitize or self._degraded
                   or self._scan_degraded)
        out: List = []
        with telemetry.span("rx.fleet.emit", {
                "step": st.step, "frames": len(st.allcands)}):
            for i, abs_start, j in sorted(st.allcands,
                                          key=lambda c: (c[0], c[1])):
                s = int(st.starts[i, j])
                win = st.arrs[i][s: min(s + self.frame_len,
                                        int(st.valid[i]))]
                try:
                    res = _rx.receive(
                        win, check_fcs=self.check_fcs,
                        viterbi_window=self.viterbi_window,
                        viterbi_metric=self.viterbi_metric,
                        viterbi_radix=self.viterbi_radix,
                        sco_track=self.sco_track)
                except Exception:  # noqa: BLE001 - counted containment
                    if not contain:
                        raise
                    self._lane_blowups += 1
                    self._health[i].blowup()
                    telemetry.count("resilience.lane_blowups")
                    continue
                out.append((i, StreamFrame(abs_start, res)))
                self._emitted[i] += 1
        _count_emitted(out, sum(self._emitted))
        self._count_truncated(_truncated(fr.result for _i, fr in out))
        return out

    def _eager_chunk(self, chunks, valid, own_lo, own_hi):
        """The degraded scan twin: the SAME stream-axis graph run
        op-by-op (eager vmap — no dependence on the failed compiled
        program; unsharded — results are bit-identical on any mesh,
        so dropping the mesh in the degraded twin loses throughput,
        never correctness). Slower (many small dispatches) but
        available; labelled ``rx.stream_chunk_multi.eager`` so chaos
        plans targeting the compiled site never block the fallback."""
        from ziria_tpu.phy.wifi import rx as _rx
        from ziria_tpu.utils import dispatch

        with dispatch.timed("rx.stream_chunk_multi.eager"):
            return _rx.multi_stream_chunk_graph(
                chunks, valid, own_lo, own_hi, self.k, self.frame_len,
                self.n_sym_bucket, self._threshold, self._min_run,
                self._dead_zone)

    def _mark_degraded(self, scan: bool) -> None:
        """Enter degraded mode for one of the two compiled programs
        (`_record_degraded`)."""
        if scan:
            self._scan_degraded = True
        else:
            self._degraded = True
        _record_degraded(True)

    def reset_degraded(self) -> None:
        """Leave degraded mode (re-probe the compiled programs on the
        next chunk-step) — the operator's lever after the underlying
        fault (a link flap, a wedged device) is known to be fixed."""
        self._degraded = False
        self._scan_degraded = False
        _record_degraded(False)


def receive_streams(streams, chunk_len: Optional[int] = None,
                    frame_len: Optional[int] = None,
                    max_frames_per_chunk: Optional[int] = None,
                    check_fcs: bool = False,
                    threshold: Optional[float] = None,
                    min_run: Optional[int] = None,
                    dead_zone: Optional[int] = None,
                    viterbi_window: int = None,
                    viterbi_metric: str = None,
                    viterbi_radix: int = None, mesh=None,
                    axis: str = "dp",
                    sco_track: Optional[bool] = None,
                    fused_demap: Optional[bool] = None,
                    geometry: Optional[_geometry.Geometry] = None):
    """Decode S concurrent multi-frame I/Q streams in O(chunk-steps)
    device dispatches — <= 2 per chunk-step *independent of S*.
    Returns ``(per_stream_frames, stats)``: a per-stream position-
    ordered list of :class:`StreamFrame` (each bit-identical, RxResult
    field for field, to what :func:`receive_stream` — and hence
    per-capture ``rx.receive`` over the slice — emits for that stream
    alone) and the :class:`MultiStreamStats`.

    ``mesh`` shards the stream axis over the dp device mesh
    (`parallel/batch.frame_mesh`; S must divide it). Push-driven
    callers (live feeds with ragged arrival) use
    :class:`MultiStreamReceiver` directly."""
    s = len(streams)
    if s == 0:
        return [], MultiStreamStats(0, 0, 0, 0, 0, 0)
    msr = MultiStreamReceiver(
        s, chunk_len=chunk_len, frame_len=frame_len,
        max_frames_per_chunk=max_frames_per_chunk, check_fcs=check_fcs,
        threshold=threshold, min_run=min_run, dead_zone=dead_zone,
        viterbi_window=viterbi_window, viterbi_metric=viterbi_metric,
        viterbi_radix=viterbi_radix, mesh=mesh, axis=axis,
        sco_track=sco_track, fused_demap=fused_demap, geometry=geometry)
    got = msr.push_many([np.asarray(st, np.float32) for st in streams])
    got += msr.flush()
    per = [[] for _ in range(s)]
    for i, fr in got:
        per[i].append(fr)
    return per, msr.stats


class StreamStats(NamedTuple):
    chunks: int                # chunk dispatch-1 scans issued
    frames: int                # StreamFrames emitted
    overflow_chunks: int       # chunks reporting > K eligible plateaus
    max_in_flight: int         # high-water chunk dispatches in flight
    sanitized: int = 0         # non-finite samples zeroed (sanitize=True)
    quarantines: int = 0       # times the stream entered quarantine
    lane_blowups: int = 0      # per-window oracle decode blowups caught
    degraded: bool = False     # a compiled program degraded to its twin


class StreamReceiver:
    """ONE stream's face over a :class:`MultiStreamReceiver` of one
    (``.fleet``): feed arbitrary sample slabs with :meth:`push`,
    close the stream with :meth:`flush`; both return the
    :class:`StreamFrame`\\ s that became decodable. The chunk
    lifecycle, the two compiled programs and their degraded twins are
    the fleet's — a lone stream is lane 0 of a one-lane fleet, with
    the fleet's geometry rules (`chunk_len` windows overlapping by
    `frame_len`, up to `max_frames_per_chunk` frames per chunk, more
    raising the overflow flag counted in :class:`StreamStats`).

    The face owns two keywords and forwards every other to the fleet:

    - ``streaming`` (None = ``--streaming-rx`` / ``ZIRIA_STREAMING_RX``,
      default on) is resolved HERE and handed down; off runs the
      per-capture oracle over the same detected windows.
    - ``checkpoint`` restores a blob of :meth:`checkpoint` (or of a
      fleet lane's) into lane 0 WHOLE. ``restore_stream`` leaves the
      old runtime's degraded flags, containment counters and emitted
      count behind, because a serving fleet has lane-mates to protect;
      this fleet has none, so the face takes them up and the restored
      receiver behaves and counts as the uninterrupted one would."""

    _FLEET_ATTRS = ("chunk_len", "frame_len", "stride", "k",
                    "n_sym_bucket", "check_fcs", "viterbi_window",
                    "viterbi_metric", "viterbi_radix", "sco_track",
                    "fused_demap", "streaming", "sanitize")

    def __init__(self, *, streaming: Optional[bool] = None,
                 checkpoint: Optional[bytes] = None, **fleet_kw):
        fleet = self.fleet = MultiStreamReceiver(
            n_streams=1, mesh=None,
            streaming=streaming_rx_enabled(streaming), **fleet_kw)
        for name in self._FLEET_ATTRS:  # fixed at construction
            setattr(self, name, getattr(fleet, name))
        if checkpoint is not None:
            from ziria_tpu.runtime import resilience

            fleet.restore_stream(0, checkpoint)   # validates the blob
            st = resilience.restore_carry(checkpoint)
            fleet._degraded = bool(st.state.get("degraded", False))
            fleet._scan_degraded = bool(
                st.state.get("scan_degraded", False))
            fleet._sanitized = int(st.state.get("sanitized", 0))
            fleet._lane_blowups = int(st.state.get("lane_blowups", 0))
            fleet._retired += int(st.emitted)

    @property
    def carry(self) -> StreamCarry:
        return self.fleet.carry(0)

    @property
    def stats(self) -> StreamStats:
        st = self.fleet.stats
        return StreamStats(st.chunk_steps, st.frames, st.overflow_chunks,
                           st.max_in_flight, st.sanitized,
                           st.quarantines, st.lane_blowups, st.degraded)

    def push(self, samples) -> List[StreamFrame]:
        """Append samples ((n, 2) float pairs) to the stream; scan
        every full chunk that completes. Returns the frames emitted."""
        return [fr for _i, fr in self.fleet.push(0, samples)]

    def flush(self) -> List[StreamFrame]:
        """Close the stream: scan the carried tail and drain the
        in-flight chunk. Idempotent."""
        return [fr for _i, fr in self.fleet.flush()]

    def checkpoint(self):
        """Serialize the live stream state: the fleet's lane blob
        (in-flight chunk DRAINED first, its frames returned alongside)
        with the containment counters in its rider. Returns
        ``(state_bytes, frames)``; a new
        ``StreamReceiver(checkpoint=state_bytes, ...)`` at the same
        geometry resumes with bit-identical subsequent emissions."""
        fleet = self.fleet
        if fleet._flushed:
            raise RuntimeError("checkpoint after flush")
        out = [fr for _i, fr in fleet.drain_pending()]
        return fleet._lane_blob(
            0, sanitized=fleet._sanitized,
            lane_blowups=fleet._lane_blowups), out

    def reset_degraded(self) -> None:
        """Leave degraded mode (`MultiStreamReceiver.reset_degraded`)."""
        self.fleet.reset_degraded()


def receive_stream(samples, chunk_len: Optional[int] = None,
                   frame_len: Optional[int] = None,
                   max_frames_per_chunk: Optional[int] = None,
                   check_fcs: bool = False,
                   threshold: Optional[float] = None,
                   min_run: Optional[int] = None,
                   dead_zone: Optional[int] = None,
                   viterbi_window: int = None,
                   viterbi_metric: str = None,
                   viterbi_radix: int = None,
                   streaming: Optional[bool] = None,
                   sco_track: Optional[bool] = None,
                   fused_demap: Optional[bool] = None,
                   geometry: Optional[_geometry.Geometry] = None):
    """Decode every frame of a long multi-frame sample stream in
    O(chunks) device dispatches (<= 2 per chunk; 1 for all-noise
    chunks). Returns ``(frames, stats)``: a position-ordered list of
    :class:`StreamFrame` — each bit-identical, RxResult field for
    field including the FCS status, to per-capture
    ``rx.receive(stream[start : start + frame_len], check_fcs=...)``
    — and the :class:`StreamStats` (chunks scanned, frames emitted,
    overflow chunks, in-flight high-water mark).

    ``streaming=False`` (or ``--no-streaming-rx`` /
    ``ZIRIA_STREAMING_RX=0``) runs the per-capture oracle over the
    same detected windows (>= 3 dispatches per frame). The convenience
    wrapper over :class:`StreamReceiver` — push-driven callers (a live
    capture feed) use the class directly, pushing slabs into one
    receiver whose :class:`StreamCarry` state threads across chunks
    internally (visible via ``.carry``). ``geometry`` supplies the
    default for every knob the caller leaves None (one declarative
    object; explicit arguments win)."""
    sr = StreamReceiver(chunk_len=chunk_len, frame_len=frame_len,
                        max_frames_per_chunk=max_frames_per_chunk,
                        check_fcs=check_fcs, threshold=threshold,
                        min_run=min_run, dead_zone=dead_zone,
                        viterbi_window=viterbi_window,
                        viterbi_metric=viterbi_metric,
                        viterbi_radix=viterbi_radix,
                        streaming=streaming, sco_track=sco_track,
                        fused_demap=fused_demap, geometry=geometry)
    frames = sr.push(samples)
    frames += sr.flush()
    return frames, sr.stats


def transmit_many(psdus, rates_mbps, add_fcs: bool = False,
                  batched_tx: Optional[bool] = None) -> List[np.ndarray]:
    """One-dispatch mixed-rate TX batch surface (thin re-export of
    phy/link.transmit_many, next to its RX twin `receive_many`): N
    frames encoded as ONE vmap(lax.switch) device call, returned at
    their true lengths — or the per-frame oracle loop under
    ``ZIRIA_BATCHED_TX=0`` — bit-identical either way."""
    from ziria_tpu.phy import link
    return link.transmit_many(psdus, rates_mbps, add_fcs=add_fcs,
                              batched_tx=batched_tx)


def loopback_many(psdus, rates_mbps, **kw) -> List[Any]:
    """The full device-resident N-frame loopback (thin re-export of
    phy/link.loopback_many): ONE fused dispatch by default, or the
    staged encode -> per-lane channel -> batched receive ~5-dispatch
    oracle under ``fused=False`` / ``ZIRIA_FUSED_LINK=0``."""
    from ziria_tpu.phy import link
    return link.loopback_many(psdus, rates_mbps, **kw)


def run_many(comp: ir.Comp, frames: Sequence[Sequence[Any]],
             max_out: Optional[int] = None,
             batcher: Optional[StepBatcher] = None) -> List[Any]:
    """Run `comp` once per entry of `frames` (each an independent input
    stream), batching chunk-machine device steps across frames. Returns
    the per-frame :class:`interp.Result`s, bit-identical to running
    each frame alone. Pass a hybridized comp (`hybrid.hybridize`) —
    a plain comp works too, it just has no device steps to batch."""
    from ziria_tpu.interp.interp import run

    n = len(frames)
    if n == 0:
        return []
    if n == 1:   # no threads, no batcher: exactly the single-frame path
        return [run(comp, list(frames[0]), max_out=max_out)]

    b = batcher if batcher is not None else StepBatcher(n)
    with b._cv:
        b._active = n   # reconcile a caller-supplied/reused batcher:
        b._parked.clear()  # a stale count deadlocks or defeats batching
    results: List[Any] = [None] * n
    errors: List[Optional[BaseException]] = [None] * n

    def worker(i: int, xs) -> None:
        C._TLS.batcher = b
        try:
            results[i] = run(comp, list(xs), max_out=max_out)
        except BaseException as e:
            errors[i] = e
        finally:
            C._TLS.batcher = None
            b.frame_finished()

    threads = [threading.Thread(target=worker, args=(i, xs),
                                name=f"ziria-frame-{i}", daemon=True)
               for i, xs in enumerate(frames)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errors:
        if e is not None:
            raise e
    return results
