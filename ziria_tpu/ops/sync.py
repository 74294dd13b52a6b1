"""Packet detection, CFO estimation/correction, channel estimation.

Counterpart of the reference RX's front half (SURVEY.md §2.3, §3.4):
packet detect via STS autocorrelation, coarse/fine CFO from STS/LTS
lag products, channel estimation from the two LTS symbols. All in pair
representation, all expressed as whole-array ops (one short
convolution for the sliding LTS correlation, `ccorrelate_valid`, and
doubling shift-adds for the window sums — see `_sliding_sum` for why
not cumsum) so a frame's worth of samples is one fused graph.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ziria_tpu.ops import cplx
from ziria_tpu.ops.ofdm import LTS_FREQ, N_FFT, lts_time_symbol


#: Output samples of one folded block. A convolution over few long
#: rows is a shape the TPU crawls on: 64 taps over [8, 1, 131 072] took
#: 86.9 ms on a v5e (12 M outputs/s) and 0.87 ms cut into 128 rows or
#: more (1.2 G outputs/s, flat from 16 to 1024 blocks a row); a lone
#: row wants 256 blocks or more (chip runs, PR 35, of the one-channel
#: products the LTS correlation then was). Since PR 47 the correlation
#: is one two-channel convolution over the same blocks, 1.56 ms at
#: [8, 131 072, 2] where the four products took 4.03, and the whole
#: `vmap(locate_frames)` 2.44 ms where it took 6.78 (4.00 against
#: 12.67 at [8, 262 144, 2]; chip run, PR 47: PERF.md section 6 has
#: every form that was priced beside it).
FOLD_BLOCK = 512


def fold_blocks(n_out: int) -> int:
    """How many overlapped blocks `ccorrelate_valid` cuts a row of
    ``n_out`` outputs into: as many as `FOLD_BLOCK` goes into it,
    rounded up, and 1 (the row passes through unfolded) where the row
    is at most two blocks long — the acquisition's window heads are
    already many and short. A function of the row's length alone: it
    is all a per-lane graph can see of its shape under ``vmap``, and
    131 072 / 512 = 256 blocks reach the wide shape at any lane
    count."""
    return 1 if n_out <= 2 * FOLD_BLOCK else -(-n_out // FOLD_BLOCK)


def fold_rows(rows: int, n: int) -> int:
    """The batch the LTS correlation (`N_FFT` taps) of ``rows`` rows
    of ``n`` samples hands the convolution: what `rx.fleet.put`
    reports as ``locate_rows``."""
    return rows * fold_blocks(n - N_FFT + 1)


def ccorrelate_valid(x, ref):
    """Sliding complex correlation of one row of pairs against ``ref``:
    ``out[k] = sum_j x[k + j] * conj(ref[j])``, THE sliding correlator
    of this module (`lts_pair_metric`), so the per-capture oracle and
    the chunk scan run the same function.

    x: (n, 2), ref: (w, 2), n >= w. Returns (n - w + 1, 2). ONE
    convolution of two input channels (re, im) and two output features
    (re, im), where four one-channel convolutions ran, one a real
    product, each filling a single column of the MXU (4.03 ms at 2048
    rows against this one's 1.56: chip run, PR 47, PERF.md section
    6). A long row is cut into `fold_blocks` blocks of `FOLD_BLOCK`
    outputs, each with the w - 1 samples of halo its last outputs
    read, the blocks ride the convolution's batch axis (under ``vmap``
    the lane axis merges into the same batch) and their outputs are
    laid end to end again. The same taps meet the same samples in the
    same order in every block, so a value depends on its own w-sample
    window alone — never on the block, the offset or the array it
    landed in (`tests/test_sync_fold.py`, and read so on the chip: a
    banded matmul was twice as fast there and did not, PERF.md).
    HIGHEST: the TPU's default convolution precision is bfloat16."""
    import jax

    w = ref.shape[0]
    n_out = x.shape[0] - w + 1
    blocks = fold_blocks(n_out)
    if blocks == 1:
        rows = x[None]
    else:
        # block b reads x[b * L : (b + 1) * L + w - 1]: its own L
        # samples and the head of the next block's (w - 1 <= L), zeros
        # past the end
        size = FOLD_BLOCK
        body = jnp.pad(x, ((0, (blocks + 1) * size - x.shape[0]), (0, 0))) \
            .reshape(blocks + 1, size, 2)
        rows = jnp.concatenate([body[:-1], body[1:, :w - 1]], axis=1)
    rr, ri = ref[:, 0], ref[:, 1]
    # [feature, channel, tap]: re = xr rr + xi ri, im = xi rr - xr ri
    taps = jnp.stack([jnp.stack([rr, ri]), jnp.stack([-ri, rr])])
    out = jax.lax.conv_general_dilated(
        rows.transpose(0, 2, 1), taps, (1,), "VALID",
        dimension_numbers=("NCH", "OIH", "NCH"),
        precision=jax.lax.Precision.HIGHEST)        # (blocks, 2, L)
    return out.transpose(0, 2, 1).reshape(-1, 2)[:n_out]


def _sliding_sum(x, w: int):
    """Sliding window sums along axis 0: out[k] = sum(x[k:k+w]).

    NOT a global cumsum difference: prefix sums accumulate f32
    rounding along the whole stream and the window value c[k+w]-c[k]
    is a catastrophic cancellation once the prefix dwarfs the window
    (measured ~0.2% metric error at 14k samples, and host vs
    stream-sharded results diverged). And no longer a w-tap
    convolution with ones (48 multiply-adds by 1.0 an output; the
    three columns of `sts_autocorr` took 3.10 ms at [8, 131 072, 2]
    and take 1.05 so: chip run, PR 47, PERF.md section 6): the window
    sums of 2, 4, 8 ... samples by doubling shift-adds, then one add
    for each further set bit of ``w`` (48 = 32 + 16: six additions an
    output). Every output is the same tree of f32 additions over its
    own w terms, so it is position-independent —
    `parallel/streampar.sliding_parallel` shards bit-compatibly, and a
    chunk and a capture that hold the same samples read the same
    values, on the chip too (the one-channel convolution's did not
    there: its summation order followed its batch).
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.inexact):
        # integer windows: cumsum differences are EXACT (no rounding)
        c = jnp.cumsum(x, axis=0)
        c = jnp.concatenate([jnp.zeros_like(c[:1]), c], axis=0)
        return c[w:] - c[:-w]
    n_out = x.shape[0] - w + 1
    sums, p = {1: x}, 1
    while 2 * p <= w:
        sums[2 * p] = sums[p][:-p] + sums[p][p:]
        p *= 2
    out, off = sums[p][:n_out], p
    for q in sorted((q for q in sums if q < p and w & q), reverse=True):
        out = out + sums[q][off:off + n_out]
        off += q
    return out


def sts_autocorr(samples, window: int = 48):
    """Normalized lag-16 autocorrelation metric over a sample stream.

    samples: (n, 2). Returns (metric (n-16-window+1,), corr pairs).
    metric ~ 1 inside the short preamble's periodic region.
    """
    x = jnp.asarray(samples, jnp.float32)
    a, b = x[:-16], x[16:]
    prod = cplx.cmul_conj(b, a)            # r[k+16] * conj(r[k])
    corr = _sliding_sum(prod, window)      # (n-16-window+1, 2)
    energy = _sliding_sum(cplx.cabs2(b), window)
    metric = jnp.sqrt(cplx.cabs2(corr)) / (energy + 1e-9)
    return metric, corr


def detect_packet(samples, window: int = 48, threshold: float = 0.75,
                  limit=None):
    """Return (detected?, start_index) — the first index where the STS
    autocorrelation metric crosses the threshold (start of the plateau).
    Data-dependent only in the returned index, so it jits (lax-friendly
    argmax over a boolean ramp).

    This is the K=1, first-crossing special case of the multi-peak
    :func:`locate_frames` scan: one threshold crossing, no plateau
    `min_run` gate, no dead-zone suppression — exactly what a
    pre-segmented one-frame capture needs, and the detection gate the
    per-capture oracle (:func:`locate_frame`) keeps. The streaming
    receiver's chunk scan generalizes it to "every plateau in a long
    chunk"; this single-crossing form stays the oracle the K=1 lane of
    that scan is judged against.

    ``limit`` (static or traced) caps the considered positions to
    those a LIMIT-length capture would evaluate — see
    :func:`locate_frame`, the one caller that needs it. This is THE
    detection gate: `locate_frame` delegates here, so the threshold/
    window defaults live in exactly one place."""
    metric, _ = sts_autocorr(samples, window)
    above = metric > threshold
    if limit is not None:
        above = above \
            & (jnp.arange(above.shape[0]) < limit - 16 - window + 1)
    detected = jnp.any(above)
    start = jnp.argmax(above).astype(jnp.int32)  # first True
    return detected, start


def estimate_cfo_sts(samples, n_pairs: int = 96):
    """CFO estimate (rad/sample) from the short preamble region of an
    aligned frame (samples[0] = frame start). Uses lag-16 products over
    the STS body."""
    x = jnp.asarray(samples, jnp.float32)[: 160]
    prod = cplx.cmul_conj(x[16:16 + n_pairs], x[:n_pairs])
    s = jnp.sum(prod, axis=0)
    return cplx.cangle(s) / 16.0


def estimate_cfo_lts(samples):
    """Fine CFO from the two aligned LTS symbols (samples[0] = frame
    start; LTS symbols at 192..256..320). Lag-64 product."""
    x = jnp.asarray(samples, jnp.float32)
    l1 = x[192:256]
    l2 = x[256:320]
    s = jnp.sum(cplx.cmul_conj(l2, l1), axis=0)
    return cplx.cangle(s) / 64.0


def correct_cfo(samples, eps):
    """Multiply samples by e^{-j*eps*n}: THE derotation, for a
    400-sample head and a 164 240-sample segment alike, its error flat
    in ``n`` (`cplx.cexp_ramp`)."""
    x = jnp.asarray(samples, jnp.float32)
    return cplx.cmul(x, cplx.cexp_ramp(-eps, x.shape[0]))


def lts_pair_metric(samples, limit=None):
    """The LTS timing metric shared by the single-frame and streaming
    locators: cross-correlate the stream against the known long
    training symbol and sum the two 64-apart peak candidates, so
    ``pair[k]`` is large exactly when the first LTS starts at ``k``
    (frame start = k - 192). samples: (n, 2). Returns (n - 127,) f32,
    all values >= 0 except ``limit``-masked tail positions, which are
    -1 sentinels (a LIMIT-length capture would never evaluate them;
    they can never win an argmax while any in-cap position exists).

    Each value depends only on its 128-sample local window — the
    position-locality that lets the chunked streaming scan and the
    per-capture path read bit-identical values off differently-sized
    arrays covering the same samples."""
    x = jnp.asarray(samples, jnp.float32)
    n = x.shape[0]
    lim = n if limit is None else limit
    corr = ccorrelate_valid(x, jnp.asarray(lts_time_symbol()))
    c = cplx.cabs2(corr)                                # (n-63,)
    pair = c[:-64] + c[64:]                             # two-peak sum
    return jnp.where(jnp.arange(pair.shape[0]) < lim - 127, pair, -1.0)


# The local alignment window of `_align_lts`, and what it reads: the
# first LTS starts LTS_OFFSET samples into a frame, and each value of
# `lts_pair_metric` reads LTS_PAIR_SPAN samples. Named because
# `phy/wifi/rx._acquire_head` derives from them how much of a window
# cut AT a frame start its acquisition can read.
ALIGN_BACK = 32
ALIGN_SPAN = 416
LTS_OFFSET = 192
LTS_PAIR_SPAN = 128


def _align_lts(pair, crossing, align_back: int = ALIGN_BACK,
               align_span: int = ALIGN_SPAN):
    """Exact frame start for the plateau that crosses the STS
    threshold at ``crossing``: the two-peak LTS argmax within
    ``[crossing - align_back, crossing - align_back + align_span)``
    minus the 192-sample preamble offset. The ONE alignment rule of
    the per-capture oracle (:func:`locate_frame`) and the K-frame
    chunk scan (:func:`locate_frames`): a local window keeps the
    frames of one capture from stealing each other's peaks."""
    pidx = jnp.arange(pair.shape[0])
    lo = crossing - align_back
    local = jnp.where((pidx >= lo) & (pidx < lo + align_span),
                      pair, -1.0)
    return jnp.argmax(local).astype(jnp.int32) - LTS_OFFSET


def locate_frame(samples, limit=None, window: int = 48,
                 threshold: float = 0.75):
    """Locate and align a frame in a sample stream: STS detection
    gate, LTS cross-correlation timing, coarse+fine CFO. Returns
    (found, frame_start_index, cfo_estimate).

    Whole-array ops at fixed shapes, data-dependent only in *values*
    (argmax index, dynamic_slice at the traced start), so it jits —
    and, crucially for the one-dispatch batched acquisition
    (phy/wifi/rx.acquire_many), it runs under ``vmap``: N captures'
    detects, peak-picks, and CFO estimates become ONE batched graph.

    ``limit`` (static or traced, default: the full length) caps the
    positions the detection gate and the peak-pick consider to those
    a LIMIT-length capture would evaluate. Values at positions below
    the cap depend only on their local window, so trailing zero
    padding never changes them — but a LONGER array also has MORE
    positions, whose windows can overlap the capture's last real
    samples. The batched acquisition pads every lane to one COMMON
    bucket, so each lane passes its OWN power-of-two bucket as
    ``limit`` and its detect/argmax stay bit-identical to the
    per-capture path padded to that bucket.
    """
    import jax

    x = jnp.asarray(samples, jnp.float32)
    n = x.shape[0]
    lim = n if limit is None else limit

    # STS detection gate: the first plateau crossing says WHICH frame
    # of the capture this is (its exact start comes from the LTS
    # timing below)
    detected, coarse = detect_packet(x, window, threshold, limit=limit)

    # LTS timing: cross-correlate with the known long symbol; the two
    # LTS peaks are 64 apart; first LTS starts at frame_start + 192.
    # The peak-pick is capped the same way as the detect gate (the
    # shared metric masks out-of-cap positions to -1 sentinels) and
    # LOCAL to the detected crossing — `locate_frames`' alignment rule
    # at K=1. A global pick is the same peak while the capture holds
    # one frame; a 65 536-sample MTU window holds up to a dozen short
    # high-rate frames, and the global pick then decoded whichever of
    # them correlated best, not the one the window starts at (PR 22).
    pair = lts_pair_metric(x, limit=lim)
    frame_start = jnp.maximum(_align_lts(pair, coarse), 0)

    # CFO from the aligned preamble: coarse (lag-16 STS, wide range)
    # then fine (lag-64 LTS, 4x resolution) on the coarse-corrected
    # head
    frame_head = jax.lax.dynamic_slice(x, (frame_start, 0), (320, 2))
    eps_c = estimate_cfo_sts(frame_head)
    head2 = correct_cfo(frame_head, eps_c)
    eps_f = estimate_cfo_lts(head2)
    return detected, frame_start, eps_c + eps_f


# ----------------------------------------------------- streaming detection
#
# The chunked streaming receiver (backend/framebatch.receive_stream)
# needs the detection front end as "every frame in a LONG multi-frame
# chunk", not "the first frame of a pre-segmented capture".
# `locate_frames` is that generalization, fully traced so a chunk's
# whole scan rides one dispatch; `locate_frame` above stays the K=1
# first-peak oracle (single crossing, global peak-pick) that the
# per-capture receive path — and the identity contract of every
# streaming test — is judged against.


def locate_frames(samples, k: int, limit=None, window: int = 48,
                  threshold: float = 0.75, min_run: int = 33,
                  dead_zone: int = 320, align_back: int = ALIGN_BACK,
                  align_span: int = ALIGN_SPAN, overflow_limit=None):
    """Locate up to ``k`` frame starts in a multi-frame sample chunk:
    top-K STS plateau extraction with dead-zone suppression, each
    candidate LTS-aligned by a local peak-pick. Returns
    ``(found (k,), starts (k,), overflow ())`` — `starts` are exact
    frame-start indices (ascending; -1 on not-found lanes), `overflow`
    is True when an eligible plateau remains beyond the K extracted
    (the caller must report it — frames are never silently dropped).

    The scan (all whole-array ops at fixed shapes, `k` static — jits
    and vmaps):

    1. **plateau gate**: a candidate needs ``min_run`` consecutive
       above-``threshold`` autocorrelation positions — the traced twin
       of `phy/search.find_packets`' host plateau rule (the energy
       roll-off at a frame's END can spike the normalized metric for a
       few positions; a real STS plateau spans ~96).
    2. **top-K extraction**: iteratively take the FIRST eligible
       plateau start, then suppress positions within ``dead_zone``
       samples of it. The dead zone must exceed the plateau run
       (~96 + noise slack, so one frame never yields two candidates)
       and stay under the minimum frame spacing (480 samples, a
       1-symbol frame at zero gap) minus the partial-preamble overhang
       a chunk boundary can introduce — 320, the preamble length,
       satisfies both.
    3. **local LTS alignment**: the shared :func:`lts_pair_metric` is
       computed ONCE over the chunk; each candidate's start is the
       two-peak argmax within ``[d - align_back, d - align_back +
       align_span)`` of its crossing ``d`` minus the 192-sample
       preamble offset. The restriction to a local window is what
       keeps K frames from stealing each other's peaks — the same
       :func:`_align_lts` rule :func:`locate_frame` applies to its one
       crossing (the K=1 oracle relationship; :func:`detect_packet`
       is the matching single-crossing gate).

    ``limit`` (static or traced) caps both the plateau gate and the
    peak-pick to positions a LIMIT-length capture would evaluate,
    exactly as in :func:`locate_frame` — chunk zero-padding (a final
    partial chunk) never manufactures or perturbs candidates.

    ``overflow_limit`` (static or traced, default: everything) caps
    the positions the OVERFLOW scan considers: a streaming chunk owns
    only its first `stride` samples, and a leftover plateau in the
    deferred overlap region is the NEXT chunk's frame, not a drop —
    without the cap it would flag healthy streams. The cap uses the
    plateau crossing index (within ~an alignment span of the exact
    start), which is exact enough for a widen-K diagnostic."""
    import jax

    x = jnp.asarray(samples, jnp.float32)
    n = x.shape[0]
    lim = n if limit is None else limit

    metric, _ = sts_autocorr(x, window)
    above = metric > threshold
    above = above & (jnp.arange(above.shape[0]) < lim - 16 - window + 1)
    # ok[p] <=> positions [p, p+min_run) all above: integer sliding sum
    # (exact cumsum-difference path of _sliding_sum)
    runs = _sliding_sum(above.astype(jnp.int32), min_run)
    ok = runs == min_run
    idx = jnp.arange(ok.shape[0])

    def body(next_free, _):
        cand = ok & (idx >= next_free)
        found = jnp.any(cand)
        d = jnp.argmax(cand).astype(jnp.int32)   # first eligible start
        return jnp.where(found, d + dead_zone, next_free), (found, d)

    next_free, (found, d) = jax.lax.scan(
        body, jnp.int32(0), None, length=k)
    rem = ok & (idx >= next_free)
    if overflow_limit is not None:
        rem = rem & (idx < overflow_limit)
    overflow = jnp.any(rem)

    pair = lts_pair_metric(x, limit=lim)
    starts = jax.vmap(
        lambda di: _align_lts(pair, di, align_back, align_span))(d)
    starts = jnp.where(found, starts, jnp.int32(-1))
    return found, starts, overflow


def estimate_channel(samples):
    """Channel estimate from the two LTS symbols of an aligned,
    CFO-corrected frame (samples[0] = frame start). Returns H as
    (64, 2) pairs (zero on unused bins), normalized to the same scale
    ofdm_demodulate uses, so H == 1 for an identity channel."""
    from ziria_tpu.ops.ofdm import TIME_SCALE

    x = jnp.asarray(samples, jnp.float32)
    l1 = cplx.fft_pair(x[192:256])
    l2 = cplx.fft_pair(x[256:320])
    avg = (l1 + l2) * (0.5 / TIME_SCALE)
    # known LTS is real +-1 (0 on unused): H = Y / X = Y * X (X real unit)
    ref = np.zeros(N_FFT, np.float32)
    ref[(np.arange(-26, 27) % N_FFT)] = LTS_FREQ.astype(np.float32)
    return avg * jnp.asarray(ref)[:, None]
