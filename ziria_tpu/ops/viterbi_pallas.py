"""Pallas TPU kernel for the K=7 soft-decision Viterbi decoder.

Counterpart of the reference's SORA SSE Viterbi brick (`sora_ext_viterbi.c`,
SURVEY.md §2.2) — its ACS is parallel across SSE lanes; here the trellis
state axis (64) lives on VPU sublanes and **frames are batched across the
128 lanes**, so one ACS step is a handful of (64, 128) vector ops with the
path metrics held in a VMEM scratch accumulator for the whole time sweep
(no HBM round-trip per trellis step, unlike a lax.scan whose carry XLA may
spill).

Trellis layout trick: state ``t``'s two predecessors are the *consecutive*
states ``2*(t%32)`` and ``2*(t%32)+1`` (shift-register structure), so the
gather ``metrics[pred]`` is a reshape-(32,2,B)-and-slice, never a real
gather. The radix-4 sweep extends it one level: ``t``'s four
grand-predecessors are the consecutive states ``4*(t%16)+j``, a
reshape-(16,4,B)-and-slice. Traceback avoids per-lane gathers the same
way: the per-state decision bit is selected with a one-hot sum over the
state axis, and the predecessor is computed arithmetically as
``((s & 31) << 1) | d``.

Three stacked levers on the ACS sweep (ISSUE 6 — the decode core is
dependency-chain-bound, not FLOP-bound, so every lever attacks issue
count or serial depth):

- **radix-4** (``radix=4``): TWO trellis steps per kernel iteration,
  butterfly pairs collapsed into a 4-way compare and both decision
  planes packed by ONE MXU matmul — half the sequential m -> m
  dependency chains per trellis step, decode bit-identical to radix 2
  at float32 and int16 (derivation at `_acs_pair_r4` /
  `_acs_pair_lut_int`).
- **LUT branch metrics** (the int paths): a step's branch metric is one
  of only FOUR values ±la±lb, so the per-state coefficient multiplies
  collapse into a 4-entry (16-entry for a radix-4 pair) combo table
  gathered per state with a one-hot MXU dot (`_lut_sel`) — Sora's
  precomputed branch-metric tables, TPU-shaped (`core/autolut.py`'s
  table-gather rewrite, lowered onto the MXU because Mosaic has no
  cheap per-sublane gather).
- **int8 saturating metrics** (``metric_dtype="int8"``): metrics resident
  as (64, 128) int8 — half the int16 path's VMEM state again — with
  soft inputs quantized to ±INT8_QUANT_MAX. The shallow int8 rail makes
  this a statistical trade (BER envelope), not a bit-identity one; see
  ops/viterbi.py and docs/quantized_viterbi.md §int8.

On top, the **fused front end** (`viterbi_decode_batch_fused`): demap +
deinterleave + depuncture run as an in-kernel prologue over the symbol
tile (`_make_fused_acs_kernel`), so the DATA LLRs are produced and
consumed in VMEM and never round-trip HBM between the receiver's
front-end dispatch and the ACS — the kernel's dominant HBM input stream
drops from 2 f32 LLRs per trellis step to the raw equalized subcarriers
(~4-9x smaller at the high rates). Its rate-SWITCHED twin
(`viterbi_decode_mixed_fused`) extends the prologue to the mixed-rate
decode every fleet surface runs: all 8 rates' slot tables stacked into
one static constant bank, row-selected per lane in-kernel from the
traced rate index.

Two kernels either way:
  1. ACS sweep  — grid (batch_tiles, T); streams per-step decision planes
     to HBM **bit-packed 8 states per byte** ((T, 8, 128) uint8 — an 8x
     cut in the kernel's dominant HBM stream vs storing the raw (64, 128)
     plane), keeps metrics (64, 128) in scratch.
  2. Traceback — grid (batch_tiles, T) with a reversed index map; walks
     the packed planes backward (one-hot row select + per-lane variable
     shift unpacks the survivor bit), one (128,)-lane state vector in
     scratch, emitting one bit plane per step.

The module-level tables come from ops/viterbi.py so the Pallas kernel and
the lax.scan reference implementation can never disagree on the trellis.
"""

from __future__ import annotations

import functools
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ziria_tpu.ops.coding import G0, G1
from ziria_tpu.ops.viterbi import (I8_MAX, I8_MIN, I16_MAX, I16_MIN,
                                   INT8_QUANT_MAX, N_STATES, QUANT_MAX,
                                   _check_metric_dtype, _check_radix,
                                   quantize_llrs)

LANES = 128
_NEG = -1e30
_HI = jax.lax.Precision.HIGHEST


def _edge_window(state, d):
    """Encoder window [b, s5..s0] of the edge into `state` with
    pred-low-bit `d` (iota-friendly: `state` may be a traced column).
    Matches ops.viterbi._edge_tables exactly."""
    b = state >> 5
    s = ((state & 31) << 1) | d
    return [b] + [(s >> (5 - i)) & 1 for i in range(6)]


def _edge_parities(state, d):
    """(acc_a, acc_b): the two coded output bits of that edge."""
    win = _edge_window(state, d)
    return tuple(sum(int(g) * w for g, w in zip(taps, win)) % 2
                 for taps in (G0, G1))


def _branch_coeffs(dtype=jnp.float32):
    """(A0, A1, B0, B1): ±1 branch-metric coefficient columns (64, 1).

    Computed from an iota inside the trace (Pallas kernels cannot capture
    array constants); matches ops.viterbi._edge_tables exactly — the edge
    into state t with predecessor-low-bit d carries encoder window
    [b, s5..s0] where b = t>>5 and s = ((t & 31) << 1) | d.
    """
    tt = jax.lax.broadcasted_iota(jnp.int32, (N_STATES, 1), 0)
    cols = []
    for d in (0, 1):
        for acc in _edge_parities(tt, d):
            cols.append((2 * acc - 1).astype(dtype))
    a0, b0, a1, b1 = cols
    return a0, a1, b0, b1


def _branch_coeffs_r4(dtype=jnp.float32):
    """Radix-4 coefficient columns (64, 1): for final state t and
    grand-predecessor selector j = (d2 << 1) | d1 the two-step path is
    step 1 into intermediate state u = ((t & 31) << 1) | d2 with
    pred-low-bit d1, then step 2 into t with pred-low-bit d2 (so t's
    grand-predecessor is the consecutive state 4*(t & 15) + j).
    Returns (step1, step2): step1[j] = (a, b) columns of the step-1
    edge, step2[d2] = those of the step-2 edge — the same VALUES
    _branch_coeffs computes, re-indexed, so the radix-4 candidates are
    expression-for-expression the radix-2 ones."""
    tt = jax.lax.broadcasted_iota(jnp.int32, (N_STATES, 1), 0)

    def cols(state, d):
        return tuple((2 * acc - 1).astype(dtype)
                     for acc in _edge_parities(state, d))

    step1 = [cols(((tt & 31) << 1) | (j >> 1), j & 1) for j in range(4)]
    step2 = [cols(tt, d2) for d2 in (0, 1)]
    return step1, step2


def _branch_pattern(state, d):
    """Sign-pattern index of that edge's branch metric in the
    `_combos4` row order: 0 = la+lb, 1 = la-lb, 2 = -la+lb,
    3 = -la-lb (a = +1 exactly when acc = 1)."""
    acc_a, acc_b = _edge_parities(state, d)
    return (1 - acc_a) * 2 + (1 - acc_b)


def _branch_patterns_r4():
    """Combined 2-step pattern index columns (64, 1) int32 per
    grand-predecessor selector j: pat1 * 4 + pat2, indexing the
    16-entry outer-sum combo table of `_acs_pair_lut_int`."""
    tt = jax.lax.broadcasted_iota(jnp.int32, (N_STATES, 1), 0)
    pats = []
    for j in range(4):
        d2, d1 = j >> 1, j & 1
        u = ((tt & 31) << 1) | d2
        pats.append(_branch_pattern(u, d1) * 4 + _branch_pattern(tt, d2))
    return pats


def _lut_sel(pat, n: int):
    """(64, n) f32 one-hot rows selecting combo row ``pat[t]`` per
    state — the branch-metric "table lookup" lowered onto the MXU:
    ``sel @ combos`` gathers every state's metric in ONE matmul
    (exact: each row sums a single value * 1.0). Sora's LUT
    discipline, TPU-shaped — `core/autolut.py` rewrites small-domain
    maps into table gathers; inside a Mosaic kernel the gather is a
    one-hot dot because there is no cheap per-sublane gather."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (N_STATES, n), 1)
    return (cols == pat).astype(jnp.float32)


def _combos4(la, lb):
    """(4, LANES) int32 branch-metric table of one trellis step: the
    only four values ±la±lb can take, in `_branch_pattern`'s row
    order. Two adds + two negates replace 64-state coefficient
    multiplies; `_lut_sel` dots gather per state."""
    s = la + lb
    d = la - lb
    return jnp.concatenate([s, d, -d, -s], axis=0)


# trellis steps processed per grid step: the per-step ACS is ~15 vector
# ops on (64, 128) tiles — far too little work to amortize a Mosaic grid
# step, which made the r1 kernel grid-overhead-bound (measured 4.6 ms
# for T=8208 at B=128). Unrolling K steps into one kernel body cuts the
# grid by K at ~K x program size.
UNROLL = 64


def _pack_sel():
    """(8, 64) bit-packing matrix: sel[i, s] is (1 << (s & 7)) when s
    lives in byte i (s >> 3 == i), else 0, so sel @ dec gives byte i =
    sum_j dec[8i+j] << j exactly (all values are small ints, exact in
    f32). ONE MXU matmul per step replaces 64 row-slice VPU ops — the
    kernel is issue-bound, not FLOP-bound. Shared by every ACS kernel
    so the packed decision format can never diverge."""
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (8, N_STATES), 1)
    b_idx = jax.lax.broadcasted_iota(jnp.int32, (8, N_STATES), 0)
    return jnp.where((s_idx >> 3) == b_idx,
                     (1 << (s_idx & 7)).astype(jnp.float32), 0.0)


def _pack_planes(pack, *decs):
    """Bit-pack one or two (64, LANES) bool decision planes with a
    SINGLE MXU matmul (planes concatenated along lanes — the radix-4
    "2 steps per write"). Returns the (8, LANES) uint8 plane(s)."""
    cat = decs[0].astype(jnp.float32) if len(decs) == 1 else \
        jnp.concatenate([d.astype(jnp.float32) for d in decs], axis=1)
    packed = jax.lax.dot(pack, cat, precision=_HI)
    # Mosaic has no f32->u8 cast; round-trip through int32
    packed = packed.astype(jnp.int32).astype(jnp.uint8)
    return packed if len(decs) == 1 else \
        tuple(packed[:, i * LANES:(i + 1) * LANES]
              for i in range(len(decs)))


# ------------------------------------------------------------ step bodies
#
# Shared by the plain lane-tile kernels and the fused front-end kernel,
# so a radix/metric combination has exactly ONE arithmetic definition.


def _acs_step_f32(m, la, lb, coeffs, pack):
    """One radix-2 f32 ACS step: (new metrics, packed decision plane).
    The oracle step body every other variant is judged against."""
    a0, a1, b0, b1 = coeffs
    pairs = m.reshape(32, 2, LANES)
    ev = jnp.concatenate([pairs[:, 0, :]] * 2, axis=0)  # pred d=0
    od = jnp.concatenate([pairs[:, 1, :]] * 2, axis=0)  # pred d=1
    cand0 = ev + a0 * la + b0 * lb
    cand1 = od + a1 * la + b1 * lb
    dec = cand1 > cand0
    m = jnp.maximum(cand0, cand1)
    return m, _pack_planes(pack, dec)


def _interleave_dec1(cA, cB):
    """Re-index the radix-4 step-1 comparisons from final-state rows t
    to intermediate-state rows u: u = 2*(t & 31) + d2, and rows
    [32:64) duplicate [0:32) (same intermediate states), so the plane
    is the 2-way interleave of the first 32 rows of each. Returned as
    a 0.0/1.0 f32 plane (what `_pack_planes` casts to anyway): Mosaic
    has no sublane-interleaving reshape of a mask register."""
    f32 = jnp.float32
    return jnp.stack([cA[:32].astype(f32), cB[:32].astype(f32)],
                     axis=1).reshape(N_STATES, LANES)


def _acs_pair_r4_f32(m, la1, lb1, la2, lb2, step1, step2, pack):
    """TWO radix-2 f32 steps as one 4-way butterfly, bit-identical to
    `_acs_step_f32` twice. p[2*d2+d1][t] is built with the exact
    radix-2 expression shape ``g + a*la + b*lb``, so it equals the
    radix-2 step-1 candidate at intermediate state u(t, d2) bit for
    bit; max() commutes with the identically-applied (monotone)
    step-2 adds, so the step-2 comparison and metrics also match bit
    for bit. What radix-4 saves is serial structure: one
    reshape/concat fan-out of m instead of two, one packing matmul
    for both decision planes, and the second step's adds no longer
    wait on a reshape of the first step's max."""
    quads = m.reshape(16, 4, LANES)
    p = []
    for j in range(4):
        g = jnp.concatenate([quads[:, j, :]] * 4, axis=0)
        a, b = step1[j]
        p.append(g + a * la1 + b * lb1)
    dec1 = _interleave_dec1(p[1] > p[0], p[3] > p[2])
    m01 = jnp.maximum(p[0], p[1])      # == m1[u(t, 0)] per row t
    m23 = jnp.maximum(p[2], p[3])      # == m1[u(t, 1)]
    (a0, b0), (a1, b1) = step2
    cand0 = m01 + a0 * la2 + b0 * lb2
    cand1 = m23 + a1 * la2 + b1 * lb2
    dec2 = cand1 > cand0
    m = jnp.maximum(cand0, cand1)
    pk1, pk2 = _pack_planes(pack, dec1, dec2)
    return m, pk1, pk2


def _acs_step_lut_int(m, la, lb, sels4, pack):
    """One radix-2 integer ACS step with LUT branch metrics: the
    4-entry ±la±lb table (`_combos4`) gathered per state by one-hot
    MXU dots. Integer arithmetic is exact, so decisions equal the
    coefficient-multiply step's bit for bit."""
    s4 = _combos4(la, lb).astype(jnp.float32)
    pairs = m.reshape(32, 2, LANES)
    ev = jnp.concatenate([pairs[:, 0, :]] * 2, axis=0)
    od = jnp.concatenate([pairs[:, 1, :]] * 2, axis=0)
    cand0 = ev + jax.lax.dot(sels4[0], s4, precision=_HI).astype(jnp.int32)
    cand1 = od + jax.lax.dot(sels4[1], s4, precision=_HI).astype(jnp.int32)
    dec = cand1 > cand0
    m = jnp.maximum(cand0, cand1)
    return m, _pack_planes(pack, dec)


def _acs_pair_lut_int(m, la1, lb1, la2, lb2, sels16, pack):
    """TWO integer trellis steps as one 4-way butterfly with COMBINED
    2-step LUT branch metrics: the 16 possible values of
    (±la1±lb1) + (±la2±lb2) are built once as an outer sum of the two
    4-entry step tables and gathered per state with one-hot MXU dots.
    Exact integers make every comparison identical to two radix-2
    steps: the step-1 plane compares candidates whose shared step-2
    term cancels, the step-2 plane compares the d1-maxima (max
    distributes over the common addend), and the pair's metrics equal
    the two-step result — so int16/int8 radix-4 decodes are
    bit-identical to their radix-2 twins by construction. The serial
    m -> m chain per 2 steps drops to concat -> add -> max -> max."""
    s1 = _combos4(la1, lb1)
    s2 = _combos4(la2, lb2)
    s16 = (s1.reshape(4, 1, LANES) + s2.reshape(1, 4, LANES)
           ).reshape(16, LANES).astype(jnp.float32)
    quads = m.reshape(16, 4, LANES)
    cand = []
    for j in range(4):
        g = jnp.concatenate([quads[:, j, :]] * 4, axis=0)
        bm = jax.lax.dot(sels16[j], s16, precision=_HI)
        cand.append(g + bm.astype(jnp.int32))
    dec1 = _interleave_dec1(cand[1] > cand[0], cand[3] > cand[2])
    m01 = jnp.maximum(cand[0], cand[1])
    m23 = jnp.maximum(cand[2], cand[3])
    dec2 = m23 > m01
    m = jnp.maximum(m01, m23)
    pk1, pk2 = _pack_planes(pack, dec1, dec2)
    return m, pk1, pk2


# ------------------------------------------------------------ ACS kernels
#
# A sweep is a grid (lane tiles, blocks of UNROLL steps). WHOLE, it runs
# every block of the trellis it is given. BOUNDED, it runs the blocks a
# tile's longest frame reaches, by a count that is data: `n_blocks`,
# int32 a tile, prefetched to scalar memory ahead of the grid
# (`_sweep`). A row at or past a lane's last data bit is a zero
# LLR pair and adds no likelihood, so past a tile's longest lane every
# row is an erasure and the survivor through those rows leads back to
# the best state at that lane's last step: stopping there and tracing
# back from the metrics as they stand gives every bit before a lane's
# `n_bits_real` as the whole trellis gives it (the argument that rests
# the trellis on clause 18's longest frame, `rx.decode_data_mixed`,
# taken to the longest frame the TILE holds).


def _sweep_step(at):
    """``(t, last)`` of a sweep's kernel: the grid step it runs and the
    step after which an ACS hands over its metrics. WHOLE (``at`` None)
    they are the grid's own; a bounded sweep hands them in, read outside
    the conditional it runs the kernel under."""
    return (pl.program_id(1), pl.num_programs(1) - 1) if at is None else at


def _bounded(kernel):
    """`kernel` for a bounded sweep: the tile's block count leads its
    refs (the prefetched scalar), a grid step at or past it runs
    nothing, and the count's last block is the sweep's last."""
    @functools.wraps(kernel)
    def bounded(n_blocks_ref, *refs):
        t, n = pl.program_id(1), n_blocks_ref[pl.program_id(0)]

        @pl.when(t < n)
        def _run():
            kernel(*refs, at=(t, n - 1))

    return bounded


def _block_maps(walk, blocks: int, bounded: bool):
    """The two index maps of a sweep's specs: a (1, UNROLL, ., 128)
    block of the tile's trellis, and a tile's one (1, ., 128) block.
    ``walk(t, n)`` gives the block grid step `t` visits among a tile's
    `n` (the ACS `t`, the traceback `n - 1 - t`). Under a bound `n` is
    the tile's own count and the step is held inside it, so that a
    skipped step names the block the last real one did: no LLR block is
    fetched and no decision or bit block written for it (the pipeline
    moves a block only when its index moves)."""
    if not bounded:
        return (lambda b, t: (b, walk(t, blocks), 0, 0),
                lambda b, t: (b, 0, 0))
    return (lambda b, t, n: (b, jnp.clip(walk(t, n[b]), 0, n[b] - 1), 0, 0),
            lambda b, t, n: (b, 0, 0))


def _sweep(kernel, n_blocks, grid, in_specs, out_specs, scratch_shapes,
           **call):
    """The `pallas_call` of a sweep over ``grid`` = (tiles, blocks):
    whole (``n_blocks`` None: a static grid and no prefetch operand,
    the program it always was) or bounded (``n_blocks`` (tiles,) int32,
    traced, held to [1, blocks], prefetched; the specs' index maps are
    `_block_maps`' bounded pair)."""
    if n_blocks is None:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes, **call)
    return functools.partial(pl.pallas_call(
        _bounded(kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        **call), jnp.clip(n_blocks.astype(jnp.int32), 1, grid[1]))


def _acs_kernel(llr_ref, dec_ref, metrics_out_ref, m_ref, at=None):
    """UNROLL trellis time-steps for one batch tile (f32, radix 2 —
    the oracle kernel).

    llr_ref: (1, UNROLL, 2, 128) this block's (A, B) soft inputs/lane.
    dec_ref: (1, UNROLL, 8, 128) uint8 packed decision planes out:
      byte i, bit j holds the survivor bit of state 8*i + j.
    metrics_out_ref: (64, 128) f32 — final metrics (last write wins).
    m_ref: (64, 128) f32 VMEM scratch — path metrics across the sweep.
    """
    t, last = _sweep_step(at)

    @pl.when(t == 0)
    def _init():
        rows = jax.lax.broadcasted_iota(jnp.int32, (N_STATES, LANES), 0)
        m_ref[:] = jnp.where(rows == 0, 0.0, _NEG).astype(jnp.float32)

    coeffs = _branch_coeffs()
    pack = _pack_sel()

    m = m_ref[:]                                  # (64, 128)
    for j in range(UNROLL):
        la = llr_ref[0, j, 0:1, :]                # (1, 128)
        lb = llr_ref[0, j, 1:2, :]
        m, packed = _acs_step_f32(m, la, lb, coeffs, pack)
        dec_ref[0, j] = packed
    # renorm once per block, not per step: decisions depend only on
    # metric *differences*, and metrics drift by at most
    # UNROLL * max|llr| between renorms — far inside f32 range
    m = m - jnp.max(m, axis=0, keepdims=True)
    m_ref[:] = m

    @pl.when(t == last)
    def _flush():
        metrics_out_ref[0] = m_ref[:]


def _acs_kernel_r4(llr_ref, dec_ref, metrics_out_ref, m_ref, at=None):
    """Radix-4 f32 ACS sweep: UNROLL trellis steps as UNROLL/2
    butterfly pairs — bit-identical to `_acs_kernel` (the pair body
    derives it) with HALF the sequential m -> m fan-out/renorm
    structure per trellis step and one packing matmul per pair."""
    t, last = _sweep_step(at)

    @pl.when(t == 0)
    def _init():
        rows = jax.lax.broadcasted_iota(jnp.int32, (N_STATES, LANES), 0)
        m_ref[:] = jnp.where(rows == 0, 0.0, _NEG).astype(jnp.float32)

    step1, step2 = _branch_coeffs_r4()
    pack = _pack_sel()

    m = m_ref[:]
    for j in range(UNROLL // 2):
        la1 = llr_ref[0, 2 * j, 0:1, :]
        lb1 = llr_ref[0, 2 * j, 1:2, :]
        la2 = llr_ref[0, 2 * j + 1, 0:1, :]
        lb2 = llr_ref[0, 2 * j + 1, 1:2, :]
        m, pk1, pk2 = _acs_pair_r4_f32(m, la1, lb1, la2, lb2,
                                       step1, step2, pack)
        dec_ref[0, 2 * j] = pk1
        dec_ref[0, 2 * j + 1] = pk2
    m = m - jnp.max(m, axis=0, keepdims=True)
    m_ref[:] = m

    @pl.when(t == last)
    def _flush():
        metrics_out_ref[0] = m_ref[:]


def _acs_kernel_i16(llr_ref, dec_ref, metrics_out_ref, m_ref,
                    at=None):
    """int16 saturating-metric ACS sweep — the SORA trade (SURVEY.md
    §2.2: the reference brick ran 16-bit path metrics across SSE
    lanes). Same trellis walk and packed decision format as
    _acs_kernel; what changes is storage width:

    llr_ref: (1, UNROLL, 2, 128) int16 — QUANTIZED soft inputs
      (ops.viterbi.quantize_llrs, |q| <= QUANT_MAX), HALF the f32
      kernel's dominant HBM input stream.
    m_ref: (64, 128) int16 VMEM scratch — half the metric footprint,
      doubling sublane density of the resident state.
    metrics_out_ref: (64, 128) int32 (traceback only argmaxes it).

    Arithmetic runs in int32 vregs across the UNROLL block (exact: the
    in-block drift is <= UNROLL * 2 * QUANT_MAX = 16256 from a
    renormed max of 0, far inside int32); the once-per-block renorm
    pins the max at 0 and the store back to int16 SATURATES — which
    only ever clips unreachable/floored states, never the surviving
    path (docs/quantized_viterbi.md has the bound), so the decode
    matches the f32 kernel bit-for-bit on the same quantized inputs.
    """
    t, last = _sweep_step(at)

    @pl.when(t == 0)
    def _init():
        rows = jax.lax.broadcasted_iota(jnp.int32, (N_STATES, LANES), 0)
        m_ref[:] = jnp.where(rows == 0, 0, I16_MIN).astype(jnp.int16)

    a0, a1, b0, b1 = _branch_coeffs(jnp.int32)
    pack = _pack_sel()

    m = m_ref[:].astype(jnp.int32)                # (64, 128)
    for j in range(UNROLL):
        la = llr_ref[0, j, 0:1, :].astype(jnp.int32)   # (1, 128)
        lb = llr_ref[0, j, 1:2, :].astype(jnp.int32)
        m, packed = _acs_step_f32(m, la, lb, (a0, a1, b0, b1), pack)
        dec_ref[0, j] = packed
    m = m - jnp.max(m, axis=0, keepdims=True)
    m_ref[:] = jnp.clip(m, I16_MIN, I16_MAX).astype(jnp.int16)

    @pl.when(t == last)
    def _flush():
        metrics_out_ref[0] = m_ref[:].astype(jnp.int32)


def _make_acs_kernel_int_lut(radix: int, lo: int, hi: int, sdtype):
    """Integer LUT-branch-metric ACS kernel factory: radix 2 or 4,
    saturation rails (lo, hi) and scratch dtype select the int16 or
    int8 storage discipline. Arithmetic is int32 in-block either way
    (exact — decisions can never round); the once-per-block renorm
    pins the max at 0 and the store saturates into [lo, hi]. For
    int16 that clip provably never touches the surviving path; for
    int8 the rail is shallow and the contract is the BER envelope
    (docs/quantized_viterbi.md §int8)."""

    def kernel(llr_ref, dec_ref, metrics_out_ref, m_ref, at=None):
        t, last = _sweep_step(at)

        @pl.when(t == 0)
        def _init():
            rows = jax.lax.broadcasted_iota(jnp.int32,
                                            (N_STATES, LANES), 0)
            m_ref[:] = jnp.where(rows == 0, 0, lo).astype(sdtype)

        pack = _pack_sel()
        m = m_ref[:].astype(jnp.int32)
        if radix == 2:
            sels4 = [_lut_sel(_branch_pattern(
                jax.lax.broadcasted_iota(jnp.int32, (N_STATES, 1), 0),
                d), 4) for d in (0, 1)]
            for j in range(UNROLL):
                la = llr_ref[0, j, 0:1, :].astype(jnp.int32)
                lb = llr_ref[0, j, 1:2, :].astype(jnp.int32)
                m, packed = _acs_step_lut_int(m, la, lb, sels4, pack)
                dec_ref[0, j] = packed
        else:
            sels16 = [_lut_sel(p, 16) for p in _branch_patterns_r4()]
            for j in range(UNROLL // 2):
                la1 = llr_ref[0, 2 * j, 0:1, :].astype(jnp.int32)
                lb1 = llr_ref[0, 2 * j, 1:2, :].astype(jnp.int32)
                la2 = llr_ref[0, 2 * j + 1, 0:1, :].astype(jnp.int32)
                lb2 = llr_ref[0, 2 * j + 1, 1:2, :].astype(jnp.int32)
                m, pk1, pk2 = _acs_pair_lut_int(m, la1, lb1, la2, lb2,
                                                sels16, pack)
                dec_ref[0, 2 * j] = pk1
                dec_ref[0, 2 * j + 1] = pk2
        m = m - jnp.max(m, axis=0, keepdims=True)
        m_ref[:] = jnp.clip(m, lo, hi).astype(sdtype)

        @pl.when(t == last)
        def _flush():
            metrics_out_ref[0] = m_ref[:].astype(jnp.int32)

    return kernel


_acs_kernel_i16_r4 = _make_acs_kernel_int_lut(4, I16_MIN, I16_MAX,
                                              jnp.int16)
_acs_kernel_i8 = _make_acs_kernel_int_lut(2, I8_MIN, I8_MAX, jnp.int8)
_acs_kernel_i8_r4 = _make_acs_kernel_int_lut(4, I8_MIN, I8_MAX,
                                             jnp.int8)

_ACS_KERNELS = {
    ("float32", 2): _acs_kernel,
    ("float32", 4): _acs_kernel_r4,
    ("int16", 2): _acs_kernel_i16,
    ("int16", 4): _acs_kernel_i16_r4,
    ("int8", 2): _acs_kernel_i8,
    ("int8", 4): _acs_kernel_i8_r4,
}
_SCRATCH_DTYPE = {"float32": jnp.float32, "int16": jnp.int16,
                  "int8": jnp.int8}


@lru_cache(maxsize=None)
def _make_traceback_kernel(unroll: int):
    """Traceback kernel body for ``unroll`` backward steps per grid
    block: select the survivor decision at the current state (one-hot
    sum — no per-lane gather), emit the decoded bit, move to the
    predecessor. The plain lane-tile decode uses UNROLL-step blocks;
    the fused front-end decode uses symbol-aligned blocks
    (spb * n_dbps steps), hence the factory.

    dec_ref: (1, unroll, 8, 128) packed decision planes for trellis
      steps [T-(t+1)*unroll, T-t*unroll), walked in reverse within the
      block.
    metrics_ref: (64, 128) final path metrics (used only at t == 0).
    bits_ref: (1, unroll, 8, 128) int32 out — decoded bit planes, row 0
      of each (8, 128) plane carries it (8 sublanes keeps the store
      tile-aligned).
    s_ref: (8, 128) int32 scratch — row 0 is the current state per lane.
    """
    def kernel(dec_ref, metrics_ref, bits_ref, s_ref, at=None):
        t, _last = _sweep_step(at)

        @pl.when(t == 0)
        def _init():
            # Mosaic's argmax takes float32 only; the quantized ACS's
            # int32 metrics sit inside the int16 rail, so the cast is
            # exact (and a no-op for the float kernel)
            end = jnp.argmax(metrics_ref[0].astype(jnp.float32),
                             axis=0).astype(jnp.int32)
            s_ref[:] = jnp.broadcast_to(end[None, :], (8, LANES))

        rows = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
        state = s_ref[0:1, :]                          # (1, 128)
        for j in reversed(range(unroll)):
            packed = dec_ref[0, j].astype(jnp.int32)   # (8, 128)
            onehot = (rows == (state >> 3)).astype(jnp.int32)
            byte = jnp.sum(packed * onehot, axis=0,
                           keepdims=True)              # (1, 128)
            d = (byte >> (state & 7)) & 1              # unpack bit
            bits_ref[0, j] = jnp.broadcast_to(state >> 5, (8, LANES))
            state = ((state & 31) << 1) | d
        s_ref[0:1, :] = state

    return kernel


def _interpret_default() -> bool:
    # a pure function of the backend: Mosaic on 'tpu', the Pallas
    # interpreter on every other backend (the CPU test suite)
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit,
                   static_argnames=("interpret", "metric_dtype", "radix"))
def _acs_tiles(llrs, interpret: bool, metric_dtype: str = "float32",
               radix: int = 2, n_blocks=None):
    """ACS sweep alone: (nb, Tp, 2, 128) lane tiles (Tp already a
    multiple of UNROLL) -> (packed decision planes, final metrics).
    Split from `_decode_tiles` so the bench breakdown can time the two
    kernels separately (tools/rx_dispatch_bench.viterbi_breakdown —
    the `bench.py:722` "dependency-chain-bound, but WHERE?" answer).

    ``n_blocks`` (nb,) int32, traced: the bound. Tile `b` runs its
    first ``n_blocks[b]`` blocks of UNROLL steps and hands over the
    metrics as they stand after them; its decision planes past them are
    not written (and hold whatever the buffer held). Exact where every
    lane of the tile is an erasure from there on: see the note above
    `_sweep_step`."""
    i_in = metric_dtype in ("int16", "int8")
    nb, Tp = llrs.shape[0], llrs.shape[1]
    TB = Tp // UNROLL                       # grid blocks per trellis
    block, tile = _block_maps(lambda t, n: t, TB, n_blocks is not None)
    return _sweep(
        _ACS_KERNELS[(metric_dtype, radix)], n_blocks, (nb, TB),
        in_specs=[pl.BlockSpec((1, UNROLL, 2, LANES), block)],
        out_specs=[
            pl.BlockSpec((1, UNROLL, 8, LANES), block),
            pl.BlockSpec((1, N_STATES, LANES), tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, Tp, 8, LANES), jnp.uint8),
            jax.ShapeDtypeStruct((nb, N_STATES, LANES),
                                 jnp.int32 if i_in else jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N_STATES, LANES),
                                   _SCRATCH_DTYPE[metric_dtype])],
        interpret=interpret,
    )(llrs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _traceback_tiles(dec, metrics, interpret: bool, n_blocks=None):
    """Traceback alone over UNROLL-step blocks: packed decision planes
    + final metrics -> (nb, Tp, 8, 128) bit planes (row 0 carries the
    decoded bit).

    ``n_blocks`` (nb,) int32, traced: the bound the ACS ran to. Tile
    `b` starts from the metrics at its block ``n_blocks[b] - 1`` and
    walks down to block 0; the bit planes past the bound are not
    written: `_decode_tiles` zeroes them."""
    nb, Tp = dec.shape[0], dec.shape[1]
    TB = Tp // UNROLL
    block, tile = _block_maps(lambda t, n: n - 1 - t, TB,
                              n_blocks is not None)
    return _sweep(
        _make_traceback_kernel(UNROLL), n_blocks, (nb, TB),
        in_specs=[
            pl.BlockSpec((1, UNROLL, 8, LANES), block),
            pl.BlockSpec((1, N_STATES, LANES), tile),
        ],
        out_specs=pl.BlockSpec((1, UNROLL, 8, LANES), block),
        out_shape=jax.ShapeDtypeStruct((nb, Tp, 8, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, LANES), jnp.int32)],
        interpret=interpret,
    )(dec, metrics)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "metric_dtype", "radix"))
def _decode_tiles(llrs, interpret: bool, metric_dtype: str = "float32",
                  radix: int = 2, n_blocks=None):
    """(nb, T, 2, 128) f32|int16 -> (nb, T, 128) uint8 decoded bit
    planes. ``metric_dtype`` picks the ACS kernel ("float32" the
    oracle, "int16"/"int8" the quantized saturating paths — quantized
    llr tiles either way); ``radix`` picks 1 or 2 trellis steps per
    kernel iteration (bit-identical at float32/int16). ``n_blocks``
    (nb,) int32, traced, bounds both sweeps a tile (`_acs_tiles`); a
    bit at or past a tile's bound reads ZERO, so no block the kernels
    left unwritten reaches an output."""
    nb, T = llrs.shape[0], llrs.shape[1]
    # pad the trellis to a multiple of UNROLL with zero LLRs (erasures:
    # they add no likelihood, so the surviving path over the real prefix
    # is unchanged); the garbage pad bits are sliced off below
    Tp = -(-T // UNROLL) * UNROLL
    if Tp != T:
        llrs = jnp.pad(llrs, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    if n_blocks is not None:
        n_blocks = jnp.clip(n_blocks.astype(jnp.int32), 1, Tp // UNROLL)
    dec, metrics = _acs_tiles(llrs, interpret, metric_dtype, radix,
                              n_blocks)
    bits = _traceback_tiles(dec, metrics, interpret, n_blocks)
    bits = bits[:, :T, 0, :].astype(jnp.uint8)
    if n_blocks is not None:
        ran = (jnp.arange(T)[None, :] < n_blocks[:, None] * UNROLL)
        bits = jnp.where(ran[..., None], bits, jnp.uint8(0))
    return bits


def _to_tiles(llrs):
    """(B, T, 2) -> lane tiles (nb, T, 2, 128): frames across the 128
    VPU lanes, lane count padded to a multiple of 128 with zero-LLR
    (erasure) rows. Returns (tiles, B)."""
    B, T = llrs.shape[0], llrs.shape[1]
    Bp = -(-B // LANES) * LANES
    x = jnp.transpose(llrs, (1, 2, 0))
    x = jnp.pad(x, ((0, 0), (0, 0), (0, Bp - B)))
    return x.reshape(T, 2, Bp // LANES, LANES).transpose(2, 0, 1, 3), B


def _quantize_for(md: str, llrs):
    """Quantize float LLRs at the kernel boundary for a quantized
    metric mode (PER-frame scale; already-int16 input passes through
    as pre-quantized — the windowed decode quantizes before cutting
    windows). int8 mode quantizes to ±INT8_QUANT_MAX but keeps the
    int16 storage dtype: the proven (1, UNROLL, 2, 128) int16 tile
    shape carries it, and the kernel's int32 upcast is identical.

    CAVEAT on the passthrough: int16 input is trusted to already be at
    THIS mode's quantization level (|q| <= 15 for int8, <= 127 for
    int16) — there is no runtime range check because the values may be
    traced. Feeding ±127-level integers into the int8 kernel would run
    its shallow saturation rail far outside the documented envelope;
    the only in-repo producer of pre-quantized input (the windowed
    decode above) quantizes with the mode's own qmax."""
    if llrs.dtype == jnp.int16:
        return llrs
    qmax = QUANT_MAX if md == "int16" else INT8_QUANT_MAX
    q, _scale = quantize_llrs(llrs, qmax=qmax)
    return q


def viterbi_decode_batch(llrs, n_bits: int = None, interpret: bool = None,
                         metric_dtype: str = None, radix: int = None,
                         n_blocks=None):
    """Batched soft decode: llrs (B, T, 2) or (B, 2T) -> (B, T) bits.

    Same contract as ops.viterbi.viterbi_decode but over a whole batch of
    frames — the bench/TPU fast path. Lanes are padded to a multiple of
    128 with zero LLRs (erasures), which decode to garbage in the pad
    lanes and are sliced off.

    ``metric_dtype="int16"`` quantizes the LLRs at the kernel boundary
    (ops.viterbi.quantize_llrs, PER-frame scale) and runs the int16
    saturating-metric ACS kernel: half the llr HBM stream, half the
    metric VMEM footprint. ``"int8"`` quantizes to ±INT8_QUANT_MAX and
    runs the int8 saturating kernel with LUT branch metrics — half the
    resident metric state again, BER-envelope accuracy
    (docs/quantized_viterbi.md §int8). Already-int16 input is taken as
    pre-quantized and passed through untouched (the windowed decode
    quantizes before cutting windows). Default/"float32" is the exact
    oracle kernel.

    ``radix=4`` runs the two-steps-per-iteration ACS — bit-identical
    to radix 2 at float32 and int16 (and to the int8 radix-2 kernel on
    the same quantized inputs), half the sequential dependency chain.

    ``n_blocks`` (ceil(B / 128),) int32, traced: a bound for each tile
    of 128 lanes, in blocks of UNROLL steps. The tile's sweeps stop
    there and its bits from there on read zero; every lane of the tile
    has to be an erasure from there on, and then every bit before a
    lane's last real row is the whole trellis's (`_acs_tiles`). Absent,
    the whole trellis: the program this has always traced.
    """
    if interpret is None:
        interpret = _interpret_default()
    md = _check_metric_dtype(metric_dtype)
    radix = _check_radix(radix)
    llrs = jnp.asarray(llrs)
    if llrs.ndim == 2:
        llrs = llrs.reshape(llrs.shape[0], -1, 2)
    if md == "float32":
        llrs = llrs.astype(jnp.float32)
    else:
        llrs = _quantize_for(md, llrs)                # int16 (B, T, 2)
    x, B = _to_tiles(llrs)
    bits = _decode_tiles(x, interpret, md, radix, n_blocks)  # (nb, T, 128)
    bits = bits.transpose(0, 2, 1).reshape(-1, llrs.shape[1])[:B]
    if n_bits is not None:
        bits = bits[:, :n_bits]
    return bits


DEFAULT_WINDOW_OVERLAP = 96   # ~14 constraint lengths of warmup


def viterbi_decode_batch_opt(llrs, n_bits: int = None,
                             window: int = None,
                             interpret: bool = None,
                             metric_dtype: str = None,
                             radix: int = None, n_blocks=None):
    """ONE dispatch for the batch decode's window/metric/radix options
    (review r5: the if/else was copied at every call site):
    ``window=None/0`` runs the exact kernel, ``window=N`` the
    sliding-window parallel decode below; ``metric_dtype`` selects the
    f32 oracle or a quantized saturating kernel and ``radix`` the
    steps-per-iteration either way. ``n_blocks`` bounds the exact
    kernel's sweeps a tile (`viterbi_decode_batch`); the windowed
    decode, whose windows ride as lanes of their own, keeps its whole
    trellis."""
    if window:
        return viterbi_decode_batch_windowed(
            llrs, n_bits=n_bits, window=window, interpret=interpret,
            metric_dtype=metric_dtype, radix=radix)
    return viterbi_decode_batch(llrs, n_bits=n_bits, interpret=interpret,
                                metric_dtype=metric_dtype, radix=radix,
                                n_blocks=n_blocks)


def viterbi_decode_batch_windowed(llrs, n_bits: int = None,
                                  window: int = 1024,
                                  overlap: int = DEFAULT_WINDOW_OVERLAP,
                                  interpret: bool = None,
                                  metric_dtype: str = None,
                                  radix: int = None,
                                  _decode=None):
    """Sliding-window PARALLEL decode: cut the T-step dependency chain
    into ceil(T/window) overlapping windows and run them as EXTRA BATCH
    LANES of the same kernel.

    The full-frame decode is dependency-chain-bound on TPU: 64 states
    fill half a VPU sublane tile while T (~8k for a 1000-byte frame)
    ACS steps run strictly sequentially, leaving the chip ~96% idle at
    B=128 (BENCH r4 roofline). Windowing converts that serial depth
    into batch parallelism: sequential depth drops from T to
    window + 2*overlap, and B*nwin lanes fill the idle lane tiles.

    Accuracy is the standard truncated-Viterbi argument (the
    reference's SORA brick likewise decodes with finite traceback
    depth): survivor paths of a K=7 code merge within ~5-10 constraint
    lengths with overwhelming probability, so each window's kept
    region [overlap, overlap+window) is decoded from fully-merged
    survivors; ``overlap`` defaults to 96 ≈ 14 constraint lengths.
    Boundary semantics match the full decode exactly where it matters:
    window 0 starts at position 0 with the kernel's known-state-0 init
    (its span is [0, window+2*overlap) and it keeps [0, window)), and
    every window ends on argmax metrics like the full decode; frames
    short enough for one window fall through to the exact path. On
    clean or operating-SNR inputs the output is bit-identical to
    ``viterbi_decode_batch`` (pinned by tests); on arbitrary
    adversarial inputs it is the windowed approximation, which is why
    this is an opt-in variant rather than the default.
    """
    if interpret is None:
        interpret = _interpret_default()
    md = _check_metric_dtype(metric_dtype)
    rdx = _check_radix(radix)
    if _decode is None:
        # the production engine; tools/windowed_ber.py injects the
        # lax.scan engine so the BER study measures exactly this
        # windowing math without interpret-mode Pallas cost on CPU
        def _decode(x):
            return viterbi_decode_batch(x, interpret=interpret,
                                        metric_dtype=md, radix=rdx)
    llrs = jnp.asarray(llrs)
    if llrs.ndim == 2:
        llrs = llrs.reshape(llrs.shape[0], -1, 2)
    if md != "float32":
        # quantize PER FRAME **before** cutting windows: every window
        # then slices the exact integers the full-frame decode sees
        # (the batch decode passes int16 through untouched), so
        # windowed int16/int8 == full int16/int8 by the same survivor-
        # merge argument as f32 — and no lane's scale depends on its
        # batch-mates. An injected _decode must accept int16 input.
        llrs = _quantize_for(md, llrs)
    else:
        llrs = llrs.astype(jnp.float32)
    B, T = llrs.shape[0], llrs.shape[1]
    ext = window + 2 * overlap
    if T <= ext:
        bits = _decode(llrs)
        return bits[:, :n_bits] if n_bits is not None else bits
    nwin = -(-T // window)
    starts = np.arange(nwin) * window - overlap
    starts[0] = 0            # window 0 keeps the known-state-0 start
    idx = jnp.asarray(starts)[:, None] + jnp.arange(ext)[None, :]
    # out-of-frame positions become zero-LLR erasures — the same
    # "adds no likelihood" padding the full decode uses for T%UNROLL.
    # idx >= 0 matters when window < overlap (review r5): without it,
    # negative warmup positions clip to 0 and feed repeated
    # full-confidence position-0 LLRs into the warmup instead of
    # neutral erasures
    valid = (idx >= 0) & (idx < T)
    wins = jnp.where(valid[None, :, :, None],
                     llrs[:, jnp.clip(idx, 0, T - 1), :],
                     jnp.zeros((), llrs.dtype))
    bits = _decode(wins.reshape(B * nwin, ext, 2))
    bits = bits.reshape(B, nwin, ext)
    keep = (jnp.where(jnp.arange(nwin) == 0, 0, overlap)[:, None]
            + jnp.arange(window)[None, :])             # (nwin, window)
    bits = jnp.take_along_axis(
        bits, jnp.broadcast_to(keep[None], (B, nwin, window)), axis=2)
    bits = bits.reshape(B, nwin * window)[:, :T]
    if n_bits is not None:
        bits = bits[:, :n_bits]
    return bits


# ------------------------------------------------------ fused front end
#
# The steady-state DATA decode's front end (demap -> deinterleave ->
# depuncture) is position-LOCAL per OFDM symbol: a symbol's n_cbps
# demapped LLRs land in exactly that symbol's 2*n_dbps depunctured
# slots (the deinterleaver permutes within the symbol; the puncture
# pattern period divides the symbol's slot count for every 802.11a
# rate). So for a KNOWN rate the whole front end is a static per-slot
# table — which subcarrier, which component, which level formula,
# which gain, erasure or not — and can run as an in-kernel prologue
# over the raw equalized symbol tile: one one-hot MXU gather for the
# component values, one for the gains, a handful of elementwise level
# ops, and the ACS consumes the LLRs straight out of VMEM. The LLR
# stream (the ACS kernel's dominant HBM input, 8 B per trellis step
# per lane) never exists in HBM at all.
#
# The tables are rate-static — but that is no longer a scope boundary:
# `viterbi_decode_mixed_fused` (below) stacks all 8 rates' tables into
# ONE constant bank and row-selects per lane IN-KERNEL from the traced
# rate index, so the mixed-rate lax.switch decode keeps its one
# rate-agnostic Viterbi across the batch AND gets the VMEM-resident
# LLR prologue (docs/architecture.md's decode-roofline section).


@lru_cache(maxsize=None)
def _front_tables(n_bpsc: int, n_cbps: int, n_dbps: int, coding: str):
    """Static one-symbol slot tables of the fused in-kernel front end.

    For depunctured slot p in [0, 2*n_dbps) of one OFDM symbol:
    ``sel_x`` (T2, 96) one-hot picks the slot's component value from
    the flattened (48 subcarriers x I/Q) symbol vector, ``sel_g``
    (T2, 48) its subcarrier's |H|^2 gain, and ``lcols`` (T2, 8) packs
    the per-slot constants (cols 0-2: level one-hot, col 3: level-1
    amplitude, col 4: depuncture validity — punctured slots stay
    all-zero and decode as exact 0.0 erasures). Composed from the SAME
    primitives the XLA front end runs (`demap.demap_bit_layout`,
    `interleave.deinterleave_slots`, `coding.PUNCTURE_KEEP`), so the
    two front ends cannot drift."""
    from ziria_tpu.ops.coding import PUNCTURE_KEEP
    from ziria_tpu.ops.demap import demap_bit_layout
    from ziria_tpu.ops.interleave import deinterleave_slots

    T2 = 2 * n_dbps
    keep = PUNCTURE_KEEP[coding]
    period, kept = keep.size, int(keep.sum())
    sub, bit = deinterleave_slots(n_cbps, n_bpsc)
    comp, lev, amp_b = demap_bit_layout(n_bpsc)
    sel_x = np.zeros((T2, 96), np.float32)
    sel_g = np.zeros((T2, 48), np.float32)
    lcols = np.zeros((T2, 8), np.float32)
    nkeep_before = np.cumsum(keep) - keep
    for p in range(T2):
        blk, off = divmod(p, period)
        if not keep[off]:
            continue
        q = blk * kept + int(nkeep_before[off])
        c, b = int(sub[q]), int(bit[q])
        sel_x[p, 2 * c + int(comp[b])] = 1.0
        sel_g[p, c] = 1.0
        lcols[p, int(lev[b])] = 1.0
        lcols[p, 3] = float(amp_b[b])
        lcols[p, 4] = 1.0
    return sel_x, sel_g, lcols


@lru_cache(maxsize=None)
def _make_fused_acs_kernel(spb: int, n_dbps: int, norm: float,
                           radix: int):
    """Fused front-end + ACS kernel for one rate (f32 metrics): each
    grid block covers ``spb`` OFDM symbols (chosen so a block is >=
    UNROLL trellis steps), demaps/deinterleaves/depunctures them in
    VMEM via the static slot tables, then runs the radix-2 or radix-4
    ACS over the block's spb*n_dbps steps. Per-lane true bit counts
    arrive as an input row: slots at/after a lane's count become exact
    0.0 erasures, the same mask decode_data_bucketed applies."""
    T2 = 2 * n_dbps

    def kernel(sym_ref, gain_ref, nbits_ref, selx_ref, selg_ref,
               lcol_ref, dec_ref, metrics_out_ref, m_ref):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _init():
            rows = jax.lax.broadcasted_iota(jnp.int32,
                                            (N_STATES, LANES), 0)
            m_ref[:] = jnp.where(rows == 0, 0.0, _NEG).astype(jnp.float32)

        pack = _pack_sel()
        if radix == 2:
            coeffs = _branch_coeffs()
        else:
            step1, step2 = _branch_coeffs_r4()
        l0 = lcol_ref[:, 0:1]
        l1 = lcol_ref[:, 1:2]
        l2 = lcol_ref[:, 2:3]
        amp = lcol_ref[:, 3:4]
        valid = lcol_ref[:, 4:5]
        nb_row = nbits_ref[0, 0:1, :]                  # (1, 128)
        srow = jax.lax.broadcasted_iota(jnp.int32, (T2, LANES), 0) >> 1

        m = m_ref[:]
        for k in range(spb):
            # demap: one-hot MXU gathers are exact (each row sums one
            # value * 1.0), and the level formulas/multiply order are
            # demap()'s own, so the LLRs match the XLA front end bit
            # for bit (zero-sign differences at erasures aside, which
            # no comparison can see)
            x = jax.lax.dot(selx_ref[:], sym_ref[0, k], precision=_HI)
            g = jax.lax.dot(selg_ref[:], gain_ref[0], precision=_HI)
            xs = x * norm
            ax = jnp.abs(xs)
            f = l0 * xs + l1 * (amp - ax) + l2 * (2.0 - jnp.abs(ax - 4.0))
            llr = f * g * valid
            step0 = (t * spb + k) * n_dbps
            llr = jnp.where(step0 + srow < nb_row, llr, 0.0)
            base = k * n_dbps
            if radix == 2:
                for jj in range(n_dbps):
                    la = llr[2 * jj:2 * jj + 1, :]
                    lb = llr[2 * jj + 1:2 * jj + 2, :]
                    m, packed = _acs_step_f32(m, la, lb, coeffs, pack)
                    dec_ref[0, base + jj] = packed
            else:
                for jj in range(n_dbps // 2):
                    la1 = llr[4 * jj:4 * jj + 1, :]
                    lb1 = llr[4 * jj + 1:4 * jj + 2, :]
                    la2 = llr[4 * jj + 2:4 * jj + 3, :]
                    lb2 = llr[4 * jj + 3:4 * jj + 4, :]
                    m, pk1, pk2 = _acs_pair_r4_f32(
                        m, la1, lb1, la2, lb2, step1, step2, pack)
                    dec_ref[0, base + 2 * jj] = pk1
                    dec_ref[0, base + 2 * jj + 1] = pk2
        m = m - jnp.max(m, axis=0, keepdims=True)
        m_ref[:] = m

        @pl.when(t == pl.num_programs(1) - 1)
        def _flush():
            metrics_out_ref[0] = m_ref[:]

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("spb", "n_dbps", "norm", "radix",
                                    "interpret"))
def _fused_decode_tiles(x, g, nbits, sel_x, sel_g, lcols, spb: int,
                        n_dbps: int, norm: float, radix: int,
                        interpret: bool):
    """Fused-front-end decode over lane tiles: symbol tiles
    (nb, n_sym_p, 96, 128) + gain (nb, 48, 128) + per-lane bit counts
    -> (nb, Tp, 128) decoded bit planes."""
    nb, n_sym_p = x.shape[0], x.shape[1]
    NB = n_sym_p // spb
    steps = spb * n_dbps
    Tp = NB * steps
    T2 = 2 * n_dbps
    dec, metrics = pl.pallas_call(
        _make_fused_acs_kernel(spb, n_dbps, norm, radix),
        grid=(nb, NB),
        in_specs=[
            pl.BlockSpec((1, spb, 96, LANES), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, 48, LANES), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, 8, LANES), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((T2, 96), lambda b, t: (0, 0)),
            pl.BlockSpec((T2, 48), lambda b, t: (0, 0)),
            pl.BlockSpec((T2, 8), lambda b, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, steps, 8, LANES), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, N_STATES, LANES), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, Tp, 8, LANES), jnp.uint8),
            jax.ShapeDtypeStruct((nb, N_STATES, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N_STATES, LANES), jnp.float32)],
        interpret=interpret,
    )(x, g, nbits, sel_x, sel_g, lcols)

    bits = pl.pallas_call(
        _make_traceback_kernel(steps),
        grid=(nb, NB),
        in_specs=[
            pl.BlockSpec((1, steps, 8, LANES),
                         lambda b, t, _n=NB: (b, _n - 1 - t, 0, 0)),
            pl.BlockSpec((1, N_STATES, LANES), lambda b, t: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, steps, 8, LANES),
                               lambda b, t, _n=NB: (b, _n - 1 - t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, Tp, 8, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, LANES), jnp.int32)],
        interpret=interpret,
    )(dec, metrics)
    return bits[:, :, 0, :].astype(jnp.uint8)


def viterbi_decode_batch_fused(data, gain, rate, n_bits: int = None,
                               nbits_real=None, radix: int = None,
                               interpret: bool = None):
    """Fused-front-end batch decode: equalized, pilot-tracked DATA
    subcarriers -> decoded bits, with demap + deinterleave +
    depuncture executed as an IN-KERNEL prologue of the ACS sweep —
    the LLRs live and die in VMEM.

    data: (B, n_sym, 48, 2) equalized data-subcarrier pairs (the
    output of rx._front_symbols under vmap); gain: (B, 48) |H|^2
    reliability weights; rate: the RateParams of the ONE rate — the
    slot tables are rate-static, which is the fused path's scope
    boundary (the mixed-rate switch keeps the XLA front end);
    nbits_real: per-lane traced true data-bit counts (slots at/after
    become exact 0.0 erasures — decode_data_bucketed's mask), default
    everything real.

    float32 metrics only: the quantized paths scale by the whole
    frame's LLR peak before the first ACS step, which the in-kernel
    prologue never materializes; callers fall back to the unfused
    front for int16/int8. Decoded bits are bit-identical to the
    unfused decode on operating inputs (the demap arithmetic is
    expression-identical; only zero-sign noise at erasures and the
    block-cadence renorm differ, neither of which moves a comparison
    at operating SNR — pinned by tests/test_viterbi_radix4.py)."""
    if interpret is None:
        interpret = _interpret_default()
    radix = _check_radix(radix)
    data = jnp.asarray(data, jnp.float32)
    gain = jnp.asarray(gain, jnp.float32)
    B, n_sym = data.shape[0], data.shape[1]
    n_dbps = rate.n_dbps
    # symbols per grid block: lowest count giving >= UNROLL trellis
    # steps, so low rates (n_dbps 24..48) still amortize the Mosaic
    # grid step the way the plain kernel's UNROLL does
    spb = -(-UNROLL // n_dbps)
    n_sym_p = -(-n_sym // spb) * spb
    if n_sym_p != n_sym:
        # pad symbols produce garbage LLRs, but every pad slot is at/
        # after each lane's nbits and masks to a 0.0 erasure
        data = jnp.pad(data,
                       ((0, 0), (0, n_sym_p - n_sym), (0, 0), (0, 0)))
    T = n_sym * n_dbps
    if nbits_real is None:
        nbits = jnp.full((B,), T, jnp.int32)
    else:
        nbits = jnp.broadcast_to(
            jnp.asarray(nbits_real, jnp.int32), (B,))
    Bp = -(-B // LANES) * LANES
    nb_tiles = Bp // LANES
    x = data.reshape(B, n_sym_p, 96)          # (48, I/Q) -> 2c + comp
    x = jnp.pad(x, ((0, Bp - B), (0, 0), (0, 0)))
    x = x.transpose(1, 2, 0).reshape(n_sym_p, 96, nb_tiles, LANES) \
         .transpose(2, 0, 1, 3)
    g = jnp.pad(gain, ((0, Bp - B), (0, 0)))
    g = g.transpose(1, 0).reshape(48, nb_tiles, LANES).transpose(1, 0, 2)
    nbp = jnp.pad(nbits, (0, Bp - B)).reshape(nb_tiles, 1, LANES)
    nbp = jnp.broadcast_to(nbp, (nb_tiles, 8, LANES))
    sel_x, sel_g, lcols = _front_tables(rate.n_bpsc, rate.n_cbps,
                                        rate.n_dbps, rate.coding)
    from ziria_tpu.ops.demap import _NORM
    bits = _fused_decode_tiles(
        x, g, nbp, jnp.asarray(sel_x), jnp.asarray(sel_g),
        jnp.asarray(lcols), spb, n_dbps, float(_NORM[rate.n_bpsc]),
        radix, interpret)
    bits = bits.transpose(0, 2, 1).reshape(Bp, -1)[:B, :T]
    if n_bits is not None:
        bits = bits[:, :n_bits]
    return bits


# ------------------------------------------- rate-switched fused front end
#
# The mixed-rate decode (phy/wifi/rx.decode_data_mixed) runs ONE
# rate-agnostic Viterbi over a batch whose lanes carry different rates;
# until ISSUE 20 its front end stayed in XLA because the fused tables
# above are rate-static. The scheduling fact that un-blocks it: every
# 802.11a n_dbps (24, 36, 48, 72, 96, 144, 192, 216) is a multiple of
# 12, so any 12-trellis-step window starting at a multiple of 12 lies
# inside exactly ONE OFDM symbol at EVERY rate, covering a 24-slot
# stretch of that rate's depunctured stream that starts at a multiple
# of 24. Chop each rate's (2*n_dbps, ...) slot tables into
# n_dbps/12 <= 18 chunks of 24 rows, stack them as one
# (8, 18, 24, ...) constant bank, and a kernel block of 72 steps
# (6 sub-blocks; 72 divides every bucket's n_sym_bucket * 216 trellis)
# needs only LEADING-dim indexing — static rate row, traced chunk
# index — to fetch the right 24 rows: the banks stay static to Mosaic
# and there is no per-lane gather. Per sub-block the kernel computes
# all 8 rates' LLRs and lane-selects with the traced rate index — the
# SAME 8-way compute-then-select the vmapped lax.switch lowers to, so
# nothing is wasted relative to the unfused graph, while the LLRs (the
# ACS kernel's dominant HBM input) and the 8-way-redundant XLA front
# end both disappear from HBM: the fused graph runs ONE rate-
# independent `rx._front_symbols` per lane instead of 8 per-rate
# branches.
#
# Gains ride the SAME one-hot: sel_x rows pick component 2*c + comp of
# the flattened symbol, and a (96, LANES) gain plane with row
# 2*c + u = gain[c] makes `sel_x @ gain2` the exact |H|^2 gather — no
# separate gain bank, keeping the constant-bank bytes (~1.4 MB) below
# the LLR bytes they remove.

#: trellis steps per mixed-fused sub-block: gcd of all 8 rates' n_dbps
MIXED_SUB = 12
#: trellis steps per mixed-fused grid block (6 sub-blocks; divides
#: the fused kernel's n_sym_bucket * MAX_DBPS trellis at every bucket
#: since 72 | 216, and so the mixed decode's bound one, 152 x 216)
MIXED_UNROLL = 72
#: chunks per rate row in the stacked bank: max n_dbps / MIXED_SUB
MIXED_CHUNKS = 18


@lru_cache(maxsize=None)
def _mixed_rate_geometry():
    """(n_dbps, norm) per rate in RATE_MBPS_ORDER — the static per-rate
    constants the mixed-fused kernel unrolls over. Imported lazily so
    ops/ keeps no import-time dependency on phy/."""
    from ziria_tpu.ops.demap import _NORM
    from ziria_tpu.phy.wifi.params import RATE_MBPS_ORDER, RATES
    ndbps = tuple(RATES[m].n_dbps for m in RATE_MBPS_ORDER)
    norms = tuple(float(_NORM[RATES[m].n_bpsc]) for m in RATE_MBPS_ORDER)
    return ndbps, norms


@lru_cache(maxsize=None)
def mixed_front_tables():
    """The stacked all-rates slot-table bank of the rate-switched fused
    front end: ``bank_x`` (8, 18, 24, 96) and ``bank_l`` (8, 18, 24, 8)
    float32, where row r is rate RATE_MBPS_ORDER[r] and chunk c holds
    depunctured slot rows [24c, 24c + 24) of that rate's `_front_tables`
    (chunks at/after n_dbps[r]/12 stay zero — they are never selected).
    Row-selecting (r, c) reproduces the per-rate tables
    `demap.demap_bit_layout` / `interleave.deinterleave_slots` /
    `coding.PUNCTURE_KEEP` emit today, which is the jax-free pin in
    tests/test_viterbi_fused_mixed.py. Numpy only — no trace, no
    compile."""
    from ziria_tpu.phy.wifi.params import RATE_MBPS_ORDER, RATES
    ndbps, _norms = _mixed_rate_geometry()
    bank_x = np.zeros((8, MIXED_CHUNKS, 2 * MIXED_SUB, 96), np.float32)
    bank_l = np.zeros((8, MIXED_CHUNKS, 2 * MIXED_SUB, 8), np.float32)
    for r, m in enumerate(RATE_MBPS_ORDER):
        rate = RATES[m]
        sel_x, _sel_g, lcols = _front_tables(rate.n_bpsc, rate.n_cbps,
                                             rate.n_dbps, rate.coding)
        for c in range(ndbps[r] // MIXED_SUB):
            rows = slice(2 * MIXED_SUB * c, 2 * MIXED_SUB * (c + 1))
            bank_x[r, c] = sel_x[rows]
            bank_l[r, c] = lcols[rows]
    return bank_x, bank_l


@lru_cache(maxsize=None)
def _make_mixed_fused_acs_kernel(n_sym_p: int, radix: int):
    """Rate-switched fused front-end + ACS kernel (f32 metrics): each
    grid block covers MIXED_UNROLL trellis steps of the bucket-maximal
    mixed trellis. Per 12-step sub-block and per rate (a STATIC 8-way
    unroll — the same 8-way compute the vmapped lax.switch lowers to),
    the symbol index and bank chunk are computed from the traced block
    position, the 24-slot tables fetched by leading-dim indexing, the
    demap expression evaluated in VMEM, and the lanes running that rate
    selected with `where` on the traced rate-index row. Slots at/after
    a lane's true bit count become exact 0.0 erasures (the mask
    decode_data_mixed applies), which also covers the clamped
    symbol-index reads past a low-rate lane's bucket."""
    ndbps, norms = _mixed_rate_geometry()
    nsub = MIXED_UNROLL // MIXED_SUB
    T2 = 2 * MIXED_SUB

    def kernel(sym_ref, gain_ref, nbits_ref, ridx_ref, *refs):
        bx_refs = refs[:8]                 # per-rate (cyc_r, 24, 96)
        bl_refs = refs[8:16]               # per-rate (cyc_r, 24, 8)
        dec_ref, metrics_out_ref, m_ref = refs[16:]
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _init():
            rows = jax.lax.broadcasted_iota(jnp.int32,
                                            (N_STATES, LANES), 0)
            m_ref[:] = jnp.where(rows == 0, 0.0, _NEG).astype(jnp.float32)

        pack = _pack_sel()
        if radix == 2:
            coeffs = _branch_coeffs()
        else:
            step1, step2 = _branch_coeffs_r4()
        nb_row = nbits_ref[0, 0:1, :]                  # (1, 128)
        r_row = ridx_ref[0, 0:1, :]                    # (1, 128) int32
        srow = jax.lax.broadcasted_iota(jnp.int32, (T2, LANES), 0) >> 1
        gain = gain_ref[0]                             # (48, 128)
        # (96, 128) plane with row 2c+u = gain[c]: sel_x @ gain2 is
        # then the exact per-slot |H|^2 gather (one-hot rows sum a
        # single value * 1.0) — no separate gain bank needed
        gain2 = jnp.concatenate([gain[:, None, :], gain[:, None, :]],
                                axis=1).reshape(96, LANES)

        # the sub-block walk is a lax.fori_loop, not a python unroll:
        # the lowered (and analytically costed) loop body is ONE
        # 12-step sub-block — 8 per-rate table reads + 16 small MXU
        # dots + 12 ACS steps — instead of MIXED_UNROLL steps of
        # straight-line code. Decision planes go straight to dec_ref
        # at a traced leading-dim offset (supported store form).
        def _sub_block(j, m):
            s0 = (t * nsub + j) * MIXED_SUB            # traced scalar
            llr = jnp.zeros((T2, LANES), jnp.float32)
            for r in range(8):
                ndb = ndbps[r]
                # this sub-block's symbol at rate r, clamped into the
                # resident tile: a low-rate lane's trellis ends at
                # n_sym_p * ndb < s0 for the clamped region, so every
                # clamped read feeds only nbits-masked erasure steps
                k_r = jnp.minimum(s0 // ndb, n_sym_p - 1)
                c_r = (s0 % ndb) // MIXED_SUB          # bank chunk
                selx = bx_refs[r][c_r]                 # (24, 96)
                lc = bl_refs[r][c_r]                   # (24, 8)
                x = jax.lax.dot(selx, sym_ref[0, k_r], precision=_HI)
                g = jax.lax.dot(selx, gain2, precision=_HI)
                xs = x * norms[r]
                ax = jnp.abs(xs)
                f = (lc[:, 0:1] * xs + lc[:, 1:2] * (lc[:, 3:4] - ax)
                     + lc[:, 2:3] * (2.0 - jnp.abs(ax - 4.0)))
                # where, not multiply: the vmapped switch also computes
                # every branch and SELECTS — NaN/Inf in a non-selected
                # rate's arithmetic must not leak across lanes
                llr = jnp.where(r_row == r, f * g * lc[:, 4:5], llr)
            llr = jnp.where(s0 + srow < nb_row, llr, 0.0)
            base = j * MIXED_SUB
            if radix == 2:
                for jj in range(MIXED_SUB):
                    la = llr[2 * jj:2 * jj + 1, :]
                    lb = llr[2 * jj + 1:2 * jj + 2, :]
                    m, packed = _acs_step_f32(m, la, lb, coeffs, pack)
                    dec_ref[0, base + jj] = packed
            else:
                for jj in range(MIXED_SUB // 2):
                    la1 = llr[4 * jj:4 * jj + 1, :]
                    lb1 = llr[4 * jj + 1:4 * jj + 2, :]
                    la2 = llr[4 * jj + 2:4 * jj + 3, :]
                    lb2 = llr[4 * jj + 3:4 * jj + 4, :]
                    m, pk1, pk2 = _acs_pair_r4_f32(
                        m, la1, lb1, la2, lb2, step1, step2, pack)
                    dec_ref[0, base + 2 * jj] = pk1
                    dec_ref[0, base + 2 * jj + 1] = pk2
            return m

        m = jax.lax.fori_loop(0, nsub, _sub_block, m_ref[:])
        m = m - jnp.max(m, axis=0, keepdims=True)
        m_ref[:] = m

        @pl.when(t == pl.num_programs(1) - 1)
        def _flush():
            metrics_out_ref[0] = m_ref[:]

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("n_sym_p", "t_max", "radix",
                                    "interpret"))
def _mixed_fused_decode_tiles(x, g, nbits, ridx, bx, bl, n_sym_p: int,
                              t_max: int, radix: int, interpret: bool):
    """Rate-switched fused decode over lane tiles: whole-resident
    symbol tiles (nb, n_sym_p, 96, 128) + gain (nb, 48, 128) + per-lane
    bit-count/rate-index rows + the stacked table bank ->
    (nb, t_max, 128) decoded bit planes.

    The stacked (8, MIXED_CHUNKS, ...) bank enters the kernel as 8
    per-rate operands trimmed to each rate's real chunk count
    (n_dbps/12): the in-kernel chunk read then dynamic-slices one
    small per-rate table, never the whole bank — rate r's row is a
    trace-time static slice, so nothing is gathered at runtime."""
    nb = x.shape[0]
    NB = t_max // MIXED_UNROLL
    ndbps, _norms = _mixed_rate_geometry()
    cyc = [n // MIXED_SUB for n in ndbps]
    bxr = [bx[r, :cyc[r]] for r in range(8)]
    blr = [bl[r, :cyc[r]] for r in range(8)]
    bank_specs = (
        [pl.BlockSpec((cyc[r], 2 * MIXED_SUB, 96),
                      lambda b, t: (0, 0, 0)) for r in range(8)]
        + [pl.BlockSpec((cyc[r], 2 * MIXED_SUB, 8),
                        lambda b, t: (0, 0, 0)) for r in range(8)])
    dec, metrics = pl.pallas_call(
        _make_mixed_fused_acs_kernel(n_sym_p, radix),
        grid=(nb, NB),
        in_specs=[
            pl.BlockSpec((1, n_sym_p, 96, LANES),
                         lambda b, t: (b, 0, 0, 0)),
            pl.BlockSpec((1, 48, LANES), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, 8, LANES), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, 8, LANES), lambda b, t: (b, 0, 0)),
        ] + bank_specs,
        out_specs=[
            pl.BlockSpec((1, MIXED_UNROLL, 8, LANES),
                         lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, N_STATES, LANES), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, t_max, 8, LANES), jnp.uint8),
            jax.ShapeDtypeStruct((nb, N_STATES, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N_STATES, LANES), jnp.float32)],
        interpret=interpret,
    )(x, g, nbits, ridx, *bxr, *blr)

    bits = pl.pallas_call(
        _make_traceback_kernel(MIXED_UNROLL),
        grid=(nb, NB),
        in_specs=[
            pl.BlockSpec((1, MIXED_UNROLL, 8, LANES),
                         lambda b, t, _n=NB: (b, _n - 1 - t, 0, 0)),
            pl.BlockSpec((1, N_STATES, LANES), lambda b, t: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, MIXED_UNROLL, 8, LANES),
                               lambda b, t, _n=NB: (b, _n - 1 - t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, t_max, 8, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, LANES), jnp.int32)],
        interpret=interpret,
    )(dec, metrics)
    return bits[:, :, 0, :].astype(jnp.uint8)


def viterbi_decode_mixed_fused(data, gain, rate_idx, nbits_real,
                               radix: int = None,
                               interpret: bool = None):
    """Rate-SWITCHED fused-front-end batch decode: equalized,
    pilot-tracked DATA subcarriers of a mixed-rate batch -> decoded
    bits over the bucket-maximal trellis, with demap + deinterleave +
    depuncture executed as an in-kernel prologue that row-selects each
    lane's slot tables from the stacked all-rates bank — the LLRs live
    and die in VMEM on the path every fleet surface actually runs.

    data: (B, n_sym_bucket, 48, 2) equalized data-subcarrier pairs
    (rx._front_symbols under ONE rate-independent vmap — the fused
    graph's whole XLA front end, vs 8 per-rate branches unfused);
    gain: (B, 48) |H|^2 weights; rate_idx: (B,) traced indices into
    RATE_MBPS_ORDER; nbits_real: (B,) traced true data-bit counts.
    Returns (B, n_sym_bucket * MAX_DBPS) raw decoded bits: the whole
    bucket at 54 Mbit/s. The unfused mixed trellis stops at the
    longest legal frame (`params.mixed_trellis_steps`), so
    `rx.decode_data_mixed` slices this output to it before the shared
    descramble tail; bounding this kernel's own grid is ROADMAP S2's.

    float32 metrics only, radix 2 or 4 (the quantized paths scale by
    the whole frame's LLR peak the prologue never materializes;
    decode_data_mixed falls back to the unfused front for them).
    Bit-identity contract vs the unfused mixed decode matches the
    known-rate fused path's: expression-identical demap arithmetic and
    the identical erasure mask, renorm cadence MIXED_UNROLL instead of
    UNROLL (pinned lane-for-lane at the test seeds across all 8 rates;
    tests/test_viterbi_fused_mixed.py)."""
    if interpret is None:
        interpret = _interpret_default()
    radix = _check_radix(radix)
    ndbps, _norms = _mixed_rate_geometry()
    data = jnp.asarray(data, jnp.float32)
    gain = jnp.asarray(gain, jnp.float32)
    B, n_sym_b = data.shape[0], data.shape[1]
    t_max = n_sym_b * max(ndbps)
    Bp = -(-B // LANES) * LANES
    nb_tiles = Bp // LANES
    x = data.reshape(B, n_sym_b, 96)          # (48, I/Q) -> 2c + comp
    x = jnp.pad(x, ((0, Bp - B), (0, 0), (0, 0)))
    x = x.transpose(1, 2, 0).reshape(n_sym_b, 96, nb_tiles, LANES) \
         .transpose(2, 0, 1, 3)
    g = jnp.pad(gain, ((0, Bp - B), (0, 0)))
    g = g.transpose(1, 0).reshape(48, nb_tiles, LANES).transpose(1, 0, 2)

    def _rows(v):
        # pad lanes ride rate 0 / nbits 0: every step masks to an
        # erasure, the unfused path's zero-LLR pad-lane semantics
        vp = jnp.pad(jnp.broadcast_to(jnp.asarray(v, jnp.int32), (B,)),
                     (0, Bp - B)).reshape(nb_tiles, 1, LANES)
        return jnp.broadcast_to(vp, (nb_tiles, 8, LANES))

    bank_x, bank_l = mixed_front_tables()
    bits = _mixed_fused_decode_tiles(
        x, g, _rows(nbits_real), _rows(rate_idx), jnp.asarray(bank_x),
        jnp.asarray(bank_l), n_sym_b, t_max, radix, interpret)
    return bits.transpose(0, 2, 1).reshape(Bp, -1)[:B]
