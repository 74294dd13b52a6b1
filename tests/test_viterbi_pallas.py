"""Pallas Viterbi kernel vs the lax.scan reference implementation.

Runs the kernels in Pallas interpret mode on CPU (conftest pins the CPU
backend); on real TPU the same code path compiles via Mosaic.
"""

import numpy as np
import pytest

from ziria_tpu.ops import coding, viterbi, viterbi_pallas


def _noisy_llrs(rng, n_bits, snr=2.0):
    bits = rng.integers(0, 2, n_bits).astype(np.uint8)
    coded = np.asarray(coding.np_conv_encode_ref(bits), np.float32)
    llr = (2.0 * coded - 1.0) * snr + rng.normal(0, 1.0, coded.size)
    return bits, llr.astype(np.float32).reshape(-1, 2)


def test_matches_scan_reference_hard():
    rng = np.random.default_rng(0)
    B, n = 5, 96
    msgs, llrs = [], []
    for _ in range(B):
        bits = rng.integers(0, 2, n).astype(np.uint8)
        bits[-coding.K + 1:] = 0  # zero-tail termination
        coded = np.asarray(coding.np_conv_encode_ref(bits), np.float32)
        msgs.append(bits)
        llrs.append((2.0 * coded - 1.0).reshape(-1, 2))
    llrs = np.stack(llrs)
    got = np.asarray(viterbi_pallas.viterbi_decode_batch(llrs))
    assert got.shape == (B, n)
    for k in range(B):
        np.testing.assert_array_equal(got[k], msgs[k])


def test_matches_scan_reference_soft():
    rng = np.random.default_rng(1)
    B, n = 4, 120
    llrs = np.stack([_noisy_llrs(rng, n)[1] for _ in range(B)])
    got = np.asarray(viterbi_pallas.viterbi_decode_batch(llrs))
    for k in range(B):
        want = np.asarray(viterbi.viterbi_decode(llrs[k]))
        np.testing.assert_array_equal(got[k], want)


def test_lane_padding_and_nbits():
    rng = np.random.default_rng(2)
    B, n = 3, 64  # B far below one 128-lane tile
    llrs = np.stack([_noisy_llrs(rng, n)[1] for _ in range(B)])
    got = np.asarray(viterbi_pallas.viterbi_decode_batch(llrs, n_bits=50))
    assert got.shape == (B, 50)
    want = np.stack(
        [np.asarray(viterbi.viterbi_decode(llrs[k], n_bits=50))
         for k in range(B)])
    np.testing.assert_array_equal(got, want)


def test_flat_llr_layout():
    rng = np.random.default_rng(3)
    _, llr = _noisy_llrs(rng, 80)
    flat = llr.reshape(1, -1)
    a = np.asarray(viterbi_pallas.viterbi_decode_batch(flat))
    b = np.asarray(viterbi_pallas.viterbi_decode_batch(llr[None]))
    np.testing.assert_array_equal(a, b)


def test_multi_tile_batch():
    rng = np.random.default_rng(4)
    B, n = 130, 40  # > 128 forces two lane tiles
    msgs, llrs = [], []
    for _ in range(B):
        bits = rng.integers(0, 2, n).astype(np.uint8)
        bits[-coding.K + 1:] = 0
        coded = np.asarray(coding.np_conv_encode_ref(bits), np.float32)
        msgs.append(bits)
        llrs.append((2.0 * coded - 1.0).reshape(-1, 2))
    got = np.asarray(viterbi_pallas.viterbi_decode_batch(np.stack(llrs)))
    np.testing.assert_array_equal(got, np.stack(msgs))


# ---------------- the sweeps stop at a bound that is data (ISSUE 53)
#
# `n_blocks` (a count a 128-lane tile, traced) stops a tile's ACS and
# traceback after that many blocks of UNROLL steps. Where every lane of
# the tile is an erasure from there on, each lane's bits before its own
# last real row are the whole trellis's; past the bound they read zero.

U = viterbi_pallas.UNROLL
BOUND_T = 6 * U
#: two tiles (130 lanes): tile 0's longest lane ends inside block 3,
#: tile 1 (lanes 128, 129) inside block 5 and ON block 2's last step
BOUND_REAL = np.r_[np.tile([17, 3 * U - 2, U, 2 * U + 1], 32),
                   [4 * U + 9, 2 * U]]
BOUND_KERNELS = [("float32", 2), ("float32", 4), ("int16", 2)]


@pytest.fixture(scope="module")
def bound_llrs():
    rng = np.random.default_rng(53)
    llr = rng.normal(0, 2.0, (BOUND_REAL.size, BOUND_T, 2))
    real = np.arange(BOUND_T)[None, :] < BOUND_REAL[:, None]
    return np.where(real[..., None], llr, 0).astype(np.float32)


@pytest.fixture(scope="module", params=BOUND_KERNELS,
                ids=[f"{m}-radix{r}" for m, r in BOUND_KERNELS])
def bound_kernel(request, bound_llrs):
    """(the bounded decode jitted once: the count is an argument; the
    whole trellis's bits)."""
    import jax
    md, rdx = request.param
    bounded = jax.jit(lambda x, n: viterbi_pallas.viterbi_decode_batch(
        x, metric_dtype=md, radix=rdx, n_blocks=n))
    whole = np.asarray(viterbi_pallas.viterbi_decode_batch(
        bound_llrs, metric_dtype=md, radix=rdx))
    return bounded, whole


@pytest.mark.parametrize("n_blocks, runs", [
    ((3, 5), (3, 5)),        # each tile to its own longest lane
    ((3, 6), (3, 6)), ((6, 6), (6, 6)),   # to the grid's last block
    ((4, 40), (4, 6)),       # a count past the trellis is the trellis
    ((5, 5), (5, 5))],
    ids=lambda v: "-".join(map(str, v)))
def test_bounded_sweeps_give_the_whole_trellis_on_real_bits(
        bound_llrs, bound_kernel, n_blocks, runs):
    bounded, whole = bound_kernel
    got = np.asarray(bounded(bound_llrs, np.asarray(n_blocks, np.int32)))
    assert got.shape == whole.shape == (BOUND_REAL.size, BOUND_T)
    tile = np.arange(BOUND_REAL.size) // viterbi_pallas.LANES
    for lane, n in enumerate(BOUND_REAL):
        np.testing.assert_array_equal(got[lane, :n], whole[lane, :n])
        assert not got[lane, runs[tile[lane]] * U:].any()


def test_a_bound_under_one_block_runs_one(bound_llrs, bound_kernel):
    """The count is held to [1, blocks]: 0 runs the first block (and
    hands over metrics the kernel wrote), so a tile whose lanes all end
    inside it still decodes."""
    bounded, whole = bound_kernel
    short = np.where(
        (np.arange(BOUND_T) < 17)[None, :, None], bound_llrs, 0)
    got = np.asarray(bounded(short, np.zeros(2, np.int32)))
    want = np.asarray(bounded(short, np.full(2, 6, np.int32)))
    np.testing.assert_array_equal(got[:, :17], want[:, :17])
    assert not got[:, U:].any()


def test_without_a_bound_the_decode_traces_the_program_it_always_did():
    """`n_blocks` absent: a static grid and no prefetch operand (what
    `decode_data_mixed`, `decode_data_bucketed` and the tools trace),
    and the lowered text is, to the byte, what the parent of PR 53
    lowered (its digest, taken on that commit in this container; the
    interpreter's lowering: Mosaic's serialized body carries source
    lines)."""
    import hashlib
    import jax
    llr = jax.ShapeDtypeStruct((3, 200, 2), np.float32)
    pinned = {("float32", 2): "b043d2e962c047ec",
              ("float32", 4): "3c7234d13d2c4674",
              ("int16", 2): "89569d97a681c168"}
    for (md, rdx), want in pinned.items():
        traced = jax.jit(lambda x: viterbi_pallas.viterbi_decode_batch(
            x, interpret=True, metric_dtype=md, radix=rdx)).trace(llr)
        calls = [e for e in _eqns(traced.jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 2
        for e in calls:
            gm = e.params["grid_mapping"]
            assert gm.num_index_operands == 0
            assert all(isinstance(g, int) for g in gm.grid)
        text = traced.lower().as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, \
            (md, rdx)


def _eqns(jaxpr):
    import jax
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from _eqns(sub)
