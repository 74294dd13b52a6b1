"""`sync.correlate_valid` folds a long row into overlapped blocks
before its one-channel convolution (ISSUE 35: `[8, 1, 131 072]` is a
shape the chip ran at 12 M outputs/s; the same taps over hundreds of
short rows a hundred times faster).

The contract held here, on the CPU and BIT FOR BIT: the folded
correlators (`_sliding_sum`'s float path, `lts_pair_metric`, and
`locate_frames` on top of them) read exactly what the plain
`vmap(jnp.convolve(..., precision="highest"))` they replace reads —
the parent's forms, written out in this file — at lengths no block
divides, under `vmap`, with and without the `limit` cap; a value
depends on its own window alone, never on the block, the offset or
the array it landed in; and the fold follows from the row's length
and from nothing else.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_rx_acquire_head as head_cases
from ziria_tpu.ops import cplx, sync
from ziria_tpu.ops.ofdm import lts_time_symbol

LENGTHS = (320, 1024, 8192, 65536, 131072, 131071, 100003)


def _assert_same_bits(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(head_cases._bits(got),
                                  head_cases._bits(want))


# ---------------------------------------- the parent's forms, unfolded


def _plain_sliding_sum(x, w: int):
    k = jnp.ones((w,), x.dtype)

    def conv1(col):
        return jnp.convolve(col, k, mode="valid", precision="highest")

    if x.ndim == 1:
        return conv1(x)
    flat = x.reshape(x.shape[0], -1)
    out = jax.vmap(conv1, in_axes=1, out_axes=1)(flat)
    return out.reshape((out.shape[0],) + x.shape[1:])


def _plain_lts_pair_metric(x, limit=None):
    n = x.shape[0]
    lim = n if limit is None else limit
    ref = cplx.conj(jnp.asarray(lts_time_symbol()))[::-1]

    def conv1(u, v):
        return jnp.convolve(u, v, precision="highest")

    re = conv1(x[:, 0], ref[:, 0]) - conv1(x[:, 1], ref[:, 1])
    im = conv1(x[:, 0], ref[:, 1]) + conv1(x[:, 1], ref[:, 0])
    c = re[63:n] ** 2 + im[63:n] ** 2
    pair = c[:-64] + c[64:]
    return jnp.where(jnp.arange(pair.shape[0]) < lim - 127, pair, -1.0)


def _rows(rows: int, n: int, cols: int = 2, seed: int = 35):
    rng = np.random.default_rng(seed + rows + n + cols)
    return jnp.asarray(rng.standard_normal((rows, n, cols)), jnp.float32)


# ------------------------------------------------------- the fold rule


@pytest.mark.parametrize("rows,n,want", [
    (1, 131072, 256), (8, 131072, 2048), (32, 131072, 8192),
    (64, 1024, 64), (256, 1024, 256)])
def test_fold_is_wide_where_rows_are_long_and_one_where_short(
        rows, n, want):
    """The batch the LTS convolution runs over: every lane count
    reaches 256 rows at the served chunk length (the lone
    `StreamReceiver` too), and the acquisition's window heads, already
    many and short, pass through unfolded."""
    assert sync.fold_rows(rows, n) == want
    assert want >= 256 or n == 1024
    blocks = sync.fold_blocks(n - 63)
    assert blocks == want // rows
    # the 48-tap window sums of the same row fold alike
    assert sync.fold_blocks(n - 16 - 47) == blocks
    # a block's halo is the head of the NEXT block: taps - 1 fit in it
    assert sync.FOLD_BLOCK >= 63


@pytest.mark.parametrize("n_out,blocks", [
    (1, 1), (512, 1), (1024, 1), (1025, 3), (1536, 3), (1537, 4),
    (100003 - 63, 196), (131072 - 63, 256), (131072, 256),
    (131073, 257)])
def test_fold_blocks_follow_from_the_length_alone(n_out, blocks):
    assert sync.fold_blocks(n_out) == blocks
    assert blocks == 1 or (blocks - 1) * sync.FOLD_BLOCK < n_out \
        <= blocks * sync.FOLD_BLOCK


# ------------------------------------------- bit for bit, by correlator


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("taps", [48, 64, 33])
def test_correlate_valid_is_jnp_convolve_valid(n, taps):
    rng = np.random.default_rng(n + taps)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    k = jnp.asarray(rng.standard_normal(taps), jnp.float32)
    want = jnp.convolve(x, k, mode="valid", precision="highest")
    _assert_same_bits(jax.jit(sync.correlate_valid)(x, k), want)


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("rows", [1, 8, 32])
def test_sts_window_sums_equal_the_plain_convolution(rows, n):
    """`_sliding_sum` as `sts_autocorr` calls it (two trailing columns
    of lag products, one of energy, window 48) under a lane `vmap`."""
    if rows == 32 and n > 8192:
        n = 8192 + n % 1000         # 32 long rows cost CPU seconds
    x = _rows(rows, n)

    def sums(fn):
        def one(xi):
            prod = cplx.cmul_conj(xi[16:], xi[:-16])
            return fn(prod, 48), fn(cplx.cabs2(xi[16:]), 48)
        return jax.jit(jax.vmap(one))(x)

    for got, want in zip(sums(sync._sliding_sum),
                         sums(_plain_sliding_sum)):
        assert got.shape[1] == n - 16 - 47
        _assert_same_bits(got, want)


@pytest.mark.parametrize("n", [320, 8192, 100003])
@pytest.mark.parametrize("cols,w", [(1, 48), (2, 33), (3, 48), (3, 33)])
def test_sliding_sum_by_trailing_columns_and_window(cols, w, n):
    x = _rows(1, n, cols)[0]
    _assert_same_bits(jax.jit(lambda a: sync._sliding_sum(a, w))(x),
                      _plain_sliding_sum(x, w))
    if cols == 1:                   # and a bare 1-D row
        _assert_same_bits(sync._sliding_sum(x[:, 0], w),
                          _plain_sliding_sum(x[:, 0], w))


@pytest.mark.parametrize("n", [320, 1024, 100003])
def test_integer_sliding_sum_stays_the_exact_cumsum(n, monkeypatch):
    """`locate_frames`' `runs` never meets a convolution."""
    def refuse(*_a, **_k):
        raise AssertionError("the integer path convolved")

    monkeypatch.setattr(sync, "correlate_valid", refuse)
    above = np.random.default_rng(n).random(n) > 0.3
    got = sync._sliding_sum(jnp.asarray(above, jnp.int32), 33)
    want = np.convolve(above.astype(np.int64), np.ones(33, np.int64),
                       mode="valid")
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("rows", [1, 8, 32])
def test_lts_pair_metric_equals_the_plain_convolution(rows, n):
    if rows == 32 and n > 8192:
        n = 8192 + n % 1000
    x = _rows(rows, n)
    got = jax.jit(jax.vmap(sync.lts_pair_metric))(x)
    want = jax.jit(jax.vmap(_plain_lts_pair_metric))(x)
    assert got.shape == (rows, n - 127)
    _assert_same_bits(got, want)


@pytest.mark.parametrize("n", [1024, 131071])
@pytest.mark.parametrize("limit", ["static", "traced"])
def test_lts_pair_metric_under_a_limit(limit, n):
    x = _rows(8, n)
    caps = np.asarray([n, n - 1, n // 2, 700, 512, 200, 128, 0],
                      np.int32)
    if limit == "traced":
        got = jax.jit(jax.vmap(sync.lts_pair_metric))(
            x, jnp.asarray(caps))
        want = jax.jit(jax.vmap(_plain_lts_pair_metric))(
            x, jnp.asarray(caps))
    else:
        got = jnp.stack([sync.lts_pair_metric(x[i], limit=int(c))
                         for i, c in enumerate(caps)])
        want = jnp.stack([_plain_lts_pair_metric(x[i], limit=int(c))
                          for i, c in enumerate(caps)])
    _assert_same_bits(got, want)
    # the sentinels sit exactly past each cap
    for row, c in zip(np.asarray(got), caps):
        assert (row[max(int(c) - 127, 0):] == -1.0).all()
        assert (row[: max(int(c) - 127, 0)] >= 0).all()


# ------------------------------------------------------ position-locality


@pytest.mark.parametrize("off_a,len_a,off_b,len_b", [
    (0, 2048, 700, 131072),         # unfolded head against a chunk
    (511, 8192, 66000, 100003),     # across different block seams
    (1, 1700, 130000, 131071)])     # a three-block row, a chunk's end
def test_a_value_depends_on_its_own_window_alone(off_a, len_a, off_b,
                                                 len_b):
    """The same 1000 samples laid at two offsets of two arrays of
    different length (different blocks, different seams, folded and
    not) read equal values: what keeps the chunk scan bit-identical to
    the per-capture path."""
    rng = np.random.default_rng(off_a + off_b)
    piece = rng.standard_normal((1000, 2)).astype(np.float32)

    def laid(off, n):
        x = rng.standard_normal((n, 2)).astype(np.float32)
        x[off: off + 1000] = piece
        return jnp.asarray(x)

    a, b = laid(off_a, len_a), laid(off_b, len_b)
    assert sync.fold_blocks(len_a - 63) != sync.fold_blocks(len_b - 63)
    pa, pb = sync.lts_pair_metric(a), sync.lts_pair_metric(b)
    _assert_same_bits(pa[off_a: off_a + 1000 - 127],
                      pb[off_b: off_b + 1000 - 127])
    (ma, ca), (mb, cb) = sync.sts_autocorr(a), sync.sts_autocorr(b)
    span = 1000 - 16 - 47
    _assert_same_bits(ma[off_a: off_a + span], mb[off_b: off_b + span])
    _assert_same_bits(ca[off_a: off_a + span], cb[off_b: off_b + span])


# ------------------------------------------------- the scan on top of them


def _locate(chunk, valid, own_hi):
    """`locate_frames` as `rx.stream_chunk_graph` calls it."""
    return sync.locate_frames(chunk, head_cases.K, limit=valid,
                              overflow_limit=own_hi + 224)


_FOLDED = jax.jit(_locate)


@pytest.fixture(scope="module")
def unfolded_locates():
    """`_locate` compiled with every row one block (the parent's
    program), once a chunk length; the fold is put back before any
    test body runs (a jit of its own: `_FOLDED`'s traces stay folded)."""
    fns = {}
    was = sync.fold_blocks
    sync.fold_blocks = lambda n_out: 1
    try:
        for geo, (chunk_len, _win) in head_cases.GEOS.items():
            fns[geo] = jax.jit(lambda *a: _locate(*a)).lower(
                jax.ShapeDtypeStruct((chunk_len, 2), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
    finally:
        sync.fold_blocks = was
    return fns


@pytest.mark.parametrize("geo", sorted(head_cases.GEOS))
@pytest.mark.parametrize("case", head_cases.CASES)
def test_locate_frames_equals_its_unfolded_form(case, geo,
                                                unfolded_locates):
    chunk_len, _win = head_cases.GEOS[geo]
    stream, own_lo, n_owned, _all = head_cases._case(case)
    chunk, valid, _lo, hi = head_cases._scan_args(stream, own_lo,
                                                  chunk_len)
    assert sync.fold_blocks(chunk_len - 63) > 1
    got = _FOLDED(chunk, valid, hi)
    want = unfolded_locates[geo](chunk, valid, hi)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    found, starts, overflow = (np.asarray(o) for o in got)
    assert found.sum() == n_owned and not overflow
    assert (np.diff(starts[found]) > 0).all()


def _conv_batches(n: int):
    """Batch x length of every convolution `lts_pair_metric` lowers
    to at row length ``n`` (the one private function it calls four
    times counts once)."""
    text = jax.jit(lambda x: sync.lts_pair_metric(x)).lower(
        jax.ShapeDtypeStruct((n, 2), jnp.float32)).as_text()
    return sorted({(int(m.group(1)), int(m.group(2))) for m in re.finditer(
        r"stablehlo\.convolution.*-> tensor<(\d+)x1x(\d+)xf32>", text)})


def test_the_folded_program_folds_and_the_unfolded_one_does_not(
        monkeypatch):
    """Read off the lowered text: 8192 samples are 16 blocks of 512
    outputs (the last one short of samples, zero-filled), and with
    the fold off, one row."""
    assert _conv_batches(8192) == [(16, sync.FOLD_BLOCK)]
    assert _conv_batches(1024) == [(1, 1024 - 63)]
    monkeypatch.setattr(sync, "fold_blocks", lambda n_out: 1)
    assert _conv_batches(8192) == [(1, 8192 - 63)]
