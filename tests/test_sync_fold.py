"""The detector's sliding correlators (`ops/sync.py`), in the shapes
the chip runs well. `sync.ccorrelate_valid` folds a long row into
overlapped blocks before its convolution (ISSUE 35: `[8, 1, 131 072]`
is a shape the chip ran at 12 M outputs/s; the same taps over hundreds
of short rows a hundred times faster), and since ISSUE 47 that
convolution is ONE, of two input channels and two output features,
where four one-channel products ran, and the 48-sample window sums are
doubling shift-adds where three more convolved with ones.

The contract held here, on the CPU and BIT FOR BIT, leads the file: a
value depends on its own window alone, never on the block, the offset
or the array it landed in; the folded correlators (`ccorrelate_valid`,
`lts_pair_metric`, `_sliding_sum`'s float path, and `locate_frames`
on top of them) read exactly what the same forms read unfolded and
unbatched, at lengths no block divides, under `vmap`, with and
without the `limit` cap; and the fold follows from the row's length
and from nothing else. Equality with `jnp.convolve` is NOT the
contract (on the chip HIGHEST is a six-pass emulation and never
equalled it): against the parent's forms, written out in this file,
the new ones are held to `BOUND` of the row's largest value (the same
64 or 48 f32 terms, added in another order: 1.4e-6 and 4e-6 read),
and `locate_frames` to the parent's `found`, `starts` and `overflow`
exactly.
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
#: new form against the parent's, as a share of the row's largest value
BOUND = 1e-5


def _assert_same_bits(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(head_cases._bits(got),
                                  head_cases._bits(want))


def _assert_close(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= BOUND * np.abs(want).max()


def _unfolded(fn):
    """``fn`` as a jit of its own whose traces cut every row into one
    block: the same form, unfolded (no folded trace is reused)."""
    def traced(*args):
        was = sync.fold_blocks
        sync.fold_blocks = lambda n_out: 1
        try:
            return fn(*args)
        finally:
            sync.fold_blocks = was
    return jax.jit(traced)


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


def _plain_ccorrelate(x, ref):
    """Four one-channel products (the parent's `lts_pair_metric`)."""
    taps = cplx.conj(ref)[::-1]

    def conv1(u, v):
        return jnp.convolve(u, v, mode="valid", precision="highest")

    return jnp.stack(
        [conv1(x[:, 0], taps[:, 0]) - conv1(x[:, 1], taps[:, 1]),
         conv1(x[:, 0], taps[:, 1]) + conv1(x[:, 1], taps[:, 0])], axis=1)


def _plain_lts_pair_metric(x, limit=None):
    lim = x.shape[0] if limit is None else limit
    c = cplx.cabs2(_plain_ccorrelate(x, jnp.asarray(lts_time_symbol())))
    pair = c[:-64] + c[64:]
    return jnp.where(jnp.arange(pair.shape[0]) < lim - 127, pair, -1.0)


def _rows(rows: int, n: int, cols: int = 2, seed: int = 35):
    rng = np.random.default_rng(seed + rows + n + cols)
    return jnp.asarray(rng.standard_normal((rows, n, cols)), jnp.float32)


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
    pair, sts = jax.jit(sync.lts_pair_metric), jax.jit(sync.sts_autocorr)
    pa, pb = pair(a), pair(b)
    _assert_same_bits(pa[off_a: off_a + 1000 - 127],
                      pb[off_b: off_b + 1000 - 127])
    (ma, ca), (mb, cb) = sts(a), sts(b)
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
def other_locates():
    """`_locate` compiled twice more a chunk length: "unfolded", the
    shipped forms with every row one block, and "parent", the
    parent's forms (four LTS products, window sums by a convolution
    with ones, both through plain `jnp.convolve`). The module is put
    back before any test body runs (jits of their own: `_FOLDED`'s
    traces stay folded and shipped)."""
    shapes = {geo: (jax.ShapeDtypeStruct((chunk_len, 2), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))
              for geo, (chunk_len, _win) in head_cases.GEOS.items()}

    def compiled():
        return {geo: jax.jit(lambda *a: _locate(*a)).lower(*shape)
                .compile() for geo, shape in shapes.items()}

    was = sync.fold_blocks, sync.lts_pair_metric, sync._sliding_sum

    def parent_sliding_sum(x, w: int):
        floats = jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)
        return (_plain_sliding_sum if floats else was[2])(x, w)

    fns = {}
    try:
        sync.fold_blocks = lambda n_out: 1
        fns["unfolded"] = compiled()
        sync.fold_blocks = was[0]
        sync.lts_pair_metric = _plain_lts_pair_metric
        sync._sliding_sum = parent_sliding_sum
        fns["parent"] = compiled()
    finally:
        sync.fold_blocks, sync.lts_pair_metric, sync._sliding_sum = was
    return fns


@pytest.mark.parametrize("geo", sorted(head_cases.GEOS))
@pytest.mark.parametrize("case", head_cases.CASES)
@pytest.mark.parametrize("other", ["unfolded", "parent"])
def test_locate_frames_equals_its_unfolded_form_and_the_parents_forms(
        other, case, geo, other_locates):
    """Folded == unfolded, and reassociated sums may move a metric
    value a rounding but no frame of any case is found, placed or
    counted differently from the parent's forms for it."""
    chunk_len, _win = head_cases.GEOS[geo]
    stream, own_lo, n_owned, _all = head_cases._case(case)
    chunk, valid, _lo, hi = head_cases._scan_args(stream, own_lo,
                                                  chunk_len)
    assert sync.fold_blocks(chunk_len - 63) > 1
    got = _FOLDED(chunk, valid, hi)
    want = other_locates[other][geo](chunk, valid, hi)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    found, starts, overflow = (np.asarray(o) for o in got)
    assert found.sum() == n_owned and not overflow
    assert (np.diff(starts[found]) > 0).all()


def _conv_batches(n: int):
    """Batch x length of every convolution `lts_pair_metric` lowers
    to at row length ``n``: ONE, of two features."""
    text = jax.jit(lambda x: sync.lts_pair_metric(x)).lower(
        jax.ShapeDtypeStruct((n, 2), jnp.float32)).as_text()
    assert "tensor<2x2x64xf32>" in text             # [feature, channel, tap]
    return [(int(m.group(1)), int(m.group(2))) for m in re.finditer(
        r"stablehlo\.convolution.*-> tensor<(\d+)x2x(\d+)xf32>", text)]


def test_the_folded_program_folds_and_the_unfolded_one_does_not(
        monkeypatch):
    """Read off the lowered text: 8192 samples are 16 blocks of 512
    outputs (the last one short of samples, zero-filled), and with
    the fold off, one row; the window sums lower to no contraction."""
    assert _conv_batches(8192) == [(16, sync.FOLD_BLOCK)]
    assert _conv_batches(1024) == [(1, 1024 - 63)]
    sums = jax.jit(lambda x: sync.sts_autocorr(x)).lower(
        jax.ShapeDtypeStruct((8192, 2), jnp.float32)).as_text()
    assert not re.search(r"stablehlo\.(convolution|dot)", sums)
    monkeypatch.setattr(sync, "fold_blocks", lambda n_out: 1)
    assert _conv_batches(8192) == [(1, 8192 - 63)]


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


# ------------------------------------ by correlator: folded == unfolded
# bit for bit, and near the parent's form


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("taps", [48, 64, 33])
def test_ccorrelate_valid_folded_is_unfolded_and_near_four_products(
        n, taps):
    rng = np.random.default_rng(n + taps)
    x = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    ref = jnp.asarray(rng.standard_normal((taps, 2)), jnp.float32)
    got, want = jax.jit(lambda *a: (sync.ccorrelate_valid(*a),
                                    _plain_ccorrelate(*a)))(x, ref)
    assert got.shape == (n - taps + 1, 2)
    _assert_same_bits(got, _unfolded(sync.ccorrelate_valid)(x, ref))
    _assert_close(got, want)


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("rows", [1, 8, 32])
def test_sts_window_sums_batched_are_unbatched_and_near_the_convolution(
        rows, n):
    """`_sliding_sum` as `sts_autocorr` calls it (two trailing columns
    of lag products, one of energy, window 48) under a lane `vmap`:
    each lane reads what it reads alone, and what the parent's
    convolution with ones read to `BOUND`."""
    if rows == 32 and n > 8192:
        n = 8192 + n % 1000         # 32 long rows cost CPU seconds
    x = _rows(rows, n)

    def one(fn):
        def sums(xi):
            prod = cplx.cmul_conj(xi[16:], xi[:-16])
            return fn(prod, 48), fn(cplx.cabs2(xi[16:]), 48)
        return sums

    got, want = jax.jit(lambda a: (
        jax.vmap(one(sync._sliding_sum))(a),
        jax.vmap(one(_plain_sliding_sum))(a)))(x)
    alone = jax.jit(one(sync._sliding_sum))
    for i in {0, rows - 1}:
        for g, a in zip(got, alone(x[i])):
            _assert_same_bits(g[i], a)
    for g, w in zip(got, want):
        assert g.shape[1] == n - 16 - 47
        _assert_close(g, w)


@pytest.mark.parametrize("n", [320, 8192, 100003])
@pytest.mark.parametrize("cols,w", [(1, 48), (2, 33), (3, 48), (3, 33)])
def test_sliding_sum_by_trailing_columns_and_window(cols, w, n):
    x = _rows(1, n, cols)[0]
    got, want = jax.jit(lambda a: (sync._sliding_sum(a, w),
                                   _plain_sliding_sum(a, w)))(x)
    _assert_close(got, want)
    bare = jax.jit(lambda a: sync._sliding_sum(a, w))
    for c in range(cols):           # a column reads what its bare row reads
        _assert_same_bits(got[:, c], bare(x[:, c]))


@pytest.mark.parametrize("n", [320, 1024, 100003])
def test_integer_sliding_sum_stays_the_exact_cumsum(n):
    """`locate_frames`' `runs`: integers in, a cumulative sum's
    difference out, exact."""
    above = np.random.default_rng(n).random(n) > 0.3
    got = sync._sliding_sum(jnp.asarray(above, jnp.int32), 33)
    want = np.convolve(above.astype(np.int64), np.ones(33, np.int64),
                       mode="valid")
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)
    jaxpr = str(jax.make_jaxpr(lambda a: sync._sliding_sum(a, 33))(
        jnp.asarray(above, jnp.int32)))
    assert "cumsum" in jaxpr and "f32" not in jaxpr


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("rows", [1, 8, 32])
def test_lts_pair_metric_folded_is_unfolded_and_near_the_plain_convolution(
        rows, n):
    if rows == 32 and n > 8192:
        n = 8192 + n % 1000
    x = _rows(rows, n)
    got, want = jax.jit(lambda a: (
        jax.vmap(sync.lts_pair_metric)(a),
        jax.vmap(_plain_lts_pair_metric)(a)))(x)
    assert got.shape == (rows, n - 127)
    # a lane alone, every row one block: the same bits
    alone = _unfolded(sync.lts_pair_metric)
    for i in {0, rows - 1}:
        _assert_same_bits(got[i], alone(x[i]))
    _assert_close(got, want)


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
        free = jax.jit(jax.vmap(sync.lts_pair_metric))(x)
    else:
        got = jnp.stack([sync.lts_pair_metric(x[i], limit=int(c))
                         for i, c in enumerate(caps)])
        want = jnp.stack([_plain_lts_pair_metric(x[i], limit=int(c))
                          for i, c in enumerate(caps)])
        free = jnp.stack([sync.lts_pair_metric(xi) for xi in x])
    _assert_close(got, want)
    # the sentinels sit exactly past each cap, and the cap moves no
    # value before it
    for row, full, c in zip(np.asarray(got), np.asarray(free), caps):
        cut = max(int(c) - 127, 0)
        assert (row[cut:] == -1.0).all()
        assert (row[:cut] >= 0).all()
        _assert_same_bits(row[:cut], full[:cut])
