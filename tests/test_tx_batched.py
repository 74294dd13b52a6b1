"""One-dispatch mixed-rate TX (tx.encode_many) and the device-resident
loopback link (phy/link.py): an N-frame batch of mixed rates AND
lengths encodes in ONE vmapped lax.switch dispatch, bit-identical lane
for lane to per-frame `encode_frame`, and the full TX -> channel -> RX
loopback runs in <= 5 device dispatches vs >= N for the per-frame
oracle loop — with identical RxResults either way.

Budget discipline (the tier-1 870 s cutoff is real): ONE module
fixture pays the expensive geometry compiles — 8 lanes, 128-bit bit
bucket, 8-symbol bucket (the decode geometry test_rx_mixed_dispatch /
test_rx_batched_acquire already compile, shared through the
process-wide jit caches) — and every test re-dispatches those
compiled graphs. Dispatch counts come from the instrumented
utils/dispatch.count_dispatches counter; compile counts from
utils/dispatch.cache_growth (lru deltas, never cache_clear).
"""

import numpy as np
import pytest

from ziria_tpu.phy import channel, link
from ziria_tpu.phy.wifi import rx, tx
from ziria_tpu.phy.wifi.params import (RATE_INDEX, RATE_MBPS_ORDER,
                                       RATES)
from ziria_tpu.utils import dispatch
from ziria_tpu.utils.bits import bytes_to_bits, np_bytes_to_bits

# all 8 rates with MIXED lengths in one batch; the 16-byte 6 Mbps lane
# pins the common symbol bucket at 8 (the suite-shared decode
# geometry), lengths stay inside the 128-bit bit bucket
LENS = (16, 10, 16, 5, 16, 12, 9, 16)
MBPS = tuple(sorted(RATES))
CFO = tuple((-1) ** k * 1e-4 * (k + 1) for k in range(8))
DELAY = tuple(20 + 17 * k for k in range(8))
SEED = 20260803


@pytest.fixture(scope="module")
def corpus():
    """PSDUs + one batched and one per-frame loopback pass (noise-free
    channel with per-lane CFO + delay), each under a dispatch
    counter. The batched pass pins ``fused=False`` throughout this
    file: it is the STAGED-vs-perframe contract; the fused one-
    dispatch graph is judged against the staged path in
    tests/test_link_fused.py."""
    rng = np.random.default_rng(SEED)
    psdus = [rng.integers(0, 256, n).astype(np.uint8) for n in LENS]
    with dispatch.count_dispatches() as d_bat:
        got_b = link.loopback_many(psdus, MBPS, snr_db=np.inf, cfo=CFO,
                                   delay=DELAY, seed=3, batched_tx=True,
                                   fused=False)
    with dispatch.count_dispatches() as d_pf:
        got_f = link.loopback_many(psdus, MBPS, snr_db=np.inf, cfo=CFO,
                                   delay=DELAY, seed=3,
                                   batched_tx=False)
    return psdus, got_b, got_f, d_bat, d_pf


#: the float-seam tolerance. Batched and per-frame TX/channel are
#: DIFFERENT XLA graphs over the same arithmetic (a vmapped
#: lax.switch vs one rate's straight line), and a compiler owes two
#: graphs no common rounding: fusion and FMA contraction move the
#: last bit or two of an O(1) float32 sample (observed <= 5e-7 under
#: jax 0.9; expect the same between chip and CPU). So I/Q samples are
#: held to this absolute bound, and everything discrete — valid
#: counts, shapes, decoded bits, rates, FCS flags — to equality.
SAMPLE_ATOL = 2e-6


def _assert_same_samples(got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0.0, atol=SAMPLE_ATOL)


def _same_result(a, b) -> bool:
    return (a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)


def test_encode_many_bit_identical_all_rates_mixed_lengths(corpus):
    # the acceptance contract: lane for lane the per-frame
    # encode_frame's samples (to SAMPLE_ATOL — the bits they carry are
    # pinned exactly by the loopback tests below) across ALL 8 rates
    # with MIXED lengths in the same batch, valid counts exact
    psdus, _gb, _gf, _db, _dp = corpus
    txb = tx.encode_many(psdus, MBPS)
    arr = np.asarray(txb.samples)
    for i, (p, m) in enumerate(zip(psdus, MBPS)):
        want = np.asarray(tx.encode_frame(p, m))
        assert txb.n_valid[i] == want.shape[0]
        _assert_same_samples(arr[i, :txb.n_valid[i]], want)
        # pad region is garbage symbols, never silently part of a frame
        assert txb.n_sym_bucket * 80 + 400 == arr.shape[1]


def test_encode_frame_jit_path_equals_eager_graph():
    # encode_frame's cached-jit dispatch vs the untraced oracle graph
    # (encode_frame_bits, itself pinned to the numpy oracle by
    # test_wifi_tx) — the single-frame half of the bit-identity story
    rng = np.random.default_rng(5)
    for m, nb in ((6, 16), (54, 9)):
        psdu = rng.integers(0, 256, nb).astype(np.uint8)
        want = np.asarray(tx.encode_frame_bits(
            bytes_to_bits(np.asarray(psdu), xp=np), RATES[m]))
        np.testing.assert_array_equal(
            np.asarray(tx.encode_frame(psdu, m)), want)


def test_loopback_batched_equals_perframe_oracle(corpus):
    psdus, got_b, got_f, _db, _dp = corpus
    assert len(got_b) == len(psdus)
    for a, b, p, m in zip(got_b, got_f, psdus, MBPS):
        assert a.ok and a.rate_mbps == m
        np.testing.assert_array_equal(a.psdu_bits, np_bytes_to_bits(p))
        assert _same_result(a, b)


def test_loopback_dispatch_counts(corpus):
    # the tentpole number: encode + channel + acquire + gather + mixed
    # decode = 5 dispatches for the whole mixed-rate batch, vs >= N
    # (here >= 5N: encode, impair, sync, SIGNAL, decode per frame) for
    # the per-frame path
    _psdus, _gb, _gf, d_bat, d_pf = corpus
    n = len(LENS)
    assert d_bat.total <= 5, dict(d_bat.counts)
    for site in ("tx.encode_many", "channel.impair_many",
                 "rx.acquire_many", "rx.gather", "rx.decode_mixed"):
        assert d_bat.counts[site] == 1, dict(d_bat.counts)
    assert d_pf.total >= n, dict(d_pf.counts)
    assert d_pf.counts["tx.encode_frame"] == n
    assert d_pf.counts["channel.impair"] == n


def test_loopback_dispatches_constant_in_batch_size(corpus):
    # O(1) means O(1): 7 lanes pad back to the fixture's 8-lane
    # geometry — same five dispatches, zero fresh compiles, results
    # still exact (keep lane 0: its 6 Mbps 16-byte frame pins the
    # shared 8-symbol decode bucket)
    psdus, got_b, _gf, _db, _dp = corpus
    with dispatch.cache_growth(tx._jit_encode_many,
                               channel._jit_impair_many,
                               rx._jit_decode_data_mixed) as g, \
            dispatch.count_dispatches() as d:
        got = link.loopback_many(psdus[:7], MBPS[:7], snr_db=np.inf,
                                 cfo=CFO[:7], delay=DELAY[:7], seed=3,
                                 batched_tx=True, fused=False)
    assert d.total <= 5
    assert g.total == 0
    for a, b in zip(got, got_b[:7]):
        assert _same_result(a, b)


def test_noisy_and_failed_lanes_match_perframe(corpus):
    # real AWGN at per-lane SNRs, one lane swamped (-25 dB): the
    # batched link classifies and decodes every lane exactly as the
    # per-frame loop — including the failure — at the fixture's
    # compiled geometry
    psdus, _gb, _gf, _db, _dp = corpus
    snrs = [25.0, 30.0, -25.0, 28.0, 25.0, 30.0, 27.0, 26.0]
    got_b = link.loopback_many(psdus, MBPS, snr_db=snrs, cfo=CFO,
                               delay=DELAY, seed=11, batched_tx=True,
                               fused=False)
    got_f = link.loopback_many(psdus, MBPS, snr_db=snrs, cfo=CFO,
                               delay=DELAY, seed=11, batched_tx=False)
    for a, b in zip(got_b, got_f):
        assert _same_result(a, b)
    assert not got_b[2].ok          # the swamped lane really failed
    assert got_b[0].ok and got_b[7].ok


def test_channel_batched_equals_oracle_samplewise(corpus):
    """The pre-Viterbi channel gate: at FINITE SNR with mixed symbol
    buckets — short lanes carry garbage bucket-pad symbols past
    n_valid, exactly the region impair_graph must mask — every capture
    sample of the batched channel equals the per-frame oracle to
    SAMPLE_ATOL (same keys, same noise draws). The decode-level identity tests cannot see a channel
    divergence the Viterbi corrects (wrong delivered SNR, perturbed
    noise scaling); this one can."""
    psdus, _gb, _gf, _db, _dp = corpus
    txb = tx.encode_many(psdus, MBPS)
    assert (txb.n_valid < txb.samples.shape[1]).any()   # pads exist
    l_cap = rx._stream_bucket(int(txb.samples.shape[1]) + max(DELAY))
    snrs = np.asarray([25.0 + k for k in range(8)], np.float32)
    caps = np.asarray(channel.impair_many(
        txb.samples, txb.n_valid, snrs, np.asarray(CFO, np.float32),
        np.asarray(DELAY, np.int32), seed=13, out_len=l_cap))
    for i, (p, m) in enumerate(zip(psdus, MBPS)):
        s = np.asarray(tx.encode_frame(p, m))
        want = np.asarray(channel.impair_one(
            s, snrs[i], CFO[i], DELAY[i], 13, i, l_cap))
        _assert_same_samples(caps[i], want)


def test_compile_count_o_log_buckets_not_o_lengths(corpus):
    # the cache-growth SHAPE contract: many (rate, length) combos, few
    # compiled encoders. 6 lengths spanning ONE bit bucket and one
    # symbol bucket per rate -> encode_frame grows O(buckets) entries
    # (<= 2 per rate here), never one per length; a second encode_many
    # batch at new lengths inside the fixture geometry grows NOTHING.
    # `corpus` is requested for exactly that: the "old geometry" is the
    # one the fixture compiled — run alone, without it, this test's
    # encode_many was the geometry's FIRST compile and grew the cache
    # by one (the failure was the test's order dependence, not an
    # extra compile).
    rng = np.random.default_rng(9)
    lens = (5, 6, 7, 9, 11, 13)
    with dispatch.cache_growth(tx._jit_encode_frame) as g:
        for m in (12, 48):
            for nb in lens:
                tx.encode_frame(rng.integers(0, 256, nb).astype(np.uint8),
                                m)
    # 2 rates x (1 bit bucket x <= 2 symbol buckets) — not 2 x 6
    assert g[tx._jit_encode_frame] <= 4, g.growth

    psdus = [rng.integers(0, 256, n).astype(np.uint8)
             for n in (14, 8, 13, 7, 11, 6, 5, 10)]
    with dispatch.cache_growth(tx._jit_encode_many) as g2:
        txb = tx.encode_many(psdus, MBPS)
    assert g2.total == 0, "new lengths in an old geometry re-compiled"
    for i, (p, m) in enumerate(zip(psdus, MBPS)):
        _assert_same_samples(txb.samples[i, :txb.n_valid[i]],
                             tx.encode_frame(p, m))


def test_transmit_many_matches_perframe(corpus):
    psdus, _gb, _gf, _db, _dp = corpus
    from ziria_tpu.backend import framebatch
    with dispatch.count_dispatches() as d:
        got = framebatch.transmit_many(psdus, MBPS, batched_tx=True)
    assert d.counts["tx.encode_many"] == 1 and d.total == 1
    ref = framebatch.transmit_many(psdus, MBPS, batched_tx=False)
    for a, b in zip(got, ref):
        _assert_same_samples(a, b)


def test_batched_tx_env_knob(monkeypatch):
    # the CLI's scoped-env pattern: default ON, ZIRIA_BATCHED_TX=0
    # forces the oracle loop, an explicit argument wins over the env
    monkeypatch.delenv("ZIRIA_BATCHED_TX", raising=False)
    assert link.batched_tx_enabled(None)
    monkeypatch.setenv("ZIRIA_BATCHED_TX", "0")
    assert not link.batched_tx_enabled(None)
    assert link.batched_tx_enabled(True)
    monkeypatch.setenv("ZIRIA_BATCHED_TX", "1")
    assert link.batched_tx_enabled(None)
    assert not link.batched_tx_enabled(False)


def test_tx_rx_bucket_rules_agree():
    # encode_many buckets symbol counts with tx._sym_bucket; the mixed
    # decode buckets with rx._sym_bucket — the loopback's geometry
    # contract is that they are the SAME rule (both call
    # utils/dispatch.pow2_bucket); a drift would silently double
    # compile classes
    for k in range(1, 200):
        assert tx._sym_bucket(k) == rx._sym_bucket(k)
    # and the switch order TX encodes with is the one RX decodes with
    assert tuple(RATE_MBPS_ORDER) == rx.RATE_MBPS_ORDER
    for m, i in RATE_INDEX.items():
        assert rx.RATE_INDEX[m] == i
