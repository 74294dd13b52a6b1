"""Mixed-rate one-dispatch DATA decode (phy/wifi/rx.decode_data_mixed
+ backend/framebatch.receive_many): a batch with ALL EIGHT rates
present decodes through ONE jitted ``lax.switch`` dispatch,
bit-identical to the host-side bucketed path, with the DATA-stage
compile count dropping from O(rates x log lengths) to O(log lengths).

The expensive geometry compiles happen ONCE in the module fixture;
the corpus length is chosen so every test's common symbol bucket hits
the same compiled dispatch. Compile counts are measured with
`utils.dispatch.cache_growth` — lru_cache DELTAS, never cache_clear:
this module runs inside the full suite, and clearing the shared
bucketed cache would throw away compiled decoders later test files
reuse (the per-rate/bucket entries are process-wide state). Here the
contract is the cache-growth SHAPE, O(log lengths) and not
O(rates x log lengths).
"""

import numpy as np
import pytest

from ziria_tpu.backend import framebatch
from ziria_tpu.phy.wifi import rx, tx
from ziria_tpu.phy.wifi.params import RATES
from ziria_tpu.utils.bits import bytes_to_bits
from ziria_tpu.utils.dispatch import cache_growth

N_BYTES = 16   # small corpus: 8-symbol common bucket keeps the
               # interpret-mode Pallas compiles inside the tier-1 budget


def _capture(rng, mbps, n_bytes):
    psdu = rng.integers(0, 256, n_bytes).astype(np.uint8)
    s = np.asarray(tx.encode_frame(psdu, mbps))
    cap = np.concatenate([np.zeros((50, 2), np.float32), s], axis=0)
    return cap, np.asarray(bytes_to_bits(psdu))


@pytest.fixture(scope="module")
def corpus():
    """All-8-rates corpus + reference results + the compile-count
    DELTAS (cache growth while decoding the corpus, measured without
    clearing the suite-shared caches)."""
    rng = np.random.default_rng(20260802)
    caps, wants = [], []
    for m in sorted(RATES):
        c, w = _capture(rng, m, N_BYTES)
        caps.append(c)
        wants.append(w)
    with cache_growth(rx._jit_decode_data_mixed) as gm:
        mixed = framebatch.receive_many(caps)
    with cache_growth(rx._jit_decode_data_bucketed) as gb:
        bucketed = [rx.receive(c) for c in caps]
    return (caps, wants, bucketed, mixed, gb.total, gm.total)


def test_all_8_rates_bit_identical_to_bucketed(corpus):
    caps, wants, bucketed, mixed, _cb, _cm = corpus
    assert [r.rate_mbps for r in mixed] == sorted(RATES)
    for b, g, w in zip(bucketed, mixed, wants):
        assert b.ok and g.ok
        assert g.length_bytes == N_BYTES
        np.testing.assert_array_equal(g.psdu_bits, w)
        np.testing.assert_array_equal(g.psdu_bits, b.psdu_bits)


def test_one_jitted_switch_serves_every_rate(corpus):
    _caps, _wants, _bucketed, _mixed, cb, cm = corpus
    # the DATA stage of the whole mixed batch is ONE compiled callable
    # (one symbol bucket here): the mixed cache grew by AT MOST one
    # entry for all 8 rates (zero when an earlier file — the batched-
    # acquire suite shares this geometry on purpose — already built
    # the same key), where the bucketed path grows one entry per
    # UNSEEN (rate, bucket) pair — up to 8 here (the shared-cache
    # economics the mixed dispatch exists to beat)
    assert cm <= 1
    assert cb <= len(RATES)


def test_mixed_int16_metric_rides_the_same_dispatch(corpus):
    caps, wants, _bucketed, _mixed, _cb, _cm = corpus
    got = framebatch.receive_many(caps, viterbi_metric="int16")
    for g, w in zip(got, wants):
        assert g.ok
        np.testing.assert_array_equal(g.psdu_bits, w)


def test_failed_lanes_keep_positions(corpus):
    # a lane that fails acquisition keeps its position and never
    # reaches the device batch. 7 live lanes pad back to the
    # fixture's 8-lane geometry, so this reuses the compiled dispatch
    # (a fresh lane count would be a fresh — expensive — compile);
    # the noise lane stays under the fixture's 1024-sample capture
    # bucket so the batched-acquire graph is reused too.
    caps, wants, _bucketed, _mixed, _cb, _cm = corpus
    rng = np.random.default_rng(3)
    noise = rng.normal(scale=0.01, size=(1000, 2)).astype(np.float32)
    lanes = [caps[0], noise] + caps[2:]
    got = framebatch.receive_many(lanes)
    assert got[0].ok and not got[1].ok
    np.testing.assert_array_equal(got[0].psdu_bits, wants[0])
    for g, w in zip(got[2:], wants[2:]):
        assert g.ok
        np.testing.assert_array_equal(g.psdu_bits, w)


def test_mixed_lengths_share_one_bucket(corpus):
    # different PSDU lengths (different true symbol counts) pad to ONE
    # common bucket: shorter lanes ride pad symbols, not a second
    # dispatch — bits still exact per lane. Lengths are chosen so the
    # common bucket equals the fixture corpus's (the 6 Mbps lane's
    # 8-symbol bucket dominates), hitting the already-compiled
    # dispatch.
    caps, wants, _bucketed, _mixed, _cb, _cm = corpus
    rng = np.random.default_rng(8)
    c54, w54 = _capture(rng, 54, 120)     # 5 syms: same 8-sym bucket
    with cache_growth(rx._jit_decode_data_mixed) as g:
        got = framebatch.receive_many(caps[:7] + [c54])
    for r, (m, nb, w) in zip(
            got, [(mm, N_BYTES, ww) for mm, ww
                  in zip(sorted(RATES)[:7], wants[:7])]
            + [(54, 120, w54)]):
        assert r.ok and r.rate_mbps == m and r.length_bytes == nb
        np.testing.assert_array_equal(r.psdu_bits, w)
    assert g.total == 0


def test_rate_index_order_is_the_switch_order():
    # decode_data_mixed's branches are built in RATE_MBPS_ORDER; the
    # index map every caller uses must agree, or a lane would decode
    # at the wrong rate (the e2e identity above would catch it late —
    # this pins the contract directly and costs nothing)
    assert rx.RATE_MBPS_ORDER == tuple(sorted(RATES))
    for i, m in enumerate(rx.RATE_MBPS_ORDER):
        assert rx.RATE_INDEX[m] == i
    assert rx.MAX_DBPS == max(p.n_dbps for p in RATES.values())
