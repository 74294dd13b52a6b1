"""The carrier derotation against float64 (ISSUE 45).

`sync.correct_cfo` used to form its phase as ONE float32 product,
``-eps * n``: half an ulp of the phase's own size, 1.2e-4 rad once it
passes 2048 rad (sample 55 960 at 20 ppm of a 5.825 GHz carrier,
0.0366 rad/sample) and 2.4e-4 at the end of `wifi-a-maxpsdu-8s`'s
164 240-sample segment: the benchmark's `segment_gap_rel` limit is
1.5e-4. `cplx.cexp_ramp` forms it so that the error is flat in ``n``;
these cases hold every caller's derotation to that, on seeded samples,
by the same float32 ``eps``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ziria_tpu.ops import cplx, sync
from ziria_tpu.phy import channel

#: every other configuration's offset, 20 ppm at channel 165 (both
#: signs) and the fine estimator's range, pi / 64
OFFSETS = (1e-4, 0.0366, -0.0366, 0.0491)
#: two blocks of the ramp, the served segment (400 + 80 x 1024) and
#: `wifi-a-maxpsdu-8s`'s (400 + 80 x 2048)
LENGTHS = (1024, 82320, 164240)
LIMIT = 2e-5


def _samples(n: int, seed: int = 45):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, 2)).astype(np.float32)


def _rotated64(x, eps, sign: float):
    """``x * exp(sign * j * eps * n)`` in float64 by the float32 eps."""
    ph = sign * np.float64(np.float32(eps)) * np.arange(x.shape[0])
    z = (x[:, 0].astype(np.float64) + 1j * x[:, 1]) * np.exp(1j * ph)
    return np.stack([z.real, z.imag], axis=-1)


def _gap(got, want, upto=None):
    """Widest gap over the RMS of a sample (both parts)."""
    rms = float(np.sqrt(np.mean(want ** 2) * 2.0))
    d = np.abs(np.asarray(got, np.float64) - want)[:upto]
    return float(d.max()) / rms


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("eps", OFFSETS)
def test_the_derotation_stays_within_2e5_of_float64_at_any_length(eps, n):
    x = _samples(n)
    got = jax.jit(sync.correct_cfo)(x, jnp.float32(eps))
    want = _rotated64(x, eps, -1.0)
    gap = _gap(got, want)
    assert gap <= LIMIT, gap
    # flat in n: the segment's end is no worse than its first two
    # blocks, where the plain product was already sound
    assert gap <= 4 * _gap(got, want, upto=1024)


def test_the_plain_product_is_what_fails_there():
    """The control: the ramp this file replaced, at the served length
    and the new configuration's offset, is six times over the limit —
    so the cases above can tell the two apart."""
    n = 82320
    x = _samples(n)
    ramp = cplx.cexp(jnp.float32(-0.0366)
                     * jnp.arange(n, dtype=jnp.float32))
    gap = _gap(cplx.cmul(x, ramp), _rotated64(x, 0.0366, -1.0))
    assert gap > 6 * LIMIT, gap


def test_sixteen_rows_with_sixteen_offsets_under_vmap():
    """The served form: `rx.gather_segment_graph` derotates K rows by
    K traced offsets under one vmap."""
    n, k = 82320, 16
    eps = np.linspace(-0.0491, 0.0491, k).astype(np.float32)
    xs = np.stack([_samples(n, seed) for seed in range(k)])
    got = np.asarray(jax.jit(jax.vmap(sync.correct_cfo))(xs, eps))
    for i in range(k):
        want = _rotated64(xs[i], eps[i], -1.0)
        gap = _gap(got[i], want)
        assert gap <= LIMIT, (i, float(eps[i]), gap)
        assert gap <= 4 * _gap(got[i], want, upto=1024)
    # and a row of the batch is that row alone, to a float32 ulp
    alone = np.asarray(jax.jit(sync.correct_cfo)(xs[3], eps[3]))
    assert np.abs(alone - got[3]).max() <= 1e-6


@pytest.mark.parametrize("eps", [0.0366, -0.0491])
def test_the_channel_turns_the_carrier_by_the_same_ramp(eps):
    n = 164240
    x = _samples(n, seed=7)
    got = channel.apply_cfo(x, eps)
    assert _gap(got, _rotated64(x, eps, +1.0)) <= LIMIT
    # what the channel turns the derotation turns back
    back = sync.correct_cfo(got, jnp.float32(eps))
    assert _gap(back, x.astype(np.float64)) <= LIMIT


def test_a_head_is_the_plain_product_bit_for_bit():
    """One block and less (the 320- and 400-sample heads of the
    acquisition) has a block phase of zero and no tail to add: every
    acquisition's numbers are what they were."""
    x = _samples(400)
    eps = jnp.float32(0.0366)
    plain = cplx.cmul(x, cplx.cexp(-eps * jnp.arange(
        400, dtype=jnp.float32)))
    assert np.array_equal(np.asarray(sync.correct_cfo(x, eps)),
                          np.asarray(plain))


def test_the_ramp_refuses_a_length_it_cannot_hold():
    with pytest.raises(ValueError, match="exceed"):
        cplx.cexp_ramp(0.01, (cplx.RAMP_BLOCK << 12) + 1)
