"""The all-integer in-language loopback (examples/wifi_loopback_fxp.zir),
second file of two (`test_wifi_loopback_fxp_zir.py` says why): the
random rate/length fuzz, and the fixed-point transmitter's air signal
under the float library receiver."""

import numpy as np
import pytest

from ziria_tpu.frontend import compile_file, compile_source
from ziria_tpu.interp.interp import run
from ziria_tpu.phy.wifi import rx
from ziria_tpu.utils.bits import bytes_to_bits

from test_wifi_loopback_fxp_zir import EXAMPLES, SRC, _frames


def test_loopback_fxp_random_rate_length_fuzz():
    """Randomized rate/length mix through the ALL-INTEGER loopback:
    every payload must come back exactly (the TX-fuzz discipline of
    test_wifi_tx_rates_zir applied to the integer chain)."""
    rng = np.random.default_rng(360)
    rates = [6, 9, 12, 18, 24, 36, 48, 54]
    pairs = [(int(rng.choice(rates)), int(rng.integers(10, 60)))
             for _ in range(5)]
    xs, want = _frames(pairs, seed=361)
    prog = compile_file(SRC, fxp_complex16=True)
    got = np.asarray(run(prog.comp, xs).out_array(), np.uint8)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("rate", [6, 18, 36, 54])
def test_fxp_tx_air_signal_decodes_under_float_receiver(rate):
    """Cross-family compliance: the integer transmitter's wire signal
    is a standard 802.11a frame the f32 LIBRARY receiver decodes."""
    src = ('#include "lib/wifi_tx_fxp_lib.zir"\n\n'
           'let comp main = read[int32] >>> repeat { tx_frame_fxp() }'
           ' >>> write[complex16]\n')
    prog = compile_source(src, src_name="tx_fxp_probe",
                          base_dir=EXAMPLES, fxp_complex16=True)
    rng = np.random.default_rng(410 + rate)
    n = 40
    psdu = rng.integers(0, 256, n).astype(np.uint8)
    bits = np.asarray(bytes_to_bits(psdu)).astype(np.int32)
    xs = [np.int32(v) for v in [rate, n] + bits.tolist()]
    x = np.asarray(run(prog.comp, xs).out_array(), np.float32)
    r = rx.receive(np.concatenate(
        [np.zeros((50, 2), np.float32), x / 512.0]))
    assert r.ok and r.rate_mbps == rate
    np.testing.assert_array_equal(r.psdu_bits,
                                  np.asarray(bytes_to_bits(psdu)))
