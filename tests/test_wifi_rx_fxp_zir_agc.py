"""The fixed-point in-language receiver (examples/wifi_rx_fxp.zir),
third file of three (`test_wifi_rx_fxp_zir.py` says why): the
power-of-two AGC over the int16 range."""

import numpy as np
import pytest

from ziria_tpu.interp.interp import run
from ziria_tpu.phy import channel
from ziria_tpu.utils.bits import bytes_to_bits

from test_wifi_rx_fxp_zir import _prog


@pytest.mark.parametrize("scale", [256.0, 8192.0, 24000.0, 30000.0])
def test_rx_fxp_zir_agc_amplitude_universal(scale):
    """The in-language power-of-two AGC normalizes ANY int16 capture
    into the Q schedule's envelope: the same frame decodes from 1/4x
    to rail-clipping amplitudes (at scale 30000 hundreds of samples
    saturate — the detector's pre-shifted products cannot wrap even
    at +-32768)."""
    psdu, cap = channel.impaired_capture(24, 40, seed=555, scale=scale,
                                         add_fcs=True)
    got = np.asarray(
        run(_prog().comp,
            [p for p in np.asarray(cap, np.int32)]).out_array(),
        np.uint8)
    np.testing.assert_array_equal(
        got, np.asarray(bytes_to_bits(np.asarray(psdu, np.uint8))))
