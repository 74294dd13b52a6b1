"""The fixed-point in-language receiver (examples/wifi_rx_fxp.zir),
second file of three (`test_wifi_rx_fxp_zir.py` says why): the hybrid
executor against the interpreter, the integer chain's bit-identical
repeat and the FCS gate."""

import numpy as np

from ziria_tpu.backend import hybrid as H
from ziria_tpu.interp.interp import run

from test_wifi_rx_fxp_zir import _capture, _prog


def test_rx_fxp_zir_hybrid_matches_interp():
    prog = _prog()
    hyb = H.hybridize(prog.comp)
    for mbps, n_bytes, seed in ((24, 60, 320), (54, 90, 321)):
        xs, want = _capture(mbps, n_bytes, seed)
        gi = np.asarray(run(prog.comp, xs).out_array(), np.uint8)
        gh = np.asarray(run(hyb, xs).out_array(), np.uint8)
        np.testing.assert_array_equal(gi, want)
        np.testing.assert_array_equal(gh, want)


def test_rx_fxp_zir_deterministic_repeat():
    # integer chain: two runs of the same capture are bit-identical
    # (not just tolerance-equal)
    prog = _prog()
    xs, _ = _capture(48, 80, seed=330)
    a = np.asarray(run(prog.comp, xs).out_array(), np.uint8)
    b = np.asarray(run(prog.comp, xs).out_array(), np.uint8)
    np.testing.assert_array_equal(a, b)


def test_rx_fxp_zir_fcs_rejects_corruption():
    xs, _ = _capture(24, 60, seed=340)
    xs = [np.asarray(x) for x in xs]
    # corrupt the DATA region (pre=60 noise + 320 preamble + 80 SIGNAL)
    for k in range(520, 536):
        xs[k] = -xs[k]
    got = run(_prog().comp, xs).out_array()
    assert np.asarray(got).shape[0] == 0
