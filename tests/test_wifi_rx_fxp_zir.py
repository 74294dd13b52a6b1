"""The FIXED-POINT receiver as a program OF the framework
(examples/wifi_rx_fxp.zir + lib/wifi_rx_fxp_lib.zir, compiled under
--fxp-complex16).

The reference's receiver ran on int16 SORA bricks end to end; this
program expresses that discipline in the surface language — integer
detect/timing/CFO-NCO/channel-est/equalize/demap — and must decode the
same impaired captures the float in-language receiver does, under both
executors, with its FCS gate intact.

Every case here is a whole capture through the interpreter (50 to 100 s
of one worker), and `--dist loadfile` gives a file to one worker: the
cases live in three files so that no worker is left holding them all
(ROADMAP D8): this one (the impaired captures, the frame batcher, the
flag matrix), `test_wifi_rx_fxp_zir_exact.py` (hybrid == interpreter,
the repeat, the FCS gate) and `test_wifi_rx_fxp_zir_agc.py` (the AGC's
amplitude range), which take `_prog` and `_capture` from here.
"""

import os

import numpy as np
import pytest

from ziria_tpu.backend import hybrid as H
from ziria_tpu.frontend import compile_file
from ziria_tpu.interp.interp import run
from ziria_tpu.phy import channel
from ziria_tpu.utils.bits import bytes_to_bits

SRC = os.path.join(os.path.dirname(__file__), "..", "examples",
                   "wifi_rx_fxp.zir")


def _prog():
    return compile_file(SRC, fxp_complex16=True)


def _capture(mbps, n_bytes, seed):
    psdu, cap = channel.impaired_capture(mbps, n_bytes, seed=seed,
                                         add_fcs=True)
    xs = [p for p in np.asarray(cap, np.int32)]
    want = np.asarray(bytes_to_bits(np.asarray(psdu, np.uint8)))
    return xs, want


@pytest.mark.parametrize("mbps,n_bytes", [(6, 40), (36, 70), (54, 90)])
def test_rx_fxp_zir_decodes_impaired_capture(mbps, n_bytes):
    xs, want = _capture(mbps, n_bytes, seed=300 + mbps)
    got = np.asarray(run(_prog().comp, xs).out_array(), np.uint8)
    np.testing.assert_array_equal(got, want)


def test_rx_fxp_zir_under_framebatch():
    """The fixed-point receiver is just another hybridized program to
    the frame batcher: N captures ride batched chunk steps and decode
    exactly as N sequential runs."""
    from ziria_tpu.backend.framebatch import StepBatcher, run_many
    prog = _prog()
    hyb = H.hybridize(prog.comp)
    caps = [_capture(m, nb, seed=350 + m)
            for m, nb in ((6, 30), (24, 60), (54, 90), (24, 45))]
    got = run_many(hyb, [xs for xs, _w in caps],
                   batcher=StepBatcher(len(caps)))
    for (xs, want), g in zip(caps, got):
        np.testing.assert_array_equal(
            np.asarray(g.out_array(), np.uint8), want)


def test_rx_fxp_zir_flag_matrix_ab_exact():
    """Flag-independence (the suite's metamorphic discipline, SURVEY
    §4): the fixed-point receiver's hybrid decode is bit-identical
    with the GF(2) loop compression and the lane vectorizer disabled."""
    xs, want = _capture(24, 60, seed=345)
    base = np.asarray(
        run(H.hybridize(_prog().comp), xs).out_array(), np.uint8)
    np.testing.assert_array_equal(base, want)
    for var in ("ZIRIA_NO_GF2_LOOPS", "ZIRIA_NO_VECTOR_LOOPS"):
        os.environ[var] = "1"
        try:
            got = np.asarray(
                run(H.hybridize(_prog().comp), xs).out_array(),
                np.uint8)
        finally:
            del os.environ[var]
        np.testing.assert_array_equal(got, base, err_msg=var)
