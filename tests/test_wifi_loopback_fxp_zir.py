"""The ALL-INTEGER in-language loopback (examples/wifi_loopback_fxp.zir):
fcs_add >>> tx_frame_fxp >>> rx_fxp under --fxp-complex16 — no floating
point touches a sample on either side, the discipline the reference's
SORA-backed PHY ran end to end. Payload in must equal payload out, and
the fixed-point transmitter's air signal must be standard-compliant
(the FLOAT library receiver decodes it too).

A case here is minutes of one worker and `--dist loadfile` gives a file
to one worker, so the cases live in two files (ROADMAP D8): the
two-frame and hybrid runs here, the five-frame fuzz and the air-signal
cases in `test_wifi_loopback_fxp_zir_fuzz.py`, which takes `_frames`,
`SRC` and `EXAMPLES` from here."""

import os

import numpy as np

from ziria_tpu.backend import hybrid as H
from ziria_tpu.frontend import compile_file
from ziria_tpu.interp.interp import run

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")
SRC = os.path.join(EXAMPLES, "wifi_loopback_fxp.zir")


def _frames(pairs, seed):
    rng = np.random.default_rng(seed)
    stream, want = [], []
    for rate, n_bytes in pairs:
        bits = rng.integers(0, 2, 8 * n_bytes).astype(np.int32)
        stream += [rate, n_bytes] + bits.tolist()
        want.append(bits.astype(np.uint8))
    return [np.int32(v) for v in stream], np.concatenate(want)


def test_loopback_fxp_two_frames_interp():
    prog = compile_file(SRC, fxp_complex16=True)
    xs, want = _frames(((12, 25), (54, 40)), seed=400)
    got = np.asarray(run(prog.comp, xs).out_array(), np.uint8)
    np.testing.assert_array_equal(got, want)


def test_loopback_fxp_hybrid_matches_interp():
    prog = compile_file(SRC, fxp_complex16=True)
    hyb = H.hybridize(prog.comp)
    xs, want = _frames(((24, 30), (48, 35)), seed=401)
    gi = np.asarray(run(prog.comp, xs).out_array(), np.uint8)
    gh = np.asarray(run(hyb, xs).out_array(), np.uint8)
    np.testing.assert_array_equal(gi, want)
    np.testing.assert_array_equal(gh, want)
