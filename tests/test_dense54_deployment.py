"""The deployment `wifi-a-dense54-8s` on the served path at toy width
(ISSUE 45): the rehearsal twin's population (96-byte bodies + FCS at
54 Mbit/s, a SIFS to a DIFS apart) through `ServeRuntime` at the real
carrier offset, 20 ppm of 5.825 GHz = 0.0366 rad/sample, at its mirror
image, and a thousandth under the benchmark reference's range (pi / 64
= 0.0491: the LTS estimator's; the receiver's own coarse stage goes to
pi / 16). Every frame held to what was sent and to the benchmark's
plain numpy receiver on the same samples.

Two sessions of the twin come from the benchmark's own generator
(`load.synth_laps`) at each offset and are served by one geometry, so
the three runs share their compiles. A CPU run: results and counts,
never speeds.
"""

import json
import math
import os

import numpy as np
import pytest

from benchmark import lap_check
from benchmark.harness import checks, counts, load
from ziria_tpu.phy.wifi import rx
from ziria_tpu.runtime import serve
from ziria_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/tests/rehearse/wifi-a-dense54-8s.json")) as _f:
    TWIN = json.load(_f)
GEO = TWIN["geometry"]
CHUNK, FRAME_LEN, K = (GEO["chunk_len"], GEO["frame_len"],
                       GEO["max_frames_per_chunk"])
S, SEED = 2, 45
#: two thirds of the twin's lap (2.875 strides), to keep this file cheap
POP = dict(TWIN["population"], frames_per_lap=8, lap_samples=11776)
CFO = TWIN["channel"]["cfo_rad_per_sample"]
OFFSETS = pytest.mark.parametrize(
    "run", [CFO, -CFO, math.pi / 64 - 1e-3], indirect=True,
    ids=["20ppm", "minus20ppm", "under_pi_over_64"])


def _serve(streams):
    """A stride a session a tick until every stream is through, then
    the steps in flight. Returns (runtime, frames per session)."""
    srv = serve.ServeRuntime(serve.ServeConfig(
        n_lanes=S, chunk_len=CHUNK, frame_len=FRAME_LEN,
        max_frames_per_chunk=K, check_fcs=True))
    stride = CHUNK - FRAME_LEN
    with telemetry.collect(srv.registry):
        for i in range(S):
            assert srv.connect(f"s{i}").admitted
        out, pos = [], 0
        while pos < max(len(st) for st in streams) + CHUNK:
            for i, st in enumerate(streams):
                slab = np.zeros((stride, 2), np.float32)
                part = st[pos: pos + stride]
                slab[:len(part)] = part
                assert srv.submit(f"s{i}", slab).accepted
            out += srv.step()
            pos += stride
        out += [(srv._lane_sid[ln], fr)
                for ln, fr in srv._rx.drain_pending()]
    return srv, [[fr for sid, fr in out if sid == f"s{i}"]
                 for i in range(S)]


@pytest.fixture(scope="module")
def run(request):
    cfg = dict(TWIN, sessions=S, population=POP,
               channel=dict(TWIN["channel"],
                            cfo_rad_per_sample=request.param))
    laps = load.synth_laps(cfg, SEED)
    with telemetry.tracing() as tr:
        srv, frames = _serve([lap.stream for lap in laps])
    classify = [e["args"] for e in tr.events()
                if e["ph"] == "X" and e["name"] == "rx.fleet.classify"]
    return request.param, laps, srv, frames, classify


def test_the_twin_is_the_deployment_in_small():
    real = json.load(open(os.path.join(
        ROOT, "benchmark/configs/wifi-a-dense54-8s.json")))
    assert TWIN["channel"] == real["channel"]
    for key in ("rates_mbps", "gap_samples", "lead_samples", "add_fcs"):
        assert POP[key] == real["population"][key], key
    assert K == real["geometry"]["max_frames_per_chunk"] == 16
    assert TWIN["sessions"] == real["sessions"]
    (body,) = POP["psdu_bytes"]
    assert counts.frame_samples(body + 4, 54) == 400 + 80 * 4 <= FRAME_LEN
    assert POP["lap_samples"] % (CHUNK - FRAME_LEN)


@OFFSETS
@pytest.mark.parametrize("i", range(S))
def test_every_frame_once_in_order_byte_identical_and_as_the_reference(
        run, i):
    _cfo, laps, _srv, frames, _cls = run
    lap, mine = laps[i], frames[i]
    assert [fr.start for fr in mine] == lap.starts.tolist()
    assert len(mine) == POP["frames_per_lap"]
    for j, fr in enumerate(mine):
        res, psdu = fr.result, lap.psdus[j]
        assert res.ok and res.rate_mbps == 54 and res.crc_ok is True, j
        assert res.length_bytes == psdu.size + 4
        assert np.array_equal(
            checks._bytes(res.psdu_bits)[: psdu.size], psdu), j
        assert lap_check.reference_agrees(
            res, lap.stream[fr.start: fr.start + FRAME_LEN]), j


@OFFSETS
def test_nothing_is_hidden_and_no_slot_overflows(run):
    _cfo, _laps, srv, _frames, _cls = run
    st = srv._rx.stats
    assert st.overflow_chunks == 0 and st.truncated_frames == 0
    rows = checks.check_hidden(st, srv.registry.snapshot(), {}, 0, 0,
                               st.chunk_steps)
    bad = [r for r in rows if not r.ok
           and r.name != "dispatches_per_chunk_step"]
    assert not bad, bad


@OFFSETS
def test_the_host_reads_the_offset_the_stations_were_sent_at(run):
    """`rx.fleet.classify` carries the step's widest offset and the
    sum over its acquired frames (micro-radians a sample, from the
    scan's rate word); the estimators' scatter at 30 dB is under 3e-4
    rad/sample, and the int16's step 7.6e-6."""
    cfo, _laps, srv, _frames, classify = run
    want = abs(cfo) * 1e6
    busy = [a for a in classify if a["acquired"]]
    assert sum(a["acquired"] for a in busy) == S * POP["frames_per_lap"]
    for a in busy:
        assert want - 300 <= a["cfo_abs_max_urad"] <= want + 300
        assert abs(a["cfo_abs_sum_urad"] / a["acquired"] - want) <= 150
    if cfo == CFO:
        # ISSUE 45's band for the traced run of the real cell
        assert all(36000 <= a["cfo_abs_max_urad"] <= 37200
                   for a in busy)
    for i in range(S):
        assert abs(srv._rx._cfo_urad[i] - want) <= 300
    # a third of the way to the coarse estimator's wrap, three
    # quarters of the way to the fine one's: what the gauge is for
    assert want < math.pi / 64 * 1e6 < math.pi / 16 * 1e6


def test_the_scan_derotates_by_the_estimate_it_returns():
    """The benchmark's float comparison re-derotates each segment in
    float64 by the scan's own `eps` (output 5, which stays on the
    device): at this offset the two agree to float32's floor (the
    twin's segment is 5520 samples, 202 rad: the lengths at which the
    plain product failed are tests/test_derotate_precision.py's)."""
    laps = load.synth_laps(dict(TWIN, sessions=1, population=POP), SEED)
    need_b = rx.FRAME_DATA_START + 80 * GEO["symbol_bucket"]
    chunk = laps[0].stream[:CHUNK][None]
    scan = rx._jit_stream_chunk_multi(K, FRAME_LEN,
                                      GEO["symbol_bucket"])
    outs = scan(chunk, np.array([CHUNK], np.int32),
                np.array([-192], np.int32),
                np.array([CHUNK - FRAME_LEN], np.int32))
    host_step = (None, [0], chunk, None, None, None)
    n, eps_gap, seg_gap = checks.float_gaps(host_step, outs, FRAME_LEN,
                                            need_b)
    assert n >= 3
    assert eps_gap <= 3e-8 and seg_gap <= 2e-5, (eps_gap, seg_gap)
    # and the rate word's copy of it is that estimate to half a step
    own, found = np.asarray(outs[0]), np.asarray(outs[3])
    rate, urad = rx.unpack_rate_word(outs[6])
    got = own & found
    assert set(rate[got]) == {0b0011}            # 54 Mbit/s
    assert np.abs(urad[got] - np.asarray(outs[5])[got] * 1e6).max() \
        <= 0.5 * 1e6 / rx.CFO_WORD_SCALE + 0.5
