"""The declarative Geometry object (ISSUE 16): the default
``Geometry()`` is a NO-OP by construction — zero new compiled
programs and bit-identical emissions against the legacy per-knob
arguments at the suite-shared 4096/1024/K=8 streaming geometry —
while ``resolve()`` folds env knobs exactly once, serialization and
the checkpoint geometry fingerprint round-trip (legacy blobs missing
post-format fields included).

Budget discipline: every compiled-path test constructs at the SAME
4096/1024/K=8 geometry the streaming/batched-acquire/mixed suites
share, pays its compiles once in a module fixture, and pins the
geometry-object path under ``dispatch.no_recompile`` against it.
"""

import dataclasses

import numpy as np
import pytest

from ziria_tpu.backend import framebatch
from ziria_tpu.phy import link
from ziria_tpu.phy.wifi import rx
from ziria_tpu.runtime import resilience, serve
from ziria_tpu.utils import dispatch
from ziria_tpu.utils.geometry import Geometry

N_BYTES = 12
CHUNK, FRAME_LEN, K = 4096, 1024, 8
#: the suite-shared streaming geometry, as a Geometry object
GEO = Geometry(chunk_len=CHUNK, frame_len=FRAME_LEN,
               max_frames_per_chunk=K)
LEGACY_KW = dict(chunk_len=CHUNK, frame_len=FRAME_LEN,
                 max_frames_per_chunk=K, check_fcs=True)


def _same_result(a, b) -> bool:
    return (a.ok == b.ok and a.rate_mbps == b.rate_mbps
            and a.length_bytes == b.length_bytes
            and np.array_equal(a.psdu_bits, b.psdu_bits)
            and a.crc_ok == b.crc_ok)


# ----------------------------------------------------- the object itself


def test_default_geometry_is_todays_constants():
    # the no-op-by-construction contract rests on these exact values;
    # a drift here silently re-keys every compiled surface
    g = Geometry()
    assert (g.chunk_len, g.frame_len, g.max_frames_per_chunk,
            g.n_streams) == (1 << 13, 2048, 8, 8)
    assert (g.sym_bucket_min, g.capture_bucket_min,
            g.bit_bucket_min) == (4, 512, 128)
    assert (g.threshold, g.min_run, g.dead_zone) == (0.75, 33, 320)
    # decode-mode knobs default to "resolve from env"
    assert g.viterbi_window is None and g.viterbi_metric is None
    assert g.viterbi_radix is None and g.fused_demap is None
    assert g.sco_track is None
    r = g.resolve()      # clean env -> the historical concrete values
    assert (r.viterbi_window, r.viterbi_metric, r.viterbi_radix,
            r.fused_demap, r.sco_track) == (0, "float32", 2, False,
                                            False)
    assert r.resolve() == r                      # idempotent


def test_geometry_is_frozen_and_hashable():
    g = Geometry()
    assert hash(g) == hash(Geometry())
    assert g == Geometry() and g != GEO
    with pytest.raises(dataclasses.FrozenInstanceError):
        g.chunk_len = 1
    d = {g: "default", GEO: "stream"}             # usable as a dict key
    assert d[Geometry()] == "default"


def test_bucket_rules_match_dispatch_pow2(monkeypatch):
    g = Geometry()
    assert g.sym_bucket(3) == 4 and g.sym_bucket(21) == 32
    assert g.capture_bucket(100) == 512
    assert g.capture_bucket(1500) == 2048
    assert g.bit_bucket(1) == 128 and g.bit_bucket(129) == 256
    # the floors are per-instance tunables, not literals
    assert Geometry(sym_bucket_min=16).sym_bucket(3) == 16


def test_resolve_env_precedence_and_scoped_restore(monkeypatch):
    monkeypatch.setenv("ZIRIA_VITERBI_RADIX", "4")
    monkeypatch.setenv("ZIRIA_VITERBI_WINDOW", "96")
    monkeypatch.setenv("ZIRIA_RX_SCO_TRACK", "1")
    r = Geometry().resolve()
    assert (r.viterbi_radix, r.viterbi_window, r.sco_track) == \
        (4, 96, True)
    # an explicit field beats the env default — CLI args win
    e = Geometry(viterbi_radix=2, viterbi_window=0).resolve()
    assert (e.viterbi_radix, e.viterbi_window) == (2, 0)
    # validation: explicit junk raises with the env var's message
    monkeypatch.setenv("ZIRIA_VITERBI_RADIX", "3")
    with pytest.raises(ValueError, match="ZIRIA_VITERBI_RADIX"):
        Geometry().resolve()
    with pytest.raises(ValueError, match="viterbi_radix"):
        Geometry(viterbi_radix=7).resolve()
    with pytest.raises(ValueError, match="viterbi_metric"):
        Geometry(viterbi_metric="float64").resolve()
    monkeypatch.delenv("ZIRIA_VITERBI_RADIX")
    monkeypatch.delenv("ZIRIA_VITERBI_WINDOW")
    monkeypatch.delenv("ZIRIA_RX_SCO_TRACK")
    # the monkeypatched reads never leaked into the module: clean env
    # resolves back to the historical defaults (scoped restore)
    r2 = Geometry().resolve()
    assert (r2.viterbi_radix, r2.viterbi_window, r2.sco_track) == \
        (2, 0, False)


def test_serialization_round_trips_strictly():
    r = dataclasses.replace(GEO, viterbi_radix=4).resolve()
    assert Geometry.from_json(r.to_json()) == r
    assert Geometry.from_dict(r.as_dict()) == r
    with pytest.raises(ValueError, match="warp_factor"):
        Geometry.from_dict({"chunk_len": 4096, "warp_factor": 9})


def test_serve_config_defaults_derive_from_geometry():
    # the ISSUE 16 dedupe satellite: ServeConfig's fleet-geometry
    # defaults ARE Geometry's — no second "1 << 13" literal to drift
    c = serve.ServeConfig()
    g = Geometry()
    assert (c.n_lanes, c.chunk_len, c.frame_len,
            c.max_frames_per_chunk) == \
        (g.n_streams, g.chunk_len, g.frame_len, g.max_frames_per_chunk)
    t = serve.ServeConfig.from_geometry(
        dataclasses.replace(g, chunk_len=16384, n_streams=4), queue_cap=3)
    assert (t.n_lanes, t.chunk_len, t.queue_cap) == (4, 16384, 3)
    assert t.frame_len == g.frame_len


# ------------------------------------------- compiled-surface no-op pin


@pytest.fixture(scope="module")
def corpus():
    """One stream at the suite-shared geometry, decoded ONCE with the
    legacy per-knob arguments (paying whatever compiles this process
    still needs) — the oracle every geometry-object path must match
    without compiling anything new."""
    from ziria_tpu.phy.wifi.params import RATES

    rng = np.random.default_rng(20260806)
    mbps = sorted(RATES)[:4]
    psdus = [rng.integers(0, 256, N_BYTES).astype(np.uint8)
             for _ in mbps]
    stream, starts = link.stream_many(
        psdus, mbps, snr_db=30.0, cfo=1e-4, delay=60, seed=5,
        add_fcs=True, tail=FRAME_LEN)
    got_legacy, _ = framebatch.receive_stream(stream, streaming=True,
                                              **LEGACY_KW)
    return stream, starts, got_legacy


def test_default_geometry_compiles_nothing_new(corpus):
    """THE tentpole pin: a receiver built from the Geometry object at
    the already-compiled geometry adds ZERO programs to any streaming
    cache and emits bit-identical frames."""
    stream, starts, got_legacy = corpus
    with dispatch.no_recompile(rx._jit_stream_chunk_multi,
                               rx._jit_stream_decode_multi):
        got_geo, _ = framebatch.receive_stream(
            stream, streaming=True, check_fcs=True, geometry=GEO)
    assert [f.start for f in got_geo] == list(starts)
    assert len(got_geo) == len(got_legacy)
    for a, b in zip(got_geo, got_legacy):
        assert a.start == b.start and _same_result(a.result, b.result)


def test_stream_receiver_ctor_geometry_equals_legacy_kwargs(corpus):
    # field-for-field: the ctor resolution maps Geometry fields onto
    # exactly the attributes the legacy arguments set — fingerprint
    # (= compile keys + checkpoint identity) included
    r_geo = framebatch.StreamReceiver(geometry=GEO, check_fcs=True)
    r_old = framebatch.StreamReceiver(**LEGACY_KW)
    assert framebatch._stream_geometry(r_geo.fleet) == \
        framebatch._stream_geometry(r_old.fleet)
    # explicit per-knob args still override the geometry object
    r_mix = framebatch.StreamReceiver(geometry=GEO, chunk_len=8192,
                                      check_fcs=True)
    assert r_mix.chunk_len == 8192 and r_mix.frame_len == FRAME_LEN


def test_fleet_geometry_equals_legacy_kwargs_bit_identical(corpus):
    """The S-stream fleet at the same shared geometry: Geometry-built
    fleet vs legacy-kwargs fleet, zero new programs, identical
    emissions lane for lane."""
    stream, _starts, _legacy = corpus
    streams = [stream, stream[: len(stream) // 2].copy()]
    got_old, _ = framebatch.receive_streams(streams, **LEGACY_KW)
    with dispatch.no_recompile(rx._jit_stream_chunk_multi,
                               rx._jit_stream_decode_multi):
        got_geo, _ = framebatch.receive_streams(
            streams, check_fcs=True, geometry=GEO)
    assert [[f.start for f in lane] for lane in got_geo] == \
        [[f.start for f in lane] for lane in got_old]
    for lane_g, lane_o in zip(got_geo, got_old):
        for a, b in zip(lane_g, lane_o):
            assert _same_result(a.result, b.result)


def test_checkpoint_fingerprint_round_trip(corpus):
    """A Geometry-built receiver's checkpoint restores into a
    legacy-kwargs receiver (and back), and a LEGACY blob missing a
    post-format geometry field (sco_track) still restores — the
    _LEGACY_GEOMETRY_DEFAULTS contract the Geometry refactor must not
    disturb."""
    stream, _starts, _legacy = corpus
    r = framebatch.StreamReceiver(geometry=GEO, check_fcs=True)
    out = r.push(stream[: CHUNK + 100])
    blob, drained = r.checkpoint()
    rest = framebatch.StreamReceiver(checkpoint=blob, **LEGACY_KW)
    a = rest.push(stream[CHUNK + 100:]) + rest.flush()
    r2 = framebatch.StreamReceiver(checkpoint=blob, geometry=GEO,
                                   check_fcs=True)
    b = r2.push(stream[CHUNK + 100:]) + r2.flush()
    assert [f.start for f in a] == [f.start for f in b]
    for x, y in zip(a, b):
        assert _same_result(x.result, y.result)

    # a pre-sco_track blob: rebuild the same state without the field
    st = resilience.restore_carry(blob)
    legacy_geo = dict(st.geometry)
    assert legacy_geo.pop("sco_track") is False
    old_blob = resilience.checkpoint_carry(
        st, seen=st.seen, geometry=legacy_geo, state=st.state)
    r3 = framebatch.StreamReceiver(checkpoint=old_blob, geometry=GEO,
                                   check_fcs=True)
    c = r3.push(stream[CHUNK + 100:]) + r3.flush()
    assert [f.start for f in c] == [f.start for f in a]

    # a MISMATCHED geometry still refuses, Geometry-built or not
    with pytest.raises(resilience.CarryCheckpointError):
        framebatch.StreamReceiver(
            checkpoint=blob, check_fcs=True,
            geometry=dataclasses.replace(GEO, chunk_len=8192))
    del out, drained
