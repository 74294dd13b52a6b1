"""Ask the chip's compiler, without the chip.

libtpu is installed next to the CPU-only jax the suite runs on, and it
compiles for a chip that is DESCRIBED (``v5e:2x2``) rather than
attached. So the programs the served receive path dispatches — and
the Pallas kernels inside them — are lowered and compiled here for
one TPU v5e exactly as the chip would compile them: a Mosaic
rejection, a fast-memory overflow or a program that does not fit HBM
fails THIS file instead of surfacing on the first chip run.

What it cannot show: nothing executes, so no result and no time.

Discipline (one process may hold libtpu; xdist workers each import
every test file): the topology is described inside a module-scoped
fixture, after a test of this file has started — never at import, in
a ``skipif`` or in ``parametrize`` arguments — and every sharding
and shape is built from it in a fixture or a test. The persistent
compile cache is off around the compiles (a described-device entry
can be written but never read back). The program picks interpret
mode from the live backend, which is the CPU here, so each test
steers ``interpret=False`` itself (explicit argument on the kernels,
monkeypatch on the programs) and builds a FRESH jit through the
factory's ``__wrapped__`` so no interpret=False trace lands in a
cache another test file shares.
"""

import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ziria_tpu.ops import viterbi_pallas as vp
from ziria_tpu.phy.wifi import rx as _rx
from ziria_tpu.phy.wifi.params import RATES, mixed_trellis_steps
from ziria_tpu.utils.geometry import DEFAULT

LANES = vp.LANES

#: the served MTU geometry (chip_smoke.py): the power-of-two capture
#: bucket holding a 1500-byte PSDU at 6 Mbit/s, its chunk, S = K = 8
MTU = dict(s=8, k=8, chunk_len=131072, frame_len=65536)
#: `wifi-a-maxpsdu-8s` (PR 43): the window a 4095-byte PSDU at
#: 6 Mbit/s needs (109 680 samples), its chunk, K = 16: one full tile
MAXPSDU = dict(s=8, k=16, chunk_len=262144, frame_len=131072)
#: `wifi-a-mix-8s` (PR 33): the MTU window at K = 32, 256 slots
MIX = dict(MTU, k=32)
#: `wifi-a-dense54-8s` (PR 45): the MTU window at K = 16, 128 slots
DENSE54 = dict(MTU, k=16)
DFLT = dict(s=DEFAULT.n_streams, k=DEFAULT.max_frames_per_chunk,
            chunk_len=DEFAULT.chunk_len, frame_len=DEFAULT.frame_len)


def _sym_bucket(frame_len: int) -> int:
    return DEFAULT.sym_bucket(
        max(1, (frame_len - _rx.FRAME_DATA_START) // 80))


#: trellis steps of the rate-agnostic decode at the MTU symbol bucket:
#: 152 x 216 = 32 832, the longest frame LENGTH can announce (the
#: bucket at 54 Mbit/s would be 1024 x 216 = 221 184)
T_MTU = mixed_trellis_steps(_sym_bucket(MTU["frame_len"]))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 - any cause is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e device, with the persistent compile cache
    off for the life of this file's compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_chip(monkeypatch):
    """Steer the programs' backend-derived interpret choice to the
    chip's (the live backend here is the CPU)."""
    monkeypatch.setattr(vp, "_interpret_default", lambda: False)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in specs]


def _compile(fn, *shapes, **static):
    t0 = time.perf_counter()
    exe = fn.lower(*shapes, **static).compile()
    print(f"compiled in {time.perf_counter() - t0:.1f}s; "
          f"{exe.memory_analysis()}")
    return exe


def _assert_mosaic(exe, at_least: int = 1):
    n = exe.as_text().count("tpu_custom_call")
    assert n >= at_least, \
        f"{n} tpu_custom_call(s) in the compiled program, wanted " \
        f">= {at_least}: the kernel lowered in interpret mode"


# ------------------------------------------------------------ main path


def test_acs_f32_radix2_kernel_at_mtu_trellis(one_chip):
    llr = jax.ShapeDtypeStruct((1, T_MTU, 2, LANES), jnp.float32,
                               sharding=one_chip)
    _assert_mosaic(_compile(vp._acs_tiles, llr, interpret=False,
                            metric_dtype="float32", radix=2))


def test_traceback_kernel_at_mtu_trellis(one_chip):
    dec = jax.ShapeDtypeStruct((1, T_MTU, 8, LANES), jnp.uint8,
                               sharding=one_chip)
    met = jax.ShapeDtypeStruct((1, vp.N_STATES, LANES), jnp.float32,
                               sharding=one_chip)
    _assert_mosaic(_compile(vp._traceback_tiles, dec, met,
                            interpret=False))


def test_bounded_decode_kernels_take_a_prefetched_bound(one_chip):
    """PR 53: the ACS and the traceback under a bound that is data
    (`_decode_tiles` with `n_blocks`, one int32 a tile) compile as
    Mosaic kernels at the served trellis, and each takes the count as
    its first operand (the scalar prefetch): what the decode program
    above runs a tile."""
    llr = jax.ShapeDtypeStruct((1, T_MTU, 2, LANES), jnp.float32,
                               sharding=one_chip)
    n_blocks = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    low = vp._decode_tiles.lower(llr, interpret=False, n_blocks=n_blocks)
    calls = [ln for ln in low.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 2 and all(
        "(tensor<1xi32>, " in ln.split(" : ")[-1] for ln in calls), \
        [ln[-300:] for ln in calls]
    _assert_mosaic(low.compile(), at_least=2)


def _decode_shapes(geo, sharding):
    nsb = _sym_bucket(geo["frame_len"])
    need = _rx.FRAME_DATA_START + 80 * nsb
    sk = (geo["s"], geo["k"])
    tab = jax.ShapeDtypeStruct(sk, jnp.int32, sharding=sharding)
    segs = jax.ShapeDtypeStruct(sk + (need, 2), jnp.float32,
                                sharding=sharding)
    return nsb, (segs, tab, tab, tab, tab)


def _chunk_shapes(geo, sharding):
    s = geo["s"]
    vec = jax.ShapeDtypeStruct((s,), jnp.int32, sharding=sharding)
    chunks = jax.ShapeDtypeStruct((s, geo["chunk_len"], 2),
                                  jnp.float32, sharding=sharding)
    return chunks, vec, vec, vec


@pytest.mark.parametrize("geo", [DFLT, MTU, MAXPSDU],
                         ids=["default", "mtu", "maxpsdu"])
def test_decode_program_compiles_with_both_kernels(one_chip, on_chip,
                                                   geo):
    """Dispatch 2 of the fleet chunk-step at the served geometry: the
    f32 radix-2 ACS and the traceback are both Mosaic kernels in the
    compiled program, and it fits one chip."""
    nsb, shapes = _decode_shapes(geo, one_chip)
    dec = _rx._jit_stream_decode_multi.__wrapped__(
        nsb, None, None, 2, None, "dp", False, False)
    low = dec.lower(*shapes)
    _assert_mosaic(low.compile(), at_least=2)
    # the walk over the slots that hold a frame (PR 46): a batch of
    # more than a group fronts one group or its whole tile by a
    # conditional on its data; a batch of one tile is no loop (the
    # loop and its bound: test_rx_multistream's toy of two tiles)
    n = geo["s"] * geo["k"]
    assert n <= LANES and not [
        w for w in _while_locations(low) if "stream_decode_graph" in w]
    assert low.as_text().count("stablehlo.case") \
        == (n > _rx.DECODE_GROUP)


def test_decode_program_runs_the_bound_trellis_at_mtu(one_chip, on_chip):
    """Lowering only: at the served bucket (1024 symbols) the ACS
    kernel's LLR operand is 152 x 216 = 32 832 steps long, nothing in
    the program is as long as the bucket at 54 Mbit/s, and the clear
    bits come back (S, K, 32 832)."""
    nsb, shapes = _decode_shapes(MTU, one_chip)
    assert (nsb, T_MTU) == (1024, 32832)
    dec = _rx._jit_stream_decode_multi.__wrapped__(
        nsb, None, None, 2, None, "dp", False, False)
    low = dec.lower(*shapes)
    clear, crc = low.out_info
    assert clear.shape == (MTU["s"], MTU["k"], T_MTU)
    assert crc.shape == (MTU["s"], MTU["k"])
    text = low.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any(f"tensor<1x{T_MTU}x2x{LANES}xf32>" in ln for ln in calls), \
        [ln[:200] for ln in calls]
    assert str(nsb * _rx.MAX_DBPS) not in text


def _chunk_scan(geo):
    return _rx._jit_stream_chunk_multi.__wrapped__(
        geo["k"], geo["frame_len"], _sym_bucket(geo["frame_len"]),
        DEFAULT.threshold, DEFAULT.min_run, DEFAULT.dead_zone, None,
        "dp")


def test_chunk_scan_program_compiles_at_default_geometry(one_chip):
    exe = _compile(_chunk_scan(DFLT), *_chunk_shapes(DFLT, one_chip))
    assert exe.memory_analysis().temp_size_in_bytes < (8 << 30)


def test_chunk_scan_program_compiles_at_mtu_geometry(one_chip):
    """Dispatch 1 at the served MTU geometry. It took 147 s here (133 s
    on the chip machine) while `ops/sync`'s sliding-window conv ran at
    the TPU's DEFAULT precision — the compiler spent minutes on that
    conv over 131072 samples x 8 streams — and 18 s once it asked for
    HIGHEST (PR 22), which is what lets it stay in tier-1.

    Its temporaries in HBM: 2 161 152 bytes (the parent of ISSUE 44:
    2 226 176; at K = 8 the compiler kept that parent's 33.5 MB window
    array in its fast memory, layout `S(1)`, so the count hardly saw
    it go; at K = 32, compiled by hand: 305 357 824 -> 86 507 008)."""
    exe = _compile(_chunk_scan(MTU), *_chunk_shapes(MTU, one_chip))
    assert exe.memory_analysis().temp_size_in_bytes < (8 << 20)


def test_chunk_scan_program_compiles_at_maxpsdu_geometry(one_chip):
    """Dispatch 1 at the one served geometry whose window is not
    65 536 (PR 43): 512 folded blocks a lane, 128 candidates' heads
    and 2048-symbol segments sliced from a 262 144-sample chunk. 11 s
    here; 86 244 864 bytes of temporaries, where the parent of ISSUE
    44, which cut 128 windows of 131 072 and padded each by 164 240
    to gather from, had 304 833 536: under half of that, or a window
    array is back. 4 705 280 since PR 45: the gather goes through a
    lane's K candidates two at a time (`rx._gather_in_groups`), so a
    step's temporaries are a group's (21 MB each here) and the
    compiler keeps them in its fast memory; that PR's derotation under
    a vmap over all K ran on a planar copy of all the masked segments
    and read 170 522 112 here."""
    assert _sym_bucket(MAXPSDU["frame_len"]) == 2048
    exe = _compile(_chunk_scan(MAXPSDU),
                   *_chunk_shapes(MAXPSDU, one_chip))
    assert exe.memory_analysis().temp_size_in_bytes < 150_000_000


def test_chunk_scan_program_compiles_at_dense54_geometry(one_chip):
    """Dispatch 1 at `wifi-a-dense54-8s`'s geometry (PR 45): the MTU
    window at K = 16, 128 candidates' heads and 1024-symbol segments,
    the phase of each segment's derotation formed exactly a sample.
    9 s here; 3 165 184 bytes of temporaries (the plain product under
    the vmap over K had 86 539 264), and every float contraction at
    HIGHEST."""
    assert _sym_bucket(DENSE54["frame_len"]) == 1024
    fn = _chunk_scan(DENSE54)
    exe = _compile(fn, *_chunk_shapes(DENSE54, one_chip))
    assert exe.memory_analysis().temp_size_in_bytes < (8 << 20)
    assert not _loose_contractions(
        fn.lower(*_chunk_shapes(DENSE54, None)).as_text())


def test_sharded_programs_compile_for_four_chips(topo, one_chip,
                                                 on_chip):
    """The path across chips (`ServeConfig(shard=True)`), compiled for
    the described 2x2 host before `chip_smoke.py --four-chips` spends
    four chips on it: both programs under shard_map over a 4-device dp
    mesh, stream axis sharded, each device's program free of
    collectives (streams are independent) and the kernels Mosaic."""
    import numpy as np
    from jax.sharding import Mesh

    from ziria_tpu.parallel.batch import lane_sharding

    mesh = Mesh(np.array(topo.devices), ("dp",))
    assert mesh.size == 4

    def placed(shapes):
        return [jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=lane_sharding(mesh, len(a.shape))) for a in shapes]

    nsb, dec_shapes = _decode_shapes(DFLT, None)
    scan = _rx._jit_stream_chunk_multi.__wrapped__(
        DFLT["k"], DFLT["frame_len"], nsb, DEFAULT.threshold,
        DEFAULT.min_run, DEFAULT.dead_zone, mesh, "dp")
    dec = _rx._jit_stream_decode_multi.__wrapped__(
        nsb, None, None, 2, mesh, "dp", False, False)
    exes = [_compile(scan, *placed(_chunk_shapes(DFLT, None))),
            _compile(dec, *placed(dec_shapes))]
    _assert_mosaic(exes[1], at_least=2)
    for exe in exes:
        text = exe.as_text()
        for op in ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute"):
            assert op not in text, f"{op} in a per-stream program"


def _loose_contractions(stablehlo_text: str):
    """Float contractions not lowered at HIGHEST precision (the rule
    `benchmark/harness/checks.loose_contractions` copied from here)."""
    return [ln.strip()[:200] for ln in stablehlo_text.splitlines()
            if re.search(r"stablehlo\.(dot_general|dot|convolution)\b",
                         ln)
            and "f32" in ln and "HIGHEST" not in ln]


@pytest.mark.parametrize("which", ["chunk_scan", "decode"])
def test_served_programs_contract_floats_at_full_precision(which):
    """A TPU's DEFAULT matmul/conv precision rounds f32 operands to
    bfloat16. The first chip run (PR 22) lost the SIGNAL field's RATE
    bits to it through the DFT-by-matmul FFT while every CPU test
    passed, because on the CPU the default IS full precision. So: no
    float contraction of the two served programs is lowered at
    DEFAULT precision (read off the StableHLO; needs no compiler)."""
    if which == "chunk_scan":
        fn, shapes = _chunk_scan(DFLT), _chunk_shapes(DFLT, None)
    else:
        nsb, shapes = _decode_shapes(DFLT, None)
        fn = _rx._jit_stream_decode_multi.__wrapped__(
            nsb, None, None, 2, None, "dp", False, False)
    loose = _loose_contractions(fn.lower(*shapes).as_text())
    assert not loose, loose[:3]


@pytest.mark.parametrize("s", [8, 1, 32])
def test_chunk_scan_at_mtu_geometry_acquires_over_the_window_head(s):
    """The per-window acquisition ran its detector and its four LTS
    convolutions over all 65 536 samples of 64 windows to re-derive a
    start that lies in each window's first few hundred (36 ms of the
    522 ms chunk-step on the chip: PERF.md, PR 30). It reads the
    window's head now (`rx._acquire_head`): at the served geometry no
    convolution of the S x K window batch is window-long any more.

    And the chunk-level one is no longer `[S, 1, 131 072]`, the
    shape that was 420.8 ms of every tick at S = 8 (PERF.md, PR 35):
    `sync.ccorrelate_valid` cuts each row into blocks of
    `sync.FOLD_BLOCK` outputs, so the chunk-level contraction of the
    scan has a batch of at least 256 — for the lone stream (S = 1) as
    for the fleet — while the head-long one passes through as it was.

    Since PR 47 the scan holds TWO convolutions where it held six: the
    LTS correlation is one of two input channels and two output
    features (four one-channel products before), once over the chunk
    and once over the heads, and the 48-sample window sums are
    shift-adds, no contraction at all (three convolutions with ones
    before). Every float contraction is HIGHEST (no compiler)."""
    from ziria_tpu.ops import sync

    k, chunk = MTU["k"], MTU["chunk_len"]
    geo = dict(MTU, s=s)
    text = _chunk_scan(geo).lower(*_chunk_shapes(geo, None)).as_text()
    assert not _loose_contractions(text)
    convs = [ln for ln in text.splitlines()
             if "stablehlo.convolution" in ln]
    outs = sorted(tuple(int(d) for d in m.groups()) for m in re.finditer(
        r"stablehlo\.convolution.*-> tensor<(\d+)x2x(\d+)xf32>", text))
    assert len(convs) == len(outs) == 2, convs
    assert all("tensor<2x2x64xf32>" in ln for ln in convs)
    head = _rx._acquire_head(MTU["frame_len"])
    # the acquisition: the LTS correlation over S K rows, head-long,
    # unfolded (frame_len - 63 long before PR 30)
    assert head < MTU["frame_len"] - 128
    assert sync.fold_blocks(head - 63) == 1
    # the chunk scan: the same one, every row cut into 256 blocks
    blocks = sync.fold_blocks(chunk - 63)
    assert blocks == 256
    assert sync.fold_rows(s, chunk) == s * blocks
    assert outs == sorted([(s * k, head - 63),
                           (s * blocks, sync.FOLD_BLOCK)])


@pytest.mark.parametrize("geo", [MTU, MIX, MAXPSDU, DENSE54],
                         ids=["mtu", "mix", "maxpsdu", "dense54"])
def test_chunk_scan_cuts_no_window_array(geo):
    """Steps 3 to 5 of `rx.stream_chunk_graph` used to cut a
    `win_len`-sample window for every one of S x K candidates, read
    1024 samples of each, pad every one by the segment's length and
    gather from that: 134 MB written and 303 MB padded a scan at 256
    windows of 65 536 or 128 of 131 072, 5.4 and 4.1 ms of the scan on
    the chip (ledger, PR 43). Every sample both reads keep is a sample
    of the chunk, so they slice the padded chunk (ISSUE 44): nothing
    in the traced scan is a window long beside the slots any more, and
    the only arrays as large as the window array was are the segment
    batch it hands the decode (lowering only, no compiler)."""
    import math

    s, k, win = geo["s"], geo["k"], geo["frame_len"]
    need_b = _rx.FRAME_DATA_START + 80 * _sym_bucket(win)
    head = _rx._acquire_head(win)
    text = _chunk_scan(geo).lower(*_chunk_shapes(geo, None)).as_text()
    shapes = {tuple(int(d) for d in m.group(1).split("x")[:-1])
              for m in re.finditer(r"tensor<((?:\d+x)+)\w+>", text)}
    assert (s, geo["chunk_len"], 2) in shapes         # the reader reads
    assert (s, k, need_b, 2) in shapes                # `segs`
    assert (s, k, head, 2) in shapes                  # the heads
    per_lane = k * win * 2
    long_as_a_window = sorted(
        sh for sh in shapes if win in sh and math.prod(sh) >= per_lane)
    assert not long_as_a_window, long_as_a_window
    as_large = sorted(sh for sh in shapes
                      if math.prod(sh) >= s * per_lane)
    assert as_large and all(need_b in sh for sh in as_large), as_large
    # the one array steps 4 and 5 slice: the chunk and a tail neither
    # read can run out of, a lane (not a slot)
    assert (s, geo["chunk_len"] + head + need_b, 2) in shapes
    assert need_b > win                   # the served relation, both


def _while_locations(lowered):
    """The location of every `stablehlo.while` of a lowered program
    (its name stack carries the `jax.named_scope`s it was traced in)."""
    found = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    if inner.operation.name == "stablehlo.while":
                        found.append(str(inner.operation.location))
                    walk(inner.operation)

    walk(lowered.compiler_ir("stablehlo").operation)
    return found


def test_served_decode_at_mtu_geometry_checks_the_fcs_without_a_loop():
    """The FCS check was a byte-serial `while` of 27 646 dependent
    steps under `rx.decode.back`, 35.9 ms of the 83 ms decode on the
    chip (ledger, PR 25). It is two XOR-reductions and a look-up now
    (`ops/crc.check_crc32_masked`), and the descrambler's period an
    XOR of constants: the scope lowers to no loop at the served size,
    and to no float contraction the chip would round (no compiler)."""
    from ziria_tpu.ops import crc
    nsb, shapes = _decode_shapes(MTU, None)
    dec = _rx._jit_stream_decode_multi.__wrapped__(
        nsb, None, None, 2, None, "dp", False, False)
    lowered = dec.lower(*shapes)
    whiles = _while_locations(lowered)
    assert not [w[:200] for w in whiles if "rx.decode.back" in w]
    # what is left is the two Pallas kernels, interpreted on the CPU
    # (64 slots are one run of the walk, PR 46: no loop of its own)
    assert all("viterbi_pallas" in w for w in whiles), whiles
    assert not _loose_contractions(lowered.as_text())

    def old(data, n):           # the scan it replaced, same scope
        with jax.named_scope("rx.decode.back"):
            return jax.vmap(crc.crc32_bytes_masked)(data, n)

    seen = _while_locations(jax.jit(old).lower(
        jax.ShapeDtypeStruct((4, 16), jnp.uint8),
        jax.ShapeDtypeStruct((4,), jnp.int32)))
    assert len(seen) == 1 and "rx.decode.back" in seen[0], seen


# ----------------------------------------------- the off-by-default levers
#
# What the chip's compiler says to every kernel variant the Geometry
# can switch on (all off by default, utils/geometry.py). A variant the
# chip cannot compile cannot be priced on it (ROADMAP S2/D2), so each
# is held to compiling here. Before PR 22 the five non-default
# (metric, radix) pairs were all refused: radix 4 by Mosaic's gather
# rule and then its mask-register reshape (`_interleave_dec1`), the
# int metrics by the traceback's int32 argmax ("Only float32 is
# supported") — both repaired in a line or three.


@pytest.mark.parametrize("metric,radix", [
    ("float32", 4), ("int16", 2), ("int16", 4), ("int8", 2),
    ("int8", 4)])
def test_acs_variant_decode_compiles(one_chip, metric, radix):
    """ACS + traceback (`_decode_tiles`) per (metric, radix) at the
    MTU trellis."""
    dt = jnp.float32 if metric == "float32" else jnp.int16
    llr = jax.ShapeDtypeStruct((1, T_MTU, 2, LANES), dt,
                               sharding=one_chip)
    _assert_mosaic(_compile(vp._decode_tiles, llr, interpret=False,
                            metric_dtype=metric, radix=radix),
                   at_least=2)


def test_fused_known_rate_decode_compiles(one_chip):
    """The known-rate fused front (demap/deinterleave/depuncture as
    an in-kernel prologue) at 6 Mbit/s over the MTU symbol bucket."""
    rate = RATES[6]
    n_sym = _sym_bucket(MTU["frame_len"])

    def f(data, gain, nbits):
        return vp.viterbi_decode_batch_fused(
            data, gain, rate, nbits_real=nbits, radix=2,
            interpret=False)

    b = MTU["s"] * MTU["k"]
    _assert_mosaic(_compile(jax.jit(f), *_shapes(
        one_chip, ((b, n_sym, 48, 2), jnp.float32),
        ((b, 48), jnp.float32), ((b,), jnp.int32))), at_least=2)


def test_fused_rate_switched_decode_compiles(one_chip):
    """The rate-switched fused front keeps a whole frame's symbols per
    block ((1, n_sym_p, 96, 128) f32 = 50 MB at symbol bucket 1024);
    the compiler takes it at one lane tile."""
    n_sym = _sym_bucket(MTU["frame_len"])

    def f(data, gain, ridx, nbits):
        return vp.viterbi_decode_mixed_fused(
            data, gain, ridx, nbits, radix=2, interpret=False)

    b = MTU["s"] * MTU["k"]
    _assert_mosaic(_compile(jax.jit(f), *_shapes(
        one_chip, ((b, n_sym, 48, 2), jnp.float32),
        ((b, 48), jnp.float32), ((b,), jnp.int32),
        ((b,), jnp.int32))), at_least=2)
