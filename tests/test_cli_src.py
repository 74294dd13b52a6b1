"""CLI end-to-end over .zir sources: the reference's golden-file flow.

Each examples/*.zir compiles via --src and runs through the driver with
file I/O in both dbg and bin modes, on both backends; outputs must agree
with the interpreter oracle (the reference's BlinkDiff discipline,
SURVEY.md §4)."""

import os

import numpy as np
import pytest

from ziria_tpu.frontend import compile_file
from ziria_tpu.interp.interp import run
from ziria_tpu.runtime.buffers import StreamSpec, read_stream, write_stream
from ziria_tpu.runtime.cli import main as cli_main
from ziria_tpu.utils.diff import stream_diff

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _run_cli(src, in_arr, in_ty, tmp_path, mode="dbg", backend="jit",
             extra=()):
    inf = tmp_path / f"in.{mode}"
    outf = tmp_path / f"out.{mode}"
    write_stream(StreamSpec(ty=in_ty, path=str(inf), mode=mode), in_arr)
    rc = cli_main([
        f"--src={src}",
        "--input=file", f"--input-file-name={inf}",
        f"--input-file-mode={mode}",
        "--output=file", f"--output-file-name={outf}",
        f"--output-file-mode={mode}", f"--backend={backend}", *extra,
    ])
    assert rc == 0
    prog = compile_file(str(src))
    return read_stream(StreamSpec(ty=prog.out_ty or in_ty, path=str(outf),
                                  mode=mode))


def _oracle(src, in_arr):
    prog = compile_file(str(src))
    return run(prog.comp, list(np.asarray(in_arr))).out_array()


@pytest.mark.parametrize("mode", ["dbg", "bin"])
@pytest.mark.parametrize("backend", ["interp", "jit"])
def test_scrambler_cli(tmp_path, mode, backend):
    src = os.path.join(EXAMPLES, "scrambler.zir")
    rng = np.random.default_rng(0)
    xs = rng.integers(0, 2, 256).astype(np.uint8)
    out = _run_cli(src, xs, "bit", tmp_path, mode, backend)
    want = _oracle(src, xs)
    np.testing.assert_array_equal(out, want.astype(np.uint8))
    # known-answer: scrambling zeros yields the 127-bit sequence
    from ziria_tpu.ops.scramble import np_lfsr_sequence_127
    zs = np.zeros(127, np.uint8)
    out0 = _run_cli(src, zs, "bit", tmp_path, mode, backend)
    # bin mode pads bit streams to a byte boundary (no length header,
    # same as the reference's buf_bit) — compare the first 127
    np.testing.assert_array_equal(
        out0[:127], np_lfsr_sequence_127(
            np.array([1, 0, 1, 1, 1, 0, 1], np.uint8)))


@pytest.mark.parametrize("backend", ["interp", "jit"])
def test_fir_cli(tmp_path, backend):
    src = os.path.join(EXAMPLES, "fir.zir")
    xs = (100 * np.sin(np.arange(200) / 5)).astype(np.int32)
    out = _run_cli(src, xs, "int32", tmp_path, "dbg", backend)
    want = _oracle(src, xs)
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("mode", ["dbg", "bin"])
def test_fft64_cli(tmp_path, mode):
    src = os.path.join(EXAMPLES, "fft64.zir")
    rng = np.random.default_rng(2)
    xs = rng.integers(-512, 512, (256, 2)).astype(np.int16)
    out = _run_cli(src, xs, "complex16", tmp_path, mode)
    want = _oracle(src, xs)
    # int16 quantization on the way out: tolerance compare (BlinkDiff role)
    rep = stream_diff(out.astype(np.float64), want.astype(np.float64),
                      atol=1.0)
    assert rep, rep.message


def test_interleaver_cli_flag_matrix(tmp_path):
    """Flag matrix: fold/autolut/backends must not change output."""
    src = os.path.join(EXAMPLES, "interleaver.zir")
    rng = np.random.default_rng(3)
    xs = rng.integers(0, 2, 480).astype(np.uint8)
    want = _oracle(src, xs)
    for backend in ("interp", "jit"):
        for extra in ((), ("--no-fold",), ("--autolut",)):
            out = _run_cli(src, xs, "bit", tmp_path, "dbg", backend,
                           extra=extra)
            np.testing.assert_array_equal(out, want.astype(np.uint8),
                                          err_msg=f"{backend} {extra}")
    # and the permutation is its own inverse's inverse: applying it twice
    # on indices returns sorted order only for the identity — sanity-check
    # the known BPSK pattern instead
    blk = want[:48]
    k = np.arange(48)
    perm = 3 * (k % 16) + k // 16
    src_blk = xs[:48]
    np.testing.assert_array_equal(blk[perm], src_blk)


@pytest.mark.parametrize("backend", ["interp", "jit"])
def test_wifi_tx_bpsk_matches_ops_chain(tmp_path, backend):
    """The surface-syntax TX bit pipeline == the ops/ oracle chain
    (scramble ^ seq -> conv_encode -> interleave at N_CBPS=48)."""
    from ziria_tpu.ops.coding import np_conv_encode_ref
    from ziria_tpu.ops.interleave import interleave
    from ziria_tpu.ops.scramble import np_lfsr_sequence_127

    src = os.path.join(EXAMPLES, "wifi_tx_bpsk.zir")
    rng = np.random.default_rng(7)
    n_bits = 24 * 8            # -> 48*8 coded bits, 8 interleaver blocks
    xs = rng.integers(0, 2, n_bits).astype(np.uint8)
    out = _run_cli(src, xs, "bit", tmp_path, "dbg", backend)

    seed = np.array([1, 0, 1, 1, 1, 0, 1], np.uint8)
    scr = xs ^ np.resize(np_lfsr_sequence_127(seed), n_bits)
    coded = np_conv_encode_ref(scr)
    want = np.concatenate([
        np.asarray(interleave(coded[k:k + 48], 48, 1))
        for k in range(0, coded.size, 48)])
    np.testing.assert_array_equal(out.astype(np.uint8), want)


def test_packet_detect_zir_dynamic_control(tmp_path):
    """The streaming STS detector: a while-loop computer terminating
    with a value (interpreter backend — data-dependent control)."""
    src = os.path.join(EXAMPLES, "packet_detect.zir")
    rng = np.random.default_rng(11)
    # 100 noise samples, then a periodic (period-16) STS-like burst
    noise = rng.normal(0, 30, (100, 2))
    sts16 = rng.normal(0, 300, (16, 2))
    burst = np.tile(sts16, (10, 1))
    xs = np.concatenate([noise, burst]).astype(np.int16)
    out = _run_cli(src, xs, "complex16", tmp_path, "dbg", "interp")
    # detection fires once the window is periodic: a little after the
    # burst start + one 16-lag window fill
    assert out.shape[0] == 1
    assert 100 <= int(out[0]) <= 140, int(out[0])


def test_lut_map_autolut_flag_matrix(tmp_path):
    """--autolut must leave output unchanged (table == direct eval)."""
    src = os.path.join(EXAMPLES, "lut_map.zir")
    xs = np.arange(-128, 128, dtype=np.int8)
    outs = {}
    for backend in ("interp", "jit"):
        for extra in ((), ("--autolut",)):
            outs[(backend, extra)] = _run_cli(
                src, xs, "int8", tmp_path, "dbg", backend, extra=extra)
    base = outs[("interp", ())]
    for k, v in outs.items():
        np.testing.assert_array_equal(v, base, err_msg=str(k))
    # spot-check the function: x=0b00001011 -> nibble 1011 reversed
    # 1101=13, parity of high nibble 0000 is 0
    assert base[128 + 0b1011] == 13


@pytest.mark.parametrize("backend", ["interp", "jit"])
def test_qam16_matches_modulate_oracle(tmp_path, backend):
    from ziria_tpu.ops.modulate import np_modulate_ref

    src = os.path.join(EXAMPLES, "qam16.zir")
    rng = np.random.default_rng(21)
    bits = rng.integers(0, 2, 64 * 4).astype(np.uint8)
    out = _run_cli(src, bits, "bit", tmp_path, "dbg", backend)
    want = np_modulate_ref(bits, 4) * 1024.0
    got = out[:, 0].astype(np.float64) + 1j * out[:, 1].astype(np.float64)
    np.testing.assert_allclose(got, want, atol=1.0)


def test_cli_profile_per_stage(tmp_path, capsys):
    """--profile prints per-stage wall time + item counts and still
    produces the golden output (VERDICT r1 #9, SURVEY.md §5)."""
    src = os.path.join(EXAMPLES, "wifi_tx_bpsk.zir")
    infile = os.path.join(EXAMPLES, "golden", "wifi_tx_bpsk.infile")
    ground = os.path.join(EXAMPLES, "golden", "wifi_tx_bpsk.outfile.ground")
    outf = tmp_path / "out.bin"
    from ziria_tpu.runtime.cli import main as cli_main
    rc = cli_main([
        f"--src={src}", "--input=file", f"--input-file-name={infile}",
        "--input-file-mode=bin", "--output=file",
        f"--output-file-name={outf}", "--output-file-mode=bin",
        "--backend=jit", "--profile",
    ])
    assert rc == 0
    err = capsys.readouterr().err
    assert "profile:" in err and "stage" in err
    with open(outf, "rb") as f1, open(ground, "rb") as f2:
        assert f1.read() == f2.read()


def test_cli_profile_trace(tmp_path):
    """--profile-trace writes a jax.profiler trace directory."""
    src = os.path.join(EXAMPLES, "scrambler.zir")
    infile = os.path.join(EXAMPLES, "golden", "scrambler.infile")
    outf = tmp_path / "out.dbg"
    tdir = tmp_path / "trace"
    from ziria_tpu.runtime.cli import main as cli_main
    rc = cli_main([
        f"--src={src}", "--input=file", f"--input-file-name={infile}",
        "--input-file-mode=dbg", "--output=file",
        f"--output-file-name={outf}", "--output-file-mode=dbg",
        "--backend=jit", f"--profile-trace={tdir}",
    ])
    assert rc == 0
    assert tdir.exists() and any(tdir.rglob("*"))


def test_cli_batch_input_files(tmp_path):
    """--batch-input-files: N captures decode in one process with
    frame-batched device calls, each output equal to its own solo
    run (the driver surface of backend/framebatch)."""
    src = os.path.join(EXAMPLES, "scrambler.zir")
    rng = np.random.default_rng(5)
    ins, outs, solo = [], [], []
    for k in range(4):
        xs = rng.integers(0, 2, 256 + 32 * k).astype(np.uint8)
        inf = tmp_path / f"in{k}.dbg"
        write_stream(StreamSpec(ty="bit", path=str(inf), mode="dbg"),
                     xs)
        ins.append(str(inf))
        outs.append(str(tmp_path / f"out{k}.dbg"))
        sof = tmp_path / f"solo{k}.dbg"
        rc = cli_main([
            f"--src={src}", "--input=file",
            f"--input-file-name={inf}", "--input-file-mode=dbg",
            "--output=file", f"--output-file-name={sof}",
            "--output-file-mode=dbg", "--backend=hybrid"])
        assert rc == 0
        solo.append(sof.read_text())
    rc = cli_main([
        f"--src={src}",
        f"--batch-input-files={','.join(ins)}",
        f"--batch-output-files={','.join(outs)}",
        "--input-file-mode=dbg", "--output-file-mode=dbg"])
    assert rc == 0
    for k, out in enumerate(outs):
        assert open(out).read() == solo[k], f"stream {k}"


def test_cli_batch_validation(tmp_path):
    src = os.path.join(EXAMPLES, "scrambler.zir")
    with pytest.raises(SystemExit, match="together"):
        cli_main([f"--src={src}", "--batch-input-files=a,b"])
    with pytest.raises(SystemExit, match="2 inputs but 1"):
        cli_main([f"--src={src}", "--batch-input-files=a,b",
                  "--batch-output-files=c"])
    with pytest.raises(SystemExit, match="cannot combine"):
        cli_main([f"--src={src}", "--batch-input-files=a",
                  "--batch-output-files=c", "--sp=4"])


def test_cli_places_the_one_compile_cache(tmp_path, monkeypatch):
    """The driver places the persistent XLA cache by the package's
    one rule (utils/compile_cache: JAX_COMPILATION_CACHE_DIR if set,
    else the fixed <checkout>/.jax_cache) — no flag, no private knob —
    and repeat runs are output-identical."""
    import jax

    from ziria_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV, raising=False)
    src = os.path.join(EXAMPLES, "fir.zir")
    xs = (100 * np.sin(np.arange(200) / 5)).astype(np.int32)
    outs = []
    for k in range(2):
        inf = tmp_path / f"in{k}.dbg"
        outf = tmp_path / f"out{k}.dbg"
        write_stream(StreamSpec(ty="int32", path=str(inf), mode="dbg"),
                     xs)
        rc = cli_main([
            f"--src={src}", "--input=file",
            f"--input-file-name={inf}", "--input-file-mode=dbg",
            "--output=file", f"--output-file-name={outf}",
            "--output-file-mode=dbg", "--backend=jit"])
        assert rc == 0
        outs.append(outf.read_text())
    assert outs[0] == outs[1]
    assert jax.config.jax_compilation_cache_dir \
        == compile_cache.checkout_dir()
    with pytest.raises(SystemExit):
        cli_main(["--compile-cache=/nowhere", "--list-progs"])
