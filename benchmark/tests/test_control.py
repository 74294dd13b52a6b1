"""The controls of ``correct``, at a size a test run can hold (the tiny
twins under tests/rehearse, any backend): the lower precision fails a
limit, and a run whose timed path is broken underneath comes out not
correct."""

import argparse

import ml_dtypes
import numpy as np
import pytest

from benchmark import control
from benchmark.harness import cell, checks, counts


def _args(workload, seed):
    return argparse.Namespace(workload=workload, seed=seed, seconds=1.5,
                              trace=0, rehearse=True)


def test_sound_run_passes_and_bfloat16_reference_fails_a_limit():
    got = {}

    def inspect(rx, host_step, outs, chunk_args, dec_args):
        need_b = counts.FRAME_DATA_START + 80 * rx.n_sym_bucket
        for name, dt in (("f32", np.float32),
                         ("bf16", ml_dtypes.bfloat16)):
            ctrl = control.reference_in_place(host_step, outs,
                                              rx.frame_len, need_b, dt)
            got[name] = checks.float_gaps(host_step, ctrl, rx.frame_len,
                                          need_b)
        got["default"] = control.contractions_at_default(rx, chunk_args,
                                                         dec_args)

    line, compared = cell.measure(_args("mtu8.saturated", 2 ** 31 + 5),
                                  inspect)
    assert line["correct"] and line["failed"] == 0 \
        and line["attempted"] > 50
    lim = checks.limits()
    n, eps, seg = got["bf16"]
    assert n > 0
    assert eps > lim["cfo_gap_rad_per_sample"] \
        or seg > lim["segment_gap_rel"]
    assert seg > lim["segment_gap_rel"]
    # the same reference in float32 stays inside both
    _n, eps32, seg32 = got["f32"]
    assert eps32 <= lim["cfo_gap_rad_per_sample"] \
        and seg32 <= lim["segment_gap_rel"]
    # and the programs traced at a TPU's DEFAULT precision are caught
    assert compared["contractions_below_highest"] == 0
    assert got["default"] > 0


def _flip_a_bit(pairs):
    if pairs:
        lane, fr = pairs[0]
        bits = np.array(fr.result.psdu_bits, copy=True)
        bits[3] ^= 1
        pairs[0] = (lane, fr._replace(
            result=fr.result._replace(psdu_bits=bits)))
    return pairs


def _drop_a_lane(pairs):
    return [(lane, fr) for lane, fr in pairs if lane != 3]


def _emit_twice(pairs):
    return pairs + pairs[:1]


@pytest.mark.parametrize("workload,breakage,kind", [
    ("mtu8.saturated", _flip_a_bit, "bytes"),
    ("mtu8.saturated", _drop_a_lane, "missing"),
    ("mtu8.saturated", _emit_twice, "delivered twice"),
    ("mtu8.paced", _drop_a_lane, "missing"),
    ("mtu8.paced", _flip_a_bit, "bytes")])
def test_broken_timed_path_is_not_correct(monkeypatch, capsys, workload,
                                          breakage, kind):
    """An answer altered, a part of the batch left out, or a frame
    handed back twice where the receiver produces it: the rest of a run
    is driven as it is, in the closed loop and in the open one, and
    ``correct`` comes out false; the line says which row refused it."""
    from ziria_tpu.backend import framebatch

    real = framebatch.MultiStreamReceiver._drain
    monkeypatch.setattr(framebatch.MultiStreamReceiver, "_drain",
                        lambda self, pend: breakage(real(self, pend)))
    line, _compared = cell.measure(_args(workload, 7))
    assert line["correct"] is False and line["failed"] >= 1
    assert f"'{kind}'" in capsys.readouterr().out
    assert line["not_ok"]["frames_failed"] == [line["failed"], 0]
    assert list(line)[-2:] == ["not_ok", "compared"]
    assert list(line["compared"])[0] == "frames_failed"


def test_beacon_cell_refuses_a_frame_that_was_not_sent(monkeypatch):
    from ziria_tpu.backend import framebatch

    real = framebatch.MultiStreamReceiver._drain

    def ghost(self, pend):
        out = real(self, pend)
        if out:
            lane, fr = out[0]
            out.append((lane, fr._replace(start=fr.start + 3000)))
        return out

    monkeypatch.setattr(framebatch.MultiStreamReceiver, "_drain", ghost)
    line, compared = cell.measure(_args("beacon8.saturated", 8))
    assert line["correct"] is False
    assert compared["frames_not_sent"] >= 1
