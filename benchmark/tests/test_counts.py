"""``harness/counts.py`` derives the decode's trellis length from the
standard; this is the one place where its rule and the program's are
held equal, and where a span arg that parts from a count is seen to
stop a traced run."""

from collections import namedtuple

import pytest

from benchmark.harness import counts

Span = namedtuple("Span", "name args")
#: symbol buckets around the bound: under it, on it, over it, served
BUCKETS = (64, 152, 153, 1024)


def test_the_longest_frame_is_152_symbols_of_216_bits():
    assert counts.n_symbols(counts.MAX_PSDU_BYTES, 54) == 152
    assert counts.trellis_steps(1024) == 152 * 216 == 32832
    assert counts.trellis_steps(64) == 64 * 216


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_benchmarks_rule_is_the_programs(bucket):
    from ziria_tpu.phy.wifi import params
    assert counts.trellis_steps(bucket) \
        == params.mixed_trellis_steps(bucket)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_decodes_pull_is_a_byte_a_step_and_a_flag_a_slot(bucket):
    t = min(bucket, 152) * 216
    assert counts.decode_d2h_bytes(8, 8, bucket) == 64 * t + 64
    assert counts.decode_d2h_bytes(8, 32, bucket) == 256 * t + 256


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_acs_least_bytes_are_sixteen_a_step_a_lane(bucket):
    t = min(bucket, 152) * 216
    assert counts.acs_min_bytes(64, bucket) == 64 * t * 16
    assert counts.acs_min_bytes(256, bucket) \
        == 4 * counts.acs_min_bytes(64, bucket)


def test_the_served_geometries_read_what_the_programs_spans_report():
    # the spans' ``bytes`` args in PR 32's and PR 33's chip runs
    assert counts.decode_d2h_bytes(8, 8, 1024) == 2101312
    assert counts.scan_d2h_bytes(8, 8) == 1480
    assert counts.decode_d2h_bytes(8, 32, 1024) == 8405248
    assert counts.scan_d2h_bytes(8, 32) == 5896


def fleet_spans(s, k, bucket, trellis=None, pulled=None, scalars=None):
    t = counts.trellis_steps(bucket)
    return [
        Span("rx.fleet.stack", {"step": 7, "active": s}),
        Span("rx.fleet.pull_scan", {"step": 6, "bytes": scalars
                                    or s * k * 23 + s}),
        Span("rx.fleet.decode", {"step": 6, "lanes": 3, "slots": s * k,
                                 "trellis_steps": trellis or s * k * t}),
        Span("rx.fleet.pull_decode", {"step": 6, "bytes": pulled
                                      or s * k * t + s * k}),
        Span("rx.fleet.emit", {"step": 6, "frames": 3})]


@pytest.mark.parametrize("s, k", [(8, 8), (8, 32), (2, 16)])
def test_spans_that_agree_with_the_counts_are_not_stale(s, k):
    assert counts.stale(fleet_spans(s, k, 1024) * 3, s, k, 1024) == []
    assert counts.stale([], s, k, 1024) == []


def test_a_span_arg_that_parts_from_its_count_is_named_with_both():
    # the whole-bucket trellis of before PR 32, against today's count
    old = fleet_spans(8, 8, 1024, trellis=64 * 221184,
                      pulled=64 * 221184 + 64)
    got = counts.stale(old + fleet_spans(8, 8, 1024), 8, 8, 1024)
    assert got == [
        "rx.fleet.decode reports trellis_steps 14155776, "
        "harness/counts.py counts 2101248",
        "rx.fleet.pull_decode reports bytes 14155840, "
        "harness/counts.py counts 2101312"]
    packed = fleet_spans(8, 8, 1024, scalars=1000)
    assert counts.stale(packed, 8, 8, 1024) == [
        "rx.fleet.pull_scan reports bytes 1000, harness/counts.py "
        "counts 1480"]
