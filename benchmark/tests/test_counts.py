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


def fleet_spans(s, k, bucket, trellis=None, pulled=None, scalars=None,
                useful_bits=3 * 12000, lanes=3, step=6):
    t = counts.trellis_steps(bucket)
    return [
        Span("rx.fleet.stack", {"step": step, "active": s}),
        Span("rx.fleet.pull_scan", {"step": step, "bytes": scalars
                                    or s * k * 23 + s}),
        Span("rx.fleet.decode", {"step": step, "lanes": lanes,
                                 "slots": s * k, "useful_bits": useful_bits,
                                 "trellis_steps": trellis or s * k * t}),
        Span("rx.fleet.pull_decode", {"step": step, "bytes": pulled
                                      or s * k * t + s * k}),
        Span("rx.fleet.emit", {"step": step, "frames": lanes})]


@pytest.mark.parametrize("s, k", [(8, 8), (8, 32), (2, 16)])
def test_spans_at_the_ceiling_are_not_stale_and_the_counts_stand(s, k):
    spans = fleet_spans(s, k, 1024) * 3
    assert counts.stale(spans, s, k, 1024) == []
    assert counts.stale([], s, k, 1024) == []
    top = counts.ceilings(s, k, 1024)
    assert counts.reported(spans, s, k, 1024) == top
    assert counts.reported([], s, k, 1024) == top
    assert top["rx.fleet.pull_decode", "bytes"] \
        == counts.decode_d2h_bytes(s, k, 1024)
    assert top["rx.fleet.decode", "trellis_steps"] \
        * counts.ACS_BYTES_PER_STEP == counts.acs_min_bytes(s * k, 1024)


def test_a_span_arg_above_its_ceiling_is_named_with_both():
    # the whole-bucket trellis of before PR 32, against today's count
    old = fleet_spans(8, 8, 1024, trellis=64 * 221184,
                      pulled=64 * 221184 + 64)
    got = counts.stale(old + fleet_spans(8, 8, 1024), 8, 8, 1024)
    assert got == [
        "rx.fleet.decode reports trellis_steps 14155776, above the "
        "ceiling harness/counts.py counts: 2101248",
        "rx.fleet.pull_decode reports bytes 14155840, above the "
        "ceiling harness/counts.py counts: 2101312"]
    assert counts.stale(fleet_spans(8, 8, 1024, scalars=1481),
                        8, 8, 1024) == [
        "rx.fleet.pull_scan reports bytes 1481, above the ceiling "
        "harness/counts.py counts: 1480"]


CASES_BETWEEN = {
    # S3's packed pull: the useful bits eight to a byte, a flag a frame
    "packed_pull": dict(pulled=36000 // 8 + 3),
    # S5(c): the trellis of the slots that hold a frame, and its pull
    "compacted": dict(trellis=3 * 32832, pulled=3 * 32832 + 3),
    # S5(e): each lane's own length
    "ragged": dict(trellis=36000, pulled=36000 + 64),
    "fewer_scalars": dict(scalars=1000),
}


@pytest.mark.parametrize("case", sorted(CASES_BETWEEN))
def test_between_floor_and_ceiling_the_counts_follow_the_spans(case):
    kw = CASES_BETWEEN[case]
    spans = fleet_spans(8, 8, 1024, **kw) + fleet_spans(8, 8, 1024,
                                                        step=7, **kw)
    assert counts.stale(spans, 8, 8, 1024) == []
    said, top = counts.reported(spans, 8, 8, 1024), \
        counts.ceilings(8, 8, 1024)
    key = {"pulled": ("rx.fleet.pull_decode", "bytes"),
           "trellis": ("rx.fleet.decode", "trellis_steps"),
           "scalars": ("rx.fleet.pull_scan", "bytes")}
    for arg, name_key in key.items():
        assert said[name_key] == kw.get(arg, top[name_key])
        assert said[name_key] <= top[name_key]


def test_the_mean_over_the_traced_calls_is_what_is_reported():
    spans = fleet_spans(8, 8, 1024, pulled=5000) \
        + fleet_spans(8, 8, 1024, pulled=7000, step=7)
    assert counts.reported(spans, 8, 8, 1024)[
        "rx.fleet.pull_decode", "bytes"] == 6000


def test_below_the_floor_its_own_spans_state_is_stale():
    short = fleet_spans(8, 8, 1024, trellis=35999, pulled=4502,
                        scalars=7)
    assert counts.stale(short, 8, 8, 1024) == [
        "rx.fleet.decode reports trellis_steps 35999, below the floor "
        "its step's spans state: 36000",
        "rx.fleet.pull_decode reports bytes 4502, below the floor its "
        "step's spans state: 4503",
        "rx.fleet.pull_scan reports bytes 7, below the floor its "
        "step's spans state: 8"]
    # the floor is that step's: another step's useful bits say nothing
    other = fleet_spans(8, 8, 1024, pulled=4502, step=9)[3:4]
    assert counts.stale(other, 8, 8, 1024) == []
