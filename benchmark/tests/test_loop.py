"""The window and delay arithmetic on a stub runtime and a fake clock,
and the traced run's profiled window on the same two loops."""

import types
from collections import namedtuple

import numpy as np
import pytest

from benchmark.harness import cell, checks, load, loop, spans

Frame = namedtuple("Frame", "start result")
Result = namedtuple("Result", "accepted")
STRIDE, CHUNK = 100, 150


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-3)


class StubRuntime:
    """A fleet whose lane takes a chunk-step whenever CHUNK samples wait,
    owns STRIDE of them, and hands a frame at every owned multiple of
    50 back at the NEXT step (the double buffer). ``step()`` costs
    ``step_s`` on the fake clock."""

    def __init__(self, n, clock, step_s):
        self.tail = [0] * n
        self.offset = [0] * n
        self.pending = []
        self.clock, self.step_s = clock, step_s
        self.fresh = False
        self.chunk_steps = 0        # step() calls that launched one

    def submit(self, sid, slab):
        self.tail[sid] += len(slab)
        self.fresh = True
        return Result(True)

    def step(self):
        self.clock.t += self.step_s
        out, self.pending = self.pending, []
        self.chunk_steps += any(t >= CHUNK for t in self.tail)
        for i in range(len(self.tail)):
            while self.tail[i] >= CHUNK:
                self.pending += [(i, Frame(s, None)) for s in
                                 range(self.offset[i],
                                       self.offset[i] + STRIDE, 50)]
                self.tail[i] -= STRIDE
                self.offset[i] += STRIDE
        return out

    def consumed(self):
        return sum(self.offset)


def laps(n, length=1000):
    lap = load.Lap(np.zeros((length, 2), np.float32), np.zeros(0, int),
                   [], [])
    return [lap] * n


def test_needed_sample_is_the_last_of_the_owning_chunk():
    assert loop.needed_sample(0, STRIDE, CHUNK) == CHUNK - 1
    assert loop.needed_sample(99, STRIDE, CHUNK) == CHUNK - 1
    assert loop.needed_sample(100, STRIDE, CHUNK) == STRIDE + CHUNK - 1


def test_closed_window_counts_whole_steps_over_measured_time():
    clock = FakeClock()
    srv = StubRuntime(2, clock, step_s=0.3)
    win = loop.run_closed(srv, [0, 1], laps(2), STRIDE, seconds=1.0,
                          warm_ticks=3, consumed=srv.consumed, clock=clock,
                          rec=spans.Recorder(), session_of=int)
    # closes at the end of the first step at or after 1.0 s: 4 ticks
    assert win.ticks == 4 and win.elapsed_s == pytest.approx(1.2)
    assert win.consumed == 4 * 2 * STRIDE
    assert win.consumed / win.elapsed_s == pytest.approx(800 / 1.2)
    # warm-up frames are kept (for the checks) and stamped before 0
    assert [e.t for e in win.emitted if e.t < 0]


class PipelinedStub:
    """A device that runs one scan of ``scan_s`` a chunk-step, back to
    back, behind a host that launches step t and then blocks on step
    t-1 (the double buffer). Every ``every``-th step has frames: its
    decode is queued behind scan t, so that ``step()`` returns only
    when scan t is done too, and the next finds nothing to wait for:
    the beacon cell's ticks of one, two, nought and one scan."""

    def __init__(self, n, clock, scan_s, every):
        self.n, self.clock, self.scan_s, self.every = n, clock, scan_s, every
        self.fed = 0
        self.offset = 0
        self.launched = 0
        self.device_free_at = 0.0
        self.pending = None         # (done at, has frames, first sample)

    def submit(self, sid, slab):
        self.fed += len(slab)
        return Result(True)

    def _launch(self):
        done = max(self.clock.t, self.device_free_at) + self.scan_s
        self.device_free_at = done
        self.launched += 1
        new = (done, self.launched % self.every == 0, self.offset)
        self.offset += STRIDE
        return new

    def _wait(self, pend):
        done, frames, first = pend
        if frames:                  # the decode runs behind every scan
            done = self.device_free_at
        self.clock.t = max(self.clock.t, done)
        return [(0, Frame(first, None))] if frames else []

    def step(self):
        prev, self.pending = self.pending, None
        if self.fed >= self.n * STRIDE:
            self.fed -= self.n * STRIDE
            self.pending = self._launch()
        return self._wait(prev) if prev else []

    def drain(self):
        prev, self.pending = self.pending, None
        return self._wait(prev) if prev else []

    def consumed(self):
        return self.n * self.offset


def closed_rate(seconds, drained):
    clock = FakeClock()
    srv = PipelinedStub(2, clock, scan_s=0.425, every=4)
    win = loop.run_closed(srv, [0, 1], laps(2), STRIDE, seconds, 4,
                          srv.consumed, clock, spans.Recorder(), int,
                          **({"drain": srv.drain} if drained else {}))
    return win.consumed / win.elapsed_s, win


def test_the_closed_window_times_the_work_it_counts_whatever_its_edge():
    true = 2 * STRIDE / 0.425
    # windows that close after a step() of one scan, of two, of none
    edges = [29.3, 29.5, 29.9, 30.3, 30.7]
    got = [closed_rate(s, drained=True) for s in edges]
    assert len({win.ticks for _r, win in got}) > 1
    for rate, win in got:
        assert rate == pytest.approx(true, rel=1e-9)
        assert win.emitted[-1].t <= win.elapsed_s
    # until PR 34 the step in flight at the close was counted and not
    # timed, the one in flight at the opening timed and not counted:
    # they cancel where every step() takes as long as the next, and
    # here the same five windows read 1.4% apart (B1(d))
    was = [closed_rate(s, drained=False)[0] for s in edges]
    assert max(was) / min(was) > 1.01


def test_open_loop_times_each_frame_from_its_slabs_due_time():
    clock = FakeClock()
    srv = StubRuntime(1, clock, step_s=0.01)
    arr = [load.Arrivals(seed=3, i=0, slab_lo=10, slab_hi=11, rate=100.0,
                         phase_s=0.0)]
    win = loop.run_open(srv, [0], laps(1), arr, warm_ticks=2, seconds=3.0,
                        stride=STRIDE, chunk_len=CHUNK,
                        consumed=srv.consumed, clock=clock,
                        sleep=clock.sleep, rec=spans.Recorder(),
                        session_of=int)
    # the warm-up fed 200 samples; 10-sample slabs come due every 0.1 s
    assert win.late_s and max(win.late_s) < 0.02 and win.refused == 0
    assert win.delays_s and min(win.delays_s) >= 0.0
    # a frame comes back at the step after its chunk's: at most one
    # slab's interval plus two steps after the chunk's last slab was due
    assert max(win.delays_s) < 0.1 + 0.1 + 2 * 0.01 + 1e-9


def test_a_stalled_server_is_charged_for_the_slabs_it_kept_waiting():
    fast, slow = FakeClock(), FakeClock()
    out = []
    for clock, step_s in ((fast, 0.01), (slow, 0.5)):
        srv = StubRuntime(1, clock, step_s)
        arr = [load.Arrivals(3, 0, 10, 11, 100.0, 0.0)]
        out.append(loop.run_open(
            srv, [0], laps(1), arr, 2, 6.0, STRIDE, CHUNK, srv.consumed,
            clock, clock.sleep, spans.Recorder(), int))
    assert np.median(out[1].delays_s) > np.median(out[0].delays_s) + 0.4
    assert max(out[1].late_s) > 0.4


def test_arrivals_are_the_same_for_a_seed_and_look_up_due_times():
    a = load.Arrivals(2 ** 31 + 7, 1, 256, 2048, 1000.0, 5.0)
    b = load.Arrivals(2 ** 31 + 7, 1, 256, 2048, 1000.0, 5.0)
    assert [a.slab(k) for k in range(50)] == [b.slab(k) for k in range(50)]
    first, size, due = a.slab(7)
    assert a.due_of_sample(first) == due == a.due_of_sample(first + size - 1)
    assert a.due_of_sample(first + size) > due


def test_steady_arrivals_are_the_formula_to_the_bit():
    """Slab k is due at phase + first_k / rate, sizes from the seed."""
    a = load.Arrivals(5000000002, 3, 256, 2048, 4.0e6 / 3, 0.0123)
    rng = np.random.default_rng([5000000002, 3, 29])
    first = 0
    for k in range(2000):
        assert a.slab(k) == (first, a.size[k],
                             0.0123 + first / (4.0e6 / 3))
        assert a.size[k] == int(rng.integers(256, 2048))
        first += a.size[k]


def test_lap_slice_wraps():
    lap = load.Lap(np.arange(20, dtype=np.float32).reshape(10, 2),
                   np.zeros(0, int), [], [])
    got = load.lap_slice(lap, 8, 5)[:, 0]
    assert list(got) == [16, 18, 0, 2, 4]
    assert load.lap_slice(lap, 23, 3)[:, 0].tolist() == [6, 8, 10]


# ------------ what a window dispatched: dispatches_per_chunk_step (PR 50)


class CountingFleet:
    """The three-deep pipeline with its dispatches counted: a launch
    dispatches scan t and then the decode of every older step that
    lacks one (its front half), and hands back the steps beyond two in
    flight; a call that launches nothing runs the halves the device
    has finished, without waiting. One session, a frame a chunk-step.
    ``twice`` is a step whose decode is dispatched a second time."""

    def __init__(self, clock, scan_s, decode_s, step_s, twice=None):
        self.clock, self.scan_s, self.decode_s = clock, scan_s, decode_s
        self.step_s, self.twice = step_s, twice
        self.fed = self.offset = self.launched = 0
        self.counts = {"scan": 0, "decode": 0}
        self.flight = []    # [step, first sample, ready at, fronted]

    def submit(self, sid, slab):
        self.fed += len(slab)
        return Result(True)

    def _front(self, st):
        self.counts["decode"] += 2 if st[0] == self.twice else 1
        st[2], st[3] = max(self.clock.t, st[2]) + self.decode_s, True

    def _settle(self, scans, depth):
        for st in self.flight[:len(self.flight) - scans]:
            if not st[3]:
                self._front(st)
        out = []
        while len(self.flight) > depth:
            st = self.flight.pop(0)
            self.clock.t = max(self.clock.t, st[2])
            out.append((0, Frame(st[1], None)))
        return out

    def step(self):
        self.clock.t += self.step_s
        if self.fed >= CHUNK:
            self.fed -= STRIDE
            self.counts["scan"] += 1
            self.flight.append([self.launched, self.offset,
                                self.clock.t + self.scan_s, False])
            self.launched += 1
            self.offset += STRIDE
            return self._settle(1, 2)
        out = []
        while True:
            st = next((x for x in self.flight if not x[3]), None)
            if st is not None and st[2] <= self.clock.t:
                self._front(st)
            elif self.flight and self.flight[0][3] \
                    and self.flight[0][2] <= self.clock.t:
                out.append((0, Frame(self.flight.pop(0)[1], None)))
            else:
                return out

    def drain(self):
        return self._settle(0, 0)

    def consumed(self):
        return self.offset


def counted_window(seconds, drained, twice=None):
    """A lane fill every 100 ms against a 30 ms scan: the open loop on
    a `CountingFleet`, and the row `checks.check_hidden` makes of what
    was dispatched from `on_open()` to the loop's return."""
    clock = FakeClock()
    srv = CountingFleet(clock, scan_s=0.03, decode_s=0.01, step_s=0.001,
                        twice=twice)
    arr = [load.Arrivals(11, 0, 5, 6, STRIDE / 0.1, 0.0)]
    at = {}

    def drain():
        at["before_tail"] = dict(srv.counts)
        return srv.drain()

    win = loop.run_open(
        srv, [0], laps(1), arr, 2, seconds, STRIDE, CHUNK, srv.consumed,
        clock, clock.sleep, spans.Recorder(), int,
        on_open=lambda: at.update(counts=dict(srv.counts),
                                  steps=srv.launched,
                                  unfronted=sum(not st[3]
                                                for st in srv.flight)),
        **({"drain": drain} if drained else {}))
    steps = srv.launched - at["steps"]
    disp = {site: srv.counts[k] - at["counts"][k] for site, k in
            zip(checks.SITES, ("scan", "decode"))}
    stats = types.SimpleNamespace(
        overflow_chunks=0, degraded=False, quarantines=0, sanitized=0,
        lane_blowups=0)
    row = next(r for r in checks.check_hidden(stats, {}, disp, 0, 0, steps)
               if r.name == "dispatches_per_chunk_step")
    return row, steps, at, srv, win


def test_a_window_that_closes_on_a_ready_step_reads_two_a_step():
    """The window's closing call launched nothing and found the newest
    scan done: its decode is dispatched inside the window, as the
    decode of the step the warm-up left unfronted was. Until PR 50 the
    row read (2n + 1) / n there and refused the run (PR 48)."""
    row, n, at, srv, win = counted_window(2.99, drained=True)
    assert n == 30 and not srv.flight
    # nothing was in flight when it opened, and the tail's drain had
    # nothing to dispatch: the closing call had fronted the last step
    assert at["unfronted"] == 0
    assert at["before_tail"] == srv.counts
    assert (row.value, row.limit, row.how, row.ok) == (2.0, 2.0, "<=", True)
    # every step's frame came back, the tail's stamped at the close
    assert len([e for e in win.emitted if e.t >= 0]) == n
    assert win.delays_s and min(win.delays_s) >= 0.0
    # the same window as the loop was: one step unfronted at the
    # opening, none at the close, 2n + 1 dispatches over n steps
    was, n_was, at_was, _srv, _win = counted_window(2.99, drained=False)
    assert n_was == n and at_was["unfronted"] == 1
    assert was.value == pytest.approx((2 * n + 1) / n) and not was.ok


def test_a_window_that_closes_before_its_newest_scan_reads_two_too():
    row, n, at, srv, _win = counted_window(2.96, drained=True)
    # the newest step's decode was the tail's to dispatch
    assert srv.counts["decode"] - at["before_tail"]["decode"] == 1
    assert (row.value, row.ok) == (2.0, True) and n == 30


@pytest.mark.parametrize("seconds", [2.99, 2.96])
def test_a_third_dispatch_in_some_step_is_not_ok(seconds):
    row, n, _at, _srv, _win = counted_window(seconds, drained=True,
                                             twice=17)
    assert row.value == pytest.approx((2 * n + 1) / n)
    assert row.limit == 2.0 and not row.ok


def test_the_rows_that_failed_come_first_in_what_the_line_keeps():
    rows = [checks.Compared("frames_attempted", 2429, 1, ">="),
            checks.Compared("frames_failed", 0, 0),
            checks.Compared("dispatches_per_chunk_step", 1043 / 521, 2.0),
            checks.Compared("segment_gap_rel", float("nan"), 1.5e-4),
            checks.Compared("negative_delays", 0, 0)]
    got = checks.report(rows)
    # `compared` stays the line's last key; `not_ok` comes before it
    assert list(got) == ["not_ok", "compared"]
    assert got["not_ok"] == {
        "dispatches_per_chunk_step": [1043 / 521, 2.0],
        "segment_gap_rel": [None, 1.5e-4]}
    assert list(got["compared"]) == [
        "dispatches_per_chunk_step", "segment_gap_rel",
        "frames_attempted", "frames_failed", "negative_delays"]
    assert got["compared"]["frames_attempted"] == [2429, 1, ">="]
    sound = checks.report(rows[:2] + rows[4:])
    assert sound["not_ok"] == {} and list(sound["compared"]) == [
        "frames_attempted", "frames_failed", "negative_delays"]


# ------------------------------------ the profiled window (cell.Profiler)


class Watched:
    """A ``cell.Profiler`` on a stub runtime's launch count, with the
    JAX profiler's two calls replaced by a record of them, and every
    ``on_tick`` noted as (tick, t, launches so far, traced)."""

    def __init__(self, monkeypatch, srv, at_s):
        import jax
        self.calls, self.log, self.srv = [], [], srv
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **kw: self.calls.append("start"))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: self.calls.append("stop"))
        self.prof = cell.Profiler("unused", at_s,
                                  lambda: srv.chunk_steps)

    def on_tick(self, tick, t):
        self.prof.on_tick(tick, t)
        self.log.append((tick, t, self.srv.chunk_steps,
                         self.prof.started is not None
                         and not self.prof.done))

    def traced(self):
        """The (tick, t, launches) the trace started at, and those of
        the tick that stopped it."""
        on = [row[:3] for row in self.log if row[3]]
        after = next(row[:3] for row in self.log if row[0] > on[-1][0])
        return on[0], after


def paced(monkeypatch, fill_s, seconds, step_s=0.001):
    """The open loop on one session whose lane fills every ``fill_s``
    (one- and two-sample slabs, so a submit and a ``step()`` every
    millisecond or two) and a server whose ``step()`` takes ``step_s``."""
    clock = FakeClock()
    srv = StubRuntime(1, clock, step_s)
    w = Watched(monkeypatch, srv, 0.4 * seconds)
    arr = [load.Arrivals(7, 0, 1, 3, STRIDE / fill_s, 0.0)]
    win = loop.run_open(srv, [0], laps(1), arr, 2, seconds, STRIDE, CHUNK,
                        srv.consumed, clock, clock.sleep, spans.Recorder(),
                        int, on_tick=w.on_tick)
    w.prof.close()
    return w, win


def test_a_tick_shorter_than_the_fill_gap_is_traced_over_five_launches(
        monkeypatch):
    # what S9 leaves of mtu8.paced: a step() of 1 ms, a lane fill (and
    # so a launch) every 91 ms
    w, win = paced(monkeypatch, fill_s=0.091, seconds=3.0)
    (tick0, t0, n0), (tick1, t1, n1) = w.traced()
    assert t0 >= 1.2 and w.calls == ["start", "stop"]
    assert n1 - n0 == cell.PROFILED_STEPS
    assert t1 - t0 == pytest.approx(5 * 0.091, abs=0.091)
    assert t1 - t0 < cell.PROFILED_SECONDS
    # hundreds of ticks, all but five of them launched nothing
    assert tick1 - tick0 > 200
    # until PR 34 the profiler stopped five TICKS after it started
    # (PROFILED_TICKS): a window of a few milliseconds with no launch
    # in it, so no stack -> emit pair, so no chunk_flight_ms, so the
    # traced line was refused as malformed (PR 26)
    by_tick = {row[0]: row for row in w.log}
    five_ticks_on = by_tick[tick0 + 5]
    assert five_ticks_on[2] - n0 == 0
    assert five_ticks_on[1] - t0 < 0.02


def test_a_fleet_that_hardly_launches_is_traced_for_the_guards_seconds(
        monkeypatch):
    # a lane fill every 4 s: the guard ends the trace with one launch
    w, win = paced(monkeypatch, fill_s=4.0, seconds=20.0)
    (tick0, t0, n0), (tick1, t1, n1) = w.traced()
    assert t0 >= 8.0 and w.calls == ["start", "stop"]
    assert n1 - n0 < cell.PROFILED_STEPS
    assert cell.PROFILED_SECONDS <= t1 - t0 < cell.PROFILED_SECONDS + 0.1
    assert win.elapsed_s >= 20.0


def test_closed_loop_five_ticks_are_five_launches_the_window_as_it_was(
        monkeypatch):
    clock = FakeClock()
    srv = StubRuntime(2, clock, step_s=0.44)
    w = Watched(monkeypatch, srv, 0.4 * 10.0)
    loop.run_closed(srv, [0, 1], laps(2), STRIDE, seconds=10.0,
                    warm_ticks=3, consumed=srv.consumed, clock=clock,
                    rec=spans.Recorder(), session_of=int,
                    on_tick=w.on_tick)
    (tick0, t0, n0), (tick1, t1, n1) = w.traced()
    # starts after the first tick to end at or after 4.0 s (the 10th),
    # stops after five more: what PROFILED_TICKS = 5 gave
    assert tick0 == 10 and tick1 - tick0 == 5 == n1 - n0
    assert t1 - t0 == pytest.approx(5 * 0.44)
    assert w.calls == ["start", "stop"]


# --------------------- PR 36: the warm-up is a count, the phases even


@pytest.mark.parametrize("step_s", [0.5, 0.026, 3.0])
def test_the_warm_up_is_its_ticks_whatever_a_tick_takes(step_s):
    """PR 36 tried "and 2 s at least" and dropped it: the number of
    warm-up ticks then followed the clock, and with it the place in
    every session's stream at which the window opens."""
    clock = FakeClock()
    srv = StubRuntime(2, clock, step_s=step_s)
    opened = []
    win = loop.run_closed(srv, [0, 1], laps(2), STRIDE, seconds=1.0,
                          warm_ticks=4, consumed=srv.consumed, clock=clock,
                          rec=spans.Recorder(), session_of=int,
                          on_open=lambda: opened.append(
                              (clock.t, srv.consumed())))
    # three of the four ticks found a chunk waiting in both lanes
    assert opened == [(pytest.approx(4 * step_s), 3 * 2 * STRIDE)]
    assert win.t_open == opened[0][0]
    assert win.consumed == win.ticks * 2 * STRIDE


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 3600004001])
def test_even_phases_are_the_same_eighths_dealt_by_the_seed(seed):
    period = 0.04
    got = load.phases(seed, 8, period)
    assert sorted(got) == pytest.approx([j * period / 8 for j in range(8)])
    assert got == load.phases(seed, 8, period)
    # another seed deals them in another order (these four all differ)
    assert got != load.phases(seed + 1, 8, period)
