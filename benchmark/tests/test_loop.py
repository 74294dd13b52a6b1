"""The window and delay arithmetic on a stub runtime and a fake clock."""

from collections import namedtuple

import numpy as np
import pytest

from benchmark.harness import load, loop, spans

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

    def submit(self, sid, slab):
        self.tail[sid] += len(slab)
        self.fresh = True
        return Result(True)

    def step(self):
        self.clock.t += self.step_s
        out, self.pending = self.pending, []
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


def test_open_loop_times_each_frame_from_its_slabs_due_time():
    clock = FakeClock()
    srv = StubRuntime(1, clock, step_s=0.01)
    arr = [load.Arrivals(seed=3, i=0, slab_lo=10, slab_hi=11, rate=100.0,
                         phase_span_s=0.0)]
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


def test_lap_slice_wraps():
    lap = load.Lap(np.arange(20, dtype=np.float32).reshape(10, 2),
                   np.zeros(0, int), [], [])
    got = load.lap_slice(lap, 8, 5)[:, 0]
    assert list(got) == [16, 18, 0, 2, 4]
    assert load.lap_slice(lap, 23, 3)[:, 0].tolist() == [6, 8, 10]
