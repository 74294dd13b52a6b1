"""``harness/cell.Profiler`` on a fake launch count, the JAX profiler's
two calls replaced by a record of them: when the trace starts, and that
it stops by chunk-steps launched or by the seconds guard, whichever
comes first, and never twice."""

import pytest

from benchmark.harness import cell


@pytest.fixture
def calls(monkeypatch):
    import jax
    got = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda logdir, **kw: got.append(("start", logdir)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: got.append(("stop",)))
    return got


class Fleet:
    chunk_steps = 0


def test_it_never_starts_before_its_time(calls):
    fleet = Fleet()
    prof = cell.Profiler("dir", 12.0, lambda: fleet.chunk_steps)
    for tick, t in enumerate((0.0, 5.0, 11.999), 1):
        fleet.chunk_steps += 3
        prof.on_tick(tick, t)
    assert calls == [] and prof.started is None
    prof.close()                # a window that closed before 40%
    assert calls == [] and prof.done
    prof.on_tick(9, 13.0)       # and nothing starts after a close
    assert calls == []


def test_it_stops_by_launches_however_many_ticks_they_take(calls):
    fleet = Fleet()
    prof = cell.Profiler("dir", 12.0, lambda: fleet.chunk_steps)
    fleet.chunk_steps = 40
    prof.on_tick(1, 12.0)
    assert calls == [("start", "dir")] and prof.started == (12.0, 40)
    tick = 1
    for launch in range(cell.PROFILED_STEPS):
        for _ in range(60):             # ticks that launch nothing
            tick += 1
            prof.on_tick(tick, 12.0 + 1e-3 * tick)
        assert not prof.done and len(calls) == 1
        fleet.chunk_steps += 1
    prof.on_tick(tick + 1, 12.4)
    assert prof.done and calls == [("start", "dir"), ("stop",)]


def test_a_step_that_launched_two_counts_for_two(calls):
    fleet = Fleet()
    prof = cell.Profiler("dir", 0.0, lambda: fleet.chunk_steps)
    prof.on_tick(1, 0.5)
    fleet.chunk_steps += 4
    prof.on_tick(2, 1.0)
    assert not prof.done
    fleet.chunk_steps += 2          # a stalled server catching up
    prof.on_tick(3, 1.5)
    assert prof.done and calls[-1] == ("stop",)


def test_the_guard_stops_it_where_the_launches_do_not_come(calls):
    fleet = Fleet()
    prof = cell.Profiler("dir", 12.0, lambda: fleet.chunk_steps)
    prof.on_tick(1, 12.5)
    fleet.chunk_steps += cell.PROFILED_STEPS - 1
    prof.on_tick(2, 12.5 + cell.PROFILED_SECONDS - 1e-3)
    assert not prof.done
    prof.on_tick(3, 12.5 + cell.PROFILED_SECONDS)
    assert prof.done and calls == [("start", "dir"), ("stop",)]


def test_close_is_idempotent_and_ends_a_trace_still_open(calls):
    fleet = Fleet()
    prof = cell.Profiler("dir", 1.0, lambda: fleet.chunk_steps)
    prof.on_tick(1, 1.0)
    prof.close()
    prof.close()
    prof.on_tick(2, 2.0)
    assert calls == [("start", "dir"), ("stop",)]


def test_todays_five_chunk_steps_and_the_stand_ins_end_by_launches():
    # 5 x 443 ms (mtu8.paced as it stands) and 5 x 655 ms (the same
    # cell offered 100 000 samples/s: PERF.md, findings of PR 34)
    assert 5 * 0.443 < cell.PROFILED_SECONDS
    assert 5 * 0.655 < cell.PROFILED_SECONDS
