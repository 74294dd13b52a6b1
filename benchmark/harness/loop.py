"""The two load loops and the window and delay arithmetic, on nothing
but a runtime with ``submit``/``step``, a clock and a sleep: a stub
runtime with a fake clock drives them in the tests.

Closed loop (``saturated``): each tick submits one slab per session and
calls ``step()``; input is always waiting, and nothing is in flight
when the window opens or closes. Open loop (``paced``): slabs
are submitted when they are due, whatever the server is doing, and
``step()`` is called whenever something was submitted; it too opens
and closes on a drained fleet, so that what is counted over it is what
it launched.
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Sequence

from . import load


class Emitted(NamedTuple):
    t: float            # seconds since the window opened (< 0: warm-up)
    session: int
    frame: object       # the program's StreamFrame (.start, .result)


class Window(NamedTuple):
    t_open: float               # on the loop's clock
    elapsed_s: float            # as measured, to the closing step's end
                                # (closed loop: its in-flight step's too)
    ticks: int
    consumed: int               # owned samples of its chunk-steps
    emitted: List[Emitted]      # every frame handed back, warm-up too
    delays_s: List[float]       # open loop: one per (step, session)
    late_s: List[float]         # open loop: submit time minus due time
    refused: int                # submits answered backlog_full


def needed_sample(start: int, stride: int, chunk_len: int) -> int:
    """The last sample the receiver needs before the chunk that owns a
    frame starting at ``start`` can be scanned: chunks begin at
    multiples of the stride and own starts in their first ``stride``
    samples (StreamReceiver's docstring; the rehearsal checks that no
    delay comes out negative)."""
    return (start // stride) * stride + chunk_len - 1


def closed_tick(srv, sids, laps, pos: List[int], slab: int, rec):
    with rec.span("bench.submit"):
        for i, sid in enumerate(sids):
            r = srv.submit(sid, load.lap_slice(laps[i], pos[i], slab))
            if r.accepted:
                pos[i] += slab
    with rec.span("bench.step"):
        return srv.step()


def _warm_up(srv, sids, laps, pos, slab, ticks, rec, session_of):
    """Closed-loop ticks before the window; their frames are kept for
    the checks, stamped before 0. A count and not a time (PR 36 tried
    "and 2 s at least": the spread did not follow, and the window's
    place in the stream stays a function of the seed)."""
    return [Emitted(-1.0, session_of(sid), fr) for _ in range(ticks)
            for sid, fr in closed_tick(srv, sids, laps, pos, slab, rec)]


def run_closed(srv, sids: Sequence, laps, slab: int, seconds: float,
               warm_ticks: int, consumed: Callable[[], int],
               clock: Callable[[], float], rec,
               session_of: Callable[[object], int],
               on_open: Callable[[], None] = lambda: None,
               on_tick: Optional[Callable[[int, float], None]] = None,
               drain: Callable[[], list] = lambda: []
               ) -> Window:
    """Warm-up ticks (set-up), ``on_open()``, then the window: it opens
    at the start of the first tick after them and closes once the first
    ``step()`` that finishes at or after ``seconds`` has returned and
    the chunk-step it left in flight is done. ``drain()`` blocks on the
    step in flight and returns its (session, frame) pairs. It is called
    before the window opens and before it closes, so the time measured
    is that of the work counted: a step launched in the window and done
    after it would be samples without their time, and one launched
    before it time without samples, and the two cancel only where every
    ``step()`` takes as long as the next (PR 34: in the beacon cell they
    take 425, 854, 8.5 and 426 ms, and a window that closed on one or
    the other read 1.5% apart)."""
    pos = [0] * len(sids)
    emitted = _warm_up(srv, sids, laps, pos, slab, warm_ticks, rec,
                       session_of)
    emitted += [Emitted(-1.0, session_of(sid), fr) for sid, fr in drain()]
    on_open()
    c0, t_open, ticks = consumed(), clock(), 0
    while True:
        with rec.span("bench.tick"):
            out = closed_tick(srv, sids, laps, pos, slab, rec)
        t = clock() - t_open
        ticks += 1
        for sid, fr in out:
            emitted.append(Emitted(t, session_of(sid), fr))
        if on_tick is not None:
            on_tick(ticks, t)
        if t >= seconds:
            # the closing step's own blocking, a tick late: timed as
            # one, so that the steps' time still adds up to the window's
            with rec.span("bench.step"):
                out = drain()
            t = clock() - t_open
            emitted += [Emitted(t, session_of(sid), fr) for sid, fr in out]
            return Window(t_open, t, ticks, consumed() - c0, emitted,
                          [], [], 0)


def run_open(srv, sids: Sequence, laps, arrivals, warm_ticks: int,
             seconds: float, stride: int, chunk_len: int,
             consumed: Callable[[], int], clock: Callable[[], float],
             sleep: Callable[[float], None], rec,
             session_of: Callable[[object], int],
             on_open: Callable[[], None] = lambda: None,
             on_tick: Optional[Callable[[int, float], None]] = None,
             drain: Callable[[], list] = lambda: []
             ) -> Window:
    """``warm_ticks`` closed-loop ticks of one stride a session
    (set-up: they leave every lane one stride short of its next
    chunk-step), ``drain()``, ``on_open()``, then the open loop from
    there on in every session's stream. Slab k of session i is due at
    ``arrivals[i].slab(k)``'s time after the window opens; it is
    submitted at the first loop pass at or after that, and a refused
    slab stays due. One delay sample per (``step()`` that
    returns frames, session with frames in it): the step's return time
    minus the due time of the slab carrying that session's earliest
    returned frame's ``needed_sample``; frames whose chunk the warm-up
    filled have no due time and give none.

    ``drain()`` blocks on every chunk-step in flight and returns its
    (session, frame) pairs, as ``run_closed``'s. It is called before
    the window opens and after it has closed (its frames are stamped
    with the closing time and give no delay sample: the window's time
    and delays are what they were), so whoever counts dispatches from
    ``on_open()`` to this function's return counts those of the
    chunk-steps the window launched, all of them and no others. Until
    PR 50 a step left unfronted by the warm-up gave the window its
    decode, and a closing call that launched nothing and found the
    last scan done did not give one back: (2n + 1) / n dispatches a
    step with every frame right (PR 48 was refused on it)."""
    n = len(sids)
    nxt = [0] * n
    pos = [0] * n
    emitted = _warm_up(srv, sids, laps, pos, stride, warm_ticks, rec,
                       session_of)
    prefill = min(pos)
    if max(pos) != prefill:
        raise RuntimeError(f"warm-up left the sessions unevenly fed: {pos}")
    emitted += [Emitted(-1.0, session_of(sid), fr) for sid, fr in drain()]
    delays: List[float] = []
    late: List[float] = []
    refused = ticks = 0
    on_open()
    c0, t_open = consumed(), clock()
    while True:
        now = clock() - t_open
        submitted = False
        with rec.span("bench.submit"):
            for i, sid in enumerate(sids):
                while True:
                    first, size, due = arrivals[i].slab(nxt[i])
                    if due > now:
                        break
                    r = srv.submit(sid, load.lap_slice(
                        laps[i], prefill + first, size))
                    if not r.accepted:
                        refused += 1
                        break
                    late.append(now - due)
                    nxt[i] += 1
                    submitted = True
        if submitted:
            with rec.span("bench.tick"):
                with rec.span("bench.step"):
                    out = srv.step()
            t = clock() - t_open
            ticks += 1
            first_of = {}
            for sid, fr in out:
                i = session_of(sid)
                emitted.append(Emitted(t, i, fr))
                first_of[i] = min(first_of.get(i, fr.start), fr.start)
            for i, start in first_of.items():
                rel = needed_sample(start, stride, chunk_len) - prefill
                if rel >= 0:
                    delays.append(t - arrivals[i].due_of_sample(rel))
            if on_tick is not None:
                on_tick(ticks, t)
        else:
            due = min(arrivals[i].slab(nxt[i])[2] for i in range(n))
            sleep(max(0.0, min(due - now, seconds - now)))
        t = clock() - t_open
        if t >= seconds:
            emitted += [Emitted(t, session_of(sid), fr)
                        for sid, fr in drain()]
            return Window(t_open, t, ticks, consumed() - c0, emitted,
                          delays, late, refused)
