"""The benchmark's own spans: recorded in memory around its calls into
the program, and written into the profiler's trace as
``TraceAnnotation``s when a traced run asks for it."""

from __future__ import annotations

import sys
import threading
import time
import traceback
from contextlib import contextmanager
from typing import List, Optional, Tuple


class Recorder:
    """``with rec.span("bench.step"): ...`` appends (name, start, end)
    on ``time.perf_counter``'s clock. Two clock reads per span whether
    traced or not, so both kinds of run do the same work."""

    def __init__(self, annotate: bool = False):
        self.spans: List[Tuple[str, float, float]] = []
        self.open_since: Optional[float] = None     # outermost open span
        self._ann = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    @contextmanager
    def span(self, name: str):
        ann = self._ann(name) if self._ann is not None else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        outermost = self.open_since is None
        if outermost:
            self.open_since = t0
        try:
            yield
        finally:
            if outermost:
                self.open_since = None
            self.spans.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def durations(self, name: str, lo: float, hi: float) -> List[float]:
        """Seconds of every span ``name`` that began in [lo, hi)."""
        return [b - a for n, a, b in self.spans
                if n == name and lo <= a < hi]


class StallWatch:
    """A sleeping thread that looks, four times a second, whether the
    main thread has been inside one span for longer than ``after_s``,
    and if so notes once where it is (file:line function, innermost
    last). It takes no part in the work; a run that reads far off can
    then say what its slow step was waiting for."""

    def __init__(self, rec: Recorder, after_s: float):
        self._rec, self._after = rec, after_s
        self._main = threading.main_thread().ident
        self._stop = threading.Event()
        self.seen: List[Tuple[float, str]] = []   # (span start, where)
        self._thread = threading.Thread(target=self._run, daemon=True)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=2.0)

    def _run(self) -> None:
        noted: Optional[float] = None
        while not self._stop.wait(0.25):
            began = self._rec.open_since
            if began is None or began == noted \
                    or time.perf_counter() - began < self._after:
                continue
            frame = sys._current_frames().get(self._main)
            where = " < ".join(
                f"{f.filename.rsplit('/', 1)[-1]}:{f.lineno} {f.name}"
                for f in reversed(traceback.extract_stack(frame)[-6:]))
            self.seen.append((began, where))
            noted = began
