"""The measured window: whole passes of a cell's schedule, timed on the
host clock, with the profiler on for one pass in the middle of a traced
run.

An engine module runs its trainer and calls :meth:`Window.pass_done` at
every pass boundary with that pass's round records and a function that
waits for the device.  The first call closes the untimed warm-up pass
(set-up ends there); the later ones are the window.  ``pass_done``
returns True when the window is over.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

#: host annotation around the traced pass (the reducer's window)
TRACED_PASS = "bench_traced_pass"


@dataclasses.dataclass
class Pass:
    records: List[Dict[str, Any]]
    t0: float                  # time.perf_counter at the pass's start
    t1: float                  # ... after the device finished it
    traced: bool = False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Window:
    def __init__(self, seconds: float, trace_dir: Optional[str] = None):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.warmup: Optional[List[Dict[str, Any]]] = None
        self.passes: List[Pass] = []
        self.t_open: Optional[float] = None     # set-up ends, window opens
        self._t0 = 0.0
        self._span = None                       # live TraceAnnotation
        self.traced_t0: Optional[float] = None  # perf_counter at its entry
        self._traced = False

    def pass_done(self, records: List[Dict[str, Any]],
                  sync: Callable[[], None]) -> bool:
        sync()
        now = time.perf_counter()
        if self.warmup is None:
            self.warmup = list(records)
            self.t_open = now
        else:
            self.passes.append(Pass(list(records), self._t0, now,
                                    traced=self._span is not None))
            if self._span is not None:
                self._stop_trace()
        timed = sum(p.seconds for p in self.passes)
        done = timed >= self.seconds
        if self.trace_dir is not None and not self._traced and self.passes \
                and (done or timed >= self.seconds / 2):
            # the middle of the window (or one pass more, if the window
            # closed before any pass could be traced)
            self._start_trace()
            done = False
        self._t0 = time.perf_counter()
        return done

    def _start_trace(self) -> None:
        import jax

        jax.profiler.start_trace(self.trace_dir)
        self._traced = True
        self._span = jax.profiler.TraceAnnotation(TRACED_PASS)
        self.traced_t0 = time.perf_counter()
        self._span.__enter__()

    def _stop_trace(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    def abort(self) -> None:
        """Stop a live trace (an engine raised inside the traced pass)."""
        if self._span is not None:
            self._stop_trace()
