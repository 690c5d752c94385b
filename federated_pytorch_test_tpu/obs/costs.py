"""Per-jit-site compile and dispatch ledger.

Every engine entry point (``train_epoch``/``comm``/``fused_round`` in
train/engine.py and the CPC/VAE equivalents) is assembled through
``analysis.sanitize.instrument_jit``; the :class:`CostLedger` hooks into
that assembly at two points:

- :meth:`CostLedger.mark` wraps the *pre-jit* python callable with a
  per-site trace counter (same trick as ``TraceSentinel``) so a compile
  event is detected exactly — the counter bumps iff jax re-traced the
  function during a dispatch.
- :meth:`CostLedger.instrument` wraps the *jitted* callable with a
  dispatch timer.  Under jax's async dispatch the timed window covers
  trace + compile but not device execution, so when the trace counter
  moved across a dispatch the elapsed wall-seconds *are* the compile
  wall-seconds (plus O(100us) of dispatch overhead).  The same elapsed
  seconds of EVERY call add up to the window's ``dispatch_seconds``:
  what the host spends enqueueing the round's programs.  The slowest
  single call of the window is kept with its site
  (``dispatch_max_seconds``, ``dispatch_max_site``).
- The same wrapper reads the jitted callable's ``_cache_size()`` before
  and after the call.  A call after which it grew while the site's trace
  counter stood still met a **new argument signature** (the same shapes
  under another sharding, committedness or weak type): nothing is
  retraced or compiled, but the dispatch leaves jax's C++ fast path, and
  on a large program that holds the host for a second.  The window
  counts them (``dispatch_new_signatures``).

Per compile event the ledger records the site, wall-seconds, the two
clock stamps and the site's cumulative trace count (1 == cold).  It asks
jax for nothing else: no program is lowered or compiled a second time.

Math identity: the wrappers never touch values — they time the call.
Tests assert bitwise-identical model state with the ledger on/off.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, NamedTuple, Tuple

__all__ = [
    "CompileEvent",
    "CostLedger",
    "RoundCosts",
    "round_cost_fields",
]

_EPS_S = 1e-9


@dataclasses.dataclass
class CompileEvent:
    """One observed compile (re-trace) of one jit site."""

    site: str
    seconds: float
    t_start: float
    t_end: float
    trace_count: int  # cumulative traces of this site; 1 == cold start

    def record(self, **extra: Any) -> Dict[str, Any]:
        """Flatten to a ``compile`` record body (env fields —
        event/schema/run_id — are the recorder's job)."""
        rec: Dict[str, Any] = {
            "site": self.site,
            "compile_seconds": float(self.seconds),
            "t_start": float(self.t_start),
            "t_end": float(self.t_end),
            "trace_count": int(self.trace_count),
        }
        rec.update(extra)
        return rec


class RoundCosts(NamedTuple):
    """One :meth:`CostLedger.drain` window (one round / epoch)."""

    events: Tuple[CompileEvent, ...]
    # host seconds inside the window's instrumented jitted calls (the
    # timer's own t1 - t0: enqueue, plus trace + compile when it compiles)
    dispatch_seconds: float = 0.0
    # calls that added an entry to their site's jit cache without a
    # retrace: a new argument signature
    new_signatures: int = 0
    # the window's slowest single call: (site, seconds)
    slowest: Tuple[str, float] = ("", 0.0)


def round_cost_fields(costs: RoundCosts, t_start: float,
                      seconds: float) -> Dict[str, Any]:
    """Round fields for one drained window.

    ``compile_seconds`` counts only events inside the
    [t_start, t_start+seconds] wall-clock window — events drained late
    (e.g. an eval compile detected next round) belong to the run, not
    this round.  Absent data is omitted, not zeroed.
    """
    out: Dict[str, Any] = {}
    t_hi = t_start + seconds + _EPS_S
    in_window = [e for e in costs.events
                 if e.t_start >= t_start - _EPS_S and e.t_end <= t_hi]
    if in_window:
        out["compile_seconds"] = float(sum(e.seconds for e in in_window))
    if costs.dispatch_seconds > 0:
        # every instrumented call drained with this window, the ones of
        # the block switch before the round included
        out["dispatch_seconds"] = float(costs.dispatch_seconds)
    if costs.slowest[1] > 0:
        out["dispatch_max_site"], out["dispatch_max_seconds"] = (
            costs.slowest[0], float(costs.slowest[1]))
    if costs.new_signatures:
        out["dispatch_new_signatures"] = int(costs.new_signatures)
    return out


class CostLedger:
    """Per-jit-site compile/dispatch recorder (see module docstring).

    Thread-compatibility: engines drive all instrumented dispatches from
    the round loop thread; the ledger is intentionally not locked.
    """

    def __init__(self) -> None:
        self._marks: Dict[str, int] = {}  # site -> traces so far
        self._events: list = []  # pending (drained per round)
        self.all_events: list = []  # full run history (bench.py)
        self._dispatch_s = 0.0
        self._new_signatures = 0
        self._slowest: Tuple[str, float] = ("", 0.0)

    # ---------------------------------------------------------- wiring

    def mark(self, fn: Callable, site: str) -> Callable:
        """Wrap the *pre-jit* callable with the per-site trace counter.
        Runs only while jax traces ``fn`` — zero steady-state cost."""
        self._marks.setdefault(site, 0)
        marks = self._marks

        @functools.wraps(fn)
        def counted(*args: Any, **kwargs: Any) -> Any:
            marks[site] = marks.get(site, 0) + 1
            return fn(*args, **kwargs)

        return counted

    def instrument(self, jfn: Callable, site: str) -> Callable:
        """Wrap the *jitted* callable with the compile-detecting timer."""
        marks = self._marks
        marks.setdefault(site, 0)
        # a sanitized site is a plain wrapper and has no cache to read
        cache_size = getattr(jfn, "_cache_size", lambda: 0)

        @functools.wraps(jfn)
        def timed(*args: Any, **kwargs: Any) -> Any:
            n0, c0 = marks.get(site, 0), cache_size()
            # Async dispatch: no block_until_ready on purpose — the
            # window must cover trace+compile (and, summed into
            # dispatch_seconds, the enqueue), NOT device execution.
            t0 = time.perf_counter()  # graftlint: disable=JG104
            out = jfn(*args, **kwargs)
            t1 = time.perf_counter()  # graftlint: disable=JG104
            if marks.get(site, 0) != n0:
                ev = CompileEvent(site=site, seconds=t1 - t0, t_start=t0,
                                  t_end=t1, trace_count=marks.get(site, 0))
                self._events.append(ev)
                self.all_events.append(ev)
            elif cache_size() > c0:
                self._new_signatures += 1
            self._dispatch_s += t1 - t0
            if t1 - t0 > self._slowest[1]:
                self._slowest = (site, t1 - t0)
            return out

        timed.__wrapped_jit__ = jfn  # the jitted fn itself, for tests
        return timed

    def drain(self) -> RoundCosts:
        """Hand the pending window to the caller and reset it."""
        out = RoundCosts(events=tuple(self._events),
                         dispatch_seconds=self._dispatch_s,
                         new_signatures=self._new_signatures,
                         slowest=self._slowest)
        self._events = []
        self._dispatch_s = 0.0
        self._new_signatures = 0
        self._slowest = ("", 0.0)
        return out

    # ------------------------------------------------------ aggregates

    def totals(self) -> Dict[str, Any]:
        evs = self.all_events
        return {
            "compile_events": len(evs),
            "compile_seconds": float(sum(e.seconds for e in evs)),
            "sites": len(self._marks),
        }
