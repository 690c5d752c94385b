"""Per-jit-site device-cost ledger.

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
  seconds of EVERY call add up to the window's ``dispatch_seconds``
  (schema v15): what the host spends enqueueing the round's programs.

Per compile event the ledger records wall-seconds, the site's cumulative
trace count (1 == cold), AOT cost-model numbers, and a persistent-
compile-cache hit/miss attribution:

- ``FEDTPU_COST_AOT=lowered`` (default): ``jfn.lower(...)`` +
  ``Lowered.cost_analysis()`` — FLOPs / bytes-accessed /
  transcendentals from the unoptimized HLO.  Nearly free (~10ms) and
  side-effect free; tracing is already cached from the dispatch itself,
  and lowering works even on donated (deleted) argument buffers because
  only avals/shardings are consulted.
- ``FEDTPU_COST_AOT=full``: additionally ``lowered.compile()`` →
  optimized-HLO ``cost_analysis()`` + ``memory_analysis()``
  (argument/output/temp/generated-code bytes and the derived
  ``peak_device_bytes``).  The first AOT compile of a program is a
  *second real compile* (XLA does not share the dispatch executable
  with the AOT path), so this mode roughly doubles compile cost — keep
  it for profiling runs.
- ``FEDTPU_COST_AOT=off``: timing + cache attribution only.

Fields the backend cannot produce are **omitted, never zeroed** — a
reader must treat every cost field as optional (PARITY.md "advisory").

Cache attribution combines two signals: if the persistent compile cache
directory (utils/compile_cache.py) grew across the compile, a fresh
entry was persisted → miss; otherwise a fast compile (below
``FEDTPU_COST_FAST_COMPILE_S``, default 0.15s) is attributed to a cache
hit.  With no cache dir configured the attribution is ``None`` and the
field is omitted.

Math identity: the wrappers never touch values — they time the call and
read AOT analyses of the *same* lowering jax already cached.  Tests
assert bitwise-identical model state with the ledger on/off.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import stat as statmod
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

__all__ = [
    "AOT_MODES",
    "CompileEvent",
    "CostLedger",
    "RoundCosts",
    "round_cost_fields",
]

AOT_MODES = ("off", "lowered", "full")

# Dispatches faster than this that did NOT grow the persistent cache dir
# are attributed to a compile-cache hit (deserialization is ~10-100x
# faster than compilation).  Deliberately generous: a miss that compiles
# this fast costs nothing to misattribute.
DEFAULT_FAST_COMPILE_S = 0.15

_EPS_S = 1e-9


def _env_aot_mode() -> str:
    mode = os.environ.get("FEDTPU_COST_AOT", "").strip().lower()
    return mode if mode in AOT_MODES else "lowered"


@dataclasses.dataclass
class CompileEvent:
    """One observed compile (re-trace) of one jit site."""

    site: str
    seconds: float
    t_start: float
    t_end: float
    trace_count: int  # cumulative traces of this site; 1 == cold start
    cache_hit: Optional[bool] = None  # None == unattributable (no cache dir)
    costs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def record(self, **extra: Any) -> Dict[str, Any]:
        """Flatten to a schema-v6 ``compile`` record body (env fields —
        event/schema/run_id — are the recorder's job)."""
        rec: Dict[str, Any] = {
            "site": self.site,
            "compile_seconds": float(self.seconds),
            "t_start": float(self.t_start),
            "t_end": float(self.t_end),
            "trace_count": int(self.trace_count),
        }
        if self.cache_hit is not None:
            rec["cache_hit"] = bool(self.cache_hit)
        rec.update(self.costs)
        rec.update(extra)
        return rec


class RoundCosts(NamedTuple):
    """One :meth:`CostLedger.drain` window (one round / epoch)."""

    events: Tuple[CompileEvent, ...]
    flops: float  # executed cost-model FLOPs (sum over dispatches)
    bytes_accessed: float  # executed cost-model HLO bytes
    peak_bytes: int  # max per-program peak_device_bytes dispatched
    # host seconds inside the window's instrumented jitted calls (the
    # timer's own t1 - t0: enqueue, plus trace + compile when it compiles)
    dispatch_seconds: float = 0.0


def round_cost_fields(costs: RoundCosts, t_start: float,
                      seconds: float) -> Dict[str, Any]:
    """Schema-v6 round fields for one drained window.

    ``compile_seconds``/``cache_hit`` count only events inside the
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
        known = [e.cache_hit for e in in_window if e.cache_hit is not None]
        if known:
            out["cache_hit"] = all(known)
    if costs.flops > 0:
        out["flops_round"] = float(costs.flops)
    if costs.bytes_accessed > 0:
        out["hlo_bytes_accessed"] = float(costs.bytes_accessed)
    if costs.peak_bytes > 0:
        out["peak_device_bytes"] = int(costs.peak_bytes)
    if costs.dispatch_seconds > 0:
        # schema v15: every instrumented call drained with this window,
        # the ones of the block switch before the round included
        out["dispatch_seconds"] = float(costs.dispatch_seconds)
    return out


def _abstract_sig(args: tuple, kwargs: dict) -> Optional[tuple]:
    """Hashable (shape, dtype) signature of a call — keys the AOT memo so
    each (site, signature) pays for analysis once per process."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        sig = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None:
                sig.append((tuple(shape), str(dtype)))
            else:
                sig.append((type(leaf).__name__, repr(leaf)[:64]))
        return tuple(sig)
    except Exception:
        return None


class CostLedger:
    """Per-jit-site compile/cost recorder (see module docstring).

    Thread-compatibility: engines drive all instrumented dispatches from
    the round loop thread; the ledger is intentionally not locked.
    """

    def __init__(self, *, aot_mode: Optional[str] = None,
                 cache_dir: Optional[str] = None,
                 fast_compile_s: Optional[float] = None) -> None:
        self.aot_mode = aot_mode if aot_mode in AOT_MODES else _env_aot_mode()
        self.fast_compile_s = (
            float(os.environ.get("FEDTPU_COST_FAST_COMPILE_S",
                                 DEFAULT_FAST_COMPILE_S))
            if fast_compile_s is None else float(fast_compile_s))
        self._marks: Dict[str, int] = {}  # site -> traces so far
        self._site_costs: Dict[str, Dict[str, Any]] = {}  # site -> last AOT
        self._aot_memo: Dict[tuple, Dict[str, Any]] = {}
        self._events: list = []  # pending (drained per round)
        self.all_events: list = []  # full run history (bench / profile)
        self._exec_flops = 0.0
        self._exec_bytes = 0.0
        self._exec_peak = 0
        self._dispatch_s = 0.0
        self._cache_dir: Optional[str] = cache_dir
        self._cache_dir_resolved = cache_dir is not None
        self._cache_entries: Optional[int] = None

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

        @functools.wraps(jfn)
        def timed(*args: Any, **kwargs: Any) -> Any:
            n0 = marks.get(site, 0)
            # Async dispatch: no block_until_ready on purpose — the
            # window must cover trace+compile (and, summed into
            # dispatch_seconds, the enqueue), NOT device execution.
            t0 = time.perf_counter()  # graftlint: disable=JG104
            out = jfn(*args, **kwargs)
            t1 = time.perf_counter()  # graftlint: disable=JG104
            if marks.get(site, 0) != n0:
                self._on_compile(site, t0, t1, jfn, args, kwargs)
            self._on_dispatch(site, t1 - t0)
            return out

        timed.__wrapped_jit__ = jfn  # AOT access for tests/tools
        return timed

    # ---------------------------------------------------------- events

    def _on_compile(self, site: str, t0: float, t1: float, jfn: Callable,
                    args: tuple, kwargs: dict) -> None:
        hit = self._classify_cache(t1 - t0)
        costs = self._analyze(jfn, site, args, kwargs)
        if costs:
            self._site_costs[site] = costs
        if self.aot_mode == "full":
            # A full-mode AOT compile may itself persist a cache entry;
            # absorb it so the *next* event's delta is clean.
            self._cache_entries = self._scan_cache()
        ev = CompileEvent(site=site, seconds=t1 - t0, t_start=t0, t_end=t1,
                          trace_count=self._marks.get(site, 0),
                          cache_hit=hit, costs=dict(costs))
        self._events.append(ev)
        self.all_events.append(ev)

    def _on_dispatch(self, site: str, seconds: float) -> None:
        self._dispatch_s += seconds
        costs = self._site_costs.get(site)
        if not costs:
            return
        self._exec_flops += float(costs.get("flops", 0.0))
        self._exec_bytes += float(costs.get("hlo_bytes_accessed", 0.0))
        peak = costs.get("peak_device_bytes")
        if isinstance(peak, int) and peak > self._exec_peak:
            self._exec_peak = peak

    def drain(self) -> RoundCosts:
        """Hand the pending window to the caller and reset accumulators."""
        out = RoundCosts(events=tuple(self._events),
                         flops=self._exec_flops,
                         bytes_accessed=self._exec_bytes,
                         peak_bytes=self._exec_peak,
                         dispatch_seconds=self._dispatch_s)
        self._events = []
        self._exec_flops = 0.0
        self._exec_bytes = 0.0
        self._exec_peak = 0
        self._dispatch_s = 0.0
        return out

    # ------------------------------------------------------ aggregates

    def totals(self) -> Dict[str, Any]:
        evs = self.all_events
        hits = sum(1 for e in evs if e.cache_hit is True)
        misses = sum(1 for e in evs if e.cache_hit is False)
        return {
            "compile_events": len(evs),
            "compile_seconds": float(sum(e.seconds for e in evs)),
            "cache_hits": hits,
            "cache_misses": misses,
            "cache_unknown": len(evs) - hits - misses,
            "sites": len(self._marks),
        }

    def cache_hit_rate(self) -> Optional[float]:
        """Hit fraction over attributable events; None if none were."""
        hits = sum(1 for e in self.all_events if e.cache_hit is True)
        misses = sum(1 for e in self.all_events if e.cache_hit is False)
        if hits + misses == 0:
            return None
        return hits / (hits + misses)

    # ------------------------------------------------- cache hit/miss

    def _resolve_cache_dir(self) -> Optional[str]:
        if not self._cache_dir_resolved:
            self._cache_dir_resolved = True
            try:
                import jax

                self._cache_dir = jax.config.jax_compilation_cache_dir
            except Exception:
                self._cache_dir = None
        return self._cache_dir

    def _scan_cache(self) -> Optional[int]:
        cache_dir = self._resolve_cache_dir()
        if not cache_dir:
            return None
        try:
            count = 0
            for name in os.listdir(cache_dir):
                try:
                    st = os.stat(os.path.join(cache_dir, name))
                except OSError:
                    continue
                if statmod.S_ISREG(st.st_mode):
                    count += 1
            return count
        except OSError:
            return None

    def _classify_cache(self, seconds: float) -> Optional[bool]:
        before = self._cache_entries
        now = self._scan_cache()
        self._cache_entries = now
        if now is None:
            return None  # no persistent cache configured -> omit
        if before is not None and now > before:
            return False  # a fresh entry was persisted -> genuine miss
        return seconds <= self.fast_compile_s

    # -------------------------------------------------------- AOT cost

    def _analyze(self, jfn: Callable, site: str, args: tuple,
                 kwargs: dict) -> Dict[str, Any]:
        if self.aot_mode == "off":
            return {}
        sig = _abstract_sig(args, kwargs)
        key = (site, sig) if sig is not None else None
        if key is not None and key in self._aot_memo:
            return dict(self._aot_memo[key])
        out: Dict[str, Any] = {}
        try:
            lowered = jfn.lower(*args, **kwargs)
        except Exception:
            return out
        self._merge_cost_analysis(out, lowered)
        if self.aot_mode == "full":
            self._merge_compiled(out, lowered)
        if key is not None:
            self._aot_memo[key] = dict(out)
        return out

    @staticmethod
    def _merge_cost_analysis(out: Dict[str, Any], analyzable: Any) -> None:
        """Pull flops / bytes-accessed / transcendentals out of a
        ``cost_analysis()`` result.  jax returns a dict (Lowered) or a
        per-device list of dicts (Compiled, some versions)."""
        try:
            ca = analyzable.cost_analysis()
        except Exception:
            return
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            return
        for src, dst in (("flops", "flops"),
                         ("bytes accessed", "hlo_bytes_accessed"),
                         ("transcendentals", "transcendentals")):
            val = ca.get(src)
            if isinstance(val, (int, float)) and not isinstance(val, bool) \
                    and val == val and val >= 0:  # NaN-safe
                out[dst] = float(val)

    @classmethod
    def _merge_compiled(cls, out: Dict[str, Any], lowered: Any) -> None:
        try:
            compiled = lowered.compile()
        except Exception:
            return
        cls._merge_cost_analysis(out, compiled)  # optimized-HLO numbers
        try:
            mem = compiled.memory_analysis()
        except Exception:
            return
        if mem is None:
            return
        total = 0
        have_any = False
        for attr, dst in (("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          ("temp_size_in_bytes", "temp_bytes"),
                          ("generated_code_size_in_bytes",
                           "generated_code_bytes")):
            val = getattr(mem, attr, None)
            if isinstance(val, (int, float)) and not isinstance(val, bool) \
                    and val >= 0:
                out[dst] = int(val)
                have_any = True
                if dst != "generated_code_bytes":
                    total += int(val)
        if have_any and total > 0:
            # Live-footprint estimate while the program runs: arguments
            # + outputs + XLA temporaries (code size excluded).
            out["peak_device_bytes"] = total
