"""What a per-layer metric's reader is given: the run's round records
and counters (:class:`Records`) and, in a traced run, the reduced device
trace (:class:`TraceView`).  A reader is a module
``benchmarks/metrics/<metric>.py`` with ``UNIT`` and
``read(records, trace, cell) -> float | None``; None leaves the metric
out of the result line.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.lib import xplane
from benchmarks.lib.window import TRACED_PASS, Pass, Window


@dataclasses.dataclass
class Records:
    warmup: List[Dict[str, Any]]
    passes: List[Pass]
    samples_per_round: int
    chips: int
    #: run-level counts: ``cache_entries_added``, ``peak_hbm_bytes``,
    #: ``scratch_reserved_bytes``, ``traced_sps_chip``
    counters: Dict[str, float]

    def timed(self, traced: Optional[bool] = None) -> List[Pass]:
        """The window's passes; ``traced=False`` leaves the profiled one
        out (the profiler slows the host)."""
        return [p for p in self.passes
                if traced is None or p.traced == traced]

    def rounds(self, traced: Optional[bool] = None) -> List[Dict[str, Any]]:
        return [r for p in self.timed(traced) for r in p.records]

    def share_pct(self, key: str) -> Optional[float]:
        """100 x sum of ``key`` over sum of ``round_seconds``, over the
        window's rounds outside the profiled pass, or None where no
        record has ``key``."""
        rounds = [r for r in self.rounds(traced=False) if key in r]
        total = sum(r["round_seconds"] for r in rounds)
        if not rounds or total <= 0:
            return None
        return 100.0 * sum(r[key] for r in rounds) / total


def throughput(passes: List[Pass], samples_per_pass: int, chips: int
               ) -> Optional[float]:
    """Samples per second per chip over whole passes."""
    seconds = sum(p.seconds for p in passes)
    if not passes or seconds <= 0:
        return None
    return len(passes) * samples_per_pass / seconds / chips


@dataclasses.dataclass
class TraceView:
    """The traced pass: device ops per chip, the pass's interval on the
    profiler's clock, and the engine's host spans put on that clock."""

    devices: Dict[str, List[xplane.Op]]
    window: Tuple[float, float]
    spans: List[xplane.Span]
    device_kind: str

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds an op ran, averaged over the chips."""
        per = [xplane.busy_ns(xplane.leaf_ops(ops), *self.window) / 1e9
               for ops in self.devices.values()]
        return sum(per) / len(per)


def engine_spans(obs_path: Optional[str]) -> List[Tuple[str, float, float, Any]]:
    """``(name, t0, t1, block)`` of the engine's own spans and rounds from
    its obs JSONL, in ``time.perf_counter`` seconds.  Rounds are named
    ``round``; ``block`` is the round's block (None on a phase span)."""
    out = []
    if not obs_path:
        return out
    with open(obs_path) as f:
        for line in f:
            rec = json.loads(line)
            t0, t1 = rec.get("t_start"), rec.get("t_end")
            if not isinstance(t0, (int, float)) or not isinstance(
                    t1, (int, float)):
                continue
            if rec.get("event") == "span" and rec.get("cat") in ("phase",
                                                                 "comm"):
                out.append((rec["name"], float(t0), float(t1), None))
            elif rec.get("event") == "round":
                out.append(("round", float(t0), float(t1),
                            (rec.get("model"), rec.get("block"))))
    return out


def trace_view(window: Window, obs_path: Optional[str], device_kind: str
               ) -> Optional[TraceView]:
    path = xplane.find_xplane(window.trace_dir) if window.trace_dir else None
    if path is None:
        return None
    trace = xplane.load(path)
    win = xplane.window_of(trace, TRACED_PASS)
    if win is None or not trace.devices:
        return None
    # perf_counter -> profiler clock: the traced pass's annotation was
    # entered at perf_counter ``traced_t0`` and starts at ``win[0]`` ns
    to_ns = lambda t: win[0] + (t - window.traced_t0) * 1e9
    recorded = engine_spans(obs_path)
    rounds = sorted((s for s in recorded if s[0] == "round"),
                    key=lambda s: s[1])
    spans = [xplane.Span(name, to_ns(t0), to_ns(t1))
             for name, t0, t1, _ in recorded if name != "round"]
    # what lies between two rounds: the engine's per-block set-up where
    # the block changes, its record keeping and the harness otherwise
    for prev, nxt in zip(rounds, rounds[1:]):
        name = ("block switch" if prev[3] != nxt[3] else "between rounds")
        spans.append(xplane.Span(name, to_ns(prev[2]), to_ns(nxt[1])))
    spans = [s for s in spans if s.end_ns > win[0] and s.start_ns < win[1]]
    return TraceView(trace.devices, win, spans, device_kind)


def breakdown(view: TraceView, n_ops: int = 10, n_gaps: int = 5
              ) -> Dict[str, List[List[Any]]]:
    """The device ops with most time (summed over the chips, containers
    such as ``while`` left out) and the longest idle gaps of the busiest
    chip's timeline, each named by the host span that covers most of it."""
    acc: Dict[str, float] = {}
    for ops in view.devices.values():
        for name, sec in xplane.op_table(xplane.leaf_ops(ops), *view.window):
            acc[name] = acc.get(name, 0.0) + sec
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n_ops]
    first = next(iter(view.devices.values()))
    gaps = xplane.idle_gaps(xplane.leaf_ops(first), *view.window)[:n_gaps]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[xplane.attribute(g, view.spans),
                           (g[1] - g[0]) / 1e9] for g in gaps]}
