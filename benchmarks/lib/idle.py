"""The chip's idle time in the traced pass, totalled by the host span
that lies over it.

``records.breakdown`` names the five longest idle gaps; this adds up ALL
of them.  The gaps of the chip with most idle (the chip
``device_idle_pct`` reports) are cut at the borders of the host spans;
each piece goes to the innermost span over it, which is the shortest of
those that cover it, and what no span covers to ``unattributed``.  The
spans are the engine's own (``stage``, ``train``, ``comm``, ``sync``,
``block_switch`` and its parts, ``round_tail``, ...) plus the two that
``records.trace_view`` makes up from the holes between round records
(``block switch``, ``between rounds``): those are the outermost, so they
catch only what the program's spans leave, and on a program that stamps
neither switch nor tail they catch all of it.

The four ``idle_*_pct`` readers each sum one group of names from
:func:`idle_by_span`; together they add up to ``device_idle_pct``.
"""

from __future__ import annotations

import bisect
import json
import math
from typing import Dict, Iterable, Optional, Sequence, Tuple

from benchmarks.lib import xplane

UNATTRIBUTED = "unattributed"

#: which span names each ``idle_*_pct`` metric adds up
GROUPS = {
    "block_switch": ("block_switch", "build_fns", "block_size", "block_vars",
                     "init_opt", "block switch"),
    "round_tail": ("round_tail", "between rounds"),
    "in_round": ("stage", "train", "comm", "sync", "overlap",
                 "overlap_dispatch"),
    "unattributed": (UNATTRIBUTED,),
}


def split_by_span(gaps: Iterable[Tuple[float, float]],
                  spans: Sequence[xplane.Span]) -> Dict[str, float]:
    """Length of ``gaps`` under each span name (same unit as the gaps).

    Between two neighbouring span borders the set of spans that cover the
    stretch is constant, so its owner is found once; a gap is then handed
    out over the stretches it crosses."""
    # the infinite ends make every gap lie between two cuts; no span
    # covers the first and the last stretch
    cuts = sorted({-math.inf, math.inf,
                   *(t for s in spans for t in (s.start_ns, s.end_ns))})
    owners = []
    for lo, hi in zip(cuts, cuts[1:]):
        over = [s for s in spans if s.start_ns <= lo and s.end_ns >= hi]
        owners.append(min(over, key=lambda s: s.end_ns - s.start_ns).name
                      if over else UNATTRIBUTED)
    out: Dict[str, float] = {}
    for a, b in gaps:
        i = bisect.bisect_right(cuts, a) - 1
        while cuts[i] < b:
            piece = min(b, cuts[i + 1]) - max(a, cuts[i])
            if piece > 0:
                out[owners[i]] = out.get(owners[i], 0.0) + piece
            i += 1
    return out


def most_idle_chip(view) -> Sequence[Tuple[float, float]]:
    """The idle gaps of the chip whose gaps add up to most."""
    per_chip = [xplane.idle_gaps(xplane.leaf_ops(ops), *view.window)
                for ops in view.devices.values()]
    return max(per_chip, key=xplane.total)


def idle_by_span(view) -> Dict[str, float]:
    """Seconds of idle per span name, on the chip with most idle.
    Computed once per view; the first call prints the whole table, so a
    run's output holds every name and not only the four groups."""
    table = getattr(view, "_idle_by_span", None)
    if table is None:
        table = {name: ns / 1e9 for name, ns in
                 split_by_span(most_idle_chip(view), view.spans).items()}
        view._idle_by_span = table
        print("idle_by_span=" + json.dumps(
            dict(sorted(table.items(), key=lambda kv: -kv[1]))))
    return table


def group_pct(view, group: str) -> Optional[float]:
    """100 x idle seconds under the spans of ``group`` over the traced
    pass; None without a device trace."""
    if view is None:
        return None
    table = idle_by_span(view)
    return 100.0 * sum(table.get(n, 0.0) for n in GROUPS[group]) \
        / view.window_s
