"""Device time by the program's ``jax.named_scope``: which of the
traced pass's device ops belong to ``gdn_scan``, ``moe_experts``,
``moe_route``, ...

The v5e trace names an event by its HLO instruction's text and carries
the instruction's ``op_name`` (the JAX path with its scopes,
``jit(train_epoch)/.../gdn/gdn_scan/dot_general``) as the stat ``tf_op``
of the event's metadata, in the same table ``xplane.op_categories``
reads ``hlo_category`` from.  A scope owns an op whose path holds its
name; the innermost listed scope wins (``gdn_scan`` before ``gdn``).
The TPU compiler replaces a ragged product by a grouped Mosaic kernel
whose metadata it writes itself (``ragged-dot-...``): those are the
experts' products, whatever path they came from.

Where the trace has no such stat (another runtime), or the program has
no such scope (the parent commit), nothing is found and a reader
returns None.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional

from benchmarks.lib import xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: innermost first
SCOPES = ("gdn_scan", "moe_experts", "moe_route", "moe_shared",
          "lm_head_loss", "gated_attn", "gdn")
RAGGED_STEM = "ragged-dot"


def scope_of(op_path: str, instruction: str = "") -> str:
    """The scope that owns an op with JAX path ``op_path`` (``""``: none)."""
    if op_path.startswith(RAGGED_STEM) or instruction.startswith(RAGGED_STEM):
        return "moe_experts"
    for s in SCOPES:
        if s in op_path:
            return s
    return ""


@dataclasses.dataclass(frozen=True)
class ScopedOp:
    op: xplane.Op
    scope: str


def event_stat(path: str, stat: str) -> Dict[str, Dict[str, str]]:
    """``{plane: {event name: value}}`` of the string stat ``stat`` in
    the planes' event metadata (fields as in ``xplane.op_categories``)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, wt, plane in xplane._fields(space):
        if num != 1 or wt != 2:
            continue
        name, events, stat_names = "", [], {}
        for pn, pw, val in xplane._fields(plane):
            if pn == 2 and pw == 2:
                name = bytes(val).decode()
            elif pn == 4 and pw == 2:
                events.extend(v for k, w, v in xplane._fields(val)
                              if k == 2 and w == 2)
            elif pn == 5 and pw == 2:
                sid, sname = 0, ""
                for k, w, v in xplane._fields(val):
                    if k == 1 and w == 0:
                        sid = v
                    elif k == 2 and w == 2:
                        for mk, mw, mv in xplane._fields(v):
                            if mk == 2 and mw == 2:
                                sname = bytes(mv).decode()
                stat_names[sid] = sname
        if not name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        want = next((i for i, n in stat_names.items() if n == stat), None)
        found: Dict[str, str] = {}
        for meta in events:
            ev_name, value = "", ""
            for k, w, v in xplane._fields(meta):
                if k == 2 and w == 2:
                    ev_name = bytes(v).decode()
                elif k == 5 and w == 2:
                    st = {sk: sv for sk, _, sv in xplane._fields(v)}
                    if st.get(1) == want:
                        if 5 in st:
                            value = bytes(st[5]).decode()
                        elif 7 in st:
                            value = stat_names.get(st[7], "")
            if value:
                found[ev_name] = value
        out[name] = found
    return out


def load(path: str) -> Dict[str, List[ScopedOp]]:
    """Every device's executed ops with their scope."""
    from jax.profiler import ProfileData

    paths = event_stat(path, "tf_op")
    cats = xplane.op_categories(path)
    out: Dict[str, List[ScopedOp]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                name = xplane.op_name(ev.name)
                op = xplane.Op(name, float(ev.start_ns),
                               float(ev.duration_ns),
                               cats.get(plane.name, {}).get(ev.name, ""),
                               xplane.PALLAS_TARGET in ev.name)
                ops.append(ScopedOp(op, scope_of(
                    paths.get(plane.name, {}).get(ev.name, ""), name)))
        out[plane.name] = ops
    return out


_LOADED: Dict[str, Dict[str, List[ScopedOp]]] = {}


def of_cell(cell) -> Optional[Dict[str, List[ScopedOp]]]:
    """The scoped ops of the cell's traced pass (``run.py`` writes the
    trace under ``out/<cell>/trace``), or None without a trace.  Read
    once per file; the first reading prints every scope's device seconds
    over the whole trace as ``scope_seconds={...}`` (first chip)."""
    path = xplane.find_xplane(os.path.join(BENCH, "out", cell.name, "trace"))
    if path is None:
        return None
    if path not in _LOADED:
        _LOADED[path] = load(path)
        first = next(iter(_LOADED[path].values()), [])
        t0 = min((o.op.start_ns for o in first), default=0.0)
        t1 = max((o.op.end_ns for o in first), default=0.0)
        print("scope_seconds=" + json.dumps({
            s or "none": round(scope_ns(first, s, t0, t1) / 1e9, 4)
            for s in SCOPES + ("",)}))
    return _LOADED[path]


def scope_ns(ops: List[ScopedOp], scope: str, t0: float, t1: float) -> float:
    """Device time inside ``[t0, t1]`` in which an op of ``scope`` ran
    (containers such as ``while`` left out: they wrap their body)."""
    return xplane.busy_ns([o.op for o in ops if o.scope == scope
                           and not xplane.is_container(o.op)], t0, t1)


def busy_share_pct(cell, trace, scope: str) -> Optional[float]:
    """100 x the scope's device time over the device's busy time in the
    traced pass, averaged over the chips; None where the scope has no op."""
    if trace is None:
        return None
    scoped = of_cell(cell)
    if not scoped:
        return None
    t0, t1 = trace.window
    shares = []
    for ops in scoped.values():
        busy = xplane.busy_ns(xplane.leaf_ops(o.op for o in ops), t0, t1)
        mine = scope_ns(ops, scope, t0, t1)
        if busy <= 0 or mine <= 0:
            return None
        shares.append(mine / busy)
    return 100.0 * sum(shares) / len(shares)


def roofline_pct(cell, trace, records, scope: str, work) -> Optional[float]:
    """100 x the least time the chip could take for the traced pass's
    work in ``scope`` (the larger of operations / peak and bytes /
    bandwidth; ``work(config, **round) -> (operations, bytes)``) over the
    scope's device time, worst chip."""
    from benchmarks.lib import lm_work, peaks

    if trace is None:
        return None
    scoped = of_cell(cell)
    rounds = records.rounds(traced=True)
    if not scoped or not rounds:
        return None
    peak = peaks.peaks_for(trace.device_kind)
    flops = bytes_ = 0.0
    for rec in rounds:
        f, b = work(cell.config, lm_work.round_of(cell, rec))
        flops, bytes_ = flops + f, bytes_ + b
    least = max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    t0, t1 = trace.window
    took = max(scope_ns(ops, scope, t0, t1) for ops in scoped.values()) / 1e9
    # the rounds' work is spread over the chips
    return None if took <= 0 else 100.0 * least / len(scoped) / took
