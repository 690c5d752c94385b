"""Reduce a JAX profiler trace (``*.xplane.pb``) to the benchmark's
device numbers: busy union, idle gaps, op table, collective overlap.

The file is read with ``jax.profiler.ProfileData`` (plus one table from
the raw file, below); no other package is needed.
Everything after :func:`load` works on plain lists of :class:`Op` and
:class:`Span`, so the arithmetic is tested on hand-written cases
(``benchmarks/tests``) as well as on the recorded trace under
``benchmarks/testdata``.

Trace layout this reads (TPU v5 lite, jax 0.9 / libtpu 0.0.34, looked
at by hand in PR 22): one plane ``/device:TPU:<n>`` per chip whose line
``XLA Ops`` holds one event per executed HLO instruction, named by the
instruction's whole text (``%fusion.3 = f32[...] fusion(...), kind=...``),
with start and duration in ns since the start of the trace; and one
plane ``/host:CPU`` whose thread lines hold the ``TraceAnnotation`` spans
the harness writes.  The host and device planes share a time base to
within a millisecond or two (a device op was seen to start 1.1 ms
"before" the host span that dispatched it), so nothing here resolves
host-to-device order below that.

What kind of op an event is (``convolution fusion``, ``all-reduce``, ...)
is the profiler's own ``hlo_category``.  It sits in the plane's event
metadata, which ``ProfileData`` does not expose, so :func:`op_categories`
reads that one table from the file's protobuf wire format directly.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

#: HLO op-name stems that move data between chips
COLLECTIVE_STEMS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast")


#: how a Pallas kernel appears in an HLO instruction's text
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def op_name(hlo_text: str) -> str:
    """``fusion.3`` from ``%fusion.3 = f32[8]{0} fusion(...), kind=...``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class Op:
    """One executed device op."""

    name: str
    start_ns: float
    dur_ns: float
    category: str = ""
    pallas: bool = False

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    """One host interval (a ``TraceAnnotation`` or an engine span put on
    the profiler's clock)."""

    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]
    host: List[Span]


# ----------------------------------------------------------------------
# hlo_category, from the raw file
# ----------------------------------------------------------------------
def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    """``(value, next index)`` of the varint at ``buf[i]``."""
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: memoryview):
    """``(field number, wire type, value)`` of one protobuf message:
    varints as ints, length-delimited fields as memoryviews, fixed-width
    fields as raw memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
            yield num, wt, val
        elif wt == 2:
            ln, i = _varint(buf, i)
            yield num, wt, buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            width = 8 if wt == 1 else 4
            yield num, wt, buf[i:i + width]
            i += width
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")


def op_categories(path: str) -> Dict[str, Dict[str, str]]:
    """``{plane name: {event name: hlo_category}}`` from the XSpace file.

    Fields read (tsl ``xplane.proto``): ``XSpace.planes = 1``;
    ``XPlane.name = 2``, ``.event_metadata = 4``, ``.stat_metadata = 5``
    (both maps: key 1, value 2); ``XEventMetadata.name = 2``,
    ``.stats = 5``; ``XStatMetadata.name = 2``; ``XStat.metadata_id = 1``,
    ``.str_value = 5``, ``.ref_value = 7`` (an id into ``stat_metadata``)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, wt, plane in _fields(space):
        if num != 1 or wt != 2:
            continue
        name, events, stat_names = "", [], {}
        for pn, pw, val in _fields(plane):
            if pn == 2 and pw == 2:
                name = bytes(val).decode()
            elif pn == 4 and pw == 2:
                events.extend(v for k, w, v in _fields(val)
                              if k == 2 and w == 2)
            elif pn == 5 and pw == 2:
                sid, sname = 0, ""
                for k, w, v in _fields(val):
                    if k == 1 and w == 0:
                        sid = v
                    elif k == 2 and w == 2:
                        for mk, mw, mv in _fields(v):
                            if mk == 2 and mw == 2:
                                sname = bytes(mv).decode()
                stat_names[sid] = sname
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        cat_id = next((i for i, n in stat_names.items()
                       if n == "hlo_category"), None)
        cats: Dict[str, str] = {}
        for meta in events:
            ev_name, cat = "", ""
            for k, w, v in _fields(meta):
                if k == 2 and w == 2:
                    ev_name = bytes(v).decode()
                elif k == 5 and w == 2:
                    stat = {sk: sv for sk, _, sv in _fields(v)}
                    if stat.get(1) == cat_id:
                        if 5 in stat:
                            cat = bytes(stat[5]).decode()
                        elif 7 in stat:
                            cat = stat_names.get(stat[7], "")
            if cat:
                cats[ev_name] = cat
        out[name] = cats
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output dir."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str, host_prefix: str = "bench_") -> Trace:
    """Device ops of every TPU plane and the host annotations whose name
    starts with ``host_prefix`` (everything else on the host plane is the
    runtime's own threads: thousands of events nothing here reads)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    categories = op_categories(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = []
            cats = categories.get(plane.name, {})
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(op_name(ev.name), float(ev.start_ns),
                                  float(ev.duration_ns),
                                  cats.get(ev.name, ""),
                                  PALLAS_TARGET in ev.name))
            ops.sort(key=lambda o: o.start_ns)
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        host.append(Span(ev.name, float(ev.start_ns),
                                         float(ev.start_ns)
                                         + float(ev.duration_ns)))
    host.sort(key=lambda s: s.start_ns)
    return Trace(devices, host)


# ----------------------------------------------------------------------
# interval arithmetic
# ----------------------------------------------------------------------
def _clip(intervals: Iterable[Tuple[float, float]], t0: float, t1: float
          ) -> List[Tuple[float, float]]:
    out = []
    for a, b in intervals:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def busy_intervals(ops: Iterable[Op], t0: float, t1: float
                   ) -> List[Tuple[float, float]]:
    """Union of the op intervals inside ``[t0, t1]``."""
    return merge(_clip(((o.start_ns, o.end_ns) for o in ops), t0, t1))


def busy_ns(ops: Iterable[Op], t0: float, t1: float) -> float:
    return total(busy_intervals(ops, t0, t1))


def idle_gaps(ops: Iterable[Op], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """Intervals of ``[t0, t1]`` in which no op ran, longest first."""
    gaps = subtract([(t0, t1)], busy_intervals(ops, t0, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def op_table(ops: Iterable[Op], t0: float, t1: float
             ) -> List[Tuple[str, float]]:
    """``[(name, seconds)]`` summed by op name over ``[t0, t1]``, most
    time first.  Durations are summed as recorded (an op nested inside
    another, such as a fusion inside a while loop, counts under both
    names: the table ranks names, it does not add up to busy time)."""
    acc: Dict[str, float] = {}
    for o in ops:
        a, b = max(o.start_ns, t0), min(o.end_ns, t1)
        if b > a:
            acc[o.name] = acc.get(o.name, 0.0) + (b - a)
    return sorted(((n, ns / 1e9) for n, ns in acc.items()),
                  key=lambda kv: -kv[1])


def is_collective(op: Op) -> bool:
    return (op.name.startswith(COLLECTIVE_STEMS)
            or op.category.startswith(COLLECTIVE_STEMS))


def is_convolution(op: Op) -> bool:
    """A convolution or a fusion around one, by the profiler's own
    ``hlo_category`` (the name alone does not say: XLA called a fused
    3x3 convolution ``convert_reduce_fusion``)."""
    return "convolution" in op.category


def is_container(op: Op) -> bool:
    """Control-flow ops whose interval only wraps their body's ops."""
    return (op.name.startswith(("while", "conditional", "call"))
            or op.category in ("while", "conditional", "call"))


def category_ns(ops: Iterable[Op], pred, t0: float, t1: float) -> float:
    """Union time of the ops that satisfy ``pred`` inside ``[t0, t1]``."""
    return busy_ns((o for o in ops if pred(o)), t0, t1)


def collective_exposed_ns(ops: Sequence[Op], t0: float, t1: float) -> float:
    """Time inside ``[t0, t1]`` in which a collective ran on this device
    and no other op did (containers such as ``while`` left out: they only
    wrap their body)."""
    coll = busy_intervals((o for o in ops if is_collective(o)), t0, t1)
    other = busy_intervals((o for o in ops if not is_collective(o)
                            and not is_container(o)), t0, t1)
    return total(subtract(coll, other))


# ----------------------------------------------------------------------
# host attribution
# ----------------------------------------------------------------------
def attribute(gap: Tuple[float, float], spans: Sequence[Span],
              default: str = "unattributed") -> str:
    """Name of the host span that covers most of ``gap``; of two that
    cover as much (a span nested in another), the shorter."""
    best, best_key = default, (0.0, 0.0)
    for s in spans:
        ov = min(gap[1], s.end_ns) - max(gap[0], s.start_ns)
        key = (ov, s.start_ns - s.end_ns)
        if ov > 0 and key > best_key:
            best, best_key = s.name, key
    return best


def window_of(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    """``[start, end]`` of the host annotation ``name`` (the traced pass)."""
    for s in trace.host:
        if s.name == name:
            return (s.start_ns, s.end_ns)
    return None


def leaf_ops(ops: Iterable[Op]) -> List[Op]:
    return [o for o in ops if not is_container(o)]
