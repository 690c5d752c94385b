"""Device time of a traced pass as the tree of the program's scopes.

The program declares its ``jax.named_scope`` names once
(``federated_pytorch_test_tpu/obs/scopes.py``); a device trace carries,
with every executed instruction, the JAX path it was traced under
(``scopes.event_stat(path, "tf_op")``).  This module matches whole path
*segments* against that table and adds the time up by the chain of
scopes an op lies in, so that every scope has its seconds and its self
seconds (its own less its children's), each split by direction:

- ``forward``: the primal pass;
- ``remat``: a rematerialised forward (``rematted_computation`` in the
  path);
- ``backward``: everything else under a ``transpose(...)``.

How a path is read (:func:`parse`): segments are split at the slashes
outside brackets, the transforms JAX wraps around a name are peeled
(``transpose(jvp(model_loss))`` is ``model_loss``), and only names of the
table count.  A backward path holds its scopes twice (the transposed
equation's, then the traced rule's), so a name that is already in the
chain cuts the chain back to it.  JAX lifts what does not depend on a
loop's carry out of the loop and the lifted op keeps only the inner part
of its path (``.../gdn/sublayer_norm/add`` without ``sublayer_mixer``
before it): a chain that starts in the middle of the tree is put where
the table's parents say it belongs (:func:`canonical`; the first
declared parent where there are two).

Ops the compiler made carry a path the compiler chose (a ``while`` that
some scope opened, or none).  Where the program's table names no scope
in it, a written rule may (:func:`rule_of`): ``xla_ragged_dot`` (the
grouped Mosaic kernels of ``jax.lax.ragged_dot``, which carry no path at
all; :func:`scope_seconds` counts them to ``moe_experts`` as the kernel
readers do), ``xla_async_copy`` (``copy-start`` / ``copy-done`` /
``slice-start`` / ... ), ``xla_fill`` (bare ``broadcast``
instructions).  What neither names is the unnamed remainder, listed by
instruction.  ``kinds`` gives the same three rules over ALL ops, named
or not.

Time is handed out once: leaf ops only (containers such as ``while``
wrap their body, as in ``scopes.scope_ns``), and an instant in which two
leaf ops ran goes to the one that started first.  The parts therefore
add up to ``xplane.busy_ns`` of the leaf ops exactly.

The table of a finished run (``benchmarks/out/<cell>/trace``, or any
``jax.profiler`` directory, or the rows an earlier call dumped)::

    python3 -m benchmarks.lib.scope_tree --workload <cell> \\
        [--under <scope>] [--by direction|instruction] [--top 25] \\
        [--trace-dir <dir> | --rows <file>] [--dump <file>]

``--dump`` writes the pass as rows ``[path, instruction, category,
seconds, events]`` (a megabyte or two where the trace is tens): enough
to ask every question above again without the trace, on another machine.

Reading the trace reuses ``scopes.load`` (it resolves each event through
the module's ``scope_of``; handed the identity it keeps the whole path).
On a checkout without the table (a parent commit) there is no name to
match and every reader returns None.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

if __package__ in (None, ""):           # python3 benchmarks/lib/scope_tree.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib import scopes, xplane  # noqa: E402

try:
    from federated_pytorch_test_tpu.obs.scopes import SCOPES
except ImportError:                     # a parent commit: nothing to match
    SCOPES = ()

NAMES = frozenset(s.name for s in SCOPES)
PARENTS: Dict[str, Tuple[str, ...]] = {s.name: s.parents for s in SCOPES}
DIRECTIONS = ("forward", "remat", "backward")
BUCKETS = ("xla_ragged_dot", "xla_async_copy", "xla_fill")
_ASYNC = ("-start", "-done")
_WRAPPED = re.compile(r"^([\w.<>-]+)\((.*)\)$", re.S)
_NUMBERED = re.compile(r"(\.\d+|\.clone|\.remat\d*)+$")


# ----------------------------------------------------------------------
# one path
# ----------------------------------------------------------------------
def _split(path: str) -> List[str]:
    """``path`` at the slashes (and the semicolons XLA joins two ops'
    names with) that lie outside every bracket."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(depth - 1, 0)
        if ch in "/;" and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return [s for s in out if s]


def segments(op_path: str) -> Tuple[List[str], List[str]]:
    """``(names, transforms)`` of a ``tf_op`` path: every segment with
    JAX's transforms peeled off, and the transforms met on the way."""
    names, transforms = [], []
    # the stat is ``<path>:<op type>``, the type mostly empty
    head, colon, tail = op_path.rpartition(":")
    todo = _split(head if colon and "/" not in tail else op_path)
    while todo:
        seg = todo.pop(0)
        m = _WRAPPED.match(seg)
        if m:
            transforms.append(m.group(1))
            todo = _split(m.group(2)) + todo
        else:
            names.append(seg)
    return names, transforms


def canonical(chain: Tuple[str, ...]) -> Tuple[str, ...]:
    """``chain`` with the ancestors the table declares put before a name
    that JAX lifted out of them (first declared parent)."""
    out: List[str] = []
    for name in chain:
        lifted_from: List[str] = []     # ancestors missing before `name`
        at = name
        while PARENTS.get(at) and not (out and out[-1] in PARENTS[at]):
            held = next((p for p in PARENTS[at] if p in out), None)
            if held is not None:
                del out[out.index(held) + 1:]
                break
            at = PARENTS[at][0]
            if at == name or at in lifted_from:     # a table that loops
                break
            lifted_from.append(at)
        out.extend(reversed(lifted_from))
        out.append(name)
    return tuple(out)


def raw_chain(names: Iterable[str]) -> Tuple[str, ...]:
    """The table's scopes among a path's ``names`` as the path has them:
    a name met again cuts the chain back to it."""
    chain: List[str] = []
    for seg in names:
        if seg in NAMES:
            if seg in chain:
                del chain[chain.index(seg) + 1:]
            else:
                chain.append(seg)
    return tuple(chain)


@functools.lru_cache(maxsize=1 << 16)       # a pass repeats its paths
def parse(op_path: str) -> Tuple[Tuple[str, ...], str]:
    """``(chain of table scopes, outermost first; direction)``."""
    names, transforms = segments(op_path)
    if "rematted_computation" in names:
        direction = "remat"
    elif "transpose" in transforms:
        direction = "backward"
    else:
        direction = "forward"
    return canonical(raw_chain(names)), direction


def rule_of(op: xplane.Op, op_path: str = "") -> str:
    """The written rule that names a compiler-made op (``""``: none)."""
    if op.name.startswith(scopes.RAGGED_STEM) \
            or op_path.startswith(scopes.RAGGED_STEM):
        return "xla_ragged_dot"
    if xplane.is_collective(op):
        return ""
    stem = _NUMBERED.sub("", op.name)
    if op.category.endswith(_ASYNC) or stem.endswith(_ASYNC):
        return "xla_async_copy"
    if op.category == "broadcast":
        return "xla_fill"
    return ""


class Leaf(NamedTuple):
    op: xplane.Op
    path: str
    chain: Tuple[str, ...]
    direction: str
    rule: str
    events: int = 1                     # what a dumped row stands for

    @property
    def bucket(self) -> str:
        """Where the leaf's time goes when no scope of the table owns
        it: a rule's bucket, or ``""`` for the unnamed remainder."""
        return "" if self.chain else self.rule


def leaves(ops: Iterable[Tuple[xplane.Op, str]]) -> List[Leaf]:
    """The leaf ops of ``(op, tf_op path)`` pairs, classified."""
    out = []
    for op, path in ops:
        if xplane.is_container(op):
            continue
        chain, direction = parse(path)
        out.append(Leaf(op, path, chain, direction, rule_of(op, path)))
    return out


# ----------------------------------------------------------------------
# the tree of one chip
# ----------------------------------------------------------------------
def shares(found: Iterable[Leaf], t0: float, t1: float
           ) -> List[Tuple[Leaf, float]]:
    """``(leaf, ns)``: the window's busy time handed out once, an
    instant to the op that started first."""
    out, end = [], t0
    for leaf in sorted(found, key=lambda f: f.op.start_ns):
        a, b = max(leaf.op.start_ns, end), min(leaf.op.end_ns, t1)
        if b > a:
            out.append((leaf, b - a))
            end = b
    return out


def _add(acc: Dict, key, sec: float, events: int) -> None:
    row = acc.setdefault(key, [0.0, 0])
    row[0] += sec
    row[1] += events


@functools.lru_cache(maxsize=1 << 12)
def _node_keys(chain: Tuple[str, ...]) -> Tuple[str, ...]:
    """``a``, ``a/b``, ``a/b/c`` for the chain ``(a, b, c)``."""
    return tuple("/".join(chain[:depth])
                 for depth in range(1, len(chain) + 1))


def tree_of(found: Iterable[Leaf], t0: float, t1: float, top: int = 10
            ) -> Dict:
    """The scope tree of one chip's leaves over ``[t0, t1]`` (seconds;
    ``nodes`` maps ``a/b/c`` to ``{"s": [...], "self": [...]}`` by
    :data:`DIRECTIONS`)."""
    nodes: Dict[str, Dict[str, List[float]]] = {}
    xla = dict.fromkeys(BUCKETS, 0.0)
    kinds = dict.fromkeys(BUCKETS, 0.0)
    unnamed: Dict[Tuple[str, str, str], List[float]] = {}
    busy = 0.0
    for leaf, ns in shares(found, t0, t1):
        sec, d = ns / 1e9, DIRECTIONS.index(leaf.direction)
        busy += sec
        if leaf.rule:
            kinds[leaf.rule] += sec
        for key in _node_keys(leaf.chain):
            node = nodes.setdefault(key, {"s": [0.0] * 3,
                                          "self": [0.0] * 3})
            node["s"][d] += sec
        if leaf.chain:
            node["self"][d] += sec              # the innermost
        elif leaf.bucket:
            xla[leaf.bucket] += sec
        else:
            names, _ = segments(leaf.path)
            _add(unnamed, (leaf.op.name, leaf.op.category,
                           "/".join(names[-3:])), sec, leaf.events)
    rest = sorted(unnamed.items(), key=lambda kv: -kv[1][0])
    return {"busy_s": busy, "nodes": nodes, "xla": xla, "kinds": kinds,
            "unnamed_s": sum(v[0] for v in unnamed.values()),
            "unnamed_top": [[*k, v[0], v[1]] for k, v in rest[:top]]}


def parts_s(tree: Dict) -> float:
    """What the tree's parts add up to: the self seconds of every node,
    the rules' buckets and the remainder (equals ``busy_s``)."""
    return (sum(sum(n["self"]) for n in tree["nodes"].values())
            + sum(tree["xla"].values()) + tree["unnamed_s"])


def scope_seconds(tree: Dict, name: str, direction: Optional[str] = None
                  ) -> float:
    """Seconds under every node named ``name``, wherever it hangs; the
    grouped kernels count to ``moe_experts`` as in the kernel readers."""
    pick = (lambda v: sum(v)) if direction is None else (
        lambda v: v[DIRECTIONS.index(direction)])
    sec = sum(pick(n["s"]) for key, n in tree["nodes"].items()
              if key.rsplit("/", 1)[-1] == name)
    if name == "moe_experts" and direction is None:
        sec += tree["xla"]["xla_ragged_dot"]
    return sec


# ----------------------------------------------------------------------
# a cell's traced pass
# ----------------------------------------------------------------------
def load(path: str) -> Dict[str, List[Leaf]]:
    """Every device's leaf ops with their path: ``scopes.load`` with the
    identity where it resolves a scope, so no loader is written again."""
    keep = scopes.scope_of
    scopes.scope_of = lambda op_path, instruction="": op_path
    try:
        loaded = scopes.load(path)
    finally:
        scopes.scope_of = keep
    return {plane: leaves((o.op, o.scope) for o in ops)
            for plane, ops in loaded.items()}


_TREES: Dict[Tuple[str, float, float], Dict[str, Dict]] = {}


def trace_path(cell_name: str) -> Optional[str]:
    return xplane.find_xplane(os.path.join(scopes.BENCH, "out", cell_name,
                                           "trace"))


def of_cell(cell, trace) -> Optional[Dict[str, Dict]]:
    """``{chip: tree}`` of the cell's traced pass; None without a trace
    or without the program's table.  Built once per file; the first
    reading prints the first chip's tree as ``scope_tree={...}``."""
    if trace is None or not NAMES:
        return None
    path = trace_path(cell.name)
    if path is None:
        return None
    key = (path, *trace.window)
    if key not in _TREES:
        _TREES[key] = {plane: tree_of(found, *trace.window)
                       for plane, found in load(path).items()}
        first = next(iter(_TREES[key].values()), None)
        if first is not None:
            print("scope_tree=" + json.dumps(rounded(first)))
    return _TREES[key] or None


def rounded(tree: Dict, digits: int = 4) -> Dict:
    r = lambda v: round(v, digits)
    return {"busy_s": r(tree["busy_s"]), "parts_s": r(parts_s(tree)),
            "nodes": {k: {"s": [r(v) for v in n["s"]],
                          "self": [r(v) for v in n["self"]]}
                      for k, n in sorted(tree["nodes"].items())},
            "xla": {k: r(v) for k, v in tree["xla"].items()},
            "kinds": {k: r(v) for k, v in tree["kinds"].items()},
            "unnamed_s": r(tree["unnamed_s"]),
            "unnamed_top": [[*row[:3], r(row[3]), row[4]]
                            for row in tree["unnamed_top"]]}


def worst_share_pct(cell, trace, seconds: Callable[[Dict], float],
                    present: Callable[[Dict], bool] = lambda tree: True
                    ) -> Optional[float]:
    """100 x ``seconds(tree)`` over the chip's busy time, worst chip;
    None without a tree, or where ``present`` finds nothing to read."""
    trees = of_cell(cell, trace)
    if not trees:
        return None
    out = [100.0 * seconds(t) / t["busy_s"] for t in trees.values()
           if t["busy_s"] > 0 and present(t)]
    return max(out) if out else None


# ----------------------------------------------------------------------
# the table of a finished run
# ----------------------------------------------------------------------
def _window(path: str) -> Tuple[float, float]:
    from benchmarks.lib.window import TRACED_PASS

    trace = xplane.load(path)
    win = xplane.window_of(trace, TRACED_PASS)
    if win is not None:
        return win
    ops = [o for dev in trace.devices.values() for o in dev]
    return (min((o.start_ns for o in ops), default=0.0),
            max((o.end_ns for o in ops), default=0.0))


def under(found: Iterable[Leaf], name: str) -> List[Leaf]:
    """The leaves under a scope ``name``, their chains cut to start
    there (every place the scope hangs is added up)."""
    return [f._replace(chain=f.chain[f.chain.index(name):])
            for f in found if name in f.chain]


def rows_of(found: Iterable[Leaf], t0: float, t1: float) -> List[List]:
    """The pass as ``[path, instruction, category, seconds, events]``,
    the seconds those :func:`shares` hands out."""
    acc: Dict[Tuple[str, str, str], List[float]] = {}
    for leaf, ns in shares(found, t0, t1):
        _add(acc, (leaf.path, leaf.op.name, leaf.op.category), ns / 1e9,
             leaf.events)
    return [[*k, v[0], v[1]] for k, v in acc.items()]


def from_rows(rows: Iterable[List]) -> Tuple[List[Leaf], float, float]:
    """Leaves that stand for dumped rows, laid end to end from 0, and
    the window that holds them."""
    ops, counts, end = [], [], 0.0
    for path, name, category, sec, events in rows:
        ops.append((xplane.Op(name, end, sec * 1e9, category), path))
        counts.append(int(events))
        end += sec * 1e9
    return ([leaf._replace(events=n) for leaf, n in zip(leaves(ops), counts)],
            0.0, end)


def by_direction(tree: Dict) -> List[List]:
    """Rows ``[depth, name, s, self, forward, remat, backward]``: the
    tree depth first, the costlier child first."""
    nodes = tree["nodes"]
    kids: Dict[str, List[str]] = {}
    for key in nodes:
        kids.setdefault(key.rpartition("/")[0], []).append(key)
    rows: List[List] = []

    def walk(key: str, depth: int) -> None:
        n = nodes[key]
        rows.append([depth, key.rsplit("/", 1)[-1], sum(n["s"]),
                     sum(n["self"]), *n["s"]])
        for kid in sorted(kids.get(key, ()), key=lambda k: -sum(
                nodes[k]["s"])):
            walk(kid, depth + 1)

    for root in sorted(kids.get("", ()), key=lambda k: -sum(nodes[k]["s"])):
        walk(root, 0)
    for name, sec in (*tree["xla"].items(),
                      ("(unnamed)", tree["unnamed_s"])):
        rows.append([0, name, sec, sec, sec, 0.0, 0.0])
    return rows


def by_instruction(found: Iterable[Leaf], t0: float, t1: float, top: int
                   ) -> List[List]:
    """Rows ``[chain, direction, instruction stem, category, seconds,
    events]``, the costliest first."""
    acc: Dict[Tuple[str, str, str, str], List[float]] = {}
    for leaf, ns in shares(found, t0, t1):
        _add(acc, ("/".join(leaf.chain) or leaf.bucket or "(unnamed)",
                   leaf.direction, _NUMBERED.sub("", leaf.op.name),
                   leaf.op.category), ns / 1e9, leaf.events)
    rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:top]
    return [[*k, v[0], v[1]] for k, v in rows]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--under", default=None, metavar="SCOPE")
    ap.add_argument("--by", choices=("direction", "instruction"),
                    default="direction")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="a jax.profiler directory other than the cell's")
    ap.add_argument("--rows", default=None, metavar="FILE",
                    help="read what --dump wrote in place of a trace")
    ap.add_argument("--dump", default=None, metavar="FILE",
                    help="write every chip's rows as JSON")
    args = ap.parse_args(argv)
    if args.rows is not None:
        with open(args.rows) as f:
            chips = {plane: from_rows(rows)
                     for plane, rows in json.load(f).items()}
    else:
        trace_dir = args.trace_dir or os.path.join(
            scopes.BENCH, "out", args.workload, "trace")
        path = xplane.find_xplane(trace_dir)
        if path is None:
            print(f"no trace under {trace_dir}", file=sys.stderr)
            return 1
        t0, t1 = _window(path)
        chips = {plane: (found, t0, t1)
                 for plane, found in load(path).items()}
    if not chips:
        print("the trace holds no device plane", file=sys.stderr)
        return 1
    if args.dump is not None:
        with open(args.dump, "w") as f:
            json.dump({plane: rows_of(*chip)
                       for plane, chip in chips.items()}, f)
    for plane, (found, t0, t1) in chips.items():
        tree = total = tree_of(found, t0, t1, top=args.top)
        if args.under is not None:
            found = under(found, args.under)
            tree = tree_of(found, t0, t1, top=args.top)
        print(f"{plane}: busy {total['busy_s']:.4f} s of a pass of "
              f"{(t1 - t0) / 1e9:.4f} s; shown {tree['busy_s']:.4f} s"
              + (f" under {args.under}" if args.under else ""))
        if args.by == "instruction":
            print(f"{'seconds':>9} {'events':>7}  direction  instruction "
                  "[category]  scopes")
            for chain, direction, stem, cat, sec, n in by_instruction(
                    found, t0, t1, args.top):
                print(f"{sec:9.4f} {n:7d}  {direction:<9}  {stem} [{cat}]  "
                      f"{chain}")
            continue
        print(f"{'seconds':>9} {'% busy':>7} {'self':>9} {'forward':>9} "
              f"{'remat':>9} {'backward':>9}  scope")
        for depth, name, sec, own, fw, rm, bw in by_direction(tree):
            print(f"{sec:9.4f} {100.0 * sec / (total['busy_s'] or 1.0):7.2f} "
                  f"{own:9.4f} {fw:9.4f} {rm:9.4f} {bw:9.4f}  "
                  + "  " * depth + name)
        if tree["unnamed_top"]:
            print("unnamed, by instruction [category] (path's end): "
                  "seconds, events")
            for name, cat, tail, sec, n in tree["unnamed_top"]:
                print(f"  {name} [{cat}] ({tail}): {sec:.4f}, {n}")
        print("rules over all ops: " + ", ".join(
            f"{k} {v:.4f}" for k, v in tree["kinds"].items()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
