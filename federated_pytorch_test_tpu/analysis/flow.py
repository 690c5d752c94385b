"""Interprocedural graftcheck: whole-program flow rules (JG108-JG111).

The lexical rules in :mod:`.rules` see one jit context at a time; this
module sees the *program*.  It runs in two phases:

1. **Extraction** — each module is reduced to a JSON-shaped
   :func:`extract_module_summary`: per-function params, hazards (host
   syncs / traced branches with the names that feed them), derives
   (local dataflow), ordered load/store/call event streams, callable
   aliases (``f = jax.jit(g, donate_argnums=...)``, partials, donating
   dict entries), return shapes, and PRNG facts.  Summaries are pure
   data, so ``lint --changed`` can cache them per file (keyed on the
   content sha1) and re-extract only what the diff touched.
2. **Resolution + rules** — a :class:`Program` links summaries into a
   call graph: bare names resolve through nesting scopes, module
   functions, and imports (dotted module names are suffix-matched, so
   absolute and relative spellings of ``..parallel.comm`` agree);
   ``functools.partial`` shifts positional bindings; ``jax.vmap`` /
   ``shard_map`` / ``*_jit``-convention wrappers are seen through; and
   ``obj.meth(...)`` on an untyped object resolves to every program
   class defining ``meth`` (the engines' method names are unique, so in
   practice this is exact).

Rules on top:

- **JG108** — a JG101/JG102 hazard (host sync, traced-value branch)
  reachable from a jit root *through call edges*: traced params are
  propagated across resolved calls and closed over local derives; the
  finding anchors at the outermost call site inside the jit context and
  prints the call chain.  Hazards lexically inside a jit context are
  the lexical rules' job and are not re-reported.
- **JG109** — use-after-donate: a buffer passed at a ``donate_argnums``
  position and then read again in the caller (the ``_bench_round`` bug
  class from PR 5).  Donation facts flow through factory returns
  (``train_epoch, comm_fns, _ = trainer._build_fns(ci)``), donating
  dict entries (``comm_fns[mode](...)``), and call-of-call subscripts
  (``self._build_fused(ci)[mode](...)``).  A store in *any* branch
  counts as a rebind (deliberate under-approximation: the rule is
  tuned for zero false positives on the shipped tree).
- **JG110** — interprocedural PRNG key lineage: the same key reaching
  two consuming sites where at least one is across a function boundary,
  without a ``split``/``fold_in``.  "Consuming" is a whole-program
  fixpoint: a callee param consumes when it feeds a ``jax.random``
  sampler directly or is passed bare to a consuming param of a resolved
  callee.  Unresolved calls never count, so handing a key to flax's
  ``Module.init`` (external) stays quiet.
- **JG111** — discarded pure result: a statement-level ``.at[...]``
  update or ``jnp.*`` call whose value is never used — a silent no-op
  under tracing.  ``np.asarray(...)`` / ``jax.tree.map(np.asarray, _)``
  statements are *not* flagged: that is this repo's deliberate
  force-a-host-fetch idiom (see bench.py).
"""

from __future__ import annotations

import ast
import hashlib
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .core import (Finding, ModuleContext, ProgramRule, Rule, Severity,
                   suppressed_rules_by_line)
from .rules import (FunctionNode, MODULE_RULES, _donate_ints, _dotted,
                    _is_jit_call, _is_partial_call, _last_name,
                    _SAMPLER_EXEMPT, _walk_scope, build_index,
                    _fn_param_names)

#: bump when the summary shape changes; stale cache entries re-extract
#: (v3 added the determinism-contract facts consumed by JG117-JG121:
#: entropy sources, dict-field stores/loads, recorder emit sites, rng
#: constructions, key derivations, unordered iteration, literal tables)
SUMMARY_VERSION = 3

#: bump whenever extraction *logic* or any rule changes behaviour without
#: changing the summary shape — ``lint --cache`` folds this into its
#: cache-validity check, so a rule edit invalidates sha1-matched entries
#: that would otherwise serve stale summaries (the shape-only
#: SUMMARY_VERSION cannot catch logic changes)
ANALYSIS_VERSION = 4

#: callable wrappers that pass their first argument's signature through
_TRANSPARENT_WRAPPERS = {"vmap", "pmap", "jit", "pjit", "shard_map",
                         "remat", "checkpoint", "grad", "value_and_grad",
                         "named_call", "checkify"}

#: attributes that concretise statically under tracing — branching on
#: ``x.shape`` / ``x.ndim`` is fine, so those loads don't taint a test
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

_AT_METHODS = {"set", "add", "subtract", "multiply", "divide", "power",
               "min", "max", "get", "apply", "mul", "div"}

#: constructor last-names classified for the concurrency pass
#: (analysis/threads.py); matched on the final attribute so both
#: ``threading.Lock()`` and a bare imported ``Lock()`` register
_SYNC_MAKERS = {
    "Lock": "lock", "RLock": "lock",
    "Event": "event", "Condition": "event", "Semaphore": "event",
    "BoundedSemaphore": "event", "Barrier": "event",
    "Queue": "queue", "LifoQueue": "queue", "PriorityQueue": "queue",
    "SimpleQueue": "queue",
    "Thread": "thread",
    "ThreadPoolExecutor": "pool", "ProcessPoolExecutor": "pool",
}

#: canonical dotted calls that read wall-clock or OS entropy (JG117);
#: call heads are resolved through the module's import aliases first, so
#: ``from time import time`` and ``import time`` both land on
#: ``time.time``
_ENTROPY_CALLS = {
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "time.gmtime", "time.localtime",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.datetime.today", "datetime.date.today",
    "os.urandom", "os.getrandom",
    "uuid.uuid1", "uuid.uuid3", "uuid.uuid4", "uuid.uuid5",
    "secrets.token_bytes", "secrets.token_hex", "secrets.token_urlsafe",
    "secrets.randbits", "secrets.randbelow", "secrets.choice",
}

#: modules whose bare draws consume the process-global — effectively
#: unseeded — generator: ``random.random()``, ``np.random.rand()``
_GLOBAL_RNG_MODULES = {"random", "numpy.random"}

#: attribute calls on those modules that are NOT entropy draws —
#: constructors (JG121's ``rng_ctors`` fact instead) and state plumbing
_RNG_NEUTRAL = {"Random", "RandomState", "default_rng", "Generator",
                "seed", "getstate", "setstate", "PRNGKey"}

#: canonical seeded-generator constructors (JG121 lineage roots)
_RNG_CTOR_CALLS = {"jax.random.PRNGKey", "jax.random.key",
                   "numpy.random.default_rng", "numpy.random.RandomState",
                   "random.Random"}

#: recorder methods whose argument is a record's field payload; values
#: are the schema record kind each method emits
_RECORDER_METHODS = {"round": "round", "span": "span", "alert": "alert",
                     "control_event": "control", "client_event": "client",
                     "campaign_event": "campaign", "serve_event": "serve",
                     "compile_event": "compile"}

#: module-level literal tables the contract rules (JG117-JG121) consume;
#: extracted with ``ast.literal_eval`` so the rules never import linted
#: code — the tables must therefore stay pure literals at their source
CONTRACT_TABLE_NAMES = (
    "ADVISORY_FIELDS", "ENVELOPE_FIELDS",
    "RESERVED_META_NAMESPACES", "DIAGNOSTIC_KINDS",
    "REPLAY_CHECKERS", "REPLAY_EXEMPT_KINDS", "EVENTS", "REQUIRED")


def extract_tables(tree: ast.Module) -> Dict[str, list]:
    """``{name: [value, line]}`` of the contract tables a module declares:
    module-level pure-literal assignments only, so the contract pass reads
    the declared contract without importing the code that declares it."""
    tables: Dict[str, list] = {}
    for node in tree.body:
        tgt = None
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            tgt = node.targets[0].id
        elif (isinstance(node, ast.AnnAssign)
              and isinstance(node.target, ast.Name)
              and node.value is not None):
            tgt = node.target.id
        if tgt in CONTRACT_TABLE_NAMES:
            try:
                tables[tgt] = [ast.literal_eval(node.value), node.lineno]
            except (ValueError, SyntaxError, TypeError):
                pass
    return tables


def _canon_call(d: str, import_mods: Dict[str, str],
                import_syms: Dict[str, List[str]]) -> str:
    """Canonical dotted name of a call through the module's imports."""
    head, _, rest = d.partition(".")
    sym = import_syms.get(head)
    if sym is not None:
        full = (sym[0] + "." + sym[1]) if sym[0] else sym[1]
    else:
        full = import_mods.get(head, head)
    return full + ("." + rest) if rest else full


def _entropy_label(canon: str) -> Optional[str]:
    """The canonical name if ``canon`` is an entropy source, else None."""
    if canon in _ENTROPY_CALLS:
        return canon
    head, _, tail = canon.rpartition(".")
    if head in _GLOBAL_RNG_MODULES and tail not in _RNG_NEUTRAL:
        return canon
    return None


def _entropy_in(node: ast.AST, import_mods, import_syms) -> List[str]:
    """Canonical names of every entropy call anywhere under ``node``."""
    out: List[str] = []
    for cur in ast.walk(node):
        if isinstance(cur, ast.Call):
            d = _dotted(cur.func)
            if d:
                label = _entropy_label(
                    _canon_call(d, import_mods, import_syms))
                if label is not None:
                    out.append(label)
    return out


def _unordered_src(node: ast.AST, known_dicts: Set[str]) -> Optional[str]:
    """Human label when iterating ``node`` has no deterministic order."""
    if isinstance(node, (ast.Set, ast.SetComp)):
        return "a set literal"
    if isinstance(node, ast.Name) and node.id in known_dicts:
        return "dict %r" % node.id
    if isinstance(node, ast.Call):
        d = _dotted(node.func)
        if d == "set":
            return "set(...)"
        if d and "." in d:
            last = d.rsplit(".", 1)[-1]
            if last in ("keys", "values", "items"):
                return d + "()"
            if last in ("listdir", "scandir", "iterdir", "glob", "iglob"):
                return d + "()"
    return None


def _assign_names(target: ast.AST) -> List[str]:
    """Plain names bound by an assignment target (incl. tuple unpack)."""
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for el in target.elts:
            if isinstance(el, ast.Starred):
                el = el.value
            if isinstance(el, ast.Name):
                out.append(el.id)
        return out
    return []


def _extract_contracts(fn_node: ast.AST, import_mods: Dict[str, str],
                       import_syms: Dict[str, List[str]]) -> dict:
    """Determinism-contract facts for one scope (summary v3).

    Everything here is a *local* observation — which names were assigned
    entropy, which const-string dict keys were written/read, where the
    recorder methods were called — stitched into whole-program taint by
    :mod:`.contracts` (JG117-JG121).  Like the rest of the extractor the
    pass is purely syntactic: no linted code is ever imported.
    """
    entropy: List[list] = []      # [name, canonical source, line]
    dstores: List[dict] = []      # const-string-key dict writes
    dloads: List[dict] = []       # const-string-key dict reads
    dkinds: Dict[str, str] = {}   # dict var -> const "event" value
    rec_calls: List[dict] = []    # recorder-method emit sites
    rng_ctors: List[dict] = []    # seeded-generator constructions
    key_derives: List[dict] = []  # split/fold_in rebindings
    unordered: List[dict] = []    # iteration with no deterministic order
    usums: List[dict] = []        # sum()/min()/max() over unordered src
    ret_esrc: List[str] = []      # entropy calls inside return values
    ret_loads: List[str] = []     # names loaded by any return value

    known_dicts: Set[str] = set()
    for node in _walk_scope(fn_node):
        if (isinstance(node, (ast.Assign, ast.AnnAssign))
                and isinstance(node.value, ast.Dict)):
            tgt = (node.targets[0] if isinstance(node, ast.Assign)
                   and len(node.targets) == 1 else
                   node.target if isinstance(node, ast.AnnAssign) else None)
            if isinstance(tgt, ast.Name):
                known_dicts.add(tgt.id)

    def ent(value: Optional[ast.AST]) -> List[str]:
        if value is None:
            return []
        return _entropy_in(value, import_mods, import_syms)

    def calls_in(value: Optional[ast.AST]) -> List[str]:
        if value is None:
            return []
        out = []
        for cur in ast.walk(value):
            if isinstance(cur, ast.Call):
                d = _dotted(cur.func)
                if d:
                    out.append(d)
        return out

    def store(var: Optional[str], key_node: ast.AST,
              value: Optional[ast.AST], line: int, col: int) -> None:
        if not (isinstance(key_node, ast.Constant)
                and isinstance(key_node.value, str)):
            return
        key = key_node.value
        dstores.append({"var": var, "key": key, "line": line, "col": col,
                        "loads": _loads_in(value) if value is not None
                        else [],
                        "esrc": ent(value), "calls": calls_in(value)})
        if (var is not None and key == "event"
                and isinstance(value, ast.Constant)
                and isinstance(value.value, str)):
            dkinds[var] = value.value

    def dict_entries(d: ast.Dict, var: Optional[str],
                     line: int, col: int) -> None:
        for k, v in zip(d.keys, d.values):
            if k is not None:
                store(var, k, v, getattr(v, "lineno", line),
                      getattr(v, "col_offset", col))

    def comp_unordered(value: ast.AST) -> Optional[str]:
        if isinstance(value, (ast.ListComp, ast.SetComp,
                              ast.GeneratorExp, ast.DictComp)):
            for gen in value.generators:
                src = _unordered_src(gen.iter, known_dicts)
                if src:
                    return src
        return None

    def call_feeds(call: ast.Call) -> Tuple[List[str], List[str]]:
        feeds: List[str] = []
        esrc: List[str] = []
        for a in list(call.args) + [kw.value for kw in call.keywords]:
            feeds.extend(_loads_in(a))
            esrc.extend(ent(a))
        return feeds, esrc

    def handle_binding(names: List[str], value: ast.AST,
                       line: int, col: int) -> None:
        """Classify one ``names = value`` binding."""
        if isinstance(value, ast.Call):
            d = _dotted(value.func)
            if d:
                canon = _canon_call(d, import_mods, import_syms)
                parts = canon.split(".")
                if canon in _RNG_CTOR_CALLS:
                    feeds, esrc = call_feeds(value)
                    for n in names:
                        rng_ctors.append({
                            "name": n, "ctor": canon, "feeds": feeds,
                            "esrc": esrc, "line": line, "col": col,
                            "unseeded": not (value.args or value.keywords)})
                    return
                if parts[-1] in ("split", "fold_in") and "random" in parts:
                    feeds, esrc = call_feeds(value)
                    for n in names:
                        key_derives.append({"name": n, "feeds": feeds,
                                            "esrc": esrc, "line": line})
                    return
        # a dict literal does not taint its own name — each entry's
        # esrc is recorded field-by-field via dict_entries instead, so
        # an exempt time_unix entry cannot smear siblings
        if not isinstance(value, ast.Dict):
            es = ent(value)
            if es and names:
                for n in names:
                    entropy.append([n, es[0], line])
        src = comp_unordered(value)
        if src and names:
            unordered.append({"targets": names, "src": src,
                              "line": line, "col": col})

    for node in _walk_scope(fn_node):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)):
                    store(t.value.id, t.slice, node.value,
                          node.lineno, node.col_offset)
            if (len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Dict)):
                dict_entries(node.value, node.targets[0].id,
                             node.lineno, node.col_offset)
            names: List[str] = []
            for t in node.targets:
                names.extend(_assign_names(t))
            handle_binding(names, node.value, node.lineno,
                           node.col_offset)
        elif isinstance(node, ast.AnnAssign):
            if node.value is None:
                continue
            if isinstance(node.target, ast.Name):
                if isinstance(node.value, ast.Dict):
                    dict_entries(node.value, node.target.id,
                                 node.lineno, node.col_offset)
                handle_binding([node.target.id], node.value,
                               node.lineno, node.col_offset)
        elif isinstance(node, ast.AugAssign):
            if isinstance(node.target, ast.Name):
                es = ent(node.value)
                if es:
                    entropy.append([node.target.id, es[0], node.lineno])
            elif (isinstance(node.target, ast.Subscript)
                  and isinstance(node.target.value, ast.Name)):
                store(node.target.value.id, node.target.slice,
                      node.value, node.lineno, node.col_offset)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            src = _unordered_src(node.iter, known_dicts)
            if src:
                names = _assign_names(node.target)
                if names:
                    unordered.append({"targets": names, "src": src,
                                      "line": node.lineno,
                                      "col": node.col_offset})
        elif isinstance(node, ast.Return):
            if node.value is not None:
                ret_esrc.extend(ent(node.value))
                ret_loads.extend(_loads_in(node.value))
        elif isinstance(node, ast.Subscript):
            if (isinstance(node.ctx, ast.Load)
                    and isinstance(node.value, ast.Name)
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)):
                dloads.append({"var": node.value.id,
                               "key": node.slice.value,
                               "line": node.lineno,
                               "col": node.col_offset, "hard": True})
        elif isinstance(node, ast.Compare):
            if (len(node.ops) == 1
                    and isinstance(node.ops[0], (ast.In, ast.NotIn))
                    and isinstance(node.left, ast.Constant)
                    and isinstance(node.left.value, str)
                    and isinstance(node.comparators[0], ast.Name)):
                dloads.append({"var": node.comparators[0].id,
                               "key": node.left.value,
                               "line": node.lineno,
                               "col": node.col_offset, "hard": False})
        elif isinstance(node, ast.Call):
            d = _dotted(node.func)
            if not d:
                continue
            parts = d.split(".")
            last = parts[-1]
            base = ".".join(parts[:-1])
            simple_base = base if base and "." not in base else None
            if d in ("sum", "min", "max") and node.args:
                arg = node.args[0]
                src = comp_unordered(arg) or _unordered_src(arg,
                                                            known_dicts)
                if src:
                    usums.append({"fn": d, "src": src, "line": node.lineno,
                                  "col": node.col_offset})
            elif last == "setdefault" and simple_base and node.args:
                store(simple_base, node.args[0],
                      node.args[1] if len(node.args) > 1 else None,
                      node.lineno, node.col_offset)
            elif (last == "update" and simple_base and node.args
                  and isinstance(node.args[0], ast.Dict)):
                dict_entries(node.args[0], simple_base,
                             node.lineno, node.col_offset)
            elif (last in ("get", "pop") and simple_base and node.args
                  and isinstance(node.args[0], ast.Constant)
                  and isinstance(node.args[0].value, str)):
                dloads.append({"var": simple_base,
                               "key": node.args[0].value,
                               "line": node.lineno,
                               "col": node.col_offset, "hard": False})
            elif last in _RECORDER_METHODS and base and node.args:
                arg = node.args[0]
                rc = {"m": last, "kind": _RECORDER_METHODS[last],
                      "line": node.lineno, "col": node.col_offset,
                      "var": arg.id if isinstance(arg, ast.Name) else None,
                      "entries": []}
                if isinstance(arg, ast.Dict):
                    for k, v in zip(arg.keys, arg.values):
                        if (k is not None and isinstance(k, ast.Constant)
                                and isinstance(k.value, str)):
                            rc["entries"].append(
                                {"key": k.value,
                                 "line": getattr(v, "lineno", node.lineno),
                                 "col": getattr(v, "col_offset", 0),
                                 "loads": _loads_in(v), "esrc": ent(v),
                                 "calls": calls_in(v)})
                rec_calls.append(rc)

    return {"entropy": entropy, "dstores": dstores, "dloads": dloads,
            "dkinds": dkinds, "rec_calls": rec_calls,
            "rng_ctors": rng_ctors, "key_derives": key_derives,
            "unordered": unordered, "usums": usums,
            "ret_esrc": ret_esrc, "ret_loads": ret_loads}


def file_sha1(text: str) -> str:
    return hashlib.sha1(text.encode("utf-8")).hexdigest()


def strip_summary(summary: dict) -> dict:
    """A JSON-safe copy for the ``--changed`` cache: :class:`Program`
    linkage adds ``_path``/``_mod`` backrefs into the per-function
    dicts, and ``_mod`` is circular (it points at the summary)."""
    out = dict(summary)
    out["functions"] = {
        q: {k: v for k, v in fn.items() if not k.startswith("_")}
        for q, fn in summary["functions"].items()}
    return out


# ============================================================ extraction

def _ref_of(expr: ast.AST) -> dict:
    """Describe a callable expression as a serializable CalleeRef."""
    d = _dotted(expr)
    if d:
        return {"k": "dotted", "v": d}
    if isinstance(expr, ast.Subscript):
        base = expr.value
        bd = _dotted(base)
        if bd:
            return {"k": "sub", "v": bd}
        if isinstance(base, ast.Call):
            return {"k": "subcall", "v": _ref_of(base.func),
                    "args": _arg_descs(base)}
    if isinstance(expr, ast.Call) and expr.args:
        wrap = _last_name(expr.func)
        if wrap and (wrap in _TRANSPARENT_WRAPPERS or wrap == "partial"
                     or wrap.endswith("_jit")):
            inner = _ref_of(expr.args[0])
            ref = {"k": "wrap", "w": wrap, "v": inner}
            if wrap == "partial":
                ref["shift"] = len(expr.args) - 1
                ref["kw"] = [k.arg for k in expr.keywords if k.arg]
            donate = ()
            for kw in expr.keywords:
                if kw.arg == "donate_argnums":
                    donate = _donate_ints(kw.value)
            if donate:
                ref["donate"] = list(donate)
            return ref
    return {"k": "opaque"}


def _loads_in(node: ast.AST) -> List[str]:
    """Bare Name loads inside an expression, skipping lambda bodies and
    skipping names only used as the base of a static attribute
    (``x.shape`` does not taint)."""
    out: List[str] = []
    stack: List[ast.AST] = [node]
    while stack:
        cur = stack.pop()
        if isinstance(cur, FunctionNode + (ast.Lambda,)):
            continue
        if (isinstance(cur, ast.Attribute) and cur.attr in _STATIC_ATTRS
                and isinstance(cur.value, ast.Name)):
            continue
        if isinstance(cur, ast.Name) and isinstance(cur.ctx, ast.Load):
            out.append(cur.id)
        stack.extend(ast.iter_child_nodes(cur))
    return out


def _self_attr(node: ast.AST) -> Optional[str]:
    """``self.X``, ``self.X.y``, ``cls.X`` -> ``X`` — the attribute that
    names the shared slot on the instance/class.  Anything not rooted at
    ``self``/``cls`` returns None."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name) and cur.id in ("self", "cls") and parts:
        return parts[-1]
    return None


def _self_attrs_in(node: ast.AST) -> Set[str]:
    """Every ``self.X`` slot read inside an expression (outermost
    attribute per chain; lambda bodies skipped like :func:`_loads_in`)."""
    out: Set[str] = set()
    stack: List[ast.AST] = [node]
    while stack:
        cur = stack.pop()
        if isinstance(cur, FunctionNode + (ast.Lambda,)):
            continue
        if isinstance(cur, ast.Attribute):
            attr = _self_attr(cur)
            if attr is not None:
                out.add(attr)
                continue
        stack.extend(ast.iter_child_nodes(cur))
    return out


def _arg_descs(call: ast.Call) -> List[dict]:
    out = []
    for a in call.args:
        if isinstance(a, ast.Starred):
            out.append({"n": None, "loads": _loads_in(a)})
        else:
            out.append({"n": a.id if isinstance(a, ast.Name) else None,
                        "loads": _loads_in(a)})
    return out


def _elt_desc(node: ast.AST) -> dict:
    if isinstance(node, ast.Name):
        return {"k": "name", "v": node.id}
    return {"k": "opaque"}


class _FnWalker(ast.NodeVisitor):
    """Linearises one function body into events + call records.

    Nested defs are skipped (they get their own summaries); branches are
    flattened in source order, so a store in any branch counts as a
    rebind; loops are bracketed with ``ls``/``le`` marker events."""

    def __init__(self):
        self.events: List[dict] = []
        self.calls: List[dict] = []
        self.aliases: Dict[str, dict] = {}
        self.dict_donates: Dict[str, List[int]] = {}
        self.tuple_binds: Dict[str, List[dict]] = {}
        self.returns: List[List[dict]] = []
        self.derives: List[Tuple[str, List[str]]] = []
        # --- concurrency effect facts (analysis/threads.py) ---
        self.spawns: List[dict] = []        # Thread(target=)/pool.submit
        self.sync_makes: List[dict] = []    # lock/queue/pool/thread ctors
        self.joins: List[dict] = []         # .join()/.shutdown() sites
        self.globals: List[str] = []        # `global X` declarations
        self._loop = 0
        self._held: List[str] = []          # lock tokens held lexically
        self._checks: List[List[str]] = []  # self-attrs checked by if/while
        self._call_idx_by_node: Dict[int, int] = {}

    # ------------------------------------------------------ expressions

    def expr(self, node: Optional[ast.AST]) -> None:
        if node is None:
            return
        if isinstance(node, FunctionNode + (ast.Lambda,)):
            return                      # deferred execution: not events
        if isinstance(node, ast.Call):
            self.expr(node.func)
            for a in node.args:
                self.expr(a)
            for k in node.keywords:
                self.expr(k.value)
            self._record_call(node)
            return
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load):
                self.events.append({"t": "load", "n": node.id,
                                    "line": node.lineno,
                                    "col": node.col_offset,
                                    "loop": self._loop})
            return
        if isinstance(node, ast.Attribute):
            attr = _self_attr(node)
            if attr is not None:
                if isinstance(node.ctx, ast.Load) \
                        and attr not in _STATIC_ATTRS:
                    self._attr_event("aload", attr, node)
                base = node.value
                while isinstance(base, ast.Attribute):
                    base = base.value
                self.expr(base)      # keep the bare `self` load event
                return
            for child in ast.iter_child_nodes(node):
                self.expr(child)
            return
        if isinstance(node, ast.NamedExpr):
            self.expr(node.value)
            self._store_target(node.target)
            return
        for child in ast.iter_child_nodes(node):
            self.expr(child)

    def _attr_event(self, t: str, attr: str, node: ast.AST,
                    rmw: bool = False) -> None:
        ev: dict = {"t": t, "n": attr, "line": node.lineno,
                    "col": node.col_offset, "loop": self._loop}
        if self._held:
            ev["h"] = sorted(set(self._held))
        if t == "astore":
            chk = sorted({a for frame in self._checks for a in frame})
            if chk:
                ev["chk"] = chk
            if rmw:
                ev["rmw"] = True
        self.events.append(ev)

    def _record_call(self, node: ast.Call) -> None:
        kw = {}
        for k in node.keywords:
            if k.arg:
                kw[k.arg] = {"n": (k.value.id
                                   if isinstance(k.value, ast.Name)
                                   else None),
                             "loads": _loads_in(k.value)}
        idx = len(self.calls)
        rec = {
            "line": node.lineno, "col": node.col_offset,
            "callee": _ref_of(node.func),
            "args": _arg_descs(node),
            "kw": kw,
            "assigned": None,
        }
        if self._held:
            rec["held"] = sorted(set(self._held))
        self.calls.append(rec)
        self._call_idx_by_node[id(node)] = idx
        self.events.append({"t": "call", "i": idx, "loop": self._loop})
        self._concurrency_call(node)

    def _concurrency_call(self, node: ast.Call) -> None:
        """Spawn edges, lock acquire/release, join/shutdown records."""
        d = _dotted(node.func)
        if not d:
            return
        base, _, last = d.rpartition(".")
        if last == "acquire" and base:
            self._held.append(base)       # recorded call is pre-acquire
        elif last == "release" and base and base in self._held:
            self._held.remove(base)
        elif last in ("join", "shutdown") and base:
            self.joins.append({"token": base, "op": last,
                               "line": node.lineno})
        elif last == "submit" and base and node.args:
            self.spawns.append({"via": "submit", "pool": base,
                                "target": _ref_of(node.args[0]),
                                "name": None,
                                "line": node.lineno,
                                "col": node.col_offset})
        elif last == "Thread":
            tgt = name = None
            for k in node.keywords:
                if k.arg == "target":
                    tgt = _ref_of(k.value)
                elif k.arg == "name" and isinstance(k.value, ast.Constant):
                    name = str(k.value.value)
            if tgt is not None:
                self.spawns.append({"via": "thread", "pool": None,
                                    "target": tgt, "name": name,
                                    "line": node.lineno,
                                    "col": node.col_offset})

    # ------------------------------------------------------- statements

    def _store_target(self, target: ast.AST,
                      value_attrs: Optional[Set[str]] = None) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name) and isinstance(
                    n.ctx, (ast.Store, ast.Del)):
                self.events.append({"t": "store", "n": n.id,
                                    "loop": self._loop})
            elif isinstance(n, ast.Attribute) and isinstance(
                    n.ctx, (ast.Store, ast.Del)):
                attr = _self_attr(n)
                if attr is not None:
                    self._attr_event(
                        "astore", attr, n,
                        rmw=bool(value_attrs and attr in value_attrs))
            elif isinstance(n, ast.Subscript) and isinstance(
                    n.ctx, (ast.Store, ast.Del)):
                attr = _self_attr(n.value)
                if attr is not None:
                    self._attr_event(
                        "astore", attr, n,
                        rmw=bool(value_attrs and attr in value_attrs))

    def _target_names(self, target: ast.AST) -> List[str]:
        if isinstance(target, ast.Name):
            return [target.id]
        if isinstance(target, (ast.Tuple, ast.List)):
            out = []
            for el in target.elts:
                if isinstance(el, ast.Starred):
                    el = el.value
                if isinstance(el, ast.Name):
                    out.append(el.id)
            return out
        return []

    def stmt(self, node: ast.stmt) -> None:
        if isinstance(node, FunctionNode + (ast.ClassDef,)):
            self.events.append({"t": "store", "n": node.name,
                                "loop": self._loop})
            return
        if isinstance(node, ast.Assign):
            self.expr(node.value)
            loads = _loads_in(node.value)
            for target in node.targets:
                for name in self._target_names(target):
                    if loads:
                        self.derives.append((name, loads))
            if len(node.targets) == 1:
                self._extract_binding(node.targets[0], node.value)
            if isinstance(node.value, ast.Call):
                ci = self._call_idx_by_node.get(id(node.value))
                if ci is not None and len(node.targets) == 1:
                    names = self._target_names(node.targets[0])
                    if names:
                        self.calls[ci]["assigned"] = names
            value_attrs = _self_attrs_in(node.value)
            for target in node.targets:
                self._store_target(target, value_attrs)
            return
        if isinstance(node, ast.AugAssign):
            if isinstance(node.target, ast.Name):
                self.events.append({"t": "load", "n": node.target.id,
                                    "line": node.lineno,
                                    "col": node.col_offset,
                                    "loop": self._loop})
                self.derives.append((node.target.id, _loads_in(node.value)))
            else:
                tbase = (node.target.value
                         if isinstance(node.target, ast.Subscript)
                         else node.target)
                attr = _self_attr(tbase)
                if attr is not None:    # self.x += 1: read-modify-write
                    self._attr_event("aload", attr, node.target)
            self.expr(node.value)
            self._store_target(node.target, _self_attrs_in(node.target))
            return
        if isinstance(node, ast.AnnAssign):
            self.expr(node.value)
            if node.value is not None:
                for name in self._target_names(node.target):
                    loads = _loads_in(node.value)
                    if loads:
                        self.derives.append((name, loads))
                if isinstance(node.value, ast.Call):
                    ci = self._call_idx_by_node.get(id(node.value))
                    names = self._target_names(node.target)
                    if ci is not None and names:
                        self.calls[ci]["assigned"] = names
                self._extract_binding(node.target, node.value)
            self._store_target(node.target,
                               _self_attrs_in(node.value)
                               if node.value is not None else None)
            return
        if isinstance(node, ast.Return):
            self.expr(node.value)
            if node.value is not None:
                if isinstance(node.value, ast.Tuple):
                    self.returns.append(
                        [_elt_desc(e) for e in node.value.elts])
                elif (isinstance(node.value, ast.Name)
                        and node.value.id in self.tuple_binds):
                    self.returns.append(self.tuple_binds[node.value.id])
                else:
                    self.returns.append([_elt_desc(node.value)])
            return
        if isinstance(node, (ast.For, ast.AsyncFor)):
            self.expr(node.iter)
            loads = _loads_in(node.iter)
            for name in self._target_names(node.target):
                if loads:
                    self.derives.append((name, loads))
            self._store_target(node.target)
            self.events.append({"t": "ls"})
            self._loop += 1
            for s in node.body:
                self.stmt(s)
            self._loop -= 1
            self.events.append({"t": "le"})
            for s in node.orelse:
                self.stmt(s)
            return
        if isinstance(node, ast.While):
            self.events.append({"t": "ls"})
            self._loop += 1
            self.expr(node.test)
            checked = sorted(_self_attrs_in(node.test))
            if checked:
                self._checks.append(checked)
            for s in node.body:
                self.stmt(s)
            if checked:
                self._checks.pop()
            self._loop -= 1
            self.events.append({"t": "le"})
            for s in node.orelse:
                self.stmt(s)
            return
        if isinstance(node, ast.If):
            self.expr(node.test)
            # a store to a self-attr the test just read is a
            # check-then-act candidate; the orelse runs when the check
            # failed, so only the body is bracketed
            checked = sorted(_self_attrs_in(node.test))
            if checked:
                self._checks.append(checked)
            for s in node.body:
                self.stmt(s)
            if checked:
                self._checks.pop()
            for s in node.orelse:
                self.stmt(s)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            pushed = 0
            for item in node.items:
                self.expr(item.context_expr)
                if item.optional_vars is not None:
                    loads = _loads_in(item.context_expr)
                    for name in self._target_names(item.optional_vars):
                        if loads:
                            self.derives.append((name, loads))
                    self._store_target(item.optional_vars)
                else:
                    d = _dotted(item.context_expr)
                    if d:               # `with self._lock:` holds a token
                        self._held.append(d)
                        pushed += 1
            for s in node.body:
                self.stmt(s)
            if pushed:
                del self._held[-pushed:]
            return
        if isinstance(node, ast.Try):
            for s in node.body:
                self.stmt(s)
            for h in node.handlers:
                for s in h.body:
                    self.stmt(s)
            for s in node.orelse + node.finalbody:
                self.stmt(s)
            return
        if isinstance(node, ast.Delete):
            for t in node.targets:
                self._store_target(t)
            return
        if isinstance(node, ast.Global):
            for n in node.names:
                if n not in self.globals:
                    self.globals.append(n)
            return
        # Expr / Assert / Raise / Global / Import / Pass / ...
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self.expr(child)

    def _sync_make(self, token: str, kind: str, value: ast.Call) -> None:
        rec: dict = {"token": token, "kind": kind, "line": value.lineno,
                     "col": value.col_offset}
        if kind == "queue":
            bounded = False
            if value.args and isinstance(value.args[0], ast.Constant) \
                    and value.args[0].value:
                bounded = True
            for k in value.keywords:
                if k.arg == "maxsize" and isinstance(k.value, ast.Constant) \
                        and k.value.value:
                    bounded = True
            rec["bounded"] = bounded
        elif kind == "pool":
            for k in value.keywords:
                if k.arg == "thread_name_prefix" \
                        and isinstance(k.value, ast.Constant):
                    rec["prefix"] = str(k.value.value)
        self.sync_makes.append(rec)

    def _extract_binding(self, target: ast.AST, value: ast.AST) -> None:
        """Callable aliases, donating dict entries, tuple binds, and
        sync-primitive constructions (lock/queue/pool/thread)."""
        if isinstance(value, ast.Call):
            mk = _last_name(value.func)
            kind = _SYNC_MAKERS.get(mk) if mk else None
            if kind is not None:
                if isinstance(target, ast.Name):
                    self._sync_make(target.id, kind, value)
                elif isinstance(target, ast.Attribute):
                    attr = _self_attr(target)
                    if attr is not None:
                        self._sync_make("self." + attr, kind, value)
        if isinstance(target, ast.Name):
            if isinstance(value, ast.Tuple):
                self.tuple_binds[target.id] = [
                    _elt_desc(e) for e in value.elts]
            elif isinstance(value, ast.Call) and value.args:
                if _is_jit_call(value):
                    donate: Tuple[int, ...] = ()
                    for kw in value.keywords:
                        if kw.arg == "donate_argnums":
                            donate = _donate_ints(kw.value)
                    self.aliases[target.id] = {
                        "target": _ref_of(value.args[0]),
                        "shift": 0, "kw": [],
                        "donate": list(donate) if donate else None}
                elif _is_partial_call(value):
                    self.aliases[target.id] = {
                        "target": _ref_of(value.args[0]),
                        "shift": len(value.args) - 1,
                        "kw": [k.arg for k in value.keywords if k.arg],
                        "donate": None}
            elif isinstance(value, (ast.Name, ast.Attribute)):
                d = _dotted(value)
                if d:
                    self.aliases[target.id] = {
                        "target": {"k": "dotted", "v": d},
                        "shift": 0, "kw": [], "donate": None}
        elif (isinstance(target, ast.Subscript)
                and isinstance(target.value, ast.Name)
                and isinstance(value, ast.Call) and value.args
                and _is_jit_call(value)):
            for kw in value.keywords:
                if kw.arg == "donate_argnums":
                    donate = _donate_ints(kw.value)
                    if donate:
                        cur = set(self.dict_donates.get(
                            target.value.id, []))
                        self.dict_donates[target.value.id] = sorted(
                            cur | set(donate))


def _extract_hazards(fn_node: ast.AST, numpy_aliases: Set[str],
                     lines: List[str]) -> List[dict]:
    def text(lineno: int) -> str:
        return (lines[lineno - 1].strip()
                if 1 <= lineno <= len(lines) else "")

    out: List[dict] = []
    for node in _walk_scope(fn_node):
        if isinstance(node, ast.Call):
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("item", "tolist")
                    and not node.args):
                out.append({"kind": "sync", "line": node.lineno,
                            "col": node.col_offset,
                            "names": _loads_in(node.func.value),
                            "msg": f".{node.func.attr}() host sync",
                            "text": text(node.lineno)})
                continue
            d = _dotted(node.func)
            if d:
                head, _, tail = d.rpartition(".")
                if head in numpy_aliases and tail in ("asarray", "array"):
                    names: List[str] = []
                    for a in node.args:
                        names.extend(_loads_in(a))
                    out.append({"kind": "sync", "line": node.lineno,
                                "col": node.col_offset, "names": names,
                                "msg": f"{d}() host materialisation",
                                "text": text(node.lineno)})
                    continue
                if d in ("jax.device_get", "device_get"):
                    names = []
                    for a in node.args:
                        names.extend(_loads_in(a))
                    out.append({"kind": "sync", "line": node.lineno,
                                "col": node.col_offset, "names": names,
                                "msg": f"{d}() host round-trip",
                                "text": text(node.lineno)})
                    continue
            if (isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int") and node.args
                    and not isinstance(node.args[0], ast.Constant)):
                out.append({"kind": "sync", "line": node.lineno,
                            "col": node.col_offset,
                            "names": _loads_in(node.args[0]),
                            "msg": f"{node.func.id}() concretisation",
                            "text": text(node.lineno)})
        elif isinstance(node, (ast.If, ast.While)):
            test = node.test
            if (isinstance(test, ast.Compare)
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in test.ops)):
                continue
            names = _loads_in(test)
            if names:
                kind = "if" if isinstance(node, ast.If) else "while"
                out.append({"kind": "branch", "line": node.lineno,
                            "col": node.col_offset, "names": names,
                            "msg": f"Python `{kind}` branch",
                            "text": text(node.lineno)})
    return out


def _extract_prng(fn_node: ast.AST) -> Tuple[List, List, List[str]]:
    key_assigns: List[List] = []
    sampler_uses: List[List] = []
    sanitized: Set[str] = set()
    for node in _walk_scope(fn_node):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and _last_name(node.value.func) == "PRNGKey"):
            key_assigns.append([node.targets[0].id, node.lineno,
                                node.col_offset])
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if not d or "random" not in d.split("."):
            continue
        tail = d.rsplit(".", 1)[-1]
        argnames = [a.id for a in node.args if isinstance(a, ast.Name)]
        if tail in ("split", "fold_in"):
            sanitized.update(argnames)
        elif tail not in _SAMPLER_EXEMPT and node.args \
                and isinstance(node.args[0], ast.Name):
            sampler_uses.append([node.args[0].id, node.lineno,
                                 node.col_offset, tail])
    return key_assigns, sampler_uses, sorted(sanitized)


def _qualname(node: ast.AST, parents: Dict[ast.AST, ast.AST]) -> str:
    parts = [node.name]
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, FunctionNode + (ast.ClassDef,)):
            parts.append(cur.name)
        cur = parents.get(cur)
    return ".".join(reversed(parts))


def _module_name_of(path: str) -> str:
    p = Path(path)
    return ".".join([*(x for x in p.parts[:-1] if x not in ("/", "\\")),
                     p.stem]).lstrip(".")


def extract_module_summary(module: ModuleContext) -> dict:
    """Reduce a parsed module to the serializable program summary."""
    cached = getattr(module, "_graft_flow_summary", None)
    if cached is not None:
        return cached
    index = build_index(module)
    tree = module.tree
    parents = index.parents
    lines = module.lines

    import_mods: Dict[str, str] = {}
    import_syms: Dict[str, List[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for al in node.names:
                import_mods[al.asname or al.name.split(".")[0]] = al.name
        elif isinstance(node, ast.ImportFrom):
            mod = (node.module or "")
            for al in node.names:
                if al.name == "*":
                    continue
                if node.module is None:
                    import_mods[al.asname or al.name] = al.name
                else:
                    import_syms[al.asname or al.name] = [mod, al.name]

    classes: Dict[str, dict] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            info = {"bases": [b for b in
                              (_last_name(x) for x in node.bases) if b],
                    "methods": {}}
            for child in node.body:
                if isinstance(child, FunctionNode):
                    info["methods"][child.name] = _qualname(child, parents)
            classes[node.name] = info

    donate_root: Dict[ast.AST, Set[int]] = {}
    for site in index.sites:
        if site.fn is not None and site.donates:
            donate_root.setdefault(site.fn, set()).update(
                site.donate_argnums_vals)

    functions: Dict[str, dict] = {}

    def _summarise_fn(fn_node, qual: str, cls: Optional[str],
                      body: List[ast.stmt], params: List[str],
                      ndefaults: int, vararg: bool, method: bool,
                      line: int) -> None:
        walker = _FnWalker()
        for s in body:
            walker.stmt(s)
        key_assigns, sampler_uses, sanitized = (
            _extract_prng(fn_node) if fn_node is not None else ([], [], []))
        functions[qual] = {
            "name": qual.rsplit(".", 1)[-1], "qual": qual, "cls": cls,
            "line": line, "method": method, "params": params,
            "ndefaults": ndefaults, "vararg": vararg,
            "in_jit": fn_node in index.contexts if fn_node else False,
            "jit_root": fn_node in index.static_by_fn if fn_node else False,
            "static": sorted(index.static_by_fn.get(fn_node, set()))
            if fn_node is not None else [],
            "donate_root": sorted(donate_root.get(fn_node, set()))
            if fn_node is not None else [],
            "hazards": (_extract_hazards(fn_node, index.numpy_aliases,
                                         lines)
                        if fn_node is not None else []),
            "derives": [[t, srcs] for t, srcs in walker.derives],
            "calls": walker.calls,
            "events": walker.events,
            "aliases": walker.aliases,
            "dict_donates": walker.dict_donates,
            "tuple_binds": walker.tuple_binds,
            "returns": walker.returns,
            "key_assigns": key_assigns,
            "sampler_uses": sampler_uses,
            "sanitized": sanitized,
            "spawns": walker.spawns,
            "sync_makes": walker.sync_makes,
            "joins": walker.joins,
            "globals": walker.globals,
        }
        if fn_node is not None:
            functions[qual].update(
                _extract_contracts(fn_node, import_mods, import_syms))

    for node in ast.walk(tree):
        if not isinstance(node, FunctionNode):
            continue
        qual = _qualname(node, parents)
        parent = parents.get(node)
        cls = parent.name if isinstance(parent, ast.ClassDef) else None
        decs = {(_last_name(d) or "") for d in node.decorator_list}
        a = node.args
        _summarise_fn(node, qual, cls, node.body, _fn_param_names(node),
                      len(a.defaults), a.vararg is not None,
                      method=cls is not None and "staticmethod" not in decs,
                      line=node.lineno)

    # the module body is a pseudo-function: module-level jitted bindings,
    # donating calls in driver code, and top-level PRNG use all live here
    mod_walker = _FnWalker()
    for s in tree.body:
        mod_walker.stmt(s)
    mk, ms, msan = _extract_prng(tree)
    functions["<module>"] = {
        "name": "<module>", "qual": "<module>", "cls": None, "line": 1,
        "method": False, "params": [], "ndefaults": 0, "vararg": False,
        "in_jit": False, "jit_root": False, "static": [],
        "donate_root": [],
        "hazards": [],
        "derives": [[t, srcs] for t, srcs in mod_walker.derives],
        "calls": mod_walker.calls,
        "events": mod_walker.events,
        "aliases": mod_walker.aliases,
        "dict_donates": mod_walker.dict_donates,
        "tuple_binds": mod_walker.tuple_binds,
        "returns": mod_walker.returns,
        "key_assigns": mk,
        "sampler_uses": ms,
        "sanitized": msan,
        "spawns": mod_walker.spawns,
        "sync_makes": mod_walker.sync_makes,
        "joins": mod_walker.joins,
        "globals": mod_walker.globals,
    }
    functions["<module>"].update(
        _extract_contracts(tree, import_mods, import_syms))

    summary = {
        "version": SUMMARY_VERSION,
        "path": module.path,
        "module_name": _module_name_of(module.path),
        "import_mods": import_mods,
        "import_syms": import_syms,
        "jnp_aliases": sorted(index.jnp_aliases),
        "classes": classes,
        "tables": extract_tables(tree),
        "functions": functions,
        "suppress": [[ln, sorted(ids)] for ln, ids in
                     sorted(suppressed_rules_by_line(module.source).items())],
    }
    module._graft_flow_summary = summary
    return summary


# ============================================================= resolution

class Target:
    """One resolved callee: the fn summary plus the positional mapping
    (partial shift, partial-bound kwargs, implicit self)."""

    __slots__ = ("fn", "shift", "bound_kw", "skip_self")

    def __init__(self, fn: dict, shift: int = 0,
                 bound_kw: Sequence[str] = (), skip_self: bool = False):
        self.fn = fn
        self.shift = shift
        self.bound_kw = frozenset(bound_kw)
        self.skip_self = skip_self

    def param_for_pos(self, pos: int) -> Optional[str]:
        idx = pos + self.shift + (1 if self.skip_self else 0)
        params = self.fn["params"]
        if 0 <= idx < len(params):
            name = params[idx]
            if name not in self.bound_kw:
                return name
        return None


class Program:
    """Linked view over every module summary of one lint run."""

    def __init__(self, summaries: Sequence[dict]):
        self.summaries = list(summaries)
        self.by_path: Dict[str, dict] = {}
        self.by_module_name: List[Tuple[str, dict]] = []
        self.fns: Dict[Tuple[str, str], dict] = {}
        self.methods: Dict[str, List[dict]] = {}
        self.classes: Dict[str, List[Tuple[dict, dict]]] = {}
        for s in self.summaries:
            self.by_path[s["path"]] = s
            self.by_module_name.append((s["module_name"], s))
            for qual, fn in s["functions"].items():
                fn["_path"] = s["path"]
                fn["_mod"] = s
                self.fns[(s["path"], qual)] = fn
            for cls, info in s["classes"].items():
                self.classes.setdefault(cls, []).append((s, info))
                for m, q in info["methods"].items():
                    fn = self.fns.get((s["path"], q))
                    if fn is not None:
                        self.methods.setdefault(m, []).append(fn)
        self.by_module_name.sort(key=lambda t: t[0])

    def all_fns(self) -> Iterator[dict]:
        for s in self.summaries:
            yield from s["functions"].values()

    def module_by_suffix(self, dotted: str) -> Optional[dict]:
        dotted = dotted.lstrip(".")
        if not dotted:
            return None
        for name, s in self.by_module_name:
            if name == dotted or name.endswith("." + dotted):
                return s
        return None

    # ------------------------------------------------------ scope chain

    def scope_chain(self, fn: dict) -> List[dict]:
        """fn, then enclosing function scopes, then the module body."""
        mod = fn["_mod"]
        out = [fn]
        parts = fn["qual"].split(".")
        for cut in range(len(parts) - 1, 0, -1):
            prefix = ".".join(parts[:cut])
            enclosing = mod["functions"].get(prefix)
            if enclosing is not None and enclosing is not fn:
                out.append(enclosing)
        module_fn = mod["functions"].get("<module>")
        if module_fn is not None and module_fn is not fn:
            out.append(module_fn)
        return out

    def lookup_alias(self, fn: dict, name: str) -> Optional[dict]:
        for scope in self.scope_chain(fn):
            alias = scope["aliases"].get(name)
            if alias is not None:
                return alias
        return None

    # ------------------------------------------------------- resolution

    def _class_method(self, cls_name: str, attr: str,
                      seen: Optional[Set[str]] = None) -> List[dict]:
        seen = seen if seen is not None else set()
        if cls_name in seen:
            return []
        seen.add(cls_name)
        out: List[dict] = []
        for s, info in self.classes.get(cls_name, []):
            q = info["methods"].get(attr)
            if q is not None:
                fn = self.fns.get((s["path"], q))
                if fn is not None:
                    out.append(fn)
            else:
                for base in info["bases"]:
                    out.extend(self._class_method(base, attr, seen))
        return out

    def _function_in_module(self, mod: dict, dotted: str) -> List[dict]:
        parts = dotted.split(".")
        if len(parts) == 1:
            fn = mod["functions"].get(parts[0])
            return [fn] if fn is not None else []
        if len(parts) == 2 and parts[0] in mod["classes"]:
            q = mod["classes"][parts[0]]["methods"].get(parts[1])
            if q is not None:
                fn = mod["functions"].get(q)
                return [fn] if fn is not None else []
        return []

    def resolve(self, fn: dict, ref: dict, shift: int = 0,
                bound_kw: Sequence[str] = (), depth: int = 0
                ) -> List[Target]:
        """All program functions a CalleeRef may call, with positional
        mapping.  Unresolvable (external, dynamic) refs return []."""
        if depth > 6 or not isinstance(ref, dict):
            return []
        kind = ref.get("k")
        if kind == "wrap":
            extra_shift = ref.get("shift", 0)
            extra_kw = ref.get("kw", [])
            return self.resolve(fn, ref["v"], shift + extra_shift,
                                list(bound_kw) + list(extra_kw), depth + 1)
        if kind != "dotted":
            return []                     # sub/subcall/opaque: no mapping
        dotted = ref["v"]
        parts = dotted.split(".")
        mod = fn["_mod"]

        if len(parts) == 1:
            name = parts[0]
            alias = self.lookup_alias(fn, name)
            if alias is not None:
                return self.resolve(fn, alias["target"],
                                    shift + alias.get("shift", 0),
                                    list(bound_kw) + list(alias.get("kw",
                                                                    [])),
                                    depth + 1)
            # nested def / sibling in enclosing scopes / module level
            quals = [fn["qual"] + "." + name]
            qparts = fn["qual"].split(".")
            for cut in range(len(qparts) - 1, 0, -1):
                prefix = ".".join(qparts[:cut])
                if prefix in mod["functions"]:
                    quals.append(prefix + "." + name)
            quals.append(name)
            for q in quals:
                got = mod["functions"].get(q)
                if got is not None:
                    return [Target(got, shift, bound_kw)]
            sym = mod["import_syms"].get(name)
            if sym is not None:
                origin = self.module_by_suffix(sym[0])
                if origin is not None:
                    got = self._function_in_module(origin, sym[1])
                    if got:
                        return [Target(g, shift, bound_kw) for g in got]
            return []

        head, attr = parts[0], parts[-1]
        if head in ("self", "cls"):
            if len(parts) == 2 and fn["cls"]:
                found = self._class_method(fn["cls"], attr)
                if found:
                    return [Target(g, shift, bound_kw,
                                   skip_self=g["method"]) for g in found]
            return [Target(g, shift, bound_kw, skip_self=g["method"])
                    for g in self.methods.get(attr, [])]
        # imported module alias: codec.get_trainable_values(...)
        origin_name = mod["import_mods"].get(head)
        if origin_name is None and head in mod["import_syms"]:
            sym = mod["import_syms"][head]
            # `from x import y` where y is a module (or a class)
            if len(parts) == 2 and sym[1] in self.classes:
                found = self._class_method(sym[1], attr)
                return [Target(g, shift, bound_kw,
                               skip_self=g["method"]) for g in found]
            origin_name = sym[0] + "." + sym[1]
        if origin_name is not None:
            origin = self.module_by_suffix(origin_name)
            if origin is not None:
                got = self._function_in_module(origin,
                                               ".".join(parts[1:]))
                return [Target(g, shift, bound_kw) for g in got]
            return []                    # external library: unresolved
        if head in mod["classes"]:
            found = self._class_method(head, attr)
            return [Target(g, shift, bound_kw, skip_self=g["method"])
                    for g in found]
        # method call on an untyped local object: every program class
        # defining the method is a candidate (union)
        if len(parts) >= 2:
            return [Target(g, shift, bound_kw, skip_self=g["method"])
                    for g in self.methods.get(attr, [])]
        return []

    # --------------------------------------------------- donation facts

    def return_facts(self, callee: dict) -> List[Optional[dict]]:
        """Per tuple position of ``callee``'s return value: a donation
        fact ``{"kind": "callable"|"dict", "argnums": [...]}`` or
        None."""
        width = max((len(r) for r in callee["returns"]), default=0)
        facts: List[Optional[dict]] = [None] * width
        for ret in callee["returns"]:
            for pos, elt in enumerate(ret):
                if elt.get("k") != "name":
                    continue
                name = elt["v"]
                alias = callee["aliases"].get(name)
                if alias is not None and alias.get("donate"):
                    facts[pos] = {"kind": "callable",
                                  "argnums": alias["donate"],
                                  "shift": alias.get("shift", 0)}
                elif name in callee["dict_donates"]:
                    facts[pos] = {"kind": "dict",
                                  "argnums": callee["dict_donates"][name]}
        return facts


def _label(fn: dict) -> str:
    return f"{Path(fn['_path']).name}:{fn['qual']}"


def _closure(fn: dict, seed: Set[str]) -> Set[str]:
    """Close a traced-name set over the function's local derives."""
    traced = set(seed)
    for _ in range(len(fn["derives"]) + 1):
        changed = False
        for target, srcs in fn["derives"]:
            if target not in traced and traced.intersection(srcs):
                traced.add(target)
                changed = True
        if not changed:
            break
    return traced


def _program_of(modules: Sequence[ModuleContext],
                extra_summaries: Sequence[dict],
                state: dict) -> Tuple[Program, Dict[str, ModuleContext]]:
    if "flow_program" not in state:
        live = {m.path: m for m in modules}
        sums = [extract_module_summary(m) for m in modules]
        seen = set(live)
        for s in extra_summaries:
            if s.get("version") == SUMMARY_VERSION \
                    and s.get("path") not in seen:
                sums.append(s)
                seen.add(s.get("path"))
        state["flow_program"] = Program(sums)
        state["flow_live"] = live
    return state["flow_program"], state["flow_live"]


def _mk_finding(rule: Rule, live: Dict[str, ModuleContext], path: str,
                line: int, col: int, message: str,
                chain: Sequence[str]) -> Finding:
    module = live.get(path)
    text = module.line_text(line) if module is not None else ""
    return Finding(path=path, line=line, col=col, rule_id=rule.id,
                   severity=rule.severity, message=message,
                   source_line=text, call_chain=tuple(chain))


# ================================================================ JG108

class CrossFunctionHazard(ProgramRule):
    """Traced values chased through resolved call edges from every jit
    root; hazards *lexically* inside a jit context stay with JG101/JG102
    (this rule would otherwise double-report every lexical finding)."""

    id = "JG108"
    severity = Severity.WARNING
    summary = "host sync / traced branch reached via calls from a jit root"

    _MAX_DEPTH = 10

    def check_program(self, modules, extra_summaries, state
                      ) -> Iterator[Finding]:
        prog, live = _program_of(modules, extra_summaries, state)
        reported: Set[Tuple] = set()
        for root in prog.all_fns():
            if not root["jit_root"] or root["_path"] not in live:
                continue
            traced = set(root["params"]) - set(root["static"])
            if not traced:
                continue
            yield from self._walk(prog, live, root, traced, reported)

    def _walk(self, prog: Program, live, root: dict, traced: Set[str],
              reported: Set[Tuple]) -> Iterator[Finding]:
        stack = [(root, frozenset(traced), (root,), None)]
        visited: Set[Tuple[str, str, frozenset]] = set()
        while stack:
            fn, fn_traced, chain, anchor = stack.pop()
            key = (fn["_path"], fn["qual"], fn_traced)
            if key in visited:
                continue
            visited.add(key)
            closed = _closure(fn, set(fn_traced))
            if len(chain) > 1 and not fn["in_jit"]:
                for haz in fn["hazards"]:
                    hit = sorted(closed.intersection(haz["names"]))
                    if not hit:
                        continue
                    rep_key = (anchor, fn["_path"], haz["line"],
                               haz["kind"])
                    if rep_key in reported:
                        continue
                    reported.add(rep_key)
                    what = ("host sync" if haz["kind"] == "sync"
                            else "traced-value branch")
                    yield _mk_finding(
                        self, live, anchor[0], anchor[1], anchor[2],
                        f"call into {_label(fn)!r} reaches a {what} "
                        f"({haz['msg']}) on traced value(s) "
                        f"{', '.join(repr(h) for h in hit)} at "
                        f"{Path(fn['_path']).name}:{haz['line']} "
                        f"(`{haz['text']}`); hoist it out of the jitted "
                        "call path or bind the argument statically",
                        chain=[_label(f) for f in chain])
            if len(chain) > self._MAX_DEPTH:
                continue
            for call in fn["calls"]:
                for target in prog.resolve(fn, call["callee"]):
                    callee = target.fn
                    callee_traced: Set[str] = set()
                    for pos, arg in enumerate(call["args"]):
                        if closed.intersection(arg["loads"]):
                            p = target.param_for_pos(pos)
                            if p is not None:
                                callee_traced.add(p)
                    for kw_name, arg in call["kw"].items():
                        if kw_name in callee["params"] \
                                and kw_name not in target.bound_kw \
                                and closed.intersection(arg["loads"]):
                            callee_traced.add(kw_name)
                    callee_traced -= set(callee["static"])
                    if not callee_traced:
                        continue
                    next_anchor = anchor if anchor is not None else (
                        fn["_path"], call["line"], call["col"])
                    stack.append((callee, frozenset(callee_traced),
                                  chain + (callee,), next_anchor))


# ================================================================ JG109

class UseAfterDonate(ProgramRule):
    """Caller-side scan: after a bare name is passed at a donated
    position, any read before a rebind — or a loop iteration that never
    rebinds it — touches a buffer jax may already have aliased away."""

    id = "JG109"
    severity = Severity.ERROR
    summary = "buffer read after being passed at a donate_argnums position"

    def check_program(self, modules, extra_summaries, state
                      ) -> Iterator[Finding]:
        prog, live = _program_of(modules, extra_summaries, state)
        for fn in prog.all_fns():
            if fn["_path"] in live:
                yield from self._check_fn(prog, live, fn)

    # ---------------------------------------------------------- facts

    def _call_donation(self, prog: Program, fn: dict, facts: Dict[str, dict],
                       call: dict) -> Tuple[List[int], int, Optional[str]]:
        """(donated argnums, positional shift, provenance label)."""
        ref = call["callee"]
        kind = ref.get("k")
        if kind == "wrap" and ref.get("donate"):
            return list(ref["donate"]), ref.get("shift", 0), None
        if kind == "dotted":
            parts = ref["v"].split(".")
            if len(parts) == 1:
                name = parts[0]
                fact = facts.get(name)
                if fact is not None and fact["kind"] == "callable":
                    return (list(fact["argnums"]), fact.get("shift", 0),
                            fact.get("from"))
                alias = prog.lookup_alias(fn, name)
                if alias is not None and alias.get("donate"):
                    return (list(alias["donate"]),
                            alias.get("shift", 0), None)
            for target in prog.resolve(fn, ref):
                if target.fn["donate_root"]:
                    return (list(target.fn["donate_root"]), target.shift
                            - (1 if target.skip_self else 0), None)
        elif kind == "sub":
            base = ref["v"].split(".")[0]
            fact = facts.get(base)
            if fact is not None and fact["kind"] == "dict":
                return list(fact["argnums"]), 0, fact.get("from")
            for scope in prog.scope_chain(fn):
                if base in scope["dict_donates"]:
                    return list(scope["dict_donates"][base]), 0, None
        elif kind == "subcall":
            for target in prog.resolve(fn, ref["v"]):
                rf = prog.return_facts(target.fn)
                if len(rf) == 1 and rf[0] is not None \
                        and rf[0]["kind"] == "dict":
                    return (list(rf[0]["argnums"]), 0, _label(target.fn))
        return [], 0, None

    def _build_facts(self, prog: Program, fn: dict) -> Dict[str, dict]:
        """Local name -> donation fact, from factory-call assignments
        (``a, b, c = trainer._build_fns(ci)``)."""
        facts: Dict[str, dict] = {}
        for call in fn["calls"]:
            assigned = call.get("assigned")
            if not assigned:
                continue
            for target in prog.resolve(fn, call["callee"]):
                rf = prog.return_facts(target.fn)
                if not any(rf):
                    continue
                label = _label(target.fn)
                if len(assigned) == 1 and len(rf) == 1:
                    if rf[0] is not None:
                        facts[assigned[0]] = dict(rf[0], **{"from": label})
                elif len(assigned) == len(rf):
                    for name, fact in zip(assigned, rf):
                        if fact is not None:
                            facts[name] = dict(fact, **{"from": label})
        return facts

    # ----------------------------------------------------------- scan

    def _check_fn(self, prog: Program, live, fn: dict
                  ) -> Iterator[Finding]:
        facts = self._build_facts(prog, fn)
        donated_at: Dict[int, Tuple[List[str], Optional[str], dict]] = {}
        for i, call in enumerate(fn["calls"]):
            argnums, shift, provenance = self._call_donation(
                prog, fn, facts, call)
            if not argnums:
                continue
            names: List[str] = []
            for p in argnums:
                pos = p - shift
                if 0 <= pos < len(call["args"]):
                    n = call["args"][pos]["n"]
                    if n is not None:
                        names.append(n)
            if names:
                donated_at[i] = (names, provenance, call)

        if not donated_at:
            return
        events = fn["events"]
        dead: Dict[str, Tuple[dict, Optional[str]]] = {}
        emitted: Set[Tuple] = set()
        for ev in events:
            t = ev["t"]
            if t == "store":
                dead.pop(ev["n"], None)
            elif t == "load":
                hit = dead.pop(ev["n"], None)
                if hit is not None:
                    call, provenance = hit
                    key = ("read", ev["n"], ev["line"])
                    if key in emitted:
                        continue
                    emitted.add(key)
                    chain = [_label(fn)] + (
                        [provenance] if provenance else [])
                    yield _mk_finding(
                        self, live, fn["_path"], ev["line"], ev["col"],
                        f"{ev['n']!r} is read after being passed at a "
                        f"donate_argnums position on line {call['line']} "
                        "— the buffer may already be donated and its "
                        "contents invalid; rebind the call's result or "
                        "pass a copy",
                        chain=chain)
            elif t == "call" and ev["i"] in donated_at:
                names, provenance, call = donated_at[ev["i"]]
                for n in names:
                    dead[n] = (call, provenance)

        # loop-carried: a donating call inside a loop whose donated name
        # is never re-stored in that loop body is reused (donated) on
        # the next iteration even if no later read appears lexically
        yield from self._loop_carried(live, fn, donated_at, emitted)

    def _loop_carried(self, live, fn: dict, donated_at, emitted
                      ) -> Iterator[Finding]:
        events = fn["events"]
        spans: List[Tuple[int, int]] = []
        stack: List[int] = []
        for idx, ev in enumerate(events):
            if ev["t"] == "ls":
                stack.append(idx)
            elif ev["t"] == "le" and stack:
                spans.append((stack.pop(), idx))
        for start, end in spans:
            span = events[start:end + 1]
            stored = {e["n"] for e in span if e["t"] == "store"}
            for e in span:
                if e["t"] != "call" or e["i"] not in donated_at:
                    continue
                names, provenance, call = donated_at[e["i"]]
                for n in names:
                    if n in stored:
                        continue
                    key = ("loop", n, call["line"])
                    if key in emitted:
                        continue
                    emitted.add(key)
                    chain = [_label(fn)] + (
                        [provenance] if provenance else [])
                    yield _mk_finding(
                        self, live, fn["_path"], call["line"],
                        call["col"],
                        f"{n!r} is passed at a donate_argnums position "
                        "inside a loop but never rebound in the loop "
                        "body — the next iteration reuses a donated "
                        "buffer; thread it through the loop like the "
                        "other carried state",
                        chain=chain)


# ================================================================ JG110

class KeyLineage(ProgramRule):
    """The same PRNG key consumed at two sites where at least one is a
    call edge into a transitively-consuming function.  Purely-local
    double consumption is JG103's finding; purely-unresolvable callees
    (flax ``Module.init``) never count as consumers."""

    id = "JG110"
    severity = Severity.WARNING
    summary = "PRNG key reaches multiple consumers across function calls"

    _MAX_ROUNDS = 20

    def _consuming_params(self, prog: Program) -> Set[Tuple[str, str, str]]:
        consuming: Set[Tuple[str, str, str]] = set()
        for fn in prog.all_fns():
            params = set(fn["params"])
            sanitized = set(fn["sanitized"])
            for name, _ln, _c, _tail in fn["sampler_uses"]:
                if name in params and name not in sanitized:
                    consuming.add((fn["_path"], fn["qual"], name))
        for _ in range(self._MAX_ROUNDS):
            changed = False
            for fn in prog.all_fns():
                params = set(fn["params"])
                sanitized = set(fn["sanitized"])
                for call in fn["calls"]:
                    for pos, arg in enumerate(call["args"]):
                        n = arg["n"]
                        if n is None or n not in params or n in sanitized:
                            continue
                        key = (fn["_path"], fn["qual"], n)
                        if key in consuming:
                            continue
                        for target in prog.resolve(fn, call["callee"]):
                            p = target.param_for_pos(pos)
                            if p is not None and (
                                    target.fn["_path"],
                                    target.fn["qual"], p) in consuming:
                                consuming.add(key)
                                changed = True
                                break
            if not changed:
                break
        return consuming

    def check_program(self, modules, extra_summaries, state
                      ) -> Iterator[Finding]:
        prog, live = _program_of(modules, extra_summaries, state)
        consuming = self._consuming_params(prog)
        for fn in prog.all_fns():
            if fn["_path"] not in live:
                continue
            sanitized = set(fn["sanitized"])
            for kname, kline, _kcol in fn["key_assigns"]:
                if kname in sanitized:
                    continue
                consumers: List[Tuple[int, int, str, Optional[str]]] = []
                for name, line, col, tail in fn["sampler_uses"]:
                    if name == kname:
                        consumers.append((line, col, "local", tail))
                for call in fn["calls"]:
                    for pos, arg in enumerate(call["args"]):
                        if arg["n"] != kname:
                            continue
                        for target in prog.resolve(fn, call["callee"]):
                            p = target.param_for_pos(pos)
                            if p is not None and (
                                    target.fn["_path"],
                                    target.fn["qual"], p) in consuming:
                                consumers.append((call["line"],
                                                  call["col"], "call",
                                                  _label(target.fn)))
                                break
                        else:
                            continue
                        break
                consumers.sort(key=lambda c: (c[0], c[1]))
                if len(consumers) < 2 or not any(
                        c[2] == "call" for c in consumers):
                    continue
                first = consumers[0]
                for line, col, kind, label in consumers[1:]:
                    via = (f"the call into {label!r}" if kind == "call"
                           else f"jax.random.{label}")
                    chain = [_label(fn)] + (
                        [label] if kind == "call" else [])
                    yield _mk_finding(
                        self, live, fn["_path"], line, col,
                        f"PRNG key {kname!r} (created line {kline}) is "
                        f"consumed again here via {via} after already "
                        f"feeding a consumer on line {first[0]} — the "
                        "streams are correlated; derive per-consumer "
                        "keys with jax.random.split/fold_in",
                        chain=chain)


# ================================================================ JG111

class DiscardedPureResult(Rule):
    """jax arrays are immutable: a statement-level ``x.at[0].set(v)`` or
    ``jnp.foo(...)`` computes a new array and drops it — a silent no-op
    that usually means the author expected in-place mutation."""

    id = "JG111"
    severity = Severity.WARNING
    summary = "result of a pure jax op is discarded (silent no-op)"

    def check(self, module: ModuleContext) -> Iterator[Finding]:
        index = build_index(module)
        jnp_aliases = index.jnp_aliases
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Expr)
                    and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            func = call.func
            if (isinstance(func, ast.Attribute)
                    and func.attr in _AT_METHODS
                    and isinstance(func.value, ast.Subscript)
                    and isinstance(func.value.value, ast.Attribute)
                    and func.value.value.attr == "at"):
                yield self.finding(
                    module, node,
                    f".at[...].{func.attr}() returns a NEW array — the "
                    "result is discarded here, so the statement is a "
                    "silent no-op; assign it (`x = x.at[...]."
                    f"{func.attr}(...)`)")
                continue
            d = _dotted(func)
            if not d or "." not in d:
                continue
            head = d.split(".")[0]
            if head in jnp_aliases or d.startswith("jax.numpy."):
                yield self.finding(
                    module, node,
                    f"result of {d}(...) is discarded — jax.numpy ops "
                    "are pure, so this statement is a silent no-op; "
                    "assign or return the result (host-fetch idioms "
                    "belong to numpy: np.asarray)")


FLOW_RULES: Tuple[Rule, ...] = (
    CrossFunctionHazard(),
    UseAfterDonate(),
    KeyLineage(),
    DiscardedPureResult(),
)

#: the full shipped rule set: lexical JG101-JG107, flow JG108-JG111,
#: concurrency JG112-JG116, determinism contracts JG117-JG121.
#: threads.py and contracts.py import Program/summaries from this
#: module, so their rules are pulled in at the bottom — every name they
#: need is already bound by the time these imports run.
from .threads import THREAD_RULES  # noqa: E402  (deliberate late import)
from .contracts import CONTRACT_RULES  # noqa: E402  (deliberate late)

ALL_RULES: Tuple[Rule, ...] = (tuple(MODULE_RULES) + FLOW_RULES
                               + THREAD_RULES + CONTRACT_RULES)
