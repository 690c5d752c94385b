"""graftcheck determinism-contract pass: JG117-JG121.

Every recorded telemetry field is contractually a pure function of
(seed, config, round coordinates) — that is what lets control/replay.py
re-derive control/cohort/campaign/serve records bit-exactly across
kill/resume.  Until now the contract was enforced only dynamically, by
tests that happen to tamper with the right field.  This pass proves it
statically, on the same whole-program summaries the flow rules use:

* **JG117** — wall-clock/OS entropy (``time.time``, ``datetime.now``,
  ``os.urandom``, ``uuid.*``, the process-global ``random`` /
  ``np.random`` draws) reaching a recorded field through any chain of
  call edges.  Fields in ``obs.schema.ADVISORY_FIELDS`` (declared
  timing/diagnostic telemetry) and ``ENVELOPE_FIELDS`` (run identity)
  are exempt — the whole point is that the exemption is *declared*,
  not inferred.
* **JG118** — the schema contract itself: every record kind of
  obs/schema.py needs a non-empty ``REQUIRED`` core, every emitted kind
  needs a ``check_*`` checker registered in control/replay.py's
  ``REPLAY_CHECKERS`` (or an explicit exemption), and every registered
  checker must still exist.
* **JG119** — iteration over an unordered collection (set, dict view,
  ``os.listdir``/glob) feeding a recorded field, or a float ``sum()``
  straight over one, without ``sorted()``.
* **JG120** — the checkpoint-meta contract: keys written on the save
  path must be read on some restore path (and vice versa for
  unconditional reads), and the reserved additive namespaces
  (``pop_*``, ``geom_*``, ledger keys) stay with their owner modules.
* **JG121** — PRNG lineage for records: key material that reaches a
  record-feeding draw must descend from the seeded lineage
  (``PRNGKey``/``fold_in``/``split`` of config seed + round
  coordinates), never from an unseeded generator, entropy, or
  iteration order.

Like every graftcheck pass this one is purely syntactic: the contract
tables are read from the *source* of obs/schema.py and
control/replay.py via ``ast.literal_eval`` (summary ``tables``), never
by importing them.  When the declaring modules are not part of the lint
run (single-fixture invocations, ``--changed`` slices) the same
extraction reads them from the shipped files (``TABLE_SOURCES``), and the
declaration-site checks are skipped.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .core import Finding, ModuleContext, ProgramRule, Rule, Severity
from .flow import (_label, _mk_finding, _program_of, extract_tables,
                   Program)

#: the shipped files that own the contract tables, for lint runs that do
#: not include them
TABLE_SOURCES: Tuple[Path, ...] = tuple(
    Path(__file__).resolve().parent.parent / rel
    for rel in ("obs/schema.py", "control/replay.py"))


def shipped_tables() -> Dict[str, object]:
    """The contract tables as ``TABLE_SOURCES`` declare them."""
    out: Dict[str, object] = {}
    for path in TABLE_SOURCES:
        tables = extract_tables(ast.parse(path.read_text(), str(path)))
        out.update((name, val[0]) for name, val in tables.items())
    return out


#: which module declares each table — a declaration from the canonical
#: owner wins over any other (fixture) declaration in the same run
_TABLE_OWNERS = {
    "ADVISORY_FIELDS": "obs.schema", "ENVELOPE_FIELDS": "obs.schema",
    "EVENTS": "obs.schema", "REQUIRED": "obs.schema",
    "DIAGNOSTIC_KINDS": "obs.schema",
    "RESERVED_META_NAMESPACES": "obs.schema",
    "REPLAY_CHECKERS": "control.replay",
    "REPLAY_EXEMPT_KINDS": "control.replay",
}


# ================================================================ model

def _blocked(fn: dict) -> Set[str]:
    """Names statically known to carry *seeded* rng lineage: entropy
    and iteration-order taint stops there (JG121 owns them instead)."""
    out: Set[str] = set()
    for rc in fn.get("rng_ctors", ()):
        out.add(rc["name"])
    for kd in fn.get("key_derives", ()):
        out.add(kd["name"])
    return out


def _closure_reasons(fn: dict, seed: Dict[str, str],
                     blocked: Set[str]) -> Dict[str, str]:
    """Close a name->reason taint map over the function's derives."""
    out = {n: r for n, r in seed.items() if n not in blocked}
    derives = fn.get("derives", ())
    for _ in range(len(derives) + 1):
        changed = False
        for target, srcs in derives:
            if target in blocked or target in out:
                continue
            hit = next((s for s in srcs if s in out), None)
            if hit is not None:
                out[target] = out[hit]
                changed = True
        if not changed:
            break
    return out


def _site(fn: dict, line: int) -> str:
    return "%s:%d" % (_label(fn).split(":")[0], line)


class _Model:
    """Whole-program contract state, built once per lint run: the
    declared tables (with provenance) and the three taint families."""

    def __init__(self, prog: Program, live: Dict[str, ModuleContext]):
        self.prog = prog
        self.live = live
        self._shipped: Optional[Dict[str, object]] = None

        # -------- contract tables: every declaration, with provenance
        self.declared: Dict[str, List[Tuple[object, str, int, str]]] = {}
        for s in sorted(prog.summaries, key=lambda s: s["path"]):
            for name, val in (s.get("tables") or {}).items():
                self.declared.setdefault(name, []).append(
                    (val[0], s["path"], val[1], s["module_name"]))

        # -------- taint: entropy / bad-rng / iteration-order
        fns = [f for f in prog.all_fns()]
        self.ent: Dict[int, Dict[str, str]] = {id(f): {} for f in fns}
        self.bad: Dict[int, Dict[str, str]] = {id(f): {} for f in fns}
        self.order: Dict[int, Dict[str, str]] = {}
        self.ent_ret: Dict[int, Optional[str]] = {id(f): None for f in fns}
        self.bad_ret: Dict[int, Optional[str]] = {id(f): None for f in fns}
        self._ent_params: Dict[int, Dict[str, str]] = \
            {id(f): {} for f in fns}
        self._bad_params: Dict[int, Dict[str, str]] = \
            {id(f): {} for f in fns}
        self._resolved: Dict[Tuple[int, int], list] = {}

        for f in fns:
            seeds: Dict[str, str] = {}
            for u in f.get("unordered", ()):
                why = "iterates %s at %s" % (u["src"], _site(f, u["line"]))
                for t in u["targets"]:
                    seeds.setdefault(t, why)
            self.order[id(f)] = _closure_reasons(f, seeds, _blocked(f))

        for _ in range(20):
            if not self._iterate(fns):
                break

    # ------------------------------------------------------- fixpoint

    def _targets(self, fn: dict, idx: int, call: dict) -> list:
        key = (id(fn), idx)
        if key not in self._resolved:
            try:
                self._resolved[key] = self.prog.resolve(fn, call["callee"])
            except RecursionError:          # pathological alias cycles
                self._resolved[key] = []
        return self._resolved[key]

    def _iterate(self, fns: List[dict]) -> bool:
        changed = False
        for f in fns:
            fid = id(f)
            blocked = _blocked(f)
            order = self.order[fid]

            ent_seed: Dict[str, str] = dict(self._ent_params[fid])
            for name, src, line in f.get("entropy", ()):
                ent_seed.setdefault(
                    name, "%s at %s" % (src, _site(f, line)))
            for idx, call in enumerate(f.get("calls", ())):
                assigned = call.get("assigned")
                if not assigned:
                    continue
                for tgt in self._targets(f, idx, call):
                    why = self.ent_ret.get(id(tgt.fn))
                    if why:
                        for n in assigned:
                            ent_seed.setdefault(
                                n, "%s (returned by %s)"
                                % (why, _label(tgt.fn)))
            ent = _closure_reasons(f, ent_seed, blocked)
            if ent.keys() != self.ent[fid].keys():
                self.ent[fid] = ent
                changed = True
            else:
                self.ent[fid] = ent

            bad_seed: Dict[str, str] = dict(self._bad_params[fid])
            for rc in f.get("rng_ctors", ()):
                why = None
                if rc.get("unseeded"):
                    why = "unseeded %s() at %s" % (rc["ctor"],
                                                   _site(f, rc["line"]))
                elif rc.get("esrc"):
                    why = "%s seeded from %s at %s" % (
                        rc["ctor"], rc["esrc"][0], _site(f, rc["line"]))
                else:
                    hit = next((n for n in rc.get("feeds", ())
                                if n in ent or n in order), None)
                    if hit is not None:
                        why = "%s seeded from tainted %r (%s) at %s" % (
                            rc["ctor"], hit,
                            ent.get(hit) or order.get(hit),
                            _site(f, rc["line"]))
                if why:
                    bad_seed.setdefault(rc["name"], why)
            for kd in f.get("key_derives", ()):
                hit = next((n for n in kd.get("feeds", ())
                            if n in ent or n in order), None)
                if kd.get("esrc"):
                    bad_seed.setdefault(
                        kd["name"], "key folded with %s at %s"
                        % (kd["esrc"][0], _site(f, kd["line"])))
                elif hit is not None:
                    bad_seed.setdefault(
                        kd["name"], "key folded with tainted %r (%s) at %s"
                        % (hit, ent.get(hit) or order.get(hit),
                           _site(f, kd["line"])))
            for idx, call in enumerate(f.get("calls", ())):
                assigned = call.get("assigned")
                if not assigned:
                    continue
                for tgt in self._targets(f, idx, call):
                    why = self.bad_ret.get(id(tgt.fn))
                    if why:
                        for n in assigned:
                            bad_seed.setdefault(
                                n, "%s (returned by %s)"
                                % (why, _label(tgt.fn)))
            bad = _closure_reasons(f, bad_seed, set())
            if bad.keys() != self.bad[fid].keys():
                self.bad[fid] = bad
                changed = True
            else:
                self.bad[fid] = bad

            # ---- returns
            ent_ret = next(iter(f.get("ret_esrc", ())), None)
            if ent_ret:
                ent_ret = "%s returned by %s" % (ent_ret, _label(f))
            bad_ret = None
            for n in f.get("ret_loads", ()):
                if ent_ret is None and n in ent:
                    ent_ret = ent[n]
                if bad_ret is None and n in bad:
                    bad_ret = bad[n]
            if ent_ret != self.ent_ret[fid]:
                self.ent_ret[fid] = ent_ret
                changed = True
            if bad_ret != self.bad_ret[fid]:
                self.bad_ret[fid] = bad_ret
                changed = True

            # ---- caller -> callee argument taint
            for idx, call in enumerate(f.get("calls", ())):
                targets = self._targets(f, idx, call)
                if not targets:
                    continue
                for pos, arg in enumerate(call.get("args", ())):
                    loads = arg.get("loads") or ()
                    e_hit = next((n for n in loads if n in ent), None)
                    b_hit = next((n for n in loads if n in bad), None)
                    if e_hit is None and b_hit is None:
                        continue
                    for tgt in targets:
                        param = tgt.param_for_pos(pos)
                        if param is None:
                            continue
                        tp = id(tgt.fn)
                        if e_hit is not None and \
                                param not in self._ent_params[tp]:
                            self._ent_params[tp][param] = \
                                "%s (passed by %s)" % (ent[e_hit],
                                                       _label(f))
                            changed = True
                        if b_hit is not None and \
                                param not in self._bad_params[tp]:
                            self._bad_params[tp][param] = \
                                "%s (passed by %s)" % (bad[b_hit],
                                                       _label(f))
                            changed = True
                for kwname, desc in (call.get("kw") or {}).items():
                    loads = (desc or {}).get("loads") or ()
                    e_hit = next((n for n in loads if n in ent), None)
                    b_hit = next((n for n in loads if n in bad), None)
                    if e_hit is None and b_hit is None:
                        continue
                    for tgt in targets:
                        if kwname not in tgt.fn["params"]:
                            continue
                        tp = id(tgt.fn)
                        if e_hit is not None and \
                                kwname not in self._ent_params[tp]:
                            self._ent_params[tp][kwname] = \
                                "%s (passed by %s)" % (ent[e_hit],
                                                       _label(f))
                            changed = True
                        if b_hit is not None and \
                                kwname not in self._bad_params[tp]:
                            self._bad_params[tp][kwname] = \
                                "%s (passed by %s)" % (bad[b_hit],
                                                       _label(f))
                            changed = True
        return changed

    # --------------------------------------------------------- tables

    def table(self, name: str):
        """The consumed value of one contract table: the canonical
        owner's declaration if present, else any declaration, else what
        the shipped owner declares."""
        decls = self.declared.get(name, ())
        owner = _TABLE_OWNERS.get(name)
        for val, _path, _line, modname in decls:
            if owner and (modname == owner
                          or modname.endswith("." + owner)):
                return val
        if decls:
            return decls[0][0]
        if self._shipped is None:
            self._shipped = shipped_tables()
        return self._shipped.get(name)

    def exempt_fields(self) -> Set[str]:
        adv = self.table("ADVISORY_FIELDS") or ()
        env = self.table("ENVELOPE_FIELDS") or ()
        return set(adv) | set(env)

    # ---------------------------------------------------------- sinks

    def sinks(self, fn: dict) -> Iterator[Tuple[str, dict]]:
        """(record kind, store fact) for every recorded-field store in
        ``fn``: stores into a dict that carries a literal ``"event"``
        kind or is passed to a recorder method, plus inline dict-literal
        entries at the recorder call itself."""
        kinds: Dict[str, str] = dict(fn.get("dkinds") or {})
        for rc in fn.get("rec_calls", ()):
            if rc.get("var"):
                kinds.setdefault(rc["var"], rc["kind"])
        if kinds:
            for ds in fn.get("dstores", ()):
                var = ds.get("var")
                if var is not None and var in kinds:
                    yield kinds[var], ds
        for rc in fn.get("rec_calls", ()):
            for e in rc.get("entries", ()):
                yield rc["kind"], e

    def emit_sites(self, fn: dict) -> Iterator[Tuple[str, int, int]]:
        """(kind, line, col) for every record-emission site in ``fn``."""
        for ds in fn.get("dstores", ()):
            var = ds.get("var")
            if (var is not None and ds["key"] == "event"
                    and (fn.get("dkinds") or {}).get(var)):
                yield fn["dkinds"][var], ds["line"], ds["col"]
        for rc in fn.get("rec_calls", ()):
            if rc.get("var") or rc.get("entries"):
                yield rc["kind"], rc["line"], rc["col"]


def _model_of(modules: Sequence[ModuleContext],
              extra_summaries: Sequence[dict], state: dict) -> _Model:
    if "contract_model" not in state:
        prog, live = _program_of(modules, extra_summaries, state)
        state["contract_model"] = _Model(prog, live)
    return state["contract_model"]


def _live_fns(model: _Model) -> Iterator[dict]:
    for fn in model.prog.all_fns():
        if fn["_path"] in model.live:
            yield fn


# ================================================================ JG117

class EntropyIntoRecord(ProgramRule):
    """Wall-clock / OS entropy flowing into a replay-checked record
    field.  Core record fields must be pure functions of (seed, config,
    round coordinates); timing telemetry belongs in a field declared in
    ``obs.schema.ADVISORY_FIELDS``.  This is the rule that catches
    ``time.time()`` leaking into ``observed`` — or a wall-clock
    ``backoff_seconds`` replacing the seeded one."""

    id = "JG117"
    severity = Severity.ERROR

    def check_program(self, modules, extra_summaries, state):
        model = _model_of(modules, extra_summaries, state)
        exempt = model.exempt_fields()
        for fn in _live_fns(model):
            ent = model.ent[id(fn)]
            for kind, ds in model.sinks(fn):
                if ds["key"] in exempt:
                    continue
                why = None
                if ds.get("esrc"):
                    why = "%s called inline" % ds["esrc"][0]
                else:
                    hit = next((n for n in ds.get("loads", ())
                                if n in ent), None)
                    if hit is not None:
                        why = "%r carries %s" % (hit, ent[hit])
                    else:
                        for d in ds.get("calls", ()):
                            for tgt in model.prog.resolve(
                                    fn, {"k": "dotted", "v": d}):
                                r = model.ent_ret.get(id(tgt.fn))
                                if r:
                                    why = "%s() returns %s" % (d, r)
                                    break
                            if why:
                                break
                if why is None:
                    continue
                yield _mk_finding(
                    self, model.live, fn["_path"], ds["line"], ds["col"],
                    "entropy reaches recorded field %r of a %r record: "
                    "%s. Core fields must re-derive from (seed, config, "
                    "round coords) for control.replay; wall-clock "
                    "telemetry belongs in an ADVISORY_FIELDS field "
                    "(obs/schema.py)." % (ds["key"], kind, why),
                    (_label(fn),))


# ================================================================ JG118

class SchemaContract(ProgramRule):
    """The schema's required cores + replay coverage.

    Declaration-site checks (only when the declaring module is in the
    lint run): every ``EVENTS`` kind needs a non-empty ``REQUIRED`` core,
    and every checker named in ``REPLAY_CHECKERS`` must exist in the
    declaring module.  Emit-site check (always): a record kind emitted
    anywhere must be replay-checked, replay-exempt, or a declared
    diagnostic."""

    id = "JG118"
    severity = Severity.ERROR

    def check_program(self, modules, extra_summaries, state):
        model = _model_of(modules, extra_summaries, state)
        yield from self._check_required(model)
        yield from self._check_checkers(model)
        yield from self._check_emits(model)

    # ------------------------------------------------ required cores

    def _check_required(self, model: _Model) -> Iterator[Finding]:
        events_at = {path: val for val, path, _line, _mod
                     in model.declared.get("EVENTS", ())}
        for required, path, line, _mod in model.declared.get(
                "REQUIRED", ()):
            events = events_at.get(path)
            if path not in model.live or not isinstance(required, dict) \
                    or not isinstance(events, (list, tuple)):
                continue
            for kind in events:
                if not required.get(kind):
                    yield _mk_finding(
                        self, model.live, path, line, 0,
                        "schema contract violated: record kind %r has no "
                        "REQUIRED core — every kind needs a stable "
                        "required-field set" % (kind,), ())

    # --------------------------------------------- checker existence

    def _check_checkers(self, model: _Model) -> Iterator[Finding]:
        for val, path, line, _mod in model.declared.get(
                "REPLAY_CHECKERS", ()):
            if path not in model.live or not isinstance(val, dict):
                continue
            summary = model.prog.by_path.get(path)
            fns = summary["functions"] if summary else {}
            for kind in sorted(val):
                names = val[kind]
                if not isinstance(names, (list, tuple)):
                    continue
                for nm in names:
                    if nm not in fns:
                        yield _mk_finding(
                            self, model.live, path, line, 0,
                            "REPLAY_CHECKERS registers %r for kind %r "
                            "but no such function exists in this module "
                            "— the replay contract for %r records is "
                            "silently unenforced" % (nm, kind, kind), ())

    # ------------------------------------------------ emit coverage

    def _check_emits(self, model: _Model) -> Iterator[Finding]:
        events = set(model.table("EVENTS") or ())
        checkers = set((model.table("REPLAY_CHECKERS") or {}).keys())
        exempt = set(model.table("REPLAY_EXEMPT_KINDS") or ())
        diagnostic = set(model.table("DIAGNOSTIC_KINDS") or ())
        covered = checkers | exempt | diagnostic
        for fn in _live_fns(model):
            for kind, line, col in model.emit_sites(fn):
                if kind in events and kind not in covered:
                    yield _mk_finding(
                        self, model.live, fn["_path"], line, col,
                        "record kind %r is emitted here but has no "
                        "check_* checker in control/replay.py's "
                        "REPLAY_CHECKERS and is not REPLAY_EXEMPT — "
                        "its records would never be replay-verified"
                        % (kind,), (_label(fn),))


# ================================================================ JG119

class UnorderedIntoRecord(ProgramRule):
    """Set/dict-order nondeterminism feeding a recorded field, or a
    float ``sum()`` taken straight over an unordered source.  Iteration
    order over sets (and, through them, any hash-order artifact) is not
    a function of (seed, config, round coords); ``sorted()`` restores
    the contract."""

    id = "JG119"
    severity = Severity.WARNING

    def check_program(self, modules, extra_summaries, state):
        model = _model_of(modules, extra_summaries, state)
        exempt = model.exempt_fields()
        for fn in _live_fns(model):
            order = model.order[id(fn)]
            for kind, ds in model.sinks(fn):
                if ds["key"] in exempt:
                    continue
                hit = next((n for n in ds.get("loads", ())
                            if n in order), None)
                if hit is None:
                    continue
                yield _mk_finding(
                    self, model.live, fn["_path"], ds["line"], ds["col"],
                    "recorded field %r of a %r record depends on "
                    "iteration order: %r %s. Wrap the iteration in "
                    "sorted() so the record re-derives bit-exactly."
                    % (ds["key"], kind, hit, order[hit]), (_label(fn),))
            for us in fn.get("usums", ()):
                if us.get("fn") != "sum":
                    continue
                yield _mk_finding(
                    self, model.live, fn["_path"], us["line"], us["col"],
                    "float reduction sum() over %s accumulates in "
                    "iteration order — float addition is not "
                    "associative, so the result is not a pure function "
                    "of the inputs. Reduce over sorted(...) instead."
                    % (us["src"],), (_label(fn),))


# ================================================================ JG120

class CheckpointMetaContract(ProgramRule):
    """Checkpoint-meta balance: every key written on a save path must
    be read by some restore path (and every unconditional restore read
    needs a writer), and reserved namespaces stay with their owners.
    Guarded reads (``meta.get(k, d)``, ``"k" in meta``, or a subscript
    dominated by a same-function membership test) are optional by
    design and never demand a writer."""

    id = "JG120"
    severity = Severity.WARNING

    def _carriers(self, fn: dict) -> Set[str]:
        out: Set[str] = set()
        if "meta" in fn.get("params", ()):
            out.add("meta")
        for ds in fn.get("dstores", ()):
            if ds.get("var") == "meta":
                out.add("meta")
        for dl in fn.get("dloads", ()):
            if dl.get("var") == "meta":
                out.add("meta")
        name = fn.get("name") or ""
        if name == "meta" or name.endswith("_meta"):
            for ret in fn.get("returns", ()):
                for elt in ret:
                    if elt.get("k") == "name":
                        out.add(elt["v"])
        return out

    def check_program(self, modules, extra_summaries, state):
        model = _model_of(modules, extra_summaries, state)
        writes: Dict[str, List[tuple]] = {}
        reads: Dict[str, List[tuple]] = {}
        soft: Set[Tuple[int, str]] = set()
        for fn in model.prog.all_fns():
            carriers = self._carriers(fn)
            if not carriers:
                continue
            for ds in fn.get("dstores", ()):
                if ds.get("var") in carriers and ds["key"] != "event":
                    writes.setdefault(ds["key"], []).append(
                        (fn, ds["line"], ds["col"]))
            for dl in fn.get("dloads", ()):
                if dl.get("var") not in carriers:
                    continue
                reads.setdefault(dl["key"], []).append(
                    (fn, dl["line"], dl["col"], dl.get("hard", False)))
                if not dl.get("hard", False):
                    soft.add((id(fn), dl["key"]))

        if writes and reads:
            for key in sorted(writes):
                if key in reads:
                    continue
                for fn, line, col in writes[key]:
                    if fn["_path"] not in model.live:
                        continue
                    yield _mk_finding(
                        self, model.live, fn["_path"], line, col,
                        "checkpoint-meta key %r is written on the save "
                        "path but never read on any restore path — "
                        "either dead weight in every checkpoint or a "
                        "restore-side check that silently never "
                        "happens" % (key,), (_label(fn),))
            for key in sorted(reads):
                if key in writes:
                    continue
                for fn, line, col, hard in reads[key]:
                    if not hard or fn["_path"] not in model.live:
                        continue
                    if (id(fn), key) in soft:
                        continue        # membership-guarded: optional
                    yield _mk_finding(
                        self, model.live, fn["_path"], line, col,
                        "checkpoint-meta key %r is read unconditionally "
                        "on the restore path but no save path writes it "
                        "— restore would KeyError on every real "
                        "checkpoint" % (key,), (_label(fn),))

        namespaces = model.table("RESERVED_META_NAMESPACES") or ()
        for key in sorted(writes):
            for ns_entry in namespaces:
                ns, owners = ns_entry[0], tuple(ns_entry[1])
                match = (key.startswith(ns) if ns.endswith("_")
                         else key == ns)
                if not match:
                    continue
                for fn, line, col in writes[key]:
                    if fn["_path"] not in model.live:
                        continue
                    modname = fn["_mod"]["module_name"]
                    if any(modname == o or modname.endswith("." + o)
                           for o in owners):
                        continue
                    yield _mk_finding(
                        self, model.live, fn["_path"], line, col,
                        "checkpoint-meta key %r collides with the "
                        "reserved namespace %r owned by %s — pick a "
                        "different prefix or move the write into the "
                        "owner" % (key, ns, "/".join(owners)),
                        (_label(fn),))


# ================================================================ JG121

class RoguePrngIntoRecord(ProgramRule):
    """A recorded field fed by a draw whose key material does not
    descend from the seeded lineage.  Record-feeding randomness must
    derive from ``cfg.seed`` + round coordinates via
    ``fold_in``/``split`` (or a seeded ``PRNGKey``/``default_rng``);
    an unseeded generator — or one seeded from entropy or iteration
    order — breaks bit-exact replay even though the value *looks*
    random either way."""

    id = "JG121"
    severity = Severity.ERROR

    def check_program(self, modules, extra_summaries, state):
        model = _model_of(modules, extra_summaries, state)
        exempt = model.exempt_fields()
        for fn in _live_fns(model):
            ent = model.ent[id(fn)]
            bad = model.bad[id(fn)]
            for kind, ds in model.sinks(fn):
                if ds["key"] in exempt:
                    continue
                if ds.get("esrc"):
                    continue            # JG117 owns inline entropy
                if any(n in ent for n in ds.get("loads", ())):
                    continue            # JG117 owns entropy taint
                why = None
                hit = next((n for n in ds.get("loads", ())
                            if n in bad), None)
                if hit is not None:
                    why = "%r carries %s" % (hit, bad[hit])
                else:
                    for d in ds.get("calls", ()):
                        for tgt in model.prog.resolve(
                                fn, {"k": "dotted", "v": d}):
                            r = model.bad_ret.get(id(tgt.fn))
                            if r:
                                why = "%s() returns %s" % (d, r)
                                break
                        if why:
                            break
                if why is None:
                    continue
                yield _mk_finding(
                    self, model.live, fn["_path"], ds["line"], ds["col"],
                    "recorded field %r of a %r record is fed by PRNG "
                    "material outside the seeded lineage: %s. Derive "
                    "record-feeding keys from cfg.seed + round coords "
                    "via fold_in/split so replay re-draws the same "
                    "value." % (ds["key"], kind, why), (_label(fn),))


CONTRACT_RULES: Tuple[Rule, ...] = (
    EntropyIntoRecord(), SchemaContract(), UnorderedIntoRecord(),
    CheckpointMetaContract(), RoguePrngIntoRecord(),
)
