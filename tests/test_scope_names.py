"""The program's scope table (``obs/scopes.py``) against the programs
themselves: the epoch and exchange programs of the five decoders (at
the tiny sizes of their benchmark tests) and of ResNet18 are lowered on
the CPU and every operation's JAX path is read from
``lower().as_text(debug_info=True)``.

- every operation that is not plumbing (constants, a loop's counters and
  slices, the vmap's moves) lies under a name of the table, and inside
  ``model_loss`` under a deeper one;
- names nest as the table says, every name occurs in the program it is
  declared for, the fifteen names of the kernel readers still occur;
- the names add no operation: the text without locations is the same
  with every ``jax.named_scope`` a no-op;
- no new name holds a name the benchmark's three ``scope_of`` functions
  match by substring unless it is nested in that scope, and on old and
  new paths of one op they answer alike.
"""

import collections
import contextlib
import dataclasses
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for path in (HERE, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmarks.lib import cells, glm_work, scope_tree, xing_work  # noqa: E402
from benchmarks.lib import scopes as bench_scopes  # noqa: E402
from federated_pytorch_test_tpu.obs.scopes import (  # noqa: E402
    KERNEL_SCOPES,
    NAMES,
    SCOPES,
    scope,
)

#: which: (benchmark test with CELL and TINY or None, engine module,
#: the program tags of ``obs/scopes.py`` it answers to)
PROGRAMS = {
    "qwen3_next": ("test_lm_benchmark", "lm", ("decoder", "qwen3_next")),
    "glm4_moe_lite": ("test_glm_benchmark", "decoder",
                      ("decoder", "glm4_moe_lite")),
    "xing4_0": ("test_xing_benchmark", "decoder_hc", ("decoder", "xing4_0")),
    "zaya": ("test_zaya_benchmark", "decoder_tied", ("decoder", "zaya")),
    "olmo_hybrid": ("test_olmo_hybrid_benchmark", "decoder_dense",
                    ("decoder", "olmo_hybrid")),
    "resnet18": (None, "classifier", ()),
}
PARENTS = {s.name: s.parents for s in SCOPES}


def ancestors(name):
    out, todo = set(), list(PARENTS[name])
    while todo:
        at = todo.pop()
        if at not in out:
            out.add(at)
            todo += PARENTS[at]
    return out


# ----------------------------------------------------------------------
# lowering without running
# ----------------------------------------------------------------------
class _Captured(Exception):
    pass


def build_trainer(which):
    test, engine, _ = PROGRAMS[which]
    eng = importlib.import_module(f"benchmarks.engines.{engine}")
    if test is None:
        cell = cells.load_cell("resnet18_fedavg_fedsgd")
        cell = dataclasses.replace(cell, config={**cell.config, "batch": 4})
    else:
        tm = importlib.import_module(test)
        cell = cells.override(cells.load_cell(tm.CELL), tm.TINY)
        # two-byte products, as on the chip: operand()'s casts are ops
        cell = dataclasses.replace(
            cell, config={**cell.config, "dtype": "bfloat16"})
    return eng.build_trainer(
        cell, 7, K=2, samples_per_client=int(cell.config["batch"]),
        blocks=[int(cell.traffic["blocks"][0])], Nloop=1, Nadmm=1)


def lowered_programs(which):
    """``{"epoch": Lowered, "comm": Lowered}`` of the trainer's first
    round: the round loop runs with both programs replaced by recorders
    (the epoch's hands its state back, the exchange's stops the run)."""
    trainer = build_trainer(which)
    got, real = {}, trainer._build_fns
    jitted = lambda f: getattr(f, "__wrapped_jit__", f)

    def build(ci):
        train_epoch, comm_fns, init_opt = real(ci)

        def epoch(*args):
            got["epoch"] = jitted(train_epoch).lower(*args)
            return args[0], jnp.zeros((2,), jnp.float32)

        def exchange(*args):
            got["comm"] = jitted(comm_fns["plain"]).lower(*args)
            raise _Captured

        return epoch, {mode: exchange for mode in comm_fns}, init_opt

    trainer._build_fns = build
    with pytest.raises(_Captured):
        trainer.run(log=lambda m: None)
    trainer.close()
    return got


@pytest.fixture(scope="module")
def lowered():
    made = {}

    def get(which):
        if which not in made:
            made[which] = lowered_programs(which)
        return made[which]

    return get


# ----------------------------------------------------------------------
# every operation's path, from the text
# ----------------------------------------------------------------------
_LOC_DEF = re.compile(r"^(#loc\d*) = loc\((.*)\)$", re.M)
_NAME = re.compile(r'^"((?:[^"\\]|\\.)*)"')
_FUNC = re.compile(r"^\s*func\.func (?:public |private )?@([\w.$-]+)\(")
_OP = re.compile(r'^\s*(?:%[\w:#, %]+ = )?"?((?:stablehlo|chlo|sdy)\.[\w.]+'
                 r'|func\.call|call|return)"?[ (<]')
_CALLEE = re.compile(r"\bcall @([\w.$-]+)\(")
_TAIL = re.compile(r"loc\((#loc\d*)\)\s*$")


def op_paths(text):
    """``[(op kind, full JAX path, line), ...]`` of a module's text with
    debug info.  An outlined function's operations carry the path inside
    the function only; the call's own path goes before it, over every
    chain of calls from ``@main``."""
    names = {}
    for m in _LOC_DEF.finditer(text):
        n = _NAME.match(m.group(2))
        # a file location starts with its path: no name
        names[m.group(1)] = "" if n is None or n.group(1).startswith("/") \
            else n.group(1)
    funcs, cur = collections.defaultdict(list), None
    for line in text.split("\n"):
        f = _FUNC.match(line)
        if f:
            cur = f.group(1)
            continue
        o, t = _OP.match(line), _TAIL.search(line)
        if cur is None or not o or not t:
            continue
        callee = _CALLEE.search(line)
        funcs[cur].append((o.group(1), names.get(t.group(1), ""),
                           callee.group(1) if callee else None, line))
    out = []

    def walk(fn, prefix):
        for kind, name, callee, line in funcs[fn]:
            path = "/".join(p for p in (prefix, name) if p)
            if callee is not None:
                walk(callee, path)
            else:
                out.append((kind, path, line))

    walk("main", "")
    return out


def raw_chain(path):
    """The table's names in ``path`` as the path has them (before
    ``scope_tree.parse`` puts lifted ops back)."""
    return list(scope_tree.raw_chain(scope_tree.segments(path)[0]))


_ALWAYS = {"stablehlo.constant", "stablehlo.iota", "stablehlo.return",
           "return", "stablehlo.dynamic_slice",
           "stablehlo.dynamic_update_slice", "stablehlo.reshape",
           "stablehlo.broadcast_in_dim", "stablehlo.transpose",
           "stablehlo.optimization_barrier"}
_COUNTERS = {"stablehlo.add", "stablehlo.subtract", "stablehlo.compare",
             "stablehlo.select"}
_SCALAR = re.compile(r"tensor<(i32|i1|ui32)>")


def plumbing(kind, line):
    """Constants, a loop's slices and stacks, its scalar counters."""
    if kind in _ALWAYS:
        return True
    return kind in _COUNTERS and _SCALAR.search(line) is not None \
        and re.search(r"tensor<\d", line) is None


# ----------------------------------------------------------------------
@pytest.mark.parametrize("which", list(PROGRAMS))
def test_every_operation_lies_under_a_name(lowered, which):
    bare = collections.Counter()
    for program in ("epoch", "comm"):
        text = lowered(which)[program].as_text(debug_info=True)
        ops = op_paths(text)
        assert len(ops) > 20
        for kind, path, line in ops:
            chain = raw_chain(path)
            inner = chain[-1] if chain else ""
            # a decoder's layers are named below model_loss: what is left
            # to it (and to client_grad) is the loops' plumbing
            frame = inner in ("", "client_grad") or (
                inner == "model_loss" and which != "resnet18")
            if frame and not plumbing(kind, line):
                bare[(program, kind, path.rsplit("/", 1)[-1])] += 1
    # the exceptions: the epoch's sum of the steps' losses, and the sum
    # JAX's transpose makes of two cotangents of one value (the last
    # layer's output feeds the head and the MTP layer)
    assert set(bare) <= {("epoch", "stablehlo.reduce", "reduce_sum"),
                         ("epoch", "stablehlo.add", "add_any")}, bare
    assert sum(bare.values()) <= 2


@pytest.mark.parametrize("which", list(PROGRAMS))
def test_names_nest_as_the_table_says(lowered, which):
    """Where two names follow one another in a path the first is an
    ancestor of the second (JAX lifts loop-invariant ops out of a loop,
    so levels may be missing; they are never out of order), and the
    reader's ``canonical`` puts every chain where the table has it."""
    pairs, chains = set(), set()
    for program in ("epoch", "comm"):
        text = lowered(which)[program].as_text(debug_info=True)
        for _, path, _ in op_paths(text):
            chain = raw_chain(path)
            pairs.update(zip(chain, chain[1:]))
            chains.add(tuple(chain))
    assert pairs
    for outer, inner in pairs:
        assert outer in ancestors(inner), (outer, inner)
    for chain in chains:
        whole = scope_tree.canonical(chain)
        assert [n for n in whole if n in chain] == list(chain)
        for outer, inner in zip(whole, whole[1:]):
            assert outer in PARENTS[inner], (chain, whole)
        assert not whole or not PARENTS[whole[0]], (chain, whole)


@pytest.mark.parametrize("which", list(PROGRAMS))
def test_every_name_of_the_program_occurs(lowered, which):
    seen = {"epoch": set(), "comm": set()}
    for program in seen:
        text = lowered(which)[program].as_text(debug_info=True)
        for _, path, _ in op_paths(text):
            seen[program].update(raw_chain(path))
    tags = PROGRAMS[which][2]
    want_epoch = {s.name for s in SCOPES
                  if "epoch" in s.programs or set(tags) & set(s.programs)}
    want_comm = {s.name for s in SCOPES if "comm" in s.programs}
    assert want_epoch <= seen["epoch"], want_epoch - seen["epoch"]
    assert seen["comm"] == want_comm
    # and no name of another program's
    assert seen["epoch"] <= want_epoch, seen["epoch"] - want_epoch


def test_the_kernel_readers_names_still_occur(lowered):
    seen = set()
    for which in ("qwen3_next", "glm4_moe_lite", "xing4_0", "zaya"):
        text = lowered(which)["epoch"].as_text(debug_info=True)
        for _, path, _ in op_paths(text):
            seen.update(raw_chain(path))
    assert set(KERNEL_SCOPES) <= seen
    assert set(KERNEL_SCOPES) <= NAMES and len(KERNEL_SCOPES) == 15
    # the fourteen the three substring readers match, and the one core
    # zaya_work.py reads from the scope tree's whole segments
    assert set(bench_scopes.SCOPES) | set(glm_work.SCOPES) \
        | set(xing_work.SCOPES) | {"mtp", "mhc", "cca_core"} \
        == set(KERNEL_SCOPES)


@pytest.mark.parametrize("which", list(PROGRAMS))
def test_the_names_add_no_operation(lowered, which, monkeypatch):
    named = {k: v.as_text() for k, v in lowered(which).items()}
    assert "loc(" not in named["epoch"].split("\n", 1)[1][:2000]
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lowered_programs(which)
    text = bare["epoch"].as_text(debug_info=True)
    assert not any(raw_chain(path) for _, path, _ in op_paths(text))
    for program in ("epoch", "comm"):
        assert bare[program].as_text() == named[program], program


# ----------------------------------------------------------------------
# the kernel readers match by substring
# ----------------------------------------------------------------------
def test_an_undeclared_name_is_refused():
    with pytest.raises(ValueError, match="not declared"):
        scope("gdn_convolution")
    assert len(NAMES) == len(SCOPES)
    for s in SCOPES:
        assert set(s.parents) <= NAMES and s.programs and s.covers


@pytest.mark.parametrize("name", sorted(NAMES - set(KERNEL_SCOPES)))
def test_no_new_name_holds_a_kernel_readers_name(name):
    for listed in KERNEL_SCOPES:
        if listed in name:
            assert listed in ancestors(name), (name, listed)


_STEP = "jit(epoch_shard)/vmap()/while/body/closed_call/"
_OLD_STEP = _STEP + "while/body/closed_call/"
_NEW_STEP = _STEP + "client_grad/while/body/closed_call/"
#: (the op's path on the parent, its path now): from traces of PR 35 and
#: the nesting this PR adds around them
_OLD_AND_NEW = [
    (_OLD_STEP + "transpose(jvp(Qwen3Next))/while/body/closed_call/"
     "checkpoint/gated_attn/gated_attn/pallas_call:",
     _NEW_STEP + "transpose(jvp(model_loss))/Qwen3Next/sublayer_mixer/while/"
     "body/closed_call/checkpoint/gated_attn/gated_attn/pallas_call:"),
    (_OLD_STEP + "jvp(Qwen3Next)/while/body/closed_call/checkpoint/gdn/"
     "mul:",
     _NEW_STEP + "jvp(model_loss)/Qwen3Next/sublayer_mixer/while/body/"
     "closed_call/checkpoint/gdn/gdn_conv/mul:"),
    (_OLD_STEP + "jvp(Qwen3Next)/while/body/closed_call/checkpoint/gdn/"
     "gdn_scan/gated_delta_chunked/hnid,hnjd->hnij/dot_general:",
     _NEW_STEP + "jvp(model_loss)/Qwen3Next/sublayer_mixer/while/body/"
     "closed_call/checkpoint/gdn/gdn_scan/gated_delta_chunked/"
     "hnid,hnjd->hnij/dot_general:"),
    (_OLD_STEP + "jvp(Glm4MoeLite)/mtp/checkpoint/moe_route/while/body/"
     "dynamic_update_slice:",
     _NEW_STEP + "jvp(model_loss)/Glm4MoeLite/mtp/sublayer_ffn/checkpoint/"
     "moe_route/pair_dispatch/while/body/dynamic_update_slice:"),
    (_OLD_STEP + "transpose(jvp(Glm4MoeLite))/mtp/jvp(Glm4MoeLite)/mtp/"
     "checkpoint/mla_attn/mla_core/mtp/mla_attn/mla_core/pallas_call:",
     _NEW_STEP + "transpose(jvp(model_loss))/Glm4MoeLite/mtp/sublayer_mixer/"
     "jvp(model_loss)/Glm4MoeLite/mtp/sublayer_mixer/checkpoint/mla_attn/"
     "mla_core/mtp/mla_attn/mla_core/pallas_call:"),
    (_OLD_STEP + "jvp(Xing4)/checkpoint/mhc/mhc_maps/n...c,ncm->m.../"
     "dot_general:",
     _NEW_STEP + "jvp(model_loss)/Xing4/sublayer_ffn/checkpoint/mhc/"
     "mhc_maps/n...c,ncm->m.../dot_general:"),
    (_OLD_STEP + "jvp(Xing4)/checkpoint/moe_experts/convert_element_type:",
     _NEW_STEP + "jvp(model_loss)/Xing4/sublayer_ffn/checkpoint/moe_experts/"
     "expert_cast/convert_element_type:"),
    (_OLD_STEP + "jvp(Glm4MoeLite)/while/body/closed_call/lm_head_loss/"
     "dot_general:",
     _NEW_STEP + "jvp(model_loss)/Glm4MoeLite/while/body/closed_call/"
     "lm_head_loss/head_product/dot_general:"),
    # what had no name has none for the kernel readers now
    (_STEP + "add:", _STEP + "opt_update/add:"),
    (_OLD_STEP + "jvp(Qwen3Next)/gather:",
     _NEW_STEP + "jvp(model_loss)/Qwen3Next/embed/gather:"),
    (_OLD_STEP + "jvp(Glm4MoeLite)/mtp/dot_general:",
     _NEW_STEP + "jvp(model_loss)/Glm4MoeLite/mtp/mtp_merge/dot_general:"),
    (_OLD_STEP + "jvp(Qwen3Next)/add:",
     _NEW_STEP + "jvp(model_loss)/Qwen3Next/sublayer_mixer/add:"),
    ("", ""),
]


@pytest.mark.parametrize("reader", [bench_scopes, glm_work, xing_work],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_the_kernel_readers_answer_alike_on_old_and_new_paths(reader):
    answers = set()
    for old, new in _OLD_AND_NEW:
        assert reader.scope_of(new) == reader.scope_of(old), new
        answers.add(reader.scope_of(new))
    assert len(answers) >= 5 and "" in answers
    assert reader.scope_of("", "ragged-dot-general.7") == "moe_experts"
    through = getattr(reader, "_THROUGH_MTP", None) or getattr(
        reader, "_THROUGH_MHC", None)
    if through is not None:
        for old, new in _OLD_AND_NEW:
            assert bool(through.search(new)) == bool(through.search(old))
