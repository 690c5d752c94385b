"""``benchmarks/lib/scope_tree.py`` and the five readers this PR adds
beside the benchmark (``unscoped_busy_pct``, ``opt_update_busy_pct``,
``remat_busy_pct``, ``dispatch_new_signatures``, ``dispatch_max_ms``) on
hand-written events and records, on the recorded TPU trace, and through
``benchmarks/run.py --trace 1 --rehearse`` with the readers listed by
the test itself (no cell's file lists them yet: PERF.md section 7).
"""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import scope_tree as st, scopes, xplane  # noqa: E402
from benchmarks.lib.records import Records, TraceView  # noqa: E402
from benchmarks.lib.window import Pass  # noqa: E402

TINY_TRACE = os.path.join(REPO, "benchmarks", "testdata",
                          "tiny_tpu.xplane.pb")
READERS = ("unscoped_busy_pct", "opt_update_busy_pct", "remat_busy_pct",
           "dispatch_new_signatures", "dispatch_max_ms")
STEP = "jit(epoch_shard)/vmap()/while/body/closed_call/"
GRAD = STEP + "client_grad/while/body/closed_call/"
FWD = GRAD + "jvp(model_loss)/Qwen3Next/"
BWD = GRAD + "transpose(jvp(model_loss))/Qwen3Next/"


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}")


def op(name, start, dur, category="loop fusion"):
    return xplane.Op(name, float(start), float(dur), category)


# ----------------------------------------------------------------------
# one path
# ----------------------------------------------------------------------
@pytest.mark.parametrize("path,chain,direction", [
    (FWD + "sublayer_mixer/while/body/closed_call/checkpoint/gdn/gdn_conv/"
     "mul:", ("client_grad", "model_loss", "sublayer_mixer", "gdn",
              "gdn_conv"), "forward"),
    # a backward path holds its scopes twice
    (BWD + "sublayer_ffn/jvp(model_loss)/Qwen3Next/sublayer_ffn/checkpoint/"
     "moe_route/pair_dispatch/pair_fill/broadcast_in_dim:",
     ("client_grad", "model_loss", "sublayer_ffn", "moe_route",
      "pair_dispatch", "pair_fill"), "backward"),
    (BWD + "sublayer_ffn/jvp(model_loss)/Qwen3Next/sublayer_ffn/checkpoint/"
     "rematted_computation/moe_experts/expert_mask/select_n:",
     ("client_grad", "model_loss", "sublayer_ffn", "moe_experts",
      "expert_mask"), "remat"),
    # the rule of a custom_vjp opens its scope again
    (BWD + "mtp/jvp(model_loss)/Glm/mtp/sublayer_mixer/checkpoint/mla_attn/"
     "mla_core/mtp/mla_attn/mla_core/pallas_call:",
     ("client_grad", "model_loss", "mtp", "sublayer_mixer", "mla_attn",
      "mla_core"), "backward"),
    # lifted out of its loop by JAX: put back where the table has it
    (GRAD + "gdn/sublayer_norm/add:",
     ("client_grad", "model_loss", "sublayer_mixer", "gdn",
      "sublayer_norm"), "forward"),
    # a parent commit's path: the old names under the new table
    (STEP + "while/body/closed_call/jvp(Qwen3Next)/while/body/closed_call/"
     "checkpoint/gdn/gdn_scan/dot_general:",
     ("client_grad", "model_loss", "sublayer_mixer", "gdn", "gdn_scan"),
     "forward"),
    (STEP + "opt_update/add:", ("opt_update",), "forward"),
    # the fused primal kernel: JAX traces it outside the checkpointed
    # mixer's scopes in a gradient's primal pass, and it opens gdn_scan
    # again (ops/gated_delta.py); in a plain forward gdn_scan twice
    (FWD + "sublayer_mixer/while/body/closed_call/closed_call:/gdn_scan/"
     "gdn_fused/pallas_call:",
     ("client_grad", "model_loss", "sublayer_mixer", "gdn", "gdn_scan",
      "gdn_fused"), "forward"),
    (FWD + "sublayer_mixer/while/body/closed_call/checkpoint/gdn/gdn_scan/"
     "gdn_scan/gdn_fused/pallas_call:",
     ("client_grad", "model_loss", "sublayer_mixer", "gdn", "gdn_scan",
      "gdn_fused"), "forward"),
    # a name is a whole segment: no substring counts
    (STEP + "my_opt_update_2/embedding/add:", (), "forward"),
    (STEP + "while:", (), "forward"),
    ("jit(f)/vmap(transpose(vmap(jvp(jit(_diag)))))/jit(diagonal)/gather:",
     (), "backward"),
    ("jit(f)/vmap(jvp(penalty))/mul:", ("client_grad", "penalty"),
     "forward"),
    # the fourth decoder: what compression adds around the core, the
    # core's backward rule under both its names, the router that is an
    # MLP, the scaled merge under either sub-layer
    (GRAD + "jvp(model_loss)/Zaya/sublayer_mixer/while/body/closed_call/"
     "checkpoint/cca_attn/cca_mix/htd,hde->hte/dot_general:",
     ("client_grad", "model_loss", "sublayer_mixer", "cca_attn", "cca_mix"),
     "forward"),
    (GRAD + "transpose(jvp(model_loss))/Zaya/sublayer_mixer/"
     "jvp(model_loss)/Zaya/sublayer_mixer/while/body/closed_call/checkpoint/"
     "cca_attn/cca_core/cca_attn/cca_core/pallas_call:",
     ("client_grad", "model_loss", "sublayer_mixer", "cca_attn", "cca_core"),
     "backward"),
    (GRAD + "transpose(jvp(model_loss))/Zaya/sublayer_ffn/"
     "jvp(model_loss)/Zaya/sublayer_ffn/checkpoint/rematted_computation/"
     "moe_route/route_mlp/dot_general:",
     ("client_grad", "model_loss", "sublayer_ffn", "moe_route", "route_mlp"),
     "remat"),
    (GRAD + "jvp(model_loss)/Zaya/sublayer_ffn/checkpoint/res_scale/mul:",
     ("client_grad", "model_loss", "sublayer_ffn", "res_scale"), "forward"),
    # lifted out of the map over sequences: put back under the mixer
    (GRAD + "res_scale/mul:",
     ("client_grad", "model_loss", "sublayer_mixer", "res_scale"),
     "forward"),
    (GRAD + "cca_core/attn_layout/convert_element_type:",
     ("client_grad", "model_loss", "sublayer_mixer", "cca_attn", "cca_core",
      "attn_layout"), "forward"),
    ("", (), "forward"),
])
def test_parse(path, chain, direction):
    assert st.parse(path) == (chain, direction)


def test_segments_peel_transforms_and_split_outside_brackets():
    names, transforms = st.segments(
        "jit(step)/transpose(jvp(mla_attn/mla_core))/reshape;checkpoint/x:")
    assert names == ["step", "mla_attn", "mla_core", "reshape",
                     "checkpoint", "x"]
    assert transforms == ["jit", "transpose", "jvp"]
    assert st.segments("a/b:custom-call")[0] == ["a", "b"]


@pytest.mark.parametrize("name,category,path,rule", [
    ("ragged-dot-general.3", "custom-call", "", "xla_ragged_dot"),
    ("fusion.9", "custom fusion", "ragged-dot.1", "xla_ragged_dot"),
    ("copy-done.10", "copy-done", STEP + "while:", "xla_async_copy"),
    ("slice-start.2", "async-start", "", "xla_async_copy"),
    ("broadcast.4974.clone", "broadcast", STEP + "closed_call:",
     "xla_fill"),
    ("all-reduce-start.1", "all-reduce-start", "", ""),
    ("fusion.1777", "custom fusion", STEP + "while:", ""),
    ("copy.51", "data formatting", "", ""),
])
def test_rule_of(name, category, path, rule):
    assert st.rule_of(op(name, 0, 1, category), path) == rule


# ----------------------------------------------------------------------
# one chip's tree
# ----------------------------------------------------------------------
def pass_of_events():
    """A hand-written pass over [0, 1000] ns: 900 ns busy."""
    conv = FWD + "sublayer_mixer/checkpoint/gdn/gdn_conv/mul:"
    remat = BWD + ("sublayer_mixer/jvp(model_loss)/Qwen3Next/sublayer_mixer/"
                   "checkpoint/rematted_computation/gdn/gdn_conv/mul:")
    back = BWD + ("sublayer_mixer/jvp(model_loss)/Qwen3Next/sublayer_mixer/"
                  "checkpoint/gdn/gdn_conv/mul:")
    return [
        (op("while.1", 0, 1000, "while"), STEP + "while:"),   # a container
        (op("fusion.1", 0, 100), conv),
        (op("fusion.2", 100, 50), remat),
        (op("fusion.3", 150, 150), back),
        (op("fusion.4", 300, 100), FWD + "sublayer_mixer/add:"),
        (op("copy-done.1", 400, 40, "copy-done"),
         FWD + "sublayer_mixer/while:"),
        (op("multiply_add_fusion.7", 440, 60), STEP + "opt_update/add:"),
        (op("ragged-dot-general.2", 500, 100, "custom-call"), ""),
        (op("copy-done.2", 600, 30, "copy-done"), STEP + "while:"),
        (op("broadcast.5.clone", 630, 20, "broadcast"), STEP),
        (op("fusion.8", 650, 100), STEP + "while:"),
        (op("convert.3", 750, 50, "non-fusion elementwise"), ""),
        # overlaps fusion.8's last 20 ns and runs past the window
        (op("fusion.9", 730, 20), FWD + "embed/gather:"),
        (op("fusion.10", 900, 200), FWD + "embed/gather:"),
    ]


def test_tree_self_time_directions_buckets_and_the_rest():
    tree = st.tree_of(st.leaves(pass_of_events()), 0.0, 1000.0)
    ns = lambda sec: round(sec * 1e9, 6)
    node = lambda key: ([ns(v) for v in tree["nodes"][key]["s"]],
                        [ns(v) for v in tree["nodes"][key]["self"]])
    assert ns(tree["busy_s"]) == 900
    conv = "client_grad/model_loss/sublayer_mixer/gdn/gdn_conv"
    assert node(conv) == ([100, 50, 150], [100, 50, 150])
    assert node("client_grad/model_loss/sublayer_mixer/gdn") == (
        [100, 50, 150], [0, 0, 0])
    # the residual add and the compiler's copy on the map's loop are the
    # sub-layer's own time
    assert node("client_grad/model_loss/sublayer_mixer") == (
        [240, 50, 150], [140, 0, 0])
    # an instant two ops share goes to the one that started first; what
    # lies past the window is cut
    assert node("client_grad/model_loss/embed") == ([100, 0, 0], [100, 0, 0])
    assert node("client_grad") == ([340, 50, 150], [0, 0, 0])
    assert node("opt_update") == ([60, 0, 0], [60, 0, 0])
    assert {k: ns(v) for k, v in tree["xla"].items()} == {
        "xla_ragged_dot": 100, "xla_async_copy": 30, "xla_fill": 20}
    # the rules over all ops count the named copy too
    assert ns(tree["kinds"]["xla_async_copy"]) == 70
    assert ns(tree["unnamed_s"]) == 150
    assert [(r[0], r[1], r[4]) for r in tree["unnamed_top"]] == [
        ("fusion.8", "loop fusion", 1),
        ("convert.3", "non-fusion elementwise", 1)]
    assert ns(st.parts_s(tree)) == ns(tree["busy_s"])
    # containers are left out
    assert "while.1" not in {r[0] for r in tree["unnamed_top"]}
    assert ns(st.scope_seconds(tree, "gdn_conv")) == 300
    assert ns(st.scope_seconds(tree, "gdn_conv", "remat")) == 50
    assert ns(st.scope_seconds(tree, "moe_experts")) == 100   # the kernels


def test_tree_of_the_fourth_decoder_s_scopes_adds_up():
    """A pass with the names PR 38 adds: every new scope is a node under
    ``sublayer_mixer`` or ``sublayer_ffn``, the mixer's node holds its
    parts, and the parts add up to the busy time."""
    zaya = GRAD + "jvp(model_loss)/Zaya/"
    mixer = zaya + "sublayer_mixer/while/body/closed_call/checkpoint/"
    ffn = zaya + "sublayer_ffn/checkpoint/"
    back = GRAD + ("transpose(jvp(model_loss))/Zaya/sublayer_mixer/"
                   "jvp(model_loss)/Zaya/sublayer_mixer/while/body/"
                   "closed_call/checkpoint/")
    pass_ = [
        (op("while.1", 0, 1000, "while"), STEP + "while:"),
        (op("fusion.1", 0, 100), mixer + "cca_attn/attn_proj_in/dot_general:"),
        (op("fusion.2", 100, 60), mixer + "cca_attn/cca_mix/mul:"),
        (op("fusion.3", 160, 40), mixer + "cca_attn/attn_norm_rope/rsqrt:"),
        (op("cca_core.4", 200, 150, "custom-call"),
         mixer + "cca_attn/cca_core/pallas_call:"),
        (op("cca_core.5", 350, 250, "custom-call"),
         back + "cca_attn/cca_core/cca_attn/cca_core/pallas_call:"),
        (op("fusion.6", 600, 30), mixer + "res_scale/add:"),
        (op("fusion.7", 630, 70), ffn + "moe_route/route_mlp/dot_general:"),
        (op("fusion.8", 700, 20), ffn + "moe_route/route_scores/reduce_max:"),
        (op("fusion.9", 720, 30), ffn + "res_scale/add:"),
        (op("ragged-dot-general.1", 750, 150, "custom-call"), ""),
    ]
    tree = st.tree_of(st.leaves(pass_), 0.0, 1000.0)
    ns = lambda sec: round(sec * 1e9, 6)
    total = lambda key: ns(sum(tree["nodes"][key]["s"]))
    top = "client_grad/model_loss/"
    assert total(top + "sublayer_mixer/cca_attn") == 600
    assert total(top + "sublayer_mixer/cca_attn/cca_core") == 400
    assert [ns(v) for v in tree["nodes"][
        top + "sublayer_mixer/cca_attn/cca_core"]["s"]] == [150, 0, 250]
    assert total(top + "sublayer_mixer/cca_attn/cca_mix") == 60
    assert total(top + "sublayer_mixer/res_scale") == 30
    assert total(top + "sublayer_ffn/res_scale") == 30
    assert total(top + "sublayer_ffn/moe_route/route_mlp") == 70
    assert ns(sum(tree["nodes"][top + "sublayer_mixer/cca_attn"]["self"])) \
        == 0
    assert ns(st.scope_seconds(tree, "res_scale")) == 60
    assert ns(st.scope_seconds(tree, "cca_core", "backward")) == 250
    assert ns(tree["busy_s"]) == 900 == ns(st.parts_s(tree))
    assert ns(tree["unnamed_s"]) == 0


def test_tree_of_the_dense_decoder_s_scopes_adds_up():
    """A pass with the dense decoder's names: the attention mixer holds
    its kernels as self time, the reordered merge is a node under either
    sub-layer (also where the compiler lifted it out of the map over
    sequences), the matrices' cast once a step is a node of the frame, and
    the Gated DeltaNet mixer has the sibling's children."""
    olmo = GRAD + "jvp(model_loss)/OlmoHybrid/"
    mixer = olmo + "sublayer_mixer/while/body/closed_call/checkpoint/"
    back = GRAD + ("transpose(jvp(model_loss))/OlmoHybrid/sublayer_mixer/"
                   "jvp(model_loss)/OlmoHybrid/sublayer_mixer/while/body/"
                   "closed_call/checkpoint/")
    pass_ = [
        (op("while.1", 0, 1000, "while"), STEP + "while:"),
        (op("fusion.1", 0, 50), olmo + "weight_cast/convert_element_type:"),
        (op("fusion.2", 50, 100), mixer + "gdn/gdn_in_proj/dot_general:"),
        (op("gdn_scan.3", 150, 150, "custom-call"),
         mixer + "gdn/gdn_scan/pallas_call:"),
        (op("fusion.4", 300, 40), mixer + "post_norm/add:"),
        (op("fusion.5", 340, 60), mixer + "mha_attn/attn_norm_rope/rsqrt:"),
        (op("mha.6", 400, 100, "custom-call"),
         mixer + "mha_attn/pallas_call:"),
        (op("mha.7", 500, 200, "custom-call"),
         back + "mha_attn/mha_attn/pallas_call:"),
        (op("fusion.8", 700, 150), olmo + "sublayer_ffn/checkpoint/"
         "dense_mlp/dot_general:"),
        (op("fusion.9", 850, 30), olmo + "sublayer_ffn/checkpoint/"
         "post_norm/add:"),
        # lifted out of the map over sequences: put back under the mixer
        (op("fusion.10", 880, 20), GRAD + "post_norm/add:"),
    ]
    tree = st.tree_of(st.leaves(pass_), 0.0, 1000.0)
    ns = lambda sec: round(sec * 1e9, 6)
    total = lambda key: ns(sum(tree["nodes"][key]["s"]))
    top = "client_grad/model_loss/"
    assert total(top + "weight_cast") == 50
    assert total(top + "sublayer_mixer/gdn") == 250
    assert total(top + "sublayer_mixer/gdn/gdn_scan") == 150
    assert total(top + "sublayer_mixer/mha_attn") == 360
    assert [ns(v) for v in tree["nodes"][
        top + "sublayer_mixer/mha_attn"]["self"]] == [100, 0, 200]
    assert total(top + "sublayer_mixer/post_norm") == 60
    assert total(top + "sublayer_ffn/post_norm") == 30
    assert total(top + "sublayer_ffn/dense_mlp") == 150
    assert ns(st.scope_seconds(tree, "post_norm")) == 90
    assert ns(st.scope_seconds(tree, "mha_attn", "backward")) == 200
    assert ns(tree["busy_s"]) == 900 == ns(st.parts_s(tree))
    assert ns(tree["unnamed_s"]) == 0


def test_under_and_the_two_tables():
    found = st.leaves(pass_of_events())
    below = st.under(found, "sublayer_mixer")
    tree = st.tree_of(below, 0.0, 1000.0)
    assert set(tree["nodes"]) == {"sublayer_mixer", "sublayer_mixer/gdn",
                                  "sublayer_mixer/gdn/gdn_conv"}
    rows = st.by_direction(st.tree_of(found, 0.0, 1000.0))
    assert [r[1] for r in rows[:4]] == ["client_grad", "model_loss",
                                        "sublayer_mixer", "gdn"]
    assert [r[0] for r in rows[:4]] == [0, 1, 2, 3]
    assert [r[1] for r in rows[-4:]] == [*st.BUCKETS, "(unnamed)"]
    top = st.by_instruction(below, 0.0, 1000.0, top=2)
    assert [(r[0], r[1], r[2]) for r in top] == [
        ("sublayer_mixer/gdn/gdn_conv", "backward", "fusion"),
        ("sublayer_mixer/gdn/gdn_conv", "forward", "fusion")]


def test_rows_stand_for_the_pass():
    found = st.leaves(pass_of_events())
    rows = st.rows_of(found, 0.0, 1000.0)
    again, t0, t1 = st.from_rows(json.loads(json.dumps(rows)))
    a, b = st.tree_of(found, 0.0, 1000.0), st.tree_of(again, t0, t1)
    assert b["nodes"].keys() == a["nodes"].keys()
    assert b["busy_s"] == pytest.approx(a["busy_s"])
    assert b["unnamed_s"] == pytest.approx(a["unnamed_s"])
    assert sum(r[4] for r in rows) == 12


def test_recorded_trace_lands_in_the_remainder():
    """The recorded TPU trace is of a program without the table's scopes:
    nothing is named, the copies go to their rule, the parts add up to
    the busy time of the leaf ops."""
    loaded = st.load(TINY_TRACE)
    t0, t1 = st._window(TINY_TRACE)
    assert scopes.scope_of("a/gdn/b") == "gdn"     # handed back after load
    (plane, found), = loaded.items()
    assert len(found) == 123
    tree = st.tree_of(found, t0, t1)
    busy = xplane.busy_ns([f.op for f in found], t0, t1) / 1e9
    assert tree["nodes"] == {}
    assert tree["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert st.parts_s(tree) == pytest.approx(busy, rel=1e-9)
    assert tree["xla"]["xla_async_copy"] > 0
    assert tree["unnamed_s"] > 0.7 * busy
    assert tree["unnamed_top"][0][:2] == ["convert_reduce_fusion",
                                          "convolution fusion"]


def test_the_command_prints_the_table(tmp_path, capsys):
    prof = tmp_path / "plugins" / "profile" / "x"
    prof.mkdir(parents=True)
    (prof / "t.xplane.pb").write_bytes(open(TINY_TRACE, "rb").read())
    rows = str(tmp_path / "rows.json")
    assert st.main(["--workload", "none", "--trace-dir", str(tmp_path),
                    "--dump", rows]) == 0
    out = capsys.readouterr().out
    assert "/device:TPU:0: busy" in out and "(unnamed)" in out
    assert st.main(["--workload", "none", "--rows", rows, "--by",
                    "instruction", "--top", "3"]) == 0
    assert "convert_reduce_fusion" in capsys.readouterr().out
    assert st.main(["--workload", "none", "--trace-dir",
                    str(tmp_path / "nothing")]) == 1


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def view_of(chips):
    return TraceView({plane: [o for o, _ in ops]
                      for plane, ops in chips.items()}, (0.0, 1000.0), [],
                     "TPU v5 lite")


def test_scope_readers_take_the_worst_of_two_chips(monkeypatch, capsys):
    first = pass_of_events()
    # the second chip: the same pass with Adam twice as long
    second = [(op(o.name, o.start_ns, 120 if "add_fusion" in o.name
                  else o.dur_ns, o.category), p) for o, p in first]
    chips = {"/device:TPU:0": first, "/device:TPU:1": second}
    monkeypatch.setattr(st, "trace_path", lambda name: "fake.xplane.pb")
    monkeypatch.setattr(st, "load", lambda path: {
        plane: st.leaves(ops) for plane, ops in chips.items()})
    monkeypatch.setattr(st, "_TREES", {})
    cell, view = types.SimpleNamespace(name="c"), view_of(chips)
    # chip 0: busy 900; chip 1: Adam's 120 ns hide 60 ns of the kernels
    assert reader("opt_update_busy_pct").read(None, view, cell) == \
        pytest.approx(100.0 * 120 / 900)
    assert reader("unscoped_busy_pct").read(None, view, cell) == \
        pytest.approx(100.0 * 150 / 900)
    assert reader("remat_busy_pct").read(None, view, cell) == \
        pytest.approx(100.0 * 50 / 900)
    printed = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("scope_tree=")]
    assert len(printed) == 1                       # once per trace
    tree = json.loads(printed[0][len("scope_tree="):])
    assert tree["busy_s"] == tree["parts_s"]
    assert set(tree["nodes"]["opt_update"]) == {"s", "self"}
    assert set(tree) == {"busy_s", "parts_s", "nodes", "xla", "kinds",
                         "unnamed_s", "unnamed_top"}
    for name in READERS[:3]:
        assert reader(name).UNIT == "%"
        assert reader(name).read(None, None, cell) is None      # untraced


def test_scope_readers_read_nothing_on_a_parent(monkeypatch):
    """A program without the scope (the recorded trace) and a checkout
    without the table."""
    cell = types.SimpleNamespace(name="c")
    t0, t1 = st._window(TINY_TRACE)
    view = TraceView({}, (t0, t1), [], "TPU v5 lite")
    monkeypatch.setattr(st, "trace_path", lambda name: TINY_TRACE)
    monkeypatch.setattr(st, "_TREES", {})
    assert reader("opt_update_busy_pct").read(None, view, cell) is None
    assert reader("remat_busy_pct").read(None, view, cell) is None
    assert reader("unscoped_busy_pct").read(None, view, cell) > 70
    monkeypatch.setattr(st, "NAMES", frozenset())
    monkeypatch.setattr(st, "_TREES", {})
    for name in READERS[:3]:
        assert reader(name).read(None, view, cell) is None


def records_of(*passes, warmup=()):
    return Records(warmup=list(warmup), passes=[
        Pass(list(recs), 0.0, 1.0, traced=traced) for traced, recs in passes],
        samples_per_round=1, chips=1, counters={})


def test_dispatch_readers_on_hand_written_records(capsys):
    r = lambda **kw: {"round_seconds": 1.0, "dispatch_seconds": 0.004,
                      "dispatch_max_seconds": 0.003,
                      "dispatch_max_site": "train_epoch[blk=1]", **kw}
    slow = r(dispatch_new_signatures=1, dispatch_max_seconds=1.1,
             dispatch_seconds=1.2)
    recs = records_of(
        (False, [slow, r()]), (True, [r(dispatch_max_seconds=0.5,
                                        dispatch_new_signatures=2)]),
        (False, [r(), r()]),
        warmup=[r(dispatch_new_signatures=2,
                  dispatch_max_site="comm[plain,blk=1]")])
    assert reader("dispatch_new_signatures").read(recs, None, None) == 3
    assert reader("dispatch_new_signatures").UNIT == "count"
    # the profiled pass is left out of the slowest call, not of the count
    assert reader("dispatch_max_ms").read(recs, None, None) == \
        pytest.approx(1100.0)
    assert reader("dispatch_max_ms").UNIT == "ms"
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("new_signatures=")]
    assert json.loads(line[len("new_signatures="):]) == [
        [-1, 0, "comm[plain,blk=1]", 3.0, 2],
        [0, 0, "train_epoch[blk=1]", 1100.0, 1],
        [1, 0, "train_epoch[blk=1]", 500.0, 2]]
    reader("dispatch_new_signatures").read(recs, None, None)
    assert "new_signatures=" not in capsys.readouterr().out     # once
    # a window without one reads 0 where the counter is there
    quiet = records_of((False, [r(), r()]))
    assert reader("dispatch_new_signatures").read(quiet, None, None) == 0


def test_dispatch_readers_read_nothing_on_a_parent():
    """The parent writes ``dispatch_seconds`` alone; a run with the cost
    ledger off writes neither."""
    parent = records_of((False, [{"round_seconds": 1.0,
                                  "dispatch_seconds": 0.004}]))
    bare = records_of((False, [{"round_seconds": 1.0}]))
    for recs in (parent, bare):
        assert reader("dispatch_new_signatures").read(recs, None, None) \
            is None
        assert reader("dispatch_max_ms").read(recs, None, None) is None


# ----------------------------------------------------------------------
# through the harness
# ----------------------------------------------------------------------
@pytest.mark.long(seconds=94)
def test_the_readers_in_a_rehearsed_traced_run():
    """The Qwen3-Next cell through ``benchmarks/run.py --trace 1`` at the
    rehearsal's tiny size with the five names listed by this test: the
    two counters are in the result line, the scope readers are left out
    (no TPU plane on a CPU), and the counter says which dispatch of the
    window met a new signature."""
    from test_lm_benchmark import CELL, TINY

    code = (
        "import dataclasses, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from benchmarks.lib import cells\n"
        "import benchmarks.run as run\n"
        "load = cells.load_cell\n"
        f"new = {list(READERS)!r}\n"
        "cells.load_cell = lambda name: (lambda c: dataclasses.replace(\n"
        "    c, per_layer=c.per_layer + new))(load(name))\n"
        f"sys.exit(run.main(['--workload', {CELL!r}, '--seed', '3000000019',"
        " '--seconds', '1', '--trace', '1', '--rehearse',"
        f" {json.dumps(TINY)!r}]))\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          cwd=REPO, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, proc.stdout[-3000:]
    metrics = result["metrics"]
    assert metrics["dispatch_max_ms"]["unit"] == "ms"
    assert metrics["dispatch_max_ms"]["value"] > 0
    assert metrics["dispatch_new_signatures"]["unit"] == "count"
    assert not set(READERS[:3]) & set(metrics)
    assert {"train_pct", "moe_load_max_over_mean"} <= set(metrics)
    line, = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("new_signatures=")]
    met = json.loads(line[len("new_signatures="):])
    # one device: round 2 of every block of the untimed pass meets the
    # signature its programs' own outputs have, and the first timed round
    # the one the whole schedule's leaves
    assert [m[:2] for m in met if m[0] == -1] == [[-1, 1], [-1, 3], [-1, 5]]
    assert metrics["dispatch_new_signatures"]["value"] == sum(
        m[4] for m in met if m[0] >= 0)
