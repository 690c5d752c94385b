"""The ``zaya1_fedavg_cca_blocks`` cell's harness on the CPU: the
configuration file against the catalog row, the cell's rehearsal through
``benchmarks/run.py --rehearse`` (untraced and traced), its entries in
``BENCHMARK.json``, the work model of ``benchmarks/lib/zaya_work.py``
against counts by hand, the five new readers on synthetic device events
(a share above 100 % is a failure here as it is for the driver), and the
engine's check failing on a float8 probe at the small size.
"""

import dataclasses
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import cells, peaks, scope_tree, xplane, zaya_work  # noqa: E402
from benchmarks.lib.records import Records, TraceView  # noqa: E402
from benchmarks.lib.window import Pass  # noqa: E402

CELL = "zaya1_fedavg_cca_blocks"
NEW = ["zaya_step_mfu_pct", "cca_attn_busy_pct", "cca_mix_busy_pct",
       "cca_core_roofline_pct", "route_mlp_busy_pct"]
#: tiny widths; matrices seeded at 0.2 so that a sub-layer's output is as
#: large beside the embedding as at the published widths; float32
#: products, because 32-wide bfloat16 contractions over weights of 0.2
#: miss ``engines/lm.py``'s round-loss limit that the published widths
#: keep by two orders (``tests/test_xing_benchmark.py``)
TINY = {"config": {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "moe_intermediate_size": 24, "num_experts": 8,
    "experts_held": 4, "ep_rank": 1, "router_hidden_size": 16,
    "vocab_rows": 64, "attn_block": 16, "seq_len": 24, "lr": 0.001,
    "init_scale": 0.2, "bias_scale": 0.05, "dtype": "float32"},
    "traffic": {"samples_per_client": 4, "check_moved_share": 0.05}}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return __import__(f"benchmarks.metrics.{name}", fromlist=["read"])


# ----------------------------------------------------------------------
# the configuration file
# ----------------------------------------------------------------------
def test_the_configuration_file_holds_the_catalog_s_values():
    """Every key of the catalog row's ``config`` is in the configuration
    file under the same key, unchanged (the row is copied here: the
    guides are not part of the repository)."""
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272}
    config = cells.load_cell(CELL).config
    for key, value in published.items():
        assert config[key] == value and type(config[key]) is type(value), key
    assert config["reduced"] == [
        "layers", "experts_held", "vocab_rows", "K", "samples_per_client",
        "rounds_per_block", "dataset"]
    assert set(config["reduced"]) == set(config["reduced_notes"])
    assert not set(config["reduced"]) & set(published)      # no width cut
    assert config["source"] == ("https://huggingface.co/Zyphra/ZAYA1-8B/"
                                "blob/main/config.json")
    # nothing under the floors: four layers of the one-layer period,
    # eight experts, an eighth of the vocabulary, two clients
    assert config["layers"] == 6 >= 4 and config["K"] >= 2
    assert config["experts_held"] == 8 and config["num_experts"] == 16
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    assert {"cca_convolutions", "cca_mean", "cca_unit_norm",
            "cca_value_shift", "rotary_layout", "router", "router_rule",
            "residual_merge", "init", "tokens", "pair_rows_factor",
            "seq_len"} <= set(config["assumed"])
    assert "2 chips" in config["deployment"] and config["ep_rank"] == 0
    assert any("skip" in d and "NOT built" in d
               for d in config["departures"])
    assert any("one leaf" in d for d in config["departures"])
    assert any("float32 router" in g for g in config["guarantees"])
    assert any("no token is dropped" in g for g in config["guarantees"])
    assert config["params"] == 708_665_036
    assert (config["model"], config["engine"]) == ("zaya", "decoder_tied")
    assert (config["K"], config["batch"], config["seq_len"], config["lr"],
            config["dtype"], config["pair_rows_factor"]) == (
        2, 2, 4096, 1e-4, "bfloat16", 2.0)


def test_the_engine_builds_the_model_from_the_configuration_s_keys():
    from benchmarks.engines import decoder_tied

    m = decoder_tied.build_model(cells.load_cell(CELL).config)
    assert type(m).__name__ == "Zaya"
    assert (m.hidden_size, m.num_attention_heads, m.num_key_value_heads,
            m.head_dim, m.cca_time0, m.cca_time1, m.router_hidden_size,
            m.num_experts, m.num_experts_per_tok, m.experts_held, m.layers,
            m.vocab_rows, m.moe_intermediate_size) == (
        2048, 8, 2, 128, 2, 2, 256, 16, 1, 8, 6, 32784, 2048)
    assert m.rope_theta() == 5e6 and m.partial_rotary_factor == 0.5
    assert [m.block_kinds()[b] for b in (0, 6, 11)] == ["embed", "moe",
                                                        "cca"]
    # the engine file names no model
    with open(decoder_tied.__file__) as f:
        assert "zaya" not in f.read().lower().replace("zaya1_8b_ep2", "")


def test_the_engine_balances_the_common_start_for_all_clients_alike():
    """``decoder_tied.build_trainer`` is ``decoder.build_trainer`` with
    every layer's balancing bias set by the load of the clients' first
    minibatches: the same for every client, no other leaf touched."""
    import numpy as np

    from benchmarks.engines import decoder, decoder_tied

    tiny = cells.override(cells.load_cell(CELL), TINY)
    kw = dict(K=2, samples_per_client=2, blocks=[11], Nloop=1, Nadmm=1)
    seeded = decoder.build_trainer(tiny, 11, **kw)
    balanced = decoder_tied.build_trainer(tiny, 11, **kw)
    try:
        for block, leaves in seeded.params0.items():
            for name, leaf in leaves.items():
                got = balanced.params0[block][name]
                assert got.shape == leaf.shape and got.sharding == \
                    leaf.sharding, (block, name)
                same = np.array_equal(np.asarray(got), np.asarray(leaf))
                assert same == (name != "router_bias"), (block, name)
        for i in range(6):
            bias = np.asarray(balanced.params0[f"layer{i}_moe"]["router_bias"])
            assert bias.shape == (2, 8) and np.array_equal(bias[0], bias[1])
            assert 0 < np.max(np.abs(bias)) <= 0.256 + 1e-6
    finally:
        seeded.close()
        balanced.close()


# ----------------------------------------------------------------------
# the cell through the harness
# ----------------------------------------------------------------------
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", str(trace), "--rehearse", json.dumps(TINY)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 6
    if not trace:
        assert set(result["metrics"]) == {"samples_per_s_chip", "setup_s"}
    else:
        known = {m["name"]: m for m in bench()["per_layer"]}
        for name, m in result["metrics"].items():
            assert m["unit"] == known[name]["unit"], name
        # what the records alone give is there; the device readers and
        # the whole step's share wait for a trace
        assert {"train_pct", "comm_pct", "wire_mb_per_round",
                "warmup_compile_s"} <= set(result["metrics"])
        assert not set(NEW) & set(result["metrics"])
    check = json.loads(next(
        line for line in lines if line.startswith("check: "))[len("check: "):])
    assert check["ok"] and len(check["rounds"]) == 2
    assert {"loss_rel", "logits_rel", "grad_rel_block0", "grad_rel_block6",
            "moe_top1_weight_mean", "moe_fill_share",
            "router_state_rms"} <= set(check)
    assert check["logits_rel"] < 1e-5 and check["grad_rel_block0"] < 1e-5
    assert 1 / 8 < check["moe_top1_weight_mean"] < 1.0
    assert 0.2 < check["moe_fill_share"] < 0.8
    # the window's own check of the router's weight ran
    assert any(line.startswith("moe_top1_weight_mean: least round ")
               for line in lines)


def test_the_cell_and_its_entries_in_benchmark_json():
    b = bench()
    cell = cells.load_cell(CELL)
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell.config_name, cell.traffic_name, 1) and len(entry["why"]) <= 200
    # the seventh cell and the sixth configuration; later PRs add after
    assert b["workloads"][6] is entry and len(b["workloads"]) >= 7
    conf = b["configs"][5]
    assert conf["name"] == cell.config_name == cell.config["name"]
    assert conf["reduced"] == cell.config["reduced"]
    assert conf["source"] == cell.config["source"]
    assert conf["file"] == f"benchmarks/configs/{cell.config_name}.json"
    assert len(conf["why"]) <= 200
    new = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW == [
        m["name"] for m in b["per_layer"][31:36]]
    assert all(m["moves"] == "samples_per_s_chip" for m in new)
    assert [(m["unit"], m["better"], m["source"], m["layer"]) for m in new] \
        == [("%", "higher", "program_span", "local epochs"),
            ("%", "lower", "device_trace", "kernels"),
            ("%", "lower", "device_trace", "kernels"),
            ("%", "higher", "device_trace", "kernels"),
            ("%", "lower", "device_trace", "kernels")]
    # the cell reports every metric without a list, and its own five
    assert cell.per_layer == [m["name"] for m in b["per_layer"]
                              if CELL in m.get("workloads", [CELL])]
    assert len(cell.per_layer) == 20
    for name in NEW:
        assert reader(name).UNIT == "%"
    # no accepted metric's list was touched
    assert not any(CELL in m.get("workloads", []) for m in b["per_layer"]
                   if m["name"] not in NEW)
    # the traffic is the issue's
    t = cell.traffic
    assert (t["algorithm"], t["blocks"], t["Nadmm"], t["Nepoch"],
            t["samples_per_client"]) == ("fedavg", [0, 6, 11], 2, 1, 8)
    # one cell on four chips
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


# ----------------------------------------------------------------------
# the work model
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


TOKENS = 65536
PAIRS = 6 * TOKENS // 2                  # six expert layers at the mean


def test_forward_work_is_a_count_by_hand(cell):
    cfg = cell.config
    mflop = lambda f: f / 1e6
    # W_q 2048 x 1024, W_k 2048 x 256, W_v1 + W_v2 2048 x 256, W_o
    assert zaya_work.cca_proj_flops(cfg) == 2 * 2048 * (1024 + 256 + 256) \
        + 2 * 1024 * 2048
    assert mflop(zaya_work.cca_proj_flops(cfg)) == pytest.approx(10.49,
                                                                 abs=0.01)
    # 2 taps x 1,280 channels, and 2 taps x 10 heads x 128 x 128
    assert zaya_work.cca_conv_flops(cfg) == 2 * 2 * 1280 \
        + 2 * 2 * 10 * 128 * 128
    assert mflop(zaya_work.cca_conv_flops(cfg)) == pytest.approx(0.66,
                                                                 abs=0.01)
    # 8 query heads, q k^T and a v at 128, half the square
    assert zaya_work.cca_core_flops(cfg, 4096) == 2 * 8 * 256 * 4097 / 2
    assert mflop(zaya_work.cca_core_flops(cfg, 4096)) == pytest.approx(
        8.39, abs=0.01)
    assert zaya_work.cca_core_bytes(cfg) == 2 * 128 * (8 + 4) + 4 * 128 * 8
    assert zaya_work.router_flops(cfg) == 2 * (2048 * 256 + 2 * 256 * 256
                                               + 256 * 16)
    assert mflop(zaya_work.router_flops(cfg)) == pytest.approx(1.32,
                                                               abs=0.01)
    assert zaya_work.pair_flops(cfg) == 3 * 2 * 2048 * 2048
    assert mflop(zaya_work.head_flops(cfg)) == pytest.approx(134.3, abs=0.05)
    # the final norm's round is the forward pass and the head's
    # activation gradient; a token forward: six layers of 10.5 + 0.7 +
    # 8.4 + 1.3 + 12.6 (half the tokens meet a held expert), the head 134
    forward = zaya_work.round_flops(cfg, 13, TOKENS, PAIRS, 4096) \
        - zaya_work.head_flops(cfg) * TOKENS
    assert mflop(forward / TOKENS) == pytest.approx(
        6 * (10.49 + 0.66 + 8.39 + 1.32 + 12.58) + 134.28, abs=0.3)
    assert mflop(forward / TOKENS) == pytest.approx(334.9, abs=0.5)


def test_round_flops_follow_the_active_block(cell):
    cfg = cell.config
    f = {b: zaya_work.round_flops(cfg, b, TOKENS, PAIRS, 4096)
         for b in range(14)}
    # the tied embedding: backward through everything and the head's
    # weight gradient, the one product its gradient needs
    assert f[0] > f[1] > f[3] > f[5] > f[7] > f[9] > f[11] > f[13] > 0
    assert f[2] > f[4] > f[6] > f[8] > f[10] > f[12] > f[13]
    whole_back = f[1] - (zaya_work.cca_proj_flops(cfg)
                         + zaya_work.cca_conv_flops(cfg)) * TOKENS
    assert f[0] == pytest.approx(whole_back
                                 + zaya_work.head_flops(cfg) * TOKENS)
    assert f[13] == pytest.approx(f[12] - 2 * zaya_work.pair_flops(cfg)
                                  * PAIRS / 6 - zaya_work.router_flops(cfg)
                                  * TOKENS)
    assert f[0] / TOKENS / 1e9 == pytest.approx(0.854, abs=0.002)
    assert f[6] / TOKENS / 1e9 == pytest.approx(0.621, abs=0.002)
    assert f[11] / TOKENS / 1e9 == pytest.approx(0.522, abs=0.002)
    kinds = [k for k, _ in zaya_work.parts(cfg)]
    assert [kinds.count(k) for k in ("cca", "moe", "head")] == [6, 6, 1]
    assert [b for _, b in zaya_work.parts(cfg)] == list(range(1, 13)) + [0]
    need = lambda b: [i for i, (a, _) in enumerate(zaya_work.needs(cfg, b))
                      if a]
    weight = lambda b: [i for i, (_, w) in enumerate(
        zaya_work.needs(cfg, b)) if w]
    assert need(0) == list(range(13)) and weight(0) == [12]
    assert need(6) == list(range(5, 13)) and weight(6) == [5]
    assert need(11) == [10, 11, 12] and weight(11) == [10]
    assert need(13) == [12] and weight(13) == []


def test_core_work_by_block(cell):
    cfg = cell.config
    core = TOKENS * zaya_work.cca_core_flops(cfg, 4096)
    # backward through six mixers, through three, through one, none: the
    # forward once, and where the gradient reaches the forward again and
    # twice its products backward
    for block, reached in ((0, 6), (6, 3), (11, 1), (13, 0)):
        fl, by = zaya_work.cca_core_work(cfg, block, TOKENS, 4096)
        assert fl == pytest.approx((6 + 3 * reached) * core)
        assert by == pytest.approx((6 + 3 * reached) * TOKENS
                                   * zaya_work.cca_core_bytes(cfg))
    # bound by its products, not its bytes
    peak = peaks.peaks_for("TPU v5 lite")
    fl, by = zaya_work.cca_core_work(cfg, 0, TOKENS, 4096)
    assert fl / peak["bf16_flops"] > by / peak["hbm_bytes_per_s"]


# ----------------------------------------------------------------------
# the readers on synthetic events
# ----------------------------------------------------------------------
STEP = "jit(epoch_shard)/vmap()/while/body/closed_call/client_grad/while/" \
    "body/closed_call/"
FWD = STEP + "jvp(model_loss)/Zaya/"
BWD = STEP + "transpose(jvp(model_loss))/Zaya/"
MIXER = "sublayer_mixer/while/body/closed_call/checkpoint/cca_attn/"


def events(core_ns, mix_ns, route_ns):
    """A forward and a backward core kernel of ``core_ns`` in all, the
    convolutions and the unit norm of ``mix_ns`` in all, a projection,
    the router, an expert product and an op of no scope; a container
    wraps it all."""
    op = lambda name, start, dur, cat="loop fusion": xplane.Op(
        name, float(start), float(dur), cat)
    t, out = 0.0, [(op("while.1", 0, 1e12, "while"), STEP + "while:")]
    for name, path, dur in (
            ("cca_core.1", FWD + MIXER + "cca_core/pallas_call:",
             core_ns / 4),
            ("cca_core.2", BWD + "sublayer_mixer/jvp(model_loss)/Zaya/"
             + MIXER + "cca_core/cca_attn/cca_core/pallas_call:",
             3 * core_ns / 4),
            ("fusion.1", FWD + MIXER + "cca_mix/mul:", mix_ns / 2),
            ("fusion.2", FWD + MIXER + "attn_norm_rope/rsqrt:", mix_ns / 2),
            ("fusion.3", FWD + MIXER + "attn_proj_in/dot_general:", mix_ns),
            # a norm of another mixer's is not this one's
            ("fusion.4", FWD + "sublayer_mixer/checkpoint/mla_attn/"
             "attn_norm_rope/mul:", mix_ns),
            ("fusion.5", FWD + "sublayer_ffn/checkpoint/moe_route/"
             "route_mlp/dot_general:", route_ns),
            ("ragged-dot-general.2", "", core_ns),
            ("fusion.9", STEP + "while:", mix_ns)):
        out.append((op(name, t, dur), path))
        t += dur
    return out, t


def fake_run(monkeypatch, core_ns, mix_ns, route_ns, rounds):
    ops, end = events(core_ns, mix_ns, route_ns)
    monkeypatch.setattr(scope_tree, "trace_path", lambda name: "fake.pb")
    monkeypatch.setattr(scope_tree, "load", lambda path: {
        "/device:TPU:0": scope_tree.leaves(ops)})
    monkeypatch.setattr(scope_tree, "_TREES", {})
    trace = TraceView({"/device:TPU:0": [o for o, _ in ops]}, (0.0, end), [],
                      "TPU v5 lite")
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0, traced=True)],
                   samples_per_round=16, chips=1, counters={})
    return trace, recs, end


def test_scope_readers_on_synthetic_events(cell, monkeypatch, capsys):
    rounds = [{"block": 0, "tokens": TOKENS, "moe_pairs_local": PAIRS,
               "round_seconds": 1.0},
              {"block": 2, "tokens": TOKENS, "moe_pairs_local": PAIRS,
               "round_seconds": 1.0}]
    peak = peaks.peaks_for("TPU v5 lite")
    least = sum(zaya_work.cca_core_work(cell.config, b, TOKENS, 4096)[0]
                for b in (0, 11)) / peak["bf16_flops"]
    roof = reader("cca_core_roofline_pct")
    # the kernels take exactly the least time: 100 %; twice it: 50 %
    for factor, want in ((1.0, 100.0), (2.0, 50.0)):
        core, mix, route = factor * least * 1e9, 4e6, 1e6
        trace, recs, end = fake_run(monkeypatch, core, mix, route, rounds)
        got = roof.read(recs, trace, cell)
        assert got == pytest.approx(want, rel=1e-6) and got <= 100.0
        # the mixer: core, cca_mix, its own norm, its projection
        assert reader("cca_attn_busy_pct").read(recs, trace, cell) \
            == pytest.approx(100.0 * (core + 2 * mix) / end)
        assert reader("cca_mix_busy_pct").read(recs, trace, cell) \
            == pytest.approx(100.0 * mix / end)
        assert reader("route_mlp_busy_pct").read(recs, trace, cell) \
            == pytest.approx(100.0 * route / end)
    assert capsys.readouterr().out.count("scope_tree=") == 2
    # a kernel faster than its least time is a fault of the work model:
    # the share passes 100 % and the driver would refuse it
    trace, recs, _ = fake_run(monkeypatch, 0.5 * least * 1e9, 4e6, 1e6,
                              rounds)
    assert roof.read(recs, trace, cell) > 105.0
    # without a trace, or on a program without these scopes (the parent)
    for name in NEW[1:]:
        assert reader(name).read(recs, None, cell) is None
    bare = [(xplane.Op("fusion.9", 0.0, 10.0, "loop fusion"),
             STEP + "opt_update/add:")]
    monkeypatch.setattr(scope_tree, "load", lambda path: {
        "/device:TPU:0": scope_tree.leaves(bare)})
    monkeypatch.setattr(scope_tree, "_TREES", {})
    trace = TraceView({"/device:TPU:0": [bare[0][0]]}, (0.0, 10.0), [],
                      "TPU v5 lite")
    for name in NEW[1:]:
        assert reader(name).read(recs, trace, cell) is None
    # a checkout without the program's table
    monkeypatch.setattr(scope_tree, "NAMES", frozenset())
    for name in NEW[1:]:
        assert reader(name).read(recs, trace, cell) is None


def test_mfu_reader(cell):
    peak = peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    rec = {"block": 1, "tokens": TOKENS, "moe_pairs_local": PAIRS}
    flops = zaya_work.round_flops(cell.config, 6, TOKENS, PAIRS, 4096)
    rounds = [dict(rec, round_seconds=flops / peak / 0.25)]
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0)],
                   samples_per_round=16, chips=1, counters={})
    trace = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert reader("zaya_step_mfu_pct").read(recs, trace, cell) \
        == pytest.approx(25.0)
    assert reader("zaya_step_mfu_pct").read(recs, None, cell) is None
    # a parent's records (no `tokens`) read nothing and raise nothing
    old = Records(warmup=[], passes=[Pass([{"round_seconds": 1.0}], 0.0,
                                          1.0)],
                  samples_per_round=16, chips=1, counters={})
    assert reader("zaya_step_mfu_pct").read(old, trace, cell) is None


def test_seconds_under_a_parent():
    tree = {"nodes": {
        "a/cca_attn": {"s": [9.0, 0, 0]},
        "a/cca_attn/cca_mix": {"s": [1.0, 0.5, 2.0]},
        "a/cca_attn/attn_norm_rope": {"s": [0.25, 0, 0]},
        "a/mla_attn/attn_norm_rope": {"s": [7.0, 0, 0]},
        "cca_mix": {"s": [5.0, 0, 0]}}}
    assert zaya_work.seconds_under(tree, "cca_attn",
                                   ("cca_mix", "attn_norm_rope")) == 3.75
    assert zaya_work.seconds_under(tree, "mla_attn", ("cca_mix",)) == 0.0


# ----------------------------------------------------------------------
# the check against a lower precision
# ----------------------------------------------------------------------
def test_the_check_fails_a_float8_probe_at_the_small_size():
    """Every product's operands rounded to float8 e4m3
    (``ops/moe.py:operand``): the nearest precision below the
    configuration's has to come out as not correct."""
    from benchmarks.engines import decoder_tied

    tiny = cells.override(cells.load_cell(CELL), TINY)
    probe = dataclasses.replace(
        tiny, config={**tiny.config, "dtype": "float8_e4m3fn"})
    check = decoder_tied.Session(probe, 3000000019).check()
    assert not check["ok"] and check["problems"]
    assert check["logits_rel"] > decoder_tied.LOGITS_RTOL
    # the router stays float32 whatever the products' dtype
    assert check["moe_top1_weight_mean"] > 1 / 8
