"""The ``olmohybrid_fedavg_gdn_blocks`` cell's harness on the CPU: the
configuration file against the catalog row, the cell's rehearsal through
``benchmarks/run.py --trace 1 --rehearse``, its entries in
``BENCHMARK.json``, the work model of ``benchmarks/lib/olmo_work.py``
against counts by hand, the three new readers on synthetic device events
(a share above 100 % is a failure: no share of a roofline can pass it), and the
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

from benchmarks.lib import cells, olmo_work, peaks, scope_tree, xplane  # noqa: E402
from benchmarks.lib.records import Records, TraceView  # noqa: E402
from benchmarks.lib.window import Pass  # noqa: E402

CELL = "olmohybrid_fedavg_gdn_blocks"
NEW = ["olmo_step_mfu_pct", "olmo_gdn_busy_pct", "gdn96_scan_roofline_pct"]
#: tiny widths; matrices seeded at 0.2 so that a product's output is of
#: the size it has at the published widths; float32 products
TINY = {"config": {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 40, "linear_num_key_heads": 3,
    "linear_num_value_heads": 3, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "vocab_rows": 64, "attn_block": 16,
    "chunk": 8, "seq_len": 24, "lr": 0.001, "init_scale": 0.2,
    "dtype": "float32"}}


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
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": ["linear_attention"] * 3 + ["full_attention"],
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    published["layer_types"] = published["layer_types"] * 8
    config = cells.load_cell(CELL).config
    for key, value in published.items():
        assert config[key] == value and type(config[key]) is type(value), key
    assert config["reduced"] == ["layers", "vocab_rows", "K",
                                 "samples_per_client", "rounds_per_block",
                                 "dataset"]
    assert set(config["reduced"]) == set(config["reduced_notes"])
    assert not set(config["reduced"]) & set(published)      # no width cut
    assert config["source"] == ("https://huggingface.co/allenai/"
                                "Olmo-Hybrid-7B/blob/main/config.json")
    # one period of the 3 : 1 pattern, an eighth of the vocabulary
    assert config["layers"] == 4 and config["K"] == 2
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    assert {"post_norm", "qk_norm", "no_rotary", "gdn_mixer", "neg_eigval",
            "decay", "mlp", "init", "tokens", "seq_len"} \
        <= set(config["assumed"])
    assert "8 pipeline stages" in config["deployment"]
    assert "No layer is divided" in config["deployment"]
    assert config["params"] == 928_862_196
    assert (config["model"], config["engine"]) == ("olmo_hybrid",
                                                    "decoder_dense")
    assert (config["batch"], config["seq_len"], config["lr"],
            config["dtype"]) == (1, 4096, 1e-4, "bfloat16")


def test_the_engine_builds_the_model_from_the_configuration_s_keys():
    from benchmarks.engines import decoder_dense

    m = decoder_dense.build_model(cells.load_cell(CELL).config)
    assert type(m).__name__ == "OlmoHybrid"
    assert (m.hidden_size, m.num_attention_heads, m.num_key_value_heads,
            m.head_dim, m.intermediate_size, m.linear_num_key_heads,
            m.linear_num_value_heads, m.linear_key_head_dim,
            m.linear_value_head_dim, m.linear_conv_kernel_dim, m.layers,
            m.vocab_rows, m.rms_norm_eps) == (
        3840, 30, 30, 128, 11008, 30, 30, 96, 192, 4, 4, 12544, 1e-6)
    assert m.linear_allow_neg_eigval and m.embed_scale == 1.0
    assert [m.block_kinds()[b] for b in (1, 3, 5, 7)] == ["gdn"] * 3 + [
        "attn"]
    # the engine file names no model
    with open(decoder_dense.__file__) as f:
        assert "olmo" not in f.read().lower().replace("olmo_hybrid_7b_pp8",
                                                      "")


# ----------------------------------------------------------------------
# the cell through the harness
# ----------------------------------------------------------------------
def test_rehearsal_of_the_cell():
    """Traced: the check, the window and every reader the cell lists
    (the untraced line is ``run.py``'s own, rehearsed by the sibling
    cells' tests)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--rehearse", json.dumps(TINY)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 6
    known = {m["name"]: m for m in bench()["per_layer"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == known[name]["unit"], name
    # what the records alone give is there; the device readers and the
    # whole step's share wait for a trace
    assert {"train_pct", "comm_pct", "wire_mb_per_round",
            "warmup_compile_s"} <= set(result["metrics"])
    assert not set(NEW) & set(result["metrics"])
    check = json.loads(next(
        line for line in lines if line.startswith("check: "))[len("check: "):])
    assert check["ok"] and len(check["rounds"]) == 2
    assert {"loss_rel", "logits_rel", "grad_rel_block1", "grad_rel_block7",
            "gdn_neg_beta_share"} <= set(check)
    assert check["logits_rel"] < 1e-5 and check["grad_rel_block7"] < 1e-5
    assert 0.2 < check["gdn_neg_beta_share"] < 0.8
    assert all(0 < r["gdn_neg_beta_share"] < 1 for r in check["rounds"])
    # the window's own check of the share ran
    assert any(line.startswith("gdn_neg_beta_share: rounds ")
               for line in lines)


def test_the_cell_and_its_entries_in_benchmark_json():
    b = bench()
    cell = cells.load_cell(CELL)
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell.config_name, cell.traffic_name, 1) and len(entry["why"]) <= 200
    # the eighth cell and the seventh configuration; later PRs add after
    assert b["workloads"][7] is entry and len(b["workloads"]) >= 8
    conf = b["configs"][6]
    assert conf["name"] == cell.config_name == cell.config["name"]
    assert conf["reduced"] == cell.config["reduced"]
    assert conf["source"] == cell.config["source"]
    assert conf["file"] == f"benchmarks/configs/{cell.config_name}.json"
    assert len(conf["why"]) <= 200
    new = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW == [
        m["name"] for m in b["per_layer"][36:39]]
    assert all(m["moves"] == "samples_per_s_chip" for m in new)
    assert [(m["unit"], m["better"], m["source"], m["layer"]) for m in new] \
        == [("%", "higher", "program_span", "local epochs"),
            ("%", "lower", "device_trace", "kernels"),
            ("%", "higher", "device_trace", "kernels")]
    # the cell reports every metric without a list, and its own three
    assert cell.per_layer == [m["name"] for m in b["per_layer"]
                              if CELL in m.get("workloads", [CELL])]
    assert len(cell.per_layer) == 18
    for name in NEW:
        assert reader(name).UNIT == "%"
    # no accepted metric's list was touched
    assert not any(CELL in m.get("workloads", []) for m in b["per_layer"]
                   if m["name"] not in NEW)
    # the cell's traffic: blocks, rounds, steps, the check's blocks
    t = cell.traffic
    assert (t["algorithm"], t["blocks"], t["Nadmm"], t["Nepoch"],
            t["samples_per_client"], t["check_grad_blocks"]) == (
        "fedavg", [1, 3, 5], 2, 1, 4, [1, 7])
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


# ----------------------------------------------------------------------
# the work model
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


TOKENS = 32768


def test_forward_work_is_a_count_by_hand(cell):
    cfg = cell.config
    H, F = 3840, 11008
    first, rest = olmo_work.gdn_weight_flops(cfg)
    # W_q, W_k 3,840 x 2,880; W_v, W_z 3,840 x 5,760; W_a, W_b 3,840 x 30
    assert first == 2 * H * (2 * 2880 + 2 * 5760 + 60)
    # three convolutions of 4 taps over 11,520 channels, W_o
    assert rest == 2 * 4 * 11520 + 2 * 5760 * H
    assert olmo_work.gdn_core_flops(cfg) == 6 * 96 * 192 * 30
    assert olmo_work.gdn_core_bytes(cfg) == 4 * 30 * (2 * 96 + 2 * 192 + 2)
    assert olmo_work.attn_weight_flops(cfg) == (2 * H * 3 * H, 2 * H * H)
    assert olmo_work.attn_core_flops(cfg, 4096) == 4 * H * 4097 / 2
    assert olmo_work.mlp_flops(cfg) == (4 * H * F, 2 * F * H)
    assert olmo_work.head_flops(cfg) == 2 * H * 12544
    # the head block active: every part forward, the head's weight
    # gradient only (nothing before it trains)
    forward = olmo_work.round_flops(cfg, 9, TOKENS, 4096) / TOKENS
    assert forward == pytest.approx(
        3 * (first + rest + olmo_work.gdn_core_flops(cfg))
        + 4 * H * H * 2 + olmo_work.attn_core_flops(cfg, 4096)
        + 4 * 6 * H * F + 2 * olmo_work.head_flops(cfg))
    # about 1.9 GFLOP a token forward, two parameters' worth of 929 M
    assert forward / 1e9 == pytest.approx(1.899, abs=0.002)


def test_round_flops_follow_the_active_block(cell):
    cfg = cell.config
    f = {b: olmo_work.round_flops(cfg, b, TOKENS, 4096) for b in range(10)}
    # the mixer's weight gradients cost more than its input's gradient:
    # layer 0's mixer block above the embedding, whose table takes a
    # scatter
    assert f[1] > f[0] > f[2] > f[3] > f[4] > f[5] > f[6] > f[7] > f[8] \
        > f[9] > 0
    kinds = [k for k, _ in olmo_work.parts(cfg)]
    assert kinds == ["gdn", "mlp"] * 3 + ["attn", "mlp", "head"]
    need = lambda b: [i for i, (a, _) in enumerate(olmo_work.needs(cfg, b))
                      if a]
    assert need(1) == list(range(9)) and need(5) == list(range(4, 9))
    assert need(0) == list(range(9)) and need(9) == [8]
    # block 1 against the embedding: the active part's own input gets no
    # gradient, the embedding's table gets a scatter
    first, rest = olmo_work.gdn_weight_flops(cfg)
    assert f[0] - f[1] == pytest.approx(first * TOKENS - (first + rest)
                                        * TOKENS)
    assert f[1] / TOKENS / 1e9 == pytest.approx(3.691, abs=0.002)
    assert f[5] / TOKENS / 1e9 == pytest.approx(2.816, abs=0.002)


def test_scan_work_by_block(cell):
    cfg = cell.config
    core = TOKENS * olmo_work.gdn_core_flops(cfg)
    # backward through three GDN mixers, two, one
    for block, reached in ((1, 3), (3, 2), (5, 1), (7, 0)):
        fl, by = olmo_work.gdn_scan_work(cfg, block, TOKENS)
        assert fl == pytest.approx((3 + 2 * reached) * core)
        assert by == pytest.approx((3 + 2 * reached) * TOKENS
                                   * olmo_work.gdn_core_bytes(cfg))
    # the recurrence alone is bound by its bytes on the v5e
    peak = peaks.peaks_for("TPU v5 lite")
    fl, by = olmo_work.gdn_scan_work(cfg, 1, TOKENS)
    assert by / peak["hbm_bytes_per_s"] > fl / peak["bf16_flops"]


# ----------------------------------------------------------------------
# the readers on synthetic events
# ----------------------------------------------------------------------
STEP = "jit(epoch_shard)/vmap()/while/body/closed_call/client_grad/while/" \
    "body/closed_call/"
FWD = STEP + "jvp(model_loss)/OlmoHybrid/"
BWD = STEP + "transpose(jvp(model_loss))/OlmoHybrid/"
MIXER = "sublayer_mixer/while/body/closed_call/checkpoint/gdn/"


def events(scan_ns, gdn_ns):
    """A forward and a backward recurrence kernel of ``scan_ns`` in all,
    the rest of the mixer ``gdn_ns``, an attention kernel, an MLP
    product and an op of no scope; a container wraps it all."""
    op = lambda name, start, dur, cat="loop fusion": xplane.Op(
        name, float(start), float(dur), cat)
    t, out = 0.0, [(op("while.1", 0, 1e12, "while"), STEP + "while:")]
    for name, path, dur in (
            ("gdn_scan.1", FWD + MIXER + "gdn_scan/pallas_call:",
             scan_ns / 4),
            ("gdn_scan.2", BWD + "sublayer_mixer/" + MIXER
             + "gdn_scan/gdn_scan/pallas_call:", 3 * scan_ns / 4),
            ("fusion.1", FWD + MIXER + "gdn_conv/mul:", gdn_ns / 2),
            ("fusion.2", FWD + MIXER + "gdn_in_proj/dot_general:",
             gdn_ns / 2),
            ("mha.1", FWD + "sublayer_mixer/while/body/closed_call/"
             "checkpoint/mha_attn/pallas_call:", gdn_ns),
            ("fusion.3", FWD + "sublayer_ffn/checkpoint/dense_mlp/"
             "dot_general:", gdn_ns),
            ("fusion.4", FWD + "sublayer_ffn/checkpoint/post_norm/add:",
             gdn_ns / 8),
            ("fusion.9", STEP + "while:", gdn_ns)):
        out.append((op(name, t, dur), path))
        t += dur
    return out, t


def fake_run(monkeypatch, scan_ns, gdn_ns, rounds):
    ops, end = events(scan_ns, gdn_ns)
    monkeypatch.setattr(scope_tree, "trace_path", lambda name: "fake.pb")
    monkeypatch.setattr(scope_tree, "load", lambda path: {
        "/device:TPU:0": scope_tree.leaves(ops)})
    monkeypatch.setattr(scope_tree, "_TREES", {})
    trace = TraceView({"/device:TPU:0": [o for o, _ in ops]}, (0.0, end), [],
                      "TPU v5 lite")
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0, traced=True)],
                   samples_per_round=8, chips=1, counters={})
    return trace, recs, end


def test_scope_readers_on_synthetic_events(cell, monkeypatch, capsys):
    rounds = [{"block": 0, "tokens": TOKENS, "moe_pairs_local": 0,
               "round_seconds": 1.0},
              {"block": 2, "tokens": TOKENS, "moe_pairs_local": 0,
               "round_seconds": 1.0}]
    peak = peaks.peaks_for("TPU v5 lite")
    work = [olmo_work.gdn_scan_work(cell.config, b, TOKENS) for b in (1, 5)]
    least = max(sum(w[0] for w in work) / peak["bf16_flops"],
                sum(w[1] for w in work) / peak["hbm_bytes_per_s"])
    roof = reader("gdn96_scan_roofline_pct")
    # the kernels take exactly the least time: 100 %; twice it: 50 %
    for factor, want in ((1.0, 100.0), (2.0, 50.0)):
        scan, rest = factor * least * 1e9, 4e6
        trace, recs, end = fake_run(monkeypatch, scan, rest, rounds)
        got = roof.read(recs, trace, cell)
        assert got == pytest.approx(want, rel=1e-6) and got <= 100.0
        # the whole mixer: the scan and the rest of it, nothing else
        assert reader("olmo_gdn_busy_pct").read(recs, trace, cell) \
            == pytest.approx(100.0 * (scan + rest) / end)
    assert capsys.readouterr().out.count("scope_tree=") == 2
    # a kernel faster than its least time is a fault of the work model:
    # the share passes 100 %, which no share of a roofline may
    trace, recs, _ = fake_run(monkeypatch, 0.5 * least * 1e9, 4e6, rounds)
    assert roof.read(recs, trace, cell) > 105.0
    # without a trace, or on a program without these scopes
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
    flops = olmo_work.round_flops(cell.config, 3, TOKENS, 4096)
    rounds = [{"block": 1, "tokens": TOKENS, "moe_pairs_local": 0,
               "round_seconds": flops / peak / 0.25}]
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0)],
                   samples_per_round=8, chips=1, counters={})
    trace = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert reader("olmo_step_mfu_pct").read(recs, trace, cell) \
        == pytest.approx(25.0)
    assert reader("olmo_step_mfu_pct").read(recs, None, cell) is None
    old = Records(warmup=[], passes=[Pass([{"round_seconds": 1.0}], 0.0,
                                          1.0)],
                  samples_per_round=8, chips=1, counters={})
    assert reader("olmo_step_mfu_pct").read(old, trace, cell) is None


# ----------------------------------------------------------------------
# the check against a lower precision
# ----------------------------------------------------------------------
def test_the_check_fails_a_float8_probe_at_the_small_size():
    """Every product's operands rounded to float8 e4m3
    (``ops/moe.py:operand``): the nearest precision below the
    configuration's has to come out as not correct."""
    from benchmarks.engines import decoder_dense

    tiny = cells.override(cells.load_cell(CELL), TINY)
    probe = dataclasses.replace(
        tiny, config={**tiny.config, "dtype": "float8_e4m3fn"})
    check = decoder_dense.Session(probe, 3000000019).check()
    assert not check["ok"] and check["problems"]
    assert check["logits_rel"] > decoder_dense.LOGITS_RTOL
    # beta's share is float32 whatever the products' dtype
    assert 0.0 < check["gdn_neg_beta_share"] < 1.0
