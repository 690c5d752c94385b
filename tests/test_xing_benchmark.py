"""The ``xing4_fedavg_mixer_blocks`` cell's harness on the CPU: the cell's
rehearsal through ``benchmarks/run.py --rehearse``, its entries in
``BENCHMARK.json``, the work model of ``benchmarks/lib/xing_work.py``
against ISSUE 34's counts, the five new readers on synthetic device
events (a share above 100 % is a failure here as it is for the driver),
and the engine's check failing on a float8 probe at the small size.
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

from benchmarks.lib import cells, peaks, xing_work, xplane  # noqa: E402
from benchmarks.lib.records import Records, TraceView  # noqa: E402
from benchmarks.lib.window import Pass  # noqa: E402

CELL = "xing4_fedavg_mixer_blocks"
NEW = ["xing_step_mfu_pct", "mhc_busy_pct", "mhc_roofline_pct",
       "mla_core192_roofline_pct", "mhc_marginal_err"]
#: tiny widths; matrices seeded at 0.2 so that a sub-layer's output is as
#: large beside the embedding as at the published widths (the streams
#: differ and the maps matter); 12 Sinkhorn iterations from a flatter
#: start keep the CPU compiles short and the marginal error at 1e-6;
#: float32 products, because 32-wide bfloat16 contractions over weights
#: of 0.2 miss ``engines/lm.py``'s round-loss limit (7.8e-4 against 5e-4)
#: that the published widths keep by two orders
TINY = {"config": {
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 12,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "intermediate_size": 80, "moe_intermediate_size": 24,
    "n_routed_experts": 16, "num_experts_per_tok": 3, "experts_held": 4,
    "ep_rank": 1, "vocab_rows": 64, "attn_block": 16, "seq_len": 24,
    "lr": 0.001, "pair_rows_factor": 8.0, "bias_scale": 0.02,
    "init_scale": 0.2, "hc_sinkhorn_iters": 12, "hc_res_diag": 0.5,
    "dtype": "float32",
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"}},
    "traffic": {"samples_per_client": 4, "check_moved_share": 0.05}}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return __import__(f"benchmarks.metrics.{name}", fromlist=["read"])


# ----------------------------------------------------------------------
@pytest.mark.long(seconds=238)
def test_rehearsal_of_the_cell():
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
    # what the records alone give is there, the counter's mean among it;
    # the device readers wait for a trace
    assert {"train_pct", "comm_pct", "wire_mb_per_round",
            "mhc_marginal_err"} <= set(result["metrics"])
    assert 0.0 < result["metrics"]["mhc_marginal_err"]["value"] < 1e-4
    assert not set(NEW[:4]) & set(result["metrics"])
    check = json.loads(next(
        line for line in lines if line.startswith("check: "))[len("check: "):])
    assert check["ok"] and len(check["rounds"]) == 2
    assert {"loss_rel", "logits_rel", "phi_zeroed_logits_rel",
            "mhc_marginal_err", "grad_rel_block1", "grad_rel_block1_hc",
            "grad_rel_block5", "grad_rel_block5_hc"} <= set(check)
    assert check["phi_zeroed_logits_rel"] > 0.06 > check["logits_rel"]
    # the window's own check of the counter ran
    assert any(line.startswith("mhc_marginal_err: worst round ")
               for line in lines)


def test_the_cell_and_its_entries_in_benchmark_json():
    b = bench()
    cell = cells.load_cell(CELL)
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell.config_name, cell.traffic_name, 1) and len(entry["why"]) <= 200
    # the sixth cell and the fifth configuration; later PRs add after them
    assert b["workloads"][5] is entry and len(b["workloads"]) >= 6
    conf = b["configs"][4]
    assert conf["name"] == cell.config_name == cell.config["name"]
    assert conf["reduced"] == cell.config["reduced"]
    assert conf["source"] == cell.config["source"]
    assert conf["file"] == f"benchmarks/configs/{cell.config_name}.json"
    assert len(conf["why"]) <= 200
    new = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW == [
        m["name"] for m in b["per_layer"][26:31]]
    assert all(m["moves"] == "samples_per_s_chip" for m in new)
    assert [(m["unit"], m["better"], m["source"], m["layer"]) for m in new] \
        == [("%", "higher", "program_span", "local epochs"),
            ("%", "lower", "device_trace", "kernels"),
            ("%", "higher", "device_trace", "kernels"),
            ("%", "higher", "device_trace", "kernels"),
            ("ratio", "lower", "program_counter", "local epochs")]
    # the cell reports every metric without a list, and its own five
    assert cell.per_layer == [m["name"] for m in b["per_layer"]
                              if CELL in m.get("workloads", [CELL])]
    assert len(cell.per_layer) == 20
    # no accepted metric's list was touched
    assert not any(CELL in m.get("workloads", []) for m in b["per_layer"]
                   if m["name"] not in NEW)
    # the traffic is the issue's
    t = cell.traffic
    assert (t["algorithm"], t["blocks"], t["Nadmm"], t["Nepoch"],
            t["samples_per_client"]) == ("fedavg", [1, 5, 9], 2, 1, 8)
    assert (cell.config["K"], cell.config["batch"],
            cell.config["seq_len"]) == (2, 2, 2048)
    assert cell.config["engine"] == "decoder_hc"
    # one cell on four chips
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_the_engine_builds_the_model_from_the_configuration_s_keys():
    from benchmarks.engines import decoder_hc

    m = decoder_hc.build_model(cells.load_cell(CELL).config)
    assert type(m).__name__ == "Xing4"
    assert (m.hidden_size, m.hc_mult, m.hc_sinkhorn_iters, m.v_head_dim,
            m.qk_nope_head_dim + m.qk_rope_head_dim, m.experts_held,
            m.num_nextn_predict_layers, m.first_k_dense_replace) == (
        3584, 4, 20, 128, 192, 8, 0, 1)
    assert m.rope_scaling["original_max_position_embeddings"] == 4096
    assert m.block_kinds()[1] == m.block_kinds()[5] == m.block_kinds()[9] \
        == "mla"


def test_the_window_opens_after_two_untimed_sweeps():
    """The first pass boundary only waits; the second hands the window
    both sweeps as its warm-up (so ``warmup_compile_s`` still sees the
    compiles); from the third on the window counts passes."""
    from benchmarks.engines import decoder_hc
    from benchmarks.lib.window import Window

    window, synced = Window(1e-9), []
    steady = decoder_hc._SteadyWindow(window)
    sync = lambda: synced.append(1)
    assert steady.pass_done([{"compile_seconds": 2.0}], sync) is False
    assert window.warmup is None and window.t_open is None and synced == [1]
    assert steady.pass_done([{"loss": 1.0}], sync) is False
    assert window.warmup == [{"compile_seconds": 2.0}, {"loss": 1.0}]
    assert window.t_open is not None and window.passes == []
    assert steady.pass_done([{"loss": 0.5}], sync) is True
    assert [p.records for p in steady.passes] == [[{"loss": 0.5}]]
    assert steady.warmup is window.warmup and len(synced) == 3


# ----------------------------------------------------------------------
# the work model
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


TOKENS = 32768
PAIRS = 4 * TOKENS * 4 * 8 // 64         # four expert layers at the mean


def test_forward_work_is_the_issue_s_count(cell):
    cfg = cell.config
    # 258 KB a token and sub-layer forward if the stream is read three
    # times and written once; the least is read once, written once
    assert 4 * 4 * 3584 * 4 == 229_376 and xing_work.mhc_bytes(
        cfg, False) == 2 * 4 * 3584 * 4 == 114_688
    assert xing_work.mhc_bytes(cfg, True) == 3 * 4 * 3584 * 4
    # 12.9 ms a step of 4,096 tokens over ten sub-layers at 819 GB/s by
    # the issue's count; the least is half of it
    assert 4096 * 10 * 258_048 / 819e9 == pytest.approx(12.9e-3, rel=0.01)
    assert 4096 * 10 * xing_work.mhc_bytes(cfg, False) / 819e9 \
        == pytest.approx(5.74e-3, rel=0.01)
    # the projections: n C x (n + n + n^2) multiply-adds a token
    assert xing_work.mhc_flops(cfg) == 2 * 14336 * 24 + 2 * 14336 \
        + 2 * 14336 * 5
    # the core at 192 / 128, unpadded, half the square
    assert xing_work.mla_core_flops(cfg, 2048) == 2 * 32 * 320 * 2049 / 2
    assert xing_work.mla_core_bytes(cfg) == 32 * (2 * (2 * 192 + 128)
                                                   + 4 * 128)
    mflop = lambda f: f / 1e6
    assert mflop(xing_work.mla_weight_flops(cfg)) == pytest.approx(56.8,
                                                                   abs=0.05)
    assert mflop(xing_work.dense_mlp_flops(cfg)) == pytest.approx(198.2,
                                                                  abs=0.05)
    assert mflop(xing_work.head_flops(cfg)) == pytest.approx(117.4, abs=0.05)
    # the head block's round is the forward pass and the head's two
    # gradients; a token forward: five mixers' projections 284 and cores
    # 105, the dense MLP 198, four expert layers 134 at the mean load,
    # the head 117, ten sub-layers' hyper-connections 8.6
    forward = xing_work.round_flops(cfg, 11, TOKENS, PAIRS, 2048) \
        - 2 * xing_work.head_flops(cfg) * TOKENS
    assert mflop(forward / TOKENS) == pytest.approx(847.2, abs=0.5)
    assert mflop(10 * xing_work.mhc_flops(cfg)) == pytest.approx(8.6,
                                                                 abs=0.05)


def test_round_flops_follow_the_active_block(cell):
    cfg = cell.config
    f = {b: xing_work.round_flops(cfg, b, TOKENS, PAIRS, 2048)
         for b in range(12)}
    # the deeper the active block, the less of the backward; the
    # embedding's gradient is a scatter and no product
    assert f[1] > f[0] > f[3] > f[5] > f[7] > f[9] > f[11] > 0
    assert f[2] > f[4] > f[6] > f[8] > f[10]
    # the head's two gradients are more than the last expert layer's
    assert f[11] > f[10] > f[11] - 2 * xing_work.head_flops(cfg) * TOKENS
    assert f[1] / TOKENS / 1e9 == pytest.approx(1.859, abs=0.002)
    assert f[5] / TOKENS / 1e9 == pytest.approx(1.425, abs=0.002)
    assert f[9] / TOKENS / 1e9 == pytest.approx(1.156, abs=0.002)
    kinds = [k for k, _ in xing_work.parts(cfg)]
    assert [kinds.count(k) for k in ("mla", "moe", "mlp", "head")] \
        == [5, 4, 1, 1]
    assert [b for _, b in xing_work.parts(cfg)] == list(range(1, 12))
    need = lambda b: [i for i, (a, _) in enumerate(xing_work.needs(cfg, b))
                      if a]
    assert need(1) == list(range(11)) and need(5) == list(range(4, 11))
    assert need(9) == [8, 9, 10] and need(11) == [10]
    assert [i for i, (_, w) in enumerate(xing_work.needs(cfg, 9)) if w] == [8]


def test_hyper_connection_and_core_work_by_block(cell):
    cfg = cell.config
    one_f, one_b = xing_work.mhc_bytes(cfg, False), xing_work.mhc_bytes(
        cfg, True)
    core = TOKENS * xing_work.mla_core_flops(cfg, 2048)
    # backward through all five layers, through three, through one
    for block, sub_back, mix_back in ((1, 10, 5), (5, 6, 3), (9, 2, 1),
                                      (11, 0, 0)):
        fl, by = xing_work.mhc_work(cfg, block, TOKENS)
        assert by == pytest.approx(TOKENS * (10 * one_f + sub_back * one_b))
        assert fl > 0
        cf, cb = xing_work.mla_core_work(cfg, block, TOKENS, 2048)
        assert cf == pytest.approx((5 + 2 * mix_back) * core)
        assert cb == pytest.approx((5 + 2 * mix_back) * TOKENS
                                   * xing_work.mla_core_bytes(cfg))
    # the hyper-connections are bound by their bytes, the core by its
    # products
    peak = peaks.peaks_for("TPU v5 lite")
    fl, by = xing_work.mhc_work(cfg, 1, TOKENS)
    assert by / peak["hbm_bytes_per_s"] > 10 * fl / peak["bf16_flops"]
    cf, cb = xing_work.mla_core_work(cfg, 1, TOKENS, 2048)
    assert cf / peak["bf16_flops"] > cb / peak["hbm_bytes_per_s"]


# ----------------------------------------------------------------------
# the readers on synthetic events
# ----------------------------------------------------------------------
def scoped(name, scope, mhc, start, dur, category=""):
    return xing_work.ScopedOp(xplane.Op(name, start, dur, category), scope,
                              mhc)


def fake_run(core_ns, mhc_ns, rounds):
    """A core kernel of ``core_ns``, then two ops through ``mhc`` of
    ``mhc_ns`` in all, an expert product and an op of no scope; a
    container wraps it all."""
    t = core_ns
    ops = [scoped("while.1", "mhc", True, 0, 10 * (core_ns + mhc_ns),
                  "while"),
           scoped("mla_core.1", "mla_core", False, 0, core_ns),
           scoped("fusion.2", "mhc_maps", True, t, mhc_ns / 4),
           scoped("fusion.3", "mhc_mix", True, t + mhc_ns / 4,
                  3 * mhc_ns / 4),
           scoped("ragged-dot-none", "moe_experts", False, t + mhc_ns,
                  core_ns),
           scoped("fusion.9", "", False, t + mhc_ns + core_ns, mhc_ns)]
    end = 2 * (core_ns + mhc_ns)
    trace = TraceView({"/device:TPU:0": [o.op for o in ops]}, (0.0, end), [],
                      "TPU v5 lite")
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0, traced=True)],
                   samples_per_round=16, chips=1, counters={})
    return {"/device:TPU:0": ops}, trace, recs


def test_scope_readers_on_synthetic_events(cell, monkeypatch):
    rounds = [{"block": 0, "tokens": TOKENS, "moe_pairs_local": PAIRS,
               "round_seconds": 1.0, "mhc_marginal_err": 2e-6},
              {"block": 2, "tokens": TOKENS, "moe_pairs_local": PAIRS,
               "round_seconds": 1.0, "mhc_marginal_err": 4e-6}]
    peak = peaks.peaks_for("TPU v5 lite")
    core = sum(xing_work.mla_core_work(cell.config, b, TOKENS, 2048)[0]
               for b in (1, 9)) / peak["bf16_flops"]
    mhc = sum(xing_work.mhc_work(cell.config, b, TOKENS)[1]
              for b in (1, 9)) / peak["hbm_bytes_per_s"]
    core_roof, mhc_roof = (reader("mla_core192_roofline_pct"),
                           reader("mhc_roofline_pct"))
    # the ops take exactly the least time: 100 %; twice it: 50 %
    for factor, want in ((1.0, 100.0), (2.0, 50.0)):
        ops, trace, recs = fake_run(factor * core * 1e9, factor * mhc * 1e9,
                                    rounds)
        monkeypatch.setattr(xing_work, "of_cell", lambda c: ops)
        for r in (core_roof, mhc_roof):
            got = r.read(recs, trace, cell)
            assert got == pytest.approx(want, rel=1e-6) and got <= 100.0
        # the container is left out; the two mhc ops over the busy time
        assert reader("mhc_busy_pct").read(recs, trace, cell) \
            == pytest.approx(100.0 * mhc / (2 * core + 2 * mhc))
    # a kernel faster than its least time is a fault of the work model:
    # the share passes 100 % and the driver would refuse it
    ops, trace, recs = fake_run(0.5 * core * 1e9, mhc * 1e9, rounds)
    monkeypatch.setattr(xing_work, "of_cell", lambda c: ops)
    assert core_roof.read(recs, trace, cell) > 105.0
    # without a trace, or on a program with no such scopes (the parent)
    for nothing in (None, {}, {"/device:TPU:0": [
            scoped("fusion.9", "", False, 0.0, 10.0)]}):
        monkeypatch.setattr(xing_work, "of_cell", lambda c: nothing)
        for name in NEW[1:4]:
            assert reader(name).read(recs, trace, cell) is None
    assert mhc_roof.read(recs, None, cell) is None
    # the counter's mean over the window's rounds; nothing on records
    # without the field
    assert reader("mhc_marginal_err").read(recs, trace, cell) \
        == pytest.approx(3e-6)
    old = Records(warmup=[], passes=[Pass([{"round_seconds": 1.0}], 0.0,
                                          1.0)],
                  samples_per_round=16, chips=1, counters={})
    assert reader("mhc_marginal_err").read(old, trace, cell) is None


def test_mfu_reader(cell):
    peak = peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    rec = {"block": 1, "tokens": TOKENS, "moe_pairs_local": PAIRS}
    flops = xing_work.round_flops(cell.config, 5, TOKENS, PAIRS, 2048)
    rounds = [dict(rec, round_seconds=flops / peak / 0.25)]
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0)],
                   samples_per_round=16, chips=1, counters={})
    trace = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert reader("xing_step_mfu_pct").read(recs, trace, cell) \
        == pytest.approx(25.0)
    assert reader("xing_step_mfu_pct").read(recs, None, cell) is None
    # a parent's records (no `tokens`) read nothing and raise nothing
    old = Records(warmup=[], passes=[Pass([{"round_seconds": 1.0}], 0.0,
                                          1.0)],
                  samples_per_round=16, chips=1, counters={})
    assert reader("xing_step_mfu_pct").read(old, trace, cell) is None


def test_scope_of_paths():
    s = xing_work.scope_of
    base = "jit(train_epoch)/vmap()/while/body/transpose(jvp(Xing4))/"
    assert s(base + "checkpoint/mla_attn/mla_core/pallas_call") == "mla_core"
    assert s(base + "checkpoint/mla_attn/dot_general") == "mla_attn"
    assert s(base + "checkpoint/mhc/mhc_maps/dot_general") == "mhc_maps"
    assert s(base + "checkpoint/mhc/mhc_mix/mul") == "mhc_mix"
    assert s(base + "checkpoint/mhc/add") == "mhc"
    assert s(base + "dense_mlp/dot_general") == "dense_mlp"
    assert s(base + "moe_route/sort") == "moe_route"
    assert s(base + "lm_head_loss/reduce_max") == "lm_head_loss"
    assert s("ragged-dot-none") == "moe_experts"
    assert s("", "ragged-dot-metadata") == "moe_experts"
    assert s("jit(comm)/reduce_sum") == ""
    through = lambda p: bool(xing_work._THROUGH_MHC.search(p))
    assert through(base + "checkpoint/mhc/mhc_maps/exp")
    assert through("mhc/mul") and through(base + "checkpoint/mhc")
    assert not through(base + "Xing4/hc_phi_pre/dot_general")
    assert not through(base + "mhc_maps/exp")       # never outside mhc
    assert not through(base + "mla_attn/dot_general")


def test_scoped_ops_of_a_recorded_trace():
    path = os.path.join(REPO, "benchmarks", "testdata", "tiny_tpu.xplane.pb")
    loaded = xing_work.load(path)["/device:TPU:0"]
    assert len(loaded) == 123
    assert {(o.scope, o.mhc) for o in loaded} == {("", False)}


# ----------------------------------------------------------------------
# the check against a lower precision
# ----------------------------------------------------------------------
@pytest.mark.long(seconds=125)
def test_the_check_fails_a_float8_probe_at_the_small_size():
    """Every product's operands rounded to float8 e4m3
    (``ops/moe.py:operand``): the nearest precision below the
    configuration's has to come out as not correct."""
    from benchmarks.engines import decoder_hc

    tiny = cells.override(cells.load_cell(CELL), TINY)
    probe = dataclasses.replace(
        tiny, config={**tiny.config, "dtype": "float8_e4m3fn"})
    check = decoder_hc.Session(probe, 3000000019).check()
    assert not check["ok"] and check["problems"]
    assert check["logits_rel"] > decoder_hc.LOGITS_RTOL
    # the hyper-connections stay float32 whatever the products' dtype
    assert check["mhc_marginal_err"] < decoder_hc.MHC_ERR_MAX
