"""The ``glm47flash_fedavg_mtp_blocks`` cell's harness on the CPU: the
cell's rehearsal through ``benchmarks/run.py --rehearse``, its entries in
``BENCHMARK.json``, the work model of ``benchmarks/lib/glm_work.py`` and
the new readers on synthetic device events (a share above 100 % is a
failure here as it is for the driver).
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import cells, glm_work, peaks, xplane  # noqa: E402
from benchmarks.lib.records import Records, TraceView  # noqa: E402
from benchmarks.lib.window import Pass  # noqa: E402

CELL = "glm47flash_fedavg_mtp_blocks"
NEW = ["glm_step_mfu_pct", "mla_attn_busy_pct", "mla_core_roofline_pct",
       "mtp_busy_pct"]
TINY = {"config": {
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 12,
    "kv_lora_rank": 8, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 16, "intermediate_size": 80, "moe_intermediate_size": 24,
    "n_routed_experts": 16, "num_experts_per_tok": 3, "experts_held": 4,
    "ep_rank": 1, "vocab_rows": 64, "attn_block": 16, "seq_len": 24,
    "lr": 0.001, "pair_rows_factor": 8.0, "bias_scale": 0.02},
    "traffic": {"samples_per_client": 4, "check_moved_share": 0.05}}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return __import__(f"benchmarks.metrics.{name}", fromlist=["read"])


# ----------------------------------------------------------------------
@pytest.mark.long(seconds=128)
def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--rehearse", json.dumps(TINY)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 6
    known = {m["name"]: m for m in bench()["per_layer"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == known[name]["unit"], name
    # what the records alone give is there; the device readers wait for a
    # trace
    assert {"train_pct", "comm_pct", "wire_mb_per_round"} \
        <= set(result["metrics"])
    assert not set(NEW) & set(result["metrics"])
    check = json.loads(next(
        line for line in lines if line.startswith("check: "))[len("check: "):])
    assert check["ok"] and len(check["rounds"]) == 2
    assert {"loss_rel", "mtp_loss_rel", "logits_rel", "grad_rel_block3",
            "grad_rel_block6"} <= set(check)
    # the window's own check of the MTP term ran
    assert any(line.startswith("mtp_loss: untimed pass ") for line in lines)


def test_the_cell_and_its_entries_in_benchmark_json():
    b = bench()
    cell = cells.load_cell(CELL)
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell.config_name, cell.traffic_name, 1) and len(entry["why"]) <= 200
    # the fifth cell and the fourth configuration; later PRs add after them
    assert b["workloads"][4] is entry and len(b["workloads"]) >= 5
    conf = b["configs"][3]
    assert conf["name"] == cell.config_name == cell.config["name"]
    assert conf["reduced"] == cell.config["reduced"]
    assert conf["source"] == cell.config["source"]
    assert conf["file"] == f"benchmarks/configs/{cell.config_name}.json"
    new = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW == [
        m["name"] for m in b["per_layer"][22:26]]
    assert all(m["moves"] == "samples_per_s_chip" and m["unit"] == "%"
               for m in new)
    # the cell reports every metric without a list, and its own four
    assert cell.per_layer == [m["name"] for m in b["per_layer"]
                              if CELL in m.get("workloads", [CELL])]
    assert len(cell.per_layer) == 19
    # the traffic is the issue's
    t = cell.traffic
    assert (t["algorithm"], t["blocks"], t["Nadmm"], t["Nepoch"],
            t["samples_per_client"]) == ("fedavg", [3, 6, 12], 2, 1, 8)
    assert (cell.config["K"], cell.config["batch"],
            cell.config["seq_len"]) == (2, 2, 4096)
    assert cell.config["engine"] == "decoder"
    # one cell on four chips
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_the_engine_builds_any_registered_decoder_from_its_own_keys():
    from benchmarks.engines import decoder

    glm = decoder.build_model(cells.load_cell(CELL).config)
    assert type(glm).__name__ == "Glm4MoeLite"
    assert (glm.q_lora_rank, glm.n_routed_experts, glm.experts_held,
            glm.mtp_loss_weight, glm.bias_scale) == (768, 64, 8, 0.1, 0.01)
    qwen = decoder.build_model(cells.load_cell(
        "qwen3next_fedavg_blocks").config)
    assert type(qwen).__name__ == "Qwen3Next"
    assert (qwen.num_experts, qwen.experts_held, qwen.layers) == (512, 32, 4)


# ----------------------------------------------------------------------
# the work model
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


TOKENS = 65536
PAIRS = 5 * TOKENS * 4 * 8 // 64         # five expert layers at the mean


def test_forward_work_is_the_issue_s_count(cell):
    cfg = cell.config
    mflop = lambda f: f / 1e6
    assert mflop(6 * glm_work.mla_weight_flops(cfg)) == pytest.approx(
        261, abs=0.5)
    assert mflop(6 * glm_work.mla_core_flops(cfg, 4096)) == pytest.approx(
        252, abs=0.5)
    assert mflop(2 * glm_work.head_flops(cfg)) == pytest.approx(159, abs=0.5)
    assert mflop(5 * (glm_work.moe_dense_flops(cfg)
                      + 0.5 * glm_work.pair_flops(cfg))) == pytest.approx(
        143, abs=0.5)
    assert mflop(glm_work.dense_mlp_flops(cfg)) == pytest.approx(126, abs=0.5)
    # the head block's round is the forward pass, the main head's
    # activation gradient and both heads' weight gradients
    forward = glm_work.round_flops(cfg, 11, TOKENS, PAIRS, 4096) \
        - 3 * glm_work.head_flops(cfg) * TOKENS
    assert mflop(forward / TOKENS) == pytest.approx(957, abs=1.5)


def test_round_flops_follow_the_active_block(cell):
    cfg = cell.config
    f = {b: glm_work.round_flops(cfg, b, TOKENS, PAIRS, 4096)
         for b in range(14)}
    # the deeper the active block in the trunk, the less of the backward;
    # the embedding's gradient is a scatter and no product
    assert f[1] > f[0] > f[3] > f[5] > f[9] and f[2] > f[4] > f[6] > f[10]
    assert f[3] > f[6] > f[12] > f[11] > f[13] > 0   # the cell's: 3, 6, 12
    assert f[0] < 3.0 * (f[11] - 3 * glm_work.head_flops(cfg) * TOKENS)
    # the parts: six mixers, five expert layers, two heads, one merge
    kinds = [k for k, _ in glm_work.parts(cfg)]
    assert [kinds.count(k) for k in ("mla", "moe", "mlp", "head",
                                     "merge")] == [6, 5, 1, 2, 1]
    # block 12 reaches the MTP branch only; block 6 layers 3, 4 and it
    need = lambda b: [i for i, (a, _) in enumerate(glm_work.needs(cfg, b))
                      if a]
    assert need(12) == [11, 12, 13, 14] and need(13) == [13, 14]
    assert need(6) == list(range(5, 15)) and need(11) == [10]
    assert [i for i, (_, w) in enumerate(glm_work.needs(cfg, 11)) if w] \
        == [10, 14]
    assert [i for i, (_, w) in enumerate(glm_work.needs(cfg, 12)) if w] \
        == [11, 12]


def test_core_work_counts_forward_in_six_mixers_and_backward_where_reached(
        cell):
    cfg = cell.config
    one = TOKENS * glm_work.mla_core_flops(cfg, 4096)
    for block, backward in ((3, 5), (6, 3), (12, 1), (11, 0), (0, 6)):
        fl, by = glm_work.mla_core_work(cfg, block, TOKENS, 4096)
        assert fl == pytest.approx((6 + 2 * backward) * one)
        assert by == pytest.approx((6 + 2 * backward) * TOKENS
                                   * glm_work.mla_core_bytes(cfg))
    assert glm_work.mla_core_flops(cfg, 4096) == 2 * 20 * 512 * 4097 / 2


# ----------------------------------------------------------------------
# the readers on synthetic events
# ----------------------------------------------------------------------
def scoped(name, scope, mtp, start, dur, category=""):
    return glm_work.ScopedOp(xplane.Op(name, start, dur, category), scope,
                             mtp)


def fake_run(took_ns, rounds):
    ops = [scoped("while.1", "mla_core", False, 0, 10 * took_ns, "while"),
           scoped("mla_core.1", "mla_core", False, 0, took_ns),
           scoped("fusion.2", "mla_core", True, took_ns / 2, took_ns / 2),
           scoped("fusion.3", "mla_attn", True, took_ns, took_ns),
           scoped("ragged-dot-none", "moe_experts", False, 2 * took_ns,
                  took_ns),
           scoped("fusion.9", "", False, 3 * took_ns, took_ns)]
    trace = TraceView({"/device:TPU:0": [o.op for o in ops]},
                      (0.0, 5.0 * took_ns), [], "TPU v5 lite")
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0, traced=True)],
                   samples_per_round=16, chips=1, counters={})
    return {"/device:TPU:0": ops}, trace, recs


def test_scope_readers_on_synthetic_events(cell, monkeypatch):
    rounds = [{"block": 0, "tokens": TOKENS, "moe_pairs_local": PAIRS,
               "round_seconds": 1.0}]
    peak = peaks.peaks_for("TPU v5 lite")
    fl, by = glm_work.mla_core_work(cell.config, 3, TOKENS, 4096)
    least = max(fl / peak["bf16_flops"], by / peak["hbm_bytes_per_s"])
    assert least == fl / peak["bf16_flops"]          # bound by the products
    roof = reader("mla_core_roofline_pct")
    # the kernels take exactly the least time: 100 %; twice it: 50 %
    for factor, want in ((1.0, 100.0), (2.0, 50.0)):
        ops, trace, recs = fake_run(factor * least * 1e9, rounds)
        monkeypatch.setattr(glm_work, "of_cell", lambda c: ops)
        got = roof.read(recs, trace, cell)
        assert got == pytest.approx(want, rel=1e-6) and got <= 100.0
        # the container is left out; fusion.2 lies inside mla_core.1; the
        # mixer is the core and what surrounds it
        assert reader("mla_attn_busy_pct").read(recs, trace, cell) \
            == pytest.approx(100.0 * 2 / 4)
        # half of the core's op and the projection pass through mtp
        assert reader("mtp_busy_pct").read(recs, trace, cell) \
            == pytest.approx(100.0 * 1.5 / 4)
    # without a trace, or on a program with no such scopes (the parent)
    for nothing in (None, {}, {"/device:TPU:0": [
            scoped("fusion.9", "", False, 0.0, 10.0)]}):
        monkeypatch.setattr(glm_work, "of_cell", lambda c: nothing)
        for name in NEW[1:]:
            assert reader(name).read(recs, trace, cell) is None
    assert roof.read(recs, None, cell) is None


def test_mfu_reader(cell):
    peak = peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    rec = {"block": 1, "tokens": TOKENS, "moe_pairs_local": PAIRS}
    flops = glm_work.round_flops(cell.config, 6, TOKENS, PAIRS, 4096)
    rounds = [dict(rec, round_seconds=flops / peak / 0.25)]
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0)],
                   samples_per_round=16, chips=1, counters={})
    trace = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert reader("glm_step_mfu_pct").read(recs, trace, cell) \
        == pytest.approx(25.0)
    assert reader("glm_step_mfu_pct").read(recs, None, cell) is None
    # a parent's records (no `tokens`) read nothing and raise nothing
    old = Records(warmup=[], passes=[Pass([{"round_seconds": 1.0}], 0.0,
                                          1.0)],
                  samples_per_round=16, chips=1, counters={})
    assert reader("glm_step_mfu_pct").read(old, trace, cell) is None


def test_scope_of_paths():
    s = glm_work.scope_of
    base = "jit(train_epoch)/vmap()/while/body/transpose(jvp(Glm4MoeLite))/"
    assert s(base + "checkpoint/mla_attn/mla_core/pallas_call") == "mla_core"
    assert s(base + "mla_attn/dot_general") == "mla_attn"
    assert s(base + "mtp/mla_attn/mla_core/mla_core.3") == "mla_core"
    assert s(base + "dense_mlp/dot_general") == "dense_mlp"
    assert s(base + "mtp/moe_route/sort") == "moe_route"
    assert s(base + "mtp/lm_head_loss/reduce_max") == "lm_head_loss"
    assert s("ragged-dot-none") == "moe_experts"
    assert s("", "ragged-dot-metadata") == "moe_experts"
    assert s("jit(comm)/reduce_sum") == ""
    through = lambda p: bool(glm_work._THROUGH_MTP.search(p))
    assert through(base + "mtp/mla_attn/mla_core/pallas_call")
    assert through("mtp/dot_general") and through(base + "checkpoint/mtp")
    assert not through(base + "Glm4MoeLite/mtp_mixer/dot_general")
    assert not through(base + "mla_attn/dot_general")


def test_scoped_ops_of_a_recorded_trace():
    path = os.path.join(REPO, "benchmarks", "testdata", "tiny_tpu.xplane.pb")
    loaded = glm_work.load(path)["/device:TPU:0"]
    assert len(loaded) == 123
    assert {(o.scope, o.mtp) for o in loaded} == {("", False)}
