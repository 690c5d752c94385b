"""The ``qwen3next_fedavg_blocks`` cell's harness on the CPU: the cell's
rehearsal through ``benchmarks/run.py --rehearse``, the work models of
``benchmarks/lib/lm_work.py`` and the scope readers on synthetic device
events (a share above 100 % is a failure here as it is for the driver).
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

from benchmarks.lib import cells, lm_work, peaks, scopes, xplane  # noqa: E402
from benchmarks.lib.records import Records, TraceView  # noqa: E402
from benchmarks.lib.window import Pass  # noqa: E402

CELL = "qwen3next_fedavg_blocks"
TINY = {"config": {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8, "num_experts": 16,
    "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "experts_held": 4, "ep_rank": 1,
    "vocab_rows": 64, "chunk": 16, "attn_block": 16, "seq_len": 24,
    "lr": 0.001, "pair_rows_factor": 8.0},
    "traffic": {"samples_per_client": 4, "check_moved_share": 0.05}}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ----------------------------------------------------------------------
def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--rehearse", json.dumps(TINY)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 6
    known = {m["name"]: m for m in bench()["per_layer"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == known[name]["unit"], name
    # what the records alone give is there; the device readers wait for a
    # trace
    assert {"train_pct", "comm_pct", "wire_mb_per_round",
            "moe_load_max_over_mean"} <= set(result["metrics"])
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    check = json.loads(next(
        line for line in proc.stdout.splitlines()
        if line.startswith("check: "))[len("check: "):])
    assert check["ok"] and len(check["rounds"]) == 2
    assert {"loss_rel", "logits_rel", "grad_rel_block1",
            "grad_rel_block4"} <= set(check)


def test_the_cell_and_its_entries_in_benchmark_json():
    b = bench()
    cell = cells.load_cell(CELL)
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell.config_name, cell.traffic_name, 1) and len(entry["why"]) <= 200
    conf = next(c for c in b["configs"] if c["name"] == cell.config_name)
    assert conf["reduced"] == cell.config["reduced"]
    assert conf["source"] == cell.config["source"]
    new = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in new) == sorted([
        "lm_step_mfu_pct", "gdn_scan_busy_pct", "moe_experts_busy_pct",
        "moe_route_busy_pct", "gdn_scan_roofline_pct",
        "moe_experts_roofline_pct", "moe_load_max_over_mean"])
    assert all(m["moves"] == "samples_per_s_chip" for m in new)
    assert set(cell.per_layer) == {m["name"] for m in b["per_layer"]
                                   if CELL in m.get("workloads", [CELL])}
    # the published widths are the catalog's
    published = {
        "hidden_size": 2048, "num_attention_heads": 16, "head_dim": 256,
        "num_key_value_heads": 2, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "num_experts": 512, "num_experts_per_tok": 10,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "rms_norm_eps": 1e-6, "num_hidden_layers": 48, "vocab_size": 151936,
        "full_attention_interval": 4, "intermediate_size": 5120}
    for key, value in published.items():
        assert cell.config[key] == value, key


# ----------------------------------------------------------------------
# the work models
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_round_flops_follow_the_active_block(cell):
    cfg, T = cell.config, 4096
    tokens, pairs = 65536, int(65536 * 10 * 32 / 512) * 4
    f = {b: lm_work.round_flops(cfg, b, tokens, pairs, T)
         for b in (1, 4, 7, 9)}
    # the deeper the active block, the more of the backward pass is needed
    assert f[1] > f[4] > f[7] > f[9] > 0
    forward = lm_work.round_flops(cfg, 9, tokens, pairs, T) \
        - lm_work.head_flops(cfg) * tokens
    per_token = forward / tokens / 1e9
    assert 0.3 < per_token < 0.8            # the issue reckons 0.47 GFLOP
    assert f[1] < 3.2 * f[9]                # never more than three passes


def test_kernel_work_models(cell):
    cfg = cell.config
    fl, by = lm_work.gdn_scan_work(cfg, 1, 4096)
    fl7, by7 = lm_work.gdn_scan_work(cfg, 7, 4096)
    assert fl == 3 * fl7 and by == 3 * by7       # backward in all three
    assert fl7 == 3 * 4096 * 6 * 128 * 128 * 32
    efl, eby = lm_work.moe_experts_work(cfg, 4, 4 * 5120)
    efl9, eby9 = lm_work.moe_experts_work(cfg, 9, 4 * 5120)
    assert efl9 == 4 * 5120 * 6 * 2048 * 512 and efl > efl9
    assert eby9 >= 4 * 2 * 3 * 32 * 2048 * 512  # the weights read once


def scoped(name, scope, start, dur, category=""):
    return scopes.ScopedOp(xplane.Op(name, start, dur, category), scope)


def fake_run(cell, took_ns, rounds):
    ops = [scoped("while.1", "gdn_scan", 0, 10 * took_ns, "while"),
           scoped("fusion.1", "gdn_scan", 0, took_ns),
           scoped("fusion.2", "gdn_scan", took_ns / 2, took_ns / 2),
           scoped("ragged-dot-none", "moe_experts", took_ns, took_ns),
           scoped("fusion.9", "", 2 * took_ns, took_ns)]
    trace = TraceView({"/device:TPU:0": [o.op for o in ops]},
                      (0.0, 4.0 * took_ns), [], "TPU v5 lite")
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0, traced=True)],
                   samples_per_round=16, chips=1, counters={})
    return {"/device:TPU:0": ops}, trace, recs


def test_scope_readers_on_synthetic_events(cell, monkeypatch):
    rounds = [{"block": 0, "tokens": 65536, "moe_pairs_local": 4 * 40960,
               "round_seconds": 1.0}]
    peak = peaks.peaks_for("TPU v5 lite")
    fl, by = lm_work.gdn_scan_work(cell.config, 1, 65536)
    least = max(fl / peak["bf16_flops"], by / peak["hbm_bytes_per_s"])
    # the kernel takes exactly the least time: 100 %; twice it: 50 %
    for factor, want in ((1.0, 100.0), (2.0, 50.0)):
        ops, trace, recs = fake_run(cell, factor * least * 1e9, rounds)
        monkeypatch.setattr(scopes, "of_cell", lambda c: ops)
        mod = __import__("benchmarks.metrics.gdn_scan_roofline_pct",
                         fromlist=["read"])
        got = mod.read(recs, trace, cell)
        assert got == pytest.approx(want, rel=1e-6) and got <= 100.0
        busy = __import__("benchmarks.metrics.gdn_scan_busy_pct",
                          fromlist=["read"]).read(recs, trace, cell)
        # the container is left out; fusion.2 lies inside fusion.1
        assert busy == pytest.approx(100.0 / 3.0)
        moe = __import__("benchmarks.metrics.moe_experts_busy_pct",
                         fromlist=["read"]).read(recs, trace, cell)
        assert moe == pytest.approx(100.0 / 3.0)
        # a scope with no op reads nothing, and nothing raises
        assert __import__("benchmarks.metrics.moe_route_busy_pct",
                          fromlist=["read"]).read(recs, trace, cell) is None
    # without a trace, or on a program with no scopes (the parent commit)
    monkeypatch.setattr(scopes, "of_cell", lambda c: None)
    assert mod.read(recs, trace, cell) is None
    assert mod.read(recs, None, cell) is None


def test_mfu_and_load_readers(cell):
    peak = peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    rec = {"block": 1, "tokens": 65536, "moe_pairs_local": 4 * 40960,
           "moe_load_max_over_mean": 1.5}
    flops = lm_work.round_flops(cell.config, 4, 65536, 4 * 40960, 4096)
    rounds = [dict(rec, round_seconds=flops / peak / 0.25)]
    recs = Records(warmup=[], passes=[Pass(rounds, 0.0, 1.0)],
                   samples_per_round=16, chips=1, counters={})
    trace = types.SimpleNamespace(device_kind="TPU v5 lite")
    mfu = __import__("benchmarks.metrics.lm_step_mfu_pct",
                     fromlist=["read"]).read(recs, trace, cell)
    assert mfu == pytest.approx(25.0)
    load = __import__("benchmarks.metrics.moe_load_max_over_mean",
                      fromlist=["read"]).read(recs, None, cell)
    assert load == 1.5


def test_scope_of_paths():
    s = scopes.scope_of
    base = "jit(train_epoch)/vmap()/while/body/transpose(jvp(Qwen3Next))/"
    assert s(base + "checkpoint/gdn/gdn_scan/hid,hde->hie/dot_general") \
        == "gdn_scan"
    assert s(base + "gdn/dot_general") == "gdn"
    assert s(base + "moe_experts/ragged_dot_general") == "moe_experts"
    assert s(base + "moe_route/sort") == "moe_route"
    assert s("ragged-dot-none") == "moe_experts"
    assert s("", "ragged-dot-metadata") == "moe_experts"
    assert s("jit(comm)/reduce_sum") == ""


def test_event_stat_reads_the_jax_path_from_a_recorded_trace():
    path = os.path.join(REPO, "benchmarks", "testdata", "tiny_tpu.xplane.pb")
    ops = scopes.event_stat(path, "tf_op")["/device:TPU:0"]
    assert any(v.startswith("jit(info_nce_fused)/") for v in ops.values())
    loaded = scopes.load(path)["/device:TPU:0"]
    assert len(loaded) == 123 and {o.scope for o in loaded} == {""}
