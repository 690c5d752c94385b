"""Tests of the benchmark itself (CPU; `python -m pytest benchmarks/tests`).

They live under ``benchmarks/`` because the benchmark's ``paths`` may name
only directories of its own, so the repo's tier-1 command (``pytest
tests/``) does not collect them: PERF.md, Open questions.

- the harness end to end: every cell through ``benchmarks/run.py`` at a
  tiny size (the ``REHEARSE`` table below), the four-chip cell on four
  virtual CPU devices; and the CPC cell that ``BENCHMARK.json`` does not
  list yet (PERF.md section 7), Pallas in interpret mode;
- the contract: names, units, files, and ``BENCHMARK.json`` against the
  workload files;
- the trace reducer on the recorded TPU trace under ``testdata/`` and on
  hand-written cases;
- the comparison that decides ``correct``: a reference with the dual
  update or the write-back dropped must fail it;
- cell 1's largest-block epoch program compiled for ``v5e:2x2`` from
  here (on-chip-measurement guide section 2), in a fixture, never at
  import.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import cells, xplane  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

#: tiny sizes for the CPU rehearsal: ResNet9 in place of ResNet18 (same
#: module, 8 blocks), a handful of clients and samples; the check's bound
#: is the cells' own only at their size (at batch 8 on ResNet9's last
#: block, 137 k elements, FedAvg's second round showed 0.0034)
_RESNET = {"config": {"model": "resnet9", "K": 2, "batch": 8},
           "traffic": {"blocks": [0, 7], "samples_per_client": 16,
                       "check_moved_share": 0.02}}
REHEARSE = {
    "resnet18_admm_blocks": _RESNET,
    "resnet18_fedavg_fedsgd": {
        "config": _RESNET["config"],
        "traffic": {"blocks": [0, 7], "samples_per_client": 8, "Nadmm": 2,
                    "check_moved_share": 0.02}},
    "resnet18_admm_blocks_x4": {
        "config": {"model": "resnet9", "K": 8, "batch": 8},
        "traffic": _RESNET["traffic"]},
    "cpc_lofar_rotation": {
        "config": {"latent_dim": 16, "reduced_dim": 8, "batch": 4, "K": 2},
        "traffic": {"Niter": 2}},
}


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(cell, trace, rehearse=True, seconds=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
           "--seed", "5", "--seconds", str(seconds), "--trace", str(trace)]
    if rehearse:
        cmd += ["--rehearse", json.dumps(REHEARSE[cell])]
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=900)


# ----------------------------------------------------------------------
# the harness end to end
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell,trace", [
    ("resnet18_admm_blocks", 0), ("resnet18_fedavg_fedsgd", 1),
    ("resnet18_admm_blocks_x4", 1), ("cpc_lofar_rotation", 0),
    ("cpc_lofar_rotation", 1)])
def test_rehearsal(cell, trace):
    proc = run_cell(cell, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS            # no trace on a CPU: no
    assert result["correct"] is True, proc.stdout[-3000:]    # `breakdown`
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    bench = benchmark_json()
    kind = "per_layer" if trace else "end_to_end"
    known = {m["name"]: m for m in bench[kind]}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["unit"] == known[name]["unit"], name
    if not trace:
        assert set(result["metrics"]) == set(known)
        assert result["metrics"]["samples_per_s_chip"]["value"] > 0
    else:
        # everything the records alone can give is there; the readers of
        # the device trace return nothing without a TPU plane
        assert {"warmup_compile_s", "host_dispatches_per_round",
                "train_pct", "traced_sps_chip"} <= set(result["metrics"])


def test_no_result_line_without_a_tpu():
    proc = run_cell("resnet18_admm_blocks", 0, rehearse=False)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


# ----------------------------------------------------------------------
# the contract
# ----------------------------------------------------------------------
def test_benchmark_json_is_consistent_with_the_files():
    bench = benchmark_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(
            BENCH, "engines", body["engine"] + ".py"))
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = set()
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        layers.add(m["layer"])
        mod = os.path.join(BENCH, "metrics", m["name"] + ".py")
        assert os.path.exists(mod), mod
        with open(mod) as f:
            assert f'UNIT = "{m["unit"]}"' in f.read(), m["name"]
    seen = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        cell = cells.load_cell(w["name"])       # the three files exist
        assert (cell.config_name, cell.traffic_name, cell.chips) == (
            w["config"], w["traffic"], w["chips"])
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        # which cell reports which per-layer metric is said in ONE place,
        # the cell's own file, so that a later cell edits no entry here
        assert cell.per_layer and set(cell.per_layer) <= {
            m["name"] for m in bench["per_layer"]
            if w["name"] in m.get("workloads", [w["name"]])}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    for path in glob.glob(os.path.join(BENCH, "**", "*"), recursive=True):
        rel = os.path.relpath(path, REPO)
        if "__pycache__" in rel or rel.startswith("benchmarks/out"):
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


# ----------------------------------------------------------------------
# the trace reducer
# ----------------------------------------------------------------------
TINY = os.path.join(BENCH, "testdata", "tiny_tpu.xplane.pb")


def test_reducer_on_the_recorded_trace():
    """Recorded on one TPU v5 lite chip in PR 22: three passes, each a
    bf16 3x3 convolution program and the InfoNCE forward + backward
    vmapped over 4 clients, inside a ``bench_pass`` annotation."""
    trace = xplane.load(TINY)
    assert list(trace.devices) == ["/device:TPU:0"]
    ops = trace.devices["/device:TPU:0"]
    assert len(ops) == 123
    passes = [s for s in trace.host if s.name == "bench_pass"]
    assert len(passes) == 3
    # the whole recording: first op to last op
    t0, t1 = ops[0].start_ns, max(o.end_ns for o in ops)
    busy = xplane.busy_ns(ops, t0, t1)
    assert busy == pytest.approx(601_884, abs=2)            # ns
    assert (t1 - t0) == pytest.approx(25_146_559, abs=2)
    gaps = xplane.idle_gaps(ops, t0, t1)
    assert xplane.total(gaps) == pytest.approx(t1 - t0 - busy)
    # the two long gaps: the host between passes (a 10 ms sleep each)
    assert [g[1] - g[0] for g in gaps[:3]] == pytest.approx(
        [11_618_426, 11_569_342, 577_517], abs=2)
    table = xplane.op_table(ops, t0, t1)
    assert table[0][0] == "convert_reduce_fusion"
    assert table[0][1] == pytest.approx(3 * 115.786e-6, rel=1e-3)
    # per pass: the 3x3 convolution, and the three matrix products of the
    # InfoNCE backward (1,591 + 1,781 + 2,070 ns), which a TPU runs as
    # convolutions too
    conv = xplane.category_ns(ops, xplane.is_convolution, t0, t1)
    assert conv == pytest.approx(3 * (115_786 + 1_591 + 1_781 + 2_070),
                                 rel=1e-3)
    pallas = [o for o in ops if o.pallas]
    assert len(pallas) == 3 and all(
        o.dur_ns == pytest.approx(5006, abs=2) for o in pallas)
    assert not any(xplane.is_collective(o) for o in ops)


def _ops(*rows):
    return [xplane.Op(n, s, d, c) for n, s, d, c in rows]


def test_interval_arithmetic():
    assert xplane.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    ops = _ops(("a", 0, 4, ""), ("b", 2, 4, ""), ("c", 10, 5, ""))
    assert xplane.busy_ns(ops, 0, 20) == 11
    assert xplane.busy_ns(ops, 3, 12) == 5
    assert xplane.idle_gaps(ops, 0, 20) == [(15, 20), (6, 10)]
    spans = [xplane.Span("train", 0, 12), xplane.Span("stage", 6, 9),
             xplane.Span("sync", 12, 20)]
    assert xplane.attribute((6, 9), spans) == "stage"     # the nested one
    assert xplane.attribute((6, 10), spans) == "train"
    assert xplane.attribute((15, 20), spans) == "sync"
    assert xplane.attribute((30, 40), spans) == "unattributed"


def test_collective_exposed_on_two_devices():
    """Device 0 hides its all-reduce behind a fusion for 30 of its 40 ns;
    device 1 runs nothing else meanwhile.  A ``while`` that wraps
    everything is a container and hides nothing."""
    dev0 = _ops(("while.1", 0, 100, "while"),
                ("fusion.1", 0, 50, "loop fusion"),
                ("all-reduce.3", 20, 40, "all-reduce"),
                ("fusion.2", 80, 10, "loop fusion"))
    dev1 = _ops(("while.1", 0, 100, "while"),
                ("fusion.1", 0, 20, "loop fusion"),
                ("all-reduce.3", 20, 40, "all-reduce"))
    assert xplane.collective_exposed_ns(dev0, 0, 100) == 10
    assert xplane.collective_exposed_ns(dev1, 0, 100) == 40
    assert xplane.collective_exposed_ns(dev1, 30, 50) == 20

    from benchmarks.lib.records import TraceView
    from benchmarks.metrics import collective_exposed_pct, device_idle_pct

    view = TraceView({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                     (0.0, 100.0), [], "TPU v5 lite")
    assert collective_exposed_pct.read(None, view, None) == 40.0
    assert device_idle_pct.read(None, view, None) == 40.0   # device 1
    one = TraceView({"/device:TPU:0": dev0[:2]}, (0.0, 100.0), [],
                    "TPU v5 lite")      # no collective: one chip
    assert collective_exposed_pct.read(None, one, None) == 0.0
    assert collective_exposed_pct.read(None, None, None) is None


# ----------------------------------------------------------------------
# the comparison that decides `correct`
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cpu_devices():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    return jax.devices()


@pytest.mark.parametrize("traffic,broken", [
    ("admm_blocks", "dual"), ("fedavg_fedsgd", "writeback")])
def test_check_fails_a_broken_exchange(cpu_devices, traffic, broken):
    from benchmarks.engines import classifier
    from benchmarks.reference.fed_round import FedRoundReference

    class Broken(FedRoundReference):
        def exchange(self, xs, z, ys):
            new_xs, z, new_ys, primal = super().exchange(xs, z, ys)
            if broken == "dual":
                return new_xs, z, ys, primal       # y never updated
            return xs, z, new_ys, primal           # clients keep their x

    name = {"admm_blocks": "resnet18_admm_blocks",
            "fedavg_fedsgd": "resnet18_fedavg_fedsgd"}[traffic]
    # block 5 = ResNet9's [24, 29], 2.4 M parameters: like the cells'
    # largest block, large enough for the consensus penalty (which grows
    # with the block) to be a visible part of the round's loss
    cell = cells.override(cells.load_cell(name), {
        "config": {"model": "resnet9", "K": 2, "batch": 8}, "chips": 1,
        "traffic": {"blocks": [5]}})
    session = classifier.Session(cell, seed=5)
    good = session.check()
    assert good["ok"], good["problems"]
    bad = session.check(reference=Broken)
    assert not bad["ok"]


def test_pooled_cpc_source_reorders_one_round():
    """The CPC engine's data: one round's minibatches per client from
    the seed, every later round the same ones in another order."""
    import numpy as np

    from benchmarks.engines.cpc import PooledSource

    make = lambda: PooledSource(["a.h5", "b.h5"], ["0", "0"], 3,
                                batch_size=2, patch_size=32, seed=5)
    src = make()
    assert src.pool.shape[:2] == (2, 3)
    rounds = [src.round_batches(3)[2] for _ in range(4)]
    key = lambda batch: sorted(float(mb.sum()) for mb in batch[0])
    assert all(key(b) == key(src.pool) for b in rounds)
    assert any(not np.array_equal(b, rounds[0]) for b in rounds[1:])
    assert np.array_equal(make().round_batches(3)[2], rounds[0])
    assert src.round_batches(3, clients=[1])[2].shape[0] == 1
    with pytest.raises(ValueError):
        src.round_batches(2)


def test_infonce_comparison_bounds():
    import numpy as np

    from benchmarks.engines import cpc

    want = np.float32([10.0, 20.0])
    g = [np.ones((2, 3), np.float32)]
    assert cpc.compare_infonce(want * 1.0001, want, g, g)["ok"]
    assert not cpc.compare_infonce(want * 1.01, want, g, g)["ok"]
    assert not cpc.compare_infonce(want, want, [g[0] * 1.2], g)["ok"]


# ----------------------------------------------------------------------
# compile for the chip, without the chip
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_largest_block_epoch_compiles_for_v5e_2x2(topo, cpu_devices):
    """The four-chip cell's epoch program on its largest block, at the
    cell's real size, for the four described chips: the sharding holds,
    the program fits a chip, and the epoch itself needs no collective
    (the exchange is its own program)."""
    from benchmarks.lib.compile_for_chip import compile_epoch

    cell = cells.load_cell("resnet18_admm_blocks_x4")
    compiled = compile_epoch(cell, topo.devices[:4], block=-1)
    mem = compiled.memory_analysis()
    per_chip = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert per_chip < 16e9, per_chip
    text = compiled.as_text()
    assert "all-reduce(" not in text and "all-gather(" not in text
