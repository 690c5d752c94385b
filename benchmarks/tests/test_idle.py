"""Tests of the round-loop readers that PR 24 added beside the benchmark
(CPU; ``python -m pytest benchmarks/tests``): ``lib/idle.py`` with the
four ``idle_*_pct`` readers, and ``block_switch_ms``, ``round_tail_ms``,
``dispatch_ms_per_round``.

No cell lists the seven yet (a cell's ``per_layer`` list is in a file
that only a ``benchmark`` PR may edit: PERF.md section 7), so the run
through ``benchmarks/run.py`` below lists them for itself.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import idle, xplane  # noqa: E402
from benchmarks.lib.records import Records, TraceView  # noqa: E402
from benchmarks.lib.window import Pass  # noqa: E402
from benchmarks.metrics import device_idle_pct  # noqa: E402

COUNTERS = ("block_switch_ms", "round_tail_ms", "dispatch_ms_per_round")
IDLE = ("idle_block_switch_pct", "idle_round_tail_pct", "idle_in_round_pct",
        "idle_unattributed_pct")
TINY = os.path.join(BENCH, "testdata", "tiny_tpu.xplane.pb")


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}")


def _ops(*rows):
    return [xplane.Op(n, s, d, "") for n, s, d in rows]


def test_the_readers_are_ready_to_be_listed():
    """What ``test_benchmark_json_is_consistent_with_the_files`` will ask
    of them once ``BENCHMARK.json`` names them."""
    for name in COUNTERS:
        assert reader(name).UNIT == "ms"
    for name in IDLE:
        assert reader(name).UNIT == "%"
        assert reader(name).read(None, None, None) is None   # no trace
    names = [n for group in idle.GROUPS.values() for n in group]
    assert len(names) == len(set(names))         # a name is in one group
    from federated_pytorch_test_tpu.train.rounds import BLOCK_SWITCH_PARTS
    assert set(BLOCK_SWITCH_PARTS) < set(idle.GROUPS["block_switch"])


# ----------------------------------------------------------------------
# idle by span
# ----------------------------------------------------------------------
def test_split_by_span_innermost_wins_and_the_rest_is_unattributed():
    spans = [xplane.Span("block switch", 10, 60),      # made up, outermost
             xplane.Span("round_tail", 10, 20),
             xplane.Span("block_switch", 20, 58),
             xplane.Span("block_vars", 30, 50),
             xplane.Span("train", 60, 90)]
    # one gap across everything, from ahead of the first span to behind
    # the last
    got = idle.split_by_span([(0, 100)], spans)
    assert got == {"unattributed": 10 + 10, "round_tail": 10,
                   "block_switch": 10 + 8, "block_vars": 20,
                   "block switch": 2, "train": 30}
    assert sum(got.values()) == 100
    # gaps that end inside spans, and one that no span touches
    got = idle.split_by_span([(25, 35), (55, 59), (95, 99)], spans)
    assert got == {"block_switch": 5 + 3, "block_vars": 5,
                   "block switch": 1, "unattributed": 4}
    # a stage nested in train, as the unfused round stamps them
    nested = [xplane.Span("train", 0, 50), xplane.Span("stage", 0, 10)]
    assert idle.split_by_span([(5, 20)], nested) == {"stage": 5, "train": 10}
    assert idle.split_by_span([(5, 20)], []) == {"unattributed": 15}
    assert idle.split_by_span([], nested) == {}


def test_idle_by_span_takes_the_chip_with_most_idle(capsys):
    dev0 = _ops(("fusion.1", 0, 60), ("fusion.2", 70, 30))          # idle 10
    dev1 = _ops(("while.1", 0, 100), ("fusion.1", 0, 20),           # idle 60;
                ("fusion.2", 80, 20))              # the container hides none
    spans = [xplane.Span("train", 0, 50), xplane.Span("comm", 50, 70)]
    view = TraceView({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                     (0.0, 100.0), spans, "TPU v5 lite")
    table = idle.idle_by_span(view)
    assert table == pytest.approx({"train": 30e-9, "comm": 20e-9,
                                   "unattributed": 10e-9})
    assert sum(table.values()) == pytest.approx(
        xplane.total(idle.most_idle_chip(view)) / 1e9)
    # computed and printed once per view
    line = capsys.readouterr().out.strip()
    assert line.startswith("idle_by_span=")
    assert json.loads(line.split("=", 1)[1]) == pytest.approx(table)
    assert idle.idle_by_span(view) is table
    assert capsys.readouterr().out == ""
    shares = {n: reader(n).read(None, view, None) for n in IDLE}
    assert shares == pytest.approx({
        "idle_block_switch_pct": 0.0, "idle_round_tail_pct": 0.0,
        "idle_in_round_pct": 50.0, "idle_unattributed_pct": 10.0})
    assert sum(shares.values()) == pytest.approx(
        device_idle_pct.read(None, view, None))               # device 1's


def test_idle_shares_on_the_recorded_trace_add_up_to_device_idle():
    """The recorded trace's three passes stand for three rounds; the
    host's 10 ms between them is a tail and a block switch, stamped the
    way the engine stamps them, under what ``trace_view`` makes up."""
    trace = xplane.load(TINY)
    ops = trace.devices["/device:TPU:0"]
    p1, p2, p3 = [s for s in trace.host if s.name == "bench_pass"]
    mid = lambda a, b, f: a + f * (b - a)
    spans = [
        xplane.Span("train", p1.start_ns, p1.end_ns),
        xplane.Span("stage", p1.start_ns, mid(p1.start_ns, p1.end_ns, 0.2)),
        xplane.Span("between rounds", p1.end_ns, p2.start_ns),
        xplane.Span("round_tail", p1.end_ns, mid(p1.end_ns, p2.start_ns, .4)),
        xplane.Span("train", p2.start_ns, p2.end_ns),
        xplane.Span("block switch", p2.end_ns, p3.start_ns),
        xplane.Span("round_tail", p2.end_ns, mid(p2.end_ns, p3.start_ns, .1)),
        xplane.Span("block_switch", mid(p2.end_ns, p3.start_ns, 0.1),
                    p3.start_ns),
        xplane.Span("block_vars", mid(p2.end_ns, p3.start_ns, 0.3),
                    mid(p2.end_ns, p3.start_ns, 0.9)),
        xplane.Span("train", p3.start_ns, p3.end_ns)]
    window = (ops[0].start_ns - 1000.0, max(o.end_ns for o in ops) + 1000.0)
    view = TraceView(trace.devices, window, spans, "TPU v5 lite")
    table = idle.idle_by_span(view)
    gap2 = p3.start_ns - p2.end_ns
    # the chip ran little under the 0.6 of the gap that block_vars covers
    assert 0.95 * 0.6 * gap2 / 1e9 < table["block_vars"] <= 0.6 * gap2 / 1e9
    shares = {n: reader(n).read(None, view, None) for n in IDLE}
    assert all(v > 0 for v in shares.values()), shares
    assert sum(shares.values()) == pytest.approx(
        device_idle_pct.read(None, view, None), abs=1e-9)
    # the two host gaps are nearly all of the recording
    assert shares["idle_block_switch_pct"] + shares["idle_round_tail_pct"] > 80


# ----------------------------------------------------------------------
# the three readers of the round records
# ----------------------------------------------------------------------
def _records(passes):
    return Records(warmup=[], passes=passes, samples_per_round=1, chips=1,
                   counters={})


def test_counter_readers_on_hand_written_records():
    rnd = lambda **kw: dict(round_seconds=1.0, **kw)
    first = Pass([rnd(block_switch_seconds=0.030, gap_seconds=0.500,
                      dispatch_seconds=0.004),      # behind the warm-up pass
                  rnd(gap_seconds=0.002, dispatch_seconds=0.002),
                  rnd(block_switch_seconds=0.010, gap_seconds=0.013,
                      dispatch_seconds=0.006)], 0.0, 3.0)
    traced = Pass([rnd(block_switch_seconds=9.0, gap_seconds=9.9,
                       dispatch_seconds=9.0)], 3.0, 4.0, traced=True)
    last = Pass([rnd(block_switch_seconds=0.020, gap_seconds=4.0,
                     dispatch_seconds=0.004),       # the profiler stopped
                 rnd(gap_seconds=0.004, dispatch_seconds=0.004)], 4.0, 6.0)
    recs = _records([first, traced, last])
    # medians over the untraced rounds: switches 30, 10, 20 ms; tails
    # 470, 2, 3, 3980, 4 ms
    assert reader("block_switch_ms").read(recs, None, None) == \
        pytest.approx(20.0)
    assert reader("round_tail_ms").read(recs, None, None) == \
        pytest.approx(4.0)
    assert reader("dispatch_ms_per_round").read(recs, None, None) == \
        pytest.approx(4.0)
    # a program that does not write the fields (the parent): nothing read
    bare = _records([Pass([rnd(), rnd()], 0.0, 2.0)])
    for name in COUNTERS:
        assert reader(name).read(bare, None, None) is None


def test_counter_metrics_in_a_rehearsed_traced_run():
    """Cell 2 through ``benchmarks/run.py --trace 1`` at the rehearsal's
    tiny size with the seven names listed: the three counters are in the
    result line, the idle shares are left out (no TPU plane on a CPU)."""
    from test_benchmarks import REHEARSE

    cell = "resnet18_fedavg_fedsgd"
    code = (
        "import dataclasses, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from benchmarks.lib import cells\n"
        "import benchmarks.run as run\n"
        "load = cells.load_cell\n"
        f"new = {list(COUNTERS + IDLE)!r}\n"
        "cells.load_cell = lambda name: (lambda c: dataclasses.replace(\n"
        "    c, per_layer=c.per_layer + new))(load(name))\n"
        f"sys.exit(run.main(['--workload', {cell!r}, '--seed', '3000000019',"
        " '--seconds', '1', '--trace', '1', '--rehearse',"
        f" {json.dumps(REHEARSE[cell])!r}]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, proc.stdout[-3000:]
    metrics = result["metrics"]
    for name in COUNTERS:
        assert metrics[name]["unit"] == "ms"
        assert metrics[name]["value"] > 0, name
    assert not set(IDLE) & set(metrics)
    assert {"train_pct", "traced_sps_chip"} <= set(metrics)   # the old ones
