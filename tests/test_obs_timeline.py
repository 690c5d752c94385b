"""The host timeline outside the round windows.

A round record spans ``[t_round, t_round + round_seconds]``.  The engine
also stamps what the host does outside of it: ``block_switch`` (with its
parts ``build_fns`` / ``block_size`` / ``block_vars`` / ``init_opt``)
ahead of each block visit's first round and ``round_tail`` behind every
round, so that rounds, switches and tails tile the whole run, and three
round fields carry the same seconds whether the recorder is on or off:
``block_switch_seconds``, ``gap_seconds``, ``dispatch_seconds``.  Beside
the first: ``block_switch_h2d_bytes``, the bytes that switch
staged from host memory; the per-block state is made on the device, so
only a stateful compressor's fresh rows are left to count.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import pytest

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import (
    BlockModule,
    elu,
    flatten,
    max_pool_2x2,
    pairs,
)
from federated_pytorch_test_tpu.obs import SCHEMA_VERSION, validate_record
from federated_pytorch_test_tpu.obs import trace as obs_trace
from federated_pytorch_test_tpu.obs.report import read_records
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FedAvg,
    FederatedConfig,
)
from federated_pytorch_test_tpu.train.rounds import BLOCK_SWITCH_PARTS

K = 4
BLOCKS, ROUNDS = 2, 2           # two block visits x two rounds each


class TinyNet(BlockModule):
    """2-block toy CNN (same shape as test_obs's)."""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                     name="conv1")(x)))
        x = flatten(x)
        return nn.Dense(10, name="fc1")(x)

    def param_order(self):
        return pairs("conv1", "fc1")

    def train_order_block_ids(self):
        return [[0, 1], [2, 3]]

    def linear_layer_ids(self):
        return [1]


@pytest.fixture(scope="module")
def data():
    return FederatedCifar10(K=K, batch=16, limit_per_client=32,
                            limit_test=32)


def small_cfg(**kw):
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=ROUNDS, default_batch=16,
                check_results=False, admm_rho0=0.1, obs_sinks="memory")
    base.update(kw)
    return FederatedConfig(**base)


ALGOS = {"fedavg": FedAvg, "admm": AdmmConsensus}
CASES = [pytest.param(a, f, id=f"{a}-{'fused' if f else 'unfused'}")
         for a in ALGOS for f in (False, True)]


def run(data, algo, fused, on_round=None, **kw):
    t = BlockwiseFederatedTrainer(
        TinyNet(), small_cfg(fused_rounds=fused, **kw), data, ALGOS[algo]())
    _, hist = t.run(log=lambda m: None, on_round=on_round)
    return t, hist


@pytest.fixture(scope="module")
def streams(data):
    """One recorded run per case, shared by the tests that only read."""
    cache = {}

    def get(algo, fused):
        if (algo, fused) not in cache:
            t, hist = run(data, algo, fused)
            cache[algo, fused] = (t.obs_recorder.memory, hist)
        return cache[algo, fused]

    return get


def spans_of(mem, name):
    return sorted((r for r in mem
                   if r["event"] == "span" and r["name"] == name),
                  key=lambda r: r["t_start"])


def rounds_of(mem):
    return [r for r in mem if r["event"] == "round"]


@pytest.mark.parametrize("algo,fused", CASES)
def test_block_switch_holds_its_parts_in_order(streams, algo, fused):
    mem, _ = streams(algo, fused)
    switches = spans_of(mem, "block_switch")
    assert len(switches) == BLOCKS               # one per block visit
    run_span = next(r for r in mem if r["event"] == "span"
                    and r["cat"] == "run")
    first_rounds = [r for r in rounds_of(mem) if r["nadmm"] == 0]
    for sw, rnd in zip(switches, first_rounds):
        assert sw["cat"] == "phase"
        assert sw["parent_span"] == run_span["span_id"]
        assert sw["round_index"] == rnd["round_index"]
        assert sw["t_end"] == rnd["t_start"]     # ends where the round opens
        parts = sorted((r for r in mem if r["event"] == "span"
                        and r.get("parent_span") == sw["span_id"]),
                       key=lambda r: r["t_start"])
        assert tuple(p["name"] for p in parts) == BLOCK_SWITCH_PARTS
        assert parts[0]["t_start"] == sw["t_start"]
        for a, b in zip(parts, parts[1:]):
            assert a["t_end"] == b["t_start"]    # stamped back to back
        assert parts[-1]["t_end"] <= sw["t_end"]


@pytest.mark.parametrize("algo,fused", CASES)
def test_every_round_has_one_tail_after_close(streams, algo, fused):
    mem, _ = streams(algo, fused)
    rounds, tails = rounds_of(mem), spans_of(mem, "round_tail")
    assert len(rounds) == BLOCKS * ROUNDS
    assert [t["round_index"] for t in tails] == \
        [r["round_index"] for r in rounds]
    for rnd, tail in zip(rounds, tails):
        assert tail["cat"] == "phase"
        # it starts at the stamp that ends round_seconds
        assert tail["t_start"] == pytest.approx(rnd["t_end"], abs=1e-9)
        assert tail["t_end"] >= tail["t_start"]


@pytest.mark.parametrize("algo,fused", CASES)
def test_rounds_switches_and_tails_tile_the_run(streams, algo, fused):
    mem, _ = streams(algo, fused)
    pieces = sorted(
        [(r["t_start"], r["t_end"]) for r in rounds_of(mem)]
        + [(s["t_start"], s["t_end"]) for n in ("block_switch", "round_tail")
           for s in spans_of(mem, n)])
    lo, hi = rounds_of(mem)[0]["t_start"], pieces[-1][1]
    assert hi == spans_of(mem, "round_tail")[-1]["t_end"]
    pieces = [p for p in pieces if p[0] >= lo]   # the first switch is ahead
    holes = 0.0
    for (_, a_end), (b_start, _) in zip(pieces, pieces[1:]):
        assert b_start >= a_end - 1e-9           # nothing overlaps
        holes += max(0.0, b_start - a_end)
    assert holes < 0.01 * (hi - lo)


@pytest.mark.parametrize("algo,fused", CASES)
def test_round_fields(streams, algo, fused):
    _, hist = streams(algo, fused)
    for i, rec in enumerate(hist):
        first_of_block = rec["nadmm"] == 0
        assert ("block_switch_seconds" in rec) == first_of_block
        assert ("block_switch_h2d_bytes" in rec) == first_of_block
        assert ("gap_seconds" in rec) == (i > 0)
        before = rec.get("gap_seconds", rec.get("block_switch_seconds"))
        if first_of_block:
            assert rec["block_switch_seconds"] > 0
            assert before >= rec["block_switch_seconds"]
            assert rec["block_switch_h2d_bytes"] == 0    # all on the device
        # the instrumented calls drained with a round ran in its window
        # or in the gap ahead of it (init_opt, at a block switch)
        assert 0 < rec["dispatch_seconds"] <= rec["round_seconds"] + before


@pytest.mark.parametrize("algo,fused", CASES)
def test_spans_carry_the_fields_seconds(streams, algo, fused):
    mem, hist = streams(algo, fused)
    switches, tails = spans_of(mem, "block_switch"), spans_of(mem,
                                                               "round_tail")
    firsts = [r for r in hist if "block_switch_seconds" in r]
    for sw, rec in zip(switches, firsts):
        assert sw["t_end"] - sw["t_start"] == pytest.approx(
            rec["block_switch_seconds"], abs=1e-9)
    # gap = the previous round's tail + what follows it up to t_round
    for prev_tail, rec in zip(tails, hist[1:]):
        tail_s = prev_tail["t_end"] - prev_tail["t_start"]
        rest = rec["gap_seconds"] - rec.get("block_switch_seconds", 0.0)
        assert rest >= tail_s - 1e-9
        assert rest - tail_s < 0.1               # loop overhead only


@pytest.mark.parametrize("algo,fused", CASES)
def test_obs_off_makes_no_span_and_keeps_the_fields(data, algo, fused,
                                                    monkeypatch):
    from federated_pytorch_test_tpu.obs import RunRecorder

    made = []
    orig = RunRecorder.span

    def counted(self, *a, **kw):
        made.append(a[0])
        return orig(self, *a, **kw)

    monkeypatch.setattr(RunRecorder, "span", counted)
    t, hist = run(data, algo, fused, obs_sinks="none")
    assert not t.obs_recorder.enabled
    assert made == []                            # span() never called
    assert t._outer_marks == [] and t._tail_open is None
    assert len(hist) == BLOCKS * ROUNDS
    for i, rec in enumerate(hist):
        assert ("block_switch_seconds" in rec) == (rec["nadmm"] == 0)
        assert ("block_switch_h2d_bytes" in rec) == (rec["nadmm"] == 0)
        assert ("gap_seconds" in rec) == (i > 0)
        assert rec["dispatch_seconds"] > 0


@pytest.mark.parametrize("algo,fused", CASES)
def test_cost_ledger_off_leaves_dispatch_seconds_out(data, algo, fused):
    t, hist = run(data, algo, fused, cost_ledger=False)
    assert all("dispatch_seconds" not in r for r in hist)
    assert all("gap_seconds" in r for r in hist[1:])
    assert len(spans_of(t.obs_recorder.memory, "round_tail")) == len(hist)


@pytest.mark.parametrize("algo,fused", CASES)
def test_stream_validates_and_exports(streams, algo, fused):
    mem, _ = streams(algo, fused)
    for rec in mem:
        assert rec["schema"] == SCHEMA_VERSION
        validate_record(rec)
    trace = obs_trace.to_chrome_trace(mem)
    obs_trace.validate_chrome_trace(trace)       # laminar, parents contain
    names = [e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"]
    for name in ("block_switch", "round_tail") + BLOCK_SWITCH_PARTS:
        assert name in names


@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_a_run_stopped_from_on_round_keeps_its_last_tail(data, algo):
    """The benchmark ends every run by raising from ``on_round``: the
    aborted stream still holds that round's tail, ending at the raise."""

    class Stop(Exception):
        pass

    seen = []

    def on_round(state, rec):
        seen.append(rec)
        if len(seen) == 3:
            raise Stop

    t = BlockwiseFederatedTrainer(TinyNet(), small_cfg(), data,
                                  ALGOS[algo]())
    with pytest.raises(Stop):
        t.run(log=lambda m: None, on_round=on_round)
    mem = t.obs_recorder.memory
    assert mem[-1]["event"] == "summary" and mem[-1]["status"] == "aborted"
    tails = spans_of(mem, "round_tail")
    assert [s["round_index"] for s in tails] == [0, 1, 2]
    run_span = next(r for r in mem if r["event"] == "span"
                    and r["cat"] == "run")
    assert run_span["t_end"] == tails[-1]["t_end"]
    assert t._outer_marks == [] and t._tail_open is None
    obs_trace.validate_chrome_trace(obs_trace.to_chrome_trace(mem))


def test_ckpt_span_is_a_child_of_its_rounds_tail(data, tmp_path):
    cfg = small_cfg(obs_dir=str(tmp_path / "obs"), obs_sinks="jsonl,memory")
    t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, FedAvg())
    t.run(log=lambda m: None, checkpoint_path=str(tmp_path / "ck"))
    records = read_records(t.obs_recorder.jsonl_path)
    tails = {s["round_index"]: s for s in spans_of(records, "round_tail")}
    ckpts = spans_of(records, "ckpt")
    assert len(ckpts) == BLOCKS * ROUNDS
    for ck in ckpts:
        tail = tails[ck["round_index"]]
        assert ck["parent_span"] == tail["span_id"]
        assert tail["t_start"] <= ck["t_start"] <= ck["t_end"] <= tail["t_end"]
    obs_trace.validate_chrome_trace(obs_trace.to_chrome_trace(records))


H2D_CASES = [
    # (id, algorithm, config) -> what the switch still stages from the host
    pytest.param("admm", {}, False, id="admm"),
    pytest.param("fedavg", {}, False, id="fedavg"),
    pytest.param("admm", {"bb_update": True}, False, id="admm-bb"),
    pytest.param("admm", {"compress": "topk"}, False, id="topk-plain"),
    pytest.param("fedavg", {"compress": "topk", "error_feedback": True},
                 True, id="topk-error-feedback"),
    pytest.param("admm", {"compress": "q8"}, True, id="q8"),
]


@pytest.mark.parametrize("algo,kw,stateful", H2D_CASES)
def test_h2d_bytes_counts_what_the_switch_stages(data, algo, kw, stateful):
    """Under 64 bytes wherever the block's state is zeros and rho0 (made
    on the device); a compressor with state of its own still stages its
    fresh rows from the host, and the field counts exactly those."""
    t, hist = run(data, algo, False, obs_sinks="none", **kw)
    firsts = [r for r in hist if "block_switch_seconds" in r]
    assert len(firsts) == BLOCKS
    assert all(("block_switch_h2d_bytes" in r)
               == ("block_switch_seconds" in r) for r in hist)
    for ci, rec in enumerate(firsts):
        comp = t._init_comp_state(ci)
        rows = sum(x.nbytes for x in jax.tree.leaves(comp))
        assert rec["block_switch_h2d_bytes"] == rows
        assert type(rec["block_switch_h2d_bytes"]) is int
        if stateful:
            assert rows > 0 and comp is not None
        else:
            assert rows < 64 and comp is None
    if kw.get("compress") == "q8":      # one PRNG key (2 x uint32) a client
        assert {r["block_switch_h2d_bytes"] for r in firsts} == {8 * K}
    if kw.get("error_feedback"):        # the residual is [K, N] float32
        assert [r["block_switch_h2d_bytes"] >= 4 * K * r["N"]
                for r in firsts] == [True] * BLOCKS


@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_resume_inside_a_block_stamps_a_switch_too(data, tmp_path, algo):
    """A resumed segment's first round is a block visit's first round: it
    carries the switch (the restore branch is its ``block_vars``), which
    staged nothing itself, and goes on from the restored z / y / rho."""
    ck = str(tmp_path / "ck")
    seen = []

    class Stop(Exception):
        pass

    def on_round(state, rec):
        seen.append(rec)
        if len(seen) == 1:
            raise Stop

    t = BlockwiseFederatedTrainer(TinyNet(), small_cfg(), data,
                                  ALGOS[algo]())
    with pytest.raises(Stop):
        t.run(log=lambda m: None, on_round=on_round, checkpoint_path=ck)
    t2 = BlockwiseFederatedTrainer(TinyNet(), small_cfg(), data,
                                   ALGOS[algo]())
    _, hist = t2.run(log=lambda m: None, checkpoint_path=ck, resume=True)
    resumed = hist[1]
    assert resumed["nadmm"] == 1 and "block_switch_seconds" in resumed
    assert resumed["block_switch_h2d_bytes"] == 0
    assert "gap_seconds" not in resumed          # the segment's first round
    mem = t2.obs_recorder.memory
    sw = spans_of(mem, "block_switch")[0]
    parts = [r["name"] for r in sorted(
        (r for r in mem if r["event"] == "span"
         and r.get("parent_span") == sw["span_id"]),
        key=lambda r: r["t_start"])]
    assert tuple(parts) == BLOCK_SWITCH_PARTS
    # r_blockvars came back: the trajectory is the uninterrupted run's
    _, whole = run(data, algo, False)
    for key in ("loss", "rho", "dual_residual", "primal_residual"):
        assert [r.get(key) for r in hist] == [r.get(key) for r in whole]
    # only the fresh switches made block state: block 0's came from disk
    made = [k for k in t2._fn_cache if k[0] == "fresh"]
    assert len(made) == BLOCKS - 1
