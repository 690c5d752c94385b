"""Observability subsystem tests (obs/): schema round-trip, sinks,
recorder invariants, report CLI, and the engine/driver emission paths.

The engine smokes run the REAL trainers on the virtual CPU client mesh
and assert the emitted telemetry — one schema-validated record per comm
round, JSONL parseable by obs.report — for every algorithm family the
repo ships (FedAvg / FedProx / ADMM / VAE / CPC).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import flax.linen as nn

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import (
    BlockModule,
    elu,
    flatten,
    max_pool_2x2,
    pairs,
)
from federated_pytorch_test_tpu.obs import (
    RunRecorder,
    SCHEMA_VERSION,
    SchemaError,
    json_safe,
    make_recorder,
    make_sinks,
    validate_record,
)
from federated_pytorch_test_tpu.obs.report import (
    read_records,
    record_ips,
    summarize,
)
from federated_pytorch_test_tpu.obs.schema import (
    ADVISORY_FIELDS,
    ENVELOPE_FIELDS,
    FIELDS,
)
from federated_pytorch_test_tpu.obs.sinks import (
    SINK_CHOICES,
    JsonlSink,
    MemorySink,
)
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FedAvg,
    FederatedConfig,
    FedProx,
)

K = 4


class TinyNet(BlockModule):
    """2-block toy CNN (same shape as test_engine's): small compiles,
    full blockwise machinery."""

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
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=16,
                check_results=False, admm_rho0=0.1, obs_sinks="memory")
    base.update(kw)
    return FederatedConfig(**base)


def round_record(i=0, **kw):
    rec = {"event": "round", "schema": SCHEMA_VERSION, "run_id": "t" * 8,
           "engine": "classifier", "round_index": i, "round_seconds": 0.5,
           "loss": 1.0 - 0.1 * i}
    rec.update(kw)
    return rec


# ----------------------------------------------------------------------
# schema


class TestSchema:
    def test_valid_round_passes(self):
        validate_record(round_record(bytes_on_wire=1024, nloop=0,
                                     guard_trips=0))

    def test_unknown_event_rejected(self):
        with pytest.raises(SchemaError, match="event"):
            validate_record(round_record() | {"event": "telemetry"})

    def test_missing_required_rejected(self):
        rec = round_record()
        del rec["round_index"]
        with pytest.raises(SchemaError, match="round_index"):
            validate_record(rec)

    def test_newer_schema_rejected(self):
        with pytest.raises(SchemaError, match="schema"):
            validate_record(round_record(schema=SCHEMA_VERSION + 1))

    def test_bool_is_not_an_int_field(self):
        with pytest.raises(SchemaError):
            validate_record(round_record(bytes_on_wire=True))

    def test_wrong_type_rejected(self):
        with pytest.raises(SchemaError):
            validate_record(round_record(loss="diverged"))

    def test_unknown_fields_are_forward_compatible(self):
        validate_record(round_record(some_future_field={"x": 1}))

    def test_field_on_wrong_event_rejected(self):
        rec = {"event": "summary", "schema": SCHEMA_VERSION,
               "run_id": "t" * 8, "status": "completed", "rounds": 1,
               "round_index": 0}      # round-only field
        with pytest.raises(SchemaError, match="round_index"):
            validate_record(rec)

    def test_json_safe_handles_numpy(self):
        out = json_safe({"a": np.float32(1.5), "b": np.arange(3),
                         "c": (1, 2)})
        assert json.loads(json.dumps(out)) == {"a": 1.5, "b": [0, 1, 2],
                                               "c": [1, 2]}

    def test_nan_loss_allowed(self):
        # fault injection legitimately produces NaN losses
        validate_record(round_record(loss=float("nan")))


#: the fields PRs 24-38 added: (kinds, a valid value, an ill-typed one,
#: advisory or core).  A new field is one more line here (and one in
#: obs/schema.py: README "Observability", "how to add a field").
DECLARED = {
    "block_switch_seconds": (("round",), 0.03, "soon", True),
    "gap_seconds": (("round",), 0.04, "soon", True),
    "dispatch_seconds": (("round",), 0.002, "soon", True),
    "dispatch_max_seconds": (("round",), 0.0015, "soon", True),
    "dispatch_max_site": (("round",), "train_epoch[blk=1]", 1, True),
    "dispatch_new_signatures": (("round",), 1, 0.5, True),
    "block_switch_h2d_bytes": (("round",), 0, "none", True),
    "tokens": (("round",), 65536, "many", False),
    "block_kind": (("round",), "gdn", 1, False),
    "moe_pairs_local": (("round",), 1024, 0.5, False),
    "moe_load_max_over_mean": (("round",), 1.6, "high", False),
    "moe_dropped": (("round",), 0, "none", False),
    "moe_fill_share": (("round",), 0.125, "an eighth", False),
    "mtp_loss": (("round",), 9.87, "high", False),
    "mhc_marginal_err": (("round",), 3e-6, "small", False),
    "moe_top1_weight_mean": (("round",), 0.11, "a ninth", False),
    "router_state_rms": (("round",), 1.4, "grown", False),
    "gdn_neg_beta_share": (("round",), 0.5, "half", False),
    "gdn_scan_impl": (("round",), "pallas", 1, True),
    "attn_impl": (("round",), "xla", 0, True),
    "mhc_impl": (("round",), "pallas_interpret", 2, True),
    "head_impl": (("round",), "fused", 3, True),
}


@pytest.mark.parametrize("name", sorted(DECLARED))
def test_declared_field(name):
    kinds, good, bad, advisory = DECLARED[name]
    assert FIELDS[name][0] == kinds
    assert (name in ADVISORY_FIELDS) == advisory
    assert name not in ENVELOPE_FIELDS
    validate_record(round_record(**{name: good}))
    with pytest.raises(SchemaError, match=name):
        validate_record(round_record(**{name: bad}))
    with pytest.raises(SchemaError, match="not valid"):
        validate_record({"event": "summary", "schema": SCHEMA_VERSION,
                         "run_id": "t" * 8, "status": "completed",
                         "rounds": 1, name: good})


# ----------------------------------------------------------------------
# sinks


class TestSinks:
    def test_auto_without_dir_is_fileless(self):
        sinks, path = make_sinks("auto", None)
        assert sinks == [] and path is None

    def test_auto_with_dir_resolves_to_jsonl(self, tmp_path):
        sinks, path = make_sinks("auto", str(tmp_path), "myrun")
        assert len(sinks) == 1 and isinstance(sinks[0], JsonlSink)
        assert path == str(tmp_path / "myrun.jsonl")

    def test_unknown_sink_rejected(self):
        with pytest.raises(ValueError, match="unknown obs sink"):
            make_sinks("jsonl,grafana")

    def test_jsonl_appends_and_flushes_per_record(self, tmp_path):
        sinks, path = make_sinks("jsonl", str(tmp_path))
        sinks[0].emit({"event": "round", "round_index": 0})
        # flushed BEFORE close: a killed run keeps completed rounds
        with open(path) as f:
            assert len(f.readlines()) == 1
        sinks[0].close()
        sinks2, _ = make_sinks("jsonl", str(tmp_path))
        sinks2[0].emit({"event": "round", "round_index": 1})
        sinks2[0].close()
        with open(path) as f:
            assert [json.loads(ln)["round_index"] for ln in f] == [0, 1]

    @pytest.mark.parametrize("gone", ["csv", "stdout"])
    def test_removed_sinks_are_rejected(self, gone, tmp_path):
        assert SINK_CHOICES == ("auto", "none", "jsonl", "memory")
        with pytest.raises(ValueError, match="unknown obs sink") as e:
            make_sinks("jsonl," + gone, str(tmp_path))
        for choice in SINK_CHOICES:
            assert choice in str(e.value)
        assert not list(tmp_path.iterdir())


# ----------------------------------------------------------------------
# recorder


class TestRecorder:
    def test_disabled_recorder_is_noop(self):
        rec = make_recorder("none", None, run_name="x", engine="classifier")
        assert not rec.enabled
        assert rec.open(config={}) is None
        assert rec.round({"round_index": 0}) is None
        assert rec.close() is None

    def test_memory_lifecycle_and_summary_totals(self):
        rec = make_recorder("memory", None, run_name="x",
                            engine="classifier", algorithm="fedavg")
        rec.open(config={"K": 4}, mesh_shape={"clients": 4})
        for i in range(3):
            rec.round({"round_index": i, "round_seconds": 0.5,
                       "comm_seconds": 0.1, "loss": 2.0 - i,
                       "bytes_on_wire": 100, "bytes_dense": 400,
                       "images": 64})
        rec.close()
        events = [r["event"] for r in rec.memory]
        assert events == ["run_header", "round", "round", "round",
                          "summary"]
        for r in rec.memory:
            validate_record(r)
        hdr, s = rec.memory[0], rec.memory[-1]
        assert hdr["config"] == {"K": 4} and hdr["platform"] == "cpu"
        assert s["rounds"] == 3
        assert s["bytes_on_wire_total"] == 300
        assert s["bytes_dense_total"] == 1200
        assert s["compression_savings_frac"] == 0.75
        assert s["loss_first"] == 2.0 and s["loss_final"] == 0.0
        assert s["comm_overhead_frac"] == pytest.approx(0.2)
        assert s["images_per_sec"] == pytest.approx(192 / 1.5)

    def test_summary_totals_without_metrics(self):
        """The summary's totals, value for value what the recorder wrote
        when obs/metrics.py kept them (numbers from a run of that tree)."""
        rec = make_recorder("memory", None, run_name="x",
                            engine="classifier", algorithm="fedavg")
        rec.open(config={"K": 4})
        rec.round(dict(round_index=0, round_seconds=0.5,
                       stage_seconds=0.125, comm_seconds=0.0625, loss=2.0,
                       bytes_on_wire=100, bytes_dense=400, images=64,
                       guard_trips=1.0, fault_dropped=1, quarantined=2))
        rec.round(dict(round_index=1, round_seconds=0.25,
                       comm_seconds=0.0625, loss=1.5, bytes_on_wire=100,
                       bytes_dense=400, images=64, guard_trips=0.5,
                       fault_straggled=2, quarantined=1))
        rec.round(dict(round_index=2, round_seconds=1, stage_seconds=0.25,
                       loss=1.0, bytes_on_wire=50, bytes_dense=400,
                       images=64, fault_corrupted=3))
        rec.compile_event({"site": "s", "compile_seconds": 0.25})
        s = rec.close()
        for k in ("run_id", "time_unix", "total_seconds"):
            del s[k]
        want = {
            "event": "summary", "schema": SCHEMA_VERSION,
            "status": "completed", "rounds": 3,
            "bytes_on_wire_total": 250, "bytes_dense_total": 1200,
            "images_total": 192, "guard_trips_total": 1.5,
            "fault_dropped_total": 1, "fault_straggled_total": 2,
            "fault_corrupted_total": 3, "round_seconds_total": 1.75,
            "stage_seconds_total": 0.375, "comm_seconds_total": 0.125,
            "quarantined_last": 1, "loss_first": 2.0, "loss_final": 1.0,
            "compile_events_total": 1, "compile_seconds_total": 0.25,
            "rounds_per_sec": 1.7142857142857142,
            "images_per_sec": 109.71428571428571,
            "comm_overhead_frac": 0.07142857142857142,
            "compression_savings_frac": 0.7916666666666666}
        assert s == want and list(s) == list(want)
        assert type(s["round_seconds_total"]) is float
        assert type(s["fault_dropped_total"]) is int

    def test_round_index_must_increase(self):
        rec = make_recorder("memory", None, run_name="x", engine="e")
        rec.open()
        rec.round({"round_index": 0, "round_seconds": 0.1})
        with pytest.raises(SchemaError, match="backwards"):
            rec.round({"round_index": 0, "round_seconds": 0.1})

    def test_resume_rounds_prior_blocks_stale_indices(self):
        rec = make_recorder("memory", None, run_name="x", engine="e")
        rec.open(resumed=True, rounds_prior=5)
        with pytest.raises(SchemaError, match="backwards"):
            rec.round({"round_index": 4, "round_seconds": 0.1})
        rec.round({"round_index": 5, "round_seconds": 0.1})

    def test_close_is_idempotent(self):
        rec = make_recorder("memory", None, run_name="x", engine="e")
        rec.open()
        rec.close(status="aborted")
        assert rec.close() is None
        assert [r["event"] for r in rec.memory].count("summary") == 1


# ----------------------------------------------------------------------
# report CLI


class TestReport:
    def _recorded_file(self, tmp_path):
        rec = make_recorder("jsonl", str(tmp_path), run_name="r",
                            engine="classifier", algorithm="admm")
        rec.open(config={"K": 2})
        for i in range(4):
            rec.round({"round_index": i, "round_seconds": 0.25,
                       "loss": 4.0 - i, "bytes_on_wire": 50,
                       "bytes_dense": 200, "images": 32})
        rec.close()
        return rec.jsonl_path

    def test_emit_jsonl_parse_validate_roundtrip(self, tmp_path):
        path = self._recorded_file(tmp_path)
        records = read_records(path)           # validates by default
        s = summarize(records)
        assert s["rounds"] == 4 and s["monotonic"]
        assert s["engine"] == "classifier" and s["algorithm"] == "admm"
        assert s["bytes_on_wire_total"] == 200
        assert s["compression_savings_frac"] == 0.75
        assert s["loss_first"] == 4.0 and s["loss_final"] == 1.0

    def test_truncated_file_still_summarizes(self, tmp_path):
        # kill-safety: drop the summary line (and one round), summarize
        # must recompute totals from the surviving rounds
        path = self._recorded_file(tmp_path)
        lines = open(path).readlines()
        open(path, "w").writelines(lines[:-2])
        s = summarize(read_records(path))
        assert s["rounds"] == 3 and s["summaries"] == 0
        assert s["bytes_on_wire_total"] == 150

    def test_malformed_line_reports_line_number(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        path.write_text(json.dumps(
            {"event": "run_header", "schema": 1, "run_id": "x" * 8,
             "engine": "e", "time_unix": 0.0}) + "\nnot json\n")
        with pytest.raises(ValueError, match=r"bad\.jsonl:2"):
            read_records(str(path))

    def test_record_ips(self):
        assert record_ips({"images": 100, "round_seconds": 2.0},
                          n_chips=2) == 25.0

    def test_cli_json_output(self, tmp_path, capsys):
        from federated_pytorch_test_tpu.obs import report

        path = self._recorded_file(tmp_path)
        assert report.main([path, "--json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert s["rounds"] == 4

    def test_cli_selftest_subprocess(self):
        # the tier-1 flow invokes exactly this command (ROADMAP.md)
        r = subprocess.run(
            [sys.executable, "-m", "federated_pytorch_test_tpu.obs.report",
             "--selftest"],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stderr
        assert "obs report selftest: OK" in r.stdout


# ----------------------------------------------------------------------
# every field an engine emits is declared


def _classifier_stream(data):
    t, _, _ = run_with_obs(data, AdmmConsensus())
    return t.obs_recorder.memory


def _vae_stream(data):
    from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN
    from federated_pytorch_test_tpu.train.vae_engine import VAETrainer

    t = VAETrainer(AutoEncoderCNN(), small_cfg(update_guard=True), data,
                   FedAvg())
    t.L = 1
    t.run(log=lambda m: None)
    return t.obs_recorder.memory


def _cpc_stream(data):
    from federated_pytorch_test_tpu.data.lofar import CPCDataSource
    from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

    src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2, seed=7)
    t = CPCTrainer(src, latent_dim=8, reduced_dim=4, lbfgs_history=3,
                   lbfgs_max_iter=1, Niter=1)
    t.run(Nloop=1, Nadmm=1, log=lambda m: None, obs_sinks="memory")
    return t.obs_recorder.memory


def _lm_stream(data):
    from test_lm_engine import MOE, lm_trainer

    t = lm_trainer([MOE], obs_sinks="memory")
    t.run(log=lambda m: None)
    return t.obs_recorder.memory


STREAMS = {"classifier": _classifier_stream, "vae": _vae_stream,
           "cpc": _cpc_stream, "lm": _lm_stream}
#: the kinds every engine writes with its defaults and a memory sink
EMITTED = ("run_header", "round", "client", "span", "compile", "summary")


@pytest.fixture(scope="module")
def stream_of(data):
    made = {}

    def get(engine):
        if engine not in made:
            made[engine] = STREAMS[engine](data)
        return made[engine]

    return get


@pytest.mark.parametrize("kind", EMITTED)
@pytest.mark.parametrize("engine", sorted(STREAMS))
def test_every_emitted_field_is_declared(stream_of, engine, kind):
    """``validate_record`` passes a field it does not know, so a field an
    engine writes without a ``FIELDS`` line would go unnoticed: every key
    of every record is declared for that record's kind."""
    records = [r for r in stream_of(engine) if r["event"] == kind]
    assert records, f"{engine} wrote no {kind!r} record"
    for rec in records:
        for name in rec:
            assert name in FIELDS, f"{engine} {kind}: undeclared {name!r}"
            assert kind in FIELDS[name][0], (engine, kind, name)


# ----------------------------------------------------------------------
# engine emission: one validated record per comm round, every algorithm


def run_with_obs(data, algo, tmp_path=None, model=None, trainer_cls=None,
                 **cfg_kw):
    if tmp_path is not None:
        cfg_kw.setdefault("obs_dir", str(tmp_path))
        cfg_kw.setdefault("obs_sinks", "jsonl,memory")
    cfg = small_cfg(**cfg_kw)
    cls = trainer_cls or BlockwiseFederatedTrainer
    t = cls(model or TinyNet(), cfg, data, algo)
    state, hist = t.run(log=lambda m: None)
    return t, state, hist


def check_emission(t, hist, *, engine="classifier", communicates=True):
    mem = t.obs_recorder.memory
    events = [r["event"] for r in mem]
    assert events[0] == "run_header" and events[-1] == "summary"
    rounds = [r for r in mem if r["event"] == "round"]
    assert len(rounds) == len(hist)
    for r in mem:
        validate_record(r)
    assert [r["round_index"] for r in rounds] == list(range(len(hist)))
    hdr = mem[0]
    assert hdr["engine"] == engine
    assert hdr["config"]["K"] == K            # config snapshot
    assert "mesh_shape" in hdr
    for r in rounds:
        assert r["round_seconds"] > 0
        assert "train_seconds" in r and "comm_seconds" in r
        assert ("bytes_on_wire" in r) == communicates
        if communicates:
            assert r["bytes_dense"] >= r["bytes_on_wire"] > 0
    # per-round images: Nepoch * K * steps * batch
    data_images = K * t.data.steps * t.data.batch
    assert all(r["images"] == t.cfg.Nepoch * data_images for r in rounds)
    return rounds, mem[-1]


class TestEngineEmission:
    @pytest.mark.parametrize("algo", [FedAvg(), FedProx(), AdmmConsensus()],
                             ids=["fedavg", "fedprox", "admm"])
    def test_round_records_per_algorithm(self, data, tmp_path, algo):
        t, state, hist = run_with_obs(data, algo, tmp_path)
        rounds, summary = check_emission(t, hist)
        assert summary["status"] == "completed"
        assert summary["rounds"] == len(hist)
        # the JSONL artifact parses to the same stream
        records = read_records(t.obs_recorder.jsonl_path)
        assert len(records) == len(t.obs_recorder.memory)
        s = summarize(records)
        assert s["monotonic"] and s["rounds"] == len(hist)
        assert s["algorithm"] == algo.name

    def test_vae_records_unify_bytes_and_guard_counters(self, data,
                                                        tmp_path):
        from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN
        from federated_pytorch_test_tpu.train.vae_engine import VAETrainer

        cfg = small_cfg(obs_dir=str(tmp_path), obs_sinks="jsonl,memory",
                        update_guard=True, Nadmm=2)
        t = VAETrainer(AutoEncoderCNN(), cfg, data, FedAvg())
        t.L = 1          # first layer only: keeps the sweep to 2 rounds
        state, hist = t.run(log=lambda m: None)
        rounds, summary = check_emission(t, hist, engine="vae")
        # the guard counters ride the SAME schema fields as the
        # classifier engine (history parity, ISSUE satellite 1)
        for r in rounds:
            assert r["guard_trips"] >= 0
            assert r["quarantined"] >= 0
        assert summary["guard_trips_total"] >= 0

    def test_cpc_records(self, tmp_path):
        from federated_pytorch_test_tpu.data.lofar import CPCDataSource
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                            seed=7)
        t = CPCTrainer(src, latent_dim=8, reduced_dim=4, lbfgs_history=3,
                       lbfgs_max_iter=1, Niter=1)
        state, hist = t.run(Nloop=1, Nadmm=1, log=lambda m: None,
                            obs_dir=str(tmp_path), obs_sinks="jsonl,memory")
        mem = t.obs_recorder.memory
        for r in mem:
            validate_record(r)
        rounds = [r for r in mem if r["event"] == "round"]
        assert len(rounds) == len(hist) > 0
        assert [r["round_index"] for r in rounds] == list(range(len(hist)))
        assert all(r["engine"] == "cpc" for r in rounds)
        assert all(r["bytes_on_wire"] == 4 * r["N"] * t.K for r in rounds)
        s = summarize(read_records(t.obs_recorder.jsonl_path))
        assert s["monotonic"] and s["rounds"] == len(hist)
        assert s["status"] == "completed"


class TestResumeAppends:
    def test_killed_run_resumes_appending_monotonically(self, data,
                                                        tmp_path):
        """Kill after round 0, resume: the SAME JSONL gains a second
        (resumed) header and strictly increasing round indices — no
        duplicates, no rewind."""

        class Killed(Exception):
            pass

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        ck = str(tmp_path / "ck")
        obs_kw = dict(obs_dir=str(tmp_path / "obs"), obs_sinks="jsonl")

        def make():
            t = BlockwiseFederatedTrainer(TinyNet(), small_cfg(**obs_kw),
                                          data, AdmmConsensus())
            return t

        with pytest.raises(Killed):
            make().run(log=lambda m: None, checkpoint_path=ck,
                       on_round=bomb)
        t = make()
        _, hist = t.run(log=lambda m: None, checkpoint_path=ck,
                        resume=True)

        records = read_records(t.obs_recorder.jsonl_path)
        headers = [r for r in records if r["event"] == "run_header"]
        summaries = [r for r in records if r["event"] == "summary"]
        rounds = [r for r in records if r["event"] == "round"]
        assert len(headers) == 2
        assert headers[0]["resumed"] is False
        assert headers[1]["resumed"] is True
        assert headers[1]["rounds_prior"] == 1
        assert [s["status"] for s in summaries] == ["aborted", "completed"]
        idx = [r["round_index"] for r in rounds]
        # appended, strictly increasing, no duplicates across the kill
        assert idx == sorted(set(idx)) == list(range(len(hist)))
        assert summarize(records)["monotonic"]


class TestBitIdentity:
    def test_obs_sinks_none_is_bit_identical(self, data):
        """--obs-sinks none must not perturb the math: final params
        bitwise equal to a memory-sink run (emission is host-side at
        round boundaries either way)."""

        def run(sinks):
            t = BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(obs_sinks=sinks), data,
                AdmmConsensus())
            state, _ = t.run(log=lambda m: None)
            return jax.device_get(state.params)

        a, b = run("none"), run("memory")
        ja = jax.tree.leaves(a)
        jb = jax.tree.leaves(b)
        assert len(ja) == len(jb)
        for x, y in zip(ja, jb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestDriverPlumbing:
    def test_parser_exposes_obs_flags(self):
        from federated_pytorch_test_tpu.drivers.common import build_parser

        p = build_parser(FederatedConfig(), "prog")
        args = p.parse_args(["--obs-sinks", "none",
                             "--obs-dir", "/tmp/somewhere"])
        assert args.obs_sinks == "none"
        assert args.obs_dir == "/tmp/somewhere"

    def test_default_obs_dir_under_checkpoint_dir(self):
        from federated_pytorch_test_tpu.drivers.common import default_obs_dir

        cfg = default_obs_dir(FederatedConfig(checkpoint_dir="/ck"))
        assert cfg.obs_dir == os.path.join("/ck", "obs")
        # explicit opt-out and explicit dir are both left alone
        assert default_obs_dir(
            FederatedConfig(obs_sinks="none")).obs_dir is None
        assert default_obs_dir(
            FederatedConfig(obs_dir="/x")).obs_dir == "/x"
