"""Live run-health layer tests (obs/trace.py, obs/health.py,
obs/compare.py + the engine/driver wiring).

Covers the schema v1→v5 ladder, the span hierarchy and its Chrome
trace export (including a resumed multi-segment file), the streaming
watchdog rules and the ``--health-action`` contract — a seeded
``corrupt=…,mode=nan`` run under ``checkpoint-abort`` must die inside
the streak window with a verified checkpoint and the triggering alert
on disk — plus the compare CLI's CI exit codes.
"""

import json
import math
import os

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
    SCHEMA_VERSION,
    RunRecorder,
    SchemaError,
    make_recorder,
    validate_record,
)
from federated_pytorch_test_tpu.obs import compare as obs_compare
from federated_pytorch_test_tpu.obs import trace as obs_trace
from federated_pytorch_test_tpu.obs.health import (
    HEALTH_ACTIONS,
    HealthMonitor,
    RunHealthAbort,
    monitor_from_config,
)
from federated_pytorch_test_tpu.obs.report import (
    read_records,
    record_ips,
    summarize,
)
from federated_pytorch_test_tpu.obs.sinks import MemorySink
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FederatedConfig,
)

pytestmark = pytest.mark.obshealth

K = 4


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
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=16,
                check_results=False, admm_rho0=0.1, obs_sinks="memory")
    base.update(kw)
    return FederatedConfig(**base)


def round_record(i=0, ver=SCHEMA_VERSION, **kw):
    rec = {"event": "round", "schema": ver, "run_id": "t" * 8,
           "engine": "classifier", "round_index": i, "round_seconds": 0.5,
           "loss": 1.0 - 0.1 * i}
    rec.update(kw)
    return rec


# ----------------------------------------------------------------------
# schema ladder v1 -> v5


class TestSchemaLadder:
    def test_v5_reader_accepts_every_prior_version(self):
        # the additive contract: a v5 reader must take v1..v4 files
        for ver in range(1, SCHEMA_VERSION + 1):
            validate_record(round_record(ver=ver))
            validate_record({"event": "run_header", "schema": ver,
                             "run_id": "r" * 8, "engine": "classifier",
                             "time_unix": 1.0})

    def test_newer_schema_rejected(self):
        with pytest.raises(SchemaError, match="newer"):
            validate_record(round_record(ver=SCHEMA_VERSION + 1))

    def test_unknown_fields_pass_known_fields_typed(self):
        validate_record(round_record(totally_new_field_v9="future"))
        with pytest.raises(SchemaError, match="t_start"):
            validate_record(round_record(t_start="not-a-number"))

    def test_span_fields_are_additive_on_round(self):
        validate_record(round_record(span_id="ab12", parent_span="cd34",
                                     t_start=1.0, t_end=1.5))

    def test_span_record_kind(self):
        validate_record({"event": "span", "schema": SCHEMA_VERSION,
                         "run_id": "r" * 8, "span_id": "ab12",
                         "name": "train", "cat": "phase",
                         "t_start": 0.0, "t_end": 1.0,
                         "parent_span": "cd34", "round_index": 3})
        with pytest.raises(SchemaError, match="t_end"):
            validate_record({"event": "span", "schema": SCHEMA_VERSION,
                             "run_id": "r" * 8, "span_id": "ab12",
                             "name": "train", "t_start": 0.0})

    def test_alert_record_kind(self):
        validate_record({"event": "alert", "schema": SCHEMA_VERSION,
                         "run_id": "r" * 8, "rule": "nonfinite_loss",
                         "round_index": 7, "severity": "fatal",
                         "observed": -1.0, "threshold": 3.0, "streak": 3,
                         "action": "checkpoint-abort", "message": "x",
                         "time_unix": 1.0})
        with pytest.raises(SchemaError, match="rule"):
            validate_record({"event": "alert", "schema": SCHEMA_VERSION,
                             "run_id": "r" * 8, "round_index": 7})

    def test_span_fields_rejected_on_summary(self):
        # event-gating still applies to the new fields
        with pytest.raises(SchemaError, match="not valid"):
            validate_record({"event": "summary", "schema": SCHEMA_VERSION,
                             "run_id": "r" * 8, "status": "completed",
                             "rounds": 1, "t_start": 0.0})


# ----------------------------------------------------------------------
# recorder span plumbing


class TestRecorderSpans:
    def test_round_with_t_start_becomes_a_span(self):
        rec = RunRecorder([MemorySink()], engine="t")
        rec.open()
        out = rec.round({"round_index": 0, "round_seconds": 0.5,
                         "t_start": 10.0})
        assert out["span_id"] and out["parent_span"] == rec.run_span_id
        assert out["t_end"] == pytest.approx(10.5)
        rec.close()
        spans = [r for r in rec.memory if r["event"] == "span"]
        assert [s["name"] for s in spans] == ["run"]
        assert spans[0]["span_id"] == rec.run_span_id
        assert rec.memory[0]["span_id"] == rec.run_span_id   # header carries it

    def test_stream_without_t_start_is_v4_shaped(self):
        # no t_start anywhere -> no span records, byte-compatible stream
        rec = RunRecorder([MemorySink()], engine="t")
        rec.open()
        rec.round({"round_index": 0, "round_seconds": 0.5})
        rec.close()
        events = [r["event"] for r in rec.memory]
        assert events == ["run_header", "round", "summary"]
        assert "span_id" not in rec.memory[1]

    def test_explicit_span_parents_to_run_by_default(self):
        rec = RunRecorder([MemorySink()], engine="t")
        rec.open()
        s = rec.span("ckpt", 1.0, 2.0, cat="ckpt", round_index=4)
        assert s["parent_span"] == rec.run_span_id
        assert s["round_index"] == 4
        validate_record(s)

    def test_disabled_recorder_spans_are_noop(self):
        rec = make_recorder("none")
        rec.open()
        assert rec.round({"round_index": 0, "round_seconds": 0.1,
                          "t_start": 1.0}) is None
        assert rec.span("x", 0.0, 1.0) is None
        assert rec.alert({"rule": "r", "round_index": 0}) is None


# ----------------------------------------------------------------------
# trace exporter


def _write_two_segment_run(d):
    """Recorder -> JSONL round-trip on a resumed (two-segment) file."""
    for seg in range(2):
        rec = make_recorder("jsonl", str(d), run_name="tr", engine="t")
        rec.open(resumed=seg > 0, rounds_prior=2 * seg)
        for i in range(2 * seg, 2 * seg + 2):
            t0 = 100.0 * seg + float(i)
            rid = f"round{i:04d}xx"
            rec.round({"round_index": i, "round_seconds": 0.9,
                       "loss": 1.0, "t_start": t0, "span_id": rid})
            rec.span("train", t0 + 0.05, t0 + 0.7, cat="phase",
                     round_index=i, parent_span=rid)
        rec.close()
    return os.path.join(str(d), "tr.jsonl")


class TestTraceExporter:
    def test_resumed_roundtrip_validates_and_keys_round_index(self,
                                                              tmp_path):
        src = _write_two_segment_run(tmp_path)
        out = os.path.join(str(tmp_path), "trace.json")
        assert obs_trace.main([src, "-o", out]) == 0
        with open(out) as f:
            trace = json.load(f)
        obs_trace.validate_chrome_trace(trace)
        xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        rounds = [e for e in xs if e["cat"] == "round"]
        # round spans keyed to the SAME round_index XProf annotates
        assert sorted(e["args"]["round_index"] for e in rounds) == [0, 1,
                                                                    2, 3]
        # a resumed file splits into one trace process per segment
        assert len({e["pid"] for e in xs}) == 2
        # phase spans are parent-linked and contained
        trains = [e for e in xs if e["name"] == "train"]
        assert all(e["args"]["parent_span"].startswith("round")
                   for e in trains)

    def test_validator_rejects_straddling_spans(self):
        bad = {"traceEvents": [
            {"ph": "X", "name": "a", "cat": "x", "pid": 1, "tid": 1,
             "ts": 0.0, "dur": 10.0, "args": {}},
            {"ph": "X", "name": "b", "cat": "x", "pid": 1, "tid": 1,
             "ts": 5.0, "dur": 10.0, "args": {}},
        ]}
        with pytest.raises(SchemaError, match="laminar"):
            obs_trace.validate_chrome_trace(bad)

    def test_validator_rejects_escaping_child(self):
        bad = {"traceEvents": [
            {"ph": "X", "name": "parent", "cat": "x", "pid": 1, "tid": 1,
             "ts": 0.0, "dur": 5.0, "args": {"span_id": "p"}},
            {"ph": "X", "name": "child", "cat": "x", "pid": 2, "tid": 1,
             "ts": 0.0, "dur": 9.0, "args": {"parent_span": "p"}},
        ]}
        with pytest.raises(SchemaError, match="escapes"):
            obs_trace.validate_chrome_trace(bad)

    def test_pre_v5_file_exports_empty_but_cleanly(self, tmp_path):
        rec = make_recorder("jsonl", str(tmp_path), run_name="old",
                            engine="t")
        rec.open()
        rec.round({"round_index": 0, "round_seconds": 0.5})
        rec.close()
        out = os.path.join(str(tmp_path), "old.trace.json")
        n = obs_trace.export(os.path.join(str(tmp_path), "old.jsonl"), out)
        assert n == 0 and os.path.exists(out)


# ----------------------------------------------------------------------
# watchdog rules (unit)


def _mon(**kw):
    kw.setdefault("action", "warn")
    m = HealthMonitor(**kw)
    rec = RunRecorder([MemorySink()], engine="t")
    rec.open()
    rec.attach_health(m)
    return m


class TestWatchdogRules:
    def test_nonfinite_streak_alerts_at_streak_length(self):
        m = _mon(streak=3)
        for i in range(3):
            m.observe({"round_index": i, "loss": float("nan")})
        assert len(m.alerts) == 1
        a = m.alerts[0]
        assert a["rule"] == "nonfinite_loss" and a["streak"] == 3
        assert m.tripped is None                      # warn never trips
        alerts = [r for r in m.recorder.memory if r["event"] == "alert"]
        assert len(alerts) == 1 and alerts[0]["rule"] == "nonfinite_loss"

    def test_finite_loss_resets_streak(self):
        m = _mon(streak=3)
        for i, loss in enumerate([float("nan"), float("nan"), 1.0,
                                  float("nan"), float("nan")]):
            m.observe({"round_index": i, "loss": loss})
        assert not m.alerts

    def test_fatal_action_sets_tripped(self):
        m = HealthMonitor(action="checkpoint-abort", streak=2)
        for i in range(2):
            m.observe({"round_index": i, "loss": float("inf")})
        assert m.tripped is not None
        assert m.tripped["severity"] == "fatal"
        assert m.tripped["action"] == "checkpoint-abort"

    def test_loss_divergence_needs_warmup(self):
        m = _mon(streak=1, window=4, loss_mult=10.0)
        for i in range(4):                            # warm the EMA at ~1
            m.observe({"round_index": i, "loss": 1.0})
        m.observe({"round_index": 4, "loss": 500.0})
        assert [a["rule"] for a in m.alerts] == ["loss_divergence"]

    def test_divergence_before_warmup_is_silent(self):
        m = _mon(streak=1, window=8)
        m.observe({"round_index": 0, "loss": 1.0})
        m.observe({"round_index": 1, "loss": 1e9})
        assert not m.alerts

    def test_throughput_collapse_vs_rolling_median(self):
        m = _mon(streak=2, window=4, tput_frac=0.25)
        for i in range(4):
            m.observe({"round_index": i, "images": 1000,
                       "round_seconds": 1.0})
        for i in range(4, 6):                         # 10x slower
            m.observe({"round_index": i, "images": 1000,
                       "round_seconds": 10.0})
        assert [a["rule"] for a in m.alerts] == ["throughput_collapse"]

    def test_guard_spike(self):
        m = _mon(streak=2, n_clients=4)
        for i in range(2):
            m.observe({"round_index": i, "guard_trips": 2.0,
                       "quarantined": 1})
        assert [a["rule"] for a in m.alerts] == ["guard_spike"]

    def test_buffer_backlog_on_growth_and_overflow(self):
        m = _mon(window=3, n_clients=8)
        for i, d in enumerate([1, 2, 3]):             # strictly growing
            m.observe({"round_index": i, "buffer_depth": d})
        assert [a["rule"] for a in m.alerts] == ["buffer_backlog"]
        m2 = _mon(n_clients=4)
        m2.observe({"round_index": 0, "buffer_depth": 4})   # >= cohort
        assert [a["rule"] for a in m2.alerts] == ["buffer_backlog"]

    def test_admission_blowup_and_zero_progress(self):
        m = _mon(streak=2)
        for i in range(2):
            m.observe({"round_index": i, "async_arrived": 3,
                       "admission_rejected": 3, "n_active": 0})
        rules = sorted(a["rule"] for a in m.alerts)
        assert rules == ["admission_blowup", "zero_progress"]

    def test_observe_never_raises(self):
        m = _mon()
        m.observe({"round_index": "garbage", "loss": object()})
        m.observe({})
        m.recorder = object()                         # broken recorder
        for i in range(5):
            m.observe({"round_index": i, "loss": float("nan")})

    def test_monitor_from_config(self):
        cfg = small_cfg(health_action="abort", health_streak=5)
        m = monitor_from_config(cfg)
        assert m.action == "abort" and m.streak == 5 and m.n_clients == K
        assert monitor_from_config(small_cfg(health_action="off")) is None


# ----------------------------------------------------------------------
# engine wiring: the acceptance scenario


class TestEngineHealth:
    def test_nan_run_checkpoint_aborts_with_verified_checkpoint(
            self, data, tmp_path):
        """Seeded corrupt=…,mode=nan + --health-action checkpoint-abort:
        terminates within the streak window, leaves a checksum-verified
        final checkpoint, and the JSONL holds the triggering alert."""
        from federated_pytorch_test_tpu.utils.checkpoint import (
            newest_slot,
            verify_checkpoint,
        )

        streak = 2
        cfg = small_cfg(Nloop=2, Nadmm=2,
                        fault_spec="corrupt=1,mode=nan,seed=3",
                        health_action="checkpoint-abort",
                        health_streak=streak,
                        obs_dir=str(tmp_path / "obs"),
                        obs_sinks="jsonl,memory")
        t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
        ck = str(tmp_path / "ck")
        with pytest.raises(RunHealthAbort) as ei:
            t.run(log=lambda m: None, checkpoint_path=ck)
        assert ei.value.alert["rule"] == "nonfinite_loss"
        # terminated within the streak window: every corrupted round has
        # a NaN loss, so the trip lands `streak` rounds in
        mem = t.obs_recorder.memory
        rounds = [r for r in mem if r["event"] == "round"]
        assert len(rounds) <= streak + 1
        # the triggering alert is IN the JSONL artifact
        records = read_records(t.obs_recorder.jsonl_path)
        alerts = [r for r in records if r["event"] == "alert"]
        assert alerts and alerts[0]["rule"] == "nonfinite_loss"
        assert alerts[0]["action"] == "checkpoint-abort"
        # obs stream closed as aborted, alert tally on the summary
        summary = records[-1]
        assert summary["event"] == "summary"
        assert summary["status"] == "aborted"
        assert summary["alerts_total"] == len(alerts)
        # a verified (checksummed) final checkpoint is on disk
        slot = newest_slot(ck)
        assert slot is not None
        assert verify_checkpoint(slot) is True

    def test_checkpoint_abort_without_midrun_uses_fallback_path(
            self, data, tmp_path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            newest_slot,
            verify_checkpoint,
        )

        cfg = small_cfg(fault_spec="corrupt=1,mode=nan,seed=3",
                        health_action="checkpoint-abort", health_streak=1,
                        checkpoint_dir=str(tmp_path))
        t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
        t.obs_run_name = "nanrun"
        with pytest.raises(RunHealthAbort):
            t.run(log=lambda m: None)                  # no checkpoint_path
        slot = newest_slot(str(tmp_path / "nanrun_health_abort"))
        assert slot is not None and verify_checkpoint(slot) is True

    def test_abort_action_raises_without_checkpoint(self, data, tmp_path):
        cfg = small_cfg(fault_spec="corrupt=1,mode=nan,seed=3",
                        health_action="abort", health_streak=1,
                        checkpoint_dir=str(tmp_path))
        t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
        with pytest.raises(RunHealthAbort):
            t.run(log=lambda m: None)
        assert not os.listdir(str(tmp_path))           # nothing saved

    def test_warn_lets_the_run_complete(self, data):
        cfg = small_cfg(fault_spec="corrupt=1,mode=nan,seed=3",
                        health_action="warn", health_streak=1)
        t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
        state, hist = t.run(log=lambda m: None)
        assert len(hist) == 4                          # full sweep ran
        alerts = [r for r in t.obs_recorder.memory if r["event"] == "alert"]
        assert alerts                                  # but it was loud
        assert t.obs_recorder.memory[-1]["alerts_total"] == len(alerts)

    def test_health_off_and_warn_are_bit_identical(self, data):
        """The watchdog observes, never perturbs: params bitwise equal
        across --health-action off/warn (the ISSUE's determinism note)."""

        def run(action):
            t = BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(obs_sinks="none",
                                     health_action=action),
                data, AdmmConsensus())
            state, _ = t.run(log=lambda m: None)
            return jax.device_get(state.params)

        a, b = run("off"), run("warn")
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_engine_emits_phase_spans(self, data, tmp_path):
        cfg = small_cfg(obs_dir=str(tmp_path), obs_sinks="jsonl,memory")
        t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
        state, hist = t.run(log=lambda m: None)
        records = read_records(t.obs_recorder.jsonl_path)
        rounds = [r for r in records if r["event"] == "round"]
        spans = [r for r in records if r["event"] == "span"]
        assert all("span_id" in r and "t_end" in r for r in rounds)
        names = {s["name"] for s in spans}
        assert {"train", "comm", "sync", "run"} <= names
        # the whole file exports to a VALID Chrome trace; compile
        # records (schema v6, obs/costs.py) export as spans too
        compiles = [r for r in records if r["event"] == "compile"]
        out = os.path.join(str(tmp_path), "t.json")
        n = obs_trace.export(t.obs_recorder.jsonl_path, out)
        assert n == len(rounds) + len(spans) + len(compiles)

    def test_invalid_health_knobs_fail_at_construction(self, data):
        with pytest.raises(ValueError, match="health_action"):
            BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(health_action="explode"), data,
                AdmmConsensus())
        with pytest.raises(ValueError, match="health_streak"):
            BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(health_streak=0), data,
                AdmmConsensus())


# ----------------------------------------------------------------------
# compare CLI


def _write_run(d, name, loss_final=1.0, secs=0.5):
    rec = make_recorder("jsonl", str(d), run_name=name, engine="t")
    rec.open()
    for i in range(3):
        rec.round({"round_index": i, "round_seconds": secs, "images": 256,
                   "loss": loss_final + (2 - i) * 0.1,
                   "comm_seconds": secs / 10})
    rec.close()
    return os.path.join(str(d), f"{name}.jsonl")


class TestCompareCLI:
    def test_self_vs_self_exits_zero(self, tmp_path, capsys):
        p = _write_run(tmp_path, "a")
        assert obs_compare.main([p, "--baseline", p]) == 0
        out = capsys.readouterr().out
        assert "0 regression(s)" in out and "images_per_sec" in out

    def test_regressed_run_exits_one(self, tmp_path, capsys):
        base = _write_run(tmp_path, "base", loss_final=1.0, secs=0.5)
        slow = _write_run(tmp_path, "slow", loss_final=1.0, secs=2.0)
        assert obs_compare.main([slow, "--baseline", base]) == 1
        assert "REGRESSED" in capsys.readouterr().out

    def test_noise_band_tolerates_small_deltas(self, tmp_path):
        base = _write_run(tmp_path, "base", secs=0.5)
        near = _write_run(tmp_path, "near", secs=0.51)     # 2% slower
        assert obs_compare.main([near, "--baseline", base,
                                 "--threshold", "5"]) == 0
        assert obs_compare.main([near, "--baseline", base,
                                 "--threshold", "1"]) == 1

    def test_empty_baseline_json_is_honest(self, tmp_path, capsys):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        run = _write_run(tmp_path, "a")
        assert obs_compare.main(
            [run, "--baseline", os.path.join(root, "BASELINE.json")]) == 0
        assert "no published numbers" in capsys.readouterr().out

    def test_unmeasured_artifact_contributes_no_verdict(self, tmp_path):
        p = os.path.join(str(tmp_path), "unmeasured.json")
        with open(p, "w") as f:
            # a pointer at another run's number must not be borrowed
            json.dump({"metric": "m", "value": 0.0, "measured": False,
                       "last_measured": {"path": "x.json", "value": 9.0}}, f)
        src = obs_compare.load_source(p)
        assert src["metrics"] == {} and "unmeasured" in src["notes"][0]

    def test_unknown_shape_exits_two(self, tmp_path):
        p = os.path.join(str(tmp_path), "weird.json")
        with open(p, "w") as f:
            json.dump({"hello": 1}, f)
        base = _write_run(tmp_path, "b")
        assert obs_compare.main([p, "--baseline", base]) == 2


# ----------------------------------------------------------------------
# report satellites


class TestReportSatellites:
    def test_record_ips_zero_seconds_is_inf_safe(self):
        assert record_ips({"images": 256, "round_seconds": 0}) == math.inf
        assert record_ips({"images": 0, "round_seconds": 0}) == 0.0
        assert record_ips({"images": 100, "round_seconds": 2.0},
                          n_chips=2) == 25.0

    def test_summarize_surfaces_async_fields(self):
        recs = [round_record(i, async_mode=True, max_staleness=2,
                             async_arrived=2, admission_rejected=i,
                             buffer_depth=i + 1, staleness_hist=[1, 1])
                for i in range(3)]
        s = summarize(recs)
        assert s["async_rounds"] == 3
        assert s["buffer_depth_peak"] == 3
        assert s["admission_rejected_total"] == 3
        assert s["staleness_hist_total"] == [3, 3]

    def test_summarize_counts_alerts(self):
        recs = [round_record(0),
                {"event": "alert", "schema": SCHEMA_VERSION,
                 "run_id": "t" * 8, "rule": "nonfinite_loss",
                 "round_index": 0}]
        s = summarize(recs)
        assert s["alerts"] == 1 and s["alert_rules"] == ["nonfinite_loss"]


# ----------------------------------------------------------------------
# driver plumbing


class TestDriverHealthPlumbing:
    def test_classifier_parser_exposes_health_action(self):
        from federated_pytorch_test_tpu.drivers.common import (
            build_parser,
            config_from_args,
        )

        p = build_parser(FederatedConfig(), "prog")
        args = p.parse_args(["--health-action", "checkpoint-abort",
                             "--health-streak", "5"])
        cfg = config_from_args(args)
        assert cfg.health_action == "checkpoint-abort"
        assert cfg.health_streak == 5
        assert config_from_args(p.parse_args([])).health_action == "warn"
        with pytest.raises(SystemExit):
            p.parse_args(["--health-action", "nonsense"])

    def test_cpc_driver_exposes_health_action(self):
        from federated_pytorch_test_tpu.drivers.federated_cpc import (
            build_parser,
        )

        p = build_parser()
        assert p.parse_args([]).health_action == "warn"
        args = p.parse_args(["--health-action", "abort"])
        assert args.health_action == "abort"

    def test_actions_tuple_is_the_flag_surface(self):
        assert HEALTH_ACTIONS == ("off", "warn", "abort",
                                  "checkpoint-abort")
