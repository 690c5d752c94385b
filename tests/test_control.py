"""Closed-loop control-plane tests (control/ + engine/driver wiring).

Covers the schema v8 ``control`` record kind and its recorder plumbing,
the deterministic policy engine (hysteresis, cooldown, bit-exact
re-derivation), the restart supervisor (bounded budget, seeded backoff,
degradation ladder, structured give-up), the graceful-degradation
satellites (JsonlSink retry/overflow, ``NoUsableCheckpointError``), the
bit-identity contract (``--control off`` == no controller;
``act`` with nothing fired == ``observe``; supervised restart with no
interventions == manual kill/resume), and the seeded chaos acceptance
run: ``corrupt=…,mode=nan`` + ``delay=`` faults under ``--control act
--max-restarts 2`` must survive via restart + the shield rung of the
ladder, with every intervention on disk as a ``control`` record that
``control.replay`` reproduces exactly.
"""

import json
import os

import jax
import numpy as np
import pytest

import flax.linen as nn

from federated_pytorch_test_tpu.control.policy import (
    COMPRESS_LADDER,
    Controller,
    ControlPolicy,
    Decision,
    SCOPE_BLOCK,
    SCOPE_RESTART,
    SCOPE_ROUND,
    controller_from_config,
)
from federated_pytorch_test_tpu.control.replay import (
    main as replay_main,
    replay,
)
from federated_pytorch_test_tpu.control.supervisor import (
    RestartBudgetExhausted,
    ladder_overrides,
    ladder_records,
    ladder_skips,
    restart_backoff_seconds,
    supervise,
    supervise_classifier,
)
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
    SchemaError,
    make_recorder,
    validate_record,
)
from federated_pytorch_test_tpu.obs.health import (
    HealthMonitor,
    RunHealthAbort,
)
from federated_pytorch_test_tpu.obs.report import read_records, summarize
from federated_pytorch_test_tpu.obs.sinks import JsonlSink
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FederatedConfig,
)
from federated_pytorch_test_tpu.utils.checkpoint import (
    NoUsableCheckpointError,
    finalize_checkpoint,
)

pytestmark = pytest.mark.control

K = 4


class TinyNet(BlockModule):
    """2-block toy CNN (same shape as test_obs_health's)."""

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


def round_rec(i, *, secs=1.0, comm=0.1, **kw):
    rec = {"event": "round", "round_index": i, "round_seconds": secs,
           "comm_seconds": comm, "loss": 1.0, "images": 64}
    rec.update(kw)
    return rec


def alert_rec(i, rule, *, severity="warn", **kw):
    rec = {"event": "alert", "round_index": i, "rule": rule,
           "severity": severity, "observed": 1.0, "threshold": 1.0,
           "streak": 1}
    rec.update(kw)
    return rec


def params_bytes(state):
    return [np.asarray(jax.device_get(leaf)).tobytes()
            for leaf in jax.tree_util.tree_leaves(state.params)]


# ----------------------------------------------------------------------
# schema v8: the control record kind


class TestControlSchema:
    def _rec(self, **kw):
        rec = {"event": "control", "schema": SCHEMA_VERSION,
               "run_id": "c" * 8, "round_index": 3, "source": "policy",
               "intervention": "escalate_compression"}
        rec.update(kw)
        return rec

    def test_minimal_control_record_validates(self):
        validate_record(self._rec())

    def test_full_control_record_validates(self):
        validate_record(self._rec(
            param="compress", from_value="none", to_value="q8",
            scope="block", reason="comm-bound", mode="act", applied=True,
            observed=0.8, threshold=0.5, streak=3, attempt=1,
            backoff_seconds=0.0, ladder_stage=1))

    @pytest.mark.parametrize("missing", ["source", "intervention",
                                         "round_index"])
    def test_missing_required_field_rejected(self, missing):
        rec = self._rec()
        del rec[missing]
        with pytest.raises(SchemaError, match=missing):
            validate_record(rec)

    def test_recorder_emits_and_counts_control_records(self, tmp_path):
        rec = make_recorder("jsonl,memory", str(tmp_path),
                            run_name="ctl", engine="classifier")
        ctl = Controller(ControlPolicy(), mode="observe")
        rec.attach_control(ctl)
        rec.open(config={"K": K})
        rec.round({"round_index": 0, "round_seconds": 1.0, "loss": 1.0})
        rec.control_event({"round_index": 0, "source": "policy",
                           "intervention": "escalate_compression",
                           "param": "compress", "from_value": "none",
                           "to_value": "q8"})
        rec.close()
        controls = [r for r in rec.memory if r["event"] == "control"]
        assert len(controls) == 1
        # determinism contract: control records never carry a timestamp
        assert "time_unix" not in controls[0]
        assert rec.memory[-1]["interventions_total"] == 1
        s = summarize(read_records(os.path.join(tmp_path, "ctl.jsonl")))
        assert s["controls"] == 1
        assert s["control_interventions"] == ["escalate_compression"]

    def test_feed_order_matches_file_order(self):
        # the recorder must show the controller records in the exact
        # order they land in the stream: round N, then round N's alerts
        seen = []

        class Spy(ControlPolicy):
            def observe(self, rec):
                seen.append((rec.get("event", "round"),
                             rec.get("round_index")))
                return super().observe(rec)

        rec = make_recorder("memory", None, run_name="order",
                            engine="classifier")
        mon = HealthMonitor(action="warn", streak=1, n_clients=K)
        rec.attach_health(mon)
        rec.attach_control(Controller(Spy(), mode="observe"))
        rec.open()
        rec.round({"round_index": 0, "round_seconds": 1.0, "loss": 1.0})
        rec.round({"round_index": 1, "round_seconds": 1.0,
                   "loss": float("nan")})
        rec.close()
        file_order = [(r["event"], r.get("round_index"))
                      for r in rec.memory
                      if r["event"] in ("round", "alert")]
        assert seen == file_order
        assert seen == [("round", 0), ("round", 1), ("alert", 1)]


# ----------------------------------------------------------------------
# policy engine: determinism + hysteresis


class TestControlPolicy:
    def test_escalation_streak_and_cooldown(self):
        p = ControlPolicy(preset="default")      # streak 3, cooldown 6
        fired = []
        for i in range(14):                      # r14 would fire rung 3
            fired += p.observe(round_rec(i, comm=0.8))
        assert [d.intervention for d in fired] == [
            "escalate_compression", "escalate_compression"]
        first, second = fired
        assert (first.round_index, first.from_value, first.to_value) == \
            (2, "none", "q8")
        # the compress param stays cooled down for 6 rounds after firing
        assert second.round_index >= first.round_index + 6
        assert (second.from_value, second.to_value) == ("q8", "q4")

    def test_decisions_are_deterministic(self):
        stream = ([round_rec(i, comm=0.9) for i in range(6)]
                  + [alert_rec(6, "admission_blowup")]
                  + [round_rec(7 + i, comm=0.01, admission_rejected=0)
                     for i in range(8)])
        def derive():
            p = ControlPolicy(preset="eager", async_rounds=True)
            out = []
            for rec in stream:
                out += p.observe(rec)
            return [d.key() for d in out]
        assert derive() == derive()
        assert derive()                  # the synthetic stream does fire

    def test_deescalation_floors_at_configured_rung(self):
        p = ControlPolicy(preset="eager")        # streak 2, cooldown 3
        for i in range(4):
            p.observe(round_rec(i, comm=0.9))    # escalate none -> q8
        assert COMPRESS_LADDER[p.cur_compress] == "q8"
        fired = []
        for i in range(4, 30):
            fired += p.observe(round_rec(i, comm=0.001))
        down = [d for d in fired
                if d.intervention == "deescalate_compression"]
        assert len(down) == 1                    # back to baseline, stop
        assert (down[0].from_value, down[0].to_value) == ("q8", "none")
        assert p.cur_compress == 0

    def test_fused_collective_caps_ladder_at_q4(self):
        p = ControlPolicy(preset="eager", compress="q8",
                          fused_collective=True)
        fired = []
        for i in range(40):
            fired += p.observe(round_rec(i, comm=0.9))
        assert [d.to_value for d in fired] == ["q4"]   # never topk

    def test_staleness_relax_capped_and_walked_back(self):
        p = ControlPolicy(preset="eager", max_staleness=2,
                          async_rounds=True)
        fired = []
        for i in range(0, 40, 4):        # spaced past the cooldown
            fired += p.observe(alert_rec(i, "admission_blowup"))
        relax = [d for d in fired if d.intervention == "relax_staleness"]
        assert [d.to_value for d in relax] == [3, 4, 5, 6]   # start + 4 cap
        assert p.cur_staleness == 6
        fired = []
        for i in range(40, 80):
            fired += p.observe(round_rec(i, admission_rejected=0))
        tight = [d for d in fired
                 if d.intervention == "tighten_staleness"]
        assert tight and tight[0].to_value == 5
        assert all(d.to_value >= 2 for d in tight)

    def test_fatal_alerts_are_supervisor_territory(self):
        p = ControlPolicy()
        assert p.observe(alert_rec(0, "nonfinite_loss",
                                   severity="fatal")) == []

    def test_nonfinite_loss_warn_requests_restart(self):
        p = ControlPolicy()
        fired = p.observe(alert_rec(0, "nonfinite_loss"))
        assert [d.intervention for d in fired] == ["checkpoint_restart"]
        assert fired[0].scope == SCOPE_RESTART

    def test_trim_requires_capable_aggregator(self):
        assert ControlPolicy(robust_agg="none").observe(
            alert_rec(0, "guard_spike")) == []
        fired = ControlPolicy(robust_agg="trim", trim_frac=0.1).observe(
            alert_rec(0, "guard_spike"))
        assert [(d.intervention, d.to_value) for d in fired] == \
            [("tighten_trim", 0.15)]

    def test_shrink_batch_floors(self):
        p = ControlPolicy(default_batch=32)      # floor = max(8, 8) = 8
        fired = []
        for i in range(0, 60, 8):
            fired += p.observe(alert_rec(i, "throughput_collapse"))
        assert [d.to_value for d in fired
                if d.intervention == "shrink_batch"] == [16, 8]

    def test_controller_routing_by_scope(self):
        ctl = Controller(ControlPolicy(), mode="act", can_restart=True)
        mk = lambda iv, param, scope: Decision(
            round_index=0, intervention=iv, param=param, from_value=1,
            to_value=2, scope=scope, reason="t")
        ctl._register(mk("relax_staleness", "max_staleness", SCOPE_ROUND))
        ctl._register(mk("escalate_compression", "compress", SCOPE_BLOCK))
        ctl._register(mk("tighten_trim", "trim_frac", SCOPE_RESTART))
        ctl._register(mk("checkpoint_restart", "run", SCOPE_RESTART))
        assert [d.param for d in ctl.take_round()] == ["max_staleness"]
        assert [d.param for d in ctl.take_block()] == ["compress"]
        assert ctl.take_restart().intervention == "checkpoint_restart"
        applied = {r["intervention"]: r["applied"] for r in ctl.records}
        assert applied["tighten_trim"] is False      # supervisor's job
        assert applied["checkpoint_restart"] is True

    def test_controller_from_config_off_is_none(self):
        assert controller_from_config(small_cfg()) is None
        ctl = controller_from_config(small_cfg(control="observe"))
        assert ctl is not None and ctl.mode == "observe"
        with pytest.raises(ValueError, match="control"):
            controller_from_config({"control": "bogus"})


# ----------------------------------------------------------------------
# restart supervisor: ladder, backoff, budget


class TestSupervisor:
    def test_backoff_is_seeded_and_exponential(self):
        a = restart_backoff_seconds(1.0, seed=7, attempt=1)
        b = restart_backoff_seconds(1.0, seed=7, attempt=2)
        assert a == restart_backoff_seconds(1.0, seed=7, attempt=1)
        assert 0.5 <= a < 1.5
        assert 1.0 <= b < 3.0
        assert restart_backoff_seconds(0.0, seed=7, attempt=3) == 0.0
        assert restart_backoff_seconds(1.0, seed=8, attempt=1) != a

    def test_ladder_restart_one_is_plain(self):
        cfg = small_cfg()
        stage, out, changes = ladder_overrides(cfg, 1)
        assert (stage, changes) == (0, [])
        assert out == cfg

    def test_ladder_stages_accumulate(self):
        cfg = small_cfg()
        _, c2, ch2 = ladder_overrides(cfg, 2)
        assert {(s, f) for s, f, _, _ in ch2} == {
            ("shield", "compress"), ("shield", "update_guard"),
            ("shield", "quarantine_rounds")}
        assert (c2.compress, c2.update_guard) == ("q8", True)
        _, c3, ch3 = ladder_overrides(cfg, 3)
        assert c3.robust_agg == "median"
        _, c4, ch4 = ladder_overrides(cfg, 4)
        assert c4.participation == 0.5
        # capped at the ladder length; stays valid arbitrarily deep
        assert ladder_overrides(cfg, 9)[1] == c4

    def test_ladder_respects_engine_constraints(self):
        bb = small_cfg(bb_update=True)
        _, out, _ = ladder_overrides(bb, 4)
        assert out.update_guard is False          # forbidden under bb
        assert out.participation == 1.0
        fused = small_cfg(compress="q4", fused_collective=True)
        _, out, _ = ladder_overrides(fused, 3)
        assert out.compress == "q4"               # capped, not topk
        assert out.robust_agg == "none"           # fused owns chokepoint

    def test_supervise_retries_then_succeeds(self):
        calls, slept = [], []
        def run_attempt(attempt, resume):
            calls.append((attempt, resume))
            if attempt < 3:
                raise RunHealthAbort({"rule": "nonfinite_loss",
                                      "round_index": attempt})
            return "done"
        out = supervise(run_attempt, max_restarts=3, backoff_base=1.0,
                        seed=11, log=lambda m: None, sleep=slept.append)
        assert out == "done"
        assert calls == [(1, False), (2, True), (3, True)]
        assert slept == [restart_backoff_seconds(1.0, 11, 1),
                         restart_backoff_seconds(1.0, 11, 2)]

    def test_supervise_budget_exhausted_writes_give_up(self, tmp_path):
        jsonl = str(tmp_path / "seg.jsonl")
        def run_attempt(attempt, resume):
            raise RunHealthAbort({"rule": "nonfinite_loss",
                                  "round_index": 5})
        with pytest.raises(RestartBudgetExhausted) as ei:
            supervise(run_attempt, max_restarts=2, backoff_base=0.0,
                      seed=0, log=lambda m: None, sleep=lambda s: None,
                      describe=lambda a: (jsonl, "r" * 8, []))
        assert ei.value.attempts == 2
        recs = read_records(jsonl, validate=True)
        assert [r["intervention"] for r in recs] == \
            ["restart", "restart", "give_up"]
        assert [r["attempt"] for r in recs] == [1, 2, 3]
        assert isinstance(ei.value.__cause__, RunHealthAbort)

    def test_supervise_gives_up_without_checkpoint(self):
        def run_attempt(attempt, resume):
            raise NoUsableCheckpointError("no slot on disk")
        with pytest.raises(NoUsableCheckpointError):
            supervise(run_attempt, max_restarts=5, backoff_base=0.0,
                      seed=0, log=lambda m: None, sleep=lambda s: None)

    def test_supervise_passes_unrelated_exceptions(self):
        def run_attempt(attempt, resume):
            raise ValueError("not a run failure")
        with pytest.raises(ValueError):
            supervise(run_attempt, max_restarts=5, backoff_base=0.0,
                      seed=0, log=lambda m: None, sleep=lambda s: None)


# ----------------------------------------------------------------------
# graceful-degradation satellites


class TestNoUsableCheckpoint:
    def test_finalize_empty_path_raises_typed_error(self, tmp_path):
        with pytest.raises(NoUsableCheckpointError):
            finalize_checkpoint(str(tmp_path / "never_saved"))
        # subclassing keeps pre-existing FileNotFoundError callers alive
        assert issubclass(NoUsableCheckpointError, FileNotFoundError)


class TestJsonlSinkDegradation:
    def test_transient_oserror_is_retried(self, tmp_path):
        slept = []
        sink = JsonlSink(str(tmp_path / "out.jsonl"), sleep=slept.append)
        real = sink._write_line
        fails = {"n": 2}
        def flaky(line):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise OSError("transient")
            real(line)
        sink._write_line = flaky
        sink.emit({"event": "round", "round_index": 0})
        assert not sink.degraded
        assert slept == [0.05, 0.1]              # bounded backoff
        sink._write_line = real
        sink.close()
        assert len(read_records(sink.path, validate=False)) == 1

    def test_persistent_oserror_degrades_once(self, tmp_path, capsys):
        sink = JsonlSink(str(tmp_path / "out.jsonl"),
                         sleep=lambda s: None)
        real = sink._write_line
        def dead(line):
            raise OSError("disk full")
        sink._write_line = dead
        for i in range(3):
            sink.emit({"event": "round", "round_index": i})
        assert sink.degraded
        assert [r["round_index"] for r in sink.overflow] == [0, 1, 2]
        err = capsys.readouterr().err.strip().splitlines()
        warnings = [l for l in err if "sink_degraded" in l]
        assert len(warnings) == 1                # ONE structured warning
        assert json.loads(warnings[0])["sink"] == "jsonl"
        # the filesystem comes back: close() lands the overflow
        sink._write_line = real
        sink.close()
        recs = read_records(sink.path, validate=False)
        assert [r["round_index"] for r in recs] == [0, 1, 2]

    def test_overflow_is_bounded(self, tmp_path):
        sink = JsonlSink(str(tmp_path / "out.jsonl"),
                         sleep=lambda s: None)
        sink._write_line = lambda line: (_ for _ in ()).throw(
            OSError("dead"))
        sink.OVERFLOW_CAP = 4
        for i in range(7):
            sink.emit({"event": "round", "round_index": i})
        assert [r["round_index"] for r in sink.overflow] == [3, 4, 5, 6]
        assert sink.dropped == 3


# ----------------------------------------------------------------------
# engine wiring: validation + in-run application


class TestEngineWiring:
    def test_bad_control_config_rejected(self, data):
        for kw in (dict(control="bogus"),
                   dict(control_policy="bogus"),
                   dict(max_restarts=-1),
                   dict(restart_backoff=-0.5)):
            with pytest.raises(ValueError):
                BlockwiseFederatedTrainer(TinyNet(), small_cfg(**kw),
                                          data, AdmmConsensus())

    def test_round_scope_applies_live(self, data):
        t = BlockwiseFederatedTrainer(
            TinyNet(), small_cfg(control="act", async_rounds=True,
                                 max_staleness=2),
            data, AdmmConsensus())
        ctl = Controller(ControlPolicy.from_config(t.cfg), mode="act")
        ctl._register(Decision(
            round_index=0, intervention="relax_staleness",
            param="max_staleness", from_value=2, to_value=3,
            scope=SCOPE_ROUND, reason="t"))
        class Obs:
            control = ctl
        t._apply_round_control(Obs(), None, log=lambda m: None)
        assert t.cfg.max_staleness == 3

    def test_block_scope_swaps_compressor(self, data):
        t = BlockwiseFederatedTrainer(
            TinyNet(), small_cfg(control="act"), data, AdmmConsensus())
        assert t.compressor.name == "none"
        ctl = Controller(ControlPolicy.from_config(t.cfg), mode="act")
        ctl._register(Decision(
            round_index=0, intervention="escalate_compression",
            param="compress", from_value="none", to_value="q8",
            scope=SCOPE_BLOCK, reason="t"))
        class Obs:
            control = ctl
        t._apply_block_control(Obs(), log=lambda m: None)
        assert t.compressor.name == "q8"
        assert t.cfg.compress == "q8"
        assert not t._fn_cache                   # forces a fresh build


# ----------------------------------------------------------------------
# bit-identity: off == no controller; act(nothing fired) == observe


class TestBitIdentity:
    def _run(self, data, **kw):
        t = BlockwiseFederatedTrainer(TinyNet(), small_cfg(**kw), data,
                                      AdmmConsensus())
        state, hist = t.run(log=lambda m: None)
        return t, state, hist

    def test_off_observe_act_are_bit_identical(self, data):
        # patient preset: streak 5 > the run's 4 rounds, so nothing can
        # fire and all three modes must produce the same bits
        t0, s0, h0 = self._run(data, control="off")
        t1, s1, h1 = self._run(data, control="observe",
                               control_policy="patient")
        t2, s2, h2 = self._run(data, control="act",
                               control_policy="patient")
        assert params_bytes(s0) == params_bytes(s1) == params_bytes(s2)
        for t in (t1, t2):
            assert [r for r in t.obs_recorder.memory
                    if r["event"] == "control"] == []


# ----------------------------------------------------------------------
# supervised restart with no interventions == manual kill/resume


# the round-record subset that is a pure function of the computation
# (no wall clock, no span ids); repr() makes NaN == NaN comparable
_DET_KEYS = ("round_index", "loss", "primal_residual", "dual_residual",
             "rho", "bytes_on_wire", "images", "n_active", "guard_trips",
             "admission_rejected")


def _det_view(rec):
    return {k: repr(rec.get(k)) for k in _DET_KEYS}


CHAOS = dict(fault_spec="corrupt=0.2,mode=nan,seed=0",
             health_action="abort", health_streak=1,
             health_residual=True, obs_sinks="jsonl,memory")


class TestSupervisedVsManualResume:
    def test_plain_restart_matches_manual_resume(self, data, tmp_path):
        # the fault schedule is stateless in the round coordinates, so a
        # plain resume trips again at the same round in both paths; the
        # replayed segment's telemetry must match bit-for-bit
        import dataclasses
        cfg = FederatedConfig(**dict(
            dict(K=K, Nloop=2, Nepoch=1, Nadmm=2, default_batch=16,
                 check_results=False, admm_rho0=0.1), **CHAOS))
        silent = lambda m: None

        # manual: run -> abort -> fresh trainer resumes -> abort again
        mdir = tmp_path / "manual"
        mcfg = dataclasses.replace(cfg, obs_dir=str(mdir / "obs"))
        t1 = BlockwiseFederatedTrainer(TinyNet(), mcfg, data,
                                       AdmmConsensus())
        t1.obs_run_name = "seg"
        with pytest.raises(RunHealthAbort):
            t1.run(log=silent, checkpoint_path=str(mdir / "ck"))
        t2 = BlockwiseFederatedTrainer(TinyNet(), mcfg, data,
                                       AdmmConsensus())
        t2.obs_run_name = "seg"
        with pytest.raises(RunHealthAbort):
            t2.run(log=silent, checkpoint_path=str(mdir / "ck"),
                   resume=True)

        # supervised: one restart of budget, so the only restart is the
        # plain (stage-0) resume — then the budget is spent
        sdir = tmp_path / "supervised"
        scfg = dataclasses.replace(cfg, obs_dir=str(sdir / "obs"),
                                   max_restarts=1, restart_backoff=0.0)
        def build(c, attempt):
            t = BlockwiseFederatedTrainer(TinyNet(), c, data,
                                          AdmmConsensus())
            t.obs_run_name = "seg"
            return t
        with pytest.raises(RestartBudgetExhausted):
            supervise_classifier(build, scfg, str(sdir / "ck"),
                                 run_kwargs={"log": silent},
                                 log=silent, sleep=lambda s: None)

        def segment_rounds(path):
            recs = read_records(path, validate=True)
            seg, idx = [], -1
            for r in recs:
                if r["event"] == "run_header":
                    idx += 1
                    seg.append([])
                elif r["event"] == "round" and idx >= 0:
                    seg[idx].append(_det_view(r))
            return seg

        manual = segment_rounds(str(mdir / "obs" / "seg.jsonl"))
        sup = segment_rounds(str(sdir / "obs" / "seg.jsonl"))
        assert len(manual) == 2 and len(sup) == 2
        assert manual[0] == sup[0]           # original segments agree
        assert manual[1] == sup[1]           # plain restart == manual
        assert manual[1], "resumed segment recorded no rounds"


# ----------------------------------------------------------------------
# seeded chaos acceptance: corrupt + delay faults, act mode, survival


class TestChaosAcceptance:
    def test_run_survives_via_restart_and_shield(self, data, tmp_path):
        cfg = FederatedConfig(**dict(
            dict(K=K, Nloop=2, Nepoch=1, Nadmm=2, default_batch=16,
                 check_results=False, admm_rho0=0.1,
                 async_rounds=True, max_staleness=2,
                 control="act", max_restarts=2, restart_backoff=0.0,
                 obs_dir=str(tmp_path / "obs")),
            **dict(CHAOS, fault_spec="corrupt=0.2,mode=nan,seed=0,"
                                     "delay=0.25,delay_max=1")))
        built = []
        def build(c, attempt):
            t = BlockwiseFederatedTrainer(TinyNet(), c, data,
                                          AdmmConsensus())
            t.obs_run_name = "chaos"
            built.append((attempt, c.compress, c.update_guard))
            return t
        state, hist = supervise_classifier(
            build, cfg, str(tmp_path / "ck"),
            run_kwargs={"log": lambda m: None},
            log=lambda m: None, sleep=lambda s: None)
        assert len(hist) == cfg.Nloop * 2 * cfg.Nadmm      # full run
        for leaf in params_bytes(state):
            assert np.all(np.isfinite(
                np.frombuffer(leaf, dtype=np.float32)))
        # restart 1 resumed plain; restart 2 carried the shield rung
        assert built[0][1:] == ("none", False)
        assert built[1][1:] == ("none", False)
        assert built[2][1:] == ("q8", True)

        path = str(tmp_path / "obs" / "chaos.jsonl")
        recs = read_records(path, validate=True)
        controls = [r for r in recs if r["event"] == "control"]
        sup = [r for r in controls if r["source"] == "supervisor"]
        restarts = [r for r in sup if r["intervention"] == "restart"]
        ladder = [r for r in sup
                  if r["intervention"] == "ladder_override"]
        assert [r["attempt"] for r in restarts] == [1, 2]
        assert {(r["param"], r["to_value"]) for r in ladder} == {
            ("compress", "q8"), ("update_guard", True),
            ("quarantine_rounds", 2)}
        assert all(r["ladder_stage"] == 1 for r in ladder)
        assert all("time_unix" not in r for r in controls)

        # replay: exit 0 on the honest stream, 1 once tampered — a
        # forged backoff no longer matches the seeded formula
        assert replay_main([path]) == 0
        lines = open(path).read().splitlines()
        tampered = str(tmp_path / "tampered.jsonl")
        out = []
        for line in lines:
            r = json.loads(line)
            if (r.get("event") == "control"
                    and r.get("intervention") == "restart"):
                r["backoff_seconds"] = 99.0
            out.append(json.dumps(r))
        with open(tampered, "w") as f:
            f.write("\n".join(out) + "\n")
        assert replay_main([tampered]) == 1
        # dropping the first restart breaks the attempt numbering
        dropped = str(tmp_path / "dropped.jsonl")
        with open(dropped, "w") as f:
            for line in lines:
                r = json.loads(line)
                if (r.get("event") == "control"
                        and r.get("intervention") == "restart"
                        and r.get("attempt") == 1):
                    continue
                f.write(line + "\n")
        assert replay_main([dropped]) == 1

    def test_elastic_preemption_reshapes_and_survives(self, tmp_path):
        # elastic-federation acceptance: a seeded preempt= fault hangs a
        # collective mid-run (CollectiveTimeoutError), the supervisor's
        # reshape rung resumes the newest checkpoint onto the surviving
        # 4-device mesh, the run completes, and control.replay verifies
        # the reshape record against the segment headers — exit 1 once
        # the record is tampered with or dropped
        data8 = FederatedCifar10(K=8, batch=16, limit_per_client=32,
                                 limit_test=32)
        cfg = FederatedConfig(
            K=8, Nloop=1, Nepoch=1, Nadmm=3, default_batch=16,
            check_results=False, admm_rho0=0.1, num_devices=8,
            fault_spec="preempt=1,seed=3", elastic_resume=True,
            max_restarts=2, restart_backoff=0.0,
            obs_sinks="jsonl,memory", obs_dir=str(tmp_path / "obs"))
        built = []

        def build(c, attempt):
            t = BlockwiseFederatedTrainer(TinyNet(), c, data8,
                                          AdmmConsensus())
            t.L = 1
            t.obs_run_name = "elastic"
            built.append((attempt, c.num_devices))
            return t

        state, hist = supervise_classifier(
            build, cfg, str(tmp_path / "ck"),
            run_kwargs={"log": lambda m: None},
            log=lambda m: None, sleep=lambda s: None)
        # the run completed despite losing half the mesh at round 1
        assert len(hist) == cfg.Nadmm
        # attempt 1 ran on the full mesh; the restart rebuilt on the
        # surviving divisor of K (8 -> 4); preemption is one-shot, so
        # the resumed segment ran to completion
        assert built[0] == (1, 8)
        assert built[1] == (2, 4)
        assert len(built) == 2

        path = str(tmp_path / "obs" / "elastic.jsonl")
        recs = read_records(path, validate=True)
        reshapes = [r for r in recs if r["event"] == "control"
                    and r["intervention"] == "reshape"]
        assert len(reshapes) == 1
        r = reshapes[0]
        assert (r["from_value"], r["to_value"]) == (8, 4)
        assert r["source"] == "supervisor" and r["scope"] == "restart"
        # the resumed segment's header advertises the reshaped mesh
        headers = [x for x in recs if x["event"] == "run_header"]
        assert [h["mesh_shape"]["clients"] for h in headers] == [8, 4]

        # replay: exit 0 on the honest stream
        assert replay_main([path]) == 0
        lines = open(path).read().splitlines()
        # tampered reshape target -> exit 1
        tampered = str(tmp_path / "tampered.jsonl")
        out = []
        for line in lines:
            rec = json.loads(line)
            if rec.get("intervention") == "reshape":
                rec["to_value"] = 2
            out.append(json.dumps(rec))
        with open(tampered, "w") as f:
            f.write("\n".join(out) + "\n")
        assert replay_main([tampered]) == 1
        # dropped reshape record -> exit 1 (the mesh changed between
        # segments with no decision on the stream)
        dropped = str(tmp_path / "dropped.jsonl")
        with open(dropped, "w") as f:
            for line in lines:
                if json.loads(line).get("intervention") != "reshape":
                    f.write(line + "\n")
        assert replay_main([dropped]) == 1

    def test_errors_list_names_divergence(self, tmp_path):
        # replay() (the library face of the CLI) reports structured
        # messages — spot-check one so the CLI text stays meaningful
        errors, stats = replay([
            {"event": "run_header", "schema": SCHEMA_VERSION,
             "run_id": "x" * 8, "time_unix": 1.0,
             "config": {"control": "observe"}},
            {"event": "control", "schema": SCHEMA_VERSION,
             "run_id": "x" * 8, "round_index": 0, "source": "policy",
             "intervention": "escalate_compression", "param": "compress",
             "from_value": "none", "to_value": "q8", "scope": "block",
             "reason": "forged"},
        ])
        assert errors and stats["segments"] == 1


# ----------------------------------------------------------------------
# engine-aware degradation ladder (ISSUE 15): CPC/VAE parametrizations


class TestEngineAwareLadder:
    def test_vae_ladder_is_the_classifier_ladder(self):
        # VAE shares the full blockwise feature set: no exclusions, no
        # skips — byte-identical ladder outcome at every attempt
        cfg = small_cfg()
        for attempt in range(1, 6):
            assert (ladder_overrides(cfg, attempt, engine="vae")
                    == ladder_overrides(cfg, attempt))
            assert ladder_skips(cfg, attempt, "vae") == []

    def test_cpc_ladder_suppresses_compress_only(self):
        cfg = small_cfg()
        _, c2, ch2 = ladder_overrides(cfg, 2, engine="cpc")
        assert {(s, f) for s, f, _, _ in ch2} == {
            ("shield", "update_guard"), ("shield", "quarantine_rounds")}
        assert c2.compress == "none"              # CPC has no compress path
        assert c2.update_guard is True
        skips = ladder_skips(cfg, 2, "cpc")
        assert [(s, f) for s, f, _ in skips] == [("shield", "compress")]
        assert "cpc" in skips[0][2]
        # later rungs are unaffected: median + reduced cohort still land
        _, c4, _ = ladder_overrides(cfg, 4, engine="cpc")
        assert c4.robust_agg == "median"
        assert c4.participation == 0.5
        assert c4.compress == "none"

    def test_ladder_records_log_skips_with_applied_false(self):
        cfg = small_cfg()
        recs = ladder_records(cfg, 2, run_id="r" * 8, ridx=3, engine="cpc")
        for r in recs:
            validate_record(r)
            assert r["intervention"] == "ladder_override"
        skipped = [r for r in recs if r.get("applied") is False]
        assert [r["param"] for r in skipped] == ["compress"]
        assert "skipped" in skipped[0]["reason"]
        applied = [r for r in recs if r["applied"]]
        assert {r["param"] for r in applied} == {"update_guard",
                                                "quarantine_rounds"}

    def test_cpc_engine_builds_every_degraded_config(self):
        # the whole point of the exclusion table: walk the ladder to its
        # deepest rung and hand each degraded config to the actual CPC
        # constructor — none may raise
        from federated_pytorch_test_tpu.data.lofar import CPCDataSource
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                            seed=7)
        cfg = FederatedConfig(check_results=False)
        for attempt in (1, 2, 3, 4):
            _, degraded, _ = ladder_overrides(cfg, attempt, engine="cpc")
            CPCTrainer(src, latent_dim=8, reduced_dim=4, lbfgs_history=3,
                       lbfgs_max_iter=1, Niter=1,
                       cfg=degraded)           # must not raise
        # counterfactual: the unfiltered classifier ladder at the same
        # rung is NOT constructible — the exclusion table is load-bearing
        _, bad, _ = ladder_overrides(cfg, 2)
        with pytest.raises(ValueError, match="compress"):
            CPCTrainer(src, latent_dim=8, reduced_dim=4, lbfgs_history=3,
                       lbfgs_max_iter=1, Niter=1, cfg=bad)


class TestCPCSupervised:
    # ~76 s: the single slowest tier-1 case (two full supervised CPC
    # runs).  Supervised crash/resume stays fast-covered by
    # TestSupervisedVsManualResume and TestChaosAcceptance above; the
    # CPC-engine resume contract by TestCPCGolden's default path +
    # tests/test_faults.py's CPC representatives.
    @pytest.mark.slow
    def test_crash_resume_matches_uninterrupted(self, tmp_path):
        """Supervised CPC (bare ``supervise`` + ladder_records describe,
        the drivers/federated_cpc path): one injected crash, restart 1
        resumes plain from the midrun slot and the stitched history is
        exactly the uninterrupted run's (``*_seconds`` stripped)."""
        from federated_pytorch_test_tpu.data.lofar import CPCDataSource
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        def make():
            src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"],
                                batch_size=2, seed=7)
            return CPCTrainer(src, latent_dim=8, reduced_dim=4,
                              lbfgs_history=3, lbfgs_max_iter=1, Niter=1,
                              cfg=FederatedConfig(check_results=False))

        # same normalization as tests/test_resume.py: the restarted
        # process re-compiles, so its wall-clock fields differ
        strip = lambda h: [
            {k: v for k, v in r.items()
             if not k.endswith("_seconds")
                and not k.startswith("dispatch_")} for r in h]
        _, want = make().run(Nloop=1, Nadmm=2, log=lambda m: None)

        ck = str(tmp_path / "cpc_sup_ck")

        class Crash(Exception):
            pass

        calls = []

        def maybe_bomb(msg):
            calls.append(msg)
            if len(calls) == 3:
                raise Crash

        def run_attempt(attempt, resume_now):
            t = make()
            log = maybe_bomb if attempt == 1 else (lambda m: None)
            return t.run(Nloop=1, Nadmm=2, log=log, checkpoint_path=ck,
                         resume=resume_now)

        _, got = supervise(run_attempt, max_restarts=2, backoff_base=0.0,
                           seed=5, retry_on=(Crash,), log=lambda m: None)
        assert strip(got) == strip(want)
