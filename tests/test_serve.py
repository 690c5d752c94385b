"""Serving plane (serve/ + engine/obs/control wiring, PR 18 tentpole).

The determinism contract under test (PARITY.md v0.14):

- the serve schedule is a pure function of (serve seed, spec, round
  index): request counts, batch plans, padding, the swap sequence and
  the drift flags re-derive bit-exactly from the stream header, across
  parses and across a kill/resume;
- a request in flight during a hot-swap is answered by exactly the old
  or exactly the new weights, never a mixture (the double buffer
  publishes with one atomic reference assignment);
- serving is a read: a run with the serving plane on trains bitwise
  the same trajectory as the same config with serving off, and
  ``serve_spec="none"`` is the literal seed path (no serve records, no
  plane constructed);
- the served eval stream closes the loop: seeded label drift trips the
  watchdog's ``serve_drift`` rule and, in act mode, a recorded policy
  intervention that forces a serving refresh at the next boundary.
"""

import dataclasses
import threading

import numpy as np
import pytest

import jax
import flax.linen as nn

from federated_pytorch_test_tpu.control.replay import replay
from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import (
    BlockModule,
    elu,
    flatten,
    max_pool_2x2,
    pairs,
)
from federated_pytorch_test_tpu.obs.report import read_records, summarize
from federated_pytorch_test_tpu.serve import (
    SERVE_FIELDS,
    BatchedPredictor,
    DoubleBuffer,
    EvalStream,
    MicroBatcher,
    ServeSchedule,
    bucket_for,
    pad_to_bucket,
    version_for,
)
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FederatedConfig,
)

pytestmark = pytest.mark.serve

K = 4

#: 8 rounds: hot-swap every 2, total label shift injected from round 4
SPEC = "qps=12,round_minutes=0.5,buckets=4+16+64,swap_every=2,drift_at=4,seed=3"


class TinyNet(BlockModule):
    """2-block toy CNN (test_engine.py convention)."""

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


class Killed(Exception):
    pass


@pytest.fixture(scope="module")
def data():
    return FederatedCifar10(K=K, batch=16, limit_per_client=32,
                            limit_test=32)


def small_cfg(**kw):
    base = dict(K=K, Nloop=2, Nepoch=1, Nadmm=4, default_batch=16,
                check_results=False, admm_rho0=0.1, seed=5,
                obs_sinks="memory")
    base.update(kw)
    return FederatedConfig(**base)


def serve_cfg(**kw):
    # health window 2 / streak 1 so the 8-round run can warm the EMA on
    # the pre-drift rounds and alert inside the drifted tail
    base = dict(serve_spec=SPEC, control="act", health_action="warn",
                health_window=2, health_streak=1, health_tput_frac=0.75)
    base.update(kw)
    return small_cfg(**base)


def run_trainer(cfg, data, **run_kw):
    t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
    t.L = 1
    run_kw.setdefault("log", lambda m: None)
    state, hist = t.run(**run_kw)
    return t, state, hist


def param_leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state.params)]


def det_view(rec):
    # wall-clock fields legitimately differ between processes
    return {k: v for k, v in rec.items()
            if isinstance(v, (int, float)) and not k.endswith("_seconds")
            and not k.startswith("dispatch_")}


def pure_fields(rec):
    return {k: rec.get(k) for k in SERVE_FIELDS}


# ----------------------------------------------------------------------
# schedule purity


class TestServeSchedule:
    def test_pure_and_roundtrips(self):
        a = ServeSchedule.parse(SPEC)
        b = ServeSchedule.parse(a.spec_string())
        for r in range(16):
            assert a.record_fields(r) == b.record_fields(r)
            assert a.requests_for(r) >= 1
        assert ServeSchedule.parse("none") is None
        assert ServeSchedule.parse("") is None

    def test_swap_and_drift_sequences(self):
        s = ServeSchedule.parse(SPEC)
        assert [s.weights_version(r) for r in range(8)] == \
            [1, 1, 2, 2, 3, 3, 4, 4]
        assert [s.swap(r) for r in range(8)] == \
            [True, False] * 4
        assert [s.drift_injected(r) for r in range(8)] == \
            [False] * 4 + [True] * 4
        assert version_for(7, 2) == 4

    def test_batch_plan_accounting(self):
        s = ServeSchedule.parse(SPEC)
        plan = s.batch_plan(70)
        assert plan == [(64, 64), (16, 6)]
        assert s.padded_slots(70) == 10
        assert s.padding_waste_frac(70) == round(10 / 80, 6)
        assert bucket_for(5, (4, 16, 64)) == 16
        x = np.zeros((5, 3), np.float32)
        assert pad_to_bucket(x, 16).shape == (16, 3)

    def test_bad_specs_raise(self):
        for bad in ("qps=0", "buckets=8+4", "swap_every=0", "nope=1",
                    "drift_at=-2"):
            with pytest.raises(ValueError):
                ServeSchedule.parse(bad)


# ----------------------------------------------------------------------
# never-torn hot swap


class TestDoubleBuffer:
    def test_in_flight_requests_never_torn(self):
        # a reader mid-request sees exactly one (version, weights) pair:
        # hammer publishes from a writer while readers assert the pair
        # stays internally consistent
        buf = DoubleBuffer()
        buf.publish(1, {"w": 1.0})
        stop = threading.Event()
        torn = []

        def writer():
            v = 1
            while not stop.is_set():
                v += 1
                buf.publish(v, {"w": float(v)})

        def reader():
            for _ in range(20000):
                version, weights = buf.acquire()
                if weights["w"] != float(version):
                    torn.append((version, weights["w"]))

        w = threading.Thread(target=writer)
        readers = [threading.Thread(target=reader) for _ in range(4)]
        w.start()
        for t in readers:
            t.start()
        for t in readers:
            t.join()
        stop.set()
        w.join()
        assert not torn, torn[:5]
        assert buf.swaps >= 1

    def test_acquire_before_publish_raises(self):
        with pytest.raises(RuntimeError):
            DoubleBuffer().acquire()


# ----------------------------------------------------------------------
# batched predictor + eval stream units


class TestPredictorUnits:
    def test_pads_to_buckets_and_slices(self):
        import jax.numpy as jnp

        pred = BatchedPredictor(lambda w, x: x * w, buckets=(4, 16))
        w = jnp.float32(2.0)
        out = pred(w, np.ones((5, 2), np.float32))
        assert out.shape == (5, 2)
        np.testing.assert_allclose(out, 2.0)
        # 5 rows pad to the 16-bucket; 3 rows to the 4-bucket — the
        # compiled-shape set is bounded by the bucket list
        pred(w, np.ones((3, 2), np.float32))
        assert pred.shapes_seen <= {(4, 2), (16, 2)}

    def test_evalstream_scores_drift(self):
        sched = ServeSchedule.parse("qps=8,drift_at=2,seed=1")
        es = EvalStream(sched, window=2)
        logits = np.eye(10, dtype=np.float32)[:8]
        labels = np.arange(8) % 10
        r0 = es.score(0, logits, labels)
        r1 = es.score(1, logits, labels)
        assert r0["serve_accuracy"] == r1["serve_accuracy"] == 1.0
        assert not r0["drift_injected"]
        r2 = es.score(2, logits, labels)
        assert r2["drift_injected"]
        assert r2["serve_accuracy"] == 0.0      # total label shift
        assert r2["drift_score"] == 1.0

    def test_microbatcher_orders_and_bounds(self):
        sched = ServeSchedule.parse("qps=8,buckets=4+16,seed=1")
        mb = MicroBatcher(sched, lambda b: [row.sum() for row in b],
                          max_queue=4)
        for i in range(4):
            mb.submit(np.full((2,), i, np.float32))
        with pytest.raises(OverflowError):
            mb.submit(np.zeros((2,), np.float32))
        outs, tel = mb.drain()
        assert [float(o) for o in outs] == [0.0, 2.0, 4.0, 6.0]
        assert tel["requests"] == 4 and tel["batches"] == 1
        assert tel["padded_slots"] == 0


# ----------------------------------------------------------------------
# live integration: train -> serve -> observe -> intervene


@pytest.fixture(scope="module")
def serve_run(data, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("serve")
    cfg = serve_cfg(obs_sinks="jsonl", obs_dir=str(tmp / "obs"))
    t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
    t.L = 1
    t.obs_run_name = "serve"
    state, hist = t.run(log=lambda m: None)
    records = read_records(str(tmp / "obs" / "serve.jsonl"),
                           validate=True)
    return cfg, state, hist, records


class TestServeIntegration:
    def test_records_rederive_and_replay(self, serve_run):
        cfg, _, hist, records = serve_run
        serves = [r for r in records if r.get("event") == "serve"]
        assert len(serves) == len(hist) == 8
        sched = ServeSchedule.parse(cfg.serve_spec)
        for rec, (r, fields) in zip(
                serves, sched.expected_records(range(8))):
            assert rec["round_index"] == r
            assert pure_fields(rec) == fields
        errors, stats = replay(records)
        assert not errors, errors
        assert stats["serve_records"] == 8, stats

    def test_swap_telemetry(self, serve_run):
        _, _, _, records = serve_run
        serves = [r for r in records if r.get("event") == "serve"]
        for rec in serves:
            if rec["swap"]:
                assert rec.get("swap_gap_seconds", 0) >= 0
            assert rec["serve_qps"] > 0
            assert rec["serve_p99_ms"] >= rec["serve_p50_ms"]
        s = summarize(records)
        assert s["serve_swaps"] == 4, s
        assert s["serve_weights_version_last"] == 4, s

    def test_drift_trips_watchdog_and_policy(self, serve_run):
        _, _, _, records = serve_run
        alerts = [r for r in records if r.get("event") == "alert"
                  and r.get("rule") == "serve_drift"]
        assert alerts, "seeded drift never tripped serve_drift"
        assert all(a["round_index"] >= 4 for a in alerts), alerts
        controls = [r for r in records if r.get("event") == "control"
                    and r.get("param") == "serve_swap"]
        assert controls, "act-mode policy never recorded the refresh"
        assert controls[0]["intervention"] == "refresh_serving"
        # the armed refresh lands at the NEXT round boundary and is
        # stamped on that round's serve record
        forced = [r for r in records if r.get("event") == "serve"
                  and r.get("forced_refresh")]
        assert forced, "forced refresh never reached the serving plane"
        assert forced[0]["round_index"] == controls[0]["round_index"] + 1

    def test_tampered_serve_record_fails_replay(self, serve_run):
        _, _, _, records = serve_run
        tampered = []
        for r in records:
            r = dict(r)
            if r.get("event") == "serve" and r.get("round_index") == 5:
                r["weights_version"] += 1
            tampered.append(r)
        errors, _ = replay(tampered)
        assert errors and "diverges" in errors[0], errors


# ----------------------------------------------------------------------
# serving is a read; serving off is the literal seed path


class TestServeOffSeedPath:
    def test_serving_never_perturbs_training(self, data, serve_run):
        cfg_on, s_on, h_on, _ = serve_run
        cfg_off = dataclasses.replace(cfg_on, serve_spec="none",
                                      obs_sinks="memory", obs_dir=None)
        t, s_off, h_off = run_trainer(cfg_off, data)
        assert t._serve_sched is None and t._serve_plane is None
        assert not any(r.get("event") == "serve"
                       for r in t.obs_recorder.memory)
        for a, b in zip(param_leaves(s_on), param_leaves(s_off)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h_on, h_off):
            assert det_view(ra) == det_view(rb)

    def test_spec_must_name_a_served_engine(self, data):
        # an engine without a serving adapter must refuse the spec
        # loudly, not silently skip the plane
        from federated_pytorch_test_tpu.train.rounds import RoundKernel
        t = BlockwiseFederatedTrainer(TinyNet(), serve_cfg(), data,
                                      AdmmConsensus())
        sched = ServeSchedule.parse(SPEC)
        with pytest.raises(ValueError, match="no serving adapter"):
            RoundKernel._build_serve_plane(t, sched)


# ----------------------------------------------------------------------
# kill/resume: the swap sequence is bitwise across segments


class TestServeKillResume:
    def test_swap_sequence_bitwise_across_restart(self, data, tmp_path,
                                                  serve_run):
        cfg_full, _, _, full_records = serve_run
        done = []

        def bomb(state, rec):
            done.append(1)
            if len(done) == 5:          # dies after completing round 4
                raise Killed

        ck = str(tmp_path / "ck")
        kcfg = dataclasses.replace(cfg_full, obs_sinks="jsonl",
                                   obs_dir=str(tmp_path / "obs"))
        t1 = BlockwiseFederatedTrainer(TinyNet(), kcfg, data,
                                       AdmmConsensus())
        t1.L = 1
        t1.obs_run_name = "seg"
        with pytest.raises(Killed):
            t1.run(log=lambda m: None, checkpoint_path=ck, on_round=bomb)
        t2 = BlockwiseFederatedTrainer(TinyNet(), kcfg, data,
                                       AdmmConsensus())
        t2.L = 1
        t2.obs_run_name = "seg"
        t2.run(log=lambda m: None, checkpoint_path=ck, resume=True)

        records = read_records(str(tmp_path / "obs" / "seg.jsonl"),
                               validate=True)
        errors, stats = replay(records)
        assert not errors, errors
        assert stats["segments"] == 2, stats
        # every serve record — including rounds the resumed segment
        # replayed — carries the same pure fields as the uninterrupted
        # run's record for that round
        want = {r["round_index"]: pure_fields(r) for r in full_records
                if r.get("event") == "serve"}
        got = [r for r in records if r.get("event") == "serve"]
        assert {r["round_index"] for r in got} == set(range(8))
        for rec in got:
            assert pure_fields(rec) == want[rec["round_index"]], rec
