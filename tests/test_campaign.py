"""Soak campaigns (campaign/ + engine/replay wiring, PR 17 tentpole).

The determinism contract under test (PARITY.md v0.13):

- the schedule compiler is a pure function of (seed, spec, round
  index): identical windows across parses, across a kill/resume, and
  across different mesh sizes — the mesh never feeds the schedule;
- the virtual clock only divides wall-clock waits; the seeded restart
  backoff VALUES (what replay verifies) are identical at any
  acceleration;
- a seeded 200-virtual-hour mini-campaign killed mid-run and resumed
  is bitwise the uninterrupted run (params + deterministic round
  fields), and its stitched stream passes ``control.replay``;
- campaign records re-derive bit-exactly from the stream header.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import flax.linen as nn

from federated_pytorch_test_tpu.campaign.clock import VirtualClock
from federated_pytorch_test_tpu.campaign.harness import (
    resolve_accel,
    soak_config,
)
from federated_pytorch_test_tpu.campaign.schedule import (
    CAMPAIGN_FIELDS,
    CampaignSchedule,
)
from federated_pytorch_test_tpu.control.replay import replay
from federated_pytorch_test_tpu.control.supervisor import (
    restart_backoff_seconds,
)
from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import (
    BlockModule,
    elu,
    flatten,
    max_pool_2x2,
    pairs,
)
from federated_pytorch_test_tpu.obs.report import read_records, summarize
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FederatedConfig,
)

pytestmark = pytest.mark.campaign

K = 4

SPEC = ("hours=200,round_minutes=600,diurnal=0.5,drop=0.2,straggle=0.1,"
        "mode=scale,scale=50,join=0.15,leave=0.15,storm=0.3,storm_len=2,"
        "storm_straggle=0.7,burst=0.2,burst_corrupt=0.3,seed=13")


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
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=16,
                check_results=False, admm_rho0=0.1, seed=5,
                obs_sinks="memory")
    base.update(kw)
    return FederatedConfig(**base)


def run_trainer(cfg, data, **run_kw):
    t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
    t.L = 1
    run_kw.setdefault("log", lambda m: None)
    state, hist = t.run(**run_kw)
    return t, state, hist


def param_leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state.params)]


def det_view(rec):
    # wall-clock fields legitimately differ between a resumed process
    # and an uninterrupted one, and a resumed segment's first round is a
    # block visit's first round: it stamps a switch
    return {k: v for k, v in rec.items()
            if isinstance(v, (int, float)) and not k.endswith("_seconds")
            and not k.startswith("dispatch_")
            and k != "block_switch_h2d_bytes"}


# ----------------------------------------------------------------------
# schedule compiler: purity


class TestScheduleCompiler:
    def test_windows_pure_across_parses(self):
        a = CampaignSchedule.parse(SPEC)
        b = CampaignSchedule.parse(SPEC)
        assert a == b
        for r in range(a.total_rounds):
            assert a.window(r) == b.window(r)

    def test_seed_changes_schedule(self):
        a = CampaignSchedule.parse(SPEC)
        b = CampaignSchedule.parse(SPEC.replace("seed=13", "seed=14"))
        assert any(a.window(r) != b.window(r)
                   for r in range(a.total_rounds))

    def test_derived_fault_specs_pure(self):
        # the per-round FaultSpec (what every seeded family draws from)
        # is itself a pure function of (spec, round index)
        a = CampaignSchedule.parse(SPEC)
        b = CampaignSchedule.parse(SPEC)
        for r in range(a.total_rounds):
            assert a.spec_for(a.window(r)) == b.spec_for(b.window(r))
            # campaign owns preemption deterministically — never as a
            # Bernoulli family draw
            assert a.spec_for(a.window(r)).preempt == 0.0

    def test_resume_tail_matches_full_sequence(self):
        a = CampaignSchedule.parse(SPEC)
        rounds = list(range(a.total_rounds))
        full = a.expected_emissions(rounds)
        cut = 7                                 # mid-hour resume point
        tail = a.expected_emissions(rounds[cut:])
        # the resumed segment re-emits its first round (segment-start
        # rule), then every transition the full run makes after the cut
        # appears in the tail with identical fields
        assert tail[0][0] == cut
        assert tail[1:] == [e for e in full if e[0] > cut]

    def test_grammar_rejections(self):
        for bad in ("hours=0,diurnal=0.5", "diurnal=1.5",
                    "hours=4,round_minutes=30",      # no load element
                    "hours=4,diurnal=0.5,mode=bogus,corrupt=0.1",
                    "hours=4,diurnal=0.5,preempt_at=-2",
                    "hours=4,diurnal=0.5,unknown_key=1"):
            with pytest.raises(ValueError):
                CampaignSchedule.parse(bad)

    def test_mutually_exclusive_with_fault_spec(self, data):
        with pytest.raises(ValueError, match="mutually exclusive"):
            BlockwiseFederatedTrainer(
                TinyNet(),
                small_cfg(campaign_spec="hours=2,diurnal=0.5",
                          fault_spec="drop=0.1"),
                data, AdmmConsensus())

    def test_mesh_size_does_not_feed_schedule(self, data):
        # K=4 clients on a 2- vs 4-device mesh: identical campaign
        # records AND identical per-round fault tallies — the schedule
        # and the seeded per-client draws never see the device count
        spec = ("hours=2,round_minutes=30,diurnal=0.6,drop=0.3,"
                "straggle=0.2,join=0.2,leave=0.2,seed=7")
        streams = {}
        for nd in (2, 4):
            t, _, hist = run_trainer(
                small_cfg(campaign_spec=spec, num_devices=nd), data)
            camp = [r for r in t.obs_recorder.memory
                    if r.get("event") == "campaign"]
            streams[nd] = (
                [{k: r.get(k) for k in CAMPAIGN_FIELDS} for r in camp],
                [{k: r.get(k) for k in ("fault_dropped",
                                        "fault_straggled",
                                        "fault_corrupted", "joined",
                                        "left", "members_active")}
                 for r in hist])
        assert streams[2] == streams[4]
        assert streams[2][0], "campaign emitted no records"


# ----------------------------------------------------------------------
# virtual clock: wall-time-only scaling


class TestVirtualClock:
    def test_accel_divides_wall_waits_only(self):
        waits = []
        clk = VirtualClock(accel=120.0, sleep=waits.append)
        clk.sleep(60.0)
        clk.sleep(6.0)
        assert waits == [0.5, 0.05]
        assert clk.virtual_slept == 66.0
        assert clk.wall_slept == 0.55

    def test_rejects_nonpositive_accel(self):
        for accel in (0.0, -5.0):
            with pytest.raises(ValueError):
                VirtualClock(accel=accel)

    def test_seeded_backoff_unchanged_under_acceleration(self):
        # what replay verifies is the recorded backoff VALUE; the clock
        # only changes how long the supervisor actually waits for it
        values = [restart_backoff_seconds(1.0, 11, a) for a in (1, 2, 3)]
        assert values == [restart_backoff_seconds(1.0, 11, a)
                          for a in (1, 2, 3)]
        slow_waits, fast_waits = [], []
        slow = VirtualClock(accel=1.0, sleep=slow_waits.append)
        fast = VirtualClock(accel=1000.0, sleep=fast_waits.append)
        for v in values:
            slow.sleep(v)
            fast.sleep(v)
        assert slow.virtual_slept == fast.virtual_slept == sum(values)
        assert fast_waits == [w / 1000.0 for w in slow_waits]

    def test_harness_accel_resolution(self):
        sched = CampaignSchedule.parse(
            "hours=4,diurnal=0.5,accel=240,health_window_hours=2")
        cfg = small_cfg()
        assert resolve_accel(cfg, sched) == 240.0
        assert resolve_accel(
            dataclasses.replace(cfg, campaign_accel=9.0), sched) == 9.0
        # 2 virtual hours at the default 30-minute rounds -> 4 rounds
        assert soak_config(cfg, sched).health_window == 4


# ----------------------------------------------------------------------
# 200-virtual-hour mini campaign: kill/resume bitwise


class TestMiniCampaignKillResume:
    def test_kill_resume_bitwise_and_replays(self, data, tmp_path):
        # 20 rounds of 10 virtual hours each = 200 virtual hours; the
        # kill lands mid-storm so the resumed segment must re-derive
        # the window it died in, not restart the schedule
        # L=1 trains one block per loop: Nloop=4 x 1 block x Nadmm=5
        # = 20 rounds
        def cfg(subdir):
            return small_cfg(Nloop=4, Nadmm=5, campaign_spec=SPEC,
                             obs_sinks="jsonl",
                             obs_dir=str(tmp_path / subdir / "obs"))

        _, s_full, h_full = run_trainer(cfg("full"), data)

        done = []

        def bomb(state, rec):
            done.append(1)
            if len(done) == 12:         # dies after completing round 11
                raise Killed

        ck = str(tmp_path / "kr" / "ck")
        kcfg = cfg("kr")
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
        s_r, h_r = t2.run(log=lambda m: None, checkpoint_path=ck,
                          resume=True)

        assert len(h_r) == len(h_full) == 20
        for a, b in zip(param_leaves(s_full), param_leaves(s_r)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h_full, h_r):
            assert det_view(ra) == det_view(rb)

        # the stitched two-segment stream replays clean: policy,
        # supervisor AND campaign records re-derive from the header
        records = read_records(str(tmp_path / "kr" / "obs" /
                                   "seg.jsonl"), validate=True)
        errors, stats = replay(records)
        assert not errors, errors
        assert stats["segments"] == 2, stats
        assert stats["campaign_records"] >= 2, stats
        s = summarize(records)
        assert s["segments"] == 2, s
        assert s["rounds_distinct"] == 20, s
        assert s["campaign_virtual_hours"] == 200.0, s
        assert s["availability_pct"] is not None, s

        # tampering one campaign window field is a replay divergence
        tampered = []
        for r in records:
            r = dict(r)
            if r.get("event") == "campaign" and r.get("round_index"):
                r["arrival_frac"] = round(r["arrival_frac"] + 0.01, 6)
            tampered.append(r)
        errors2, _ = replay(tampered)
        assert errors2 and "diverges" in errors2[0], errors2


# ----------------------------------------------------------------------
# campaign off is the literal seed path


class TestCampaignOff:
    def test_off_matches_no_campaign_construction(self, data):
        # campaign_spec="none" must be bit-identical to a config that
        # never heard of campaigns: same fast path, no campaign records
        t, s_off, h_off = run_trainer(small_cfg(), data)
        assert t.campaign is None
        assert not any(r.get("event") == "campaign"
                       for r in t.obs_recorder.memory)
