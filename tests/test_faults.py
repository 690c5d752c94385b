"""Fault-tolerance layer tests: fault-injection harness (train/faults.py),
robust aggregation (parallel/comm.py robust_federated_mean), and the
engine's update guards + quarantine.

Fast by construction: every engine run here uses the 2-block TinyNet at
K in {4, 8} on the virtual CPU mesh, one loop, and 1-4 comm rounds — the
whole module is part of the `-m 'not slow'` smoke path.
"""

import os

import jax
import jax.numpy as jnp
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
from federated_pytorch_test_tpu.parallel.comm import (
    make_robust_mean,
    robust_federated_mean,
)
from federated_pytorch_test_tpu.parallel.mesh import (
    CLIENT_AXIS,
    client_mesh,
    shard_map,
)
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FedAvg,
    FederatedConfig,
    FedProx,
)
from federated_pytorch_test_tpu.train.faults import (
    CORRUPT_MODES,
    FaultSpec,
    apply_corruption,
)

from jax.sharding import PartitionSpec as P

K = 4


class TinyNet(BlockModule):
    """Same 2-block toy CNN as tests/test_engine.py — small compiles."""

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


@pytest.fixture(scope="module")
def data8():
    return FederatedCifar10(K=8, batch=16, limit_per_client=64,
                            limit_test=64)


def small_cfg(**kw):
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=16,
                check_results=False, admm_rho0=0.1)
    base.update(kw)
    return FederatedConfig(**base)


def run_trainer(cfg, data, algo=None, L=1, **run_kw):
    t = BlockwiseFederatedTrainer(TinyNet(), cfg, data,
                                  algo or FedAvg())
    t.L = L
    return t, t.run(log=lambda m: None, **run_kw)


# ---------------------------------------------------------------------------
# spec grammar
# ---------------------------------------------------------------------------
class TestFaultSpecParse:
    @pytest.mark.parametrize("s", [None, "", "none", "  none "])
    def test_disabled_spellings(self, s):
        spec = FaultSpec.parse(s)
        assert not spec.enabled and not spec.masking

    def test_full_grammar(self):
        spec = FaultSpec.parse("drop=0.1,straggle=0.2,corrupt=0.3,"
                               "mode=signflip,scale=7,seed=9,clients=0+2")
        assert spec.drop == 0.1 and spec.straggle == 0.2
        assert spec.corrupt == 0.3 and spec.mode == "signflip"
        assert spec.scale == 7.0 and spec.seed == 9
        assert spec.clients == (0, 2)
        assert spec.enabled and spec.masking

    def test_corrupt_only_is_not_masking(self):
        spec = FaultSpec.parse("corrupt=1,mode=nan")
        assert spec.enabled and not spec.masking

    def test_delay_grammar(self):
        spec = FaultSpec.parse("delay=0.4,delay_max=3,seed=2")
        assert spec.delay == 0.4 and spec.delay_max == 3
        assert spec.enabled and spec.delaying and not spec.masking

    def test_delay_only_spec_is_enabled(self):
        # latency alone turns the harness on (needed for --async-rounds)
        # but injects no drop/straggle/corrupt faults
        spec = FaultSpec.parse("delay=0.2")
        assert spec.enabled
        rf = spec.round_faults(4, 0, 0, 0)
        assert not rf.drop.any() and not rf.corrupt.any()

    def test_new_corrupt_modes_parse(self):
        for mode in ("innerprod", "collude"):
            spec = FaultSpec.parse(f"corrupt=0.5,mode={mode},scale=3")
            assert spec.mode == mode and spec.scale == 3.0

    @pytest.mark.parametrize("bad", [
        "drop",                        # not key=value
        "drop=1.5",                    # probability out of range
        "mode=nan",                    # no probability named
        "corrupt=0.1,mode=weird",      # unknown mode
        "corrupt=0.1,clients=",        # empty client list
        "corrupt=0.1,clients=-1",      # negative index
        "frobnicate=1",                # unknown key
        "delay=1.0",                   # delay must stay below 1
        "delay=-0.1",                  # negative delay
        "delay=0.5,delay_max=-1",      # negative staleness cap
    ])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            FaultSpec.parse(bad)

    def test_clients_out_of_range_fails_at_draw(self):
        spec = FaultSpec.parse("corrupt=1,clients=9")
        with pytest.raises(ValueError, match="out of range"):
            spec.round_faults(4, 0, 0, 0)

    def test_churn_and_preempt_grammar(self):
        spec = FaultSpec.parse("join=0.2,leave=0.3,preempt=0.1,seed=3")
        assert spec.join == 0.2 and spec.leave == 0.3
        assert spec.preempt == 0.1
        assert spec.enabled and spec.churn_enabled and not spec.masking

    @pytest.mark.parametrize("bad", [
        "join=1.5",                    # probability out of range
        "leave=-0.1",
        "preempt=2",
    ])
    def test_churn_rejects(self, bad):
        with pytest.raises(ValueError):
            FaultSpec.parse(bad)


class TestChurnSchedule:
    def test_same_seed_same_ledger(self):
        a = FaultSpec.parse("join=0.4,leave=0.4,seed=11")
        b = FaultSpec.parse("join=0.4,leave=0.4,seed=11")
        ma = mb = np.ones(8, bool)
        for r in range(6):
            ma = a.round_churn(ma, 0, 0, r)
            mb = b.round_churn(mb, 0, 0, r)
            np.testing.assert_array_equal(ma, mb)

    def test_at_least_one_member_survives(self):
        # leave=1 empties the roster except the anchor (lowest-indexed
        # live client), which is immune by construction
        spec = FaultSpec.parse("leave=1,seed=0")
        m = np.ones(4, bool)
        for r in range(4):
            m = spec.round_churn(m, 0, 0, r)
            assert m.sum() >= 1
        np.testing.assert_array_equal(m, [True, False, False, False])

    def test_join_readmits_departed_clients(self):
        spec = FaultSpec.parse("join=1,seed=0")
        m = np.asarray([True, False, False, False])
        m = spec.round_churn(m, 0, 0, 0)
        assert m.all()

    def test_disabled_churn_is_identity(self):
        spec = FaultSpec.parse("drop=0.5,seed=1")
        m = np.asarray([True, False, True, False])
        out = spec.round_churn(m, 0, 0, 0)
        np.testing.assert_array_equal(out, m)

    def test_preempt_draw_deterministic(self):
        a = FaultSpec.parse("preempt=0.5,seed=9")
        b = FaultSpec.parse("preempt=0.5,seed=9")
        draws_a = [a.round_preempt(n, 0, r)
                   for n in range(3) for r in range(4)]
        draws_b = [b.round_preempt(n, 0, r)
                   for n in range(3) for r in range(4)]
        assert draws_a == draws_b
        assert any(draws_a) and not all(draws_a)

    def test_preempt_disabled_never_fires(self):
        spec = FaultSpec.parse("drop=0.5,seed=1")
        assert not any(spec.round_preempt(0, 0, r) for r in range(8))


class TestFaultSchedule:
    def test_same_seed_bit_identical(self):
        a = FaultSpec.parse("drop=0.3,straggle=0.3,corrupt=0.3,seed=4")
        b = FaultSpec.parse("drop=0.3,straggle=0.3,corrupt=0.3,seed=4")
        for coords in [(0, 0, 0), (2, 1, 3), (7, 0, 1)]:
            fa, fb = a.round_faults(8, *coords), b.round_faults(8, *coords)
            for xa, xb in zip(fa, fb):
                np.testing.assert_array_equal(xa, xb)

    def test_seed_and_round_vary_the_schedule(self):
        a = FaultSpec.parse("drop=0.5,seed=1")
        b = FaultSpec.parse("drop=0.5,seed=2")
        diff_seed = any(
            not np.array_equal(a.round_faults(8, n, 0, r).drop,
                               b.round_faults(8, n, 0, r).drop)
            for n in range(4) for r in range(4))
        diff_round = any(
            not np.array_equal(a.round_faults(8, 0, 0, 0).drop,
                               a.round_faults(8, 0, 0, r).drop)
            for r in range(1, 8))
        assert diff_seed and diff_round

    def test_precedence_drop_straggle_corrupt_disjoint(self):
        spec = FaultSpec(drop=1.0, straggle=1.0, corrupt=1.0)
        rf = spec.round_faults(8, 0, 0, 0)
        np.testing.assert_array_equal(rf.drop, np.ones(8, np.float32))
        np.testing.assert_array_equal(rf.straggle, np.zeros(8))
        np.testing.assert_array_equal(rf.corrupt, np.zeros(8))

    def test_clients_limits_eligibility(self):
        spec = FaultSpec(corrupt=1.0, clients=(1, 3))
        rf = spec.round_faults(6, 0, 0, 0)
        np.testing.assert_array_equal(
            rf.corrupt, np.asarray([0, 1, 0, 1, 0, 0], np.float32))

    def test_round_delays_deterministic_and_capped(self):
        a = FaultSpec(delay=0.6, delay_max=3, seed=5)
        b = FaultSpec(delay=0.6, delay_max=3, seed=5)
        seen = set()
        for coords in [(0, 0, 0), (1, 0, 2), (3, 1, 5)]:
            da, db = a.round_delays(8, *coords), b.round_delays(8, *coords)
            np.testing.assert_array_equal(da, db)
            assert da.dtype == np.int64
            assert da.min() >= 0 and da.max() <= 3
            seen.add(tuple(da))
        assert len(seen) > 1               # the draw varies per round

    def test_round_delays_zero_when_disabled(self):
        for spec in (FaultSpec(), FaultSpec(delay=0.5, delay_max=0)):
            np.testing.assert_array_equal(spec.round_delays(8, 0, 0, 0),
                                          np.zeros(8, np.int64))

    def test_delay_not_gated_by_clients(self):
        # latency is a network property, not an adversary property: the
        # clients= subset scopes corruption only, every client draws a delay
        spec = FaultSpec(delay=0.9, delay_max=4, clients=(0,), seed=1)
        hits = np.zeros(8, bool)
        for r in range(16):
            hits |= spec.round_delays(8, 0, 0, r) > 0
        assert hits[1:].any()


class TestApplyCorruption:
    def _delta(self):
        return jnp.asarray(np.arange(8, dtype=np.float32).reshape(4, 2) + 1)

    def test_modes(self):
        d = self._delta()
        c = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        nan = np.asarray(apply_corruption(d, c, "nan", 0.0))
        assert np.all(np.isnan(nan[[0, 2]]))
        inf = np.asarray(apply_corruption(d, c, "inf", 0.0))
        assert np.all(np.isinf(inf[[0, 2]]))
        sf = np.asarray(apply_corruption(d, c, "signflip", 0.0))
        np.testing.assert_array_equal(sf[[0, 2]], -np.asarray(d)[[0, 2]])
        sc = np.asarray(apply_corruption(d, c, "scale", 10.0))
        np.testing.assert_array_equal(sc[[0, 2]], 10 * np.asarray(d)[[0, 2]])

    @pytest.mark.parametrize("mode", CORRUPT_MODES)
    def test_untouched_rows_bit_identical(self, mode):
        d = self._delta()
        c = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        out = np.asarray(apply_corruption(d, c, mode, 100.0))
        np.testing.assert_array_equal(out[[1, 3]], np.asarray(d)[[1, 3]])

    def test_innerprod_flips_against_honest_mean(self):
        d = self._delta()
        c = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        out = np.asarray(apply_corruption(d, c, "innerprod", 2.0))
        honest = np.asarray(d)[[1, 3]].mean(axis=0)
        np.testing.assert_allclose(out[0], -2.0 * honest, rtol=1e-6)
        np.testing.assert_allclose(out[2], -2.0 * honest, rtol=1e-6)

    def test_collude_ships_one_shared_scaled_copy(self):
        d = self._delta()
        c = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        out = np.asarray(apply_corruption(d, c, "collude", 5.0))
        shared = 5.0 * np.asarray(d)[[0, 2]].mean(axis=0)
        np.testing.assert_allclose(out[0], shared, rtol=1e-6)
        np.testing.assert_array_equal(out[0], out[2])     # coordinated

    def test_directed_modes_respect_participation_weights(self):
        # an inactive honest client (w=0) must not contribute to the
        # innerprod target; an inactive colluder contributes nothing to
        # the shared copy
        d = self._delta()
        c = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        w = jnp.asarray([1.0, 1.0, 0.0, 0.0])
        out = np.asarray(apply_corruption(d, c, "innerprod", 1.0, w=w))
        np.testing.assert_allclose(out[0], -np.asarray(d)[1], rtol=1e-6)
        out = np.asarray(apply_corruption(d, c, "collude", 1.0, w=w))
        np.testing.assert_allclose(out[0], np.asarray(d)[0], rtol=1e-6)


# ---------------------------------------------------------------------------
# robust aggregation
# ---------------------------------------------------------------------------
def _run_robust(x, w, **kw):
    """Drive robust_federated_mean through the real shard_map collective."""
    mesh = client_mesh(4)
    fn = shard_map(
        lambda xs, ws: robust_federated_mean(xs, ws, **kw),
        mesh=mesh, in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
        out_specs=P(), check_vma=False)
    return np.asarray(jax.jit(fn)(jnp.asarray(x), jnp.asarray(w)))


def _ref_krum(x, w, trim_frac):
    """Closed-form multi-Krum: unweighted scores over active rows,
    lexicographic (score, index) ranking, weighted average of the
    selected m - f lowest-score rows."""
    K = x.shape[0]
    act = (w > 0) & np.isfinite(x).all(axis=1)
    m = int(act.sum())
    d2 = np.full((K, K), np.inf)
    for i in range(K):
        for j in range(K):
            if i != j and act[j]:
                d2[i, j] = float(np.sum((x[i] - x[j]) ** 2))
    f = int(np.floor(trim_frac * m))
    n_nb = max(m - f - 2, 1)
    score = np.array([np.sort(d2[i])[:n_nb].sum() if act[i] else np.inf
                      for i in range(K)])
    order = np.lexsort((np.arange(K), score))
    sel = order[:max(m - f, 1)]
    sel = sel[act[sel]]
    if sel.size == 0:
        return np.zeros(x.shape[1], x.dtype)
    ws = w[sel]
    return (x[sel] * ws[:, None]).sum(axis=0) / ws.sum()


def _ref_geomed(x, w, iters=16, eps=1e-8):
    """Closed-form Weiszfeld: same fixed iteration count, weighted mean
    start, eps-floored distances — mirrors GEOMED_ITERS exactly."""
    act = (w > 0) & np.isfinite(x).all(axis=1)
    wg = np.where(act, w, 0.0)
    safe = np.where(act[:, None], x, 0.0)
    den0 = wg.sum()
    v = (safe * wg[:, None]).sum(axis=0) / (den0 if den0 > 0 else 1.0)
    for _ in range(iters):
        r = np.sqrt(((safe - v[None, :]) ** 2).sum(axis=1))
        inv = wg / np.maximum(r, eps)
        den = inv.sum()
        v = (safe * inv[:, None]).sum(axis=0) / (den if den > 0 else 1.0)
    return v


class TestRobustMean:
    def setup_method(self, method):
        rng = np.random.default_rng(0)
        self.x = rng.normal(size=(8, 5)).astype(np.float32)
        self.w = np.ones(8, np.float32)

    def test_trim_matches_numpy(self):
        got = _run_robust(self.x, self.w, kind="trim", trim_frac=0.2)
        s = np.sort(self.x, axis=0)           # t = floor(0.2 * 8) = 1
        want = s[1:-1].mean(axis=0)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_trim_zero_frac_is_plain_mean(self):
        got = _run_robust(self.x, self.w, kind="trim", trim_frac=0.0)
        np.testing.assert_allclose(got, self.x.mean(axis=0), rtol=1e-5)

    def test_median_matches_numpy(self):
        got = _run_robust(self.x, self.w, kind="median")
        np.testing.assert_allclose(got, np.median(self.x, axis=0),
                                   rtol=1e-5)

    def test_median_odd_count_with_mask(self):
        w = self.w.copy()
        w[5] = 0.0                             # 7 active -> true element
        got = _run_robust(self.x, w, kind="median")
        want = np.median(self.x[w > 0], axis=0)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_clip_matches_numpy(self):
        x = self.x.copy()
        x[3] *= 50.0                           # magnitude attacker
        got = _run_robust(x, self.w, kind="clip", clip_mult=3.0)
        nrm = np.linalg.norm(x, axis=1)
        c = 3.0 * np.median(nrm)
        scl = np.minimum(1.0, c / nrm)
        want = (x * scl[:, None]).mean(axis=0)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        # and the attacker's pull really is bounded
        assert np.linalg.norm(got) < np.linalg.norm(x.mean(axis=0))

    def test_krum_matches_numpy(self):
        got = _run_robust(self.x, self.w, kind="krum", trim_frac=0.25)
        want = _ref_krum(self.x, self.w, 0.25)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_krum_zero_frac_selects_all(self):
        # f = 0: multi-Krum keeps every active row -> plain mean
        got = _run_robust(self.x, self.w, kind="krum", trim_frac=0.0)
        np.testing.assert_allclose(got, self.x.mean(axis=0), rtol=1e-5)

    def test_krum_weighted_and_masked(self):
        w = np.asarray([2, 1, 1, 0, 1, 1, 3, 1], np.float32)
        got = _run_robust(self.x, w, kind="krum", trim_frac=0.25)
        want = _ref_krum(self.x, w, 0.25)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_krum_excludes_colluding_pair(self):
        x = self.x.copy()
        x[0] = x[1] = 100.0 * self.x[:2].mean(axis=0)   # coordinated copies
        got = _run_robust(x, self.w, kind="krum", trim_frac=0.4)
        honest = self.x[2:].mean(axis=0)
        assert np.linalg.norm(got - honest) < 1.0
        np.testing.assert_allclose(got, _ref_krum(x, self.w, 0.4),
                                   rtol=1e-5)

    def test_geomed_matches_weiszfeld_reference(self):
        got = _run_robust(self.x, self.w, kind="geomed")
        np.testing.assert_allclose(got, _ref_geomed(self.x, self.w),
                                   rtol=1e-4, atol=1e-6)

    def test_geomed_weighted_and_masked(self):
        w = np.asarray([2, 1, 1, 0, 1, 1, 3, 1], np.float32)
        got = _run_robust(self.x, w, kind="geomed")
        np.testing.assert_allclose(got, _ref_geomed(self.x, w),
                                   rtol=1e-4, atol=1e-6)

    def test_geomed_resists_colluding_pair(self):
        x = self.x.copy()
        x[0] = x[1] = 100.0 * self.x[:2].mean(axis=0)
        got = _run_robust(x, self.w, kind="geomed")
        honest = self.x[2:].mean(axis=0)
        # the pair drags the plain mean far away; the geometric median
        # stays inside the honest cluster
        assert np.linalg.norm(got - honest) < 2.0
        assert np.linalg.norm(x.mean(axis=0) - honest) > 10.0

    def test_colluding_pair_degrades_trim_median_not_krum_geomed(self):
        """2-of-8 coordinated copies (the collude fault mode's wire
        pattern): the attack-induced shift — same estimator with and
        without the attack — is catastrophic for trim (one copy survives
        every t=1 coordinate window), a visible rank-displacement bias
        for median, and negligible for the selection/geometric
        estimators the attack cannot out-vote."""
        rng = np.random.default_rng(3)
        base = rng.normal(size=(8, 5)).astype(np.float32)
        x = base.copy()
        x[0] = x[1] = 100.0 * base[:2].mean(axis=0)
        tf = {"trim": 0.2, "median": 0.1, "krum": 0.4, "geomed": 0.1}
        shift = {
            k: np.linalg.norm(
                _run_robust(x, self.w, kind=k, trim_frac=tf[k])
                - _run_robust(base, self.w, kind=k, trim_frac=tf[k]))
            for k in tf}
        assert shift["krum"] < 0.05 and shift["geomed"] < 0.5
        assert shift["trim"] > 20.0
        assert shift["median"] > 2.0 * shift["geomed"]

    @pytest.mark.parametrize("kind", ["trim", "median", "clip", "krum",
                                      "geomed"])
    def test_nonfinite_rows_never_leak(self, kind):
        x = self.x.copy()
        x[2] = np.nan
        x[6] = np.inf
        got = _run_robust(x, self.w, kind=kind, trim_frac=0.1)
        assert np.all(np.isfinite(got))
        if kind == "median":                   # exact: median of the 6 honest
            want = np.median(x[[0, 1, 3, 4, 5, 7]], axis=0)
            np.testing.assert_allclose(got, want, rtol=1e-5)
        elif kind == "krum":
            np.testing.assert_allclose(got, _ref_krum(x, self.w, 0.1),
                                       rtol=1e-5)
        elif kind == "geomed":
            np.testing.assert_allclose(got, _ref_geomed(x, self.w),
                                       rtol=1e-4, atol=1e-6)

    def test_trim_defeats_one_byzantine_scaler(self):
        x = self.x.copy()
        x[0] *= 1e6
        got = _run_robust(x, self.w, kind="trim", trim_frac=0.2)
        honest = self.x[1:].mean(axis=0)
        # the corrupted coordinate lands in the trimmed tail everywhere
        assert np.linalg.norm(got - honest) < 1.0
        plain = x.mean(axis=0)
        assert np.linalg.norm(plain - honest) > 1e3

    def test_all_rejected_returns_zero(self):
        x = np.full((8, 5), np.nan, np.float32)
        for kind in ("trim", "median", "clip", "krum", "geomed"):
            got = _run_robust(x, self.w, kind=kind)
            np.testing.assert_array_equal(got, np.zeros(5, np.float32))

    def test_factory_validation(self):
        assert make_robust_mean("none") is None
        with pytest.raises(ValueError):
            make_robust_mean("bogus")
        with pytest.raises(ValueError):
            make_robust_mean("trim", trim_frac=0.5)
        with pytest.raises(ValueError):
            make_robust_mean("clip", clip_mult=0.0)

    def test_unknown_kind_error_lists_every_choice(self):
        # the message is derived from ROBUST_AGG_CHOICES, so the two new
        # estimators must appear in both the factory and the kernel error
        for raiser in (lambda: make_robust_mean("bogus"),
                       lambda: robust_federated_mean(
                           jnp.zeros((4, 3)), jnp.ones(4), kind="bogus")):
            with pytest.raises(ValueError) as ei:
                raiser()
            assert "krum" in str(ei.value) and "geomed" in str(ei.value)


# ---------------------------------------------------------------------------
# engine smoke: every algorithm x every fault class, one round each
# ---------------------------------------------------------------------------
ALGOS = [("fedavg", FedAvg), ("fedprox", FedProx),
         ("consensus", AdmmConsensus)]


class TestEngineFaultSmoke:
    @pytest.mark.parametrize("algo_name,algo_cls", ALGOS,
                             ids=[a for a, _ in ALGOS])
    def test_drop(self, data, algo_name, algo_cls):
        cfg = small_cfg(Nadmm=1, fault_spec="drop=1,clients=0")
        _, (state, hist) = run_trainer(cfg, data, algo_cls())
        rec = hist[0]
        assert rec["fault_dropped"] == 1 and rec["n_active"] == K - 1
        assert np.isfinite(rec["loss"])

    @pytest.mark.parametrize("algo_name,algo_cls", ALGOS,
                             ids=[a for a, _ in ALGOS])
    def test_straggle(self, data, algo_name, algo_cls):
        cfg = small_cfg(Nadmm=1, fault_spec="straggle=1,clients=0")
        t, (state, hist) = run_trainer(cfg, data, algo_cls())
        rec = hist[0]
        # a straggler withholds its local epochs but still joins the
        # exchange with round-start params
        assert rec["fault_straggled"] == 1 and rec["n_active"] == K
        if not t.algo.writeback:     # fedprox/admm: params stay round-start
            init = np.asarray(jax.tree.leaves(
                jax.device_get(t.init_state().params))[0])
            after = np.asarray(jax.tree.leaves(
                jax.device_get(state.params))[0])
            np.testing.assert_array_equal(after[0], init[0])
            assert not np.array_equal(after[1], init[1])

    @pytest.mark.parametrize("algo_name,algo_cls", ALGOS,
                             ids=[a for a, _ in ALGOS])
    @pytest.mark.parametrize("mode", CORRUPT_MODES)
    def test_corrupt_with_guard_stays_finite(self, data, algo_name,
                                             algo_cls, mode):
        cfg = small_cfg(Nadmm=1,
                        fault_spec=f"corrupt=1,mode={mode},clients=0",
                        update_guard=True)
        _, (state, hist) = run_trainer(cfg, data, algo_cls())
        rec = hist[0]
        assert np.isfinite(rec["loss"])
        assert np.isfinite(rec["dual_residual"])
        if mode in ("nan", "inf"):   # non-finite wire update MUST trip
            assert rec["guard_trips"] == 1 and rec["n_ok"] == K - 1
        for leaf in jax.tree.leaves(jax.device_get(state.params)):
            assert np.all(np.isfinite(leaf))


class TestEngineFaultDeterminism:
    def test_two_runs_identical_history(self, data):
        cfg = small_cfg(fault_spec="drop=0.4,straggle=0.3,corrupt=0.3,"
                        "mode=scale,scale=5,seed=3",
                        update_guard=True, robust_agg="trim",
                        trim_frac=0.25)
        _, (_, h1) = run_trainer(cfg, data, L=2)
        _, (_, h2) = run_trainer(cfg, data, L=2)
        assert len(h1) == len(h2)
        for a, b in zip(h1, h2):
            for k in ("loss", "dual_residual", "n_active", "guard_trips",
                      "fault_dropped", "fault_straggled",
                      "fault_corrupted", "quarantined"):
                assert a[k] == b[k], k

    def test_fault_spec_none_matches_plain_run(self, data):
        base = small_cfg(Nadmm=2)
        _, (_, h_plain) = run_trainer(base, data, L=2)
        _, (_, h_none) = run_trainer(small_cfg(Nadmm=2, fault_spec="none"),
                                     data, L=2)
        assert len(h_plain) == len(h_none)
        for a, b in zip(h_plain, h_none):
            assert set(a.keys()) == set(b.keys())
            assert a["loss"] == b["loss"]
            assert a["dual_residual"] == b["dual_residual"]
            # no fault/guard fields on the parity path
            for k in ("fault_dropped", "guard_trips", "quarantined",
                      "n_active", "n_ok"):
                assert k not in a and k not in b


# ---------------------------------------------------------------------------
# update guards + quarantine
# ---------------------------------------------------------------------------
class TestUpdateGuard:
    def test_quarantine_cadence(self, data):
        # client 0 corrupts EVERY round it participates: trips in round 0,
        # sits out round 1 (quarantined), returns and trips again in 2
        cfg = small_cfg(Nadmm=3,
                        fault_spec="corrupt=1,mode=nan,clients=0",
                        update_guard=True, quarantine_rounds=1)
        _, (_, hist) = run_trainer(cfg, data)
        assert [h["guard_trips"] for h in hist] == [1.0, 0.0, 1.0]
        assert [h["quarantined"] for h in hist] == [0, 1, 0]
        assert [h["n_active"] for h in hist] == [K, K - 1, K]
        assert all(np.isfinite(h["loss"]) for h in hist)

    def test_all_rejected_round_carries_z_over(self, data):
        # every client ships NaN: round must degrade gracefully, z (zeros
        # at block start) must survive, and training must continue
        cfg = small_cfg(Nadmm=2, fault_spec="corrupt=1,mode=nan",
                        update_guard=True, quarantine_rounds=0)
        t, (state, hist) = run_trainer(cfg, data)
        assert [h["guard_trips"] for h in hist] == [float(K)] * 2
        assert [h["n_ok"] for h in hist] == [0.0] * 2
        assert all(np.isfinite(h["loss"]) for h in hist)
        for leaf in jax.tree.leaves(jax.device_get(state.params)):
            assert np.all(np.isfinite(leaf))

    def test_guard_no_false_positives_on_clean_run(self, data):
        cfg = small_cfg(Nadmm=3, update_guard=True)
        t, (_, hist) = run_trainer(cfg, data)
        assert [h["guard_trips"] for h in hist] == [0.0] * 3
        assert [h["n_ok"] for h in hist] == [float(K)] * 3
        assert np.isfinite(t._guard_scale)        # calibrated by round 0

    def test_norm_bound_trips_scale_attack_after_calibration(self, data):
        # mine a seed whose schedule leaves client 0 clean in round 0 —
        # the calibration round — and corrupts it in round 1: a finite
        # but 1000x-scaled update must then exceed the z-relative norm
        # bound (guard_norm_mult x the honest round-0 delta scale)
        def clean_then_corrupt(s):
            spec = FaultSpec(corrupt=0.6, clients=(0,), seed=s)
            return (spec.round_faults(K, 0, 0, 0).corrupt[0] == 0
                    and spec.round_faults(K, 0, 0, 1).corrupt[0] == 1)

        seed = next(s for s in range(1000) if clean_then_corrupt(s))
        cfg = small_cfg(Nadmm=2,
                        fault_spec="corrupt=0.6,mode=scale,scale=1000,"
                        f"clients=0,seed={seed}",
                        update_guard=True, guard_norm_mult=10.0,
                        quarantine_rounds=0)
        _, (_, hist) = run_trainer(cfg, data)
        assert hist[0]["guard_trips"] == 0.0      # honest calibration round
        assert hist[1]["guard_trips"] == 1.0      # bounded: attacker caught

    def test_ef_residual_reset_on_quarantine(self, data):
        # NaN corruption poisons the EF residual (encode sees the poisoned
        # delta); the guard must reset the offender's residual so its
        # rejoin round cannot re-inject non-finite mass
        cfg = small_cfg(Nadmm=3, compress="topk", topk_frac=0.5,
                        error_feedback=True,
                        fault_spec="corrupt=1,mode=nan,clients=0",
                        update_guard=True, quarantine_rounds=1)
        t, (state, hist) = run_trainer(cfg, data)
        assert all(np.isfinite(h["loss"]) for h in hist)
        resid = np.asarray(jax.device_get(state.comp["resid"]))
        assert np.all(np.isfinite(resid))
        for leaf in jax.tree.leaves(jax.device_get(state.params)):
            assert np.all(np.isfinite(leaf))

    def test_guard_off_nan_propagates(self, data):
        # the counterfactual: same corruption, no guard, plain mean — the
        # NaN reaches z and (FedAvg write-back) every client
        cfg = small_cfg(Nadmm=2, fault_spec="corrupt=1,mode=nan,clients=0")
        t, (state, _) = run_trainer(cfg, data)
        x = np.concatenate([np.ravel(l) for l in jax.tree.leaves(
            jax.device_get(state.params))])
        assert not np.all(np.isfinite(x))


# ---------------------------------------------------------------------------
# adversarial convergence (ISSUE acceptance criterion)
# ---------------------------------------------------------------------------
class TestAdversarialConvergence:
    """1 of 8 clients Byzantine. trimmed/median aggregation must land
    within 5% of the clean plain-mean baseline's final loss; the plain
    mean with guards off must visibly diverge (scale) or go non-finite
    (NaN)."""

    def _final_loss(self, data8, **kw):
        cfg = FederatedConfig(K=8, Nloop=1, Nepoch=2, Nadmm=4,
                              default_batch=16, check_results=False,
                              admm_rho0=0.1, **kw)
        _, (_, hist) = run_trainer(cfg, data8)
        return hist[-1]["loss"]

    @pytest.fixture(scope="class")
    def clean_loss(self, data8):
        return self._final_loss(data8)

    @pytest.mark.parametrize("agg", ["trim", "median"])
    @pytest.mark.parametrize("attack", ["mode=nan",
                                        "mode=scale,scale=100"])
    def test_robust_agg_tracks_clean_baseline(self, data8, clean_loss,
                                              agg, attack):
        loss = self._final_loss(
            data8, fault_spec=f"corrupt=1,clients=0,{attack}",
            robust_agg=agg, trim_frac=0.2)
        assert np.isfinite(loss)
        assert abs(loss - clean_loss) / clean_loss < 0.05

    def test_plain_mean_goes_nonfinite_under_nan(self, data8):
        loss = self._final_loss(data8,
                                fault_spec="corrupt=1,clients=0,mode=nan")
        assert not np.isfinite(loss)

    def test_plain_mean_diverges_under_scaling(self, data8, clean_loss):
        loss = self._final_loss(
            data8, fault_spec="corrupt=1,clients=0,mode=scale,scale=100")
        # the 100x client drags z far off every round; the honest clients'
        # loss blows up well past the robust-agg tolerance band
        assert not np.isfinite(loss) or loss > 1.5 * clean_loss


class TestColludingAsyncAdversary:
    """ISSUE 6 acceptance: under a seeded 2-of-8 colluding scale attack
    with ``delay=`` stragglers active (``--async-rounds`` buffered
    aggregation, staleness-weighted mixing), krum/geomed converge within
    5% of the clean async baseline while the plain mean diverges — and
    trim (t=1 < 2 colluders) visibly degrades, which is exactly why the
    selection/geometric estimators exist."""

    DELAY = "delay=0.3,delay_max=2,seed=11"
    ATTACK = "corrupt=1,clients=0+1,mode=collude,scale=100," + DELAY

    def _final_loss(self, data8, **kw):
        cfg = FederatedConfig(K=8, Nloop=1, Nepoch=2, Nadmm=4,
                              default_batch=16, check_results=False,
                              admm_rho0=0.1, async_rounds=True,
                              max_staleness=4, **kw)
        _, (_, hist) = run_trainer(cfg, data8)
        return hist[-1]["loss"]

    @pytest.fixture(scope="class")
    def clean_async_loss(self, data8):
        return self._final_loss(data8, fault_spec=self.DELAY)

    @pytest.mark.asyncfl
    @pytest.mark.parametrize("agg,frac", [("krum", 0.4), ("geomed", 0.1)])
    def test_krum_geomed_track_clean_baseline(self, data8,
                                              clean_async_loss, agg, frac):
        loss = self._final_loss(data8, fault_spec=self.ATTACK,
                                robust_agg=agg, trim_frac=frac)
        assert np.isfinite(loss)
        assert abs(loss - clean_async_loss) / clean_async_loss < 0.05

    @pytest.mark.asyncfl
    def test_plain_mean_diverges(self, data8, clean_async_loss):
        loss = self._final_loss(data8, fault_spec=self.ATTACK)
        assert not np.isfinite(loss) or loss > 1.5 * clean_async_loss

    @pytest.mark.asyncfl
    def test_trim_degrades_under_collusion(self, data8, clean_async_loss):
        # one coordinated copy survives every trimmed coordinate window
        loss = self._final_loss(data8, fault_spec=self.ATTACK,
                                robust_agg="trim", trim_frac=0.2)
        assert not np.isfinite(loss) or loss > 1.5 * clean_async_loss


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------
class TestValidation:
    def test_bb_update_incompatible(self, data):
        cfg = small_cfg(bb_update=True, fault_spec="drop=0.5")
        with pytest.raises(ValueError, match="bb_update"):
            BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())

    def test_bad_robust_agg(self, data):
        with pytest.raises(ValueError, match="robust"):
            BlockwiseFederatedTrainer(TinyNet(), small_cfg(robust_agg="avg"),
                                      data, FedAvg())

    def test_bad_async_knobs(self, data):
        with pytest.raises(ValueError, match="max_staleness"):
            BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(async_rounds=True, max_staleness=-1),
                data, FedAvg())
        with pytest.raises(ValueError, match="staleness_alpha"):
            BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(async_rounds=True,
                                     staleness_alpha=-0.5), data, FedAvg())
        with pytest.raises(ValueError, match="bb_update"):
            BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(async_rounds=True, bb_update=True),
                data, AdmmConsensus())

    def test_bad_guard_knobs(self, data):
        with pytest.raises(ValueError, match="quarantine_rounds"):
            BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(update_guard=True,
                                     quarantine_rounds=-1), data, FedAvg())
        with pytest.raises(ValueError, match="guard_norm_mult"):
            BlockwiseFederatedTrainer(
                TinyNet(), small_cfg(update_guard=True,
                                     guard_norm_mult=0.0), data, FedAvg())


# ---------------------------------------------------------------------------
# engine parity: the one round kernel on VAE and CPC (ISSUE 15)
# ---------------------------------------------------------------------------


def run_vae(data, L=1, **cfg_kw):
    from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN
    from federated_pytorch_test_tpu.train.vae_engine import VAETrainer

    base = dict(K=8, Nloop=1, Nepoch=1, Nadmm=3, default_batch=16,
                check_results=False, admm_rho0=0.1)
    base.update(cfg_kw)
    t = VAETrainer(AutoEncoderCNN(), FederatedConfig(**base), data, FedAvg())
    t.L = L
    return t, t.run(log=lambda m: None)


class TestVAEKernelParity:
    """The classifier's guard/quarantine and Byzantine-survival
    contracts verbatim on the VAE engine — same kernel, same knobs,
    same cadence and tolerance band."""

    def test_quarantine_cadence(self, data8):
        _, (_, hist) = run_vae(data8,
                               fault_spec="corrupt=1,mode=nan,clients=0",
                               update_guard=True, quarantine_rounds=1)
        assert [h["guard_trips"] for h in hist] == [1.0, 0.0, 1.0]
        assert [h["quarantined"] for h in hist] == [0, 1, 0]
        assert [h["n_active"] for h in hist] == [8, 7, 8]
        assert all(np.isfinite(h["loss"]) for h in hist)

    DELAY = "delay=0.3,delay_max=2,seed=11"
    ATTACK = "corrupt=1,clients=0,mode=nan," + DELAY

    @pytest.fixture(scope="class")
    def clean_vae_loss(self, data8):
        _, (_, hist) = run_vae(data8, fault_spec=self.DELAY,
                               async_rounds=True, max_staleness=4)
        return hist[-1]["loss"]

    # slow: the clean-baseline fixture plus two aggregator runs cost
    # ~2 minutes of VAE training; test_quarantine_cadence above keeps a
    # fast VAE-kernel representative in the tier-1 run
    @pytest.mark.slow
    @pytest.mark.parametrize("agg,frac", [("median", 0.2), ("krum", 0.4)])
    def test_byzantine_nan_tracks_clean_baseline(self, data8,
                                                 clean_vae_loss, agg, frac):
        # the ISSUE 15 acceptance shape: 1-of-8 Byzantine NaN client
        # under delay stragglers (buffered-async admission), no guard —
        # the robust aggregator alone must keep the run finite and
        # within 5% of the clean async baseline
        _, (_, hist) = run_vae(data8, fault_spec=self.ATTACK,
                               async_rounds=True, max_staleness=4,
                               robust_agg=agg, trim_frac=frac)
        loss = hist[-1]["loss"]
        assert np.isfinite(loss)
        assert abs(loss - clean_vae_loss) / clean_vae_loss < 0.05


def run_cpc(src, Nadmm=1, run_kw=None, **cfg_kw):
    from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

    t = CPCTrainer(src, latent_dim=8, reduced_dim=4, lbfgs_history=3,
                   lbfgs_max_iter=1, Niter=1,
                   cfg=FederatedConfig(check_results=False, **cfg_kw))
    kw = dict(log=lambda m: None)
    kw.update(run_kw or {})
    return t, t.run(Nloop=1, Nadmm=Nadmm, **kw)


@pytest.fixture(scope="module")
def cpc_chaos(tmp_path_factory):
    """Seeded corrupt=nan CPC run: client 1 ships NaN every round it is
    admitted; guard + quarantine on, JSONL + memory sinks recording."""
    from federated_pytorch_test_tpu.data.lofar import CPCDataSource

    d = tmp_path_factory.mktemp("cpc_chaos")
    src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2, seed=7)
    t, (state, hist) = run_cpc(
        src, Nadmm=3,
        fault_spec="corrupt=1,mode=nan,clients=1,seed=7",
        update_guard=True, quarantine_rounds=1,
        run_kw=dict(obs_dir=str(d), obs_sinks="jsonl,memory"))
    jsonls = [os.path.join(d, f) for f in os.listdir(d)
              if f.endswith(".jsonl")]
    assert len(jsonls) == 1
    return t, state, hist, jsonls[0]


class TestCPCKernelParity:
    """Guard cadence, client-grain attribution, and async kill/resume
    ledger exactness on the CPC rotation — the knobs that were
    classifier-only before the round kernel."""

    def test_quarantine_cadence(self, cpc_chaos):
        # encoder block 0 runs Nadmm=3 rounds first: client 1 trips in
        # round 0, sits out round 1 (quarantined), returns and trips in
        # round 2 — the classifier cadence verbatim
        _, _, hist, _ = cpc_chaos
        assert [h["guard_trips"] for h in hist[:3]] == [1.0, 0.0, 1.0]
        assert [h["quarantined"] for h in hist[:3]] == [0, 1, 0]
        assert [h["n_active"] for h in hist[:3]] == [2, 1, 2]
        assert all(np.isfinite(h["loss"]) for h in hist)

    def test_client_records_name_the_corrupt_client(self, cpc_chaos):
        from federated_pytorch_test_tpu.obs.clients import (
            ledger_from_records,
        )
        from federated_pytorch_test_tpu.obs.report import read_records

        t, _, hist, path = cpc_chaos
        crecs = [r for r in t.obs_recorder.memory if r["event"] == "client"]
        assert len(crecs) == len(hist) > 0
        led = ledger_from_records(read_records(path))
        assert led.ranking()[0]["client"] == 1

    def test_cli_expect_top_gate_on_cpc_stream(self, cpc_chaos, capsys):
        from federated_pytorch_test_tpu.obs.clients import (
            main as clients_main,
        )

        _, _, _, path = cpc_chaos
        assert clients_main([path, "--expect-top", "1"]) == 0
        assert clients_main([path, "--expect-top", "0"]) == 2
        capsys.readouterr()

    # slow: three full CPC runs (uninterrupted, killed, resumed) cost
    # ~100 s; the cpc_chaos fixture trio above keeps the fast CPC-kernel
    # representatives in the tier-1 run, and tests/test_serve.py's
    # kill/resume case covers the checkpoint path every tier-1 run
    @pytest.mark.slow
    def test_async_kill_resume_ledger_exact(self, tmp_path):
        # --async-rounds with delay stragglers, guard + quarantine and a
        # median aggregator: interrupting mid-block and resuming must
        # reproduce the uninterrupted history EXACTLY — staleness
        # weights, fault counters, quarantine ticks and client-ledger
        # fields included (only wall-clock *_seconds and per-process
        # compile-cache attribution stripped, as in tests/test_resume.py)
        from federated_pytorch_test_tpu.data.lofar import CPCDataSource

        def make_src():
            return CPCDataSource(["a.h5", "b.h5"], ["0", "1"],
                                 batch_size=2, seed=7)

        kw = dict(fault_spec="corrupt=0.5,clients=0,mode=scale,scale=9,"
                             "delay=0.4,delay_max=2,seed=13",
                  async_rounds=True, max_staleness=3,
                  update_guard=True, quarantine_rounds=1,
                  robust_agg="median")
        strip = lambda h: [
            {k: v for k, v in r.items()
             if not k.endswith("_seconds")
                and not k.startswith("dispatch_")} for r in h]
        _, (_, want) = run_cpc(make_src(), Nadmm=2, **kw)
        ck = str(tmp_path / "cpc_async_ck")

        class Stop(Exception):
            pass

        calls = []

        def bomb(msg):
            calls.append(msg)
            if len(calls) == 3:
                raise Stop

        with pytest.raises(Stop):
            run_cpc(make_src(), Nadmm=2,
                    run_kw=dict(log=bomb, checkpoint_path=ck), **kw)
        _, (_, got) = run_cpc(make_src(), Nadmm=2,
                              run_kw=dict(checkpoint_path=ck, resume=True),
                              **kw)
        assert strip(got) == strip(want)


@pytest.mark.slow
class TestCPCAdversarialConvergence:
    """ISSUE 15 acceptance: 1-of-8 Byzantine NaN client under delay
    stragglers (buffered-async admission) survives via krum/median
    within 5% of the clean async baseline on the CPC engine, while the
    plain mean goes non-finite."""

    DELAY = "delay=0.3,delay_max=2,seed=11"
    ATTACK = "corrupt=1,clients=0,mode=nan," + DELAY

    @pytest.fixture(scope="class")
    def cpc_src8(self):
        from federated_pytorch_test_tpu.data.lofar import CPCDataSource

        return CPCDataSource([f"{c}.h5" for c in "abcdefgh"],
                             [str(i % 2) for i in range(8)],
                             batch_size=2, seed=7)

    def _final_loss(self, src, **kw):
        _, (_, hist) = run_cpc(src, Nadmm=2, async_rounds=True,
                               max_staleness=4, **kw)
        return hist[-1]["loss"]

    @pytest.fixture(scope="class")
    def clean_async_loss(self, cpc_src8):
        return self._final_loss(cpc_src8, fault_spec=self.DELAY)

    @pytest.mark.parametrize("agg,frac", [("median", 0.2), ("krum", 0.4)])
    def test_byzantine_nan_tracks_clean_baseline(self, cpc_src8,
                                                 clean_async_loss,
                                                 agg, frac):
        loss = self._final_loss(cpc_src8, fault_spec=self.ATTACK,
                                robust_agg=agg, trim_frac=frac)
        assert np.isfinite(loss)
        assert abs(loss - clean_async_loss) / clean_async_loss < 0.05

    def test_plain_mean_goes_nonfinite(self, cpc_src8):
        loss = self._final_loss(cpc_src8, fault_spec=self.ATTACK)
        assert not np.isfinite(loss)
