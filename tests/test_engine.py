"""Engine + algorithm tests on the virtual CPU client mesh.

These exercise the real shard_map/psum path over 4 of the 8 virtual devices
(SURVEY.md section 4's distributed-test strategy).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import BlockModule, elu, flatten, max_pool_2x2, pairs
from federated_pytorch_test_tpu.parallel.mesh import client_mesh
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FedAvg,
    FederatedConfig,
    FedProx,
    NoConsensus,
)
from federated_pytorch_test_tpu.utils import codec

K = 4


class TinyNet(BlockModule):
    """2-block toy CNN — keeps per-test XLA compiles small while exercising
    the full blockwise machinery (masking, codec, collectives)."""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2), name="conv1")(x)))
        x = flatten(x)
        return nn.Dense(10, name="fc1")(x)

    def param_order(self):
        return pairs("conv1", "fc1")

    def train_order_block_ids(self):
        return [[0, 1], [2, 3]]

    def linear_layer_ids(self):
        return [1]  # block 1 (fc) gets L1/L2 — exercises the reg path


def Net():  # the engine tests only need TinyNet's speed
    return TinyNet()


@pytest.fixture(scope="module")
def data():
    return FederatedCifar10(K=K, batch=16, limit_per_client=32, limit_test=32)


def small_cfg(**kw):
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=16,
                check_results=False, admm_rho0=0.1)
    base.update(kw)
    return FederatedConfig(**base)


def client_param_stacks(trainer, state, ci):
    """Flat active-block vectors per client, gathered to host [K, N]."""
    mask = trainer.mask_for_block(ci)
    params = jax.device_get(state.params)
    outs = []
    for k in range(K):
        p_k = jax.tree.map(lambda x: x[k], params)
        outs.append(np.asarray(codec.get_trainable_values(p_k, trainer.order, mask)))
    return np.stack(outs)


class TestFedAvg:
    def test_writeback_makes_clients_identical_on_block(self, data):
        cfg = small_cfg()
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg())
        state, hist = t.run(log=lambda m: None)
        # after the last round of the last block (ci = L-1) all clients hold z
        x = client_param_stacks(t, state, t.L - 1)
        np.testing.assert_allclose(x[0], x[1], rtol=1e-5)
        np.testing.assert_allclose(x[0], x[3], rtol=1e-5)
        assert all("dual_residual" in h for h in hist)

    def test_inactive_block_frozen(self, data):
        # sweep ONLY block 0: block 1's params must remain bit-identical to
        # the common init (masked grads => exact zero updates for frozen
        # leaves, the jit analogue of requires_grad freezing,
        # simple_utils.py:34-45)
        cfg = small_cfg()
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg())
        t.L = 1  # truncate the sweep to the first block
        init = t.init_state()
        x_before = client_param_stacks(t, init, 1)
        state, _ = t.run(log=lambda m: None)
        x_after = client_param_stacks(t, state, 1)
        np.testing.assert_array_equal(x_before, x_after)
        # ...while block 0 did change
        assert not np.allclose(client_param_stacks(t, init, 0),
                               client_param_stacks(t, state, 0))


class TestFedProx:
    def test_no_writeback_clients_stay_distinct(self, data):
        cfg = small_cfg()
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedProx())
        state, hist = t.run(log=lambda m: None)
        x = client_param_stacks(t, state, t.L - 1)
        # different data shards => different local params (no z write-back)
        assert not np.allclose(x[0], x[1])
        assert all("primal_residual" in h for h in hist)


class TestAdmm:
    def test_dual_state_and_residuals(self, data):
        cfg = small_cfg()
        t = BlockwiseFederatedTrainer(Net(), cfg, data, AdmmConsensus())
        state, hist = t.run(log=lambda m: None)
        assert all("primal_residual" in h and "dual_residual" in h for h in hist)
        # residuals are finite and decreasing within a block's rounds
        assert all(np.isfinite(h["dual_residual"]) for h in hist)

    def test_bb_update_runs_and_keeps_rho_bounded(self, data):
        cfg = small_cfg(Nadmm=3, bb_update=True)
        t = BlockwiseFederatedTrainer(Net(), cfg, data, AdmmConsensus())
        state, hist = t.run(log=lambda m: None)
        for h in hist:
            assert 0 < h["rho"] <= max(cfg.bb_rhomax, cfg.admm_rho0) + 1e-6


class TestAlgorithmAlgebra:
    """Collective algebra checked against closed-form numpy on a tiny mesh."""

    def _run_global(self, algo, x, z, y, rho):
        from federated_pytorch_test_tpu.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = client_mesh(2)

        def f(x, z, y, rho):
            return algo.global_update(x, z, y, rho, K=x.shape[0] * 2)

        # note: inside shard_map each device sees K/2 rows
        fn = shard_map(
            lambda x, z, y, rho: f(x, z, y, rho),
            mesh=mesh,
            in_specs=(P("clients"), P(), P("clients"), P()),
            out_specs=(P(), P("clients"), {k: P() for k in self._diag_keys(algo)}),
            check_vma=False,
        )
        return fn(x, z, y, rho)

    @staticmethod
    def _diag_keys(algo):
        if isinstance(algo, FedAvg):
            return ["dual_residual"]
        return ["primal_residual", "dual_residual"]

    def test_fedavg_mean(self):
        x = jnp.arange(16, dtype=jnp.float32).reshape(4, 4)
        z = jnp.zeros(4)
        y = jnp.zeros((4, 1))
        z_new, _, diag = self._run_global(FedAvg(), x, z, y, jnp.float32(1.0))
        np.testing.assert_allclose(np.asarray(z_new), np.asarray(x).mean(0), rtol=1e-6)
        np.testing.assert_allclose(
            float(diag["dual_residual"]),
            np.linalg.norm(np.asarray(x).mean(0)) / 4, rtol=1e-5)

    def test_admm_z_and_dual_update(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
        z = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
        rho = jnp.float32(0.3)
        z_new, y_new, diag = self._run_global(AdmmConsensus(), x, z, y, rho)
        xe, ye, ze = map(np.asarray, (x, y, z))
        z_exp = (ye + 0.3 * xe).sum(0) / (4 * 0.3)       # consensus_multi.py:281-285
        y_exp = ye + 0.3 * (xe - z_exp)                  # :291-297
        np.testing.assert_allclose(np.asarray(z_new), z_exp, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y_new), y_exp, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(float(diag["dual_residual"]),
                                   np.linalg.norm(ze - z_exp) / 6, rtol=1e-5)
        np.testing.assert_allclose(
            float(diag["primal_residual"]),
            sum(np.linalg.norm(0.3 * (xe[k] - z_exp)) for k in range(4)) / 6,
            rtol=1e-5)

    def test_fedprox_matches_plain_mean(self):
        x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 5)), jnp.float32)
        z = jnp.zeros(5)
        y = jnp.zeros((4, 1))
        z_new, y_new, _ = self._run_global(FedProx(), x, z, y, jnp.float32(1.0))
        np.testing.assert_allclose(np.asarray(z_new), np.asarray(x).mean(0), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(y_new), np.asarray(y))  # untouched


class TestIndependent:
    def test_runs_and_reports(self, data):
        cfg = FederatedConfig(K=K, Nepoch=1, default_batch=16,
                              check_results=True)
        t = BlockwiseFederatedTrainer(Net(), cfg, data, NoConsensus())
        state, hist = t.run_independent(log=lambda m: None)
        assert len(hist) == 1
        assert hist[0]["accuracy"].shape == (K,)


class TestLbfgsLocalOptimizer:
    def test_fedavg_with_lbfgs(self, data):
        cfg = small_cfg(Nadmm=1, optimizer="lbfgs", lbfgs_history_size=5,
                        lbfgs_max_iter=2)
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg())
        state, hist = t.run(log=lambda m: None)
        assert all(np.isfinite(h["dual_residual"]) for h in hist)
        assert all(np.isfinite(h["loss"]) for h in hist)


class TestCommonInit:
    def test_all_clients_start_identical(self, data):
        t = BlockwiseFederatedTrainer(Net(), small_cfg(), data, FedAvg())
        p = jax.device_get(t.params0)
        flat = jax.tree.leaves(p)
        for leaf in flat:
            for k in range(1, K):
                np.testing.assert_array_equal(leaf[0], leaf[k])


class TestTracing:
    """SURVEY.md section 5 tracing/profiling subsystem."""

    def test_round_seconds_recorded(self, data):
        cfg = small_cfg()
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg())
        t.L = 1
        _, hist = t.run(log=lambda m: None)
        assert all(h["round_seconds"] > 0 for h in hist)

    def test_profile_trace_written(self, data, tmp_path):
        cfg = small_cfg(profile_dir=str(tmp_path / "trace"))
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg())
        t.L = 1
        t.run(log=lambda m: None)
        # jax.profiler.trace writes plugins/profile/<ts>/*.xplane.pb
        hits = list((tmp_path / "trace").rglob("*.xplane.pb"))
        assert hits, "no xplane trace written"


class TestMeshInvariance:
    @pytest.mark.slow   # three mesh shapes = three fresh compiles of
    #                     every block program
    def test_history_invariant_to_device_count(self, data):
        """K=4 clients packed onto 4, 2, or 1 device(s) must train
        identically (up to float reduction order): the vmap-over-local-
        clients grouping plus the psum over fewer devices is the same
        federated math (SURVEY.md section 7 decision 1 — K_local = K/D
        clients per device when K exceeds the device count)."""
        def run(nd):
            cfg = small_cfg(num_devices=nd, check_results=True)
            t = BlockwiseFederatedTrainer(Net(), cfg, data, AdmmConsensus())
            assert t.K_local == K // nd
            _, hist = t.run(log=lambda m: None)
            return hist

        h4 = run(4)
        for other in (run(2), run(1)):
            assert len(other) == len(h4)
            for a, b in zip(h4, other):
                np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-4)
                np.testing.assert_allclose(a["dual_residual"],
                                           b["dual_residual"], rtol=1e-3,
                                           atol=1e-7)
                # argmax counts over 32 test samples: allow one near-tie
                # logit flip under the different reduction order
                np.testing.assert_allclose(a["accuracy"], b["accuracy"],
                                           atol=100.0 / 32 + 1e-6)


class TestEpochPrefetch:
    def test_prefetch_matches_direct_trajectory(self, data):
        """Epoch data is a pure function of (cfg.seed, counter), so runs
        with the staging worker thread on and off must be bit-identical
        (engine._stage_epoch).  device_data=False pins the HOST staging
        path — device mode has no worker thread."""
        # advisory fields name the machine's path (which dispatch was the
        # slowest: ``dispatch_max_site``), not the trajectory
        from federated_pytorch_test_tpu.obs.schema import ADVISORY_FIELDS
        strip = lambda h: [{k: v for k, v in r.items()
                            if not k.endswith("seconds")
                            and k not in ADVISORY_FIELDS} for r in h]

        def run(prefetch):
            t = BlockwiseFederatedTrainer(
                Net(), small_cfg(Nepoch=2, device_data=False), data,
                AdmmConsensus())
            assert t._dev_gather is None
            t._prefetch_epochs = prefetch
            _, hist = t.run(log=lambda m: None)
            return strip(hist)

        assert run(True) == run(False)

    def test_epoch_seeds_differ_across_counter_and_stream(self, data):
        t = BlockwiseFederatedTrainer(Net(), small_cfg(), data, FedAvg())
        assert t._epoch_seed(0, 0) != t._epoch_seed(1, 0)
        assert t._epoch_seed(0, 0) != t._epoch_seed(0, 1)
        assert t._epoch_seed(3, 0) == t._epoch_seed(3, 0)

    def test_no_trailing_prefetch_after_run(self, data):
        """The run's final epoch must not queue a never-consumed build
        (its dataset-sized result would stay pinned on the trainer)."""
        t = BlockwiseFederatedTrainer(Net(),
                                      small_cfg(device_data=False), data,
                                      AdmmConsensus())
        t.run(log=lambda m: None)
        assert t._pending is None


class TestDeviceResidentData:
    """Device-resident epoch staging (engine._setup_device_data): the raw
    uint8 shards live in HBM and each epoch is an on-device permutation
    gather — no per-epoch host shuffle / H2D copy.  Auto-on for small
    datasets; the host path stays available via device_data=False."""

    @pytest.fixture(scope="class")
    def rdata(self):
        # limit 24 with batch 16 -> steps=2 with an 8-row remainder batch
        return FederatedCifar10(K=K, batch=16, limit_per_client=24,
                                limit_test=16)

    def test_auto_enables_for_small_data(self, rdata):
        t = BlockwiseFederatedTrainer(Net(), small_cfg(), rdata,
                                      AdmmConsensus())
        assert t._dev_gather is not None

    def test_epoch_covers_shard_with_wrap_pad_and_weights(self, rdata):
        t = BlockwiseFederatedTrainer(Net(), small_cfg(), rdata,
                                      AdmmConsensus())
        xb, yb, wb = t._stage_epoch()
        assert xb.dtype == jnp.uint8
        xb, yb, wb = (np.asarray(v) for v in (xb, yb, wb))
        xt, yt = rdata.train_shards_raw()
        n = rdata.samples_per_client
        for ck in range(K):
            flat_y = yb[ck].reshape(-1)
            # real rows = a permutation of the client's shard labels
            assert sorted(flat_y[:n].tolist()) == sorted(yt[ck].tolist())
            # image rows stay paired with their labels through the gather
            flat_x = xb[ck].reshape(-1, 32, 32, 3)
            for r in (0, n // 2, n - 1):
                hit = (xt[ck] == flat_x[r]).all(axis=(1, 2, 3))
                assert hit.any() and yt[ck][hit.argmax()] == flat_y[r]
            # pad rows of the remainder batch carry weight 0
            assert wb[ck, :-1].all()
            assert wb[ck, -1, : rdata.remainder].all()
            assert not wb[ck, -1, rdata.remainder:].any()

    def test_counter_keyed_determinism(self, rdata):
        def epoch0():
            t = BlockwiseFederatedTrainer(Net(), small_cfg(), rdata,
                                          AdmmConsensus())
            return np.asarray(t._stage_epoch()[1])

        np.testing.assert_array_equal(epoch0(), epoch0())

    def test_trains_equivalently_to_host_staging(self, rdata):
        """Same engine, same algorithm — the two staging paths draw
        different permutations (jax vs numpy RNG) but must both train to
        finite residuals with identical record structure."""
        hists = {}
        for dev in (True, False):
            t = BlockwiseFederatedTrainer(
                Net(), small_cfg(device_data=dev), rdata, AdmmConsensus())
            assert (t._dev_gather is not None) == dev
            _, hist = t.run(log=lambda m: None)
            hists[dev] = hist
        assert len(hists[True]) == len(hists[False])
        for a, b in zip(hists[True], hists[False]):
            assert a.keys() == b.keys()
            assert np.isfinite(a["loss"]) and np.isfinite(a["dual_residual"])


class TestPartialParticipation:
    """cfg.participation < 1: per-round Bernoulli client sampling — the
    FedProx paper's motivating regime, cited but never implemented by the
    reference (README.md:17; SURVEY.md section 5 'partial participation is
    not implemented').  Inactive clients neither train nor exchange:
    params/opt state/duals stay bit-untouched until next sampled."""

    def _mask(self, trainer, nloop, ci, nadmm):
        return np.asarray(jax.device_get(
            trainer._round_mask(nloop, ci, nadmm)))

    def test_full_participation_uses_ones_and_old_signature_results(
            self, data):
        t = BlockwiseFederatedTrainer(Net(), small_cfg(), data,
                                      AdmmConsensus())
        assert t._round_mask(0, 0, 0) is t._ones_mask

    def test_mask_is_stateless_and_guarantees_one_active(self, data):
        cfg = small_cfg(participation=0.25)
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg())
        m1 = self._mask(t, 1, 0, 2)
        m2 = self._mask(t, 1, 0, 2)
        np.testing.assert_array_equal(m1, m2)      # resume redraws same
        masks = [self._mask(t, nl, 0, na)
                 for nl in range(4) for na in range(4)]
        assert all(m.sum() >= 1 for m in masks)
        assert any(m.sum() < K for m in masks)     # sampling really thins
        # tiny probability: the >=1 guarantee must kick in
        t2 = BlockwiseFederatedTrainer(
            Net(), small_cfg(participation=1e-9), data, FedAvg())
        assert all(self._mask(t2, nl, 0, 0).sum() == 1 for nl in range(6))

    def test_inactive_clients_bit_untouched_fedavg(self, data):
        cfg = small_cfg(participation=0.5, Nadmm=1, seed=3)
        t = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg())
        t.L = 1                  # exactly one communication round
        active = self._mask(t, 0, 0, 0)
        assert 0 < active.sum() < K, "seed must give a mixed round"
        before = client_param_stacks(t, t.init_state(), 0)
        seen = {}
        t.run(log=lambda m: None,
              on_round=lambda s, r: seen.update(r=r, s=s))
        after = client_param_stacks(t, seen["s"], 0)
        for k in range(K):
            if active[k]:          # participants end the round holding z
                assert not np.allclose(after[k], before[k])
            else:                  # stragglers: params bit-identical
                np.testing.assert_array_equal(after[k], before[k])
        # all participants share the same z (FedAvg write-back)
        act = [after[k] for k in range(K) if active[k]]
        for a in act[1:]:
            np.testing.assert_array_equal(a, act[0])
        assert seen["r"]["n_active"] == active.sum()

    def test_admm_duals_only_move_for_participants(self, data):
        from federated_pytorch_test_tpu.parallel.mesh import (
            client_sharding, replicated_sharding, stage_global,
        )

        cfg = small_cfg(participation=0.5, Nadmm=1, seed=3)
        t = BlockwiseFederatedTrainer(Net(), cfg, data, AdmmConsensus())
        t.L = 1
        active = self._mask(t, 0, 0, 0)
        assert 0 < active.sum() < K
        # one comm round by hand so y is observable (the run loop keeps it
        # internal): nonzero duals in, assert straggler rows bit-identical
        train_epoch, comm_fns, init_opt = t._build_fns(0)
        N = t.block_size(0)
        state = t.init_state()
        state = state._replace(opt_state=init_opt(state.params))
        rsh, csh = replicated_sharding(t.mesh), client_sharding(t.mesh)
        z = stage_global(np.zeros(N, np.float32), rsh)
        y0 = np.linspace(0.5, 1.5, K * N).astype(np.float32).reshape(K, N)
        y = stage_global(y0, csh)
        rho = stage_global(np.float32(cfg.admm_rho0), rsh)
        dummy = stage_global(np.zeros((K, 1), np.float32), csh)
        amask = t._round_mask(0, 0, 0)
        xb, yb, wb = t._stage_epoch()
        state, _ = train_epoch(state, y, t.client_norm, t._epoch_keys(),
                               xb, yb, wb, z, rho, amask)
        # base 7-tuple; the tail is variadic (client-ledger probes)
        outs = comm_fns["plain"](
            state, z, y, rho, dummy, dummy, amask,
            t._zero_corrupt, t._inf_bound)
        _, _, y_new, _, _, _, diag = outs[:7]
        y_new = np.asarray(jax.device_get(y_new))
        assert float(diag["n_active"]) == active.sum()
        assert np.isfinite(float(diag["primal_residual"]))
        for k in range(K):
            if active[k]:          # participants: y_k += rho (x_k - z)
                assert not np.array_equal(y_new[k], y0[k])
            else:                  # stragglers: duals bit-untouched
                np.testing.assert_array_equal(y_new[k], y0[k])

    def test_active_mean_is_mean_over_participants(self, data):
        from federated_pytorch_test_tpu.train.algorithms import FedAvg
        from federated_pytorch_test_tpu.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = client_mesh(4)
        x = np.arange(4 * 3, dtype=np.float32).reshape(4, 3)
        w = np.asarray([1.0, 0.0, 1.0, 0.0], np.float32)
        algo = FedAvg()

        def f(x, w, z, y):
            z2, _, d = algo.global_update(x, z, y, jnp.float32(1.0), 4, w=w)
            return z2

        z = jnp.zeros(3)
        y = np.zeros((4, 1), np.float32)
        got = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P("clients"), P("clients"), P(),
                                    P("clients")),
            out_specs=P(), check_vma=False))(x, w, z, y)
        np.testing.assert_allclose(np.asarray(got), x[[0, 2]].mean(axis=0),
                                   rtol=1e-6)

    def test_bb_update_incompatible(self, data):
        with pytest.raises(ValueError, match="bb_update"):
            BlockwiseFederatedTrainer(
                Net(), small_cfg(participation=0.5, bb_update=True), data,
                AdmmConsensus())

    def test_participation_range_validated(self, data):
        with pytest.raises(ValueError, match="participation"):
            BlockwiseFederatedTrainer(
                Net(), small_cfg(participation=0.0), data, FedAvg())


class TestMultihostHelpers:
    """stage_global / fetch (parallel/mesh.py): single-process they reduce
    to device_put / np.asarray; the multi-process branch's callback slicing
    is validated directly against the sharding's index map."""

    def test_stage_global_matches_device_put(self):
        from federated_pytorch_test_tpu.parallel.mesh import (
            client_sharding, stage_global,
        )
        mesh = client_mesh(4)
        x = np.arange(4 * 6, dtype=np.float32).reshape(4, 6)
        a = stage_global(x, client_sharding(mesh))
        b = jax.device_put(x, client_sharding(mesh))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.sharding == b.sharding

    def test_callback_branch_reassembles_global_array(self):
        # the branch multi-host staging takes, runnable single-process:
        # each addressable shard is cut from the full host array
        from federated_pytorch_test_tpu.parallel.mesh import client_sharding
        mesh = client_mesh(4)
        sh = client_sharding(mesh)
        x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
        a = jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])
        np.testing.assert_array_equal(np.asarray(a), x)

    def test_fetch_roundtrip(self):
        from federated_pytorch_test_tpu.parallel.mesh import (
            client_sharding, fetch, stage_global,
        )
        mesh = client_mesh(4)
        x = np.arange(4 * 5, dtype=np.float32).reshape(4, 5)
        np.testing.assert_array_equal(
            fetch(stage_global(x, client_sharding(mesh))), x)

    def test_initialize_multihost_noop_when_unset(self, monkeypatch):
        from federated_pytorch_test_tpu.parallel.mesh import (
            initialize_multihost,
        )
        monkeypatch.delenv("FEDTPU_DISTRIBUTED", raising=False)
        assert initialize_multihost() is False
        assert jax.process_count() == 1

    def test_multiprocess_branches_run(self, monkeypatch):
        """Force the process_count>1 code paths (make_array_from_callback
        staging, process_allgather fetch) — both execute fine in a single
        process, so the branches get real coverage without a pod.

        Caveat: the staged array here is fully addressable, so
        ``process_allgather`` takes its host-local tiled-concat path — NOT
        the replicate path a genuinely client-sharded pod array (with
        non-addressable shards) takes.  This test therefore witnesses that
        ``fetch`` calls process_allgather with ``tiled=True``, not the
        pod-side behavior of process_allgather itself."""
        from federated_pytorch_test_tpu.parallel import mesh as meshmod
        monkeypatch.setattr(meshmod, "_process_count", lambda: 2)
        m = client_mesh(4)
        sh = meshmod.client_sharding(m)
        x = np.arange(4 * 5, dtype=np.float32).reshape(4, 5)
        staged = meshmod.stage_global(x, sh)
        np.testing.assert_array_equal(meshmod.fetch(staged), x)


# ----------------------------------------------------------------------
# the block switch: per-block state made by a device program, block_size
# from shapes
# ----------------------------------------------------------------------
def _stage_switch_from_host(t):
    """Put the block switch of PR 26's parent back on trainer ``t``: z, y,
    rho, x0, yhat0 and top-k's scratch as host arrays through
    ``stage_global`` (a copy of the old staging, kept here as the pin)."""
    from federated_pytorch_test_tpu.parallel.mesh import (
        client_sharding, replicated_sharding, stage_global,
    )
    cfg = t.cfg
    rsh, csh = replicated_sharding(t.mesh), client_sharding(t.mesh)

    def block_vars(N):
        z = stage_global(np.zeros((N,), np.float32), rsh)
        ydim = N if t.algo.needs_dual else 1
        y = stage_global(np.zeros((cfg.K, ydim), np.float32), csh)
        rho = stage_global(np.asarray(cfg.admm_rho0, np.float32), rsh)
        x0 = stage_global(
            np.zeros((cfg.K, N if cfg.bb_update else 1), np.float32), csh)
        if cfg.bb_update:
            return z, y, rho, x0
        return z, y, rho, x0, stage_global(
            np.zeros((cfg.K, 1), np.float32), csh)

    def scratch(N):
        if not getattr(t.compressor, "sparse", False):
            return None
        return stage_global(np.zeros((cfg.K, N), np.float32), csh)

    t._fresh_block_vars = block_vars
    t._init_sparse_scratch = scratch


def _old_block_size(t, ci):
    """``block_size`` as it was: client 0 sliced out of every leaf."""
    one = jax.tree.map(lambda x: x[0], t.params0)
    return codec.masked_size(one, t.order, t.mask_for_block(ci))


SWITCH_CASES = {
    "admm": (AdmmConsensus, {}),
    "fedavg": (FedAvg, {}),
    "admm-bb": (AdmmConsensus, {"bb_update": True, "Nadmm": 3}),
    "admm-topk": (AdmmConsensus, {"compress": "topk"}),
    "fedavg-q8": (FedAvg, {"compress": "q8"}),
}


class TestBlockSwitchOnDevice:
    def _run(self, data, algo, host_staged, **kw):
        from federated_pytorch_test_tpu.obs.schema import ADVISORY_FIELDS
        from federated_pytorch_test_tpu.parallel.mesh import fetch

        t = BlockwiseFederatedTrainer(
            Net(), small_cfg(retrace_sentinel=True, **kw), data, algo())
        if host_staged:
            _stage_switch_from_host(t)
        seen, emit = [], t._emit_round_obs

        def spy(*a, **k):
            # z, y after each round's exchange (the loop keeps them
            # internal); fetched now, the next round donates them
            seen.append([np.asarray(fetch(v)) for v in k["blockvars"]])
            return emit(*a, **k)

        t._emit_round_obs = spy
        state, hist = t.run(log=lambda m: None)
        core = [{k: v for k, v in r.items() if k not in ADVISORY_FIELDS}
                for r in hist]
        return t, jax.device_get(state.params), core, seen

    @pytest.mark.parametrize("D", [1, 4])
    @pytest.mark.parametrize("case", sorted(SWITCH_CASES))
    def test_bitwise_the_host_staged_switch(self, data, case, D):
        algo, kw = SWITCH_CASES[case]
        kw = dict(kw, Nloop=2, num_devices=D)
        t_new, p_new, h_new, v_new = self._run(data, algo, False, **kw)
        t_old, p_old, h_old, v_old = self._run(data, algo, True, **kw)
        assert h_new == h_old and len(h_new) == 2 * 2 * kw.get("Nadmm", 2)
        jax.tree.map(np.testing.assert_array_equal, p_new, p_old)
        for new, old in zip(v_new, v_old):          # z, y, rho, x0, yhat0
            for a, b in zip(new, old):
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(a, b)
        # the device-made arrays are committed to the shardings the
        # host-staged ones had: the round fns saw the same signatures,
        # so their jit caches hold as many entries either way
        entries = []
        for t in (t_new, t_old):
            train_epoch, comm_fns, _ = t._fn_cache[("blk", 0)]
            entries.append([f.__wrapped_jit__._cache_size()
                            for f in (train_epoch, *comm_fns.values())])
        assert entries[0] == entries[1]

    @pytest.mark.parametrize("D", [1, 4])
    @pytest.mark.parametrize("case", ["admm", "fedavg", "admm-bb"])
    def test_made_under_the_staged_shardings(self, data, case, D):
        from federated_pytorch_test_tpu.parallel.mesh import (
            client_sharding, replicated_sharding,
        )
        algo, kw = SWITCH_CASES[case]
        t = BlockwiseFederatedTrainer(
            Net(), small_cfg(num_devices=D, **kw), data, algo())
        rsh, csh = replicated_sharding(t.mesh), client_sharding(t.mesh)
        N = t.block_size(1)
        made = t._fresh_block_vars(N)
        assert len(made) == (4 if t.cfg.bb_update else 5)
        want = [((N,), rsh), ((K, N if t.algo.needs_dual else 1), csh),
                ((), rsh), ((K, N if t.cfg.bb_update else 1), csh),
                ((K, 1), csh)]
        for a, (shape, sh) in zip(made, want):
            assert a.shape == shape and a.dtype == jnp.float32
            assert a.sharding == sh and a.committed
            assert a.sharding.spec == sh.spec    # not only equivalent
        assert np.asarray(made[2]) == np.float32(t.cfg.admm_rho0)
        assert not any(np.asarray(a).any() for a in made[:2] + made[3:])
        # fresh buffers on every call: the comm step donates them
        again = t._fresh_block_vars(N)
        shards = [s for a in (*made, *again) for s in a.addressable_shards]
        assert len({s.data.unsafe_buffer_pointer() for s in shards}) \
            == len(shards)
        t.close()

    @pytest.mark.parametrize("case", sorted(SWITCH_CASES))
    def test_second_sweep_compiles_and_retraces_nothing(self, data, case):
        algo, kw = SWITCH_CASES[case]
        t = BlockwiseFederatedTrainer(
            Net(), small_cfg(Nloop=3, retrace_sentinel=True, **kw), data,
            algo())
        events = []

        def on_round(state, rec):
            events.append(len(t._ledger.all_events))

        _, hist = t.run(log=lambda m: None, on_round=on_round)
        per_sweep = len(hist) // 3
        assert all(r["jit_retraces"] == 0 for r in hist)
        assert all("compile_seconds" not in r for r in hist[per_sweep:])
        assert events[per_sweep - 1] == events[-1]      # ledger flat
        assert t._sentinel.retraces == 0
        fresh = [k for k in t._fn_cache if k[0] == "fresh"]
        sparse = getattr(t.compressor, "sparse", False)
        assert len(fresh) == t.L * (2 if sparse else 1)

    def test_no_block_state_leaves_host_memory(self, data, monkeypatch):
        """Nothing of a block's size goes through ``stage_global`` at a
        switch: ADMM, BB and plain top-k stage no [K, N] / [N] array."""
        from federated_pytorch_test_tpu.train import engine as engine_mod

        for case in ("admm", "admm-bb", "admm-topk"):
            algo, kw = SWITCH_CASES[case]
            t = BlockwiseFederatedTrainer(Net(), small_cfg(**kw), data,
                                          algo())
            sizes = {t.block_size(ci) for ci in range(t.L)}
            staged, real = [], engine_mod.stage_global

            def counted(x, sharding):
                staged.append(np.shape(x))
                return real(x, sharding)

            monkeypatch.setattr(engine_mod, "stage_global", counted)
            t.run(log=lambda m: None)
            monkeypatch.setattr(engine_mod, "stage_global", real)
            assert staged                               # epoch data, keys
            assert not [s for s in staged if s and s[-1] in sizes]


def _skeleton(cls, model, *sample):
    """A trainer of class ``cls`` with just what ``block_size`` reads
    (order, block partition, sweep, the [K]-stacked ``params0``), its
    leaves zero-copy numpy views of the model's real shapes: building a
    ResNet18 trainer through ``__init__`` costs 20 s of op-by-op init."""
    params, _ = jax.eval_shape(
        lambda: model.init_variables(jax.random.PRNGKey(0), *sample))
    t = object.__new__(cls)
    t.order = model.param_order()
    t.block_ids = model.train_order_block_ids()
    t.L = len(t.block_ids)
    t.params0 = jax.tree.map(
        lambda v: np.broadcast_to(np.zeros((), v.dtype), (2,) + v.shape),
        params)
    t._block_sizes = {}
    return t


class TestBlockSizeFromShapes:
    def _check(self, t):
        want = [_old_block_size(t, ci) for ci in range(t.L)]
        whole = _old_block_size(t, None)
        got = [t.block_size(ci) for ci in range(t.L)]
        assert got == want and t.block_size(None) == whole
        assert all(type(n) is int and n > 0 for n in got)
        # kept per set of paths: asked again, nothing is recomputed
        t.mask_for_block = lambda ci: pytest.fail("block_size() not kept")
        assert [t.block_size(ci) for ci in range(t.L)] == got
        del t.mask_for_block
        return got

    def test_all_ten_resnet18_blocks(self):
        from federated_pytorch_test_tpu.models import ResNet18

        t = _skeleton(BlockwiseFederatedTrainer, ResNet18(),
                      jnp.zeros((1, 32, 32, 3)))
        got = self._check(t)
        assert len(got) == 10 and got[0] == 1856 and got[8] == 4720640
        assert sum(got) == t.block_size(None) == 11173962
        # the benchmark re-points block_ids after construction
        t.block_ids = [t.block_ids[b] for b in (8, 0)]
        t.L = 2
        assert [t.block_size(0), t.block_size(1)] == [4720640, 1856]
        assert t.block_size(0) == _old_block_size(t, 0)

    def test_reads_shapes_only(self, data):
        """No program is dispatched: params0 is never indexed."""
        t = BlockwiseFederatedTrainer(Net(), small_cfg(), data, FedAvg())

        class Shapes:
            def __init__(self, x):
                self.shape, self.dtype = x.shape, x.dtype

        t.params0 = jax.tree.map(Shapes, t.params0)
        assert [t.block_size(0), t.block_size(1)] == [304, 2570]
        assert t.block_size(None) == 2874
        t.close()

    @pytest.mark.parametrize("which", ["vae-layers", "vae-cl"])
    def test_vae_trainers(self, which):
        from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN
        from federated_pytorch_test_tpu.models.vae_cl import AutoEncoderCNNCL
        from federated_pytorch_test_tpu.train.vae_engine import (
            VAECLTrainer, VAETrainer,
        )
        sample = (jnp.zeros((1, 32, 32, 3)), jax.random.PRNGKey(0))
        if which == "vae-layers":
            t = _skeleton(VAETrainer, AutoEncoderCNN(), *sample)
            assert t.sweep == "layers"
        else:
            t = _skeleton(VAECLTrainer, AutoEncoderCNNCL(), *sample)
            assert t.sweep == "blocks"
        got = self._check(t)
        assert len(got) == t.L
        if which == "vae-layers":       # every layer once: sizes add up
            assert sum(got) == t.block_size(None)
