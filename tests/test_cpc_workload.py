"""CPC workload tests: InfoNCE parity, LOFAR patching, trainer smoke."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.data.lofar import (
    CPCDataSource,
    extract_patches,
    get_data_minibatch,
)
from federated_pytorch_test_tpu.train.cpc_losses import info_nce


class TestInfoNCE:
    def naive(self, z, zhat):
        """Literal port of the reference's nested loops
        (federated_cpc.py:149-180); z, zhat [B, C, px, py] NCHW."""
        B, C, px, py = z.shape
        P = px * py
        Z = z.reshape(-1, P)
        Zhat = zhat.reshape(-1, P)
        zz = np.zeros((P, P))
        for ci in range(P):
            zn = np.linalg.norm(Z[:, ci])
            for cj in range(P):
                zz[ci, cj] = Z[:, ci] @ Zhat[:, cj] / (
                    zn * np.linalg.norm(Zhat[:, cj]))
        loss = 0.0
        for ci in range(P):
            num = np.exp(zz[ci, ci])
            den = num + sum(np.exp(zz[ci, cj]) for cj in range(P) if cj != ci)
            loss -= np.log(num / den + 1e-6)
        return loss

    def test_matches_reference_loops(self):
        rng = np.random.default_rng(0)
        z = rng.normal(size=(3, 4, 2, 3)).astype(np.float32)     # B,C,px,py
        zh = rng.normal(size=(3, 4, 2, 3)).astype(np.float32)
        # ours takes NHWC [B, px, py, C]
        got = float(info_nce(jnp.asarray(z.transpose(0, 2, 3, 1)),
                             jnp.asarray(zh.transpose(0, 2, 3, 1))))
        np.testing.assert_allclose(got, self.naive(z, zh), rtol=1e-4)


class TestLofarPipeline:
    def test_extract_patches_shapes_and_content(self):
        x = np.arange(2 * 3 * 64 * 64, dtype=np.float32).reshape(2, 3, 64, 64)
        px, py, y = extract_patches(x, 32, 16)
        assert (px, py) == (3, 3)
        assert y.shape == (2 * 9, 3, 32, 32)
        # row r = b*9 + ci*3 + cj (baseline-major; see deviation note)
        np.testing.assert_array_equal(y[0], x[0, :, 0:32, 0:32])
        np.testing.assert_array_equal(y[1], x[0, :, 0:32, 16:48])
        np.testing.assert_array_equal(y[3], x[0, :, 16:48, 0:32])
        np.testing.assert_array_equal(y[9], x[1, :, 0:32, 0:32])

    def test_synthetic_minibatch(self):
        rng = np.random.default_rng(0)
        px, py, y = get_data_minibatch("no_such_file.h5", "0", batch_size=2,
                                       rng=rng)
        assert y.shape == (2 * px * py, 32, 32, 8)
        assert y.dtype == np.float32
        assert np.all(np.abs(y) <= 1e6)

    def test_synthetic_cube_deterministic_per_file_sap(self):
        r1 = np.random.default_rng(5)
        r2 = np.random.default_rng(5)
        _, _, a = get_data_minibatch("f.h5", "1", 2, rng=r1)
        _, _, b = get_data_minibatch("f.h5", "1", 2, rng=r2)
        np.testing.assert_array_equal(a, b)
        _, _, c = get_data_minibatch("f.h5", "2", 2,
                                     rng=np.random.default_rng(5))
        assert not np.array_equal(a, c)

    def test_round_batches_shape(self):
        src = CPCDataSource(["a.h5", "b.h5"], ["0", "0"], batch_size=2)
        px, py, batch = src.round_batches(niter=2)
        assert batch.shape == (2, 2, 2 * px * py, 32, 32, 8)

    def test_round_batches_draws_keyed_per_round_and_client(self):
        """(seed, round, client)-keyed draws: a client-subset build must
        reproduce the full build's rows exactly (multi-host: each process
        builds only its clients), and successive rounds must differ."""
        a = CPCDataSource(["a.h5", "b.h5", "c.h5"], ["0", "0", "0"],
                          batch_size=2, seed=3)
        b = CPCDataSource(["a.h5", "b.h5", "c.h5"], ["0", "0", "0"],
                          batch_size=2, seed=3)
        _, _, full = a.round_batches(niter=2)
        _, _, sub = b.round_batches(niter=2, clients=[1, 2])
        np.testing.assert_array_equal(sub, full[1:])
        _, _, full2 = a.round_batches(niter=2)          # round counter bumped
        assert not np.array_equal(full, full2)

    def test_round_prefetcher_matches_direct_calls(self):
        from federated_pytorch_test_tpu.data.lofar import RoundPrefetcher

        direct = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                               seed=11)
        want = [direct.round_batches(2) for _ in range(3)]
        pre_src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                                seed=11)
        pre = RoundPrefetcher(pre_src, niter=2, total_rounds=3)
        try:
            for px, py, batch in want:
                gpx, gpy, got = pre.get()
                assert (gpx, gpy) == (px, py)
                np.testing.assert_array_equal(got, batch)
        finally:
            pre.close()

    def test_round_prefetcher_relays_producer_failure(self):
        from federated_pytorch_test_tpu.data.lofar import RoundPrefetcher

        class Boom:
            def round_batches(self, niter, clients=None):
                raise ValueError("disk on fire")

        pre = RoundPrefetcher(Boom(), niter=1, total_rounds=1)
        with pytest.raises(RuntimeError, match="producer failed"):
            pre.get()
        pre.close()

    def test_local_client_rows_single_process_is_all(self):
        from federated_pytorch_test_tpu.parallel.mesh import (
            client_mesh,
            local_client_rows,
        )

        mesh = client_mesh(4)
        assert local_client_rows(mesh, 8) == list(range(8))

    def test_stage_client_rows_roundtrip(self):
        from federated_pytorch_test_tpu.parallel import mesh as meshmod

        mesh = meshmod.client_mesh(4)
        sh = meshmod.client_sharding(mesh)
        x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
        np.testing.assert_array_equal(
            np.asarray(meshmod.stage_client_rows(x, sh)), x)


class TestCPCDriverCLI:
    @pytest.mark.slow
    def test_save_then_load_roundtrip(self, tmp_path, monkeypatch):
        """drivers/federated_cpc main(): end-of-run checkpoint then a
        second run restoring it through the multi-host staging path
        (stage_tree_global; reference save/load quirk fixed,
        federated_cpc.py:126-134 vs :308-318)."""
        monkeypatch.chdir(tmp_path)
        from federated_pytorch_test_tpu.drivers.federated_cpc import main

        common = ["--file-list", "a.h5", "b.h5", "--sap-list", "0", "1",
                  "--Lc", "8", "--Rc", "4", "--batch-size", "2",
                  "--Niter", "1", "--no-use-tpu"]
        state, hist = main(common)
        assert os.path.isdir("checkpoints/federated_cpc")
        state2, hist2 = main(common + ["--load-model"])
        assert len(hist2) == len(hist)
        # the loaded run starts from run 1's federated weights, not from
        # common init: its first-round losses must differ
        assert hist2[0]["loss"] != hist[0]["loss"]


class TestCPCMidrunResume:
    @pytest.mark.slow
    def test_interrupted_run_resumes_bit_identically(self, tmp_path):
        """Kill-and-resume parity for the CPC rotation: a run interrupted
        mid-block (LBFGS state + z + rotation counters + data-order
        counter restored) must produce the exact history an uninterrupted
        run does (engine analogue: tests/test_resume.py)."""
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        def make():
            src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                                seed=7)
            return CPCTrainer(src, latent_dim=8, reduced_dim=4,
                              lbfgs_history=3, lbfgs_max_iter=1, Niter=1)

        strip = lambda h: [{k: v for k, v in r.items()
                            if not k.endswith("_seconds")
                               and not k.startswith("dispatch_")} for r in h]
        ck = str(tmp_path / "cpc_midrun")

        # uninterrupted reference trajectory: 4 blocks x Nadmm=2 rounds
        _, want = make().run(Nloop=1, Nadmm=2, log=lambda m: None)

        # interrupted: stop after 3 rounds (mid-block: encoder block 1,
        # nadmm 0 done, 1 pending) by raising from the log callback
        t = make()

        class Stop(Exception):
            pass

        calls = []

        def bomb(msg):
            calls.append(msg)
            if len(calls) == 3:
                raise Stop

        with pytest.raises(Stop):
            t.run(Nloop=1, Nadmm=2, log=bomb, checkpoint_path=ck)

        # fresh trainer resumes from the checkpoint and finishes
        t2 = make()
        _, got = t2.run(Nloop=1, Nadmm=2, log=lambda m: None,
                        checkpoint_path=ck, resume=True)
        assert strip(got) == strip(want)

    @pytest.mark.slow
    def test_resume_with_smaller_nadmm_completes(self, tmp_path):
        """Resuming under a different Nadmm must not hang: the prefetcher
        is sized by walking the actual remaining loop structure, not by
        subtracting the old run's history length."""
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        def make():
            src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                                seed=9)
            return CPCTrainer(src, latent_dim=8, reduced_dim=4,
                              lbfgs_history=3, lbfgs_max_iter=1, Niter=1)

        ck = str(tmp_path / "cpc_midrun")

        class Stop(Exception):
            pass

        calls = []

        def bomb(msg):
            calls.append(msg)
            if len(calls) == 3:          # stop mid-block (Nadmm=2)
                raise Stop

        with pytest.raises(Stop):
            make().run(Nloop=1, Nadmm=2, log=bomb, checkpoint_path=ck)
        _, got = make().run(Nloop=1, Nadmm=1, log=lambda m: None,
                            checkpoint_path=ck, resume=True)
        # restored 3 records + the remaining blocks at the smaller Nadmm
        assert len(got) > 3
        assert all(np.isfinite(h["loss"]) for h in got)


class TestCPCTrainer:
    @pytest.mark.slow
    def test_rotation_trains_all_submodels(self):
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer
        src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2)
        t = CPCTrainer(src, latent_dim=16, reduced_dim=4, Niter=2)
        state, hist = t.run(Nloop=1, Nadmm=1, log=lambda m: None)
        models = {h["model"] for h in hist}
        assert models == {"encoder", "contextgen", "predictor"}
        assert all(np.isfinite(h["dual_residual"]) for h in hist)
        assert all(np.isfinite(h["loss"]) for h in hist)
        # the stage/compute wall-clock split is recorded per round
        assert all(h["stage_seconds"] >= 0 and h["compute_seconds"] >= 0
                   and h["round_seconds"] >= h["compute_seconds"]
                   for h in hist)

    @pytest.mark.slow
    def test_profile_trace_written(self, tmp_path):
        """--profile-dir parity with the classifier engine (SURVEY.md
        section 5 tracing): the CPC run wraps in jax.profiler.trace."""
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2)
        t = CPCTrainer(src, latent_dim=8, reduced_dim=4, Niter=1)
        t.run(Nloop=1, Nadmm=1, log=lambda m: None,
              profile_dir=str(tmp_path / "trace"))
        hits = list((tmp_path / "trace").rglob("*.xplane.pb"))
        assert hits, "no xplane trace written"

    @pytest.mark.slow
    def test_prefetch_matches_direct_trajectory(self):
        """The (seed, round, client)-keyed draws make the prefetched and
        direct pipelines bit-identical — losses and residuals must agree
        exactly (only the *_seconds timing fields may differ)."""
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        def run(prefetch):
            src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                                seed=4)
            t = CPCTrainer(src, latent_dim=8, reduced_dim=4, Niter=1)
            _, hist = t.run(Nloop=1, Nadmm=1, log=lambda m: None,
                            prefetch=prefetch)
            return [{k: v for k, v in h.items()
                     if not k.endswith("_seconds")
                        and not k.startswith("dispatch_")} for h in hist]

        assert run(True) == run(False)
