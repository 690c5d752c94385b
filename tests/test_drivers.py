"""Driver CLI + checkpoint round-trip tests."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from federated_pytorch_test_tpu.utils.checkpoint import load_checkpoint, save_checkpoint


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
                 "step": jnp.int32(7)}
        save_checkpoint(str(tmp_path / "ck"), state, meta={"rounds": 3})
        restored, meta = load_checkpoint(str(tmp_path / "ck"))
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                                   np.arange(6.0).reshape(2, 3))
        assert meta["rounds"] == 3

    def test_restore_onto_shardings(self, tmp_path):
        state = {"w": jnp.ones((4, 2))}
        save_checkpoint(str(tmp_path / "ck"), state)
        like = {"w": jnp.zeros((4, 2))}
        restored, _ = load_checkpoint(str(tmp_path / "ck"), like=like)
        assert restored["w"].shape == (4, 2)
        np.testing.assert_allclose(np.asarray(restored["w"]), 1.0)

    def test_swapped_save_promotes_a_next_only_survivor(self, tmp_path):
        """Crash-window regression: a kill between orbax finalizing
        'ck.next' and the rename leaves ONLY '.next' on disk.  The next
        swapped save must promote that survivor to the primary slot
        BEFORE clearing '.next', so a second kill mid-save can never
        leave zero complete checkpoints."""
        from federated_pytorch_test_tpu.utils.checkpoint import (
            newest_slot,
            save_checkpoint_swapped,
        )

        ck = str(tmp_path / "ck")
        save_checkpoint(ck + ".next", {"v": np.asarray(1)})   # crash relic
        assert newest_slot(ck) == ck + ".next"
        save_checkpoint_swapped(ck, {"v": np.asarray(2)})
        assert newest_slot(ck) == ck
        restored, _ = load_checkpoint(ck)
        assert int(restored["v"]) == 2
        assert not os.path.isdir(ck + ".next")

    def test_swapped_save_sequence_keeps_primary_current(self, tmp_path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            save_checkpoint_swapped,
        )

        ck = str(tmp_path / "ck")
        for v in (1, 2, 3):
            save_checkpoint_swapped(ck, {"v": np.asarray(v)})
        restored, _ = load_checkpoint(ck)
        assert int(restored["v"]) == 3


class TestDriverCLI:
    # stays in the quick loop despite two runs: it is the only CLI coverage
    # of the no_consensus path and the end-of-run checkpoint load
    def test_no_consensus_smoke_and_resume(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        from federated_pytorch_test_tpu.drivers.no_consensus_multi import main
        common = ["--K", "2", "--Nepoch", "1", "--n-train", "32",
                  "--n-test", "32", "--default-batch", "16"]
        state, hist = main(common)
        assert os.path.isdir("checkpoints/no_consensus_multi")
        assert len(hist) == 1 and hist[0]["accuracy"].shape == (2,)
        # resume path restores params
        state2, hist2 = main(common + ["--load-model"])
        assert len(hist2) == 1

    def test_fedavg_driver_smoke(self, tmp_path, monkeypatch, capsys):
        monkeypatch.chdir(tmp_path)
        from federated_pytorch_test_tpu.drivers.federated_multi import main
        state, hist = main([
            "--K", "2", "--Nloop", "1", "--Nadmm", "1", "--n-train", "32",
            "--n-test", "32", "--default-batch", "16", "--no-save-model",
            "--no-check-results"])
        assert all("dual_residual" in h for h in hist)
        # the first line names the data source and the device JAX found
        banner = capsys.readouterr().out.splitlines()[0]
        assert "data=synthetic" in banner
        assert (f"platform=cpu device_kind='cpu' "
                f"device_count={len(jax.devices())}") in banner

    def test_no_use_tpu_after_backend_init_is_an_error(self, monkeypatch):
        """It used to warn and carry on on the other platform."""
        from federated_pytorch_test_tpu.drivers import common
        from federated_pytorch_test_tpu.train.config import FederatedConfig

        common.apply_platform(FederatedConfig(use_tpu=False))   # cpu: fine
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="already initialized on 'tpu'"):
            common.apply_platform(FederatedConfig(use_tpu=False))

    def test_fedprox_driver_smoke(self, tmp_path, monkeypatch):
        """FedProx CLI end to end: proximal penalty runs and z is NEVER
        written back (reference fedprox_multi.py has no
        put_trainable_values; history carries the primal residual)."""
        monkeypatch.chdir(tmp_path)
        from federated_pytorch_test_tpu.drivers.fedprox_multi import main
        state, hist = main([
            "--K", "2", "--Nloop", "1", "--Nadmm", "1", "--n-train", "32",
            "--n-test", "32", "--default-batch", "16", "--no-save-model",
            "--no-check-results"])
        assert all("primal_residual" in h for h in hist)
        assert all(np.isfinite(h["loss"]) for h in hist)

    def test_model_flag_resolves_every_choice(self):
        """--model replaces the reference's source-edit model switch
        (federated_multi.py:92-97)."""
        from federated_pytorch_test_tpu.drivers.common import pick_model
        from federated_pytorch_test_tpu.train import FederatedConfig

        names = {"net": "Net", "net1": "Net1", "net2": "Net2",
                 "resnet9": "ResNet", "resnet18": "ResNet"}
        for choice, cls in names.items():
            m = pick_model(FederatedConfig(model=choice))
            assert type(m).__name__ == cls, choice
        assert type(pick_model(FederatedConfig())).__name__ == "Net"
        assert type(pick_model(
            FederatedConfig(use_resnet=True))).__name__ == "ResNet"
        with pytest.raises(ValueError, match="unknown model"):
            pick_model(FederatedConfig(model="resnet"))

    @pytest.mark.slow   # full compile+train of a non-default model
    def test_model_flag_trains_net1(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        from federated_pytorch_test_tpu.drivers.federated_multi import main
        _, hist = main([
            "--K", "2", "--Nloop", "1", "--Nadmm", "1", "--n-train", "32",
            "--n-test", "32", "--default-batch", "16", "--no-save-model",
            "--no-check-results", "--model", "net1"])
        assert all(np.isfinite(h["loss"]) for h in hist)

    def test_parser_keeps_reference_knob_names(self):
        from federated_pytorch_test_tpu.drivers.consensus_multi import DEFAULTS
        from federated_pytorch_test_tpu.drivers.common import build_parser
        p = build_parser(DEFAULTS, "consensus_multi")
        args = p.parse_args(["--K", "4", "--Nadmm", "7", "--bb-update",
                             "--admm-rho0", "0.05"])
        assert args.K == 4 and args.Nadmm == 7
        assert args.bb_update is True and args.admm_rho0 == 0.05
        # tri-state device_data: absent -> None (auto), both overrides work
        assert args.device_data is None
        assert p.parse_args(["--device-data"]).device_data is True
        assert p.parse_args(["--no-device-data"]).device_data is False

    def test_every_reference_knob_has_a_flag(self):
        """EVERY module-level constant of the reference driver skeleton
        (SURVEY.md section 5 config inventory: federated_multi.py:9-48 +
        the consensus BB knobs) parses as a CLI flag with its reference
        name (``use_cuda`` -> ``use_tpu`` per BASELINE.json)."""
        from federated_pytorch_test_tpu.drivers.consensus_multi import DEFAULTS
        from federated_pytorch_test_tpu.drivers.common import build_parser
        p = build_parser(DEFAULTS, "consensus_multi")
        knobs = ["K", "default_batch", "Nloop", "Nepoch", "Nadmm",
                 "lambda1", "lambda2", "admm_rho0", "load_model",
                 "init_model", "save_model", "check_results",
                 "biased_input", "be_verbose", "use_resnet", "use_tpu",
                 "bb_update", "bb_period_T", "bb_rhomax", "bb_alphacorrmin",
                 "bb_epsilon"]
        args = p.parse_args([])
        for k in knobs:
            assert hasattr(args, k), f"reference knob {k} has no CLI flag"

    @pytest.mark.slow   # two full driver runs; engine-level resume is
    #                     covered fast in tests/test_resume.py
    def test_midrun_checkpoint_flag_saves_and_resumes(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.chdir(tmp_path)
        from federated_pytorch_test_tpu.drivers.federated_multi import main
        common = ["--K", "2", "--Nloop", "1", "--Nadmm", "1", "--n-train",
                  "32", "--n-test", "32", "--default-batch", "16",
                  "--no-save-model", "--no-check-results",
                  "--midrun-checkpoint"]
        _, hist = main(common)
        assert os.path.isdir("checkpoints/federated_multi_midrun")
        # resume of a completed run is a no-op returning the SAVED history:
        # round_seconds is unique wall-clock from run 1, so equality proves
        # the records were restored, not regenerated by a silent retrain
        _, hist2 = main(common + ["--load-model"])
        assert len(hist2) == len(hist)
        assert hist2[0]["round_seconds"] == hist[0]["round_seconds"]

    @pytest.mark.slow
    def test_profile_dir_flag_writes_trace(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        from federated_pytorch_test_tpu.drivers.federated_multi import main
        main(["--K", "2", "--Nloop", "1", "--Nadmm", "1", "--n-train", "32",
              "--n-test", "32", "--default-batch", "16", "--no-save-model",
              "--no-check-results", "--profile-dir", str(tmp_path / "prof")])
        assert list((tmp_path / "prof").rglob("*.xplane.pb"))
