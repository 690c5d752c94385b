"""Federated VAE: layer-wise FedAvg on AutoEncoderCNN.

Reference: federated_vae.py (K=10, Nloop=12, Nepoch=1, Nadmm=3, Adam lr=1e-3,
biased_input=True, z written back every round).
"""

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.drivers import common
from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN
from federated_pytorch_test_tpu.train.algorithms import FedAvg
from federated_pytorch_test_tpu.train.config import FederatedConfig
from federated_pytorch_test_tpu.train.vae_engine import VAETrainer

DEFAULTS = FederatedConfig(K=10, Nloop=12, Nepoch=1, Nadmm=3,
                           biased_input=True, check_results=False)


def main(argv=None):
    args = common.build_parser(DEFAULTS, "federated_vae").parse_args(argv)
    cfg = common.default_obs_dir(common.config_from_args(args))
    common.setup_runtime(cfg)
    data = FederatedCifar10(
        K=cfg.K, batch=cfg.default_batch, biased_input=cfg.biased_input,
        drop_last_sample=cfg.drop_last_sample, data_dir=cfg.data_dir,
        limit_per_client=args.n_train, limit_test=args.n_test)
    trainer = VAETrainer(AutoEncoderCNN(), cfg, data, FedAvg())
    trainer.obs_run_name = "federated_vae"
    print(f"federated_vae: K={cfg.K} devices={trainer.D} data={data.source} "
          f"{common.device_banner()}")
    state = common.maybe_load(trainer, "federated_vae")
    supervised = cfg.max_restarts > 0
    # supervision is resume-from-checkpoint: a restart budget forces the
    # mid-run checkpoint on even without --midrun-checkpoint
    ck = (common.checkpoint_path(cfg, "federated_vae_midrun")
          if (cfg.midrun_checkpoint or supervised) else None)
    if supervised:
        from federated_pytorch_test_tpu.control.supervisor import (
            supervise_classifier,
        )

        def build_trainer(c, attempt):
            nonlocal trainer
            if attempt > 1:
                # the failed attempt's trainer is closed (staging pool
                # shut down); rebuild on the ladder-degraded config —
                # engine="vae" keeps the ladder within what VAETrainer
                # can construct
                trainer = VAETrainer(AutoEncoderCNN(), c, data, FedAvg())
                trainer.obs_run_name = "federated_vae"
            return trainer

        state, history = supervise_classifier(
            build_trainer, cfg, ck, state=state,
            resume=cfg.load_model, engine="vae")
    else:
        state, history = trainer.run(state, checkpoint_path=ck,
                                     resume=cfg.load_model and ck is not None)
    print("Finished Training")
    common.print_obs_artifact(trainer)
    common.finish(trainer, state, "federated_vae", history)
    return state, history


if __name__ == "__main__":
    main()
