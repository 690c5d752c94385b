"""Federated clustering VAE (arXiv:2005.04613).

Reference: federated_vae_cl.py (K=1 default, Kc=10 clusters, Lc=32 latent,
Nloop=12, Nepoch=1, Nadmm=3, lambda2=1e-3, 3-block sweep with per-block
Adam/LBFGS switching, z written back).
"""

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.drivers import common
from federated_pytorch_test_tpu.models.vae_cl import AutoEncoderCNNCL
from federated_pytorch_test_tpu.train.algorithms import FedAvg
from federated_pytorch_test_tpu.train.config import FederatedConfig
from federated_pytorch_test_tpu.train.vae_engine import VAECLTrainer

DEFAULTS = FederatedConfig(K=1, Nloop=12, Nepoch=1, Nadmm=3,
                           lambda2=1e-3, biased_input=False,
                           check_results=False,
                           lbfgs_history_size=10, lbfgs_max_iter=4)


def main(argv=None):
    p = common.build_parser(DEFAULTS, "federated_vae_cl")
    p.add_argument("--Kc", type=int, default=10,
                   help="number of clusters (federated_vae_cl.py:22)")
    p.add_argument("--Lc", type=int, default=32,
                   help="latent dimension (federated_vae_cl.py:23)")
    args = p.parse_args(argv)
    cfg = common.default_obs_dir(common.config_from_args(args))
    common.setup_runtime(cfg)
    data = FederatedCifar10(
        K=cfg.K, batch=cfg.default_batch, biased_input=cfg.biased_input,
        drop_last_sample=cfg.drop_last_sample, data_dir=cfg.data_dir,
        limit_per_client=args.n_train, limit_test=args.n_test)
    model = AutoEncoderCNNCL(K=args.Kc, L=args.Lc)
    trainer = VAECLTrainer(model, cfg, data, FedAvg())
    trainer.obs_run_name = "federated_vae_cl"
    print(f"federated_vae_cl: K={cfg.K} Kc={args.Kc} Lc={args.Lc} "
          f"devices={trainer.D} data={data.source} "
          f"{common.device_banner()}")
    state = common.maybe_load(trainer, "federated_vae_cl")
    ck = (common.checkpoint_path(cfg, "federated_vae_cl_midrun")
          if cfg.midrun_checkpoint else None)
    state, history = trainer.run(state, checkpoint_path=ck,
                                 resume=cfg.load_model and ck is not None)
    print("Finished Training")
    common.print_obs_artifact(trainer)
    common.finish(trainer, state, "federated_vae_cl", history)
    return state, history


if __name__ == "__main__":
    main()
