"""Federated CPC on LOFAR visibilities (arXiv:1905.09272).

Reference: federated_cpc.py (K=4 clients <-> (H5 file, SAP) pairs, Lc=256,
Rc=32, batch_size=128, Nloop=1, Niter=10, Nadmm=1, LBFGSNew(history 7,
max_iter 2, batch_mode)).  Files that are absent (the LOFAR extracts are not
redistributable) fall back to deterministic synthetic visibility cubes keyed
on (file, SAP) — see data/lofar.py.

The CLI is the shared classifier surface (drivers/common.build_parser —
every FederatedConfig field is a flag, so ``--fault-spec``,
``--update-guard``, ``--robust-agg``, ``--async-rounds``,
``--max-restarts`` etc. work here exactly as on the classifier drivers)
plus the CPC-specific data/model knobs below.  Flags the CPC engine
cannot honour (``--compress``, ``--fused-collective``,
``--sharded-update``, ``--bb-update``) fail fast with the constructor's
ValueError rather than being silently ignored.

Checkpoints: one orbax directory holding all three sub-models' stacked
client pytrees (the reference writes encoder<k>.model etc. per client but
LOADS from unsuffixed names — a quirk we fix, federated_cpc.py:126-134 vs
:308-318).
"""

import argparse
import os

from federated_pytorch_test_tpu.data.lofar import CPCDataSource
from federated_pytorch_test_tpu.drivers import common
from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer
from federated_pytorch_test_tpu.train.config import FederatedConfig
from federated_pytorch_test_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

DEFAULT_FILES = ["L785751.MS_extract.h5", "L785751.MS_extract.h5",
                 "L785747.MS_extract.h5", "L785757.MS_extract.h5"]
DEFAULT_SAPS = ["1", "2", "0", "0"]

#: reference defaults (federated_cpc.py argparse block): K comes from the
#: file list, one outer loop, one ADMM step per block, midrun off.
DEFAULTS = FederatedConfig(K=4, Nloop=1, Nadmm=1, midrun_checkpoint=False,
                           check_results=False)


def build_parser() -> argparse.ArgumentParser:
    p = common.build_parser(DEFAULTS, "federated_cpc")
    p.description = "TPU-native federated CPC on LOFAR visibilities"
    # CPC-specific knobs (none are FederatedConfig fields, so no clash
    # with the generated flag surface)
    p.add_argument("--file-list", nargs="+", default=DEFAULT_FILES)
    p.add_argument("--sap-list", nargs="+", default=DEFAULT_SAPS)
    p.add_argument("--Lc", type=int, default=256,
                   help="CPC latent dimension (reference Lc)")
    p.add_argument("--Rc", type=int, default=32,
                   help="reduced/context dimension (reference Rc)")
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--patch-size", type=int, default=32)
    p.add_argument("--Niter", type=int, default=10,
                   help="LBFGS data batches per client per round")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    cfg = common.default_obs_dir(common.config_from_args(args))
    common.setup_runtime(cfg)
    data = CPCDataSource(args.file_list, args.sap_list,
                         batch_size=args.batch_size,
                         patch_size=args.patch_size, seed=cfg.seed)

    def make_trainer(c):
        return CPCTrainer(data, latent_dim=args.Lc, reduced_dim=args.Rc,
                          Niter=args.Niter, cfg=c)

    trainer = make_trainer(cfg)
    print(f"federated_cpc: K={data.K} Lc={args.Lc} Rc={args.Rc} "
          f"devices={trainer.D} {common.device_banner()}")
    state = trainer.state0
    ckpt = common.checkpoint_path(cfg, "federated_cpc")
    if cfg.load_model and os.path.isdir(os.path.abspath(
            os.path.expanduser(ckpt))):
        restored, _ = load_checkpoint(ckpt)
        from federated_pytorch_test_tpu.parallel.mesh import (
            client_sharding,
            stage_tree_global,
        )
        csh = client_sharding(trainer.mesh)
        state = type(state)(**{k: stage_tree_global(restored[k], csh)
                               for k in restored})
        print(f"loaded checkpoint <- {ckpt}")
    supervised = cfg.max_restarts > 0
    # supervision is resume-from-checkpoint: a restart budget forces the
    # mid-run checkpoint on even without --midrun-checkpoint
    midrun = (common.checkpoint_path(cfg, "federated_cpc_midrun")
              if (cfg.midrun_checkpoint or supervised) else None)
    run_kwargs = dict(
        Nloop=cfg.Nloop, Nadmm=cfg.Nadmm, profile_dir=cfg.profile_dir,
        checkpoint_path=midrun, async_checkpoint=cfg.async_checkpoint,
        obs_dir=cfg.obs_dir, obs_sinks=cfg.obs_sinks,
        obs_run_name="federated_cpc", health_action=cfg.health_action)
    if supervised:
        from federated_pytorch_test_tpu.control.supervisor import (
            ladder_overrides,
            ladder_records,
            supervise,
        )

        box = {"trainer": trainer}

        def run_attempt(attempt, resume_now):
            if attempt > 1:
                # CPC's run takes no externally-built state, so a fresh
                # attempt rebuilds the trainer on the (possibly
                # ladder-degraded) config and resumes from the midrun
                # slot; engine="cpc" keeps the ladder within what
                # CPCTrainer can construct (no compression path)
                _, degraded, _ = ladder_overrides(cfg, attempt - 1,
                                                  engine="cpc")
                box["trainer"] = make_trainer(degraded)
            t = box["trainer"]
            st = state if attempt == 1 else t.state0
            return t.run(state=st,
                         resume=cfg.load_model or resume_now,
                         **run_kwargs)

        def describe(attempt, exc=None):
            rec = getattr(box["trainer"], "obs_recorder", None)
            jsonl_path = getattr(rec, "jsonl_path", None)
            run_id = getattr(rec, "run_id", "") or ""
            ridx = getattr(rec, "_last_index", -1)
            if not isinstance(ridx, int):
                ridx = -1
            extra = []
            if attempt <= max(0, cfg.max_restarts):
                extra = ladder_records(cfg, attempt, run_id=run_id,
                                       ridx=ridx, engine="cpc")
            return jsonl_path, run_id, extra

        state, history = supervise(
            run_attempt, max_restarts=cfg.max_restarts,
            backoff_base=cfg.restart_backoff, seed=cfg.seed,
            describe=describe)
        trainer = box["trainer"]
    else:
        state, history = trainer.run(
            state=state, resume=cfg.load_model and midrun is not None,
            **run_kwargs)
    print("Finished Training")
    common.print_obs_artifact(trainer)
    if cfg.save_model:
        save_checkpoint(ckpt, state._asdict(), meta={"rounds": len(history)})
        print(f"saved checkpoint -> {ckpt}")
    return state, history


if __name__ == "__main__":
    main()
