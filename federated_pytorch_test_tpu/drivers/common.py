"""Shared CLI plumbing for the classifier drivers.

Factors out the ~120-line skeleton the reference duplicates across its six
CIFAR scripts (SURVEY.md "Shared driver skeleton"): flags, data partition,
model choice, common init, engine construction, final checkpoint.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Optional

from federated_pytorch_test_tpu.compress import COMPRESS_CHOICES
from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.parallel.comm import ROBUST_AGG_CHOICES
from federated_pytorch_test_tpu.models.resnet import ResNet9, ResNet18
from federated_pytorch_test_tpu.models.simple import Net, Net1, Net2
from federated_pytorch_test_tpu.train.algorithms import Algorithm
from federated_pytorch_test_tpu.train.config import FederatedConfig
from federated_pytorch_test_tpu.train.engine import BlockwiseFederatedTrainer
from federated_pytorch_test_tpu.utils.checkpoint import load_checkpoint, save_checkpoint


def build_parser(defaults: FederatedConfig, prog: str) -> argparse.ArgumentParser:
    """Argparse over the FederatedConfig fields, reference knob names kept."""
    p = argparse.ArgumentParser(
        prog=prog,
        description="TPU-native federated CIFAR10 driver "
                    "(reference parity: see module docstring)")
    # converters for Optional[...] fields (default None carries no type)
    _optional_types = {"data_dir": str, "num_devices": int,
                       "profile_dir": str, "obs_dir": str}
    # tri-state booleans: absent -> None (auto), --flag/--no-flag override
    _optional_bools = {"device_data", "donate"}
    for f in dataclasses.fields(FederatedConfig):
        default = getattr(defaults, f.name)
        arg = "--" + f.name.replace("_", "-")
        if f.name in _optional_bools or isinstance(default, bool):
            p.add_argument(arg, action=argparse.BooleanOptionalAction,
                           default=default)
        elif f.name == "optimizer":
            p.add_argument(arg, choices=("adam", "lbfgs"), default=default)
        elif f.name == "norm":
            p.add_argument(arg, choices=("batch", "group"), default=default)
        elif f.name == "compress":
            p.add_argument(arg, choices=COMPRESS_CHOICES, default=default)
        elif f.name == "robust_agg":
            p.add_argument(arg, choices=ROBUST_AGG_CHOICES, default=default)
        elif f.name == "fault_spec":
            p.add_argument(
                arg, type=str, default=default, metavar="SPEC",
                help="fault-injection spec: 'none' or "
                     "drop=P,straggle=P,corrupt=P,mode=nan|inf|signflip|"
                     "scale|innerprod|collude,scale=X,seed=N,clients=i+j,"
                     "delay=P,delay_max=N,join=P,leave=P,preempt=P "
                     "(train/faults.py; delay= drives --async-rounds "
                     "arrival times; join=/leave= drive the membership "
                     "ledger, preempt= simulates mid-run preemption)")
        elif f.name == "campaign_spec":
            p.add_argument(
                arg, type=str, default=default, metavar="SPEC",
                help="soak-campaign schedule (campaign/schedule.py): "
                     "'none' or hours=H,round_minutes=M,diurnal=A,"
                     "drop=P,straggle=P,corrupt=P,mode=...,join=P,"
                     "leave=P,storm=P,storm_len=N,storm_straggle=P,"
                     "burst=P,burst_len=N,burst_corrupt=P,"
                     "preempt_at=H1+H2,seed=N,accel=X,"
                     "health_window_hours=H — compiles diurnal load, "
                     "churn waves, straggler storms, corruption bursts "
                     "and deterministic preemptions onto the seeded "
                     "fault families; mutually exclusive with "
                     "--fault-spec (README 'Soak campaigns')")
        elif f.name == "model":
            p.add_argument(arg, choices=MODEL_CHOICES, default=default)
        elif f.name == "health_action":
            from federated_pytorch_test_tpu.obs.health import HEALTH_ACTIONS
            p.add_argument(
                arg, choices=HEALTH_ACTIONS, default=default,
                help="streaming watchdog response (obs/health.py): warn "
                     "emits alert records, abort raises RunHealthAbort, "
                     "checkpoint-abort saves+verifies a final checkpoint "
                     "first (default: warn)")
        elif f.name == "control":
            from federated_pytorch_test_tpu.control.policy import (
                CONTROL_MODES,
            )
            p.add_argument(
                arg, choices=CONTROL_MODES, default=default,
                help="closed-loop control plane (control/): observe "
                     "records deterministic intervention decisions, act "
                     "applies them; replay with python -m "
                     "federated_pytorch_test_tpu.control.replay "
                     "(default: off — bit-identical to no controller)")
        elif f.name == "control_policy":
            from federated_pytorch_test_tpu.control.policy import (
                CONTROL_POLICIES,
            )
            p.add_argument(
                arg, choices=CONTROL_POLICIES, default=default,
                help="hysteresis preset for --control decisions "
                     "(control/policy.py; default: default)")
        elif f.name == "cohort_sampling":
            from federated_pytorch_test_tpu.population import (
                SAMPLER_CHOICES,
            )
            p.add_argument(
                arg, choices=SAMPLER_CHOICES, default=default,
                help="population cohort sampler (population/sampler.py): "
                     "uniform, weighted (seeded static availability "
                     "weights) or stratified (one id per contiguous "
                     "stratum); only meaningful with --population > 0 "
                     "(default: uniform)")
        elif default is None:
            conv = _optional_types.get(f.name)
            if conv is None:
                raise TypeError(
                    f"FederatedConfig.{f.name} has default None; add its "
                    "converter to _optional_types in drivers/common.py")
            p.add_argument(arg, type=conv, default=None)
        else:
            p.add_argument(arg, type=type(default), default=default)
    # data-size overrides for smoke runs (not in the reference)
    p.add_argument("--n-train", type=int, default=None,
                   help="cap samples per client (smoke tests)")
    p.add_argument("--n-test", type=int, default=None,
                   help="cap test-set size (smoke tests)")
    return p


def config_from_args(args: argparse.Namespace) -> FederatedConfig:
    kw = {f.name: getattr(args, f.name) for f in dataclasses.fields(FederatedConfig)}
    return FederatedConfig(**kw)


def default_obs_dir(cfg: FederatedConfig) -> FederatedConfig:
    """Driver-entry observability default: file telemetry ON.

    A driver run with no ``--obs-dir`` writes its JSONL under
    ``<checkpoint_dir>/obs`` (``--obs-sinks none`` opts out); bare
    engine-API callers (unit tests) keep the file-free ``auto``+None
    behaviour.  Summarise with
    ``python -m federated_pytorch_test_tpu.obs.report <file>``.
    """
    if cfg.obs_dir is None and cfg.obs_sinks == "auto":
        cfg = dataclasses.replace(
            cfg, obs_dir=os.path.join(cfg.checkpoint_dir, "obs"))
    return cfg


def setup_runtime(cfg: FederatedConfig) -> None:
    """One driver-entry chokepoint, called before the first device query:
    enable the shared persistent compile cache (TPU compiles of the
    per-block epoch dominate cold runs), join the multi-host runtime when
    requested, and honor the ``use_tpu`` platform gate (``apply_platform``).
    Every CLI main routes through here (the CPC main passes its argparse
    namespace — only ``.use_tpu`` is read)."""
    from federated_pytorch_test_tpu.utils.compile_cache import (
        enable_persistent_compile_cache,
    )

    enable_persistent_compile_cache()
    apply_platform(cfg)


def apply_platform(cfg: FederatedConfig) -> None:
    """Honor ``use_tpu`` (the reference's ``use_cuda`` gate,
    federated_multi.py:32): when False, run on the host CPU platform.
    Must be called before the first JAX device query; a backend that is
    already up on another platform is an error — the run would otherwise
    carry on where the flag said not to.

    Also joins the multi-host runtime first when ``FEDTPU_DISTRIBUTED=1``
    (parallel/mesh.py:initialize_multihost).  Drivers reach this via
    ``setup_runtime``.
    """
    from federated_pytorch_test_tpu.parallel.mesh import initialize_multihost

    initialize_multihost()
    if cfg.use_tpu:
        return
    import jax
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "--no-use-tpu requested but the JAX backend is already "
            f"initialized on {jax.default_backend()!r}; select the platform "
            "before the first device query (or export JAX_PLATFORMS=cpu)")


def device_banner() -> str:
    """``platform=... device_kind=... device_count=...`` as JAX reports
    the default backend — every driver prints it, so a run that landed on
    the wrong platform says so in its first line."""
    import jax

    dev = jax.devices()[0]
    return (f"platform={dev.platform} device_kind={dev.device_kind!r} "
            f"device_count={len(jax.devices())}")


# the single model registry: argparse choices and pick_model both derive
# from it, so the two cannot drift
_MODELS = {"net": Net, "net1": Net1, "net2": Net2,
           "resnet9": ResNet9, "resnet18": ResNet18}
MODEL_CHOICES = ("auto",) + tuple(_MODELS)


def pick_model(cfg: FederatedConfig):
    """Classifier model from cfg.model (the reference's source-edit model
    switch, federated_multi.py:92-97, as a flag); "auto" keeps the
    use_resnet semantics."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if cfg.bf16 else None
    name = cfg.model
    if name == "auto":
        name = "resnet18" if cfg.use_resnet else "net"
    if name not in _MODELS:
        raise ValueError(f"unknown model {name!r}; "
                         f"expected one of {MODEL_CHOICES}")
    if name.startswith("resnet"):
        return _MODELS[name](dtype=dtype, norm=cfg.norm)
    return _MODELS[name](dtype=dtype)


def make_trainer(cfg: FederatedConfig, algorithm: Algorithm,
                 n_train: Optional[int] = None,
                 n_test: Optional[int] = None) -> BlockwiseFederatedTrainer:
    model = pick_model(cfg)
    data = FederatedCifar10(
        K=cfg.K, batch=cfg.default_batch, biased_input=cfg.biased_input,
        drop_last_sample=cfg.drop_last_sample, data_dir=cfg.data_dir,
        limit_per_client=n_train, limit_test=n_test)
    return BlockwiseFederatedTrainer(model, cfg, data, algorithm)


def checkpoint_path(cfg: FederatedConfig, name: str) -> str:
    return os.path.join(cfg.checkpoint_dir, name)


def finish(trainer: BlockwiseFederatedTrainer, state, name: str, history):
    """Save the end-of-run checkpoint (reference federated_multi.py:226-233).

    Saves the optimizer state of the final block alongside the model, as the
    reference does (:231 stores optimizer.state_dict()); like the reference,
    ``maybe_load`` restores model variables only (:99-103)."""
    cfg = trainer.cfg
    if cfg.save_model:
        meta = {"rounds": len(history)}
        opt_state = state.opt_state if state.opt_state is not None else ()
        save_checkpoint(checkpoint_path(cfg, name),
                        state._asdict() | {"opt_state": opt_state}, meta)
        print(f"saved checkpoint -> {checkpoint_path(cfg, name)}")


def maybe_load(trainer: BlockwiseFederatedTrainer, name: str):
    """Resume model params if --load-model (reference :99-103 restores model
    state only; we restore params + batch_stats)."""
    cfg = trainer.cfg
    state = trainer.init_state()
    path = checkpoint_path(cfg, name)
    if cfg.load_model and os.path.isdir(os.path.abspath(os.path.expanduser(path))):
        restored, meta = load_checkpoint(path, like=None)
        from federated_pytorch_test_tpu.parallel.mesh import (
            client_sharding,
            stage_tree_global,
        )
        csh = client_sharding(trainer.mesh)
        state = state._replace(
            params=stage_tree_global(restored["params"], csh),
            batch_stats=stage_tree_global(restored["batch_stats"], csh))
        rounds_prior = int(meta.get("rounds", 0)) if meta else 0
        print(f"loaded checkpoint <- {path} (rounds={rounds_prior})")
    return state


def print_obs_artifact(trainer) -> None:
    """Point the operator at the run's JSONL telemetry (if any)."""
    rec = getattr(trainer, "obs_recorder", None)
    if rec is not None and rec.jsonl_path:
        print(f"obs artifact -> {rec.jsonl_path} "
              f"(python -m federated_pytorch_test_tpu.obs.report "
              f"{rec.jsonl_path})")


def run_classifier_driver(prog: str, defaults: FederatedConfig,
                          algorithm: Algorithm, independent: bool = False,
                          argv=None):
    args = build_parser(defaults, prog).parse_args(argv)
    cfg = default_obs_dir(config_from_args(args))
    setup_runtime(cfg)
    trainer = make_trainer(cfg, algorithm, args.n_train, args.n_test)
    trainer.obs_run_name = prog
    mname = type(trainer.model).__name__
    if mname == "ResNet":
        mname = f"ResNet{trainer.model.qualifier}"
    print(f"{prog}: K={cfg.K} model={mname} "
          f"devices={trainer.D} clients/device={trainer.K_local} "
          f"data={trainer.data.source} {device_banner()}")
    state = maybe_load(trainer, prog)
    if independent:
        state, history = trainer.run_independent(state)
    else:
        supervised = cfg.max_restarts > 0
        campaign = getattr(cfg, "campaign_spec", "none") not in (
            "none", "", None)
        # supervision is resume-from-checkpoint: a restart budget (or a
        # campaign, whose deterministic preemptions need a resume point)
        # forces the mid-run checkpoint on even without
        # --midrun-checkpoint
        ck = (checkpoint_path(cfg, prog + "_midrun")
              if (cfg.midrun_checkpoint or supervised or campaign)
              else None)
        if supervised or campaign:
            def build_trainer(c, attempt):
                nonlocal trainer
                if attempt > 1:
                    # the failed attempt's trainer is closed (staging
                    # pool shut down); rebuild on the (possibly
                    # ladder-degraded) config
                    trainer = make_trainer(c, algorithm,
                                           args.n_train, args.n_test)
                    trainer.obs_run_name = prog
                return trainer

            if campaign:
                from federated_pytorch_test_tpu.campaign.harness import (
                    run_soak,
                )

                (state, history), clock = run_soak(
                    build_trainer, cfg, ck, state=state,
                    resume=cfg.load_model, run_name=prog)
                print(f"soak campaign done: {clock!r}")
            else:
                from federated_pytorch_test_tpu.control.supervisor import (
                    supervise_classifier,
                )

                state, history = supervise_classifier(
                    build_trainer, cfg, ck, state=state,
                    resume=cfg.load_model)
        else:
            state, history = trainer.run(
                state, checkpoint_path=ck,
                resume=cfg.load_model and ck is not None)
    print("Finished Training")
    print_obs_artifact(trainer)
    finish(trainer, state, prog, history)
    return state, history
