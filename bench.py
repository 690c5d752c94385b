"""Headline benchmark: federated CIFAR10 training throughput on TPU.

``python bench.py`` is ONE process.  It touches JAX once, names the
device it found (``platform`` / ``device_kind`` / ``device_count``), and
measures only on a TPU: without one, on a ``device_kind`` that is not in
the peaks table, or when any measurement phase raises, it exits non-zero
and prints no artifact.  There is no CPU fallback for the measured path —
a number taken on the CPU backend is not a speed.  On success it prints
one JSON line:

  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "platform": "tpu", "device_kind": "...", "device_count": N,
   "staging": "device"|"host", "stem_block_ips_chip": N,
   "big_block_ips_chip": N, "big_block_N": N, "no_consensus_ips_chip": N,
   "mfu": N,
   "infonce_pallas_us": N, "infonce_xla_us": N, "infonce_speedup": N,
   "infonce_grad_pallas_us": N, "infonce_grad_xla_us": N,
   "infonce_grad_speedup": N, ...}

The reference publishes no quantitative numbers (BASELINE.md); the
driver-set target is >=5,000 CIFAR10 images/sec/chip for the consensus
ResNet18 config (BASELINE.json), so ``vs_baseline`` is value / 5000.

HEADLINE (``value``): sustained throughput of one FULL consensus round on
the largest ResNet18 partition — Nepoch=1 local epoch + ADMM collective +
dual update + z write-back, INCLUDING the per-epoch staging a production
round pays.  With the default device-resident data path (train/engine.py
``_setup_device_data``: raw uint8 shards live in HBM, each epoch is an
on-device permutation gather) staging is device-side work; datasets over
the HBM budget fall back to host shuffle + H2D copy, which this same
timed region then measures.  This is what a user of the reference's
end-to-end loop (federated_multi.py:143-220) experiences.  Side fields
characterise the parts:

  * stem_block_ips_chip: local-epoch-only throughput on the stem block
    ci=0 (N=1,856), data staged once.  It flatters: gradient masking
    lets XLA prune most of the backward.
  * big_block_ips_chip: local-epoch-only throughput on the LARGEST
    ResNet18 partition (reference block [54,59], N=4,720,640), staged
    once.
  * no_consensus_ips_chip: full-net epoch (every parameter trainable,
    the no_consensus driver's path), staged once.

MFU is computed from ``no_consensus_ips_chip`` ONLY: with the whole net
trainable the executed graph is the full fwd + 2x bwd, so the analytic
ResNet18 model-FLOP count is the FLOPs actually executed (XLA's
cost_analysis undercounts fused TPU convolutions ~13x here, so the
analytic count is used).  Masked-block throughputs are NOT converted to
MFU — their backward is partially pruned and any full-FLOP MFU would
overstate sustained throughput.

The infonce_* fields time the Pallas-fused CPC loss kernel against its
XLA path (ops/infonce.py) — forward alone and value_and_grad (the CPC
LBFGS closure evaluates the latter, so the grad timing is the one the
training loop feels).  The cpc_* fields time one full federated-CPC
rotation (3 sub-models, every block, LBFGS closures) on synthetic LOFAR
cubes: ``cpc_rotation_seconds`` (warm) and ``cpc_patches_per_sec_chip``,
at the reduced dims recorded in ``cpc_config`` (see ``_bench_cpc``).
``FEDTPU_BENCH_CPC=0`` / ``FEDTPU_BENCH_VAE=0`` / ``FEDTPU_BENCH_COMPRESS=0``
skip a side group; a group that runs and raises fails the run.

Scale knobs (the artifact records what ran): ``FEDTPU_BENCH_CLIENTS_PER_CHIP``
/ ``FEDTPU_BENCH_BATCH`` / ``FEDTPU_BENCH_STEPS`` / ``FEDTPU_BENCH_REPS``.

The ``--smoke`` / ``--population-bench`` / ``--soak`` / ``--serve-bench``
modes are CPU count-gates (byte models, replayed counts), not speeds;
they force the 8-device CPU mesh themselves.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

TARGET = 5000.0  # images/sec/chip (BASELINE.json north star)

_HEADLINE_METRIC = "cifar10_resnet18_consensus_full_round_throughput"

# peak dense bf16 FLOP/s per chip by device kind (public spec sheets);
# a kind that is not here is an error, never a default
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}

# analytic CIFAR ResNet18 step FLOPs/image: forward ~0.56 GMAC (3x3 stem
# @32x32: 1.8 MMAC; layer1 4x 3x3x64x64 @32x32: 151 MMAC; layers2-4 ~134
# MMAC each after stride-2 downsamples), train step ~3x forward (fwd +
# 2x bwd) at 2 FLOPs/MAC
_STEP_FLOPS_PER_IMAGE = 3 * 2 * 0.56e9

def _peak_flops(device_kind: str) -> float:
    for k, v in _PEAK_BF16.items():
        if device_kind.startswith(k):
            return v
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}; add it "
        "to _PEAK_BF16 with its source before benchmarking on it")


def _device_fields() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    the default backend — every printed result carries them."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def _bench_scale() -> tuple:
    """(clients_per_chip*n_chips, batch, steps, reps) — production scale
    with FEDTPU_BENCH_* overrides (the bench obs header records the
    knobs that were set)."""
    import jax

    n_chips = len(jax.devices())
    K = int(os.environ.get("FEDTPU_BENCH_CLIENTS_PER_CHIP", 16)) * n_chips
    batch = int(os.environ.get("FEDTPU_BENCH_BATCH", 128))
    steps = int(os.environ.get("FEDTPU_BENCH_STEPS", 8))
    reps = int(os.environ.get("FEDTPU_BENCH_REPS", 5))
    return K, batch, steps, reps


#: RunRecorder for the current measurement suite (obs/): every timed
#: region emits one schema-validated round record into
#: artifacts/bench.jsonl, and the throughput fields the artifact
#: publishes are DERIVED from those records (report.record_ips), so the
#: JSONL is the primary perf evidence and the JSON artifact a view of it.
_BENCH_OBS = None


def _open_bench_obs(out: dict):
    """Open the bench RunRecorder (artifacts/bench.jsonl)."""
    global _BENCH_OBS
    from federated_pytorch_test_tpu.obs import make_recorder

    art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "artifacts")
    obs = make_recorder("jsonl", art, run_name="bench", engine="bench")
    obs.open(config={k: v for k, v in os.environ.items()
                     if k.startswith("FEDTPU_BENCH")})
    if obs.jsonl_path:
        out["obs_jsonl"] = os.path.join(
            "artifacts", os.path.basename(obs.jsonl_path))
    _BENCH_OBS = obs
    return obs


def _close_bench_obs(status: str = "completed") -> None:
    global _BENCH_OBS
    obs, _BENCH_OBS = _BENCH_OBS, None
    if obs is not None:
        obs.close(status=status)


#: last record built by _obs_emit_round — sections that publish a field
#: of the record (e.g. compression bytes/round) read it from here so the
#: artifact value and the telemetry value share one source
_LAST_OBS_ROUND: dict = {}


def _obs_emit_round(**fields) -> dict:
    """Emit one bench timed-region record; returns the record either way
    so callers derive their published numbers from it (record_ips)."""
    obs = _BENCH_OBS
    rec = dict(fields)
    _LAST_OBS_ROUND.clear()
    _LAST_OBS_ROUND.update(rec)
    if obs is not None and obs.enabled:
        idx = getattr(obs, "_bench_next_index", 0)
        obs._bench_next_index = idx + 1
        emitted = obs.round(dict(rec, round_index=idx))
        if emitted is not None:
            return emitted
    return rec


def _bench_round(trainer, ci, *, reps, with_comm=False, with_staging=False,
                 label=None):
    """images/sec/chip for block ci's local epoch under ``trainer``'s
    algorithm.  ``with_comm`` adds the comm round (+write-back) per
    rep; ``with_staging`` pays the per-epoch staging inside the timed
    region, exactly as a production round does — an on-device
    permutation gather under the default device-resident data path,
    or host shuffle + uint8 H2D copy on the fallback.

    The timed region lands in the bench obs JSONL as one round record
    (``label`` names it) and the returned throughput is computed FROM
    that record, so artifact and telemetry cannot disagree.

    Module-level (not a closure of ``_measure``) so the VAE and
    compression sections bench their trainers through the identical
    timed region."""
    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.parallel.mesh import (
        client_sharding,
        replicated_sharding,
    )

    K = trainer.cfg.K
    images_per_epoch = K * trainer.data.steps * trainer.data.batch
    csh = client_sharding(trainer.mesh)
    rsh = replicated_sharding(trainer.mesh)
    # epoch prefetch (the production path) stays on only when staging
    # is part of the measurement; otherwise the worker thread would
    # build a never-consumed epoch during the timed region
    trainer._prefetch_epochs = with_staging
    if not with_staging:        # with_staging re-stages inside the loop
        xb, yb, wb = trainer._stage_epoch()
        keys = trainer._epoch_keys()
    train_epoch, comm_fns, init_opt = trainer._build_fns(ci)
    N = trainer.block_size(ci)
    state = trainer.init_state()
    state = state._replace(opt_state=init_opt(state.params),
                           comp=trainer._init_comp_state(ci))
    # a non-communicating algorithm ignores z/y (penalty 0): keep them
    # token-sized exactly like engine.run_independent does
    zdim = N if trainer.algo.communicates else 1
    ydim = N if trainer.algo.needs_dual else 1
    z = jax.device_put(jnp.zeros((zdim,), jnp.float32), rsh)
    y = jax.device_put(jnp.zeros((K, ydim), jnp.float32), csh)
    rho = jax.device_put(jnp.float32(trainer.cfg.admm_rho0), rsh)
    x0 = jax.device_put(jnp.zeros((K, 1), jnp.float32), csh)
    yhat0 = jax.device_put(jnp.zeros((K, 1), jnp.float32), csh)

    # every loop-carried array is threaded THROUGH round_ and rebound,
    # x0/yhat0 included: with --donate the comm fn donates all six block
    # vars, so reusing a stale captured buffer on the next rep would hit
    # a deleted-array error (and silently measure nothing on backends
    # that tolerate it)
    def round_(state, z, y, rho, x0, yhat0):
        if with_staging:
            bx, by, bw = trainer._stage_epoch()
            ks = trainer._epoch_keys()
        else:
            bx, by, bw, ks = xb, yb, wb, keys
        state, losses = train_epoch(state, y, trainer.client_norm, ks,
                                    bx, by, bw, z, rho,
                                    trainer._ones_mask)
        diag, extras = None, ()
        if with_comm:
            # the comm fn's output is variadic past the base 7-tuple
            # (client-ledger probes, guard verdicts); keep the tail so
            # the last rep's per-client norms can land in the artifact
            outs = comm_fns["plain"](
                state, z, y, rho, x0, yhat0, trainer._ones_mask,
                trainer._zero_corrupt, trainer._inf_bound)
            state, z, y, rho, x0, yhat0, diag = outs[:7]
            extras = outs[7:]
        return state, z, y, rho, x0, yhat0, losses, diag, extras

    def sync(losses, diag, extras=()):
        # a host fetch of values that depend on the full computation
        # closes out every dispatched rep
        np.asarray(losses)
        if diag is not None:
            jax.tree.map(np.asarray, diag)

    # warm-up / compile
    carry = round_(state, z, y, rho, x0, yhat0)
    sync(*carry[6:])

    t0 = time.perf_counter()
    for _ in range(reps):
        carry = round_(*carry[:6])
    sync(*carry[6:])
    dt = time.perf_counter() - t0

    from federated_pytorch_test_tpu.obs.report import record_ips

    fields = dict(label=label or f"block_{ci}", N=int(N), K=int(K),
                  round_seconds=dt, images=reps * images_per_epoch,
                  nadmm=reps,
                  # schema-v5 span bounds: the timed region itself (the
                  # recorder derives t_end = t_start + round_seconds, and
                  # obs/trace.py exports it to a Chrome trace timeline)
                  t_start=t0,
                  # jitted dispatches the host issued inside the timed
                  # region: one epoch (+ one comm) per rep — the fused
                  # engine path collapses the same work to 1/round
                  host_dispatches=reps * (2 if with_comm else 1))
    if trainer._sentinel is not None:
        # cumulative across the trainer: any growth between sections
        # means a timed region recompiled mid-measurement
        fields["jit_retraces"] = trainer._sentinel.retraces
    # sync/async throughput must be distinguishable in the artifact:
    # async rounds skip the per-round barrier, so their img/s is not
    # comparable to a synchronous number with the same label
    fields["async_mode"] = bool(trainer.cfg.async_rounds)
    if trainer.cfg.async_rounds:
        fields["max_staleness"] = int(trainer.cfg.max_staleness)
        fields["admission_rejected"] = int(trainer._async_rejected)
    # elastic federation: a churned roster changes the work per round, so
    # the live-member count must ride next to any throughput number
    if trainer.faults.churn_enabled:
        fields["members_active"] = int(trainer._members.sum())
    if with_comm and trainer.algo.communicates:
        fields["bytes_on_wire"] = reps * trainer.round_bytes_on_wire(N, K)
        fields["bytes_dense"] = reps * 4 * N * K
    rec = _obs_emit_round(**fields)
    _emit_client_grain(trainer, rec, carry[8], N, K, with_comm)
    return record_ips(rec, trainer.D)


#: per-client aggregates from the most recent comm-bearing timed region
#: (cleared on each _bench_round) — _measure publishes them into the
#: artifact so the bench.jsonl client record and the JSON summary agree
_LAST_CLIENT_AGG: dict = {}


def _emit_client_grain(trainer, rec, extras, N, K, with_comm) -> None:
    """Land the last rep's client-ledger probe outputs as a ``client``
    record next to the bench round record, plus host-side aggregates
    (norm skew, bytes per client) for the artifact summary."""
    _LAST_CLIENT_AGG.clear()
    if not (with_comm and getattr(trainer, "_client_probe", False)
            and len(extras) >= 2):
        return
    cl_nrm = np.asarray(extras[0], np.float64)
    cl_dist = np.asarray(extras[1], np.float64)
    bytes_per_client = int(trainer.round_bytes_on_wire(N, 1))
    finite = cl_nrm[np.isfinite(cl_nrm)]
    med = float(np.median(finite)) if finite.size else 0.0
    agg = {
        "client_norm_max": round(float(finite.max()), 6)
        if finite.size else None,
        "client_norm_median": round(med, 6) if finite.size else None,
        # max/median spread of per-client update norms: ~1 means the
        # synthetic shards pull evenly; a big skew means one client
        # dominates the consensus step
        "client_norm_skew": round(float(finite.max()) / med, 4)
        if finite.size and med > 0 else None,
        "client_bytes": bytes_per_client,
        "clients": int(K),
    }
    _LAST_CLIENT_AGG.update(agg)
    obs = _BENCH_OBS
    if obs is not None and obs.enabled:
        from federated_pytorch_test_tpu.obs.clients import (
            client_round_fields,
        )
        obs.client_event(client_round_fields(
            int(rec.get("round_index", 0)), int(K),
            update_norm=cl_nrm, dist_z=cl_dist,
            payload_bytes=bytes_per_client))


def _measure(out: dict) -> None:
    """All measurements, in this process.  A phase that raises is not
    caught: the run fails and no artifact is printed."""
    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
    from federated_pytorch_test_tpu.models.resnet import ResNet18
    from federated_pytorch_test_tpu.train import (
        AdmmConsensus,
        BlockwiseFederatedTrainer,
        FederatedConfig,
        NoConsensus,
    )

    K, batch, steps, reps = _bench_scale()
    _open_bench_obs(out)

    # retrace sentinel is free after compile (the counting wrapper only
    # runs when jit traces) and turns a silent recompile regression into
    # a visible nonzero jit_retraces field in the artifact
    cfg = FederatedConfig(K=K, default_batch=batch, check_results=False,
                          use_resnet=True, admm_rho0=0.1, bf16=True,
                          retrace_sentinel=True)
    data = FederatedCifar10(K=K, batch=batch,
                            limit_per_client=steps * batch, limit_test=batch)
    # bf16 conv/dense compute (params, BN and head stay f32) feeds the MXU
    # at full rate: ~1.5x over f32 on v5e
    trainer = BlockwiseFederatedTrainer(ResNet18(dtype=jnp.bfloat16), cfg,
                                        data, AdmmConsensus())

    def bench_block(trainer, ci, reps=reps, **kw):
        return _bench_round(trainer, ci, reps=reps, **kw)

    # block sizes across the sweep; biggest = reference block [54,59]
    sizes = [trainer.block_size(ci) for ci in range(trainer.L)]
    big_ci = int(np.argmax(sizes))
    out["big_block_N"] = sizes[big_ci]
    # which staging path the headline's timed region pays (engine auto:
    # device-resident when the raw shards fit the HBM budget)
    out["staging"] = ("device" if trainer._dev_gather is not None
                      else "host")

    out["stem_block_ips_chip"] = round(
        bench_block(trainer, 0, label="stem_block"), 1)
    out["big_block_ips_chip"] = round(
        bench_block(trainer, big_ci, label="big_block"), 1)

    # HEADLINE: the full production consensus round on the biggest block,
    # staging included
    headline = bench_block(trainer, big_ci, with_comm=True,
                           with_staging=True, label="headline_full_round")
    out["value"] = round(headline, 1)
    out["vs_baseline"] = round(headline / TARGET, 3)
    out["measured"] = True
    # nonzero here = the headline's timed reps recompiled (perf numbers
    # then include trace time and are not comparable run-to-run)
    out["jit_retraces"] = trainer._sentinel.retraces
    # elastic-federation posture of this run: whether reshape resume and
    # bounded barriers were armed, and whether any collective actually
    # tripped the timeout (nonzero = the numbers above span a reshape)
    from federated_pytorch_test_tpu.parallel.mesh import (
        barrier_timeout, collective_timeout_count)
    out["elastic"] = {
        "elastic_resume": bool(trainer.cfg.elastic_resume),
        "barrier_timeout_s": float(barrier_timeout()),
        "collective_timeouts": int(collective_timeout_count()),
        "members_joined": int(trainer._members_joined),
        "members_left": int(trainer._members_left),
    }
    # client-grain summary of the headline round (the comm-bearing timed
    # region): norm dispersion across the K shards + bytes each client
    # ships per round; the per-client vectors are in bench.jsonl as a
    # ``client`` record (see obs/clients.py)
    if _LAST_CLIENT_AGG:
        out["client_grain"] = dict(_LAST_CLIENT_AGG)

    # full-net epoch (the no_consensus driver's path): every parameter
    # trainable and NO consensus penalty, so the executed graph is the
    # full fwd + 2x bwd — the ONLY config whose analytic FLOP count equals
    # executed FLOPs, hence the MFU basis
    trainer_nc = BlockwiseFederatedTrainer(ResNet18(dtype=jnp.bfloat16),
                                           cfg, data, NoConsensus())
    full_net = bench_block(trainer_nc, None, label="no_consensus_full_net")
    out["no_consensus_ips_chip"] = round(full_net, 1)
    out["mfu"] = round(full_net * _STEP_FLOPS_PER_IMAGE
                       / _peak_flops(out["device_kind"]), 4)

    out.update(_bench_infonce())
    if os.environ.get("FEDTPU_BENCH_CPC") != "0":
        out.update(_bench_cpc())
    if os.environ.get("FEDTPU_BENCH_VAE") != "0":
        out.update(_bench_vae())
    if os.environ.get("FEDTPU_BENCH_COMPRESS") != "0":
        out.update(_bench_compression(cfg, data, big_ci))

    # persistent-cache + cost-ledger attribution
    from federated_pytorch_test_tpu.utils.compile_cache import cache_stats

    out["compile_cache"] = cache_stats()
    ledger = trainer._ledger
    if ledger is not None:
        totals = ledger.totals()
        out["compile_events"] = totals["compile_events"]
        out["compile_seconds"] = round(totals["compile_seconds"], 3)


def _bench_cpc() -> dict:
    """One full federated-CPC rotation (3 sub-models, every block, K=4
    clients, LBFGSNew(h=7, m=2), Niter=10 fresh minibatches — the
    reference loop shape, federated_cpc.py:194-304) on synthetic LOFAR
    visibility cubes.  Reports wall-clock for the warm rotation (a
    warm-up rotation pays the compiles) and the patch throughput the
    LBFGS closures sustain; the artifact records the dims it ran at.

    Defaults to Lc=64, batch 32 — NOT the reference's Lc=256/batch 128:
    see README "Known issues" for what the composed CPC round costs to
    compile at reference width.  The reduced dims compile in seconds and
    exercise the identical graph shape.  Override with
    FEDTPU_BENCH_CPC_LC / FEDTPU_BENCH_CPC_BATCH (e.g. 256/128 for
    reference width); skip entirely with FEDTPU_BENCH_CPC=0."""
    from federated_pytorch_test_tpu.data.lofar import CPCDataSource
    from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

    Lc = int(os.environ.get("FEDTPU_BENCH_CPC_LC", 64))
    batch = int(os.environ.get("FEDTPU_BENCH_CPC_BATCH", 32))
    # reference pairing: Rc=32 at Lc=256 (federated_cpc.py:27-29);
    # scale Rc down with Lc below that
    Rc, niter = min(32, max(Lc // 4, 8)), 10
    src = CPCDataSource([f"bench{i}.h5" for i in range(4)], ["0"] * 4,
                        batch_size=batch, patch_size=32)
    trainer = CPCTrainer(src, latent_dim=Lc, reduced_dim=Rc,
                         lbfgs_history=7, lbfgs_max_iter=2, Niter=niter,
                         num_devices=1)
    # patches per staged minibatch (batch_size * patchx * patchy)
    px, py, y0 = src.minibatch(0)
    patches_per_batch = int(y0.shape[0])

    def rotation():
        t0 = time.perf_counter()
        state, hist = trainer.run(Nloop=1, Nadmm=1, log=lambda m: None)
        # the run's own per-round fetches sync each round, but the FINAL
        # round's write-back is still in flight at return: close it out
        # so the rotation time covers all dispatched work
        jax.block_until_ready(state)
        return time.perf_counter() - t0, hist

    rotation()                       # warm-up: pays the LBFGS compiles
    dt, hist = rotation()
    # every (model, block) round runs Niter minibatches on each of the
    # trainer.K clients; clients run data-parallel across the trainer's
    # OWN mesh (trainer.D devices), so that is the per-chip divisor
    patches = len(hist) * niter * trainer.K * patches_per_batch
    return {
        "cpc_rotation_seconds": round(dt, 2),
        "cpc_patches_per_sec_chip": round(patches / dt / trainer.D, 1),
        "cpc_rounds": len(hist),
        "cpc_config": f"Lc={Lc},Rc={Rc},batch={batch},Niter={niter}",
    }


def _bench_vae() -> dict:
    """Round throughput of the two VAE workloads (federated_vae /
    federated_vae_cl drivers) at the headline scale: largest-layer local
    epoch + FedAvg collective + write-back, data staged once.  The plain
    VAE sweeps layers under Adam; the clustering VAE's encoder block runs
    the LBFGS closure path, so its number carries the line-search cost the
    reference driver pays (federated_vae_cl.py:200-205).  Skip with
    FEDTPU_BENCH_VAE=0."""
    from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
    from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN
    from federated_pytorch_test_tpu.models.vae_cl import AutoEncoderCNNCL
    from federated_pytorch_test_tpu.train import FederatedConfig
    from federated_pytorch_test_tpu.train.algorithms import FedAvg
    from federated_pytorch_test_tpu.train.vae_engine import (
        VAECLTrainer,
        VAETrainer,
    )

    K, batch, steps, reps = _bench_scale()
    reps = max(2, reps // 2)        # side fields: bound the extra wall-clock
    data = FederatedCifar10(K=K, batch=batch,
                            limit_per_client=steps * batch, limit_test=batch)
    out = {}

    cfg = FederatedConfig(K=K, default_batch=batch, check_results=False)
    trainer = VAETrainer(AutoEncoderCNN(), cfg, data, FedAvg())
    sizes = [trainer.block_size(ci) for ci in range(trainer.L)]
    big_ci = int(np.argmax(sizes))
    out["vae_block_N"] = sizes[big_ci]
    out["vae_ips_chip"] = round(
        _bench_round(trainer, big_ci, reps=reps, with_comm=True,
                     label="vae_big_block"), 1)

    # reference clustering-VAE shape: Kc=10 clusters, Lc=32 latent,
    # lambda2=1e-3 (federated_vae_cl.py:12,22-23); encoder block ci=0
    # runs LBFGS
    cfg_cl = FederatedConfig(K=K, default_batch=batch, check_results=False,
                             lambda2=1e-3)
    trainer_cl = VAECLTrainer(AutoEncoderCNNCL(K=10, L=32), cfg_cl, data,
                              FedAvg())
    out["vaecl_block_N"] = trainer_cl.block_size(0)
    out["vaecl_ips_chip"] = round(
        _bench_round(trainer_cl, 0, reps=reps, with_comm=True,
                     label="vaecl_encoder_block"), 1)
    return out


def _bench_compression(cfg, data, big_ci) -> dict:
    """The compressed-communication settings (--compress) on the headline
    workload: full consensus round on the largest ResNet18 block at each
    setting, staged data, same timed region as ``big_block_ips_chip`` +
    comm — so ``compress_none_round_ips_chip`` is the dense comparator and
    the others show what the encode/decode work costs end-to-end.  Per
    setting: round throughput, measured uplink bytes/round (K clients x
    bytes_on_wire(N)), and a single-vector jitted encode+decode
    microbench (``*_encdec_us``).  Skip with FEDTPU_BENCH_COMPRESS=0."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.compress import make_compressor
    from federated_pytorch_test_tpu.models.resnet import ResNet18
    from federated_pytorch_test_tpu.train import (
        AdmmConsensus,
        BlockwiseFederatedTrainer,
    )

    _, _, _, reps = _bench_scale()
    reps = max(2, reps // 2)        # side fields: bound the extra wall-clock
    settings = (("none", {}),
                ("q8", {"compress": "q8"}),
                ("q4", {"compress": "q4"}),
                ("topk", {"compress": "topk", "topk_frac": 0.01,
                          "error_feedback": True}))
    out = {}
    for name, kw in settings:
        cfg_c = dataclasses.replace(cfg, **kw)
        trainer = BlockwiseFederatedTrainer(ResNet18(dtype=jnp.bfloat16),
                                            cfg_c, data, AdmmConsensus())
        N = trainer.block_size(big_ci)
        out.setdefault("compress_block_N", N)
        ips = _bench_round(trainer, big_ci, reps=reps, with_comm=True,
                           label=f"compress_{name}")
        out[f"compress_{name}_round_ips_chip"] = round(ips, 1)
        # published bytes/round come from the emitted obs record (the
        # timed region covers ``reps`` comm rounds)
        out[f"compress_{name}_bytes_round"] = (
            _LAST_OBS_ROUND["bytes_on_wire"] // reps
            if _LAST_OBS_ROUND.get("bytes_on_wire")
            else trainer.round_bytes_on_wire(N, cfg.K))
        if name != "none":       # encode+decode overhead in isolation
            comp = make_compressor(kw["compress"],
                                   topk_frac=kw.get("topk_frac", 0.01),
                                   quant_chunk=cfg.quant_chunk)
            st = comp.init_state(N, jax.random.key_data(jax.random.PRNGKey(0)))

            @jax.jit
            def encdec(v, st, comp=comp, N=N):
                payload, st = comp.encode(v, st)
                return comp.decode(payload, N), st

            v = jnp.asarray(np.random.default_rng(0).normal(size=(N,)),
                            jnp.float32)
            d, st2 = encdec(v, st)
            np.asarray(d)                              # compile + sync
            t0 = time.perf_counter()
            for _ in range(30):
                d, st = encdec(v, st)
            np.asarray(d)
            out[f"compress_{name}_encdec_us"] = round(
                (time.perf_counter() - t0) / 30 * 1e6, 1)
    return out


def _bench_infonce() -> dict:
    """Pallas-fused vs XLA InfoNCE (ops/infonce.py) at a grid-spanning
    shape (P=256 -> two row tiles; D=512): microseconds/call for the
    forward alone and for value_and_grad — the CPC LBFGS closure evaluates
    the latter on every (re-)evaluation, so the grad number is the one the
    training loop feels."""
    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.ops.infonce import (
        force_infonce_impl,
        info_nce_fused,
    )

    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(16, 16, 16, 32)).astype(np.float32))
    zh = jnp.asarray(rng.normal(size=(16, 16, 16, 32)).astype(np.float32))
    fwd_us, grad_us = {}, {}
    for impl in ("pallas", "xla"):
        with force_infonce_impl(impl):
            # fresh lambdas per impl: JAX's jaxpr cache is keyed on the
            # raw function object and does not see _FORCE_IMPL, so jitting
            # info_nce_fused directly would reuse the first impl's trace
            # for both timings
            fns = {
                "fwd": jax.jit(lambda a, b: info_nce_fused(a, b)),
                "grad": jax.jit(
                    lambda a, b: jax.value_and_grad(info_nce_fused,
                                                    argnums=(0, 1))(a, b)),
            }
            for name, fn in fns.items():
                jax.tree.map(np.asarray, fn(z, zh))    # compile + sync
                t0 = time.perf_counter()
                r = None
                for _ in range(30):
                    r = fn(z, zh)
                jax.tree.map(np.asarray, r)            # host fetch = sync
                us = (time.perf_counter() - t0) / 30 * 1e6
                (fwd_us if name == "fwd" else grad_us)[impl] = us
    return {
        "infonce_pallas_us": round(fwd_us["pallas"], 1),
        "infonce_xla_us": round(fwd_us["xla"], 1),
        "infonce_speedup": round(fwd_us["xla"] / fwd_us["pallas"], 3),
        "infonce_grad_pallas_us": round(grad_us["pallas"], 1),
        "infonce_grad_xla_us": round(grad_us["xla"], 1),
        "infonce_grad_speedup": round(grad_us["xla"] / grad_us["pallas"], 3),
    }


def main() -> int:
    from federated_pytorch_test_tpu.utils.compile_cache import (
        enable_persistent_compile_cache,
    )

    enable_persistent_compile_cache()
    out = {"metric": _HEADLINE_METRIC, "unit": "images/sec/chip",
           **_device_fields()}
    if out["platform"] != "tpu":
        print(f"bench: the measured path needs a TPU backend; JAX found "
              f"platform={out['platform']} device_kind="
              f"{out['device_kind']!r} device_count={out['device_count']}",
              file=sys.stderr)
        return 2
    _peak_flops(out["device_kind"])     # unknown kind: fail before timing
    try:
        _measure(out)
    except BaseException:
        _close_bench_obs(status="aborted")
        raise
    _close_bench_obs()
    out["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime())
    # which code produced this artifact (self-description);
    # --dirty so an uncommitted tree cannot masquerade as its HEAD
    out["git"] = _git_describe()
    out["baseline_ref"] = "BASELINE.json"
    print(json.dumps(out))
    return 0


def _git_describe() -> Optional[str]:
    try:
        return subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "describe", "--always", "--dirty"],
            capture_output=True, text=True, timeout=10).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


_SMOKE_BASELINE = "artifacts/SMOKE_BASELINE.json"
_SMOKE_METRIC = "smoke_fused_q8_wire_savings_ratio"


def _smoke_predicted() -> dict:
    """Pure-math predicted comm-path metrics at a STATIC geometry
    (N=8192, K=8, D=8, chunk=256) — no timing, no hardware, so the
    numbers are bit-reproducible on any CI box and a delta can only mean
    the byte model (compress/ payload shapes or ops/packed_reduce.py hop
    accounting) actually changed."""
    from federated_pytorch_test_tpu.compress import make_compressor
    from federated_pytorch_test_tpu.ops.packed_reduce import (
        fused_bytes_on_wire,
    )

    N, K, D, chunk = 8192, 8, 8, 256
    seg = -(-N // D)
    out = {"smoke_geometry": f"N={N},K={K},D={D},chunk={chunk}"}
    # dense comparator: the SAME butterfly movement pattern at f32 with
    # no scale sidecar — what an unfused all-reduce moves for this
    # geometry (2 phases x D devices x (D-1) hop-halves x f32 segment)
    out["smoke_dense_collective_wire_bytes"] = 2 * D * (D - 1) * seg * 4
    for name in ("q8", "q4"):
        comp = make_compressor(name, quant_chunk=chunk)
        out[f"smoke_fused_{name}_wire_bytes"] = int(
            fused_bytes_on_wire(comp, N, D, K))
        out[f"smoke_{name}_uplink_wire_bytes"] = K * comp.bytes_on_wire(N)
    topk = make_compressor("topk", topk_frac=0.01)
    out["smoke_fused_topk_wire_bytes"] = int(
        fused_bytes_on_wire(topk, N, D, K))
    # chunked robust aggregation (--robust-chunked): predicted per-device
    # gathered working set from the pure byte model — dense materializes
    # the [K, N] all-gather, chunked owns a [K, ceil(N/D)] segment slab
    # (parallel/comm.py robust_gather_bytes); the compiled
    # memory_analysis counterpart is gated below (_smoke_robust_memory)
    from federated_pytorch_test_tpu.parallel.comm import robust_gather_bytes
    for kind in ("trim", "krum"):
        dense = robust_gather_bytes(kind, K, N, D, chunked=False)
        chunk_b = robust_gather_bytes(kind, K, N, D, chunked=True)
        out[f"smoke_robust_{kind}_dense_gather_bytes"] = int(dense)
        out[f"smoke_robust_{kind}_chunked_gather_bytes"] = int(chunk_b)
        out[f"smoke_robust_{kind}_gather_savings_ratio"] = round(
            dense / chunk_b, 4)
    return out


def _smoke_robust_memory() -> dict:
    """Compiled-memory gate for the chunked robust-agg path: lower each
    estimator through jit on the forced 8-device CPU mesh at the static
    smoke geometry and read ``memory_analysis`` peak bytes (argument +
    output + temp, the obs/costs.py definition) for the dense all-gather
    formulation vs the ``--robust-chunked`` segment-owned one.  These are
    compiler facts, not timings — deterministic for a fixed jax/XLA
    build, so the committed-baseline diff holds them down like the
    predicted byte fields; the hard "chunked strictly lower" assertion
    lives in tests/test_comm_kernels.py."""
    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.parallel.comm import (
        make_robust_mean,
    )
    from federated_pytorch_test_tpu.parallel.mesh import (
        CLIENT_AXIS,
        client_mesh,
        shard_map,
    )

    P = jax.sharding.PartitionSpec
    N, K, D = 8192, 8, 8
    mesh = client_mesh(D)
    out = {}

    def peak(kind, chunked):
        mf = make_robust_mean(kind, trim_frac=0.1, chunked=chunked, D=D)
        fn = shard_map(lambda s, w: mf(s, w), mesh=mesh,
                       in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
                       out_specs=P(), check_vma=False)
        shapes = (jax.ShapeDtypeStruct((K, N), jnp.float32),
                  jax.ShapeDtypeStruct((K,), jnp.float32))
        stats = jax.jit(fn).lower(*shapes).compile().memory_analysis()
        return int(stats.argument_size_in_bytes
                   + stats.output_size_in_bytes
                   + stats.temp_size_in_bytes)

    for kind in ("trim", "krum"):
        out[f"smoke_robust_{kind}_dense_peak_device_bytes"] = peak(
            kind, False)
        out[f"smoke_robust_{kind}_chunked_peak_device_bytes"] = peak(
            kind, True)
    return out


def _smoke_engine_run() -> dict:
    """Tiny REAL engine run (``--compress q8 --fused-collective``) on the
    forced 8-device CPU mesh: proves the fused comm path executes
    end-to-end (shard_map butterfly, packed hops, telemetry) and
    publishes its deterministic byte fields for the gate; the wall-clock
    is info-only (CI boxes are too noisy to gate on)."""
    import flax.linen as nn

    from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
    from federated_pytorch_test_tpu.models.base import (
        BlockModule,
        elu,
        flatten,
        max_pool_2x2,
        pairs,
    )
    from federated_pytorch_test_tpu.train import (
        AdmmConsensus,
        BlockwiseFederatedTrainer,
        FederatedConfig,
    )

    class SmokeNet(BlockModule):
        @nn.compact
        def __call__(self, x, train=True):
            x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                         name="conv1")(x)))
            return nn.Dense(10, name="fc1")(flatten(x))

        def param_order(self):
            return pairs("conv1", "fc1")

        def train_order_block_ids(self):
            return [[0, 1], [2, 3]]

        def linear_layer_ids(self):
            return [1]

    K = 8
    cfg = FederatedConfig(K=K, Nloop=1, Nepoch=1, Nadmm=1, default_batch=16,
                          check_results=False, admm_rho0=0.1, seed=0,
                          compress="q8", fused_collective=True)
    data = FederatedCifar10(K=K, batch=16, limit_per_client=16,
                            limit_test=16)
    # informational wall-clock (compare direction 0): run() fetches the
    # round diagnostics to host before returning, which is sync enough
    t0 = time.perf_counter()  # graftlint: disable=JG104
    trainer = BlockwiseFederatedTrainer(SmokeNet(), cfg, data,
                                        AdmmConsensus())
    _, hist = trainer.run(log=lambda m: None)
    dt = time.perf_counter() - t0
    rec = next(r for r in hist if r.get("bytes_fused"))
    return {
        "smoke_engine_fused_wire_bytes": int(rec["bytes_fused"]),
        "smoke_engine_uplink_wire_bytes": int(rec["bytes_on_wire"]),
        "smoke_run_seconds": round(dt, 2),
    }


def _smoke() -> int:
    """``bench.py --smoke``: the no-TPU CI gate for the roofline comm
    path.  Emits a bench-shaped artifact (``artifacts/smoke.json``) whose
    headline is the predicted dense/q8-fused wire-byte ratio at a static
    geometry, plus the per-codec predicted byte fields and a tiny real
    engine run's telemetry, then diffs it against the committed
    ``artifacts/SMOKE_BASELINE.json`` via obs/compare.py — exit 1 on
    regression (ratio down, any ``*_wire_bytes`` up), exit 0 otherwise.
    ``measured`` is true in the bench-artifact sense of "this run
    produced its own numbers", but every gated field is deterministic
    byte accounting, not a timing (the unit string says so)."""
    # must land before this process's first jax import
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    out = {
        "metric": _SMOKE_METRIC,
        "unit": "x (dense/fused wire bytes, predicted)",
        "measured": True,
        "baseline_ref": _SMOKE_BASELINE,
    }
    out.update(_smoke_predicted())
    out["value"] = round(out["smoke_dense_collective_wire_bytes"]
                         / out["smoke_fused_q8_wire_bytes"], 4)
    try:
        out.update(_smoke_robust_memory())
    except Exception as e:      # noqa: BLE001 — predicted gate still runs
        out["error"] = f"smoke robust memory failed: {type(e).__name__}: {e}"
    try:
        out.update(_smoke_engine_run())
    except Exception as e:      # noqa: BLE001 — predicted gate still runs
        out["error"] = f"smoke engine run failed: {type(e).__name__}: {e}"
    out["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    out["git"] = _git_describe()
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "artifacts")
    path = os.path.join(art_dir, "smoke.json")
    try:
        os.makedirs(art_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    except OSError as e:
        print(f"bench: cannot write smoke artifact: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    if out.get("error"):
        return 1
    baseline = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            _SMOKE_BASELINE)
    if not os.path.exists(baseline):
        print(f"bench: no committed {_SMOKE_BASELINE}; smoke gate skipped "
              "(commit the emitted artifacts/smoke.json there to arm it)",
              file=sys.stderr)
        return 0
    from federated_pytorch_test_tpu.obs import compare as obs_compare

    return obs_compare.main([path, "--baseline", baseline,
                             "--threshold", "2"])


_POPULATION_BASELINE = "artifacts/POPULATION_BASELINE.json"
_POPULATION_METRIC = "population_sublinearity_savings_ratio"


def _population_round_seconds(population: int) -> float:
    """Steady-state per-round wall clock (median over the run's round
    records, which rides out the per-block compile rounds) for a tiny
    real engine with ``population`` registered clients sampled down to
    the fixed 8-slot cohort on the forced 8-device CPU mesh."""
    import numpy as np

    import flax.linen as nn

    from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
    from federated_pytorch_test_tpu.models.base import (
        BlockModule,
        elu,
        flatten,
        max_pool_2x2,
        pairs,
    )
    from federated_pytorch_test_tpu.train import (
        AdmmConsensus,
        BlockwiseFederatedTrainer,
        FederatedConfig,
    )

    class PopNet(BlockModule):
        @nn.compact
        def __call__(self, x, train=True):
            x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                         name="conv1")(x)))
            return nn.Dense(10, name="fc1")(flatten(x))

        def param_order(self):
            return pairs("conv1", "fc1")

        def train_order_block_ids(self):
            return [[0, 1], [2, 3]]

        def linear_layer_ids(self):
            return [1]

    K = 8
    cfg = FederatedConfig(K=K, Nloop=1, Nepoch=1, Nadmm=6, default_batch=16,
                          check_results=False, admm_rho0=0.1, seed=0,
                          population=population)
    data = FederatedCifar10(K=K, batch=16, limit_per_client=16,
                            limit_test=16)
    trainer = BlockwiseFederatedTrainer(PopNet(), cfg, data,
                                        AdmmConsensus())
    _, hist = trainer.run(log=lambda m: None)
    secs = [float(r["round_seconds"]) for r in hist
            if "round_seconds" in r and "nadmm" in r]
    if not secs:
        raise RuntimeError("population bench run produced no round records")
    return float(np.median(secs))


def _population_bench() -> int:
    """``bench.py --population-bench``: the no-TPU CI gate for population
    federation (population/).  Registers K virtual clients for K in
    {256, 2048, 10240} over a FIXED 8-slot cohort on the forced 8-device
    CPU mesh, times steady-state rounds, and emits a bench-shaped
    artifact (``artifacts/population.json``) whose headline is the
    sublinearity ratio

        (K_hi / K_lo) / (wall_hi / wall_lo)

    — the factor of the 40x registry growth that per-round wall clock
    did NOT pay.  40 means rounds cost the same at 10,240 registered
    clients as at 256 (perfectly cohort-bounded); 1 would mean rounds
    scale linearly in K.  Every number here is a CPU-box timing, so the
    committed-baseline gate runs with a WIDE threshold: it exists to
    catch the subsystem going accidentally linear-in-K, not 10%% drift.
    """
    # must land before this process's first jax import
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    populations = [256, 2048, 10240]
    out = {
        "metric": _POPULATION_METRIC,
        "unit": "x (K-growth over wall-growth, steady-state rounds)",
        "measured": True,
        "baseline_ref": _POPULATION_BASELINE,
        "population_cohort": 8,
        "population_registered_max": populations[-1],
    }
    walls = {}
    try:
        for pop in populations:
            walls[pop] = _population_round_seconds(pop)
            out[f"population_K{pop}_round_seconds"] = round(walls[pop], 4)
    except Exception as e:      # noqa: BLE001 — report, don't traceback
        out["error"] = (
            f"population bench run failed: {type(e).__name__}: {e}")
    if not out.get("error"):
        lo, hi = populations[0], populations[-1]
        out["value"] = round((hi / lo) / (walls[hi] / walls[lo]), 4)
        out["population_round_throughput"] = round(1.0 / walls[hi], 4)
        # human-readable section mirroring the gated flat fields
        out["population"] = {
            "registered": populations,
            "cohort": 8,
            "rounds_per_second_at_max_K": out["population_round_throughput"],
            "round_seconds": {str(p): out[f"population_K{p}_round_seconds"]
                              for p in populations},
            "sublinearity_ratio": out["value"],
        }
    out["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    out["git"] = _git_describe()
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "artifacts")
    path = os.path.join(art_dir, "population.json")
    try:
        os.makedirs(art_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    except OSError as e:
        print(f"bench: cannot write population artifact: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps(out))
    if out.get("error"):
        return 1
    baseline = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            _POPULATION_BASELINE)
    if not os.path.exists(baseline):
        print(f"bench: no committed {_POPULATION_BASELINE}; population gate "
              "skipped (commit the emitted artifacts/population.json there "
              "to arm it)", file=sys.stderr)
        return 0
    from federated_pytorch_test_tpu.obs import compare as obs_compare

    # timings on shared CI boxes: gate only on halving/doubling-scale
    # movement of the ratio and throughput, anything subtler is info
    return obs_compare.main([path, "--baseline", baseline,
                             "--threshold", "45"])


_SOAK_BASELINE = "artifacts/SOAK_BASELINE.json"
_SOAK_METRIC = "soak_availability_pct"
#: the nightly campaign: 48 virtual hours of diurnal load with churn
#: waves, straggler storms, correlated corruption bursts, and two
#: deterministic preemptions (virtual hours 12 and 30) that force two
#: supervised restarts with elastic mesh reshapes.  accel=600 turns the
#: seeded restart backoffs into milliseconds of wall clock without
#: touching any recorded value (PARITY.md v0.13).
_SOAK_SPEC = ("hours=48,round_minutes=30,diurnal=0.6,drop=0.15,"
              "straggle=0.1,mode=scale,scale=50,join=0.1,leave=0.1,"
              "storm=0.2,storm_len=2,storm_straggle=0.6,burst=0.25,"
              "burst_len=2,burst_corrupt=0.4,preempt_at=12+30,seed=11,"
              "accel=600")


def _soak_engine_run(tmp: str):
    """Run the seeded 48-virtual-hour campaign unattended; returns the
    stitched multi-segment JSONL path."""
    import flax.linen as nn

    from federated_pytorch_test_tpu.campaign.harness import run_soak
    from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
    from federated_pytorch_test_tpu.models.base import (
        BlockModule,
        elu,
        flatten,
        max_pool_2x2,
        pairs,
    )
    from federated_pytorch_test_tpu.train import (
        AdmmConsensus,
        BlockwiseFederatedTrainer,
        FederatedConfig,
    )

    class SoakNet(BlockModule):
        @nn.compact
        def __call__(self, x, train=True):
            x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                         name="conv1")(x)))
            return nn.Dense(10, name="fc1")(flatten(x))

        def param_order(self):
            return pairs("conv1", "fc1")

        def train_order_block_ids(self):
            return [[0, 1], [2, 3]]

        def linear_layer_ids(self):
            return [1]

    K = 8
    # Nloop * blocks * Nadmm = 8 * 2 * 6 = 96 rounds = 48 virtual hours
    # at 30-minute rounds, covering the full campaign span
    cfg = FederatedConfig(K=K, Nloop=8, Nepoch=1, Nadmm=6,
                          default_batch=16, check_results=False,
                          admm_rho0=0.1, seed=11,
                          campaign_spec=_SOAK_SPEC, control="act",
                          max_restarts=3, restart_backoff=1.0,
                          elastic_resume=True,
                          obs_dir=os.path.join(tmp, "obs"),
                          obs_sinks="jsonl")
    data = FederatedCifar10(K=K, batch=16, limit_per_client=16,
                            limit_test=16)

    def build(c, attempt):
        t = BlockwiseFederatedTrainer(SoakNet(), c, data, AdmmConsensus())
        t.obs_run_name = "soak"
        return t

    run_soak(build, cfg, os.path.join(tmp, "ck"),
             run_kwargs={"log": lambda m: None}, log=lambda m: None)
    return os.path.join(tmp, "obs", "soak.jsonl")


def _soak() -> int:
    """``bench.py --soak``: the nightly no-TPU availability gate for soak
    campaigns (campaign/).  Runs the seeded accelerated 48-virtual-hour
    campaign (diurnal load, churn waves, storms, corruption bursts, two
    deterministic preemptions -> two supervised restarts with elastic
    reshapes), verifies the stitched stream with ``control.replay``
    (any divergence fails the gate), and emits a bench-shaped artifact
    (``artifacts/soak.json``) whose headline is availability %% —
    distinct rounds over distinct + lost (replayed + restarts) — diffed
    against the committed ``artifacts/SOAK_BASELINE.json`` via
    obs/compare.py (availability down or rounds-lost up is exit 1).
    The campaign is a pure function of its seeds, so the gated numbers
    are deterministic, not timings."""
    # must land before this process's first jax import
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import tempfile

    out = {
        "metric": _SOAK_METRIC,
        "unit": "percent (distinct rounds / (distinct + lost))",
        "measured": True,
        "baseline_ref": _SOAK_BASELINE,
        "soak_spec": _SOAK_SPEC,
    }
    t0 = time.perf_counter()  # graftlint: disable=JG104
    try:
        with tempfile.TemporaryDirectory() as tmp:
            path = _soak_engine_run(tmp)
            from federated_pytorch_test_tpu.control.replay import replay
            from federated_pytorch_test_tpu.obs.report import (
                read_records,
                summarize,
            )

            records = read_records(path)
            s = summarize(records)
            errors, stats = replay(records)
    except Exception as e:      # noqa: BLE001 — report, don't traceback
        out["error"] = f"soak campaign run failed: {type(e).__name__}: {e}"
    else:
        out["value"] = s.get("availability_pct")
        out["soak_rounds_lost"] = s.get("rounds_lost")
        out["soak_rounds_distinct"] = s.get("rounds_distinct")
        out["soak_segments"] = s.get("segments")
        out["soak_restarts"] = s.get("restarts")
        out["soak_reshapes"] = s.get("reshapes")
        out["soak_campaign_records"] = s.get("campaign_records")
        out["soak_virtual_hours"] = s.get("campaign_virtual_hours")
        out["soak_replay_errors"] = len(errors)
        out["soak_replay_records"] = stats
        if errors:
            out["error"] = ("soak stream failed replay verification: "
                            + errors[0])
        elif s.get("restarts", 0) < 2 or not s.get("reshapes"):
            out["error"] = (
                "soak campaign did not exercise the restart path "
                f"(restarts={s.get('restarts')}, "
                f"reshapes={s.get('reshapes')}); the schedule's "
                "preempt_at events must force >= 2 supervised restarts "
                "with >= 1 mesh reshape")
    out["soak_wall_seconds"] = round(time.perf_counter() - t0, 2)  # graftlint: disable=JG104
    out["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    out["git"] = _git_describe()
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "artifacts")
    path = os.path.join(art_dir, "soak.json")
    try:
        os.makedirs(art_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    except OSError as e:
        print(f"bench: cannot write soak artifact: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    if out.get("error"):
        return 1
    baseline = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            _SOAK_BASELINE)
    if not os.path.exists(baseline):
        print(f"bench: no committed {_SOAK_BASELINE}; soak gate skipped "
              "(commit the emitted artifacts/soak.json there to arm it)",
              file=sys.stderr)
        return 0
    from federated_pytorch_test_tpu.obs import compare as obs_compare

    # the campaign is seed-deterministic; the band only absorbs
    # rounding of the availability percentage
    return obs_compare.main([path, "--baseline", baseline,
                             "--threshold", "5"])


_SERVE_BASELINE = "artifacts/SERVE_BASELINE.json"
_SERVE_METRIC = "serve_qps_chip"
#: the serving gate's traffic: seeded constant-rate requests (the ±10%%
#: per-round jitter still applies), pad-to-bucket batching over three
#: static shapes, a hot-swap every 2 rounds, and a total label shift
#: injected from round 4 on so the served eval stream drifts and the
#: watchdog/policy loop (health window 2, streak 1, act mode) has
#: something to close on.  Every non-timing field in the record stream
#: is a pure function of this spec (PARITY.md v0.14), so replay
#: verification gates exact values; only qps/p99/swap-gap are timings.
_SERVE_SPEC = ("qps=16,round_minutes=0.5,buckets=8+32+128,swap_every=2,"
               "drift_at=4,seed=3")


def _serve_engine_run(tmp: str):
    """Tiny REAL training run with the serving plane on: 8 rounds of
    the 2-block net, consensus weights hot-swapped every 2 rounds,
    seeded traffic served at every round boundary, drift injected from
    round 4.  Returns the run's JSONL path."""
    import flax.linen as nn

    from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
    from federated_pytorch_test_tpu.models.base import (
        BlockModule,
        elu,
        flatten,
        max_pool_2x2,
        pairs,
    )
    from federated_pytorch_test_tpu.train import (
        AdmmConsensus,
        BlockwiseFederatedTrainer,
        FederatedConfig,
    )

    class ServeNet(BlockModule):
        @nn.compact
        def __call__(self, x, train=True):
            x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                         name="conv1")(x)))
            return nn.Dense(10, name="fc1")(flatten(x))

        def param_order(self):
            return pairs("conv1", "fc1")

        def train_order_block_ids(self):
            return [[0, 1], [2, 3]]

        def linear_layer_ids(self):
            return [1]

    K = 8
    # Nloop * blocks * Nadmm = 2 * 2 * 2 = 8 rounds: enough for 4 swaps
    # and 4 drifted serving rounds after drift_at=4
    cfg = FederatedConfig(K=K, Nloop=2, Nepoch=1, Nadmm=2,
                          default_batch=16, check_results=False,
                          admm_rho0=0.1, seed=0,
                          serve_spec=_SERVE_SPEC, control="act",
                          health_action="warn", health_window=2,
                          health_streak=1, health_tput_frac=0.75,
                          obs_dir=os.path.join(tmp, "obs"),
                          obs_sinks="jsonl")
    data = FederatedCifar10(K=K, batch=16, limit_per_client=16,
                            limit_test=16)
    trainer = BlockwiseFederatedTrainer(ServeNet(), cfg, data,
                                        AdmmConsensus())
    trainer.obs_run_name = "serve"
    trainer.run(log=lambda m: None)
    return os.path.join(tmp, "obs", "serve.jsonl")


def _serve_bench() -> int:
    """``bench.py --serve-bench``: the no-TPU CI gate for the serving
    plane (serve/).  Runs a tiny training run with seeded traffic
    served at every round boundary, verifies the stream with
    ``control.replay`` (the pure serve fields must re-derive from the
    header config alone — any divergence fails the gate), and emits a
    bench-shaped artifact (``artifacts/serve.json``) whose headline is
    sustained QPS per chip, plus p99 latency and the worst hot-swap
    publish gap, diffed against the committed
    ``artifacts/SERVE_BASELINE.json`` via obs/compare.py — exit 1 on
    regression (QPS down, p99/swap-gap up).  The request counts,
    batching plan, swap sequence, and drift schedule are seed-
    deterministic; only the latency/QPS numbers are timings, hence the
    wide noise band."""
    # must land before this process's first jax import
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import tempfile

    out = {
        "metric": _SERVE_METRIC,
        "unit": "requests/sec/chip (batched online inference)",
        "measured": True,
        "baseline_ref": _SERVE_BASELINE,
        "serve_spec": _SERVE_SPEC,
    }
    t0 = time.perf_counter()  # graftlint: disable=JG104
    try:
        with tempfile.TemporaryDirectory() as tmp:
            path = _serve_engine_run(tmp)
            import jax

            from federated_pytorch_test_tpu.control.replay import replay
            from federated_pytorch_test_tpu.obs.report import (
                read_records,
                summarize,
            )

            n_chips = jax.device_count()
            records = read_records(path)
            s = summarize(records)
            errors, stats = replay(records)
    except Exception as e:      # noqa: BLE001 — report, don't traceback
        out["error"] = f"serve bench run failed: {type(e).__name__}: {e}"
    else:
        qps = s.get("serve_qps_mean") or 0.0
        out["value"] = round(qps / max(n_chips, 1), 3)
        out["serve_p99_ms"] = s.get("serve_p99_ms_max")
        out["serve_swap_gap_seconds"] = s.get("serve_swap_gap_max")
        out["serve_qps_mean"] = s.get("serve_qps_mean")
        out["serve_p50_ms_mean"] = s.get("serve_p50_ms_mean")
        # deterministic section (seed-derived, replay-checked): info
        # direction in the diff, but divergence already failed replay
        out["serve_records"] = s.get("serve_records")
        out["serve_requests_total"] = s.get("serve_requests_total")
        out["serve_batches_total"] = s.get("serve_batches_total")
        out["serve_padding_waste_frac"] = s.get("serve_padding_waste_frac")
        out["serve_swaps"] = s.get("serve_swaps")
        out["serve_drift_rounds"] = s.get("serve_drift_rounds")
        out["serve_drift_alerts"] = s.get("serve_drift_alerts")
        out["serve_forced_refreshes"] = s.get("serve_forced_refreshes")
        out["serve_replay_errors"] = len(errors)
        out["serve_replay_records"] = stats
        if errors:
            out["error"] = ("serve stream failed replay verification: "
                            + errors[0])
        elif (s.get("serve_swaps", 0) < 2
                or not s.get("serve_drift_rounds")):
            out["error"] = (
                "serve bench did not exercise the hot-swap/drift path "
                f"(swaps={s.get('serve_swaps')}, "
                f"drift_rounds={s.get('serve_drift_rounds')}); the "
                "serve_spec must force >= 2 swaps and a drifted tail")
    out["serve_wall_seconds"] = round(time.perf_counter() - t0, 2)  # graftlint: disable=JG104
    out["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    out["git"] = _git_describe()
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "artifacts")
    path = os.path.join(art_dir, "serve.json")
    try:
        os.makedirs(art_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    except OSError as e:
        print(f"bench: cannot write serve artifact: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    if out.get("error"):
        return 1
    baseline = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            _SERVE_BASELINE)
    if not os.path.exists(baseline):
        print(f"bench: no committed {_SERVE_BASELINE}; serve gate skipped "
              "(commit the emitted artifacts/serve.json there to arm it)",
              file=sys.stderr)
        return 0
    from federated_pytorch_test_tpu.obs import compare as obs_compare

    # qps/p99/swap-gap are timings on shared CI boxes: gate only on
    # halving/doubling-scale movement, anything subtler is info
    return obs_compare.main([path, "--baseline", baseline,
                             "--threshold", "50"])


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        sys.exit(_smoke())
    if "--population-bench" in sys.argv[1:]:
        sys.exit(_population_bench())
    if "--soak" in sys.argv[1:]:
        sys.exit(_soak())
    if "--serve-bench" in sys.argv[1:]:
        sys.exit(_serve_bench())
    sys.exit(main())
