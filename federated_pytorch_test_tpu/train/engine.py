"""Blockwise-federated training engine.

One engine replaces the reference's six copy-paste driver skeletons
(SURVEY.md "Shared driver skeleton").  The canonical loop nest
(federated_multi.py:13-16) is preserved::

    Nloop (sweeps over the net) -> L blocks -> Nadmm (comm rounds)
      -> Nepoch (local epochs) -> K clients -> minibatches

but the two inner levels are *compiled*: clients live on the ``'clients'``
mesh axis (``shard_map``; groups of K/D clients per device are ``vmap``-ed),
and the minibatch loop is a ``lax.scan``.  The communication round is an XLA
collective on the masked flat block vector.  The reference's sequential
``for ck in range(K)`` (federated_multi.py:168) does not exist on any path.

Per-block state (z, duals, optimizer) is recreated at each block switch,
matching the reference (federated_multi.py:148-159); masks are static Python
data so each block compiles its own specialised step (cached across the
Nloop sweeps).
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from federated_pytorch_test_tpu.analysis.sanitize import (
    TraceSentinel,
    instrument_jit,
)
from federated_pytorch_test_tpu.compress import make_compressor, stacked_init
from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import BlockModule
from federated_pytorch_test_tpu.obs import device_memory_stats
from federated_pytorch_test_tpu.obs.costs import CostLedger, round_cost_fields
from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.optim.lbfgs import LBFGSNew
from federated_pytorch_test_tpu.parallel.mesh import (
    CLIENT_AXIS,
    client_mesh,
    client_sharding,
    fetch,
    replicated_sharding,
    shard_map,
    stage_global,
    stage_tree_global,
    usable_device_count,
)
from federated_pytorch_test_tpu.train.algorithms import (
    Algorithm,
    BBConfig,
    bb_rho_update,
)
from federated_pytorch_test_tpu.train.config import FederatedConfig
from federated_pytorch_test_tpu.train.faults import apply_corruption
from federated_pytorch_test_tpu.train.rounds import RoundKernel
from federated_pytorch_test_tpu.train.losses import accuracy_count, cross_entropy, l1_l2
from federated_pytorch_test_tpu.utils import blocks as blocklib
from federated_pytorch_test_tpu.utils import codec
from federated_pytorch_test_tpu.utils.initializers import init_weights
from federated_pytorch_test_tpu.utils.profiling import profile_ctx, round_trace
from federated_pytorch_test_tpu.utils.tree import get_by_path, set_by_path


class ClientState(NamedTuple):
    """Per-client training state, stacked on the leading K axis.

    ``comp`` is the update-compression state (compress/base.py): PRNG keys
    for stochastic quantization and/or error-feedback residuals, threaded
    through every comm round.  ``None`` on the dense path (--compress none)
    so the default pytrees — and their compiled programs — are unchanged.
    """

    params: Any
    batch_stats: Any
    opt_state: Any
    comp: Any = None


def _normalize_u8(x_u8: jnp.ndarray, norm: jnp.ndarray) -> jnp.ndarray:
    """Device-side ToTensor+Normalize (federated_multi.py:62-71): ``norm`` is
    the client's [2, 3] (mean, std) — the reference biases BOTH Normalize
    arguments with the same per-client triple (federated_multi.py:66)."""
    x = x_u8.astype(jnp.float32) / 255.0
    return (x - norm[0]) / norm[1]


class BlockwiseFederatedTrainer(RoundKernel):
    """Shared engine for the classifier drivers (no_consensus / fedavg /
    fedprox / consensus).  The VAE / clustering-VAE trainers subclass it and
    override the hook methods (``model_loss``, ``sweep_paths``,
    ``optimizer_for_block``, ...) — the reference instead copy-pastes the
    whole driver skeleton per workload (SURVEY.md "Shared driver skeleton").
    """

    #: "blocks" sweeps train_order_block_ids() (federated_multi.py:145-147);
    #: "layers" sweeps (weight, bias) pairs — the VAE driver's
    #: unfreeze_one_layer path (federated_vae.py:129)
    sweep: str = "blocks"

    #: engine tag in every obs record (subclasses override: "vae",
    #: "vae_cl"; the CPC trainer reports "cpc")
    obs_engine: str = "classifier"

    def sample_init_args(self):
        """Args after rng for ``model.init``: one prepared sample of the
        data's own shape (rng-taking models override and add their key;
        a token trainer returns int32 ids)."""
        return (jnp.zeros((1,) + self._sample_shape, jnp.float32),)

    def prepare_batch(self, xb_raw, norm):
        """A minibatch as staged (uint8 images) -> what ``model_loss``
        is given; ``norm`` is the client's row of ``data.norm_stats``.
        The image trainers normalise on the device; a token trainer
        passes its int32 ids through."""
        return _normalize_u8(xb_raw, norm)

    def __init__(
        self,
        model: BlockModule,
        cfg: FederatedConfig,
        data: FederatedCifar10,
        algorithm: Algorithm,
        loss_fn: Callable = cross_entropy,
        mesh=None,
    ):
        self.model = model
        self.cfg = cfg
        self.data = data
        self.algo = algorithm
        self.loss_fn = loss_fn
        # observability (obs/): the last RunRecorder this trainer opened
        # (tests read .memory off it); drivers set obs_run_name to their
        # prog name so the JSONL artifact is predictably named
        self.obs_recorder = None
        self.obs_run_name: Optional[str] = None
        # control-plane cfg swaps (_apply_round_control/_apply_block_
        # control) replace the frozen cfg dataclass while the epoch-stage
        # worker reads fields off it; the lock makes the read-swap
        # sequence atomic against that role
        self._cfg_swap_lock = threading.Lock()
        # update compression (compress/): validated here so a bad flag
        # combination fails at construction, not mid-run inside jit
        self.compressor = make_compressor(
            cfg.compress, topk_frac=cfg.topk_frac,
            quant_chunk=cfg.quant_chunk,
            error_feedback=cfg.error_feedback)
        # the shared round kernel (train/rounds.py): fault injection +
        # robust aggregation + update guards + async/churn/client ledgers
        self._init_round_kernel()
        # roofline comm path (cfg.fused_collective / cfg.sharded_update /
        # cfg.overlap_staging): validated here like the robust/compress
        # knobs so a bad flag combination fails at construction
        self._fused_coll = bool(cfg.fused_collective)
        if cfg.fused_collective and self.compressor.name == "none":
            raise ValueError(
                "fused_collective requires a compressed wire format "
                "(--compress q8/q4/topk): the fused reduction transports "
                "the packed payloads, and the dense path has nothing to "
                "keep packed")
        if (cfg.fused_collective or cfg.sharded_update) \
                and cfg.robust_agg != "none":
            raise ValueError(
                "fused_collective/sharded_update are incompatible with "
                "--robust-agg: both replace the aggregation chokepoint, "
                "and the robust estimators need the full [K, N] stack "
                "replicated on every device")
        if (self._fused_coll and getattr(self.compressor, "sparse", False)
                and algorithm.needs_dual):
            import warnings
            warnings.warn(
                "fused_collective with a sparse compressor is unavailable "
                "for dual-state algorithms: the aggregated stack y + rho*x "
                "is dense, not the sparse wire payload; falling back to "
                "the unfused reduction", stacklevel=2)
            self._fused_coll = False
        self._overlap = bool(cfg.overlap_staging)
        # shared robustness/health/control flag validation (RoundKernel)
        self._validate_round_cfg()

        self.order = model.param_order()
        self.block_ids = model.train_order_block_ids()
        self.linear_ids = model.linear_layer_ids()
        # in BOTH sweep modes ci ranges over len(train_order_block_ids()):
        # the reference VAE driver iterates that count but freezes LAYER ci
        # (federated_vae.py:126-129) — for its models layer and block counts
        # coincide; assert that so a mismatched future model fails loudly
        self.L = len(self.block_ids)
        if self.sweep == "layers":
            n_layers = (len(self.order) + 1) // 2
            assert self.L == n_layers, (
                f"layer sweep needs len(train_order_block_ids())=={n_layers} "
                f"(layers), got {self.L}")

        K = cfg.K
        if mesh is None:
            # `is None`, not `or`: an explicit 0 must reach client_mesh's
            # validation instead of silently selecting the auto default
            mesh = client_mesh(usable_device_count(K)
                               if cfg.num_devices is None
                               else cfg.num_devices)
        self.mesh = mesh
        self.D = mesh.devices.size
        if K % self.D:
            raise ValueError(f"K={K} not divisible by device count {self.D}")
        if not 0.0 < cfg.participation <= 1.0:
            raise ValueError(
                f"participation={cfg.participation} must be in (0, 1]")
        if cfg.participation < 1.0 and cfg.bb_update:
            raise ValueError(
                "participation < 1 is incompatible with bb_update: the BB "
                "spectral history (x0/yhat0 deltas) assumes every client "
                "moves every round (consensus_multi.py:242-278)")
        # (overlap_staging x population used to raise here: the lookahead
        # is now cohort-aware — _prestage_round builds only the
        # cohort-independent shuffle ahead of time and the cohort
        # re-index + H2D run at consumption, under the round's actual
        # cohort — see _epoch_raw/_finish_epoch)
        self.K_local = K // self.D
        if getattr(cfg, "robust_chunked", False):
            # chunked robust aggregation needs the mesh size, which the
            # pre-mesh _init_round_kernel above did not have: rebuild the
            # estimator segment-owned.  make_robust_mean validates the
            # robust_agg="none" combination (raises).
            from federated_pytorch_test_tpu.parallel.comm import (
                make_robust_mean,
            )
            self.mean_fn = make_robust_mean(
                cfg.robust_agg, trim_frac=cfg.trim_frac,
                clip_mult=cfg.clip_mult, chunked=True, D=self.D)

        # --- common init: all K clients start from identical weights
        # (reference seeds torch.manual_seed(0) before init of EVERY client,
        # federated_multi.py:124-128)
        rng = jax.random.PRNGKey(cfg.init_seed)
        # one sample's shape, from the pipeline's own test batches
        # ([tsteps, B, ...]): nothing here assumes a 32x32x3 image
        test_raw = data.test_batches_raw()
        self._sample_shape = tuple(test_raw[0].shape[2:])
        params, batch_stats = model.init_variables(rng, *self.sample_init_args())
        if cfg.init_model:
            # SEED COMPAT (graftcheck JG103): init_weights used to rebuild
            # PRNGKey(cfg.init_seed) and so drew the SAME stream as the
            # module init above; fold_in gives it a distinct child stream,
            # which changes init_model=True draws vs earlier releases
            # (see PARITY.md)
            params = init_weights(params, jax.random.fold_in(rng, 1))
        self.has_bn = bool(batch_stats)

        stack = lambda t: jax.tree.map(
            lambda v: np.broadcast_to(np.asarray(v)[None], (K,) + v.shape), t
        )
        csh = client_sharding(mesh)
        # stage_tree_global, not device_put: on multi-host each process
        # materialises only its addressable client shards, and device_put of
        # a host array onto a global sharding costs a cross-process
        # assert_equal collective per call (parallel/mesh.py)
        self.params0 = stage_tree_global(stack(params), csh)
        self.batch_stats0 = stage_tree_global(stack(batch_stats), csh)

        self._fn_cache: Dict[Any, Any] = {}
        self._block_sizes: Dict[Any, int] = {}     # block_size(), by paths
        # retrace sentinel: counts jit traces of the instrumented step
        # functions (analysis/sanitize.py); None when off so the step
        # builders wrap nothing and the jitted chain is literally the
        # uninstrumented one
        self._sentinel = TraceSentinel() if cfg.retrace_sentinel else None
        # compile ledger (obs/costs.py): per-jit-site compile
        # wall-seconds and dispatch seconds, drained into the obs round
        # records each round.
        # None when off so the jitted chain is literally the
        # uninstrumented one (same contract as the sentinel)
        self._ledger = CostLedger() if cfg.cost_ledger else None
        # stateless per-epoch randomness: epochs are keyed on a counter
        # (see _epoch_seed), so the NEXT epoch's host-side shuffle/gather
        # can be built on a worker thread while the devices compute this
        # round (_stage_epoch), and mid-run resume only needs the counter
        self._epochs_staged = 0
        self._keys_staged = 0
        self._prefetch_epochs = bool(cfg.prefetch)
        self._pending: Optional[tuple] = None
        # staging/comm overlap (cfg.overlap_staging): (counter, arrays)
        # built ahead by _prestage_round while the comm dispatch executes;
        # the counters advance only at CONSUMPTION (_stage_epoch /
        # _epoch_keys), so checkpoints record consumption state and a
        # resumed run rebuilds the same epoch from the counter
        self._staged_ahead: Optional[tuple] = None
        self._keys_ahead: Optional[tuple] = None
        # buffer donation (cfg.donate; None = auto: accelerators only —
        # CPU honors donation too, but keeping the caller-side arrays
        # alive is the safer default where nobody is memory-bound):
        # the train/comm/fused step jits donate the client state and the
        # consensus block vars, every one of which the round loop rebinds
        # from the step's outputs before the next dispatch
        self._donate = (cfg.donate if cfg.donate is not None
                        else jax.default_backend() != "cpu")
        # train-phase host dispatches (cumulative): the unfused loop costs
        # Nepoch per comm round, the fused executor exactly 1 — the obs
        # per-round delta is the tracked metric (`host_dispatches`)
        self._host_dispatches = 0
        # async checkpoint writer (utils/checkpoint.py), created by
        # _run_impl when cfg.async_checkpoint and a checkpoint path exist
        self._ckpt_writer = None
        import concurrent.futures
        self._stage_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="epoch-stage")

        # test set staged once: uint8 replicated across the mesh, labels and
        # pad weights replicated, per-client normalisation stats sharded
        # (stage_global = device_put single-process; local-shards-only on
        # multi-host, parallel/mesh.py)
        rsh = replicated_sharding(mesh)
        xt_u8, yt, wt = test_raw
        self.test_x = stage_global(xt_u8, rsh)       # [tsteps, B, 32,32,3] u8
        self.test_y = stage_global(yt, rsh)          # [tsteps, B] i32
        self.test_w = stage_global(wt, rsh)          # [tsteps, B] f32
        self.test_n = int(wt.sum())                  # true test sample count
        # host copy kept for population mode: slot k's normalisation
        # stats follow the cohort's data shard (rid % K), restaged per
        # round in _run_impl (population off never touches it again)
        self._client_norm_host = np.asarray(data.norm_stats, np.float32)
        self.client_norm = stage_global(
            self._client_norm_host, csh                  # [K, 2, 3]
        )
        # the kernel's per-run constant masks (full-participation ones
        # mask, zero corruption vector, +inf guard bound), staged once
        self._stage_round_constants()

        # device-resident training data (cfg.device_data; None = auto by
        # size): the raw uint8 shards live in HBM and every epoch's
        # shuffled batches come from an on-device permutation gather, so
        # the per-epoch host shuffle + H2D copy — the dominant cost of a
        # production round whenever the host link is slow — vanishes from
        # the steady state (_stage_epoch)
        self._dev_gather = None
        if self._want_device_data():
            self._setup_device_data()
        # fused round execution (cfg.fused_rounds): needs the epoch data
        # device-resident (the whole round must be traceable) and is
        # pointless under be_verbose (per-epoch host prints force the
        # Nepoch dispatch pattern back anyway)
        self._use_fused = bool(cfg.fused_rounds)
        if self._use_fused and (self._dev_gather is None or cfg.be_verbose):
            import warnings
            why = ("be_verbose syncs the host every epoch"
                   if cfg.be_verbose else
                   "population sampling re-indexes epoch data on the host"
                   if self._pop_active else
                   "epoch data is not device-resident (device_data)")
            warnings.warn(
                f"fused_rounds requested but unusable: {why}; "
                "falling back to the per-epoch round loop", stacklevel=2)
            self._use_fused = False
        # whole-round overlap (cfg.overlap_round): pre-dispatch round
        # N+1's first train epoch behind round N's comm collective.
        # Honest gating, same shape as the fused fallback above: every
        # excluded knob makes round N+1's INPUTS depend on round N's
        # host-visible outcome (guard verdicts feed quarantine, async/
        # faults/churn/campaign tick host ledgers, population rotates
        # the cohort), so a lookahead would dispatch against stale
        # state.  What remains — participation draws (_round_mask is
        # stateless in the round coords), BB rho (a device array), the
        # control plane's round-scope rungs (each targets one of the
        # subsystems gated off here) — is safe by construction.
        self._overlap_round = bool(getattr(cfg, "overlap_round", False))
        self._round_ahead: Optional[tuple] = None
        if self._overlap_round:
            why = None
            if self._use_fused or cfg.fused_rounds:
                why = ("fused_rounds already runs the whole round as one "
                       "dispatch — there is no host gap to hide")
            elif cfg.update_guard:
                why = ("guard verdicts decide the next round's "
                       "quarantine set after the comm fetch")
            elif cfg.async_rounds:
                why = ("the async scheduler admits updates on the host "
                       "between rounds")
            elif self.faults.enabled:
                why = ("fault/churn families tick host ledgers at every "
                       "round boundary")
            elif self.campaign is not None:
                why = "campaign schedules re-derive the fault spec per round"
            elif self._pop_active:
                why = "population sampling rotates the cohort per round"
            if why is not None:
                import warnings
                warnings.warn(
                    f"overlap_round requested but unsafe: {why}; "
                    "falling back to the sequential round loop",
                    stacklevel=2)
                self._overlap_round = False

    # ------------------------------------------------------------------
    # masks / per-block plumbing (hooks overridable by workload subclasses)
    # ------------------------------------------------------------------
    def sweep_paths(self, ci: int):
        """Active leaf paths of sweep unit ``ci``."""
        if self.sweep == "layers":
            return blocklib.layer_paths(self.order, ci)
        return blocklib.block_paths(self.order, self.block_ids[ci])

    def mask_for_block(self, ci: Optional[int]):
        """Leaf mask for sweep unit ``ci``; ``None`` -> the whole net."""
        paths = tuple(self.order) if ci is None else self.sweep_paths(ci)
        return blocklib.build_mask(jax.tree.map(lambda _: 0, self.params0), paths)

    def block_size(self, ci: Optional[int]) -> int:
        """Flat size ``N`` of sweep unit ``ci`` (``None``: the whole net),
        read from the leaves' shapes alone (no device op) and kept per
        set of active paths: callers may re-point ``block_ids`` after
        construction, so the key is what ``sweep_paths`` returns now."""
        key = None if ci is None else tuple(self.sweep_paths(ci))
        if key not in self._block_sizes:
            one = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                self.params0)
            self._block_sizes[key] = codec.masked_size(
                one, self.order, self.mask_for_block(ci))
        return self._block_sizes[key]

    def optimizer_for_block(self, ci: Optional[int]) -> str:
        """'adam' | 'lbfgs' — the VAE-CL driver switches per block
        (federated_vae_cl.py:200-205)."""
        return self.cfg.optimizer

    def lr_for_block(self, ci: Optional[int]) -> float:
        return self.cfg.lr

    def reg_for_block(self, ci: Optional[int]):
        """(lambda1, lambda2) applied to the flat trainable vector.

        Classifier default reproduces the reference quirk: the *block* index
        is tested against parameter-enumeration ids (federated_multi.py:183).
        """
        if ci is not None and ci in self.linear_ids:
            return (self.cfg.lambda1, self.cfg.lambda2)
        return (0.0, 0.0)

    def wrap_client_grad(self, grad_fn):
        """``grad_fn`` is one client's loss and active-block gradient for
        one minibatch; the epoch program vmaps it over the device's
        clients.  A trainer whose clients' activations do not fit side
        by side returns it wrapped so that it runs client after client
        (``jax.custom_batching.sequential_vmap``).  Here: as it is."""
        return grad_fn

    def round_fields(self, state: ClientState, ci: int) -> Dict[str, Any]:
        """Further fields of this round's record, read after the round's
        one host sync (a token trainer: tokens, block kind, routing
        counts).  None here."""
        return {}

    def model_loss(self, p, bs, xb, yb, wb, rng):
        """Per-batch core loss -> (scalar, new_batch_stats).

        Classifier default: CE on logits (federated_multi.py:178-189).
        ``wb`` [B] marks pad rows of the final partial minibatch with 0
        (drop_last=False parity); the weighted mean equals the reference's
        mean over the true partial batch.  Subclasses override for
        VAE/VAE-CL losses and must thread ``wb`` into their weighted loss
        the same way (train/vae_losses.py).
        """
        logits, new_bs = self._apply_train(p, bs, xb, wb)
        return self.loss_fn(logits, yb, wb), new_bs

    def _apply_train(self, p, bs, xb, wb=None):
        if self.has_bn:
            # sample_weight excludes wrap-pad rows from BN batch statistics
            # (MaskedBatchNorm, models/resnet.py): torch BN only ever sees
            # the true partial batch (federated_multi.py:74-83).  When the
            # dataset provably has NO remainder batch (remainder == 0, a
            # static property) every weight is 1, so the plain-BN path
            # runs — the weighted-stat arithmetic costs ~5% of a local
            # epoch for nothing.  A pipeline without a `remainder`
            # attribute keeps the weighted path: correctness over speed
            # when the contract can't prove the weights are all-ones.
            if getattr(self.data, "remainder", 1) == 0:
                wb = None
            out, mut = self.model.apply(
                {"params": p, "batch_stats": bs}, xb, train=True,
                sample_weight=wb, mutable=["batch_stats"])
            return out, mut["batch_stats"]
        return self.model.apply({"params": p}, xb, train=True), bs

    # ------------------------------------------------------------------
    # compiled steps (built per block; cached)
    # ------------------------------------------------------------------
    def _instrument_jit(self, fn, name: str, **jit_kwargs):
        """jit ``fn`` with the config's sanitize/retrace/cost-ledger
        instrumentation (analysis/sanitize.py).  With all knobs off
        this is exactly ``jax.jit(fn, **jit_kwargs)``: the dense path
        stays bit-identical by construction."""
        return instrument_jit(fn, name, sanitize=self.cfg.sanitize,
                              sentinel=self._sentinel,
                              ledger=self._ledger, **jit_kwargs)

    def _donate_argnums(self, argnums) -> tuple:
        """donate_argnums for a step jit: the real tuple when donation is
        on, else ``()`` — identical to not donating (jax treats an empty
        tuple exactly like an absent kwarg), but the kwarg is always
        spelled at the call site so the donation contract is visible
        (graftcheck JG106)."""
        return tuple(argnums) if self._donate else ()

    def _build_fns(self, ci: Optional[int]):
        """(train_epoch, comm_round, init_opt) specialised to block ``ci``."""
        key = ("blk", ci)
        if key in self._fn_cache:
            return self._fn_cache[key]

        cfg, algo = self.cfg, self.algo
        order = self.order
        mask = self.mask_for_block(ci)
        # the active leaves, by path: gradients and Adam's moments exist
        # for these alone.  A frozen leaf's moments were identically zero
        # and its update 0, so every active leaf's trajectory is what it
        # was when the optimizer spanned the model; what is gone is a
        # gradient, two moments and the backward residuals per frozen leaf
        active_paths = (tuple(order) if ci is None
                        else tuple(self.sweep_paths(ci)))
        take_active = lambda p: {path: get_by_path(p, path)
                                 for path in active_paths}

        def put_active(p, act):
            for path in active_paths:
                p = set_by_path(p, path, act[path])
            return p

        lam1, lam2 = self.reg_for_block(ci)
        reg_on = lam1 != 0.0 or lam2 != 0.0
        opt_name = self.optimizer_for_block(ci)
        if opt_name not in ("adam", "lbfgs"):
            raise ValueError(f"unknown optimizer {opt_name!r}; "
                             "expected 'adam' or 'lbfgs'")
        use_lbfgs = opt_name == "lbfgs"
        tx = optax.adam(self.lr_for_block(ci))
        has_bn = self.has_bn
        model_loss = self.model_loss
        K = cfg.K

        def batch_loss(p, bs, xb, yb, wb, rng, z, y, rho):
            with scope("model_loss"):
                loss, new_bs = model_loss(p, bs, xb, yb, wb, rng)
            with scope("penalty"):
                xflat = codec.get_trainable_values(p, order, mask)
                loss = loss + algo.penalty(xflat, z, y, rho)
                if reg_on:
                    loss = loss + l1_l2(xflat, lam1, lam2)
            return loss, new_bs

        def active_loss(act, p, *rest):
            return batch_loss(put_active(p, act), *rest)

        grad_fn = self.wrap_client_grad(
            jax.value_and_grad(active_loss, has_aux=True))
        prepare_batch = self.prepare_batch
        if use_lbfgs and has_bn:
            raise ValueError(
                "lbfgs local optimizer requires a BatchNorm-free model "
                "(closure re-evaluation with mutable stats is ill-defined; "
                "the reference only pairs LBFGSNew with BN-free models)")
        lbfgs = LBFGSNew(history_size=cfg.lbfgs_history_size,
                         max_iter=cfg.lbfgs_max_iter,
                         line_search_fn=True, batch_mode=True)

        def adam_step(carry, batch):
            p, bs, os = carry
            xb_raw, yb, wb, rng, z, y, rho, norm = batch
            with scope("step_prepare"):
                xb = prepare_batch(xb_raw, norm)
                act = take_active(p)
            with scope("client_grad"):
                (loss, new_bs), g = grad_fn(act, p, bs, xb, yb, wb, rng, z,
                                            y, rho)
            with scope("opt_update"):
                updates, os = tx.update(g, os, act)
                p = put_active(p, optax.apply_updates(act, updates))
            return (p, new_bs, os), loss

        def lbfgs_step(carry, batch):
            # the reference pairs LBFGSNew with a closure re-evaluating the
            # local loss (federated_multi.py:158, federated_cpc.py:238-248);
            # here the closure is a pure flat-vector objective on the active
            # block and step() runs bounded line searches inside jit
            p, bs, os = carry
            xb_raw, yb, wb, rng, z, y, rho, norm = batch
            xb = prepare_batch(xb_raw, norm)

            def flat_loss(v):
                pv = codec.put_trainable_values(p, order, mask, v)
                loss, _ = batch_loss(pv, bs, xb, yb, wb, rng, z, y, rho)
                return loss

            xflat = codec.get_trainable_values(p, order, mask)
            xnew, os, loss = lbfgs.step(flat_loss, xflat, os)
            return (codec.put_trainable_values(p, order, mask, xnew), bs, os), loss

        local_step = lbfgs_step if use_lbfgs else adam_step

        def per_client_epoch(p, bs, os, y, norm, key, xb_u8, yb, wb, z, rho):
            steps = xb_u8.shape[0]
            def step(carry, batch):
                xb_u8, yb, wb, i = batch
                with scope("step_prepare"):
                    rng = jax.random.fold_in(key, i)
                return local_step(carry, (xb_u8, yb, wb, rng, z, y, rho, norm))
            (p, bs, os), losses = lax.scan(
                step, (p, bs, os), (xb_u8, yb, wb, jnp.arange(steps)))
            return p, bs, os, jnp.sum(losses)

        # partial participation (cfg.participation < 1) is a STATIC mode:
        # the default full-participation build carries no mask plumbing at
        # all, so the reference-parity path compiles exactly as before.
        # Fault injection and update guards reuse the same plumbing (a
        # dropped/quarantined client IS a non-participant), so either
        # turns the masked mode on too — as does async mode, where the
        # activity vector carries the fractional staleness weights of the
        # round's arrivals (_round_activity_async).
        faults_on = self.faults.enabled
        guard_on = cfg.update_guard
        # population sampling makes every round partial too: the cohort
        # rung can mask slots out, so the aggregation must renormalize
        # over the activity vector.  population == K (identity) keeps
        # the unmasked program — the bitwise full-participation contract.
        pop_partial = (getattr(cfg, "population", 0) > 0
                       and cfg.population != cfg.K)
        partial = (cfg.participation < 1.0 or faults_on or guard_on
                   or cfg.async_rounds or pop_partial)
        has_corrupt = faults_on and self.faults.corrupt > 0
        corrupt_mode, corrupt_scale = self.faults.mode, self.faults.scale
        mean_fn = self.mean_fn
        # client-grain flight recorder (cfg.client_ledger, obs/clients.py):
        # a STATIC probe mode — when off, the comm program below is the
        # literal pre-probe chain (no extra outputs traced at all)
        client_probe = self._client_probe
        if client_probe:
            from federated_pytorch_test_tpu.parallel.comm import (
                per_client_norms,
            )

        def _sel(active, new, old):
            """Per-leaf where(active_k, new, old) over the client axis —
            inactive clients' state is bit-untouched this round."""
            pick = lambda a, b: jnp.where(
                active.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, b)
            return jax.tree.map(pick, new, old)

        def epoch_shard(state: ClientState, y, norm, keys, xb_u8, yb, wb, z,
                        rho, active):
            p, bs, os, loss = jax.vmap(
                per_client_epoch,
                in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, None, None)
            )(state.params, state.batch_stats, state.opt_state, y, norm, keys,
              xb_u8, yb, wb, z, rho)
            new = ClientState(p, bs, os, state.comp)
            if partial:
                # inactive clients compute (static shapes on the mesh) but
                # every result is discarded: params/stats/opt state keep
                # their pre-round values and their loss reads 0
                new = ClientState(*_sel(active, tuple(new), tuple(state)))
                loss = loss * active
            return new, loss

        # compressed exchange (compress/): the LITERAL dense code path is
        # kept whenever --compress none — encode/decode never enter the
        # traced program, so the default round stays bit-identical
        compressor = self.compressor
        compressed = compressor.name != "none"
        N = self.block_size(ci) if compressed else None
        # roofline comm path (ops/packed_reduce.py): the fused dense
        # reduction replaces the aggregation chokepoint outright — the
        # quantized payload stays packed across every ppermute hop.  The
        # sparse variant is per-round (it closes over the encoded payload
        # inside comm_shard below).  sharded_update reuses the same
        # chokepoint with a psum_scatter/all_gather split; the fused path
        # wins when both are on (it already divides on the owned shard).
        fused_dense = (self._fused_coll and compressed
                       and not getattr(compressor, "sparse", False))
        fused_sparse = (self._fused_coll and compressed
                        and getattr(compressor, "sparse", False))
        if fused_dense:
            from federated_pytorch_test_tpu.ops.packed_reduce import (
                make_fused_mean,
            )
            mean_fn = make_fused_mean(compressor, self.D, K)
        elif cfg.sharded_update and mean_fn is None:
            from federated_pytorch_test_tpu.parallel.comm import (
                sharded_federated_mean,
            )
            mean_fn = functools.partial(sharded_federated_mean,
                                        K=K, D=self.D)
        # sparse donated scratch: the top-k dense accumulator [K, N] is a
        # threaded operand the comm step zeroes and returns, so donation
        # reuses one HBM buffer round after round instead of
        # materializing fresh zeros (satellite of the fused-collective
        # work; base is always zeros, so the math is bitwise unchanged)
        use_scratch = bool(compressed and getattr(compressor, "sparse",
                                                  False))

        def comm_shard(state: ClientState, z, y, rho, x0, yhat0, active,
                       corrupt, gbound, scratch=None, mode=None):
            with scope("exchange_flatten"):
                x = jax.vmap(lambda p: codec.get_trainable_values(p, order, mask))(
                    state.params
                )
                if has_corrupt:
                    # fault injection happens at the encode(x_k - z) boundary:
                    # the wire delta is poisoned BEFORE compression, exactly
                    # where a faulty client corrupts a real deployment — the
                    # compressor (and its EF residual) sees the poisoned delta.
                    # active/CLIENT_AXIS feed the collective modes (innerprod/
                    # collude) their cross-client honest/colluder means; the
                    # elementwise modes ignore both.
                    x = z[None, :] + apply_corruption(
                        x - z[None, :], corrupt, corrupt_mode, corrupt_scale,
                        w=active, axis_name=CLIENT_AXIS)
                comp_state = state.comp
                round_mean = mean_fn
                if compressed:
                    # uplink-compress the update delta d_k = x_k - z; the
                    # "server" sees only x̂_k = z + decode(payload): every
                    # algorithm update below (mean / duals / BB) runs on the
                    # reconstructions, exactly what a wire-compressed
                    # deployment computes
                    from federated_pytorch_test_tpu.parallel.comm import (
                        decode_stack,
                    )
                    payload, comp_new = jax.vmap(compressor.encode)(
                        x - z[None, :], comp_state)
                    if fused_sparse:
                        # the k-sized payloads go over the wire themselves
                        # (all_gather of {idx, val}, one scatter-add per
                        # device) — the aggregate never ships dense
                        from federated_pytorch_test_tpu.ops.packed_reduce \
                            import make_sparse_fused_mean
                        round_mean = make_sparse_fused_mean(payload, z, K)
                    x = z[None, :] + decode_stack(payload, compressor, N,
                                                  scratch=scratch)
                    if partial:
                        # stragglers' PRNG/residual state stays bit-untouched
                        comp_new = _sel(active, comp_new, comp_state)
                    comp_state = comp_new
                cl_nrm = None
                if client_probe:
                    # ledger probe: raw pre-guard per-client ||x_k - z|| on the
                    # exact folded tensors (post-corruption, post-decode) — a
                    # NaN/inf delta stays visible here even though the guard
                    # below rewrites the row to z
                    cl_nrm = per_client_norms(x, z)
                w = active
                if guard_on:
                    # update guards: every incoming delta must be finite and
                    # within the round's norm bound; offenders are masked out
                    # exactly like non-participants.  NaN hygiene throughout:
                    # where-selects only — 0 * NaN is NaN, masks must never be
                    # multiplied into possibly-corrupt rows.
                    d = x - z[None, :]
                    finite = jax.vmap(lambda v: jnp.all(jnp.isfinite(v)))(d)
                    nrm = jax.vmap(jnp.linalg.norm)(
                        jnp.where(finite[:, None], d, 0.0))
                    okf = (finite & (nrm <= gbound)).astype(jnp.float32)
                    w = active * okf
                    n_ok = lax.psum(jnp.sum(w), CLIENT_AXIS)
                    n_trip = lax.psum(jnp.sum(active * (1.0 - okf)), CLIENT_AXIS)
                    norm_mean = lax.psum(jnp.sum(w * nrm), CLIENT_AXIS) \
                        / jnp.maximum(n_ok, 1.0)
                    # rejected rows are neutralised to z so no non-finite value
                    # can reach the aggregation, the BB history, or a psum
                    x = jnp.where(okf[:, None] > 0, x, z[None, :])
                    if compressed and comp_state is not None:
                        # quarantine/EF interplay: a rejected round's residual
                        # was computed from the rejected delta (non-finite for
                        # nan/inf corruption) and must NOT be applied when the
                        # client rejoins — reset it, keep stream state
                        rst = jax.vmap(compressor.reset_state)(comp_state)
                        comp_state = _sel(1.0 - active * (1.0 - okf),
                                          comp_state, rst)
            with scope("exchange_update"):
                if mode == "bb_store":  # nadmm == 0 (consensus_multi.py:243-246)
                    x0 = x
                elif mode == "bb":      # nadmm % T == 0 (:247-278)
                    rho, x0, yhat0 = bb_rho_update(
                        x, z, y, rho, x0, yhat0,
                        BBConfig(cfg.bb_period_T, cfg.bb_alphacorrmin,
                                 cfg.bb_epsilon, cfg.bb_rhomax),
                        self.D,
                    )
                znew, ynew, diag = algo.global_update(
                    x, z, y, rho, K, w=w if partial else None,
                    mean_fn=round_mean)
                if guard_on:
                    # all-rejected round degrades gracefully: z carries over
                    # (ynew is already a no-op — every ydelta is masked by w)
                    znew = jnp.where(n_ok > 0, znew, z)
                    diag["guard_trips"] = n_trip
                    diag["guard_norm_mean"] = norm_mean
                    diag["n_ok"] = n_ok
                cl_dist = None
                if client_probe:
                    # ledger probe: post-fold ||x_k - z_new|| (guard-neutralised
                    # rows measure z -> z_new, i.e. how far the round moved)
                    cl_dist = per_client_norms(x, znew)
            with scope("exchange_writeback"):
                params = state.params
                if algo.writeback:
                    wrote = jax.vmap(
                        lambda p: codec.put_trainable_values(p, order, mask, znew)
                    )(params)
                    # partial FedAvg: only the round's participants receive z;
                    # stragglers stay stale until next sampled (standard
                    # partial-participation semantics).  Guard-rejected clients
                    # do NOT receive z either (w, not active): the server has
                    # no reason to trust the return channel of a client whose
                    # uplink just failed validation; quarantine keeps them out
                    # until they re-qualify.
                    params = _sel(w, wrote, params) if partial else wrote
            if partial:
                diag["n_active"] = lax.psum(jnp.sum(active), CLIENT_AXIS)
            out_state = ClientState(params, state.batch_stats,
                                    state.opt_state, comp_state)
            out = (out_state, znew, ynew, rho, x0, yhat0, diag)
            if client_probe:
                # probe outputs sit between the base tuple and the okf/
                # scratch tail; the round loop pops from the end in the
                # reverse order (scratch, okf, probes)
                out = out + (cl_nrm, cl_dist)
            if guard_on:
                # okf rides back to the host so the round loop can
                # quarantine the offenders it names
                out = out + (okf,)
            if scratch is not None:
                # hand the (re-zeroed) accumulator back so donation can
                # alias it into next round's scratch operand (the fused
                # executor runs this body without one — fresh zeros base,
                # bitwise the same math)
                out = out + (jnp.zeros_like(scratch),)
            return out

        spec_c = P(CLIENT_AXIS)
        spec_r = P()
        state_specs = ClientState(spec_c, spec_c, spec_c, spec_c)

        # donation (cfg.donate): the state is argnum 0 everywhere; the
        # comm/fused steps additionally own the block vars z/y/rho/x0/
        # yhat0 (argnums 1-5) — every donated input is rebound from the
        # step's outputs by the round loop before the next dispatch.
        # Replicated per-round inputs (masks, norm stats, staged data,
        # guard bound) are NEVER donated: they are reused across rounds.
        train_epoch = self._instrument_jit(
            shard_map(
                epoch_shard,
                mesh=self.mesh,
                in_specs=(state_specs, spec_c, spec_c, spec_c, spec_c, spec_c,
                          spec_c, spec_r, spec_r, spec_c),
                out_specs=(state_specs, spec_c),
                check_vma=False,
            ),
            f"train_epoch[blk={ci}]",
            donate_argnums=self._donate_argnums((0,)))

        if self._overlap_round:
            # whole-round overlap: the pre-dispatched epoch runs while
            # the host still reads `state` behind it (checkpoint
            # snapshot, eval, obs emit) — the lookahead dispatch must
            # NOT donate.  Same shard body, so the math is identical;
            # with donation off (the CPU default) the main train_epoch
            # already satisfies this and is reused as-is.
            self._fn_cache[("ahead", ci)] = (
                self._instrument_jit(
                    shard_map(
                        epoch_shard,
                        mesh=self.mesh,
                        in_specs=(state_specs, spec_c, spec_c, spec_c,
                                  spec_c, spec_c, spec_c, spec_r, spec_r,
                                  spec_c),
                        out_specs=(state_specs, spec_c),
                        check_vma=False,
                    ),
                    f"train_epoch_ahead[blk={ci}]",
                    donate_argnums=())
                if self._donate else train_epoch)

        comm_out = (state_specs, spec_r, spec_c, spec_r, spec_c,
                    spec_c, spec_r)
        if client_probe:
            comm_out = comm_out + (spec_c, spec_c)   # cl_nrm, cl_dist
        if guard_on:
            comm_out = comm_out + (spec_c,)      # okf verdicts to the host
        comm_in = (state_specs, spec_r, spec_c, spec_r, spec_c,
                   spec_c, spec_c, spec_c, spec_r)
        comm_donate = (0, 1, 2, 3, 4, 5)
        if use_scratch:
            # the sparse scratch is operand 9, donated so its HBM is
            # reused for the zeroed accumulator handed back as the last
            # output
            comm_in = comm_in + (spec_c,)
            comm_out = comm_out + (spec_c,)
            comm_donate = comm_donate + (9,)
        comm_fns = {}
        for mode in ("plain", "bb_store", "bb"):
            comm_fns[mode] = self._instrument_jit(
                shard_map(
                    functools.partial(comm_shard, mode=mode),
                    mesh=self.mesh,
                    in_specs=comm_in,
                    out_specs=comm_out,
                    check_vma=False,
                ),
                f"comm[{mode},blk={ci}]",
                donate_argnums=self._donate_argnums(comm_donate))

        def init_opt(params):
            if use_lbfgs:
                return jax.vmap(
                    lambda p: lbfgs.init(
                        codec.get_trainable_values(p, order, mask))
                )(params)
            return jax.vmap(lambda p: tx.init(take_active(p)))(params)
        # no donation: callers keep ``params`` (the state that carries it
        # is re-assembled around the fresh opt state) — see JG106 note
        init_opt = jax.jit(  # graftlint: disable=JG106
            shard_map(init_opt, mesh=self.mesh, in_specs=(spec_c,),
                      out_specs=spec_c, check_vma=False)
        )

        # raw shard bodies for the fused executor (_build_fused): the
        # fused round re-traces them inside its own shard_map context
        self._fn_cache[("shard_bodies", ci)] = (epoch_shard, comm_shard)
        fns = (train_epoch, comm_fns, init_opt)
        self._fn_cache[key] = fns
        return fns

    def _comm_mode(self, nadmm: int) -> str:
        """Which comm variant this round runs (consensus_multi.py:242-278):
        BB stores the round-0 snapshot, refreshes rho every bb_period_T
        rounds, and otherwise runs the plain consensus update."""
        cfg = self.cfg
        if cfg.bb_update and nadmm == 0:
            return "bb_store"
        if cfg.bb_update and nadmm > 0 and nadmm % cfg.bb_period_T == 0:
            return "bb"
        return "plain"

    def _build_fused(self, ci: Optional[int]):
        """Fused round executor for block ``ci`` (cfg.fused_rounds).

        One jitted dispatch runs the whole communication round:
        ``lax.scan`` over the Nepoch local epochs — each epoch's shuffle
        permutation AND reparam keys are derived ON DEVICE from the same
        counter-keyed seeds the host staging path uses (`_epoch_seed`),
        via the identical ``key_data(split(PRNGKey(seed), K))``
        construction, so the math is bit-identical to the unfused path —
        with the comm update (`plain`/`bb_store`/`bb`, static) fused
        behind the scan.  Requires device-resident epoch data
        (``_setup_device_data``): the raw shards enter as non-donated
        operands and the per-epoch gather happens inside the trace.
        """
        key = ("fused", ci)
        if key in self._fn_cache:
            return self._fn_cache[key]
        assert self._dev_gather is not None, \
            "fused rounds need device-resident epoch data"
        self._build_fns(ci)            # populates the shard bodies
        epoch_shard, comm_shard = self._fn_cache[("shard_bodies", ci)]
        cfg = self.cfg
        K, K_local = cfg.K, self.K_local
        steps, B = self.data.steps, self.data.batch
        n = self.data.samples_per_client
        nB = steps * B
        guard_on = cfg.update_guard
        client_probe = self._client_probe

        def local_keys(seed):
            # EXACTLY the host staging construction (_stage_epoch /
            # _epoch_keys): key_data(split(PRNGKey(seed), K)) -> [K, 2]
            # u32, then this device's contiguous client block.  The raw
            # u32 rows are legacy keys, as on the host path.
            kd = jax.random.key_data(
                jax.random.split(jax.random.PRNGKey(seed), K))
            d = lax.axis_index(CLIENT_AXIS)
            return lax.dynamic_slice_in_dim(kd, d * K_local, K_local)

        def gather_one(key, x, y):
            # mirror of _setup_device_data's per-client epoch gather
            perm = jax.random.permutation(key, n)
            if nB > n:
                perm = jnp.concatenate([perm, perm[: nB - n]])
            idx = perm[:nB]
            return (x[idx].reshape(steps, B, *x.shape[1:]),
                    y[idx].reshape(steps, B, *y.shape[1:]))

        def fused_shard(state: ClientState, z, y, rho, x0, yhat0, active,
                        comm_active, corrupt, gbound, seeds, norm, xs, ys,
                        wb, mode):
            def epoch(carry, seed_pair):
                st, loss_acc = carry
                xb, yb = jax.vmap(gather_one, in_axes=(0, 0, 0))(
                    local_keys(seed_pair[0]), xs, ys)
                st, losses = epoch_shard(st, y, norm, local_keys(seed_pair[1]),
                                         xb, yb, wb, z, rho, active)
                return (st, loss_acc + losses), None

            (state, loss_acc), _ = lax.scan(
                epoch, (state, jnp.zeros((K_local,), jnp.float32)), seeds)
            out = comm_shard(state, z, y, rho, x0, yhat0, comm_active,
                             corrupt, gbound, mode=mode)
            return out + (loss_acc,)

        spec_c = P(CLIENT_AXIS)
        spec_r = P()
        state_specs = ClientState(spec_c, spec_c, spec_c, spec_c)
        comm_out = (state_specs, spec_r, spec_c, spec_r, spec_c,
                    spec_c, spec_r)
        if client_probe:
            comm_out = comm_out + (spec_c, spec_c)   # cl_nrm, cl_dist
        if guard_on:
            comm_out = comm_out + (spec_c,)
        fused_fns = {}
        for mode in ("plain", "bb_store", "bb"):
            fused_fns[mode] = self._instrument_jit(
                shard_map(
                    functools.partial(fused_shard, mode=mode),
                    mesh=self.mesh,
                    in_specs=(state_specs, spec_r, spec_c, spec_r, spec_c,
                              spec_c, spec_c, spec_c, spec_c, spec_r,
                              spec_r, spec_c, spec_c, spec_c, spec_c),
                    out_specs=comm_out + (spec_c,),
                    check_vma=False,
                ),
                f"fused_round[{mode},blk={ci}]",
                donate_argnums=self._donate_argnums((0, 1, 2, 3, 4, 5)))
        self._fn_cache[key] = fused_fns
        return fused_fns

    def _fused_epoch_seeds(self):
        """Stage this round's [Nepoch, 2] int32 epoch seeds (column 0:
        data shuffle stream, column 1: reparam-key stream) and advance
        BOTH counters by Nepoch — exactly the bookkeeping the unfused
        loop's Nepoch (_stage_epoch + _epoch_keys) calls perform, so a
        checkpoint taken after a fused round resumes identically on
        either path."""
        c0, c1 = self._epochs_staged, self._keys_staged
        Nepoch = self.cfg.Nepoch
        seeds = np.asarray(
            [[self._epoch_seed(c0 + e, 0), self._epoch_seed(c1 + e, 1)]
             for e in range(Nepoch)], np.int32)
        self._epochs_staged += Nepoch
        self._keys_staged += Nepoch
        return stage_global(seeds, replicated_sharding(self.mesh))

    def _build_gather(self, ci: Optional[int]):
        """[K, N] stack of flat active-block vectors (cached per block)."""
        key = ("gather", ci)
        if key not in self._fn_cache:
            mask = self.mask_for_block(ci)
            order = self.order
            self._fn_cache[key] = jax.jit(
                shard_map(
                    lambda p: jax.vmap(
                        lambda q: codec.get_trainable_values(q, order, mask)
                    )(p),
                    mesh=self.mesh, in_specs=(P(CLIENT_AXIS),),
                    out_specs=P(CLIENT_AXIS), check_vma=False,
                )
            )
        return self._fn_cache[key]

    def _apply_eval(self, p, bs, xb):
        if self.has_bn:
            return self.model.apply(
                {"params": p, "batch_stats": bs}, xb, train=False)
        return self.model.apply({"params": p}, xb, train=False)

    def eval_batch_metric(self, p, bs, xb, yb, wb):
        """Per-test-batch accumulated metric (classifier: correct count;
        pad rows of the wrap-padded final test batch carry weight 0)."""
        logits = self._apply_eval(p, bs, xb)
        return accuracy_count(logits, yb, wb).astype(jnp.float32)

    def eval_finalize(self, totals: np.ndarray, n_samples: int) -> np.ndarray:
        """Classifier: percent accuracy (federated_multi.py:121)."""
        return 100.0 * totals / n_samples

    def _build_eval(self):
        key = ("eval",)
        if key in self._fn_cache:
            return self._fn_cache[key]
        metric, prepare_batch = self.eval_batch_metric, self.prepare_batch

        def per_client(p, bs, norm, xt_u8, yt, wt):
            def step(acc, batch):
                xb_u8, yb, wb = batch
                return acc + metric(p, bs, prepare_batch(xb_u8, norm), yb,
                                    wb), None
            acc, _ = lax.scan(step, jnp.float32(0), (xt_u8, yt, wt))
            return acc

        def eval_shard(params, batch_stats, norm, xt_u8, yt, wt):
            return jax.vmap(per_client, in_axes=(0, 0, 0, None, None, None))(
                params, batch_stats, norm, xt_u8, yt, wt
            )

        spec_c = P(CLIENT_AXIS)
        # no donation: evaluation is a read — the caller's state (and the
        # round loop behind it) keeps using params/batch_stats
        fn = jax.jit(  # graftlint: disable=JG106
            shard_map(
                eval_shard,
                mesh=self.mesh,
                in_specs=(spec_c, spec_c, spec_c, P(), P(), P()),
                out_specs=spec_c,
                check_vma=False,
            )
        )
        self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    # host-side driver
    # ------------------------------------------------------------------
    def evaluate(self, state: ClientState) -> np.ndarray:
        """Per-client metric over the full test set — classifier default is
        top-1 accuracy %, verification_error_check (federated_multi.py:108-121).
        All 10k test images count: the wrap-padded remainder batch is
        weighted out, so the divisor is the true sample count."""
        fn = self._build_eval()
        totals = fn(state.params, state.batch_stats, self.client_norm,
                    self.test_x, self.test_y, self.test_w)
        return self.eval_finalize(fetch(totals), self.test_n)

    def _serve_export(self, state: ClientState):
        """The served consensus (serve/, RoundKernel._serve_tick): the
        tree-mean over the [K] client stack of (params, batch_stats) —
        the plain average the consensus z converges to.  A read, not a
        donation (same rule as _build_eval): the trainer keeps using
        ``state`` after every export."""
        from federated_pytorch_test_tpu.serve.infer import consensus_weights
        return consensus_weights((state.params, state.batch_stats))

    def _build_serve_plane(self, sched) -> dict:
        """Serving runtime for the classifier-shaped engines (serve/):
        the engine head wrapped in a bucketed jitted predictor, the
        double-buffered hot-swap, the micro-batcher, and a host traffic
        pool drawn from the real test set (wrap-padded rows weighted
        out).  The classifier engine also gets the eval stream —
        served answers scored live against the requests' labels
        (serve/evalstream.py, the serve_drift feed)."""
        from federated_pytorch_test_tpu.serve.batcher import MicroBatcher
        from federated_pytorch_test_tpu.serve.evalstream import EvalStream
        from federated_pytorch_test_tpu.serve.infer import (
            HEADS,
            BatchedPredictor,
        )
        from federated_pytorch_test_tpu.serve.swap import DoubleBuffer

        # serving normalization: the consensus model reads the MEAN of
        # the per-client train norm stats (serving is an advisory path —
        # the training math never sees this array)
        norm = np.asarray(self._client_norm_host.mean(axis=0), np.float32)

        def forward(weights, xb_u8):
            p, bs = weights
            return self._apply_eval(p, bs, self.prepare_batch(xb_u8, norm))

        head_key = ("vae" if self.obs_engine.startswith("vae")
                    else "cpc" if self.obs_engine == "cpc"
                    else "classifier")
        pred = BatchedPredictor(HEADS[head_key](forward), sched.buckets)
        plane: dict = {"buffer": DoubleBuffer(), "pred": pred}
        # the dispatch closure reads the tick's acquired snapshot
        # (plane["current"]) — one weights version per drained round
        plane["batcher"] = MicroBatcher(
            sched, lambda batch: pred(plane["current"], batch),
            max_queue=1 << 20)
        xt = np.asarray(fetch(self.test_x))
        yt = np.asarray(fetch(self.test_y))
        wt = np.asarray(fetch(self.test_w))
        keep = wt.reshape(-1) > 0
        plane["pool_x"] = xt.reshape((-1,) + xt.shape[2:])[keep]
        plane["pool_y"] = yt.reshape(-1)[keep]
        plane["pool_n"] = int(plane["pool_x"].shape[0])
        plane["stream"] = (
            EvalStream(sched, window=self.cfg.health_window)
            if head_key == "classifier" else None)
        return plane

    def _epoch_seed(self, counter: int, stream: int) -> int:
        """Deterministic seed keyed on (config seed, epoch counter, stream).

        Stateless by design: epoch ``c``'s data is a pure function of
        ``c``, so the prefetcher can build epochs ahead of the consumer
        and a mid-run checkpoint only has to record the counter (the
        previous sequential-generator scheme made the staged-one-ahead
        state unserialisable)."""
        return int(np.random.default_rng(
            [self.cfg.seed, counter, stream]).integers(2**31))

    def _host_epoch(self, counter: int):
        """Host-side (numpy) shuffle + gather for epoch ``counter`` — the
        expensive part of staging, safe to run on the worker thread."""
        return self.data.epoch_batches_raw(self._epoch_seed(counter, 0))

    def _want_device_data(self) -> bool:
        want = self.cfg.device_data
        if want is False:
            return False
        if self._pop_active:
            # population sampling re-indexes every epoch's batches by
            # the round's cohort on the HOST (slot k reads registry
            # client cohort[k]'s shard); the device-resident gather has
            # no cohort input, so auto resolves to off
            if want:
                raise ValueError(
                    "device_data=True is incompatible with population "
                    "sampling: epoch batches are re-indexed by the "
                    "round's cohort on the host (only auto/False are "
                    "valid here)")
            return False
        if not hasattr(self.data, "train_shards_raw"):
            if want:      # an explicit True that cannot be honored: say so
                raise ValueError(
                    "device_data=True but the data pipeline "
                    f"({type(self.data).__name__}) exposes no "
                    "train_shards_raw(); only auto/False are valid here")
            return False
        xt, yt = self.data.train_shards_raw()
        if want is None:      # auto: fit within the HBM budget
            budget = float(os.environ.get("FEDTPU_DEVICE_DATA_MB",
                                          2048)) * 2**20
            return xt.nbytes + yt.nbytes <= budget
        return True

    def _setup_device_data(self):
        csh = client_sharding(self.mesh)
        xt, yt = self.data.train_shards_raw()
        self._dev_x = stage_tree_global((xt, yt.astype(np.int32)), csh)
        steps, B = self.data.steps, self.data.batch
        n = self.data.samples_per_client
        nB = steps * B
        # pad weights are identical every epoch (only the last batch can
        # be partial): stage once
        w = np.ones((self.cfg.K, steps, B), np.float32)
        if getattr(self.data, "remainder", 0):
            w[:, -1, self.data.remainder:] = 0.0
        self._dev_w = stage_global(w, csh)

        def gather(keys, xs, ys):
            # per-client shuffled epoch, wrap-padded to the static step
            # grid (same drop_last=False semantics as epoch_batches_raw)
            def one(key, x, y):
                perm = jax.random.permutation(key, n)
                if nB > n:
                    perm = jnp.concatenate([perm, perm[: nB - n]])
                idx = perm[:nB]
                return (x[idx].reshape(steps, B, *x.shape[1:]),
                        y[idx].reshape(steps, B, *y.shape[1:]))
            return jax.vmap(one)(keys, xs, ys)

        self._dev_gather = jax.jit(gather, out_shardings=(csh, csh))

    def _build_epoch(self, c: int, last: bool = False):
        """Staged device arrays (xb, yb, wb) for epoch counter ``c``.

        Pure in the counter (no counter mutation — ``_stage_epoch`` owns
        that), so the overlap lookahead (``_prestage_round``) can build
        epoch ``c`` early and the consumer later accounts for it."""
        if self._dev_gather is not None:
            # device-resident path: per-client permutation keys are the
            # only host->device bytes of the epoch (counter-keyed, so
            # resume and prefetch-free runs are bit-identical)
            base = jax.random.PRNGKey(self._epoch_seed(c, 0))
            kd = np.asarray(
                jax.random.key_data(jax.random.split(base, self.cfg.K)))
            keys = stage_global(kd, client_sharding(self.mesh))
            xb, yb = self._dev_gather(keys, *self._dev_x)
            return xb, yb, self._dev_w
        return self._finish_epoch(self._epoch_raw(c, last))

    def _epoch_raw(self, c: int, last: bool = False):
        """Cohort-INDEPENDENT host half of epoch ``c``: the seeded
        shuffle (or its prefetch future) plus next-epoch prefetch
        bookkeeping.  Split out of ``_build_epoch`` so the overlap
        lookahead can run it for a population round whose cohort is not
        drawn yet — ``_finish_epoch`` applies the cohort at
        consumption."""
        if self._pending is not None and self._pending[0] == c:
            xb, yb, wb = self._pending[1].result()
        else:                        # first epoch / after resume: build now
            xb, yb, wb = self._host_epoch(c)
        self._pending = None
        if self._prefetch_epochs and not last:
            # overlap epoch c+1's permutation/gather with this round's
            # device compute; the counter-keyed seed makes the result
            # identical whether or not the future is ever consumed.
            # ``last`` (the run's provably-final epoch) suppresses the
            # submit: a trailing build would be wasted work whose
            # dataset-sized result stays pinned until the trainer dies
            self._pending = (c + 1,
                             self._stage_pool.submit(self._host_epoch, c + 1))
        return xb, yb, wb

    def _finish_epoch(self, raw):
        """Cohort re-index + H2D staging of a ``_epoch_raw`` result."""
        xb, yb, wb = raw
        if self._pop_active and self._cohort is not None:
            # population re-index: slot k trains on registry client
            # cohort[k]'s data shard (rid % K — the K on-disk shards are
            # shared round-robin across the registered id space, the
            # standard simulation regime for K ≫ dataset partitions).
            # Applied at CONSUMPTION, after the counter-keyed prefetch
            # future resolves, so the prefetch (and the overlap
            # lookahead) stays cohort-free and a resumed run re-derives
            # the identical rows from the cohort it restored.
            rows = (self._cohort % self.cfg.K).astype(np.int64)
            xb, yb, wb = xb[rows], yb[rows], wb[rows]
        sh = client_sharding(self.mesh)
        return (stage_global(xb, sh), stage_global(yb, sh),
                stage_global(wb, sh))

    def _stage_epoch(self, last: bool = False):
        # every process builds the same shuffle (seed-deterministic), so on
        # multi-host each stages only its addressable client shards
        c = self._epochs_staged
        self._epochs_staged += 1
        if self._staged_ahead is not None and self._staged_ahead[0] == c:
            # overlap lookahead hit (cfg.overlap_staging): this epoch was
            # staged while the previous round's comm step executed.
            # Population lookaheads carry the RAW host arrays (the
            # cohort was not drawn at prestage time) — finish them now,
            # under this round's actual cohort.
            _, payload, needs_finish = self._staged_ahead
            self._staged_ahead = None
            return self._finish_epoch(payload) if needs_finish else payload
        self._staged_ahead = None
        return self._build_epoch(c, last)

    def _build_keys(self, c: int):
        base = jax.random.PRNGKey(self._epoch_seed(c, 1))
        keys = jax.random.split(base, self.cfg.K)
        keys = np.asarray(jax.random.key_data(keys))
        return stage_global(keys, client_sharding(self.mesh))

    def _epoch_keys(self):
        """Per-client PRNG keys [K, 2] for this epoch (reparam sampling —
        replaces torch.cuda.FloatTensor.normal_, simple_models.py:292-301)."""
        c = self._keys_staged
        self._keys_staged += 1
        if self._keys_ahead is not None and self._keys_ahead[0] == c:
            out = self._keys_ahead[1]
            self._keys_ahead = None
            return out
        self._keys_ahead = None
        return self._build_keys(c)

    def _prestage_round(self) -> float:
        """Staging/comm overlap (cfg.overlap_staging): build the NEXT
        epoch's batches and reparam keys now — the caller invokes this
        between the comm round's asynchronous dispatch and the blocking
        diagnostics fetch, so the host shuffle + H2D copy execute while
        the devices run the collective.  Pure lookahead on the
        counter-keyed seeds: only consumption (``_stage_epoch`` /
        ``_epoch_keys``) advances the counters, so checkpoint meta,
        telemetry counters, and the math are bit-identical with the flag
        off, and a kill between prestage and consumption resumes exactly
        (the cache is rebuilt from the counter).  Returns the host
        seconds spent, 0.0 when there is nothing left to stage."""
        cfg = self.cfg
        total = cfg.Nloop * self.L * cfg.Nadmm * cfg.Nepoch
        c = self._epochs_staged
        if c >= total or self._staged_ahead is not None:
            return 0.0
        # deliberately times dispatch, not execution: overlap_seconds is
        # the HOST cost of the lookahead (shuffle + H2D enqueue) — a sync
        # here would serialize the copy against the comm step, which is
        # exactly what --overlap-staging exists to avoid
        t0 = time.perf_counter()  # graftlint: disable=JG104
        last = c == total - 1
        if self._pop_active:
            # the NEXT round's cohort is not drawn yet — stage the
            # cohort-independent half (seeded shuffle) and defer the
            # cohort re-index + H2D copy to consumption (needs_finish)
            self._staged_ahead = (c, self._epoch_raw(c, last), True)
        else:
            self._staged_ahead = (c, self._build_epoch(c, last), False)
        if self._keys_ahead is None:
            ck = self._keys_staged
            self._keys_ahead = (ck, self._build_keys(ck))
        return time.perf_counter() - t0

    def _predispatch_round(self, coords, train_epoch_ahead,
                           state, z, y, rho, cnorm) -> float:
        """Round-level overlap (cfg.overlap_round): dispatch the NEXT
        round's first train epoch while the current comm collective is
        still executing on-device.  The ahead dispatch reuses the
        overlap-staging cache (``_prestage_round``), derives the next
        round's participation mask from the stateless counter-keyed
        ``_round_mask`` and never donates its inputs — the comm outputs
        it closes over are only donated by the NEXT comm call, after
        this dispatch's result has been consumed.  Values are identical
        to the sequential loop (same fn, same operands); only dispatch
        ORDER changes, so trajectories stay bitwise and kill/resume is
        exact (counters advance at consumption, ``_take_round_ahead``).
        Returns host seconds spent enqueueing, 0.0 when skipped."""
        cfg = self.cfg
        total = cfg.Nloop * self.L * cfg.Nadmm * cfg.Nepoch
        c = self._epochs_staged
        if c >= total:
            return 0.0
        t0 = time.perf_counter()  # graftlint: disable=JG104
        self._prestage_round()           # no-op if already staged
        if self._staged_ahead is None or self._keys_ahead is None:
            return 0.0               # nothing stageable (end of schedule)
        _, payload, needs_finish = self._staged_ahead
        if needs_finish:
            # defensive: raw (cohort-deferred) payloads only exist when
            # population sampling is active, and population disables
            # overlap_round at __init__ — but if that gating ever
            # relaxes, dispatching here under a stale cohort would be
            # wrong, so leave the staged payload for _stage_epoch (the
            # consumption path, which finishes under the actual cohort)
            return 0.0
        xb, yb, wb = payload
        ck, keys = self._keys_ahead
        active = self._round_mask(*coords)
        out = train_epoch_ahead(state, y, cnorm, keys, xb, yb, wb,
                                z, rho, active)
        self._round_ahead = (coords, c, ck, out)
        return time.perf_counter() - t0

    def _take_round_ahead(self, coords):
        """Consume a ``_predispatch_round`` result if it matches this
        round's coords and counters; advances the staging counters (the
        checkpoint-meta source of truth) exactly as the sequential
        ``_stage_epoch`` + ``_epoch_keys`` pair would."""
        ra, self._round_ahead = self._round_ahead, None
        if ra is None:
            return None
        rc, c, ck, out = ra
        if (rc != coords or c != self._epochs_staged
                or ck != self._keys_staged):
            return None              # resume/desync: fall back, recompute
        self._epochs_staged += 1
        self._keys_staged += 1
        self._staged_ahead = None
        self._keys_ahead = None
        self._host_dispatches += 1
        return out

    def init_state(self) -> ClientState:
        """A fresh training state — a deep COPY of the staged init, never
        an alias: the round fns donate the state's buffers (``--donate``),
        and ``params0``/``batch_stats0`` must survive them (``block_size``
        and the mask builders read ``params0`` all run long)."""
        copy = lambda t: jax.tree.map(jnp.copy, t)
        return ClientState(copy(self.params0), copy(self.batch_stats0), None)

    def _init_comp_state(self, ci: Optional[int]):
        """Fresh [K]-stacked compressor state for block ``ci`` (or None).

        Recreated at every block switch like the optimizer state: the
        residual/PRNG shapes follow the active block's flat size.  Seeded
        deterministically per (cfg.seed, block), so a resumed run that
        re-enters a block draws the identical quantization streams.
        """
        if self.compressor.name == "none":
            return None
        seed = int(np.random.default_rng(
            [self.cfg.seed, 23, 0 if ci is None else ci]).integers(2**31))
        host = stacked_init(self.compressor, self.cfg.K,
                            self.block_size(ci), seed)
        if host is None:                   # stateless compressor (plain topk)
            return None
        return stage_tree_global(host, client_sharding(self.mesh))

    def _fresh_comp_host(self, ci: Optional[int]):
        """Host-side fresh [K]-stacked compressor state for block ``ci``
        — the un-staged twin of ``_init_comp_state`` (same seed recipe),
        cached per block: the population comp-row rotation consults the
        fresh rows every round."""
        key = (0 if ci is None else ci, self.cfg.compress)
        cached = getattr(self, "_pop_comp_fresh", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        seed = int(np.random.default_rng(
            [self.cfg.seed, 23, 0 if ci is None else ci]).integers(2**31))
        host = stacked_init(self.compressor, self.cfg.K,
                            self.block_size(ci), seed)
        self._pop_comp_fresh = (key, host)
        return host

    def _population_swap_comp(self, comp, ci: Optional[int]):
        """Rotate the [K]-stacked compressor/EF rows to this round's
        cohort (population mode): stash the previous cohort's rows in
        the registry, rebuild the stack as each new member's stored row
        (if it was sampled before this block) or the block's fresh init
        row for the slot it landed in, and restage.  A host round trip —
        population rounds already pay a host boundary for the cohort
        gather, and the comp state is [K, ~N] small next to the epoch
        data.  This is what makes EF residuals PER-CLIENT state: a
        client resuming after rounds unsampled carries on from its own
        residual, not whatever its slot last held."""
        reg = self._registry
        cohort = self._cohort
        if (self._pop_comp_prev is not None
                and np.array_equal(self._pop_comp_prev, cohort)):
            return comp              # same cohort: rows already in place
        if self._pop_comp_prev is None and reg.comp_rows == 0:
            # first round of the block: the live state IS the fresh init
            self._pop_comp_prev = cohort.copy()
            return comp
        leaves = [np.asarray(fetch(l)) for l in jax.tree.leaves(comp)]
        treedef = jax.tree.structure(comp)
        stacked = [l.ndim >= 1 and l.shape[0] == self.cfg.K
                   for l in leaves]
        if self._pop_comp_prev is not None:
            reg.stash_comp_rows(self._pop_comp_prev, leaves, stacked)
        fresh_leaves = [np.asarray(l)
                        for l in jax.tree.leaves(self._fresh_comp_host(ci))]
        out = reg.load_comp_rows(cohort, fresh_leaves, stacked)
        # block-global (non-client-stacked) leaves keep their live values
        out = [o if is_k else cur
               for o, cur, is_k in zip(out, leaves, stacked)]
        self._pop_comp_prev = cohort.copy()
        return stage_tree_global(jax.tree.unflatten(treedef, out),
                                 client_sharding(self.mesh))

    def _fresh_fn(self, name: str, make, shardings):
        """``jit(make)`` of a program WITHOUT operands: every call hands
        back fresh device buffers (the comm step donates them), filled
        where they live instead of copied from host memory.
        ``out_shardings`` are the ``NamedSharding`` objects
        ``stage_global`` committed the host arrays to, letter for letter
        (on a one-device mesh a ``shard_map`` output would come back as
        ``P()`` whatever its spec), so the round fns see the signatures
        they always saw and hit the same compiled entries.  Cached by
        ``name`` beside the block's other fns and instrumented like
        them, so the cost ledger and the retrace sentinel see it."""
        key = ("fresh", name)
        if key not in self._fn_cache:
            self._fn_cache[key] = self._instrument_jit(
                make, name, out_shardings=shardings)
        return self._fn_cache[key]

    def _fresh_block_vars(self, N: int):
        """Fresh per-block ``(z, y, rho, x0)`` — plus ``yhat0`` where
        ``bb_update`` is off (under BB it is the gather of the params at
        block start) — made by one device program per ``(N, ydim, x0
        width)`` (federated_multi.py:148-159): zeros and ``admm_rho0``,
        float32, z/rho replicated and the [K, .] stacks client-sharded."""
        cfg = self.cfg
        ydim = N if self.algo.needs_dual else 1
        x0w = N if cfg.bb_update else 1
        rsh, csh = replicated_sharding(self.mesh), client_sharding(self.mesh)

        def make():
            zeros = lambda *shape: jnp.zeros(shape, jnp.float32)
            out = (zeros(N), zeros(cfg.K, ydim),
                   jnp.asarray(cfg.admm_rho0, jnp.float32),
                   zeros(cfg.K, x0w))
            return out if cfg.bb_update else out + (zeros(cfg.K, 1),)

        shardings = (rsh, csh, rsh, csh)
        return self._fresh_fn(
            f"block_vars[N={N},y={ydim},x0={x0w}]", make,
            shardings if cfg.bb_update else shardings + (csh,))()

    def _init_sparse_scratch(self, N: int):
        """Zeroed [K, N] accumulator the sparse top-k comm step scatters
        into and hands back re-zeroed — the donated operand that lets XLA
        reuse one HBM buffer for the dense accumulation every round
        instead of materializing fresh zeros (``comm_shard``).  ``None``
        on every non-sparse path so default signatures are untouched."""
        if not getattr(self.compressor, "sparse", False):
            return None
        K = self.cfg.K
        return self._fresh_fn(
            f"sparse_scratch[N={N}]",
            lambda: jnp.zeros((K, N), jnp.float32),
            client_sharding(self.mesh))()

    def round_bytes_on_wire(self, N: int, n_active: int) -> int:
        """Uplink bytes this comm round: every participant ships one
        encoded block payload (the dense path ships the f32 block — the
        reference's README.md:2 claim, now measured per round)."""
        return int(n_active) * int(self.compressor.bytes_on_wire(N))

    def round_bytes_fused(self, N: int) -> int:
        """Predicted device-to-device bytes of the fused collective this
        round (ops/packed_reduce.py): the packed reduce-scatter +
        all-gather hop volume for dense q8/q4, the payload all_gather for
        top-k.  Compare against ``bytes_on_wire`` (the unfused uplink
        model) in the pareto table."""
        from federated_pytorch_test_tpu.ops.packed_reduce import (
            fused_bytes_on_wire,
        )
        return int(fused_bytes_on_wire(self.compressor, N, self.D,
                                       self.cfg.K))

    # ------------------------------------------------------------------
    # mid-run checkpoint / resume (SURVEY.md section 5 "actually resumable
    # mid-run").  The reference can only restart from its end-of-run
    # s<k>.model files (federated_multi.py:99-103, :226-233); here every
    # communication round checkpoints params + batch_stats + optimizer
    # state + the ADMM block variables (z, y, rho, BB state) + loop
    # counters + the host shuffle PRNG, so a killed run resumes at the
    # exact round with a bit-identical trajectory.
    # ------------------------------------------------------------------
    def _save_midrun(self, path, state: ClientState, blockvars, nxt,
                     history) -> None:
        from federated_pytorch_test_tpu.utils.checkpoint import (
            mesh_geometry_meta,
            pack_history,
            save_checkpoint_swapped,
            snapshot_to_host,
        )

        nloop, ci, nadmm = nxt
        mid_block = nadmm > 0
        tree = {"params": state.params, "batch_stats": state.batch_stats}
        if mid_block:   # block vars only meaningful while inside a block
            # flat leaf list: orbax round-trips optax/LBFGS NamedTuple
            # states as plain dicts, so the structure is rebuilt on restore
            # from a freshly init'd template (leaf order is deterministic)
            tree["opt_state_leaves"] = list(jax.tree.leaves(state.opt_state))
            comp_leaves = list(jax.tree.leaves(state.comp))
            if comp_leaves:   # stateful compression: PRNG keys / residuals
                tree["comp_state_leaves"] = comp_leaves
            tree.update(zip(("z", "y", "rho", "x0", "yhat0"), blockvars))
        meta = {
            "nloop": nloop, "ci": ci, "nadmm": nadmm,
            "mid_block": int(mid_block),
            # per-epoch randomness is keyed on these counters
            # (_epoch_seed), so they are the ENTIRE data-order state —
            # resume replays the exact epoch sequence
            "epochs_staged": self._epochs_staged,
            "keys_staged": self._keys_staged,
            "history": pack_history(history),
        }
        # mesh geometry + churn/guard/async ledgers (RoundKernel): both
        # ride the sync AND async writers identically — plain meta keys
        meta.update(self._ledger_meta())
        if self._ckpt_writer is not None:
            # async path: materialize a host copy NOW (donation-safe — the
            # device buffers may be donated away by the very next round's
            # dispatch) and let the writer thread serialize/sha256/rotate;
            # the submission queue orders saves, so slot rotation for
            # round N always completes before round N+1 touches the dir
            self._ckpt_writer.submit(path, snapshot_to_host(tree), meta)
        else:
            save_checkpoint_swapped(path, tree, meta)

    def _restore_midrun(self, path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            load_checkpoint,
            restore_leaves,
            unpack_history,
            validate_geometry,
        )

        tree, meta = load_checkpoint(path)
        # geometry gate FIRST: a wrong-D/wrong-K slot must die with the
        # typed, actionable error before any device_put can produce an
        # opaque reshape traceback.  Under cfg.elastic_resume a D != D'
        # checkpoint passes and the stage_tree_global calls below restage
        # the [K, ...] client stacks onto the CURRENT mesh — the client
        # axis re-shards, replicated vars re-lay out, and the jitted fns
        # were already built over this mesh (PARITY.md: bitwise when
        # D' == D, allclose + exact history when D' != D).
        validate_geometry(meta, devices=self.D,
                          processes=jax.process_count(), K=self.cfg.K,
                          elastic=self.cfg.elastic_resume)
        csh = client_sharding(self.mesh)
        rsh = replicated_sharding(self.mesh)
        put_c = lambda t: stage_tree_global(t, csh)
        put_r = lambda t: stage_tree_global(t, rsh)
        mid = bool(meta["mid_block"])
        params = put_c(tree["params"])
        opt = None
        comp = None
        blockvars = None
        if mid:
            _, _, init_opt = self._build_fns(int(meta["ci"]))
            # eval_shape: only the template STRUCTURE is needed — skip the
            # jitted shard_map init compile + device work at restore time
            opt = put_c(restore_leaves(tree["opt_state_leaves"],
                                       jax.eval_shape(init_opt, params)))
            if "comp_state_leaves" in tree:
                # fresh init supplies the structure; saved leaves (PRNG
                # keys mid-stream, EF residuals) overwrite its values
                comp = put_c(restore_leaves(
                    tree["comp_state_leaves"],
                    self._init_comp_state(int(meta["ci"]))))
            else:
                # checkpoint predates compression (or was saved dense):
                # a stateful compressor starts this block's state fresh
                comp = self._init_comp_state(int(meta["ci"]))
            blockvars = (put_r(tree["z"]), put_c(tree["y"]),
                         put_r(tree["rho"]), put_c(tree["x0"]),
                         put_c(tree["yhat0"]))
        state = ClientState(params, put_c(tree["batch_stats"]), opt, comp)
        if "epochs_staged" not in meta:
            raise RuntimeError(
                "mid-run checkpoint predates the counter-keyed epoch "
                "staging (old pickled-generator format) and cannot be "
                "resumed by this build; restart the run or load the "
                "end-of-run checkpoint instead")
        self._epochs_staged = int(meta["epochs_staged"])
        self._keys_staged = int(meta["keys_staged"])
        # any overlap lookahead predates the restored counters: drop it —
        # the counter-keyed seeds rebuild the identical epoch on demand
        self._staged_ahead = None
        self._keys_ahead = None
        self._round_ahead = None
        self._restore_ledger_meta(meta)
        # a pending prefetched epoch stays valid across restore IF its
        # counter matches (epochs are pure functions of the counter);
        # _stage_epoch's counter check handles both cases
        history = unpack_history(meta["history"])
        return state, blockvars, (int(meta["nloop"]), int(meta["ci"]),
                                  int(meta["nadmm"]), mid), history

    def _check_restored_finite(self, restored) -> None:
        """Reject a restored snapshot that carries NaN/inf params or
        block consensus vars.  Used by the resume slot-walk: such a
        slot is checksum-valid (the poison was faithfully saved) but
        resuming it replays the failure, so the walk treats it like a
        corrupt slot and falls back to the next-older generation."""
        state, blockvars = restored[0], restored[1]
        leaves = list(jax.tree_util.tree_leaves(state.params))
        if blockvars is not None:
            leaves += [blockvars[0], blockvars[1]]   # z, y: the fold targets
        for leaf in leaves:
            a = np.asarray(jax.device_get(leaf))
            if a.dtype.kind == "V":                  # ml_dtypes bf16 et al.
                a = a.astype(np.float32)
            if a.dtype.kind == "f" and not np.all(np.isfinite(a)):
                raise ValueError(
                    "restored state carries non-finite values "
                    "(poisoned checkpoint)")

    def _profile_ctx(self):
        """jax.profiler trace over the run when cfg.profile_dir is set
        (shared helper, utils/profiling.py)."""
        return profile_ctx(self.cfg.profile_dir)

    def _obs_epoch_images(self) -> int:
        """Images processed per LOCAL EPOCH across all clients
        (bench.py's convention: K * steps * batch, wrap-padding
        included); a comm round covers cfg.Nepoch of these."""
        steps = getattr(self.data, "steps", None)
        batch = getattr(self.data, "batch", None)
        if not steps or not batch:
            return 0
        return int(self.cfg.K * steps * batch)

    def close(self):
        """Stop the epoch-staging worker and drop any in-flight prefetch.

        Without this, an aborted run (exception mid-loop, or a caller like
        bench_block that drives ``_stage_epoch`` directly and never reaches
        the ``last=True`` suppression) leaves a dataset-sized epoch pinned
        by the pending future and a non-daemon worker delaying interpreter
        exit.  Idempotent; mirrors ``RoundPrefetcher.close`` (data/lofar.py).
        """
        self._prefetch_epochs = False     # no further submits
        self._pending = None
        self._staged_ahead = None
        self._keys_ahead = None
        self._round_ahead = None
        self._stage_pool.shutdown(wait=False, cancel_futures=True)
        # drain the async checkpoint writer so an aborted run's LAST
        # submitted round is still durable on disk (the kill/resume
        # contract); a background write failure must not mask the
        # exception that aborted the run, so it is swallowed here —
        # the normal-exit barrier in _run_impl re-raises instead
        try:
            self._flush_ckpt_writer()
        except Exception:
            pass

    def _flush_ckpt_writer(self) -> None:
        """Write barrier: wait for queued async checkpoint saves, then
        retire the writer (idempotent; re-raises background failures)."""
        writer, self._ckpt_writer = self._ckpt_writer, None
        if writer is not None:
            writer.close()

    def _apply_block_control(self, obs, log=print):
        """Apply act-mode block-scope decisions (compressor swap).

        Runs at the block boundary BEFORE the block's fns/scratch/comp
        state are built: the new compressor is baked into freshly
        compiled round fns and gets fresh per-block compression state,
        exactly as if the run had been constructed with it.  A swap
        that would violate a construction rule (sparse wire under a
        fused dual-state collective) is skipped, not forced.
        """
        import dataclasses as _dc

        ctl = obs.control
        for d in ctl.take_block():
            if d.param != "compress":
                continue
            new = str(d.to_value)
            if new == self.cfg.compress:
                continue
            comp = make_compressor(new, topk_frac=self.cfg.topk_frac,
                                   quant_chunk=self.cfg.quant_chunk,
                                   error_feedback=self.cfg.error_feedback)
            if self._fused_coll and comp.name == "none":
                log("control: skip compress -> none (fused_collective "
                    "needs a packed wire format)")
                continue
            if (self._fused_coll and getattr(comp, "sparse", False)
                    and self.algo.needs_dual):
                log(f"control: skip compress -> {new} (sparse wire is "
                    "unavailable under a fused dual-state collective)")
                continue
            old = self.cfg.compress
            with self._cfg_swap_lock:
                self.compressor = comp
                self.cfg = _dc.replace(self.cfg, compress=new)
            self._fn_cache.clear()
            log(f"control: {d.intervention} compress {old} -> {new} at "
                f"block boundary ({d.reason})")

    def __del__(self):
        try:
            self.close()
        except Exception:                 # interpreter teardown: best-effort
            pass

    def run(self, *args, **kw):
        """The full loop nest (see ``_run_impl``), optionally profiled."""
        try:
            with self._profile_ctx():
                return self._run_impl(*args, **kw)
        except BaseException:
            # an aborted nest leaves a pending prefetch + live worker; the
            # trainer is done either way, so release them (close is the
            # documented terminal state — _stage_epoch stops prefetching).
            # The obs stream gets its summary event too, flagged aborted
            # (idempotent: a no-op if the run closed it normally), after
            # the tail of the round that raised (it ends here: on_round
            # is where a caller stops a run) and the other pending marks
            self._close_round_tail()
            self.close()
            if self.obs_recorder is not None:
                self._flush_outer_spans(self.obs_recorder)
                self.obs_recorder.close(status="aborted")
            raise

    def _run_impl(
        self,
        state: Optional[ClientState] = None,
        log: Callable[[str], None] = print,
        on_round: Optional[Callable[..., None]] = None,
        checkpoint_path: Optional[str] = None,
        resume: bool = False,
    ):
        """The full loop nest.  Returns (state, history).

        ``checkpoint_path``: save a resumable mid-run checkpoint after every
        communication round.  ``resume=True`` (with an existing checkpoint)
        restores it and continues at the exact next round.

        ``history`` records per communication round: block, residuals, rho,
        and per-client accuracies (when cfg.check_results).
        """
        cfg, algo = self.cfg, self.algo
        state = state or self.init_state()
        history: List[Dict[str, Any]] = []
        csh = client_sharding(self.mesh)

        from federated_pytorch_test_tpu.utils.checkpoint import (
            CheckpointCorruptError,
            CheckpointGeometryError,
            checkpoint_slots,
            verify_checkpoint,
        )

        resume_at = None
        slots = (checkpoint_slots(checkpoint_path)
                 if resume and checkpoint_path is not None else [])
        failures = []
        for slot in slots:
            try:
                verify_checkpoint(slot)      # raises on checksum mismatch
                restored = self._restore_midrun(slot)
                # poison screen: a checkpoint whose params/block vars
                # carry NaN/inf is checksum-valid but useless — resuming
                # it replays the failure forever.  Fall back to the
                # next-older slot instead (the rotation keeps three
                # generations, so the last pre-poison save is usually
                # still on disk).  This is the restore path ALL resumes
                # share, so a supervised restart stays bitwise identical
                # to a manual one.
                self._check_restored_finite(restored)
                state, r_blockvars, resume_at, history = restored
            except CheckpointGeometryError:
                # every slot was written on the same geometry — falling
                # back cannot fix a mesh mismatch and would only bury
                # the actionable message under a corrupt-slot error
                raise
            except Exception as e:           # corrupt/truncated slot:
                failures.append(f"{slot}: {e}")     # fall back, don't die
                log(f"WARNING: checkpoint slot {slot} is unusable ({e}); "
                    "falling back to the previous slot")
                continue
            log(f"resumed mid-run checkpoint {slot} at "
                f"(nloop, block, nadmm)={resume_at[:3]}")
            break
        else:
            if failures:
                raise CheckpointCorruptError(
                    "no valid mid-run checkpoint slot survives: "
                    + "; ".join(failures))

        # one-shot preemption arming: the preempt= draw is deterministic
        # in the round coordinates, so a RESUMED segment replaying the
        # failing round must not re-fire — the simulated slice was
        # already lost once, and the supervisor's restart is the
        # surviving mesh carrying on
        self._preempt_armed = resume_at is None
        # the campaign twin of that arming flag: deterministic
        # preempt_at events only fire STRICTLY past the resumed
        # segment's starting round, and the transition-only `campaign`
        # record emission restarts with the segment
        self._campaign_floor = len(history) if resume_at is not None else -1
        self._campaign_last_hour = None

        if cfg.async_checkpoint and checkpoint_path is not None:
            # created AFTER the resume restore (nothing may be in flight
            # while slots are being read); multi-host keeps the sync path
            # — the orbax save is a collective and must stay on the main
            # thread of every process
            if jax.process_count() > 1:
                log("WARNING: async_checkpoint is single-process only; "
                    "multi-host runs keep the synchronous save")
            elif self._ckpt_writer is None:
                from federated_pytorch_test_tpu.utils.checkpoint import (
                    AsyncCheckpointWriter,
                )
                self._ckpt_writer = AsyncCheckpointWriter()

        obs = self._open_obs(resumed=resume_at is not None,
                             rounds_prior=len(history))
        if obs.control is not None:
            # checkpoint-then-restart is only on the table when there is
            # a checkpoint to restart from; without one the decision is
            # recorded (applied=False) and nothing is raised
            obs.control.can_restart = checkpoint_path is not None
        obs_images = cfg.Nepoch * self._obs_epoch_images()
        # the end stamp of the previous round, for gap_seconds (schema v15)
        t_prev_end = None
        for nloop in range(cfg.Nloop):
            for ci in range(self.L):
                if resume_at is not None and (nloop, ci) < resume_at[:2]:
                    continue
                # block switch: its start, then the end of each of
                # rounds.BLOCK_SWITCH_PARTS (plain clock reads, no sync:
                # what these calls enqueue runs behind them)
                switch = [time.perf_counter()]
                if obs.control is not None:
                    # block-scope interventions (compressor swap) land
                    # HERE, before the round fns/scratch/comp-state for
                    # this block are built — the compressor is baked
                    # into the compiled fns, so mid-block application
                    # is impossible by construction
                    self._apply_block_control(obs, log)
                train_epoch, comm_fns, init_opt = self._build_fns(ci)
                # non-donating twin for the overlap_round pre-dispatch:
                # its operands (this round's comm outputs) must survive
                # until the NEXT comm call donates them
                train_epoch_ahead = self._fn_cache.get(
                    ("ahead", ci), train_epoch)
                switch.append(time.perf_counter())
                N = self.block_size(ci)
                # donated sparse accumulator (top-k only): zeroed [K, N]
                # buffer the comm step scatters into and hands back
                # re-zeroed, so one HBM allocation serves every round of
                # the block.  Not checkpointed — it is zeros between
                # rounds by construction.
                scratch = self._init_sparse_scratch(N)
                switch.append(time.perf_counter())
                nadmm_start = 0
                # bytes this switch stages from host memory (schema v16):
                # a stateful compressor's fresh rows, nothing else
                switch_h2d = 0
                if (resume_at is not None and (nloop, ci) == resume_at[:2]
                        and resume_at[3]):
                    # resume inside this block: restored z/y/rho/BB/opt state
                    z, y, rho, x0, yhat0 = r_blockvars
                    nadmm_start = resume_at[2]
                    resume_at = None
                    switch.append(time.perf_counter())
                else:
                    resume_at = None
                    # fresh per-block state (federated_multi.py:148-159),
                    # made on the device: no array leaves host memory
                    if cfg.bb_update:
                        # yhat0 init = params at block start
                        # (consensus_multi.py:184)
                        z, y, rho, x0 = self._fresh_block_vars(N)
                        yhat0 = self._build_gather(ci)(state.params)
                    else:
                        z, y, rho, x0, yhat0 = self._fresh_block_vars(N)
                    switch.append(time.perf_counter())
                    comp = self._init_comp_state(ci)
                    switch_h2d = sum(int(x.nbytes)
                                     for x in jax.tree.leaves(comp))
                    state = ClientState(state.params, state.batch_stats,
                                        init_opt(state.params), comp)
                    # fresh block => fresh guard scale, void in-flight
                    # async updates (RoundKernel)
                    self._reset_block_ledgers()
                switch.append(time.perf_counter())

                for nadmm in range(nadmm_start, cfg.Nadmm):
                    # one XProf step per comm round, keyed on the
                    # global round index == the obs round_index, so
                    # trace steps line up 1:1 with the JSONL records
                    with round_trace(len(history),
                                     enabled=cfg.profile_dir is not None):
                        t_round = time.perf_counter()
                        switch_s = None
                        if switch is not None:
                            # the block visit's first round: the switch
                            # ends where the round's window opens
                            switch_s = t_round - switch[0]
                            if obs.enabled:
                                self._mark_block_switch(switch, t_round,
                                                        len(history))
                            switch = None
                        # the campaign tick FIRST: it derives this
                        # round's fault spec (and may raise the
                        # deterministic preempt_at event) before any
                        # family draws from it
                        self._campaign_tick(len(history), nloop, ci,
                                            nadmm, checkpoint_path)
                        self._maybe_preempt(nloop, ci, nadmm,
                                            len(history), checkpoint_path)
                        active, comm_active, corrupt, comm_host, fcounts = \
                            self._round_activity(nloop, ci, nadmm)
                        n_comm = fcounts.pop("n_comm", 1)
                        cnorm = self.client_norm
                        if self._pop_active:
                            # the cohort just rotated: move per-client
                            # compressor/EF rows to the new members and
                            # re-point slot norm stats at the cohort's
                            # data shards (rid % K, like _build_epoch)
                            if jax.tree.leaves(state.comp):
                                state = state._replace(
                                    comp=self._population_swap_comp(
                                        state.comp, ci))
                            rows = (self._cohort % cfg.K).astype(np.int64)
                            cnorm = stage_global(
                                self._client_norm_host[rows], csh)
                        if (self._churn_live
                                and self._rejoined_mask.any()
                                and jax.tree.leaves(state.comp)):
                            # rejoining clients are NEW clients: their
                            # stale EF residual / compressor PRNG rows
                            # reset to block-init values
                            state = state._replace(comp=self._reset_comp_rows(
                                state.comp, ci, self._rejoined_mask))
                        q_start = (int(np.sum(self._quarantine > 0))
                                   if cfg.update_guard else 0)
                        loss_acc = None       # on-device [K] accumulator: the
                        cl_nrm = cl_dist = None   # client-ledger probes
                        stage_s = 0.0         # host fetch happens ONCE per round
                        overlap_s = 0.0       # host staging hidden behind comm
                        overlap_dispatch_s = 0.0   # ahead-epoch enqueue cost
                        phase_marks = []      # (name, cat, t0, t1) span bounds
                        dispatch0 = self._host_dispatches
                        run_fused = (self._use_fused and algo.communicates
                                     and n_comm > 0)
                        if run_fused:
                            # fused round (cfg.fused_rounds): ONE dispatch
                            # scans the Nepoch epochs and runs the comm
                            # update behind them; the [Nepoch, 2] seed
                            # stage is the round's only H2D traffic.  The
                            # whole round is one program, so the dispatch
                            # lands in train_seconds and comm_seconds
                            # reads 0 (PARITY.md timing note)
                            t_stage = time.perf_counter()
                            seeds = self._fused_epoch_seeds()
                            gbound = self._round_gbound()
                            self._obs_sync(obs, seeds)
                            stage_s += time.perf_counter() - t_stage
                            t_train = time.perf_counter()
                            mode = self._comm_mode(nadmm)
                            out = self._build_fused(ci)[mode](
                                state, z, y, rho, x0, yhat0, active,
                                comm_active, corrupt, gbound, seeds,
                                self.client_norm, *self._dev_x,
                                self._dev_w)
                            self._host_dispatches += 1
                            # pop the variadic tail in reverse build
                            # order: loss, okf verdicts, ledger probes
                            loss_acc = out[-1]
                            out = out[:-1]
                            if cfg.update_guard:
                                okf = out[-1]
                                out = out[:-1]
                            if self._client_probe:
                                cl_nrm, cl_dist = out[-2], out[-1]
                                out = out[:-2]
                            state, z, y, rho, x0, yhat0, diag = out
                            diag = {k: float(v) for k, v in diag.items()}
                            if cfg.update_guard:
                                self._apply_guard_verdicts(
                                    diag, okf, comm_host)
                            self._obs_sync(obs, state, z, y, loss_acc)
                            train_s = time.perf_counter() - t_train
                            comm_s = 0.0
                            if obs.enabled:
                                # span bounds reuse the timestamps just
                                # taken — no extra syncs (obs/trace.py)
                                phase_marks = [
                                    ("stage", "phase", t_stage,
                                     t_stage + stage_s),
                                    ("train", "phase", t_train,
                                     t_train + train_s)]
                        else:
                            t_train = time.perf_counter()
                            for nepoch in range(cfg.Nepoch):
                                ahead = (self._take_round_ahead(
                                    (nloop, ci, nadmm))
                                    if nepoch == 0 and self._overlap_round
                                    else None)
                                if ahead is not None:
                                    # epoch 0 was pre-dispatched behind
                                    # the previous round's collective
                                    # (cfg.overlap_round) — same fn,
                                    # same operands, values bitwise; the
                                    # counters advanced at _take time
                                    state, losses = ahead
                                else:
                                    t_stage = time.perf_counter()
                                    xb, yb, wb = self._stage_epoch(
                                        last=(nloop == cfg.Nloop - 1
                                              and ci == self.L - 1
                                              and nadmm == cfg.Nadmm - 1
                                              and nepoch == cfg.Nepoch - 1))
                                    keys = self._epoch_keys()
                                    self._obs_sync(obs, xb, yb, wb, keys)
                                    now = time.perf_counter()
                                    stage_s += now - t_stage
                                    if obs.enabled:
                                        phase_marks.append(
                                            ("stage", "phase", t_stage,
                                             now))
                                    state, losses = train_epoch(
                                        state, y, cnorm, keys,
                                        xb, yb, wb, z, rho, active)
                                    self._host_dispatches += 1
                                loss_acc = (losses if loss_acc is None
                                            else loss_acc + losses)
                                if cfg.be_verbose:
                                    # per-client epoch losses (the
                                    # reference's be_verbose minibatch
                                    # prints, federated_multi.py:199-200)
                                    # — the only path that syncs the host
                                    # inside the epoch loop
                                    log(f"verbose: block={ci} "
                                        f"nadmm={nadmm} "
                                        f"epoch={nepoch} client_loss="
                                        + np.array2string(fetch(losses),
                                                          precision=4))
                            # obs phase segments: with obs recording, each
                            # boundary drains the dispatch queue
                            # (_obs_sync) so stage/train/comm measure
                            # execution; with obs off the syncs vanish and
                            # the segments are wall-clock between the
                            # round's single host sync — see README
                            # "Observability" and PARITY.md
                            self._obs_sync(obs, state, loss_acc)
                            t_train_end = time.perf_counter()
                            train_s = t_train_end - t_train - stage_s
                            if obs.enabled:
                                # the train span covers the epoch chain
                                # (stage spans nest inside it)
                                phase_marks.append(
                                    ("train", "phase", t_train, t_train_end))
                            t_comm = time.perf_counter()
                            if algo.communicates and n_comm > 0:
                                mode = self._comm_mode(nadmm)
                                args = (state, z, y, rho, x0, yhat0,
                                        comm_active, corrupt,
                                        self._round_gbound())
                                if scratch is not None:
                                    args = args + (scratch,)
                                out = comm_fns[mode](*args)
                                if self._overlap:
                                    # the dispatch above is async: stage
                                    # round N+1's first epoch + keys on
                                    # the host NOW, before the blocking
                                    # diag/verdict fetches below drain it
                                    t_ov = time.perf_counter()
                                    overlap_s = self._prestage_round()
                                    if obs.enabled and overlap_s > 0:
                                        phase_marks.append(
                                            ("overlap", "phase", t_ov,
                                             t_ov + overlap_s))
                                if scratch is not None:
                                    scratch = out[-1]
                                    out = out[:-1]
                                if cfg.update_guard:
                                    okf = out[-1]
                                    out = out[:-1]
                                if self._client_probe:
                                    cl_nrm, cl_dist = out[-2], out[-1]
                                    out = out[:-2]
                                state, z, y, rho, x0, yhat0, diag = out
                                if (self._overlap_round
                                        and not obs.enabled
                                        and nadmm + 1 < cfg.Nadmm):
                                    # dispatch the NEXT round's first
                                    # epoch before the blocking diag
                                    # fetch below drains the queue —
                                    # the collective is still executing.
                                    # Same-block rounds only: block
                                    # boundaries rebuild fns/state and
                                    # may swap compressors (control)
                                    overlap_dispatch_s += \
                                        self._predispatch_round(
                                            (nloop, ci, nadmm + 1),
                                            train_epoch_ahead,
                                            state, z, y, rho, cnorm)
                                diag = {k: float(v)
                                        for k, v in diag.items()}
                                if cfg.update_guard:
                                    self._apply_guard_verdicts(
                                        diag, okf, comm_host)
                            elif algo.communicates:
                                # every client dropped/quarantined out of
                                # the exchange: degrade gracefully — no
                                # collective runs, z/y/rho carry over
                                # unchanged and the round is still
                                # recorded (and still serves quarantine
                                # time)
                                diag = {"n_active": 0.0}
                                if cfg.update_guard:
                                    diag.update(guard_trips=0.0, n_ok=0.0)
                                    self._quarantine = np.maximum(
                                        self._quarantine - 1, 0)
                            else:
                                diag = {}
                            self._obs_sync(obs, state, z, y)
                            comm_s = time.perf_counter() - t_comm
                            if obs.enabled and algo.communicates:
                                phase_marks.append(
                                    ("comm", "comm", t_comm,
                                     t_comm + comm_s))
                            if (self._overlap_round and obs.enabled
                                    and algo.communicates and n_comm > 0
                                    and nadmm + 1 < cfg.Nadmm):
                                # with obs recording, the pre-dispatch
                                # waits until AFTER the comm sync above
                                # so comm_seconds keeps measuring the
                                # collective alone (honest attribution);
                                # the ahead epoch then executes behind
                                # the loss fetch in the sync phase
                                t_ov = time.perf_counter()
                                dt = self._predispatch_round(
                                    (nloop, ci, nadmm + 1),
                                    train_epoch_ahead,
                                    state, z, y, rho, cnorm)
                                overlap_dispatch_s += dt
                                if dt > 0:
                                    phase_marks.append(
                                        ("overlap_dispatch", "phase",
                                         t_ov, t_ov + dt))
                        t_sync = time.perf_counter()
                        # single host sync per round: the loss fetch depends on
                        # every epoch in the chain and the diag/rho floats on
                        # the collective, so round_seconds (taken after both)
                        # covers the device compute honestly.  stage_seconds
                        # isolates host shuffle + H2D copy — with the epoch
                        # prefetch it should stay near zero unless the host
                        # pipeline is the bottleneck
                        loss_host = (np.asarray(fetch(loss_acc))
                                     if loss_acc is not None else None)
                        loss_sum = (float(np.sum(loss_host))
                                    if loss_host is not None else 0.0)
                        if cl_nrm is not None:
                            # the probes ride the same single round sync
                            cl_nrm = np.asarray(fetch(cl_nrm))
                            cl_dist = np.asarray(fetch(cl_dist))
                        # the workload's own round fields (a fetch of
                        # a few scalars for a trainer that has any)
                        workload_fields = self.round_fields(state, ci)
                        sync_s = time.perf_counter() - t_sync
                        if obs.enabled:
                            phase_marks.append(
                                ("sync", "phase", t_sync, t_sync + sync_s))
                        rho_host = float(rho)   # a fetch: inside the window
                        t_round_end = time.perf_counter()
                        rec = dict(nloop=nloop, block=ci, nadmm=nadmm, N=N,
                                   loss=loss_sum, rho=rho_host,
                                   round_seconds=t_round_end - t_round,
                                   stage_seconds=stage_s,
                                   train_seconds=train_s,
                                   comm_seconds=comm_s,
                                   sync_seconds=sync_s,
                                   **fcounts, **diag, **workload_fields)
                        # the host seconds no segment above counts (schema
                        # v15): everything from here to on_round's return
                        # is this round's tail and lands in the NEXT
                        # round's gap_seconds, with the switch where the
                        # block changes
                        if switch_s is not None:
                            rec["block_switch_seconds"] = switch_s
                            rec["block_switch_h2d_bytes"] = switch_h2d
                        if t_prev_end is not None:
                            rec["gap_seconds"] = t_round - t_prev_end
                        t_prev_end = t_round_end
                        if obs.enabled:
                            self._open_round_tail(len(history), t_round_end)
                        if self._overlap:
                            # host staging seconds hidden behind the comm
                            # dispatch (schema v7) — 0.0 on fused rounds
                            # and whenever the lookahead had nothing to do
                            rec["overlap_seconds"] = overlap_s
                        if self._overlap_round:
                            # host seconds spent enqueueing the NEXT
                            # round's first epoch behind this round's
                            # collective (schema v14) — 0.0 on the last
                            # round of a block and whenever the ahead
                            # cache was already spent
                            rec["overlap_dispatch_seconds"] = \
                                overlap_dispatch_s
                        # train-phase dispatches this round: Nepoch on the
                        # per-epoch loop, exactly 1 when fused — the
                        # tentpole's tracked metric
                        rec["host_dispatches"] = (self._host_dispatches
                                                  - dispatch0)
                        if self._sentinel is not None:
                            # cumulative traces-beyond-first: flat in steady
                            # state, growing when something retraces
                            rec["jit_retraces"] = self._sentinel.retraces
                        # drain the cost ledger BEFORE the eval below: an
                        # eval compile lands in the next round's drain and
                        # is attributed to the run, not this round
                        ledger_events = ()
                        if self._ledger is not None:
                            rcosts = self._ledger.drain()
                            ledger_events = rcosts.events
                            rec.update(round_cost_fields(
                                rcosts, t_round, rec["round_seconds"]))
                        if cfg.update_guard and algo.communicates:
                            # quarantine census at round START (who sat this
                            # round out), next to the guard_trips the round
                            # itself produced
                            rec["quarantined"] = q_start
                        if algo.communicates:
                            rec["bytes_on_wire"] = self.round_bytes_on_wire(
                                N, diag.get("n_active", cfg.K))
                            if self._fused_coll:
                                # predicted device-to-device bytes of the
                                # fused collective (schema v7; additive —
                                # absent whenever the flag is off)
                                rec["bytes_fused"] = self.round_bytes_fused(N)
                        if cfg.check_results:
                            rec["accuracy"] = self.evaluate(state)
                        history.append(rec)
                        # resume coordinates for the NEXT round (also the
                        # health watchdog's fallback-save target when it
                        # trips without mid-run checkpointing on)
                        if nadmm + 1 < cfg.Nadmm:
                            nxt = (nloop, ci, nadmm + 1)
                        elif ci + 1 < self.L:
                            nxt = (nloop, ci + 1, 0)
                        else:
                            nxt = (nloop + 1, 0, 0)
                        t_ckpt = None
                        if checkpoint_path is not None:
                            # checkpoint BEFORE the obs emit so the round
                            # record carries its own write cost; under
                            # --async-checkpoint this times only the D2H
                            # snapshot + queue handoff (the serialize +
                            # sha256 + rotation run on the writer thread)
                            # no device sync wanted here: the sync save
                            # materializes every leaf via np.asarray (its
                            # own sync) and the async save deliberately
                            # times only the host-side snapshot + enqueue
                            t_ckpt = time.perf_counter()  # graftlint: disable=JG104
                            self._save_midrun(checkpoint_path, state,
                                              (z, y, rho, x0, yhat0), nxt,
                                              history)
                            rec["ckpt_write_seconds"] = (
                                time.perf_counter() - t_ckpt)
                        extra_fields = {}
                        if cfg.async_rounds:
                            extra_fields["async_mode"] = True
                            # self.cfg, not the loop-local snapshot: a
                            # round-scope control intervention may have
                            # moved the cutoff, and the record must carry
                            # the value actually in force
                            extra_fields["max_staleness"] = \
                                self.cfg.max_staleness
                        if algo.communicates:
                            # dense comparator for the wire bytes: every
                            # participant's f32 block payload
                            extra_fields["bytes_dense"] = 4 * N * int(
                                diag.get("n_active", cfg.K))
                        self._emit_round_obs(
                            obs, rec, round_index=len(history) - 1,
                            t_round=t_round, images=obs_images,
                            extra_fields=extra_fields, N=N,
                            loss_host=loss_host, cl_nrm=cl_nrm,
                            cl_dist=cl_dist, phase_marks=phase_marks,
                            t_ckpt=t_ckpt, ledger_events=ledger_events,
                            checkpoint_path=checkpoint_path, state=state,
                            blockvars=(z, y, rho, x0, yhat0), nxt=nxt,
                            history=history, log=log)
                        blk = self.block_ids[ci]
                        msg = (f"block=[{blk[0]},{blk[1]}]({N},{float(rho):f}) "
                               f"round={nadmm}/{nloop} "
                               + " ".join(f"{k}={v:e}" for k, v in diag.items()))
                        if cfg.check_results:
                            msg += " acc=" + np.array2string(
                                rec["accuracy"], precision=2)
                        log(msg)
                        if on_round is not None:
                            on_round(state, rec)
                        self._close_round_tail()
        self._flush_outer_spans(obs)
        obs.close()
        # write barrier on run exit: every queued async checkpoint must be
        # durable before the caller sees the run as finished (a failed
        # background save surfaces HERE, not silently)
        self._flush_ckpt_writer()
        return state, history

    def run_independent(self, state: Optional[ClientState] = None,
                        log: Callable[[str], None] = print):
        """`no_consensus` path: whole net trainable, Nepoch epochs, Adam
        re-created every epoch (no_consensus_multi.py:128-166), no comm."""
        try:
            with self._profile_ctx():
                return self._run_independent_impl(state, log)
        except BaseException:
            self.close()
            if self.obs_recorder is not None:
                self.obs_recorder.close(status="aborted")
            raise

    def _run_independent_impl(self, state, log):
        cfg = self.cfg
        state = state or self.init_state()
        train_epoch, _, init_opt = self._build_fns(None)
        history: List[Dict[str, Any]] = []
        z = stage_global(np.zeros((1,), np.float32),
                         replicated_sharding(self.mesh))
        y = stage_global(np.zeros((cfg.K, 1), np.float32),
                         client_sharding(self.mesh))
        rho = stage_global(np.asarray(cfg.admm_rho0, np.float32),
                           replicated_sharding(self.mesh))
        obs = self._open_obs(resumed=False, rounds_prior=0)
        obs_images = self._obs_epoch_images()
        for epoch in range(cfg.Nepoch):
            t_epoch = time.perf_counter()
            state = ClientState(state.params, state.batch_stats,
                                init_opt(state.params))
            xb, yb, wb = self._stage_epoch(last=epoch == cfg.Nepoch - 1)
            state, losses = train_epoch(state, y, self.client_norm,
                                        self._epoch_keys(), xb, yb, wb, z,
                                        rho, self._ones_mask)
            self._host_dispatches += 1
            rec = dict(epoch=epoch, loss=float(np.sum(fetch(losses))),
                       epoch_seconds=time.perf_counter() - t_epoch,
                       host_dispatches=1)
            if self._sentinel is not None:
                rec["jit_retraces"] = self._sentinel.retraces
            # drain before the eval: eval compiles attribute to the run
            # via the next epoch's drain, not this epoch's window
            ledger_events = ()
            if self._ledger is not None:
                rcosts = self._ledger.drain()
                ledger_events = rcosts.events
                rec.update(round_cost_fields(
                    rcosts, t_epoch, rec["epoch_seconds"]))
            if cfg.check_results:
                rec["accuracy"] = self.evaluate(state)
                log(f"Epoch {epoch} acc="
                    + np.array2string(rec["accuracy"], precision=2))
            else:
                log(f"Epoch {epoch} loss={rec['loss']:e}")
            history.append(rec)
            if obs.enabled or obs.health is not None:
                rrec = obs.round(dict(rec, round_index=epoch,
                                      round_seconds=rec["epoch_seconds"],
                                      images=obs_images, t_start=t_epoch,
                                      **device_memory_stats()))
                if obs.enabled:
                    rspan = (rrec or {}).get("span_id")
                    t_hi = t_epoch + rec["epoch_seconds"] + 1e-9
                    for cev in ledger_events:
                        in_rnd = (rspan is not None
                                  and cev.t_start >= t_epoch - 1e-9
                                  and cev.t_end <= t_hi)
                        obs.compile_event(
                            cev.record(round_index=epoch),
                            parent_span=rspan if in_rnd else None)
                if (obs.health is not None
                        and obs.health.tripped is not None):
                    # no mid-run checkpointing on this path:
                    # checkpoint-abort degrades to a plain abort
                    from federated_pytorch_test_tpu.obs.health import (
                        RunHealthAbort,
                    )

                    raise RunHealthAbort(obs.health.tripped)
        obs.close()
        return state, history
