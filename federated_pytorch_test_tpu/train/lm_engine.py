"""Language-model trainer: a small subclass of the blockwise engine.

``LMTrainer`` overrides the workload hooks only (as ``VAETrainer``
does), so staging, the epoch scan, the vmap over clients, the exchange,
the block switch and the recorder are the engine's own.  A batch is
``[B, T]`` int32 ids with their next ids as labels; a sample is one
sequence.  The model's routing counts ride in the engine's per-client
non-parameter state (``ClientState.batch_stats``), summed over the steps,
and ``round_fields`` turns them into the round record's ``tokens``,
``block_kind``, ``moe_pairs_local``, ``moe_load_max_over_mean``,
``moe_dropped`` and ``moe_fill_share`` (the pairs that found a row over
the rows of the steps' pair buffers: how much of what ``ops/moe.py``
sizes for the worst traffic its loops visit; 0 for a model without
experts), ``mtp_loss`` (the summed multi-token-prediction term
of a model that has such a layer, else 0), ``mhc_marginal_err`` (the
worst marginal error of a model's hyper-connection mixing matrices,
averaged over the round's steps, else 0), ``moe_top1_weight_mean`` (the
mean routing weight of the local pairs of a model that reports their
sum, ``aux["moe_weight_sum"]``: at one expert a token, the chosen
expert's probability; else 0) and ``router_state_rms`` (the RMS of the
state a model's routers hand from layer to layer, after the last layer,
averaged over the round's steps, else 0) and ``gdn_neg_beta_share``
(the share of (token, head) pairs of a model's Gated DeltaNet layers
whose ``beta`` is above 1, averaged over the round's steps, else 0), and
adds the model's own ``impl_fields``: which implementations its shapes
take on this backend (``attn_impl``, ``gdn_scan_impl``).  The trainer names no model: a
decoder is a ``BlockModule`` whose ``__call__(ids, labels)`` returns
``(loss per sequence, aux)`` and that has ``block_kinds()`` and
``impl_fields(tokens)``; its ``aux`` may leave out any counter (a dense
model reports no routing), which then counts 0.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import sequential_vmap

from federated_pytorch_test_tpu.models.decoder import weighted_mean
from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.parallel.mesh import (
    client_sharding,
    fetch,
    stage_tree_global,
)
from federated_pytorch_test_tpu.train.engine import (
    BlockwiseFederatedTrainer,
    ClientState,
)

#: the counters kept per client, all sums over local steps
_COUNTERS = ("steps", "moe_pairs_local", "moe_dropped", "moe_rows",
             "moe_load_sum", "mtp_loss_sum", "mhc_err_sum",
             "moe_weight_sum", "router_rms_sum", "neg_beta_sum")
_FLOAT_COUNTERS = ("moe_load_sum", "mtp_loss_sum", "mhc_err_sum",
                   "moe_weight_sum", "router_rms_sum", "neg_beta_sum")


class LMTrainer(BlockwiseFederatedTrainer):
    """Federated next-token training of a ``BlockModule`` whose
    ``__call__(ids)`` returns ``(logits, aux)`` (``models/qwen3_next.py``,
    ``models/glm4_moe_lite.py``, ``models/xing4_0.py``,
    ``models/zaya.py``, ``models/olmo_hybrid.py``).
    No L1/L2 term on any block; evaluation is the mean test loss."""

    obs_engine = "lm"

    def __init__(self, model, cfg, data, algorithm, **kw):
        self._counters_seen: Dict[str, np.ndarray] = {}
        super().__init__(model, cfg, data, algorithm, **kw)
        # the per-client counters ARE the engine's batch_stats here (the
        # model has none of its own); has_bn stays False so nothing takes
        # them for batch-norm statistics
        zeros = {k: np.zeros((cfg.K,), np.float32 if k in _FLOAT_COUNTERS
                             else np.int32) for k in _COUNTERS}
        self.batch_stats0 = stage_tree_global(zeros,
                                              client_sharding(self.mesh))

    def wrap_client_grad(self, grad_fn):
        # a step of one client is 8,192 tokens through matrices thousands
        # wide: nothing is gained by batching K of them, and K clients'
        # activations side by side do not fit the chip
        return sequential_vmap(grad_fn)

    def init_state(self) -> ClientState:
        """The staged init itself, handed over: the weights are held K
        times already, and a second staged copy of them (the base class
        keeps ``params0`` and copies it) does not fit.  ``params0`` keeps
        the shapes, which is all the masks and ``block_size`` read."""
        if any(isinstance(a, jax.ShapeDtypeStruct)
               for a in jax.tree.leaves(self.params0)):
            raise RuntimeError(
                "LMTrainer.init_state() hands its staged weights over and "
                "can be called once; build a new trainer for a new run")
        self._counters_seen = {}
        state = ClientState(self.params0,
                            jax.tree.map(jnp.copy, self.batch_stats0), None)
        self.params0 = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params0)
        return state

    def sample_init_args(self):
        # no parameter's shape depends on the sequence length, and flax
        # runs the init forward op by op: eight ids, not a whole sequence
        return (jnp.zeros((1, 8), jnp.int32),)

    def prepare_batch(self, xb_raw, norm):
        return xb_raw

    def reg_for_block(self, ci):
        return (0.0, 0.0)

    def model_loss(self, p, bs, xb, yb, wb, rng):
        per_seq, aux = self.model.apply({"params": p}, xb, yb)
        with scope("step_stats"):
            new = {"steps": bs["steps"] + 1,
                   "moe_pairs_local": bs["moe_pairs_local"]
                   + aux.get("moe_pairs_local", 0),
                   "moe_dropped": bs["moe_dropped"]
                   + aux.get("moe_dropped", 0),
                   "moe_rows": bs["moe_rows"] + aux.get("moe_rows", 0),
                   "moe_load_sum": bs["moe_load_sum"]
                   + aux.get("moe_load_max_over_mean", 0.0),
                   "mtp_loss_sum": bs["mtp_loss_sum"] + (
                       weighted_mean(aux["mtp_loss"], wb)
                       if "mtp_loss" in aux else 0.0),
                   "mhc_err_sum": bs["mhc_err_sum"]
                   + aux.get("mhc_marginal_err", 0.0),
                   "moe_weight_sum": bs["moe_weight_sum"]
                   + aux.get("moe_weight_sum", 0.0),
                   "router_rms_sum": bs["router_rms_sum"]
                   + aux.get("router_state_rms", 0.0),
                   "neg_beta_sum": bs["neg_beta_sum"]
                   + aux.get("gdn_neg_beta_share", 0.0)}
            return weighted_mean(per_seq, wb), new

    def eval_batch_metric(self, p, bs, xb, yb, wb):
        per_seq, _ = self.model.apply({"params": p}, xb, yb)
        return weighted_mean(per_seq, wb) * jnp.sum(wb)

    def eval_finalize(self, totals: np.ndarray, n_samples: int) -> np.ndarray:
        return totals / n_samples          # mean test loss per sequence

    def _restore_midrun(self, path):
        out = super()._restore_midrun(path)
        self._counters_seen = {
            k: np.asarray(fetch(out[0].batch_stats[k])) for k in _COUNTERS}
        return out

    def round_fields(self, state: ClientState, ci: int) -> Dict[str, Any]:
        """This round's share of the cumulative counters (clients
        summed; the load ratio averaged over the round's steps)."""
        now = {k: np.asarray(fetch(state.batch_stats[k])) for k in _COUNTERS}
        d = {k: float(np.sum(now[k] - self._counters_seen.get(k, 0)))
             for k in _COUNTERS}
        self._counters_seen = now
        steps = max(d["steps"], 1.0)
        return {"tokens": int(d["steps"]) * self.data.batch
                * self.data.tokens_per_sample,
                "block_kind": self.model.block_kinds()[self._block_index(ci)],
                "moe_pairs_local": int(d["moe_pairs_local"]),
                "moe_dropped": int(d["moe_dropped"]),
                "moe_load_max_over_mean": d["moe_load_sum"] / steps,
                "moe_fill_share": (d["moe_pairs_local"] - d["moe_dropped"])
                / max(d["moe_rows"], 1.0),
                "mtp_loss": d["mtp_loss_sum"],
                "mhc_marginal_err": d["mhc_err_sum"] / steps,
                "moe_top1_weight_mean": d["moe_weight_sum"] / max(
                    d["moe_pairs_local"] - d["moe_dropped"], 1.0),
                "router_state_rms": d["router_rms_sum"] / steps,
                "gdn_neg_beta_share": d["neg_beta_sum"] / steps,
                **self.model.impl_fields(self.data.tokens_per_sample)}

    def _block_index(self, ci: int) -> int:
        """Index into the model's own block list of sweep unit ``ci``
        (callers may re-point ``block_ids`` at a subset)."""
        return self.model.train_order_block_ids().index(
            list(self.block_ids[ci]))
