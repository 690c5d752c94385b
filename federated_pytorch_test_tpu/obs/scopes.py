"""The program's ``jax.named_scope`` names, declared once.

A scope writes its name into the ``op_name`` of every operation traced
inside it (``jit(epoch_shard)/.../sublayer_mixer/.../gdn/gdn_conv/mul``)
and adds no operation: the lowered program without locations is the same
text with or without it (``tests/test_scope_names.py``).  A device trace
carries that path with every executed instruction, so device time can be
read by the names below; ``benchmarks/lib/scope_tree.py`` does, matching
whole path segments.

:data:`SCOPES` is the one table: the engine (``train/engine.py``,
``train/lm_engine.py``, ``train/algorithms.py``), the five decoders
(``models/decoder.py``, ``qwen3_next.py``, ``glm4_moe_lite.py``,
``xing4_0.py``, ``zaya.py``, ``olmo_hybrid.py``) and the ops they call
open their scopes with :func:`scope`, which refuses a name that is not
declared here.  A row is
``(name, parents, programs, covers)``:

- ``parents``: the declared scopes the name is opened directly inside
  (``()``: at the top of its program).  A scope's *self time* is its
  time less its children's, and the ``covers`` text says what that is
  where a scope has children.
- ``programs``: where it occurs: ``epoch`` (every cell's epoch program:
  the engine's step), ``comm`` (the exchange program), ``decoder`` (all
  five decoders) or a model's registered name.

The names of :data:`TPU_ONLY` are opened only around a kernel that runs
on a TPU alone, and a program lowered on the CPU holds none of them.
The names of :data:`KERNEL_SCOPES` are what the benchmark's kernel
readers match: the fourteen that stood before this table by substring,
``cca_core`` as a whole segment.  No other name may hold one of them
unless it is nested inside that scope (``gdn_conv`` inside ``gdn``).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax

__all__ = ["KERNEL_SCOPES", "NAMES", "SCOPES", "Scope", "TPU_ONLY", "scope"]


class Scope(NamedTuple):
    name: str
    parents: Tuple[str, ...]
    programs: Tuple[str, ...]
    covers: str


_DECODER = ("decoder",)
#: the decoders that norm a sub-layer's input, and those with expert
#: layers: the four but the dense one, which norms outputs instead
_PRE_NORM = _MOE = ("qwen3_next", "glm4_moe_lite", "xing4_0", "zaya")
_GDN = ("qwen3_next", "olmo_hybrid")
_MIXERS = ("gdn", "gated_attn", "mla_attn", "cca_attn")
_ATTN = ("gated_attn", "mla_attn", "cca_attn", "mha_attn")
_SHARED_EXPERT = ("qwen3_next", "glm4_moe_lite", "xing4_0")
_FRAME = ("model_loss", "mtp")

SCOPES: Tuple[Scope, ...] = (
    # -- the engine's step (train/engine.py: adam_step, batch_loss) ------
    Scope("step_prepare", (), ("epoch",),
          "the step's key, prepare_batch, take_active"),
    Scope("client_grad", (), ("epoch",),
          "value_and_grad of one client's loss (self: where a trainer runs "
          "its clients one after another, the loop's slices and stacks of "
          "each client's arguments, and what the compiler hoists to it)"),
    Scope("model_loss", ("client_grad",), ("epoch",),
          "the trainer's model_loss, forward and backward: the whole model "
          "(self: a classifier's layers; a decoder's are named below)"),
    Scope("penalty", ("client_grad",), ("epoch",),
          "get_trainable_values, algo.penalty, l1_l2, forward and backward"),
    Scope("opt_update", (), ("epoch",),
          "tx.update (Adam over the active leaves), apply_updates, "
          "put_active"),
    # -- the exchange (train/engine.py: comm_shard) ----------------------
    Scope("exchange_flatten", (), ("comm",),
          "the clients' active leaves as rows [K, N]; corruption, "
          "compression, probes and guards where a run turns them on"),
    Scope("exchange_update", (), ("comm",),
          "algo.global_update: the z / dual update and the residual norms "
          "(self), the Barzilai-Borwein rho update"),
    Scope("exchange_reduce", ("exchange_update",), ("comm",),
          "Algorithm._agg: the mean over the clients (psum over the mesh)"),
    Scope("exchange_writeback", (), ("comm",),
          "put_trainable_values of z into every client's leaves"),
    # -- a decoder's frame -----------------------------------------------
    Scope("embed", ("model_loss",), _DECODER,
          "the embedding gather and its transposed scatter-add"),
    Scope("hc_streams", ("model_loss",), ("xing4_0",),
          "the embedding copied into the n streams; the streams' sum "
          "before the head"),
    Scope("sublayer_mixer", _FRAME, _DECODER,
          "a whole mixer sub-layer (self: the residual add, the map over "
          "sequences)"),
    Scope("sublayer_ffn", _FRAME, _DECODER,
          "a whole expert or dense sub-layer (self: the residual add, "
          "routing_counts)"),
    Scope("sublayer_norm", _MIXERS + ("sublayer_ffn",), _PRE_NORM,
          "the sub-layer's input RMS norm"),
    Scope("weight_cast", ("model_loss",), ("olmo_hybrid",),
          "the layers' matrices cast to the products' dtype once a step"),
    Scope("step_stats", ("model_loss",), _DECODER,
          "moe_aux, weighted_mean, the counters of LMTrainer.model_loss, "
          "the mean of the Gated DeltaNet layers' beta shares"),
    Scope("mtp", ("model_loss",), ("glm4_moe_lite",),
          "the multi-token-prediction layer, its head and loss term "
          "(self: the targets' roll, the term's weight)"),
    Scope("mtp_merge", ("mtp",), ("glm4_moe_lite",),
          "[N(embedding of the next id) ; N(hidden)] through eh_proj"),
    # -- mixers ----------------------------------------------------------
    Scope("gdn", ("sublayer_mixer",), _GDN,
          "a Gated DeltaNet mixer (self: nothing)"),
    Scope("gdn_in_proj", ("gdn",), _GDN,
          "the input projections and their slices (Qwen3-Next: "
          "in_proj_qkvz, in_proj_ba; Olmo-Hybrid: q, k, v, the output "
          "gate, a, b)"),
    Scope("gdn_conv", ("gdn",), _GDN,
          "the causal depthwise convolutions and their SiLU"),
    Scope("gdn_qk_prep", ("gdn",), _GDN,
          "the slices into q, k, v, unit norms, repeat, beta, g (and "
          "the share of beta above 1)"),
    Scope("gdn_scan", ("gdn",), _GDN,
          "the moves to heads-first; ops/gated_delta.py: the chunked delta "
          "rule, kernels and chunk-local part (self: all of it but the "
          "fused primal kernel)"),
    Scope("gdn_fused", ("gdn_scan",), _GDN,
          "the whole delta rule as one kernel where no gradient is asked "
          "(on a TPU only): the decays' cumulative sum, the kernel"),
    Scope("gdn_out_gate", ("gdn",), _GDN,
          "the move back, the output norm, the SiLU gate"),
    Scope("gdn_out_proj", ("gdn",), _GDN, "out_proj"),
    Scope("gated_attn", ("sublayer_mixer",), ("qwen3_next",),
          "a gated-attention mixer (self: the attention kernels, or the "
          "XLA core)"),
    Scope("mla_attn", ("sublayer_mixer",), ("glm4_moe_lite", "xing4_0"),
          "a latent-attention mixer (self: nothing)"),
    Scope("mla_core", ("mla_attn",), ("glm4_moe_lite", "xing4_0"),
          "causal_attention of the latent mixer (self: the attention "
          "kernels, or the XLA core)"),
    Scope("mha_attn", ("sublayer_mixer",), ("olmo_hybrid",),
          "a multi-head attention mixer, QK-norm over the whole width, no "
          "rotary (self: the attention kernels, or the XLA core)"),
    Scope("cca_attn", ("sublayer_mixer",), ("zaya",),
          "a compressed-convolutional-attention mixer (self: nothing)"),
    Scope("cca_mix", ("cca_attn",), ("zaya",),
          "what compression adds before the core: the value shift, the "
          "depthwise and the per-head causal convolution of q and k, "
          "the q/k means added back"),
    Scope("cca_core", ("cca_attn",), ("zaya",),
          "causal_attention inside the compressed space (self: the "
          "attention kernels, or the XLA core)"),
    Scope("attn_proj_in", _ATTN, _DECODER,
          "the projections into the core (q, k, v; MLA: q_a, q_b, kv_a, "
          "kv_b; CCA: q, k, v1, v2 down to the compressed widths) and "
          "their slices"),
    Scope("attn_norm_rope", _ATTN, _DECODER,
          "the head norms (MLA: the latents' norms; CCA: the unit-sphere "
          "norm and the key temperature; MHA: q and k normed over the "
          "whole width), rotary, the softmax scale"),
    Scope("attn_layout", ("gated_attn", "mla_core", "cca_core", "mha_attn"),
          _DECODER,
          "ops/flash_attention.py's wrapper: casts, padding, the "
          "regrouping transposes before and after the kernels"),
    Scope("attn_proj_out", _ATTN, _DECODER,
          "o_proj, with the sigmoid gate where there is one"),
    Scope("res_scale", ("sublayer_mixer", "sublayer_ffn"), ("zaya",),
          "the scaled residual merge (s_r h + b_r) + (s_o F + b_o)"),
    Scope("post_norm", ("sublayer_mixer", "sublayer_ffn"), ("olmo_hybrid",),
          "the reordered merge h + N(F): the sub-layer's output normed"),
    # -- feed-forward sub-layers -----------------------------------------
    Scope("dense_mlp", ("sublayer_ffn",),
          ("glm4_moe_lite", "xing4_0", "olmo_hybrid"),
          "the dense SwiGLU"),
    Scope("moe_route", ("sublayer_ffn",), _MOE,
          "router to pair buffer and back (self: filled_rows)"),
    Scope("route_mlp", ("moe_route",), ("zaya",),
          "the router that is an MLP with a state: the down-projection, "
          "the previous layer's state added, its norm, three products"),
    Scope("route_scores", ("moe_route",), _MOE,
          "the router product (where the router is one matrix), softmax "
          "or sigmoid, the bias, top_k"),
    Scope("route_sort", ("moe_route",), _MOE,
          "route_local: the sort, bincount, indices, weights"),
    Scope("pair_dispatch", ("moe_route",), _MOE,
          "ops/moe.py:dispatch's loop and its rule's"),
    Scope("pair_combine", ("moe_route",), _MOE,
          "ops/moe.py:combine's loop and its rule's"),
    Scope("pair_fill", ("pair_dispatch", "pair_combine"), _MOE,
          "the zero fills the four loops start from"),
    Scope("moe_experts", ("sublayer_ffn",), _MOE,
          "the held experts (self: SiLU(gate) x up)"),
    Scope("expert_cast", ("moe_experts",), _MOE,
          "operand()'s casts of rows, weights and cotangents"),
    Scope("expert_products", ("moe_experts",), _MOE,
          "the grouped products (on a TPU the compiler's ragged-dot "
          "kernels, which carry no path)"),
    Scope("expert_mask", ("moe_experts",), _MOE,
          "_ragged's zeroing of the rows past the last group"),
    Scope("moe_shared", ("sublayer_ffn",), _SHARED_EXPERT,
          "the shared expert and its add"),
    # -- hyper-connections (ops/hyper_connections.py) --------------------
    Scope("mhc", ("sublayer_mixer", "sublayer_ffn"), ("xing4_0",),
          "a sub-layer's hyper-connections (self: nothing)"),
    Scope("mhc_maps", ("mhc",), ("xing4_0",),
          "hyper_connections.pre: norm, projection, sigmoids, Sinkhorn "
          "(on a TPU one kernel each way, which holds H_pre X and ALL "
          "of the streams' cotangent too)"),
    Scope("mhc_mix", ("mhc",), ("xing4_0",),
          "contract and expand: the streams' mixing (on a TPU expand's "
          "kernel and its transpose's)"),
    # -- the head --------------------------------------------------------
    Scope("lm_head_loss", _FRAME, _DECODER,
          "final norm, head product, cross-entropy (self: nothing)"),
    Scope("head_norm", ("lm_head_loss",), _DECODER, "the final RMS norm"),
    Scope("head_product", ("lm_head_loss",), _DECODER,
          "the head's matrix product and its casts; ops/head_loss.py's "
          "forward rule's dx and dw products"),
    Scope("head_softmax", ("lm_head_loss",), _DECODER,
          "logsumexp, the picked logit, the mean over the sequence; "
          "(softmax - onehot) x token weight, and the backward rule's "
          "scale"),
)

NAMES = frozenset(s.name for s in SCOPES)

#: opened only on a TPU's kernel path (``ops/gated_delta.py``: the fused
#: primal kernel, which no width of the CPU's test configurations takes)
TPU_ONLY = ("gdn_fused",)

#: what the benchmark's kernel readers match: by substring
#: (``benchmarks/lib/scopes.py``, ``glm_work.py``, ``xing_work.py``), and
#: ``cca_core`` as a segment of the scope tree (``zaya_work.py``)
KERNEL_SCOPES = ("gdn", "gdn_scan", "gated_attn", "moe_route", "moe_experts",
                 "moe_shared", "lm_head_loss", "dense_mlp", "mla_attn",
                 "mla_core", "mtp", "mhc", "mhc_maps", "mhc_mix", "cca_core")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`."""
    if name not in NAMES:
        raise ValueError(f"scope {name!r} is not declared in obs/scopes.py")
    return jax.named_scope(name)
