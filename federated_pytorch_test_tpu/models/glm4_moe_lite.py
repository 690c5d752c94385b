"""GLM-4.7-Flash decoder (``model_type glm4_moe_lite``): multi-head latent
attention in every layer, a leading dense layer, then sparse expert
layers with sigmoid routing under a selection bias and one shared
expert, and one multi-token-prediction (MTP) layer.

Source: https://huggingface.co/zai-org/GLM-4.7-Flash config.json; latent
attention: arXiv:2405.04434 section 2.1; the router's bias and the MTP
layer: arXiv:2412.19437 sections 2.1.2 and 2.2.  The equations (``N`` is
the plain RMS norm ``x / sqrt(mean(x^2) + eps) * w``)::

    layer:  h = x + MLA(N1(x));  out = h + FFN(N2(h))
    FFN of layer l is a SwiGLU of intermediate_size where
    l < first_k_dense_replace, else the expert layer
    MTP:    h' = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)], one whole layer,
            the model's own head after a norm of the MTP layer's own

are written out in ``benchmarks/reference/glm4_moe_lite.py``, which this
file is compared with.  Here latent attention runs in its expanded form
(every head's keys and values are formed from the latent; the absorbed
form and a compressed cache are serving's), the matrix products run in
``dtype`` (bfloat16 on the chip) with float32 sums; parameters, norms,
the router and the loss are float32; each sub-layer is rematerialised in
the backward pass (``jax.checkpoint``), the mixers sequence by sequence;
both heads' losses take their inputs' gradient in the forward pass
(``ops/head_loss.py``).

The expert layer is told which experts it holds (``experts_held`` of
``n_routed_experts`` from ``ep_rank * experts_held``): it routes over all
of them and adds only its own experts' terms (``models/decoder.py:
held_experts``, ``ops/moe.py``).

Blocks, from the layer list: ``0`` the embedding, ``1 + 2l`` layer
``l``'s MLA with its input norm, ``2 + 2l`` its FFN block (the dense MLP,
or norm, held experts and shared expert), then the final norm and the
head, then ``mtp_mixer`` (the MTP layer's two input norms, ``W_eh``, its
MLA with its norm) and ``mtp_moe`` (its expert block and its head norm).
The MTP layer shares the embedding and the head's matrix with the main
model: their gradient has two sources when their block is active.

The router and its selection bias belong to no block and are never
trained here, for the reason ``models/qwen3_next.py`` gives: one
expert-parallel rank has its own share of the router's gradient only.
The bias is a buffer that no gradient reaches in a deployment either
(it is moved by the load, not by the loss).

With ``labels`` the loss of a sequence is the mean next-token
cross-entropy plus ``mtp_loss_weight`` times the mean cross-entropy of
the MTP layer's prediction of the second-next token over the ``T - 1``
positions that have one (``aux["mtp_loss"]``, per sequence, unweighted).
"""

from __future__ import annotations

from typing import Any, Dict, List

import flax.linen as nn
import jax
import jax.numpy as jnp

from federated_pytorch_test_tpu.models.base import BlockModule
from federated_pytorch_test_tpu.models.decoder import (
    HEAD_IMPL,
    _F32,
    _ONES,
    _Leaves,
    _mm,
    _normal,
    dense_mlp,
    dense_mlp_leaves,
    head_losses,
    latent_attention,
    mla_leaves,
    moe_aux,
    rms_norm,
    routing_counts,
    sigmoid_expert_layer as expert_layer,
    sigmoid_moe_leaves,
)
from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops.flash_attention import plan as attn_plan


class Glm4MoeLite(BlockModule):
    """``__call__(ids [B, T] int32) -> (logits [B, T, vocab_rows] f32,
    aux)``; with ``labels [B, T]`` ``(loss per sequence [B], aux)``.
    ``aux`` holds the routing counts summed over the expert layers, the
    MTP layer's among them (``moe_pairs_local``, ``moe_dropped``), the
    worst layer's ``moe_load_max_over_mean`` and, with ``labels``,
    ``mtp_loss [B]``."""

    hidden_size: int = 2048
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 10240
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    num_nextn_predict_layers: int = 1
    # the cut: layers kept, this chip's share of experts and vocabulary
    layers: int = 5
    experts_held: int = 8
    ep_rank: int = 0
    vocab_rows: int = 19360
    #: rows of the sorted pair buffer as a multiple of the mean count, as
    #: ``models/qwen3_next.py`` has it; 8 x the mean is every pair that
    #: can exist at these widths (four choices a token, all held here)
    pair_rows_factor: float = 8.0
    #: weight of the MTP term in the loss (not in the config: assumed)
    mtp_loss_weight: float = 0.1
    init_scale: float = 0.02
    #: scale of the seeded embedding (assumed).  At ``init_scale`` the
    #: first mixer's output, a causal average of values that all positions
    #: share, leads the residual stream: every token of a layer then picks
    #: the same experts and this chip's pairs swing by seed (0.9 to 2.7 T
    #: over four layers, positions' hidden states 0.87 alike in cosine).
    #: A trained model's stream is led by the token: at 1.0 the pairs are
    #: 1.7 to 2.4 T (PERF.md section 6, PR 32)
    embed_scale: float = 1.0
    #: scale of the seeded selection bias (assumed; 0 in a fresh model)
    bias_scale: float = 0.01
    attn_block: int = 512
    dtype: Any = jnp.bfloat16

    # -- the layer list and the blocks made from it ---------------------
    def layer_kinds(self) -> List[str]:
        return ["mlp" if i < self.first_k_dense_replace else "moe"
                for i in range(self.layers)]

    def block_names(self) -> List[str]:
        names = ["embed"]
        for i, kind in enumerate(self.layer_kinds()):
            names += [f"layer{i}_mixer", f"layer{i}_{kind}"]
        names.append("head")
        if self.num_nextn_predict_layers:
            names += ["mtp_mixer", "mtp_moe"]
        return names

    def block_kinds(self) -> List[str]:
        """``embed`` / ``mla`` / ``mlp`` / ``moe`` / ``head`` /
        ``mtp_mixer`` / ``mtp_moe`` per block."""
        kinds = ["embed"]
        for k in self.layer_kinds():
            kinds += ["mla", k]
        kinds.append("head")
        if self.num_nextn_predict_layers:
            kinds += ["mtp_mixer", "mtp_moe"]
        return kinds

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def attn_impl(self, tokens: int) -> str:
        """What runs the attention core of a latent-attention layer for a
        sequence of ``tokens`` here ("pallas" | "pallas_interpret" |
        "xla": ``ops/flash_attention.py:plan``)."""
        return attn_plan(tokens, self.num_attention_heads, 1,
                         self.qk_head_dim, self.dtype)["impl"]

    def impl_fields(self, tokens: int) -> Dict[str, str]:
        """The round record's fields that name this backend's
        implementations for sequences of ``tokens``."""
        return {"attn_impl": self.attn_impl(tokens), "head_impl": HEAD_IMPL}

    def _spec(self, name: str):
        H, s = self.hidden_size, _normal(self.init_scale)
        if name == "embed":
            return (("embedding", (self.vocab_rows, H),
                     _normal(self.embed_scale)),)
        if name == "head":
            return (("norm", (H,), _ONES),
                    ("kernel", (H, self.vocab_rows), s))
        if name == "mtp_mixer":
            return (("enorm", (H,), _ONES), ("hnorm", (H,), _ONES),
                    ("eh_proj", (2 * H, H), s)) + mla_leaves(self)
        if name == "mtp_moe":
            return sigmoid_moe_leaves(self) + (("head_norm", (H,), _ONES),)
        if name.endswith("_moe"):
            return sigmoid_moe_leaves(self)
        if name.endswith("_mlp"):
            return dense_mlp_leaves(self)
        return mla_leaves(self)

    def param_order(self) -> List[str]:
        return [f"{b}/{leaf}" for b in self.block_names()
                for leaf, _, _ in self._spec(b)]

    def train_order_block_ids(self) -> List[List[int]]:
        """Inclusive index ranges into ``param_order()``.  An expert
        block's range starts AFTER its router and the router's bias (the
        first two leaves of its spec), which therefore lie in no block:
        see the module's note on the router."""
        out, lo = [], 0
        for b in self.block_names():
            n = len(self._spec(b))
            out.append([lo + (2 if b.endswith("_moe") else 0), lo + n - 1])
            lo += n
        return out

    # -- forward ---------------------------------------------------------
    @nn.compact
    def __call__(self, ids, labels=None):
        """With ``labels [B, T]``: each sequence's loss ``[B]`` in place
        of the logits (sequence by sequence, so only one sequence's
        float32 logits are alive at a time)."""
        p = {b: _Leaves(self._spec(b), name=b)() for b in self.block_names()}
        return forward(self, p, ids, labels)


def decoder_layer(cfg: Glm4MoeLite, pm, pf, x, outer: str = ""):
    """``x [B, T, H]`` through one layer: MLA with ``pm``, then the dense
    MLP or the expert layer with ``pf`` (by its leaves); ``-> (out,
    routing counts or None)``."""
    eps = cfg.rms_norm_eps
    B, T, H = x.shape

    # each sub-layer is rematerialised in the backward pass, the mixer
    # sequence by sequence, as in models/qwen3_next.py
    @jax.checkpoint
    def mix(xt):
        with scope("mla_attn"):
            with scope("sublayer_norm"):
                xn = rms_norm(xt, pm["norm"], eps)
            return latent_attention(cfg, pm, xn, outer)

    @jax.checkpoint
    def ffn(h):
        # tokens are independent here: one batch of B * T
        with scope("sublayer_norm"):
            flat = rms_norm(h, pf["norm"], eps).reshape(B * T, H)
        if "router" in pf:
            y, r = expert_layer(cfg, pf, flat)
            return y, routing_counts(r)
        return dense_mlp(cfg, pf, flat), None

    with scope("sublayer_mixer"):
        h = x + jax.lax.map(mix, x)
    with scope("sublayer_ffn"):
        y, counts = ffn(h)
        return h + y.reshape(B, T, H), counts


def head_logits(cfg: Glm4MoeLite, p, x, norm):
    """``x [..., H]`` through the norm ``norm`` and the model's head."""
    with scope("lm_head_loss"):
        with scope("head_norm"):
            xn = rms_norm(x, norm, cfg.rms_norm_eps)
        with scope("head_product"):
            return _mm(cfg, xn, p["head"]["kernel"])


def mtp_layer(cfg: Glm4MoeLite, p, x, nxt):
    """The MTP layer up to its head norm: ``x [B, T, H]`` the last
    layer's output, ``nxt [B, T]`` each position's next id ``-> (out [B,
    T, H], routing counts)``."""
    pm, eps = p["mtp_mixer"], cfg.rms_norm_eps

    @jax.checkpoint
    def merge(h, t):
        with scope("mtp_merge"):
            both = jnp.concatenate([
                rms_norm(p["embed"]["embedding"][t], pm["enorm"], eps),
                rms_norm(h, pm["hnorm"], eps)], -1)
            return _mm(cfg, both.reshape(-1, both.shape[-1]),
                       pm["eh_proj"]).reshape(h.shape)

    return decoder_layer(cfg, pm, p["mtp_moe"], merge(x, nxt), "mtp/")


def forward(cfg: Glm4MoeLite, p, ids, labels=None):
    """``ids [B, T]`` -> ``(logits [B, T, V], aux)``, or with ``labels``
    ``(loss per sequence [B], aux)``."""
    routed = []          # each expert layer's routing_counts
    with scope("embed"):
        x = p["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg.layer_kinds()):
        x, counts = decoder_layer(cfg, p[f"layer{i}_mixer"],
                                  p[f"layer{i}_{kind}"], x)
        routed += [counts] if counts is not None else []

    def aux(**more):
        with scope("step_stats"):
            return {**moe_aux(routed), **more}

    if labels is None:
        return head_logits(cfg, p, x, p["head"]["norm"]), aux()
    norm = lambda w: lambda a: rms_norm(a, w, cfg.rms_norm_eps)
    loss = head_losses(cfg, norm(p["head"]["norm"]), x, p["head"]["kernel"],
                       labels)
    mtp = jnp.zeros_like(loss)
    if cfg.num_nextn_predict_layers:
        with scope("mtp"):
            T = ids.shape[1]
            # position i holds t_{i+1} = labels[i] and predicts
            # t_{i+2} = labels[i + 1]; the last position has no target
            z, counts = mtp_layer(cfg, p, x, labels)
            routed.append(counts)
            target = jnp.roll(labels, -1, axis=1)
            weight = (jnp.arange(T) < T - 1).astype(_F32) / max(T - 1, 1)
            mtp = head_losses(cfg, norm(p["mtp_moe"]["head_norm"]), z,
                              p["head"]["kernel"], target, weight)
            loss = loss + cfg.mtp_loss_weight * mtp
    return loss, aux(mtp_loss=mtp)
