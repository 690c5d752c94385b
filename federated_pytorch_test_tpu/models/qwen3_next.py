"""Qwen3-Next decoder (``model_type qwen3_next``): Gated DeltaNet and
gated softmax attention, three to one, each followed by a sparse expert
layer with one gated shared expert.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json;
Gated DeltaNet: arXiv:2412.06464.  The equations (``N`` is the
zero-centred RMS norm ``x / sqrt(mean(x^2) + eps) * (1 + w)``)::

    layer:  h = x + Mixer(N1(x));  out = h + MoE(N2(h))
    layer i is attention where (i + 1) % full_attention_interval == 0

are written out in ``benchmarks/reference/qwen3_next.py``, which this
file is compared with.  Here the matrix products run in ``dtype``
(bfloat16 on the chip) with float32 sums; parameters, norms, the router,
the gates, the state decay and the loss are float32; each sub-layer is
rematerialised in the backward pass (``jax.checkpoint``), the mixers
sequence by sequence; the head's loss takes its inputs' gradient in the
forward pass (``ops/head_loss.py``).

What this decoder shares with ``models/glm4_moe_lite.py`` (a block's
leaves, the rotary tables, the loss helpers, the held experts' sort,
grouped products and scatter) lies in ``models/decoder.py``.

The expert layer is told which experts it holds (``experts_held`` of
``n_experts`` from ``ep_rank * experts_held``): it routes over all of
them and adds only its own experts' terms (``ops/moe.py``).

Blocks, from the layer list: ``0`` the embedding, ``1 + 2l`` layer
``l``'s mixer with its input norm, ``2 + 2l`` its expert block (norm,
held experts, shared expert and its gate), the last the final norm and
the head.

The router belongs to no block and is never trained here.  It is
replicated over the ranks that share the experts, and its gradient is
the sum of every rank's terms; one rank alone has a sixteenth of them,
all of which say "send more tokens to MY experts" (only held experts
lower this chip's loss).  Trained on that, the router moved every
token's top-k onto the 32 held experts within some thirty Adam steps on
the v5e (PR 27: the pair buffer of twice the mean overflowed by 77,492
pairs in one window).  A deployment all-reduces the router's gradient;
a single rank that cannot, leaves the router as it is.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import flax.linen as nn
import jax
import jax.numpy as jnp

from federated_pytorch_test_tpu.models.base import BlockModule
from federated_pytorch_test_tpu.models.decoder import (  # noqa: F401
    HEAD_IMPL,
    _F32,
    _ONES,
    _ZEROS,
    _Leaves,
    _mm,
    _normal,
    apply_rope,
    head_losses,
    held_experts,
    moe_aux,
    next_token_loss,
    rope_tables,
    routing_counts,
    weighted_mean,
)
from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops import moe as moelib
from federated_pytorch_test_tpu.ops.flash_attention import (
    causal_attention,
    plan as attn_plan,
)
from federated_pytorch_test_tpu.ops.gated_delta import (
    gated_delta_chunked,
    plan as gdn_scan_plan,
)


def _a_log(key, shape, dtype=_F32):
    # the released code draws A from U(0, 16); U(1, 16) keeps log A finite
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _conv_taps(key, shape, dtype=_F32):
    # torch's default for a depthwise Conv1d of kernel 4: U(-1/2, 1/2)
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def rms_norm(x, w, eps):
    """Zero-centred weight: ``(1 + w)``."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


class Qwen3Next(BlockModule):
    """``__call__(ids [B, T] int32) -> (logits [B, T, vocab_rows] f32,
    aux)`` where ``aux`` holds the routing counts summed over the layers
    (``moe_pairs_local``, ``moe_dropped``) and the worst layer's
    ``moe_load_max_over_mean``."""

    hidden_size: int = 2048
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    # the cut: layers kept, this chip's share of experts and vocabulary
    layers: int = 4
    experts_held: int = 32
    ep_rank: int = 0
    vocab_rows: int = 18992
    #: rows of the sorted pair buffer per token batch, as a multiple of
    #: the mean ``T * top_k * experts_held / num_experts``; pairs beyond
    #: it are counted in ``moe_dropped``
    pair_rows_factor: float = 3.0
    init_scale: float = 0.02
    chunk: int = 64
    attn_block: int = 512
    dtype: Any = jnp.bfloat16

    # -- the layer list and the blocks made from it ---------------------
    def layer_kinds(self) -> List[str]:
        return ["attn" if (i + 1) % self.full_attention_interval == 0
                else "gdn" for i in range(self.layers)]

    def block_names(self) -> List[str]:
        names = ["embed"]
        for i in range(self.layers):
            names += [f"layer{i}_mixer", f"layer{i}_moe"]
        return names + ["head"]

    def block_kinds(self) -> List[str]:
        """``embed`` / ``gdn`` / ``attn`` / ``moe`` / ``head`` per block."""
        kinds = ["embed"]
        for k in self.layer_kinds():
            kinds += [k, "moe"]
        return kinds + ["head"]

    def gdn_scan_impl(self, tokens: int) -> str:
        """What runs the delta rule's chunk recurrence for a sequence of
        ``tokens`` in a Gated DeltaNet layer here ("pallas" |
        "pallas_interpret" | "xla": ``ops/gated_delta.py:plan``)."""
        return gdn_scan_plan(
            self.linear_num_value_heads, -(-tokens // self.chunk),
            self.chunk, self.linear_key_head_dim,
            self.linear_value_head_dim, self.dtype)["impl"]

    def attn_impl(self, tokens: int) -> str:
        """What runs the attention core (scores, mask, softmax, ``a v``)
        for a sequence of ``tokens`` in a gated-attention layer here
        ("pallas" | "pallas_interpret" | "xla":
        ``ops/flash_attention.py:plan``)."""
        return attn_plan(
            tokens, self.num_key_value_heads,
            self.num_attention_heads // self.num_key_value_heads,
            self.head_dim, self.dtype)["impl"]

    def impl_fields(self, tokens: int) -> Dict[str, str]:
        """The round record's fields that name this backend's
        implementations for sequences of ``tokens``."""
        return {"gdn_scan_impl": self.gdn_scan_impl(tokens),
                "attn_impl": self.attn_impl(tokens), "head_impl": HEAD_IMPL}

    def _spec(self, name: str):
        H, s = self.hidden_size, _normal(self.init_scale)
        if name == "embed":
            return (("embedding", (self.vocab_rows, H), s),)
        if name == "head":
            return (("norm", (H,), _ZEROS),
                    ("kernel", (H, self.vocab_rows), s))
        if name.endswith("_moe"):
            E, F = self.experts_held, self.moe_intermediate_size
            Fs = self.shared_expert_intermediate_size
            return (("router", (H, self.num_experts), s),
                    ("norm", (H,), _ZEROS),
                    ("experts_gate", (E, H, F), s),
                    ("experts_up", (E, H, F), s),
                    ("experts_down", (E, F, H), s),
                    ("shared_gate_proj", (H, Fs), s),
                    ("shared_up", (H, Fs), s),
                    ("shared_down", (Fs, H), s),
                    ("shared_gate", (H,), s))
        kind = self.layer_kinds()[int(name[5:].split("_")[0])]
        if kind == "attn":
            nq, nkv, d = (self.num_attention_heads, self.num_key_value_heads,
                          self.head_dim)
            return (("norm", (H,), _ZEROS),
                    ("q_proj", (H, nq * 2 * d), s),
                    ("k_proj", (H, nkv * d), s),
                    ("v_proj", (H, nkv * d), s),
                    ("q_norm", (d,), _ZEROS),
                    ("k_norm", (d,), _ZEROS),
                    ("o_proj", (nq * d, H), s))
        nk, nv = self.linear_num_key_heads, self.linear_num_value_heads
        dk, dv = self.linear_key_head_dim, self.linear_value_head_dim
        conv = 2 * nk * dk + nv * dv
        return (("norm", (H,), _ZEROS),
                ("in_proj_qkvz", (H, conv + nv * dv), s),
                ("in_proj_ba", (H, 2 * nv), s),
                ("conv", (self.linear_conv_kernel_dim, conv), _conv_taps),
                ("A_log", (nv,), _a_log),
                ("dt_bias", (nv,), _ONES),
                ("out_norm", (dv,), _ONES),
                ("out_proj", (nv * dv, H), s))

    def param_order(self) -> List[str]:
        return [f"{b}/{leaf}" for b in self.block_names()
                for leaf, _, _ in self._spec(b)]

    def train_order_block_ids(self) -> List[List[int]]:
        """Inclusive index ranges into ``param_order()``.  An expert
        block's range starts AFTER its router (the first leaf of its
        spec), which therefore lies in no block: see the module's note
        on the router."""
        out, lo = [], 0
        for b in self.block_names():
            n = len(self._spec(b))
            out.append([lo + (1 if b.endswith("_moe") else 0), lo + n - 1])
            lo += n
        return out

    # -- forward ---------------------------------------------------------
    @nn.compact
    def __call__(self, ids, labels=None):
        """With ``labels [B, T]``: each sequence's mean next-token loss
        ``[B]`` in place of the logits (sequence by sequence, so only one
        sequence's float32 logits are alive at a time)."""
        p = {b: _Leaves(self._spec(b), name=b)() for b in self.block_names()}
        return forward(self, p, ids, labels)


def gated_attention(cfg: Qwen3Next, p, x):
    """``x [T, H]`` (already normed) -> ``[T, H]``."""
    T = x.shape[0]
    nq, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with scope("attn_proj_in"):
        qg = _mm(cfg, x, p["q_proj"]).reshape(T, nq, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = _mm(cfg, x, p["k_proj"]).reshape(T, nkv, d)
        v = _mm(cfg, x, p["v_proj"]).reshape(T, nkv, d)
    with scope("attn_norm_rope"):
        q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        cos, sin = rope_tables(T, int(d * cfg.partial_rotary_factor),
                               cfg.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        q = q.reshape(T, nkv, nq // nkv, d) * (1.0 / math.sqrt(d))
    o = causal_attention(q, k, v, dtype=cfg.dtype, block=cfg.attn_block)
    with scope("attn_proj_out"):
        o = o.reshape(T, nq, d) * jax.nn.sigmoid(gate)
        return _mm(cfg, o.reshape(T, nq * d), p["o_proj"])


def gated_delta_net(cfg: Qwen3Next, p, x):
    """``x [T, H]`` (already normed) -> ``[T, H]``."""
    T = x.shape[0]
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    conv_dim = 2 * nk * dk + nv * dv
    with scope("gdn_in_proj"):
        qkvz = _mm(cfg, x, p["in_proj_qkvz"])
        qkv, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:].reshape(T, nv, dv)
        # b, a feed a sigmoid and the state decay: float32 products
        ba = jnp.dot(x, p["in_proj_ba"], precision=jax.lax.Precision.HIGHEST)
        b, a = ba[:, :nv], ba[:, nv:]
    with scope("gdn_conv"):
        # causal depthwise convolution, kernel taps oldest first, then SiLU
        kw = cfg.linear_conv_kernel_dim
        padded = jnp.pad(qkv, ((kw - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(padded[j:j + T] * p["conv"][j]
                              for j in range(kw)))
    with scope("gdn_qk_prep"):
        q = qkv[:, :nk * dk].reshape(T, nk, dk)
        k = qkv[:, nk * dk:2 * nk * dk].reshape(T, nk, dk)
        v = qkv[:, 2 * nk * dk:].reshape(T, nv, dv)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + 1e-6)
        q, k = unit(q) * (1.0 / math.sqrt(dk)), unit(k)
        # each key head serves nv / nk value heads
        q, k = (jnp.repeat(t, nv // nk, axis=1) for t in (q, k))
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    # the moves to heads-first have always been the scan's own
    heads = lambda t: jnp.moveaxis(t, 1, 0)
    with scope("gdn_scan"):
        o = gated_delta_chunked(heads(q), heads(k), heads(v), heads(g),
                                heads(beta), chunk=cfg.chunk,
                                dtype=cfg.dtype)
    with scope("gdn_out_gate"):
        o = jnp.moveaxis(o, 0, 1)                          # [T, nv, dv]
        o = p["out_norm"] * o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
        o = o * jax.nn.silu(z)
    with scope("gdn_out_proj"):
        return _mm(cfg, o.reshape(T, nv * dv), p["out_proj"])


def expert_layer(cfg: Qwen3Next, p, x):
    """``x [T, H]`` (already normed) -> ``([T, H], routing)``."""
    with scope("moe_route"), scope("route_scores"):
        logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
        w, e = moelib.router_weights(logits, cfg.num_experts_per_tok,
                                     cfg.norm_topk_prob)
    y, r = held_experts(cfg, p, x, w, e, cfg.num_experts)
    with scope("moe_shared"):
        hs = jax.nn.silu(_mm(cfg, x, p["shared_gate_proj"])) \
            * _mm(cfg, x, p["shared_up"])
        gate = jax.nn.sigmoid(jnp.dot(x, p["shared_gate"],
                                      precision=jax.lax.Precision.HIGHEST))
        y = y + gate[:, None] * _mm(cfg, hs, p["shared_down"])
    return y, r


def forward(cfg: Qwen3Next, p, ids, labels=None):
    """``ids [B, T]`` -> ``(logits [B, T, V], aux)``, or with ``labels``
    ``(loss per sequence [B], aux)``."""
    eps = cfg.rms_norm_eps

    def layer(kind, pm, pe, x):
        B, T, H = x.shape
        mixer = gated_attention if kind == "attn" else gated_delta_net
        # each sub-layer is rematerialised in the backward pass, the
        # mixer sequence by sequence: a Gated DeltaNet layer's
        # intermediates for ONE sequence of 4,096 tokens are over a
        # gigabyte, and only one sequence's are alive at a time
        @jax.checkpoint
        def mix(xt):
            with scope("gated_attn" if kind == "attn" else "gdn"):
                with scope("sublayer_norm"):
                    xn = rms_norm(xt, pm["norm"], eps)
                return mixer(cfg, pm, xn)

        @jax.checkpoint
        def experts(h):
            # tokens are independent here: one batch of B * T
            with scope("sublayer_norm"):
                hn = rms_norm(h, pe["norm"], eps).reshape(B * T, H)
            return expert_layer(cfg, pe, hn)

        with scope("sublayer_mixer"):
            h = x + jax.lax.map(mix, x)
        with scope("sublayer_ffn"):
            y, r = experts(h)
            return h + y.reshape(B, T, H), routing_counts(r)

    with scope("embed"):
        x = p["embed"]["embedding"][ids]
    routed = []
    for i, kind in enumerate(cfg.layer_kinds()):
        x, counts = layer(kind, p[f"layer{i}_mixer"], p[f"layer{i}_moe"], x)
        routed.append(counts)
    with scope("step_stats"):
        aux = moe_aux(routed)

    norm = lambda xt: rms_norm(xt, p["head"]["norm"], eps)
    if labels is None:
        with scope("lm_head_loss"):
            with scope("head_norm"):
                xn = norm(x)
            with scope("head_product"):
                return _mm(cfg, xn, p["head"]["kernel"]), aux
    return head_losses(cfg, norm, x, p["head"]["kernel"], labels), aux
