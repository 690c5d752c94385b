"""Olmo-Hybrid-7B decoder (``model_type olmo_hybrid``): Gated DeltaNet
with negative eigenvalues in three of four layers, full multi-head
attention without rotary in the fourth, a dense SwiGLU after each mixer,
and every sub-layer's output normed before it joins the stream.

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B config.json; Gated
DeltaNet: arXiv:2412.06464, as flash-linear-attention's ``GatedDeltaNet``
builds it; eigenvalues in (-1, 1): arXiv:2411.12537; the reordered norm
and the QK-norm: OLMo 2, arXiv:2501.00656.  The equations (``N`` the
plain RMS norm ``x / sqrt(mean(x^2) + eps) * w``)::

    sub-layer:  h = x + N_post(F(x))          (F's input is not normed)
    GDN:        q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)),
                SiLU(conv(x W_v)) (each its own causal depthwise
                convolution, no bias);
                q, k unit length per head, q / sqrt(d_k);
                beta = 2 sigmoid(x W_b)
                g = -exp(A_log) softplus(x W_a + dt_bias)
                S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1}
                      + beta_t k_t v_t^T
                o_t = S_t^T q_t;  F = (N_dv(o) * SiLU(x W_z)) W_o
    attention:  q = N(x W_q), k = N(x W_k) over the whole width, then heads;
                no rotary; causal softmax at 1 / sqrt(d); F = o W_o
    MLP:        F = (SiLU(x W_gate) * x W_up) W_down
    head:       logits = N_final(h_L) W_head      (untied)
    layer i is attention where layer_types[i] is full_attention

are written out in ``benchmarks/reference/olmo_hybrid.py``, which this
file is compared with.  Matrix products run in ``dtype`` (bfloat16 on the
chip) with float32 sums; parameters, norms, the convolutions, ``beta``,
``g``, the state and the loss are float32, ``W_a`` and ``W_b`` multiply
in float32.  Each sub-layer is rematerialised in the backward pass
(``jax.checkpoint``), the mixers sequence by sequence; the head's loss
takes its inputs' gradient in the forward pass (``ops/head_loss.py``).

Blocks, from the layer list: ``0`` the embedding, ``1 + 2l`` layer ``l``'s
mixer with its post norm, ``2 + 2l`` its MLP with its post norm, the last
the final norm and the head.  The model is dense: ``aux`` holds no
routing counts, only ``gdn_neg_beta_share``, the share of (token, head)
pairs of the Gated DeltaNet layers whose ``beta`` is above 1 (the
transition's eigenvalue along ``k`` is then negative).
"""

from __future__ import annotations

import math
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
    _op,
    dense_mlp,
    head_losses,
    post_norm_merge,
    rms_norm,
)
from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops.flash_attention import (
    causal_attention,
    plan as attn_plan,
)
from federated_pytorch_test_tpu.ops.gated_delta import (
    gated_delta_chunked,
    plan as gdn_scan_plan,
)

_HI = jax.lax.Precision.HIGHEST


def _a_log(key, shape, dtype=_F32):
    # A from U(1, 16): the released code draws U(0, 16); 1 keeps log A finite
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias(key, shape, dtype=_F32):
    # flash-linear-attention's: softplus^-1 of dt, log-uniform in [1e-3, 0.1]
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_taps(key, shape, dtype=_F32):
    # torch's default for a depthwise Conv1d of kernel 4: U(-1/2, 1/2)
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


#: the leaves of a layer that multiply in ``dtype``: :func:`step_weights`
_MATRICES = frozenset({"q_proj", "k_proj", "v_proj", "g_proj", "o_proj",
                       "gate_proj", "up_proj", "down_proj"})


class OlmoHybrid(BlockModule):
    """``__call__(ids [B, T] int32) -> (logits [B, T, vocab_rows] f32,
    aux)``; with ``labels [B, T]`` ``(loss per sequence [B], aux)``."""

    hidden_size: int = 3840
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-6
    #: the config's list; the cut keeps its first ``layers``
    layer_types: Any = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # the cut: layers kept, this chip's share of the vocabulary
    layers: int = 4
    vocab_rows: int = 12544
    # how the model is seeded (assumed; the configuration file says why)
    init_scale: float = 0.02
    embed_scale: float = 1.0
    chunk: int = 64
    attn_block: int = 512
    dtype: Any = jnp.bfloat16

    # -- the layer list and the blocks made from it ---------------------
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layer_kinds(self) -> List[str]:
        types = self.layer_types or [
            "full_attention" if (i + 1) % 4 == 0 else "linear_attention"
            for i in range(self.layers)]
        return ["attn" if t == "full_attention" else "gdn"
                for t in types[:self.layers]]

    def block_names(self) -> List[str]:
        names = ["embed"]
        for i in range(self.layers):
            names += [f"layer{i}_mixer", f"layer{i}_mlp"]
        return names + ["head"]

    def block_kinds(self) -> List[str]:
        """``embed`` / ``gdn`` / ``attn`` / ``mlp`` / ``head`` per block."""
        kinds = ["embed"]
        for k in self.layer_kinds():
            kinds += [k, "mlp"]
        return kinds + ["head"]

    def gdn_scan_impl(self, tokens: int) -> str:
        """What runs the delta rule's chunk recurrence for a sequence of
        ``tokens`` here ("pallas" | "pallas_interpret" | "xla":
        ``ops/gated_delta.py:plan``)."""
        return gdn_scan_plan(
            self.linear_num_value_heads, -(-tokens // self.chunk),
            self.chunk, self.linear_key_head_dim,
            self.linear_value_head_dim, self.dtype)["impl"]

    def attn_impl(self, tokens: int) -> str:
        """What runs the attention core for a sequence of ``tokens`` here
        (``ops/flash_attention.py:plan``)."""
        nkv = self.num_key_value_heads
        return attn_plan(tokens, nkv, self.num_attention_heads // nkv,
                         self.head_dim, self.dtype)["impl"]

    def impl_fields(self, tokens: int) -> Dict[str, str]:
        """The round record's fields that name this backend's
        implementations for sequences of ``tokens``."""
        return {"gdn_scan_impl": self.gdn_scan_impl(tokens),
                "attn_impl": self.attn_impl(tokens), "head_impl": HEAD_IMPL}

    # -- parameters --------------------------------------------------------
    def _spec(self, name: str):
        H, s = self.hidden_size, _normal(self.init_scale)
        post = (("post_norm", (H,), _ONES),)
        if name == "embed":
            return (("embedding", (self.vocab_rows, H),
                     _normal(self.embed_scale)),)
        if name == "head":
            return (("norm", (H,), _ONES),
                    ("kernel", (H, self.vocab_rows), s))
        if name.endswith("_mlp"):
            F = self.intermediate_size
            return (("gate_proj", (H, F), s), ("up_proj", (H, F), s),
                    ("down_proj", (F, H), s)) + post
        if self.layer_kinds()[int(name[5:].split("_")[0])] == "attn":
            q, kv = (self.num_attention_heads * self.head_dim,
                     self.num_key_value_heads * self.head_dim)
            return (("q_proj", (H, q), s), ("k_proj", (H, kv), s),
                    ("v_proj", (H, kv), s), ("q_norm", (q,), _ONES),
                    ("k_norm", (kv,), _ONES), ("o_proj", (q, H), s)) + post
        nk, nv = self.linear_num_key_heads, self.linear_num_value_heads
        k, v = nk * self.linear_key_head_dim, nv * self.linear_value_head_dim
        kw = self.linear_conv_kernel_dim
        return (("q_proj", (H, k), s), ("k_proj", (H, k), s),
                ("v_proj", (H, v), s), ("g_proj", (H, v), s),
                ("a_proj", (H, nv), s), ("b_proj", (H, nv), s),
                ("q_conv", (kw, k), _conv_taps),
                ("k_conv", (kw, k), _conv_taps),
                ("v_conv", (kw, v), _conv_taps),
                ("A_log", (nv,), _a_log), ("dt_bias", (nv,), _dt_bias),
                ("o_norm", (self.linear_value_head_dim,), _ONES),
                ("o_proj", (v, H), s)) + post

    def param_order(self) -> List[str]:
        return [f"{b}/{leaf}" for b in self.block_names()
                for leaf, _, _ in self._spec(b)]

    def train_order_block_ids(self) -> List[List[int]]:
        """Inclusive index ranges into ``param_order()``, one a block."""
        out, lo = [], 0
        for b in self.block_names():
            n = len(self._spec(b))
            out.append([lo, lo + n - 1])
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


def causal_conv(z, taps):
    """``z [T, C]`` through a causal depthwise convolution with ``taps
    [kw, C]`` (oldest first, zeros before the start, no bias), then
    SiLU."""
    T, kw = z.shape[0], taps.shape[0]
    padded = jnp.pad(z, ((kw - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[j:j + T] * taps[j] for j in range(kw)))


def gated_delta_net(cfg: OlmoHybrid, p, x):
    """``x [T, H]`` (the sub-layer's input, not normed) -> ``([T, H], the
    share of (token, head) pairs whose beta is above 1)``.  Key and value
    heads are as many (30 / 30): each key head serves one value head."""
    T = x.shape[0]
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    with scope("gdn_in_proj"):
        q, k, v = (_mm(cfg, x, p[n]) for n in ("q_proj", "k_proj", "v_proj"))
        z = _mm(cfg, x, p["g_proj"]).reshape(T, nv, dv)
        # b, a feed a sigmoid and the state decay: float32 products
        b = jnp.dot(x, p["b_proj"], precision=_HI)
        a = jnp.dot(x, p["a_proj"], precision=_HI)
    with scope("gdn_conv"):
        q, k, v = (causal_conv(t, p[n]) for t, n in
                   ((q, "q_conv"), (k, "k_conv"), (v, "v_conv")))
    with scope("gdn_qk_prep"):
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + 1e-6)
        q = unit(q.reshape(T, nk, dk)) * (1.0 / math.sqrt(dk))
        k = unit(k.reshape(T, nk, dk))
        beta = jax.nn.sigmoid(b)
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
        neg = jnp.mean((beta > 1.0).astype(_F32))
    heads = lambda t: jnp.moveaxis(t, 1, 0)
    with scope("gdn_scan"):
        o = gated_delta_chunked(heads(q), heads(k),
                                heads(v.reshape(T, nv, dv)), heads(g),
                                heads(beta), chunk=cfg.chunk, dtype=cfg.dtype)
    with scope("gdn_out_gate"):
        o = rms_norm(jnp.moveaxis(o, 0, 1), p["o_norm"], cfg.rms_norm_eps) \
            * jax.nn.silu(z)
    with scope("gdn_out_proj"):
        return _mm(cfg, o.reshape(T, nv * dv), p["o_proj"]), neg


def attention(cfg: OlmoHybrid, p, x):
    """``x [T, H]`` (not normed) -> ``[T, H]``: causal multi-head
    attention with q and k each normed over the whole width, no rotary."""
    T, eps = x.shape[0], cfg.rms_norm_eps
    n, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with scope("attn_proj_in"):
        q, k, v = (_mm(cfg, x, p[w]) for w in ("q_proj", "k_proj", "v_proj"))
    with scope("attn_norm_rope"):
        q = rms_norm(q, p["q_norm"], eps).reshape(T, nkv, n // nkv, d) \
            * (1.0 / math.sqrt(d))
        k = rms_norm(k, p["k_norm"], eps).reshape(T, nkv, d)
    o = causal_attention(q, k, v.reshape(T, nkv, d), dtype=cfg.dtype,
                         block=cfg.attn_block, scope="mha_attn")
    with scope("attn_proj_out"):
        return _mm(cfg, o.reshape(T, n * d), p["o_proj"])


def mixer_sub_layer(cfg: OlmoHybrid, kind: str, p, x):
    """``x [B, T, H]`` through a mixer sub-layer with the leaves ``p``,
    sequence by sequence, each rematerialised in the backward pass ->
    ``(h, the beta share of a Gated DeltaNet layer, else None)``."""
    eps = cfg.rms_norm_eps

    @jax.checkpoint
    def gdn(xt):
        with scope("gdn"):
            y, neg = gated_delta_net(cfg, p, xt)
        return post_norm_merge(xt, y, p["post_norm"], eps), neg

    @jax.checkpoint
    def mha(xt):
        with scope("mha_attn"):
            y = attention(cfg, p, xt)
        return post_norm_merge(xt, y, p["post_norm"], eps)

    with scope("sublayer_mixer"):
        if kind == "attn":
            return jax.lax.map(mha, x), None
        h, neg = jax.lax.map(gdn, x)
        return h, jnp.mean(neg)


def mlp_sub_layer(cfg: OlmoHybrid, p, x):
    """``x [B, T, H]`` through the dense SwiGLU with the leaves ``p``,
    all tokens at once, rematerialised in the backward pass."""
    B, T, H = x.shape

    @jax.checkpoint
    def ffn(h):
        y = dense_mlp(cfg, p, h.reshape(B * T, H)).reshape(B, T, H)
        return post_norm_merge(h, y, p["post_norm"], cfg.rms_norm_eps)

    with scope("sublayer_ffn"):
        return ffn(x)


def step_weights(cfg: OlmoHybrid, p, ids):
    """``p`` with the layers' matrices (:data:`_MATRICES`) in the products'
    ``dtype``, cast once for the step (forward and backward) from one
    client's float32 copies.  Each is first multiplied by a 1.0 that the
    compiler cannot prove is one (it is made from the step's ``ids``), so
    the casts are not hoisted out of the engine's step loop: hoisted, both
    clients' bfloat16 copies of every frozen matrix stay alive for the
    whole epoch beside one client's slice of them; made here, one client's
    copy (1.7 GB at 833 M matrix weights) lives for its step.  The TPU
    compiler's count for the largest epoch program of the published cut
    (K = 2, one sequence of 4,096 a step): 16.2 GiB hoisted, 14.4 made
    here, of the 15.75 GiB a v5e offers."""
    with scope("weight_cast"):
        one = 1.0 + 0.0 * ids.reshape(-1)[0].astype(_F32)
        return {b: {n: _op(w * one, cfg.dtype)
                    if n in _MATRICES and b.startswith("layer") else w
                    for n, w in leaves.items()} for b, leaves in p.items()}


def forward(cfg: OlmoHybrid, p, ids, labels=None):
    """``ids [B, T]`` -> ``(logits [B, T, V], aux)``, or with ``labels``
    ``(loss per sequence [B], aux)``."""
    with scope("embed"):
        x = p["embed"]["embedding"][ids]
    p = step_weights(cfg, p, ids)
    shares = []
    for i, kind in enumerate(cfg.layer_kinds()):
        x, neg = mixer_sub_layer(cfg, kind, p[f"layer{i}_mixer"], x)
        if neg is not None:
            shares.append(neg)
        x = mlp_sub_layer(cfg, p[f"layer{i}_mlp"], x)
    with scope("step_stats"):
        aux = {"gdn_neg_beta_share": sum(shares) / len(shares) if shares
               else _F32(0)}
    norm = lambda xt: rms_norm(xt, p["head"]["norm"], cfg.rms_norm_eps)
    if labels is None:
        with scope("lm_head_loss"):
            with scope("head_norm"):
                xn = norm(x)
            with scope("head_product"):
                return _mm(cfg, xn, p["head"]["kernel"]), aux
    return head_losses(cfg, norm, x, p["head"]["kernel"], labels), aux
