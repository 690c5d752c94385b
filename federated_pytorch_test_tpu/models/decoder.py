"""What the decoders share (``models/qwen3_next.py``,
``models/glm4_moe_lite.py``, ``models/xing4_0.py``, ``models/zaya.py``,
``models/olmo_hybrid.py``): a block's leaves, the rotary tables (plain or
YaRN's), the reordered merge that norms a sub-layer's output, the loss
helpers,
the head of a model whose embedding is also its head's matrix, the part
of an expert layer that follows the router on a chip that holds a share
of the experts (sort, grouped products, scatter), and what the two
decoders of the DeepSeek-V3 line share: the plain RMS norm, multi-head
latent attention, the dense SwiGLU, the expert layer under the sigmoid
rule with its ungated shared expert, and their leaves.  Each decoder
keeps its own layer and residual path.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops import moe as moelib
from federated_pytorch_test_tpu.ops.flash_attention import causal_attention
from federated_pytorch_test_tpu.ops import head_loss as headlib

_F32 = jnp.float32
_op = moelib.operand
#: the round field ``head_impl`` of every decoder
HEAD_IMPL = headlib.IMPL
_ZEROS, _ONES = nn.initializers.zeros, nn.initializers.ones


def _normal(scale):
    return lambda key, shape, dtype=_F32: scale * jax.random.normal(
        key, shape, dtype)


class _Leaves(nn.Module):
    """The parameters of one block: ``((name, shape, init), ...)``."""

    spec: Tuple[Tuple[str, Tuple[int, ...], Any], ...]

    @nn.compact
    def __call__(self) -> Dict[str, jnp.ndarray]:
        return {n: self.param(n, init, shape, _F32)
                for n, shape, init in self.spec}


def _mm(cfg, x, w):
    return jnp.dot(_op(x, cfg.dtype), _op(w, cfg.dtype),
                   preferred_element_type=_F32)


def rope_tables(T: int, rot: int, theta: float, inv=None):
    """``cos, sin [T, rot]`` (rotate-half layout: the ``rot / 2``
    frequencies repeated).  ``inv [rot / 2]`` takes the place of the
    plain inverse frequencies (:func:`yarn_inv_freq`)."""
    if inv is None:
        inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=_F32) / rot)
    ang = jnp.arange(T, dtype=_F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def yarn_inv_freq(rot: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies ``[rot / 2]`` (arXiv:2309.00071, as the
    DeepSeek-V3 line's ``rope_scaling`` of ``type yarn`` has them): a
    frequency that turns more than ``beta_fast`` times over the
    ``original`` context stays, one that turns fewer than ``beta_slow``
    times is divided by ``factor``, and a linear ramp lies between."""
    turns_at = lambda beta: rot * math.log(original / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rot - 1)
    i = jnp.arange(rot // 2, dtype=_F32)
    keep = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = 1.0 / theta ** (2.0 * i / rot)
    return plain / factor * (1.0 - keep) + plain * keep


def yarn_softmax_scale(factor: float, mscale_all_dim: float) -> float:
    """What YaRN multiplies the softmax scale by: ``(0.1 mscale_all_dim
    ln factor + 1)^2`` (1 where nothing is stretched)."""
    if factor <= 1 or not mscale_all_dim:
        return 1.0
    return (0.1 * mscale_all_dim * math.log(factor) + 1.0) ** 2


def apply_rope(x, cos, sin):
    """``x [..., T, heads, d]``: rotate the first ``cos.shape[-1]``
    dimensions of each head."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([xr * c + turned * s, rest], -1)


def rms_norm(x, w, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def post_norm_merge(h, y, w, eps):
    """The reordered residual merge (OLMo 2, arXiv:2501.00656): a
    sub-layer's output ``y`` normed, then added to its un-normed input
    ``h``: ``h + N(y; w)``."""
    with scope("post_norm"):
        return h + rms_norm(y, w, eps)


def latent_attention(cfg, p, x, outer: str = "", scale=None, inv_freq=None):
    """Multi-head latent attention in its expanded form (arXiv:2405.04434
    section 2.1): ``x [T, H]`` (already normed) -> ``[T, H]``.  The value
    heads' width ``cfg.v_head_dim`` is their own.  ``outer`` is the scope
    path the caller stands in (``"mtp/"``), for the backward kernel's
    name; ``scale`` the softmax scale (the key width's inverse root where
    not given); ``inv_freq`` the rotary inverse frequencies (plain ones
    of ``cfg.rope_theta`` where not given)."""
    T, n = x.shape[0], cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.rms_norm_eps
    if scale is None:
        scale = 1.0 / math.sqrt(dn + dr)
    # the scopes alternate so that the operations keep their order
    with scope("attn_proj_in"):
        c_q = _mm(cfg, x, p["q_a_proj"])
    with scope("attn_norm_rope"):
        c_q = rms_norm(c_q, p["q_a_norm"], eps)
    with scope("attn_proj_in"):
        q = _mm(cfg, c_q, p["q_b_proj"]).reshape(T, n, dn + dr)
        kv_a = _mm(cfg, x, p["kv_a_proj"])
        c_kv = kv_a[:, :cfg.kv_lora_rank]
    with scope("attn_norm_rope"):
        # the norm is the latent's; the rotary key, shared by every head,
        # goes by it untouched
        c_kv = rms_norm(c_kv, p["kv_a_norm"], eps)
    with scope("attn_proj_in"):
        k_rope = kv_a[:, cfg.kv_lora_rank:].reshape(T, 1, dr)
        kv = _mm(cfg, c_kv, p["kv_b_proj"]).reshape(T, n, dn + dv)
    with scope("attn_norm_rope"):
        cos, sin = rope_tables(T, dr, cfg.rope_theta, inv_freq)
        q = jnp.concatenate([q[..., :dn],
                             apply_rope(q[..., dn:], cos, sin)], -1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            apply_rope(k_rope, cos, sin), (T, n, dr))], -1)
        q = q.reshape(T, n, 1, dn + dr) * scale
    with scope("mla_core"):
        o = causal_attention(q, k, kv[..., dn:], dtype=cfg.dtype,
                             block=cfg.attn_block,
                             scope=outer + "mla_attn/mla_core")
    with scope("attn_proj_out"):
        return _mm(cfg, o.reshape(T, n * dv), p["o_proj"])


def mla_leaves(cfg):
    """A latent-attention mixer's leaves with its input norm."""
    H, s, n = cfg.hidden_size, _normal(cfg.init_scale), \
        cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return (("norm", (H,), _ONES),
            ("q_a_proj", (H, cfg.q_lora_rank), s),
            ("q_a_norm", (cfg.q_lora_rank,), _ONES),
            ("q_b_proj", (cfg.q_lora_rank, n * qk), s),
            ("kv_a_proj", (H, cfg.kv_lora_rank + cfg.qk_rope_head_dim), s),
            ("kv_a_norm", (cfg.kv_lora_rank,), _ONES),
            ("kv_b_proj", (cfg.kv_lora_rank,
                           n * (cfg.qk_nope_head_dim + cfg.v_head_dim)), s),
            ("o_proj", (n * cfg.v_head_dim, H), s))


def dense_mlp_leaves(cfg):
    H, F, s = cfg.hidden_size, cfg.intermediate_size, \
        _normal(cfg.init_scale)
    return (("norm", (H,), _ONES), ("gate_proj", (H, F), s),
            ("up_proj", (H, F), s), ("down_proj", (F, H), s))


def sigmoid_moe_leaves(cfg):
    """An expert block's leaves under the sigmoid rule: the router and
    its selection bias FIRST (they lie in no federated block), then the
    norm, the held experts and the shared expert."""
    H, s = cfg.hidden_size, _normal(cfg.init_scale)
    E, F = cfg.experts_held, cfg.moe_intermediate_size
    Fs = F * cfg.n_shared_experts
    return (("router", (H, cfg.n_routed_experts), s),
            ("router_bias", (cfg.n_routed_experts,),
             _normal(cfg.bias_scale)),
            ("norm", (H,), _ONES),
            ("experts_gate", (E, H, F), s),
            ("experts_up", (E, H, F), s),
            ("experts_down", (E, F, H), s),
            ("shared_gate_proj", (H, Fs), s),
            ("shared_up", (H, Fs), s),
            ("shared_down", (Fs, H), s))


def dense_mlp(cfg, p, x):
    """``x [T, H]`` (already normed) -> ``[T, H]``."""
    with scope("dense_mlp"):
        h = jax.nn.silu(_mm(cfg, x, p["gate_proj"])) \
            * _mm(cfg, x, p["up_proj"])
        return _mm(cfg, h, p["down_proj"])


def sigmoid_expert_layer(cfg, p, x):
    """``x [T, H]`` (already normed) -> ``([T, H], routing)``: sigmoid
    scores, the top-k by score + selection bias, weights without the
    bias (``ops/moe.py``), the held experts' terms and one ungated
    shared expert."""
    with scope("moe_route"), scope("route_scores"):
        logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
        w, e = moelib.sigmoid_router_weights(
            logits, p["router_bias"], cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scaling_factor)
    y, r = held_experts(cfg, p, x, w, e, cfg.n_routed_experts)
    with scope("moe_shared"):
        hs = jax.nn.silu(_mm(cfg, x, p["shared_gate_proj"])) \
            * _mm(cfg, x, p["shared_up"])
        y = y + _mm(cfg, hs, p["shared_down"])
    return y, r


def held_experts(cfg, p, x, w, e, n_experts: int):
    """The routed part of an expert layer on this chip: of the pairs
    ``w, e [T, k]`` (every token's weights and experts among all
    ``n_experts``) those that hit ``cfg.experts_held`` experts from
    ``cfg.ep_rank * cfg.experts_held``, sorted by expert into a buffer of
    ``rows`` rows (``cfg.pair_rows_factor`` x the mean count, at most
    every pair that can exist), through
    ``p["experts_gate" | "experts_up" | "experts_down"]`` and added into
    ``y [T, H]``; ``-> (y, routing)``.  The buffer is sized for the
    worst traffic; what moves the tokens' rows into it and the experts'
    rows out of it (``ops/moe.py``: ``dispatch``, ``combine``) visits the
    filled rows only."""
    T = x.shape[0]
    E, k = cfg.experts_held, e.shape[-1]
    rows = int(math.ceil(cfg.pair_rows_factor * T * k * E / n_experts
                         / 8.0)) * 8
    rows = min(rows, T * min(k, E))
    with scope("moe_route"), scope("route_sort"):
        r = moelib.route_local(w, e, cfg.ep_rank * E, E, rows)
    xs = moelib.dispatch(x, r)
    with scope("moe_experts"):
        gm = lambda a, wt: moelib.grouped_matmul(a, wt, r.group_sizes,
                                                 cfg.dtype)
        h = jax.nn.silu(gm(xs, p["experts_gate"])) * gm(xs, p["experts_up"])
        ys = gm(h, p["experts_down"])
    return moelib.combine(ys, r, T), r


def routing_counts(r):
    """What a step keeps of one expert layer's routing ``r``: the pairs
    that hit a held expert, those of them that found no row, the load
    ratio, and the pair buffer's (static) rows."""
    return r.pairs_local, r.dropped, r.load_max_over_mean, r.token.shape[0]


def moe_aux(routed):
    """A step's ``aux`` counters from the :func:`routing_counts` of its
    expert layers: sums over the layers, the worst layer's load ratio."""
    pairs, dropped, load, rows = zip(*routed) if routed else ((),) * 4
    return {"moe_pairs_local": sum(pairs, jnp.int32(0)),
            "moe_dropped": sum(dropped, jnp.int32(0)),
            "moe_load_max_over_mean": functools.reduce(jnp.maximum, load,
                                                       _F32(0)),
            "moe_rows": sum(rows, jnp.int32(0))}


def tied_head_logits(cfg, x, norm, embedding):
    """``x [..., H]`` through the final norm ``norm [H]`` and the head of
    a model with a tied embedding: ``N(x) Emb^T`` over the rows
    ``embedding [V, H]`` holds, the matrix contracted along its width as
    it lies (no transposed copy is asked for)."""
    with scope("lm_head_loss"):
        with scope("head_norm"):
            xn = rms_norm(x, norm, cfg.rms_norm_eps)
        return headlib.logits(xn, embedding, contract=1, dtype=cfg.dtype)


def head_losses(cfg, norm, x, w, labels, token_weight=None, contract=0):
    """The loss of each sequence ``[B]`` of ``x [B, T, H]`` against
    ``labels [B, T]``: the model's final norm ``norm`` (``[T, H] -> [T,
    H]``), then ``ops/head_loss.py:head_loss`` over the head's matrix
    ``w`` (``[H, V]``; ``[V, H]`` with ``contract=1``), sequence by
    sequence, so that one sequence's ``[T, V]`` float32 logits are
    alive at a time.  Nothing is rematerialised: the op takes the
    gradient of its inputs in the forward pass, and what the map keeps
    for the backward is ``[T, H]`` a sequence (the norm's input, the
    op's ``dx``) and, where ``w`` is being trained, ``w``'s shape."""
    def one(a):
        with scope("lm_head_loss"):
            with scope("head_norm"):
                xn = norm(a[0])
            return headlib.head_loss(xn, w, a[1], token_weight,
                                     contract=contract, dtype=cfg.dtype)

    return jax.lax.map(one, (x, labels))


def sequence_loss(logits, labels):
    """Mean cross-entropy of ``logits [..., T, V]`` against ``labels
    [..., T]`` over ``T``, in float32."""
    with scope("lm_head_loss"), scope("head_softmax"):
        logits = logits.astype(_F32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - picked, axis=-1)


def weighted_mean(per_sequence, weights=None):
    """Mean over the sequences; ``weights [B]`` (0/1) leaves pad
    sequences out."""
    if weights is None:
        return jnp.mean(per_sequence)
    return jnp.sum(per_sequence * weights) / jnp.maximum(jnp.sum(weights),
                                                         1.0)


def next_token_loss(logits, labels, weights=None):
    """Mean cross-entropy of ``logits [B, T, V]`` against ``labels [B, T]``
    in float32; ``weights [B]`` (0/1) leaves pad sequences out."""
    return weighted_mean(sequence_loss(logits, labels), weights)
