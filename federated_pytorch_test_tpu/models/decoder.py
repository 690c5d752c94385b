"""What the decoders share (``models/qwen3_next.py``,
``models/glm4_moe_lite.py``): a block's leaves, the rotary tables, the
loss helpers, and the part of an expert layer that follows the router on
a chip that holds a share of the experts (sort, grouped products,
scatter).  Each decoder keeps its own norm, mixers and router rule.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from federated_pytorch_test_tpu.ops import moe as moelib

_F32 = jnp.float32
_op = moelib.operand
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


def rope_tables(T: int, rot: int, theta: float):
    """``cos, sin [T, rot]`` (rotate-half layout: the ``rot / 2``
    frequencies repeated)."""
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=_F32) / rot)
    ang = jnp.arange(T, dtype=_F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """``x [..., T, heads, d]``: rotate the first ``cos.shape[-1]``
    dimensions of each head."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([xr * c + turned * s, rest], -1)


def held_experts(cfg, p, x, w, e, n_experts: int):
    """The routed part of an expert layer on this chip: of the pairs
    ``w, e [T, k]`` (every token's weights and experts among all
    ``n_experts``) those that hit ``cfg.experts_held`` experts from
    ``cfg.ep_rank * cfg.experts_held``, sorted by expert, through
    ``p["experts_gate" | "experts_up" | "experts_down"]`` and added into
    ``y [T, H]``; ``-> (y, routing)``."""
    T, H = x.shape
    E, k = cfg.experts_held, e.shape[-1]
    rows = int(math.ceil(cfg.pair_rows_factor * T * k * E / n_experts
                         / 8.0)) * 8
    rows = min(rows, T * min(k, E))
    with jax.named_scope("moe_route"):
        r = moelib.route_local(w, e, cfg.ep_rank * E, E, rows)
        xs = x[r.token]
    with jax.named_scope("moe_experts"):
        gm = lambda a, wt: moelib.grouped_matmul(a, wt, r.group_sizes,
                                                 cfg.dtype)
        h = jax.nn.silu(gm(xs, p["experts_gate"])) * gm(xs, p["experts_up"])
        ys = gm(h, p["experts_down"])
    with jax.named_scope("moe_route"):
        ys = jnp.where(r.weight[:, None] > 0, ys * r.weight[:, None], 0.0)
        y = jnp.zeros((T, H), _F32).at[r.token].add(ys)
    return y, r


def sequence_loss(logits, labels):
    """Mean cross-entropy of ``logits [..., T, V]`` against ``labels
    [..., T]`` over ``T``, in float32."""
    with jax.named_scope("lm_head_loss"):
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
