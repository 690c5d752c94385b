"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): the residual path as ``n`` streams.

A sub-layer ``F`` reads one learned, input-dependent contraction of the
streams and writes back through a doubly stochastic mixing of them::

    v      = rms(vec(X))                      X in R^{n x C} per token
    P~     = a_pre  * (v phi_pre)  + b_pre    H_pre  = sigmoid(P~)     [n]
    Q~     = a_post * (v phi_post) + b_post   H_post = 2 sigmoid(Q~)   [n]
    R~     = a_res  * mat(v phi_res) + b_res  H_res  = sinkhorn(R~)    [n, n]
    X'     = H_res X + H_post^T F(H_pre X)

Everything here is float32 (the projections at the highest precision):
the maps are small outputs that decide much, as a router's are.  No
Pallas kernel on any backend: plain ``jax.numpy``, differentiated by JAX.

**Layout.**  The streams are ``X [n, ..., C]``: the stream axis leads, so
each stream is a dense ``[tokens, C]`` matrix and the mixing is ``n x n``
scaled adds of such matrices.  (With the stream axis second to last the
TPU would tile a ``[4, C]`` minor pair and pad it to eight sublanes:
twice the memory and twice the traffic of every pass over the streams.)
The maps have the tokens LAST, ``[n, ...]`` and ``[n, n, ...]``: the
Sinkhorn iterations are then elementwise over lanes of tokens and their
row and column sums are adds of whole vectors.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from federated_pytorch_test_tpu.obs.scopes import scope

_F32 = jnp.float32


class Maps(NamedTuple):
    """The three maps of one sub-layer for every token (tokens last)."""

    pre: jnp.ndarray           # [n, ...]    H_pre, in (0, 1)
    post: jnp.ndarray          # [n, ...]    H_post, in (0, 2)
    res: jnp.ndarray           # [n, n, ...] H_res[j, i]: stream i into j
    marginal_err: jnp.ndarray  # (): worst |row sum - 1| or |column sum - 1|


def sinkhorn(logits, iters: int, eps: float):
    """``logits [n, n, ...]`` (already clamped) -> ``exp`` of them
    projected towards the doubly stochastic matrices: ``iters`` times
    every column divided by its sum plus ``eps``, then every row.  The
    matrix lies in the two LEADING axes (``[row, column, ...]``)."""
    m = jnp.exp(logits.astype(_F32))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def maps(x, leaves: Dict[str, jnp.ndarray], *, iters: int, eps: float,
         clamp=(-30.0, 30.0), norm_eps: float = 1e-6) -> Maps:
    """The maps of the streams ``x [n, ..., C]`` under one sub-layer's
    ``leaves``: ``phi_pre, phi_post [n C, n]``, ``phi_res [n C, n n]``
    (rows in ``vec(X)``'s order, stream by stream; ``phi_res``'s columns
    row by row), ``a_pre, a_post, a_res [1]``, ``b_pre, b_post [n]``,
    ``b_res [n, n]``."""
    n, C = x.shape[0], x.shape[-1]
    tokens = x.shape[1:-1]
    with scope("mhc_maps"):
        x = x.astype(_F32)
        # rms over all n C entries of a token; it scales the projections
        # (v phi = r * (vec(X) phi)), so v itself is never written
        r = lax.rsqrt(jnp.sum(x * x, axis=(0, -1)) / (n * C) + norm_eps)
        phi = jnp.concatenate([leaves["phi_pre"], leaves["phi_post"],
                               leaves["phi_res"]], axis=1)
        proj = jnp.einsum("n...c,ncm->m...", x, phi.reshape(n, C, -1),
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=_F32) * r
        over = lambda a: a.reshape(a.shape + (1,) * len(tokens))
        pre = leaves["a_pre"][0] * proj[:n] + over(leaves["b_pre"])
        post = leaves["a_post"][0] * proj[n:2 * n] + over(leaves["b_post"])
        res = leaves["a_res"][0] * proj[2 * n:].reshape((n, n) + tokens) \
            + over(leaves["b_res"])
        h_res = sinkhorn(jnp.clip(res, clamp[0], clamp[1]), iters, eps)
        err = lax.stop_gradient(jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0)),
            jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0))))
        return Maps(jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res,
                    err)


def contract(h_pre, x):
    """``H_pre X``: the sub-layer's input ``[..., C]`` from the streams
    ``x [n, ..., C]`` and ``h_pre [n, ...]``."""
    with scope("mhc_mix"):
        return jnp.sum(h_pre[..., None] * x, axis=0)


def expand(h_res, h_post, x, y):
    """``H_res X + H_post^T y``: the next streams ``[n, ..., C]`` from
    the streams ``x``, the sub-layer's output ``y [..., C]``, ``h_res [n,
    n, ...]`` and ``h_post [n, ...]``."""
    with scope("mhc_mix"):
        n = x.shape[0]
        return jnp.stack([
            sum(h_res[j, i][..., None] * x[i] for i in range(n))
            + h_post[j][..., None] * y for j in range(n)])
