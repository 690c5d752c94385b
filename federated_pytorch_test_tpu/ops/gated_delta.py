"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464), chunked.

Per head, with state ``S [d_k, d_v]``, for ``t = 0 .. T-1``::

    S <- exp(g_t) S
    S <- S + k_t (x) beta_t (v_t - S^T k_t)
    o_t = S^T q_t

:func:`gated_delta_stepwise` is that recurrence, token by token, in
float32.  :func:`gated_delta_chunked` computes the same in chunks of
``chunk`` tokens (the WY form: inside a chunk the ``chunk`` rank-one
updates are folded into one unit-lower-triangular solve, and only the
chunk boundaries carry ``S``), so the work is matrix products; its
backward pass is JAX's transpose of it.  Sequences that are no multiple
of ``chunk`` are padded at the end with ``beta = 0, g = 0`` steps, which
leave ``S`` alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from federated_pytorch_test_tpu.ops.moe import operand

_HI = lax.Precision.HIGHEST


def gated_delta_stepwise(q, k, v, g, beta):
    """``q, k [T, d_k]``, ``v [T, d_v]``, ``g, beta [T]`` of ONE head ->
    ``o [T, d_v]``; float32 throughout (vmap it over heads)."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt) * S
        S = S + jnp.outer(kt, bt * (vt - jnp.dot(kt, S, precision=_HI)))
        return S, jnp.dot(qt, S, precision=_HI)

    S0 = jnp.zeros((k.shape[-1], v.shape[-1]), jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)
    _, o = lax.scan(step, S0, (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return o


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a [..., C, C]``:
    ``a`` is nilpotent, so the Neumann series ends and equals the product
    ``(I - a)(I + a^2)(I + a^4)...`` of ``log2 C`` factors: matrix
    products instead of ``C`` steps of forward substitution."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)
    mm = lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y, precision=_HI)
    inv, power = eye - a, a
    span = 2
    while span < C:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        span *= 2
    return inv


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = 64,
                        dtype=jnp.bfloat16):
    """Heads-first: ``q, k [H, T, d_k]``, ``v [H, T, d_v]``, ``g, beta
    [H, T]`` (``q`` already scaled, ``q`` and ``k`` already normalised)
    -> ``o [H, T, d_v]`` float32.  The products with ``S``, of ``q`` with
    ``k`` and of the chunk's own values run in ``dtype`` with float32
    sums; decays, the triangular inverse and ``S`` stay float32."""
    H, T, dk = k.shape
    dv = v.shape[-1]
    pad = (-T) % chunk
    if pad:
        z = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)
    N = (T + pad) // chunk
    f32 = lambda a: a.astype(jnp.float32)
    cut = lambda a: f32(a).reshape(H, N, chunk, *a.shape[2:])
    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    ein = lambda spec, a, b: jnp.einsum(
        spec, operand(a, dtype), operand(b, dtype),
        preferred_element_type=jnp.float32)

    gc = jnp.cumsum(g, axis=-1)                        # [H, N, C]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    # exp(gc_i - gc_j) for i >= j; masked BEFORE exp so the upper half
    # (positive differences) cannot overflow into the gradient
    diff = jnp.where(tri, gc[..., :, None] - gc[..., None, :], 0.0)
    decay = jnp.where(tri, jnp.exp(diff), 0.0)         # [H, N, C, C]
    kb = k * beta[..., None]
    a = jnp.where(strict, jnp.einsum("hnid,hnjd->hnij", kb, k,
                                     precision=_HI) * decay, 0.0)
    inv = _unit_lower_inverse(a)                       # (I + a)^-1
    # the chunk's pseudo-values and the decayed keys that meet S
    u = jnp.einsum("hnij,hnjd->hnid", inv, v * beta[..., None],
                   precision=_HI)
    w = jnp.einsum("hnij,hnjd->hnid", inv, kb * jnp.exp(gc)[..., None],
                   precision=_HI)
    qk = jnp.where(tri, ein("hnid,hnjd->hnij", q, k) * decay, 0.0)
    q_in = q * jnp.exp(gc)[..., None]                  # q_i exp(gc_i)
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]  # k_i exp(gc_C - gc_i)
    g_last = jnp.exp(gc[..., -1])                      # [H, N]

    def step(S, x):
        u_n, w_n, qk_n, q_n, k_n, gl = x
        v_new = u_n - ein("hid,hde->hie", w_n, S)
        o = ein("hid,hde->hie", q_n, S) + ein("hij,hje->hie", qk_n, v_new)
        S = S * gl[:, None, None] + ein("hid,hie->hde", k_n, v_new)
        return S, o

    first = lambda a: jnp.moveaxis(a, 1, 0)            # chunks lead
    S0 = jnp.zeros((H, dk, dv), jnp.float32)
    _, o = lax.scan(step, S0, tuple(map(first, (u, w, qk, q_in, k_out,
                                                g_last))))
    return jnp.moveaxis(o, 0, 1).reshape(H, N * chunk, dv)[:, :T]
