"""Plain reference for Olmo-Hybrid-7B (``model_type olmo_hybrid``):
forward, loss and gradient of ONE sequence in float32 ``jax.numpy``.

Nothing here comes from the package under test (``get_path`` /
``set_path`` are ``fed_round.py``'s, the other plain reference).  Every
matrix product runs under ``jax.default_matmul_precision("highest")``
(callers use :func:`loss_and_grad`, which sets it); without it a TPU
multiplies float32 operands in bfloat16 passes.

The equations (config.json of allenai/Olmo-Hybrid-7B for the shapes;
Gated DeltaNet: arXiv:2412.06464 as flash-linear-attention's
``GatedDeltaNet`` builds it; ``linear_allow_neg_eigval``: arXiv:2411.12537;
the reordered norm and the QK-norm: OLMo 2, arXiv:2501.00656).  ``N(x;
w) = x / sqrt(mean(x^2) + eps) * w``; ``h_0 = Emb[ids]``.

* every sub-layer: ``h' = h + N(F(h); w_post)``; ``F`` reads ``h`` itself,
  not normed.  Layer ``i`` is attention where ``layer_types[i]`` is
  ``full_attention``, else Gated DeltaNet; each mixer is followed by the
  MLP.
* Gated DeltaNet ``F(x)``:

  1. ``q = x W_q``, ``k = x W_k`` (``n_k`` heads of ``d_k``), ``v = x W_v``
     (``n_v`` heads of ``d_v``), ``z = x W_z``, ``a = x W_a``, ``b = x
     W_b``.
  2. ``q, k, v`` each through its own causal depthwise convolution
     (``c[j]``, taps oldest first, zeros before the start, no bias), then
     SiLU.
  3. ``q, k`` unit length per head (``t / sqrt(sum t^2 + 1e-6)``), ``q``
     times ``d_k^-1/2``; each key head serves ``n_v / n_k`` value heads.
  4. ``beta = 2 sigmoid(b)`` (``sigmoid(b)`` without negative
     eigenvalues), ``g = -exp(A_log) softplus(a + dt_bias)``.
  5. per head, token by token: ``S_t = exp(g_t) (I - beta_t k_t k_t^T)
     S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``, ``S_0 = 0``.
  6. ``F = (N(o; w_o) * silu(z)) W_o``, ``N`` over each head's ``d_v``.

* attention ``F(x)``: ``q = N(x W_q; w_q)``, ``k = N(x W_k; w_k)``, each
  normed over its whole width before it is cut into heads, ``v = x W_v``;
  no rotary; per head ``softmax(q k^T / sqrt(d))`` under the causal mask,
  times ``v``; ``F = o W_o``.
* MLP ``F(x) = (silu(x W_gate) * x W_up) W_down``.
* head: ``logits = N(h_L; w_f) W_head`` over the held vocabulary rows; the
  loss of a sequence is the mean next-token cross-entropy.

Departures and assumptions (also in the configuration file): the
placements above where the config is silent (the norms' places, the
QK-norm's width, no rotary read from a null ``rope_theta``, separate
convolutions and their SiLU, the output gate's norm); no dropout.

To fit beside the trainer at the published widths attention runs head by
head, the recurrence is rematerialised in runs of 64 steps, and each
sub-layer is rematerialised in the backward pass.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.fed_round import get_path, set_path  # noqa: F401

F32 = jnp.float32


def norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def delayed(x, steps: int):
    """``x [T, ...]`` moved ``steps`` later along ``T``, zeros in front."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:steps]), x[:-steps]], axis=0)


def is_attention(cfg, i: int) -> bool:
    types = cfg.get("layer_types")
    if types:
        return types[i] == "full_attention"
    return (i + 1) % 4 == 0


# ----------------------------------------------------------------------
# Gated DeltaNet
# ----------------------------------------------------------------------
def delta_rule(q, k, v, g, beta, run: int = 64):
    """The recurrence for one head, token by token: ``q, k [T, d_k]``,
    ``v [T, d_v]``, ``g, beta [T]`` -> ``o [T, d_v]``."""
    T = q.shape[0]
    pad = (-T) % run

    def step(S, x):
        qt, kt, vt, gt, bt = x
        # exp(g) (I - beta k k^T) S + beta k v^T
        S = jnp.exp(gt) * (S - bt * jnp.outer(kt, kt @ S)) \
            + bt * jnp.outer(kt, vt)
        return S, qt @ S

    @jax.checkpoint
    def steps(S, xs):
        return lax.scan(step, S, xs)

    xs = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (T + pad) // run, run, *a.shape[1:]) for a in (q, k, v, g, beta))
    S0 = jnp.zeros((k.shape[-1], v.shape[-1]), F32)
    _, o = lax.scan(steps, S0, xs)
    return o.reshape(T + pad, -1)[:T]


def conv(x, taps):
    """Causal depthwise convolution of ``x [T, C]`` by ``taps [kw, C]``
    (oldest first) as shifted sums, then SiLU."""
    kw = taps.shape[0]
    return jax.nn.silu(sum(taps[j][None, :] * delayed(x, kw - 1 - j)
                           for j in range(kw)))


def delta_net(cfg, p, x):
    T = x.shape[0]
    nk = int(cfg["linear_num_key_heads"])
    nv = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    q = conv(x @ p["q_proj"], p["q_conv"]).reshape(T, nk, dk)
    k = conv(x @ p["k_proj"], p["k_conv"]).reshape(T, nk, dk)
    v = conv(x @ p["v_proj"], p["v_conv"]).reshape(T, nv, dv)
    z = (x @ p["g_proj"]).reshape(T, nv, dv)
    l2 = lambda t: t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    q = jnp.repeat(q, nv // nk, axis=1)
    k = jnp.repeat(k, nv // nk, axis=1)
    beta = jax.nn.sigmoid(x @ p["b_proj"])
    if cfg.get("linear_allow_neg_eigval", False):
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(x @ p["a_proj"]
                                               + p["dt_bias"])
    o = jax.vmap(delta_rule, in_axes=(1, 1, 1, 1, 1), out_axes=1)(
        q, k, v, g, beta)                                      # [T, nv, dv]
    o = norm(o, p["o_norm"], float(cfg["rms_norm_eps"])) * jax.nn.silu(z)
    return o.reshape(T, nv * dv) @ p["o_proj"]


# ----------------------------------------------------------------------
# attention, the MLP
# ----------------------------------------------------------------------
def attention(cfg, p, x):
    T = x.shape[0]
    n, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, eps = int(cfg["hidden_size"]) // n, float(cfg["rms_norm_eps"])
    q = norm(x @ p["q_proj"], p["q_norm"], eps).reshape(T, n, d)
    k = norm(x @ p["k_proj"], p["k_norm"], eps).reshape(T, nkv, d)
    v = (x @ p["v_proj"]).reshape(T, nkv, d)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(h):
        qh = lax.dynamic_index_in_dim(q, h, 1, keepdims=False)
        kh = lax.dynamic_index_in_dim(k, h // (n // nkv), 1, keepdims=False)
        vh = lax.dynamic_index_in_dim(v, h // (n // nkv), 1, keepdims=False)
        s = jnp.where(causal, qh @ kh.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    o = jnp.moveaxis(lax.map(head, jnp.arange(n)), 0, 1)      # [T, n, d]
    return o.reshape(T, n * d) @ p["o_proj"]


def mlp(cfg, p, x):
    return (jax.nn.silu(x @ p["gate_proj"]) * (x @ p["up_proj"])) \
        @ p["down_proj"]


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def sub_layer(cfg, f, p, h):
    return h + norm(f(cfg, p, h), p["post_norm"], float(cfg["rms_norm_eps"]))


def hidden(cfg: Dict[str, Any], params, ids):
    """``ids [T]`` -> the last layer's output ``[T, H]``."""
    h = params["embed"]["embedding"][ids]
    for i in range(int(cfg["layers"])):
        mixer = attention if is_attention(cfg, i) else delta_net
        h = jax.checkpoint(lambda p, h, f=mixer: sub_layer(cfg, f, p, h))(
            params[f"layer{i}_mixer"], h)
        h = jax.checkpoint(lambda p, h: sub_layer(cfg, mlp, p, h))(
            params[f"layer{i}_mlp"], h)
    return h


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]


def sequence_loss(cfg, params, ids, labels):
    """The loss of one sequence and ``{"logits"}``."""
    h = hidden(cfg, params, ids)
    logits = norm(h, params["head"]["norm"], float(cfg["rms_norm_eps"])) \
        @ params["head"]["kernel"]
    return jnp.mean(cross_entropy(logits, labels)), {"logits": logits}


_GRAD_CACHE: Dict[Any, Any] = {}


def loss_and_grad(cfg, params, paths: Sequence[str], ids, labels):
    """``(loss, {"logits"}, [d loss / d leaf for the leaves at paths])``
    of one sequence ``ids, labels [T]``."""
    key = (id(cfg), tuple(paths))
    if key not in _GRAD_CACHE:
        def f(leaves, params, ids, labels):
            for path, leaf in zip(paths, leaves):
                params = set_path(params, path, leaf)
            return sequence_loss(cfg, params, ids, labels)
        _GRAD_CACHE[key] = jax.jit(jax.value_and_grad(f, has_aux=True))
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = _GRAD_CACHE[key](
            [get_path(params, p) for p in paths], params, ids, labels)
    return loss, aux, grads
