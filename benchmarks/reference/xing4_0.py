"""Plain reference for Xing4.0-29B-A4B (``model_type xing4_0``): forward,
loss and gradient of ONE sequence in float32 ``jax.numpy``.

Nothing here comes from the package under test (``get_path`` /
``set_path`` are ``fed_round.py``'s, the other plain reference).  Every
matrix product runs under ``jax.default_matmul_precision("highest")``
(callers use :func:`loss_and_grad`, which sets it); without it a TPU
multiplies float32 operands in bfloat16 passes.

The equations (config.json of XingChen-AGI/Xing4.0-29B-A4B; the
hyper-connections: arXiv:2512.24880 over arXiv:2409.19606; latent
attention: arXiv:2405.04434 section 2.1; routing: arXiv:2412.19437
section 2.1.2; YaRN: arXiv:2309.00071).  ``N(x) = x / sqrt(mean(x^2) +
eps) * w``; ``n = hc_mult`` streams of width ``C``; a token's streams
are ``X in R^{n x C}``.

* streams: ``X_0`` is the token's embedding copied into ``n`` rows.
* maps of a sub-layer, per token: ``v = vec(X) / sqrt(mean(vec(X)^2) +
  eps)`` (no learned weight); ``H_pre = sigmoid(a_pre (v phi_pre) +
  b_pre)`` in ``R^n``; ``H_post = 2 sigmoid(a_post (v phi_post) +
  b_post)`` in ``R^n``; ``R = a_res mat(v phi_res) + b_res`` in ``R^{n x
  n}`` (row by row); ``M = exp(clip(R, clamp_min, clamp_max))``, then
  ``hc_sinkhorn_iters`` times: every column of ``M`` divided by its sum
  plus ``hc_eps``, then every row; ``H_res = M``.
* sub-layer ``F`` (latent attention, the dense SwiGLU or the expert
  layer, each after its own input norm): ``X' = H_res X + H_post^T
  F(N(H_pre X))``: row ``j`` of ``X'`` is ``sum_i H_res[j, i] X[i] +
  H_post[j] y``.
* latent attention as ``reference/glm4_moe_lite.py`` has it, with value
  heads of their own width, YaRN's frequencies ``inv_i = (1 - m_i) /
  (factor theta^(2i/d)) + m_i / theta^(2i/d)``, ``m_i = 1 - clip((i -
  low) / (high - low), 0, 1)``, ``low = floor(d ln(L / (beta_fast 2 pi))
  / (2 ln theta))``, ``high = ceil(d ln(L / (beta_slow 2 pi)) / (2 ln
  theta))`` clipped to ``[0, d - 1]`` (``d = qk_rope_head_dim``, ``L =
  original_max_position_embeddings``), cos and sin unscaled, and the
  softmax scale ``(qk_nope + qk_rope)^-1/2 (0.1 mscale_all_dim ln factor
  + 1)^2``.
* expert layer: sigmoid scores, top-k of score + bias, weights without
  the bias, renormalised, times ``routed_scaling_factor``; one ungated
  shared expert.  This chip holds experts ``[ep_rank * experts_held,
  (ep_rank + 1) * experts_held)`` and adds only their terms; the shared
  expert is whole.
* output: ``logits = W_head N(sum of the n rows of X_L)`` over the held
  vocabulary rows; the loss of a sequence is the mean next-token
  cross-entropy.

Departures and assumptions (also in the configuration file): the map
equations are the papers', only ``hc_mult``, the iteration count,
``hc_eps`` and the clamp are the config's; no learned weight in the
maps' norm; columns before rows; the output is the rows' sum (the final
norm makes sum and mean one model); rotate-half rotary layout; latent
attention in its expanded form; no multi-token-prediction layer; no
auxiliary loss, no dropout.

To fit beside the trainer at the published widths attention runs head by
head, experts run one after another over all tokens, and each sub-layer
is rematerialised in the backward pass.
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


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# ----------------------------------------------------------------------
# hyper-connections
# ----------------------------------------------------------------------
def sinkhorn_one(r, iters: int, eps: float, lo: float, hi: float):
    """One token's ``R [n, n]`` -> ``H_res [n, n]``, written out."""
    m = jnp.exp(jnp.clip(r, lo, hi))
    for _ in range(iters):
        column_sums = jnp.sum(m, axis=0)
        m = m / (column_sums[None, :] + eps)
        row_sums = jnp.sum(m, axis=1)
        m = m / (row_sums[:, None] + eps)
    return m


def token_maps(cfg, p, x):
    """One token's streams ``x [n, C]`` -> ``(H_pre [n], H_post [n],
    H_res [n, n])``."""
    n = int(cfg["hc_mult"])
    flat = x.reshape(-1)
    v = flat * lax.rsqrt(jnp.mean(flat * flat) + float(cfg["rms_norm_eps"]))
    h_pre = jax.nn.sigmoid(p["hc_a_pre"][0] * (v @ p["hc_phi_pre"])
                           + p["hc_b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(p["hc_a_post"][0] * (v @ p["hc_phi_post"])
                                  + p["hc_b_post"])
    r = p["hc_a_res"][0] * (v @ p["hc_phi_res"]).reshape(n, n) + p["hc_b_res"]
    h_res = sinkhorn_one(r, int(cfg["hc_sinkhorn_iters"]),
                         float(cfg["hc_eps"]),
                         float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"]))
    return h_pre, h_post, h_res


def hyper(cfg, p, f, X):
    """``X [T, n, C]`` through the sub-layer ``f([T, C]) -> [T, C]`` wired
    by ``p``'s hyper-connection leaves."""
    h_pre, h_post, h_res = jax.vmap(lambda x: token_maps(cfg, p, x))(X)
    u = jnp.sum(h_pre[:, :, None] * X, axis=1)
    y = f(u)
    kept = jnp.einsum("tji,tic->tjc", h_res, X)
    return kept + h_post[:, :, None] * y[:, None, :]


# ----------------------------------------------------------------------
# latent attention under YaRN
# ----------------------------------------------------------------------
def yarn_inverse_frequencies(cfg):
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    rs = cfg.get("rope_scaling")
    out = []
    for i in range(d // 2):
        plain = 1.0 / theta ** (2.0 * i / d)
        if not rs:
            out.append(plain)
            continue
        L = float(rs["original_max_position_embeddings"])
        where = lambda beta: d * math.log(L / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
        low = max(math.floor(where(float(rs["beta_fast"]))), 0)
        high = min(math.ceil(where(float(rs["beta_slow"]))), d - 1)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        m = 1.0 - ramp
        out.append((1.0 - m) * plain / float(rs["factor"]) + m * plain)
    return jnp.asarray(out, F32)


def softmax_scale(cfg) -> float:
    rs = cfg.get("rope_scaling") or {}
    scale = 1.0 / math.sqrt(int(cfg["qk_nope_head_dim"])
                            + int(cfg["qk_rope_head_dim"]))
    if rs and float(rs["factor"]) > 1 and float(rs.get("mscale_all_dim", 0)):
        scale *= (0.1 * float(rs["mscale_all_dim"])
                  * math.log(float(rs["factor"])) + 1.0) ** 2
    return scale


def rotate(x, inv):
    """``x [T, d]``: rotary embedding on all ``d`` dims (rotate-half)."""
    T, d = x.shape
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))
    half = jnp.concatenate([-x[:, d // 2:], x[:, :d // 2]], -1)
    return x * cos + half * sin


def mla(cfg, p, x):
    """``x [T, H]`` (normed) -> ``[T, H]``, head by head."""
    T = x.shape[0]
    n = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    r_kv, eps = int(cfg["kv_lora_rank"]), float(cfg["rms_norm_eps"])
    inv, scale = yarn_inverse_frequencies(cfg), softmax_scale(cfg)
    c_q = norm(x @ p["q_a_proj"], p["q_a_norm"], eps)
    q = (c_q @ p["q_b_proj"]).reshape(T, n, dn + dr)
    kv_a = x @ p["kv_a_proj"]
    c_kv = norm(kv_a[:, :r_kv], p["kv_a_norm"], eps)
    k_rope = rotate(kv_a[:, r_kv:], inv)                   # [T, dr], shared
    kv = (c_kv @ p["kv_b_proj"]).reshape(T, n, dn + dv)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(h):
        qh = lax.dynamic_index_in_dim(q, h, 1, keepdims=False)
        kvh = lax.dynamic_index_in_dim(kv, h, 1, keepdims=False)
        q_h = jnp.concatenate([qh[:, :dn], rotate(qh[:, dn:], inv)], -1)
        k_h = jnp.concatenate([kvh[:, :dn], k_rope], -1)
        s = jnp.where(causal, q_h @ k_h.T * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ kvh[:, dn:]

    o = jnp.moveaxis(lax.map(head, jnp.arange(n)), 0, 1)     # [T, n, dv]
    return o.reshape(T, n * dv) @ p["o_proj"]


# ----------------------------------------------------------------------
# the expert layer
# ----------------------------------------------------------------------
def route(cfg, p, x):
    """``(weights [T, k], experts [T, k])`` over all routed experts."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, e = lax.top_k(s + lax.stop_gradient(p["router_bias"]),
                     int(cfg["num_experts_per_tok"]))
    w = jnp.take_along_axis(s, e, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * float(cfg["routed_scaling_factor"]), e


def experts(cfg, p, x):
    held, rank = int(cfg["experts_held"]), int(cfg["ep_rank"])
    w, e = route(cfg, p, x)

    def one(acc, j):
        wg, wu, wd = (lax.dynamic_index_in_dim(p[n], j, 0, keepdims=False)
                      for n in ("experts_gate", "experts_up", "experts_down"))
        mine = jnp.sum(jnp.where(e == rank * held + j, w, 0.0), -1)
        return acc + mine[:, None] * swiglu(x, wg, wu, wd), None

    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(x), jnp.arange(held))
    return y + swiglu(x, p["shared_gate_proj"], p["shared_up"],
                      p["shared_down"])


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def mixer_sub_layer(cfg, p, X):
    eps = float(cfg["rms_norm_eps"])
    return hyper(cfg, p, lambda u: mla(cfg, p, norm(u, p["norm"], eps)), X)


def ffn_sub_layer(cfg, p, X):
    eps = float(cfg["rms_norm_eps"])

    def f(u):
        un = norm(u, p["norm"], eps)
        if "router" in p:
            return experts(cfg, p, un)
        return swiglu(un, p["gate_proj"], p["up_proj"], p["down_proj"])

    return hyper(cfg, p, f, X)


def hidden(cfg: Dict[str, Any], params, ids):
    """``ids [T]`` -> the sum of the last layer's streams ``[T, C]``."""
    emb = params["embed"]["embedding"][ids]
    X = jnp.stack([emb] * int(cfg["hc_mult"]), axis=1)       # [T, n, C]
    for i in range(int(cfg["layers"])):
        kind = "mlp" if i < int(cfg["first_k_dense_replace"]) else "moe"
        X = jax.checkpoint(lambda p, X: mixer_sub_layer(cfg, p, X))(
            params[f"layer{i}_mixer"], X)
        X = jax.checkpoint(lambda p, X: ffn_sub_layer(cfg, p, X))(
            params[f"layer{i}_{kind}"], X)
    return jnp.sum(X, axis=1)


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]


def sequence_loss(cfg, params, ids, labels):
    """The loss of one sequence and ``{"logits"}``."""
    eps = float(cfg["rms_norm_eps"])
    h = hidden(cfg, params, ids)
    logits = norm(h, params["head"]["norm"], eps) @ params["head"]["kernel"]
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
