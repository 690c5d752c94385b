"""Plain reference for Qwen3-Next (``model_type qwen3_next``): forward,
loss and gradient of ONE sequence in float32 ``jax.numpy``.

Nothing here comes from the package under test (``get_path`` /
``set_path`` are ``fed_round.py``'s, the other plain reference).  Every matrix product
runs under ``jax.default_matmul_precision("highest")`` (callers use
:func:`loss_and_grad` / :func:`logits_and_loss`, which set it); without
it a TPU multiplies float32 operands in bfloat16 passes.

The equations, as published (Hugging Face ``Qwen3NextForCausalLM``,
config.json of Qwen/Qwen3-Next-80B-A3B-Instruct; Gated DeltaNet:
arXiv:2412.06464).  ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``.

* layer ``i``: ``h = x + Mixer(N1(x))``, ``out = h + MoE(N2(h))``;
  attention where ``(i + 1) % full_attention_interval == 0``, else
  Gated DeltaNet.
* gated attention: ``W_q x`` gives per head ``[q | gate]``; ``q, k`` are
  normed per head (``N`` over ``head_dim``), rotated on their first
  ``partial_rotary_factor * head_dim`` dimensions (rotate-half, theta
  ``rope_theta``); causal softmax of ``q . k / sqrt(head_dim)``, each KV
  head serving ``heads / kv_heads`` query heads; ``y = W_o (attn *
  sigmoid(gate))``.
* Gated DeltaNet: ``[q, k, v, z] = W_qkvz x``, ``[b, a] = W_ba x``;
  causal depthwise convolution (kernel 4, no bias) over ``[q, k, v]``
  then SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; ``q, k`` L2-normalised (eps 1e-6), ``q / sqrt(d_k)``, each
  key head repeated to ``n_v / n_k`` value heads; per head, token by
  token: ``S <- exp(g_t) S``, ``S <- S + k_t (x) beta_t (v_t - S^T
  k_t)``, ``o_t = S^T q_t``; ``y = W_out (w * o / rms(o) * silu(z))``.
* expert layer: ``p = softmax(W_r x)`` over all experts, top-k, weights
  renormalised to sum 1; expert ``E(x) = W_d (silu(W_g x) * W_u x)``;
  plus ``sigmoid(w_s . x) * E_shared(x)``.  This chip holds experts
  ``[ep_rank * experts_held, (ep_rank + 1) * experts_held)`` and adds
  only their terms (model-configs guide, section 4); the shared expert
  is whole.
* head: ``logits = W_head N(h)`` over the held vocabulary rows; mean
  next-token cross-entropy.

Departures from the released code (also in the configuration file):
multi-token prediction is absent from the config's keys and left out;
the column layout of ``in_proj_qkvz`` is ``[q | k | v | z]`` and of
``in_proj_ba`` ``[b | a]``, contiguous (random weights: any fixed layout
is the same model); no attention dropout, no router auxiliary loss.

To fit beside the trainer at the published widths the work is cut in
blocks that change no number: attention runs head by head, the
recurrence is rematerialised in runs of 64 steps, experts run one after
another over all tokens.
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
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def is_attention(cfg, i: int) -> bool:
    return (i + 1) % int(cfg["full_attention_interval"]) == 0


def rotate(x, theta: float, rot: int):
    """``x [T, heads, d]``: rotary embedding on the first ``rot`` dims."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


def attention(cfg, p, x):
    T = x.shape[0]
    nq, nkv, d = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    eps = float(cfg["rms_norm_eps"])
    qg = (x @ p["q_proj"]).reshape(T, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_proj"]).reshape(T, nkv, d)
    v = (x @ p["v_proj"]).reshape(T, nkv, d)
    rot = int(d * float(cfg["partial_rotary_factor"]))
    theta = float(cfg["rope_theta"])
    q = rotate(norm(q, p["q_norm"], eps), theta, rot)
    k = rotate(norm(k, p["k_norm"], eps), theta, rot)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(h):
        qh = lax.dynamic_index_in_dim(q, h, 1, keepdims=False)
        kh = lax.dynamic_index_in_dim(k, h // (nq // nkv), 1, keepdims=False)
        vh = lax.dynamic_index_in_dim(v, h // (nq // nkv), 1, keepdims=False)
        s = jnp.where(causal, qh @ kh.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    o = jnp.moveaxis(lax.map(head, jnp.arange(nq)), 0, 1)     # [T, nq, d]
    return (o * jax.nn.sigmoid(gate)).reshape(T, nq * d) @ p["o_proj"]


def delta_rule(q, k, v, g, beta, run: int = 64):
    """The recurrence for one head, token by token: ``q, k [T, d_k]``,
    ``v [T, d_v]``, ``g, beta [T]`` -> ``o [T, d_v]``."""
    T = q.shape[0]
    pad = (-T) % run

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt) * S
        S = S + jnp.outer(kt, bt * (vt - kt @ S))
        return S, qt @ S

    @jax.checkpoint
    def steps(S, xs):
        return lax.scan(step, S, xs)

    xs = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (T + pad) // run, run, *a.shape[1:]) for a in (q, k, v, g, beta))
    S0 = jnp.zeros((k.shape[-1], v.shape[-1]), F32)
    _, o = lax.scan(steps, S0, xs)
    return o.reshape(T + pad, -1)[:T]


def delta_net(cfg, p, x):
    T = x.shape[0]
    nk, nv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    kw = int(cfg["linear_conv_kernel_dim"])
    conv = 2 * nk * dk + nv * dv
    qkvz = x @ p["in_proj_qkvz"]
    qkv, z = qkvz[:, :conv], qkvz[:, conv:].reshape(T, nv, dv)
    ba = x @ p["in_proj_ba"]
    b, a = ba[:, :nv], ba[:, nv:]
    padded = jnp.pad(qkv, ((kw - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + T] * p["conv"][j] for j in range(kw)))
    q = qkv[:, :nk * dk].reshape(T, nk, dk)
    k = qkv[:, nk * dk:2 * nk * dk].reshape(T, nk, dk)
    v = qkv[:, 2 * nk * dk:].reshape(T, nv, dv)
    l2 = lambda t: t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    q = jnp.repeat(q, nv // nk, axis=1)
    k = jnp.repeat(k, nv // nk, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = jax.vmap(delta_rule, in_axes=(1, 1, 1, 1, 1), out_axes=1)(
        q, k, v, g, beta)                                      # [T, nv, dv]
    o = p["out_norm"] * o * lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + float(cfg["rms_norm_eps"]))
    return (o * jax.nn.silu(z)).reshape(T, nv * dv) @ p["out_proj"]


def experts(cfg, p, x):
    top_k = int(cfg["num_experts_per_tok"])
    held, rank = int(cfg["experts_held"]), int(cfg["ep_rank"])
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    w, e = lax.top_k(probs, top_k)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)

    def one(acc, j):
        wg, wu, wd = (lax.dynamic_index_in_dim(p[n], j, 0, keepdims=False)
                      for n in ("experts_gate", "experts_up", "experts_down"))
        mine = jnp.sum(jnp.where(e == rank * held + j, w, 0.0), -1)
        return acc + mine[:, None] * ((jax.nn.silu(x @ wg) * (x @ wu)) @ wd), None

    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(x), jnp.arange(held))
    shared = (jax.nn.silu(x @ p["shared_gate_proj"]) * (x @ p["shared_up"])) \
        @ p["shared_down"]
    return y + jax.nn.sigmoid(x @ p["shared_gate"])[:, None] * shared


def forward(cfg: Dict[str, Any], params, ids):
    """``ids [T]`` int32 -> ``logits [T, vocab_rows]``."""
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"]["embedding"][ids]
    for i in range(int(cfg["layers"])):
        pm, pe = params[f"layer{i}_mixer"], params[f"layer{i}_moe"]
        mixer = attention if is_attention(cfg, i) else delta_net
        h = x + mixer(cfg, pm, norm(x, pm["norm"], eps))
        x = h + experts(cfg, pe, norm(h, pe["norm"], eps))
    return norm(x, params["head"]["norm"], eps) @ params["head"]["kernel"]


def sequence_loss(cfg, params, ids, labels):
    """Mean next-token cross-entropy of one sequence, and its logits."""
    logits = forward(cfg, params, ids)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1)), logits


_GRAD_CACHE: Dict[Any, Any] = {}


def loss_and_grad(cfg, params, paths: Sequence[str], ids, labels):
    """``(loss, logits, [d loss / d leaf for the leaves at paths])`` of
    one sequence ``ids, labels [T]``."""
    key = (id(cfg), tuple(paths))
    if key not in _GRAD_CACHE:
        def f(leaves, params, ids, labels):
            for path, leaf in zip(paths, leaves):
                params = set_path(params, path, leaf)
            return sequence_loss(cfg, params, ids, labels)
        _GRAD_CACHE[key] = jax.jit(jax.value_and_grad(f, has_aux=True))
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = _GRAD_CACHE[key](
            [get_path(params, p) for p in paths], params, ids, labels)
    return loss, logits, grads
