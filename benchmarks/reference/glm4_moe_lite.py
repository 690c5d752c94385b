"""Plain reference for GLM-4.7-Flash (``model_type glm4_moe_lite``):
forward, the two-term loss and its gradient of ONE sequence in float32
``jax.numpy``.

Nothing here comes from the package under test (``get_path`` /
``set_path`` are ``fed_round.py``'s, the other plain reference).  Every
matrix product runs under ``jax.default_matmul_precision("highest")``
(callers use :func:`loss_and_grad`, which sets it); without it a TPU
multiplies float32 operands in bfloat16 passes.

The equations, as published (config.json of zai-org/GLM-4.7-Flash;
latent attention: DeepSeek-V2, arXiv:2405.04434, section 2.1; routing
and multi-token prediction: DeepSeek-V3, arXiv:2412.19437, sections
2.1.2 and 2.2, which the family follows).  ``N(x) = x / sqrt(mean(x^2)
+ eps) * w``.

* layer ``l``: ``h = x + MLA(N1(x))``, ``out = h + FFN(N2(h))``; the FFN
  is ``W_d (silu(W_g x) * W_u x)`` of ``intermediate_size`` where ``l <
  first_k_dense_replace``, else the expert layer.
* MLA, ``n`` heads: ``c_q = N(W_qa x)``, ``[q_nope_h | q_rope_h] = W_qb
  c_q``; ``[c_kv | k_rope] = W_kva x``, ``c_kv = N(c_kv)`` (``k_rope``
  is not normed), ``[k_nope_h | v_h] = W_kvb c_kv``; rotary (theta
  ``rope_theta``, every one of the ``qk_rope_head_dim`` dims) on
  ``q_rope_h`` and on the one ``k_rope`` all heads share; ``q_h =
  [q_nope_h | q_rope_h]``, ``k_h = [k_nope_h | k_rope]``; causal softmax
  of ``q_h . k_h / sqrt(qk_nope_head_dim + qk_rope_head_dim)``; ``y =
  W_o [o_1 .. o_n]``; no biases.
* expert layer: ``s = sigmoid(W_r x)`` over all routed experts; the
  top-k are chosen by ``s + b`` (``topk_method noaux_tc``, one group);
  ``w = s[chosen]``, ``w /= sum(w) + 1e-20`` (``norm_topk_prob``), ``w
  *= routed_scaling_factor``; expert ``E(x) = W_d (silu(W_g x) * W_u
  x)``; plus the shared expert, ungated.  This chip holds experts
  ``[ep_rank * experts_held, (ep_rank + 1) * experts_held)`` and adds
  only their terms (model-configs guide, section 4); the shared expert
  is whole.
* head: ``logits = W_head N(h)`` over the held vocabulary rows.
* MTP layer: ``h'_i = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)]`` with ``h_i``
  the last layer's output (before the final norm), one whole layer (MLA
  and expert layer, positions as in the main model), ``logits'_i =
  W_head N'(out_i)`` for ``t_{i+2}`` through the main model's embedding
  and head.
* loss of a sequence: mean next-token cross-entropy over ``T`` plus
  ``mtp_loss_weight`` times the mean of the MTP cross-entropy over the
  ``T - 1`` positions that have a second-next token.

Departures and assumptions (also in the configuration file): the MTP
term's weight is not in the config (0.1); the concatenation's order is
``[embedding ; hidden]``; the rotary layout is rotate-half, contiguous
(the released code interleaves; with seeded weights any fixed layout is
the same model); the selection bias is seeded; no dropout, no router
auxiliary loss; latent attention in its expanded form (no absorbed
projections, no compressed cache: those are serving's).

To fit beside the trainer at the published widths the work is cut in
blocks that change no number: attention runs head by head, experts run
one after another over all tokens, and each layer is rematerialised in
the backward pass.
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


def rotate(x, theta: float):
    """``x [T, d]``: rotary embedding on all ``d`` dims (rotate-half)."""
    T, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))
    half = jnp.concatenate([-x[:, d // 2:], x[:, :d // 2]], -1)
    return x * cos + half * sin


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def mla(cfg, p, x):
    """``x [T, H]`` (normed) -> ``[T, H]``, head by head."""
    T = x.shape[0]
    n = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    r_kv, eps = int(cfg["kv_lora_rank"]), float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    c_q = norm(x @ p["q_a_proj"], p["q_a_norm"], eps)
    q = (c_q @ p["q_b_proj"]).reshape(T, n, dn + dr)
    kv_a = x @ p["kv_a_proj"]
    c_kv = norm(kv_a[:, :r_kv], p["kv_a_norm"], eps)
    k_rope = rotate(kv_a[:, r_kv:], theta)                 # [T, dr], shared
    kv = (c_kv @ p["kv_b_proj"]).reshape(T, n, dn + dv)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(h):
        qh = lax.dynamic_index_in_dim(q, h, 1, keepdims=False)
        kvh = lax.dynamic_index_in_dim(kv, h, 1, keepdims=False)
        q_h = jnp.concatenate([qh[:, :dn], rotate(qh[:, dn:], theta)], -1)
        k_h = jnp.concatenate([kvh[:, :dn], k_rope], -1)
        s = jnp.where(causal, q_h @ k_h.T / math.sqrt(dn + dr), -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ kvh[:, dn:]

    o = jnp.moveaxis(lax.map(head, jnp.arange(n)), 0, 1)     # [T, n, dv]
    return o.reshape(T, n * dv) @ p["o_proj"]


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


def layer(cfg, pm, pf, x):
    eps = float(cfg["rms_norm_eps"])
    h = x + mla(cfg, pm, norm(x, pm["norm"], eps))
    hn = norm(h, pf["norm"], eps)
    if "router" in pf:
        return h + experts(cfg, pf, hn)
    return h + swiglu(hn, pf["gate_proj"], pf["up_proj"], pf["down_proj"])


def hidden(cfg: Dict[str, Any], params, ids):
    """``ids [T]`` -> the last layer's output ``[T, H]``."""
    x = params["embed"]["embedding"][ids]
    for i in range(int(cfg["layers"])):
        kind = "mlp" if i < int(cfg["first_k_dense_replace"]) else "moe"
        x = jax.checkpoint(lambda pm, pf, x: layer(cfg, pm, pf, x))(
            params[f"layer{i}_mixer"], params[f"layer{i}_{kind}"], x)
    return x


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]


def mtp_logits(cfg, params, h, nxt):
    """The MTP layer: ``h [T, H]`` the last layer's output, ``nxt [T]``
    the next ids -> logits ``[T, V]`` for the second-next ids."""
    eps = float(cfg["rms_norm_eps"])
    pm, pe = params["mtp_mixer"], params["mtp_moe"]
    emb = params["embed"]["embedding"][nxt]
    both = jnp.concatenate([norm(emb, pm["enorm"], eps),
                            norm(h, pm["hnorm"], eps)], -1)
    out = jax.checkpoint(lambda pm, pe, x: layer(cfg, pm, pe, x))(
        pm, pe, both @ pm["eh_proj"])
    return norm(out, pe["head_norm"], eps) @ params["head"]["kernel"]


def sequence_loss(cfg, params, ids, labels):
    """The loss of one sequence and ``{"logits", "next_token_loss",
    "mtp_loss"}``."""
    eps = float(cfg["rms_norm_eps"])
    h = hidden(cfg, params, ids)
    logits = norm(h, params["head"]["norm"], eps) @ params["head"]["kernel"]
    nxt = jnp.mean(cross_entropy(logits, labels))
    mtp = jnp.float32(0.0)
    if int(cfg.get("num_nextn_predict_layers", 0)):
        # position i knows t_{i+1} = labels[i]; its target t_{i+2} is
        # labels[i + 1], which the last position lacks
        per = cross_entropy(mtp_logits(cfg, params, h, labels)[:-1],
                            labels[1:])
        mtp = jnp.sum(per) / max(len(ids) - 1, 1)
    loss = nxt + float(cfg["mtp_loss_weight"]) * mtp
    return loss, {"logits": logits, "next_token_loss": nxt, "mtp_loss": mtp}


_GRAD_CACHE: Dict[Any, Any] = {}


def loss_and_grad(cfg, params, paths: Sequence[str], ids, labels):
    """``(loss, {"logits", "next_token_loss", "mtp_loss"}, [d loss / d
    leaf for the leaves at paths])`` of one sequence ``ids, labels
    [T]``."""
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
