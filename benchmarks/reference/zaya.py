"""Plain reference for ZAYA1-8B (``model_type zaya``): forward, loss and
gradient of ONE sequence in float32 ``jax.numpy``.

Nothing here comes from the package under test (``get_path`` /
``set_path`` are ``fed_round.py``'s, the other plain reference).  Every
matrix product runs under ``jax.default_matmul_precision("highest")``
(callers use :func:`loss_and_grad`, which sets it); without it a TPU
multiplies float32 operands in bfloat16 passes.

The equations (config.json of Zyphra/ZAYA1-8B for the shapes; compressed
convolutional attention: arXiv:2510.04476; the router, the residual
scaling and the tied head: arXiv:2511.17127).  ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``; ``H`` hidden, ``d`` head width, ``n_q``
query heads, ``n_kv`` key/value heads, ``rep = n_q / n_kv``; every layer
is an attention sub-layer, then an expert sub-layer; ``h_0 = Emb[ids]``.

* residual merge, both sub-layers: ``h' = (s_r * h + b_r) + (s_o *
  F(N(h; w)) + b_o)`` with four vectors ``[H]``.
* CCA mixer ``F_attn(x)``, ``x = N(h; w_attn)``:

  1. ``q~ = x W_q [T, n_q, d]``, ``k~ = x W_k [T, n_kv, d]``, ``v = [x
     W_v1 ; shift(x) W_v2] [T, n_kv, d]``, ``shift(x)_t = x_{t-1}``,
     ``shift(x)_0 = 0``: the first half of the key/value heads hold the
     current token's values, the second half the previous token's.
  2. ``z = [q~ ; k~] [T, n_q + n_kv, d]``; ``z1_t = sum_j c0[j] *
     z_{t - (K0 - 1) + j} + beta0`` (depthwise, zeros before the start);
     ``z2_t = sum_j z1_{t - (K1 - 1) + j} C1[j] + beta1`` (one ``d x d``
     matrix a head and tap); ``[q_c ; k_c] = z2``.  No activation.
  3. ``m_q[:, rep g + r] = (q~[:, rep g + r] + k~[:, g]) / 2``; ``m_k[:,
     g] = mean_r m_q[:, rep g + r]``; ``q = q_c + m_q``, ``k = k_c +
     m_k``.
  4. ``q <- q / sqrt(mean(q^2) + eps)`` (that is ``sqrt(d) q / |q|``),
     ``k <- tau_g k / sqrt(mean(k^2) + eps)``, per head and token.
  5. rotary on the first ``partial_rotary_factor d`` dims of every query
     and key head (rotate-half over contiguous halves), theta
     ``rope_parameters.hybrid.rope_theta``, positions from 0.
  6. ``o = causal_softmax(q k^T / sqrt(d)) v``, query head ``rep g + r``
     on key/value head ``g``.
  7. ``F_attn = o.reshape(T, n_q d) W_o``.

* expert sub-layer ``F_moe(x)``, ``x = N(h; w_moe)``, with the previous
  layer's router state ``s_{l-1} [T, D_r]`` (zeros before layer 0):

  1. ``r = x W_d + b_d + gamma * s_{l-1}``; ``s_l = r``; ``u = N(r;
     w_s)``; ``logits = W_3 gelu(W_2 gelu(W_1 u + b_1) + b_2) + b_3``
     (the exact GELU).
  2. ``p = softmax(logits)``; ``e = argmax(p + b_bal)``; the weight is
     ``p_e``: no bias, not renormalised.
  3. ``F_moe = p_e W_down_e (silu(W_gate_e x) * W_up_e x)`` where ``e``
     is one of the experts ``[ep_rank * experts_held, (ep_rank + 1) *
     experts_held)`` this chip holds, else 0.

* head: ``logits = N(h_L; w_f) Emb^T`` over the held vocabulary rows; the
  loss of a sequence is the mean next-token cross-entropy.

Departures and assumptions (also in the configuration file): the
placements above where the papers leave them open (convolutions on
queries and keys together, biases, no activation; the mean taken before
the convolutions; the value shift by heads; the router reads the normed
input and its state is the sum before the norm); no mixture-of-depths
skip choice; no auxiliary loss, no update of the balancing bias, no
dropout.

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


def delayed(x, steps: int):
    """``x [T, ...]`` moved ``steps`` later along ``T``, zeros in front."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:steps]), x[:-steps]], axis=0)


def rotate(x, rot: int, theta: float):
    """``x [T, d]``: rotary embedding on the first ``rot`` dims."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))
    xr, rest = x[:, :rot], x[:, rot:]
    half = jnp.concatenate([-xr[:, rot // 2:], xr[:, :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


# ----------------------------------------------------------------------
# compressed convolutional attention
# ----------------------------------------------------------------------
def convolutions(cfg, p, z):
    """``z [T, heads, d]`` through the depthwise and the per-head causal
    convolution, as explicit shifted sums."""
    T, heads, d = z.shape
    k0, k1 = int(cfg["cca_time0"]), int(cfg["cca_time1"])
    flat = z.reshape(T, heads * d)
    z1 = p["conv0_bias"][None, :]
    for j in range(k0):
        z1 = z1 + p["conv0"][j][None, :] * delayed(flat, k0 - 1 - j)
    z1 = z1.reshape(T, heads, d)
    z2 = p["conv1_bias"].reshape(1, heads, d)
    for j in range(k1):
        z2 = z2 + jnp.einsum("thd,hde->the", delayed(z1, k1 - 1 - j),
                             p["conv1"][j])
    return z2


def cca(cfg, p, x):
    """``x [T, H]`` (normed) -> ``[T, H]``, head by head."""
    T = x.shape[0]
    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, rep, eps = int(cfg["head_dim"]), nq // nkv, float(cfg["rms_norm_eps"])
    rot = int(d * float(cfg["partial_rotary_factor"]))
    theta = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    q0 = (x @ p["q_proj"]).reshape(T, nq, d)
    k0 = (x @ p["k_proj"]).reshape(T, nkv, d)
    v = jnp.concatenate([x @ p["v1_proj"], delayed(x, 1) @ p["v2_proj"]],
                        axis=-1).reshape(T, nkv, d)
    z = convolutions(cfg, p, jnp.concatenate([q0, k0], axis=1))
    q_c, k_c = z[:, :nq], z[:, nq:]
    m_q = jnp.stack([(q0[:, h] + k0[:, h // rep]) / 2.0 for h in range(nq)],
                    axis=1)
    m_k = jnp.stack([sum(m_q[:, g * rep + r] for r in range(rep)) / rep
                     for g in range(nkv)], axis=1)
    q, k = q_c + m_q, k_c + m_k
    unit = lambda t: t * lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps)
    q = unit(q)
    k = unit(k) * p["temperature"][None, :, None]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(h):
        g = h // rep
        q_h = rotate(lax.dynamic_index_in_dim(q, h, 1, keepdims=False), rot,
                     theta)
        k_h = rotate(lax.dynamic_index_in_dim(k, g, 1, keepdims=False), rot,
                     theta)
        v_h = lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        s = jnp.where(causal, q_h @ k_h.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v_h

    o = jnp.moveaxis(lax.map(head, jnp.arange(nq)), 0, 1)    # [T, nq, d]
    return o.reshape(T, nq * d) @ p["o_proj"]


# ----------------------------------------------------------------------
# the expert layer
# ----------------------------------------------------------------------
def router(cfg, p, x, state):
    """``(p_e [T], e [T], this layer's state [T, D_r])``."""
    gelu = lambda a: jax.nn.gelu(a, approximate=False)
    r = x @ p["router_down"] + p["router_down_bias"] \
        + p["router_state_scale"] * state
    u = norm(r, p["router_norm"], float(cfg["rms_norm_eps"]))
    u = gelu(u @ p["router_fc1"] + p["router_fc1_bias"])
    u = gelu(u @ p["router_fc2"] + p["router_fc2_bias"])
    probs = jax.nn.softmax(u @ p["router_out"] + p["router_out_bias"], -1)
    e = jnp.argmax(probs + lax.stop_gradient(p["router_bias"]), axis=-1)
    return jnp.take_along_axis(probs, e[:, None], -1)[:, 0], e, r


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def experts(cfg, p, x, state):
    """``(F_moe [T, H], this layer's state)``: the held experts one
    after another over all tokens under a mask."""
    held, rank = int(cfg["experts_held"]), int(cfg["ep_rank"])
    if int(cfg["num_experts_per_tok"]) != 1:
        raise ValueError("the reference routes one expert a token")
    w, e, state = router(cfg, p, x, state)

    def one(acc, j):
        wg, wu, wd = (lax.dynamic_index_in_dim(p[n], j, 0, keepdims=False)
                      for n in ("experts_gate", "experts_up", "experts_down"))
        mine = jnp.where(e == rank * held + j, w, 0.0)
        return acc + mine[:, None] * swiglu(x, wg, wu, wd), None

    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(x), jnp.arange(held))
    return y, state


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def merge(p, h, y):
    return (p["res_scale"] * h + p["res_bias"]) \
        + (p["out_scale"] * y + p["out_bias"])


def mixer_sub_layer(cfg, p, h):
    return merge(p, h, cca(cfg, p, norm(h, p["norm"],
                                        float(cfg["rms_norm_eps"]))))


def expert_sub_layer(cfg, p, h, state):
    y, state = experts(cfg, p, norm(h, p["norm"], float(cfg["rms_norm_eps"])),
                       state)
    return merge(p, h, y), state


def hidden(cfg: Dict[str, Any], params, ids):
    """``ids [T]`` -> the last layer's output ``[T, H]``."""
    h = params["embed"]["embedding"][ids]
    state = jnp.zeros((ids.shape[0], int(cfg["router_hidden_size"])), F32)
    for i in range(int(cfg["layers"])):
        h = jax.checkpoint(lambda p, h: mixer_sub_layer(cfg, p, h))(
            params[f"layer{i}_mixer"], h)
        h, state = jax.checkpoint(
            lambda p, h, s: expert_sub_layer(cfg, p, h, s))(
                params[f"layer{i}_moe"], h, state)
    return h


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]


def sequence_loss(cfg, params, ids, labels):
    """The loss of one sequence and ``{"logits"}``."""
    h = hidden(cfg, params, ids)
    logits = norm(h, params["final_norm"]["norm"],
                  float(cfg["rms_norm_eps"])) @ params["embed"]["embedding"].T
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
