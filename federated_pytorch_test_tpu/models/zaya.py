"""ZAYA1-8B decoder (``model_type zaya``): attention that runs wholly
inside a compressed space with convolutions on queries and keys (CCA),
an expert-only FFN whose top-1 router is an MLP with a state carried
from layer to layer, learned residual scales, and an embedding that is
also the head's matrix.

Source: https://huggingface.co/Zyphra/ZAYA1-8B config.json; compressed
convolutional attention: arXiv:2510.04476; the router, the residual
scaling and the tied head: the ZAYA1 report, arXiv:2511.17127.  The
equations (``N`` the plain RMS norm, ``d`` the head width, ``rep =
num_attention_heads / num_key_value_heads``)::

    merge:  h' = (s_r * h + b_r) + (s_o * F(N(h)) + b_o)     both sub-layers
    CCA:    q~ = x W_q, k~ = x W_k, v = [x W_v1 ; shift(x) W_v2]  (by heads)
            [q_c ; k_c] = conv1(conv0([q~ ; k~]))   causal: depthwise, then
                                                    one d x d matrix a head
            q = q_c + m_q,  m_q[g, r] = (q~[g, r] + k~[g]) / 2
            k = k_c + m_k,  m_k[g] = mean_r m_q[g, r]
            q, k to sqrt(d) on the unit sphere, k times tau_g; rotary on
            the first partial_rotary_factor d dims; causal softmax core
            at 1 / sqrt(d); W_o from the compressed width
    router: r = x W_d + b_d + gamma * s_prev;  s = r  (to the next layer)
            logits = W_3 gelu(W_2 gelu(W_1 N(r) + b_1) + b_2) + b_3
            p = softmax(logits); e = argmax(p + b_bal); weight p_e
    head:   logits = N(h_L) Emb^T

are written out in ``benchmarks/reference/zaya.py``, which this file is
compared with.  Matrix products run in ``dtype`` (bfloat16 on the chip)
with float32 sums, the per-head convolution's among them; parameters,
norms, the depthwise taps, the unit-sphere norm, the whole router (at
``HIGHEST``) and the loss are float32.  Each sub-layer is rematerialised
in the backward pass (``jax.checkpoint``), the mixer sequence by
sequence; the head's loss runs sequence by sequence and takes its
inputs' gradient in the forward pass (``ops/head_loss.py``).

The expert layer is told which experts it holds (``experts_held`` of
``num_experts`` from ``ep_rank * experts_held``): it routes over all of
them and adds only its own experts' terms (``models/decoder.py:
held_experts``, ``ops/moe.py``).  The mixture-of-depths "skip" choice
some models of the family have is not built: the config has as many
router outputs as experts and no key for it.

Blocks, from the layer list: ``0`` the tied embedding (gathered at the
bottom, the head's matrix at the top: its gradient has two sources, and
there is no head block), ``1 + 2l`` layer ``l``'s CCA block (norm,
projections, both convolutions, temperatures, its four residual-scale
leaves), ``2 + 2l`` its expert block (norm, held experts, its four
residual-scale leaves), last the final norm alone.  The router's eleven
leaves come FIRST in an expert block's spec and lie in no block, for the
reason ``models/qwen3_next.py`` gives: one expert-parallel rank has its
own share of the router's gradient only; the balancing bias is a buffer
no gradient reaches in a deployment either.  The router's state makes an
upstream block's gradient pass through every later router all the same.

The balancing bias is seeded small and nothing here updates it;
:func:`router_balance` sets it by the load of a batch as a deployment's
update would have, for a benchmark whose seeded weights stand for
trained ones.

``aux`` holds the routing counts summed over the expert layers,
``moe_weight_sum`` (the local pairs' weights, summed) and
``router_state_rms`` (of the last layer's state).
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
    apply_rope,
    head_losses,
    held_experts,
    moe_aux,
    rms_norm,
    rope_tables,
    routing_counts,
    tied_head_logits,
)
from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops import moe as moelib
from federated_pytorch_test_tpu.ops.flash_attention import (
    causal_attention,
    plan as attn_plan,
)

_HI = jax.lax.Precision.HIGHEST
#: the leaves of an expert block's spec that are the router's
ROUTER_LEAVES = 11


def _around(mean, scale):
    return lambda key, shape, dtype=_F32: mean + scale * jax.random.normal(
        key, shape, dtype)


def _const(value):
    return lambda key, shape, dtype=_F32: jnp.full(shape, value, dtype)


class Zaya(BlockModule):
    """``__call__(ids [B, T] int32) -> (logits [B, T, vocab_rows] f32,
    aux)``; with ``labels [B, T]`` ``(loss per sequence [B], aux)``."""

    hidden_size: int = 2048
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    #: the config's group; ``hybrid`` is every layer's type
    rope_parameters: Any = None
    rms_norm_eps: float = 1e-5
    moe_intermediate_size: int = 2048
    num_experts: int = 16
    num_experts_per_tok: int = 1
    router_hidden_size: int = 256
    # the cut: layers kept, this chip's share of experts and vocabulary
    layers: int = 6
    experts_held: int = 8
    ep_rank: int = 0
    vocab_rows: int = 32784
    #: rows of the sorted pair buffer as a multiple of the mean count:
    #: 2 x the mean is every pair that can exist (one choice a token,
    #: half the experts held)
    pair_rows_factor: float = 2.0
    # how the model is seeded (assumed; the configuration file says why)
    init_scale: float = 0.02
    embed_scale: float = 1.0
    bias_scale: float = 0.01          # the balancing bias
    scale_spread: float = 0.1         # s_r, s_o, tau, gamma around 1
    attn_block: int = 512
    dtype: Any = jnp.bfloat16

    # -- the layer list and the blocks made from it ---------------------
    def block_names(self) -> List[str]:
        names = ["embed"]
        for i in range(self.layers):
            names += [f"layer{i}_mixer", f"layer{i}_moe"]
        return names + ["final_norm"]

    def block_kinds(self) -> List[str]:
        """``embed`` / ``cca`` / ``moe`` / ``norm`` per block."""
        return ["embed"] + ["cca", "moe"] * self.layers + ["norm"]

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def k_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def rope_theta(self) -> float:
        group = (self.rope_parameters or {}).get("hybrid", {})
        return float(group.get("rope_theta", 5e6))

    def attn_impl(self, tokens: int) -> str:
        """What runs the attention core for a sequence of ``tokens`` here
        ("pallas" | "pallas_interpret" | "xla": ``ops/flash_attention.py:
        plan``)."""
        nkv = self.num_key_value_heads
        return attn_plan(tokens, nkv, self.num_attention_heads // nkv,
                         self.head_dim, self.dtype)["impl"]

    def impl_fields(self, tokens: int) -> Dict[str, str]:
        """The round record's fields that name this backend's
        implementations for sequences of ``tokens``."""
        return {"attn_impl": self.attn_impl(tokens), "head_impl": HEAD_IMPL}

    def router_balance(self, params, ids):
        """``params``' balancing biases set by the load of ``ids [B,
        T]``: :func:`router_balance`."""
        return router_balance(self, params, ids)

    # -- parameters --------------------------------------------------------
    def _merge_spec(self):
        H = self.hidden_size
        near_one, small = _around(1.0, self.scale_spread), \
            _normal(self.init_scale)
        return (("res_scale", (H,), near_one), ("res_bias", (H,), small),
                ("out_scale", (H,), near_one), ("out_bias", (H,), small))

    def _spec(self, name: str):
        H, s, d = self.hidden_size, _normal(self.init_scale), self.head_dim
        if name == "embed":
            return (("embedding", (self.vocab_rows, H),
                     _normal(self.embed_scale)),)
        if name == "final_norm":
            # the embedding is the head's matrix too: at 1 / sqrt(H) the
            # normed vector has unit length and the logits unit scale
            return (("norm", (H,), _const(H ** -0.5)),)
        if name.endswith("_moe"):
            Dr, E, F = self.router_hidden_size, self.experts_held, \
                self.moe_intermediate_size
            wide = _normal(Dr ** -0.5)
            return (("router_down", (H, Dr), _normal(H ** -0.5)),
                    ("router_down_bias", (Dr,), s),
                    ("router_state_scale", (Dr,),
                     _around(1.0, self.scale_spread)),
                    ("router_norm", (Dr,), _ONES),
                    ("router_fc1", (Dr, Dr), wide),
                    ("router_fc1_bias", (Dr,), s),
                    ("router_fc2", (Dr, Dr), wide),
                    ("router_fc2_bias", (Dr,), s),
                    ("router_out", (Dr, self.num_experts), wide),
                    ("router_out_bias", (self.num_experts,), s),
                    ("router_bias", (self.num_experts,),
                     _normal(self.bias_scale)),
                    ("norm", (H,), _ONES),
                    ("experts_gate", (E, H, F), s),
                    ("experts_up", (E, H, F), s),
                    ("experts_down", (E, F, H), s)) + self._merge_spec()
        Lq, Lk = self.q_width, self.k_width
        heads = self.num_attention_heads + self.num_key_value_heads
        k0, k1 = self.cca_time0, self.cca_time1
        return (("norm", (H,), _ONES),
                ("q_proj", (H, Lq), s), ("k_proj", (H, Lk), s),
                ("v1_proj", (H, Lk // 2), s), ("v2_proj", (H, Lk // 2), s),
                ("conv0", (k0, Lq + Lk), _normal(k0 ** -0.5)),
                ("conv0_bias", (Lq + Lk,), s),
                ("conv1", (k1, heads, d, d), _normal((k1 * d) ** -0.5)),
                ("conv1_bias", (Lq + Lk,), s),
                ("temperature", (self.num_key_value_heads,),
                 _around(1.0, self.scale_spread)),
                ("o_proj", (Lq, H), s)) + self._merge_spec()

    def param_order(self) -> List[str]:
        return [f"{b}/{leaf}" for b in self.block_names()
                for leaf, _, _ in self._spec(b)]

    def train_order_block_ids(self) -> List[List[int]]:
        """Inclusive index ranges into ``param_order()``.  An expert
        block's range starts AFTER its router's leaves (the first
        :data:`ROUTER_LEAVES` of its spec), which therefore lie in no
        block: see the module's note on the router."""
        out, lo = [], 0
        for b in self.block_names():
            n = len(self._spec(b))
            out.append([lo + (ROUTER_LEAVES if b.endswith("_moe") else 0),
                        lo + n - 1])
            lo += n
        return out

    # -- forward ---------------------------------------------------------
    @nn.compact
    def __call__(self, ids, labels=None):
        """With ``labels [B, T]``: each sequence's loss ``[B]`` in place
        of the logits (sequence by sequence, so only one sequence's
        float32 logits are alive at a time)."""
        if self.num_key_value_heads % 2:
            raise ValueError(
                f"num_key_value_heads {self.num_key_value_heads}: half the "
                "value heads are the previous token's, so the count is even")
        p = {b: _Leaves(self._spec(b), name=b)() for b in self.block_names()}
        return forward(self, p, ids, labels)


def shift(x):
    """``x [T, ...]`` one step later along ``T``: row ``t`` holds row
    ``t - 1``, row 0 zeros."""
    return jnp.pad(x, ((1, 0),) + ((0, 0),) * (x.ndim - 1))[:-1]


def causal_taps(z, taps, tap):
    """``sum_j tap(z_{t - (taps - 1) + j}, j)`` over ``z [T, ...]`` with
    zeros before the sequence's start: taps oldest first."""
    T = z.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0),) + ((0, 0),) * (z.ndim - 1))
    return sum(tap(padded[j:j + T], j) for j in range(taps))


def cca_mix(cfg: Zaya, p, q0, k0):
    """Steps 2 and 3: ``q0 [T, nq, d]``, ``k0 [T, nkv, d]`` (the
    projections) through both causal convolutions, the means of the
    projections added back; ``-> (q [T, nq, d], k [T, nkv, d])``."""
    T, nq, d = q0.shape
    nkv = k0.shape[1]
    z = jnp.concatenate([q0, k0], axis=1)                   # [T, heads, d]
    z = causal_taps(z.reshape(T, -1), cfg.cca_time0,
                    lambda a, j: a * p["conv0"][j]) + p["conv0_bias"]
    z = z.reshape(T, nq + nkv, d)
    # one d x d product a head: heads lead the product (the batch axis
    # first is the one form the CPU multiplies two-byte operands in)
    per_head = lambda a, j: jnp.swapaxes(jnp.einsum(
        "htd,hde->hte", _op(jnp.swapaxes(a, 0, 1), cfg.dtype),
        _op(p["conv1"][j], cfg.dtype), preferred_element_type=_F32), 0, 1)
    z = causal_taps(z, cfg.cca_time1, per_head) \
        + p["conv1_bias"].reshape(nq + nkv, d)
    m_q = 0.5 * (q0.reshape(T, nkv, nq // nkv, d) + k0[:, :, None, :])
    return z[:, :nq] + m_q.reshape(T, nq, d), \
        z[:, nq:] + jnp.mean(m_q, axis=2)


def cca_attention(cfg: Zaya, p, x):
    """``x [T, H]`` (already normed) -> ``[T, q_width]`` through ``W_o``
    -> ``[T, H]``: compressed convolutional attention of one sequence."""
    T = x.shape[0]
    nq, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    # the scopes alternate so that the operations keep their order
    with scope("attn_proj_in"):
        q0 = _mm(cfg, x, p["q_proj"]).reshape(T, nq, d)
        k0 = _mm(cfg, x, p["k_proj"]).reshape(T, nkv, d)
        v_now = _mm(cfg, x, p["v1_proj"])
        # shift(x) W = shift(x W): a zero row gives a zero row
        v_before = _mm(cfg, x, p["v2_proj"])
    with scope("cca_mix"):
        # the value shift is by heads: the first half of the key/value
        # heads hold the current token's values, the second half the
        # previous token's
        v = jnp.concatenate([v_now, shift(v_before)], -1).reshape(T, nkv, d)
        q, k = cca_mix(cfg, p, q0, k0)
    with scope("attn_norm_rope"):
        # sqrt(d) x / |x| is x over its root mean square: the plain norm
        # with no weight, and with the temperature as the keys'
        q = rms_norm(q, 1.0, cfg.rms_norm_eps)
        k = rms_norm(k, p["temperature"][:, None], cfg.rms_norm_eps)
        cos, sin = rope_tables(T, int(d * cfg.partial_rotary_factor),
                               cfg.rope_theta())
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        q = q.reshape(T, nkv, nq // nkv, d) * (1.0 / math.sqrt(d))
    with scope("cca_core"):
        o = causal_attention(q, k, v, dtype=cfg.dtype, block=cfg.attn_block,
                             scope="cca_attn/cca_core")
    with scope("attn_proj_out"):
        return _mm(cfg, o.reshape(T, nq * d), p["o_proj"])


def router_logits(cfg: Zaya, p, x, state):
    """``x [T, H]`` (already normed), ``state [T, D_r]`` the previous
    layer's router state or None (zeros) ``-> (logits [T, num_experts],
    this layer's state [T, D_r])``, all float32 at ``HIGHEST``."""
    dot = lambda a, w: jnp.dot(a, w, precision=_HI)
    gelu = lambda a: jax.nn.gelu(a, approximate=False)
    r = dot(x, p["router_down"]) + p["router_down_bias"]
    if state is not None:
        r = r + p["router_state_scale"] * state
    u = rms_norm(r, p["router_norm"], cfg.rms_norm_eps)
    u = gelu(dot(u, p["router_fc1"]) + p["router_fc1_bias"])
    u = gelu(dot(u, p["router_fc2"]) + p["router_fc2_bias"])
    return dot(u, p["router_out"]) + p["router_out_bias"], r


def expert_layer(cfg: Zaya, p, x, state):
    """``x [T, H]`` (already normed) -> ``([T, H], routing, this layer's
    router state)``: the MLP router over all experts, the one expert a
    token by probability + balancing bias, its probability as the weight,
    the held experts' terms."""
    with scope("moe_route"):
        with scope("route_mlp"):
            logits, state = router_logits(cfg, p, x, state)
        with scope("route_scores"):
            w, e = moelib.softmax_bias_router_weights(
                logits, p["router_bias"], cfg.num_experts_per_tok)
    y, r = held_experts(cfg, p, x, w, e, cfg.num_experts)
    return y, r, state


def merge(p, h, y):
    """The scaled residual merge of a sub-layer's input ``h`` and output
    ``y``."""
    with scope("res_scale"):
        return (p["res_scale"] * h + p["res_bias"]) \
            + (p["out_scale"] * y + p["out_bias"])


def mixer_sub_layer(cfg: Zaya, pm, x):
    """``x [B, T, H]`` through a CCA sub-layer with the leaves ``pm``,
    sequence by sequence, each rematerialised in the backward pass."""
    @jax.checkpoint
    def mix(xt):
        with scope("cca_attn"):
            with scope("sublayer_norm"):
                xn = rms_norm(xt, pm["norm"], cfg.rms_norm_eps)
            y = cca_attention(cfg, pm, xn)
        return merge(pm, xt, y)

    with scope("sublayer_mixer"):
        return jax.lax.map(mix, x)


def expert_sub_layer(cfg: Zaya, pf, h, state):
    """``h [B, T, H]`` and the previous layer's router state ``[B * T,
    D_r]`` (None before the first layer) through an expert sub-layer with
    the leaves ``pf``; ``-> (out, state, routing counts, the local pairs'
    weights summed)``."""
    B, T, H = h.shape

    @jax.checkpoint
    def ffn(h, state):
        # tokens are independent here: one batch of B * T
        with scope("sublayer_norm"):
            flat = rms_norm(h, pf["norm"], cfg.rms_norm_eps).reshape(B * T, H)
        y, r, state = expert_layer(cfg, pf, flat, state)
        return merge(pf, h, y.reshape(B, T, H)), state, routing_counts(r), \
            jnp.sum(r.weight)

    with scope("sublayer_ffn"):
        return ffn(h, state)


def decoder_layer(cfg: Zaya, pm, pf, x, state):
    """One layer: CCA with ``pm``, then the expert layer with ``pf``."""
    return expert_sub_layer(cfg, pf, mixer_sub_layer(cfg, pm, x), state)


def router_balance(cfg: Zaya, p, ids, steps: int = 256, rate: float = 1e-3):
    """``{block: {"router_bias": [num_experts]}}``: every expert layer's
    balancing bias as a deployment's load-driven update leaves it on the
    tokens ``ids [B, T]`` (the auxiliary-loss-free rule, arXiv:2408.15664:
    after every step ``b_e += rate * sign(mean load - load_e)``; here
    ``steps`` updates from zeros on one batch), layer by layer with the
    layers below already balanced.  A seeded bias knows nothing of the
    load: with a token that is an eighth of a Zipf stream going wherever
    the seeded router sends it, the share of the tokens that meet a held
    expert swings by a third from seed to seed, and an expert layer's
    work with it (``PERF.md`` section 6, PR 38); a trained router's does
    not.  Traceable: a benchmark's set-up calls it under one ``jax.jit``."""
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    x, state, out = p["embed"]["embedding"][ids], None, {}
    B, T, H = x.shape
    for i in range(cfg.layers):
        pm, pf = p[f"layer{i}_mixer"], p[f"layer{i}_moe"]
        h = mixer_sub_layer(cfg, pm, x)
        flat = rms_norm(h, pf["norm"], cfg.rms_norm_eps).reshape(B * T, H)
        probs = jax.nn.softmax(router_logits(cfg, pf, flat, state)[0], -1)

        def update(_, b):
            _, e = jax.lax.top_k(probs + b, k)
            load = jnp.bincount(e.reshape(-1), length=E)
            return b + rate * jnp.sign(B * T * k / E - load)

        bias = jax.lax.fori_loop(0, steps, update, jnp.zeros((E,), _F32))
        out[f"layer{i}_moe"] = {"router_bias": bias}
        x, state, _, _ = expert_sub_layer(cfg, {**pf, "router_bias": bias},
                                          h, state)
    return out


def forward(cfg: Zaya, p, ids, labels=None):
    """``ids [B, T]`` -> ``(logits [B, T, V], aux)``, or with ``labels``
    ``(loss per sequence [B], aux)``."""
    routed, weights, state = [], [], None
    with scope("embed"):
        emb = p["embed"]["embedding"]
        x = emb[ids]
    for i in range(cfg.layers):
        x, state, counts, weight = decoder_layer(
            cfg, p[f"layer{i}_mixer"], p[f"layer{i}_moe"], x, state)
        routed.append(counts)
        weights.append(weight)
    with scope("step_stats"):
        aux = {**moe_aux(routed), "moe_weight_sum": sum(weights, _F32(0)),
               "router_state_rms": jnp.sqrt(jnp.mean(state * state))}
    norm = p["final_norm"]["norm"]
    if labels is None:
        return tied_head_logits(cfg, x, norm, emb), aux
    return head_losses(cfg, lambda a: rms_norm(a, norm, cfg.rms_norm_eps),
                       x, emb, labels, contract=1), aux
