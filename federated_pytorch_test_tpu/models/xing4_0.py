"""Xing4.0-29B-A4B decoder (``model_type xing4_0``): a residual path of
``hc_mult`` streams mixed by manifold-constrained hyper-connections
(mHC), multi-head latent attention with 192-wide keys beside 128-wide
values under YaRN, leading dense layers, then sparse expert layers with
sigmoid routing under a selection bias and one shared expert.

Source: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B config.json;
the hyper-connections: arXiv:2512.24880 over arXiv:2409.19606 (the map
equations are the papers'; ``hc_mult``, the Sinkhorn iteration count,
``hc_eps`` and the clamp are the config's); latent attention:
arXiv:2405.04434; the router's bias: arXiv:2412.19437; YaRN:
arXiv:2309.00071.  The equations (``N`` the plain RMS norm, ``n`` streams
of width ``C``, a sub-layer ``F`` with its own input norm)::

    X_0     = Emb(t) copied into n rows
    X_{l+1} = H_res X_l + H_post^T F(N(H_pre X_l))    (ops/
              hyper_connections.py: the maps from rms(vec(X_l)))
    output  = head(N(sum of the n rows of X_L))

are written out in ``benchmarks/reference/xing4_0.py``, which this file
is compared with.  Layer ``l``'s two sub-layers are latent attention
(``models/decoder.py:latent_attention``, expanded form) and a SwiGLU of
``intermediate_size`` where ``l < first_k_dense_replace``, else the
expert layer.  Matrix products run in ``dtype`` (bfloat16 on the chip)
with float32 sums; parameters, norms, the router, the loss and
everything of the hyper-connections are float32 (on a TPU their passes
over the streams are Pallas kernels, ``ops/hyper_connections.py:plan``;
``mhc_impl`` says which path a shape takes).  Each sub-layer, its
maps and mixing with it, is rematerialised in the backward pass
(``jax.checkpoint``): what a sub-layer boundary keeps is the ``n``
streams, and the rematerialised forward stops at the sub-layer's
input (``expand`` is not run again).  (Run sequence by sequence instead, the compiler's count of
the largest program's temporaries rose from 8.2 to 9.7 GiB.)

The streams are held as ``[n, B, T, C]`` (why the stream axis leads:
``ops/hyper_connections.py``).

Blocks, from the layer list as in ``models/glm4_moe_lite.py``: ``0`` the
embedding, ``1 + 2l`` layer ``l``'s latent attention with its input norm,
``2 + 2l`` its FFN block, last the final norm and the head.  Each
sub-layer's hyper-connection leaves (``hc_*``) lie in that sub-layer's
block: a federated block carries how it is wired into the streams.  The
router and its selection bias belong to no block (``models/
qwen3_next.py`` says why).  The multi-token-prediction layer of the
published model is not built: a configuration with
``num_nextn_predict_layers`` other than 0 is refused.

``aux["mhc_marginal_err"]`` is the largest ``|row sum - 1|`` or
``|column sum - 1|`` of any ``H_res`` of the call.
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
    _ZEROS,
    _Leaves,
    _mm,
    _normal,
    dense_mlp,
    dense_mlp_leaves,
    head_losses,
    latent_attention,
    mla_leaves,
    moe_aux,
    rms_norm,
    routing_counts,
    sigmoid_expert_layer as expert_layer,
    sigmoid_moe_leaves,
    yarn_inv_freq,
    yarn_softmax_scale,
)
from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops import hyper_connections as hc
from federated_pytorch_test_tpu.ops.flash_attention import plan as attn_plan


#: what every ``a_pre``, ``a_post``, ``a_res`` starts at (assumed; the
#: fields' comment on the seeding says why)
HC_ALPHA = 0.5


def _const(value):
    return lambda key, shape, dtype=_F32: jnp.full(shape, value, dtype)


def _eye(scale):
    return lambda key, shape, dtype=_F32: scale * jnp.eye(shape[0],
                                                          dtype=dtype)


class Xing4(BlockModule):
    """``__call__(ids [B, T] int32) -> (logits [B, T, vocab_rows] f32,
    aux)``; with ``labels [B, T]`` ``(loss per sequence [B], aux)``.
    ``aux`` holds the routing counts summed over the expert layers
    (``moe_pairs_local``, ``moe_dropped``), the worst layer's
    ``moe_load_max_over_mean`` and ``mhc_marginal_err``."""

    hidden_size: int = 3584
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    #: the config's group (``type yarn``); None: plain rotary tables
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    intermediate_size: int = 9216
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    num_nextn_predict_layers: int = 0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # the cut: layers kept, this chip's share of experts and vocabulary
    layers: int = 5
    experts_held: int = 8
    ep_rank: int = 0
    vocab_rows: int = 16384
    #: as ``models/glm4_moe_lite.py`` has them: every pair that can
    #: exist has a row; the embedding leads the streams
    pair_rows_factor: float = 8.0
    init_scale: float = 0.02
    embed_scale: float = 1.0
    bias_scale: float = 0.01
    #: how the hyper-connections are seeded (assumed).  ``phi_*`` are
    #: normal(0, 1 / (n C)), so that ``v phi`` is of unit scale at any
    #: width; ``a_*`` start at :data:`HC_ALPHA`: the input-dependent part
    #: moves every map's logit by that much (tenths of a map's range,
    #: not thousandths).  ``b_pre = b_post = 0`` (``H_pre`` about a half,
    #: ``H_post`` about one); ``b_res = hc_res_diag I``: ``H_res`` keeps
    #: about half of a stream and spreads the rest, and 20 Sinkhorn
    #: iterations reach float32's resolution (at 2.0 the columns are
    #: still 2e-4 off)
    hc_res_diag: float = 1.0
    attn_block: int = 512
    dtype: Any = jnp.bfloat16

    # -- the layer list and the blocks made from it ---------------------
    def layer_kinds(self) -> List[str]:
        return ["mlp" if i < self.first_k_dense_replace else "moe"
                for i in range(self.layers)]

    def block_names(self) -> List[str]:
        names = ["embed"]
        for i, kind in enumerate(self.layer_kinds()):
            names += [f"layer{i}_mixer", f"layer{i}_{kind}"]
        return names + ["head"]

    def block_kinds(self) -> List[str]:
        """``embed`` / ``mla`` / ``mlp`` / ``moe`` / ``head`` per block."""
        kinds = ["embed"]
        for k in self.layer_kinds():
            kinds += ["mla", k]
        return kinds + ["head"]

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def attn_impl(self, tokens: int) -> str:
        """What runs the attention core for a sequence of ``tokens`` here
        ("pallas" | "pallas_interpret" | "xla": ``ops/flash_attention.py:
        plan``)."""
        return attn_plan(tokens, self.num_attention_heads, 1,
                         self.qk_head_dim, self.dtype,
                         self.v_head_dim)["impl"]

    def mhc_impl(self, tokens: int) -> str:
        """What runs the hyper-connections' passes over the streams of
        ``tokens`` tokens here ("pallas" | "pallas_interpret" | "xla":
        ``ops/hyper_connections.py:plan``)."""
        return hc.plan(self.hc_mult, tokens, self.hidden_size)["impl"]

    def impl_fields(self, tokens: int) -> Dict[str, str]:
        """The round record's fields that name this backend's
        implementations for sequences of ``tokens``."""
        return {"attn_impl": self.attn_impl(tokens),
                "mhc_impl": self.mhc_impl(tokens), "head_impl": HEAD_IMPL}

    # -- rotary tables and the softmax scale -----------------------------
    def rope_inv_freq(self):
        """The rotary inverse frequencies ``[qk_rope_head_dim / 2]``:
        YaRN's where ``rope_scaling`` is set, else None (plain)."""
        rs = self.rope_scaling
        if not rs:
            return None
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             float(rs["factor"]),
                             int(rs["original_max_position_embeddings"]),
                             float(rs["beta_fast"]), float(rs["beta_slow"]))

    def softmax_scale(self) -> float:
        rs = self.rope_scaling or {}
        return yarn_softmax_scale(float(rs.get("factor", 1.0)),
                                  float(rs.get("mscale_all_dim", 0.0))) \
            / math.sqrt(self.qk_head_dim)

    # -- parameters --------------------------------------------------------
    def _hc_spec(self):
        n = self.hc_mult
        nC = n * self.hidden_size
        phi, a = _normal(nC ** -0.5), _const(HC_ALPHA)
        return (("hc_phi_pre", (nC, n), phi), ("hc_phi_post", (nC, n), phi),
                ("hc_phi_res", (nC, n * n), phi),
                ("hc_a_pre", (1,), a), ("hc_a_post", (1,), a),
                ("hc_a_res", (1,), a),
                ("hc_b_pre", (n,), _ZEROS), ("hc_b_post", (n,), _ZEROS),
                ("hc_b_res", (n, n), _eye(self.hc_res_diag)))

    def _spec(self, name: str):
        H, s = self.hidden_size, _normal(self.init_scale)
        if name == "embed":
            return (("embedding", (self.vocab_rows, H),
                     _normal(self.embed_scale)),)
        if name == "head":
            return (("norm", (H,), _ONES),
                    ("kernel", (H, self.vocab_rows), s))
        if name.endswith("_moe"):
            own = sigmoid_moe_leaves(self)
        elif name.endswith("_mlp"):
            own = dense_mlp_leaves(self)
        else:
            own = mla_leaves(self)
        return own + self._hc_spec()

    def param_order(self) -> List[str]:
        return [f"{b}/{leaf}" for b in self.block_names()
                for leaf, _, _ in self._spec(b)]

    def train_order_block_ids(self) -> List[List[int]]:
        """Inclusive index ranges into ``param_order()``.  An expert
        block's range starts AFTER its router and the router's bias (the
        first two leaves of its spec), which therefore lie in no block."""
        out, lo = [], 0
        for b in self.block_names():
            n = len(self._spec(b))
            out.append([lo + (2 if b.endswith("_moe") else 0), lo + n - 1])
            lo += n
        return out

    # -- forward ---------------------------------------------------------
    @nn.compact
    def __call__(self, ids, labels=None):
        """With ``labels [B, T]``: each sequence's loss ``[B]`` in place
        of the logits (sequence by sequence, so only one sequence's
        float32 logits are alive at a time)."""
        if self.num_nextn_predict_layers:
            raise ValueError(
                "num_nextn_predict_layers "
                f"{self.num_nextn_predict_layers}: a multi-token-prediction "
                "layer over hyper-connection streams is not built")
        p = {b: _Leaves(self._spec(b), name=b)() for b in self.block_names()}
        return forward(self, p, ids, labels)


def hyper_pre(cfg: Xing4, p, X):
    """``(H_pre X, the maps, X handed through)`` of the streams ``X [n,
    ..., C]`` under the sub-layer whose block leaves are ``p``
    (``ops/hyper_connections.py:pre``)."""
    return hc.pre(
        X, {k[3:]: v for k, v in p.items() if k.startswith("hc_")},
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
        norm_eps=cfg.rms_norm_eps)


def sub_layer(cfg: Xing4, p, f, X):
    """``X_{l+1} = H_res X_l + H_post^T f(H_pre X_l)`` with the maps of
    ``p``'s hyper-connection leaves; ``X [n, B, T, C]``, ``f([B, T, C])
    -> ([B, T, C], anything)``.  ``-> (X_{l+1}, marginal error,
    anything)``; the whole of it is rematerialised in the backward
    pass."""

    @jax.checkpoint
    def run(X):
        with scope("mhc"):
            u, m, X = hyper_pre(cfg, p, X)
        y, more = f(u)
        with scope("mhc"):
            return hc.expand(m.res, m.post, X, y), m.marginal_err, more

    return run(X)


def decoder_layer(cfg: Xing4, pm, pf, X):
    """The streams ``X [n, B, T, C]`` through one layer: latent attention
    with ``pm``, then the dense MLP or the expert layer with ``pf`` (by
    its leaves); ``-> (X, marginal error, routing counts or None)``."""
    eps = cfg.rms_norm_eps
    _, B, T, H = X.shape

    def mix(u):
        # sequence by sequence: attention does not cross sequences
        def one(ut):
            with scope("mla_attn"):
                with scope("sublayer_norm"):
                    un = rms_norm(ut, pm["norm"], eps)
                return latent_attention(cfg, pm, un, scale=scale,
                                        inv_freq=inv_freq)
        return jax.lax.map(one, u), None

    def ffn(u):
        # tokens are independent here: one batch of B * T
        with scope("sublayer_norm"):
            flat = rms_norm(u, pf["norm"], eps).reshape(B * T, H)
        if "router" in pf:
            y, r = expert_layer(cfg, pf, flat)
            return y.reshape(B, T, H), routing_counts(r)
        return dense_mlp(cfg, pf, flat).reshape(B, T, H), None

    with scope("sublayer_mixer"):
        scale, inv_freq = cfg.softmax_scale(), cfg.rope_inv_freq()
        X, err_m, _ = sub_layer(cfg, pm, mix, X)
    with scope("sublayer_ffn"):
        X, err_f, counts = sub_layer(cfg, pf, ffn, X)
    with scope("step_stats"):
        return X, jnp.maximum(err_m, err_f), counts


def forward(cfg: Xing4, p, ids, labels=None):
    """``ids [B, T]`` -> ``(logits [B, T, V], aux)``, or with ``labels``
    ``(loss per sequence [B], aux)``."""
    routed, err = [], _F32(0)
    with scope("embed"):
        emb = p["embed"]["embedding"][ids]
    with scope("hc_streams"):
        X = jnp.broadcast_to(emb[None], (cfg.hc_mult,) + emb.shape)
    for i, kind in enumerate(cfg.layer_kinds()):
        X, e, counts = decoder_layer(cfg, p[f"layer{i}_mixer"],
                                     p[f"layer{i}_{kind}"], X)
        with scope("step_stats"):
            err = jnp.maximum(err, e)
        routed += [counts] if counts is not None else []
    with scope("hc_streams"):
        x = jnp.sum(X, axis=0)
    with scope("step_stats"):
        aux = {**moe_aux(routed), "mhc_marginal_err": err}

    norm = lambda a: rms_norm(a, p["head"]["norm"], cfg.rms_norm_eps)
    if labels is None:
        with scope("lm_head_loss"):
            with scope("head_norm"):
                xn = norm(x)
            with scope("head_product"):
                return _mm(cfg, xn, p["head"]["kernel"]), aux
    return head_losses(cfg, norm, x, p["head"]["kernel"], labels), aux
