"""The ``olmo_hybrid`` cell's work model.

The least operations (and, for the delta rule's recurrence, bytes) a
round needs, from the configuration's keys and the round's counts, as
``glm_work.py`` words it: what ANY implementation must do, never what
this one does: nothing rematerialised, no masked half of a causal
product, no padding of a 96- or 192-wide head to a lane tile.  A share
computed from these cannot pass 100 %.

A product of ``[m, k]`` with ``[k, n]`` is ``2 m k n`` operations,
forward; its backward pass is one such product for the activation's
gradient and one for the weight's, which is needed in the active block
only.  A weightless product (the attention core's two, the recurrence's
three) has two operands' gradients.  The active part's own input gets no
gradient (everything before it is frozen), so its products that read
that input count no activation gradient.

The parts of a step in forward order, with the block that owns their
weights: layer ``l``'s mixer (Gated DeltaNet or attention) at ``2 l``
(block ``1 + 2 l``), its MLP at ``2 l + 1`` (block ``2 + 2 l``), then the
head (block ``2 L + 1``).  Block ``0`` is the embedding, whose gradient
is a scatter: no products.  Scopes are read from ``scope_tree.py``'s tree
with ``zaya_work.py``'s two functions.
"""

from __future__ import annotations

from typing import List, Tuple

# the head's product, a round's numbers and the scope readers are the
# siblings'
from benchmarks.lib.glm_work import head_flops  # noqa: F401
from benchmarks.lib.zaya_work import (  # noqa: F401
    busy_share_pct,
    roofline_pct,
    round_of,
)


# ----------------------------------------------------------------------
# per-token forward operations of each part: (reading the part's input,
# the rest) for weighted products
# ----------------------------------------------------------------------
def _gdn_widths(cfg) -> Tuple[int, int]:
    """``(key width, value width)`` of a Gated DeltaNet layer."""
    return (int(cfg["linear_num_key_heads"]) * int(cfg["linear_key_head_dim"]),
            int(cfg["linear_num_value_heads"])
            * int(cfg["linear_value_head_dim"]))


def gdn_weight_flops(cfg) -> Tuple[float, float]:
    """The mixer's products with weights: ``W_q``, ``W_k``, ``W_v``,
    ``W_z``, ``W_a``, ``W_b`` from the input; the three convolutions' taps
    and ``W_o``."""
    H, nv = int(cfg["hidden_size"]), int(cfg["linear_num_value_heads"])
    k, v = _gdn_widths(cfg)
    taps = int(cfg["linear_conv_kernel_dim"])
    return 2.0 * H * (2 * k + 2 * v + 2 * nv), \
        2.0 * taps * (2 * k + v) + 2.0 * v * H


def gdn_core_flops(cfg) -> float:
    """The recurrence per token: ``S^T k``, ``k (x) delta`` and ``S^T q``
    on a ``d_k x d_v`` state per head (the decay's multiply, one more
    pass over the state, is left out: a lower bound)."""
    return 6.0 * int(cfg["linear_key_head_dim"]) \
        * int(cfg["linear_value_head_dim"]) \
        * int(cfg["linear_num_value_heads"])


def gdn_core_bytes(cfg) -> float:
    """One pass over ``q, k, v, g, beta`` in and ``o`` out per token,
    float32, unpadded."""
    return 4.0 * int(cfg["linear_num_value_heads"]) * (
        2 * int(cfg["linear_key_head_dim"])
        + 2 * int(cfg["linear_value_head_dim"]) + 2)


def attn_weight_flops(cfg) -> Tuple[float, float]:
    """``W_q``, ``W_k``, ``W_v`` from the input; ``W_o``."""
    H, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    d = H // n
    q, kv = n * d, int(cfg["num_key_value_heads"]) * d
    return 2.0 * H * (q + 2 * kv), 2.0 * q * H


def attn_core_flops(cfg, seq_len: int) -> float:
    """Causal ``q k^T`` and ``a v`` per token, averaged over the
    sequence: each token meets ``(T + 1) / 2`` keys; the heads' widths
    add up to the hidden width."""
    return 4.0 * int(cfg["hidden_size"]) * (seq_len + 1) / 2.0


def mlp_flops(cfg) -> Tuple[float, float]:
    """``W_gate``, ``W_up`` from the input; ``W_down``."""
    H, F = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    return 4.0 * H * F, 2.0 * F * H


# ----------------------------------------------------------------------
# a round
# ----------------------------------------------------------------------
def _is_attention(cfg, layer: int) -> bool:
    types = cfg.get("layer_types")
    if types:
        return types[layer] == "full_attention"
    return (layer + 1) % 4 == 0


def parts(cfg) -> List[Tuple[str, int]]:
    """``(kind, owning block)`` of every part of a step in forward order:
    kinds ``gdn`` / ``attn`` / ``mlp`` / ``head``."""
    L, out = int(cfg["layers"]), []
    for l in range(L):
        out += [("attn" if _is_attention(cfg, l) else "gdn", 1 + 2 * l),
                ("mlp", 2 + 2 * l)]
    return out + [("head", 2 * L + 1)]


def needs(cfg, block: int) -> List[Tuple[bool, bool]]:
    """Per part of :func:`parts`: ``(the gradient reaches it, its weights
    are the active block's)``.  The gradient reaches every part at or
    after the active block's; all of them for the embedding."""
    ps = parts(cfg)
    first = 0 if block == 0 else next(
        i for i, (_, b) in enumerate(ps) if b == block)
    return [(i >= first, b == block) for i, (_, b) in enumerate(ps)]


def _part_flops(cfg, kind: str, seq_len: int) -> Tuple[float, float, float]:
    """``(weighted products reading the input, the other weighted ones,
    weightless ones)`` of a part per token."""
    if kind == "gdn":
        return (*gdn_weight_flops(cfg), gdn_core_flops(cfg))
    if kind == "attn":
        return (*attn_weight_flops(cfg), attn_core_flops(cfg, seq_len))
    if kind == "mlp":
        return (*mlp_flops(cfg), 0.0)
    return head_flops(cfg), 0.0, 0.0


def round_flops(cfg, block: int, tokens: int, seq_len: int, **_) -> float:
    """Forward and backward of ``tokens`` tokens with ``block`` active:
    forward of every part; where the gradient reaches, the weightless
    products twice more and the weighted ones once more (the active
    part's products from its own input excepted); the active block's
    weight gradients."""
    total = 0.0
    for (kind, _), (reached, active) in zip(parts(cfg), needs(cfg, block)):
        first, rest, core = _part_flops(cfg, kind, seq_len)
        total += first + rest + core
        if reached:
            total += rest + 2.0 * core + (0.0 if active else first)
        if active:
            total += first + rest
    return total * tokens


def gdn_scan_work(cfg, block: int, tokens: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of the recurrences of one round at the
    published widths, unpadded: forward in every Gated DeltaNet layer;
    backward (twice the forward's products, and a second pass over the
    operands plus their gradients) in those the gradient reaches."""
    flops = bytes_ = 0.0
    for (kind, _), (reached, _) in zip(parts(cfg), needs(cfg, block)):
        if kind == "gdn":
            flops += tokens * gdn_core_flops(cfg) * (3 if reached else 1)
            bytes_ += tokens * gdn_core_bytes(cfg) * (3 if reached else 1)
    return flops, bytes_
