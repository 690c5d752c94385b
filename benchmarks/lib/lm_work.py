"""The least work a round of the ``lm`` engine needs, from shapes and
counts: operations and bytes for the step's utilisation and for the two
kernels' roofline shares.

Each function counts what ANY implementation must do, never what this
one does: no rematerialised forward pass, no padding of a group to a
tile, no masked half of a causal product.  A share computed from these
can therefore not pass 100 % (PR 22's InfoNCE reader read 341 % from a
byte model that counted more than the kernel moved).

A product of ``[m, k]`` with ``[k, n]`` is ``2 m k n`` operations, forward;
its backward pass is two such products (one for each operand's
gradient), of which the weight's is needed only in the active block.

``block`` is an index into the model's own block list: 0 the embedding,
``1 + 2l`` layer ``l``'s mixer, ``2 + 2l`` its expert block, the last
the head.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def is_attention(cfg: Dict[str, Any], layer: int) -> bool:
    return (layer + 1) % int(cfg["full_attention_interval"]) == 0


def position(cfg, block: int) -> float:
    """Where the block sits in forward order, in sub-layers: mixer ``l``
    at ``2 l``, expert block ``l`` at ``2 l + 1``, the embedding before
    and the head after them all."""
    last = 2 * int(cfg["layers"]) + 1
    return -1.0 if block == 0 else (
        2.0 * int(cfg["layers"]) if block == last else float(block - 1))


# -- per-token forward operations of each part ---------------------------
def gdn_core_flops(cfg) -> float:
    """The recurrence per token: ``S^T k``, ``k (x) delta`` and ``S^T q``
    on a ``d_k x d_v`` state per value head (the decay's multiply, one
    more pass over the state, is left out: a lower bound)."""
    return 6.0 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] \
        * cfg["linear_num_value_heads"]


def gdn_core_bytes(cfg) -> float:
    """One pass over q, k, v, g, beta in and o out per token, float32,
    at the value heads' count (each key head serves two)."""
    nv = cfg["linear_num_value_heads"]
    return 4.0 * nv * (2 * cfg["linear_key_head_dim"]
                       + 2 * cfg["linear_value_head_dim"] + 2)


def gdn_weight_flops(cfg) -> Tuple[float, float]:
    """``(input projections, output projection)`` per token."""
    H = cfg["hidden_size"]
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width_in = 2 * nk * dk + 2 * nv * dv + 2 * nv
    return 2.0 * H * width_in, 2.0 * nv * dv * H


def attn_core_flops(cfg, seq_len: int) -> float:
    """Causal ``q k^T`` and ``a v`` per token, averaged over the
    sequence: each token meets ``(T + 1) / 2`` keys."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * (seq_len + 1) / 2.0


def attn_weight_flops(cfg) -> Tuple[float, float]:
    H, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2.0 * H * (2 * nq * d + 2 * nkv * d), 2.0 * nq * d * H


def pair_flops(cfg) -> float:
    """One token through one expert: three ``H x F`` products."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_dense_flops(cfg) -> float:
    """Router, shared expert and its gate per token."""
    H = cfg["hidden_size"]
    return 2.0 * H * cfg["num_experts"] \
        + 6.0 * H * cfg["shared_expert_intermediate_size"] + 2.0 * H


def head_flops(cfg) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_rows"]


# -- a round -------------------------------------------------------------
def round_flops(cfg, block: int, tokens: int, pairs_local: int,
                seq_len: int) -> float:
    """Forward and backward of ``tokens`` tokens with ``block`` active:
    every part's forward; the backward of every part after the active
    block (both operands' gradients of a weightless product, the
    activation's of a weighted one); inside the active block the weights'
    gradients and the activation's gradients of all but its input
    projections.  ``pairs_local`` counts token-expert pairs over all
    layers, so the experts' share is exact."""
    L, pos = int(cfg["layers"]), position(cfg, block)
    total = 0.0
    for l in range(L):
        if is_attention(cfg, l):
            core, (w_in, w_out) = attn_core_flops(cfg, seq_len), \
                attn_weight_flops(cfg)
        else:
            core, (w_in, w_out) = gdn_core_flops(cfg), gdn_weight_flops(cfg)
        here = 2.0 * l
        fwd = core + w_in + w_out
        if here > pos:                       # after the active block
            total += tokens * (fwd + 2 * core + w_in + w_out)
        elif here == pos:                    # the active mixer
            total += tokens * (fwd + 2 * core + w_out      # activations
                               + w_in + w_out)             # weights
        else:
            total += tokens * fwd
        # the expert block of layer l
        here = 2.0 * l + 1
        dense = moe_dense_flops(cfg) * tokens
        sparse = pair_flops(cfg) * pairs_local / L
        fwd = dense + sparse
        if here > pos:
            total += 2 * fwd
        elif here == pos:
            # weights' gradients of everything; activations' gradients
            # of the second and third products only (2/3 of the experts',
            # of the shared expert's), none of the router's input
            total += fwd + fwd + 2.0 / 3.0 * (
                sparse + 6.0 * cfg["hidden_size"]
                * cfg["shared_expert_intermediate_size"] * tokens)
        else:
            total += fwd
    head = head_flops(cfg) * tokens
    last = 2 * L + 1
    total += head * (3 if block == last else 2)   # the loss needs d logits
    return total


def gdn_scan_work(cfg, block: int, tokens: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of the recurrences of one round: forward
    in every GDN layer; backward (twice the forward's products, and a
    second pass over the operands plus their gradients) in the GDN
    layers at or after the active block."""
    pos = position(cfg, block)
    flops = bytes_ = 0.0
    for l in range(int(cfg["layers"])):
        if is_attention(cfg, l):
            continue
        back = 2.0 * l >= pos
        flops += tokens * gdn_core_flops(cfg) * (3 if back else 1)
        bytes_ += tokens * gdn_core_bytes(cfg) * (3 if back else 1)
    return flops, bytes_


def moe_experts_work(cfg, block: int, pairs_local: int
                     ) -> Tuple[float, float]:
    """``(operations, bytes)`` of the held experts' products of one
    round: forward in every layer (the held weights read once in
    bfloat16, each pair's row in and out); backward in the expert blocks
    at or after the active block (weights read once more; the active
    block's weight gradient written once in float32)."""
    L, pos = int(cfg["layers"]), position(cfg, block)
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3.0 * cfg["experts_held"] * H * F
    pairs = pairs_local / L
    rows = pairs * (2.0 * H + 4.0 * H)       # bf16 in, f32 out
    flops = bytes_ = 0.0
    for l in range(L):
        here = 2.0 * l + 1
        flops += pairs * pair_flops(cfg)
        bytes_ += 2.0 * weights + rows
        if here > pos:
            flops += pairs * pair_flops(cfg)
            bytes_ += 2.0 * weights + rows
        elif here == pos:
            flops += pairs * pair_flops(cfg) * (1.0 + 2.0 / 3.0)
            bytes_ += 2.0 * weights + 4.0 * weights + rows
    return flops, bytes_


def round_of(cell, rec) -> Dict[str, Any]:
    """What the functions above need, from a round record of the cell:
    ``block`` (the model's own index), ``tokens``, ``pairs_local``."""
    return {"block": int(cell.traffic["blocks"][int(rec["block"])]),
            "tokens": int(rec["tokens"]),
            "pairs_local": int(rec["moe_pairs_local"])}
