"""The ``zaya`` cell's work model and what its readers take from the
scope tree.

**Work.**  The least operations (and, for the attention core, bytes) a
round needs, from the configuration's keys and the round's counts, as
``glm_work.py`` words it: what ANY implementation must do, never what
this one does: no masked half of a causal product, the router's float32
products counted once (not as the bfloat16 passes ``HIGHEST`` makes of
them), the experts by the pairs that hit a held one.  A share computed
from these cannot pass 100 %.

A product of ``[m, k]`` with ``[k, n]`` is ``2 m k n`` operations,
forward; its backward pass is one such product for the activation's
gradient and one for the weight's, which is needed in the active block
only.  A weightless product (the attention core's two) has two operands'
gradients.

The parts of a step in forward order, with the block that owns their
weights: layer ``l``'s compressed convolutional attention at ``2 l``
(block ``1 + 2 l``), its expert layer at ``2 l + 1`` (block ``2 + 2 l``:
the experts; the router lies in no block), then the head, whose matrix
is the embedding (block ``0``).  The last block, ``2 L + 1``, is the
final norm alone.

**Scopes.**  Read from ``scope_tree.py``'s tree of the traced pass
(whole path segments against the program's table): a scope's seconds
wherever it hangs, or under one parent only.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.lib import glm_work, peaks, scope_tree

# one token through one expert, the head's product and what a round
# record gives are counted as for the sibling: the same keys
from benchmarks.lib.glm_work import head_flops, pair_flops  # noqa: F401


# ----------------------------------------------------------------------
# per-token forward operations of each part
# ----------------------------------------------------------------------
def _widths(cfg) -> Tuple[int, int, int, int]:
    """``(n_q, n_kv, d, H)``."""
    return (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]), int(cfg["hidden_size"]))


def cca_proj_flops(cfg) -> float:
    """The mixer's five projections per token: ``W_q``, ``W_k``,
    ``W_v1`` and ``W_v2`` down, ``W_o`` up."""
    nq, nkv, d, H = _widths(cfg)
    return 2.0 * H * (nq * d + 2 * nkv * d) + 2.0 * nq * d * H


def cca_conv_flops(cfg) -> float:
    """Both causal convolutions per token: the depthwise taps and one
    ``d x d`` matrix a head and tap."""
    nq, nkv, d, _ = _widths(cfg)
    heads = nq + nkv
    return 2.0 * int(cfg["cca_time0"]) * heads * d \
        + 2.0 * int(cfg["cca_time1"]) * heads * d * d


def cca_core_flops(cfg, seq_len: int) -> float:
    """Causal ``q k^T`` and ``a v`` per token, averaged over the
    sequence: each token meets ``(T + 1) / 2`` keys."""
    nq, _, d, _ = _widths(cfg)
    return 2.0 * nq * 2 * d * (seq_len + 1) / 2.0


def cca_core_bytes(cfg) -> float:
    """One pass per token over ``q`` and the key/value heads' ``k, v``
    in (two bytes) and ``o`` out (float32)."""
    nq, nkv, d, _ = _widths(cfg)
    return 2.0 * d * (nq + 2 * nkv) + 4.0 * d * nq


def router_flops(cfg) -> float:
    """The router per token: the down-projection, two hidden layers,
    the output."""
    H, Dr = int(cfg["hidden_size"]), int(cfg["router_hidden_size"])
    return 2.0 * (H * Dr + 2 * Dr * Dr + Dr * int(cfg["num_experts"]))


# ----------------------------------------------------------------------
# a round
# ----------------------------------------------------------------------
def parts(cfg) -> List[Tuple[str, int]]:
    """``(kind, owning block)`` of every part of a step in forward
    order: kinds ``cca`` / ``moe`` / ``head``."""
    out = []
    for l in range(int(cfg["layers"])):
        out += [("cca", 1 + 2 * l), ("moe", 2 + 2 * l)]
    return out + [("head", 0)]


def needs(cfg, block: int) -> List[Tuple[bool, bool]]:
    """Per part of :func:`parts`: ``(the activation's gradient, the
    weights' gradient)`` a round with ``block`` active needs.  The
    gradient reaches every part at or after the active block's: all of
    them for the tied embedding (whose weight gradient as the head's
    matrix is a product; as the gathered table, a scatter), the head
    alone for the final norm."""
    ps = parts(cfg)
    if block == 0:
        first = 0
    elif block == len(ps):                   # the final norm
        first = len(ps) - 1
    else:
        first = next(i for i, (_, b) in enumerate(ps) if b == block)
    return [(i >= first, b == block) for i, (_, b) in enumerate(ps)]


def round_flops(cfg, block: int, tokens: int, pairs_local: int,
                seq_len: int) -> float:
    """Forward and backward of ``tokens`` tokens with ``block`` active.
    ``pairs_local`` counts token-expert pairs over all expert layers, so
    the experts' share is exact."""
    ps = parts(cfg)
    n_moe = sum(1 for kind, _ in ps if kind == "moe")
    total = 0.0
    for (kind, _), (act, wgt) in zip(ps, needs(cfg, block)):
        core = own = 0.0                  # weightless / in no block
        if kind == "cca":
            weighted = (cca_proj_flops(cfg) + cca_conv_flops(cfg)) * tokens
            core = cca_core_flops(cfg, seq_len) * tokens
        elif kind == "moe":
            weighted = pair_flops(cfg) * pairs_local / n_moe
            own = router_flops(cfg) * tokens
        else:
            weighted = head_flops(cfg) * tokens
        total += weighted + own + core
        if act:
            total += weighted + own + 2.0 * core
        if wgt:
            total += weighted
    return total


def cca_core_work(cfg, block: int, tokens: int, seq_len: int
                  ) -> Tuple[float, float]:
    """``(operations, bytes)`` of the attention cores of one round as
    the ``cca_core`` scope runs them: forward in every mixer; in the
    mixers the gradient reaches, the forward once more (``jax.checkpoint``
    calls the kernel again: counted, because the scope's time holds that
    call) and backward (twice the forward's products; a pass over the
    operands and their gradients).  The score tiles the backward kernel
    computes again are not counted."""
    flops = bytes_ = 0.0
    for (kind, _), (act, _) in zip(parts(cfg), needs(cfg, block)):
        if kind == "cca":
            flops += tokens * cca_core_flops(cfg, seq_len) * (4 if act else 1)
            bytes_ += tokens * cca_core_bytes(cfg) * (4 if act else 1)
    return flops, bytes_


round_of = glm_work.round_of


# ----------------------------------------------------------------------
# the scope tree
# ----------------------------------------------------------------------
def seconds_under(tree: Dict, parent: str, names: Sequence[str]) -> float:
    """Seconds of the scopes ``names`` where they hang directly under
    ``parent``."""
    return sum(sum(n["s"]) for key, n in tree["nodes"].items()
               if key.rsplit("/", 2)[-2:] in [[parent, name]
                                              for name in names])


def busy_share_pct(cell, trace, seconds) -> Optional[float]:
    """100 x ``seconds(tree)`` over the chip's busy time in the traced
    pass, worst chip; None where the tree has no such scope (the parent
    commit's program, a run without a trace)."""
    return scope_tree.worst_share_pct(
        cell, trace, seconds, present=lambda tree: seconds(tree) > 0)


def roofline_pct(cell, trace, records, scope: str, work) -> Optional[float]:
    """100 x the least time the chip could take for the traced pass's
    work in ``scope`` (the larger of operations / peak and bytes /
    bandwidth; ``work(config, **round) -> (operations, bytes)``) over the
    scope's device time, worst chip."""
    trees = scope_tree.of_cell(cell, trace)
    rounds = [r for r in records.rounds(traced=True) if "tokens" in r]
    if not trees or not rounds:
        return None
    peak = peaks.peaks_for(trace.device_kind)
    flops = bytes_ = 0.0
    for rec in rounds:
        f, b = work(cell.config, **round_of(cell, rec))
        flops, bytes_ = flops + f, bytes_ + b
    least = max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    took = max(scope_tree.scope_seconds(t, scope) for t in trees.values())
    # the rounds' work is spread over the chips
    return None if took <= 0 else 100.0 * least / len(trees) / took
