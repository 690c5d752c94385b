"""The ``glm4_moe_lite`` cell's work model and scope list.

**Work.**  The least operations (and, for the attention core, bytes) a
round needs, from the configuration's keys and the round's counts, as
``lm_work.py`` words it: what ANY implementation must do, never what
this one does: no rematerialised forward pass, no padding of a group to
a tile, no masked half of a causal product.  A share computed from these
cannot pass 100 %.

A product of ``[m, k]`` with ``[k, n]`` is ``2 m k n`` operations,
forward; its backward pass is one such product for the activation's
gradient and one for the weight's, which is needed in the active block
only.  A weightless product (the attention core's two) has two operands'
gradients.

The parts of a step, in forward order, with the block that owns their
weights: the trunk's sub-layers ``0 .. 2 L - 1`` (layer ``l``'s MLA at ``2
l``, block ``1 + 2 l``; its dense MLP or expert layer at ``2 l + 1``,
block ``2 + 2 l``), then two branches off the last layer's output: the
head (block ``2 L + 1``), and the MTP layer: the merge ``W_eh`` and its
MLA (block ``2 L + 2``), its expert layer (block ``2 L + 3``) and the
head again, whose matrix is the main model's.  Block ``0`` is the
embedding.  What a round with ``block`` active needs backward:
:func:`needs`.

**Scopes.**  The model's ``jax.named_scope`` names, read from a trace's
``tf_op`` stat as ``scopes.py`` reads Qwen3-Next's: an op belongs to the
innermost listed scope its path holds, and besides to the MTP layer if
its path passes through ``mtp``.  The TPU compiler's grouped kernels
(``ragged-dot-...``) carry no path: they are the experts' products,
whichever layer's, and count for no ``mtp``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.lib import peaks, scopes, xplane


# ----------------------------------------------------------------------
# per-token forward operations of each part
# ----------------------------------------------------------------------
def _qk_dim(cfg) -> int:
    return int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])


def mla_weight_flops(cfg) -> float:
    """The five projections of one latent-attention mixer per token."""
    H, n = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return 2.0 * (H * rq + rq * n * _qk_dim(cfg)
                  + H * (rkv + cfg["qk_rope_head_dim"])
                  + rkv * n * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                  + n * cfg["v_head_dim"] * H)


def mla_core_flops(cfg, seq_len: int) -> float:
    """Causal ``q k^T`` and ``a v`` per token, averaged over the
    sequence: each token meets ``(T + 1) / 2`` keys."""
    return 2.0 * cfg["num_attention_heads"] \
        * (_qk_dim(cfg) + cfg["v_head_dim"]) * (seq_len + 1) / 2.0


def mla_core_bytes(cfg) -> float:
    """One pass per token over ``q, k, v`` in (two bytes) and ``o`` out
    (float32)."""
    n = cfg["num_attention_heads"]
    return n * (2.0 * (2 * _qk_dim(cfg) + cfg["v_head_dim"])
                + 4.0 * cfg["v_head_dim"])


def dense_mlp_flops(cfg) -> float:
    return 6.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def pair_flops(cfg) -> float:
    """One token through one expert: three ``H x F`` products."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_dense_flops(cfg) -> float:
    """Router and shared experts per token."""
    H = cfg["hidden_size"]
    return 2.0 * H * cfg["n_routed_experts"] + 6.0 * H \
        * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]


def head_flops(cfg) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_rows"]


def merge_flops(cfg) -> float:
    return 4.0 * cfg["hidden_size"] ** 2


# ----------------------------------------------------------------------
# a round
# ----------------------------------------------------------------------
def parts(cfg) -> List[Tuple[str, int]]:
    """``(kind, owning block)`` of every part of a step in forward order:
    kinds ``mla`` / ``mlp`` / ``moe`` / ``head`` / ``merge``; the MTP
    layer's parts come last."""
    L = int(cfg["layers"])
    out = []
    for l in range(L):
        out += [("mla", 1 + 2 * l),
                ("mlp" if l < int(cfg["first_k_dense_replace"]) else "moe",
                 2 + 2 * l)]
    out.append(("head", 2 * L + 1))
    if int(cfg["num_nextn_predict_layers"]):
        out += [("merge", 2 * L + 2), ("mla", 2 * L + 2), ("moe", 2 * L + 3),
                ("head", 2 * L + 1)]
    return out


def needs(cfg, block: int) -> List[Tuple[bool, bool]]:
    """Per part of :func:`parts`: ``(the activation's gradient, the
    weights' gradient)`` a round with ``block`` active needs.  The
    gradient reaches every part at or after the active block's first
    part on a path to a loss term: the rest of the trunk and both
    branches for a trunk block (all of it for the embedding), the MTP
    branch alone for an MTP block, and for the head block (final norm
    and the matrix both heads share) the main head's activation (its
    norm is in the block) and both heads' weight."""
    ps = parts(cfg)
    L = int(cfg["layers"])
    head, trunk = 2 * L + 1, 2 * L
    first = next((i for i, (_, b) in enumerate(ps) if b == block), 0)
    out = []
    for i, (_, b) in enumerate(ps):
        if block == head:
            out.append((i == trunk, b == head))
        elif block > head:                    # an MTP block
            out.append((i >= first and i > trunk, b == block))
        else:                                 # the embedding or the trunk
            out.append((i >= first, b == block))
    return out


def round_flops(cfg, block: int, tokens: int, pairs_local: int,
                seq_len: int) -> float:
    """Forward and backward of ``tokens`` tokens with ``block`` active.
    ``pairs_local`` counts token-expert pairs over all expert layers (the
    MTP layer's among them), so the experts' share is exact."""
    ps = parts(cfg)
    n_moe = sum(1 for kind, _ in ps if kind == "moe")
    total = 0.0
    for (kind, _), (act, wgt) in zip(ps, needs(cfg, block)):
        core = 0.0
        if kind == "mla":
            weighted = mla_weight_flops(cfg) * tokens
            core = mla_core_flops(cfg, seq_len) * tokens
        elif kind == "mlp":
            weighted = dense_mlp_flops(cfg) * tokens
        elif kind == "moe":
            weighted = moe_dense_flops(cfg) * tokens \
                + pair_flops(cfg) * pairs_local / n_moe
        elif kind == "head":
            weighted = head_flops(cfg) * tokens
        else:
            weighted = merge_flops(cfg) * tokens
        total += weighted + core
        if act:
            total += weighted + 2.0 * core
        if wgt:
            # the router is in no block: no gradient of its weight
            total += weighted - (2.0 * cfg["hidden_size"] * tokens
                                 * cfg["n_routed_experts"]
                                 if kind == "moe" else 0.0)
    return total


def mla_core_work(cfg, block: int, tokens: int, seq_len: int
                  ) -> Tuple[float, float]:
    """``(operations, bytes)`` of the attention cores of one round:
    forward in every mixer, the MTP layer's too; backward (twice the
    forward's products; a second pass over the operands and their
    gradients) in the mixers the gradient reaches."""
    flops = bytes_ = 0.0
    for (kind, _), (act, _) in zip(parts(cfg), needs(cfg, block)):
        if kind == "mla":
            flops += tokens * mla_core_flops(cfg, seq_len) * (3 if act else 1)
            bytes_ += tokens * mla_core_bytes(cfg) * (3 if act else 1)
    return flops, bytes_


def round_of(cell, rec) -> Dict[str, Any]:
    """What the functions above need, from a round record of the cell."""
    return {"block": int(cell.traffic["blocks"][int(rec["block"])]),
            "tokens": int(rec["tokens"]),
            "pairs_local": int(rec["moe_pairs_local"]),
            "seq_len": int(cell.config["seq_len"])}


# ----------------------------------------------------------------------
# scopes
# ----------------------------------------------------------------------
#: innermost first
SCOPES = ("mla_core", "moe_experts", "moe_route", "moe_shared",
          "lm_head_loss", "dense_mlp", "mla_attn")
#: the whole mixer: projections, norms, rotary and the core
MIXER = ("mla_core", "mla_attn")
_THROUGH_MTP = re.compile(r"(^|/)mtp(/|$)")


def scope_of(op_path: str, instruction: str = "") -> str:
    """The scope that owns an op with JAX path ``op_path`` (``""``: none)."""
    if op_path.startswith(scopes.RAGGED_STEM) \
            or instruction.startswith(scopes.RAGGED_STEM):
        return "moe_experts"
    return next((s for s in SCOPES if s in op_path), "")


@dataclasses.dataclass(frozen=True)
class ScopedOp:
    op: xplane.Op
    scope: str
    mtp: bool          # the op's path passes through the MTP layer's scope


def load(path: str) -> Dict[str, List[ScopedOp]]:
    """Every device's executed ops with their scope."""
    from jax.profiler import ProfileData

    paths = scopes.event_stat(path, "tf_op")
    cats = xplane.op_categories(path)
    out: Dict[str, List[ScopedOp]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                name = xplane.op_name(ev.name)
                op_path = paths.get(plane.name, {}).get(ev.name, "")
                ops.append(ScopedOp(
                    xplane.Op(name, float(ev.start_ns), float(ev.duration_ns),
                              cats.get(plane.name, {}).get(ev.name, ""),
                              xplane.PALLAS_TARGET in ev.name),
                    scope_of(op_path, name),
                    bool(_THROUGH_MTP.search(op_path))))
        out[plane.name] = ops
    return out


def picked_ns(ops: List[ScopedOp], pick, t0: float, t1: float) -> float:
    """Device time inside ``[t0, t1]`` in which an op with ``pick(op)``
    ran (containers such as ``while`` left out: they wrap their body)."""
    return xplane.busy_ns([o.op for o in ops if pick(o)
                           and not xplane.is_container(o.op)], t0, t1)


_LOADED: Dict[str, Dict[str, List[ScopedOp]]] = {}


def of_cell(cell) -> Optional[Dict[str, List[ScopedOp]]]:
    """The scoped ops of the cell's traced pass, or None without a trace.
    Read once per file; the first reading prints every scope's device
    seconds over the whole trace as ``scope_seconds={...}`` (first
    chip; ``mtp`` overlaps the others)."""
    path = xplane.find_xplane(os.path.join(scopes.BENCH, "out", cell.name,
                                           "trace"))
    if path is None:
        return None
    if path not in _LOADED:
        _LOADED[path] = load(path)
        first = next(iter(_LOADED[path].values()), [])
        t0 = min((o.op.start_ns for o in first), default=0.0)
        t1 = max((o.op.end_ns for o in first), default=0.0)
        sec = {s or "none": round(picked_ns(
            first, lambda o, s=s: o.scope == s, t0, t1) / 1e9, 4)
            for s in SCOPES + ("",)}
        sec["mtp"] = round(picked_ns(first, lambda o: o.mtp, t0, t1) / 1e9, 4)
        print("scope_seconds=" + json.dumps(sec))
    return _LOADED[path]


def busy_share_pct(cell, trace, pick) -> Optional[float]:
    """100 x the device time of the ops with ``pick(op)`` over the
    device's busy time in the traced pass, averaged over the chips; None
    where no such op ran."""
    scoped = of_cell(cell) if trace is not None else None
    if not scoped:
        return None
    t0, t1 = trace.window
    shares = []
    for ops in scoped.values():
        busy = xplane.busy_ns(xplane.leaf_ops(o.op for o in ops), t0, t1)
        mine = picked_ns(ops, pick, t0, t1)
        if busy <= 0 or mine <= 0:
            return None
        shares.append(mine / busy)
    return 100.0 * sum(shares) / len(shares)


def roofline_pct(cell, trace, records, scope: str, work) -> Optional[float]:
    """100 x the least time the chip could take for the traced pass's
    work in ``scope`` (the larger of operations / peak and bytes /
    bandwidth; ``work(config, **round) -> (operations, bytes)``) over the
    scope's device time, worst chip."""
    scoped = of_cell(cell) if trace is not None else None
    rounds = [r for r in records.rounds(traced=True) if "tokens" in r]
    if not scoped or not rounds:
        return None
    peak = peaks.peaks_for(trace.device_kind)
    flops = bytes_ = 0.0
    for rec in rounds:
        f, b = work(cell.config, **round_of(cell, rec))
        flops, bytes_ = flops + f, bytes_ + b
    least = max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    t0, t1 = trace.window
    took = max(picked_ns(ops, lambda o: o.scope == scope, t0, t1)
               for ops in scoped.values()) / 1e9
    # the rounds' work is spread over the chips
    return None if took <= 0 else 100.0 * least / len(scoped) / took
