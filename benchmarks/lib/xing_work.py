"""The ``xing4_0`` cell's work model and scope list.

**Work.**  The least operations and bytes a round needs, from the
configuration's keys and the round's counts, as ``glm_work.py`` words
it: what ANY implementation must do, never what this one does: no
rematerialised forward pass, no padding of a 192-wide key to 256, no
masked half of a causal product, the streams read once and written once
a sub-layer and pass.  A share computed from these cannot pass 100 %.

The parts of a step in forward order, with the block that owns their
weights: layer ``l``'s latent attention at ``2 l`` (block ``1 + 2 l``),
its dense MLP or expert layer at ``2 l + 1`` (block ``2 + 2 l``), then
the head (block ``2 L + 1``).  Block ``0`` is the embedding.  Every part
but the head is a sub-layer wired into the streams by its own
hyper-connection leaves, which lie in the part's block.

**Scopes.**  The model's ``jax.named_scope`` names, read from a trace's
``tf_op`` stat as ``glm_work.py`` reads GLM-4.7-Flash's: an op belongs
to the innermost listed scope its path holds, and besides to the
hyper-connections if its path passes through ``mhc``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.lib import glm_work, peaks, scopes, xplane

# what latent attention, the dense MLP, the expert layer and the head
# need is counted as for the sibling: the same keys, the same products
from benchmarks.lib.glm_work import (  # noqa: F401
    dense_mlp_flops,
    head_flops,
    mla_weight_flops,
    moe_dense_flops,
    pair_flops,
    picked_ns,
)


# ----------------------------------------------------------------------
# per-token forward work of what this configuration adds
# ----------------------------------------------------------------------
def _streams(cfg) -> int:
    return int(cfg["hc_mult"]) * int(cfg["hidden_size"])


def mhc_flops(cfg) -> float:
    """One sub-layer's hyper-connections per token, forward: the three
    projections of ``vec(X)`` (``n C x (2 n + n^2)``), the contraction
    (``n C``) and the expansion (``n^2 C + n C``), two operations a
    multiply-add."""
    n = int(cfg["hc_mult"])
    return 2.0 * _streams(cfg) * (2 * n + n * n) + 2.0 * _streams(cfg) \
        + 2.0 * _streams(cfg) * (n + 1)


def mhc_bytes(cfg, backward: bool) -> float:
    """One sub-layer's float32 streams per token: read once and written
    once forward; backward the cotangent read, the streams read once
    more (the maps' and the mixing's gradients need them) and their
    cotangent written."""
    return 4.0 * _streams(cfg) * (3 if backward else 2)


def mla_core_flops(cfg, seq_len: int) -> float:
    """Causal ``q k^T`` at the key width and ``a v`` at the value width
    per token, unpadded, averaged over the sequence: each token meets
    ``(T + 1) / 2`` keys."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * (seq_len + 1) / 2.0


def mla_core_bytes(cfg) -> float:
    """One pass per token over ``q, k, v`` in (two bytes) and ``o`` out
    (float32)."""
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * (
        2.0 * (2 * dk + cfg["v_head_dim"]) + 4.0 * cfg["v_head_dim"])


# ----------------------------------------------------------------------
# a round
# ----------------------------------------------------------------------
def parts(cfg) -> List[Tuple[str, int]]:
    """``(kind, owning block)`` of every part of a step in forward order:
    kinds ``mla`` / ``mlp`` / ``moe`` / ``head``."""
    L = int(cfg["layers"])
    out = []
    for l in range(L):
        out += [("mla", 1 + 2 * l),
                ("mlp" if l < int(cfg["first_k_dense_replace"]) else "moe",
                 2 + 2 * l)]
    return out + [("head", 2 * L + 1)]


def needs(cfg, block: int) -> List[Tuple[bool, bool]]:
    """Per part of :func:`parts`: ``(the activation's gradient, the
    weights' gradient)`` a round with ``block`` active needs: the
    gradient reaches every part at or after the active block's (all of
    them for the embedding; of the head block the head alone)."""
    ps = parts(cfg)
    first = next((i for i, (_, b) in enumerate(ps) if b == block), 0)
    return [(i >= first, b == block) for i, (_, b) in enumerate(ps)]


def round_flops(cfg, block: int, tokens: int, pairs_local: int,
                seq_len: int) -> float:
    """Forward and backward of ``tokens`` tokens with ``block`` active,
    the hyper-connections' products among them.  ``pairs_local`` counts
    token-expert pairs over all expert layers, so the experts' share is
    exact."""
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
        else:
            weighted = head_flops(cfg) * tokens
        if kind != "head":
            # the mixing has no weight (two operands' gradients, as the
            # attention core); the projections have phi
            n = int(cfg["hc_mult"])
            proj = 2.0 * _streams(cfg) * (2 * n + n * n) * tokens
            weighted += proj
            core += mhc_flops(cfg) * tokens - proj
        total += weighted + core
        if act:
            total += weighted + 2.0 * core
        if wgt:
            # the router is in no block: no gradient of its weight
            total += weighted - (2.0 * cfg["hidden_size"] * tokens
                                 * cfg["n_routed_experts"]
                                 if kind == "moe" else 0.0)
    return total


def mhc_work(cfg, block: int, tokens: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of the hyper-connections of one round:
    forward in every sub-layer; backward where the gradient reaches (the
    projections' product once more for the streams' gradient, the
    mixing's twice for its two operands') and once more the projections'
    for ``phi`` in the active block."""
    flops = bytes_ = 0.0
    n = int(cfg["hc_mult"])
    proj = 2.0 * _streams(cfg) * (2 * n + n * n)
    mix = mhc_flops(cfg) - proj
    for (kind, _), (act, wgt) in zip(parts(cfg), needs(cfg, block)):
        if kind == "head":
            continue
        flops += tokens * (proj + mix + (proj + 2.0 * mix if act else 0.0)
                           + (proj if wgt else 0.0))
        bytes_ += tokens * (mhc_bytes(cfg, False)
                            + (mhc_bytes(cfg, True) if act else 0.0))
    return flops, bytes_


def mla_core_work(cfg, block: int, tokens: int, seq_len: int
                  ) -> Tuple[float, float]:
    """``(operations, bytes)`` of the attention cores of one round:
    forward in every mixer; backward (twice the forward's products; a
    second pass over the operands and their gradients) in the mixers the
    gradient reaches."""
    flops = bytes_ = 0.0
    for (kind, _), (act, _) in zip(parts(cfg), needs(cfg, block)):
        if kind == "mla":
            flops += tokens * mla_core_flops(cfg, seq_len) * (3 if act else 1)
            bytes_ += tokens * mla_core_bytes(cfg) * (3 if act else 1)
    return flops, bytes_


round_of = glm_work.round_of


# ----------------------------------------------------------------------
# scopes
# ----------------------------------------------------------------------
#: innermost first
SCOPES = ("mla_core", "moe_experts", "moe_route", "moe_shared",
          "lm_head_loss", "dense_mlp", "mla_attn", "mhc_maps", "mhc_mix",
          "mhc")
_THROUGH_MHC = re.compile(r"(^|/)mhc(/|$)")


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
    mhc: bool          # the op's path passes through the ``mhc`` scope


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
                    bool(_THROUGH_MHC.search(op_path))))
        out[plane.name] = ops
    return out


_LOADED: Dict[str, Dict[str, List[ScopedOp]]] = {}


def of_cell(cell) -> Optional[Dict[str, List[ScopedOp]]]:
    """The scoped ops of the cell's traced pass, or None without a trace.
    Read once per file; the first reading prints every scope's device
    seconds over the whole trace as ``scope_seconds={...}`` (first chip;
    ``mhc_all`` is every op through ``mhc``, whichever scope owns it)."""
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
        sec["mhc_all"] = round(picked_ns(first, lambda o: o.mhc, t0, t1)
                               / 1e9, 4)
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


def roofline_pct(cell, trace, records, pick, work) -> Optional[float]:
    """100 x the least time the chip could take for the traced pass's
    work (the larger of operations / peak and bytes / bandwidth;
    ``work(config, **round) -> (operations, bytes)``) over the device
    time of the ops with ``pick(op)``, worst chip."""
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
    took = max(picked_ns(ops, pick, t0, t1) for ops in scoped.values()) / 1e9
    # the rounds' work is spread over the chips
    return None if took <= 0 else 100.0 * least / len(scoped) / took
