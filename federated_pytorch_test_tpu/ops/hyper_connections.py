"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): the residual path as ``n`` streams.

A sub-layer ``F`` reads one learned, input-dependent contraction of the
streams and writes back through a doubly stochastic mixing of them::

    v      = rms(vec(X))                      X in R^{n x C} per token
    P~     = a_pre  * (v phi_pre)  + b_pre    H_pre  = sigmoid(P~)     [n]
    Q~     = a_post * (v phi_post) + b_post   H_post = 2 sigmoid(Q~)   [n]
    R~     = a_res  * mat(v phi_res) + b_res  H_res  = sinkhorn(R~)    [n, n]
    X'     = H_res X + H_post^T F(H_pre X)

Everything here is float32 (the projections at the highest precision):
the maps are small outputs that decide much, as a router's are.

**Layout.**  The streams are ``X [n, ..., C]``: the stream axis leads, so
each stream is a dense ``[tokens, C]`` matrix and the mixing is ``n x n``
scaled adds of such matrices.  (With the stream axis second to last the
TPU would tile a ``[4, C]`` minor pair and pad it to eight sublanes:
twice the memory and twice the traffic of every pass over the streams.)
The maps have the tokens LAST, ``[n, ...]`` and ``[n, n, ...]``: the
Sinkhorn iterations are then elementwise over lanes of tokens and their
row and column sums are adds of whole vectors.

**Two lowerings of one algorithm**, chosen per call from what the call
shows (:func:`plan`).  A sub-layer is :func:`pre`, ``F``, :func:`expand`.
Everywhere (the CPU, a width that is no multiple of 128) they are the
``jax.numpy`` lines of :func:`maps`, :func:`contract` and the stacked
sums, differentiated by JAX.  On a TPU each is one ``jax.custom_vjp``
whose passes are Pallas kernels over tiles of tokens that read the
streams ONCE and write their result once:

- ``pre``'s forward kernel: the sum of squares, the projection onto the
  ``2 n + n^2`` columns, ``H_pre`` and ``u = H_pre X`` from one read of
  the tile; it hands the streams through, so that every use of ``X`` in
  a sub-layer goes through one op and JAX adds no cotangents of
  ``[n, tokens, C]`` arrays.  Sigmoids of ``H_post``, Sinkhorn and the
  marginal error stay ``jax.numpy`` on ``[24, tokens]`` arrays.
- ``expand``'s forward kernel reads ``X``, ``y`` and writes ``X'``; its
  rule's kernel reads ``dX'``, ``X``, ``y`` once and writes ``dy``, the
  maps' cotangents and ``H_res^T dX'``, the cotangent of the streams
  handed through.
- ``pre``'s rule's kernel reads ``X``, ``du`` and that cotangent and
  writes ONE ``dX = H_res^T dX' + H_pre du + g phi^T - (rms term) X``.

The kernels see the small per-token arrays tokens-major and lane-padded,
``[tokens, 128]`` (a *pack*: one value a token row in a fixed lane), and
read a lane of a pack as a column by a masked sum.  The projection's
float32 product at the highest precision is three bfloat16 pieces of
each operand with float32 sums, as XLA's is, but the pieces of the small
operand lie side by side in the 128 lanes (forward: ``phi``'s three
pieces as 72 columns; backward: five and four pairs of pieces along the
contraction), so all nine pairs cost three passes of the MXU forward
and two backward.  Tests run the kernels on the CPU in interpret mode
(:func:`force_mhc_impl`).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from federated_pytorch_test_tpu.obs.scopes import scope

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_LANE = 128                 # stream widths the kernels take: multiples of this
_ROWS = 8                   # token rows a step of a kernel's loops holds
# token rows a grid step holds, at most (32 / 64 / 128 at 4 x 3,584 on the
# v5e: a sub-layer forward, rematerialised and backward 3.71 / 3.43 / 3.40
# ms; 256 does not fit the backward kernel's three stream tiles)
_TILE = 128
# what plan() lets the largest kernel's blocks take and what every kernel
# asks Mosaic for (the v5e has 128 MiB of VMEM, 16 MiB of it by default)
_VMEM_BUDGET = 96 * 2**20

# None = by the backend; "pallas_interpret" stands in for a TPU in tests
_FORCE_IMPL = None


@contextlib.contextmanager
def force_mhc_impl(impl: str):
    """Take ``impl`` ("pallas" | "pallas_interpret" | "xla") for the
    backend's answer: tests run the kernels on the CPU in interpret
    mode.  The rules on ``dtype`` and shapes still decide
    (:func:`plan`)."""
    global _FORCE_IMPL
    prev, _FORCE_IMPL = _FORCE_IMPL, impl
    try:
        yield
    finally:
        _FORCE_IMPL = prev


def plan(n: int, tokens: int, C: int, dtype=_F32) -> dict:
    """What :func:`pre` and :func:`expand` run for ``n`` streams of width
    ``C`` over ``tokens`` tokens on the current backend, and what decided
    it: ``impl`` ("pallas" | "pallas_interpret" | "xla"), the kernels'
    ``tile`` (token rows a grid step holds) and the largest kernel's VMEM
    estimate."""
    backend = _FORCE_IMPL or ("pallas" if jax.default_backend() == "tpu"
                              else "xla")
    out = {"impl": "xla", "tile": 0, "vmem_bytes": 0,
           "vmem_budget": _VMEM_BUDGET}
    if backend == "xla":
        return dict(out, why="no TPU")
    if jnp.dtype(dtype) != jnp.dtype(_F32):
        return dict(out, why=f"{jnp.dtype(dtype).name} streams")
    if C % _LANE:
        return dict(out, why="stream width no multiple of 128")
    if 5 * (2 * n + n * n) > _LANE:
        return dict(out, why="the maps' columns exceed a pack's lanes")
    # a multiple of 16 (a bfloat16 tile's rows), no longer than the tokens
    tile = min(_TILE, -(-tokens // 16) * 16)
    while tile > 16 and _vmem_bytes(n, tile, C) > _VMEM_BUDGET:
        tile //= 2
    need = _vmem_bytes(n, tile, C)
    if need > _VMEM_BUDGET:
        return dict(out, why="a tile exceeds the VMEM budget")
    return dict(out, impl=backend, tile=tile, vmem_bytes=need, why="fits")


def _vmem_bytes(n: int, tile: int, C: int) -> int:
    """VMEM estimate for ``_pre_bwd_kernel`` (the largest): the streams,
    the cotangent handed through and ``dX``, ``du``, both stacks of
    ``phi`` and five packs, all double-buffered by the pipeline, and
    three packs of scratch."""
    streams = 3 * n * tile * C * 4 + tile * C * 4
    phis = 2 * n * _LANE * C * 2
    return 2 * (streams + phis + 5 * tile * _LANE * 4) + 3 * tile * _LANE * 4


class Maps(NamedTuple):
    """The three maps of one sub-layer for every token (tokens last)."""

    pre: jnp.ndarray           # [n, ...]    H_pre, in (0, 1)
    post: jnp.ndarray          # [n, ...]    H_post, in (0, 2)
    res: jnp.ndarray           # [n, n, ...] H_res[j, i]: stream i into j
    marginal_err: jnp.ndarray  # (): worst |row sum - 1| or |column sum - 1|


def sinkhorn(logits, iters: int, eps: float):
    """``logits [n, n, ...]`` (already clamped) -> ``exp`` of them
    projected towards the doubly stochastic matrices: ``iters`` times
    every column divided by its sum plus ``eps``, then every row.  The
    matrix lies in the two LEADING axes (``[row, column, ...]``)."""
    m = jnp.exp(logits.astype(_F32))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def _phi(leaves):
    """``[n C, 2 n + n^2]``: the three projections side by side."""
    return jnp.concatenate([leaves["phi_pre"], leaves["phi_post"],
                            leaves["phi_res"]], axis=1)


def _maps_of(proj, leaves, n, tokens, iters, eps, clamp) -> Maps:
    """The maps from ``proj [2 n + n^2, ...]``, the projections of
    ``v``."""
    over = lambda a: a.reshape(a.shape + (1,) * len(tokens))
    pre = leaves["a_pre"][0] * proj[:n] + over(leaves["b_pre"])
    post = leaves["a_post"][0] * proj[n:2 * n] + over(leaves["b_post"])
    res = leaves["a_res"][0] * proj[2 * n:].reshape((n, n) + tokens) \
        + over(leaves["b_res"])
    h_res = sinkhorn(jnp.clip(res, clamp[0], clamp[1]), iters, eps)
    err = lax.stop_gradient(jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0)),
        jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0))))
    return Maps(jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res, err)


def maps(x, leaves: Dict[str, jnp.ndarray], *, iters: int, eps: float,
         clamp=(-30.0, 30.0), norm_eps: float = 1e-6) -> Maps:
    """The maps of the streams ``x [n, ..., C]`` under one sub-layer's
    ``leaves``: ``phi_pre, phi_post [n C, n]``, ``phi_res [n C, n n]``
    (rows in ``vec(X)``'s order, stream by stream; ``phi_res``'s columns
    row by row), ``a_pre, a_post, a_res [1]``, ``b_pre, b_post [n]``,
    ``b_res [n, n]``.  Plain ``jax.numpy`` on every backend."""
    n, C = x.shape[0], x.shape[-1]
    tokens = x.shape[1:-1]
    with scope("mhc_maps"):
        x = x.astype(_F32)
        # rms over all n C entries of a token; it scales the projections
        # (v phi = r * (vec(X) phi)), so v itself is never written
        r = lax.rsqrt(jnp.sum(x * x, axis=(0, -1)) / (n * C) + norm_eps)
        proj = jnp.einsum("n...c,ncm->m...", x, _phi(leaves).reshape(n, C, -1),
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=_F32) * r
        return _maps_of(proj, leaves, n, tokens, iters, eps, clamp)


def contract(h_pre, x):
    """``H_pre X``: the sub-layer's input ``[..., C]`` from the streams
    ``x [n, ..., C]`` and ``h_pre [n, ...]``."""
    with scope("mhc_mix"):
        return jnp.sum(h_pre[..., None] * x, axis=0)


def pre(x, leaves: Dict[str, jnp.ndarray], *, iters: int, eps: float,
        clamp=(-30.0, 30.0), norm_eps: float = 1e-6):
    """The first half of a sub-layer: ``(u, maps, x)`` with ``u = H_pre
    X`` the sub-layer's input, the :class:`Maps` of :func:`maps` and the
    streams handed through for :func:`expand` (use them, not ``x``:
    the kernels' rule then writes the streams' cotangent once)."""
    n, C = x.shape[0], x.shape[-1]
    tokens = x.shape[1:-1]
    p = plan(n, math.prod(tokens), C, x.dtype)
    if p["impl"] == "xla":
        m = maps(x, leaves, iters=iters, eps=eps, clamp=clamp,
                 norm_eps=norm_eps)
        return contract(m.pre, x), m, x
    with scope("mhc_maps"):
        m_ = 2 * n + n * n
        cfg = (n, C, p["tile"], p["impl"] == "pallas_interpret", norm_eps)
        u, pack, xt = _pre(cfg, x.reshape(n, -1, C), _phi(leaves),
                           leaves["a_pre"], leaves["b_pre"])
        proj = (pack[:, :m_] * pack[:, m_:m_ + 1]).T.reshape((m_,) + tokens)
        return (u.reshape(tokens + (C,)),
                _maps_of(proj, leaves, n, tokens, iters, eps, clamp),
                xt.reshape(x.shape))


def expand(h_res, h_post, x, y):
    """``H_res X + H_post^T y``: the next streams ``[n, ..., C]`` from
    the streams ``x``, the sub-layer's output ``y [..., C]``, ``h_res [n,
    n, ...]`` and ``h_post [n, ...]``."""
    n, C = x.shape[0], x.shape[-1]
    p = plan(n, math.prod(x.shape[1:-1]), C, x.dtype)
    with scope("mhc_mix"):
        if p["impl"] == "xla":
            return jnp.stack([
                sum(h_res[j, i][..., None] * x[i] for i in range(n))
                + h_post[j][..., None] * y for j in range(n)])
        cfg = (n, p["tile"], p["impl"] == "pallas_interpret")
        cols = jnp.concatenate([h_res.reshape(n * n, -1),
                                h_post.reshape(n, -1)]).astype(_F32).T
        # a pack: [tokens, 128], zeros after the n n + n columns
        pack = jnp.pad(cols, ((0, 0), (0, _LANE - cols.shape[1])))
        out = _expand(cfg, x.reshape(n, -1, C),
                      y.reshape(-1, C).astype(_F32), pack)
        return out.reshape(x.shape)


# ----------------------------------------------------------------------
# the kernels
# ----------------------------------------------------------------------
def _lane(rows: int):
    return lax.broadcasted_iota(jnp.int32, (rows, _LANE), 1)


def _col(pack, lane, c: int):
    """Lane ``c`` of ``pack [rows, 128]`` as a column ``[rows, 1]``."""
    return jnp.sum(jnp.where(lane == c, pack, 0.0), axis=1, keepdims=True)


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _pieces(x):
    """``x`` float32 as three bfloat16 pieces whose sum is ``x`` exactly
    (8 + 8 + 8 bits of mantissa: what a product at the highest
    precision takes of each operand).  Cut by masking bits, not by
    rounding to bfloat16 and back: outside a kernel XLA takes a float32
    -> bfloat16 -> float32 round trip for the identity on a TPU
    (``xla_allow_excess_precision``), the rest would be zero and ``x``
    one piece."""
    def cut(a):
        bits = lax.bitcast_convert_type(a, jnp.int32) & jnp.int32(-65536)
        return lax.bitcast_convert_type(bits, _F32)

    hi = cut(x)
    mid = cut(x - hi)
    return (hi.astype(_BF16), mid.astype(_BF16),
            ((x - hi) - mid).astype(_BF16))


def _mm(a, b, dims):
    """``dot_general`` of two bfloat16 tiles, float32 sums."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=_F32)


_NN, _NT = ((1,), (0,)), ((1,), (1,))


def _rows(k, rows: int = _ROWS):
    return pl.ds(pl.multiple_of(k * rows, rows), rows)


# The loops below hold ``_ROWS`` token rows of the whole width a step
# (``[8, C]``: 28 registers a value at 3,584) and read a ref again at
# every use, so that no value lives across the step; written out per
# lane chunk the kernels traced and lowered 28 times as many equations,
# which cost the cell three times its warm set-up.
def _pre_kernel(n, C, norm_eps, s_ref, x_ref, phi_ref, u_ref, pack_ref,
                hi_ref, mid_ref, lo_ref):
    """One tile of tokens: ``x_ref [n, tile, C]``, ``phi_ref [n, 128, C]``
    bfloat16 (rows ``[0, m)``, ``[m, 2 m)``, ``[2 m, 3 m)``: the three
    pieces of ``phi^T``), ``s_ref`` (SMEM) ``a_pre, b_pre[0..n)`` ->
    ``u_ref [tile, C]`` and ``pack_ref``: lanes ``[0, m)`` the raw
    projections ``vec(X) phi``, lane ``m`` the reciprocal rms ``r``."""
    m = 2 * n + n * n
    tile = u_ref.shape[0]
    prod = jnp.zeros((tile, _LANE), _F32)
    for i in range(n):
        def split(k, carry):
            rows = _rows(k, 16)         # a bfloat16 tile's rows
            hi_ref[rows, :], mid_ref[rows, :], lo_ref[rows, :] = \
                _pieces(x_ref[i, rows, :])
            return carry

        lax.fori_loop(0, tile // 16, split, None)
        # lane group g of the sum holds x . (piece g of phi): the small
        # pieces first
        prod = prod + (_mm(lo_ref[...], phi_ref[i], _NT)
                       + _mm(mid_ref[...], phi_ref[i], _NT)
                       + _mm(hi_ref[...], phi_ref[i], _NT))
    raw = pltpu.roll(prod, _LANE - 2 * m, 1) + pltpu.roll(prod, _LANE - m, 1) \
        + prod
    pack_ref[...] = jnp.where(_lane(tile) < m, raw, 0.0)
    a = s_ref[0]

    def mix(k, carry):
        rows = _rows(k)
        lane = _lane(_ROWS)
        pack = pack_ref[rows, :]
        ss = sum(jnp.sum(x_ref[i, rows, :] * x_ref[i, rows, :], axis=1,
                         keepdims=True) for i in range(n))
        r = lax.rsqrt(ss / (n * C) + norm_eps)
        h = [_sigmoid(a * (_col(pack, lane, i) * r) + s_ref[1 + i])
             for i in range(n)]
        u = h[0] * x_ref[0, rows, :]
        for i in range(1, n):
            u = u + h[i] * x_ref[i, rows, :]
        u_ref[rows, :] = u
        pack_ref[rows, :] = jnp.where(lane == m, r, pack)
        return carry

    lax.fori_loop(0, tile // _ROWS, mix, None)


def _expand_kernel(n, x_ref, y_ref, map_ref, o_ref):
    """One tile of tokens: ``o[j] = sum_i H_res[j, i] x[i] + H_post[j]
    y``; ``map_ref``: lanes ``[0, n n)`` ``H_res`` row by row, then
    ``H_post``."""
    def mix(k, carry):
        rows = _rows(k)
        lane = _lane(_ROWS)
        pack = map_ref[rows, :]
        for j in range(n):
            o = _col(pack, lane, j * n) * x_ref[0, rows, :]
            for i in range(1, n):
                o = o + _col(pack, lane, j * n + i) * x_ref[i, rows, :]
            o_ref[j, rows, :] = o + _col(pack, lane, n * n + j) \
                * y_ref[rows, :]
        return carry

    lax.fori_loop(0, o_ref.shape[1] // _ROWS, mix, None)


def _expand_bwd_kernel(n, g_ref, x_ref, y_ref, map_ref, dy_ref, dx_ref,
                       dmap_ref):
    """The transpose of :func:`_expand_kernel` for one tile: from ``g_ref
    [n, tile, C]``, the cotangent of its output, ``dy = sum_j H_post[j]
    g[j]``, ``dx[i] = sum_j H_res[j, i] g[j]`` and, in ``map_ref``'s
    lanes, ``<g[j], x[i]>`` and ``<g[j], y>``."""
    def step(k, carry):
        rows = _rows(k)
        lane = _lane(_ROWS)
        pack = map_ref[rows, :]

        def mixed(cols):
            d = _col(pack, lane, cols[0]) * g_ref[0, rows, :]
            for j in range(1, n):
                d = d + _col(pack, lane, cols[j]) * g_ref[j, rows, :]
            return d

        dy_ref[rows, :] = mixed([n * n + j for j in range(n)])
        for i in range(n):
            dx_ref[i, rows, :] = mixed([j * n + i for j in range(n)])
        out = jnp.zeros((_ROWS, _LANE), _F32)
        for j in range(n):
            for i in range(n + 1):
                other = y_ref[rows, :] if i == n else x_ref[i, rows, :]
                dot = jnp.sum(g_ref[j, rows, :] * other, axis=1,
                              keepdims=True)
                out = jnp.where(lane == (n * n + j if i == n else j * n + i),
                                dot, out)
        dmap_ref[rows, :] = out
        return carry

    lax.fori_loop(0, dy_ref.shape[0] // _ROWS, step, None)


def _pre_bwd_kernel(n, C, s_ref, x_ref, du_ref, dxt_ref, pack_ref, ct_ref,
                    b1_ref, b2_ref, dx_ref, out_ref, h_ref, a1_ref, a2_ref):
    """The transpose of :func:`_pre_kernel` for one tile.  ``du_ref
    [tile, C]``, ``dxt_ref [n, tile, C]`` (the cotangent of the streams
    handed through) and ``ct_ref`` (of the pack) come in; ``dx_ref``
    takes ALL of the streams' cotangent; ``out_ref``: lanes ``[0, m)``
    ``g``, the raw projections' whole cotangent (what ``phi``'s gradient
    needs), lanes ``[m, m + n)`` the cotangent of ``H_pre``'s logits.
    ``b1_ref, b2_ref [n, 128, C]`` bfloat16: pieces of ``phi^T`` in the
    order the pieces of ``g`` meet them (:func:`_phi_stacks`)."""
    m = 2 * n + n * n
    tile = du_ref.shape[0]
    a = s_ref[0]

    def small(k, carry):
        rows = _rows(k)
        lane = _lane(_ROWS)
        pack, ct = pack_ref[rows, :], ct_ref[rows, :]
        r = _col(pack, lane, m)
        g = jnp.where(lane < m, ct, 0.0)
        dr = _col(ct, lane, m)
        hs = dl = jnp.zeros((_ROWS, _LANE), _F32)
        for i in range(n):
            raw = _col(pack, lane, i)
            h = _sigmoid(a * (raw * r) + s_ref[1 + i])
            d = jnp.sum(du_ref[rows, :] * x_ref[i, rows, :], axis=1,
                        keepdims=True) * h * (1.0 - h)
            g = g + jnp.where(lane == i, (a * d) * r, 0.0)
            dr = dr + (a * d) * raw
            hs = jnp.where(lane == i, h, hs)
            dl = jnp.where(lane == m + i, d, dl)
        # r = (ss / (n C) + eps)^(-1/2): dr/dx = -r^3 x / (n C)
        h_ref[rows, :] = jnp.where(lane == n, dr * (r * r * r) / -(n * C), hs)
        out_ref[rows, :] = g + dl
        hi, mid, lo = (p.astype(_F32) for p in _pieces(g))
        a1_ref[rows, :] = hi + pltpu.roll(hi, m, 1) + pltpu.roll(mid, 2 * m, 1) \
            + pltpu.roll(hi, 3 * m, 1) + pltpu.roll(lo, 4 * m, 1)
        a2_ref[rows, :] = mid + pltpu.roll(mid, m, 1) + pltpu.roll(lo, 2 * m, 1) \
            + pltpu.roll(lo, 3 * m, 1)
        return carry

    lax.fori_loop(0, tile // _ROWS, small, None)
    a1, a2 = a1_ref[...].astype(_BF16), a2_ref[...].astype(_BF16)
    for i in range(n):
        dx_ref[i] = _mm(a2, b2_ref[i], _NN) + _mm(a1, b1_ref[i], _NN)

    def add(k, carry):
        rows = _rows(k)
        lane = _lane(_ROWS)
        hs = h_ref[rows, :]
        for i in range(n):
            dx_ref[i, rows, :] = dxt_ref[i, rows, :] + (
                _col(hs, lane, i) * du_ref[rows, :]
                + (dx_ref[i, rows, :] + _col(hs, lane, n) * x_ref[i, rows, :]))
        return carry

    lax.fori_loop(0, tile // _ROWS, add, None)


def _phi_stacks(phi, n, C, groups):
    """``[n, 128, C]`` bfloat16 for each of ``groups``: a group names,
    by index into ``phi``'s three pieces, which piece's transpose lies
    in rows ``[k m, (k + 1) m)``."""
    pieces = _pieces(phi.astype(_F32))
    out = []
    for group in groups:
        rows = jnp.concatenate([pieces[g] for g in group], axis=1)
        rows = jnp.pad(rows, ((0, 0), (0, _LANE - rows.shape[1])))
        out.append(rows.reshape(n, C, _LANE).transpose(0, 2, 1))
    return out


def _spec(tile, shape, dtype):
    """How a kernel sees an operand: ``[n, T, C]`` or ``[T, c]`` one tile
    of tokens at a time (the last may be ragged: every row of a kernel
    is a token on its own), ``[n, 128, C]`` bfloat16 whole."""
    if len(shape) == 2:
        return pl.BlockSpec((tile, shape[1]), lambda t: (t, 0))
    if shape[1] == _LANE and dtype == _BF16:
        return pl.BlockSpec(shape, lambda t: (0, 0, 0))
    return pl.BlockSpec((shape[0], tile, shape[2]), lambda t: (0, t, 0))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5), inline=True)
def _call(kernel, static, tile, interpret, outs, scratch, scalars, ins):
    """``kernel(*static, *refs)`` over tiles of ``tile`` tokens
    (:func:`_spec`); ``scalars`` go to SMEM; ``outs`` and ``scratch`` are
    ``(shape, dtype)`` pairs.  Jitted and inlined, so that a program
    which calls a kernel at forty places traces its body once:
    ``pallas_call`` itself keeps no trace."""
    T = outs[0][0][-2]
    spec = functools.partial(_spec, tile)
    return pl.pallas_call(
        functools.partial(kernel, *static),
        grid=(pl.cdiv(T, tile),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars)
        + [spec(a.shape, a.dtype) for a in ins],
        out_specs=[spec(*o) for o in outs],
        out_shape=[jax.ShapeDtypeStruct(*o) for o in outs],
        scratch_shapes=[pltpu.VMEM(*sc) for sc in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BUDGET),
        interpret=interpret,
    )(*scalars, *ins)


def _f32(*shape):
    return shape, _F32


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pre(cfg, x, phi, a_pre, b_pre):
    """``x [n, T, C]`` -> ``(u [T, C], pack [T, 128], x)``."""
    return _pre_fwd(cfg, x, phi, a_pre, b_pre)[0]


def _pre_fwd(cfg, x, phi, a_pre, b_pre):
    n, C, tile, interpret, norm_eps = cfg
    T = x.shape[1]
    (stack,) = _phi_stacks(phi, n, C, [(0, 1, 2)])
    u, pack = _call(
        _pre_kernel, (n, C, norm_eps), tile, interpret,
        (_f32(T, C), _f32(T, _LANE)), (((tile, C), _BF16),) * 3,
        (jnp.concatenate([a_pre, b_pre]).astype(_F32),), (x, stack))
    return (u, pack, x), (x, phi, a_pre, b_pre, pack)


def _pre_bwd(cfg, res, cts):
    n, C, tile, interpret, _ = cfg
    x, phi, a_pre, b_pre, pack = res
    du, dpack, dxt = cts
    m = 2 * n + n * n
    T = x.shape[1]
    # the pieces of g along the lanes meet these pieces of phi^T: (hi,
    # hi), (hi, mid), (mid, hi), (hi, lo), (lo, hi); then (mid, mid),
    # (mid, lo), (lo, mid), (lo, lo)
    b1, b2 = _phi_stacks(phi, n, C, [(0, 1, 0, 2, 0), (1, 2, 1, 2)])
    dx, out = _call(
        _pre_bwd_kernel, (n, C), tile, interpret,
        (_f32(n, T, C), _f32(T, _LANE)), (_f32(tile, _LANE),) * 3,
        (jnp.concatenate([a_pre, b_pre]).astype(_F32),),
        (x, du, dxt, pack, dpack, b1, b2))
    g, dl = out[:, :m], out[:, m:m + n]
    dphi = jnp.einsum("ntc,tm->ncm", x, g, precision=lax.Precision.HIGHEST,
                      preferred_element_type=_F32).reshape(phi.shape)
    da = jnp.sum(dl * pack[:, :n] * pack[:, m:m + 1]).reshape(a_pre.shape)
    return dx, dphi.astype(phi.dtype), da.astype(a_pre.dtype), \
        jnp.sum(dl, axis=0).astype(b_pre.dtype)


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _expand(cfg, x, y, cols):
    """``x [n, T, C]``, ``y [T, C]``, ``cols [T, 128]`` (``H_res`` row by
    row, then ``H_post``) -> the next streams ``[n, T, C]``."""
    return _expand_fwd(cfg, x, y, cols)[0]


def _expand_fwd(cfg, x, y, cols):
    n, tile, interpret = cfg
    (out,) = _call(_expand_kernel, (n,), tile, interpret,
                   (_f32(*x.shape),), (), (), (x, y, cols))
    return out, (x, y, cols)


def _expand_bwd(cfg, res, g):
    n, tile, interpret = cfg
    x, y, cols = res
    dy, dx, dcols = _call(
        _expand_bwd_kernel, (n,), tile, interpret,
        (_f32(*y.shape), _f32(*x.shape), _f32(*cols.shape)), (), (),
        (g, x, y, cols))
    return dx, dy, dcols


_expand.defvjp(_expand_fwd, _expand_bwd)
