"""Causal softmax attention with grouped query heads, blockwise.

Per key/value head ``g`` and each of the ``rep`` query heads it serves::

    s_tu = q_t . k_u  (u <= t);   a = softmax_u(s);   o_t = sum_u a_tu v_u

:func:`causal_attention` takes ``q`` already scaled, normed and rotated.
The value heads may be narrower or wider than the key heads (latent
attention with 192-wide keys and 128-wide values).
Which path runs is decided per call from what the call shows
(:func:`plan`): on a TPU, for a two- or four-byte ``dtype``, a value
width that is a multiple of 128, a sequence that is a multiple of the
kernels' key block and blocks inside the VMEM budget, a pair of Pallas
kernels under one ``jax.custom_vjp``; everywhere else (the CPU, a
one-byte ``dtype``, odd shapes) the XLA path: query blocks of ``block``
against all keys under the mask, each block rematerialised.  A key width
that is no multiple of 128 reaches the kernels with zero columns appended
to ``q`` and ``k`` up to the next multiple (:func:`plan`'s ``pad_k``):
a zero adds nothing to a score, so the result is exact, and the columns'
gradient is dropped by the padding's own transpose.  Both do the same
arithmetic at the same precision: products in ``dtype`` with float32
sums, maximum, exponent, sum and the output's normalisation in float32,
the probabilities rounded to ``dtype`` only as the operand of ``a v``.

The kernels (the "flash" form, arXiv:2205.14135): a grid step holds one
query block of ALL ``rep`` heads of a group as ``rep * block_q`` rows, so
the group's heads share one load of their key/value head, which stays in
VMEM for the whole group.  It walks the key blocks at or below the
diagonal only (above it nothing is multiplied; on the diagonal block the
mask is applied to the score tile in registers), and a score tile never
leaves VMEM.  Both kernels hold a score tile with the keys in rows,
``[block_k, rows]``: the softmax's maximum and sum run down the sublanes,
and the running maximum and sum, the log-sum-exp and ``sum(o * do)`` are
lane-dense rows ``[1, rows]``; ``o`` and its cotangent are therefore
held transposed, ``[d, rows]``.  The forward kernel returns ``o`` and
each row's log-sum-exp.  The backward kernel recomputes the score tiles
and adds into ``dq`` for its rows and into ``dk``, ``dv`` of the whole
key/value head, which stay in VMEM while the grid walks the group's
query blocks: the group's heads are summed there in float32.  Tests run
the kernels on the CPU in interpret mode (:func:`force_attn_impl`).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from federated_pytorch_test_tpu.obs import scopes
from federated_pytorch_test_tpu.ops.moe import operand

_F32 = jnp.float32
_LANE = 128                 # head widths the kernels take: multiples of this
_ROWS = 1024                # query rows (heads x block_q) a grid step holds
_BLOCK_K = 256              # keys a loop step visits, at most
# what plan() lets the backward kernel's blocks take and what both kernels
# ask Mosaic for (the v5e has 128 MiB of VMEM, 16 MiB of it by default)
_VMEM_BUDGET = 64 * 2**20
_MASKED = -1e30             # a score above the diagonal: exp() gives 0.0

# None = by the backend; "pallas_interpret" stands in for a TPU in tests
_FORCE_IMPL = None


@contextlib.contextmanager
def force_attn_impl(impl: str):
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


def plan(T: int, n_kv: int, rep: int, d: int, dtype, dv: int = 0) -> dict:
    """What :func:`causal_attention` runs for ``n_kv`` key/value heads
    with keys of width ``d`` and values of width ``dv`` (``d`` where not
    given), ``rep`` query heads each, over ``T`` tokens on the current
    backend, and what decided it: ``impl`` ("pallas" | "pallas_interpret"
    | "xla"), the kernels' ``block_q`` (queries of one head a grid step
    holds) and ``block_k``, ``pad_k`` (zero columns appended to ``q`` and
    ``k``) and the backward kernel's VMEM estimate."""
    dv = dv or d
    b = jnp.dtype(dtype).itemsize
    backend = _FORCE_IMPL or ("pallas" if jax.default_backend() == "tpu"
                              else "xla")
    out = {"impl": "xla", "block_q": 0, "block_k": 0, "pad_k": 0,
           "vmem_bytes": 0, "vmem_budget": _VMEM_BUDGET}
    if backend == "xla":
        return dict(out, why="no TPU")
    if b not in (2, 4):
        return dict(out, why=f"{jnp.dtype(dtype).name} operands")
    if dv % _LANE:
        return dict(out, why="head width no multiple of 128")
    pad_k = (-d) % _LANE
    block_k = next((c for c in (_BLOCK_K, _LANE) if T % c == 0), 0)
    if not block_k:
        return dict(out, why="sequence no multiple of the kernels' blocks")
    # powers of two, so block_q divides block_k and the diagonal is one
    # key block; at least a sublane tile of the operands
    block_q = block_k
    while rep * block_q > _ROWS and block_q > 32 // b:
        block_q //= 2
    need = _grad_vmem_bytes(T, rep * block_q, block_k, d + pad_k, b, dv)
    if need > _VMEM_BUDGET:
        return dict(out, why="blocks exceed the VMEM budget")
    return dict(out, impl=backend, block_q=block_q, block_k=block_k,
                pad_k=pad_k, vmem_bytes=need, why="fits")


def _grad_vmem_bytes(T: int, rows: int, block_k: int, d: int, b: int,
                     dv: int = 0) -> int:
    """VMEM estimate for ``_grad_kernel`` (the larger of the two): the
    head's ``k``, ``v`` and float32 ``dk``, ``dv`` and the step's ``q``,
    ``do``, ``dq`` and two rows, all double-buffered by the pipeline, and
    about five ``[block_k, rows]`` float32 tiles (keys ``d`` wide, values
    ``dv``)."""
    dv = dv or d
    head = T * (d + dv) * (b + 4)
    step = rows * (d * (b + 4) + dv * b) + 2 * 8 * rows * 4
    return 2 * (head + step) + 5 * block_k * rows * 4


def causal_attention(q, k, v, *, dtype=jnp.bfloat16, block: int = 512,
                     scope: str = "gated_attn"):
    """``q [T, n_kv, rep, d]`` (scaled, normed, rotated), ``k [T, n_kv,
    d]``, ``v [T, n_kv, dv]`` -> ``o [T, n_kv, rep, dv]`` float32: query
    head ``(g, r)`` attends to key/value head ``g`` at its own and
    earlier positions.  ``block``
    is the XLA path's query block.  ``scope`` is the caller's
    ``jax.named_scope`` around this call: a custom_vjp's backward rule is
    traced outside it, so the rule opens it again and a trace still finds
    the backward kernel under the mixer's name."""
    T, n_kv, rep, d = q.shape
    dv = v.shape[-1]
    p = plan(T, n_kv, rep, d, dtype, dv)
    with scopes.scope("attn_layout"):
        kc, vc = operand(k, dtype), operand(v, dtype)
    if p["impl"] == "xla":
        return _blocks_against_all_keys(q, kc, vc, dtype, block)
    bq, nq = p["block_q"], T // p["block_q"]
    with scopes.scope("attn_layout"):
        qc = operand(q, dtype)
        if p["pad_k"]:
            qc, kc = (jnp.pad(a, ((0, 0),) * (a.ndim - 1)
                              + ((0, p["pad_k"]),)) for a in (qc, kc))
            d += p["pad_k"]
        # a group's heads side by side as the rows of one query block
        qr = qc.reshape(nq, bq, n_kv, rep, d).transpose(
            2, 0, 3, 1, 4).reshape(n_kv, nq, rep * bq, d)
        kt, vt = kc.transpose(1, 0, 2), vc.transpose(1, 0, 2)
    o = _attention(bq, p["block_k"], p["impl"] == "pallas_interpret", scope,
                   qr, kt, vt)
    with scopes.scope("attn_layout"):
        return o.reshape(n_kv, nq, dv, rep, bq).transpose(
            1, 4, 0, 3, 2).reshape(T, n_kv, rep, dv)


def _blocks_against_all_keys(q, kc, vc, dtype, block):
    """The XLA path: a map over query blocks of ``block``, each against
    all keys under the mask and rematerialised in the backward pass (one
    block's scores are ``[n_kv, rep, block, T]`` float32)."""
    T, n_kv, rep, d = q.shape
    bq = min(block, T)
    pad = (-T) % bq
    with scopes.scope("attn_layout"):
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
            -1, bq, n_kv, rep, d)
    pos_k = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qb, start = args                       # [bq, n_kv, rep, d]
        s = jnp.einsum("qgrd,kgd->grqk", operand(qb, dtype), kc,
                       preferred_element_type=_F32)
        seen = (start + jnp.arange(bq))[:, None] >= pos_k[None, :]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", operand(a, dtype), vc,
                          preferred_element_type=_F32)

    starts = jnp.arange(qp.shape[0]) * bq
    o = lax.map(one, (qp, starts))
    with scopes.scope("attn_layout"):
        return o.reshape(-1, n_kv, rep, vc.shape[-1])[:T]


# ----------------------------------------------------------------------
# the kernel pair
# ----------------------------------------------------------------------
def _mm(a, b, dims):
    """``dot_general`` of two tiles, operands as they are, float32 sums.
    Float32 operands follow the process's default matmul precision, as
    the XLA path's products do; for two-byte operands a precision asks
    Mosaic for what it does not have."""
    precision = None if a.dtype.itemsize == 4 else lax.Precision.DEFAULT
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=_F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _walk(i, bq, bk, visit):
    """``visit(keys, diagonal)`` for the key blocks query block ``i``
    sees: those below the diagonal, then the one that holds it."""
    at = lambda j: pl.ds(pl.multiple_of(j * bk, bk), bk)
    last = (i * bq) // bk

    def below(j, carry):
        visit(at(j), None)
        return carry

    lax.fori_loop(0, last, below, None)
    visit(at(last), last * bk)


def _seen(i, bq, diagonal, shape):
    """``[bk, rows]``: key ``diagonal + row`` is at or before the query
    of column ``col`` (position ``i * bq + col % bq``)."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    return diagonal + row <= i * bq + (col & (bq - 1))


def _attn_kernel(bq, bk, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref):
    """One query block of a group's heads, ``q_ref [rows, d]`` with row
    ``r`` at position ``i * bq + r % bq``, against ``k_ref [T, d]`` and
    ``v_ref [T, dv]``; score tiles have the keys in rows, ``[bk, rows]``,
    so the running maximum ``m_ref`` and sum ``l_ref`` and the
    log-sum-exp ``lse_ref`` are lane-dense rows ``[1, rows]`` and ``o_ref
    [dv, rows]`` float32, the running output, is transposed."""
    i = pl.program_id(1)
    q = q_ref[...]
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)

    def visit(keys, diagonal):
        s = _mm(k_ref[keys, :], q, _NT)                    # [bk, rows]
        if diagonal is not None:
            s = jnp.where(_seen(i, bq, diagonal, s.shape), s, _MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        v = v_ref[keys, :]
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
        o_ref[...] = alpha * o_ref[...] + _mm(v, p.astype(v.dtype), _TN)

    _walk(i, bq, bk, visit)
    l = l_ref[...]
    o_ref[...] = o_ref[...] / l
    lse_ref[...] = m_ref[...] + jnp.log(l)


def _grad_kernel(bq, bk, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                 dq_ref, dk_ref, dv_ref):
    """The transpose of :func:`_attn_kernel` for one query block of a
    group's heads, ``do_ref [dv, rows]`` the cotangent of its ``o_ref``;
    score tiles are recomputed.  ``dk_ref [T, d]`` and ``dv_ref [T, dv]``
    float32 are the whole key/value head's and stay in VMEM along the
    query blocks."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q, do = q_ref[...], do_ref[...]
    lse, di = lse_ref[...], di_ref[...]                    # [1, rows]
    dq_ref[...] = jnp.zeros_like(dq_ref)

    def visit(keys, diagonal):
        k, v = k_ref[keys, :], v_ref[keys, :]
        s = _mm(k, q, _NT)                                 # [bk, rows]
        if diagonal is not None:
            s = jnp.where(_seen(i, bq, diagonal, s.shape), s, _MASKED)
        p = jnp.exp(s - lse)
        dv_ref[keys, :] += _mm(p.astype(do.dtype), do, _NT)
        ds = (p * (_mm(v, do, _NN) - di)).astype(q.dtype)
        dk_ref[keys, :] += _mm(ds, q, _NN)
        dq_ref[...] += _mm(ds, k, _TN)

    _walk(i, bq, bk, visit)


def _call(kernel, interpret, semantics, ins, outs, scratch=()):
    """``kernel`` over the grid ``(n_kv, query blocks)``: an operand
    ``[n_kv, nq, r, c]`` is seen one query block at a time, an operand
    ``[n_kv, T, d]`` one whole head at a time."""
    n_kv, nq = ins[0].shape[:2]

    def spec(a):
        if a.ndim == 4:
            return pl.BlockSpec((None, None) + tuple(a.shape[2:]),
                                lambda g, i: (g, i, 0, 0))
        return pl.BlockSpec((None,) + tuple(a.shape[1:]),
                            lambda g, i: (g, 0, 0))

    return pl.pallas_call(
        kernel,
        grid=(n_kv, nq),
        in_specs=[spec(a) for a in ins],
        out_specs=[spec(o) for o in outs],
        out_shape=outs,
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_VMEM_BUDGET),
        interpret=interpret,
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _attention(bq, bk, interpret, scope, q, k, v):
    """``q [n_kv, nq, rep * bq, d]``, ``k [n_kv, T, d]``, ``v [n_kv, T,
    dv]``, all in the products' dtype -> ``o [n_kv, nq, dv, rep * bq]``
    float32."""
    return _forward(bq, bk, interpret, q, k, v)[0]


def _forward(bq, bk, interpret, q, k, v):
    """``o`` (transposed) and the rows' log-sum-exp ``[n_kv, nq, 1, rep *
    bq]``."""
    n_kv, nq, rows, _ = q.shape
    outs = [jax.ShapeDtypeStruct((n_kv, nq, v.shape[-1], rows), _F32),
            jax.ShapeDtypeStruct((n_kv, nq, 1, rows), _F32)]
    return _call(functools.partial(_attn_kernel, bq, bk), interpret,
                 ("parallel", "parallel"), (q, k, v), outs,
                 [pltpu.VMEM((1, rows), _F32)] * 2)


def _attention_fwd(bq, bk, interpret, scope, q, k, v):
    o, lse = _forward(bq, bk, interpret, q, k, v)
    return o, (q, k, v, o, lse)


def _attention_bwd(bq, bk, interpret, scope, res, do):
    q, k, v, o, lse = res
    with jax.named_scope(scope):
        di = jnp.sum(o * do, axis=2, keepdims=True)
        outs = [jax.ShapeDtypeStruct(q.shape, _F32),
                jax.ShapeDtypeStruct(k.shape, _F32),
                jax.ShapeDtypeStruct(v.shape, _F32)]
        dq, dk, dv = _call(functools.partial(_grad_kernel, bq, bk),
                           interpret, ("parallel", "arbitrary"),
                           (q, k, v, do.astype(q.dtype), lse, di), outs)
        # one cotangent per operand, in its dtype
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_attention.defvjp(_attention_fwd, _attention_bwd)
