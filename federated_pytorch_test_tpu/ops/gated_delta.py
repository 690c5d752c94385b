"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464), chunked.

Per head, with state ``S [d_k, d_v]``, for ``t = 0 .. T-1``::

    S <- exp(g_t) S
    S <- S + k_t (x) beta_t (v_t - S^T k_t)
    o_t = S^T q_t

:func:`gated_delta_stepwise` is that recurrence, token by token, in
float32.  :func:`gated_delta_chunked` computes the same in chunks of
``chunk`` tokens (the WY form: inside a chunk the ``chunk`` rank-one
updates are folded into one unit-lower-triangular solve, and only the
chunk boundaries carry ``S``), so the work is matrix products.
Sequences that are no multiple of ``chunk`` are padded at the end with
``beta = 0, g = 0`` steps, which leave ``S`` alone.

The chunked form has two parts.  The chunk-local part (decays, the
triangular inverse, the chunk's pseudo-values ``u``, the decayed keys
``w``, ``q k^T``) is plain XLA, and its backward pass is JAX's transpose
of it but for the triangular inverse, whose derivative is the closed form
``-T^T dT T^T`` under a ``jax.custom_vjp`` (:func:`_unit_lower_inverse`:
two products and ``T`` alone kept, where the transpose of the Neumann
product runs nineteen and keeps every power).  The recurrence over the
chunks::

    v_new = u - w S;   o = q_in S + qk v_new;   S <- g_last S + k_out^T v_new

runs on a TPU as a pair of Pallas kernels under one ``jax.custom_vjp``
(:func:`_scan_kernel` forward, :func:`_scan_grad_kernel` backward with
the chunks in reverse): the grid is ``(heads / heads per step, chunks)``
with the chunk axis sequential, and ``S`` (``dS`` in the backward
kernel) stays in VMEM scratch from one chunk to the next, where a
``lax.scan`` sends it through HBM at every step.  The forward rule
keeps ``S`` at each chunk's start for the backward kernel, which
recomputes ``v_new`` from it.  Which path runs is decided per call from
what the call shows (:func:`plan`): the kernels on a TPU for a two- or
four-byte ``dtype``, head widths that are multiples of 128 or fill three
quarters of a lane tile (96-wide keys, 192-wide values: full-width blocks,
no padding in HBM), a chunk that
is a multiple of 8 and blocks that fit the VMEM budget; the ``lax.scan``
everywhere else (the CPU, a one-byte ``dtype``, odd widths), with the
same arithmetic.  Tests run the kernels on the CPU in interpret mode
(:func:`force_gdn_scan_impl`).

On the kernel path, where no gradient is asked, the whole rule is one
more Pallas kernel (:func:`_fused_kernel`): a grid step loads a chunk's
``q``, ``k``, ``v`` and the rows of its cumulative decay and ``beta``,
does the chunk-local part in VMEM in the XLA path's order and at its
precisions (the ten inverse products at ``HIGHEST`` among them), then the
recurrence step, and writes ``o`` alone.  It is the primal function of a
``jax.custom_vjp`` whose forward rule is ``jax.vjp`` of the path above,
so a gradient runs the XLA chunk-local part and the kernel pair as
before; under ``jax.checkpoint`` the primal pass runs the fused kernel
(``optimize_remat``: the forward rule's residuals would be thrown away)
and the rematerialised forward the forward rule.  :func:`plan` says
whether its blocks fit, and with how many heads a step.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops.moe import operand

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
_LANE = 128                 # head widths the kernels take: multiples of this
_SUBLANE = 8                # and chunks that are multiples of this
_HEADS = 8                  # heads a grid step handles, at most
# budget for plan()'s estimate of the larger (backward) kernel's blocks,
# under the 16 MiB of scoped VMEM Mosaic allows a kernel on the v5e
_VMEM_BUDGET = 12 * 2**20
# the program's scope for this module (models/qwen3_next.py opens it
# around the forward call; a custom_vjp's backward rule is traced outside
# it, so the rules, and the fused primal that a checkpointed gradient
# traces apart, open it again and a trace still finds their ops)
_SCOPE = "gdn_scan"

# None = by the backend; "pallas_interpret" stands in for a TPU in tests
_FORCE_IMPL = None


@contextlib.contextmanager
def force_gdn_scan_impl(impl: str):
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


def gated_delta_stepwise(q, k, v, g, beta):
    """``q, k [T, d_k]``, ``v [T, d_v]``, ``g, beta [T]`` of ONE head ->
    ``o [T, d_v]``; float32 throughout (vmap it over heads)."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt) * S
        S = S + jnp.outer(kt, bt * (vt - jnp.dot(kt, S, precision=_HI)))
        return S, jnp.dot(qt, S, precision=_HI)

    S0 = jnp.zeros((k.shape[-1], v.shape[-1]), jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)
    _, o = lax.scan(step, S0, (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return o


def _mm(x, y):
    return jnp.einsum("...ij,...jk->...ik", x, y, precision=_HI)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a [..., C, C]``:
    ``a`` is nilpotent, so the Neumann series ends and equals the product
    ``(I - a)(I + a^2)(I + a^4)...`` of ``log2 C`` factors: matrix
    products instead of ``C`` steps of forward substitution.  Its
    derivative is that of an inverse, ``dT = -T da T``, not the transpose
    of the product factor by factor."""
    return _doubling(a, jnp.eye(a.shape[-1], dtype=a.dtype), _mm)


def _doubling(a, eye, mm):
    """``(I - a)(I + a^2)(I + a^4)...`` with the products ``mm``: here
    and in the fused kernel, which has no ``jnp.eye``."""
    inv, power = eye - a, a
    span = 2
    while span < a.shape[-1]:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        span *= 2
    return inv


def _unit_lower_inverse_fwd(a):
    inv = _unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, d_inv):
    """``-T^T dT T^T`` for any cotangent ``dT``, full or triangular (the
    caller's mask on ``a`` transposes to the mask on this)."""
    with scope(_SCOPE):
        inv_t = jnp.swapaxes(inv, -1, -2)
        return (-_mm(inv_t, _mm(d_inv, inv_t)),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = 64,
                        dtype=jnp.bfloat16):
    """Heads-first: ``q, k [H, T, d_k]``, ``v [H, T, d_v]``, ``g, beta
    [H, T]`` (``q`` already scaled, ``q`` and ``k`` already normalised)
    -> ``o [H, T, d_v]`` float32.  The products with ``S``, of ``q`` with
    ``k`` and of the chunk's own values run in ``dtype`` with float32
    sums; decays, the triangular inverse and ``S`` stay float32."""
    H, T, dk = k.shape
    dv = v.shape[-1]
    pad = (-T) % chunk
    if pad:
        z = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)
    p = plan(H, (T + pad) // chunk, chunk, dk, dv, dtype)
    how = (p["impl"], p["heads"], chunk, dtype)
    if p["fused_heads"]:
        o = _delta_rule(p["fused_heads"], *how, q, k, v, g, beta)
    else:
        o = _chunked(*how, q, k, v, g, beta)
    return o[:, :T]


def _chunked(impl, heads, chunk, dtype, q, k, v, g, beta):
    """The chunk-local part in XLA, then the recurrence (the ``lax.scan``
    or the kernel pair) over ``T``, a multiple of ``chunk``."""
    H, T, dk = k.shape
    dv = v.shape[-1]
    N = T // chunk
    f32 = lambda a: a.astype(jnp.float32)
    cut = lambda a: f32(a).reshape(H, N, chunk, *a.shape[2:])
    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    ein = functools.partial(_ein, dtype)

    gc = jnp.cumsum(g, axis=-1)                        # [H, N, C]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    # exp(gc_i - gc_j) for i >= j; masked BEFORE exp so the upper half
    # (positive differences) cannot overflow into the gradient
    diff = jnp.where(tri, gc[..., :, None] - gc[..., None, :], 0.0)
    decay = jnp.where(tri, jnp.exp(diff), 0.0)         # [H, N, C, C]
    kb = k * beta[..., None]
    a = jnp.where(strict, jnp.einsum("hnid,hnjd->hnij", kb, k,
                                     precision=_HI) * decay, 0.0)
    inv = _unit_lower_inverse(a)                       # (I + a)^-1
    # the chunk's pseudo-values and the decayed keys that meet S
    u = jnp.einsum("hnij,hnjd->hnid", inv, v * beta[..., None],
                   precision=_HI)
    w = jnp.einsum("hnij,hnjd->hnid", inv, kb * jnp.exp(gc)[..., None],
                   precision=_HI)
    qk = jnp.where(tri, ein("hnid,hnjd->hnij", q, k) * decay, 0.0)
    q_in = q * jnp.exp(gc)[..., None]                  # q_i exp(gc_i)
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]  # k_i exp(gc_C - gc_i)
    g_last = jnp.exp(gc[..., -1])                      # [H, N]

    if impl == "xla":
        first = lambda a: jnp.moveaxis(a, 1, 0)        # chunks lead
        S0 = jnp.zeros((H, dk, dv), jnp.float32)
        _, o = lax.scan(functools.partial(_step, dtype), S0,
                        tuple(map(first, (u, w, qk, q_in, k_out, g_last))))
        o = jnp.moveaxis(o, 0, 1)
    else:
        # the rounding `_step` applies to its operands, applied once
        cast = lambda a: operand(a, dtype)
        o = _recurrence(heads, impl == "pallas_interpret",
                        u, cast(w), cast(qk), cast(q_in), cast(k_out),
                        jnp.broadcast_to(g_last[..., None, None],
                                         (H, N, 1, dv)))
    return o.reshape(H, T, dv)


def _ein(dtype, spec, a, b):
    return jnp.einsum(spec, operand(a, dtype), operand(b, dtype),
                      preferred_element_type=jnp.float32)


def _step(dtype, S, x):
    """One chunk of the recurrence, all heads: ``S [H, d_k, d_v]``, ``x``
    the chunk's ``(u, w, qk, q_in, k_out, g_last)`` -> ``(S', o)``."""
    u_n, w_n, qk_n, q_n, k_n, gl = x
    ein = functools.partial(_ein, dtype)
    v_new = u_n - ein("hid,hde->hie", w_n, S)
    o = ein("hid,hde->hie", q_n, S) + ein("hij,hje->hie", qk_n, v_new)
    S = S * gl[:, None, None] + ein("hid,hie->hde", k_n, v_new)
    return S, o


# ----------------------------------------------------------------------
# the recurrence as a Pallas kernel pair
# ----------------------------------------------------------------------
def _lanes(d: int) -> int:
    """``d`` rounded up to whole 128-lane tiles, as Mosaic lays it out."""
    return -(-d // _LANE) * _LANE


def _takes_width(d: int) -> bool:
    """A head width the kernels take: a multiple of 128, or a full-width
    block of a multiple of 32 that fills at least three quarters of its
    lane tiles (96, 192: Mosaic pads such a block to 128 / 256 lanes in
    VMEM, and the padding's share is what the roofline share loses)."""
    return d % _LANE == 0 or (d % 32 == 0 and 4 * d >= 3 * _lanes(d))


def plan(H: int, N: int, chunk: int, dk: int, dv: int, dtype) -> dict:
    """What :func:`gated_delta_chunked` runs for the recurrence of ``H``
    heads over ``N`` chunks on the current backend, and what decided it:
    ``impl`` ("pallas" | "pallas_interpret" | "xla"), ``heads`` a grid
    step handles and the backward kernel's VMEM estimate for them; and
    for the whole rule where no gradient is asked, ``fused_heads`` a
    step of the fused kernel handles (0: the primal pass runs the
    chunk-local part and the kernel pair too), its own VMEM estimate
    and ``fused_why``."""
    b = jnp.dtype(dtype).itemsize
    backend = _FORCE_IMPL or ("pallas" if jax.default_backend() == "tpu"
                              else "xla")
    out = {"impl": "xla", "heads": 0, "vmem_bytes": 0,
           "vmem_budget": _VMEM_BUDGET, "fused_heads": 0,
           "fused_vmem_bytes": 0, "fused_why": "no kernel pair"}
    if backend == "xla":
        return dict(out, why="no TPU")
    if b not in (2, 4):
        return dict(out, why=f"{jnp.dtype(dtype).name} operands")
    if not (_takes_width(dk) and _takes_width(dv)) or chunk % _SUBLANE:
        return dict(out, why="head widths neither multiples of 128 nor "
                             "three quarters of a lane tile, or chunk no "
                             "multiple of 8")
    exceed = "one head's blocks exceed the VMEM budget"
    for heads in range(min(_HEADS, H), 0, -1):
        need = _grad_vmem_bytes(heads, chunk, dk, dv, b)
        if H % heads == 0 and need <= _VMEM_BUDGET:
            out = dict(out, impl=backend, heads=heads, vmem_bytes=need,
                       why="fits", fused_why=exceed)
            break
    else:
        return dict(out, why=exceed)
    for heads in range(min(_HEADS, H), 0, -1):
        need = _fused_vmem_bytes(heads, chunk, dk, dv)
        if H % heads == 0 and need <= _VMEM_BUDGET:
            return dict(out, fused_heads=heads, fused_vmem_bytes=need,
                        fused_why="fits")
    return out


def _grad_vmem_bytes(heads: int, C: int, dk: int, dv: int, b: int) -> int:
    """VMEM estimate for ``_scan_grad_kernel`` (the larger of the two):
    per head the blocks in (``u``, ``do``, ``S``, ``w``, ``q_in``,
    ``k_out``, ``qk``, ``g_last``'s row) and out (``du``, ``dw``,
    ``dq_in``, ``dk_out``, ``dqk``, ``dg_last``'s row), double-buffered
    by the pipeline; ``dS`` in scratch; per head about four ``[d_k,
    d_v]`` and six ``[C, d]`` float32 temporaries; widths as laid out in
    whole lane tiles."""
    dk, dv = _lanes(dk), _lanes(dv)
    d = max(dk, dv)
    row = 4 * _SUBLANE * dv
    blocks_in = 2 * 4 * C * dv + 4 * dk * dv + b * (3 * C * dk + C * C) + row
    blocks_out = 4 * C * dv + b * (3 * C * dk + C * C) + row
    return heads * (2 * (blocks_in + blocks_out) + 4 * dk * dv
                    + 4 * (4 * dk * dv + 6 * C * d))


def _fused_vmem_bytes(heads: int, C: int, dk: int, dv: int) -> int:
    """VMEM estimate for ``_fused_kernel``: per head the blocks in (``q``,
    ``k``, ``v`` float32, the rows of the cumulative decay and ``beta``,
    ``g_last``'s row) and out (``o``), double-buffered by the pipeline;
    ``S`` in scratch; per head about eight ``[C, C]`` float32 temporaries
    (decays, ``a``, the inverse and its factors, ``q k^T``), ten ``[C,
    d]`` and two ``[d_k, d_v]``; widths as laid out in whole lane tiles,
    a row in whole sublane tiles."""
    dk, dv, c = _lanes(dk), _lanes(dv), _lanes(C)
    d = max(dk, dv)
    blocks_in = 4 * (2 * C * dk + C * dv + _SUBLANE * (c + dv))
    blocks_out = 4 * C * dv
    return heads * (2 * (blocks_in + blocks_out) + 4 * dk * dv
                    + 4 * (8 * C * c + 10 * C * d + 2 * dk * dv))


def _bmm(spec, a, b):
    """Product of ``[heads, ., .]`` blocks, one per head, operands as they
    are, float32 sums.  Float32 operands follow the process's default
    matmul precision, as the scan's products do; for two-byte operands a
    precision asks Mosaic for what it does not have."""
    precision = None if a.dtype.itemsize == 4 else lax.Precision.DEFAULT
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=_F32)


def _scan_kernel(save, u_ref, w_ref, qk_ref, q_ref, k_ref, gl_ref, o_ref,
                 *rest):
    """One chunk of a grid step's heads.  ``rest`` is ``(states_ref,
    s_ref)`` when the chunk's incoming ``S`` is kept for the backward
    kernel, else ``(s_ref,)``; ``s_ref [heads, d_k, d_v]`` float32 is the
    scratch that carries ``S`` along the chunk axis."""
    s_ref = rest[-1]
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    S = s_ref[...]
    if save:
        rest[0][...] = S
    Sb = S.astype(dt)
    vb = (u_ref[...] - _bmm("hid,hde->hie", w_ref[...], Sb)).astype(dt)
    o_ref[...] = (_bmm("hid,hde->hie", q_ref[...], Sb)
                  + _bmm("hij,hje->hie", qk_ref[...], vb))
    s_ref[...] = S * gl_ref[...] + _bmm("hid,hie->hde", k_ref[...], vb)


def _scan_grad_kernel(u_ref, w_ref, qk_ref, q_ref, k_ref, gl_ref,
                      states_ref, do_ref, du_ref, dw_ref, dqk_ref, dq_ref,
                      dk_ref, dgl_ref, ds_ref):
    """The transpose of one chunk of a grid step's heads; the grid walks
    the chunks in reverse and ``ds_ref [heads, d_k, d_v]`` float32
    carries the cotangent of ``S``.  ``dgl_ref`` takes ``S * dS'`` summed
    over ``d_k`` only: a lane-dense row, as ``gl_ref`` is."""
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    S, dS = states_ref[...], ds_ref[...]
    Sb, dSb = S.astype(dt), dS.astype(dt)
    w, k, dob = w_ref[...], k_ref[...], do_ref[...].astype(dt)
    vb = (u_ref[...] - _bmm("hid,hde->hie", w, Sb)).astype(dt)
    dv = (_bmm("hij,hie->hje", qk_ref[...], dob)
          + _bmm("hid,hde->hie", k, dSb))
    dvb = dv.astype(dt)
    du_ref[...] = dv
    dw_ref[...] = (-_bmm("hie,hde->hid", dvb, Sb)).astype(dt)
    dqk_ref[...] = _bmm("hie,hje->hij", dob, vb).astype(dt)
    dq_ref[...] = _bmm("hie,hde->hid", dob, Sb).astype(dt)
    dk_ref[...] = _bmm("hie,hde->hid", vb, dSb).astype(dt)
    dgl_ref[...] = jnp.sum(S * dS, axis=1, keepdims=True)
    ds_ref[...] = (_bmm("hid,hie->hde", q_ref[...], dob) + dS * gl_ref[...]
                   - _bmm("hid,hie->hde", w, dvb))


def _chunk_call(kernel, heads, interpret, ins, outs, at):
    """``kernel`` over the grid ``(H / heads, N)``: every operand and
    output is ``[H, N, r, c]`` and a grid step sees ``heads`` heads of
    chunk ``at(n)`` of each, the chunk axis squeezed; one float32
    ``[heads, d_k, d_v]`` scratch carries the state along the chunks."""
    H, N = ins[0].shape[:2]
    dk, dv = ins[1].shape[-1], ins[0].shape[-1]
    spec = lambda a: pl.BlockSpec((heads, None) + tuple(a.shape[2:]),
                                  lambda h, n: (h, at(n), 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(H // heads, N),
        in_specs=[spec(a) for a in ins],
        out_specs=[spec(o) for o in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _recurrence(heads, interpret, u, w, qk, q_in, k_out, gl):
    """``u [H, N, C, d_v]`` float32; ``w, q_in, k_out [H, N, C, d_k]`` and
    ``qk [H, N, C, C]`` already in the products' dtype; ``gl [H, N, 1,
    d_v]``, ``g_last`` as lane-dense rows -> ``o [H, N, C, d_v]`` float32.
    Where no gradient is asked no ``S`` is written out."""
    return _scan(heads, interpret, False, u, w, qk, q_in, k_out, gl)[0]


def _scan(heads, interpret, save, *ins):
    """``[o]`` and, with ``save``, ``S`` at each chunk's start
    ``[H, N, d_k, d_v]`` after it."""
    (H, N, _, dv), dk = ins[0].shape, ins[1].shape[-1]
    outs = [jax.ShapeDtypeStruct(ins[0].shape, _F32)]
    if save:
        outs.append(jax.ShapeDtypeStruct((H, N, dk, dv), _F32))
    return _chunk_call(functools.partial(_scan_kernel, save), heads,
                       interpret, ins, outs, lambda n: n)


def _recurrence_fwd(heads, interpret, *ins):
    o, states = _scan(heads, interpret, True, *ins)
    return o, (*ins, states)


def _recurrence_bwd(heads, interpret, res, do):
    N = do.shape[1]
    # one cotangent per operand, in its shape and dtype; ``gl``'s rows
    # hold ``S * dS'`` summed over ``d_k``, and JAX's transpose of the
    # caller's broadcast sums them over ``d_v``
    outs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in res[:-1]]
    with scope(_SCOPE):
        return tuple(_chunk_call(_scan_grad_kernel, heads, interpret,
                                 (*res, do), outs, lambda n: N - 1 - n))


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


# ----------------------------------------------------------------------
# the whole rule as one kernel, where no gradient is asked
# ----------------------------------------------------------------------
def _hi(spec, a, b):
    """Product of float32 blocks at the highest precision, as ``_mm``."""
    return jnp.einsum(spec, a, b, precision=_HI, preferred_element_type=_F32)


def _fused_kernel(dtype, q_ref, k_ref, v_ref, r_ref, gl_ref, o_ref, s_ref):
    """One chunk of a grid step's heads, the chunk-local part and the
    recurrence step in VMEM: ``q, k [heads, C, d_k]``, ``v [heads, C,
    d_v]``, the chunk's cumulative decay and ``beta`` as the rows of
    ``r_ref [heads, 2, C]``, and ``g_last`` as the lane-dense row ``gl_ref
    [heads, 1, d_v]`` that :func:`_scan_kernel` reads -> ``o_ref [heads,
    C, d_v]``; ``s_ref [heads, d_k, d_v]`` float32 carries ``S`` along
    the chunk axis.  The arithmetic of :func:`_chunked`, then of
    :func:`_scan_kernel`, in their order and at their precisions."""
    C = q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    q, k, v = (r[...].astype(_F32) for r in (q_ref, k_ref, v_ref))
    i = lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    j = lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    tri, strict, eye = i >= j, i > j, i == j
    # a row [heads, 1, C] as a column [heads, C, 1]: one term a sum, exact
    # (a column block would fill a 128-lane tile of HBM per value)
    col = lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=-1,
                              keepdims=True)
    gr = r_ref[:, 0:1, :]
    gc, beta = col(gr), col(r_ref[:, 1:2, :])
    last = gc[:, C - 1:, :]                            # [heads, 1, 1]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, gc - gr, 0.0)), 0.0)
    kb = k * beta
    a = jnp.where(strict, _hi("hid,hjd->hij", kb, k) * decay, 0.0)
    inv = _doubling(a, eye.astype(_F32),
                    functools.partial(_hi, "hij,hjk->hik"))
    u = _hi("hij,hjd->hid", inv, v * beta)
    w = _hi("hij,hjd->hid", inv, kb * jnp.exp(gc))
    qk = jnp.where(tri, _bmm("hid,hjd->hij", q.astype(dtype),
                             k.astype(dtype)) * decay, 0.0)
    q_in, k_out = q * jnp.exp(gc), k * jnp.exp(last - gc)

    S = s_ref[...]
    Sb = S.astype(dtype)
    vb = (u - _bmm("hid,hde->hie", w.astype(dtype), Sb)).astype(dtype)
    o_ref[...] = (_bmm("hid,hde->hie", q_in.astype(dtype), Sb)
                  + _bmm("hij,hje->hie", qk.astype(dtype), vb))
    s_ref[...] = S * gl_ref[...] + _bmm("hid,hie->hde", k_out.astype(dtype),
                                        vb)


def _fused(heads, interpret, chunk, dtype, q, k, v, g, beta):
    """``q, k [H, T, d_k]``, ``v [H, T, d_v]``, ``g, beta [H, T]``, ``T``
    a multiple of ``chunk`` -> ``o [H, T, d_v]`` float32 through
    :func:`_fused_kernel` over the grid ``(H / heads, T / chunk)``; only
    the cumulative sum of ``g`` within each chunk, and ``g_last``'s row
    (Mosaic broadcasts along sublanes or lanes, not both at once), are
    XLA's."""
    H, T, dk = k.shape
    dv = v.shape[-1]
    N = T // chunk
    cut = lambda a: a.astype(_F32).reshape(H, N, chunk)
    gc = jnp.cumsum(cut(g), axis=-1)
    tile = lambda d: pl.BlockSpec((heads, chunk, d), lambda h, n: (h, n, 0))
    rows = lambda r, c: pl.BlockSpec((heads, None, r, c),
                                     lambda h, n: (h, n, 0, 0))
    return pl.pallas_call(
        functools.partial(_fused_kernel, dtype),
        grid=(H // heads, N),
        in_specs=[tile(dk), tile(dk), tile(dv), rows(2, chunk), rows(1, dv)],
        out_specs=tile(dv),
        out_shape=jax.ShapeDtypeStruct((H, T, dv), _F32),
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, jnp.stack([gc, cut(beta)], axis=2),
      jnp.broadcast_to(jnp.exp(gc[..., -1])[..., None, None], (H, N, 1, dv)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _delta_rule(fused, impl, heads, chunk, dtype, q, k, v, g, beta):
    """The kernel path over ``T``, a multiple of ``chunk``: where no
    gradient is asked (the primal pass under ``jax.checkpoint``) one
    kernel, ``fused`` heads a step, that writes ``o`` alone; under a
    gradient :func:`_chunked` as it is, whose ``jax.vjp`` closure is the
    residual, so the rematerialised forward and the backward pass run the
    kernel pair and the inverse's rule.  Under ``jax.checkpoint`` the
    primal pass runs this function in place of the forward rule, whose
    residuals it would throw away (``optimize_remat``), traced outside the
    caller's scope: it opens ``gdn_scan`` again."""
    with scope(_SCOPE), scope("gdn_fused"):
        return _fused(fused, impl == "pallas_interpret", chunk, dtype,
                      q, k, v, g, beta)


def _delta_rule_fwd(fused, impl, heads, chunk, dtype, *ins):
    return jax.vjp(functools.partial(_chunked, impl, heads, chunk, dtype),
                   *ins)


def _delta_rule_bwd(fused, impl, heads, chunk, dtype, vjp, do):
    with scope(_SCOPE):
        return vjp(do)


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd, optimize_remat=True)
