"""Fused InfoNCE (CPC contrastive loss) as a Pallas TPU kernel.

The reference computes the (P x P) normalised inner-product matrix with
nested Python loops (federated_cpc.py:149-180); the framework's XLA path
(ops/infonce_core.py) is one matmul + log-softmax.  This module fuses the
whole per-row pipeline into ONE kernel so the score matrix never leaves
VMEM:

    scores_tile = (Z_tile^T @ Zhat) / (||Z_tile|| ||Zhat||)   (MXU)
    log_p_row   = diag(scores) - logsumexp_row(scores)        (VPU)

i.e. column norms, the Gram matmul, the numerically-stable row softmax
and the positive-pair (diagonal) gather all happen in one VMEM residency
— the [P, P] matrix is never materialised in HBM.  The grid tiles rows of
the score matrix (T=128 = MXU edge); each program reads its [D, T] column
slab of Z plus the full [D, P] Zhat.

Gradients: the op carries a ``jax.custom_vjp`` with a hand-derived
backward built from the saved ``log_p`` residual (one matmul to rebuild
the score matrix — unavoidable, the softmax Jacobian needs it — but no
forward re-run and no logsumexp recompute), so the kernel drops into the
CPC training closure (LBFGS re-evaluates value_and_grad inside
``lax.while_loop``) with no tracing restrictions and no extra forward.
The backward is ALSO a Pallas kernel (``_grad_kernel``): the training
path calls ``value_and_grad`` on every LBFGS closure evaluation, so the
backward dominates wall-clock — it rebuilds each [T, P] score-matrix
row tile in VMEM, forms the softmax-Jacobian product there, and writes
only the [D, P] gradients to HBM (the XLA backward materialises several
P x P intermediates).  The dZhat term needs a sum over row tiles; the
kernel accumulates it across the sequential TPU grid.

Dispatch: the Pallas path runs when the default backend is TPU and the
working set fits the VMEM budget; otherwise the XLA path runs (identical
result).  Tests exercise the kernel on CPU via ``interpret=True``
(:func:`force_infonce_impl`).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from federated_pytorch_test_tpu.ops.infonce_core import (
    flat_patch_matrix,
    log_p_flat,
    safe_norms,
)

_TILE = 128                 # row tile = MXU edge
_SUBLANE = 8                # float32 sublane multiple
# budget for the estimates below (_fwd_vmem_bytes/_bwd_vmem_bytes), under
# the 16 MiB scoped-VMEM limit Mosaic enforces.  chip_smoke.py compiles
# both kernels, vmapped over clients as the CPC engine calls them, at the
# largest D each gate admits on every chip run — a gate that admits a
# shape Mosaic refuses fails there, not in a training run
_VMEM_BUDGET = 12 * 2**20

# None = auto (TPU -> pallas, else XLA); "xla" | "pallas" | "pallas_interpret"
_FORCE_IMPL = None


@contextlib.contextmanager
def force_infonce_impl(impl: str):
    """Force the InfoNCE implementation ("xla" | "pallas" |
    "pallas_interpret") — tests run the kernel on CPU via interpret mode."""
    global _FORCE_IMPL
    prev, _FORCE_IMPL = _FORCE_IMPL, impl
    try:
        yield
    finally:
        _FORCE_IMPL = prev


def _loss_from_log_p(log_p: jnp.ndarray) -> jnp.ndarray:
    """-sum log(softmax_diag + 1e-6) — the reference adds 1e-6 inside the
    log (federated_cpc.py:178)."""
    return -jnp.sum(jnp.log(jnp.exp(log_p) + 1e-6))


def _log_p_kernel(P: int, z_ref, zhat_ref, out_ref):
    """One [T, P_pad] row-tile of the score matrix, reduced to log_p [T].

    ``P`` (static) is the true column count; pad columns are masked to
    -inf before the row logsumexp.  Pad columns have zero norm, so the
    divisor is made pad-safe (the masked scores never contribute).
    """
    i = pl.program_id(0)
    a = z_ref[:, :]          # [D_pad, T]   this tile's columns of Z
    zh = zhat_ref[:, :]      # [D_pad, P_pad]
    zn = jnp.sqrt(jnp.sum(a * a, axis=0))       # [T]
    zhn = jnp.sqrt(jnp.sum(zh * zh, axis=0))    # [P_pad]
    zn = jnp.where(zn == 0.0, 1.0, zn)
    zhn = jnp.where(zhn == 0.0, 1.0, zhn)
    # contract over D without an explicit transpose: [T, P_pad] on the MXU
    zz = jax.lax.dot_general(
        a, zh, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / (zn[:, None] * zhn[None, :])

    t = zz.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (t, zz.shape[1]), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (t, zz.shape[1]), 0) + i * t
    valid = col < P
    zzm = jnp.where(valid, zz, -jnp.inf)
    m = jnp.max(zzm, axis=1, keepdims=True)            # [T, 1]
    lse = m[:, 0] + jnp.log(jnp.sum(jnp.exp(zzm - m), axis=1))
    diag = jnp.sum(jnp.where(col == row, zz, 0.0), axis=1)
    out_ref[0, :] = diag - lse


def _padded_dims(D: int, P: int) -> tuple:
    """(D_pad, P_pad): D to the f32 sublane multiple, P to the row tile."""
    return pl.cdiv(D, _SUBLANE) * _SUBLANE, pl.cdiv(P, _TILE) * _TILE


def _fwd_vmem_bytes(D_pad: int, P_pad: int) -> int:
    """VMEM estimate for ``_log_p_kernel``: the Z tile [D, T] and Zhat
    [D, P] blocks, each double-buffered by the Pallas pipeline, Zhat's
    squares for the norms, and ~3 [T, P] score-sized temporaries.  At
    [7680, 256] this gives 31.9 MB where Mosaic reported a 30.13 MB
    scoped allocation (my chip run, PR 21)."""
    return 4 * (2 * D_pad * _TILE + 3 * D_pad * P_pad + 3 * _TILE * P_pad)


def _bwd_vmem_bytes(D_pad: int, P_pad: int) -> int:
    """VMEM estimate for ``_grad_kernel``: Z tile + dZ tile [D, T] and
    Zhat + dZhat [D, P], each double-buffered; the dZhat partial and
    Zhat's squares [D, P], one [D, T] product, and ~5 [T, P] score-sized
    temporaries (zz, s, G, Gn, G*zz).  The single-buffered count this
    replaces admitted [4096, 128] — the CPC reference shape — which
    Mosaic refuses once ``vmap`` over clients gives the grid a second
    step to pipeline across (my chip run, PR 21)."""
    return 4 * (5 * D_pad * _TILE + 6 * D_pad * P_pad + 5 * _TILE * P_pad)


def _pallas_fits(D_pad: int, P_pad: int) -> bool:
    return _fwd_vmem_bytes(D_pad, P_pad) <= _VMEM_BUDGET


def _pallas_bwd_fits(D_pad: int, P_pad: int) -> bool:
    return _bwd_vmem_bytes(D_pad, P_pad) <= _VMEM_BUDGET


def dispatch_plan(D: int, P: int) -> dict:
    """What :func:`info_nce_fused` runs for a ``[D, P]`` patch matrix on
    the current backend, and the numbers that decided it — forward and
    backward resolve separately, so a shape can run the fused forward
    with an XLA backward; callers that care (chip_smoke.py) print this
    instead of assuming."""
    D_pad, P_pad = _padded_dims(D, P)
    fwd = _fwd_vmem_bytes(D_pad, P_pad)
    bwd = _bwd_vmem_bytes(D_pad, P_pad)
    return {"backend": jax.default_backend(), "forced": _FORCE_IMPL,
            "padded": (D_pad, P_pad), "vmem_budget": _VMEM_BUDGET,
            "forward": _resolve_impl(fwd <= _VMEM_BUDGET),
            "forward_vmem_bytes": fwd,
            "backward": _resolve_impl(bwd <= _VMEM_BUDGET),
            "backward_vmem_bytes": bwd}


def _log_p_pallas(Z: jnp.ndarray, Zhat: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    D, P = Z.shape
    D_pad, P_pad = _padded_dims(D, P)
    Zp = jnp.pad(Z, ((0, D_pad - D), (0, P_pad - P)))
    Zhp = jnp.pad(Zhat, ((0, D_pad - D), (0, P_pad - P)))
    out = pl.pallas_call(
        functools.partial(_log_p_kernel, P),
        grid=(P_pad // _TILE,),
        in_specs=[
            pl.BlockSpec((D_pad, _TILE), lambda i: (0, i)),
            pl.BlockSpec((D_pad, P_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, P_pad), jnp.float32),
        interpret=interpret,
    )(Zp, Zhp)
    return out[0, :P]


def _dispatch_log_p(Z: jnp.ndarray, Zhat: jnp.ndarray) -> jnp.ndarray:
    impl = _resolve_impl(_pallas_fits(*_padded_dims(*Z.shape)))
    if impl == "xla":
        return log_p_flat(Z, Zhat)          # shared core, ops/infonce_core.py
    return _log_p_pallas(Z, Zhat, interpret=impl == "pallas_interpret")


def _resolve_impl(fits: bool) -> str:
    """"xla" | "pallas" | "pallas_interpret" for this call site.

    ``fits`` is the caller's VMEM estimate; forward and backward have
    different working sets, so under auto dispatch a shape can run the
    fused forward while its backward falls back to XLA (results agree
    either way).  A forced impl (tests, benches) wins unconditionally.
    """
    impl = _FORCE_IMPL
    if impl is None:
        return "pallas" if (jax.default_backend() == "tpu" and fits) else "xla"
    return impl


@jax.custom_vjp
def _fused_flat(Z: jnp.ndarray, Zhat: jnp.ndarray) -> jnp.ndarray:
    return _loss_from_log_p(_dispatch_log_p(Z, Zhat))


def _fused_flat_fwd(Z, Zhat):
    log_p = _dispatch_log_p(Z, Zhat)
    return _loss_from_log_p(log_p), (Z, Zhat, log_p)


def _grads_xla(Z, Zhat, log_p, ghat):
    """XLA backward (the fallback path of ``_dispatch_grads``)."""
    # same zero-norm guard as every forward path (infonce_core.safe_norms):
    # a guarded column has zz ≡ 0, so the norm-path terms (dzn/dzhn)
    # vanish and only the finite numerator path contributes — no NaNs
    zn = safe_norms(Z)
    zhn = safe_norms(Zhat)
    denom = zn[:, None] * zhn[None, :]
    zz = (Z.T @ Zhat) / denom
    lse = jnp.diag(zz) - log_p
    s = jnp.exp(zz - lse[:, None])                    # softmax rows
    G = ghat[:, None] * (jnp.eye(zz.shape[0], dtype=zz.dtype) - s)
    Gn = G / denom
    dzn = -jnp.sum(G * zz, axis=1) / zn
    dzhn = -jnp.sum(G * zz, axis=0) / zhn
    dZ = Zhat @ Gn.T + Z * (dzn / zn)[None, :]
    dZhat = Z @ Gn + Zhat * (dzhn / zhn)[None, :]
    return dZ, dZhat


def _grad_kernel(P: int, z_ref, zhat_ref, logp_ref, ghat_ref,
                 dz_ref, dzhat_ref):
    """One [T, P_pad] row tile of the backward: rebuild the tile's scores,
    form the softmax-Jacobian product G in VMEM, and emit this tile's
    [D_pad, T] slab of dZ plus its additive contribution to dZhat.

    dZhat needs a sum over ALL row tiles (column reduction of G); the TPU
    grid runs sequentially, so the kernel accumulates into ``dzhat_ref``
    (initialised by the first program).  Pad rows are inert by
    construction: their ghat is staged as 0, so their G row vanishes; pad
    columns are masked out of the softmax like the forward.
    """
    i = pl.program_id(0)
    a = z_ref[:, :]            # [D_pad, T]   this tile's columns of Z
    zh = zhat_ref[:, :]        # [D_pad, P_pad]
    logp = logp_ref[0, :]      # [T]
    ghat = ghat_ref[0, :]      # [T]          0 on pad rows
    zn = jnp.sqrt(jnp.sum(a * a, axis=0))       # [T]
    zhn = jnp.sqrt(jnp.sum(zh * zh, axis=0))    # [P_pad]
    zn = jnp.where(zn == 0.0, 1.0, zn)          # infonce_core.safe_norms
    zhn = jnp.where(zhn == 0.0, 1.0, zhn)
    denom = zn[:, None] * zhn[None, :]
    zz = jax.lax.dot_general(
        a, zh, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / denom                                   # [T, P_pad]

    t = zz.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, zz.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, zz.shape, 0) + i * t
    on_diag = col == row
    diag = jnp.sum(jnp.where(on_diag, zz, 0.0), axis=1)      # [T]
    lse = diag - logp                           # forward residual identity
    # pad rows: zz ≡ 0 (zero Z column, guarded norm) and logp staged 0, so
    # lse = 0 and s stays bounded — no inf/NaN can leak into the masked G
    s = jnp.where(col < P, jnp.exp(zz - lse[:, None]), 0.0)
    G = ghat[:, None] * (jnp.where(on_diag, 1.0, 0.0) - s)   # [T, P_pad]
    Gn = G / denom
    dzn = -jnp.sum(G * zz, axis=1) / (zn * zn)               # [T]
    dz_ref[:, :] = jax.lax.dot_general(
        zh, Gn, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + a * dzn[None, :]
    part = jax.lax.dot_general(
        a, Gn, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + zh * (-jnp.sum(G * zz, axis=0) / (zhn * zhn))[None, :]

    @pl.when(i == 0)
    def _init():
        dzhat_ref[:, :] = part

    @pl.when(i > 0)
    def _acc():
        dzhat_ref[:, :] += part


def _grads_pallas(Z, Zhat, log_p, ghat, interpret: bool = False):
    D, P = Z.shape
    D_pad, P_pad = _padded_dims(D, P)
    pad2 = lambda m: jnp.pad(m, ((0, D_pad - D), (0, P_pad - P)))
    pad_row = lambda v: jnp.pad(v, (0, P_pad - P))[None, :]
    dZ, dZhat = pl.pallas_call(
        functools.partial(_grad_kernel, P),
        grid=(P_pad // _TILE,),
        in_specs=[
            pl.BlockSpec((D_pad, _TILE), lambda i: (0, i)),
            pl.BlockSpec((D_pad, P_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, _TILE), lambda i: (0, i)),
            pl.BlockSpec((1, _TILE), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((D_pad, _TILE), lambda i: (0, i)),
            pl.BlockSpec((D_pad, P_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D_pad, P_pad), jnp.float32),
            jax.ShapeDtypeStruct((D_pad, P_pad), jnp.float32),
        ],
        interpret=interpret,
    )(pad2(Z), pad2(Zhat), pad_row(log_p), pad_row(ghat))
    return dZ[:D, :P], dZhat[:D, :P]


def _fused_flat_bwd(res, ct):
    """Hand-derived VJP from the saved ``log_p`` residual.

    The LBFGS closure evaluates value_and_grad on every (re-)evaluation,
    so the backward matters: rebuilding the score matrix costs one matmul
    (unavoidable — the softmax Jacobian needs it), but the saved log_p
    recovers the row logsumexp as ``diag(zz) - log_p``, so no reduction
    or forward pass is re-run.  With L = -sum_i log(exp(g_i) + 1e-6),
    g_i = zz_ii - lse_i and zz = (Z^T Zhat) / (zn zhn^T):

        dL/dzz_ij = ghat_i (delta_ij - softmax_i(zz)_ij),
        ghat_i    = -ct * exp(g_i) / (exp(g_i) + 1e-6)

    then the quotient rule routes dL/dzz into Z, Zhat both through the
    Gram numerator and the column norms.  On TPU the whole product is a
    Pallas kernel (``_grad_kernel``) — the [P, P] intermediates (scores,
    softmax, G) live only in VMEM, tile by tile.
    """
    Z, Zhat, log_p = res
    c = jnp.exp(log_p)
    ghat = -ct * c / (c + 1e-6)                       # [P]
    impl = _resolve_impl(_pallas_bwd_fits(*_padded_dims(*Z.shape)))
    if impl == "xla":
        return _grads_xla(Z, Zhat, log_p, ghat)
    return _grads_pallas(Z, Zhat, log_p, ghat,
                         interpret=impl == "pallas_interpret")


_fused_flat.defvjp(_fused_flat_fwd, _fused_flat_bwd)


def info_nce_fused(z: jnp.ndarray, zhat: jnp.ndarray) -> jnp.ndarray:
    """InfoNCE over patch positions, same contract as
    :func:`train.cpc_losses.info_nce` (z, zhat: [B, px, py, R] NHWC;
    reference federated_cpc.py:149-180) — Pallas-fused on TPU."""
    return _fused_flat(flat_patch_matrix(z), flat_patch_matrix(zhat))
