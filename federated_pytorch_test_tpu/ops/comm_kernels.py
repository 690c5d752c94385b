"""Comm-path kernel suite: fused quantize / dequantize-accumulate /
Gram-distance Pallas TPU kernels (ISSUE 20 tentpole).

PR 11 left the packed-collective hot path (ops/packed_reduce.py) as XLA
fusions plus one experimental single-block quantize kernel gated behind
``FEDTPU_FUSED_PALLAS=1``.  This module promotes that experiment into a
first-class suite with the ``ops/infonce.py`` dispatch contract:

- :func:`quantize_chunks` — ONE kernel computes the per-chunk max-abs
  scale AND the round-to-nearest int8 quantization in a single VMEM
  residency.  The old experiment read ``vv`` twice from HBM (XLA max
  reduce, then the divide/round/clip kernel); here each row tile is
  loaded once.
- :func:`dequant_add` — the reduce-scatter hop's ``acc + decode(q, s)``
  (the "partial reduce" of the fused transport): dequantize and
  accumulate without materializing the dense decoded buffer in HBM
  between two XLA fusions.
- :func:`gram_matrix` — the krum distance pass's ``A @ A.T`` streamed
  over column chunks: each grid step loads one ``[K, CHUNK]`` slab and
  accumulates the ``[K, K]`` Gram block in VMEM, so the full activation
  row never needs to be co-resident with the output
  (parallel/comm.py robust_federated_mean_chunked).

Dispatch (:func:`force_comm_kernels_impl`): ``None`` = auto (Pallas on
TPU when the working set fits VMEM, XLA elsewhere); tests force
``"pallas_interpret"`` to run the kernels on CPU.  The XLA paths are the
LITERAL pre-suite jnp chains and stay the tolerance reference:

- quantize/dequant: interpret mode is bit-identical to XLA (same f32
  ops in the same order); on real TPU hardware the max reduce may
  re-associate — PARITY.md carries the allclose contract.
- gram: the chunked accumulation re-associates the contraction, so
  Pallas (either mode) is allclose to the one-shot XLA matmul, not
  bitwise (documented in PARITY.md).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANES = 128                 # f32/int8 lane width
_ROW_TILE = 32               # int8 sublane multiple (covers f32's 8)
_GRAM_CHUNK = 512            # contraction slab per grid step
# budget for the per-program block estimates below.  The estimates count
# each block once although the Pallas pipeline double-buffers them; the
# headroom under the 16 MiB scoped-VMEM default absorbs that at the
# shapes these kernels see (tiles of a few hundred KB) — chip_smoke.py
# compiles them at driver shapes on every chip run
_VMEM_BUDGET = 12 * 2**20

# None = auto (TPU -> pallas, else XLA); "xla" | "pallas" | "pallas_interpret"
_FORCE_IMPL = None


@contextlib.contextmanager
def force_comm_kernels_impl(impl: str):
    """Force the comm-kernel implementation ("xla" | "pallas" |
    "pallas_interpret") — tests run the kernels on CPU via interpret
    mode, exactly the ``ops/infonce.py`` contract."""
    global _FORCE_IMPL
    prev, _FORCE_IMPL = _FORCE_IMPL, impl
    try:
        yield
    finally:
        _FORCE_IMPL = prev


def _resolve_impl(fits: bool) -> str:
    """"xla" | "pallas" | "pallas_interpret" for this call site; a
    forced impl (tests, benches) wins unconditionally."""
    impl = _FORCE_IMPL
    if impl is None:
        return "pallas" if (jax.default_backend() == "tpu" and fits) else "xla"
    return impl


def dispatch_plan(chunk: int, k: int) -> dict:
    """What the three entry points run on the current backend for
    ``[c, chunk]`` payload rows and a ``[k, n]`` client stack, and the
    VMEM estimate that decided each (chip_smoke.py prints this)."""
    est = {"quantize_chunks": _quantize_vmem_bytes(chunk),
           "dequant_add": _dequant_vmem_bytes(chunk),
           "gram_matrix": _gram_vmem_bytes(k)}
    plan = {name: {"impl": _resolve_impl(b <= _VMEM_BUDGET), "vmem_bytes": b}
            for name, b in est.items()}
    plan.update(backend=jax.default_backend(), forced=_FORCE_IMPL,
                vmem_budget=_VMEM_BUDGET)
    return plan


def _pad2(a, rows: int, cols: int):
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


# ----------------------------------------------------------------------
# fused quantize: per-chunk max-abs scale + round/clip in one residency
# ----------------------------------------------------------------------
def _quantize_xla(vv, qmax: int):
    """The literal pack_chunks math (ops/packed_reduce.py) — the
    reference path and the interpret-parity oracle."""
    scale = jnp.max(jnp.abs(vv), axis=1) / qmax
    safe = jnp.where(scale > 0, scale, 1.0).astype(vv.dtype)
    q = jnp.clip(jnp.round(vv / safe[:, None]), -qmax, qmax).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _quantize_kernel(qmax: int, cols: int, v_ref, q_ref, s_ref):
    """One ``[R, C_pad]`` row tile: scale, quantize, emit both.

    ``cols`` (static) is the true chunk width; pad columns hold zeros,
    which can never raise the max-|.| (magnitudes are >= 0), and their
    quantized value is 0 — the caller slices them off."""
    v = v_ref[...]                                     # [R, C_pad] f32
    scale = jnp.max(jnp.abs(v), axis=1) / qmax         # [R]
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(v / safe[:, None]), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    # lane-replicated scale row: a [R, 1] output block would fall below
    # the f32 tile floor on hardware; 128 copies cost nothing next to
    # the payload and the caller reads lane 0
    s_ref[...] = jnp.broadcast_to(scale[:, None], (v.shape[0], _LANES))
    del cols


def _quantize_vmem_bytes(cols: int) -> int:
    # v tile f32 + q tile int8 + scale lanes, per program
    return 4 * _ROW_TILE * cols + _ROW_TILE * cols + 4 * _ROW_TILE * _LANES


def _quantize_pallas(vv, qmax: int, interpret: bool = False):
    c, w = vv.shape
    c_pad = pl.cdiv(c, _ROW_TILE) * _ROW_TILE
    w_pad = pl.cdiv(w, _LANES) * _LANES
    q, s = pl.pallas_call(
        functools.partial(_quantize_kernel, qmax, w),
        grid=(c_pad // _ROW_TILE,),
        in_specs=[pl.BlockSpec((_ROW_TILE, w_pad), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((_ROW_TILE, w_pad), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c_pad, w_pad), jnp.int8),
            jax.ShapeDtypeStruct((c_pad, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(_pad2(vv, c_pad, w_pad))
    return q[:c, :w], s[:c, 0]


def quantize_chunks(vv, qmax: int):
    """``(q, scale)`` of the ``[c, chunk]`` row matrix: per-row
    ``scale = max|row| / qmax`` and round-to-nearest
    ``q = clip(round(row / safe), ±qmax)`` int8 — the deterministic
    transport codec of ops/packed_reduce.py, fused."""
    impl = _resolve_impl(_quantize_vmem_bytes(vv.shape[1]) <= _VMEM_BUDGET)
    if impl == "xla":
        return _quantize_xla(vv, qmax)
    return _quantize_pallas(vv, qmax, interpret=impl == "pallas_interpret")


# ----------------------------------------------------------------------
# fused dequantize + accumulate: the reduce-scatter hop's partial reduce
# ----------------------------------------------------------------------
def _dequant_add_xla(acc, q, scale):
    """Literal hop math: ``acc + q * safe`` (ops/packed_reduce.py
    unpack_chunks followed by the add), the parity oracle."""
    safe = jnp.where(scale > 0, scale, 1.0)
    return acc + q.astype(jnp.float32) * safe[:, None]


def _dequant_add_kernel(a_ref, q_ref, s_ref, o_ref):
    safe_row = s_ref[:, 0]                             # lane-replicated in
    safe = jnp.where(safe_row > 0, safe_row, 1.0)
    o_ref[...] = a_ref[...] + q_ref[...].astype(jnp.float32) * safe[:, None]


def _dequant_vmem_bytes(cols: int) -> int:
    # acc + out f32, q int8, scale lanes, per program
    return 2 * 4 * _ROW_TILE * cols + _ROW_TILE * cols \
        + 4 * _ROW_TILE * _LANES


def _dequant_add_pallas(acc, q, scale, interpret: bool = False):
    c, w = acc.shape
    c_pad = pl.cdiv(c, _ROW_TILE) * _ROW_TILE
    w_pad = pl.cdiv(w, _LANES) * _LANES
    s_lanes = jnp.broadcast_to(
        jnp.pad(scale, (0, c_pad - c))[:, None], (c_pad, _LANES))
    out = pl.pallas_call(
        _dequant_add_kernel,
        grid=(c_pad // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, w_pad), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, w_pad), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, w_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, w_pad), jnp.float32),
        interpret=interpret,
    )(_pad2(acc, c_pad, w_pad), _pad2(q, c_pad, w_pad), s_lanes)
    return out[:c, :w]


def dequant_add(acc, q, scale):
    """``acc + dequantize(q, scale)`` for ``[c, chunk]`` rows — the
    packed reduce-scatter hop's accumulate, without an HBM round-trip
    for the decoded buffer.  ``q`` is int8 rows (q4 payloads are
    nibble-unfolded by the caller; the fold is a pure byte shuffle XLA
    keeps inside the surrounding fusion either way)."""
    impl = _resolve_impl(_dequant_vmem_bytes(acc.shape[1]) <= _VMEM_BUDGET)
    if impl == "xla":
        return _dequant_add_xla(acc, q, scale)
    return _dequant_add_pallas(acc, q, scale,
                               interpret=impl == "pallas_interpret")


# ----------------------------------------------------------------------
# chunk-streamed Gram matrix: the krum distance pass
# ----------------------------------------------------------------------
def _gram_xla(a):
    """One-shot ``A @ A.T`` — the dense reference (and the tolerance
    oracle: the chunked kernel re-associates the contraction)."""
    return a @ a.T


def _gram_kernel(a_ref, g_ref):
    """Accumulate one ``[K_pad, CHUNK]`` slab's Gram contribution.

    The TPU grid runs sequentially, so the output block accumulates
    across steps (``ops/infonce.py`` ``_grad_kernel`` pattern); pad
    rows/columns are zeros and contribute exactly nothing."""
    j = pl.program_id(0)
    a = a_ref[...]
    g = lax.dot_general(a, a, dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = g

    @pl.when(j > 0)
    def _acc():
        g_ref[...] += g


def _gram_vmem_bytes(k: int) -> int:
    # [K_pad, CHUNK] slab + [K_pad, K_pad] accumulator block, per program
    k_pad = pl.cdiv(k, _LANES) * _LANES
    return 4 * (k_pad * _GRAM_CHUNK + k_pad * k_pad)


def _gram_pallas(a, interpret: bool = False):
    k, n = a.shape
    # K rides both sublanes and lanes of the [K_pad, K_pad] output:
    # pad to the lane width once, K is small (the client count)
    k_pad = pl.cdiv(k, _LANES) * _LANES
    n_pad = pl.cdiv(n, _GRAM_CHUNK) * _GRAM_CHUNK
    g = pl.pallas_call(
        _gram_kernel,
        grid=(n_pad // _GRAM_CHUNK,),
        in_specs=[pl.BlockSpec((k_pad, _GRAM_CHUNK), lambda j: (0, j))],
        out_specs=pl.BlockSpec((k_pad, k_pad), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k_pad, k_pad), jnp.float32),
        interpret=interpret,
    )(_pad2(a, k_pad, n_pad))
    return g[:k, :k]


def gram_matrix(a):
    """``A @ A.T`` of a ``[K, n]`` client stack, streamed over column
    chunks on TPU so only one ``[K, CHUNK]`` slab is VMEM-resident per
    grid step.  Chunked accumulation re-associates the contraction:
    Pallas output is allclose to the XLA matmul, not bitwise
    (PARITY.md)."""
    impl = _resolve_impl(_gram_vmem_bytes(a.shape[0]) <= _VMEM_BUDGET)
    if impl == "xla":
        return _gram_xla(a)
    return _gram_pallas(a, interpret=impl == "pallas_interpret")
