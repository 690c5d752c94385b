"""On-device chunked top-|v| selection for the sparse comm path.

``compress/topk.py`` used to run one global ``lax.top_k`` over the full
flat block vector.  On TPU that lowers to a monolithic sort-based
selection whose working set is the whole ``[n]`` vector plus the sort
scratch — for the block sizes the sparse path carries (hundreds of
thousands of coordinates) that is the single largest temporary in the
encode program.  The chunked kernel here runs the textbook two-stage
exact algorithm instead:

1. reshape to ``[c, chunk]`` and take each chunk's local top-``min(k,
   chunk)`` (one vectorized ``lax.top_k`` over the minor axis — the
   shape XLA:TPU tiles well),
2. run one final ``lax.top_k`` over the ``c * min(k, chunk)``
   candidates.

Any global top-k element is, by definition, inside its own chunk's
local top-k, so the result set is exact.  Tie-breaking is ALSO exact:
``lax.top_k`` breaks value ties toward the lower index, candidates are
laid out chunk-major (ascending global index), and stage 2 breaks its
ties toward the lower candidate position — which is the lower global
index.  The dispatch therefore promises **bitwise** identity with the
single-shot reference, and tests assert it (ties included).

Dispatch follows ``ops/infonce.py``: ``force_topk_impl`` pins
``"xla"`` (single-shot ``lax.top_k``) or ``"chunked"``; auto picks
chunked on TPU for vectors past the chunk size, single-shot elsewhere.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax

_CHUNK = 2048                # per-stage-1 slab; multiple of the 128 lanes

# None = auto (TPU + large n -> chunked); "xla" | "chunked"
_FORCE_IMPL = None


@contextlib.contextmanager
def force_topk_impl(impl: str):
    """Force the top-k implementation ("xla" | "chunked") — tests pin
    both sides and assert bitwise equality."""
    global _FORCE_IMPL
    prev, _FORCE_IMPL = _FORCE_IMPL, impl
    try:
        yield
    finally:
        _FORCE_IMPL = prev


def _resolve_impl(n: int) -> str:
    impl = _FORCE_IMPL
    if impl is None:
        return "chunked" if (jax.default_backend() == "tpu"
                             and n > _CHUNK) else "xla"
    return impl


def dispatch_plan(n: int) -> dict:
    """What :func:`top_k_abs_indices` runs for an ``[n]`` vector on the
    current backend, and why (chip_smoke.py prints this)."""
    return {"backend": jax.default_backend(), "forced": _FORCE_IMPL,
            "impl": _resolve_impl(n), "chunk": _CHUNK}


def _topk_abs_xla(vec, k: int):
    """The seed path: one global sort-based selection."""
    _, idx = lax.top_k(jnp.abs(vec), k)
    return idx.astype(jnp.int32)


def _topk_abs_chunked(vec, k: int):
    n = vec.shape[0]
    c = -(-n // _CHUNK)
    # pad with -1: magnitudes are >= 0, so a pad slot can only be
    # selected when fewer than k real candidates exist — and k <= n
    mag = jnp.pad(jnp.abs(vec), (0, c * _CHUNK - n), constant_values=-1.0)
    mag = mag.reshape(c, _CHUNK)
    kc = min(k, _CHUNK)
    cand_v, cand_i = lax.top_k(mag, kc)                     # [c, kc]
    cand_g = cand_i + (jnp.arange(c, dtype=cand_i.dtype) * _CHUNK)[:, None]
    # chunk-major flatten keeps candidates in ascending-global-index
    # order within each value class, so stage 2's lower-position
    # tie-break IS the lower-global-index tie-break
    _, pos = lax.top_k(cand_v.reshape(-1), k)
    return cand_g.reshape(-1)[pos].astype(jnp.int32)


def top_k_abs_indices(vec, k: int):
    """Indices of the ``k`` largest ``|vec|`` entries, sorted by
    descending magnitude with ties broken toward the lower index —
    bitwise the single-shot ``lax.top_k(|vec|, k)`` result on every
    implementation."""
    if _resolve_impl(vec.shape[0]) == "xla":
        return _topk_abs_xla(vec, k)
    return _topk_abs_chunked(vec, k)
