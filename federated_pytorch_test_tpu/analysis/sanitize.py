"""Runtime sanitizers: checkify wiring + retrace sentinel.

Both are default-off and wrap the engines' jitted callables at build
time, so the default path constructs the *literal* pre-existing
``jax.jit(shard_map(fn))`` chain — bit-identical by construction (the
same contract as compress/faults/obs).

- ``--sanitize``: every instrumented step runs under
  ``jax.experimental.checkify`` with NaN/inf (``float_checks``) and
  out-of-bounds index (``index_checks``) assertions; the error payload
  is thrown on the host after each call (which forces a sync — this is
  a debugging mode, not a perf mode).
- ``--retrace-sentinel``: counts executions of the traced Python body
  of each instrumented callable.  The body only runs when jit traces
  (compiled dispatch never re-enters Python), so ``count - 1`` per
  callable is its retrace count; regressions (a leaked weak type, an
  unhashable static, a rebuilt closure) show up as a nonzero
  ``jit_retraces`` in the obs round records and the bench artifact.
  Zero runtime cost: the wrapper is never called after compilation.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.experimental import checkify

_errors_cache: "frozenset | None" = None


def index_checks_supported(version: str) -> bool:
    """Whether this jax version's ``index_checks`` are trustworthy.

    Every 0.4.x ``checkify.scatter_oob`` crashes (internal IndexError,
    not a check failure) on the scatter in a gather VJP — the exact op
    the cross-entropy ``take_along_axis`` backward pass emits — so the
    whole 0.4 line is gated off without probing.  0.5+ carries the fix;
    an unparseable version string returns True so the runtime probe in
    :func:`sanitize_errors` gets the final word.
    """
    try:
        major, minor = (int(x) for x in version.split(".")[:2])
    except (ValueError, TypeError):
        return True
    return (major, minor) >= (0, 5)


def sanitize_errors():
    """NaN/inf checks always; index checks when this jax supports them.

    The version gate (:func:`index_checks_supported`) rejects the 0.4.x
    line outright; newer jax is still probed once on a tiny gather-grad
    and index_checks dropped if the instrumentation itself is broken.
    Cached after the first call, so a jax bump flips index checks on
    with no code change here.
    """
    global _errors_cache
    if _errors_cache is None:
        errs = checkify.float_checks
        if index_checks_supported(jax.__version__):
            try:
                def _probe(x, i):
                    sel = jnp.take_along_axis(x, i[..., None], axis=-1)
                    return sel[..., 0].sum()

                checkify.checkify(jax.grad(_probe),
                                  errors=checkify.index_checks)(
                    jnp.ones((2, 3)), jnp.arange(2))
                errs = errs | checkify.index_checks
            except Exception:
                pass
        _errors_cache = errs
    return _errors_cache


class TraceSentinel:
    """Counts traces of jit-wrapped callables by name.

    ``wrap(fn, name)`` returns a callable that bumps ``counts[name]``
    and delegates; wrap it *inside* ``jax.jit`` so the bump happens
    exactly once per trace (first compile included).
    """

    def __init__(self):
        self.counts: Dict[str, int] = {}

    def wrap(self, fn: Callable, name: str) -> Callable:
        self.counts.setdefault(name, 0)
        counts = self.counts

        @functools.wraps(fn)
        def counted(*args, **kwargs):
            counts[name] += 1
            return fn(*args, **kwargs)

        return counted

    @property
    def traces(self) -> int:
        return sum(sorted(self.counts.values()))

    @property
    def retraces(self) -> int:
        """Traces beyond the first per callable — the regressions."""
        return sum(sorted(v - 1 for v in self.counts.values() if v > 0))


def checkify_callable(fn: Callable) -> Callable:
    """Transform ``fn`` so its outputs become ``(error, outputs)``.

    Apply to the *pre-jit* callable (shard_map output included — the
    checks thread through the mesh axes), then jit the result: the
    checkified jaxpr is traced once and cached like any jitted fn.
    """
    return checkify.checkify(fn, errors=sanitize_errors())


def throwing(jitted_fn: Callable) -> Callable:
    """Unwrap a checkified jitted fn: throw the error, return outputs.

    ``err.throw()`` raises :class:`jax.experimental.checkify.JaxRuntimeError`
    on the first failed check (with the failing primitive named) and
    forces a host sync on the error payload.
    """

    @functools.wraps(jitted_fn)
    def wrapper(*args: Any, **kwargs: Any):
        err, out = jitted_fn(*args, **kwargs)
        err.throw()
        return out

    return wrapper


def instrument_jit(fn: Callable, name: str, *, sanitize: bool,
                   sentinel: "TraceSentinel | None",
                   ledger=None, **jit_kwargs) -> Callable:
    """The one assembly point: conditionally checkify + count, then jit.

    With all knobs off this is exactly ``jax.jit(fn, **jit_kwargs)``.
    ``ledger`` is an ``obs.costs.CostLedger``: its trace counter wraps
    the pre-jit callable (innermost, like the sentinel) and its dispatch
    timer wraps the jitted fn directly — under ``throwing`` so the timed
    window never includes the checkify host sync.
    """
    if sanitize:
        fn = checkify_callable(fn)
        if "out_shardings" in jit_kwargs:
            # the checkified fn returns (error, outputs): the error
            # payload's placement is left to jax
            jit_kwargs["out_shardings"] = (None, jit_kwargs["out_shardings"])
    if sentinel is not None:
        fn = sentinel.wrap(fn, name)
    if ledger is not None:
        fn = ledger.mark(fn, name)
    jfn = jax.jit(fn, **jit_kwargs)
    if ledger is not None:
        jfn = ledger.instrument(jfn, name)
    if sanitize:
        jfn = throwing(jfn)
    return jfn
