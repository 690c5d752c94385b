"""Shared persistent XLA compile-cache setup.

One helper for the compile-heavy entry surfaces (tests/conftest.py,
__graft_entry__.py, bench.py, chip_smoke.py, drivers/common.py): first
compiles dominate their wall-clock, so they share one on-disk cache that
survives across processes.

Where the cache lives is decided by exactly one thing:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets NO directory in code — whoever launched the process
  (the chip driver, a test that spawns workers) owns the placement.
- unset: the fixed ``tests/.jax_cache`` inside the checkout.  The path is
  part of the cache key, so it must not move between runs.

What goes in: every program, except in a CPU-only process, which keeps
out those that compile in under a second (see the function).

``cache_stats()`` reports entry count / total bytes for the bench
artifact, the chip smoke's "second run adds no entries" check and the
cost ledger's hit/miss attribution (obs/costs.py watches the entry count
across compile events).
"""

from __future__ import annotations

import os
import stat
from typing import Any, Dict, Optional

import jax

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    "tests", ".jax_cache")


def enable_persistent_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns the directory
    in effect (see the module docstring for who chooses it)."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Which programs are worth a file: a CPU-only process (the test suite)
    # compiles thousands of sub-second programs, so those stay out.
    # Anywhere else every program is kept — with a floor, a program that
    # compiles in 0.9 s on one run and 1.1 s on the next adds an entry on
    # the second run (five did on the chip, PR 21), and a warm start
    # should be served from the cache entirely.
    cpu_only = jax.config.jax_platforms == "cpu"
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      1.0 if cpu_only else 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def cache_stats(cache_dir: Optional[str] = None) -> Dict[str, Any]:
    """Entry count / total bytes / location of the persistent cache.

    With no argument, reads the directory jax is currently configured
    with (empty stats when the cache is off or the dir is missing —
    never raises; this feeds the bench artifact).
    """
    if cache_dir is None:
        cache_dir = jax.config.jax_compilation_cache_dir
    out: Dict[str, Any] = {"dir": cache_dir or None,
                           "entries": 0, "total_bytes": 0}
    if not cache_dir or not os.path.isdir(cache_dir):
        return out
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return out
    for name in names:
        try:
            st = os.stat(os.path.join(cache_dir, name))
        except OSError:
            continue
        if stat.S_ISREG(st.st_mode):
            out["entries"] += 1
            out["total_bytes"] += int(st.st_size)
    return out
