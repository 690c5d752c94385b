"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; JAX's
``xla_force_host_platform_device_count`` gives 8 virtual CPU devices so the
client-mesh collectives (shard_map / pmean over the 'clients' axis) are
exercised for real (SURVEY.md section 4's distributed-test strategy).
"""

import os

# FEDTPU_TEST_TPU=1 keeps the hardware backend so the TPU-gated tests
# (e.g. test_ops.py::test_compiled_kernels_on_tpu) run compiled on the real
# chip; everything else in the suite still passes there or skips.  The
# backend is asserted below: an exported JAX_PLATFORMS=cpu must not turn
# such a run into all-skips with exit code 0.
_USE_TPU = os.environ.get("FEDTPU_TEST_TPU") == "1"

if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

_WORKER = os.environ.get("PYTEST_XDIST_WORKER")

import jax  # noqa: E402

if not _USE_TPU:
    # jax may already be imported (a plugin, pytest -p) with the env read;
    # the env var above is then too late, so force via config.
    jax.config.update("jax_platforms", "cpu")
    assert jax.devices()[0].platform == "cpu", \
        "tests must run on the CPU mesh"
    assert len(jax.devices()) >= 8, (
        "expected 8 virtual CPU devices; "
        "xla_force_host_platform_device_count was not honored "
        "(jax already initialized its backend?)"
    )

if _USE_TPU:
    assert jax.default_backend() == "tpu", (
        f"FEDTPU_TEST_TPU=1 but the backend is {jax.default_backend()!r} "
        f"({jax.devices()}): the TPU-gated tests would pass by skipping")

jax.config.update("jax_default_matmul_precision", "float32")

# persistent compilation cache: XLA:CPU compiles dominate test wall-clock;
# cache them across pytest runs
from federated_pytorch_test_tpu.utils.compile_cache import (  # noqa: E402
    enable_persistent_compile_cache,
)

enable_persistent_compile_cache()
if _WORKER:
    # Under pytest-xdist nothing is written to the cache (no program takes
    # an hour to compile).  jaxlib's CPU client dies of a segmentation
    # fault, now and then, while it serialises an executable for the cache
    # or loads one back (``compiler.py:_cache_write`` / ``_cache_read`` on
    # the stack, the engine's eight-device programs below them; no lock
    # or rename protects an entry either): on 2026-10-05 in each of four
    # whole runs of PR 38's tree at ``-n 6``, with a directory a worker
    # or not.  A dead worker costs every later test of its file, and a
    # checkout that starts without a cache, as the driver's does, gains
    # nothing from one: the whole run took 704 s without it and 810 s
    # with it.  (What is left, on the same day and on the parent's tree
    # too: the same fault inside the compile itself,
    # ``backend_compile_and_load``, once or twice a run, whatever
    # ``--xla_cpu_parallel_codegen_split_count`` says.)  Without xdist the
    # floor stays where the helper puts it.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end training tests (quick loop: -m 'not slow')")
