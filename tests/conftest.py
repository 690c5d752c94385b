"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; JAX's
``xla_force_host_platform_device_count`` gives 8 virtual CPU devices so the
client-mesh collectives (shard_map / pmean over the 'clients' axis) are
exercised for real (SURVEY.md section 4's distributed-test strategy).
"""

import gc
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
    # Under pytest-xdist the persistent cache is off: nothing is read or
    # written.  jaxlib's CPU client dies of a segmentation fault, now and
    # then, while it serialises an executable for the cache or loads one
    # back (``compiler.py:_cache_write`` / ``_cache_read`` on the stack;
    # no lock or rename protects an entry either): on 2026-10-05 in each
    # of four whole runs of PR 38's tree at ``-n 6``.  A dead worker costs
    # every later test of its file, and a checkout that starts without a
    # cache, as the driver's does, gains nothing from one (704 s without
    # it, 810 s with it).  PR 38 raised the floor to an hour instead, and
    # every test that starts a driver or ``benchmarks/run.py`` in its own
    # process (``drivers/common.py:setup_runtime``, ``run.py:main``) put
    # it back to a second: the driver's run of PR 38's tree lost a worker
    # in ``_cache_write`` under ``test_xing_benchmark.py``.  The switch
    # below is one no helper touches.  The faults inside the compile
    # itself (``backend_compile_and_load``) were the memory maps
    # (``pytest_runtest_teardown`` below); whether the cache's were too is
    # not known.  Without xdist the cache stays where the helper puts it.
    jax.config.update("jax_enable_compilation_cache", False)


def pytest_runtest_teardown(item, nextitem):
    # Between two test files, drop every compiled program.  An XLA:CPU
    # executable keeps its code in memory maps of its own for as long as a
    # jit cache holds it, and one process may hold 65,530 maps
    # (``vm.max_map_count``): run in one process, ``test_engine.py``,
    # ``test_fused.py``, ``test_golden_trajectories.py`` and
    # ``test_glm4_moe_lite.py`` leave 63,983, and the next compile aborts
    # in ``contiguous_section_memory_manager.cc`` ("allocateMappedMemory
    # failed ... Cannot allocate memory") or dies of a segmentation fault.
    # That is what killed a worker late in each of the driver's ``-n 6``
    # runs, in whichever large compile came next (``test_zaya.py``,
    # ``test_xing4_0.py``, the benchmark files' checks), PRs 38 and 39.
    if nextitem is None or nextitem.module is not item.module:
        jax.clear_caches()
        gc.collect()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end training tests (quick loop: -m 'not slow')")
