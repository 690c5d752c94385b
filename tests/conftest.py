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


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end training tests (quick loop: -m 'not slow')")
