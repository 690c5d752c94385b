"""Pallas kernel ops vs their XLA reference paths.

The kernels are exercised on CPU via ``interpret=True``
(``force_infonce_impl("pallas_interpret")``), so the same kernel code that
runs compiled on TPU is validated in CI without TPU hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.ops.infonce import (
    _pallas_bwd_fits,
    _pallas_fits,
    force_infonce_impl,
    info_nce_fused,
)
from federated_pytorch_test_tpu.train.cpc_losses import info_nce


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32))


def _grad_tol():
    """Gradient comparison tolerance: TPU matmul rounding (even at f32
    precision) shifts small-shape gradients by up to ~3e-4 relative, so the
    FEDTPU_TEST_TPU=1 run needs more headroom than the CPU mesh."""
    if jax.default_backend() == "tpu":
        return dict(rtol=2e-3, atol=1e-5)
    return dict(rtol=1e-4, atol=1e-6)


class TestInfoNCEPallas:
    @pytest.mark.parametrize("B,px,py,R", [
        (3, 2, 3, 4),      # P=6 — single tile, heavy padding
        (2, 12, 12, 3),    # P=144 — two row tiles (grid > 1)
    ])
    def test_kernel_matches_xla(self, B, px, py, R):
        z = _rand((B, px, py, R), 0)
        zhat = _rand((B, px, py, R), 1)
        with force_infonce_impl("xla"):
            want = float(info_nce_fused(z, zhat))
        with force_infonce_impl("pallas_interpret"):
            got = float(info_nce_fused(z, zhat))
        np.testing.assert_allclose(got, want, rtol=1e-5)
        # and both equal the plain train/cpc_losses implementation
        np.testing.assert_allclose(want, float(info_nce(z, zhat)), rtol=1e-5)

    @pytest.mark.parametrize("B,px,py,R", [
        (2, 2, 2, 3),      # P=4 — single tile, heavy padding
        (2, 12, 12, 3),    # P=144 — two row tiles: exercises the backward
                           # kernel's cross-tile dZhat accumulation
    ])
    def test_gradients_flow_through_kernel(self, B, px, py, R):
        z = _rand((B, px, py, R), 2)
        zhat = _rand((B, px, py, R), 3)
        with force_infonce_impl("pallas_interpret"):
            gz, gzh = jax.grad(info_nce_fused, argnums=(0, 1))(z, zhat)
        wz, wzh = jax.grad(info_nce, argnums=(0, 1))(z, zhat)
        np.testing.assert_allclose(np.asarray(gz), np.asarray(wz),
                                   **_grad_tol())
        np.testing.assert_allclose(np.asarray(gzh), np.asarray(wzh),
                                   **_grad_tol())

    def test_backward_kernel_scales_with_cotangent(self):
        """The VJP threads the incoming cotangent through ghat; a scaled
        downstream loss must scale the Pallas-kernel gradients exactly."""
        z = _rand((2, 3, 3, 4), 8)
        zhat = _rand((2, 3, 3, 4), 9)
        with force_infonce_impl("pallas_interpret"):
            g1 = jax.grad(lambda a, b: info_nce_fused(a, b))(z, zhat)
            g3 = jax.grad(lambda a, b: 3.0 * info_nce_fused(a, b))(z, zhat)
        np.testing.assert_allclose(np.asarray(g3), 3 * np.asarray(g1),
                                   rtol=1e-5)

    def test_value_and_grad_under_scan(self):
        """The CPC LBFGS closure calls value_and_grad inside lax.scan under
        jit — both Pallas kernels (fwd + bwd) must trace cleanly there."""
        z = _rand((2, 2, 2, 3), 10)
        zhat = _rand((2, 2, 2, 3), 11)

        @jax.jit
        def scanned(z, zhat):
            def step(c, _):
                v, g = jax.value_and_grad(info_nce_fused)(z, zhat)
                return (c[0] + v, c[1] + g), None
            (v, g), _ = jax.lax.scan(
                step, (jnp.float32(0), jnp.zeros_like(z)), None, length=2)
            return v, g

        with force_infonce_impl("pallas_interpret"):
            v, g = scanned(z, zhat)
        wv, wg = jax.value_and_grad(info_nce)(z, zhat)
        np.testing.assert_allclose(float(v), 2 * float(wv), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(wg),
                                   **_grad_tol())

    def test_kernel_works_under_jit_and_scan(self):
        """The CPC closure runs under jit inside lax.scan — the kernel must
        trace cleanly there."""
        z = _rand((2, 2, 2, 3), 4)
        zhat = _rand((2, 2, 2, 3), 5)

        @jax.jit
        def scanned(z, zhat):
            def step(c, _):
                return c + info_nce_fused(z, zhat), None
            out, _ = jax.lax.scan(step, jnp.float32(0), None, length=3)
            return out

        with force_infonce_impl("pallas_interpret"):
            got = float(scanned(z, zhat))
        np.testing.assert_allclose(got, 3 * float(info_nce(z, zhat)),
                                   rtol=1e-5)

    def test_vmem_guard(self):
        assert _pallas_fits(128, 256)
        assert not _pallas_fits(200_000, 8192)   # would blow VMEM
        assert _pallas_bwd_fits(512, 256)        # the CPC training shape
        assert not _pallas_bwd_fits(200_000, 8192)

    def test_compiled_kernels_on_tpu(self):
        """Both Pallas kernels COMPILED (Mosaic, not interpret) vs XLA on
        the TPU backend, at a grid-spanning shape (P=256 -> two row tiles;
        D=512, the CPC training scale).  Skipped off-TPU: conftest pins the
        test env to the CPU mesh unless ``FEDTPU_TEST_TPU=1``, so this runs
        via ``FEDTPU_TEST_TPU=1 pytest tests/test_ops.py`` on a TPU host
        (a Mosaic miscompile of e.g. the backward's sequential-grid dZhat
        accumulation must surface here, not in a user's training run)."""
        if jax.default_backend() != "tpu":
            pytest.skip("real TPU backend required (FEDTPU_TEST_TPU=1)")
        z = _rand((16, 16, 16, 32), 20)      # P=256, D=512
        zhat = _rand((16, 16, 16, 32), 21)
        with force_infonce_impl("xla"):
            want_v, (want_gz, want_gzh) = jax.jit(
                lambda a, b: jax.value_and_grad(info_nce_fused,
                                                argnums=(0, 1))(a, b))(z, zhat)
        with force_infonce_impl("pallas"):
            got_v, (got_gz, got_gzh) = jax.jit(
                lambda a, b: jax.value_and_grad(info_nce_fused,
                                                argnums=(0, 1))(a, b))(z, zhat)
        np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got_gz), np.asarray(want_gz),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_gzh), np.asarray(want_gzh),
                                   rtol=1e-4, atol=1e-6)

    def test_compiled_comm_kernels_on_tpu(self):
        """The three comm kernels COMPILED by Mosaic vs their XLA chains,
        at the shape the fused collective produces for the largest
        ResNet18 block ([N/256, 256], N = 4,720,640) and a [K, n] Gram
        slab.  Interpret mode is bitwise (tests/test_comm_kernels.py);
        the hardware contract is allclose (PARITY.md)."""
        if jax.default_backend() != "tpu":
            pytest.skip("real TPU backend required (FEDTPU_TEST_TPU=1)")
        from federated_pytorch_test_tpu.ops.comm_kernels import (
            dequant_add,
            force_comm_kernels_impl,
            gram_matrix,
            quantize_chunks,
        )

        vv = _rand((4_720_640 // 256, 256), 30)
        acc = _rand(vv.shape, 31)
        stack = _rand((8, 1 << 20), 32)
        got = {}
        for impl in ("xla", "pallas"):
            with force_comm_kernels_impl(impl):
                q, s = jax.jit(lambda v: quantize_chunks(v, 127))(vv)
                got[impl] = (q, s,
                             jax.jit(lambda a, q, s: dequant_add(a, q, s))(
                                 acc, q, s),
                             jax.jit(lambda a: gram_matrix(a))(stack))
        (qx, sx, ax, gx), (qp, sp, ap, gp) = got["xla"], got["pallas"]
        np.testing.assert_allclose(np.asarray(sp), np.asarray(sx), rtol=1e-6)
        dq = np.abs(np.asarray(qp, np.int32) - np.asarray(qx, np.int32))
        assert dq.max() <= 1 and dq.mean() < 1e-3
        np.testing.assert_allclose(np.asarray(ap), np.asarray(ax),
                                   rtol=1e-5, atol=1e-5)
        # conftest pins float32 matmul precision, so both Gram paths
        # carry f32 products; the slab accumulation re-associates
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                                   rtol=1e-4, atol=1e-4 * (1 << 20))

    def test_zero_norm_column_finite_and_consistent(self):
        """A dead (all-zero) patch column must give the same finite loss
        and finite gradients on every dispatch path (safe_norms guard)."""
        z = _rand((2, 2, 2, 3), 6)
        zhat = _rand((2, 2, 2, 3), 7)
        # zero out patch position (0, 0) across batch/channels in z
        z = z.at[:, 0, 0, :].set(0.0)
        with force_infonce_impl("xla"):
            want = float(info_nce_fused(z, zhat))
            gz, _ = jax.grad(info_nce_fused, argnums=(0, 1))(z, zhat)
        with force_infonce_impl("pallas_interpret"):
            got = float(info_nce_fused(z, zhat))
            gz2, _ = jax.grad(info_nce_fused, argnums=(0, 1))(z, zhat)
        assert np.isfinite(want) and np.isfinite(got)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        assert np.all(np.isfinite(np.asarray(gz)))
        np.testing.assert_allclose(np.asarray(gz2), np.asarray(gz),
                                   **_grad_tol())
        # autodiff straight through the XLA path (no custom VJP) must be
        # finite too: safe_norms guards inside the sqrt, so the norm VJP
        # cannot produce 0/0 at a zero column (train/cpc_losses.py)
        gz3, _ = jax.grad(info_nce, argnums=(0, 1))(z, zhat)
        assert np.all(np.isfinite(np.asarray(gz3)))
        np.testing.assert_allclose(np.asarray(gz3), np.asarray(gz),
                                   **_grad_tol())
