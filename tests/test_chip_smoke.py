"""chip_smoke.py's phases, tiny, on the CPU mesh (kernels in interpret
mode) — and the one thing only ``main()`` does: refuse a backend that is
not the TPU.  The chip run itself is ``python3 chip_smoke.py`` through
the chip tool; this file keeps the script's plumbing from rotting
between chip runs."""

import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from federated_pytorch_test_tpu.ops.comm_kernels import (  # noqa: E402
    force_comm_kernels_impl,
)

TINY = dict(model="net", batch=16, steps=2)


def test_main_refuses_a_cpu_backend():
    """With the sandbox's JAX_PLATFORMS=cpu exported the script must not
    quietly become a CPU run: non-zero exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout
    assert '"ok"' not in r.stdout
    assert "no TPU backend" in r.stderr or "not tpu" in r.stderr


def test_train_phase_through_the_driver(tmp_path):
    out = chip_smoke.phase_train(
        8, model_argv=("--model", "net"), batch=32, n_train=64, n_test=64,
        platform="cpu", out_dir=str(tmp_path / "out"),
        ckpt_dir=str(tmp_path / "ck"))
    assert out["D"] == len(jax.devices()) and out["rounds"] == 10
    assert [b["block"] for b in out["blocks"]] == list(range(5))
    assert not (tmp_path / "ck").exists()        # read back, then removed


def test_a_failing_phase_is_reported_and_the_rest_still_run(capsys):
    ran = []

    def bad():
        ran.append("bad")
        chip_smoke.check(False, "accuracy is not above chance")

    results, failed = chip_smoke.run_phases([
        ("good", lambda: ran.append("good") or {"x": 1}),
        ("bad", bad),
        ("raises", lambda: 1 / 0),
        ("after", lambda: ran.append("after") or {})])
    assert ran == ["good", "bad", "after"]
    assert failed == ["bad", "raises"]       # main() exits non-zero on these
    assert results["good"]["status"] == "ok" and results["good"]["x"] == 1
    assert "above chance" in results["bad"]["error"]
    assert "ZeroDivisionError" in results["raises"]["error"]
    assert "phase bad: FAILED" in capsys.readouterr().out


def test_parity_and_mesh_phases_share_the_full_run():
    result, full_run = chip_smoke.phase_parity(
        8, cpu_devices=jax.devices()[:1], **TINY)
    assert result["mesh"] == "cpux8" and result["cpu_mesh"] == "cpux1"
    assert result["loss_rel"] <= 1e-3
    out = chip_smoke.phase_mesh(8, full_run=full_run, **TINY)
    assert out["D"] == 8 and out["rows_per_device"] == 1


def test_kernels_phase_interpreted():
    out = chip_smoke.phase_kernels(
        impl="pallas_interpret", N=8192, K=8, D=2,
        infonce_shapes=[(64, 16, True), (96, 144, False)])
    assert out["quantize_chunks"]["max_dq"] == 0       # interpret: bitwise
    assert out["gram_matrix"]["shape"] == [8, 4096]
    # off the chip auto-dispatch must resolve to the XLA paths, and say so
    assert out["dispatch"]["infonce_cpc_reference"]["forward"] == "xla"
    assert out["dispatch"]["comm_kernels"]["gram_matrix"]["impl"] == "xla"
    assert out["dispatch"]["topk"]["impl"] == "xla"


def test_compressed_phase_runs_the_kernels_inside_the_engine():
    with force_comm_kernels_impl("pallas_interpret"):
        out = chip_smoke.phase_compressed(8, bf16=False, **TINY)
    assert set(out) == {"q8+fused", "topk", "krum+chunked"}
    assert out["q8+fused"]["hops"] == 3 and out["q8+fused"]["bytes_fused"] > 0
