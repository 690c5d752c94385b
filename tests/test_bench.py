"""bench.py contract: one process that measures on a TPU or fails.

No artifact is printed and the exit code is non-zero when JAX finds no
TPU, when the ``device_kind`` has no peak on record, and when any
measurement phase raises.  Every printed result names the device."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

V5E = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def test_off_chip_exits_nonzero_and_prints_no_artifact():
    """End to end in a fresh interpreter on the CPU backend."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("FEDTPU_BENCH_")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "platform=cpu" in r.stderr and "device_count=" in r.stderr


def test_it_is_one_process(monkeypatch):
    """Nothing on the measured path may start a child: a parent that has
    touched JAX holds the chip."""
    def no_children(*a, **kw):
        pytest.fail(f"bench started a child process: {a}")

    monkeypatch.setattr(bench.subprocess, "Popen", no_children)
    monkeypatch.setattr(bench, "_git_describe", lambda: "abc1234")
    monkeypatch.setattr(bench.subprocess, "run", no_children)
    monkeypatch.setattr(bench, "_device_fields", lambda: dict(V5E))
    monkeypatch.setattr(bench, "_measure",
                        lambda out: out.update(value=1.0, measured=True))
    assert bench.main() == 0
    assert not hasattr(bench, "_acquire_backend")
    assert not hasattr(bench, "_run_measurement")


def test_artifact_names_the_device(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_device_fields", lambda: dict(V5E))
    monkeypatch.setattr(bench, "_measure",
                        lambda out: out.update(value=1.0, measured=True))
    assert bench.main() == 0
    art = json.loads(capsys.readouterr().out.strip())
    for key, want in V5E.items():
        assert art[key] == want
    assert art["unit"] == "images/sec/chip" and art["value"] == 1.0


def test_device_fields_are_what_jax_reports():
    import jax

    got = bench._device_fields()
    assert got == {"platform": jax.devices()[0].platform,
                   "device_kind": jax.devices()[0].device_kind,
                   "device_count": len(jax.devices())}


def test_unknown_device_kind_is_an_error(monkeypatch, capsys):
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peak"):
        bench._peak_flops("TPU v99")
    monkeypatch.setattr(bench, "_device_fields",
                        lambda: dict(V5E, device_kind="TPU v99"))
    monkeypatch.setattr(bench, "_measure",
                        lambda out: pytest.fail("timed an unknown device"))
    with pytest.raises(ValueError, match="TPU v99"):
        bench.main()
    assert capsys.readouterr().out.strip() == ""


def test_a_failing_phase_fails_the_run(monkeypatch, capsys):
    """The side groups used to be wrapped in try/except-print; a phase
    that dies must take the run (and the artifact) with it."""
    monkeypatch.setattr(bench, "_device_fields", lambda: dict(V5E))

    def measure(out):
        out["value"] = 1.0
        raise RuntimeError("compression phase fell over")

    monkeypatch.setattr(bench, "_measure", measure)
    with pytest.raises(RuntimeError, match="fell over"):
        bench.main()
    assert capsys.readouterr().out.strip() == ""


def test_measure_does_not_swallow_side_group_failures(monkeypatch):
    """Inside _measure too: the InfoNCE / CPC / VAE / compression calls
    are plain calls, not guarded ones."""
    import inspect

    src = inspect.getsource(bench._measure)
    assert "try:" not in src and "except" not in src
