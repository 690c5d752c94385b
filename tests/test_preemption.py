"""Preemption-tolerant collectives (elastic-federation tentpole).

Fast half: ``bounded_wait``'s contract in-process — timeout <= 0 is the
literal unwrapped call (bit-identity), a hung callable converts into the
typed ``CollectiveTimeoutError`` naming the site and bound, a callable
that raises re-raises its own error, and the env/config plumbing for the
global bound.

Slow half: two REAL ``jax.distributed`` processes.  Worker 1 dies right
after a warm-up barrier (a simulated preemption); worker 0's next
``sync_global`` would block on the coordination service until its ~100s
peer-heartbeat timeout — the 8s ``FEDTPU_BARRIER_TIMEOUT`` bound must
convert that hang into ``CollectiveTimeoutError`` first, which is the
signal the restart supervisor's reshape rung consumes
(control/supervisor.py).
"""

import os
import socket
import subprocess
import sys
import time

import pytest

from federated_pytorch_test_tpu.parallel.mesh import (
    CollectiveTimeoutError,
    barrier_timeout,
    bounded_wait,
    collective_timeout_count,
    configure_barrier_timeout,
    heartbeat,
    last_heartbeat_age,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestBoundedWait:
    def test_zero_timeout_is_the_literal_call(self):
        # bit-identity contract: no thread, no wrapping — the return
        # value and any exception pass straight through
        calls = []
        assert bounded_wait(lambda: calls.append(1) or 7,
                            name="t", timeout=0) == 7
        assert calls == [1]
        with pytest.raises(KeyError):
            bounded_wait(lambda: {}["missing"], name="t", timeout=0)

    def test_hung_callable_raises_typed_error(self):
        before = collective_timeout_count()
        with pytest.raises(CollectiveTimeoutError, match="sync:stuck"):
            bounded_wait(lambda: time.sleep(30), name="sync:stuck",
                         timeout=0.1)
        assert collective_timeout_count() == before + 1

    def test_peer_error_re_raised_not_swallowed(self):
        def dead():
            raise RuntimeError("peer went away")

        with pytest.raises(RuntimeError, match="peer went away"):
            bounded_wait(dead, name="t", timeout=5.0)

    def test_result_returned_within_bound(self):
        assert bounded_wait(lambda: 42, name="t", timeout=5.0) == 42

    def test_configure_and_env_plumbing(self, monkeypatch):
        prev = configure_barrier_timeout(3.5)
        try:
            assert barrier_timeout() == 3.5
        finally:
            configure_barrier_timeout(prev)
        # the module-load seed comes from FEDTPU_BARRIER_TIMEOUT
        from federated_pytorch_test_tpu.parallel.mesh import (
            _env_barrier_timeout,
        )
        monkeypatch.setenv("FEDTPU_BARRIER_TIMEOUT", "2.5")
        assert _env_barrier_timeout() == 2.5
        monkeypatch.setenv("FEDTPU_BARRIER_TIMEOUT", "junk")
        assert _env_barrier_timeout() == 0.0

    def test_heartbeat_age_tracks_progress(self):
        heartbeat("unit")
        age = last_heartbeat_age()
        assert age is not None and age >= 0.0


_WORKER = r"""
import json, os, sys, time
pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["FEDTPU_BARRIER_TIMEOUT"] = "8"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=nproc, process_id=pid)
assert jax.process_count() == nproc

from federated_pytorch_test_tpu.parallel.mesh import (
    CollectiveTimeoutError, collective_timeout_count, sync_global,
)

# both workers meet at the warm-up barrier, proving the bounded wrapper
# passes a healthy collective through
sync_global("warmup")

if pid == 1:
    # simulated preemption: die without detaching — the peer's next
    # barrier now has nobody to meet
    os._exit(1)

time.sleep(1.0)        # let the peer's exit land
t0 = time.monotonic()
try:
    sync_global("dead-peer")
    print("RESULT", json.dumps({"caught": False}), flush=True)
except CollectiveTimeoutError as e:
    print("RESULT", json.dumps({
        "caught": True,
        "waited": time.monotonic() - t0,
        "timeouts": collective_timeout_count(),
        "message": str(e)[:200],
    }), flush=True)
# skip jax.distributed shutdown: it would block on the dead peer
os._exit(0)
"""


@pytest.mark.slow
def test_two_process_preemption_times_out_typed(tmp_path):
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(__file__), ".jax_cache_mp")
    logs = [tmp_path / f"worker{i}.log" for i in range(2)]
    procs = []
    try:
        for i in range(2):
            with open(logs[i], "w") as f:
                procs.append(subprocess.Popen(
                    [sys.executable, str(worker), str(i), "2", str(port)],
                    env=env, cwd=REPO, stdout=f,
                    stderr=subprocess.STDOUT))
        try:
            procs[0].wait(timeout=540)
        except subprocess.TimeoutExpired:
            pytest.fail("surviving worker hung past the barrier bound")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    out = logs[0].read_text()
    assert procs[0].returncode == 0, f"survivor failed:\n{out[-3000:]}"

    import json as js
    lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    assert len(lines) == 1, out
    res = js.loads(lines[0][len("RESULT "):])
    assert res["caught"] is True, res
    assert res["timeouts"] >= 1
    # the typed error fired at the configured bound, far ahead of the
    # coordination service's own peer-failure detection
    assert res["waited"] < 60, res
    assert "dead-peer" in res["message"]
