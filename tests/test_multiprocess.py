"""REAL multi-process coverage of the multi-host seams.

The quick tests force ``_process_count() == 2`` inside one process, which
executes the multi-process branches but over fully-addressable arrays —
``process_allgather`` then takes its host-local path, not the replicate
path a pod takes (see the caveat on
``test_engine.py::test_multiprocess_branches_run``).  Here two REAL
``jax.distributed`` processes (2 virtual CPU devices each, one 4-device
global mesh) run a federated round end-to-end, so ``stage_global``'s
make_array_from_callback staging, ``stage_client_rows``'s
process-local-data staging, ``local_client_rows``'s ownership split and
``fetch``'s cross-process all-gather all execute against genuinely
non-addressable shards (SURVEY.md section 5 comm plan; the reference's
equivalent scale-out is its MPI/NCCL layer).
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import json, os, sys
pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=nproc, process_id=pid)
assert jax.process_count() == nproc, jax.process_count()
assert len(jax.devices()) == 2 * nproc      # global mesh
assert len(jax.local_devices()) == 2

import numpy as np
from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.simple import Net
from federated_pytorch_test_tpu.parallel import mesh as meshmod
from federated_pytorch_test_tpu.train import (
    BlockwiseFederatedTrainer, FedAvg, FederatedConfig,
)

K = 4
mesh = meshmod.client_mesh(2 * nproc)

# ownership split: each process holds its own contiguous client rows
rows = meshmod.local_client_rows(mesh, K)
assert rows == list(range(pid * 2, pid * 2 + 2)), rows

# stage_client_rows: non-addressable global array from per-process slabs
full = np.arange(K * 3, dtype=np.float32).reshape(K, 3)
staged = meshmod.stage_client_rows(full[rows], meshmod.client_sharding(mesh))
assert not staged.is_fully_addressable
np.testing.assert_array_equal(meshmod.fetch(staged), full)   # allgather

# one federated round through the real engine on the 2-process mesh
cfg = FederatedConfig(K=K, Nloop=1, Nepoch=1, Nadmm=1, default_batch=8,
                      check_results=True, admm_rho0=0.1)
data = FederatedCifar10(K=K, batch=8, limit_per_client=16, limit_test=8)
trainer = BlockwiseFederatedTrainer(Net(), cfg, data, FedAvg(), mesh=mesh)
trainer.L = 1
state, hist = trainer.run(log=lambda m: None)
rec = hist[0]

# mid-run checkpointing on the 2-process mesh: the orbax save is a
# collective; ALL slot surgery (promote/sweep/swap) runs on process 0
# between barriers (utils/checkpoint.py).  Then a resumed run restores
# the completed history as a no-op.
ck = os.path.join(sys.argv[4], "mp_ck")
cfg2 = FederatedConfig(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=8,
                       check_results=False, admm_rho0=0.1)
t2 = BlockwiseFederatedTrainer(Net(), cfg2, data, FedAvg(), mesh=mesh)
t2.L = 1
_, h2 = t2.run(log=lambda m: None, checkpoint_path=ck)
t3 = BlockwiseFederatedTrainer(Net(), cfg2, data, FedAvg(), mesh=mesh)
t3.L = 1
_, h3 = t3.run(log=lambda m: None, checkpoint_path=ck, resume=True)
assert len(h2) == 2 and len(h3) == 2, (len(h2), len(h3))
assert h3[-1]["dual_residual"] == h2[-1]["dual_residual"]

# MID-BLOCK kill + resume: the round-0 checkpoint has mid_block=True, so
# the resume restores opt_state_leaves and the ADMM block vars — the
# restore consumers that exercise stage_tree_global's non-addressable
# branch hardest — and must continue to the uninterrupted trajectory.
class Killed(Exception):
    pass

def bomb(state, rec):
    if rec["nadmm"] == 0:
        raise Killed

ck2 = os.path.join(sys.argv[4], "mp_ck2")
t4 = BlockwiseFederatedTrainer(Net(), cfg2, data, FedAvg(), mesh=mesh)
t4.L = 1
try:
    t4.run(log=lambda m: None, checkpoint_path=ck2, on_round=bomb)
    raise AssertionError("bomb did not fire")
except Killed:
    pass
t5 = BlockwiseFederatedTrainer(Net(), cfg2, data, FedAvg(), mesh=mesh)
t5.L = 1
_, h5 = t5.run(log=lambda m: None, checkpoint_path=ck2, resume=True)
assert len(h5) == 2, len(h5)
assert h5[-1]["dual_residual"] == h2[-1]["dual_residual"], \
    (h5[-1]["dual_residual"], h2[-1]["dual_residual"])

print("RESULT", json.dumps({
    "pid": pid,
    "loss": rec["loss"],
    "dual": rec["dual_residual"],
    "acc": [float(a) for a in rec["accuracy"]],
    "ck_dual": h2[-1]["dual_residual"],
}), flush=True)
"""


@pytest.mark.slow
def test_two_process_mesh_runs_and_agrees(tmp_path):
    # best-effort free port (racy in principle: another process could grab
    # it between close and the coordinator's bind; SO_REUSEADDR + the
    # ephemeral range makes that vanishingly rare on this single-user box)
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    # stable cache dir so reruns hit warm XLA executables (cache keys
    # include device topology, so the suite's 8-device entries can't
    # collide with these 2-device ones; a distinct dir just keeps the
    # shared cache free of multi-process entries)
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(__file__), ".jax_cache_mp")
    # file-redirected output: PIPE would deadlock if an undrained worker
    # filled its pipe buffer mid-collective while we communicate() with
    # the other one
    logs = [tmp_path / f"worker{i}.log" for i in range(2)]
    procs = []
    try:
        ckdir = tmp_path / "ck"
        ckdir.mkdir()
        for i in range(2):
            with open(logs[i], "w") as f:
                procs.append(subprocess.Popen(
                    [sys.executable, str(worker), str(i), "2", str(port),
                     str(ckdir)],
                    env=env, cwd=REPO, stdout=f, stderr=subprocess.STDOUT))
        for p in procs:
            try:
                p.wait(timeout=540)
            except subprocess.TimeoutExpired:
                pytest.fail("multi-process worker hung")
    finally:
        # a failed worker must not leave its peer blocked in a collective
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    outs = [log.read_text() for log in logs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"

    import json as js
    results = []
    for out in outs:
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert len(lines) == 1, out
        results.append(js.loads(lines[0][len("RESULT "):]))
    a, b = sorted(results, key=lambda r: r["pid"])
    # SPMD: every process computes the same global metrics
    assert a["loss"] == b["loss"]
    assert a["dual"] == b["dual"]
    np.testing.assert_array_equal(a["acc"], b["acc"])
    assert np.isfinite(a["loss"]) and np.isfinite(a["dual"])
    # the checkpointed + resumed leg agreed across processes too
    assert a["ck_dual"] == b["ck_dual"] and np.isfinite(a["ck_dual"])
