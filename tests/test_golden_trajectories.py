"""Golden-trajectory bitwise-identity gate for the unified round kernel.

The refactor contract of the engine-unification PR (train/rounds.py):
with all robustness knobs off, each engine's trajectory — including
fused rounds and kill/resume — must be bitwise identical to the
pre-refactor engines.  The goldens under tests/golden/ were generated
at the pre-refactor commit with::

    FEDTPU_WRITE_GOLDEN=1 python -m pytest tests/test_golden_trajectories.py

and committed; this module re-runs the same tiny configs on the virtual
8-device CPU mesh and compares the full history (repr-exact floats, so
NaN-safe and bit-strict) plus the final parameter bytes (sha256).  Any
numerical drift in the default path — however small — fails here.

Regenerating the goldens is a deliberate act: it asserts the new
trajectory is the intended one (document why in the commit).
"""

import hashlib
import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest

import flax.linen as nn

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import (
    BlockModule,
    elu,
    flatten,
    max_pool_2x2,
    pairs,
)
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FedAvg,
    FederatedConfig,
)

GOLDEN_DIR = Path(__file__).parent / "golden"
WRITE = os.environ.get("FEDTPU_WRITE_GOLDEN") == "1"

K = 4

# the round-record subset that is a pure function of the computation
# (no wall clock, no span/cost bookkeeping); repr() keeps full float
# precision and makes NaN == NaN comparable
_DET_KEYS = ("nloop", "model", "block", "nadmm", "N", "loss", "rho",
             "dual_residual", "primal_residual", "bytes_on_wire",
             "quarantined", "n_active", "guard_trips", "n_ok",
             "host_dispatches")


def _digest(history, state):
    hist = [{k: repr(r.get(k)) for k in _DET_KEYS if k in r}
            for r in history]
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(
            state._asdict() if hasattr(state, "_asdict") else state):
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    # the weights alone (PR 27: the optimizer tree shrank to the active
    # leaves, which changes the hash above and must not change this one)
    w = hashlib.sha256()
    if hasattr(state, "params"):
        for leaf in jax.tree_util.tree_leaves((state.params,
                                               state.batch_stats)):
            w.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    return {"history": hist, "params_sha256": h.hexdigest(),
            "weights_sha256": w.hexdigest()}


def _check(name, digest):
    path = GOLDEN_DIR / f"{name}.json"
    if WRITE:
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_text(json.dumps(digest, indent=1, sort_keys=True) + "\n")
        return
    assert path.exists(), (
        f"golden {path} missing; regenerate at a known-good commit with "
        "FEDTPU_WRITE_GOLDEN=1")
    want = json.loads(path.read_text())
    assert digest["params_sha256"] == want["params_sha256"], \
        f"{name}: final parameter bytes diverged from the golden"
    if "weights_sha256" in want:
        assert digest["weights_sha256"] == want["weights_sha256"], \
            f"{name}: final weights diverged from the golden"
    assert len(digest["history"]) == len(want["history"]), \
        (name, len(digest["history"]), len(want["history"]))
    for i, (got, exp) in enumerate(zip(digest["history"],
                                       want["history"])):
        assert got == exp, f"{name}: round {i} diverged:\n{got}\nvs\n{exp}"


class TinyNet(BlockModule):
    """2-block toy CNN (same shape as tests/test_faults.py)."""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                     name="conv1")(x)))
        x = flatten(x)
        return nn.Dense(10, name="fc1")(x)

    def param_order(self):
        return pairs("conv1", "fc1")

    def train_order_block_ids(self):
        return [[0, 1], [2, 3]]

    def linear_layer_ids(self):
        return [1]


@pytest.fixture(scope="module")
def data():
    return FederatedCifar10(K=K, batch=16, limit_per_client=32,
                            limit_test=32)


def small_cfg(**kw):
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=16,
                check_results=False, admm_rho0=0.1)
    base.update(kw)
    return FederatedConfig(**base)


def _run_classifier(data, algo, **cfg_kw):
    t = BlockwiseFederatedTrainer(TinyNet(), small_cfg(**cfg_kw), data,
                                  algo)
    t.L = 2
    state, hist = t.run(log=lambda m: None)
    return _digest(hist, state)


class TestClassifierGolden:
    def test_admm_default_path(self, data):
        _check("classifier_admm", _run_classifier(data, AdmmConsensus()))

    def test_fedavg_fused_rounds(self, data):
        _check("classifier_fedavg_fused",
               _run_classifier(data, FedAvg(), fused_rounds=True))

    def test_population_off_is_the_seed_path(self, data):
        """``--population`` off (explicitly zeroed) must be the seed
        path bit for bit: the golden generated before population/
        existed still holds, proving the subsystem composes without
        perturbing the default trajectory."""
        _check("classifier_admm",
               _run_classifier(data, AdmmConsensus(), population=0))

    def test_kill_resume_matches_uninterrupted(self, data, tmp_path):
        """Kill after round 1 (mid-block), resume in a fresh trainer:
        the combined trajectory must equal the UNINTERRUPTED golden."""
        cfg = small_cfg()
        ck = str(tmp_path / "ck")

        class Killed(Exception):
            pass

        def bomb(state, rec):
            if rec["nadmm"] == 1 and rec["block"] == 0:
                raise Killed

        t1 = BlockwiseFederatedTrainer(TinyNet(), cfg, data,
                                       AdmmConsensus())
        t1.L = 2
        with pytest.raises(Killed):
            t1.run(log=lambda m: None, checkpoint_path=ck, on_round=bomb)
        t2 = BlockwiseFederatedTrainer(TinyNet(), cfg, data,
                                       AdmmConsensus())
        t2.L = 2
        state, hist = t2.run(log=lambda m: None, checkpoint_path=ck,
                             resume=True)
        _check("classifier_admm", _digest(hist, state))


class TestVAEGolden:
    def _make(self, data, **cfg_kw):
        from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN
        from federated_pytorch_test_tpu.train.vae_engine import VAETrainer

        t = VAETrainer(AutoEncoderCNN(), small_cfg(**cfg_kw), data,
                       FedAvg())
        t.L = 1
        return t

    def test_default_path(self, data):
        state, hist = self._make(data).run(log=lambda m: None)
        _check("vae_fedavg", _digest(hist, state))

    def test_fused_rounds(self, data):
        state, hist = self._make(data, fused_rounds=True).run(
            log=lambda m: None)
        _check("vae_fused", _digest(hist, state))

    def test_kill_resume_matches_uninterrupted(self, data, tmp_path):
        ck = str(tmp_path / "ck")

        class Killed(Exception):
            pass

        def bomb(state, rec):
            # kill MID-BLOCK (a later round still runs after resume, so
            # the final state is live, not a restored block-boundary
            # snapshot whose opt_state was legitimately dropped)
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            self._make(data).run(log=lambda m: None, checkpoint_path=ck,
                                 on_round=bomb)
        state, hist = self._make(data).run(log=lambda m: None,
                                           checkpoint_path=ck, resume=True)
        _check("vae_fedavg", _digest(hist, state))


class TestCPCGolden:
    def _make(self):
        from federated_pytorch_test_tpu.data.lofar import CPCDataSource
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        src = CPCDataSource(["a.h5", "b.h5"], ["0", "1"], batch_size=2,
                            seed=7)
        return CPCTrainer(src, latent_dim=8, reduced_dim=4,
                          lbfgs_history=3, lbfgs_max_iter=1, Niter=1)

    @pytest.mark.long(seconds=80)
    def test_default_path(self):
        state, hist = self._make().run(Nloop=1, Nadmm=2,
                                       log=lambda m: None)
        _check("cpc_admm", _digest(hist, state))

    # ~30 s (two CPC runs): the CPC trajectory itself stays pinned by
    # test_default_path above; classifier + VAE keep their fast golden
    # kill/resume cases, and the one-round-kernel refactor means the
    # checkpoint path under test is engine-shared
    @pytest.mark.slow
    def test_kill_resume_matches_uninterrupted(self, tmp_path):
        """Stop after 3 rounds (mid-block) via the log callback, resume
        in a fresh trainer: combined history must equal the golden."""
        ck = str(tmp_path / "ck")

        class Stop(Exception):
            pass

        calls = []

        def bomb(msg):
            calls.append(msg)
            if len(calls) == 3:
                raise Stop

        with pytest.raises(Stop):
            self._make().run(Nloop=1, Nadmm=2, log=bomb,
                             checkpoint_path=ck)
        state, hist = self._make().run(Nloop=1, Nadmm=2,
                                       log=lambda m: None,
                                       checkpoint_path=ck, resume=True)
        _check("cpc_admm", _digest(hist, state))


@pytest.mark.skipif(not WRITE, reason="generation mode only")
def test_goldens_written():
    for name in ("classifier_admm", "classifier_fedavg_fused",
                 "vae_fedavg", "vae_fused", "cpc_admm"):
        assert (GOLDEN_DIR / f"{name}.json").exists(), name
