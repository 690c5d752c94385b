"""Engine kind ``cpc``: ``CPCTrainer`` built the way the CPC driver
builds it, one ``run(Nloop, Nadmm)`` per pass as ``bench.py:_bench_cpc``
does (``CPCTrainer.run`` has no ``on_round``: PERF.md, Open questions).

No cell of ``BENCHMARK.json`` uses this engine yet: with the program's
stand-in generator drawing every minibatch anew the chip idled 90 % of a
rotation (PR 22, PERF.md section 6), so the data is pooled (below), and
the pooled cell has run on the CPU only.  A later benchmark PR measures
it on the chip and lists it.

Configuration keys read: ``latent_dim``, ``reduced_dim``, ``batch``,
``patch_size``, ``K``, ``lbfgs_history``, ``lbfgs_max_iter``.
Traffic keys read: ``Nloop``, ``Nadmm``, ``Niter``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict

import numpy as np

from benchmarks.lib.cells import Cell
from benchmarks.lib.window import Window
from federated_pytorch_test_tpu.data.lofar import CPCDataSource


def _dispatch():
    """On a TPU, whatever the tree's auto-dispatch resolves to.  Off it
    (the CPU rehearsal) the Pallas kernels in interpret mode, so that the
    rehearsal walks the kernel path and not the XLA fallback."""
    import jax

    from federated_pytorch_test_tpu.ops.infonce import force_infonce_impl

    if jax.default_backend() == "tpu":
        return contextlib.nullcontext()
    return force_infonce_impl("pallas_interpret")


class PooledSource(CPCDataSource):
    """ONE round's minibatches per client, drawn in set-up with the
    program's own generator and handed out in a new seeded order every
    round.

    The program's synthetic generator (``data/lofar.py``) rebuilds its
    cube in numpy for every minibatch, about 10 s a round on the chip's
    host beside a round program of 1 s.  It is a stand-in for h5 files no
    user of the system reads that way, and it would make the cell a
    measurement of numpy.  What stays in the round is what a user pays:
    the host-to-device copy of a round's minibatches and the round."""

    def __init__(self, files, saps, niter: int, **kw):
        super().__init__(files, saps, **kw)
        # [K, niter, batch * px * py, patch, patch, 8]
        self.px, self.py, self.pool = super().round_batches(niter)

    def round_batches(self, niter, clients=None):
        if niter != self.pool.shape[1]:
            raise ValueError(f"the pool holds {self.pool.shape[1]} "
                             f"minibatches a client, asked for {niter}")
        with self._lock:        # as the parent: the prefetcher's thread
            rnd = self._round   # and the caller's both bump the counter
            self._round += 1
        order = np.random.default_rng([self.seed, rnd]).permutation(niter)
        rows = self.pool if clients is None else self.pool[list(clients)]
        return self.px, self.py, rows[:, order]


class Session:
    def __init__(self, cell: Cell, seed: int, obs_dir=None):
        self.cell, self.seed, self.obs_dir = cell, seed, obs_dir
        c, t = cell.config, cell.traffic
        K = int(c["K"])
        # one client per (file, SAP) pair; the synthetic cube is seeded
        # by its file name (data/lofar.py), so the names carry the seed
        self.source = PooledSource(
            [f"bench_seed{seed}_{i}.h5" for i in range(K)], ["0"] * K,
            int(t["Niter"]), batch_size=int(c["batch"]),
            patch_size=int(c["patch_size"]), seed=seed)
        self.px, self.py = self.source.px, self.source.py
        # patches as bench.py:_bench_cpc counts them: every staged
        # minibatch holds batch x px x py of them
        self.samples_per_round = (int(t["Niter"]) * K
                                  * int(self.source.pool.shape[2]))
        self.samples_per_pass = None        # rounds per pass: known after
        self.obs_path = None                # the untimed pass
        self.counters: Dict[str, float] = {}

    def run(self, window: Window) -> None:
        import jax

        from federated_pytorch_test_tpu.train.config import FederatedConfig
        from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer

        c, t = self.cell.config, self.cell.traffic
        cfg = FederatedConfig(
            K=self.source.K, init_seed=self.seed, seed=self.seed,
            num_devices=self.cell.chips, retrace_sentinel=True,
            check_results=False)
        trainer = CPCTrainer(
            self.source, latent_dim=int(c["latent_dim"]),
            reduced_dim=int(c["reduced_dim"]),
            lbfgs_history=int(c["lbfgs_history"]),
            lbfgs_max_iter=int(c["lbfgs_max_iter"]), Niter=int(t["Niter"]),
            cfg=cfg)
        state, done = None, False
        try:
            with _dispatch():
                while not done:
                    state, history = trainer.run(
                        Nloop=int(t["Nloop"]), Nadmm=int(t["Nadmm"]),
                        state=state, log=lambda msg: None,
                        obs_dir=self.obs_dir, obs_run_name=self.cell.name)
                    self.samples_per_pass = (len(history)
                                             * self.samples_per_round)
                    done = window.pass_done(
                        history, lambda: jax.block_until_ready(state))
        finally:
            window.abort()
            rec = trainer.obs_recorder
            self.obs_path = getattr(rec, "jsonl_path", None)

    # ------------------------------------------------------------------
    def check(self) -> Dict[str, Any]:
        """The InfoNCE loss and its gradients, through the path the
        engine dispatches to (``ops.infonce.info_nce_fused``, vmapped
        over clients and jitted as the round program calls it), against
        the plain float32 reference on one seeded batch of the cell's
        shape ``[K, batch, px, py, reduced_dim]``."""
        import jax
        import jax.numpy as jnp

        from benchmarks.reference import infonce as reference
        from federated_pytorch_test_tpu.ops.infonce import info_nce_fused

        t0 = time.perf_counter()
        c = self.cell.config
        shape = (min(int(c["K"]), 4), int(c["batch"]), self.px, self.py,
                 int(c["reduced_dim"]))
        kz, kh = jax.random.split(jax.random.PRNGKey(self.seed))
        z = jax.random.normal(kz, shape, jnp.float32)
        # predictions correlated with the latents, as a trained predictor's
        # are: an all-noise pair puts every soft-max near 1/P
        zhat = 0.5 * z + jax.random.normal(kh, shape, jnp.float32)
        vg = lambda f: jax.jit(jax.vmap(jax.value_and_grad(f, argnums=(0, 1))))
        with _dispatch():
            got, got_g = vg(info_nce_fused)(z, zhat)
        want, want_g = vg(reference.info_nce)(z, zhat)
        return compare_infonce(np.asarray(got), np.asarray(want),
                               [np.asarray(g) for g in got_g],
                               [np.asarray(g) for g in want_g],
                               seconds=time.perf_counter() - t0)


#: Tolerances, and why.  The engine calls the loss at the backend's
#: default matmul precision (bfloat16 passes on a TPU, in the Pallas
#: forward and in the XLA backward alike); the reference runs at
#: "highest".  Cosines of 4,096-long columns then agree to about 1e-3,
#: the loss (a sum of P log-soft-max terms of those) to well under a
#: percent, and the gradients to a few percent of their largest element.
#: A kernel that dropped the 1e-6 inside the log, the norms or a tile of
#: the P x P matrix is off by far more.  Seen on the chip at the cell's
#: shape (PR 22, two seeds): loss 5e-6 and 2e-5, gradients 4.2e-3 and
#: 5.0e-3 of their largest element.
LOSS_RTOL = 1e-3
GRAD_RTOL = 2.5e-2


def compare_infonce(got, want, got_g, want_g, seconds=0.0) -> Dict[str, Any]:
    problems = []
    loss_rel = float(np.max(np.abs(got - want) / np.abs(want)))
    if not loss_rel <= LOSS_RTOL:
        problems.append(f"InfoNCE loss {got!r} vs reference {want!r} "
                        f"(rel {loss_rel:.2e} > {LOSS_RTOL})")
    grad_rel = 0.0
    for g, w in zip(got_g, want_g):
        grad_rel = max(grad_rel,
                       float(np.max(np.abs(g - w)) / np.max(np.abs(w))))
    if not grad_rel <= GRAD_RTOL:
        problems.append(f"InfoNCE gradient off the reference by "
                        f"{grad_rel:.2e} of its largest element "
                        f"(bound {GRAD_RTOL})")
    return {"ok": not problems, "problems": problems, "seconds": seconds,
            "loss_rel": loss_rel, "grad_rel": grad_rel}
