"""Plain reference for the classifier engine: federated rounds written
out client by client, step by step, in float32.

Nothing here comes from ``train/``, ``parallel/``, ``ops/``, ``compress/``
or ``optim/``: only the model's own ``apply`` (``models/``) for the
forward pass.  Every matrix product runs under
``jax.default_matmul_precision("highest")``; without it a TPU multiplies
float32 operands in bfloat16 passes.

One round, as the reference drivers do it (federated_multi.py:160-217,
consensus_multi.py:209-299), for the clients ``k = 0 .. K-1`` in turn:

1. normalise the client's shard with its own (mean, std), run the model
   in training mode (batch statistics of that one minibatch), take the
   mean cross-entropy, and for ADMM add ``y_k . (x_k - z) + rho/2 |x_k - z|^2``
   over the active block's parameters ``x_k``;
2. take the gradient, keep only the active block's part, make one Adam
   step (lr, b1 0.9, b2 0.999, eps 1e-8; the moments persist over the
   rounds of a block);

then exchange: FedAvg sets ``z = mean_k x_k`` and overwrites every
client's block with ``z``; ADMM sets ``z = mean_k (x_k + y_k / rho)``,
then ``y_k += rho (x_k - z)``, and leaves the clients' blocks alone.

A round here is ONE minibatch holding the client's whole shard, so the
result does not depend on the order in which an engine shuffles it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
B1, B2, EPS = 0.9, 0.999, 1e-8


def get_path(tree: Dict[str, Any], path: str):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def set_path(tree: Dict[str, Any], path: str, value) -> Dict[str, Any]:
    """A copy of the nested dict ``tree`` with ``value`` at ``path``."""
    key, _, rest = path.partition("/")
    out = dict(tree)
    out[key] = set_path(tree[key], rest, value) if rest else value
    return out


def block_paths(model, block: Sequence[int]) -> List[str]:
    """Parameter paths of the inclusive index range ``block`` of the
    model's ``param_order()`` (reference simple_utils.py:34-45)."""
    lo, hi = block
    return list(model.param_order()[lo:hi + 1])


class FedRoundReference:
    """``algorithm`` is ``"fedavg"`` or ``"admm"``."""

    def __init__(self, model, paths: Sequence[str], algorithm: str,
                 rho: float, lr: float):
        if algorithm not in ("fedavg", "admm"):
            raise ValueError(f"no reference for algorithm {algorithm!r}")
        self.model, self.paths = model, list(paths)
        self.admm = algorithm == "admm"
        self.rho, self.lr = float(rho), float(lr)
        self._step = jax.jit(self._local_step)
        self._exchange = jax.jit(self.exchange)

    def _loss(self, block, params, batch_stats, x_u8, labels, norm, z, y):
        """Client loss as a function of the active block's leaves only:
        the gradient with respect to ``block`` IS the masked gradient."""
        for path, leaf in zip(self.paths, block):
            params = set_path(params, path, leaf)
        x = (x_u8.astype(jnp.float32) / 255.0 - norm[0]) / norm[1]
        logits, mut = self.model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        if self.admm:
            for leaf, zl, yl in zip(block, z, y):
                d = leaf - zl
                loss = loss + jnp.sum(yl * d) + 0.5 * self.rho * jnp.sum(d * d)
        return loss, mut["batch_stats"]

    def _local_step(self, x, m, v, t, params, batch_stats, x_u8, labels,
                    norm, z, y):
        """One client's minibatch: gradient of the block, then Adam's
        ``t``-th step.  Returns ``(x, m, v, batch_stats, loss)``."""
        (loss, batch_stats), g = jax.value_and_grad(
            self._loss, has_aux=True)(x, params, batch_stats, x_u8, labels,
                                      norm, z, y)
        m = [B1 * mi + (1 - B1) * gi for mi, gi in zip(m, g)]
        v = [B2 * vi + (1 - B2) * gi * gi for vi, gi in zip(v, g)]
        x = [xi - self.lr * (mi / (1 - B1 ** t))
             / (jnp.sqrt(vi / (1 - B2 ** t)) + EPS)
             for xi, mi, vi in zip(x, m, v)]
        return x, m, v, batch_stats, loss

    def run(self, params, batch_stats, shards_x, shards_y, norms,
            rounds: int) -> List[Dict[str, Any]]:
        """``rounds`` rounds from the common start ``params`` /
        ``batch_stats`` (one client's, as nested dicts of float32
        arrays).  ``shards_x`` ``[K, n, 32, 32, 3]`` uint8, ``shards_y``
        ``[K, n]``, ``norms`` ``[K, 2, 3]``.  Returns per round: the
        clients' block leaves after the round ``x`` (``[K]`` lists of
        leaves), the summed loss, and the two residuals as the engine's
        algorithms define them."""
        K = len(shards_x)
        f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
        params, batch_stats = f32(params), f32(batch_stats)
        start = [get_path(params, p) for p in self.paths]
        n_block = sum(int(a.size) for a in start)
        xs = [list(start) for _ in range(K)]
        stats = [batch_stats for _ in range(K)]
        ms = [[jnp.zeros_like(a) for a in start] for _ in range(K)]
        vs = [[jnp.zeros_like(a) for a in start] for _ in range(K)]
        z = [jnp.zeros_like(a) for a in start]
        ys = [[jnp.zeros_like(a) for a in start] for _ in range(K)]
        out = []
        with jax.default_matmul_precision("highest"):
            for t in range(1, rounds + 1):
                loss_sum = 0.0
                for k in range(K):
                    xs[k], ms[k], vs[k], stats[k], loss = self._step(
                        xs[k], ms[k], vs[k], jnp.float32(t), params,
                        stats[k], jnp.asarray(shards_x[k]),
                        jnp.asarray(shards_y[k]), jnp.asarray(norms[k]),
                        z, ys[k])
                    loss_sum += float(loss)
                z_old = z
                xs, z, ys, primal = self._exchange(xs, z, ys)
                dual = _norm([a - b for a, b in zip(z_old, z)]) / n_block
                out.append({"x": [list(x) for x in xs], "loss": loss_sum,
                            "dual_residual": float(dual),
                            "primal_residual": (None if primal is None
                                                else float(primal))})
        return out

    def exchange(self, xs, z, ys):
        """``(xs, z, ys, primal residual)`` after the round's exchange."""
        K, L = len(xs), len(z)
        n_block = sum(int(a.size) for a in z)
        if not self.admm:
            z = [sum(xs[k][i] for k in range(K)) / K for i in range(L)]
            return [list(z) for _ in range(K)], z, ys, None   # write-back
        z = [sum(xs[k][i] + ys[k][i] / self.rho for k in range(K)) / K
             for i in range(L)]
        dy = [[self.rho * (xs[k][i] - z[i]) for i in range(L)]
              for k in range(K)]
        ys = [[ys[k][i] + dy[k][i] for i in range(L)] for k in range(K)]
        return xs, z, ys, sum(_norm(dy[k]) for k in range(K)) / n_block


def _norm(leaves):
    return jnp.sqrt(sum(jnp.sum(a * a) for a in leaves))
