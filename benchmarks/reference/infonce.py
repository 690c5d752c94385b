"""Plain reference for the CPC loss: InfoNCE over patch positions, in
float32 at "highest" matmul precision, with no kernel.

As the reference computes it (federated_cpc.py:149-180): for latent
grids ``z`` and predictions ``zhat`` of shape ``[B, px, py, R]``, column
``p`` of the matrix ``Z`` stacks the ``B x R`` values at patch position
``p``; ``zz[i, j]`` is the cosine of column ``i`` of ``Z`` and column
``j`` of ``Zhat``; each row is soft-maxed and the loss is
``-sum_i log(softmax(zz[i])[i] + 1e-6)``.  A zero column is given norm 1
(the reference divides 0 by 0 there; every path of the program guards it
the same way).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def info_nce(z: jnp.ndarray, zhat: jnp.ndarray) -> jnp.ndarray:
    B, px, py, R = z.shape
    with jax.default_matmul_precision("highest"):
        Z = jnp.transpose(z, (0, 3, 1, 2)).reshape(B * R, px * py)
        Zh = jnp.transpose(zhat, (0, 3, 1, 2)).reshape(B * R, px * py)
        Z, Zh = Z.astype(jnp.float32), Zh.astype(jnp.float32)

        def norms(M):
            sq = jnp.sum(M * M, axis=0)
            return jnp.sqrt(jnp.where(sq == 0.0, 1.0, sq))

        zz = (Z.T @ Zh) / (norms(Z)[:, None] * norms(Zh)[None, :])
        p = jax.nn.softmax(zz, axis=1)
        return -jnp.sum(jnp.log(jnp.diagonal(p) + 1e-6))
