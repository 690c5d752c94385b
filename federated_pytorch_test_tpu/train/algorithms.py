"""Federated algorithm strategies: FedAvg, FedProx, ADMM consensus, none.

Each strategy supplies (a) the per-client penalty added to the local loss and
(b) the global update run at each communication round.  All functions operate
on the *flat masked block vector* ``x`` (utils/codec.py) so the exchanged and
penalised quantity is exactly the active block, as in the reference.

Inside the engine these run under ``shard_map``: ``x``/``y`` carry a local
client axis ``[K_local, N]``, ``z``/``rho`` are replicated.

Write-back semantics differ per algorithm and are preserved exactly
(SURVEY.md section 7, decision 5):
  * FedAvg overwrites every client with z (federated_multi.py:216-217);
  * FedProx / ADMM never write back — consensus only via the penalty
    (fedprox_multi.py:227 comment is aspirational; consensus_multi.py:291-297).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.parallel.comm import federated_mean, federated_sum
from federated_pytorch_test_tpu.parallel.mesh import CLIENT_AXIS


def _sumsq(d: jnp.ndarray) -> jnp.ndarray:
    """``||d||^2`` as a multiply + sum reduce, not ``jnp.vdot``: XLA:CPU
    accumulates an [N] dot product sequentially in f32, and at the
    largest ResNet18 block (N = 4.7M) that put the penalty — most of the
    reported consensus loss — 0.4% under its float64 value, while the
    TPU's reduction agreed with float64 to 1e-7 (chip_smoke parity,
    PR 21).  The reduce is pairwise on every backend; the gradient (2d)
    is the same expression either way."""
    return jnp.sum(d * d)


def _active_mean(x: jnp.ndarray, w, K: int) -> jnp.ndarray:
    """Mean of x [K_local, N] over the ACTIVE clients.

    ``w`` is the per-client participation weight [K_local] (1 active /
    0 inactive); ``None`` means full participation (reference semantics,
    every client in every round) and reduces to ``federated_mean``.
    Partial participation — the FedProx paper's motivating regime, cited
    but never implemented by the reference (README.md:17,
    fedprox_multi.py:173) — averages over the sampled subset only.
    """
    if w is None:
        return federated_mean(x, K)
    n_act = lax.psum(jnp.sum(w), CLIENT_AXIS)
    # where(n > 0): an all-rejected guard round (train/engine.py update
    # guards) has n_act == 0 — return the zero vector instead of 0/0 NaN;
    # the engine then carries z over.  Unreachable under participation
    # sampling alone (>= 1 client is always kept).  A where-select, not
    # max(n, 1): async staleness weights are fractional, and a round
    # whose only arrivals are downweighted (0 < n_act < 1) must still
    # divide by the true weight sum to stay a convex combination.
    return federated_sum(w[:, None] * x) / jnp.where(n_act > 0, n_act, 1.0)


class Algorithm:
    """Base strategy (also the `no_consensus` strategy: train, never talk)."""

    name = "none"
    needs_dual = False   # per-client y state
    writeback = False    # overwrite client params with z after the round
    communicates = False

    def penalty(self, x: jnp.ndarray, z: jnp.ndarray, y: jnp.ndarray,
                rho: jnp.ndarray) -> jnp.ndarray:
        """Extra per-client local-loss term; x is the client's flat block."""
        return jnp.float32(0.0)

    def global_update(self, x, z, y, rho, K: int, w=None, mean_fn=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
        """(z_new, y_new, diagnostics) from local stacks x,y [K_local, N].

        ``w`` [K_local]: participation weights for this round (1 active,
        0 inactive); ``None`` = every client (reference parity).
        ``mean_fn``: optional ``(stack, w) -> aggregate`` replacing the
        plain active mean — the robust-aggregation hook
        (parallel/comm.py ``make_robust_mean``); ``None`` keeps the
        literal psum-mean path."""
        return z, y, {}

    @staticmethod
    def _agg(stack, w, K, mean_fn):
        """The one chokepoint every strategy averages through."""
        with scope("exchange_reduce"):
            if mean_fn is None:
                return _active_mean(stack, w, K)
            return mean_fn(stack, w)


class NoConsensus(Algorithm):
    """K independent models, no exchange ever (no_consensus_multi.py)."""


class FedAvg(Algorithm):
    """Blockwise federated averaging (federated_multi.py:203-217)."""

    name = "fedavg"
    writeback = True
    communicates = True

    def global_update(self, x, z, y, rho, K, w=None, mean_fn=None):
        znew = self._agg(x, w, K, mean_fn)                # z = sum x_k / K
        dual = jnp.linalg.norm(z - znew) / x.shape[-1]    # ||z-znew|| / N
        return znew, y, {"dual_residual": dual}


class FedProx(Algorithm):
    """Proximal local objective, averaging only (fedprox_multi.py).

    Local loss += (rho/2)||x - z||^2 (fedprox_multi.py:187-192); z is the
    running average but is NEVER sent back to clients.
    """

    name = "fedprox"
    communicates = True

    def penalty(self, x, z, y, rho):
        d = x - z
        return 0.5 * rho * _sumsq(d)

    def global_update(self, x, z, y, rho, K, w=None, mean_fn=None):
        znew = self._agg(x, w, K, mean_fn)
        n = x.shape[-1]
        dual = jnp.linalg.norm(z - znew) / n
        # primal = sum_k ||rho (x_k - znew)|| / N  (fedprox_multi.py:228-232)
        # — over the round's participants only under partial participation
        per = jax.vmap(lambda xa: jnp.linalg.norm(rho * (xa - znew)))(x)
        if w is not None:
            per = per * w
        primal = lax.psum(jnp.sum(per), CLIENT_AXIS) / n
        return znew, y, {"primal_residual": primal, "dual_residual": dual}


class AdmmConsensus(Algorithm):
    """Scaled-ADMM consensus with optional Barzilai-Borwein adaptive rho
    (consensus_multi.py:209-299).

    Local loss += y^T (x-z) + (rho/2)||x-z||^2; global
    z = sum_k (y_k + rho x_k) / (K rho); dual update y_k += rho (x_k - z).
    """

    name = "consensus"
    needs_dual = True
    communicates = True

    def penalty(self, x, z, y, rho):
        d = x - z
        return jnp.sum(y * d) + 0.5 * rho * _sumsq(d)

    def global_update(self, x, z, y, rho, K, w=None, mean_fn=None):
        # consensus_multi.py:281-285; under partial participation the
        # average and the dual updates below run over the round's
        # participants only — inactive y_k stay untouched until sampled
        znew = self._agg(y + rho * x, w, K, mean_fn) / rho
        n = x.shape[-1]
        dual = jnp.linalg.norm(z - znew) / n               # :287 (before y update)
        ydelta = rho * (x - znew)                          # :294
        if w is not None:
            ydelta = w[:, None] * ydelta
        local = jnp.sum(jax.vmap(jnp.linalg.norm)(ydelta))
        primal = lax.psum(local, CLIENT_AXIS) / n          # :292-297
        return znew, y + ydelta, {"primal_residual": primal, "dual_residual": dual}


@dataclasses.dataclass(frozen=True)
class BBConfig:
    period_T: int = 2
    alphacorrmin: float = 0.2
    epsilon: float = 1e-3
    rhomax: float = 0.1


def bb_rho_update(x, z, y, rho, x0, yhat0, bb: BBConfig, mesh_axis_size: int):
    """Barzilai-Borwein spectral rho update (consensus_multi.py:242-278).

    Per client: yhat = y + rho(x - z); Δy = yhat - yhat0; Δx = x - x0;
    d11 = Δy.Δy, d12 = Δy.Δx, d22 = Δx.Δx; α = d12/sqrt(d11 d22),
    α_SD = d11/d22, α_MG = d12/d22; α̂ = α_MG if 2α_MG > α_SD else α_SD - α_MG/2;
    accept iff α >= alphacorrmin and α̂ < rhomax (catches negative d12).

    DOCUMENTED DEVIATION: the reference overwrites the single scalar
    ``rho[ci,0]`` inside its sequential client loop, so later clients see
    rho values already modified by earlier ones and the final value is the
    last client's decision (consensus_multi.py:248-273).  Here every client
    evaluates with the round-incoming rho in parallel and the globally-last
    client's (k = K-1) decision is adopted — identical to the sequential
    semantics when no update fires, or when ONLY the last client fires (the
    common cases; bb_update defaults to False in the reference,
    consensus_multi.py:41).  When earlier clients fire, the schemes diverge
    two ways: the last client's accepted candidate is computed from the
    round-incoming rho rather than the partially-updated one, and an
    earlier client's lone accepted update is dropped when the last client
    rejects (the sequential loop would keep it).
    tests/test_bb_boundary.py characterizes each case against a numpy
    port of the reference loop.

    Returns (rho_new, x0_new, yhat0_new).
    """
    def per_client(xa, ya, x0a, yhat0a):
        yhat = ya + rho * (xa - z)
        dy = yhat - yhat0a
        dx = xa - x0a
        d11 = jnp.vdot(dy, dy)
        d12 = jnp.vdot(dy, dx)
        d22 = jnp.vdot(dx, dx)
        ok_den = (jnp.abs(d12) > bb.epsilon) & (d11 > bb.epsilon) & (d22 > bb.epsilon)
        alpha = d12 / jnp.sqrt(d11 * d22 + 1e-30)
        alpha_sd = d11 / (d22 + 1e-30)
        alpha_mg = d12 / (d22 + 1e-30)
        alphahat = jnp.where(2.0 * alpha_mg > alpha_sd, alpha_mg,
                             alpha_sd - 0.5 * alpha_mg)
        accept = ok_den & (alpha >= bb.alphacorrmin) & (alphahat < bb.rhomax)
        return jnp.where(accept, alphahat, rho), yhat

    cand, yhat = jax.vmap(per_client)(x, y, x0, yhat0)
    # adopt the globally-last client's candidate: last local row of last device
    is_last_dev = lax.axis_index(CLIENT_AXIS) == mesh_axis_size - 1
    rho_new = lax.psum(jnp.where(is_last_dev, cand[-1], 0.0), CLIENT_AXIS)
    return rho_new, x, yhat
