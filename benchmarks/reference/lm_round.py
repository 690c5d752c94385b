"""Plain reference for the ``lm`` engine's federated rounds: local Adam
steps and FedAvg written out client by client, minibatch by minibatch,
sequence by sequence, in float32 (as ``fed_round.py`` does for images).

One round, for the clients ``k = 0 .. K-1`` in turn: for each of the
client's minibatches in the order given, the mean over the minibatch's
sequences of the reference model's loss
(``reference/qwen3_next.py``), its gradient with respect to the active
block's leaves, and one Adam step (lr, b1 0.9, b2 0.999, eps 1e-8; the
moments persist over the rounds of a block).  Then FedAvg: ``z = mean_k
x_k`` overwrites every client's block.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

from benchmarks.reference import qwen3_next as ref

B1, B2, EPS = 0.9, 0.999, 1e-8


@jax.jit
def _adam(x, m, v, g, t, lr):
    m = [B1 * mi + (1 - B1) * gi for mi, gi in zip(m, g)]
    v = [B2 * vi + (1 - B2) * gi * gi for vi, gi in zip(v, g)]
    x = [xi - lr * (mi / (1 - B1 ** t)) / (jnp.sqrt(vi / (1 - B2 ** t)) + EPS)
         for xi, mi, vi in zip(x, m, v)]
    return x, m, v


def minibatch_grad(cfg, params, paths, ids, labels):
    """Mean loss and block gradient over the sequences of one minibatch
    ``ids, labels [B, T]``."""
    loss, grad = 0.0, None
    for i in range(len(ids)):
        l, _, g = ref.loss_and_grad(cfg, params, paths, jnp.asarray(ids[i]),
                                    jnp.asarray(labels[i]))
        loss += float(l) / len(ids)
        g = [gi / len(ids) for gi in g]
        grad = g if grad is None else [a + b for a, b in zip(grad, g)]
    return loss, grad


def run_rounds(cfg: Dict[str, Any], params, paths: Sequence[str], lr: float,
               batches: List[List[Any]]) -> List[Dict[str, Any]]:
    """``batches[r][k]`` is round ``r``'s list of ``(ids [B, T], labels
    [B, T])`` minibatches of client ``k``, in the order the client
    visits them.  From the common start ``params`` returns per round the
    clients' block leaves after the exchange (``x[k]``: list of leaves)
    and the summed loss (over clients and minibatches, as the engine's
    round record has it)."""
    K = len(batches[0])
    start = [ref.get_path(params, p) for p in paths]
    xs = [list(start) for _ in range(K)]
    ms = [[jnp.zeros_like(a) for a in start] for _ in range(K)]
    vs = [[jnp.zeros_like(a) for a in start] for _ in range(K)]
    ts = [0] * K
    out = []
    for rnd in batches:
        loss_sum = 0.0
        for k in range(K):
            for ids, labels in rnd[k]:
                p = params
                for path, leaf in zip(paths, xs[k]):
                    p = ref.set_path(p, path, leaf)
                loss, g = minibatch_grad(cfg, p, paths, ids, labels)
                ts[k] += 1
                xs[k], ms[k], vs[k] = _adam(xs[k], ms[k], vs[k], g,
                                            jnp.float32(ts[k]),
                                            jnp.float32(lr))
                loss_sum += loss
        z = [sum(xs[k][i] for k in range(K)) / K for i in range(len(start))]
        xs = [list(z) for _ in range(K)]
        out.append({"x": [list(x) for x in xs], "loss": loss_sum})
    return out
