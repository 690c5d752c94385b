"""``lm_round.py``'s federated rounds (local Adam steps, then FedAvg,
client by client, minibatch by minibatch, sequence by sequence, in
float32) for any decoder's plain reference: the reference module is an
argument.  It offers ``loss_and_grad(cfg, params, paths, ids, labels) ->
(loss, anything, gradients)`` of one sequence and ``get_path`` /
``set_path``, as ``reference/qwen3_next.py`` and
``reference/glm4_moe_lite.py`` do.  The Adam step is ``lm_round.py``'s
own.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax.numpy as jnp

from benchmarks.reference.lm_round import _adam


def minibatch_grad(ref, cfg, params, paths, ids, labels):
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


def run_rounds(ref, cfg: Dict[str, Any], params, paths: Sequence[str],
               lr: float, batches: List[List[Any]]) -> List[Dict[str, Any]]:
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
                loss, g = minibatch_grad(ref, cfg, p, paths, ids, labels)
                ts[k] += 1
                xs[k], ms[k], vs[k] = _adam(xs[k], ms[k], vs[k], g,
                                            jnp.float32(ts[k]),
                                            jnp.float32(lr))
                loss_sum += loss
        z = [sum(xs[k][i] for k in range(K)) / K for i in range(len(start))]
        xs = [list(z) for _ in range(K)]
        out.append({"x": [list(x) for x in xs], "loss": loss_sum})
    return out
