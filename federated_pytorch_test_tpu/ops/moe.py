"""Expert layer of a chip that holds a share of the experts.

The chip routes over ALL ``n_experts`` (the router keeps its published
width; :func:`router_weights` is the softmax rule,
:func:`sigmoid_router_weights` the sigmoid rule with a selection bias,
:func:`softmax_bias_router_weights` the softmax rule with a balancing
bias and weights that are not renormalised),
keeps each token's top-k weights as they are, and computes only the terms
of the experts ``[first, first + held)`` it holds.  What the
absent experts would add is left out; on one chip there is no exchange.

:func:`route_local` sorts the token-expert pairs that hit a held expert
by expert into a buffer of ``rows`` rows; :func:`grouped_matmul` is the
matrix product of each expert's rows with that expert's weights
(``jax.lax.ragged_dot``, which the TPU compiler lowers to a grouped
Mosaic kernel whose work follows the group sizes).  A pair that finds no
row is counted in ``dropped`` and never silently lost: the caller sizes
``rows`` for its traffic and the benchmark's ``correct`` fails on a
non-zero count.

:func:`dispatch` copies the tokens' rows into that buffer and
:func:`combine` adds the experts' weighted rows back into the tokens.
The buffer is sized for the worst traffic and is mostly empty (the sort
puts the ``n = min(pairs_local, rows)`` filled rows first), so both, and
both transposes, are loops over chunks of ``CHUNK`` rows whose trip
count is ``ceil(n / CHUNK)``: data, read on the device, because the
pair count is known only once the router has run.  Nothing
differentiates through a loop (each function is one ``jax.custom_vjp``
whose rule is such a loop; its ops carry the call's scopes in their
paths, ``moe_route`` > ``pair_dispatch`` / ``pair_combine``, as JAX's
own transposes do, and the zeros a loop starts from ``pair_fill``
below that: ``obs/scopes.py``).  What they promise about
the rows from ``n`` on: ``dispatch`` and the cotangent of ``combine``
hold exact zeros there, and nothing reads them in ``combine``'s operand
or in ``dispatch``'s cotangent (a NaN there reaches no output).  A full
buffer runs every chunk and costs what the whole-buffer gather and
scatter cost, plus the loop.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import sequential_vmap

from federated_pytorch_test_tpu.obs.scopes import scope


class Routing(NamedTuple):
    """``rows`` sorted pair slots of one token batch ``[T, H]``."""

    token: jnp.ndarray        # [rows] int32: the pair's token
    weight: jnp.ndarray       # [rows] f32: its routing weight (0: empty row)
    group_sizes: jnp.ndarray  # [held] int32: rows of each held expert
    pairs_local: jnp.ndarray  # () int32: pairs that hit a held expert
    dropped: jnp.ndarray      # () int32: of those, pairs with no row
    load_max_over_mean: jnp.ndarray   # () f32 over the held experts


def router_weights(logits: jnp.ndarray, top_k: int, renormalise: bool):
    """``(weights [T, k] f32, experts [T, k] int32)``: softmax over all
    experts in float32, top-k, weights renormalised to sum 1."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, e = lax.top_k(probs, top_k)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, e.astype(jnp.int32)


def sigmoid_router_weights(logits: jnp.ndarray, bias: jnp.ndarray, top_k: int,
                           renormalise: bool, scale: float):
    """``(weights [T, k] f32, experts [T, k] int32)`` of the
    auxiliary-loss-free rule (``topk_method noaux_tc`` with one group,
    arXiv:2412.19437 section 2.1.2): sigmoid scores over all experts in
    float32; the top-k are chosen by score + ``bias [n_experts]``, their
    weights are the scores WITHOUT the bias, renormalised to sum 1, times
    ``scale``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, e = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(scores, e, axis=-1)
    if renormalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, e.astype(jnp.int32)


def softmax_bias_router_weights(logits: jnp.ndarray, bias: jnp.ndarray,
                                top_k: int):
    """``(weights [T, k] f32, experts [T, k] int32)``: softmax over all
    experts in float32; the top-k are chosen by probability + ``bias
    [n_experts]`` (a balancing buffer that no gradient reaches), their
    weights are the probabilities WITHOUT the bias and NOT renormalised:
    at ``k = 1`` a renormalised weight is the constant 1 and the router
    would get no gradient."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, e = lax.top_k(probs + lax.stop_gradient(bias), top_k)
    return jnp.take_along_axis(probs, e, axis=-1), e.astype(jnp.int32)


def route_local(weights, experts, first: int, held: int, rows: int) -> Routing:
    """Sort the pairs of ``experts [T, k]`` that hit ``[first, first +
    held)`` by expert into ``rows`` slots (stable: by token within an
    expert)."""
    T, k = experts.shape
    flat = experts.reshape(-1) - first
    here = (flat >= 0) & (flat < held)
    local = jnp.where(here, flat, held)                # held: "not here"
    order = jnp.argsort(local, stable=True)[:rows]
    counts = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    pairs = jnp.sum(counts)
    # rows that exist: the first ``rows`` of the sorted local pairs
    ends = jnp.minimum(jnp.cumsum(counts), order.shape[0])
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    filled = jnp.arange(order.shape[0]) < pairs
    w = jnp.where(filled, weights.reshape(-1)[order], 0.0)
    mean = jnp.maximum(pairs.astype(jnp.float32) / held, 1e-9)
    return Routing(
        token=(order // k).astype(jnp.int32), weight=w,
        group_sizes=group_sizes, pairs_local=pairs.astype(jnp.int32),
        dropped=jnp.maximum(pairs - order.shape[0], 0).astype(jnp.int32),
        load_max_over_mean=jnp.max(counts).astype(jnp.float32) / mean)


def operand(x, dtype):
    """``x`` as an operand of a product in ``dtype``.  A one-byte float
    ``dtype`` (float8) rounds to it and multiplies in bfloat16, since
    the v5e has no float8 unit: the benchmark's precision probe, which
    has to come out as not correct (``benchmarks/engines/lm.py``)."""
    if jnp.dtype(dtype).itemsize == 1:
        return x.astype(dtype).astype(jnp.bfloat16)
    return x.astype(dtype)


def _grouped_rows(rows: int, group_sizes):
    """``[rows, 1]`` mask of the rows that belong to a group."""
    return (jnp.arange(rows) < jnp.sum(group_sizes))[:, None]


def _ragged(x, w, group_sizes):
    # the TPU's grouped kernel writes the rows of its groups and no
    # other: rows past the last group hold whatever the buffer held
    # (seen on the v5e: a gradient 1e9 times too large), so they are
    # zeroed here, as the CPU's lowering leaves them
    with scope("expert_products"):
        y = lax.ragged_dot(x, w, group_sizes,
                           preferred_element_type=jnp.float32)
    with scope("expert_mask"):
        return jnp.where(_grouped_rows(x.shape[0], group_sizes), y, 0.0)


def _ragged_vjp(x, w, group_sizes, dy):
    """``(dx [rows, k], dw [groups, k, n])`` in float32: ``dy`` against
    each row's expert, and each expert's rows against their ``dy``."""
    with scope("expert_products"):
        wt = jnp.swapaxes(w, 1, 2)
    dx = _ragged(dy, wt, group_sizes)
    with scope("expert_products"):
        dw = lax.ragged_dot_general(
            x, dy, group_sizes,
            lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
            preferred_element_type=jnp.float32)
    return dx, dw


# The TPU compiler takes a ragged product without batch dimensions only,
# and the engine vmaps its clients: under vmap each product runs client
# after client (a loop of K), which is what K clients' experts are.
_ragged_seq = sequential_vmap(_ragged)
_ragged_vjp_seq = sequential_vmap(_ragged_vjp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(x, w, group_sizes, dtype=jnp.bfloat16):
    """``y[r] = x[r] @ w[g(r)]`` for the rows ``r`` of group ``g`` (rows
    ``[sum(group_sizes[:g]), sum(group_sizes[:g + 1]))``); rows past the
    last group read 0.  ``x [rows, k]`` and ``w [groups, k, n]`` are
    float32 and multiplied in ``dtype``; the result and both cotangents
    are float32 (an expert's weight gradient is summed in float32)."""
    return _gm_fwd(x, w, group_sizes, dtype)[0]


def _gm_fwd(x, w, group_sizes, dtype):
    with scope("expert_cast"):
        xc, wc = operand(x, dtype), operand(w, dtype)
    return _ragged_seq(xc, wc, group_sizes), (xc, w, group_sizes)


def _gm_bwd(dtype, res, dy):
    xc, w, group_sizes = res
    with scope("expert_cast"):
        wc, dyc = operand(w, dtype), operand(dy, dtype)
    dx, dw = _ragged_vjp_seq(xc, wc, group_sizes, dyc)
    return dx, dw, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


#: rows of the pair buffer that one iteration of a loop below moves
CHUNK = 512


def filled_rows(routing: Routing):
    """``n`` () int32: the buffer's rows below ``n`` hold a pair."""
    return jnp.minimum(routing.pairs_local, routing.token.shape[0])


def _over_chunks(token, n, step, init):
    """``init`` through ``step(carry, lo, token[lo:lo + c], fresh, filled)``
    for the ``ceil(n / c)`` chunks of ``c = min(CHUNK, rows)`` rows that
    hold a filled row.  The last chunk is moved back to end with the
    buffer, so it may repeat rows of the one before: ``filled [c]`` marks
    the rows below ``n`` (repeated ones among them: writing a row twice
    is harmless), ``fresh [c]`` those of them no earlier chunk held
    (adding a row twice is not)."""
    rows = token.shape[0]
    c = min(CHUNK, rows)

    def body(i, carry):
        lo = jnp.minimum(i * c, rows - c)
        at = lo + jnp.arange(c)
        filled = at < n
        return step(carry, lo, lax.dynamic_slice(token, (lo,), (c,)),
                    filled & (at >= i * c), filled)

    return lax.fori_loop(0, (n + c - 1) // c, body, init)


def _fill(shape, dtype):
    """The zeros a loop starts from, under a scope of their own."""
    with scope("pair_fill"):
        return jnp.zeros(shape, dtype)


def _rows_at(a, lo, c):
    return lax.dynamic_slice(a, (lo, 0), (c, a.shape[1]))


def _masked(keep, a):
    return jnp.where(keep[:, None], a, 0.0)


def _weights_at(weight, lo, keep):
    """``weight[lo:lo + c]``, 0 outside ``keep [c]``."""
    return jnp.where(keep, lax.dynamic_slice(weight, (lo,), keep.shape), 0.0)


def _weighted(w, ys):
    # the routing weight of a filled row is positive; one that
    # underflowed to 0 takes its row out, as an empty row's does
    return jnp.where(w[:, None] > 0, ys * w[:, None], 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(T, x, token, n):
    def step(xs, lo, t, fresh, filled):
        return lax.dynamic_update_slice(xs, _masked(filled, x[t]), (lo, 0))

    return _over_chunks(token, n, step,
                        _fill((token.shape[0], x.shape[1]), x.dtype))


def _dispatch_fwd(T, x, token, n):
    return _dispatch(T, x, token, n), (token, n)


def _dispatch_bwd(T, res, dxs):
    token, n = res

    def step(dx, lo, t, fresh, filled):
        return dx.at[t].add(_masked(fresh, _rows_at(dxs, lo, t.shape[0])))

    dx = _over_chunks(token, n, step, _fill((T, dxs.shape[1]), dxs.dtype))
    return dx, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(T, ys, weight, token, n):
    def step(y, lo, t, fresh, filled):
        return y.at[t].add(_weighted(_weights_at(weight, lo, fresh),
                                     _rows_at(ys, lo, t.shape[0])))

    return _over_chunks(token, n, step, _fill((T, ys.shape[1]), ys.dtype))


def _combine_fwd(T, ys, weight, token, n):
    return _combine(T, ys, weight, token, n), (ys, weight, token, n)


def _combine_bwd(T, res, dy):
    ys, weight, token, n = res

    def step(carry, lo, t, fresh, filled):
        dys, dw = carry
        w, g = _weights_at(weight, lo, filled), dy[t]
        at_w = jnp.sum(_masked(w > 0, _rows_at(ys, lo, t.shape[0])) * g, -1)
        return (lax.dynamic_update_slice(dys, _weighted(w, g), (lo, 0)),
                lax.dynamic_update_slice(dw, at_w, (lo,)))

    with scope("pair_fill"):
        init = jnp.zeros_like(ys), jnp.zeros_like(weight)
    dys, dw = _over_chunks(token, n, step, init)
    return dys, dw, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch(x, routing: Routing):
    """``xs [rows, H]``: ``x[token[i]]`` in the filled rows of the pair
    buffer, exact zeros from :func:`filled_rows` on.  Its transpose adds
    the filled rows' cotangents into their tokens and reads no other
    row."""
    with scope("moe_route"):
        n = filled_rows(routing)
        with scope("pair_dispatch"):
            return _dispatch(x.shape[0], x, routing.token, n)


def combine(ys, routing: Routing, T: int):
    """``y [T, H]``: row ``t`` is the float32 sum of ``weight[i] *
    ys[i]`` over the filled rows ``i`` of token ``t``; no row from
    :func:`filled_rows` on is read.  Its transpose gives ``weight[i] *
    dy[token[i]]`` in the filled rows of ``ys``' cotangent and exact
    zeros in the rest, and ``sum(ys[i] * dy[token[i]])`` to the weights."""
    with scope("moe_route"):
        n = filled_rows(routing)
        with scope("pair_combine"):
            return _combine(T, ys, routing.weight, routing.token, n)
