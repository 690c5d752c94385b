"""``ops/moe.py:dispatch`` and ``combine`` against the three lines of
``models/decoder.py:held_experts`` they replaced (``x[token]``, the masked
weight, ``zeros.at[token].add``): values and gradients over the pair
count, what they promise about the rows past the count, the same under
``jax.checkpoint`` and the two vmaps, and the scope of every equation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.custom_batching import sequential_vmap

from federated_pytorch_test_tpu.ops import moe

#: 2.5 chunks of rows: the last chunk is moved back and repeats rows
CHUNK = moe.CHUNK
T, H, ROWS = 12, 8, 5 * CHUNK // 2
#: pairs that hit a held expert; above ROWS the rest is dropped
COUNTS = {"none": 0, "one": 1, "chunk-1": CHUNK - 1, "chunk": CHUNK,
          "chunk+1": CHUNK + 1, "two_chunks": 2 * CHUNK, "rows-1": ROWS - 1,
          "rows": ROWS, "dropped": ROWS + 15}
F32 = jnp.float32


def routing(pairs, seed=0):
    """A routing as ``route_local`` leaves it: the filled rows first,
    weight 0 from there on (and one weight that underflowed inside)."""
    rng = np.random.default_rng(seed)
    n = min(pairs, ROWS)
    w = np.where(np.arange(ROWS) < n, rng.uniform(0.1, 1.0, ROWS), 0.0)
    if n > 3:
        w[2] = 0.0
    return moe.Routing(
        token=jnp.asarray(rng.integers(0, T, ROWS), jnp.int32),
        weight=jnp.asarray(w, F32), group_sizes=jnp.asarray([n], jnp.int32),
        pairs_local=jnp.int32(pairs), dropped=jnp.int32(pairs - n),
        load_max_over_mean=F32(1.0))


def operands(seed=1):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(T, H)), F32),
            jnp.asarray(rng.normal(size=(ROWS, H)), F32),
            jnp.asarray(rng.normal(size=(H, H)), F32))


def below(n):
    return (jnp.arange(ROWS) < n)[:, None]


def old_combine(ys, w, token):
    ys = jnp.where(w[:, None] > 0, ys * w[:, None], 0.0)
    return jnp.zeros((T, H), F32).at[token].add(ys)


def old_layer(x, w, r, a):
    """The replaced lines around a stand-in for the experts, which, as
    ``grouped_matmul`` does, leave zeros in the rows of no group."""
    n = jnp.sum(r.group_sizes)
    return old_combine(jnp.where(below(n), jnp.tanh(x[r.token] @ a), 0.0),
                       w, r.token)


def new_layer(x, w, r, a):
    r = r._replace(weight=w)
    xs = moe.dispatch(x, r)
    ys = jnp.where(below(jnp.sum(r.group_sizes)), jnp.tanh(xs @ a), 0.0)
    return moe.combine(ys, r, T)


def loss_of(layer, r, a):
    return lambda x, w: jnp.sum(jnp.sin(layer(x, w, r, a)))


@pytest.mark.parametrize("pairs", COUNTS.values(), ids=COUNTS.keys())
def test_values_and_gradients_are_the_replaced_lines(pairs):
    r, (x, ys, a) = routing(pairs), operands()
    n = min(pairs, ROWS)
    xs = moe.dispatch(x, r)
    assert np.array_equal(xs[:n], x[r.token][:n])
    assert not np.any(np.asarray(xs[n:]))
    np.testing.assert_allclose(moe.combine(ys, r, T),
                               old_combine(ys, r.weight, r.token),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(new_layer(x, r.weight, r, a),
                               old_layer(x, r.weight, r, a),
                               rtol=1e-6, atol=1e-6)
    # with respect to x and the weights through both, and to ys alone
    got = jax.grad(loss_of(new_layer, r, a), (0, 1))(x, r.weight)
    want = jax.grad(loss_of(old_layer, r, a), (0, 1))(x, r.weight)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    dys = jax.grad(lambda y: jnp.sum(jnp.sin(moe.combine(y, r, T))))(
        ys)
    np.testing.assert_allclose(dys, jax.grad(lambda y: jnp.sum(jnp.sin(
        old_combine(y, r.weight, r.token))))(ys), rtol=1e-5, atol=1e-6)
    assert not np.any(np.asarray(dys[n:]))


@pytest.mark.parametrize("pairs", COUNTS.values(), ids=COUNTS.keys())
def test_nan_in_the_unfilled_rows_reaches_nothing(pairs):
    """Neither in ``combine``'s operand nor in ``dispatch``'s cotangent
    (the grouped kernel leaves stale memory there on a TPU)."""
    r, (x, ys, _) = routing(pairs), operands()
    keep = below(min(pairs, ROWS))
    bad = jnp.where(keep, ys, jnp.nan)
    y, pull = jax.vjp(lambda a, w: moe.combine(a, r._replace(weight=w), T),
                      bad, r.weight)
    assert np.array_equal(y, moe.combine(jnp.where(keep, ys, 0.0), r, T))
    dys, dw = pull(jnp.ones((T, H), F32))
    assert np.all(np.isfinite(dys)) and np.all(np.isfinite(dw))
    assert not np.any(np.asarray(dw)[min(pairs, ROWS):])
    _, pull = jax.vjp(lambda a: moe.dispatch(a, r), x)
    (dx,), (want,) = pull(bad), pull(jnp.where(keep, ys, 0.0))
    assert np.all(np.isfinite(dx)) and np.array_equal(dx, want)


def test_under_checkpoint():
    r, (x, _, a) = routing(CHUNK + 1), operands()
    got = jax.jit(jax.grad(jax.checkpoint(loss_of(new_layer, r, a)),
                           (0, 1)))(x, r.weight)
    want = jax.grad(loss_of(old_layer, r, a), (0, 1))(x, r.weight)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "vmap", [jax.vmap, lambda f: jax.vmap(sequential_vmap(f))],
    ids=["vmap", "sequential_vmap"])
def test_clients_with_unequal_counts(vmap):
    """Under a plain ``jax.vmap`` the loops' trip count batches to the
    largest; the engine's clients run under ``sequential_vmap``."""
    counts = (0, CHUNK + 1, ROWS + 15, 1)
    rs = jax.tree.map(lambda *a: jnp.stack(a),
                      *[routing(c, seed=c) for c in counts])
    x, _, a = operands()
    xb = jnp.stack([x * (i + 1) for i in range(len(counts))])

    def value_and_grads(layer):
        one = lambda xi, wi, ri: jax.value_and_grad(
            lambda u, v: jnp.sum(jnp.sin(layer(u, v, ri, a))), (0, 1))(xi, wi)
        return jax.jit(vmap(one))(xb, rs.weight, rs)

    for g, w in zip(jax.tree.leaves(value_and_grads(new_layer)),
                    jax.tree.leaves(value_and_grads(old_layer))):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def equations(jaxpr, outer=""):
    """``(equation, its whole name stack)``: an inner jaxpr's stacks are
    relative to the equation that holds it."""
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub, path)


@pytest.mark.parametrize("what", ["dispatch", "dispatch_vjp", "combine",
                                  "combine_vjp"])
def test_every_equation_lies_under_moe_route(what):
    """Of the backward rules too, without their opening the scope again:
    JAX gives a custom_vjp's rule the name stack of the call it answers,
    as it gives its own transposes, and ``moe_route_busy_pct`` reads the
    scope from the device ops' paths."""
    r, (x, ys, _) = routing(CHUNK + 1), operands()
    fns = {"dispatch": (lambda a: moe.dispatch(a, r), x, ys),
           "combine": (lambda a, w: moe.combine(a, r._replace(weight=w), T),
                       (ys, r.weight), x)}
    inner, primal, ct = fns[what.split("_")[0]]
    primal = primal if isinstance(primal, tuple) else (primal,)

    def f(*a):
        with jax.named_scope("mtp"):
            return inner(*a)

    if what.endswith("_vjp"):
        jaxpr = jax.make_jaxpr(lambda ct, *p: jax.vjp(f, *p)[1](ct))(
            ct, *primal)
    else:
        jaxpr = jax.make_jaxpr(f)(*primal)
    paths = [path for _, path in equations(jaxpr.jaxpr)]
    assert len(paths) > 10
    if what.endswith("_vjp"):
        assert sum("transpose(" in path for path in paths) > 10
    # under the caller's scopes as well (``mtp_busy_pct`` reads ``mtp``)
    assert all("moe_route" in path and "mtp" in path for path in paths), [
        path for path in paths if "moe_route" not in path][:3]
