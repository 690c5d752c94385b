"""``ops/head_loss.py``: the head's cross-entropy with the gradient of its
inputs taken in the forward pass, against autodiff of the plain
``sequence_loss(head(x))`` under ``jax.checkpoint`` (what the decoders ran
before it), over a map of sequences with a different cotangent each: the
loss, ``dx`` and ``dw`` in both layouts of the head's matrix, with and
without a token weight, for every choice of what is being trained, at
float32 ``highest`` and in bfloat16; bit for bit where the sequences'
scale is a power of two (the training loss's ``1 / B``); and in a
decoder's lowered step, the head's products with the vocabulary: none
rematerialised, and ``dw``'s only where the matrix is trained.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.models import get_model
from federated_pytorch_test_tpu.models.decoder import sequence_loss
from federated_pytorch_test_tpu.ops.head_loss import head_loss, logits

B, T, H, V = 2, 12, 16, 40
#: what is being trained: (xn's upstream, the head's matrix)
TRAINED = {"neither": (False, False), "xn": (True, False),
           "w": (False, True), "both": (True, True)}


@pytest.fixture(scope="module")
def data():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (B, T, H))
    w = 0.3 * jax.random.normal(k[1], (H, V))
    labels = jax.random.randint(k[2], (B, T), 0, V)
    weight = (jnp.arange(T) < T - 1).astype(jnp.float32) / (T - 1)
    return x, w, labels, weight


def losses(fused, x, w, labels, weight, contract, dtype):
    """Loss per sequence ``[B]`` through the op or the plain path."""
    if fused:
        one = lambda a: head_loss(a[0], w, a[1], weight, contract=contract,
                                  dtype=dtype)
    else:
        def one(a):
            z = logits(a[0], w, contract=contract, dtype=dtype)
            if weight is None:
                return sequence_loss(z, a[1])
            lse = jax.nn.logsumexp(z, axis=-1)
            picked = jnp.take_along_axis(z, a[1][:, None], -1)[:, 0]
            return jnp.sum((lse - picked) * weight)
        one = jax.checkpoint(one)
    return jax.lax.map(one, (x, labels))


def value_and_grads(fused, data, contract, weighted, trained, dtype, g):
    """``(loss, d/ds, dx, dw)`` of ``s * sum(g * losses)``: ``s`` stands
    for a leaf upstream of nothing, so "neither" still has a gradient."""
    x, w, labels, weight = data
    w = w if contract == 0 else w.T
    weight = weight if weighted else None
    on = TRAINED[trained]
    args = (1.0, x, w)
    argnums = (0,) + tuple(i + 1 for i in (0, 1) if on[i])

    def f(s, x, w):
        return s * jnp.sum(g * losses(fused, x, w, labels, weight, contract,
                                      dtype))

    val, grads = jax.jit(jax.value_and_grad(f, argnums))(*args)
    grads = dict(zip(argnums, grads))
    return val, grads[0], grads.get(1), grads.get(2)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("trained", sorted(TRAINED))
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["mean", "token_weight"])
@pytest.mark.parametrize("contract", [0, 1], ids=["HV", "VH"])
def test_loss_and_gradients_are_autodiff_s(data, contract, weighted,
                                           trained, dtype):
    """Against autodiff of the plain path, each sequence with its own
    cotangent: float32 at ``highest`` to float32's rounding; bfloat16
    operands to a unit of bfloat16's last place (the op rounds ``dx`` and
    ``dw`` to the operand's precision before the sequence's scale,
    autodiff after it)."""
    g = jnp.array([0.3, 1.7])
    dt = jnp.dtype(dtype)
    with jax.default_matmul_precision("highest"):
        got = value_and_grads(True, data, contract, weighted, trained, dt, g)
        want = value_and_grads(False, data, contract, weighted, trained, dt,
                               g)
    tol = 1e-5 if dtype == "float32" else 1e-2
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-6)
    for a, b, on in zip(got[2:], want[2:], TRAINED[trained]):
        assert (a is None) == (b is None) == (not on)
        if on:
            assert a.shape == b.shape and a.dtype == jnp.float32
            assert rel(a, b) < tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("contract", [0, 1], ids=["HV", "VH"])
def test_bit_for_bit_at_a_power_of_two_scale(data, contract, dtype):
    """The training loss's cotangent is ``1 / B`` a sequence: a power of
    two commutes with every rounding, so the op's loss and both
    gradients are autodiff's to the last bit (``p`` is formed in
    autodiff's own order, the products are JAX's own transposes)."""
    g = jnp.full((B,), 1.0 / B)
    dt = jnp.dtype(dtype)
    got = value_and_grads(True, data, contract, False, "both", dt, g)
    want = value_and_grads(False, data, contract, False, "both", dt, g)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_primal_is_the_plain_loss(data):
    x, w, labels, _ = data
    for dt in (jnp.float32, jnp.bfloat16):
        got = losses(True, x, w, labels, None, 0, dt)
        want = jax.vmap(lambda a, y: sequence_loss(
            logits(a, w, contract=0, dtype=dt), y))(x, labels)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------------
# in a decoder's step: the head's products with the vocabulary
# ----------------------------------------------------------------------
#: a vocabulary no other width of the tiny ZAYA1 shares
VOCAB = 72


def vocab_products(f, *args):
    """The op names of the lowered program's products that have a
    ``VOCAB``-wide dimension."""
    txt = jax.jit(f).lower(*args).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', txt, re.M))
    out = []
    for line in txt.splitlines():
        if "stablehlo.dot_general" in line and re.search(
                rf"[<x]{VOCAB}x", line):
            loc = re.search(r"loc\((#loc\d+)\)\s*$", line)
            out.append(names.get(loc.group(1), "") if loc else "")
    return out


@pytest.mark.parametrize("block", ["embedding", "expert_block"])
def test_no_head_product_is_rematerialised(block):
    """A step of the tied-embedding decoder: with the matrix frozen (an
    expert block trains) the head's products with the vocabulary are
    exactly two, the forward and ``dx``; with the matrix trained (the
    embedding's block) three, ``dw`` besides; none under
    ``rematted_computation``."""
    model = get_model("zaya", hidden_size=32, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=8,
                      moe_intermediate_size=24, num_experts=8,
                      num_experts_per_tok=1, router_hidden_size=16,
                      layers=2, experts_held=4, vocab_rows=VOCAB,
                      attn_block=16, dtype=jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, VOCAB)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init_variables(jax.random.PRNGKey(0), x)[0]
    name = "embed" if block == "embedding" else "layer0_moe"

    def step(leaves, x, y):
        p = {**params, name: {**params[name], **leaves}}
        return jnp.mean(model.apply({"params": p}, x, y)[0])

    leaves = ({"embedding": params["embed"]["embedding"]}
              if block == "embedding" else
              {"experts_up": params["layer0_moe"]["experts_up"]})
    found = vocab_products(jax.value_and_grad(step), leaves, x, y)
    assert len(found) == (3 if block == "embedding" else 2), found
    assert all("head_product" in n for n in found), found
    assert not any("rematted_computation" in n for n in found), found
