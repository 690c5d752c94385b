"""A decoder head's cross-entropy, with the gradient of its inputs
taken in the forward pass.

``head_loss(xn, w, labels)`` is ``sequence_loss(xn W, labels)`` for one
sequence: the normed last hidden states ``xn [T, H]`` through the head's
matrix ``w`` (``[H, V]``, or ``[V, H]`` contracted as it lies, a tied
embedding's), the logits' log-sum-exp less the label's logit, the mean
over ``T`` (or the sum weighted by ``token_weight [T]``).  The products
take operands in ``dtype`` with float32 sums, as ``models/decoder.py:_mm``
does; the softmax and the loss are float32.

The loss is the last operation of a step, so its cotangent is one scale
``g`` a sequence, and the forward already holds what the gradient needs:
the logits, their log-sum-exp and the labels.  So under one
``jax.custom_vjp`` the forward rule forms ``p = (softmax - onehot) * tw``
(``tw`` the token weight, ``1 / T`` where none is given) from the logits
it made and multiplies it back at once, ``dx = p w^T`` and ``dw = p^T
xn``, each product and each cast exactly what JAX's own transpose of the
head's product does (``jax.linear_transpose`` of it: ``p`` enters the
product in float32 and the result is rounded to the operand's ``dtype``
on its way back, so the residuals are kept in ``dtype``); the backward
rule is ``g * dx`` and ``g * dw``, nothing else.  Where the step wraps
the head in ``jax.checkpoint`` so as not to keep ``[T, V]`` float32
logits, the backward ran the head's product a second time only to form
``p``: that product is gone, and nothing of ``[T, V]`` is kept.

``symbolic_zeros`` carries the schedule: ``dw``'s product is traced only
where the matrix is perturbed (the block being trained holds it) and
``dx``'s only where something upstream is; a step that trains neither
runs the primal alone.  The primal, with no gradient asked for, is the
plain product and ``lse - picked``.  The logits of a whole sequence are
the largest transient, as they were under the checkpoint.

:data:`IMPL` is what the round field ``head_impl`` says; the plain
``sequence_loss(head(x))`` under ``jax.checkpoint`` is the tests'
reference (``tests/test_head_loss.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from federated_pytorch_test_tpu.obs.scopes import scope
from federated_pytorch_test_tpu.ops.moe import operand as _op

__all__ = ["IMPL", "head_loss", "logits"]

_F32 = jnp.float32

#: the round field ``head_impl``: the decoders' losses go through
#: :func:`head_loss` ("plain": ``sequence_loss(head(x))``, rematerialised)
IMPL = "fused"


def _product(xo, wo, contract: int):
    return jax.lax.dot_general(
        xo, wo, (((xo.ndim - 1,), (contract,)), ((), ())),
        preferred_element_type=_F32)


def logits(xn, w, *, contract: int, dtype):
    """``xn [..., H]`` through ``w`` (its width on axis ``contract``)
    ``-> [..., V]`` float32: the head's product alone."""
    with scope("head_product"):
        return _product(_op(xn, dtype), _op(w, dtype), contract)


def _transpose(f, like, ct):
    return jax.linear_transpose(f, like)(ct)[0]


def _loss(z, labels, tw):
    """``-> (loss, exp(z - m), their sum)``: ``jax.nn.logsumexp`` written
    out, so that the forward rule keeps what the gradient is made of."""
    m = jnp.max(z, axis=-1, keepdims=True)
    m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0.0))
    e = jnp.exp(z - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    lse = (jnp.log(s) + m)[..., 0]
    picked = jnp.take_along_axis(z, labels[..., None], -1)[..., 0]
    if tw is None:
        return jnp.mean(lse - picked, axis=-1), e, s
    return jnp.sum((lse - picked) * tw, axis=-1), e, s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _head_loss(contract, dtype, xn, w, labels, tw):
    z = logits(xn, w, contract=contract, dtype=dtype)
    with scope("head_softmax"):
        return _loss(z, labels, tw)[0]


def _head_loss_fwd(contract, dtype, xn, w, labels, tw):
    dx_on, dw_on = xn.perturbed, w.perturbed
    xn, w, labels = xn.value, w.value, labels.value
    tw = None if tw is None else tw.value
    xo, wo = _op(xn, dtype), _op(w, dtype)
    with scope("head_product"):
        z = _product(xo, wo, contract)
    with scope("head_softmax"):
        loss, e, s = _loss(z, labels, tw)
        if not (dx_on or dw_on):
            return loss, (None, None)
        # the order of autodiff's own: (scale / sum) * exp, less the
        # scale at the label
        scale = (jnp.full(labels.shape, 1.0 / labels.shape[-1], _F32)
                 if tw is None else tw)[..., None]
        p = scale / s * e
        hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
            == labels[..., None]
        p = jnp.where(hit, p - scale, p)
    with scope("head_product"):
        dx = _transpose(lambda a: _product(a, wo, contract), xo, p) \
            if dx_on else None
        dw = _transpose(lambda b: _product(xo, b, contract), wo, p) \
            if dw_on else None
    return loss, (dx, dw)


def _head_loss_bwd(contract, dtype, res, g):
    if isinstance(g, SymbolicZero):
        return None, None, None, None

    def back(r):
        # the transpose of operand(., dtype) from float32: the cast
        # autodiff gives the operand's cotangent, then the sequence's scale
        if r is None:
            return None
        up = _transpose(lambda a: _op(a, dtype),
                        jax.ShapeDtypeStruct(r.shape, _F32), r)
        return g * up

    with scope("head_softmax"):
        return back(res[0]), back(res[1]), None, None


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd, symbolic_zeros=True)


def head_loss(xn, w, labels, token_weight=None, *, contract: int, dtype):
    """The loss of one sequence: ``xn [T, H]`` (float32, normed),
    ``w`` the head's matrix with its width on axis ``contract`` (0:
    ``[H, V]``; 1: ``[V, H]``), ``labels [T]`` int, ``token_weight [T]``
    (a constant: no cotangent) or the mean over ``T``; ``-> []``
    float32.  The gradient with respect to ``xn`` and ``w`` is taken in
    the forward pass (module docstring)."""
    return _head_loss(contract, dtype, xn, w, labels, token_weight)
