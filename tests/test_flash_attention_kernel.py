"""Causal attention as a Pallas kernel pair (``ops/flash_attention.py``),
on the CPU in interpret mode: forward and gradient against the XLA path
and against a plain float32 softmax (keys and values of one width, and
192-wide keys beside 128-wide values), causality bit for bit, the rule
that picks the path, the round field that reports it, and the lowered
programs of the two decoders whose widths are equal, which this file
pins to the commit before the widths came apart.  (Both kernels are
compiled at the published widths for a described v5e in
``tests/test_gated_delta_kernel.py``, which holds the topology fixture.)
"""

import functools
import hashlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for path in (HERE, REPO):       # HERE: the delta-rule test's trainer
    if path not in sys.path:
        sys.path.insert(0, path)

from federated_pytorch_test_tpu.obs.schema import (  # noqa: E402
    ADVISORY_FIELDS,
    FIELDS,
    SCHEMA_VERSION,
    SchemaError,
    validate_record,
)
from federated_pytorch_test_tpu.ops import flash_attention as fa  # noqa: E402
from test_gated_delta_kernel import (  # noqa: E402
    lm_trainer as gdn_lm_trainer,
)

F32, BF16 = jnp.float32, jnp.bfloat16
#: agreement with the float32 softmax that each operand dtype allows
TOL = {F32: 1e-5, BF16: 2e-2}
GRAD_TOL = {F32: 1e-4, BF16: 3e-2}


def rel(a, b):
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def attn_inputs(T, n_kv, rep, d, seed=0, dv=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (T, n_kv, rep, d)) / math.sqrt(d)
    return (q, jax.random.normal(ks[1], (T, n_kv, d)),
            jax.random.normal(ks[2], (T, n_kv, dv or d)))


def plain_softmax(q, k, v):
    """The definition, float32 throughout, all keys at once."""
    T = q.shape[0]
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision="highest")
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", a, v, precision="highest")


def xla_path(dtype, *args, block=128):
    return fa.causal_attention(*args, dtype=dtype, block=block)


def kernels(dtype, *args, **kw):
    with fa.force_attn_impl("pallas_interpret"):
        return fa.causal_attention(*args, dtype=dtype, block=128, **kw)


def calls_a_kernel(f, *args):
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


def with_grad(f, args):
    loss = lambda *a: jnp.sum(f(*a) ** 2)
    return f(*args), jax.grad(loss, argnums=(0, 1, 2))(*args)


# ----------------------------------------------------------------------
# forward and gradient
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rep", [1, 8])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("T", [256, 512])       # one key block, and two
def test_kernel_path_matches_the_softmax_and_the_xla_path(T, d, rep, dtype):
    # two key/value heads where a group is one head, one where it is
    # eight: the same rows either way
    args = attn_inputs(T, 2 if rep == 1 else 1, rep, d)
    assert calls_a_kernel(functools.partial(kernels, dtype), *args)
    assert not calls_a_kernel(functools.partial(xla_path, dtype), *args)
    o, g = with_grad(functools.partial(kernels, dtype), args)
    o_x, g_x = with_grad(functools.partial(xla_path, dtype), args)
    o_w, g_w = with_grad(plain_softmax, args)
    assert o.shape == o_w.shape and o.dtype == F32
    assert rel(o, o_w) < TOL[dtype]
    # the same arithmetic as the XLA path: what differs is the order of
    # sums and where the running maximum rounds the exponent's argument
    assert rel(o, o_x) < (1e-6 if dtype == F32 else 1e-2)
    for got, xla, want in zip(g, g_x, g_w):
        assert got.shape == want.shape and got.dtype == F32
        assert rel(got, want) < GRAD_TOL[dtype]
        # and no further from it than the XLA path's own gradient
        assert rel(got, want) < 2.0 * rel(xla, want) + 1e-5


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rep,d,dv", [(1, 192, 128), (4, 192, 128),
                                      (1, 64, 128), (2, 128, 256)])
def test_keys_and_values_of_different_widths(rep, d, dv, dtype):
    """192-wide keys beside 128-wide values (latent attention of the
    DeepSeek-V3 line), a key narrower than a lane tile, and values wider
    than the keys: ``q`` and ``k`` reach the kernels zero-padded to the
    next multiple of 128, ``v`` at its own width, and the result and all
    three gradients are those of the softmax at the widths given."""
    T = 512
    args = attn_inputs(T, 2, rep, d, dv=dv)
    f = functools.partial(kernels, dtype)
    jaxpr = str(jax.make_jaxpr(f)(*args))
    assert jaxpr.count("pallas_call") == 1
    padded = d + (-d) % 128
    assert f"{padded}]" in jaxpr and (padded == d or "pad" in jaxpr)
    o, g = with_grad(f, args)
    o_x, g_x = with_grad(functools.partial(xla_path, dtype), args)
    o_w, g_w = with_grad(plain_softmax, args)
    assert o.shape == (T, 2, rep, dv) == o_x.shape == o_w.shape
    assert rel(o, o_w) < TOL[dtype]
    assert rel(o, o_x) < (1e-6 if dtype == F32 else 1e-2)
    for got, xla, want, arg in zip(g, g_x, g_w, args):
        assert got.shape == arg.shape == want.shape and got.dtype == F32
        assert rel(got, want) < GRAD_TOL[dtype]
        assert rel(got, want) < 2.0 * rel(xla, want) + 1e-5


def test_padding_the_keys_is_exact():
    """A zero column adds nothing to a score: the kernels at 192 / 128
    give bit for bit what they give on operands padded by hand to 256,
    and the padded columns get no gradient."""
    q, k, v = attn_inputs(256, 2, 1, 192, dv=128)
    wide = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, 64),))
    f = functools.partial(kernels, BF16)
    assert np.array_equal(np.asarray(f(q, k, v)),
                          np.asarray(f(wide(q), wide(k), v)))
    loss = lambda *a: jnp.sum(f(*a) ** 2)
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(loss, argnums=(0, 1, 2))(wide(q), wide(k), v)
    for a, b in zip(g, gw):
        assert np.array_equal(np.asarray(a),
                              np.asarray(b[..., :a.shape[-1]]))
    assert float(jnp.max(jnp.abs(gw[0][..., 192:]))) == 0.0
    assert float(jnp.max(jnp.abs(gw[1][..., 192:]))) == 0.0


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_later_tokens_leave_earlier_outputs_bit_for_bit(dtype):
    """Positions 300.. change (inside the second of three key blocks, in
    the middle of a query block): ``o[:300]`` is the same to the last
    bit."""
    t = 300
    q, k, v = attn_inputs(768, 1, 8, 128)
    q2, k2, v2 = attn_inputs(768, 1, 8, 128, seed=1)
    late = lambda a, b: a.at[t:].set(b[t:])
    first = kernels(dtype, q, k, v)
    second = kernels(dtype, late(q, q2), late(k, k2), late(v, v2))
    assert np.array_equal(np.asarray(first[:t]), np.asarray(second[:t]))
    assert not np.array_equal(np.asarray(first[t:]), np.asarray(second[t:]))


def test_one_forward_and_one_backward_kernel():
    """The primal is one kernel; a gradient runs the forward once more
    (for ``o`` and the log-sum-exp) and one backward kernel, with no
    ``remat`` of a block inside."""
    args = attn_inputs(256, 1, 8, 128)
    f = functools.partial(kernels, BF16)
    fwd = str(jax.make_jaxpr(f)(*args))
    both = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(f(*a))))(*args))
    assert fwd.count("pallas_call") == 1 and both.count("pallas_call") == 2
    assert "checkpoint" not in both and "remat" not in both
    xla = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(xla_path(BF16, *a))))(*args))
    assert "checkpoint" in xla or "remat" in xla


def kernel_paths(jaxpr):
    """The name stacks of every ``pallas_call`` in ``jaxpr``, in order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += kernel_paths(sub)
    return out


@pytest.mark.parametrize("scope,want", [
    (None, "gated_attn"), ("mla_attn/mla_core", "mla_attn/mla_core"),
    ("mtp/mla_attn/mla_core", "mtp/mla_attn/mla_core")])
def test_the_backward_kernel_s_path_carries_the_scope_given(scope, want):
    """A ``custom_vjp``'s backward rule is traced outside the caller's
    scope: the rule opens the scope it was given (the Qwen3-Next caller
    gives none and keeps ``gated_attn``, which ``benchmarks/lib/
    scopes.py`` reads)."""
    args = attn_inputs(256, 2, 1, 128)
    kw = {} if scope is None else {"scope": scope}

    def loss(*a):
        with jax.named_scope("forward_scope"):
            return jnp.sum(kernels(BF16, *a, **kw))

    fwd, bwd = kernel_paths(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(*args).jaxpr)
    assert "forward_scope" in fwd
    # what the rule opened comes after whatever JAX keeps of the forward
    assert bwd.endswith("/" + want) and not bwd.startswith(want)
    # the scope names a path and changes no number
    base = jax.grad(lambda *a: jnp.sum(kernels(BF16, *a)))(*args)
    assert np.array_equal(np.asarray(jax.grad(loss)(*args)), np.asarray(base))


# ----------------------------------------------------------------------
# which path runs
# ----------------------------------------------------------------------
@pytest.mark.parametrize("case,T,d,dtype,why", [
    ("one_byte_dtype", 256, 128, jnp.float8_e4m3fn, "float8_e4m3fn"),
    ("head_width_64", 256, 64, BF16, "multiple of 128"),
    ("odd_length", 200, 128, BF16, "no multiple of the kernels' blocks"),
    ("over_the_budget", 2**17, 256, F32, "exceed"),
])
def test_what_the_kernels_do_not_take_falls_back_to_xla(case, T, d, dtype,
                                                        why):
    with fa.force_attn_impl("pallas_interpret"):
        p = fa.plan(T, 2, 8, d, dtype)
    assert p["impl"] == "xla" and p["block_q"] == 0 and why in p["why"]
    if T <= 256:
        args = attn_inputs(T, 1, 2, d)
        f = functools.partial(kernels, dtype)
        assert not calls_a_kernel(f, *args)
        # the one-byte probe is still a different result, not an error
        err = rel(f(*args), plain_softmax(*args))
        assert err > 0.02 if case == "one_byte_dtype" else err < 2e-2


def test_without_a_tpu_the_xla_path_runs():
    assert jax.default_backend() == "cpu"
    p = fa.plan(4096, 2, 8, 256, BF16)
    assert p["impl"] == "xla" and p["why"] == "no TPU"
    assert not calls_a_kernel(functools.partial(xla_path, BF16),
                              *attn_inputs(256, 1, 2, 128))


def test_plan_for_192_wide_keys_and_128_wide_values():
    """The published shape of the third decoder: 32 heads, one query
    head a key head, 2,048 tokens."""
    with fa.force_attn_impl("pallas"):
        p = fa.plan(2048, 32, 1, 192, BF16, 128)
        same = fa.plan(2048, 32, 1, 256, BF16, 256)
        wide = fa.plan(2048, 32, 1, 256, BF16)
        narrow_v = fa.plan(2048, 32, 1, 192, BF16, 64)
    assert p["impl"] == "pallas" and p["why"] == "fits"
    assert (p["pad_k"], p["block_q"], p["block_k"]) == (64, 256, 256)
    # the estimate is that of keys of 256 beside values of 128: smaller
    # than both at 256
    assert 0 < p["vmem_bytes"] < same["vmem_bytes"] <= p["vmem_budget"]
    assert p["vmem_bytes"] == fa._grad_vmem_bytes(2048, 256, 256, 256, 2, 128)
    # equal widths: nothing is padded and the answer is what it was
    assert same == wide and same["pad_k"] == 0
    # values that are no multiple of 128 still fall to the XLA path
    assert narrow_v["impl"] == "xla" and "128" in narrow_v["why"]
    assert fa.plan(2048, 32, 1, 192, BF16, 128)["why"] == "no TPU"


@pytest.mark.parametrize("T,rep,dtype,block_q,block_k", [
    (4096, 8, BF16, 128, 256),      # the published shape: 1,024 rows a step
    (4096, 1, BF16, 256, 256),
    (4096, 16, BF16, 64, 256),
    (384, 8, BF16, 128, 128),       # no multiple of 256
    (4096, 128, F32, 8, 256),       # never under a sublane tile
])
def test_plan_s_blocks(T, rep, dtype, block_q, block_k):
    with fa.force_attn_impl("pallas"):
        p = fa.plan(T, 2, rep, 256, dtype)
    assert p["impl"] == "pallas" and p["why"] == "fits"
    assert (p["block_q"], p["block_k"]) == (block_q, block_k)
    assert T % block_k == 0 and block_k % block_q == 0
    assert 0 < p["vmem_bytes"] <= p["vmem_budget"]
    # the estimate grows with the sequence: dk, dv of a whole head stay
    assert p["vmem_bytes"] > fa._grad_vmem_bytes(
        T // 2, rep * block_q, block_k, 256, jnp.dtype(dtype).itemsize)


# ----------------------------------------------------------------------
# the round field
# ----------------------------------------------------------------------
def lm_trainer():
    """The delta-rule test's two-layer model with narrow GDN heads,
    attention heads as wide as the kernels ask and a sequence of three
    key blocks; the attention block is active."""
    return gdn_lm_trainer(block=3, seq_len=384, head_dim=128,
                          attn_block=128, linear_key_head_dim=8,
                          linear_value_head_dim=8)


@pytest.fixture(scope="module")
def rounds():
    out = {}
    for impl in ("xla", "pallas_interpret"):
        t = lm_trainer()
        with fa.force_attn_impl(impl):
            state, hist = t.run(log=lambda m: None)
        t.close()
        out[impl] = (jax.tree.map(np.asarray, state.params), hist)
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_every_round_says_which_implementation_ran(rounds, impl):
    _, hist = rounds[impl]
    assert len(hist) == 2
    assert [r["attn_impl"] for r in hist] == [impl] * 2
    assert all(r["block_kind"] == "attn" for r in hist)
    assert all(r["gdn_scan_impl"] == "xla" for r in hist)


def test_rounds_through_the_kernels_train_what_the_xla_path_trains(rounds):
    """The whole path: ``custom_vjp`` under ``jax.checkpoint``, the map
    over sequences and the engine's client-by-client gradient."""
    (p_x, h_x), (p_k, h_k) = rounds["xla"], rounds["pallas_interpret"]
    for a, b in zip(h_x, h_k):
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)
    moved = 0.0
    for a, b in zip(jax.tree.leaves(p_x), jax.tree.leaves(p_k)):
        # Adam's first steps are lr * sign(g): compare to a tenth of lr
        assert np.max(np.abs(a - b)) < 1e-4
        moved = max(moved, float(np.max(np.abs(a[0] - a[1]))))
    assert moved == 0.0             # FedAvg left the clients equal


def test_fields_declare_attn_impl():
    assert FIELDS["attn_impl"][0] == ("round",)
    assert "attn_impl" in ADVISORY_FIELDS
    base = {"event": "round", "schema": SCHEMA_VERSION, "run_id": "t" * 8,
            "engine": "lm", "round_index": 0, "round_seconds": 0.5,
            "loss": 1.0}
    validate_record(dict(base, attn_impl="pallas"))
    with pytest.raises(SchemaError, match="attn_impl"):
        validate_record(dict(base, attn_impl=1))


# ----------------------------------------------------------------------
# the sibling decoders' lowered programs: no unintended change
# ----------------------------------------------------------------------
#: sha256 of the lowered (StableHLO) forward and loss gradient of both
#: sibling decoders at their cells' shapes (the configuration files'
#: widths, batch and sequence), recorded with ``lowered_hashes`` below
#: under this file's own pytest set-up (``conftest.py``'s flags are part
#: of a lowered module) on the tree of PR 35, which changed both on
#: purpose (``ops/moe.py``: ``dispatch``, ``combine``), the two loss
#: gradients again on the tree of PR 39, which changed both heads' loss
#: on purpose (``ops/head_loss.py``; the forwards stayed), and
#: Qwen3-Next's kernel path on the tree that fused the delta rule's primal
#: pass into one kernel (``ops/gated_delta.py``: its forward holds that
#: kernel, its gradient's primal pass too; its XLA path and GLM's
#: programs stayed).  They guard against a change of either decoder's lowered program that nobody
#: meant: PR 34 let the key and value widths of the attention kernels
#: differ and left all six as they were.  A PR that means to change
#: either model's program records them again and says so.
#: ``pallas_interpret`` lowers the kernels' own bodies on the CPU,
#: ``xla`` the XLA path.
RECORDED_LOWERED = {
    "qwen3_next/pallas_interpret/forward": "679c79e1acdbeba3",
    "qwen3_next/pallas_interpret/loss_grad": "f30bee64af6092fe",
    "qwen3_next/xla/loss_grad": "e0a42e9fa622c653",
    "glm4_moe_lite/pallas_interpret/forward": "02fdb2bcc7564198",
    "glm4_moe_lite/pallas_interpret/loss_grad": "511f4d43533b55e6",
    "glm4_moe_lite/xla/loss_grad": "57b72354aabdeff0",
}


def lowered_hashes(cell_name):
    from benchmarks.engines import decoder
    from benchmarks.lib import cells
    from federated_pytorch_test_tpu.ops import gated_delta as gd

    cfg = cells.load_cell(cell_name).config
    model = decoder.build_model(cfg)
    ids = jax.ShapeDtypeStruct((int(cfg["batch"]), int(cfg["seq_len"])),
                               jnp.int32)
    params = jax.eval_shape(lambda: model.init_variables(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[0])
    sha = lambda f, *ops: hashlib.sha256(
        jax.jit(f).lower(*ops).as_text().encode()).hexdigest()[:16]
    out = {}
    for impl, what in (("pallas_interpret", ("forward", "loss_grad")),
                       ("xla", ("loss_grad",))):
        with fa.force_attn_impl(impl), gd.force_gdn_scan_impl(impl):
            # fresh functions: a jitted one would answer from the trace
            # it made under the other implementation
            if "forward" in what:
                out[f"{cfg['model']}/{impl}/forward"] = sha(
                    lambda p, x: model.apply({"params": p}, x)[0], params,
                    ids)
            out[f"{cfg['model']}/{impl}/loss_grad"] = sha(
                jax.value_and_grad(lambda p, x, y: jnp.mean(
                    model.apply({"params": p}, x, y)[0])), params, ids, ids)
    return out


@pytest.mark.parametrize("cell", ["qwen3next_fedavg_blocks",
                                  "glm47flash_fedavg_mtp_blocks"])
def test_sibling_decoders_lower_byte_for_byte_as_recorded(cell):
    """The lowered programs of both sibling models, at their cells'
    shapes, are the recorded ones."""
    got = lowered_hashes(cell)
    assert got and set(got) <= set(RECORDED_LOWERED)
    assert got == {k: RECORDED_LOWERED[k] for k in got}
