"""The delta rule's chunk recurrence as a Pallas kernel pair
(``ops/gated_delta.py``), on the CPU in interpret mode: against the
token-by-token recurrence and against the ``lax.scan`` path, the backward
kernel line by line against ``jax.vjp`` of the XLA step, the rule that
picks the path, the round field that reports it, and a compile of both
kernels at the published widths for a described v5e, and of the attention
kernel pair (``ops/flash_attention.py``) and the hyper-connections'
stream kernels (``ops/hyper_connections.py``) beside them: one file holds
the topology fixture, because only one test process may load the TPU's
library.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from federated_pytorch_test_tpu.data.tokens import FederatedTokens  # noqa: E402
from federated_pytorch_test_tpu.models import get_model  # noqa: E402
from federated_pytorch_test_tpu.ops import flash_attention as fa  # noqa: E402
from federated_pytorch_test_tpu.ops import gated_delta as gd  # noqa: E402
from federated_pytorch_test_tpu.ops import hyper_connections as hc  # noqa: E402
from federated_pytorch_test_tpu.train import (  # noqa: E402
    FedAvg,
    FederatedConfig,
)
from federated_pytorch_test_tpu.train.lm_engine import LMTrainer  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
CHUNK, D = 64, 128          # the published chunk and head width
INPUTS = ("q", "k", "v", "g", "beta")
#: agreement with the float32 recurrence that each operand dtype allows
TOL = {F32: 1e-5, BF16: 2e-2}
GRAD_TOL = {F32: 1e-4, BF16: 3e-2}


def rel(a, b):
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def delta_inputs(length, H=2, dk=D, dv=D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (H, length, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (H, length, dk)))
    v = jax.random.normal(ks[2], (H, length, dv))
    g = -3.0 * jax.random.uniform(ks[3], (H, length))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (H, length)))
    return q, k, v, g, beta


def chunked(dtype, *args, chunk=CHUNK):
    return gd.gated_delta_chunked(*args, chunk=chunk, dtype=dtype)


def kernels(dtype, *args, **kw):
    with gd.force_gdn_scan_impl("pallas_interpret"):
        return chunked(dtype, *args, **kw)


def calls_a_kernel(f, *args):
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


# ----------------------------------------------------------------------
# forward and gradient, whole function
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length", [64, 128, 100, 7])
def test_kernel_path_matches_the_recurrence_and_the_scan(length, dtype):
    args = delta_inputs(length)
    assert calls_a_kernel(functools.partial(kernels, dtype), *args)
    assert not calls_a_kernel(functools.partial(chunked, dtype), *args)
    got = kernels(dtype, *args)
    want = jax.vmap(gd.gated_delta_stepwise)(*args)
    assert got.shape == want.shape and got.dtype == F32
    assert rel(got, want) < TOL[dtype]
    # the same arithmetic as the scan: what differs is the order of sums
    assert rel(got, chunked(dtype, *args)) < (1e-6 if dtype == F32 else 1e-2)


@pytest.fixture(scope="module")
def gradients():
    """Gradients of all five inputs at a length that needs padding:
    the recurrence's, and per dtype the kernel path's and the scan's."""
    args = delta_inputs(100)
    loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)
    grad = lambda f: jax.grad(loss(f), argnums=(0, 1, 2, 3, 4))(*args)
    out = {"want": grad(jax.vmap(gd.gated_delta_stepwise))}
    for dtype in (F32, BF16):
        out[dtype] = (grad(functools.partial(kernels, dtype)),
                      grad(functools.partial(chunked, dtype)))
    return out


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("i", range(5), ids=INPUTS)
def test_kernel_path_gradient_matches_the_recurrence(gradients, i, dtype):
    got, scan = gradients[dtype]
    want = gradients["want"][i]
    assert got[i].shape == want.shape
    assert rel(got[i], want) < GRAD_TOL[dtype]
    # and no further from it than the scan's own gradient, rounding aside
    assert rel(got[i], want) < 2.0 * rel(scan[i], want) + 1e-5


def test_forward_without_a_gradient_writes_no_states():
    """Where no gradient is asked the primal kernel runs: one output."""
    args = delta_inputs(128)
    fwd = str(jax.make_jaxpr(functools.partial(kernels, BF16))(*args))
    both = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kernels(BF16, *a))))(*args))
    assert fwd.count("pallas_call") == 1 and both.count("pallas_call") == 2
    states = "f32[2,2,128,128]"       # S at each chunk's start
    assert states not in fwd and states in both


# ----------------------------------------------------------------------
# the triangular inverse's closed-form derivative
# ----------------------------------------------------------------------
#: the Neumann product as JAX differentiates it, factor by factor
neumann_product = gd._unit_lower_inverse.__wrapped__


def linalg_inv(a):
    return jnp.linalg.inv(jnp.eye(a.shape[-1], dtype=a.dtype) + a)


def inverse_case(C, lead, seed=0):
    """A strictly lower-triangular ``a [*lead, C, C]`` of the size the
    delta rule gives it (``beta k_i . k_j`` of unit keys, decayed) and a
    FULL cotangent, as ``u`` and ``w`` send back."""
    ka, kc = jax.random.split(jax.random.PRNGKey(seed))
    a = jnp.tril(0.1 * jax.random.normal(ka, (*lead, C, C)), -1)
    return a, jax.random.normal(kc, a.shape)


def inverse_grad(inverse, a, ct):
    return jax.grad(lambda x: jnp.sum(inverse(x) * ct))(a)


@pytest.mark.parametrize("reference", [neumann_product, linalg_inv],
                         ids=["neumann_product", "linalg_inv"])
@pytest.mark.parametrize("lead", [(3,), (2, 3)], ids=["one_axis", "two_axes"])
@pytest.mark.parametrize("C", [8, 64])
def test_inverse_gradient_is_the_derivative_of_an_inverse(C, lead, reference):
    a, ct = inverse_case(C, lead)
    assert rel(gd._unit_lower_inverse(a), reference(a)) < 1e-5
    got = inverse_grad(gd._unit_lower_inverse, a, ct)
    assert got.shape == a.shape and got.dtype == F32
    assert rel(got, inverse_grad(reference, a, ct)) < 1e-5


@pytest.mark.parametrize("inverse,products", [
    (gd._unit_lower_inverse, 12), (neumann_product, 29)],
    ids=["closed_form", "neumann_product"])
def test_inverse_gradient_runs_two_products_more_not_nineteen(inverse,
                                                              products):
    """At the published chunk: ten products forward either way; the rule
    adds two where JAX's transpose of the ten adds nineteen."""
    a, ct = inverse_case(CHUNK, (2, 3))
    lowered = jax.jit(functools.partial(inverse_grad, inverse)).lower(a, ct)
    assert lowered.as_text().count("dot_general") == products


def test_inverse_keeps_the_inverse_alone_for_the_backward_pass():
    a, _ = inverse_case(CHUNK, (2, 3))
    kept = lambda f: jax.tree_util.tree_leaves(jax.vjp(f, a)[1])
    assert [(r.shape, r.dtype) for r in kept(gd._unit_lower_inverse)] \
        == [(a.shape, F32)]
    assert len(kept(neumann_product)) > 1       # powers, partial products


def test_inverse_backward_rule_opens_the_scope_again():
    """The rule is traced outside the caller's scope; without its own the
    two products would be found under no scope in a trace."""
    a, ct = inverse_case(CHUNK, (3,))
    text = jax.jit(functools.partial(
        inverse_grad, gd._unit_lower_inverse)).lower(a, ct).as_text(
            debug_info=True)
    products = [line for line in text.splitlines()
                if line.startswith("#loc") and "dot_general" in line
                and "transpose(jvp(" in line]
    assert products and all(gd._SCOPE in line for line in products)


@pytest.mark.parametrize("how", ["vmap", "checkpoint", "lax_map"])
def test_inverse_gradient_is_the_same_as_the_model_calls_it(how):
    """``models/qwen3_next.py`` reaches it under ``jax.checkpoint`` inside
    ``lax.map`` over the sequences; a trainer may ``vmap`` its clients."""
    a, ct = inverse_case(CHUNK, (2, 3))
    wrapped = {"vmap": jax.vmap(gd._unit_lower_inverse),
               "checkpoint": jax.checkpoint(gd._unit_lower_inverse),
               "lax_map": lambda x: jax.lax.map(
                   jax.checkpoint(gd._unit_lower_inverse), x)}[how]
    want = inverse_grad(neumann_product, a, ct)
    assert rel(jax.jit(functools.partial(inverse_grad, wrapped))(a, ct),
               want) < 1e-5


# ----------------------------------------------------------------------
# the backward kernel, line by line
# ----------------------------------------------------------------------
STEP_GRADS = ("du", "dw", "dqk", "dq_in", "dk_out", "dg_last")


@pytest.fixture(scope="module")
def step_vjp():
    """Two chunks, so that the second's ``dS`` reaches the first and the
    first's ``S`` is not zero at the second: the kernel pair's cotangents
    and ``jax.vjp`` of two applications of the XLA step, per dtype."""
    H, N, C = 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    n = lambda key, *s: jax.random.normal(key, s)
    ops = (n(ks[0], H, N, C, D), 0.3 * n(ks[1], H, N, C, D),
           jnp.tril(0.3 * n(ks[2], H, N, C, C)), 0.3 * n(ks[3], H, N, C, D),
           0.3 * n(ks[4], H, N, C, D),
           jnp.exp(-jax.random.uniform(ks[5], (H, N))))
    do = n(ks[6], H, N, C, D)
    out = {}
    for dtype in (F32, BF16):
        def xla(*ops):
            S, outs = jnp.zeros((H, D, D), F32), []
            for c in range(N):
                S, o = gd._step(dtype, S, tuple(a[:, c] for a in ops))
                outs.append(o)
            return jnp.stack(outs, 1)

        def pallas(u, w, qk, q_in, k_out, gl):
            c = lambda a: a.astype(dtype)
            return gd._recurrence(H, True, u, c(w), c(qk), c(q_in), c(k_out),
                                  jnp.broadcast_to(gl[..., None, None],
                                                   (H, N, 1, D)))

        o_x, vjp_x = jax.vjp(xla, *ops)
        o_p, vjp_p = jax.vjp(pallas, *ops)
        out[dtype] = (o_p, o_x, vjp_p(do), vjp_x(do))
    return out


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("i", range(6), ids=STEP_GRADS)
def test_backward_kernel_matches_the_vjp_of_the_xla_step(step_vjp, i, dtype):
    o_p, o_x, got, want = step_vjp[dtype]
    assert rel(o_p, o_x) < (1e-6 if dtype == F32 else 1e-2)
    assert got[i].shape == want[i].shape and got[i].dtype == want[i].dtype
    assert rel(got[i], want[i]) < (2e-6 if dtype == F32 else 2e-2)


# ----------------------------------------------------------------------
# which path runs
# ----------------------------------------------------------------------
@pytest.mark.parametrize("case,dk,dv,chunk,dtype,why", [
    ("one_byte_dtype", D, D, CHUNK, jnp.float8_e4m3fn, "float8_e4m3fn"),
    ("key_width_64", 64, D, CHUNK, BF16, "multiples of 128"),
    ("key_width_160", 160, D, CHUNK, BF16, "multiples of 128"),
    ("value_width_64", D, 64, CHUNK, BF16, "multiples of 128"),
    ("chunk_12", D, D, 12, BF16, "multiple of 8"),
    ("over_the_budget", 1024, 1024, 512, F32, "exceed"),
])
def test_what_the_kernels_do_not_take_falls_back_to_the_scan(
        case, dk, dv, chunk, dtype, why):
    with gd.force_gdn_scan_impl("pallas_interpret"):
        p = gd.plan(2, 2, chunk, dk, dv, dtype)
    assert p["impl"] == "xla" and p["heads"] == 0 and why in p["why"]
    if dk * dv <= D * D:
        args = delta_inputs(2 * chunk, dk=dk, dv=dv)
        f = functools.partial(kernels, dtype, chunk=chunk)
        assert not calls_a_kernel(f, *args)
        # the one-byte probe is still a different result, not an error
        want = jax.vmap(gd.gated_delta_stepwise)(*args)
        err = rel(f(*args), want)
        assert err > 0.02 if case == "one_byte_dtype" else err < 2e-2


def test_without_a_tpu_the_scan_runs():
    assert jax.default_backend() == "cpu"
    p = gd.plan(32, 64, CHUNK, D, D, BF16)
    assert p["impl"] == "xla" and p["why"] == "no TPU"
    assert not calls_a_kernel(functools.partial(chunked, BF16),
                              *delta_inputs(CHUNK))


@pytest.mark.parametrize("H,heads", [(32, 8), (16, 8), (12, 6), (6, 6),
                                     (7, 7), (11, 1)])
def test_plan_takes_the_most_heads_that_divide_and_fit(H, heads):
    with gd.force_gdn_scan_impl("pallas"):
        p = gd.plan(H, 64, CHUNK, D, D, BF16)
    assert p["impl"] == "pallas" and p["heads"] == heads
    assert 0 < p["vmem_bytes"] <= p["vmem_budget"] <= 16 * 2**20
    # the estimate grows with what a step holds
    assert p["vmem_bytes"] >= gd._grad_vmem_bytes(1, CHUNK, D, D, 2)
    with gd.force_gdn_scan_impl("pallas"):
        wide = gd.plan(H, 64, CHUNK, D, D, F32)
    assert wide["vmem_bytes"] > p["vmem_bytes"] or wide["heads"] < heads


# ----------------------------------------------------------------------
# unequal widths, beta up to 2: 96-wide keys, 192-wide values
# ----------------------------------------------------------------------
DK, DV = 96, 192


def neg_eigval_inputs(length, H=3, seed=5):
    """Three heads (no multiple of 8) of 96-wide keys and 192-wide
    values with ``beta = 2 sigmoid(.)``: transitions whose eigenvalue
    along ``k`` lies in (-1, 1)."""
    q, k, v, g, _ = delta_inputs(length, H=H, dk=DK, dv=DV, seed=seed)
    beta = 2.0 * jax.nn.sigmoid(
        2.0 * jax.random.normal(jax.random.PRNGKey(seed + 1), (H, length)))
    return q, k, v, g, beta


def test_plan_takes_96_wide_keys_and_192_wide_values():
    """Native blocks: full-width in HBM, estimated as Mosaic lays them
    out in VMEM (128 and 256 lanes); 30 heads go six to a step."""
    with gd.force_gdn_scan_impl("pallas"):
        p = gd.plan(30, 64, CHUNK, DK, DV, BF16)
        assert (p["impl"], p["heads"]) == ("pallas", 6)
        assert gd.plan(3, 2, CHUNK, DK, DV, F32)["heads"] == 3
    assert p["vmem_bytes"] == gd._grad_vmem_bytes(6, CHUNK, 128, 256, 2)


def test_plan_at_128_returns_what_it_returned_before():
    with gd.force_gdn_scan_impl("pallas"):
        assert gd.plan(32, 64, CHUNK, D, D, BF16) == {
            "impl": "pallas", "heads": 8, "vmem_bytes": 8781824,
            "vmem_budget": 12582912, "why": "fits"}
        assert gd.plan(16, 64, CHUNK, D, D, F32) == {
            "impl": "pallas", "heads": 8, "vmem_bytes": 10616832,
            "vmem_budget": 12582912, "why": "fits"}


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length", [128, 100])
def test_kernels_at_96_192_with_beta_up_to_2_match_the_scan(length, dtype):
    args = neg_eigval_inputs(length)
    assert float(jnp.max(args[4])) > 1.8 and float(jnp.min(args[4])) < 0.2
    assert calls_a_kernel(functools.partial(kernels, dtype), *args)
    got = kernels(dtype, *args)
    want = jax.vmap(gd.gated_delta_stepwise)(*args)
    assert got.shape == want.shape == (3, length, DV) and got.dtype == F32
    assert rel(got, want) < TOL[dtype]
    assert rel(got, chunked(dtype, *args)) < (1e-6 if dtype == F32 else 1e-2)


@pytest.fixture(scope="module")
def gradients_96_192():
    args = neg_eigval_inputs(100)
    loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)
    grad = lambda f: jax.grad(loss(f), argnums=(0, 1, 2, 3, 4))(*args)
    out = {"want": grad(jax.vmap(gd.gated_delta_stepwise))}
    for dtype in (F32, BF16):
        out[dtype] = (grad(functools.partial(kernels, dtype)),
                      grad(functools.partial(chunked, dtype)))
    return out


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("i", range(5), ids=INPUTS)
def test_kernels_at_96_192_gradient_matches_the_scan(gradients_96_192, i,
                                                     dtype):
    got, scan = gradients_96_192[dtype]
    want = gradients_96_192["want"][i]
    assert got[i].shape == want.shape
    assert rel(got[i], want) < GRAD_TOL[dtype]
    assert rel(got[i], want) < 2.0 * rel(scan[i], want) + 1e-5
    # the kernels and the scan do the same arithmetic
    assert rel(got[i], scan[i]) < (1e-5 if dtype == F32 else 2e-2)


#: sha256 of the kernel pair's lowering (StableHLO; the kernels' bodies in
#: interpret mode) at 128 / 128, as the tree before 96 / 192 was taken
#: lowered them under this file's pytest set-up: what Qwen3-Next runs
RECORDED_128 = {"forward": "8f9017ba6cc7b008", "gradient": "44b79d5daf973730",
                "chunked_gradient": "11f86c437b4fef29"}


def test_the_kernel_pair_lowers_at_128_as_before():
    import hashlib

    S = jax.ShapeDtypeStruct
    H, N = 16, 4
    ops = (S((H, N, CHUNK, D), F32), S((H, N, CHUNK, D), BF16),
           S((H, N, CHUNK, CHUNK), BF16), S((H, N, CHUNK, D), BF16),
           S((H, N, CHUNK, D), BF16), S((H, N, 1, D), F32))
    sha = lambda f, *a: hashlib.sha256(
        jax.jit(f).lower(*a).as_text().encode()).hexdigest()[:16]
    f = functools.partial(gd._recurrence, 8, True)
    args = tuple(S((2, 100, D), F32) for _ in range(3)) \
        + (S((2, 100), F32), S((2, 100), F32))
    with gd.force_gdn_scan_impl("pallas_interpret"):
        loss = lambda *a: jnp.sum(chunked(BF16, *a) ** 2)
        got = {"forward": sha(f, *ops),
               "gradient": sha(jax.grad(lambda *a: jnp.sum(f(*a)),
                                        argnums=tuple(range(6))), *ops),
               "chunked_gradient": sha(jax.grad(loss, argnums=tuple(
                   range(5))), *args)}
    assert got == RECORDED_128


# ----------------------------------------------------------------------
# the round field
# ----------------------------------------------------------------------
def lm_trainer(block=1, seq_len=24, **widths):
    """Two layers (GDN, attention) at tiny widths but for the GDN heads,
    which are as wide as the kernels ask; the GDN block is active.
    (``tests/test_flash_attention_kernel.py`` asks for wide attention
    heads and the attention block instead.)"""
    model = get_model("qwen3_next", **{**dict(
        hidden_size=32, num_attention_heads=2,
        num_key_value_heads=1, head_dim=16, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=D,
        linear_value_head_dim=D, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        layers=2, full_attention_interval=2, experts_held=4, vocab_rows=64,
        chunk=16, attn_block=16, pair_rows_factor=8.0, dtype=F32), **widths})
    data = FederatedTokens(K=2, batch=2, samples_per_client=2,
                           seq_len=seq_len, vocab=64, seed=3, head=16)
    cfg = FederatedConfig(K=2, Nloop=1, Nepoch=1, Nadmm=2, default_batch=2,
                          check_results=False, lr=1e-3, num_devices=1,
                          save_model=False)
    t = LMTrainer(model, cfg, data, FedAvg())
    t.block_ids, t.L = [t.block_ids[block]], 1
    return t


@pytest.fixture(scope="module")
def rounds():
    out = {}
    for impl in ("xla", "pallas_interpret"):
        t = lm_trainer()
        with gd.force_gdn_scan_impl(impl):
            state, hist = t.run(log=lambda m: None)
        t.close()
        out[impl] = (jax.tree.map(np.asarray, state.params), hist)
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_every_round_says_which_implementation_ran(rounds, impl):
    _, hist = rounds[impl]
    assert len(hist) == 2
    assert [r["gdn_scan_impl"] for r in hist] == [impl] * 2
    assert all(r["block_kind"] == "gdn" for r in hist)


def test_rounds_through_the_kernels_train_what_the_scan_trains(rounds):
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


# ----------------------------------------------------------------------
# compile for the chip, without the chip
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compiled_for(f, *ops):
    """The text of ``f`` compiled for the described chip."""
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(f).lower(*ops).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_kernels_compile_for_a_v5e_at_the_published_widths(one_chip, what):
    """Mosaic takes both kernels at 32 heads of 128, 64 chunks of 64 and
    the heads per step that ``plan`` picks; interpret mode cannot tell."""
    H, N = 32, 64
    with gd.force_gdn_scan_impl("pallas"):
        heads = gd.plan(H, N, CHUNK, D, D, BF16)["heads"]
    sh = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    ops = (sh(F32, H, N, CHUNK, D), sh(BF16, H, N, CHUNK, D),
           sh(BF16, H, N, CHUNK, CHUNK), sh(BF16, H, N, CHUNK, D),
           sh(BF16, H, N, CHUNK, D), sh(F32, H, N, 1, D))
    f = functools.partial(gd._recurrence, heads, False)
    if what != "forward":
        f = jax.grad(lambda *a, f=f: jnp.sum(f(*a)), argnums=(0, 1, 2, 3, 4,
                                                              5))
    text = compiled_for(f, *ops)
    assert text.count("tpu_custom_call") == (1 if what == "forward" else 2)


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_kernels_compile_for_a_v5e_at_96_192(one_chip, what):
    """Mosaic takes both kernels at Olmo-Hybrid's 30 heads of 96-wide keys
    and 192-wide values as full-width blocks, six heads a step."""
    H, N = 30, 64
    with gd.force_gdn_scan_impl("pallas"):
        heads = gd.plan(H, N, CHUNK, DK, DV, BF16)["heads"]
    assert heads == 6
    sh = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    ops = (sh(F32, H, N, CHUNK, DV), sh(BF16, H, N, CHUNK, DK),
           sh(BF16, H, N, CHUNK, CHUNK), sh(BF16, H, N, CHUNK, DK),
           sh(BF16, H, N, CHUNK, DK), sh(F32, H, N, 1, DV))
    f = functools.partial(gd._recurrence, heads, False)
    if what != "forward":
        f = jax.grad(lambda *a, f=f: jnp.sum(f(*a)), argnums=tuple(range(6)))
    text = compiled_for(f, *ops)
    assert text.count("tpu_custom_call") == (1 if what == "forward" else 2)


@pytest.mark.parametrize("n_kv,rep", [(2, 8), (20, 1)],
                         ids=["qwen3_next_16_2", "glm4_moe_lite_20_20"])
@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_attention_kernels_compile_for_a_v5e_at_the_published_widths(
        one_chip, what, n_kv, rep):
    """The attention pair at 16 / 2 heads of 256 (Qwen3-Next) and at 20
    / 20 heads of 256 (GLM-4.7-Flash's latent attention, expanded) over
    4,096 tokens with ``plan``'s blocks: the whole key/value head and
    its float32 ``dk``, ``dv`` in VMEM, a transposed-operand product, a
    dynamic trip count."""
    T, d = 4096, 256
    sh = lambda *s: jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
    ops = (sh(T, n_kv, rep, d), sh(T, n_kv, d), sh(T, n_kv, d))

    def f(*a):
        with fa.force_attn_impl("pallas"):
            assert fa.plan(T, n_kv, rep, d, BF16)["impl"] == "pallas"
            return fa.causal_attention(*a, dtype=BF16,
                                       scope="mla_attn/mla_core")

    if what != "forward":
        f = jax.grad(lambda *a, f=f: jnp.sum(f(*a)), argnums=(0, 1, 2))
    text = compiled_for(f, *ops)
    assert text.count("tpu_custom_call") == (1 if what == "forward" else 2)


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_stream_kernels_compile_for_a_v5e_at_the_published_widths(one_chip,
                                                                  what):
    """A sub-layer of Xing4.0's hyper-connections at 4 streams of 3,584
    over 2 x 2,048 tokens with ``plan``'s tile: three double-buffered
    stream tiles beside two stacks of ``phi`` in 96 MiB of VMEM, lane
    rolls, masked lane sums, scalars in SMEM.  Forward: ``pre`` and
    ``expand``; backward: ``pre`` again and the two rules' kernels
    (``expand`` is not run again)."""
    n, C, lead = 4, 3584, (2, 2048)
    sh = lambda *s: jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
    leaves = {"phi_pre": sh(n * C, n), "phi_post": sh(n * C, n),
              "phi_res": sh(n * C, n * n), "a_pre": sh(1), "a_post": sh(1),
              "a_res": sh(1), "b_pre": sh(n), "b_post": sh(n),
              "b_res": sh(n, n)}

    def f(leaves, x):
        with hc.force_mhc_impl("pallas"):
            assert hc.plan(n, 4096, C)["impl"] == "pallas"
            u, m, xt = hc.pre(x, leaves, iters=20, eps=1e-6)
            return hc.expand(m.res, m.post, xt, jnp.tanh(u))

    if what != "forward":
        f = jax.grad(lambda *a, f=f: jnp.sum(f(*a)), argnums=(0, 1))
    text = compiled_for(f, leaves, sh(n, *lead, C))
    assert text.count("tpu_custom_call") == (2 if what == "forward" else 3)
