"""Manifold-constrained hyper-connections (``ops/hyper_connections.py``)
on the CPU: the Sinkhorn projection and its gradient against the
reference's written-out loop over one token's matrix, the maps against
the reference's per-token maps, the mixing, and the plain residual as
the special case it is.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.reference import xing4_0 as ref  # noqa: E402
from federated_pytorch_test_tpu.ops import hyper_connections as hc  # noqa: E402

N, C, T = 4, 24, 50
CFG = {"hc_mult": N, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
       "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
       "rms_norm_eps": 1e-6}


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def logits(scale, seed=0, tokens=T):
    """``[n, n, tokens]`` logits as the model's: a diagonal and noise."""
    r = jnp.eye(N)[:, :, None] + scale * jax.random.normal(
        jax.random.PRNGKey(seed), (N, N, tokens))
    return jnp.clip(r, -30.0, 30.0)


def leaves(seed=0, alpha=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    phi = lambda k, m: jax.random.normal(k, (N * C, m)) * (N * C) ** -0.5
    return {"phi_pre": phi(ks[0], N), "phi_post": phi(ks[1], N),
            "phi_res": phi(ks[2], N * N),
            "a_pre": jnp.full((1,), alpha), "a_post": jnp.full((1,), alpha),
            "a_res": jnp.full((1,), alpha),
            "b_pre": 0.3 * jax.random.normal(ks[3], (N,)),
            "b_post": 0.3 * jax.random.normal(ks[4], (N,)),
            "b_res": jnp.eye(N) + 0.3 * jax.random.normal(ks[5], (N, N))}


def ref_maps(lv, x):
    """The reference's per-token maps of ``x [n, T, C]``, tokens last as
    the op has them."""
    p = {"hc_" + k: v for k, v in lv.items()}
    pre, post, res = jax.vmap(lambda xt: ref.token_maps(CFG, p, xt))(
        jnp.moveaxis(x, 1, 0))
    return pre.T, post.T, jnp.moveaxis(res, 0, -1)


# ----------------------------------------------------------------------
# the Sinkhorn projection
# ----------------------------------------------------------------------
@pytest.mark.parametrize("scale", [0.25, 0.5])
def test_sinkhorn_is_doubly_stochastic_after_20_iterations(scale):
    """At the spread the model's maps are seeded with (0.5) and below."""
    h = hc.sinkhorn(logits(scale, tokens=1000), 20, 1e-6)
    assert h.shape == (N, N, 1000) and bool(jnp.all(h > 0))
    assert float(jnp.max(jnp.abs(jnp.sum(h, axis=0) - 1.0))) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(h, axis=1) - 1.0))) < 1e-5


def test_a_wider_spread_of_logits_converges_more_slowly():
    """Rows are exact after their division whatever the spread; columns
    converge at a rate the matrix sets, which is why the round field
    ``mhc_marginal_err`` exists."""
    for scale, lo, hi in ((1.0, 1e-5, 1e-2), (2.0, 1e-3, 1e-1)):
        h = hc.sinkhorn(logits(scale, tokens=1000), 20, 1e-6)
        assert float(jnp.max(jnp.abs(jnp.sum(h, axis=1) - 1.0))) < 1e-5
        assert lo < float(jnp.max(jnp.abs(jnp.sum(h, axis=0) - 1.0))) < hi


def test_sinkhorn_of_clamped_extremes_stays_finite_and_rows_sum_to_one():
    """Logits at both ends of the clamp (``exp`` spans 26 decades): rows
    are exact after their division; columns need not have converged."""
    r = jnp.clip(100.0 * jax.random.normal(jax.random.PRNGKey(1),
                                           (N, N, 64)), -30.0, 30.0)
    h = hc.sinkhorn(r, 20, 1e-6)
    assert bool(jnp.all(jnp.isfinite(h)))
    assert float(jnp.max(jnp.abs(jnp.sum(h, axis=1) - 1.0))) < 1e-5


def test_sinkhorn_and_its_gradient_match_the_written_out_loop():
    r = logits(1.0)
    ct = jax.random.normal(jax.random.PRNGKey(2), r.shape)
    loop = lambda r: jnp.moveaxis(jax.vmap(
        lambda m: ref.sinkhorn_one(m, 20, 1e-6, -30.0, 30.0))(
            jnp.moveaxis(r, -1, 0)), 0, -1)
    assert rel(hc.sinkhorn(r, 20, 1e-6), loop(r)) < 1e-6
    got = jax.grad(lambda r: jnp.sum(hc.sinkhorn(r, 20, 1e-6) * ct))(r)
    want = jax.grad(lambda r: jnp.sum(loop(r) * ct))(r)
    assert rel(got, want) < 1e-5
    # one matrix without a token axis is the same function
    assert rel(hc.sinkhorn(r[..., 0], 20, 1e-6), loop(r)[..., 0]) < 1e-6


def test_fewer_iterations_leave_a_larger_marginal_error():
    r = 2.0 * jnp.eye(N)[:, :, None] + 0.5 * jax.random.normal(
        jax.random.PRNGKey(3), (N, N, 256))
    err = lambda it: float(jnp.max(jnp.abs(
        jnp.sum(hc.sinkhorn(r, it, 1e-6), axis=0) - 1.0)))
    assert err(5) > err(10) > err(20) > err(40)


# ----------------------------------------------------------------------
# the maps and the mixing
# ----------------------------------------------------------------------
@pytest.mark.parametrize("lead", [(T,), (2, 25)], ids=["tokens", "batch"])
def test_maps_match_the_reference_token_by_token(lead):
    lv = leaves()
    x = jax.random.normal(jax.random.PRNGKey(4), (N,) + lead + (C,))
    with jax.default_matmul_precision("highest"):
        m = hc.maps(x, lv, iters=20, eps=1e-6)
        pre, post, res = ref_maps(lv, x.reshape(N, T, C))
    assert m.pre.shape == (N,) + lead and m.res.shape == (N, N) + lead
    assert rel(m.pre.reshape(N, T), pre) < 1e-5
    assert rel(m.post.reshape(N, T), post) < 1e-5
    assert rel(m.res.reshape(N, N, T), res) < 1e-5
    assert 0.0 < float(jnp.min(m.pre)) and float(jnp.max(m.pre)) < 1.0
    assert 0.0 < float(jnp.min(m.post)) and float(jnp.max(m.post)) < 2.0
    worst = max(float(jnp.max(jnp.abs(jnp.sum(m.res, axis=a) - 1.0)))
                for a in (0, 1))
    assert float(m.marginal_err) == pytest.approx(worst) and worst < 1e-5


def test_the_input_dependent_part_moves_every_map_by_tenths():
    lv = leaves()
    x = jax.random.normal(jax.random.PRNGKey(5), (N, T, C))
    m = hc.maps(x, lv, iters=20, eps=1e-6)
    still = hc.maps(x, {**lv, **{k: jnp.zeros_like(v) for k, v in lv.items()
                                 if k.startswith("phi_")}}, iters=20,
                    eps=1e-6)
    for a, b in ((m.pre, still.pre), (m.post, still.post),
                 (m.res, still.res)):
        moved = jnp.abs(a - b)
        assert 0.03 < float(jnp.mean(moved)) and float(jnp.max(moved)) < 1.0
        # without phi a map is the same for every token
        assert float(jnp.max(jnp.std(b, axis=-1))) < 1e-6


def test_the_maps_gradient_matches_the_reference(monkeypatch):
    lv = leaves()
    x = jax.random.normal(jax.random.PRNGKey(6), (N, T, C))
    w = [jax.random.normal(jax.random.PRNGKey(7 + i), s)
         for i, s in enumerate([(N, T), (N, T), (N, N, T)])]
    score = lambda maps3: sum(jnp.sum(a * b) for a, b in zip(maps3, w))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda lv, x: score(hc.maps(
            x, lv, iters=20, eps=1e-6)[:3]), argnums=(0, 1))(lv, x)
        want = jax.grad(lambda lv, x: score(ref_maps(lv, x)),
                        argnums=(0, 1))(lv, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(a, b) < 2e-5


def test_contract_and_expand_are_the_two_sums():
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(ks[0], (N, 2, 7, C))
    y = jax.random.normal(ks[1], (2, 7, C))
    pre, post = (jax.random.uniform(k, (N, 2, 7)) for k in ks[2:4])
    res = jax.random.uniform(ks[4], (N, N, 2, 7))
    assert rel(hc.contract(pre, x),
               jnp.einsum("ibt,ibtc->btc", pre, x)) < 1e-6
    want = jnp.einsum("jibt,ibtc->jbtc", res, x) \
        + jnp.einsum("jbt,btc->jbtc", post, y)
    assert rel(hc.expand(res, post, x, y), want) < 1e-6


def test_identity_mixing_and_one_hot_maps_are_the_plain_residual():
    """``H_res = I``, ``H_pre`` and ``H_post`` one-hot on stream 2: that
    stream is ``x + F(x)`` and the others pass untouched."""
    x = jax.random.normal(jax.random.PRNGKey(9), (N, T, C))
    f = lambda u: jnp.tanh(u) * 3.0
    e2 = jnp.zeros((N, T)).at[2].set(1.0)
    eye = jnp.broadcast_to(jnp.eye(N)[:, :, None], (N, N, T))
    out = hc.expand(eye, e2, x, f(hc.contract(e2, x)))
    assert np.array_equal(np.asarray(out[2]), np.asarray(x[2] + f(x[2])))
    for i in (0, 1, 3):
        assert np.array_equal(np.asarray(out[i]), np.asarray(x[i]))


def test_everything_is_float32_whatever_comes_in():
    lv = leaves()
    x = jax.random.normal(jax.random.PRNGKey(10), (N, T, C)).astype(
        jnp.bfloat16)
    m = hc.maps(x, lv, iters=20, eps=1e-6)
    assert {a.dtype for a in m} == {jnp.dtype(jnp.float32)}
    jaxpr = str(jax.make_jaxpr(lambda x: hc.maps(x, lv, iters=3,
                                                 eps=1e-6).res)(x))
    assert "precision=HIGHEST" in jaxpr or "Precision.HIGHEST" in jaxpr
    assert "pallas_call" not in jaxpr


def test_scopes_name_the_maps_and_the_mixing():
    lv = leaves()
    x = jax.random.normal(jax.random.PRNGKey(11), (N, T, C))

    def f(x):
        with jax.named_scope("mhc"):
            m = hc.maps(x, lv, iters=2, eps=1e-6)
            return hc.expand(m.res, m.post, x, hc.contract(m.pre, x))

    stacks = {str(e.source_info.name_stack)
              for e in jax.make_jaxpr(f)(x).jaxpr.eqns}
    assert {"mhc/mhc_maps", "mhc/mhc_mix"} <= stacks
    assert all(s.startswith("mhc/mhc_m") for s in stacks)


# ----------------------------------------------------------------------
# the kernels (interpret mode) against the jax.numpy path
# ----------------------------------------------------------------------
CK = 256                       # a width the kernels take
#: one and a half tiles of tokens: the second tile is ragged
TK = hc._TILE + hc._TILE // 2
LEADS = {"tokens": (TK,), "batch": (2, TK // 2)}
LEAVES = ("phi_pre", "phi_post", "phi_res", "a_pre", "a_post", "a_res",
          "b_pre", "b_post", "b_res")


def wide_leaves(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    phi = lambda k, m: jax.random.normal(k, (N * CK, m)) * (N * CK) ** -0.5
    return {"phi_pre": phi(ks[0], N), "phi_post": phi(ks[1], N),
            "phi_res": phi(ks[2], N * N),
            "a_pre": jnp.full((1,), 0.5), "a_post": jnp.full((1,), 0.5),
            "a_res": jnp.full((1,), 0.5),
            "b_pre": 0.3 * jax.random.normal(ks[3], (N,)),
            "b_post": 0.3 * jax.random.normal(ks[4], (N,)),
            "b_res": jnp.eye(N) + 0.3 * jax.random.normal(ks[5], (N, N))}


def sub_layer(lv, x, y0, w):
    """A sub-layer as the model runs it, with ``F(u) = tanh(u w) + y0``
    so that ``y`` has a gradient of its own."""
    u, m, xt = hc.pre(x, lv, iters=20, eps=1e-6)
    return hc.expand(m.res, m.post, xt, jnp.tanh(u @ w) + y0), u


@pytest.fixture(scope="module", params=sorted(LEADS))
def both_paths(request):
    """``{what: (kernels, jax.numpy)}`` for one leading shape: ``u``,
    the next streams, and a scalar loss's gradients."""
    lead = LEADS[request.param]
    lv = wide_leaves()
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    # streams of unlike scale, as a trained model's are
    x = jax.random.normal(ks[0], (N,) + lead + (CK,)) \
        * jnp.arange(1.0, N + 1.0).reshape((N,) + (1,) * (len(lead) + 1))
    y0 = jax.random.normal(ks[1], lead + (CK,))
    w = jax.random.normal(ks[2], (CK, CK)) * CK ** -0.5
    ct = jax.random.normal(ks[3], x.shape)

    def run():
        # fresh functions: jit's cache does not know of the force
        sub = lambda *a: sub_layer(*a)
        loss = lambda lv, x, y0: jnp.sum(sub(lv, x, y0, w)[0] * ct)
        out, u = jax.jit(sub)(lv, x, y0, w)
        g_lv, g_x, g_y = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(lv, x, y0)
        return {"u": u, "streams": out, "dX": g_x, "dy": g_y, **g_lv}

    with jax.default_matmul_precision("highest"):
        want = run()
        with hc.force_mhc_impl("pallas_interpret"):
            assert hc.plan(N, TK, CK)["impl"] == "pallas_interpret"
            got = run()
    # the two lowerings sum in different orders: equal to the last bit
    # everywhere would mean one of them ran twice
    assert any(not np.array_equal(np.asarray(got[k]), np.asarray(want[k]))
               for k in want)
    return {k: (got[k], want[k]) for k in want}


@pytest.mark.parametrize("what", ("u", "streams", "dX", "dy") + LEAVES)
def test_kernels_match_the_jax_numpy_path(both_paths, what):
    got, want = both_paths[what]
    assert got.shape == want.shape and got.dtype == want.dtype
    # a gain's gradient is ONE number, a sum over tokens and entries that
    # cancels: either lowering is up to 4e-6 from a float64 evaluation
    assert rel(got, want) < (5e-5 if what.startswith("a_") else 1e-5)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _eqns(j)


def _model_sub_layer_grad(impl):
    """The jaxpr of the gradient of ``models/xing4_0.py:sub_layer`` (its
    ``jax.checkpoint`` with it) with respect to the leaves and the
    streams."""
    from federated_pytorch_test_tpu.models import xing4_0 as xing

    cfg = xing.Xing4(hidden_size=CK)
    p = {"hc_" + k: v for k, v in wide_leaves().items()}
    x = jax.random.normal(jax.random.PRNGKey(13), (N, 2, TK // 2, CK))
    ct = jax.random.normal(jax.random.PRNGKey(14), x.shape)

    def loss(p, x):
        out, _, _ = xing.sub_layer(cfg, p, lambda u: (jnp.tanh(u), None), x)
        return jnp.sum(out * ct)

    with hc.force_mhc_impl(impl):
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(p, x).jaxpr


def _stream_adds(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "add_any"
            and len(e.outvars[0].aval.shape) >= 3
            and e.outvars[0].aval.shape[0] == N
            and e.outvars[0].aval.shape[-1] == CK]


def test_the_streams_cotangent_is_written_once():
    """Every use of the streams in a sub-layer goes through ``pre``, so
    JAX has no cotangents of ``[n, ..., C]`` arrays to add; differentiating
    the ``jax.numpy`` lines it has many."""
    assert _stream_adds(_model_sub_layer_grad("pallas_interpret")) == []
    assert len(_stream_adds(_model_sub_layer_grad("xla"))) >= 3


def test_five_kernels_run_under_the_scopes_and_expand_is_not_run_again():
    """Forward ``pre`` and ``expand``; backward ``pre`` again (the
    rematerialised forward stops at the sub-layer's input) and the two
    rules' kernels, each under ``mhc`` and the scope of the call it
    answers."""
    stacks = [str(e.source_info.name_stack)
              for e in _eqns(_model_sub_layer_grad("pallas_interpret"))
              if e.primitive.name == "pallas_call"]
    assert len(stacks) == 5
    assert sum("rematted_computation" in s for s in stacks) == 1
    assert sorted(s.split("/")[-1] for s in stacks) == \
        ["mhc_maps"] * 3 + ["mhc_mix"] * 2
    assert all("mhc" in s.replace("jvp(mhc)", "mhc").split("/")
               for s in stacks)


@pytest.mark.parametrize("n,tokens,C,dtype,impl,why", [
    (4, 96, 256, jnp.float32, "pallas_interpret", "fits"),
    (4, 4096, 3584, jnp.float32, "pallas_interpret", "fits"),
    (4, 5, 128, jnp.float32, "pallas_interpret", "fits"),
    (4, 96, 24, jnp.float32, "xla", "stream width no multiple of 128"),
    (4, 96, 200, jnp.float32, "xla", "stream width no multiple of 128"),
    (4, 96, 256, jnp.bfloat16, "xla", "bfloat16 streams"),
    (5, 96, 256, jnp.float32, "xla",
     "the maps' columns exceed a pack's lanes"),
])
def test_plan_says_what_runs_and_why(n, tokens, C, dtype, impl, why):
    with hc.force_mhc_impl("pallas_interpret"):
        p = hc.plan(n, tokens, C, dtype)
    assert (p["impl"], p["why"]) == (impl, why)
    if impl != "xla":
        assert p["tile"] % 16 == 0 and p["vmem_bytes"] <= p["vmem_budget"]
    # without a TPU, and without the force, the jax.numpy lines run
    assert hc.plan(n, tokens, C, dtype)["impl"] == "xla"


def test_a_shape_the_kernels_do_not_take_runs_the_jax_numpy_lines():
    """The tiny widths of the model's tests under the force: no kernel in
    the program, the same numbers as ``maps`` / ``contract``."""
    lv = leaves()
    x = jax.random.normal(jax.random.PRNGKey(15), (N, T, C))
    with hc.force_mhc_impl("pallas_interpret"):
        jaxpr = jax.make_jaxpr(lambda x: hc.pre(x, lv, iters=3, eps=1e-6))(x)
        u, m, xt = hc.pre(x, lv, iters=20, eps=1e-6)
    assert "pallas_call" not in str(jaxpr)
    want = hc.maps(x, lv, iters=20, eps=1e-6)
    assert np.array_equal(np.asarray(u),
                          np.asarray(hc.contract(want.pre, x)))
    assert np.array_equal(np.asarray(m.res), np.asarray(want.res))
    assert xt is x


def test_phi_is_cut_into_pieces_xla_cannot_fold_away():
    """A float32 -> bfloat16 -> float32 round trip is the identity to
    the TPU compiler (``xla_allow_excess_precision``): cut by rounding,
    the rest after the first piece would be zero there.  The pieces are
    cut by masking bits, and add up to ``phi`` exactly."""
    phi = jax.random.normal(jax.random.PRNGKey(16), (N * CK, 24))
    cut = lambda phi: hc._phi_stacks(phi, N, CK, [(0, 1, 2)])[0]
    jaxpr = str(jax.make_jaxpr(cut)(phi))
    assert jaxpr.count("bitcast_convert_type") == 4
    # nothing is converted back from bfloat16
    assert "convert_element_type[new_dtype=float32" not in jaxpr
    stack = np.asarray(cut(phi).astype(jnp.float32))      # [n, 128, C]
    whole = sum(stack[:, k * 24:(k + 1) * 24] for k in range(3))
    want = np.asarray(phi).reshape(N, CK, 24).transpose(0, 2, 1)
    assert np.array_equal(whole, want)
    assert np.all(stack[:, 72:] == 0.0)


def test_compiled_kernels_match_the_jax_numpy_path_on_the_chip():
    """What interpret mode cannot tell: Mosaic's and the TPU compiler's
    own arithmetic (with the leaves traced, so nothing of theirs is
    folded on the host), at the published width.  Runs via
    ``FEDTPU_TEST_TPU=1 pytest tests/test_hyper_connections.py`` on a TPU
    host."""
    if jax.default_backend() != "tpu":
        pytest.skip("real TPU backend required (FEDTPU_TEST_TPU=1)")
    Cw, Tw = 3584, 512
    ks = jax.random.split(jax.random.PRNGKey(17), 8)
    phi = lambda k, m: jax.random.normal(k, (N * Cw, m)) * (N * Cw) ** -0.5
    lv = {**wide_leaves(), "phi_pre": phi(ks[0], N),
          "phi_post": phi(ks[1], N), "phi_res": phi(ks[2], N * N)}
    x = jax.random.normal(ks[3], (N, Tw, Cw)) \
        * jnp.arange(1.0, N + 1.0).reshape(N, 1, 1)
    w = jax.random.normal(ks[4], (Cw,))
    ct = jax.random.normal(ks[5], x.shape)

    def run(impl):
        def sub(lv, x):         # fresh: jit's cache does not know of the force
            u, m, xt = hc.pre(x, lv, iters=20, eps=1e-6)
            return hc.expand(m.res, m.post, xt, jnp.tanh(u) * w)

        with hc.force_mhc_impl(impl):
            out = jax.jit(sub)(lv, x)
            g = jax.jit(jax.grad(lambda lv, x: jnp.sum(sub(lv, x) * ct),
                                 argnums=(0, 1)))(lv, x)
        return [out] + jax.tree.leaves(g)

    for got, want in zip(run("pallas"), run("xla")):
        assert rel(got, want) < 1e-5
