"""ZAYA1-8B (``zaya``) at tiny widths on the CPU: the program against the
plain reference (``benchmarks/reference/zaya.py``): logits, loss, every
block kind's gradient, the tied embedding's two sources; what compressed
convolutional attention promises (causality through the shift, both
convolutions and the core; the previous token's values in the second
half of the value heads; a partial rotary); the router's rule and its
state through the depth; the expert-parallel shares against the uncut
layer; the blocks and the parameter count at the published cut; the core
through the attention kernels in interpret mode at 4 query heads a key
head of 128; and FedAvg rounds of ``LMTrainer`` against
``decoder_round.run_rounds``.
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

from benchmarks.reference import decoder_round, zaya as ref  # noqa: E402
from federated_pytorch_test_tpu.data.tokens import FederatedTokens  # noqa: E402
from federated_pytorch_test_tpu.models import (  # noqa: E402
    MODEL_REGISTRY,
    get_model,
)
from federated_pytorch_test_tpu.models import decoder, zaya  # noqa: E402
from federated_pytorch_test_tpu.models.decoder import weighted_mean  # noqa: E402
from federated_pytorch_test_tpu.ops import moe as moelib  # noqa: E402
from federated_pytorch_test_tpu.ops.flash_attention import (  # noqa: E402
    force_attn_impl,
)
from federated_pytorch_test_tpu.train import (  # noqa: E402
    FedAvg,
    FederatedConfig,
    LMTrainer,
)
from federated_pytorch_test_tpu.utils.tree import get_by_path  # noqa: E402

ROPE = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"}, "rope_type": "default"}
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
            rope_parameters=ROPE, moe_intermediate_size=24, num_experts=8,
            num_experts_per_tok=1, router_hidden_size=16, layers=3,
            experts_held=4, ep_rank=1, vocab_rows=64)
#: what the reference reads beside the widths (the published value)
REF_CFG = dict(TINY, rms_norm_eps=1e-5)
T = 40
#: blocks of the three-layer model
EMBED, CCA1, MOE1, NORM = 0, 3, 4, 7


def tiny_model(**kw):
    # matrices seeded at 0.2 where the published widths take 0.02: at 32
    # wide a sub-layer's output is then as large beside the embedding as
    # at 2,048, and a misplaced scale or bias shows
    return get_model("zaya", **{
        **TINY, "attn_block": 16, "init_scale": 0.2, "bias_scale": 0.05,
        "dtype": jnp.float32, **kw})


@pytest.fixture(scope="module")
def setup():
    model = tiny_model()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, 64)
    x, y = ids[:, :-1], ids[:, 1:]
    params, stats = model.init_variables(jax.random.PRNGKey(0), x)
    assert stats == {}
    return model, params, x, y


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


# ----------------------------------------------------------------------
# the program against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("taps", [(2, 2), (3, 1), (1, 4)])
def test_cca_mixer_matches_reference(setup, taps):
    """The whole mixer on one sequence, at the published two taps and at
    tap counts that tell the two convolutions apart."""
    k0, k1 = taps
    model = tiny_model(cca_time0=k0, cca_time1=k1)
    p = model.init_variables(jax.random.PRNGKey(5), jnp.zeros(
        (1, 8), jnp.int32))[0]["layer1_mixer"]
    assert p["conv0"].shape == (k0, 48) and p["conv1"].shape == (k1, 6, 8, 8)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got = zaya.cca_attention(model, p, x)
        want = ref.cca({**REF_CFG, "cca_time0": k0, "cca_time1": k1}, p, x)
    assert got.shape == (T, 32) and rel(got, want) < 2e-5


def test_expert_sub_layer_matches_reference(setup):
    model, params, _, _ = setup
    p = params["layer1_moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (1, T, 32))
    state = jax.random.normal(jax.random.PRNGKey(4), (T, 16))
    with jax.default_matmul_precision("highest"):
        flat = decoder.rms_norm(h, p["norm"], 1e-5).reshape(T, 32)
        y, r, s = zaya.expert_layer(model, p, flat, state)
        got = zaya.merge(p, h[0], y)
        want, want_s = ref.expert_sub_layer(REF_CFG, p, h[0], state)
    assert 0 < int(r.pairs_local) < T and int(r.dropped) == 0
    assert rel(got, want) < 2e-5 and rel(s, want_s) < 2e-5


def test_model_logits_and_loss_match_reference(setup):
    model, params, x, y = setup
    with jax.default_matmul_precision("highest"):
        logits, aux = model.apply({"params": params}, x)
        per_seq, aux_l = model.apply({"params": params}, x, y)
    assert logits.shape == (2, T, 64)
    assert int(aux["moe_dropped"]) == 0 and int(aux["moe_pairs_local"]) > 0
    # every pair that can exist has a row: T rows a layer
    assert int(aux["moe_rows"]) == 3 * 2 * T
    assert float(aux["router_state_rms"]) > 0
    assert float(aux_l["moe_weight_sum"]) == float(aux["moe_weight_sum"])
    # one expert a token: a pair's weight is a probability among eight
    mean_w = float(aux["moe_weight_sum"]) / int(aux["moe_pairs_local"])
    assert 1 / 8 < mean_w < 1.0
    for b in range(2):
        loss, want, _ = ref.loss_and_grad(REF_CFG, params, [], x[b], y[b])
        assert rel(logits[b], want["logits"]) < 2e-5
        assert float(per_seq[b]) == pytest.approx(float(loss), rel=1e-5)


def _block_paths(model, block):
    lo, hi = model.train_order_block_ids()[block]
    return model.param_order()[lo:hi + 1]


def _router_paths(model):
    return [f"layer1_moe/{leaf}" for leaf, _, _ in
            model._spec("layer1_moe")[:zaya.ROUTER_LEAVES - 1]]


@pytest.fixture(scope="module")
def grads(setup):
    """The first sequence's gradient of every leaf the tests below read,
    the program's and the reference's, each ONE jitted program.  Op by
    op the model's gradient is hundreds of small CPU compiles a test, and
    a reference program for each set of leaves is as many large ones,
    each holding memory maps until the file ends (``conftest.py``)."""
    model, params, x, y = setup
    paths = [p for b in (EMBED, CCA1, MOE1, NORM)
             for p in _block_paths(model, b)] + _router_paths(model)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: weighted_mean(
            model.apply({"params": p}, x[:1], y[:1])[0])))(params)
    _, _, want = ref.loss_and_grad(REF_CFG, params, paths, x[0], y[0])
    return got, dict(zip(paths, want))


@pytest.mark.parametrize("block", [EMBED, CCA1, MOE1, NORM],
                         ids=["embed", "cca", "experts", "norm"])
def test_block_gradient_matches_reference(setup, grads, block):
    got, want = grads
    for path in _block_paths(setup[0], block):
        assert float(jnp.max(jnp.abs(want[path]))) > 0, path
        assert rel(get_by_path(got, path), want[path]) < 2e-4, path


def test_router_leaves_gradient_matches_reference(setup, grads):
    """The router lies in no block, yet an upstream block's gradient
    passes through it: its own gradient against the reference's, the
    state's scale of a later layer among it (layer 0's meets zeros)."""
    got, want = grads
    paths = _router_paths(setup[0])
    assert paths[2] == "layer1_moe/router_state_scale"
    for path in paths:
        assert float(jnp.max(jnp.abs(want[path]))) > 0, path
        assert rel(get_by_path(got, path), want[path]) < 2e-4, path
    # the balancing bias is a buffer: no gradient reaches it
    assert not np.any(np.asarray(got["layer1_moe"]["router_bias"]))
    assert not np.any(np.asarray(got["layer0_moe"]["router_state_scale"]))


def _loss_of_two_embeddings(model, params, ids, labels):
    """The model's loss with the gathered embedding and the head's
    matrix as two arguments, composed from the module's own pieces: the
    head's part through ``ops/head_loss.py``, as the cell runs it."""
    norm = lambda a: decoder.rms_norm(a, params["final_norm"]["norm"],
                                      model.rms_norm_eps)

    def loss(gathered, head):
        x, state = gathered[ids], None
        for i in range(model.layers):
            x, state, _, _ = zaya.decoder_layer(
                model, params[f"layer{i}_mixer"], params[f"layer{i}_moe"], x,
                state)
        return jnp.mean(decoder.head_losses(model, norm, x, head, labels,
                                            contract=1))
    return loss


def test_the_tied_embedding_s_gradient_has_two_sources(setup, grads):
    model, params, x, y = setup
    emb = params["embed"]["embedding"]
    got, want = grads
    whole, want = got["embed"]["embedding"], want["embed/embedding"]
    with jax.default_matmul_precision("highest"):
        loss = _loss_of_two_embeddings(model, params, x[:1], y[:1])
        assert float(jax.jit(loss)(emb, emb)) == pytest.approx(float(
            weighted_mean(jax.jit(lambda p: model.apply(
                {"params": p}, x[:1], y[:1])[0])(params))), rel=1e-6)
        gather_part, head_part = jax.jit(jax.grad(loss, argnums=(0, 1)))(
            emb, emb)
    assert rel(whole, want) < 2e-4
    assert rel(gather_part + head_part, whole) < 1e-5
    # the gather reaches the rows the sequence holds, the head every row
    seen = np.zeros(64, bool)
    seen[np.asarray(x[0])] = True
    rows = lambda g: np.any(np.asarray(g) != 0, axis=1)
    assert np.array_equal(rows(gather_part), seen) and rows(head_part).all()
    assert float(jnp.max(jnp.abs(head_part))) > 0.01 * float(
        jnp.max(jnp.abs(gather_part))) > 0


# ----------------------------------------------------------------------
# what compressed convolutional attention promises
# ----------------------------------------------------------------------
def test_the_model_is_causal(setup):
    """Changing token ``t`` leaves every output before ``t`` as it was:
    through the value shift, both convolutions, the core, and the
    router's state (which is per token)."""
    model, params, x, _ = setup
    t = 17
    other = x.at[:, t].set((x[:, t] + 1) % 64)
    a, _ = model.apply({"params": params}, x)
    b, _ = model.apply({"params": params}, other)
    assert np.array_equal(np.asarray(a[:, :t]), np.asarray(b[:, :t]))
    assert np.all(np.any(np.asarray(a[:, t:]) != np.asarray(b[:, t:]),
                         axis=-1))


def test_the_mixer_alone_is_causal_with_longer_taps():
    model = tiny_model(cca_time0=3, cca_time1=3)
    p = model.init_variables(jax.random.PRNGKey(5), jnp.zeros(
        (1, 8), jnp.int32))[0]["layer0_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(2), (T, 32))
    t = 11
    other = x.at[t].add(1.0)
    a, b = zaya.cca_attention(model, p, x), zaya.cca_attention(model, p,
                                                                other)
    assert np.array_equal(np.asarray(a[:t]), np.asarray(b[:t]))
    assert np.all(np.any(np.asarray(a[t:t + 6]) != np.asarray(b[t:t + 6]),
                         axis=-1))


def test_the_second_half_of_the_value_heads_is_the_previous_token_s(
        setup, monkeypatch):
    """Head 0's values are ``x_t W_v1``, head 1's ``x_{t-1} W_v2`` with
    zeros at the start: read from what the core is handed."""
    model, params, _, _ = setup
    p = params["layer0_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(2), (T, 32))
    seen = {}

    def core(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, kw=kw)
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)

    monkeypatch.setattr(zaya, "causal_attention", core)
    with jax.default_matmul_precision("highest"):
        zaya.cca_attention(model, p, x)
        now, before = x @ p["v1_proj"], x @ p["v2_proj"]
    v = seen["v"]
    assert v.shape == (T, 2, 8) and seen["q"].shape == (T, 2, 2, 8)
    assert rel(v[:, 0], now) < 1e-6
    assert rel(v[1:, 1], before[:-1]) < 1e-6
    assert not np.any(np.asarray(v[0, 1]))
    assert seen["kw"]["scope"] == "cca_attn/cca_core"
    # q and k reach the core on the sphere: sqrt(d) long, k times tau,
    # q times the softmax scale 1 / sqrt(d)
    norms = lambda a: np.asarray(jnp.sqrt(jnp.sum(a * a, -1)))
    np.testing.assert_allclose(norms(seen["q"]), 1.0, rtol=1e-4)
    np.testing.assert_allclose(
        norms(seen["k"]), np.sqrt(8.0) * np.abs(np.asarray(
            p["temperature"]))[None, :] * np.ones((T, 1)), rtol=1e-4)


def test_the_partial_rotary_leaves_the_second_half_of_a_head_alone(
        setup, monkeypatch):
    """``partial_rotary_factor`` 0.5: dims ``d / 2 ..`` of every query
    and key head reach the core as they left the norm; position 0 is
    not turned at all, later positions are."""
    model, params, _, _ = setup
    p = params["layer0_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(2), (T, 32))
    seen = []

    def core(q, k, v, **kw):
        seen.append((q, k))
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)

    monkeypatch.setattr(zaya, "causal_attention", core)
    zaya.cca_attention(model, p, x)
    monkeypatch.setattr(zaya, "apply_rope", lambda t, cos, sin: t)
    zaya.cca_attention(model, p, x)
    (q, k), (q_plain, k_plain) = seen
    for turned, plain in ((q, q_plain), (k, k_plain)):
        assert np.array_equal(np.asarray(turned[..., 4:]),
                              np.asarray(plain[..., 4:]))
        assert np.array_equal(np.asarray(turned[0]), np.asarray(plain[0]))
        assert np.all(np.any(np.asarray(turned[1:, ..., :4])
                             != np.asarray(plain[1:, ..., :4]), axis=-1))
    assert model.rope_theta() == 5e6 and tiny_model(
        rope_parameters=None).rope_theta() == 5e6


# ----------------------------------------------------------------------
# the router
# ----------------------------------------------------------------------
def test_router_rule_chooses_by_probability_plus_bias_and_weighs_without():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.1, 0.2, 0.3]])
    probs = jax.nn.softmax(logits, -1)
    w, e = moelib.softmax_bias_router_weights(logits, jnp.zeros(4), 1)
    assert e.dtype == jnp.int32 and e.tolist() == [[0], [3]]
    assert rel(w[:, 0], jnp.asarray([probs[0, 0], probs[1, 3]])) < 1e-6
    # a bias moves the choice and not the weight; nothing is renormalised
    bias = jnp.asarray([-1.0, 0.0, 0.0, 0.0])
    w, e = moelib.softmax_bias_router_weights(logits, bias, 1)
    assert e.tolist() == [[1], [3]]
    assert float(w[0, 0]) == pytest.approx(float(probs[0, 1]))
    w2, e2 = moelib.softmax_bias_router_weights(logits, bias, 2)
    assert e2.tolist() == [[1, 2], [3, 2]] and float(jnp.sum(w2[0])) < 0.5
    # the weight carries the router's gradient; the bias gets none
    g_logits, g_bias = jax.grad(
        lambda l, b: jnp.sum(moelib.softmax_bias_router_weights(l, b, 1)[0]),
        argnums=(0, 1))(logits, bias)
    assert float(jnp.max(jnp.abs(g_logits))) > 0.01
    assert not np.any(np.asarray(g_bias))


def test_the_router_s_gradient_reaches_its_first_matrix(setup):
    model, params, _, _ = setup
    p = params["layer1_moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 32))
    state = jax.random.normal(jax.random.PRNGKey(4), (T, 16))

    def out(leaves):
        y, _, _ = zaya.expert_layer(model, {**p, **leaves}, x, state)
        return jnp.sum(y * y)

    g = jax.grad(out)({k: p[k] for k in ("router_fc1", "router_down",
                                         "router_state_scale")})
    for k, v in g.items():
        assert float(jnp.max(jnp.abs(v))) > 0, k


def test_the_router_s_state_reaches_the_next_layer(setup):
    """Zeroing layer 1's ``gamma`` changes the router logits of layer 1
    and not of layer 0; the state a layer hands on is its sum before
    the norm, whatever it was handed."""
    model, params, _, _ = setup
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 32))
    p0, p1 = params["layer0_moe"], params["layer1_moe"]
    zeroed = {**p1, "router_state_scale": jnp.zeros_like(
        p1["router_state_scale"])}
    l0, s0 = zaya.router_logits(model, p0, x, None)
    l0_again, _ = zaya.router_logits(model, p0, x, jnp.zeros((T, 16)))
    assert np.array_equal(np.asarray(l0), np.asarray(l0_again))
    l1, s1 = zaya.router_logits(model, p1, x, s0)
    l1_cut, s1_cut = zaya.router_logits(model, zeroed, x, s0)
    assert rel(l1_cut, l1) > 1e-2
    assert rel(s1 - s1_cut, p1["router_state_scale"] * s0) < 1e-5
    # through the whole model: layer 0's counts stay, the logits move
    model_cut = {**params, "layer1_moe": zeroed}
    a, _ = model.apply({"params": params}, jnp.zeros((1, T), jnp.int32)
                       + jnp.arange(T) % 64)
    b, _ = model.apply({"params": model_cut}, jnp.zeros((1, T), jnp.int32)
                       + jnp.arange(T) % 64)
    assert rel(b, a) > 1e-4


@pytest.mark.parametrize("seed", [0, 1, 3])
def test_router_balance_sets_the_bias_by_the_load(seed):
    """Zipf tokens, two clients: a seeded bias leaves the routing to the
    seeded router and the stream's commonest tokens; the load-driven
    update evens the held experts' load and brings the held share toward
    a half, in every layer, and changes no other leaf."""
    model = tiny_model(hidden_size=64, vocab_rows=512, ep_rank=0)
    data = FederatedTokens(2, 2, 2, 256, 512, seed, head=64)
    ids = jnp.asarray(data.train_shards_raw()[0].reshape(-1, 256))
    params, _ = model.init_variables(jax.random.PRNGKey(seed), ids[:, :8])
    new = jax.jit(model.router_balance)(params, ids)
    assert sorted(new) == [f"layer{i}_moe" for i in range(3)]
    assert all(list(v) == ["router_bias"] and v["router_bias"].shape == (8,)
               for v in new.values())
    # sign steps of 1e-3 from zeros
    grid = np.asarray(new["layer1_moe"]["router_bias"]) / 1e-3
    assert np.allclose(grid, np.round(grid), atol=1e-3) \
        and np.max(np.abs(grid)) <= 256
    balanced = {b: {**v, **new.get(b, {})} for b, v in params.items()}
    _, seeded_aux = model.apply({"params": params}, ids)
    _, aux = model.apply({"params": balanced}, ids)
    share = lambda a: int(a["moe_pairs_local"]) / int(a["moe_rows"])
    assert float(aux["moe_load_max_over_mean"]) \
        < float(seeded_aux["moe_load_max_over_mean"])
    assert abs(share(aux) - 0.5) < 0.07
    if seed != 3:           # a seeded router can land near a half by luck
        assert abs(share(seeded_aux) - 0.5) > 0.07
    assert float(aux["moe_load_max_over_mean"]) < 2.0
    # the chosen expert's probability stays above a flat router's
    assert float(aux["moe_weight_sum"]) / int(aux["moe_pairs_local"]) > 1 / 8


# ----------------------------------------------------------------------
# blocks, counts
# ----------------------------------------------------------------------
def test_blocks_come_from_the_layer_list():
    model = tiny_model(layers=6)
    ids, order = model.train_order_block_ids(), model.param_order()
    assert MODEL_REGISTRY["zaya"] is zaya.Zaya
    assert len(ids) == 14 == len(model.block_kinds())
    assert model.block_kinds() == ["embed"] + ["cca", "moe"] * 6 + ["norm"]
    assert ids[0] == [0, 0] and ids[-1] == [len(order) - 1] * 2
    assert order[0] == "embed/embedding" and order[-1] == "final_norm/norm"
    for (lo, hi), name in zip(ids, model.block_names()):
        assert all(p.startswith(name + "/") for p in order[lo:hi + 1])
    # there is no head: the embedding is the head's matrix
    assert not any("head" in p or "kernel" in p for p in order)
    # every sub-layer's block carries its four residual-scale leaves
    for b, kind in enumerate(model.block_kinds()):
        lo, hi = ids[b]
        own = [p.rsplit("/", 1)[1] for p in order[lo:hi + 1]]
        assert (own[-4:] == ["res_scale", "res_bias", "out_scale",
                             "out_bias"]) == (kind in ("cca", "moe")), b
    # the blocks tile the parameters but for each expert layer's router
    covered = {i for lo, hi in ids for i in range(lo, hi + 1)}
    outside = [order[i] for i in range(len(order)) if i not in covered]
    assert len(outside) == 6 * zaya.ROUTER_LEAVES == 66
    assert all("/router_" in p for p in outside)
    assert not any("/router_" in order[i] for i in covered)
    assert outside[:zaya.ROUTER_LEAVES] == [
        "layer0_moe/" + leaf for leaf in (
            "router_down", "router_down_bias", "router_state_scale",
            "router_norm", "router_fc1", "router_fc1_bias", "router_fc2",
            "router_fc2_bias", "router_out", "router_out_bias",
            "router_bias")]


def test_an_odd_count_of_value_heads_is_refused():
    with pytest.raises(ValueError, match="num_key_value_heads"):
        tiny_model(num_attention_heads=3, num_key_value_heads=3
                   ).init_variables(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32))


def test_published_widths_give_the_issue_s_parameter_counts():
    """From shapes alone, leaf by leaf as ISSUE 38 counts them."""
    full = get_model("zaya")
    shapes = jax.eval_shape(lambda: full.init_variables(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))[0]
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    part = lambda t, pick: count({k: v for k, v in t.items() if pick(k)})
    mixer, moe = shapes["layer0_mixer"], shapes["layer3_moe"]
    merge = ("res_scale", "res_bias", "out_scale", "out_bias")
    assert part(mixer, lambda k: k in merge) == 8_192 == part(
        moe, lambda k: k in merge)
    assert part(mixer, lambda k: k.startswith("conv0")) == 3_840
    assert part(mixer, lambda k: k.startswith("conv1")) == 328_960
    assert part(mixer, lambda k: k not in merge) == 5_577_730
    assert part(moe, lambda k: k.startswith("router")) == 660_768
    assert part(moe, lambda k: k.startswith("experts")) == 8 * 12_582_912
    assert count(shapes["embed"]) == 32_784 * 2_048 == 67_141_632
    assert count(shapes["final_norm"]) == 2_048
    assert count(mixer) + count(moe) == 106_920_226
    assert count(shapes) == 708_665_036
    order, ids = full.param_order(), full.train_order_block_ids()
    size = lambda b: sum(int(np.prod(get_by_path(shapes, p).shape))
                         for p in order[ids[b][0]:ids[b][1] + 1])
    assert (size(0), size(6), size(11), size(13)) == (
        67_141_632, 100_673_536, 5_585_922, 2_048)
    # one query block of all four heads of a group: 1,024 rows a grid step
    from federated_pytorch_test_tpu.ops.flash_attention import plan
    with force_attn_impl("pallas_interpret"):
        assert full.impl_fields(4096) == {"attn_impl": "pallas_interpret",
                                          "head_impl": "fused"}
        p = plan(4096, 2, 4, 128, jnp.bfloat16)
    assert (p["block_q"], p["pad_k"], p["impl"]) == (256, 0,
                                                     "pallas_interpret")
    assert full.impl_fields(4096) == {"attn_impl": "xla",     # the CPU
                                      "head_impl": "fused"}


# ----------------------------------------------------------------------
# the attention core as a kernel pair (interpret mode) against the XLA path
# ----------------------------------------------------------------------
def test_model_through_the_attention_kernels_matches_the_xla_path():
    """8 query heads on 2 key/value heads of 128 (``rep`` 4, no key
    padding) over a sequence of three key blocks: logits and the CCA
    block's gradient, the convolutions' and temperatures' among it."""
    model = tiny_model(head_dim=128, num_attention_heads=8,
                       num_key_value_heads=2, attn_block=128, layers=2,
                       init_scale=0.05)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 385), 0, 64)
    x, y = ids[:, :-1], ids[:, 1:]
    params, _ = model.init_variables(jax.random.PRNGKey(0), x[:, :8])
    lo, hi = model.train_order_block_ids()[1]
    paths = model.param_order()[lo:hi + 1]

    def run(impl):
        with force_attn_impl(impl), jax.default_matmul_precision("highest"):
            assert model.impl_fields(384) == {"attn_impl": impl,
                                              "head_impl": "fused"}
            # fresh functions under each implementation: one jitted
            # program each, where op by op they are hundreds of compiles
            logits, _ = jax.jit(lambda p: model.apply({"params": p}, x))(
                params)
            grads = jax.jit(jax.grad(lambda p: weighted_mean(
                model.apply({"params": p}, x, y)[0])))(params)
        return logits, [get_by_path(grads, path) for path in paths]

    (logits, grads), (want, want_grads) = run("pallas_interpret"), run("xla")
    assert rel(logits, want) < 2e-5
    for path, g, w in zip(paths, grads, want_grads):
        assert float(jnp.max(jnp.abs(w))) > 0, path
        assert rel(g, w) < 2e-4, path


# ----------------------------------------------------------------------
# the expert-parallel share
# ----------------------------------------------------------------------
def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """2 ranks x 4 experts of 8, as the deployment has 2 x 8 of 16: the
    ranks' sub-layer outputs, with what both compute alike (``s_r h +
    b_r`` and ``b_o``) counted once, are the uncut sub-layer of the
    reference; the router and its state are the same on both."""
    base = dict(TINY, experts_held=8, ep_rank=0)
    whole = tiny_model(**base)
    p = whole.init_variables(jax.random.PRNGKey(4), jnp.zeros(
        (1, 8), jnp.int32))[0]["layer1_moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (T, 32))
    state = jax.random.normal(jax.random.PRNGKey(6), (T, 16))
    share = lambda r: {k: (v[4 * r:4 * r + 4] if k.startswith("experts_")
                           else v) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, want_state = ref.expert_sub_layer({**REF_CFG, **base}, p, h,
                                                state)
        flat = decoder.rms_norm(h, p["norm"], 1e-5)
        alike = zaya.merge(p, h, jnp.zeros_like(h))
        total, pairs = alike, 0
        for rank in range(2):
            part = tiny_model(**dict(base, experts_held=4, ep_rank=rank))
            y, r, s = zaya.expert_layer(part, share(rank), flat, state)
            assert int(r.dropped) == 0 and rel(s, want_state) < 2e-5
            total = total + (zaya.merge(p, h, y) - alike)
            pairs += int(r.pairs_local)
            # the reference, given one rank's share, gives that rank's part
            mine, _ = ref.expert_sub_layer(
                {**REF_CFG, **base, "experts_held": 4, "ep_rank": rank},
                share(rank), h, state)
            assert rel(zaya.merge(p, h, y), mine) < 2e-5
    assert pairs == T                    # every token has one expert
    assert rel(total, want) < 2e-5


# ----------------------------------------------------------------------
# the normal path
# ----------------------------------------------------------------------
@pytest.mark.parametrize("block,kind", [(EMBED, "embed"), (4, "moe")],
                         ids=["tied_embedding", "experts"])
def test_two_fedavg_rounds_of_lm_trainer_match_the_round_reference(block,
                                                                   kind):
    """The fourth decoder through the same trainer: the tied embedding
    (a gradient from both ends) and an expert block (downstream of a
    router whose state comes from the layer before)."""
    model, ref_cfg = tiny_model(layers=2), dict(REF_CFG, layers=2)
    data = FederatedTokens(K=2, batch=2, samples_per_client=2, seq_len=24,
                           vocab=64, seed=3, head=16)
    cfg = FederatedConfig(K=2, Nloop=1, Nepoch=1, Nadmm=2, default_batch=2,
                          check_results=False, lr=1e-3, num_devices=1,
                          save_model=False)
    t = LMTrainer(model, cfg, data, FedAvg())
    t.block_ids, t.L = [t.block_ids[block]], 1
    lo, hi = t.block_ids[0]
    paths = t.order[lo:hi + 1]
    params = jax.tree.map(lambda a: np.asarray(a[0]), t.params0)
    xs, ys = t.data.train_shards_raw()
    seen = []
    with jax.default_matmul_precision("highest"):
        _, hist = t.run(log=lambda m: None, on_round=lambda s, r: seen.append(
            [np.asarray(get_by_path(s.params, p)) for p in paths]))
        want = decoder_round.run_rounds(
            ref, ref_cfg, params, paths, 1e-3,
            [[[(xs[k], ys[k])] for k in range(2)] for _ in range(2)])
    t.close()
    for got, w, rec in zip(seen, want, hist):
        assert rec["loss"] == pytest.approx(w["loss"], rel=1e-5)
        assert rec["block_kind"] == kind and rec["moe_dropped"] == 0
        assert rec["tokens"] == 2 * 2 * 24 and rec["attn_impl"] == "xla"
        assert rec["mtp_loss"] == 0.0 and rec["mhc_marginal_err"] == 0.0
        assert "mhc_impl" not in rec and "gdn_scan_impl" not in rec
        # two layers x 48 tokens x 2 clients, every pair with a row
        assert rec["moe_fill_share"] == pytest.approx(
            rec["moe_pairs_local"] / (2 * 2 * 48))
        assert 1 / 8 < rec["moe_top1_weight_mean"] < 1.0
        assert rec["router_state_rms"] > 0.1
        for path, leaf, ref_leaves in zip(paths, got, zip(*w["x"])):
            for k in range(2):
                assert np.max(np.abs(leaf[k] - ref_leaves[k])) < 1e-5, path
    assert all(np.array_equal(leaf[0], leaf[1]) for leaf in seen[-1])
    assert hist[1]["loss"] < hist[0]["loss"]
