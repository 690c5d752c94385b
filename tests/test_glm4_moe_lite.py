"""GLM-4.7-Flash (``glm4_moe_lite``) at tiny widths on the CPU: the
program against the plain reference (``benchmarks/reference/
glm4_moe_lite.py``), the expert-parallel shares against the uncut layer,
the router's selection bias, and the model through the attention kernels
in interpret mode.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.reference import glm4_moe_lite as ref  # noqa: E402
from federated_pytorch_test_tpu.models import get_model  # noqa: E402
from federated_pytorch_test_tpu.models import glm4_moe_lite as glm  # noqa: E402
from federated_pytorch_test_tpu.models.decoder import weighted_mean  # noqa: E402
from federated_pytorch_test_tpu.ops import moe as moelib  # noqa: E402
from federated_pytorch_test_tpu.ops.flash_attention import (  # noqa: E402
    force_attn_impl,
)
from federated_pytorch_test_tpu.utils.tree import get_by_path  # noqa: E402

TINY = dict(hidden_size=32, num_attention_heads=4, q_lora_rank=12,
            kv_lora_rank=8, qk_nope_head_dim=12, qk_rope_head_dim=4,
            v_head_dim=16, intermediate_size=80, moe_intermediate_size=24,
            n_routed_experts=16, num_experts_per_tok=3, layers=3,
            experts_held=4, ep_rank=1, vocab_rows=64)
#: what the reference reads beside the widths (the published values)
REF_CFG = dict(TINY, first_k_dense_replace=1, num_nextn_predict_layers=1,
               rope_theta=1e6, rms_norm_eps=1e-5, norm_topk_prob=True,
               routed_scaling_factor=1.8, mtp_loss_weight=0.1)
T = 40
#: blocks of the three-layer model
MLA1, MOE1, MOE2, HEAD, MTP_MIXER, MTP_MOE = 3, 4, 6, 7, 8, 9


def tiny_model(**kw):
    # a bias as large as the scores' spread at these widths (logits of
    # 0.02 x sqrt(32)), so that it changes choices and decides none alone
    return get_model("glm4_moe_lite", **{
        **TINY, "attn_block": 16, "pair_rows_factor": 8.0,
        "bias_scale": 0.02, "dtype": jnp.float32, **kw})


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
# the layers against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("block,system,reference", [
    ("layer1_mixer", glm.latent_attention, ref.mla),
    ("mtp_mixer", glm.latent_attention, ref.mla),
    ("layer0_mlp", glm.dense_mlp, lambda c, p, x: ref.swiglu(
        x, p["gate_proj"], p["up_proj"], p["down_proj"])),
    ("layer1_moe", lambda c, p, x: glm.expert_layer(c, p, x)[0],
     ref.experts),
])
def test_layer_matches_reference(setup, block, system, reference):
    model, params, _, _ = setup
    x = jax.random.normal(jax.random.PRNGKey(2), (T, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got = system(model, params[block], x)
        want = reference(REF_CFG, params[block], x)
    assert rel(got, want) < 2e-5


def test_mtp_layer_matches_reference(setup):
    model, params, x, y = setup
    h = jax.random.normal(jax.random.PRNGKey(3), (2, T, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        z, counts = glm.mtp_layer(model, params, h, y)
        got = glm.head_logits(model, params, z,
                              params["mtp_moe"]["head_norm"])
        for b in range(2):
            want = ref.mtp_logits(REF_CFG, params, h[b], y[b])
            assert rel(got[b], want) < 2e-5
    assert int(counts[0]) > 0 and int(counts[1]) == 0


def test_model_logits_and_both_loss_terms_match_reference(setup):
    model, params, x, y = setup
    with jax.default_matmul_precision("highest"):
        logits, aux = model.apply({"params": params}, x)
        per_seq, aux_l = model.apply({"params": params}, x, y)
    assert int(aux["moe_dropped"]) == 0 and "mtp_loss" not in aux
    # the MTP layer's experts are counted with the layers'
    assert int(aux_l["moe_pairs_local"]) > int(aux["moe_pairs_local"]) > 0
    for b in range(2):
        loss, want, _ = ref.loss_and_grad(REF_CFG, params, [], x[b], y[b])
        assert rel(logits[b], want["logits"]) < 2e-5
        assert float(per_seq[b]) == pytest.approx(float(loss), rel=1e-5)
        assert float(aux_l["mtp_loss"][b]) == pytest.approx(
            float(want["mtp_loss"]), rel=1e-5)
        assert float(per_seq[b] - 0.1 * aux_l["mtp_loss"][b]) \
            == pytest.approx(float(want["next_token_loss"]), rel=1e-5)
    assert float(want["mtp_loss"]) > 1.0         # near log(64): a real term


@pytest.mark.parametrize("block", [MLA1, MOE2, MTP_MIXER, MTP_MOE, 0, HEAD],
                         ids=["mla", "experts", "mtp_mixer", "mtp_moe",
                              "embed", "head"])
def test_block_gradient_matches_reference(setup, block):
    model, params, x, y = setup
    lo, hi = model.train_order_block_ids()[block]
    paths = model.param_order()[lo:hi + 1]
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: weighted_mean(
            model.apply({"params": p}, x[:1], y[:1])[0]))(params)
    _, _, want = ref.loss_and_grad(REF_CFG, params, paths, x[0], y[0])
    for path, w in zip(paths, want):
        assert float(jnp.max(jnp.abs(w))) > 0, path
        assert rel(get_by_path(grads, path), w) < 2e-4, path


def test_blocks_come_from_the_layer_list():
    model = tiny_model(layers=5)
    ids, order = model.train_order_block_ids(), model.param_order()
    assert len(ids) == 14 == len(model.block_kinds())
    assert model.block_names()[11:] == ["head", "mtp_mixer", "mtp_moe"]
    assert model.block_kinds() == ["embed", "mla", "mlp"] \
        + ["mla", "moe"] * 4 + ["head", "mtp_mixer", "mtp_moe"]
    assert ids[0] == [0, 0] and ids[-1][1] == len(order) - 1
    for (lo, hi), name in zip(ids, model.block_names()):
        assert all(p.startswith(name + "/") for p in order[lo:hi + 1])
    # the blocks tile the parameters but for each expert layer's router
    # and its selection bias, which one expert-parallel rank does not train
    covered = {i for lo, hi in ids for i in range(lo, hi + 1)}
    assert [order[i] for i in range(len(order)) if i not in covered] == [
        f"{b}/{leaf}" for b in ["layer1_moe", "layer2_moe", "layer3_moe",
                                "layer4_moe", "mtp_moe"]
        for leaf in ("router", "router_bias")]
    # without the MTP layer the model is the plain decoder
    plain = tiny_model(layers=5, num_nextn_predict_layers=0)
    assert plain.block_names() == model.block_names()[:12]


def test_published_widths_give_the_issue_s_parameter_counts():
    full = get_model("glm4_moe_lite")
    shapes = jax.eval_shape(lambda: full.init_variables(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))[0]
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(shapes["layer1_mixer"]) == 21_761_280
    assert count(shapes["layer0_mlp"]) == 62_916_608
    assert count(shapes["layer2_moe"]) == 85_067_840
    assert count(shapes["mtp_mixer"]) == 30_153_984
    assert count(shapes["embed"]) == count(shapes["head"]) - 2048 \
        == 39_649_280
    assert count(shapes) == 706_518_848
    order, ids = full.param_order(), full.train_order_block_ids()
    size = lambda b: sum(int(np.prod(get_by_path(shapes, p).shape))
                         for p in order[ids[b][0]:ids[b][1] + 1])
    assert (size(3), size(6), size(12)) == (21_761_280, 84_936_704,
                                            30_153_984)


def test_the_configuration_file_holds_the_catalog_s_values():
    """Every number of the catalog row's ``config`` is in the
    configuration file under the same key, unchanged (the row is copied
    here: the guides are not part of the repository)."""
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "glm47flash_30b_a3b_ep8.json")) as f:
        config = json.load(f)
    for key, value in published.items():
        assert config[key] == value and type(config[key]) is type(value), key
    assert config["reduced"] == [
        "layers", "experts_held", "vocab_rows", "K", "samples_per_client",
        "rounds_per_block", "dataset"]
    # nothing under the floors: four layers after the dense one, eight
    # experts, an eighth of the vocabulary, two clients
    assert config["layers"] - config["first_k_dense_replace"] >= 4
    assert config["experts_held"] >= 8 and config["K"] >= 2
    assert config["vocab_rows"] * 8 >= config["vocab_size"]
    assert {"mtp_loss_weight", "router_bias", "mtp_concatenation",
            "rotary_layout", "seq_len", "pair_rows_factor"} \
        <= set(config["assumed"])
    assert "8 chips" in config["deployment"] and config["ep_rank"] == 0
    assert config["departures"] and config["guarantees"]
    # the model class takes every key it declares at the file's value
    model = get_model("glm4_moe_lite", **{
        k: config[k] for k in glm.Glm4MoeLite.__dataclass_fields__
        if k in config and k not in ("name", "parent", "dtype")})
    assert model.vocab_rows == 19360 and model.layers == 5
    assert config["params"] == 706_518_848


# ----------------------------------------------------------------------
# the attention core as a kernel pair (interpret mode) against the XLA path
# ----------------------------------------------------------------------
@pytest.mark.parametrize("block", [1, MTP_MIXER], ids=["mla", "mtp_mixer"])
def test_model_through_the_attention_kernels_matches_the_xla_path(block):
    """Heads of 96 + 32 = 128 and a sequence of three key blocks: what
    ``plan()`` sends to the kernels, at one query head a key/value head
    (``rep`` 1) as the published widths have it."""
    model = tiny_model(qk_nope_head_dim=96, qk_rope_head_dim=32,
                       v_head_dim=128, attn_block=128, layers=2)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 385), 0, 64)
    x, y = ids[:, :-1], ids[:, 1:]
    params, _ = model.init_variables(jax.random.PRNGKey(0), x[:, :8])
    lo, hi = model.train_order_block_ids()[
        block if block == 1 else model.block_names().index("mtp_mixer")]
    paths = model.param_order()[lo:hi + 1]

    def run(impl):
        with force_attn_impl(impl), jax.default_matmul_precision("highest"):
            assert model.impl_fields(384) == {"attn_impl": impl,
                                              "head_impl": "fused"}
            logits, _ = model.apply({"params": params}, x)
            grads = jax.grad(lambda p: weighted_mean(
                model.apply({"params": p}, x, y)[0]))(params)
        return logits, [get_by_path(grads, path) for path in paths]

    (logits, grads), (want, want_grads) = run("pallas_interpret"), run("xla")
    assert rel(logits, want) < 2e-5
    for path, g, w in zip(paths, grads, want_grads):
        assert rel(g, w) < 2e-4, path


# ----------------------------------------------------------------------
# the router's rule and the expert-parallel share
# ----------------------------------------------------------------------
def test_the_bias_changes_the_choice_but_not_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    w0, e0 = moelib.sigmoid_router_weights(logits, jnp.zeros(16), 3, True,
                                           1.8)
    w1, e1 = moelib.sigmoid_router_weights(logits, bias, 3, True, 1.8)
    assert bool(jnp.any(jnp.sort(e0, -1) != jnp.sort(e1, -1)))
    # weights are the chosen experts' own scores, renormalised and scaled
    s = jax.nn.sigmoid(logits)
    for w, e in ((w0, e0), (w1, e1)):
        picked = jnp.take_along_axis(s, e, -1)
        assert rel(w, 1.8 * picked / jnp.sum(picked, -1, keepdims=True)) \
            < 1e-6
        assert np.allclose(np.asarray(jnp.sum(w, -1)), 1.8, rtol=1e-6)
    # without the bias the choice is the plain top-k of the scores
    assert bool(jnp.all(e0 == jax.lax.top_k(s, 3)[1]))
    # unnormalised: the scores themselves, scaled
    w2, _ = moelib.sigmoid_router_weights(logits, bias, 3, False, 1.0)
    assert rel(w2, jnp.take_along_axis(s, e1, -1)) < 1e-6
    # no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(moelib.sigmoid_router_weights(
        logits, b, 3, True, 1.8)[0] ** 2))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """8 ranks x 2 experts of 16: the ranks' partial outputs, with the
    shared expert counted once, are the uncut layer's output."""
    base = dict(TINY, experts_held=16, ep_rank=0)
    whole = tiny_model(**base)
    x = jax.random.normal(jax.random.PRNGKey(3), (T, TINY["hidden_size"]))
    p = whole.init_variables(jax.random.PRNGKey(4), jnp.zeros(
        (1, 8), jnp.int32))[0]["layer1_moe"]
    share = lambda r: {k: (v[2 * r:2 * r + 2] if k.startswith("experts_")
                           else v) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, r = glm.expert_layer(whole, p, x)
        assert int(r.pairs_local) == T * TINY["num_experts_per_tok"]
        shared = ref.swiglu(x, p["shared_gate_proj"], p["shared_up"],
                            p["shared_down"])
        total, pairs = shared, 0
        for rank in range(8):
            part = tiny_model(**dict(base, experts_held=2, ep_rank=rank))
            y, rr = glm.expert_layer(part, share(rank), x)
            assert int(rr.dropped) == 0
            total = total + (y - shared)
            pairs += int(rr.pairs_local)
        # the uncut reference gives the whole layer too
        assert rel(ref.experts({**REF_CFG, **base}, p, x), want) < 2e-5
    assert pairs == T * TINY["num_experts_per_tok"]
    assert rel(total, want) < 2e-5
    # and the reference, given one rank's share, gives that rank's part
    with jax.default_matmul_precision("highest"):
        want5 = ref.experts({**REF_CFG, **base, "experts_held": 2,
                             "ep_rank": 5}, share(5), x)
        got5, _ = glm.expert_layer(
            tiny_model(**dict(base, experts_held=2, ep_rank=5)), share(5), x)
    assert rel(got5, want5) < 2e-5
