"""Xing4.0-29B-A4B (``xing4_0``) at tiny widths on the CPU: the program
against the plain reference (``benchmarks/reference/xing4_0.py``), YaRN's
tables, the expert-parallel shares against the uncut layer, the blocks
and parameter counts, the configuration file, the model through the
attention kernels in interpret mode, and one FedAvg run of ``LMTrainer``
against ``decoder_round.run_rounds``.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.reference import decoder_round, xing4_0 as ref  # noqa: E402
from federated_pytorch_test_tpu.data.tokens import FederatedTokens  # noqa: E402
from federated_pytorch_test_tpu.models import (  # noqa: E402
    MODEL_REGISTRY,
    get_model,
)
from federated_pytorch_test_tpu.models import decoder, xing4_0 as xing  # noqa: E402
from federated_pytorch_test_tpu.models.decoder import weighted_mean  # noqa: E402
from federated_pytorch_test_tpu.ops.flash_attention import (  # noqa: E402
    force_attn_impl,
)
from federated_pytorch_test_tpu.ops.hyper_connections import (  # noqa: E402
    force_mhc_impl,
)
from federated_pytorch_test_tpu.train import (  # noqa: E402
    FedAvg,
    FederatedConfig,
    LMTrainer,
)
from federated_pytorch_test_tpu.utils.tree import get_by_path  # noqa: E402

#: the published group with the original context cut to the test's scale
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
TINY = dict(hidden_size=32, num_attention_heads=4, q_lora_rank=12,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=80, moe_intermediate_size=24,
            n_routed_experts=16, num_experts_per_tok=3, layers=3,
            experts_held=4, ep_rank=1, vocab_rows=64, rope_scaling=YARN)
#: what the reference reads beside the widths (the published values)
REF_CFG = dict(TINY, first_k_dense_replace=1, rope_theta=1e4,
               rms_norm_eps=1e-6, norm_topk_prob=True,
               routed_scaling_factor=2.0, hc_mult=4, hc_sinkhorn_iters=20,
               hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
T = 40
#: blocks of the three-layer model
MLA1, MOE1, MLP0, HEAD = 3, 4, 2, 7


def tiny_model(**kw):
    # matrices seeded at 0.2 where the published widths take 0.02: at 32
    # wide a sub-layer's output is then as large beside the embedding as
    # at 3,584, so the streams differ and the maps matter
    return get_model("xing4_0", **{
        **TINY, "attn_block": 16, "pair_rows_factor": 8.0,
        "bias_scale": 0.02, "init_scale": 0.2, "dtype": jnp.float32, **kw})


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
def _system_sub_layer(model, p, X, kind):
    """``X [T, n, C]`` (the reference's layout) through the program's
    sub-layer of ``kind``."""
    streams = jnp.moveaxis(X, 1, 0)[:, None]            # [n, 1, T, C]
    eps = model.rms_norm_eps

    def f(u):
        if kind == "mla":
            return jax.lax.map(lambda ut: decoder.latent_attention(
                model, p, decoder.rms_norm(ut, p["norm"], eps),
                scale=model.softmax_scale(),
                inv_freq=model.rope_inv_freq()), u), None
        flat = decoder.rms_norm(u, p["norm"], eps).reshape(-1, u.shape[-1])
        y = xing.expert_layer(model, p, flat)[0] if kind == "moe" \
            else xing.dense_mlp(model, p, flat)
        return y.reshape(u.shape), None

    out, err, _ = xing.sub_layer(model, p, f, streams)
    return jnp.moveaxis(out[:, 0], 0, 1), err


@pytest.mark.parametrize("block,kind,reference", [
    ("layer1_mixer", "mla", ref.mixer_sub_layer),
    ("layer0_mlp", "mlp", ref.ffn_sub_layer),
    ("layer1_moe", "moe", ref.ffn_sub_layer),
])
def test_sub_layer_matches_reference(setup, block, kind, reference):
    """Each layer kind with its maps and mixing, on streams that differ."""
    model, params, _, _ = setup
    X = jax.random.normal(jax.random.PRNGKey(2), (T, 4, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got, err = _system_sub_layer(model, params[block], X, kind)
        want = reference(REF_CFG, params[block], X)
    assert rel(got, want) < 2e-5 and float(err) < 1e-5


def test_latent_attention_takes_values_narrower_than_keys(setup):
    """Keys of 8 + 4 beside values of 8, YaRN's tables and scale: the
    shared function against the reference's head-by-head form."""
    model, params, _, _ = setup
    x = jax.random.normal(jax.random.PRNGKey(3), (T, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got = decoder.latent_attention(
            model, params["layer1_mixer"], x, scale=model.softmax_scale(),
            inv_freq=model.rope_inv_freq())
        want = ref.mla(REF_CFG, params["layer1_mixer"], x)
        plain = decoder.latent_attention(model, params["layer1_mixer"], x)
    assert got.shape == (T, TINY["hidden_size"]) and rel(got, want) < 2e-5
    # without the scale and the tables it is another function
    assert rel(plain, want) > 1e-2


def test_model_logits_and_loss_match_reference(setup):
    model, params, x, y = setup
    with jax.default_matmul_precision("highest"):
        logits, aux = model.apply({"params": params}, x)
        per_seq, aux_l = model.apply({"params": params}, x, y)
    assert int(aux["moe_dropped"]) == 0 and int(aux["moe_pairs_local"]) > 0
    assert 0.0 < float(aux["mhc_marginal_err"]) < 1e-5
    assert float(aux_l["mhc_marginal_err"]) == float(aux["mhc_marginal_err"])
    assert "mtp_loss" not in aux_l
    for b in range(2):
        loss, want, _ = ref.loss_and_grad(REF_CFG, params, [], x[b], y[b])
        assert rel(logits[b], want["logits"]) < 2e-5
        assert float(per_seq[b]) == pytest.approx(float(loss), rel=1e-5)


@pytest.mark.parametrize("block", [MLA1, MOE1, MLP0, 0, HEAD],
                         ids=["mla", "experts", "mlp", "embed", "head"])
def test_block_gradient_matches_reference(setup, block):
    """Hyper-connection leaves among the block's (the embedding and the
    head have none)."""
    model, params, x, y = setup
    lo, hi = model.train_order_block_ids()[block]
    paths = model.param_order()[lo:hi + 1]
    assert any("/hc_phi_res" in p for p in paths) == (block not in (0, HEAD))
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: weighted_mean(
            model.apply({"params": p}, x[:1], y[:1])[0]))(params)
    _, _, want = ref.loss_and_grad(REF_CFG, params, paths, x[0], y[0])
    for path, w in zip(paths, want):
        assert float(jnp.max(jnp.abs(w))) > 0, path
        assert rel(get_by_path(grads, path), w) < 2e-4, path


def test_zeroing_phi_moves_the_logits_beyond_the_check_s_tolerance(setup):
    """The maps' input-dependent part is no decoration: without it the
    logits move by more than ``engines/decoder_hc.py`` lets the program
    differ from the reference."""
    from benchmarks.engines import decoder_hc

    model, params, x, _ = setup
    logits, _ = model.apply({"params": params}, x)
    still, _ = model.apply({"params": decoder_hc._zero_phi(params)}, x)
    moved = float(jnp.sqrt(jnp.sum((still - logits) ** 2)
                           / jnp.sum(logits ** 2)))
    assert moved > 2.0 * decoder_hc.LOGITS_RTOL
    zeroed = decoder_hc._zero_phi(params)["layer1_moe"]
    assert float(jnp.max(jnp.abs(zeroed["hc_phi_res"]))) == 0.0
    assert np.array_equal(np.asarray(zeroed["hc_b_res"]),
                          np.asarray(params["layer1_moe"]["hc_b_res"]))


# ----------------------------------------------------------------------
# YaRN
# ----------------------------------------------------------------------
def test_yarn_tables_match_the_written_out_formula():
    """At the published numbers: rotary width 64, theta 1e4, factor 64
    over 4,096, beta 32 / 1."""
    d, theta, factor, L = 64, 1e4, 64.0, 4096
    low = math.floor(d * math.log(L / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(L / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    want = []
    for i in range(d // 2):
        m = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        want.append((1 - m) / (factor * theta ** (2 * i / d))
                    + m / theta ** (2 * i / d))
    got = decoder.yarn_inv_freq(d, theta, factor, L, 32.0, 1.0)
    assert got.shape == (32,) and rel(got, jnp.asarray(want)) < 1e-6
    plain = 1.0 / theta ** (jnp.arange(0, d, 2) / d)
    # fast frequencies stay, slow ones are divided by the factor
    assert rel(got[:11], plain[:11]) < 1e-6
    assert rel(got[23:], plain[23:] / factor) < 1e-6
    assert bool(jnp.all(got[11:23] < plain[11:23]))
    assert bool(jnp.all(got[11:23] > plain[11:23] / factor))
    cos, sin = decoder.rope_tables(2048, d, theta, got)
    cos0, sin0 = decoder.rope_tables(2048, d, theta)
    assert cos.shape == (2048, d) and rel(cos, cos0) > 0.5
    assert np.array_equal(np.asarray(cos[:, :11]), np.asarray(cos0[:, :11]))
    # cos and sin are not scaled (mscale / mscale_all_dim = 1): the
    # softmax scale carries it
    assert float(jnp.max(jnp.abs(cos))) <= 1.0
    assert decoder.yarn_softmax_scale(64.0, 1.0) == pytest.approx(
        (0.1 * math.log(64.0) + 1.0) ** 2)
    assert decoder.yarn_softmax_scale(1.0, 1.0) == 1.0
    model = get_model("xing4_0", rope_scaling={
        **YARN, "original_max_position_embeddings": 4096})
    assert model.softmax_scale() == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64.0) + 1.0) ** 2)
    assert rel(model.rope_inv_freq(), got) == 0.0
    # the reference's table is written out on its own
    assert rel(ref.yarn_inverse_frequencies(
        {"qk_rope_head_dim": d, "rope_theta": theta, "rope_scaling": {
            **YARN, "original_max_position_embeddings": L}}), got) < 1e-6
    assert get_model("xing4_0").rope_inv_freq() is None


# ----------------------------------------------------------------------
# blocks, counts, the configuration file
# ----------------------------------------------------------------------
def test_blocks_come_from_the_layer_list():
    model = tiny_model(layers=5)
    ids, order = model.train_order_block_ids(), model.param_order()
    assert MODEL_REGISTRY["xing4_0"] is xing.Xing4
    assert len(ids) == 12 == len(model.block_kinds())
    assert model.block_kinds() == ["embed", "mla", "mlp"] \
        + ["mla", "moe"] * 4 + ["head"]
    assert ids[0] == [0, 0] and ids[-1][1] == len(order) - 1
    for (lo, hi), name in zip(ids, model.block_names()):
        assert all(p.startswith(name + "/") for p in order[lo:hi + 1])
    # every sub-layer's block carries its nine hyper-connection leaves
    for b, kind in enumerate(model.block_kinds()):
        lo, hi = ids[b]
        n_hc = sum("/hc_" in p for p in order[lo:hi + 1])
        assert n_hc == (0 if kind in ("embed", "head") else 9), b
    # the blocks tile the parameters but for each expert layer's router
    # and its selection bias
    covered = {i for lo, hi in ids for i in range(lo, hi + 1)}
    assert [order[i] for i in range(len(order)) if i not in covered] == [
        f"layer{l}_moe/{leaf}" for l in (1, 2, 3, 4)
        for leaf in ("router", "router_bias")]
    # two leading dense layers, as published, are two `mlp` blocks
    assert tiny_model(layers=5, first_k_dense_replace=2).block_kinds()[:5] \
        == ["embed", "mla", "mlp", "mla", "mlp"]


def test_an_mtp_layer_over_streams_is_refused():
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        tiny_model(num_nextn_predict_layers=1).init_variables(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_published_widths_give_the_issue_s_parameter_counts():
    full = get_model("xing4_0")
    shapes = jax.eval_shape(lambda: full.init_variables(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))[0]
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    hc_leaves = lambda t: {k: v for k, v in t.items() if k.startswith("hc_")}
    assert count(hc_leaves(shapes["layer0_mixer"])) == 344_091
    assert count(shapes["layer1_mixer"]) == 28_758_811 == 28_414_720 + 344_091
    assert count(shapes["layer0_mlp"]) == 99_094_016 + 344_091
    assert count(shapes["layer2_moe"]) == 99_094_016 + 344_091 + 229_440
    assert count(shapes["embed"]) == 58_720_256
    assert count(shapes["head"]) == 58_723_840
    assert count(shapes) == 759_346_446
    order, ids = full.param_order(), full.train_order_block_ids()
    size = lambda b: sum(int(np.prod(get_by_path(shapes, p).shape))
                         for p in order[ids[b][0]:ids[b][1] + 1])
    assert (size(1), size(5), size(9)) == (28_758_811,) * 3
    assert size(4) == 99_438_107


def test_the_configuration_file_holds_the_catalog_s_values():
    """Every number of the catalog row's ``config`` is in the
    configuration file under the same key, unchanged but for the two the
    cut changes (the row is copied here: the guides are not part of the
    repository)."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4_29b_a4b_ep8.json")) as f:
        config = json.load(f)
    cut = {"first_k_dense_replace": 1, "num_nextn_predict_layers": 0}
    for key, value in published.items():
        want = cut.get(key, value)
        assert config[key] == want and type(config[key]) is type(want), key
    assert config["reduced"] == [
        "layers", "first_k_dense_replace", "num_nextn_predict_layers",
        "experts_held", "vocab_rows", "K", "samples_per_client",
        "rounds_per_block", "dataset"]
    assert set(config["reduced"]) == set(config["reduced_notes"])
    assert set(cut) <= set(config["reduced"])
    assert config["source"] == ("https://huggingface.co/XingChen-AGI/"
                                "Xing4.0-29B-A4B/blob/main/config.json")
    # nothing under the floors: four layers after the dense one, eight
    # experts, an eighth of the vocabulary, two clients
    assert config["layers"] - config["first_k_dense_replace"] >= 4
    assert config["experts_held"] >= 8 and config["K"] >= 2
    assert config["vocab_rows"] * 8 >= config["vocab_size"]
    assert {"hyper_connection_maps", "hyper_connection_norm",
            "sinkhorn_order", "hyper_connection_output",
            "hyper_connection_init", "rotary_layout", "yarn", "init",
            "tokens", "seq_len"} <= set(config["assumed"])
    assert "8 chips" in config["deployment"] and config["ep_rank"] == 0
    assert any("expanded form" in d for d in config["departures"])
    assert any("float32 streams and maps" in g for g in config["guarantees"])
    # the model class takes every key it declares at the file's value
    model = get_model("xing4_0", **{
        k: config[k] for k in xing.Xing4.__dataclass_fields__
        if k in config and k not in ("name", "parent", "dtype")})
    assert (model.vocab_rows, model.layers, model.hc_mult) == (16384, 5, 4)
    assert model.rope_scaling["factor"] == 64 and model.hc_res_diag == 1.0
    assert config["params"] == 759_346_446
    assert (config["model"], config["engine"]) == ("xing4_0", "decoder_hc")


# ----------------------------------------------------------------------
# the attention core as a kernel pair (interpret mode) against the XLA path
# ----------------------------------------------------------------------
@pytest.mark.long(seconds=79)
def test_model_through_the_attention_kernels_matches_the_xla_path():
    """Keys of 128 + 64 = 192 beside values of 128 and a sequence of three
    key blocks: what ``plan()`` sends to the kernels with ``q`` and ``k``
    padded to 256, at one query head a key head as the published widths
    have it."""
    model = tiny_model(qk_nope_head_dim=128, qk_rope_head_dim=64,
                       v_head_dim=128, num_attention_heads=2,
                       attn_block=128, layers=2)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 385), 0, 64)
    x, y = ids[:, :-1], ids[:, 1:]
    params, _ = model.init_variables(jax.random.PRNGKey(0), x[:, :8])
    # layer 1's mixer: in layer 0 the streams are still copies of the
    # embedding, the input norm takes H_pre's scale away and H_res mixes
    # equal rows, so phi_pre's and phi_res's gradients are rounding noise
    lo, hi = model.train_order_block_ids()[3]
    paths = model.param_order()[lo:hi + 1]

    def run(impl):
        with force_attn_impl(impl), jax.default_matmul_precision("highest"):
            assert model.impl_fields(384) == {"attn_impl": impl,
                                              "mhc_impl": "xla",
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
# the hyper-connections as kernels (interpret mode) against jax.numpy
# ----------------------------------------------------------------------
@pytest.mark.parametrize("force,hidden,impl", [
    (None, 128, "xla"),                      # the CPU
    ("pallas_interpret", 128, "pallas_interpret"),
    ("pallas_interpret", 32, "xla"),         # a width the kernels refuse
    ("xla", 128, "xla"),
])
def test_impl_fields_say_what_runs_the_hyper_connections(force, hidden,
                                                         impl):
    model = tiny_model(hidden_size=hidden)
    ask = lambda: model.impl_fields(T)["mhc_impl"]
    if force is None:
        assert ask() == impl
    else:
        with force_mhc_impl(force):
            assert ask() == impl == model.mhc_impl(T)


@pytest.mark.long(seconds=108)
def test_model_through_the_stream_kernels_matches_the_jax_numpy_path():
    """Streams of 128: ``plan()`` sends every sub-layer's ``pre`` and
    ``expand`` to the kernels (two sequences of 40 tokens: one ragged
    tile), forward and backward through ``jax.checkpoint``; the logits
    and the gradients of layer 0's MLP block and layer 1's mixer block,
    hyper-connection leaves among them, against the ``jax.numpy`` lines
    differentiated by JAX.  (Not the first sub-layer, whose streams are
    equal, nor the last, whose ``H_res`` cannot move the streams' sum:
    their ``phi_res`` gradients are rounding noise on either path.)"""
    model = tiny_model(hidden_size=128, layers=2)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, 64)
    x, y = ids[:, :-1], ids[:, 1:]
    params, _ = model.init_variables(jax.random.PRNGKey(0), x[:, :8])
    paths = [q for b in (2, 3) for q in model.param_order()[
        model.train_order_block_ids()[b][0]:
        model.train_order_block_ids()[b][1] + 1]]

    def run(impl):
        with force_mhc_impl(impl), jax.default_matmul_precision("highest"):
            assert model.mhc_impl(2 * T) == impl
            (logits, aux) = model.apply({"params": params}, x)
            grads = jax.grad(lambda p: weighted_mean(
                model.apply({"params": p}, x, y)[0]))(params)
        return logits, aux["mhc_marginal_err"], \
            [get_by_path(grads, path) for path in paths]

    (logits, err, grads), (want, want_err, want_grads) = \
        run("pallas_interpret"), run("xla")
    assert rel(logits, want) < 2e-5
    # a few units in float32's last place of a row or column sum
    assert 0.0 < float(err) < 1e-5 and 0.0 < float(want_err) < 1e-5
    assert any("hc_phi_res" in q for q in paths)
    for path, g, w in zip(paths, grads, want_grads):
        assert rel(g, w) < 2e-4, path


# ----------------------------------------------------------------------
# the expert-parallel share
# ----------------------------------------------------------------------
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
        want, r = xing.expert_layer(whole, p, x)
        assert int(r.pairs_local) == T * TINY["num_experts_per_tok"]
        shared = ref.swiglu(x, p["shared_gate_proj"], p["shared_up"],
                            p["shared_down"])
        total, pairs = shared, 0
        for rank in range(8):
            part = tiny_model(**dict(base, experts_held=2, ep_rank=rank))
            y, rr = xing.expert_layer(part, share(rank), x)
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
        got5, _ = xing.expert_layer(
            tiny_model(**dict(base, experts_held=2, ep_rank=5)), share(5), x)
    assert rel(got5, want5) < 2e-5


# ----------------------------------------------------------------------
# the normal path
# ----------------------------------------------------------------------
def test_two_fedavg_rounds_of_lm_trainer_match_the_round_reference():
    """The third decoder through the same trainer, a mixer block active:
    its hyper-connection leaves are exchanged with it, and the round
    record carries the worst marginal error."""
    model, ref_cfg = tiny_model(layers=2), dict(REF_CFG, layers=2)
    data = FederatedTokens(K=2, batch=2, samples_per_client=2, seq_len=24,
                           vocab=64, seed=3, head=16)
    cfg = FederatedConfig(K=2, Nloop=1, Nepoch=1, Nadmm=2, default_batch=2,
                          check_results=False, lr=1e-3, num_devices=1,
                          save_model=False)
    t = LMTrainer(model, cfg, data, FedAvg())
    t.block_ids, t.L = [t.block_ids[3]], 1              # layer 1's mixer
    lo, hi = t.block_ids[0]
    paths = t.order[lo:hi + 1]
    assert paths[0] == "layer1_mixer/norm" \
        and paths[-1] == "layer1_mixer/hc_b_res"
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
        assert rec["block_kind"] == "mla" and rec["moe_dropped"] == 0
        assert rec["tokens"] == 2 * 2 * 24 and rec["attn_impl"] == "xla"
        assert rec["mhc_impl"] == "xla"
        assert rec["mtp_loss"] == 0.0 and "gdn_scan_impl" not in rec
        assert 0.0 < rec["mhc_marginal_err"] < 1e-5
        for path, leaf, ref_leaves in zip(paths, got, zip(*w["x"])):
            for k in range(2):
                assert np.max(np.abs(leaf[k] - ref_leaves[k])) < 1e-5, path
    # the exchange left the clients equal, hyper-connection leaves too
    assert all(np.array_equal(leaf[0], leaf[1]) for leaf in seen[-1])
    assert hist[1]["loss"] < hist[0]["loss"]
