"""Olmo-Hybrid-7B (``olmo_hybrid``) at tiny widths on the CPU: the program
against the plain reference (``benchmarks/reference/olmo_hybrid.py``):
logits, loss, the gradients of a Gated DeltaNet block, the attention block
and an MLP block; the chunked delta rule against the stepwise one at
unequal key and value widths with ``beta`` in (0, 2); where the norms sit
(on the sub-layers' outputs); the blocks and the parameter count at the
published widths; the share of negative eigenvalues; both kernel pairs in
interpret mode at 96 / 192 and 128-wide attention heads; and FedAvg rounds
of ``LMTrainer`` against ``decoder_round.run_rounds``.
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

from benchmarks.reference import decoder_round, olmo_hybrid as ref  # noqa: E402
from federated_pytorch_test_tpu.data.tokens import FederatedTokens  # noqa: E402
from federated_pytorch_test_tpu.models import (  # noqa: E402
    MODEL_REGISTRY,
    get_model,
)
from federated_pytorch_test_tpu.models.decoder import weighted_mean  # noqa: E402
from federated_pytorch_test_tpu.ops import gated_delta as gd  # noqa: E402
from federated_pytorch_test_tpu.ops.flash_attention import (  # noqa: E402
    force_attn_impl,
)
from federated_pytorch_test_tpu.train import (  # noqa: E402
    FedAvg,
    FederatedConfig,
    LMTrainer,
)
from federated_pytorch_test_tpu.utils.tree import get_by_path  # noqa: E402

TINY = dict(hidden_size=48, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=40, linear_num_key_heads=3,
            linear_num_value_heads=3, linear_key_head_dim=8,
            linear_value_head_dim=16, layers=4, vocab_rows=64)
#: what the reference reads beside the widths (the published values)
REF_CFG = dict(TINY, rms_norm_eps=1e-6, linear_conv_kernel_dim=4,
               linear_allow_neg_eigval=True)
T = 40
#: blocks of the four-layer model: layer 0's GDN mixer, layer 1's MLP,
#: layer 3's attention, the head
GDN0, MLP1, ATTN, HEAD = 1, 4, 7, 9


def tiny_model(**kw):
    # matrices seeded at 0.2 where the published widths take 0.02: at 48
    # wide a product's output is then of the size it has at 3,840
    return get_model("olmo_hybrid", **{
        **TINY, "attn_block": 16, "chunk": 8, "init_scale": 0.2,
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
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def ref_logits(params, x, cfg=REF_CFG):
    return jnp.stack([ref.loss_and_grad(cfg, params, [], x[i], x[i])[1][
        "logits"] for i in range(x.shape[0])])


# ----------------------------------------------------------------------
# the model against the reference
# ----------------------------------------------------------------------
def test_model_logits_and_loss_match_reference(setup):
    model, params, x, y = setup
    logits, aux = model.apply({"params": params}, x)
    per_seq, _ = model.apply({"params": params}, x, y)
    assert logits.shape == (2, T, 64) and per_seq.shape == (2,)
    for i in range(2):
        loss, seen, _ = ref.loss_and_grad(REF_CFG, params, [], x[i], y[i])
        assert rel(logits[i], seen["logits"]) < 1e-5
        assert float(per_seq[i]) == pytest.approx(float(loss), rel=1e-5)
    # a dense model: no routing counters
    assert set(aux) == {"gdn_neg_beta_share"}


def _block_paths(model, block):
    lo, hi = model.train_order_block_ids()[block]
    return model.param_order()[lo:hi + 1]


@pytest.fixture(scope="module")
def grads(setup):
    model, params, x, y = setup
    g = jax.grad(lambda p: weighted_mean(
        model.apply({"params": p}, x, y)[0]))(params)
    return g


@pytest.mark.parametrize("block", [GDN0, MLP1, ATTN, HEAD],
                         ids=["gdn", "mlp", "attention", "head"])
def test_block_gradient_matches_reference(setup, grads, block):
    model, params, x, y = setup
    paths = _block_paths(model, block)
    want = None
    for i in range(2):
        _, _, g = ref.loss_and_grad(REF_CFG, params, paths, x[i], y[i])
        want = g if want is None else [a + b for a, b in zip(want, g)]
    for path, w in zip(paths, want):
        got = get_by_path(grads, path)
        assert float(jnp.max(jnp.abs(w))) > 0, path
        assert rel(got, w / 2) < 2e-5, path


# ----------------------------------------------------------------------
# the chunked delta rule: unequal widths, beta up to 2
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dk,dv,chunk,length", [
    (8, 16, 8, 40), (16, 8, 8, 33), (96, 192, 64, 100)],
    ids=["narrow_keys", "narrow_values", "published_widths"])
def test_chunked_delta_rule_matches_stepwise_with_negative_eigenvalues(
        dk, dv, chunk, length):
    H = 3
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (H, length, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (H, length, dk)))
    v = jax.random.normal(ks[2], (H, length, dv))
    g = -0.2 * jax.random.uniform(ks[3], (H, length))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (H, length)))
    assert float(jnp.max(beta)) > 1.8
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(gd.gated_delta_stepwise)(q, k, v, g, beta)
        got = gd.gated_delta_chunked(q, k, v, g, beta, chunk=chunk,
                                     dtype=jnp.float32)
        # the reference's form, exp(g) (I - beta k k^T) S + beta k v^T
        written = jax.vmap(ref.delta_rule)(q, k, v, g, beta)
    assert got.shape == want.shape == (H, length, dv)
    assert rel(got, want) < 1e-5 and rel(written, want) < 1e-5


# ----------------------------------------------------------------------
# where the norms sit
# ----------------------------------------------------------------------
def _with(params, block, leaf, value):
    return {**params, block: {**params[block], leaf: value}}


def test_a_sub_layer_with_its_post_norm_zeroed_passes_its_input(setup):
    """``h + N(F(h)) w``: with ``w`` 0 nothing else of the sub-layer
    counts, in program and reference alike (a norm on the input would
    leave ``F`` in the stream)."""
    model, params, x, _ = setup
    for block, leaf in (("layer0_mixer", "q_proj"), ("layer1_mlp", "up_proj"),
                        ("layer3_mixer", "k_proj")):
        zero = _with(params, block, "post_norm",
                     jnp.zeros_like(params[block]["post_norm"]))
        moved = _with(zero, block, leaf, 3.0 * zero[block][leaf])
        a = model.apply({"params": zero}, x)[0]
        b = model.apply({"params": moved}, x)[0]
        assert float(jnp.max(jnp.abs(a - b))) == 0.0, block
        assert rel(a, ref_logits(zero, x)) < 1e-5


def test_the_norm_sits_on_the_sub_layer_s_output(setup):
    """Scaling a sub-layer's last matrix scales ``F`` and leaves ``N(F)``:
    the logits do not move.  Perturbing a post norm's weight moves them,
    in program and reference alike."""
    model, params, x, _ = setup
    base = model.apply({"params": params}, x)[0]
    for block, leaf in (("layer1_mixer", "o_proj"), ("layer2_mlp",
                                                     "down_proj"),
                        ("layer3_mixer", "o_proj")):
        scaled = _with(params, block, leaf, 7.0 * params[block][leaf])
        assert rel(model.apply({"params": scaled}, x)[0], base) < 1e-5
    w = params["layer2_mixer"]["post_norm"]
    bent = _with(params, "layer2_mixer", "post_norm",
                 w * (1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(5),
                                                    w.shape)))
    got = model.apply({"params": bent}, x)[0]
    assert rel(got, base) > 1e-2
    assert rel(got, ref_logits(bent, x)) < 1e-5


def test_attention_has_no_rotary_and_norms_q_over_all_heads(setup):
    """The QK-norm's weight is one vector over all heads: scaling the
    whole query projection moves nothing, scaling one head's columns
    does (a per-head norm would undo both)."""
    model, params, x, _ = setup
    p = params["layer3_mixer"]
    base = model.apply({"params": params}, x)[0]
    whole = _with(params, "layer3_mixer", "q_proj", 5.0 * p["q_proj"])
    assert rel(model.apply({"params": whole}, x)[0], base) < 1e-5
    one_head = _with(params, "layer3_mixer", "q_proj",
                     p["q_proj"].at[:, :12].multiply(5.0))
    got = model.apply({"params": one_head}, x)[0]
    assert rel(got, base) > 1e-3
    assert rel(got, ref_logits(one_head, x)) < 1e-5


# ----------------------------------------------------------------------
# blocks, counts and the share of negative eigenvalues
# ----------------------------------------------------------------------
def test_blocks_come_from_the_layer_list():
    model = tiny_model()
    assert model.layer_kinds() == ["gdn", "gdn", "gdn", "attn"]
    assert model.block_kinds() == ["embed", "gdn", "mlp", "gdn", "mlp",
                                   "gdn", "mlp", "attn", "mlp", "head"]
    ranges = model.train_order_block_ids()
    assert len(ranges) == 10 and ranges[0][0] == 0
    assert all(b[0] == a[1] + 1 for a, b in zip(ranges, ranges[1:]))
    assert ranges[-1][1] == len(model.param_order()) - 1
    # every sub-layer's block carries its post norm
    for b in range(1, 9):
        assert any(p.endswith("/post_norm") for p in _block_paths(model, b))
    # the config's list decides the kinds
    mixed = tiny_model(layer_types=["full_attention", "linear_attention"] * 2)
    assert mixed.layer_kinds() == ["attn", "gdn", "attn", "gdn"]


def test_published_widths_give_the_configuration_s_parameter_count():
    """From shapes alone, block by block, against the configuration
    file's ``params`` and its parts."""
    from benchmarks.engines import decoder_dense

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo_hybrid_7b_pp8.json")) as f:
        cfg = json.load(f)
    full = decoder_dense.build_model(cfg)
    count = lambda b: sum(int(np.prod(shape)) for _, shape, _ in
                          full._spec(b))
    sizes = [count(b) for b in full.block_names()]
    parts = cfg["params_by_part"]
    assert sizes[1] == sizes[3] == sizes[5] == parts["gdn_mixer_block"] \
        == 88_754_172
    assert sizes[7] == parts["attention_block"] == 58_993_920
    assert sizes[2] == sizes[8] == parts["mlp_block"] == 126_816_000
    assert sizes[0] == parts["embedding"] == 12_544 * 3_840
    assert sizes[9] == parts["head_block"] == 12_544 * 3_840 + 3_840
    assert sum(sizes) == cfg["params"] == 928_862_196
    assert full.head_dim == 128 and full.layer_kinds() == ["gdn"] * 3 + [
        "attn"]


def test_gdn_neg_beta_share_lies_inside_0_and_1(setup):
    model, params, x, _ = setup
    share = float(model.apply({"params": params}, x)[1][
        "gdn_neg_beta_share"])
    assert 0.2 < share < 0.8
    # without negative eigenvalues beta stays below 1
    plain = tiny_model(linear_allow_neg_eigval=False)
    assert float(plain.apply({"params": params}, x)[1][
        "gdn_neg_beta_share"]) == 0.0
    assert rel(plain.apply({"params": params}, x)[0], ref_logits(
        params, x, dict(REF_CFG, linear_allow_neg_eigval=False))) < 1e-5
    # a model without Gated DeltaNet layers reports 0
    attn_only = tiny_model(layer_types=["full_attention"] * 4)
    p = attn_only.init_variables(jax.random.PRNGKey(0), x)[0]
    assert float(attn_only.apply({"params": p}, x)[1][
        "gdn_neg_beta_share"]) == 0.0


def test_registered():
    assert MODEL_REGISTRY["olmo_hybrid"] is type(tiny_model())


# ----------------------------------------------------------------------
# both kernel pairs (interpret mode) against the XLA paths
# ----------------------------------------------------------------------
def test_model_through_the_kernels_matches_the_xla_path():
    """Two Gated DeltaNet heads of 96 / 192 with ``beta`` up to 2 and one
    attention head of 128 over 256 tokens: logits and a GDN block's
    gradient."""
    model = tiny_model(hidden_size=128, num_attention_heads=1,
                       num_key_value_heads=1, intermediate_size=32,
                       linear_num_key_heads=2, linear_num_value_heads=2,
                       linear_key_head_dim=96, linear_value_head_dim=192,
                       attn_block=128, chunk=64, init_scale=0.05)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 64)
    x, y = ids[:, :-1], ids[:, 1:]
    params, _ = model.init_variables(jax.random.PRNGKey(0), x[:, :8])
    paths = _block_paths(model, GDN0)

    def run(impl):
        with force_attn_impl(impl), gd.force_gdn_scan_impl(impl), \
                jax.default_matmul_precision("highest"):
            assert model.impl_fields(256) == {
                "gdn_scan_impl": impl, "attn_impl": impl,
                "head_impl": "fused"}
            logits, aux = jax.jit(lambda p: model.apply({"params": p}, x))(
                params)
            grads = jax.jit(jax.grad(lambda p: weighted_mean(
                model.apply({"params": p}, x, y)[0])))(params)
        return logits, aux, [get_by_path(grads, path) for path in paths]

    (logits, aux, grads), (want, _, want_grads) = run("pallas_interpret"), \
        run("xla")
    assert 0.0 < float(aux["gdn_neg_beta_share"]) < 1.0
    assert rel(logits, want) < 2e-5
    for path, g, w in zip(paths, grads, want_grads):
        assert float(jnp.max(jnp.abs(w))) > 0, path
        assert rel(g, w) < 2e-4, path


# ----------------------------------------------------------------------
# the normal path
# ----------------------------------------------------------------------
@pytest.mark.parametrize("block,kind", [(GDN0, "gdn"), (ATTN, "attn")],
                         ids=["gdn", "attention"])
def test_two_fedavg_rounds_of_lm_trainer_match_the_round_reference(block,
                                                                   kind):
    """The dense decoder through the same trainer: no expert counter is
    asked of it, the round record reads them 0 and gives the share of
    negative eigenvalues."""
    model = tiny_model()
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
            ref, REF_CFG, params, paths, 1e-3,
            [[[(xs[k], ys[k])] for k in range(2)] for _ in range(2)])
    t.close()
    for got, w, rec in zip(seen, want, hist):
        assert rec["loss"] == pytest.approx(w["loss"], rel=1e-5)
        assert rec["block_kind"] == kind and rec["tokens"] == 2 * 2 * 24
        assert rec["gdn_scan_impl"] == rec["attn_impl"] == "xla"
        assert rec["moe_pairs_local"] == rec["moe_dropped"] == 0
        assert rec["moe_fill_share"] == rec["moe_top1_weight_mean"] == 0.0
        assert 0.0 < rec["gdn_neg_beta_share"] < 1.0
        # Adam's first steps are lr * sign(g): an element whose gradient is
        # at rounding level may step the other way (a matrix under a post
        # norm has a direction of exactly zero gradient, its own), so the
        # SHARE of elements further than a tenth of lr is what is held
        d = np.concatenate([np.abs(leaf[k] - r[k]).ravel() for leaf, r in
                            zip(got, zip(*w["x"])) for k in range(2)])
        assert np.max(d) <= 2.0 * 1e-3 * (1 + 1e-3)
        assert np.mean(d > 1e-4) < 1e-3
    assert all(np.array_equal(leaf[0], leaf[1]) for leaf in seen[-1])
    assert hist[1]["loss"] < hist[0]["loss"]
