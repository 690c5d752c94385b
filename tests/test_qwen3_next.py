"""Qwen3-Next at tiny widths on the CPU: the program against the plain
reference (``benchmarks/reference/qwen3_next.py``, ``lm_round.py``), the
chunked delta rule against the recurrence, the expert-parallel share
against the uncut layer, and ``LMTrainer`` on the engine's normal path.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.reference import lm_round, qwen3_next as ref  # noqa: E402
from federated_pytorch_test_tpu.data.tokens import FederatedTokens  # noqa: E402
from federated_pytorch_test_tpu.models import get_model  # noqa: E402
from federated_pytorch_test_tpu.models import qwen3_next as qn  # noqa: E402
from federated_pytorch_test_tpu.ops import moe as moelib  # noqa: E402
from federated_pytorch_test_tpu.ops.flash_attention import (  # noqa: E402
    force_attn_impl,
)
from federated_pytorch_test_tpu.ops.gated_delta import (  # noqa: E402
    gated_delta_chunked,
    gated_delta_stepwise,
)
from federated_pytorch_test_tpu.utils.tree import get_by_path  # noqa: E402

TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8, num_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, layers=4, experts_held=4,
            ep_rank=1, vocab_rows=64)
#: what the reference reads beside the widths (the published values)
REF_CFG = dict(TINY, full_attention_interval=4, partial_rotary_factor=0.25,
               rope_theta=1e7, rms_norm_eps=1e-6, linear_conv_kernel_dim=4,
               norm_topk_prob=True)
T = 40           # not a multiple of the chunk (16) nor of the block (16)


def tiny_model(**kw):
    return get_model("qwen3_next", **{**TINY, "chunk": 16, "attn_block": 16,
                                      "pair_rows_factor": 8.0,
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
# the layers against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("block,system,reference", [
    ("layer0_mixer", qn.gated_delta_net, ref.delta_net),
    ("layer3_mixer", qn.gated_attention, ref.attention),
    ("layer1_moe", lambda c, p, x: qn.expert_layer(c, p, x)[0], ref.experts),
])
def test_layer_matches_reference(setup, block, system, reference):
    model, params, _, _ = setup
    x = jax.random.normal(jax.random.PRNGKey(2), (T, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got = system(model, params[block], x)
        want = reference(REF_CFG, params[block], x)
    assert rel(got, want) < 2e-5


def test_model_logits_and_loss_match_reference(setup):
    model, params, x, y = setup
    with jax.default_matmul_precision("highest"):
        logits, aux = model.apply({"params": params}, x)
        per_seq, _ = model.apply({"params": params}, x, y)
    assert int(aux["moe_dropped"]) == 0 and int(aux["moe_pairs_local"]) > 0
    for b in range(2):
        loss, want, _ = ref.loss_and_grad(REF_CFG, params, [], x[b], y[b])
        assert rel(logits[b], want) < 2e-5
        assert float(per_seq[b]) == pytest.approx(float(loss), rel=1e-5)
    assert float(qn.next_token_loss(logits, y)) == pytest.approx(
        float(jnp.mean(per_seq)), rel=1e-6)


@pytest.mark.parametrize("block", [1, 4, 7, 0, 9])
def test_block_gradient_matches_reference(setup, block):
    model, params, x, y = setup
    lo, hi = model.train_order_block_ids()[block]
    paths = model.param_order()[lo:hi + 1]
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: qn.next_token_loss(
            model.apply({"params": p}, x[:1])[0], y[:1]))(params)
    _, _, want = ref.loss_and_grad(REF_CFG, params, paths, x[0], y[0])
    for path, w in zip(paths, want):
        assert rel(get_by_path(grads, path), w) < 2e-4, path


def test_blocks_come_from_the_layer_list():
    model = tiny_model(layers=8)
    ids, order = model.train_order_block_ids(), model.param_order()
    assert len(ids) == 2 + 2 * 8 == len(model.block_kinds())
    assert ids[0] == [0, 0] and ids[-1][1] == len(order) - 1
    assert model.block_kinds()[1:9] == ["gdn", "moe"] * 3 + ["attn", "moe"]
    for (lo, hi), name in zip(ids, model.block_names()):
        assert all(p.startswith(name + "/") for p in order[lo:hi + 1])
    # the blocks tile the parameters but for each layer's router, which
    # one expert-parallel rank alone does not train
    covered = {i for lo, hi in ids for i in range(lo, hi + 1)}
    assert [order[i] for i in range(len(order)) if i not in covered] == [
        f"layer{l}_moe/router" for l in range(8)]


def test_published_widths_give_the_issue_s_parameter_counts():
    full = get_model("qwen3_next")
    shapes = jax.eval_shape(lambda: full.init_variables(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))[0]
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(shapes["layer0_mixer"]) == 33_720_512
    assert count(shapes["layer3_mixer"]) == 27_265_536
    assert count(shapes["layer1_moe"]) == 104_861_696
    assert count(shapes["embed"]) + count(shapes["head"]) == 77_793_280
    assert count(shapes) == 625_667_136


# ----------------------------------------------------------------------
# the attention core as a kernel pair (interpret mode) against the XLA path
# ----------------------------------------------------------------------
@pytest.mark.long(seconds=99)
@pytest.mark.parametrize("block", [7], ids=["attention_block"])
def test_model_through_the_attention_kernels_matches_the_xla_path(block):
    """The file's model has heads of 16, which ``plan()`` sends to the XLA
    path: this one has heads of 128 and a sequence of three key blocks."""
    model = tiny_model(head_dim=128, attn_block=128)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 385), 0, 64)
    x, y = ids[:, :-1], ids[:, 1:]
    params, _ = model.init_variables(jax.random.PRNGKey(0), x[:, :8])
    lo, hi = model.train_order_block_ids()[block]
    paths = model.param_order()[lo:hi + 1]

    def run(impl):
        with force_attn_impl(impl), jax.default_matmul_precision("highest"):
            assert model.attn_impl(384) == impl
            logits, _ = model.apply({"params": params}, x)
            grads = jax.grad(lambda p: qn.next_token_loss(
                model.apply({"params": p}, x)[0], y))(params)
        return logits, [get_by_path(grads, path) for path in paths]

    (logits, grads), (want, want_grads) = run("pallas_interpret"), run("xla")
    assert rel(logits, want) < 2e-5
    for path, g, w in zip(paths, grads, want_grads):
        assert rel(g, w) < 2e-4, path


def test_heads_of_16_take_the_xla_path_even_when_the_kernel_is_forced():
    with force_attn_impl("pallas_interpret"):
        assert tiny_model().attn_impl(256) == "xla"
        assert tiny_model(head_dim=128).attn_impl(256) == "pallas_interpret"
        assert tiny_model(head_dim=128).attn_impl(T) == "xla"   # 40 tokens


# ----------------------------------------------------------------------
# the chunked delta rule against the recurrence
# ----------------------------------------------------------------------
def delta_inputs(length, H=3, dk=8, dv=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (H, length, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (H, length, dk)))
    v = jax.random.normal(ks[2], (H, length, dv))
    g = -3.0 * jax.random.uniform(ks[3], (H, length))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (H, length)))
    return q, k, v, g, beta


@pytest.mark.parametrize("length", [64, 128, 100, 7])
def test_chunked_scan_matches_the_recurrence(length):
    args = delta_inputs(length)
    want = jax.vmap(gated_delta_stepwise)(*args)
    got = gated_delta_chunked(*args, chunk=64, dtype=jnp.float32)
    assert got.shape == want.shape and rel(got, want) < 1e-5


def test_chunked_scan_gradient_matches_the_recurrence():
    args = delta_inputs(100)
    loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)
    want = jax.grad(loss(jax.vmap(gated_delta_stepwise)),
                    argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(loss(lambda *a: gated_delta_chunked(
        *a, chunk=64, dtype=jnp.float32)), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-4


# ----------------------------------------------------------------------
# the expert-parallel share
# ----------------------------------------------------------------------
def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """16 ranks x 2 experts of 32: the ranks' partial outputs, with the
    shared expert counted once, are the uncut layer's output."""
    base = dict(TINY, num_experts=32, experts_held=32, ep_rank=0)
    whole = tiny_model(**base)
    H = TINY["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(3), (T, H))
    p = whole.init_variables(jax.random.PRNGKey(4), jnp.zeros(
        (1, 8), jnp.int32))[0]["layer0_moe"]
    with jax.default_matmul_precision("highest"):
        want, r = qn.expert_layer(whole, p, x)
        assert int(r.pairs_local) == T * TINY["num_experts_per_tok"]
        shared = jax.nn.sigmoid(x @ p["shared_gate"])[:, None] * (
            (jax.nn.silu(x @ p["shared_gate_proj"]) * (x @ p["shared_up"]))
            @ p["shared_down"])
        total, pairs = shared, 0
        for rank in range(16):
            part = tiny_model(**dict(base, experts_held=2, ep_rank=rank))
            mine = {k: (v[2 * rank:2 * rank + 2] if k.startswith("experts_")
                        else v) for k, v in p.items()}
            y, rr = qn.expert_layer(part, mine, x)
            assert int(rr.dropped) == 0
            total = total + (y - shared)
            pairs += int(rr.pairs_local)
    assert pairs == T * TINY["num_experts_per_tok"]
    assert rel(total, want) < 2e-5
    # and the reference, given one rank's share, gives that rank's part
    with jax.default_matmul_precision("highest"):
        want5 = ref.experts({**REF_CFG, **base, "experts_held": 2,
                             "ep_rank": 5},
                            {k: (v[10:12] if k.startswith("experts_") else v)
                             for k, v in p.items()}, x)
        got5, _ = qn.expert_layer(
            tiny_model(**dict(base, experts_held=2, ep_rank=5)),
            {k: (v[10:12] if k.startswith("experts_") else v)
             for k, v in p.items()}, x)
    assert rel(got5, want5) < 2e-5


def test_a_pair_without_a_row_is_counted():
    w, e = moelib.router_weights(
        jax.random.normal(jax.random.PRNGKey(5), (64, 16)), 3, True)
    full = moelib.route_local(w, e, 4, 4, rows=64 * 3)
    short = moelib.route_local(w, e, 4, 4, rows=8)
    assert int(full.dropped) == 0
    assert int(short.dropped) == int(full.pairs_local) - 8
    assert int(jnp.sum(short.group_sizes)) == 8
    assert float(full.load_max_over_mean) >= 1.0


def test_grouped_matmul_under_vmap_is_client_by_client():
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(k[0], (2, 12, 8))
    w = jax.random.normal(k[1], (2, 3, 8, 5))
    gs = jnp.array([[4, 0, 6], [1, 10, 1]], jnp.int32)
    f = lambda x, w, g: jnp.sum(moelib.grouped_matmul(x, w, g,
                                                      jnp.float32) ** 2)
    got = jax.vmap(jax.grad(f, argnums=(0, 1)))(x, w, gs)
    for c in range(2):
        want = jax.grad(f, argnums=(0, 1))(x[c], w[c], gs[c])
        for a, b in zip(got, want):
            assert rel(a[c], b) < 1e-5
    # rows past the last group read 0 and carry no gradient
    y = moelib.grouped_matmul(x[0], w[0], gs[0], jnp.float32)
    assert float(jnp.abs(y[10:]).max()) == 0.0
    assert float(jnp.abs(got[0][0][10:]).max()) == 0.0


def test_token_source_is_seeded_and_clients_differ():
    a = FederatedTokens(3, 2, 4, 32, 512, seed=7, head=64)
    b = FederatedTokens(3, 2, 4, 32, 512, seed=7, head=64)
    c = FederatedTokens(3, 2, 4, 32, 512, seed=8, head=64)
    xa, ya = a.train_shards_raw()
    assert xa.shape == ya.shape == (3, 4, 32) and xa.dtype == np.int32
    assert np.array_equal(xa, b.train_shards_raw()[0])
    assert not np.array_equal(xa, c.train_shards_raw()[0])
    assert np.array_equal(xa[:, :, 1:], ya[:, :, :-1])      # next ids
    assert xa.min() >= 0 and xa.max() < 512
    top = [np.bincount(xa[k].ravel(), minlength=512).argmax()
           for k in range(3)]
    assert len(set(top)) > 1                 # the commonest id differs
    xb, yb, wb = a.epoch_batches_raw(5)
    assert xb.shape == (3, 2, 2, 32) and wb.shape == (3, 2, 2)
    xt, yt, wt = a.test_batches_raw()
    assert xt.shape[1:] == (2, 32) and wt.sum() == 2
