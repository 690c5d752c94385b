"""``LMTrainer`` on the engine's normal path at tiny widths on the CPU:
two FedAvg rounds against ``benchmarks/reference/lm_round.py`` (and, for
the second decoder with its two-term loss, ``decoder_round.py``), the
optimizer over the active leaves, resume across the smaller optimizer
tree.  Two layers (one Gated DeltaNet, one attention) keep the compiles
short; the four-layer model is in ``tests/test_qwen3_next.py``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for path in (HERE, REPO):       # HERE: the second decoder's tiny model
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmarks.reference import (  # noqa: E402
    decoder_round,
    glm4_moe_lite as glm_ref,
    lm_round,
)
from federated_pytorch_test_tpu.data.tokens import FederatedTokens  # noqa: E402
from federated_pytorch_test_tpu.models import get_model  # noqa: E402
from federated_pytorch_test_tpu.train import (  # noqa: E402
    FedAvg,
    FederatedConfig,
    LMTrainer,
)
from federated_pytorch_test_tpu.utils.tree import get_by_path  # noqa: E402
from test_glm4_moe_lite import (  # noqa: E402
    REF_CFG as GLM_REF_CFG,
    tiny_model as glm_tiny_model,
)

TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8, num_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, layers=2,
            full_attention_interval=2, experts_held=4, ep_rank=1,
            vocab_rows=64)
REF_CFG = dict(TINY, partial_rotary_factor=0.25, rope_theta=1e7,
               rms_norm_eps=1e-6, linear_conv_kernel_dim=4,
               norm_topk_prob=True)
T = 24
#: blocks of the two-layer model: 1 GDN mixer, 2 experts, 3 attention
GDN, MOE, ATTN = 1, 2, 3


def tiny_model(**kw):
    return get_model("qwen3_next", **{**TINY, "chunk": 16, "attn_block": 16,
                                      "pair_rows_factor": 8.0,
                                      "dtype": jnp.float32, **kw})


def lm_trainer(blocks, Nadmm=2, samples=2, model=None, **cfg_kw):
    data = FederatedTokens(K=2, batch=2, samples_per_client=samples,
                           seq_len=T, vocab=64, seed=3, head=16)
    cfg = FederatedConfig(K=2, Nloop=1, Nepoch=1, Nadmm=Nadmm,
                          default_batch=2, check_results=False, lr=1e-3,
                          num_devices=1, save_model=False, **cfg_kw)
    t = LMTrainer(model or tiny_model(), cfg, data, FedAvg())
    t.block_ids = [t.block_ids[b] for b in blocks]
    t.L = len(blocks)
    return t


def test_two_fedavg_rounds_match_the_reference():
    t = lm_trainer([MOE])
    lo, hi = t.block_ids[0]
    paths = t.order[lo:hi + 1]
    params = jax.tree.map(lambda a: np.asarray(a[0]), t.params0)
    xs, ys = t.data.train_shards_raw()
    seen = []
    with jax.default_matmul_precision("highest"):
        _, hist = t.run(log=lambda m: None, on_round=lambda s, r: seen.append(
            [np.asarray(get_by_path(s.params, p)) for p in paths]))
        want = lm_round.run_rounds(
            REF_CFG, params, paths, 1e-3,
            [[[(xs[k], ys[k])] for k in range(2)] for _ in range(2)])
    t.close()
    for got, w, rec in zip(seen, want, hist):
        assert rec["loss"] == pytest.approx(w["loss"], rel=1e-5)
        assert rec["block_kind"] == "moe" and rec["moe_dropped"] == 0
        assert rec["tokens"] == 2 * 2 * T
        for leaf, ref_leaves in zip(got, zip(*w["x"])):
            for k in range(2):
                # Adam's first steps are lr * sign(g): an element whose
                # gradient is rounding noise may land 2 lr away; none does
                # at float32 on these shapes beyond a hundredth of lr
                assert np.max(np.abs(leaf[k] - ref_leaves[k])) < 1e-5


def test_two_fedavg_rounds_on_mtp_mixer_match_the_round_reference():
    """The second decoder through the same trainer: the loss is two terms,
    the round record carries the MTP term, and ``impl_fields`` is the
    model's own (no ``gdn_scan_impl``)."""
    model, ref_cfg = glm_tiny_model(layers=2), dict(GLM_REF_CFG, layers=2)
    data = FederatedTokens(K=2, batch=2, samples_per_client=2, seq_len=T,
                           vocab=64, seed=3, head=16)
    cfg = FederatedConfig(K=2, Nloop=1, Nepoch=1, Nadmm=2, default_batch=2,
                          check_results=False, lr=1e-3, num_devices=1,
                          save_model=False)
    t = LMTrainer(model, cfg, data, FedAvg())
    block = model.block_names().index("mtp_mixer")
    t.block_ids, t.L = [t.block_ids[block]], 1
    lo, hi = t.block_ids[0]
    paths = t.order[lo:hi + 1]
    assert paths[0] == "mtp_mixer/enorm" and paths[-1] == "mtp_mixer/o_proj"
    params = jax.tree.map(lambda a: np.asarray(a[0]), t.params0)
    xs, ys = t.data.train_shards_raw()
    seen = []
    with jax.default_matmul_precision("highest"):
        _, hist = t.run(log=lambda m: None, on_round=lambda s, r: seen.append(
            [np.asarray(get_by_path(s.params, p)) for p in paths]))
        want = decoder_round.run_rounds(
            glm_ref, ref_cfg, params, paths, 1e-3,
            [[[(xs[k], ys[k])] for k in range(2)] for _ in range(2)])
        # the round's MTP term: both clients' one minibatch, unweighted
        mtp = sum(float(glm_ref.loss_and_grad(
            ref_cfg, params, [], jnp.asarray(xs[k][b]),
            jnp.asarray(ys[k][b]))[1]["mtp_loss"]) / 2
            for k in range(2) for b in range(2))
    t.close()
    assert hist[0]["mtp_loss"] == pytest.approx(mtp, rel=1e-5)
    assert 0 < hist[1]["mtp_loss"] < hist[0]["mtp_loss"]
    for got, w, rec in zip(seen, want, hist):
        assert rec["loss"] == pytest.approx(w["loss"], rel=1e-5)
        assert rec["block_kind"] == "mtp_mixer" and rec["moe_dropped"] == 0
        assert rec["tokens"] == 2 * 2 * T and rec["attn_impl"] == "xla"
        assert "gdn_scan_impl" not in rec
        for leaf, ref_leaves in zip(got, zip(*w["x"])):
            for k in range(2):
                assert np.max(np.abs(leaf[k] - ref_leaves[k])) < 1e-5


def test_a_model_without_an_mtp_layer_reports_zero():
    t = lm_trainer([ATTN], Nadmm=1)
    _, hist = t.run(log=lambda m: None)
    t.close()
    assert [r["mtp_loss"] for r in hist] == [0.0]
    assert {"gdn_scan_impl", "attn_impl"} <= set(hist[0])


@pytest.mark.parametrize("experts", [True, False],
                         ids=["experts", "no_experts"])
def test_moe_fill_share_is_pairs_over_rows(experts):
    # without: one dense layer and no MTP layer, no expert layer at all
    t = lm_trainer([ATTN], Nadmm=1) if experts else lm_trainer(
        [1], Nadmm=1, model=glm_tiny_model(layers=1,
                                           num_nextn_predict_layers=0))
    _, hist = t.run(log=lambda m: None)
    t.close()
    (rec,) = hist
    if not experts:
        assert rec["moe_fill_share"] == 0.0 and rec["moe_pairs_local"] == 0
        return
    # a step is 2 x T tokens with top-3 of 16 experts, 4 of them held:
    # pair_rows_factor 8 asks for 288 rows a layer and every pair that
    # can exist is 2 T x 3; two layers, one step, two clients
    rows = 2 * T * 3 * 2 * 1 * 2
    assert rec["moe_dropped"] == 0 and 0 < rec["moe_pairs_local"] < rows
    assert rec["moe_fill_share"] == pytest.approx(
        rec["moe_pairs_local"] / rows, rel=1e-6)


def test_the_trainer_names_no_model():
    import inspect

    from federated_pytorch_test_tpu.train import lm_engine

    src = inspect.getsource(lm_engine)
    imports = [line for line in src.splitlines()
               if line.startswith(("from ", "import "))]
    assert imports and not any("qwen3_next" in line or "glm4_moe_lite" in line
                               for line in imports)
    # and calls no model-specific method by name
    assert "gdn_scan_impl(" not in src and "attn_impl(" not in src


def test_optimizer_and_gradient_hold_the_active_leaves_only():
    t = lm_trainer([GDN])
    lo, hi = t.block_ids[0]
    active = list(t.order[lo:hi + 1])
    train_epoch, _, init_opt = t._build_fns(0)
    state = t.init_state()
    opt = init_opt(state.params)
    adam = opt[0]
    assert sorted(adam.mu) == sorted(active) == sorted(adam.nu)
    for path in active:
        assert adam.mu[path].shape == get_by_path(state.params, path).shape
    n_opt = sum(int(a.size) for a in jax.tree.leaves(opt))
    assert n_opt == 2 * 2 * t.block_size(0) + 2          # mu, nu, K counts
    # the epoch program holds no gradient of a frozen leaf: its jaxpr
    # differentiates with respect to the active leaves alone
    xb, yb, wb = t._stage_epoch()
    z, y, rho, x0, yhat0 = t._fresh_block_vars(t.block_size(0))
    state = state._replace(opt_state=opt)
    new, _ = train_epoch(state, y, t.client_norm, t._epoch_keys(), xb, yb,
                         wb, z, rho, t._ones_mask)
    changed = [p for p in t.order if not np.array_equal(
        np.asarray(get_by_path(new.params, p)),
        np.asarray(get_by_path(state.params, p)))] \
        if not t._donate else None
    if changed is not None:
        assert sorted(changed) == sorted(active)
    t.close()


def test_resume_across_the_smaller_optimizer_tree(tmp_path):
    """A run killed inside a block resumes from its own checkpoint (the
    optimizer leaves saved are the active block's) to the same weights
    and round fields as the uninterrupted run."""
    whole = lm_trainer([ATTN, GDN])
    state_a, hist_a = whole.run(log=lambda m: None)
    whole.close()

    class Kill(Exception):
        pass

    path = str(tmp_path / "ckpt")
    first = lm_trainer([ATTN, GDN])

    def stop(state, rec):
        if rec["block"] == 1 and rec["nadmm"] == 0:
            raise Kill

    with pytest.raises(Kill):
        first.run(log=lambda m: None, on_round=stop, checkpoint_path=path)
    second = lm_trainer([ATTN, GDN])
    state_b, hist_b = second.run(log=lambda m: None, checkpoint_path=path,
                                 resume=True)
    second.close()
    assert len(hist_b) == len(hist_a) == 4
    for a, b in zip(hist_a, hist_b):
        for key in ("loss", "tokens", "moe_pairs_local", "block_kind"):
            assert a[key] == b[key], key
    for a, b in zip(jax.tree.leaves(state_a.params),
                    jax.tree.leaves(state_b.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert sorted(state_b.opt_state[0].mu) == sorted(
        second.order[second.block_ids[1][0]:second.block_ids[1][1] + 1])


def test_init_state_hands_the_weights_over_once():
    t = lm_trainer([ATTN])
    state = t.init_state()
    assert all(isinstance(a, jax.ShapeDtypeStruct)
               for a in jax.tree.leaves(t.params0))
    assert t.block_size(0) == sum(
        int(np.prod(get_by_path(state.params, p).shape[1:]))
        for p in t.sweep_paths(0))
    with pytest.raises(RuntimeError):
        t.init_state()
    t.close()


