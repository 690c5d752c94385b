"""Kernel-suite + whole-round-overlap tests (ISSUE 20).

Unit layer: interpret-mode parity for every ``ops/comm_kernels.py``
kernel against its literal jnp reference (bitwise where the contract
promises it, allclose where chunked accumulation re-associates), the
chunked top-k selection (bitwise, ties included), and the segment-owned
robust aggregation vs the dense all-gather path on the virtual 8-device
mesh — including the compiled ``memory_analysis`` "chunked strictly
lower" gate.  Engine layer: ``--robust-chunked`` trajectory parity,
``--overlap-round`` bitwise off==on, warn-fallback gating, composition
with ``--overlap-staging``, and kill/resume across an overlapped round
boundary.

Parity tests jit BOTH sides: XLA rewrites ``x / s`` into
``x * (1 / s)`` under jit on CPU, so an eager reference would differ by
one ulp from the jitted kernel for reasons that have nothing to do with
the kernel (PARITY.md).
"""

import warnings

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import (
    BlockModule,
    elu,
    flatten,
    max_pool_2x2,
    pairs,
)
from federated_pytorch_test_tpu.ops.comm_kernels import (
    _dequant_add_pallas,
    _dequant_add_xla,
    _gram_pallas,
    _gram_xla,
    _quantize_pallas,
    _quantize_xla,
    force_comm_kernels_impl,
    quantize_chunks,
)
from federated_pytorch_test_tpu.ops.topk_select import (
    force_topk_impl,
    top_k_abs_indices,
)
from federated_pytorch_test_tpu.parallel.comm import (
    make_robust_mean,
    robust_federated_mean,
    robust_federated_mean_chunked,
    robust_gather_bytes,
)
from federated_pytorch_test_tpu.parallel.mesh import (
    CLIENT_AXIS,
    client_mesh,
    client_sharding,
    shard_map,
)
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FederatedConfig,
)

pytestmark = pytest.mark.commkernels

P = jax.sharding.PartitionSpec

# ---------------------------------------------------------------------------
# fused quantize / dequant-accumulate / gram kernels (interpret parity)


class TestQuantizeKernel:
    # shapes chosen to exercise the pad paths: rows off the 32-sublane
    # tile, cols off the 128-lane tile, and an exact-tile control
    SHAPES = [(5, 200), (32, 256), (17, 128), (1, 100)]

    @pytest.mark.parametrize("qmax", [127, 7], ids=["q8", "q4"])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_interpret_bitwise_matches_xla(self, qmax, shape):
        rng = np.random.default_rng(0)
        vv = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        q_ref, s_ref = jax.jit(
            lambda v: _quantize_xla(v, qmax))(vv)
        q_pl, s_pl = jax.jit(
            lambda v: _quantize_pallas(v, qmax, interpret=True))(vv)
        # the contract is BITWISE — scale included, not just the int8
        # payload: both run the same f32 ops in the same order
        np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_pl))
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pl))
        assert q_pl.dtype == jnp.int8 and s_pl.dtype == jnp.float32

    def test_zero_row_quantizes_to_zero_with_zero_scale(self):
        vv = jnp.zeros((4, 128), jnp.float32)
        q, s = jax.jit(
            lambda v: _quantize_pallas(v, 127, interpret=True))(vv)
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(s), 0.0)

    def test_saturating_values_clip_to_qmax(self):
        # one dominant coordinate per row: it must land exactly on ±qmax
        vv = jnp.asarray([[3.0, -1.5, 0.0, 0.75] * 32,
                          [-8.0, 4.0, 2.0, -1.0] * 32], jnp.float32)
        q_ref, s_ref = jax.jit(lambda v: _quantize_xla(v, 7))(vv)
        q_pl, s_pl = jax.jit(
            lambda v: _quantize_pallas(v, 7, interpret=True))(vv)
        np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_pl))
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pl))
        assert np.abs(np.asarray(q_pl)).max() == 7

    def test_auto_dispatch_is_xla_on_cpu(self):
        # no force, CPU backend: the dispatch must take the literal
        # pack_chunks math — bitwise the reference by identity
        rng = np.random.default_rng(1)
        vv = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
        q_a, s_a = jax.jit(lambda v: quantize_chunks(v, 127))(vv)
        q_r, s_r = jax.jit(lambda v: _quantize_xla(v, 127))(vv)
        np.testing.assert_array_equal(np.asarray(q_a), np.asarray(q_r))
        np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_r))

    def test_forced_impl_restored_after_context(self):
        from federated_pytorch_test_tpu.ops import comm_kernels
        assert comm_kernels._FORCE_IMPL is None
        with force_comm_kernels_impl("pallas_interpret"):
            assert comm_kernels._FORCE_IMPL == "pallas_interpret"
        assert comm_kernels._FORCE_IMPL is None


class TestDequantAddKernel:
    @pytest.mark.parametrize("shape", [(5, 200), (32, 256), (3, 128)])
    def test_interpret_bitwise_matches_xla(self, shape):
        rng = np.random.default_rng(2)
        acc = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        q = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        scale = jnp.asarray(
            np.abs(rng.normal(size=shape[0])).astype(np.float32))
        ref = jax.jit(_dequant_add_xla)(acc, q, scale)
        got = jax.jit(
            lambda a, qq, s: _dequant_add_pallas(a, qq, s, interpret=True)
        )(acc, q, scale)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_zero_scale_rows_pass_through_acc(self):
        # scale == 0 means the chunk was all-zero at encode time: the
        # safe-divide contract decodes it as acc + q * 1.0 on BOTH paths
        acc = jnp.ones((2, 128), jnp.float32)
        q = jnp.zeros((2, 128), jnp.int8)
        scale = jnp.zeros((2,), jnp.float32)
        got = jax.jit(
            lambda a, qq, s: _dequant_add_pallas(a, qq, s, interpret=True)
        )(acc, q, scale)
        np.testing.assert_array_equal(np.asarray(got), 1.0)


class TestGramKernel:
    @pytest.mark.parametrize("shape", [(8, 1300), (4, 512), (16, 700)])
    def test_interpret_allclose_to_dense_matmul(self, shape):
        # chunked accumulation re-associates the contraction: allclose,
        # never bitwise (PARITY.md) — tolerance sized for f32 dot over
        # ~1e3-element rows
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        ref = jax.jit(_gram_xla)(a)
        got = jax.jit(lambda x: _gram_pallas(x, interpret=True))(a)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=1e-5, atol=1e-4)

    def test_gram_is_symmetric_psd_diagonal(self):
        rng = np.random.default_rng(4)
        a = jnp.asarray(rng.normal(size=(6, 600)).astype(np.float32))
        g = np.asarray(jax.jit(
            lambda x: _gram_pallas(x, interpret=True))(a))
        np.testing.assert_allclose(g, g.T, rtol=1e-6)
        assert (np.diag(g) >= 0).all()


# ---------------------------------------------------------------------------
# chunked top-k selection (bitwise, ties included)


class TestTopKSelect:
    def _both(self, vec, k):
        v = jnp.asarray(vec)
        with force_topk_impl("xla"):
            ref = np.asarray(jax.jit(
                lambda x: top_k_abs_indices(x, k))(v))
        with force_topk_impl("chunked"):
            got = np.asarray(jax.jit(
                lambda x: top_k_abs_indices(x, k))(v))
        return ref, got

    @pytest.mark.parametrize("n,k", [(5000, 100), (2048, 64), (100, 10),
                                     (4097, 1)])
    def test_chunked_bitwise_matches_single_shot(self, n, k):
        rng = np.random.default_rng(5)
        vec = rng.normal(size=n).astype(np.float32)
        ref, got = self._both(vec, k)
        np.testing.assert_array_equal(ref, got)

    def test_tie_breaking_is_bitwise(self):
        # magnitudes drawn from a 4-value set over 3 chunks: massive tie
        # classes straddling every chunk boundary — the chunk-major
        # candidate layout must reproduce lax.top_k's lower-index break
        rng = np.random.default_rng(6)
        vals = np.array([2.0, -2.0, 1.0, -1.0], np.float32)
        vec = vals[rng.integers(0, 4, size=6000)]
        ref, got = self._both(vec, 500)
        np.testing.assert_array_equal(ref, got)

    def test_all_equal_vector(self):
        ref, got = self._both(np.full(4096, 3.5, np.float32), 64)
        np.testing.assert_array_equal(ref, got)

    def test_k_equals_n(self):
        rng = np.random.default_rng(7)
        vec = rng.normal(size=300).astype(np.float32)
        ref, got = self._both(vec, 300)
        np.testing.assert_array_equal(ref, got)

    def test_auto_is_single_shot_on_cpu(self):
        from federated_pytorch_test_tpu.ops import topk_select
        assert topk_select._resolve_impl(10**6) == "xla"


# ---------------------------------------------------------------------------
# segment-owned robust aggregation on the 8-device mesh


D = 8


def _drive(fn_of_stack_w, x, w):
    mesh = client_mesh(D)
    csh = client_sharding(mesh)
    fn = shard_map(fn_of_stack_w, mesh=mesh,
                   in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
                   out_specs=P(), check_vma=False)
    return np.asarray(jax.jit(fn)(
        jax.device_put(jnp.asarray(x), csh),
        jax.device_put(jnp.asarray(w, jnp.float32), csh)))


def _dense(x, w, kind, **kw):
    return _drive(lambda xs, ws: robust_federated_mean(
        xs, ws, kind=kind, **kw), x, w)


def _chunked(x, w, kind, **kw):
    return _drive(lambda xs, ws: robust_federated_mean_chunked(
        xs, ws, kind=kind, D=D, **kw), x, w)


class TestChunkedRobustMean:
    K, n = 8, 1000          # n not a multiple of D: exercises the pad

    def setup_method(self, method):
        rng = np.random.default_rng(8)
        self.x = rng.normal(size=(self.K, self.n)).astype(np.float32)
        self.w = np.ones(self.K, np.float32)

    @pytest.mark.parametrize("kind", ["trim", "median"])
    def test_coordinatewise_kinds_bitwise(self, kind):
        # trim/median are per-coordinate: every coordinate sees the
        # identical K values on either path — bitwise by contract
        np.testing.assert_array_equal(
            _dense(self.x, self.w, kind, trim_frac=0.2),
            _chunked(self.x, self.w, kind, trim_frac=0.2))

    @pytest.mark.parametrize("kind", ["clip", "krum", "geomed"])
    def test_norm_coupled_kinds_allclose(self, kind):
        # per-client norms / Gram blocks are psum'd across segments:
        # re-associated sums — allclose, not bitwise (PARITY.md)
        np.testing.assert_allclose(
            _dense(self.x, self.w, kind, trim_frac=0.2),
            _chunked(self.x, self.w, kind, trim_frac=0.2),
            rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("kind", ["trim", "median", "clip", "krum",
                                      "geomed"])
    def test_nonfinite_client_screened_exactly(self, kind):
        # the chunked screen psums per-segment non-finite counts: a NaN
        # anywhere in a row folds that client out on EVERY device, even
        # when only one segment holds the NaN
        x = self.x.copy()
        x[3, 900] = np.nan          # lives in the LAST segment only
        got = _chunked(x, self.w, kind, trim_frac=0.2)
        ref = _dense(x, self.w, kind, trim_frac=0.2)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-6)

    def test_partial_weights_match_dense(self):
        w = np.array([1, 0, 1, 1, 0, 1, 1, 1], np.float32)
        np.testing.assert_array_equal(
            _dense(self.x, w, "trim", trim_frac=0.2),
            _chunked(self.x, w, "trim", trim_frac=0.2))

    def test_all_rejected_round_yields_zero(self):
        w = np.zeros(self.K, np.float32)
        out = _chunked(self.x, w, "trim", trim_frac=0.2)
        np.testing.assert_array_equal(out, np.zeros(self.n, np.float32))

    def test_unweighted_call_matches_dense(self):
        mesh = client_mesh(D)
        csh = client_sharding(mesh)
        xs = jax.device_put(jnp.asarray(self.x), csh)

        def run(f):
            fn = shard_map(lambda s: f(s, None), mesh=mesh,
                           in_specs=(P(CLIENT_AXIS),), out_specs=P(),
                           check_vma=False)
            return np.asarray(jax.jit(fn)(xs))

        np.testing.assert_array_equal(
            run(lambda s, w: robust_federated_mean(s, w, kind="median")),
            run(lambda s, w: robust_federated_mean_chunked(
                s, w, kind="median", D=D)))

    def test_single_device_falls_back_to_dense(self):
        # D<=1: the "gathered" matrix IS the local stack — the chunked
        # entry point must defer to the dense program outright
        mesh = client_mesh(1)
        x = jnp.asarray(self.x)

        def run(f):
            fn = shard_map(lambda s: f(s, None), mesh=mesh,
                           in_specs=(P(CLIENT_AXIS),), out_specs=P(),
                           check_vma=False)
            return np.asarray(jax.jit(fn)(x))

        np.testing.assert_array_equal(
            run(lambda s, w: robust_federated_mean_chunked(
                s, w, kind="trim", trim_frac=0.2, D=1)),
            run(lambda s, w: robust_federated_mean(
                s, w, kind="trim", trim_frac=0.2)))

    def test_none_with_chunked_raises(self):
        with pytest.raises(ValueError, match="robust estimator"):
            make_robust_mean("none", chunked=True, D=D)

    def test_factory_returns_chunked_callable(self):
        mf = make_robust_mean("trim", trim_frac=0.2, chunked=True, D=D)
        got = _drive(mf, self.x, self.w)
        np.testing.assert_array_equal(
            got, _dense(self.x, self.w, "trim", trim_frac=0.2))


class TestRobustByteAndMemoryModel:
    def test_gather_bytes_model(self):
        assert robust_gather_bytes("none", 8, 8192, 8, True) == 0
        assert robust_gather_bytes("trim", 8, 8192, 8, False) == 4 * 8 * 8192
        assert robust_gather_bytes("trim", 8, 8192, 8, True) == 4 * 8 * 1024
        # krum's psum'd [K, K] Gram block rides along on the chunked path
        assert robust_gather_bytes("krum", 8, 8192, 8, True) == \
            4 * 8 * 1024 + 4 * 8 * 8
        # D=1 has no segments to own: chunked degenerates to dense
        assert robust_gather_bytes("trim", 8, 8192, 1, True) == 4 * 8 * 8192

    @staticmethod
    def _peak(kind, chunked, N=8192, K=8):
        mesh = client_mesh(D)
        mf = make_robust_mean(kind, trim_frac=0.1, chunked=chunked, D=D)
        fn = shard_map(lambda s, w: mf(s, w), mesh=mesh,
                       in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
                       out_specs=P(), check_vma=False)
        shapes = (jax.ShapeDtypeStruct((K, N), jnp.float32),
                  jax.ShapeDtypeStruct((K,), jnp.float32))
        stats = jax.jit(fn).lower(*shapes).compile().memory_analysis()
        return int(stats.argument_size_in_bytes
                   + stats.output_size_in_bytes
                   + stats.temp_size_in_bytes)

    @pytest.mark.parametrize("kind", ["trim", "krum"])
    def test_chunked_peak_strictly_below_dense(self, kind):
        # the ISSUE's acceptance gate, as a compiler fact: per-device
        # peak bytes (argument + output + temp, the obs/costs.py
        # definition) of the segment-owned program must be strictly
        # below the all-gather program at the smoke geometry
        dense = self._peak(kind, chunked=False)
        chunk = self._peak(kind, chunked=True)
        assert chunk < dense, (kind, chunk, dense)


# ---------------------------------------------------------------------------
# engine integration


class TinyNet(BlockModule):
    @nn.compact
    def __call__(self, x, train=True):
        x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                     name="conv1")(x)))
        return nn.Dense(10, name="fc1")(flatten(x))

    def param_order(self):
        return pairs("conv1", "fc1")

    def train_order_block_ids(self):
        return [[0, 1], [2, 3]]

    def linear_layer_ids(self):
        return [1]


K = 4


class Killed(Exception):
    pass


@pytest.fixture(scope="module")
def data():
    return FederatedCifar10(K=K, batch=16, limit_per_client=32,
                            limit_test=32)


def _cfg(**kw):
    base = dict(K=K, Nloop=1, Nepoch=2, Nadmm=3, default_batch=16,
                check_results=False, admm_rho0=0.1, seed=5)
    base.update(kw)
    return FederatedConfig(**base)


def _run(cfg, data, L=1, **run_kw):
    t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, AdmmConsensus())
    t.L = L
    run_kw.setdefault("log", lambda m: None)
    state, hist = t.run(**run_kw)
    return t, state, hist


def _leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state.params)]


def _strip(rec):
    # wall-clock and XLA cost-ledger fields are dispatch-attributed:
    # wall-clock fields differ between runs, and a resumed segment's
    # first round is a block visit's first round: it stamps a switch —
    # the trajectory contract covers everything else, bitwise
    return {k: v for k, v in rec.items()
            if isinstance(v, (int, float)) and not k.endswith("_seconds")
            and not k.startswith("dispatch_")
            and k != "block_switch_h2d_bytes"}


class TestEngineRobustChunked:
    def test_trim_chunked_matches_dense_bitwise(self, data):
        _, s_d, h_d = _run(_cfg(robust_agg="trim", trim_frac=0.2), data)
        _, s_c, h_c = _run(_cfg(robust_agg="trim", trim_frac=0.2,
                                robust_chunked=True), data)
        for a, b in zip(_leaves(s_d), _leaves(s_c)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h_d, h_c):
            assert ra["loss"] == rb["loss"]

    def test_krum_chunked_tracks_dense(self, data):
        _, s_d, _ = _run(_cfg(robust_agg="krum", trim_frac=0.2), data)
        _, s_c, _ = _run(_cfg(robust_agg="krum", trim_frac=0.2,
                              robust_chunked=True), data)
        for a, b in zip(_leaves(s_d), _leaves(s_c)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_chunked_without_estimator_raises(self, data):
        with pytest.raises(ValueError, match="robust estimator"):
            _run(_cfg(robust_chunked=True), data)


class TestEngineOverlapRound:
    def test_overlap_is_bitwise_invisible(self, data):
        _, s0, h0 = _run(_cfg(), data)
        _, s1, h1 = _run(_cfg(overlap_round=True), data)
        for a, b in zip(_leaves(s0), _leaves(s1)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h0, h1):
            assert _strip(ra) == _strip(rb)
        # advisory telemetry appears only on the overlapped run, and a
        # mid-block round must actually have pre-dispatched
        assert "overlap_dispatch_seconds" not in h0[0]
        assert all("overlap_dispatch_seconds" in r for r in h1)
        assert h1[0]["overlap_dispatch_seconds"] > 0

    def test_last_round_of_block_has_no_lookahead(self, data):
        _, _, h1 = _run(_cfg(overlap_round=True), data)
        # nothing to pre-dispatch past the final round of the block
        assert h1[-1]["overlap_dispatch_seconds"] == 0.0

    def test_composes_with_overlap_staging_bitwise(self, data):
        _, s0, h0 = _run(_cfg(), data)
        _, s1, h1 = _run(_cfg(overlap_round=True, overlap_staging=True),
                         data)
        for a, b in zip(_leaves(s0), _leaves(s1)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h0, h1):
            assert ra["loss"] == rb["loss"]
        assert "overlap_seconds" in h1[0]
        assert "overlap_dispatch_seconds" in h1[0]

    def test_composes_with_robust_chunked_bitwise(self, data):
        base = dict(robust_agg="trim", trim_frac=0.2, robust_chunked=True)
        _, s0, h0 = _run(_cfg(**base), data)
        _, s1, h1 = _run(_cfg(overlap_round=True, **base), data)
        for a, b in zip(_leaves(s0), _leaves(s1)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h0, h1):
            assert ra["loss"] == rb["loss"]

    def test_multi_block_overlap_bitwise(self, data):
        _, s0, h0 = _run(_cfg(), data, L=2)
        _, s1, h1 = _run(_cfg(overlap_round=True), data, L=2)
        for a, b in zip(_leaves(s0), _leaves(s1)):
            np.testing.assert_array_equal(a, b)
        assert [h["block"] for h in h0] == [h["block"] for h in h1]
        for ra, rb in zip(h0, h1):
            assert ra["loss"] == rb["loss"]

    @pytest.mark.parametrize("kw,frag", [
        (dict(update_guard=True), "guard verdicts"),
        (dict(async_rounds=True, max_staleness=2), "async scheduler"),
        (dict(fault_spec="drop=0.3,seed=7"), "host ledgers"),
        (dict(population=64), "rotates the cohort"),
        (dict(fused_rounds=True), "no host gap"),
    ])
    def test_unsafe_knobs_warn_and_fall_back_bitwise(self, data, kw, frag):
        with warnings.catch_warnings(record=True) as wrec:
            warnings.simplefilter("always")
            _, s1, h1 = _run(_cfg(overlap_round=True, **kw), data)
        assert any("overlap_round requested but unsafe" in str(x.message)
                   and frag in str(x.message) for x in wrec)
        _, s0, h0 = _run(_cfg(**kw), data)
        for a, b in zip(_leaves(s0), _leaves(s1)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h0, h1):
            assert ra["loss"] == rb["loss"]
        # fallen back means no lookahead telemetry either
        assert "overlap_dispatch_seconds" not in h1[0]

    def test_kill_resume_across_overlapped_boundary(self, data, tmp_path):
        # the lookahead cache (_round_ahead / _staged_ahead) is
        # process-local and keyed on the round counters: a kill between
        # pre-dispatch and consumption must resume onto the sequential
        # re-derivation and still replay the uninterrupted trajectory
        # bit-for-bit
        cfg = _cfg(overlap_round=True)
        ck = str(tmp_path / "ck")
        _, _, hist_full = _run(cfg, data)

        def bomb(state, rec):
            if rec["nadmm"] == 0:   # round 1 is already pre-dispatched
                raise Killed

        with pytest.raises(Killed):
            _run(cfg, data, checkpoint_path=ck, on_round=bomb)
        _, _, hist_r = _run(cfg, data, checkpoint_path=ck, resume=True)
        assert len(hist_r) == len(hist_full)
        for a, b in zip(hist_r, hist_full):
            assert _strip(a) == _strip(b)

    def test_population_composes_with_overlap_staging(self, data):
        # the S1 lift: population sampling no longer blocks
        # overlap_staging — the staged batch is cohort-independent raw
        # payload, finished under the actual cohort at consumption
        _, s0, h0 = _run(_cfg(population=64), data)
        _, s1, h1 = _run(_cfg(population=64, overlap_staging=True), data)
        for a, b in zip(_leaves(s0), _leaves(s1)):
            np.testing.assert_array_equal(a, b)
        for ra, rb in zip(h0, h1):
            assert ra["loss"] == rb["loss"]
        assert "overlap_seconds" in h1[0]


# ---------------------------------------------------------------------------
# schema v14


class TestSchemaV14:
    def test_round_accepts_overlap_dispatch_seconds(self):
        from federated_pytorch_test_tpu.obs.schema import (
            SCHEMA_VERSION,
            validate_record,
        )

        assert SCHEMA_VERSION >= 14
        validate_record({"event": "round", "schema": 14, "run_id": "r",
                         "round_index": 0, "engine": "blockwise",
                         "round_seconds": 0.1,
                         "overlap_dispatch_seconds": 0.02})

    def test_field_is_advisory(self):
        from federated_pytorch_test_tpu.obs.schema import ADVISORY_FIELDS

        assert "overlap_dispatch_seconds" in ADVISORY_FIELDS

    def test_peak_device_bytes_regressions_trip_compare(self):
        from federated_pytorch_test_tpu.obs.compare import _direction

        assert _direction("smoke_robust_trim_chunked_peak_device_bytes") < 0
        assert _direction("smoke_robust_trim_dense_gather_bytes") < 0
        assert _direction("smoke_robust_trim_gather_savings_ratio") > 0
