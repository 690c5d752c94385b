"""Device-cost observability tests (obs/costs.py + obs/profile.py).

Covers the PR 10 surface: the v1->v6 schema ladder and the new
``compile`` record kind, the CostLedger compile-detection/AOT-analysis
path on the CPU backend (availability probed — absent cost fields must
be OMITTED, never zeroed), compile-span nesting under the PR 8
Chrome-trace validator, bitwise math identity with the ledger on/off,
the profile CLI exit-code contract, and the bytes-on-wire
reconciliation math against hand-computed numbers.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.base import (
    BlockModule,
    elu,
    flatten,
    max_pool_2x2,
    pairs,
)
from federated_pytorch_test_tpu.obs import (
    SCHEMA_VERSION,
    SchemaError,
    make_recorder,
    validate_record,
)
from federated_pytorch_test_tpu.obs.compare import _direction, load_source
from federated_pytorch_test_tpu.obs.costs import (
    AOT_MODES,
    CompileEvent,
    CostLedger,
    RoundCosts,
    round_cost_fields,
)
from federated_pytorch_test_tpu.obs.profile import (
    collect,
    main as profile_main,
    profile_metrics,
    selftest as profile_selftest,
)
from federated_pytorch_test_tpu.obs.report import read_records
from federated_pytorch_test_tpu.obs.trace import (
    to_chrome_trace,
    validate_chrome_trace,
)
from federated_pytorch_test_tpu.train import (
    BlockwiseFederatedTrainer,
    FedAvg,
    FederatedConfig,
)
from federated_pytorch_test_tpu.utils import compile_cache
from federated_pytorch_test_tpu.utils.compile_cache import (
    cache_stats,
    enable_persistent_compile_cache,
)

pytestmark = pytest.mark.obscost

K = 4


class TinyNet(BlockModule):
    """Same 2-block toy CNN as test_obs: small compiles, full blockwise
    machinery (so both train_epoch and comm jit sites exist)."""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = max_pool_2x2(elu(nn.Conv(4, (5, 5), strides=(2, 2),
                                     name="conv1")(x)))
        x = flatten(x)
        return nn.Dense(10, name="fc1")(x)

    def param_order(self):
        return pairs("conv1", "fc1")

    def train_order_block_ids(self):
        return [[0, 1], [2, 3]]

    def linear_layer_ids(self):
        return [1]


@pytest.fixture(scope="module")
def data():
    return FederatedCifar10(K=K, batch=16, limit_per_client=32,
                            limit_test=32)


def small_cfg(**kw):
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=2, default_batch=16,
                check_results=False, admm_rho0=0.1, obs_sinks="memory")
    base.update(kw)
    return FederatedConfig(**base)


def round_record(i=0, ver=SCHEMA_VERSION, **kw):
    rec = {"event": "round", "schema": ver, "run_id": "t" * 8,
           "engine": "classifier", "round_index": i, "round_seconds": 0.5,
           "loss": 1.0 - 0.1 * i}
    rec.update(kw)
    return rec


def compile_record(**kw):
    rec = {"event": "compile", "schema": SCHEMA_VERSION,
           "run_id": "t" * 8, "site": "train_epoch[blk=0]",
           "compile_seconds": 0.25}
    rec.update(kw)
    return rec


# ----------------------------------------------------------------------
# schema ladder v1 -> v6


class TestSchemaV6:
    def test_v6_reader_accepts_every_prior_version(self):
        for ver in range(1, SCHEMA_VERSION + 1):
            validate_record(round_record(ver=ver))
            validate_record({"event": "run_header", "schema": ver,
                             "run_id": "r" * 8, "engine": "classifier",
                             "time_unix": 1.0})

    def test_newer_schema_rejected(self):
        with pytest.raises(SchemaError, match="newer"):
            validate_record(round_record(ver=SCHEMA_VERSION + 1))

    def test_compile_record_kind(self):
        validate_record(compile_record(
            engine="classifier", algorithm="fedavg", round_index=0,
            trace_count=1, cache_hit=False, flops=1.0e9,
            hlo_bytes_accessed=1.5e6, transcendentals=2.0e3,
            argument_bytes=1024, output_bytes=512, temp_bytes=256,
            generated_code_bytes=4096, peak_device_bytes=1792,
            span_id="ab12", parent_span="cd34",
            t_start=1.0, t_end=1.25))

    def test_compile_required_fields(self):
        with pytest.raises(SchemaError, match="site"):
            validate_record({"event": "compile",
                             "schema": SCHEMA_VERSION,
                             "run_id": "t" * 8, "compile_seconds": 0.1})
        with pytest.raises(SchemaError, match="compile_seconds"):
            validate_record({"event": "compile",
                             "schema": SCHEMA_VERSION,
                             "run_id": "t" * 8, "site": "x"})

    def test_compile_fields_typed(self):
        with pytest.raises(SchemaError, match="cache_hit"):
            validate_record(compile_record(cache_hit="yes"))
        with pytest.raises(SchemaError, match="flops"):
            validate_record(compile_record(flops="many"))
        with pytest.raises(SchemaError, match="peak_device_bytes"):
            validate_record(compile_record(peak_device_bytes=1.5))

    def test_unknown_fields_pass_on_compile(self):
        # additive contract: a v7 writer's extra field must not break us
        validate_record(compile_record(totally_new_field_v9="future"))

    def test_round_cost_fields_additive(self):
        validate_record(round_record(
            compile_seconds=0.5, cache_hit=True, flops_round=1.0e9,
            hlo_bytes_accessed=2.0e6, peak_device_bytes=4096))

    def test_cost_fields_event_gated(self):
        # site belongs to compile records only
        with pytest.raises(SchemaError, match="not valid"):
            validate_record(round_record(site="train_epoch[blk=0]"))
        # flops (per-program) belongs to compile, not round
        with pytest.raises(SchemaError, match="not valid"):
            validate_record(round_record(flops=1.0e9))

    def test_summary_cost_totals(self):
        validate_record({"event": "summary", "schema": SCHEMA_VERSION,
                         "run_id": "t" * 8, "status": "completed",
                         "rounds": 2, "time_unix": 1.0,
                         "compile_events_total": 3,
                         "compile_seconds_total": 0.42,
                         "cache_hits_total": 1, "cache_misses_total": 2,
                         "mem_peak_bytes_watermark": 1 << 20,
                         "mem_final_vs_peak_bytes": 1 << 10})


# ----------------------------------------------------------------------
# ledger unit behavior (no jax dispatch needed)


class TestLedgerUnit:
    def test_round_cost_fields_windowing(self):
        ev_in = CompileEvent(site="a", seconds=0.2, t_start=10.2,
                             t_end=10.4, trace_count=1, cache_hit=None)
        ev_out = CompileEvent(site="b", seconds=0.3, t_start=11.5,
                              t_end=11.8, trace_count=1, cache_hit=None)
        costs = RoundCosts(events=(ev_in, ev_out), flops=0.0,
                           bytes_accessed=0.0, peak_bytes=0)
        fields = round_cost_fields(costs, t_start=10.0, seconds=1.0)
        # out-of-window event excluded; absent data omitted, not zeroed
        assert fields == {"compile_seconds": pytest.approx(0.2)}

    def test_round_cost_fields_exec_accumulators(self):
        costs = RoundCosts(events=(), flops=2.0e9, bytes_accessed=3.0e6,
                           peak_bytes=4096)
        fields = round_cost_fields(costs, t_start=0.0, seconds=1.0)
        assert fields == {"flops_round": 2.0e9,
                          "hlo_bytes_accessed": 3.0e6,
                          "peak_device_bytes": 4096}
        assert isinstance(fields["peak_device_bytes"], int)

    def test_round_cost_fields_dispatch_seconds(self):
        # schema v15: written when the window held a dispatch, omitted
        # (not zeroed) when it held none or the field was never set
        costs = RoundCosts(events=(), flops=0.0, bytes_accessed=0.0,
                           peak_bytes=0, dispatch_seconds=0.0125)
        assert round_cost_fields(costs, t_start=0.0, seconds=1.0) == {
            "dispatch_seconds": pytest.approx(0.0125)}
        bare = RoundCosts(events=(), flops=0.0, bytes_accessed=0.0,
                          peak_bytes=0)
        assert bare.dispatch_seconds == 0.0
        assert round_cost_fields(bare, t_start=0.0, seconds=1.0) == {}
        validate_record(round_record(dispatch_seconds=0.0125))

    def test_event_record_omits_absent_fields(self):
        ev = CompileEvent(site="s", seconds=0.1, t_start=0.0, t_end=0.1,
                          trace_count=1, cache_hit=None, costs={})
        rec = ev.record()
        assert "cache_hit" not in rec and "flops" not in rec
        ev2 = CompileEvent(site="s", seconds=0.1, t_start=0.0, t_end=0.1,
                           trace_count=2, cache_hit=True,
                           costs={"flops": 7.0})
        rec2 = ev2.record(round_index=3)
        assert rec2["cache_hit"] is True and rec2["flops"] == 7.0
        assert rec2["round_index"] == 3 and rec2["trace_count"] == 2

    def test_cache_classification(self, tmp_path):
        led = CostLedger(aot_mode="off", cache_dir=str(tmp_path),
                         fast_compile_s=0.15)
        # empty dir, fast compile, no baseline delta -> heuristic hit
        assert led._classify_cache(0.01) is True
        # a fresh persisted entry across the compile -> genuine miss,
        # regardless of speed
        (tmp_path / "entry-0").write_bytes(b"x" * 64)
        assert led._classify_cache(0.01) is False
        # no new entry: fast -> hit, slow -> miss
        assert led._classify_cache(0.01) is True
        assert led._classify_cache(0.5) is False

    def test_no_cache_dir_is_unattributable(self):
        led = CostLedger(aot_mode="off", cache_dir="")
        assert led._classify_cache(0.01) is None
        assert led.cache_hit_rate() is None


# ----------------------------------------------------------------------
# ledger on real jit dispatches (CPU backend; availability probed)

_COST_KEYS = {"flops", "hlo_bytes_accessed", "transcendentals",
              "argument_bytes", "output_bytes", "temp_bytes",
              "generated_code_bytes", "peak_device_bytes"}


def _instrumented(led, site, fn):
    return led.instrument(jax.jit(led.mark(fn, site)), site)


class TestLedgerJit:
    def test_cold_compile_detected_once(self):
        led = CostLedger(aot_mode="lowered", cache_dir="")
        f = _instrumented(led, "tanh2", lambda x: jnp.tanh(x) * 2.0)
        x = jnp.ones((8, 8), jnp.float32)
        np.testing.assert_allclose(np.asarray(f(x)),
                                   np.tanh(np.ones((8, 8))) * 2.0,
                                   rtol=1e-6)
        assert len(led.all_events) == 1
        ev = led.all_events[0]
        assert ev.site == "tanh2" and ev.trace_count == 1
        assert ev.seconds > 0 and ev.t_end > ev.t_start
        # warm dispatch: no new event
        f(x)
        assert len(led.all_events) == 1
        # availability probed: whatever the backend produced is typed
        # and nonzero-or-absent — never a zeroed placeholder
        assert set(ev.costs) <= _COST_KEYS
        for k, v in ev.costs.items():
            assert isinstance(v, (int, float)) and v >= 0, (k, v)
        rec = ev.record()
        for k in _COST_KEYS - set(ev.costs):
            assert k not in rec

    def test_retrace_on_new_shape(self):
        led = CostLedger(aot_mode="off", cache_dir="")
        f = _instrumented(led, "s", lambda x: x + 1.0)
        f(jnp.ones((4,)))
        f(jnp.ones((5,)))
        f(jnp.ones((4,)))  # cached executable, no retrace
        assert [e.trace_count for e in led.all_events] == [1, 2]

    def test_drain_resets_window(self):
        led = CostLedger(aot_mode="lowered", cache_dir="")
        f = _instrumented(led, "d", lambda x: x * x)
        f(jnp.ones((16,)))
        rc = led.drain()
        assert len(rc.events) == 1
        if "flops" in rc.events[0].costs:
            assert rc.flops == pytest.approx(rc.events[0].costs["flops"])
        # drained: next window starts empty, exec accumulators reset
        rc2 = led.drain()
        assert rc2.events == () and rc2.flops == 0.0
        # warm dispatches keep accumulating executed cost
        f(jnp.ones((16,)))
        f(jnp.ones((16,)))
        rc3 = led.drain()
        if "flops" in led.all_events[0].costs:
            assert rc3.flops == pytest.approx(
                2 * led.all_events[0].costs["flops"])

    @pytest.mark.parametrize("aot_mode", ["off", "lowered"])
    def test_dispatch_seconds_accumulates_and_resets(self, aot_mode):
        """Every instrumented call's own timer adds up, compiling or not,
        with or without a cost model; ``drain`` hands it out and resets."""
        led = CostLedger(aot_mode=aot_mode, cache_dir="")
        f = _instrumented(led, "a", lambda x: x * 3.0)
        g = _instrumented(led, "b", lambda x: x - 3.0)
        assert led.drain().dispatch_seconds == 0.0
        x = jnp.ones((16,))
        f(x)                                    # compiles: inside the sum
        cold = led.drain()
        assert len(cold.events) == 1
        assert cold.dispatch_seconds >= cold.events[0].seconds > 0
        assert led.drain().dispatch_seconds == 0.0       # reset
        f(x)
        one = led.drain().dispatch_seconds
        assert 0 < one < cold.dispatch_seconds           # warm: no compile
        g(x)
        led.drain()
        t0 = time.perf_counter()
        for _ in range(5):
            f(x)
            g(x)
        wall = time.perf_counter() - t0
        many = led.drain()
        assert many.events == ()
        assert 0 < many.dispatch_seconds <= wall         # both sites, summed
        fields = round_cost_fields(many, t_start=t0, seconds=wall)
        assert fields["dispatch_seconds"] == many.dispatch_seconds

    def test_off_mode_records_timing_only(self):
        led = CostLedger(aot_mode="off", cache_dir="")
        f = _instrumented(led, "o", lambda x: x - 1.0)
        f(jnp.ones((4,)))
        ev = led.all_events[0]
        assert ev.costs == {}
        assert "flops" not in ev.record()
        tot = led.totals()
        assert tot["compile_events"] == 1 and tot["sites"] == 1
        assert tot["cache_unknown"] == 1

    def test_full_mode_memory_analysis(self):
        led = CostLedger(aot_mode="full", cache_dir="")
        f = _instrumented(led, "m", lambda x: jnp.dot(x, x))
        f(jnp.ones((8, 8), jnp.float32))
        ev = led.all_events[0]
        # memory_analysis availability is backend-dependent: probe, and
        # when present assert the derived peak identity
        if "peak_device_bytes" in ev.costs:
            parts = sum(ev.costs.get(k, 0) for k in
                        ("argument_bytes", "output_bytes", "temp_bytes"))
            assert ev.costs["peak_device_bytes"] == parts > 0
        if "argument_bytes" in ev.costs:
            assert ev.costs["argument_bytes"] >= 8 * 8 * 4

    def test_aot_modes_constant(self):
        assert AOT_MODES == ("off", "lowered", "full")
        # bad mode falls back to the env default rather than raising
        assert CostLedger(aot_mode="bogus").aot_mode in AOT_MODES


# ----------------------------------------------------------------------
# engine integration: one real FedAvg run, shared by the assertions


@pytest.fixture(scope="module")
def cost_run(data, tmp_path_factory):
    d = tmp_path_factory.mktemp("cost_run")
    cfg = small_cfg(obs_dir=str(d), obs_sinks="jsonl,memory")
    t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, FedAvg())
    state, hist = t.run(log=lambda m: None)
    jsonls = [os.path.join(d, f) for f in os.listdir(d)
              if f.endswith(".jsonl")]
    assert len(jsonls) == 1
    return t, state, hist, jsonls[0]


class TestEngineIntegration:
    def test_rounds_carry_cost_fields(self, cost_run):
        t, _, hist, _ = cost_run
        assert t._ledger is not None  # default-on
        # the cold round(s) must show nonzero in-window compile seconds
        assert any(r.get("compile_seconds", 0) > 0 for r in hist)
        # executed-cost fields ride along when the backend produced them
        if any("flops" in e.costs for e in t._ledger.all_events):
            assert any(r.get("flops_round", 0) > 0 for r in hist)

    def test_compile_records_emitted_and_valid(self, cost_run):
        t, _, _, _ = cost_run
        mem = t.obs_recorder.memory
        compiles = [r for r in mem if r["event"] == "compile"]
        assert len(compiles) == len(t._ledger.all_events) > 0
        for c in compiles:
            validate_record(c)
            # every instrumented site: the round fns and the block
            # switch's device program (its compile lies outside a round)
            assert c["site"].startswith(("train_epoch[", "comm[",
                                         "block_vars["))
            assert c["compile_seconds"] > 0

    def test_summary_totals_match_events(self, cost_run):
        t, _, _, _ = cost_run
        mem = t.obs_recorder.memory
        summary = mem[-1]
        compiles = [r for r in mem if r["event"] == "compile"]
        assert summary["compile_events_total"] == len(compiles)
        assert summary["compile_seconds_total"] == pytest.approx(
            sum(c["compile_seconds"] for c in compiles))

    def test_compile_spans_nest_in_trace(self, cost_run):
        t, _, _, path = cost_run
        records = read_records(path)
        trace = to_chrome_trace(records)
        validate_chrome_trace(trace)
        cats = {e.get("cat") for e in trace["traceEvents"]}
        assert "compile" in cats

    def test_profile_on_real_run(self, cost_run):
        _, _, _, path = cost_run
        a = collect(read_records(path))
        assert a["compile_events"] > 0 and a["rounds"] > 0
        # acceptance: attribution covers round wall-clock within 5%
        assert a["attribution"]["coverage"] == pytest.approx(1.0,
                                                             abs=0.05)
        m = profile_metrics(read_records(path))
        assert m["compile_seconds"] > 0

    def test_compare_ingests_cost_metrics(self, cost_run):
        _, _, _, path = cost_run
        src = load_source(path)
        assert "compile_seconds" in src["metrics"]
        assert src["metrics"]["compile_seconds"] > 0


class TestBitwiseIdentity:
    def test_ledger_and_obs_toggles_do_not_move_math(self, data):
        def run(**kw):
            cfg = small_cfg(**kw)
            t = BlockwiseFederatedTrainer(TinyNet(), cfg, data, FedAvg())
            state, hist = t.run(log=lambda m: None)
            return jax.device_get(state.params), hist

        p_on, h_on = run(cost_ledger=True, obs_sinks="memory")
        p_off, h_off = run(cost_ledger=False, obs_sinks="memory")
        p_dark, _ = run(cost_ledger=True, obs_sinks="none")
        for a, b in zip(jax.tree_util.tree_leaves(p_on),
                        jax.tree_util.tree_leaves(p_off)):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(p_on),
                        jax.tree_util.tree_leaves(p_dark)):
            np.testing.assert_array_equal(a, b)
        assert [r["loss"] for r in h_on] == [r["loss"] for r in h_off]


# ----------------------------------------------------------------------
# profile CLI


class TestProfileCLI:
    def test_selftest_exit_0(self, capsys):
        assert profile_main(["--selftest"]) == 0
        assert "OK" in capsys.readouterr().out

    def test_selftest_math(self):
        assert "OK" in profile_selftest()

    def test_missing_file_exit_1(self, tmp_path, capsys):
        assert profile_main([str(tmp_path / "nope.jsonl")]) == 1
        assert "error" in capsys.readouterr().err

    def test_no_args_exit_2(self):
        with pytest.raises(SystemExit) as e:
            profile_main([])
        assert e.value.code == 2

    def test_report_and_json_on_real_run(self, cost_run, capsys):
        _, _, _, path = cost_run
        assert profile_main([path]) == 0
        out = capsys.readouterr().out
        assert "device-cost profile" in out and "attribution" in out
        assert profile_main([path, "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["compile_events"] > 0

    def test_reconciliation_hand_math(self):
        # 2 rounds, mean predicted wire bytes (1000 + 3000) / 2 = 2000;
        # comm site HLO bytes 5000 -> ratio 2.5
        records = [
            round_record(0, bytes_on_wire=1000, t_start=1.0),
            round_record(1, bytes_on_wire=3000, t_start=2.0),
            compile_record(site="comm[plain,blk=0]", trace_count=1,
                           hlo_bytes_accessed=5000.0),
            compile_record(site="train_epoch[blk=0]", trace_count=1,
                           hlo_bytes_accessed=9.0e9),
        ]
        a = collect(records)
        rows = {r["site"]: r for r in a["reconciliation"]}
        # train sites never show up in the wire reconciliation
        assert set(rows) == {"comm[plain,blk=0]"}
        row = rows["comm[plain,blk=0]"]
        assert row["predicted_wire_bytes"] == pytest.approx(2000.0)
        assert row["ratio"] == pytest.approx(2.5)
        assert row["fused"] is False


# ----------------------------------------------------------------------
# recorder: compile records + device-memory watermark


class TestRecorderCosts:
    def _recorder(self, d):
        rec = make_recorder("jsonl,memory", str(d), run_name="costrec",
                            engine="classifier", algorithm="fedavg")
        rec.open(config={"K": 2}, mesh_shape={"clients": 1})
        return rec

    def test_compile_event_spans_parent_to_run(self, tmp_path):
        rec = self._recorder(tmp_path)
        out = rec.compile_event({"site": "s", "compile_seconds": 0.1,
                                 "t_start": 5.0, "t_end": 5.1})
        validate_record(out)
        assert out["parent_span"] == rec.run_span_id
        rrec = rec.round({"round_index": 0, "round_seconds": 0.5,
                          "t_start": 5.2, "loss": 1.0})
        nested = rec.compile_event(
            {"site": "s", "compile_seconds": 0.05,
             "t_start": 5.3, "t_end": 5.35},
            parent_span=rrec["span_id"])
        assert nested["parent_span"] == rrec["span_id"]
        summary = rec.close()
        assert summary["compile_events_total"] == 2
        assert summary["compile_seconds_total"] == pytest.approx(0.15)
        records = read_records(rec.jsonl_path)
        validate_chrome_trace(to_chrome_trace(records))

    def test_memory_watermark_on_summary(self, tmp_path):
        rec = self._recorder(tmp_path)
        rec.round({"round_index": 0, "round_seconds": 0.5, "loss": 1.0,
                   "mem_peak_bytes_in_use": 3000,
                   "mem_bytes_in_use": 2000})
        rec.round({"round_index": 1, "round_seconds": 0.5, "loss": 0.9,
                   "mem_peak_bytes_in_use": 5000,
                   "mem_bytes_in_use": 1500})
        summary = rec.close()
        assert summary["mem_peak_bytes_watermark"] == 5000
        assert summary["mem_final_vs_peak_bytes"] == 5000 - 1500


# ----------------------------------------------------------------------
# satellites: compile-cache knobs + compare directions


class TestCompileCacheSatellite:
    def test_cache_stats_counts_entries(self, tmp_path):
        (tmp_path / "a").write_bytes(b"x" * 10)
        (tmp_path / "b").write_bytes(b"y" * 32)
        s = cache_stats(str(tmp_path))
        assert s["entries"] == 2 and s["total_bytes"] == 42
        assert s["dir"] == str(tmp_path)

    def test_cache_stats_never_raises(self):
        s = cache_stats("/nonexistent/fedtpu/cache")
        assert s["entries"] == 0 and s["total_bytes"] == 0

    def test_env_set_means_no_directory_is_set_in_code(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: whoever launched the process
        placed the cache (jax reads the variable itself at import); the
        helper must not call jax.config.update("jax_compilation_cache_dir")."""
        updates = []
        real = jax.config.update

        def spy(name, value):
            updates.append(name)
            real(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        before = jax.config.jax_compilation_cache_dir
        assert enable_persistent_compile_cache() == "/x"
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_compilation_cache_dir == before

    def test_env_unset_means_the_fixed_in_checkout_path(self, monkeypatch):
        prev = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        try:
            got = enable_persistent_compile_cache()
            assert got == os.path.join(repo, "tests", ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_compile_time_floor_follows_the_platform(self, monkeypatch):
        """CPU-only: sub-second programs stay out.  Any other platform
        list: every program is kept, so a second run adds no entries."""
        prev = jax.config.jax_persistent_cache_min_compile_time_secs
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        try:
            assert jax.config.jax_platforms == "cpu"      # conftest
            enable_persistent_compile_cache()
            assert jax.config.jax_persistent_cache_min_compile_time_secs \
                == 1.0
            with monkeypatch.context() as m:
                m.setattr(type(jax.config), "jax_platforms", "tpu,cpu",
                          raising=False)
                enable_persistent_compile_cache()
                assert (jax.config
                        .jax_persistent_cache_min_compile_time_secs) == 0.0
        finally:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", prev)

    def test_there_is_no_other_knob(self, monkeypatch):
        """No argument, no FEDTPU_* variable, no config field, no flag."""
        import inspect

        from federated_pytorch_test_tpu.drivers.common import build_parser

        assert not inspect.signature(
            enable_persistent_compile_cache).parameters
        assert "FEDTPU" not in inspect.getsource(compile_cache)
        assert not hasattr(FederatedConfig(), "compile_cache_dir")
        with pytest.raises(SystemExit):
            build_parser(FederatedConfig(), "prog").parse_args(
                ["--compile-cache-dir", "/x"])


class TestCompareDirections:
    @pytest.mark.parametrize("name,sign", [
        ("compile_seconds", -1), ("compile_seconds_cold", -1),
        ("peak_device_bytes", -1), ("utilization", +1),
        ("cache_hit_rate", +1)])
    def test_new_metric_directions(self, name, sign):
        assert _direction(name) == sign
