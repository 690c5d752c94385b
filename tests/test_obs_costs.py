"""Compile-ledger tests (obs/costs.py).

Covers the ``compile`` record kind, the CostLedger's compile detection
and dispatch timer on the CPU backend (and that it asks jax for nothing
more: no second lowering), compile-span nesting under the Chrome-trace
validator, and bitwise math identity with the ledger on/off.
"""

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
from federated_pytorch_test_tpu.obs.costs import (
    CompileEvent,
    CostLedger,
    RoundCosts,
    round_cost_fields,
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
# the compile record kind and the round's compile fields


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
            trace_count=1, span_id="ab12", parent_span="cd34",
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
        with pytest.raises(SchemaError, match="site"):
            validate_record(compile_record(site=3))
        with pytest.raises(SchemaError, match="compile_seconds"):
            validate_record(compile_record(compile_seconds="slow"))
        with pytest.raises(SchemaError, match="trace_count"):
            validate_record(compile_record(trace_count=1.5))

    def test_unknown_fields_pass_on_compile(self):
        # additive contract: a v7 writer's extra field must not break us
        validate_record(compile_record(totally_new_field_v9="future"))

    def test_round_cost_fields_additive(self):
        validate_record(round_record(
            compile_seconds=0.5, dispatch_seconds=0.51))

    def test_cost_fields_event_gated(self):
        # site belongs to compile records only
        with pytest.raises(SchemaError, match="not valid"):
            validate_record(round_record(site="train_epoch[blk=0]"))
        # trace_count (per-site) belongs to compile, not round
        with pytest.raises(SchemaError, match="not valid"):
            validate_record(round_record(trace_count=1))

    def test_summary_cost_totals(self):
        validate_record({"event": "summary", "schema": SCHEMA_VERSION,
                         "run_id": "t" * 8, "status": "completed",
                         "rounds": 2, "time_unix": 1.0,
                         "compile_events_total": 3,
                         "compile_seconds_total": 0.42,
                         "mem_peak_bytes_watermark": 1 << 20,
                         "mem_final_vs_peak_bytes": 1 << 10})


# ----------------------------------------------------------------------
# ledger unit behavior (no jax dispatch needed)


class TestLedgerUnit:
    def test_round_cost_fields_windowing(self):
        ev_in = CompileEvent(site="a", seconds=0.2, t_start=10.2,
                             t_end=10.4, trace_count=1)
        ev_out = CompileEvent(site="b", seconds=0.3, t_start=11.5,
                              t_end=11.8, trace_count=1)
        costs = RoundCosts(events=(ev_in, ev_out))
        fields = round_cost_fields(costs, t_start=10.0, seconds=1.0)
        # out-of-window event excluded; absent data omitted, not zeroed
        assert fields == {"compile_seconds": pytest.approx(0.2)}

    def test_round_cost_fields_dispatch_seconds(self):
        # written when the window held a dispatch, omitted (not zeroed)
        # when it held none or the field was never set
        costs = RoundCosts(events=(), dispatch_seconds=0.0125)
        assert round_cost_fields(costs, t_start=0.0, seconds=1.0) == {
            "dispatch_seconds": pytest.approx(0.0125)}
        bare = RoundCosts(events=())
        assert bare.dispatch_seconds == 0.0
        assert round_cost_fields(bare, t_start=0.0, seconds=1.0) == {}
        validate_record(round_record(dispatch_seconds=0.0125))

    def test_event_record_fields(self):
        ev = CompileEvent(site="s", seconds=0.1, t_start=0.0, t_end=0.1,
                          trace_count=2)
        assert ev.record() == {"site": "s", "compile_seconds": 0.1,
                               "t_start": 0.0, "t_end": 0.1,
                               "trace_count": 2}
        assert ev.record(round_index=3)["round_index"] == 3
        validate_record(compile_record(**ev.record(round_index=3)))


# ----------------------------------------------------------------------
# ledger on real jit dispatches (CPU backend; availability probed)

def _instrumented(led, site, fn):
    return led.instrument(jax.jit(led.mark(fn, site)), site)


class TestLedgerJit:
    def test_cold_compile_detected_once(self):
        led = CostLedger()
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
        assert led.totals() == {"compile_events": 1, "sites": 1,
                                "compile_seconds": ev.seconds}

    def test_a_compile_lowers_once(self):
        """The ledger times the dispatch and counts the trace; it never
        asks jax to lower (or compile) the program a second time."""

        class CountsLower:
            def __init__(self, jfn):
                self.jfn, self.lowered = jfn, 0

            def __call__(self, *args):
                return self.jfn(*args)

            def lower(self, *args, **kwargs):
                self.lowered += 1
                return self.jfn.lower(*args, **kwargs)

        led = CostLedger()
        jfn = CountsLower(jax.jit(led.mark(lambda x: x * x + 1.0, "sq")))
        f = led.instrument(jfn, "sq")
        f(jnp.ones((8,)))
        f(jnp.ones((8,)))
        assert jfn.lowered == 0
        (ev,) = led.all_events
        assert ev.seconds > 0 and ev.trace_count == 1
        assert set(ev.record()) == {"site", "compile_seconds", "t_start",
                                    "t_end", "trace_count"}

    def test_retrace_on_new_shape(self):
        led = CostLedger()
        f = _instrumented(led, "s", lambda x: x + 1.0)
        f(jnp.ones((4,)))
        f(jnp.ones((5,)))
        f(jnp.ones((4,)))  # cached executable, no retrace
        assert [e.trace_count for e in led.all_events] == [1, 2]

    def test_drain_resets_window(self):
        led = CostLedger()
        f = _instrumented(led, "d", lambda x: x * x)
        f(jnp.ones((16,)))
        rc = led.drain()
        assert len(rc.events) == 1
        # drained: next window starts empty
        rc2 = led.drain()
        assert rc2.events == () and rc2.dispatch_seconds == 0.0
        # warm dispatches add no event; the run history keeps the first
        f(jnp.ones((16,)))
        assert led.drain().events == () and len(led.all_events) == 1

    def test_dispatch_seconds_accumulates_and_resets(self):
        """Every instrumented call's own timer adds up, compiling or not;
        ``drain`` hands it out and resets."""
        led = CostLedger()
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


# ----------------------------------------------------------------------
# new argument signatures and the window's slowest call


def _one_device_shardings():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("clients",))
    return NamedSharding(mesh, P("clients")), NamedSharding(mesh, P())


def _second_sharding(f):
    """The same array under the two specs a one-device mesh has: what
    ``init_state`` stages and what every program returns."""
    by_clients, replicated = _one_device_shardings()
    x = jnp.ones((8, 4))
    f(jax.device_put(x, by_clients))
    return lambda: f(jax.device_put(x, replicated))


def _second_weak_type(f):
    f(1.0)
    return lambda: f(jnp.asarray(1.0))


class TestNewSignatures:
    @pytest.mark.parametrize("second", [_second_sharding, _second_weak_type])
    def test_a_second_signature_is_counted_and_is_no_compile(self, second):
        led = CostLedger()
        again = second(_instrumented(led, "s", lambda x: x * 2.0))
        cold = led.drain()
        assert len(cold.events) == 1 and cold.new_signatures == 0
        again()
        met = led.drain()
        assert met.events == () and met.new_signatures == 1
        assert len(led.all_events) == 1
        fields = round_cost_fields(met, t_start=0.0, seconds=1e9)
        assert fields["dispatch_new_signatures"] == 1
        assert "compile_seconds" not in fields
        validate_record(round_record(**fields))
        again()                                 # the signature is known now
        assert led.drain().new_signatures == 0

    @pytest.mark.parametrize("calls", [
        pytest.param([(4,)], id="cold"),
        pytest.param([(4,), (5,)], id="retrace"),
        pytest.param([(4,), (4,)], id="warm"),
    ])
    def test_a_compile_is_no_new_signature(self, calls):
        led = CostLedger()
        f = _instrumented(led, "s", lambda x: x + 1.0)
        for shape in calls:
            f(jnp.ones(shape))
        assert led.drain().new_signatures == 0

    def test_a_site_without_a_cache_counts_none(self):
        """A sanitized site is a plain wrapper: no ``_cache_size``."""
        led = CostLedger()
        jfn = jax.jit(led.mark(lambda x: x * x, "p"))
        f = led.instrument(lambda *a: jfn(*a), "p")
        by_clients, replicated = _one_device_shardings()
        for sh in (by_clients, replicated):
            f(jax.device_put(jnp.ones((8, 4)), sh))
        assert led.drain().new_signatures == 0

    def test_slowest_call_and_its_site(self):
        led = CostLedger()
        f = _instrumented(led, "fast", lambda x: x * 3.0)
        g = _instrumented(led, "slow", lambda x: x - 3.0)
        x = jnp.ones((16,))
        f(x)
        led.drain()
        for _ in range(3):
            f(x)
        g(x)                                    # compiles: the slowest
        rc = led.drain()
        assert rc.slowest[0] == "slow"
        assert rc.slowest[1] == rc.events[0].seconds
        assert 0 < rc.slowest[1] <= rc.dispatch_seconds
        fields = round_cost_fields(rc, t_start=0.0, seconds=1e9)
        assert fields["dispatch_max_site"] == "slow"
        assert fields["dispatch_max_seconds"] == rc.slowest[1]
        validate_record(round_record(**fields))

    def test_both_reset_on_drain_and_are_omitted_at_zero(self):
        led = CostLedger()
        again = _second_sharding(_instrumented(led, "s", lambda x: x * 2.0))
        again()
        assert led.drain().new_signatures == 1
        empty = led.drain()
        assert empty.new_signatures == 0 and empty.slowest == ("", 0.0)
        assert round_cost_fields(empty, t_start=0.0, seconds=1.0) == {}
        again()
        warm = round_cost_fields(led.drain(), t_start=0.0, seconds=1.0)
        assert set(warm) == {"dispatch_seconds", "dispatch_max_seconds",
                             "dispatch_max_site"}

    @pytest.mark.parametrize("field,bad", [
        ("dispatch_new_signatures", 1.5), ("dispatch_max_seconds", "slow"),
        ("dispatch_max_site", 3)])
    def test_the_fields_are_typed(self, field, bad):
        with pytest.raises(SchemaError, match=field):
            validate_record(round_record(**{field: bad}))


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
        assert all(r["dispatch_seconds"] > 0 for r in hist)
        # the slowest call of each round and its site; written with the
        # recorder on (here) and off (TestBitwiseIdentity)
        assert all(0 < r["dispatch_max_seconds"] <= r["dispatch_seconds"]
                   for r in hist)
        assert all(r["dispatch_max_site"].startswith(
            ("train_epoch[", "comm[", "block_vars[")) for r in hist)

    def test_a_one_device_mesh_meets_a_second_signature(self, data):
        """A block's first round compiles; on a one-device mesh its
        second meets the epoch program's second argument signature (the
        state comes back from the round's programs under another spec
        than ``init_state``'s): nothing compiles, the counter says so."""
        t = BlockwiseFederatedTrainer(
            TinyNet(), small_cfg(num_devices=1, obs_sinks="none"), data,
            FedAvg())
        _, hist = t.run(log=lambda m: None)
        assert [r.get("dispatch_new_signatures", 0) for r in hist] == [
            0 if "compile_seconds" in r else 1 for r in hist]
        assert sum("compile_seconds" in r for r in hist) == 2
        assert all(r["dispatch_max_site"].startswith("train_epoch[")
                   for r in hist)

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
        # the dispatch fields come from the ledger, recorder on or off
        _, h_dark = run(cost_ledger=True, obs_sinks="none")
        assert all("dispatch_max_site" in r for r in h_dark)
        assert not any(k.startswith("dispatch_") for r in h_off for k in r)


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
# satellite: compile-cache knobs


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
