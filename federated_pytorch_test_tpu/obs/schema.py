"""Versioned record schema for run telemetry.

One run = one JSONL stream of ten event kinds:

- ``run_header``  — emitted once when a run (or resumed segment) opens:
  config snapshot, mesh shape, jax/backend versions, git rev.
- ``round``       — one per communication round (or per epoch on the
  no-consensus path): loop coordinates, loss/residuals/rho, wall-clock
  phase timings, ``bytes_on_wire``, guard/fault/quarantine counters,
  device memory stats where the backend reports them.
- ``summary``     — emitted once when the run closes (``completed`` or
  ``aborted``): totals and derived rates.
- ``span``        — one per phase/sub-span: a parent-linked node of the
  run -> round -> phase timeline; export with
  ``python -m federated_pytorch_test_tpu.obs.trace``.
- ``alert``       — a streaming-watchdog verdict (``obs/health.py``):
  which rule tripped, on which round, and what the configured
  ``--health-action`` did about it.
- ``compile``     — one per observed jit compile event
  (``obs/costs.py``): site label, compile wall-seconds, the site's
  cumulative trace count.
- ``control``     — one per control-plane decision (``control/``): a
  typed intervention from the deterministic policy engine or the
  restart supervisor — which knob, from/to values, scope, whether it was
  applied, and the telemetry that justified it.  Pure function of the
  recorded stream (no wall clock): replay with
  ``python -m federated_pytorch_test_tpu.control.replay``.
- ``client``      — at most one per communication round
  (``obs/clients.py``): the client-grain flight recorder.  Parallel
  length-K list fields carry per-client update norms, delta-vs-z
  distance, loss contribution, guard verdicts and quarantine state,
  fault tags, async staleness/admission, and membership — the round
  record's counters, un-aggregated.  Emitted right AFTER the round
  record it describes, so file order is the replay order.
- ``campaign``    — one per schedule-window transition (``campaign/``):
  the hour-quantized slice of the trace-driven soak schedule the engine
  applied from this round on.  Pure function of (campaign seed,
  round_index): ``control.replay`` re-derives the whole campaign from
  the run header's ``campaign_spec``.
- ``serve``       — one per communication round while the serving plane
  is on (``serve/``): the seeded traffic draw, the greedy pad-to-bucket
  batch plan, the hot-swap weights version, and advisory
  p50/p99/QPS/swap-gap/eval-stream telemetry.  The pure subset
  re-derives from the run header's ``serve_spec`` + round index alone.

Every record carries ``schema`` (the version) and validates via
:func:`validate_record`.  Unknown fields are ALLOWED (forward
compatibility — a newer writer must not break an older reader); known
fields are type-checked.  A stream whose subsystem is off carries none
of that subsystem's fields or kinds: absent means "not produced", never
zero (PARITY.md).

A field is declared once: its line in :data:`FIELDS` (name -> kinds,
types; the comment there says what it means) and, if it is
machine-dependent, one word in :data:`ADVISORY_FIELDS`.  Adding a field
never changes ``SCHEMA_VERSION``, because readers pass unknown fields;
removing, renaming or retyping one that a reader could count on (a
``REQUIRED`` one, or one every stream of its kind carried) does, and
``validate_record`` accepts every ``ver <= SCHEMA_VERSION``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

SCHEMA_VERSION = 18

EVENTS = ("run_header", "round", "summary", "span", "alert", "compile",
          "control", "client", "campaign", "serve")


class SchemaError(ValueError):
    """A record fails schema validation (missing/ill-typed field)."""


# bool is an int subclass: _INT/_NUM must not silently admit True/False
_NUM = (int, float)      # numeric (counters may arrive as float from psum)
_INT = (int,)
_STR = (str,)
_BOOL = (bool,)
_LIST = (list,)
_DICT = (dict,)
_ANY = None              # any JSON value

#: known fields -> (event kinds they may appear on, allowed types)
FIELDS: Dict[str, Any] = {
    # envelope
    "event":        (EVENTS, _STR),
    "schema":       (EVENTS, _INT),
    "run_id":       (EVENTS, _STR),
    "run_name":     (("run_header",), _STR),
    "engine":       (("run_header", "round", "compile"), _STR),
    "algorithm":    (("run_header", "round", "compile"), _STR),
    # header
    "time_unix":    (("run_header", "summary", "alert"), _NUM),
    "config":       (("run_header",), _DICT),
    "mesh_shape":   (("run_header",), _DICT),
    "devices":      (("run_header",), _INT),
    "local_devices": (("run_header",), _INT),
    "platform":     (("run_header",), _STR),
    "jax_version":  (("run_header",), _STR),
    "jaxlib_version": (("run_header",), _STR),
    "git_rev":      (("run_header",), _STR),
    "resumed":      (("run_header",), _BOOL),
    "rounds_prior": (("run_header",), _INT),
    "host":         (("run_header",), _STR),
    "pid":          (("run_header",), _INT),
    # round coordinates (spans and alerts are keyed to the same index the
    # XProf round_trace annotations use, so all three timelines correlate)
    "round_index":  (("round", "span", "alert", "compile", "control",
                      "client", "campaign", "serve"), _INT),
    "nloop":        (("round",), _INT),
    "block":        (("round",), _INT),
    "nadmm":        (("round",), _INT),
    "epoch":        (("round",), _INT),
    "model":        (("round",), _STR),   # CPC submodel name
    "N":            (("round",), _INT),
    "label":        (("round",), _STR),   # bench section tag
    # round measurements
    "loss":         (("round",), _NUM),
    "rho":          (("round",), _NUM),
    "dual_residual": (("round",), _NUM),
    "primal_residual": (("round",), _NUM),
    "accuracy":     (("round",), _LIST),
    "images":       (("round",), _INT),
    # wall-clock phase segments (host perf_counter; they sum to about
    # round_seconds: README "Observability" has the attribution caveat)
    "round_seconds": (("round",), _NUM),
    "stage_seconds": (("round",), _NUM),
    "train_seconds": (("round",), _NUM),
    "comm_seconds": (("round",), _NUM),
    "sync_seconds": (("round",), _NUM),
    "compute_seconds": (("round",), _NUM),
    "epoch_seconds": (("round",), _NUM),
    # cumulative jit retraces from the sentinel (--retrace-sentinel)
    "jit_retraces": (("round",), _INT),
    # jitted dispatches the host issued for the round (a fused round: 1)
    "host_dispatches": (("round",), _INT),
    # seconds inside the mid-run save call (async: snapshot + enqueue)
    "ckpt_write_seconds": (("round",), _NUM),
    # communication volume: the uplink model, K client payloads once
    "bytes_on_wire": (("round",), _INT),
    "bytes_dense":  (("round",), _INT),
    # --fused-collective: predicted device-to-device bytes of the packed
    # collective (every ppermute hop's payload + scale sidecar), NOT the
    # uplink model above.  --overlap-staging: host seconds pre-staging the
    # next round while the comm dispatch was in flight
    "bytes_fused":  (("round",), _INT),
    "overlap_seconds": (("round",), _NUM),
    # --overlap-round: host seconds enqueueing the NEXT round's first
    # epoch under this round's collective (0.0 on a block's last round)
    "overlap_dispatch_seconds": (("round",), _NUM),
    # the host timeline outside the round window (perf_counter
    # differences, written recorder on or off; with it on the same stamps
    # are the `block_switch` / `round_tail` spans under the run span).
    # block_switch_seconds: first round of a block visit only; top of the
    # block loop body to the round's t_start.  gap_seconds: every round
    # but the run's first; previous t_end to this t_start (the tail:
    # ledger drain, checkpoint, obs emission, log, on_round; plus the
    # switch where the block changed).  dispatch_seconds: host seconds
    # inside the instrumented jitted calls drained with the round
    # (obs/costs.py; a compile is inside it), absent with cost_ledger off.
    # dispatch_max_seconds / dispatch_max_site: the slowest single one of
    # those calls and its jit site.  dispatch_new_signatures: how many of
    # them added an entry to their site's jit cache without a retrace
    # (the same shapes under a new sharding or weak type: the dispatch
    # leaves jax's C++ fast path); absent at 0
    "block_switch_seconds": (("round",), _NUM),
    "gap_seconds": (("round",), _NUM),
    "dispatch_seconds": (("round",), _NUM),
    "dispatch_max_seconds": (("round",), _NUM),
    "dispatch_max_site": (("round",), _STR),
    "dispatch_new_signatures": (("round",), _INT),
    # bytes that block switch staged from host memory (same rounds as
    # block_switch_seconds): 0 but for a stateful compressor's fresh rows,
    # and 0 on a resumed segment's first round (the restore staged them)
    "block_switch_h2d_bytes": (("round",), _NUM),
    # the language-model trainer (train/lm_engine.py: round_fields), pure
    # functions of (seed, config, round coordinates).  tokens: consumed by
    # the round's local steps, over the clients.  block_kind: embed | gdn
    # | attn | mla | mlp | moe | head | mtp_mixer | mtp_moe.
    # moe_pairs_local: token-expert pairs that hit an expert this chip
    # holds, over layers, steps, clients.
    # moe_load_max_over_mean: most loaded held expert over the held mean,
    # worst layer, mean over steps.  moe_dropped: pairs that found no row
    # in the sorted pair buffer (ops/moe.py); 0, or `correct` fails.
    # moe_fill_share: pairs that found a row / rows of the steps' pair
    # buffers, over layers, steps, clients: the share of the buffer that
    # dispatch and combine visit (0.0 for a model without experts).
    # mtp_loss: the multi-token-prediction term of the loss, unweighted,
    # summed over the round's steps and clients as `loss` is (0.0 where
    # the model has no such layer).  mhc_marginal_err: the largest
    # |row sum - 1| or |column sum - 1| of a step's hyper-connection
    # mixing matrices, mean over the round's steps (0.0 for a model
    # without streams).  moe_top1_weight_mean: the mean routing weight of
    # the pairs that found a row, over layers, steps, clients, of a model
    # that reports the weights' sum (at one expert a token: the chosen
    # expert's probability; 1 / experts says the router does not tell
    # tokens apart; 0.0 where the model reports none).
    # router_state_rms: the RMS of the state a model's routers hand from
    # layer to layer, after the last layer, mean over the round's steps
    # (0.0 for a model whose routers keep none).  gdn_neg_beta_share: the
    # share of (token, head) pairs of the Gated DeltaNet layers whose beta
    # is above 1 (the transition's eigenvalue along k is negative), mean
    # over the round's steps (0.0 for a model whose beta stays below 1)
    "tokens":       (("round",), _INT),
    "block_kind":   (("round",), _STR),
    "moe_pairs_local": (("round",), _INT),
    "moe_load_max_over_mean": (("round",), _NUM),
    "moe_dropped":  (("round",), _INT),
    "moe_fill_share": (("round",), _NUM),
    "mtp_loss":     (("round",), _NUM),
    "mhc_marginal_err": (("round",), _NUM),
    "moe_top1_weight_mean": (("round",), _NUM),
    "router_state_rms": (("round",), _NUM),
    "gdn_neg_beta_share": (("round",), _NUM),
    # what ran the delta rule's chunk recurrence (ops/gated_delta.py:
    # plan): pallas | pallas_interpret | xla.  Names the machine's path,
    # not the trajectory, hence advisory
    "gdn_scan_impl": (("round",), _STR),
    # the same for the attention core (ops/flash_attention.py: plan)
    "attn_impl": (("round",), _STR),
    # the same for the hyper-connections' passes over the streams
    # (ops/hyper_connections.py: plan)
    "mhc_impl": (("round",), _STR),
    # the same for the head's loss (ops/head_loss.py: IMPL): fused
    "head_impl": (("round",), _STR),
    # fault / guard counters
    "guard_trips":  (("round",), _NUM),
    "guard_norm_mean": (("round",), _NUM),
    "n_ok":         (("round",), _NUM),
    "n_active":     (("round",), _NUM),
    "n_comm":       (("round",), _INT),
    "quarantined":  (("round",), _INT),
    "fault_dropped": (("round",), _INT),
    "fault_straggled": (("round",), _INT),
    "fault_corrupted": (("round",), _INT),
    # elastic federation churn ledger (join=/leave= fault families): live
    # members after this round's tick, and this round's transitions
    "members_active": (("round",), _INT),
    "joined":       (("round",), _INT),
    "left":         (("round",), _INT),
    # buffered-async federation (--async-rounds): the mode stamp,
    # deliveries this round, those staler than max_staleness (discarded),
    # updates still in flight, admitted deliveries by staleness 0..max
    "async_mode":   (("round",), _BOOL),
    "max_staleness": (("round",), _INT),
    "async_arrived": (("round",), _INT),
    "admission_rejected": (("round",), _INT),
    "buffer_depth": (("round",), _INT),
    "staleness_hist": (("round",), _LIST),
    # device memory (absent when the backend reports none, e.g. CPU)
    "mem_bytes_in_use": (("round",), _INT),
    "mem_peak_bytes_in_use": (("round",), _INT),
    # compile ledger (obs/costs.py).  The round field sums the compile
    # events inside the round's window; `compile` records carry each one
    "site":         (("compile",), _STR),     # jit site label
    "compile_seconds": (("round", "compile"), _NUM),
    "trace_count":  (("compile",), _INT),     # cumulative; 1 == cold
    # span tracing (obs/trace.py).  `span_id`/`parent_span` ride on
    # round and compile records too; `t_start`/`t_end` are HOST MONOTONIC
    # (time.perf_counter) stamps taken at the phase boundaries the
    # engines already time (their `_obs_sync` points: no new syncs)
    "span_id":      (("run_header", "round", "span", "compile"), _STR),
    "parent_span":  (("round", "span", "compile"), _STR),
    "t_start":      (("round", "span", "compile"), _NUM),
    "t_end":        (("round", "span", "compile"), _NUM),
    "name":         (("span",), _STR),        # phase/sub-span label
    "cat":          (("span",), _STR),        # run|round|phase|comm|ckpt|...
    # streaming watchdog verdicts (obs/health.py)
    "rule":         (("alert",), _STR),
    "severity":     (("alert",), _STR),       # warn|fatal
    "message":      (("alert",), _STR),
    "observed":     (("alert", "control"), _NUM),  # triggering value
    "threshold":    (("alert", "control"), _NUM),
    "streak":       (("alert", "control"), _INT),  # consecutive bad rounds
    "action":       (("alert",), _STR),       # health_action at trip time
    # closed-loop control plane (control/).  NO time_unix on purpose: a
    # control record is a pure function of recorded telemetry and the
    # round index, so control.replay reproduces it bit-exactly.
    "source":       (("control",), _STR),     # policy|supervisor
    "intervention": (("control",), _STR),     # typed action name
    "param":        (("control",), _STR),     # cfg knob it targets
    "from_value":   (("control",), _ANY),
    "to_value":     (("control",), _ANY),
    "reason":       (("control",), _STR),
    "mode":         (("control",), _STR),     # observe|act
    "applied":      (("control",), _BOOL),    # engine took the action
    "scope":        (("control",), _STR),     # round|block|restart
    "attempt":      (("control",), _INT),     # supervisor: restart count
    "backoff_seconds": (("control",), _NUM),  # supervisor: seeded backoff
    "ladder_stage": (("control",), _INT),     # supervisor: degradation rung
    # client-grain flight recorder (obs/clients.py).  All list fields are
    # parallel, length `clients`, indexed by client id; each is present
    # only when its subsystem ran.
    "clients":      (("client",), _INT),      # cohort size K
    "update_norm":  (("client",), _LIST),     # ||x_k - z|| pre-guard
    "dist_z":       (("client",), _LIST),     # ||x_k - z_new|| post-fold
    "loss_client":  (("client",), _LIST),
    "weight":       (("client",), _LIST),     # mean weight (partic+stale)
    "active":       (("client",), _LIST),     # 0/1 contributed this round
    "guard_ok":     (("client",), _LIST),     # guard verdicts (guard on)
    "quarantine":   (("client",), _LIST),     # rounds remaining
    "dropped":      (("client",), _LIST),     # fault tags this round
    "straggled":    (("client",), _LIST),
    "corrupted":    (("client",), _LIST),
    "staleness":    (("client",), _LIST),     # async: rounds stale
    "admitted":     (("client",), _LIST),     # async: admission outcome
    "members":      (("client",), _LIST),     # churn roster after tick
    "registry_ids": (("client",), _LIST),     # --population: slot -> rid
    "payload_bytes": (("client",), _INT),     # uplink bytes/participant
    # soak-campaign schedule windows (campaign/).  One per window
    # TRANSITION, right after the round record it rides with; no
    # time_unix — every field is a pure function of (campaign seed,
    # round_index), re-derived bit-exactly by control.replay from the
    # header config's campaign_spec.
    "virtual_seconds": (("campaign",), _NUM),  # round_index * round secs
    "arrival_frac": (("campaign",), _NUM),     # diurnal curve, [0, 1]
    "drop_p":       (("campaign",), _NUM),     # derived family probs
    "straggle_p":   (("campaign",), _NUM),
    "corrupt_p":    (("campaign",), _NUM),
    "join_p":       (("campaign",), _NUM),
    "leave_p":      (("campaign",), _NUM),
    "storm":        (("campaign",), _BOOL),    # seeded tag-73 event live
    "burst":        (("campaign",), _BOOL),    # seeded tag-79 event live
    "preempt_now":  (("campaign",), _BOOL),    # deterministic preempt_at
    "phase":        (("campaign",), _STR),     # trough|shoulder|peak|...
    # serving plane (serve/).  Pure subset first (re-derived by
    # control.replay from the header serve_spec + round index), then the
    # advisory timing/eval telemetry; no time_unix on the record —
    # wall-clock facts ride ONLY in advisory fields.
    "weights_version": (("serve",), _INT),     # 1 + ridx // swap_every
    "requests":     (("serve",), _INT),        # seeded traffic draw (tag 83)
    "batches":      (("serve",), _INT),        # dispatched micro-batches
    "padded_slots": (("serve",), _INT),        # bucket slots left empty
    "padding_waste_frac": (("serve",), _NUM),  # padded / total slots
    "drift_injected": (("serve",), _BOOL),     # ridx >= drift_at
    "swap":         (("serve",), _BOOL),       # ridx % swap_every == 0
    "serve_p50_ms": (("serve",), _NUM),        # advisory from here down
    "serve_p99_ms": (("serve",), _NUM),
    "serve_qps":    (("serve",), _NUM),
    "swap_gap_seconds": (("serve",), _NUM),    # double-buffer publish gap
    "serve_accuracy": (("serve",), _NUM),      # eval-stream live accuracy
    "drift_score":  (("serve",), _NUM),        # 1 - acc/EMA, floored at 0
    "forced_refresh": (("serve",), _BOOL),     # control-plane republish
    # summary totals / rates
    "status":       (("summary",), _STR),
    "rounds":       (("summary",), _INT),
    "total_seconds": (("summary",), _NUM),
    "round_seconds_total": (("summary",), _NUM),
    "stage_seconds_total": (("summary",), _NUM),
    "comm_seconds_total": (("summary",), _NUM),
    "bytes_on_wire_total": (("summary",), _INT),
    "bytes_dense_total": (("summary",), _INT),
    "images_total": (("summary",), _INT),
    "guard_trips_total": (("summary",), _NUM),
    "fault_dropped_total": (("summary",), _INT),
    "fault_straggled_total": (("summary",), _INT),
    "fault_corrupted_total": (("summary",), _INT),
    "quarantined_last": (("summary",), _INT),
    "loss_first":   (("summary",), _NUM),
    "loss_final":   (("summary",), _NUM),
    "rounds_per_sec": (("summary",), _NUM),
    "images_per_sec": (("summary",), _NUM),
    "comm_overhead_frac": (("summary",), _NUM),
    "compression_savings_frac": (("summary",), _NUM),
    "alerts_total": (("summary",), _INT),
    "interventions_total": (("summary",), _INT),
    # compile ledger + device-memory watermark over the run
    "compile_events_total": (("summary",), _INT),
    "compile_seconds_total": (("summary",), _NUM),
    "mem_peak_bytes_watermark": (("summary",), _INT),
    "mem_final_vs_peak_bytes": (("summary",), _INT),
}

# ------------------------------------------------------------------- #
# Machine-readable determinism contract (graftcheck JG117-JG121).
#
# The contract pass (analysis/contracts.py) reads the tables below from
# this file's source via ast.literal_eval — it never imports this module
# — so each MUST stay a pure literal (no comprehensions, no function
# calls, no name references).

#: fields that are wall-clock / host-measured / model-dependent by
#: design and therefore exempt from the replay contract: they may be fed
#: by time.* or measurement state, and control/replay.py never compares
#: them.  Everything NOT in this tuple (or ENVELOPE_FIELDS) is a core
#: field: a pure function of (seed, config, round coordinates), and
#: JG117/JG119/JG121 flag any entropy, iteration-order or rogue-PRNG
#: taint flowing into it.  PARITY.md pins this list as part of the
#: contract — an addition needs a PARITY note.
ADVISORY_FIELDS = (
    # wall-clock stamps + per-round host timings
    "time_unix", "round_seconds", "stage_seconds", "train_seconds",
    "comm_seconds", "sync_seconds", "compute_seconds", "epoch_seconds",
    "ckpt_write_seconds", "overlap_seconds", "overlap_dispatch_seconds",
    "compile_seconds", "t_start", "t_end",
    # host timeline outside the round window
    "block_switch_seconds", "gap_seconds", "dispatch_seconds",
    "dispatch_max_seconds", "dispatch_max_site", "dispatch_new_signatures",
    "block_switch_h2d_bytes",
    # which implementation this backend took for the recurrence, for the
    # attention core, for the hyper-connections and for the head's loss
    "gdn_scan_impl", "attn_impl", "mhc_impl", "head_impl",
    # serving-plane latency/throughput telemetry
    "serve_p50_ms", "serve_p99_ms", "serve_qps", "swap_gap_seconds",
    "serve_accuracy", "drift_score", "forced_refresh",
    # summary wall-clock totals and derived rates
    "total_seconds", "round_seconds_total", "stage_seconds_total",
    "comm_seconds_total", "compile_seconds_total",
    "rounds_per_sec", "images_per_sec", "comm_overhead_frac",
    # bench.py's capture timestamp: a diagnostic, never replay-checked
    "captured_utc",
)

#: run/record identity fields stamped by the recorder envelope — host
#: facts (pid, git rev, jax versions) and the uuid-derived span ids.
#: They identify *which* run produced a stream; replay compares streams
#: only within one run, so envelope fields are outside the taint rules.
ENVELOPE_FIELDS = (
    "event", "schema", "run_id", "run_name", "span_id", "parent_span",
    "engine", "algorithm", "host", "pid", "git_rev", "devices",
    "local_devices", "platform", "jax_version", "jaxlib_version",
    "resumed", "rounds_prior", "config", "mesh_shape",
)

#: the stable core of each kind (JG118: every kind has a non-empty one)
REQUIRED = {
    "run_header": ("event", "schema", "run_id", "engine", "time_unix"),
    "round": ("event", "schema", "run_id", "round_index", "engine",
              "round_seconds"),
    "summary": ("event", "schema", "run_id", "status", "rounds"),
    "span": ("event", "schema", "run_id", "span_id", "name", "t_start",
             "t_end"),
    "alert": ("event", "schema", "run_id", "rule", "round_index"),
    "compile": ("event", "schema", "run_id", "site", "compile_seconds"),
    "control": ("event", "schema", "run_id", "round_index", "source",
                "intervention"),
    "client": ("event", "schema", "run_id", "round_index", "clients"),
    "campaign": ("event", "schema", "run_id", "round_index",
                 "virtual_seconds"),
    "serve": ("event", "schema", "run_id", "round_index",
              "weights_version", "requests"),
}

#: out-of-band diagnostic emissions that look like records (they carry
#: an "event" key for grep-ability) but never enter a telemetry stream —
#: JG118's emit-coverage check allows them without a replay checker
DIAGNOSTIC_KINDS = ("sink_degraded",)

#: checkpoint-meta key namespaces reserved for one owner module (JG120):
#: a namespace ending in "_" is a prefix, anything else an exact key;
#: the owner tuple lists module-path suffixes allowed to write it
RESERVED_META_NAMESPACES = (
    ("pop_", ("population.registry",)),
    ("geom_", ("utils.checkpoint",)),
    ("members", ("utils.checkpoint",)),
)


def json_safe(obj):
    """Coerce ``obj`` into JSON-serialisable types.

    numpy arrays/scalars become lists/Python scalars, tuples become
    lists, dataclasses become dicts, anything else falls back to
    ``repr`` — so a config snapshot or an ``accuracy`` ndarray can ride
    in a record without the caller caring.
    """
    import numpy as np

    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, dict):
        return {str(k): json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set)):
        return [json_safe(v) for v in obj]
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return json_safe(dataclasses.asdict(obj))
    return repr(obj)


def _type_ok(value, types) -> bool:
    if types is _ANY or types is None:
        return True
    if isinstance(value, bool) and bool not in types:
        return False            # bool passes isinstance(int) checks
    if isinstance(value, types):
        return True
    # json round-trips ints inside float fields and vice versa
    if float in types and isinstance(value, int):
        return True
    return False


def validate_record(rec: Dict[str, Any]) -> Dict[str, Any]:
    """Validate one record against the schema; returns it unchanged.

    Raises :class:`SchemaError` on: non-dict input, unknown/missing
    ``event``, missing ``schema`` version or one newer than this reader,
    a missing required field, or a known field of the wrong type.
    Unknown fields pass (forward compatibility).
    """
    if not isinstance(rec, dict):
        raise SchemaError(f"record must be a dict, got {type(rec).__name__}")
    event = rec.get("event")
    if event not in EVENTS:
        raise SchemaError(f"unknown event {event!r}; expected one of {EVENTS}")
    ver = rec.get("schema")
    if not isinstance(ver, int) or isinstance(ver, bool) or ver < 1:
        raise SchemaError(f"bad schema version {ver!r}")
    if ver > SCHEMA_VERSION:
        raise SchemaError(
            f"record schema v{ver} is newer than this reader "
            f"(v{SCHEMA_VERSION})")
    for name in REQUIRED[event]:
        if rec.get(name) is None:
            raise SchemaError(f"{event} record missing required {name!r}")
    for name, value in rec.items():
        spec = FIELDS.get(name)
        if spec is None or value is None:
            continue                       # unknown field / JSON null: pass
        kinds, types = spec
        if event not in kinds:
            raise SchemaError(
                f"field {name!r} is not valid on a {event!r} record")
        if not _type_ok(value, types):
            raise SchemaError(
                f"field {name!r} on {event!r} has type "
                f"{type(value).__name__}, expected one of "
                f"{tuple(t.__name__ for t in types)}")
    return rec
