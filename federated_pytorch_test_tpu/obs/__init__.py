"""Observability: structured run telemetry for every engine.

- :mod:`.schema`   — versioned run_header / round / summary records.
- :mod:`.sinks`    — JSONL / in-memory emitters.
- :mod:`.recorder` — the per-run emitter the engines thread through.
- :mod:`.report`   — ``python -m federated_pytorch_test_tpu.obs.report``.
- :mod:`.trace`    — span timeline → Chrome trace-event JSON exporter.
- :mod:`.health`   — streaming anomaly watchdog (``--health-action``).
- :mod:`.compare`  — cross-run regression CLI (CI gate).
- :mod:`.costs`    — per-jit-site compile and dispatch ledger.
- :mod:`.scopes`   — the program's ``jax.named_scope`` names, one table.
- :mod:`.clients`  — client-grain flight recorder: per-client ledgers,
  deterministic anomaly ranking, cohort rollups
  (``python -m federated_pytorch_test_tpu.obs.clients``).

See README "Observability" for the artifact format and how XProf traces
(``--profile-dir`` + per-round ``StepTraceAnnotation``) correlate with
the JSONL timeline.
"""

from federated_pytorch_test_tpu.obs.clients import (  # noqa: F401
    ClientLedger,
    client_round_fields,
    ledger_from_records,
    summarize_clients,
)
from federated_pytorch_test_tpu.obs.costs import (  # noqa: F401
    CostLedger,
    round_cost_fields,
)
from federated_pytorch_test_tpu.obs.health import (  # noqa: F401
    HEALTH_ACTIONS,
    HealthMonitor,
    RunHealthAbort,
    monitor_from_config,
)
from federated_pytorch_test_tpu.obs.recorder import (  # noqa: F401
    RunRecorder,
    device_memory_stats,
    git_rev,
    make_recorder,
)
from federated_pytorch_test_tpu.obs.schema import (  # noqa: F401
    SCHEMA_VERSION,
    SchemaError,
    json_safe,
    validate_record,
)
from federated_pytorch_test_tpu.obs.sinks import (  # noqa: F401
    JsonlSink,
    MemorySink,
    Sink,
    make_sinks,
)
from federated_pytorch_test_tpu.obs.trace import (  # noqa: F401
    to_chrome_trace,
    validate_chrome_trace,
)
