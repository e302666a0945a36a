"""First-class telemetry subsystem.

Four pillars:

  * `metrics`  — `MetricAccumulator`, an on-device running-statistics
    pytree carried through the jitted train step (zero host syncs on hot
    steps; one device-to-host fetch per flush interval), and the JSONL
    `MetricLogger` grown with schema'd records (run_id, code_rev,
    backend, host metadata).
  * `runtime`  — `RetraceWatchdog`: jit-cache-size / compile-event /
    device-memory snapshots per flush, with a loud structured warning
    when a step function retraces after warmup; and the compile log
    (`compile_log`, `compile_seconds`): what JAX reports of every
    trace, lowering, compile and cache load, by function.
  * `timing`   — `PhaseTimer`: host-side wall-clock reservoirs with
    windowed p50/p95/max per phase, plus `named_scope` / `profile_trace`
    for device-side (xprof) attribution of the model phases.
  * `report`   — aggregate one or more telemetry JSONL streams into
    one summary per run. CLI: `scripts/obs_report.py`.

Two attribution pillars joined in PR 6:

  * `costs`    — HLO cost ledger: any lowered/AOT executable ->
    schema'd `cost` record (flops/bytes via `cost_analysis()` with an
    HLO-parse fallback, peak HBM split argument/output/temp, per-class
    collective bytes). Consumed by the training step
    factories, `InferenceEngine.warmup` (one record per shape bucket),
    and scripts/width_table.py; enforced by scripts/perf_gate.py.
  * `profiling` — the one trace reducer: the profiler's `.xplane.pb`
    (parsed as an `XSpace` through the few message types the module
    declares itself: the op_name is a stat of the event's metadata, which
    `jax.profiler.ProfileData` does not show; no tensorboard) -> device
    seconds per leaf of `MODEL_SCOPES`, per phase, per kernel role and
    degree pair, with coverage -> schema'd `profile` record.

Two fleet pillars joined in PR 16:

  * `tracing`  — request tracing across the serving fleet: `Tracer`
    records spans (admit/queue_wait/batch_fill/dispatch/device_run/
    retry/redispatch/probe/rollout) under globally-unique trace ids
    minted at `FleetRouter.submit`; span-tree analysis + the
    completeness invariant land in the schema'd `trace` record.
  * `slo`      — mergeable fixed-boundary latency histograms (merged
    fleet percentiles exact by construction) + `SLOAggregator`, which
    folds heartbeat-scraped host stats into the schema'd `slo` record
    (availability, error-budget burn, breaker dwell, rollouts).
    CLI: `scripts/slo_report.py`; gate: `make slo-smoke`.

`schema` holds the record contract both producers and the validator
share (`make obs-smoke` gates on it).
"""
from .metrics import (  # noqa: F401
    MetricAccumulator, MetricLogger, collect_run_meta, merge_windows,
)
from .runtime import (  # noqa: F401
    RetraceWarning, RetraceWatchdog, compile_log, compile_seconds,
    device_memory_stats,
)
from .timing import (  # noqa: F401
    MODEL_SCOPES, PhaseTimer, named_scope, profile_trace,
)
from .schema import (  # noqa: F401
    SCHEMA_VERSION, validate_record, validate_stream,
)
from .report import (  # noqa: F401
    load_jsonl, summarize_telemetry, summarize_tune_records,
)
from .costs import (  # noqa: F401
    cost_payload, step_cost_payload,
)
from .profiling import (  # noqa: F401
    capture_step_profile, profile_payload,
)
from .tracing import (  # noqa: F401
    Tracer, complete_request_trees, multi_host_traces, orphan_spans,
    span_trees, trace_record_body,
)
from .slo import (  # noqa: F401
    LatencyHistogram, SLOAggregator, histogram_percentiles,
    merge_histograms,
)
