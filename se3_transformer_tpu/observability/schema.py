"""The telemetry-stream record contract (pure Python — no jax import, so
`scripts/obs_report.py --validate` runs without touching a backend).

A stream is JSONL; every record carries `kind` and `run_id`. Kinds:

  run_meta         stream header: schema_version, backend, code_rev,
                   host {hostname, pid, python, jax}, device metadata.
                   MUST be the first record of a stream.
  step             per-step metrics: step, t, free-form numeric fields.
  flush            one per flush interval: step, window (per-metric
                   {count, mean, min, max} from the on-device
                   accumulator), timing (per-phase {count, p50_ms,
                   p95_ms, max_ms, mean_ms}), runtime (watchdog
                   snapshot: cache_sizes, retraced, compile_events,
                   memory), optional nodes_steps_per_sec.
  retrace_warning  a step function retraced after warmup (loud copy of
                   the flush's `retraced` payload).
  pipeline         one per flush interval (and one at close) of a
                   pipelined training run (training.pipeline): steps
                   delivered, queue {capacity, depth_mean}, prefetch
                   {depth, hits, stalls, hit_rate, host_wait_ms,
                   place_ms}, and a producer_bound / device_bound /
                   balanced verdict — the proof of where a step's time
                   goes (`make pipeline-smoke` gates on it).
  serve            one per serving flush interval (inference subsystem):
                   requests {admitted, served, rejected}, buckets
                   (per-bucket latency {count, p50_ms, p95_ms, p99_ms,
                   max_ms} — SLO percentiles are load-bearing, so p99 is
                   REQUIRED here), queue_depth, runtime (watchdog
                   snapshot), post_warmup_compiles (REQUIRED — the AOT
                   zero-compile contract rides this field). Multi-
                   replica runs (serving.RouterTelemetry) fold in the
                   cross-replica aggregation fields, validated when
                   present: replicas (per-replica-id {depth, ...}),
                   swaps ({count, events} — rolling weight-swap
                   evidence), continuous_admissions (int — requests
                   admitted into an already-open in-flight bucket slot,
                   the continuous-batching proof counter).
  tune             one per kernel-autotuner candidate
                   (scripts/tune_kernels.py): kernel kind + shape,
                   candidate blocks, the end-to-end step_ms /
                   nodes_steps_per_sec A/B evidence, and the
                   load-bearing pair: verdict (admitted / promoted /
                   rejected / consulted / error)
                   + promoted (bool). Promotion evidence must be
                   END-TO-END — the schema cannot check that, but the
                   tuner records the pairs so a reviewer can.
  comm             sequence-parallel communication accounting for one
                   traced program (parallel.exchange.comm_payload): ring
                   configuration {sp, ring_steps, overlap, exchange},
                   per-collective-class {count, bytes} from the compiled
                   HLO text, and the load-bearing pair:
                   full_width_all_gathers (the [b, N, ...] gathers the
                   neighbor-sparse exchange exists to kill — shapes, so
                   a violation is diagnosable from the record alone) +
                   all_gather_free (bool — `make ring-smoke` gates on
                   it for the sp>1 exchange arm).
  cost             HLO cost ledger for one compiled program
                   (observability.costs.cost_payload): label, flops /
                   bytes_accessed with the load-bearing `source` field
                   (cost_analysis / hlo_estimate / unavailable — a
                   fallback estimate must never masquerade as XLA's
                   analysis), memory split {argument_bytes,
                   output_bytes, temp_bytes, ...}, peak_bytes (the
                   static argument+output+temp estimate), and the
                   per-class collective {count, bytes} ledger reused
                   from parallel.exchange.analyze_hlo_comm.
  profile          per-scope device-time attribution of one captured
                   trace (observability.profiling.profile_payload):
                   label, scopes (per-MODEL_SCOPES-leaf {time_ms,
                   share}), device_time_ms, and the load-bearing
                   coverage field (share of device time under a leaf —
                   `make profile-smoke` gates on it); optional phases
                   (forward / backward / replay, same shape as scopes),
                   kernels (per launch role) and roofline utilization
                   vs the bf16 MXU peak.
  guard            training-side fault-domain evidence for one guarded
                   run (training.guardian, exercised by
                   scripts/train_chaos_smoke.py): the counter set
                   {trips, rollbacks, restarts, skipped_batches,
                   preemptions, injections_total} — CUMULATIVE across
                   process restarts (the guardian's sidecar carries
                   them over a kill, so the record a resumed run banks
                   tells the whole run's story) — plus the
                   load-bearing `diverged` bit: final params
                   non-finite, or a trip the rollback policy never
                   paid down. MUST be false; `make train-chaos-smoke`
                   and obs_report --require guard gate on it, and a
                   guard record with zero injections proves nothing.
  fault            fault-domain evidence for one chaos/serving run
                   (serving.RouterTelemetry.fault_flush, exercised by
                   scripts/chaos_smoke.py): injections (the seeded
                   FaultInjector's firing log) + injections_total,
                   health_transitions (per-replica breaker moves) +
                   recoveries (quarantine -> live count), the retry /
                   request_failures / timeouts / deadline_sheds
                   counters, and the load-bearing verdict:
                   lost_requests (submits that resolved neither
                   answered nor structured-error — MUST be 0; `make
                   chaos-smoke` and obs_report --require fault gate
                   on it, and a fault record with zero injections
                   proves nothing).
  fleet            cross-host fault-domain evidence for one fleet run
                   (serving.fleet.FleetRouter.record_body, exercised by
                   scripts/fleet_chaos_smoke.py): hosts (per-host-id
                   breaker snapshot + last scraped routing signals),
                   host_transitions (the HOST-level breaker moves) +
                   recoveries (host quarantine -> live count, e.g. a
                   SIGKILLed process restarting and closing its breaker
                   via probe), cross_host_retries (redispatches onto
                   sibling hosts), request_failures / timeouts,
                   heartbeats ({ok, failed, stale_marks}), rollouts
                   ({count, events} — canaried weight-rollout evidence
                   incl. the gate verdicts) + rollbacks (auto-roll-back
                   count), and the load-bearing verdict: lost_requests
                   (submits that resolved neither answered nor
                   structured-error FLEET-WIDE — MUST be 0; `make
                   serve-fleet-smoke` and obs_report --require fleet
                   gate on it, and a fleet record with an empty
                   host_transitions log proves nothing was exercised).
  trace            fleet-wide request-tracing evidence for one run
                   (observability.tracing.trace_record_body, exercised
                   by scripts/slo_smoke.py and the chaos smokes):
                   traces (request span trees observed) +
                   complete_trees, spans_total + spans_by_name
                   (per-name {count, total_ms, exclusive_ms} — the
                   exclusive figures come from the per-thread
                   interval-stack idiom, so nested spans never
                   double-count), retry_hops / redispatch_hops (must
                   reconcile with the Router/FleetRouter retry
                   counters), multi_host_traces (traces whose spans
                   touched >= 2 hosts — cross-host redispatch made
                   visible), and the load-bearing pair: orphan_spans
                   (spans whose parent never appears in their trace —
                   MUST be 0) + completeness_total (fraction of
                   answered-or-structured-failed requests with exactly
                   one single-root span tree — MUST be 1.0; `make
                   slo-smoke` and obs_report --require trace gate it).
  slo              fleet SLO aggregation for one run
                   (observability.slo.SLOAggregator.record_body,
                   scraped over FleetRouter heartbeats): hosts folded,
                   availability (answered / (answered + failures) —
                   the load-bearing field, budgeted by
                   fleet_availability_floor), answered /
                   request_failures / timeouts, buckets (per-bucket
                   fleet p50/p95/p99 off MERGED fixed-boundary
                   histograms — exact by construction, never averaged
                   percentiles), error_budget ({target, budget,
                   burn_rate}), breaker_dwell (per-host seconds in
                   each breaker state off the transition log), and the
                   rollout/rollback history.
  mesh_sweep       composed dp x sp x tp parallelism evidence for ONE
                   mesh point (scripts/width_table.py --mesh-sweep,
                   banked to MESH_SWEEP.jsonl by `make mesh-smoke`):
                   dp/sp/tp axis sizes, n / per_device_nodes, executed
                   step_s + loss_finite, per_shard_total_gb (XLA
                   per-shard memory), and the load-bearing comm block
                   (parallel.exchange.comm_payload WITH mesh_shape):
                   collectives, all_gather_free, and axis_collectives
                   — the per-mesh-axis {count, bytes} split that
                   PERF_BUDGETS.json's per-axis ceilings gate on. A
                   sweep row that cannot attribute its traffic to an
                   axis proves nothing about which axis regressed.
  transport        fleet RPC transport A/B evidence for one loadgen
                   run (scripts/transport_loadgen.py, banked to
                   TRANSPORT_AB.jsonl by `make transport-smoke`): the
                   seeded workload shape, per-arm figures for the
                   legacy connect-per-call JSON wire and the pooled
                   multiplexed binary wire (requests, errors, qps,
                   p50/p99 ms, bytes per call), the load-bearing
                   binary-vs-legacy ratios (qps / p99 / wire bytes)
                   the committed transport budgets gate on, and the
                   binary client's transport counters (connections
                   opened, reconnects, peak in-flight, bytes each way,
                   frame errors). `serve`/`fleet` records carry the
                   same counter section under their optional
                   `transport` key.
  summary          end-of-run cumulative record (metrics, timing,
                   nodes_steps_per_sec, loss trajectory,
                   retrace_warnings_total).

`make obs-smoke` gates a 3-step CPU denoise run on `validate_stream`;
`make serve-smoke` gates a mixed-length serving run the same way.
"""
from __future__ import annotations

import json
from collections import Counter
from typing import Iterable, Union

SCHEMA_VERSION = 1

KNOWN_KINDS = ('run_meta', 'step', 'flush', 'retrace_warning', 'pipeline',
               'serve', 'tune', 'comm', 'cost', 'profile', 'fault', 'guard',
               'fleet', 'trace', 'slo', 'assembly', 'mesh_sweep',
               'transport', 'summary')

_REQUIRED = {
    'run_meta': ('run_id', 'schema_version', 'backend', 'code_rev', 'host'),
    'step': ('run_id', 'step', 't'),
    'flush': ('run_id', 'step', 'window', 'timing', 'runtime'),
    'retrace_warning': ('run_id', 'retraced'),
    # the verdict (producer_bound / device_bound / balanced) is the
    # load-bearing field: a pipeline record that cannot say who waited
    # on whom proves nothing
    'pipeline': ('run_id', 'steps', 'queue', 'prefetch', 'verdict'),
    # post_warmup_compiles is the load-bearing field of the AOT serving
    # contract (must be 0) — a serve record without it is invalid
    'serve': ('run_id', 'requests', 'buckets', 'runtime', 'queue_depth',
              'post_warmup_compiles'),
    # verdict + promoted are the load-bearing pair of the autotuner
    # contract: a tune record that cannot say what happened to the
    # candidate (and whether the table changed) proves nothing
    'tune': ('run_id', 'kernel', 'shape', 'candidate', 'blocks', 'verdict',
             'promoted'),
    # all_gather_free is the load-bearing field of the neighbor-sparse
    # exchange contract: a comm record that cannot say whether the
    # traced program re-materialized a full-width operand proves nothing
    'comm': ('run_id', 'sp', 'ring_steps', 'overlap', 'exchange',
             'collectives', 'full_width_all_gathers', 'all_gather_free'),
    # source is the load-bearing field of the cost ledger: a record
    # that cannot say whether its numbers came from XLA's analysis or
    # a parsed-HLO estimate proves nothing about either
    'cost': ('run_id', 'label', 'source', 'flops', 'bytes_accessed',
             'memory', 'peak_bytes', 'collectives'),
    # coverage is the load-bearing field of the attribution contract:
    # a profile record that cannot say how much device time its scopes
    # account for proves nothing about where the time went
    'profile': ('run_id', 'label', 'scopes', 'device_time_ms', 'coverage'),
    # lost_requests is the load-bearing field of the fault-domain
    # contract: a fault record that cannot say whether every submit
    # resolved answered-or-structured-error proves nothing about
    # robustness (and injections_total=0 proves nothing was exercised)
    'fault': ('run_id', 'label', 'injections', 'injections_total',
              'health_transitions', 'recoveries', 'retries',
              'request_failures', 'timeouts', 'lost_requests'),
    # diverged is the load-bearing field of the training fault-domain
    # contract: a guard record that cannot say whether the run ended on
    # finite, policy-clean parameters proves nothing about
    # self-healing (and injections_total=0 proves nothing was
    # exercised). Counters are cumulative across process restarts.
    'guard': ('run_id', 'step', 'trips', 'rollbacks', 'restarts',
              'skipped_batches', 'preemptions', 'injections_total',
              'diverged'),
    # lost_requests is the load-bearing field of the CROSS-HOST
    # fault-domain contract: a fleet record that cannot say whether
    # every submit resolved answered-or-structured-error across host
    # deaths, redispatches and a canaried rollout proves nothing (and
    # an empty host_transitions log proves nothing was exercised)
    'fleet': ('run_id', 'label', 'hosts', 'host_transitions',
              'recoveries', 'cross_host_retries', 'request_failures',
              'timeouts', 'rollouts', 'rollbacks', 'lost_requests'),
    # orphan_spans + completeness_total are the load-bearing pair of
    # the tracing contract: a trace record that cannot say whether
    # every answered-or-structured-failed request produced exactly one
    # single-root span tree proves nothing about end-to-end visibility
    'trace': ('run_id', 'label', 'traces', 'complete_trees',
              'orphan_spans', 'spans_total', 'spans_by_name',
              'retry_hops', 'redispatch_hops', 'multi_host_traces',
              'completeness_total'),
    # availability is the load-bearing field of the SLO contract: an
    # slo record that cannot say what fraction of requests the fleet
    # answered proves nothing about "millions of users" — and its
    # bucket percentiles must come from merged histograms, never
    # averaged per-host percentiles
    'slo': ('run_id', 'label', 'hosts', 'availability', 'answered',
            'request_failures', 'timeouts', 'buckets', 'error_budget',
            'breaker_dwell', 'rollouts'),
    # the large-assembly serving contract (kNN-free global attention):
    # the memory ratio vs the materialized control arm, parity,
    # equivariance, AND proof the request was actually served through
    # an engine bucket with no post-warmup compile — an assembly record
    # that cannot say all four proves nothing about O(n) serving
    'assembly': ('run_id', 'label', 'n', 'bucket', 'global_peak_bytes',
                 'materialized_peak_bytes', 'hbm_materialized_vs_global',
                 'parity_linf', 'equivariance_l2', 'bucket_served',
                 'post_warmup_compiles'),
    # axis_collectives (inside comm) is the load-bearing field of the
    # composed-parallelism contract: a mesh-point row that cannot split
    # its collective traffic by mesh axis cannot be gated per axis, so
    # a tp regression would hide inside the dp gradient psum
    'mesh_sweep': ('run_id', 'dp', 'sp', 'tp', 'n', 'per_device_nodes',
                   'step_s', 'per_shard_total_gb', 'loss_finite', 'comm'),
    # the binary-vs-legacy ratios are the load-bearing trio of the
    # transport contract: an A/B record that cannot say the
    # multiplexed binary arm was faster, no worse at the tail, AND
    # lighter on the wire — on the same seeded workload — proves
    # nothing about real fleet QPS
    'transport': ('run_id', 'label', 'workload', 'arms',
                  'qps_binary_vs_legacy', 'p99_binary_vs_legacy',
                  'wire_bytes_binary_vs_legacy', 'transport'),
    'summary': ('run_id', 'steps', 'metrics', 'timing'),
}

_TUNE_VERDICTS = ('admitted', 'promoted', 'rejected', 'consulted',
                  'error')

_PIPELINE_PREFETCH_REQUIRED = ('depth', 'hits', 'stalls')
_PIPELINE_VERDICTS = ('producer_bound', 'device_bound', 'balanced')

_HEALTH_STATES = ('healthy', 'degraded', 'quarantined')
_FAULT_COUNTERS = ('injections_total', 'recoveries', 'retries',
                   'request_failures', 'timeouts', 'lost_requests')
_GUARD_COUNTERS = ('trips', 'rollbacks', 'restarts', 'skipped_batches',
                   'preemptions', 'injections_total')
_FLEET_COUNTERS = ('recoveries', 'cross_host_retries', 'request_failures',
                   'timeouts', 'rollbacks', 'lost_requests')
# the transport counter section (serve/fleet records' optional
# `transport` key, and the transport A/B record's required one): wire
# accounting every arm reports with the same shape
_TRANSPORT_COUNTERS = ('connections_opened', 'reconnects',
                       'peak_in_flight', 'bytes_sent', 'bytes_received',
                       'frame_errors')
_TRANSPORT_ARM_REQUIRED = ('requests', 'errors', 'qps', 'p50_ms',
                           'p99_ms', 'bytes_per_call')

_COST_SOURCES = ('cost_analysis', 'hlo_estimate', 'unavailable')
_COST_MEMORY_REQUIRED = ('argument_bytes', 'output_bytes', 'temp_bytes')
_PROFILE_SCOPE_REQUIRED = ('time_ms', 'share')

_TIMING_REQUIRED = ('count', 'p50_ms', 'p95_ms', 'max_ms')
# serving SLOs are quoted at p99 — a serve record without it is invalid
_SERVE_TIMING_REQUIRED = _TIMING_REQUIRED + ('p99_ms',)
_WINDOW_REQUIRED = ('count', 'mean', 'min', 'max')


class SchemaError(ValueError):
    pass


def _fail(index, msg):
    where = f'record {index}: ' if index is not None else ''
    raise SchemaError(where + msg)


def _validate_latency_hist(hist, index, where):
    """One mergeable-histogram section: bucket -> {bounds, counts,
    count}. Counts must have one more slot than bounds (the overflow
    bucket) and sum to count — a snapshot that cannot merge exactly is
    worse than no snapshot."""
    if not isinstance(hist, dict):
        _fail(index, f'{where}.latency_hist must be an object '
                     f'(bucket -> histogram snapshot)')
    for bucket, snap in hist.items():
        if not isinstance(snap, dict):
            _fail(index, f'{where}.latency_hist[{bucket!r}] must be an '
                         f'object')
        bounds, counts = snap.get('bounds'), snap.get('counts')
        if not isinstance(bounds, list) or not isinstance(counts, list) \
                or len(counts) != len(bounds) + 1:
            _fail(index, f'{where}.latency_hist[{bucket!r}] must carry '
                         f'bounds plus len(bounds)+1 counts (the last '
                         f'slot is the overflow bucket)')
        total = snap.get('count')
        if not isinstance(total, int) or isinstance(total, bool) \
                or total < 0:
            _fail(index, f'{where}.latency_hist[{bucket!r}].count must '
                         f'be a non-negative int, got {total!r}')
        if sum(counts) != total:
            _fail(index, f'{where}.latency_hist[{bucket!r}].count='
                         f'{total} contradicts counts summing to '
                         f'{sum(counts)} — the snapshot cannot merge '
                         f'exactly')


def _validate_model_families(val, index, where):
    """A family capability list (serve records / fleet host stats):
    non-empty list of non-empty strings (e.g. ['se3_v1', 'se3_v2'])."""
    if not isinstance(val, list) or not val or any(
            not isinstance(f, str) or not f for f in val):
        _fail(index, f'{where} must be a non-empty list of non-empty '
                     f'strings (model families served), got {val!r}')


def _validate_transport_section(val, index, where):
    """The transport counter section (`serve`/`fleet` optional key,
    `transport` record required key): every counter present and a
    non-negative int — wire accounting that cannot count proves
    nothing about the wire."""
    if not isinstance(val, dict):
        _fail(index, f'{where} must be an object, got '
                     f'{type(val).__name__}')
    for field in _TRANSPORT_COUNTERS:
        v = val.get(field)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            _fail(index, f'{where}.{field} must be a non-negative int '
                         f'(the transport counter contract), got {v!r}')


def validate_record(rec: dict, index=None) -> dict:
    """Validate one record; raises SchemaError, returns the record."""
    if not isinstance(rec, dict):
        _fail(index, f'not an object: {type(rec).__name__}')
    kind = rec.get('kind')
    if kind not in KNOWN_KINDS:
        _fail(index, f'unknown kind {kind!r} (known: {KNOWN_KINDS})')
    missing = [k for k in _REQUIRED[kind] if k not in rec]
    if missing:
        _fail(index, f'{kind} record missing required fields {missing}')
    if kind == 'run_meta':
        host = rec['host']
        if not isinstance(host, dict) or 'hostname' not in host \
                or 'pid' not in host:
            _fail(index, 'run_meta.host must carry hostname and pid')
    if kind == 'step' and not isinstance(rec['step'], int):
        _fail(index, f'step must be an int, got {rec["step"]!r}')
    if kind == 'pipeline':
        prefetch = rec['prefetch']
        missing = [k for k in _PIPELINE_PREFETCH_REQUIRED
                   if not isinstance(prefetch, dict) or k not in prefetch]
        if missing:
            _fail(index, f'pipeline.prefetch missing {missing} '
                         f'(hit/stall counts are the whole point)')
        if not isinstance(rec['queue'], dict) \
                or 'capacity' not in rec['queue']:
            _fail(index, 'pipeline.queue must carry capacity')
        if rec['verdict'] not in _PIPELINE_VERDICTS:
            _fail(index, f'pipeline.verdict {rec["verdict"]!r} not in '
                         f'{_PIPELINE_VERDICTS}')
        # source fault counters (BatchProducer retry/skip) are optional
        # but validated when present — the train-chaos gate reads them
        if 'source' in rec:
            src = rec['source']
            if not isinstance(src, dict):
                _fail(index, 'pipeline.source must be an object')
            for field in ('retries', 'skipped'):
                val = src.get(field)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(index, f'pipeline.source.{field} must be a '
                                 f'non-negative int, got {val!r}')
    if kind == 'serve':
        requests = rec['requests']
        if not isinstance(requests, dict) or 'served' not in requests \
                or 'rejected' not in requests:
            _fail(index, 'serve.requests must carry served and rejected')
        buckets = rec['buckets']
        if not isinstance(buckets, dict):
            _fail(index, 'serve.buckets must be an object')
        for bucket, st in buckets.items():
            missing = [k for k in _SERVE_TIMING_REQUIRED
                       if not isinstance(st, dict) or k not in st]
            if missing:
                _fail(index, f'buckets[{bucket!r}] missing {missing} '
                             f'(per-bucket p50/p95/p99 are the SLO '
                             f'surface)')
        # multi-replica aggregation fields (serving.RouterTelemetry)
        # are optional but validated when present
        if 'continuous_admissions' in rec:
            ca = rec['continuous_admissions']
            if not isinstance(ca, int) or isinstance(ca, bool) or ca < 0:
                _fail(index, f'serve.continuous_admissions must be a '
                             f'non-negative int, got {ca!r}')
        if 'replicas' in rec:
            replicas = rec['replicas']
            if not isinstance(replicas, dict):
                _fail(index, 'serve.replicas must be an object '
                             '(replica id -> snapshot)')
            for rid, snap in replicas.items():
                if not isinstance(snap, dict) or 'depth' not in snap:
                    _fail(index, f'replicas[{rid!r}] must carry depth '
                                 f'(per-replica depth IS the load '
                                 f'surface)')
                if 'model_family' in snap and (
                        not isinstance(snap['model_family'], str)
                        or not snap['model_family']):
                    _fail(index, f'replicas[{rid!r}].model_family must '
                                 f'be a non-empty string, got '
                                 f'{snap["model_family"]!r}')
        # the family capability signal (heterogeneous serving: v1/v2
        # replicas behind one router) — optional but validated when
        # present, because fleet placement will route on it
        if 'model_families' in rec:
            _validate_model_families(rec['model_families'], index,
                                     'serve.model_families')
        if 'swaps' in rec:
            swaps = rec['swaps']
            if not isinstance(swaps, dict) \
                    or not isinstance(swaps.get('count'), int) \
                    or not isinstance(swaps.get('events'), list):
                _fail(index, f'serve.swaps must carry an int count and '
                             f'an events list, got {swaps!r}')
        # fault-domain routing signals (router serve records): optional
        # but validated when present — item 5's cross-host tier routes
        # on them, so a malformed signal is worse than a missing one
        for field in ('retries', 'request_failures', 'timeouts',
                      'deadline_sheds'):
            if field in rec:
                val = rec[field]
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(index, f'serve.{field} must be a non-negative '
                                 f'int, got {val!r}')
        if 'health' in rec:
            health = rec['health']
            if not isinstance(health, dict):
                _fail(index, 'serve.health must be an object '
                             '(replica id -> breaker snapshot)')
            for rid, snap in health.items():
                if not isinstance(snap, dict) \
                        or snap.get('state') not in _HEALTH_STATES:
                    _fail(index, f'serve.health[{rid!r}] must carry a '
                                 f'state in {_HEALTH_STATES}')
        # host-side wire counters (serve.py attaches the socket
        # server's transport_stats): optional but validated when
        # present — a malformed counter section is worse than none
        if 'transport' in rec:
            _validate_transport_section(rec['transport'], index,
                                        'serve.transport')
        # mergeable per-bucket latency histograms (observability.slo):
        # optional but validated when present — the fleet SLO
        # aggregation merges these by count addition, so a malformed
        # snapshot poisons the fleet percentiles
        if 'latency_hist' in rec:
            _validate_latency_hist(rec['latency_hist'], index, 'serve')
    if kind == 'fault':
        for field in ('injections', 'health_transitions'):
            if not isinstance(rec[field], list):
                _fail(index, f'fault.{field} must be a list (the '
                             f'evidence log, empty when clean)')
        for field in _FAULT_COUNTERS:
            val = rec[field]
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'fault.{field} must be a non-negative '
                             f'int, got {val!r}')
        if rec['injections_total'] != len(rec['injections']):
            _fail(index, f'fault.injections_total='
                         f'{rec["injections_total"]} contradicts '
                         f'{len(rec["injections"])} logged injections')
        for e in rec['health_transitions']:
            if not isinstance(e, dict) or 'from_state' not in e \
                    or 'to_state' not in e:
                _fail(index, f'fault.health_transitions entries must '
                             f'carry from_state/to_state, got {e!r}')
    if kind == 'fleet':
        hosts = rec['hosts']
        if not isinstance(hosts, dict) or not hosts:
            _fail(index, 'fleet.hosts must be a non-empty object '
                         '(host id -> breaker snapshot + scraped '
                         'signals)')
        for hid, snap in hosts.items():
            if not isinstance(snap, dict) \
                    or snap.get('state') not in _HEALTH_STATES:
                _fail(index, f'fleet.hosts[{hid!r}] must carry a state '
                             f'in {_HEALTH_STATES}')
            stats = snap.get('stats')
            if isinstance(stats, dict) and 'model_families' in stats:
                _validate_model_families(
                    stats['model_families'], index,
                    f'fleet.hosts[{hid!r}].stats.model_families')
        if not isinstance(rec['host_transitions'], list):
            _fail(index, 'fleet.host_transitions must be a list (the '
                         'host-breaker evidence log, empty when clean)')
        for e in rec['host_transitions']:
            if not isinstance(e, dict) or 'from_state' not in e \
                    or 'to_state' not in e:
                _fail(index, f'fleet.host_transitions entries must '
                             f'carry from_state/to_state, got {e!r}')
        for field in _FLEET_COUNTERS:
            val = rec[field]
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'fleet.{field} must be a non-negative '
                             f'int, got {val!r}')
        rollouts = rec['rollouts']
        if not isinstance(rollouts, dict) \
                or not isinstance(rollouts.get('count'), int) \
                or not isinstance(rollouts.get('events'), list):
            _fail(index, f'fleet.rollouts must carry an int count and '
                         f'an events list, got {rollouts!r}')
        for e in rollouts['events']:
            if not isinstance(e, dict) or 'canary' not in e \
                    or 'passed' not in e:
                _fail(index, f'fleet.rollouts.events entries must '
                             f'carry canary/passed (the gate verdict '
                             f'IS the evidence), got {e!r}')
        # fleet-side wire counters (aggregated per-host transport
        # stats): optional but validated when present
        if 'transport' in rec:
            _validate_transport_section(rec['transport'], index,
                                        'fleet.transport')
    if kind == 'transport':
        workload = rec['workload']
        if not isinstance(workload, dict) \
                or not isinstance(workload.get('requests'), int) \
                or workload.get('requests', 0) <= 0:
            _fail(index, f'transport.workload must carry a positive '
                         f'int requests count (the A/B proves nothing '
                         f'about an empty workload), got {workload!r}')
        arms = rec['arms']
        if not isinstance(arms, dict) or 'legacy' not in arms \
                or 'binary' not in arms:
            _fail(index, 'transport.arms must carry both the legacy '
                         'and the binary arm (the A/B IS the record)')
        for name, arm in arms.items():
            missing = [k for k in _TRANSPORT_ARM_REQUIRED
                       if not isinstance(arm, dict) or k not in arm]
            if missing:
                _fail(index, f'transport.arms[{name!r}] missing '
                             f'{missing}')
            for k in _TRANSPORT_ARM_REQUIRED:
                v = arm[k]
                if not isinstance(v, (int, float)) \
                        or isinstance(v, bool) or v < 0:
                    _fail(index, f'transport.arms[{name!r}].{k} must '
                                 f'be a non-negative number, got {v!r}')
        for field in ('qps_binary_vs_legacy', 'p99_binary_vs_legacy',
                      'wire_bytes_binary_vs_legacy'):
            v = rec[field]
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v <= 0:
                _fail(index, f'transport.{field} must be a positive '
                             f'number (the ratio the budgets gate on), '
                             f'got {v!r}')
        _validate_transport_section(rec['transport'], index,
                                    'transport.transport')
    if kind == 'guard':
        for field in _GUARD_COUNTERS + ('step',):
            val = rec[field]
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'guard.{field} must be a non-negative '
                             f'int, got {val!r}')
        if not isinstance(rec['diverged'], bool):
            _fail(index, f'guard.diverged must be a bool, got '
                         f'{rec["diverged"]!r}')
    if kind == 'tune':
        if rec['verdict'] not in _TUNE_VERDICTS:
            _fail(index, f'tune.verdict {rec["verdict"]!r} not in '
                         f'{_TUNE_VERDICTS}')
        if not isinstance(rec['promoted'], bool):
            _fail(index, f'tune.promoted must be a bool, got '
                         f'{rec["promoted"]!r}')
        if rec['verdict'] == 'promoted' and not rec['promoted']:
            _fail(index, 'tune verdict "promoted" requires promoted=true')
        for field in ('candidate', 'blocks', 'shape'):
            val = rec[field]
            if not isinstance(val, (list, tuple)) or \
                    not all(isinstance(v, int) for v in val):
                _fail(index, f'tune.{field} must be a list of ints, '
                             f'got {val!r}')
    if kind == 'comm':
        for field in ('overlap', 'exchange', 'all_gather_free'):
            if not isinstance(rec[field], bool):
                _fail(index, f'comm.{field} must be a bool, got '
                             f'{rec[field]!r}')
        for field in ('sp', 'ring_steps'):
            if not isinstance(rec[field], int) or rec[field] < 1:
                _fail(index, f'comm.{field} must be a positive int, got '
                             f'{rec[field]!r}')
        colls = rec['collectives']
        if not isinstance(colls, dict):
            _fail(index, 'comm.collectives must be an object')
        for cls, st in colls.items():
            missing = [k for k in ('count', 'bytes')
                       if not isinstance(st, dict) or k not in st]
            if missing:
                _fail(index, f'collectives[{cls!r}] missing {missing} '
                             f'(per-class count+bytes are the whole '
                             f'accounting)')
        if not isinstance(rec['full_width_all_gathers'], list):
            _fail(index, 'comm.full_width_all_gathers must be a list '
                         '(the offending shapes, empty when clean)')
        if rec['all_gather_free'] and rec['full_width_all_gathers']:
            _fail(index, 'comm.all_gather_free=true contradicts a '
                         'non-empty full_width_all_gathers list')
    if kind == 'cost':
        if rec['source'] not in _COST_SOURCES:
            _fail(index, f'cost.source {rec["source"]!r} not in '
                         f'{_COST_SOURCES}')
        mem = rec['memory']
        missing = [k for k in _COST_MEMORY_REQUIRED
                   if not isinstance(mem, dict) or k not in mem]
        if missing:
            _fail(index, f'cost.memory missing {missing} (the '
                         f'argument/output/temp split IS the ledger)')
        for k in _COST_MEMORY_REQUIRED:
            if not isinstance(mem[k], (int, float)) or mem[k] < 0:
                _fail(index, f'cost.memory[{k!r}] must be a '
                             f'non-negative number, got {mem[k]!r}')
        if not isinstance(rec['peak_bytes'], (int, float)) \
                or rec['peak_bytes'] < 0:
            _fail(index, f'cost.peak_bytes must be a non-negative '
                         f'number, got {rec["peak_bytes"]!r}')
        if rec['source'] == 'cost_analysis' and (
                not isinstance(rec['flops'], (int, float))
                or rec['flops'] < 0):
            _fail(index, f'cost.flops must be a non-negative number '
                         f'when source=cost_analysis, got '
                         f'{rec["flops"]!r}')
        colls = rec['collectives']
        if not isinstance(colls, dict):
            _fail(index, 'cost.collectives must be an object')
        for cls, st in colls.items():
            missing = [k for k in ('count', 'bytes')
                       if not isinstance(st, dict) or k not in st]
            if missing:
                _fail(index, f'cost.collectives[{cls!r}] missing '
                             f'{missing}')
    if kind == 'profile':
        scopes = rec['scopes']
        if not isinstance(scopes, dict):
            _fail(index, 'profile.scopes must be an object')
        for scope, st in scopes.items():
            missing = [k for k in _PROFILE_SCOPE_REQUIRED
                       if not isinstance(st, dict) or k not in st]
            if missing:
                _fail(index, f'profile.scopes[{scope!r}] missing '
                             f'{missing} (per-scope time+share are the '
                             f'whole attribution)')
        phases = rec.get('phases', {})
        if not isinstance(phases, dict):
            _fail(index, 'profile.phases must be an object')
        for phase, st in phases.items():
            missing = [k for k in _PROFILE_SCOPE_REQUIRED
                       if not isinstance(st, dict) or k not in st]
            if phase not in ('forward', 'backward', 'replay') or missing:
                _fail(index, f'profile.phases[{phase!r}] must be one of '
                             f'forward/backward/replay with time_ms and '
                             f'share (missing {missing})')
        cov = rec['coverage']
        if not isinstance(cov, (int, float)) or not 0 <= cov <= 1:
            _fail(index, f'profile.coverage must be a number in [0, 1], '
                         f'got {cov!r}')
        if not isinstance(rec['device_time_ms'], (int, float)) \
                or rec['device_time_ms'] < 0:
            _fail(index, f'profile.device_time_ms must be a '
                         f'non-negative number, got '
                         f'{rec["device_time_ms"]!r}')
    if kind == 'assembly':
        for field in ('n', 'bucket', 'post_warmup_compiles'):
            val = rec[field]
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'assembly.{field} must be a non-negative '
                             f'int, got {val!r}')
        for field in ('global_peak_bytes', 'materialized_peak_bytes',
                      'hbm_materialized_vs_global', 'parity_linf',
                      'equivariance_l2'):
            val = rec[field]
            if not isinstance(val, (int, float)) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'assembly.{field} must be a non-negative '
                             f'number, got {val!r}')
        if not isinstance(rec['bucket_served'], int) \
                or isinstance(rec['bucket_served'], bool) \
                or rec['bucket_served'] < 0:
            _fail(index, f'assembly.bucket_served must be a non-negative '
                         f'int (rows served through the engine bucket), '
                         f'got {rec["bucket_served"]!r}')
    if kind == 'mesh_sweep':
        for field in ('dp', 'sp', 'tp', 'n', 'per_device_nodes'):
            if not isinstance(rec[field], int) \
                    or isinstance(rec[field], bool) or rec[field] < 1:
                _fail(index, f'mesh_sweep.{field} must be a positive '
                             f'int, got {rec[field]!r}')
        for field in ('step_s', 'per_shard_total_gb'):
            val = rec[field]
            if not isinstance(val, (int, float)) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'mesh_sweep.{field} must be a non-negative '
                             f'number, got {val!r}')
        if not isinstance(rec['loss_finite'], bool):
            _fail(index, f'mesh_sweep.loss_finite must be a bool, got '
                         f'{rec["loss_finite"]!r}')
        comm = rec['comm']
        if not isinstance(comm, dict):
            _fail(index, 'mesh_sweep.comm must be an object (the '
                         'comm_payload block)')
        for field in ('collectives', 'all_gather_free',
                      'axis_collectives', 'mesh'):
            if field not in comm:
                _fail(index, f'mesh_sweep.comm missing {field!r} — the '
                             f'per-axis split is the point of the record')
        if not isinstance(comm['all_gather_free'], bool):
            _fail(index, f'mesh_sweep.comm.all_gather_free must be a '
                         f'bool, got {comm["all_gather_free"]!r}')
        mesh_shape = comm['mesh']
        if not isinstance(mesh_shape, dict) or any(
                mesh_shape.get(a) != rec[a] for a in ('dp', 'sp', 'tp')):
            _fail(index, f'mesh_sweep.comm.mesh {mesh_shape!r} must echo '
                         f'the row axes dp={rec["dp"]} sp={rec["sp"]} '
                         f'tp={rec["tp"]} (the attribution ran on a '
                         f'different mesh otherwise)')
        axes = comm['axis_collectives']
        if not isinstance(axes, dict):
            _fail(index, 'mesh_sweep.comm.axis_collectives must be an '
                         'object (per-axis-label per-class accounting)')
        known = set(mesh_shape) | {'local'}
        for label, classes in axes.items():
            parts = set(label.split('+'))
            if not parts <= known:
                _fail(index, f'axis_collectives label {label!r} names '
                             f'non-mesh axes {sorted(parts - known)}')
            if not isinstance(classes, dict):
                _fail(index, f'axis_collectives[{label!r}] must be an '
                             f'object')
            for cls, st in classes.items():
                missing = [k for k in ('count', 'bytes')
                           if not isinstance(st, dict) or k not in st]
                if missing:
                    _fail(index, f'axis_collectives[{label!r}][{cls!r}] '
                                 f'missing {missing}')
    if kind == 'trace':
        for field in ('traces', 'complete_trees', 'orphan_spans',
                      'spans_total', 'retry_hops', 'redispatch_hops',
                      'multi_host_traces'):
            val = rec[field]
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'trace.{field} must be a non-negative '
                             f'int, got {val!r}')
        comp = rec['completeness_total']
        if not isinstance(comp, (int, float)) or isinstance(comp, bool) \
                or not 0 <= comp <= 1:
            _fail(index, f'trace.completeness_total must be a number in '
                         f'[0, 1], got {comp!r}')
        if rec['complete_trees'] > rec['traces']:
            _fail(index, f'trace.complete_trees={rec["complete_trees"]} '
                         f'exceeds traces={rec["traces"]}')
        if rec['orphan_spans'] > 0 and rec['traces'] > 0 and comp >= 1.0:
            _fail(index, f'trace.completeness_total={comp} contradicts '
                         f'{rec["orphan_spans"]} orphan spans — an '
                         f'orphaned span means some tree is incomplete')
        by_name = rec['spans_by_name']
        if not isinstance(by_name, dict):
            _fail(index, 'trace.spans_by_name must be an object '
                         '(span name -> exclusive-duration entry)')
        for name, entry in by_name.items():
            missing = [k for k in ('count', 'total_ms', 'exclusive_ms')
                       if not isinstance(entry, dict) or k not in entry]
            if missing:
                _fail(index, f'trace.spans_by_name[{name!r}] missing '
                             f'{missing} (exclusive durations are the '
                             f'whole attribution)')
    if kind == 'slo':
        for field in ('hosts', 'answered', 'request_failures',
                      'timeouts'):
            val = rec[field]
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 0:
                _fail(index, f'slo.{field} must be a non-negative int, '
                             f'got {val!r}')
        avail = rec['availability']
        if not isinstance(avail, (int, float)) \
                or isinstance(avail, bool) or not 0 <= avail <= 1:
            _fail(index, f'slo.availability must be a number in [0, 1], '
                         f'got {avail!r}')
        buckets = rec['buckets']
        if not isinstance(buckets, dict):
            _fail(index, 'slo.buckets must be an object (bucket -> '
                         'merged fleet percentiles)')
        for bucket, st in buckets.items():
            missing = [k for k in ('count', 'p50_ms', 'p95_ms', 'p99_ms')
                       if not isinstance(st, dict) or k not in st]
            if missing:
                _fail(index, f'slo.buckets[{bucket!r}] missing {missing} '
                             f'(merged fleet percentiles are the whole '
                             f'point)')
        budget = rec['error_budget']
        if not isinstance(budget, dict) or 'target' not in budget \
                or 'burn_rate' not in budget:
            _fail(index, f'slo.error_budget must carry target and '
                         f'burn_rate, got {budget!r}')
        if not isinstance(rec['breaker_dwell'], dict):
            _fail(index, 'slo.breaker_dwell must be an object '
                         '(host -> per-state seconds)')
        rollouts = rec['rollouts']
        if not isinstance(rollouts, dict) \
                or not isinstance(rollouts.get('count'), int) \
                or not isinstance(rollouts.get('rollbacks'), int):
            _fail(index, f'slo.rollouts must carry int count and '
                         f'rollbacks, got {rollouts!r}')
    if kind in ('flush', 'summary'):
        timing = rec['timing']
        if not isinstance(timing, dict):
            _fail(index, 'timing must be an object')
        for phase, st in timing.items():
            missing = [k for k in _TIMING_REQUIRED
                       if not isinstance(st, dict) or k not in st]
            if missing:
                _fail(index, f'timing[{phase!r}] missing {missing} '
                             f'(per-phase p50/p95 are load-bearing)')
        window = rec.get('window') if kind == 'flush' else rec['metrics']
        if not isinstance(window, dict):
            _fail(index, 'metric window must be an object')
        for name, st in window.items():
            missing = [k for k in _WINDOW_REQUIRED
                       if not isinstance(st, dict) or k not in st]
            if missing:
                _fail(index, f'window[{name!r}] missing {missing}')
    return rec


def validate_stream(source: Union[str, Iterable[str]]) -> dict:
    """Validate a JSONL stream (path or iterable of lines).

    Returns {'records': N, 'kinds': {kind: count}, 'run_ids': [...]}.
    Raises SchemaError on the first invalid record; the first record of
    a stream must be run_meta (consumers key everything off it).
    """
    if isinstance(source, str):
        with open(source) as f:
            lines = f.readlines()
    else:
        lines = list(source)
    kinds = Counter()
    run_ids = []
    n = 0
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError as e:
            _fail(i, f'invalid JSON: {e}')
        validate_record(rec, index=i)
        if n == 0 and rec['kind'] != 'run_meta':
            _fail(i, f'stream must open with run_meta, got {rec["kind"]!r}')
        if rec['kind'] == 'run_meta' and rec['run_id'] not in run_ids:
            run_ids.append(rec['run_id'])
        kinds[rec['kind']] += 1
        n += 1
    if n == 0:
        raise SchemaError('empty stream')
    return dict(records=n, kinds=dict(kinds), run_ids=run_ids)
