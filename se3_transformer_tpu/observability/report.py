"""Aggregate telemetry JSONL streams into one summary per run.

A stream of schema.py records from a `--telemetry` run is reduced to
one record per run_id: metric/value/unit/vs_baseline/step_ms, the loss
trajectory, per-phase p50/p95, the retrace count, and the views of the
pipeline, tune, comm, cost and profile records that rode along.

Pure Python on purpose: `scripts/obs_report.py` must run without
initializing a backend (a chip belongs to one process at a time, and
the report has to work beside the process that holds it).
"""
from __future__ import annotations

import json
from typing import List, Optional


def load_jsonl(path: str, strict: bool = False) -> List[dict]:
    """Parse a JSONL file. Non-JSON lines are skipped (a log can carry
    stderr interleaving) unless strict=True."""
    records = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                if strict:
                    raise ValueError(f'{path}:{i + 1}: invalid JSON')
                continue
            if isinstance(rec, dict):
                records.append(rec)
    return records


def summarize_telemetry(records: List[dict],
                        anchor: Optional[float] = None) -> List[dict]:
    """Reduce telemetry stream(s) to run summaries.

    Returns one dict per run_id, in stream order (metric/value/unit/
    vs_baseline/step_ms/window_rates/steps_trained/loss trajectory)
    plus per-phase percentiles and the retrace-warning count."""
    runs = {}
    order = []
    for rec in records:
        rid = rec.get('run_id')
        if rid is None:
            continue
        if rid not in runs:
            runs[rid] = dict(meta=None, flushes=[], summary=None,
                             retrace_warnings=0, steps=[], pipeline=None,
                             tune=[], comm=[], cost=[], profile=[])
            order.append(rid)
        kind = rec.get('kind')
        if kind == 'run_meta':
            runs[rid]['meta'] = rec
        elif kind == 'flush':
            runs[rid]['flushes'].append(rec)
        elif kind == 'summary':
            runs[rid]['summary'] = rec
        elif kind == 'retrace_warning':
            runs[rid]['retrace_warnings'] += 1
        elif kind == 'step':
            runs[rid]['steps'].append(rec)
        elif kind == 'pipeline':
            # cumulative counters: the last record of the run wins
            runs[rid]['pipeline'] = rec
        elif kind == 'tune':
            runs[rid]['tune'].append(rec)
        elif kind == 'comm':
            # one per traced program; an A/B run carries several (the
            # overlapped and serialized arms), all surfaced
            runs[rid]['comm'].append(rec)
        elif kind == 'cost':
            # one per compiled program (a bucketed engine carries one
            # per shape bucket), all surfaced
            runs[rid]['cost'].append(rec)
        elif kind == 'profile':
            runs[rid]['profile'].append(rec)

    out = []
    for rid in order:
        run = runs[rid]
        meta = run['meta'] or {}
        summary = run['summary'] or {}
        backend = meta.get('backend') or 'cpu'
        on_chip = backend != 'cpu'
        label = summary.get('label') or meta.get('label') or 'telemetry'

        window_rates = [f['nodes_steps_per_sec'] for f in run['flushes']
                        if f.get('nodes_steps_per_sec')]
        value = summary.get('nodes_steps_per_sec')
        if value is None and window_rates:
            # best-of-windows (one-sided host noise only slows a
            # window down)
            value = max(window_rates)

        timing = summary.get('timing') or {}
        step_t = timing.get('step') or {}
        retraces = summary.get('retrace_warnings_total',
                               run['retrace_warnings'])

        rec = {
            'metric': f'denoise_train_nodes_steps_per_sec'
                      f'({label},backend={backend})',
            'value': round(value, 2) if value else None,
            'unit': f'nodes*steps/sec/{"chip" if on_chip else "cpu-host"}',
            'vs_baseline': round(value / anchor, 3)
            if (value and anchor) else 1.0,
            'step_ms': step_t.get('mean_ms'),
            'step_ms_p50': step_t.get('p50_ms'),
            'step_ms_p95': step_t.get('p95_ms'),
            'step_ms_max': step_t.get('max_ms'),
            'timing': timing,
            'window_rates': [round(w, 2) for w in window_rates],
            'steps_trained': summary.get('steps'),
            'retrace_warnings': retraces,
            'run_id': rid,
            'code_rev': meta.get('code_rev'),
        }
        for k in ('loss_first', 'loss_last', 'loss_decreased'):
            if k in summary:
                rec[k] = summary[k]
        if meta.get('device_kind'):
            rec['device_kind'] = meta['device_kind']
        if run['pipeline'] is not None:
            pipe = run['pipeline']
            rec['pipeline'] = {k: pipe[k] for k in
                               ('steps', 'queue', 'prefetch', 'verdict')
                               if k in pipe}
        if run['tune']:
            rec['kernel_tuning'] = summarize_tune_records(run['tune'])
        if run['comm']:
            rec['comm'] = summarize_comm_records(run['comm'])
        if run['cost']:
            rec['cost'] = summarize_cost_records(run['cost'])
        if run['profile']:
            rec['profile'] = summarize_profile_records(run['profile'])
        out.append(rec)
    return out


def summarize_tune_records(records: List[dict]) -> dict:
    """Reduce a tune-record stream (scripts/tune_kernels.py) to the
    adopted-vs-heuristic view the run report surfaces: per-verdict
    counts plus the promoted entries with their end-to-end evidence."""
    tunes = [r for r in records if r.get('kind', 'tune') == 'tune']
    verdicts = {}
    for r in tunes:
        v = r.get('verdict', 'unknown')
        verdicts[v] = verdicts.get(v, 0) + 1
    promoted = [
        {k: r[k] for k in ('kernel', 'shape', 'candidate', 'blocks',
                           'step_ms', 'nodes_steps_per_sec', 'pairs',
                           'incumbent') if k in r}
        for r in tunes if r.get('promoted') and r.get('verdict') ==
        'promoted']
    consulted = [
        {k: r[k] for k in ('kernel', 'shape', 'blocks') if k in r}
        for r in tunes if r.get('verdict') == 'consulted']
    return dict(candidates=len(tunes), verdicts=verdicts,
                promoted=promoted, consulted=consulted)


def write_record_stream(path: str, run_id: str,
                        records: List[dict],
                        append: bool = False) -> List[dict]:
    """Schema-valid JSONL telemetry stream: one run_meta header + the
    given records (each a dict WITH its `kind`; run_id is stamped in).
    Every record is validated before anything is written — ring_smoke,
    `width_table --weak-scaling` and `make profile-smoke` all route
    their streams through here, so a schema change breaks loudly in
    exactly one place.

    The header's backend/device metadata comes from the live process
    (metrics.collect_run_meta — callers have an initialized backend by
    the time they hold records to write), so an on-chip session's
    banked cost/profile evidence is never mislabeled as CPU. This lazy
    import is the one jax touch in this module; the read/summarize
    paths stay backend-free, so `obs_report` runs beside a process
    that holds the chip."""
    import os

    from .metrics import collect_run_meta
    from .schema import validate_record

    meta = collect_run_meta()
    meta.update(run_id=run_id,
                code_rev=meta.get('code_rev')
                or os.environ.get('SE3_TPU_CODE_REV', 'dev'),
                backend=meta.get('backend') or 'cpu')
    out = [meta]
    out += [dict(rec, run_id=run_id) for rec in records]
    for r in out:
        validate_record(r)
    # append=True is for long-lived banks:
    # each run adds its own run_meta + records, so cross-session
    # trajectories survive and perf_gate's latest-record-wins model
    # holds; per-run /tmp streams keep the default truncate
    with open(path, 'a' if append else 'w') as f:
        for r in out:
            f.write(json.dumps(r) + '\n')
    return out


def write_comm_stream(path: str, run_id: str,
                      comm_bodies: List[dict]) -> List[dict]:
    """write_record_stream for a comm-accounting run: one kind='comm'
    record per body (each a `parallel.exchange.comm_payload` dict,
    optionally already carrying label/step_s)."""
    return write_record_stream(
        path, run_id, [dict(kind='comm', **body) for body in comm_bodies])


def summarize_comm_records(records: List[dict]) -> dict:
    """Reduce comm records (parallel.exchange.comm_payload rows) to the
    view the run report surfaces: per-arm {overlap, exchange, collective
    counts/bytes} plus the aggregate all-gather-free verdict (true only
    when EVERY exchange-enabled arm traced clean — the serialized/dense
    control arm of an A/B is allowed its gathers, that is its point)."""
    comms = [r for r in records if r.get('kind', 'comm') == 'comm']
    arms = []
    for r in comms:
        arm = {k: r[k] for k in ('sp', 'ring_steps', 'overlap', 'exchange',
                                 'all_gather_free', 'step_s', 'label')
               if k in r}
        arm['collectives'] = {
            cls: dict(count=st.get('count'), bytes=st.get('bytes'))
            for cls, st in (r.get('collectives') or {}).items()}
        if r.get('full_width_all_gathers'):
            arm['full_width_all_gathers'] = r['full_width_all_gathers']
        arms.append(arm)
    exchange_arms = [a for a in arms if a.get('exchange')]
    return dict(
        programs=len(arms),
        arms=arms,
        all_gather_free=bool(exchange_arms) and all(
            a.get('all_gather_free') for a in exchange_arms),
    )


def summarize_cost_records(records: List[dict]) -> dict:
    """Reduce cost records (observability.costs.cost_payload rows) to
    the view the run report surfaces: one row per program label with
    flops/peak memory and the source that produced them (a fallback
    estimate stays distinguishable from XLA's analysis)."""
    costs = [r for r in records if r.get('kind', 'cost') == 'cost']
    programs = []
    for r in costs:
        row = {k: r[k] for k in ('label', 'source', 'flops',
                                 'bytes_accessed') if k in r}
        mem = r.get('memory') or {}
        row['peak_bytes'] = r.get('peak_bytes')
        row['peak_gb'] = round((r.get('peak_bytes') or 0) / 2**30, 3)
        row['temp_bytes'] = mem.get('temp_bytes')
        if r.get('collectives'):
            row['collectives'] = r['collectives']
        programs.append(row)
    return dict(programs=len(programs), by_program=programs)


def summarize_profile_records(records: List[dict]) -> dict:
    """Reduce profile records (observability.profiling.profile_payload
    rows) to the surfaced view: per-program coverage, device time, and
    the hottest scopes."""
    profs = [r for r in records if r.get('kind', 'profile') == 'profile']
    programs = []
    for r in profs:
        row = {k: r[k] for k in ('label', 'device_time_ms', 'coverage',
                                 'steps') if k in r}
        scopes = r.get('scopes') or {}
        row['scopes'] = {
            s: st.get('share') for s, st in
            sorted(scopes.items(),
                   key=lambda kv: -(kv[1].get('time_ms') or 0))}
        if r.get('roofline'):
            row['roofline'] = r['roofline']
        programs.append(row)
    return dict(programs=len(programs), by_program=programs)


def summarize_fleet_records(records: List[dict]) -> dict:
    """Reduce fleet records (serving.fleet.FleetRouter.record_body
    rows) to the surfaced view: the final record's per-host states,
    transition/recovery counts, cross-host retry + rollout/rollback
    evidence, and the load-bearing zero-lost verdict (counters are
    cumulative, so the last record carries the run's story)."""
    fleets = [r for r in records if r.get('kind', 'fleet') == 'fleet']
    if not fleets:
        return dict(records=0)
    last = fleets[-1]
    hosts = last.get('hosts') or {}
    return dict(
        records=len(fleets),
        label=last.get('label'),
        hosts={hid: snap.get('state') for hid, snap in hosts.items()},
        host_transitions=len(last.get('host_transitions') or []),
        recoveries=last.get('recoveries'),
        cross_host_retries=last.get('cross_host_retries'),
        request_failures=last.get('request_failures'),
        timeouts=last.get('timeouts'),
        heartbeats=last.get('heartbeats'),
        rollouts=(last.get('rollouts') or {}).get('count'),
        rollbacks=last.get('rollbacks'),
        submitted=last.get('submitted'),
        answered=last.get('answered'),
        lost_requests=last.get('lost_requests'),
        zero_lost=last.get('lost_requests') == 0,
    )


def summarize(records: List[dict], anchor: Optional[float] = None):
    """One run's summary, or a `telemetry_summary` of several."""
    tele = summarize_telemetry(records, anchor=anchor)
    if len(tele) == 1:
        return tele[0]
    return dict(kind='telemetry_summary', runs=tele)
