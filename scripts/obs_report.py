"""Render telemetry JSONL streams into one summary JSON.

Usage:
    python scripts/obs_report.py STREAM.jsonl [MORE.jsonl ...]
        [--validate] [--out SUMMARY.json] [--anchor FLOAT]
        [--require kind[,kind...]]

--require gates the stream on record kinds (pipeline / comm / tune /
cost / profile / serve / ... / assembly / mesh_sweep), each with its
load-bearing check.

A stream (kind=run_meta/step/flush/summary records from a
`denoise.py --telemetry` run) is reduced to one record per run
(metric/value/unit/vs_baseline/step_ms/loss trajectory) with per-phase
p50/p95 and the retrace-warning count.

--validate additionally gates the streams on the record schema
(observability.schema) and exits non-zero on violation — `make
obs-smoke` runs exactly that. Never initializes a device backend (no
jax.devices()/default_backend() call anywhere on this path), so it
works beside a process that holds the chip.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from se3_transformer_tpu.observability.report import (  # noqa: E402
    load_jsonl, summarize,
)
from se3_transformer_tpu.observability.schema import (  # noqa: E402
    SchemaError, validate_stream,
)


def _gate_pipeline(records):
    pipes = [r for r in records if r.get('kind') == 'pipeline']
    if not pipes:
        print('PIPELINE GATE: no pipeline records in the stream '
              '(was the run started with --pipelined?)', file=sys.stderr)
        return False
    last = pipes[-1].get('prefetch', {})
    hits, stalls = last.get('hits', 0), last.get('stalls', 0)
    if not hits:
        print(f'PIPELINE GATE: 100% prefetch stalls ({stalls} stalls, '
              f'0 hits) — the producer never got ahead of the device',
              file=sys.stderr)
        return False
    print(f'pipeline gate ok: {hits} hits / {stalls} stalls, '
          f'verdict {pipes[-1].get("verdict")}', file=sys.stderr)
    return True


def _gate_comm(records):
    comms = [r for r in records if r.get('kind') == 'comm']
    if not comms:
        print('COMM GATE: no comm records in the stream (was the run '
              'traced with the exchange instrumented?)', file=sys.stderr)
        return False
    ex_arms = [r for r in comms if r.get('exchange')]
    if not ex_arms:
        print('COMM GATE: no exchange-enabled comm record — the '
              'sparse path was never traced', file=sys.stderr)
        return False
    dirty = [r for r in ex_arms if not r.get('all_gather_free')]
    if dirty:
        shapes = [s for r in dirty
                  for s in r.get('full_width_all_gathers', [])]
        print(f'COMM GATE: {len(dirty)} exchange-enabled program(s) '
              f'still carry full-width all-gathers: {shapes}',
              file=sys.stderr)
        return False
    print(f'comm gate ok: {len(comms)} comm records, '
          f'{len(ex_arms)} exchange arms, all all-gather-free',
          file=sys.stderr)
    return True


def _gate_tune(records):
    tunes = [r for r in records if r.get('kind') == 'tune']
    if not tunes:
        print('TUNE GATE: no tune records in the stream (was '
              'scripts/tune_kernels.py run?)', file=sys.stderr)
        return False
    promoted = [r for r in tunes if r.get('verdict') == 'promoted']
    consulted = [r for r in tunes if r.get('verdict') == 'consulted']
    if not promoted:
        print('TUNE GATE: no candidate was promoted', file=sys.stderr)
        return False
    if not consulted:
        print('TUNE GATE: no consulted verdict — the promoted entry '
              'was never proven to steer a subsequent pick',
              file=sys.stderr)
        return False
    print(f'tune gate ok: {len(tunes)} tune records, '
          f'{len(promoted)} promoted, {len(consulted)} consulted',
          file=sys.stderr)
    return True


def _gate_cost(records):
    costs = [r for r in records if r.get('kind') == 'cost']
    if not costs:
        print('COST GATE: no cost records in the stream (was the run '
              'ledgered — engine warmup, --cost-record?)', file=sys.stderr)
        return False
    empty = [r for r in costs if not r.get('peak_bytes')]
    if empty:
        labels = [r.get('label') for r in empty]
        print(f'COST GATE: {len(empty)} cost record(s) with zero peak '
              f'memory — the ledger measured nothing: {labels}',
              file=sys.stderr)
        return False
    unavailable = [r.get('label') for r in costs
                   if r.get('source') == 'unavailable']
    if unavailable:
        print(f'COST GATE: source=unavailable for {unavailable} — '
              f'neither cost_analysis nor the HLO fallback produced '
              f'numbers', file=sys.stderr)
        return False
    print(f'cost gate ok: {len(costs)} cost records, peak '
          f'{max(r["peak_bytes"] for r in costs) / 2**20:.1f} MiB max',
          file=sys.stderr)
    return True


def _gate_profile(records):
    profs = [r for r in records if r.get('kind') == 'profile']
    if not profs:
        print('PROFILE GATE: no profile records in the stream (was a '
              'trace captured and attributed — make profile-smoke?)',
              file=sys.stderr)
        return False
    dead = [r.get('label') for r in profs
            if not r.get('device_time_ms') or not r.get('scopes')]
    if dead:
        print(f'PROFILE GATE: profile record(s) with no device time or '
              f'no scopes: {dead} — the trace attributed nothing',
              file=sys.stderr)
        return False
    worst = min(r.get('coverage', 0) for r in profs)
    print(f'profile gate ok: {len(profs)} profile records, worst '
          f'coverage {worst:.0%} (the >=80% bar is enforced where the '
          f'trace is captured: scripts/profile_smoke.py)',
          file=sys.stderr)
    return True


def _gate_serve(records):
    serves = [r for r in records if r.get('kind') == 'serve']
    if not serves:
        print('SERVE GATE: no serve records in the stream (was the run '
              'served through ServeTelemetry/RouterTelemetry?)',
              file=sys.stderr)
        return False
    # counters are cumulative, so the last record carries the verdict
    last = serves[-1]
    answered = (last.get('requests') or {}).get('served') or 0
    if not answered:
        print('SERVE GATE: zero answered requests in the final serve '
              'record — the stream proves nothing was served',
              file=sys.stderr)
        return False
    timed = [(b, st) for r in serves
             for b, st in (r.get('buckets') or {}).items()]
    if not timed:
        print('SERVE GATE: no per-bucket latency section in any serve '
              'record — the SLO surface is empty', file=sys.stderr)
        return False
    broken = [b for b, st in timed
              if not isinstance(st, dict)
              or any(st.get(k) is None
                     for k in ('count', 'p50_ms', 'p95_ms', 'p99_ms'))]
    if broken:
        print(f'SERVE GATE: bucket(s) {sorted(set(broken))} missing or '
              f'null latency percentiles (count/p50/p95/p99 are the '
              f'SLO surface)', file=sys.stderr)
        return False
    extras = ''
    if 'continuous_admissions' in last:
        extras = (f", {last['continuous_admissions']} continuous "
                  f"admissions, {len(last.get('replicas') or {})} "
                  f"replicas, {(last.get('swaps') or {}).get('count', 0)} "
                  f"swap events")
    print(f'serve gate ok: {len(serves)} serve records, {answered} '
          f'answered rows, {len(timed)} timed bucket windows{extras}',
          file=sys.stderr)
    return True


def _gate_fault(records):
    faults = [r for r in records if r.get('kind') == 'fault']
    if not faults:
        print('FAULT GATE: no fault records in the stream (was the run '
              'chaos-exercised — scripts/chaos_smoke.py?)',
              file=sys.stderr)
        return False
    last = faults[-1]
    if not last.get('injections_total'):
        print('FAULT GATE: zero injections in the final fault record — '
              'a fault record that exercised nothing proves nothing',
              file=sys.stderr)
        return False
    lost = last.get('lost_requests')
    if lost != 0:
        print(f'FAULT GATE: lost_requests={lost!r} — every submit must '
              f'resolve answered-or-structured-error under injected '
              f'faults (zero-lost contract)', file=sys.stderr)
        return False
    print(f"fault gate ok: {len(faults)} fault records, "
          f"{last['injections_total']} injections, "
          f"{last.get('recoveries', 0)} quarantine recoveries, "
          f"{last.get('retries', 0)} retries / "
          f"{last.get('timeouts', 0)} timeouts / "
          f"{last.get('request_failures', 0)} structured failures, "
          f"0 lost", file=sys.stderr)
    return True


def _gate_guard(records):
    guards = [r for r in records if r.get('kind') == 'guard']
    if not guards:
        print('GUARD GATE: no guard records in the stream (was the run '
              'trained through the guardian — train_guarded / '
              'scripts/train_chaos_smoke.py?)', file=sys.stderr)
        return False
    last = guards[-1]
    if not last.get('injections_total'):
        print('GUARD GATE: zero injections in the final guard record — '
              'a guard record that exercised nothing proves nothing',
              file=sys.stderr)
        return False
    if last.get('diverged') is not False:
        print(f'GUARD GATE: diverged={last.get("diverged")!r} — the '
              f'guarded run must end on finite, policy-clean '
              f'parameters (rollback paid every trip down)',
              file=sys.stderr)
        return False
    print(f"guard gate ok: {len(guards)} guard records, "
          f"{last['injections_total']} injections, "
          f"{last.get('trips', 0)} trips / "
          f"{last.get('rollbacks', 0)} rollbacks / "
          f"{last.get('restarts', 0)} restarts / "
          f"{last.get('preemptions', 0)} preemptions, not diverged",
          file=sys.stderr)
    return True


def _gate_fleet(records):
    fleets = [r for r in records if r.get('kind') == 'fleet']
    if not fleets:
        print('FLEET GATE: no fleet records in the stream (was the run '
              'served through a FleetRouter — '
              'scripts/fleet_chaos_smoke.py / serve.py --fleet?)',
              file=sys.stderr)
        return False
    last = fleets[-1]
    if not last.get('host_transitions'):
        print('FLEET GATE: empty host_transitions log in the final '
              'fleet record — a fleet record where no host breaker '
              'ever moved proves nothing was exercised',
              file=sys.stderr)
        return False
    lost = last.get('lost_requests')
    if lost != 0:
        print(f'FLEET GATE: lost_requests={lost!r} — every submit must '
              f'resolve answered-or-structured-error FLEET-WIDE across '
              f'host deaths, redispatches and rollouts (zero-lost '
              f'contract)', file=sys.stderr)
        return False
    print(f"fleet gate ok: {len(fleets)} fleet records, "
          f"{len(last.get('hosts') or {})} hosts, "
          f"{len(last['host_transitions'])} host transitions / "
          f"{last.get('recoveries', 0)} recoveries, "
          f"{last.get('cross_host_retries', 0)} cross-host retries, "
          f"{(last.get('rollouts') or {}).get('count', 0)} rollout "
          f"events / {last.get('rollbacks', 0)} rollbacks, 0 lost",
          file=sys.stderr)
    return True


def _gate_trace(records):
    recs = [r for r in records if r.get('kind') == 'trace']
    if not recs:
        print('TRACE GATE: no trace records in the stream (was '
              'scripts/slo_smoke.py / fleet_chaos_smoke.py run?)',
              file=sys.stderr)
        return False
    last = recs[-1]
    if not last.get('complete_trees'):
        print(f'TRACE GATE: zero complete span trees (traces='
              f'{last.get("traces")}) — no request produced a '
              f'single-root tree', file=sys.stderr)
        return False
    if last.get('orphan_spans'):
        print(f'TRACE GATE: {last["orphan_spans"]} orphan span(s) — '
              f'spans whose parent never appears in their trace '
              f'(instrumentation lost part of a request\'s story)',
              file=sys.stderr)
        return False
    print(f'trace gate ok: {last.get("complete_trees")}/'
          f'{last.get("traces")} complete trees, zero orphans, '
          f'{last.get("multi_host_traces")} multi-host trace(s), '
          f'{last.get("redispatch_hops")} redispatch hop(s) '
          f'(completeness_total itself is enforced by '
          f'scripts/perf_gate.py)', file=sys.stderr)
    return True


def _gate_slo(records):
    recs = [r for r in records if r.get('kind') == 'slo']
    if not recs:
        print('SLO GATE: no slo records in the stream (was '
              'scripts/slo_smoke.py run?)', file=sys.stderr)
        return False
    last = recs[-1]
    if not last.get('answered'):
        print('SLO GATE: zero answered requests — the record proves '
              'no served traffic', file=sys.stderr)
        return False
    avail = last.get('availability')
    if not isinstance(avail, (int, float)):
        print(f'SLO GATE: availability {avail!r} is not numeric',
              file=sys.stderr)
        return False
    print(f'slo gate ok: {last.get("hosts")} host(s), availability '
          f'{avail}, {last.get("answered")} answered, buckets '
          f'{sorted(last.get("buckets") or {})} (the availability '
          f'floor itself is enforced by scripts/perf_gate.py)',
          file=sys.stderr)
    return True


def _gate_assembly(records):
    recs = [r for r in records if r.get('kind') == 'assembly']
    if not recs:
        print('ASSEMBLY GATE: no assembly records in the stream (was '
              'scripts/assembly_smoke.py run?)', file=sys.stderr)
        return False
    last = recs[-1]
    if not last.get('bucket_served'):
        print('ASSEMBLY GATE: zero rows served through the engine '
              'bucket — the record proves nothing about serving',
              file=sys.stderr)
        return False
    if last.get('post_warmup_compiles'):
        print(f'ASSEMBLY GATE: {last["post_warmup_compiles"]} '
              f'post-warmup compile(s) — the AOT bucket executable '
              f'was not actually reused', file=sys.stderr)
        return False
    parity = last.get('parity_linf')
    if not isinstance(parity, (int, float)) or parity >= 1e-4:
        print(f'ASSEMBLY GATE: global-vs-materialized parity '
              f'{parity!r} >= 1e-4 (or missing) — the streaming arm '
              f'diverged from the all-pairs reference', file=sys.stderr)
        return False
    ratio = last.get('hbm_materialized_vs_global')
    if not isinstance(ratio, (int, float)) or ratio <= 0:
        print(f'ASSEMBLY GATE: degenerate hbm_materialized_vs_global '
              f'{ratio!r} — the record proves no memory claim',
              file=sys.stderr)
        return False
    print(f'assembly gate ok: {len(recs)} assembly records, '
          f'n={last.get("n")} served via bucket {last.get("bucket")} '
          f'({last.get("bucket_served")} rows, zero post-warmup '
          f'compiles), parity {parity:.2e}, eq '
          f'{last.get("equivariance_l2")}, materialized/global HBM '
          f'{ratio} (the >=3x floor and the equivariance ceiling are '
          f'enforced by scripts/perf_gate.py)', file=sys.stderr)
    return True


def _gate_mesh_sweep(records):
    recs = [r for r in records if r.get('kind') == 'mesh_sweep']
    if not recs:
        print('MESH GATE: no mesh_sweep records in the stream (was '
              'scripts/width_table.py --mesh-sweep run?)', file=sys.stderr)
        return False
    # latest row per (dp, sp, tp) point: EVERY mesh point must hold the
    # composed contract, not just the final one swept
    latest = {}
    for r in recs:
        latest[(r.get('dp'), r.get('sp'), r.get('tp'))] = r
    bad = []
    for point, r in sorted(latest.items()):
        comm = r.get('comm') or {}
        if not r.get('loss_finite'):
            bad.append(f'{point}: non-finite loss')
        elif not comm.get('all_gather_free'):
            bad.append(f'{point}: full-width all-gathers '
                       f'{comm.get("full_width_all_gathers")}')
        elif not comm.get('axis_collectives', {}) and (
                r.get('sp', 1) > 1 or r.get('dp', 1) > 1
                or r.get('tp', 1) > 1):
            bad.append(f'{point}: empty axis_collectives on a '
                       f'multi-axis mesh — nothing to gate per axis')
    if bad:
        print(f'MESH GATE: {len(bad)}/{len(latest)} mesh points '
              f'breach the composed contract: ' + '; '.join(bad),
              file=sys.stderr)
        return False
    pts = ' '.join(f'({d},{s},{t})' for d, s, t in sorted(latest))
    print(f'mesh gate ok: {len(recs)} mesh_sweep records over '
          f'{len(latest)} points {pts} — all loss-finite and '
          f'all-gather-free with per-axis attribution (byte ceilings '
          f'are enforced by scripts/perf_gate.py)', file=sys.stderr)
    return True


def _gate_transport(records):
    recs = [r for r in records if r.get('kind') == 'transport']
    if not recs:
        print('TRANSPORT GATE: no transport records in the stream '
              '(was scripts/transport_loadgen.py run?)', file=sys.stderr)
        return False
    r = recs[-1]
    arms = r.get('arms') or {}
    bad = []
    for name in ('legacy', 'binary'):
        arm = arms.get(name) or {}
        if not arm.get('requests'):
            bad.append(f'{name} arm served no requests — the A/B '
                       f'compares nothing')
        elif arm.get('errors'):
            bad.append(f'{name} arm had {arm["errors"]} errors on a '
                       f'fault-free workload')
    tstats = r.get('transport') or {}
    if tstats.get('frame_errors'):
        bad.append(f'{tstats["frame_errors"]} frame errors on a '
                   f'clean wire — the framing is corrupting data')
    if tstats.get('reconnects'):
        bad.append(f'{tstats["reconnects"]} reconnects with no host '
                   f'restart — connections are not persisting')
    if (tstats.get('peak_in_flight') or 0) < 2:
        bad.append(f'binary peak_in_flight='
                   f'{tstats.get("peak_in_flight")} — nothing ever '
                   f'multiplexed, the pooled arm degenerated to '
                   f'serial calls')
    if bad:
        print(f'TRANSPORT GATE: ' + '; '.join(bad), file=sys.stderr)
        return False
    print(f'transport gate ok: binary {r.get("qps_binary_vs_legacy")}x '
          f'qps vs legacy, p99 ratio {r.get("p99_binary_vs_legacy")}, '
          f'wire-bytes ratio {r.get("wire_bytes_binary_vs_legacy")}, '
          f'peak in-flight {tstats.get("peak_in_flight")}, zero frame '
          f'errors (the numeric floors/ceilings are enforced by '
          f'scripts/perf_gate.py)', file=sys.stderr)
    return True


_REQUIRE_GATES = dict(pipeline=_gate_pipeline, comm=_gate_comm,
                      tune=_gate_tune, cost=_gate_cost,
                      profile=_gate_profile, serve=_gate_serve,
                      fault=_gate_fault, guard=_gate_guard,
                      fleet=_gate_fleet,
                      trace=_gate_trace, slo=_gate_slo,
                      assembly=_gate_assembly,
                      mesh_sweep=_gate_mesh_sweep,
                      transport=_gate_transport)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='aggregate telemetry JSONL into one summary')
    ap.add_argument('paths', nargs='+', help='JSONL stream(s)')
    ap.add_argument('--validate', action='store_true',
                    help='gate telemetry streams on the record schema '
                         '(exit 1 on violation)')
    ap.add_argument('--out', default=None,
                    help='also write the summary JSON to this path')
    ap.add_argument('--anchor', type=float, default=None,
                    help='vs_baseline anchor for telemetry throughput')
    ap.add_argument('--require', default=None, metavar='KIND[,KIND...]',
                    help='gate the stream on record kinds: '
                         f'{sorted(_REQUIRE_GATES)}. Each kind runs its '
                         'load-bearing check (pipeline: >=1 prefetch '
                         'hit; comm: every exchange arm all-gather-'
                         'free; tune: a promotion that is consulted; '
                         'cost: every program ledgers nonzero peak '
                         'memory; profile: per-scope attribution '
                         'present with its coverage figure; serve: '
                         'per-bucket latency percentiles present and '
                         'a nonzero answered count; fault: injections '
                         'present and zero lost requests; guard: '
                         'injections present and diverged == false; '
                         'fleet: host-breaker transitions present and '
                         'zero lost requests fleet-wide; trace: at '
                         'least one complete span tree and zero '
                         'orphan spans; slo: nonzero answered and a '
                         'numeric availability; assembly: rows served '
                         'through an engine bucket with zero '
                         'post-warmup compiles and sub-1e-4 parity) '
                         'and exits non-zero on failure')
    args = ap.parse_args(argv)

    required = {k.strip() for k in (args.require or '').split(',')
                if k.strip()}
    unknown = required - set(_REQUIRE_GATES)
    if unknown:
        print(f'unknown --require kinds {sorted(unknown)} '
              f'(known: {sorted(_REQUIRE_GATES)})', file=sys.stderr)
        return 2

    records = []
    for path in args.paths:
        recs = load_jsonl(path)
        if args.validate and any(r.get('kind') == 'run_meta'
                                 for r in recs):
            try:
                info = validate_stream(path)
            except SchemaError as e:
                print(f'{path}: SCHEMA VIOLATION: {e}', file=sys.stderr)
                return 1
            print(f'{path}: schema ok ({info["records"]} records, '
                  f'kinds {info["kinds"]})', file=sys.stderr)
        records += recs

    if not records:
        print('no records found', file=sys.stderr)
        return 1

    for kind in sorted(required):
        if not _REQUIRE_GATES[kind](records):
            return 1

    summary = summarize(records, anchor=args.anchor)
    text = json.dumps(summary, indent=1)
    print(text)
    if args.out:
        with open(args.out, 'w') as f:
            f.write(text + '\n')
    return 0


if __name__ == '__main__':
    sys.exit(main())
