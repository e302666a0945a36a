"""Automated regression gate: committed budgets vs record streams.

A committed budget file (PERF_BUDGETS.json) declares per-metric
floors/ceilings with noise margins — proof bits, byte and memory
ceilings read from HLO (per mesh axis too), zero-lost-request and
equivariance facts; none reads a clock — and this script compares
record streams against them, exiting non-zero with a readable diff on
any breach.

    python scripts/perf_gate.py [RECORDS.jsonl ...]
        [--budgets PERF_BUDGETS.json] [--fresh-cost STREAM.jsonl]
        [--inject-regression [NAME]] [--strict]

With no record paths, the committed evidence set is gated
(DEFAULT_RECORDS; tier-1 holds the same in tests/test_costs.py) —
`make perf-gate` additionally produces a FRESH toy cost record
(--fresh-cost compiles the toy denoise train step on CPU and ledgers it
through observability.costs),
then re-runs with --inject-regression and asserts the non-zero exit:
the gate must both pass on healthy numbers AND actually fire.

Budget semantics (see PERF_BUDGETS.json):
  * `kind`   — which records the budget applies to: 'width'
    (width_table rows) or a telemetry `kind` (comm / cost / serve /
    profile ...).
  * `match`  — field -> expected filters (dotted paths; a string value
    matches as substring, anything else as equality).
  * `field`  — dotted path of the gated value.
  * one of `min` / `max` / `equals`, with `margin` (relative): a min
    budget passes at value >= min*(1-margin), a max budget at
    value <= max*(1+margin). `missing` says what an absent field
    means: 'fail' (default), 'zero' (absent collective class = 0
    bytes), or 'skip'.
  * evaluation uses the LAST matching record — streams are
    append-only chronological, so the latest evidence is gated and
    historical rows can never permanently trip a tightened budget.
    `group_by` (dotted path, e.g. "sp", or a comma-separated list of
    paths, e.g. "dp,sp,tp") instead judges the latest record of EVERY
    distinct value (tuple of values) of those fields, so a proof bit
    over a sweep ("all_gather_free at every sp" / "at every mesh
    point") cannot be masked by the final sweep point being clean.
  * `axis`   — annotation naming the mesh axis a collective budget
    guards (surfaced in the diff, so an sp-axis regression reads as
    one).

Budgets whose kind has no matching record are SKIPPED (reported;
--strict turns them into failures): a smoke's own stream holds its
own kinds only.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

DEFAULT_BUDGETS = os.path.join(REPO, 'PERF_BUDGETS.json')
# SERVE_MULTI.jsonl: the banked `make serve-multi-smoke` stream, so the
# serving budgets (zero post-warmup compiles, continuous-admission
# proof bit) are judged by a plain `make perf-gate`.
# CHAOS_SMOKE.jsonl: the banked `make chaos-smoke` fault-domain stream,
# so the zero-lost-requests contract, the observed quarantine->recovery
# transition, and the nonzero-injections proof bit are judged too.
# TRAIN_CHAOS.jsonl: the banked `make train-chaos-smoke` self-healing
# training stream, so the zero-divergence contract, the observed
# rollback, and the nonzero-injections proof bit are judged too.
# FLEET_CHAOS.jsonl: the banked `make serve-fleet-smoke` cross-host
# stream, so the fleet-wide zero-lost contract, the observed host
# quarantine->recovery, and the canary auto-rollback are judged too.
# SLO_SMOKE.jsonl: the banked `make slo-smoke` traced-fleet stream, so
# the fleet availability floor and the trace-completeness invariant
# (every resolved request = one complete single-root span tree) are
# judged by a plain `make perf-gate`.
# ASSEMBLY_SWEEP.jsonl: the banked `make assembly-smoke` kNN-free
# large-assembly stream, so the >=3x streaming-vs-materialized peak-HBM
# floor at the 4096 bucket, the tightened global equivariance ceiling,
# and the served-through-an-engine-bucket proof bit are judged too.
# MESH_SWEEP.jsonl: the banked `make mesh-smoke` composed-parallelism
# sweep (one row per (dp,sp,tp) mesh point on the 8-device sim), so the
# every-point all-gather-free proof bit, the per-axis ppermute /
# all-reduce byte ceilings, and the per-shard memory ceiling are judged
# by a plain `make perf-gate`.
# TRANSPORT_AB.jsonl: the banked `make transport-smoke` loadgen A/B
# (legacy connect-per-call JSON vs pooled multiplexed binary framing on
# the same seeded workload), so the binary-vs-legacy wire-bytes ceiling
# is judged by a plain `make perf-gate`.
DEFAULT_RECORDS = ('WIDTH_TABLE.jsonl', 'SERVE_MULTI.jsonl',
                   'CHAOS_SMOKE.jsonl', 'TRAIN_CHAOS.jsonl',
                   'FLEET_CHAOS.jsonl', 'SLO_SMOKE.jsonl',
                   'ASSEMBLY_SWEEP.jsonl', 'MESH_SWEEP.jsonl',
                   'TRANSPORT_AB.jsonl')


# --------------------------------------------------------------------- #
# record loading / classification
# --------------------------------------------------------------------- #
def load_records(path):
    """JSONL stream, JSON list, or a single JSON object."""
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except ValueError:
        data = None
    if isinstance(data, list):
        return [r for r in data if isinstance(r, dict)]
    if isinstance(data, dict):
        return [data]
    from se3_transformer_tpu.observability.report import load_jsonl
    return load_jsonl(path)


def record_kind(rec):
    if 'kind' in rec:
        return rec['kind']
    if rec.get('weak_scaling') or 'per_shard_total_gb' in rec:
        return 'width'
    return None


# --------------------------------------------------------------------- #
# budget evaluation
# --------------------------------------------------------------------- #
def get_path(rec, dotted):
    cur = rec
    for part in dotted.split('.'):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def matches(rec, match):
    for path, want in (match or {}).items():
        got = get_path(rec, path)
        if isinstance(want, str) and not isinstance(want, bool):
            if got is None or want not in str(got):
                return False
        elif got != want:
            return False
    return True


def evaluate(budget, records):
    """-> (status, detail) with status in {'ok', 'FAIL', 'skip'}.

    With `group_by` (a dotted path, e.g. "sp", or several separated by
    commas, e.g. "dp,sp,tp"), the pool is partitioned by those fields'
    value tuple and the LAST record of EVERY group is judged — a
    proof-bit budget over a multi-point sweep (all_gather_free "at
    every sp" / "at every (dp,sp,tp) mesh point") can then never be
    masked by the final sweep point being clean while an earlier one
    regressed. Multi-key grouping matters on composed sweeps: grouped
    by "sp" alone, a clean (2,2,2) row would shadow a regressed
    (4,2,1) row that shares its sp value."""
    group_by = budget.get('group_by')
    if group_by:
        pool = [r for r in records if record_kind(r) == budget.get('kind')
                and matches(r, budget.get('match'))]
        if not pool:
            return 'skip', f'no matching {budget.get("kind")} record'
        keys = [k.strip() for k in group_by.split(',') if k.strip()]
        groups = {}
        for r in pool:   # later records overwrite: latest-per-group
            groups[tuple(str(get_path(r, k)) for k in keys)] = r
        results = [(key, *_evaluate_one(budget, [rec]))
                   for key, rec in sorted(groups.items())]
        fails = [f'{key[0] if len(key) == 1 else key}: {d}'
                 for key, s, d in results if s == 'FAIL']
        if fails:
            return 'FAIL', f'{len(fails)}/{len(results)} {group_by}-' \
                           f'groups breach: ' + '; '.join(fails)
        return 'ok', f'all {len(results)} {group_by}-groups ok ' \
                     f'(latest per group judged; e.g. {results[0][2]})'
    return _evaluate_one(budget, records)


def _evaluate_one(budget, records):
    name = budget.get('name', '?')
    kind = budget.get('kind')
    field = budget['field']
    margin = float(budget.get('margin', 0.0))
    pool = [r for r in records if record_kind(r) == kind
            and matches(r, budget.get('match'))]
    if not pool:
        return 'skip', f'no matching {kind} record'
    rec = pool[-1]   # latest evidence wins (streams are chronological)
    value = get_path(rec, field)
    if value is None:
        missing = budget.get('missing', 'fail')
        if missing == 'zero':
            value = 0
        elif missing == 'skip':
            return 'skip', f'field {field} absent in the matching record'
        else:
            return 'FAIL', f'field {field} MISSING in the matching ' \
                           f'record (of {len(pool)})'
    axis = f" [axis={budget['axis']}]" if budget.get('axis') else ''
    src = f'{len(pool)} matching, gated the last'
    if 'equals' in budget:
        want = budget['equals']
        if value != want:
            return 'FAIL', f'{field}={value!r} != required {want!r}' \
                           f'{axis} ({src})'
        return 'ok', f'{field}={value!r}{axis}'
    if 'min' in budget:
        floor = budget['min'] * (1.0 - margin)
        if not isinstance(value, (int, float)) or value < floor:
            return 'FAIL', (f'{field}={value} < floor {floor:.6g} '
                            f'(budget min {budget["min"]}, margin '
                            f'{margin:.0%}){axis} ({src})')
        return 'ok', f'{field}={value} >= {floor:.6g}{axis}'
    if 'max' in budget:
        ceil = budget['max'] * (1.0 + margin)
        if not isinstance(value, (int, float)) or value > ceil:
            return 'FAIL', (f'{field}={value} > ceiling {ceil:.6g} '
                            f'(budget max {budget["max"]}, margin '
                            f'{margin:.0%}){axis} ({src})')
        return 'ok', f'{field}={value} <= {ceil:.6g}{axis}'
    return 'FAIL', f'budget {name} declares no min/max/equals'


def synthesize_breach(budget):
    """A record matching the budget's filters but breaching its
    constraint by 2x the margin — the injected-regression arm that
    proves the gate actually fires."""
    rec = {}
    kind = budget.get('kind')
    if kind == 'width':
        rec['weak_scaling'] = True
    else:
        rec['kind'] = kind
    for path, want in (budget.get('match') or {}).items():
        cur = rec
        parts = path.split('.')
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = want
    margin = float(budget.get('margin', 0.0))
    if 'equals' in budget:
        want = budget['equals']
        breach = (not want) if isinstance(want, bool) else f'not_{want}'
    elif 'min' in budget:
        breach = budget['min'] * (1.0 - margin) * 0.5
    else:
        breach = budget['max'] * (1.0 + margin) * 2.0
    cur = rec
    parts = budget['field'].split('.')
    for p in parts[:-1]:
        cur = cur.setdefault(p, {})
    cur[parts[-1]] = breach
    return rec


# --------------------------------------------------------------------- #
# fresh evidence: one toy cost record, compiled now on this host
# --------------------------------------------------------------------- #
def fresh_cost_stream(path):
    """Compile the toy denoise train step on CPU, ledger it through
    observability.costs, and write a schema-valid stream (run_meta +
    one `cost` record) to `path`. This is the gate's end-to-end proof
    that the ledger itself still works on the current tree."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    from se3_transformer_tpu.observability.report import write_record_stream
    from se3_transformer_tpu.training.denoise import (
        DenoiseConfig, DenoiseTrainer, synthetic_protein_batch,
    )
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    enable_compilation_cache()
    cfg = DenoiseConfig(num_nodes=48, accum_steps=1, num_degrees=2)
    trainer = DenoiseTrainer(cfg)
    batch = synthetic_protein_batch(cfg, trainer.np_rng)
    trainer.init(batch)
    body = trainer.cost_record(batch)
    body['label'] = 'perf_gate_toy,' + body.get('label', '')
    records = write_record_stream(
        path, f'perf_gate_{os.getpid()}', [body])
    flops = (f'{body["flops"]:.3g}' if body['flops'] is not None
             else 'None')
    print(f'fresh cost record -> {path} '
          f'(peak {body["peak_bytes"] / 2**20:.1f} MiB, '
          f'flops {flops}, source {body["source"]})',
          file=sys.stderr)
    return records


# --------------------------------------------------------------------- #
def main(argv=None):
    ap = argparse.ArgumentParser(
        description='compare record streams against committed perf '
                    'budgets; exit non-zero on regression')
    ap.add_argument('paths', nargs='*',
                    help=f'record files (default: the committed '
                         f'evidence set {DEFAULT_RECORDS})')
    ap.add_argument('--budgets', default=DEFAULT_BUDGETS)
    ap.add_argument('--fresh-cost', default=None, metavar='STREAM',
                    help='also compile the toy train step NOW, write '
                         'its cost record stream here, and gate it')
    ap.add_argument('--inject-regression', nargs='?', const='*',
                    default=None, metavar='NAME',
                    help='append a synthetic record breaching the '
                         'named budget (default: every budget) — the '
                         'gate must exit non-zero, proving it fires')
    ap.add_argument('--strict', action='store_true',
                    help='budgets with no matching record fail instead '
                         'of skipping')
    args = ap.parse_args(argv)

    with open(args.budgets) as f:
        spec = json.load(f)
    budgets = spec.get('budgets', [])
    default_margin = float(spec.get('default_margin', 0.0))
    for b in budgets:
        b.setdefault('margin', default_margin)

    paths = list(args.paths) or [
        p for p in (os.path.join(REPO, name) for name in DEFAULT_RECORDS)
        if os.path.exists(p)]
    records = []
    for p in paths:
        recs = load_records(p)
        print(f'{p}: {len(recs)} records', file=sys.stderr)
        records += recs
    if args.fresh_cost:
        records += fresh_cost_stream(args.fresh_cost)

    if args.inject_regression:
        injected = [b for b in budgets
                    if args.inject_regression in ('*', b.get('name'))]
        if not injected:
            print(f'no budget named {args.inject_regression!r}',
                  file=sys.stderr)
            return 2
        for b in injected:
            records.append(synthesize_breach(b))
        print(f'injected {len(injected)} synthetic breach record(s)',
              file=sys.stderr)

    failures = skips = 0
    for b in budgets:
        status, detail = evaluate(b, records)
        tag = {'ok': ' ok ', 'FAIL': 'FAIL', 'skip': 'SKIP'}[status]
        print(f'[{tag}] {b.get("name", "?")}: {detail}')
        if status == 'FAIL':
            failures += 1
        elif status == 'skip':
            skips += 1
    verdict = 'REGRESSION' if failures else 'ok'
    print(f'perf gate {verdict}: {len(budgets) - failures - skips} ok, '
          f'{failures} failed, {skips} skipped '
          f'(budgets {os.path.relpath(args.budgets, REPO)} v'
          f'{spec.get("version", "?")})')
    if failures:
        return 1
    if args.strict and skips:
        print('--strict: skipped budgets count as failures',
              file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
