"""First-class kernel block-size autotuner (END-TO-END, shape-keyed).

A standalone kernel timing ranks block shapes the other way round
from the step they run in (root PERF.md section 6 has three
instances), so this tuner never times a kernel in isolation:

  1. build the real train step (recipes + synthetic batch +
     make_sharded_train_step) and
     trace it once: the kernels' pick functions record every
     (kind, shape, dtype) they resolved — those are the tuning targets;
  2. per target, enumerate only tile-legal, VMEM-model-admissible
     candidates (kernels.tuning.admissible_candidates — the bwd-aware
     admission that excludes up front the bxf (512, 16) / (256, 16)
     Mosaic VMEM compile failures the old sweep paid for);
  3. measure each candidate through the full train step in ALTERNATING
     A/B pairs against the incumbent (host-side dispatch noise is
     one-sided and time-correlated; alternation cancels the drift), via
     `tuning.force(...)` — an in-process pending table entry, no
     subprocess and no env-string round-trip;
  4. promote into the persistent shape-keyed cache (kernels/tuning.py)
     only a candidate that beats the incumbent BY the noise margin in
     EVERY alternating pair;
  5. prove adoption: re-trace the step and require the promoted entry to
     resolve from the cache (`consulted` verdict) — exit non-zero
     otherwise.

Every step emits a schema'd `tune` JSONL record
(observability/schema.py; crash-safe append). `make tune-smoke` runs
the interpret-mode CPU mini-sweep. On chip the tuner is one process by
construction (a chip belongs to one process at a time, so a
subprocess-per-candidate design would fail against its own parent).

Usage:
    python scripts/tune_kernels.py [--dry-run] [--smoke]
        [--out TUNE.jsonl] [--steps 10] [--pairs 3] [--margin 0.03]
        [--recipe flagship_fast] [--kinds plain bxf attention]
        [--max-candidates 0] [--fuse-basis]

--margin is the fractional end-to-end win a candidate must clear; the
default 0.03 sits above the observed same-session window spread
(~1-2%). A non-positive margin still measures end-to-end (never the
standalone kernel) — `make tune-smoke` uses it to exercise the
promotion/consult machinery deterministically on CPU.
"""
import argparse
import json
import os
import sys
import time
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def _emit(args, rec):
    """Schema-validate, then crash-safe append + mirror to stdout."""
    from se3_transformer_tpu.observability.schema import validate_record
    validate_record(rec)
    line = json.dumps(rec)
    print(line, flush=True)
    with open(args.out, 'a') as f:
        f.write(line + '\n')
        f.flush()


def _build_step(args):
    """The real program: module + synthetic batch + sharded
    train step factory. Returns (make_step, state) where make_step()
    hands back a FRESH jitted step (each candidate must re-trace so the
    pick functions re-run) and state carries params/opt_state/data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.parallel.sharding import (
        make_sharded_train_step,
    )
    from se3_transformer_tpu.training import recipes

    if args.smoke:
        # interpret-mode toy: the Pallas kernels forced through the
        # interpreter
        # so the pick functions actually resolve on CPU.
        # --conv-backend so2 traces the banded SO(2) path instead, so
        # the 'so2' kind's streaming chunks become tuning targets
        num_nodes, dim = args.nodes or 32, 8
        # --fuse-pairwise routes the attention blocks through the
        # streaming flash kernel (interpret mode), so the 'flash' /
        # 'flash_stream' kinds become tuning targets; --pallas-attention
        # enables the per-degree fused attention kernel so 'attention'
        # AND 'attention_bwd' picks resolve in the traced train step
        if args.attention_mode == 'global':
            # the kNN-free step: the XLA streaming dispatch consults
            # the 'flash_global' chunk kind directly on CPU (pallas
            # off — the global kernel's stream fallback IS the CPU
            # path), no interpret-mode kernels needed
            assert not (args.fuse_pairwise or args.fuse_basis
                        or args.pallas_attention), \
                '--attention-mode global subsumes the fused-attention ' \
                'flags (the global path always streams)'
            module = SE3TransformerModule(
                num_tokens=24, dim=dim, dim_head=8, heads=2, depth=1,
                attend_self=True, input_degrees=1, num_degrees=2,
                output_degrees=2, reduce_dim_out=True,
                differentiable_coors=True, pallas=False,
                attention_mode='global',
                conv_backend=args.conv_backend)
            label = f'smoke,dim={dim},global,{args.conv_backend}'
        else:
            module = SE3TransformerModule(
                num_tokens=24, dim=dim, dim_head=8, heads=2, depth=1,
                attend_self=True, input_degrees=1, num_degrees=2,
                output_degrees=2, reduce_dim_out=True,
                differentiable_coors=True, num_neighbors=8,
                pallas=True, pallas_interpret=True,
                fuse_basis=args.fuse_basis,
                fuse_pairwise=args.fuse_pairwise,
                flash_interpret=args.fuse_pairwise,
                shared_radial_hidden=args.fuse_pairwise,
                pallas_attention=args.pallas_attention or None,
                pallas_attention_interpret=args.pallas_attention,
                conv_backend=args.conv_backend)
            label = f'smoke,dim={dim},interpret,{args.conv_backend}'
    else:
        num_nodes = args.nodes or 1024
        overrides = dict(output_degrees=2, reduce_dim_out=True)
        if args.fuse_pairwise:
            overrides.update(fuse_pairwise=True,
                             shared_radial_hidden=True)
        if args.pallas_attention:
            overrides['pallas_attention'] = True
        if args.attention_mode == 'global':
            overrides['attention_mode'] = 'global'
        module = recipes.RECIPES[args.recipe](dim=args.dim, **overrides)
        label = f'{args.recipe},dim={args.dim}'

    rng = np.random.RandomState(0)
    if args.smoke:
        seqs = jnp.asarray(rng.randint(0, 24, (1, num_nodes)))
    else:
        seqs = jnp.asarray(rng.normal(size=(1, num_nodes, args.dim)),
                           jnp.float32)
    coords = jnp.asarray(np.cumsum(
        rng.normal(size=(1, num_nodes, 3)), axis=1), jnp.float32)
    coords = coords - coords.mean(axis=1, keepdims=True)
    data = dict(seqs=seqs, coords=coords,
                masks=jnp.ones((1, num_nodes), bool))

    def loss_fn(params, batch, key):
        noise = jax.random.normal(key, batch['coords'].shape,
                                  batch['coords'].dtype)
        noised = batch['coords'] + noise
        out = module.apply({'params': params}, batch['seqs'], noised,
                           mask=batch['masks'], return_type=1)
        loss = (((noised + out) - batch['coords']) ** 2).sum(-1).mean()
        return loss, dict()

    init_fn = jax.jit(module.init, static_argnames=('return_type',))
    params = init_fn(jax.random.PRNGKey(0), seqs, coords,
                     mask=data['masks'], return_type=1)['params']
    optimizer = optax.adam(1e-4)
    state = dict(params=params, opt_state=optimizer.init(params),
                 data=data, key=jax.random.PRNGKey(1),
                 num_nodes=num_nodes, label=label)

    def make_step():
        return make_sharded_train_step(loss_fn, optimizer)

    return make_step, state


def _measure_window(step, state, steps):
    """One timed end-to-end window; returns nodes*steps/sec. The tail
    is host-fetched before the clock stops."""
    import jax
    t0 = time.monotonic()
    params, opt_state = state['params'], state['opt_state']
    key, data = state['key'], state['data']
    loss = None
    for _ in range(steps):
        key, sub = jax.random.split(key)
        params, opt_state, loss, _ = step(params, opt_state, data, sub)
    float(loss)
    jax.block_until_ready(params)
    dt = time.monotonic() - t0
    state.update(params=params, opt_state=opt_state, key=key)
    return state['num_nodes'] * steps / dt


def _targets_from_trace(make_step, state, kinds):
    """Lower (trace-only, no backend compile) a fresh step and read the
    pick-function consult log: the (kind, shape, dtype) tuples the real
    program resolves are the tuning targets."""
    from se3_transformer_tpu.kernels import tuning
    tuning.clear_kernel_caches()
    tuning.reset_consults()
    step = make_step()
    step.lower(state['params'], state['opt_state'], state['data'],
               state['key'])
    targets = []
    seen = set()
    for c in tuning.consults():
        key = (c['kernel'], tuple(c['shape']), c['dtype'])
        if c['kernel'] in kinds and key not in seen:
            seen.add(key)
            targets.append(dict(kernel=c['kernel'], shape=list(c['shape']),
                                dtype=c['dtype'], source=c['source'],
                                blocks=c['blocks']))
    return targets


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='end-to-end shape-keyed kernel block autotuner')
    ap.add_argument('--out', default=os.path.join(REPO, 'TUNE.jsonl'))
    ap.add_argument('--dry-run', action='store_true',
                    help='enumerate admissible candidates and emit tune '
                         'records without measuring or promoting')
    ap.add_argument('--smoke', action='store_true',
                    help='interpret-mode CPU mini-sweep (make tune-smoke)')
    ap.add_argument('--steps', type=int, default=10,
                    help='train steps per timed window')
    ap.add_argument('--pairs', type=int, default=3,
                    help='alternating incumbent/candidate window pairs')
    ap.add_argument('--margin', type=float, default=0.03,
                    help='fractional end-to-end win required to promote')
    ap.add_argument('--recipe', default='flagship_fast')
    ap.add_argument('--dim', type=int, default=64)
    ap.add_argument('--nodes', type=int, default=0)
    ap.add_argument('--kinds', nargs='+',
                    default=['plain', 'bxf', 'attention',
                             'attention_bwd', 'so2', 'flash',
                             'flash_stream', 'flash_global'])
    ap.add_argument('--conv-backend', default='dense',
                    help="smoke module's conv backend ('dense'|'so2');"
                         " 'so2' makes the banded contraction's chunk "
                         "count a tuning target")
    ap.add_argument('--max-candidates', type=int, default=0,
                    help='per target; 0 = all admissible')
    ap.add_argument('--max-targets', type=int, default=0,
                    help='tune only the first N discovered targets; '
                         '0 = all (the smoke gate bounds its runtime '
                         'with this — interpret-mode compiles are slow)')
    ap.add_argument('--fuse-basis', action='store_true',
                    help='smoke: exercise the bxf kind instead of '
                         'plain')
    ap.add_argument('--fuse-pairwise', action='store_true',
                    help='route attention through the streaming flash '
                         'kernel so the flash/flash_stream kinds become '
                         'tuning targets (implies shared_radial_hidden)')
    ap.add_argument('--pallas-attention', action='store_true',
                    help='enable the per-degree fused attention kernel '
                         "so the 'attention' and 'attention_bwd' kinds "
                         'become tuning targets')
    ap.add_argument('--attention-mode', default='knn',
                    choices=('knn', 'global'),
                    help="'global' traces the kNN-free large-assembly "
                         "step so the 'flash_global' stream-chunk kind "
                         'becomes a tuning target')
    args = ap.parse_args(argv)

    if args.smoke:
        os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    if args.smoke:
        try:
            jax.config.update('jax_platforms', 'cpu')
        except Exception:  # noqa: BLE001 - already pinned via env
            pass

    from se3_transformer_tpu.kernels import tuning
    from se3_transformer_tpu.observability import collect_run_meta

    run_id = f'tune-{uuid.uuid4().hex[:12]}'
    meta = collect_run_meta(extra=dict(
        tool='tune_kernels', mode='smoke' if args.smoke else 'full',
        dry_run=args.dry_run, margin=args.margin, pairs=args.pairs,
        steps=args.steps, cache_file=tuning.cache_file()))
    meta['run_id'] = run_id
    _emit(args, meta)

    make_step, state = _build_step(args)
    targets = _targets_from_trace(make_step, state, set(args.kinds))
    if not targets:
        print('no tunable kernel picks resolved in this program '
              '(is the Pallas path enabled?)', file=sys.stderr)
        return 1
    if args.max_targets > 0:
        targets = targets[:args.max_targets]
    device_kind = tuning.current_device_kind()

    promoted_entries = {}  # (kernel, shape, dtype) -> entry; promote()
    # overwrites by key, so only the LAST winner per target is verifiable
    failures = 0
    for tgt in targets:
        kind, shape, dtype = tgt['kernel'], tgt['shape'], tgt['dtype']
        incumbent = tuple(tgt['blocks'])
        cands = [c for c in tuning.admissible_candidates(kind, shape)
                 if c != incumbent]
        if args.max_candidates > 0:
            cands = cands[:args.max_candidates]
        print(f'target {kind}{tuple(shape)} dtype={dtype}: incumbent '
              f'{incumbent} ({tgt["source"]}), {len(cands)} candidates',
              file=sys.stderr)
        if args.dry_run:
            for cand in cands:
                _emit(args, dict(
                    kind='tune', run_id=run_id, kernel=kind, shape=shape,
                    dtype=dtype, candidate=list(cand),
                    incumbent=list(incumbent), blocks=list(incumbent),
                    step_ms=None, verdict='admitted', promoted=False))
            continue

        # incumbent arm: fresh trace at the current pick (cache entry if
        # one is already promoted, else heuristic)
        tuning.clear_kernel_caches()
        step_inc = make_step()
        _measure_window(step_inc, state, 1)  # compile outside the clock
        for cand in cands:
            # shape+dtype pinned: the candidate steers ONLY the target
            # pick — other same-kind shapes in the program keep their
            # deployed resolution, so the A/B measures the program that
            # will actually run after promotion
            with tuning.force(kind, cand, shape=shape, dtype=dtype):
                step_cand = make_step()
                try:
                    _measure_window(step_cand, state, 1)  # compile
                except Exception as e:  # noqa: BLE001 - isolate per
                    # candidate: a Mosaic VMEM reject the model missed
                    # must be recorded, not abort the sweep
                    _emit(args, dict(
                        kind='tune', run_id=run_id, kernel=kind,
                        shape=shape, dtype=dtype, candidate=list(cand),
                        incumbent=list(incumbent),
                        blocks=list(incumbent), step_ms=None,
                        verdict='error', promoted=False,
                        error=f'{type(e).__name__}: {e}'[:300]))
                    failures += 1
                    continue
                pairs = []
                for _ in range(max(1, args.pairs)):
                    r_inc = _measure_window(step_inc, state, args.steps)
                    r_cand = _measure_window(step_cand, state, args.steps)
                    pairs.append(dict(incumbent=round(r_inc, 2),
                                      candidate=round(r_cand, 2)))
            inc_best = max(p['incumbent'] for p in pairs)
            cand_best = max(p['candidate'] for p in pairs)
            # the promotion rule, verbatim from the measured history: the
            # candidate must beat the incumbent BY THE NOISE MARGIN in
            # EVERY alternating pair — a single lost pair under
            # one-sided host noise means the direction is not proven
            wins_all = all(p['candidate'] > p['incumbent'] *
                           (1.0 + args.margin) for p in pairs)
            verdict = 'promoted' if wins_all else 'rejected'
            rec = dict(
                kind='tune', run_id=run_id, kernel=kind, shape=shape,
                dtype=dtype, candidate=list(cand),
                incumbent=list(incumbent),
                blocks=list(cand if verdict == 'promoted' else incumbent),
                # rate = nodes*steps/dt, so dt/steps = nodes/rate
                step_ms=round(state['num_nodes'] / cand_best * 1e3, 3),
                nodes_steps_per_sec=cand_best,
                incumbent_nodes_steps_per_sec=inc_best,
                pairs=pairs, margin=args.margin,
                verdict=verdict, promoted=verdict == 'promoted')
            if verdict == 'promoted':
                tuning.promote(
                    kind, shape, cand, dtype=dtype,
                    device_kind=device_kind,
                    provenance=dict(
                        benched_nodes_steps_per_sec=cand_best,
                        incumbent_nodes_steps_per_sec=inc_best,
                        incumbent_blocks=list(incumbent),
                        pairs=pairs, steps_per_window=args.steps,
                        margin=args.margin, label=state['label'],
                        run_id=run_id))
                promoted_entries[(kind, tuple(shape), dtype)] = \
                    dict(kernel=kind, shape=shape, dtype=dtype,
                         blocks=list(cand))
                # the new entry is the incumbent for later candidates
                incumbent = tuple(cand)
                tuning.clear_kernel_caches()
                step_inc = make_step()
                _measure_window(step_inc, state, 1)
            _emit(args, rec)

    # prove adoption: a fresh trace must resolve every promoted entry
    # from the cache — the `make tune-smoke` gate rides this verdict
    if promoted_entries:
        tuning.clear_kernel_caches()
        tuning.reset_consults()
        step = make_step()
        step.lower(state['params'], state['opt_state'], state['data'],
                   state['key'])
        resolved = {(c['kernel'], tuple(c['shape']), c['dtype']):
                    (c['source'], tuple(c['blocks']))
                    for c in tuning.consults()}
        for ent in promoted_entries.values():
            got = resolved.get(
                (ent['kernel'], tuple(ent['shape']), ent['dtype']))
            ok = got is not None and got[0] == 'cache' \
                and got[1] == tuple(ent['blocks'])
            _emit(args, dict(
                kind='tune', run_id=run_id, kernel=ent['kernel'],
                shape=ent['shape'], dtype=ent['dtype'],
                candidate=ent['blocks'], blocks=ent['blocks'],
                step_ms=None, verdict='consulted' if ok else 'error',
                promoted=bool(ok),
                error=None if ok else f'promoted entry not consulted '
                                      f'(resolved {got})'))
            if not ok:
                failures += 1

    n_promoted = len(promoted_entries)
    print(f'tune_kernels: {len(targets)} targets, {n_promoted} promoted, '
          f'{failures} failures; table at {tuning.cache_file()}',
          file=sys.stderr)
    return 1 if failures else 0


if __name__ == '__main__':
    sys.exit(main())
