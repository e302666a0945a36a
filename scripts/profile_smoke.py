"""Profile-attribution smoke gate (`make profile-smoke`).

Toy run -> jax.profiler trace (`.xplane.pb`) -> device time by the leaves
of MODEL_SCOPES and by phase (observability.profiling, the program's one
trace reducer; on the CPU the AOT executable's HLO text gives each
instruction's op_name) -> schema-valid `cost` + `profile` records.
Exits non-zero unless:

  * the trace parsed into nonzero device time,
  * the leaves cover >= --min-coverage of it (the proof that the labels
    still blanket the hot paths — a new unscoped subsystem shows up here
    as falling coverage, with the offending ops named in the record), and
  * the emitted records validate against observability.schema
    (`scripts/obs_report.py --validate --require cost,profile` re-gates
    the stream from the file alone).

Usage:
    python scripts/profile_smoke.py [--metrics STREAM.jsonl]
        [--min-coverage 0.8] [--nodes 64] [--steps 3]
        [--trace-dir DIR] [--train]

Default is the toy model FORWARD; --train profiles the full train step
instead (`loss` and `optimizer` are leaves too, and forward, backward and
replay are told apart; its coverage is reported, not gated: XLA:CPU
rewrites more instructions without metadata than the chip's compiler).
The traced flagship step on the chip is `benchmark/run.py --trace 1`.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='toy profile-attribution gate (cost+profile records)')
    ap.add_argument('--metrics', default=None,
                    help='write the schema-valid record stream here')
    ap.add_argument('--min-coverage', type=float, default=0.8)
    ap.add_argument('--nodes', type=int, default=64)
    ap.add_argument('--steps', type=int, default=3)
    ap.add_argument('--trace-dir', default='/tmp/profile_smoke_trace')
    ap.add_argument('--train', action='store_true',
                    help='profile the train step instead of the forward '
                         '(coverage reported, not gated)')
    args = ap.parse_args(argv)

    import shutil

    import jax
    jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu.observability.costs import cost_payload
    from se3_transformer_tpu.observability.profiling import (
        capture_step_profile, profile_payload,
    )
    from se3_transformer_tpu.observability.report import write_record_stream
    from se3_transformer_tpu.training.denoise import (
        DenoiseConfig, DenoiseTrainer, synthetic_protein_batch,
    )
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    enable_compilation_cache()

    cfg = DenoiseConfig(num_nodes=args.nodes, accum_steps=1, num_degrees=2)
    trainer = DenoiseTrainer(cfg)
    batch = synthetic_protein_batch(cfg, trainer.np_rng)
    trainer.init(batch)
    module, params = trainer.module, trainer.params

    if args.train:
        label = f'profile_smoke_train,n={args.nodes}'
        rng = jax.random.PRNGKey(0)
        compiled = trainer._step_fn.lower(
            trainer.params, trainer.opt_state, batch, rng).compile()
        # the step donates params/opt_state (parallel.sharding
        # donate_argnums): each call must re-feed the previous call's
        # outputs or the second dispatch reads deleted buffers
        state = dict(params=trainer.params, opt_state=trainer.opt_state)

        def run():
            out = compiled(state['params'], state['opt_state'], batch, rng)
            state['params'], state['opt_state'] = out[0], out[1]
            return out
    else:
        label = f'profile_smoke_forward,n={args.nodes}'

        def fwd(params, coords):
            return module.apply({'params': params}, batch['seqs'], coords,
                                mask=batch['masks'],
                                adj_mat=batch['adj_mat'], return_type=1)

        compiled = jax.jit(fwd).lower(params, batch['coords']).compile()
        coords = jnp.asarray(np.asarray(batch['coords']))

        def run():
            return compiled(params, coords)

    jax.block_until_ready(run())   # warm (AOT, but first dispatch pays
    #                                buffer setup — keep it out of the
    #                                attributed window)
    hlo_text = compiled.as_text()
    cost = cost_payload(compiled, label=label, hlo_text=hlo_text)

    shutil.rmtree(args.trace_dir, ignore_errors=True)
    capture_step_profile(run, log_dir=args.trace_dir, steps=args.steps)
    profile = profile_payload(
        args.trace_dir, label=label, hlo_text=hlo_text,
        flops_per_step=cost['flops'], steps=args.steps,
        device_kind=(None if jax.default_backend() == 'cpu'
                     else jax.devices()[0].device_kind))

    print(json.dumps(dict(label=label,
                          coverage=profile['coverage'],
                          device_time_ms=profile['device_time_ms'],
                          scopes={s: st['share']
                                  for s, st in profile['scopes'].items()},
                          phases={s: st['share']
                                  for s, st in profile['phases'].items()},
                          unattributed_top=profile['unattributed_top'][:5],
                          peak_bytes=cost['peak_bytes'],
                          flops=cost['flops'],
                          roofline=profile.get('roofline')), indent=1))

    if args.metrics:
        write_record_stream(
            args.metrics, f'profile_smoke_{os.getpid()}',
            [dict(cost, kind='cost'), dict(profile, kind='profile')])
        print(f'records -> {args.metrics}', file=sys.stderr)

    ok = True
    if not profile['device_time_ms']:
        print('FAIL: trace carried zero device time', file=sys.stderr)
        ok = False
    if not cost['peak_bytes']:
        print('FAIL: cost ledger measured zero peak memory',
              file=sys.stderr)
        ok = False
    if not args.train and profile['coverage'] < args.min_coverage:
        print(f'FAIL: scope attribution covers {profile["coverage"]:.0%} '
              f'of device time < required {args.min_coverage:.0%} — '
              f'hottest unattributed ops: '
              f'{profile["unattributed_top"][:5]}', file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
