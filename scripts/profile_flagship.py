"""Trace the bench-identical flagship TRAIN step (fast path by default).

Traces the full training step of the exact program bench.py times —
fast/conservative, optional remat policy and edge_chunks — so
trace_summary.py can attribute the step's wall clock op by op.

    python scripts/profile_flagship.py [--conservative] [--remat POLICY]
        [--chunks N] [--steps 2] [--out /tmp/flagship_fast_trace]

One process per chip: run only when no other process holds the chip.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--out', default='/tmp/flagship_fast_trace')
    ap.add_argument('--conservative', action='store_true')
    ap.add_argument('--remat', default=None,
                    help="remat_policy override (e.g. save_conv_outputs)")
    ap.add_argument('--chunks', type=int, default=None,
                    help='edge_chunks override (0 = unchunked)')
    ap.add_argument('--steps', type=int, default=2)
    ap.add_argument('--nodes', type=int, default=1024)
    ap.add_argument('--cpu', action='store_true')
    args = ap.parse_args(argv)

    import jax
    if args.cpu:
        jax.config.update('jax_platforms', 'cpu')
    from _flagship_common import build_flagship_step
    from se3_transformer_tpu.utils.helpers import fetch_sync
    from se3_transformer_tpu.utils.observability import profile_trace

    step, params, opt_state, data, key, module = build_flagship_step(
        fast=not args.conservative, remat=args.remat, chunks=args.chunks,
        nodes=args.nodes)
    name = 'flagship' if args.conservative else 'flagship_fast'

    t0 = time.time()
    params, opt_state, loss, _ = step(params, opt_state, data, key)
    fetch_sync(loss)  # block_until_ready returns early on this runtime
    print(f'compile+first step: {time.time() - t0:.1f} s '
          f'({name}, remat={args.remat}, chunks={args.chunks})')

    with profile_trace(args.out):
        for _ in range(args.steps):
            key, sub = jax.random.split(key)
            params, opt_state, loss, _ = step(params, opt_state, data, sub)
        # the trace window must not close before the steps have run
        fetch_sync(loss)
    print(f'trace written to {args.out}; summarize with '
          f'scripts/trace_summary.py --dir {args.out}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
