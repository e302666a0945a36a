"""Diagnose the 2487-nodes*steps/s conservative bench artifact (19:29Z).

Replicates bench.py's on-chip conservative flagship program EXACTLY
(donated buffers, same seeds) and prints what bench discards: the
per-step loss sequence and per-step wall time. --mode aot runs the
lower().compile() executable bench times; --mode jit runs the plain
jitted call. Deterministic seeds => the two modes' loss sequences must
match across separate processes if the AOT program is computing the
same function.

One process per chip: run it when no other process holds the chip.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--mode', choices=('aot', 'jit'), default='aot')
    ap.add_argument('--steps', type=int, default=6)
    ap.add_argument('--fast', action='store_true')
    ap.add_argument('--remat', default=None)
    ap.add_argument('--async-loop', action='store_true',
                    help='bench-style: dispatch all steps, block once at '
                         'the end (vs per-step blocking)')
    args = ap.parse_args(argv)

    import jax
    from _flagship_common import build_flagship_step
    print('backend:', jax.default_backend(), flush=True)
    step, params, opt_state, data, key, _ = build_flagship_step(
        fast=args.fast, remat=args.remat)

    exec_fn = step
    if args.mode == 'aot':
        t0 = time.time()
        exec_fn = step.lower(params, opt_state, data, key).compile()
        print(f'AOT compile: {time.time() - t0:.1f} s', flush=True)

    # bench warmup call (key, as bench uses it)
    t0 = time.time()
    params, opt_state, loss, _ = exec_fn(params, opt_state, data, key)
    loss = jax.block_until_ready(loss)
    print(f'warmup: loss={float(loss):.3f}  {time.time() - t0:.1f} s',
          flush=True)

    losses, times = [], []
    if args.async_loop:
        t0 = time.time()
        for _ in range(args.steps):
            key, sub = jax.random.split(key)
            params, opt_state, loss, _ = exec_fn(params, opt_state, data,
                                                 sub)
            losses.append(loss)
        jax.block_until_ready(loss)
        dt = time.time() - t0
        t1 = time.time()
        losses = [float(l) for l in losses]
        print(f'async loop: {dt:.2f} s for {args.steps} steps '
              f'({dt / args.steps * 1e3:.0f} ms/step); float() of all '
              f'losses took a further {time.time() - t1:.2f} s', flush=True)
        times = [dt / args.steps]
    else:
        for _ in range(args.steps):
            key, sub = jax.random.split(key)
            t0 = time.time()
            params, opt_state, loss, _ = exec_fn(params, opt_state, data,
                                                 sub)
            loss = jax.block_until_ready(loss)
            times.append(time.time() - t0)
            losses.append(float(loss))
    print(f'{args.mode}: losses=' + ' '.join(f'{l:.4f}' for l in losses),
          flush=True)
    print(f'{args.mode}: per-step s=' + ' '.join(f'{t:.2f}' for t in times),
          flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
