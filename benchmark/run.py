#!/usr/bin/env python3
"""One process, one cell, once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell from BENCHMARK.json and its data files, builds the program's
state on the device from the seed, warms exactly the cell's own shapes,
measures for `--seconds`, compares what the timed path produced with the plain
reference, and prints one JSON object as its last line. Without a TPU it exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import importlib  # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import loader
    cell = loader.load_cell(args.workload)

    try:
        import se3_transformer_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise SystemExit(f'benchmark: the program is not in this directory '
                         f'({e}); nothing to measure')

    from harness import device, spans as spans_mod
    devices, kind, peaks = device.require_accelerator(
        cell['workload']['chips'])
    print(f'device: {len(devices)} x {kind} ({devices[0].platform}); peaks '
          f'{peaks["bf16_flops"] / 1e12:g} TFLOP/s bf16, '
          f'{peaks["hbm_bytes_per_s"] / 1e9:g} GB/s', flush=True)

    # the compilation cache: where JAX_COMPILATION_CACHE_DIR says, else the
    # fixed <checkout>/.jax_cache/jit (the program's own rule)
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    print(f'cache: {enable_compilation_cache()}', flush=True)

    spans = spans_mod.Spans(annotate=bool(args.trace))
    spans.watch_compiles()
    # the entry path is a module of the harness with a run(): a later PR
    # brings harness/<entry>.py with its cells, and edits nothing here
    entry = cell['config']['entry']
    try:
        runner = importlib.import_module(f'harness.{entry}')
    except ModuleNotFoundError:
        raise SystemExit(f'benchmark: unknown entry path {entry!r}')
    result = runner.run(cell, args, T_START, spans, devices, kind, peaks)

    from se3_transformer_tpu.kernels import tuning
    consults = tuning.consults_since({})
    tuned = [c for c in consults if c.get('source') != 'heuristic']
    print(f'kernel block table: {len(consults)} consults, {len(tuned)} from '
          f'a measured table', flush=True)

    units = cell['units']
    line = {'correct': bool(result['correct']),
            'attempted': int(result['attempted']),
            'failed': int(result['failed']),
            'metrics': {k: {'value': v, 'unit': units[k]}
                        for k, v in result['metrics'].items()},
            'device': result['device']}
    if result.get('breakdown'):
        line['breakdown'] = result['breakdown']
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
