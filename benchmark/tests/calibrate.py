"""Run on the chip, not a test: reads, over many seeds in one process, what
`correct` compares, for the program against the reference and for the two
controls against the reference: the reference with bfloat16 activations and
accumulators, and the reference with every learned operand rounded to fp8. The
limits in a configuration file are set from these readings (PERF.md).

    python3 benchmark/tests/calibrate.py <cell> <fp8>[,<bf16>] <seed> [<seed> ...]

The first <fp8> seeds also read the fp8 control, the first <bf16> (default
as many) the whole-bfloat16 one. One JSON line per seed goes
to chiprun_out/calibrate_<cell>_<first seed>.jsonl, with every leaf's numbers,
so that a statistic can be chosen afterwards without another call. The first
seed is in the name because a chip call starts without chiprun_out/ and its
files replace those of the same name here, an empty one too.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)


def _gaps(prog, ref):
    """The numbers `correct` compares, and per leaf what they are made of:
    [program's norm, reference's norm, norm of the difference] of the first
    gradient, and the two norms of the parameters' change."""
    from harness.correct import worst_leaf_gap
    from harness.train import grad_rel_diff
    names = list(ref['grad'])
    diffs = [float(np.linalg.norm((a - b).ravel()))
             for a, b in zip(prog['grad_tree'], ref['grad_tree'])]
    return {'grad_rel_diff': grad_rel_diff(prog, ref),
            'loss': [abs(a - b) / abs(b)
                     for a, b in zip(prog['losses'], ref['losses'])],
            'grad': worst_leaf_gap(prog['grad'], ref['grad']),
            'delta': worst_leaf_gap(prog['delta'], ref['delta']),
            'leaves': {k: [prog['grad'][k], ref['grad'][k], d,
                           prog['delta'][k], ref['delta'][k]]
                       for k, d in zip(names, diffs)}}


def main():
    import jax.numpy as jnp
    from harness import device, loader, spans as spans_mod, train as T
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    cell = loader.load_cell(sys.argv[1])
    n_fp8, n_bf16 = (int(x) for x in (sys.argv[2] + ',' + sys.argv[2])
                     .split(',')[:2])
    seeds = [int(s) for s in sys.argv[3:]]
    device.require_accelerator(cell['workload']['chips'])
    enable_compilation_cache()
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    path = os.path.join(ROOT, 'chiprun_out',
                        f'calibrate_{cell["name"]}_{seeds[0]}.jsonl')
    spans = spans_mod.Spans()
    prog = T.build(cell, seeds[0])
    n = cell['config']['correct']['check_steps']
    with open(path, 'a') as out:
        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            T.reseed(prog, cell, seed)
            numbers, keys = T.first_steps(prog, n, spans)
            inputs = {k: prog[k] for k in ('data', 'fill', 'wkey')}
            prog['params'] = prog['opt_state'] = None
            t1 = time.perf_counter()
            ref = T.reference_steps(cell, inputs, keys)
            t2 = time.perf_counter()
            row = {'seed': seed, 'program': _gaps(numbers, ref),
                   'losses': numbers['losses'], 'ref_losses': ref['losses']}
            for name, dtype, upto in (('fp8', jnp.float8_e4m3fn, n_fp8),
                                      ('bfloat16', jnp.bfloat16, n_bf16)):
                if i < upto:
                    ctl = T.reference_steps(cell, inputs, keys, dtype=dtype)
                    row[name] = _gaps(ctl, ref)
                    row[name + '_losses'] = ctl['losses']
            row['seconds'] = [t1 - t0, t2 - t1, time.perf_counter() - t2]
            brief = {k: ({kk: vv for kk, vv in v.items() if kk != 'leaves'}
                         if isinstance(v, dict) else v)
                     for k, v in row.items()}
            print(json.dumps(brief), flush=True)
            out.write(json.dumps(row) + '\n')
            out.flush()


if __name__ == '__main__':
    main()
