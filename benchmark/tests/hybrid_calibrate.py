"""Run on the chip, not a test: `lm_calibrate.py` for a cell whose entry is
not `lm_train` (the entry module is the one the cell's configuration names:
it has `build`, `reseed`, `first_steps`, `reference_steps`, `INPUTS`). Reads,
over many seeds in one process, what `correct` compares, for the program
against the reference and, on the first <fp8> seeds, for the control (the
reference with every learned operand rounded to float8_e4m3fn) against the
reference.

    python3 benchmark/tests/hybrid_calibrate.py <cell> <fp8> <seed> [...]

One JSON line per seed goes to chiprun_out/lm_calibrate_<cell>_<first
seed>.jsonl, with every leaf's numbers.
"""
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from lm_calibrate import ROOT, gaps  # noqa: E402  (puts the bench on the path)


def main():
    from harness import device, loader, lm_reference, spans as spans_mod
    cell = loader.load_cell(sys.argv[1])
    T = importlib.import_module(f'harness.{cell["config"]["entry"]}')
    n_fp8 = int(sys.argv[2])
    seeds = [int(s) for s in sys.argv[3:]]
    device.require_accelerator(cell['workload']['chips'])
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    path = os.path.join(ROOT, 'chiprun_out',
                        f'lm_calibrate_{cell["name"]}_{seeds[0]}.jsonl')
    spans = spans_mod.Spans()
    T.own_cache(cell, cell['config']['entry'])
    built = T.build(cell, seeds[0], T.program(cell['config']))
    n = cell['config']['correct']['check_steps']
    with open(path, 'a') as out:
        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            T.reseed(built, cell, seed)
            numbers = T.first_steps(built, n, spans)
            inputs = {k: built[k] for k in T.INPUTS}
            built['params'] = built['opt_state'] = None
            ref = T.reference_steps(cell, inputs, n)
            row = {'seed': seed, 'program': gaps(numbers, ref),
                   'losses': numbers['losses'], 'ref_losses': ref['losses'],
                   'counters': numbers['counters']}
            if i < n_fp8:
                ctl = T.reference_steps(cell, inputs, n,
                                        operand_bits=lm_reference.FP8_E4M3)
                row['fp8'] = gaps(ctl, ref)
                row['fp8_losses'] = ctl['losses']
                del ctl
            row['seconds'] = round(time.perf_counter() - t0, 1)
            out.write(json.dumps(row) + '\n')
            out.flush()
            short = {k: v for k, v in row.items()
                     if k not in ('program', 'fp8')}
            for side in ('program', 'fp8'):
                if side in row:
                    short[side] = {k: v for k, v in row[side].items()
                                   if k != 'leaves'}
            print(json.dumps(short), flush=True)
            del numbers, ref


if __name__ == '__main__':
    main()
