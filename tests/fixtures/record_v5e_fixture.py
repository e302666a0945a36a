"""Run on the chip after a `--trace 1` run of a cell: cuts the newest trace
under .bench_out/ down to its first step (from the first `step_call` span to
the end of the first `loss_fetch`), in `observability.profiling.read_xplane`'s
own form with each event's op_name, and writes it to chiprun_out/ (from where
a builder copies it to tests/fixtures/). Not a test."""
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from se3_transformer_tpu.observability import profiling  # noqa: E402


def main(steps=1):
    path = profiling.newest_xplane(os.path.join(ROOT, '.bench_out', 'trace'))
    ev = profiling.read_xplane(path, ('step_call', 'loss_fetch'))
    first = sorted(h[2] for h in ev['host'] if h[1] == 'step_call')
    last = sorted(h[2] + h[3] for h in ev['host'] if h[1] == 'loss_fetch')
    lo, hi = first[0], last[steps - 1]
    cut = dict(ev, steps=steps, window_ns=[lo, hi],
               source=os.path.relpath(path, ROOT),
               device={t: [r for r in rows if lo <= r[1] and r[1] + r[2] <= hi]
                       for t, rows in ev['device'].items()},
               host=[h for h in ev['host']
                     if lo <= h[2] and h[2] + h[3] <= hi])
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    out = os.path.join(ROOT, 'chiprun_out', 'v5e_d4_train_1step.json.gz')
    with gzip.open(out, 'wt') as fh:
        json.dump(cut, fh)
    n = sum(len(v) for v in cut['device'].values())
    red = profiling.reduce_events(cut)
    print(f'fixture: {n} device events, {os.path.getsize(out) / 1e6:.2f} MB '
          f'at {out}; op_name from {ev["op_name_source"]}; busy '
          f'{red["busy_s"]:.4f} s, coverage {red["coverage"]:.4f}')


if __name__ == '__main__':
    main()
