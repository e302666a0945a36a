"""Run after a `--trace 1` run of a cell (on the chip, or here on a trace the
chip wrote): cuts the newest trace under .bench_out/ (or the `.xplane.pb`
given) down to its first step, from the first span named `first` to the end
of the first span named `last`, in `observability.profiling.read_xplane`'s
own form with each event's op_name and the side table of the instructions
that ran in the cut, and writes it to chiprun_out/v5e_<cell>_1step.json.gz
(from where a builder copies it to tests/fixtures/). Not a test.

    python3 tests/fixtures/record_v5e_fixture.py <cell> [first last [xplane]]

d4's loop opens a step with `step_call` and closes it with `loss_fetch`,
the defaults; the decoder cells' loops have the same two spans."""
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from se3_transformer_tpu.observability import profiling  # noqa: E402


def main(cell='d4_onehead_train', first='step_call', last='loss_fetch',
         path=None, steps=1):
    path = path or profiling.newest_xplane(
        os.path.join(ROOT, '.bench_out', 'trace'))
    ev = profiling.read_xplane(path, (first, last))
    opened = sorted(h[2] for h in ev['host'] if h[1] == first)
    closed = sorted(h[2] + h[3] for h in ev['host'] if h[1] == last)
    lo, hi = opened[0], closed[steps - 1]
    device = {t: [r for r in rows if lo <= r[1] and r[1] + r[2] <= hi]
              for t, rows in ev['device'].items()}
    ran = {(r[4], r[0]) for rows in device.values() for r in rows}
    cut = dict(ev, cell=cell, steps=steps, window_ns=[lo, hi],
               source=os.path.relpath(path, ROOT), device=device,
               host=[h for h in ev['host']
                     if lo <= h[2] and h[2] + h[3] <= hi])
    if 'instructions' in ev:
        cut['instructions'] = {
            program: {k: v for k, v in rows.items() if (program, k) in ran}
            for program, rows in ev['instructions'].items()}
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    out = os.path.join(ROOT, 'chiprun_out', f'v5e_{cell}_1step.json.gz')
    with gzip.open(out, 'wt') as fh:
        json.dump(cut, fh)
    n = sum(len(v) for v in cut['device'].values())
    red = profiling.reduce_events(cut)
    print(f'fixture: {n} device events, {os.path.getsize(out) / 1e6:.2f} MB '
          f'at {out}; op_name from {ev["op_name_source"]}, flops from '
          f'{red["flops_source"]}; busy {red["busy_s"]:.4f} s, coverage '
          f'{red["coverage"]:.4f}')


if __name__ == '__main__':
    main(*sys.argv[1:5])
