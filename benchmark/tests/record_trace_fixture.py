"""Run on the chip after a `--trace 1` run of a cell: cuts the newest trace
under .bench_out/ down to its first steps and writes it, in the reducer's own
`extract` form, to chiprun_out/ (from where a builder copies it to
benchmark/tests/data/). Not a test."""
import glob
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
ROOT = os.path.dirname(os.path.dirname(HERE))

from harness import trace  # noqa: E402


def main(spans=('step_call', 'loss_fetch'), keep_spans=2):
    paths = sorted(glob.glob(os.path.join(
        ROOT, '.bench_out', 'trace', '*', 'plugins', 'profile', '*',
        '*.xplane.pb')), key=os.path.getmtime)
    ev = trace.extract(paths[-1])
    first = sorted(h for h in ev['host'] if h[1] == spans[0])
    last = sorted(h for h in ev['host'] if h[1] == spans[1])
    lo = first[0][2]
    hi = last[keep_spans - 1][2] + last[keep_spans - 1][3]
    cut = {'device': {p: [e for e in evs if lo <= e[1] and e[1] + e[2] <= hi]
                      for p, evs in ev['device'].items()},
           'host': [h for h in ev['host'] if lo <= h[2] and h[2] + h[3] <= hi],
           'window_ns': [lo, hi], 'source': os.path.relpath(paths[-1], ROOT)}
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    out = os.path.join(ROOT, 'chiprun_out', 'trace_fixture.json.gz')
    with gzip.open(out, 'wt') as fh:
        json.dump(cut, fh)
    n = sum(len(v) for v in cut['device'].values())
    print(f'fixture: {n} device events, {len(cut["host"])} host spans, '
          f'{os.path.getsize(out) / 1e6:.2f} MB at {out}')


if __name__ == '__main__':
    main()
