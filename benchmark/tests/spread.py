"""Not a test: the spread of each metric over sets of runs, as the bound's
rule takes it (the distance between the first and third quartile by
`statistics.quantiles(values, n=4)`, as a share of the median).

    python3 benchmark/tests/spread.py <runs.jsonl> [<runs per set>]

The file holds one result line of run.py per run, in order; the first run of
the file (which compiles) is left out if it carries "first": true.
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
    rows = [r for r in rows if not r.get('first')]
    per = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    sets = [rows[i:i + per] for i in range(0, len(rows), per)]
    print(f'{len(rows)} runs in {len(sets)} sets; correct: '
          f'{sum(r["correct"] for r in rows)} of {len(rows)}')
    for name in rows[0]['metrics']:
        cols = [[r['metrics'][name]['value'] for r in s] for s in sets]
        meds = [statistics.median(c) for c in cols]
        spreads = [spread(c) if len(c) >= 2 else float('nan') for c in cols]
        print(f'{name}: medians {[round(m, 4) for m in meds]} spreads '
              f'{[round(100 * s, 3) for s in spreads]} %; all values '
              f'{[[round(v, 3) for v in c] for c in cols]}')
    print('memory_peak_bytes',
          sorted({r['device']['memory_peak_bytes'] for r in rows}))


if __name__ == '__main__':
    main()
