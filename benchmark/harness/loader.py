"""From a cell's name to everything the run needs, by data alone.

`BENCHMARK.json` names the cell's configuration and traffic mix; the
configuration's file is the entry's `file`, the traffic mix is
`<bench>/traffic/<traffic>.json`, and every per-layer metric of the cell is
`<bench>/layer_metrics/<name>.json` (or `<name>.py` with a `read(ctx)`
function, for a reader the harness lacks). Nothing about a cell is written in
Python: a later PR adds files and entries, and edits none.
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _in_cell(metric, cell, reported):
    cells = metric.get('workloads')
    if cells is not None:
        return cell in cells
    moves = metric.get('moves')
    return moves is None or moves in reported


def load_cell(name, root=ROOT):
    """The cell `name` as a dict: workload, config, traffic, end_to_end
    (names) and per_layer ({name: spec})."""
    bench = _read(os.path.join(root, 'BENCHMARK.json'))
    bench_dir = os.path.join(root, bench['paths'][0])
    cells = {w['name']: w for w in bench['workloads']}
    if name not in cells:
        raise SystemExit(f'benchmark: no workload {name!r} in BENCHMARK.json '
                         f'(known: {sorted(cells)})')
    workload = cells[name]
    entry = {c['name']: c for c in bench['configs']}[workload['config']]
    config = _read(os.path.join(root, entry['file']))
    traffic = _read(os.path.join(bench_dir, 'traffic',
                                 workload['traffic'] + '.json'))
    end_to_end = [m['name'] for m in bench['end_to_end']
                  if _in_cell(m, name, ())]
    per_layer = {}
    for m in bench['per_layer']:
        if not _in_cell(m, name, end_to_end):
            continue
        base = os.path.join(bench_dir, 'layer_metrics', m['name'])
        if os.path.exists(base + '.json'):
            spec = _read(base + '.json')
        elif os.path.exists(base + '.py'):
            spec = {'reader': {'source': 'python', 'path': base + '.py'}}
        else:
            raise SystemExit(f'benchmark: per-layer metric {m["name"]!r} has '
                             f'no file under layer_metrics/')
        spec['unit'] = m['unit']
        per_layer[m['name']] = spec
    units = {m['name']: m['unit']
             for m in bench['end_to_end'] + bench['per_layer']}
    return {'name': name, 'workload': workload, 'config': config,
            'traffic': traffic, 'end_to_end': end_to_end,
            'per_layer': per_layer, 'units': units,
            'run_seconds': bench['run_seconds'], 'bench_dir': bench_dir,
            'root': root}


def load_python_reader(path):
    spec = importlib.util.spec_from_file_location(
        'layer_metric_' + os.path.basename(path)[:-3].replace('.', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
