"""Per-layer metrics, each read by the small declarative reader its file
names. A reader that finds nothing to read returns None, and the metric is
left out of the line.

A metric file: {"layer", "moves", "cells", "reader": {"source", ...}} with
source one of

  host_span          "span": name; "arith": mean_ms | p50_ms | sum_s
  counter            "num": counter, "den": counter; "arith": ratio |
                     one_minus_ratio
  trace_device_busy  "arith": idle_share_pct | per_unit_ms ("unit": counter)
  trace_op_regex     "regex" on device operation names; "arith": per_unit_ms
                     ("unit": counter) | roofline_share ("flops", "bytes":
                     functions of harness/counts.py)
  memory_stats       "key"; "scale": divisor
  python             a `<name>.py` beside the file, with read(ctx)
"""
import os
import re

import numpy as np

from . import counts
from .loader import load_python_reader


def _host_span(r, ctx):
    xs = ctx['spans'].get(r['span'])
    if not xs:
        return None
    return {'mean_ms': 1e3 * float(np.mean(xs)),
            'p50_ms': 1e3 * float(np.median(xs)),
            'sum_s': float(np.sum(xs))}[r['arith']]


def _counter(r, ctx):
    c = ctx['counters']
    if r['num'] not in c or not c.get(r['den']):
        return None
    ratio = c[r['num']] / c[r['den']]
    return {'ratio': ratio, 'one_minus_ratio': 1.0 - ratio}[r['arith']]


def _units(r, ctx):
    return ctx['counters'].get(r['unit']) or None


def _trace_device_busy(r, ctx):
    t = ctx.get('trace')
    if not t or not t['busy_s']:
        return None
    if r['arith'] == 'idle_share_pct':
        return 100.0 * (1.0 - t['busy_s'] / t['window_s'])
    units = _units(r, ctx)
    return None if not units else 1e3 * t['busy_s'] / units


def matched_seconds(trace, regex):
    pat = re.compile(regex)
    return sum(s for name, s in trace['op_seconds'].items()
               if pat.search(name))


def _trace_op_regex(r, ctx):
    t = ctx.get('trace')
    if not t:
        return None
    secs = matched_seconds(t, r['regex'])
    if not secs:
        return None
    if r['arith'] == 'per_unit_ms':
        units = _units(r, ctx)
        return None if not units else 1e3 * secs / units
    flops = sum(getattr(counts, r['flops'])(ctx['model'], s['nodes'],
                                            s['backward']) * s['times']
                for s in ctx['shapes_run'])
    nbytes = sum(getattr(counts, r['bytes'])(ctx['model'], s['nodes'],
                                             s['backward']) * s['times']
                 for s in ctx['shapes_run'])
    t_flops = flops / ctx['peaks']['bf16_flops']
    t_bytes = nbytes / ctx['peaks']['hbm_bytes_per_s']
    print(f'roofline: {flops:.4g} operations ({t_flops:.4f} s at the bf16 '
          f'peak), {nbytes:.4g} bytes ({t_bytes:.4f} s at the HBM peak), '
          f'{secs:.4f} s measured: bound by '
          f'{"MXU" if t_flops >= t_bytes else "HBM"}', flush=True)
    return 100.0 * max(t_flops, t_bytes) / secs


def _memory_stats(r, ctx):
    v = ctx['memory_stats'].get(r['key'])
    return None if v is None else v / r.get('scale', 1)


def _python(r, ctx):
    return load_python_reader(r['path'])(ctx)


READERS = {'host_span': _host_span, 'counter': _counter,
           'trace_device_busy': _trace_device_busy,
           'trace_op_regex': _trace_op_regex, 'memory_stats': _memory_stats,
           'python': _python}


def read_all(cell, ctx):
    out = {}
    for name, spec in cell['per_layer'].items():
        r = spec['reader']
        if r['source'] not in READERS:
            raise SystemExit(f'benchmark: metric {name!r} asks for a reader '
                             f'{r["source"]!r} the harness lacks; bring '
                             f'layer_metrics/{name}.py')
        value = READERS[r['source']](r, ctx)
        if value is not None:
            out[name] = float(value)
    return out


def print_cache_size():
    """The compilation cache's size after set-up, on a line of its own: the
    chip machine caps it at 192 MiB (LRU), and a cell over the cap turns
    every warm run cold."""
    import jax
    path = jax.config.jax_compilation_cache_dir
    if not path or not os.path.isdir(path):
        print(f'cache: no directory at {path!r}', flush=True)
        return
    total = sum(os.path.getsize(os.path.join(path, f))
                for f in os.listdir(path)
                if os.path.isfile(os.path.join(path, f)))
    print(f'cache: {path} holds {total / 2**20:.1f} MiB after set-up '
          f'(cap on the chip machine 192 MiB)', flush=True)
