"""What the program says of its own time, for the `.py` readers beside this
file: the program's trace reducer (`observability/profiling.py`) run once on
the run's trace and kept here, and the program's compile log
(`observability/runtime.py`) reduced by the program's own function.

The trace is the newest `*.xplane.pb` under `<checkout>/.bench_out/trace/`
(`harness/trace.py::start` empties the run's directory first, so the newest is
this run's). A program without the reducer or the log (the parent of the PR
that brought these readers), or a run without a trace, gives None: the metric
is then left out of the line. So does a program whose reducer or log raises,
or answers in another shape (`or_nothing`): a reader here never ends a run.
"""
import os
import time

_KEPT = {}     # (path, mtime) -> the reduction
_SAID = set()  # functions whose compile-log line has been printed


def or_nothing(read, ctx):
    """`read(ctx)`, or None with a line that says why when the program's side
    of the reading raises: the metric is left out and the run goes on."""
    try:
        return read(ctx)
    except Exception as e:   # whatever a program that is not this one's does
        print(f'layer metric {read.__module__}: left out, the program gave '
              f'{type(e).__name__}: {e}', flush=True)
        return None


def _root(reader_file):
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))


def profile(ctx, reader_file):
    """The program's reduction of this run's trace, or None."""
    try:
        from se3_transformer_tpu.observability import profiling
    except ImportError:
        return None
    if not hasattr(profiling, 'reduce_xplane'):
        return None
    root = ctx.get('trace_root') or os.path.join(
        _root(reader_file), '.bench_out', 'trace')
    path = profiling.newest_xplane(root)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _KEPT:
        _KEPT.clear()
        t0 = time.perf_counter()
        red = _KEPT[key] = profiling.reduce_xplane(path)
        # what the labels cost when tracing is on, on a line of its own
        print(f'program profile: {red["events"]} device events of '
              f'{os.path.getsize(path) / 2**20:.1f} MiB reduced in '
              f'{time.perf_counter() - t0:.2f} s; op_name from '
              f'{red["op_name_source"]}; coverage '
              f'{100 * red["coverage"]:.2f}%, of which under the catch-all '
              f'`loss` {1e3 * red["leaf_s"].get("loss", 0.0):.3f} ms',
              flush=True)
    return _KEPT[key]


def per_step_ms(ctx, seconds):
    steps = ctx['counters'].get('steps')
    return None if not steps or seconds is None else 1e3 * seconds / steps


def compile_seconds(ctx, fun_name='train_step'):
    """{trace_s, load_s, other_s, ...} of the step from the program's
    compile log (`ctx['compile_log']` in a test), or None."""
    try:
        from se3_transformer_tpu.observability import runtime
    except ImportError:
        return None
    if not hasattr(runtime, 'compile_seconds'):
        return None
    secs = runtime.compile_seconds(fun_name, ctx.get('compile_log'))
    if secs is not None and fun_name not in _SAID:
        _SAID.add(fun_name)
        top = ', '.join(f'{f} {k} {v:.1f}' for f, k, v in
                        secs.get('other_top', [])[:6])
        print(f'compile log: {fun_name} trace {secs["trace_s"]:.1f} s, load '
              f'{secs["load_s"]:.1f} s (cache hit: {secs["cache_hit"]}), '
              f'other functions {secs["other_s"]:.1f} s [{top}]; '
              f'{secs.get("entries")} entries', flush=True)
    return secs
