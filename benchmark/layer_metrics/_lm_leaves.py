"""For the token decoder's `.py` readers beside this file: device seconds
under leaves of the program's own labels, by the program's reducer run once
on the run's trace (found as `_program_profile` finds it). A program without
the reducer, a run without a trace, or a trace in which none of the leaves
occurs (a program without the token decoder) gives None.

One difference from `_program_profile.profile`: each event's path is read
without its last component, which is the primitive's name and no scope. The
reducer takes the innermost component on the closed list, and `gather`, a
leaf of the SE(3) model, is also what XLA calls the row lookups of the
dispatch, the combine, the embedding and the loss's target pick: read whole,
those would all be filed under `gather`."""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
try:
    import _program_profile as prog  # noqa: E402
except ImportError:   # a checkout that lacks the helper reads nothing
    prog = None


_KEPT = {}     # (path, mtime) -> the reduction


def profile(ctx, reader_file):
    """The program's reduction of this run's trace by scopes alone, or
    None."""
    try:
        from se3_transformer_tpu.observability import profiling
    except ImportError:
        return None
    if not hasattr(profiling, 'reduce_events'):
        return None
    root = ctx.get('trace_root') or os.path.join(
        prog._root(reader_file), '.bench_out', 'trace')
    path = profiling.newest_xplane(root)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _KEPT:
        _KEPT.clear()
        t0 = time.perf_counter()
        events = profiling.read_xplane(path)
        events['device'] = {
            track: [[name, start, dur,
                     op and op.split(';', 1)[0].rsplit('/', 1)[0], module]
                    for name, start, dur, op, module in rows]
            for track, rows in events['device'].items()}
        red = _KEPT[key] = profiling.reduce_events(events)
        top = sorted(red['leaf_s'].items(), key=lambda kv: -kv[1])[:8]
        print(f'program profile by scopes alone: {red["events"]} events '
              f'reduced in {time.perf_counter() - t0:.2f} s; coverage '
              f'{100 * red["coverage"]:.2f}%; ms by leaf '
              f'{[(k, round(1e3 * v, 2)) for k, v in top]}', flush=True)
    return _KEPT[key]


def leaf_seconds(ctx, reader_file, leaves):
    """Seconds under `leaves`, all phases, or None if none of them occurs."""
    red = profile(ctx, reader_file)
    if red is None:
        return None
    found = [red['leaf_s'][leaf] for leaf in leaves if leaf in red['leaf_s']]
    return sum(found) if found else None


def leaf_ms_per_step(ctx, reader_file, leaves):
    return prog.per_step_ms(ctx, leaf_seconds(ctx, reader_file, leaves))


def roofline_share(ctx, seconds, flops, nbytes, what):
    """100 max(operations / peak, bytes / peak) / seconds, with a line that
    says which bounds it."""
    if not seconds:
        return None
    t_flops = flops / ctx['peaks']['bf16_flops']
    t_bytes = nbytes / ctx['peaks']['hbm_bytes_per_s']
    print(f'roofline {what}: {flops:.4g} operations ({t_flops:.4f} s at the '
          f'bf16 peak), {nbytes:.4g} bytes ({t_bytes:.4f} s at the HBM '
          f'peak), {seconds:.4f} s measured: bound by '
          f'{"MXU" if t_flops >= t_bytes else "HBM"}', flush=True)
    return 100.0 * max(t_flops, t_bytes) / seconds


def guarded(read):
    """The module's `read(ctx)`: None without the helper, and never
    raising."""
    def safe(ctx):
        return prog and prog.or_nothing(read, ctx)
    return safe
