"""From the profiler's trace to numbers: the reduction every PR shares.

Written against one v5e trace looked at by hand (PR 23): the device is the
plane `/device:TPU:<n>`; its line `XLA Ops` holds one event per executed HLO
operation, named by the operation's whole HLO text (`%fusion.12 = ...`), and a
Pallas kernel appears there as a custom call named after the kernel's function
(`%fused_pairwise_conv_bxf.78 = ... custom-call(...)`). `Async XLA Ops` holds
copy-start..copy-done spans that overlap the compute and are left out of busy
time. Host threads are lines of `/host:CPU`; a `TraceAnnotation` is an event
named as it was given, on the same clock as the device.

`extract` reads the `.xplane.pb` with nothing but JAX into a small dict
(what the tests keep a recorded copy of); `reduce` turns that into busy
seconds, seconds per operation family, and the breakdown.
"""
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
OPS_LINE = 'XLA Ops'
HOST_PLANE = '/host:CPU'
HOST_SPANS = ('key_split', 'step_call', 'loss_fetch')   # the train loop's
SHORT_GAP_NS = 20e3    # shorter gaps are launch overhead, not the host


def short_name(hlo_text):
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo_text.split(' = ', 1)[0].lstrip('%').strip()


def family(name):
    """`fused_pairwise_conv_bwd.17` -> `fused_pairwise_conv_bwd`."""
    return re.sub(r'(\.\d+)+$', '', name)


def start(cell, seed):
    import jax
    d = os.path.join(cell['root'], '.bench_out', 'trace',
                     f'{cell["name"]}-{seed}')
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    jax.profiler.start_trace(d)
    return d


def stop(trace_dir, window_s, host_keep=HOST_SPANS):
    """`host_keep`: the names of the run's own annotations (an entry path
    passes the names of its spans)."""
    import jax
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    if not paths:
        raise SystemExit(f'benchmark: no trace under {trace_dir}')
    events = extract(paths[0], host_keep)
    print(f'trace: {paths[0]} ({os.path.getsize(paths[0]) / 2**20:.1f} MiB), '
          f'{sum(len(v) for v in events["device"].values())} device '
          f'operations on {sorted(events["device"])}', flush=True)
    return reduce(events, window_s)


def extract(path, host_keep=HOST_SPANS):
    """{'device': {plane: [[name, start_ns, dur_ns], ...]} (XLA Ops line),
    'host': [[thread, name, start_ns, dur_ns], ...]} (the annotations named
    in `host_keep`)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = {'device': {}, 'host': []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out['device'][plane.name] = [
                        [short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name not in host_keep:
                        continue
                    out['host'].append([line.name, e.name, float(e.start_ns),
                                        float(e.duration_ns)])
    return out


def union_intervals(events):
    """Merged [start, end] intervals of (name, start, dur) events."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def exclusive_seconds(events):
    """{family: seconds} with each event's time less the events nested in
    it, so that a `while` and its body are not both counted."""
    out = {}
    stack = []   # [end, child_ns, name, dur]

    def close(item):
        out[family(item[2])] = out.get(family(item[2]), 0.0) \
            + max(item[3] - item[1], 0.0) * 1e-9

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0] - 1e-6:
            close(stack.pop())
        if stack:
            stack[-1][1] += d
        stack.append([s + d, 0.0, name, d])
    while stack:
        close(stack.pop())
    return out


def idle_gaps(merged, host):
    """{host span or 'no_span': idle seconds}: every gap between merged
    device intervals, given to the innermost host annotation open at its
    middle."""
    out = {}
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        mid, gap = 0.5 * (e0 + s1), s1 - e0
        if gap < SHORT_GAP_NS:
            out['between_ops'] = out.get('between_ops', 0.0) + gap * 1e-9
            continue
        best = None
        for _, name, s, d in host:
            if s <= mid <= s + d and (best is None or d < best[1]):
                best = (name, d)
        key = best[0] if best else 'no_span'
        out[key] = out.get(key, 0.0) + gap * 1e-9
    return out


def reduce(events, window_s):
    """Busy seconds averaged over the chips used, seconds by operation
    family (exclusive, summed over chips), and the breakdown."""
    planes = events['device']
    if not planes:
        raise SystemExit('benchmark: the trace holds no device plane')
    busy, op_seconds, gaps = [], {}, {}
    for evs in planes.values():
        merged = union_intervals(evs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for k, v in exclusive_seconds(evs).items():
            op_seconds[k] = op_seconds.get(k, 0.0) + v
        for k, v in idle_gaps(merged, events['host']).items():
            gaps[k] = gaps.get(k, 0.0) + v
    top = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {'busy_s': sum(busy) / len(busy), 'window_s': float(window_s),
            'op_seconds': op_seconds,
            'breakdown': {'device_ops': [[k, v] for k, v in top],
                          'idle_gaps': [[k, v] for k, v in top_gaps]}}
