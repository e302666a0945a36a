"""Write a profiler trace (`.xplane.pb`) from events in the form
`observability.profiling.read_xplane` returns, with the message classes the
reader itself declares: the tests of the trace reducer (here and in
benchmark/tests) feed it files, as the profiler does. A track named
`/device:TPU:<n>` becomes that plane's `XLA Ops` line, each instruction one
event metadata record with its op_name in the stat `tf_op` (trailing `:`, as
the chip's profiler writes it); any other track becomes a thread of
`/host:CPU` whose events carry `hlo_op` / `hlo_module` stats (a CPU trace)."""
import os

from se3_transformer_tpu.observability.profiling import xspace_class


class _Plane:
    def __init__(self, space, name):
        self.plane = space.planes.add(name=name)
        self.lines, self.events, self.stats = {}, {}, {}

    def _stat(self, name):
        if name not in self.stats:
            self.stats[name] = len(self.stats) + 1
            entry = self.plane.stat_metadata.add(key=self.stats[name])
            entry.value.id, entry.value.name = self.stats[name], name
        return self.stats[name]

    def _event(self, name, meta_stats=()):
        # two programs of one trace may both hold a `fusion.16`
        key = (name, tuple(meta_stats))
        if key not in self.events:
            self.events[key] = len(self.events) + 1
            entry = self.plane.event_metadata.add(key=self.events[key])
            entry.value.id, entry.value.name = self.events[key], name
            for stat, val in meta_stats:
                entry.value.stats.add(metadata_id=self._stat(stat),
                                      str_value=val)
        return self.events[key]

    def add(self, line, name, start_ns, dur_ns, stats=(), meta_stats=()):
        if line not in self.lines:
            self.lines[line] = self.plane.lines.add(
                id=len(self.lines) + 1, name=line)
        ev = self.lines[line].events.add(
            metadata_id=self._event(name, meta_stats),
            offset_ps=int(round(start_ns * 1000)),
            duration_ps=int(round(dur_ns * 1000)))
        for key, val in stats:
            ev.stats.add(metadata_id=self._stat(key), str_value=val)


def write_xplane(path, events):
    """`events`: {'device': {track: [[name, start_ns, dur_ns, op_name,
    module], ...]}, 'host': [[thread, name, start_ns, dur_ns], ...]}."""
    space = xspace_class()()
    planes = {}

    def plane(name):
        if name not in planes:
            planes[name] = _Plane(space, name)
        return planes[name]

    for track, rows in events.get('device', {}).items():
        for name, start, dur, op, module in rows:
            if track.startswith('/device:'):
                plane(track).add(
                    'XLA Ops', f'%{name} = f32[] op()', start, dur,
                    meta_stats=[('tf_op', op + ':')] if op else [])
            else:
                plane('/host:CPU').add(
                    track.split('/')[-1], name, start, dur,
                    [('hlo_op', name), ('hlo_module', module or 'jit_f')])
    for thread, name, start, dur in events.get('host', []):
        plane('/host:CPU').add(thread, name, start, dur)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'wb') as fh:
        fh.write(space.SerializeToString())
    return path
