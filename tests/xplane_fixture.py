"""Write a profiler trace (`.xplane.pb`) from events in the form
`observability.profiling.read_xplane` returns, with the message classes the
reader itself declares: the tests of the trace reducer (here and in
benchmark/tests) feed it files, as the profiler does. A track named
`/device:TPU:<n>` becomes that plane's `XLA Ops` line, each instruction one
event metadata record with its op_name in the stat `tf_op` (trailing `:`, as
the chip's profiler writes it); any other track becomes a thread of
`/host:CPU` whose events carry `hlo_op` / `hlo_module` stats (a CPU trace).
A side table `instructions` is written as the profiler writes what it is read
from: `hlo_category`, `bytes_accessed` and the program's id as stats of each
instruction's metadata, and in `/host:metadata` one record per program,
keyed by that id (as a signed number), with a module whose instructions
count to the table's operations and products by the reducer's rule. A row's
program (its fifth column) is such a record's name, `jit_step(<id>)`."""
import os
import re

from se3_transformer_tpu.observability.profiling import (
    hlo_proto_class, xspace_class,
)


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

    def _stats(self, message, stats):
        for stat, val in stats:
            field = {str: 'str_value', bytes: 'bytes_value',
                     int: 'uint64_value'}[type(val)]
            message.stats.add(metadata_id=self._stat(stat), **{field: val})

    def _event(self, name, meta_stats=(), key=None):
        # two programs of one trace may both hold a `fusion.16`
        known = (name, tuple(meta_stats))
        if known not in self.events:
            self.events[known] = key or len(self.events) + 1
            entry = self.plane.event_metadata.add(key=self.events[known])
            entry.value.id, entry.value.name = self.events[known], name
            self._stats(entry.value, meta_stats)
        return self.events[known]

    def add(self, line, name, start_ns, dur_ns, stats=(), meta_stats=()):
        if line not in self.lines:
            self.lines[line] = self.plane.lines.add(
                id=len(self.lines) + 1, name=line)
        ev = self.lines[line].events.add(
            metadata_id=self._event(name, meta_stats),
            offset_ps=int(round(start_ns * 1000)),
            duration_ps=int(round(dur_ns * 1000)))
        self._stats(ev, stats)


def _module_counting_to(instructions):
    """A serialized `HloProto` in which each instruction of the table holds
    what the table says: a custom call where the operations are unknown, a
    fusion of `products` dots the first of which has all the operations,
    else a copy."""
    proto = hlo_proto_class()()
    main = proto.hlo_module.computations.add(name='main', id=1)
    for n, (name, row) in enumerate(sorted(instructions.items())):
        ins = main.instructions.add(name=name, id=3 * n + 1, opcode='copy')
        if row['flops'] is None:
            ins.opcode = 'custom-call'
        elif row['products']:
            fused = proto.hlo_module.computations.add(
                name=f'fused.{name}', id=3 * n + 2)
            ins.opcode, ins.fusion_kind = 'fusion', 'kOutput'
            ins.called_computation_ids.append(fused.id)
            operand = fused.instructions.add(
                name=f'{name}.operand', id=3 * n + 2, opcode='parameter')
            for k in range(row['products']):
                dot = fused.instructions.add(
                    name=f'{name}.dot.{k}', id=3 * n + 3, opcode='dot')
                dot.operand_ids.extend([operand.id, operand.id])
                dot.shape.dimensions.append(0 if k else row['flops'] // 2)
    return proto.SerializeToString()


_PROGRAM = re.compile(r'^(.*)\((\d+)\)$')


def _program_stats(program):
    """What the profiler says of an event's program: the module's name (a
    CPU event's `hlo_module`) and, of a stored one, its id."""
    named = _PROGRAM.match(program or '')
    if not named:
        return [('hlo_module', program or 'jit_f')]
    return [('hlo_module', named.group(1)),
            ('program_id', int(named.group(2)))]


def write_xplane(path, events):
    """`events`: {'device': {track: [[name, start_ns, dur_ns, op_name,
    program], ...]}, 'host': [[thread, name, start_ns, dur_ns], ...],
    'instructions': {program: {name: {category, flops, bytes,
    products}}}}."""
    space = xspace_class()()
    planes = {}
    table = events.get('instructions') or {}

    def plane(name):
        if name not in planes:
            planes[name] = _Plane(space, name)
        return planes[name]

    for track, rows in events.get('device', {}).items():
        for name, start, dur, op, program in rows:
            if track.startswith('/device:'):
                row = table.get(program, {}).get(name, {})
                plane(track).add(
                    'XLA Ops', f'%{name} = f32[] op()', start, dur,
                    meta_stats=([('tf_op', op + ':')] if op else [])
                    + [(stat, row[key]) for stat, key in
                       (('hlo_category', 'category'),
                        ('bytes_accessed', 'bytes'))
                       if row.get(key) is not None]
                    + (_program_stats(program)[1:] if program else []))
            else:
                plane('/host:CPU').add(
                    track.split('/')[-1], name, start, dur,
                    [('hlo_op', name)] + _program_stats(program))
    for thread, name, start, dur in events.get('host', []):
        plane('/host:CPU').add(thread, name, start, dur)
    for program, rows in table.items():
        plane('/host:metadata')._event(
            program, [('hlo_proto', _module_counting_to(rows))],
            key=(_program_stats(program)[1][1] + 2 ** 63) % 2 ** 64 - 2 ** 63)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'wb') as fh:
        fh.write(space.SerializeToString())
    return path
