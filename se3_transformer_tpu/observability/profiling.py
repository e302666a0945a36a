"""Device time by the program's own labels, read from the profiler's trace.

One rule: a label is written by the program where the work is issued
(`named_scope`s of `timing.MODEL_SCOPES`, flax module names, the `name=` of a
Pallas launch), and read back from the same `.xplane.pb` that times the work.

The trace, as the chip writes it (the layout `benchmark/harness/trace.py`
documents and tests): one plane per chip, `/device:TPU:<n>`; its line
`XLA Ops` holds one event per executed HLO instruction, named by the
instruction's HLO text; `Async XLA Ops` holds copy-start..copy-done spans that
overlap the compute and stay out; host threads are lines of `/host:CPU`, where
a `TraceAnnotation` is an event named as it was given. A CPU trace has no
device plane: there the device events are those of `/host:CPU` that carry an
`hlo_op` stat (the XLA:CPU thunk tracer), one line per worker thread.

For each event the `op_name` (the path JAX wrote into the instruction's
metadata: `jit(train_step)/loss/transpose(...)/.../attn_block2/attention/attn/
attn_qkv/to_k/pair_1_2/jit(fused_pairwise_conv_bwd)/pairwise_layout/
transpose`) is looked for, in this order:

  (a) in the trace itself: the chip's profiler writes it as the stat `tf_op`
      of the event's METADATA (one record per instruction, shared by its
      events), beside `hlo_category`, `source` and `bytes_accessed`;
  (c) HLO text handed over by the caller (`hlo_op_names`), joined on the
      instruction's name. On the CPU, whose events carry `hlo_op` and
      `hlo_module` and nothing else, the caller's AOT executable gives it at
      no cost (`make profile-smoke`); on the chip it would cost a second
      trace of the step and is not needed.

`jax.profiler.ProfileData` shows a plane's lines and each event's own stats,
not the event metadata's, so it cannot see (a) (nor (b), the whole HLO module
the profiler stores in the event metadata of the plane `/host:metadata`, 39 MB
of the flagship trace's 61). The file is therefore read as what it is, an
`XSpace` protocol buffer, through the fields of `xplane.proto` declared below
(google.protobuf, which the installation has; nothing of TensorFlow or
tensorboard). (b) is not read: every instruction that has an op_name has it
under (a) already.

From the path: the LEAF is the innermost component on the closed list
`MODEL_SCOPES` (`pair_<d_in>_<d_out>` reads as `pair`); the PHASE is `replay`
under a `rematted_computation` component, `backward` under a `transpose(...)`
one, else `forward`; a kernel launch's ROLE is its instruction's family name
(`fused_pairwise_conv_bwd_a`) and its PAIR the `pair_*` component. Seconds
are exclusive (an event's time less the events nested in it) and summed over
chips; busy time is the union of intervals, averaged over chips.

`make profile-smoke` gates a toy run on coverage plus schema validity;
`benchmark/run.py --trace 1` is the traced flagship step (its readers in
`benchmark/layer_metrics/` call `reduce_xplane`).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .timing import MODEL_SCOPES, PAIR_SCOPE, profile_trace

__all__ = [
    'exclusive_durations', 'union_length', 'scope_leaf', 'scope_phase',
    'scope_pair',
    'kernel_role', 'compiler_launch_leaf', 'hlo_op_names', 'newest_xplane',
    'xspace_class', 'read_xplane',
    'reduce_events', 'reduce_xplane', 'capture_step_profile',
    'profile_payload',
]

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
OPS_LINE = 'XLA Ops'
HOST_PLANE = '/host:CPU'
# the stat of an event's metadata in which the chip's profiler keeps the
# instruction's op_name path (with a trailing `:`)
OP_NAME_STAT = 'tf_op'
PHASES = ('forward', 'backward', 'replay')
KERNEL_ROLES = re.compile(r'^(fused_|pallas_attention_)')
# launches the compiler writes itself and gives no path: the TPU's rewrite of
# `jax.lax.ragged_dot` (ops/expert_layer.py::grouped_dot, under the scope
# `moe_experts`) into Mosaic calls names them `ragged-dot-none.N` and
# `ragged-dot-metadata.N` with that name alone as their op_name
COMPILER_LAUNCH_LEAVES = ((re.compile(r'^ragged-dot'), 'moe_experts'),)
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.MULTILINE)


# --------------------------------------------------------------------- #
# reading a path
# --------------------------------------------------------------------- #
def _components(op_name: str) -> List[str]:
    # a fusion's metadata may join several paths with ';': the first is
    # the root instruction's
    return op_name.split(';', 1)[0].split('/')


def scope_leaf(op_name: Optional[str],
               scopes: Sequence[str] = MODEL_SCOPES) -> Optional[str]:
    """The innermost component of the path that is on the closed list."""
    if not op_name:
        return None
    known = set(scopes)
    for comp in reversed(_components(op_name)):
        if comp in known:
            return comp
        if 'pair' in known and PAIR_SCOPE.match(comp):
            return 'pair'
    return None


def scope_phase(op_name: Optional[str]) -> str:
    comps = _components(op_name or '')
    if 'rematted_computation' in comps:
        return 'replay'
    if any(c.startswith('transpose(') for c in comps):
        return 'backward'
    return 'forward'


def scope_pair(op_name: Optional[str]) -> Optional[str]:
    """`'1,2'` for a path through `pair_1_2` (`'all,2'` for a grouped
    launch), innermost first."""
    for comp in reversed(_components(op_name or '')):
        m = PAIR_SCOPE.match(comp)
        if m:
            return f'{m.group(1)},{m.group(2)}'
    return None


def family(name: str) -> str:
    """`fused_pairwise_conv_bwd_a.17` -> `fused_pairwise_conv_bwd_a`;
    `fusion.123.clone` -> `fusion`."""
    return re.sub(r'(\.\d+)*(\.clone)?(\.\d+)*$', '', name)


def kernel_role(name: str) -> Optional[str]:
    """The role of a Pallas launch, which is its instruction's family name
    (`name=` on the pallas_call), or None for any other instruction."""
    fam = family(name)
    return fam if KERNEL_ROLES.match(fam) else None


def compiler_launch_leaf(name: str) -> Optional[str]:
    """The leaf of a launch that the compiler names and gives no path
    (`COMPILER_LAUNCH_LEAVES`), or None."""
    for pattern, leaf in COMPILER_LAUNCH_LEAVES:
        if pattern.match(name):
            return leaf
    return None


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} from compiled HLO text."""
    return {m.group(1): m.group(2)
            for m in _INSTRUCTION.finditer(hlo_text)}


def _hlo_module_name(hlo_text: str) -> Optional[str]:
    m = re.match(r'\s*HloModule\s+([\w.\-]+)', hlo_text)
    return m.group(1) if m else None


# --------------------------------------------------------------------- #
# reading the trace
# --------------------------------------------------------------------- #
def newest_xplane(root: str) -> Optional[str]:
    """The newest `*.xplane.pb` under `root`, or None."""
    hits = glob.glob(os.path.join(root, '**', '*.xplane.pb'),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def _short_name(text: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return text.split(' = ', 1)[0].lstrip('%').strip()


# the fields of tsl/profiler/protobuf/xplane.proto this reader uses, by
# (name, number, type[, message | repeated]); anything else in the file is
# skipped by the parser
_XPLANE_FIELDS = {
    'XStat': [('metadata_id', 1, 'int64'), ('double_value', 2, 'double'),
              ('uint64_value', 3, 'uint64'), ('int64_value', 4, 'int64'),
              ('str_value', 5, 'string'), ('ref_value', 7, 'uint64')],
    'XEvent': [('metadata_id', 1, 'int64'), ('offset_ps', 2, 'int64'),
               ('duration_ps', 3, 'int64'), ('stats', 4, 'XStat*')],
    'XLine': [('id', 1, 'int64'), ('name', 2, 'string'),
              ('timestamp_ns', 3, 'int64'), ('events', 4, 'XEvent*')],
    'XEventMetadata': [('id', 1, 'int64'), ('name', 2, 'string'),
                       ('stats', 5, 'XStat*')],
    'XStatMetadata': [('id', 1, 'int64'), ('name', 2, 'string')],
    'EventMetadataEntry': [('key', 1, 'int64'),
                           ('value', 2, 'XEventMetadata')],
    'StatMetadataEntry': [('key', 1, 'int64'), ('value', 2, 'XStatMetadata')],
    'XPlane': [('id', 1, 'int64'), ('name', 2, 'string'),
               ('lines', 3, 'XLine*'),
               ('event_metadata', 4, 'EventMetadataEntry*'),
               ('stat_metadata', 5, 'StatMetadataEntry*')],
    'XSpace': [('planes', 1, 'XPlane*')],
}
_XSPACE = []


def xspace_class():
    """The `XSpace` message class, built once from `_XPLANE_FIELDS`."""
    if _XSPACE:
        return _XSPACE[0]
    from google.protobuf import (
        descriptor_pb2, descriptor_pool, message_factory,
    )
    fdp = descriptor_pb2.FileDescriptorProto(
        name='se3_xplane_subset.proto', package='se3_xplane',
        syntax='proto3')
    F = descriptor_pb2.FieldDescriptorProto
    scalar = dict(int64=F.TYPE_INT64, uint64=F.TYPE_UINT64,
                  double=F.TYPE_DOUBLE, string=F.TYPE_STRING)
    for name, fields in _XPLANE_FIELDS.items():
        msg = fdp.message_type.add(name=name)
        for fname, number, ftype in fields:
            repeated = ftype.endswith('*')
            ftype = ftype.rstrip('*')
            field = msg.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if ftype in scalar:
                field.type = scalar[ftype]
            else:
                field.type = F.TYPE_MESSAGE
                field.type_name = f'.se3_xplane.{ftype}'
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    _XSPACE.append(message_factory.GetMessageClass(
        pool.FindMessageTypeByName('se3_xplane.XSpace')))
    return _XSPACE[0]


def _stat_values(stats, stat_names) -> dict:
    out = {}
    for st in stats:
        key = stat_names.get(st.metadata_id)
        if st.ref_value:
            out[key] = stat_names.get(st.ref_value, '')
        elif st.str_value:
            out[key] = st.str_value
    return out


def read_xplane(path: str, host_names: Iterable[str] = ()) -> dict:
    """The trace in the reducer's own form (what tests keep recorded cuts
    of): {'device': {track: [[name, start_ns, dur_ns, op_name | None,
    module | None], ...]}, 'host': [[thread, name, start_ns, dur_ns], ...],
    'selector', 'op_name_source'}. A track is a chip's `XLA Ops` line or,
    in a CPU trace, one worker thread of `/host:CPU`. `host_names`: the
    annotations to keep."""
    space = xspace_class()()
    with open(path, 'rb') as fh:
        space.ParseFromString(fh.read())
    host_names = set(host_names)
    device: Dict[str, list] = {}
    cpu: Dict[str, list] = {}
    host, found = [], 0
    for plane in space.planes:
        on_chip = bool(DEVICE_PLANE.match(plane.name))
        if not on_chip and plane.name != HOST_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        op_of = {}       # event metadata id -> (short name, op_name)
        for line in plane.lines:
            if on_chip and line.name != OPS_LINE:
                continue
            t0 = line.timestamp_ns
            for ev in line.events:
                start = t0 + ev.offset_ps * 1e-3
                dur = ev.duration_ps * 1e-3
                if on_chip:
                    if ev.metadata_id not in op_of:
                        m = meta[ev.metadata_id]
                        op = _stat_values(m.stats, stat_names).get(
                            OP_NAME_STAT)
                        op_of[ev.metadata_id] = (
                            _short_name(m.name),
                            op[:-1] if op and op.endswith(':') else op)
                    name, op = op_of[ev.metadata_id]
                    found += op is not None
                    device.setdefault(plane.name, []).append(
                        [name, start, dur, op, None])
                    continue
                name = meta[ev.metadata_id].name
                if name in host_names:
                    host.append([line.name, name, start, dur])
                    continue
                stats = _stat_values(ev.stats, stat_names)
                if 'hlo_op' in stats:
                    cpu.setdefault(f'{plane.name}/{line.name}', []).append(
                        [stats['hlo_op'], start, dur, None,
                         stats.get('hlo_module')])
    if device:
        return dict(device=device, host=host, selector='device_plane',
                    op_name_source=f'metadata_stat:{OP_NAME_STAT}'
                    if found else 'none')
    return dict(device=cpu, host=host, selector='hlo_op',
                op_name_source='none')


# --------------------------------------------------------------------- #
# reducing it
# --------------------------------------------------------------------- #
def exclusive_durations(events) -> List[Tuple[dict, float]]:
    """(event, exclusive_us) pairs: each event's duration minus the time
    of events nested inside it on the same thread. Without this, a
    wrapping `call` and its fusion body would both be counted and every
    aggregate would double. Events are dicts with pid, tid, ts, dur
    (observability.tracing feeds request spans through the same stack)."""
    out = []
    by_thread: Dict[tuple, list] = {}
    for ev in events:
        by_thread.setdefault((ev.get('pid'), ev.get('tid')), []).append(ev)
    for evs in by_thread.values():
        # parents first on ties: longer duration wins the outer slot
        evs.sort(key=lambda e: (float(e.get('ts', 0.0)),
                                -float(e.get('dur', 0.0))))
        stack: list = []   # entries [end_ts, child_time, event]
        for ev in evs:
            ts = float(ev.get('ts', 0.0))
            dur = float(ev.get('dur', 0.0))
            while stack and ts >= stack[-1][0] - 1e-9:
                end, child, parent = stack.pop()
                out.append((parent, float(parent.get('dur', 0.0)) - child))
            if stack:
                stack[-1][1] += dur
            stack.append([ts + dur, 0.0, ev])
        while stack:
            end, child, parent = stack.pop()
            out.append((parent, float(parent.get('dur', 0.0)) - child))
    return out


def union_length(spans, holes=()) -> float:
    """Length of the union of (start, end) spans, outside `holes`."""
    holes = sorted(holes)
    cut = []
    for s, e in spans:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                cut.append((s, hs))
            s = max(s, he)
            if s >= e:
                break
        if s < e:
            cut.append((s, e))
    total, edge = 0.0, float('-inf')
    for s, e in sorted(cut):
        if e > edge:
            total += e - max(s, edge)
            edge = e
    return total


def _busy_seconds(rows) -> float:
    return union_length((r[1], r[1] + r[2]) for r in rows) * 1e-9


def _add(table: dict, key, seconds: float):
    table[key] = table.get(key, 0.0) + seconds


def reduce_events(events: dict, op_names: Optional[Dict[str, str]] = None,
                  module: Optional[str] = None,
                  scopes: Sequence[str] = MODEL_SCOPES, top: int = 10) -> dict:
    """Seconds by the program's labels for one trace in `read_xplane`'s
    form. `op_names`: {instruction: op_name} for events that carry none
    (source (c)); `module`: keep only events of this HLO module, where
    events say theirs (a CPU trace holds every program that ran).

    Returns busy_s (union of intervals, averaged over chips; over worker
    threads in a CPU trace they overlap and the union is of all of them),
    device_s (exclusive seconds, summed), leaf_s {leaf: s}, phase_s,
    leaf_phase_s {leaf: {phase: s}}, kernel_s {role: s}, kernel_pair_s
    {role: {pair: s}}, labelled_s, unlabelled_s, unlabelled_top
    [[instruction family, s], ...] and coverage = labelled_s / device_s."""
    op_names = op_names or {}
    leaf_s: Dict[str, float] = {}
    phase_s: Dict[str, float] = {}
    leaf_phase_s: Dict[str, Dict[str, float]] = {}
    kernel_s: Dict[str, float] = {}
    kernel_pair_s: Dict[str, Dict[str, float]] = {}
    unlabelled: Dict[str, float] = {}
    device_s = labelled_s = 0.0
    n_events = from_hlo = 0
    tracks = {t: [r for r in rows if module is None or r[4] in (None, module)]
              for t, rows in events['device'].items()}
    if events.get('selector') == 'hlo_op':
        # worker threads overlap: one union over all of them
        busy = [_busy_seconds([r for rows in tracks.values() for r in rows])]
    else:
        busy = [_busy_seconds(rows) for rows in tracks.values()]
    for track, rows in tracks.items():
        dicts = [dict(pid=track, tid=0, ts=r[1], dur=r[2], row=r)
                 for r in rows]
        for ev, excl_ns in exclusive_durations(dicts):
            if excl_ns <= 0:
                continue
            name, _, _, op, _ = ev['row']
            if op is None and name in op_names:
                op, from_hlo = op_names[name], from_hlo + 1
            secs = excl_ns * 1e-9
            n_events += 1
            device_s += secs
            role = kernel_role(name)
            if role is not None:
                _add(kernel_s, role, secs)
                _add(kernel_pair_s.setdefault(role, {}),
                     scope_pair(op) or 'none', secs)
            leaf = scope_leaf(op, scopes) or compiler_launch_leaf(name)
            if leaf is None:
                _add(unlabelled, family(name), secs)
                continue
            phase = scope_phase(op)
            labelled_s += secs
            _add(leaf_s, leaf, secs)
            _add(phase_s, phase, secs)
            _add(leaf_phase_s.setdefault(leaf, {}), phase, secs)
    source = events.get('op_name_source', 'none')
    if from_hlo:
        source = 'hlo_text'
    return dict(
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        device_s=device_s, labelled_s=labelled_s,
        unlabelled_s=device_s - labelled_s,
        coverage=labelled_s / device_s if device_s else 0.0,
        leaf_s=leaf_s, phase_s=phase_s, leaf_phase_s=leaf_phase_s,
        kernel_s=kernel_s, kernel_pair_s=kernel_pair_s,
        unlabelled_top=[[k, v] for k, v in sorted(
            unlabelled.items(), key=lambda kv: -kv[1])[:top]],
        events=n_events, tracks=sorted(events['device']),
        selector=events.get('selector'), op_name_source=source)


def reduce_xplane(path: str, hlo_text: Optional[str] = None,
                  scopes: Sequence[str] = MODEL_SCOPES) -> dict:
    """`reduce_events` on a `.xplane.pb` (or the newest one under a
    directory). With `hlo_text`, events that carry no op_name get it from
    there, and a CPU trace is cut down to that module's events."""
    if os.path.isdir(path):
        found = newest_xplane(path)
        if found is None:
            raise FileNotFoundError(f'no *.xplane.pb under {path}')
        path = found
    events = read_xplane(path)
    names = hlo_op_names(hlo_text) if hlo_text else None
    module = _hlo_module_name(hlo_text) \
        if hlo_text and events['selector'] == 'hlo_op' else None
    out = reduce_events(events, names, module, scopes)
    out['source'] = path
    return out


# --------------------------------------------------------------------- #
# capture + record body
# --------------------------------------------------------------------- #
def capture_step_profile(fn, args=(), *, log_dir: str, steps: int = 3):
    """Run `fn(*args)` `steps` times under trace capture (the callable
    must already be warm — a compile inside the window would swamp the
    attribution) and block on the last result. Returns log_dir."""
    import jax
    out = None
    with profile_trace(log_dir):
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
    return log_dir


def _table(seconds: Dict[str, float], total: float) -> dict:
    return {k: dict(time_ms=round(v * 1e3, 3),
                    share=round(v / total, 4) if total else 0.0)
            for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])}


def profile_payload(trace_dir: str, *, label: str,
                    hlo_text: Optional[str] = None,
                    scopes: Sequence[str] = MODEL_SCOPES,
                    flops_per_step: Optional[float] = None,
                    steps: int = 1, top_unattributed: int = 8,
                    device_kind: Optional[str] = None) -> dict:
    """The schema'd `profile` record body (kind='profile', minus
    run_id): per-leaf and per-phase device-time shares + attribution
    coverage for the newest trace under `trace_dir`, and the roofline
    figure when the caller supplies the program's per-step flops
    (observability.costs). `device_kind` names the accelerator the trace
    was captured on (`jax.devices()[0].device_kind`); utilization is
    priced against that device's published peak and omitted without it."""
    red = reduce_xplane(trace_dir, hlo_text, scopes)
    total = red['device_s']
    body = dict(
        label=label,
        scopes=_table(red['leaf_s'], total),
        phases=_table(red['phase_s'], total),
        device_time_ms=round(total * 1e3, 3),
        coverage=round(red['coverage'], 4),
        steps=steps,
        tracks=dict(selector=red['selector'], tracks=red['tracks'],
                    op_name_source=red['op_name_source']),
        unattributed_top=[
            dict(op=op, time_ms=round(s * 1e3, 3))
            for op, s in red['unlabelled_top'][:top_unattributed]],
    )
    if red['kernel_s']:
        body['kernels'] = _table(red['kernel_s'], total)
    if flops_per_step and total:
        flops_per_sec = flops_per_step * steps / total
        body['roofline'] = dict(
            flops_per_step=flops_per_step,
            device_flops_per_sec=round(flops_per_sec, 1))
        if device_kind is not None:
            # against the peak of the device the trace was taken on; a
            # CPU trace (device_kind=None) carries no utilization
            from ..utils.flops import device_peaks
            body['roofline']['utilization_vs_bf16_peak'] = round(
                flops_per_sec / device_peaks(device_kind)['bf16_flops'], 6)
    return body
