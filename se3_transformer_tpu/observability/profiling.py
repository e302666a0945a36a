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
      `hlo_module` and no path, the caller's AOT executable gives it at
      no cost (`make profile-smoke`); on the chip it would cost a second
      trace of the step and is not needed.

`jax.profiler.ProfileData` shows a plane's lines and each event's own stats,
not the event metadata's, so it cannot see (a). The file is therefore read as
what it is, an `XSpace` protocol buffer, through the fields of `xplane.proto`
declared below (google.protobuf, which the installation has; nothing of
TensorFlow or tensorboard).

What each instruction IS and how much it COMPUTES is read from the same file
(PR 36) into a side table {program: {instruction: {category, flops, bytes,
products}}}: `category` is the metadata stat `hlo_category` (`convolution
fusion`, `loop fusion`, `data formatting`, `custom-call`, ...), `bytes` the
stat `bytes_accessed`, and the operations of the products an instruction holds
(`products` of them: `dot`s and `convolution`s, a fusion's being its fused
computation's) are counted by one rule, `product_counts`, from

  (b) the HLO module the profiler stores in the plane `/host:metadata` (an
      `HloProto` in the stat `Hlo Proto` of one event metadata record per
      program; 5 to 9 of a decoder trace's 9 to 15 MB), through a handful of
      `hlo.proto` fields declared as `xplane.proto`'s are. XLA:CPU's profiler
      stores it too, so there is one front end; a reader of compiled HLO
      text by the same rule is kept under tests/ as the independent check of
      the declared fields (tests/hlo_text_reference.py).

An instruction's name is unique in a program, not in a trace, so the join is
on (program, instruction): the record of `/host:metadata` is keyed by the
program's id and named `jit_train_step(<id>)`, every instruction's metadata on
the chip carries that id in the stat `program_id`, and every event of a CPU
trace carries it as a stat of its own. The name of the record is the fifth
column of a device row (a CPU row has the bare `hlo_module` where the trace
stores no record); only the programs that ran in the trace are parsed.

The chip's profiler also writes a stat `flops` beside `bytes_accessed` (source
(a) of the count), and it is not used: it is XLA's cost analysis of the whole
instruction, elementwise operations included and a float32 product at
`highest` precision counted six times (its bf16 passes), so it equals the
products' count only on a fusion that holds nothing else (22 of the GLM step's
273 product instructions; never below it: the check that costs nothing). A
custom call (a Pallas or Mosaic launch, the compiler's `ragged-dot-*`) has
`flops` None: its operations are its own roofline's business. `flops_source`
says whether the trace stored a module to count from (`hlo_proto` or `none`).

From the path: the LEAF is the innermost component on the closed list
`MODEL_SCOPES` (`pair_<d_in>_<d_out>` reads as `pair`); the PHASE is `replay`
under a `rematted_computation` component, `backward` under a `transpose(...)`
one, else `forward`; a kernel launch's ROLE is its instruction's family name
(`fused_pairwise_conv_bwd_a`) and its PAIR the `pair_*` component; the PASS
of a looped stack is the component `ut_<t>` (`pass_s`, by phase, whatever
the leaf). Seconds
are exclusive (an event's time less the events nested in it) and summed over
chips; busy time is the union of intervals, averaged over chips. With the side
table the same seconds are split three ways, by leaf: instructions that hold
a product XLA compiled (`product_s`, with `product_flops` and `product_bytes`,
by phase too), custom calls (`launch_s`), and everything else by category
(`glue_s`); `format_products` prints all five as the table an operator wants
(`product_bytes` as the products' share of the HBM peak).

`make profile-smoke` gates a toy run on coverage plus schema validity;
`benchmark/run.py --trace 1` is the traced flagship step (its readers in
`benchmark/layer_metrics/` call `reduce_xplane`).
"""
from __future__ import annotations

import functools
import glob
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .timing import MODEL_SCOPES, PAIR_SCOPE, PASS_SCOPE, profile_trace

__all__ = [
    'exclusive_durations', 'union_length', 'scope_leaf', 'scope_phase',
    'scope_pair', 'scope_pass',
    'kernel_role', 'compiler_launch_leaf', 'hlo_op_names', 'newest_xplane',
    'xspace_class', 'read_xplane',
    'hlo_proto_computations', 'product_counts',
    'reduce_events', 'reduce_xplane', 'format_products', 'format_passes',
    'capture_step_profile', 'profile_payload',
]

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
OPS_LINE = 'XLA Ops'
HOST_PLANE = '/host:CPU'
METADATA_PLANE = '/host:metadata'
HLO_PROTO_STAT = 'hlo_proto'
# the stat of an event's metadata in which the chip's profiler keeps the
# instruction's op_name path (with a trailing `:`)
OP_NAME_STAT = 'tf_op'
CATEGORY_STAT = 'hlo_category'
BYTES_STAT = 'bytes_accessed'
PROGRAM_STAT = 'program_id'
# `jit_train_step(125)`, a stored program's record, names the module
# `jit_train_step`
_PROGRAM_ID = re.compile(r'\(\d+\)$')
PHASES = ('forward', 'backward', 'replay')
UNLABELLED = 'unlabelled'
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


def scope_pass(op_name: Optional[str]) -> Optional[str]:
    """`'ut_2'` for a path through the component `ut_2`, the third pass of a
    looped stack (`timing.PASS_SCOPE`); None for a path without one."""
    for comp in _components(op_name or ''):
        if PASS_SCOPE.match(comp):
            return comp
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
# counting an instruction's products
# --------------------------------------------------------------------- #
# One rule (`product_counts`) over one form of a module, {computation:
# [instruction, ...]} with an instruction as (name, opcode, kind, extents,
# windows, calls). A product's operations are 2 x the product of `extents` x
# the valid positions of each of `windows`. For a `dot`, `extents` are the
# output's dimensions and the left operand's contracting ones, and there is
# no window. For a `convolution` (the TPU's compiler writes nearly every dot
# as one) they are the output's batch and feature dimensions and the kernel's
# input feature dimension, and each spatial dimension is a window (input
# size, output size, kernel size, stride, low padding, kernel dilation, input
# dilation): a dot's batch dimension comes as a window whose stride and input
# dilation leave one tap in `size` on the input, a per-head projection as a
# window of `size` taps over an input of one, all but one in the padding.
# `calls` are the computations a fusion names. `hlo_proto_computations` gives
# that form from the module the profiler stores in the trace; a reader of
# compiled HLO text under tests/ gives it too, and the two agree to the
# operation on the deviceless compile of every decoder step
# (tests/test_tpu_compile.py).
PRODUCT_OPCODES = ('dot', 'convolution')
LAUNCH_OPCODE = 'custom-call'


@functools.lru_cache(maxsize=4096)
def _valid_positions(n_in, n_out, size, stride, pad_low, dilation,
                     in_dilation) -> int:
    """The (tap, output position) pairs of one window dimension that fall on
    an element of the input and not into padding or between dilated
    elements (the count XLA's own cost analysis makes). Tap k meets output
    position o at o * stride + k * dilation - pad_low of the dilated input:
    per tap, the positions in range are an interval of o, and those on an
    element a residue class of it (a window of 32,768 taps is a batch
    dimension: no loop over both)."""
    reach = (n_in - 1) * in_dilation + 1
    g = math.gcd(stride, in_dilation)
    period = in_dilation // g
    total = 0
    for k in range(size):
        c = k * dilation - pad_low
        lo, hi = max(0, -(c // stride)), min(n_out - 1,
                                             (reach - 1 - c) // stride)
        if c % g or hi < lo:
            continue
        if period > 1:     # stride * o + c = 0 modulo the input's dilation
            first = (-c // g) * pow(stride // g, -1, period) % period
            lo += (first - lo) % period
        if lo <= hi:
            total += (hi - lo) // period + 1
    return total


def hlo_proto_computations(module) -> Dict[str, list]:
    """The counting rule's form of an `HloModuleProto` (`_HLO_FIELDS`)."""
    comp_names = {c.id: c.name for c in module.computations}
    dims_of = {i.id: tuple(i.shape.dimensions)
               for c in module.computations for i in c.instructions}
    comps: Dict[str, list] = {}
    for c in module.computations:
        rows = comps.setdefault(c.name, [])
        for i in c.instructions:
            extents, windows = tuple(i.shape.dimensions), ()
            if i.opcode == 'dot':
                lhs = dims_of[i.operand_ids[0]]
                extents += tuple(
                    lhs[a] for a in
                    i.dot_dimension_numbers.lhs_contracting_dimensions)
            elif i.opcode == 'convolution':
                lhs, rhs = (dims_of[k] for k in i.operand_ids[:2])
                numbers = i.convolution_dimension_numbers
                spatial = list(numbers.output_spatial_dimensions)
                windows = tuple(
                    (lhs[a], extents[b], w.size, w.stride or 1,
                     w.padding_low, w.window_dilation or 1,
                     w.base_dilation or 1)
                    for a, b, w in zip(numbers.input_spatial_dimensions,
                                       spatial, i.window.dimensions))
                extents = tuple(
                    e for a, e in enumerate(extents) if a not in spatial) \
                    + (rhs[numbers.kernel_input_feature_dimension],)
            fused = i.opcode == 'fusion'
            rows.append((i.name, i.opcode,
                         i.fusion_kind[1:] if fused else '',
                         extents, windows,
                         [comp_names[k] for k in i.called_computation_ids]
                         if fused else []))
    return comps


def product_counts(computations: Dict[str, list]) -> Dict[str, dict]:
    """{instruction: {opcode, kind, flops, products}} for every instruction
    of a module. A product's operations are 2 x its extents x the valid
    positions of its windows (above); a fusion's are those of its fused
    computation. A `while`, a `conditional` or a `call` counts nothing of
    the computations it runs: their instructions are events of their own,
    each time they run. A custom call's operations are not known here
    (`flops` None): Pallas and the compiler's own Mosaic launches have
    rooflines of their own."""
    own = {}
    for rows in computations.values():
        for name, opcode, kind, extents, windows, calls in rows:
            n = opcode in PRODUCT_OPCODES
            flops = 0
            if n:
                flops = 2
                for e in extents:
                    flops *= e
                for w in windows:
                    flops *= _valid_positions(*w)
            own[name] = (flops, int(n))
    held: Dict[str, Tuple[int, int]] = {}

    def of_computation(comp):
        if comp not in held:
            held[comp] = (0, 0)               # a cycle would be a fault
            held[comp] = tuple(map(sum, zip(
                (0, 0), *(of(row) for row in computations.get(comp, ())))))
        return held[comp]

    def of(row):
        flops, n = own[row[0]]
        for comp in row[5]:
            f, k = of_computation(comp)
            flops, n = flops + f, n + k
        return flops, n

    out = {}
    for rows in computations.values():
        for row in rows:
            flops, n = of(row)
            out[row[0]] = dict(
                opcode=row[1], kind=row[2], products=n,
                flops=None if row[1] == LAUNCH_OPCODE else flops)
    return out


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
# (name, number, type[, oneof]) with `*` on a repeated one; anything else in
# the file is skipped by the parser
_XPLANE_FIELDS = {
    'XStat': [('metadata_id', 1, 'int64'),
              ('double_value', 2, 'double', 'value'),
              ('uint64_value', 3, 'uint64', 'value'),
              ('int64_value', 4, 'int64', 'value'),
              ('str_value', 5, 'string', 'value'),
              ('bytes_value', 6, 'bytes', 'value'),
              ('ref_value', 7, 'uint64', 'value')],
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
# and of xla/service/hlo.proto and xla/xla_data.proto, for the module the
# profiler stores beside the trace: what the counting rule of
# `product_counts` asks of an instruction, and nothing else
_HLO_FIELDS = {
    'ShapeProto': [('dimensions', 3, 'int64*')],
    'DotDimensionNumbers': [('lhs_contracting_dimensions', 1, 'int64*')],
    'ConvolutionDimensionNumbers': [
        ('kernel_input_feature_dimension', 3, 'int64'),
        ('kernel_spatial_dimensions', 6, 'int64*'),
        ('input_spatial_dimensions', 11, 'int64*'),
        ('output_spatial_dimensions', 12, 'int64*')],
    'WindowDimension': [('size', 1, 'int64'), ('stride', 2, 'int64'),
                        ('padding_low', 3, 'int64'),
                        ('window_dilation', 5, 'int64'),
                        ('base_dilation', 6, 'int64')],
    'Window': [('dimensions', 1, 'WindowDimension*')],
    'HloInstructionProto': [
        ('name', 1, 'string'), ('opcode', 2, 'string'),
        ('shape', 3, 'ShapeProto'), ('fusion_kind', 11, 'string'),
        ('window', 15, 'Window'),
        ('convolution_dimension_numbers', 16,
         'ConvolutionDimensionNumbers'),
        ('dot_dimension_numbers', 30, 'DotDimensionNumbers'),
        ('id', 35, 'int64'), ('operand_ids', 36, 'int64*'),
        ('called_computation_ids', 38, 'int64*')],
    'HloComputationProto': [('name', 1, 'string'),
                            ('instructions', 2, 'HloInstructionProto*'),
                            ('id', 5, 'int64')],
    'HloModuleProto': [('name', 1, 'string'),
                       ('computations', 3, 'HloComputationProto*')],
    'HloProto': [('hlo_module', 1, 'HloModuleProto')],
}
_CLASSES = {}


def _message_class(package: str, fields: dict, root: str):
    """The message class `root` of a file built from a table of fields,
    once per package."""
    if package in _CLASSES:
        return _CLASSES[package]
    from google.protobuf import (
        descriptor_pb2, descriptor_pool, message_factory,
    )
    fdp = descriptor_pb2.FileDescriptorProto(
        name=f'{package}_subset.proto', package=package, syntax='proto3')
    F = descriptor_pb2.FieldDescriptorProto
    scalar = dict(int64=F.TYPE_INT64, uint64=F.TYPE_UINT64,
                  double=F.TYPE_DOUBLE, string=F.TYPE_STRING,
                  bytes=F.TYPE_BYTES)
    for name, rows in fields.items():
        msg = fdp.message_type.add(name=name)
        for fname, number, ftype, *oneof in rows:
            repeated = ftype.endswith('*')
            ftype = ftype.rstrip('*')
            field = msg.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if ftype in scalar:
                field.type = scalar[ftype]
            else:
                field.type = F.TYPE_MESSAGE
                field.type_name = f'.{package}.{ftype}'
            if oneof:
                if not msg.oneof_decl:
                    msg.oneof_decl.add(name=oneof[0])
                field.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    _CLASSES[package] = message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f'{package}.{root}'))
    return _CLASSES[package]


def xspace_class():
    """The `XSpace` message class, built once from `_XPLANE_FIELDS`."""
    return _message_class('se3_xplane', _XPLANE_FIELDS, 'XSpace')


def hlo_proto_class():
    """The `HloProto` message class, built once from `_HLO_FIELDS`."""
    return _message_class('se3_hlo', _HLO_FIELDS, 'HloProto')


def _stat_values(stats, stat_names) -> dict:
    """{stat name: value} whatever the value's type: a reference reads as
    the name it points at, anything else as it is written."""
    out = {}
    for st in stats:
        which = st.WhichOneof('value')
        if which == 'ref_value':
            out[stat_names.get(st.metadata_id)] = stat_names.get(
                st.ref_value, '')
        elif which:
            out[stat_names.get(st.metadata_id)] = getattr(st, which)
    return out


def _stored_programs(plane) -> Dict[int, Tuple[str, bytes]]:
    """{program id: (record name, serialized `HloProto`)} for every program
    the profiler stored in the plane `/host:metadata`: one event metadata
    record per program, keyed by its id (an unsigned number in a signed
    field) and named `jit_train_step(<id>)`, the module in a stat the chip
    names `hlo_proto` and XLA:CPU `Hlo Proto`."""
    stat_names = {e.key: e.value.name.lower().replace(' ', '_')
                  for e in plane.stat_metadata}
    return {e.key % 2 ** 64: (e.value.name, st.bytes_value)
            for e in plane.event_metadata for st in e.value.stats
            if stat_names.get(st.metadata_id) == HLO_PROTO_STAT}


def _counts_of(raw: bytes) -> Dict[str, dict]:
    proto = hlo_proto_class()()
    proto.ParseFromString(raw)
    return product_counts(hlo_proto_computations(proto.hlo_module))


def read_xplane(path: str, host_names: Iterable[str] = ()) -> dict:
    """The trace in the reducer's own form (what tests keep recorded cuts
    of): {'device': {track: [[name, start_ns, dur_ns, op_name | None,
    program | None], ...]}, 'host': [[thread, name, start_ns, dur_ns], ...],
    'selector', 'op_name_source', 'instructions', 'flops_source'}. A track
    is a chip's `XLA Ops` line or, in a CPU trace, one worker thread of
    `/host:CPU`. `host_names`: the annotations to keep. A row's program is
    the name of the record the trace stores of it (`jit_train_step(<id>)`;
    a CPU event of a program without a record has its bare `hlo_module`).
    `instructions` is {program: {instruction: {category, flops, bytes,
    products}}} for every instruction that ran (`instruction_table`), and
    is left out where the trace stores no module to count from."""
    space = xspace_class()()
    with open(path, 'rb') as fh:
        space.ParseFromString(fh.read())
    host_names = set(host_names)
    device: Dict[str, list] = {}
    cpu: Dict[str, list] = {}
    # ((program id, module), instruction) -> the instruction's metadata stats
    stats_of: Dict[Tuple[tuple, str], dict] = {}
    programs: Dict[int, Tuple[str, bytes]] = {}
    host, found = [], 0
    for plane in space.planes:
        if plane.name == METADATA_PLANE:
            programs = _stored_programs(plane)
            continue
        on_chip = bool(DEVICE_PLANE.match(plane.name))
        if not on_chip and plane.name != HOST_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        op_of = {}    # event metadata id -> (short name, op_name, program)
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
                        stats = _stat_values(m.stats, stat_names)
                        op = stats.get(OP_NAME_STAT) or None
                        name = _short_name(m.name)
                        program = (stats.get(PROGRAM_STAT), None)
                        op_of[ev.metadata_id] = (
                            name, op[:-1] if op and op.endswith(':') else op,
                            program)
                        stats_of[program, name] = stats
                    name, op, program = op_of[ev.metadata_id]
                    found += op is not None
                    device.setdefault(plane.name, []).append(
                        [name, start, dur, op, program])
                    continue
                name = meta[ev.metadata_id].name
                if name in host_names:
                    host.append([line.name, name, start, dur])
                    continue
                stats = _stat_values(ev.stats, stat_names)
                if 'hlo_op' in stats:
                    program = (stats.get(PROGRAM_STAT),
                               stats.get('hlo_module'))
                    stats_of.setdefault((program, stats['hlo_op']), {})
                    cpu.setdefault(f'{plane.name}/{line.name}', []).append(
                        [stats['hlo_op'], start, dur, None, program])
    if device:
        out = dict(device=device, host=host, selector='device_plane',
                   op_name_source=f'metadata_stat:{OP_NAME_STAT}'
                   if found else 'none')
    else:
        out = dict(device=cpu, host=host, selector='hlo_op',
                   op_name_source='none')
    # the stored records come after the chip's planes: a row's program is
    # named once they are read. An executable that XLA:CPU loaded from the
    # compilation cache runs under another id than the one its module was
    # stored under: there the module's name joins them, if one stored
    # program alone bears it.
    of_module: Dict[str, list] = {}
    for program, (name, _) in programs.items():
        of_module.setdefault(_PROGRAM_ID.sub('', name), []).append(program)

    def stored(program, module):
        alone = of_module.get(module, ())
        return program if program in programs \
            else alone[0] if len(alone) == 1 else None

    for rows in out['device'].values():
        for row in rows:
            program = stored(*row[4])
            row[4] = row[4][1] if program is None else programs[program][0]
    ran: Dict[int, dict] = {}
    for (program, name), stats in stats_of.items():
        program = stored(*program)
        if program is not None:
            ran.setdefault(program, {})[name] = stats
    if ran:
        out.update(flops_source='hlo_proto', instructions={
            programs[program][0]: instruction_table(
                _counts_of(programs[program][1]), stats)
            for program, stats in ran.items()})
    return out


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


def _add2(table: dict, key, sub, value: float):
    _add(table.setdefault(key, {}), sub, value)


def reduce_events(events: dict, op_names: Optional[Dict[str, str]] = None,
                  module: Optional[str] = None,
                  scopes: Sequence[str] = MODEL_SCOPES, top: int = 10) -> dict:
    """Seconds by the program's labels for one trace in `read_xplane`'s
    form. `op_names`: {instruction: op_name} for events that carry none
    (source (c)); `module`: keep only events of this HLO module, where
    events say their program (a CPU trace holds every program that ran).

    Returns busy_s (union of intervals, averaged over chips; over worker
    threads in a CPU trace they overlap and the union is of all of them),
    device_s (exclusive seconds, summed), leaf_s {leaf: s}, phase_s,
    leaf_phase_s {leaf: {phase: s}}, pass_s {`ut_<t>`: {phase: s}} (over
    the events whose path has such a component, labelled or not; empty for a
    program without a looped stack), kernel_s {role: s}, kernel_pair_s
    {role: {pair: s}}, labelled_s, unlabelled_s, unlabelled_top
    [[instruction family, s], ...] and coverage = labelled_s / device_s.

    With a side table, by the same exclusive seconds, leaves and phases,
    what carries no leaf under `unlabelled`, each event joined to its row
    on (program, instruction): product_s, product_flops, product_bytes
    {leaf: {phase: value}} over the events whose instruction holds at least
    one product XLA compiled (operations and bytes once per event: a loop's
    body counts each time it ran); launch_s {leaf: s} over custom calls
    and launch_roles {leaf: {launch's family name: {phase: [events, s]}}},
    the same seconds by what each launch is called;
    glue_s {leaf: {category: s}} over everything else (an event of a
    program the table lacks under the category `unknown`), so that the
    three sum to device_s and a leaf's remainder reads as `loop fusion`,
    `data formatting`, `copy` and not as `fusion`. Without a table the five
    are empty and `flops_source` is 'none'."""
    op_names = op_names or {}
    table = events.get('instructions') or {}
    leaf_s: Dict[str, float] = {}
    phase_s: Dict[str, float] = {}
    leaf_phase_s: Dict[str, Dict[str, float]] = {}
    pass_s: Dict[str, Dict[str, float]] = {}
    kernel_s: Dict[str, float] = {}
    kernel_pair_s: Dict[str, Dict[str, float]] = {}
    unlabelled: Dict[str, float] = {}
    product_s: Dict[str, Dict[str, float]] = {}
    product_flops: Dict[str, Dict[str, float]] = {}
    product_bytes: Dict[str, Dict[str, float]] = {}
    glue_s: Dict[str, Dict[str, float]] = {}
    launch_s: Dict[str, float] = {}
    launch_roles: Dict[str, Dict[str, Dict[str, list]]] = {}
    device_s = labelled_s = 0.0
    n_events = from_hlo = 0
    tracks = {t: [r for r in rows if module is None or r[4] is None
                  or _PROGRAM_ID.sub('', r[4]) == module]
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
            name, _, _, op, program = ev['row']
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
            phase = scope_phase(op)
            ut = scope_pass(op)
            if ut is not None:
                _add2(pass_s, ut, phase, secs)
            if table:
                info = table.get(program, {}).get(name) or {}
                filed = leaf or UNLABELLED
                if 'flops' in info and info['flops'] is None:
                    _add(launch_s, filed, secs)
                    row = launch_roles.setdefault(filed, {}).setdefault(
                        family(name), {}).setdefault(phase, [0, 0.0])
                    row[0], row[1] = row[0] + 1, row[1] + secs
                elif info.get('products'):
                    _add2(product_s, filed, phase, secs)
                    _add2(product_flops, filed, phase, info['flops'])
                    _add2(product_bytes, filed, phase,
                          info.get('bytes') or 0)
                else:
                    _add2(glue_s, filed, info.get('category') or 'unknown',
                          secs)
            if leaf is None:
                _add(unlabelled, family(name), secs)
                continue
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
        pass_s=pass_s, kernel_s=kernel_s, kernel_pair_s=kernel_pair_s,
        product_s=product_s, product_flops=product_flops,
        product_bytes=product_bytes, launch_s=launch_s,
        launch_roles=launch_roles, glue_s=glue_s,
        unlabelled_top=[[k, v] for k, v in sorted(
            unlabelled.items(), key=lambda kv: -kv[1])[:top]],
        events=n_events, tracks=sorted(events['device']),
        selector=events.get('selector'), op_name_source=source,
        flops_source=events.get('flops_source', 'none') if table else 'none')


def instruction_table(counts: Dict[str, dict],
                      stats: Dict[str, dict]) -> dict:
    """One program's part of `read_xplane`'s side table, {instruction:
    {category, flops, bytes, products}}: `product_counts` of its module
    beside the metadata stats of each instruction that ran (`stats`), where
    the profiler wrote them (`hlo_category`, `bytes_accessed`). Without
    the stat (a CPU trace) the category is the opcode (a fusion's kind and
    `fusion`)."""
    out = {}
    for name, st in stats.items():
        c = counts.get(name, {})
        out[name] = dict(
            category=st.get(CATEGORY_STAT) or (
                f'{c["kind"].lower()} fusion' if c.get('kind')
                else c.get('opcode')) or 'unknown',
            flops=c.get('flops', 0), bytes=st.get(BYTES_STAT),
            products=c.get('products', 0))
    return out


def reduce_xplane(path: str, hlo_text: Optional[str] = None,
                  scopes: Sequence[str] = MODEL_SCOPES) -> dict:
    """`reduce_events` on a `.xplane.pb` (or the newest one under a
    directory). With `hlo_text`, events that carry no op_name get it from
    there and a CPU trace is cut down to that module's events."""
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


def format_products(red: dict, peak_flops: float, peak_bytes: float,
                    steps: int = 1) -> str:
    """The table an operator wants from a reduction that has the product
    tables: per leaf (heaviest products first), for forward | replay |
    backward the products' ms a step, their TFLOP a step, their share of
    `peak_flops` and the share of `peak_bytes` (a second) that the bytes
    XLA says they access come to, then the leaf's ms outside products and
    launches with its two heaviest categories, and its launches' ms. A
    product far below both peaks is bound by neither: look at its layout."""
    order = ('forward', 'replay', 'backward')
    leaves = sorted(
        set(red['product_s']) | set(red['glue_s']) | set(red['launch_s']),
        key=lambda leaf: -sum(red['product_s'].get(leaf, {}).values()))
    lines = [f'{"leaf":<16}' + ''.join(
        f'| {phase + ": ms TFLOP %peak %hbm":<32}' for phase in order)
        + '| glue ms (two heaviest categories); launches ms']
    for leaf in leaves:
        cells = []
        for phase in order:
            secs = red['product_s'].get(leaf, {}).get(phase, 0.0)
            flops = red['product_flops'].get(leaf, {}).get(phase, 0.0)
            nbytes = red['product_bytes'].get(leaf, {}).get(phase, 0.0)
            cells.append(
                f'| {1e3 * secs / steps:8.2f} {1e-12 * flops / steps:7.3f} '
                f'{100 * flops / secs / peak_flops:6.1f} '
                f'{100 * nbytes / secs / peak_bytes:6.1f}  ' if secs
                else '| ' + ' ' * 32)
        glue = sorted(red['glue_s'].get(leaf, {}).items(),
                      key=lambda kv: -kv[1])
        heaviest = ', '.join(f'{c} {1e3 * v / steps:.2f}'
                             for c, v in glue[:2])
        lines.append(
            f'{leaf:<16}' + ''.join(cells)
            + f'| {1e3 * sum(v for _, v in glue) / steps:7.2f}'
            + (f' ({heaviest})' if glue else '')
            + f'; {1e3 * red["launch_s"].get(leaf, 0.0) / steps:.2f}')
    return '\n'.join(lines)


def format_launches(red: dict, steps: int = 1) -> str:
    """A row a launch by its name (`name=` on a `pl.pallas_call`, or what
    the compiler calls its own) under the leaf that files it: events and ms
    a step, forward | replay | backward. How often a path engages: a layer
    that fell back to XLA's own operations has no row."""
    order = ('forward', 'replay', 'backward')
    lines = [f'{"leaf / launch":<36}' + ''.join(
        f'| {phase + ": events ms":<24}' for phase in order)]
    for leaf, roles in sorted(red['launch_roles'].items()):
        for role, phases in sorted(roles.items()):
            cells = (phases.get(phase, (0, 0.0)) for phase in order)
            lines.append(f'{leaf + " / " + role:<36}' + ''.join(
                f'| {n / steps:8.2f} {1e3 * secs / steps:10.2f}     '
                if n else '| ' + ' ' * 24 for n, secs in cells))
    return '\n'.join(lines)


def format_passes(red: dict, steps: int = 1) -> str:
    """A looped stack's passes apart (`pass_s`): a row a pass, ms a step
    forward | replay | backward and in all. The last pass's backward runs
    first and the first pass's replay last."""
    order = ('forward', 'replay', 'backward')
    lines = [f'{"pass":<8}' + ''.join(f'| {phase + " ms":<12}'
                                      for phase in order) + '| all ms']
    for ut, phases in sorted(red['pass_s'].items()):
        lines.append(f'{ut:<8}' + ''.join(
            f'| {1e3 * phases.get(phase, 0.0) / steps:<12.2f}'
            for phase in order)
            + f'| {1e3 * sum(phases.values()) / steps:.2f}')
    return '\n'.join(lines)


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


def main(argv=None):
    """`python scripts/product_table.py <trace>`: the operator's table
    (`format_products`) from a trace directory or an `.xplane.pb`, and what
    the products, the launches and the rest add up to."""
    import argparse
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument('trace', help='a directory with *.xplane.pb, or one')
    ap.add_argument('--steps', type=int, default=1,
                    help='steps in the trace, to print per step')
    ap.add_argument('--device-kind', default='TPU v5 lite',
                    help='whose bf16 peak the shares are of')
    args = ap.parse_args(argv)
    from ..utils.flops import device_peaks
    red = reduce_xplane(args.trace)
    if red['flops_source'] == 'none':
        raise SystemExit(f'{red["source"]} stores no module to count from')
    peaks = device_peaks(args.device_kind)
    print(format_products(red, peaks['bf16_flops'],
                          peaks['hbm_bytes_per_sec'], args.steps))
    print(format_launches(red, args.steps))
    if red['pass_s']:
        print(format_passes(red, args.steps))
    parts = [sum(sum(v.values()) for v in red[k].values())
             for k in ('product_s', 'glue_s')]
    parts.append(sum(red['launch_s'].values()))
    print(f'device {1e3 * red["device_s"] / args.steps:.2f} ms a step = '
          f'products {1e3 * parts[0] / args.steps:.2f} + glue '
          f'{1e3 * parts[1] / args.steps:.2f} + launches '
          f'{1e3 * parts[2] / args.steps:.2f}; operations from '
          f'{red["flops_source"]}, {red["events"]} events of {red["source"]}')
