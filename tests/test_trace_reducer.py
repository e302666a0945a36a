"""The trace reducer on one step of this repo's own v5e trace
(`benchmark/run.py --workload d4_onehead_train --trace 1`, PR 24), cut by
tests/fixtures/record_v5e_fixture.py with each event's op_name as the chip's
profiler wrote it."""
import gzip
import json
import os

import pytest

from hlo_text_reference import hlo_text_computations
from se3_transformer_tpu.observability import profiling
from se3_transformer_tpu.observability.timing import MODEL_SCOPES

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'fixtures', 'v5e_d4_train_1step.json.gz')
ROLES = ('fused_pairwise_conv_bxf', 'fused_pairwise_conv_bwd_a',
         'fused_pairwise_conv_bwd_b')


@pytest.fixture(scope='module')
def step():
    with gzip.open(FIXTURE, 'rt') as fh:
        return json.load(fh)


@pytest.fixture(scope='module')
def red(step):
    return profiling.reduce_events(step)


def test_leaf_seconds_sum_to_busy_seconds(step, red):
    # on the chip's `XLA Ops` line no two operations overlap: exclusive
    # seconds, the union of intervals and the plain sum are one number
    rows = step['device']['/device:TPU:0']
    assert red['busy_s'] == pytest.approx(sum(r[2] for r in rows) * 1e-9)
    assert red['device_s'] == pytest.approx(red['busy_s'])
    assert red['busy_s'] == pytest.approx(0.96315, abs=1e-4)   # one step
    assert sum(red['leaf_s'].values()) + red['unlabelled_s'] \
        == pytest.approx(red['busy_s'])
    assert sum(red['phase_s'].values()) == pytest.approx(red['labelled_s'])
    for leaf, by_phase in red['leaf_phase_s'].items():
        assert leaf in MODEL_SCOPES
        assert sum(by_phase.values()) == pytest.approx(red['leaf_s'][leaf])
    assert red['op_name_source'] == 'metadata_stat:tf_op'


def test_kernel_roles_and_pairs(step, red):
    assert set(red['kernel_s']) == set(ROLES)
    a, b = (red['kernel_s'][r] for r in ROLES[1:])
    # A + B is the backward, as the benchmark's old regex reads it
    bwd = sum(r[2] for r in step['device']['/device:TPU:0']
              if r[0].startswith('fused_pairwise_conv_bwd')) * 1e-9
    assert a + b == pytest.approx(bwd)
    assert a == pytest.approx(0.28992, abs=1e-4)
    assert b == pytest.approx(0.15671, abs=1e-4)
    assert red['kernel_s'][ROLES[0]] == pytest.approx(0.10822, abs=1e-4)
    # every launch sits under its degree pair: 16 pairs a role at degree 4
    for role in ROLES:
        pairs = red['kernel_pair_s'][role]
        assert set(pairs) == {f'{i},{o}' for i in range(4)
                              for o in range(4)}
        assert sum(pairs.values()) == pytest.approx(red['kernel_s'][role])
    # the launches themselves are the leaf `pair`, never `pairwise_layout`
    assert red['leaf_s']['pair'] >= sum(red['kernel_s'].values())


def test_coverage_and_the_unowned_third_as_on_the_chip(red):
    assert red['coverage'] == pytest.approx(0.98326, abs=1e-4)
    top = dict(red['unlabelled_top'])
    # what stays unlabelled: the async pairs the compiler makes
    assert list(top)[:2] == ['copy-done', 'slice-done']
    assert top['copy-done'] == pytest.approx(0.01455, abs=1e-4)
    # the third of the step no kernel owns, by leaf: the basis contraction
    # of the basis-fused kernels' backward, not the wrappers' relayouts
    ms = {k: 1e3 * v for k, v in red['leaf_s'].items()}
    assert ms['basis_contract'] == pytest.approx(269.6, abs=0.5)
    assert red['leaf_phase_s']['basis_contract'].keys() == {'backward'}
    assert ms['gather'] == pytest.approx(42.8, abs=0.5)
    assert ms['pairwise_layout'] == pytest.approx(29.8, abs=0.5)
    assert 1e3 * red['phase_s']['replay'] == pytest.approx(10.0, abs=0.5)
    assert 'replay' not in red['leaf_phase_s']['pair']


def test_the_same_numbers_from_a_file(step, red, tmp_path):
    """Through the reader: the step written as an `.xplane.pb`, found as
    the newest under a directory and reduced."""
    from xplane_fixture import write_xplane
    write_xplane(str(tmp_path / 'plugins' / 'profile' / 'run' /
                     'vm.xplane.pb'), step)
    got = profiling.reduce_xplane(str(tmp_path))
    assert got['source'].endswith('vm.xplane.pb')
    assert got['events'] == red['events'] == 24408
    for key in ('busy_s', 'labelled_s', 'coverage'):
        assert got[key] == pytest.approx(red[key], rel=1e-6)
    assert got['leaf_s'] == pytest.approx(red['leaf_s'], rel=1e-6)
    assert got['kernel_s'] == pytest.approx(red['kernel_s'], rel=1e-6)
    kept = profiling.read_xplane(got['source'], ['step_call', 'loss_fetch'])
    assert sorted(h[1] for h in kept['host']) == ['loss_fetch', 'step_call']
    with pytest.raises(FileNotFoundError):
        profiling.reduce_xplane(str(tmp_path / 'plugins' / 'profile' / 'no'))


def test_a_launch_the_compiler_names_is_filed_under_its_leaf():
    """The TPU's grouped matrix product (`jax.lax.ragged_dot` rewritten into
    Mosaic calls) carries its own name as op_name and no scope: it is the
    expert layer's `moe_experts`, as a scoped operation beside it is; any
    other instruction without a scope stays unlabelled."""
    rows = [['ragged-dot-none.31', 0, 3e6, 'ragged-dot-none', None],
            ['ragged-dot-metadata.2', 3e6, 1e6, 'ragged-dot-metadata', None],
            ['fusion.7', 4e6, 2e6,
             'jit(train_step)/loss/jvp(loss)/blocks_1/moe/moe_experts', None],
            ['copy.12', 6e6, 1e6, None, None],
            ['fusion.9', 7e6, 1e6, 'ragged-dot-like/mul', None]]
    red = profiling.reduce_events({'device': {'/device:TPU:0': rows},
                                   'host': [], 'selector': 'xla_ops'})
    assert red['leaf_s'] == pytest.approx({'moe_experts': 6e-3})
    assert red['unlabelled_s'] == pytest.approx(2e-3)
    assert red['coverage'] == pytest.approx(0.75)
    assert profiling.compiler_launch_leaf('fusion.9') is None


# ------------------------------------------------------------------ #
# what each instruction is and how much it computes (PR 36)
# ------------------------------------------------------------------ #
def test_numeric_stats_are_read_by_the_same_rule_as_strings():
    space = profiling.xspace_class()()
    plane = space.planes.add(name='/device:TPU:0')
    names = {1: 'hlo_category', 2: 'bytes_accessed', 3: 'flops', 4: 'share',
             5: 'tf_op', 6: 'convolution fusion', 7: 'signed', 8: 'raw'}
    meta = plane.event_metadata.add(key=1).value
    for key, (field, value) in {
            1: ('ref_value', 6), 2: ('uint64_value', 4096),
            3: ('uint64_value', 0), 4: ('double_value', 0.25),
            5: ('str_value', 'jit(f)/ff:'), 7: ('int64_value', -3),
            8: ('bytes_value', b'\x08\x01')}.items():
        meta.stats.add(metadata_id=key, **{field: value})
    assert profiling._stat_values(meta.stats, names) == {
        'hlo_category': 'convolution fusion', 'bytes_accessed': 4096,
        'flops': 0, 'share': 0.25, 'tf_op': 'jit(f)/ff:', 'signed': -3,
        'raw': b'\x08\x01'}


HLO_TEXT = '''HloModule jit_f, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,16], param_1.2: bf16[16,32]) -> f32[8,32] {
  %param_0.1 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = bf16[16,32]{1,0:T(8,128)(2,1)} parameter(1)
  %convolution.3 = f32[8,32]{1,0:T(8,128)} convolution(%param_0.1, %param_1.2), dim_labels=bf_io->bf, metadata={op_name="jit(f)/ff/dot_general"}
  ROOT %add.4 = f32[8,32]{1,0:T(8,128)} add(%convolution.3, %convolution.3)
}

%body.5 (p.6: (s32[], f32[2,8,4])) -> (s32[], f32[2,8,4]) {
  %p.6 = (s32[], f32[2,8,4]{2,1,0}) parameter(0)
  %x.7 = f32[2,8,4]{2,1,0} get-tuple-element(%p.6), index=1
  %dot.8 = f32[2,8,8]{2,1,0} dot(%x.7, %x.7), lhs_batch_dims={0}, lhs_contracting_dims={2}, rhs_batch_dims={0}, rhs_contracting_dims={2}
  ROOT %t.9 = (s32[], f32[2,8,4]{2,1,0}) tuple(%x.7, %x.7)
}

ENTRY %main.10 (a: bf16[8,16], b: bf16[16,32], c: f32[2,16,8]) -> f32[8,32] {
  %a = bf16[8,16]{1,0} parameter(0)
  %b = bf16[16,32]{1,0} parameter(1)
  %c = f32[2,16,8]{2,1,0} parameter(2)
  %w = f32[1,8,32]{2,1,0} convolution(%c, %k), window={size=2}, dim_labels=0fb_0io->0bf
  %k = f32[2,16,32]{2,1,0} constant({...})
  %x3 = f32[4,8,6]{2,1,0} constant({...})
  %batched = f32[4,8,8]{2,1,0} convolution(%x3, %x3), window={size=4 stride=3 lhs_dilate=4}, dim_labels=0bf_0oi->0bf
  %u = f32[8,16,1]{2,1,0} constant({...})
  %v = f32[20,4,16]{2,1,0} constant({...})
  %heads = f32[8,20,4]{2,1,0} convolution(%u, %v), window={size=20 pad=19_19 rhs_reversal=1}, dim_labels=bf0_0oi->b0f
  %fusion.11 = f32[8,32]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/ff/dot_general"}
  %while.12 = (s32[], f32[2,8,4]{2,1,0}) while(%init), condition=%cond, body=%body.5
  %launch.13 = (f32[8,32]{1,0}, f32[8]{0}) custom-call(%a, %b), custom_call_target="tpu_custom_call"
  ROOT %copy.14 = f32[8,32]{1,0} copy(%fusion.11)
}
'''


@pytest.mark.parametrize('name,expected', [
    # a fusion's operations are its fused computation's: 2 x 8 x 32 x 16
    ('fusion.11', dict(opcode='fusion', kind='Output', products=1,
                       flops=8192)),
    ('convolution.3', dict(opcode='convolution', kind='', products=1,
                           flops=8192)),
    # a batched dot: 2 x (2 x 8 x 8) x 4
    ('dot.8', dict(opcode='dot', kind='', products=1, flops=1024)),
    # a window that sums over a spatial dimension of 2, the kernel defined
    # below its use: 2 x (8 x 32) x 16 x 2 taps
    ('w', dict(opcode='convolution', kind='', products=1, flops=16384)),
    # as the TPU's compiler writes a dot's batch dimension: of 4 x 4 (tap,
    # position) pairs the stride and the input's dilation leave the
    # diagonal: 2 x 4 x (8 x 8) x 6
    ('batched', dict(opcode='convolution', kind='', products=1,
                     flops=3072)),
    # and a projection per head: 20 taps over an input of one, every
    # output position reached by one, the rest in the padding:
    # 2 x 8 x (20 x 4) x 16
    ('heads', dict(opcode='convolution', kind='', products=1, flops=20480)),
    # a loop counts nothing of its body: the body's events are their own
    ('while.12', dict(opcode='while', kind='', products=0, flops=0)),
    # a custom call's operations are not known here
    ('launch.13', dict(opcode='custom-call', kind='', products=0,
                       flops=None)),
    ('copy.14', dict(opcode='copy', kind='', products=0, flops=0)),
])
def test_the_counting_rule_on_a_stored_module(name, expected):
    counts = profiling.product_counts(
        profiling.hlo_proto_computations(_stored_module().hlo_module))
    assert counts[name] == expected


def test_a_windows_valid_positions_against_the_loop_over_both():
    """The count per tap by interval and residue class, against the plain
    loop over every (tap, output position) pair, on a few thousand small
    windows of every kind the compiler writes."""
    import itertools

    def plain(n_in, n_out, size, stride, pad_low, dilation, in_dilation):
        reach = (n_in - 1) * in_dilation + 1
        at = [o * stride - pad_low + k * dilation
              for k in range(size) for o in range(n_out)]
        return sum(0 <= a < reach and a % in_dilation == 0 for a in at)

    for window in itertools.product((1, 3, 4), (1, 2, 5), (1, 3, 4),
                                    (1, 2, 3), (0, 1, 3), (1, 2),
                                    (1, 2, 4, 6)):
        assert profiling._valid_positions(*window) == plain(*window), window
    # a dot's batch dimension of 32,768, as the TPU's compiler writes it
    assert profiling._valid_positions(32768, 32768, 32768, 32767, 0, 1,
                                      32768) == 32768


def _stored_module():
    """The module of `HLO_TEXT` as the protocol buffer the profiler stores
    (shapes, operand ids, dimension numbers and windows as `hlo.proto` has
    them), serialized and parsed through the declared fields."""
    shapes = {'param_0.1': (8, 16), 'param_1.2': (16, 32),
              'convolution.3': (8, 32), 'add.4': (8, 32), 'p.6': (),
              'x.7': (2, 8, 4), 'dot.8': (2, 8, 8), 't.9': (), 'a': (8, 16),
              'b': (16, 32), 'c': (2, 16, 8), 'w': (1, 8, 32),
              'k': (2, 16, 32), 'x3': (4, 8, 6), 'batched': (4, 8, 8),
              'u': (8, 16, 1), 'v': (20, 4, 16), 'heads': (8, 20, 4),
              'fusion.11': (8, 32), 'while.12': (), 'launch.13': (),
              'copy.14': (8, 32)}
    operands = {'convolution.3': ['param_0.1', 'param_1.2'],
                'dot.8': ['x.7', 'x.7'], 'w': ['c', 'k'],
                'batched': ['x3', 'x3'], 'heads': ['u', 'v']}
    # kernel input feature, input | output spatial dimensions, and per
    # window (size, stride, low padding, kernel dilation, input dilation)
    convolutions = {'convolution.3': (0, [], [], []),
                    'w': (1, [0], [0], [(2, 1, 0, 1, 1)]),
                    'batched': (2, [0], [0], [(4, 3, 0, 1, 4)]),
                    'heads': (2, [2], [1], [(20, 1, 19, 1, 1)])}
    # computation: [(instruction, opcode, fusion kind, called), ...]
    module = {
        'fused_computation.1': [('param_0.1', 'parameter'),
                                ('param_1.2', 'parameter'),
                                ('convolution.3', 'convolution'),
                                ('add.4', 'add')],
        'body.5': [('p.6', 'parameter'), ('x.7', 'get-tuple-element'),
                   ('dot.8', 'dot'), ('t.9', 'tuple')],
        'main.10': [('a', 'parameter'), ('b', 'parameter'),
                    ('c', 'parameter'), ('w', 'convolution'),
                    ('k', 'constant'), ('x3', 'constant'),
                    ('batched', 'convolution'), ('u', 'constant'),
                    ('v', 'constant'), ('heads', 'convolution'),
                    ('fusion.11', 'fusion', 'kOutput',
                     'fused_computation.1'),
                    ('while.12', 'while'), ('launch.13', 'custom-call'),
                    ('copy.14', 'copy')]}
    ids = {name: n + 1 for n, name in enumerate(shapes)}
    proto = profiling.hlo_proto_class()()
    for n, (comp, rows) in enumerate(module.items()):
        c = proto.hlo_module.computations.add(name=comp, id=100 + n)
        for name, opcode, *fused in rows:
            i = c.instructions.add(name=name, opcode=opcode, id=ids[name],
                                   fusion_kind=fused[0] if fused else '')
            i.shape.dimensions.extend(shapes[name])
            i.operand_ids.extend(ids[o] for o in operands.get(name, ()))
            i.called_computation_ids.extend(
                100 + list(module).index(k) for k in fused[1:])
            if name == 'dot.8':
                i.dot_dimension_numbers.lhs_contracting_dimensions.append(2)
            if name in convolutions:
                k_in, spatial_in, spatial_out, windows = convolutions[name]
                numbers = i.convolution_dimension_numbers
                numbers.kernel_input_feature_dimension = k_in
                numbers.input_spatial_dimensions.extend(spatial_in)
                numbers.output_spatial_dimensions.extend(spatial_out)
                for size, stride, low, dilation, in_dilation in windows:
                    i.window.dimensions.add(
                        size=size, stride=stride, padding_low=low,
                        window_dilation=dilation, base_dilation=in_dilation)
    again = profiling.hlo_proto_class()()
    again.ParseFromString(proto.SerializeToString())
    return again


def test_the_stored_module_and_compiled_text_give_the_rule_the_same_form():
    """The reducer reads the module the profiler stores; a reader of
    compiled HLO text (tests/hlo_text_reference.py) is the independent
    check of the fields it declares: instruction for instruction, the same
    form."""
    assert profiling.hlo_proto_computations(_stored_module().hlo_module) \
        == hlo_text_computations(HLO_TEXT)


D_MODEL, D_FF, VOCAB, TOKENS, STEPS = 32, 48, 48, 2 * 64, 2


@pytest.fixture(scope='module')
def cpu_step(tmp_path_factory):
    """Two traced steps of a tiny decoder on the CPU with its compiled HLO
    text: one layer of a gated short convolution and a dense feed-forward,
    each in a rematted block, under a tied head outside any block, the loss
    in two chunks (a loop whose body holds the head's products). XLA:CPU
    keeps these shapes' products as `dot` instructions, each its own event
    or the root of a fusion."""
    import jax
    import optax
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES['lfm2_decoder'](
        hybrid_override_pattern='CF', bf16_operands=False,
        hidden_size=D_MODEL, intermediate_size=D_FF, vocab_rows=VOCAB)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, VOCAB)
    params = module.init(jax.random.PRNGKey(1), tokens)['params']
    optimizer = optax.adam(1e-3)
    state = [params, optimizer.init(params)]
    args = (dict(tokens=tokens), jax.random.PRNGKey(2))
    compiled = make_sharded_train_step(
        make_lm_loss(module, chunk=64), optimizer).lower(
        *state, *args).compile()

    def run():        # the step donates its state
        out = compiled(*state, *args)
        state[:] = out[:2]
        return out

    jax.block_until_ready(run())
    trace_dir = str(tmp_path_factory.mktemp('cpu_step'))
    profiling.capture_step_profile(run, log_dir=trace_dir, steps=STEPS)
    text = compiled.as_text()
    events = profiling.read_xplane(profiling.newest_xplane(trace_dir))
    # the trace brings the module of every program that ran, the step's
    # among them; the same table counted from the step's compiled text is
    # the check
    stripped = {k: v for k, v in events.items()
                if k not in ('instructions', 'flops_source')}
    names, module = profiling.hlo_op_names(text), \
        profiling._hlo_module_name(text)
    step = next(p for p in events['instructions']
                if p.startswith(module + '('))
    by_text = dict(stripped, flops_source='hlo_text', instructions={
        step: profiling.instruction_table(
            profiling.product_counts(hlo_text_computations(text)),
            {name: {} for name in events['instructions'][step]})})
    return dict(
        events=events, step=step,
        with_table=profiling.reduce_xplane(trace_dir, text),
        by_text=profiling.reduce_events(by_text, names, module),
        without=profiling.reduce_events(stripped, names, module))


# forward operations of one step by hand, 2 x tokens x in x out a product
HAND = {
    # [B ; C ; X] = u W_in; the replay repeats it, the backward has dW and du
    'sconv_in': dict(forward=2 * TOKENS * D_MODEL * 3 * D_MODEL, replay=1),
    # the block's last product: nothing in the backward reads its output,
    # so the replay leaves it out
    'sconv_out': dict(forward=2 * TOKENS * D_MODEL * D_MODEL, replay=0),
    # gate, up and down; the replay leaves `down` out for the same reason
    'dense_ff': dict(forward=3 * 2 * TOKENS * D_MODEL * D_FF, replay=2 / 3),
    # outside any block, but the chunked loss recomputes its logits
    'lm_head': dict(forward=2 * TOKENS * D_MODEL * VOCAB, replay=1),
}


@pytest.mark.parametrize('leaf', sorted(HAND))
def test_product_operations_by_leaf_and_phase_equal_the_hand_count(
        cpu_step, leaf):
    red = cpu_step['with_table']
    assert red['flops_source'] == 'hlo_proto'
    forward = STEPS * HAND[leaf]['forward']
    got = red['product_flops'][leaf]
    assert got['forward'] == forward
    assert got['backward'] == 2 * forward
    assert got.get('replay', 0) == HAND[leaf]['replay'] * forward
    assert set(red['product_s'][leaf]) == set(got)
    assert all(v > 0 for v in red['product_s'][leaf].values())


def test_the_module_in_the_trace_and_the_steps_text_count_alike(cpu_step):
    """XLA:CPU's profiler stores each program's module in `/host:metadata`
    as the chip's does, and the reducer counts from there. Counted from the
    step's compiled text instead (tests/hlo_text_reference.py), every table
    is the same."""
    proto, text = cpu_step['with_table'], cpu_step['by_text']
    assert proto['product_flops'] == text['product_flops']
    for key in ('product_s', 'glue_s', 'launch_s', 'leaf_s'):
        assert proto[key] == text[key], key
    # one part of the table a program that ran (here the step alone), keyed
    # as its rows are
    events = cpu_step['events']
    ran = {(r[4], r[0]) for rows in events['device'].values() for r in rows}
    assert {(program, name) for program, rows in
            events['instructions'].items() for name in rows} == ran
    assert all(set(row) == {'category', 'flops', 'bytes', 'products'}
               for rows in events['instructions'].values()
               for row in rows.values())


def test_an_instruction_is_joined_on_its_program_too(cpu_step):
    """An instruction's name is unique in a program, not in a trace: an
    event of another program that bears the name of one of the step's
    products is that program's instruction, and where the table has no row
    for it, glue of an unknown category."""
    events, step = cpu_step['events'], cpu_step['step']
    product = next(name for name, row in events['instructions'][step].items()
                   if row['products'])
    at = 1 + max(r[1] + r[2] for rows in events['device'].values()
                 for r in rows)
    one = {'/host:CPU/late': [[product, at, 1e6, None, 'jit_other(7)']]}
    plain = profiling.reduce_events(events)
    for table in (events['instructions'],
                  dict(events['instructions'],
                       **{'jit_other(7)': {product: dict(
                           category='loop fusion', flops=0, bytes=8,
                           products=0)}})):
        got = profiling.reduce_events(dict(
            events, device=dict(events['device'], **one),
            instructions=table))
        assert got['product_s'] == plain['product_s']
        assert got['product_flops'] == plain['product_flops']
        category = 'loop fusion' if 'jit_other(7)' in table else 'unknown'
        assert got['glue_s'][profiling.UNLABELLED][category] \
            == pytest.approx(
                plain['glue_s'][profiling.UNLABELLED].get(category, 0.0)
                + 1e-3)


def test_only_the_leaves_with_products_have_products(cpu_step):
    red = cpu_step['with_table']
    assert set(red['product_flops']) == set(HAND)
    assert red['launch_s'] == {}         # no custom call on the CPU


def _by_leaf(red):
    """Products + launches + glue, by leaf."""
    whole = dict(red['launch_s'])
    for key in ('product_s', 'glue_s'):
        for leaf, row in red[key].items():
            whole[leaf] = whole.get(leaf, 0.0) + sum(row.values())
    return whole


def test_the_new_tables_sum_to_the_seconds_they_split(cpu_step):
    red = cpu_step['with_table']
    assert _by_leaf(red) == pytest.approx(
        dict(red['leaf_s'], **{profiling.UNLABELLED: red['unlabelled_s']}))
    parts = sum(sum(v.values()) for v in red['product_s'].values()) \
        + sum(sum(v.values()) for v in red['glue_s'].values()) \
        + sum(red['launch_s'].values())
    assert parts == pytest.approx(red['device_s'])
    # a leaf's remainder reads by what kind of instruction it is
    assert 'loop fusion' in red['glue_s']['norm']


def test_existing_keys_equal_a_reduction_without_the_side_table(cpu_step):
    red, plain = cpu_step['with_table'], cpu_step['without']
    new = {'product_s', 'product_flops', 'product_bytes', 'launch_s',
           'launch_roles', 'glue_s', 'flops_source'}
    assert set(red) - {'source'} == set(plain)
    for key in set(plain) - new:
        assert red[key] == plain[key], key
    for key in new - {'flops_source'}:
        assert plain[key] == {}
    assert plain['flops_source'] == 'none'


def test_the_operators_table_has_a_row_a_leaf(cpu_step):
    red = cpu_step['with_table']
    lines = profiling.format_products(red, 197e12, 819e9,
                                      STEPS).splitlines()
    assert lines[0].startswith('leaf') and '%hbm' in lines[0]
    rows = {line.split()[0]: line for line in lines[1:]}
    assert set(rows) == set(_by_leaf(red))
    assert list(rows)[:4] == sorted(
        HAND, key=lambda leaf: -sum(red['product_s'][leaf].values()))
    assert 'loop fusion' in rows['norm']


# one step of `lfm2_a2b_ep8_train_8k` on the v5e (PR 36), cut by
# tests/fixtures/record_v5e_fixture.py with the side table of the
# instructions that ran in it, counted from the module the trace stores
DECODER_FIXTURE = os.path.join(os.path.dirname(FIXTURE),
                               'v5e_lfm2_a2b_ep8_train_8k_1step.json.gz')
TOKENS_8K, D_2048 = 2 * 8192, 2048


@pytest.fixture(scope='module')
def decoder_step():
    with gzip.open(DECODER_FIXTURE, 'rt') as fh:
        return json.load(fh)


@pytest.fixture(scope='module')
def decoder_red(decoder_step):
    return profiling.reduce_events(decoder_step)


# leaf: (forward operations by hand, replay / forward, ms forward | replay |
# backward as the chip ran them)
PINNED = {
    'dense_ff': (3 * 2 * TOKENS_8K * D_2048 * 11776, 2 / 3,
                 (15.625, 10.232, 67.545)),
    'sconv_in': (2 * TOKENS_8K * D_2048 * 3 * D_2048 * 4, 1,
                 (8.748, 9.410, 29.031)),
    'sconv_out': (2 * TOKENS_8K * D_2048 * D_2048 * 4, 0,
                  (4.812, 0.0, 14.677)),
    # q of 32 heads and k, v of 8, heads of 64
    'mha_qkv': (2 * TOKENS_8K * D_2048 * (32 + 2 * 8) * 64, 1,
                (1.238, 1.251, 3.000)),
    'mha_out': (2 * TOKENS_8K * D_2048 * D_2048, 0, (0.739, 0.0, 1.756)),
    'moe_router': (2 * TOKENS_8K * D_2048 * 64 * 4, 1,
                   (0.821, 0.815, 2.434)),
    'lm_head': (2 * TOKENS_8K * D_2048 * 8192, 1, (2.979, 2.888, 6.484)),
}


@pytest.mark.parametrize('leaf', sorted(PINNED))
def test_the_recorded_decoder_steps_product_split(decoder_red, leaf):
    """By leaf and phase: the operations are the hand count to the
    operation (backward twice the forward; the replay repeats every product
    but a block's last, whose output nothing in the backward reads), the
    seconds are the chip's, and no share of the bf16 peak passes 100."""
    forward, replayed, ms = PINNED[leaf]
    flops, secs = (decoder_red[k][leaf]
                   for k in ('product_flops', 'product_s'))
    assert flops['forward'] == forward
    assert flops['backward'] == 2 * forward
    assert flops.get('replay', 0) == pytest.approx(replayed * forward,
                                                   abs=0.5)
    for phase, want in zip(('forward', 'replay', 'backward'), ms):
        assert 1e3 * secs.get(phase, 0.0) == pytest.approx(want, abs=2e-3)
        if want:
            assert flops[phase] / secs[phase] < 197e12
            assert decoder_red['product_bytes'][leaf][phase] > 0


def test_the_recorded_decoder_step_sums_and_its_other_tables(
        decoder_step, decoder_red):
    red = decoder_red
    assert red['flops_source'] == 'hlo_proto'
    assert set(red['product_s']) == set(PINNED)
    products = sum(sum(v.values()) for v in red['product_s'].values())
    glue = sum(sum(v.values()) for v in red['glue_s'].values())
    launches = sum(red['launch_s'].values())
    assert 1e3 * products == pytest.approx(184.485, abs=5e-3)
    assert 1e3 * glue == pytest.approx(130.716, abs=5e-3)
    assert 1e3 * launches == pytest.approx(77.630, abs=5e-3)
    assert products + glue + launches == pytest.approx(red['device_s'])
    assert red['device_s'] == pytest.approx(red['busy_s'])
    # the launches: the streaming attention kernel's three and the
    # compiler's `ragged-dot-*`, under their leaves
    assert 1e3 * red['launch_s']['mha_core'] == pytest.approx(49.530,
                                                              abs=5e-3)
    assert 1e3 * red['launch_s']['moe_experts'] == pytest.approx(28.100,
                                                                 abs=5e-3)
    # the same seconds by what each launch is called, with its events: one
    # forward and two backward launches of the one attention layer
    roles = red['launch_roles']
    assert {leaf: sum(secs for phases in by_role.values()
                      for _, secs in phases.values())
            for leaf, by_role in roles.items()} == pytest.approx(
        red['launch_s'])
    assert sorted((role.split('_block_')[0], phase, n)
                  for role, phases in roles['mha_core'].items()
                  for phase, (n, _) in phases.items()) == [
        ('flash_attention', 'forward', 1),
        ('flash_mha_bwd_dkv', 'backward', 1),
        ('flash_mha_bwd_dq', 'backward', 1)]
    table = profiling.format_launches(red).splitlines()
    assert table[0].startswith('leaf / launch') and len(table) == 1 + sum(
        len(by_role) for by_role in roles.values())
    # a leaf's remainder reads by what the instructions are
    assert 'dense_ff' not in red['glue_s']       # all of it is products
    assert set(red['glue_s']['norm']) == {'loop fusion'}
    assert max(red['glue_s']['moe_dispatch'],
               key=red['glue_s']['moe_dispatch'].get) == 'custom fusion'
    assert _by_leaf(red) == pytest.approx(
        dict(red['leaf_s'], **{profiling.UNLABELLED: red['unlabelled_s']}))
    # the keys a reducer had before are what they are without the table
    plain = profiling.reduce_events(
        {k: v for k, v in decoder_step.items() if k != 'instructions'})
    for key in ('busy_s', 'device_s', 'coverage', 'leaf_s', 'phase_s',
                'leaf_phase_s', 'kernel_s', 'unlabelled_top', 'events'):
        assert red[key] == plain[key], key
    assert red['coverage'] == pytest.approx(0.97927, abs=1e-4)


def test_the_recorded_decoder_step_through_a_file(decoder_step, decoder_red,
                                                  tmp_path):
    """Written as the profiler writes it (stats of each instruction's
    metadata, a module in `/host:metadata`) and read back: the same
    tables."""
    from xplane_fixture import write_xplane
    write_xplane(str(tmp_path / 'plugins' / 'profile' / 'run' /
                     'vm.xplane.pb'), decoder_step)
    got = profiling.reduce_xplane(str(tmp_path))
    assert got['flops_source'] == 'hlo_proto'
    assert got['product_flops'] == decoder_red['product_flops']
    assert got['product_bytes'] == decoder_red['product_bytes']
    for key in ('product_s', 'glue_s'):
        for leaf, row in decoder_red[key].items():
            assert got[key][leaf] == pytest.approx(row, rel=1e-6), key
    assert got['launch_s'] == pytest.approx(decoder_red['launch_s'],
                                            rel=1e-6)


def test_two_programs_of_one_trace_that_share_an_instructions_name(tmp_path):
    """d4's window holds the step, `jit__threefry_split` and `jit__unstack`
    (PR 36), and an id may pass 2**63, which the record's signed key shows
    as negative: each event is its own program's instruction."""
    from xplane_fixture import write_xplane
    step, split = 'jit_train_step(10449458372726318080)', 'jit_split(7)'
    op = 'jit(train_step)/loss/jvp(loss)/dense_ff/dot_general'
    events = {
        'device': {'/device:TPU:0': [
            ['fusion.1', 0.0, 2e6, op, step],
            ['fusion.1', 3e6, 1e6, 'jit(split)/threefry2x32', split],
            ['fusion.2', 5e6, 1e6, None, 'jit_gone(9)']]},
        'host': [],
        'instructions': {
            step: {'fusion.1': dict(category='convolution fusion',
                                    flops=4096, bytes=512, products=1)},
            split: {'fusion.1': dict(category='loop fusion', flops=0,
                                     bytes=16, products=0)}}}
    write_xplane(str(tmp_path / 'plugins' / 'profile' / 'run' /
                     'vm.xplane.pb'), events)
    read = profiling.read_xplane(profiling.newest_xplane(str(tmp_path)))
    assert read['instructions'] == events['instructions']
    # a program the trace stores no record of has no name to go by
    assert [r[4] for r in read['device']['/device:TPU:0']] == [
        step, split, None]
    red = profiling.reduce_events(read)
    assert red['product_s'] == {'dense_ff': {'forward': pytest.approx(2e-3)}}
    assert red['product_flops'] == {'dense_ff': {'forward': 4096}}
    assert red['product_bytes'] == {'dense_ff': {'forward': 512}}
    assert red['glue_s'] == {profiling.UNLABELLED: {
        'loop fusion': pytest.approx(1e-3), 'unknown': pytest.approx(1e-3)}}
