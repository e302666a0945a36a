"""Cost/profile attribution layer (PR 6): schema negative cases for the
new `cost`/`profile` record kinds, the cost ledger on a real compiled
CPU program plus the fallback path when `cost_analysis()` returns None,
trace reading + per-leaf attribution on a synthetic `.xplane.pb` (written
with the reader's own message classes, tests/xplane_fixture.py), the
unified `obs_report --require` flag, and the perf gate's pass /
breach / injected-regression behavior on synthetic budgets."""
import json
import os
import sys

import pytest

from se3_transformer_tpu.observability import profiling
from se3_transformer_tpu.observability.costs import (
    cost_payload, hlo_dot_flops,
)
from se3_transformer_tpu.observability.report import write_record_stream
from se3_transformer_tpu.observability.schema import (
    SchemaError, validate_record,
)

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'scripts')


def _cost_body(**over):
    body = dict(kind='cost', run_id='r', label='t', source='cost_analysis',
                flops=1.0, bytes_accessed=2.0,
                memory=dict(argument_bytes=1, output_bytes=2, temp_bytes=3),
                peak_bytes=6,
                collectives={'all-reduce': dict(count=1, bytes=4)})
    body.update(over)
    return body


def _profile_body(**over):
    body = dict(kind='profile', run_id='r', label='t',
                scopes=dict(trunk=dict(time_ms=1.0, share=0.5)),
                device_time_ms=2.0, coverage=0.5)
    body.update(over)
    return body


# --------------------------------------------------------------------- #
# schema: negative cases
# --------------------------------------------------------------------- #
def test_cost_profile_records_validate():
    validate_record(_cost_body())
    validate_record(_profile_body())


@pytest.mark.parametrize('mutation, fragment', [
    (dict(source='guess'), 'source'),
    (dict(memory=dict(argument_bytes=1, output_bytes=2)), 'temp_bytes'),
    (dict(memory=dict(argument_bytes=-1, output_bytes=2, temp_bytes=3)),
     'non-negative'),
    (dict(peak_bytes=-5), 'peak_bytes'),
    (dict(flops=None), 'flops'),           # required numeric under
    #                                        source=cost_analysis
    (dict(collectives={'all-gather': dict(count=1)}), 'bytes'),
    (dict(collectives='lots'), 'object'),
])
def test_cost_schema_negative(mutation, fragment):
    with pytest.raises(SchemaError, match=fragment):
        validate_record(_cost_body(**mutation))


def test_cost_flops_may_be_null_for_fallback_sources():
    validate_record(_cost_body(source='hlo_estimate', flops=None))
    validate_record(_cost_body(source='unavailable', flops=None))


@pytest.mark.parametrize('mutation, fragment', [
    (dict(coverage=1.5), 'coverage'),
    (dict(coverage='high'), 'coverage'),
    (dict(scopes=dict(trunk=dict(time_ms=1.0))), 'share'),
    (dict(scopes=['trunk']), 'object'),
    (dict(device_time_ms=-1.0), 'device_time_ms'),
])
def test_profile_schema_negative(mutation, fragment):
    with pytest.raises(SchemaError, match=fragment):
        validate_record(_profile_body(**mutation))


def test_required_fields_missing():
    for kind, body in (('cost', _cost_body()), ('profile', _profile_body())):
        for field in ('label', 'run_id'):
            bad = dict(body)
            del bad[field]
            with pytest.raises(SchemaError, match='missing'):
                validate_record(bad)


# --------------------------------------------------------------------- #
# cost ledger on a real compiled program + the None-cost_analysis
# fallback (the CPU-backend fallback satellite)
# --------------------------------------------------------------------- #
_HLO_DOT = '''
ENTRY %main {
  %dot.1 = f32[8,16]{1,0} dot(f32[8,32]{1,0} %a, f32[32,16]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %dot.2 = f32[8,8]{1,0} dot(f32[8,16]{1,0} %dot.1, f32[8,16]{1,0} %c), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}
'''


def test_hlo_dot_flops_counts_contractions():
    # 2*8*16*32 + 2*8*8*16 = 8192 + 2048
    assert hlo_dot_flops(_HLO_DOT) == 10240.0


@pytest.fixture(scope='module')
def tiny_compiled():
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return jnp.tanh(x @ y).sum(-1)

    x = jnp.ones((32, 16))
    return jax.jit(f).lower(x, x.T).compile()


def test_cost_payload_real_backend(tiny_compiled):
    body = cost_payload(tiny_compiled, label='tiny')
    validate_record(dict(kind='cost', run_id='r', **body))
    assert body['source'] == 'cost_analysis'
    assert body['flops'] > 0
    assert body['peak_bytes'] > 0
    mem = body['memory']
    assert body['peak_bytes'] == (mem['argument_bytes']
                                  + mem['output_bytes'] + mem['temp_bytes'])


class _NullCostExecutable:
    """A backend whose cost_analysis returns None (some plugin backends
    do) but which still exposes HLO text and memory analysis."""

    def __init__(self, inner):
        self._inner = inner

    def cost_analysis(self):
        return None

    def memory_analysis(self):
        return self._inner.memory_analysis()

    def as_text(self):
        return self._inner.as_text()


def test_cost_payload_falls_back_to_hlo_estimate(tiny_compiled):
    body = cost_payload(_NullCostExecutable(tiny_compiled), label='fb')
    validate_record(dict(kind='cost', run_id='r', **body))
    assert body['source'] == 'hlo_estimate'
    # the dot is 2*32*32*16; elementwise tanh/sum are deliberately
    # uncounted by the fallback
    assert body['flops'] == pytest.approx(2 * 32 * 32 * 16)
    assert body['bytes_accessed'] is None
    assert body['peak_bytes'] > 0


class _DeadCostExecutable:
    """memory_analysis works; cost_analysis AND HLO text do not —
    the source='unavailable' path with honest memory numbers."""

    def __init__(self, inner):
        self._inner = inner

    def cost_analysis(self):
        raise RuntimeError('backend exposes nothing')

    def memory_analysis(self):
        return self._inner.memory_analysis()

    def as_text(self):
        raise RuntimeError('no HLO either')


def test_cost_payload_unavailable_source_keeps_real_memory(tiny_compiled):
    body = cost_payload(_DeadCostExecutable(tiny_compiled), label='dead')
    validate_record(dict(kind='cost', run_id='r', **body))
    assert body['source'] == 'unavailable'
    assert body['flops'] is None
    assert body['peak_bytes'] > 0


def test_cost_payload_refuses_zero_memory_fabrication(tiny_compiled):
    """A backend without memory_analysis must raise, never emit a
    peak_bytes=0 record that passes every memory ceiling vacuously."""

    class _NoMemory:
        def cost_analysis(self):
            return tiny_compiled.cost_analysis()

        def memory_analysis(self):
            return None

        def as_text(self):
            return ''

    with pytest.raises(RuntimeError, match='memory_analysis'):
        cost_payload(_NoMemory(), label='nomem')


# --------------------------------------------------------------------- #
# trace reading + attribution on a synthetic CPU trace (.xplane.pb)
# --------------------------------------------------------------------- #
def _x(name, ts, dur, pid=7, tid=1):
    return dict(ph='X', pid=pid, tid=tid, ts=ts, dur=dur, name=name)


_SYNTH_HLO = '''HloModule jit_f, entry_computation_layout={()->f32[]}
%dot.3 = f32[4,4]{1,0} dot(...), metadata={op_name="jit(f)/jit(main)/trunk/matmul"}
%exp_fusion.clone = f32[4]{0} fusion(...), metadata={op_name="jit(f)/jit(main)/transpose(jvp(attention))/trunk/attention/exp"}
%rsqrt.7 = f32[4]{0} rsqrt(...), metadata={op_name="jit(f)/transpose(jvp(M))/trunk/checkpoint/rematted_computation/ff_block0/ff/prenorm/norm/rsqrt"}
%call.2 = f32[4]{0} call(...), metadata={op_name="jit(f)/jit(main)"}
'''


def test_exclusive_durations_subtract_nested_children():
    events = [
        _x('call.2', 0, 100),          # wraps the fusion: 40 exclusive
        _x('exp_fusion.clone', 10, 60),
        _x('dot.3', 200, 50),
    ]
    excl = {ev['name']: us
            for ev, us in profiling.exclusive_durations(events)}
    assert excl == {'call.2': 40.0, 'exp_fusion.clone': 60.0, 'dot.3': 50.0}


def test_scope_attribution_and_payload(tmp_path):
    """A CPU trace: the device events are those of `/host:CPU` that carry
    an `hlo_op` stat, nested per thread, and the caller's HLO text gives
    each instruction's op_name."""
    from xplane_fixture import write_xplane
    worker = '/host:CPU/tf_XLAEigen'
    events = {'device': {worker: [
        ['call.2', 0.0, 100e3, None, 'jit_f'],
        ['exp_fusion.clone', 10e3, 60e3, None, 'jit_f'],  # attention, bwd
        ['dot.3', 200e3, 50e3, None, 'jit_f'],             # trunk
        ['rsqrt.7', 260e3, 20e3, None, 'jit_f'],           # norm, replay
        ['mystery.9', 300e3, 30e3, None, 'jit_f'],         # unlabelled
        ['dot.3', 400e3, 999e3, None, 'jit_other'],        # another program
    ]}, 'host': [['python', 'step', 0.0, 500e3]]}
    d = str(tmp_path / 'trace')
    write_xplane(os.path.join(d, 'plugins', 'profile', 'run',
                              'host.xplane.pb'), events)

    read = profiling.read_xplane(profiling.newest_xplane(d), ['step'])
    assert read['selector'] == 'hlo_op'
    assert sum(len(v) for v in read['device'].values()) == 6
    assert read['host'] == [['python', 'step', 0.0, 500e3]]

    names = profiling.hlo_op_names(_SYNTH_HLO)
    assert profiling.scope_leaf(names['dot.3']) == 'trunk'
    assert profiling.scope_leaf(names['exp_fusion.clone']) == 'attention'
    assert profiling.scope_leaf(names['call.2']) is None

    body = profiling.profile_payload(d, label='synthetic',
                                     hlo_text=_SYNTH_HLO,
                                     flops_per_step=1e6, steps=2)
    validate_record(dict(kind='profile', run_id='r', **body))
    # exclusive device time of jit_f's events: 40 (call) + 60 + 50 + 20
    # + 30 = 200 us; labelled: 60 (attention) + 50 (trunk) + 20 (norm)
    assert body['device_time_ms'] == pytest.approx(0.2)
    assert body['coverage'] == pytest.approx(130 / 200, abs=1e-3)
    assert body['scopes']['attention']['time_ms'] == pytest.approx(0.06)
    assert body['scopes']['trunk']['share'] == pytest.approx(50 / 200,
                                                             abs=1e-3)
    assert body['phases']['backward']['time_ms'] == pytest.approx(0.06)
    assert body['phases']['replay']['time_ms'] == pytest.approx(0.02)
    assert body['phases']['forward']['time_ms'] == pytest.approx(0.05)
    assert body['unattributed_top'][0]['op'] in ('call', 'mystery')
    assert body['tracks']['op_name_source'] == 'hlo_text'
    assert body['roofline']['device_flops_per_sec'] == pytest.approx(
        2e6 / 200e-6)


def test_innermost_leaf_wins_and_the_path_gives_phase_and_pair():
    leaf, phase, pair = (profiling.scope_leaf, profiling.scope_phase,
                         profiling.scope_pair)
    assert leaf('jit(f)/trunk/attention/mul') == 'attention'
    assert leaf('jit(f)/trunk/pallas_attention/kernel') \
        == 'pallas_attention'
    fwd = ('jit(train_step)/loss/jvp(M)/M._body/trunk/attn_block1/attention/'
           'attn/attn_qkv/to_k/pair_1_2/jit(fused_pairwise_conv_bxf)/')
    assert leaf(fwd + 'fused_pairwise_conv_bxf/pallas_call') == 'pair'
    assert leaf(fwd + 'pairwise_layout/transpose') == 'pairwise_layout'
    assert pair(fwd + 'pairwise_layout/transpose') == '1,2'
    assert pair('jit(f)/conv_in/pair_all_3/dot_general') == 'all,3'
    assert phase(fwd + 'pairwise_layout/transpose') == 'forward'
    # a module that merely starts like a leaf is not one; the step's own
    # scopes catch what the model's do not claim
    assert leaf('jit(train_step)/loss/jvp(M)/pairing/mul') == 'loss'
    assert leaf('jit(train_step)/optimizer/mul') == 'optimizer'
    assert leaf('jit(other)/mul') is None and leaf(None) is None
    bwd = 'jit(train_step)/loss/transpose(jvp(M))/M._body/trunk/checkpoint/'
    assert phase(bwd + 'attn_block1/attention/attn/to_out/dot') == 'backward'
    assert phase(bwd + 'rematted_computation/ff_block0/ff/add') == 'replay'
    # a fusion's metadata may join paths with ';': the first counts
    assert leaf('jit(f)/basis/mul;jit(f)/trunk/add') == 'basis'
    assert profiling.kernel_role('fused_pairwise_conv_bwd_a.17') \
        == 'fused_pairwise_conv_bwd_a'
    assert profiling.kernel_role('fusion.12.clone') is None


# --------------------------------------------------------------------- #
# obs_report: unified --require flag + aliases
# --------------------------------------------------------------------- #
@pytest.fixture(scope='module')
def scripts_path():
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(SCRIPTS)
    yield
    mp.undo()


def _stream(path, bodies):
    write_record_stream(str(path), 'testrun', bodies)
    return str(path)


def test_obs_report_require_cost_profile(tmp_path, scripts_path, capsys):
    import obs_report
    good = _stream(tmp_path / 'good.jsonl',
                   [{k: v for k, v in _cost_body().items()
                     if k != 'run_id'},
                    {k: v for k, v in _profile_body().items()
                     if k != 'run_id'}])
    assert obs_report.main([good, '--validate',
                            '--require', 'cost,profile']) == 0
    # a zero-peak ledger fails the cost gate
    empty = _stream(tmp_path / 'empty.jsonl',
                    [{k: v for k, v in
                      _cost_body(peak_bytes=0).items() if k != 'run_id'}])
    assert obs_report.main([empty, '--require', 'cost']) == 1
    # profile gate needs a profile record
    assert obs_report.main([good, '--require', 'tune']) == 1
    assert obs_report.main([good, '--require', 'nonsense']) == 2
    capsys.readouterr()


def test_obs_report_require_comm(tmp_path, scripts_path, capsys):
    import obs_report

    def comm(name, **over):
        body = dict(
            kind='comm', sp=2, ring_steps=2, overlap=True, exchange=True,
            collectives={}, full_width_all_gathers=[],
            all_gather_free=True)
        body.update(over)
        return _stream(tmp_path / name, [body])

    assert obs_report.main([comm('clean.jsonl'), '--require', 'comm']) == 0
    # an exchange arm that still gathers full width fails the gate, and
    # so does a stream whose only arm is the dense control
    assert obs_report.main([
        comm('dirty.jsonl', all_gather_free=False,
             full_width_all_gathers=['f32[1,64,8]']),
        '--require', 'comm']) == 1
    assert obs_report.main([comm('dense.jsonl', exchange=False),
                            '--require', 'comm']) == 1
    capsys.readouterr()


# --------------------------------------------------------------------- #
# perf gate: pass, breach, injection, missing semantics
# --------------------------------------------------------------------- #
@pytest.fixture()
def gate(tmp_path, scripts_path):
    import perf_gate

    budgets = dict(version=1, default_margin=0.1, budgets=[
        dict(name='admissions_floor', kind='serve',
             match={'label': 'toy'}, field='continuous_admissions',
             min=100.0),
        dict(name='mem_ceiling', kind='cost',
             match={'label': 'toy'}, field='peak_bytes',
             max=1000, margin=0.2),
        dict(name='ag_free', kind='comm', match={'exchange': True},
             field='all_gather_free', equals=True, axis='sp'),
        dict(name='absent_coll', kind='comm', match={'exchange': True},
             field='collectives.all-gather.bytes', max=10,
             missing='zero'),
    ])
    bpath = tmp_path / 'budgets.json'
    bpath.write_text(json.dumps(budgets))

    def run(records, extra=()):
        rpath = tmp_path / 'records.jsonl'
        with open(rpath, 'w') as f:
            for r in records:
                f.write(json.dumps(r) + '\n')
        return perf_gate.main([str(rpath), '--budgets', str(bpath),
                               *extra])

    return run


GOOD = [
    dict(kind='serve', label='toy(run)', continuous_admissions=150.0),
    dict(kind='cost', label='toy', peak_bytes=900),
    dict(kind='comm', exchange=True, all_gather_free=True,
         collectives={}),
]


def test_perf_gate_passes_within_margins(gate, capsys):
    assert gate(GOOD) == 0
    out = capsys.readouterr().out
    assert out.count('[ ok ]') == 4 and 'REGRESSION' not in out


def test_perf_gate_fails_on_breach_and_names_it(gate, capsys):
    bad = GOOD + [dict(kind='cost', label='toy', peak_bytes=5000)]
    assert gate(bad) == 1
    out = capsys.readouterr().out
    assert '[FAIL] mem_ceiling' in out and 'ceiling 1200' in out


def test_perf_gate_latest_record_wins(gate, capsys):
    # an old breach followed by a healthy record passes: streams are
    # chronological and the gate judges the latest evidence
    healed = [dict(kind='cost', label='toy', peak_bytes=5000)] + GOOD
    assert gate(healed) == 0
    capsys.readouterr()


def test_perf_gate_margin_is_applied(gate, capsys):
    # min 100 at margin 10% -> floor 90
    edge = [dict(GOOD[0], continuous_admissions=91.0)] + GOOD[1:]
    assert gate(edge) == 0
    below = [dict(GOOD[0], continuous_admissions=89.0)] + GOOD[1:]
    assert gate(below) == 1
    capsys.readouterr()


def test_perf_gate_injection_fires_every_budget(gate, capsys):
    assert gate(GOOD, extra=('--inject-regression',)) == 1
    capsys.readouterr()


def test_perf_gate_skip_vs_strict(gate, capsys):
    only_serve = [GOOD[0]]
    assert gate(only_serve) == 0                       # others skip
    assert gate(only_serve, extra=('--strict',)) == 1  # skips fail
    out = capsys.readouterr().out
    assert '[SKIP]' in out


def test_perf_gate_equals_and_missing_zero(gate, capsys):
    dirty = GOOD[:2] + [dict(kind='comm', exchange=True,
                             all_gather_free=False, collectives={})]
    assert gate(dirty) == 1
    out = capsys.readouterr().out
    assert '[FAIL] ag_free' in out and '[axis=sp]' in out
    # absent collective class counts as 0 bytes under missing: zero
    assert '[ ok ] absent_coll' in out


def test_perf_gate_group_by_judges_every_axis_point(tmp_path,
                                                    scripts_path, capsys):
    """A clean final sweep point must not mask a regression at an
    earlier axis value: group_by judges the latest record PER sp."""
    import perf_gate
    budgets = dict(version=1, budgets=[dict(
        name='ag_free_all_sp', kind='comm', match={'exchange': True},
        field='all_gather_free', equals=True, axis='sp',
        group_by='sp')])
    bpath = tmp_path / 'b.json'
    bpath.write_text(json.dumps(budgets))

    def run(records):
        rpath = tmp_path / 'r.jsonl'
        with open(rpath, 'w') as f:
            for r in records:
                f.write(json.dumps(r) + '\n')
        return perf_gate.main([str(rpath), '--budgets', str(bpath)])

    def comm(sp, clean):
        return dict(kind='comm', exchange=True, sp=sp,
                    all_gather_free=clean, collectives={})

    # sp=2 latest record dirty, sp=8 clean and LAST in the stream
    assert run([comm(2, True), comm(2, False), comm(8, True)]) == 1
    out = capsys.readouterr().out
    assert 'sp-groups breach' in out
    # a healed sp=2 row later in the stream clears its group
    assert run([comm(2, False), comm(2, True), comm(8, True)]) == 0
    capsys.readouterr()


def test_perf_gate_group_by_multi_key_no_cross_point_masking(
        tmp_path, scripts_path, capsys):
    """Comma-separated group_by keys one group per MESH POINT: a clean
    (2,2,2) row must not mask a regressed (4,1,2) row, even though the
    two share every individual axis value with some clean row. Grouped
    by any single axis this stream would pass — the regressed point's
    sp=1 is shadowed only when the full (dp,sp,tp) tuple is the key."""
    import perf_gate
    budgets = dict(version=1, budgets=[dict(
        name='mesh_ag_free_every_point', kind='mesh_sweep',
        field='comm.all_gather_free', equals=True,
        group_by='dp,sp,tp')])
    bpath = tmp_path / 'b.json'
    bpath.write_text(json.dumps(budgets))

    def run(records):
        rpath = tmp_path / 'r.jsonl'
        with open(rpath, 'w') as f:
            for r in records:
                f.write(json.dumps(r) + '\n')
        return perf_gate.main([str(rpath), '--budgets', str(bpath)])

    def row(dp, sp, tp, clean):
        return dict(kind='mesh_sweep', dp=dp, sp=sp, tp=tp,
                    comm=dict(all_gather_free=clean))

    dirty_412 = [row(4, 1, 2, False), row(2, 2, 2, True),
                 row(4, 2, 1, True), row(1, 2, 4, True)]
    assert run(dirty_412) == 1
    out = capsys.readouterr().out
    assert 'dp,sp,tp-groups breach' in out and "('4', '1', '2')" in out

    # the same stream with a LATER healed (4,1,2) row clears its group
    assert run(dirty_412 + [row(4, 1, 2, True)]) == 0

    # single-key grouping on sp WOULD mask it: (1,2,4)'s sp=2 row is
    # latest for sp=2 and (4,1,2)'s dirty sp=1... still caught; but
    # grouped by dp alone the clean (4,2,1) shadows dirty (4,1,2) —
    # the exact masking the multi-key form exists to prevent
    budgets['budgets'][0]['group_by'] = 'dp'
    bpath.write_text(json.dumps(budgets))
    assert run(dirty_412) == 0
    capsys.readouterr()


def test_perf_gate_committed_budgets_are_loadable(scripts_path):
    # the committed PERF_BUDGETS.json must stay structurally valid:
    # every budget names a kind, a field, and exactly one constraint
    root = os.path.dirname(SCRIPTS)
    with open(os.path.join(root, 'PERF_BUDGETS.json')) as f:
        spec = json.load(f)
    assert spec['budgets'], 'no budgets committed'
    for b in spec['budgets']:
        assert b.get('name') and b.get('kind') and b.get('field')
        assert sum(k in b for k in ('min', 'max', 'equals')) == 1


ROOT = os.path.dirname(SCRIPTS)
# the root's record files, each banked by a smoke and judged by a
# committed budget: scripts/perf_gate.py's DEFAULT_RECORDS
COMMITTED_RECORDS = (
    'WIDTH_TABLE.jsonl', 'SERVE_MULTI.jsonl', 'CHAOS_SMOKE.jsonl',
    'TRAIN_CHAOS.jsonl', 'FLEET_CHAOS.jsonl', 'SLO_SMOKE.jsonl',
    'ASSEMBLY_SWEEP.jsonl', 'MESH_SWEEP.jsonl', 'TRANSPORT_AB.jsonl')


def _gate_lines(perf_gate, capsys, paths):
    """(rc, {budget name: 'ok' | 'FAIL' | 'SKIP'}) of the committed
    budgets over `paths`."""
    import re
    capsys.readouterr()
    rc = perf_gate.main(list(paths))
    verdicts = {name: tag for tag, name in re.findall(
        r'^\[ *(\w+) *\] ([^:]+):', capsys.readouterr().out, re.M)}
    return rc, verdicts


@pytest.mark.parametrize('name', COMMITTED_RECORDS)
def test_committed_record_passes_committed_budgets(name, scripts_path,
                                                   capsys):
    import perf_gate
    rc, verdicts = _gate_lines(perf_gate, capsys,
                               [os.path.join(ROOT, name)])
    assert rc == 0, verdicts
    assert 'ok' in verdicts.values(), f'{name}: no budget judged'


def test_every_committed_budget_is_judged_by_a_committed_record(
        scripts_path, capsys):
    import perf_gate
    assert set(perf_gate.DEFAULT_RECORDS) == set(COMMITTED_RECORDS)
    rc, verdicts = _gate_lines(
        perf_gate, capsys,
        [os.path.join(ROOT, name) for name in COMMITTED_RECORDS])
    assert rc == 0, verdicts
    with open(perf_gate.DEFAULT_BUDGETS) as f:
        kinds = {b['name']: b['kind'] for b in json.load(f)['budgets']}
    assert set(verdicts) == set(kinds)
    # judged on a fresh record, not a banked one: `cost` by `make
    # perf-gate` (--fresh-cost); `comm` is what `make ring-smoke`
    # streams, and no committed file holds one
    skipped = {kinds[n] for n, tag in verdicts.items() if tag == 'SKIP'}
    assert skipped == {'cost', 'comm'}, verdicts


# --------------------------------------------------------------------- #
# trainer cost record (the training-step-factory wiring)
# --------------------------------------------------------------------- #
@pytest.mark.heavy
def test_trainer_cost_record_schema_and_peak(tmp_path):
    from se3_transformer_tpu.observability import MetricLogger
    from se3_transformer_tpu.observability.schema import validate_stream
    from se3_transformer_tpu.training.denoise import (
        DenoiseConfig, DenoiseTrainer, synthetic_protein_batch,
    )
    cfg = DenoiseConfig(num_nodes=24, accum_steps=1, num_degrees=2)
    trainer = DenoiseTrainer(cfg)
    batch = synthetic_protein_batch(cfg, trainer.np_rng)
    trainer.init(batch)
    path = str(tmp_path / 'cost.jsonl')
    with MetricLogger(path, mirror=None) as logger:
        rec = trainer.cost_record(batch, metric_logger=logger)
    assert rec['kind'] == 'cost'
    assert rec['peak_bytes'] > 0
    assert rec['memory']['temp_bytes'] > 0
    assert rec['label'].startswith('denoise,')
    info = validate_stream(path)
    assert info['kinds']['cost'] == 1
