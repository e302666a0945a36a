"""The metrics that read the program's own labels (PR 24): the nine load
through `loader.load_cell`; the two JSON readers run on a reduced form of the
recorded v5e step (tests/fixtures/, one step of d4_onehead_train with each
event's op_name); the `.py` readers run the program's reducer on that step
written back as an `.xplane.pb`, and the program's reduction of a fabricated
compile log; without a trace, or with a program that lacks the reducer, they
return None."""
import gzip
import json
import os
import sys

import pytest

from harness import loader, readers, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, 'tests'))
FIXTURE = os.path.join(ROOT, 'tests', 'fixtures',
                       'v5e_d4_train_1step.json.gz')

TRACE = ['pairwise_bwd_a_ms_per_step.train',
         'pairwise_bwd_b_ms_per_step.train',
         'pairwise_layout_ms_per_step.train', 'replay_ms_per_step.train',
         'attn_core_ms_per_step.train', 'scope_coverage.train']
SETUP = ['setup_step_trace_s.train', 'setup_step_load_s.train',
         'setup_other_compile_s.train']


def _program_side():
    from se3_transformer_tpu.observability import profiling, runtime
    return (hasattr(profiling, 'reduce_xplane')
            and hasattr(runtime, 'compile_seconds')
            and os.path.exists(FIXTURE))


# these files are laid over the parent of PR 24 too, whose program has no
# reducer, no compile log and no recorded step: there the readers read
# nothing, which `test_no_trace_or_no_reducer_reads_as_none` still checks
needs_program = pytest.mark.skipif(
    not _program_side(), reason='the program lacks the reducer, the compile '
    'log or the recorded v5e step')


@pytest.fixture(scope='module')
def cell():
    return loader.load_cell('d4_onehead_train')


@pytest.fixture(scope='module')
def step():
    with gzip.open(FIXTURE, 'rt') as fh:
        return json.load(fh)


@pytest.fixture(scope='module')
def trace_root(step, tmp_path_factory):
    """The recorded step as the profiler would have written it, under a
    checkout's `.bench_out/trace/`."""
    from xplane_fixture import write_xplane
    root = tmp_path_factory.mktemp('bench_out') / 'trace'
    write_xplane(str(root / 'cell-1' / 'plugins' / 'profile' / 'run' /
                     'vm.xplane.pb'), step)
    return str(root)


def _read(cell, name, ctx):
    spec = cell['per_layer'][name]
    return readers.READERS[spec['reader']['source']](spec['reader'], ctx)


def test_the_nine_metrics_load_as_data(cell):
    for name in TRACE + SETUP:
        assert name in cell['per_layer'], name
    assert [cell['per_layer'][n]['reader']['source'] for n in TRACE[:2]] \
        == ['trace_op_regex'] * 2
    for name in TRACE[2:] + SETUP:
        spec = cell['per_layer'][name]['reader']
        assert spec['source'] == 'python' and os.path.exists(spec['path'])
    assert {cell['units'][n] for n in SETUP} == {'s'}
    assert cell['units']['scope_coverage.train'] == '%'
    # appended: what the benchmark had before comes first, unchanged
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    names = [m['name'] for m in bench['per_layer']]
    assert names[:8] == [
        'step_dispatch_ms.train', 'device_ms_per_step.train',
        'kernels_ms_per_step.train', 'pairwise_fwd_ms_per_step.train',
        'pairwise_bwd_ms_per_step.train', 'kernels_roofline.train',
        'device_idle_share.train', 'hbm_reserved_gib.train']
    assert names[8:17] == TRACE + SETUP


@needs_program
def test_json_readers_split_the_backward_by_role(cell, step):
    reduced = {'device': {t: [r[:3] for r in rows]
                          for t, rows in step['device'].items()},
               'host': step['host']}
    lo, hi = step['window_ns']
    summary = trace.reduce(reduced, (hi - lo) * 1e-9)
    ctx = dict(trace=summary, counters={'steps': step['steps']})
    a = _read(cell, 'pairwise_bwd_a_ms_per_step.train', ctx)
    b = _read(cell, 'pairwise_bwd_b_ms_per_step.train', ctx)
    bwd = _read(cell, 'pairwise_bwd_ms_per_step.train', ctx)
    fwd = _read(cell, 'pairwise_fwd_ms_per_step.train', ctx)
    kernels = _read(cell, 'kernels_ms_per_step.train', ctx)
    # the old prefixes read what they read before the launches were named
    assert a + b == pytest.approx(bwd, rel=1e-9)
    assert fwd + bwd == pytest.approx(kernels, rel=1e-9)
    assert a > b > 0
    # 136 launches of each role in a step
    rows = step['device']['/device:TPU:0']
    for role in ('fused_pairwise_conv_bxf', 'fused_pairwise_conv_bwd_a',
                 'fused_pairwise_conv_bwd_b'):
        assert sum(trace.family(r[0]) == role for r in rows) == 136, role
    # a trace of a program without the role names gives nothing to read
    old = {'device': {t: [[r[0].replace('_bwd_a', '_bwd').replace(
        '_bwd_b', '_bwd'), r[1], r[2]] for r in rows]
        for t, rows in reduced['device'].items()}, 'host': []}
    ctx_old = dict(trace=trace.reduce(old, 1.0), counters={'steps': 1})
    assert _read(cell, 'pairwise_bwd_a_ms_per_step.train', ctx_old) is None
    assert _read(cell, 'pairwise_bwd_ms_per_step.train', ctx_old) \
        == pytest.approx(bwd, rel=1e-9)


@needs_program
def test_python_readers_on_the_recorded_step(cell, step, trace_root):
    from se3_transformer_tpu.observability import profiling
    ctx = dict(counters={'steps': step['steps']}, trace_root=trace_root)
    got = {name: _read(cell, name, ctx) for name in TRACE[2:]}
    red = profiling.reduce_events(step)
    per_step = 1e3 / step['steps']
    assert got['pairwise_layout_ms_per_step.train'] == pytest.approx(
        red['leaf_s']['pairwise_layout'] * per_step, rel=1e-6)
    assert got['replay_ms_per_step.train'] == pytest.approx(
        red['phase_s']['replay'] * per_step, rel=1e-6)
    assert got['attn_core_ms_per_step.train'] == pytest.approx(
        red['leaf_s']['attn_core'] * per_step, rel=1e-6)
    # the catch-all `loss` (0.002 ms of this step) is not an owner
    assert got['scope_coverage.train'] == pytest.approx(
        100 * (red['labelled_s'] - red['leaf_s']['loss']) / red['device_s'],
        rel=1e-6)
    assert got['scope_coverage.train'] == pytest.approx(98.3258, abs=1e-3)
    assert 0 < got['replay_ms_per_step.train'] \
        < got['pairwise_layout_ms_per_step.train']
    assert 90.0 < got['scope_coverage.train'] <= 100.0


# ms of the recorded step under each leaf, as the program's reducer and
# closed list file them (PR 24). These four metrics' yardstick lies in the
# program (`profiling.reduce_events`, `scope_leaf`, `scope_phase`,
# `timing.MODEL_SCOPES`): a change there that moves the split of a trace that
# has not changed fails here.
LEAF_MS = {
    'pair': 566.340, 'basis_contract': 269.577, 'gather': 42.748,
    'pairwise_layout': 29.821, 'radial': 8.702, 'attn_qkv': 8.317,
    'attention': 8.142, 'attn_core': 4.401, 'neighbors': 2.494,
    'basis': 2.223, 'optimizer': 2.206, 'trunk': 0.870, 'conv_in': 0.508,
    'ff': 0.353, 'norm': 0.279, 'conv_out': 0.046, 'readout': 0.003,
    'loss': 0.002}
PHASE_MS = {'forward': 152.635, 'backward': 784.377, 'replay': 10.020}


@needs_program
def test_the_programs_split_of_the_recorded_step_is_pinned(step):
    from se3_transformer_tpu.observability import profiling
    red = profiling.reduce_events(step)
    assert set(red['leaf_s']) == set(LEAF_MS)
    for leaf, ms in LEAF_MS.items():
        assert 1e3 * red['leaf_s'][leaf] == pytest.approx(ms, abs=2e-3), leaf
    assert set(red['phase_s']) == set(PHASE_MS)
    for phase, ms in PHASE_MS.items():
        assert 1e3 * red['phase_s'][phase] == pytest.approx(ms, abs=2e-3)
    assert 1e3 * red['unlabelled_s'] == pytest.approx(16.123, abs=2e-3)
    assert red['leaf_phase_s']['pairwise_layout'] == pytest.approx(
        {'forward': 1.31e-3, 'backward': 28.51e-3}, abs=1e-5)
    assert set(red['leaf_phase_s']['basis_contract']) == {'backward'}


@needs_program
def test_a_lost_model_scope_lowers_the_coverage(cell, step, tmp_path):
    """An operation whose model scopes are gone still has a leaf, the
    `loss` that wraps the differentiated model: the program's coverage does
    not see it go, the metric does."""
    from se3_transformer_tpu.observability import profiling
    from xplane_fixture import write_xplane
    lost = dict(step, device={
        t: [r[:3] + ['jit(train_step)/loss/transpose(jvp(M))/mul', r[4]]
            if profiling.scope_leaf(r[3]) == 'gather' else r for r in rows]
        for t, rows in step['device'].items()})
    write_xplane(str(tmp_path / 'trace' / 'vm.xplane.pb'), lost)
    ctx = dict(counters={'steps': step['steps']},
               trace_root=str(tmp_path / 'trace'))
    red = profiling.reduce_events(lost)
    assert red['coverage'] == pytest.approx(0.98326, abs=1e-4)
    assert 1e3 * red['leaf_s']['loss'] == pytest.approx(42.75, abs=0.01)
    assert _read(cell, 'scope_coverage.train', ctx) == pytest.approx(
        98.3258 - 100 * 42.748e-3 / red['device_s'], abs=1e-2)


@needs_program
def test_python_readers_on_a_fabricated_compile_log(cell):
    def e(kind, fun, start, end):
        return dict(kind=kind, fun_name=fun, seconds=end - start,
                    start=start, end=end)
    log = [e('jaxpr_trace', 'fill', 0.0, 2.0),
           e('backend_compile', 'fill', 2.0, 5.0),
           e('jaxpr_trace', 'fused_pairwise_conv_bxf', 11.0, 12.0),
           e('jaxpr_trace', 'train_step', 10.0, 40.0),
           e('lower', 'train_step', 40.0, 60.0),
           e('cache_retrieval', 'train_step', 61.0, 88.0),
           e('backend_compile', 'train_step', 60.0, 88.0),
           e('jaxpr_trace', 'step', 100.0, 150.0)]   # the reference's
    ctx = dict(counters={'steps': 4}, compile_log=log)
    assert _read(cell, 'setup_step_trace_s.train', ctx) \
        == pytest.approx(50.0)
    assert _read(cell, 'setup_step_load_s.train', ctx) == pytest.approx(28.0)
    assert _read(cell, 'setup_other_compile_s.train', ctx) \
        == pytest.approx(5.0)
    # a process in which no `train_step` compiled has nothing to report
    ctx = dict(counters={'steps': 4}, compile_log=log[:2])
    assert all(_read(cell, name, ctx) is None for name in SETUP)


def test_no_trace_or_no_reducer_reads_as_none(cell, tmp_path, monkeypatch):
    ctx = dict(counters={'steps': 4}, trace_root=str(tmp_path))
    assert all(_read(cell, name, ctx) is None for name in TRACE[2:])
    # the parent of the PR that brought these readers: a program whose
    # profiling and runtime modules lack what they call
    from se3_transformer_tpu.observability import profiling, runtime
    monkeypatch.delattr(profiling, 'reduce_xplane', raising=False)
    monkeypatch.delattr(runtime, 'compile_seconds', raising=False)
    assert all(_read(cell, name, dict(counters={'steps': 4})) is None
               for name in TRACE[2:] + SETUP)
    # and `read_all` then leaves them out of the line without raising
    only = dict(cell, per_layer={n: cell['per_layer'][n]
                                 for n in TRACE[2:] + SETUP})
    assert readers.read_all(only, dict(counters={'steps': 4})) == {}


@needs_program
def test_a_reader_never_ends_the_run(cell, trace_root, monkeypatch, capsys):
    """A program whose reducer or log raises, or answers in another shape,
    and a checkout without the helper beside the readers: the metric is left
    out with a line that says why, and `read_all` returns."""
    from se3_transformer_tpu.observability import profiling, runtime
    ctx = dict(counters={'steps': 4}, trace_root=trace_root)
    only = dict(cell, per_layer={n: cell['per_layer'][n]
                                 for n in TRACE[2:] + SETUP})

    def raises(*a, **k):
        raise RuntimeError('not this program')
    monkeypatch.setattr(profiling, 'reduce_xplane', raises)
    monkeypatch.setattr(runtime, 'compile_seconds', lambda *a: {'trace': 1.0})
    assert readers.read_all(only, ctx) == {}
    out = capsys.readouterr().out
    assert out.count('left out, the program gave') == 7
    assert 'RuntimeError: not this program' in out and 'KeyError' in out
    # no helper: the readers load, and read nothing
    monkeypatch.setitem(sys.modules, '_program_profile', None)
    assert readers.read_all(only, ctx) == {}
