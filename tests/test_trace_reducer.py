"""The trace reducer on one step of this repo's own v5e trace
(`benchmark/run.py --workload d4_onehead_train --trace 1`, PR 24), cut by
tests/fixtures/record_v5e_fixture.py with each event's op_name as the chip's
profiler wrote it."""
import gzip
import json
import os

import pytest

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
