"""The trace reducer on a recorded v5e trace: two training steps (the
dim_head-8, depth-6 configuration of PR 23's first session) cut from a
`--trace 1` run on the chip, in `extract`'s own form."""
import gzip
import json
import os

import pytest

from harness import readers, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data',
                    'v5e_flagship_train_2steps.json.gz')


@pytest.fixture(scope='module')
def events():
    with gzip.open(DATA, 'rt') as fh:
        return json.load(fh)


@pytest.fixture(scope='module')
def summary(events):
    lo, hi = events['window_ns']
    return trace.reduce(events, (hi - lo) * 1e-9)


def test_names():
    hlo = ('%fused_pairwise_conv_bwd.17 = (f32[7,448,16384]{2,1,0:T(8,128)}, '
           'f32[28672,128]{1,0}) custom-call(f32[128,16384] %x)')
    assert trace.short_name(hlo) == 'fused_pairwise_conv_bwd.17'
    assert trace.family('fused_pairwise_conv_bwd.17') \
        == 'fused_pairwise_conv_bwd'
    assert trace.family('fusion.12.clone.3') == 'fusion.12.clone'
    assert trace.family('copy') == 'copy'


def test_busy_union_and_idle_share(events, summary):
    evs = events['device']['/device:TPU:0']
    merged = trace.union_intervals(evs)
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))
    busy = sum(e - s for s, e in merged) * 1e-9
    assert busy == pytest.approx(summary['busy_s'])
    # on this chip's `XLA Ops` line no two operations overlap
    assert busy == pytest.approx(sum(e[2] for e in evs) * 1e-9)
    assert summary['busy_s'] == pytest.approx(3.8056, abs=2e-3)
    idle = 100 * (1 - summary['busy_s'] / summary['window_s'])
    assert 1.5 < idle < 2.5
    # the two steps, 1.9 s each, nearly all of it busy
    assert summary['window_s'] == pytest.approx(3.8837, abs=1e-3)


def test_mosaic_call_selection(events, summary):
    evs = events['device']['/device:TPU:0']
    calls = [e for e in evs if e[0].startswith('fused_')]
    # 600 Pallas custom calls a step: 200 forward (bxf), 400 backward
    assert len(calls) == 2 * 600
    assert sum(e[0].startswith('fused_pairwise_conv_bxf') for e in calls) \
        == 2 * 200
    kernels = readers.matched_seconds(summary, '^fused_')
    assert kernels == pytest.approx(2.596, abs=2e-3)
    fwd = readers.matched_seconds(summary, '^fused_pairwise_conv_bxf')
    bwd = readers.matched_seconds(summary, '^fused_pairwise_conv_bwd')
    assert fwd + bwd == pytest.approx(kernels)
    assert bwd > 2.5 * fwd          # the backward kernels are the bulk
    # exclusive times add up to no more than the busy time
    assert sum(summary['op_seconds'].values()) <= summary['busy_s'] * 1.001


def test_nested_and_overlapping_events_are_not_counted_twice():
    evs = [['while.1', 0.0, 100.0], ['fusion.1', 10.0, 30.0],
           ['fusion.2', 50.0, 40.0], ['copy.3', 95.0, 25.0],
           ['fusion.9', 200.0, 10.0]]
    assert trace.union_intervals(evs) == [[0.0, 120.0], [200.0, 210.0]]
    ex = trace.exclusive_seconds(evs)
    assert ex['while'] == pytest.approx(5e-9)       # 100 - 30 - 40 - 25
    assert ex['fusion'] == pytest.approx(80e-9)
    host = [['python3', 'step_call', 100.0, 150.0],
            ['python3', 'loss_fetch', 150.0, 20.0]]
    merged = [[0.0, 120.0], [200.0, 210.0]]
    trace_gaps = trace.idle_gaps(merged, host)
    # an 80 ns gap is launch overhead, not the host's doing
    assert trace_gaps == {'between_ops': pytest.approx(80e-9)}
    merged = [[0.0, 120.0], [200e3, 210e3]]
    host = [['python3', 'step_call', 50e3, 150e3],
            ['python3', 'loss_fetch', 90e3, 20e3]]
    assert list(trace.idle_gaps(merged, host)) == ['loss_fetch']


def test_breakdown_and_readers(summary):
    ops = summary['breakdown']['device_ops']
    assert [n for n, _ in ops[:2]] == ['fused_pairwise_conv_bwd',
                                       'fused_pairwise_conv_bxf']
    assert len(ops) <= 10 and len(summary['breakdown']['idle_gaps']) <= 10
    gaps = dict(summary['breakdown']['idle_gaps'])
    assert set(gaps) <= set(trace.HOST_SPANS) | {'no_span', 'between_ops'}
    ctx = dict(trace=summary, counters={'steps': 2}, spans={},
               memory_stats={}, model=dict(
                   dim=64, depth=6, num_degrees=4, heads=8, dim_head=8,
                   num_neighbors=32, output_degrees=2),
               peaks={'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9},
               shapes_run=[dict(nodes=1024, times=2, backward=True)])
    per_step = readers.READERS['trace_op_regex'](
        dict(regex='^fused_', arith='per_unit_ms', unit='steps'), ctx)
    assert per_step == pytest.approx(1298.0, abs=1.0)
    share = readers.READERS['trace_op_regex'](
        dict(regex='^fused_', arith='roofline_share', flops='kernel_flops',
             bytes='kernel_bytes'), ctx)
    assert 20.0 < share < 25.0          # and never above 100
    # a reader with nothing to read returns nothing
    assert readers.READERS['trace_op_regex'](
        dict(regex='^no_such_kernel', arith='per_unit_ms', unit='steps'),
        ctx) is None
    assert readers.READERS['host_span'](
        dict(span='queue_wait', arith='p50_ms'), ctx) is None
