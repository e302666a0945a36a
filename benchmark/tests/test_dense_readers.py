"""The four readers of XLA's dense products (PR 36) and their helper: on a
fabricated step whose instructions carry the reducer's side table, on the
recorded cut of one v5e step of the short-convolution cell, beside a
reducer without the new keys (the parent's), and on other cells' contexts.
Not tier-1."""
import gzip
import json
import os
import sys

import pytest

from harness import loader, readers

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, 'tests'))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_lm_readers import _write  # noqa: E402

CELLS = ['glm47_flash_ep8_train_8k', 'nemotron_twotower_ep16_train_8k',
         'lfm2_a2b_ep8_train_8k']
NEW = ['dense_products_ms_per_step.train', 'dense_products_peak_share.train',
       'dense_products_bwd_peak_share.train', 'xla_glue_ms_per_step.train']
PEAKS = {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9}
STEP = 'jit_train_step(11)'      # the program, as the trace's record names it
RECORDED = os.path.join(ROOT, 'tests', 'fixtures',
                        'v5e_lfm2_a2b_ep8_train_8k_1step.json.gz')
# what the four read on that step (every product there is dense: the cores
# hold none that XLA compiled)
RECORDED_READS = {'dense_products_ms_per_step.train': 184.4849,
                  'dense_products_peak_share.train': 56.2522,
                  'dense_products_bwd_peak_share.train': 44.5369,
                  'xla_glue_ms_per_step.train': 130.7163}


def test_the_four_are_appended_for_the_three_decoder_cells_alone():
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    assert [m['name'] for m in bench['per_layer'][-4:]] == NEW
    for m in bench['per_layer'][-4:]:
        assert m['workloads'] == CELLS
        assert (m['moves'], m['source']) == ('train_node_steps_per_s',
                                             'device_trace')
    assert [m['layer'] for m in bench['per_layer'][-4:]] == [
        'kernels', 'kernels', 'kernels', 'model']
    for cell in CELLS:
        assert set(NEW) <= set(loader.load_cell(cell)['per_layer'])
    assert not set(NEW) & set(loader.load_cell('d4_onehead_train')
                              ['per_layer'])


def test_new_files_import_nothing_of_the_program_at_module_level():
    for name in NEW + ['_dense_products']:
        path = os.path.join(BENCH, 'layer_metrics', name + '.py')
        for line in open(path):
            if line.startswith(('import ', 'from ')):
                assert 'se3_transformer_tpu' not in line, (path, line)


def _step():
    """A fabricated device track, 1 ms an event: a dense feed-forward's `up`
    forward, replayed and backward (dW and dx in one fusion of two
    products), a product under a core, a launch, glue with and without a
    leaf."""
    base = 'jit(train_step)/loss/'
    block = 'HybridDecoder.hidden_states/checkpoint/blocks_1/'
    up = 'dense_ff/mlp/up/dot_general'
    rows = [
        ['fusion.1', base + 'jvp(loss)/' + block + up],
        ['fusion.2', base + 'transpose(jvp(loss))/' + block
         + 'rematted_computation/' + up],
        ['multiply_reduce_fusion.3',
         base + 'transpose(jvp(loss))/' + block + up],
        ['fusion.4', base + 'jvp(loss)/' + block
         + 'conv/sconv_core/conv/mul'],
        ['flash_attention.5', base + 'jvp(loss)/' + block
         + 'attn/mha_core/jit(flash_attention)/pallas_call'],
        ['fusion.6', base + 'jvp(loss)/' + block + 'pre_norm/mul'],
        ['copy.7', None],
        ['convolution_add_fusion.8', base + 'jvp(loss)/lm_head/dot_general'],
    ]
    product = dict(category='convolution fusion', bytes=1000, products=1)
    table = {
        'fusion.1': dict(product, flops=int(100e9)),
        'fusion.2': dict(product, flops=int(100e9)),
        'multiply_reduce_fusion.3': dict(product, flops=int(200e9),
                                         products=2),
        'fusion.4': dict(product, flops=int(50e9)),
        'flash_attention.5': dict(category='custom-call', flops=None,
                                  bytes=10, products=0),
        'fusion.6': dict(category='loop fusion', flops=0, bytes=10,
                         products=0),
        'copy.7': dict(category='data formatting', flops=0, bytes=10,
                       products=0),
        'convolution_add_fusion.8': dict(product, flops=int(150e9)),
    }
    return {'device': {'/device:TPU:0': [
        [name, 1e6 * i, 1e6, op, STEP] for i, (name, op) in enumerate(rows)]},
        'host': [], 'selector': 'xla_ops',
        'op_name_source': 'metadata_stat:tf_op',
        'instructions': {STEP: table}}


def _ctx(cell, trace_root, steps=2):
    return dict(counters=dict(steps=steps), traffic=cell['traffic'],
                model=cell['config']['model'], peaks=PEAKS,
                trace={'busy_s': 0.008, 'window_s': 1.0, 'op_seconds': {}},
                trace_root=trace_root)


def _only(cell):
    return dict(cell, per_layer={n: cell['per_layer'][n] for n in NEW})


@pytest.mark.parametrize('name', CELLS)
def test_the_four_readers_on_a_step_with_the_side_table(
        tmp_path, name, capsys):
    cell = loader.load_cell(name)
    got = readers.read_all(_only(cell), _ctx(
        cell, _write(_step(), str(tmp_path / 'trace'))))
    assert set(got) == set(NEW)
    # the dense products: `up` three times and the head, not the core's
    assert got['dense_products_ms_per_step.train'] == pytest.approx(4 / 2)
    assert got['dense_products_peak_share.train'] == pytest.approx(
        100 * (100e9 + 100e9 + 200e9 + 150e9) / 4e-3 / 197e12)
    assert got['dense_products_bwd_peak_share.train'] == pytest.approx(
        100 * 200e9 / 1e-3 / 197e12)
    # the norm's pass and the copy: neither product nor launch
    assert got['xla_glue_ms_per_step.train'] == pytest.approx(2 / 2)
    out = capsys.readouterr().out
    assert out.count('products by leaf and phase') == 1     # once a trace
    assert 'operations from hlo_proto' in out
    assert 'device seconds 0.008000 = products 0.005000 + launches ' \
           '0.001000 + glue 0.002000' in out
    assert 'by harness/' in out and 'no hand count' not in out
    assert 'left out' not in out


def test_on_the_recorded_step_of_the_short_convolution_cell(tmp_path, capsys):
    """One v5e step of `lfm2_a2b_ep8_train_8k` as the chip's profiler wrote
    it (tests/fixtures/record_v5e_fixture.py, PR 36): what the readers give
    for it, the sum, and no share over 100."""
    with gzip.open(RECORDED, 'rt') as fh:
        recorded = json.load(fh)
    cell = loader.load_cell('lfm2_a2b_ep8_train_8k')
    ctx = _ctx(cell, _write(recorded, str(tmp_path / 'trace')), steps=1)
    got = readers.read_all(_only(cell), ctx)
    assert set(got) == set(NEW)
    assert got == pytest.approx(RECORDED_READS, rel=1e-5)
    assert got['dense_products_peak_share.train'] < 100
    assert got['dense_products_bwd_peak_share.train'] < 100
    import _dense_products as dp    # its directory is on the path by now
    red = dp.lm.profile(ctx, dp.__file__)
    whole = dp.total(red['product_s']) + dp.total(red['glue_s']) \
        + dp.total(red['launch_s'])
    assert whole == pytest.approx(red['device_s'], rel=1e-9)
    assert red['device_s'] == pytest.approx(red['busy_s'], rel=1e-6)
    assert 'left out' not in capsys.readouterr().out


@pytest.mark.parametrize('name', CELLS + ['d4_onehead_train'])
def test_none_beside_a_reducer_without_the_new_keys(
        tmp_path, name, capsys, monkeypatch):
    """The driver lays these files over the parent's program, whose
    `reduce_events` returns no product tables and whose `read_xplane` no
    side table: every reader gives nothing, raises nothing, and the helper
    prints nothing."""
    from se3_transformer_tpu.observability import profiling
    new_keys = ('product_s', 'product_flops', 'product_bytes', 'launch_s',
                'glue_s', 'flops_source')
    real = profiling.reduce_events

    def parents(events, *args, **kwargs):
        return {k: v for k, v in real(events, *args, **kwargs).items()
                if k not in new_keys}
    monkeypatch.setattr(profiling, 'reduce_events', parents)
    monkeypatch.delattr(profiling, 'format_products')
    decoder = loader.load_cell(CELLS[-1])
    cell = dict(loader.load_cell(name), per_layer=_only(decoder)['per_layer'])
    ctx = _ctx(cell, _write(_step(), str(tmp_path / 'trace')))
    assert readers.read_all(cell, ctx) == {}
    out = capsys.readouterr().out
    assert 'left out' not in out and 'products by leaf' not in out
    ctx['trace_root'] = str(tmp_path / 'nothing')     # and without a trace
    assert readers.read_all(cell, ctx) == {}


def test_a_trace_without_a_stored_module_reads_nothing(tmp_path, capsys):
    step = {k: v for k, v in _step().items() if k != 'instructions'}
    cell = loader.load_cell(CELLS[0])
    ctx = _ctx(cell, _write(step, str(tmp_path / 'trace')))
    assert readers.read_all(_only(cell), ctx) == {}
    assert 'left out' not in capsys.readouterr().out
