"""The files of the decoder that mixes global and sliding-window attention
layers beside a program that lacks it, and its four readers on a trace that
has its leaves.

The driver lays this benchmark over the parent's checkout too: with the
program's recipe hidden, the new cell's entry ends at once in one line; every
new reader gives nothing, without raising, on a context of the d4, the GLM,
the hybrid, the short-convolution and the block-diffusion cell; on a
fabricated step with the new leaves each reads what its name says, and the
shared readers the cell's name was appended to read it rightly."""
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
from test_hybrid_readers import _hybrid_step  # noqa: E402
from test_lfm2_readers import _lfm2_step  # noqa: E402
from test_lm_readers import (  # noqa: E402
    FIXTURE, SHARED, _decoder_step, _write,
)
from test_sdar_readers import _sdar_step  # noqa: E402

NEW_CELL = 'smallthinker_a3b_swa_train_16k'
NEW_METRICS = ['swa_core_ms_per_step.train', 'swa_core_roofline.train',
               'mha28_core_roofline.train', 'smallthinker_step_mfu.train']
# accepted readers that go by leaf, counter or the reducer's tables alone
TAKEN = {'moe_experts_ms_per_step.train', 'moe_route_ms_per_step.train',
         'expert_load_max_over_mean.train',
         'dense_products_ms_per_step.train',
         'dense_products_peak_share.train',
         'dense_products_bwd_peak_share.train', 'xla_glue_ms_per_step.train'}
PEAKS = {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9}


@pytest.fixture(scope='module')
def step():
    with gzip.open(FIXTURE, 'rt') as fh:
        return json.load(fh)


def test_the_new_cell_reads_its_metrics_and_no_other_cell_does():
    """Subset checks (ROADMAP B1 (f)): what this file names, and nothing
    about cells or metrics a later PR adds."""
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    assert set(NEW_METRICS) | TAKEN | SHARED \
        <= set(loader.load_cell(NEW_CELL)['per_layer'])
    by_name = {m['name']: m for m in bench['per_layer']}
    for name, unit in zip(NEW_METRICS, ('ms', '%', '%', '%')):
        m = by_name[name]
        assert m['workloads'] == [NEW_CELL] and m['unit'] == unit, name
        assert m['moves'] == 'train_node_steps_per_s'
    for name in TAKEN:
        assert NEW_CELL in by_name[name]['workloads'], name
    for w in bench['workloads']:
        if w['name'] != NEW_CELL:
            assert not set(NEW_METRICS) & set(
                loader.load_cell(w['name'])['per_layer']), w['name']
    cell = next(w for w in bench['workloads'] if w['name'] == NEW_CELL)
    assert cell == dict(cell, chips=1,
                        config='smallthinker-21b-a3b-swa-train',
                        traffic='lm_train_s16384_b1')
    config = next(c for c in bench['configs']
                  if c['name'] == 'smallthinker-21b-a3b-swa-train')
    assert config['reduced'] == [
        'num_hidden_layers', 'moe_num_primary_experts', 'vocab_size']
    rate = next(m for m in bench['end_to_end']
                if m['name'] == 'train_node_steps_per_s')
    assert NEW_CELL in rate['workloads']
    mix = loader.load_cell(NEW_CELL)['traffic']
    assert (mix['kind'], mix['batch'], mix['seq'], mix['n_batches'],
            mix['trace_steps'], mix['zipf_exponent']) == (
        'lm_train_closed', 1, 16384, 8, 4, 1.1)
    assert mix['document_tokens'] == loader.load_cell(
        'glm47_flash_ep8_train_8k')['traffic']['document_tokens']


def test_new_files_import_nothing_of_the_program_at_module_level():
    new = [os.path.join(BENCH, 'harness', f) for f in
           ('smallthinker_train.py', 'smallthinker_reference.py',
            'smallthinker_counts.py')]
    new += [os.path.join(BENCH, 'layer_metrics', m + '.py')
            for m in NEW_METRICS]
    for path in new:
        for line in open(path):
            if line.startswith(('import ', 'from ')):
                assert 'se3_transformer_tpu' not in line, (path, line)


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog's `config` under its own name, but the
    three listed in `reduced`; the two layouts copied whole."""
    cfg = loader.load_cell(NEW_CELL)['config']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.exists(catalog):
        pytest.skip('no catalog here')
    row = next(r for r in map(json.loads, open(catalog))
               if r['source_url'] == cfg['source'])
    differs = {k for k, v in row['config'].items() if cfg.get(k, None) != v}
    assert differs == {'num_hidden_layers', 'moe_num_primary_experts',
                       'vocab_size'} == set(cfg['reduced'])
    assert all(k in cfg for k in row['config'])
    assert (cfg['num_hidden_layers'], cfg['moe_num_primary_experts'],
            cfg['vocab_size'], cfg['chips_per_layer']) == (4, 16, 37984, 4)
    assert cfg['vocab_size'] * 4 == row['config']['vocab_size']
    assert cfg['moe_num_primary_experts'] * 4 \
        == row['config']['moe_num_primary_experts']
    m, pub = cfg['model'], row['config']
    # one whole period of the two layouts, by letter
    assert m['hybrid_override_pattern'] == ''.join(
        ('W' if s else '*') + 'E'
        for s in pub['sliding_window_layout'][:4]) == '*EWEWEWE'
    assert pub['rope_layout'] == pub['sliding_window_layout']
    assert m['rope_theta'] is None          # the global layers: no rotation
    # no width is cut
    for ours, theirs in (('hidden_size', 'hidden_size'),
                         ('moe_intermediate_size', 'moe_ffn_hidden_size'),
                         ('num_experts_per_tok',
                          'moe_num_active_primary_experts'),
                         ('num_attention_heads', 'num_attention_heads'),
                         ('num_key_value_heads', 'num_key_value_heads'),
                         ('head_dim', 'head_dim'),
                         ('layer_norm_epsilon', 'rms_norm_eps'),
                         ('norm_topk_prob', 'norm_topk_prob'),
                         ('sliding_rope_theta', 'rope_theta'),
                         ('sliding_window_size', 'sliding_window_size'),
                         ('tie_word_embeddings', 'tie_word_embeddings')):
        assert m[ours] == pub[theirs], ours
    assert m['n_routed_experts'] == 64 and m['experts_held'] == 16
    assert m['scoring_func'] == 'softmax' and m['mlp_hidden_act'] == 'relu'
    assert m['moe_enable_early_router'] is True and m['qk_norm'] is False
    assert loader.load_cell(NEW_CELL)['traffic']['seq'] \
        == pub['max_position_embeddings']
    for key in ('assumed', 'deployment', 'precision', 'stands_for'):
        assert cfg[key], key
    for name, why in cfg['assumed'].items():
        assert len(why) > 40, name
    assert {'early_router', 'no_qk_norms_no_biases', 'rotation', 'window',
            'across_documents', 'correction_bias', 'weights',
            'execution'} <= set(cfg['assumed'])


def test_the_entry_ends_at_once_on_a_program_without_the_recipe(monkeypatch):
    import se3_transformer_tpu  # noqa: F401
    from se3_transformer_tpu.training import recipes
    monkeypatch.delitem(recipes.RECIPES, 'smallthinker_decoder')
    from harness import smallthinker_train
    with pytest.raises(SystemExit,
                       match="recipe 'smallthinker_decoder'") as e:
        smallthinker_train.program(loader.load_cell(NEW_CELL)['config'])
    assert '\n' not in str(e.value)


def test_the_entrys_line_about_the_cores(monkeypatch):
    """Pairs and tiles a head of each core, the sliding layers' from the
    program's table; on a program without the module the pairs alone."""
    from harness import smallthinker_train
    cfg = loader.load_cell(NEW_CELL)['config']
    line = smallthinker_train.cores(cfg, 16384)
    assert '134,225,920 visible pairs' in line and '528 tiles of 512' in line
    assert '58,722,304 pairs a head: 252 tiles visited, 56 of them on a ' \
        'boundary' in line
    assert '\n' not in line
    import se3_transformer_tpu.ops
    monkeypatch.setitem(sys.modules,
                        'se3_transformer_tpu.ops.sliding_window', None)
    monkeypatch.delattr(se3_transformer_tpu.ops, 'sliding_window')
    line = smallthinker_train.cores(cfg, 16384)
    assert line.endswith('58,722,304 pairs a head')


def _smallthinker_step():
    """A fabricated device track with the cell's leaves, 1 ms each."""
    base = ('jit(train_step)/loss/transpose(jvp(loss))/'
            'HybridDecoder.hidden_states/checkpoint/')
    fwd = ('jit(train_step)/loss/jvp(loss)/HybridDecoder.hidden_states/'
           'checkpoint/')
    paths = [base + 'blocks_0/attn/mha_qkv/q/dot_general',
             base + 'blocks_0/attn/mha_core/jit(flash_attention)/'
             'flash_mha_bwd_dkv/pallas_call',
             base + 'blocks_0/attn/mha_core/convert_element_type',
             fwd + 'blocks_2/attn/mha_qkv/jit(forward)/qk_pass_fwd',
             fwd + 'blocks_2/attn/swa_core/jit(_fwd)/swa_core_fwd',
             base + 'blocks_2/attn/swa_core/jit(_bwd)/swa_core_bwd',
             base + 'blocks_4/attn/swa_core/jit(_bwd)/swa_core_bwd',
             base + 'blocks_2/attn/mha_out/out/dot_general',
             base + 'blocks_1/moe/moe_router/router/dot_general',
             base + 'blocks_1/moe/moe_dispatch/gather',
             base + 'blocks_1/moe/moe_experts/ragged_dot',
             base + 'blocks_1/moe/moe_combine/gather',
             base + 'blocks_1/pre_norm/mul',
             'jit(train_step)/loss/jvp(loss)/lm_head/dot_general',
             'jit(train_step)/optimizer/mul']
    rows = [[f'fusion.{i}', 1e6 * i, 1e6, p, None]
            for i, p in enumerate(paths)]
    return {'device': {'/device:TPU:0': rows}, 'host': [],
            'selector': 'xla_ops', 'op_name_source': 'metadata_stat:tf_op'}


def test_the_four_readers_on_a_step_with_the_new_leaves(tmp_path):
    from harness import smallthinker_counts as sc
    cell = loader.load_cell(NEW_CELL)
    model = cell['config']['model']
    steps, pairs = 2, 2 * 4 * 24576
    ctx = dict(spans={'step_call': [0.003, 0.003]},
               trace={'busy_s': 0.015, 'window_s': 1.0, 'op_seconds': {}},
               peaks=PEAKS, model=model, traffic=cell['traffic'],
               memory_stats={'peak_bytes_reserved': 3 * 2**30},
               counters=dict(steps=steps, moe_local_pairs=pairs,
                             moe_load_max=2 * 1700.0,
                             moe_load_mean=2 * 1536.0, moe_dropped=0.0,
                             moe_bounded=8.0,
                             expert_layer_steps=steps * 4),
               trace_root=_write(_smallthinker_step(),
                                 str(tmp_path / 'trace')))
    got = readers.read_all(cell, ctx)
    assert set(NEW_METRICS) | {'moe_experts_ms_per_step.train',
                               'moe_route_ms_per_step.train',
                               'expert_load_max_over_mean.train'} <= set(got)
    # three launches under the leaf, over two steps
    assert got['swa_core_ms_per_step.train'] == pytest.approx(1.5)
    assert got['moe_experts_ms_per_step.train'] == pytest.approx(0.5)
    assert got['moe_route_ms_per_step.train'] == pytest.approx(1.5)
    assert got['expert_load_max_over_mean.train'] == pytest.approx(
        1700 / 1536)
    # each bound by its operations: three sliding layers and one global
    # layer a step, a launch a sequence; the window's at its own pairs
    assert got['swa_core_roofline.train'] == pytest.approx(
        100 * sc.core_train_flops(model, 16384, 'W', 2 * 3) / 197e12 / 3e-3,
        rel=1e-6)
    assert got['mha28_core_roofline.train'] == pytest.approx(
        100 * sc.core_train_flops(model, 16384, '*', 2 * 1) / 197e12 / 2e-3,
        rel=1e-6)
    assert sc.core_train_flops(model, 16384, 'W', 1) \
        == 3 * 28 * 58_722_304 * 512
    assert got['smallthinker_step_mfu.train'] == pytest.approx(
        100 * steps * sc.train_step_flops(model, 16384, pairs / steps)
        / 1.0 / 197e12, rel=1e-6)


@pytest.mark.parametrize('other', ['d4_onehead_train',
                                   'glm47_flash_ep8_train_8k',
                                   'nemotron_twotower_ep16_train_8k',
                                   'lfm2_a2b_ep8_train_8k',
                                   'sdar_a3b_ep8_bd_train_8k'])
def test_the_new_readers_give_nothing_on_another_cells_context(
        step, tmp_path, other, capsys):
    """On a context of the d4 cell (its recorded step) and of the four
    decoder cells (a step with each one's leaves, `mha_core` among them, and
    its counters): nothing, and no raise; nor on a run without a trace."""
    new = loader.load_cell(NEW_CELL)
    cell = loader.load_cell(other)
    only = dict(cell, per_layer={n: new['per_layer'][n]
                                 for n in NEW_METRICS})
    recorded = {'d4_onehead_train': lambda: step,
                'glm47_flash_ep8_train_8k': _decoder_step,
                'nemotron_twotower_ep16_train_8k': _hybrid_step,
                'lfm2_a2b_ep8_train_8k': _lfm2_step,
                'sdar_a3b_ep8_bd_train_8k': _sdar_step}[other]()
    ctx = dict(counters=dict(steps=1, moe_local_pairs=20000.0,
                             expert_layer_steps=5),
               traffic=cell['traffic'], model=cell['config']['model'],
               trace={'busy_s': 0.5, 'window_s': 1.0, 'op_seconds': {}},
               peaks=PEAKS,
               trace_root=_write(recorded, str(tmp_path / 'trace')))
    assert readers.read_all(only, ctx) == {}
    assert 'left out' not in capsys.readouterr().out     # nothing raised
    ctx['trace_root'] = str(tmp_path / 'nothing')
    assert readers.read_all(only, ctx) == {}
