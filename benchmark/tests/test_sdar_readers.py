"""The files of the decoder trained by diffusion over blocks beside a
program that lacks it, and its three readers on a trace that has its leaves.

The driver lays this benchmark over the parent's checkout too: with the
program's recipe hidden, the new cell's entry ends at once in one line; every
new reader gives nothing, without raising, on a context of the d4, the GLM,
the hybrid and the short-convolution cell; on a fabricated step with the new
leaves each reads what its name says, and the shared readers the cell's name
was appended to read it rightly."""
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
    D4_METRICS, FIXTURE, SHARED, _decoder_step, _write,
)

NEW_CELL = 'sdar_a3b_ep8_bd_train_8k'
NEW_METRICS = {'bd_core_ms_per_step.train', 'bd_core_roofline.train',
               'sdar_step_mfu.train'}
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


def test_the_new_cell_reads_its_metrics_and_d4_its_own():
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    assert set(loader.load_cell(NEW_CELL)['per_layer']) \
        == NEW_METRICS | TAKEN | SHARED
    assert set(loader.load_cell('d4_onehead_train')['per_layer']) \
        == D4_METRICS
    for m in bench['per_layer']:
        if m['name'] in NEW_METRICS:
            assert m['workloads'] == [NEW_CELL], m['name']
            assert m['moves'] == 'train_node_steps_per_s'
        elif m['name'] in TAKEN:
            assert m['workloads'][-1] == NEW_CELL, m['name']
        else:
            assert NEW_CELL not in m.get('workloads', ()), m['name']
    assert [m['name'] for m in bench['per_layer'][-3:]] == [
        'bd_core_ms_per_step.train', 'bd_core_roofline.train',
        'sdar_step_mfu.train']
    assert bench['workloads'][-1] == dict(
        bench['workloads'][-1], name=NEW_CELL, chips=1,
        config='sdar-30b-a3b-ep8-train', traffic='lm_train_s8192_b1')
    assert bench['configs'][-1]['name'] == 'sdar-30b-a3b-ep8-train'
    assert bench['configs'][-1]['reduced'] == ['depth', 'experts_held',
                                               'vocab_size']
    rate = next(m for m in bench['end_to_end']
                if m['name'] == 'train_node_steps_per_s')
    assert rate['workloads'][-1] == NEW_CELL
    mix = loader.load_cell(NEW_CELL)['traffic']
    assert (mix['batch'], mix['seq'], mix['n_batches'],
            mix['trace_steps']) == (1, 8192, 8, 4)


def test_new_files_import_nothing_of_the_program_at_module_level():
    new = [os.path.join(BENCH, 'harness', f) for f in
           ('sdar_train.py', 'sdar_reference.py', 'sdar_counts.py')]
    new += [os.path.join(BENCH, 'layer_metrics', m + '.py')
            for m in NEW_METRICS]
    for path in new:
        for line in open(path):
            if line.startswith(('import ', 'from ')):
                assert 'se3_transformer_tpu' not in line, (path, line)


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog's `config` under its own name, but the
    one listed in `reduced` that the catalog names."""
    cfg = loader.load_cell(NEW_CELL)['config']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.exists(catalog):
        pytest.skip('no catalog here')
    row = next(r for r in map(json.loads, open(catalog))
               if r['source_url'] == cfg['source'])
    differs = {k for k, v in row['config'].items() if cfg.get(k, None) != v}
    assert differs == {'vocab_size'}
    assert all(k in cfg for k in row['config'])
    assert set(cfg['reduced']) == {'depth', 'experts_held', 'vocab_size'}
    assert (cfg['depth'], cfg['experts_held'], cfg['vocab_size'],
            cfg['chips_per_layer']) == (5, 16, 18992, 8)
    assert cfg['vocab_size'] * 8 == row['config']['vocab_size']
    m, pub = cfg['model'], row['config']
    assert m['hybrid_override_pattern'] == '*E' * 5
    # no width is cut
    for ours, theirs in (('hidden_size', 'hidden_size'),
                         ('moe_intermediate_size', 'moe_intermediate_size'),
                         ('n_routed_experts', 'num_experts'),
                         ('num_experts_per_tok', 'num_experts_per_tok'),
                         ('num_attention_heads', 'num_attention_heads'),
                         ('num_key_value_heads', 'num_key_value_heads'),
                         ('head_dim', 'head_dim'),
                         ('layer_norm_epsilon', 'rms_norm_eps'),
                         ('norm_topk_prob', 'norm_topk_prob'),
                         ('rope_theta', 'rope_theta'),
                         ('mlp_hidden_act', 'hidden_act'),
                         ('tie_word_embeddings', 'tie_word_embeddings')):
        assert m[ours] == pub[theirs], ours
    assert m['scoring_func'] == 'softmax' and m['qk_norm'] is True
    assert pub['mlp_only_layers'] == [] and pub['decoder_sparse_step'] == 1
    for key in ('assumed', 'deployment', 'precision', 'stands_for'):
        assert cfg[key], key
    for name, why in cfg['assumed'].items():
        assert len(why) > 40, name
    assert {'block_length', 'noise_schedule', 'in_place_prediction',
            'streams', 'qk_norm', 'mask_id', 'across_documents',
            'correction_bias', 'weights', 'execution'} <= set(cfg['assumed'])


def test_the_entry_ends_at_once_on_a_program_without_the_recipe(monkeypatch):
    import se3_transformer_tpu  # noqa: F401
    from se3_transformer_tpu.training import recipes
    monkeypatch.delitem(recipes.RECIPES, 'sdar_decoder')
    from harness import sdar_train
    with pytest.raises(SystemExit, match="recipe 'sdar_decoder'") as e:
        sdar_train.program(loader.load_cell(NEW_CELL)['config'])
    assert '\n' not in str(e.value)


def test_the_noise_is_the_seeds_and_spans_the_levels():
    import numpy as np

    from harness import lm_traffic, sdar_train
    mix = loader.load_cell(NEW_CELL)['traffic']
    tokens = lm_traffic.token_batches(mix, 2**31 + 5, 18991)
    assert tokens.max() < 18991                  # the mask is no data token
    a = sdar_train.noise(tokens, 2**31 + 5, 18991)
    b = sdar_train.noise(tokens, 2**31 + 5, 18991)
    c = sdar_train.noise(tokens, 2**31 + 6, 18991)
    assert len(a) == 8
    for x, y, z in zip(a, b, c):
        assert np.array_equal(x['noised'], y['noised'])
        assert not np.array_equal(x['noised'], z['noised'])
        masked = x['weight'] > 0
        assert masked.any(axis=1).all()
        assert np.array_equal(x['noised'] == 18991, masked)
        t = 1.0 / x['weight'].max()
        assert sdar_train.NOISE_EPS < t <= 1.0
        assert abs(masked.mean() - t) < 0.03
        assert x['weight'].dtype == np.float32
        assert x['noised'].dtype == np.int32


def _sdar_step():
    """A fabricated device track with the cell's leaves, 1 ms each."""
    base = ('jit(train_step)/loss/transpose(jvp(loss))/'
            'HybridDecoder.hidden_states/checkpoint/')
    core = 'blocks_0/attn/bd_core/vmap(jit(_splash_attention))/'
    paths = [base + 'blocks_0/attn/mha_qkv/q/dot_general',
             base + 'blocks_0/attn/mha_qkv/q_norm/mul',
             base + core + 'splash_mha_dkv_no_residuals/'
             'splash_mha_dkv_no_residuals/pallas_call',
             base + core + 'splash_mha_dq_no_residuals/'
             'splash_mha_dq_no_residuals/pallas_call',
             base + 'blocks_0/attn/bd_core/convert_element_type',
             base + 'blocks_0/attn/mha_out/out/dot_general',
             base + 'blocks_1/moe/moe_router/router/dot_general',
             base + 'blocks_1/moe/moe_dispatch/gather',
             base + 'blocks_1/moe/moe_experts/ragged_dot',
             base + 'blocks_1/moe/moe_combine/gather',
             base + 'blocks_1/pre_norm/mul',
             'jit(train_step)/loss/jvp(loss)/HybridDecoder.hidden_states/'
             'bd_streams/concatenate',
             'jit(train_step)/loss/jvp(loss)/lm_head/dot_general',
             'jit(train_step)/optimizer/mul']
    rows = [[f'fusion.{i}', 1e6 * i, 1e6, p, None]
            for i, p in enumerate(paths)]
    return {'device': {'/device:TPU:0': rows}, 'host': [],
            'selector': 'xla_ops', 'op_name_source': 'metadata_stat:tf_op'}


def test_the_three_readers_on_a_step_with_the_new_leaves(tmp_path):
    from harness import sdar_counts as sc
    cell = loader.load_cell(NEW_CELL)
    model = cell['config']['model']
    steps, pairs = 2, 2 * 5 * 16384
    ctx = dict(spans={'step_call': [0.003, 0.003]},
               trace={'busy_s': 0.014, 'window_s': 1.0, 'op_seconds': {}},
               peaks=PEAKS, model=model, traffic=cell['traffic'],
               memory_stats={'peak_bytes_reserved': 3 * 2**30},
               counters=dict(steps=steps, moe_local_pairs=pairs,
                             moe_load_max=2 * 1500.0,
                             moe_load_mean=2 * 1024.0, moe_dropped=0.0,
                             moe_bounded=10.0, bd_masked=8000.0,
                             bd_weight=16000.0,
                             expert_layer_steps=steps * 5),
               loss=cell['config']['loss'],
               trace_root=_write(_sdar_step(), str(tmp_path / 'trace')))
    got = readers.read_all(cell, ctx)
    assert NEW_METRICS | {'moe_experts_ms_per_step.train',
                          'moe_route_ms_per_step.train',
                          'expert_load_max_over_mean.train'} <= set(got)
    # two launches and a cast, over two steps
    assert got['bd_core_ms_per_step.train'] == pytest.approx(1.5)
    assert got['moe_experts_ms_per_step.train'] == pytest.approx(0.5)
    assert got['moe_route_ms_per_step.train'] == pytest.approx(1.5)
    assert got['expert_load_max_over_mean.train'] == pytest.approx(
        1500 / 1024)
    # bound by its operations: five layers a step, a launch a sequence
    assert got['bd_core_roofline.train'] == pytest.approx(
        100 * sc.bd_core_train_flops(model, 8192, 4, 2 * 5) / 197e12 / 3e-3,
        rel=1e-6)
    assert got['sdar_step_mfu.train'] == pytest.approx(
        100 * steps * sc.train_step_flops(model, 8192, pairs / steps, 4)
        / 1.0 / 197e12, rel=1e-6)


@pytest.mark.parametrize('other', ['d4_onehead_train',
                                   'glm47_flash_ep8_train_8k',
                                   'nemotron_twotower_ep16_train_8k',
                                   'lfm2_a2b_ep8_train_8k'])
def test_the_new_readers_give_nothing_on_another_cells_context(
        step, tmp_path, other, capsys):
    """On a context of the d4 cell (its recorded step) and of the three
    decoder cells (a step with each one's leaves, `mha_core` among them, and
    its counters): nothing, and no raise; nor on a run without a trace."""
    new = loader.load_cell(NEW_CELL)
    cell = loader.load_cell(other)
    only = dict(cell, per_layer={n: new['per_layer'][n]
                                 for n in NEW_METRICS})
    recorded = {'d4_onehead_train': lambda: step,
                'glm47_flash_ep8_train_8k': _decoder_step,
                'nemotron_twotower_ep16_train_8k': _hybrid_step,
                'lfm2_a2b_ep8_train_8k': _lfm2_step}[other]()
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
