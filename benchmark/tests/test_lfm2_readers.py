"""The files of the decoder with gated short convolutions beside a program
that lacks it, and its four readers on a trace that has its leaves.

The driver lays this benchmark over the parent's checkout too: with the
program's new module and recipe hidden, the new cell's entry ends at once in
one line; every new reader gives nothing, without raising, on a context of
the d4, the GLM and the hybrid cell; on a fabricated step with the new leaves
each reads what its name says."""
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
from test_lm_readers import (  # noqa: E402
    D4_METRICS, FIXTURE, SHARED, _decoder_step, _write,
)

NEW_CELL = 'lfm2_a2b_ep8_train_8k'
NEW_METRICS = {
    'sconv_mixer_ms_per_step.train', 'sconv_core_roofline.train',
    'mha64_core_roofline.train', 'lfm2_step_mfu.train'}
# accepted readers that go by leaf and counter alone
TAKEN = {'moe_experts_ms_per_step.train', 'moe_route_ms_per_step.train',
         'expert_load_max_over_mean.train'}
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
    assert bench['workloads'][-1] == dict(
        bench['workloads'][-1], name=NEW_CELL, chips=1,
        config='lfm2-24b-a2b-ep8-train', traffic='lm_train_s8192_b2')
    assert bench['configs'][-1]['name'] == 'lfm2-24b-a2b-ep8-train'
    mix = loader.load_cell(NEW_CELL)['traffic']
    assert (mix['batch'], mix['seq'], mix['n_batches'],
            mix['trace_steps']) == (2, 8192, 8, 4)


def test_new_files_import_nothing_of_the_program_at_module_level():
    new = [os.path.join(BENCH, 'harness', f) for f in
           ('lfm2_train.py', 'lfm2_reference.py', 'lfm2_counts.py')]
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
    assert set(cfg['reduced']) == {'depth', 'experts_held', 'vocab_size'}
    assert (cfg['depth'], cfg['experts_held'], cfg['vocab_size'],
            cfg['chips_per_layer']) == (5, 8, 8192, 8)
    m, pub = cfg['model'], row['config']
    assert m['hybrid_override_pattern'] == 'CF*ECECECE'
    # layer 0 and layers 2 to 5 of the published layer_types
    assert [pub['layer_types'][i] for i in (0, 2, 3, 4, 5)] == [
        'conv', 'full_attention', 'conv', 'conv', 'conv']
    # no width is cut
    for ours, theirs in (('hidden_size', 'hidden_size'),
                         ('conv_L_cache', 'conv_L_cache'),
                         ('intermediate_size', 'intermediate_size'),
                         ('moe_intermediate_size', 'moe_intermediate_size'),
                         ('n_routed_experts', 'num_experts'),
                         ('num_experts_per_tok', 'num_experts_per_tok'),
                         ('num_attention_heads', 'num_attention_heads'),
                         ('num_key_value_heads', 'num_key_value_heads'),
                         ('layer_norm_epsilon', 'norm_eps'),
                         ('norm_topk_prob', 'norm_topk_prob'),
                         ('routed_scaling_factor', 'routed_scaling_factor')):
        assert m[ours] == pub[theirs], ours
    assert m['head_dim'] * m['num_attention_heads'] == pub['hidden_size']
    assert m['rope_theta'] == pub['rope_parameters']['rope_theta']
    assert m['norm_topk_eps'] == 1e-6
    for key in ('assumed', 'deployment', 'precision', 'stands_for'):
        assert cfg[key], key
    for name, why in cfg['assumed'].items():
        assert len(why) > 40, name


def test_the_entry_ends_at_once_on_a_program_without_the_recipe(monkeypatch):
    import se3_transformer_tpu  # noqa: F401
    from se3_transformer_tpu.training import recipes
    monkeypatch.setitem(sys.modules, 'se3_transformer_tpu.ops.short_conv',
                        None)
    monkeypatch.delitem(recipes.RECIPES, 'lfm2_decoder')
    from harness import lfm2_train
    with pytest.raises(SystemExit, match="recipe 'lfm2_decoder'") as e:
        lfm2_train.program(loader.load_cell(NEW_CELL)['config'])
    assert '\n' not in str(e.value)


def _lfm2_step():
    """A fabricated device track with the cell's leaves, 1 ms each."""
    base = ('jit(train_step)/loss/transpose(jvp(loss))/'
            'HybridDecoder.hidden_states/checkpoint/')
    paths = [base + 'blocks_0/conv/sconv_in/in_proj/dot_general',
             base + 'blocks_0/conv/sconv_core/conv/mul',
             base + 'blocks_0/conv/sconv_core/mul',
             base + 'blocks_0/conv/sconv_out/out_proj/dot_general',
             base + 'blocks_1/dense_ff/mlp/gate/dot_general',
             base + 'blocks_2/attn/mha_qkv/q_norm/mul',
             base + 'blocks_2/attn/mha_core/jit(flash_attention)/pallas_call',
             base + 'blocks_2/attn/mha_out/out/dot_general',
             base + 'blocks_3/moe/moe_router/router/dot_general',
             base + 'blocks_3/moe/moe_dispatch/gather',
             base + 'blocks_3/moe/moe_experts/ragged_dot',
             base + 'blocks_3/moe/moe_combine/gather',
             base + 'blocks_3/pre_norm/mul',
             'jit(train_step)/loss/jvp(loss)/lm_head/dot_general',
             'jit(train_step)/optimizer/mul']
    rows = [[f'fusion.{i}', 1e6 * i, 1e6, p, None]
            for i, p in enumerate(paths)]
    return {'device': {'/device:TPU:0': rows}, 'host': [],
            'selector': 'xla_ops', 'op_name_source': 'metadata_stat:tf_op'}


def test_the_four_readers_on_a_step_with_the_new_leaves(tmp_path):
    from harness import lfm2_counts as lc
    cell = loader.load_cell(NEW_CELL)
    model = cell['config']['model']
    steps, pairs = 2, 2 * 4 * 8192
    ctx = dict(spans={'step_call': [0.003, 0.003]},
               trace={'busy_s': 0.015, 'window_s': 1.0, 'op_seconds': {}},
               peaks=PEAKS, model=model, traffic=cell['traffic'],
               memory_stats={'peak_bytes_reserved': 3 * 2**30},
               counters=dict(steps=steps, moe_local_pairs=pairs,
                             moe_load_max=2 * 1500.0,
                             moe_load_mean=2 * 1024.0, moe_dropped=0.0,
                             expert_layer_steps=steps * 4),
               trace_root=_write(_lfm2_step(), str(tmp_path / 'trace')))
    got = readers.read_all(cell, ctx)
    assert set(got) == NEW_METRICS | TAKEN | SHARED
    assert got['sconv_mixer_ms_per_step.train'] == pytest.approx(2.0)
    assert got['moe_experts_ms_per_step.train'] == pytest.approx(0.5)
    assert got['moe_route_ms_per_step.train'] == pytest.approx(1.5)
    assert got['expert_load_max_over_mean.train'] == pytest.approx(
        1500 / 1024)
    # the core of the convolution is bound by its bytes (four operators a
    # step over both sequences' tokens), the attention core by its
    # operations (one layer, a launch a sequence)
    assert got['sconv_core_roofline.train'] == pytest.approx(
        100 * lc.sconv_core_bytes(model, 16384, 2 * 4) / 819e9 / 2e-3,
        rel=1e-6)
    assert got['mha64_core_roofline.train'] == pytest.approx(
        100 * lc.attention_core_train_flops(model, 8192, 2 * 2) / 197e12
        / 1e-3, rel=1e-6)
    assert got['lfm2_step_mfu.train'] == pytest.approx(
        100 * steps * 2 * lc.train_step_flops(model, 8192, pairs / steps / 2)
        / 1.0 / 197e12, rel=1e-6)


@pytest.mark.parametrize('other', ['d4_onehead_train',
                                   'glm47_flash_ep8_train_8k',
                                   'nemotron_twotower_ep16_train_8k'])
def test_the_new_readers_give_nothing_on_another_cells_context(
        step, tmp_path, other, capsys):
    """On a context of the d4 cell (its recorded step), of the GLM cell and of
    the hybrid cell (a step with its leaves, `mha_core` among them, and its
    counters): nothing, and no raise; nor on a run without a trace."""
    new = loader.load_cell(NEW_CELL)
    cell = loader.load_cell(other)
    only = dict(cell, per_layer={n: new['per_layer'][n]
                                 for n in NEW_METRICS})
    recorded = {'d4_onehead_train': lambda: step,
                'glm47_flash_ep8_train_8k': _decoder_step,
                'nemotron_twotower_ep16_train_8k': _hybrid_step}[other]()
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
