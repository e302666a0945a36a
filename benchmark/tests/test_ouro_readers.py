"""The files of the looped decoder beside a program that lacks it, and its
five readers on a trace that has its passes and leaves.

The driver lays this benchmark over the parent's checkout too: with the
program's recipe hidden, the new cell's entry ends at once in one line; every
new reader gives nothing, without raising, on a context of the d4, the GLM,
the hybrid, the short-convolution, the block-diffusion and the
sliding-window cell, and on a reduction without `pass_s` (the parent's); on
a fabricated step with the passes and the new leaves each reads what its
name says. Subset checks only: nothing here names what a later PR adds."""
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
from test_smallthinker_readers import _smallthinker_step  # noqa: E402

NEW_CELL = 'ouro_2p6b_loop4_train_8k'
NEW_METRICS = ['ouro_step_mfu.train', 'loop_body_ms_per_step.train',
               'exit_heads_ms_per_step.train', 'mha16_core_roofline.train',
               'exit_last_share.train']
UNITS = ('%', 'ms', 'ms', '%', 'ratio')
# accepted readers the cell's name was appended to
TAKEN = {'dense_products_ms_per_step.train',
         'dense_products_peak_share.train',
         'dense_products_bwd_peak_share.train', 'xla_glue_ms_per_step.train'}
PEAKS = {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9}


@pytest.fixture(scope='module')
def step():
    with gzip.open(FIXTURE, 'rt') as fh:
        return json.load(fh)


def test_the_new_cell_reads_its_metrics_and_no_other_cell_does():
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    assert set(NEW_METRICS) | TAKEN | SHARED \
        <= set(loader.load_cell(NEW_CELL)['per_layer'])
    by_name = {m['name']: m for m in bench['per_layer']}
    for name, unit in zip(NEW_METRICS, UNITS):
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
    assert cell == dict(cell, chips=1, config='ouro-2.6b-loop4-train',
                        traffic='lm_train_s8192_b1')
    config = next(c for c in bench['configs']
                  if c['name'] == 'ouro-2.6b-loop4-train')
    assert config['reduced'] == ['num_hidden_layers']
    rate = next(m for m in bench['end_to_end']
                if m['name'] == 'train_node_steps_per_s')
    assert NEW_CELL in rate['workloads']
    # the traffic is the GLM cell's, file and all
    assert loader.load_cell(NEW_CELL)['traffic'] == loader.load_cell(
        'glm47_flash_ep8_train_8k')['traffic']


def test_new_files_import_nothing_of_the_program_at_module_level():
    new = [os.path.join(BENCH, 'harness', f) for f in
           ('ouro_train.py', 'ouro_reference.py', 'ouro_counts.py')]
    new += [os.path.join(BENCH, 'layer_metrics', m + '.py')
            for m in NEW_METRICS if m != 'exit_last_share.train']
    for path in new:
        for line in open(path):
            if line.startswith(('import ', 'from ')):
                assert 'se3_transformer_tpu' not in line, (path, line)


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog's `config` under its own name, but the
    depth; the layers' kinds copied whole."""
    cfg = loader.load_cell(NEW_CELL)['config']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.exists(catalog):
        pytest.skip('no catalog here')
    row = next(r for r in map(json.loads, open(catalog))
               if r['source_url'] == cfg['source'])
    differs = {k for k, v in row['config'].items() if cfg.get(k, None) != v}
    assert differs == {'num_hidden_layers'} == set(cfg['reduced'])
    assert all(k in cfg for k in row['config'])
    assert (cfg['num_hidden_layers'], cfg['total_ut_steps'],
            cfg['vocab_size']) == (4, 4, 49152)
    m, pub = cfg['model'], row['config']
    assert m['hybrid_override_pattern'] == '*F' * cfg['num_hidden_layers']
    assert set(pub['layer_types']) == {'full_attention'}
    # no width is cut, and neither the passes nor the vocabulary
    for ours, theirs in (('hidden_size', 'hidden_size'),
                         ('intermediate_size', 'intermediate_size'),
                         ('num_attention_heads', 'num_attention_heads'),
                         ('num_key_value_heads', 'num_key_value_heads'),
                         ('head_dim', 'head_dim'),
                         ('layer_norm_epsilon', 'rms_norm_eps'),
                         ('rope_theta', 'rope_theta'),
                         ('vocab_rows', 'vocab_size'),
                         ('total_ut_steps', 'total_ut_steps'),
                         ('tie_word_embeddings', 'tie_word_embeddings')):
        assert m[ours] == pub[theirs], ours
    assert m['sandwich_norm'] is True and m['qk_norm'] is False
    assert loader.load_cell(NEW_CELL)['traffic']['seq'] \
        <= pub['max_position_embeddings']
    assert cfg['loss'] == {'chunk': 1024, 'beta': 0.1}
    for key in ('assumed', 'deployment', 'precision', 'stands_for'):
        assert cfg[key], key
    for name, why in cfg['assumed'].items():
        assert len(why) > 40, name
    assert {'sandwich_norm', 'norm_in_the_loop', 'exit_gate', 'objective',
            'no_biases_no_qk_norms', 'rotation', 'across_documents',
            'weights', 'execution'} <= set(cfg['assumed'])
    limits = cfg['correct']
    assert {'loss_rel_gap', 'grad_leaf_gap', 'grad_rel_diff',
            'delta_leaf_gap', 'loss_ut_rel_gap', 'exit_share_rel_gap'} \
        <= set(limits)
    assert 'choice_mismatch_share' not in limits     # no experts


def test_the_entry_ends_at_once_on_a_program_without_the_recipe(monkeypatch):
    import se3_transformer_tpu  # noqa: F401
    from se3_transformer_tpu.training import recipes
    monkeypatch.delitem(recipes.RECIPES, 'ouro_decoder')
    from harness import ouro_train
    with pytest.raises(SystemExit, match="recipe 'ouro_decoder'") as e:
        ouro_train.program(loader.load_cell(NEW_CELL)['config'])
    assert '\n' not in str(e.value)


def _ouro_step():
    """A fabricated device track with the cell's passes and leaves, 1 ms
    each: pass 0 forward, pass 3 backward and replayed, the exits."""
    root = 'jit(train_step)/loss/'
    fwd = root + 'jvp(loss)/HybridDecoder.hidden_states/'
    bwd = root + 'transpose(jvp(loss))/HybridDecoder.hidden_states/'
    paths = [fwd + 'ut_0/checkpoint/blocks_0/attn/mha_qkv/jit(forward)/'
             'qk_pass_fwd',
             fwd + 'ut_0/checkpoint/blocks_0/attn/mha_core/jit(_fwd)/'
             'mha_core_fwd',
             fwd + 'ut_0/checkpoint/blocks_1/dense_ff/mlp/dot_general',
             fwd + 'ut_0/norm/final_norm/mul',
             bwd + 'ut_3/checkpoint/rematted_computation/blocks_0/attn/'
             'mha_qkv/q/dot_general',
             bwd + 'ut_3/checkpoint/blocks_0/attn/mha_core/jit(_bwd)/'
             'mha_core_bwd',
             bwd + 'ut_3/checkpoint/blocks_0/add',
             root + 'jvp(loss)/HybridDecoder.exit_logits/exit_gate/'
             'exit_gate/dot_general',
             root + 'jvp(loss)/exit_mix/log_sigmoid/log1p',
             root + 'jvp(loss)/lm_head/checkpoint/dot_general',
             root + 'transpose(jvp(loss))/lm_head/checkpoint/dot_general',
             root + 'jvp(loss)/HybridDecoder.hidden_states/embed/embedding/'
             'gather',
             'jit(train_step)/optimizer/mul']
    rows = [[f'fusion.{i}', 1e6 * i, 1e6, p, None]
            for i, p in enumerate(paths)]
    return {'device': {'/device:TPU:0': rows}, 'host': [],
            'selector': 'xla_ops', 'op_name_source': 'metadata_stat:tf_op'}


def _ctx(cell, tmp_path, recorded, steps=2, **counters):
    return dict(spans={'step_call': [0.003, 0.003]},
                trace={'busy_s': 0.013, 'window_s': 1.0, 'op_seconds': {}},
                peaks=PEAKS, model=cell['config']['model'],
                traffic=cell['traffic'],
                memory_stats={'peak_bytes_reserved': 3 * 2**30},
                counters=dict(steps=steps, **counters),
                trace_root=_write(recorded, str(tmp_path / 'trace')))


def test_the_five_readers_on_a_step_with_the_passes_and_the_new_leaves(
        tmp_path, capsys):
    from harness import ouro_counts as oc
    cell = loader.load_cell(NEW_CELL)
    model = cell['config']['model']
    ctx = _ctx(cell, tmp_path, _ouro_step(), exit_mass_last=2 * 1023.875,
               exit_tokens=2 * 8191.0)
    got = readers.read_all(cell, ctx)
    assert set(NEW_METRICS) <= set(got)
    # seven events under a pass (the residual add too, which is under no
    # leaf of its own), four under the exits' leaves, over two steps
    assert got['loop_body_ms_per_step.train'] == pytest.approx(3.5)
    assert got['exit_heads_ms_per_step.train'] == pytest.approx(2.0)
    assert got['exit_last_share.train'] == pytest.approx(0.125)
    # two launches' milliseconds against a step's 16 forward and 16
    # backward launches, twice
    assert got['mha16_core_roofline.train'] == pytest.approx(
        100 * oc.core_train_flops(model, 8192, 2 * 16) / 197e12 / 2e-3,
        rel=1e-6)
    assert got['ouro_step_mfu.train'] == pytest.approx(
        100 * 2 * oc.train_step_flops(model, 8192) / 1.0 / 197e12, rel=1e-6)
    out = capsys.readouterr().out
    assert out.count('passes of the looped stack') == 1
    assert 'ut_0' in out and 'ut_3' in out and 'ut_1' not in out


def test_a_reduction_without_the_passes_table_reads_as_nothing(
        tmp_path, monkeypatch, capsys):
    """The parent's reducer has no `pass_s`: the loop's reader gives
    nothing and does not raise; the readers by leaf still read."""
    from se3_transformer_tpu.observability import profiling
    reduce_events = profiling.reduce_events

    def as_the_parent(*args, **kwargs):
        red = reduce_events(*args, **kwargs)
        red.pop('pass_s', None)
        return red

    monkeypatch.setattr(profiling, 'reduce_events', as_the_parent)
    cell = loader.load_cell(NEW_CELL)
    got = readers.read_all(cell, _ctx(cell, tmp_path, _ouro_step()))
    assert 'loop_body_ms_per_step.train' not in got
    assert 'exit_last_share.train' not in got        # no counters either
    assert 'exit_heads_ms_per_step.train' in got
    assert 'left out' not in capsys.readouterr().out     # nothing raised


@pytest.mark.parametrize('other', ['d4_onehead_train',
                                   'glm47_flash_ep8_train_8k',
                                   'nemotron_twotower_ep16_train_8k',
                                   'lfm2_a2b_ep8_train_8k',
                                   'sdar_a3b_ep8_bd_train_8k',
                                   'smallthinker_a3b_swa_train_16k'])
def test_the_new_readers_give_nothing_on_another_cells_context(
        step, tmp_path, other, capsys):
    """On a context of the d4 cell (its recorded step) and of the five
    decoder cells (a step with each one's leaves, `mha_core` and `lm_head`
    among them, and its counters): nothing, and no raise; nor on a run
    without a trace."""
    new = loader.load_cell(NEW_CELL)
    cell = loader.load_cell(other)
    only = dict(cell, per_layer={n: new['per_layer'][n]
                                 for n in NEW_METRICS})
    recorded = {'d4_onehead_train': lambda: step,
                'glm47_flash_ep8_train_8k': _decoder_step,
                'nemotron_twotower_ep16_train_8k': _hybrid_step,
                'lfm2_a2b_ep8_train_8k': _lfm2_step,
                'sdar_a3b_ep8_bd_train_8k': _sdar_step,
                'smallthinker_a3b_swa_train_16k': _smallthinker_step}[other]()
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
