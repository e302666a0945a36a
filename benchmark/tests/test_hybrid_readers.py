"""The hybrid decoder's files beside a program that lacks it, and its readers
on a trace that has its leaves.

The driver lays this benchmark over the parent's checkout too: every accepted
cell must load and read there as before. So: no accepted cell reads a new
metric; with the program's new modules hidden from import `readers.read_all`
on the recorded v5e step of `d4_onehead_train` gives every accepted metric
and raises nothing, and the new cell's entry ends at once; every new reader
gives nothing, without raising, on a context of the d4 cell and of the GLM
cell."""
import gzip
import json
import os
import sys

import pytest

from harness import loader, readers, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, 'tests'))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_lm_readers import (  # noqa: E402
    D4_METRICS, FIXTURE, NEW_METRICS as GLM_METRICS, SHARED, _decoder_step,
    _write,
)

NEW_CELL = 'nemotron_twotower_ep16_train_8k'
NEW_MODULES = ('se3_transformer_tpu.models.hybrid_decoder',
               'se3_transformer_tpu.ops.state_space',
               'se3_transformer_tpu.ops.grouped_attention')
NEW_METRICS = {
    'ssm_mixer_ms_per_step.train', 'ssm_scan_ms_per_step.train',
    'ssm_scan_roofline.train', 'mha_core_roofline.train',
    'hybrid_step_mfu.train'}
# accepted readers that need no count of the other decoder's
TAKEN = {'moe_experts_ms_per_step.train', 'moe_route_ms_per_step.train',
         'expert_load_max_over_mean.train'}
PEAKS = {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9}


@pytest.fixture(scope='module')
def step():
    with gzip.open(FIXTURE, 'rt') as fh:
        return json.load(fh)


def test_the_new_cell_reads_its_metrics_and_the_accepted_cells_theirs():
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    got = {w['name']: set(loader.load_cell(w['name'])['per_layer'])
           for w in bench['workloads']}
    assert got == {'d4_onehead_train': D4_METRICS,
                   'glm47_flash_ep8_train_8k': GLM_METRICS | SHARED,
                   NEW_CELL: NEW_METRICS | TAKEN | SHARED}
    for m in bench['per_layer']:
        if m['name'] in NEW_METRICS:
            assert m['workloads'] == [NEW_CELL], m['name']
        elif m['name'] in TAKEN:
            assert m['workloads'] == ['glm47_flash_ep8_train_8k', NEW_CELL]
    assert bench['workloads'][-1]['name'] == NEW_CELL
    assert bench['configs'][-1]['name'] == 'nemotron-twotower-ep16-train'


def test_new_files_import_nothing_of_the_program_at_module_level():
    new = [os.path.join(BENCH, 'harness', f) for f in
           ('hybrid_train.py', 'hybrid_reference.py', 'hybrid_counts.py')]
    new += [os.path.join(BENCH, 'layer_metrics', m + '.py')
            for m in NEW_METRICS]
    for path in new:
        for line in open(path):
            if line.startswith(('import ', 'from ')):
                assert 'se3_transformer_tpu' not in line, (path, line)


def test_the_configuration_holds_every_published_key(tmp_path):
    """Every number of the catalog's `config` under its own name, but the
    three listed in `reduced`."""
    cfg = loader.load_cell(NEW_CELL)['config']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.exists(catalog):
        pytest.skip('no catalog here')
    row = next(r for r in map(json.loads, open(catalog))
               if r['source_url'] == cfg['source'])
    differs = {k for k, v in row['config'].items() if cfg.get(k, None) != v}
    assert differs == {'vocab_size'}
    assert set(cfg['reduced']) == {'depth', 'experts_held', 'vocab_size'}
    assert (cfg['depth'], cfg['experts_held'], cfg['vocab_size']) \
        == (9, 8, 16384)
    m = cfg['model']
    assert m['hybrid_override_pattern'] == 'MEMEM*EME' \
        == cfg['hybrid_override_pattern'][:9]
    # no width is cut
    for ours, theirs in (('mamba_num_heads', 'mamba_num_heads'),
                         ('mamba_head_dim', 'mamba_head_dim'),
                         ('ssm_state_size', 'ssm_state_size'),
                         ('n_groups', 'n_groups'),
                         ('hidden_size', 'hidden_size'),
                         ('moe_intermediate_size', 'moe_intermediate_size'),
                         ('moe_shared_expert_intermediate_size',
                          'moe_shared_expert_intermediate_size'),
                         ('n_routed_experts', 'n_routed_experts'),
                         ('num_experts_per_tok', 'num_experts_per_tok'),
                         ('head_dim', 'head_dim'),
                         ('num_attention_heads', 'num_attention_heads'),
                         ('num_key_value_heads', 'num_key_value_heads'),
                         ('chunk_size', 'chunk_size'),
                         ('conv_kernel', 'conv_kernel')):
        assert m[ours] == row['config'][theirs], ours
    for key in ('assumed', 'left_out', 'deployment', 'precision'):
        assert cfg[key], key


def test_accepted_cells_read_as_before_beside_a_program_without_the_decoder(
        step, tmp_path, monkeypatch):
    import se3_transformer_tpu  # noqa: F401  (the parent's imports too)
    from se3_transformer_tpu.training import recipes
    trace_root = _write(step, str(tmp_path / 'trace'))
    for name in NEW_MODULES:
        monkeypatch.setitem(sys.modules, name, None)    # import raises
    monkeypatch.delitem(recipes.RECIPES, 'hybrid_decoder')
    cell = loader.load_cell('d4_onehead_train')
    reduced = {'device': {t: [r[:3] for r in rows]
                          for t, rows in step['device'].items()},
               'host': step['host']}
    lo, hi = step['window_ns']
    log = [dict(kind=k, fun_name='train_step', seconds=e - s, start=s, end=e)
           for k, s, e in (('jaxpr_trace', 0.0, 30.0), ('lower', 30.0, 40.0),
                           ('backend_compile', 40.0, 48.0))]
    ctx = dict(spans={'step_call': [0.005], 'loss_fetch': [0.9]},
               trace=trace.reduce(reduced, (hi - lo) * 1e-9), peaks=PEAKS,
               model=cell['config']['model'],
               memory_stats={'peak_bytes_reserved': 6 * 2**30},
               counters={'steps': step['steps']}, compile_log=log,
               trace_root=trace_root,
               shapes_run=[dict(nodes=1024, times=1, backward=True)])
    got = readers.read_all(cell, ctx)
    assert set(got) == D4_METRICS
    assert got['scope_coverage.train'] == pytest.approx(98.3258, abs=1e-3)
    # and the new cell's entry ends at once on such a program, in one line
    from harness import hybrid_train
    new = loader.load_cell(NEW_CELL)
    with pytest.raises(SystemExit, match="recipe 'hybrid_decoder'") as e:
        hybrid_train.program(new['config'])
    assert '\n' not in str(e.value)


def _hybrid_step():
    """A fabricated device track with the hybrid decoder's leaves, 1 ms
    each."""
    base = ('jit(train_step)/loss/transpose(jvp(loss))/'
            'HybridDecoder.hidden_states/checkpoint/')
    paths = [base + 'blocks_0/ssm/ssm_in/in_proj/dot_general',
             base + 'blocks_0/ssm/ssm_conv/conv/mul',
             base + 'blocks_0/ssm/ssm_scan/dot_general',
             base + 'blocks_0/ssm/ssm_scan/exp',
             base + 'blocks_0/ssm/ssm_gate/gate_norm/mul',
             base + 'blocks_0/ssm/ssm_out/out_proj/dot_general',
             base + 'blocks_5/attn/mha_qkv/q/dot_general',
             base + 'blocks_5/attn/mha_core/jit(flash_attention)/pallas_call',
             base + 'blocks_5/attn/mha_out/out/dot_general',
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


def test_the_new_readers_on_a_step_with_the_hybrid_leaves(tmp_path):
    from harness import hybrid_counts as hc
    cell = loader.load_cell(NEW_CELL)
    model = cell['config']['model']
    steps, pairs = 2, 2 * 4 * 3072
    ctx = dict(spans={'step_call': [0.003, 0.003]},
               trace={'busy_s': 0.016, 'window_s': 1.0, 'op_seconds': {}},
               peaks=PEAKS, model=model, traffic=cell['traffic'],
               memory_stats={'peak_bytes_reserved': 3 * 2**30},
               counters=dict(steps=steps, moe_local_pairs=pairs,
                             moe_load_max=2 * 700.0, moe_load_mean=2 * 384.0,
                             moe_dropped=0.0, expert_layer_steps=steps * 4),
               trace_root=_write(_hybrid_step(), str(tmp_path / 'trace')))
    got = readers.read_all(cell, ctx)
    assert set(got) == NEW_METRICS | TAKEN | SHARED
    assert got['ssm_mixer_ms_per_step.train'] == pytest.approx(3.0)
    assert got['ssm_scan_ms_per_step.train'] == pytest.approx(1.0)
    assert got['moe_experts_ms_per_step.train'] == pytest.approx(0.5)
    assert got['moe_route_ms_per_step.train'] == pytest.approx(1.5)
    assert got['expert_load_max_over_mean.train'] == pytest.approx(700 / 384)
    # the scan is bound by its bytes, the attention core by its operations
    assert got['ssm_scan_roofline.train'] == pytest.approx(
        100 * hc.scan_bytes(model, 8192, 2 * 4) / 819e9 / 2e-3, rel=1e-6)
    assert got['mha_core_roofline.train'] == pytest.approx(
        100 * hc.attention_core_train_flops(model, 8192, 2) / 197e12 / 1e-3,
        rel=1e-6)
    assert got['hybrid_step_mfu.train'] == pytest.approx(
        100 * steps * hc.train_step_flops(model, 8192, pairs / steps)
        / 1.0 / 197e12, rel=1e-6)


@pytest.mark.parametrize('other', ['d4_onehead_train',
                                   'glm47_flash_ep8_train_8k'])
def test_the_new_readers_give_nothing_on_another_cells_context(
        step, tmp_path, other, capsys):
    """On a context of the d4 cell (its recorded step) and of the GLM cell (a
    step with its leaves and its counters): nothing, and no raise; nor on a
    run without a trace."""
    new = loader.load_cell(NEW_CELL)
    cell = loader.load_cell(other)
    only = dict(cell, per_layer={n: new['per_layer'][n]
                                 for n in NEW_METRICS})
    recorded = step if other == 'd4_onehead_train' else _decoder_step()
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
