"""The token decoder's files beside a program that lacks the decoder, and its
readers on a trace that has the decoder's leaves.

The driver lays this benchmark over the parent's checkout too: every accepted
cell must load and read there as before. So: with the program's new modules
hidden from import, every accepted cell loads with the metric names the
ledger has for it, and `readers.read_all` on the recorded v5e step of
`d4_onehead_train` gives every one of them and raises nothing."""
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
NEW_CELL = 'glm47_flash_ep8_train_8k'
NEW_MODULES = ('se3_transformer_tpu.models.token_decoder',
               'se3_transformer_tpu.ops.expert_layer',
               'se3_transformer_tpu.ops.latent_attention',
               'se3_transformer_tpu.training.lm_loss')
# the per-layer metrics of the ledger's PR 25 line for d4_onehead_train
D4_METRICS = {
    'step_dispatch_ms.train', 'device_ms_per_step.train',
    'kernels_ms_per_step.train', 'pairwise_fwd_ms_per_step.train',
    'pairwise_bwd_ms_per_step.train', 'kernels_roofline.train',
    'device_idle_share.train', 'hbm_reserved_gib.train',
    'pairwise_bwd_a_ms_per_step.train', 'pairwise_bwd_b_ms_per_step.train',
    'pairwise_layout_ms_per_step.train', 'replay_ms_per_step.train',
    'attn_core_ms_per_step.train', 'scope_coverage.train',
    'setup_step_trace_s.train', 'setup_step_load_s.train',
    'setup_other_compile_s.train'}
NEW_METRICS = {
    'moe_experts_ms_per_step.train', 'moe_route_ms_per_step.train',
    'latent_attn_ms_per_step.train', 'moe_experts_roofline.train',
    'latent_core_roofline.train', 'step_mfu.train',
    'expert_load_max_over_mean.train'}
SHARED = {'step_dispatch_ms.train', 'device_ms_per_step.train',
          'device_idle_share.train', 'hbm_reserved_gib.train'}


@pytest.fixture(scope='module')
def step():
    with gzip.open(FIXTURE, 'rt') as fh:
        return json.load(fh)


def _write(step, root):
    from xplane_fixture import write_xplane
    write_xplane(os.path.join(root, 'cell-1', 'plugins', 'profile', 'run',
                              'vm.xplane.pb'), step)
    return root


def test_no_new_metric_is_read_in_an_accepted_cell():
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    for w in bench['workloads']:
        cell = loader.load_cell(w['name'])
        if w['name'] == NEW_CELL:
            assert set(cell['per_layer']) == NEW_METRICS | SHARED
        else:
            assert set(cell['per_layer']) == D4_METRICS, w['name']
    for m in bench['per_layer']:
        if m['name'] in NEW_METRICS:
            assert m['workloads'] == [NEW_CELL], m['name']


def test_new_files_import_nothing_of_the_program_at_module_level():
    new = [os.path.join(BENCH, 'harness', f) for f in
           ('lm_train.py', 'lm_reference.py', 'lm_counts.py',
            'lm_traffic.py')]
    new += [os.path.join(BENCH, 'layer_metrics', f)
            for f in os.listdir(os.path.join(BENCH, 'layer_metrics'))
            if f == '_lm_leaves.py' or f[:-3] in NEW_METRICS]
    assert len(new) == 4 + 1 + 6
    for path in new:
        for line in open(path):
            if line.startswith(('import ', 'from ')):
                assert 'se3_transformer_tpu' not in line, (path, line)


def test_accepted_cells_read_as_before_beside_a_program_without_the_decoder(
        step, tmp_path, monkeypatch):
    import se3_transformer_tpu  # noqa: F401  (the parent's imports too)
    trace_root = _write(step, str(tmp_path / 'trace'))
    for name in NEW_MODULES:
        monkeypatch.setitem(sys.modules, name, None)    # import raises
    with pytest.raises(ImportError):
        import se3_transformer_tpu.training.lm_loss  # noqa: F401
    cell = loader.load_cell('d4_onehead_train')
    reduced = {'device': {t: [r[:3] for r in rows]
                          for t, rows in step['device'].items()},
               'host': step['host']}
    lo, hi = step['window_ns']
    log = [dict(kind=k, fun_name='train_step', seconds=e - s, start=s, end=e)
           for k, s, e in (('jaxpr_trace', 0.0, 30.0), ('lower', 30.0, 40.0),
                           ('backend_compile', 40.0, 48.0))]
    ctx = dict(spans={'step_call': [0.005], 'loss_fetch': [0.9]},
               trace=trace.reduce(reduced, (hi - lo) * 1e-9),
               peaks={'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9},
               model=cell['config']['model'],
               memory_stats={'peak_bytes_reserved': 6 * 2**30},
               counters={'steps': step['steps']}, compile_log=log,
               trace_root=trace_root,
               shapes_run=[dict(nodes=1024, times=1, backward=True)])
    got = readers.read_all(cell, ctx)
    assert set(got) == D4_METRICS
    assert got['scope_coverage.train'] == pytest.approx(98.3258, abs=1e-3)
    # and the new cell's entry ends at once on such a program
    from harness import lm_train
    new = loader.load_cell(NEW_CELL)
    with pytest.raises(SystemExit, match='this program has no token decoder'):
        lm_train.program(new['config'])


def _decoder_step():
    """A fabricated device track with the decoder's leaves, 1 ms each; the
    row lookups end in the primitive's name, `gather`."""
    base = ('jit(train_step)/loss/transpose(jvp(loss))/'
            'TokenDecoder.hidden_states/loss/jvp(loss)/'
            'TokenDecoder.hidden_states/checkpoint/blocks_1/')
    paths = [base + 'attn/latent_qkv/q_a/dot_general',
             base + 'attn/latent_core/jit(flash_attention)/pallas_call',
             base + 'attn/latent_out/out/dot_general',
             base + 'moe/moe_router/router/dot_general',
             base + 'moe/moe_dispatch/gather',
             base + 'moe/moe_experts/ragged_dot',
             base + 'moe/moe_experts/ragged_dot',
             base + 'moe/moe_combine/gather',
             'jit(train_step)/loss/jvp(loss)/lm_head/dot_general',
             'jit(train_step)/optimizer/mul']
    rows = [[f'fusion.{i}', 1e6 * i, 1e6, p, None]
            for i, p in enumerate(paths)]
    return {'device': {'/device:TPU:0': rows}, 'host': [],
            'selector': 'xla_ops', 'op_name_source': 'metadata_stat:tf_op'}


def test_the_new_readers_on_a_step_with_the_decoders_leaves(tmp_path):
    cell = loader.load_cell(NEW_CELL)
    model = cell['config']['model']
    steps, pairs = 2, 2 * 5 * 4096
    ctx = dict(spans={'step_call': [0.003, 0.003]},
               trace={'busy_s': 0.010, 'window_s': 1.0, 'op_seconds': {}},
               peaks={'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9},
               model=model, traffic=cell['traffic'],
               memory_stats={'peak_bytes_reserved': 3 * 2**30},
               counters=dict(steps=steps, moe_local_pairs=pairs,
                             moe_load_max=2 * 900.0, moe_load_mean=2 * 512.0,
                             moe_dropped=0.0, expert_layer_steps=steps * 5),
               trace_root=_write(_decoder_step(), str(tmp_path / 'trace')))
    got = readers.read_all(cell, ctx)
    assert set(got) == NEW_METRICS | SHARED
    assert got['moe_experts_ms_per_step.train'] == pytest.approx(1.0)
    # the lookups of dispatch and combine are theirs, not the leaf `gather`'s
    assert got['moe_route_ms_per_step.train'] == pytest.approx(1.5)
    assert got['latent_attn_ms_per_step.train'] == pytest.approx(1.5)
    assert got['expert_load_max_over_mean.train'] == pytest.approx(900 / 512)
    from harness import lm_counts
    flops = lm_counts.grouped_flops(model, pairs)
    assert got['moe_experts_roofline.train'] == pytest.approx(
        100 * flops / 197e12 / 2e-3, rel=1e-6)
    step_flops = lm_counts.train_step_flops(model, 8192, pairs / steps)
    assert got['step_mfu.train'] == pytest.approx(
        100 * steps * step_flops / 1.0 / 197e12, rel=1e-6)
    assert got['latent_core_roofline.train'] == pytest.approx(
        100 * lm_counts.attention_core_train_flops(model, 8192, 2 * 6)
        / 197e12 / 1e-3, rel=1e-6)


def test_the_new_readers_read_nothing_from_a_step_without_the_decoder(
        step, tmp_path):
    """The parent's trace of the new cell does not exist, but a reader may
    meet a trace without its leaves, or none at all: None, never a raise."""
    cell = loader.load_cell(NEW_CELL)
    only = dict(cell, per_layer={n: cell['per_layer'][n]
                                 for n in NEW_METRICS})
    ctx = dict(counters={'steps': 1}, traffic=cell['traffic'],
               model=cell['config']['model'],
               peaks={'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9},
               trace_root=_write(step, str(tmp_path / 'trace')))
    assert readers.read_all(only, ctx) == {}
    ctx['trace_root'] = str(tmp_path / 'nothing')
    assert readers.read_all(only, ctx) == {}
