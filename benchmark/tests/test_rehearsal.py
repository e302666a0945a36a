"""A cell, a configuration, a traffic mix and a per-layer metric are each
added as new files plus one entry, and the harness runs the new cells on the
CPU at a tiny size. The chip requirement is stubbed here, in the test, never
by an option of run.py. One run has the timed path broken underneath (a step
that returns its state unchanged) and must come out not correct."""
import importlib
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_MODEL = {"dim": 8, "depth": 1, "num_degrees": 4, "heads": 2,
              "dim_head": 4, "one_headed_key_values": True,
              "num_neighbors": 6, "input_degrees": 1, "output_degrees": 2}


def _tiny_copy(tmp_path):
    root = tmp_path / 'checkout'
    root.mkdir()
    shutil.copytree(BENCH, root / 'benchmark',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    os.symlink(os.path.join(ROOT, 'se3_transformer_tpu'),
               root / 'se3_transformer_tpu')
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    b = root / 'benchmark'
    # a configuration file
    cfg = json.load(open(b / 'configs' / 'd4-onehead-train.json'))
    cfg.update(
        name='tiny-train',
        overrides=dict(cfg['overrides'], dim=8, depth=1, heads=2, dim_head=4,
                       num_neighbors=6, radial_bf16=False),
        model=TINY_MODEL, reference={"block": 8},
        correct={"check_steps": 3, "loss_rel_gap": 1e-3,
                 "grad_leaf_gap": 2e-2, "grad_rel_diff": 2e-2,
                 "delta_leaf_gap": 2e-2})
    json.dump(cfg, open(b / 'configs' / 'tiny-train.json', 'w'))
    # a second one, through a recipe of the program
    cfg2 = dict(cfg, name='tiny-recipe', recipe='flagship_fast',
                overrides={"dim": 8, "depth": 1, "num_neighbors": 6,
                           "output_degrees": 2, "reduce_dim_out": True,
                           "radial_bf16": False},
                model={"dim": 8, "depth": 1, "num_degrees": 4, "heads": 8,
                       "dim_head": 8, "num_neighbors": 6, "input_degrees": 1,
                       "output_degrees": 2})
    json.dump(cfg2, open(b / 'configs' / 'tiny-recipe.json', 'w'))
    # a traffic file
    json.dump({"kind": "train_closed", "nodes": 24, "batch": 1,
               "trace_steps": 2}, open(b / 'traffic' / 'tiny_train.json', 'w'))
    # a per-layer metric file, read from a host span
    json.dump({"layer": "trainer", "moves": "train_node_steps_per_s",
               "reader": {"source": "host_span", "span": "loss_fetch",
                          "arith": "p50_ms"}},
              open(b / 'layer_metrics' / 'loss_fetch_p50_ms.tiny.json', 'w'))
    # and the entries
    bench['configs'] += [
        {"name": "tiny-train", "source": "test",
         "file": "benchmark/configs/tiny-train.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-recipe", "source": "test",
         "file": "benchmark/configs/tiny-recipe.json", "reduced": [],
         "why": "test"}]
    bench['workloads'] += [
        {"name": "tiny_train", "config": "tiny-train",
         "traffic": "tiny_train", "chips": 1, "why": "test"},
        {"name": "tiny_recipe", "config": "tiny-recipe",
         "traffic": "tiny_train", "chips": 1, "why": "test"}]
    for m in bench['end_to_end']:
        if m['name'] == 'train_node_steps_per_s':
            m['workloads'] += ['tiny_train', 'tiny_recipe']
    bench['per_layer'].append(
        {"name": "loss_fetch_p50_ms.tiny", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "trainer",
         "moves": "train_node_steps_per_s", "workloads": ["tiny_train"]})
    json.dump(bench, open(root / 'BENCHMARK.json', 'w'))
    return root


@pytest.fixture
def tiny(tmp_path, monkeypatch, capsys):
    """A temporary checkout with the tiny cells, its run.py imported, and the
    look for a chip stubbed to hand over the CPU under a known kind."""
    import jax
    root = _tiny_copy(tmp_path)
    for name in [n for n in sys.modules
                 if n == 'harness' or n.startswith('harness.')]:
        monkeypatch.delitem(sys.modules, name)
    monkeypatch.syspath_prepend(str(root / 'benchmark'))
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR',
                       str(tmp_path / 'jit_cache'))
    spec = importlib.util.spec_from_file_location(
        'tiny_run', root / 'benchmark' / 'run.py')
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from harness import device, peaks
    monkeypatch.setattr(
        device, 'require_accelerator',
        lambda chips: (jax.devices()[:chips], 'TPU v5 lite',
                       peaks.peaks_for('TPU v5 lite')))

    def go(workload, seconds, seed=2**31 + 12345):
        run.main(['--workload', workload, '--seed', str(seed),
                  '--seconds', str(seconds), '--trace', '0'])
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return go, monkeypatch


def test_new_train_cell_as_data(tiny):
    go, _ = tiny
    line, out = go('tiny_train', 1.0)
    assert line['correct'] is True, out
    assert set(line['metrics']) == {'train_node_steps_per_s', 'setup_s'}
    assert line['attempted'] >= 1 and line['failed'] == 0
    assert set(line) == {'correct', 'attempted', 'failed', 'metrics',
                         'device'}


def test_cell_through_a_recipe_and_the_metric_added_as_a_file(tiny):
    go, _ = tiny
    line, out = go('tiny_recipe', 0.5)
    assert line['correct'] is True, out
    from harness import loader
    cell = loader.load_cell('tiny_train', root=loader.ROOT)
    assert 'loss_fetch_p50_ms.tiny' in cell['per_layer']
    # a metric without `workloads` is read in every cell that reports what
    # it moves, the new one too
    assert 'step_dispatch_ms.train' in cell['per_layer']
    assert 'kernels_roofline.train' not in cell['per_layer']


def test_step_that_returns_its_state_unchanged_is_not_correct(tiny):
    go, monkeypatch = tiny
    from se3_transformer_tpu.parallel import sharding

    def broken(loss_fn, optimizer, **kw):
        import jax

        def step(params, opt_state, batch, rng):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, rng)
            _, opt_state = optimizer.update(grads, opt_state, params)
            return params, opt_state, loss, aux     # the update is lost
        return jax.jit(step)

    monkeypatch.setattr(sharding, 'make_sharded_train_step', broken)
    line, out = go('tiny_train', 0.5)
    assert line['correct'] is False, out
    assert 'param_change_worst_leaf_gap' in out
