"""The token decoder's entry (`harness/lm_train.py`) rehearsed on the CPU at a
tiny size, with the look for a chip stubbed here, in the test: the cell comes
out correct with its counters; a program without the token decoder ends the
cell at once; a step that returns its state unchanged is not correct."""
import importlib
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = dict(vocab_rows=48, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_hidden_layers=2,
            first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=12,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=12, n_routed_experts=8, n_shared_experts=1,
            num_experts_per_tok=2, experts_held=4, expert_rank=0,
            routed_scaling_factor=1.8, norm_topk_prob=True,
            num_nextn_predict_layers=1, rope_theta=1e6, rms_norm_eps=1e-5)


def _tiny_copy(tmp_path):
    root = tmp_path / 'checkout'
    root.mkdir()
    shutil.copytree(BENCH, root / 'benchmark',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    os.symlink(os.path.join(ROOT, 'se3_transformer_tpu'),
               root / 'se3_transformer_tpu')
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    b = root / 'benchmark'
    cfg = json.load(open(b / 'configs' / 'glm47-flash-ep8-train.json'))
    cfg.update(
        name='tiny-lm', model=TINY,
        overrides=dict(attention_block=8, bf16_operands=False),
        loss=dict(mtp_weight=0.3, chunk=8),
        reference=dict(attention_block=8, chunk=8),
        correct=dict(check_steps=3, loss_rel_gap=1e-4, grad_leaf_gap=1e-3,
                     grad_rel_diff=1e-3, delta_leaf_gap=1e-2,
                     choice_mismatch_share=0.0))
    json.dump(cfg, open(b / 'configs' / 'tiny-lm.json', 'w'))
    json.dump({"kind": "lm_train_closed", "seq": 16, "batch": 2,
               "n_batches": 3, "zipf_exponent": 1.1,
               "document_tokens": {"median": 6, "sigma": 1.2},
               "trace_steps": 2}, open(b / 'traffic' / 'tiny_lm.json', 'w'))
    bench['configs'].append(
        {"name": "tiny-lm", "source": "test",
         "file": "benchmark/configs/tiny-lm.json", "reduced": [],
         "why": "test"})
    bench['workloads'].append(
        {"name": "tiny_lm", "config": "tiny-lm", "traffic": "tiny_lm",
         "chips": 1, "why": "test"})
    for m in bench['end_to_end']:
        if m['name'] == 'train_node_steps_per_s':
            m['workloads'].append('tiny_lm')
    json.dump(bench, open(root / 'BENCHMARK.json', 'w'))
    return root


@pytest.fixture
def tiny(tmp_path, monkeypatch, capsys):
    import jax
    root = _tiny_copy(tmp_path)
    for name in [n for n in sys.modules
                 if n == 'harness' or n.startswith('harness.')]:
        monkeypatch.delitem(sys.modules, name)
    monkeypatch.syspath_prepend(str(root / 'benchmark'))
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR',
                       str(tmp_path / 'jit_cache'))
    keep = jax.config.jax_compilation_cache_dir
    spec = importlib.util.spec_from_file_location(
        'tiny_lm_run', root / 'benchmark' / 'run.py')
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from harness import device, peaks
    monkeypatch.setattr(
        device, 'require_accelerator',
        lambda chips: (jax.devices()[:chips], 'TPU v5 lite',
                       peaks.peaks_for('TPU v5 lite')))

    def go(seconds=0.5, seed=2**31 + 4242):
        run.main(['--workload', 'tiny_lm', '--seed', str(seed),
                  '--seconds', str(seconds), '--trace', '0'])
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    yield go, monkeypatch, root
    jax.config.update('jax_compilation_cache_dir', keep)


def test_the_entry_runs_a_tiny_cell_and_keeps_its_own_caches(tiny):
    go, _, root = tiny
    line, out = go()
    assert line['correct'] is True, out
    assert set(line['metrics']) == {'train_node_steps_per_s', 'setup_s'}
    assert line['attempted'] >= 1 and line['failed'] == 0
    for check in ('first_grad_rel_l2_diff', 'choice_mismatch_share',
                  'moe_dropped_is_zero', 'no_compile_in_window'):
        assert f'check {check}' in out, check
    # the step's and the reference's executables, each in its own directory
    assert os.path.isdir(root / '.jax_cache' / 'lm_train')
    assert os.path.isdir(root / '.jax_cache' / 'lm_reference')
    assert 'pairs a step' in out


def test_a_program_without_the_token_decoder_ends_the_cell_at_once(tiny):
    go, monkeypatch, _ = tiny
    from se3_transformer_tpu.training import recipes
    monkeypatch.delitem(recipes.RECIPES, 'token_decoder')
    with pytest.raises(SystemExit, match='this program has no token decoder'):
        go()
    monkeypatch.setitem(sys.modules, 'se3_transformer_tpu.training.lm_loss',
                        None)
    with pytest.raises(SystemExit, match='this program has no token decoder'):
        go()


def test_step_that_returns_its_state_unchanged_is_not_correct(tiny):
    go, monkeypatch, _ = tiny
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
    line, out = go()
    assert line['correct'] is False, out
    assert 'param_change_worst_leaf_gap' in out
