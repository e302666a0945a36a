"""The entry of the decoder that mixes global and sliding-window attention
layers (`harness/smallthinker_train.py`) rehearsed on the CPU at a tiny size,
with the look for a chip stubbed here, in the test: the cell comes out
correct with its counters and its line about the two cores; the control
(every learned operand rounded to float8_e4m3fn) does not; a program without
the recipe ends the cell at once in one line."""
import importlib
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = dict(vocab_rows=48, hidden_size=32,
            hybrid_override_pattern='*EWEWEWE', moe_intermediate_size=16,
            n_routed_experts=8, num_experts_per_tok=2, experts_held=4,
            expert_rank=0, mlp_hidden_act='relu', scoring_func='softmax',
            routed_scaling_factor=1.0, norm_topk_prob=True,
            norm_topk_eps=1e-20, moe_enable_early_router=True,
            num_attention_heads=6, num_key_value_heads=2, head_dim=8,
            qk_norm=False, rope_theta=None, sliding_window_size=5,
            sliding_rope_theta=1500000.0, layer_norm_epsilon=1e-6,
            tie_word_embeddings=False)
LIMITS = dict(check_steps=3, loss_rel_gap=1e-4, grad_leaf_gap=1e-3,
              grad_rel_diff=1e-3, delta_leaf_gap=1e-2,
              choice_mismatch_share=0.0)


def _tiny_copy(tmp_path):
    root = tmp_path / 'checkout'
    root.mkdir()
    shutil.copytree(BENCH, root / 'benchmark',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    os.symlink(os.path.join(ROOT, 'se3_transformer_tpu'),
               root / 'se3_transformer_tpu')
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    b = root / 'benchmark'
    cfg = json.load(open(b / 'configs' / 'smallthinker-21b-a3b-swa-train.json'))
    cfg.update(
        name='tiny-smallthinker', model=TINY,
        overrides=dict(attention_block=8, bf16_operands=False),
        loss=dict(chunk=8),
        reference=dict(attn_block=8, chunk=8), correct=LIMITS)
    json.dump(cfg, open(b / 'configs' / 'tiny-smallthinker.json', 'w'))
    json.dump({"kind": "lm_train_closed", "seq": 16, "batch": 2,
               "n_batches": 3, "zipf_exponent": 1.1,
               "document_tokens": {"median": 6, "sigma": 1.2},
               "trace_steps": 2},
              open(b / 'traffic' / 'tiny_smallthinker.json', 'w'))
    bench['configs'].append(
        {"name": "tiny-smallthinker", "source": "test",
         "file": "benchmark/configs/tiny-smallthinker.json", "reduced": [],
         "why": "test"})
    bench['workloads'].append(
        {"name": "tiny_smallthinker", "config": "tiny-smallthinker",
         "traffic": "tiny_smallthinker", "chips": 1, "why": "test"})
    for m in bench['end_to_end']:
        if m['name'] == 'train_node_steps_per_s':
            m['workloads'].append('tiny_smallthinker')
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
        'tiny_smallthinker_run', root / 'benchmark' / 'run.py')
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from harness import device, peaks
    monkeypatch.setattr(
        device, 'require_accelerator',
        lambda chips: (jax.devices()[:chips], 'TPU v5 lite',
                       peaks.peaks_for('TPU v5 lite')))

    def go(seconds=0.5, seed=2**31 + 4242):
        run.main(['--workload', 'tiny_smallthinker', '--seed', str(seed),
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
    assert os.path.isdir(root / '.jax_cache' / 'smallthinker_train')
    assert os.path.isdir(root / '.jax_cache' / 'smallthinker_reference')
    assert 'pairs a step' in out and 'moe_bounded over the' in out
    # once, each core's visible pairs a head: the triangle of 16 and a
    # window of 5 (16 x 5 - 10); off the TPU the launches do not run
    assert out.count('cores: global') == 1
    assert '136 visible pairs' in out and '70 pairs a head' in out
    assert 'the launches do not run here' in out


def test_the_fp8_operand_control_comes_out_not_correct(tiny):
    """The reference with every learned operand rounded to float8_e4m3fn,
    held to the tiny cell's limits in the program's place."""
    go, _, root = tiny
    from harness import (
        loader, smallthinker_reference, smallthinker_train as T, spans,
    )
    cell = loader.load_cell('tiny_smallthinker', root=str(root))
    built = T.build(cell, 2**31 + 7, T.program(cell['config']))
    numbers = T.first_steps(built, 3, spans.Spans())
    inputs = {k: built[k] for k in T.INPUTS}
    ref = T.reference_steps(cell, inputs, 3)
    assert T.compare(numbers, ref, LIMITS).ok
    ctl = T.reference_steps(cell, inputs, 3,
                            operand_bits=smallthinker_reference.FP8_E4M3)
    assert not T.compare(ctl, ref, LIMITS).ok
    # and by more than rounding: at limits as wide as the chip cell's
    wide = dict(LIMITS, loss_rel_gap=7e-4, grad_leaf_gap=0.17,
                grad_rel_diff=8e-2, delta_leaf_gap=1.4e-2,
                choice_mismatch_share=8e-2)
    assert not T.compare(ctl, ref, wide).ok


def test_a_program_without_the_recipe_ends_the_cell_at_once(tiny):
    go, monkeypatch, _ = tiny
    from se3_transformer_tpu.training import recipes
    monkeypatch.delitem(recipes.RECIPES, 'smallthinker_decoder')
    with pytest.raises(SystemExit, match="recipe 'smallthinker_decoder'"):
        go()
