"""The entry of the looped decoder (`harness/ouro_train.py`) rehearsed on
the CPU at a tiny size, with the look for a chip stubbed here, in the test:
the cell comes out correct in float32 with its exits' checks and its line
about the passes; the control (every learned operand rounded to
float8_e4m3fn) does not; a program without the recipe ends the cell at once
in one line."""
import importlib
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = dict(vocab_rows=48, hidden_size=32, hybrid_override_pattern='*F*F',
            intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=4, head_dim=8, qk_norm=False,
            rope_theta=1000000.0, layer_norm_epsilon=1e-6,
            tie_word_embeddings=False, sandwich_norm=True, total_ut_steps=4)
LIMITS = dict(check_steps=3, loss_rel_gap=1e-4, grad_leaf_gap=1e-3,
              grad_rel_diff=1e-3, delta_leaf_gap=1e-2, loss_ut_rel_gap=1e-4,
              exit_share_rel_gap=1e-4)


def _tiny_copy(tmp_path):
    root = tmp_path / 'checkout'
    root.mkdir()
    shutil.copytree(BENCH, root / 'benchmark',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    os.symlink(os.path.join(ROOT, 'se3_transformer_tpu'),
               root / 'se3_transformer_tpu')
    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    b = root / 'benchmark'
    cfg = json.load(open(b / 'configs' / 'ouro-2.6b-loop4-train.json'))
    cfg.update(
        name='tiny-ouro', model=TINY,
        overrides=dict(attention_block=8, bf16_operands=False),
        loss=dict(chunk=8, beta=0.1),
        reference=dict(attn_block=8, chunk=8), correct=LIMITS)
    json.dump(cfg, open(b / 'configs' / 'tiny-ouro.json', 'w'))
    json.dump({"kind": "lm_train_closed", "seq": 16, "batch": 2,
               "n_batches": 3, "zipf_exponent": 1.1,
               "document_tokens": {"median": 6, "sigma": 1.2},
               "trace_steps": 2},
              open(b / 'traffic' / 'tiny_ouro.json', 'w'))
    bench['configs'].append(
        {"name": "tiny-ouro", "source": "test",
         "file": "benchmark/configs/tiny-ouro.json", "reduced": [],
         "why": "test"})
    bench['workloads'].append(
        {"name": "tiny_ouro", "config": "tiny-ouro",
         "traffic": "tiny_ouro", "chips": 1, "why": "test"})
    for m in bench['end_to_end']:
        if m['name'] == 'train_node_steps_per_s':
            m['workloads'].append('tiny_ouro')
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
        'tiny_ouro_run', root / 'benchmark' / 'run.py')
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from harness import device, peaks
    monkeypatch.setattr(
        device, 'require_accelerator',
        lambda chips: (jax.devices()[:chips], 'TPU v5 lite',
                       peaks.peaks_for('TPU v5 lite')))

    def go(seconds=0.5, seed=2**31 + 4242):
        run.main(['--workload', 'tiny_ouro', '--seed', str(seed),
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
    for check in ('first_grad_rel_l2_diff', 'loss_ut_pass1_rel_gap',
                  'loss_ut_pass4_rel_gap', 'exit_share_pass1_rel_gap',
                  'exit_share_pass4_rel_gap', 'no_compile_in_window'):
        assert f'check {check}' in out, check
    assert 'choice_mismatch' not in out and 'moe_' not in out
    assert os.path.isdir(root / '.jax_cache' / 'ouro_train')
    assert os.path.isdir(root / '.jax_cache' / 'ouro_reference')
    assert 'run 4 times a step' in out
    assert 'share of the mass a pass' in out
    assert 'the last pass takes' in out


def test_the_fp8_operand_control_comes_out_not_correct(tiny):
    """The reference with every learned operand rounded to float8_e4m3fn,
    held to the tiny cell's limits in the program's place."""
    go, _, root = tiny
    from harness import loader, ouro_reference, ouro_train as T, spans
    cell = loader.load_cell('tiny_ouro', root=str(root))
    built = T.build(cell, 2**31 + 7, T.program(cell['config']))
    numbers = T.first_steps(built, 3, spans.Spans())
    assert len(numbers['loss_ut']) == len(numbers['exit_share']) == 4
    assert abs(sum(numbers['exit_share']) - 1.0) < 1e-5
    inputs = {k: built[k] for k in T.INPUTS}
    ref = T.reference_steps(cell, inputs, 3)
    assert T.compare(numbers, ref, LIMITS).ok
    ctl = T.reference_steps(cell, inputs, 3,
                            operand_bits=ouro_reference.FP8_E4M3)
    assert not T.compare(ctl, ref, LIMITS).ok
    # and by more than rounding: at limits as wide as the chip cell's
    wide = json.load(open(os.path.join(
        BENCH, 'configs', 'ouro-2.6b-loop4-train.json')))['correct']
    assert wide['grad_rel_diff'] > 10 * LIMITS['grad_rel_diff']
    assert not T.compare(ctl, ref, wide).ok


def test_a_program_without_the_recipe_ends_the_cell_at_once(tiny):
    go, monkeypatch, _ = tiny
    from se3_transformer_tpu.training import recipes
    monkeypatch.delitem(recipes.RECIPES, 'ouro_decoder')
    with pytest.raises(SystemExit, match="recipe 'ouro_decoder'"):
        go()
