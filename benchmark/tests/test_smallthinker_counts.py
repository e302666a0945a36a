"""`harness/smallthinker_counts.py` against hand arithmetic: the table of the
configuration's cut (ISSUE 44) to the parameter, the module's own tree, the
whole published model, each core's work at its visible pairs, and the work
of a step."""
import json
import os

import pytest

from harness import smallthinker_counts as sc

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    BENCH, 'configs', 'smallthinker-21b-a3b-swa-train.json')))
M = CFG['model']


def test_the_cut_s_table_to_the_parameter():
    p = sc.matrix_params(M)
    assert p['embedding'] == p['head'] == 37984 * 2560 == 97_239_040
    # q and out 2560 x 3584, k and v 2560 x 512; no q/k norms
    assert p['attention'] == 2 * 9_175_040 + 2 * 1_310_720
    assert sc.router_params(M) == 163_840
    assert sc.expert_params(M) == 3 * 2560 * 768 == 3 * 1_966_080
    assert p['expert_layer'] == 163_840 + 16 * 5_898_240
    assert [sc.layers(M, k) for k in '*WE'] == [1, 3, 4]
    assert sc.layers(M, '*W') == sc.expert_layers(M) == 4
    assert sc.vector_params(M) == 9 * 2560
    layer = p['attention'] + 2 * 2560 + p['expert_layer']
    assert layer == 115_512_320
    total = sc.total_params(M)
    assert total == 4 * layer + 2 * 97_239_040 + 2_560 == 656_529_920
    assert sc.bias_entries(M) == 256
    assert total * 16 / 1e9 == pytest.approx(10.50, abs=0.01)      # GB
    assert total * 16 / 2**30 == pytest.approx(9.78, abs=0.01)     # GiB
    assert total * 16 / (15.75 * 2**30) == pytest.approx(0.62, abs=0.005)
    # a fifth layer does not fit beside a 16k step, and a period is four
    five = total + layer
    assert five * 16 / 1e9 == pytest.approx(12.35, abs=0.01)
    # the one fallback (8 chips a layer: 8 experts held, an eighth of the
    # vocabulary), not taken: the step compiled at 14.06 GiB and the chip
    # held it
    fallback = dict(M, experts_held=8, vocab_rows=18992)
    assert sc.total_params(fallback) == 370_547_200


def test_the_modules_own_tree_counts_the_same():
    import jax
    import jax.numpy as jnp

    from harness import state
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES[CFG['recipe']](**M, **CFG['overrides'])
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 16), jnp.int32))['params']
    assert state.param_count(params) == 656_529_920 + 256
    assert params['head']['kernel'].shape == (2560, 37984)
    assert sorted(params['blocks_0']['attn']) == ['k', 'out', 'q', 'v']
    assert sorted(params['blocks_2']['attn']) == ['k', 'out', 'q', 'v']
    assert params['blocks_0']['attn']['q']['kernel'].shape == (2560, 3584)
    assert params['blocks_0']['attn']['k']['kernel'].shape == (2560, 512)
    assert params['blocks_1']['moe']['experts_gate'].shape == (16, 2560, 768)
    assert params['blocks_1']['moe']['router']['kernel'].shape == (2560, 64)
    assert 'shared' not in params['blocks_1']['moe']


def test_the_whole_published_model_counts_its_published_size():
    """52 layers, all 64 experts, the whole vocabulary: 21.5 G parameters,
    the published 21B, of which a token meets 3.7 G, 3.3 G beside the
    embedding's rows (A3B)."""
    whole = dict(M, experts_held=64, vocab_rows=151936,
                 hybrid_override_pattern='*EWEWEWE' * 13)
    assert sc.total_params(whole) == 21_506_562_560
    active = sc.total_params(dict(whole, experts_held=6))
    assert active / 1e9 == pytest.approx(3.72, abs=0.01)
    assert (active - 151936 * 2560) / 1e9 == pytest.approx(3.33, abs=0.01)
    assert M['hybrid_override_pattern'] == whole[
        'hybrid_override_pattern'][:8]
    # the configuration's top level: the published keys, the three cut
    assert (CFG['num_hidden_layers'], CFG['moe_num_primary_experts'],
            CFG['vocab_size']) == (4, 16, 37984)
    assert CFG['sliding_window_layout'][:4] == CFG['rope_layout'][:4] \
        == [0, 1, 1, 1] and len(CFG['rope_layout']) == 52
    assert (CFG['hidden_size'], CFG['head_dim'], CFG['num_attention_heads'],
            CFG['num_key_value_heads'], CFG['moe_ffn_hidden_size'],
            CFG['moe_num_active_primary_experts'],
            CFG['sliding_window_size'], CFG['rope_theta'],
            CFG['max_position_embeddings']) == (
        2560, 128, 28, 4, 768, 6, 4096, 1500000, 16384)


def test_each_core_is_priced_at_its_visible_pairs():
    assert sc.visible_pairs(16384) == 16384 * 16385 // 2 == 134_225_920
    assert sc.visible_pairs(16384, 4096) == 58_722_304
    assert sc.visible_pairs(16384, 4096) == 16384 * 4096 - 4096 * 4095 // 2
    assert sc.visible_pairs(16, 5) == 70 and sc.visible_pairs(16, 99) == 136
    glob, slide = (sc.core_flops(M, 16384, k) for k in '*W')
    assert glob == 28 * 134_225_920 * 4 * 128
    assert slide == 28 * 58_722_304 * 4 * 128
    assert slide / glob == pytest.approx(0.4375, abs=0.0005)
    # a token's share, forward: 117.4 and 51.4 MFLOP
    assert glob / 16384 / 1e6 == pytest.approx(117.4, abs=0.05)
    assert slide / 16384 / 1e6 == pytest.approx(51.4, abs=0.05)
    assert sc.core_train_flops(M, 16384, 'W', 3) == 9 * slide
    t = 16384
    forward = 2 * t * 128 * (28 + 28 + 4 + 4) + 4 * t * 28
    backward = 2 * t * 128 * (4 * 28 + 4 * 4) + 4 * t * 28
    assert sc.core_bytes(M, 16384, 1) == forward + backward
    # both bound by their operations, by far
    for kind in '*W':
        assert sc.core_train_flops(M, 16384, kind, 1) / 197e12 \
            > 10 * sc.core_bytes(M, 16384, 1) / 819e9


def test_a_step_s_operations():
    """ISSUE 44's hand count, a token forward: the two cores 271.5 MFLOP
    (38%), the projections 167.8 (24%), the head 194.5 (28%), the held
    experts 70.8 (10%), 706 in all."""
    seq = 16384
    pairs = 4 * seq * 6 * 16 // 64          # balanced: 16 of 64 held
    assert pairs == 4 * 24576 and pairs // (4 * 16) == 1536
    fwd = sc.forward_flops(M, seq, pairs)
    cores = sc.core_flops(M, seq, '*') + 3 * sc.core_flops(M, seq, 'W')
    projections = 4 * 2 * seq * sc.attention_matrix_params(M)
    experts = 2 * pairs * sc.expert_params(M)
    routers = 4 * 2 * seq * sc.router_params(M)
    head = 2 * seq * 2560 * 37984
    assert fwd == cores + projections + experts + routers + head
    per_token = [x / seq / 1e6 for x in (cores, projections, head, experts)]
    assert per_token == pytest.approx([271.6, 167.8, 194.5, 70.8], abs=0.06)
    assert fwd / seq / 1e6 == pytest.approx(706, abs=0.5)
    assert [round(100 * x / fwd) for x in (cores, projections, head,
                                           experts)] == [38, 24, 28, 10]
    assert sc.train_step_flops(M, seq, pairs) == 3 * fwd
    assert 3 * fwd / 1e12 == pytest.approx(34.7, abs=0.05)
    # in the whole model a token finds all 6 of its experts and the whole
    # vocabulary's head follows thirteen such periods: the cores' share is
    # 35%, the head's 8%
    whole = cores + projections + routers + 4 * experts + 4 * head / 13
    assert cores / whole == pytest.approx(0.35, abs=0.01)
    assert 4 * head / 13 / whole == pytest.approx(0.08, abs=0.005)
