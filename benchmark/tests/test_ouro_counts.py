"""`harness/ouro_counts.py` against hand arithmetic: the table of the
configuration's cut (ISSUE 47) to the parameter, the module's own tree, the
whole published model, the core's work at the causal triangle, and the work
of a step over its four passes and four exits."""
import json
import os

import pytest

from harness import ouro_counts as oc

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    BENCH, 'configs', 'ouro-2.6b-loop4-train.json')))
M = CFG['model']


def test_the_cut_s_table_to_the_parameter():
    assert oc.attention_matrix_params(M) == 4 * 2048 ** 2 == 16_777_216
    assert oc.ff_params(M) == 3 * 2048 * 5632 == 34_603_008
    assert oc.layer_params(M) == 51_388_416
    assert [oc.layers(M, k) for k in '*F'] == [4, 4]
    assert oc.gate_params(M) == 2_049
    head_and_embedding = 2 * 49_152 * 2048
    assert head_and_embedding == 201_326_592
    total = oc.total_params(M)
    # four layers, the two matrices over the rows, the final norm, the gate;
    # the four passes add no parameter
    assert total == 4 * 51_388_416 + 201_326_592 + 2_048 + 2_049 \
        == 406_884_353
    assert oc.total_params(dict(M, total_ut_steps=1)) == total
    assert total * 16 / 1e9 == pytest.approx(6.51, abs=0.005)      # GB
    assert total * 16 / 2**30 == pytest.approx(6.06, abs=0.005)    # GiB
    assert total * 16 / (15.75 * 2**30) == pytest.approx(0.385, abs=0.005)
    # six layers would hold too, by the parameters
    six = dict(M, hybrid_override_pattern='*F' * 6)
    assert oc.total_params(six) / 1e6 == pytest.approx(509.7, abs=0.05)


def test_the_modules_own_tree_counts_the_same():
    import jax
    import jax.numpy as jnp

    from harness import state
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES[CFG['recipe']](**M, **CFG['overrides'])
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 16), jnp.int32))['params']
    assert state.param_count(params) == 406_884_353
    assert params['head']['kernel'].shape == (2048, 49152)
    assert params['embedding']['embedding'].shape == (49152, 2048)
    assert sorted(params['blocks_0']) == ['attn', 'post_norm', 'pre_norm']
    assert sorted(params['blocks_1']) == ['mlp', 'post_norm', 'pre_norm']
    assert sorted(params['blocks_0']['attn']) == ['k', 'out', 'q', 'v']
    for name in 'qkv':
        assert params['blocks_0']['attn'][name]['kernel'].shape \
            == (2048, 2048)
    assert params['blocks_1']['mlp']['gate']['kernel'].shape == (2048, 5632)
    assert params['exit_gate']['kernel'].shape == (2048, 1)
    assert params['exit_gate']['bias'].shape == (1,)
    assert sum(name.startswith('blocks_') for name in params) == 8


def test_the_whole_published_model_counts_its_published_size():
    """48 layers: 2.67 G parameters, the published 2.6B."""
    whole = dict(M, hybrid_override_pattern='*F' * 48)
    assert oc.total_params(whole) == 2_667_974_657
    assert M['hybrid_override_pattern'] == whole[
        'hybrid_override_pattern'][:8]
    # the configuration's top level: the published keys, the one cut
    assert (CFG['num_hidden_layers'], CFG['vocab_size'],
            CFG['total_ut_steps']) == (4, 49152, 4)
    assert CFG['layer_types'] == ['full_attention'] * 48
    assert (CFG['hidden_size'], CFG['head_dim'], CFG['num_attention_heads'],
            CFG['num_key_value_heads'], CFG['intermediate_size'],
            CFG['rope_theta'], CFG['rms_norm_eps'],
            CFG['max_position_embeddings'], CFG['early_exit_threshold']) == (
        2048, 128, 16, 16, 5632, 1000000, 1e-6, 65536, 1)


def test_the_core_is_priced_at_the_causal_triangle():
    assert oc.visible_pairs(8192) == 8192 * 8193 // 2 == 33_558_528
    core = oc.core_flops(M, 8192)
    assert core == 16 * 33_558_528 * 4 * 128
    # a token's share of a layer-pass, forward and of a step
    assert core / 8192 / 1e6 == pytest.approx(33.56, abs=0.01)
    assert 3 * core / 8192 / 1e6 == pytest.approx(100.7, abs=0.05)
    assert oc.core_train_flops(M, 8192, 16) == 48 * core
    t = 8192
    forward = 2 * t * 128 * (16 + 16 + 16 + 16) + 4 * t * 16
    backward = 2 * t * 128 * (4 * 16 + 4 * 16) + 4 * t * 16
    assert oc.core_bytes(M, 8192, 1) == forward + backward
    # bound by its operations, eight times over (groups of one: every
    # query head brings a key and a value head of its own)
    assert oc.core_train_flops(M, 8192, 1) / 197e12 \
        > 8 * oc.core_bytes(M, 8192, 1) / 819e9


def test_a_step_s_operations():
    """ISSUE 47's hand count, a token and step (3x forward): a layer-pass
    308.3 MFLOP of products and 100.7 of the triangle, 16 of them 6,544;
    four exits 2,416; 8.96 GFLOP a token, 73.4 TFLOP a step; products 55%,
    the core 18%, the exits 27%."""
    seq = 8192
    products = 16 * 6 * (oc.attention_matrix_params(M) + oc.ff_params(M))
    cores = 16 * 3 * oc.core_flops(M, seq) / seq
    exits = 4 * 3 * 2 * 49152 * 2048
    assert 6 * 51.38e6 / 1e6 == pytest.approx(308.3, abs=0.05)
    assert (products + cores) / 1e6 == pytest.approx(6544, abs=1)
    assert exits / 1e6 == pytest.approx(2416, abs=0.5)
    step = oc.train_step_flops(M, seq)
    assert step == pytest.approx(seq * (products + cores + exits), rel=1e-12)
    assert step / seq / 1e9 == pytest.approx(8.96, abs=0.005)
    assert step / 1e12 == pytest.approx(73.4, abs=0.05)
    shares = [round(100 * x / (products + cores + exits))
              for x in (products, cores, exits)]
    assert shares == [55, 18, 27]
    assert oc.forward_flops(M, seq) == 4 * (oc.pass_flops(M, seq)
                                            + oc.exit_flops(M, seq))
    # one pass alone is a quarter of it: the passes multiply the work and
    # nothing else
    assert oc.train_step_flops(dict(M, total_ut_steps=1), seq) * 4 == step
    # in the whole model the exits follow 48 layers, not 4: 3%
    whole = 12 * (products + cores) + exits
    assert exits / whole == pytest.approx(0.03, abs=0.003)
    # at 35 to 45% of the peak: 0.83 to 1.06 s a step
    assert step / 197e12 / 0.45 == pytest.approx(0.83, abs=0.005)
    assert step / 197e12 / 0.35 == pytest.approx(1.06, abs=0.005)
