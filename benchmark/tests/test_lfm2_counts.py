"""`harness/lfm2_counts.py` against hand arithmetic: the table of the
configuration's cut (ISSUE 34) to the parameter, the module's own tree, the
short convolution's and the attention core's work, and the work of a step."""
import json
import os

import pytest

from harness import lfm2_counts as lc

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    BENCH, 'configs', 'lfm2-24b-a2b-ep8-train.json')))
M = CFG['model']


def test_the_cut_s_table_to_the_parameter():
    p = lc.matrix_params(M)
    assert p['embedding'] == 8192 * 2048 == 16_777_216            # tied: once
    # W_in 2048 x 6144 and W_out 2048 x 2048, then three taps a channel
    assert p['conv'] == 12_582_912 + 4_194_304
    assert lc.conv_vector_params(M) == 6_144
    # q and out 2048 x 2048, k and v 2048 x 512, two scales of 64
    assert p['attention'] == 4_194_304 + 2 * 1_048_576 + 4_194_304
    assert lc.attention_vector_params(M) == 128
    assert p['dense_ff'] == 3 * 2048 * 11776 == 72_351_744
    assert lc.expert_params(M) == 3 * 2048 * 1536 == 9_437_184
    assert p['expert_layer'] == 131_072 + 8 * 9_437_184 == 75_628_544
    assert [lc.layers(M, k) for k in 'CF*E'] == [4, 1, 1, 4]
    assert lc.expert_layers(M) == 4
    # eleven norms of 2048, four sets of taps, one pair of q/k scales
    assert lc.vector_params(M) == 11 * 2048 + 4 * 6_144 + 128
    layer0 = 16_783_360 + 72_351_744 + 4_096
    layer2 = 10_485_888 + 75_628_544 + 4_096
    layer3 = 16_783_360 + 75_628_544 + 4_096
    assert (layer0, layer2, layer3) == (89_139_200, 86_118_528, 92_416_000)
    total = lc.total_params(M)
    assert total == 16_777_216 + layer0 + layer2 + 3 * layer3 + 2_048 \
        == 469_284_992
    assert lc.bias_entries(M) == 256
    assert total * 16 / 1e9 == pytest.approx(7.51, abs=0.01)      # GB
    assert total * 16 / 2**30 == pytest.approx(6.99, abs=0.01)    # GiB
    # two whole periods would not fit beside the activations
    assert (total + layer2 + 3 * layer3) / 1e6 == pytest.approx(832, abs=1)


def test_the_modules_own_tree_counts_the_same():
    import jax
    import jax.numpy as jnp

    from harness import state
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES[CFG['recipe']](**M, **CFG['overrides'])
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 16), jnp.int32))['params']
    assert state.param_count(params) == 469_284_992 + 256
    assert 'head' not in params
    assert params['blocks_0']['conv']['in_proj']['kernel'].shape \
        == (2048, 6144)
    assert params['blocks_2']['attn']['q_norm']['scale'].shape == (64,)


def test_the_whole_published_model_counts_its_published_size():
    """40 layers (30 C, 10 *; 2 dense feed-forwards and 38 expert layers),
    all 64 experts, the whole vocabulary: 23.8 G parameters, the published
    24B, of which a token meets 2.3 G (A2B)."""
    whole = dict(M, experts_held=64, vocab_rows=65536,
                 hybrid_override_pattern='CFCF*E' + 'CECECE*E' * 9 + 'CE')
    assert [lc.layers(whole, k) for k in 'CF*E'] == [30, 2, 10, 38]
    assert [t for t in CFG['layer_types']] == [
        {'C': 'conv', '*': 'full_attention'}[k]
        for k in whole['hybrid_override_pattern'] if k in 'C*']
    assert lc.total_params(whole) / 1e9 == pytest.approx(23.84, abs=0.01)
    active = lc.total_params(dict(whole, experts_held=4))
    assert active / 1e9 == pytest.approx(2.3, abs=0.05)
    # this cut is that model's layers 0 and 2 to 5
    assert M['hybrid_override_pattern'] == 'CF' + whole[
        'hybrid_override_pattern'][4:12]


def test_the_short_convolutions_core_is_bound_by_its_bytes():
    tokens = 2 * 8192
    # B * X, three multiply-adds, C * z: 8 operations a channel and token
    assert lc.sconv_core_flops(M, tokens) == tokens * 2048 * 8
    assert lc.sconv_core_train_flops(M, tokens, 4) \
        == 12 * lc.sconv_core_flops(M, tokens)
    # B, C, X, out forward; B, C, X, d out and three cotangents backward
    assert lc.sconv_core_bytes(M, tokens, 1) == 4 * tokens * 2048 * 11
    assert lc.sconv_core_bytes(M, tokens, 4) / 819e9 == pytest.approx(
        7.21e-3, rel=1e-2)
    assert lc.sconv_core_train_flops(M, tokens, 4) / 197e12 \
        == pytest.approx(1.6e-5, rel=5e-2)


def test_the_attention_core_s_count_is_the_hybrid_cells_at_these_heads():
    assert lc.attention_core_flops(M, 8192) == 32 * 8192 * 8192 * 2 * 64
    assert lc.attention_core_train_flops(M, 8192, 2) \
        == 6 * lc.attention_core_flops(M, 8192)
    assert lc.attention_core_bytes(M, 8192, 1) == 2 * 8192 * 32 * 64 * 12
    from harness import hybrid_counts
    assert lc.attention_core_flops(M, 8192) \
        == hybrid_counts.attention_core_flops(M, 8192)
    assert lc.attention_core_bytes(M, 8192, 3) \
        == hybrid_counts.attention_core_bytes(M, 8192, 3)


def test_a_step_s_operations():
    # per token, forward, in MFLOP: the issue's hand count
    seq, pairs = 8192, 4 * 4096          # 4 of 64, 8 held: 0.5 a token
    per_token = lc.forward_flops(M, seq, pairs) / seq / 1e6
    conv = 2 * 16.777216 + 2048 * 8 / 1e6
    dense = 2 * 72.351744
    attention = 2 * 10.48576 + 32 * 8192 * 2 * 64 / 1e6
    experts = 4 * (0.5 * 2 * 9.437184 + 2 * 0.131072)
    head = 2 * 2048 * 8192 / 1e6
    assert per_token == pytest.approx(
        4 * conv + dense + attention + experts + head, rel=1e-9)
    assert per_token == pytest.approx(406, abs=0.5)
    assert 4 * conv / per_token == pytest.approx(0.33, abs=0.01)
    assert dense / per_token == pytest.approx(0.36, abs=0.01)
    assert attention / per_token == pytest.approx(0.13, abs=0.01)
    assert experts / per_token == pytest.approx(0.10, abs=0.01)
    assert head / per_token == pytest.approx(0.08, abs=0.01)
    # a step of two sequences
    assert 2 * lc.train_step_flops(M, seq, pairs) / 1e12 == pytest.approx(
        19.95, abs=0.01)
