"""`harness/sdar_counts.py` against hand arithmetic: the table of the
configuration's cut (ISSUE 40) to the parameter, the module's own tree, the
whole published model, the block-diffusion core's work at its visible pairs,
and the work of a step."""
import json
import os

import pytest

from harness import sdar_counts as sc

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    BENCH, 'configs', 'sdar-30b-a3b-ep8-train.json')))
M = CFG['model']
BK = CFG['loss']['block_length']


def test_the_cut_s_table_to_the_parameter():
    p = sc.matrix_params(M)
    assert p['embedding'] == p['head'] == 18992 * 2048 == 38_895_616
    # q and out 2048 x 4096, k and v 2048 x 512, two scales of 128
    assert p['attention'] == 8_388_608 + 2 * 1_048_576 + 8_388_608
    assert sc.attention_vector_params(M) == 256
    assert sc.router_params(M) == 262_144
    assert sc.expert_params(M) == 3 * 2048 * 768 == 3 * 1_572_864
    assert p['expert_layer'] == 262_144 + 16 * 4_718_592
    assert [sc.layers(M, k) for k in '*E'] == [5, 5]
    assert sc.expert_layers(M) == 5
    # eleven norms of 2048, five pairs of q/k scales
    assert sc.vector_params(M) == 11 * 2048 + 5 * 256
    layer = p['attention'] + 256 + 2 * 2048 + p['expert_layer']
    assert layer == 94_638_336
    total = sc.total_params(M)
    assert total == 5 * layer + 2 * 38_895_616 + 2_048 == 550_984_960
    assert sc.bias_entries(M) == 640
    assert total * 16 / 1e9 == pytest.approx(8.82, abs=0.01)      # GB
    assert total * 16 / 2**30 == pytest.approx(8.21, abs=0.01)    # GiB
    assert total * 16 / (15.75 * 2**30) == pytest.approx(0.52, abs=0.005)
    # a sixth layer would leave under 6.2 GiB for the step
    six = total + layer
    assert six == 645_623_296
    assert six * 16 / 1e9 == pytest.approx(10.33, abs=0.01)
    assert 15.75 - six * 16 / 2**30 < 6.2


def test_the_modules_own_tree_counts_the_same():
    import jax
    import jax.numpy as jnp

    from harness import state
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES[CFG['recipe']](**M, **CFG['overrides'])
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 16), jnp.int32))['params']
    assert state.param_count(params) == 550_984_960 + 640
    assert params['head']['kernel'].shape == (2048, 18992)
    assert params['blocks_0']['attn']['q_norm']['scale'].shape == (128,)
    assert params['blocks_0']['attn']['k']['kernel'].shape == (2048, 512)
    assert params['blocks_1']['moe']['experts_gate'].shape == (16, 2048, 768)
    assert params['blocks_1']['moe']['router']['kernel'].shape == (2048, 128)
    assert 'shared' not in params['blocks_1']['moe']


def test_the_whole_published_model_counts_its_published_size():
    """48 layers, all 128 experts, the whole vocabulary: 30.5 G parameters,
    the published 30B, of which a token meets 3.3 G (A3B)."""
    whole = dict(M, experts_held=128, vocab_rows=151936,
                 hybrid_override_pattern='*E' * 48)
    assert sc.total_params(whole) / 1e9 == pytest.approx(30.5, abs=0.05)
    active = sc.total_params(dict(whole, experts_held=8))
    assert active / 1e9 == pytest.approx(3.3, abs=0.1)
    assert M['hybrid_override_pattern'] == whole[
        'hybrid_override_pattern'][:10]
    assert (CFG['num_hidden_layers'], CFG['num_experts'], CFG['depth'],
            CFG['experts_held']) == (48, 128, 5, 16)


def test_the_core_is_priced_at_its_visible_pairs():
    assert sc.visible_pairs(8192, BK) == 8192 ** 2 + 8192 * 4 == 67_141_632
    assert sc.visible_pairs(16, 4) == 320
    one = sc.bd_core_flops(M, 8192, BK)
    assert one == 32 * 67_141_632 * 4 * 128
    assert one / 1e12 == pytest.approx(1.100, abs=0.0005)
    # half of a causal core over both streams' 16,384 positions
    causal = 32 * (16384 * 16385 // 2) * 4 * 128
    assert one / causal == pytest.approx(0.5, abs=0.001)
    assert sc.bd_core_train_flops(M, 8192, BK, 5) == 15 * one
    # forward: q, o at 32 heads and k, v at 4 in bfloat16, the float32
    # log-sum-exp; backward: q, o, do, dq and k, v, dk, dv, the log-sum-exp
    t = 16384
    forward = 2 * t * 128 * (32 + 32 + 4 + 4) + 4 * t * 32
    backward = 2 * t * 128 * (4 * 32 + 4 * 4) + 4 * t * 32
    assert sc.bd_core_bytes(M, 8192, 1) == forward + backward
    # bound by its operations, by far
    assert sc.bd_core_train_flops(M, 8192, BK, 1) / 197e12 \
        > 15 * sc.bd_core_bytes(M, 8192, 1) / 819e9


def test_a_step_s_operations():
    """ISSUE 40's hand count: the core 55%, projections 31%, held experts
    8%, head 6% of a step's forward."""
    seq = 8192
    pairs = 5 * 16384 * 8 * 16 // 128       # balanced: 16 of 128 held
    assert pairs == 5 * 16384
    fwd = sc.forward_flops(M, seq, pairs, BK)
    core = 5 * sc.bd_core_flops(M, seq, BK)
    projections = 5 * 2 * 16384 * sc.attention_matrix_params(M)
    experts = 2 * pairs * sc.expert_params(M)
    routers = 5 * 2 * 16384 * sc.router_params(M)
    head = 2 * 8192 * 2048 * 18992            # the noised stream alone
    assert fwd == core + projections + experts + routers + head
    assert core / 1e12 == pytest.approx(5.50, abs=0.005)
    assert projections / 1e12 == pytest.approx(3.09, abs=0.005)
    assert experts / 1e12 == pytest.approx(0.77, abs=0.005)
    assert head / 1e12 == pytest.approx(0.64, abs=0.005)
    assert core / fwd == pytest.approx(0.55, abs=0.01)
    assert projections / fwd == pytest.approx(0.31, abs=0.01)
    assert experts / fwd == pytest.approx(0.08, abs=0.01)
    assert head / fwd == pytest.approx(0.06, abs=0.01)
    assert sc.train_step_flops(M, seq, pairs, BK) == 3 * fwd
    assert 3 * fwd / 1e12 == pytest.approx(30.1, abs=0.1)
    # in the whole model a token finds all 8 of its experts: the core's
    # share falls to 37%
    whole = core + projections + routers + 8 * experts + head / 5
    assert core / whole == pytest.approx(0.37, abs=0.01)
