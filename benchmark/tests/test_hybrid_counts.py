"""`harness/hybrid_counts.py` against hand arithmetic: the table of the
configuration's cut (ISSUE 32) to the parameter, the scan's operations at
T = 8192, and the work of a step."""
import json
import os

import pytest

from harness import hybrid_counts as hc

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    BENCH, 'configs', 'nemotron-twotower-ep16-train.json')))
M = CFG['model']


def test_the_cut_s_table_to_the_parameter():
    p = hc.matrix_params(M)
    assert p['embedding_and_head'] == 2 * 16384 * 2688 == 88_080_384
    # W_in 2688 x (4096 + 6144 + 64), W_out 4096 x 2688
    assert p['mamba_layer'] == 2688 * 10304 + 4096 * 2688 == 38_707_200
    # taps and bias of 6144 channels, dt_bias + A_log + D, the gate's scale
    assert hc.mamba_vector_params(M) == 6144 * 5 + 3 * 64 + 4096 == 35_008
    expert, shared, router = 2 * 2688 * 1856, 2 * 2688 * 3712, 2688 * 128
    assert (expert, shared, router) == (9_977_856, 19_955_712, 344_064)
    assert p['expert_layer'] == 8 * expert + shared + router == 100_122_624
    assert p['attention_layer'] == 2 * 2688 * 4096 + 2 * 2688 * 256 \
        == 23_396_352
    assert [hc.layers(M, k) for k in 'ME*'] == [4, 4, 1]
    assert hc.expert_layers(M) == 4
    # ten norms of 2688, the state-space vectors, four correction biases
    assert hc.vector_params(M) == 10 * 2688 + 4 * 35_008 + 4 * 128 == 167_424
    total = hc.total_params(M)
    assert total == 88_080_384 + 4 * 38_707_200 + 4 * 100_122_624 \
        + 23_396_352 + 167_424 == 666_963_456
    assert total / 1e6 == pytest.approx(667.0, abs=0.05)
    assert total * 16 / 1e9 == pytest.approx(10.67, abs=0.01)     # GB
    assert total * 16 / 2**30 == pytest.approx(9.94, abs=0.01)    # GiB
    # 16 experts held would not fit beside the activations
    assert (total + 4 * 8 * expert) / 1e6 == pytest.approx(986, abs=0.5)


def test_the_whole_published_model_counts_its_published_size():
    """52 layers (23 M, 23 E, 6 *), all 128 experts, the whole vocabulary,
    inner width heads x head_dim: 31.58 G parameters, the published
    30B-A3B; `expand` 2 (inner 5376) would not."""
    whole = dict(M, hybrid_override_pattern=CFG['hybrid_override_pattern'],
                 experts_held=128, vocab_rows=131072)
    assert [hc.layers(whole, k) for k in 'ME*'] == [23, 23, 6]
    assert hc.total_params(whole) / 1e9 == pytest.approx(31.58, abs=0.01)


def test_the_scan_s_operations_at_8192_tokens():
    # inside a chunk of 128 at half the square: C B^T per group (8 x 128)
    # and its product with dt x per head (64 x 64); then the chunk states
    # built and read, 2 x 128 x 64 x 64 each, per token
    inside = 8192 * 128 * (128 * 8 + 64 * 64)
    states = 4 * 8192 * 128 * 64 * 64
    assert hc.scan_flops(M, 8192) == inside + states == 22_548_578_304
    assert hc.scan_train_flops(M, 8192, 4 * 3) == 3 * 12 * 22_548_578_304
    # x (4096), B and C (1024 each), y (4096), dt (64): float32, forward
    # once, they and their cotangents backward
    assert hc.scan_bytes(M, 8192, 1) == 3 * 4 * 8192 * (4096 + 2048 + 4096
                                                         + 64)
    # at the peaks of a v5e the bytes bound it (1.24 ms a layer and step),
    # not the operations (0.34 ms)
    assert hc.scan_bytes(M, 8192, 1) / 819e9 == pytest.approx(1.237e-3,
                                                               rel=1e-3)
    assert hc.scan_train_flops(M, 8192, 1) / 197e12 == pytest.approx(
        0.343e-3, rel=1e-2)


def test_the_attention_core_s_count_is_the_other_cells_at_these_heads():
    # 32 heads, scores and weighted sum at half the square
    assert hc.attention_core_flops(M, 8192) == 32 * 8192 * 8192 * 2 * 128
    assert hc.attention_core_train_flops(M, 8192, 2) \
        == 6 * hc.attention_core_flops(M, 8192)
    assert hc.attention_core_bytes(M, 8192, 1) == 2 * 8192 * 32 * 128 * 12
    from harness import lm_counts
    as_latent = dict(num_attention_heads=32, qk_nope_head_dim=128,
                     qk_rope_head_dim=0, v_head_dim=128)
    assert hc.attention_core_flops(M, 8192) \
        == lm_counts.attention_core_flops(as_latent, 8192)
    assert hc.attention_core_bytes(M, 8192, 3) \
        == lm_counts.attention_core_bytes(as_latent, 8192, 3)


def test_a_step_s_operations():
    # per token, forward, in MFLOP: the issue's hand count
    seq, pairs = 8192, 4 * 3072          # 6 of 128, 8 held: 0.375 a token
    per_token = hc.forward_flops(M, seq, pairs) / seq / 1e6
    mamba = 2 * 38.7072 + 2 * 4 * 6144 / 1e6 + 22_548_578_304 / 8192 / 1e6
    expert = 2 * (19.955712 + 0.344064) + 0.375 * 2 * 9.977856
    attention = 2 * 23.396352 + 32 * 8192 * 2 * 128 / 1e6
    head = 2 * 2688 * 16384 / 1e6
    assert per_token == pytest.approx(
        4 * mamba + 4 * expert + attention + head, rel=1e-9)
    # the state-space layers are the largest part, 45% of the operations
    assert 4 * mamba / per_token == pytest.approx(0.45, abs=0.01)
    assert mamba == pytest.approx(80.2, abs=0.1)
    assert hc.train_step_flops(M, seq, pairs) / 1e12 == pytest.approx(
        17.58, abs=0.01)
