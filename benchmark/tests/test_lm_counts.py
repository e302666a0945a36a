"""`harness/lm_counts.py` against hand arithmetic: the table of the
configuration's cut (ISSUE 27 section B) and the work of a step."""
import json
import os

import pytest

from harness import lm_counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M = json.load(open(os.path.join(
    BENCH, 'configs', 'glm47-flash-ep8-train.json')))['model']


def test_the_cut_s_table():
    attn = (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048)
    assert lm_counts.attention_params(M) == attn == 21_757_952
    expert = 3 * 2048 * 1536
    p = lm_counts.matrix_params(M)
    assert p['embedding_and_head'] == 2 * 19360 * 2048
    assert p['dense_block'] == attn + 3 * 2048 * 10240
    assert p['expert_block'] == attn + expert + 2048 * 64 + 8 * expert
    assert p['prediction_block'] == 2 * 2048 * 2048 + p['expert_block']
    total = lm_counts.total_params(M)
    assert total == (p['embedding_and_head'] + p['dense_block']
                     + 4 * p['expert_block'] + p['prediction_block']
                     + lm_counts.vector_params(M))
    assert total / 1e6 == pytest.approx(706.5, abs=0.05)
    assert total * 16 / 2**30 == pytest.approx(10.53, abs=0.01)   # GiB
    assert lm_counts.expert_layers(M) == 5


def test_a_step_s_operations():
    # per token, forward: the issue's hand count, in MFLOP
    seq, pairs = 8192, 5 * 4096          # a token finds 0.5 experts here
    per_token = lm_counts.forward_flops(M, seq, pairs) / seq / 1e6
    dense = 2 * (21.757952 + 62.914560)
    expert = 2 * (21.757952 + 9.437184 + 0.131072 + 0.5 * 9.437184)
    heads = 2 * 2 * 2048 * 19360 / 1e6
    merge = 2 * 2 * 2048 * 2048 / 1e6
    core = 6 * 20 * 8192 * 512 / 1e6     # causal: half of 4 T H d
    assert per_token == pytest.approx(
        dense + 5 * expert + heads + merge + core, rel=1e-9)
    assert per_token == pytest.approx(1208, abs=2)
    assert core / per_token == pytest.approx(0.42, abs=0.01)
    assert lm_counts.train_step_flops(M, seq, pairs) \
        == 3 * lm_counts.forward_flops(M, seq, pairs)
    assert lm_counts.train_step_flops(M, seq, pairs) / 1e12 \
        == pytest.approx(29.7, abs=0.1)


def test_the_grouped_products_operations_and_bytes():
    pairs = 5 * 4096
    assert lm_counts.grouped_flops(M, pairs) \
        == 3 * 2 * pairs * 3 * 2048 * 1536
    weights = 5 * 8 * 3 * 2048 * 1536
    nbytes = lm_counts.grouped_bytes(M, pairs, 5)
    # forward: x in (bf16), gate and up out (f32), hidden in (bf16), y out
    # (f32), the weights (bf16); backward: dy, dhidden, dgate and dup, dx,
    # the weights again and their gradients (f32)
    assert nbytes == (pairs * (2 * 2048 + 8 * 1536 + 2 * 1536 + 4 * 2048)
                      + 2 * weights
                      + pairs * (2 * 2048 + 4 * 1536 + 4 * 1536 + 4 * 2048)
                      + 6 * weights)
    # at 512 pairs an expert the products are bound by the MXU, not by HBM
    assert lm_counts.grouped_flops(M, pairs) / 197e12 > nbytes / 819e9
    assert lm_counts.attention_core_train_flops(M, 8192, 6) \
        == 3 * 6 * 20 * 8192 * 8192 * 512
