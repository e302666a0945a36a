"""The token decoder (latent attention, held experts, prediction block)
against the plain reference the benchmark keeps
(`benchmark/harness/lm_reference.py`, loaded by path: it imports nothing of
the program), at tiny widths in float32 on the CPU, and the properties the
architecture states one by one."""
import importlib.util
import os
import re
from fractions import Fraction
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from test_hybrid_decoder import SIZES as HYBRID_SIZES

from se3_transformer_tpu.models.token_decoder import TokenDecoder
from se3_transformer_tpu.ops import expert_layer, latent_attention
from se3_transformer_tpu.ops.expert_layer import (
    ExpertLayer, balance_bias, grouped_dot, route,
)
from se3_transformer_tpu.ops.latent_attention import LatentAttention
from se3_transformer_tpu.ops.rotary import apply_rotary_halves, rotary_angles
from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
from se3_transformer_tpu.training.lm_loss import (
    balance_expert_load, chunked_cross_entropy, make_lm_loss,
)
from se3_transformer_tpu.training.recipes import RECIPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(vocab_rows=48, hidden_size=32, intermediate_size=48,
             moe_intermediate_size=16, num_hidden_layers=3,
             first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=12,
             kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
             v_head_dim=12, n_routed_experts=8, n_shared_experts=1,
             num_experts_per_tok=2, experts_held=4, expert_rank=1,
             routed_scaling_factor=1.8, norm_topk_prob=True,
             num_nextn_predict_layers=1, rope_theta=1e6, rms_norm_eps=1e-5)


@pytest.fixture(scope='module')
def ref():
    spec = importlib.util.spec_from_file_location(
        'lm_reference',
        os.path.join(ROOT, 'benchmark', 'harness', 'lm_reference.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _perturbed(params, seed=100):
    """Scales off one and correction biases off zero, so that a comparison
    covers them."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(flat):
        z = jax.random.normal(jax.random.PRNGKey(seed + i), a.shape)
        name = str(path[-1].key)
        out.append(1 + 0.1 * z if name == 'scale'
                   else 0.05 * z if name == 'correction_bias' else a)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope='module')
def tiny():
    module = RECIPES['token_decoder'](bf16_operands=False, attention_block=8,
                                      **SIZES)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 48, (2, 16)), jnp.int32)
    params = _perturbed(jax.jit(module.init)(jax.random.PRNGKey(0),
                                             tokens)['params'])
    return module, params, tokens


def test_recipe_builds_the_decoder():
    assert isinstance(RECIPES['token_decoder'](), TokenDecoder)
    assert RECIPES['token_decoder'](experts_held=2).experts_held == 2


def test_loss_and_every_gradient_leaf_match_the_plain_reference(tiny, ref):
    module, params, tokens = tiny
    loss_fn = make_lm_loss(module, chunk=8)
    (loss, aux), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, dict(tokens=tokens), None)
    (want, chosen), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, SIZES, attn_block=8, chunk=8),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert np.array_equal(np.sort(np.asarray(aux['moe_choice']), -1),
                          np.sort(np.asarray(chosen), -1))
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.linalg.norm(b))
        name = jax.tree_util.keystr(path)
        if 'correction_bias' in name:
            assert scale == 0 and float(jnp.linalg.norm(a)) == 0, name
            continue
        assert scale > 0, name
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * scale, name


# ------------------------------------------------------------------ #
# the expert layer
# ------------------------------------------------------------------ #
D, WIDTH, E, K = 16, 8, 8, 2
LAYER = dict(hidden_size=D, moe_intermediate_size=WIDTH, n_routed_experts=E,
             num_experts_per_tok=K, routed_scaling_factor=1.8,
             norm_topk_prob=True)


def _layer(held, rank, shared=True, **kw):
    return ExpertLayer(width=WIDTH, n_experts=E, top_k=K, experts_held=held,
                       expert_rank=rank, shared_width=WIDTH if shared else 0,
                       routed_scale=1.8, bf16_operands=False, **kw)


@pytest.fixture(scope='module')
def whole_layer():
    """The uncut layer's parameters (all 8 experts held) and some tokens."""
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D))
    params = _layer(E, 0).init(jax.random.PRNGKey(2), x)['params']
    params = dict(params, correction_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(3), (E,)))
    return params, x


def _share(params, held, rank, shared):
    cut = {k: (v[rank * held:(rank + 1) * held] if k.startswith('experts_')
               else v) for k, v in params.items()}
    if not shared:
        cut.pop('shared')
    return cut


@pytest.mark.parametrize('held', [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(whole_layer, ref, held):
    """The routed parts of all E / held shares, with the shared expert counted
    once, are what the uncut reference gives for the whole layer."""
    params, x = whole_layer
    want, _ = ref.expert_layer(params, x, LAYER, lambda w: w, held=range(E))
    total = 0.0
    for rank in range(E // held):
        shared = rank == 0
        out, stats = _layer(held, rank, shared).apply(
            {'params': _share(params, held, rank, shared)}, x)
        assert int(stats['dropped']) == 0
        total = total + out
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)


def test_nothing_is_dropped_when_every_token_picks_the_same_held_experts(
        whole_layer, ref):
    params, x = whole_layer
    held, rank = 4, 1            # experts 4..7; every token picks 5 and 6
    bias = jnp.zeros(E).at[jnp.asarray([5, 6])].set(10.0)
    params = dict(params, correction_bias=bias)
    out, stats = _layer(held, rank).apply(
        {'params': _share(params, held, rank, True)}, x)
    assert np.asarray(stats['load']).tolist() == [0, 24, 24, 0]
    assert int(stats['dropped']) == 0
    assert set(np.asarray(stats['chosen']).ravel().tolist()) == {5, 6}
    m = dict(LAYER, experts_held=held, expert_rank=rank)
    want, _ = ref.expert_layer(_share(params, held, rank, True), x, m,
                               lambda w: w)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def _bound_rows_at(monkeypatch, c, held, n_pairs=24 * K):
    """Patch the layer's one constant so that `held_row_bound` is c."""
    monkeypatch.setattr(expert_layer, 'HELD_ROW_BOUND',
                        (Fraction(c * E, n_pairs * held), 1))
    assert expert_layer.held_row_bound(n_pairs, held, E) == min(c, n_pairs)


@pytest.mark.parametrize('over', [-1, 0, 1, 'every pair'])
@pytest.mark.parametrize('hidden_act', ['silu', 'relu2'])
def test_the_bounded_rows_give_the_full_sizes_output_and_gradients(
        monkeypatch, hidden_act, over):
    """H held pairs against a bound of C rows, at H = C - 1, C, C + 1 and
    N * k: the output and every gradient leaf are the full size's (which is
    all there is while the constant reaches N * k), the first two from C
    rows, the others from all rows by the fallback; nothing is dropped."""
    held, rank = 4, 1
    layer = _layer(held, rank, hidden_act=hidden_act)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D))
    cot = jax.random.normal(jax.random.PRNGKey(5), (24, D))
    params = layer.init(jax.random.PRNGKey(2), x)['params']
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (E,))
    if over == 'every pair':         # every token picks held experts 5 and 6
        bias = bias.at[jnp.asarray([5, 6])].set(10.0)
    params = dict(params, correction_bias=bias)

    def run():                       # traced anew: the constant is read then
        def scalar(params, x):
            out, stats = layer.apply({'params': params}, x)
            return jnp.sum(out * cot), (out, stats)
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
            scalar, argnums=(0, 1), has_aux=True))(params, x)
        return out, stats, grads

    assert expert_layer.held_row_bound(24 * K, held, E) == 24 * K
    want, stats, want_grads = run()
    h = int(stats['load'].sum())
    assert (h == 24 * K) if over == 'every pair' else (8 < h < 24 * K - 8)
    assert int(stats['bounded']) == 1 and int(stats['dropped']) == 0

    c = 24 if over == 'every pair' else h - over
    _bound_rows_at(monkeypatch, c, held)
    got, stats, got_grads = run()
    assert int(stats['load'].sum()) == h and int(stats['dropped']) == 0
    assert int(stats['bounded']) == (1 if over in (-1, 0) else 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    leaves = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    # the bias, the router, the experts' and the shared one's matrices, x
    assert len(leaves) == (9 if hidden_act == 'silu' else 7)
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(want_grads)):
        name, scale = jax.tree_util.keystr(path), float(jnp.linalg.norm(b))
        assert (scale > 0) != ('correction_bias' in name), name
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * scale, name


def test_the_correction_bias_moves_the_choice_and_not_the_weights():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(4), (32, E)))
    plain, w_plain = route(scores, jnp.zeros(E), K, 1.8, True)
    bias = jnp.zeros(E).at[3].set(10.0)
    moved, w_moved = route(scores, bias, K, 1.8, True)
    assert not np.array_equal(np.asarray(plain), np.asarray(moved))
    assert (np.asarray(moved) == 3).any(axis=-1).all()
    # the weights are the chosen experts' own scores, the bias nowhere
    picked = jnp.take_along_axis(scores, moved, axis=-1)
    np.testing.assert_allclose(
        w_moved, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # and the bias gets no gradient
    g = jax.grad(lambda b: route(scores, b, K, 1.8, True)[1].sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize('normalize,total', [(True, 1.8), (False, None)])
def test_route_weights_are_normalized_and_scaled(normalize, total):
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5), (32, E)))
    chosen, w = route(scores, jnp.zeros(E), 4, 1.8, normalize)
    top = jnp.sort(scores, axis=-1)[:, -4:]
    assert np.array_equal(np.sort(np.asarray(
        jnp.take_along_axis(scores, chosen, axis=-1)), -1), np.asarray(top))
    want = total if normalize else 1.8 * np.asarray(top.sum(-1))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), want, rtol=1e-5)


def test_the_balancing_rule_evens_the_load_and_starts_from_the_bias_given():
    """Scores with a common lean towards a few experts: unbiased, those take
    most pairs; the settled bias brings every expert near the mean."""
    key = jax.random.PRNGKey(14)
    lean = jnp.zeros(E).at[:2].set(1.5)
    scores = jax.nn.sigmoid(jax.random.normal(key, (512, E)) + lean)

    def load(bias):
        return np.bincount(np.asarray(
            route(scores, bias, K, 1.0, True)[0]).ravel(), minlength=E)

    before = load(jnp.zeros(E))
    bias = balance_bias(scores, jnp.zeros(E), K)
    after = load(bias)
    mean = 512 * K / E
    assert before.max() > 2.0 * mean
    assert after.max() < 1.15 * mean and after.min() > 0.85 * mean
    assert float(bias[:2].max()) < float(bias[2:].min())
    # no step at all leaves the bias it was given
    start = 0.01 * jax.random.normal(key, (E,))
    np.testing.assert_array_equal(
        balance_bias(scores, start, K, steps=0), start)


def test_balance_expert_load_settles_every_layer_on_the_batches_given(tiny):
    module, params, tokens = tiny
    batches = [dict(tokens=tokens), dict(tokens=jnp.flip(tokens, axis=1))]
    settled = balance_expert_load(module, params, batches, steps=200)
    names = module.expert_layer_names()
    assert names == ['blocks_1', 'blocks_2', 'mtp_block']

    def spread(p):
        """max / mean load over all experts, per expert layer."""
        out = []
        for i in range(len(names)):
            chosen = np.concatenate([np.asarray(module.apply(
                {'params': p}, b['tokens'], method='hidden_states')[2][i][
                    'chosen']) for b in batches])
            load = np.bincount(chosen.ravel(), minlength=8)
            out.append(load.max() / load.mean())
        return out

    assert all(a <= b for a, b in zip(spread(settled), spread(params)))
    assert max(spread(settled)) < 1.35
    # only the correction biases moved
    moved = {jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(settled)[0],
        jax.tree_util.tree_leaves(params)) if not np.array_equal(a, b)}
    assert moved == {f"['{n}']['moe']['correction_bias']" for n in names}


def test_grouped_dot_leaves_rows_past_the_groups_zero_and_differentiates():
    lhs = jax.random.normal(jax.random.PRNGKey(6), (12, 5))
    rhs = jax.random.normal(jax.random.PRNGKey(7), (3, 5, 4))
    sizes = jnp.asarray([4, 0, 3], jnp.int32)      # rows 7.. belong to none
    group = np.asarray([0] * 4 + [2] * 3)

    def dense(lhs, rhs):
        rows = jnp.einsum('rk,rkn->rn', lhs[:7], rhs[group])
        return jnp.concatenate((rows, jnp.zeros((5, 4))))

    np.testing.assert_allclose(grouped_dot(lhs, rhs, sizes), dense(lhs, rhs),
                               rtol=1e-5, atol=1e-6)
    cot = jax.random.normal(jax.random.PRNGKey(8), (12, 4))
    got = jax.grad(lambda a, b: (grouped_dot(a, b, sizes) * cot).sum(),
                   argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda a, b: (dense(a, b) * cot).sum(),
                    argnums=(0, 1))(lhs, rhs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # operands rounded to bfloat16 on request, float32 out
    out = grouped_dot(lhs, rhs, sizes, jnp.bfloat16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, dense(lhs, rhs), rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize('dtype', [None, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('form', ['product', 'relu2', 'silu', 'relu'])
def test_padded_widths_give_the_unpadded_products_and_cotangents(
        monkeypatch, form, dtype):
    """The form a TPU takes at widths that are multiples of nothing (40 and
    24, run at 64 and 32) against the widths as they are, the operands
    rounded alike on both sides: one product called with `widths`, and both
    expert forms through `experts` with the rule steered. Output and every
    cotangent agree to 1e-6 of their largest entry, a cotangent has its
    primal's shape (the padding's is dropped), and rows past the groups
    read zero."""
    d, width, rows = 40, 24, 20
    sizes = jnp.asarray([6, 0, 9], jnp.int32)      # rows 15.. belong to none
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    xs = jax.random.normal(keys[0], (rows, d))
    mats = {name: jax.random.normal(key, shape) / 6 for name, key, shape in (
        ('up', keys[1], (3, d, width)), ('gate', keys[2], (3, d, width)),
        ('down', keys[3], (3, width, d)))}
    if form == 'product':
        cot = jax.random.normal(keys[4], (rows, width))

        def run(padded):
            def out(xs, up):
                widths = (64, 32) if padded else None
                return grouped_dot(xs, up, sizes, dtype, widths)[:, :width]
            return out, (xs, mats['up'])
    else:
        cot = jax.random.normal(keys[4], (rows, d))
        if form == 'relu2':
            del mats['gate']

        def run(padded):
            monkeypatch.setattr(expert_layer, 'is_tpu_backend',
                                lambda: padded)
            monkeypatch.setattr(expert_layer, 'PADDED_WIDTH', (16, 32))
            assert expert_layer.padded_width(d) == (64 if padded else d)
            assert expert_layer.padded_width(width) == (32 if padded
                                                        else width)
            assert expert_layer.padded_width(48) == 48
            return expert_layer._stages(
                rows, None, None, sizes, rows, 1,
                expert_layer.EXPERT_FORMS[form][1], dtype)[1], (xs, mats)

    results = []
    for padded in (False, True):
        fn, args = run(padded)
        out, vjp = jax.vjp(fn, *args)
        results.append(jax.tree_util.tree_leaves((out, vjp(cot))))
        assert out.shape == cot.shape and not np.asarray(out[15:]).any()
        assert jax.tree_util.tree_map(jnp.shape, vjp(cot)) \
            == jax.tree_util.tree_map(jnp.shape, args)
    for want, got in zip(*results):
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-6 * np.abs(want).max())


# ------------------------------------------------------------------ #
# latent attention
# ------------------------------------------------------------------ #
def test_rotation_of_halves_depends_on_relative_position_only():
    d = 8
    q, k = jax.random.normal(jax.random.PRNGKey(9), (2, d))
    ang = rotary_angles(jnp.arange(12), d, 1e6)
    assert ang.shape == (12, d // 2)
    np.testing.assert_allclose(apply_rotary_halves(q, ang[0]), q)
    rq, rk = apply_rotary_halves(q, ang), apply_rotary_halves(k, ang)
    np.testing.assert_allclose(rq[7] @ rk[3], rq[9] @ rk[5], rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(rq, axis=-1),
                               jnp.full(12, jnp.linalg.norm(q)), rtol=1e-5)
    # the pairs are (x_i, x_{i + d/2}), frequency base^(-2i/d)
    one = apply_rotary_halves(jnp.eye(d)[1], ang[5])
    theta = 5.0 * 1e6 ** (-2 / d)
    np.testing.assert_allclose(one[jnp.asarray([1, 1 + d // 2])],
                               [np.cos(theta), np.sin(theta)], rtol=1e-5)


@pytest.mark.parametrize('block', [4, 16])
def test_one_rotary_key_a_token_and_the_scale_of_the_whole_query(ref, block):
    dn, dr, dv, h, t = 8, 4, 12, 2, 16
    attn = LatentAttention(dim=32, heads=h, q_lora_rank=12, kv_lora_rank=8,
                           qk_nope_head_dim=dn, qk_rope_head_dim=dr,
                           v_head_dim=dv, rope_theta=1e6, block=block)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, t, 32))
    params = attn.init(jax.random.PRNGKey(11), x)['params']
    # one rotary key a token: kv_a gives the latent and dr more columns
    assert params['kv_a']['kernel'].shape == (32, 8 + dr)
    assert params['kv_b']['kernel'].shape == (8, h * (dn + dv))
    apply = jax.jit(attn.apply)
    got = apply({'params': params}, x)[0]
    m = dict(num_attention_heads=h, qk_nope_head_dim=dn, qk_rope_head_dim=dr,
             v_head_dim=dv, kv_lora_rank=8, rms_norm_eps=1e-5, rope_theta=1e6)
    want = jax.jit(lambda p, x: ref.attention(p, x, m, lambda w: w, 8))(
        params, x[0])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # causal: a later token does not move an earlier output
    moved = apply({'params': params}, x.at[0, 9].add(1.0))[0]
    np.testing.assert_allclose(moved[:9], got[:9], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(moved[9:] - got[9:]).max()) > 1e-3
    # the scale is 1 / sqrt(dn + dr): by hand for the last query of head 0
    p = params
    norm = lambda v, s: v * jax.lax.rsqrt(      # noqa: E731
        jnp.mean(v * v, -1, keepdims=True) + 1e-5) * s
    q = (norm(x[0] @ p['q_a']['kernel'], p['q_a_norm']['scale'])
         @ p['q_b']['kernel']).reshape(t, h, dn + dr)
    ckv_kr = x[0] @ p['kv_a']['kernel']
    kv = (norm(ckv_kr[:, :8], p['kv_a_norm']['scale'])
          @ p['kv_b']['kernel']).reshape(t, h, dn + dv)
    ang = rotary_angles(jnp.arange(t), dr, 1e6)
    kr = apply_rotary_halves(ckv_kr[:, 8:], ang)          # shared by heads
    heads = []
    for i in range(h):
        qr = apply_rotary_halves(q[-1, i, dn:], ang[-1])
        s = (kv[:, i, :dn] @ q[-1, i, :dn] + kr @ qr) / np.sqrt(dn + dr)
        heads.append(jax.nn.softmax(s) @ kv[:, i, dn:])
    np.testing.assert_allclose(jnp.concatenate(heads) @ p['out']['kernel'],
                               got[-1], rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------ #
# the prediction block and the loss
# ------------------------------------------------------------------ #
def test_the_prediction_block_targets_the_token_after_next(tiny):
    module, params, tokens = tiny
    b, t = tokens.shape
    _, aux = make_lm_loss(module, chunk=8)(params, dict(tokens=tokens), None)
    apply = jax.jit(module.apply)
    main, ahead, _ = apply({'params': params}, tokens)
    for logits, shift, name in ((main, 1, 'loss_main'),
                                (ahead, 2, 'loss_mtp')):
        logp = jax.nn.log_softmax(logits[:, :t - shift], axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, shift:, None], -1)
        assert float(aux[name]) == pytest.approx(float(nll.mean()), rel=1e-5)
    # a later token does not reach an earlier prediction of either head
    moved = apply({'params': params},
                  tokens.at[:, 12].set((tokens[:, 12] + 1) % 48))
    np.testing.assert_allclose(moved[0][:, :12], main[:, :12], atol=1e-5)
    np.testing.assert_allclose(moved[1][:, :11], ahead[:, :11], atol=1e-5)
    assert float(jnp.abs(moved[1][:, 11] - ahead[:, 11]).max()) > 1e-4


def test_embedding_and_head_are_shared_with_the_prediction_block(tiny):
    module, params, tokens = tiny
    loss_fn = make_lm_loss(module, chunk=8)
    g = jax.jit(jax.grad(lambda p: loss_fn(p, dict(tokens=tokens), None)[1][
        'loss_mtp']))(params)
    for leaf in (g['embedding']['embedding'], g['head']['kernel'],
                 g['mtp_proj']['kernel'], g['blocks_0']['attn']['q_a'][
                     'kernel']):
        assert float(jnp.linalg.norm(leaf)) > 0
    # the main head's loss does not reach the prediction block
    g = jax.jit(jax.grad(lambda p: loss_fn(p, dict(tokens=tokens), None)[1][
        'loss_main']))(params)
    assert float(jnp.linalg.norm(g['mtp_proj']['kernel'])) == 0
    assert 'mtp_block' in params and 'mtp_head' not in params


def test_chunked_cross_entropy_is_the_plain_one():
    h = jax.random.normal(jax.random.PRNGKey(12), (24, 8))
    kernel = jax.random.normal(jax.random.PRNGKey(13), (8, 11))
    targets = jnp.arange(24) % 11
    valid = jnp.arange(24) % 5 != 0
    nll = -jnp.take_along_axis(jax.nn.log_softmax(h @ kernel),
                               targets[:, None], -1)[:, 0]
    want = float((nll * valid).sum() / valid.sum())
    for chunk in (4, 24, 1024):
        got = chunked_cross_entropy(h, kernel, targets, valid, chunk)
        assert float(got) == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize('bound', [expert_layer.HELD_ROW_BOUND, (1, 1)],
                         ids=['the full size alone', 'a bound that binds'])
def test_three_steps_on_the_one_step_factory_with_the_counters_in_aux(
        tiny, monkeypatch, bound):
    """With the layer's constant as it is these sizes have the full size
    alone; at the balanced expectation itself a layer's held pairs fall on
    either side of the bound, inside the recomputed blocks of the step."""
    monkeypatch.setattr(expert_layer, 'HELD_ROW_BOUND', bound)
    rows = expert_layer.held_row_bound(32 * 2, 4, 8)
    assert rows == (32 * 2 if bound[0] == 2 else 32)
    module, params, tokens = tiny
    optimizer = optax.adam(1e-3)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    assert step.__name__ == 'train_step'
    params = jax.tree_util.tree_map(jnp.array, params)     # donated below
    opt_state = optimizer.init(params)
    losses = []
    for i in range(3):
        params, opt_state, loss, aux = step(params, opt_state,
                                            dict(tokens=tokens),
                                            jax.random.PRNGKey(i))
        losses.append(float(loss))
        pairs = int(aux['moe_local_pairs'])
        # three expert layers (two blocks and the prediction block), 4 of 8
        # experts held: about half of 3 x 32 tokens x 2 choices
        assert 0 < pairs <= 3 * 32 * 2
        assert float(aux['moe_load_mean']) == pytest.approx(pairs / 12)
        assert int(aux['moe_load_max']) >= float(aux['moe_load_mean'])
        assert int(aux['moe_dropped']) == 0
        assert aux['moe_choice'].shape == (3, 32, 2)
        held = np.asarray(aux['moe_choice']) // 4 == 1           # rank 1
        assert held.sum() == pairs
        fit = held.sum(axis=(1, 2)) <= rows
        assert int(aux['moe_bounded']) == fit.sum()
    assert losses[2] < losses[1] < losses[0]


# ------------------------------------------------------------------ #
# the streaming core's own custom_vjp, its three launches stood in for
# ------------------------------------------------------------------ #
@pytest.fixture
def core_stand_ins(monkeypatch):
    """The library kernel's three launches as plain `jax.numpy` of the same
    signatures and dtypes (they do not lower on a CPU). The forward is a
    host callback, so a compiled program holds one custom call a launch and
    a run counts them: `forwards` gets each one's `save_residuals`."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    forwards = []

    def masked_scores(q, k, scale):
        s = jnp.einsum('bhqd,bhkd->bhqk', q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        t = q.shape[2]
        return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s,
                         fa.DEFAULT_MASK_VALUE)

    def forward(q, k, v, ab, segment_ids, save_residuals, causal, sm_scale,
                *blocks):
        assert ab is None and segment_ids is None and causal

        def on_host(q, k, v):
            forwards.append(save_residuals)
            q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
            s = np.einsum('bhqd,bhkd->bhqk', q, k) * sm_scale
            t = q.shape[2]
            s = np.where(np.tril(np.ones((t, t), bool)), s,
                         fa.DEFAULT_MASK_VALUE)
            m = s.max(-1)
            p = np.exp(s - m[..., None])
            l = p.sum(-1)
            o = np.einsum('bhqk,bhkd->bhqd', p / l[..., None], v)
            return o.astype(np.float32), l, m

        f32 = partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        o, l, m = jax.pure_callback(
            on_host, (f32(q.shape), f32(q.shape[:3]), f32(q.shape[:3])),
            q, k, v)
        o = o.astype(q.dtype)
        return (o, l, m) if save_residuals else o

    def p_and_ds(q, k, v, l, m, do, di, scale):
        p = jnp.exp(masked_scores(q, k, scale) - m[..., None]) / l[..., None]
        dp = jnp.einsum('bhqd,bhkd->bhqk', do.astype(jnp.float32),
                        v.astype(jnp.float32))
        return p, p * (dp - di[..., None]) * scale

    def bwd_dkv(q, k, v, ab, segment_ids, l, m, do, di, *, sm_scale, causal,
                **blocks):
        assert ab is None and segment_ids is None and causal
        p, ds = p_and_ds(q, k, v, l, m, do, di, sm_scale)
        dk = jnp.einsum('bhqk,bhqd->bhkd', ds, q.astype(jnp.float32))
        dv = jnp.einsum('bhqk,bhqd->bhkd', p, do.astype(jnp.float32))
        return dk.astype(k.dtype), dv.astype(v.dtype)

    def bwd_dq(q, k, v, ab, segment_ids, l, m, do, di, *, sm_scale, causal,
               **blocks):
        assert ab is None and segment_ids is None and causal
        _, ds = p_and_ds(q, k, v, l, m, do, di, sm_scale)
        dq = jnp.einsum('bhqk,bhkd->bhqd', ds, k.astype(jnp.float32))
        return dq.astype(q.dtype), None

    monkeypatch.setattr(fa, '_flash_attention_impl', forward)
    monkeypatch.setattr(fa, '_flash_attention_bwd_dkv', bwd_dkv)
    monkeypatch.setattr(fa, '_flash_attention_bwd_dq', bwd_dq)
    latent_attention.flash_attention.clear_cache()   # a trace keeps its calls
    yield forwards
    latent_attention.flash_attention.clear_cache()


def _qkv_and_block(t=64, d=16, block=16):
    """q, k, v [1, 2, t, d] and a stand-in for a decoder block around a
    core: what feeds it is replayed, what follows needs its output."""
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(key, (1, 2, t, d)) for key in (kq, kk, kv))
    w = jax.random.normal(kw, (d, d)) / 4

    def around(core):
        return lambda q, k, v: jnp.tanh(
            core(jnp.sin(q), k, v, d ** -0.5, block) @ w)
    return (q, k, v), around


@pytest.mark.parametrize('policy,launches', [
    (latent_attention.SAVE_ATTN_CORE, 1), (None, 2)],
    ids=["the blocks' policy", 'the default policy'])
def test_a_replayed_block_holds_the_forward_core_once_under_the_blocks_policy(
        core_stand_ins, policy, launches):
    """Saved (o, l, m) leave the replay no forward to launch; with nothing
    saved the forward pass and the replay each hold one. Either way both
    carry statistics."""
    qkv, around = _qkv_and_block()
    block = jax.checkpoint(around(latent_attention.flash_attention),
                           policy=policy)
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(block(*a) ** 2),
                            argnums=(0, 1, 2)))
    text = grad.lower(*qkv).compile().as_text()
    assert len(re.findall(r'custom_call_target="[^"]*callback[^"]*"',
                          text)) == launches
    jax.block_until_ready(grad(*qkv))
    assert core_stand_ins == [True] * launches


@pytest.mark.parametrize('block', [16, 64])
def test_the_streaming_cores_gradients_are_the_blocked_cores(core_stand_ins,
                                                             block):
    """The wiring itself in float32 (the entry rounds to bfloat16 before
    it): output and the gradients of q, k, v under the blocks' policy."""
    qkv, around = _qkv_and_block(block=block)

    def value_and_grads(core, **remat):
        f = jax.checkpoint(around(core), **remat)
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2)))(*qkv)

    got, got_grads = value_and_grads(latent_attention.flash_attention,
                                     policy=latent_attention.SAVE_ATTN_CORE)
    want, want_grads = value_and_grads(
        latent_attention.causal_attention_blocked)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_the_primal_alone_asks_for_no_statistics(core_stand_ins):
    """Undifferentiated (serving, `eval_shape`) the entry launches one
    forward without (l, m), on bfloat16 operands, and returns float32."""
    (q, k, v), _ = _qkv_and_block()
    out = jax.jit(partial(latent_attention.causal_attention_flash,
                          scale=0.25, block=16))(q, k, v)
    assert out.dtype == jnp.float32 and core_stand_ins == [False]
    want = latent_attention.causal_attention_blocked(q, k, v, 0.25, 16)
    np.testing.assert_allclose(out, want, atol=3e-2)
    shape = jax.eval_shape(partial(latent_attention.causal_attention_flash,
                                   scale=0.25, block=16), q, k, v)
    assert shape.shape == q.shape and core_stand_ins == [False]


@pytest.mark.parametrize('recipe,sizes,layers', [
    ('token_decoder', SIZES, 4), ('hybrid_decoder', HYBRID_SIZES, 1)])
def test_a_decoders_step_launches_one_forward_core_an_attention_layer(
        core_stand_ins, monkeypatch, recipe, sizes, layers):
    """Both decoders' blocks carry the policy: on the streaming path (128
    tokens, the platform check stood in for) the loss's gradient runs one
    forward a layer and none in a block's replay; with nothing saved it runs
    two and gives the same gradient, since what is saved is what the replay
    would rebuild. The loss is the blocked path's to the kernel's bfloat16
    rounding (the gradients' gap holds flipped expert choices too)."""
    from se3_transformer_tpu.models import hybrid_decoder, token_decoder
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 48, (1, 128)), jnp.int32)

    module = RECIPES[recipe](bf16_operands=False, attention_block=64, **sizes)
    params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens)['params']

    def loss_and_grads():     # traced anew: the blocks read the patches
        (loss, _), grads = jax.jit(jax.value_and_grad(
            make_lm_loss(module, chunk=32), has_aux=True))(
            params, dict(tokens=tokens), None)
        return loss, grads

    blocked, _ = loss_and_grads()
    assert core_stand_ins == []                  # blocks of queries in XLA
    monkeypatch.setattr(latent_attention, 'is_tpu_backend', lambda: True)
    loss, grads = loss_and_grads()
    assert core_stand_ins == [True] * layers
    assert float(loss) == pytest.approx(float(blocked), rel=2e-3)
    del core_stand_ins[:]
    for models in (token_decoder, hybrid_decoder):
        monkeypatch.setattr(models, 'SAVE_ATTN_CORE', None)
    replayed_loss, replayed_grads = loss_and_grads()
    assert core_stand_ins == [True] * 2 * layers
    assert float(replayed_loss) == float(loss)
    jax.tree_util.tree_map(
        partial(np.testing.assert_allclose, rtol=1e-6, atol=1e-9),
        grads, replayed_grads)
