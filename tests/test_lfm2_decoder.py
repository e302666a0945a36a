"""The decoder whose layers are an operator and a feed-forward (the pattern
`CF*ECECECE`: gated short convolutions, grouped-query attention with q/k
norms and rotation, a dense or an expert feed-forward, tied head) against the
plain reference the benchmark keeps (`benchmark/harness/lfm2_reference.py`,
loaded under a private package name: it imports nothing of the program), at
tiny widths in float32 on the CPU, and the properties the architecture states
one by one."""
import importlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from se3_transformer_tpu.models.hybrid_decoder import HybridDecoder
from se3_transformer_tpu.ops.expert_layer import ExpertLayer, route
from se3_transformer_tpu.ops.grouped_attention import GroupedQueryAttention
from se3_transformer_tpu.ops.latent_attention import causal_attention
from se3_transformer_tpu.ops.short_conv import ShortConvMixer
from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
from se3_transformer_tpu.training.lm_loss import (
    balance_expert_load, make_lm_loss,
)
from se3_transformer_tpu.training.recipes import RECIPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(vocab_rows=48, hidden_size=32,
             hybrid_override_pattern='CF*ECECECE', conv_L_cache=3,
             intermediate_size=48, moe_intermediate_size=16,
             n_routed_experts=8, num_experts_per_tok=2, experts_held=4,
             expert_rank=1, mlp_hidden_act='silu', routed_scaling_factor=1.0,
             norm_topk_prob=True, norm_topk_eps=1e-6, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, qk_norm=True,
             rope_theta=1e6, layer_norm_epsilon=1e-5,
             tie_word_embeddings=True)


@pytest.fixture(scope='module')
def ref():
    """`lfm2_reference.py` imports `lm_reference.py` from its own directory:
    both are loaded as a package of a name of their own, beside whatever
    `harness` another test has on its path."""
    d = os.path.join(ROOT, 'benchmark', 'harness')
    spec = importlib.util.spec_from_file_location(
        'plain_lfm2_references', os.path.join(d, '__init__.py'),
        submodule_search_locations=[d])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules['plain_lfm2_references'] = pkg
    spec.loader.exec_module(pkg)
    try:
        yield importlib.import_module('plain_lfm2_references.lfm2_reference')
    finally:
        for name in [n for n in sys.modules
                     if n.split('.')[0] == 'plain_lfm2_references']:
            del sys.modules[name]


def _perturbed(params, seed=100):
    """Scales off one and the correction biases off zero, so that a
    comparison covers them."""
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
    module = RECIPES['lfm2_decoder'](bf16_operands=False, attention_block=8,
                                     **SIZES)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 48, (2, 24)), jnp.int32)
    params = _perturbed(jax.jit(module.init)(jax.random.PRNGKey(0),
                                             tokens)['params'])
    return module, params, tokens


def test_recipe_builds_the_decoder_with_two_mixers_a_layer():
    module = RECIPES['lfm2_decoder']()
    assert isinstance(module, HybridDecoder)
    assert module.hybrid_override_pattern == 'CF*ECE'
    assert module.expert_layer_names() == ['blocks_3', 'blocks_5']
    assert RECIPES['lfm2_decoder'](experts_held=2).experts_held == 2


def test_loss_and_every_gradient_leaf_match_the_plain_reference(tiny, ref):
    module, params, tokens = tiny
    loss_fn = make_lm_loss(module, chunk=8)
    (loss, aux), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, dict(tokens=tokens), None)
    (want, chosen), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, SIZES, attn_block=8, chunk=8),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert 'loss_mtp' not in aux
    assert np.array_equal(np.sort(np.asarray(aux['moe_choice']), -1),
                          np.sort(np.asarray(chosen), -1))
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == len(jax.tree_util.tree_leaves(want_grads))
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.linalg.norm(b))
        name = jax.tree_util.keystr(path)
        if 'correction_bias' in name:
            assert scale == 0 and float(jnp.linalg.norm(a)) == 0, name
            continue
        assert scale > 0, name
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * scale, name


def test_the_pattern_builds_an_operator_then_a_feed_forward(tiny):
    module, params, _ = tiny
    kinds = {'conv': 'C', 'mlp': 'F', 'attn': '*', 'moe': 'E'}
    built = ''.join(
        kinds[next(k for k in params[f'blocks_{i}'] if k != 'pre_norm')]
        for i in range(10))
    assert built == 'CF*ECECECE'
    assert all(len(params[f'blocks_{i}']) == 2 for i in range(10))
    assert set(params['blocks_1']['mlp']) == {'gate', 'up', 'down'}
    assert params['blocks_1']['mlp']['gate']['kernel'].shape == (32, 48)
    # three matrices an expert, no shared one
    assert set(params['blocks_3']['moe']) == {
        'router', 'correction_bias', 'experts_gate', 'experts_up',
        'experts_down'}
    assert module.expert_layer_names() == [
        'blocks_3', 'blocks_5', 'blocks_7', 'blocks_9']


def test_fields_of_mixers_the_pattern_lacks_need_not_be_given():
    conv_only = HybridDecoder(vocab_rows=16, hidden_size=8,
                              hybrid_override_pattern='CC')
    tokens = jnp.zeros((1, 4), jnp.int32)
    params = conv_only.init(jax.random.PRNGKey(0), tokens)['params']
    assert set(params) == {'blocks_0', 'blocks_1', 'embedding', 'final_norm',
                           'head'}
    assert conv_only.apply({'params': params}, tokens)[0].shape == (1, 4, 16)


# ------------------------------------------------------------------ #
# the tied head
# ------------------------------------------------------------------ #
def test_a_tied_head_has_no_subtree_and_is_the_embeddings_transpose(tiny):
    module, params, tokens = tiny
    assert 'head' not in params
    assert set(params) == {f'blocks_{i}' for i in range(10)} | {
        'embedding', 'final_norm'}
    emb = params['embedding']['embedding']
    assert np.array_equal(module.head_kernel(params), emb.T)
    logits, _ = module.apply({'params': params}, tokens)
    main, _, _ = module.apply({'params': params}, tokens,
                              method='hidden_states')
    np.testing.assert_allclose(logits, main @ emb.T, rtol=1e-5, atol=1e-6)
    untied = RECIPES['lfm2_decoder'](tie_word_embeddings=False)
    p2 = jax.eval_shape(untied.init, jax.random.PRNGKey(0), tokens)['params']
    assert p2['head']['kernel'].shape == (32, 48)
    assert untied.head_kernel(p2) is p2['head']['kernel']


def test_the_embeddings_gradient_is_the_sum_of_its_two_uses(tiny):
    """As the rows looked up and as the head's matrix: the loss with the
    head's matrix held apart gives the two parts, and they add up."""
    module, params, tokens = tiny
    loss_fn = make_lm_loss(module, chunk=8)
    whole = jax.grad(lambda p: loss_fn(p, dict(tokens=tokens), None)[0])(
        params)['embedding']['embedding']

    class Apart(HybridDecoder):
        def head_kernel(self, params):
            return params['head_apart']

    apart = Apart(**{**SIZES, 'bf16_operands': False, 'attention_block': 8})
    loss_apart = make_lm_loss(apart, chunk=8)
    emb = params['embedding']['embedding']
    g = jax.grad(lambda p: loss_apart(p, dict(tokens=tokens), None)[0])(
        dict(params, head_apart=emb.T))
    as_rows, as_head = g['embedding']['embedding'], g['head_apart'].T
    assert float(jnp.linalg.norm(as_rows)) > 0
    assert float(jnp.linalg.norm(as_head)) > 0
    np.testing.assert_allclose(whole, as_rows + as_head, rtol=1e-5,
                               atol=1e-7)


# ------------------------------------------------------------------ #
# the gated short convolution
# ------------------------------------------------------------------ #
@pytest.fixture(scope='module')
def conv():
    mixer = ShortConvMixer(dim=16, taps=3)
    u = jax.random.normal(jax.random.PRNGKey(20), (2, 12, 16))
    params = mixer.init(jax.random.PRNGKey(21), u)['params']
    return mixer, params, u


def _conv_by_hand(params, u):
    """One token at a time: the window of the last three gated inputs."""
    w_in, w_out = params['in_proj']['kernel'], params['out_proj']['kernel']
    taps = np.asarray(params['conv']['kernel'])
    d = u.shape[-1]
    out = np.zeros(u.shape, np.float64)
    for s in range(u.shape[0]):
        window = np.zeros((3, d))
        for t in range(u.shape[1]):
            bcx = np.asarray(u[s, t] @ w_in, np.float64)
            b, c, x = bcx[:d], bcx[d:2 * d], bcx[2 * d:]
            window = np.concatenate((window[1:], (b * x)[None]))
            z = (taps * window).sum(axis=0)      # tap 2 reads the token itself
            out[s, t] = (c * z) @ np.asarray(w_out, np.float64)
    return out


def test_the_short_convolution_is_the_loop_over_time(conv, ref):
    mixer, params, u = conv
    assert {jax.tree_util.keystr(p): a.shape for p, a in
            jax.tree_util.tree_leaves_with_path(params)} == {
        "['conv']['kernel']": (3, 16), "['in_proj']['kernel']": (16, 48),
        "['out_proj']['kernel']": (16, 16)}
    got = jax.jit(mixer.apply)({'params': params}, u)
    np.testing.assert_allclose(got, _conv_by_hand(params, u), rtol=1e-5,
                               atol=1e-6)
    with jax.default_matmul_precision('highest'):
        for s in range(2):
            np.testing.assert_allclose(
                got[s], ref.short_conv(params, u[s], lambda w: w),
                rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('t', [0, 5, 11])
def test_the_short_convolution_is_causal_to_the_bit(conv, t):
    """Changing token t leaves every output before t as it was, bit for
    bit, and reaches t, t + 1 and t + 2 and no further."""
    mixer, params, u = conv
    apply = jax.jit(mixer.apply)
    before = np.asarray(apply({'params': params}, u))
    after = np.asarray(apply({'params': params},
                             u.at[0, t].add(jnp.ones(16))))
    assert np.array_equal(after[0, :t], before[0, :t])
    assert np.array_equal(after[1], before[1])          # the other sequence
    assert np.array_equal(after[0, t + 3:], before[0, t + 3:])
    assert not np.array_equal(after[0, t], before[0, t])


def test_the_short_convolutions_gradient_is_the_references(conv, ref):
    mixer, params, u = conv
    w = jax.random.normal(jax.random.PRNGKey(22), u.shape)
    got = jax.grad(lambda p, u: jnp.sum(
        w * mixer.apply({'params': p}, u)), argnums=(0, 1))(params, u)
    with jax.default_matmul_precision('highest'):
        want = jax.grad(lambda p, u: sum(
            jnp.sum(w[s] * ref.short_conv(p, u[s], lambda a: a))
            for s in range(2)), argnums=(0, 1))(params, u)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------------ #
# grouped-query attention with q/k norms and rotation
# ------------------------------------------------------------------ #
ATTN = dict(dim=24, heads=8, kv_heads=2, head_dim=4)


@pytest.mark.parametrize('block', [4, 16])
def test_attention_with_qk_norms_and_rotation_against_a_plain_form(ref,
                                                                   block):
    t = 16
    attn = GroupedQueryAttention(**ATTN, block=block, qk_norm=True,
                                 rope_theta=1e6, eps=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, t, 24))
    params = _perturbed(attn.init(jax.random.PRNGKey(11), x)['params'])
    assert params['q_norm']['scale'].shape == (4,)
    assert params['k_norm']['scale'].shape == (4,)
    got = jax.jit(attn.apply)({'params': params}, x)[0]
    h, kv, dh = 8, 2, 4

    def norm(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * g

    def rotate(a):              # [T, heads, dh], pairs (i, i + dh / 2)
        inv = 1.0 / 1e6 ** (np.arange(0, dh, 2) / dh)
        ang = np.arange(t)[:, None, None] * inv
        a1, a2 = a[..., :dh // 2], a[..., dh // 2:]
        return np.concatenate((a1 * np.cos(ang) - a2 * np.sin(ang),
                               a2 * np.cos(ang) + a1 * np.sin(ang)), -1)

    x0 = np.asarray(x[0], np.float64)
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    q = rotate(norm((x0 @ p['q']['kernel']).reshape(t, h, dh),
                    p['q_norm']['scale']))
    k = rotate(norm((x0 @ p['k']['kernel']).reshape(t, kv, dh),
                    p['k_norm']['scale']))
    v = (x0 @ p['v']['kernel']).reshape(t, kv, dh)
    heads = []
    for j in range(h):
        s = q[:, j] @ k[:, j // (h // kv)].T / np.sqrt(dh)
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        heads.append(w / w.sum(-1, keepdims=True) @ v[:, j // (h // kv)])
    want = np.concatenate(heads, axis=-1) @ p['out']['kernel']
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    m = dict(num_attention_heads=h, num_key_value_heads=kv, head_dim=dh,
             layer_norm_epsilon=1e-5, rope_theta=1e6)
    with jax.default_matmul_precision('highest'):
        np.testing.assert_allclose(
            got, ref.attention(params, x[0], m, lambda w: w, 8), rtol=2e-5,
            atol=2e-6)


def test_rotated_scores_depend_on_the_distance_between_positions():
    """Shifting the whole sequence by a constant feature pattern that is the
    same at every position: only the rotation tells positions apart, and it
    keeps a query's product with a key a function of their distance."""
    attn = GroupedQueryAttention(**ATTN, block=8, qk_norm=True,
                                 rope_theta=100.0)
    row = jax.random.normal(jax.random.PRNGKey(12), (24,))
    x = jnp.broadcast_to(row, (1, 16, 24))
    params = attn.init(jax.random.PRNGKey(13), x)['params']
    out = attn.apply({'params': params}, x)[0]
    # every value is the same row, so the output is too, whatever the scores
    np.testing.assert_allclose(out, jnp.broadcast_to(out[0], out.shape),
                               rtol=1e-5, atol=1e-6)
    plain = GroupedQueryAttention(**ATTN, block=8)
    tokens = jax.random.normal(jax.random.PRNGKey(14), (1, 16, 24))
    p0 = plain.init(jax.random.PRNGKey(13), tokens)['params']
    rotated = GroupedQueryAttention(**ATTN, block=8, rope_theta=100.0)
    assert not np.allclose(plain.apply({'params': p0}, tokens),
                           rotated.apply({'params': p0}, tokens))


@pytest.mark.parametrize('block', [4, 16])
def test_without_norms_and_rotation_attention_is_what_it_was(block):
    """The hybrid cell's form: the parameter tree has four matrices and the
    output is, to the bit, the arithmetic this module had before q/k norms
    and rotation became fields (written out here)."""
    attn = GroupedQueryAttention(**ATTN, block=block)
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 16, 24))
    params = attn.init(jax.random.PRNGKey(16), x)['params']
    assert {jax.tree_util.keystr(p): a.shape for p, a in
            jax.tree_util.tree_leaves_with_path(params)} == {
        "['q']['kernel']": (24, 32), "['k']['kernel']": (24, 8),
        "['v']['kernel']": (24, 8), "['out']['kernel']": (32, 24)}

    @jax.jit
    def before(params, x):
        b, t, _ = x.shape
        h, kv, dh = 8, 2, 4
        q = (x @ params['q']['kernel']).reshape(b, t, h, dh)
        k, v = (jnp.repeat((x @ params[n]['kernel']).reshape(b, t, kv, dh),
                           h // kv, axis=2) for n in ('k', 'v'))
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        o = causal_attention(q, k, v, dh ** -0.5, block)
        return o.transpose(0, 2, 1, 3).reshape(b, t, h * dh) \
            @ params['out']['kernel']

    got = jax.jit(attn.apply)({'params': params}, x)
    assert np.array_equal(np.asarray(got), np.asarray(before(params, x)))


# ------------------------------------------------------------------ #
# the expert layer in this form: no shared expert, the published normaliser
# ------------------------------------------------------------------ #
E, K, D, WIDTH = 16, 3, 12, 10
LAYER = dict(n_routed_experts=E, num_experts_per_tok=K, experts_held=E,
             expert_rank=0, routed_scaling_factor=1.0, norm_topk_prob=True)


def _layer(held, rank, **kw):
    return ExpertLayer(width=WIDTH, n_experts=E, top_k=K, experts_held=held,
                       expert_rank=rank, shared_width=0, hidden_act='silu',
                       routed_scale=1.0, norm_topk_eps=1e-6,
                       bf16_operands=False, **kw)


@pytest.fixture(scope='module')
def whole_layer():
    """The uncut layer's parameters (all 16 experts held) and some tokens."""
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D))
    params = _layer(E, 0).init(jax.random.PRNGKey(2), x)['params']
    params = dict(params, correction_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(3), (E,)))
    return params, x


@pytest.mark.parametrize('held', [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(whole_layer, ref, held):
    """The parts of all 16 / held ranks, with no shared expert to count
    once, are what the uncut reference gives for the whole layer."""
    params, x = whole_layer
    assert 'shared' not in params and params['experts_gate'].shape == (
        E, D, WIDTH)
    with jax.default_matmul_precision('highest'):
        want, _ = ref.expert_layer(params, x, LAYER, lambda w: w,
                                   held=range(E))
    total = 0.0
    for rank in range(E // held):
        cut = {k: (v[rank * held:(rank + 1) * held]
                   if k.startswith('experts_') else v)
               for k, v in params.items()}
        out, stats = _layer(held, rank).apply({'params': cut}, x)
        assert int(stats['dropped']) == 0
        total = total + out
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('eps', [1e-20, 1e-6, 0.5])
def test_the_routers_normaliser_is_a_field(eps):
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(4), (6, E)))
    chosen, w = route(scores, jnp.zeros(E), K, 1.0, True, eps)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(-1, keepdims=True) + eps), rtol=1e-6)
    # the default is the value the other two decoders' configurations keep
    assert np.array_equal(route(scores, jnp.zeros(E), K, 1.0, True)[1],
                          route(scores, jnp.zeros(E), K, 1.0, True, 1e-20)[1])
    assert ExpertLayer(width=4, n_experts=8, top_k=2,
                       experts_held=4).norm_topk_eps == 1e-20


# ------------------------------------------------------------------ #
# on the step factory
# ------------------------------------------------------------------ #
def test_balance_expert_load_finds_the_patterns_expert_layers(tiny):
    module, params, tokens = tiny
    settled = balance_expert_load(module, params, [dict(tokens=tokens)],
                                  steps=50)
    moved = {jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(settled)[0],
        jax.tree_util.tree_leaves(params)) if not np.array_equal(a, b)}
    assert moved == {f"['{n}']['moe']['correction_bias']"
                     for n in ('blocks_3', 'blocks_5', 'blocks_7',
                               'blocks_9')}


def test_three_steps_on_the_one_step_factory_with_the_counters_in_aux(tiny):
    module, params, tokens = tiny
    optimizer = optax.adam(1e-3)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    params = jax.tree_util.tree_map(jnp.array, params)     # donated below
    before = np.asarray(params['embedding']['embedding'])
    opt_state = optimizer.init(params)
    losses = []
    for i in range(3):
        params, opt_state, loss, aux = step(params, opt_state,
                                            dict(tokens=tokens),
                                            jax.random.PRNGKey(i))
        losses.append(float(loss))
        pairs = int(aux['moe_local_pairs'])
        # four expert layers, 4 of 8 experts held: about half of 4 x 48
        # tokens x 2 choices
        assert 0 < pairs <= 4 * 48 * 2
        assert int(aux['moe_dropped']) == 0
        assert aux['moe_choice'].shape == (4, 48, 2)
        held = np.asarray(aux['moe_choice']) // 4 == 1           # rank 1
        assert held.sum() == pairs
    assert losses[2] < losses[1] < losses[0]
    assert 'head' not in params
    assert not np.array_equal(np.asarray(params['embedding']['embedding']),
                              before)
