"""The hybrid decoder (layers by a pattern string: Mamba-2 state-space
mixers, two-matrix held experts, grouped-query attention) against the plain
reference the benchmark keeps (`benchmark/harness/hybrid_reference.py`,
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
from se3_transformer_tpu.ops import expert_layer
from se3_transformer_tpu.ops.expert_layer import ExpertLayer
from se3_transformer_tpu.ops.grouped_attention import GroupedQueryAttention
from se3_transformer_tpu.ops.state_space import Mamba2Mixer, chunked_scan
from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
from se3_transformer_tpu.training.lm_loss import (
    balance_expert_load, make_lm_loss,
)
from se3_transformer_tpu.training.recipes import RECIPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(vocab_rows=48, hidden_size=32, hybrid_override_pattern='ME*ME',
             mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=8,
             n_groups=2, conv_kernel=4, chunk_size=16, use_conv_bias=True,
             moe_intermediate_size=16, moe_shared_expert_intermediate_size=24,
             n_routed_experts=8, num_experts_per_tok=2, experts_held=4,
             expert_rank=1, mlp_hidden_act='relu2', routed_scaling_factor=2.5,
             norm_topk_prob=True, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, layer_norm_epsilon=1e-5)


@pytest.fixture(scope='module')
def ref():
    """`hybrid_reference.py` imports `lm_reference.py` from its own
    directory: both are loaded as a package of a name of their own, beside
    whatever `harness` another test has on its path."""
    d = os.path.join(ROOT, 'benchmark', 'harness')
    spec = importlib.util.spec_from_file_location(
        'plain_references', os.path.join(d, '__init__.py'),
        submodule_search_locations=[d])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules['plain_references'] = pkg
    spec.loader.exec_module(pkg)
    try:
        yield importlib.import_module('plain_references.hybrid_reference')
    finally:
        for name in [n for n in sys.modules
                     if n.split('.')[0] == 'plain_references']:
            del sys.modules[name]


def _perturbed(params, seed=100):
    """Scales and D off one, biases off zero, so that a comparison covers
    them."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(flat):
        z = jax.random.normal(jax.random.PRNGKey(seed + i), a.shape)
        name = str(path[-1].key)
        out.append(1 + 0.1 * z if name in ('scale', 'D')
                   else 0.05 * z if name in ('correction_bias', 'bias')
                   else a)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope='module')
def tiny():
    module = RECIPES['hybrid_decoder'](bf16_operands=False, attention_block=8,
                                       **SIZES)
    # 24 tokens: one whole chunk of 16 and half of another
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 48, (2, 24)), jnp.int32)
    params = _perturbed(jax.jit(module.init)(jax.random.PRNGKey(0),
                                             tokens)['params'])
    return module, params, tokens


def test_recipe_builds_the_decoder():
    assert isinstance(RECIPES['hybrid_decoder'](), HybridDecoder)
    assert RECIPES['hybrid_decoder'](experts_held=2).experts_held == 2


def test_loss_and_every_gradient_leaf_match_the_plain_reference(tiny, ref):
    module, params, tokens = tiny
    loss_fn = make_lm_loss(module, chunk=8)
    (loss, aux), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, dict(tokens=tokens), None)
    (want, chosen), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, SIZES, attn_block=8, ssm_block=8,
                           chunk=8), has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert 'loss_mtp' not in aux
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
# the pattern string
# ------------------------------------------------------------------ #
def test_the_pattern_string_builds_its_layers_in_order():
    module = RECIPES['hybrid_decoder'](hybrid_override_pattern='MEMEM*EME')
    assert module.expert_layer_names() == [
        'blocks_1', 'blocks_3', 'blocks_6', 'blocks_8']
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    kinds = {'ssm': 'M', 'moe': 'E', 'attn': '*'}
    built = ''.join(
        kinds[next(k for k in params[f'blocks_{i}'] if k != 'pre_norm')]
        for i in range(9))
    assert built == 'MEMEM*EME'
    # each layer is one mixer and its norm, nothing else; embedding and head
    # are untied; no prediction block
    assert all(len(params[f'blocks_{i}']) == 2 for i in range(9))
    assert set(params) == {f'blocks_{i}' for i in range(9)} | {
        'embedding', 'final_norm', 'head'}
    with pytest.raises(AssertionError):
        jax.eval_shape(RECIPES['hybrid_decoder'](
            hybrid_override_pattern='M-E').init, jax.random.PRNGKey(0),
            tokens)


def test_balance_expert_load_finds_the_patterns_expert_layers(tiny):
    module, params, tokens = tiny
    settled = balance_expert_load(module, params, [dict(tokens=tokens)],
                                  steps=50)
    moved = {jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(settled)[0],
        jax.tree_util.tree_leaves(params)) if not np.array_equal(a, b)}
    assert moved == {f"['{n}']['moe']['correction_bias']"
                     for n in ('blocks_1', 'blocks_4')}


# ------------------------------------------------------------------ #
# the scan
# ------------------------------------------------------------------ #
H, P, N, G = 4, 8, 8, 2


def _scan_inputs(t, dt_scale=1.0, seed=20, groups=G):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (t, H, P))
    dt = dt_scale * jax.nn.softplus(jax.random.normal(keys[1], (t, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (H,), minval=0.0, maxval=2.7))
    b = jax.random.normal(keys[3], (t, groups, N))
    c = jax.random.normal(keys[4], (t, groups, N))
    d = 1 + 0.1 * jax.random.normal(keys[5], (H,))
    return x, dt, a, b, c, d


def _chunked(args, chunk):
    x, dt, a, b, c, d = args
    return chunked_scan(x[None], dt[None], a, b[None], c[None], d, chunk)[0]


@pytest.fixture(params=['einsums', 'kernels'])
def scan_path(request):
    if request.param == 'kernels':
        request.getfixturevalue('scan_on_kernels')
    return request.param


def _value_and_grads(args, chunk, cot):
    return _chunked(args, chunk), jax.grad(
        lambda *v: (_chunked(v, chunk) * cot).sum(), argnums=range(6))(*args)


@pytest.mark.parametrize('t,chunk', [(32, 8), (29, 8), (5, 8), (16, 16)])
def test_the_chunked_scan_is_the_literal_recurrence(ref, scan_path, t, chunk):
    """At lengths that are and are not a multiple of the chunk, shorter than
    one chunk, and one chunk exactly: values and every gradient, in XLA's
    einsums and in the kernels."""
    args = _scan_inputs(t)
    want = ref.scan_recurrence(*args)
    cot = jax.random.normal(jax.random.PRNGKey(21), want.shape)
    got, got_g = _value_and_grads(args, chunk, cot)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    want_g = jax.grad(lambda *v: (ref.scan_recurrence(*v) * cot).sum(),
                      argnums=range(6))(*args)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize('groups', [1, 2])
@pytest.mark.parametrize('t,chunk,dt_scale', [
    (32, 8, 1.0), (29, 8, 1.0), (5, 8, 1.0), (32, 8, 400.0)])
def test_the_kernels_are_the_einsums(request, t, chunk, dt_scale, groups):
    """The kernel path against the einsum path in float32: the value and
    each of the six gradients to 1e-5 of the leaf, at a length the chunk
    divides, one it does not and one shorter than a chunk, where every
    chunk's decay underflows, with the heads in one group and in two."""
    args = _scan_inputs(t, dt_scale, groups=groups)
    cot = jax.random.normal(jax.random.PRNGKey(22), args[0].shape)
    want, want_g = _value_and_grads(args, chunk, cot)
    request.getfixturevalue('scan_on_kernels')
    got, got_g = _value_and_grads(args, chunk, cot)
    scale = {name: float(jnp.abs(b).max()) for name, b in zip(
        ('y', 'x', 'dt', 'a', 'b', 'c', 'd'), (want,) + want_g)}
    if dt_scale > 1:
        # d a = sum_t dt_t g_t is there a sum of terms near dt's own
        # gradient, times dt (50 a token), that cancel to a thousandth of
        # their size: float32 leaves either path 0.013 off float64's
        # -0.0057, and they are held to the terms' size, not the sum's
        scale['a'] = float(jnp.abs(args[1]).sum(0).max()) * scale['dt'] \
            / float(jnp.abs(args[2]).min())
    for name, a, b in zip(scale, (got,) + got_g, (want,) + want_g):
        assert np.isfinite(np.asarray(a)).all(), name
        assert float(jnp.abs(a - b).max()) <= 1e-5 * scale[name], name


def test_a_chunk_whose_decay_underflows_gives_zero_and_no_nan(ref, scan_path):
    """dt large enough that exp(sum of dt A) over a chunk is 0 in float32:
    the state a chunk hands on is forgotten, nothing divides by it, forward
    and backward, on either path."""
    args = _scan_inputs(32, dt_scale=400.0)
    x, dt, a = args[:3]
    assert float(jnp.exp((dt * a).reshape(4, 8, H).sum(1)).max()) == 0.0
    want = ref.scan_recurrence(*args)
    cot = jax.random.normal(jax.random.PRNGKey(23), want.shape)
    got, grads = _value_and_grads(args, 8, cot)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


@pytest.mark.parametrize('block', [4, 8, 32])
def test_the_references_masked_product_is_the_literal_recurrence(ref, block):
    args = _scan_inputs(32)
    np.testing.assert_allclose(ref.scan_masked(*args, block),
                               ref.scan_recurrence(*args), rtol=2e-5,
                               atol=2e-5)


def test_the_state_is_causal_and_a_head_reads_its_groups_b_and_c(ref):
    args = _scan_inputs(24)
    x, dt, a, b, c, d = args
    got = _chunked(args, 8)
    # a later token does not move an earlier output
    moved = _chunked((x.at[13].add(1.0), dt, a, b, c, d), 8)
    np.testing.assert_allclose(moved[:13], got[:13], rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(moved[13:] - got[13:]).max()) > 1e-3
    # heads 0, 1 read group 0 and heads 2, 3 group 1: moving group 1's B
    # leaves heads 0 and 1 alone
    moved = _chunked((x, dt, a, b.at[:, 1].add(1.0), c, d), 8)
    np.testing.assert_allclose(moved[:, :2], got[:, :2], rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(moved[:, 2:] - got[:, 2:]).max()) > 1e-3


def test_the_mixer_against_the_reference_and_its_parameters(ref):
    mixer = Mamba2Mixer(dim=32, num_heads=H, head_dim=P, state_size=N,
                        n_groups=G, chunk_size=8)
    u = jax.random.normal(jax.random.PRNGKey(22), (1, 21, 32))
    params = _perturbed(mixer.init(jax.random.PRNGKey(23), u)['params'])
    inner, gn = H * P, G * N
    assert params['in_proj']['kernel'].shape == (32, 2 * inner + 2 * gn + H)
    assert params['conv']['kernel'].shape == (4, inner + 2 * gn)
    assert params['conv']['bias'].shape == (inner + 2 * gn,)
    assert params['gate_norm']['scale'].shape == (inner,)
    assert params['out_proj']['kernel'].shape == (inner, 32)
    for name in ('A_log', 'dt_bias', 'D'):
        assert params[name].shape == (H,)
    # Mamba-2's initialisation: A in [1, 16], softplus(dt_bias) in
    # [time_step_min, time_step_max]
    fresh = mixer.init(jax.random.PRNGKey(24), u)['params']
    assert 0.0 <= float(fresh['A_log'].min()) \
        and float(fresh['A_log'].max()) <= np.log(16.0)
    dt0 = jax.nn.softplus(fresh['dt_bias'])
    assert 1e-3 * 0.999 <= float(dt0.min()) and float(dt0.max()) <= 0.1001
    m = dict(SIZES, chunk_size=8)
    want = ref.mamba(params, u[0], m, lambda w: w, 8)
    np.testing.assert_allclose(mixer.apply({'params': params}, u)[0], want,
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ #
# the two-matrix expert layer
# ------------------------------------------------------------------ #
D, WIDTH, SHARED, E, K = 16, 8, 12, 16, 3
LAYER = dict(hidden_size=D, moe_intermediate_size=WIDTH, n_routed_experts=E,
             num_experts_per_tok=K, routed_scaling_factor=2.5,
             norm_topk_prob=True)


def _layer(held, rank, shared=True, **kw):
    return ExpertLayer(width=WIDTH, n_experts=E, top_k=K, experts_held=held,
                       expert_rank=rank, shared_width=SHARED if shared else 0,
                       hidden_act='relu2', routed_scale=2.5,
                       bf16_operands=False, **kw)


@pytest.fixture(scope='module')
def whole_layer():
    """The uncut layer's parameters (all 16 experts held) and some tokens."""
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D))
    params = _layer(E, 0).init(jax.random.PRNGKey(2), x)['params']
    params = dict(params, correction_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(3), (E,)))
    return params, x


def test_an_expert_has_two_matrices_and_the_shared_one_its_own_width(
        whole_layer):
    params, _ = whole_layer
    assert set(params) == {'router', 'correction_bias', 'experts_up',
                           'experts_down', 'shared'}
    assert params['experts_up'].shape == (E, D, WIDTH)
    assert params['experts_down'].shape == (E, WIDTH, D)
    assert set(params['shared']) == {'up', 'down'}
    assert params['shared']['up']['kernel'].shape == (D, SHARED)


@pytest.mark.parametrize('held', [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(whole_layer, ref, held):
    """The routed parts of all 16 / held shares, with the shared expert
    counted once, are what the uncut reference gives for the whole layer."""
    params, x = whole_layer
    want, _ = ref.expert_layer(params, x, LAYER, lambda w: w, held=range(E))
    total = 0.0
    for rank in range(E // held):
        shared = rank == 0
        cut = {k: (v[rank * held:(rank + 1) * held]
                   if k.startswith('experts_') else v)
               for k, v in params.items() if shared or k != 'shared'}
        out, stats = _layer(held, rank, shared).apply({'params': cut}, x)
        assert int(stats['dropped']) == 0
        total = total + out
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)


def test_the_experts_form_is_relu_squared_by_hand(whole_layer):
    """One token, by hand: its three experts' relu(x U)^2 D, weighted, and
    the shared expert's."""
    params, x = whole_layer
    out, stats = _layer(E, 0).apply({'params': params}, x)
    scores = jax.nn.sigmoid(x[5] @ params['router']['kernel'])
    chosen = np.asarray(stats['chosen'][5])
    w = 2.5 * scores[chosen] / scores[chosen].sum()
    want = sum(
        w_e * (jnp.square(jax.nn.relu(x[5] @ params['experts_up'][e]))
               @ params['experts_down'][e]) for e, w_e in zip(chosen, w))
    s = params['shared']
    want = want + jnp.square(jax.nn.relu(x[5] @ s['up']['kernel'])) \
        @ s['down']['kernel']
    np.testing.assert_allclose(out[5], want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ #
# grouped-query attention
# ------------------------------------------------------------------ #
@pytest.mark.parametrize('block', [4, 16])
def test_grouped_query_attention_against_explicit_heads(ref, block):
    """Every query head by hand against the key-value head of its group
    (32 heads over 2 key-value heads), no rotation, scale 1 / sqrt(head_dim);
    and against the reference, which never repeats a key-value head."""
    h, kv, dh, t = 32, 2, 4, 16
    attn = GroupedQueryAttention(dim=24, heads=h, kv_heads=kv, head_dim=dh,
                                 block=block)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, t, 24))
    params = attn.init(jax.random.PRNGKey(11), x)['params']
    assert params['q']['kernel'].shape == (24, h * dh)
    assert params['k']['kernel'].shape == (24, kv * dh)
    assert params['v']['kernel'].shape == (24, kv * dh)
    assert params['out']['kernel'].shape == (h * dh, 24)
    got = jax.jit(attn.apply)({'params': params}, x)[0]
    q = (x[0] @ params['q']['kernel']).reshape(t, h, dh)
    k = (x[0] @ params['k']['kernel']).reshape(t, kv, dh)
    v = (x[0] @ params['v']['kernel']).reshape(t, kv, dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    heads = []
    for j in range(h):
        s = q[:, j] @ k[:, j // (h // kv)].T / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        heads.append(p @ v[:, j // (h // kv)])
    want = jnp.concatenate(heads, axis=-1) @ params['out']['kernel']
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    m = dict(num_attention_heads=h, num_key_value_heads=kv, head_dim=dh)
    np.testing.assert_allclose(
        got, ref.attention(params, x[0], m, lambda w: w, 8), rtol=2e-5,
        atol=2e-6)
    # no rotation: the first query sees only itself, whatever its position
    np.testing.assert_allclose(
        got[0], jnp.repeat(v[0], h // kv, axis=0).reshape(-1)
        @ params['out']['kernel'], rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------------ #
# on the step factory; the other decoder's tree
# ------------------------------------------------------------------ #
@pytest.mark.parametrize('bound', [expert_layer.HELD_ROW_BOUND, (1, 1)],
                         ids=['the full size alone', 'a bound that binds'])
def test_three_steps_on_the_one_step_factory_with_the_counters_in_aux(
        tiny, monkeypatch, bound):
    """With the layer's constant as it is these sizes have the full size
    alone; at the balanced expectation itself a layer's held pairs fall on
    either side of the bound, inside the recomputed blocks of the step."""
    monkeypatch.setattr(expert_layer, 'HELD_ROW_BOUND', bound)
    rows = expert_layer.held_row_bound(48 * 2, 4, 8)
    assert rows == (48 * 2 if bound[0] == 2 else 48)
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
        # two expert layers, 4 of 8 experts held: about half of 2 x 48
        # tokens x 2 choices
        assert 0 < pairs <= 2 * 48 * 2
        assert float(aux['moe_load_mean']) == pytest.approx(pairs / 8)
        assert int(aux['moe_load_max']) >= float(aux['moe_load_mean'])
        assert int(aux['moe_dropped']) == 0
        assert aux['moe_choice'].shape == (2, 48, 2)
        held = np.asarray(aux['moe_choice']) // 4 == 1           # rank 1
        assert held.sum() == pairs
        fit = held.sum(axis=(1, 2)) <= rows
        assert int(aux['moe_bounded']) == fit.sum()
    assert losses[2] < losses[1] < losses[0]


def test_the_token_decoders_parameter_tree_is_what_it_was():
    """The expert's form became a field of the expert layer: the other
    decoder's tree (paths and shapes at its recipe's tiny widths) is pinned,
    its seeded fill being by leaf name."""
    module = RECIPES['token_decoder']()
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 16), jnp.int32))['params']
    got = {jax.tree_util.keystr(p): a.shape for p, a in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    attn = {"['q_a']['kernel']": (32, 12), "['q_a_norm']['scale']": (12,),
            "['q_b']['kernel']": (12, 24), "['kv_a']['kernel']": (32, 12),
            "['kv_a_norm']['scale']": (8,), "['kv_b']['kernel']": (8, 40),
            "['out']['kernel']": (24, 32)}
    moe = {"['router']['kernel']": (32, 8), "['correction_bias']": (8,),
           "['experts_gate']": (4, 32, 16), "['experts_up']": (4, 32, 16),
           "['experts_down']": (4, 16, 32),
           "['shared']['gate']['kernel']": (32, 16),
           "['shared']['up']['kernel']": (32, 16),
           "['shared']['down']['kernel']": (16, 32)}
    mlp = {"['gate']['kernel']": (32, 48), "['up']['kernel']": (32, 48),
           "['down']['kernel']": (48, 32)}

    def block(name, ff_name, ff):
        out = {f"['{name}']['attn']{k}": v for k, v in attn.items()}
        out.update({f"['{name}']['{ff_name}']{k}": v for k, v in ff.items()})
        out.update({f"['{name}']['attn_norm']['scale']": (32,),
                    f"['{name}']['ff_norm']['scale']": (32,)})
        return out

    want = {"['embedding']['embedding']": (48, 32),
            "['final_norm']['scale']": (32,), "['head']['kernel']": (32, 48),
            "['mtp_token_norm']['scale']": (32,),
            "['mtp_hidden_norm']['scale']": (32,),
            "['mtp_final_norm']['scale']": (32,),
            "['mtp_proj']['kernel']": (64, 32)}
    want.update(block('blocks_0', 'mlp', mlp))
    want.update(block('blocks_1', 'moe', moe))
    want.update(block('mtp_block', 'moe', moe))
    assert got == want
