"""The decoder that mixes global attention layers without rotation and
sliding-window layers with it, ReGLU experts routed by the attention step's
input (`ops/sliding_window.py`, `ops/grouped_attention.py`,
`ops/expert_layer.py`, `models/hybrid_decoder.py`) against the plain
reference the benchmark keeps (`benchmark/harness/smallthinker_reference.py`,
loaded under a private package name: it imports nothing of the program), at
tiny widths in float32 on the CPU, and the window's properties one by one."""
import importlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from se3_transformer_tpu.kernels import pallas_block_attention as kernels
from se3_transformer_tpu.kernels.pallas_qk_pass import rotary_tables
from se3_transformer_tpu.ops import sliding_window as sw
from se3_transformer_tpu.ops.expert_layer import ExpertLayer
from se3_transformer_tpu.ops.grouped_attention import GroupedQueryAttention
from se3_transformer_tpu.ops.latent_attention import (
    causal_attention_blocked,
)
from se3_transformer_tpu.ops.rotary import apply_rotary_halves, rotary_angles
from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
from se3_transformer_tpu.training.lm_loss import (
    balance_expert_load, make_lm_loss,
)
from se3_transformer_tpu.training.recipes import RECIPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(vocab_rows=48, hidden_size=32,
             hybrid_override_pattern='*EWEWEWE', moe_intermediate_size=16,
             n_routed_experts=8, num_experts_per_tok=2, experts_held=4,
             expert_rank=1, mlp_hidden_act='relu', scoring_func='softmax',
             routed_scaling_factor=1.0, norm_topk_prob=True,
             norm_topk_eps=1e-20, moe_enable_early_router=True,
             num_attention_heads=6, num_key_value_heads=2, head_dim=8,
             qk_norm=False, rope_theta=None, sliding_window_size=5,
             sliding_rope_theta=1.5e6, layer_norm_epsilon=1e-6)
T = 16


@pytest.fixture(scope='module')
def ref():
    """`smallthinker_reference.py` imports `lm_reference.py` from its own
    directory: both are loaded as a package of a name of their own, beside
    whatever `harness` another test has on its path."""
    d = os.path.join(ROOT, 'benchmark', 'harness')
    name = 'plain_smallthinker_references'
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(d, '__init__.py'), submodule_search_locations=[d])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules[name] = pkg
    spec.loader.exec_module(pkg)
    try:
        yield importlib.import_module(f'{name}.smallthinker_reference')
    finally:
        for n in [n for n in sys.modules if n.split('.')[0] == name]:
            del sys.modules[n]


def _perturbed(params, seed=100):
    """Scales off one and the correction biases off zero, so that a
    comparison covers them."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(flat):
        z = jax.random.normal(jax.random.PRNGKey(seed + i), a.shape)
        name = str(path[-1].key)
        out.append(1 + 0.1 * z if name == 'scale'
                   else 0.005 * z if name == 'correction_bias' else a)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope='module')
def tiny():
    module = RECIPES['smallthinker_decoder'](
        bf16_operands=False, attention_block=8, **SIZES)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 48)
    params = _perturbed(module.init(jax.random.PRNGKey(0), tokens)['params'])
    return module, params, tokens


# ------------------------------------------------------------------ #
# the rule and its counts
# ------------------------------------------------------------------ #
def test_the_window_counts_the_token_itself():
    """Query i sees keys i - w + 1 .. i: min(i + 1, w) of them."""
    ids = np.arange(T)
    seen = sw.visible(ids[:, None], ids[None, :], 5)
    for i in ids:
        assert set(np.flatnonzero(seen[i])) == set(range(max(0, i - 4),
                                                         i + 1)), i
    assert seen.sum() == sw.visible_pairs(T, 5) == 16 * 5 - 10 == 70
    assert sw.visible_pairs(T, T) == sw.visible_pairs(T, 99) == 136
    # the cell's: 44% of the causal triangle
    assert sw.visible_pairs(16384, 4096) == 58_722_304
    assert sw.visible_pairs(16384, 16384) == 134_225_920


# ------------------------------------------------------------------ #
# the blocked core
# ------------------------------------------------------------------ #
def _qkv(t, heads, d, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (1, heads, t, d)) for k in keys]


def _dense(q, k, v, scale, window):
    """softmax over an explicit [T, T] mask."""
    t = q.shape[2]
    ids = jnp.arange(t)
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    s = jnp.where(sw.visible(ids[:, None], ids[None, :], window), s,
                  -jnp.inf)
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize('window,block', [(5, 8), (13, 8), (8, 8), (1, 4),
                                          (20, 16), (31, 8)])
def test_the_blocked_core_is_the_dense_masked_softmax(window, block):
    """Forward and the gradients of q, k and v, at windows that divide no
    block (5, 13, 31 of blocks of 8), that are a block, and of one key."""
    q, k, v, do = _qkv(32, 3, 8)
    with jax.default_matmul_precision('highest'):
        got, vjp = jax.vjp(lambda *a: causal_attention_blocked(
            *a, 0.3, block, window), q, k, v)
        want, want_vjp = jax.vjp(lambda *a: _dense(*a, 0.3, window), q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        for a, b in zip(vjp(do), want_vjp(do)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize('window', [0, 32, 33, 1000])
def test_a_window_of_the_sequence_or_more_is_the_causal_core_to_the_bit(
        window):
    q, k, v, do = _qkv(32, 3, 8)
    got, vjp = jax.vjp(lambda *a: causal_attention_blocked(
        *a, 0.3, 8, window), q, k, v)
    want, want_vjp = jax.vjp(lambda *a: causal_attention_blocked(*a, 0.3, 8),
                             q, k, v)
    assert np.array_equal(got, want)
    for a, b in zip(vjp(do), want_vjp(do)):
        assert np.array_equal(a, b)


def test_keys_behind_the_window_are_never_computed():
    """A block of queries is handed the keys from its first row's first
    visible key on: static extents, so what lies behind the window costs
    nothing (at T = 16,384 and a window of 4,096, 44% of the triangle)."""
    q, k, v, _ = _qkv(64, 2, 8)
    jaxpr = str(jax.make_jaxpr(lambda *a: causal_attention_blocked(
        *a, 0.3, 8, 12))(q, k, v))
    widths = sorted({int(m) for m in re.findall(
        r'f32\[1,2,8,(\d+)\] = (?:mul|dot_general)', jaxpr)})
    # rows i .. i + 7 against keys max(0, i - 11) .. i + 7
    assert widths == [8, 16, 19], widths


# ------------------------------------------------------------------ #
# the table and the launches
# ------------------------------------------------------------------ #
TABLES = [(512, 200, 128), (512, 128, 128), (512, 129, 128), (1024, 256, 128),
          (512, 1, 128), (512, 512, 128), (512, 9999, 128), (768, 300, 256)]


@pytest.mark.parametrize('positions,window,tile', TABLES)
def test_the_window_table_is_the_tiles_with_a_visible_pair(positions, window,
                                                           tile):
    """Every tile with a visible pair is a column, once, in the order of the
    query tiles, and no other; a `FULL` tile is wholly visible; a boundary
    tile's bounds give the rule on its offsets."""
    table = kernels.window_table(positions, window, tile)
    n = positions // tile
    ids = np.arange(positions)
    seen = sw.visible(ids[:, None], ids[None, :], window)
    tiles = seen.reshape(n, tile, n, tile).transpose(0, 2, 1, 3)
    want = {(i, j) for i in range(n) for j in range(n) if tiles[i, j].any()}
    columns = list(zip(table[kernels.QUERY], table[kernels.KEY]))
    assert len(columns) == len(set(columns)) and set(columns) == want
    assert list(table[kernels.QUERY]) == sorted(table[kernels.QUERY])
    assert sw.visited_tiles(positions, window, tile) == len(want)
    r, c = np.arange(tile)[:, None], np.arange(tile)[None, :]
    for col in table.T:
        i, j, kind, low, high, first, last = (int(x) for x in col)
        mask = (r - c >= low) & (r - c <= high)
        assert np.array_equal(mask, tiles[i, j]), (i, j)
        assert (kind == kernels.FULL) == bool(tiles[i, j].all()), (i, j)
    firsts = table[kernels.FIRST].astype(bool)
    assert list(table[kernels.QUERY][firsts]) == list(range(n))
    assert sw.boundary_tiles(positions, window, tile) == sum(
        1 for i, j in want if not tiles[i, j].all())


def test_the_cells_table_launches_no_tile_outside_the_window():
    """At 16,384 positions, a window of 4,096 and tiles of 512: 252 tiles a
    head where the causal triangle has 528, 56 of them on a boundary."""
    assert sw.visited_tiles(16384, 4096, 512) == 252
    assert sw.boundary_tiles(16384, 4096, 512) == 56
    assert sw.visited_tiles(16384, 16384, 512) == 528
    table = kernels.window_table(16384, 4096, 512)
    back = table[kernels.QUERY] - table[kernels.KEY]
    assert back.min() == 0 and back.max() == 8
    assert kernels.launches_run(16384, 512, 28, 4, 128)
    assert not kernels.launches_run(65536, 512, 28, 4, 128)     # dk, dv
    assert not kernels.launches_run(16384, 512, 28, 4, 64)
    assert not kernels.launches_run(16384, 512, 28, 3, 128)


def _projected(t, heads, kv, d, seed=5):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (1, t, heads * d)),
            jax.random.normal(keys[1], (1, t, kv * d)),
            jax.random.normal(keys[2], (1, t, kv * d)),
            jax.random.normal(keys[3], (1, t, heads * d)))


def _composed(q, k, v, angles, heads, kv, d, window, block):
    """What the layer does off the TPU: heads laid out, rotated, the
    key-value heads repeated, the blocked core."""
    t = q.shape[1]
    q, k, v = (a.reshape(1, t, n, d) for a, n in ((q, heads), (k, kv),
                                                   (v, kv)))
    if angles is not None:
        q, k = (apply_rotary_halves(a, angles[None, :, None, :])
                for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    o = causal_attention_blocked(q, k, v, d ** -0.5, block, window)
    return o.transpose(0, 2, 1, 3).reshape(1, t, heads * d)


@pytest.mark.parametrize('window', [200, 128, 300, 512])
@pytest.mark.parametrize('rotate', [True, False])
def test_the_launches_interpreted_are_the_blocked_core(window, rotate):
    """`qk_pass_fwd`, `swa_core_fwd`, `swa_core_bwd` and `qk_pass_bwd`
    interpreted, in the projections' layout, at 14 query heads over 2
    key-value heads (groups of 7) and tiles of 128, against the composition
    and the blocked core: a window that divides no tile, one that is a tile,
    one between two tiles and the causal triangle; forward and the
    gradients of q, k and v."""
    t, heads, kv, d = 512, 14, 2, 128
    q, k, v, do = _projected(t, heads, kv, d)
    angles = rotary_angles(jnp.arange(t), d, 1.5e6) if rotate else None

    @jax.jit        # one program: op by op the composition takes a minute
    def both(q, k, v, do):
        got, vjp = jax.vjp(lambda *a: kernels.block_attention(
            *a, None, rotary_tables(angles) if rotate else None, d,
            d ** -0.5, 1e-6, ('swa', window), 128, True), q, k, v)
        want, want_vjp = jax.vjp(lambda *a: _composed(
            *a, angles, heads, kv, d, window, 64), q, k, v)
        return got, vjp(do), want, want_vjp(do)

    with jax.default_matmul_precision('highest'):
        got, grads, want, want_grads = both(q, k, v, do)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(grads, want_grads):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


@pytest.mark.parametrize('policy,forwards', [('SAVE_ATTN_CORE', 1),
                                             (None, 2)])
def test_a_rematted_window_core_replays_no_forward_launch(policy, forwards):
    """As the block-diffusion core: the forward's output and log-sum-exp
    carry the names `SAVE_ATTN_CORE` keeps."""
    from se3_transformer_tpu.ops import latent_attention
    q = jnp.ones((1, 256, 2 * 128))
    k = v = q[:, :, :128]
    core = jax.checkpoint(
        lambda q, k, v: kernels.block_attention(
            q, k, v, None, None, 128, 0.1, 1e-6, ('swa', 100), 128, True),
        policy=policy and getattr(latent_attention, policy))
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: core(*a).sum(), argnums=(0, 1, 2)))(q, k, v))
    found = re.findall(r'name=(swa_core_\w+)', jaxpr)
    assert sorted(found) == ['swa_core_bwd'] + ['swa_core_fwd'] * forwards, \
        found
    assert 'bd_core' not in jaxpr


# ------------------------------------------------------------------ #
# the layer
# ------------------------------------------------------------------ #
@pytest.mark.parametrize('case,t,block,head_dim,kv,runs', [
    ('the cell\'s shapes in small', 256, 128, 128, 2, True),
    ('a sequence shorter than a tile', 128, 512, 128, 2, True),
    ('a sequence that no tile divides', 192, 128, 128, 2, False),
    ('heads of 64', 256, 128, 64, 2, False),
    ('query heads that no group divides', 256, 128, 128, 4, False),
])
def test_on_a_tpu_a_window_layer_takes_the_launches_where_they_run(
        monkeypatch, case, t, block, head_dim, kv, runs):
    """One path a platform: on a TPU the layer takes the kernels (the one
    pass and the core under the window's rule, in the projections' layout)
    at the shapes `launches_run` admits, the composition and the blocked
    core at any other; the parameter tree is the same either way, and has no
    q/k norms."""
    from se3_transformer_tpu.ops import grouped_attention
    taken = []
    monkeypatch.setattr(
        grouped_attention, 'causal_attention_blocked',
        lambda q, k, v, scale, block, window: taken.append(
            ('blocked', q.shape, k.shape, window)) or q)
    monkeypatch.setattr(
        kernels, 'block_attention',
        lambda q, k, v, norms, rotary, *a: taken.append(
            ('kernels', q.shape, k.shape, norms, len(rotary)) + a) or q)
    attn = GroupedQueryAttention(dim=32, heads=6, kv_heads=kv,
                                 head_dim=head_dim, block=block,
                                 rope_theta=1.5e6, eps=1e-6, window=100)
    x = jax.ShapeDtypeStruct((1, t, 32), jnp.float32)

    def tree():
        taken.clear()
        if 6 % kv:
            with pytest.raises(AssertionError):
                jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)
            return None
        params = jax.eval_shape(attn.init, jax.random.PRNGKey(0),
                                x)['params']
        return jax.tree_util.tree_map(lambda a: a.shape, params)

    off = tree()
    if 6 % kv:
        return
    assert taken == [('blocked', (1, 6, t, head_dim), (1, 6, t, head_dim),
                      100)], case
    monkeypatch.setattr(sw, 'is_tpu_backend', lambda: True)
    assert tree() == off == dict(
        q=dict(kernel=(32, 6 * head_dim)), k=dict(kernel=(32, kv * head_dim)),
        v=dict(kernel=(32, kv * head_dim)),
        out=dict(kernel=(6 * head_dim, 32)))
    assert taken == ([(
        'kernels', (1, t, 6 * head_dim), (1, t, kv * head_dim), None, 2,
        head_dim, head_dim ** -0.5, 1e-6, ('swa', 100), min(block, t))]
        if runs else [('blocked', (1, 6, t, head_dim), (1, 6, t, head_dim),
                       100)]), case


def test_a_window_layer_is_the_dense_masked_softmax_at_groups_of_7():
    """The module with 14 query heads over 2 key-value heads, rotation and a
    window that divides no block, against an explicit mask: forward and the
    gradient of every parameter."""
    attn = GroupedQueryAttention(dim=24, heads=14, kv_heads=2, head_dim=8,
                                 block=8, rope_theta=1.5e6, window=11)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 24))
    params = attn.init(jax.random.PRNGKey(1), x)['params']

    def dense(p, x):
        b, t, _ = x.shape
        q = (x @ p['q']['kernel']).reshape(b, t, 14, 8)
        k = (x @ p['k']['kernel']).reshape(b, t, 2, 8)
        v = (x @ p['v']['kernel']).reshape(b, t, 2, 8)
        ang = rotary_angles(jnp.arange(t), 8, 1.5e6)[None, :, None, :]
        q, k = apply_rotary_halves(q, ang), apply_rotary_halves(k, ang)
        k, v = (jnp.repeat(a, 7, axis=2) for a in (k, v))
        o = _dense(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)), 8 ** -0.5,
                   11)
        return o.transpose(0, 2, 1, 3).reshape(b, t, 112) @ p['out']['kernel']

    with jax.default_matmul_precision('highest'):
        got, g = jax.jit(jax.value_and_grad(lambda p: jnp.sum(jnp.sin(
            attn.apply({'params': p}, x)))))(params)
        want, w = jax.jit(jax.value_and_grad(lambda p: jnp.sum(jnp.sin(
            dense(p, x)))))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(w)):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


def test_a_window_and_a_block_length_do_not_go_together():
    attn = GroupedQueryAttention(dim=8, heads=2, kv_heads=1, head_dim=8,
                                 window=4)
    x = jnp.zeros((1, 16, 8))
    with pytest.raises(AssertionError):
        attn.init(jax.random.PRNGKey(0), x, jnp.arange(16), 4)


# ------------------------------------------------------------------ #
# the expert form and the early router
# ------------------------------------------------------------------ #
D, WIDTH, E, K = 16, 24, 8, 3


def _layer(held, rank, **kw):
    fields = dict(width=WIDTH, n_experts=E, top_k=K, experts_held=held,
                  expert_rank=rank, shared_width=0, hidden_act='relu',
                  routed_scale=1.0, scoring_func='softmax',
                  bf16_operands=False)
    return ExpertLayer(**dict(fields, **kw))


@pytest.fixture(scope='module')
def whole_layer():
    """The uncut layer's parameters (all 8 experts held), the rows its
    experts read and the rows its router reads."""
    x, u = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    params = _layer(E, 0).init(jax.random.PRNGKey(2), x)['params']
    params = dict(params, correction_bias=0.01 * jax.random.normal(
        jax.random.PRNGKey(3), (E,)))
    return params, x, u


def _per_token(params, x, u, shared=False):
    """A loop over tokens and their chosen experts, every expert held."""
    p = jax.nn.softmax(jnp.dot(u, params['router']['kernel']), axis=-1)
    _, chosen = jax.lax.top_k(
        p + jax.lax.stop_gradient(params['correction_bias']), K)
    rows = []
    for n in range(x.shape[0]):
        w = p[n, chosen[n]] / p[n, chosen[n]].sum()
        row = 0.0
        for slot in range(K):
            e = chosen[n, slot]
            gate, up, down = (params[f'experts_{name}'][e]
                              for name in ('gate', 'up', 'down'))
            row = row + w[slot] * (
                jax.nn.relu(x[n] @ gate) * (x[n] @ up)) @ down
        if shared:
            s = params['shared']
            row = row + (jax.nn.relu(x[n] @ s['gate']['kernel'])
                         * (x[n] @ s['up']['kernel'])) @ s['down']['kernel']
        rows.append(row)
    return jnp.stack(rows)


@pytest.mark.parametrize('shared', [False, True])
def test_the_relu_form_is_a_loop_over_tokens(whole_layer, shared):
    """`EXPERT_FORMS['relu']`, routed experts (through `grouped_dot`) and the
    shared one (through `gated_ff` with ReLU and its slope): forward and
    every gradient leaf against a per-token loop."""
    params, x, _ = whole_layer
    layer = _layer(E, 0, shared_width=20 if shared else 0)
    if shared:
        params = dict(layer.init(jax.random.PRNGKey(2), x)['params'],
                      **{k: v for k, v in params.items()})
    with jax.default_matmul_precision('highest'):
        got, g = jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            layer.apply({'params': p}, x)[0])), argnums=(0, 1))(params, x)
        want, w = jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            _per_token(p, x, x, shared))), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(g)[0]
    assert len(flat) == (9 if shared else 6)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(w)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['correction_bias']"):
            assert not np.any(a) and not np.any(b)
            continue
        assert float(jnp.linalg.norm(b)) > 0, name
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5, name


def test_the_gated_rule_with_relu_is_autodiff_of_the_plain_formula():
    """`gated_ff(..., act='relu')`: output and four cotangents against
    `jax.vjp` of (relu(x wg) * (x wu)) wd, operands in float32."""
    from se3_transformer_tpu.ops.expert_layer import GATE_ACTS, gated_ff
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    x, dy = (jax.random.normal(k, (48, 16)) for k in keys[:2])
    wg, wu = (jax.random.normal(k, (16, 40)) * 0.25 for k in keys[2:4])
    wd = jax.random.normal(keys[4], (40, 16)) * 40 ** -0.5
    assert sorted(GATE_ACTS) == ['relu', 'silu']
    with jax.default_matmul_precision('highest'):
        got, vjp = jax.vjp(lambda *a: gated_ff(*a, None, 'relu'), x, wg, wu,
                           wd)
        want, want_vjp = jax.vjp(lambda x, wg, wu, wd: (
            jax.nn.relu(x @ wg) * (x @ wu)) @ wd, x, wg, wu, wd)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        for a, b in zip(vjp(dy), want_vjp(dy)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_a_layer_handed_nothing_is_todays_to_the_bit(whole_layer):
    """No routing input, `None`, and the experts' own rows handed over as the
    routing input: one output, one set of stats, one parameter tree."""
    params, x, _ = whole_layer
    layer = _layer(4, 1)
    cut = {k: v[4:] if k.startswith('experts_') else v
           for k, v in params.items()}
    base = layer.apply({'params': cut}, x)
    for args in ((x, None), (x, x)):
        out = layer.apply({'params': cut}, *args)
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(base)):
            assert np.array_equal(a, b)
    shapes = [jax.tree_util.tree_map(jnp.shape, layer.init(
        jax.random.PRNGKey(0), *args)['params']) for args in ((x,), (x, x))]
    assert shapes[0] == shapes[1]


def test_the_router_reads_what_it_is_handed_and_the_experts_their_rows(
        whole_layer):
    """Choices and weights from u, the experts' products over x: against
    the per-token loop, forward and the gradients of x, u and every
    leaf."""
    params, x, u = whole_layer
    layer = _layer(E, 0)
    out, stats = layer.apply({'params': params}, x, u)
    p = jax.nn.softmax(jnp.dot(u, params['router']['kernel'],
                               precision='highest'), axis=-1)
    np.testing.assert_allclose(stats['scores'], p, rtol=1e-6)
    assert np.array_equal(stats['chosen'], jax.lax.top_k(
        p + params['correction_bias'], K)[1])
    assert not np.array_equal(stats['chosen'],
                              layer.apply({'params': params}, x)[1]['chosen'])
    with jax.default_matmul_precision('highest'):
        got, g = jax.value_and_grad(lambda p, x, u: jnp.sum(jnp.sin(
            layer.apply({'params': p}, x, u)[0])), argnums=(0, 1, 2))(
            params, x, u)
        want, w = jax.value_and_grad(lambda p, x, u: jnp.sum(jnp.sin(
            _per_token(p, x, u))), argnums=(0, 1, 2))(params, x, u)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(w)):
        if np.any(b):
            assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


@pytest.mark.parametrize('held', [2, 4])
def test_the_shares_add_up_to_the_uncut_references_layer(whole_layer, ref,
                                                         held):
    """The partial results of all 8 / held ranks (four shares of two, as the
    cell's four of sixteen), routed by the attention step's input, with no
    shared expert to count once, are what the uncut reference gives for the
    whole layer."""
    params, x, u = whole_layer
    sizes = dict(num_experts_per_tok=K, experts_held=E, expert_rank=0)
    with jax.default_matmul_precision('highest'):
        want, chosen = ref.expert_layer(params, x, u, sizes, lambda w: w,
                                        held=range(E))
    total = 0.0
    for rank in range(E // held):
        cut = {k: (v[rank * held:(rank + 1) * held]
                   if k.startswith('experts_') else v)
               for k, v in params.items()}
        out, stats = _layer(held, rank).apply({'params': cut}, x, u)
        assert int(stats['dropped']) == 0
        assert np.array_equal(stats['chosen'], chosen)
        total = total + out
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ #
# the decoder against the reference
# ------------------------------------------------------------------ #
def test_recipe_builds_one_period_of_global_and_sliding_layers():
    module = RECIPES['smallthinker_decoder']()
    assert module.hybrid_override_pattern == '*EWEWEWE'
    assert module.rope_theta is None and module.sliding_rope_theta == 1.5e6
    assert module.sliding_window_size == 5 and not module.qk_norm
    assert module.mlp_hidden_act == 'relu' and module.moe_enable_early_router
    assert module.scoring_func == 'softmax'
    assert module.num_attention_heads // module.num_key_value_heads == 3
    assert not module.tie_word_embeddings


def test_loss_and_every_gradient_leaf_match_the_plain_reference(tiny, ref):
    module, params, tokens = tiny
    loss_fn = make_lm_loss(module, chunk=8)
    with jax.default_matmul_precision('highest'):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, dict(tokens=tokens), None)
    (want, chosen), want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, SIZES, attn_block=8, chunk=8),
        has_aux=True)(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    # embedding, head, final norm; 4 x (norm, q, k, v, out); 4 x (norm,
    # router, bias, gate, up, down)
    assert len(flat) == 3 + 4 * 5 + 4 * 6 == 47
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['correction_bias']"):
            assert not np.any(g) and not np.any(w), name
            continue
        assert float(jnp.linalg.norm(w)) > 0, name
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err < 1e-5, (name, err)
    assert aux['moe_choice'].shape == (4, 2 * T, 2) == chosen.shape
    assert np.array_equal(np.sort(np.asarray(aux['moe_choice']), -1),
                          np.sort(np.asarray(chosen), -1))


def test_an_expert_step_is_routed_by_the_attention_steps_input(tiny):
    """The choices of every expert layer are the top-2 of softmax(u Wr + b)
    with u the normed input of the ATTENTION step before it, not of the
    expert step itself; without the field they are the expert step's."""
    module, params, tokens = tiny
    _, state = module.apply({'params': params}, tokens,
                            capture_intermediates=lambda m, _:
                            m.name == 'pre_norm')
    normed = state['intermediates']
    _, stats = module.apply({'params': params}, tokens)
    late = RECIPES['smallthinker_decoder'](
        bf16_operands=False, attention_block=8,
        **dict(SIZES, moe_enable_early_router=False))
    _, late_stats = late.apply({'params': params}, tokens)
    differ = 0
    for i, s in enumerate(stats):
        moe = params[f'blocks_{2 * i + 1}']['moe']

        def chosen(block):
            u = normed[block]['pre_norm']['__call__'][0].reshape(-1, 32)
            p = jax.nn.softmax(jnp.dot(u, moe['router']['kernel'],
                                       precision='highest'), axis=-1)
            return jax.lax.top_k(p + moe['correction_bias'], 2)[1]

        assert np.array_equal(s['chosen'], chosen(f'blocks_{2 * i}'))
        if i == 0:      # later layers read what earlier choices made
            assert np.array_equal(late_stats[0]['chosen'],
                                  chosen('blocks_1'))
        differ += not np.array_equal(s['chosen'], late_stats[i]['chosen'])
    assert differ == len(stats)
    assert jax.tree_util.tree_structure(
        late.init(jax.random.PRNGKey(0), tokens)) \
        == jax.tree_util.tree_structure(
        module.init(jax.random.PRNGKey(0), tokens))


def test_the_global_layer_carries_no_position_and_the_sliding_ones_do(tiny):
    """With every window at the sequence's length a permutation of the
    earlier tokens leaves the global layer's last row alone and moves a
    sliding layer's (it is rotated)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, T, 32))
    swapped = x.at[0, 2].set(x[0, 5]).at[0, 5].set(x[0, 2])
    fields = dict(dim=32, heads=6, kv_heads=2, head_dim=8, block=8)
    for theta, window, moves in ((None, 0, False), (1.5e6, T, True)):
        attn = GroupedQueryAttention(rope_theta=theta, window=window,
                                     **fields)
        params = attn.init(jax.random.PRNGKey(0), x)['params']
        a = attn.apply({'params': params}, x)[0, -1]
        b = attn.apply({'params': params}, swapped)[0, -1]
        assert (float(jnp.abs(a - b).max()) > 1e-3) == moves, theta


def test_a_token_behind_the_window_changes_nothing_to_the_bit(tiny):
    """Three sliding layers of a window of 5 reach 12 tokens back; the
    global layer reaches everything: with the global layer's output held
    fixed (a pattern of sliding layers alone), token 0 cannot move row 15."""
    _, _, tokens = tiny
    module = RECIPES['smallthinker_decoder'](
        bf16_operands=False, attention_block=8,
        **dict(SIZES, hybrid_override_pattern='WEWEWE'))
    params = module.init(jax.random.PRNGKey(0), tokens)['params']
    other = tokens.at[:, :3].set((tokens[:, :3] + 7) % 48)
    a, _ = module.apply({'params': params}, tokens)
    b, _ = module.apply({'params': params}, other)
    assert np.array_equal(a[:, 15:], b[:, 15:])
    assert not np.array_equal(a[:, 3:12], b[:, 3:12])


def test_three_steps_on_the_one_step_factory_with_the_counters_in_aux(tiny):
    module, params, tokens = tiny
    params = balance_expert_load(module, params, [dict(tokens=tokens)],
                                 steps=20)
    optimizer = optax.adam(1e-3)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    opt_state = optimizer.init(params)
    losses = []
    for i in range(3):
        params, opt_state, loss, aux = step(
            params, opt_state, dict(tokens=tokens), jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert losses[2] < losses[0] and np.all(np.isfinite(losses))
    assert int(aux['moe_dropped']) == 0
    assert 0 <= int(aux['moe_bounded']) <= 4
    assert int(aux['moe_local_pairs']) == int(np.sum(
        np.asarray(aux['moe_choice']) // 4 == 1))
