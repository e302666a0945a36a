"""A global layer of `GroupedQueryAttention` (no window, no block length) on
the repo's own two launches: the third rule of
`kernels/pallas_block_attention.py`, ('mha', 0), the causal triangle under
the leaf `mha_core`. The table, the launches interpreted on the CPU against
the blocked causal core, and which path a layer takes, at small shapes
(beside `tests/test_sliding_window.py`, whose window of T is the same
table)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.kernels import pallas_block_attention as kernels
from se3_transformer_tpu.kernels.pallas_qk_pass import rotary_tables
from se3_transformer_tpu.ops import grouped_attention, latent_attention
from se3_transformer_tpu.ops import sliding_window as sw
from se3_transformer_tpu.ops.grouped_attention import GroupedQueryAttention
from se3_transformer_tpu.ops.latent_attention import (
    causal_attention_blocked,
)
from se3_transformer_tpu.ops.rotary import apply_rotary_halves, rotary_angles

CAUSAL = ('mha', 0)


# ------------------------------------------------------------------ #
# the table
# ------------------------------------------------------------------ #
@pytest.mark.parametrize('positions,tile,tiles,diagonal', [
    (16384, 512, 528, 32), (8192, 512, 136, 16), (384, 128, 6, 3),
    (128, 128, 1, 1)])
def test_the_causal_rules_table_is_the_windows_at_a_window_of_t(
        positions, tile, tiles, diagonal):
    """528 columns a head at 16,384 / 512 (the sliding-window cell's global
    layer) and 136 at 8,192 / 512 (the hybrid's); the boundary columns are
    the diagonal and nothing else, their mask `0 <= r - c`; every other
    column is a whole tile below it."""
    table = kernels.rule_table(CAUSAL, positions, tile)
    assert table is kernels.window_table(positions, positions, tile)
    assert table.shape == (7, tiles)
    assert sw.visited_tiles(positions, positions, tile) == tiles
    assert sw.boundary_tiles(positions, positions, tile) == diagonal
    edge = table[kernels.KIND] != kernels.FULL
    assert edge.sum() == diagonal
    assert np.array_equal(table[kernels.QUERY][edge], table[kernels.KEY][edge])
    assert np.all(table[kernels.QUERY][~edge] > table[kernels.KEY][~edge])
    assert set(table[kernels.LOW][edge]) == {0}
    assert table[kernels.HIGH][edge].min() >= tile - 1
    columns = set(zip(table[kernels.QUERY], table[kernels.KEY]))
    n = positions // tile
    assert columns == {(i, j) for i in range(n) for j in range(i + 1)}
    assert kernels._granule(CAUSAL) == 1


# ------------------------------------------------------------------ #
# the launches
# ------------------------------------------------------------------ #
def _composed(q, k, v, norms, angles, heads, kv, d, block, eps):
    """What the layer does off the TPU: heads laid out, normed, rotated, the
    key-value heads repeated, the blocked causal core."""
    t = q.shape[1]
    q, k, v = (a.reshape(1, t, n, d) for a, n in ((q, heads), (k, kv),
                                                   (v, kv)))
    if norms is not None:
        q, k = (a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
                * w for a, w in zip((q, k), norms))
    if angles is not None:
        q, k = (apply_rotary_halves(a, angles[None, :, None, :])
                for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    o = causal_attention_blocked(q, k, v, d ** -0.5, block)
    return o.transpose(0, 2, 1, 3).reshape(1, t, heads * d)


@pytest.mark.parametrize('group', [7, 16])
@pytest.mark.parametrize('dressed', [False, True],
                         ids=['no norms, no rotation', 'norms and rotation'])
def test_the_launches_under_the_causal_rule_are_the_blocked_causal_core(
        group, dressed):
    """`qk_pass_fwd`, `mha_core_fwd`, `mha_core_bwd` and `qk_pass_bwd`
    interpreted, in the projections' layout, at groups of 7 (the
    sliding-window cell's global layer) and of 16 (the hybrid's) over 2
    key-value heads of 128 and tiles of 128, as both cells have the layer
    (no norms, no rotation: the pass scales and rounds) and as a model with
    both would: o and the gradients of q, k, v and the norms' scales against
    the composition and the blocked core, at the window's tests'
    tolerances."""
    t, kv, d, eps = 256, 2, 128, 1e-6
    heads = group * kv
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    q, do = (jax.random.normal(key, (1, t, heads * d)) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, t, kv * d)) for key in keys[2:4])
    norms = tuple(1 + 0.1 * jax.random.normal(key, (d,))
                  for key in keys[4:]) if dressed else None
    angles = rotary_angles(jnp.arange(t), d, 1.5e6) if dressed else None

    @jax.jit
    def both(q, k, v, norms, do):
        got, vjp = jax.vjp(lambda q, k, v, norms: kernels.block_attention(
            q, k, v, norms, rotary_tables(angles) if dressed else None, d,
            d ** -0.5, eps, CAUSAL, 128, True), q, k, v, norms)
        want, want_vjp = jax.vjp(lambda q, k, v, norms: _composed(
            q, k, v, norms, angles, heads, kv, d, 64, eps), q, k, v, norms)
        return got, vjp(do), want, want_vjp(do)

    with jax.default_matmul_precision('highest'):
        got, grads, want, want_grads = both(q, k, v, norms, do)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    pairs = list(zip(jax.tree_util.tree_leaves(grads),
                     jax.tree_util.tree_leaves(want_grads)))
    assert len(pairs) == (5 if dressed else 3)
    for a, b in pairs:
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


@pytest.mark.parametrize('policy,forwards', [('SAVE_ATTN_CORE', 1),
                                             (None, 2)])
def test_a_rematted_causal_core_replays_no_forward_launch(policy, forwards):
    """As under the other two rules: the forward's output and log-sum-exp
    carry the names `SAVE_ATTN_CORE` keeps; the launches are named by the
    rule, `mha_core_*`, and no other rule's are there."""
    q = jnp.ones((1, 256, 2 * 128))
    k = v = q[:, :, :128]
    core = jax.checkpoint(
        lambda q, k, v: kernels.block_attention(
            q, k, v, None, None, 128, 0.1, 1e-6, CAUSAL, 128, True),
        policy=policy and getattr(latent_attention, policy))
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: core(*a).sum(), argnums=(0, 1, 2)))(q, k, v))
    found = re.findall(r'name=((?:mha|swa|bd)_core_\w+)', jaxpr)
    assert sorted(found) == ['mha_core_bwd'] + ['mha_core_fwd'] * forwards, \
        found


# ------------------------------------------------------------------ #
# the layer
# ------------------------------------------------------------------ #
@pytest.mark.parametrize('case,t,block,head_dim,kv,runs', [
    ('the hybrid cell\'s shapes in small', 256, 128, 128, 2, True),
    ('a sequence shorter than a tile', 128, 512, 128, 2, True),
    ('a sequence that no tile divides', 192, 128, 128, 2, False),
    ('heads of 64', 256, 128, 64, 2, False),
])
@pytest.mark.parametrize('dressed', [False, True],
                         ids=['no norms, no rotation', 'norms and rotation'])
def test_on_a_tpu_a_global_layer_takes_the_launches_where_they_run(
        monkeypatch, case, t, block, head_dim, kv, runs, dressed):
    """The choice is by platform and shape, the sliding layers' predicate:
    on a TPU a layer with neither window nor block length takes the one
    pass and the core under ('mha', 0) at the shapes `launches_run` admits,
    with its norms and rotation handed to the pass as it has them (each
    there or None); heads of 64 (the short-convolution cell's) and a length
    no tile divides keep the composition and `causal_attention`, as
    everything does off the TPU. The parameter tree is the same either
    way."""
    taken = []
    monkeypatch.setattr(
        grouped_attention, 'causal_attention',
        lambda q, k, v, scale, block: taken.append(
            ('composed', q.shape, k.shape, v.shape)) or q)
    monkeypatch.setattr(
        kernels, 'block_attention',
        lambda q, k, v, norms, rotary, *a: taken.append(
            ('kernels', q.shape, k.shape, norms and len(norms),
             rotary and len(rotary)) + a) or q)
    attn = GroupedQueryAttention(
        dim=32, heads=6, kv_heads=kv, head_dim=head_dim, block=block,
        eps=1e-6, qk_norm=dressed, rope_theta=1e4 if dressed else None)
    x = jax.ShapeDtypeStruct((1, t, 32), jnp.float32)

    def tree():
        taken.clear()
        params = jax.eval_shape(attn.init, jax.random.PRNGKey(0),
                                x)['params']
        return jax.tree_util.tree_map(lambda a: a.shape, params)

    repeated = (1, 6, t, head_dim)
    off = tree()
    assert taken == [('composed', repeated, repeated, repeated)], case
    monkeypatch.setattr(sw, 'is_tpu_backend', lambda: True)
    scales = dict(q_norm=dict(scale=(head_dim,)),
                  k_norm=dict(scale=(head_dim,))) if dressed else {}
    assert tree() == off == dict(
        q=dict(kernel=(32, 6 * head_dim)), k=dict(kernel=(32, kv * head_dim)),
        v=dict(kernel=(32, kv * head_dim)),
        out=dict(kernel=(6 * head_dim, 32)), **scales)
    two = 2 if dressed else None
    assert taken == ([(
        'kernels', (1, t, 6 * head_dim), (1, t, kv * head_dim), two, two,
        head_dim, head_dim ** -0.5, 1e-6, CAUSAL, min(block, t))]
        if runs else [('composed', repeated, repeated, repeated)]), case


@pytest.mark.parametrize('dressed', [False, True],
                         ids=['no norms, no rotation', 'norms and rotation'])
def test_a_global_layer_is_one_function_on_both_paths(monkeypatch, dressed):
    """The module at 6 query heads over 2 key-value heads of 128 on the
    composition (as off the TPU) and, the predicate forced, on the launches
    (interpreted): one parameter tree, the same output, and the same
    gradient of every parameter and of the input."""
    attn = GroupedQueryAttention(
        dim=24, heads=6, kv_heads=2, head_dim=128, block=128, eps=1e-6,
        qk_norm=dressed, rope_theta=1e4 if dressed else None)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 24))
    params = attn.init(jax.random.PRNGKey(1), x)['params']
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape)
        if a.ndim == 1 else a, params)

    def grads():
        fn = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            attn.apply({'params': p}, x))), argnums=(0, 1)))
        with jax.default_matmul_precision('highest'):
            return fn(params, x)

    want, want_g = grads()
    interpreted = kernels.block_attention
    launched = []
    monkeypatch.setattr(sw, 'is_tpu_backend', lambda: True)
    monkeypatch.setattr(
        kernels, 'block_attention',
        lambda *a: launched.append(a[8:]) or interpreted(*a, True))
    got, got_g = grads()
    assert launched == [(CAUSAL, 128)]
    assert jax.tree_util.tree_structure(got_g) \
        == jax.tree_util.tree_structure(want_g)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5
