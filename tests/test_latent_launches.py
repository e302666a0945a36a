"""`LatentAttention`'s core on the repo's own two launches: the fourth rule
of `kernels/pallas_block_attention.py`, ('latent', 0), the causal triangle
under the leaf `latent_core` at heads of two lane rows (256 channels, a
group of one). The table, the launches interpreted on the CPU against the
blocked causal core, the layer on both of its paths, and which path it
takes, at small shapes (beside `tests/test_causal_launches.py`, whose rule
has the same table and another leaf)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_scopes import _eqn_paths

from se3_transformer_tpu.kernels import pallas_block_attention as kernels
from se3_transformer_tpu.observability import profiling
from se3_transformer_tpu.ops import latent_attention
from se3_transformer_tpu.ops.latent_attention import (
    LatentAttention, causal_attention_blocked,
)

LATENT = ('latent', 0)
LEAVES = ('latent_qkv', 'latent_core', 'latent_out')


def _layer(block=128, dn=192, dr=64, dv=256, heads=3):
    """The GLM cell's layer in small: its head widths, three heads."""
    return LatentAttention(dim=24, heads=heads, q_lora_rank=16,
                           kv_lora_rank=32, qk_nope_head_dim=dn,
                           qk_rope_head_dim=dr, v_head_dim=dv, block=block,
                           rope_theta=1e4)


def _interpreted(monkeypatch):
    """The layer's TPU path on the CPU: the predicate forced, the launches
    interpreted; returns the rules and tiles it was called with."""
    launched, run = [], kernels.rounded_attention
    monkeypatch.setattr(latent_attention, 'is_tpu_backend', lambda: True)
    monkeypatch.setattr(
        kernels, 'rounded_attention',
        lambda *a: launched.append(a[3:]) or run(*a, True))
    return launched


# ------------------------------------------------------------------ #
# the table
# ------------------------------------------------------------------ #
@pytest.mark.parametrize('positions,tile,tiles,diagonal', [
    (8192, 512, 136, 16), (384, 128, 6, 3), (128, 128, 1, 1)])
def test_the_latent_rules_table_is_the_causal_rules(positions, tile, tiles,
                                                    diagonal):
    """136 columns a head at 8,192 / 512 (the GLM cell), the diagonal's 16
    alone on a boundary: the very table ('mha', 0) reads, under another
    leaf."""
    table = kernels.rule_table(LATENT, positions, tile)
    assert table is kernels.rule_table(('mha', 0), positions, tile)
    assert table is kernels.window_table(positions, positions, tile)
    assert table.shape == (7, tiles)
    assert np.count_nonzero(table[kernels.KIND] != kernels.FULL) == diagonal
    assert kernels._granule(LATENT) == 1


# ------------------------------------------------------------------ #
# the launches
# ------------------------------------------------------------------ #
@pytest.mark.parametrize('t', [128, 384], ids=['one tile', 'three tiles'])
def test_the_launches_at_heads_of_256_are_the_blocked_causal_core(t):
    """`latent_core_fwd` and `latent_core_bwd` interpreted at two heads of
    256 in groups of one, token-major, with the scale and the rounding
    `rounded_attention` writes around them: o, dq, dk and dv against the
    blocked causal core in the head-major layout, at `highest`."""
    heads, d = 2, 256
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v, do = (jax.random.normal(key, (1, t, heads * d)) for key in keys)

    def by_heads(a):
        return a.reshape(1, t, heads, d).transpose(0, 2, 1, 3)

    def blocked(q, k, v):
        o = causal_attention_blocked(by_heads(q), by_heads(k), by_heads(v),
                                     d ** -0.5, 64)
        return o.transpose(0, 2, 1, 3).reshape(1, t, heads * d)

    @jax.jit
    def both(q, k, v, do):
        got, vjp = jax.vjp(lambda *a: kernels.rounded_attention(
            *a, d, d ** -0.5, LATENT, 128, True), q, k, v)
        want, want_vjp = jax.vjp(blocked, q, k, v)
        return got, vjp(do), want, want_vjp(do)

    with jax.default_matmul_precision('highest'):
        got, grads, want, want_grads = both(q, k, v, do)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(grads, want_grads):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


# ------------------------------------------------------------------ #
# the layer
# ------------------------------------------------------------------ #
def test_the_layer_is_one_function_on_both_paths(monkeypatch):
    """The module at the GLM cell's head widths on the composition (as off
    the TPU: heads laid out, the blocked core) and, the predicate forced, on
    the launches (interpreted; q, k and v written token-major by products
    over rearranged kernels): one parameter tree, the same output, and the
    same gradient of every parameter and of the input."""
    attn = _layer()
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
    launched = _interpreted(monkeypatch)
    got, got_g = grads()
    assert launched == [(256, 256 ** -0.5, LATENT, 128)]
    assert jax.tree_util.tree_structure(got_g) \
        == jax.tree_util.tree_structure(want_g)
    assert set(got_g[0]) == {'q_a', 'q_a_norm', 'q_b', 'kv_a', 'kv_a_norm',
                             'kv_b', 'out'}
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


def _rematted_layer_paths(monkeypatch, policy):
    """(equation, path) of the layer's gradient on the launches, the layer
    rematted as the decoder's blocks are."""
    _interpreted(monkeypatch)
    attn = _layer()
    x = jnp.ones((1, 256, 24))
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)['params']
    layer = jax.checkpoint(lambda p, x: attn.apply({'params': p}, x),
                           policy=policy)
    return _eqn_paths(jax.make_jaxpr(jax.grad(
        lambda p, x: layer(p, x).sum(), argnums=(0, 1)))(params, x).jaxpr)


@pytest.mark.parametrize('policy,forwards', [('SAVE_ATTN_CORE', 1),
                                             (None, 2)])
def test_a_rematted_layer_replays_no_forward_launch(monkeypatch, policy,
                                                    forwards):
    """As under the other rules: the forward's output and log-sum-exp carry
    the names `SAVE_ATTN_CORE` keeps, so a block rematted under it launches
    the forward once; the launches are named by the rule, and no other
    rule's and no launch of a pass are there."""
    paths = _rematted_layer_paths(
        monkeypatch, policy and getattr(latent_attention, policy))
    found = sorted(eqn.params['name'] for eqn, _ in paths
                   if eqn.primitive.name == 'pallas_call')
    assert found == ['latent_core_bwd'] + ['latent_core_fwd'] * forwards


def test_every_operation_of_the_layer_is_under_one_of_its_three_leaves(
        monkeypatch):
    """The readers of `latent_attn_ms_per_step.train` and
    `latent_core_roofline.train` file by the innermost leaf: both launches
    under `latent_core` (forward in the forward phase, backward in the
    backward, none in the replay) with nothing beside them but the names
    and remat's rounding of the saved o; the scale, the rounding and the
    products that write q, k and v under `latent_qkv`; every equation of the
    layer under one of the three."""
    paths = [(eqn, path) for eqn, path in _rematted_layer_paths(
        monkeypatch, latent_attention.SAVE_ATTN_CORE)
        if 'LatentAttention' in path]
    launches = {(eqn.params['name'], profiling.scope_leaf(path),
                 profiling.scope_phase(path)) for eqn, path in paths
                if eqn.primitive.name == 'pallas_call'}
    assert launches == {('latent_core_fwd', 'latent_core', 'forward'),
                        ('latent_core_bwd', 'latent_core', 'backward')}
    filed = {(profiling.scope_leaf(path), profiling.scope_phase(path))
             for _, path in paths}
    assert {leaf for leaf, _ in filed} == set(LEAVES)
    # the output product reads the saved o: nothing of it is replayed
    assert {('latent_qkv', phase) for phase in profiling.PHASES} | {
        ('latent_out', 'forward'), ('latent_out', 'backward')} <= filed
    under_core = {str(eqn.primitive) for eqn, path in paths
                  if profiling.scope_leaf(path) == 'latent_core'}
    assert under_core <= {'pallas_call', 'jit', 'name',
                          'reduce_precision'}, under_core


@pytest.mark.parametrize('case,t,block,dn,dr,dv,runs', [
    ('the GLM cell\'s shapes in small', 256, 128, 192, 64, 256, True),
    ('a sequence shorter than a tile', 128, 512, 192, 64, 256, True),
    ('heads of one lane row', 256, 128, 64, 64, 128, True),
    ('a sequence that no tile divides', 192, 128, 192, 64, 256, False),
    ('heads of a lane row and a half', 256, 128, 128, 64, 192, False),
    ('values narrower than keys', 256, 128, 192, 64, 128, False),
    ('32,768 positions at heads of 256', 32768, 512, 192, 64, 256, False),
    ('16,384 positions at heads of 256', 16384, 512, 192, 64, 256, True),
])
def test_on_a_tpu_the_layer_takes_the_launches_where_they_run(
        monkeypatch, case, t, block, dn, dr, dv, runs):
    """The choice is by platform and shape: on a TPU the layer takes the
    launches under ('latent', 0) exactly where `launches_run` says (whole
    tiles, heads of whole lane rows, keys and values of one width, a head's
    dk and dv resident in VMEM) and `causal_attention`, the library's kernel
    or the blocked core, elsewhere, as everything does off the TPU. The
    parameter tree is the same either way."""
    taken = []
    monkeypatch.setattr(
        latent_attention, 'causal_attention',
        lambda q, k, v, scale, block: taken.append(
            ('library', q.shape, k.shape, v.shape)) or v)
    monkeypatch.setattr(
        kernels, 'rounded_attention',
        lambda q, k, v, *a: taken.append(
            ('launches', q.shape, k.shape, v.shape) + a) or v)
    attn = _layer(block, dn, dr, dv)
    x = jax.ShapeDtypeStruct((1, t, 24), jnp.float32)

    def tree():
        taken.clear()
        params = jax.eval_shape(attn.init, jax.random.PRNGKey(0),
                                x)['params']
        return jax.tree_util.tree_map(lambda a: a.shape, params)

    d = dn + dr
    by_heads = [('library',) + ((1, 3, t, d),) * 2 + ((1, 3, t, dv),)]
    off = tree()
    assert taken == by_heads, case
    monkeypatch.setattr(latent_attention, 'is_tpu_backend', lambda: True)
    assert runs == (d == dv and kernels.launches_run(
        t, min(block, t), 3, 3, d)), case
    assert tree() == off and off['q_b'] == dict(kernel=(16, 3 * d)) \
        and off['kv_b'] == dict(kernel=(32, 3 * (dn + dv))), case
    assert taken == ([('launches',) + ((1, t, 3 * d),) * 3 + (
        d, d ** -0.5, LATENT, min(block, t))] if runs else by_heads), case
