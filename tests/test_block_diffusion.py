"""The decoder trained by diffusion over blocks (two streams of one sequence
under a block mask, softmax-routed experts, a masked-token loss in place;
`ops/block_diffusion.py`, `models/hybrid_decoder.py`, `training/lm_loss.py`)
against the plain reference the benchmark keeps
(`benchmark/harness/sdar_reference.py`, loaded under a private package name:
it imports nothing of the program), at tiny widths in float32 on the CPU, and
the properties the mask states one by one. The last cases hold the three
decoders that were there to what they computed before this path existed."""
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
from se3_transformer_tpu.ops import block_diffusion as bd
from se3_transformer_tpu.ops.expert_layer import (
    SCORING_FUNCS, ExpertLayer, route,
)
from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
from se3_transformer_tpu.training.lm_loss import (
    balance_expert_load, make_block_diffusion_loss, make_lm_loss,
    noise_tokens,
)
from se3_transformer_tpu.training.recipes import RECIPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(vocab_rows=48, hidden_size=32, hybrid_override_pattern='*E*E',
             moe_intermediate_size=16, n_routed_experts=8,
             num_experts_per_tok=2, experts_held=4, expert_rank=1,
             mlp_hidden_act='silu', scoring_func='softmax',
             routed_scaling_factor=1.0, norm_topk_prob=True,
             norm_topk_eps=1e-20, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, qk_norm=True,
             rope_theta=1e6, layer_norm_epsilon=1e-6)
L, BK, MASK = 16, 4, 47


@pytest.fixture(scope='module')
def ref():
    """`sdar_reference.py` imports `lm_reference.py` from its own directory:
    both are loaded as a package of a name of their own, beside whatever
    `harness` another test has on its path."""
    d = os.path.join(ROOT, 'benchmark', 'harness')
    spec = importlib.util.spec_from_file_location(
        'plain_sdar_references', os.path.join(d, '__init__.py'),
        submodule_search_locations=[d])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules['plain_sdar_references'] = pkg
    spec.loader.exec_module(pkg)
    try:
        yield importlib.import_module('plain_sdar_references.sdar_reference')
    finally:
        for name in [n for n in sys.modules
                     if n.split('.')[0] == 'plain_sdar_references']:
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
                   else 0.005 * z if name == 'correction_bias' else a)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope='module')
def tiny():
    module = RECIPES['sdar_decoder'](bf16_operands=False, attention_block=8,
                                     **SIZES)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, L), 0, MASK)
    params = _perturbed(module.init(jax.random.PRNGKey(0), tokens)['params'])
    return module, params, noise_tokens(jax.random.PRNGKey(2), tokens, MASK)


# ------------------------------------------------------------------ #
# the rule
# ------------------------------------------------------------------ #
def _by_hand(q):
    """The keys query `q` of 2 x 16 positions sees at blocks of 4, written
    out: q's stream and block, then the three lines that let it see."""
    noised, block = q < 16, (q % 16) // 4
    if noised:      # its own block of the noised stream, the clean prefix
        return set(range(4 * block, 4 * block + 4)) \
            | set(range(16, 16 + 4 * block))
    return set(range(16, 16 + 4 * block + 4))   # clean: through its block


def test_the_visibility_rule_is_the_four_lines_enumerated_by_hand():
    ids = np.arange(2 * L)
    seen = bd.visible(ids[:, None], ids[None, :], L, BK)
    assert seen.dtype == np.bool_ and isinstance(seen, np.ndarray)
    for q in ids:
        assert set(np.flatnonzero(seen[q])) == _by_hand(q), q
    # spot checks of the four lines
    assert _by_hand(5) == {4, 5, 6, 7, 16, 17, 18, 19}
    assert _by_hand(0) == {0, 1, 2, 3}               # no clean prefix yet
    assert _by_hand(16 + 5) == set(range(16, 24))
    assert not seen[L:, :L].any()                    # clean -> noised: never
    assert seen.sum() == bd.visible_pairs(L, BK) == L * L + L * BK == 320
    # half of what a causal core over 2 L positions computes, no subset of it
    causal = ids[:, None] >= ids[None, :]
    assert (seen & ~causal).any() and causal.sum() == 528


@pytest.mark.parametrize('length,block_length', [(24, 3), (8192, 4)])
def test_the_pair_count_at_other_sizes(length, block_length):
    """A block length that is no power of two takes the rule's division; at
    the cell's size the count is the issue's, by rows."""
    if length > 100:
        assert bd.visible_pairs(length, block_length) == 67_141_632
        q = np.arange(0, 2 * length, 997)
    else:
        q = np.arange(2 * length)
    k = np.arange(2 * length)
    seen = bd.visible(q[:, None], k[None, :], length, block_length)
    noised = q < length
    block = (q % length) // block_length
    want = np.where(noised, block_length + block * block_length,
                    (block + 1) * block_length)
    assert np.array_equal(seen.sum(axis=1), want)


def test_the_rule_inside_a_jit_is_the_rule_on_numpy():
    ids = np.arange(2 * L)
    traced = jax.jit(lambda q, k: bd.visible(q, k, L, BK))(
        ids[:, None], ids[None, :])
    assert np.array_equal(np.asarray(traced),
                          bd.visible(ids[:, None], ids[None, :], L, BK))


# ------------------------------------------------------------------ #
# the blocked core
# ------------------------------------------------------------------ #
def _dense(q, k, v, scale, length, block_length):
    """Every score, masked, one softmax a row."""
    ids = np.arange(2 * length)
    seen = bd.visible(ids[:, None], ids[None, :], length, block_length)
    groups = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, groups, axis=1) for a in (k, v))
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', p, v)


@pytest.mark.parametrize('block', [4, 8])
def test_the_blocked_core_is_the_dense_masked_softmax(block):
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (2, 4, 2 * L, 8))
    k, v = (jax.random.normal(key, (2, 2, 2 * L, 8)) for key in keys[1:3])
    w = jax.random.normal(keys[3], q.shape)
    with jax.default_matmul_precision('highest'):
        got = bd.block_diffusion_attention_blocked(q, k, v, 8 ** -0.5, BK,
                                                   block)
        want = _dense(q, k, v, 8 ** -0.5, L, BK)
        g_got = jax.grad(lambda *a: jnp.sum(
            w * bd.block_diffusion_attention_blocked(
                *a, 8 ** -0.5, BK, block)), argnums=(0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda *a: jnp.sum(
            w * _dense(*a, 8 ** -0.5, L, BK)), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _attention(head_dim=128, **fields):
    from se3_transformer_tpu.ops.grouped_attention import (
        GroupedQueryAttention,
    )
    return GroupedQueryAttention(dim=32, heads=4, kv_heads=2,
                                 head_dim=head_dim, **fields)


def test_off_the_tpu_the_core_is_the_blocked_one(monkeypatch):
    from se3_transformer_tpu.ops import grouped_attention
    assert not bd.kernels_run(256, BK, 128, 4, 2, 128)
    calls = []
    monkeypatch.setattr(grouped_attention, 'block_diffusion_attention_blocked',
                        lambda *a: calls.append(a[4:]) or a[0])
    attn = _attention(block=128, qk_norm=True, rope_theta=1e6)
    x = jnp.ones((1, 512, 32))
    params = attn.init(jax.random.PRNGKey(0), x)
    attn.apply(params, x, jnp.arange(512) % 256, BK)
    assert calls == [(BK, 128)]     # init is of the causal path


def _tokens(a):
    """[B, n, T, D] -> [B, T, n D]."""
    b, n, t, d = a.shape
    return a.transpose(0, 2, 1, 3).reshape(b, t, n * d)


def _composition(q, k, v, norms, angles, heads, kv_heads, eps, scale):
    """What the layer does between its projections and its core where the
    kernels do not run: `RMSNorm`'s arithmetic, `apply_rotary_halves`, the
    heads laid out, then the scale as the core applies it; float32."""
    from se3_transformer_tpu.ops.rotary import apply_rotary_halves
    b, t, _ = q.shape
    q, k, v = (a.reshape(b, t, n, -1) for a, n in
               ((q, heads), (k, kv_heads), (v, kv_heads)))
    if norms is not None:
        q, k = (a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                                  + eps) * w for a, w in zip((q, k), norms))
    if angles is not None:
        q, k = (apply_rotary_halves(a, angles[None, :, None, :])
                for a in (q, k))
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    return q * scale, k, v


def _projected(length, heads, kv, dh, seed=5):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (1, 2 * length, heads * dh))
    k, v = (jax.random.normal(key, (1, 2 * length, kv * dh))
            for key in keys[1:3])
    norms = tuple(1 + 0.1 * jax.random.normal(key, (dh,))
                  for key in keys[3:5])
    w = jax.random.normal(keys[5], q.shape)
    return q, k, v, norms, w


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize('norm', [True, False])
@pytest.mark.parametrize('rotate', [True, False])
def test_the_streaming_kernel_interpreted_is_the_blocked_core(norm, rotate):
    """The rule that ships (`kernels.block_attention`: `qk_pass_fwd`, the
    core's two launches and `qk_pass_bwd` under one differentiation rule,
    every operand in the projections' own layout [B, 2L, H D]) in interpret
    mode at tiles of 128 (two streams of 256 tokens, 4 query and 2 key-value
    heads of 128: a noised tile, the clean prefix's tiles, the clean
    stream's lower triangle), with and without norms and rotation, the two
    copies of a token at one position: output and every gradient (the
    projections' outputs and both norm scales) against today's composition
    and the blocked core at `highest`. Interpreted, the products' operands
    stay float32, so the two agree far inside the bfloat16 tolerances."""
    from se3_transformer_tpu.kernels.pallas_qk_pass import rotary_tables
    from se3_transformer_tpu.ops.rotary import rotary_angles
    length, heads, kv, dh = 256, 4, 2, 128
    q, k, v, norms, w = _projected(length, heads, kv, dh)
    angles = rotary_angles(jnp.tile(jnp.arange(length), 2), dh, 1e6)
    assert kernels.can_run(length, BK, 128, heads, kv, dh)
    # 2 q tiles a stream: noised tile i meets itself and clean tiles 0..i,
    # clean tile i clean tiles 0..i: (2 + 3) + (1 + 2) of 16
    assert bd.visited_tiles(length, BK, 128) == 8
    assert bd.boundary_tiles(length, BK, 128) == 6

    def streamed(q, k, v, norms):
        return kernels.block_attention(
            q, k, v, norms if norm else None,
            rotary_tables(angles) if rotate else None, dh, dh ** -0.5, 1e-6,
            ('bd', BK), 128, True)

    def blocked(q, k, v, norms):
        q, k, v = _composition(q, k, v, norms if norm else None,
                               angles if rotate else None, heads, kv, 1e-6,
                               dh ** -0.5)
        return _tokens(bd.block_diffusion_attention_blocked(q, k, v, 1.0, BK,
                                                            128))

    got, g_got = jax.value_and_grad(
        lambda *a: jnp.sum(w * streamed(*a)), argnums=(0, 1, 2, 3))(
        q, k, v, norms)
    with jax.default_matmul_precision('highest'):
        want, g_want = jax.value_and_grad(
            lambda *a: jnp.sum(w * blocked(*a)), argnums=(0, 1, 2, 3))(
            q, k, v, norms)
    np.testing.assert_allclose(got, want, rtol=2e-2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        if not norm and a.shape == (dh,):     # no norm: no scale is read
            assert not a.any() and not b.any()
            continue
        assert _rel(a, b) < 1e-5, _rel(a, b)


# (length, block length, tile): the cell's size; the interpreted test's; a
# block length that is no power of two
TABLES = [(8192, 4, 512), (256, 4, 128), (1536, 3, 384)]


def _tile_ids(tile_index, tile, stride=1):
    return np.arange(tile_index * tile, (tile_index + 1) * tile, stride)


@pytest.mark.parametrize('length,block_length,tile', TABLES)
def test_the_tile_table_is_the_tiles_with_a_visible_pair(length,
                                                         block_length, tile):
    """The table both launches take their grid from: a head's visited (query
    tile, key tile) pairs in the order of their query tiles, a kind each.
    288 visited and 48 on a boundary at the cell's size, 16 of each kind;
    the tiles in it are those in which `visible` shows a pair, no other."""
    table = kernels.tile_table(length, block_length, tile)
    n = length // tile
    query, key, kind = (table[row] for row in
                        (kernels.QUERY, kernels.KEY, kernels.KIND))
    assert table.shape == (7, n * (n + 2)) and table.dtype == np.int32
    assert bd.visited_tiles(length, block_length, tile) == table.shape[1]
    assert bd.boundary_tiles(length, block_length, tile) == 3 * n
    for boundary in (kernels.NOISED_NOISED, kernels.NOISED_CLEAN,
                     kernels.CLEAN_CLEAN):
        assert np.count_nonzero(kind == boundary) == n
    if length == 8192:
        assert (table.shape[1], 3 * n, n) == (288, 48, 16)
    # a query tile's columns are neighbours, opened and closed once
    assert np.all(np.diff(query) >= 0)
    assert np.array_equal(table[kernels.FIRST],
                          np.diff(query, prepend=-1) != 0)
    assert np.array_equal(table[kernels.LAST],
                          np.diff(query, append=-1) != 0)
    assert len(set(zip(query, key))) == table.shape[1]
    # the rule, a row and a column a block
    ids = np.arange(0, 2 * length, block_length)
    seen = bd.visible(ids[:, None], ids[None, :], length, block_length)
    per = tile // block_length
    any_pair = seen.reshape(2 * n, per, 2 * n, per).any(axis=(1, 3))
    in_table = np.zeros_like(any_pair)
    in_table[query, key] = True
    assert np.array_equal(any_pair, in_table)
    every_pair = seen.reshape(2 * n, per, 2 * n, per).all(axis=(1, 3))
    full = kind == kernels.FULL
    assert every_pair[query[full], key[full]].all()
    assert not every_pair[query[~full], key[~full]].any()


@pytest.mark.parametrize('kind', ['NOISED_NOISED', 'NOISED_CLEAN',
                                  'CLEAN_CLEAN'])
@pytest.mark.parametrize('length,block_length,tile', TABLES[:1] + TABLES[2:])
def test_a_boundary_kinds_mask_is_the_rule_on_its_tiles_ids(
        length, block_length, tile, kind):
    """What the kernels evaluate on a boundary tile, from the offsets inside
    the tile and the table's two bounds, is `visible` on every such tile's
    ids to the bit, either way up."""
    table = kernels.tile_table(length, block_length, tile)
    columns = np.flatnonzero(table[kernels.KIND] == getattr(kernels, kind))
    assert len(columns) == length // tile
    for n in columns:
        low, high = (int(table[row, n])
                     for row in (kernels.LOW, kernels.HIGH))
        assert (low, high) == kernels.BOUNDS[getattr(kernels, kind)]
        want = bd.visible(
            _tile_ids(table[kernels.QUERY, n], tile)[:, None],
            _tile_ids(table[kernels.KEY, n], tile)[None, :],
            length, block_length)
        got = np.asarray(kernels.boundary_mask(low, high, tile,
                                               block_length))
        assert got.dtype == np.bool_ and np.array_equal(got, want), n
        assert not want.all() and want.any()
    assert np.array_equal(np.asarray(kernels.boundary_mask(
        low, high, tile, block_length, keys_in_lanes=False)), want.T)


@pytest.mark.parametrize('length,block_length,tile', TABLES[:2])
def test_a_full_tiles_ids_are_all_visible(length, block_length, tile):
    table = kernels.tile_table(length, block_length, tile)
    columns = np.flatnonzero(table[kernels.KIND] == kernels.FULL)
    assert len(columns) == table.shape[1] - 3 * (length // tile)
    for n in columns[::7]:
        assert bd.visible(
            _tile_ids(table[kernels.QUERY, n], tile)[:, None],
            _tile_ids(table[kernels.KEY, n], tile)[None, :],
            length, block_length).all(), n
        assert tuple(table[[kernels.LOW, kernels.HIGH], n]) \
            == kernels.BOUNDS[kernels.FULL]


@pytest.mark.parametrize('case,length,block_length,block,head_dim,runs', [
    ('the cell, shrunk', 256, 4, 128, 128, True),
    ('a length the tile does not divide', 320, 4, 128, 128, False),
    ('a block length that does not divide the tile', 384, 3, 128, 128,
     False),
    ('heads of 64', 256, 4, 128, 64, False),
    ("more keys than the backward's resident dk and dv have room for",
     32768, 4, 512, 128, False),
])
def test_on_a_tpu_the_kernels_run_where_can_run_holds(
        monkeypatch, case, length, block_length, block, head_dim, runs):
    """One path a platform: on a TPU the layer takes the kernels (the one
    pass and the core, in the projections' layout) at the shapes `can_run`
    admits, the composition and the blocked core at any other, chosen from
    the shapes alone; the parameter tree is the same either way."""
    from se3_transformer_tpu.ops import grouped_attention
    assert kernels.can_run(length, block_length, min(block, length), 4, 2,
                           head_dim) == runs, case
    taken = []
    monkeypatch.setattr(
        grouped_attention, 'block_diffusion_attention_blocked',
        lambda q, *a: taken.append('blocked') or q)
    monkeypatch.setattr(
        kernels, 'block_attention',
        lambda q, k, v, norms, rotary, *a: taken.append(
            ('kernels', q.shape, k.shape, len(norms), len(rotary)) + a)
        or q)
    attn = _attention(head_dim, block=block, qk_norm=True, rope_theta=1e6,
                      eps=1e-6)
    x = jax.ShapeDtypeStruct((1, 2 * length, 32), jnp.float32)
    positions = jax.ShapeDtypeStruct((2 * length,), jnp.int32)

    def tree():
        taken.clear()
        params = jax.eval_shape(
            lambda x, p: attn.init(jax.random.PRNGKey(0), x, p, block_length),
            x, positions)['params']
        return jax.tree_util.tree_map(lambda a: a.shape, params)

    off = tree()
    assert taken == ['blocked'], case
    monkeypatch.setattr(bd, 'is_tpu_backend', lambda: True)
    assert tree() == off == dict(
        q=dict(kernel=(32, 4 * head_dim)), k=dict(kernel=(32, 2 * head_dim)),
        v=dict(kernel=(32, 2 * head_dim)), out=dict(kernel=(4 * head_dim, 32)),
        q_norm=dict(scale=(head_dim,)), k_norm=dict(scale=(head_dim,)))
    assert taken == ([(
        'kernels', (1, 2 * length, 4 * head_dim),
        (1, 2 * length, 2 * head_dim), 2, 2, head_dim, head_dim ** -0.5,
        1e-6, ('bd', block_length), block)] if runs else ['blocked']), case


def _core(q, k, v):
    """The rule that ships with no norm and no rotation, interpreted, in and
    out in the projections' layout."""
    return kernels.block_attention(q, k, v, None, None, 128, 0.1, 1e-6,
                                   ('bd', BK), 128, True)


@pytest.mark.parametrize('policy,forwards', [('SAVE_ATTN_CORE', 1),
                                             (None, 2)])
def test_a_rematted_core_replays_no_forward_launch(policy, forwards):
    """The forward's output and log-sum-exp carry the names
    `SAVE_ATTN_CORE` keeps: the gradient of a block rematted under it holds
    one forward launch of the core; rematted whole (the control) it holds
    two. The pass before the core is replayed either way (its outputs are
    what the backward launch reads), and runs backward once."""
    from se3_transformer_tpu.ops import latent_attention
    q = jnp.ones((1, 256, 2 * 128))
    k = v = q[:, :, :128]
    core = jax.checkpoint(
        _core, policy=policy and getattr(latent_attention, policy))
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: core(*a).sum(), argnums=(0, 1, 2)))(q, k, v))
    found = re.findall(r'name=(bd_core_\w+)', jaxpr)
    assert sorted(found) == ['bd_core_bwd'] + ['bd_core_fwd'] * forwards, \
        found
    found = re.findall(r'name=(qk_pass_\w+)', jaxpr)
    assert sorted(found) == ['qk_pass_bwd'] + ['qk_pass_fwd'] * 2, found


# ------------------------------------------------------------------ #
# the one pass between the projections and the core
# (kernels/pallas_qk_pass.py)
# ------------------------------------------------------------------ #
PASSES = [(dh, norm, rotate) for dh in (128, 64) for norm in (True, False)
          for rotate in (True, False)]


def _pass_case(dh, norm, rotate, length=128, heads=4, kv=2):
    from se3_transformer_tpu.kernels.pallas_qk_pass import rotary_tables
    from se3_transformer_tpu.ops.rotary import rotary_angles
    q, k, v, norms, _ = _projected(length, heads, kv, dh, seed=11)
    # the two copies of a token share a position
    angles = rotary_angles(jnp.tile(jnp.arange(length), 2), dh, 1e6)
    return (q, k, v), norms if norm else None, \
        (angles, rotary_tables(angles)) if rotate else (None, None)


@pytest.mark.parametrize('dh,norm,rotate', PASSES)
def test_the_pass_is_todays_composition(dh, norm, rotate):
    """`qk_pass_fwd` interpreted against `RMSNorm` -> `apply_rotary_halves`
    -> scale -> round, over heads of 128 (the width the layer takes it at)
    and of 64 (the arithmetic at any width), norm and rotation on and off,
    two streams at shared positions: within 1e-6 in float32, within one
    bfloat16 ulp after the rounding."""
    from se3_transformer_tpu.kernels import pallas_qk_pass as qk_pass
    qkv, norms, (angles, tables) = _pass_case(dh, norm, rotate)
    want = [_tokens(a) for a in _composition(
        *qkv, norms, angles, 4, 2, 1e-6, dh ** -0.5)]
    got = qk_pass.forward(*qkv, norms, tables, dh, dh ** -0.5, 1e-6,
                          jnp.float32, True)
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    rounded = qk_pass.forward(*qkv, norms, tables, dh, dh ** -0.5, 1e-6,
                              jnp.bfloat16, True)
    for a, b in zip(rounded, want):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   b.astype(jnp.bfloat16).astype(jnp.float32),
                                   rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize('dh,norm,rotate', PASSES)
def test_the_pass_backward_is_the_compositions_gradient(dh, norm, rotate):
    """`qk_pass_bwd` interpreted against autodiff of the composition at
    float32 cotangents: the projections' three and both norm scales within
    1e-6 relative; rounded, within a bfloat16 ulp of them."""
    from se3_transformer_tpu.kernels import pallas_qk_pass as qk_pass
    qkv, norms, (angles, tables) = _pass_case(dh, norm, rotate)
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    cts = [jax.random.normal(key, a.shape) for key, a in zip(keys, qkv)]

    def composed(q, k, v, norms):
        return [_tokens(a) for a in _composition(
            q, k, v, norms, angles, 4, 2, 1e-6, dh ** -0.5)]

    want = jax.vjp(composed, *qkv, norms)[1](cts)
    got = qk_pass.backward(*cts, *qkv[:2], norms, tables, dh, dh ** -0.5,
                           1e-6, jnp.float32, True)
    for a, b in zip(got[:3], want[:3]):
        assert _rel(a, b) < 1e-6, _rel(a, b)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    if norm:
        for a, b in zip(got[3], want[3]):
            assert a.shape == (dh,) and _rel(a, b) < 1e-6, _rel(a, b)
    else:
        assert got[3] is None
    rounded = qk_pass.backward(*cts, *qkv[:2], norms, tables, dh,
                               dh ** -0.5, 1e-6, jnp.bfloat16, True)
    for a, b in zip(rounded[:3], want[:3]):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(jnp.float32), b, rtol=2 ** -7,
                                   atol=1e-5)


# ------------------------------------------------------------------ #
# the decoder against the reference
# ------------------------------------------------------------------ #
def test_recipe_builds_a_decoder_of_attention_and_softmax_routed_experts():
    module = RECIPES['sdar_decoder']()
    assert module.hybrid_override_pattern == '*E*E'
    assert module.scoring_func == 'softmax' and module.qk_norm
    assert not module.tie_word_embeddings
    assert module.moe_shared_expert_intermediate_size == 0


def test_loss_and_every_gradient_leaf_match_the_plain_reference(tiny, ref):
    module, params, batch = tiny
    loss_fn = make_block_diffusion_loss(module, BK, chunk=8)
    with jax.default_matmul_precision('highest'):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, batch, None)
    (want, chosen), want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, batch['tokens'], batch['noised'],
                           batch['weight'], SIZES, BK, attn_block=8,
                           chunk=8), has_aux=True)(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == 29
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['correction_bias']"):
            assert not np.any(g) and not np.any(w), name
            continue
        assert float(jnp.linalg.norm(w)) > 0, name
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err < 1e-5, (name, err)
    # both streams' choices, layer by layer, as sets
    assert aux['moe_choice'].shape == (2, 2 * 2 * L, 2) == chosen.shape
    assert np.array_equal(np.sort(np.asarray(aux['moe_choice']), -1),
                          np.sort(np.asarray(chosen), -1))


def test_the_loss_is_the_weighted_sum_over_masked_positions(tiny):
    """In place, no shift: (1 / (B L)) sum m_i / t nll_i over the noised
    stream's logits."""
    module, params, batch = tiny
    loss, aux = make_block_diffusion_loss(module, BK, chunk=8)(
        params, batch, None)
    main, _, _ = module.apply({'params': params}, batch['tokens'],
                              batch['noised'], BK, method='hidden_states')
    assert main.shape == (2, L, 32)              # the noised stream alone
    logits = main @ params['head']['kernel']
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, batch['tokens'][..., None], -1)[..., 0]
    np.testing.assert_allclose(
        loss, jnp.sum(batch['weight'] * nll) / (2 * L), rtol=1e-5)
    assert int(aux['bd_masked']) == int((batch['weight'] > 0).sum())
    np.testing.assert_allclose(aux['bd_weight'], batch['weight'].sum(),
                               rtol=1e-6)
    # unmasked positions carry no weight: their targets do not matter
    moved = dict(batch, tokens=jnp.where(batch['weight'] > 0,
                                         batch['tokens'], 0))
    assert not np.array_equal(moved['tokens'], batch['tokens'])


def _streams(module, params, tokens, noised=None, block_length=0):
    """The last block's output over every position [B, T, d]: both streams
    of a block-diffusion pass, or the causal path's one."""
    last = f'blocks_{len(module.hybrid_override_pattern) - 1}'
    args = (tokens,) if noised is None else (tokens, noised, block_length)
    _, state = module.apply(
        {'params': params}, *args, method='hidden_states',
        capture_intermediates=lambda mdl, _: mdl.name == last,
        mutable=['intermediates'])
    return state['intermediates'][last]['__call__'][0][0]


def test_the_clean_stream_never_sees_the_noised_one_to_the_bit(tiny):
    module, params, batch = tiny
    h = _streams(module, params, batch['tokens'], batch['noised'], BK)
    assert h.shape == (2, 2 * L, 32)
    other = batch['noised'].at[:, 5].set(3)      # a token of noised block 1
    assert not np.array_equal(other, batch['noised'])
    h2 = _streams(module, params, batch['tokens'], other, BK)
    assert np.array_equal(h[:, L:], h2[:, L:])           # clean: unmoved
    assert not np.array_equal(h[:, 4:8], h2[:, 4:8])     # its own block
    # another noised block does not move, before it or after it
    assert np.array_equal(h[:, :4], h2[:, :4])
    assert np.array_equal(h[:, 8:L], h2[:, 8:L])


def test_a_noised_block_never_sees_a_later_block_to_the_bit(tiny):
    module, params, batch = tiny
    h = _streams(module, params, batch['tokens'], batch['noised'], BK)
    later = batch['tokens'].at[:, 9].set(3)      # a clean token of block 2
    assert not np.array_equal(later, batch['tokens'])
    h2 = _streams(module, params, later, batch['noised'], BK)
    assert np.array_equal(h[:, :12], h2[:, :12])         # noised blocks 0-2
    assert not np.array_equal(h[:, 12:L], h2[:, 12:L])   # block 3 reads it
    assert np.array_equal(h[:, L:L + 8], h2[:, L:L + 8])     # clean before
    assert not np.array_equal(h[:, L + 8:], h2[:, L + 8:])


def test_at_blocks_of_one_the_clean_stream_is_the_causal_path(tiny):
    module, params, batch = tiny
    causal = _streams(module, params, batch['tokens'])
    assert causal.shape == (2, L, 32)
    clean = _streams(module, params, batch['tokens'], batch['noised'],
                     1)[:, L:]
    np.testing.assert_allclose(clean, causal, rtol=1e-5, atol=1e-5)


def test_the_two_copies_of_a_token_share_a_rotary_position(tiny):
    """With nothing masked and blocks of one, a noised position sees itself
    and the clean prefix before it: what the clean position sees, its own
    key twice removed. Both streams then hold the same hidden states only if
    the positions agree too."""
    module, params, batch = tiny
    h = _streams(module, params, batch['tokens'], batch['tokens'], 1)
    np.testing.assert_allclose(h[:, :L], h[:, L:], rtol=1e-5, atol=1e-5)


def test_a_pattern_with_a_scan_or_a_convolution_has_no_such_pass():
    module = RECIPES['lfm2_decoder']()
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens)['params']
    with pytest.raises(AssertionError):
        module.apply({'params': params}, tokens, tokens, 4,
                     method='hidden_states')


# ------------------------------------------------------------------ #
# the router
# ------------------------------------------------------------------ #
E, K, D, WIDTH = 16, 3, 12, 10
LAYER = dict(n_routed_experts=E, num_experts_per_tok=K, experts_held=E,
             expert_rank=0, routed_scaling_factor=1.0, norm_topk_prob=True)


def _layer(held, rank, **kw):
    kw.setdefault('scoring_func', 'softmax')
    return ExpertLayer(width=WIDTH, n_experts=E, top_k=K, experts_held=held,
                       expert_rank=rank, shared_width=0, hidden_act='silu',
                       routed_scale=1.0, bf16_operands=False, **kw)


@pytest.fixture(scope='module')
def whole_layer():
    """The uncut layer's parameters (all 16 experts held) and some tokens."""
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D))
    params = _layer(E, 0).init(jax.random.PRNGKey(2), x)['params']
    return params, x


def test_softmax_routing_is_the_references(whole_layer, ref):
    params, x = whole_layer
    params = dict(params, correction_bias=0.01 * jax.random.normal(
        jax.random.PRNGKey(3), (E,)))
    out, stats = _layer(E, 0).apply({'params': params}, x)
    with jax.default_matmul_precision('highest'):
        want, chosen = ref.expert_layer(params, x, LAYER, lambda w: w)
        want_chosen, want_w = ref.route(params, x, LAYER, lambda w: w)
    np.testing.assert_allclose(stats['scores'].sum(-1), 1.0, rtol=1e-6)
    assert np.array_equal(stats['chosen'], want_chosen)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(want_w.sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize('held', [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(whole_layer, ref, held):
    """The parts of all 16 / held ranks (eight shares of two, as the cell's
    eight of sixteen), with no shared expert to count once, are what the
    uncut reference gives for the whole layer."""
    params, x = whole_layer
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


def test_the_bias_at_zero_is_the_published_arithmetic(whole_layer):
    """softmax over all the outputs, the k largest, their probabilities
    over their sum: no bias anywhere."""
    params, x = whole_layer
    assert not np.any(params['correction_bias'])
    _, stats = _layer(E, 0).apply({'params': params}, x)
    p = jax.nn.softmax(jnp.dot(x, params['router']['kernel'],
                               precision='highest'), axis=-1)
    np.testing.assert_allclose(stats['scores'], p, rtol=1e-6)
    top, chosen = jax.lax.top_k(p, K)
    assert np.array_equal(stats['chosen'], chosen)
    _, w = route(p, params['correction_bias'], K, 1.0, True)
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_the_scoring_function_is_a_field_and_sigmoid_the_default(
        whole_layer):
    params, x = whole_layer
    assert set(SCORING_FUNCS) == {'sigmoid', 'softmax'}
    assert ExpertLayer(width=4, n_experts=8, top_k=2,
                       experts_held=4).scoring_func == 'sigmoid'
    _, stats = _layer(E, 0, scoring_func='sigmoid').apply(
        {'params': params}, x)
    logits = jnp.dot(x, params['router']['kernel'], precision='highest')
    np.testing.assert_allclose(stats['scores'], jax.nn.sigmoid(logits),
                               rtol=1e-6)
    with pytest.raises(KeyError):
        _layer(E, 0, scoring_func='tanh').apply({'params': params}, x)


# ------------------------------------------------------------------ #
# the noise
# ------------------------------------------------------------------ #
def test_the_noising_functions_rates_and_weights():
    tokens = jax.random.randint(jax.random.PRNGKey(0), (64, 512), 0, MASK)
    batch = noise_tokens(jax.random.PRNGKey(1), tokens, MASK, eps=0.05)
    masked = np.asarray(batch['weight'] > 0)
    assert np.array_equal(batch['tokens'], tokens)
    assert np.array_equal(np.asarray(batch['noised'] == MASK), masked)
    assert np.array_equal(np.asarray(batch['noised'])[~masked],
                          np.asarray(tokens)[~masked])
    # one level a sequence: every masked token of a row carries 1 / t
    w = np.asarray(batch['weight'])
    t = 1.0 / w.max(axis=1)
    assert np.all((t > 0.05) & (t <= 1.0))
    for row, level in zip(w, t):
        assert np.allclose(row[row > 0], 1.0 / level)
    # tokens masked independently at rate t (512 draws a row)
    np.testing.assert_allclose(masked.mean(axis=1), t, atol=0.08)
    # t uniform over the sequences, so half of all tokens in the mean, and
    # the weights sum to the tokens' count in expectation
    assert abs(masked.mean() - 0.525) < 0.08
    assert abs(w.sum() / tokens.size - 1.0) < 0.1
    assert batch['weight'].dtype == jnp.float32
    assert batch['noised'].dtype == tokens.dtype


# ------------------------------------------------------------------ #
# on the step factory
# ------------------------------------------------------------------ #
def test_balance_expert_load_settles_both_streams_scores(tiny):
    module, params, batch = tiny
    settled = balance_expert_load(module, params, [batch], steps=50,
                                  block_length=BK)
    moved = {jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(settled)[0],
        jax.tree_util.tree_leaves(params)) if not np.array_equal(a, b)}
    assert moved == {f"['{n}']['moe']['correction_bias']"
                     for n in ('blocks_1', 'blocks_3')}


def test_three_steps_on_the_one_step_factory_with_the_counters_in_aux(tiny):
    module, params, batch = tiny
    optimizer = optax.adam(1e-3)
    step = make_sharded_train_step(
        make_block_diffusion_loss(module, BK, chunk=8), optimizer)
    params = jax.tree_util.tree_map(jnp.array, params)     # donated below
    before = np.asarray(params['head']['kernel'])
    opt_state = optimizer.init(params)
    losses = []
    for i in range(3):
        params, opt_state, loss, aux = step(params, opt_state, batch,
                                            jax.random.PRNGKey(i))
        losses.append(float(loss))
        pairs = int(aux['moe_local_pairs'])
        # two expert layers over both streams, 4 of 8 experts held: about
        # half of 2 x 64 positions x 2 choices
        assert 0 < pairs <= 2 * 64 * 2
        assert int(aux['moe_dropped']) == 0
        assert int(aux['moe_bounded']) == 2
        assert aux['moe_choice'].shape == (2, 64, 2)
        held = np.asarray(aux['moe_choice']) // 4 == 1           # rank 1
        assert held.sum() == pairs
        assert int(aux['bd_masked']) == int((batch['weight'] > 0).sum())
        assert float(aux['bd_weight']) == pytest.approx(
            float(batch['weight'].sum()))
    assert losses[2] < losses[1] < losses[0]
    assert not np.array_equal(np.asarray(params['head']['kernel']), before)


# ------------------------------------------------------------------ #
# the three decoders that were there
# ------------------------------------------------------------------ #
def three_steps(recipe):
    """The parameter tree's leaves (path, shape), the first three losses and
    one number of the parameters after three steps of `recipe` at its tiny
    default sizes, next-token loss, Adam at 1e-3: float.hex() strings. Uses
    what the program had before the block-diffusion path alone, so that the
    parent's tree computes PARENT below with this very function."""
    module = RECIPES[recipe]()
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0, 48)
    params = module.init(jax.random.PRNGKey(8), tokens)['params']
    tree = [(jax.tree_util.keystr(path), a.shape) for path, a in
            jax.tree_util.tree_flatten_with_path(params)[0]]
    optimizer = optax.adam(1e-3)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    opt_state = optimizer.init(params)
    losses = []
    for i in range(3):
        params, opt_state, loss, _ = step(params, opt_state,
                                          dict(tokens=tokens),
                                          jax.random.PRNGKey(i))
        losses.append(float(loss).hex())
    total = sum(float(np.abs(np.asarray(a, np.float64)).sum())
                for a in jax.tree_util.tree_leaves(params))
    return tree, losses, total.hex()


# computed by `three_steps` on the parent commit's tree (8a2b7b8)
PARENT = {
    'token_decoder': (53, [
        '0x1.6e1ba40000000p+2', '0x1.5387e80000000p+2',
        '0x1.3e48ca0000000p+2'], '0x1.51aef6751884ep+12'),
    'hybrid_decoder': (40, [
        '0x1.16c1880000000p+2', '0x1.f556960000000p+1',
        '0x1.ccc7560000000p+1'], '0x1.1fbefb61bcfabp+12'),
    'lfm2_decoder': (33, [
        '0x1.0e9a960000000p+2', '0x1.e4d7220000000p+1',
        '0x1.ba0c960000000p+1'], '0x1.3114ed5ae6586p+12'),
}


@pytest.mark.parametrize('recipe', sorted(PARENT))
def test_with_default_arguments_a_decoder_is_the_parents_to_the_bit(recipe):
    tree, losses, total = three_steps(recipe)
    n_leaves, want_losses, want_total = PARENT[recipe]
    assert len(tree) == n_leaves
    assert not [name for name, _ in tree if 'scoring' in name]
    assert losses == want_losses
    assert total == want_total
