"""SE3TransformerV2 family tests (se3_transformer_tpu.v2): the
separable S2 activation in isolation (grid exactness, equivariance at
degrees 4/6/8, permutation, padded parity, grads at degenerate inputs),
the per-m conv's structural no-dense-basis guarantee, model-level
equivariance / permutation / padding / gradient behavior, the
checkpoint model-family guard (both directions + back-compat), the v2
partition-rule coverage on a 2-axis mesh (QuantTensor descent
included), the capability signal through engine/replica/telemetry, and
the degree-6 train-save-serve end-to-end."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from se3_transformer_tpu.ops.fiber import Fiber
from se3_transformer_tpu.v2 import (
    DEFAULT_V2_MID_DIM, SE3TransformerV2, SE3TransformerV2Module,
    SeparableS2Activation, V2ConvSE3, s2_grid_matrices, v2_band_rows,
)

F32 = jnp.float32


# --------------------------------------------------------------------- #
# separable S2 activation, isolated
# --------------------------------------------------------------------- #
def test_s2_grid_analysis_inverts_synthesis():
    """A @ Y == I to float64 for every degree the family serves — the
    Gram solve must absorb the SH normalization convention."""
    from se3_transformer_tpu.v2.s2act import default_grid
    for degree in range(1, 9):
        n_theta, n_phi = default_grid(degree)
        Y, A = s2_grid_matrices(degree, n_theta, n_phi)
        np.testing.assert_allclose(A @ Y, np.eye(2 * degree + 1),
                                   atol=1e-12)


def _act_features(fiber, n=5, seed=0):
    # 0.3x: the aliasing of gelu-on-grid grows with function amplitude
    # (the high-frequency tail of gelu(f) scales with |f|); in-model
    # activations sit well below unit scale, so test there
    rng = np.random.RandomState(seed)
    return {str(d): jnp.asarray(
        0.3 * rng.normal(size=(1, n, c, 2 * d + 1)), F32)
            for d, c in fiber}


@pytest.mark.parametrize('degree', [4, 6, 8])
def test_s2_activation_equivariance(degree):
    """act(x . D) == act(x) . D for a non-degenerate rotation's irrep
    matrix: the grid nonlinearity is pointwise on S2, so rotation (which
    acts on the synthesized function by composition) commutes with it
    up to quadrature aliasing — the per-degree default grid keeps that
    below ~1e-6 even at degree 8."""
    from se3_transformer_tpu.so3 import irr_repr
    fiber = Fiber({0: 4, degree: 4})
    act = SeparableS2Activation(fiber)
    x = _act_features(fiber)
    params = act.init(jax.random.PRNGKey(0), x)['params']
    D = jnp.asarray(irr_repr(degree, 0.37, 1.12, -0.64), F32)
    x_rot = {**x, str(degree): jnp.einsum('...cp,pq->...cq',
                                          x[str(degree)], D)}
    out = act.apply({'params': params}, x)
    out_rot = act.apply({'params': params}, x_rot)
    want = jnp.einsum('...cp,pq->...cq', out[str(degree)], D)
    err = float(jnp.abs(out_rot[str(degree)] - want).max())
    assert err < 1e-4, f's2 activation broke equivariance at degree ' \
                       f'{degree}: {err}'
    # degree 0 is rotation-blind: identical either way
    np.testing.assert_allclose(np.asarray(out_rot['0']),
                               np.asarray(out['0']), atol=0)


def test_s2_activation_gate_only_mode_is_exact():
    """grid_nonlin=False leaves the per-degree scalar gate as the only
    l>0 transform — exactly equivariant (no quadrature anywhere), at
    any resolution."""
    from se3_transformer_tpu.so3 import irr_repr
    degree = 6
    fiber = Fiber({0: 4, degree: 4})
    act = SeparableS2Activation(fiber, grid_nonlin=False)
    x = _act_features(fiber)
    params = act.init(jax.random.PRNGKey(0), x)['params']
    D = jnp.asarray(irr_repr(degree, 0.9, 0.4, 2.2), F32)
    x_rot = {**x, str(degree): jnp.einsum('...cp,pq->...cq',
                                          x[str(degree)], D)}
    out = act.apply({'params': params}, x)
    out_rot = act.apply({'params': params}, x_rot)
    want = jnp.einsum('...cp,pq->...cq', out[str(degree)], D)
    assert float(jnp.abs(out_rot[str(degree)] - want).max()) < 1e-6


def test_s2_activation_permutation_equivariance():
    fiber = Fiber.create(3, 4)
    act = SeparableS2Activation(fiber)
    x = _act_features(fiber, n=7, seed=3)
    params = act.init(jax.random.PRNGKey(1), x)['params']
    out = act.apply({'params': params}, x)
    perm = np.random.RandomState(0).permutation(7)
    x_p = {k: v[:, perm] for k, v in x.items()}
    out_p = act.apply({'params': params}, x_p)
    for k in out:
        np.testing.assert_allclose(np.asarray(out_p[k]),
                                   np.asarray(out[k])[:, perm],
                                   rtol=1e-6, atol=1e-6)


def test_s2_activation_padded_parity():
    """Zero (pad) rows stay exactly zero through the grid roundtrip
    (gelu(0) == 0, A @ 0 == 0, gate * 0 == 0) and real rows are
    untouched by the padding — the engines' bucket contract holds with
    no mask plumbed through the activation at all."""
    fiber = Fiber.create(3, 4)
    act = SeparableS2Activation(fiber)
    x = _act_features(fiber, n=6, seed=5)
    params = act.init(jax.random.PRNGKey(2), x)['params']
    out = act.apply({'params': params}, x)
    x_pad = {k: jnp.concatenate(
        [v, jnp.zeros_like(v[:, :3])], axis=1) for k, v in x.items()}
    out_pad = act.apply({'params': params}, x_pad)
    for k in out:
        np.testing.assert_allclose(np.asarray(out_pad[k])[:, :6],
                                   np.asarray(out[k]), atol=0)
        if k != '0':
            assert float(jnp.abs(out_pad[k][:, 6:]).max()) == 0.0


def test_s2_activation_grads_finite_at_zero_features():
    """NormSE3 needs a safe-norm clip to keep grads finite at zero
    features; the S2 path has no norm, so the degenerate point is
    regular for free."""
    fiber = Fiber.create(3, 4)
    act = SeparableS2Activation(fiber)
    x = {str(d): jnp.zeros((1, 4, c, 2 * d + 1), F32)
         for d, c in fiber}
    params = act.init(jax.random.PRNGKey(0), x)['params']

    def loss(p, feats):
        out = act.apply({'params': p}, feats)
        return sum((v ** 2).sum() for v in out.values())

    gp, gx = jax.grad(loss, argnums=(0, 1))(params, x)
    for g in jax.tree_util.tree_leaves((gp, gx)):
        assert bool(jnp.isfinite(g).all())


# --------------------------------------------------------------------- #
# per-m conv: structure
# --------------------------------------------------------------------- #
def test_v2_band_rows():
    assert v2_band_rows(0, 4) == 1
    assert v2_band_rows(2, 4) == 5
    assert v2_band_rows(4, 4) == 9
    assert v2_band_rows(4, 4, max_m=1) == 3


def _v2_data(n=16, dim=4, seed=0):
    rng = np.random.RandomState(seed)
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), F32)
    coors = jnp.asarray(rng.normal(size=(1, n, 3)), F32)
    mask = jnp.ones((1, n), bool)
    return feats, coors, mask


def _v2_kwargs(max_degree, dim=4, **over):
    kw = dict(dim=dim, depth=1, num_degrees=max_degree + 1,
              output_degrees=2, num_neighbors=4)
    kw.update(over)
    return kw


def test_v2_never_touches_dense_basis_or_canonical_path(monkeypatch):
    """The structural no-dense claim: a v2 forward must succeed with
    BOTH the dense-basis constructor and the v1 canonical banded
    contraction rigged to explode — v2's radial trunk emits the banded
    blocks directly, so neither can be on any code path. The param
    tree backs it up: per-m blocks only, nothing w3-shaped."""
    import se3_transformer_tpu.basis as basis_mod
    import se3_transformer_tpu.so2.contract as so2_contract

    def boom(*a, **k):
        raise AssertionError('dense/canonical path reached from v2')

    monkeypatch.setattr(basis_mod, 'get_basis', boom)
    monkeypatch.setattr(so2_contract, 'banded_z', boom)
    monkeypatch.setattr(so2_contract, 'canonical_blocks', boom,
                        raising=False)

    feats, coors, mask = _v2_data()
    module = SE3TransformerV2Module(**_v2_kwargs(3))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    out = module.apply({'params': params}, feats, coors, mask=mask,
                       return_type=1)
    assert out.shape == (1, 16, 4, 3)   # [b, n, channels, xyz]
    assert bool(jnp.isfinite(out).all())

    import re as _re
    flat = {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    assert any("'wm" in p for p in flat)
    for path, leaf in flat.items():
        if _re.search(r"\['w\d+'\]", path):
            # v1's dense-shaped radial weights are rank-3 w{d} leaves
            # [mid, O, C*F]; only LinearSE3's rank-2 per-degree
            # mixers may share the name class
            assert leaf.ndim == 2, f'dense-shaped radial leaf: {path}'
        if "'wm" in path:
            assert leaf.ndim == 3
            # K axis is C or 2C — never the dense path's C*F
            assert leaf.shape[1] <= 2 * 4


def test_v2_conv_max_m_truncation_changes_params_not_equivariance():
    from se3_transformer_tpu.utils.validation import equivariance_l2
    feats, coors, mask = _v2_data(seed=1)
    full = SE3TransformerV2Module(**_v2_kwargs(3))
    trunc = SE3TransformerV2Module(max_m=1, **_v2_kwargs(3))
    p_full = full.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                       return_type=1)['params']
    p_trunc = trunc.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    n_full = len(jax.tree_util.tree_leaves(p_full))
    n_trunc = len(jax.tree_util.tree_leaves(p_trunc))
    assert n_trunc < n_full            # blocks beyond |m|=1 are GONE
    err = equivariance_l2(trunc, p_trunc, feats, coors, mask)
    assert err < 1e-4, f'max_m truncation broke equivariance: {err}'


# --------------------------------------------------------------------- #
# model level
# --------------------------------------------------------------------- #
@pytest.mark.slow
@pytest.mark.parametrize('max_degree', [4, 6, 8])
def test_v2_model_equivariance_high_degree(max_degree):
    """The family acceptance gate: ~1e-6 rotation equivariance at
    degrees 4-8 (per-m blocks commute exactly; the S2 grids alias
    below 1e-6 at the default per-degree resolution)."""
    from se3_transformer_tpu.utils.validation import equivariance_l2
    feats, coors, mask = _v2_data()
    module = SE3TransformerV2Module(**_v2_kwargs(max_degree))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    err = equivariance_l2(module, params, feats, coors, mask)
    assert err < 1e-4, f'v2 not equivariant at degree {max_degree}: ' \
                       f'{err}'


@pytest.mark.heavy
def test_v2_model_permutation_equivariance():
    feats, coors, mask = _v2_data(seed=2)
    module = SE3TransformerV2Module(**_v2_kwargs(3))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    out = module.apply({'params': params}, feats, coors, mask=mask,
                       return_type=1)
    perm = np.random.RandomState(0).permutation(feats.shape[1])
    out_p = module.apply({'params': params}, feats[:, perm],
                         coors[:, perm], mask=mask, return_type=1)
    np.testing.assert_allclose(np.asarray(out_p),
                               np.asarray(out)[:, perm],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.heavy
def test_v2_model_padded_matches_unpadded():
    """With a neighbor budget covering every real node, padding is
    inert (the engines' bucket contract): pad rows carry zero features
    and masked-out neighbors, and the S2 activation keeps zeros zero."""
    rng = np.random.RandomState(4)
    n, pad, dim = 10, 4, 4
    feats = np.concatenate(
        [rng.normal(size=(1, n, dim)), np.zeros((1, pad, dim))],
        axis=1).astype(np.float32)
    coors = np.concatenate(
        [rng.normal(size=(1, n, 3)), np.zeros((1, pad, 3))],
        axis=1).astype(np.float32)
    mask = np.concatenate(
        [np.ones((1, n), bool), np.zeros((1, pad), bool)], axis=1)
    module = SE3TransformerV2Module(**_v2_kwargs(3, num_neighbors=32))
    p = module.init(jax.random.PRNGKey(0), jnp.asarray(feats[:, :n]),
                    jnp.asarray(coors[:, :n]),
                    mask=jnp.ones((1, n), bool),
                    return_type=1)['params']
    out_u = module.apply({'params': p}, jnp.asarray(feats[:, :n]),
                         jnp.asarray(coors[:, :n]),
                         mask=jnp.ones((1, n), bool), return_type=1)
    out_p = module.apply({'params': p}, jnp.asarray(feats),
                         jnp.asarray(coors), mask=jnp.asarray(mask),
                         return_type=1)
    assert bool(jnp.isfinite(out_p).all())
    np.testing.assert_allclose(np.asarray(out_p)[:, :n],
                               np.asarray(out_u), rtol=1e-4, atol=1e-5)


@pytest.mark.heavy
def test_v2_grads_finite_at_coincident_points():
    """Zero-distance edges (coincident nodes) hit the frames pole
    guard; grads through coords AND params must stay finite — the S2
    activation adds no norm singularities on top."""
    feats, coors, mask = _v2_data(n=8)
    coors = coors.at[:, 1].set(coors[:, 0])     # duplicate node 0
    module = SE3TransformerV2Module(differentiable_coors=True,
                                    **_v2_kwargs(2))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']

    def loss(p, c):
        out = module.apply({'params': p}, feats, c, mask=mask,
                           return_type=1)
        return (out ** 2).sum()

    gp, gc = jax.grad(loss, argnums=(0, 1))(params, coors)
    for g in jax.tree_util.tree_leaves((gp, gc)):
        assert bool(jnp.isfinite(g).all())


@pytest.mark.heavy
def test_v2_eager_wrapper_and_output_conventions():
    model = SE3TransformerV2(dim=4, depth=1, num_degrees=2,
                             output_degrees=1, num_neighbors=4,
                             num_tokens=8)
    assert model.model_family == 'se3_v2'
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 8, size=(1, 12)))
    coors = jnp.asarray(rng.normal(size=(1, 12, 3)), F32)
    mask = jnp.ones((1, 12), bool)
    out = model(tokens, coors, mask=mask)        # output_degrees==1
    assert out.shape == (1, 12, 4)               # '0' squeezed
    pooled = model(tokens, coors, mask=mask, return_pooled=True)
    assert pooled.shape == (1, 4)


# --------------------------------------------------------------------- #
# checkpoint model-family guard
# --------------------------------------------------------------------- #
def _state(v=1.0):
    return dict(params=dict(w=np.full(3, v, np.float32)), step=0)


def test_checkpoint_family_guard_both_directions(tmp_path):
    from se3_transformer_tpu.training.checkpoint import (
        CheckpointManager, ModelFamilyMismatch,
    )
    d1 = os.path.join(tmp_path, 'v1ck')
    with CheckpointManager(d1, model_family='se3_v1') as mgr:
        mgr.save(1, _state())
    # v1 checkpoint into a v2 restorer: LOUD, structured, both APIs
    v2mgr = CheckpointManager(d1, model_family='se3_v2')
    with pytest.raises(ModelFamilyMismatch) as ei:
        v2mgr.restore(1)
    assert ei.value.expected == 'se3_v2'
    assert ei.value.found == 'se3_v1'
    assert ei.value.step == 1
    with pytest.raises(ModelFamilyMismatch):
        v2mgr.restore_params(1)
    # step=None must not silently "fall back past" the mismatch — it
    # is a config error, not a torn checkpoint
    with pytest.raises(ModelFamilyMismatch):
        v2mgr.restore()
    # and the reverse direction
    d2 = os.path.join(tmp_path, 'v2ck')
    with CheckpointManager(d2, model_family='se3_v2') as mgr:
        mgr.save(1, _state(2.0))
    with pytest.raises(ModelFamilyMismatch):
        CheckpointManager(d2, model_family='se3_v1').restore(1)
    # same family passes
    state = CheckpointManager(d2, model_family='se3_v2').restore(1)
    assert np.allclose(state['params']['w'], 2.0)


def test_checkpoint_family_guard_back_compat(tmp_path):
    """Unstamped (pre-guard / family-agnostic) checkpoints restore
    under ANY expected family, and a stamped checkpoint restores under
    a family-agnostic manager — the guard only fires when both sides
    declare and disagree."""
    from se3_transformer_tpu.training.checkpoint import CheckpointManager
    d = os.path.join(tmp_path, 'legacy')
    with CheckpointManager(d) as mgr:            # no family: unstamped
        mgr.save(1, _state())
    assert not [f for f in os.listdir(d) if f.endswith('.meta.json')]
    state = CheckpointManager(d, model_family='se3_v2').restore(1)
    assert np.allclose(state['params']['w'], 1.0)

    d2 = os.path.join(tmp_path, 'stamped')
    with CheckpointManager(d2, model_family='se3_v1') as mgr:
        mgr.save(1, _state())
    metas = [f for f in os.listdir(d2) if f.endswith('.meta.json')]
    assert metas, 'family stamp sidecar missing'
    assert json.load(open(os.path.join(d2, metas[0])))[
        'model_family'] == 'se3_v1'
    state = CheckpointManager(d2).restore(1)     # agnostic reader
    assert np.allclose(state['params']['w'], 1.0)


def test_checkpoint_family_sidecar_follows_gc(tmp_path):
    from se3_transformer_tpu.training.checkpoint import CheckpointManager
    d = os.path.join(tmp_path, 'gc')
    with CheckpointManager(d, max_to_keep=2,
                           model_family='se3_v2') as mgr:
        for s in (1, 2, 3):
            mgr.save(s, _state(float(s)))
    metas = sorted(f for f in os.listdir(d) if f.endswith('.meta.json'))
    assert len(metas) == 2
    assert not any('00000001' in m for m in metas)


# --------------------------------------------------------------------- #
# partition rules: v2 param paths on a 2-axis mesh
# --------------------------------------------------------------------- #
def _v2_param_like_tree():
    """Synthetic tree with the v2 leaf names/shapes: per-m radial
    blocks (plain and quantized), their biases, an S2 gate head, and
    the shared radial-trunk Dense kernels."""
    from se3_transformer_tpu.quant.qtensor import quantize
    wm = np.zeros((32, 8, 8), np.float32)
    return {
        'block0': {
            'wm0_1_2': wm.copy(),
            'wm3_3_3': wm.copy(),                # 'wm3' is not a w3
            'bm0_1_2': np.zeros((8, 8), np.float32),
            'wm2_2_2': quantize(np.ones((32, 8, 8), np.float32)),
            'Dense_0': {'kernel': np.zeros((1, 32), np.float32),
                        'bias': np.zeros((32,), np.float32)},
        },
        'act0': {'gate2': {'kernel': np.zeros((4, 4), np.float32),
                           'bias': np.zeros((4,), np.float32)}},
    }


def test_v2_partition_rules_two_axis_mesh_with_quant_descent():
    """tp shards every per-m block's output-channel axis (QuantTensor
    q AND scale descending alike), fsdp dim-0-shards the blocks and
    replicates quantized scales without a demotion warning, and the
    default LOUD unmatched-leaf audit passes over the whole v2-shaped
    tree — no v2 leaf falls through uncovered."""
    from jax.sharding import Mesh
    from se3_transformer_tpu.parallel.rules import (
        fsdp_rules, match_partition_rules, tp_rules,
    )
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ('dp', 'tp'))
    params = _v2_param_like_tree()

    def _flat(specs):
        return {jax.tree_util.keystr(path): spec for path, spec in
                jax.tree_util.tree_flatten_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))[0]}

    # on_unmatched defaults to LOUD: completing without ValueError IS
    # the coverage audit
    tp = _flat(match_partition_rules(tp_rules(), params, mesh=mesh))
    assert tp["['block0']['wm0_1_2']"] == P(None, None, 'tp')
    assert tp["['block0']['wm3_3_3']"] == P(None, None, 'tp')
    assert tp["['block0']['bm0_1_2']"] == P(None, 'tp')
    assert tp["['block0']['wm2_2_2'].q"] == P(None, None, 'tp')
    assert tp["['block0']['wm2_2_2'].scale"] == P(None, None, 'tp')
    assert tp["['act0']['gate2']['kernel']"] == P()

    # the radial trunk's first Dense has a size-1 dim 0 (scalar
    # distance input): fsdp must demote it to replication AND say so
    with pytest.warns(UserWarning, match='demoted'):
        fsdp = _flat(match_partition_rules(fsdp_rules(), params,
                                           mesh=mesh))
    assert fsdp["['block0']['wm0_1_2']"] == P('dp')
    assert fsdp["['block0']['wm2_2_2'].q"] == P('dp')
    assert fsdp["['block0']['wm2_2_2'].scale"] == P()
    # dim 0 has size 1: demoted in place to replication
    assert fsdp["['block0']['Dense_0']['kernel']"] == P(None)
    assert fsdp["['act0']['gate2']['kernel']"] == P('dp')


@pytest.mark.heavy
def test_v2_real_param_tree_fully_covered_by_rule_sets():
    """The REAL v2 init tree (not a synthetic lookalike) passes the
    loud audit under both built-in rule sets."""
    from jax.sharding import Mesh
    from se3_transformer_tpu.parallel.rules import (
        fsdp_rules, match_partition_rules, tp_rules,
    )
    feats, coors, mask = _v2_data()
    module = SE3TransformerV2Module(**_v2_kwargs(2))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ('dp', 'tp'))
    for rules in (tp_rules(), fsdp_rules()):
        match_partition_rules(rules, params, mesh=mesh)  # loud default


def test_v2_quant_rules_class_membership():
    """The per-m blocks are invariant-input radial matmuls: int8-class
    under the shipped mixes (rank-guarded), while bm biases and l>0
    mixers stay out."""
    from se3_transformer_tpu.quant.rules import (
        MIXES, resolve_precision,
    )
    rules = MIXES['int8_mix']
    assert resolve_precision(rules, 'block0/wm3_2_2', ndim=3) == 'int8'
    assert resolve_precision(rules, 'block0/wm0_1_4', ndim=3) == 'int8'
    # rank guard: a 2-d leaf that happens to share the name class
    assert resolve_precision(rules, 'block0/wm3_2_2', ndim=2) == 'fp32'
    assert resolve_precision(rules, 'block0/bm3_2_2', ndim=2) == 'fp32'
    # v2's radial trunk reuses radial_hidden -> Dense kernels int8
    assert resolve_precision(rules, 'block0/Dense_0/kernel',
                             ndim=2) == 'int8'
    assert resolve_precision(rules, 'act0/gate2/kernel',
                             ndim=2) == 'fp32'


@pytest.mark.heavy
def test_v2_params_quantize_under_int8_mix():
    """quantize_params over a real v2 tree: wm blocks become
    QuantTensors, nothing trips the equivariant-precision guard, and
    the quantized model still runs."""
    from se3_transformer_tpu.quant import quantize_params
    from se3_transformer_tpu.quant.qtensor import QuantTensor
    feats, coors, mask = _v2_data()
    module = SE3TransformerV2Module(**_v2_kwargs(2))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    host = jax.tree_util.tree_map(np.asarray, params)
    qparams, report = quantize_params(host, 'int8_mix')
    assert report['leaves'].get('int8', 0) > 0
    flat = {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(
                qparams, is_leaf=lambda x: isinstance(x, QuantTensor)
            )[0]}
    wm_leaves = [v for k, v in flat.items() if "'wm" in k]
    assert wm_leaves
    assert all(isinstance(v, QuantTensor) for v in wm_leaves)
    out = module.apply({'params': qparams}, feats, coors, mask=mask,
                       return_type=1)
    assert bool(jnp.isfinite(out).all())


# --------------------------------------------------------------------- #
# capability signal: engine / replica / telemetry / schema
# --------------------------------------------------------------------- #
class _FamilyFakeEngine:
    """Engine-shaped stand-in carrying a model_family (the serving
    tests' fake, reduced to what the capability plumbing reads)."""

    def __init__(self, family='se3_v2', buckets=(4,), batch_size=2):
        from se3_transformer_tpu.observability import PhaseTimer
        self.model_family = family
        self.precision_name = 'fp32'
        self.buckets = tuple(buckets)
        self.batch_size = batch_size
        self.timer = PhaseTimer()
        self.executables = {}
        self.cost_payloads = {}
        self.params = 'v0'
        self.rows_served = {b: 0 for b in self.buckets}

    def run(self, bucket, tokens, coords, mask):
        with self.timer.phase(f'bucket_{bucket}'):
            self.rows_served[bucket] += int(np.asarray(mask).any(
                axis=-1).sum())
        return np.zeros(tokens.shape + (3,), np.float32)


def test_replica_and_router_surface_model_families():
    from se3_transformer_tpu.observability.schema import validate_record
    from se3_transformer_tpu.serving import (
        ReplicaWorker, Router, RouterTelemetry,
    )
    timer = None
    engines = [_FamilyFakeEngine('se3_v1'), _FamilyFakeEngine('se3_v2')]
    for e in engines:                   # telemetry contract: ONE timer
        timer = timer or e.timer
        e.timer = timer
    workers = [ReplicaWorker(i, e, max_wait_ms=10.0)
               for i, e in enumerate(engines)]
    assert workers[0].snapshot()['model_family'] == 'se3_v1'
    assert workers[1].snapshot()['model_family'] == 'se3_v2'
    router = Router(workers)
    tele = RouterTelemetry(router)
    tele.arm()
    rng = np.random.RandomState(0)
    for _ in range(4):
        router.submit(rng.randint(0, 8, size=4),
                      rng.normal(size=(4, 3)).astype(np.float32))
    router.drain()
    rec = tele.flush()
    assert rec['model_families'] == ['se3_v1', 'se3_v2']
    validate_record(dict(rec, kind='serve', run_id='t'))


def test_serve_schema_rejects_malformed_model_families():
    from se3_transformer_tpu.observability.schema import (
        SchemaError, validate_record,
    )
    base = dict(kind='serve', run_id='r',
                requests=dict(served=3, rejected={}),
                buckets={}, runtime=dict(compile_events_delta=0),
                queue_depth=0, post_warmup_compiles=0)
    snap = dict(depth=0, outstanding=0, served_rows=0)
    validate_record(dict(base, model_families=['se3_v2']))
    validate_record(dict(base, replicas={
        '0': dict(snap, model_family='se3_v2')}))
    with pytest.raises(SchemaError, match='model_families'):
        validate_record(dict(base, model_families='se3_v2'))
    with pytest.raises(SchemaError, match='model_families'):
        validate_record(dict(base, model_families=[1]))
    with pytest.raises(SchemaError, match='model_family'):
        validate_record(dict(base, replicas={
            '0': dict(snap, model_family='')}))


# --------------------------------------------------------------------- #
# end to end: train -> checkpoint -> serve
# --------------------------------------------------------------------- #
def _train_save_serve(max_degree, tmp_path, steps=3):
    import optax
    from se3_transformer_tpu.inference import InferenceEngine
    from se3_transformer_tpu.training.checkpoint import (
        CheckpointManager, ModelFamilyMismatch,
    )
    L = 6
    module = SE3TransformerV2Module(
        dim=4, depth=1, num_degrees=max_degree + 1, output_degrees=2,
        reduce_dim_out=True, num_neighbors=4, num_tokens=8)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 8, size=(1, L)))
    coors = jnp.asarray(rng.normal(size=(1, L, 3)), F32)
    target = jnp.asarray(rng.normal(size=(1, L, 3)), F32)
    mask = jnp.ones((1, L), bool)
    params = module.init(jax.random.PRNGKey(0), tokens, coors,
                         mask=mask, return_type=1)['params']

    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(p, s):
        def loss_fn(p):
            out = module.apply({'params': p}, tokens, coors, mask=mask,
                               return_type=1)
            return ((out - target) ** 2).mean()
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    losses = []
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], f'loss did not decrease: {losses}'

    ckpt = os.path.join(tmp_path, 'v2ck')
    with CheckpointManager(ckpt,
                           model_family=module.model_family) as mgr:
        mgr.save(steps, dict(params=params, step=steps))

    engine = InferenceEngine.from_checkpoint(
        module, ckpt, buckets=(L,), batch_size=1, return_type=1)
    assert engine.model_family == 'se3_v2'
    assert engine.stats()['model_family'] == 'se3_v2'
    out = engine.run(L, np.asarray(tokens), np.asarray(coors),
                     np.asarray(mask))
    assert np.asarray(out).shape == (1, L, 3)
    assert np.isfinite(np.asarray(out)).all()

    # a v1 module must NOT be able to serve this checkpoint
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    v1 = SE3TransformerModule(dim=4, depth=1, num_degrees=2,
                              num_tokens=8)
    with pytest.raises(ModelFamilyMismatch):
        InferenceEngine.from_checkpoint(v1, ckpt, buckets=(L,),
                                        batch_size=1, return_type=1)


@pytest.mark.heavy
def test_v2_train_save_serve_degree2(tmp_path):
    """Tier-1-affordable end-to-end: train steps decrease the loss,
    the stamped checkpoint serves through the AOT engine, and the v1
    family is locked out."""
    _train_save_serve(2, tmp_path)


@pytest.mark.slow
def test_v2_train_save_serve_degree6(tmp_path):
    """The acceptance criterion verbatim: SE3TransformerV2 at degree 6
    trains and serves end-to-end on CPU."""
    _train_save_serve(6, tmp_path)
