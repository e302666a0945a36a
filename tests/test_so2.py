"""SO(2)-reduced contraction backend (se3_transformer_tpu.so2).

Tiers: the op-level numerics (canonical blocks vs Q_J, Wigner
factorization, banded-vs-dense contraction, pairwise parity, tuning
kind, sweep schema) run in tier-1; the model-level programs (full-model
parity, equivariance at degrees 4-6, permutation/padding invariance)
compile multi-pair models on the 1-core CPU host and are marked slow —
same tiering rationale as the pallas/ring model suites.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.basis import get_basis
from se3_transformer_tpu.so2.canonical import (
    _compute_from_qj, canonical_blocks, canonical_kernel,
)
from se3_transformer_tpu.so2.contract import banded_z
from se3_transformer_tpu.so2.frames import (
    edge_frames, j_matrix, rotate_in, rotate_out, wigner_from_frames,
)
from se3_transformer_tpu.so3.wigner import (
    rot, wigner_d_from_rotation, x_to_alpha_beta,
)

F32 = jnp.float32


def _unit_vectors(n, seed=0):
    rng = np.random.RandomState(seed)
    v = rng.normal(size=(n, 3))
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


# --------------------------------------------------------------------- #
# canonical blocks
# --------------------------------------------------------------------- #
def test_canonical_seed_matches_qj_construction():
    """The committed seed must equal the from-first-principles Q_J
    construction (same intertwiners, same sign convention — the parity
    guarantee rides on this)."""
    for d_in, d_out in [(0, 1), (1, 1), (1, 2), (2, 2)]:
        a_seed, b_seed = canonical_blocks(d_in, d_out)
        a_qj, b_qj = _compute_from_qj(d_in, d_out)
        np.testing.assert_allclose(a_seed, a_qj, atol=1e-12)
        np.testing.assert_allclose(b_seed, b_qj, atol=1e-12)


def test_canonical_kernel_matches_dense_basis_at_axis():
    """reconstruct(blocks) == get_basis(e_z) for every frequency: the
    canonical kernels ARE the dense basis evaluated on the axis."""
    ez = jnp.asarray([[0.0, 0.0, 1.0]])
    for d_in, d_out in [(1, 1), (2, 3), (3, 3)]:
        dense = np.asarray(get_basis(ez, max(d_in, d_out))
                           [f'{d_in},{d_out}'][0])       # [P, Q, F]
        Kc = canonical_kernel(d_in, d_out)               # [F, P, Q]
        np.testing.assert_allclose(np.moveaxis(dense, -1, 0), Kc,
                                   atol=1e-6)


def test_canonical_blocks_cover_committed_degrees():
    """The committed seed covers every pair <= degree 6 (nobody pays
    the degree-6 Sylvester solves at runtime) with b[:, 0] == 0."""
    seed = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        '..', 'se3_transformer_tpu', 'so2',
                        '_canonical_seed.npz')
    with np.load(seed) as data:
        keys = set(data.files)
        for d_in in range(7):
            for d_out in range(7):
                assert f'{d_in}_{d_out}_a' in keys, (d_in, d_out)
                b = data[f'{d_in}_{d_out}_b']
                np.testing.assert_allclose(b[:, 0], 0.0, atol=0.0)


# --------------------------------------------------------------------- #
# frames / Wigner factorization
# --------------------------------------------------------------------- #
def test_wigner_from_frames_matches_host_wigner():
    """The traced Dz/J factorization must reproduce the host float64
    Wigner matrices of the alignment rotation rhat = R(alpha, beta, 0)
    e_z for every degree the backend supports."""
    vs = _unit_vectors(5)
    frames = edge_frames(jnp.asarray(vs, F32), 6)
    for l in range(1, 7):
        D = np.asarray(wigner_from_frames(frames, l))
        for i, v in enumerate(vs):
            al, be = x_to_alpha_beta(v)
            D_ref = wigner_d_from_rotation(l, rot(al, be, 0.0))
            np.testing.assert_allclose(D[i], D_ref, atol=5e-6)


def test_j_matrix_conjugates_z_into_y():
    for l in (1, 3, 5):
        J = j_matrix(l)
        beta = 0.83
        lhs = wigner_d_from_rotation(
            l, np.array([[np.cos(beta), 0, np.sin(beta)],
                         [0, 1, 0],
                         [-np.sin(beta), 0, np.cos(beta)]]))
        Dz = wigner_d_from_rotation(
            l, np.array([[np.cos(beta), -np.sin(beta), 0],
                         [np.sin(beta), np.cos(beta), 0], [0, 0, 1]]))
        np.testing.assert_allclose(lhs, J @ Dz @ J.T, atol=1e-12)


def test_rotate_in_out_roundtrip_and_pole_safety():
    rng = np.random.RandomState(3)
    # include exact poles and the zero vector (padding edges)
    rel = np.concatenate([rng.normal(size=(6, 3)),
                          [[0, 0, 1.0], [0, 0, -1.0], [0, 0, 0.0]]])
    frames = edge_frames(jnp.asarray(rel, F32), 4)
    for l in (0, 2, 4):
        x = jnp.asarray(rng.normal(size=(rel.shape[0], 3, 2 * l + 1)),
                        F32)
        back = rotate_out(rotate_in(x, frames, l), frames, l)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=1e-5)


def test_edge_frames_differentiable_flag():
    rel = jnp.asarray(_unit_vectors(4, seed=5), F32)

    def probe(r, differentiable):
        f = edge_frames(r, 2, differentiable=differentiable)
        return (f['cos_a'].sum() + f['sin_b'].sum())

    g_off = jax.grad(lambda r: probe(r, False))(rel)
    g_on = jax.grad(lambda r: probe(r, True))(rel)
    assert float(jnp.abs(g_off).max()) == 0.0
    assert float(jnp.abs(g_on).max()) > 0.0
    assert bool(jnp.isfinite(g_on).all())


# --------------------------------------------------------------------- #
# banded contraction
# --------------------------------------------------------------------- #
def test_banded_z_matches_dense_canonical_einsum():
    """banded_z == the dense einsum against the reconstructed [F, P, Q]
    canonical kernels (the band compression drops nothing)."""
    rng = np.random.RandomState(7)
    for d_in, d_out in [(0, 2), (1, 1), (2, 1), (3, 2), (2, 3)]:
        C, Q = 3, 2 * d_in + 1
        xr = jnp.asarray(rng.normal(size=(4, C, Q)), F32)
        Kc = jnp.asarray(canonical_kernel(d_in, d_out), F32)  # [F, P, Q]
        ref = jnp.einsum('fpq,ecq->epcf', Kc, xr)
        ref = ref.reshape(4, 2 * d_out + 1, -1)
        z = banded_z(xr, d_in, d_out)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   atol=1e-6)
        # band-only form: the pad-trimmed rows are exactly the zeros
        mmin = min(d_in, d_out)
        zb = banded_z(xr, d_in, d_out, pad_rows=False)
        np.testing.assert_allclose(
            np.asarray(zb),
            np.asarray(ref[:, d_out - mmin:d_out + mmin + 1]), atol=1e-6)


def test_pairwise_so2_matches_dense():
    """PairwiseConvSE3 backend='so2' vs 'dense' on identical params
    (the same w3/b3 tree serves both backends)."""
    from se3_transformer_tpu.ops.conv import PairwiseConvSE3
    rng = np.random.RandomState(0)
    for d_in, d_out in [(0, 1), (1, 2), (2, 2), (3, 1)]:
        b, n, k, ci, co = 1, 5, 3, 2, 3
        Q = 2 * d_in + 1
        edge = jnp.asarray(rng.normal(size=(b, n, k, 1)), F32)
        x = jnp.asarray(rng.normal(size=(b, n, k, ci, Q)), F32)
        rel = jnp.asarray(rng.normal(size=(b, n, k, 3)), F32)
        basis = get_basis(rel, max(d_in, d_out))
        frames = edge_frames(rel, max(d_in, d_out))
        dense = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False)
        so2 = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                              backend='so2')
        params = dense.init(jax.random.PRNGKey(1), edge,
                            basis[f'{d_in},{d_out}'], x)
        out_d = dense.apply(params, edge, basis[f'{d_in},{d_out}'], x)
        out_s = so2.apply(params, edge, frames, x)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                                   atol=2e-5)


def test_unknown_backend_is_loud():
    from se3_transformer_tpu.ops.conv import get_conv_backend
    with pytest.raises(KeyError, match='unknown conv backend'):
        get_conv_backend('nope')


def test_resolve_conv_backend_rules():
    from se3_transformer_tpu.ops.conv import resolve_conv_backend
    assert resolve_conv_backend('so2', 'conv_in') == 'so2'
    spec = (('to_[vk]', 'so2'), ('conv_out', 'dense'), ('.*', 'so2'))
    assert resolve_conv_backend(spec, 'attn_block0/to_v') == 'so2'
    assert resolve_conv_backend(spec, 'conv_out') == 'dense'
    assert resolve_conv_backend(spec, 'preconv1') == 'so2'
    # implicit dense tail when no rule matches
    assert resolve_conv_backend((('to_v', 'so2'),), 'conv_in') == 'dense'


# --------------------------------------------------------------------- #
# tuning kind
# --------------------------------------------------------------------- #
def test_so2_tuning_kind_registered_and_consulted(tmp_path, monkeypatch):
    from se3_transformer_tpu.kernels import tuning
    from se3_transformer_tpu.so2.contract import _pick_so2_chunks

    assert 'so2' in tuning.KINDS
    shape = (64, 4, 4, 9, 9, 9)
    cands = tuning.admissible_candidates('so2', shape)
    assert (1,) in cands and (8,) in cands
    assert all(c[0] <= 64 for c in cands)

    monkeypatch.setenv('SE3_TPU_CACHE_PATH', str(tmp_path))
    monkeypatch.delenv('SE3_TPU_SO2_CHUNKS', raising=False)
    tuning.reset_consults()
    assert _pick_so2_chunks(shape, 'float32') == 1        # heuristic
    tuning.promote('so2', shape, (4,), dtype='float32')
    assert _pick_so2_chunks(shape, 'float32') == 4        # cache hit
    with tuning.force('so2', (2,), shape=shape, dtype='float32'):
        assert _pick_so2_chunks(shape, 'float32') == 2    # forced
    monkeypatch.setenv('SE3_TPU_SO2_CHUNKS', '8')
    assert _pick_so2_chunks(shape, 'float32') == 8        # env wins
    sources = {c['source'] for c in tuning.consults()
               if c['kernel'] == 'so2'}
    assert {'heuristic', 'cache', 'forced', 'env'} <= sources


def test_so2_invalid_table_entry_degrades_to_heuristic(tmp_path,
                                                       monkeypatch):
    from se3_transformer_tpu.kernels import tuning
    from se3_transformer_tpu.so2.contract import _pick_so2_chunks
    monkeypatch.setenv('SE3_TPU_CACHE_PATH', str(tmp_path))
    monkeypatch.delenv('SE3_TPU_SO2_CHUNKS', raising=False)
    shape = (64, 4, 4, 9, 9, 9)
    tuning.promote('so2', shape, (128,), dtype='float32')  # > n: illegal
    with pytest.warns(UserWarning, match='not tile-legal'):
        assert _pick_so2_chunks(shape, 'float32') == 1


def test_so2_chunk_streaming_matches_unchunked():
    """SE3_TPU_SO2_CHUNKS streams the node axis through lax.map; the
    result must be bit-comparable to the unchunked contraction."""
    from se3_transformer_tpu.so2.contract import so2_pair_contract
    rng = np.random.RandomState(2)
    b, n, k, C, d_in, d_out, O, mid = 1, 6, 3, 2, 2, 1, 3, 8
    Q, F = 2 * d_in + 1, 2 * min(d_in, d_out) + 1
    h = jnp.asarray(rng.normal(size=(b, n, k, mid)), F32)
    w3 = jnp.asarray(rng.normal(size=(mid, C * F, O)), F32)
    b3 = jnp.asarray(rng.normal(size=(C * F, O)), F32)
    x = jnp.asarray(rng.normal(size=(b, n, k, C, Q)), F32)
    frames = edge_frames(jnp.asarray(rng.normal(size=(b, n, k, 3)), F32),
                         max(d_in, d_out))
    kwargs = dict(d_in=d_in, d_out=d_out, pallas=False,
                  pallas_interpret=False)
    ref = so2_pair_contract(h, w3, b3, frames, x, edge_chunks=None,
                            **kwargs)
    chunked = so2_pair_contract(h, w3, b3, frames, x, edge_chunks=3,
                                **kwargs)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(ref),
                               atol=1e-6)


# --------------------------------------------------------------------- #
# model level (slow tier: multi-pair compiles on the 1-core CPU host)
# --------------------------------------------------------------------- #
def _model_data(n=24, dim=8, seed=0):
    rng = np.random.RandomState(seed)
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), F32)
    coors = jnp.asarray(rng.normal(size=(1, n, 3)), F32)
    mask = jnp.ones((1, n), bool)
    return feats, coors, mask


def _model_kwargs(max_degree, dim=8, **over):
    kw = dict(dim=dim, depth=1, num_degrees=max_degree + 1,
              output_degrees=2, attend_self=True, num_neighbors=4,
              heads=2, dim_head=4)
    kw.update(over)
    return kw


@pytest.mark.slow
def test_model_so2_matches_dense_degree3():
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    feats, coors, mask = _model_data()
    dense = SE3TransformerModule(**_model_kwargs(3))
    so2 = SE3TransformerModule(conv_backend='so2', **_model_kwargs(3))
    params = dense.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                        return_type=1)['params']
    out_d = dense.apply({'params': params}, feats, coors, mask=mask,
                        return_type=1)
    out_s = so2.apply({'params': params}, feats, coors, mask=mask,
                      return_type=1)
    assert float(jnp.abs(out_d - out_s).max()) < 1e-4


@pytest.mark.slow
def test_model_so2_shared_radial_matches_dense_degree2():
    """The grouped (shared_radial_hidden) so2 path — one fused radial
    launch per output degree — against dense grouped, same params."""
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    feats, coors, mask = _model_data()
    kw = _model_kwargs(2, shared_radial_hidden=True)
    dense = SE3TransformerModule(**kw)
    so2 = SE3TransformerModule(conv_backend='so2', **kw)
    params = dense.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                        return_type=1)['params']
    out_d = dense.apply({'params': params}, feats, coors, mask=mask,
                        return_type=1)
    out_s = so2.apply({'params': params}, feats, coors, mask=mask,
                      return_type=1)
    assert float(jnp.abs(out_d - out_s).max()) < 1e-4


@pytest.mark.slow
@pytest.mark.parametrize('max_degree', [4, 5, 6])
def test_so2_equivariance_high_degree(max_degree):
    """The acceptance gate: rotation equivariance at degrees 4-6, where
    the dense backend is no longer affordable (all-so2 model — no dense
    basis, no degree-6 Q_J)."""
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.utils.validation import equivariance_l2
    feats, coors, mask = _model_data(dim=4)
    module = SE3TransformerModule(conv_backend='so2',
                                  **_model_kwargs(max_degree, dim=4))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    err = equivariance_l2(module, params, feats, coors, mask)
    assert err < 1e-4, f'so2 backend not equivariant at degree ' \
                       f'{max_degree}: {err}'


@pytest.mark.slow
def test_so2_permutation_equivariance_degree4():
    """Permuting the nodes permutes the outputs (neighbor selection +
    frames + banded contraction carry no positional leakage)."""
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    feats, coors, mask = _model_data(dim=4, seed=2)
    module = SE3TransformerModule(conv_backend='so2',
                                  **_model_kwargs(4, dim=4))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    out = module.apply({'params': params}, feats, coors, mask=mask,
                       return_type=1)
    perm = np.random.RandomState(0).permutation(feats.shape[1])
    out_p = module.apply({'params': params}, feats[:, perm],
                         coors[:, perm], mask=mask, return_type=1)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out)[:, perm],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_so2_padded_matches_dense_padded_degree3():
    """The padded-parity case for the so2 path: padding introduces
    zero-length (degenerate) edges whose frames hit the pole guard —
    on a padded batch the so2 backend must still agree with the dense
    backend to roundoff on EVERY row (pad rows included), and produce
    no NaN/Inf anywhere.

    (Absolute padded-vs-unpadded parity is NOT a property of the model
    under a tight num_neighbors budget on either backend: neighbor
    RANKING follows the reference and ranks masked pairs by true
    distance, so origin-coordinate pad nodes can occupy top-k slots —
    identical behavior dense vs so2, verified here by the cross-backend
    comparison on the padded inputs. With num_neighbors >= n the model
    IS pad-invariant, which is the serving engines' bucket contract —
    covered by test_inference/test_serving padded-parity tests.)"""
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    rng = np.random.RandomState(4)
    n, pad, dim = 12, 5, 4
    feats = np.concatenate(
        [rng.normal(size=(1, n, dim)), np.zeros((1, pad, dim))],
        axis=1).astype(np.float32)
    coors = np.concatenate(
        [rng.normal(size=(1, n, 3)), np.zeros((1, pad, 3))],
        axis=1).astype(np.float32)
    mask = np.concatenate(
        [np.ones((1, n), bool), np.zeros((1, pad), bool)], axis=1)
    kw = _model_kwargs(3, dim=dim, num_neighbors=4)
    dense = SE3TransformerModule(**kw)
    so2 = SE3TransformerModule(conv_backend='so2', **kw)
    params = dense.init(jax.random.PRNGKey(0), jnp.asarray(feats),
                        jnp.asarray(coors), mask=jnp.asarray(mask),
                        return_type=1)['params']
    out_d = dense.apply({'params': params}, jnp.asarray(feats),
                        jnp.asarray(coors), mask=jnp.asarray(mask),
                        return_type=1)
    out_s = so2.apply({'params': params}, jnp.asarray(feats),
                      jnp.asarray(coors), mask=jnp.asarray(mask),
                      return_type=1)
    assert bool(jnp.isfinite(out_s).all())
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               rtol=1e-4, atol=1e-5)

    # and with a neighbor budget covering every node, padding IS inert
    # on the so2 path (the engines' bucket contract)
    kw_full = _model_kwargs(3, dim=dim, num_neighbors=64)
    so2_full = SE3TransformerModule(conv_backend='so2', **kw_full)
    p_full = so2_full.init(jax.random.PRNGKey(0),
                           jnp.asarray(feats[:, :n]),
                           jnp.asarray(coors[:, :n]),
                           mask=jnp.ones((1, n), bool),
                           return_type=1)['params']
    out_u = so2_full.apply({'params': p_full}, jnp.asarray(feats[:, :n]),
                           jnp.asarray(coors[:, :n]),
                           mask=jnp.ones((1, n), bool), return_type=1)
    out_p = so2_full.apply({'params': p_full}, jnp.asarray(feats),
                           jnp.asarray(coors), mask=jnp.asarray(mask),
                           return_type=1)
    np.testing.assert_allclose(np.asarray(out_p)[:, :n],
                               np.asarray(out_u), rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_so2_gradients_finite_with_differentiable_coors():
    """Coordinate gradients flow through the frames (guarded pole
    division) and stay finite; param grads too."""
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    feats, coors, mask = _model_data(n=12, dim=4)
    module = SE3TransformerModule(conv_backend='so2',
                                  differentiable_coors=True,
                                  **_model_kwargs(2, dim=4))
    params = module.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']

    def loss(p, c):
        out = module.apply({'params': p}, feats, c, mask=mask,
                           return_type=1)
        return (out ** 2).sum()

    gp, gc = jax.grad(loss, argnums=(0, 1))(params, coors)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(gp))
    assert bool(jnp.isfinite(gc).all())
    assert float(jnp.abs(gc).max()) > 0.0
