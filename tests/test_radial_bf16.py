"""radial_bf16: bf16 radial trunk/matmul must preserve equivariance.

The radial MLP's inputs are rotation-invariant scalars, so quantizing it
to bf16 adds noise that (nearly) cancels between the rotated and
unrotated forward — unlike a global bf16 matmul policy, which quantizes
the equivariant contractions and costs ~1e-3 equivariance error on chip.
These tests pin that property and the numeric agreement of the XLA and Pallas (interpret) bf16 paths.
"""
import jax
import jax.numpy as jnp
import numpy as np

from se3_transformer_tpu import SE3TransformerModule
from se3_transformer_tpu.basis import get_basis
from se3_transformer_tpu.ops.conv import PairwiseConvSE3


def _data(n=16, dim=8, seed=0):
    rng = np.random.RandomState(seed)
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, n, 3)), jnp.float32)
    mask = jnp.ones((1, n), bool)
    return feats, coors, mask


def test_model_radial_bf16_equivariant_and_close_to_f32():
    from se3_transformer_tpu.so3.wigner import rot

    feats, coors, mask = _data()
    base = dict(dim=8, depth=1, attend_self=True, num_neighbors=5,
                num_degrees=3, output_degrees=2, heads=2, dim_head=4)
    f32 = SE3TransformerModule(**base)
    bf16 = SE3TransformerModule(**base, radial_bf16=True)
    params = f32.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                      return_type=1)['params']

    o32 = f32.apply({'params': params}, feats, coors, mask=mask,
                    return_type=1)
    obf = bf16.apply({'params': params}, feats, coors, mask=mask,
                     return_type=1)
    assert obf.dtype == jnp.float32  # equivariant path stays f32
    # bf16 radial noise perturbs values a little...
    rel = float(np.abs(np.asarray(obf - o32)).max()
                / (np.abs(np.asarray(o32)).max() + 1e-9))
    assert 0 < rel < 3e-2, rel

    # ...but NOT equivariance: rotate coords (host f64), compare outputs
    R = np.asarray(rot(0.31, -1.2, 0.7), np.float64)
    coors_r = jnp.asarray(np.asarray(coors, np.float64) @ R.T, jnp.float32)
    obf_r = bf16.apply({'params': params}, feats, coors_r, mask=mask,
                       return_type=1)
    eq = float(np.abs(np.asarray(obf_r)
                      - np.asarray(obf) @ R.T.astype(np.float32)).max())
    assert eq < 1e-4, eq


def test_radial_bf16_gradients_finite_and_param_dtypes():
    feats, coors, mask = _data(seed=1)
    mod = SE3TransformerModule(dim=8, depth=1, attend_self=True,
                               num_neighbors=5, num_degrees=2,
                               output_degrees=2, radial_bf16=True)
    params = mod.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                      return_type=1)['params']
    # params stay f32 (bf16 is compute dtype only)
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.dtype == jnp.float32

    def loss(p):
        out = mod.apply({'params': p}, feats, coors, mask=mask,
                        return_type=1)
        return (out ** 2).sum()

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert leaf.dtype == jnp.float32
        assert bool(jnp.isfinite(leaf).all())


def test_radial_bf16_pallas_paths_match_xla():
    """bf16 trunk + kernel rt dot (interpret): plain and basis-fused
    Pallas paths agree with the bf16 XLA path (same bf16 operands, f32
    accumulation everywhere)."""
    rng = np.random.RandomState(2)
    d_in, d_out, ci, co = 1, 1, 4, 5
    b, n, k = 1, 6, 3
    edge = jnp.asarray(rng.normal(size=(b, n, k, 2)), jnp.float32)
    rel = jnp.asarray(rng.normal(size=(b, n, k, 3)), jnp.float32)
    basis = get_basis(rel, 1)[f'{d_in},{d_out}']
    x = jnp.asarray(rng.normal(size=(b, n, k, ci, 3)), jnp.float32)

    xla = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                          radial_bf16=True)
    params = xla.init(jax.random.PRNGKey(0), edge, basis, x)
    # nonzero bias: the bias must be quantized identically on every path
    params = {'params': {**params['params'],
                         'b3': params['params']['b3'] + 0.37}}
    out_ref = xla.apply(params, edge, basis, x)

    for kwargs in (dict(), dict(fuse_basis=True)):
        mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                              pallas_interpret=True, radial_bf16=True,
                              **kwargs)
        out = mod.apply(params, edge, basis, x)
        assert jnp.abs(out - out_ref).max() < 1e-4, kwargs

        def loss(p):
            return (mod.apply(p, edge, basis, x) ** 2).sum()

        for leaf in jax.tree_util.tree_leaves(jax.grad(loss)(params)):
            assert bool(jnp.isfinite(leaf).all())


def test_differentiable_coors_with_full_fast_path():
    """The fast-bench combination (shared radial + fuse_basis +
    radial_bf16, interpret kernels) keeps the differentiable_coors
    contract: nonzero finite coordinate gradients through the basis."""
    rng = np.random.RandomState(3)
    feats = jnp.asarray(rng.randint(0, 24, (1, 16)))
    coors = jnp.asarray(rng.normal(size=(1, 16, 3)), jnp.float32)
    mask = jnp.ones((1, 16), bool)
    mod = SE3TransformerModule(
        num_tokens=24, dim=8, dim_head=8, heads=2, depth=1,
        attend_self=True, input_degrees=1, num_degrees=2, output_degrees=2,
        reduce_dim_out=True, differentiable_coors=True, num_neighbors=4,
        shared_radial_hidden=True, fuse_basis=True, radial_bf16=True,
        pallas_interpret=True)
    params = mod.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                      return_type=1)['params']

    def loss(c):
        out = mod.apply({'params': params}, feats, c, mask=mask,
                        return_type=1)
        return ((c + out - coors) ** 2).sum()

    g = jax.grad(loss)(coors + 0.1)
    assert bool(jnp.isfinite(g).all())
    assert float(jnp.abs(g).max()) > 0
