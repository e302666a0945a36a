"""Smoke + gradient tests for every tracked benchmark recipe
(BASELINE.json configs), on tiny shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.training.recipes import RECIPES


def _inputs(module, n=12, b=1, seed=0):
    rng = np.random.RandomState(seed)
    coors = jnp.asarray(rng.normal(size=(b, n, 3)), jnp.float32)
    mask = jnp.ones((b, n), bool)
    kwargs = dict(mask=mask)
    if module.num_tokens is not None:
        feats = jnp.asarray(rng.randint(0, module.num_tokens, (b, n)))
    else:
        dim_in = module.dim_in if module.dim_in is not None else module.dim
        feats = jnp.asarray(rng.normal(size=(b, n, dim_in)), jnp.float32)
    if module.attend_sparse_neighbors or module.num_adj_degrees:
        i = np.arange(n)
        adj = np.abs(i[:, None] - i[None, :]) == 1
        kwargs['adj_mat'] = jnp.asarray(adj)
    if module.has_edges:
        kwargs['edges'] = jnp.asarray(rng.randint(0, 4, (b, n, n)))
    return feats, coors, kwargs


# the SE(3) recipes (features + coordinates in, fibers out); the token
# decoders' recipes take tokens: tests/test_token_decoder.py,
# tests/test_hybrid_decoder.py, tests/test_lfm2_decoder.py,
# tests/test_block_diffusion.py, tests/test_sliding_window.py,
# tests/test_looped_decoder.py
@pytest.mark.parametrize('name', sorted(
    set(RECIPES) - {'token_decoder', 'hybrid_decoder', 'lfm2_decoder',
                    'sdar_decoder', 'smallthinker_decoder',
                    'ouro_decoder'}))
def test_recipe_forward_and_grad(name):
    builder = RECIPES[name]
    module = builder(dim=16) if name != 'toy_denoise' else builder()
    if name in ('egnn_stress', 'flagship', 'flagship_fast'):
        module = RECIPES[name](dim=8, depth=2)  # tiny depth for CI speed

    feats, coors, kwargs = _inputs(module)
    rt = 1 if (module.use_egnn or module.output_degrees > 1) else 0
    params = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coors, return_type=rt, **kwargs)[
            'params']

    def loss(p, c):
        out = module.apply({'params': p}, feats, c, return_type=rt, **kwargs)
        return (out ** 2).sum()

    val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
        params, coors)
    assert np.isfinite(float(val))
    g_coors = grads[1]
    assert np.isfinite(np.asarray(g_coors)).all()
    if getattr(module, 'differentiable_coors', False):
        assert np.abs(np.asarray(g_coors)).max() > 0
