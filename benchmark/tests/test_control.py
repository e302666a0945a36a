"""The controls: the plain reference computed below the precision the
configuration states must come out not correct under the limits its file
carries: with every learned operand rounded to fp8 here, at a size a test run
can hold; PERF.md has the readings of both controls at the cell's own size on
the chip."""
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference, state, train

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = dict(depth=2, num_degrees=4, heads=4, dim_head=8, kv_heads=1,
             output_degrees=2)
DIM, N = 16, 48


def _limits(name):
    return json.load(open(os.path.join(BENCH, 'configs', name)))['correct']


def _weights():
    from harness.train import build_module
    cfg = json.load(open(os.path.join(BENCH, 'configs',
                                      'd4-onehead-train.json')))
    cfg['overrides'].update(dim=DIM, depth=2, heads=4, dim_head=8,
                            num_neighbors=12)
    cfg['model'] = {}
    module = build_module(cfg)
    abstract = jax.eval_shape(
        partial(module.init, return_type=1), jax.random.PRNGKey(0),
        jnp.zeros((1, N, DIM), jnp.float32), jnp.zeros((1, N, 3)),
        mask=jnp.ones((1, N), bool))['params']
    return state.make_fill(abstract)(state.prng_key(2**31 + 5, 0))


def _structure(seed=3):
    rng = np.random.default_rng(seed)
    coors = np.cumsum(rng.normal(size=(N, 3)) * 2.2, axis=0) + 300.0
    return (jnp.asarray(rng.normal(size=(N, DIM)), jnp.float32),
            jnp.asarray(coors, jnp.float32), jnp.ones(N, bool))


def test_training_steps_in_fp8_fail_a_limit():
    theta = _weights()
    feats, coors, mask = _structure()
    limits = _limits('d4-onehead-train.json')
    keys = list(jax.random.split(jax.random.PRNGKey(1), 3))

    def steps(dtype):
        vg = jax.jit(jax.value_and_grad(partial(
            reference.denoise_loss, **MODEL, block=16, dtype=dtype,
            remat=True)))
        th = theta
        m = jax.tree_util.tree_map(jnp.zeros_like, th)
        v = jax.tree_util.tree_map(jnp.zeros_like, th)
        out = dict(losses=[])
        for t, key in enumerate(keys, start=1):
            noised = coors + jax.random.normal(key, coors.shape)
            geom = reference.geometry(noised, mask, 12, 4)
            loss, g = vg(th, feats, noised, coors, geom)
            out['losses'].append(float(loss))
            if t == 1:
                out['grad'] = state.leaf_norms(g)
                out['grad_tree'] = [np.asarray(a) for a in
                                    jax.tree_util.tree_leaves(g)]
            th, m, v = reference.adam_update(th, g, m, v, float(t))
        out['delta'] = state.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, th, theta))
        return out

    ref, ctl = steps(jnp.float32), steps(jnp.float8_e4m3fn)
    assert train.compare(ref, ref, limits).ok      # the reference passes
    assert not train.compare(ctl, ref, limits).ok  # one precision below fails
