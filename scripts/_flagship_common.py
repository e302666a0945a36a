"""The flagship train step as chip_smoke.py builds it: seeds, denoise
objective, adam(1e-4), donated make_sharded_train_step, on one device
or over a mesh. `benchmark/harness/train.py` builds the same program
from a cell's configuration; the benchmark is what times it.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_flagship_step(fast=True, remat=None, chunks=None, nodes=1024,
                        dim=64, batch=1, mesh=None, **recipe_kwargs):
    """Returns (step, params, opt_state, data, key, module): the
    donated train step and its initial state.

    remat: remat_policy override ('none' forces the policy off);
    chunks: edge_chunks override (0 = unchunked); recipe_kwargs: the
    recipe's own arguments (depth, num_neighbors — a cut-depth run).

    mesh: a parallel.mesh.make_mesh mesh. The same program then runs
    SPMD: params and adam's state tensor-parallel over 'tp'
    (parallel.sharding.composed_state_shardings under the 'tp' rules),
    the batch over 'dp' (parallel.mesh.shard_batch), and the step built
    with make_sharded_train_step(mesh=..., state_shardings=<those
    placements>). Same seeds as the one-device build, so the two are
    comparable loss for loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from se3_transformer_tpu.parallel.mesh import shard_batch
    from se3_transformer_tpu.parallel.sharding import (
        composed_state_shardings, make_sharded_train_step,
    )
    from se3_transformer_tpu.training import recipes
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    enable_compilation_cache()

    name = 'flagship_fast' if fast else 'flagship'
    overrides = dict(output_degrees=2, reduce_dim_out=True, **recipe_kwargs)
    if remat:
        overrides['remat_policy'] = None if remat == 'none' else remat
    if chunks is not None:
        overrides['edge_chunks'] = chunks or None
    module = recipes.RECIPES[name](dim=dim, **overrides)

    rng = np.random.RandomState(0)
    seqs = jnp.asarray(rng.normal(size=(batch, nodes, dim)), jnp.float32)
    coords = jnp.asarray(np.cumsum(
        rng.normal(size=(batch, nodes, 3)), axis=1), jnp.float32)
    coords = coords - coords.mean(axis=1, keepdims=True)
    masks = jnp.ones((batch, nodes), bool)

    def loss_fn(params, data, key):
        noise = jax.random.normal(key, data['coords'].shape,
                                  data['coords'].dtype)
        noised = data['coords'] + noise
        out = module.apply({'params': params}, data['seqs'], noised,
                           mask=data['masks'], return_type=1)
        loss = (((noised + out) - data['coords']) ** 2).sum(-1).mean()
        return loss, dict()

    init_fn = jax.jit(module.init, static_argnames=('return_type',))
    params = init_fn(jax.random.PRNGKey(0), seqs, coords, mask=masks,
                     return_type=1)['params']
    optimizer = optax.adam(1e-4)
    data = dict(seqs=seqs, coords=coords, masks=masks)
    if mesh is None:
        opt_state = optimizer.init(params)
        step = make_sharded_train_step(loss_fn, optimizer)
    else:
        # tp placement for params AND adam's state (scalars like `count`
        # replicated ON the mesh — an eager optimizer.init leaves them on
        # the first device and the jitted step rejects the device mix),
        # and the step's in AND out state shardings pinned to it: left
        # to GSPMD (tensor_parallel=True alone) some leaves come back
        # from the first step under another spec, which a jitted step
        # answers with a silent recompile and an AOT executable with a
        # refusal
        params, opt_state, placed = composed_state_shardings(
            params, optimizer.init(params), mesh, rules='tp')
        data = shard_batch(data, mesh)
        step = make_sharded_train_step(loss_fn, optimizer, mesh=mesh,
                                       state_shardings=placed)
    return step, params, opt_state, data, jax.random.PRNGKey(1), module
