"""Multi-chip parallelism cookbook: dp + ring(sp) + tp in one train step.

Runs anywhere: on a TPU slice the mesh spans real chips; on CPU simulate
a pod with
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python examples/parallelism.py --cpu

Demonstrates the three mesh axes composing in one jitted update:
  * dp — batch sharding,
  * sp — ring sequence parallelism (`sequence_parallel='ring'`): exact
    kNN neighbor selection under shard_map, no O(N^2) tensor anywhere,
  * tp — real tensor parallelism: radial/attention-head weights
    partitioned by Megatron-style column/row specs.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# runs on the default backend (every chip of the host); --cpu forces the
# CPU, where the XLA_FLAGS of the docstring give it virtual devices
if '--cpu' in sys.argv:
    jax.config.update('jax_platforms', 'cpu')

import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from se3_transformer_tpu import SE3TransformerModule
from se3_transformer_tpu.parallel import make_mesh, shard_params
from se3_transformer_tpu.parallel.sharding import make_sharded_train_step


def main():
    n_dev = len(jax.devices())
    dp = 2 if n_dev % 2 == 0 else 1
    tp = 2 if (n_dev // dp) % 2 == 0 else 1
    mesh = make_mesh(dp=dp, tp=tp)  # sp gets the rest
    print('mesh:', dict(zip(mesh.axis_names, mesh.devices.shape)))

    module = SE3TransformerModule(
        dim=16, depth=2, attend_self=True, num_neighbors=8, num_degrees=3,
        output_degrees=2, heads=4, dim_head=4,
        sequence_parallel='ring', mesh=mesh)

    rng = np.random.RandomState(0)
    b, n = max(2, dp), 128
    feats = jnp.asarray(rng.normal(size=(b, n, 16)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(b, n, 3)) * 3, jnp.float32)
    mask = jnp.ones((b, n), bool)

    params = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coors, mask=mask,
        return_type=1)['params']
    params = shard_params(params, mesh)       # tp partitioning
    opt = optax.adam(1e-3)
    opt_state = jax.jit(opt.init)(params)

    def loss_fn(params, batch, key):
        noise = jax.random.normal(key, batch['coors'].shape)
        out = module.apply({'params': params}, batch['feats'],
                           batch['coors'] + noise, mask=batch['mask'],
                           return_type=1)
        return ((out - noise[:, :, None, :]) ** 2).mean(), {}

    step = make_sharded_train_step(loss_fn, opt, mesh=mesh,
                                   tensor_parallel=True)
    batch = {
        'feats': jax.device_put(feats, NamedSharding(mesh, P('dp', 'sp', None))),
        'coors': jax.device_put(coors, NamedSharding(mesh, P('dp', 'sp', None))),
        'mask': jax.device_put(mask, NamedSharding(mesh, P('dp', 'sp'))),
    }
    key = jax.random.PRNGKey(1)
    for i in range(3):
        key, sub = jax.random.split(key)
        params, opt_state, loss, _ = step(params, opt_state, batch, sub)
        print(f'step {i}: loss {float(loss):.4f}')

    n_tp = sum(1 for _, l in jax.tree_util.tree_flatten_with_path(params)[0]
               if 'tp' in str(getattr(l.sharding, 'spec', '')))
    print(f'{n_tp} params remain tp-partitioned after updates')


if __name__ == '__main__':
    main()
