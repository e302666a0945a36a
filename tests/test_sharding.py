"""Multi-device SPMD tests on the simulated 8-device CPU mesh."""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.parallel import make_mesh, shard_batch
from se3_transformer_tpu.training import (
    DenoiseConfig, DenoiseTrainer, synthetic_protein_batch,
)


def test_mesh_factorization():
    assert len(jax.devices()) == 8, 'conftest must provide 8 CPU devices'
    mesh = make_mesh()
    assert mesh.devices.size == 8
    assert mesh.axis_names == ('dp', 'sp', 'tp')

    mesh2 = make_mesh(dp=4, sp=2, tp=1)
    assert mesh2.devices.shape == (4, 2, 1)


def test_sharded_train_step_matches_single_device():
    cfg = DenoiseConfig(num_nodes=24, batch_size=4, num_degrees=2,
                        max_sparse_neighbors=4, seed=3)
    batch = synthetic_protein_batch(cfg, np.random.RandomState(0))

    single = DenoiseTrainer(cfg)
    loss_single = float(single.train_step(batch))

    mesh = make_mesh(dp=4, sp=2, tp=1)
    sharded = DenoiseTrainer(cfg, mesh=mesh)
    loss_sharded = float(sharded.train_step(batch))

    assert np.isfinite(loss_single) and np.isfinite(loss_sharded)
    assert abs(loss_single - loss_sharded) < 1e-3 * max(1.0, abs(loss_single))

    # params after one step agree too (same rng path, same data)
    flat1 = jax.tree_util.tree_leaves(single.params)
    flat2 = jax.tree_util.tree_leaves(sharded.params)
    for a, b in zip(flat1, flat2):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_graft_entry_dryrun():
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    import __graft_entry__ as g
    g.dryrun_multichip(8)


def test_shard_batch_placement():
    mesh = make_mesh(dp=2, sp=2, tp=2)
    batch = dict(feats=jnp.zeros((4, 16)), coors=jnp.zeros((4, 16, 3)),
                 mask=jnp.ones((4, 16), bool))
    placed = shard_batch(batch, mesh)
    for v in placed.values():
        assert len(v.sharding.device_set) in (4, 8)


def test_pod_mesh_cpu_fallback():
    from se3_transformer_tpu.parallel import distributed
    assert distributed.initialize() is False  # single host: no-op
    mesh = distributed.pod_mesh(dp=2, sp=2, tp=2)
    assert mesh.devices.size == 8
    assert mesh.axis_names == ('dp', 'sp', 'tp')


def test_shard_batch_warns_on_replication_fallback():
    import warnings
    mesh = make_mesh(dp=4, sp=2, tp=1)
    batch = dict(feats=jnp.zeros((3, 16)))  # 3 % dp=4 != 0
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        shard_batch(batch, mesh)
    assert any('redundant work' in str(x.message) for x in w)

    # clean divisions stay silent
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        shard_batch(dict(feats=jnp.zeros((4, 16))), mesh)
    assert not w


def test_tensor_parallel_params_partitioned_and_match_replicated():
    """tp is real: radial w3 / attention-head weights are actually
    partitioned over the tp axis, stay partitioned through an update, and
    the numerics match the replicated path."""
    from se3_transformer_tpu.parallel import param_partition_specs, shard_params

    cfg = DenoiseConfig(num_nodes=24, batch_size=2, num_degrees=2,
                        max_sparse_neighbors=4, seed=3)
    batch = synthetic_protein_batch(cfg, np.random.RandomState(0))

    mesh_r = make_mesh(dp=2, sp=2, tp=2)
    repl = DenoiseTrainer(cfg, mesh=mesh_r)
    loss_repl = float(repl.train_step(batch))

    cfg_tp = dataclasses.replace(cfg, tensor_parallel=True)
    tp = DenoiseTrainer(cfg_tp, mesh=mesh_r)
    loss_tp = float(tp.train_step(batch))

    # numerics agree with the replicated path
    assert np.isfinite(loss_tp)
    assert abs(loss_repl - loss_tp) < 1e-4 * max(1.0, abs(loss_repl))
    for a, b in zip(jax.tree_util.tree_leaves(repl.params),
                    jax.tree_util.tree_leaves(tp.params)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    # params are ACTUALLY partitioned (not cosmetic), before and after
    # the update
    n_sharded = 0
    flat_p = jax.tree_util.tree_flatten_with_path(tp.params)[0]
    for path, leaf in flat_p:
        spec = leaf.sharding.spec if hasattr(leaf.sharding, 'spec') else None
        if spec and 'tp' in [s for s in spec if isinstance(s, str)]:
            n_sharded += 1
            ax = list(spec).index('tp')
            # each tp shard holds 1/tp of the axis
            shard_shapes = {s.data.shape for s in leaf.addressable_shards}
            assert all(sh[ax] == leaf.shape[ax] // 2 for sh in shard_shapes)
    assert n_sharded >= 4, f'only {n_sharded} params tp-sharded'


def test_combined_ring_tp_dp_train_step():
    """3D parallelism in one step: dp-sharded batch, ring (sp) neighbor
    selection inside the traced forward, tp-partitioned params — all in a
    single jitted update with finite loss and params still partitioned.

    Regression pin for the composed route: the old shard_params +
    tensor_parallel=True wiring died in jax 0.4.37's GSPMD donation
    aliasing (INTERNAL: unsupported aliasing) as soon as tp was live
    next to dp; `composed_state_shardings` places params AND opt state
    (scalars included) and repins the step with both placements as
    in/out shardings, which is the only configuration that compiles AND
    runs. Two steps, because donation bugs often only bite on the
    second call (the first consumes the originally-placed buffers)."""
    import optax
    from se3_transformer_tpu import SE3TransformerModule
    from se3_transformer_tpu.parallel.sharding import (
        composed_state_shardings, make_sharded_train_step)
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(dp=2, sp=2, tp=2)
    module = SE3TransformerModule(dim=8, depth=1, attend_self=True,
                                  num_neighbors=4, num_degrees=2,
                                  output_degrees=2, heads=2, dim_head=4,
                                  sequence_parallel='ring', mesh=mesh)
    rng = np.random.RandomState(0)
    b, n = 2, 32
    feats = jnp.asarray(rng.normal(size=(b, n, 8)), np.float32)
    coors = jnp.asarray(rng.normal(size=(b, n, 3)), np.float32)
    mask = jnp.ones((b, n), bool)

    params = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coors, mask=mask,
        return_type=1)['params']
    opt = optax.adam(1e-3)
    params, opt_state, shardings = composed_state_shardings(
        params, opt.init(params), mesh)

    def loss_fn(params, batch, key):
        noise = jax.random.normal(key, batch['coors'].shape)
        out = module.apply({'params': params}, batch['feats'],
                           batch['coors'] + noise, mask=batch['mask'],
                           return_type=1)
        # out is [b, n, c, 3] (no reduce_dim_out); broadcast the target
        return ((out - noise[:, :, None, :]) ** 2).mean(), {}

    step = make_sharded_train_step(loss_fn, opt, mesh=mesh,
                                   state_shardings=shardings)
    batch = {
        'feats': jax.device_put(feats, NamedSharding(mesh, P('dp', 'sp', None))),
        'coors': jax.device_put(coors, NamedSharding(mesh, P('dp', 'sp', None))),
        'mask': jax.device_put(mask, NamedSharding(mesh, P('dp', 'sp'))),
    }
    for i in range(2):  # donation rebinds state each call
        params, opt_state, loss, _ = step(params, opt_state, batch,
                                          jax.random.PRNGKey(1 + i))
        assert np.isfinite(float(loss)), f'non-finite loss at step {i}'

    # tp partitioning survived the updates
    n_sharded = sum(
        1 for _, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        if 'tp' in str(getattr(leaf.sharding, 'spec', '')))
    assert n_sharded >= 4, f'only {n_sharded} params tp-sharded after step'


def test_composed_mesh_step_matches_dp_only():
    """Fast tier-1 sibling of the combined ring/tp/dp step: on the full
    2x2x2 mesh the composed route (params/opt state over (dp, tp) with
    pinned in/out shardings) must produce the SAME update as a plain
    dp-only data-parallel step — placement is an execution detail, not
    math. Small model, no ring, one step: this is the cheap canary that
    keeps the composed route compiling in every tier-1 run."""
    import optax
    from se3_transformer_tpu import SE3TransformerModule
    from se3_transformer_tpu.parallel.sharding import (
        composed_state_shardings, make_sharded_train_step)
    from jax.sharding import NamedSharding, PartitionSpec as P

    module = SE3TransformerModule(dim=8, depth=1, attend_self=True,
                                  num_neighbors=4, num_degrees=2,
                                  output_degrees=2, heads=2, dim_head=4)
    rng = np.random.RandomState(0)
    b, n = 2, 16
    feats = jnp.asarray(rng.normal(size=(b, n, 8)), np.float32)
    coors = jnp.asarray(rng.normal(size=(b, n, 3)), np.float32)
    mask = jnp.ones((b, n), bool)

    params0 = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coors, mask=mask,
        return_type=1)['params']
    opt = optax.adam(1e-3)
    # noise rides in the batch, NOT drawn inside the step: on this jax,
    # jax.random.normal traced under pjit yields sharding-DEPENDENT
    # values (threefry_partitionable=False), so in-step rng would make
    # the two arms denoise different targets and parity meaningless
    noise0 = jax.random.normal(jax.random.PRNGKey(1), coors.shape)

    def loss_fn(params, batch, key):
        del key
        noise = batch['noise']
        out = module.apply({'params': params}, batch['feats'],
                           batch['coors'] + noise, mask=batch['mask'],
                           return_type=1)
        return ((out - noise[:, :, None, :]) ** 2).mean(), {}

    def run(mesh, composed):
        # each arm gets its own buffers: the steps donate their state,
        # and a device_put onto a replicated spec can ALIAS the source
        # buffer — donating the placed tree would delete params0's
        # leaves out from under the other arm
        params = jax.tree_util.tree_map(jnp.array, params0)
        if composed:
            params, opt_state, shardings = composed_state_shardings(
                params, opt.init(params), mesh)
            step = make_sharded_train_step(loss_fn, opt, mesh=mesh,
                                           state_shardings=shardings)
        else:
            opt_state = jax.jit(opt.init)(params)
            step = make_sharded_train_step(loss_fn, opt, mesh=mesh)
        node = P('dp', 'sp', None) if composed else P('dp', None, None)
        flat = P('dp', 'sp') if composed else P('dp', None)
        batch = {
            'feats': jax.device_put(feats, NamedSharding(mesh, node)),
            'coors': jax.device_put(coors, NamedSharding(mesh, node)),
            'noise': jax.device_put(noise0, NamedSharding(mesh, node)),
            'mask': jax.device_put(mask, NamedSharding(mesh, flat)),
        }
        params, _, loss, _ = step(params, opt_state, batch,
                                  jax.random.PRNGKey(1))
        return float(loss), params

    loss_c, params_c = run(make_mesh(dp=2, sp=2, tp=2), composed=True)
    loss_d, params_d = run(make_mesh(jax.devices()[:2], dp=2, sp=1, tp=1),
                           composed=False)

    assert np.isfinite(loss_c)
    assert abs(loss_c - loss_d) < 1e-5 * max(1.0, abs(loss_d))
    for a, b_ in zip(jax.tree_util.tree_leaves(params_c),
                     jax.tree_util.tree_leaves(params_d)):
        assert np.allclose(np.asarray(a), np.asarray(b_), atol=1e-5)

    # the composed arm really partitioned over tp (not cosmetic)
    n_tp = sum(
        1 for _, leaf in jax.tree_util.tree_flatten_with_path(params_c)[0]
        if 'tp' in str(getattr(leaf.sharding, 'spec', '')))
    assert n_tp >= 4, f'only {n_tp} params tp-sharded'


def test_tensor_parallel_shared_radial_group_params():
    """The shared-radial group layout names its radial weights
    w3_{d_in}_{d_out}; the tp rules must still shard them over the output
    channel axis (regression: the rename silently fell through to P())."""
    from se3_transformer_tpu.parallel import param_partition_specs
    from se3_transformer_tpu import SE3TransformerModule

    mesh = make_mesh(dp=2, sp=2, tp=2)
    m = SE3TransformerModule(dim=8, depth=1, attend_self=True,
                             num_neighbors=4, num_degrees=2,
                             output_degrees=2, shared_radial_hidden=True)
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.normal(size=(1, 16, 8)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, 16, 3)), jnp.float32)
    mask = jnp.ones((1, 16), bool)
    params = m.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                    return_type=1)['params']
    specs = param_partition_specs(params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    hits = [(jax.tree_util.keystr(path), spec) for path, spec in flat
            if 'w3_' in jax.tree_util.keystr(path)]
    assert hits, 'no group-layout radial weights found'
    sharded = [s for _, s in hits if 'tp' in str(s)]
    assert sharded, f'w3_* leaves all replicated: {hits[:4]}'


def test_shard_host_local_batch_single_process():
    """Single-process case: the per-host batch IS the global batch; output
    arrays are globally shaped, sharded by the canonical specs, and equal
    to the plain shard_batch placement."""
    from se3_transformer_tpu.parallel import distributed, shard_batch

    mesh = make_mesh(dp=2, sp=4)
    rng = np.random.RandomState(0)
    batch = dict(
        feats=rng.randint(0, 10, (2, 16)),
        coors=rng.normal(size=(2, 16, 3)).astype(np.float32),
        mask=np.ones((2, 16), bool),
    )
    global_arrays = distributed.shard_host_local_batch(batch, mesh)
    ref = shard_batch({k: jnp.asarray(v) for k, v in batch.items()}, mesh)
    for k in batch:
        assert global_arrays[k].shape == batch[k].shape
        assert str(global_arrays[k].sharding.spec) == str(ref[k].sharding.spec), k
        assert np.allclose(np.asarray(global_arrays[k]), np.asarray(ref[k]))


def test_pallas_kernels_partition_under_pjit():
    """The fused pairwise kernels carry custom_partitioning rules: the
    edge axis (and the output-channel axis, under tp) partitions with NO
    all-gather of the edge tensors; dW3's edge-partial sums are psum'd in
    the partition body. The rules and partition callbacks exercised here
    on the CPU mesh are exactly the multi-chip mechanism on a real pod —
    only the inner kernel body differs (interpret vs Mosaic)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv, fused_pairwise_conv_bwd,
        fused_pairwise_conv_bxf,
    )

    mesh = make_mesh(sp=8)
    E, mid, IF, O, Pp, C, Q, F = 256, 16, 12, 8, 5, 4, 3, 3
    rng = np.random.RandomState(0)
    h0 = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
    w30 = jnp.asarray(rng.normal(size=(mid, IF, O)), jnp.float32)
    v20 = jnp.asarray(rng.normal(size=(E, Pp, IF)), jnp.float32)
    g0 = jnp.asarray(rng.normal(size=(E, Pp, O)), jnp.float32)

    def rel(a, b):
        return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))

    # forward, edge-sharded
    ref = fused_pairwise_conv(h0, w30, v20, interpret=True)
    sharded = [jax.device_put(a, NamedSharding(mesh, s)) for a, s in
               [(h0, P('sp')), (w30, P()), (v20, P('sp'))]]
    fn = jax.jit(lambda h, w, v: fused_pairwise_conv(h, w, v,
                                                     interpret=True))
    out = fn(*sharded)
    assert 'sp' in str(out.sharding.spec)
    hlo = fn.lower(*sharded).compile().as_text()
    assert 'all-gather' not in hlo
    assert rel(out, ref) < 1e-5

    # forward, tensor-parallel w3 (o-sharded): output stays o-sharded
    tp_args = [jax.device_put(a, NamedSharding(mesh, s)) for a, s in
               [(h0, P()), (w30, P(None, None, 'sp')), (v20, P())]]
    out_tp = fn(*tp_args)
    assert 'sp' in str(out_tp.sharding.spec)
    assert rel(out_tp, ref) < 1e-5

    # colliding shardings (edge AND output-channel pinned to the same
    # mesh axis): the partition callback drops the o sharding instead of
    # crashing with a local-shape mismatch
    col_args = [jax.device_put(a, NamedSharding(mesh, s)) for a, s in
                [(h0, P('sp')), (w30, P(None, None, 'sp')),
                 (v20, P('sp'))]]
    assert rel(fn(*col_args), ref) < 1e-5

    # backward, edge-sharded: dh/dv2 stay sharded, dw3 is psum'd full
    refs = fused_pairwise_conv_bwd(h0, w30, v20, g0, interpret=True)
    bargs = sharded + [jax.device_put(g0, NamedSharding(mesh, P('sp')))]
    bfn = jax.jit(lambda h, w, v, g: fused_pairwise_conv_bwd(
        h, w, v, g, interpret=True))
    outs = bfn(*bargs)
    assert 'sp' in str(outs[0].sharding.spec)
    assert 'sp' in str(outs[2].sharding.spec)
    hlo_b = bfn.lower(*bargs).compile().as_text()
    assert 'all-gather' not in hlo_b
    assert 'all-reduce' in hlo_b  # the dW3 edge psum
    for a, b in zip(outs, refs):
        assert rel(a, b) < 1e-5

    # basis-fused forward, edge-sharded
    bas0 = jnp.asarray(rng.normal(size=(E, Pp * F * Q)), jnp.float32)
    x0 = jnp.asarray(rng.normal(size=(E, C, Q)), jnp.float32)
    w3b0 = jnp.asarray(rng.normal(size=(mid, C * F, O)), jnp.float32)
    ref2 = fused_pairwise_conv_bxf(h0, w3b0, bas0, x0, (Pp, Q, F),
                                   interpret=True)
    args = [jax.device_put(a, NamedSharding(mesh, s)) for a, s in
            [(h0, P('sp')), (w3b0, P()), (bas0, P('sp')), (x0, P('sp'))]]
    fn2 = jax.jit(lambda h, w, b, x: fused_pairwise_conv_bxf(
        h, w, b, x, (Pp, Q, F), interpret=True))
    out2 = fn2(*args)
    assert 'sp' in str(out2.sharding.spec)
    hlo2 = fn2.lower(*args).compile().as_text()
    assert 'all-gather' not in hlo2
    assert rel(out2, ref2) < 1e-5


def test_fused_attention_partitions_under_pjit():
    """The fused attention kernel's custom_partitioning rules: node axis
    (sequence parallelism) and batch*head axis partition without
    all-gathers; an indivisible leading-axis sharding (shards not
    aligned to kv groups) falls back to replication rather than
    miscomputing; gradients keep their primal shardings with no
    cross-shard reductions."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from se3_transformer_tpu.kernels.pallas_attention import (
        attention_reference, fused_attention,
    )

    B, h, kvh, n, J, D = 2, 4, 2, 64, 9, 16
    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.normal(size=(B * h, n, D)), jnp.float32)
    k0 = jnp.asarray(rng.normal(size=(B * kvh, n, J, D)), jnp.float32)
    v0 = jnp.asarray(rng.normal(size=(B * kvh, n, J, D)), jnp.float32)
    mask0 = jnp.asarray(rng.rand(B, n, J) > 0.3).at[:, :, 0].set(True)
    scale = D ** -0.5
    ref = attention_reference(q0, k0, v0, mask0, scale)

    def rel(a, b):
        return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))

    mesh = make_mesh(sp=8)
    fn = jax.jit(lambda q, k, v, m: fused_attention(q, k, v, m, h, scale,
                                                    True))

    # node-axis (sequence-parallel) sharding
    args_n = [jax.device_put(a, NamedSharding(mesh, s)) for a, s in
              [(q0, P(None, 'sp')), (k0, P(None, 'sp')),
               (v0, P(None, 'sp')), (mask0, P(None, 'sp'))]]
    out = fn(*args_n)
    assert 'sp' in str(out.sharding.spec)
    assert 'all-gather' not in fn.lower(*args_n).compile().as_text()
    assert rel(out, ref) < 1e-5

    # leading-axis shard count (8) does not divide B*kv_h (4): falls back
    # to replication, stays correct
    args_a = [jax.device_put(a, NamedSharding(mesh, s)) for a, s in
              [(q0, P('sp')), (k0, P()), (v0, P()), (mask0, P())]]
    assert rel(fn(*args_a), ref) < 1e-5

    # dp x sp: both axes kept
    mesh2 = Mesh(np.asarray(jax.devices()).reshape(2, 4), ('dp', 'sp'))
    args_d = [jax.device_put(a, NamedSharding(mesh2, s)) for a, s in
              [(q0, P('dp', 'sp')), (k0, P('dp', 'sp')),
               (v0, P('dp', 'sp')), (mask0, P('dp', 'sp'))]]
    out3 = fn(*args_d)
    assert 'dp' in str(out3.sharding.spec) and 'sp' in str(out3.sharding.spec)
    assert rel(out3, ref) < 1e-5

    # gradients through the partitioned backward
    g = jax.grad(lambda q, k, v: (fused_attention(
        q, k, v, mask0, h, scale, True) ** 2).sum(), argnums=(0, 1, 2))
    for a, b in zip(jax.jit(g)(*args_n[:3]), g(q0, k0, v0)):
        assert rel(a, b) < 1e-5


def test_checkpoint_roundtrip_preserves_shardings():
    """Saving tp-partitioned params and restoring with a sharded `like`
    target yields arrays placed with the same NamedShardings (no host
    gather, no silent replication on resume)."""
    import tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from se3_transformer_tpu.training.checkpoint import CheckpointManager

    mesh = make_mesh(dp=2, sp=2, tp=2)
    state = {
        'w3': jax.device_put(jnp.arange(2 * 6 * 4, dtype=jnp.float32)
                             .reshape(2, 6, 4),
                             NamedSharding(mesh, P(None, None, 'tp'))),
        'bias': jax.device_put(jnp.ones((8,), jnp.float32),
                               NamedSharding(mesh, P())),
        'step': np.int64(7),
    }
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(3, state)
        restored = mgr.restore(like=state)
    assert restored['w3'].sharding == state['w3'].sharding
    assert np.allclose(np.asarray(restored['w3']), np.asarray(state['w3']))
    assert np.allclose(np.asarray(restored['bias']),
                       np.asarray(state['bias']))
    assert int(restored['step']) == 7


def test_fsdp_sharded_opt_state_train_and_restore():
    """True-FSDP wiring (ROADMAP item 4's named next step): with
    cfg.fsdp the trainer shards params AND adam's mu/nu dim-0 over dp
    (shard_opt_state — the moments inherit each param's audited spec),
    the step factory pins in/out shardings to those placements (the
    explicit-aliasing route around the jax-0.4.37 GSPMD donation bug),
    and a host-roundtripped checkpoint restores BACK into the shards —
    never replicated 2x param memory until the first step."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(dp=2)
    cfg = DenoiseConfig(num_nodes=24, batch_size=2, num_degrees=2,
                        max_sparse_neighbors=4, use_mesh=True, fsdp=True)
    tr = DenoiseTrainer(cfg, mesh=mesh)
    batch = synthetic_protein_batch(cfg, tr.np_rng)
    tr.init(batch)

    def mu_leaf(state):
        return state[0].mu['conv_in']['pair_0_0']['w3']

    assert mu_leaf(tr.opt_state).sharding.spec == P('dp')
    l1 = float(tr.train_step(batch))
    l2 = float(tr.train_step(batch))
    assert np.isfinite(l1) and np.isfinite(l2)
    # the donated sharded state stays sharded through the update
    assert mu_leaf(tr.opt_state).sharding.spec == P('dp')
    assert tr.params['conv_in']['pair_0_0']['w3'].sharding.spec == \
        P('dp')

    # checkpoint-restore path: host leaves re-place into their shards
    host = jax.tree_util.tree_map(
        np.asarray, (tr.params, tr.opt_state, tr.step_count))
    tr.restore(host)
    assert mu_leaf(tr.opt_state).sharding.spec == P('dp')
    l3 = float(tr.train_step(batch))
    assert np.isfinite(l3)
