"""Max-width-per-chip-count table: flagship memory vs (dim, devices).

VERDICT r3 next #4: BASELINE.md's tracked flagship label is
SE3Transformer(dim=512, depth=6, num_degrees=4) at 1024 nodes, but
nothing had ever instantiated dim>=128 — the multi-chip memory story was
untested theory. This harness compiles the FULL sharded training step
(sp-sharded nodes + tp-sharded radial weights + edge_chunks, the same
program dryrun_multichip validates) at the label shape n=1024/k=32 over
an N-virtual-CPU-device mesh and records XLA's per-shard memory analysis
(SPMD emits one per-device program, so temp+argument sizes ARE the
per-chip footprint estimate). Optionally executes one step at a reduced
node count to prove the label-width program actually runs end to end.

The numbers are XLA:CPU SPMD estimates — layouts/fusion differ from TPU
(measured on-chip: dim=64 needs the remat recipe to fit 16 GB, which
matches this harness's estimate within ~20%) — so the table is stated
as the scaling story; the dim=64 single-chip point is the benchmark's
`hbm_reserved_gib.train` in `d4_onehead_train` (PERF_LEDGER.jsonl).

Usage (fresh process per device count — the virtual device count is
fixed at backend init):
    python scripts/width_table.py --devices 8 --dims 512 [--exec-dim 512]
    python scripts/width_table.py --devices 1 --dims 64 128
    python scripts/width_table.py --devices 8 --weak-scaling --ab \
        [--metrics COMM.jsonl]
    python scripts/width_table.py --devices 8 --mesh-sweep \
        [--points 2,2,2 4,1,2]
Writes crash-safe JSONL to WIDTH_TABLE.jsonl (append). --weak-scaling
rows carry a `comm` payload (collective classes/bytes + the full-width
all-gather scan of the traced HLO); --ab measures the overlapped+sparse
vs serialized+dense comm arms in one process.
--mesh-sweep instead walks every (dp, sp, tp) mesh point covering the
device count through the composed-parallelism route (params+opt state
over (dp, tp), ring sp when sp>1, donation pinned through explicit
in/out shardings) and banks schema'd `mesh_sweep` records — per-axis
collective split + per-shard memory — to MESH_SWEEP.jsonl for
scripts/perf_gate.py's per-axis budgets.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def _setup(n_devices: int):
    flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + f' --xla_force_host_platform_device_count={n_devices}'
        ).strip()
    import jax
    jax.config.update('jax_platforms', 'cpu')
    return jax


def _flagship_step(jax, mesh, dim, n, k, tp, compile_only=True):
    """Lower + compile the flagship training program (flagship_fast
    recipe, denoise objective, adam) over the mesh; returns (compiled,
    compile_s, example_args)."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from se3_transformer_tpu.parallel.sharding import (
        make_sharded_train_step, shard_params,
    )
    from se3_transformer_tpu.training import recipes

    module = recipes.RECIPES['flagship_fast'](
        dim=dim, num_neighbors=k, output_degrees=2, reduce_dim_out=True)

    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coords = jnp.asarray(
        np.cumsum(rng.normal(size=(1, n, 3)), axis=1), jnp.float32)
    masks = jnp.ones((1, n), bool)

    def loss_fn(params, data, key):
        noise = jax.random.normal(key, data['coords'].shape,
                                  data['coords'].dtype)
        noised = data['coords'] + noise
        out = module.apply({'params': params}, data['seqs'], noised,
                           mask=data['masks'], return_type=1)
        loss = (((noised + out) - data['coords']) ** 2).sum(-1).mean()
        return loss, dict()

    # init with abstract eval only — a real init at dim=512 would
    # EXECUTE the forward on CPU (minutes to hours); eval_shape gives the
    # param tree structure for lowering, and zeros fill it for execution
    init_shapes = jax.eval_shape(
        lambda key: module.init(key, feats, coords, mask=masks,
                                return_type=1),
        jax.random.PRNGKey(0))['params']
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), init_shapes)
    params = shard_params(params, mesh)
    optimizer = optax.adam(1e-4)
    opt_state = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(optimizer.init, params))
    opt_state = jax.tree_util.tree_map(
        lambda v: jax.device_put(v, NamedSharding(mesh, P())), opt_state)

    step = make_sharded_train_step(loss_fn, optimizer, mesh=mesh,
                                   donate=False, tensor_parallel=(tp > 1))

    node_spec = P(None, 'sp', None)
    data = dict(
        seqs=jax.device_put(feats, NamedSharding(mesh, node_spec)),
        coords=jax.device_put(coords, NamedSharding(mesh, node_spec)),
        masks=jax.device_put(masks, NamedSharding(mesh, P(None, 'sp'))))
    key = jax.random.PRNGKey(1)

    t0 = time.time()
    compiled = step.lower(params, opt_state, data, key).compile()
    compile_s = time.time() - t0
    return compiled, compile_s, (params, opt_state, data, key)


def measure_point(jax, mesh, dim, n, k, tp, execute=False):
    compiled, compile_s, args = _flagship_step(jax, mesh, dim, n, k, tp)
    rec = dict(dim=dim, n=n, k=k, compile_s=round(compile_s, 1))
    try:
        # the schema'd cost ledger (observability.costs): flops + the
        # arg/output/temp split scripts/perf_gate.py budgets; the
        # legacy row fields below derive from THE SAME ledger (one
        # memory_analysis call, one representation — they can't drift)
        from se3_transformer_tpu.observability.costs import cost_payload
        rec['cost'] = cost_payload(compiled,
                                   label=f'width,dim={dim},n={n},k={k}')
        mem = rec['cost']['memory']
        for name, legacy in (('temp_bytes', 'temp_size_mb'),
                             ('argument_bytes', 'argument_size_mb'),
                             ('output_bytes', 'output_size_mb'),
                             ('alias_bytes', 'alias_size_mb'),
                             ('generated_code_bytes',
                              'generated_code_size_mb')):
            if name in mem:
                rec[legacy] = round(mem[name] / 2**20, 1)
        # per-shard footprint estimate: live temporaries + resident
        # arguments (params+opt state+batch shard). alias'd buffers are
        # counted inside argument size already.
        rec['per_shard_total_gb'] = round(
            (mem['temp_bytes'] + mem['argument_bytes']) / 2**30, 3)
    except Exception as e:  # noqa: BLE001 - accounting is best-effort
        rec['memory_analysis_error'] = f'{type(e).__name__}: {e}'[:200]
    if execute:
        t0 = time.time()
        params, opt_state, data, key = args
        out = compiled(params, opt_state, data, key)
        jax.block_until_ready(out[2])
        rec['exec_step_s'] = round(time.time() - t0, 1)
        rec['loss_finite'] = bool(jax.numpy.isfinite(out[2]))
    return rec


def weak_scaling_point(jax, n_devices, per_device_nodes, dim, k, steps=3,
                       overlap=True, exchange=True):
    """One weak-scaling row (VERDICT r4 next #8): sp=n_devices ring-path
    training step at FIXED per-device node count, executed for wall-clock
    + XLA per-shard memory. All virtual devices share this host's cores,
    so ideal weak scaling here is wall-clock LINEAR in total nodes (not
    flat); the rows record step_s only — the overhead factor
    step_s / (sp * step_s_at_sp1) is derived downstream from the sp=1
    row, and per-shard memory should stay
    ~flat (the actual weak-scaling claim).

    overlap/exchange are the PR-5 comm knobs (parallel/ring.py,
    parallel/exchange.py); `--ab` measures both settings of the pair in
    one process so the A/B shares the host. Every row carries a `comm`
    payload — collective classes + bytes and the full-width-all-gather
    scan of THIS row's traced HLO (parallel.exchange.comm_payload)."""
    import time as _time

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from se3_transformer_tpu.parallel.exchange import comm_payload
    from se3_transformer_tpu.parallel.mesh import make_mesh
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training import recipes

    n = per_device_nodes * n_devices
    mesh = make_mesh(jax.devices()[:n_devices], dp=1, tp=1)
    module = recipes.RECIPES['flagship_fast'](
        dim=dim, num_neighbors=k, output_degrees=2, reduce_dim_out=True,
        depth=1, sequence_parallel='ring', mesh=mesh,
        ring_overlap=overlap, ring_exchange=exchange)

    rng = np.random.RandomState(0)
    node_spec = P(None, 'sp', None)
    feats = jax.device_put(
        jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32),
        NamedSharding(mesh, node_spec))
    coords = jax.device_put(
        jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                    jnp.float32), NamedSharding(mesh, node_spec))
    masks = jax.device_put(jnp.ones((1, n), bool),
                           NamedSharding(mesh, P(None, 'sp')))

    def loss_fn(params, data, key):
        noise = jax.random.normal(key, data['coords'].shape,
                                  data['coords'].dtype)
        noised = data['coords'] + noise
        out = module.apply({'params': params}, data['seqs'], noised,
                           mask=data['masks'], return_type=1)
        return (((noised + out) - data['coords']) ** 2).sum(-1).mean(), {}

    params = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coords, mask=masks,
        return_type=1)['params']
    optimizer = optax.adam(1e-4)
    opt_state = optimizer.init(params)
    step = make_sharded_train_step(loss_fn, optimizer, donate=False)
    data = dict(seqs=feats, coords=coords, masks=masks)
    key = jax.random.PRNGKey(1)

    t0 = _time.time()
    compiled = step.lower(params, opt_state, data, key).compile()
    compile_s = _time.time() - t0
    rec = dict(weak_scaling=True, devices=n_devices, sp=n_devices,
               per_device_nodes=per_device_nodes, n=n, dim=dim, k=k,
               depth=1, compile_s=round(compile_s, 1),
               host_cpus=os.cpu_count(), backend='cpu-spmd',
               overlap=overlap, exchange=exchange)
    hlo_text = None
    try:
        hlo_text = compiled.as_text()
        rec['comm'] = comm_payload(
            hlo_text, sp=n_devices, ring_steps=n_devices,
            overlap=overlap, exchange=exchange, full_width_dim=n)
    except Exception as e:  # noqa: BLE001 - accounting is best-effort
        rec['comm_error'] = f'{type(e).__name__}: {e}'[:200]
    try:
        # one ledger, one memory_analysis call; the legacy per-shard
        # fields derive from it so row and cost record cannot disagree
        from se3_transformer_tpu.observability.costs import cost_payload
        rec['cost'] = cost_payload(
            compiled, hlo_text=hlo_text,
            label=f'weak_scaling,sp={n_devices},pdn={per_device_nodes},'
                  f'overlap={overlap},exchange={exchange}')
        mem = rec['cost']['memory']
        rec['per_shard_temp_mb'] = round(mem['temp_bytes'] / 2**20, 1)
        rec['per_shard_total_gb'] = round(
            (mem['temp_bytes'] + mem['argument_bytes']) / 2**30, 3)
    except Exception as e:  # noqa: BLE001 - memory analysis best-effort
        rec['memory_analysis_error'] = f'{type(e).__name__}: {e}'[:200]
    out = compiled(params, opt_state, data, key)  # warmup
    jax.block_until_ready(out[2])
    t0 = _time.time()
    for _ in range(steps):
        key, sub = jax.random.split(key)
        out = compiled(params, opt_state, data, sub)
    jax.block_until_ready(out[2])
    rec['step_s'] = round((_time.time() - t0) / steps, 3)
    rec['loss_finite'] = bool(jax.numpy.isfinite(out[2]))
    return rec


def mesh_sweep_point(jax, dp, sp, tp, per_device_nodes, dim, k, steps=3):
    """One composed-parallelism row (ROADMAP item 4): the dp x sp x tp
    train step at FIXED per-device work (batch dp, nodes
    per_device_nodes * sp), built through the explicit-aliasing route
    (parallel.sharding.composed_state_shardings: params + opt state
    over (dp, tp), step in/out shardings pinned, donation ON — the
    exact configuration the jax-0.4.37 GSPMD donation bug kills
    without the pin) and EXECUTED for wall-clock. The row's `comm`
    block carries the per-mesh-axis collective split
    (parallel.exchange.attribute_collective_axes) the per-axis budgets
    in PERF_BUDGETS.json gate on, plus the all-gather-free proof scan;
    `cost` is the usual ledger, and per_shard_total_gb the XLA
    per-shard memory estimate."""
    import time as _time

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from se3_transformer_tpu.parallel.exchange import comm_payload
    from se3_transformer_tpu.parallel.mesh import make_mesh, mesh_shape_dict
    from se3_transformer_tpu.parallel.sharding import (
        composed_state_shardings, make_sharded_train_step,
    )
    from se3_transformer_tpu.training import recipes

    n_devices = dp * sp * tp
    b, n = dp, per_device_nodes * sp
    mesh = make_mesh(jax.devices()[:n_devices], dp=dp, sp=sp, tp=tp)
    ring = dict(sequence_parallel='ring', ring_overlap=True,
                ring_exchange=True) if sp > 1 else {}
    module = recipes.RECIPES['flagship_fast'](
        dim=dim, num_neighbors=k, output_degrees=2, reduce_dim_out=True,
        depth=1, mesh=mesh, **ring)

    rng = np.random.RandomState(0)
    node_spec = P('dp', 'sp', None)
    feats = jax.device_put(
        jnp.asarray(rng.normal(size=(b, n, dim)), jnp.float32),
        NamedSharding(mesh, node_spec))
    coords = jax.device_put(
        jnp.asarray(np.cumsum(rng.normal(size=(b, n, 3)), axis=1),
                    jnp.float32), NamedSharding(mesh, node_spec))
    masks = jax.device_put(jnp.ones((b, n), bool),
                           NamedSharding(mesh, P('dp', 'sp')))

    def loss_fn(params, data, key):
        noise = jax.random.normal(key, data['coords'].shape,
                                  data['coords'].dtype)
        noised = data['coords'] + noise
        out = module.apply({'params': params}, data['seqs'], noised,
                           mask=data['masks'], return_type=1)
        return (((noised + out) - data['coords']) ** 2).sum(-1).mean(), {}

    params = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coords, mask=masks,
        return_type=1)['params']
    optimizer = optax.adam(1e-4)
    params, opt_state, shardings = composed_state_shardings(
        params, optimizer.init(params), mesh)
    step = make_sharded_train_step(loss_fn, optimizer, mesh=mesh,
                                   state_shardings=shardings)
    data = dict(seqs=feats, coords=coords, masks=masks)
    key = jax.random.PRNGKey(1)

    t0 = _time.time()
    compiled = step.lower(params, opt_state, data, key).compile()
    compile_s = _time.time() - t0
    rec = dict(dp=dp, sp=sp, tp=tp, devices=n_devices, b=b, n=n,
               per_device_nodes=per_device_nodes, dim=dim, k=k, depth=1,
               compile_s=round(compile_s, 1), host_cpus=os.cpu_count(),
               backend='cpu-spmd')
    hlo_text = compiled.as_text()
    rec['comm'] = comm_payload(
        hlo_text, sp=sp, ring_steps=sp, overlap=sp > 1, exchange=sp > 1,
        full_width_dim=n, mesh_shape=mesh_shape_dict(mesh))
    try:
        from se3_transformer_tpu.observability.costs import cost_payload
        rec['cost'] = cost_payload(
            compiled, hlo_text=hlo_text,
            label=f'mesh_sweep,dp={dp},sp={sp},tp={tp},'
                  f'pdn={per_device_nodes}')
        mem = rec['cost']['memory']
        rec['per_shard_total_gb'] = round(
            (mem['temp_bytes'] + mem['argument_bytes']) / 2**30, 3)
    except Exception as e:  # noqa: BLE001 - memory analysis best-effort
        rec['memory_analysis_error'] = f'{type(e).__name__}: {e}'[:200]
        rec['per_shard_total_gb'] = 0.0   # schema'd field; error above
        #                                   flags the degenerate value
    # donation is ON (the aliasing route under test) — rebind the
    # donated state every call or the second step reads invalidated
    # buffers
    params, opt_state, loss, _ = compiled(params, opt_state, data, key)
    jax.block_until_ready(loss)                               # warmup
    t0 = _time.time()
    for _ in range(steps):
        key, sub = jax.random.split(key)
        params, opt_state, loss, _ = compiled(params, opt_state, data,
                                              sub)
    jax.block_until_ready(loss)
    rec['step_s'] = round((_time.time() - t0) / steps, 3)
    rec['loss_finite'] = bool(jax.numpy.isfinite(loss))
    return rec


def _write_comm_stream(path, recs):
    """Schema-valid telemetry stream for the weak-scaling run: run_meta +
    one `comm` AND one `cost` record per measured arm (observability
    kinds 'comm'/'cost' — gated via obs_report --require comm,cost)."""
    from se3_transformer_tpu.observability.report import write_record_stream

    bodies = []
    for rec in recs:
        if 'comm' in rec:
            bodies.append(dict(rec['comm'], kind='comm',
                               step_s=rec.get('step_s'),
                               label=rec.get('arm')))
        if 'cost' in rec:
            bodies.append(dict(rec['cost'], kind='cost'))
    write_record_stream(path, f'weak_scaling_{os.getpid()}', bodies)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--devices', type=int, required=True)
    ap.add_argument('--dims', type=int, nargs='*', default=[],
                    help='label-shape (n=1024) compile+memory points. '
                         'CAUTION: XLA:CPU memory analysis measured ~4x '
                         'over the real TPU footprint (dim=64/8dev said '
                         '32.6 GB/shard vs <16 GB measured on one whole '
                         'chip) — treat as an upper bound only')
    ap.add_argument('--nodes', type=int, default=1024)
    ap.add_argument('--k', type=int, default=32)
    ap.add_argument('--dp', type=int, default=1)
    ap.add_argument('--tp', type=int, default=None,
                    help='tp axis size (default 2 when devices%%2==0)')
    ap.add_argument('--exec-dim', type=int, default=None,
                    help='also EXECUTE one step at this dim (reduced '
                         'nodes, see --exec-nodes)')
    ap.add_argument('--exec-nodes', type=int, default=128)
    ap.add_argument('--out', default=os.path.join(REPO, 'WIDTH_TABLE.jsonl'))
    ap.add_argument('--weak-scaling', action='store_true',
                    help='one weak-scaling row: sp=devices ring path at '
                         'fixed per-device nodes, executed (fresh process '
                         'per device count)')
    ap.add_argument('--mesh-sweep', action='store_true',
                    help='composed dp x sp x tp sweep: every (dp,sp,tp) '
                         'mesh point covering --devices (mesh.mesh_points)'
                         ', each built via the explicit-aliasing route '
                         'and executed; writes a schema-valid mesh_sweep '
                         'stream (default MESH_SWEEP.jsonl, append)')
    ap.add_argument('--points', nargs='*', default=None,
                    metavar='DP,SP,TP',
                    help='with --mesh-sweep: explicit mesh points '
                         '(e.g. 2,2,2 4,1,2) instead of the full '
                         'enumeration')
    ap.add_argument('--per-device-nodes', type=int, default=256)
    ap.add_argument('--weak-dim', type=int, default=16)
    ap.add_argument('--ab', action='store_true',
                    help='with --weak-scaling: measure BOTH comm arms in '
                         'this process — overlapped+sparse (the default '
                         'path) and serialized+dense (ring_overlap='
                         'ring_exchange=False, the pre-PR5 program) — so '
                         'the A/B shares the host and the overhead delta '
                         'is attributable to the comm discipline alone')
    ap.add_argument('--no-overlap', action='store_true',
                    help='with --weak-scaling (single-arm): serialize the '
                         'ring ppermutes')
    ap.add_argument('--no-exchange', action='store_true',
                    help='with --weak-scaling (single-arm): dense global '
                         'gathers instead of the neighbor-sparse exchange')
    ap.add_argument('--metrics', default=None,
                    help='with --weak-scaling: also write a schema-valid '
                         'telemetry stream (run_meta + one comm record '
                         'per arm) for scripts/obs_report.py '
                         '--require comm')
    args = ap.parse_args(argv)

    jax = _setup(args.devices)

    if args.mesh_sweep:
        from se3_transformer_tpu.observability.report import (
            write_record_stream,
        )
        from se3_transformer_tpu.parallel.mesh import mesh_points
        if args.points:
            points = [tuple(int(x) for x in p.split(','))
                      for p in args.points]
            bad = [p for p in points
                   if len(p) != 3 or
                   p[0] * p[1] * p[2] != args.devices]
            assert not bad, \
                f'points {bad} do not cover {args.devices} devices'
        else:
            points = mesh_points(args.devices)
        out = args.out
        if os.path.basename(out) == 'WIDTH_TABLE.jsonl':
            out = os.path.join(os.path.dirname(out), 'MESH_SWEEP.jsonl')
        bodies = []
        for dp, sp, tp in points:
            rec = mesh_sweep_point(jax, dp, sp, tp,
                                   args.per_device_nodes, args.weak_dim,
                                   min(args.k, 8))
            print(json.dumps(rec), flush=True)
            bodies.append(dict(rec, kind='mesh_sweep'))
        write_record_stream(out, f'mesh_sweep_{os.getpid()}', bodies,
                            append=True)
        print(f'{len(bodies)} mesh_sweep records -> {out}',
              file=sys.stderr)
        return

    if args.weak_scaling:
        arms = [(True, True), (False, False)] if args.ab else \
            [(not args.no_overlap, not args.no_exchange)]
        recs = []
        for overlap, exchange in arms:
            rec = weak_scaling_point(
                jax, args.devices, args.per_device_nodes, args.weak_dim,
                min(args.k, 8), overlap=overlap, exchange=exchange)
            rec['arm'] = ('overlapped_sparse' if overlap and exchange
                          else 'serialized_dense'
                          if not (overlap or exchange) else
                          f'overlap={overlap},exchange={exchange}')
            recs.append(rec)
            print(json.dumps(rec), flush=True)
            with open(args.out, 'a') as f:
                f.write(json.dumps(rec) + '\n')
        if len(recs) == 2 and all('step_s' in r for r in recs):
            ratio = dict(weak_scaling_ab=True, devices=args.devices,
                         sp=args.devices, n=recs[0]['n'],
                         dim=args.weak_dim,
                         overlapped_sparse_step_s=recs[0]['step_s'],
                         serialized_dense_step_s=recs[1]['step_s'],
                         overlapped_vs_serialized=round(
                             recs[1]['step_s'] / recs[0]['step_s'], 3))
            print(json.dumps(ratio), flush=True)
            with open(args.out, 'a') as f:
                f.write(json.dumps(ratio) + '\n')
        if args.metrics:
            _write_comm_stream(args.metrics, recs)
        return
    from se3_transformer_tpu.parallel.mesh import make_mesh
    devices = jax.devices()[:args.devices]
    assert len(devices) >= args.devices, \
        f'only {len(devices)} devices visible'
    tp = args.tp if args.tp is not None else (
        2 if args.devices % 2 == 0 else 1)
    mesh = make_mesh(devices, dp=args.dp, tp=tp)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    print(f'mesh: {mesh_shape}', flush=True)

    for dim in args.dims:
        rec = dict(devices=args.devices, mesh=mesh_shape, backend='cpu-spmd')
        try:
            rec.update(measure_point(jax, mesh, dim, args.nodes, args.k, tp))
        except Exception as e:  # noqa: BLE001 - keep sweeping
            rec.update(dim=dim, n=args.nodes, k=args.k,
                       error=f'{type(e).__name__}: {e}'[:300])
        print(json.dumps(rec), flush=True)
        with open(args.out, 'a') as f:
            f.write(json.dumps(rec) + '\n')

    if args.exec_dim:
        rec = dict(devices=args.devices, mesh=mesh_shape,
                   backend='cpu-spmd', executed=True)
        try:
            rec.update(measure_point(jax, mesh, args.exec_dim,
                                     args.exec_nodes, min(args.k, 16), tp,
                                     execute=True))
        except Exception as e:  # noqa: BLE001
            rec.update(dim=args.exec_dim, n=args.exec_nodes,
                       error=f'{type(e).__name__}: {e}'[:300])
        print(json.dumps(rec), flush=True)
        with open(args.out, 'a') as f:
            f.write(json.dumps(rec) + '\n')


if __name__ == '__main__':
    main()
