"""Sequence-parallel comm smoke (`make ring-smoke`).

Virtual-8-device CPU mesh, one small ring-path model (padded mask +
bonded adjacency — the semantics that must survive the sparse exchange),
three gates, exit non-zero on any miss:

  1. EXCHANGE PARITY — the neighbor-sparse exchange arm
     (ring_exchange=True, the default) matches the dense-gather control
     arm (ring_exchange=False) on the same params/inputs, and the
     overlapped ring matches the serialized ring BIT-EXACTLY
     (parallel.ring.ring_scan's contract).
  2. COMM SCHEMA — the run writes a telemetry stream (run_meta + one
     `comm` record per traced arm) that observability.schema validates;
     the Makefile target re-gates it through
     `scripts/obs_report.py --require comm`.
  3. ALL-GATHER-FREE — the traced sp=8 forward of the exchange arm
     contains no full-width [b, N, ...] all-gather (the artifact the
     exchange exists to kill), while the dense control arm is REQUIRED
     to contain one (proving the scan actually detects them — a
     detector that never fires gates nothing).

Usage:
    python scripts/ring_smoke.py [--metrics STREAM.jsonl]
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--metrics', default=None,
                    help='write the schema-valid comm stream here')
    ap.add_argument('--devices', type=int, default=8)
    args = ap.parse_args(argv)

    flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags +
            f' --xla_force_host_platform_device_count={args.devices}'
        ).strip()
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from se3_transformer_tpu import SE3TransformerModule
    from se3_transformer_tpu.parallel import make_mesh
    from se3_transformer_tpu.parallel.exchange import comm_payload
    from se3_transformer_tpu.parallel.ring import ring_knn

    failures = []
    sp = args.devices
    mesh = make_mesh(dp=1, sp=sp, tp=1)
    rng = np.random.RandomState(0)
    n, k = 64, 6
    feats = jnp.asarray(rng.normal(size=(1, n, 8)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, n, 3)) * 2, jnp.float32)
    mask = np.ones((1, n), bool)
    mask[:, n - 8:] = False                      # padded tail
    mask = jnp.asarray(mask)
    adj = np.zeros((n, n), bool)                 # a chain of bonds
    idx_ = np.arange(n - 9)
    adj[idx_, idx_ + 1] = adj[idx_ + 1, idx_] = True
    adj = jnp.asarray(adj[None])

    # gate 1a: overlapped vs serialized ring_knn — bit-exact
    d1, i1 = ring_knn(coors, k, mesh, mask=mask, overlap=True)
    d0, i0 = ring_knn(coors, k, mesh, mask=mask, overlap=False)
    if not (np.array_equal(np.asarray(d1), np.asarray(d0))
            and np.array_equal(np.asarray(i1), np.asarray(i0))):
        failures.append('ring_knn overlap=True vs overlap=False not '
                        'bit-exact')

    # gate 1b: exchange arm vs dense-gather control arm on one model
    kw = dict(dim=8, depth=1, attend_self=True, num_neighbors=k,
              num_degrees=2, output_degrees=2,
              attend_sparse_neighbors=True, max_sparse_neighbors=2,
              sequence_parallel='ring', mesh=mesh)
    arms = {
        'overlapped_sparse': SE3TransformerModule(**kw),
        'serialized_dense': SE3TransformerModule(
            **kw, ring_overlap=False, ring_exchange=False),
    }
    call = dict(mask=mask, adj_mat=adj, return_type=1)
    params = arms['overlapped_sparse'].init(
        jax.random.PRNGKey(7), feats, coors, **call)['params']
    outs = {}
    hlos = {}
    shard = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    sharded = (shard(feats, P(None, 'sp', None)),
               shard(coors, P(None, 'sp', None)),
               shard(mask, P(None, 'sp')), shard(adj, P(None, 'sp', None)))
    for name, module in arms.items():
        compiled = jax.jit(
            lambda p, f, c, m, a, module=module: module.apply(
                {'params': p}, f, c, mask=m, adj_mat=a, return_type=1)
        ).lower(params, *sharded).compile()
        outs[name] = np.asarray(compiled(params, *sharded))
        hlos[name] = compiled.as_text()
    diff = float(np.abs(outs['overlapped_sparse']
                        - outs['serialized_dense']).max())
    if diff > 1e-5:
        failures.append(f'exchange arm vs dense control arm diverge: '
                        f'max diff {diff}')

    # gate 3: the exchange trace is all-gather-free; the dense control
    # trace must NOT be (detector liveness)
    payloads = {}
    for name, (ov, ex) in (('overlapped_sparse', (True, True)),
                           ('serialized_dense', (False, False))):
        payloads[name] = comm_payload(hlos[name], sp=sp, ring_steps=sp,
                                      overlap=ov, exchange=ex,
                                      full_width_dim=n)
    if not payloads['overlapped_sparse']['all_gather_free']:
        failures.append(
            'exchange arm traced full-width all-gathers: '
            f"{payloads['overlapped_sparse']['full_width_all_gathers']}")
    if payloads['serialized_dense']['all_gather_free']:
        failures.append('dense control arm traced NO full-width '
                        'all-gather — the detector cannot be trusted')

    # gate 2: schema'd comm stream
    if args.metrics:
        from se3_transformer_tpu.observability.report import (
            write_comm_stream,
        )
        write_comm_stream(
            args.metrics, f'ring_smoke_{os.getpid()}',
            [dict(payload, label=name)
             for name, payload in payloads.items()])

    summary = dict(
        sp=sp, n=n, k=k, parity_max_diff=diff,
        overlap_bit_exact='ring_knn overlap' not in ' '.join(failures),
        exchange_all_gather_free=payloads[
            'overlapped_sparse']['all_gather_free'],
        dense_full_width_all_gathers=len(payloads[
            'serialized_dense']['full_width_all_gathers']),
        failures=failures,
    )
    print(json.dumps(summary))
    if failures:
        for f_ in failures:
            print(f'RING SMOKE FAIL: {f_}', file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
