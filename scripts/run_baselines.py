"""Train every tracked BASELINE config for N steps and record throughput.

One process, runs each recipe from training.recipes (BASELINE.json
"configs") end to end: init, jitted denoise-style train steps, finite-loss
assertion, and a throughput line per config. Writes a JSON summary.

Usage: python scripts/run_baselines.py [--steps 8] [--out BASELINES.json]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def node_counts():
    # per-config data scale: flagship gets the north-star 1024 nodes,
    # stress configs enough nodes to exercise memory, toys stay toy
    return dict(toy_denoise=96, flagship=1024, flagship_fast=1024,
                af2_refinement=256, molecular_edges=128, egnn_stress=512)


def run_config(name, module, n, steps, rng, batch=1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    needs_adj = bool(module.attend_sparse_neighbors or module.num_adj_degrees)
    has_tokens = module.num_tokens is not None
    b = batch

    if has_tokens:
        feats = jnp.asarray(rng.randint(0, module.num_tokens, (b, n)))
    else:
        feats = jnp.asarray(rng.normal(size=(b, n, module.dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(b, n, 3)), axis=1)
                        .astype(np.float32))
    coors = coors - coors.mean(axis=1, keepdims=True)
    mask = jnp.ones((b, n), bool)
    kwargs = dict(mask=mask)
    if needs_adj:
        i = np.arange(n)
        kwargs['adj_mat'] = jnp.asarray(
            np.broadcast_to((np.abs(i[:, None] - i[None, :]) == 1), (b, n, n))
            .copy())
    if module.num_edge_tokens is not None:
        kwargs['edges'] = jnp.asarray(
            rng.randint(0, module.num_edge_tokens, (b, n, n)))

    # output convention per config: denoise-style refinement loss where
    # the model emits a single type-1 vector per node (reduce_dim_out +
    # output_degrees>=2); plain mean-square objective otherwise (scalar
    # heads / EGNN multi-channel type-1)
    if module.use_egnn:
        return_type, denoise = 1, False
    elif module.reduce_dim_out and (module.output_degrees or 0) >= 2:
        return_type, denoise = 1, True
    else:
        return_type, denoise = 0, False

    def loss_fn(params, coors, key):
        noise = jax.random.normal(key, coors.shape, coors.dtype)
        noised = coors + noise
        out = module.apply({'params': params}, feats, noised,
                           return_type=return_type, **kwargs)
        if denoise:
            return (((noised + out) - coors) ** 2).sum(-1).mean()
        return (out ** 2).mean()

    init = jax.jit(module.init, static_argnames=('return_type',))
    params = init(jax.random.PRNGKey(0), feats, coors,
                  return_type=return_type, **kwargs)['params']
    opt = optax.adam(1e-4)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, key):
        loss, grads = jax.value_and_grad(loss_fn)(params, coors, key)
        gnorm = optax.global_norm(grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, gnorm

    key = jax.random.PRNGKey(1)
    t_c0 = time.time()
    params, opt_state, loss, gnorm = step(params, opt_state, key)
    jax.block_until_ready(loss)
    compile_s = time.time() - t_c0

    from se3_transformer_tpu.utils.helpers import fetch_sync
    # training-sanity signal travels with EVERY row (VERDICT r4 next #4:
    # fast-but-diverging must be visible in the record): per-step losses
    # and grad norms stay on device during the timed window (no extra
    # host syncs) and are floated after the clock stops
    losses, gnorms = [], []
    t0 = time.time()
    for _ in range(steps):
        key, sub = jax.random.split(key)
        params, opt_state, loss, gnorm = step(params, opt_state, sub)
        losses.append(loss)
        gnorms.append(gnorm)
    # host-materialize inside the window (loss gates the last forward, a
    # small param leaf gates the optimizer tail): block_until_ready was
    # observed to return tens of seconds early on this runtime
    loss = float(losses[-1])
    fetch_sync(min(jax.tree_util.tree_leaves(params), key=lambda l: l.size))
    dt = time.time() - t0
    losses = [float(l) for l in losses[:-1]] + [loss]
    gnorms = [float(g) for g in gnorms]
    assert np.isfinite(loss), f'{name}: non-finite loss'
    from se3_transformer_tpu.utils.helpers import loss_trajectory_fields
    rec = dict(config=name, nodes=n, steps=steps, loss=loss,
               step_ms=round(dt / steps * 1e3, 2),
               nodes_steps_per_sec=round(b * n * steps / dt, 2),
               compile_s=round(compile_s, 1),
               **loss_trajectory_fields(losses),
               grad_norm_first=round(gnorms[0], 4),
               grad_norm_last=round(gnorms[-1], 4),
               grad_norms_finite=bool(np.isfinite(gnorms).all()))
    # provenance (ADVICE r4 #5): a re-captured row that regresses purely
    # from a different host (1-core container) or code revision must be
    # explainable from the JSON alone
    from se3_transformer_tpu.observability.metrics import _code_rev
    rev = _code_rev()
    if rev:
        rec['code_rev'] = rev
    rec['host_cpus'] = os.cpu_count()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=8)
    ap.add_argument('--configs', nargs='+', default=None)
    ap.add_argument('--flagship-dim', type=int, default=64)
    ap.add_argument('--out', type=str, default=None)
    ap.add_argument('--cpu', action='store_true',
                    help='force CPU (a chip belongs to one process at a '
                         'time; use this when another process holds it)')
    args = ap.parse_args(argv)

    import jax
    if args.cpu:
        jax.config.update('jax_platforms', 'cpu')
    import numpy as np

    from se3_transformer_tpu.training.recipes import RECIPES
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    enable_compilation_cache()

    backend = jax.default_backend()
    print(f'backend: {backend}')
    counts = node_counts()
    # merge-on-write: a partial run (e.g. a crash after config 1)
    # must not clobber rows from configs it never reached — round 4 lost
    # the six-row on-chip table exactly that way. New rows replace
    # same-config/same-backend rows; everything else is preserved.
    prior = []
    if args.out and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                loaded = json.load(f)
            # shape-validate: a malformed prior must degrade to "no
            # prior", not crash the write loop after config 1
            prior = [r for r in loaded if isinstance(r, dict)
                     and 'config' in r] if isinstance(loaded, list) else []
        except Exception:
            prior = []
    results = []
    # the SE(3) recipes: the token decoders take tokens, and have their own
    # cells in the benchmark
    names = args.configs or [n for n in RECIPES if n not in (
        'token_decoder', 'hybrid_decoder')]
    failed = []

    def merged():
        # key on (config, backend): a --cpu liveness run must never
        # replace the on-chip row for the same config
        done = {(r['config'], r.get('backend')) for r in results}
        keep = [r for r in prior
                if (r['config'], r.get('backend')) not in done]
        return keep + results
    for name in names:
        builder = RECIPES[name]
        module = builder(dim=args.flagship_dim) \
            if name.startswith('flagship') else builder()
        rng = np.random.RandomState(0)
        # one config failing (e.g. an OOM at a new width) must not lose
        # the configs already measured — record and continue
        try:
            rec = run_config(name, module, counts[name], args.steps, rng)
        except Exception as e:  # noqa: BLE001
            print(f'{name} FAILED: {type(e).__name__}: {str(e)[:300]}',
                  file=sys.stderr)
            failed.append(name)
            continue
        rec['backend'] = backend
        print(json.dumps(rec))
        results.append(rec)
        if args.out:  # write-as-you-go: survive a later config crashing
            with open(args.out, 'w') as f:
                json.dump(merged(), f, indent=1)
    if args.out and results:
        print(f'wrote {args.out}')
    if failed:
        raise RuntimeError(f'configs failed: {failed}')


if __name__ == '__main__':
    main()
