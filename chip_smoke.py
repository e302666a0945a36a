"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python3 chip_smoke.py             # one chip: device check, train, serve
    python3 chip_smoke.py --chips 4   # one four-chip host: the cross-chip
                                      # paths and what they are compared
                                      # with, and no other phase

One process, no child that touches JAX (a chip belongs to one process at
a time). There is no CPU branch: without a TPU the device check fails
and nothing else runs. Each phase returns the facts it observed and a
`check_*` function raises on the first one that is wrong, so a failed
phase can never be reported under exit code 0.

One chip:
  * train — the flagship at full width (recipes.flagship_fast, dim=64,
    n=1024, k=32, degree 4, depth 6, batch 1) through
    scripts/_flagship_common.build_flagship_step (jitted init,
    adam(1e-4), parallel.sharding.make_sharded_train_step, donated
    state): the compiled step must contain Mosaic kernels, every loss
    is fetched to the host, finite, last < first.
  * serve — InferenceEngine + MicroBatcher + AdmissionController wired as
    scripts/serve.py wires them, on a token-input module at the same
    flagship width (depth cut to 2), two buckets, a mixed-length stream
    plus one oversize reject: every answer finite and of its request's
    shape, zero post-warmup compiles, and the served function
    SE(3)-equivariant through an engine at float32 matmul precision
    (utils.validation's measure, relative to the output's scale).

Four chips (`--chips 4`):
  * replicas — four one-device engines behind serving.Router, each
    placed on its own chip through the engine's `mesh` argument.
  * mesh — the flagship step (depth cut to 2) as one program over
    make_mesh(dp=2, tp=2), batch = dp, at n=1024, state spread over all
    four devices; and a parity pair at n=256: mesh losses against the
    same seeds on one device. On the XLA contraction (pallas=False,
    edge_chunks=8): this installation's compiler cannot partition the
    Pallas kernels (KERNELS_DO_NOT_PARTITION below).

The last line of stdout is the result the driver reads:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, 'scripts'))

# the repo's own gates: equivariance at float32 matmul precision (README,
# tests/test_equivariance.py) — taken relative to
# the output's scale, which at flagship width and random weights is far
# from the toy models' O(1) — and the sharding tests' loss tolerance
# (tests/test_sharding.py)
EQUIVARIANCE_TOL = 1e-4
MESH_LOSS_RTOL = 1e-4

SERVE_NUM_TOKENS = 24


class SmokeFailure(AssertionError):
    """A phase observed something wrong."""


def say(msg):
    print(msg, flush=True)


def require_tpu(chips):
    """The device check: TPU or fail. No probe, no retry, no CPU branch."""
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        raise SystemExit(
            f'chip_smoke: needs a TPU, JAX found {devices[0].platform!r} '
            f'({len(devices)} device(s)); there is no CPU substitute')
    if len(devices) < chips:
        raise SystemExit(f'chip_smoke: --chips {chips} needs {chips} '
                         f'devices, JAX found {len(devices)}')
    return devices


class CacheCounter:
    """Persistent-compilation-cache hits and misses, from jax.monitoring
    (listeners cannot be unregistered, so one instance serves a run)."""

    def __init__(self):
        from jax import monitoring
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.misses += 1

    def timed(self, label, fn):
        """Run a compiling call; say how long it took and whether the
        persistent cache served it."""
        hits, misses, t0 = self.hits, self.misses, time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        say(f'{label}: {dt:.1f} s (persistent cache: '
            f'{self.hits - hits} hit, {self.misses - misses} miss)')
        return out


def memory_line(device):
    """The allocator's counters: arrays are `in_use`, a running
    program's temporaries are `reserved`."""
    stats = device.memory_stats()
    return ', '.join(f'{k} {stats[k] / 2**30:.2f} GiB' for k in (
        'peak_bytes_in_use', 'peak_bytes_reserved', 'bytes_limit'))


def run_steps(compiled, params, opt_state, data, key, steps):
    """`steps` optimizer steps, each loss fetched to the host (the fetch
    is the sync that closes the step's clock)."""
    import jax
    losses, step_ms = [], []
    for _ in range(steps):
        key, sub = jax.random.split(key)
        t0 = time.perf_counter()
        params, opt_state, loss, _ = compiled(params, opt_state, data, sub)
        losses.append(float(loss))
        step_ms.append((time.perf_counter() - t0) * 1e3)
    return params, opt_state, losses, step_ms


def drive_stream(front, lengths, rng, num_tokens):
    """Submit one random request per length to a MicroBatcher or a
    Router (same submit / pump / drain surface), then drain the
    deadline stragglers. Returns (pending results, reject codes)."""
    import numpy as np
    from se3_transformer_tpu.inference import RequestRejected
    pending, rejected = [], []
    for length in lengths:
        tokens = rng.randint(0, num_tokens, size=length)
        coords = rng.normal(size=(length, 3)).astype(np.float32)
        try:
            pending.append(front.submit(tokens, coords))
        except RequestRejected as e:
            rejected.append(e.code)
        front.pump()
    while front.queue_depth:
        wait = front.next_deadline()
        if wait:
            time.sleep(wait)
        front.pump()
    return pending, rejected


def check_stream(label, facts):
    """What every served stream is held to: each admitted request
    answered with finite [length, 3] rows, the oversize ones rejected,
    nothing compiled after warmup."""
    import numpy as np
    for p in facts['pending']:
        if not p.ok:
            raise SmokeFailure(f'{label}: request {p.request_id} '
                               f'unanswered ({p.error!r})')
        out = np.asarray(p.result)
        if out.shape != (p.length, 3) or not np.isfinite(out).all():
            raise SmokeFailure(
                f'{label}: request {p.request_id} (length {p.length}) '
                f'answered with shape {out.shape}, finite='
                f'{bool(np.isfinite(out).all())}')
    if facts['rejected'] != ['oversize'] * facts['oversize']:
        raise SmokeFailure(f'{label}: expected {facts["oversize"]} '
                           f'oversize reject(s), got {facts["rejected"]}')
    if facts['post_warmup_compiles']:
        raise SmokeFailure(
            f'{label}: {facts["post_warmup_compiles"]} compile events '
            f'after warmup — the AOT bucket contract is broken')


def check_finite(label, losses):
    import math
    if not all(math.isfinite(l) for l in losses):
        raise SmokeFailure(f'{label}: non-finite loss in {losses}')


def check_losses(label, losses):
    check_finite(label, losses)
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f'{label}: loss did not decrease: {losses}')


# --------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------- #

def train_phase(cache, *, nodes=1024, dim=64, depth=6, num_neighbors=32,
                steps=4, mesh=None, batch=1, **overrides):
    """A few optimizer steps of the flagship; returns the facts.
    `overrides` reach scripts/_flagship_common.build_flagship_step."""
    import jax
    from _flagship_common import build_flagship_step
    from se3_transformer_tpu.kernels import tuning

    tuning.clear_kernel_caches()   # picks resolve at trace time
    snap = tuning.snapshot()
    step, params, opt_state, data, key, module = cache.timed(
        'train: build + jitted init',
        lambda: build_flagship_step(
            fast=True, nodes=nodes, dim=dim, batch=batch, mesh=mesh,
            depth=depth, num_neighbors=num_neighbors, **overrides))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    say(f'train: flagship_fast dim={dim} n={nodes} k={num_neighbors} '
        f'degree={module.num_degrees} depth={depth} batch={batch} '
        f'params={n_params / 1e6:.1f}M mesh='
        f'{dict(mesh.shape) if mesh is not None else None} {overrides}')
    compiled = cache.timed(
        'train: step compile',
        lambda: step.lower(params, opt_state, data, key).compile())
    text = compiled.as_text()
    mosaic_calls = text.count('tpu_custom_call')
    collectives = text.count('all-reduce(') + text.count('all-gather(')
    params, opt_state, losses, step_ms = run_steps(
        compiled, params, opt_state, data, key, steps)
    say(f'train: losses {[round(l, 4) for l in losses]}')
    say(f'train: step ms {[round(t, 1) for t in step_ms]}')
    say('train: kernel block picks '
        + json.dumps(tuning.consult_summary(tuning.consults_since(snap))))
    return dict(losses=losses, mosaic_calls=mosaic_calls,
                collectives=collectives, params=params,
                opt_state=opt_state)


def check_train(facts):
    if facts['mosaic_calls'] <= 0:
        raise SmokeFailure(
            'train: no tpu_custom_call in the compiled step — the Pallas '
            'kernels are not in the program (an XLA path ran in their '
            'place)')
    say(f'train: {facts["mosaic_calls"]} Mosaic custom calls in the '
        f'compiled step')
    check_losses('train', facts['losses'])


def build_serve_module(buckets, *, dim=64, depth=2, num_neighbors=32,
                       seed=0):
    """The flagship recipe with a token embedding in front: the engine
    takes it as it takes scripts/serve.py's toy module (no chain
    adjacency — the flagship's graph is kNN)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from se3_transformer_tpu.training import recipes

    module = recipes.flagship_fast(
        dim=dim, depth=depth, num_neighbors=num_neighbors,
        num_tokens=SERVE_NUM_TOKENS, output_degrees=2, reduce_dim_out=True)
    rng = np.random.RandomState(seed)
    L = buckets[0]
    init_fn = jax.jit(module.init, static_argnames=('return_type',))
    params = init_fn(
        jax.random.PRNGKey(seed),
        jnp.asarray(rng.randint(0, SERVE_NUM_TOKENS, size=(1, L))),
        jnp.asarray(rng.normal(size=(1, L, 3)).astype(np.float32)),
        mask=jnp.ones((1, L), bool), return_type=1)['params']
    say(f'serve: flagship_fast + token embedding, dim={dim} '
        f'k={num_neighbors} degree={module.num_degrees} depth={depth} '
        f'heads={module.heads} dim_head={module.dim_head}')
    return module, params


def engine_equivariance(engine, length, seed=0):
    """utils.validation.equivariance_l2's measure, taken through an
    engine's executables (padding and masking included) and divided by
    the output's scale: max per-node L2 of f(tokens, c R) - f(tokens, c) R
    over the largest per-node L2 of f(tokens, c) R. The rotation is
    applied in float64 on the host."""
    import numpy as np
    from se3_transformer_tpu.so3 import rot
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, SERVE_NUM_TOKENS, size=length)
    coords = rng.normal(size=(length, 3)) * 2.0
    R = rot(0.37, 1.12, -0.64)
    out_rot = np.asarray(engine.predict(
        tokens, (coords @ R).astype(np.float32)), np.float64)
    out_ref = np.asarray(engine.predict(
        tokens, coords.astype(np.float32)), np.float64) @ R
    norm = lambda a: np.sqrt((a ** 2).sum(-1)).max()  # noqa: E731
    return float(norm(out_rot - out_ref) / norm(out_ref))


def serve_phase(cache, *, buckets=(256, 1024), batch_size=2, requests=6,
                oversize=1, seed=0, **module_kwargs):
    """Warm an engine on two buckets, answer a mixed-length stream plus
    an oversize reject; returns the facts."""
    import jax
    import numpy as np
    import serve as serve_script
    from se3_transformer_tpu.inference import (
        AdmissionController, InferenceEngine, MicroBatcher, ServeTelemetry,
    )

    module, params = cache.timed(
        'serve: build + jitted init',
        lambda: build_serve_module(buckets, seed=seed, **module_kwargs))
    engine = cache.timed(
        f'serve: AOT warmup of buckets {list(buckets)}',
        lambda: InferenceEngine(module, params, buckets=buckets,
                                batch_size=batch_size, return_type=1,
                                with_chain_adjacency=False))
    say(f'serve: compile seconds by bucket {engine.compile_seconds}')
    admission = AdmissionController(max_len=engine.max_len,
                                    max_queue_depth=64)
    batcher = MicroBatcher(engine.run, buckets=engine.buckets,
                           batch_size=batch_size, max_wait_ms=5.0,
                           admission=admission)
    telemetry = ServeTelemetry(engine, batcher, admission)
    telemetry.arm()              # every compile from here on is a fault

    rng = np.random.RandomState(seed)
    args = argparse.Namespace(requests=requests, oversize=oversize)
    lengths = serve_script.request_lengths(args, engine.buckets,
                                           engine.max_len, rng)
    pending, rejected = drive_stream(batcher, lengths, rng,
                                     SERVE_NUM_TOKENS)
    # the stream's own executables run the backend's default matmul
    # precision (one bf16 pass on a TPU): worth knowing, not gated
    probe_len = min(buckets[0], 64)
    equivariance_default = engine_equivariance(engine, probe_len, seed)
    summary = telemetry.close()
    # the gate is the repo's: float32 matmul precision. One more bucket
    # executable, traced under that precision, the same params
    with jax.default_matmul_precision('float32'):
        precise = cache.timed(
            f'serve: AOT warmup of bucket {buckets[0]} at float32 matmul '
            f'precision',
            lambda: InferenceEngine(module, params, buckets=buckets[:1],
                                    batch_size=batch_size, return_type=1,
                                    with_chain_adjacency=False))
    equivariance = engine_equivariance(precise, probe_len, seed)
    say(f'serve: lengths {lengths} -> answered '
        f'{sum(p.ok for p in pending)}, rejected {rejected}, '
        f'{batcher.batches_dispatched} batches')
    say('serve: bucket latency ms ' + json.dumps({
        k: v['p50_ms'] for k, v in summary['timing'].items()
        if k.startswith('bucket_')}))
    say(f'serve: relative equivariance error through the engine '
        f'{equivariance:.3e} at float32 matmul precision (gated), '
        f'{equivariance_default:.3e} at the default precision')
    return dict(pending=pending, rejected=rejected, oversize=oversize,
                post_warmup_compiles=telemetry.post_warmup_compiles,
                equivariance=equivariance)


def check_serve(facts):
    check_stream('serve', facts)
    if not facts['equivariance'] < EQUIVARIANCE_TOL:
        raise SmokeFailure(
            f'serve: relative equivariance error '
            f'{facts["equivariance"]:.3e} through the engine at float32 '
            f'matmul precision is not under {EQUIVARIANCE_TOL}')


# --------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------- #

def placement(tree):
    """Where a state pytree lives: per leaf the set of devices holding
    its addressable shards, and how many leaves are split (their shards
    cover different index ranges) rather than replicated."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return dict(
        devices=[frozenset(s.device for s in leaf.addressable_shards)
                 for leaf in leaves],
        split=sum(1 for leaf in leaves if len(
            {str(s.index) for s in leaf.addressable_shards}) > 1))


# What the TPU compiler (jax 0.9.0, libtpu 0.0.34) answers when a Pallas
# kernel sits in a program jitted over several devices — on the chip and
# in the deviceless compile alike (tests/test_tpu_compile.py holds it as
# a strict xfail). The kernels partition through
# jax.experimental.custom_partitioning, whose custom call this compiler
# neither resolves nor can emit. Until the kernels partition another
# way, a mesh step on this installation runs the XLA contraction.
KERNELS_DO_NOT_PARTITION = ('INVALID_ARGUMENT: Custom emitter for '
                            'CustomSPMDPartitioning not found')


def mesh_phase(cache, devices, *, nodes=1024, parity_nodes=256, depth=2,
               steps=3, **size_kwargs):
    """The flagship step over a dp=2 x tp=2 mesh, and the parity pair."""
    import jax
    from se3_transformer_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(devices[:4], dp=2, sp=1, tp=2)
    say(f'mesh: widths unchanged; depth cut to {depth} (four chips are '
        f'charged four times over, and depth adds no mechanism); batch '
        f'2 = dp; pallas=False with edge_chunks=8, the XLA contraction '
        f'at a size that fits — with the kernels in, the compiler says '
        f'{KERNELS_DO_NOT_PARTITION!r}')
    sizes = dict(size_kwargs, depth=depth, steps=steps, batch=2,
                 pallas=False, chunks=8)
    full = train_phase(cache, nodes=nodes, mesh=mesh, **sizes)
    # keep where the state lives, not the state: the pair needs the room
    placed = {name: placement(full.pop(name))
              for name in ('params', 'opt_state')}
    # MESH_LOSS_RTOL is the tolerance of f32 tests. At the chip's default
    # precision (one bf16 pass, and the recipe's bf16 radial casts) two
    # programs that fuse differently round differently: the same pair
    # read 1.3e-4 .. 8.9e-4 apart there (PR 21). So the pair runs in
    # float32 throughout, and what is left to differ is the sharding.
    say(f'mesh: parity pair at n={parity_nodes}, where batch 2 fits one '
        f'chip with room to spare, at float32 matmul precision with '
        f'radial_bf16=False')
    pair = dict(sizes, nodes=parity_nodes, radial_bf16=False)
    with jax.default_matmul_precision('float32'):
        on_mesh = train_phase(cache, mesh=mesh, **pair)['losses']
        on_one = train_phase(cache, mesh=None, **pair)['losses']
    return dict(mesh_devices=set(mesh.devices.flat), full=full,
                placed=placed, on_mesh=on_mesh, on_one=on_one)


def check_mesh(facts):
    full = facts['full']
    if full['collectives'] <= 0:
        raise SmokeFailure('mesh: no all-reduce or all-gather in the '
                           'compiled step — it is not one program over '
                           'the mesh')
    say(f'mesh: {full["collectives"]} all-reduce/all-gather ops in the '
        f'compiled step')
    check_losses('mesh', full['losses'])
    for name, where in facts['placed'].items():
        if not all(d == facts['mesh_devices'] for d in where['devices']):
            raise SmokeFailure(
                f'mesh: some {name} leaves do not live on all four '
                f'devices (devices per leaf: '
                f'{sorted({len(d) for d in where["devices"]})})')
        say(f'mesh: {name}: {len(where["devices"])} leaves on 4 devices, '
            f'{where["split"]} of them split over tp (not replicated)')
        if where['split'] < 4:
            raise SmokeFailure(f'mesh: only {where["split"]} {name} '
                               f'leaves are partitioned over tp')
    # the pair is held to agreement, not to learning
    for label in ('on_mesh', 'on_one'):
        check_finite(f'mesh parity ({label})', facts[label])
    for a, b in zip(facts['on_mesh'], facts['on_one']):
        if abs(a - b) > MESH_LOSS_RTOL * max(1.0, abs(b)):
            raise SmokeFailure(
                f'mesh: losses on the mesh {facts["on_mesh"]} differ '
                f'from one device {facts["on_one"]} by more than '
                f'{MESH_LOSS_RTOL} (relative)')
    say(f'mesh: mesh and one-device losses agree within {MESH_LOSS_RTOL}')


def replica_phase(cache, devices, *, buckets=(12, 24), batch_size=2,
                  requests=12, seed=0):
    """Four one-device engines behind serving.Router, wired as
    scripts/serve.py --replicas wires them (its toy module: the widths
    are printed), each placed on its own device."""
    import numpy as np
    import serve as serve_script
    from se3_transformer_tpu.inference import (
        AdmissionController, InferenceEngine,
    )
    from se3_transformer_tpu.observability import PhaseTimer
    from se3_transformer_tpu.serving import (
        ReplicaWorker, Router, RouterTelemetry,
    )

    args = argparse.Namespace(seed=seed, checkpoint=None,
                              requests=requests, oversize=1)
    cfg, module, params = serve_script.build_module_and_params(args, buckets)
    say(f'replicas: scripts/serve.py module dim={cfg.dim} depth={cfg.depth} '
        f'degrees={cfg.num_degrees} heads={cfg.heads}')
    timer = PhaseTimer()
    engines = cache.timed(
        'replicas: AOT warmup of 4 engines',
        lambda: [InferenceEngine(
            module, params, buckets=buckets, batch_size=batch_size,
            return_type=1, timer=timer,
            mesh=serve_script.replica_mesh(i, devices),
            partition_rules='replicated') for i in range(4)])
    workers = [ReplicaWorker(i, e, max_wait_ms=5.0)
               for i, e in enumerate(engines)]
    admission = AdmissionController(max_len=buckets[-1], max_queue_depth=64)
    rng = np.random.RandomState(seed)
    with Router(workers, admission=admission) as router:
        telemetry = RouterTelemetry(router, admission)
        telemetry.arm()
        pending, rejected = drive_stream(
            router, serve_script.request_lengths(args, buckets,
                                                 router.max_len, rng),
            rng, cfg.num_tokens)
    telemetry.close()
    served = {w.id: w.served_rows for w in workers}
    say(f'replicas: answered {sum(p.ok for p in pending)} of '
        f'{len(pending)}, rejected {rejected}, rows by replica {served}')
    return dict(pending=pending, rejected=rejected, oversize=args.oversize,
                engines=engines,
                post_warmup_compiles=telemetry.post_warmup_compiles)


def check_replicas(facts, devices):
    check_stream('replicas', facts)
    placed = []
    for engine in facts['engines']:
        devs = set().union(*placement(engine.params)['devices'])
        if len(devs) != 1:
            raise SmokeFailure(f'replicas: one replica\'s params span '
                               f'{len(devs)} devices')
        placed.append(next(iter(devs)))
    say(f'replicas: params on {[str(d) for d in placed]}')
    if set(placed) != set(devices[:4]):
        raise SmokeFailure(f'replicas: the four replicas do not sit on '
                           f'four different devices: {placed}')


# --------------------------------------------------------------------- #

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1,
                    help='4 runs only the cross-chip paths (builder-run)')
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    say(f'device: {devices[0].device_kind} x {len(devices)} '
        f'(platform {devices[0].platform})')

    from se3_transformer_tpu.native.loader import native_available
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    say(f'compile cache: {enable_compilation_cache()}')
    say('host graph pipeline: ' + (
        'native/libse3graph.so built from graph_builder.cpp'
        if native_available() else 'NumPy path (g++ build unavailable)'))
    cache = CacheCounter()

    if args.chips == 1:
        check_train(train_phase(cache))
        say(f'train: device memory {memory_line(devices[0])}')
        check_serve(serve_phase(cache))
    else:
        # the cheap phase first: a fault there costs a minute, not ten
        check_replicas(replica_phase(cache, devices), devices)
        check_mesh(mesh_phase(cache, devices))
        for d in devices[:4]:
            say(f'mesh: device {d.id} memory {memory_line(d)}')

    print(json.dumps(dict(ok=True, device=dict(
        platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices)))), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
