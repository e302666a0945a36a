"""Serve a mixed-length request stream through the inference subsystem.

Usage:
    python scripts/serve.py [--requests N] [--oversize K]
        [--buckets 12,24] [--batch-size 2] [--max-wait-ms 5]
        [--max-queue-depth 64] [--bf16] [--checkpoint DIR] [--cpu]
        [--metrics SERVE.jsonl] [--out SUMMARY.json] [--seed S]
        [--replicas N] [--swap-at K]
        [--fleet N | --host --port P --host-id K]

Startup: restore params (params-only — optimizer state never
materializes) or init a toy model, AOT-compile one executable per
bucket, arm the compile-event watchdog. Serve loop: admit -> enqueue ->
micro-batch (flush on full or deadline) -> answer. Close: a
SESSION_SUMMARY-style report.

This doubles as the `make serve-smoke` gate, exiting non-zero when
  * the telemetry stream fails schema validation, or
  * any post-warmup compile event fired (the AOT contract: a
    mixed-length stream over precompiled buckets must compile NOTHING),
  * or an in-range request failed to produce a result.

`--replicas N` (N > 1) switches to the multi-replica continuous-
batching router (se3_transformer_tpu.serving): N replica workers, each
owning its own AOT engine placed on its own device (round-robin over
the devices JAX sees — `replica_mesh`), least-outstanding dispatch,
requests admitted into in-flight bucket slots (deadline only as a
fallback),
and — with `--swap-at K` — one rolling weight swap after the K-th
request (fresh seeded params; zero recompiles, zero dropped requests).
This is the `make serve-multi-smoke` gate; on top of the single-replica
gates it also exits non-zero when
  * no request was ever admitted into an in-flight slot
    (continuous_admissions == 0 — the router degenerated to flush
    barriers), or
  * the rolling swap did not complete across every replica.

Every serving mode installs a SIGTERM/SIGINT handler in the
`PreemptionGuard` idiom (set a flag, nothing else): a preempted serve
loop stops admitting, drains what it already accepted, flushes the
final telemetry records, and exits 0 — a mid-serve SIGTERM must never
lose the telemetry bank (tests/test_fleet.py pins it with a real
signal).

`--host` runs this process as one FLEET HOST: the replicas/router stack
above, exposed on a TCP port through `serving.transport.serve_socket` +
`serving.fleet.HostServer` (methods: ping / stats / infer / swap /
drain). It prints `FLEET HOST READY host=K port=P` once the AOT warmup
finished and the socket listens, then parks until SIGTERM (graceful
drain + final records + a host `fault` record, exit 0). `--poison-step
S` is the chaos hook: after a swap RPC restores step S, every
subsequent dispatch fails deterministically until a swap restores a
different step — the fault-injected canary of `make serve-fleet-smoke`.

`--fleet N` (N > 1) runs the CROSS-HOST front-end: spawn N `--host`
worker processes — always on the CPU backend: a chip belongs to one
process at a time, so workers on chips need one chip each and are
started one per chip by whoever owns the chips, never by this script —
and route the request stream through a
`serving.fleet.FleetRouter` (host-level breakers, cross-host
redispatch, deadline propagation), bank the schema'd `fleet` record,
and SIGTERM the workers on the way out (each must exit 0). Exits
non-zero when any in-range submit resolves unanswered, any request is
lost, the stream fails schema validation, or a worker exits non-zero.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from se3_transformer_tpu.utils.compilation_cache import (  # noqa: E402
    enable_compilation_cache,
)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description='bucketed AOT serving over a mixed-length stream')
    ap.add_argument('--requests', type=int, default=8,
                    help='in-range requests, lengths cycling across '
                         'buckets (mixed-length by construction)')
    ap.add_argument('--oversize', type=int, default=1,
                    help='extra requests longer than the largest bucket '
                         '(must be rejected, never compiled)')
    ap.add_argument('--buckets', type=str, default='12,24')
    ap.add_argument('--batch-size', type=int, default=2)
    ap.add_argument('--max-wait-ms', type=float, default=5.0)
    ap.add_argument('--max-queue-depth', type=int, default=64)
    ap.add_argument('--flush-every', type=int, default=2,
                    help='emit a serve record every N dispatched batches')
    ap.add_argument('--bf16', action='store_true',
                    help='bf16 activation path (coords cast in, f32 out)')
    ap.add_argument('--precision', type=str, default=None,
                    help='weight-precision mix (quant.rules: fp32 / '
                         'bf16 / int8_mix / fp8_mix). Params quantize '
                         'at restore time — the fp32 tree never lands '
                         'on device. With --replicas N, a comma list '
                         'builds a HETEROGENEOUS fleet (cycled across '
                         'replicas, e.g. "fp32,int8_mix"); rolling '
                         'swaps re-quantize per replica at its own mix '
                         '(zero drops, zero recompiles)')
    ap.add_argument('--checkpoint', type=str, default=None,
                    help='CheckpointManager directory; params-only '
                         'restore (optimizer state is never read)')
    ap.add_argument('--metrics', type=str, default=None,
                    help='JSONL telemetry stream (serve records)')
    ap.add_argument('--out', type=str, default=None,
                    help='write the summary report JSON here')
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--cpu', action='store_true',
                    help='force the CPU backend')
    ap.add_argument('--replicas', type=int, default=1,
                    help='>1 routes through the multi-replica '
                         'continuous-batching router '
                         '(se3_transformer_tpu.serving)')
    ap.add_argument('--swap-at', type=int, default=None,
                    help='multi-replica only: after this many submitted '
                         'requests, hot-swap fresh weights with a '
                         'rolling drain (zero recompiles, zero drops)')
    ap.add_argument('--async-dispatch', action='store_true',
                    help='multi-replica only: per-replica thread-pool '
                         'dispatch — replica executions overlap instead '
                         'of serializing through the submit loop '
                         '(serving.ReplicaWorker async_dispatch)')
    ap.add_argument('--timeout-s', type=float, default=None,
                    help='multi-replica only: per-request deadline '
                         '(submitted_at + timeout); expired requests '
                         'shed before dispatch and resolve with a '
                         'structured RequestFailed("deadline")')
    ap.add_argument('--max-retries', type=int, default=1,
                    help='multi-replica only: redispatches of a failed '
                         "batch's requests onto sibling replicas before "
                         'a structured RequestFailed("retries_'
                         'exhausted")')
    ap.add_argument('--pace-ms', type=float, default=0.0,
                    help='sleep this long between submitted requests '
                         '(stream pacing — gives probes/deadlines/'
                         'signals real time to land mid-serve)')
    # ---- cross-host fleet tier (serving.fleet) ---------------------- #
    ap.add_argument('--fleet', type=int, default=1,
                    help='>1 spawns N --host worker processes and '
                         'routes through the cross-host FleetRouter '
                         '(host-level breakers, cross-host redispatch, '
                         'schema\'d fleet record)')
    ap.add_argument('--host', action='store_true', dest='host_mode',
                    help='run as ONE fleet host: serve the replicas/'
                         'router stack on a TCP port (serving.fleet.'
                         'HostServer) until SIGTERM')
    ap.add_argument('--host-id', type=int, default=0,
                    help='--host only: this host\'s id in the fleet')
    ap.add_argument('--port', type=int, default=0,
                    help='--host only: TCP port (0 = OS-assigned; the '
                         'READY line names the bound port)')
    ap.add_argument('--transport', choices=('binary', 'legacy'),
                    default='binary',
                    help='fleet wire: "binary" (persistent pooled '
                         'connections, correlation-id multiplexing, '
                         'raw numpy array frames — the default) or '
                         '"legacy" (connect-per-call newline-JSON '
                         'escape hatch)')
    ap.add_argument('--checkpoint-step', type=int, default=None,
                    help='with --checkpoint: restore this step instead '
                         'of the latest (the fleet smoke starts hosts '
                         'on the OLD weights while the rollout target '
                         'sits at a later step)')
    ap.add_argument('--poison-step', type=int, default=None,
                    help='--host only (chaos hook): after a swap RPC '
                         'restores this step, every dispatch fails '
                         'deterministically until a different step is '
                         'restored — the fault-injected canary arm of '
                         'make serve-fleet-smoke')
    return ap.parse_args(argv)


# the toy serving model's vocab size — ONE constant shared by the
# module builder and every request-stream generator (a fleet front-end
# sampling out-of-vocab ids would silently gather wrong embeddings)
TOY_NUM_TOKENS = 24


def precision_mixes(args):
    """The per-replica precision list: None -> fp32 everywhere; a
    single mix applies to every replica; a comma list cycles."""
    if not args.precision:
        return [None] * max(args.replicas, 1)
    mixes = [m.strip() or None for m in args.precision.split(',')]
    if args.replicas <= 1 and len(mixes) > 1:
        raise SystemExit('--precision got a comma list but --replicas '
                         'is 1 — heterogeneous mixes need a fleet')
    return [mixes[i % len(mixes)] for i in range(max(args.replicas, 1))]


def build_module_and_params(args, buckets, seed=None):
    """Toy module + params (checkpoint restore or seeded init) — shared
    by the single-replica and router paths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu.native.loader import chain_adjacency
    from se3_transformer_tpu.training.denoise import DenoiseConfig

    seed = args.seed if seed is None else seed
    cfg = DenoiseConfig(num_tokens=TOY_NUM_TOKENS, dim=8, dim_head=8,
                        heads=2, depth=2, num_degrees=2,
                        max_sparse_neighbors=4)
    module = cfg.build_module()
    rng = np.random.RandomState(seed)
    if args.checkpoint:
        from se3_transformer_tpu.training.checkpoint import CheckpointManager
        step = getattr(args, 'checkpoint_step', None)
        params = CheckpointManager(args.checkpoint).restore_params(step)
        print(f'restored params-only from {args.checkpoint}'
              f'{f" @ step {step}" if step is not None else ""}')
    else:
        L = buckets[0]
        params = module.init(
            jax.random.PRNGKey(seed),
            jnp.asarray(rng.randint(0, cfg.num_tokens, size=(1, L))),
            jnp.asarray(rng.normal(size=(1, L, 3)).astype(np.float32)),
            mask=jnp.ones((1, L), bool),
            adj_mat=jnp.asarray(chain_adjacency(L)),
            return_type=1)['params']
        print(f'no --checkpoint: initialized fresh params (seed {seed})')
    return cfg, module, params


def replica_mesh(i, devices=None):
    """A one-device mesh pinning replica `i` to its own device
    (round-robin over `devices`, default every device JAX sees). The
    engine's `mesh` argument is its placement mechanism; without one
    every replica's params and executables land on the first device and
    N replicas on a multi-chip host share one chip."""
    import jax

    from se3_transformer_tpu.parallel.mesh import make_mesh
    devices = jax.devices() if devices is None else devices
    return make_mesh([devices[i % len(devices)]], dp=1, sp=1, tp=1)


def request_lengths(args, buckets, max_len, rng):
    """Mixed-length stream: in-range lengths cycling across buckets,
    plus the oversize (must-reject) tail, shuffled."""
    lows = [1] + [b + 1 for b in buckets[:-1]]
    lengths = [int(rng.randint(lows[i % len(buckets)],
                               buckets[i % len(buckets)] + 1))
               for i in range(args.requests)]
    lengths += [max_len + int(rng.randint(1, 32))
                for _ in range(args.oversize)]
    rng.shuffle(lengths)
    return lengths


def main(argv=None):
    args = parse_args(argv)
    import jax
    if args.cpu:
        jax.config.update('jax_platforms', 'cpu')
    enable_compilation_cache()
    if args.host_mode:
        return serve_host(args)
    if args.fleet > 1:
        return serve_fleet(args)
    if args.replicas > 1:
        return serve_multi(args)
    import numpy as np

    from se3_transformer_tpu.inference import (
        AdmissionController, InferenceEngine, MicroBatcher,
        RequestRejected, ServeTelemetry,
    )
    from se3_transformer_tpu.observability import MetricLogger
    from se3_transformer_tpu.observability.schema import (
        SchemaError, validate_stream,
    )
    import jax.numpy as jnp

    buckets = tuple(int(b) for b in args.buckets.split(','))
    cfg, module, params = build_module_and_params(args, buckets)

    # ---- startup: AOT-compile every bucket, then arm the watchdog ---- #
    t0 = time.perf_counter()
    engine = InferenceEngine(
        module, params, buckets=buckets, batch_size=args.batch_size,
        return_type=1, precision=precision_mixes(args)[0],
        activation_dtype=jnp.bfloat16 if args.bf16 else None)
    print(f'warmup: compiled {len(engine.executables)} bucket '
          f'executables in {time.perf_counter() - t0:.1f}s '
          f'({engine.compile_seconds}, precision '
          f'{engine.precision_name})')

    admission = AdmissionController(max_len=engine.max_len,
                                    max_queue_depth=args.max_queue_depth)
    batcher = MicroBatcher(engine.run, buckets=engine.buckets,
                           batch_size=args.batch_size,
                           max_wait_ms=args.max_wait_ms,
                           admission=admission)
    logger = MetricLogger(args.metrics, run_meta=dict(
        mode='serve', buckets=list(buckets), batch_size=args.batch_size,
        dtype=engine.dtype_name, precision=engine.precision_name))
    telemetry = ServeTelemetry(engine, batcher, admission, logger)
    telemetry.arm()

    # ---- the request stream: lengths cycle across buckets ----------- #
    from se3_transformer_tpu.training.guardian import PreemptionGuard

    rng = np.random.RandomState(args.seed)
    lengths = request_lengths(args, engine.buckets, engine.max_len, rng)

    pending, flushed_at, interrupted = [], 0, None
    with PreemptionGuard() as guard:
        for length in lengths:
            if guard.stop_requested:
                # graceful preemption: stop admitting, drain what we
                # accepted, flush the bank — a mid-serve SIGTERM must
                # not lose the telemetry stream
                interrupted = guard.signame
                print(f'{interrupted}: graceful shutdown — draining '
                      f'{batcher.queue_depth} queued requests, flushing '
                      f'telemetry', flush=True)
                break
            tokens = rng.randint(0, cfg.num_tokens, size=length)
            coords = rng.normal(size=(length, 3)).astype(np.float32)
            try:
                pending.append(batcher.submit(tokens, coords))
            except RequestRejected as e:
                print(f'rejected: {e.code} {e.detail}')
                logger.log_record('step', mirror=False, step=len(pending),
                                  rejected=e.to_record())
            batcher.pump()
            if args.pace_ms:
                time.sleep(args.pace_ms / 1e3)
            if batcher.batches_dispatched - flushed_at >= args.flush_every:
                telemetry.flush()
                flushed_at = batcher.batches_dispatched
        # deadline-drain the stragglers, then close the stream (the
        # drain still runs under the guard: a SECOND signal just sets
        # the already-set flag instead of killing the drain)
        while batcher.queue_depth:
            wait = batcher.next_deadline()
            if wait:
                time.sleep(wait)
            batcher.pump()
    telemetry.flush()
    summary = telemetry.close()
    logger.close()

    # ---- gates + report --------------------------------------------- #
    ok = True
    unanswered = [p.request_id for p in pending if not p.ok]
    if unanswered:
        print(f'FAIL: {len(unanswered)} admitted requests unanswered')
        ok = False
    if telemetry.post_warmup_compiles:
        print(f'FAIL: {telemetry.post_warmup_compiles} compile events '
              f'after warmup — the AOT bucket contract is broken')
        ok = False
    if args.metrics:
        try:
            info = validate_stream(args.metrics)
            print(f'schema ok: {info["records"]} records {info["kinds"]}')
        except SchemaError as e:
            print(f'FAIL: telemetry stream invalid: {e}')
            ok = False

    report = dict(
        ok=ok,
        interrupted=interrupted,
        requests=dict(total=len(lengths), answered=len(pending) -
                      len(unanswered), **admission.snapshot()),
        batches=batcher.batches_dispatched,
        post_warmup_compiles=telemetry.post_warmup_compiles,
        compile_seconds=engine.stats()['compile_seconds'],
        # memory-per-bucket off the warmup cost ledger (the full
        # schema'd cost records are in the --metrics stream)
        peak_hbm_by_bucket=engine.stats()['peak_hbm_by_bucket'],
        latency_by_bucket={
            k: {p: v[p] for p in
                ('count', 'p50_ms', 'p95_ms', 'p99_ms', 'max_ms')}
            for k, v in summary['timing'].items()
            if k.startswith('bucket_')},
        request_latency_ms=summary['metrics']['request_latency_ms'],
        batch_fill=summary['metrics'].get('batch_fill'),
    )
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, 'w') as f:
            json.dump(report, f, indent=2)
        print(f'report -> {args.out}')
    return 0 if ok else 1


def serve_multi(args):
    """Multi-replica continuous-batching path (`--replicas N`)."""
    import numpy as np

    from se3_transformer_tpu.inference import (
        AdmissionController, InferenceEngine, RequestRejected,
    )
    from se3_transformer_tpu.observability import MetricLogger, PhaseTimer
    from se3_transformer_tpu.observability.schema import (
        SchemaError, validate_stream,
    )
    from se3_transformer_tpu.serving import (
        ReplicaWorker, Router, RouterTelemetry,
    )
    import jax.numpy as jnp

    buckets = tuple(int(b) for b in args.buckets.split(','))
    cfg, module, params = build_module_and_params(args, buckets)

    # ---- startup: N replicas, ONE shared PhaseTimer (the aggregate
    # per-bucket SLO surface), every bucket AOT-compiled per replica --- #
    t0 = time.perf_counter()
    timer = PhaseTimer()
    mixes = precision_mixes(args)
    engines = [InferenceEngine(
        module, params, buckets=buckets, batch_size=args.batch_size,
        return_type=1, timer=timer, precision=mixes[i],
        activation_dtype=jnp.bfloat16 if args.bf16 else None,
        mesh=replica_mesh(i), partition_rules='replicated')
        for i in range(args.replicas)]
    print(f'warmup: {args.replicas} replicas x '
          f'{len(engines[0].executables)} bucket executables in '
          f'{time.perf_counter() - t0:.1f}s (precision mixes '
          f'{[e.precision_name for e in engines]})')

    workers = [ReplicaWorker(i, e, max_wait_ms=args.max_wait_ms,
                             async_dispatch=args.async_dispatch)
               for i, e in enumerate(engines)]
    admission = AdmissionController(max_len=buckets[-1],
                                    max_queue_depth=args.max_queue_depth)
    # the router is a context manager: its dispatch executors shut down
    # when the block exits, ON ERROR PATHS TOO — a crashed serve loop
    # must not leak replica threads
    with Router(workers, admission=admission,
                max_retries=args.max_retries,
                default_timeout_s=args.timeout_s) as router:
        # materialize the swap weights BEFORE arming the compile
        # watchdog: a real rolling reload restores numpy leaves off the
        # async-checkpoint path (zero compiles); the smoke's stand-in —
        # a fresh seeded init — compiles eager init programs, which
        # must land in the warmup window, not against the AOT contract
        swap_params = None
        if args.swap_at is not None:
            _, _, swap_params = build_module_and_params(
                args, buckets, seed=args.seed + 1)
        logger = MetricLogger(args.metrics, run_meta=dict(
            mode='serve_multi', replicas=args.replicas,
            buckets=list(buckets), batch_size=args.batch_size,
            dtype=engines[0].dtype_name,
            precision_mixes=[e.precision_name for e in engines]))
        telemetry = RouterTelemetry(router, admission, logger)
        telemetry.arm()

        # ---- the request stream, with one mid-run rolling swap ------ #
        from se3_transformer_tpu.training.guardian import PreemptionGuard

        rng = np.random.RandomState(args.seed)
        lengths = request_lengths(args, buckets, router.max_len, rng)

        pending, flushed_at, swapped, interrupted = [], 0, False, None
        with PreemptionGuard() as guard:
            for i, length in enumerate(lengths):
                if guard.stop_requested:
                    # graceful preemption: stop admitting, let the
                    # router drain below — the bank must survive
                    interrupted = guard.signame
                    print(f'{interrupted}: graceful shutdown — '
                          f'draining {router.queue_depth} queued '
                          f'requests, flushing telemetry', flush=True)
                    break
                if args.swap_at is not None and i == args.swap_at \
                        and not swapped:
                    # same shapes, new values: the swap must compile
                    # NOTHING and drop NOTHING (the gates below prove
                    # both)
                    events = router.swap_weights(
                        swap_params, tag=f'seed_{args.seed + 1}')
                    swapped = True
                    print(f'rolling weight swap after request {i}: '
                          f'{len(events)} replicas swapped, '
                          f'{sum(e["drained_batches"] for e in events)} '
                          f'partial batches drained')
                tokens = rng.randint(0, cfg.num_tokens, size=length)
                coords = rng.normal(size=(length, 3)).astype(np.float32)
                try:
                    pending.append(router.submit(tokens, coords))
                except RequestRejected as e:
                    print(f'rejected: {e.code} {e.detail}')
                    logger.log_record('step', mirror=False,
                                      step=len(pending),
                                      rejected=e.to_record())
                router.pump()
                if args.pace_ms:
                    time.sleep(args.pace_ms / 1e3)
                if router.batches_dispatched - flushed_at >= \
                        args.flush_every:
                    telemetry.flush()
                    flushed_at = router.batches_dispatched
            # deadline-drain the stragglers, then close the stream
            while router.queue_depth:
                wait = router.next_deadline()
                if wait:
                    time.sleep(wait)
                elif args.async_dispatch:
                    # async mode: queue_depth includes executor-inflight
                    # rows that no deadline governs — yield, don't spin
                    time.sleep(0.001)
                router.pump()
    # __exit__ barriered on any async dispatches and shut the
    # executors down (no-op for synchronous replicas)
    telemetry.flush()
    summary = telemetry.close()
    logger.close()

    # ---- gates + report --------------------------------------------- #
    ok = True
    unanswered = [p.request_id for p in pending if not p.ok]
    if unanswered:
        print(f'FAIL: {len(unanswered)} admitted requests unanswered '
              f'(the rolling swap must drop NOTHING)')
        ok = False
    if telemetry.post_warmup_compiles:
        print(f'FAIL: {telemetry.post_warmup_compiles} compile events '
              f'after warmup — a weight swap or mixed-length stream '
              f'broke the AOT contract')
        ok = False
    if not router.continuous_admissions and not interrupted:
        # an interrupted run may have been preempted before any slot
        # ever held two requests — graceful preemption must exit 0
        print('FAIL: zero continuous admissions — no request ever '
              'joined an in-flight bucket slot, the router degenerated '
              'to flush barriers')
        ok = False
    if args.swap_at is not None and not interrupted and \
            len(router.swap_events) != args.replicas:
        # an interrupted run may have been preempted before swap_at —
        # a graceful shutdown is not a failed swap
        print(f'FAIL: rolling swap incomplete: '
              f'{len(router.swap_events)} swap events for '
              f'{args.replicas} replicas')
        ok = False
    if args.metrics:
        try:
            info = validate_stream(args.metrics)
            print(f'schema ok: {info["records"]} records {info["kinds"]}')
        except SchemaError as e:
            print(f'FAIL: telemetry stream invalid: {e}')
            ok = False

    report = dict(
        ok=ok,
        interrupted=interrupted,
        replicas=args.replicas,
        precision_mixes=[e.precision_name for e in engines],
        requests=dict(total=len(lengths), answered=len(pending) -
                      len(unanswered), **admission.snapshot()),
        batches=router.batches_dispatched,
        continuous_admissions=router.continuous_admissions,
        deadline_flushes=router.deadline_flushes,
        swaps=dict(count=len(router.swap_events),
                   events=router.swap_events),
        post_warmup_compiles=telemetry.post_warmup_compiles,
        per_replica={str(w.id): w.snapshot() for w in router.workers},
        latency_by_bucket={
            k: {p: v[p] for p in
                ('count', 'p50_ms', 'p95_ms', 'p99_ms', 'max_ms')}
            for k, v in summary['timing'].items()
            if k.startswith('bucket_')},
        request_latency_ms=summary['metrics']['request_latency_ms'],
    )
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, 'w') as f:
            json.dump(report, f, indent=2)
        print(f'report -> {args.out}')
    return 0 if ok else 1


def serve_host(args):
    """One fleet host (`--host`): the serve_multi stack behind a TCP
    RPC surface, parked until SIGTERM (graceful drain + final records,
    exit 0)."""
    import jax.numpy as jnp

    from se3_transformer_tpu.faults import FaultInjector
    from se3_transformer_tpu.inference import (
        AdmissionController, InferenceEngine,
    )
    from se3_transformer_tpu.observability import MetricLogger, PhaseTimer
    from se3_transformer_tpu.observability.schema import (
        SchemaError, validate_stream,
    )
    from se3_transformer_tpu.serving import (
        HostServer, ReplicaWorker, Router, RouterTelemetry, serve_binary,
        serve_socket,
    )
    from se3_transformer_tpu.training.guardian import PreemptionGuard

    buckets = tuple(int(b) for b in args.buckets.split(','))
    cfg, module, params = build_module_and_params(args, buckets)

    t0 = time.perf_counter()
    timer = PhaseTimer()
    mixes = precision_mixes(args)
    injector = FaultInjector(seed=args.seed)
    engines = [InferenceEngine(
        module, params, buckets=buckets, batch_size=args.batch_size,
        return_type=1, timer=timer, precision=mixes[i],
        activation_dtype=jnp.bfloat16 if args.bf16 else None,
        mesh=replica_mesh(i), partition_rules='replicated')
        for i in range(max(args.replicas, 1))]
    print(f'host {args.host_id}: warmup {len(engines)} replicas x '
          f'{len(engines[0].executables)} bucket executables in '
          f'{time.perf_counter() - t0:.1f}s', flush=True)
    workers = [ReplicaWorker(i, e, max_wait_ms=args.max_wait_ms,
                             async_dispatch=args.async_dispatch,
                             fault_injector=injector)
               for i, e in enumerate(engines)]
    admission = AdmissionController(max_len=buckets[-1],
                                    max_queue_depth=args.max_queue_depth)

    ok = True
    with Router(workers, admission=admission,
                max_retries=args.max_retries,
                default_timeout_s=args.timeout_s) as router:
        logger = MetricLogger(args.metrics, run_meta=dict(
            mode='serve_host', host_id=args.host_id,
            replicas=len(engines), buckets=list(buckets),
            batch_size=args.batch_size, seed=args.seed,
            precision_mixes=[e.precision_name for e in engines]))
        telemetry = RouterTelemetry(router, admission, logger)
        telemetry.arm()

        # the chaos hook: after a swap restores --poison-step, every
        # dispatch fails deterministically (an every=1 injector plan)
        # until a DIFFERENT step is restored — "the new weights are bad
        # on this host", which the fleet's canary gate must catch
        poison_plans = []

        def on_swap(payload, events, _inj=injector):
            if args.poison_step is None:
                return
            tag = (events[0].get('tag') or '') if events else ''
            restored = tag.rsplit('@', 1)[-1]
            if restored == str(args.poison_step):
                poison_plans.append(_inj.plan(
                    'replica_dispatch', 'exception', every=1))
                print(f'host {args.host_id}: POISON ARMED — step '
                      f'{restored} restored, every dispatch now fails '
                      f'until a different step is swapped in',
                      flush=True)
            elif poison_plans:
                for p in poison_plans:
                    p.max_fires = p.fires    # exhausted: disarmed
                del poison_plans[:]
                print(f'host {args.host_id}: poison disarmed (step '
                      f'{restored} restored)', flush=True)

        host_server = HostServer(router, host_id=args.host_id,
                                 telemetry=telemetry,
                                 flush_every_batches=args.flush_every,
                                 on_swap=on_swap)
        if args.transport == 'binary':
            sock = serve_binary(host_server, port=args.port)
            # every serve record this host flushes carries the wire's
            # own counters (schema'd `transport` section)
            telemetry.transport_source = sock.transport_stats
        else:
            sock = serve_socket(host_server, port=args.port)
        print(f'FLEET HOST READY host={args.host_id} port={sock.port} '
              f'transport={args.transport}', flush=True)
        with PreemptionGuard() as guard:
            while not guard.stop_requested:
                time.sleep(0.05)
        print(f'host {args.host_id}: {guard.signame} — graceful '
              f'shutdown: close socket, drain router, flush the bank',
              flush=True)
        sock.close()
        host_server.stop(drain=True)
    # __exit__ -> close(): drained, retries settled, executors down
    telemetry.flush()
    telemetry.fault_flush(injector=injector, label=f'host_{args.host_id}')
    telemetry.close()
    logger.close()

    if telemetry.post_warmup_compiles:
        print(f'FAIL: host {args.host_id}: '
              f'{telemetry.post_warmup_compiles} post-warmup compile '
              f'events — a swap or mixed-length stream broke the AOT '
              f'contract', flush=True)
        ok = False
    if args.metrics:
        try:
            info = validate_stream(args.metrics)
            print(f'host {args.host_id}: schema ok '
                  f'({info["records"]} records {info["kinds"]})',
                  flush=True)
        except SchemaError as e:
            print(f'FAIL: host {args.host_id}: telemetry stream '
                  f'invalid: {e}', flush=True)
            ok = False
    print(f'host {args.host_id}: served '
          f'{sum(w.served_rows for w in router.workers)} rows in '
          f'{router.batches_dispatched} batches, '
          f'{len(router.swap_events)} swaps, '
          f'{router.request_failures} structured failures', flush=True)
    return 0 if ok else 1


# --------------------------------------------------------------------- #
# fleet-worker process management (shared with fleet_chaos_smoke)
# --------------------------------------------------------------------- #
def host_command(host_id, *, port=0, buckets='8,16', batch_size=2,
                 replicas=1, seed=0, max_wait_ms=10.0, timeout_s=None,
                 max_retries=1, max_queue_depth=None, checkpoint=None,
                 checkpoint_step=None, metrics=None, poison_step=None,
                 bf16=False, async_dispatch=False, cpu=True,
                 transport='binary'):
    """The argv for one `--host` worker process."""
    cmd = [sys.executable, os.path.abspath(__file__), '--host',
           '--host-id', str(host_id), '--port', str(port),
           '--buckets', str(buckets), '--batch-size', str(batch_size),
           '--replicas', str(replicas), '--seed', str(seed),
           '--max-wait-ms', str(max_wait_ms),
           '--max-retries', str(max_retries),
           '--transport', str(transport)]
    if cpu:
        cmd.append('--cpu')
    if bf16:
        cmd.append('--bf16')
    if async_dispatch:
        cmd.append('--async-dispatch')
    if timeout_s is not None:
        cmd += ['--timeout-s', str(timeout_s)]
    if max_queue_depth is not None:
        cmd += ['--max-queue-depth', str(max_queue_depth)]
    if checkpoint:
        cmd += ['--checkpoint', checkpoint]
    if checkpoint_step is not None:
        cmd += ['--checkpoint-step', str(checkpoint_step)]
    if metrics:
        cmd += ['--metrics', metrics]
    if poison_step is not None:
        cmd += ['--poison-step', str(poison_step)]
    return cmd


def spawn_host(host_id, **kw):
    """Start one `--host` worker (stdout piped — call
    `wait_host_ready` to block until its READY line AND keep the pipe
    drained afterwards, or the worker wedges on a full pipe)."""
    import subprocess
    return subprocess.Popen(host_command(host_id, **kw),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            bufsize=1)


def wait_host_ready(proc, timeout_s=300.0):
    """Block until the worker prints its READY line; returns
    `(port, sink)` where `sink` is the list a daemon reader thread
    keeps appending the worker's output into (started immediately, so
    the pipe can never fill and wedge the worker, AND the deadline is
    enforced even against a worker that wedges without printing — a
    blocking readline here would wait forever)."""
    import threading
    sink = []
    eof = threading.Event()

    def drain(p=proc, s=sink):
        try:
            for line in p.stdout:
                s.append(line)
        finally:
            eof.set()

    threading.Thread(target=drain, daemon=True).start()
    deadline = time.monotonic() + timeout_s
    scanned = 0
    while time.monotonic() < deadline:
        n = len(sink)
        while scanned < n:
            line = sink[scanned]
            scanned += 1
            if 'FLEET HOST READY' in line:
                port = int(line.split('port=')[1].split()[0])
                return port, sink
        if eof.is_set() and scanned >= len(sink):
            raise RuntimeError(
                f'fleet host died during warmup (rc={proc.poll()}):\n'
                + ''.join(sink[-30:]))
        time.sleep(0.05)
    raise RuntimeError('fleet host not READY within '
                       f'{timeout_s}s:\n' + ''.join(sink[-30:]))


def stop_host(proc, timeout_s=90.0):
    """Graceful stop: SIGTERM, wait, escalate to SIGKILL only on a
    wedge. Returns the exit code (0 = the graceful-shutdown contract
    held)."""
    import signal
    import subprocess
    if proc.poll() is None:
        try:
            proc.send_signal(signal.SIGTERM)
        except OSError:
            pass
    try:
        return proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10.0)
        return proc.returncode


def serve_fleet(args):
    """Cross-host front-end (`--fleet N`): spawn N `--host` workers,
    route the stream through a FleetRouter, bank the `fleet` record,
    SIGTERM the workers (each must exit 0)."""
    import numpy as np

    from se3_transformer_tpu.inference.admission import RequestRejected
    from se3_transformer_tpu.observability import MetricLogger
    from se3_transformer_tpu.observability.schema import (
        SchemaError, validate_stream,
    )
    from se3_transformer_tpu.serving import (
        BinaryTransport, FleetRouter, SocketTransport,
    )
    from se3_transformer_tpu.training.guardian import PreemptionGuard

    buckets = tuple(int(b) for b in args.buckets.split(','))
    procs, sinks, ports = [], [], []
    print(f'spawning {args.fleet} fleet hosts...', flush=True)
    for i in range(args.fleet):
        procs.append(spawn_host(
            i, buckets=args.buckets, batch_size=args.batch_size,
            replicas=args.replicas, seed=args.seed,
            max_wait_ms=args.max_wait_ms, timeout_s=args.timeout_s,
            max_retries=args.max_retries,
            max_queue_depth=args.max_queue_depth,
            checkpoint=args.checkpoint,
            checkpoint_step=args.checkpoint_step, bf16=args.bf16,
            async_dispatch=args.async_dispatch, cpu=True,
            transport=args.transport))
    try:
        for p in procs:
            port, sink = wait_host_ready(p)
            ports.append(port)
            sinks.append(sink)
        print(f'fleet up: {args.fleet} hosts on ports {ports}',
              flush=True)

        if args.transport == 'binary':
            transports = {i: BinaryTransport('127.0.0.1', port)
                          for i, port in enumerate(ports)}
        else:
            transports = {i: SocketTransport('127.0.0.1', port)
                          for i, port in enumerate(ports)}
        ok = True
        rng = np.random.RandomState(args.seed)
        lengths = request_lengths(args, buckets, buckets[-1], rng)
        logger = MetricLogger(args.metrics, run_meta=dict(
            mode='serve_fleet', hosts=args.fleet, ports=ports,
            buckets=list(buckets), batch_size=args.batch_size,
            seed=args.seed))
        pending, interrupted = [], None
        with FleetRouter(transports, max_retries=args.max_retries,
                         default_timeout_s=args.timeout_s) as fleet:
            with PreemptionGuard() as guard:
                for length in lengths:
                    if guard.stop_requested:
                        interrupted = guard.signame
                        print(f'{interrupted}: graceful shutdown — '
                              f'draining the fleet, flushing the bank',
                              flush=True)
                        break
                    tokens = rng.randint(0, TOY_NUM_TOKENS, size=length)
                    coords = rng.normal(
                        size=(length, 3)).astype(np.float32)
                    try:
                        pending.append(fleet.submit(tokens, coords))
                    except RequestRejected as e:
                        print(f'rejected: {e.code} {e.detail}')
                        logger.log_record('step', mirror=False,
                                          step=len(pending),
                                          rejected=e.to_record())
                    fleet.pump()
                    if args.pace_ms:
                        time.sleep(args.pace_ms / 1e3)
                fleet.drain()
            body = fleet.record_body(pending, label='serve_fleet')
            logger.log_record('fleet', mirror=False, **body)
        logger.close()
        for t in transports.values():
            if hasattr(t, 'close'):
                t.close()    # joins the binary arm's reader threads

        lost = [p.request_id for p in pending if not p.done]
        # a host-side RequestRejected (oversize before the first bucket
        # scrape landed) is a structured outcome, not a lost answer
        unanswered = [p.request_id for p in pending
                      if not p.ok
                      and not isinstance(p.error, RequestRejected)]
        if lost:
            print(f'FAIL: {len(lost)} requests LOST fleet-wide')
            ok = False
        if unanswered:
            print(f'FAIL: {len(unanswered)} in-range requests resolved '
                  f'unanswered (healthy fleet must answer everything)')
            ok = False
        if args.metrics:
            try:
                info = validate_stream(args.metrics)
                print(f'schema ok: {info["records"]} records '
                      f'{info["kinds"]}')
            except SchemaError as e:
                print(f'FAIL: telemetry stream invalid: {e}')
                ok = False
    finally:
        rcs = [stop_host(p) for p in procs]
    print(f'fleet hosts stopped: rcs {rcs}')
    if any(rc != 0 for rc in rcs):
        print('FAIL: a fleet host exited non-zero on graceful SIGTERM')
        ok = False

    report = dict(ok=ok, interrupted=interrupted, hosts=args.fleet,
                  host_rcs=rcs,
                  requests=dict(total=len(lengths),
                                submitted=len(pending),
                                answered=len(pending) - len(unanswered),
                                lost=len(lost)),
                  fleet=dict(answered=body['answered'],
                             cross_host_retries=body['cross_host_retries'],
                             request_failures=body['request_failures'],
                             heartbeats=body['heartbeats']))
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, 'w') as f:
            json.dump(report, f, indent=2)
        print(f'report -> {args.out}')
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
