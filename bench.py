"""Benchmark: denoise-style training throughput on the flagship config.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Config follows BASELINE.json's north star (1024 nodes, num_degrees=4,
kNN neighbors) in a denoise.py-scale model. The reference publishes no
benchmark numbers (BASELINE.md: "published": {}), so vs_baseline is
reported against this repo's own first recorded value (RECORD below);
1.0 until a prior record exists.

A bare `python bench.py` measures the chip and fails when JAX finds no
TPU: there is no probe, no retry and no CPU substitute. The CPU toy is
a different program under a different unit and runs only when asked for
by name (`bench.main('cpu', ...)`, as tests/test_bench_record.py does).
All heavy imports happen inside main() so the CPU-mesh harness modes can
set their flags before jax initializes its backends.
"""
import json
import os
import sys
import time

# nodes*steps/sec/chip anchors on TPU v5e-1, rolled forward each round so
# vs_baseline measures THIS round's progress against the last round's
# banked session records (each path compares against its own record —
# they run different programs). Round-5 session (16:06-17:11Z,
# code_rev 4fff503, BENCH_SESSION.jsonl): conservative 337.07 (the
# idle-host block_ab arm; the bench-stage row was 331.11), fast 536.76.
# ESTIMATOR NOTE: chip timing moved to best-of-two windows this round
# (host dispatch noise is one-sided); the fast anchor re-measured 536.94
# under it — indistinguishable — and both anchors are best *observed*
# windows, so best-of-two vs them carries no built-in tailwind beyond
# the ~1-2% single-session spread. Round-4 anchors were 296.26 / 536.69;
# round-3 262.38 / 309.57.
RECORD = 337.07
FAST_RECORD = 536.76

# what a bare `python bench.py` runs: True = the perf knobs
# (recipes.flagship_fast), False = the conservative recipe. A failure of
# either is a failure — no path re-runs as the other.
DEFAULT_FAST = True


def main(backend: str, fast=None, fallback_reason=None, pipelined=False):
    """backend='tpu' measures the flagship on the chip and raises when
    JAX's first device is not a TPU. backend='cpu' runs the FROZEN CPU
    toy (explicit callers only — tests/test_bench_record.py); its record
    is labelled backend=cpu under a cpu-host unit and carries the
    caller's `fallback_reason` (why a toy record was asked for).

    fast=True enables the validated perf knobs (shared radial trunk,
    basis-fused Pallas kernel, bf16 radial) — same model family, same
    training task; fast=False is the conservative recipe. Default: the
    SE3_TPU_BENCH_FAST env var ('1'/'0'/...), else DEFAULT_FAST. A path
    that raises fails the run; nothing re-runs as the other path.
    Accuracy evidence: equivariance_l2 is measured on CPU runs (and on
    TPU with SE3_TPU_BENCH_EQ=1, a second full-flagship compile at f32
    matmul precision); default TPU runs measure a reduced-width twin.

    pipelined=True (`python bench.py --pipelined`) measures a DIFFERENT
    program from the records above: host batches are REBUILT every step
    (the synchronous records reuse one fixed device batch, i.e. zero
    host batch-build time) and the run compares a synchronous
    build->transfer->step loop against the training.pipeline overlapped
    path (BatchProducer thread + device_prefetch) on the SAME
    executable. The record's value is the pipelined rate; it carries
    the sync arm's rate, a `pipeline` payload (prefetch hits/stalls,
    producer-bound vs device-bound verdict — same shape as the schema'd
    pipeline JSONL record), and never compares against the synchronous
    RECORD anchors."""
    import jax

    on_chip = backend != 'cpu'

    if fast is None:
        env = os.environ.get('SE3_TPU_BENCH_FAST', '').lower()
        fast = env in ('1', 'true', 'yes', 'on') if env else DEFAULT_FAST

    if on_chip:
        platform = jax.devices()[0].platform
        if platform != 'tpu':
            raise SystemExit(
                f'bench: needs a TPU, JAX found {platform!r}; the CPU toy '
                f"runs only when asked for by name (bench.main('cpu'))")
    else:
        jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np
    import optax

    from se3_transformer_tpu.models.se3_transformer import SE3TransformerModule
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training import recipes
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    from se3_transformer_tpu.utils.helpers import fetch_sync

    enable_compilation_cache()

    # kernel-tuning consult delta: which block picks this record's
    # executables resolved from the measured table vs the heuristic
    # (kernels/tuning.py). Snapshot/delta, not reset: one process may
    # run several stages off one consult log.
    # The kernel jit caches must be dropped first: picks resolve at
    # trace time, so a kernel already traced by an earlier stage (e.g.
    # the tune stage's adoption proof) would reuse its executable and
    # record NOTHING here — a record benched under tuned blocks
    # masquerading as consult-free.
    from se3_transformer_tpu.kernels import tuning as kernel_tuning
    kernel_tuning.clear_kernel_caches()
    tuning_snap = kernel_tuning.snapshot()

    if on_chip:
        # the tracked config (BASELINE.md): SE3Transformer flagship at
        # 1024 nodes, num_degrees=4, kNN k=32. dim=64 is the max width
        # that fits one v5e at this node count (recipes.py); a toy-width
        # body cannot demonstrate MXU utilization (VERDICT r2 #4).
        # SE3_TPU_BENCH_BATCH raises the per-step batch (per-chip
        # throughput scales with batch while HBM lasts; the reference's
        # own training aggregates 16 micro-batches, denoise.py:13,55) —
        # the metric label carries b= when != 1.
        num_nodes, num_degrees, batch, num_neighbors, steps = 1024, 4, 1, 32, 20
        batch = int(os.environ.get('SE3_TPU_BENCH_BATCH', batch))
        dim = 64
        recipe_name = 'flagship_fast' if fast else 'flagship'
        # SE3_TPU_BENCH_CHUNKS overrides the recipe's edge_chunks (0 =
        # unchunked). Used by the session's batched record so the bench
        # runs the SAME chunk setting a size sweep measured as fitting for
        # the elected batch (a b>1 that fits chunked can OOM unchunked);
        # the label carries ec= whenever the override is set, so an
        # overridden record is always distinguishable from a bare run.
        chunk_env = os.environ.get('SE3_TPU_BENCH_CHUNKS', '')
        overrides = dict(output_degrees=2, reduce_dim_out=True)
        if chunk_env != '':
            overrides['edge_chunks'] = int(chunk_env) or None
        # SE3_TPU_BENCH_REMAT overrides the reversible remat policy
        # (e.g. 'save_conv_outputs' — the backward replay then skips the
        # dominant radial contraction, ops/trunk.py; 'none' forces the
        # policy OFF, the control arm now that the flagship_fast recipe
        # defaults it on). Labelled rp= so an overridden record never
        # masquerades as the recipe default.
        remat_env = os.environ.get('SE3_TPU_BENCH_REMAT', '')
        if remat_env:
            overrides['remat_policy'] = (
                None if remat_env.lower() == 'none' else remat_env)
        # vector head for the denoise objective: the recipe default
        # output_degrees=1 is scalar-out (return_type coerced to 0)
        module = recipes.RECIPES[recipe_name](dim=dim, **overrides)
        num_degrees = module.num_degrees
        label = f'{recipe_name},dim={dim},depth={module.depth}' + (
            f',b={batch}' if batch != 1 else '') + (
            f',ec={int(chunk_env)}' if chunk_env != '' else '') + (
            f',rp={remat_env}' if remat_env else '')
    else:
        # the CPU toy (explicit callers only): tiny config, honestly
        # labelled backend=cpu. FROZEN DEFINITION (VERDICT r3 weak #5):
        # this branch runs the exact r03 toy program — fast knobs pinned
        # as an explicit dict (decoupled from whatever 'fast' means in
        # future recipes), steps=10, label 'toy,dim=8,depth=2' + ',fast'
        # — so the CPU trend metric stays comparable round over round.
        # The caller's `fast` is deliberately ignored.
        fast = True
        num_nodes, num_degrees, batch, num_neighbors, steps = 128, 2, 1, 8, 10
        perf = dict(shared_radial_hidden=True, fuse_basis=True,
                    radial_bf16=True)
        module = SE3TransformerModule(
            num_tokens=24, dim=8, dim_head=8, heads=2, depth=2,
            attend_self=True, input_degrees=1, num_degrees=num_degrees,
            output_degrees=2, reduce_dim_out=True, differentiable_coors=True,
            num_neighbors=num_neighbors, **perf)
        label = 'toy,dim=8,depth=2'

    rng = np.random.RandomState(0)
    if on_chip:
        # flagship takes continuous degree-0 features (no token table)
        seqs = jnp.asarray(rng.normal(size=(batch, num_nodes, dim)),
                           jnp.float32)
    else:
        seqs = jnp.asarray(rng.randint(0, 24, (batch, num_nodes)))
    coords = jnp.asarray(np.cumsum(
        rng.normal(size=(batch, num_nodes, 3)), axis=1), jnp.float32)
    coords = coords - coords.mean(axis=1, keepdims=True)
    masks = jnp.ones((batch, num_nodes), bool)

    def loss_fn(params, data, key):
        noise = jax.random.normal(key, data['coords'].shape,
                                  data['coords'].dtype)
        noised = data['coords'] + noise
        out = module.apply({'params': params}, data['seqs'], noised,
                           mask=data['masks'], return_type=1)
        loss = (((noised + out) - data['coords']) ** 2).sum(-1).mean()
        return loss, dict()

    # jit the init: eager init dispatches thousands of tiny ops one by
    # one and takes minutes at 1024 nodes
    init_fn = jax.jit(module.init, static_argnames=('return_type',))
    params = init_fn(jax.random.PRNGKey(0), seqs, coords, mask=masks,
                     return_type=1)['params']
    optimizer = optax.adam(1e-4)
    opt_state = optimizer.init(params)
    step = make_sharded_train_step(loss_fn, optimizer)

    data = dict(seqs=seqs, coords=coords, masks=masks)
    key = jax.random.PRNGKey(1)

    # AOT-compile once: the same executable serves the FLOP count (MFU
    # estimate), the cost ledger, and the benchmark loop —
    # lower().compile() does not populate the jit cache, so executing
    # `step` afterwards would compile the multi-minute flagship program
    # a second time
    step_flops = None
    cost_body = None
    exec_fn = step
    try:
        compiled = step.lower(params, opt_state, data, key).compile()
        exec_fn = compiled
        from se3_transformer_tpu.observability.costs import cost_payload
        cost_body = cost_payload(compiled, label=label)
        step_flops = cost_body['flops'] \
            if cost_body['source'] == 'cost_analysis' else None
    except Exception as e:
        # the ledger must never cost the timing: a cost/introspection
        # failure falls back to the uninstrumented jit path
        print(f'bench: cost introspection unavailable '
              f'({type(e).__name__}: {e})', file=sys.stderr)

    # warmup (fetch_sync: an early-returning block here would leak
    # warmup work into the timed window)
    params, opt_state, loss, _ = exec_fn(params, opt_state, data, key)
    fetch_sync(loss)

    # retrace watchdog (observability.runtime): arm on the warmed-up
    # trace cache; any post-warmup retrace marks the record — a silent
    # recompile inside a timed window is exactly the class of artifact
    # the loss-trajectory check cannot see
    from se3_transformer_tpu.observability import RetraceWatchdog
    watchdog = RetraceWatchdog({'train_step': step})
    watchdog.check()  # first check arms

    # keep dispatch async (block only at the end — same timing semantics
    # as before) but RETAIN every step's loss: the 19:29Z session record
    # measured an impossible 411 ms conservative step and the losses
    # that would have exposed (or exonerated) it were discarded. The
    # trajectory now travels with the record.
    # Two timed windows, rate from the BEST one: host dispatch latency
    # spikes are strictly additive — min-over-windows removes one-sided
    # noise (the 16:57Z rehearsal measured 519 on code that benched 537
    # in-session minutes earlier).
    # Both window rates travel with the record. Training state carries
    # across windows, so the loss trajectory spans all 2*steps steps.
    losses = []
    window_rates = []
    pipeline_snapshot = None
    sync_rate = None
    if pipelined:
        # ---- pipelined data-path A/B -------------------------------- #
        # Different program from the fixed-batch records: host batches
        # are REBUILT per step in both arms, so the comparison isolates
        # the overlap (producer thread + device prefetch) from the host
        # work itself. Both arms run the SAME compiled executable (no
        # second compile on chip) and two windows each, best-of-window
        # (the established one-sided-noise estimator).
        from se3_transformer_tpu.training.pipeline import (
            BatchProducer, PipelineStats, device_prefetch,
        )

        host_rng = np.random.RandomState(7)

        def host_batch(_i):
            if on_chip:
                s = host_rng.normal(size=(batch, num_nodes, dim)) \
                    .astype(np.float32)
            else:
                s = host_rng.randint(0, 24, (batch, num_nodes)) \
                    .astype(np.int32)
            c = np.cumsum(host_rng.normal(size=(batch, num_nodes, 3)),
                          axis=1).astype(np.float32)
            c -= c.mean(axis=1, keepdims=True)
            return dict(seqs=s, coords=c,
                        masks=np.ones((batch, num_nodes), bool))

        def run_window(batches_iter):
            nonlocal params, opt_state, key
            win_losses = []
            t0 = time.monotonic()
            n = 0
            for b in batches_iter:
                key, sub = jax.random.split(key)
                params, opt_state, loss, _ = exec_fn(params, opt_state,
                                                     b, sub)
                win_losses.append(loss)
                n += 1
            # same window-close semantics as the synchronous bench:
            # host-materialize the chain tail, then stop the clock
            last = float(win_losses[-1])
            fetch_sync(min(jax.tree_util.tree_leaves(params),
                           key=lambda l: l.size))
            dt_w = time.monotonic() - t0
            losses.extend([float(l) for l in win_losses[:-1]] + [last])
            return batch * num_nodes * n / dt_w

        sync_rates, pipe_rates = [], []
        for _ in range(2):
            sync_rates.append(run_window(
                {k: jnp.asarray(v) for k, v in host_batch(i).items()}
                for i in range(steps)))
        stats = PipelineStats(depth=2, capacity=4)
        for _ in range(2):
            with BatchProducer((host_batch(i) for i in range(steps)),
                               capacity=4) as producer:
                pipe_rates.append(run_window(device_prefetch(
                    producer, depth=2, stats=stats)))
        sync_rate = max(sync_rates)
        window_rates = pipe_rates
        nodes_steps_per_sec = max(pipe_rates)
        pipeline_snapshot = stats.snapshot()
        label += ',pipelined'
    # the CPU toy keeps its FROZEN single-window
    # definition (round-over-round trend comparability); only chip
    # records get the best-of-two estimator
    n_windows = 0 if pipelined else (2 if on_chip else 1)
    for _ in range(n_windows):
        win_losses = []
        t0 = time.monotonic()
        for _ in range(steps):
            key, sub = jax.random.split(key)
            params, opt_state, loss, _ = exec_fn(
                params, opt_state, data, sub)
            win_losses.append(loss)
        # close the window by HOST-MATERIALIZING the chain tail
        # (utils.helpers.fetch_sync). Only the TAIL is fetched inside the
        # window (final loss gates the last forward, one small param leaf
        # gates the optimizer tail) — fetching every loss here would add
        # a host round-trip per step to dt; the earlier losses are
        # floated after the clock stops.
        last = float(win_losses[-1])
        fetch_sync(min(jax.tree_util.tree_leaves(params),
                       key=lambda l: l.size))
        dt = time.monotonic() - t0
        losses += [float(l) for l in win_losses[:-1]] + [last]
        window_rates.append(batch * num_nodes * steps / dt)

    if not pipelined:
        nodes_steps_per_sec = max(window_rates)
    dt = batch * num_nodes * steps / nodes_steps_per_sec

    # post-window watchdog snapshot: retrace count + device memory
    # (guarded — a diagnostics failure must not lose the timing)
    retrace_post_warmup = None
    hbm_peak_bytes = None
    try:
        snap = watchdog.check()
        retrace_post_warmup = len(snap['retraced'])
        if snap.get('memory'):
            hbm_peak_bytes = snap['memory'].get('peak_bytes_in_use')
    except Exception as e:  # noqa: BLE001
        print(f'watchdog snapshot failed ({type(e).__name__}: {e})',
              file=sys.stderr)

    # equivariance L2 error of the trained model (the BASELINE metric's
    # second component)
    eq_err = None
    eq_scope = None
    eq_env = os.environ.get('SE3_TPU_BENCH_EQ', '').lower()
    # On TPU, full-flagship equivariance is a SECOND multi-minute compile
    # at f32 matmul precision — opt into it with SE3_TPU_BENCH_EQ=1. The
    # DEFAULT chip record instead measures a reduced-width twin of the
    # same recipe (seconds to compile), so the official record carries a
    # non-null equivariance_l2 (VERDICT r3 missing #5), labelled with
    # its scope. SE3_TPU_BENCH_EQ=0 skips both.
    from se3_transformer_tpu.utils.validation import equivariance_l2
    if eq_env in ('1', 'true', 'yes', 'on') \
            or (jax.default_backend() == 'cpu'
                and eq_env not in ('0', 'false', 'no', 'off')):
        eq_err = equivariance_l2(module, params, seqs, coords, masks)
    elif on_chip and eq_env not in ('0', 'false', 'no', 'off'):
        twin = recipes.RECIPES[recipe_name](
            dim=16, depth=2, num_neighbors=8, output_degrees=2,
            reduce_dim_out=True)
        t_n = 128
        t_feats = jnp.asarray(rng.normal(size=(1, t_n, 16)), jnp.float32)
        t_coors = jnp.asarray(rng.normal(size=(1, t_n, 3)) * 2,
                              jnp.float32)
        t_mask = jnp.ones((1, t_n), bool)
        t_params = jax.jit(twin.init, static_argnames=('return_type',))(
            jax.random.PRNGKey(0), t_feats, t_coors, mask=t_mask,
            return_type=1)['params']
        eq_err = equivariance_l2(twin, t_params, t_feats, t_coors, t_mask)
        eq_scope = f'reduced_twin({recipe_name},dim=16,depth=2,' \
                   f'deg={twin.num_degrees},n={t_n},k=8)'

    actual = jax.default_backend()
    # the chip branch verified at entry that the device is a TPU;
    # RECORD/FAST_RECORD are v5e numbers and the peaks come from the
    # table keyed by this device_kind (an unknown kind raises)
    device_kind = jax.devices()[0].device_kind if on_chip else None
    # each path compares against its own TPU flagship record (different
    # programs); a CPU toy or batch!=1 run measures a different
    # workload, so comparing would fabricate a regression/speedup
    # pipelined records measure a different program (per-step host batch
    # rebuild) — comparing them to the fixed-batch anchors would
    # fabricate a regression, so they self-compare against their own
    # sync arm instead (pipelined_vs_sync below)
    ref = FAST_RECORD if fast else RECORD
    vs = nodes_steps_per_sec / ref \
        if (ref and on_chip and batch == 1 and not pipelined) else 1.0
    record = {
        'metric': f'denoise_train_nodes_steps_per_sec_per_chip'
                  f'({label},n={num_nodes},deg={num_degrees},'
                  f'k={num_neighbors},'
                  f'backend={actual}{",fast" if fast else ""})',
        'value': round(nodes_steps_per_sec, 2),
        'unit': f'nodes*steps/sec/{"chip" if on_chip else "cpu-host"}',
        'vs_baseline': round(vs, 3),
        'equivariance_l2': eq_err,
        'step_ms': round(dt / steps * 1e3, 2),
        'window_rates': [round(r, 2) for r in window_rates],
        # optimizer steps the loss trajectory spans (2*steps once both
        # windows complete) — keeps loss_last comparable across rounds
        # whose window counts differ
        'steps_trained': len(losses),
        # the estimator, explicit (ADVICE r5 #1): cross-round comparisons
        # must never infer it from len(window_rates)
        'timing': 'best-of-2' if (on_chip or pipelined) else 'frozen-toy',
    }
    try:
        # adopted-vs-heuristic block picks travel with the number: a
        # record benched under a tuned table entry must never be read as
        # a heuristic-pick measurement (kernels/tuning.py)
        record['kernel_tuning'] = kernel_tuning.consult_summary(
            kernel_tuning.consults_since(tuning_snap))
    except Exception as e:  # noqa: BLE001 - diagnostics must not lose
        # the timing already measured
        print(f'kernel tuning summary failed ({type(e).__name__}: {e})',
              file=sys.stderr)
    if pipelined:
        record['mode'] = 'pipelined'
        # same payload shape as the schema'd `pipeline` JSONL record:
        # the proof of where a step's time went travels with the number
        record['pipeline'] = pipeline_snapshot
        record['sync_nodes_steps_per_sec'] = round(sync_rate, 2)
        record['pipelined_vs_sync'] = round(
            nodes_steps_per_sec / sync_rate, 3)
    if retrace_post_warmup is not None:
        # 0 on a healthy run; >0 means a window paid a recompile and the
        # timing is suspect (the watchdog also warned on stderr)
        record['retrace_post_warmup'] = retrace_post_warmup
    if hbm_peak_bytes is not None:
        record['hbm_peak_bytes'] = hbm_peak_bytes
    if cost_body is not None:
        # the schema'd `cost` payload (observability.costs): the
        # BENCH_*.json trajectory tracks peak memory alongside
        # nodes*steps/s, and scripts/perf_gate.py budgets both.
        # peak_hbm_bytes is XLA's static argument+output+temp estimate;
        # hbm_peak_bytes above stays the watchdog's MEASURED figure
        # where the backend reports one. The label is re-stamped here
        # because the pipelined arm appends ',pipelined' AFTER the
        # ledger captured the base label — a cost record must name the
        # arm it measured
        cost_body['label'] = label
        record['cost'] = cost_body
        record['peak_hbm_bytes'] = cost_body['peak_bytes']
    # loss-trajectory sanity: adam at 1e-4 on this objective decreases
    # monotonically-ish from the first step; a flat or garbage sequence
    # means the executable did not run the program the label claims.
    # Shared definition with run_baselines (utils.helpers)
    from se3_transformer_tpu.utils.helpers import loss_trajectory_fields
    record.update(loss_trajectory_fields(losses))
    if eq_scope:
        record['equivariance_scope'] = eq_scope
    if device_kind:
        record['device_kind'] = device_kind
    if os.environ.get('SE3_TPU_CODE_REV'):
        # sessions pin the package-tree fingerprint at chip acquisition;
        # carrying it in the record ties every number to the code that
        # produced it (the 01:39Z picker-regression record was only
        # identifiable by timestamp — BENCH_SESSION.jsonl, round 4)
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    if fallback_reason:
        record['fallback_reason'] = fallback_reason
    if on_chip:
        # FLOP accounting (corrected round 4): XLA cost_analysis is
        # doubly blind on this program — Pallas-kernel FLOPs are
        # invisible AND lax.map (edge_chunks) bodies count once instead
        # of trip-count times. The r03 records' "MFU 0.0027" was that
        # artifact (utils/flops.py docstring has the audit numbers); the
        # analytic count is the honest one and both are recorded.
        t_step = dt / steps
        if step_flops:
            record['step_tflops_xla_visible'] = round(step_flops / 1e12, 3)
        from se3_transformer_tpu.utils.flops import (
            device_peaks, train_step_flops_estimate,
        )
        peaks = device_peaks(device_kind)
        # module.num_neighbors is authoritative (the recipe built the
        # model; bench's local is just the label)
        fl = train_step_flops_estimate(module, num_nodes,
                                       module.num_neighbors, batch)
        record['step_tflops_analytic'] = round(fl / 1e12, 2)
        record['mfu_f32_analytic'] = round(
            fl / t_step / peaks['f32_flops'], 4)
        record['mfu_bf16_analytic'] = round(
            fl / t_step / peaks['bf16_flops'], 4)
        if fl / t_step > peaks['bf16_flops']:
            # sustaining more than bf16 peak is physically impossible
            # for this program: the executable cannot have run the
            # labelled computation (19:29Z artifact class)
            record['implausible_throughput'] = True
    print(json.dumps(record))
    return record


def ring_main(n_devices: int, per_device_nodes: int = None):
    """`python bench.py --ring N`: sequence-parallel comm A/B on an
    N-virtual-device CPU mesh (sp=N ring-path training step, fixed
    per-device nodes — the scripts/width_table.py --weak-scaling harness,
    shared so the numbers are the same program PERF.md tables).

    Prints ONE bench-shaped JSON line whose value is the
    overlapped+sparse arm's nodes·steps/s; the serialized+dense control
    arm rides along (`overlapped_vs_serialized`) with BOTH arms' schema'd
    `comm` payloads — collective classes/bytes and the full-width
    all-gather scan of each traced HLO (parallel.exchange.comm_payload),
    the same end-to-end A/B discipline as --pipelined (never compared
    against the single-device RECORD anchors: different program).

    CPU-mesh caveat travels with the record: all virtual devices share
    this host's cores, so overlap cannot hide transfer latency here —
    the honest CPU-side win is the all-gather-free trace + flat
    per-shard memory; overlap is measured for regression, not for the
    ICI story (that needs a real pod)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'scripts'))
    import width_table

    if per_device_nodes is None:
        per_device_nodes = int(os.environ.get('SE3_TPU_RING_PDN', 64))
    jax = width_table._setup(n_devices)
    arms = {}
    for overlap, exchange, arm in ((True, True, 'overlapped_sparse'),
                                   (False, False, 'serialized_dense')):
        arms[arm] = width_table.weak_scaling_point(
            jax, n_devices, per_device_nodes, dim=16, k=8,
            overlap=overlap, exchange=exchange)
    fast_arm = arms['overlapped_sparse']
    n = fast_arm['n']
    record = {
        'metric': f'ring_comm_ab_nodes_steps_per_sec'
                  f'(sp={n_devices},pdn={per_device_nodes},dim=16)',
        'value': round(n / fast_arm['step_s'], 2),
        'unit': 'nodes*steps/sec/cpu-host',
        'vs_baseline': 1.0,  # own-program A/B; RECORD anchors don't apply
        'mode': 'ring_ab',
        'sp': n_devices,
        'n': n,
        'step_s': fast_arm['step_s'],
        'serialized_dense_step_s': arms['serialized_dense']['step_s'],
        'overlapped_vs_serialized': round(
            arms['serialized_dense']['step_s'] / fast_arm['step_s'], 3),
        'per_shard_total_gb': fast_arm.get('per_shard_total_gb'),
        'comm': {arm: rec.get('comm') for arm, rec in arms.items()},
        'cost': {arm: rec.get('cost') for arm, rec in arms.items()},
        'loss_finite': bool(fast_arm.get('loss_finite')
                            and arms['serialized_dense'].get('loss_finite')),
    }
    if os.environ.get('SE3_TPU_CODE_REV'):
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    print(json.dumps(record))
    return record


def mesh_main(dp: int, sp: int, tp: int, per_device_nodes: int = None):
    """`python bench.py --mesh dp,sp,tp`: composed-parallelism A/B on
    the virtual CPU mesh (ROADMAP item 4). Arm A runs the dp x sp x tp
    train step through the explicit-aliasing composed route
    (scripts/width_table.py mesh_sweep_point — the same program the
    MESH_SWEEP.jsonl bank rows come from); arm B runs the IDENTICAL
    global problem (same batch, same node count) as plain (dp, 1, 1)
    data parallelism. Placement is the only difference, so the ratio
    isolates what composing sp and tp costs/buys on this host.

    Prints ONE bench-shaped JSON line whose value is the composed arm's
    nodes*steps/s; the dp-only control rides along
    (`composed_vs_dp_only`) with BOTH arms' schema'd `comm` payloads —
    per-class AND per-mesh-axis collective bytes plus the axis-aware
    full-width all-gather scan — and both cost-ledger payloads. Same
    CPU-mesh caveat as --ring: virtual devices share this host's cores,
    so wall-clock ratios measure regression, not the ICI story; the
    transferable wins are the all-gather-free proof bit and the
    per-shard memory column. Never compared against the single-device
    RECORD anchors: different program."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'scripts'))
    import width_table

    if per_device_nodes is None:
        per_device_nodes = int(os.environ.get('SE3_TPU_MESH_PDN', 64))
    n_devices = dp * sp * tp
    jax = width_table._setup(max(n_devices, 2))
    arms = {
        'composed': width_table.mesh_sweep_point(
            jax, dp, sp, tp, per_device_nodes, dim=16, k=8),
        # same global shapes: b=dp, n=per_device_nodes*sp, on (dp,1,1)
        'dp_only': width_table.mesh_sweep_point(
            jax, dp, 1, 1, per_device_nodes * sp, dim=16, k=8),
    }
    composed = arms['composed']
    n = composed['n']
    assert arms['dp_only']['n'] == n, 'arms must share global shapes'
    record = {
        'metric': f'mesh_comm_ab_nodes_steps_per_sec'
                  f'(dp={dp},sp={sp},tp={tp},pdn={per_device_nodes},'
                  f'dim=16)',
        'value': round(n / composed['step_s'], 2),
        'unit': 'nodes*steps/sec/cpu-host',
        'vs_baseline': 1.0,  # own-program A/B; RECORD anchors don't apply
        'mode': 'mesh_ab',
        'dp': dp, 'sp': sp, 'tp': tp,
        'n': n,
        'step_s': composed['step_s'],
        'dp_only_step_s': arms['dp_only']['step_s'],
        'composed_vs_dp_only': round(
            arms['dp_only']['step_s'] / composed['step_s'], 3),
        'per_shard_total_gb': composed.get('per_shard_total_gb'),
        'dp_only_per_shard_total_gb':
            arms['dp_only'].get('per_shard_total_gb'),
        'comm': {arm: rec.get('comm') for arm, rec in arms.items()},
        'cost': {arm: rec.get('cost') for arm, rec in arms.items()},
        'loss_finite': bool(composed.get('loss_finite')
                            and arms['dp_only'].get('loss_finite')),
    }
    if os.environ.get('SE3_TPU_CODE_REV'):
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    print(json.dumps(record))
    return record


def flash_main(steps: int = 6, n: int = 128, k: int = 16,
               num_degrees: int = 4, dim: int = 16):
    """`python bench.py --flash`: fused-vs-XLA streaming-attention A/B
    on the CPU toy bench (the ISSUE 11 acceptance harness).

    Builds the SAME conv-weighted attention toy model twice — the
    unfused trunk (materialized basis + gathered/keyed features +
    scores) and the fuse_pairwise streaming path
    (kernels.pallas_flash, identical parameters) — and measures a
    jitted value_and_grad TRAIN step per arm, best-of-two windows.
    Peak HBM comes from the PR 6 cost ledger on each arm's compiled
    executable, so the before/after activation-memory claim is a
    ledger entry, not prose. Prints ONE bench-shaped JSON line whose
    value is the fused arm's nodes*steps/s; scripts/flash_smoke.py
    wraps the payload into the schema'd `flash` record and
    PERF_BUDGETS.json enforces the step-time and peak-HBM wins plus
    the fused equivariance gate. Never compared against the RECORD
    anchors: different program."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.observability.costs import cost_payload
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    from se3_transformer_tpu.utils.validation import equivariance_l2

    enable_compilation_cache()
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                        jnp.float32)
    mask = jnp.ones((1, n), bool)
    kw = dict(dim=dim, depth=1, num_degrees=num_degrees,
              output_degrees=2, reduce_dim_out=True, attend_self=True,
              use_null_kv=True, num_neighbors=k, heads=2, dim_head=8,
              tie_key_values=True, shared_radial_hidden=True)
    unfused = SE3TransformerModule(**kw)
    fused = SE3TransformerModule(fuse_pairwise=True, **kw)
    params = jax.jit(fused.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coors, mask=mask,
        return_type=1)['params']

    arms = {}
    for arm, mod in (('unfused', unfused), ('fused', fused)):
        def loss(p, mod=mod):
            out = mod.apply({'params': p}, feats, coors, mask=mask,
                            return_type=1)
            return (out ** 2).mean()
        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            params).compile()
        cost = cost_payload(compiled, label=f'flash_ab_{arm}')
        _, g = compiled(params)
        jax.block_until_ready(g)                  # warmup
        arms[arm] = dict(compiled=compiled, cost=cost,
                         peak_hbm_bytes=cost['peak_bytes'], best=None)
    # ALTERNATING windows (the tune_kernels A/B-pair discipline): a
    # monotonic host-load drift then hits both arms equally instead of
    # whichever arm happened to run second
    for _ in range(3):
        for arm in ('unfused', 'fused'):
            compiled = arms[arm]['compiled']
            t0 = time.monotonic()
            for _ in range(steps):
                _, g = compiled(params)
            jax.block_until_ready(g)
            dt = (time.monotonic() - t0) / steps
            if arms[arm]['best'] is None or dt < arms[arm]['best']:
                arms[arm]['best'] = dt
    for arm in ('unfused', 'fused'):
        arms[arm]['step_ms'] = round(arms[arm].pop('best') * 1e3, 2)
        del arms[arm]['compiled']
        print(f'{arm}: {arms[arm]["step_ms"]} ms/step, peak '
              f'{arms[arm]["peak_hbm_bytes"] / 2**20:.1f} MiB',
              file=sys.stderr)

    out_u = unfused.apply({'params': params}, feats, coors, mask=mask,
                          return_type=1)
    out_f = fused.apply({'params': params}, feats, coors, mask=mask,
                        return_type=1)
    parity = float(jnp.abs(out_u - out_f).max())
    eq = equivariance_l2(fused, params, feats, coors, mask)

    # global (graph-free) scenario: the large-assembly variant with NO
    # kNN truncation — streaming per-tile rel_pos/radial/payload vs the
    # materialized all-pairs formulation of the same function. Guarded:
    # a failure here must not lose the kNN A/B already measured.
    global_payload = None
    try:
        global_payload = _flash_global_ab(steps=max(2, steps // 2))
    except Exception as e:  # noqa: BLE001
        print(f'global-scenario A/B failed ({type(e).__name__}: {e}); '
              f'recording the kNN A/B without it', file=sys.stderr)

    fused_s = arms['fused']['step_ms'] / 1e3
    record = {
        'metric': f'flash_attention_ab_nodes_steps_per_sec'
                  f'(dim={dim},n={n},k={k},deg={num_degrees},'
                  f'backend=cpu)',
        'value': round(n / fused_s, 2),
        'unit': 'nodes*steps/sec/cpu-host',
        'vs_baseline': 1.0,     # own-program A/B; anchors don't apply
        'mode': 'flash_ab',
        'timing': 'best-of-3-alternating',
        'fused_step_ms': arms['fused']['step_ms'],
        'unfused_step_ms': arms['unfused']['step_ms'],
        'fused_vs_unfused': round(
            arms['unfused']['step_ms'] / arms['fused']['step_ms'], 3),
        'parity_l2': parity,
        'equivariance_l2_fused': eq,
        'peak_hbm_fused': arms['fused']['peak_hbm_bytes'],
        'peak_hbm_unfused': arms['unfused']['peak_hbm_bytes'],
        'hbm_unfused_vs_fused': round(
            arms['unfused']['peak_hbm_bytes']
            / max(arms['fused']['peak_hbm_bytes'], 1), 3),
        'cost': {arm: rec['cost'] for arm, rec in arms.items()},
    }
    if global_payload is not None:
        record['global'] = global_payload
    if os.environ.get('SE3_TPU_CODE_REV'):
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    print(json.dumps(record))
    return record


def _flash_global_ab(n: int = 192, steps: int = 3):
    """Streaming global attention vs the materialized all-pairs
    reference (forward, one output degree): step ms + ledgered peak
    bytes both arms. The payload the --flash record carries for the
    graph-free scenario."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu.kernels import pallas_flash as pf
    from se3_transformer_tpu.observability.costs import cost_payload

    rng = np.random.RandomState(3)
    B, heads, kv_h, dim_head, mid = 1, 2, 2, 8, 32
    pairs = ((0, 8), (1, 8))
    d_out = 1
    Dh = dim_head * (2 * d_out + 1)
    IF = sum(c * (2 * min(d, d_out) + 1) for d, c in pairs)
    O = kv_h * dim_head
    q = jnp.asarray(rng.normal(size=(B, n, heads, Dh)), jnp.float32)
    xs = tuple(jnp.asarray(rng.normal(size=(B, n, c, 2 * d + 1)),
                           jnp.float32) for d, c in pairs)
    coords = jnp.asarray(rng.normal(size=(B, n, 3)) * 2, jnp.float32)
    rp = tuple(jnp.asarray(rng.normal(size=s), jnp.float32) * 0.3
               for s in [(1, mid), (mid,), (mid,), (mid,), (mid, mid),
                         (mid,), (mid,), (mid,)])
    wv = jnp.asarray(rng.normal(size=(mid, IF, O)), jnp.float32)
    bv = jnp.asarray(rng.normal(size=(IF, O)), jnp.float32)
    scale = dim_head ** -0.5
    cfg = pf.FlashConfig(pairs=pairs, d_out=d_out, heads=heads,
                         kv_heads=kv_h, scale=scale, arm_v='dense',
                         arm_k='dense', tie=True)
    consts = {k: jnp.asarray(v, jnp.float32)
              for k, v in pf._arm_consts(cfg).items()}

    def streaming(c):
        return pf.flash_global_attention(
            q, xs, c, rp, wv, bv, pairs=pairs, d_out=d_out, heads=heads,
            kv_heads=kv_h, scale=scale, arm='dense', pallas=False)

    def materialized(c):
        rel = c[:, :, None, :] - c[:, None, :, :]
        h = pf._radial_apply(pf._safe_dist(rel)[..., None], rp)
        sh = pf.flash_sh_payload(rel, pf._sh_degree(cfg),
                                 differentiable=True)
        xg = tuple(jnp.broadcast_to(x[:, None], (B, n, *x.shape[1:]))
                   for x in xs)
        kv = pf._kv_block('dense', pairs, d_out, xg, h, sh, None, wv,
                          bv, consts).reshape(B, n, n, kv_h, Dh)
        notself = (jnp.arange(n)[:, None] != jnp.arange(n)[None])[None]
        return pf._row_attention(cfg, q, kv, kv, notself)

    out = {}
    parity = None
    fns = dict(streaming=streaming, materialized=materialized)
    compiled = {}
    results = {}
    for arm, fn in fns.items():
        compiled[arm] = jax.jit(fn).lower(coords).compile()
        cost = cost_payload(compiled[arm], label=f'flash_global_{arm}')
        results[arm] = compiled[arm](coords)
        jax.block_until_ready(results[arm])
        out[arm] = dict(peak_hbm_bytes=cost['peak_bytes'], best=None)
    parity = float(jnp.abs(results['streaming']
                           - results['materialized']).max())
    for _ in range(2):      # alternating windows, like the kNN A/B
        for arm in fns:
            t0 = time.monotonic()
            for _ in range(steps):
                r = compiled[arm](coords)
            jax.block_until_ready(r)
            dt = (time.monotonic() - t0) / steps
            if out[arm]['best'] is None or dt < out[arm]['best']:
                out[arm]['best'] = dt
    for arm in fns:
        out[arm]['step_ms'] = round(out[arm].pop('best') * 1e3, 2)
    return dict(
        n=n, parity_l2=parity,
        streaming_step_ms=out['streaming']['step_ms'],
        materialized_step_ms=out['materialized']['step_ms'],
        peak_hbm_streaming=out['streaming']['peak_hbm_bytes'],
        peak_hbm_materialized=out['materialized']['peak_hbm_bytes'],
        hbm_materialized_vs_streaming=round(
            out['materialized']['peak_hbm_bytes']
            / max(out['streaming']['peak_hbm_bytes'], 1), 3))


def assembly_main(ns=(256, 512), steps: int = 3, dim: int = 8):
    """`python bench.py --assembly n1,n2,...`: kNN-free global-vs-
    materialized large-assembly A/B on the CPU toy MODEL (the ISSUE 18
    acceptance harness; the kernel-level pair lives in --flash's
    `global` payload).

    Builds the SAME attention_mode='global' model twice — the streaming
    arm (O(n) activation memory, per-tile pair payload) and the
    global_materialize=True control arm (every [b, n, n, ...] per-edge
    tensor in HBM, plain autodiff) — with IDENTICAL parameters, and
    measures a jitted forward per arm per n in alternating best-of-2
    windows. Peak HBM per arm comes from the PR 6 cost ledger on each
    compiled executable, so the memory claim is a ledger entry, not
    prose (the --ring / --degrees discipline). Prints ONE bench-shaped
    JSON line whose value is the largest-n streaming arm's
    nodes*steps/s; scripts/assembly_smoke.py wraps the serving-side
    variant into the schema'd `assembly` record and PERF_BUDGETS.json
    enforces the >=3x HBM floor + equivariance. Never compared against
    the RECORD anchors: different program."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.observability.costs import cost_payload
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    kw = dict(num_tokens=24, dim=dim, depth=1, num_degrees=2,
              output_degrees=2, reduce_dim_out=True, attend_self=True,
              use_null_kv=True, heads=2, dim_head=8, pallas=False,
              attention_mode='global')
    mods = {'global': SE3TransformerModule(**kw),
            'materialized': SE3TransformerModule(
                **kw, global_materialize=True)}

    rng = np.random.RandomState(0)
    params = None
    points = {}
    for n in ns:
        feats = jnp.asarray(rng.randint(0, 24, (1, n)))
        coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                            jnp.float32)
        mask = jnp.ones((1, n), bool)
        if params is None:
            # one seeded tree serves every n and BOTH arms (the params
            # are n-independent; identical-params parity is the point)
            params = jax.jit(
                mods['global'].init,
                static_argnames=('return_type',))(
                jax.random.PRNGKey(0), feats, coors, mask=mask,
                return_type=1)['params']

        arms = {}
        results = {}
        for arm, mod in mods.items():
            def fn(f, c, m, _mod=mod):
                return _mod.apply({'params': params}, f, c, mask=m,
                                  return_type=1)
            compiled = jax.jit(fn).lower(feats, coors, mask).compile()
            cost = cost_payload(compiled,
                                label=f'assembly_{arm},n={n},dim={dim}')
            results[arm] = compiled(feats, coors, mask)
            jax.block_until_ready(results[arm])
            arms[arm] = dict(compiled=compiled, cost=cost,
                             peak_hbm_bytes=cost['peak_bytes'], best=None)
        parity = float(jnp.abs(results['global']
                               - results['materialized']).max())
        for _ in range(2):      # alternating windows (the --flash idiom)
            for arm, rec in arms.items():
                t0 = time.monotonic()
                for _ in range(steps):
                    r = rec['compiled'](feats, coors, mask)
                jax.block_until_ready(r)
                dt = (time.monotonic() - t0) / steps
                if rec['best'] is None or dt < rec['best']:
                    rec['best'] = dt
        entry = dict(
            n=n, parity_linf=parity,
            global_step_ms=round(arms['global']['best'] * 1e3, 2),
            materialized_step_ms=round(
                arms['materialized']['best'] * 1e3, 2),
            peak_hbm_global=arms['global']['peak_hbm_bytes'],
            peak_hbm_materialized=arms['materialized']['peak_hbm_bytes'],
            hbm_materialized_vs_global=round(
                arms['materialized']['peak_hbm_bytes']
                / max(arms['global']['peak_hbm_bytes'], 1), 3),
            cost={arm: rec['cost'] for arm, rec in arms.items()})
        points[str(n)] = entry
        print(f'n={n}: {entry["global_step_ms"]} ms/step streaming vs '
              f'{entry["materialized_step_ms"]} ms materialized, HBM '
              f'ratio {entry["hbm_materialized_vs_global"]}, parity '
              f'{parity:.2e}', file=sys.stderr)

    top = str(max(ns))
    record = {
        'metric': f'assembly_ab_nodes_steps_per_sec'
                  f'(dim={dim},ns={",".join(str(n) for n in ns)},'
                  f'backend=cpu)',
        'value': round(max(ns) / (points[top]['global_step_ms'] / 1e3), 2),
        'unit': 'nodes*steps/sec/cpu-host',
        'vs_baseline': 1.0,     # own-program A/B; anchors don't apply
        'mode': 'assembly_ab',
        'timing': 'best-of-2-alternating',
        'points': points,
    }
    if os.environ.get('SE3_TPU_CODE_REV'):
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    print(json.dumps(record))
    return record


def quant_main(mix: str = 'int8_mix', steps: int = 5,
               buckets=(12, 24), batch_size: int = 2,
               eq_degrees=(2, 4)):
    """`python bench.py --quant [int8_mix|bf16|fp8_mix]`: fp32-vs-
    quantized-mix serving A/B on the CPU toy engines (the ROADMAP
    item 3 acceptance harness).

    Builds THREE AOT engines from ONE seeded param tree — fp32, the
    quantized mix (restore-time quantization: the fp32 tree never
    lands on device), and the fp32 REFERENCE of the same quantized
    weights (dequantized host-side) — and measures engine.run latency
    per bucket in alternating best-of-3 windows. Three claims land as
    record fields, not prose:

      * argument_bytes_ratio — quantized/fp32 argument bytes off each
        bucket's PR 6 cost ledger (the per-replica memory claim;
        budget ceiling 0.6);
      * parity_max_abs — quantized engine vs the fp32 reference OF THE
        SAME QUANTIZED WEIGHTS, padded AND unpadded rows (the serving
        implementation must add nothing beyond quantization itself;
        gated at the repo-wide 1e-4 bar). The error vs the RAW fp32
        engine is quant_error_max_abs — the accuracy tradeoff a mix
        buys its memory with, banked per record (an absolute 1e-4
        there is mathematically unreachable for any int8 weight grid:
        per-channel rounding alone is ~0.4% relative);
      * equivariance_l2 — worst-case over feats models at
        `eq_degrees`, quantized params (weight-only quantization must
        preserve equivariance to roundoff).

    Prints ONE bench-shaped JSON line; scripts/quant_smoke.py wraps
    the payload into the schema'd `quant_ab` record and
    PERF_BUDGETS.json enforces ratio + parity + equivariance. Never
    compared against the RECORD anchors: different program."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu import quant
    from se3_transformer_tpu.inference import InferenceEngine
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.native.loader import chain_adjacency
    from se3_transformer_tpu.training.denoise import DenoiseConfig
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    from se3_transformer_tpu.utils.validation import equivariance_l2

    enable_compilation_cache()
    buckets = tuple(int(b) for b in buckets)
    rng = np.random.RandomState(0)
    cfg = DenoiseConfig(num_tokens=24, dim=8, dim_head=8, heads=2,
                        depth=2, num_degrees=2, max_sparse_neighbors=4)
    module = cfg.build_module()
    L = buckets[0]
    params = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.num_tokens, size=(1, L))),
        jnp.asarray(rng.normal(size=(1, L, 3)).astype(np.float32)),
        mask=jnp.ones((1, L), bool),
        adj_mat=jnp.asarray(chain_adjacency(L)),
        return_type=1)['params']
    host_params = jax.tree_util.tree_map(np.asarray, params)

    qtree, quant_report = quant.quantize_params(host_params, mix)
    # the fp32 reference OF THE QUANTIZED WEIGHTS: dequantize (and
    # upcast the bf16 casts) host-side — the implementation-parity
    # oracle every fused epilogue must match
    ref_tree = jax.tree_util.tree_map(
        lambda x: quant.dequantize(x)
        if isinstance(x, quant.QuantTensor)
        else (np.asarray(x, np.float32)
              if getattr(x, 'dtype', None) == jnp.bfloat16 else x),
        qtree, is_leaf=lambda x: isinstance(x, quant.QuantTensor))

    engines = {
        'fp32': InferenceEngine(module, host_params, buckets=buckets,
                                batch_size=batch_size),
        'quant': InferenceEngine(module, host_params, buckets=buckets,
                                 batch_size=batch_size, precision=mix),
        'ref': InferenceEngine(module, ref_tree, buckets=buckets,
                               batch_size=batch_size),
    }

    # one padded + one unpadded request set per bucket (fixed across
    # arms so the comparison is input-identical)
    requests = {}
    for b in buckets:
        full = (rng.randint(0, cfg.num_tokens, size=b),
                rng.normal(size=(b, 3)).astype(np.float32))
        short_len = max(1, b - 3)
        short = (rng.randint(0, cfg.num_tokens, size=short_len),
                 rng.normal(size=(short_len, 3)).astype(np.float32))
        requests[b] = (full, short)

    outputs = {arm: {} for arm in engines}
    for arm, engine in engines.items():
        for b, (full, short) in requests.items():
            outputs[arm][b] = (np.asarray(engine.predict(*full)),
                               np.asarray(engine.predict(*short)))
    parity = max(float(np.abs(outputs['quant'][b][i]
                              - outputs['ref'][b][i]).max())
                 for b in buckets for i in (0, 1))
    quant_error = max(float(np.abs(outputs['quant'][b][i]
                                   - outputs['fp32'][b][i]).max())
                      for b in buckets for i in (0, 1))

    # ALTERNATING windows per bucket (the tune_kernels A/B-pair
    # discipline): host-load drift hits both arms equally
    per_bucket = {b: {'fp32': None, 'quant': None} for b in buckets}
    from se3_transformer_tpu.native.loader import pad_to_bucket
    for _ in range(3):
        for arm in ('fp32', 'quant'):
            engine = engines[arm]
            for b in buckets:
                tok, crd = requests[b][0]
                t, c, m = pad_to_bucket([tok], [crd], b,
                                        batch_size=batch_size)
                t0 = time.monotonic()
                for _ in range(steps):
                    out = engine.run(b, t, c, m)
                jax.block_until_ready(out)
                dt = (time.monotonic() - t0) / steps
                best = per_bucket[b][arm]
                if best is None or dt < best:
                    per_bucket[b][arm] = dt

    bucket_entries = {}
    for b in buckets:
        f_ms = per_bucket[b]['fp32'] * 1e3
        q_ms = per_bucket[b]['quant'] * 1e3
        bucket_entries[str(b)] = dict(
            fp32_ms=round(f_ms, 3), quant_ms=round(q_ms, 3),
            quant_vs_fp32=round(f_ms / q_ms, 3))

    # the memory claim off the cost ledger: argument bytes of the
    # LARGEST bucket's executable, per arm (params dominate; the
    # request arrays are identical between arms)
    top = buckets[-1]
    costs = {arm: engines[arm].cost_payloads[engines[arm]._key(top)]
             for arm in ('fp32', 'quant')}
    arg_fp32 = costs['fp32']['memory']['argument_bytes']
    arg_quant = costs['quant']['memory']['argument_bytes']

    # equivariance at the swept degrees: feats models, quantized params
    eq_by_degree = {}
    n, k, dim = 64, 8, 8
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                        jnp.float32)
    mask = jnp.ones((1, n), bool)
    for d in eq_degrees:
        mod = SE3TransformerModule(
            dim=dim, depth=1, num_degrees=d + 1, output_degrees=2,
            reduce_dim_out=True, attend_self=True, num_neighbors=k,
            heads=2, dim_head=8, num_conv_layers=2, tie_key_values=True)
        dparams = jax.jit(mod.init, static_argnames=('return_type',))(
            jax.random.PRNGKey(0), feats, coors, mask=mask,
            return_type=1)['params']
        dq, _ = quant.quantize_params(
            jax.tree_util.tree_map(np.asarray, dparams), mix)
        eq_by_degree[str(d)] = equivariance_l2(mod, dq, feats, coors,
                                               mask)

    record = {
        'metric': f'quant_ab_{mix}(dim={cfg.dim},depth={cfg.depth},'
                  f'buckets={",".join(str(b) for b in buckets)},'
                  f'backend=cpu)',
        'value': bucket_entries[str(top)]['quant_vs_fp32'],
        'unit': 'quant_vs_fp32_step_ratio',
        'vs_baseline': 1.0,     # own-program A/B; anchors don't apply
        'mode': 'quant_ab',
        'timing': 'best-of-3-alternating',
        'mix': quant_report['mix'],
        'buckets': bucket_entries,
        'argument_bytes_fp32': arg_fp32,
        'argument_bytes_quant': arg_quant,
        'argument_bytes_ratio': round(arg_quant / max(arg_fp32, 1), 4),
        'params_bytes_ratio': quant_report['bytes_ratio'],
        'quant_report': quant_report,
        'parity_max_abs': parity,
        'quant_error_max_abs': quant_error,
        'equivariance_l2': max(eq_by_degree.values()),
        'equivariance_by_degree': eq_by_degree,
        'cost': {arm: dict(body) for arm, body in costs.items()},
    }
    if os.environ.get('SE3_TPU_CODE_REV'):
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    for arm in ('fp32', 'quant'):
        print(f"{arm}: {bucket_entries[str(top)][f'{arm}_ms']} ms/step "
              f"@ bucket {top}, argument bytes "
              f"{costs[arm]['memory']['argument_bytes']}",
              file=sys.stderr)
    print(f'impl parity {parity:.2e}, quant error {quant_error:.2e}, '
          f'worst eq {record["equivariance_l2"]:.2e}', file=sys.stderr)
    print(json.dumps(record))
    return record


def degrees_main(degrees, dense_max: int = 4, steps: int = 5):
    """`python bench.py --degrees 2,4,6`: per-degree so2-vs-dense A/B on
    the CPU toy bench (the ROADMAP item 2 acceptance harness).

    For each max degree d, builds the SAME conv-weighted toy model (two
    preconv layers + one attention block, tied k/v — the conv
    contraction is the term the backends differ on) twice — dense CG
    backend and the so2 banded backend, IDENTICAL parameters — and
    times the jitted forward, best-of-two windows of `steps` fixed-batch
    applies each. The dense arm runs only at degrees <= `dense_max`
    (default 4): the dense basis at degree 6 needs the full degree-6
    Q_J intertwiners, whose one-time host Sylvester solves take tens of
    minutes on a cold cache — exactly the cost class the so2 backend
    exists to avoid (its canonical blocks ship as a committed seed).

    Prints ONE bench-shaped JSON line whose value is the so2 arm's
    nodes*steps/s at the highest swept degree; the per-degree payload
    (`degrees`: dense/so2 step ms, dense_vs_so2 ratio, so2 equivariance
    L2, dense-vs-so2 parity where dense ran) is what scripts/
    so2_smoke.py wraps into the schema'd `so2_sweep` record and what
    the committed perf budgets judge (PERF_BUDGETS.json:
    so2_degree4_beats_dense / so2_degree4_throughput_floor). Never
    compared against the RECORD anchors: different program."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    from se3_transformer_tpu.utils.validation import equivariance_l2

    enable_compilation_cache()
    n, k, dim = 128, 12, 8
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                        jnp.float32)
    mask = jnp.ones((1, n), bool)

    from se3_transformer_tpu.observability.costs import cost_payload

    def bench_forward(mod, params, label):
        fwd = jax.jit(lambda c: mod.apply({'params': params}, feats, c,
                                          mask=mask, return_type=1))
        # AOT-compile so the SAME executable serves the cost ledger and
        # the timed windows (the --ring / --flash discipline): each
        # arm's peak-HBM split is a ledger entry, not prose
        compiled = fwd.lower(coors).compile()
        cost = cost_payload(compiled, label=label)
        out = compiled(coors)
        out.block_until_ready()                       # warmup
        best = None
        for _ in range(2):
            t0 = time.monotonic()
            for _ in range(steps):
                out = compiled(coors)
            out.block_until_ready()
            dt = (time.monotonic() - t0) / steps
            best = dt if best is None or dt < best else best
        return best, cost

    per_degree = {}
    for d in degrees:
        kw = dict(dim=dim, depth=1, num_degrees=d + 1, output_degrees=2,
                  reduce_dim_out=True, attend_self=True, num_neighbors=k,
                  heads=2, dim_head=8, num_conv_layers=2,
                  tie_key_values=True)
        so2_mod = SE3TransformerModule(conv_backend='so2', **kw)
        # init through the so2 module: identical param tree, and at
        # degrees > dense_max it never touches the dense basis' Q_J
        params = jax.jit(so2_mod.init,
                         static_argnames=('return_type',))(
            jax.random.PRNGKey(0), feats, coors, mask=mask,
            return_type=1)['params']
        so2_s, so2_cost = bench_forward(so2_mod, params,
                                        f'so2_sweep_d{d}_so2')
        entry = dict(
            so2_step_ms=round(so2_s * 1e3, 2),
            so2_nodes_steps_per_sec=round(n / so2_s, 2),
            equivariance_l2_so2=equivariance_l2(so2_mod, params, feats,
                                                coors, mask),
            so2_peak_hbm_bytes=so2_cost['peak_bytes'],
            cost={'so2': so2_cost})
        if d <= dense_max:
            dense_mod = SE3TransformerModule(**kw)
            out_d = dense_mod.apply({'params': params}, feats, coors,
                                    mask=mask, return_type=1)
            out_s = so2_mod.apply({'params': params}, feats, coors,
                                  mask=mask, return_type=1)
            entry['parity_l2'] = float(jnp.abs(out_d - out_s).max())
            dense_s, dense_cost = bench_forward(dense_mod, params,
                                                f'so2_sweep_d{d}_dense')
            entry['dense_step_ms'] = round(dense_s * 1e3, 2)
            entry['dense_vs_so2'] = round(dense_s / so2_s, 3)
            # per-arm peak-HBM split: the so2 memory claim rides the
            # ledger (like --ring's per-arm cost payloads), not prose
            entry['dense_peak_hbm_bytes'] = dense_cost['peak_bytes']
            entry['cost']['dense'] = dense_cost
        per_degree[str(d)] = entry
        print(f'degree {d}: {entry}', file=sys.stderr)

    top = str(max(degrees))
    record = {
        'metric': f'so2_degree_sweep(dim={dim},n={n},k={k},ncl=2,'
                  f'degrees={",".join(str(d) for d in degrees)},'
                  f'backend=cpu)',
        'value': per_degree[top]['so2_nodes_steps_per_sec'],
        'unit': 'nodes*steps/sec/cpu-host',
        'vs_baseline': 1.0,     # own-program A/B; anchors don't apply
        'mode': 'so2_sweep',
        'timing': 'best-of-2',
        'degrees': per_degree,
    }
    if os.environ.get('SE3_TPU_CODE_REV'):
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    print(json.dumps(record))
    return record


def v2_degrees_main(degrees, so2_max: int = 6, steps: int = 5):
    """`python bench.py --v2-degrees 2,4,6,8`: per-degree A/B of the v2
    eSCN-direct model family against the v1+so2 baseline on the CPU toy
    bench (the SE3TransformerV2 acceptance harness).

    Unlike --degrees this is a MODEL-FAMILY A/B, not a backend A/B on
    identical parameters — v2 is deliberately not checkpoint-compatible
    with v1 (its radial trunks emit per-m banded blocks directly, no
    dense-shaped radial output exists to share), so each arm inits its
    own params and the comparison is per-step wall clock + peak HBM off
    the cost ledger + the v2 arm's equivariance L2. The v1+so2 arm runs
    only at degrees <= `so2_max` (default 6): its per-degree canonical-
    block compile grows steeply on CPU, and past the crossover the v2
    arm is the only one worth timing — exactly the regime the family
    exists for.

    Prints ONE bench-shaped JSON line whose value is the v2 arm's
    nodes*steps/s at the highest swept degree; the per-degree payload
    (`degrees`: v2 step ms / throughput / equivariance / peak HBM,
    so2 step ms and so2_vs_v2 where the baseline ran) is what
    scripts/v2_smoke.py wraps into the schema'd `v2_sweep` record and
    what the committed budgets judge (PERF_BUDGETS.json:
    v2_degree6_beats_so2 / v2_degree6_throughput_floor /
    v2_equivariance_gate_degree_max). Never compared against the
    RECORD anchors: different program."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp
    import numpy as np

    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.observability.costs import cost_payload
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    from se3_transformer_tpu.utils.validation import equivariance_l2
    from se3_transformer_tpu.v2 import SE3TransformerV2Module

    enable_compilation_cache()
    n, k, dim = 128, 12, 8
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                        jnp.float32)
    mask = jnp.ones((1, n), bool)

    def bench_arm(mod, label):
        params = jax.jit(mod.init, static_argnames=('return_type',))(
            jax.random.PRNGKey(0), feats, coors, mask=mask,
            return_type=1)['params']
        fwd = jax.jit(lambda c: mod.apply({'params': params}, feats, c,
                                          mask=mask, return_type=1))
        # AOT-compile so the SAME executable serves the cost ledger and
        # the timed windows (the --degrees discipline): each arm's
        # peak-HBM claim is a ledger entry, not prose
        compiled = fwd.lower(coors).compile()
        cost = cost_payload(compiled, label=label)
        out = compiled(coors)
        out.block_until_ready()                       # warmup
        best = None
        for _ in range(2):
            t0 = time.monotonic()
            for _ in range(steps):
                out = compiled(coors)
            out.block_until_ready()
            dt = (time.monotonic() - t0) / steps
            best = dt if best is None or dt < best else best
        return best, cost, params

    per_degree = {}
    for d in degrees:
        v2_mod = SE3TransformerV2Module(
            dim=dim, depth=2, num_degrees=d + 1, output_degrees=2,
            reduce_dim_out=True, num_neighbors=k)
        v2_s, v2_cost, v2_params = bench_arm(v2_mod, f'v2_sweep_d{d}_v2')
        entry = dict(
            v2_step_ms=round(v2_s * 1e3, 2),
            v2_nodes_steps_per_sec=round(n / v2_s, 2),
            equivariance_l2_v2=equivariance_l2(v2_mod, v2_params, feats,
                                               coors, mask),
            v2_peak_hbm_bytes=v2_cost['peak_bytes'],
            cost={'v2': v2_cost})
        if d <= so2_max:
            so2_mod = SE3TransformerModule(
                dim=dim, depth=1, num_degrees=d + 1, output_degrees=2,
                reduce_dim_out=True, attend_self=True, num_neighbors=k,
                heads=2, dim_head=8, num_conv_layers=2,
                tie_key_values=True, conv_backend='so2',
                shared_radial_hidden=True)
            so2_s, so2_cost, _ = bench_arm(so2_mod, f'v2_sweep_d{d}_so2')
            entry['so2_step_ms'] = round(so2_s * 1e3, 2)
            entry['so2_vs_v2'] = round(so2_s / v2_s, 3)
            entry['so2_peak_hbm_bytes'] = so2_cost['peak_bytes']
            entry['cost']['so2'] = so2_cost
        per_degree[str(d)] = entry
        print(f'degree {d}: {entry}', file=sys.stderr)

    top = str(max(degrees))
    record = {
        'metric': f'v2_degree_sweep(dim={dim},n={n},k={k},'
                  f'degrees={",".join(str(d) for d in degrees)},'
                  f'backend=cpu)',
        'value': per_degree[top]['v2_nodes_steps_per_sec'],
        'unit': 'nodes*steps/sec/cpu-host',
        'vs_baseline': 1.0,     # own-program A/B; anchors don't apply
        'mode': 'v2_sweep',
        'timing': 'best-of-2',
        'degrees': per_degree,
    }
    if os.environ.get('SE3_TPU_CODE_REV'):
        record['code_rev'] = os.environ['SE3_TPU_CODE_REV']
    print(json.dumps(record))
    return record


if __name__ == '__main__':
    if '--flash' in sys.argv[1:]:
        # CPU A/B harness (like --degrees): streaming
        # fused attention vs the unfused trunk, flags parsed before jax
        # initializes its backends
        _steps = 6
        if '--steps' in sys.argv[1:]:
            _steps = int(sys.argv[sys.argv.index('--steps') + 1])
        flash_main(steps=_steps)
        sys.exit(0)
    if '--assembly' in sys.argv[1:]:
        # CPU A/B harness (like --degrees): streaming
        # global attention vs the materialized all-pairs control arm
        # at each requested n, flags parsed before jax initializes
        _i = sys.argv.index('--assembly')
        _ns = [int(x) for x in sys.argv[_i + 1].split(',')] \
            if len(sys.argv) > _i + 1 \
            and not sys.argv[_i + 1].startswith('--') else [256, 512]
        _steps = 3
        if '--steps' in sys.argv[1:]:
            _steps = int(sys.argv[sys.argv.index('--steps') + 1])
        assembly_main(tuple(_ns), steps=_steps)
        sys.exit(0)
    if '--quant' in sys.argv[1:]:
        # CPU A/B harness (like --degrees): fp32 vs a
        # quantized precision mix over the serving engines, flags
        # parsed before jax initializes its backends
        _i = sys.argv.index('--quant')
        _mix = sys.argv[_i + 1] if len(sys.argv) > _i + 1 and \
            not sys.argv[_i + 1].startswith('--') else 'int8_mix'
        _steps = 5
        if '--steps' in sys.argv[1:]:
            _steps = int(sys.argv[sys.argv.index('--steps') + 1])
        quant_main(mix=_mix, steps=_steps)
        sys.exit(0)
    if '--v2-degrees' in sys.argv[1:]:
        # CPU A/B harness (like --degrees): per-degree
        # v2-vs-(v1+so2) model-family comparison, flags parsed before
        # jax initializes its backends
        _i = sys.argv.index('--v2-degrees')
        _degs = [int(x) for x in sys.argv[_i + 1].split(',')] \
            if len(sys.argv) > _i + 1 else [2, 4]
        _sm = 6
        if '--so2-max' in sys.argv[1:]:
            _sm = int(sys.argv[sys.argv.index('--so2-max') + 1])
        _steps = 5
        if '--steps' in sys.argv[1:]:
            _steps = int(sys.argv[sys.argv.index('--steps') + 1])
        v2_degrees_main(_degs, so2_max=_sm, steps=_steps)
        sys.exit(0)
    if '--degrees' in sys.argv[1:]:
        # CPU A/B harness (like --ring): per-degree
        # so2-vs-dense comparison, flags parsed before jax initializes
        _i = sys.argv.index('--degrees')
        _degs = [int(x) for x in sys.argv[_i + 1].split(',')] \
            if len(sys.argv) > _i + 1 else [2, 4]
        _dm = 4
        if '--dense-max' in sys.argv[1:]:
            _dm = int(sys.argv[sys.argv.index('--dense-max') + 1])
        degrees_main(_degs, dense_max=_dm)
        sys.exit(0)
    if '--ring' in sys.argv[1:]:
        # CPU-mesh harness (the sp story needs more devices than one
        # chip: virtual ones), flags parsed before jax initializes its
        # backends
        _i = sys.argv.index('--ring')
        _n = int(sys.argv[_i + 1]) if len(sys.argv) > _i + 1 else 8
        ring_main(_n)
        sys.exit(0)
    if '--mesh' in sys.argv[1:]:
        # composed dp x sp x tp A/B on the virtual CPU mesh, same
        # discipline as --ring
        _i = sys.argv.index('--mesh')
        _spec = sys.argv[_i + 1] if len(sys.argv) > _i + 1 else '2,2,2'
        _dp, _sp, _tp = (int(x) for x in _spec.split(','))
        mesh_main(_dp, _sp, _tp)
        sys.exit(0)
    main('tpu', pipelined='--pipelined' in sys.argv[1:])
