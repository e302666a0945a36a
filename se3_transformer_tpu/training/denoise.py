"""Coordinate-denoising trainer (the reference's flagship application).

TPU-native rework of reference denoise.py (protein-backbone denoising on
sidechainnet CASP12, /root/reference/denoise.py:1-93): the model predicts a
type-1 refinement of Gaussian-noised coordinates, trained with masked MSE
and gradient accumulation. Differences by design:

  * data — sidechainnet is not available offline; `synthetic_protein_batch`
    generates chain-structured point clouds with the same shapes/adjacency
    semantics (3 backbone atoms per residue, chain adjacency matrix).
    Swap in a real dataset by yielding the same batch dict.
  * precision — the reference runs float64 on CUDA (denoise.py:10); TPUs
    emulate f64 slowly, so the trainer runs f32 (bf16-matmul optional)
    which passes the same 1e-4 equivariance budget.
  * the step is jitted/pjit-able, grad accumulation is a lax.scan, and
    metrics (nodes*steps/sec/chip) are collected without host sync every
    step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..models.se3_transformer import SE3TransformerModule
from ..native.loader import chain_adjacency
from ..parallel.mesh import make_mesh, shard_batch
from ..parallel.sharding import (
    make_accumulating_train_step, make_sharded_train_step,
)


@dataclasses.dataclass
class DenoiseConfig:
    # model (reference denoise.py:22-38 toy config, scaled by BASELINE.json)
    num_tokens: int = 24
    dim: int = 8
    dim_head: int = 8
    heads: int = 2
    depth: int = 2
    num_degrees: int = 2
    output_degrees: int = 2
    num_neighbors: int = 0
    attend_sparse_neighbors: bool = True
    max_sparse_neighbors: int = 8
    num_adj_degrees: int = 2
    adj_dim: int = 4
    # data
    batch_size: int = 1
    num_nodes: int = 96          # 32 residues x 3 backbone atoms
    noise_scale: float = 1.0
    # optimization (reference denoise.py:12-13, 51; its example accumulates
    # 16 micro-batches per update — set accum_steps=16 for parity, the CLI
    # does so by default)
    learning_rate: float = 1e-4
    accum_steps: int = 1
    # infra
    seed: int = 0
    use_mesh: bool = False
    # partition radial/head weights over the mesh's tp axis (see
    # parallel.sharding.param_partition_specs); requires a mesh with tp>1
    tensor_parallel: bool = False
    # true FSDP (ROADMAP item 4's named next step): shard params AND
    # adam's mu/nu dim-0 over the mesh's dp axis (parallel.rules fsdp
    # set + shard_opt_state — the moments inherit their param's audited
    # spec), and build the step with sharded_state=True so the update
    # runs shard-local and the donated state aliases in place. Before
    # this knob, opt state replicated on every chip (2x param memory)
    # despite the PR 10 specs existing. Requires a mesh with dp>1.
    fsdp: bool = False
    # composed dp x sp x tp parallelism (ROADMAP item 4): params AND
    # optimizer state over (dp, tp) via the parallel.rules 'composed'
    # set, with the step's in/out shardings pinned to those placements
    # (parallel.sharding.composed_state_shardings — the explicit-
    # aliasing route around the jax-0.4.37 GSPMD donation bug, which
    # otherwise kills the dp>1/sp>1/tp>1 mesh with an INTERNAL
    # aliased-size error). Batch placement is unchanged (dp over batch,
    # sp over nodes via shard_batch). Supersedes tensor_parallel/fsdp
    # when set; requires a mesh.
    composed: bool = False
    log_every: int = 1
    # first-class telemetry (observability package): thread an on-device
    # MetricAccumulator through the jitted step (zero host syncs on hot
    # steps), time host phases, watch for post-warmup retraces, and
    # flush one schema'd record every flush_every steps
    telemetry: bool = False
    flush_every: int = 10
    # overlapped data path (training.pipeline): build batches on a
    # background producer thread and keep prefetch_depth batches
    # device-resident ahead of the step loop (train_pipelined)
    pipeline: bool = False
    prefetch_depth: int = 2
    producer_capacity: int = 4
    # donate the per-step batch buffers to the jitted step. Safe ONLY
    # when every batch is freshly built/placed (the pipelined path, or
    # mesh training where shard_batch copies per call) — a caller that
    # feeds the same device batch twice must leave this off (see the
    # donation audit in parallel.sharding.make_sharded_train_step)
    donate_batch: bool = False
    # emit one schema'd `cost` record for the compiled train step
    # (observability.costs) after the first step of train()/
    # train_pipelined(). Opt-in: the ledger lowers+compiles the step a
    # second time — warm under the persistent compilation cache and
    # seconds on toy configs, minutes for a cold flagship program, which
    # should opt in deliberately
    cost_record: bool = False

    def build_module(self) -> SE3TransformerModule:
        return SE3TransformerModule(
            num_tokens=self.num_tokens, dim=self.dim, dim_head=self.dim_head,
            heads=self.heads, depth=self.depth, attend_self=True,
            input_degrees=1, num_degrees=self.num_degrees,
            output_degrees=self.output_degrees, reduce_dim_out=True,
            differentiable_coors=True, num_neighbors=self.num_neighbors,
            attend_sparse_neighbors=self.attend_sparse_neighbors,
            max_sparse_neighbors=self.max_sparse_neighbors,
            num_adj_degrees=self.num_adj_degrees, adj_dim=self.adj_dim)




@functools.lru_cache(maxsize=64)
def _chain_adjacency_cached(n: int) -> np.ndarray:
    """Per-node-count chain adjacency, computed once per process.

    The adjacency of an n-node chain depends only on n, yet the batch
    builder used to recompute the O(n^2) matrix on EVERY call — pure
    waste on the producer thread of the pipelined path, where host
    batch-build time is exactly what the prefetcher is trying to hide.
    The cached base is marked read-only: every consumer broadcasts or
    copies it, never mutates it."""
    adj = chain_adjacency(n)
    adj.setflags(write=False)
    return adj


def synthetic_protein_batch_host(cfg: DenoiseConfig,
                                 rng: np.random.RandomState) -> dict:
    """Host-side (pure numpy) chain-structured point cloud with residue
    tokens; mimics the backbone-atom layout of the reference's
    sidechainnet pipeline. This is the producer-thread half of the
    pipelined data path: no jax calls, so it never contends for the
    dispatch lock. `adj_mat` is a read-only broadcast view of the cached
    per-n adjacency — device_put/jnp.asarray copy it on transfer."""
    b, n = cfg.batch_size, cfg.num_nodes
    seqs = rng.randint(0, cfg.num_tokens, size=(b, n)).astype(np.int32)
    # random-walk chain coordinates: consecutive atoms ~bond-length apart
    steps = rng.normal(size=(b, n, 3)).astype(np.float32)
    steps /= np.linalg.norm(steps, axis=-1, keepdims=True)
    coords = np.cumsum(1.5 * steps, axis=1).astype(np.float32)
    coords -= coords.mean(axis=1, keepdims=True)
    masks = np.ones((b, n), dtype=bool)
    adj = np.broadcast_to(_chain_adjacency_cached(n)[None], (b, n, n))
    return dict(seqs=seqs, coords=coords, masks=masks, adj_mat=adj)


def synthetic_protein_batch(cfg: DenoiseConfig, rng: np.random.RandomState):
    """Device-placed synthetic batch (see synthetic_protein_batch_host
    for the host half; values are identical)."""
    return {k: jnp.asarray(v)
            for k, v in synthetic_protein_batch_host(cfg, rng).items()}


def denoise_loss_fn(module: SE3TransformerModule):
    """Masked-MSE denoising loss (reference denoise.py:73-89): predict the
    refinement that maps noised coords back to the clean ones."""

    def loss_fn(params, batch, rng):
        noise = jax.random.normal(rng, batch['coords'].shape,
                                  batch['coords'].dtype)
        noised = batch['coords'] + noise
        out = module.apply({'params': params}, batch['seqs'], noised,
                           mask=batch['masks'], adj_mat=batch['adj_mat'],
                           return_type=1)
        denoised = noised + out
        sq = ((denoised - batch['coords']) ** 2).sum(-1)
        m = batch['masks']
        loss = jnp.where(m, sq, 0.).sum() / jnp.maximum(m.sum(), 1)
        return loss, dict(loss=loss)

    return loss_fn


class DenoiseTrainer:
    """End-to-end trainer: init, accumulated+jitted steps, metrics, and
    (via training.checkpoint) save/restore."""

    def __init__(self, cfg: DenoiseConfig, mesh=None):
        self.cfg = cfg
        self.module = cfg.build_module()
        self.mesh = mesh if mesh is not None else (
            make_mesh() if cfg.use_mesh else None)
        self.optimizer = optax.adam(cfg.learning_rate)
        self.loss_fn = denoise_loss_fn(self.module)
        self.tensor_parallel = bool(cfg.tensor_parallel
                                    and self.mesh is not None)
        self.fsdp = bool(cfg.fsdp and self.mesh is not None)
        self.composed = bool(cfg.composed and self.mesh is not None)
        if self.composed:
            # the composed route subsumes both single-axis modes: params
            # carry tp AND dp placements, opt state inherits them, and
            # the pinned-shardings step covers the donation aliasing
            self.tensor_parallel = self.fsdp = False
        if cfg.composed and self.mesh is None:
            import warnings
            warnings.warn('composed=True without a mesh — falling back '
                          'to the single-device step; build the trainer '
                          'with make_mesh(dp=..., sp=..., tp=...)',
                          stacklevel=2)
        self.opt_state_specs = None   # filled by init()/restore() (fsdp)
        if cfg.tensor_parallel and (
                self.mesh is None or self.mesh.shape.get('tp', 1) == 1):
            import warnings
            warnings.warn(
                'tensor_parallel=True but the mesh has no tp axis '
                '(make_mesh defaults tp=1) — params will be fully '
                'replicated; build the mesh with make_mesh(tp=...) to '
                'actually partition them', stacklevel=2)
        if cfg.fsdp and (
                self.mesh is None or self.mesh.shape.get('dp', 1) == 1):
            import warnings
            warnings.warn(
                'fsdp=True but the mesh has no dp axis > 1 — params '
                'and optimizer state will end up replicated (the fsdp '
                'rule set demotes indivisible dims); build the mesh '
                'with make_mesh(dp=...) to actually shard them',
                stacklevel=2)
        self._step_fn = self._make_step()
        self.np_rng = np.random.RandomState(cfg.seed)
        self.rng = jax.random.PRNGKey(cfg.seed)
        self.params = None
        self.opt_state = None
        self.step_count = 0
        self.last_micro_losses = None
        self.metric_acc = None
        self.phase_timer = None
        self.watchdog = None
        if cfg.telemetry:
            from ..observability import (
                MetricAccumulator, PhaseTimer, RetraceWatchdog,
            )
            self.metric_acc = MetricAccumulator.zero(('loss', 'grad_norm'))
            self.phase_timer = PhaseTimer()
            self.watchdog = RetraceWatchdog({'train_step': self._step_fn})
            self._cum_metrics = None     # host-side merge of windows
            self._flush_count = 0
            self._last_flush_step = 0
            self._first_loss = None      # device refs: synced at close
            self._last_loss = None
            # compile happens on the first step of THIS process, not of
            # the run — a checkpoint-resumed trainer has step_count > 0
            # but still pays the compile on its first dispatch
            self._warmed_up = False

    def _make_step(self, state_shardings=None):
        """Build the jitted step (factored so the fsdp path can REBUILD
        it once placements exist — state_shardings pins in/out
        shardings to the placed state, the explicit-aliasing route
        around the jax-0.4.37 GSPMD donation bug; see
        parallel.sharding.make_sharded_train_step)."""
        cfg = self.cfg
        kwargs = dict(mesh=self.mesh, donate_batch=cfg.donate_batch,
                      tensor_parallel=self.tensor_parallel,
                      sharded_state=self.fsdp,
                      state_shardings=state_shardings,
                      telemetry=cfg.telemetry)
        if cfg.accum_steps > 1:
            # reference denoise.py:13,55: 16 micro-batches per update
            return make_accumulating_train_step(
                self.loss_fn, self.optimizer, cfg.accum_steps, **kwargs)
        return make_sharded_train_step(
            self.loss_fn, self.optimizer, **kwargs)

    def _pin_state_step(self):
        """Rebuild the step with in/out shardings pinned to the placed
        params/opt-state (called from init()/restore() under fsdp and
        under the composed dp x sp x tp mode — the explicit-aliasing
        route around the GSPMD donation bug on multi-axis meshes)."""
        shardings = tuple(
            jax.tree_util.tree_map(lambda leaf: leaf.sharding, tree)
            for tree in (self.params, self.opt_state))
        self._step_fn = self._make_step(state_shardings=shardings)
        if self.watchdog is not None:
            self.watchdog.track('train_step', self._step_fn)

    def init(self, batch=None):
        batch = batch if batch is not None else synthetic_protein_batch(
            self.cfg, self.np_rng)
        self.rng, sub, noise_rng = jax.random.split(self.rng, 3)
        noised = batch['coords'] + jax.random.normal(
            noise_rng, batch['coords'].shape, batch['coords'].dtype)
        init_fn = jax.jit(self.module.init, static_argnames=('return_type',))
        self.params = init_fn(
            sub, batch['seqs'], noised, mask=batch['masks'],
            adj_mat=batch['adj_mat'], return_type=1)['params']
        if self.composed:
            # composed dp x sp x tp: params AND opt state over (dp, tp)
            # via the 'composed' rule set, then the step repinned with
            # both placements as in/out shardings (scalars like adam's
            # count must be mesh-placed too, or the pin trips an
            # incompatible-devices error)
            from ..parallel.sharding import composed_state_shardings
            self.params, self.opt_state, _ = composed_state_shardings(
                self.params, self.optimizer.init(self.params), self.mesh)
            self._pin_state_step()
        elif self.fsdp:
            # true FSDP: params dim-0 over dp (fsdp rule set), then the
            # optimizer state through shard_opt_state so adam's mu/nu
            # inherit each param's AUDITED spec — the step factory's
            # sharded_state=True keeps both placements through the
            # update (nothing re-replicates, donation aliases in place)
            from ..parallel.rules import shard_opt_state
            from ..parallel.sharding import shard_params
            self.params = shard_params(self.params, self.mesh,
                                       rules='fsdp')
            self.opt_state, self.opt_state_specs = shard_opt_state(
                self.optimizer.init(self.params), self.params, self.mesh)
            self._pin_state_step()
        elif self.tensor_parallel:
            from ..parallel.sharding import shard_params
            self.params = shard_params(self.params, self.mesh)
            # jit so the adam moments inherit the param placement (eager
            # zeros_like would leave them uncommitted/replicated)
            self.opt_state = jax.jit(self.optimizer.init)(self.params)
        else:
            self.opt_state = self.optimizer.init(self.params)
        return self.params

    def restore(self, state) -> None:
        """Adopt a restored (params, opt_state, step_count) checkpoint
        tuple, RE-PLACING it under the trainer's sharding config:
        orbax/pickle restores hand back host (or replicated) leaves,
        and a resumed fsdp run must land mu/nu back in their dim-0
        shards — not replicate 2x the param memory on every chip until
        the first step reshards them implicitly."""
        params, opt_state, step_count = state
        if self.composed:
            from ..parallel.sharding import composed_state_shardings
            self.params, self.opt_state, _ = composed_state_shardings(
                params, opt_state, self.mesh)
            self.step_count = int(step_count)
            self._pin_state_step()
            return
        elif self.fsdp:
            from ..parallel.rules import shard_opt_state
            from ..parallel.sharding import shard_params
            params = shard_params(params, self.mesh, rules='fsdp')
            opt_state, self.opt_state_specs = shard_opt_state(
                opt_state, params, self.mesh)
            self.params, self.opt_state = params, opt_state
            self.step_count = int(step_count)
            self._pin_state_step()
            return
        elif self.tensor_parallel:
            from ..parallel.rules import shard_opt_state
            from ..parallel.sharding import shard_params
            params = shard_params(params, self.mesh)
            opt_state, _ = shard_opt_state(opt_state, params, self.mesh,
                                           rules='tp')
        self.params, self.opt_state = params, opt_state
        self.step_count = int(step_count)

    def train_step(self, batch, preplaced: bool = False) -> jax.Array:
        """One optimizer update. With accum_steps > 1 the batch leaves must
        carry a leading [accum_steps, ...] axis (see micro_batches).

        Returns the DEVICE loss array (a scalar, or the per-micro-step
        mean with accumulation) — never a Python float: forcing the sync
        here would stall the dispatch pipeline every step. Callers
        float() it at their own cadence (`train` does so only at the log
        interval; the telemetry path never does — metrics accumulate on
        device and flush per interval).

        `preplaced=True` skips the shard_batch placement: the pipelined
        path (`train_pipelined` / training.pipeline.device_prefetch)
        already device_put the batch with the mesh's NamedShardings."""
        if self.params is None:
            init_batch = batch
            if self.cfg.accum_steps > 1:
                init_batch = jax.tree_util.tree_map(lambda v: v[0], batch)
            self.init(init_batch)
        if self.mesh is not None and not preplaced:
            # seqs/coords/masks resolve to the canonical feats/coors/mask
            # specs via parallel.mesh's key aliases
            batch = shard_batch(batch, self.mesh,
                                leading_axes=1 if self.cfg.accum_steps > 1
                                else 0)
        self.rng, sub = jax.random.split(self.rng)
        if self.cfg.telemetry:
            # the step signature differs only by the accumulator pytree;
            # 'step' wall clock is dispatch-to-dispatch — no forced sync.
            # The first dispatch of this process carries the XLA
            # compile: bill it to 'warmup' so step percentiles and
            # throughput stay honest (also on checkpoint resume)
            phase = 'step' if self._warmed_up else 'warmup'
            self._warmed_up = True
            with self.phase_timer.phase(phase):
                (self.params, self.opt_state, loss, aux,
                 self.metric_acc) = self._step_fn(
                    self.params, self.opt_state, batch, sub,
                    self.metric_acc)
            if self._first_loss is None:
                self._first_loss = loss   # device ref; float()ed at close
            self._last_loss = loss
        else:
            self.params, self.opt_state, loss, aux = self._step_fn(
                self.params, self.opt_state, batch, sub)
        # with accum_steps > 1 the aux slot carries the per-micro-step
        # losses (VERDICT r2 weak #6: the mean alone hides a diverging
        # micro-batch; the reference prints every step, denoise.py:91)
        self.last_micro_losses = aux if self.cfg.accum_steps > 1 else None
        self.step_count += 1
        return loss

    def micro_batches(self):
        """Draw accum_steps micro-batches stacked on a leading axis."""
        batches = [synthetic_protein_batch(self.cfg, self.np_rng)
                   for _ in range(max(1, self.cfg.accum_steps))]
        if self.cfg.accum_steps <= 1:
            return batches[0]
        return jax.tree_util.tree_map(
            lambda *vs: jnp.stack(vs), *batches)

    def micro_batches_host(self):
        """Host-side (numpy) counterpart of micro_batches — the default
        producer-thread batch source for train_pipelined. Same values,
        same rng stream; the device transfer happens downstream in
        device_prefetch."""
        batches = [synthetic_protein_batch_host(self.cfg, self.np_rng)
                   for _ in range(max(1, self.cfg.accum_steps))]
        if self.cfg.accum_steps <= 1:
            return batches[0]
        return {k: np.stack([b[k] for b in batches]) for k in batches[0]}

    # ------------------------------------------------------------------ #
    # cost ledger (observability.costs): the step factories' compiled
    # program -> one schema'd `cost` record
    # ------------------------------------------------------------------ #
    def cost_record(self, batch, metric_logger=None) -> dict:
        """Ledger the CURRENT train step executable against `batch`
        (same placement rules as train_step): flops, bytes accessed,
        peak memory split argument/output/temp, collective bytes.
        Emits a `cost` record through `metric_logger` when given;
        returns the record fields either way. Lower+compile only — the
        copy never executes, so donation marks are harmless — and warm
        whenever the step already compiled under the persistent
        compilation cache."""
        assert self.params is not None, 'cost_record requires an ' \
            'initialized trainer (run a step or call init first)'
        from ..observability.costs import step_cost_payload
        if self.mesh is not None:
            batch = shard_batch(batch, self.mesh,
                                leading_axes=1 if self.cfg.accum_steps > 1
                                else 0)
        rng = jax.random.PRNGKey(self.cfg.seed)
        args = (self.params, self.opt_state, batch, rng)
        if self.cfg.telemetry:
            args = args + (self.metric_acc,)
        fields = step_cost_payload(self._step_fn, *args,
                                   label=self._telemetry_label())
        if metric_logger is not None:
            return metric_logger.log_record('cost', mirror=False, **fields)
        fields['kind'] = 'cost'
        return fields

    def _maybe_cost_record(self, batch, metric_logger, history):
        """First-step ledger hook, shared by train/train_pipelined and
        denoise.py's dataset loop. Call it BEFORE the first step: with
        donate_batch on, the step deletes the batch buffers, and
        lower() only reads shapes. Lazily inits exactly like
        train_step (accum batches carry a leading micro axis)."""
        if not self.cfg.cost_record:
            return
        try:
            if self.params is None:
                self.init(jax.tree_util.tree_map(lambda v: v[0], batch)
                          if self.cfg.accum_steps > 1 else batch)
            history.append(self.cost_record(batch, metric_logger))
        except Exception as e:  # noqa: BLE001 - the ledger must never
            # cost the training run
            import warnings
            warnings.warn(f'cost record failed ({type(e).__name__}: {e})',
                          stacklevel=2)

    # ------------------------------------------------------------------ #
    # telemetry (observability package): flush cadence owned by the host
    # ------------------------------------------------------------------ #
    def _telemetry_label(self) -> str:
        c = self.cfg
        return (f'denoise,dim={c.dim},depth={c.depth},n={c.num_nodes},'
                f'deg={c.num_degrees},accum={max(1, c.accum_steps)}')

    def _nodes_per_step(self) -> int:
        return (self.cfg.batch_size * self.cfg.num_nodes
                * max(1, self.cfg.accum_steps))

    def telemetry_flush(self, metric_logger=None):
        """Flush the window: ONE device-to-host sync (the accumulator
        fetch), host-phase percentiles, and the retrace/memory snapshot,
        as one schema'd `flush` record. Returns the record fields."""
        assert self.cfg.telemetry, 'telemetry_flush requires cfg.telemetry'
        from ..observability.metrics import merge_windows
        window, self.metric_acc = self.metric_acc.flush()
        timing = self.phase_timer.window_summary()
        runtime = self.watchdog.check()
        self._cum_metrics = merge_windows(self._cum_metrics, window)
        self._flush_count += 1
        fields = dict(step=self.step_count, window=window, timing=timing,
                      runtime=runtime)
        self._last_flush_step = self.step_count
        step_t = timing.get('step')
        if step_t and step_t['mean_ms'] > 0:
            # rate over the steps this window actually timed (the warmup
            # step is billed to its own phase and excluded)
            fields['nodes_steps_per_sec'] = round(
                self._nodes_per_step() / (step_t['mean_ms'] / 1e3), 2)
        if runtime['retraced'] and metric_logger is not None:
            metric_logger.log_record('retrace_warning',
                                     step=self.step_count,
                                     retraced=runtime['retraced'])
        if metric_logger is not None:
            return metric_logger.log_record('flush', **fields)
        return fields

    def telemetry_close(self, metric_logger=None):
        """Final flush (residual window) + the cumulative `summary`
        record: run-wide per-phase percentiles, merged metric stats,
        throughput, loss trajectory, total retrace warnings."""
        assert self.cfg.telemetry, 'telemetry_close requires cfg.telemetry'
        if self.step_count > self._last_flush_step:
            self.telemetry_flush(metric_logger)
        timing = self.phase_timer.cumulative_summary()
        total_step_s = self.phase_timer.total_seconds('step')
        steps = self.phase_timer.total_count('step')
        fields = dict(
            steps=self.step_count,
            label=self._telemetry_label(),
            metrics=self._cum_metrics or {},
            timing=timing,
            retrace_warnings_total=self.watchdog.warnings_total,
        )
        if steps and total_step_s > 0:
            fields['nodes_steps_per_sec'] = round(
                self._nodes_per_step() * steps / total_step_s, 2)
        if self._first_loss is not None:
            # the only other host syncs of the run: two scalars, at close
            first = float(jnp.asarray(self._first_loss).mean())
            last = float(jnp.asarray(self._last_loss).mean())
            fields.update(loss_first=round(first, 4),
                          loss_last=round(last, 4),
                          loss_decreased=bool(last < first)
                          and bool(np.isfinite(first))
                          and bool(np.isfinite(last)))
        if metric_logger is not None:
            return metric_logger.log_record('summary', **fields)
        return fields

    def train(self, num_steps: int, log=print, checkpoint_manager=None,
              checkpoint_every: int = 0, metric_logger=None):
        """Reference denoise.py:54-93 outer loop, with structured metrics.

        With a CheckpointManager and checkpoint_every > 0, state is saved
        periodically — the preemption-recovery story for TPU slices (the
        CLI additionally saves at exit and resumes at start).

        With cfg.telemetry, the per-step float(loss) sync disappears:
        metrics accumulate on device and flush (through `metric_logger`
        when given) every cfg.flush_every steps plus once at the end —
        history then holds the flush/summary records.

        With cfg.pipeline, dispatches to `train_pipelined` (synthetic
        batches built on a producer thread, device prefetch, async
        checkpoints) — the knob selects the overlapped loop wherever a
        caller only holds a config."""
        if self.cfg.pipeline:
            return self.train_pipelined(
                num_steps, log=log, checkpoint_manager=checkpoint_manager,
                checkpoint_every=checkpoint_every,
                metric_logger=metric_logger)
        history = []
        t0 = time.time()
        micro = max(1, self.cfg.accum_steps)
        telemetry = self.cfg.telemetry
        for i in range(num_steps):
            if telemetry:
                with self.phase_timer.phase('data'):
                    batch = self.micro_batches()
            else:
                batch = self.micro_batches()
            if i == 0:
                self._maybe_cost_record(batch, metric_logger, history)
            loss = self.train_step(batch)
            if (checkpoint_manager is not None and checkpoint_every > 0
                    and self.step_count % checkpoint_every == 0):
                with (self.phase_timer.phase('checkpoint') if telemetry
                      else contextlib.nullcontext()):
                    checkpoint_manager.save(
                        self.step_count,
                        (self.params, self.opt_state, self.step_count))
            if telemetry:
                if (i + 1) % self.cfg.flush_every == 0:
                    history.append(self.telemetry_flush(metric_logger))
                continue
            if (i + 1) % self.cfg.log_every == 0:
                loss = float(loss)  # host sync only at log interval
                dt = time.time() - t0
                nodes_per_sec = (self.cfg.batch_size * self.cfg.num_nodes
                                 * micro * (i + 1)) / dt
                rec = dict(step=self.step_count, loss=loss,
                           nodes_steps_per_sec=nodes_per_sec)
                extra = ''
                if self.last_micro_losses is not None:
                    # the mean alone hides a diverging micro-batch
                    # (reference prints every step, denoise.py:91)
                    ml = [float(v) for v in self.last_micro_losses]
                    rec['micro_loss_min'] = min(ml)
                    rec['micro_loss_max'] = max(ml)
                    extra = f' micro [{min(ml):.4f}, {max(ml):.4f}]'
                history.append(rec)
                log(f'step {self.step_count} loss {loss:.4f} '
                    f'nodes*steps/sec {nodes_per_sec:.1f}{extra}')
        if telemetry:
            history.append(self.telemetry_close(metric_logger))
        return history

    # ------------------------------------------------------------------ #
    # overlapped pipeline (training.pipeline): producer thread + device
    # prefetch + async checkpointing
    # ------------------------------------------------------------------ #
    def _pipeline_record(self, stats, metric_logger=None) -> dict:
        """One schema'd `pipeline` record from the prefetch stats."""
        fields = stats.snapshot()
        fields['step'] = self.step_count
        if metric_logger is not None:
            return metric_logger.log_record('pipeline', **fields)
        fields['kind'] = 'pipeline'
        return fields

    def train_pipelined(self, num_steps: int, batch_source=None, log=print,
                        checkpoint_manager=None, checkpoint_every: int = 0,
                        metric_logger=None, async_checkpoint: bool = True):
        """`train`, with the host taken off the critical path.

        Batches are built on a `BatchProducer` thread (default source:
        `micro_batches_host` — synthetic host batches; pass any iterator
        of host batch dicts, e.g. `pipeline.dataset_batch_source`, to
        train from files), device-placed `cfg.prefetch_depth` steps
        ahead by `device_prefetch` (honoring the mesh's NamedShardings
        when the trainer has one), and checkpoints write asynchronously
        (`CheckpointManager.save_async`) so serialization overlaps the
        step loop. With cfg.telemetry, flush records grow `host_wait` /
        `prefetch` phases and every flush interval also emits a
        `pipeline` record (prefetch hits vs stalls, producer queue
        depth, producer-bound vs device-bound verdict).

        The batch source is consumed exactly once on the producer thread
        (single-consumer); source exhaustion ends training early and
        cleanly, a source exception propagates out of this method."""
        import itertools

        from .pipeline import BatchProducer, PipelineStats, device_prefetch
        cfg = self.cfg
        telemetry = cfg.telemetry
        if batch_source is None:
            batch_source = (self.micro_batches_host()
                            for _ in range(num_steps))
        place = None
        if self.mesh is not None:
            lead = 1 if cfg.accum_steps > 1 else 0
            mesh = self.mesh

            def place(b):  # noqa: E306 - closure over mesh/lead
                return shard_batch(b, mesh, leading_axes=lead)

        stats = PipelineStats(depth=cfg.prefetch_depth,
                              capacity=cfg.producer_capacity)
        history = []
        t0 = time.time()
        micro = max(1, cfg.accum_steps)
        with BatchProducer(batch_source,
                           capacity=cfg.producer_capacity) as producer:
            stats.bind_source(producer)
            batches = device_prefetch(
                producer, depth=cfg.prefetch_depth, sharding=place,
                phase_timer=self.phase_timer, stats=stats)
            for i, batch in enumerate(itertools.islice(batches, num_steps)):
                if i == 0:
                    self._maybe_cost_record(batch, metric_logger, history)
                loss = self.train_step(batch, preplaced=True)
                if (checkpoint_manager is not None and checkpoint_every > 0
                        and self.step_count % checkpoint_every == 0):
                    with (self.phase_timer.phase('checkpoint') if telemetry
                          else contextlib.nullcontext()):
                        state = (self.params, self.opt_state,
                                 self.step_count)
                        if async_checkpoint and hasattr(checkpoint_manager,
                                                        'save_async'):
                            checkpoint_manager.save_async(self.step_count,
                                                          state)
                        else:
                            checkpoint_manager.save(self.step_count, state)
                if telemetry:
                    if (i + 1) % cfg.flush_every == 0:
                        history.append(self.telemetry_flush(metric_logger))
                        history.append(self._pipeline_record(stats,
                                                             metric_logger))
                    continue
                if (i + 1) % cfg.log_every == 0:
                    loss = float(loss)  # host sync only at log interval
                    dt = time.time() - t0
                    rate = (cfg.batch_size * cfg.num_nodes * micro
                            * (i + 1)) / dt
                    history.append(dict(step=self.step_count, loss=loss,
                                        nodes_steps_per_sec=rate))
                    log(f'step {self.step_count} loss {loss:.4f} '
                        f'nodes*steps/sec {rate:.1f} '
                        f'[pipelined: {stats.hits} hits '
                        f'{stats.stalls} stalls]')
        if checkpoint_manager is not None and hasattr(
                checkpoint_manager, 'wait_until_finished'):
            checkpoint_manager.wait_until_finished()
        if telemetry:
            history.append(self.telemetry_close(metric_logger))
            history.append(self._pipeline_record(stats, metric_logger))
        return history

    # ------------------------------------------------------------------ #
    # self-healing elastic loop (training.guardian): NaN/spike rollback,
    # preemption-safe emergency save, deterministic per-step replay
    # ------------------------------------------------------------------ #
    def train_guarded(self, num_steps: int, checkpoint_manager,
                      guard=None, injector=None, metric_logger=None,
                      restart: bool = False, step_hook=None, log=print):
        """`train` with the training fault domain wrapped around it
        (docs/ROBUSTNESS.md "Training fault domain"): window-level
        non-finite/spike detection off the telemetry accumulator (no
        extra host sync on clean steps), bounded rollback to the newest
        restorable checkpoint, SIGTERM/SIGINT -> one synchronous
        emergency save + a resumable exit, and a schema'd `guard`
        record. Requires cfg.telemetry; honors cfg.pipeline. Batches
        and step rngs derive from the ABSOLUTE step index, so a
        rolled-back or resumed run replays bit-exactly — `make
        train-chaos-smoke` gates final-param parity on it. Returns a
        `guardian.GuardResult` (`.exit_code`: 0 clean, 1 diverged,
        75 preempted-resumable)."""
        from .guardian import run_guarded
        return run_guarded(self, num_steps, checkpoint_manager,
                           guard=guard, injector=injector,
                           metric_logger=metric_logger, restart=restart,
                           step_hook=step_hook, log=log)
