"""AOT-precompiled bucketed inference engine.

Training amortizes one compile over thousands of steps; serving cannot —
a request that triggers a fresh XLA compile pays seconds-to-minutes of
latency, which on a tail percentile is an outage. The engine therefore
moves ALL compilation to startup:

  * `jax.jit(fn).lower(...).compile()` once per shape bucket, giving a
    dict of AOT executables keyed `(bucket_len, batch_size, dtype)`.
    AOT executables cannot retrace — an off-contract shape is a loud
    TypeError at the engine boundary, never a silent compile (the
    `RetraceWatchdog`'s compile-event counter doubles as the proof:
    zero post-warmup events on a healthy engine).
  * the bucket's chain adjacency is baked into each executable as a
    trace-time constant (one fewer transfer per call), matching the
    shapes `PointCloudDataset.batches` produces for training.
  * `donate_buffers=True` (default off-CPU) donates the coords buffer —
    the largest per-call input — back to XLA for output reuse.
  * `activation_dtype=jnp.bfloat16` casts coords on the way in and the
    output back to float32: the bf16 serving path; it quantizes tensors
    that rotate, so equivariance lands in the 1e-3 class, not 1e-6.

Params stay a call argument (not baked), so a checkpoint refresh is
`engine.params = mgr.restore_params()` — no recompile as long as shapes
match. The persistent compilation cache (`utils.compilation_cache`)
makes even the startup compiles warm across process restarts.

Quantized serving (ROADMAP item 3): `precision='int8_mix'` (or any
quant.rules mix / explicit rule list) quantizes the params INSIDE the
params setter — restore-time, on host — so the AOT buckets compile
against the quantized abstract tree and the fp32 degree-0 weights
never materialize on device. Weight swaps re-quantize at the engine's
own mix (zero recompiles — shapes/dtypes are unchanged), and every
bucket's cost record carries the mix + the before/after param bytes.

Sharded serving (ROADMAP item 3): pass `mesh` (+ optionally
`partition_rules`, a `parallel.rules` rule set name or rule list —
default 'tp') and the engine becomes mesh-aware end to end: params are
restored/placed directly into their `NamedSharding`s via the partition-
rule engine (the SAME rules training's `shard_params` uses — serving
and training shardings cannot drift), every bucket executable is
AOT-compiled against the SHARDED abstract params (so one large model
spans chips while DP replicas multiply throughput), and request arrays
are committed replicated onto the mesh at `run()`. The params-only
orbax restore path and the per-bucket cost ledger are unchanged.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..native.loader import chain_adjacency, pad_to_bucket
from ..observability import PhaseTimer
from .admission import fit_bucket, oversize_error


def bucket_phase(bucket: int) -> str:
    """The PhaseTimer phase name for a bucket's execute latency."""
    return f'bucket_{bucket}'


class InferenceEngine:
    """Restore params, precompile per bucket, answer fixed-shape batches.

        module = DenoiseConfig(...).build_module()
        engine = InferenceEngine.from_checkpoint(
            module, '/ckpts/run1', buckets=(64, 128), batch_size=8)
        out = engine.predict(tokens, coords)          # one request
        out = engine.run(128, tokens, coords, mask)   # a padded batch

    `run` is the `MicroBatcher` runner; `predict` is the convenience
    single-request path (pads to the smallest fitting bucket). Both
    block until the result is ready so the per-bucket PhaseTimer
    percentiles are honest device latencies.
    """

    def __init__(self, module, params, *,
                 buckets: Sequence[int] = (64, 128, 256, 512),
                 batch_size: int = 1,
                 return_type: int = 1,
                 activation_dtype: Optional[jnp.dtype] = None,
                 with_chain_adjacency: bool = True,
                 donate_buffers: Optional[bool] = None,
                 apply_kwargs: Optional[dict] = None,
                 timer: Optional[PhaseTimer] = None,
                 mesh: Optional[Mesh] = None,
                 partition_rules=None,
                 precision=None,
                 precompile: bool = True,
                 fault_injector=None):
        self.module = module
        # the family capability signal (replica snapshots / HostServer
        # stats surface it for family-aware fleet placement; v1 modules
        # stamp 'se3_v1', v2 'se3_v2')
        self.model_family = getattr(module, 'model_family', 'se3_v1')
        self.mesh = mesh
        # chaos-harness hook (faults.FaultInjector): fires at the top
        # of run() so injected engine failures/latency walk the real
        # execution path; None in production costs nothing
        self.fault_injector = fault_injector
        # rule set name ('replicated'/'tp'/'fsdp') or explicit rule
        # list (parallel.rules); only consulted when a mesh is given
        self.partition_rules = ('tp' if partition_rules is None
                                else partition_rules)
        # weight-precision mix (quant.rules): a shipped mix name
        # ('int8_mix' / 'bf16' / 'fp8_mix') or explicit (regex,
        # precision) rules. The params SETTER quantizes — restore-time,
        # on host, BEFORE the device_put — so the fp32 degree-0 weights
        # never materialize on device (test-pinned); None/'fp32' is the
        # bit-identical passthrough. Orthogonal to activation_dtype
        # (weight storage vs activation compute).
        self.precision = None if precision in (None, 'fp32') \
            else precision
        self.precision_name = 'fp32'
        self.quant_report = None
        if self.precision is not None:
            from ..quant import mix_name, resolve_mix
            resolve_mix(self.precision)   # fail fast on a bad mix name
            self.precision_name = mix_name(self.precision)
        self.param_specs = None      # filled by the params setter
        self.params = params         # property setter device_puts once
        self.buckets = tuple(sorted(int(b) for b in buckets))
        assert self.buckets, 'no buckets'
        self.batch_size = int(batch_size)
        self.return_type = return_type
        self.activation_dtype = activation_dtype
        self.with_chain_adjacency = with_chain_adjacency
        if donate_buffers is None:
            # donation is a no-op-with-warning on CPU; auto-enable only
            # where the backend implements it
            donate_buffers = jax.default_backend() != 'cpu'
        self.donate_buffers = bool(donate_buffers)
        self.apply_kwargs = dict(apply_kwargs or {})
        self.timer = timer if timer is not None else PhaseTimer()
        self._executables: Dict[Tuple[int, int, str], Callable] = {}
        self.compile_seconds: Dict[Tuple[int, int, str], float] = {}
        # per-bucket schema'd `cost` record bodies (observability.costs)
        # — serving capacity planning reads memory-per-bucket off these;
        # ServeTelemetry.arm() emits them into the telemetry stream
        self.cost_payloads: Dict[Tuple[int, int, str], dict] = {}
        self.tuning_consults: list = []  # filled by warmup()
        self.batches_served: Dict[int, int] = {b: 0 for b in self.buckets}
        self.rows_served: Dict[int, int] = {b: 0 for b in self.buckets}
        if precompile:
            self.warmup()

    # ------------------------------------------------------------------ #
    @classmethod
    def from_checkpoint(cls, module, checkpoint_dir: str,
                        step: Optional[int] = None, **kwargs
                        ) -> 'InferenceEngine':
        """Params-only restore (`CheckpointManager.restore_params`) —
        optimizer state never materializes on the serving host. The
        module's `model_family` stamp rides into the manager, so
        loading a v1 checkpoint into a v2 module (or vice versa) fails
        with the structured ModelFamilyMismatch, not a flax key
        error."""
        from ..training.checkpoint import CheckpointManager
        params = CheckpointManager(
            checkpoint_dir,
            model_family=getattr(module, 'model_family', None),
        ).restore_params(step)
        return cls(module, params, **kwargs)

    # ------------------------------------------------------------------ #
    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        # device_put ONCE per (re)load — restore_params hands back numpy
        # leaves, and re-transferring the whole parameter set host-to-
        # device on every run() call would dominate per-batch latency
        # off-CPU. A setter so the checkpoint-refresh recipe
        # `engine.params = mgr.restore_params()` stays fast too. With a
        # mesh, every leaf goes straight into the NamedSharding its
        # partition rule names (host arrays shard on the way in — the
        # full tensor is never replicated across the mesh first), and a
        # weight swap re-places into the SAME specs so the AOT
        # executables keep matching without a recompile.
        #
        # With a precision mix, quantization happens HERE, on host,
        # before any device placement: the quantized pytree (int8/fp8
        # QuantTensors + scales, bf16 casts) is what lands in HBM — the
        # fp32 tree never does. The same setter is the rolling-swap
        # re-quantization contract: `swap_weights(raw_fp32_params)`
        # re-quantizes at THIS engine's mix (each replica may run its
        # own), shapes/dtypes are unchanged, so the AOT executables
        # keep matching — zero drops, zero recompiles. A tree that is
        # already quantized (e.g. handed between engines) passes
        # through untouched.
        if self.precision is not None:
            from ..quant import is_quantized, quantize_params
            if not is_quantized(value):
                value, self.quant_report = quantize_params(
                    value, self.precision)
        if self.mesh is None:
            self._params = jax.device_put(value)
            return
        from ..parallel.rules import place_with_rules
        self._params, self.param_specs = place_with_rules(
            value, self.mesh, self.partition_rules)

    @property
    def dtype_name(self) -> str:
        return (jnp.dtype(self.activation_dtype).name
                if self.activation_dtype is not None else 'float32')

    def _key(self, bucket: int) -> Tuple[int, int, str]:
        # the precision mix folds into the key's dtype slot: an int8
        # engine's executables must never collide with an fp32 one's
        # in caches keyed on these tuples (the bucket stays slot 0 —
        # telemetry reads key[0])
        dt = self.dtype_name
        if self.precision is not None:
            dt = f'{dt}+{self.precision_name}'
        return (int(bucket), self.batch_size, dt)

    @property
    def executables(self) -> Dict[Tuple[int, int, str], Callable]:
        return dict(self._executables)

    def _make_fn(self, bucket: int) -> Callable:
        adj = (jnp.asarray(chain_adjacency(bucket))
               if self.with_chain_adjacency else None)
        act = self.activation_dtype
        module, return_type, extra = (self.module, self.return_type,
                                      self.apply_kwargs)

        def fn(params, tokens, coords, mask):
            if act is not None:
                coords = coords.astype(act)
            out = module.apply({'params': params}, tokens, coords,
                               mask=mask, adj_mat=adj,
                               return_type=return_type, **extra)
            if act is not None:
                out = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), out)
            return out

        return fn

    @property
    def _replicated(self) -> Optional[NamedSharding]:
        return (NamedSharding(self.mesh, P())
                if self.mesh is not None else None)

    def _abstract_batch(self, bucket: int):
        B, L = self.batch_size, bucket
        repl = self._replicated

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

        return (sds((B, L), jnp.int32), sds((B, L, 3), jnp.float32),
                sds((B, L), jnp.bool_))

    def _abstract_params(self):
        """ShapeDtypeStructs of the placed params; on a mesh they carry
        the rule engine's NamedShardings, so the AOT compile partitions
        the whole program around sharded weights."""
        mesh = self.mesh

        def abstract(a, spec=None):
            sharding = (NamedSharding(mesh, spec)
                        if mesh is not None else None)
            return jax.ShapeDtypeStruct(
                np.shape(a), getattr(a, 'dtype', np.dtype(type(a))),
                sharding=sharding)

        if mesh is None:
            return jax.tree_util.tree_map(abstract, self.params)
        return jax.tree_util.tree_map(abstract, self.params,
                                      self.param_specs)

    def compile_bucket(self, bucket: int) -> Callable:
        """AOT lower+compile one bucket's executable (idempotent)."""
        key = self._key(bucket)
        if key in self._executables:
            return self._executables[key]
        assert bucket in self.buckets, f'{bucket} is not a configured bucket'
        abstract_params = self._abstract_params()
        tokens, coords, mask = self._abstract_batch(bucket)
        donate = (2,) if self.donate_buffers else ()  # coords buffer
        t0 = time.perf_counter()
        executable = (jax.jit(self._make_fn(bucket), donate_argnums=donate)
                      .lower(abstract_params, tokens, coords, mask)
                      .compile())
        self.compile_seconds[key] = round(time.perf_counter() - t0, 3)
        self._executables[key] = executable
        try:
            # one cost ledger entry per bucket executable: peak HBM
            # split + flops, the capacity-planning surface (guarded —
            # introspection must never fail a compile that succeeded)
            from ..observability.costs import cost_payload
            body = cost_payload(
                executable,
                label=f'bucket_{bucket},b={self.batch_size},'
                      f'dtype={self.dtype_name},'
                      f'precision={self.precision_name}')
            # the precision mix + the restore-time before/after param
            # bytes ride every bucket's cost record — the per-replica
            # memory claim is a ledger field, not prose (extra fields
            # are schema-legal on cost records)
            body['precision_mix'] = self.precision_name
            if self.quant_report is not None:
                body['quant'] = dict(self.quant_report)
            self.cost_payloads[key] = body
        except Exception as e:  # noqa: BLE001
            import sys
            print(f'engine: cost introspection failed for bucket '
                  f'{bucket} ({type(e).__name__}: {e})', file=sys.stderr)
        return executable

    def warmup(self) -> Dict[Tuple[int, int, str], float]:
        """Compile every bucket; returns per-executable compile seconds.
        Call before arming a RetraceWatchdog — afterwards a healthy
        engine produces ZERO compile events. Each compile also ledgers
        its executable into `cost_payloads` (one schema'd `cost` body
        per bucket — ServeTelemetry.arm() streams them out).

        Also records which kernel block picks the AOT compiles resolved
        from the measured tuning table vs the heuristic
        (kernels/tuning.py): `tuning_consults` / stats()['kernel_tuning']
        — a serving deployment benchmarked under a tuned entry must be
        distinguishable from a heuristic one in its telemetry."""
        from ..kernels import tuning
        # drop the kernel jit caches first: picks resolve at trace time,
        # so a kernel traced earlier in-process (training, a prior
        # engine) would compile these buckets without recording a single
        # consult
        if any(self._key(b) not in self._executables
               for b in self.buckets):
            tuning.clear_kernel_caches()
        snap = tuning.snapshot()
        for b in self.buckets:
            self.compile_bucket(b)
        consults = tuning.consults_since(snap)
        if consults or not self.tuning_consults:
            # a re-warmup with every bucket already compiled records an
            # (accurate) empty delta — it must not wipe the consults of
            # the warmup that actually built the executables
            self.tuning_consults = consults
        adopted = [c for c in self.tuning_consults
                   if c['source'] != 'heuristic']
        if adopted:
            import sys
            print('engine warmup: tuned kernel table entries in effect: '
                  + '; '.join(
                      f"{c['kernel']}{tuple(c['shape'])}->"
                      f"{tuple(c['blocks'])} ({c['source']})"
                      for c in adopted), file=sys.stderr)
        return dict(self.compile_seconds)

    # ------------------------------------------------------------------ #
    def bucket_for(self, length: int) -> Optional[int]:
        return fit_bucket(self.buckets, length)

    @property
    def max_len(self) -> int:
        return self.buckets[-1]

    def run(self, bucket: int, tokens, coords, mask):
        """Execute one padded fixed-shape batch on the bucket's AOT
        executable; blocks until the result is ready (honest latency)."""
        if self.fault_injector is not None:
            self.fault_injector.fire('engine_run', bucket=int(bucket))
        executable = self._executables.get(self._key(bucket))
        if executable is None:
            executable = self.compile_bucket(bucket)
        tokens = jnp.asarray(tokens, jnp.int32)
        coords = jnp.asarray(coords, jnp.float32)
        mask = jnp.asarray(mask, jnp.bool_)
        if self.mesh is not None:
            # AOT executables are strict about input placement: commit
            # the request arrays replicated onto the mesh (the compiled
            # program was lowered with exactly these shardings)
            repl = self._replicated
            tokens, coords, mask = (jax.device_put(x, repl)
                                    for x in (tokens, coords, mask))
        with self.timer.phase(bucket_phase(bucket)):
            out = executable(self.params, tokens, coords, mask)
            out = jax.block_until_ready(out)
        self.batches_served[bucket] += 1
        self.rows_served[bucket] += int(np.asarray(mask).any(-1).sum())
        return out

    def predict(self, tokens, coords) -> np.ndarray:
        """One request end to end: pad to the smallest fitting bucket,
        run, return only the real (unpadded) rows."""
        tokens = np.asarray(tokens)
        length = len(tokens)
        bucket = self.bucket_for(length)
        if bucket is None:
            raise oversize_error(length, self.max_len)
        t, c, m = pad_to_bucket([tokens], [coords], bucket,
                                batch_size=self.batch_size)
        out = np.asarray(self.run(bucket, t, c, m))
        return out[0, :length]

    # ------------------------------------------------------------------ #
    def stats(self) -> dict:
        """Engine-side counters for the serve telemetry record."""
        sharding = None
        if self.mesh is not None:
            n_sharded = sum(
                1 for s in jax.tree_util.tree_leaves(
                    self.param_specs,
                    is_leaf=lambda x: isinstance(x, P))
                if any(a is not None for a in s))
            sharding = dict(
                mesh={a: int(s) for a, s in
                      zip(self.mesh.axis_names, self.mesh.devices.shape)},
                rules=(self.partition_rules
                       if isinstance(self.partition_rules, str)
                       else 'custom'),
                sharded_params=n_sharded)
        return dict(
            buckets=list(self.buckets), batch_size=self.batch_size,
            dtype=self.dtype_name, sharding=sharding,
            precision=self.precision_name,
            model_family=self.model_family,
            quant=(dict(self.quant_report)
                   if self.quant_report is not None else None),
            executables=[list(k) for k in self._executables],
            compile_seconds={str(k[0]): v
                             for k, v in self.compile_seconds.items()},
            batches_served={str(b): n
                            for b, n in self.batches_served.items() if n},
            rows_served={str(b): n
                         for b, n in self.rows_served.items() if n},
            # memory-per-bucket off the ledger (peak = arg+out+temp,
            # XLA's static estimate; full bodies in cost_payloads)
            peak_hbm_by_bucket={str(k[0]): v['peak_bytes']
                                for k, v in self.cost_payloads.items()},
            kernel_tuning=list(self.tuning_consults))
