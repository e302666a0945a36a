"""Sharded training-step construction (pjit over the dp/sp/tp mesh).

Builds a jitted SPMD train step: parameters and optimizer state are
replicated (they are tiny relative to the O(B*N*K) edge activations), data
is sharded dp over batch and sp over the node axis, and GSPMD propagates
shardings through the model — neighbor gathers over the full source-node
axis lower to all-gathers over ICI, loss reductions to psums. This replaces
the reference's absent distributed backend (SURVEY.md §2.9) with XLA
collectives rather than a hand-rolled NCCL/MPI layer.
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def _expect_unusable_batch_donation():
    """Batch leaves can never alias a step output (no output shares
    their shapes/dtypes — outputs alias the donated params/opt_state),
    so XLA reports every batch donation 'not usable'. That is expected
    on the donate_batch path — donation there only marks the buffers
    dead early — so silence exactly that warning instead of spamming
    every pipelined compile (docs/PERFORMANCE.md "When donation is
    safe"). Params/opt_state donations DO alias; a genuine aliasing
    regression there would surface as a perf/HBM change, not only as
    this message."""
    warnings.filterwarnings(
        'ignore', message='Some donated buffers were not usable')


# ---------------------------------------------------------------------- #
# tensor parallelism (SURVEY §5 "optional tensor sharding of the
# radial-MLP and head axes")
#
# The Megatron-style column/row rules that used to be hand-coded here
# now live as data in `parallel.rules.tp_rules` — serving
# (inference.engine, serving.*) consults the SAME rule engine, so
# training and serving shardings cannot drift. These two functions are
# thin callers kept for the established call sites.
# ---------------------------------------------------------------------- #
def param_partition_specs(params, mesh: Mesh, axis: Optional[str] = None,
                          rules=None):
    """Rule-engine-backed PartitionSpec tree for a model param pytree
    (see `parallel.rules`). Default rules: the built-in tensor-parallel
    set; `rules` may name another built-in set ('replicated' | 'tp' |
    'fsdp') or pass an explicit rule list. `axis` overrides the named
    set's own default mesh axis ('tp' for tp rules, 'dp' for fsdp) and
    is forwarded to the set factory — never silently dropped.
    Dimensions that do not divide their mesh axis demote to replication
    (audited with a summary warning, never silent)."""
    from .rules import match_partition_rules, resolve_rules, tp_rules
    if rules is None:
        rules = tp_rules(axis) if axis is not None else tp_rules()
    else:
        rules = resolve_rules(rules, axis)
    return match_partition_rules(rules, params, mesh=mesh)


def shard_params(params, mesh: Mesh, axis: Optional[str] = None,
                 rules=None):
    """Place a param pytree on the mesh with rule-engine sharding
    (tensor-parallel by default; `axis`/`rules` as in
    `param_partition_specs`)."""
    specs = param_partition_specs(params, mesh, axis, rules=rules)
    return jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        params, specs)


def composed_state_shardings(params, opt_state, mesh: Mesh,
                             rules='composed', axis: Optional[str] = None):
    """Place params + optimizer state for the composed dp x sp x tp mesh
    and hand back the pinned-sharding pair the step factories need.

    This is the ROADMAP item 4 route, end to end: params through the
    rule engine (default: the `composed` set — Megatron tp placements
    with a tp-free dim over dp), optimizer state through
    `shard_opt_state` under the SAME rules (adam's mu/nu inherit each
    param's audited spec; scalars like `count` replicate ON THE MESH —
    an eager `optimizer.init` leaves them on a SingleDeviceSharding and
    the pinned jit then rejects the device mix), then the leaves'
    actual NamedShardings collected into the
    `state_shardings=(param_shardings, opt_shardings)` pair.

    Pinning matters: on jax 0.4.37 the dp2/sp2/tp2 mesh dies in the
    GSPMD donation-aliasing INTERNAL error ("Expected aliased input ...
    to have the same size") whenever out_shardings are left to AUTO —
    GSPMD picks a finer output sharding than the donated input carries.
    Passing this pair to `make_sharded_train_step(...,
    state_shardings=...)` pins in AND out shardings on every donated
    state argument, so each alias stays shape-preserving and the
    combined mesh compiles and runs (the PR 13 fsdp fix, extended to
    all three axes).

    Returns (placed_params, placed_opt_state, state_shardings)."""
    from .rules import place_with_rules, resolve_rules, shard_opt_state
    resolved = resolve_rules(rules, axis)   # once: params and opt state
    params, _ = place_with_rules(params, mesh, resolved)
    opt_state, _ = shard_opt_state(opt_state, params, mesh, rules=resolved)
    shardings = tuple(
        jax.tree_util.tree_map(lambda leaf: leaf.sharding, tree)
        for tree in (params, opt_state))
    return params, opt_state, shardings


def make_sharded_train_step(loss_fn: Callable, optimizer,
                            mesh: Optional[Mesh] = None,
                            donate: bool = True,
                            donate_batch: bool = False,
                            tensor_parallel: bool = False,
                            sharded_state: bool = False,
                            state_shardings=None,
                            telemetry: bool = False):
    """loss_fn(params, batch, rng) -> (loss, aux). Returns
    step(params, opt_state, batch, rng) -> (params, opt_state, loss, aux),
    jitted; when `mesh` is given, the caller is expected to place `batch`
    with parallel.mesh.shard_batch. Params/opt_state are replicated by
    default; with `tensor_parallel=True` they instead keep the placement
    the caller gave them (see `shard_params`), so tp-partitioned weights
    stay partitioned through the update and GSPMD inserts the psum for
    the row-parallel contractions.

    `sharded_state=True` is the true-FSDP wiring (ROADMAP item 4's
    named next step): like tensor_parallel, params AND optimizer state
    follow the placement the caller gave them — the caller shards
    params with `shard_params(..., rules='fsdp')` and the optimizer
    state with `parallel.rules.shard_opt_state` (adam's mu/nu inherit
    their param's audited spec), and the step's in/out shardings stay
    None on both so the update runs shard-local and nothing
    re-replicates. Before this flag, opt state replicated by default on
    every chip — 2x the parameter memory — despite the specs existing.

    `state_shardings=(param_shardings, opt_shardings)` (pytrees of
    NamedSharding matching the state trees) PINS the step's in AND out
    shardings for params/opt_state to exactly those placements. This is
    the explicit-aliasing route around the jax-0.4.37 GSPMD donation
    bug (the PR 5 residue): with out_shardings left to AUTO, GSPMD may
    pick a FINER output sharding than the donated input carries (e.g.
    dp+sp on a multi-axis mesh where the input is dp-only) and the
    donation dies in an INTERNAL aliased-size error — pinning output
    to input keeps every alias shape-preserving. The caller knows the
    placements (it made them with shard_params/shard_opt_state), so it
    passes them; DenoiseTrainer does this under cfg.fsdp.

    With `telemetry=True` the step signature grows by exactly one
    argument/result — an `observability.MetricAccumulator` pytree that
    folds loss and global grad norm ON DEVICE (a handful of scalar ops,
    no host sync): step(params, opt_state, batch, rng, acc) ->
    (params, opt_state, loss, aux, acc). The host flushes the
    accumulator once per logging interval.

    Donation audit. `donate=True` donates params/opt_state (and the
    telemetry accumulator) — always safe: the caller rebinds all three
    to the step's outputs, and sharded buffers are donated in place so
    tp-partitioned training resumes/continues without a host round
    trip; with `sharded_state` the donated adam mu/nu are themselves
    sharded and alias their (identically-sharded) outputs shard-for-
    shard — the input and output live on the same devices with the
    same per-shard shapes, so donation stays an in-place alias, never
    a cross-device move; checkpointing snapshots device copies first
    (`training.checkpoint.snapshot_device_arrays`), so async saves
    survive the donation too. `donate_batch=True` additionally donates
    the batch pytree (argnum 2) and is OPT-IN: it is only safe when
    every batch the step sees is freshly built or freshly placed — the
    `training.pipeline.device_prefetch` path, or any caller going
    through `parallel.mesh.shard_batch` (which device_puts fresh
    arrays per call). A caller that feeds the SAME device batch to two
    steps must leave it off, or the second step reads deleted buffers.
    """

    # `loss` and `optimizer` are leaves of MODEL_SCOPES: whatever the
    # model's own scopes do not claim inside the differentiated loss (the
    # objective, its noise) reads as `loss`, the update as `optimizer`.
    # Both variants are called `train_step`, the `fun_name` under which
    # the compile log (observability.runtime) files their seconds.
    def _grads_and_update(params, opt_state, batch, rng):
        with jax.named_scope('loss'):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, rng)
        with jax.named_scope('optimizer'):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss, aux, grads

    if telemetry:
        def train_step(params, opt_state, batch, rng, acc):
            params, opt_state, loss, aux, grads = _grads_and_update(
                params, opt_state, batch, rng)
            acc = acc.update(loss=loss, grad_norm=optax.global_norm(grads))
            return params, opt_state, loss, aux, acc
    else:
        def train_step(params, opt_state, batch, rng):
            return _grads_and_update(params, opt_state, batch, rng)[:4]

    # the accumulator is replaced every step — donate it like the state;
    # the batch (argnum 2) only on request (see the donation audit above)
    donate_argnums = ((0, 1, 4) if telemetry else (0, 1)) if donate else ()
    if donate and donate_batch:
        donate_argnums = tuple(sorted(donate_argnums + (2,)))
        _expect_unusable_batch_donation()
    if mesh is None:
        return jax.jit(train_step, donate_argnums=donate_argnums)

    repl = replicated(mesh)
    acc_in = (repl,) if telemetry else ()
    acc_out = (repl,) if telemetry else ()
    if state_shardings is not None:
        ps, os_ = state_shardings
        return jax.jit(train_step,
                       in_shardings=(ps, os_, None, repl) + acc_in,
                       out_shardings=(ps, os_, repl, repl) + acc_out,
                       donate_argnums=donate_argnums)
    if tensor_parallel or sharded_state:
        # None = follow the argument/result placement (params arrive
        # pre-sharded by shard_params, opt state — under sharded_state —
        # by shard_opt_state; donation keeps buffers in place)
        return jax.jit(train_step,
                       in_shardings=(None, None, None, repl) + acc_in,
                       out_shardings=(None, None, repl, repl) + acc_out,
                       donate_argnums=donate_argnums)
    return jax.jit(
        train_step,
        in_shardings=(repl, repl, None, repl) + acc_in,
        out_shardings=(repl, repl, repl, repl) + acc_out,
        donate_argnums=donate_argnums)


def make_accumulating_train_step(loss_fn: Callable, optimizer,
                                 accum_steps: int,
                                 mesh: Optional[Mesh] = None,
                                 donate_batch: bool = False,
                                 tensor_parallel: bool = False,
                                 sharded_state: bool = False,
                                 state_shardings=None,
                                 telemetry: bool = False):
    """Gradient-accumulation variant (reference denoise.py:13,55 uses 16
    micro-steps). batch leaves must have a leading [accum_steps, ...] axis;
    micro-batches are consumed with lax.scan so the compiled program is
    O(1) in accum_steps.

    `telemetry=True` threads a MetricAccumulator exactly like
    make_sharded_train_step; the per-micro-step loss VECTOR folds in, so
    the flushed window's loss min/max expose a diverging micro-batch.
    `donate_batch=True` donates the stacked micro-batch pytree — same
    opt-in safety contract as make_sharded_train_step (fresh batch per
    step only). `sharded_state=True` follows the caller's params AND
    opt-state placement (the true-FSDP wiring — see
    make_sharded_train_step's donation audit: sharded mu/nu donate as
    in-place aliases)."""

    def _grads_and_losses(params, batch, rng):
        def micro(carry, xs):
            acc, rng = carry
            micro_batch, = xs
            rng, sub = jax.random.split(rng)
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, micro_batch, sub)
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return (acc, rng), loss

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (grads, _), losses = jax.lax.scan(micro, (zeros, rng), (batch,))
        return jax.tree_util.tree_map(lambda g: g / accum_steps,
                                      grads), losses

    # named and scoped like make_sharded_train_step's: `train_step` in the
    # compile log, the leaves `loss` and `optimizer` in a trace
    def _grads_and_update(params, opt_state, batch, rng):
        with jax.named_scope('loss'):
            grads, losses = _grads_and_losses(params, batch, rng)
        with jax.named_scope('optimizer'):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, losses, grads

    if telemetry:
        def train_step(params, opt_state, batch, rng, acc):
            params, opt_state, losses, grads = _grads_and_update(
                params, opt_state, batch, rng)
            acc = acc.update(loss=losses,
                             grad_norm=optax.global_norm(grads))
            return params, opt_state, losses.mean(), losses, acc
    else:
        def train_step(params, opt_state, batch, rng):
            # per-micro-step losses ride along (the reference prints every
            # outer step's loss, denoise.py:91: the mean alone hides a
            # diverging micro-batch); same 4-arity as
            # make_sharded_train_step
            params, opt_state, losses, _ = _grads_and_update(
                params, opt_state, batch, rng)
            return params, opt_state, losses.mean(), losses

    fn = train_step
    donate_argnums = (0, 1, 4) if telemetry else (0, 1)
    if donate_batch:
        donate_argnums = tuple(sorted(donate_argnums + (2,)))
        _expect_unusable_batch_donation()
    if mesh is None:
        return jax.jit(fn, donate_argnums=donate_argnums)
    repl = replicated(mesh)
    acc_s = (repl,) if telemetry else ()
    if state_shardings is not None:
        # pinned state placements (see make_sharded_train_step: the
        # explicit-aliasing route around the GSPMD donation bug)
        ps, os_ = state_shardings
        return jax.jit(fn, in_shardings=(ps, os_, None, repl) + acc_s,
                       out_shardings=(ps, os_, repl, repl) + acc_s,
                       donate_argnums=donate_argnums)
    if tensor_parallel or sharded_state:
        return jax.jit(fn, in_shardings=(None, None, None, repl) + acc_s,
                       out_shardings=(None, None, repl, repl) + acc_s,
                       donate_argnums=donate_argnums)
    return jax.jit(fn, in_shardings=(repl, repl, None, repl) + acc_s,
                   out_shardings=(repl, repl, repl, repl) + acc_s,
                   donate_argnums=donate_argnums)
