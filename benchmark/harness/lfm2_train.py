"""The training cells of the decoder whose layers are an operator and a
feed-forward (gated short convolutions, grouped-query attention with q/k
norms and rotation, a dense or an expert feed-forward, tied head): the
program's decoder on the program's one step factory, exactly as
`lm_train.py` drives the token decoder, compared with its own plain
reference (`lfm2_reference.py`) and priced by its own counts
(`lfm2_counts.py`).

Everything of `lm_train.py` that names neither `lm_reference` nor
`lm_counts` is taken from there, as `hybrid_train.py` takes it: the
program's lookup (a program without the recipe ends the cell at once with
one line), the caches, the seeded batches, the step, the fetch, the first
steps and the comparison. Its `build`, `reference_steps` and `run` name
them, so this file has its own; the window loop is `lm_train.run`'s, line
for line.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import lfm2_counts, lfm2_reference, lm_train, state
from .device import device_record
from .lm_train import (  # noqa: F401  (the calibration reads them here)
    COUNTERS, INPUTS, SPANS, compare, fetch, first_steps, own_cache, program,
    reseed,
)


def make_fill(abstract):
    """`lm_train.make_fill` (the experts' stacked matrices by fan_in, the
    correction bias at 0.01 z), but the embedding by `state.py`'s rule, rows
    of RMS 1 / sqrt(features): the matrix is the head too, and at unit RMS
    the logits would have a deviation of sqrt(features) = 45."""
    base = lm_train.make_fill(abstract)

    def adjust(path, leaf):
        if state._leaf_name(path) == 'embedding':
            return leaf * leaf.shape[-1] ** -0.5
        return leaf

    def fill(key):
        return jax.tree_util.tree_map_with_path(adjust, base(key))

    jitted = jax.jit(fill)
    jitted.delta = jax.jit(lambda p, key: jax.tree_util.tree_map(
        jnp.subtract, p, fill(key)))
    return jitted


def build(cell, seed, prog):
    import optax
    cfg, mix = cell['config'], cell['traffic']
    module = prog['recipe'](**cfg['model'], **cfg['overrides'])
    abstract = jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((mix['batch'], mix['seq']), jnp.int32))['params']
    assert state.param_count(abstract) == lfm2_counts.total_params(
        cfg['model']) + lfm2_counts.bias_entries(cfg['model']), \
        'config model sizes are not what the module has'
    opt = cfg['optimizer']
    assert opt['name'] == 'adam', opt
    optimizer = optax.adam(opt['learning_rate'])
    loss_fn = prog['make_lm_loss'](module, **cfg['loss'])
    built = dict(step=prog['make_step'](loss_fn, optimizer),
                 fill=make_fill(abstract), abstract=abstract,
                 balance=lambda params, data: prog['balance'](
                     module, params, data),
                 bias_names=module.expert_layer_names(),
                 init_opt=jax.jit(optimizer.init),
                 key=jax.random.PRNGKey(0))      # the loss draws nothing
    reseed(built, cell, seed)
    return built


_PLAIN_STEPS = {}


def _plain_step(cfg, operand_bits):
    """Loss and gradient by the plain reference, then plain Adam: one program
    for every step (t is traced), batch and seed."""
    key = (cfg['name'], operand_bits)
    if key in _PLAIN_STEPS:
        return _PLAIN_STEPS[key]

    def loss_of(theta, tokens):
        return lfm2_reference.loss(theta, tokens, cfg['model'],
                                   **cfg['reference'],
                                   operand_bits=operand_bits)

    def step(theta, mu, nu, t, tokens):
        (loss, chosen), g = jax.value_and_grad(loss_of, has_aux=True)(
            theta, tokens)
        theta, mu, nu = lfm2_reference.adam_update(
            theta, g, mu, nu, t, lr=cfg['optimizer']['learning_rate'])
        return theta, mu, nu, loss, g, chosen

    _PLAIN_STEPS[key] = jax.jit(
        step, donate_argnums=(0, 1, 2),
        compiler_options={'exec_time_optimization_effort': -1.0,
                          'memory_fitting_effort': -1.0})
    return _PLAIN_STEPS[key]


def reference_steps(cell, inputs, n_steps, operand_bits=None):
    """The plain reference follows the same first steps from the same seeded
    weights and batches, with its own Adam. `operand_bits`: the control."""
    cfg = cell['config']
    fill, wkey, tokens, biases = (inputs[k] for k in INPUTS)
    plain_step = _plain_step(cfg, operand_bits)
    keep = own_cache(cell, 'lfm2_reference')
    try:
        losses, grad, grad_tree, choice = [], None, None, None
        theta = fill(wkey)
        for name, bias in biases.items():
            theta[name]['moe']['correction_bias'] = jnp.asarray(bias)
        mu = jax.tree_util.tree_map(jnp.zeros_like, theta)
        nu = jax.tree_util.tree_map(jnp.zeros_like, theta)
        for t in range(1, n_steps + 1):
            t0 = time.perf_counter()
            theta, mu, nu, loss, g, chosen = plain_step(
                theta, mu, nu, jnp.float32(t),
                jnp.asarray(tokens[(t - 1) % len(tokens)]))
            losses.append(float(np.asarray(loss)))
            print(f'reference step {t}'
                  f'{"" if operand_bits is None else f" {operand_bits}"}: '
                  f'{time.perf_counter() - t0:.1f} s', flush=True)
            if t == 1:
                grad = state.leaf_norms(g)
                grad_tree = [np.asarray(a)
                             for a in jax.tree_util.tree_leaves(g)]
                choice = np.asarray(chosen)
            del g, chosen
        delta = fill.delta(theta, wkey)
        return dict(losses=losses, grad=grad, grad_tree=grad_tree,
                    choice=choice, delta=state.leaf_norms(delta))
    finally:
        lm_train._cache_dir(keep)


def run(cell, args, t_start, spans, devices, kind, peaks):
    from . import readers, trace as trace_mod

    cfg, mix = cell['config'], cell['traffic']
    prog = program(cfg)
    own_cache(cell, 'lfm2_train')
    seq, batch = mix['seq'], mix['batch']
    n_check = cfg['correct']['check_steps']
    built = build(cell, args.seed, prog)
    print(f'state: {state.param_count(built["abstract"]) / 1e6:.1f} M '
          f'parameters filled from the seed on the device; '
          f'{len(built["data"])} batches of {batch} x {seq} tokens placed',
          flush=True)
    numbers = first_steps(built, n_check, spans)
    print(f'first {n_check} steps (compile or cache hit in the first): '
          f'{[round(x, 2) for x in spans.durations["first_step"]]} s, '
          f'losses {numbers["losses"]}', flush=True)
    readers.print_cache_size()

    tracing = bool(args.trace)
    budget = mix['trace_steps'] if tracing else None
    losses, counters = [], dict.fromkeys(COUNTERS, 0.0)
    if tracing:
        trace_dir = trace_mod.start(cell, args.seed)
    spans.armed = True
    t0 = t_last = time.perf_counter()
    setup_s = t0 - t_start
    while (len(losses) < budget) if tracing else \
            (time.perf_counter() < t0 + args.seconds):
        with spans.span('batch_pick'):
            batch_i = built['data'][built['turn'] % len(built['data'])]
            built['turn'] += 1
        with spans.span('step_call'):
            built['params'], built['opt_state'], loss, aux = built['step'](
                built['params'], built['opt_state'], batch_i, built['key'])
        with spans.span('loss_fetch'):
            loss, scalars = fetch(loss, aux)
        losses.append(loss)
        for k in COUNTERS:
            counters[k] += scalars[k]
        t_last = time.perf_counter()
    spans.armed = False
    elapsed = t_last - t0
    summary = trace_mod.stop(trace_dir, elapsed, set(SPANS)) \
        if tracing else None
    spans.check_no_compiles()
    device = device_record(devices, kind)
    mem_stats = devices[0].memory_stats() or {}

    steps = len(losses)
    rate = batch * seq * steps / elapsed
    flops = batch * lfm2_counts.train_step_flops(
        cfg['model'], seq, counters['moe_local_pairs'] / max(steps, 1) / batch)
    print(f'window: {steps} steps in {elapsed:.3f} s, {rate:.2f} '
          f'token-steps/s, model-FLOP utilization '
          f'{100 * flops * steps / elapsed / peaks["bf16_flops"]:.2f}% of the'
          f' bf16 peak (3x forward, causal attention at half, no replay); '
          f'{counters["moe_local_pairs"] / max(steps, 1):.0f} pairs a step, '
          f'a held expert\'s load a step: max '
          f'{counters["moe_load_max"] / max(steps, 1):.0f}, mean '
          f'{counters["moe_load_mean"] / max(steps, 1):.1f}', flush=True)

    # the reference, after the program's state is freed
    inputs = {k: built[k] for k in INPUTS}
    built.clear()
    t_ref = time.perf_counter()
    ref = reference_steps(cell, inputs, n_check)
    print(f'reference: {n_check} plain steps in '
          f'{time.perf_counter() - t_ref:.1f} s', flush=True)
    checks = compare(numbers, ref, cfg['correct'])
    dropped = counters['moe_dropped'] \
        + sum(c['moe_dropped'] for c in numbers['counters'])
    checks.true('moe_dropped_is_zero', dropped == 0)
    checks.true('losses_finite', bool(np.all(np.isfinite(losses))))
    checks.true('no_compile_in_window', not spans.compiles)

    failed = int(np.sum(~np.isfinite(losses)))
    if tracing:
        counters.update(
            steps=steps,
            expert_layer_steps=steps * lfm2_counts.expert_layers(
                cfg['model']))
        ctx = dict(spans=spans.durations, trace=summary, peaks=peaks,
                   model=cfg['model'], traffic=mix, memory_stats=mem_stats,
                   counters=counters)
        metrics = readers.read_all(cell, ctx)
        device.update(busy_s=summary['busy_s'], window_s=summary['window_s'])
    else:
        metrics = {'train_node_steps_per_s': rate, 'setup_s': setup_s}
    return dict(correct=checks.ok, attempted=steps, failed=failed,
                metrics=metrics, device=device,
                breakdown=summary['breakdown'] if tracing else None)
