"""The training cell of the decoder whose global attention layers carry no
rotation and whose other layers slide a window with one, each followed by
ReGLU experts routed by the attention step's input (untied head): the
program's decoder on the program's one step factory, exactly as
`lm_train.py` drives the token decoder, compared with its own plain
reference (`smallthinker_reference.py`) and priced by its own counts
(`smallthinker_counts.py`).

Everything of `lm_train.py` that names neither `lm_reference` nor
`lm_counts` is taken from there, as `lfm2_train.py` takes it: the program's
lookup (a program without the recipe ends the cell at once with one line),
the caches, the seeded fill (embedding rows at unit RMS under the untied
head), the seeded batches, the step and the comparison. The step's `aux` is
fetched with one scalar `lm_train.COUNTERS` lacks (`moe_bounded`, the expert
layers whose held pairs fit the row bound), so `fetch`, `first_steps` and
the window loop are this file's; the loop is `lm_train.run`'s but for it.
The entry prints, once, each core's visible pairs and tiles a head.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import lm_train, smallthinker_counts, smallthinker_reference, state
from .device import device_record
from .lm_train import (  # noqa: F401  (the calibration reads them here)
    INPUTS, SPANS, compare, make_fill, one_step, own_cache, program, reseed,
)

# the step's `aux` scalars, fetched with the loss and summed over the steps
COUNTERS = lm_train.COUNTERS + ('moe_bounded',)


def cores(cfg, seq):
    """One line: each attention core's visible pairs and tiles a head, the
    sliding layers' from the table the program's launches take their grid
    from (a program without it: the pairs alone)."""
    m, tile = cfg['model'], cfg['overrides']['attention_block']
    window, n = m['sliding_window_size'], seq // tile
    line = (f'cores: global (`*`, leaf mha_core) '
            f'{smallthinker_counts.visible_pairs(seq):,} visible pairs a '
            f'head, the causal triangle: {n * (n + 1) // 2} tiles of {tile}; '
            f'sliding (`W`, leaf swa_core, window {window}) '
            f'{smallthinker_counts.visible_pairs(seq, window):,} pairs a '
            f'head')
    try:
        from se3_transformer_tpu.ops import sliding_window as sw
    except ImportError:
        return line
    runs = sw.kernels_run(seq, tile, m['num_attention_heads'],
                          m['num_key_value_heads'], m['head_dim'])
    return (f'{line}: {sw.visited_tiles(seq, window, tile)} tiles visited, '
            f'{sw.boundary_tiles(seq, window, tile)} of them on a boundary, '
            f'the launches {"run" if runs else "do not run"} here')


def fetch(loss, aux):
    """The loss and the counters in one transfer."""
    loss, scalars = jax.device_get((loss, {k: aux[k] for k in COUNTERS}))
    return float(loss), {k: float(v) for k, v in scalars.items()}


def first_steps(built, n_steps, spans):
    """`lm_train.first_steps` with this file's `fetch`."""
    losses, counters, grad, grad_tree, choice = [], [], None, None, None
    for i in range(n_steps):
        t0 = time.perf_counter()
        loss, aux = one_step(built)
        loss, scalars = fetch(loss, aux)
        spans.add('first_step', time.perf_counter() - t0)
        losses.append(loss)
        counters.append(scalars)
        if i == 0:
            choice = np.asarray(aux['moe_choice'])
            mu = built['opt_state'][0].mu
            grad = {k: v / 0.1 for k, v in state.leaf_norms(mu).items()}
            grad_tree = [np.asarray(a) / np.float32(0.1)
                         for a in jax.tree_util.tree_leaves(mu)]
    delta = built['fill'].delta(built['params'], built['wkey'])
    numbers = dict(losses=losses, grad=grad, grad_tree=grad_tree,
                   choice=choice, delta=state.leaf_norms(delta),
                   counters=counters)
    del delta
    return numbers


def build(cell, seed, prog):
    import optax
    cfg, mix = cell['config'], cell['traffic']
    module = prog['recipe'](**cfg['model'], **cfg['overrides'])
    abstract = jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((mix['batch'], mix['seq']), jnp.int32))['params']
    assert state.param_count(abstract) == smallthinker_counts.total_params(
        cfg['model']) + smallthinker_counts.bias_entries(cfg['model']), \
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
        return smallthinker_reference.loss(theta, tokens, cfg['model'],
                                   **cfg['reference'],
                                   operand_bits=operand_bits)

    def step(theta, mu, nu, t, tokens):
        (loss, chosen), g = jax.value_and_grad(loss_of, has_aux=True)(
            theta, tokens)
        theta, mu, nu = smallthinker_reference.adam_update(
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
    keep = own_cache(cell, 'smallthinker_reference')
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
    own_cache(cell, 'smallthinker_train')
    seq, batch = mix['seq'], mix['batch']
    n_check = cfg['correct']['check_steps']
    n_layers = smallthinker_counts.expert_layers(cfg['model'])
    print(cores(cfg, seq), flush=True)
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
    bounded = [c['moe_bounded'] for c in numbers['counters']]
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
        bounded.append(scalars['moe_bounded'])
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
    flops = batch * smallthinker_counts.train_step_flops(
        cfg['model'], seq, counters['moe_local_pairs'] / max(steps, 1) / batch)
    print(f'window: {steps} steps in {elapsed:.3f} s, {rate:.2f} '
          f'token-steps/s, model-FLOP utilization '
          f'{100 * flops * steps / elapsed / peaks["bf16_flops"]:.2f}% of the'
          f' bf16 peak (3x forward, each core at its visible pairs, no '
          f'replay); {counters["moe_local_pairs"] / max(steps, 1):.0f} pairs '
          f'a step, a held expert\'s load a step: max '
          f'{counters["moe_load_max"] / max(steps, 1):.0f}, mean '
          f'{counters["moe_load_mean"] / max(steps, 1):.1f}; moe_bounded '
          f'over the {len(bounded)} steps of set-up and window: min '
          f'{min(bounded):.0f}, max {max(bounded):.0f} of {n_layers} layers',
          flush=True)

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
        counters.update(steps=steps, expert_layer_steps=steps * n_layers)
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
