"""The training cell of the looped decoder (a stack of sandwich-normed layers
run several times a step on one set of weights, an exit after every pass, a
learned gate that shares a token's loss among them): the program's decoder
and the program's objective over the exits on the program's one step
factory, exactly as `lm_train.py` drives the token decoder, compared with
its own plain reference (`ouro_reference.py`) and priced by its own counts
(`ouro_counts.py`).

Everything of `lm_train.py` that names neither `lm_reference` nor
`lm_counts` nor an expert is taken from there, as `smallthinker_train.py`
takes it: the program's lookup (a program without the recipe ends the cell
at once with one line), the caches, the seeded fill (embedding rows at unit
RMS under the untied head; the gate's kernel by fan-in, as every kernel),
the seeded batches, the step. There are no experts: nothing is balanced in
set-up, and no choice is compared. The step's `aux` is fetched with the
exits' counters instead (`COUNTERS`; the first step's `loss_ut` and
`exit_share` too, which `correct` holds to the reference entry by entry), so
`fetch`, `first_steps`, `compare` and the window loop are this file's; the
loop is `lm_train.run`'s but for them.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import lm_train, ouro_counts, ouro_reference, state, train
from .device import device_record
from .lm_train import (  # noqa: F401  (the calibration reads them here)
    SPANS, make_fill, one_step, own_cache,
)

# what the reference is given of a built program
INPUTS = ('fill', 'wkey', 'tokens')
# the step's `aux` scalars, fetched with the loss and summed over the steps
COUNTERS = ('exit_mass_last', 'exit_tokens')
# the step's `aux` vectors, a number a pass: held to the reference at step 1
EXITS = ('loss_ut', 'exit_share')


def program(cfg):
    """`lm_train.program`, and the program's objective over the exits."""
    prog = lm_train.program(cfg)
    try:
        from se3_transformer_tpu.training.lm_loss import make_looped_lm_loss
    except ImportError as e:
        raise SystemExit(f'benchmark: this program has no loss over a '
                         f'looped stack\'s exits: {type(e).__name__}: {e}')
    return dict(prog, make_loss=make_looped_lm_loss)


def fetch(loss, aux):
    """The loss and the counters in one transfer."""
    loss, scalars = jax.device_get((loss, {k: aux[k] for k in COUNTERS}))
    return float(loss), {k: float(v) for k, v in scalars.items()}


def first_steps(built, n_steps, spans):
    """`lm_train.first_steps` with this file's `fetch`: each loss, the first
    step's cross-entropy and share of the mass a pass, the first gradient as
    the optimizer got it (the shared layers' leaves are sums over the
    passes), the leaf norms of the parameters' change, the counters."""
    losses, counters, grad, grad_tree, exits = [], [], None, None, None
    for i in range(n_steps):
        t0 = time.perf_counter()
        loss, aux = one_step(built)
        loss, scalars = fetch(loss, aux)
        spans.add('first_step', time.perf_counter() - t0)
        losses.append(loss)
        counters.append(scalars)
        if i == 0:
            exits = {k: [float(x) for x in np.asarray(aux[k])]
                     for k in EXITS}
            mu = built['opt_state'][0].mu
            grad = {k: v / 0.1 for k, v in state.leaf_norms(mu).items()}
            grad_tree = [np.asarray(a) / np.float32(0.1)
                         for a in jax.tree_util.tree_leaves(mu)]
    delta = built['fill'].delta(built['params'], built['wkey'])
    numbers = dict(losses=losses, grad=grad, grad_tree=grad_tree,
                   delta=state.leaf_norms(delta), counters=counters, **exits)
    del delta
    return numbers


def build(cell, seed, prog):
    import optax
    cfg, mix = cell['config'], cell['traffic']
    module = prog['recipe'](**cfg['model'], **cfg['overrides'])
    abstract = jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((mix['batch'], mix['seq']), jnp.int32))['params']
    assert state.param_count(abstract) == ouro_counts.total_params(
        cfg['model']), 'config model sizes are not what the module has'
    opt = cfg['optimizer']
    assert opt['name'] == 'adam', opt
    optimizer = optax.adam(opt['learning_rate'])
    loss_fn = prog['make_loss'](module, **cfg['loss'])
    built = dict(step=prog['make_step'](loss_fn, optimizer),
                 fill=make_fill(abstract), abstract=abstract,
                 balance=lambda params, data: params,   # no expert layer
                 bias_names=(),
                 init_opt=jax.jit(optimizer.init),
                 key=jax.random.PRNGKey(0))      # the loss draws nothing
    reseed(built, cell, seed)
    return built


reseed = lm_train.reseed


_PLAIN_STEPS = {}


def _plain_step(cfg, operand_bits):
    """Loss and gradient by the plain reference, then plain Adam: one program
    for every step (t is traced), batch and seed."""
    key = (cfg['name'], operand_bits)
    if key in _PLAIN_STEPS:
        return _PLAIN_STEPS[key]

    def loss_of(theta, tokens):
        return ouro_reference.loss(theta, tokens, cfg['model'],
                                   beta=cfg['loss']['beta'],
                                   **cfg['reference'],
                                   operand_bits=operand_bits)

    def step(theta, mu, nu, t, tokens):
        (loss, exits), g = jax.value_and_grad(loss_of, has_aux=True)(
            theta, tokens)
        theta, mu, nu = ouro_reference.adam_update(
            theta, g, mu, nu, t, lr=cfg['optimizer']['learning_rate'])
        return theta, mu, nu, loss, g, exits

    _PLAIN_STEPS[key] = jax.jit(
        step, donate_argnums=(0, 1, 2),
        compiler_options={'exec_time_optimization_effort': -1.0,
                          'memory_fitting_effort': -1.0})
    return _PLAIN_STEPS[key]


def reference_steps(cell, inputs, n_steps, operand_bits=None):
    """The plain reference follows the same first steps from the same seeded
    weights and batches, with its own Adam. `operand_bits`: the control."""
    cfg = cell['config']
    fill, wkey, tokens = (inputs[k] for k in INPUTS)
    plain_step = _plain_step(cfg, operand_bits)
    keep = own_cache(cell, 'ouro_reference')
    try:
        losses, grad, grad_tree, first = [], None, None, None
        theta = fill(wkey)
        mu = jax.tree_util.tree_map(jnp.zeros_like, theta)
        nu = jax.tree_util.tree_map(jnp.zeros_like, theta)
        for t in range(1, n_steps + 1):
            t0 = time.perf_counter()
            theta, mu, nu, loss, g, exits = plain_step(
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
                first = {k: [float(x) for x in np.asarray(exits[k])]
                         for k in EXITS}
            del g, exits
        delta = fill.delta(theta, wkey)
        return dict(losses=losses, grad=grad, grad_tree=grad_tree,
                    delta=state.leaf_norms(delta), **first)
    finally:
        lm_train._cache_dir(keep)


def compare(prog, ref, limits, checks=None):
    """The training cells' comparison (each loss, the first gradient by its
    worst leaf and as a whole, the parameters' change), then the first
    step's exits entry by entry: each pass's cross-entropy and each pass's
    share of the mass."""
    checks = train.compare(prog, ref, limits, checks)
    for key in EXITS:
        for t, (a, b) in enumerate(zip(prog[key], ref[key]), start=1):
            checks.at_most(f'{key}_pass{t}_rel_gap', abs(a - b) / abs(b),
                           limits[f'{key}_rel_gap'])
    return checks


def run(cell, args, t_start, spans, devices, kind, peaks):
    from . import readers, trace as trace_mod

    cfg, mix = cell['config'], cell['traffic']
    prog = program(cfg)
    own_cache(cell, 'ouro_train')
    seq, batch = mix['seq'], mix['batch']
    n_check = cfg['correct']['check_steps']
    built = build(cell, args.seed, prog)
    print(f'state: {state.param_count(built["abstract"]) / 1e6:.1f} M '
          f'parameters filled from the seed on the device, run '
          f'{cfg["model"]["total_ut_steps"]} times a step; '
          f'{len(built["data"])} batches of {batch} x {seq} tokens placed',
          flush=True)
    numbers = first_steps(built, n_check, spans)
    print(f'first {n_check} steps (compile or cache hit in the first): '
          f'{[round(x, 2) for x in spans.durations["first_step"]]} s, '
          f'losses {numbers["losses"]}; the first step\'s cross-entropy a '
          f'pass {[round(x, 4) for x in numbers["loss_ut"]]}, share of the '
          f'mass a pass {[round(x, 4) for x in numbers["exit_share"]]}',
          flush=True)
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
    flops = batch * ouro_counts.train_step_flops(cfg['model'], seq)
    print(f'window: {steps} steps in {elapsed:.3f} s, {rate:.2f} '
          f'token-steps/s (a token counts once a step, whatever the '
          f'passes), model-FLOP utilization '
          f'{100 * flops * steps / elapsed / peaks["bf16_flops"]:.2f}% of the'
          f' bf16 peak (3x forward over the passes, the core at the causal '
          f'triangle, the head once a pass, no replay); the last pass takes '
          f'{counters["exit_mass_last"] / max(counters["exit_tokens"], 1):.4f}'
          f' of the mass', flush=True)

    # the reference, after the program's state is freed
    inputs = {k: built[k] for k in INPUTS}
    built.clear()
    t_ref = time.perf_counter()
    ref = reference_steps(cell, inputs, n_check)
    print(f'reference: {n_check} plain steps in '
          f'{time.perf_counter() - t_ref:.1f} s', flush=True)
    checks = compare(numbers, ref, cfg['correct'])
    checks.true('losses_finite', bool(np.all(np.isfinite(losses))))
    checks.true('no_compile_in_window', not spans.compiles)

    failed = int(np.sum(~np.isfinite(losses)))
    if tracing:
        counters.update(steps=steps)
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
