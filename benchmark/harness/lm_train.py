"""The token decoder's training cells: the program's decoder on the program's
one step factory, its state filled from the seed, driven through its first
steps in set-up, handed to the window as it is, and compared with the plain
reference (`lm_reference.py`) once the window has closed.

Nothing of the program is imported before `run()` is called: on a program
that has no token decoder the cell ends at once with a line that says so. The
entry keeps its executables in cache directories of its own
(`<checkout>/.jax_cache/lm_train`, `.../lm_reference`): beside another cell's
step they would pass the chip machine's cap and evict it.
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import lm_counts, lm_reference, lm_traffic, state, train
from .device import device_record

SPANS = ('batch_pick', 'step_call', 'loss_fetch')
# what the reference is given of a built program
INPUTS = ('fill', 'wkey', 'tokens', 'biases')
# the step's `aux` scalars, fetched with the loss and summed over the steps
COUNTERS = ('moe_local_pairs', 'moe_load_max', 'moe_load_mean',
            'moe_dropped')


def program(cfg):
    """What the benchmark takes from the program, or SystemExit."""
    try:
        from se3_transformer_tpu.parallel.sharding import (
            make_sharded_train_step,
        )
        from se3_transformer_tpu.training import recipes
        from se3_transformer_tpu.training.lm_loss import (
            balance_expert_load, make_lm_loss,
        )
        recipe = recipes.RECIPES[cfg['recipe']]
    except (ImportError, KeyError) as e:
        raise SystemExit(f'benchmark: this program has no token decoder '
                         f'(recipe {cfg["recipe"]!r}): {type(e).__name__}: '
                         f'{e}')
    return dict(recipe=recipe, make_lm_loss=make_lm_loss,
                make_step=make_sharded_train_step,
                balance=balance_expert_load)


def _cache_dir(path):
    """Point JAX's persistent cache at `path`; returns where it pointed."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = jax.config.jax_compilation_cache_dir
    if path:
        os.makedirs(path, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', path)
    cc.reset_cache()
    return keep


def own_cache(cell, name):
    return _cache_dir(os.path.join(cell['root'], '.jax_cache', name))


def make_fill(abstract):
    """`state.make_fill`, with three departures from its rule by name: the
    experts' stacked matrices [held, fan_in, fan_out] are scaled by fan_in
    (the rule takes axis 0); the router's correction bias is drawn at 0.01 z
    (a buffer; `balanced` then settles it); the embedding's rows have unit
    RMS (at the rule's 1 / sqrt(features) every block's output swamps the
    token, all tokens look alike to the routers, and one expert takes nearly
    every token: `moe_load_max` 8,137 of 8,192, my chip run, PR 27)."""
    base = state.make_fill(abstract)

    def adjust(path, leaf):
        name = state._leaf_name(path)
        if name.startswith('experts_'):
            return leaf * (leaf.shape[0] / leaf.shape[1]) ** 0.5
        if name == 'correction_bias':
            return leaf * (0.01 * leaf.shape[0] ** 0.5)
        if name == 'embedding':
            return leaf * leaf.shape[-1] ** 0.5
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
    assert state.param_count(abstract) == lm_counts.total_params(
        cfg['model']), 'config model sizes are not what the module has'
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


def reseed(built, cell, seed):
    """Weights, optimizer state and batches of `seed` for the same compiled
    step (tests/lm_calibrate.py reads many seeds in one process). The
    routers' correction biases are settled on the cell's own batches by the
    program's balancing rule, as training would have left them, and kept for
    the reference (`biases`): drawn from the seed alone, the pairs computed
    here swing by a quarter from seed to seed, and the rate with them."""
    tokens = lm_traffic.token_batches(cell['traffic'], seed,
                                      cell['config']['model']['vocab_rows'])
    built['tokens'] = tokens
    built['data'] = [dict(tokens=jax.device_put(t)) for t in tokens]
    built['wkey'] = state.prng_key(seed, 0)
    built['params'] = built['balance'](built['fill'](built['wkey']),
                                       built['data'])
    built['biases'] = {name: np.asarray(
        built['params'][name]['moe']['correction_bias'])
        for name in built['bias_names']}
    built['opt_state'] = built['init_opt'](built['params'])
    built['turn'] = 0


def one_step(built):
    """The next batch through the step; (loss, aux) still on the device."""
    batch = built['data'][built['turn'] % len(built['data'])]
    built['turn'] += 1
    built['params'], built['opt_state'], loss, aux = built['step'](
        built['params'], built['opt_state'], batch, built['key'])
    return loss, aux


def fetch(loss, aux):
    """The loss and the counters in one transfer."""
    loss, scalars = jax.device_get((loss, {k: aux[k] for k in COUNTERS}))
    return float(loss), {k: float(v) for k, v in scalars.items()}


def first_steps(built, n_steps, spans):
    """The window's own step object through its first steps, and what
    `correct` compares: each loss, the first gradient as the optimizer got it
    (Adam's mu after one step is (1 - b1) g), the first step's choices, the
    leaf norms of the parameters' change, the counters."""
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


_PLAIN_STEPS = {}


def _plain_step(cfg, operand_bits):
    """Loss and gradient by the plain reference, then plain Adam: one program
    for every step (t is traced), batch and seed."""
    key = (cfg['name'], operand_bits)
    if key in _PLAIN_STEPS:
        return _PLAIN_STEPS[key]
    ref = cfg['reference']

    def loss_of(theta, tokens):
        return lm_reference.loss(
            theta, tokens, cfg['model'], mtp_weight=cfg['loss']['mtp_weight'],
            attn_block=ref['attention_block'], chunk=ref['chunk'],
            operand_bits=operand_bits)

    def step(theta, mu, nu, t, tokens):
        (loss, chosen), g = jax.value_and_grad(loss_of, has_aux=True)(
            theta, tokens)
        theta, mu, nu = lm_reference.adam_update(
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
    fill, wkey, tokens, biases = (
        inputs[k] for k in ('fill', 'wkey', 'tokens', 'biases'))
    plain_step = _plain_step(cfg, operand_bits)
    keep = own_cache(cell, 'lm_reference')
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
        _cache_dir(keep)


def choice_mismatch(prog, ref):
    """Share of the first step's (token, slot) choices that the reference did
    not make for that token in that layer (as sets: two nearly tied scores
    may swap slots)."""
    a, b = prog['choice'].astype(np.int64), ref['choice'].astype(np.int64)
    found = (a[..., :, None] == b[..., None, :]).any(axis=-1)
    return 1.0 - float(found.mean())


def compare(prog, ref, limits, checks=None):
    """The training cells' comparison (each loss, the first gradient by its
    worst leaf and as a whole, the parameters' change), then the choices."""
    checks = train.compare(prog, ref, limits, checks)
    checks.at_most('choice_mismatch_share', choice_mismatch(prog, ref),
                   limits['choice_mismatch_share'])
    return checks


def run(cell, args, t_start, spans, devices, kind, peaks):
    from . import readers, trace as trace_mod

    cfg, mix = cell['config'], cell['traffic']
    prog = program(cfg)
    own_cache(cell, 'lm_train')
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
    flops = batch * lm_counts.train_step_flops(
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
            expert_layer_steps=steps * lm_counts.expert_layers(cfg['model']))
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
