"""The training cells: one compiled step with its state, driven from the seed
through its first steps in set-up, handed to the window as it is, and compared
with the plain reference once the window has closed."""
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import counts, reference, state, traffic as traffic_mod
from .correct import Checks, worst_leaf_gap
from .device import device_record


def build_module(cfg):
    """The program's module from a configuration file: a recipe of
    `training/recipes.py::RECIPES` with `overrides`, or, with no `recipe`,
    `SE3TransformerModule(**overrides)` (the recipes fix heads and dim_head).
    `model`, the sizes the reference and the counts read, must be what the
    module really has."""
    from se3_transformer_tpu import SE3TransformerModule
    from se3_transformer_tpu.training import recipes

    build = recipes.RECIPES[cfg['recipe']] if cfg.get('recipe') \
        else SE3TransformerModule
    module = build(**cfg['overrides'])
    for k, v in cfg['model'].items():
        got = getattr(module, k)
        assert got == v, f'config model.{k}={v} but the module has {got}'
    return module


def build(cell, seed):
    """The program under test: the denoise step as
    scripts/_flagship_common.py builds it (denoise loss, optax.adam, donated
    make_sharded_train_step), with state filled from the seed instead of a
    compiled `module.init`."""
    import optax
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step

    cfg, mix = cell['config'], cell['traffic']
    module = build_module(cfg)
    seqs, coords, masks = traffic_mod.train_structure(mix, seed,
                                                      cfg['model']['dim'])
    data = dict(seqs=jnp.asarray(seqs), coords=jnp.asarray(coords),
                masks=jnp.asarray(masks))
    abstract = jax.eval_shape(
        partial(module.init, return_type=1), jax.random.PRNGKey(0),
        data['seqs'], data['coords'], mask=data['masks'])['params']
    fill = state.make_fill(abstract)

    def loss_fn(params, batch, key):
        noise = jax.random.normal(key, batch['coords'].shape,
                                  batch['coords'].dtype)
        noised = batch['coords'] + noise
        out = module.apply({'params': params}, batch['seqs'], noised,
                           mask=batch['masks'], return_type=1)
        loss = (((noised + out) - batch['coords']) ** 2).sum(-1).mean()
        return loss, dict()

    opt = cfg['optimizer']
    assert opt['name'] == 'adam', opt
    optimizer = optax.adam(opt['learning_rate'])
    step = make_sharded_train_step(loss_fn, optimizer)
    wkey = state.prng_key(seed, 0)
    params = fill(wkey)
    opt_state = jax.jit(optimizer.init)(params)
    return dict(step=step, params=params, opt_state=opt_state, data=data,
                key=state.prng_key(seed, 1), fill=fill, wkey=wkey,
                abstract=abstract, module=module)


def reseed(prog, cell, seed):
    """New weights, optimizer state, structure and keys from another seed for
    the same compiled step (tests/calibrate.py reads many seeds in one
    process)."""
    import optax
    cfg, mix = cell['config'], cell['traffic']
    seqs, coords, masks = traffic_mod.train_structure(mix, seed,
                                                      cfg['model']['dim'])
    prog['data'] = dict(seqs=jnp.asarray(seqs), coords=jnp.asarray(coords),
                        masks=jnp.asarray(masks))
    prog['wkey'] = state.prng_key(seed, 0)
    prog['key'] = state.prng_key(seed, 1)
    prog['params'] = prog['fill'](prog['wkey'])
    prog['opt_state'] = jax.jit(optax.adam(
        cfg['optimizer']['learning_rate']).init)(prog['params'])


def first_steps(prog, n_steps, spans):
    """Drive the window's own step object through its first steps and read
    what `correct` compares: each loss, the first gradient's leaf norms as the
    optimizer got them (Adam's mu after one step is (1 - b1) g) and the leaf
    norms of the parameters' change. Returns (numbers, the keys used)."""
    losses, keys, grad, grad_tree = [], [], None, None
    for i in range(n_steps):
        prog['key'], sub = jax.random.split(prog['key'])
        keys.append(sub)
        t0 = time.perf_counter()
        prog['params'], prog['opt_state'], loss, _ = prog['step'](
            prog['params'], prog['opt_state'], prog['data'], sub)
        losses.append(float(np.asarray(loss)))
        spans.add('first_step', time.perf_counter() - t0)
        if i == 0:
            mu = prog['opt_state'][0].mu
            grad = {k: v / 0.1 for k, v in state.leaf_norms(mu).items()}
            # the gradient itself goes to the host until the reference has
            # its own: the device keeps only what the program holds
            grad_tree = [np.asarray(a) / np.float32(0.1)
                         for a in jax.tree_util.tree_leaves(mu)]
    fill, wkey = prog['fill'], prog['wkey']
    delta = fill.delta(prog['params'], wkey)
    numbers = dict(losses=losses, grad=grad, grad_tree=grad_tree,
                   delta=state.leaf_norms(delta))
    del delta
    return numbers, keys


_PLAIN_STEPS = {}    # (configuration, dtype) -> the reference's one program


def _plain_step(cfg, dtype):
    """Loss and gradient at theta by the plain reference, then plain Adam:
    one program for every step (t is traced) and every seed."""
    name = dtype if isinstance(dtype, str) else jnp.dtype(dtype).name
    if (cfg['name'], name) in _PLAIN_STEPS:
        return _PLAIN_STEPS[cfg['name'], name], name
    model = {k: cfg['model'][k] for k in
             ('depth', 'num_degrees', 'heads', 'dim_head', 'output_degrees')}
    if cfg['model'].get('one_headed_key_values'):
        model['kv_heads'] = 1
    loss_of = partial(reference.denoise_loss, **model, dtype=dtype,
                      remat=True, block=cfg['reference']['block'])

    def step(theta, m, v, t, feats, noised, coors, geom):
        with jax.default_matmul_precision('highest'):
            loss, g = jax.value_and_grad(loss_of)(theta, feats, noised,
                                                  coors, geom)
        theta, m, v = reference.adam_update(
            theta, g, m, v, t, lr=cfg['optimizer']['learning_rate'])
        return theta, m, v, loss, g

    # every run pays this compile: ask the compiler for the least effort (a
    # third of the time here, PERF.md); the mathematics is the same
    _PLAIN_STEPS[cfg['name'], name] = jax.jit(
        step, donate_argnums=(0, 1, 2),
        compiler_options={'exec_time_optimization_effort': -1.0,
                          'memory_fitting_effort': -1.0})
    return _PLAIN_STEPS[cfg['name'], name], name


def reference_steps(cell, prog_inputs, keys, dtype=jnp.float32):
    """The plain reference follows the same first steps from the same seeded
    weights and noise, with its own Adam."""
    cfg = cell['config']
    data, fill, wkey = (prog_inputs[k] for k in ('data', 'fill', 'wkey'))
    feats, coors, mask = (data['seqs'][0], data['coords'][0],
                          data['masks'][0])
    plain_step, name = _plain_step(cfg, dtype)
    # the reference's executable goes to a cache directory of its own, at a
    # fixed path inside the checkout: beside the step's it can exceed the
    # chip machine's 192 MiB cap, and the two then evict each other in turn
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = jax.config.jax_compilation_cache_dir
    ref_dir = os.path.join(cell['root'], '.jax_cache', 'reference')
    os.makedirs(ref_dir, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', ref_dir)
    cc.reset_cache()
    try:
        losses, grad, grad_tree = [], None, None
        theta = fill(wkey)
        m = jax.tree_util.tree_map(jnp.zeros_like, theta)
        v = jax.tree_util.tree_map(jnp.zeros_like, theta)
        for t, key in enumerate(keys, start=1):
            noised = coors + jax.random.normal(key, data['coords'].shape,
                                               data['coords'].dtype)[0]
            t0 = time.perf_counter()
            geom = reference.geometry(np.asarray(noised), np.asarray(mask),
                                      cfg['model']['num_neighbors'],
                                      cfg['model']['num_degrees'])
            theta, m, v, loss, g = plain_step(theta, m, v, jnp.float32(t),
                                              feats, noised, coors, geom)
            losses.append(float(np.asarray(loss)))
            print(f'reference step {t} ({name}): '
                  f'{time.perf_counter() - t0:.1f} s', flush=True)
            if t == 1:
                grad = state.leaf_norms(g)
                grad_tree = [np.asarray(a)
                             for a in jax.tree_util.tree_leaves(g)]
            del g
        delta = fill.delta(theta, wkey)
        return dict(losses=losses, grad=grad, grad_tree=grad_tree,
                    delta=state.leaf_norms(delta))
    finally:
        jax.config.update('jax_compilation_cache_dir', keep)
        cc.reset_cache()


def grad_rel_diff(prog, ref):
    """||g_program - g_reference|| / ||g_reference|| over the whole first
    gradient: first-order in rounding noise, where a gap between norms is
    second-order and cannot tell bfloat16 from fp8 (PERF.md)."""
    num = den = 0.0
    for a, b in zip(prog['grad_tree'], ref['grad_tree']):
        d = (a - b).ravel()
        num += float(np.dot(d, d))
        den += float(np.dot(b.ravel(), b.ravel()))
    return (num / den) ** 0.5


def compare(prog, ref, limits, checks=None):
    """Each number beside its limit (limits from the configuration file)."""
    checks = checks or Checks()
    for i, (a, b) in enumerate(zip(prog['losses'], ref['losses']), start=1):
        checks.at_most(f'loss_step{i}_rel_gap', abs(a - b) / abs(b),
                       limits['loss_rel_gap'])
    gap, leaf = worst_leaf_gap(prog['grad'], ref['grad'])
    checks.at_most(f'first_grad_worst_leaf_gap[{leaf}]', gap,
                   limits['grad_leaf_gap'])
    checks.at_most('first_grad_rel_l2_diff', grad_rel_diff(prog, ref),
                   limits['grad_rel_diff'])
    gap, leaf = worst_leaf_gap(prog['delta'], ref['delta'])
    checks.at_most(f'param_change_worst_leaf_gap[{leaf}]', gap,
                   limits['delta_leaf_gap'])
    return checks


def run(cell, args, t_start, spans, devices, kind, peaks):
    from . import readers, trace as trace_mod

    cfg, mix = cell['config'], cell['traffic']
    n, batch = mix['nodes'], mix['batch']
    n_check = cfg['correct']['check_steps']
    prog = build(cell, args.seed)
    print(f'state: {state.param_count(prog["abstract"]) / 1e6:.1f} M '
          f'parameters filled from the seed on the device', flush=True)
    numbers, keys = first_steps(prog, n_check, spans)
    print(f'first {n_check} steps (compile or cache hit in the first): '
          f'{[round(x, 2) for x in spans.durations["first_step"]]} s, '
          f'losses {numbers["losses"]}', flush=True)
    readers.print_cache_size()

    tracing = bool(args.trace)
    budget = mix['trace_steps'] if tracing else None
    losses = []
    if tracing:
        trace_dir = trace_mod.start(cell, args.seed)
    spans.armed = True
    t0 = t_last = time.perf_counter()
    setup_s = t0 - t_start
    while (len(losses) < budget) if tracing else \
            (time.perf_counter() < t0 + args.seconds):
        with spans.span('key_split'):
            prog['key'], sub = jax.random.split(prog['key'])
        with spans.span('step_call'):
            prog['params'], prog['opt_state'], loss, _ = prog['step'](
                prog['params'], prog['opt_state'], prog['data'], sub)
        with spans.span('loss_fetch'):
            losses.append(float(np.asarray(loss)))
        t_last = time.perf_counter()
    spans.armed = False
    elapsed = t_last - t0
    summary = trace_mod.stop(trace_dir, elapsed, set(spans.durations)) \
        if tracing else None
    spans.check_no_compiles()
    device = device_record(devices, kind)
    mem_stats = devices[0].memory_stats() or {}

    steps = len(losses)
    rate = batch * n * steps / elapsed
    mfu = counts.train_step_flops(cfg['model'], n) * batch * steps \
        / elapsed / peaks['bf16_flops']
    print(f'window: {steps} steps in {elapsed:.3f} s, {rate:.2f} node-steps/s'
          f', model-FLOP utilization {100 * mfu:.2f}% of the bf16 peak '
          f'(3x forward, no replay)', flush=True)

    # the reference, after the program's state is freed
    inputs = {k: prog[k] for k in ('data', 'fill', 'wkey')}
    prog.clear()
    t_ref = time.perf_counter()
    ref = reference_steps(cell, inputs, keys)
    print(f'reference: {n_check} plain steps in '
          f'{time.perf_counter() - t_ref:.1f} s', flush=True)
    checks = compare(numbers, ref, cfg['correct'])
    checks.true('losses_finite', bool(np.all(np.isfinite(losses))))
    checks.true('no_compile_in_window', not spans.compiles)

    failed = int(np.sum(~np.isfinite(losses)))
    if tracing:
        ctx = dict(spans=spans.durations, trace=summary, peaks=peaks,
                   model=cfg['model'], memory_stats=mem_stats,
                   counters={'steps': steps},
                   shapes_run=[dict(nodes=n, times=batch * steps,
                                    backward=True)])
        metrics = readers.read_all(cell, ctx)
        device.update(busy_s=summary['busy_s'], window_s=summary['window_s'])
    else:
        metrics = {'train_node_steps_per_s': rate, 'setup_s': setup_s}
    return dict(correct=checks.ok, attempted=steps, failed=failed,
                metrics=metrics, device=device,
                breakdown=summary['breakdown'] if tracing else None)
