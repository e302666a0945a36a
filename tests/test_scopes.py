"""The program labels its own time: every `named_scope` the package writes is
a leaf of the closed list, a tiny train step lowered on the CPU (kernels in
interpret mode) has every labelled instruction under a leaf with forward,
backward and replay told apart, and the pairwise launches carry three
distinct role names with the old prefixes."""
import ast
import os
import re
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.observability import profiling
from se3_transformer_tpu.observability.timing import (
    MODEL_SCOPES, PAIR_SCOPE, PASS_SCOPE,
)

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'se3_transformer_tpu')


def _scope_literals():
    """(file, line, text) of the first argument of every `named_scope(...)`
    call in the package; an f-string gives its literal parts with `{}`
    holes."""
    out = []
    for dirpath, _, files in os.walk(PACKAGE):
        for f in files:
            if not f.endswith('.py'):
                continue
            path = os.path.join(dirpath, f)
            for node in ast.walk(ast.parse(open(path).read())):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                fn = node.func
                name = fn.attr if isinstance(fn, ast.Attribute) else \
                    getattr(fn, 'id', None)
                if name != 'named_scope':
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant):
                    text = arg.value
                elif isinstance(arg, ast.JoinedStr):
                    text = ''.join(v.value if isinstance(v, ast.Constant)
                                   else '{}' for v in arg.values)
                else:
                    text = None      # timing.named_scope's own forwarder
                out.append((os.path.relpath(path, PACKAGE), node.lineno,
                            text))
    return out


def test_every_named_scope_in_the_package_is_a_leaf():
    sites = _scope_literals()
    assert len(sites) > 30
    forwarders = [s for s in sites if s[2] is None]
    assert [s[0] for s in forwarders] == ['observability/timing.py']
    for path, line, text in sites:
        if text is None:
            continue
        if '{}' in text:
            # `pair_<d_in>_<d_out>` / `pair_all_<d_out>`, the one pattern of
            # a leaf; `ut_<t>`, a looped stack's pass, a component and no
            # leaf (models/hybrid_decoder.py alone writes it)
            filled = text.replace('{}', '1')
            assert PAIR_SCOPE.match(filled) or (
                PASS_SCOPE.match(filled)
                and path == 'models/hybrid_decoder.py'), (path, line)
            continue
        assert text in MODEL_SCOPES, \
            f'{path}:{line}: named_scope({text!r}) is not in MODEL_SCOPES'
    assert len(set(MODEL_SCOPES)) == len(MODEL_SCOPES)


# --------------------------------------------------------------------- #
# a tiny train step, lowered and compiled on the CPU
# --------------------------------------------------------------------- #
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([\w\-]+)\(')
_PLUMBING = {'parameter', 'constant', 'tuple', 'get-tuple-element',
             'bitcast'}


@pytest.fixture(scope='module')
def step_hlo():
    """HLO text of make_sharded_train_step around a degree-2, depth-1
    reversible model with the flagship's execution knobs, the pairwise
    kernels in interpret mode."""
    import optax

    from se3_transformer_tpu import SE3TransformerModule
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    n = 8
    module = SE3TransformerModule(
        dim=4, depth=1, num_degrees=2, heads=2, dim_head=2,
        one_headed_key_values=True, attend_self=True, num_neighbors=3,
        valid_radius=1e5, input_degrees=1, output_degrees=2,
        reduce_dim_out=True, shared_radial_hidden=True, fuse_basis=True,
        reversible=True, remat_policy='save_conv_outputs',
        pallas_interpret=True)
    rng = np.random.default_rng(0)
    data = dict(seqs=jnp.asarray(rng.normal(size=(1, n, 4)), jnp.float32),
                coords=jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), 1),
                                   jnp.float32),
                masks=jnp.ones((1, n), bool))
    params = jax.eval_shape(
        partial(module.init, return_type=1), jax.random.PRNGKey(0),
        data['seqs'], data['coords'], mask=data['masks'])['params']

    def loss_fn(params, batch, key):
        noised = batch['coords'] + jax.random.normal(
            key, batch['coords'].shape)
        out = module.apply({'params': params}, batch['seqs'], noised,
                           mask=batch['masks'], return_type=1)
        return (((noised + out) - batch['coords']) ** 2).sum(-1).mean(), {}

    optimizer = optax.adam(1e-4)
    step = make_sharded_train_step(loss_fn, optimizer)
    assert step.__name__ == 'train_step'
    lowered = step.lower(params, jax.eval_shape(optimizer.init, params),
                         data, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return lowered.compile().as_text()


@pytest.fixture(scope='module')
def labelled(step_hlo):
    """[(instruction, opcode, op_name)] for every instruction that carries
    an op_name and is not plumbing."""
    rows = []
    for line in step_hlo.splitlines():
        m = _INSTR.match(line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op and m.group(2) not in _PLUMBING:
            rows.append((m.group(1), m.group(2), op.group(1)))
    assert len(rows) > 1000
    return rows


def test_every_instruction_of_the_step_is_under_a_leaf(labelled):
    """Every instruction that does arithmetic or moves data, and that the
    compiler left its op_name, resolves to a leaf of MODEL_SCOPES."""
    lost = [(n, p) for n, _, p in labelled
            if p.startswith('jit(train_step)')
            and profiling.scope_leaf(p) is None]
    assert not lost, lost[:5]
    # the rest are bodies of interpret-mode kernel loops, which the
    # interpreter traces under the launch's name alone (no such
    # instructions exist on the chip, where a launch is one custom call)
    rest = {p.split('/')[0] for _, _, p in labelled
            if not p.startswith('jit(train_step)') and '/' in p}
    assert all(profiling.kernel_role(r) for r in rest), rest


def test_step_leaves_and_phases_are_all_present(labelled):
    cells = {(profiling.scope_leaf(p), profiling.scope_phase(p))
             for _, _, p in labelled}
    leaves = {leaf for leaf, _ in cells}
    for leaf in ('loss', 'optimizer', 'pairwise_layout', 'pair', 'radial',
                 'gather', 'norm', 'ff', 'basis',
                 'attn_core', 'attn_qkv', 'neighbors', 'readout'):
        assert leaf in leaves, leaf
    # every convolution of this step is a basis-fused pair, forward and
    # backward, and no gradient reaches the basis: nothing is left for the
    # leaf that holds the basis contraction outside the kernels
    assert 'basis_contract' not in leaves
    # the optimizer runs once, after the gradient; the layout traffic of
    # the kernels' wrappers exists forward and backward; the reversible
    # trunk replays its cheap glue (never a kernel's wrapper: the policy
    # saves the convolutions' outputs)
    assert ('optimizer', 'forward') in cells
    assert {('pairwise_layout', 'forward'),
            ('pairwise_layout', 'backward')} <= cells
    assert ('pairwise_layout', 'replay') not in cells
    for leaf in ('radial', 'norm', 'attn_core'):
        assert {(leaf, 'forward'), (leaf, 'backward'),
                (leaf, 'replay')} <= cells, leaf
    assert {phase for _, phase in cells} == set(profiling.PHASES)


def test_pairwise_launches_lower_to_three_role_names(labelled):
    """In interpret mode a launch is a loop, whose instructions carry the
    launch's name as a path component: the component XLA takes as the
    custom call's instruction name on the chip (checked there by the
    deviceless compile, tests/test_tpu_compile.py)."""
    roles = set()
    for _, _, p in labelled:
        comps = p.split(';')[0].split('/')
        roles.update(c for c in comps if c.startswith('fused_'))
    assert roles == {'fused_pairwise_conv_bxf', 'fused_pairwise_conv_bwd_a',
                     'fused_pairwise_conv_bwd_b'}
    # the old prefixes: what the benchmark's metrics select on
    assert sum(r.startswith('fused_pairwise_conv_bwd') for r in roles) == 2
    assert sum(r.startswith('fused_pairwise_conv_bxf') for r in roles) == 1
    # each launch sits under its degree pair, and its phase is readable
    launches = {(profiling.scope_pair(p), profiling.scope_phase(p))
                for _, _, p in labelled if 'fused_pairwise_conv_bwd_a' in p
                and p.startswith('jit(train_step)')}
    assert {pair for pair, _ in launches} == {'0,0', '0,1', '1,0', '1,1'}
    assert {phase for _, phase in launches} == {'backward'}


@pytest.mark.parametrize('telemetry', [False, True])
def test_the_accumulating_step_is_named_and_scoped_like_the_plain_one(
        telemetry):
    """`train_step` in the compile log, `loss` around the scanned
    micro-batches and `optimizer` around the update."""
    import optax

    from se3_transformer_tpu.parallel.sharding import (
        make_accumulating_train_step,
    )

    def loss_fn(params, batch, key):
        return ((batch @ params['w']) ** 2).mean(), {}

    optimizer = optax.adam(1e-3)
    step = make_accumulating_train_step(loss_fn, optimizer, accum_steps=2,
                                        telemetry=telemetry)
    assert step.__name__ == 'train_step'
    params = dict(w=jnp.ones((4, 4)))
    args = [params, optimizer.init(params), jnp.ones((2, 3, 4)),
            jax.random.PRNGKey(0)]
    if telemetry:
        from se3_transformer_tpu.observability import MetricAccumulator
        args.append(MetricAccumulator.zero(('loss', 'grad_norm')))
    text = step.lower(*args).as_text(debug_info=True)
    paths = set(re.findall(r'"(jit\(train_step\)/[^"]*)"', text))
    leaves = {profiling.scope_leaf(p) for p in paths}
    assert {'loss', 'optimizer'} <= leaves, sorted(paths)[:5]
    assert any('/loss/' in p and 'while' in p for p in paths)


# --------------------------------------------------------------------- #
# the token decoder's leaves, and that the SE(3) step has none of them
# --------------------------------------------------------------------- #
DECODER_LEAVES = ('embed', 'latent_qkv', 'latent_core', 'latent_out',
                  'moe_router', 'moe_dispatch', 'moe_experts', 'moe_combine',
                  'shared_expert', 'dense_ff', 'mtp_merge', 'lm_head')


def test_the_token_decoders_leaves_are_on_the_closed_list():
    assert set(DECODER_LEAVES) <= set(MODEL_SCOPES)


def test_no_decoder_leaf_occurs_in_a_path_of_the_se3_step(labelled):
    """Flax writes module names into the same paths: a new leaf that is also
    a component of an SE(3) path would take that operation's time."""
    comps = {c for _, _, p in labelled for c in p.split(';')[0].split('/')}
    assert not comps & set(DECODER_LEAVES)
    leaves = {profiling.scope_leaf(p) for _, _, p in labelled}
    assert not leaves & set(DECODER_LEAVES)


def test_a_tiny_decoder_step_has_every_decoder_leaf_and_the_three_phases():
    import optax

    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES['token_decoder'](attention_block=8)
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    text = step.lower(params, jax.eval_shape(optimizer.init, params),
                      dict(tokens=tokens),
                      jax.ShapeDtypeStruct((2,), jnp.uint32)
                      ).as_text(debug_info=True)
    # a path's last component is the primitive's name, and `gather`, the
    # primitive behind every row lookup here, is also a leaf of the SE(3)
    # model: the decoder's readers (benchmark/layer_metrics/_lm_leaves.py)
    # read the scopes alone, and so does this test
    paths = {p.rsplit('/', 1)[0] for p in re.findall(
        r'"(jit\(train_step\)/[^"]*)"', text)}
    cells = {(profiling.scope_leaf(p), profiling.scope_phase(p))
             for p in paths}
    leaves = {leaf for leaf, _ in cells}
    assert set(DECODER_LEAVES) | {'norm', 'loss', 'optimizer'} <= leaves
    assert None not in leaves
    # every block is recomputed in the backward pass: its leaves have the
    # three phases; the heads are recomputed chunk by chunk
    for leaf in ('latent_qkv', 'latent_core', 'latent_out', 'moe_router',
                 'moe_dispatch', 'moe_experts', 'moe_combine',
                 'shared_expert', 'dense_ff', 'lm_head'):
        assert {(leaf, ph) for ph in profiling.PHASES} <= cells, leaf
    # no SE(3) leaf but the shared `norm`, `loss` and `optimizer`
    assert leaves <= set(DECODER_LEAVES) | {'norm', 'loss', 'optimizer'}


@lru_cache(maxsize=None)
def _tiny_step_text(recipe):
    """A one-sequence step of `recipe` at its smallest, lowered."""
    import optax

    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES[recipe](attention_block=8)
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    return step.lower(params, jax.eval_shape(optimizer.init, params),
                      dict(tokens=tokens),
                      jax.ShapeDtypeStruct((2,), jnp.uint32)
                      ).as_text(debug_info=True)


@pytest.mark.parametrize('recipe,leaf', [
    ('token_decoder', 'dense_ff'), ('token_decoder', 'shared_expert'),
    ('lfm2_decoder', 'dense_ff')])
def test_the_gated_rules_backward_files_under_its_callers_leaf(recipe, leaf):
    """`ops/expert_layer.py::gated_ff` opens no scope of its own: its
    backward is traced under the name stack of the call, so the one pass
    (sigmoid, barrier) and the six products carry the caller's leaf with
    phase backward, and the replay the forward rule's two products."""
    text = _tiny_step_text(recipe)
    met = {}
    for scopes, primitive in (p.rsplit('/', 1) for p in re.findall(
            r'"(jit\(train_step\)/[^"]*)"', text)):
        if primitive == 'optimization_barrier':
            assert profiling.scope_leaf(scopes) in (
                'dense_ff', 'shared_expert'), scopes
            assert profiling.scope_phase(scopes) == 'backward', scopes
        if profiling.scope_leaf(scopes) == leaf:
            met.setdefault(profiling.scope_phase(scopes), set()).add(
                primitive)
    assert {'optimization_barrier', 'logistic', 'dot_general',
            'convert_element_type'} <= met['backward'], met
    assert 'dot_general' in met['replay'] and \
        'optimization_barrier' not in met['replay'] | met['forward'], met


# --------------------------------------------------------------------- #
# the hybrid decoder's leaves: each met in a lowered step
# --------------------------------------------------------------------- #
HYBRID_LEAVES = ('ssm_in', 'ssm_conv', 'ssm_scan', 'ssm_gate', 'ssm_out',
                 'mha_qkv', 'mha_core', 'mha_out')


def test_the_hybrid_decoders_leaves_are_on_the_closed_list_and_new(labelled):
    assert set(HYBRID_LEAVES) <= set(MODEL_SCOPES)
    # none is a JAX or XLA primitive's name (`gather` taught that), none a
    # component of an SE(3) path
    import jax.extend.core as jex
    import jax._src.lax.lax as lax_impl
    primitives = {getattr(lax_impl, n).name for n in dir(lax_impl)
                  if isinstance(getattr(lax_impl, n), jex.Primitive)}
    assert len(primitives) > 50 and 'exp' in primitives
    assert not set(HYBRID_LEAVES) & primitives
    comps = {c for _, _, p in labelled for c in p.split(';')[0].split('/')}
    assert not comps & set(HYBRID_LEAVES)


def test_a_tiny_hybrid_step_has_every_leaf_and_the_three_phases():
    import optax

    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES['hybrid_decoder'](attention_block=8)
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    text = step.lower(params, jax.eval_shape(optimizer.init, params),
                      dict(tokens=tokens),
                      jax.ShapeDtypeStruct((2,), jnp.uint32)
                      ).as_text(debug_info=True)
    # the scopes alone, as the decoders' readers take them (see above)
    paths = {p.rsplit('/', 1)[0] for p in re.findall(
        r'"(jit\(train_step\)/[^"]*)"', text)}
    cells = {(profiling.scope_leaf(p), profiling.scope_phase(p))
             for p in paths}
    leaves = {leaf for leaf, _ in cells}
    shared = {'embed', 'moe_router', 'moe_dispatch', 'moe_experts',
              'moe_combine', 'shared_expert', 'lm_head', 'norm', 'loss',
              'optimizer'}
    assert leaves == set(HYBRID_LEAVES) | shared
    # every layer is recomputed in the backward pass: its leaves have the
    # three phases, but a leaf that is a layer's last product alone, whose
    # output no cotangent needs (`mha_out` also lays the heads out again)
    last = ('ssm_out',)
    for leaf in HYBRID_LEAVES + ('moe_experts', 'shared_expert'):
        phases = {ph for ph in profiling.PHASES
                  if leaf not in last or ph != 'replay'}
        assert {ph for lf, ph in cells if lf == leaf} == phases, leaf
    # off the TPU the scan is XLA's einsums, the carry of the chunk states
    # one masked product that keeps its scope: nothing of the step sits in
    # a loop but the optimizer's and the sort's own (on a TPU the carry is
    # a kernel's scratch, and a launch is no `while` either)
    assert not any('ssm_scan' in p and 'while' in p for p in paths)


def test_the_scans_launches_are_filed_under_ssm_scan_in_all_three_phases(
        scan_on_kernels):
    """The step as a TPU takes it (`chunked_scan`'s rule steered by the
    fixture, the launches interpreted). Each launch is a jit of its own
    (`_fwd`, `_bwd`: a step lowers each once), called under `ssm_scan` in
    the forward pass and in a block's replay (`_fwd`) and in the backward
    pass (`_bwd`, which the `custom_vjp` traces under the call's name
    stack); inside it the launch's role is the path's next component, which
    the chip's compiler joins to the call's and takes as the custom call's
    name (`tests/test_tpu_compile.py` pins that on the compiled step)."""
    text = _tiny_step_text.__wrapped__('hybrid_decoder')
    roles = {'jit(_fwd)': 'ssm_scan_fwd', 'jit(_bwd)': 'ssm_scan_bwd'}
    launches = set()
    for path in re.findall(r'"(jit\(train_step\)/[^"]*)"', text):
        scopes, _, last = path.rpartition('/')
        if last in roles:
            # the launch is issued inside the scope, not beside it
            assert profiling.scope_leaf(scopes) == 'ssm_scan', path
            assert f'"{roles[last]}/pallas_call"' in text
            launches.add((roles[last], profiling.scope_phase(scopes)))
    assert launches == {('ssm_scan_fwd', 'forward'),
                        ('ssm_scan_fwd', 'replay'),
                        ('ssm_scan_bwd', 'backward')}
    # the einsum form is off this path: nothing [Q, Q] is left to XLA
    assert 'zcgrij' not in text and 'zcign' not in text


def test_both_sizes_of_the_expert_layer_keep_their_leaves(monkeypatch):
    """Where the held-row bound is under N * k the layer is a `cond` in the
    forward pass and one in the backward pass (ops/expert_layer.py): every
    operation inside either branch of either is under `moe_dispatch`,
    `moe_experts` or `moe_combine`, the backward ones as `backward`."""
    from se3_transformer_tpu.ops import expert_layer
    monkeypatch.setattr(expert_layer, 'HELD_ROW_BOUND', (1, 1))
    layer = expert_layer.ExpertLayer(width=8, n_experts=8, top_k=2,
                                     experts_held=4, shared_width=8)
    assert expert_layer.held_row_bound(24 * 2, 4, 8) == 24
    x = jax.ShapeDtypeStruct((24, 16), jnp.float32)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)['params']

    def loss(params, x):
        with jax.named_scope('loss'):
            return jnp.square(jax.checkpoint(lambda p, x: layer.apply(
                {'params': p}, x)[0])(params, x)).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).as_text(
        debug_info=True)
    paths = {p.rsplit('/', 1)[0] for p in re.findall(r'"(jit\(loss\)/[^"]*)"',
                                                     text)}
    inside = {p for p in paths if re.search(r'/branch_[01]_fun(/|$)', p)}
    stages = {'moe_dispatch', 'moe_experts', 'moe_combine'}
    cells = set()
    for p in inside:
        head, branch, tail = re.split(r'/(branch_[01]_fun)', p, maxsplit=1)
        if not tail:
            continue             # the branch's own plumbing: no operation
        leaf = profiling.scope_leaf(p)
        assert leaf in stages and leaf in tail.split('/'), p
        backward = 'transpose(jvp(loss))' in head.split('/')
        assert (profiling.scope_phase(p) == 'backward') == backward, p
        cells.add((branch, backward, leaf))
    assert cells == {(b, back, leaf) for b in ('branch_0_fun', 'branch_1_fun')
                     for back in (False, True) for leaf in stages}


# --------------------------------------------------------------------- #
# the gated short convolution's leaves (models/hybrid_decoder.py's `C`)
# --------------------------------------------------------------------- #
SCONV_LEAVES = ('sconv_in', 'sconv_core', 'sconv_out')


def test_the_short_convolutions_leaves_are_on_the_closed_list_and_new(
        labelled):
    assert set(SCONV_LEAVES) <= set(MODEL_SCOPES)
    assert not set(SCONV_LEAVES) & set(HYBRID_LEAVES + DECODER_LEAVES)
    comps = {c for _, _, p in labelled for c in p.split(';')[0].split('/')}
    assert not comps & set(SCONV_LEAVES)


def test_every_operation_of_the_short_convolution_is_under_its_leaves():
    """The operator alone, recomputed as its block is: forward, replay and
    backward, every operation under `sconv_in`, `sconv_core` or `sconv_out`,
    the products under the first and the last, the gates and taps under the
    middle one."""
    from se3_transformer_tpu.ops.short_conv import ShortConvMixer
    mixer = ShortConvMixer(dim=16, taps=3)
    u = jax.ShapeDtypeStruct((2, 12, 16), jnp.float32)
    params = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), u)['params']

    def loss(params, u):
        with jax.named_scope('loss'):
            return jnp.square(jax.checkpoint(lambda p, u: mixer.apply(
                {'params': p}, u))(params, u)).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, u).as_text(
        debug_info=True)
    found = set(re.findall(r'"(jit\(loss\)/[^"]*)"', text))
    inside = {p for p in found if 'ShortConvMixer' in p.split('/')}
    # what is left is the square, its sum and the recomputed block's call
    assert {p.rsplit('/', 1)[1] for p in found - inside} <= {
        'mul', 'broadcast_in_dim', 'remat2', 'reduce_sum', 'checkpoint'}
    cells = set()
    for p in inside:
        scopes, primitive = p.rsplit('/', 1)
        leaf = profiling.scope_leaf(scopes)
        assert leaf in SCONV_LEAVES, p
        cells.add((leaf, profiling.scope_phase(scopes)))
        if primitive == 'dot_general':
            assert leaf in ('sconv_in', 'sconv_out'), p
        if primitive in ('mul', 'pad', 'slice'):
            assert leaf == 'sconv_core', p
    assert {leaf for leaf, _ in cells} == set(SCONV_LEAVES)
    for leaf in ('sconv_in', 'sconv_core'):
        assert {ph for lf, ph in cells if lf == leaf} == set(
            profiling.PHASES), leaf
    assert ('sconv_out', 'backward') in cells


def test_a_tiny_step_of_two_mixer_layers_has_every_leaf_and_the_phases():
    import optax

    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES['lfm2_decoder'](attention_block=8)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    step = make_sharded_train_step(make_lm_loss(module, chunk=8), optimizer)
    text = step.lower(params, jax.eval_shape(optimizer.init, params),
                      dict(tokens=tokens),
                      jax.ShapeDtypeStruct((2,), jnp.uint32)
                      ).as_text(debug_info=True)
    paths = {p.rsplit('/', 1)[0] for p in re.findall(
        r'"(jit\(train_step\)/[^"]*)"', text)}
    cells = {(profiling.scope_leaf(p), profiling.scope_phase(p))
             for p in paths}
    leaves = {leaf for leaf, _ in cells}
    # q/k norms and rotation under `mha_qkv`, the dense feed-forward under
    # `dense_ff`; no shared expert, no head of its own
    assert leaves == set(SCONV_LEAVES) | {
        'mha_qkv', 'mha_core', 'mha_out', 'dense_ff', 'embed', 'moe_router',
        'moe_dispatch', 'moe_experts', 'moe_combine', 'lm_head', 'norm',
        'loss', 'optimizer'}
    for leaf in ('sconv_in', 'sconv_core', 'mha_qkv', 'mha_core', 'dense_ff',
                 'moe_experts'):
        assert {ph for lf, ph in cells if lf == leaf} == set(
            profiling.PHASES), leaf
    rotation = [p for p in paths if p.endswith('/attn/mha_qkv') or
                '/attn/mha_qkv/' in p]
    assert any('q_norm' in p for p in rotation)
    assert any('k_norm' in p for p in rotation)


# --------------------------------------------------------------------- #
# the block-diffusion pass's leaves (two streams under a block mask)
# --------------------------------------------------------------------- #
BD_LEAVES = ('bd_core', 'bd_streams')


def test_the_block_diffusion_leaves_are_on_the_closed_list_and_new(labelled):
    assert set(BD_LEAVES) <= set(MODEL_SCOPES)
    assert not set(BD_LEAVES) & set(
        HYBRID_LEAVES + DECODER_LEAVES + SCONV_LEAVES)
    comps = {c for _, _, p in labelled for c in p.split(';')[0].split('/')}
    assert not comps & set(BD_LEAVES)


def _tiny_block_diffusion_step_paths(batch_of):
    import optax

    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training import lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES['sdar_decoder'](attention_block=8)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    loss_fn, batch = batch_of(lm_loss, module, tokens)
    text = make_sharded_train_step(loss_fn, optimizer).lower(
        params, jax.eval_shape(optimizer.init, params), batch,
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    return {p.rsplit('/', 1)[0] for p in re.findall(
        r'"(jit\(train_step\)/[^"]*)"', text)}


def test_a_tiny_block_diffusion_step_has_every_leaf_and_the_three_phases():
    """The core under `bd_core` in forward, replay and backward (off the TPU
    the blocked form is recomputed; on it the replay holds no launch, which
    `tests/test_tpu_compile.py` reads in the compiled step); the streams'
    building under `bd_streams`; no operation under `mha_core`."""
    def batch_of(lm_loss, module, tokens):
        weight = jax.ShapeDtypeStruct(tokens.shape, jnp.float32)
        return (lm_loss.make_block_diffusion_loss(module, 4, chunk=8),
                dict(tokens=tokens, noised=tokens, weight=weight))

    paths = _tiny_block_diffusion_step_paths(batch_of)
    cells = {(profiling.scope_leaf(p), profiling.scope_phase(p))
             for p in paths}
    assert {leaf for leaf, _ in cells} == set(BD_LEAVES) | {
        'mha_qkv', 'mha_out', 'embed', 'moe_router', 'moe_dispatch',
        'moe_experts', 'moe_combine', 'lm_head', 'norm', 'loss', 'optimizer'}
    for leaf in ('bd_core', 'mha_qkv', 'moe_experts', 'moe_router'):
        assert {ph for lf, ph in cells if lf == leaf} == set(
            profiling.PHASES), leaf
    assert ('bd_streams', 'forward') in cells
    core = [p for p in paths if '/bd_core' in p]
    assert core and all('/attn/bd_core' in p for p in core)


def _eqn_paths(jaxpr, outer='', found=None):
    """(equation, name stack) of every equation in a jaxpr, nested ones
    under their callers' stacks; a launch counts as one, its body is not
    the program's."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        path = '/'.join(p for p in (outer, str(eqn.source_info.name_stack))
                        if p)
        found.append((eqn, path))
        if eqn.primitive.name == 'pallas_call':
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _eqn_paths(sub, path, found)
    return found


def _launch_paths(jaxpr):
    """(launch name, name stack) of every Pallas launch in a jaxpr."""
    return [(eqn.params['name'], path) for eqn, path in _eqn_paths(jaxpr)
            if eqn.primitive.name == 'pallas_call']


@pytest.mark.parametrize('rematted', [False, True])
def test_on_a_tpu_the_cores_two_launches_are_filed_under_bd_core(
        monkeypatch, rematted):
    """What the attention layer takes on a TPU at shapes `can_run` admits:
    `kernels/pallas_block_attention.py`'s launches, `bd_core_fwd` in the
    forward phase and `bd_core_bwd` in the backward, both under the leaf
    `bd_core` (their own names are no leaves), so the readers of the leaf
    read them whatever they are called; and the one pass before and after
    them (`kernels/pallas_qk_pass.py`), `qk_pass_fwd` and `qk_pass_bwd`
    under `mha_qkv`: role names, no leaves. A block rematted as the
    decoders' are replays the pass and no launch of the core. Nothing else
    is issued under `bd_core`: no cast of q, k or v, and di = sum(o do) is
    inside the backward launch."""
    from se3_transformer_tpu.ops import block_diffusion, latent_attention
    from se3_transformer_tpu.ops.grouped_attention import (
        GroupedQueryAttention,
    )
    monkeypatch.setattr(block_diffusion, 'is_tpu_backend', lambda: True)
    attn = GroupedQueryAttention(dim=32, heads=2, kv_heads=1, head_dim=128,
                                 block=128, qk_norm=True, rope_theta=1e6)
    x = jnp.ones((1, 512, 32))
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)['params']

    def layer(p, x):
        return attn.apply({'params': p}, x, jnp.arange(512) % 256, 4)

    if rematted:
        layer = jax.checkpoint(layer, policy=latent_attention.SAVE_ATTN_CORE)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: layer(p, x).sum()))(params)
    filed = {(name, profiling.scope_leaf(path), profiling.scope_phase(path))
             for name, path in _launch_paths(jaxpr.jaxpr)}
    assert filed == {('bd_core_fwd', 'bd_core', 'forward'),
                     ('bd_core_bwd', 'bd_core', 'backward'),
                     ('qk_pass_fwd', 'mha_qkv', 'forward'),
                     ('qk_pass_bwd', 'mha_qkv', 'backward')} | (
        {('qk_pass_fwd', 'mha_qkv', 'replay')} if rematted else set())
    roles = {'bd_core_fwd', 'bd_core_bwd', 'qk_pass_fwd', 'qk_pass_bwd'}
    assert not roles & set(MODEL_SCOPES)
    under_core = {str(eqn.primitive) for eqn, path in _eqn_paths(jaxpr.jaxpr)
                  if profiling.scope_leaf(path) == 'bd_core'}
    # `name` marks what `SAVE_ATTN_CORE` keeps; remat rounds the saved o to
    # its own width (`reduce_precision`, which changes nothing)
    assert under_core <= {'pallas_call', 'jit', 'name',
                          'reduce_precision'}, under_core


def test_the_same_module_trained_next_token_keeps_the_causal_leaf():
    def batch_of(lm_loss, module, tokens):
        return lm_loss.make_lm_loss(module, chunk=8), dict(tokens=tokens)

    leaves = {profiling.scope_leaf(p)
              for p in _tiny_block_diffusion_step_paths(batch_of)}
    assert 'mha_core' in leaves and not leaves & set(BD_LEAVES)


# --------------------------------------------------------------------- #
# the sliding-window layers' leaf, beside the global layers' `mha_core`
# --------------------------------------------------------------------- #
def test_the_sliding_window_leaf_is_on_the_closed_list_and_new(labelled):
    assert 'swa_core' in MODEL_SCOPES
    assert 'swa_core' not in (HYBRID_LEAVES + DECODER_LEAVES + SCONV_LEAVES
                              + BD_LEAVES)
    comps = {c for _, _, p in labelled for c in p.split(';')[0].split('/')}
    assert 'swa_core' not in comps


def test_a_tiny_sliding_window_step_tells_its_two_cores_apart():
    """One program, two cores: the global layer's under `mha_core`, the three
    sliding layers' under `swa_core`, each in forward, replay and backward
    (off the TPU the blocked forms are recomputed); the router's product
    under `moe_router` in the expert step, though its input is the attention
    step's; every other leaf as the other decoders write it."""
    import optax

    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES['smallthinker_decoder'](attention_block=8)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    text = make_sharded_train_step(
        make_lm_loss(module, chunk=8), optimizer).lower(
        params, jax.eval_shape(optimizer.init, params), dict(tokens=tokens),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    paths = {p.rsplit('/', 1)[0] for p in re.findall(
        r'"(jit\(train_step\)/[^"]*)"', text)}
    cells = {(profiling.scope_leaf(p), profiling.scope_phase(p))
             for p in paths}
    assert {leaf for leaf, _ in cells} == {
        'swa_core', 'mha_core', 'mha_qkv', 'mha_out', 'embed', 'moe_router',
        'moe_dispatch', 'moe_experts', 'moe_combine', 'lm_head', 'norm',
        'loss', 'optimizer'}
    for leaf in ('swa_core', 'mha_core', 'mha_qkv', 'moe_experts',
                 'moe_router'):
        assert {ph for lf, ph in cells if lf == leaf} == set(
            profiling.PHASES), leaf
    by_block = {leaf: {re.search(r'blocks_(\d)', p).group(1) for p in paths
                       if f'/attn/{leaf}' in p}
                for leaf in ('mha_core', 'swa_core')}
    assert by_block == {'mha_core': {'0'}, 'swa_core': {'2', '4', '6'}}
    routers = {re.search(r'blocks_(\d)', p).group(1) for p in paths
               if '/moe_router' in p}
    assert routers == {'1', '3', '5', '7'}


@pytest.mark.parametrize('rematted', [False, True])
def test_on_a_tpu_the_window_cores_launches_are_filed_under_swa_core(
        monkeypatch, rematted):
    """What a layer with a window takes on a TPU at shapes `launches_run`
    admits: the same two kernels under the window's rule, named
    `swa_core_fwd` and `swa_core_bwd` and filed under the leaf `swa_core`,
    the one pass before and after them under `mha_qkv`; a rematted block
    replays the pass and no launch of the core."""
    from se3_transformer_tpu.ops import latent_attention, sliding_window
    from se3_transformer_tpu.ops.grouped_attention import (
        GroupedQueryAttention,
    )
    monkeypatch.setattr(sliding_window, 'is_tpu_backend', lambda: True)
    attn = GroupedQueryAttention(dim=32, heads=7, kv_heads=1, head_dim=128,
                                 block=128, rope_theta=1.5e6, window=200)
    x = jnp.ones((1, 512, 32))
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)['params']

    def layer(p, x):
        return attn.apply({'params': p}, x)

    if rematted:
        layer = jax.checkpoint(layer, policy=latent_attention.SAVE_ATTN_CORE)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: layer(p, x).sum()))(params)
    filed = {(name, profiling.scope_leaf(path), profiling.scope_phase(path))
             for name, path in _launch_paths(jaxpr.jaxpr)}
    assert filed == {('swa_core_fwd', 'swa_core', 'forward'),
                     ('swa_core_bwd', 'swa_core', 'backward'),
                     ('qk_pass_fwd', 'mha_qkv', 'forward'),
                     ('qk_pass_bwd', 'mha_qkv', 'backward')} | (
        {('qk_pass_fwd', 'mha_qkv', 'replay')} if rematted else set())
    assert not {'swa_core_fwd', 'swa_core_bwd'} & set(MODEL_SCOPES)
    under_core = {str(eqn.primitive) for eqn, path in _eqn_paths(jaxpr.jaxpr)
                  if profiling.scope_leaf(path) == 'swa_core'}
    assert under_core <= {'pallas_call', 'jit', 'name',
                          'reduce_precision'}, under_core


@pytest.mark.parametrize('rematted', [False, True])
def test_on_a_tpu_a_global_layers_launches_are_filed_under_mha_core(
        monkeypatch, rematted):
    """What a layer with neither window nor block length takes on a TPU at
    shapes `launches_run` admits (groups of 16 here, as the hybrid's): the
    same two kernels under the rule ('mha', 0), named `mha_core_fwd` and
    `mha_core_bwd` and filed under the leaf the global layers always had,
    `mha_core`, so what reads that leaf reads the same work whoever runs
    it; no new leaf on the closed list, the launches' names are none; the
    one pass before and after them under `mha_qkv`; a rematted block
    replays the pass and no launch of the core, and nothing else is issued
    under the leaf; no array anywhere has the heads laid out or the
    key-value head repeated ([T, H, D], [H, T, D])."""
    from se3_transformer_tpu.ops import latent_attention, sliding_window
    from se3_transformer_tpu.ops.grouped_attention import (
        GroupedQueryAttention,
    )
    before = set(MODEL_SCOPES)
    monkeypatch.setattr(sliding_window, 'is_tpu_backend', lambda: True)
    attn = GroupedQueryAttention(dim=32, heads=16, kv_heads=1, head_dim=128,
                                 block=128)
    x = jnp.ones((1, 256, 32))
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)['params']

    def layer(p, x):
        return attn.apply({'params': p}, x)

    if rematted:
        layer = jax.checkpoint(layer, policy=latent_attention.SAVE_ATTN_CORE)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: layer(p, x).sum()))(params)
    filed = {(name, profiling.scope_leaf(path), profiling.scope_phase(path))
             for name, path in _launch_paths(jaxpr.jaxpr)}
    assert filed == {('mha_core_fwd', 'mha_core', 'forward'),
                     ('mha_core_bwd', 'mha_core', 'backward'),
                     ('qk_pass_fwd', 'mha_qkv', 'forward'),
                     ('qk_pass_bwd', 'mha_qkv', 'backward')} | (
        {('qk_pass_fwd', 'mha_qkv', 'replay')} if rematted else set())
    assert not {'mha_core_fwd', 'mha_core_bwd'} & set(MODEL_SCOPES)
    assert set(MODEL_SCOPES) == before and 'mha_core' in before
    under_core = {str(eqn.primitive) for eqn, path in _eqn_paths(jaxpr.jaxpr)
                  if profiling.scope_leaf(path) == 'mha_core'}
    assert under_core <= {'pallas_call', 'jit', 'name',
                          'reduce_precision'}, under_core
    shapes = {v.aval.shape for eqn, _ in _eqn_paths(jaxpr.jaxpr)
              for v in eqn.outvars}
    assert not shapes & {(1, 256, 16, 128), (1, 16, 256, 128)}, shapes


LOOP_LEAVES = ('exit_gate', 'exit_mix')


def test_the_looped_stacks_leaves_are_on_the_closed_list_and_new(labelled):
    for leaf in LOOP_LEAVES:
        assert leaf in MODEL_SCOPES
    assert not set(LOOP_LEAVES) & set(
        HYBRID_LEAVES + DECODER_LEAVES + SCONV_LEAVES + BD_LEAVES)
    comps = {c for _, _, p in labelled for c in p.split(';')[0].split('/')}
    assert not set(LOOP_LEAVES) & comps
    # a pass is a component of the path and no leaf
    assert not any(PASS_SCOPE.match(leaf) for leaf in MODEL_SCOPES)
    assert not any(PASS_SCOPE.match(c) for c in comps)
    assert profiling.scope_pass(
        'jit(train_step)/loss/transpose(jvp(loss))/ut_2/blocks_0/norm/mul') \
        == 'ut_2'
    assert profiling.scope_pass('jit(train_step)/loss/blocks_0/mul') is None
    assert profiling.scope_pass('jit(f)/loss/output_2/mul') is None


def test_a_tiny_looped_step_carries_its_passes_in_all_three_phases():
    """One set of blocks, four passes: flax gives the four calls of
    `blocks_0` one name, so every path through a block (and through the
    final norm that closes a pass) carries the component `ut_<t>` of its
    pass, forward, replayed and backward alike; the exits' leaves are
    outside any pass; every leaf reads as the other decoders write it."""
    import optax

    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_looped_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES
    module = RECIPES['ouro_decoder'](attention_block=8)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    text = make_sharded_train_step(
        make_looped_lm_loss(module, 0.1, chunk=8), optimizer).lower(
        params, jax.eval_shape(optimizer.init, params), dict(tokens=tokens),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    paths = {p.rsplit('/', 1)[0] for p in re.findall(
        r'"(jit\(train_step\)/[^"]*)"', text)}
    cells = {(profiling.scope_leaf(p), profiling.scope_phase(p))
             for p in paths}
    assert {leaf for leaf, _ in cells} == {
        'mha_core', 'mha_qkv', 'mha_out', 'dense_ff', 'embed', 'norm',
        'lm_head', 'exit_gate', 'exit_mix', 'loss', 'optimizer'}
    by_pass = {}
    for p in paths:
        by_pass.setdefault(profiling.scope_pass(p), set()).add(
            (profiling.scope_leaf(p), profiling.scope_phase(p)))
    assert set(by_pass) == {None, 'ut_0', 'ut_1', 'ut_2', 'ut_3'}
    inside = {(leaf, phase) for leaf in ('mha_core', 'mha_qkv', 'mha_out',
                                         'dense_ff', 'norm')
              for phase in profiling.PHASES}
    for t in range(4):      # a block's residual add is under no leaf of
        #                     its own and reads as `loss`, as in every decoder
        assert inside <= by_pass[f'ut_{t}'], t
        assert {leaf for leaf, _ in by_pass[f'ut_{t}'] - inside} == {'loss'}
    # every path through a block is in a pass; the exits are in none
    assert all(profiling.scope_pass(p) for p in paths if '/blocks_' in p)
    outside = {leaf for leaf, _ in by_pass[None]}
    assert outside == {'embed', 'lm_head', 'exit_gate', 'exit_mix', 'loss',
                       'optimizer'}
    for leaf in ('lm_head', 'exit_gate', 'exit_mix'):
        assert {ph for lf, ph in by_pass[None] if lf == leaf} >= {
            'forward', 'backward'}, leaf
    assert {ph for lf, ph in by_pass[None] if lf == 'lm_head'} == set(
        profiling.PHASES)
