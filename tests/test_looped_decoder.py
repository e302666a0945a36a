"""The looped stack (`HybridDecoder` with `total_ut_steps` above 1 and
`sandwich_norm`) and its objective (`make_looped_lm_loss`) against the plain
reference the benchmark keeps (`benchmark/harness/ouro_reference.py`), in
float32 on the CPU at a small size: hidden 64, two layers, 4 heads of 16, 256
rows, 64 positions, four passes."""
import importlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.models.hybrid_decoder import HybridDecoder
from se3_transformer_tpu.training.lm_loss import (
    chunked_weighted_nll, make_lm_loss, make_looped_lm_loss,
)
from se3_transformer_tpu.training.recipes import RECIPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = dict(vocab_rows=256, hidden_size=64, hybrid_override_pattern='*F*F',
             intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=4, head_dim=16, rope_theta=1e6,
             layer_norm_epsilon=1e-6, tie_word_embeddings=False,
             sandwich_norm=True, total_ut_steps=4)
B, T, P, BETA = 2, 64, 4, 0.1
BLOCKS = ('blocks_0', 'blocks_1', 'blocks_2', 'blocks_3')


@pytest.fixture(scope='module')
def ref():
    """`ouro_reference.py` imports `lm_reference.py` from its own directory:
    both are loaded as a package of a name of their own, beside whatever
    `harness` another test has on its path."""
    d = os.path.join(ROOT, 'benchmark', 'harness')
    name = 'plain_ouro_references'
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(d, '__init__.py'), submodule_search_locations=[d])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules[name] = pkg
    spec.loader.exec_module(pkg)
    try:
        yield importlib.import_module(f'{name}.ouro_reference')
    finally:
        for n in [n for n in sys.modules if n.split('.')[0] == name]:
            del sys.modules[n]


def _perturbed(params, seed=100):
    """Scales off one and biases off zero, so that a comparison covers
    them."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(flat):
        z = jax.random.normal(jax.random.PRNGKey(seed + i), a.shape)
        name = str(path[-1].key)
        out.append(a + 0.1 * z if name in ('scale', 'bias') else a)
    return jax.tree_util.tree_unflatten(treedef, out)


def _module(**changes):
    return RECIPES['ouro_decoder'](bf16_operands=False, attention_block=16,
                                   **dict(SIZES, **changes))


@pytest.fixture(scope='module')
def tiny():
    module = _module()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 256)
    params = _perturbed(module.init(jax.random.PRNGKey(0), tokens)['params'])
    return module, params, tokens


@pytest.fixture(scope='module')
def program(tiny):
    """((loss, aux), gradient) of the program's objective, float32."""
    module, params, tokens = tiny
    with jax.default_matmul_precision('highest'):
        return jax.jit(jax.value_and_grad(
            make_looped_lm_loss(module, BETA, chunk=32), has_aux=True))(
            params, dict(tokens=tokens), None)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_the_parameter_tree_is_one_set_of_layers_two_norms_a_mixer_a_gate(
        tiny):
    module, params, tokens = tiny
    assert sorted(params) == sorted(
        BLOCKS + ('embedding', 'final_norm', 'head', 'exit_gate'))
    for name, mixer in zip(BLOCKS, ('attn', 'mlp', 'attn', 'mlp')):
        assert sorted(params[name]) == sorted(
            (mixer, 'pre_norm', 'post_norm'))
    assert params['exit_gate']['kernel'].shape == (64, 1)
    assert params['exit_gate']['bias'].shape == (1,)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    # a layer: attention, SwiGLU, four norms; two matrices over the rows, the
    # final norm, the gate. The passes add nothing
    assert count == 2 * (4 * 64 * 64 + 3 * 64 * 96 + 4 * 64) \
        + 2 * 256 * 64 + 64 + 65
    main, ahead, stats = module.apply({'params': params}, tokens,
                                      method='hidden_states')
    assert main.shape == (P, B, T, 64) and ahead is None and stats == []
    logits, _ = module.apply({'params': params}, tokens)
    np.testing.assert_allclose(
        logits, main[-1] @ params['head']['kernel'], rtol=1e-5, atol=1e-5)


def test_loss_exits_and_every_gradient_leaf_match_the_plain_reference(
        tiny, program, ref):
    module, params, tokens = tiny
    (loss, aux), grads = program
    (want, want_aux), want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, SIZES, BETA, attn_block=16, chunk=32),
        has_aux=True)(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for key in ('loss_ut', 'exit_share', 'exit_entropy'):
        assert aux[key].shape == want_aux[key].shape, key
        np.testing.assert_allclose(aux[key], want_aux[key], rtol=1e-5,
                                   err_msg=key)
    assert aux['loss_ut'].shape == aux['exit_share'].shape == (P,)
    np.testing.assert_allclose(aux['loss_main'], want_aux['loss_ut'][-1],
                               rtol=1e-5)
    np.testing.assert_allclose(aux['exit_mass_last'] / aux['exit_tokens'],
                               want_aux['exit_share'][-1], rtol=1e-5)
    assert float(aux['exit_tokens']) == B * (T - 1)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    # embedding, head, final norm, the gate's two; 2 x (two norms, q, k, v,
    # out); 2 x (two norms, gate, up, down)
    assert len(flat) == 5 + 2 * 6 + 2 * 5 == 27
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(w)) > 0, name
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_the_shared_layers_gradient_is_the_sum_of_four_untied_passes(
        tiny, program, ref):
    """The sharing tied to the mathematics: the reference handed four untied
    copies of the layers (equal values) returns a gradient a pass, no two
    alike, and their sum is what the program gives its one set."""
    module, params, tokens = tiny
    _, grads = program
    untied = [[params[name] for name in BLOCKS] for _ in range(P)]
    per_pass = jax.grad(lambda passes: ref.loss(
        params, tokens, SIZES, BETA, attn_block=16, chunk=32,
        passes=passes)[0])(untied)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_pass)
    for name, got in zip(BLOCKS, summed):
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(grads[name])[0],
                jax.tree_util.tree_leaves(got)):
            assert _rel(g, w) < 1e-5, (name, jax.tree_util.keystr(path))
    first, last = (jax.tree_util.tree_leaves(per_pass[i]) for i in (0, -1))
    assert all(_rel(a, b) > 1e-2 for a, b in zip(first, last))


@pytest.mark.parametrize('bias,shares', [
    (-1e4, (0.0, 0.0, 0.0, 1.0)), (1e4, (1.0, 0.0, 0.0, 0.0)),
    (0.0, (0.5, 0.25, 0.125, 0.125))],
    ids=['lam = 0', 'lam = 1', 'lam = 1/2'])
def test_the_exits_share_one_unit_of_mass_and_a_closed_gate_is_the_last_pass(
        tiny, ref, bias, shares):
    """p_1..p_4 sum to one whatever the gate says; with the gate shut (lam =
    0) the loss is the last pass's plain cross-entropy and the entropy is
    zero, with it open (lam = 1) the first pass's; at lam = 1/2 the mass
    halves pass by pass and the last pass takes what is left."""
    module, params, tokens = tiny
    gate = dict(kernel=jnp.zeros((64, 1)), bias=jnp.full((1,), bias))
    loss, aux = make_looped_lm_loss(module, BETA, chunk=32)(
        dict(params, exit_gate=gate), dict(tokens=tokens), None)
    np.testing.assert_allclose(aux['exit_share'], shares, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(aux['exit_share']), 1.0, rtol=1e-6)
    entropy = -sum(p * np.log(p) for p in shares if p)
    np.testing.assert_allclose(aux['exit_entropy'], entropy, atol=1e-6)
    np.testing.assert_allclose(
        loss, np.dot(shares, aux['loss_ut']) - BETA * entropy, rtol=1e-6)
    if bias == -1e4:
        np.testing.assert_allclose(loss, aux['loss_main'], rtol=1e-6)
    log_p = ref.exit_log_probabilities(
        jax.random.normal(jax.random.PRNGKey(5), (P - 1, 32)) * 3)
    np.testing.assert_allclose(jnp.sum(jnp.exp(log_p), axis=0), 1.0,
                               rtol=1e-6)


def test_the_gates_gradient_comes_through_the_row_weights(tiny):
    """A finite difference of the loss along the gate's own gradient, at beta
    0, where the row weights are the gate's only way into the loss: the
    head's rows are weighted by p_t, and the weight is differentiated."""
    module, params, tokens = tiny
    loss_fn = jax.jit(lambda p: make_looped_lm_loss(
        module, 0.0, chunk=32)(p, dict(tokens=tokens), None)[0])
    with jax.default_matmul_precision('highest'):
        g = jax.grad(loss_fn)(params)['exit_gate']
    norm = float(jnp.sqrt(sum(jnp.sum(a * a)
                              for a in jax.tree_util.tree_leaves(g))))
    assert norm > 1e-3

    def at(eps):
        gate = jax.tree_util.tree_map(lambda a, d: a + eps * d / norm,
                                      params['exit_gate'], g)
        with jax.default_matmul_precision('highest'):
            return float(loss_fn(dict(params, exit_gate=gate)))

    np.testing.assert_allclose((at(0.05) - at(-0.05)) / 0.1, norm, rtol=2e-2)


def test_one_pass_without_the_second_norm_is_the_decoder_as_it_was(tiny):
    """`total_ut_steps=1, sandwich_norm=False` are the defaults: the
    parameter tree has neither a `post_norm` nor a gate, `hidden_states`
    returns one state [B, T, d] and the next-token loss is the plain
    cross-entropy of `__call__`'s logits, as for every pattern before."""
    _, _, tokens = tiny
    module = _module(total_ut_steps=1, sandwich_norm=False)
    fields = dict(SIZES)
    del fields['total_ut_steps'], fields['sandwich_norm']
    assert module == HybridDecoder(bf16_operands=False, attention_block=16,
                                   **fields)
    params = module.init(jax.random.PRNGKey(0), tokens)['params']
    assert sorted(params) == sorted(
        BLOCKS + ('embedding', 'final_norm', 'head'))
    for name, mixer in zip(BLOCKS, ('attn', 'mlp', 'attn', 'mlp')):
        assert sorted(params[name]) == sorted((mixer, 'pre_norm'))
    main, _, _ = module.apply({'params': params}, tokens,
                              method='hidden_states')
    assert main.shape == (B, T, 64)
    with jax.default_matmul_precision('highest'):
        loss, aux = make_lm_loss(module, chunk=32)(
            params, dict(tokens=tokens), None)
        logits, _ = module.apply({'params': params}, tokens)
    nll = jax.nn.logsumexp(logits[:, :-1], axis=-1) - jnp.take_along_axis(
        logits[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(loss, jnp.mean(nll), rtol=1e-5)
    assert set(aux) == {'loss_main'}


def test_a_looped_stack_refuses_a_block_diffusion_pass(tiny):
    module, params, tokens = tiny
    with pytest.raises(AssertionError):
        module.apply({'params': params}, tokens, tokens, 4,
                     method='hidden_states')


def test_one_pass_through_the_head_gives_a_sum_a_column_of_weights():
    key = jax.random.PRNGKey(2)
    h = jax.random.normal(key, (64, 16))
    kernel = jax.random.normal(jax.random.fold_in(key, 1), (16, 40))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (64,), 0, 40)
    weights = jax.random.uniform(jax.random.fold_in(key, 3), (64, 3))
    both = chunked_weighted_nll(h, kernel, targets, weights, chunk=16)
    assert both.shape == (3,)
    for i in range(3):
        np.testing.assert_allclose(
            both[i], chunked_weighted_nll(h, kernel, targets, weights[:, i],
                                          chunk=16), rtol=1e-6)
