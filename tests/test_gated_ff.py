"""The gated feed-forward's differentiation rule (`ops/expert_layer.py::
gated_ff`): its output and four cotangents against `jax.grad` of the plain
formula, with the backward's three built operands in float32 and rounded to
bfloat16, bare and inside a recomputed block."""
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.ops.expert_layer import SwiGLU, gated_ff
from se3_transformer_tpu.ops.latent_attention import SAVE_ATTN_CORE

N, D, WIDTH = 48, 16, 40
NAMES = ('y', 'dx', 'd_gate_kernel', 'd_up_kernel', 'd_down_kernel')


def _inputs():
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    x, dy = (jax.random.normal(k, (N, D)) for k in keys[:2])
    wg, wu = (jax.random.normal(k, (D, WIDTH)) * D ** -0.5 for k in keys[2:4])
    wd = jax.random.normal(keys[4], (WIDTH, D)) * WIDTH ** -0.5
    return x, wg, wu, wd, dy


def _plain(x, wg, wu, wd):
    return (nn.silu(x @ wg) * (x @ wu)) @ wd


def _plain_with_rounded_operands(x, wg, wu, wd, dy, dtype):
    """The backward written out, SiLU's derivative from `jax.grad`: d_gate,
    d_up and hidden pass through `dtype`, nothing else does."""
    def rounded(a):
        return a.astype(dtype).astype(jnp.float32)
    gate, up = x @ wg, x @ wu
    act = nn.silu(gate)
    d_act = jax.vmap(jax.vmap(jax.grad(nn.silu)))(gate)
    dh = dy @ wd.T
    d_gate, d_up, hidden = (rounded(a) for a in (
        dh * up * d_act, dh * act, act * up))
    return ((act * up) @ wd, d_gate @ wg.T + d_up @ wu.T, x.T @ d_gate,
            x.T @ d_up, hidden.T @ dy)


def _through_the_module(x, wg, wu, wd, dy, bf16, remat):
    module = (nn.remat(SwiGLU, policy=SAVE_ATTN_CORE) if remat else SwiGLU)(
        WIDTH, bf16_operands=bf16)
    params = dict(gate=dict(kernel=wg), up=dict(kernel=wu),
                  down=dict(kernel=wd))
    y, vjp = jax.vjp(lambda p, x: module.apply({'params': p}, x), params, x)
    d_params, dx = vjp(dy)
    return (y, dx) + tuple(d_params[k]['kernel']
                           for k in ('gate', 'up', 'down'))


def _close(got, want, name):
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-6 * scale, err_msg=name)


@pytest.mark.parametrize('remat', [False, True], ids=['bare', 'remat'])
def test_float32_rule_is_jax_grad_of_the_plain_formula(remat):
    x, wg, wu, wd, dy = _inputs()
    with jax.default_matmul_precision('float32'):
        y, vjp = jax.vjp(_plain, x, wg, wu, wd)
        want = (y,) + vjp(dy)
        got = _through_the_module(x, wg, wu, wd, dy, False, remat)
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape, name
        _close(g, w, name)


@pytest.mark.parametrize('remat', [False, True], ids=['bare', 'remat'])
def test_bfloat16_rule_rounds_the_three_built_operands_and_nothing_else(
        remat):
    x, wg, wu, wd, dy = _inputs()
    with jax.default_matmul_precision('float32'):
        want = _plain_with_rounded_operands(x, wg, wu, wd, dy, jnp.bfloat16)
        exact = _plain_with_rounded_operands(x, wg, wu, wd, dy, jnp.float32)
        got = _through_the_module(x, wg, wu, wd, dy, True, remat)
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == jnp.float32, name
        _close(g, w, name)
    # the forward is not rounded; the cotangents are, and visibly
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(exact[0]))
    for name, w, e in zip(NAMES[1:], want[1:], exact[1:]):
        gap = float(jnp.abs(w - e).max() / jnp.abs(e).max())
        assert 1e-5 < gap < 1e-2, (name, gap)


@pytest.mark.parametrize('shape', [(N, D), (2, N // 2, D)],
                         ids=['rows', 'batch_of_sequences'])
def test_swiglu_keeps_three_nn_denses_parameters_and_output(shape):
    """The subtree is `gate/kernel`, `up/kernel`, `down/kernel` with
    `nn.Dense`'s own initial values, and the output the three products'."""
    class Plain(nn.Module):
        @nn.compact
        def __call__(self, x):
            dense = partial(nn.Dense, use_bias=False)
            hidden = nn.silu(dense(WIDTH, name='gate')(x)) \
                * dense(WIDTH, name='up')(x)
            return dense(x.shape[-1], name='down')(hidden)

    x = jax.random.normal(jax.random.PRNGKey(3), shape)
    want = Plain().init(jax.random.PRNGKey(0), x)
    got = SwiGLU(WIDTH).init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(SwiGLU(WIDTH).apply(got, x)),
                                  np.asarray(Plain().apply(want, x)))


def test_the_three_operands_are_built_once_behind_one_barrier():
    """One sigmoid in the backward (the forward's is inside `silu`), and the
    five products downstream of one `optimization_barrier` of three [N, width]
    tensors in the stated width."""
    x, wg, wu, wd, dy = _inputs()
    jaxpr = jax.make_jaxpr(
        lambda *a: jax.vjp(partial(gated_ff, operand_dtype=jnp.bfloat16),
                           *a[:4])[1](a[4]))(x, wg, wu, wd, dy)
    names = [e.primitive.name for e in jaxpr.eqns]
    assert names.count('logistic') == 1 and 'exp' not in names, names
    assert names.count('optimization_barrier') == 1
    barrier = jaxpr.eqns[names.index('optimization_barrier')]
    assert [(v.aval.shape, v.aval.dtype) for v in barrier.outvars] == \
        [((N, WIDTH), jnp.bfloat16)] * 3
    # the forward rule's three and dh before it, five after it
    assert names[:names.index('optimization_barrier')].count(
        'dot_general') == 4
    assert names[names.index('optimization_barrier'):].count(
        'dot_general') == 5
