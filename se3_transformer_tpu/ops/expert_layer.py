"""An expert layer that is told which experts it holds.

    s = sigmoid(x Wr)                          [N, n_experts], float32
    chosen = top_k(s + b)                      b: the correction bias; it
                                               enters the choice only
    w_i = scale * s_i / (sum_chosen s + 1e-20)
    out = sum_{i chosen and held here} w_i Expert_i(x) + Shared(x)

An expert, routed or shared, has one of two forms, by `hidden_act` (the name
a published config gives the choice):

    'silu'    (silu(x Wg) * (x Wu)) Wd     gated, three matrices
    'relu2'   relu(x Wu)^2 Wd              squared ReLU, two matrices

The router keeps all `n_experts` outputs; this chip holds experts
`expert_rank * experts_held ...` of them and computes their part of the
result. What the absent experts would add is left out (the partial result an
expert-parallel rank has before the exchange); nothing stands in for it.

Nothing is dropped and every shape is static: the N * top_k (token, slot)
pairs are sorted so that the pairs of a held expert come first, expert by
expert, and `jax.lax.ragged_dot` (a native grouped matrix product on the
TPU) runs over the sorted rows with the count of each held expert; rows past
the last held pair are not computed and read as zero. Every token choosing
the same held experts fills all N * top_k rows, which is the static size.
"""
from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import RaggedDotDimensionNumbers

from ..observability import named_scope

# dW[g] = lhs[rows of g].T @ dy[rows of g]: the rows (axis 0 of both) are
# the ragged, contracted dimension
_DW_DIMS = RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _head_rows(y, group_sizes):
    """Rows at or past sum(group_sizes) belong to no group held here: the
    grouped product leaves them unwritten; they read as zero."""
    rows = jnp.arange(y.shape[0], dtype=jnp.int32)[:, None]
    return jnp.where(rows < jnp.sum(group_sizes), y, 0.0)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(lhs, rhs, group_sizes, operand_dtype=None):
    """lhs [P, k] with rows sorted by group, rhs [G, k, n], group_sizes [G]
    -> [P, n] float32: row r of group g is lhs[r] @ rhs[g]. With
    `operand_dtype` the operands are rounded to it first (float32
    accumulation either way); cotangents come back in float32."""
    return _grouped_dot_fwd(lhs, rhs, group_sizes, operand_dtype)[0]


def _cast(a, dtype):
    return a if dtype is None else a.astype(dtype)


def _grouped_dot_fwd(lhs, rhs, group_sizes, operand_dtype):
    lhs, rhs = _cast(lhs, operand_dtype), _cast(rhs, operand_dtype)
    y = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                           preferred_element_type=jnp.float32)
    return _head_rows(y, group_sizes), (lhs, rhs, group_sizes)


def _grouped_dot_bwd(operand_dtype, res, dy):
    lhs, rhs, group_sizes = res
    dy = _cast(dy, operand_dtype)
    dlhs = jax.lax.ragged_dot(dy, rhs.swapaxes(1, 2), group_sizes,
                              preferred_element_type=jnp.float32)
    drhs = jax.lax.ragged_dot_general(lhs, dy, group_sizes, _DW_DIMS,
                                      preferred_element_type=jnp.float32)
    return _head_rows(dlhs, group_sizes), drhs, None


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


@jax.custom_vjp
def _take_tokens(x, order, inverse):
    """x [N, d] -> [N * k, d]: sorted row r is the token of pair order[r].
    The cotangent is gathered back through `inverse` (every token has
    exactly k rows), where XLA's own transpose would scatter-add."""
    return x[order // (order.shape[0] // x.shape[0])]


def _take_tokens_fwd(x, order, inverse):
    return _take_tokens(x, order, inverse), (inverse, x.shape[0])


def _take_tokens_bwd(res, g):
    inverse, n = res
    return g[inverse].reshape(n, -1, g.shape[-1]).sum(axis=1), None, None


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


@jax.custom_vjp
def _permute_rows(y, perm, inverse):
    """y[perm] for a permutation; the cotangent is g[inverse]."""
    return y[perm]


def _permute_rows_fwd(y, perm, inverse):
    return y[perm], inverse


def _permute_rows_bwd(inverse, g):
    return g[inverse], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


class SwiGLU(nn.Module):
    """(silu(x Wg) * (x Wu)) Wd."""
    width: int

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False)
        gate = dense(self.width, name='gate')(x)
        up = dense(self.width, name='up')(x)
        return dense(x.shape[-1], name='down')(nn.silu(gate) * up)


class SquaredReLU(nn.Module):
    """relu(x Wu)^2 Wd."""
    width: int

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False)
        up = dense(self.width, name='up')(x)
        return dense(x.shape[-1], name='down')(jnp.square(nn.relu(up)))


# hidden_act -> (the shared expert's module, whether an expert has a gate)
EXPERT_FORMS = {'silu': (SwiGLU, True), 'relu2': (SquaredReLU, False)}


def route(scores, bias, top_k: int, scale: float, normalize: bool):
    """scores [N, E] in (0, 1), bias [E] -> (chosen [N, k] int32, weights
    [N, k]). The bias moves the choice, never the weights."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), scale * picked


BALANCE_RATES = (0.05, 0.001)    # of the first and the last step


def balance_bias(scores, bias, top_k: int, steps: int = 300):
    """The aux-loss-free balancing rule of the correction bias, run on fixed
    scores [N, E]: bias <- bias + rate * sign(mean load - load), `steps`
    times with the rate falling geometrically (BALANCE_RATES). Training
    applies the rule once a step as the data streams by; this is what it has
    done to the buffer by the time the loads have settled."""
    n, e = scores.shape
    target = n * top_k / e
    rate, final_rate = BALANCE_RATES

    def step(i, b):
        _, chosen = jax.lax.top_k(scores + b, top_k)
        load = jnp.bincount(chosen.reshape(-1), length=e)
        r = rate * (final_rate / rate) ** (i / steps)
        return b + r * jnp.sign(target - load)

    return jax.lax.fori_loop(0, steps, step, bias.astype(jnp.float32))


_expert_init = nn.initializers.variance_scaling(
    1.0, 'fan_in', 'normal', in_axis=1, out_axis=2, batch_axis=0)


class ExpertLayer(nn.Module):
    width: int                 # of a routed expert
    n_experts: int             # the router's outputs, held here or not
    top_k: int
    experts_held: int
    expert_rank: int = 0
    shared_width: int = 0      # of the shared expert(s); 0: none
    hidden_act: str = 'silu'   # the experts' form: a key of EXPERT_FORMS
    routed_scale: float = 1.0
    norm_topk: bool = True
    bf16_operands: bool = True   # of the grouped products (every other
    #                              product is rounded by the TPU's default)

    @nn.compact
    def __call__(self, x):
        """x [N, d] -> (out [N, d], stats): `load` [experts_held] pairs
        computed per held expert, `chosen` [N, top_k], `scores` [N,
        n_experts], `dropped` (0)."""
        n, d = x.shape
        k, held = self.top_k, self.experts_held
        assert (self.expert_rank + 1) * held <= self.n_experts
        x = x.astype(jnp.float32)
        with named_scope('moe_router'):
            logits = nn.Dense(self.n_experts, use_bias=False, name='router',
                              precision=jax.lax.Precision.HIGHEST)(x)
            bias = self.param('correction_bias', nn.initializers.zeros,
                              (self.n_experts,))
            scores = nn.sigmoid(logits)
            chosen, weights = route(scores, bias, k, self.routed_scale,
                                    self.norm_topk)
        with named_scope('moe_dispatch'):
            local = chosen - self.expert_rank * held
            here = (local >= 0) & (local < held)
            # held pairs first, by expert; every other pair after them
            key = jnp.where(here, local, held).reshape(n * k)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
            xs = _take_tokens(x, order, inverse)
        with named_scope('moe_experts'):
            dtype = jnp.bfloat16 if self.bf16_operands else None
            shared, gated = EXPERT_FORMS[self.hidden_act]

            def experts_up(name):
                return grouped_dot(xs, self.param(
                    f'experts_{name}', _expert_init, (held, d, self.width)),
                    load, dtype)

            hidden = nn.silu(experts_up('gate')) * experts_up('up') \
                if gated else jnp.square(nn.relu(experts_up('up')))
            w_down = self.param('experts_down', _expert_init,
                                (held, self.width, d))
            ys = grouped_dot(hidden, w_down, load, dtype)
        with named_scope('moe_combine'):
            pairs = _permute_rows(ys, inverse, order).reshape(n, k, d)
            out = jnp.sum(pairs * weights[..., None], axis=1)
        if self.shared_width:
            with named_scope('shared_expert'):
                out = out + shared(self.shared_width, name='shared')(x)
        stats = dict(load=load, chosen=chosen, scores=scores,
                     dropped=jnp.sum(here, dtype=jnp.int32) - jnp.sum(load))
        return out, stats
