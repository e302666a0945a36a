"""An expert layer that is told which experts it holds.

    s = sigmoid(x Wr)                          [N, n_experts], float32; or
                                               softmax(x Wr) over all
                                               n_experts (`scoring_func`,
                                               the name published configs
                                               give the choice)
    chosen = top_k(s + b)                      b: the correction bias; it
                                               enters the choice only
    w_i = scale * s_i / (sum_chosen s + norm_topk_eps)
                                               the normaliser is a field:
                                               1e-20 (the default) and 1e-6
                                               are both published
    out = sum_{i chosen and held here} w_i Expert_i(x) + Shared(x)

An expert, routed or shared, has one of three forms, by `hidden_act` (the
name a published config gives the choice):

    'silu'    (silu(x Wg) * (x Wu)) Wd     gated, three matrices (SwiGLU)
    'relu'    (relu(x Wg) * (x Wu)) Wd     gated, three matrices (ReGLU)
    'relu2'   relu(x Wu)^2 Wd              squared ReLU, two matrices

The dense gated form (`SwiGLU`: the shared expert and the decoders' dense
feed-forward) has a backward of its own, `gated_ff`: one pass over gate, up
and dh = dy Wd^T writes d_gate, d_up and act(gate) * up once, in the width
the products round their operands to (bfloat16 where `bf16_operands`), and the
five products of the weights' and x's cotangents read them; the gate's
activation and its derivative are the rule's parameter (`GATE_ACTS`).

The router reads the rows its experts read, or rows handed to it apart from
them (`routing_input`: a decoder whose router is placed before attention
routes a token by the attention step's normed input, while its experts read
the feed-forward step's).

The router keeps all `n_experts` outputs; this chip holds experts
`expert_rank * experts_held ...` of them and computes their part of the
result. What the absent experts would add is left out (the partial result an
expert-parallel rank has before the exchange); nothing stands in for it.

Nothing is dropped and every shape is static: the N * top_k (token, slot)
pairs are sorted so that the H pairs of a held expert come first, expert by
expert, and `jax.lax.ragged_dot` (a native grouped matrix product on the
TPU) runs over sorted rows with the count of each held expert; rows past the
last held pair are not computed and read as zero. So the first C sorted rows
give the whole result whenever H <= C, and dispatch, the grouped products
and combine work on C rows: `held_row_bound`, a multiple of the balanced
expectation N * top_k * experts_held / n_experts that follows from the
layer's shapes alone (`HELD_ROW_BOUND`). A layer whose routing puts more
than C pairs here (H is a device scalar, so `jax.lax.cond` decides) takes
all N * top_k rows instead, the static size that every token choosing the
same held experts fills. Both are exact: the same products over the same
pairs, and neither drops one. Where C reaches N * top_k (every expert held,
tiny sizes) there is the full size alone.

On a TPU the grouped products run at the widths the chip's product wants:
XLA tiles a contracted or output width by the largest of 512 / 256 / 128
that divides it, and at 128 a call is its thousands of programs' overhead
(2,688 x 1,856: 1.7 to 2.5 ms a call where 3,072 x 2,048 takes 0.6 to 0.9).
So a width that is no multiple of 256 is padded with zeros to the next
multiple of 512 (`padded_width`, from the shapes and the backend alone;
`PADDED_WIDTH`): `grouped_dot` pads the operands as it rounds them, the
hidden width stays padded from `up` to `down` (relu(0)^2 = 0 and
silu(0) * 0 = 0), and only the layer's output and the cotangents are
sliced back. Zero columns and rows add nothing to a sum and round to
nothing: the same products at the same precision. Widths that divide by 256
(2,048 and 1,536) are left as they are, to the instruction.
"""
from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import RaggedDotDimensionNumbers

from ..observability import named_scope
from ..utils.helpers import is_tpu_backend

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


def _widen(a, widths):
    """Zeros after a's trailing axes, up to `widths`."""
    pads = [(0, 0, 0)] * (a.ndim - len(widths)) + [
        (0, w - s, 0) for s, w in zip(a.shape[-len(widths):], widths)]
    return jax.lax.pad(a, jnp.zeros((), a.dtype), pads) \
        if any(hi for _, hi, _ in pads) else a


def _narrow(a, widths):
    """The first `widths` of a's trailing axes: what `_widen` was given."""
    shape = a.shape[:a.ndim - len(widths)] + tuple(widths)
    return a if shape == a.shape else jax.lax.slice(a, (0,) * a.ndim, shape)


def grouped_dot(lhs, rhs, group_sizes, operand_dtype=None, widths=None):
    """lhs [P, k] with rows sorted by group, rhs [G, k, n], group_sizes [G]
    -> [P, n] float32: row r of group g is lhs[r] @ rhs[g]. With
    `operand_dtype` the operands are rounded to it first (float32
    accumulation either way); cotangents come back in float32.

    With `widths` = (k', n') the product runs at those widths, on operands
    padded with zeros once they are rounded: -> [P, n'], columns past n zero,
    for the caller to slice or to hand to the next product as they are (lhs
    may come with k' columns: those past k meet zero rows). The backward
    products read the padded operands the forward saved, and every cotangent
    is sliced to its primal's shape."""
    return _grouped_dot(lhs, rhs, group_sizes, operand_dtype,
                        tuple(widths or rhs.shape[1:]))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_dot(lhs, rhs, group_sizes, operand_dtype, widths):
    return _grouped_dot_fwd(lhs, rhs, group_sizes, operand_dtype, widths)[0]


def _cast(a, dtype):
    return a if dtype is None else a.astype(dtype)


def _grouped_dot_fwd(lhs, rhs, group_sizes, operand_dtype, widths):
    unpadded = lhs.shape[1:], rhs.shape[1:]
    lhs = _widen(_cast(lhs, operand_dtype), widths[:1])
    rhs = _widen(_cast(rhs, operand_dtype), widths)
    y = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                           preferred_element_type=jnp.float32)
    return _head_rows(y, group_sizes), (lhs, rhs, group_sizes, unpadded)


def _grouped_dot_bwd(operand_dtype, widths, res, dy):
    lhs, rhs, group_sizes, unpadded = res
    dy = _cast(dy, operand_dtype)
    dlhs = jax.lax.ragged_dot(dy, rhs.swapaxes(1, 2), group_sizes,
                              preferred_element_type=jnp.float32)
    drhs = jax.lax.ragged_dot_general(lhs, dy, group_sizes, _DW_DIMS,
                                      preferred_element_type=jnp.float32)
    return (_narrow(_head_rows(dlhs, group_sizes), unpadded[0]),
            _narrow(drhs, unpadded[1]), None)


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


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


# The rows of the bounded path: this multiple of the balanced expectation of
# the held pairs, rounded up to this many rows (the grouped product's tile)
HELD_ROW_BOUND = (2, 512)


def held_row_bound(n_pairs: int, held: int, n_experts: int) -> int:
    """C, from the layer's shapes alone; n_pairs where C would reach it."""
    multiple, tile = HELD_ROW_BOUND
    rows = -(-multiple * n_pairs * held // n_experts)
    return min(n_pairs, -(-rows // tile) * tile)


# On a TPU a width of a grouped product that is no multiple of the first is
# padded to the next multiple of the second: XLA tiles a width by the largest
# of 512 / 256 / 128 that divides it, and at 128 a call is its programs'
# overhead (ROADMAP M9 has the picks by width)
PADDED_WIDTH = (256, 512)


def padded_width(width: int) -> int:
    """The width the chip's grouped product runs `width` at."""
    unless, tile = PADDED_WIDTH
    if not is_tpu_backend() or width % unless == 0:
        return width
    return -(-width // tile) * tile


def _stages(rows, order, inverse, load, n, k, act, dtype):
    """(take, experts, combine) over the first `rows` sorted rows, which hold
    every held pair: x [N, d] -> xs [rows, d] -> ys [rows, d] -> out [N, d].
    `act`: the gate's activation, or None for the squared ReLU's two
    matrices. At the full size both gathers go through `inverse`; below it a
    row is added into its token's, and so is its cotangent."""
    full = rows == n * k

    def take(x):
        if full:
            return _take_tokens(x, order, inverse)
        return x[order[:rows] // k]

    def experts(xs, mats):
        # the hidden width stays padded from `up` to `down`: every form
        # sends a zero column to a zero column
        d = xs.shape[1]
        wide = padded_width(d), padded_width(mats['up'].shape[2])
        up = grouped_dot(xs, mats['up'], load, dtype, wide)
        hidden = act(grouped_dot(xs, mats['gate'], load, dtype, wide)) \
            * up if act else jnp.square(nn.relu(up))
        return grouped_dot(hidden, mats['down'], load, dtype,
                           wide[::-1])[:, :d]

    def combine(ys, weights):
        if full:
            pairs = _permute_rows(ys, inverse, order).reshape(n, k, -1)
            return jnp.sum(pairs * weights[..., None], axis=1)
        head = order[:rows]
        scaled = ys * weights.reshape(n * k)[head][:, None]
        return jnp.zeros((n, ys.shape[1]), ys.dtype).at[head // k].add(scaled)

    return take, experts, combine


def _held_experts(stages, x, weights, mats):
    """sum_{i chosen and held here} w_i Expert_i(x), each stage under its
    leaf."""
    take, experts, combine = stages
    with named_scope('moe_dispatch'):
        xs = take(x)
    with named_scope('moe_experts'):
        ys = experts(xs, mats)
    with named_scope('moe_combine'):
        return combine(ys, weights)


def _held_experts_vjp(stages, x, weights, mats, g):
    """The cotangents of `_held_experts` at g, stage by stage: a transform
    wraps the first scope it meets (`jvp(moe_dispatch)`, which is no leaf),
    so each stage is differentiated inside its scope, not the whole."""
    take, experts, combine = stages
    with named_scope('moe_dispatch'):
        xs, take_t = jax.vjp(take, x)
    with named_scope('moe_experts'):
        ys, experts_t = jax.vjp(experts, xs, mats)
    with named_scope('moe_combine'):
        d_ys, d_weights = jax.vjp(combine, ys, weights)[1](g)
    with named_scope('moe_experts'):
        d_xs, d_mats = experts_t(d_ys)
    with named_scope('moe_dispatch'):
        return take_t(d_xs)[0], d_weights, d_mats


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bounded_or_full(rows, act, dtype, fits, x, weights, mats, order,
                     inverse, load):
    """`_held_experts` over the first `rows` sorted rows where the held pairs
    fit them (`fits`), else over all N * k. One rule for both directions,
    each with its own `cond` and nothing saved but the arguments:
    differentiated as it stands, a `cond` hands its backward the residuals of
    both branches, the untaken one's (the full size's operands and
    pre-activations) as zeros."""
    return _bounded_or_full_fwd(rows, act, dtype, fits, x, weights, mats,
                                order, inverse, load)[0]


def _branches(fn, rows, act, dtype, order, inverse, load, n, k):
    return [partial(fn, _stages(r, order, inverse, load, n, k, act, dtype))
            for r in (rows, n * k)]


def _bounded_or_full_fwd(rows, act, dtype, fits, x, weights, mats, order,
                         inverse, load):
    bounded, full = _branches(_held_experts, rows, act, dtype, order,
                              inverse, load, *weights.shape)
    out = jax.lax.cond(fits, bounded, full, x, weights, mats)
    return out, (fits, x, weights, mats, order, inverse, load)


def _bounded_or_full_bwd(rows, act, dtype, res, g):
    fits, x, weights, mats, order, inverse, load = res
    bounded, full = _branches(_held_experts_vjp, rows, act, dtype, order,
                              inverse, load, *weights.shape)
    return (None,) + jax.lax.cond(fits, bounded, full, x, weights, mats, g) \
        + (None, None, None)


_bounded_or_full.defvjp(_bounded_or_full_fwd, _bounded_or_full_bwd)


def _silu_and_slope(gate):
    s = nn.sigmoid(gate)
    return gate * s, s * (1.0 + gate * (1.0 - s))


def _relu_and_slope(gate):
    return nn.relu(gate), (gate > 0).astype(gate.dtype)


# the gate's activation -> (the function the forward applies, (act(gate),
# act'(gate)) for the backward's one pass)
GATE_ACTS = {'silu': (nn.silu, _silu_and_slope),
             'relu': (nn.relu, _relu_and_slope)}


def _gated_operands(gate, up, dh, dtype, act_and_slope):
    """One pass over gate, up [N, width] (float32) and dh = dy Wd^T: the three
    operands of the gated form's backward products, d_gate = dh * up *
    act'(gate), d_up = dh * act(gate) and hidden = act(gate) * up, each
    written once in `dtype`. Behind the barrier, or XLA fuses each chain into
    every product that reads it and computes it from the float32 tensors once
    a pass over that operand's tiles."""
    act, d_act = act_and_slope(gate)
    return jax.lax.optimization_barrier(tuple(
        _cast(a, dtype) for a in (dh * up * d_act, dh * act, act * up)))


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gated_ff(x, wg, wu, wd, operand_dtype=None, act='silu'):
    """(act(x wg) * (x wu)) wd for x [N, d], float32, `act` a key of
    `GATE_ACTS`. The backward's five products read `_gated_operands` in
    `operand_dtype` (None: as they are); nothing else is rounded, and the
    cotangents are float32."""
    return _gated_ff_fwd(x, wg, wu, wd, operand_dtype, act)[0]


def _contract(a, i, b, j):
    """Axis i of a with axis j of b, in float32."""
    return jax.lax.dot_general(a, b, (((i,), (j,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _gated_ff_fwd(x, wg, wu, wd, operand_dtype, act):
    gate, up = _contract(x, 1, wg, 0), _contract(x, 1, wu, 0)
    return (_contract(GATE_ACTS[act][0](gate) * up, 1, wd, 0),
            (x, wg, wu, wd, gate, up))


def _gated_ff_bwd(operand_dtype, act, res, dy):
    x, wg, wu, wd, gate, up = res
    dh = _contract(dy, 1, wd, 1)
    d_gate, d_up, hidden = _gated_operands(gate, up, dh, operand_dtype,
                                           GATE_ACTS[act][1])
    dx = _contract(d_gate, 1, wg, 1) + _contract(d_up, 1, wu, 1)
    return (dx, _contract(x, 0, d_gate, 0), _contract(x, 0, d_up, 0),
            _contract(hidden, 0, dy, 0))


gated_ff.defvjp(_gated_ff_fwd, _gated_ff_bwd)


class _Kernel(nn.Module):
    """A `nn.Dense`'s parameter (`<name>/kernel`) without its product."""
    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param('kernel', nn.initializers.lecun_normal(),
                          self.shape)


class SwiGLU(nn.Module):
    """(silu(x Wg) * (x Wu)) Wd, differentiated by `gated_ff`; with `act`
    'relu' the ReGLU form, (relu(x Wg) * (x Wu)) Wd."""
    width: int
    bf16_operands: bool = True   # of the backward's three built operands
    act: str = 'silu'            # the gate's: a key of GATE_ACTS

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        wg, wu, wd = (_Kernel(shape, name=name)() for name, shape in (
            ('gate', (d, self.width)), ('up', (d, self.width)),
            ('down', (self.width, d))))
        y = gated_ff(x.reshape(-1, d), wg, wu, wd,
                     jnp.bfloat16 if self.bf16_operands else None, self.act)
        return y.reshape(x.shape)


class SquaredReLU(nn.Module):
    """relu(x Wu)^2 Wd."""
    width: int

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False)
        up = dense(self.width, name='up')(x)
        return dense(x.shape[-1], name='down')(jnp.square(nn.relu(up)))


# hidden_act -> (the shared expert's module, the activation of an expert's
# gate or None where it has none)
EXPERT_FORMS = {'silu': (SwiGLU, nn.silu), 'relu2': (SquaredReLU, None),
                'relu': (partial(SwiGLU, act='relu'), nn.relu)}

# scoring_func -> the router's scores from its logits [N, n_experts]: each
# expert alone, or over all the router's outputs, held here or not
SCORING_FUNCS = {'sigmoid': nn.sigmoid, 'softmax': nn.softmax}


def route(scores, bias, top_k: int, scale: float, normalize: bool,
          eps: float = 1e-20):
    """scores [N, E] in (0, 1), bias [E] -> (chosen [N, k] int32, weights
    [N, k]). The bias moves the choice, never the weights; `eps` is the
    normaliser's."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + eps)
    return chosen.astype(jnp.int32), scale * picked


BALANCE_RATES = (0.05, 0.001)    # of the first and the last step


def balance_bias(scores, bias, top_k: int, steps: int = 300,
                 scale: float = 1.0):
    """The aux-loss-free balancing rule of the correction bias, run on fixed
    scores [N, E]: bias <- bias + rate * sign(mean load - load), `steps`
    times with the rate falling geometrically (`scale` x BALANCE_RATES: the
    rates are of sigmoid scores, which lie about 0.5; softmax scores lie
    about 1 / E, and a step of 0.001 is wider than the gaps between them).
    Training applies the rule once a step as the data streams by; this is
    what it has done to the buffer by the time the loads have settled."""
    n, e = scores.shape
    target = n * top_k / e
    rate, final_rate = (scale * r for r in BALANCE_RATES)

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
    scoring_func: str = 'sigmoid'   # a key of SCORING_FUNCS
    norm_topk: bool = True
    norm_topk_eps: float = 1e-20   # added to the chosen scores' sum
    bf16_operands: bool = True   # of the grouped products (every other
    #                              product is rounded by the TPU's default)

    @nn.compact
    def __call__(self, x, routing_input=None):
        """x [N, d] -> (out [N, d], stats): `load` [experts_held] pairs
        computed per held expert, `chosen` [N, top_k], `scores` [N,
        n_experts], `dropped` (0). `routing_input` [N, d]: the rows the
        router reads where they are not the experts' (None: x)."""
        n, d = x.shape
        k, held = self.top_k, self.experts_held
        assert (self.expert_rank + 1) * held <= self.n_experts
        x = x.astype(jnp.float32)
        routed_by = x if routing_input is None \
            else routing_input.astype(jnp.float32)
        with named_scope('moe_router'):
            logits = nn.Dense(self.n_experts, use_bias=False, name='router',
                              precision=jax.lax.Precision.HIGHEST)(routed_by)
            bias = self.param('correction_bias', nn.initializers.zeros,
                              (self.n_experts,))
            scores = SCORING_FUNCS[self.scoring_func](logits)
            chosen, weights = route(scores, bias, k, self.routed_scale,
                                    self.norm_topk, self.norm_topk_eps)
        with named_scope('moe_dispatch'):
            local = chosen - self.expert_rank * held
            here = (local >= 0) & (local < held)
            # held pairs first, by expert; every other pair after them
            key = jnp.where(here, local, held).reshape(n * k)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        shared, act = EXPERT_FORMS[self.hidden_act]
        up, down = (held, d, self.width), (held, self.width, d)
        shapes = dict(gate=up, up=up, down=down) if act \
            else dict(up=up, down=down)
        mats = {name: self.param(f'experts_{name}', _expert_init, shape)
                for name, shape in shapes.items()}
        dtype = jnp.bfloat16 if self.bf16_operands else None
        rows = held_row_bound(n * k, held, self.n_experts)
        fits = jnp.sum(load) <= rows
        if rows == n * k:
            out = _held_experts(_stages(rows, order, inverse, load, n, k,
                                        act, dtype), x, weights, mats)
        else:
            out = _bounded_or_full(rows, act, dtype, fits, x, weights, mats,
                                   order, inverse, load)
        if self.shared_width:
            fields = dict(bf16_operands=self.bf16_operands) if act else {}
            with named_scope('shared_expert'):
                out = out + shared(self.shared_width, **fields,
                                   name='shared')(x)
        stats = dict(load=load, chosen=chosen, scores=scores,
                     dropped=jnp.sum(here, dtype=jnp.int32) - jnp.sum(load),
                     bounded=fits.astype(jnp.int32))
        return out, stats
