"""The road between an attention layer's three projections and the core's
launches (`kernels/pallas_block_attention.py`, under any of its rules:
`<kind>_core_fwd` and `<kind>_core_bwd` for a kind of `bd`, `swa` or `mha`),
one pass over HBM each way, in the layout both ends share: token-major,
q [B, T, H D], k and v [B, T, KV D], a head a run of D lanes (whole lane
rows: D a multiple of 128, as the core's `launches_run` asks).

    qk_pass_fwd  reads the projections' float32 outputs and writes what the
                 core's forward launch reads, once: q normed (float32 mean of
                 squares over the head's channels, eps, the learned scale),
                 rotated (half-split pairs), multiplied by the softmax's
                 scale and rounded; k normed, rotated, rounded; v rounded
    qk_pass_bwd  reads dq, dk and dv in float32 as the core's backward
                 launch sums them and the projections' outputs again (where
                 there is a norm: nothing else reads them): the scale, the
                 rotation's transpose and the norm's backward in float32,
                 the two norm scales' gradients summed in float32 (one
                 partial row a program, added up outside), and the
                 projections' cotangents written once in the width their
                 products' operands are rounded to

Norm and rotation are each there or not (static), as the layer has them: a
global layer with neither (the hybrid's, the sliding-window decoder's) gets
q scaled and rounded, k and v rounded, and nothing else.
The rotation's tables are [T, D] float32, built once from the positions
(`rotary_tables`): cos twice over, and sin with the first half negated, so
that a head is rotated in its lanes, x C + swap(x) S with swap the halves
exchanged (a lane rotation by D / 2): the same products and sums as
`ops/rotary.py::apply_rotary_halves`, and the transpose is the same form on
the cotangent, g C + swap(g S).

The grid is (sequence, row tile, key-value head): a program takes the rows
of a group's query heads and of its key-value head, so every launch reads
and writes whole lane rows. No launch asks for more VMEM than the default.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# of a program: 1 MiB of a group's float32 q at 8 heads of 128, 2 MiB at 16
# (the hybrid's global layer: compiled for the chip within the default,
# tests/test_tpu_compile.py)
ROWS = 256


def rotary_tables(angles):
    """angles [T, D / 2] -> (C, S) [T, D] float32 as the launches read them."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (jnp.concatenate((cos, cos), axis=-1),
            jnp.concatenate((-sin, sin), axis=-1))


def _swap(x):
    """The halves of x [rows, D] exchanged."""
    return pltpu.roll(x, x.shape[1] // 2, axis=1)


def _inverse_rms(x, eps):
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _take(refs, there):
    """The next two of `refs` where the layer has them, else None twice."""
    return (next(refs), next(refs)) if there else (None, None)


def head_lanes(h, d: int):
    """Head h's lanes of a group's block [rows, g d]."""
    return pl.ds(pl.multiple_of(h * d, d), d)


def _fwd_kernel(*refs, g, d, scale, eps, norm, rotate):
    refs = iter(refs)
    q_ref, k_ref, v_ref = next(refs), next(refs), next(refs)
    qw_ref, kw_ref = _take(refs, norm)
    c_ref, s_ref = _take(refs, rotate)
    qo_ref, ko_ref, vo_ref = refs

    def road(x, w_ref):
        if norm:
            x = x * _inverse_rms(x, eps) * w_ref[...]
        if rotate:
            x = x * c_ref[...] + _swap(x) * s_ref[...]
        return x

    def head(h, _):         # a loop, so that a launch traces one head
        lanes = head_lanes(h, d)
        qo_ref[0, :, lanes] = (road(q_ref[0, :, lanes], qw_ref)
                               * scale).astype(qo_ref.dtype)

    jax.lax.fori_loop(0, g, head, None)
    ko_ref[0] = road(k_ref[0], kw_ref).astype(ko_ref.dtype)
    vo_ref[0] = v_ref[0].astype(vo_ref.dtype)


def _bwd_kernel(*refs, g, d, scale, eps, norm, rotate):
    refs = iter(refs)
    dq_ref, dk_ref, dv_ref = next(refs), next(refs), next(refs)
    (q_ref, k_ref), (qw_ref, kw_ref) = _take(refs, norm), _take(refs, norm)
    c_ref, s_ref = _take(refs, rotate)
    dqo_ref, dko_ref, dvo_ref = next(refs), next(refs), next(refs)

    def road(dy, x, w_ref):
        """The cotangent of a head's rows before norm and rotation, and the
        rows' sum of what its norm's scale gets [1, D]."""
        if rotate:
            dy = dy * c_ref[...] + _swap(dy * s_ref[...])
        if not norm:
            return dy, None
        r = _inverse_rms(x, eps)
        xn, dxn = x * r, dy * w_ref[...]
        dx = r * (dxn - xn * jnp.mean(dxn * xn, axis=-1, keepdims=True))
        return dx, jnp.sum(dy * xn, axis=0, keepdims=True)

    def head(h, dw):
        lanes = head_lanes(h, d)
        dx, dw_h = road(dq_ref[0, :, lanes] * scale,
                        q_ref[0, :, lanes] if norm else None, qw_ref)
        dqo_ref[0, :, lanes] = dx.astype(dqo_ref.dtype)
        return dw + dw_h if norm else dw

    dqw = jax.lax.fori_loop(0, g, head, jnp.zeros((1, d), jnp.float32))
    dk, dkw = road(dk_ref[0], k_ref[0] if norm else None, kw_ref)
    dko_ref[0] = dk.astype(dko_ref.dtype)
    dvo_ref[0] = dv_ref[0].astype(dvo_ref.dtype)
    if norm:
        dw_ref, = refs
        dw_ref[0, 0, 0, 0:1, :] = dqw
        dw_ref[0, 0, 0, 1:2, :] = dkw


def _specs(rows, g, d):
    """A group's query heads at a row tile, its key-value head there, a
    norm's scale, the rotation's tables at the tile."""
    heads = pl.BlockSpec((1, rows, g * d), lambda z, t, c: (z, t, c))
    keys = pl.BlockSpec((1, rows, d), lambda z, t, c: (z, t, c))
    scales = pl.BlockSpec((1, d), lambda z, t, c: (0, 0))
    tables = pl.BlockSpec((rows, d), lambda z, t, c: (t, 0))
    return heads, keys, scales, tables


def _operands(norms, rotary, scales, tables):
    """The optional operands and their blocks, in the kernels' order."""
    arrays = [w[None] for w in norms or ()] + list(rotary or ())
    specs = [scales] * (2 if norms else 0) + [tables] * (2 if rotary else 0)
    return arrays, specs


_STATIC = ('head_dim', 'scale', 'eps', 'dtype', 'interpret')
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'parallel'))


@functools.partial(jax.jit, static_argnames=_STATIC)
def forward(q, k, v, norms, rotary, head_dim, scale, eps, dtype,
            interpret=False):
    """q [B, T, H D], k, v [B, T, KV D] float32, `norms` the queries' and
    the keys' scale [D] (or None), `rotary` the tables of `rotary_tables`
    (or None) -> q, k, v of the same shapes in `dtype`. A jit of its own, so
    that a step traces and lowers the launch once, not once a layer."""
    b, t, hd = q.shape
    d, kv = head_dim, k.shape[2] // head_dim
    g, rows = hd // d // kv, math.gcd(t, ROWS)
    heads, keys, scales, tables = _specs(rows, g, d)
    arrays, specs = _operands(norms, rotary, scales, tables)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g, d=d, scale=scale, eps=eps,
                          norm=norms is not None, rotate=rotary is not None),
        grid=(b, t // rows, kv), in_specs=[heads, keys, keys] + specs,
        out_specs=[heads, keys, keys],
        out_shape=[jax.ShapeDtypeStruct(a.shape, dtype) for a in (q, k, v)],
        compiler_params=_PARAMS, interpret=interpret, name='qk_pass_fwd',
    )(q, k, v, *arrays)


@functools.partial(jax.jit, static_argnames=_STATIC)
def backward(dq, dk, dv, q, k, norms, rotary, head_dim, scale, eps, dtype,
             interpret=False):
    """dq, dk, dv float32 of `forward`'s outputs, q and k its inputs ->
    the cotangents of its q, k and v in `dtype`, and of `norms` in float32
    (None where there is no norm)."""
    b, t, hd = dq.shape
    d, kv = head_dim, dk.shape[2] // head_dim
    g, rows = hd // d // kv, math.gcd(t, ROWS)
    heads, keys, scales, tables = _specs(rows, g, d)
    arrays, specs = _operands(norms, rotary, scales, tables)
    norm = norms is not None
    # a program's sums for the two scales, rows of one block of its own
    sums = pl.BlockSpec((1, 1, 1, 2, d), lambda z, t, c: (z, t, c, 0, 0))
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, g=g, d=d, scale=scale, eps=eps,
                          norm=norm, rotate=rotary is not None),
        grid=(b, t // rows, kv),
        in_specs=[heads, keys, keys] + ([heads, keys] if norm else [])
        + specs,
        out_specs=[heads, keys, keys] + ([sums] if norm else []),
        out_shape=[jax.ShapeDtypeStruct(a.shape, dtype)
                   for a in (dq, dk, dv)] + ([jax.ShapeDtypeStruct(
                       (b, t // rows, kv, 2, d), jnp.float32)] if norm else []),
        compiler_params=_PARAMS, interpret=interpret, name='qk_pass_bwd',
    )(dq, dk, dv, *([q, k] if norm else []), *arrays)
    dw = tuple(out[3].sum((0, 1, 2))) if norm else None
    return out[0], out[1], out[2], dw
