"""The chunked state-space scan (`ops/state_space.py`) as two Pallas kernels:
scores, decay, the masked product and the chunk states never leave VMEM.

For one chunk of Q tokens, head h of group g (B, C [Q, N] a group; `cum` the
chunk's inclusive cumulative sum of dt A, non-positive; E [P, N] the state
entering the chunk, zero before the first):

    S = C B^T                                   [Q, Q], once a group
    L_ij = exp(cum_i - cum_j), j <= i; 0 above  masked BEFORE the exponential
    y   = (S o L)(dt x) + exp(cum) o (C E^T) + D x
    own = ((dt x) o exp(cum_last - cum))^T B    [P, N], the chunk's own state
    E'  = exp(cum_last) E + own                 the state entering the next

Two launches, named by role (`name=` on `pl.pallas_call`, the instruction
name a device trace shows), a program a (sequence, chunk) over all heads,
the chunks of a sequence in order with the state carried in a VMEM scratch:

    ssm_scan_fwd  y, and where a backward will follow each chunk's E
    ssm_scan_bwd  the chunks last to first, d E' carried the same way:
                  dx, ddt, dcum, dB, dC, dD, with S and L rebuilt in VMEM
                  from the same inputs; nothing [Q, Q] is saved, E is

`chunk_scan` ties them in a `jax.custom_vjp` whose residuals are its
arguments and E. The carry is the recurrence itself, in float32 on the
vector unit: XLA's masked product over the chunks wants the states tiled
with the chunks in sublanes, which a program a chunk cannot write, and paid
two relayouts of the states a product for it (PR 38).

Arithmetic: `cum`, the differences, the exponentials, L, the carried state,
every accumulator and every output in float32; the operands of each product
rounded to bfloat16 once with float32 accumulation (float32 operands under
`interpret`, where the CPU has no such product), which is what XLA's
default precision does with the einsum form. An exponent is a sum of
non-positive terms taken before the exponential; nothing divides.

Layout: features by tokens, the tokens in lanes. That is the layout XLA
gives the mixer's activations around the scan on a TPU ([B, T, width] with
T minor), so the caller's transposes at the launches' edges are bitcasts,
and asking for tokens by features instead slowed the neighbouring
projections and gates by 17 ms a step (PR 38). x^T, B^T and C^T are three
views of the one array [B, H P + 2 G N, T] the mixer's convolution writes,
and their cotangents leave as one; y^T [B, H P, T], a head its P rows;
dt^T, cum^T [B, H, T], so a head's dt and cum are rows that broadcast over
its sublanes for nothing; states [H P, N]. S and L are
held transposed, [j, i]. Of L's two indices i runs along lanes (cum^T's
row) and j along sublanes, for which `cum` comes a second time as columns,
[B, G, T, H / G] so that a group's are a block of their own; the one
cotangent that falls out as a column (the sums over i of dL o L) leaves the
same way. A program loops over the groups and unrolls a group's heads, so
a launch traces H / G heads and not H.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# a program holds a chunk of x, dy, dx and E, twice each (the pipeline's two
# buffers), and the carried state: 22 MiB at the hybrid cell's widths
VMEM_LIMIT = 64 * 2 ** 20
# the chunks of a sequence run in order: a scratch carries the state
PARAMS = pltpu.CompilerParams(dimension_semantics=('parallel', 'arbitrary'),
                              vmem_limit_bytes=VMEM_LIMIT)


def can_run(h: int, p: int, g: int, n: int, chunk: int) -> bool:
    """The widths Mosaic tiles: a chunk and a state of whole lane rows; a
    head, and a group's heads (their rows of dt and cum), whole sublane
    tiles; B and C whole blocks of the array that holds them beside x."""
    return chunk % LANES == 0 and n % LANES == 0 and p % 8 == 0 \
        and h % (8 * g) == 0 and (h * p) % (g * n) == 0


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _nt(a, b):        # a [m, k], b [n, k] -> a b^T
    return _dot(a, b, (1, 1))


def _tn(a, b):        # a [k, m], b [k, n] -> a^T b
    return _dot(a, b, (0, 0))


def _nn(a, b):
    return _dot(a, b, (1, 0))


def _keep(q):
    """[j, i]: token j feeds token i."""
    return jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _decay(cum, cum_col, keep):
    """L^T [j, i] of a head from its cum as a row [1, Q] (i) and as a column
    [Q, 1] (j): the mask goes on the exponent."""
    return jnp.exp(jnp.where(keep, cum - cum_col, -jnp.inf))


def _exp_row(value, width):
    """exp of a [1, 1] as [1, width]: Mosaic broadcasts along one of lanes
    and sublanes at a time, so a scalar that scales a tile is spread over
    the lanes before the exponential and over the sublanes after it."""
    return jnp.exp(jnp.broadcast_to(value, (1, width)))


def _stack(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def _group_rows(ref, gi, height):
    """Group gi's `height` rows of a [1, G height, Q] block."""
    return ref[0, pl.ds(pl.multiple_of(gi * height, height), height), :]


def _fwd_kernel(x_ref, dt_ref, cum_ref, cumc_ref, b_ref, c_ref, d_ref,
                y_ref, *rest, dims, od):
    h, p, g, n = dims
    r, q = h // g, x_ref.shape[2]
    e_ref, state = rest if len(rest) == 2 else (None, rest[0])
    keep = _keep(q)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def group(gi, _):           # a loop, so that a launch traces one group
        bt = _group_rows(b_ref, gi, n).astype(od)                # [N, Q]
        ct = _group_rows(c_ref, gi, n).astype(od)
        scores = _tn(bt, ct)                                     # [j, i]
        rows = pl.ds(pl.multiple_of(gi * r * p, r * p), r * p)
        e = state[rows, :]                                       # [R P, N]
        if e_ref is not None:
            e_ref[0, 0, rows, :] = e
        read = _nn(e.astype(od), ct)                             # [R P, Q]
        x, dt = x_ref[0, rows, :], _group_rows(dt_ref, gi, r)
        cums, cols = _group_rows(cum_ref, gi, r), cumc_ref[0, gi]
        ds = d_ref[pl.ds(pl.multiple_of(gi * r, r), r), :]
        ys, weighted, decayed = [], [], []
        for j in range(r):
            local = slice(j * p, (j + 1) * p)
            cum, xj = cums[j:j + 1], x[local]
            xd = xj * dt[j:j + 1]
            m = scores * _decay(cum, cols[:, j:j + 1], keep)
            ys.append(_nn(xd.astype(od), m.astype(od))
                      + jnp.exp(cum) * read[local] + ds[j:j + 1] * xj)
            last = cum[:, q - 1:q]
            weighted.append((xd * jnp.exp(last - cum)).astype(od))
            decayed.append(_exp_row(last, n) * e[local])
        y_ref[0, rows, :] = _stack(ys)
        state[rows, :] = _stack(decayed) + _nt(_stack(weighted), bt)

    jax.lax.fori_loop(0, g, group, None)


def _views(dims, chunk, order):
    """x^T, B^T and C^T as three views of one [B, H P + 2 G N, T] array,
    which is how the mixer holds them: no slice is made for the launch."""
    h, p, g, n = dims
    view = lambda height, at: pl.BlockSpec(       # noqa: E731
        (1, height, chunk), lambda z, c: (z, at, order(c)))
    return [view(h * p, 0), view(g * n, h * p // (g * n)),
            view(g * n, h * p // (g * n) + 1)]


_STATIC = ('dims', 'chunk', 'interpret', 'save_states')


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd(xbc, dt, cum, cumc, d, dims, chunk, interpret, save_states):
    """xbc [B, H P + 2 G N, T] -> y [B, H P, T], and with `save_states` E
    [B, nc, H P, N]. A jit of its own, so that a step traces and lowers the
    launch once, not once a layer and phase (2 s of set-up each)."""
    h, p, g, n = dims
    bsz, _, t = xbc.shape
    hp, nc = h * p, t // chunk
    od, f32 = jnp.float32 if interpret else jnp.bfloat16, jnp.float32
    rows = lambda height: pl.BlockSpec(           # noqa: E731
        (1, height, chunk), lambda z, c: (z, 0, c))
    x, b, c = _views(dims, chunk, lambda c: c)
    out_specs = [rows(hp)]
    out_shape = [jax.ShapeDtypeStruct((bsz, hp, t), f32)]
    if save_states:
        out_specs.append(pl.BlockSpec((1, 1, hp, n),
                                      lambda z, c: (z, c, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, hp, n), f32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dims=dims, od=od),
        grid=(bsz, nc),
        in_specs=[x, rows(h), rows(h),
                  pl.BlockSpec((1, g, chunk, h // g),
                               lambda z, c: (z, 0, c, 0)), b, c,
                  pl.BlockSpec((h, chunk), lambda z, c: (0, 0))],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hp, n), f32)],
        compiler_params=PARAMS, interpret=interpret, name='ssm_scan_fwd',
    )(xbc, dt, cum, cumc, xbc, xbc, jnp.broadcast_to(d[:, None], (h, chunk)))


# --------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------- #
def _over_rows(v):
    return jnp.sum(v, axis=0, keepdims=True)


def _total(v):
    """v [m, n] -> its sum, [1, 1]."""
    return jnp.sum(_over_rows(v), axis=1, keepdims=True)


def _bwd_kernel(x_ref, dy_ref, dt_ref, cum_ref, cumc_ref, b_ref, c_ref,
                e_ref, d_ref,
                dxbc_ref, ddt_ref, dcum_ref, dcumc_ref, dd_ref,
                dstate, *, dims, od):
    h, p, g, n = dims
    r, q = h // g, x_ref.shape[2]
    keep = _keep(q)
    head = jax.lax.broadcasted_iota(jnp.int32, (q, r), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1

    @pl.when(pl.program_id(1) == 0)       # the sequence's last chunk
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def group(gi, _):
        bt = _group_rows(b_ref, gi, n).astype(od)                # [N, Q]
        ct = _group_rows(c_ref, gi, n).astype(od)
        scores = _tn(bt, ct)                                     # [j, i]
        dscores = jnp.zeros((q, q), jnp.float32)
        rows = pl.ds(pl.multiple_of(gi * r * p, r * p), r * p)
        heads = pl.ds(pl.multiple_of(gi * r, r), r)
        # E entering the chunk, and the cotangent of E' leaving it, which
        # is the cotangent of the chunk's own state
        e, down = e_ref[0, 0, rows, :], dstate[rows, :]          # [R P, N]
        e_o, down_o = e.astype(od), down.astype(od)
        read = _nn(e_o, ct)                                      # [R P, Q]
        from_own = _nn(down_o, bt)
        via_carry = jnp.sum(down * e, axis=1, keepdims=True)     # [R P, 1]
        x, dy = x_ref[0, rows, :], dy_ref[0, rows, :]
        dts, cums, cols = (dt_ref[0, heads, :], cum_ref[0, heads, :],
                           cumc_ref[0, gi])
        ds = d_ref[heads, :]
        dx, dd, ddt, dcum, weighted, read_cot, decayed = (
            [] for _ in range(7))
        dcum_cols = jnp.zeros((q, r), jnp.float32)
        for j in range(r):
            local = slice(j * p, (j + 1) * p)
            xj, dyj, dt, cum = x[local], dy[local], dts[j:j + 1], cums[j:j + 1]
            last = cum[:, q - 1:q]
            to_end, decay_in = jnp.exp(last - cum), jnp.exp(cum)
            xd = xj * dt
            xd_o, dy_o = xd.astype(od), dyj.astype(od)
            decay = _decay(cum, cols[:, j:j + 1], keep)
            m = scores * decay
            dm = _tn(xd_o, dy_o)                                 # [j, i]
            dxd = _nt(dy_o, m.astype(od)) + to_end * from_own[local]
            dscores = dscores + dm * decay
            wm = dm * m
            dx.append(dxd * dt + ds[j:j + 1] * dyj)
            dd.append(_over_rows(dyj * xj))
            ddt.append(_over_rows(dxd * xj))
            # d cum: through L, + the sums over j at i and - the sums over i
            # at j (a column); through the read of E; through `to_end`,
            # - at j and + their sum at the last token; through E' =
            # exp(cum_last) E + own, at the last token
            via_end = to_end * _over_rows(xd * from_own[local])
            at_last = _total(via_end) \
                + jnp.exp(last) * _total(via_carry[local])
            dcum.append(_over_rows(wm)
                        + decay_in * _over_rows(dyj * read[local]) - via_end
                        + jnp.where(is_last, at_last, 0.0))
            dcum_cols = jnp.where(head == j,
                                  jnp.sum(wm, axis=1, keepdims=True),
                                  dcum_cols)
            weighted.append((xd * to_end).astype(od))
            read_cot.append((decay_in * dyj).astype(od))
            decayed.append(_exp_row(last, n) * down[local])
        weighted, read_cot = _stack(weighted), _stack(read_cot)  # [R P, Q]
        ds_o = dscores.astype(od)
        # [dx | dB | dC], as x, B and C came; S^T [j, i] = sum_n B^T C^T
        dxbc_ref[0, rows, :] = _stack(dx)
        at = pl.multiple_of(h * p + gi * n, n)
        dxbc_ref[0, pl.ds(at, n), :] = _nt(ct, ds_o) + _tn(down_o, weighted)
        dxbc_ref[0, pl.ds(at + g * n, n), :] = _nn(bt, ds_o) \
            + _tn(e_o, read_cot)
        dd_ref[0, heads, :] = _stack(dd)
        ddt_ref[0, heads, :] = _stack(ddt)
        dcum_ref[0, heads, :] = _stack(dcum)
        dcumc_ref[0, gi] = dcum_cols
        dstate[rows, :] = _stack(decayed) + _nt(read_cot, ct)

    jax.lax.fori_loop(0, g, group, None)


@functools.partial(jax.jit, static_argnames=_STATIC[:3])
def _bwd(xbc, dy, dt, cum, cumc, entering, d, dims, chunk, interpret):
    h, p, g, n = dims
    bsz, _, t = xbc.shape
    hp, nc = h * p, t // chunk
    od, f32 = jnp.float32 if interpret else jnp.bfloat16, jnp.float32
    last_first = lambda c: nc - 1 - c             # noqa: E731
    rows = lambda height: pl.BlockSpec(           # noqa: E731
        (1, height, chunk), lambda z, c: (z, 0, last_first(c)))
    cols = pl.BlockSpec((1, g, chunk, h // g),
                        lambda z, c: (z, 0, last_first(c), 0))
    like = lambda a: jax.ShapeDtypeStruct(a.shape, f32)   # noqa: E731
    x, b, c = _views(dims, chunk, last_first)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dims=dims, od=od),
        grid=(bsz, nc),
        in_specs=[x, rows(hp), rows(h), rows(h), cols, b, c,
                  pl.BlockSpec((1, 1, hp, n),
                               lambda z, c: (z, last_first(c), 0, 0)),
                  pl.BlockSpec((h, chunk), lambda z, c: (0, 0))],
        out_specs=[rows(xbc.shape[1]), rows(h), rows(h), cols, rows(h)],
        out_shape=[like(xbc), like(dt), like(dt), like(cumc), like(dt)],
        scratch_shapes=[pltpu.VMEM((hp, n), f32)],
        compiler_params=PARAMS, interpret=interpret, name='ssm_scan_bwd',
    )(xbc, dy, dt, cum, cumc, xbc, xbc, entering,
      jnp.broadcast_to(d[:, None], (h, chunk)))


# --------------------------------------------------------------------- #
# the scan over a sequence of whole chunks
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def chunk_scan(xbc, dt, cum, cum_cols, d, dims, chunk, interpret=False):
    """Features by tokens: xbc [B, H P + 2 G N, T] (x, B and C one above
    the other, as the mixer's convolution leaves them), dt [B, H, T], cum
    [B, H, T] (the inclusive sum of dt A inside each chunk) and the same a
    second time as `cum_cols` [B, G, T, H / G], d [H], dims (H, P, G, N) ->
    y [B, H P, T], float32; T a multiple of `chunk`; the state starts at
    zero. D x included."""
    return _fwd(xbc, dt, cum, cum_cols, d, dims=dims, chunk=chunk,
                interpret=interpret, save_states=False)[0]


def _chunk_scan_fwd(xbc, dt, cum, cum_cols, d, dims, chunk, interpret):
    y, entering = _fwd(xbc, dt, cum, cum_cols, d, dims=dims, chunk=chunk,
                       interpret=interpret, save_states=True)
    return y, (xbc, dt, cum, cum_cols, d, entering)


def _chunk_scan_bwd(dims, chunk, interpret, residuals, dy):
    xbc, dt, cum, cum_cols, d, entering = residuals
    dxbc, ddt, dcum, dcum_cols, dd = _bwd(
        xbc, dy, dt, cum, cum_cols, entering, d, dims=dims, chunk=chunk,
        interpret=interpret)
    return dxbc, ddt, dcum, -dcum_cols, dd.sum((0, 2))


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
