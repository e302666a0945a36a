"""An attention core whose mask is a static rule, as two Pallas kernels over
a table of the tiles that hold a visible pair. Four rules, each a pair
(kind, n) that names its leaf and its launches (`<kind>_core`,
`<kind>_core_fwd`, `<kind>_core_bwd`):

    ('bd', block_length)   the two streams of a decoder trained by diffusion
                           over blocks (`ops/block_diffusion.py`), below
    ('swa', window)        a causal sliding window over T positions
                           (`ops/sliding_window.py`): query i sees key j
                           exactly when 0 <= i - j < window. A query tile
                           meets the key tiles m = 0, 1, ... tiles back
                           while m tile - (tile - 1) < window: the diagonal
                           (m = 0), whole tiles, and the far edge, a
                           boundary tile's mask being `low <= r - c <= high`
                           at (-m tile, window - 1 - m tile)
                           (`window_table`). At T = 16,384, a window of
                           4,096 and tiles of 512 a head has 252 tiles in
                           the table where the causal triangle has 528, 56
                           of them on a boundary; a window of T or more is
                           the causal triangle.
    ('mha', 0)             the causal triangle over T positions, a decoder's
                           global layers: the window's table at a window of
                           T (n says nothing: 0, as a layer with no window
                           has it), 528 tiles a head at T = 16,384 and
                           tiles of 512 and 136 at 8,192, the diagonal's 32
                           and 16 alone on a boundary (`0 <= r - c`). A
                           rule of its own for the leaf: what reads
                           `mha_core` reads a model's global layers, what
                           reads `swa_core` its sliding ones.
    ('latent', 0)          the same triangle and table for latent attention
                           (`ops/latent_attention.py`), under its own leaf,
                           `latent_core`, at heads of two lane rows: 20
                           heads of 256 channels in groups of one, so a
                           program is one head, k and v are read once a
                           head, and a tile's two and five products are
                           twice as deep for the same softmax. The layer
                           has no pass before the launches
                           (`rounded_attention`, below).

The block-diffusion rule:

A sequence of L tokens is 2 L positions, noised then clean, cut into tiles of
`tile` positions. With the tile dividing L and the block length dividing the
tile, a (query tile, key tile) pair is one of five things (`tile_table`):

    empty            no visible pair: not in the table, never visited
    full             every pair visible: scores, softmax and products with
                     no compare and no select (a noised or clean query tile
                     against an earlier tile of the clean stream)
    noised -> noised the query tile against itself:     r // bl == c // bl
    noised -> clean  against its own tile, clean:       c // bl <  r // bl
    clean  -> clean  the clean tile against itself:     c // bl <= r // bl

r and c the offsets inside the tile, so a boundary tile's mask is one of
three constants and is `low <= r // bl - c // bl <= high` for two scalars of
the table (`boundary_mask`). At L = 8,192, blocks of 4 and tiles of 512 a
head has 288 tiles of 1,024 in the table, 48 of them on a boundary.

The table goes to both launches by scalar prefetch: the grid is (sequence,
key-value head, table entry), exactly the visited tiles, in the order of
their query tiles, and the index maps read the entry's tiles. Every operand
is in the layout the projections write, token-major: q, o, do and dq
[B, 2L, H D] with a block (1, tile, g D), the g query heads that share a
key-value head side by side, a head a run of D lanes of it; k, v, dk and dv
[B, 2L, KV D] with a block (1, tile, D); the statistics [B, H, 1, 2L]. A
program loops over its group's heads, so k and v are loaded once for all of
them, and nothing is laid out again on either side of a launch. Two
launches, named by role (`name=` on `pl.pallas_call`):

    bd_core_fwd  s = q k^T [query, key]; the running maximum, sum and
                 output of a query tile carried in VMEM scratch over its
                 entries; at its last entry o = acc / l (rounded to the
                 operands' width) and the log-sum-exp, one float32 a row,
                 turned once into a lane row [1, tile]
    bd_core_bwd  at a query tile's first entry di = sum(o do), a lane row a
                 head kept in scratch; then every score and dP once, five
                 products a tile, scores transposed [key, query] so that
                 the log-sum-exp and di are lane rows as stored:
                 p = exp(s - lse), dv += p do, dp = v do^T,
                 ds = p (dp - di), dk += ds q, dq += ds^T k. dq of a query
                 tile is summed in float32 in its output block, resident
                 over the tile's entries; dk and dv of a whole key-value
                 head [2L, D] float32 are resident in VMEM over all of its
                 entries and query heads, so the key-value heads stay
                 unrepeated and nothing is summed in HBM.

`block_attention` ties them and the pass on either side of them
(`kernels/pallas_qk_pass.py`: norm, rotation, scale and rounding of the
projections' float32 outputs in one launch, `qk_pass_fwd`, and their
transposes in another, `qk_pass_bwd`) in one `jax.custom_vjp`: under one
rule dq, dk and dv go from the core to the pass in float32, and the
projections get their cotangents rounded once. The forward's output and
log-sum-exp carry `ATTN_CORE_OUT` / `ATTN_CORE_STATS`, so a block rematted
under `SAVE_ATTN_CORE` replays the pass and no forward launch of the core.
`rounded_attention` is the core alone under the same names, for a layer
whose q, k and v XLA's own products write (the latent layer's: there is
nothing to norm, and the rotation is in the weights): the scale and the one
rounding are `jax.numpy`'s there and fuse into those products, dq, dk and dv
leave in float32, and no pass is launched.

Arithmetic: q (carrying the scale), k, v, p, do and ds rounded to bfloat16
once, float32 accumulation in every product (float32 operands under
`interpret`, where the CPU has no such product); norm, rotation, maxima,
exponentials, sums, log-sum-exp, the running output and every accumulator in
float32. A masked score is a large finite negative, so a row whose tile
hides every key (the first block's rows of a noised -> clean tile) stays
finite and its weights vanish against the tile that holds its own block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import named_scope
from . import pallas_qk_pass as qk_pass

# What a block's replay must not rebuild: an attention core's output and its
# softmax statistics, named in the forward rules below (and in the library
# kernel's, `ops/latent_attention.py`, whose `SAVE_ATTN_CORE` keeps them).
ATTN_CORE_OUT, ATTN_CORE_STATS = 'attn_core_out', 'attn_core_stats'
LANES = 128
# the backward holds a key-value head's dk and dv whole (2 x 8 MiB at 16,384
# positions of 128, twice for the pipeline's buffers) beside a query tile's
# q, o, do and dq for every head of the group and a tile's scores
VMEM_LIMIT = 96 * 2 ** 20
PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'arbitrary'),
    vmem_limit_bytes=VMEM_LIMIT)
MASKED = -0.7 * float(np.finfo(np.float32).max)

# the table's rows, and a tile's kinds
QUERY, KEY, KIND, LOW, HIGH, FIRST, LAST = range(7)
FULL, NOISED_NOISED, NOISED_CLEAN, CLEAN_CLEAN, WINDOW_EDGE = range(5)
FAR = 2 ** 30
# a boundary tile shows the pairs with low <= r // bl - c // bl <= high
BOUNDS = {FULL: (-FAR, FAR), NOISED_NOISED: (0, 0), NOISED_CLEAN: (1, FAR),
          CLEAN_CLEAN: (0, FAR)}


def launches_run(positions: int, tile: int, heads: int, kv_heads: int,
                 head_dim: int) -> bool:
    """What Mosaic's tiles ask of any rule: whole tiles, tiles and heads
    of whole lane rows, whole groups of query heads (of any size: the
    programs loop over a group's heads); and what the backward asks: a
    key-value head's dk and dv [positions, head_dim] float32, in the
    pipeline's two buffers each, in two thirds of the VMEM it may use
    (32,768 positions at heads of 128 fit, 65,536 do not)."""
    return tile % LANES == 0 and positions % tile == 0 \
        and head_dim % LANES == 0 and heads % kv_heads == 0 \
        and 2 * 2 * positions * head_dim * 4 <= 2 * VMEM_LIMIT // 3


def can_run(length: int, block_length: int, tile: int, heads: int,
            kv_heads: int, head_dim: int) -> bool:
    """`launches_run` over a sequence's two streams, and what the
    block-diffusion table asks: whole tiles in a stream and whole blocks in
    a tile (so a boundary's mask is one of three constants)."""
    return length % tile == 0 and tile % block_length == 0 \
        and launches_run(2 * length, tile, heads, kv_heads, head_dim)


@functools.lru_cache(maxsize=None)
def tile_table(length: int, block_length: int, tile: int) -> np.ndarray:
    """[7, n] int32, a column a visited (query tile, key tile) of one head in
    the order both launches take them: by query tile, noised stream first.
    Rows: `QUERY`, `KEY` (tiles of the 2 length positions), `KIND`, `LOW` and
    `HIGH` (`BOUNDS` of the kind), `FIRST` and `LAST` (of its query tile's
    columns). Built once a process."""
    assert length % tile == 0 and tile % block_length == 0, \
        (length, block_length, tile)
    n = length // tile
    columns = []
    for i in range(n):                  # a noised tile: itself, the clean
        columns += [(i, i, NOISED_NOISED)]              # prefix, its own
        columns += [(i, n + j, FULL) for j in range(i)]     # tile clean
        columns += [(i, n + i, NOISED_CLEAN)]
    for i in range(n):                  # a clean tile: block-causal
        columns += [(n + i, n + j, FULL) for j in range(i)]
        columns += [(n + i, n + i, CLEAN_CLEAN)]
    return _table([column + BOUNDS[column[2]] for column in columns])


def _table(columns):
    """[7, n] from (query, key, kind, low, high) a column, sorted by query
    tile."""
    query, key, kind, low, high = np.array(columns, np.int64).T
    new = np.diff(query, prepend=-1, append=-1) != 0
    table = np.stack([query, key, kind, low, high, new[:-1], new[1:]]
                     ).astype(np.int32)
    table.setflags(write=False)
    return table


@functools.lru_cache(maxsize=None)
def window_table(positions: int, window: int, tile: int) -> np.ndarray:
    """The same table for a causal window over `positions`: a query tile i
    against the key tiles i - m that hold a pair with 0 <= distance <
    window, distance = m tile + r - c. A tile is `FULL` where every offset
    pair lies inside (m >= 1 and (m + 1) tile <= window), else its bounds
    are on r - c (the launches' granule is 1). Any window >= 1; one of
    `positions` or more gives the causal triangle."""
    assert positions % tile == 0 and window >= 1, (positions, window, tile)
    columns = []
    for i in range(positions // tile):
        for m in range(min(i, (window + tile - 2) // tile), -1, -1):
            low, high = -m * tile, window - 1 - m * tile
            whole = low <= 1 - tile and high >= tile - 1
            columns += [(i, i - m, FULL if whole else WINDOW_EDGE,
                         max(low, -FAR), min(high, FAR))]
    return _table(columns)


def rule_table(rule, positions: int, tile: int) -> np.ndarray:
    """The table of `rule` = (kind, n) over `positions` (both streams of a
    block-diffusion sequence, a window's T, or the causal triangle's, under
    'mha' and 'latent' alike: the window's table at a window of T)."""
    kind, n = rule
    return tile_table(positions // 2, n, tile) if kind == 'bd' \
        else window_table(positions, n if kind == 'swa' else positions, tile)


def _core_scope(rule):
    """The leaf a rule's two launches run under (each a literal, as the
    closed list of leaves is checked)."""
    return named_scope('bd_core') if rule[0] == 'bd' \
        else named_scope('swa_core') if rule[0] == 'swa' \
        else named_scope('latent_core') if rule[0] == 'latent' \
        else named_scope('mha_core')


def _qkv_scope(rule):
    """The leaf of what stands between a layer's projections and its core
    (literals again): the latent layer's own, or `GroupedQueryAttention`'s."""
    return named_scope('latent_qkv') if rule[0] == 'latent' \
        else named_scope('mha_qkv')


def _granule(rule) -> int:
    """What a boundary tile's mask divides its offsets by."""
    return rule[1] if rule[0] == 'bd' else 1


def floor_div(a, n: int):
    """a // n for a >= 0; a shift where n is a power of two (the vector unit
    has no integer division)."""
    return a >> (n.bit_length() - 1) if n & (n - 1) == 0 else a // n


def boundary_mask(low, high, tile: int, block_length: int,
                  keys_in_lanes: bool = True):
    """[tile, tile] booleans, queries by keys (keys by queries with
    `keys_in_lanes` off): whether the query at offset r sees the key at
    offset c of a boundary tile whose bounds are `low`, `high`. Comparisons
    and one conjunction: Mosaic refuses a select between booleans."""
    q_dim = 0 if keys_in_lanes else 1
    blocks = [floor_div(jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), dim), block_length) for dim in (0, 1)]
    ahead = blocks[q_dim] - blocks[1 - q_dim]
    return (ahead >= low) & (ahead <= high)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _nt(a, b):        # a [m, k], b [n, k] -> a b^T
    return _dot(a, b, (1, 1))


def _tn(a, b):        # a [k, m], b [k, n] -> a^T b
    return _dot(a, b, (0, 0))


def _nn(a, b):
    return _dot(a, b, (1, 0))


def _lanes(a, width: int):
    """a [rows, LANES], every lane alike -> [rows, width]."""
    return a if width == LANES else jnp.tile(a, (1, width // LANES))


def _by_kind(tab, n, block_length, tile, keys_in_lanes, entry):
    """`entry(hidden)` for table column n: `hidden` None on a full tile,
    else what a boundary tile adds to its scores (0 or `MASKED`), built once
    for all of a program's heads."""
    @pl.when(tab[KIND, n] == FULL)
    def _():
        entry(None)

    @pl.when(tab[KIND, n] != FULL)
    def _():
        seen = boundary_mask(tab[LOW, n], tab[HIGH, n], tile, block_length,
                             keys_in_lanes)
        entry(jnp.where(seen, 0.0, MASKED))


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def _fwd_kernel(tab, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, block_length, od):
    n = pl.program_id(2)
    tile, d = k_ref.shape[1:]
    g = q_ref.shape[2] // d
    last = tab[LAST, n] == 1

    @pl.when(tab[FIRST, n] == 1)
    def _():
        m_scr[...] = jnp.full_like(m_scr, MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def entry(hidden):
        k, v = k_ref[0], v_ref[0]

        def head(h, _):         # a loop, so that a launch traces one head
            lanes = qk_pass.head_lanes(h, d)
            s = _nt(q_ref[0, :, lanes], k)                # [query, key]
            if hidden is not None:
                s = s + hidden
            m_prev, l_prev = m_scr[h], l_scr[h]           # [tile, LANES]
            m_next = jnp.maximum(m_prev,
                                 jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_next, tile))
            alpha = jnp.exp(m_prev - m_next)
            l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = _lanes(alpha, d) * acc_scr[h] + _nn(p.astype(od), v)
            m_scr[h], l_scr[h], acc_scr[h] = m_next, l_next, acc

            @pl.when(last)
            def _():
                o_ref[0, :, lanes] = (acc / _lanes(l_next, d)
                                      ).astype(o_ref.dtype)
                # a row's log-sum-exp, alike in every lane -> rows in lanes
                lse_ref[0, h] = (m_next + jnp.log(l_next)).T[:1]

        jax.lax.fori_loop(0, g, head, None)

    _by_kind(tab, n, block_length, tile, True, entry)


def _specs(g, tile, d):
    """The block of a group's query heads at a column's query tile
    [B, 2L, H D], of a key-value head at its key tile [B, 2L, KV D], and of
    the group's rows of statistics [B, H, 1, 2L]."""
    heads = pl.BlockSpec((1, tile, g * d),
                         lambda z, c, n, tab: (z, tab[QUERY, n], c))
    keys = pl.BlockSpec((1, tile, d),
                        lambda z, c, n, tab: (z, tab[KEY, n], c))
    stats = pl.BlockSpec((1, g, 1, tile),
                         lambda z, c, n, tab: (z, c, 0, tab[QUERY, n]))
    return heads, keys, stats


_STATIC = ('head_dim', 'rule', 'tile', 'interpret')


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd(q, k, v, head_dim, rule, tile, interpret):
    """q [B, 2L, H D], k, v [B, 2L, KV D] in the operands' width -> o like
    q and the log-sum-exp [B, H, 1, 2L] float32 (a window's T positions
    where these lines say 2L). A jit of its own, so that a step traces and
    lowers the launch once, not once a layer."""
    b, t, hd = q.shape
    d, kv = head_dim, k.shape[2] // head_dim
    h, f32 = hd // d, jnp.float32
    g = h // kv
    table = rule_table(rule, t, tile)
    heads, keys, stats = _specs(g, tile, d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_length=_granule(rule),
                          od=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, kv, table.shape[1]),
            in_specs=[heads, keys, keys], out_specs=[heads, stats],
            scratch_shapes=[pltpu.VMEM((g, tile, LANES), f32),
                            pltpu.VMEM((g, tile, LANES), f32),
                            pltpu.VMEM((g, tile, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, t), f32)],
        compiler_params=PARAMS, interpret=interpret,
        name=f'{rule[0]}_core_fwd',
    )(jnp.asarray(table), q, k, v)


# --------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------- #
def _bwd_kernel(tab, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, di_scr, *, block_length, od):
    n = pl.program_id(2)
    tile, d = k_ref.shape[1:]
    g = q_ref.shape[2] // d

    @pl.when(n == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(tab[FIRST, n] == 1)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

        def row_sums(h, _):     # di = sum(o do), once a query tile
            lanes = qk_pass.head_lanes(h, d)
            di = jnp.sum(o_ref[0, :, lanes].astype(jnp.float32)
                         * do_ref[0, :, lanes].astype(jnp.float32),
                         axis=1, keepdims=True)
            # a row's sum, alike in every lane -> rows in lanes
            di_scr[h] = jnp.broadcast_to(di, (tile, LANES)).T[:1]

        jax.lax.fori_loop(0, g, row_sums, None)

    def entry(hidden):
        k, v = k_ref[0], v_ref[0]

        def head(h, sums):
            dk, dv = sums
            lanes = qk_pass.head_lanes(h, d)
            q, do = q_ref[0, :, lanes], do_ref[0, :, lanes]
            s = _nt(k, q)                                 # [key, query]
            if hidden is not None:
                s = s + hidden
            p = jnp.exp(s - lse_ref[0, h])
            ds = (p * (_nt(v, do) - di_scr[h])).astype(od)
            dq_ref[0, :, lanes] += _tn(ds, k)
            return dk + _nn(ds, q), dv + _nn(p.astype(od), do)

        zero = jnp.zeros((tile, d), jnp.float32)
        dk, dv = jax.lax.fori_loop(0, g, head, (zero, zero))
        rows = pl.ds(pl.multiple_of(tab[KEY, n] * tile, tile), tile)
        dk_ref[0, rows, :] += dk
        dv_ref[0, rows, :] += dv

    _by_kind(tab, n, block_length, tile, False, entry)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd(q, k, v, o, do, lse, head_dim, rule, tile, interpret):
    """o and its cotangent like q -> dq like q's shape, dk and dv like k's,
    float32."""
    b, t, hd = q.shape
    d, kv = head_dim, k.shape[2] // head_dim
    g, f32 = hd // d // kv, jnp.float32
    table = rule_table(rule, t, tile)
    heads, keys, stats = _specs(g, tile, d)
    whole = pl.BlockSpec((1, t, d), lambda z, c, n, tab: (z, 0, c))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_length=_granule(rule),
                          od=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, kv, table.shape[1]),
            in_specs=[heads, keys, keys, heads, heads, stats],
            out_specs=[heads, whole, whole],
            scratch_shapes=[pltpu.VMEM((g, 1, tile), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, f32)],
        compiler_params=PARAMS, interpret=interpret,
        name=f'{rule[0]}_core_bwd',
    )(jnp.asarray(table), q, k, v, o, do, lse)


# --------------------------------------------------------------------- #
# from the projections' outputs to the core's, under any rule
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def block_attention(q, k, v, norms, rotary, head_dim, scale, eps, rule, tile,
                    interpret=False):
    """q [B, 2L, H D], k, v [B, 2L, KV D] float32 as the projections write
    them (the noised stream, then the clean one; under a window's rule one
    stream of T positions); `norms` the scales [D] of the queries' and the
    keys' RMSNorm, or None; `rotary` the rotation's tables
    (`pallas_qk_pass.rotary_tables`), or None; `rule` = ('bd', block_length),
    ('swa', window) or ('mha', 0) -> o [B, 2L, H D] in the operands' width,
    as the output projection reads it; shapes as `can_run` / `launches_run`
    ask.
    One rule of differentiation for the pass and the core, so that dq, dk
    and dv go from the core's backward launch to `qk_pass_bwd` in float32;
    the core's launches under the leaf `<kind>_core`."""
    return _block_attention_fwd(q, k, v, norms, rotary, head_dim, scale, eps,
                                rule, tile, interpret)[0]


def _named_fwd(qr, kr, vr, head_dim, rule, tile, interpret):
    """The forward launch under the rule's leaf, its two outputs carrying
    the names `SAVE_ATTN_CORE` keeps."""
    with _core_scope(rule):
        o, lse = _fwd(qr, kr, vr, head_dim, rule, tile, interpret)
        return (checkpoint_name(o, ATTN_CORE_OUT),
                checkpoint_name(lse, ATTN_CORE_STATS))


def _block_attention_fwd(q, k, v, norms, rotary, head_dim, scale, eps, rule,
                         tile, interpret):
    od = jnp.float32 if interpret else jnp.bfloat16
    with _qkv_scope(rule):
        qr, kr, vr = qk_pass.forward(q, k, v, norms, rotary, head_dim, scale,
                                     eps, od, interpret)
    o, lse = _named_fwd(qr, kr, vr, head_dim, rule, tile, interpret)
    return o, (q, k, norms, rotary, qr, kr, vr, o, lse)


def _block_attention_bwd(head_dim, scale, eps, rule, tile, interpret,
                         residuals, do):
    q, k, norms, rotary, qr, kr, vr, o, lse = residuals
    f32 = jnp.float32
    with _core_scope(rule):
        # do arrives in o's width, rounded once, as the output projection's
        # dx writes it: the products and the row sums see one do
        dq, dk, dv = _bwd(qr, kr, vr, o, do, lse, head_dim, rule, tile,
                          interpret)
    with _qkv_scope(rule):
        dq, dk, dv, dw = qk_pass.backward(dq, dk, dv, q, k, norms, rotary,
                                          head_dim, scale, eps, qr.dtype,
                                          interpret)
    return dq.astype(f32), dk.astype(f32), dv.astype(f32), dw, None


block_attention.defvjp(_block_attention_fwd, _block_attention_bwd)


# --------------------------------------------------------------------- #
# the core alone, where XLA assembles the operands itself
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def rounded_attention(q, k, v, head_dim, scale, rule, tile, interpret=False):
    """`block_attention` with neither norm nor rotation and no pass: q
    [B, T, H D], k, v [B, T, KV D] float32 as XLA's own fusions assemble
    them (the latent layer's: rotation and concatenations) -> o [B, T, H D]
    in the operands' width. The scale and the one rounding are written here
    in `jax.numpy`, under the rule's `*_qkv` leaf, so that XLA puts them
    into whatever writes q, k and v and no float32 copy of them reaches
    HBM; dq (times the scale), dk and dv leave in float32 as the backward
    launch sums them."""
    return _rounded_attention_fwd(q, k, v, head_dim, scale, rule, tile,
                                  interpret)[0]


def _rounded_attention_fwd(q, k, v, head_dim, scale, rule, tile, interpret):
    od = jnp.float32 if interpret else jnp.bfloat16
    with _qkv_scope(rule):
        qr, kr, vr = (q * scale).astype(od), k.astype(od), v.astype(od)
    o, lse = _named_fwd(qr, kr, vr, head_dim, rule, tile, interpret)
    return o, (qr, kr, vr, o, lse)


def _rounded_attention_bwd(head_dim, scale, rule, tile, interpret, residuals,
                           do):
    qr, kr, vr, o, lse = residuals
    with _core_scope(rule):
        dq, dk, dv = _bwd(qr, kr, vr, o, do, lse, head_dim, rule, tile,
                          interpret)
    with _qkv_scope(rule):
        return dq * scale, dk, dv


rounded_attention.defvjp(_rounded_attention_fwd, _rounded_attention_bwd)
