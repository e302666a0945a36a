"""Pallas TPU kernel for the fused pairwise TFN convolution.

This is THE compute hot spot of the model (SURVEY.md §3.3): per edge e and
degree pair (d_in, d_out), the reference computes a radial profile
R[e, o, i, f] with a per-pair MLP, multiplies by the angular basis
B[e, P, Q, f] (P = 2*d_out+1, Q = 2*d_in+1) and contracts with gathered
neighbor features x[e, i, Q] (reference se3_transformer_pytorch.py:336-338).
The XLA path materializes R in HBM — 2*E*IF*O floats of traffic that dwarf
the FLOPs (bandwidth-bound ~6x). This kernel fuses the final radial matmul
with the contraction so R only ever exists as VMEM tiles.

Mosaic-lowering ground rules (learned on-chip: `infer-vector-layout:
unsupported shape cast` / `lhs contracting dims must be of size 1`):
every in-kernel tensor op must be a 2D matmul with single contracting
dims, a static sublane (row) slice, a [1, E] x [O, E] sublane broadcast,
or a sublane reduction. All reshapes/transposes happen OUTSIDE the kernel
in XLA, where they are free relayouts. The layout that makes that
possible puts the EDGE axis on lanes:

    hT  [mid, E]        radial-MLP hidden, transposed
    w3T [IF*O, mid]     final radial weight, (if, o) flattened if-major
    b3T [IF*O, 1]       radial bias column, same row order as w3T
    v2T [P, IF, E]      = sum_Q B[e,P,Q,f] x[e,i,Q], edge-last
    per (e-block, if-chunk) program:
        rT   = w3T_chunk @ hT_blk + b3T_chunk   # one 2D MXU matmul + a
                                                # [S,1]-over-lanes broadcast
        out[pO+o, e] += v2T[p, i, e] * rT[iO+o, e]   # P*bif sublane FMAs
    outT [P*O, E] -> transpose/reshape outside -> out [E, P, O]

    The bias rides as its own [S, 1] operand rather than folded into the
    matmul (a ones column on h / bias row on w3): folding makes the
    contraction dim mid+1 = 129, and the MXU contracts in 128-chunks —
    the dominant dot would pay a second, 1/129-useful pass, a
    structural ~2x tax on every path. mid stays exactly 128.

The grid is (n_e, n_if) with the out block revisited across the inner
if-axis (consecutive revisits — the legal TPU accumulation pattern), so
the huge R tensor never touches HBM and w3 streams through VMEM.

The backward runs as TWO kernels because its two accumulated cotangents
want different inner grid axes: dW3 accumulates over edges (grid
(n_if, n_e), e inner) while dH accumulates over if-chunks (grid
(n_e, n_if), f inner). dV2 falls out of kernel A for free. dR exists only
in VMEM in both: each program stacks its if-chunk's rows, [bif*O, E_b],
in a scratch and feeds them to ONE dot (dW3 in A, dH in B), so the MXU
sees bif*O rows (or a bif*O-wide contraction), never O. The three dots of
the backward take h and w3 in the dtype they arrive in, like the forward.

Each comes in two forms, chosen by what the call site holds: a V2 (the
plain forward and backward), or the flat basis and the gathered features
it is the product of (`fused_pairwise_conv_bxf`,
`fused_pairwise_conv_bwd_bxf`). The second builds V2 in VMEM, forward and
backward, and its kernel A also emits dx: no V2, dV2 or dx passes through
HBM as an XLA operand.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The launches are named by role (`name=` on pallas_call): the name is the
# innermost component of the op's name stack, which XLA takes as the custom
# call's instruction name, so the device trace holds `fused_pairwise_conv`,
# `_bxf` (forward), `fused_pairwise_conv_bwd_a` (dV2, dW3, dB3) and
# `fused_pairwise_conv_bwd_b` (dH). The pads, transposes and reshapes the
# wrappers issue on either side of a launch sit under the leaf scope
# `pairwise_layout` (observability.timing.MODEL_SCOPES), never the launch
# itself.


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _vmem_plain(be: int, bif: int, IF: int, O: int, P: int, mid: int,
                bwd: bool = False) -> int:
    """Working-set bytes of the plain kernel at (be, bif) — the model
    _pick_blocks budgets against and tuning.admissible_candidates
    admits with. bif*O*128: the [S, 1] bias column tile-pads its lane
    dim to 128."""
    total = 4 * (mid * be + bif * O * mid + bif * O * 128
                 + 2 * bif * O * be + P * bif * be + P * O * be)
    if bwd:
        # kernel A additionally holds h_p (be*mid), the gT block
        # (= out-sized), the dv2 block (= v2-sized), the dw3 block
        # (= w3-sized) and the db3 block (= b3-sized). The stacked-dR
        # scratch (bif*O*be) adds no term: it is the second of the two
        # [bif*O, be] tiles above, of which the backward holds R alone
        total += 4 * (be * mid + P * O * be + P * bif * be
                      + bif * O * mid + bif * O * 128)
    return total


def _vmem_bx(be: int, cb: int, O: int, P: int, Q: int, F: int,
             mid: int) -> int:
    """Working-set bytes of the basis-fused kernel at (be, cb)."""
    return 4 * (mid * be + cb * F * O * mid + cb * F * O * 128
                + 2 * cb * F * O * be
                + P * F * Q * be + cb * Q * be + P * O * be)


def _consult_table(kind, shape, heuristic_fn):
    """Measured-config table consult (kernels.tuning), ahead of the
    heuristic: forced tuner candidates and promoted cache entries steer
    the pick; a cache entry failing the tile-quantum / VMEM admission
    model degrades to the heuristic with a warning. The table's dtype
    key is float32: V2, the basis and x reach these kernels in no other.
    Every resolution is recorded for telemetry (serving warmup / run
    report)."""
    from . import tuning
    dtype = 'float32'
    hit = tuning.lookup(kind, shape, dtype=dtype)
    if hit is not None:
        blocks, source = hit
        # forced candidates were admitted by the tuner's own enumeration;
        # re-validating them here would just duplicate warnings
        if source == 'forced' or tuning.validate_entry(kind, shape,
                                                       blocks):
            tuning.record_consult(kind, shape, dtype, source, blocks)
            return blocks
    blocks = heuristic_fn()
    tuning.record_consult(kind, shape, dtype, 'heuristic', blocks)
    return blocks


def _pick_blocks(E: int, IF: int, O: int, P: int, mid: int,
                 vmem_budget: Optional[int] = None,
                 max_unroll: int = 256, bwd: bool = False):
    """Choose (block_e, block_if) so the working set fits in VMEM (with
    headroom for double buffering) and the in-kernel unrolled loop count
    P*block_if stays bounded (Mosaic compile time).

    Resolution order (forward only — the backward always runs this
    heuristic against its own 6 MiB model): the measured shape-keyed
    table (kernels.tuning: tuner-forced candidates, then promoted cache
    entries), then the VMEM-model heuristic below. With an empty table
    the pick is bit-identical to the heuristic (regression-pinned in
    tests/test_kernel_tuning.py).

    Budget: 7 MiB forward / 6 MiB backward. The forward's 7 admits the
    flagship plain pick (512, 16), the middle of three block_if values
    that ranked non-monotonically in the step (8, 16, 32: 16 fastest,
    32 slowest by 3x). The backward keeps 6 MiB: its ~2x working set
    was never measured past it. Other shapes inherit the 7 MiB forward
    budget unvalidated — re-rank in the step before trusting a changed
    pick at a new shape.

    Mosaic block-shape rule: every blocked dim must either cover the full
    array or be divisible by its tile quantum — so block_if is the full IF
    (n_if == 1) or a multiple of 8, and block_e a multiple of 128.

    The preference is block_e first, and it is not to be re-tuned from
    a standalone sweep: alone, the plain kernel at the unchunked
    flagship shape ranked (256, 32) far ahead of (512, 8), and the step
    — the same contraction at E=4096 per chunk under lax.map+remat —
    ran 2.7x slower with it. Inside the chunked/remat program the large
    w3/R tiles of a wide block_if evict the lax.map body's working set
    and the e-grid shortens 8x, while standalone the tiny block_if=8
    tiles are DMA-bound. Re-rank only from the step's own time (the
    benchmark's d4 cell)."""
    if vmem_budget is None:
        vmem_budget = (6 if bwd else 7) * 2 ** 20  # see docstring

    def _heuristic():
        e_cap = _round_up(E, 128)
        for block_e in (512, 256, 128):
            if block_e > e_cap:
                continue
            block_if = min(IF, max(1, max_unroll // max(P, 1)))
            if block_if < IF:
                block_if = max(8, block_if // 8 * 8)
            while True:
                if _vmem_plain(block_e, block_if, IF, O, P, mid,
                               bwd=bwd) <= vmem_budget:
                    return block_e, block_if
                if block_if <= 8:
                    break
                block_if = max(8, block_if // 2 // 8 * 8)
        return 128, min(IF, 8)

    if bwd:
        # the backward never takes table entries (its ~2x working set
        # was only ever validated under this model's picks)
        return _heuristic()
    return _consult_table('plain', (E, IF, O, P, mid), _heuristic)


def _fwd_kernel(ht_ref, w3t_ref, b3t_ref, *rest, P, O, bif,
                precision, scaled=False):
    if scaled:
        st_ref, v2t_ref, o_ref = rest
    else:
        st_ref, (v2t_ref, o_ref) = None, rest
    f = pl.program_id(1)
    w = w3t_ref[:]
    hb = ht_ref[:]
    if w.dtype != hb.dtype:
        # quantized storage (int8/fp8 serving mixes): dequant INSIDE
        # the tile — upcast the VMEM block for the dot, then fold the
        # per-(if,o)-channel scale column in below. The fp32 weight
        # never exists outside this tile.
        w = w.astype(hb.dtype if hb.dtype == jnp.bfloat16
                     else jnp.float32)
    # R chunk, transposed: [bif*O, E_b] — exists only in VMEM. The bias
    # column broadcasts over lanes ([S, 1] + [S, E], the row-stat pattern
    # flash-attention kernels lower every day); the quant scale column
    # rides the same way ([S, 1] * [S, E]).
    rt = jax.lax.dot_general(
        w, hb,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)
    if scaled:
        rt = rt * st_ref[:]
    rt = rt + b3t_ref[:]
    for p in range(P):
        acc = None
        for i in range(bif):
            vrow = v2t_ref[p, i:i + 1, :]            # [1, E_b]
            term = vrow * rt[i * O:(i + 1) * O, :]   # [O, E_b]
            acc = term if acc is None else acc + term
        sl = slice(p * O, (p + 1) * O)

        @pl.when(f == 0)
        def _(acc=acc, sl=sl):
            o_ref[sl, :] = acc.astype(o_ref.dtype)

        @pl.when(f > 0)
        def _(acc=acc, sl=sl):
            o_ref[sl, :] = o_ref[sl, :] + acc.astype(o_ref.dtype)


def _to_lanes(h, w3, v2, g=None):
    """XLA-side relayouts (free) into the edge-on-lanes kernel layouts."""
    E, mid = h.shape
    _, IF, O = w3.shape
    P = v2.shape[1]
    ht = h.T                                        # [mid, E]
    w3t = w3.reshape(mid, IF * O).T                 # [(if,o), mid]
    v2t = v2.transpose(1, 2, 0)                     # [P, IF, E]
    gt = None if g is None else g.transpose(1, 2, 0).reshape(P * O, E)
    return ht, w3t, v2t, gt


def _bias_column(b3, IF, O, IFp):
    """[IF, O] bias -> [IFp*O, 1] kernel operand in w3T row order
    ((if, o) if-major), zero rows for the padded if's."""
    b3t = b3.astype(jnp.float32).reshape(IF * O, 1)
    if IFp != IF:
        b3t = jnp.pad(b3t, ((0, (IFp - IF) * O), (0, 0)))
    return b3t


def _fused_pairwise_conv_impl(h, w3, b3, v2, interpret, precision,
                              w3_scale=None):
    E, mid = h.shape
    _, IF, O = w3.shape
    P = v2.shape[1]

    # bf16 radial operands (radial_bf16): run the rt dot MXU-native with
    # f32 accumulation. Must be an EXPLICIT DEFAULT: None inherits the
    # caller's jax.default_matmul_precision context, and fp32 contract
    # precision on bf16 operands is rejected by Mosaic ("Bad lhs type")
    if h.dtype == jnp.bfloat16:
        precision = jax.lax.Precision.DEFAULT
        if interpret:  # CPU interpret can't dispatch BF16xBF16=F32 dots;
            # the upcast is exact and accumulation is f32 either way
            h = h.astype(jnp.float32)
            if w3_scale is None:
                w3 = w3.astype(jnp.float32)
            # quantized w3 keeps its storage dtype — the kernel body's
            # dtype-mismatch upcast is the dequant-in-tile

    block_e, block_if = _pick_blocks(E, IF, O, P, mid)
    Ep, IFp = _round_up(E, block_e), _round_up(IF, block_if)

    with jax.named_scope('pairwise_layout'):
        ht, w3t, v2t, _ = _to_lanes(h, w3, v2)
        b3t = _bias_column(b3, IF, O, IFp)
        if Ep != E:
            ht = jnp.pad(ht, ((0, 0), (0, Ep - E)))
            v2t = jnp.pad(v2t, ((0, 0), (0, 0), (0, Ep - E)))
        if IFp != IF:
            w3t = jnp.pad(w3t, ((0, (IFp - IF) * O), (0, 0)))
            v2t = jnp.pad(v2t, ((0, 0), (0, IFp - IF), (0, 0)))

    n_e, n_if = Ep // block_e, IFp // block_if

    scaled = w3_scale is not None
    in_specs = [
        pl.BlockSpec((mid, block_e), lambda e, f: (0, e),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_if * O, mid), lambda e, f: (f, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_if * O, 1), lambda e, f: (f, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [ht, w3t, b3t]
    if scaled:
        # per-(if,o)-channel dequant scales in the w3T row order — the
        # same [S, 1] column layout (and zero-row padding) as the bias
        with jax.named_scope('pairwise_layout'):
            st = _bias_column(jnp.asarray(w3_scale, jnp.float32).reshape(
                IF, O), IF, O, IFp)
        in_specs.append(pl.BlockSpec((block_if * O, 1),
                                     lambda e, f: (f, 0),
                                     memory_space=pltpu.VMEM))
        args.append(st)
    in_specs.append(pl.BlockSpec((P, block_if, block_e),
                                 lambda e, f: (0, f, e),
                                 memory_space=pltpu.VMEM))
    args.append(v2t)

    outt = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, O=O, bif=block_if,
                          precision=precision, scaled=scaled),
        grid=(n_e, n_if),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((P * O, block_e), lambda e, f: (0, e),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((P * O, Ep), jnp.float32),
        interpret=interpret,
        name='fused_pairwise_conv',
    )(*args)

    with jax.named_scope('pairwise_layout'):
        return outt.reshape(P, O, Ep).transpose(2, 0, 1)[:E]


# --------------------------------------------------------------------- #
# SPMD partitioning rules
# --------------------------------------------------------------------- #
# The kernels are embarrassingly parallel over the edge axis (e) and the
# output-channel axis (o); only mid (m) and the contracted IF axis (k)
# must be replicated. Without these rules GSPMD treats the Mosaic custom
# call as opaque and would all-gather the sharded edge tensors onto every
# device. With them, a dp/sp-sharded model runs each device's kernel on
# its local edges, and tp-sharded radial weights (param_partition_specs
# shards w3 on o) keep the conv output o-sharded. The backward psums dW3
# over the edge-sharded axes and dH/dV2 over o-sharded axes inside the
# partition body — Shardy sees the results as fully reduced.


def _spec_axes(sharding, dim):
    spec = sharding.spec
    return spec[dim] if len(spec) > dim else None


def _axis_tuple(axes):
    if axes is None:
        return ()
    return axes if isinstance(axes, tuple) else (axes,)


def _factor_positions(rule, factor):
    """(operand_idx, dim) pairs where `factor` appears on the lhs of a
    'e m, m k o, ... -> ...' sharding rule."""
    lhs = rule.split('->')[0]
    return [(i, j) for i, op in enumerate(lhs.split(','))
            for j, f in enumerate(op.split()) if f == factor]


def _edge_o_axes(arg_shapes, e_pos, o_pos):
    """Resolve the (edge, output-channel) sharding axes by scanning EVERY
    operand that carries the factor (positions parsed from the rule
    string) — resolving e from h alone would silently drop the edge
    sharding when h arrives replicated but v2/basis/x/g carry it, and
    GSPMD would then all-gather the edge tensors. A mesh
    axis can't shard both factors — on collision the edge sharding wins
    and the o-carrying operands get resharded by the partitioner."""
    def first(positions):
        for i, j in positions:
            ax = _spec_axes(arg_shapes[i].sharding, j)
            if ax is not None:
                return ax
        return None

    e, o = first(e_pos), first(o_pos)
    if set(_axis_tuple(e)) & set(_axis_tuple(o)):
        o = None
    return e, o


def _make_partitioned(impl, rule, need_repl, arg_specs, result_specs,
                      psum_fn=None):
    """Build a custom_partitioning wrapper around `impl`.

    arg_specs/result_specs: callables (P_, e, o) -> tuple of
    PartitionSpec (one per operand / result; a single-result entry point
    passes a 1-tuple and unwraps). psum_fn(outs, e, o): reduce partial
    sums inside the partition body (backward only)."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P_

    single = psum_fn is None and len(result_specs(P_, None, None)) == 1
    e_pos, o_pos = _factor_positions(rule, 'e'), _factor_positions(rule, 'o')

    @custom_partitioning
    def f(*args):
        return impl(*args)

    def _shardings(mesh, specs):
        return tuple(NamedSharding(mesh, s) for s in specs)

    def partition(mesh, arg_shapes, result_shape):
        e, o = _edge_o_axes(arg_shapes, e_pos, o_pos)
        arg_sh = _shardings(mesh, arg_specs(P_, e, o))
        res_sh = _shardings(mesh, result_specs(P_, e, o))

        def lower_fn(*args):
            outs = impl(*args)
            return psum_fn(outs, e, o) if psum_fn else outs

        return (mesh, lower_fn, res_sh[0] if single else res_sh, arg_sh)

    def infer(mesh, arg_shapes, shape):
        e, o = _edge_o_axes(arg_shapes, e_pos, o_pos)
        m = arg_shapes[0].sharding.mesh
        res = _shardings(m, result_specs(P_, e, o))
        return res[0] if single else res

    f.def_partition(partition=partition,
                    infer_sharding_from_operands=infer,
                    sharding_rule=rule,
                    need_replication_factors=need_repl)
    return f


@functools.lru_cache(maxsize=None)
def _fwd_partitioned(interpret, precision):
    return _make_partitioned(
        lambda h, w3, b3, v2: _fused_pairwise_conv_impl(h, w3, b3, v2,
                                                        interpret,
                                                        precision),
        rule='e m, m k o, k o, e p k -> e p o', need_repl=('m', 'k'),
        arg_specs=lambda P_, e, o: (P_(e, None), P_(None, None, o),
                                    P_(None, o), P_(e, None, None)),
        result_specs=lambda P_, e, o: (P_(e, None, o),))


@functools.partial(jax.jit, static_argnames=('interpret', 'precision'))
def fused_pairwise_conv(h: jnp.ndarray, w3: jnp.ndarray, v2: jnp.ndarray,
                        b3: jnp.ndarray = None,
                        interpret: bool = False,
                        precision=None,
                        w3_scale: jnp.ndarray = None) -> jnp.ndarray:
    """h [E, mid], w3 [mid, IF, O], v2 [E, P, IF], b3 [IF, O] (optional,
    zeros when None) -> out [E, P, O] (f32): out = v2 . (h@w3 + b3).

    The bias is a separate [S, 1] kernel operand, NOT folded into the
    contraction — folding made mid 129 and cost a structural ~2x on the
    dominant dot (module docstring). `precision` feeds the in-kernel MXU
    dots (captured from jax.default_matmul_precision by the caller — the
    kernel body traces outside that context). Partitions over sharded
    edge/output-channel axes (see the SPMD rules above).

    `w3_scale` [1, IF, O] switches on the quantized-serving epilogue:
    `w3` is then int8/fp8 STORAGE, dequantized inside the tile (upcast
    of the VMEM block + a per-(if,o)-channel scale column riding like
    the bias operand) so the fp32 radial weight never exists in HBM —
    out = v2 . ((h@w3) * scale + b3). Single-program only: the SPMD
    partition rules describe the 4-operand fp path, and quantized
    serving replicates params (quant + tp sharding is follow-up work).
    """
    if b3 is None:
        b3 = jnp.zeros(w3.shape[1:], jnp.float32)
    if w3_scale is not None:
        return _fused_pairwise_conv_impl(h, w3, b3, v2, interpret,
                                         precision, w3_scale=w3_scale)
    return _fwd_partitioned(interpret, precision)(h, w3, b3, v2)


def pallas_available() -> bool:
    from ..utils.helpers import is_tpu_backend
    return is_tpu_backend()


# --------------------------------------------------------------------- #
# basis-fused forward (V2 never touches HBM)
# --------------------------------------------------------------------- #
# The plain kernel above takes V2[e, P, IF] = sum_Q B[e,P,Q,F] x[e,c,Q]
# precomputed by an XLA einsum — which materializes V2 in HBM (write +
# read of E*P*IF floats, ~4-10x the traffic of B and x themselves at
# trunk widths). This variant moves that contraction into the kernel:
# per (e-block, c-chunk) program it reconstructs each V2 row [1, E] from
# a [Q, E] elementwise product + sublane reduction, so V2 only ever
# exists rows-at-a-time in VMEM. One kernel per (d_in, d_out) pair
# (the group concat of conv.py needs a uniform IF chunk axis, which
# heterogeneous (Q, F) segments don't give).
#
# Layouts (edge-on-lanes, as above):
#   bt [P*F*Q, E]   B rows, (p, f, q) flattened p-major — the (p, f)
#                   row-pairs the kernel reduces over are contiguous
#   xt [C*Q, E]     gathered features, (c, q) flattened c-major,
#                   C padded to a multiple of the c-chunk
#   w3t [(IF)*O, mid]  (i=(c,f), o) flattened i-major, rows padded with
#                   zeros for the padded c's (their contributions vanish)
# Grid (n_e, n_c) with the out block accumulated over the inner c axis.


def _fwd_bx_kernel(ht_ref, w3t_ref, b3t_ref, bt_ref, xt_ref, o_ref, *,
                   P, O, Q, F, cb, precision):
    c0 = pl.program_id(1)
    rt = jax.lax.dot_general(
        w3t_ref[:], ht_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32) + b3t_ref[:]  # [cb*F*O, E_b]
    for p in range(P):
        acc = None
        for il in range(cb * F):
            c_l, f_l = divmod(il, F)
            b_sl = (p * F + f_l) * Q
            # V2 row for (p, i=(c, f)): one [Q, E] product + reduction
            v2row = jnp.sum(bt_ref[b_sl:b_sl + Q, :]
                            * xt_ref[c_l * Q:(c_l + 1) * Q, :],
                            axis=0, keepdims=True)   # [1, E_b]
            term = v2row * rt[il * O:(il + 1) * O, :]
            acc = term if acc is None else acc + term
        sl = slice(p * O, (p + 1) * O)

        @pl.when(c0 == 0)
        def _(acc=acc, sl=sl):
            o_ref[sl, :] = acc.astype(o_ref.dtype)

        @pl.when(c0 > 0)
        def _(acc=acc, sl=sl):
            o_ref[sl, :] = o_ref[sl, :] + acc.astype(o_ref.dtype)


def _pick_blocks_bx(E: int, C: int, O: int, P: int, Q: int, F: int,
                    mid: int, vmem_budget: int = 6 * 2 ** 20,
                    max_unroll: int = 512):
    """(block_e, cb) for the basis-fused kernel. cb is the c-chunk: a
    multiple of 8 (so the xt row-block cb*Q and w3t row-block cb*F*O are
    tile-aligned for any odd Q/F) or the full (padded) C.

    Resolution order mirrors _pick_blocks: the measured shape-keyed
    table (kernels.tuning, kind 'bxf'), then the heuristic below.

    Alone, the kernel ranks the default (128, 8) within 2% of the best
    other pick at the flagship bxf shape, and a standalone ranking is
    not the step's (see _pick_blocks), so the budget and ordering stay;
    the end-to-end tuner (scripts/tune_kernels.py) is the
    experimentation path."""
    def _heuristic():
        for block_e in (512, 256, 128):
            if block_e > _round_up(E, 128):
                continue
            cb = min(_round_up(C, 8), max(8, max_unroll // max(P * F, 1)
                                          // 8 * 8))
            while True:
                if _vmem_bx(block_e, cb, O, P, Q, F, mid) <= vmem_budget:
                    return block_e, cb
                if cb <= 8:
                    break
                cb = max(8, cb // 2 // 8 * 8)
        # even the smallest block exceeds the model budget: the estimate
        # mirrors the loop's accounting at (128, 8). The flagship bxf
        # shape (P=7, Q=7, F=7, O=64, mid=128) lands here at ~7.5 MiB and
        # runs on the v5e (the benchmark's d4 cell is that shape) — the
        # model is conservative, so estimates within a margin of that
        # validated point stay SILENT (a warning that fires on every
        # healthy flagship run trains users to ignore it). Only
        # genuinely larger shapes
        # get the heads-up that pre-explains a real Mosaic VMEM failure.
        total = _vmem_bx(128, 8, O, P, Q, F, mid)
        validated_silence = 9 * 2 ** 20  # flagship 7.5 MiB + margin
        if total > validated_silence:
            import warnings
            warnings.warn(
                f'fused bxf kernel working-set model ~{total / 2**20:.1f} '
                f'MiB exceeds the {vmem_budget / 2**20:.0f} MiB budget '
                f'even at the smallest block (P={P}, Q={Q}, F={F}, O={O}, '
                f'mid={mid}) and is beyond the production-validated '
                f'~7.5 MiB flagship point; using (128, 8) — a Mosaic '
                f'VMEM error here means: use the unfused path',
                stacklevel=4)
        return 128, 8

    return _consult_table('bxf', (E, C, O, P, Q, F, mid), _heuristic)


def _fused_pairwise_conv_bx_impl(h, w3, b3, basis, x, interpret, precision,
                                 pqf):
    """basis is [E, P*F*Q], flattened per edge in (p, f, q) order (the
    layout get_basis(layout='pfq_flat') produces, pqf = (P, Q, F)): the
    kernel operand bt [P*F*Q, E] is a plain 2D transpose, where the
    structured [E, P, Q, F] form would be a 6D relayout reading a ~60x
    tile-padded HBM buffer."""
    E, mid = h.shape
    P, Q, F = pqf
    assert basis.shape == (E, P * F * Q), (basis.shape, pqf)
    C = x.shape[1]
    O = w3.shape[-1]
    assert w3.shape[1] == C * F, (w3.shape, C, F)
    # the kernels hold one storage width: a basis built from bf16
    # coordinates (the serving engine's activation_dtype) goes up here
    basis, x = basis.astype(jnp.float32), x.astype(jnp.float32)
    if h.dtype == jnp.bfloat16:  # see fused_pairwise_conv (explicit
        # DEFAULT — None would inherit a possibly-fp32 context precision,
        # which Mosaic rejects on bf16 operands)
        precision = jax.lax.Precision.DEFAULT
        if interpret:
            h, w3 = h.astype(jnp.float32), w3.astype(jnp.float32)

    block_e, cb = _pick_blocks_bx(E, C, O, P, Q, F, mid)
    Cp = _round_up(C, cb)
    Ep = _round_up(E, block_e)

    with jax.named_scope('pairwise_layout'):
        ht = h.T                                          # [mid, E]
        bt = basis.T                                      # [(p,f,q), E]
        xt = x.transpose(1, 2, 0).reshape(C * Q, E)
        w3t = w3.reshape(mid, C * F * O).T                # [(c,f,o), mid]
        b3t = _bias_column(b3, C * F, O, Cp * F)
        if Cp != C:
            xt = jnp.pad(xt, ((0, (Cp - C) * Q), (0, 0)))
            w3t = jnp.pad(w3t, ((0, (Cp - C) * F * O), (0, 0)))
        if Ep != E:
            ht = jnp.pad(ht, ((0, 0), (0, Ep - E)))
            bt = jnp.pad(bt, ((0, 0), (0, Ep - E)))
            xt = jnp.pad(xt, ((0, 0), (0, Ep - E)))

    n_e, n_c = Ep // block_e, Cp // cb

    outt = pl.pallas_call(
        functools.partial(_fwd_bx_kernel, P=P, O=O, Q=Q, F=F, cb=cb,
                          precision=precision),
        grid=(n_e, n_c),
        in_specs=[
            pl.BlockSpec((mid, block_e), lambda e, c: (0, e),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cb * F * O, mid), lambda e, c: (c, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cb * F * O, 1), lambda e, c: (c, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P * F * Q, block_e), lambda e, c: (0, e),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cb * Q, block_e), lambda e, c: (c, e),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((P * O, block_e), lambda e, c: (0, e),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((P * O, Ep), jnp.float32),
        interpret=interpret,
        name='fused_pairwise_conv_bxf',
    )(ht, w3t, b3t, bt, xt)

    with jax.named_scope('pairwise_layout'):
        return outt.reshape(P, O, Ep).transpose(2, 0, 1)[:E]


@functools.lru_cache(maxsize=None)
def _bxf_partitioned(pqf, interpret, precision):
    return _make_partitioned(
        lambda h, w3, b3, basis, x: _fused_pairwise_conv_bx_impl(
            h, w3, b3, basis, x, interpret, precision, pqf=pqf),
        rule='e m, m i o, i o, e z, e c q -> e p o',
        need_repl=('m', 'i', 'z', 'c', 'q'),
        arg_specs=lambda P_, e, o: (P_(e, None), P_(None, None, o),
                                    P_(None, o),
                                    P_(e, None), P_(e, None, None)),
        result_specs=lambda P_, e, o: (P_(e, None, o),))


@functools.partial(jax.jit,
                   static_argnames=('pqf', 'interpret', 'precision'))
def fused_pairwise_conv_bxf(h: jnp.ndarray, w3: jnp.ndarray,
                            basis_flat: jnp.ndarray, x: jnp.ndarray,
                            pqf: tuple, b3: jnp.ndarray = None,
                            interpret: bool = False,
                            precision=None) -> jnp.ndarray:
    """Basis-fused forward: h [E, mid], w3 [mid, C*F, O] (i=(c,f)
    c-major), basis_flat [E, P*F*Q] in (p, f, q) order (get_basis
    layout='pfq_flat'), x [E, C, Q], pqf = (P, Q, F) static ints, b3
    [C*F, O] (optional, zeros when None) -> out [E, P, O] (f32).

    Equals fused_pairwise_conv(h, w3, einsum('epqf,ecq->e p (c f)', ...),
    b3) without ever materializing that V2 tensor in HBM. The flat form
    keeps the HBM basis buffer ~60x smaller at num_degrees=4 than the
    structured [.., P, Q, F] one, which tile-pads its two small odd minor
    axes to (8, 128); the flat form pads one axis to the next 128
    multiple. Partitions over sharded edge/output-channel axes (see the
    SPMD rules above)."""
    if b3 is None:
        b3 = jnp.zeros(w3.shape[1:], jnp.float32)
    return _bxf_partitioned(tuple(pqf), interpret, precision)(
        h, w3, b3, basis_flat, x)


# --------------------------------------------------------------------- #
# fused backward, V2 given (the plain forward's; the basis-fused
# forward's own backward follows further down)
# --------------------------------------------------------------------- #
# Cotangents of out[e,P,o] = sum_{if} V2[e,P,if] R[e,if,o],
# R = H W3 + B3:
#   dV2[e,P,if] = sum_o  g[e,P,o]  R[e,if,o]
#   dR [e,if,o] = sum_P  V2[e,P,if] g[e,P,o]
#   dH [e,m]    = sum_{if,o} dR[e,if,o] W3[m,if,o]
#   dW3[m,if,o] = sum_e  H[e,m] dR[e,if,o]
#   dB3[if,o]   = sum_e  dR[e,if,o]
# Kernel A (grid (n_if, n_e), e inner): rT matmul (+bias) -> dV2 rows
# (sublane reduce); dR rows stacked i-major in a VMEM scratch -> dW3 (one
# matmul over the stack) and dB3 (one lane reduce), both accumulated over
# the inner edge axis.
# Kernel B (grid (n_e, n_if), f inner): the same stacked dR (no rT matmul
# needed) -> dH (one matmul, contraction bif*O wide) accumulated over the
# inner if axis.
# A per-i form (bif dots, each with an O-wide side) feeds a 128 x 128 MXU
# O/128 of its rate: at O = 24 it took 1.6-2x the stacked form's time on
# the v5e, where the VPU side (the FMAs that build dR, kernel A's dV2
# reductions and one-row stores) now sets the pace (PERF.md, PR 25).


def _bwd_a_kernel(ht_ref, h_ref, w3t_ref, b3t_ref, v2t_ref, gt_ref,
                  dv2_ref, dw3_ref, db3_ref, dr_ref, *, P, O, bif, precision,
                  mxu_dtype):
    e = pl.program_id(1)
    # R must include the bias here: dV2 = g . R
    rt = jax.lax.dot_general(
        w3t_ref[:], ht_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32) + b3t_ref[:]  # [bif*O, E_b]
    g = gt_ref[:]                                    # [P*O, E_b]
    for i in range(bif):
        r_i = rt[i * O:(i + 1) * O, :]               # [O, E_b]
        dr_i = None
        for p in range(P):
            gp = g[p * O:(p + 1) * O, :]             # [O, E_b]
            # dV2[(p, i)] = sum_o g[p,o,:] * r[i,o,:]
            dv2_ref[p, i:i + 1, :] = jnp.sum(
                gp * r_i, axis=0, keepdims=True).astype(dv2_ref.dtype)
            term = v2t_ref[p, i:i + 1, :] * gp       # [O, E_b]
            dr_i = term if dr_i is None else dr_i + term
        dr_ref[i * O:(i + 1) * O, :] = dr_i
    dr = dr_ref[:]                                   # [bif*O, E_b], f32
    hp = h_ref[:]
    # dW3 rows of the whole if-chunk in ONE dot, [bif*O, E_b] @ [E_b, mid]
    # (per-i dots would hand the MXU O rows at a time), accumulated over
    # the inner edge grid axis (consecutive revisits). dR is rounded to
    # the dtype h arrived in (mxu_dtype; the second cast is the exact
    # upcast of interpret mode, nothing under Mosaic)
    upd = jax.lax.dot_general(
        dr.astype(mxu_dtype).astype(hp.dtype), hp,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)          # [bif*O, mid]
    # dB3 rows: sum the f32 dR over edges (lane reduction), same revisit
    # accumulation. Padded edge lanes contribute zeros (v2/g padded).
    db3_upd = jnp.sum(dr, axis=1, keepdims=True)     # [bif*O, 1]

    @pl.when(e == 0)
    def _():
        dw3_ref[:] = upd.astype(dw3_ref.dtype)
        db3_ref[:] = db3_upd.astype(db3_ref.dtype)

    @pl.when(e > 0)
    def _():
        dw3_ref[:] = dw3_ref[:] + upd.astype(dw3_ref.dtype)
        db3_ref[:] = db3_ref[:] + db3_upd.astype(db3_ref.dtype)


def _bwd_b_kernel(w3f_ref, v2t_ref, gt_ref, dh_ref, dr_ref, *, P, O, bif,
                  precision, mxu_dtype):
    f = pl.program_id(1)
    g = gt_ref[:]                                    # [P*O, E_b]
    for i in range(bif):
        dr_i = None
        for p in range(P):
            term = v2t_ref[p, i:i + 1, :] * g[p * O:(p + 1) * O, :]
            dr_i = term if dr_i is None else dr_i + term
        dr_ref[i * O:(i + 1) * O, :] = dr_i
    w3f = w3f_ref[0]                                 # [mid, bif*O]
    # dH partial of the whole if-chunk in ONE dot, [mid, bif*O] @
    # [bif*O, E_b]: the contraction is bif*O wide, not O
    acc = jax.lax.dot_general(
        w3f, dr_ref[:].astype(mxu_dtype).astype(w3f.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)          # [mid, E_b]

    @pl.when(f == 0)
    def _():
        dh_ref[:] = acc.astype(dh_ref.dtype)

    @pl.when(f > 0)
    def _():
        dh_ref[:] = dh_ref[:] + acc.astype(dh_ref.dtype)


def _fused_pairwise_conv_bwd_impl(h, w3, b3, v2, g, interpret, precision):
    # The MXU operands keep the dtype h arrives in, as in the forward:
    # bf16 radial operands (radial_bf16) feed the three dots as they are,
    # with dR rounded to bf16 in the tile; f32 h leaves everything f32 at
    # the caller's precision. g, the reductions (dV2, dB3) and every
    # accumulator are f32 either way
    E, mid = h.shape
    _, IF, O = w3.shape
    P = v2.shape[1]

    block_e, block_if = _pick_blocks(E, IF, O, P, mid, bwd=True)
    Ep, IFp = _round_up(E, block_e), _round_up(IF, block_if)
    n_e, n_if = Ep // block_e, IFp // block_if

    mxu_dtype = jnp.bfloat16 if h.dtype == jnp.bfloat16 else jnp.float32
    if mxu_dtype == jnp.bfloat16:
        # explicit DEFAULT, see fused_pairwise_conv: None would inherit a
        # possibly-fp32 context precision, which Mosaic rejects on bf16
        precision = jax.lax.Precision.DEFAULT
    # CPU interpret can't dispatch BF16xBF16=F32 dots: h and w3 go up
    # (exactly) there, and the kernels still round dR to mxu_dtype
    rdt = jnp.float32 if interpret else mxu_dtype

    with jax.named_scope('pairwise_layout'):
        h, w3 = h.astype(rdt), w3.astype(rdt)
        g = g.astype(jnp.float32)
        ht, w3t, v2t, gt = _to_lanes(h, w3, v2, g)
        b3t = _bias_column(b3, IF, O, IFp)
        h_p, w3f = h, w3.reshape(mid, IF * O)
        if Ep != E:
            ht = jnp.pad(ht, ((0, 0), (0, Ep - E)))
            h_p = jnp.pad(h_p, ((0, Ep - E), (0, 0)))
            v2t = jnp.pad(v2t, ((0, 0), (0, 0), (0, Ep - E)))
            gt = jnp.pad(gt, ((0, 0), (0, Ep - E)))
        if IFp != IF:
            w3t = jnp.pad(w3t, ((0, (IFp - IF) * O), (0, 0)))
            w3f = jnp.pad(w3f, ((0, 0), (0, (IFp - IF) * O)))
            v2t = jnp.pad(v2t, ((0, 0), (0, IFp - IF), (0, 0)))
        # kernel B's w3: the if-chunk axis rides a leading block-1 dim so
        # the (mid, bif*O) tail covers its full array dims (Mosaic
        # block-shape rule)
        w3f3 = w3f.reshape(mid, n_if, block_if * O).transpose(1, 0, 2)

    # the if-chunk's dR, stacked i-major: the one operand of each kernel's
    # large dot, never in HBM
    dr_scratch = pltpu.VMEM((block_if * O, block_e), jnp.float32)

    # kernel A: dV2 + dW3 + dB3 (accumulate over inner e axis)
    dv2t, dw3t, db3t = pl.pallas_call(
        functools.partial(_bwd_a_kernel, P=P, O=O, bif=block_if,
                          precision=precision, mxu_dtype=mxu_dtype),
        grid=(n_if, n_e),
        in_specs=[
            pl.BlockSpec((mid, block_e), lambda f, e: (0, e),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_e, mid), lambda f, e: (e, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_if * O, mid), lambda f, e: (f, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_if * O, 1), lambda f, e: (f, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P, block_if, block_e), lambda f, e: (0, f, e),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P * O, block_e), lambda f, e: (0, e),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((P, block_if, block_e), lambda f, e: (0, f, e),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_if * O, mid), lambda f, e: (f, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_if * O, 1), lambda f, e: (f, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, IFp, Ep), jnp.float32),
            jax.ShapeDtypeStruct((IFp * O, mid), jnp.float32),
            jax.ShapeDtypeStruct((IFp * O, 1), jnp.float32),
        ],
        scratch_shapes=[dr_scratch],
        interpret=interpret,
        name='fused_pairwise_conv_bwd_a',
    )(ht, h_p, w3t, b3t, v2t, gt)

    # kernel B: dH (accumulate over inner if axis; no matmul with w3T
    # needed — dR comes straight from v2/g)
    dht = pl.pallas_call(
        functools.partial(_bwd_b_kernel, P=P, O=O, bif=block_if,
                          precision=precision, mxu_dtype=mxu_dtype),
        grid=(n_e, n_if),
        in_specs=[
            pl.BlockSpec((1, mid, block_if * O), lambda e, f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P, block_if, block_e), lambda e, f: (0, f, e),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P * O, block_e), lambda e, f: (0, e),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((mid, block_e), lambda e, f: (0, e),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mid, Ep), jnp.float32),
        scratch_shapes=[dr_scratch],
        interpret=interpret,
        name='fused_pairwise_conv_bwd_b',
    )(w3f3, v2t, gt)

    with jax.named_scope('pairwise_layout'):
        dh = dht.T[:E]
        dw3 = dw3t.reshape(IFp, O, mid).transpose(2, 0, 1)[:, :IF]
        dv2 = dv2t.transpose(2, 0, 1)[:E, :, :IF]
        db3 = db3t.reshape(IFp, O)[:IF]
    return dh, dw3, dv2, db3


def _bwd_psums(outs, e, o):
    dh, dw3, dv2, db3 = outs
    # dW3/dB3 sum over edges (sharded e axes); dH/dV2 sum over the output
    # channels (sharded o axes under tensor parallelism)
    if _axis_tuple(e):
        dw3 = jax.lax.psum(dw3, _axis_tuple(e))
        db3 = jax.lax.psum(db3, _axis_tuple(e))
    if _axis_tuple(o):
        dh = jax.lax.psum(dh, _axis_tuple(o))
        dv2 = jax.lax.psum(dv2, _axis_tuple(o))
    return dh, dw3, dv2, db3


@functools.lru_cache(maxsize=None)
def _bwd_partitioned(interpret, precision):
    return _make_partitioned(
        lambda h, w3, b3, v2, g: _fused_pairwise_conv_bwd_impl(
            h, w3, b3, v2, g, interpret, precision),
        rule='e m, m k o, k o, e p k, e p o -> e m, m k o, e p k, k o',
        need_repl=('m', 'k'),
        arg_specs=lambda P_, e, o: (P_(e, None), P_(None, None, o),
                                    P_(None, o),
                                    P_(e, None, None), P_(e, None, o)),
        result_specs=lambda P_, e, o: (P_(e, None), P_(None, None, o),
                                       P_(e, None, None), P_(None, o)),
        psum_fn=_bwd_psums)


@functools.partial(jax.jit, static_argnames=('interpret', 'precision'))
def fused_pairwise_conv_bwd(h: jnp.ndarray, w3: jnp.ndarray,
                            v2: jnp.ndarray, g: jnp.ndarray,
                            b3: jnp.ndarray = None,
                            interpret: bool = False, precision=None):
    """Backward of fused_pairwise_conv: returns (dh, dw3, dv2, db3),
    all f32.

    h [E, mid], w3 [mid, IF, O], v2 [E, P, IF], g [E, P, O], b3 [IF, O]
    (optional, zeros when None — b3 feeds dV2 = g . R with R including
    the bias; db3 itself is bias-independent: sum_e dR).
    The dtype of h decides the MXU operands, as in the forward: bf16 h
    and w3 (radial_bf16) enter the three dots as they are, with dR
    rounded to bf16 in the tile, one pass, f32 accumulation; f32 h keeps
    every operand f32 at `precision`. g, dV2, dB3 and every accumulator
    are f32 either way.
    Partitions over sharded edge/output-channel axes with the dW3/dB3
    (and, under tp, dH/dV2) partial sums reduced in the partition body.
    """
    if b3 is None:
        b3 = jnp.zeros(w3.shape[1:], jnp.float32)
    return _bwd_partitioned(interpret, precision)(h, w3, b3, v2, g)


# --------------------------------------------------------------------- #
# basis-fused backward (V2, dV2 -> dx never touch HBM or XLA)
# --------------------------------------------------------------------- #
# The backward of fused_pairwise_conv_bxf: kernels A and B above with the
# V2 block they load replaced by one they build in a VMEM scratch from B
# and x, and with A also folding the dV2 block it writes into dx,
#   V2[(p, f), c, :]  = sum_q B[(p, f), q, :] x[q, c, :]
#   dx[q, c, :]       = sum_(p, f) B[(p, f), q, :] dV2[(p, f), c, :].
# Both put the chunk's CHANNELS on sublanes and broadcast a row of B over
# them, so a tile of 8 channels costs 2Q - 1 (2 P F) VPU operations and
# no sublane reduction: the forward's form, one [Q, E_b] product and a
# reduction per (p, c, f), measured 1.8 ns a row in these kernels where
# their whole dR body takes 2.4 (PERF.md, PR 28). The if-chunk is whole
# channels (cb of them, cb*F i's), so a dx block is finished inside one
# program. dbasis sums over channels, A's OUTER grid axis, so it stays
# outside: A writes dV2 as before and the wrapper reduces it against x in
# XLA, which drops both when nothing asks for the basis' cotangent.
#
# Every loop is ROLLED but the P (or Q) body inside it and one pass of
# at most _O_PER_PASS o's, so the traced kernel does not grow with cb, and
# with O only up to that constant. What a loop indexes is a leading,
# untiled axis, one sublane row, or a tile-aligned row or lane offset:
#   bt  [P*F, Q, E]    B, one [Q, E_b] slab per (p, f)
#   xt  [Q, C, E]      gathered features; dxt the same
#   dv2 [P*F, C, E]    A's output; the V2 scratch is one block of this
#   gt  [P*O, E]       the cotangent, one row per (p, o), O padded to a
#                      multiple of 8 by the wrapper (zero rows of w3, b3
#                      and g), so that every row offset below is a tile's
#   B's dR scratch [cb*F*O, E_b], rows of i = (c, f) at i*O: one V2 row
#                      times a [O, E_b] slab of g per (p, i), and the
#                      columns of w3f3 in w3's own order
#   A's R, dR scratches [F*(cb/8)*O*8, E_b], rows ordered (f, t, o, c)
#                      over the chunk's tiles t of 8 channels, c fastest:
#                      the 8 channels of one (f, t, o) are ONE sublane
#                      tile, as V2 and dv2 hold them for one (p, f). So
#                      dV2's sum over o accumulates ACROSS tiles, a row of
#                      g broadcast over the channels, with no sublane
#                      reduction and no one-row store, and dR is built
#                      from whole V2 tiles. w3t, b3t, dw3 and db3 take
#                      that order chunk by chunk (_stack_rows,
#                      _unstack_rows). B has no sum over o and gains
#                      nothing from the order (PERF.md, PR 30), so it
#                      keeps its own


def _contract_over_q(bt_ref, xt_ref, v2_ref, Q):
    """v2_ref[(p, f)] = sum_q B[(p, f), q] x[q], all cb channels at once."""
    def one(pf, carry):
        b = bt_ref[pf]                               # [Q, E_b]
        acc = None
        for q in range(Q):
            term = b[q:q + 1, :] * xt_ref[q]         # [cb, E_b]
            acc = term if acc is None else acc + term
        v2_ref[pf] = acc
        return carry

    jax.lax.fori_loop(0, v2_ref.shape[0], one, 0)


def _row_chunks(S, rows=512):
    """The R / dR stack in slices of at most `rows` rows: each of the
    kernels' dots runs slice by slice, still full tiles for the MXU, so
    what the compiler holds beside the blocks and scratches (a dot's
    result, dR rounded for the MXU, the lane-padded dB3 column) is
    `rows` tall, not cb*F*O."""
    return [slice(s0, min(S, s0 + rows)) for s0 in range(0, S, rows)]


def _stack_rows(a, cb, F):
    """[Cp*F, O, ...], rows i = c*F + f, to the kernels' stacks
    [n_c, F*O*cb, ...]: chunk n holds channels n*cb.., its rows ordered
    (f, t, o, c) over the chunk's tiles t of 8 channels, c fastest."""
    O = a.shape[1]
    a = a.reshape(-1, cb // 8, 8, F, O, *a.shape[2:])
    return jnp.moveaxis(a, (3, 1, 4, 2), (1, 2, 3, 4)).reshape(
        a.shape[0], F * O * cb, *a.shape[5:])


def _unstack_rows(s, cb, F, O):
    """The inverse of _stack_rows: [n_c, F*O*cb, ...] to [Cp*F, O, ...]."""
    s = s.reshape(s.shape[0], F, cb // 8, O, 8, *s.shape[2:])
    return jnp.moveaxis(s, (1, 2, 3, 4), (3, 1, 4, 2)).reshape(
        -1, O, *s.shape[5:])


# o's per pass of kernel A's rolled o loop, written out in the pass: the
# cell's O = 24 is one pass and its O = 64 two. Alone on the chip 8 a pass
# cost the (3,3) launch 7% over 24 or 32 (the pass's first loads are not
# hidden), and the traced body grows by 5 P lines an o (PERF.md, PR 30)
_O_PER_PASS = 32


def _dr_rows(c, f, F, O):
    return pl.ds(pl.multiple_of((c * F + f) * O, 8), O)


def _stack_dv2_dr(gt_ref, v2_ref, r_ref, dr_ref, dv2_ref, *, P, O, F, cb):
    """Kernel A's vector work over its stacks, one tile of 8 channels and
    128 lanes (up to 512 where P is small) at a time:
      dR[(f, t, o)]   = sum_p V2[(p, f), t] g[p, o]
      dV2[(p, f), t]  = sum_o g[p, o] R[(f, t, o)]
    the second accumulated over the same o loop in P registers and stored
    once. A row of g reaches the tile's 8 channels by a stride-0 load:
    the one form of a single-row load the compiler spreads over sublanes
    at no vector operation, and it wants a static row, so the rolled o
    loop steps ONE view of gt by whole tiles and the rows inside it are
    Python's. Each g row is used for both products where it is loaded
    and each dR tile stored where it is finished, so that nothing but
    V2[p] and dV2[p] lives across an o."""
    U = min(_O_PER_PASS, O)
    n_t = cb // 8
    # lanes of one pass: the P tiles of V2 and the P of dV2 it carries
    # stay within a quarter of the 64 vector registers, and where P is
    # small the wider tile gives its one chain of adds others to overlap
    n_l = dr_ref.shape[1] // 128
    lw = 128 * max(k for k in (1, 2, 4) if n_l % k == 0
                   and (k == 1 or k * P <= 8))

    def tile(lanes, t, f):
        ch = pl.ds(pl.multiple_of(t * 8, 8), 8)
        v2 = [v2_ref[p * F + f, ch, lanes] for p in range(P)]

        def some(o0, n, dv2):
            """o0 .. o0 + n of this tile; dv2 [p] so far"""
            dv2 = list(dv2)
            base = ((f * n_t + t) * O + o0) * 8
            # one view for all P (o0 is a multiple of 8): row p*O + u of
            # it is g[p, o0 + u]
            g = gt_ref.at[pl.ds(pl.multiple_of(o0, 8), (P - 1) * O + n),
                          lanes]
            for u in range(n):
                rows = pl.ds(pl.multiple_of(base + u * 8, 8), 8)
                r = r_ref[rows, lanes]
                dr = None
                for p in range(P):
                    gb = g[p * O + u:p * O + u + 1, :]
                    term = v2[p] * gb
                    dr = term if p == 0 else dr + term
                    dv2[p] = dv2[p] + gb * r
                dr_ref[rows, lanes] = dr
            return tuple(dv2)

        dv2 = (jnp.zeros((8, lw), jnp.float32),) * P
        dv2 = jax.lax.fori_loop(0, O // U,
                                lambda ou, dv2: some(ou * U, U, dv2), dv2)
        if O % U:
            dv2 = some(O - O % U, O % U, dv2)
        for p in range(P):
            dv2_ref[p * F + f, ch, lanes] = dv2[p]

    def lane_group(l, carry):
        lanes = pl.ds(pl.multiple_of(l * lw, 128), lw)

        def tiles(t, carry):
            def fs(f, carry):
                tile(lanes, t, f)
                return carry
            return jax.lax.fori_loop(0, F, fs, carry)
        return jax.lax.fori_loop(0, n_t, tiles, carry)

    jax.lax.fori_loop(0, dr_ref.shape[1] // lw, lane_group, 0)


def _dx_from_dv2(bt_ref, dv2_ref, dx_ref, *, PF, Q, cb):
    """dx[q, c] = sum_(p, f) B[(p, f), q] dV2[(p, f), c]: the Q sums of a
    group of channel tiles carried in registers over ONE rolled loop of
    (p, f), so that dV2 is read once and a pass has Q products to overlap
    (Q loops of one product each ran at the latency of a load, a multiply
    and an add: 11 bundles for 8 vector operations, PERF.md, PR 30)."""
    n_t = cb // 8
    # tiles per group: Q accumulators of k tiles within half the registers
    k = max(d for d in range(1, n_t + 1)
            if n_t % d == 0
            and d * (Q + 1) * (dx_ref.shape[2] // 128) <= 32)

    def group(tg, carry):
        ch = pl.ds(pl.multiple_of(tg * (8 * k), 8), 8 * k)

        def add(pf, accs):
            d = dv2_ref[pf, ch, :]
            return tuple(acc + bt_ref[pf, q:q + 1, :] * d
                         for q, acc in enumerate(accs))

        zero = jnp.zeros((8 * k, dx_ref.shape[2]), jnp.float32)
        accs = jax.lax.fori_loop(0, PF, add, (zero,) * Q)
        for q in range(Q):
            dx_ref[q, ch, :] = accs[q]
        return carry

    jax.lax.fori_loop(0, n_t // k, group, 0)


def _bwd_bxf_a_kernel(ht_ref, h_ref, w3t_ref, b3t_ref, bt_ref, xt_ref,
                      gt_ref, dv2_ref, dw3_ref, db3_ref, dx_ref, r_ref,
                      dr_ref, v2_ref, *, P, O, Q, F, cb, precision,
                      mxu_dtype):
    e = pl.program_id(1)
    hb = ht_ref[:]
    # R of the whole c-chunk, bias included (dV2 = g . R): [F*O*cb, E_b]
    for rows in _row_chunks(r_ref.shape[0]):
        r_ref[rows, :] = jax.lax.dot_general(
            w3t_ref[rows, :], hb,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32) + b3t_ref[rows, :]
    _contract_over_q(bt_ref, xt_ref, v2_ref, Q)
    _stack_dv2_dr(gt_ref, v2_ref, r_ref, dr_ref, dv2_ref, P=P, O=O, F=F,
                  cb=cb)

    _dx_from_dv2(bt_ref, dv2_ref, dx_ref, PF=P * F, Q=Q, cb=cb)

    hp = h_ref[:]
    # as _bwd_a_kernel: dW3 rows by full-tile dots over the stacked dR,
    # dB3 by lane reductions, both accumulated over the inner edge axis
    for rows in _row_chunks(dr_ref.shape[0]):
        dr = dr_ref[rows, :]
        upd = jax.lax.dot_general(
            dr.astype(mxu_dtype).astype(hp.dtype), hp,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)      # [rows, mid]
        db3_upd = jnp.sum(dr, axis=1, keepdims=True)  # [rows, 1]

        @pl.when(e == 0)
        def _(rows=rows, upd=upd, db3_upd=db3_upd):
            dw3_ref[rows, :] = upd
            db3_ref[rows, :] = db3_upd

        @pl.when(e > 0)
        def _(rows=rows, upd=upd, db3_upd=db3_upd):
            dw3_ref[rows, :] = dw3_ref[rows, :] + upd
            db3_ref[rows, :] = db3_ref[rows, :] + db3_upd


def _bwd_bxf_b_kernel(w3f_ref, bt_ref, xt_ref, gt_ref, dh_ref, dr_ref,
                      v2_ref, *, P, O, Q, F, cb, precision, mxu_dtype):
    c0 = pl.program_id(1)
    _contract_over_q(bt_ref, xt_ref, v2_ref, Q)

    def channel(c, carry):
        for f in range(F):
            dr_i = None
            for p in range(P):
                term = v2_ref[p * F + f, pl.ds(c, 1), :] \
                    * gt_ref[p * O:(p + 1) * O, :]
                dr_i = term if dr_i is None else dr_i + term
            dr_ref[_dr_rows(c, f, F, O), :] = dr_i
        return carry

    jax.lax.fori_loop(0, cb, channel, 0)
    acc = None
    for rows in _row_chunks(dr_ref.shape[0]):
        w3f = w3f_ref[0, :, rows]                    # [mid, rows]
        part = jax.lax.dot_general(
            w3f, dr_ref[rows, :].astype(mxu_dtype).astype(w3f.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)      # [mid, E_b]
        acc = part if acc is None else acc + part

    @pl.when(c0 == 0)
    def _():
        dh_ref[:] = acc

    @pl.when(c0 > 0)
    def _():
        dh_ref[:] = dh_ref[:] + acc


def _vmem_bxf_bwd(be: int, cb: int, O: int, P: int, Q: int, F: int,
                  mid: int) -> int:
    """Bytes kernel A of the basis-fused backward holds at (be, cb), the
    larger of the two, at float32: the blocks that move with the edge
    axis twice (double buffering), the weight-shaped ones (w3t, b3t, dw3,
    db3: one buffer each, they change with the outer axis only) and the
    R, dR and V2 scratches once, and what one slice of _row_chunks leaves
    beside them. O as padded, cb a multiple of 8; Q pads to the sublane
    tile in B's slabs; the [S, 1] columns pad lanes to 128."""
    S = cb * F * O
    moving = (2 * mid * be                           # ht, h
              + P * F * _round_up(Q, 8) * be         # bt
              + 2 * Q * cb * be                      # xt, dxt
              + P * O * be + P * F * cb * be)        # gt; dv2
    held = 2 * S * mid + 2 * S * 128 + 2 * S * be + P * F * cb * be
    return 4 * (2 * moving + held + 4 * min(S, 512) * max(be, mid))


def _pick_blocks_bxf_bwd(E: int, C: int, O: int, P: int, Q: int, F: int,
                         mid: int, vmem_budget: int = 18 * 2 ** 20):
    """(block_e, cb) of the basis-fused backward: the widest edge block
    at which the model holds 8 channels, then as many as fit. Channels
    ride sublanes, so cb is a multiple of the sublane tile, 8, and C
    counts up to one. The heuristic alone decides, as for the plain
    backward; the pick is recorded under a kind of its own, so a step's
    consult log counts the launches that took this form.

    The budget is in the model's bytes, which are not the compiler's:
    with float32 operands and every output kept, the v5e compiler took
    each (block_e, cb) tried at the cell's pairs (C 64; O 24 and 64) that
    the model puts at 19.2 MiB or less, refused some from 19.5 on and all
    from 21.5 (deviceless compile, PR 28); the picks at O = 24 are the
    ones a whole step ran with on the chip."""
    C = _round_up(C, 8)

    def _heuristic():
        for block_e in (512, 256, 128):
            if block_e > _round_up(E, 128):
                continue
            cb = C
            while cb > 8 and _vmem_bxf_bwd(block_e, cb, O, P, Q, F,
                                           mid) > vmem_budget:
                cb = _round_up(cb // 2, 8)
            if _vmem_bxf_bwd(block_e, cb, O, P, Q, F, mid) <= vmem_budget:
                return block_e, cb
        return 128, 8

    from . import tuning
    blocks = _heuristic()
    tuning.record_consult('bxf_bwd', (E, C, O, P, Q, F, mid), 'float32',
                          'heuristic', blocks)
    return blocks


def _fused_pairwise_conv_bwd_bxf_impl(h, w3, b3, basis, x, g, pqf,
                                      interpret, precision):
    # dtypes as _fused_pairwise_conv_bwd_impl: h decides the MXU operands
    # of the three dots; the basis contraction, dV2, dx, dB3 and every
    # accumulator are f32 on the VPU
    P, Q, F = pqf
    E, mid = h.shape
    C = x.shape[1]
    O = w3.shape[-1]
    assert basis.shape == (E, P * F * Q), (basis.shape, pqf)
    assert w3.shape[1] == C * F, (w3.shape, C, F)
    Op = _round_up(O, 8)

    mxu_dtype = jnp.bfloat16 if h.dtype == jnp.bfloat16 else jnp.float32
    if mxu_dtype == jnp.bfloat16:
        precision = jax.lax.Precision.DEFAULT  # see fused_pairwise_conv
    rdt = jnp.float32 if interpret else mxu_dtype
    # one storage width, as the forward
    basis, x = basis.astype(jnp.float32), x.astype(jnp.float32)

    block_e, cb = _pick_blocks_bxf_bwd(E, C, Op, P, Q, F, mid)
    Ep, Cp = _round_up(E, block_e), _round_up(C, cb)
    n_e, n_c = Ep // block_e, Cp // cb
    S = cb * F * Op

    with jax.named_scope('pairwise_layout'):
        h, w3 = h.astype(rdt), w3.astype(rdt)
        g = g.astype(jnp.float32)
        # zero output channels up to the sublane tile, zero input
        # channels up to the chunk, zero edges up to the block: each
        # contributes nothing to any sum and its own rows are cut below
        w3 = jnp.pad(w3, ((0, 0), (0, (Cp - C) * F), (0, Op - O)))
        b3 = jnp.pad(b3.astype(jnp.float32),
                     ((0, (Cp - C) * F), (0, Op - O)))
        pad_e = (0, Ep - E)
        ht = jnp.pad(h.T, ((0, 0), pad_e))                    # [mid, E]
        h_p = jnp.pad(h, (pad_e, (0, 0)))
        gt = jnp.pad(g, ((0, 0), (0, 0), (0, Op - O))).transpose(
            1, 2, 0).reshape(P * Op, E)
        gt = jnp.pad(gt, ((0, 0), pad_e))
        bt = jnp.pad(basis.T.reshape(P * F, Q, E),
                     ((0, 0), (0, 0), pad_e))
        xt = jnp.pad(x.transpose(2, 1, 0),
                     ((0, 0), (0, Cp - C), pad_e))            # [Q, Cp, Ep]
        # B's dR stack is rows (c, f, o), A's tiles (f, t, o) of 8 channels
        w3f3 = w3.reshape(mid, n_c, S).transpose(1, 0, 2)
        w3t = _stack_rows(w3.transpose(1, 2, 0), cb, F).reshape(
            n_c * S, mid)
        b3t = _stack_rows(b3, cb, F).reshape(n_c * S, 1)

    def vmem(shape, index_map, **kw):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM, **kw)

    once = dict(pipeline_mode=pl.Buffered(1))
    s_e = jax.ShapeDtypeStruct
    rows = pltpu.VMEM((S, block_e), jnp.float32)
    v2 = pltpu.VMEM((P * F, cb, block_e), jnp.float32)
    statics = dict(P=P, O=Op, Q=Q, F=F, cb=cb, precision=precision,
                   mxu_dtype=mxu_dtype)

    dv2t, dw3t, db3t, dxt = pl.pallas_call(
        functools.partial(_bwd_bxf_a_kernel, **statics),
        grid=(n_c, n_e),
        in_specs=[
            vmem((mid, block_e), lambda c, e: (0, e)),
            vmem((block_e, mid), lambda c, e: (e, 0)),
            vmem((S, mid), lambda c, e: (c, 0), **once),
            vmem((S, 1), lambda c, e: (c, 0), **once),
            vmem((P * F, Q, block_e), lambda c, e: (0, 0, e)),
            vmem((Q, cb, block_e), lambda c, e: (0, c, e)),
            vmem((P * Op, block_e), lambda c, e: (0, e)),
        ],
        out_specs=[
            vmem((P * F, cb, block_e), lambda c, e: (0, c, e)),
            vmem((S, mid), lambda c, e: (c, 0), **once),
            vmem((S, 1), lambda c, e: (c, 0), **once),
            vmem((Q, cb, block_e), lambda c, e: (0, c, e)),
        ],
        out_shape=[
            s_e((P * F, Cp, Ep), jnp.float32),
            s_e((Cp * F * Op, mid), jnp.float32),
            s_e((Cp * F * Op, 1), jnp.float32),
            s_e((Q, Cp, Ep), jnp.float32),
        ],
        scratch_shapes=[rows, rows, v2],
        interpret=interpret,
        name='fused_pairwise_conv_bwd_a',
    )(ht, h_p, w3t, b3t, bt, xt, gt)

    dht = pl.pallas_call(
        functools.partial(_bwd_bxf_b_kernel, **statics),
        grid=(n_e, n_c),
        in_specs=[
            vmem((1, mid, S), lambda e, c: (c, 0, 0)),
            vmem((P * F, Q, block_e), lambda e, c: (0, 0, e)),
            vmem((Q, cb, block_e), lambda e, c: (0, c, e)),
            vmem((P * Op, block_e), lambda e, c: (0, e)),
        ],
        out_specs=vmem((mid, block_e), lambda e, c: (0, e)),
        out_shape=s_e((mid, Ep), jnp.float32),
        scratch_shapes=[rows, v2],
        interpret=interpret,
        name='fused_pairwise_conv_bwd_b',
    )(w3f3, bt, xt, gt)

    with jax.named_scope('pairwise_layout'):
        dh = dht.T[:E]
        dw3 = _unstack_rows(dw3t.reshape(n_c, S, mid), cb, F, Op).transpose(
            2, 0, 1)[:, :C * F, :O]
        db3 = _unstack_rows(db3t.reshape(n_c, S), cb, F, Op)[:C * F, :O]
        dx = dxt.transpose(2, 1, 0)[:E, :C]
    with jax.named_scope('basis_contract'):
        # dbasis[(p,f), q, e] = sum_c dV2[(p,f), c, e] x[q, c, e], on the
        # kernels' own layouts: one multiply-reduce with E on lanes
        dbt = jnp.sum(dv2t[:, None] * xt[None], axis=2)
        dbasis = dbt.reshape(P * F * Q, Ep).T[:E]
    return dh, dw3, db3, dbasis, dx


def _bwd_bxf_psums(outs, e, o):
    dh, dw3, db3, dbasis, dx = outs
    # as _bwd_psums; dbasis and dx are linear in dV2
    if _axis_tuple(e):
        dw3 = jax.lax.psum(dw3, _axis_tuple(e))
        db3 = jax.lax.psum(db3, _axis_tuple(e))
    if _axis_tuple(o):
        dh, dbasis, dx = (jax.lax.psum(t, _axis_tuple(o))
                          for t in (dh, dbasis, dx))
    return dh, dw3, db3, dbasis, dx


@functools.lru_cache(maxsize=None)
def _bwd_bxf_partitioned(pqf, interpret, precision):
    return _make_partitioned(
        lambda h, w3, b3, basis, x, g: _fused_pairwise_conv_bwd_bxf_impl(
            h, w3, b3, basis, x, g, pqf, interpret, precision),
        rule='e m, m i o, i o, e z, e c q, e p o '
             '-> e m, m i o, i o, e z, e c q',
        need_repl=('m', 'i', 'z', 'c', 'q'),
        arg_specs=lambda P_, e, o: (P_(e, None), P_(None, None, o),
                                    P_(None, o), P_(e, None),
                                    P_(e, None, None), P_(e, None, o)),
        result_specs=lambda P_, e, o: (P_(e, None), P_(None, None, o),
                                       P_(None, o), P_(e, None),
                                       P_(e, None, None)),
        psum_fn=_bwd_bxf_psums)


@functools.partial(jax.jit,
                   static_argnames=('pqf', 'interpret', 'precision'))
def fused_pairwise_conv_bwd_bxf(h: jnp.ndarray, w3: jnp.ndarray,
                                basis_flat: jnp.ndarray, x: jnp.ndarray,
                                g: jnp.ndarray, pqf: tuple,
                                b3: jnp.ndarray = None,
                                interpret: bool = False, precision=None):
    """Backward of fused_pairwise_conv_bxf: (dh, dw3, db3, dbasis, dx),
    all f32, in the shapes of h [E, mid], w3 [mid, C*F, O], b3 [C*F, O],
    basis_flat [E, P*F*Q] and x [E, C, Q]; g [E, P, O].

    Two launches under the plain backward's names (the roles are the
    same: A gives dW3, dB3, dV2 and here dx too, B gives dH); each builds
    its V2 block in a VMEM scratch. A's R and dR stacks lie in sublane
    tiles of 8 channels, rows (f, t, o, c), so that dV2 sums over o
    across tiles; B's dR in rows (c, f, o), as w3 has them. MXU operands
    as fused_pairwise_conv_bwd; everything else f32. dbasis is reduced from
    A's dV2 in XLA and costs nothing where its result is unused.
    Partitions like the forward, partial sums reduced in the body."""
    if b3 is None:
        b3 = jnp.zeros(w3.shape[1:], jnp.float32)
    return _bwd_bxf_partitioned(tuple(pqf), interpret, precision)(
        h, w3, b3, basis_flat, x, g)
