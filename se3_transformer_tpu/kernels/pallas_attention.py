"""Pallas TPU kernel: fused multi-degree SE(3) attention.

The reference computes attention per degree with separate einsums
(/root/reference/se3_transformer_pytorch/se3_transformer_pytorch.py:508-516):
logits summed jointly over (channel, m), softmax, then a weighted sum per
degree — with the [b, h, n, J] similarity/attention tensors round-tripping
memory between those steps (SURVEY.md §3.4 hot loop, §7.2 step 7b).

TPU-native formulation: attention stays PER DEGREE (each degree has its
own softmax, as in the reference), but within a degree the (dim_head, m)
axes are flattened into one feature axis D = dim_head * (2d+1) — the
logits reduce over both jointly — and one kernel fuses the whole
sim/softmax/weighted-sum chain over the kv slots in VMEM:

    per (b*h, n-block) program:
        sim[e, j] = scale * sum_D q[e, D] k[e, j, D]     (VPU reduce)
        attn      = softmax_j(sim + mask)                 (VMEM)
        out[e, D] = sum_j attn[e, j] v[e, j, D]           (VPU reduce)

so sim/attn never exist in HBM and k/v are read exactly once. J (self +
null + neighbors) is small (~K+2 <= 64), so the whole slot axis fits in
VMEM and no online-softmax machinery is needed — this is the
graph-attention analogue of a single flash-attention tile. The caller
(ops.attention.AttentionSE3) invokes it once per degree; degrees share
nothing but the mask, so per-degree calls lose no fusion opportunity.

Multi-query attention (kv_heads < heads) is handled in the index maps:
query-head programs map onto their shared kv head, so the 1-head k/v is
never materialized per query head.

Backward: a second fused kernel (custom_vjp) recomputes sim/softmax in
VMEM and emits dq/dk/dv in one kv pass — grid (n_blocks, bh) with bh
inner so shared-kv dk/dv blocks accumulate over consecutive
query-head-group iterations (multi-query). Numerics are gated against
the XLA path in tests (interpreter mode) and on-chip
(scripts/kernel_smoke.py, scripts/tpu_checks.py).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def attention_reference(q, k, v, mask, scale):
    """XLA reference: q [BH, n, D], k/v [BKV, n, J, D], mask [B, n, J] or
    None -> out [BH, n, D]. BH = B*h, BKV = B*kv_h; kv heads are shared
    by contiguous groups of query heads."""
    BH = q.shape[0]
    BKV = k.shape[0]
    group = BH // BKV  # query heads per kv head
    kq = jnp.repeat(k, group, axis=0)
    vq = jnp.repeat(v, group, axis=0)
    sim = jnp.einsum('bnd,bnjd->bnj', q, kq) * scale
    if mask is not None:
        h = BH // mask.shape[0]
        mq = jnp.repeat(mask, h, axis=0)
        sim = jnp.where(mq, sim, NEG_INF)
    attn = jax.nn.softmax(sim, axis=-1)
    return jnp.einsum('bnj,bnjd->bnd', attn, vq)


def _softmax_weighted_sum(q, k, v, sim, o_ref):
    m = jnp.max(sim, axis=-1, keepdims=True)
    p = jnp.exp(sim - m)
    attn = p / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = jnp.sum(attn[:, :, None] * v, axis=1).astype(o_ref.dtype)


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale):
    q = q_ref[0]            # [n_b, D]
    k = k_ref[0]            # [n_b, J, D]
    v = v_ref[0]            # [n_b, J, D]
    sim = jnp.sum(k * q[:, None, :], axis=-1) * scale      # [n_b, J]
    sim = jnp.where(mask_ref[0], sim, NEG_INF)
    _softmax_weighted_sum(q, k, v, sim, o_ref)


def _kernel_nomask(q_ref, k_ref, v_ref, o_ref, *, scale):
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    sim = jnp.sum(k * q[:, None, :], axis=-1) * scale
    _softmax_weighted_sum(q, k, v, sim, o_ref)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Mosaic's scoped-vmem stack limit is 16 MiB; stay under it with slack
# for compiler temporaries. Verified the hard way: the first guess of
# this budget ignored tiling pads and OOM'd at the flagship shapes
# (n=1024, J=33) with "Scoped allocation ... exceeded scoped vmem limit".
_VMEM_LIMIT = 12 * 2 ** 20


def _block_row_bytes(J: int, D: int, bwd: bool) -> int:
    """VMEM bytes per node-row of the kernel working set, with the real
    TPU tile pads: the minor (lane) dim pads to 128, the second-minor
    (sublane) dim to 8 — so a [n_b, J, D] kv block occupies
    n_b * roundup(J,8) * roundup(D,128) f32 slots (D=8 inflates 16x),
    and a [n_b, J] sim-class array occupies n_b * roundup(J,128). Pallas
    double-buffers every in/out block across grid steps: x2."""
    Jp, Dp, Jl = _round_up(J, 8), _round_up(D, 128), _round_up(J, 128)
    if bwd:
        # in: k, v [n_b,J,D]; q, g [n_b,D]; mask. out: dq; dk, dv.
        # sim-class temporaries: sim, p/a, da, dsim + slack
        blocks = 4 * Jp * Dp + 3 * Dp + Jl
        temps = 6 * Jl
    else:
        # in: k, v; q; mask. out: out. temporaries: sim, p/attn + slack
        blocks = 2 * Jp * Dp + 2 * Dp + Jl
        temps = 4 * Jl
    return (2 * blocks + temps) * 4


def _pick_block_n(n: int, J: int, D: int, bwd: bool = False,
                  dtype: str = 'float32') -> int:
    """block_n resolution: the measured shape-keyed table
    (kernels.tuning) first, then the VMEM-ladder heuristic. The forward
    consults kind 'attention' (the tuner admits candidates against the
    BACKWARD row model, since training differentiates with the same
    block family); the backward consults its OWN kind 'attention_bwd'
    against its ~2x row model — previously the bwd ran the heuristic
    only, so scripts/tune_kernels.py could never promote a measured bwd
    block. `dtype` is the storage dtype of the q/k/v operands and keys
    the table entry. With an empty table every pick is bit-identical to
    the heuristic."""
    row = _block_row_bytes(J, D, bwd)
    cap = max(8, _round_up(n, 8))  # a tiny input must not pad to a full
    # 512-row block

    def _heuristic():
        for block_n in (512, 256, 128, 64, 32, 16, 8):
            if block_n * row <= _VMEM_LIMIT:
                return min(block_n, cap)
        return 8

    from . import tuning
    kind = 'attention_bwd' if bwd else 'attention'
    hit = tuning.lookup(kind, (n, J, D), dtype=dtype)
    if hit is not None:
        blocks, source = hit
        if len(blocks) == 1 and (
                source == 'forced'
                or tuning.validate_entry(kind, (n, J, D), blocks)):
            block_n = min(int(blocks[0]), cap)
            tuning.record_consult(kind, (n, J, D), dtype,
                                  source, (block_n,))
            return block_n
    block_n = _heuristic()
    tuning.record_consult(kind, (n, J, D), dtype, 'heuristic',
                          (block_n,))
    return block_n


def fused_attention_fits(J: int, D: int, bwd: bool = True) -> bool:
    """True when the fused kernel's working set fits the scoped-VMEM
    budget at SOME block size. The dispatch in ops.attention falls back
    to the XLA path when this is False (e.g. num_neighbors~512 at a wide
    dim_head) instead of surfacing a Mosaic VMEM error.

    bwd=True is DELIBERATELY conservative: the module
    dispatch cannot know whether the caller will differentiate, so it
    budgets for the ~2x backward working set even in inference-only use.
    A config whose forward fits but backward doesn't therefore runs XLA;
    callers that never differentiate can query fits(bwd=False) and call
    kernels.pallas_attention.fused_attention directly."""
    return 8 * _block_row_bytes(J, D, bwd) <= _VMEM_LIMIT


@functools.partial(jax.jit, static_argnames=('heads', 'scale', 'interpret'))
def _fused_attention_fwd_impl(q, k, v, mask, heads: int, scale: float,
                              interpret: bool = False):
    BH, n, D = q.shape
    BKV, _, J, _ = k.shape
    group = BH // BKV

    block_n = _pick_block_n(n, J, D, dtype=jnp.dtype(q.dtype).name)
    np_ = _round_up(n, block_n)
    if np_ != n:
        q = jnp.pad(q, ((0, 0), (0, np_ - n), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, np_ - n), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, np_ - n), (0, 0), (0, 0)))
        if mask is not None:
            # padded rows: keep slots valid so their softmax stays finite
            mask = jnp.pad(mask, ((0, 0), (0, np_ - n), (0, 0)),
                           constant_values=True)

    in_specs = [
        pl.BlockSpec((1, block_n, D), lambda bh, e: (bh, e, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_n, J, D),
                     lambda bh, e: (bh // group, e, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_n, J, D),
                     lambda bh, e: (bh // group, e, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(
            pl.BlockSpec((1, block_n, J), lambda bh, e: (bh // heads, e, 0),
                         memory_space=pltpu.VMEM))
        args.append(mask)
        kernel = functools.partial(_kernel, scale=scale)
    else:
        # no mask input at all: the constant-True mask would only waste a
        # [1, block_n, J] DMA per program
        kernel = functools.partial(_kernel_nomask, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(BH, np_ // block_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_n, D), lambda bh, e: (bh, e, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, np_, D), jnp.float32),
        interpret=interpret,
        # the launch's role is its instruction name in the device trace
        # (see kernels.pallas_pairwise); no `fused_` prefix, which the
        # benchmark's pairwise-kernel metrics select on
        name='pallas_attention_fwd',
    )(*args)
    return out[:, :n]


# --------------------------------------------------------------------- #
# fused backward
# --------------------------------------------------------------------- #
# Per (node-block, bh) program, recompute sim/attn in VMEM (cheaper than
# round-tripping them through HBM) and emit all three cotangents:
#   dv_j  += a_j * g                      (accumulated over the head group)
#   da_j   = <g, v_j>
#   dsim_j = a_j * (da_j - sum_l a_l da_l)
#   dq     = scale * sum_j dsim_j k_j
#   dk_j  += scale * dsim_j * q           (accumulated over the head group)
# The grid is (n_e, BH) with bh INNER so the shared-kv dk/dv blocks are
# revisited on consecutive iterations (the legal accumulation pattern for
# multi-query attention, group = heads / kv_heads).


def _bwd_compute(q, k, v, g, sim, group, scale, dq_ref, dk_ref, dv_ref):
    bh = pl.program_id(1)
    m = jnp.max(sim, axis=-1, keepdims=True)
    p = jnp.exp(sim - m)
    a = p / jnp.sum(p, axis=-1, keepdims=True)            # [n_b, J]
    da = jnp.sum(v * g[:, None, :], axis=-1)              # [n_b, J]
    dsim = a * (da - jnp.sum(a * da, axis=-1, keepdims=True))
    dq_ref[0] = (scale * jnp.sum(dsim[:, :, None] * k, axis=1)
                 ).astype(dq_ref.dtype)
    dk_blk = scale * dsim[:, :, None] * q[:, None, :]     # [n_b, J, D]
    dv_blk = a[:, :, None] * g[:, None, :]                # [n_b, J, D]

    @pl.when(bh % group == 0)
    def _():
        dk_ref[0] = dk_blk.astype(dk_ref.dtype)
        dv_ref[0] = dv_blk.astype(dv_ref.dtype)

    @pl.when(bh % group != 0)
    def _():
        dk_ref[0] = dk_ref[0] + dk_blk.astype(dk_ref.dtype)
        dv_ref[0] = dv_ref[0] + dv_blk.astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, g_ref,
                dq_ref, dk_ref, dv_ref, *, group, scale):
    q, k, v, g = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
    sim = jnp.sum(k * q[:, None, :], axis=-1) * scale
    sim = jnp.where(mask_ref[0], sim, NEG_INF)
    _bwd_compute(q, k, v, g, sim, group, scale, dq_ref, dk_ref, dv_ref)


def _bwd_kernel_nomask(q_ref, k_ref, v_ref, g_ref,
                       dq_ref, dk_ref, dv_ref, *, group, scale):
    q, k, v, g = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
    sim = jnp.sum(k * q[:, None, :], axis=-1) * scale
    _bwd_compute(q, k, v, g, sim, group, scale, dq_ref, dk_ref, dv_ref)


@functools.partial(jax.jit, static_argnames=('heads', 'scale', 'interpret'))
def _fused_attention_bwd_impl(q, k, v, mask, g, heads: int, scale: float,
                              interpret: bool = False):
    BH, n, D = q.shape
    BKV, _, J, _ = k.shape
    group = BH // BKV

    # the backward holds ~2x the forward's kv-sized blocks (dk/dv
    # outputs); kind 'attention_bwd' keys its own measured entries
    block_n = _pick_block_n(n, J, D, bwd=True,
                            dtype=jnp.dtype(q.dtype).name)
    np_ = _round_up(n, block_n)
    if np_ != n:
        pad = ((0, 0), (0, np_ - n), (0, 0))
        q, g = jnp.pad(q, pad), jnp.pad(g, pad)
        k = jnp.pad(k, ((0, 0), (0, np_ - n), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, np_ - n), (0, 0), (0, 0)))
        if mask is not None:
            # padded rows: g is zero there, so grads vanish; keep slots
            # valid so the recomputed softmax stays finite
            mask = jnp.pad(mask, ((0, 0), (0, np_ - n), (0, 0)),
                           constant_values=True)

    q_spec = pl.BlockSpec((1, block_n, D), lambda e, bh: (bh, e, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_n, J, D),
                           lambda e, bh: (bh // group, e, 0, 0),
                           memory_space=pltpu.VMEM)
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(
            pl.BlockSpec((1, block_n, J), lambda e, bh: (bh // heads, e, 0),
                         memory_space=pltpu.VMEM))
        args.append(mask)
        kernel = functools.partial(_bwd_kernel, group=group, scale=scale)
    else:
        kernel = functools.partial(_bwd_kernel_nomask, group=group,
                                   scale=scale)
    args.append(g)
    in_specs.append(q_spec)

    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(np_ // block_n, BH),
        in_specs=in_specs,
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, np_, D), jnp.float32),
            jax.ShapeDtypeStruct((BKV, np_, J, D), jnp.float32),
            jax.ShapeDtypeStruct((BKV, np_, J, D), jnp.float32),
        ],
        interpret=interpret,
        name='pallas_attention_bwd',
    )(*args)
    # cotangent dtypes must match the primals (custom_vjp contract); the
    # kernel accumulates in f32 regardless
    return (dq[:, :n].astype(q.dtype), dk[:, :n].astype(k.dtype),
            dv[:, :n].astype(v.dtype))


# --------------------------------------------------------------------- #
# SPMD partitioning rules
# --------------------------------------------------------------------- #
# The kernel is embarrassingly parallel over the node axis n (sequence
# parallelism — the long-context axis) and over the flattened batch*head
# axis; the slot (j) and feature (d) axes reduce inside and must be
# replicated. Without a rule GSPMD treats the Mosaic call as opaque and
# replicates the sharded operands. The leading axes of q [B*h, ...] and
# k/v [B*kv_h, ...] are DIFFERENT factor sizes, so the callbacks must
# check that the shard count divides B*kv_h (and B for the mask): then a
# q shard's kv-group range [bh//group] lands exactly on the matching k/v
# shard. Otherwise the leading-axis sharding is dropped (replicated).
# The backward needs no cross-shard reductions — every cotangent keeps
# its primal's axes, and multi-query dk/dv accumulation over the head
# group stays inside a shard (shards contain whole groups by the
# divisibility condition).


from .pallas_pairwise import (
    _axis_tuple as _att_axis_tuple, _spec_axes as _att_spec_axes,
)


def _att_resolve(mesh, arg_shapes, has_mask):
    """(bh_axes, n_axes) consistent with kv-group alignment; None = keep
    replicated."""
    def nshards(axes):
        s = 1
        for ax in _att_axis_tuple(axes):
            s *= mesh.shape[ax]
        return s

    def first_axes(dim):
        # any operand may carry the sharding (e.g. only the bwd cotangent
        # is node-sharded when it propagates from downstream)
        for a in arg_shapes:
            ax = _att_spec_axes(a.sharding, dim)
            if ax is not None:
                return ax
        return None

    q_sh, k_sh = arg_shapes[0], arg_shapes[1]
    a = first_axes(0)
    nax = first_axes(1)
    if set(_att_axis_tuple(a)) & set(_att_axis_tuple(nax)):
        a = None  # one mesh axis can't shard both; the node axis wins
    if a is not None:
        s = nshards(a)
        BKV = k_sh.shape[0]
        B = arg_shapes[3].shape[0] if has_mask else None
        if BKV % s != 0 or (B is not None and B % s != 0):
            a = None
    if nax is not None:
        s = nshards(nax)
        if q_sh.shape[1] % s != 0:
            nax = None
    return a, nax


@functools.lru_cache(maxsize=None)
def _att_partitioned(heads, scale, interpret, has_mask, bwd):
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P_

    if bwd:
        def impl(q, k, v, *rest):
            mask = rest[0] if has_mask else None
            g = rest[-1]
            return _fused_attention_bwd_impl(q, k, v, mask, g, heads,
                                             scale, interpret)
    else:
        def impl(q, k, v, *rest):
            mask = rest[0] if has_mask else None
            return _fused_attention_fwd_impl(q, k, v, mask, heads, scale,
                                             interpret)

    @custom_partitioning
    def f(*args):
        return impl(*args)

    def specs(P_, a, nax):
        q_s = P_(a, nax, None)
        kv_s = P_(a, nax, None, None)
        arg = [q_s, kv_s, kv_s]
        if has_mask:
            arg.append(P_(a, nax, None))
        if bwd:
            arg.append(q_s)  # g
            res = (q_s, kv_s, kv_s)
        else:
            res = (q_s,)
        return tuple(arg), res

    def partition(mesh, arg_shapes, result_shape):
        a, nax = _att_resolve(mesh, arg_shapes, has_mask)
        arg_specs, res_specs = specs(P_, a, nax)
        arg_sh = tuple(NamedSharding(mesh, s) for s in arg_specs)
        res_sh = tuple(NamedSharding(mesh, s) for s in res_specs)
        return (mesh, impl, res_sh if bwd else res_sh[0], arg_sh)

    def infer(mesh, arg_shapes, shape):
        a, nax = _att_resolve(mesh, arg_shapes, has_mask)
        m = arg_shapes[0].sharding.mesh
        _, res_specs = specs(P_, a, nax)
        res = tuple(NamedSharding(m, s) for s in res_specs)
        return res if bwd else res[0]

    mask_term = ', c n j' if has_mask else ''
    if bwd:
        rule = (f'a n d, b n j d, b n j d{mask_term}, a n d '
                f'-> a n d, b n j d, b n j d')
    else:
        rule = f'a n d, b n j d, b n j d{mask_term} -> a n d'
    # special-factor indices must be sorted by first appearance in the
    # rule: d (q's last dim) precedes the slot axis j
    f.def_partition(partition=partition,
                    infer_sharding_from_operands=infer,
                    sharding_rule=rule,
                    need_replication_factors=('d', 'j'))
    return f


# --------------------------------------------------------------------- #
# J-on-lanes layout: RETIRED (round-4 decision table)
# --------------------------------------------------------------------- #
# VERDICT r3 #6 asked for data or retirement on the attention kernel's
# layout. A J-on-lanes forward variant (k/v blocked [n_b, D, J], J
# padding 33->128 = 3.9x instead of D=8->128 = 16x) was measured against
# XLA and the D-on-lanes kernel at every flagship per-degree shape
# (J=33, n=1024, scripts/tpu_checks.py, TPU v5e, 22:54Z round 4):
#
#   D=8 : xla 4.39 ms   D-lanes 4.30 (1.02x)   J-lanes 4.05 (1.08x)
#   D=24: xla 3.97 ms   D-lanes 4.34 (0.91x)   J-lanes 3.70 (1.07x)
#   D=40: xla 4.85 ms   D-lanes 4.34 (1.12x)   J-lanes 4.52 (1.07x)
#   D=56: xla 4.40 ms   D-lanes 4.48 (0.98x)   J-lanes 4.79 (0.92x)
#
# Neither layout reaches the 1.2x bar anywhere; both sit in the noise
# band around XLA, and attention is <2% of the flagship step (the
# pairwise conv kernels dominate). Decision: XLA is the attention path;
# the D-on-lanes kernel above stays as the numerics-validated opt-in
# (pallas_attention=True) with fwd+bwd+SPMD rules; the forward-only
# J-on-lanes experiment is deleted (this note is its record; the code
# is one git checkout away).

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_attention(q, k, v, mask, heads: int, scale: float,
                    interpret: bool = False):
    """Fused multi-degree attention. q [B*h, n, D], k/v [B*kv_h, n, J, D],
    mask [B, n, J] bool or None -> [B*h, n, D] float32. Partitions over
    sharded node / batch-head axes (see the SPMD rules above)."""
    # scope the kernel dispatch so xprof traces attribute it by name
    # (observability.timing.MODEL_SCOPES)
    with jax.named_scope('pallas_attention'):
        f = _att_partitioned(heads, scale, interpret, mask is not None,
                             False)
        args = (q, k, v) + ((mask,) if mask is not None else ())
        return f(*args)


def _fa_fwd(q, k, v, mask, heads, scale, interpret):
    out = fused_attention(q, k, v, mask, heads, scale, interpret)
    return out, (q, k, v, mask)


def _fa_bwd(heads, scale, interpret, res, g):
    q, k, v, mask = res
    with jax.named_scope('pallas_attention_bwd'):
        f = _att_partitioned(heads, scale, interpret, mask is not None,
                             True)
        args = (q, k, v) + ((mask,) if mask is not None else ()) + (g,)
        dq, dk, dv = f(*args)
    return dq, dk, dv, None


fused_attention.defvjp(_fa_fwd, _fa_bwd)
