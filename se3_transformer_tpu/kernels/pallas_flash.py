"""Streaming (flash-style) equivariant attention with on-the-fly
pairwise contraction.

The trunk's unfused attention path materializes, per layer and per
degree, three per-edge HBM tensors before a single score is computed:

  * the pairwise kernel basis  [b, n, k, P, Q, F]   (get_basis),
  * the keyed features k/v     [b, kv_h, n, J, D]   (ConvSE3 pool=False
    on exchange_index_select-gathered neighbors),
  * the score tensor           [b, h, n, J].

This module computes all three INSIDE the attention kernel, per
(node-block, kv-slot-block) tile, with an online softmax carried across
slot blocks — the flash-attention formulation of E2Former-V2
(arXiv:2601.16622) / the Clebsch-Gordan Transformer (arXiv:2509.24093)
specialized to the TFN contraction. Per tile the kernel:

  1. gathers the slot block's neighbor features from the NODE-level
     feature tensors (jnp.take on the in-VMEM [n, C, Q] operand — the
     [b, n, k, C, Q] gathered tensor never exists in HBM);
  2. runs the pluggable pairwise contraction in VMEM:
       'dense' arm — rebuilds the basis block from the per-edge
         spherical-harmonics stack Y [.., S] and the static Q_J
         constants (S = (2*max_J+1)^2 floats/edge versus the basis's
         P*Q*F *per degree pair*), contracts with the gathered block,
         and applies the radial matmul;
       'so2' arm — fuses PR 10's rotate-in -> banded-z -> radial ->
         rotate-out chain (previously pure XLA — the named residue) on
         the block, using the same factored Wigner application and
         canonical banded blocks as so2/contract.py;
  3. folds the block's scores into an online-softmax state (m, l, acc)
     held in VMEM scratch across the slot-block grid axis.

The always-valid prefix slots ([global, null, self] — the unfused
path's left-padded concat order) ride as a tiny [b, n, S0, kv_h*D]
tensor folded into the state at slot-block 0; neighbor masks keep the
unfused semantics exactly (finite NEG_INF fill, so a fully-masked row
degrades to the same uniform average the XLA softmax produces).

Dispatch: the Pallas kernel runs under `interpret=True` (the CPU tests);
its body does not compile for the TPU yet (MOSAIC_REFUSES below), so on
the chip — and everywhere else — `_flash_stream` computes the identical
function by streaming REMAT'D NODE CHUNKS through XLA (lax.map +
jax.checkpoint), which is also what the `custom_vjp` backward replays —
recompute-in-backward, so the only saved residuals are the kernel's
inputs and the whole path composes with the reversible trunk for
near-O(1) activation memory.

A graph-free GLOBAL variant (`flash_global_attention`) drops the kNN
truncation entirely: per (i-block, j-block) tile it computes rel_pos /
rel_dist from the coordinates, the radial hidden through an inlined
Dense-LN-GELU trunk, and the harmonics/frames payload on the fly — NO
per-edge tensor of any kind touches HBM, so activation memory is O(n)
at O(n^2) compute. This is the large-assembly scenario where kNN
truncation is the accuracy bottleneck.

Block sizes are tuning kinds 'flash' ((block_n, block_j), admitted
against the VMEM row model below) and 'flash_stream' (the XLA
fallback's node-chunk count); every resolution is consulted through
kernels/tuning.py like the other kernels.
"""
from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# Mosaic scoped-vmem budget, same hard-won constant as pallas_attention
_VMEM_LIMIT = 12 * 2 ** 20

_FRAME_KEYS = ('cos_a', 'sin_a', 'cos_b', 'sin_b')

ARMS = ('dense', 'so2')


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class FlashConfig(NamedTuple):
    """Static configuration of one flash-attention call (hashable —
    rides as the custom_vjp/jit static argument)."""
    pairs: Tuple[Tuple[int, int], ...]  # (d_in, channels) per input degree
    d_out: int
    heads: int
    kv_heads: int
    scale: float
    arm_v: str = 'dense'
    arm_k: str = 'dense'
    tie: bool = False            # keys ARE values (tie_key_values)
    prefix: int = 0              # always-valid leading kv slots
    has_mask: bool = False
    mode: str = 'knn'            # 'knn' | 'global'
    exclude_self: bool = False   # global mode: mask the j == i slot
    use_pallas: bool = False
    interpret: bool = False


# --------------------------------------------------------------------- #
# pairwise-contraction arms (pure jnp: shared by the kernel body, the
# XLA streaming fallback, and the recompute-in-backward replay)
# --------------------------------------------------------------------- #

@lru_cache(maxsize=None)
def _pair_cg(d_in: int, d_out: int) -> np.ndarray:
    """Static contraction constants turning the per-edge SH stack into
    the pairwise basis: T[s, p, q, f] with s indexing the flattened
    Y stack (degree J occupies rows J^2..(J+1)^2, offset by lo^2), so
    basis[.., p, q, f] = sum_s Y[.., lo^2 + s] T[s, p, q, f] equals
    get_basis's Q_J contraction exactly."""
    from ..basis import basis_transformation_Q_J
    lo, hi = abs(d_in - d_out), d_in + d_out
    P, Q = 2 * d_out + 1, 2 * d_in + 1
    F = 2 * min(d_in, d_out) + 1
    T = np.zeros(((hi + 1) ** 2 - lo ** 2, P, Q, F))
    for fi, J in enumerate(range(lo, hi + 1)):
        QJ = basis_transformation_Q_J(J, d_in, d_out)  # [(P*Q), 2J+1]
        T[J * J - lo * lo:(J + 1) * (J + 1) - lo * lo, :, :, fi] = \
            QJ.reshape(P, Q, 2 * J + 1).transpose(2, 0, 1)
    return T


def flash_sh_payload(rel_pos: jnp.ndarray, max_degree: int,
                     differentiable: bool = False) -> jnp.ndarray:
    """The dense arm's per-edge payload: real spherical harmonics
    J = 0..2*max_degree stacked to [..., (2*max_degree + 1)^2] —
    O(S) floats per edge versus the materialized basis's O(P*Q*F) per
    degree pair. Same normalization/stop_gradient contract as
    get_basis."""
    from ..basis import safe_normalize
    from ..so3.spherical_harmonics import real_spherical_harmonics_all
    rhat, _ = safe_normalize(rel_pos)
    Ys = real_spherical_harmonics_all(2 * max_degree, rhat, xp=jnp)
    out = jnp.concatenate([Ys[J] for J in range(2 * max_degree + 1)],
                          axis=-1)
    if not differentiable:
        out = jax.lax.stop_gradient(out)
    return out


def pack_frames(frames) -> jnp.ndarray:
    """so2 frames dict -> one [..., 4 * L1] array (kernel ref layout)."""
    return jnp.concatenate([frames[k] for k in _FRAME_KEYS], axis=-1)


def unpack_frames(packed: jnp.ndarray) -> dict:
    L1 = packed.shape[-1] // 4
    return {k: packed[..., i * L1:(i + 1) * L1]
            for i, k in enumerate(_FRAME_KEYS)}


@lru_cache(maxsize=None)
def _so2_pair_consts(d_in: int, d_out: int):
    """The canonical banded 2x2 blocks for one pair (so2/canonical.py)."""
    from ..so2.canonical import canonical_blocks
    a, b = canonical_blocks(d_in, d_out)
    return np.asarray(a), np.asarray(b)


@lru_cache(maxsize=None)
def _rot_consts(l: int):
    """Gather-free constants for the factored Wigner application at
    degree l: SEL [l+1, 2l+1] one-hot mapping harmonics m = 0..l onto
    the |m_q| positions (replaces so2.frames._dz_apply's constant-index
    gather — Pallas kernels cannot capture constant arrays, so every
    constant rides as an input ref), SGN [1, 2l+1] the +/-m block
    signs, and J_l the involution matrix."""
    from ..so2.frames import j_matrix
    m_abs = np.abs(np.arange(-l, l + 1))
    sel = np.zeros((l + 1, 2 * l + 1))
    sel[m_abs, np.arange(2 * l + 1)] = 1.0
    sgn = np.sign(-np.arange(-l, l + 1)).astype(np.float64)[None]
    return sel, sgn, j_matrix(l)


def _arm_consts(cfg: 'FlashConfig') -> dict:
    """Every constant array the contraction arms need, as numpy — the
    Pallas path passes them as kernel inputs, the XLA path converts
    them in place."""
    arms = {cfg.arm_v} | ({cfg.arm_k} if not cfg.tie else set())
    out = {}
    if 'dense' in arms:
        for i, (d_in, _) in enumerate(cfg.pairs):
            out[f'cg{i}'] = _pair_cg(d_in, cfg.d_out)
    if 'so2' in arms:
        for i, (d_in, _) in enumerate(cfg.pairs):
            a, b = _so2_pair_consts(d_in, cfg.d_out)
            out[f'so2a{i}'], out[f'so2b{i}'] = a, b
        for l in sorted({d for d, _ in cfg.pairs} | {cfg.d_out}):
            if l > 0:
                sel, sgn, J = _rot_consts(l)
                out[f'sel{l}'], out[f'sgn{l}'], out[f'J{l}'] = sel, sgn, J
    return out


def _dz_apply_c(x, cos_m, sin_m, sign, sel, sgn):
    """so2.frames._dz_apply with the constant-index gather replaced by a
    one-hot contraction (sel/sgn from _rot_consts) — bit-identical
    values, kernel-legal form."""
    cv = jnp.einsum('...m,mp->...p', cos_m, sel)
    sv = sign * jnp.einsum('...m,mp->...p', sin_m, sel) * sgn[0]
    while cv.ndim < x.ndim:
        cv, sv = cv[..., None, :], sv[..., None, :]
    return cv * x + sv * x[..., ::-1]


def _rotate_in_c(x, fr, l, consts):
    if l == 0:
        return x
    sel = consts[f'sel{l}']
    sgn = consts[f'sgn{l}']
    J = consts[f'J{l}']
    t = _dz_apply_c(x, fr['cos_a'][..., :l + 1], fr['sin_a'][..., :l + 1],
                    -1.0, sel, sgn)
    t = jnp.einsum('qp,...q->...p', J, t)       # J^T contraction
    t = _dz_apply_c(t, fr['cos_b'][..., :l + 1], fr['sin_b'][..., :l + 1],
                    -1.0, sel, sgn)
    return jnp.einsum('pq,...q->...p', J, t)


def _rotate_out_c(y, fr, l, consts):
    if l == 0:
        return y
    sel = consts[f'sel{l}']
    sgn = consts[f'sgn{l}']
    J = consts[f'J{l}']
    t = jnp.einsum('qp,...q->...p', J, y)       # J^T contraction
    t = _dz_apply_c(t, fr['cos_b'][..., :l + 1], fr['sin_b'][..., :l + 1],
                    1.0, sel, sgn)
    t = jnp.einsum('pq,...q->...p', J, t)
    return _dz_apply_c(t, fr['cos_a'][..., :l + 1], fr['sin_a'][..., :l + 1],
                       1.0, sel, sgn)


def _banded_z_c(xr, d_in: int, d_out: int, a, b):
    """so2.contract.banded_z (pad_rows=True) with the +/-m pair gathers
    rewritten as slices — same values, kernel-legal form."""
    mmin = min(d_in, d_out)
    xneg = xr[..., d_in - mmin:d_in + 1][..., ::-1][..., None, :]
    xpos = xr[..., d_in:d_in + mmin + 1][..., None, :]
    zneg = a * xneg + b * xpos                  # [..., C, F, M+1]
    zpos = a * xpos - b * xneg
    band = jnp.concatenate(
        (zneg[..., :0:-1], zneg[..., :1], zpos[..., 1:]), axis=-1)
    band = jnp.moveaxis(band, -1, -3)           # [..., band, C, F]
    if d_out > mmin:
        pad = [(0, 0)] * band.ndim
        pad[-3] = (d_out - mmin, d_out - mmin)
        band = jnp.pad(band, pad)
    C = xr.shape[-2]
    return band.reshape(*band.shape[:-2], C * band.shape[-1])


def _kv_block(arm: str, pairs, d_out: int, xg, h, sh, fr, w3, b3,
              consts, w3_scale=None):
    """One slot block's keyed features, entirely in registers/VMEM:
    xg tuple of [..., C, Q] gathered features (one per input degree),
    h [..., mid] radial hidden, sh [..., S] SH stack (dense arm),
    fr frames dict (so2 arm), w3 [mid, IF, O] / b3 [IF, O] grouped
    radial params, consts from _arm_consts -> [..., O, P]. Matches
    ConvSE3's grouped shared-radial contraction segment-for-segment
    (same params, same concat order), so the fused path is
    checkpoint-compatible.

    `w3_scale` [1, IF, O] is the quantized-serving epilogue: `w3` is
    then int8/fp8 storage riding as a kernel input ref, upcast in-tile
    for the radial dot, the per-channel scale folded into R — the fp32
    grouped weight never exists in HBM (quant.rules / the
    _radial_contract epilogue, kernel-side)."""
    segs = []
    for i, ((d_in, _), x) in enumerate(zip(pairs, xg)):
        if arm == 'dense':
            lo, hi = abs(d_in - d_out), d_in + d_out
            T = consts[f'cg{i}'].astype(x.dtype)
            y = sh[..., lo * lo:(hi + 1) * (hi + 1)]
            # HIGHEST precision like get_basis's Q_J contraction, so the
            # rebuilt basis block matches the materialized one bit-close
            basis = jnp.einsum('...s,spqf->...pqf', y, T,
                               precision=jax.lax.Precision.HIGHEST)
            v2 = jnp.einsum('...pqf,...cq->...pcf', basis, x)
            segs.append(v2.reshape(*v2.shape[:-2], -1))
        elif arm == 'so2':
            xr = _rotate_in_c(x, fr, d_in, consts)
            segs.append(_banded_z_c(xr, d_in, d_out,
                                    consts[f'so2a{i}'].astype(x.dtype),
                                    consts[f'so2b{i}'].astype(x.dtype)))
        else:
            raise ValueError(f'unknown contraction arm {arm!r} '
                             f'(known: {ARMS})')
    z = jnp.concatenate(segs, axis=-1) if len(segs) > 1 else segs[0]
    if w3_scale is not None:
        R = jnp.einsum('...m,mio->...io', h,
                       w3.astype(jnp.float32),
                       preferred_element_type=jnp.float32) \
            * w3_scale[0] + b3
    else:
        R = jnp.einsum('...m,mio->...io', h, w3,
                       preferred_element_type=jnp.float32) + b3
    out = jnp.einsum('...pi,...io->...po', z, R)
    out = jnp.swapaxes(out, -1, -2)                     # [..., O, P]
    if arm == 'so2':
        out = _rotate_out_c(out, fr, d_out, consts)
    return out


def _radial_apply(x: jnp.ndarray, rp: Tuple[jnp.ndarray, ...]
                  ) -> jnp.ndarray:
    """Inlined radial trunk (Dense -> LN -> GELU, twice) for the global
    kernel, where the per-edge hidden never exists in HBM. rp is the
    8-tuple (w1, b1, ln1_scale, ln1_bias, w2, b2, ln2_scale, ln2_bias)
    with every 1-D param reshaped [1, mid] (TPU refs want >= 2D)."""
    w1, b1, s1, o1, w2, b2, s2, o2 = rp

    def ln(t, s, o):
        mu = t.mean(-1, keepdims=True)
        var = ((t - mu) ** 2).mean(-1, keepdims=True)
        return (t - mu) * jax.lax.rsqrt(var + 1e-6) * s + o

    t = jnp.einsum('...e,em->...m', x, w1) + b1
    t = jax.nn.gelu(ln(t, s1, o1))
    t = jnp.einsum('...e,em->...m', t, w2) + b2
    return jax.nn.gelu(ln(t, s2, o2))


def _safe_dist(rel: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    return jnp.sqrt(jnp.maximum(jnp.sum(rel ** 2, axis=-1), eps ** 2))


def _global_edge_payload(cfg: 'FlashConfig', rel, rp_v, rp_k=None):
    """Everything the global (graph-free) tile computes on the fly from
    a [..., 3] rel_pos block: the radial hiddens through the inlined
    Dense-LN-GELU trunk and the harmonics/frames payload the active
    arms need. Shared by the XLA stream's chunk body, the Pallas kernel
    body, and the ring-sharded fold so the three dispatches stay one
    function by construction."""
    ef = _safe_dist(rel)[..., None]
    h_v = _radial_apply(ef, rp_v)
    h_k = _radial_apply(ef, rp_k) if rp_k is not None else h_v
    sh = flash_sh_payload(rel, _sh_degree(cfg), differentiable=True) \
        if 'dense' in (cfg.arm_v, cfg.arm_k) else None
    fr = None
    if 'so2' in (cfg.arm_v, cfg.arm_k):
        from ..so2.frames import edge_frames
        fr = edge_frames(rel, _frame_degree(cfg), differentiable=True)
    return h_v, h_k, sh, fr


# --------------------------------------------------------------------- #
# online softmax
# --------------------------------------------------------------------- #

def _attend_block(qr, kblk, vblk, maskblk, m, l, acc, scale,
                  inbounds=None):
    """Fold one kv slot block into the running online-softmax state.
    qr [..., kv, g, D]; k/v [..., j, kv, D]; maskblk [..., j] or None;
    m/l [..., kv, g]; acc [..., kv, g, D].

    `maskblk` keeps the UNFUSED semantics (finite NEG_INF fill — a
    fully-masked row degrades to the uniform average, exactly like the
    XLA softmax). `inbounds` [j] marks slots that exist only because
    the slot axis padded to the block quantum: their probability is
    HARD-zeroed after the exp, so padding never changes any row's
    result — including fully-masked rows."""
    sim = jnp.einsum('...kgd,...jkd->...kgj', qr, kblk) * scale
    if maskblk is not None:
        sim = jnp.where(maskblk[..., None, None, :], sim, NEG_INF)
    if inbounds is not None:
        sim = jnp.where(inbounds, sim, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(sim, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(sim - m_new[..., None])
    if inbounds is not None:
        p = p * inbounds.astype(p.dtype)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + \
        jnp.einsum('...kgj,...jkd->...kgd', p, vblk)
    return m_new, l_new, acc_new


def _init_state(qr, prefix_k, prefix_v, scale, Dh):
    """State after the always-valid prefix slots ([global, null, self]
    left of the neighbors, all True in the unfused path's left-padded
    mask); NEG_INF/0/0 when there is no prefix."""
    lead = qr.shape[:-1]
    if prefix_k is None:
        m0 = jnp.full(lead, NEG_INF, jnp.float32)
        l0 = jnp.zeros(lead, jnp.float32)
        acc0 = jnp.zeros((*lead, Dh), jnp.float32)
        return m0, l0, acc0
    m0 = jnp.full(lead, NEG_INF, jnp.float32)
    l0 = jnp.zeros(lead, jnp.float32)
    acc0 = jnp.zeros((*lead, Dh), jnp.float32)
    return _attend_block(qr, prefix_k, prefix_v, None, m0, l0, acc0,
                         scale)


# --------------------------------------------------------------------- #
# block-size resolution (tuning kinds 'flash' / 'flash_stream')
# --------------------------------------------------------------------- #

# allowance for the contraction constant tables (Q_J / canonical-band /
# Wigner-factor refs — cfg-dependent, largest for dense high-degree
# pairs; 1 MiB covers every pair set <= degree 6 with tile pads)
_CONST_VMEM_ALLOWANCE = 1 * 2 ** 20


def _flash_vmem_bytes(bn: int, bj: int, S0: int, heads: int, kv_h: int,
                      Dh: int, mid: int, IF: int, P: int,
                      n: int = 0, xres: int = 0) -> int:
    """Coarse per-program VMEM model with the TPU tile pads (minor dim
    -> 128, second-minor -> 8), double-buffered in/out blocks plus the
    dominant in-kernel temporaries (the rebuilt basis block, the
    per-edge radial matrix R, and the kv block). `xres` is the
    node-feature row footprint sum_i roundup(C_i * Q_i, 128) — in kNN
    mode those operands are VMEM-RESIDENT at FULL n (the in-tile gather
    reads them whole), an n-scaled term NO block size can shrink; in
    global mode (n=0 here) they are bj-blocked instead."""
    Dhp = _round_up(Dh, 128)
    midp = _round_up(mid, 128)
    bj8 = _round_up(bj, 8)
    blocks = (bn * heads * Dhp            # q
              + bn * heads * Dhp          # out
              + 2 * bn * bj8 * midp       # h_v, h_k
              + bn * bj8 * 128            # idx / mask / payload minors
              + bn * _round_up(max(S0, 1), 8) * _round_up(kv_h * Dh, 128))
    scratch = bn * heads * Dhp + 2 * bn * _round_up(heads, 128)
    temps = (2 * bn * bj8 * kv_h * Dhp            # kv blocks (k and v)
             + bn * bj8 * P * _round_up(IF, 128)  # z / basis block
             + bn * bj8 * IF * 128)               # R [.., IF, O] minor pad
    resident = _round_up(max(n, bj8), 8) * xres   # node features (see above)
    return 4 * (2 * blocks + scratch + temps + resident) \
        + _CONST_VMEM_ALLOWANCE


def flash_admissible_blocks(shape) -> list:
    """Tile-legal, VMEM-admissible (block_n, block_j) candidates for a
    'flash' shape tuple (n, K, S0, heads, kv_h, Dh, mid, IF, P, xres)
    — what scripts/tune_kernels.py may measure. Both block sizes are
    multiples of 8: block_j is a second-minor block dimension of the
    [.., block_j, mid] payload operands, and the operands whose MINOR
    axis is the slot axis (idx / nmask / nodemask) are laid out
    slot-block-major in _flash_fwd_impl so that any such block_j spans
    their whole minor axis. In kNN mode (K > 0)
    the node-feature residency is n-scaled and block-independent: a
    shape whose resident set alone busts the budget admits NOTHING
    (the caller must fall back to the XLA stream), rather than
    admitting blocks that Mosaic would refuse to compile."""
    n, K, S0, heads, kv_h, Dh, mid, IF, P, xres = \
        (int(s) for s in tuple(shape) + (0,) * (10 - len(tuple(shape))))
    out = []
    slot = K if K > 0 else n
    res_n = n if K > 0 else 0
    for bn in (128, 64, 32, 16, 8):
        if bn > _round_up(n, 8):
            continue
        for bj in (8, 16, 32, 64, 128):
            if bj > _round_up(slot, 8):
                continue
            if _flash_vmem_bytes(bn, bj, S0, heads, kv_h, Dh, mid, IF,
                                 P, n=res_n, xres=xres) <= _VMEM_LIMIT:
                out.append((bn, bj))
    return out


def _pick_flash_blocks(shape, dtype: str) -> Tuple[int, int]:
    """(block_n, block_j) resolution: env override > measured table
    (kind 'flash') > VMEM-ladder heuristic; every resolution recorded."""
    from . import tuning
    env = os.environ.get('SE3_TPU_FLASH_BLOCKS', '')
    if env:
        bn, bj = (int(x) for x in env.split(','))
        tuning.record_consult('flash', shape, dtype, 'env', (bn, bj))
        return bn, bj
    hit = tuning.lookup('flash', shape, dtype=dtype)
    if hit is not None:
        blocks, source = hit
        if len(blocks) == 2 and (
                source == 'forced'
                or tuning.validate_entry('flash', shape, blocks)):
            tuning.record_consult('flash', shape, dtype, source,
                                  tuple(blocks))
            return int(blocks[0]), int(blocks[1])
    n, K, S0, heads, kv_h, Dh, mid, IF, P, xres = (int(s) for s in shape)
    slot = K if K > 0 else n
    # prefer a slot block covering the (small) kNN slot axis; the pick
    # must come FROM the admissible set — a blind fallback here would
    # hand Mosaic a config _dispatch just confirmed exists some
    # admissible alternative for (the scoped-VMEM error class the
    # fallback guard exists to prevent)
    bj_pref = min(_round_up(slot, 8), 32)
    cands = flash_admissible_blocks(shape)
    if cands:
        bn = max(c[0] for c in cands)
        row = [c[1] for c in cands if c[0] == bn]
        below = [b for b in row if b <= bj_pref]
        bj = max(below) if below else min(row)
    else:
        # nothing fits at any block size: _dispatch routes to the XLA
        # stream and this pick is never compiled
        bn, bj = 8, bj_pref
    tuning.record_consult('flash', shape, dtype, 'heuristic', (bn, bj))
    return bn, bj


def _pick_stream_chunks(shape, dtype: str,
                        kind: str = 'flash_stream') -> int:
    """Node-chunk count for the XLA streaming path (and the backward's
    recompute replay). Heuristic: ~16-node chunks — measured best on
    the CPU toy A/B sweep (SE3_TPU_FLASH_CHUNKS 1/2/4/8/16: 8 chunks
    at n=128 beat 4 on BOTH step time and peak bytes; 1 = unchunked
    loses the memory win entirely), small enough that the per-chunk
    edge tensors stay cache-sized.

    `kind` keys the measured table: 'flash_stream' for the kNN stream,
    'flash_global' for the graph-free variant, whose per-chunk working
    set is O(rows * n) rather than O(rows * K) — at assembly n the
    small-n-calibrated n // 16 hard-code is exactly what the measured
    table exists to override (its candidate ladder extends to 2048
    chunks, tuning.admissible_candidates)."""
    from . import tuning
    env = os.environ.get('SE3_TPU_FLASH_CHUNKS', '')
    if env:
        chunks = max(1, int(env))
        tuning.record_consult(kind, shape, dtype, 'env', (chunks,))
        return chunks
    hit = tuning.lookup(kind, shape, dtype=dtype)
    if hit is not None:
        blocks, source = hit
        if source == 'forced' or tuning.validate_entry(
                kind, shape, blocks):
            tuning.record_consult(kind, shape, dtype, source, blocks)
            return int(blocks[0])
    n = int(shape[0])
    chunks = max(1, n // 16)
    tuning.record_consult(kind, shape, dtype, 'heuristic', (chunks,))
    return chunks


def _stream_kind(cfg: 'FlashConfig') -> str:
    return 'flash_global' if cfg.mode == 'global' else 'flash_stream'


def _shape_key(cfg: FlashConfig, ops) -> Tuple[int, ...]:
    q = ops['q']
    n = int(q.shape[1])
    K = int(ops['idx'].shape[-1]) if cfg.mode == 'knn' else 0
    Dh = int(q.shape[-1])
    mid = int(ops['h_v'].shape[-1]) if 'h_v' in ops \
        else int(ops['rp_v'][4].shape[0])
    IF = int(ops['wv'].shape[1])
    # node-feature row footprint (tile-padded): n-RESIDENT in kNN mode,
    # so the VMEM admission model must see it (no block shrinks it)
    xres = sum(_round_up(c * (2 * d + 1), 128) for d, c in cfg.pairs)
    return (n, K, cfg.prefix, cfg.heads, cfg.kv_heads, Dh, mid, IF,
            2 * cfg.d_out + 1, xres)


# --------------------------------------------------------------------- #
# XLA streaming path (CPU/GPU forward AND the recompute backward)
# --------------------------------------------------------------------- #

def _gather_nodes(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """x [B, n, ...], idx [B, nc, K] -> [B, nc, K, ...]."""
    return jax.vmap(lambda xb, ib: xb[ib])(x, idx)


def _row_attention(cfg: FlashConfig, q, kf, vf, mask_full):
    """Full-row attention for one node chunk (q [..., h, D];
    kf/vf [..., J, kv, D]; mask [..., J] or None) — mathematically the
    online-softmax limit with one block, and bit-compatible with the
    unfused einsum+softmax path."""
    group = cfg.heads // cfg.kv_heads
    qr = q.reshape(*q.shape[:-2], cfg.kv_heads, group, q.shape[-1])
    sim = jnp.einsum('...kgd,...jkd->...kgj', qr, kf) * cfg.scale
    if mask_full is not None:
        sim = jnp.where(mask_full[..., None, None, :], sim, NEG_INF)
    attn = jax.nn.softmax(sim, axis=-1)
    out = jnp.einsum('...kgj,...jkd->...kgd', attn, vf)
    return out.reshape(*q.shape)


def _chunk_body(cfg: FlashConfig, chunk, full):
    """One node chunk of the streaming computation. `chunk` holds the
    per-node operands sliced along the node axis; `full` the node-level
    feature tensors and parameters (closed over by lax.map)."""
    q = chunk['q']                              # [B, nc, h, Dh]
    Dh = q.shape[-1]
    kv_h = cfg.kv_heads
    if cfg.mode == 'knn':
        idx = chunk['idx']
        xg = tuple(_gather_nodes(x, idx) for x in full['xs'])
        h_v, h_k = chunk['h_v'], chunk.get('h_k', chunk['h_v'])
        sh = chunk.get('sh')
        fr = unpack_frames(chunk['fr']) if 'fr' in chunk else None
        nmask = chunk.get('nmask')
    else:
        ci = chunk['coords']                    # [B, nc, 3]
        cj = full['coords']                     # [B, n, 3]
        rel = ci[:, :, None, :] - cj[:, None, :, :]
        h_v, h_k, sh, fr = _global_edge_payload(
            cfg, rel, full['rp_v'], full.get('rp_k'))
        xg = tuple(jnp.broadcast_to(x[:, None], (x.shape[0], q.shape[1],
                                                 *x.shape[1:]))
                   for x in full['xs'])
        nmask = None
        if 'nodemask' in full:
            nmask = jnp.broadcast_to(full['nodemask'][:, None, :],
                                     rel.shape[:-1])
        if cfg.exclude_self:
            rows = chunk['row_id'][..., None]       # [B, nc, 1]
            cols = jnp.arange(cj.shape[1])[None, None, :]
            notself = rows != cols
            nmask = notself if nmask is None else (nmask & notself)

    consts = full['consts']
    kv_v = _kv_block(cfg.arm_v, cfg.pairs, cfg.d_out, xg, h_v, sh, fr,
                     full['wv'], full['bv'], consts,
                     w3_scale=full.get('wv_scale'))
    kv_v = kv_v.reshape(*kv_v.shape[:-2], kv_h, Dh)
    if cfg.tie:
        kv_k = kv_v
    else:
        kv_k = _kv_block(cfg.arm_k, cfg.pairs, cfg.d_out, xg, h_k, sh,
                         fr, full['wk'], full['bk'], consts,
                         w3_scale=full.get('wk_scale'))
        kv_k = kv_k.reshape(*kv_k.shape[:-2], kv_h, Dh)

    if cfg.prefix:
        S0 = cfg.prefix
        pk = chunk['prefix_k'].reshape(*q.shape[:-2], S0, kv_h, Dh)
        pv = chunk['prefix_v'].reshape(*q.shape[:-2], S0, kv_h, Dh)
        kv_k = jnp.concatenate((pk, kv_k), axis=-3)
        kv_v = jnp.concatenate((pv, kv_v), axis=-3)
        if nmask is not None:
            ones = jnp.ones((*nmask.shape[:-1], S0), bool)
            nmask = jnp.concatenate((ones, nmask), axis=-1)
    return _row_attention(cfg, q, kv_k, kv_v, nmask)


def _sh_degree(cfg: FlashConfig) -> int:
    """SH stack degree covering every pair's J range: ceil(max_J / 2)
    since flash_sh_payload stacks J = 0..2*max_degree."""
    max_j = max(d_in + cfg.d_out for d_in, _ in cfg.pairs)
    return (max_j + 1) // 2

def _frame_degree(cfg: FlashConfig) -> int:
    return max([cfg.d_out] + [d for d, _ in cfg.pairs])


_CHUNKED_KEYS = ('q', 'idx', 'nmask', 'h_v', 'h_k', 'sh', 'fr',
                 'prefix_k', 'prefix_v', 'coords', 'row_id')


def _flash_stream(cfg: FlashConfig, ops: dict, chunks: int
                  ) -> jnp.ndarray:
    """The XLA streaming path: lax.map over remat'd node chunks — the
    per-edge working set exists only one chunk at a time, both forward
    and (via jax.checkpoint) in the backward replay."""
    chunked = {k: v for k, v in ops.items()
               if k in _CHUNKED_KEYS and v is not None}
    if cfg.mode == 'global':
        chunked['coords'] = ops['coords']
        B, n = ops['q'].shape[:2]
        chunked['row_id'] = jnp.broadcast_to(jnp.arange(n)[None], (B, n))
    full = {k: v for k, v in ops.items() if k not in chunked}
    if cfg.mode == 'global':
        full['coords'] = ops['coords']
    full['consts'] = {k: jnp.asarray(v, jnp.float32)
                      for k, v in _arm_consts(cfg).items()}

    body = partial(_chunk_body, cfg)
    n = ops['q'].shape[1]
    c = max(1, min(chunks, n))
    if c == 1:
        return body(chunked, full)
    n_pad = -(-n // c) * c

    def split(a):
        if n_pad != n:
            pad = [(0, 0)] * a.ndim
            pad[1] = (0, n_pad - n)
            a = jnp.pad(a, pad)
        a = a.reshape(a.shape[0], c, n_pad // c, *a.shape[2:])
        return jnp.swapaxes(a, 0, 1)

    out = jax.lax.map(jax.checkpoint(lambda t: body(t, full)),
                      jax.tree_util.tree_map(split, chunked))
    out = jnp.swapaxes(out, 0, 1)
    out = out.reshape(out.shape[0], n_pad, *out.shape[3:])
    return out[:, :n] if n_pad != n else out


# --------------------------------------------------------------------- #
# Pallas kernel
# --------------------------------------------------------------------- #

def _flash_kernel_body(cfg: FlashConfig, spec, dims, *refs):
    (bn, bj, jcount, S0, L1) = (dims['bn'], dims['bj'], dims['jcount'],
                                dims['S0'], dims['L1'])
    named = dict(zip(spec, refs[:len(spec)]))
    out_ref = refs[len(spec)]
    m_scr, l_scr, acc_scr = refs[len(spec) + 1:]
    j = pl.program_id(2)
    heads, kv_h = cfg.heads, cfg.kv_heads
    group = heads // kv_h
    q = named['q'][0].astype(jnp.float32)          # [bn, h, Dh]
    Dh = q.shape[-1]
    qr = q.reshape(bn, kv_h, group, Dh)

    @pl.when(j == 0)
    def _init():
        if cfg.prefix:
            pk = named['prefix_k'][0].reshape(bn, S0, kv_h, Dh)
            pv = named['prefix_v'][0].reshape(bn, S0, kv_h, Dh)
        else:
            pk = pv = None
        m0, l0, acc0 = _init_state(qr, pk, pv, cfg.scale, Dh)
        m_scr[...] = m0.reshape(bn, heads)
        l_scr[...] = l0.reshape(bn, heads)
        acc_scr[...] = acc0.reshape(bn, heads, Dh)

    # ---- the slot block's keyed features, built in VMEM ---- #
    # node features ride as flat [n, C*Q] refs (ONE minor-dim tile pad
    # per degree instead of Q -> 128 per channel row); unflatten after
    # the gather
    if cfg.mode == 'knn':
        idxb = named['idx'][0, 0]                  # [bn, bj] int32
        xg = tuple(
            jnp.take(named[f'x{i}'][0], idxb,
                     axis=0).reshape(bn, bj, c, 2 * d + 1)
            for i, (d, c) in enumerate(cfg.pairs))
        h_v = named['h_v'][0]
        h_k = named['h_k'][0] if 'h_k' in named else h_v
        sh = named['sh'][0] if 'sh' in named else None
        fr = unpack_frames(named['fr'][0]) if 'fr' in named else None
        maskb = named['nmask'][0, 0] if cfg.has_mask else None
    else:
        ci = named['coords_i'][0]                  # [bn, 3]
        cj = named['coords_j'][0]                  # [bj, 3]
        rel = ci[:, None, :] - cj[None, :, :]
        rp_v = tuple(named[f'rpv{i}'][...] for i in range(8))
        rp_k = tuple(named[f'rpk{i}'][...] for i in range(8)) \
            if 'rpk0' in named else None
        h_v, h_k, sh, fr = _global_edge_payload(cfg, rel, rp_v, rp_k)
        xg = tuple(
            jnp.broadcast_to(
                named[f'x{i}'][0].reshape(bj, c, 2 * d + 1)[None],
                (bn, bj, c, 2 * d + 1))
            for i, (d, c) in enumerate(cfg.pairs))
        maskb = None
        if cfg.has_mask:
            maskb = jnp.broadcast_to(named['nodemask'][0, 0], (bn, bj))
        if cfg.exclude_self:
            rows = pl.program_id(1) * bn + \
                jax.lax.broadcasted_iota(jnp.int32, (bn, bj), 0)
            cols = j * bj + \
                jax.lax.broadcasted_iota(jnp.int32, (bn, bj), 1)
            notself = rows != cols
            maskb = notself if maskb is None else (maskb & notself)

    consts = {k[2:]: named[k][...] for k in spec if k.startswith('c_')}
    kv_v = _kv_block(cfg.arm_v, cfg.pairs, cfg.d_out, xg, h_v, sh, fr,
                     named['wv'][...], named['bv'][...], consts,
                     w3_scale=(named['wv_scale'][...]
                               if 'wv_scale' in named else None))
    kv_v = kv_v.reshape(bn, bj, kv_h, Dh)
    if cfg.tie:
        kv_k = kv_v
    else:
        kv_k = _kv_block(cfg.arm_k, cfg.pairs, cfg.d_out, xg, h_k, sh,
                         fr, named['wk'][...], named['bk'][...], consts,
                         w3_scale=(named['wk_scale'][...]
                                   if 'wk_scale' in named else None))
        kv_k = kv_k.reshape(bn, bj, kv_h, Dh)

    # slots past the true axis length exist only because of the block
    # quantum — hard-zeroed so padding never changes a row's result
    inb = None
    if dims['slots'] % bj != 0:
        inb = (j * bj + jax.lax.iota(jnp.int32, bj)) < dims['slots']

    m = m_scr[...].reshape(bn, kv_h, group)
    l = l_scr[...].reshape(bn, kv_h, group)
    acc = acc_scr[...].reshape(bn, kv_h, group, Dh)
    m, l, acc = _attend_block(qr, kv_k, kv_v, maskb, m, l, acc,
                              cfg.scale, inbounds=inb)
    m_scr[...] = m.reshape(bn, heads)
    l_scr[...] = l.reshape(bn, heads)
    acc_scr[...] = acc.reshape(bn, heads, Dh)

    @pl.when(j == jcount - 1)
    def _finalize():
        out_ref[0] = (acc / l[..., None]).reshape(
            bn, heads, Dh).astype(out_ref.dtype)


@partial(jax.jit, static_argnames=('cfg',))
def _flash_fwd_impl(cfg: FlashConfig, ops: dict) -> jnp.ndarray:
    """The Pallas forward: grid (B, node blocks, slot blocks) with the
    slot axis INNERMOST so the online-softmax scratch state is carried
    sequentially; out written at the last slot block."""
    q = ops['q']
    B, n, heads, Dh = q.shape
    kv_h = cfg.kv_heads
    shape = _shape_key(cfg, ops)
    bn, bj = _pick_flash_blocks(shape, jnp.dtype(q.dtype).name)
    bn = min(bn, _round_up(n, 8))

    def pad_nodes(a, fill=0):
        if a is None:
            return None
        n_pad = _round_up(n, bn)
        if n_pad == n:
            return a
        pad = [(0, 0)] * a.ndim
        pad[1] = (0, n_pad - n)
        return jnp.pad(a, pad, constant_values=fill)

    n_p = _round_up(n, bn)
    spec_names, in_specs, args = [], [], []

    def add(name, arr, block, index_map):
        spec_names.append(name)
        in_specs.append(pl.BlockSpec(block, index_map,
                                     memory_space=pltpu.VMEM))
        args.append(arr)

    add('q', pad_nodes(q), (1, bn, heads, Dh),
        lambda b, i, j: (b, i, 0, 0))

    if cfg.mode == 'knn':
        K = ops['idx'].shape[-1]
        K_p = _round_up(K, min(bj, _round_up(K, 8)))
        bj = min(bj, K_p)
        jcount = K_p // bj
        slots = K

        def pad_slots(a, fill=0):
            if a is None or a.shape[2] == K_p:
                return a
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, K_p - a.shape[2])
            return jnp.pad(a, pad, constant_values=fill)

        def slot_major(a):
            """[B, n_p, K_p] -> [B, jcount, n_p, bj]: Mosaic wants a
            block's last two dims (8, 128)-divisible or the array's
            full extent, and a bj-wide slot block of a [.., n_p, K_p]
            operand is neither on its minor axis. With the slot-block
            index as its own axis the (bn, bj) block spans the whole
            minor axis."""
            return jnp.swapaxes(
                a.reshape(a.shape[0], n_p, jcount, bj), 1, 2)

        # padded slots are hard-zeroed by the `inbounds` vector in the
        # kernel body, so no mask is needed for them
        add('idx', slot_major(pad_slots(pad_nodes(ops['idx']))),
            (1, 1, bn, bj), lambda b, i, j: (b, j, i, 0))
        if cfg.has_mask:
            add('nmask', slot_major(pad_slots(
                pad_nodes(ops['nmask'], False), False)),
                (1, 1, bn, bj), lambda b, i, j: (b, j, i, 0))
        mid = ops['h_v'].shape[-1]
        add('h_v', pad_slots(pad_nodes(ops['h_v'])), (1, bn, bj, mid),
            lambda b, i, j: (b, i, j, 0))
        if not cfg.tie and 'h_k' in ops:
            add('h_k', pad_slots(pad_nodes(ops['h_k'])),
                (1, bn, bj, mid), lambda b, i, j: (b, i, j, 0))
        if 'sh' in ops:
            S = ops['sh'].shape[-1]
            add('sh', pad_slots(pad_nodes(ops['sh'])), (1, bn, bj, S),
                lambda b, i, j: (b, i, j, 0))
        if 'fr' in ops:
            FL = ops['fr'].shape[-1]
            add('fr', pad_slots(pad_nodes(ops['fr'])), (1, bn, bj, FL),
                lambda b, i, j: (b, i, j, 0))
        for i, x in enumerate(ops['xs']):
            x2 = x.reshape(x.shape[0], x.shape[1], -1)   # [B, n, C*Q]
            add(f'x{i}', x2, (1,) + x2.shape[1:],
                lambda b, i_, j: (b, 0, 0))
        L1 = (ops['fr'].shape[-1] // 4) if 'fr' in ops else 0
    else:
        bj = min(bj, _round_up(n, 8))
        n_pj = _round_up(n, bj)
        jcount = n_pj // bj
        slots = n

        def pad_cols(a, axis, fill=0):
            if a.shape[axis] == n_pj:
                return a
            pad = [(0, 0)] * a.ndim
            pad[axis] = (0, n_pj - a.shape[axis])
            return jnp.pad(a, pad, constant_values=fill)

        add('coords_i', pad_nodes(ops['coords']), (1, bn, 3),
            lambda b, i, j: (b, i, 0))
        add('coords_j', pad_cols(ops['coords'], 1), (1, bj, 3),
            lambda b, i, j: (b, j, 0))
        if cfg.has_mask:
            # [B, n_pj] -> [B, jcount, 1, bj]: same minor-axis rule as
            # the kNN arm's idx/nmask (slot_major above)
            add('nodemask', pad_cols(ops['nodemask'], 1, False).reshape(
                B, jcount, 1, bj), (1, 1, 1, bj),
                lambda b, i, j: (b, j, 0, 0))
        for i, x in enumerate(ops['xs']):
            xp = pad_cols(x.reshape(x.shape[0], x.shape[1], -1), 1)
            add(f'x{i}', xp, (1, bj, xp.shape[-1]),
                lambda b, i_, j: (b, j, 0))
        for i, p in enumerate(ops['rp_v']):
            add(f'rpv{i}', p, p.shape, lambda b, i_, j: (0, 0))
        if 'rp_k' in ops:
            for i, p in enumerate(ops['rp_k']):
                add(f'rpk{i}', p, p.shape, lambda b, i_, j: (0, 0))
        L1 = 0

    add('wv', ops['wv'], ops['wv'].shape, lambda b, i, j: (0, 0, 0))
    add('bv', ops['bv'], ops['bv'].shape, lambda b, i, j: (0, 0))
    if 'wv_scale' in ops:
        # quantized grouped radial weights: the per-channel dequant
        # scales ride as their own [1, IF, O] input ref, like PR 11's
        # contraction constants
        add('wv_scale', ops['wv_scale'], ops['wv_scale'].shape,
            lambda b, i, j: (0, 0, 0))
    if not cfg.tie:
        add('wk', ops['wk'], ops['wk'].shape, lambda b, i, j: (0, 0, 0))
        add('bk', ops['bk'], ops['bk'].shape, lambda b, i, j: (0, 0))
        if 'wk_scale' in ops:
            add('wk_scale', ops['wk_scale'], ops['wk_scale'].shape,
                lambda b, i, j: (0, 0, 0))
    if cfg.prefix:
        S0 = cfg.prefix
        KD = kv_h * Dh
        add('prefix_k', pad_nodes(ops['prefix_k']), (1, bn, S0, KD),
            lambda b, i, j: (b, i, 0, 0))
        add('prefix_v', pad_nodes(ops['prefix_v']), (1, bn, S0, KD),
            lambda b, i, j: (b, i, 0, 0))
    # contraction constants (Q_J / canonical-band / Wigner-factor
    # tables): Pallas kernels cannot capture constant arrays, so every
    # one rides as a VMEM input ref
    for name, arr in sorted(_arm_consts(cfg).items()):
        carr = jnp.asarray(arr, jnp.float32)
        zeros = (0,) * carr.ndim
        add(f'c_{name}', carr, carr.shape,
            lambda b, i, j, _z=zeros: _z)

    dims = dict(bn=bn, bj=bj, jcount=jcount, S0=cfg.prefix, L1=L1,
                slots=slots)
    kernel = partial(_flash_kernel_body, cfg, tuple(spec_names), dims)
    out = pl.pallas_call(
        kernel,
        grid=(B, n_p // bn, jcount),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bn, heads, Dh),
                               lambda b, i, j: (b, i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, n_p, heads, Dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, heads), jnp.float32),
            pltpu.VMEM((bn, heads), jnp.float32),
            pltpu.VMEM((bn, heads, Dh), jnp.float32),
        ],
        interpret=cfg.interpret,
    )(*args)
    return out[:, :n]


# --------------------------------------------------------------------- #
# dispatch + recompute-in-backward custom_vjp
# --------------------------------------------------------------------- #

def _dispatch(cfg: FlashConfig, ops: dict) -> jnp.ndarray:
    shape = _shape_key(cfg, ops)
    if cfg.use_pallas or cfg.interpret:
        # kNN mode holds the node-feature operands VMEM-resident at
        # full n — a shape whose resident set busts the scoped budget
        # at EVERY block size must fall back to the XLA stream, not
        # surface a Mosaic VMEM error (the fused_attention_fits idiom)
        if cfg.interpret or flash_admissible_blocks(shape):
            return _flash_fwd_impl(cfg, ops)
        import warnings
        warnings.warn(
            f'flash kernel working set (shape {shape}) exceeds the '
            f'scoped-VMEM budget at every block size; using the XLA '
            f'streaming path', stacklevel=2)
    chunks = _pick_stream_chunks(shape, jnp.dtype(ops['q'].dtype).name,
                                 kind=_stream_kind(cfg))
    return _flash_stream(cfg, ops, chunks)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg: FlashConfig, ops: dict) -> jnp.ndarray:
    return _dispatch(cfg, ops)


def _flash_core_fwd(cfg, ops):
    # residuals are the INPUTS only — no basis, keyed features, or
    # scores survive the forward
    return _dispatch(cfg, ops), ops


def _flash_core_bwd(cfg, ops, g):
    # recompute-in-backward: replay the chunked XLA streaming path under
    # jax.vjp — activations exist one node chunk at a time, composing
    # with the reversible trunk's outer remat for near-O(1) memory
    shape = _shape_key(cfg, ops)
    chunks = _pick_stream_chunks(shape, jnp.dtype(ops['q'].dtype).name,
                                 kind=_stream_kind(cfg))
    _, vjp = jax.vjp(lambda o: _flash_stream(cfg, o, chunks), ops)
    (dops,) = vjp(g)
    return (dops,)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# What the TPU compiler says to each mode's kernel body once the block
# specs are legal (deviceless compile for a described v5e, jax 0.9.0 /
# libtpu 0.0.34; tests/test_tpu_compile.py holds each as a strict xfail,
# so the entry goes the day the arm compiles). The body is the XLA
# stream's jnp code run on a tile, and Mosaic lowers neither a general
# gather nor a dot with two batch dimensions.
MOSAIC_REFUSES = {
    'knn': "ValueError: Shape mismatch in input, indices and output — "
           "the in-tile neighbour gather, jnp.take of the [n, C*Q] "
           "features with a [block_n, block_j] index block (dense and "
           "so2 arms alike)",
    'global': "'tpu.matmul' op Not implemented: Up to 1 batch dim "
              "supported — the per-edge einsums carry (block_n, block_j) "
              "as two batch dimensions",
}


def _resolve_pallas(pallas: Optional[bool], interpret: bool,
                    mode: str) -> bool:
    """The selector. Interpret mode (the CPU tests) runs the kernel. On
    the chip no arm compiles yet (MOSAIC_REFUSES), so the default is the
    XLA stream by rule — never by catching the compiler — and asking
    for the kernel outright says why it cannot be had."""
    if interpret:
        return True
    if pallas:
        raise NotImplementedError(
            f'pallas=True: the flash kernel\'s {mode!r} arm does not '
            f'compile for the TPU ({MOSAIC_REFUSES[mode]}). Leave pallas '
            f'unset for the XLA streaming path, or use interpret mode.')
    return False


# --------------------------------------------------------------------- #
# public entry points
# --------------------------------------------------------------------- #

def flash_attention(q, xs, idx, nmask, h_v, wv, bv, *,
                    pairs, d_out, heads, kv_heads, scale,
                    arm_v='dense', arm_k=None, h_k=None, wk=None,
                    bk=None, sh=None, frames=None, prefix_k=None,
                    prefix_v=None, wv_scale=None, wk_scale=None,
                    pallas=None, interpret=False
                    ) -> jnp.ndarray:
    """Streaming kNN equivariant attention for ONE output degree.

    q [B, n, h, Dh] (Dh = dim_head * (2*d_out+1), (dim_head, m)-major);
    xs tuple of node features [B, n, C_i, Q_i] per input degree (pairs
    order); idx [B, n, K] neighbor ids; nmask [B, n, K] bool or None;
    h_v/h_k [B, n, K, mid] radial hiddens; wv/bv (wk/bk) the grouped
    radial params [mid, IF, O] / [IF, O] with O = kv_heads * dim_head;
    sh the flash_sh_payload stack (dense arm); frames the so2 edge
    frames dict (so2 arm); prefix_k/v [B, n, S0, kv_heads * Dh] the
    always-valid [global, null, self] slots. tie keys to values by
    omitting wk. Returns [B, n, h, Dh] float32.
    """
    tie = wk is None
    arm_k = arm_v if arm_k is None else arm_k
    cfg = FlashConfig(
        pairs=tuple((int(d), int(c)) for d, c in pairs),
        d_out=int(d_out), heads=int(heads), kv_heads=int(kv_heads),
        scale=float(scale), arm_v=arm_v, arm_k=arm_k, tie=tie,
        prefix=int(prefix_k.shape[2]) if prefix_k is not None else 0,
        has_mask=nmask is not None, mode='knn',
        use_pallas=_resolve_pallas(pallas, interpret, 'knn'),
        interpret=interpret)
    ops = dict(q=q, xs=tuple(xs), idx=idx, h_v=h_v, wv=wv, bv=bv)
    if wv_scale is not None:
        # quantized grouped radial weights (quant.QuantTensor split by
        # the caller): wv is int8/fp8 storage, the scale dequants
        # in-tile as an epilogue on the radial dot
        ops['wv_scale'] = jnp.asarray(wv_scale, jnp.float32)
    if nmask is not None:
        ops['nmask'] = nmask
    if not tie:
        ops.update(wk=wk, bk=bk)
        if wk_scale is not None:
            ops['wk_scale'] = jnp.asarray(wk_scale, jnp.float32)
        if h_k is not None:
            ops['h_k'] = h_k
    if 'dense' in (arm_v, arm_k if not tie else arm_v):
        assert sh is not None, 'dense arm needs the sh payload'
        ops['sh'] = sh
    if 'so2' in (arm_v, arm_k if not tie else arm_v):
        assert frames is not None, 'so2 arm needs the edge frames'
        ops['fr'] = pack_frames(frames)
    if prefix_k is not None:
        ops.update(prefix_k=prefix_k, prefix_v=prefix_v)
    with jax.named_scope('flash_attention'):
        return _flash_core(cfg, ops)


def flash_global_attention(q, xs, coords, rp_v, wv, bv, *,
                           pairs, d_out, heads, kv_heads, scale,
                           arm='dense', rp_k=None, wk=None, bk=None,
                           node_mask=None, prefix_k=None, prefix_v=None,
                           exclude_self=True, pallas=None,
                           interpret=False,
                           materialize=False) -> jnp.ndarray:
    """Graph-free global equivariant attention (no kNN truncation): every
    node attends to every other node, with rel_pos/rel_dist, the radial
    hidden (rp_* = the 8-tuple Dense-LN-GELU trunk params, 1-D leaves
    reshaped [1, mid]) and the harmonics/frames payload computed on the
    fly per tile — no per-edge tensor ever exists in HBM, activation
    memory is O(n) at O(n^2) compute. The large-assembly scenario.

    `materialize=True` is the CONTROL arm: the identical function run as
    one unchunked pass (every [B, n, n, ...] per-edge tensor in HBM,
    plain autodiff — no custom_vjp, no recompute). Same params, same
    math; only the memory story differs. The assembly smoke and
    bench --assembly A/B the two arms for parity and the peak-HBM
    ledger claim."""
    tie = wk is None
    cfg = FlashConfig(
        pairs=tuple((int(d), int(c)) for d, c in pairs),
        d_out=int(d_out), heads=int(heads), kv_heads=int(kv_heads),
        scale=float(scale), arm_v=arm, arm_k=arm, tie=tie,
        prefix=int(prefix_k.shape[2]) if prefix_k is not None else 0,
        has_mask=node_mask is not None, mode='global',
        exclude_self=bool(exclude_self),
        use_pallas=(False if materialize
                    else _resolve_pallas(pallas, interpret, 'global')),
        interpret=interpret)
    rp_v = tuple(p.reshape(1, -1) if p.ndim == 1 else p for p in rp_v)
    ops = dict(q=q, xs=tuple(xs), coords=coords, rp_v=rp_v, wv=wv, bv=bv)
    if node_mask is not None:
        ops['nodemask'] = node_mask
    if not tie:
        assert rp_k is not None, 'untied keys need their radial params'
        ops.update(rp_k=tuple(p.reshape(1, -1) if p.ndim == 1 else p
                              for p in rp_k), wk=wk, bk=bk)
    if prefix_k is not None:
        ops.update(prefix_k=prefix_k, prefix_v=prefix_v)
    if materialize:
        # one chunk == the fully-materialized all-pairs computation,
        # differentiated by plain autodiff (no recompute-in-backward):
        # the O(n^2)-memory reference the streaming arm is judged against
        with jax.named_scope('global_attention_materialized'):
            return _flash_stream(cfg, ops, 1)
    with jax.named_scope('flash_global_attention'):
        return _flash_core(cfg, ops)


def flash_global_attention_sharded(q, xs, coords, rp_v, wv, bv, *,
                                   mesh, pairs, d_out, heads, kv_heads,
                                   scale, axis_name='sp', overlap=True,
                                   arm='dense', rp_k=None, wk=None,
                                   bk=None, node_mask=None,
                                   prefix_k=None, prefix_v=None,
                                   exclude_self=True) -> jnp.ndarray:
    """Sequence-parallel global attention: node axis sharded over the
    `axis_name` mesh axis, the SOURCE blocks (coords / features / mask)
    rotated one hop per step via `parallel.ring.ring_scan` while each
    device folds the visiting block into its rows' online-softmax state.
    Per-device memory is O(n_local^2) per step and the only collectives
    are the ring's ppermutes — `analyze_hlo_comm` proves the compiled
    program free of full-width all-gathers (the PR 11 residue: the
    flash path used to bypass the ring exchange scope entirely).

    Same argument contract as `flash_global_attention` plus the mesh;
    bit-compatible results (the fold is `_attend_block`, the same
    online softmax the kernel and the stream run)."""
    from jax.sharding import PartitionSpec as P
    from ..parallel.ring import pcast_varying, ring_scan
    tie = wk is None
    cfg = FlashConfig(
        pairs=tuple((int(d), int(c)) for d, c in pairs),
        d_out=int(d_out), heads=int(heads), kv_heads=int(kv_heads),
        scale=float(scale), arm_v=arm, arm_k=arm, tie=tie,
        prefix=int(prefix_k.shape[2]) if prefix_k is not None else 0,
        has_mask=node_mask is not None, mode='global',
        exclude_self=bool(exclude_self))
    rp_v = tuple(p.reshape(1, -1) if p.ndim == 1 else p for p in rp_v)
    if rp_k is not None:
        rp_k = tuple(p.reshape(1, -1) if p.ndim == 1 else p for p in rp_k)
    n = q.shape[1]
    sp = mesh.shape[axis_name]
    assert n % sp == 0, f'n={n} must divide over {axis_name}={sp}'
    if node_mask is None:
        node_mask = jnp.ones(coords.shape[:2], bool)

    row = lambda ndim: P(None, axis_name, *([None] * (ndim - 2)))  # noqa: E731
    sharded = [q, coords, node_mask, *xs]
    in_specs = [row(a.ndim) for a in sharded]
    n_xs = len(xs)
    has_prefix = prefix_k is not None
    if has_prefix:
        sharded += [prefix_k, prefix_v]
        in_specs += [row(4), row(4)]
    # weights replicated on every device (the ring rotates activations,
    # never parameters)
    repl = [*rp_v, wv, bv]
    if not tie:
        assert rp_k is not None, 'untied keys need their radial params'
        repl += [*rp_k, wk, bk]
    in_specs += [P()] * len(repl)

    def local(q, coords, nmask, *rest):
        xs_l = rest[:n_xs]
        rest = rest[n_xs:]
        if has_prefix:
            pk, pv = rest[0], rest[1]
            rest = rest[2:]
        else:
            pk = pv = None
        rpv = rest[:8]
        rest = rest[8:]
        wv_l, bv_l = rest[0], rest[1]
        rest = rest[2:]
        rpk = wk_l = bk_l = None
        if not tie:
            rpk = rest[:8]
            wk_l, bk_l = rest[8], rest[9]
        return _global_sharded_local(
            cfg, q, xs_l, coords, nmask, pk, pv, rpv, rpk, wv_l, bv_l,
            wk_l, bk_l, axis_name=axis_name, overlap=overlap,
            pcast=pcast_varying, ring=ring_scan)

    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=row(4))
    with jax.named_scope('flash_global_attention_sharded'):
        return fn(*sharded, *repl)


def _global_sharded_local(cfg, q, xs, coords, nmask, prefix_k, prefix_v,
                          rp_v, rp_k, wv, bv, wk, bk, *, axis_name,
                          overlap, pcast, ring):
    """Per-shard body: every operand is this device's row block.
    Queries stay pinned; (coords, mask, features) rotate as the source
    blocks. Exactly sp ppermutes per operand, no other collectives."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, nl = q.shape[:2]
    Dh = q.shape[-1]
    kv_h = cfg.kv_heads
    group = cfg.heads // kv_h
    qr = q.reshape(b, nl, kv_h, group, Dh)
    if prefix_k is not None:
        S0 = cfg.prefix
        pk = prefix_k.reshape(b, nl, S0, kv_h, Dh)
        pv = prefix_v.reshape(b, nl, S0, kv_h, Dh)
    else:
        pk = pv = None
    m, l, acc = _init_state(qr, pk, pv, cfg.scale, Dh)
    m, l, acc = (pcast(t, axis_name) for t in (m, l, acc))
    consts = {k: jnp.asarray(v, jnp.float32)
              for k, v in _arm_consts(cfg).items()}
    row_gid = my_idx * nl + jnp.arange(nl, dtype=jnp.int32)

    def fold(carry, blocks, t):
        m, l, acc = carry
        cj, mask_j, *xs_j = blocks
        owner = (my_idx + t) % axis_size
        rel = coords[:, :, None, :] - cj[:, None, :, :]
        h_v, h_k, sh, fr = _global_edge_payload(cfg, rel, rp_v, rp_k)
        xg = tuple(jnp.broadcast_to(x[:, None], (b, nl, *x.shape[1:]))
                   for x in xs_j)
        kv_v = _kv_block(cfg.arm_v, cfg.pairs, cfg.d_out, xg, h_v, sh,
                         fr, wv, bv, consts).reshape(b, nl, nl, kv_h, Dh)
        if cfg.tie:
            kv_k = kv_v
        else:
            kv_k = _kv_block(cfg.arm_k, cfg.pairs, cfg.d_out, xg, h_k,
                             sh, fr, wk, bk,
                             consts).reshape(b, nl, nl, kv_h, Dh)
        maskb = jnp.broadcast_to(mask_j[:, None, :], (b, nl, nl)) \
            if cfg.has_mask else None
        if cfg.exclude_self:
            col_gid = owner * nl + jnp.arange(nl, dtype=jnp.int32)
            notself = (row_gid[:, None] != col_gid[None, :])[None]
            maskb = notself if maskb is None else (maskb & notself)
        return _attend_block(qr, kv_k, kv_v, maskb, m, l, acc, cfg.scale)

    m, l, acc = ring(fold, (m, l, acc), (coords, nmask, *xs),
                     axis_name, overlap=overlap)
    return (acc / l[..., None]).reshape(b, nl, cfg.heads, Dh)
