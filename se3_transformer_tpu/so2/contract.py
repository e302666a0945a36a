"""The banded SO(2) contraction: conv backend 'so2'.

Computes the SAME function as the dense PairwiseConvSE3 fused path —
identical parameters (the radial trunk's w3/b3), identical output
contract [..., c_out, P] — through the eSCN factorization:

    out = D_out(R_e) . RadialApply( Banded( D_in(R_e)^T x ) )

  1. rotate-in   xr = D_in^T x            (frames.rotate_in: banded)
  2. banded      z[p, (c, f)] = (Kc_f xr_c)[p]
                 — Kc_f is the canonical-axis kernel, nonzero ONLY on
                 the |m_out| == |m_in| band (canonical.canonical_blocks),
                 so this is elementwise multiplies on the +/-m component
                 pairs: O(C * F * mmin) per edge versus the dense path's
                 O(C * P * Q * F) basis contraction;
  3. radial      out_rot = _radial_contract(h, w3, b3, z)
                 — EXACTLY the dense path's fused radial matmul (z is
                 shape-identical to the dense V2), so the Pallas 'plain'
                 kernel and the PR 4 tuning table apply to the so2
                 backend unchanged;
  4. rotate-out  out = D_out out_rot      (frames.rotate_out)

Tuning: the node-axis streaming of steps 1-4 is registered as kernel
kind 'so2' in kernels/tuning.py — blocks = (chunks,), 1 = unchunked.
`_pick_so2_chunks` resolves env override > forced candidate > measured
table > heuristic and records every consult, so scripts/tune_kernels.py
owns the knob end-to-end like the Pallas block sizes.
"""
from __future__ import annotations

import os
import warnings
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .canonical import canonical_blocks
from .frames import Frames, rotate_in, rotate_out

# frames payload keys in node-axis order — the streaming split and the
# contract closure must agree on this order
_FRAME_KEYS = ('cos_a', 'sin_a', 'cos_b', 'sin_b')


def banded_z(xr: jnp.ndarray, d_in: int, d_out: int,
             pad_rows: bool = True) -> jnp.ndarray:
    """Apply the canonical banded kernels: xr [..., C, Q] in the edge
    frame -> z [..., P, C * F], the drop-in replacement for the dense
    path's V2 = basis . x (same shape, same (c, f) minor ordering).

    Per +/-m pair the 2x2 rotation-like block [[a, b], [-b, a]] acts as
    elementwise multiplies; rows with |m_out| > min(d_in, d_out) are
    structurally zero (the band) and are filled by a static pad —
    unless `pad_rows=False`, which returns only the
    B = 2 * min(d_in, d_out) + 1 band rows so the radial matmul that
    consumes z can skip the zero rows entirely (a (0, 6) pair then
    contracts 1 row instead of 13; so2_pair_contract pads AFTER the
    radial apply instead)."""
    a_np, b_np = canonical_blocks(d_in, d_out)
    mmin = min(d_in, d_out)
    F = a_np.shape[0]
    C = xr.shape[-2]
    a = jnp.asarray(a_np, xr.dtype)            # [F, mmin + 1]
    b = jnp.asarray(b_np, xr.dtype)

    # +/-m component pairs of the edge-frame features
    idx_neg = np.arange(d_in, d_in - mmin - 1, -1)   # q = d_in - m
    idx_pos = np.arange(d_in, d_in + mmin + 1)       # q = d_in + m
    xneg = xr[..., idx_neg][..., None, :]             # [..., C, 1, M+1]
    xpos = xr[..., idx_pos][..., None, :]
    zneg = a * xneg + b * xpos                        # [..., C, F, M+1]
    zpos = a * xpos - b * xneg

    # assemble the P axis: rows d_out - mmin .. d_out + mmin carry the
    # band (m = 0 row once — b[:, 0] == 0 makes zneg[..., 0] the value),
    # everything beyond is zero
    band = jnp.concatenate(
        (zneg[..., :0:-1], zneg[..., :1], zpos[..., 1:]), axis=-1)
    band = jnp.moveaxis(band, -1, -3)                 # [..., band, C, F]
    if pad_rows and d_out > mmin:
        pad = [(0, 0)] * band.ndim
        pad[-3] = (d_out - mmin, d_out - mmin)
        band = jnp.pad(band, pad)
    return band.reshape(*band.shape[:-2], C * F)     # [..., P|B, C*F]


def _pick_so2_chunks(shape, dtype: str) -> int:
    """Node-axis chunk count for streaming the so2 contraction
    (1 = unchunked, the heuristic default — the banded working set is
    small; chunking exists for huge channel counts and as the
    autotuner's measurable knob). Precedence: env > forced/table >
    heuristic, every resolution recorded (kernels/tuning.py)."""
    from ..kernels import tuning

    env = os.environ.get('SE3_TPU_SO2_CHUNKS', '')
    if env:
        chunks = max(1, int(env))
        tuning.record_consult('so2', shape, dtype, 'env', (chunks,))
        return chunks
    hit = tuning.lookup('so2', shape, dtype=dtype)
    if hit is not None:
        blocks, source = hit
        if source == 'forced' or tuning.validate_entry('so2', shape,
                                                       blocks):
            tuning.record_consult('so2', shape, dtype, source, blocks)
            return int(blocks[0])
    heuristic = (1,)
    tuning.record_consult('so2', shape, dtype, 'heuristic', heuristic)
    return heuristic[0]


def so2_pair_contract(h: jnp.ndarray, w3: jnp.ndarray, b3: jnp.ndarray,
                      frames: Frames, x: jnp.ndarray, *, d_in: int,
                      d_out: int, pallas: Optional[bool],
                      pallas_interpret: bool,
                      edge_chunks: Optional[int],
                      edge_frame_io: bool = False) -> jnp.ndarray:
    """One (d_in -> d_out) pairwise contraction via the SO(2) reduction:
    h [b, n, k, mid], w3 [mid, C*F, O], b3 [C*F, O], x [b, n, k, C, Q]
    -> [b, n, k, O, P] (the dense path's post-swap output contract).

    `edge_frame_io=True` is ConvSE3's rotation-hoisting protocol: `x`
    arrives ALREADY rotated into the edge frame and the output is
    returned edge-frame too (the caller rotates in once per input
    degree and back once per output degree — without the hoist a
    degree-6 layer would redo the rotations for every one of its 49
    pairs, which measured as most of the so2 step).

    `edge_chunks` keeps the dense path's meaning (explicit node-axis
    streaming); when None the tuning table's 'so2' kind decides."""
    from ..ops.conv import _radial_contract, _stream_node_chunks

    C, Q = x.shape[-2], x.shape[-1]
    P = 2 * d_out + 1
    F = 2 * min(d_in, d_out) + 1
    O = w3.shape[-1]
    chunks = edge_chunks
    if chunks is None:
        shape = (int(x.shape[1]), C, O, P, Q, F)
        chunks = _pick_so2_chunks(shape, np.dtype(x.dtype).name)
        if chunks <= 1:
            chunks = None

    mmin = min(d_in, d_out)

    def contract(h_c, x_c, *frame_arrays):
        if edge_frame_io:
            xr = x_c
        else:
            frames_c = dict(zip(_FRAME_KEYS, frame_arrays))
            xr = rotate_in(x_c, frames_c, d_in)
        # band rows only through the radial matmul (the |m| > mmin rows
        # of z are structurally zero — contracting them would waste
        # (P - B) / P of the apply flops); pad back to P after
        z = banded_z(xr, d_in, d_out, pad_rows=False)
        out_rot = _radial_contract(h_c, w3, b3, z, pallas=pallas,
                                   pallas_interpret=pallas_interpret,
                                   edge_chunks=None)     # [..., B, O]
        out = jnp.swapaxes(out_rot, -1, -2)              # [..., O, B]
        if d_out > mmin:
            pad = [(0, 0)] * out.ndim
            pad[-1] = (d_out - mmin, d_out - mmin)
            out = jnp.pad(out, pad)                      # [..., O, P]
        if edge_frame_io:
            return out
        return rotate_out(out, frames_c, d_out)

    operands = (h, x) + (() if edge_frame_io
                         else tuple(frames[k] for k in _FRAME_KEYS))
    if chunks is None:
        return contract(*operands)
    return _stream_node_chunks(contract, operands, chunks)


def _register():
    from ..ops.conv import register_conv_backend
    register_conv_backend('so2', so2_pair_contract)


_register()
