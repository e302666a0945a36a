"""Per-m radial convolution: the SE3TransformerV2 contraction layer.

The v1 so2 backend keeps the dense path's parameterization — a radial
trunk emitting [mid, C*F, O] blocks that couple every canonical-kernel
frequency to every output row — and gets its win purely from replacing
the basis contraction with the banded rotate-in/rotate-out reduction.
That still materializes a dense-basis-SHAPED radial output (the
``R = h @ w3`` intermediate is mid x C*F x O per edge), which caps the
measured speedup (ROADMAP item 2).

V2 goes the rest of the way (EquiformerV2, arXiv:2306.12059): the
radial trunk emits the per-+/-m banded weight blocks DIRECTLY.  For a
degree pair (d_in -> d_out) and each m <= min(d_in, d_out) the learned
per-edge kernel is the 2x2 rotation-like block

    [[a, b], [-b, a]]        acting on the (q = d_in - m, q = d_in + m)
                             component pair of the edge-frame features,

with (a, b) produced per (channel, output-channel) by
``R_m = h @ wm + bm`` — so R_m IS the banded block and nothing
dense-basis-shaped ever exists.  Exact equivariance is structural:
both the kernel block and the frame rotation's Dz blocks live in
span{I, [[0, 1], [-1, 0]]} on each +/-m pair (so2/frames._dz_apply's
index convention), hence commute; the m-truncation knob ``max_m``
(zeroing blocks with m > max_m, EquiformerV2's mmax) therefore costs
zero equivariance.

Spine reuse, per the family contract:

  * rotate-in / rotate-out come from so2/frames (hoisted once per
    input/output degree like ConvSE3's so2 branch);
  * the per-m apply is the existing ops.conv._radial_contract — the
    Pallas 'plain' kernel, QuantTensor fused dequant and node-axis
    streaming all serve v2 unchanged;
  * node-axis chunking consults the SAME 'so2' tuning kind
    (so2.contract._pick_so2_chunks), so scripts/tune_kernels.py owns
    the knob for both families;
  * the radial trunk is ops.conv.radial_hidden, so its Dense_0/Dense_1
    kernels keep the int8-safe quant class (invariant inputs).

No canonical-kernel table, no banded_z, no basis.get_basis — v2 never
imports them (tests/test_v2.py asserts this structurally by making
both raise during a v2 forward).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from ..ops.conv import _radial_contract, radial_hidden
from ..ops.core import LinearSE3, residual_se3
from ..ops.fiber import Fiber
from ..parallel.exchange import exchange_index_select
from ..quant.qtensor import concat_weights
from ..utils.helpers import masked_mean

Features = Dict[str, jnp.ndarray]
EdgeInfo = Tuple[Optional[jnp.ndarray], Optional[jnp.ndarray],
                 Optional[jnp.ndarray]]

# v2's compact default trunk width: the per-m blocks are [mid, 2C, O]
# instead of v1's [mid, C*F, O], so the trunk that feeds them can be
# narrow without starving the contraction (EquiformerV2 uses the same
# regime).
DEFAULT_V2_MID_DIM = 32


def v2_band_rows(d_in: int, d_out: int,
                 max_m: Optional[int] = None) -> int:
    """Band rows a (d_in -> d_out) pair contributes: 2 * M + 1 with
    M = min(d_in, d_out[, max_m]). The truncation is exactly
    equivariant (dropped blocks are identically zero weights)."""
    m = min(d_in, d_out)
    if max_m is not None:
        m = min(m, max_m)
    return 2 * m + 1


class V2ConvSE3(nn.Module):
    """Graph convolution over precomputed neighborhoods with per-m
    radial parameterization (module docstring). Same call contract as
    ConvSE3 except the basis dict is replaced by the edge ``frames``
    payload (v2 has exactly one backend — there is nothing dense to
    fall back to)."""
    fiber_in: Fiber
    fiber_out: Fiber
    self_interaction: bool = True
    pool: bool = True
    edge_dim: int = 0
    mid_dim: int = DEFAULT_V2_MID_DIM
    # EquiformerV2's mmax: truncate the per-m blocks at |m| <= max_m
    # (None = full band). Zero weights, not an approximation: exactly
    # equivariant at any setting.
    max_m: Optional[int] = None
    pallas: Optional[bool] = None
    pallas_interpret: bool = False
    edge_chunks: Optional[int] = None
    radial_bf16: bool = False

    def _per_m_params(self, m: int, degree_in: int, degree_out: int,
                      mid: int, m_in: int, m_out: int):
        """The (wm, bm) block for one (m, d_in, d_out) triple: K = 2C
        columns (the [a | b] halves of the 2x2 block) for m > 0, C for
        the unpaired m = 0 row."""
        K = m_in if m == 0 else 2 * m_in
        wm = self.param(
            f'wm{m}_{degree_in}_{degree_out}',
            nn.initializers.variance_scaling(1.0, 'fan_in',
                                             'truncated_normal',
                                             in_axis=0, out_axis=(1, 2)),
            (mid, K, m_out), jnp.float32)
        bm = self.param(f'bm{m}_{degree_in}_{degree_out}',
                        nn.initializers.zeros, (K, m_out), jnp.float32)
        return wm, bm

    @nn.compact
    def __call__(self, inp: Features, edge_info: EdgeInfo,
                 rel_dist: jnp.ndarray, frames) -> Features:
        from ..so2.contract import _pick_so2_chunks
        from ..so2.frames import rotate_in, rotate_out

        neighbor_indices, neighbor_masks, edges = edge_info

        edge_features = rel_dist[..., None]                # [b, n, k, 1]
        if edges is not None:
            edge_features = jnp.concatenate((edge_features, edges),
                                            axis=-1)

        hidden = radial_hidden(
            edge_features, self.mid_dim,
            dtype=jnp.bfloat16 if self.radial_bf16 else None)

        # gather + rotate into the edge frame ONCE per input degree
        # (ConvSE3's so2 hoist — rotations are parameter-free)
        rotated = {}
        for degree_in, _ in self.fiber_in:
            g = exchange_index_select(inp[str(degree_in)],
                                      neighbor_indices, axis=1)
            rotated[str(degree_in)] = rotate_in(g, frames, degree_in)

        # node-axis streaming rides _radial_contract's edge_chunks and
        # shares the 'so2' tuning kind (one autotuner knob for both
        # families); the layer-level key mirrors so2_pair_contract's
        max_din = max(d for d, _ in self.fiber_in)
        max_dout = max(d for d, _ in self.fiber_out)
        chunks = self.edge_chunks
        if chunks is None:
            cmax = max(c for _, c in self.fiber_in)
            omax = max(c for _, c in self.fiber_out)
            shape = (int(rel_dist.shape[1]), cmax, omax,
                     max_din, max_dout,
                     -1 if self.max_m is None else int(self.max_m))
            chunks = _pick_so2_chunks(shape,
                                      np.dtype(rel_dist.dtype).name)
        if chunks is not None and chunks <= 1:
            chunks = None

        outputs = {}
        for degree_out, m_out in self.fiber_out:
            # band order M (the +/-m reach of this output degree)
            M = min(degree_out, max_din)
            if self.max_m is not None:
                M = min(M, self.max_m)
            neg_rows, pos_rows = [], []
            center = None
            for m in range(M + 1):
                # every input degree whose band reaches m contributes;
                # segments concatenate along the contracted K axis
                # exactly like the grouped so2 path's z segments
                segs, wms, bms = [], [], []
                for degree_in, m_in in self.fiber_in:
                    if min(degree_in, degree_out) < m:
                        continue
                    wm, bm = self._per_m_params(
                        m, degree_in, degree_out, hidden.shape[-1],
                        m_in, m_out)
                    wms.append(wm)
                    bms.append(bm)
                    xr = rotated[str(degree_in)]   # [..., C, 2di+1]
                    if m == 0:
                        segs.append((xr[..., degree_in][..., None, :],))
                    else:
                        xneg = xr[..., degree_in - m]      # [..., C]
                        xpos = xr[..., degree_in + m]
                        row_neg = jnp.concatenate((xneg, xpos), axis=-1)
                        row_pos = jnp.concatenate((xpos, -xneg), axis=-1)
                        segs.append((row_neg[..., None, :],
                                     row_pos[..., None, :]))
                # v2_m [..., rows, K]: rows = (−m, +m) for m > 0
                rows = len(segs[0])
                v2_m = jnp.concatenate(
                    [jnp.concatenate([s[r] for s in segs], axis=-1)
                     for r in range(rows)], axis=-2)
                out_m = _radial_contract(
                    hidden, concat_weights(wms, axis=1),
                    jnp.concatenate(bms, axis=0), v2_m,
                    pallas=self.pallas,
                    pallas_interpret=self.pallas_interpret,
                    edge_chunks=chunks)            # [..., rows, O]
                if m == 0:
                    center = out_m[..., 0, :]
                else:
                    neg_rows.append(out_m[..., 0, :])
                    pos_rows.append(out_m[..., 1, :])
            # assemble the P axis: rows d_out-M .. d_out+M carry the
            # band, everything beyond (including m > max_m when
            # truncating) is structurally zero
            band = jnp.stack(
                neg_rows[::-1] + [center] + pos_rows,
                axis=-2)                           # [..., 2M+1, O]
            if degree_out > M:
                pad = [(0, 0)] * band.ndim
                pad[-2] = (degree_out - M, degree_out - M)
                band = jnp.pad(band, pad)
            acc = rotate_out(jnp.swapaxes(band, -1, -2), frames,
                             degree_out)           # [..., O, P]

            if self.pool:
                acc = masked_mean(acc, neighbor_masks, axis=2) \
                    if neighbor_masks is not None else acc.mean(axis=2)
            outputs[str(degree_out)] = acc

        if self.self_interaction:
            assert self.pool, \
                'must pool edges if followed with self interaction'
            self_out = LinearSE3(self.fiber_in, self.fiber_out,
                                 name='self_interact')(inp)
            outputs = residual_se3(outputs, self_out)

        # same remat tag as ConvSE3: under save_only_these_names the
        # trunk's backward replay fetches these instead of re-running
        # the per-m contractions
        from jax.ad_checkpoint import checkpoint_name
        outputs = {k: checkpoint_name(v, 'conv_out')
                   for k, v in outputs.items()}
        return outputs
