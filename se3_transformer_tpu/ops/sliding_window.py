"""A causal sliding window over T positions: query i sees key j exactly when
0 <= i - j < window, the token itself counted (`visible`), so a row sees
min(i + 1, window) keys and a head T w - w (w - 1) / 2 pairs for w =
min(window, T) (`visible_pairs`): at T = 16,384 and a window of 4,096,
58.7 M of the causal triangle's 134.2 M.

Two forms, as the block-diffusion core has; `kernels_run` says which a layer
takes:

  * off the TPU `ops/latent_attention.py::causal_attention_blocked` with a
    `window`: blocks of queries against static key extents that start at
    the window's far edge, each block recomputed in the backward pass;
  * on the TPU the repo's own two launches
    (`kernels/pallas_block_attention.py` under the rule ('swa', window):
    `swa_core_fwd`, `swa_core_bwd`) over a static table of the tiles that
    hold a visible pair (`visited_tiles`: no tile wholly outside the window
    is launched), the comparison on the diagonal and the far edge alone
    (`boundary_tiles`), every operand in the projections' own layout and
    the rotation in `kernels/pallas_qk_pass.py`'s one pass, exactly as the
    block-diffusion core runs them.

A window of T or more is the causal triangle, and a decoder's global layers
(no window at all) ask the same predicate and take the same launches over
that table, `window_table(T, T, tile)`, under a rule and a leaf of their
own (('mha', 0): `mha_core_fwd`, `mha_core_bwd` under `mha_core`), so that
`swa_core` holds a model's sliding layers and nothing else. Where the
predicate fails (heads of 64, a length no tile divides, off the TPU) they
keep `ops/latent_attention.py::causal_attention`.
"""
from __future__ import annotations

import numpy as np

from ..kernels import pallas_block_attention as kernels
from ..utils.helpers import is_tpu_backend


def visible(q_pos, k_pos, window: int):
    """Whether the query at `q_pos` sees the key at `k_pos` (broadcast
    against each other)."""
    return (k_pos <= q_pos) & (q_pos - k_pos < window)


def visible_pairs(positions: int, window: int) -> int:
    """The (query, key) pairs a head computes."""
    w = min(window, positions)
    return positions * w - w * (w - 1) // 2


def visited_tiles(positions: int, window: int, tile: int) -> int:
    """The (query tile, key tile) pairs a head's launches compute: the
    columns of the table both take their grid from."""
    return kernels.window_table(positions, window, tile).shape[1]


def boundary_tiles(positions: int, window: int, tile: int) -> int:
    """Those of them that evaluate the rule: the diagonal and the window's
    far edge."""
    kinds = kernels.window_table(positions, window, tile)[kernels.KIND]
    return int(np.count_nonzero(kinds != kernels.FULL))


def kernels_run(positions: int, block: int, heads: int, kv_heads: int,
                head_dim: int) -> bool:
    """Whether a layer over one stream of `positions`, with a window or
    without, takes the kernels (on a TPU, at the shapes of
    `kernels.launches_run`, tiles of `block` or the sequence) or the
    composition and its core, from the platform and the shapes alone."""
    return is_tpu_backend() and kernels.launches_run(
        positions, min(block, positions), heads, kv_heads, head_dim)
