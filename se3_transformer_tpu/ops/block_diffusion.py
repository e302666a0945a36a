"""The attention core of a decoder trained by diffusion over blocks.

A sequence of L tokens is read twice in one pass, 2 L positions: its noised
copy first (stream ids 0 .. L - 1), then the clean one (L .. 2 L - 1). With
b(i) = i // block_length the block of a token, query q sees key k exactly
when (`visible`):

    noised -> noised:  b(q) = b(k)      bidirectional inside its own block
    noised -> clean:   b(k) < b(q)      the clean prefix before its block
    clean  -> clean:   b(k) <= b(q)     block-causal
    clean  -> noised:  never

L^2 + L block_length visible pairs a sequence (`visible_pairs`): half of
what a causal core over 2 L positions computes, and no subset of it. Every
row sees a key (a noised token its own block, a clean one itself).

Two forms, as the causal core has (`ops/latent_attention.py`), both over
grouped heads, unrepeated; `kernels_run` says which a layer takes:

  * off the TPU, over q [B, H, 2L, D], k and v [B, KV, 2L, D], blocks of
    queries against static key extents (a noised block meets its own blocks
    of the noised stream and the clean prefix, a clean block the clean
    prefix through its own blocks; the clean stream's upper half and the
    clean -> noised quarter are never computed), the mask made from the
    stream ids, each block recomputed in the backward pass;
  * on the TPU the repo's own kernels (`kernels/pallas_block_attention.py`)
    over q [B, 2L, H D], k and v [B, 2L, KV D], the projections' own layout
    (norm, rotation, scale and rounding are one launch before them,
    `kernels/pallas_qk_pass.py`, under the same differentiation rule), and
    over a static table of the tiles that hold a visible pair: the grid is
    those tiles and no other (`visited_tiles`), the rule runs on the tiles a
    boundary crosses alone (`boundary_tiles`), and the forward's output and
    log-sum-exp carry `ATTN_CORE_OUT` / `ATTN_CORE_STATS`, which
    `SAVE_ATTN_CORE` keeps, so a rematted block's replay launches no
    forward. The launches: `bd_core_fwd`, `bd_core_bwd`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import pallas_block_attention as kernels
from ..kernels.pallas_block_attention import floor_div as _div
from ..utils.helpers import is_tpu_backend


def _mod(a, n: int):
    return a & (n - 1) if n & (n - 1) == 0 else a % n


def visible(q_ids, kv_ids, length: int, block_length: int):
    """Whether query `q_ids` sees key `kv_ids` (stream ids in 0 .. 2 length,
    broadcast against each other), by the four lines above. Comparisons and
    bitwise operators alone: NumPy arrays give a NumPy mask, and inside a
    kernel nothing selects between booleans (Mosaic refuses that)."""
    q_noised, k_noised = q_ids < length, kv_ids < length
    qb = _div(_mod(q_ids, length), block_length)
    kb = _div(_mod(kv_ids, length), block_length)
    return (k_noised & q_noised & (qb == kb)) | (
        ~k_noised & ((q_noised & (kb < qb)) | (~q_noised & (kb <= qb))))


def visible_pairs(length: int, block_length: int) -> int:
    """The (query, key) pairs a sequence's two streams compute."""
    return length * length + length * block_length


def block_diffusion_attention_blocked(q, k, v, scale: float,
                                      block_length: int, block_q: int = 512):
    """q [B, H, 2L, D], k, v [B, KV, 2L, D] -> [B, H, 2L, D] float32."""
    b, h, t2, d = q.shape
    kv = k.shape[1]
    length = t2 // 2
    assert t2 == 2 * length and h % kv == 0 and length % block_length == 0, \
        (q.shape, k.shape, block_length)
    bq = min(block_q, length)
    assert length % bq == 0, (length, bq)
    q = q.reshape(b, kv, h // kv, t2, d)

    @jax.checkpoint
    def block(qi, kj, vj, q_ids, k_ids):
        s = jnp.einsum('bgrqd,bgkd->bgrqk', qi, kj,
                       preferred_element_type=jnp.float32) * scale
        seen = visible(q_ids[:, None], k_ids[None, :], length, block_length)
        s = jnp.where(seen, s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum('bgrqk,bgkd->bgrqd', p, vj,
                          preferred_element_type=jnp.float32)

    def cut(a, extents):
        parts = [a[:, :, lo:hi] for lo, hi in extents if hi > lo]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=2)

    outs = []
    for stream in (0, length):          # noised queries, then clean
        for i in range(0, length, bq):
            # the rows' blocks, whole: noised keys lo .. hi; the clean
            # prefix through a clean query's own block, before the last
            # noised query's (earlier rows' masks cut it shorter)
            lo = i // block_length * block_length
            hi = -(-(i + bq) // block_length) * block_length
            extents = [(length, length + hi)] if stream \
                else [(lo, hi), (length, length + hi - block_length)]
            ids = np.concatenate([np.arange(*e) for e in extents])
            rows = slice(stream + i, stream + i + bq)
            outs.append(block(q[:, :, :, rows], cut(k, extents),
                              cut(v, extents),
                              np.arange(rows.start, rows.stop), ids))
    return jnp.concatenate(outs, axis=3).reshape(b, h, t2, d)


def visited_tiles(length: int, block_length: int, tile: int) -> int:
    """The (query tile, key tile) pairs a head's launches compute: the
    columns of the table both take their grid from, none of them idle."""
    return kernels.tile_table(length, block_length, tile).shape[1]


def boundary_tiles(length: int, block_length: int, tile: int) -> int:
    """Those of them that evaluate the rule; the others are wholly visible
    and run with no compare and no select."""
    kinds = kernels.tile_table(length, block_length, tile)[kernels.KIND]
    return int(np.count_nonzero(kinds != kernels.FULL))


def kernels_run(length: int, block_length: int, block: int, heads: int,
                kv_heads: int, head_dim: int) -> bool:
    """Whether a layer of these shapes takes the kernels (on a TPU, at the
    shapes of `kernels.can_run`, tiles of `block` or a stream's length) or
    the blocked core, from the platform and the shapes alone."""
    return is_tpu_backend() and kernels.can_run(
        length, block_length, min(block, length), heads, kv_heads, head_dim)
