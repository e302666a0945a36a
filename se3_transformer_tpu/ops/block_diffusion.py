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
grouped heads (q [B, H, 2L, D], k and v [B, KV, 2L, D], unrepeated):

  * off the TPU, blocks of queries against static key extents (a noised
    block meets its own blocks of the noised stream and the clean prefix, a
    clean block the clean prefix through its own blocks; the clean stream's
    upper half and the clean -> noised quarter are never computed), the mask
    made from the stream ids, each block recomputed in the backward pass;
  * on the TPU JAX's splash attention (`jax.experimental.pallas.ops.tpu.
    splash_attention`) under a mask it computes inside the kernel from row
    and column ids: tiles with no visible pair are never visited
    (`visited_tiles` reads the launch's own tile table), and the forward's
    output and log-sum-exp carry `ATTN_CORE_OUT`, which `SAVE_ATTN_CORE`
    keeps, so a rematted block's replay launches no forward. The launches
    are the library's: `splash_mha_fwd_residuals`, `splash_mha_dkv_*`,
    `splash_mha_dq_*`.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.helpers import is_tpu_backend
from .latent_attention import ATTN_CORE_OUT


def _div(a, n: int):
    """a // n for a >= 0; a shift where n is a power of two (the kernel's
    vector unit has no integer division)."""
    return a >> (n.bit_length() - 1) if n & (n - 1) == 0 else a // n


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


def _streams_mask(length: int, block_length: int):
    """The library's computable mask for `visible`: it evaluates the rule on
    NumPy ids for the tile tables and inside the kernels on the tiles'."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as mask_lib,
    )

    class StreamsMask(mask_lib._ComputableMask):
        def __eq__(self, other):
            return isinstance(other, StreamsMask)

        def __hash__(self):
            return hash((StreamsMask, self.shape))

    return StreamsMask(
        shape=(2 * length, 2 * length),
        mask_function=lambda q_ids, kv_ids: visible(
            q_ids, kv_ids, length, block_length))


@lru_cache(maxsize=None)
def splash_kernel(heads: int, length: int, block_length: int, block: int,
                  interpret: bool = False):
    """The library's kernel object over `heads` query heads for a sequence
    of `length` tokens (2 length positions) at tiles of `block`: the three
    launches' tile tables, built once a process from the mask (seconds at
    16,384 positions) as constants of whatever program calls it.
    `interpret`: the kernels in Pallas's interpret mode, for a test off the
    TPU."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as mask_lib,
    )
    mask = mask_lib.MultiHeadMask(
        [_streams_mask(length, block_length)] * heads)
    b = min(block, length)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            mask, block_sizes=sizes, head_shards=1, q_seq_shards=1,
            residual_checkpoint_name=ATTN_CORE_OUT, interpret=interpret)


def visited_tiles(kernel) -> int:
    """The (query tile, key tile) pairs a head's forward launch computes: the
    non-zero entries of its tile table. The grid's other programs load
    nothing and run nothing."""
    table = np.asarray(kernel.fwd_mask_info.block_mask)
    return int(np.count_nonzero(table)) // table.shape[0]


def block_diffusion_attention_splash(q, k, v, scale: float,
                                     block_length: int, block: int = 512,
                                     interpret: bool = False):
    """The same on the TPU's streaming kernel: operands rounded to bfloat16,
    float32 softmax and accumulation, the scale folded into q (the library
    takes none)."""
    kernel = splash_kernel(q.shape[1], q.shape[2] // 2, block_length, block,
                           interpret)
    q = (q * scale).astype(jnp.bfloat16)
    k, v = (a.astype(jnp.bfloat16) for a in (k, v))
    return jax.vmap(kernel)(q, k, v).astype(jnp.float32)


def block_diffusion_attention(q, k, v, scale: float, block_length: int,
                              block: int = 512):
    """The streaming kernel where it can run (on a TPU, at a length its
    tiles divide), blocks of queries elsewhere."""
    core = block_diffusion_attention_splash \
        if is_tpu_backend() and (q.shape[2] // 2) % 128 == 0 \
        else block_diffusion_attention_blocked
    return core(q, k, v, scale, block_length, block)
