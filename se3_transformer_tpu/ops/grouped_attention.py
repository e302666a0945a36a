"""Grouped-query causal attention; per-head q/k norms, rotation and a
sliding window are optional:

    q = u Wq -> `heads` heads of `head_dim`;  k, v = u Wk, u Wv ->
    `kv_heads` heads, each shared by heads / kv_heads query heads;
    `qk_norm`:     q, k <- RMSNorm over each head's channels, one scale of
                   `head_dim` for the queries and one for the keys
    `rope_theta`:  q, k <- rotation by positions 0 .. T - 1 at this base,
                   all `head_dim` channels, pairs (i, i + head_dim / 2);
                   None: no rotation (a decoder whose state-space layers
                   carry position), and with `qk_norm` off the parameter
                   tree and the arithmetic are what they were without both
    `window`:      key j is visible to query i iff 0 <= i - j < window
                   (the token itself counts); 0: every key at or before i
    softmax(q k^T / sqrt(head_dim)), causal, in float32;  out = o Wo

Three cores, each under a leaf of its own, so one table tells them apart:
`mha_core` a global layer's (every key at or before the query), `swa_core`
that of a layer with a `window` (`ops/sliding_window.py`), `bd_core` the
block-diffusion core's (`ops/block_diffusion.py`): called with a
`block_length` (static) the input is the two streams of a decoder trained by
diffusion over blocks, [noised ; clean] along T, and `positions` [T] the
rotation's (the two copies of a token share one).

On a TPU, at the shapes the kernels' predicate admits
(`sliding_window.kernels_run`, `block_diffusion.kernels_run`: heads of whole
lane rows, whole tiles, whole groups of any size, 7 query heads a key-value
head or 16 as 8), all three are `kernels/pallas_block_attention.py`'s two
launches under the layer's rule (('mha', 0), ('swa', window), ('bd',
block_length): the table of the tiles that hold a visible pair, no other
launched, and the names `<leaf>_fwd` / `<leaf>_bwd`), and nothing is laid
out again on either side of them: the projections' outputs [B, T, H D] and
[B, T, KV D] go through one launch that norms, rotates, scales and rounds
them (`kernels/pallas_qk_pass.py`: `qk_pass_fwd` under `mha_qkv`, and
`qk_pass_bwd` on the way back; norm and rotation each there or not, as the
layer has them), the core's launches read and write that layout with the
key-value heads as they are, and the output projection reads o [B, T, H D]
as the core wrote it.

At any other shape and off the TPU the composition below: the heads laid out
[B, H, T, D], and a blocked core with the layer's key extents (a window's,
the two streams'); a global layer's core is then the latent attention's
(`ops/latent_attention.py::causal_attention`: JAX's streaming Pallas kernel
on a TPU, as heads of 64 take it, blocks of queries elsewhere), which takes
as many key-value heads as query heads, so they are repeated here and
autodiff sums their gradients over each group. Projections, norms and
rotation are the same arithmetic either way, and so is the parameter tree.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..kernels import pallas_block_attention as kernels
from ..kernels.pallas_qk_pass import rotary_tables
from ..observability import named_scope
from . import sliding_window
from .block_diffusion import block_diffusion_attention_blocked, kernels_run
from .latent_attention import (
    RMSNorm, causal_attention, causal_attention_blocked,
)
from .rotary import apply_rotary_halves, rotary_angles


class _Scale(nn.Module):
    """An `RMSNorm`'s parameter (`<name>/scale`) without its arithmetic."""
    width: int

    @nn.compact
    def __call__(self):
        return self.param('scale', nn.initializers.ones, (self.width,))


class GroupedQueryAttention(nn.Module):
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    block: int = 512      # of queries (and of keys, in the kernel)
    qk_norm: bool = False
    rope_theta: Optional[float] = None
    eps: float = 1e-5     # of the q/k norms
    window: int = 0       # keys a query sees, itself counted; 0: all before

    @nn.compact
    def __call__(self, x, positions=None, block_length: int = 0):
        """x [B, T, dim] -> [B, T, dim]; positions [T] (None: 0 .. T - 1)."""
        b, t, _ = x.shape
        h, kv, dh = self.heads, self.kv_heads, self.head_dim
        assert h % kv == 0, (h, kv)
        dense = partial(nn.Dense, use_bias=False)
        assert not (block_length and self.window), (block_length, self.window)
        if block_length:
            rule, tile = ('bd', block_length), min(self.block, t // 2)
            one_pass = kernels_run(t // 2, block_length, self.block, h, kv,
                                   dh)
        else:           # one stream of T positions, a window of them or all
            rule = ('swa', self.window) if self.window else ('mha', 0)
            tile = min(self.block, t)
            one_pass = sliding_window.kernels_run(t, self.block, h, kv, dh)

        def heads_of(a, n):     # the kernels take the products' own layout
            return a if one_pass else a.reshape(b, t, n, dh)

        def angles():
            return rotary_angles(
                jnp.arange(t) if positions is None else positions, dh,
                self.rope_theta)

        with named_scope('mha_qkv'):
            q = heads_of(dense(h * dh, name='q')(x), h)
            k, v = (heads_of(dense(kv * dh, name=name)(x), kv)
                    for name in ('k', 'v'))
            if one_pass:
                norms = tuple(_Scale(dh, name=name)() for name in
                              ('q_norm', 'k_norm')) if self.qk_norm else None
                rotary = None if self.rope_theta is None \
                    else rotary_tables(angles())
            else:
                if self.qk_norm:
                    q = RMSNorm(self.eps, name='q_norm')(q)
                    k = RMSNorm(self.eps, name='k_norm')(k)
                if self.rope_theta is not None:
                    ang = angles()
                    q, k = (apply_rotary_halves(a, ang[None, :, None, :])
                            for a in (q, k))
                if not block_length:
                    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
                q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        if one_pass:    # its scopes are the rule's own: `mha_qkv` and the
            #             core's, `bd_core`, `swa_core` or `mha_core`
            o = kernels.block_attention(
                q, k, v, norms, rotary, dh, dh ** -0.5, self.eps, rule, tile)
        elif self.window:
            with named_scope('swa_core'):
                o = causal_attention_blocked(q, k, v, dh ** -0.5, self.block,
                                             self.window)
        elif block_length:
            with named_scope('bd_core'):
                o = block_diffusion_attention_blocked(
                    q, k, v, dh ** -0.5, block_length, self.block)
        else:
            with named_scope('mha_core'):
                o = causal_attention(q, k, v, dh ** -0.5, self.block)
        with named_scope('mha_out'):
            if not one_pass:
                o = o.transpose(0, 2, 1, 3).reshape(b, t, h * dh)
            return dense(self.dim, name='out')(o)
