"""Latent attention for a causal token decoder.

Queries go through a low-rank bottleneck; keys and values are expanded from
one compressed latent a token; one rotary key a token is shared by every
head; value heads may be wider than the keys' un-rotated part:

    cq = RMSNorm(x Wqa)                 q = cq Wqb -> [qn ; qr] per head
    [ckv ; kr] = x Wkva                 ckv <- RMSNorm(ckv)
    [kn ; v] = ckv Wkvb per head        qr, kr <- rotation(positions)
    scores = (qn . kn + qr . kr) / sqrt(d_nope + d_rope), causal,
    softmax in float32; out = (softmax . v) Wo

Training materializes kn and v (no absorbed form). The core never holds the
[heads, T, T] scores, and `kernels_run` says which of three it is:

  * on a TPU, at heads of whole lane rows, keys and values of one width
    and a length the tile divides (the GLM cell: 20 heads of 256 over 8,192,
    under 32,768), the repo's own two launches
    (`kernels/pallas_block_attention.py` under the rule ('latent', 0):
    `latent_core_fwd`, `latent_core_bwd` over the causal triangle's table,
    bfloat16 operands, float32 softmax and sums). Every operand is
    token-major, [B, T, heads d] with a head a run of d lanes, and written
    in that layout by products alone (`token_major_qkv`: the rotation's
    lane exchange and the shared key's place are in the weights), so
    nothing is transposed or viewed by heads on either side of the core;
  * on a TPU elsewhere, `causal_attention_flash`: JAX's streaming Pallas
    kernel (`jax.experimental.pallas.ops.tpu.flash_attention`) in the
    head-major layout it wants. This layer no longer reaches it at the GLM
    cell's shapes; `GroupedQueryAttention` still does at heads of 64 (the
    short-convolution cell);
  * off the TPU blocks of queries against the keys at or before them, each
    block recomputed in the backward pass.

In the first two the core's output and softmax statistics are named, and a
rematted block saves them (`SAVE_ATTN_CORE`) instead of launching the
forward again.
"""
from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..kernels import pallas_block_attention as kernels
from ..kernels.pallas_block_attention import ATTN_CORE_OUT, ATTN_CORE_STATS
from ..observability import named_scope
from ..utils.helpers import is_tpu_backend
from .rotary import apply_rotary_halves, rotary_angles


class RMSNorm(nn.Module):
    """x / sqrt(mean(x^2) + eps) * scale, in float32."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param('scale', nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale


def causal_attention_blocked(q, k, v, scale: float, block_q: int = 512,
                             window: int = 0):
    """q, k [B, H, T, Dk], v [B, H, T, Dv] -> [B, H, T, Dv]. Query block i
    meets keys 0 .. (i + 1) block_q only (static extents, so the masked half
    is never computed) and is recomputed in the backward pass.

    With a `window` under T, query i sees key j exactly when 0 <= i - j <
    window (the token itself counts): a block's keys start at its first
    row's first visible key, whatever the window divides, and the keys
    behind the window are never computed either. A window of T or more (or
    0: none) is the causal core, to the bit."""
    t = q.shape[2]
    bq = min(block_q, t)
    assert t % bq == 0, (t, bq)
    windowed = 0 < window < t

    @jax.checkpoint
    def block(qi, kj, vj, q0, k0):
        s = jnp.einsum('bhqd,bhkd->bhqk', qi, kj,
                       preferred_element_type=jnp.float32) * scale
        qpos = q0 + jnp.arange(qi.shape[2])[:, None]
        kpos = k0 + jnp.arange(kj.shape[2])[None, :]
        seen = kpos <= qpos
        if windowed:
            seen = seen & (qpos - kpos < window)
        s = jnp.where(seen, s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum('bhqk,bhkd->bhqd', p, vj,
                          preferred_element_type=jnp.float32)

    outs = []
    for i in range(0, t, bq):
        lo = max(0, i - window + 1) if windowed else 0
        outs.append(block(q[:, :, i:i + bq], k[:, :, lo:i + bq],
                          v[:, :, lo:i + bq], i, lo))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)


# What a block's replay must not rebuild: a core's output and its softmax
# statistics, named in the forward rule below and in the repo's launches'.
# The decoders' blocks are rematted under `SAVE_ATTN_CORE`, which keeps these
# and nothing else.
SAVE_ATTN_CORE = jax.checkpoint_policies.save_only_these_names(
    ATTN_CORE_OUT, ATTN_CORE_STATS)


def _flash_forward(q, k, v, scale, block, save_residuals):
    """The library's forward launch at blocks of `block`: o, or (o, l, m)
    with the softmax's sum and maximum a row, [B, H, T] float32."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    b = min(block, q.shape[2])
    return fa._flash_attention_impl(
        q, k, v, None, None, save_residuals, True, scale, 1, b, b, b, False)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, scale, block):
    return _flash_forward(q, k, v, scale, block, False)


def _flash_core_fwd(q, k, v, scale, block):
    o, l, m = _flash_forward(q, k, v, scale, block, True)
    o = checkpoint_name(o, ATTN_CORE_OUT)
    l, m = (checkpoint_name(a, ATTN_CORE_STATS) for a in (l, m))
    return o, (q, k, v, o, l, m)


def _flash_core_bwd(scale, block, residuals, do):
    """The library's backward rule (`flash_attention.py::
    _flash_attention_bwd`) on the residuals above."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    q, k, v, o, l, m = residuals
    b = min(block, q.shape[2])
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    common = dict(sm_scale=scale, causal=True,
                  mask_value=fa.DEFAULT_MASK_VALUE, debug=False)
    dk, dv = fa._flash_attention_bwd_dkv(
        q, k, v, None, None, l, m, do, di, block_q_major=b, block_q=b,
        block_k_major=b, block_k=b, **common)
    dq, _ = fa._flash_attention_bwd_dq(
        q, k, v, None, None, l, m, do, di, block_q_major=b, block_k_major=b,
        block_k=b, **common)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@partial(jax.jit, static_argnames=('scale', 'block'))
def flash_attention(q, k, v, scale, block):
    """A jit of this name, as the library's entry is: it is the forward
    launch's name in a trace and a component of all three launches' paths."""
    return _flash_core(q, k, v, scale, block)


def causal_attention_flash(q, k, v, scale: float, block: int = 512):
    """The same on the TPU's streaming kernel; operands rounded to bfloat16
    here, where the XLA form leaves it to the default matmul precision. The
    library's three launches under a `custom_vjp` of the repo's own, so that
    the forward's (o, l, m) carry names a remat policy can save: under
    `SAVE_ATTN_CORE` a block's replay launches no forward. Undifferentiated,
    the forward computes no statistics."""
    if not q.shape == k.shape == v.shape:
        raise NotImplementedError(
            f'the streaming kernel takes q, k, v of one shape, not '
            f'{q.shape}, {k.shape}, {v.shape}')
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    return flash_attention(q, k, v, scale, block).astype(jnp.float32)


def causal_attention(q, k, v, scale: float, block: int = 512):
    """The streaming kernel where it can run (on a TPU, at a length its
    tiles divide), blocks of queries elsewhere."""
    core = causal_attention_flash \
        if is_tpu_backend() and q.shape[2] % 128 == 0 \
        else causal_attention_blocked
    return core(q, k, v, scale, block)


def kernels_run(positions: int, block: int, heads: int, qk_dim: int,
                v_dim: int) -> bool:
    """Whether the layer's core is the repo's two launches
    (`kernels/pallas_block_attention.py` under ('latent', 0)): on a TPU, at
    one width for keys and values (the launches are written over one), at
    the shapes its `launches_run` admits (tiles of `block` or the sequence,
    heads of whole lane rows, a head's dk and dv resident in VMEM), from the
    platform and the shapes alone."""
    return is_tpu_backend() and qk_dim == v_dim and kernels.launches_run(
        positions, min(block, positions), heads, heads, qk_dim)


def token_major_qkv(cq, ckv, kr, wq, wkv, angles, heads: int, dn: int):
    """The launches' operands in the launches' layout, [B, T, heads d]
    float32 with a head a run of d = dn + dr lanes, written by products
    alone: on a TPU [T, heads d] and [T, heads, d] are different memory, and
    a view by heads to rotate a head's last dr lanes or to put the shared
    key there costs passes over the whole tensor. So the lanes are moved
    where the kernels are small, in the weights:

        q = (cq wq) C + (cq wq') S      wq' holds, in the column of each
                                        rotary lane, wq's column of the
                                        lane's partner and zeros elsewhere;
                                        C is 1 and S 0 on the first dn lanes
                                        of a head, cos and -sin | sin after
        k = [ckv ; kr] [wk ; E]         wk kn's columns of `wkv` with zero
                                        columns where the rotated key goes,
                                        E the 0 / 1 rows that put kr there,
                                        the same for every head
        v = ckv wv                      v's columns of `wkv`

    cq [B, T, q_lora_rank] and ckv [B, T, kv_lora_rank] normed, kr
    [B, T, dr] rotated, wq [q_lora_rank, heads d] and wkv [kv_lora_rank,
    heads (dn + dv)] the kernels of `q_b` and `kv_b`, angles [T, dr / 2]."""
    r = angles.shape[-1]
    d = dn + 2 * r
    wq3, wkv3 = (w.reshape(w.shape[0], heads, -1) for w in (wq, wkv))
    nothing = jnp.zeros(wq3.shape[:2] + (dn,), wq.dtype)
    partners = jnp.concatenate(
        (nothing, wq3[..., dn + r:], wq3[..., dn:dn + r]), axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    one = jnp.ones((angles.shape[0], dn), cos.dtype)
    by_head = lambda *parts: jnp.concatenate(
        [jnp.concatenate(parts, axis=-1)] * heads, axis=-1)
    q = (cq @ wq) * by_head(one, cos, cos) \
        + (cq @ partners.reshape(wq.shape)) * by_head(0 * one, -sin, sin)
    wk = jnp.pad(wkv3[..., :dn], ((0, 0), (0, 0), (0, 2 * r)))
    place = jnp.pad(jnp.eye(2 * r, dtype=wkv.dtype), ((0, 0), (dn, 0)))
    k = jnp.concatenate((ckv, kr), axis=-1) @ jnp.concatenate(
        (wk.reshape(-1, heads * d), jnp.tile(place, (1, heads))), axis=0)
    v = ckv @ wkv3[..., dn:].reshape(wkv.shape[0], -1)
    return q, k, v


class _Kernel(nn.Module):
    """An `nn.Dense`'s parameter (`<name>/kernel`) without its product."""
    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param('kernel', nn.linear.default_kernel_init,
                          self.shape)


class LatentAttention(nn.Module):
    dim: int
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    block: int = 512      # of queries (and of keys, in the kernel)

    @nn.compact
    def __call__(self, x):
        """x [B, T, dim] -> [B, T, dim]; positions are 0 .. T - 1."""
        b, t, _ = x.shape
        h, dn, dr, dv = (self.heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        d = dn + dr
        dense = partial(nn.Dense, use_bias=False)
        launched = kernels_run(t, self.block, h, d, dv)
        with named_scope('latent_qkv'):
            cq = RMSNorm(self.eps, name='q_a_norm')(
                dense(self.q_lora_rank, name='q_a')(x))
            ckv_kr = dense(self.kv_lora_rank + dr, name='kv_a')(x)
            ckv = RMSNorm(self.eps, name='kv_a_norm')(
                ckv_kr[..., :self.kv_lora_rank])
            angles = rotary_angles(jnp.arange(t), dr, self.rope_theta)
            kr = apply_rotary_halves(ckv_kr[..., self.kv_lora_rank:],
                                     angles[None])             # [B, T, dr]
            if launched:
                wq = _Kernel((self.q_lora_rank, h * d), name='q_b')()
                wkv = _Kernel((self.kv_lora_rank, h * (dn + dv)),
                              name='kv_b')()
                q, k, v = token_major_qkv(cq, ckv, kr, wq, wkv, angles, h,
                                          dn)
            else:
                q = dense(h * d, name='q_b')(cq).reshape(b, t, h, d)
                kv = dense(h * (dn + dv), name='kv_b')(ckv).reshape(
                    b, t, h, dn + dv)
                qr = apply_rotary_halves(q[..., dn:],
                                         angles[None, :, None, :])
                q = jnp.concatenate((q[..., :dn], qr), axis=-1)
                k = jnp.concatenate(
                    (kv[..., :dn], jnp.broadcast_to(
                        kr[:, :, None, :], (b, t, h, dr))), axis=-1)
                q, k, v = (a.transpose(0, 2, 1, 3)
                           for a in (q, k, kv[..., dn:]))
        scale = d ** -0.5
        if launched:    # under `latent_qkv` and `latent_core` by its rule
            o = kernels.rounded_attention(q, k, v, d, scale, ('latent', 0),
                                          min(self.block, t))
        else:
            with named_scope('latent_core'):
                o = causal_attention(q, k, v, scale, self.block)
        with named_scope('latent_out'):
            if not launched:
                o = o.transpose(0, 2, 1, 3).reshape(b, t, h * dv)
            return dense(self.dim, name='out')(o)
