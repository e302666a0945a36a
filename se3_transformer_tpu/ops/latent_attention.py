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
[heads, T, T] scores: on a TPU it is JAX's streaming Pallas kernel
(`jax.experimental.pallas.ops.tpu.flash_attention`: bfloat16 operands,
float32 softmax and accumulation; its output and softmax statistics are
named, and a rematted block saves them instead of launching the forward
again), elsewhere blocks of queries against the keys at or before them, each
block recomputed in the backward pass.
"""
from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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


# What a block's replay must not rebuild: the streaming core's output and its
# softmax statistics, named in the forward rule below. The decoders' blocks
# are rematted under `SAVE_ATTN_CORE`, which keeps these and nothing else.
ATTN_CORE_OUT, ATTN_CORE_STATS = 'attn_core_out', 'attn_core_stats'
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
        dense = partial(nn.Dense, use_bias=False)
        with named_scope('latent_qkv'):
            cq = RMSNorm(self.eps, name='q_a_norm')(
                dense(self.q_lora_rank, name='q_a')(x))
            q = dense(h * (dn + dr), name='q_b')(cq).reshape(b, t, h, dn + dr)
            ckv_kr = dense(self.kv_lora_rank + dr, name='kv_a')(x)
            ckv = RMSNorm(self.eps, name='kv_a_norm')(
                ckv_kr[..., :self.kv_lora_rank])
            kr = ckv_kr[..., self.kv_lora_rank:]               # [B, T, dr]
            kv = dense(h * (dn + dv), name='kv_b')(ckv).reshape(
                b, t, h, dn + dv)
            angles = rotary_angles(jnp.arange(t), dr, self.rope_theta)
            qr = apply_rotary_halves(q[..., dn:], angles[None, :, None, :])
            kr = apply_rotary_halves(kr, angles[None])
            q = jnp.concatenate((q[..., :dn], qr), axis=-1)
            k = jnp.concatenate(
                (kv[..., :dn],
                 jnp.broadcast_to(kr[:, :, None, :], (b, t, h, dr))), axis=-1)
            q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., dn:]))
        scale = (dn + dr) ** -0.5
        with named_scope('latent_core'):
            o = causal_attention(q, k, v, scale, self.block)
        with named_scope('latent_out'):
            o = o.transpose(0, 2, 1, 3).reshape(b, t, h * dv)
            return dense(self.dim, name='out')(o)
