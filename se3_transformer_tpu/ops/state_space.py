"""A Mamba-2 state-space mixer for a causal token decoder.

    [z | xBC | dt] = u W_in            widths HP | HP + 2GN | H
    xBC <- silu(causal depthwise conv over `conv_kernel` taps (xBC) + b)
    [x | B | C] = xBC                  widths HP | GN | GN
    dt = softplus(dt + dt_bias);  A = -exp(A_log), one a head
    head j of group j // (H / G), state S [P, N]:
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
        y_t = S_t C_t + D x_t
    y <- GroupRMSNorm(y * silu(z)) over the G groups, one scale of HP
    out = y W_out

The scan is the chunked matrix form (`chunked_scan`): inside a chunk of Q
tokens the masked product (L o C B^T)(dt x) with L_ij = exp(sum_{j<r<=i} dt_r
A); one P x N state a chunk and head from the chunk's own tokens; the chunk
states carried forward. Every exponent is a sum of non-positive terms taken
before the exponential, so a chunk whose decay underflows gives zero, never a
quotient of zeros. `dt`, the cumulative sums, the decays and the carried state
are float32; the large products take the backend's default precision (on a
TPU operands rounded to bfloat16, float32 accumulation).

Two forms, one rule (`chunked_scan`). On a TPU, at widths Mosaic tiles, the
two kernels of `kernels/pallas_scan.py` (`scan_kernels`): scores, decays, the
masked product and the chunk states stay in VMEM, the state carried from
chunk to chunk in a scratch, a backward launch of its own under a
`custom_vjp`; XLA keeps the cumulative sums of dt A. Elsewhere XLA's einsums
(`scan_einsums`), which autodiff differentiates and which are the kernels'
oracle: there the chunk states are carried by one more masked product over
the chunks (no `lax.scan`: inside a scanned body operations lose their
scopes).
"""
from __future__ import annotations

import math
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..kernels import pallas_scan
from ..observability import named_scope
from ..utils.helpers import is_tpu_backend


def _decay_below(cum, axis):
    """cum [..., n, ...] inclusive cumulative sums of non-positive terms
    along `axis` -> a new axis after it: out[i, j] = exp(cum_i - cum_j) for
    j <= i, 0 above the diagonal (masked before the exponential)."""
    n = cum.shape[axis]
    diff = jnp.expand_dims(cum, axis + 1) - jnp.expand_dims(cum, axis)
    keep = jnp.tril(jnp.ones((n, n), bool)).reshape(
        (1,) * axis + (n, n) + (1,) * (cum.ndim - axis - 1))
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def chunked_scan(x, dt, a, b, c, d, chunk: int):
    """x [B, T, H, P], dt [B, T, H] (positive), a [H] (negative), b, c
    [B, T, G, N], d [H] -> y [B, T, H, P] of the recurrence above, float32.
    T need not be a multiple of `chunk`: the tail is padded with dt = 0,
    which neither decays nor feeds the state. The two kernels of
    `kernels/pallas_scan.py` where they can run (on a TPU, at widths Mosaic
    tiles), XLA's einsums elsewhere."""
    t, h, p = x.shape[1:]
    g, n = b.shape[2:]
    core = scan_kernels if is_tpu_backend() \
        and pallas_scan.can_run(h, p, g, n, chunk) else scan_einsums
    pad = -t % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    return core(x.astype(jnp.float32), dt, a, b, c, d, chunk)[:, :t]


def _chunk_cumsum(dt, a, chunk):
    """The sum of dt A from a chunk's first token to each: [B, nc, Q, H]."""
    bsz, t, h = dt.shape
    return jnp.cumsum((dt * a).reshape(bsz, t // chunk, chunk, h), axis=2)


def scan_kernels(x, dt, a, b, c, d, chunk: int, interpret: bool = False):
    """`chunked_scan` at a whole number of chunks, the inside of a chunk, the
    carry over the chunks and `+ D x` in the repo's kernels; the cumulative
    sums are XLA's. The launches read x, B and C as views of one array: the
    mixer hands over the three parts of one, and XLA takes the
    concatenation of adjacent slices for the array they were cut from. They
    read and write features by tokens, which is how XLA lays the mixer's
    activations out on a TPU, so the transposes move nothing; each fuses
    into the pass on its far side (the convolution's activation; the
    gate's backward, which writes dy) and is filed with that pass."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    xbc = jnp.concatenate([v.reshape(bsz, t, -1) for v in (x, b, c)], -1)
    cum = _chunk_cumsum(dt, a, chunk).reshape(dt.shape)
    with named_scope('ssm_conv'):
        xbc = xbc.swapaxes(1, 2)
    y = pallas_scan.chunk_scan(
        xbc, dt.swapaxes(1, 2), cum.swapaxes(1, 2),
        cum.reshape(bsz, t, g, h // g).swapaxes(1, 2), d, (h, p, g, n), chunk,
        interpret)
    with named_scope('ssm_gate'):
        return y.swapaxes(1, 2).reshape(x.shape)


def scan_einsums(x, dt, a, b, c, d, chunk: int):
    """The same in einsums, which autodiff differentiates: the path off the
    TPU and the kernels' oracle."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    nc = t // chunk
    xd = (x * dt[..., None]).reshape(bsz, nc, chunk, g, r, p)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    cum = _chunk_cumsum(dt, a, chunk).reshape(bsz, nc, chunk, g, r)

    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) L_ij dt_j x_j
    scores = jnp.einsum('zcign,zcjgn->zcgij', c, b,
                        preferred_element_type=jnp.float32)
    decay = _decay_below(jnp.moveaxis(cum, 2, -1), 4)     # [B,nc,G,R,Q,Q]
    y = jnp.einsum('zcgrij,zcjgrp->zcigrp', scores[:, :, :, None] * decay,
                   xd, preferred_element_type=jnp.float32)

    # a chunk's own state at its end, and the state entering each chunk
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    own = jnp.einsum('zcjgn,zcjgrp->zcgrpn', b, xd * to_end[..., None],
                     preferred_element_type=jnp.float32)
    total = jnp.cumsum(cum[:, :, -1], axis=1)             # [B,nc,G,R]
    # entering chunk i: sum_{k < i} exp(total_{i-1} - total_k) own_k
    carry = _decay_below(total, 1)[:, :-1]                # [B,nc-1,nc,G,R]
    entering = jnp.einsum('zikgr,zkgrpn->zigrpn', carry, own,
                          precision=jax.lax.Precision.HIGHEST)
    entering = jnp.pad(entering, ((0, 0), (1, 0)) + ((0, 0),) * 4)
    y = y + jnp.einsum('zcign,zcgrpn->zcigrp', c, entering,
                       preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[..., None]
    return y.reshape(bsz, t, h, p) + x * d[:, None]


def _conv_taps(x, kernel, bias):
    """x [B, T, C], kernel [K, C]: y_t = sum_k kernel[k] x_{t - (K-1) + k}
    + bias, positions before the first read as zero."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + t] * kernel[i] for i in range(k))
    return y if bias is None else y + bias


def _dt_bias_init(lo, hi, floor):
    """Mamba-2's: dt log-uniform in [lo, hi], floored, through the inverse
    of softplus."""
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, dtype)
        dt = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo))
                                 + math.log(lo)), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class CausalConv(nn.Module):
    """Depthwise, over `taps` positions ending at the token."""
    taps: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        kernel = self.param('kernel', nn.initializers.variance_scaling(
            1.0, 'fan_in', 'uniform', in_axis=0, out_axis=1),
            (self.taps, x.shape[-1]))
        bias = self.param('bias', nn.initializers.zeros, (x.shape[-1],)) \
            if self.use_bias else None
        return _conv_taps(x, kernel, bias)


class GroupRMSNorm(nn.Module):
    """RMSNorm over each of `groups` equal parts of the last axis, one scale
    of its whole width."""
    groups: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param('scale', nn.initializers.ones, (x.shape[-1],))
        parts = x.astype(jnp.float32).reshape(
            *x.shape[:-1], self.groups, x.shape[-1] // self.groups)
        var = jnp.mean(parts * parts, axis=-1, keepdims=True)
        return (parts * jax.lax.rsqrt(var + self.eps)).reshape(x.shape) \
            * scale


class Mamba2Mixer(nn.Module):
    dim: int
    num_heads: int             # H
    head_dim: int              # P
    state_size: int            # N
    n_groups: int              # G: heads j // (H / G) share B and C
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    eps: float = 1e-5

    @nn.compact
    def __call__(self, u):
        """u [B, T, dim] -> [B, T, dim]; the state starts at zero."""
        bsz, t, _ = u.shape
        h, p, n, g = (self.num_heads, self.head_dim, self.state_size,
                      self.n_groups)
        inner, gn = h * p, g * n
        assert h % g == 0, (h, g)
        dense = partial(nn.Dense, use_bias=False)
        with named_scope('ssm_in'):
            zxbcdt = dense(2 * inner + 2 * gn + h, name='in_proj')(u)
            z, xbc, dt = jnp.split(zxbcdt, (inner, 2 * inner + 2 * gn),
                                   axis=-1)
        with named_scope('ssm_conv'):
            xbc = nn.silu(CausalConv(self.conv_kernel, self.use_conv_bias,
                                     name='conv')(xbc))
            x, b, c = jnp.split(xbc, (inner, inner + gn), axis=-1)
        with named_scope('ssm_scan'):
            dt_bias = self.param('dt_bias', _dt_bias_init(
                self.time_step_min, self.time_step_max,
                self.time_step_floor), (h,))
            a_log = self.param('A_log', _a_log_init, (h,))
            d = self.param('D', nn.initializers.ones, (h,))
            y = chunked_scan(
                x.reshape(bsz, t, h, p), nn.softplus(dt + dt_bias),
                -jnp.exp(a_log), b.reshape(bsz, t, g, n),
                c.reshape(bsz, t, g, n), d, self.chunk_size)
        with named_scope('ssm_gate'):
            y = GroupRMSNorm(g, self.eps, name='gate_norm')(
                y.reshape(bsz, t, inner) * nn.silu(z))
        with named_scope('ssm_out'):
            return dense(self.dim, name='out_proj')(y)
