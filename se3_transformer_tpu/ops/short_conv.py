"""A gated short-convolution mixer for a causal token decoder: the whole
sequence mixing is a few depthwise taps between two multiplicative gates.

    [B ; C ; X] = u W_in               thirds of 3 dim, in this order, no bias
    z_t = sum_j w[j] (B * X)_{t - (taps-1) + j}     depthwise and causal: the
                                       last tap reads the token itself,
                                       positions before the first read as
                                       zero; no bias, no activation
    out = (C * z) W_out

The gates and the taps are float32 elementwise passes in XLA (shifts and
multiply-adds, as the state-space mixer's convolution is); the two products
take the backend's default precision. The window starts empty at every
sequence: nothing is carried in, and a packed sequence's documents are not
told apart. Autodiff differentiates it.
"""
from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax.numpy as jnp

from ..observability import named_scope
from .state_space import CausalConv


class ShortConvMixer(nn.Module):
    dim: int
    taps: int = 3              # the published `conv_L_cache`

    @nn.compact
    def __call__(self, u):
        """u [B, T, dim] -> [B, T, dim]."""
        dense = partial(nn.Dense, use_bias=False)
        with named_scope('sconv_in'):
            b, c, x = jnp.split(dense(3 * self.dim, name='in_proj')(u), 3,
                                axis=-1)
        with named_scope('sconv_core'):
            y = c * CausalConv(self.taps, use_bias=False, name='conv')(b * x)
        with named_scope('sconv_out'):
            return dense(self.dim, name='out_proj')(y)
