"""Core equivariant modules: Linear, Norm, Residual, FeedForward.

TPU-native flax.linen analogues of reference se3_transformer_pytorch.py:
  ResidualSE3 (:67), LinearSE3 (:78), NormSE3 (:97),
  FeedForwardSE3/FeedForwardBlockSE3 (:347-383).

Feature dicts are {str(degree): [..., channels, 2*degree+1]} pytrees. All
per-degree weights are independent parameters; the channel contraction is a
plain matmul over the channel axis, which XLA batches onto the MXU.
"""
from __future__ import annotations

from typing import Callable, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability import named_scope
from ..quant.qtensor import QuantTensor
from ..utils.helpers import safe_norm
from .fiber import Fiber


Features = Dict[str, jnp.ndarray]


def channel_mix(x: jnp.ndarray, w) -> jnp.ndarray:
    """The per-degree channel contraction `x [..., c, m] @ w [c, e] ->
    [..., e, m]`, quant-aware: a QuantTensor weight contracts in its
    int8/fp8 STORAGE form and the per-output-channel scale folds in as
    an epilogue — the fp32 weight never exists outside this fusion
    (serving's restore-time quantization rides on exactly that). A bf16
    weight promotes through the einsum; math stays f32 either way."""
    if isinstance(w, QuantTensor):
        out = jnp.einsum('...cm,ce->...em', x,
                         jnp.asarray(w.q).astype(x.dtype),
                         preferred_element_type=jnp.float32)
        # scale [1, e] -> [e, 1]: the output channel axis is -2
        return out * w.scale[0][:, None]
    return jnp.einsum('...cm,ce->...em', x, w)


def residual_se3(x: Features, res: Features) -> Features:
    """Degree-wise residual add; keys may differ (reference :67-76)."""
    out = {}
    for degree, tensor in x.items():
        out[degree] = tensor + res[degree] if degree in res else tensor
    return out


class LinearSE3(nn.Module):
    """Per-degree channel-mixing linear map (reference :78-95).

    Only degrees present in both fibers are produced, matching the reference's
    intersection semantics.
    """
    fiber_in: Fiber
    fiber_out: Fiber

    @nn.compact
    def __call__(self, x: Features) -> Features:
        out = {}
        for degree, dim_in, dim_out in (self.fiber_in & self.fiber_out):
            key = str(degree)
            w = self.param(
                f'w{key}',
                nn.initializers.normal(stddev=dim_in ** -0.5),
                (dim_in, dim_out), x[key].dtype)
            out[key] = channel_mix(x[key], w)
        return out


class NormSE3(nn.Module):
    """Norm-gated equivariant nonlinearity (reference :97-152).

    Per degree: split into (norm, unit direction), pass the norms through a
    learnable scale (or a gating matrix) and a nonlinearity, re-multiply the
    direction. Rotation-equivariant because only the invariant norm is
    transformed.
    """
    fiber: Fiber
    nonlin: Callable = nn.gelu
    gated_scale: bool = False
    eps: float = 1e-12

    @nn.compact
    def __call__(self, features: Features) -> Features:
        with named_scope('norm'):
            output = {}
            for degree, t in features.items():
                chan = t.shape[-2]
                norm = jnp.clip(safe_norm(t, axis=-1, keepdims=True),
                                self.eps, None)
                phase = t / norm

                scalars = norm[..., 0]  # [..., c]
                if self.gated_scale:
                    w_gate = self.param(
                        f'w_gate{degree}',
                        lambda key, shape, dtype: jax.random.uniform(
                            key, shape, dtype, -1e-3, 1e-3),
                        (chan, chan), t.dtype)
                    scaled = jnp.einsum('...c,ce->...e', scalars, w_gate)
                else:
                    scale = self.param(
                        f'scale{degree}', nn.initializers.ones, (1, 1, chan),
                        t.dtype)
                    scaled = scalars * scale.reshape(
                        (1,) * (scalars.ndim - 1) + (chan,))
                transformed = self.nonlin(scaled)
                output[degree] = transformed[..., None] * phase
            return output


class FeedForwardSE3(nn.Module):
    """Linear -> Norm-nonlinearity -> Linear with widening `mult`
    (reference :347-365)."""
    fiber: Fiber
    mult: int = 4

    @nn.compact
    def __call__(self, features: Features) -> Features:
        fiber_hidden = self.fiber.scale(self.mult)
        x = LinearSE3(self.fiber, fiber_hidden, name='project_in')(features)
        x = NormSE3(fiber_hidden, name='nonlin')(x)
        x = LinearSE3(fiber_hidden, self.fiber, name='project_out')(x)
        return x


class FeedForwardBlockSE3(nn.Module):
    """Prenorm + feedforward + residual (reference :367-383)."""
    fiber: Fiber
    norm_gated_scale: bool = False

    @nn.compact
    def __call__(self, features: Features) -> Features:
        res = features
        with named_scope('ff'):
            out = NormSE3(self.fiber, gated_scale=self.norm_gated_scale,
                          name='prenorm')(features)
            out = FeedForwardSE3(self.fiber, name='feedforward')(out)
            return residual_se3(out, res)
