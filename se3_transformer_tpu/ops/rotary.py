"""Rotary position embeddings for degree-0 channels.

Functional JAX analogue of reference rotary.py (SinusoidalEmbeddings /
apply_rotary_pos_emb). Rotary features are applied only to the invariant
(degree-0) q/k/v channels, so they do not interact with equivariance.

`rotary_angles` / `apply_rotary_halves` are the same rotation for a plain
`[..., d]` layout at a given base (the token decoder's shared rotary key).
"""
from __future__ import annotations

import jax.numpy as jnp


def sinusoidal_embeddings(t: jnp.ndarray, dim: int) -> jnp.ndarray:
    """t [...]-shaped positions -> [..., dim] rotary phase angles
    (reference rotary.py:5-13; frequencies repeated pairwise)."""
    inv_freq = 1.0 / (10000 ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freqs = t[..., None].astype(jnp.float32) * inv_freq
    return jnp.repeat(freqs, 2, axis=-1)  # (d r) with r=2: f1,f1,f2,f2,...


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    # channels axis is -2 (layout [..., d, m]); pairs are consecutive
    x = x.reshape(*x.shape[:-2], -1, 2, x.shape[-1])
    x1, x2 = x[..., 0, :], x[..., 1, :]
    out = jnp.stack((-x2, x1), axis=-2)
    return out.reshape(*out.shape[:-3], -1, out.shape[-1])


def apply_rotary_pos_emb(t: jnp.ndarray, freqs: jnp.ndarray) -> jnp.ndarray:
    """Rotate the first rot_dim channels of t [..., d, m] by freqs [..., rot_dim]
    (reference rotary.py:20-24; note the trailing irrep axis m)."""
    freqs = freqs[..., None]  # broadcast over m
    rot_dim = freqs.shape[-2]
    t_rot, t_pass = t[..., :rot_dim, :], t[..., rot_dim:, :]
    t_rot = (t_rot * jnp.cos(freqs)) + (_rotate_half(t_rot) * jnp.sin(freqs))
    return jnp.concatenate((t_rot, t_pass), axis=-2)


def rotary_angles(positions: jnp.ndarray, dim: int,
                  base: float = 10000.0) -> jnp.ndarray:
    """positions [...] -> [..., dim // 2] angles, one per rotated pair."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return positions[..., None].astype(jnp.float32) * inv_freq


def apply_rotary_halves(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """Rotate all of x [..., d] by angles (broadcast against [..., d // 2]);
    the pairs are (x_i, x_{i + d/2}): the half-split pairing, no trailing
    irrep axis."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin),
                           axis=-1)
