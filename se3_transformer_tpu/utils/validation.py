"""Runtime validation helpers shared by the smokes, scripts/tpu_checks.py
and the tests."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def equivariance_l2(module, params, feats, coords, mask,
                    angles=(0.37, 1.12, -0.64), return_type=1,
                    precision='float32', **apply_kwargs) -> float:
    """Max per-node L2 error of ||f(feats, R c) - f(feats, c) R||.

    Uses a NON-degenerate rotation (beta != 0 — a beta=0 triple is a pure
    z-rotation and blind to most of SO(3)), applied in float64 on host so
    device matmul precision doesn't contaminate the measurement.
    """
    from ..so3 import rot
    R = rot(*angles)
    coords64 = np.asarray(coords, np.float64)
    with jax.default_matmul_precision(precision):
        fwd = jax.jit(lambda c: module.apply(
            {'params': params}, feats, c, mask=mask,
            return_type=return_type, **apply_kwargs))
        out_rot = np.asarray(
            fwd(jnp.asarray(coords64 @ R, coords.dtype)), np.float64)
        out_ref = np.asarray(fwd(coords), np.float64) @ R
    return float(np.sqrt(((out_rot - out_ref) ** 2).sum(-1)).max())
