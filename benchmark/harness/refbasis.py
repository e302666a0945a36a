"""The plain reference's own tables: real spherical harmonics, Wigner-D
matrices and the Q_J intertwiners, in NumPy float64.

A copy of the arithmetic of `se3_transformer_tpu/so3/` and
`se3_transformer_tpu/basis.py` (the same fixed rotations and the same sign
rule, so that one set of weights means the same function), kept here so that
the reference imports nothing of the program and reads none of its caches.
Nothing is written to disk: the tables are rebuilt in every run.
"""
import math
from functools import lru_cache

import numpy as np

_RANDOM_ANGLES = np.array([
    [4.41301023, 5.56684102, 4.59384642],
    [4.93325116, 6.12697327, 4.14574096],
    [0.53878964, 4.14301185, 2.62721626],
    [2.67997558, 4.66598984, 0.41322213],
    [0.14730622, 4.18146178, 0.78533526],
])


def _norm_const(l, m):
    k = math.sqrt((2 * l + 1) / (4 * math.pi)
                  * math.factorial(l - m) / math.factorial(l + m))
    return k * math.sqrt(2.0) if m > 0 else k


def _double_factorial(n):
    out = 1
    while n > 1:
        out *= n
        n -= 2
    return out


def spherical_harmonics_all(l_max, xyz, xp=np):
    """Real SH for l = 0..l_max at unit vectors xyz[..., 3]: a list whose
    entry l is [..., 2l+1], m = -l..l. `xp` is numpy or jax.numpy."""
    x, y, z = xyz[..., 0], xyz[..., 1], xyz[..., 2]
    A, B = [xp.ones_like(x)], [xp.zeros_like(x)]
    for m in range(1, l_max + 1):
        A.append(x * A[m - 1] - y * B[m - 1])
        B.append(x * B[m - 1] + y * A[m - 1])
    P = {}
    for m in range(l_max + 1):
        pmm = float(_double_factorial(2 * m - 1))
        P[(m, m)] = pmm * xp.ones_like(z)
        if m + 1 <= l_max:
            P[(m + 1, m)] = (2 * m + 1) * pmm * z
        for l in range(m + 2, l_max + 1):
            P[(l, m)] = ((2 * l - 1) * z * P[(l - 1, m)]
                         - (l + m - 1) * P[(l - 2, m)]) / (l - m)
    out = []
    for l in range(l_max + 1):
        cols = [_norm_const(l, m) * P[(l, m)] * B[m] for m in range(l, 0, -1)]
        cols.append(_norm_const(l, 0) * P[(l, 0)])
        cols += [_norm_const(l, m) * P[(l, m)] * A[m] for m in range(1, l + 1)]
        out.append(xp.stack(cols, axis=-1))
    return out


def _rot_z(g):
    c, s = np.cos(g), np.sin(g)
    return np.array([[c, -s, 0.], [s, c, 0.], [0., 0., 1.]])


def _rot_y(b):
    c, s = np.cos(b), np.sin(b)
    return np.array([[c, 0., s], [0., 1., 0.], [-s, 0., c]])


def rot(a, b, c):
    return _rot_z(a) @ _rot_y(b) @ _rot_z(c)


def wigner_d(l, R):
    """Real Wigner-D with D Y_l(p) = Y_l(R p): least squares over sampled
    points, polished to an orthogonal matrix."""
    if l == 0:
        return np.ones((1, 1))
    n = max(8 * (2 * l + 1), 32)
    pts = np.random.RandomState(12345 + l).normal(size=(n, 3))
    pts = pts / np.linalg.norm(pts, axis=-1, keepdims=True)
    Y = spherical_harmonics_all(l, pts)[l]
    Yr = spherical_harmonics_all(l, pts @ np.asarray(R, np.float64).T)[l]
    Dt, *_ = np.linalg.lstsq(Y, Yr, rcond=None)
    U, _, Vt = np.linalg.svd(Dt.T)
    return U @ Vt


@lru_cache(maxsize=None)
def q_j(J, d_in, d_out):
    """The intertwiner Q_J, [(2 d_out+1)(2 d_in+1), 2J+1], float64: the null
    space of the stacked Sylvester systems, largest element made positive."""
    dim = (2 * d_out + 1) * (2 * d_in + 1)
    mats = []
    for a, b, c in _RANDOM_ANGLES:
        R = rot(a, b, c)
        R_tensor = np.kron(wigner_d(d_out, R), wigner_d(d_in, R))
        mats.append(np.kron(R_tensor, np.eye(2 * J + 1))
                    - np.kron(np.eye(dim), wigner_d(J, R).T))
    _, s, Vt = np.linalg.svd(np.concatenate(mats, axis=0),
                             full_matrices=False)
    null = Vt[s < 1e-10]
    assert null.shape[0] == 1, (J, d_in, d_out, null.shape)
    Q = null[0].reshape(dim, 2 * J + 1)
    flat = Q.ravel()
    return Q * np.sign(flat[np.argmax(np.abs(flat))])
