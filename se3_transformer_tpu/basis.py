"""Equivariant kernel basis construction.

TPU-native rework of reference basis.py. The split is:

  * Q_J intertwiners — cold path, computed ONCE per (J, d_in, d_out) on the
    host in NumPy float64 (SVD null space of a stacked Sylvester system over
    fixed random rotations, reference basis.py:113-138), lru-cached in memory
    and optionally persisted to a versioned .npz. They enter traced code as
    jit constants — no disk I/O, file locks, or pickle caches on the hot
    path (cf. reference utils.py:151-206).

  * get_basis — hot path, fully jit-traceable JAX: evaluates the real
    spherical harmonics polynomially from Cartesian offsets (no angle
    conversion / axis-permutation shims, cf. reference basis.py:57-95) and
    contracts them with the Q_J constants into the pairwise kernel bases.

Returned layout per ('d_in,d_out') key: [..., 2*d_out+1, 2*d_in+1, n_freq]
with n_freq = 2*min(d_in, d_out) + 1 frequencies J = |d_in-d_out|..d_in+d_out
(the reference keeps two extra singleton axes for eager broadcasting,
basis.py:196-198 — unnecessary under XLA).

Unlike the reference — where gradients never actually flow through the basis
in either mode (see reference basis.py:171,200-203) — `differentiable=True`
here genuinely makes the basis differentiable w.r.t. coordinates, and
`differentiable=False` applies jax.lax.stop_gradient.
"""
from __future__ import annotations

import os
from functools import lru_cache
from itertools import product

import jax
import jax.numpy as jnp
import numpy as np

from .so3.spherical_harmonics import real_spherical_harmonics_all
from .so3.wigner import wigner_d_from_rotation, rot
from .utils.compilation_cache import CHECKOUT_CACHE_DIR

# fixed, well-conditioned random rotations for the Sylvester system
# (role of reference basis.py:20-26 RANDOM_ANGLES; values are our own)
_RANDOM_ANGLES = np.array([
    [4.41301023, 5.56684102, 4.59384642],
    [4.93325116, 6.12697327, 4.14574096],
    [0.53878964, 4.14301185, 2.62721626],
    [2.67997558, 4.66598984, 0.41322213],
    [0.14730622, 4.18146178, 0.78533526],
])

CACHE_PATH = os.environ.get('SE3_TPU_CACHE_PATH', CHECKOUT_CACHE_DIR)
CLEAR_CACHE = 'SE3_TPU_CLEAR_CACHE' in os.environ
_CACHE_VERSION = 1


def _kron(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.kron(a, b)


def _sylvester_nullspace(mats) -> np.ndarray:
    """Orthonormal basis of the common null space of stacked matrices
    (reference basis.py:36-55), float64 SVD."""
    A = np.concatenate(mats, axis=0)
    _, s, Vt = np.linalg.svd(A, full_matrices=False)
    return Vt[s < 1e-10]


@lru_cache(maxsize=None)
def basis_transformation_Q_J(J: int, d_in: int, d_out: int) -> np.ndarray:
    """The unique (up to sign) intertwiner Q_J with
        (D_out(R) ⊗ D_in(R)) Q_J = Q_J D_J(R)   for all R in SO(3),
    shape [(2*d_out+1)*(2*d_in+1), 2*J+1], float64 (reference basis.py:123-138).

    Row-major flattening: row index = m_out * (2*d_in+1) + m_in, so the
    reshaped kernel K transforms as K(R r) = D_out K(r) D_in^T.
    """
    cached = _load_cached_qj(J, d_in, d_out)
    if cached is not None:
        return cached

    dim = (2 * d_out + 1) * (2 * d_in + 1)
    mats = []
    for a, b, c in _RANDOM_ANGLES:
        R = rot(a, b, c)
        R_tensor = _kron(wigner_d_from_rotation(d_out, R),
                         wigner_d_from_rotation(d_in, R))
        D_J = wigner_d_from_rotation(J, R)
        # A Q - Q B = 0  <=>  (A ⊗ I - I ⊗ B^T) vec_row(Q) = 0
        mats.append(_kron(R_tensor, np.eye(2 * J + 1))
                    - _kron(np.eye(dim), D_J.T))
    null = _sylvester_nullspace(mats)
    assert null.shape[0] == 1, (
        f'expected a 1-dimensional intertwiner space for (J={J}, d_in={d_in}, '
        f'd_out={d_out}), got {null.shape[0]}')
    Q = null[0].reshape(dim, 2 * J + 1)
    # deterministic sign: largest-|.| element made positive
    flat = Q.ravel()
    Q = Q * np.sign(flat[np.argmax(np.abs(flat))])
    _store_cached_qj(J, d_in, d_out, Q)
    return Q


def _qj_cache_file() -> str:
    return os.path.join(CACHE_PATH, f'qj_v{_CACHE_VERSION}.npz')


def _load_cached_qj(J, d_in, d_out):
    if CLEAR_CACHE or not CACHE_PATH:
        return None
    path = _qj_cache_file()
    if not os.path.exists(path):
        return None
    try:
        with np.load(path) as data:
            key = f'{J}_{d_in}_{d_out}'
            if key in data:
                return data[key]
    except Exception:  # corrupted/truncated cache: treat as a miss
        return None
    return None


def _store_cached_qj(J, d_in, d_out, Q):
    if CLEAR_CACHE or not CACHE_PATH:
        return
    try:
        os.makedirs(CACHE_PATH, exist_ok=True)
        path = _qj_cache_file()
        # inter-process mutex around the read-modify-write (the role of the
        # reference's FileLock, utils.py:169): concurrent writers would
        # otherwise drop each other's entries. Locking failures degrade to
        # best-effort (worst case: a recomputable cache miss).
        lock_path = os.path.join(CACHE_PATH, 'qj.lock')
        with open(lock_path, 'w') as lock_fh:
            try:
                import fcntl
                fcntl.flock(lock_fh, fcntl.LOCK_EX)
            except (ImportError, OSError):
                pass
            existing = {}
            if os.path.exists(path):
                try:
                    with np.load(path) as data:
                        existing = {k: data[k] for k in data.files}
                except Exception:
                    # corrupted cache: rebuild from scratch
                    existing = {}
            existing[f'{J}_{d_in}_{d_out}'] = Q
            # NOTE: np.savez appends '.npz' when the name lacks it — the
            # tmp name must already end in .npz or os.replace misses
            tmp = path + f'.{os.getpid()}.tmp.npz'
            np.savez(tmp, **existing)
            os.replace(tmp, path)
            # housekeeping: drop tmp files orphaned by crashed writers.
            # Age-gated so an in-flight write from a writer running without
            # the flock (no fcntl / flock failure) is never reaped.
            import time as _time
            base = os.path.basename(path)
            cutoff = _time.time() - 300
            for name in os.listdir(CACHE_PATH):
                full = os.path.join(CACHE_PATH, name)
                if (name.startswith(base + '.') and name.endswith('.tmp.npz')
                        and name != os.path.basename(tmp)):
                    try:
                        if os.path.getmtime(full) < cutoff:
                            os.remove(full)
                    except OSError:
                        pass
    except OSError:
        pass


def safe_normalize(vec: jnp.ndarray, eps: float = 1e-8):
    """Unit vectors with a differentiable guard at the origin."""
    sq = jnp.sum(vec ** 2, axis=-1, keepdims=True)
    norm = jnp.sqrt(jnp.maximum(sq, eps ** 2))
    return vec / norm, norm[..., 0]


def get_basis(rel_pos: jnp.ndarray, max_degree: int,
              differentiable: bool = False, layout: str = 'pqf') -> dict:
    """Pairwise equivariant kernel bases for all degree pairs.

    rel_pos: [..., 3] relative offsets (need not be normalized).
    layout='pqf' (default): {f'{d_in},{d_out}':
    [..., 2*d_out+1, 2*d_in+1, n_freq]} for all d_in, d_out in
    0..max_degree (reference basis.py:153-205).

    layout='pfq_flat': the same values flattened per edge to
    [..., P*F*Q] in (p, f, q) order — the TPU hot-path layout. The
    structured form puts two small odd axes (Q, F) in the tile-padded
    minor positions, inflating the materialized HBM buffers up to ~60x
    at num_degrees=4 ((Q,F)=(7,7) pads to (8,128)); one flat minor axis
    pads only to the next 128 multiple (~1.1x), and (p,f,q) is exactly
    the order the fused bxf kernel's [P*F*Q, E] operand wants, so the
    relayout into the kernel is a plain 2D transpose.
    """
    rhat, _ = safe_normalize(rel_pos)
    Ys = real_spherical_harmonics_all(2 * max_degree, rhat, xp=jnp)

    out = {}
    for d_in, d_out in product(range(max_degree + 1), repeat=2):
        Ks = []
        for J in range(abs(d_in - d_out), d_in + d_out + 1):
            Q = jnp.asarray(basis_transformation_Q_J(J, d_in, d_out),
                            dtype=rel_pos.dtype)
            # tiny contraction — full f32 precision even on the MXU, so basis
            # accuracy (and hence equivariance error) is not bf16-limited
            K_flat = jnp.einsum('...j,kj->...k', Ys[J], Q,
                                precision=jax.lax.Precision.HIGHEST)
            Ks.append(K_flat.reshape(*K_flat.shape[:-1],
                                     2 * d_out + 1, 2 * d_in + 1))
        if layout == 'pfq_flat':
            k = jnp.stack(Ks, axis=-2)              # [..., P, F, Q]
            out[f'{d_in},{d_out}'] = k.reshape(*k.shape[:-3], -1)
        elif layout == 'pqf':
            out[f'{d_in},{d_out}'] = jnp.stack(Ks, axis=-1)
        else:
            raise ValueError(f'unknown basis layout {layout!r}')

    if not differentiable:
        out = jax.tree_util.tree_map(jax.lax.stop_gradient, out)
    return out


def num_basis_keys(max_degree: int) -> int:
    return (max_degree + 1) ** 2
