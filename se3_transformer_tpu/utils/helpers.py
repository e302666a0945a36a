"""Generic tensor helpers (TPU-native analogues of reference utils.py).

Reference: /root/reference/se3_transformer_pytorch/utils.py — this module
re-provides the same helper surface (exists/default/to_order/
batched_index_select/masked_mean/fourier_encode/broadcat/...) as pure
jit-traceable JAX functions with static shapes.
"""
from __future__ import annotations

import time
from functools import wraps

import jax
import jax.numpy as jnp


def exists(val):
    return val is not None


def default(val, d):
    return val if exists(val) else d


def uniq(arr):
    return list({el: True for el in arr}.keys())


def to_order(degree: int) -> int:
    """Dimension of the degree-l irrep of SO(3): 2l + 1."""
    return 2 * degree + 1


def map_values(fn, d: dict) -> dict:
    return {k: fn(v) for k, v in d.items()}


def is_tpu_backend() -> bool:
    """True when the default backend's platform is 'tpu'. Every kernel
    auto-dispatch rule (ops.conv, kernels.pallas_pairwise,
    kernels.pallas_flash, the one-hot gather below) asks this one
    question; a backend that fails to initialize raises here instead of
    quietly turning the kernels off."""
    return jax.default_backend() == 'tpu'


def safe_cat(arr, el, axis):
    if not exists(arr):
        return el
    return jnp.concatenate((arr, el), axis=axis)


def cast_tuple(val, depth):
    return val if isinstance(val, tuple) else (val,) * depth


def batched_index_select(values: jnp.ndarray, indices: jnp.ndarray, axis: int = 1) -> jnp.ndarray:
    """Gather `values` along `axis` with batched integer `indices`.

    values:  [..., n, *value_dims]  where n sits at `axis`
    indices: [..., *idx_dims] — leading dims must match values[:axis]
    returns: values with axis `axis` replaced by idx_dims.

    Equivalent of reference utils.py:56 (batched_index_select) expressed with
    jnp.take_along_axis so XLA lowers it to a single gather.

    CONTRACT: indices must be IN-RANGE [0, n) and `values`
    FINITE. On TPU, large float gathers dispatch to a one-hot MXU matmul
    (`_onehot_gather`) whose semantics diverge from the CPU take path
    exactly outside this contract: OOB indices yield zero rows (take
    clips), and a non-finite element anywhere in `values` poisons every
    output via 0*NaN (take reads only addressed rows) — so a dataset
    with un-zeroed padded rows produces TPU-only NaNs that vanish on CPU.
    The model's own neighbor pipeline satisfies the contract by
    construction (ops.neighbors builds indices from iota); external
    callers passing `neighbors=` must zero masked rows themselves.
    """
    value_dims = values.shape[axis + 1:]
    batch_dims = values.shape[:axis]
    idx_extra = indices.shape[len(batch_dims):]
    flat_idx = indices.reshape(*batch_dims, -1)
    if _use_onehot_gather(values, flat_idx, axis):
        return _onehot_gather(values, flat_idx).reshape(
            *batch_dims, *idx_extra, *value_dims)
    # vmap'd jnp.take keeps the gather indices at [batch..., K]: the old
    # take_along_axis formulation broadcast them across every trailing
    # value dim, and XLA materialized s32 index tensors of the FULL
    # gathered shape with a tile-padded trailing singleton — 1.00 GB
    # EACH at flagship scale (E=32768, dim=64; round-3 HBM OOM dump)
    take = lambda v, i: jnp.take(v, i, axis=0)  # noqa: E731
    for _ in batch_dims:
        take = jax.vmap(take)
    out = take(values, flat_idx)
    return out.reshape(*batch_dims, *idx_extra, *value_dims)


def _use_onehot_gather(values, flat_idx, axis) -> bool:
    """Route large node-axis gathers through the MXU (see _onehot_gather).

    XLA lowers a big float gather to an element-flattened kGather running
    at ~1.4 GB/s on TPU — measured 209 ms PER BLOCK for the flagship's
    neighbor-feature gather (f32[14.7M], round-3 profile trace,
    fusion.11). The one-hot matmul formulation runs the same gather on
    the MXU in ~1-2 ms. Worth it when the gathered volume is large, the
    node axis is modest (the one-hot factor is [K, n]), and the values
    are float (one-hot rows are exact in any float precision).
    """
    n = values.shape[axis]
    row = 1
    for d in values.shape[axis + 1:]:
        row *= d
    work = flat_idx.size * row
    # flat_idx.size * n bounds the materialized one-hot factor itself:
    # 2^28 f32 elements = 1 GiB (flagship gather: 33792 * 1024 = 0.13 GiB).
    # Without this cap, n=8192 with n*32 edges would build an 8.6 GiB
    # one-hot and OOM worse than the kGather it replaces.
    return (is_tpu_backend()
            and jnp.issubdtype(values.dtype, jnp.floating)
            and n <= 8192 and row >= 8 and work >= (1 << 20)
            and flat_idx.size * n <= (1 << 28))


def _onehot_gather(values, flat_idx):
    """values [*B, n, *V], flat_idx [*B, K] -> [*B, K, *V] via
    one_hot(idx) @ values on the MXU.

    Exact for f32 values under 3-pass float32 precision: every output
    element is a single 1.0 * x product (the bf16 triple-split of x
    recombines to x exactly). OOB indices yield ZERO rows (jax one_hot
    semantics) where jnp.take clips — neighbor indices are in-range by
    construction (ops.neighbors builds them from iota).

    NaN caveat: the reduction touches EVERY row (0 * NaN = NaN), so a
    non-finite value anywhere in `values` poisons all outputs, where
    take reads only the addressed rows. Acceptable here: a non-finite
    node feature means training is already diverged, and a where-guard
    would forfeit the MXU formulation this path exists for.
    """
    nb = flat_idx.ndim - 1
    n = values.shape[nb]
    value_dims = values.shape[nb + 1:]
    row = 1
    for d in value_dims:
        row *= d
    v2 = values.reshape(*values.shape[:nb], n, row)
    oh = jax.nn.one_hot(flat_idx, n, dtype=values.dtype)     # [*B, K, n]
    out = jnp.matmul(oh, v2, precision=jax.lax.Precision('float32'))
    return out.reshape(*flat_idx.shape, *value_dims)


def masked_mean(tensor: jnp.ndarray, mask, axis: int = -1) -> jnp.ndarray:
    """Mean over `axis` counting only entries where mask is True.

    mask broadcasts from the left (trailing dims of tensor are kept).
    Mirrors reference utils.py:72 semantics (0 where nothing is valid).
    """
    if mask is None:
        return tensor.mean(axis=axis)
    diff_len = tensor.ndim - mask.ndim
    mask = mask.reshape(mask.shape + (1,) * diff_len)
    tensor = jnp.where(mask, tensor, 0.)

    total_el = mask.sum(axis=axis)
    mean = tensor.sum(axis=axis) / jnp.clip(total_el, 1, None).astype(tensor.dtype)
    return jnp.where(total_el == 0, 0., mean)


def fourier_encode(x: jnp.ndarray, num_encodings: int = 4, include_self: bool = True,
                   flatten: bool = True) -> jnp.ndarray:
    """Sin/cos positional features at dyadic scales (reference utils.py:96)."""
    x = x[..., None]
    orig_x = x
    scales = 2 ** jnp.arange(num_encodings, dtype=x.dtype)
    x = x / scales
    x = jnp.concatenate([jnp.sin(x), jnp.cos(x)], axis=-1)
    if include_self:
        x = jnp.concatenate((x, orig_x), axis=-1)
    if flatten:
        x = x.reshape(*x.shape[:3], -1)
    return x


def broadcat(tensors, axis=-1):
    """Concatenate after broadcasting every non-concat dim to the max size
    (reference utils.py:38)."""
    ndim = tensors[0].ndim
    assert all(t.ndim == ndim for t in tensors)
    axis = axis % ndim
    shapes = [list(t.shape) for t in tensors]
    target = []
    for d in range(ndim):
        if d == axis:
            target.append(None)
        else:
            target.append(max(s[d] for s in shapes))
    out = []
    for t in tensors:
        shape = [t.shape[d] if d == axis else target[d] for d in range(ndim)]
        out.append(jnp.broadcast_to(t, shape))
    return jnp.concatenate(out, axis=axis)


def benchmark(fn):
    """Wall-clock a function call, blocking on JAX async dispatch."""
    @wraps(fn)
    def inner(*args, **kwargs):
        start = time.time()
        res = fn(*args, **kwargs)
        res = jax.block_until_ready(res)
        return time.time() - start, res
    return inner


def masked_fill(tensor, mask, value):
    return jnp.where(mask, jnp.asarray(value, dtype=tensor.dtype), tensor)


def safe_norm(x: jnp.ndarray, axis: int = -1, keepdims: bool = False):
    """L2 norm with a well-defined (zero) gradient at x = 0.

    jnp.linalg.norm's gradient at 0 is NaN; torch subgradients to 0 there.
    Exactly-zero vectors occur structurally (EGNN self-loops, padded
    neighbors), so use the double-where trick: the forward value is exact,
    the 0-branch blocks the NaN cotangent.
    """
    sq = jnp.sum(x * x, axis=axis, keepdims=keepdims)
    is_zero = sq == 0
    safe = jnp.sqrt(jnp.where(is_zero, 1.0, sq))
    return jnp.where(is_zero, 0.0, safe)


def fetch_sync(tree) -> None:
    """Synchronize with the device by HOST-MATERIALIZING every array leaf
    (np.asarray): a device->host copy cannot return before the value
    exists, so it closes a timing window as surely as
    jax.block_until_ready and hands the host the values it is about to
    read anyway. Fetch only SMALL leaves (scalars/losses/one param
    tensor) — the copy itself must stay negligible next to what is
    being timed.
    """
    import numpy as _np
    for leaf in jax.tree_util.tree_leaves(tree):
        _np.asarray(leaf)


# Deterministic device-memory exhaustion, as XLA words it: one list, so
# every harness that sweeps sizes records a size that cannot fit the
# same way instead of retrying it.
OOM_SIGNATURES = ('out of memory', 'resource_exhausted',
                  'exceeded hbm capacity')


def is_oom_error(msg: str) -> bool:
    low = msg.lower()
    return any(s in low for s in OOM_SIGNATURES)


def fetch_sync_tail(tree) -> None:
    """fetch_sync for potentially LARGE results: materialize a single
    element of the first leaf. Any dependent op gates the producing
    program, so one element proves completion without copying MB-scale
    activations to the host inside a timing window."""
    import numpy as _np
    leaves = jax.tree_util.tree_leaves(tree)
    if leaves:
        _np.asarray(leaves[0].ravel()[:1])
