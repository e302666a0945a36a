"""Neighbor-sparse feature exchange for the sequence-parallel ring path.

After `parallel.ring.ring_knn` returns GLOBAL neighbor ids, every
consumer needs source-node values at those ids: coordinates, per-degree
features (once per conv/attention layer), masks. Expressed as a plain
`batched_index_select(values, idx, axis=1)` over a node-sharded operand,
GSPMD can only serve the global gather by ALL-GATHERING the full
[b, N, ...] operand onto every device — O(N) feature memory per shard
and full-width ICI traffic, which un-does exactly the O(n_local) memory
story the ring exists for.

`neighbor_gather` is the sparse replacement: a shard_map'd ring that
rotates the OWNED value blocks one hop per step (double-buffered via
`ring.ring_scan`, so the transfer hides under the select) and selects
on the fly — each device ends with only its O(n_local * k) neighbor
rows, exact-parity with the dense gather for in-range ids. Per-device
traffic is O(n_local * feature) per hop (the operand's shard size, paid
sp-1 times = one full rotation) versus the all-gather's same total but
with an O(N) resident copy and no overlap.

`rowwise_gather` covers the second gather family of the ring branch:
row-sharded FULL-column operands ([b, n, N, ...] edges / adjacency
labels) selected along the column axis by row-aligned ids. That gather
needs no communication at all — shard_map pins it local so GSPMD can
never decide to materialize the full operand. `bonded_priority_mask`
does the same for the jittered bonded-neighbor selection (noise scatter
+ per-row top-k): row-parallel by construction, yet GSPMD's scatter
partitioner serves the dense formulation with a full-width [b, N, N]
all-gather — measured, not hypothetical.

`exchange_scope` threads the mesh through the trunk without widening
every layer signature: inside the scope, `exchange_index_select`
(called by ConvSE3 / attention / EGNN neighbor gathers) routes
axis-1 gathers through `neighbor_gather`. The scope is TRACE-time
state, same discipline as jax.default_matmul_precision.

`analyze_hlo_comm` / `comm_payload` turn a compiled program's HLO text
into the schema'd `comm` record (observability.schema): per-class
collective counts + estimated bytes and the all-gather-free proof the
weak-scaling harness and `make ring-smoke` gate on.
"""
from __future__ import annotations

import contextlib
import re
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..utils.helpers import batched_index_select
from .ring import pcast_varying, ring_scan


# ------------------------------------------------------------------------- #
# neighbor-sparse gathers
# ------------------------------------------------------------------------- #
def _gather_local(vals: jnp.ndarray, idx: jnp.ndarray, axis_name: str,
                  overlap: bool = True) -> jnp.ndarray:
    """Per-shard body: vals is this device's [b, nl, *f] value block, idx
    its [b, nq, k] GLOBAL ids. Rotates value blocks around the ring; at
    each step the ids that fall inside the held block's global window
    select from it. In-range ids are hit exactly once over the full
    rotation, so the where-merge reproduces the dense gather verbatim;
    out-of-range ids (never produced by ring_knn) yield zeros."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    nl = vals.shape[1]

    out = jnp.zeros(idx.shape + vals.shape[2:], vals.dtype)
    out = pcast_varying(out, axis_name)

    def select(out, blocks, t):
        (blk,) = blocks
        owner = (my_idx + t) % axis_size
        local = idx - owner * nl
        hit = (local >= 0) & (local < nl)
        gathered = batched_index_select(
            blk, jnp.clip(local, 0, nl - 1), axis=1)   # [b, nq, k, *f]
        hit = hit.reshape(hit.shape + (1,) * (gathered.ndim - hit.ndim))
        return jnp.where(hit, gathered, out)

    return ring_scan(select, out, (vals,), axis_name, overlap=overlap)


def neighbor_gather(values: jnp.ndarray, idx: jnp.ndarray, mesh: Mesh,
                    axis_name: str = 'sp',
                    overlap: bool = True) -> jnp.ndarray:
    """Sparse equivalent of `batched_index_select(values, idx, axis=1)`
    for a node-sharded operand: values [b, n, *f] sharded over `axis_name`
    on axis 1, idx [b, n, k] global ids sharded the same way. Returns
    [b, n, k, *f] with identical sharding — no device ever holds more
    than its own value shard plus the in-flight hop buffer.

    Exact parity with the dense gather for in-range ids (the ring_knn
    contract: ids are always valid global node ids, even in invalid
    slots); masked/padded/bonded semantics live entirely in the ids and
    validity masks the caller computed, so they carry over unchanged.
    """
    n = values.shape[1]
    sp = mesh.shape[axis_name]
    assert n % sp == 0, f'n={n} must divide over {axis_name}={sp}'
    vspec = P(None, axis_name, *([None] * (values.ndim - 2)))
    ispec = P(None, axis_name, None)
    ospec = P(None, axis_name, *([None] * (values.ndim - 1)))
    fn = jax.shard_map(
        partial(_gather_local, axis_name=axis_name, overlap=overlap),
        mesh=mesh, in_specs=(vspec, ispec), out_specs=ospec)
    # 'exchange' scopes the rotation+select for xprof attribution
    # (observability.timing.MODEL_SCOPES)
    with jax.named_scope('exchange'):
        return fn(values, idx)


def rowwise_gather(values: jnp.ndarray, idx: jnp.ndarray, mesh: Mesh,
                   axis_name: str = 'sp') -> jnp.ndarray:
    """Column selection out of a query-row-sharded full-width operand:
    values [b, n, N, *f] (rows sharded over `axis_name`, column axis
    full — the layout of the ring branch's edge / adjacency-label
    tensors), idx [b, n, k] global COLUMN ids aligned with the rows.

    Every row's columns are locally resident, so this is zero-comm by
    construction; shard_map pins that, where leaving it to GSPMD's
    gather partitioner risks a full-operand materialization.
    """
    n = values.shape[1]
    sp = mesh.shape[axis_name]
    assert n % sp == 0, f'n={n} must divide over {axis_name}={sp}'
    vspec = P(None, axis_name, *([None] * (values.ndim - 2)))
    ispec = P(None, axis_name, None)
    ospec = P(None, axis_name, *([None] * (values.ndim - 2)))
    fn = jax.shard_map(lambda v, i: batched_index_select(v, i, axis=2),
                       mesh=mesh, in_specs=(vspec, ispec), out_specs=ospec)
    with jax.named_scope('exchange'):
        return fn(values, idx)


def _bonded_local(adj: jnp.ndarray, noise_n1: jnp.ndarray,
                  num_sparse: int, n: int, axis_name: str) -> jnp.ndarray:
    """Per-shard body: adj is this device's [b, nl, N] adjacency row
    block, noise_n1 its [b, nl, N-1] jitter rows (drawn in the dense
    path's self-excluded layout — the parity contract). Rebuilds the
    dense construction row-locally: scatter the noise to full width
    through the LOCAL rows' self-exclusion map, drop the diagonal, take
    the jittered per-row top-k."""
    from ..ops.neighbors import sparse_neighbor_mask

    b, nl, _ = adj.shape
    my_idx = jax.lax.axis_index(axis_name)
    gids = my_idx * nl + jnp.arange(nl, dtype=jnp.int32)
    # exclude_self_indices rows for the local block: global row g lists
    # source j + (j >= g), j in [0, N-1)
    j = jnp.arange(n - 1, dtype=jnp.int32)[None, :]
    self_excl = j + (j >= gids[:, None])
    noise_full = jnp.zeros((b, nl, n), noise_n1.dtype).at[
        :, jnp.arange(nl)[:, None], self_excl].set(noise_n1)
    not_self = gids[:, None] != jnp.arange(n)[None, :]
    adj_noself = adj.astype(bool) & not_self[None]
    return sparse_neighbor_mask(adj_noself, num_sparse, noise_full)


def bonded_priority_mask(adj_mat: jnp.ndarray, noise_n1: jnp.ndarray,
                         num_sparse: int, mesh: Mesh,
                         axis_name: str = 'sp') -> jnp.ndarray:
    """Row-sharded construction of the jittered bonded-priority mask
    (models _adjacency_predicates): adj_mat [b, N, N], noise_n1
    [b, N, N-1] (the dense layout's draw — same rng stream as the dense
    branch, so the jittered top-k picks identical bonded subsets),
    returns the [b, N, N] bool mask with rows sharded over `axis_name`.

    The dense formulation's noise scatter + per-row top-k are row-
    parallel by construction, but GSPMD's scatter partitioner serves
    them with a full-width [b, N, N] all-gather (measured — the exact
    artifact class `make ring-smoke` gates). shard_map pins every step
    to the local row block: zero collectives, exact parity (the ring
    sparse-adjacency tests compare the full model against the dense
    branch)."""
    n = adj_mat.shape[1]
    sp = mesh.shape[axis_name]
    assert n % sp == 0, f'n={n} must divide over {axis_name}={sp}'
    row = P(None, axis_name, None)
    fn = jax.shard_map(
        partial(_bonded_local, num_sparse=num_sparse, n=n,
                axis_name=axis_name),
        mesh=mesh, in_specs=(row, row), out_specs=row)
    with jax.named_scope('exchange'):
        return fn(adj_mat, noise_n1)


# ------------------------------------------------------------------------- #
# trunk routing: trace-time exchange scope
# ------------------------------------------------------------------------- #
class _ExchangeScope(NamedTuple):
    mesh: Mesh
    axis_name: str
    overlap: bool


_SCOPES: list = []   # trace-time stack (same discipline as jax context
#                      managers: tracing is single-threaded per program)


@contextlib.contextmanager
def exchange_scope(mesh: Mesh, axis_name: str = 'sp',
                   overlap: bool = True):
    """While active, `exchange_index_select` routes node-axis neighbor
    gathers through `neighbor_gather(mesh, axis_name)`. Entered by the
    model's ring branch around the trunk so ConvSE3/attention/EGNN need
    no signature change; a no-op for every other caller."""
    _SCOPES.append(_ExchangeScope(mesh, axis_name, overlap))
    try:
        yield
    finally:
        _SCOPES.pop()


def active_exchange() -> Optional[_ExchangeScope]:
    return _SCOPES[-1] if _SCOPES else None


def exchange_index_select(values: jnp.ndarray, indices: jnp.ndarray,
                          axis: int = 1) -> jnp.ndarray:
    """`batched_index_select` that becomes neighbor-sparse under an
    active exchange scope. Falls back to the dense gather whenever the
    operand doesn't fit the exchange layout (non-node axis, node count
    not divisible over the mesh axis, non-[b, n, k] indices)."""
    scope = active_exchange()
    if scope is None or axis != 1 or indices.ndim != 3 \
            or values.ndim < 2 \
            or values.shape[:1] != indices.shape[:1] \
            or values.shape[1] % scope.mesh.shape[scope.axis_name] != 0 \
            or values.shape[1] != indices.shape[1]:
        return batched_index_select(values, indices, axis=axis)
    return neighbor_gather(values, indices, scope.mesh,
                           axis_name=scope.axis_name,
                           overlap=scope.overlap)


# ------------------------------------------------------------------------- #
# comm accounting from traced HLO
# ------------------------------------------------------------------------- #
_DTYPE_BYTES = dict(pred=1, s8=1, u8=1, s16=2, u16=2, bf16=2, f16=2,
                    s32=4, u32=4, f32=4, s64=8, u64=8, f64=8, c64=8,
                    c128=16)

# collective classes as they appear in post-SPMD HLO text. Sync ops
# carry a plain result shape; async pairs appear as <op>-start/-done
# where the -start result is a TUPLE — e.g. on TPU
#   %ags = (f32[1,256,3], f32[1,2048,3]) all-gather-start(...)
# (operand alias first, transferred result after, sometimes trailing
# u32[] context scalars). The shape field therefore matches EITHER a
# single shape token or a whole parenthesized tuple; the -start side is
# counted once and -done is skipped.
_COLLECTIVE_RE = re.compile(
    r'=\s*(?P<shapes>\([^()]*\)|\S+)\s+'
    r'(?P<cls>all-gather|all-reduce|collective-permute|all-to-all|'
    r'reduce-scatter)'
    r'(?P<phase>-start|-done)?\(')
_SHAPE_RE = re.compile(r'(\w+)\[([\d,]*)\]')
_GATHER_DIM_RE = re.compile(r'dimensions=\{(\d+)\}')


def analyze_hlo_comm(hlo_text: str,
                     full_width_dim: Optional[int] = None,
                     mesh_shape: Optional[dict] = None,
                     seq_axis: str = 'sp') -> dict:
    """Parse compiled (post-partitioning) HLO text into per-class
    collective counts and estimated byte volumes.

    full_width_dim: the GLOBAL node count N. An all-gather is flagged as
    full-width when its output carries the whole node axis — gather
    dimension >= 1 (node-sharded operands here are [b, n, ...] /
    [b, n, N, ...]; axis 0 is batch) with output size >= N at that
    dimension. Keying on the op's `dimensions={...}` attribute rather
    than any-dim-matches keeps replicated-parameter all-gathers (axis-0
    gathers whose sizes are unrelated to N) out of the proof bit
    `make ring-smoke` gates on. Byte estimates are shape upper bounds of
    each op's transferred result, per execution of the op's computation
    (loop trip counts are invisible in HLO text — stated as per-class
    *shape* bytes, not per-step traffic).

    mesh_shape (ordered {axis: size}, see `attribute_collective_axes`):
    makes the full-width scan AXIS-AWARE for composed meshes. The node
    axis is sharded over `seq_axis` only, so a >= N output dimension
    can only be materialized by gathering across the seq-axis device
    groups — an all-gather whose replica groups hold the seq coordinate
    fixed (a dp weight prefetch, a tp channel gather) cannot
    rematerialize the sequence even when an unrelated channel dim
    happens to reach N (heads*dim_head collides with toy node counts).
    A flagged line with no group attribute spans every device and stays
    counted; with seq_axis at size 1 nothing shards the sequence and no
    grouped gather is flagged.
    """
    seq_varies = None
    if mesh_shape is not None:
        axis_names = list(mesh_shape)
        sizes = [int(mesh_shape[a]) for a in axis_names]
        seq_idx = axis_names.index(seq_axis) if seq_axis in axis_names \
            else None

        def seq_varies(line):
            groups = _collective_groups(line)
            if groups is None:
                return True  # spans every device, incl. the seq axis
            if seq_idx is None:
                return False
            for grp in groups:
                base = _device_coords(grp[0], sizes)[seq_idx]
                for member in grp[1:]:
                    if _device_coords(member, sizes)[seq_idx] != base:
                        return True
            return False

    classes: dict = {}
    full_width_hits = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None or m.group('phase') == '-done':
            continue
        cls = m.group('cls')
        shapes = []
        for dtype, dims_s in _SHAPE_RE.findall(m.group('shapes')):
            dims = [int(d) for d in dims_s.split(',') if d]
            size = _DTYPE_BYTES.get(dtype, 4)
            for d in dims:
                size *= d
            shapes.append((size, dtype, dims_s, dims))
        if not shapes:
            continue
        # async -start tuples: the transferred payload is the largest
        # element (the operand alias is 1/axis_size of it, the context
        # scalars are bytes); for sync ops there is exactly one
        size, dtype, dims_s, dims = max(shapes, key=lambda s: s[0])
        entry = classes.setdefault(cls, dict(count=0, bytes=0))
        entry['count'] += 1
        entry['bytes'] += size
        if cls == 'all-gather' and full_width_dim is not None:
            gd = _GATHER_DIM_RE.search(line)
            if gd is not None:
                axis = int(gd.group(1))
                full = axis >= 1 and axis < len(dims) \
                    and dims[axis] >= full_width_dim
            else:  # no dimensions attribute — conservative any-dim scan
                full = any(d >= full_width_dim for d in dims[1:])
            if full and seq_varies is not None and not seq_varies(line):
                full = False
            if full:
                full_width_hits.append(f'{dtype}[{dims_s}]')
    return dict(
        collectives=classes,
        full_width_all_gathers=full_width_hits,
        all_gather_free=not full_width_hits,
    )


# per-axis attribution: map each collective's replica groups back onto
# mesh axes. Post-SPMD HLO names groups either explicitly
# (replica_groups={{0,1},{2,3}}), in the iota form
# (replica_groups=[4,2]<=[8] or [4,2]<=[2,4]T(1,0)), or — for
# collective-permute — as source_target_pairs={{0,2},{2,0}}.
_EXPLICIT_GROUPS_RE = re.compile(
    r'replica_groups=\{(\{[^{}]*\}(?:,\{[^{}]*\})*)\}')
_IOTA_GROUPS_RE = re.compile(
    r'replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]'
    r'(?:T\(([\d,]+)\))?')
_PAIRS_RE = re.compile(
    r'source_target_pairs=\{(\{[^{}]*\}(?:,\{[^{}]*\})*)\}')


def _collective_groups(line: str) -> Optional[list]:
    """Device-id groups of one HLO collective line (each a list of
    ints), or None when the line carries no group attribute."""
    m = _EXPLICIT_GROUPS_RE.search(line) or _PAIRS_RE.search(line)
    if m is not None:
        return [[int(x) for x in grp.split(',') if x]
                for grp in m.group(1)[1:-1].split('},{')]
    m = _IOTA_GROUPS_RE.search(line)
    if m is not None:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(',')]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(',')])
        return ids.reshape(n_groups, group_size).tolist()
    return None


def _device_coords(device_id: int, sizes) -> tuple:
    coords = []
    for size in reversed(sizes):
        coords.append(device_id % size)
        device_id //= size
    return tuple(reversed(coords))


def attribute_collective_axes(hlo_text: str, mesh_shape: dict) -> dict:
    """Per-mesh-axis collective {count, bytes} from partitioned HLO.

    mesh_shape: ordered {axis: size} as `parallel.mesh.mesh_shape_dict`
    returns it — device id = row-major index into that shape, which
    holds for `make_mesh` over the default device order (the CPU-sim
    meshes every sweep/test here runs on; a permuted physical mesh
    would need the id->coords map threaded through instead).

    Each collective op is classified by the mesh coordinates its
    replica groups (or ppermute source/target pairs) vary over: a group
    whose members differ only in the tp coordinate is tp traffic, the
    gradient psum over dp and sp lands under 'dp+sp', and an op whose
    groups never leave one device (or a mesh axis of size 1) counts as
    'local'. Byte values are the same per-op transferred-shape upper
    bounds `analyze_hlo_comm` reports, so the per-axis split sums to
    (at most) its per-class totals. Ops with no group attribute span
    every device and land on the joint label of all size>1 axes."""
    axis_names = list(mesh_shape)
    sizes = [int(mesh_shape[a]) for a in axis_names]
    wide = [a for a, s in zip(axis_names, sizes) if s > 1]
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None or m.group('phase') == '-done':
            continue
        shapes = []
        for dtype, dims_s in _SHAPE_RE.findall(m.group('shapes')):
            dims = [int(d) for d in dims_s.split(',') if d]
            size = _DTYPE_BYTES.get(dtype, 4)
            for d in dims:
                size *= d
            shapes.append(size)
        if not shapes:
            continue
        nbytes = max(shapes)
        groups = _collective_groups(line)
        if groups is None:
            label = '+'.join(wide) if wide else 'local'
        else:
            varying = set()
            for grp in groups:
                base = _device_coords(grp[0], sizes)
                for member in grp[1:]:
                    for name, a, b in zip(axis_names, base,
                                          _device_coords(member, sizes)):
                        if a != b:
                            varying.add(name)
            label = '+'.join(a for a in axis_names if a in varying) \
                or 'local'
        entry = out.setdefault(label, {}).setdefault(
            m.group('cls'), dict(count=0, bytes=0))
        entry['count'] += 1
        entry['bytes'] += nbytes
    return out


def comm_payload(hlo_text: str, *, sp: int, ring_steps: int,
                 overlap: bool, exchange: bool,
                 full_width_dim: Optional[int] = None,
                 mesh_shape: Optional[dict] = None) -> dict:
    """The schema'd `comm` record body (observability.schema kind='comm',
    minus run_id): ring configuration + the HLO-derived collective
    accounting. Attachable verbatim to bench records and flush payloads.
    With `mesh_shape` (an ordered {axis: size} dict, see
    `attribute_collective_axes`) the payload additionally carries
    `axis_collectives` — the per-mesh-axis split the composed-mesh
    budgets gate on — and the full-width all-gather scan becomes
    axis-aware (only sp-varying gathers can rematerialize the
    sequence; see `analyze_hlo_comm`)."""
    payload = dict(sp=sp, ring_steps=ring_steps, overlap=overlap,
                   exchange=exchange)
    payload.update(analyze_hlo_comm(hlo_text, full_width_dim=full_width_dim,
                                    mesh_shape=mesh_shape))
    if mesh_shape is not None:
        payload['axis_collectives'] = attribute_collective_axes(
            hlo_text, mesh_shape)
        payload['mesh'] = dict(mesh_shape)
    return payload
