"""Ring sequence-parallel neighbor selection for long point clouds.

The O(N^2) pairwise distance matrix is the reference's long-context scaling
wall (it materializes [b, n, n-1] host tensors before top-k — reference
se3_transformer_pytorch.py:1222,1277; SURVEY.md §5 'long-context'). With
the node axis sharded over the `sp` mesh axis, this module computes exact
kNN without ever materializing a full distance row:

  each device holds a query block [b, n_local] and a source block; at every
  ring step it scores queries against the current source block, merges a
  running top-K via fixed-size top_k on the concatenation, and ppermutes
  the source block to the next device over ICI. After sp steps every query
  has its exact K nearest — peak memory O(n_local^2) instead of
  O(n_local * N).

Two comm disciplines (PR 5):

  * the ring is DOUBLE-BUFFERED by default (`overlap=True`): the
    ppermute moving the source block for step t+1 is issued *before*
    step t's score/merge, so the ICI transfer hides under the
    O(n_local^2) distance compute instead of serializing with it
    (`ring_scan` below — the same helper drives
    `parallel.exchange.neighbor_gather`);
  * scoring runs on SQUARED distances (one multiply-add per pair instead
    of a sqrt over [b, nl, nl] per ring step); the single sqrt happens
    once on the merged [b, nl, k] result. The transform is monotone, so
    selection order and the FINF / bonded-0 sentinel semantics are
    preserved exactly (`_unsquare_rank`).

This is the graph-transformer analogue of ring attention: the ring carries
key/source *coordinates* instead of k/v blocks, and what flows back is a
neighbor list that the (local, O(n_local * K)) conv/attention stage
consumes after a neighbor-sparse feature exchange (parallel.exchange).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.neighbors import FINF, _top_k_smallest


def pcast_varying(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Mark a per-shard constant as device-varying for shard_map's vma
    (varying-manual-axes) tracking."""
    return jax.lax.pcast(x, (axis_name,), to='varying')


def ring_scan(body, carry, blocks, axis_name: str, overlap: bool = True):
    """Fold `body(carry, blocks, t) -> carry` over every ring position
    t = 0..sp-1, rotating `blocks` (a tuple of per-shard arrays sharing
    their leading layout) one hop per step so each device sees every
    device's block exactly once.

    overlap=True double-buffers the rotation: the ppermute producing the
    blocks for step t+1 is issued BEFORE step t's body, so on TPU the
    ICI transfer overlaps the body's compute (XLA's async
    collective-permute scheduler needs the transfer to be
    data-independent of the in-flight body, which this ordering
    guarantees; the serialized variant chains rotate-after-score). Both
    variants issue exactly `sp` ppermutes per block and produce
    bit-identical results — the off switch exists so the overlap can be
    A/B'd and disabled without changing numerics.

    The per-pair transfer is O(b * n_local) per step either way; what
    overlap buys is hiding that latency under the O(n_local^2) score.
    """
    axis_size = jax.lax.psum(1, axis_name)
    perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]

    def rotate(bs):
        # 'ici_wait' labels the transfer for xprof attribution
        # (observability.timing.MODEL_SCOPES): in an overlapped trace the
        # scope's exclusive time is the NON-hidden remainder of the hop
        with jax.named_scope('ici_wait'):
            return tuple(jax.lax.ppermute(b, axis_name, perm) for b in bs)

    if not overlap or axis_size == 1:
        def step(c, t):
            carry, bs = c
            carry = body(carry, bs, t)
            return (carry, rotate(bs)), None

        (carry, _), _ = jax.lax.scan(
            step, (carry, blocks), jnp.arange(axis_size, dtype=jnp.int32))
        return carry

    # double-buffered: cur holds the block for step t, nxt the one for
    # step t+1 (already in flight — its ppermute was issued one body
    # ago). The final block is scored outside the scan, so the loop
    # issues sp-1 hops and the prologue 1: sp total, same as serialized.
    nxt = rotate(blocks)

    def step(c, t):
        carry, cur, nxt = c
        fut = rotate(nxt)          # kick off the t+2 transfer first ...
        carry = body(carry, cur, t)  # ... then score block t under it
        return (carry, nxt, fut), None

    (carry, cur, _), _ = jax.lax.scan(
        step, (carry, blocks, nxt),
        jnp.arange(axis_size - 1, dtype=jnp.int32))
    return body(carry, cur, axis_size - 1)


def _unsquare_rank(rank_sq: jnp.ndarray) -> jnp.ndarray:
    """Map a merged SQUARED-distance ranking back to distance space with
    the sentinel semantics intact: excluded slots carry FINF (not
    sqrt(FINF)), bonded-priority slots carry exactly 0, and the gradient
    at zero distance is 0 rather than NaN (the safe_norm double-where —
    jnp.sqrt's gradient at 0 is inf*cotangent)."""
    is_zero = rank_sq == 0
    safe = jnp.sqrt(jnp.where(is_zero, 1.0, rank_sq))
    rank = jnp.where(is_zero, 0.0, safe)
    return jnp.where(rank_sq >= FINF, FINF, rank)


def _ring_knn_local(coors_q: jnp.ndarray, coors_src: jnp.ndarray,
                    mask_src: jnp.ndarray,
                    nm_rows: Optional[jnp.ndarray],
                    sp_rows: Optional[jnp.ndarray],
                    k: int, axis_name: str,
                    causal: bool = False,
                    overlap: bool = True
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-shard body (runs under shard_map). coors_q/coors_src are this
    device's [b, nl, 3] blocks, mask_src its [b, nl] source validity.
    nm_rows/sp_rows are this device's QUERY-row shards of the full-width
    per-pair predicates ([b, nl, N]): the user neighbor mask and the
    bonded (sparse-adjacency) priority — each ring step slices the
    source-block column window out of them. Returns (rank [b, nl, k],
    idx [b, nl, k]) with idx in GLOBAL node coordinates; rank is the
    MODIFIED ranking the dense path sorts by (reference
    se3_transformer_pytorch.py:1257,1262,1267 — neighbor-mask
    exclusions FINF, bonded 0, future FINF under causal), which is what
    the `rank <= valid_radius` validity rule must consume; masked-out
    sources never occupy a neighbor slot.

    The running merge lives in SQUARED-distance space (the sentinels
    FINF and 0 are fixed points of the monotone transform, so the
    selection is unchanged); `_unsquare_rank` restores distances once at
    the end."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, nl, _ = coors_q.shape

    best_r = jnp.full((b, nl, k), FINF, coors_q.dtype)
    best_i = jnp.zeros((b, nl, k), jnp.int32)
    # mark the running top-K as device-varying for shard_map's vma tracking
    best_r = pcast_varying(best_r, axis_name)
    best_i = pcast_varying(best_i, axis_name)
    q_global = my_idx * nl + jnp.arange(nl, dtype=jnp.int32)

    def score(carry, blocks, t):
        best_r, best_i = carry
        src, m_src = blocks
        # at ring step t, this device holds the block originally owned by
        # (my_idx + t) mod axis_size
        src_owner = (my_idx + t) % axis_size
        # SQUARED distances to the current source block (no per-step sqrt)
        diff = coors_q[:, :, None] - src[:, None, :]
        d = jnp.sum(diff * diff, axis=-1)
        src_global = src_owner * nl + jnp.arange(nl, dtype=jnp.int32)
        # exclude self-pairs (same global id) and masked-out sources
        self_mask = q_global[:, None] == src_global[None, :]
        d = jnp.where(self_mask[None], FINF, d)
        d = jnp.where(m_src[:, None, :], d, FINF)
        # per-pair semantics, in the dense path's exact order (so e.g. a
        # bonded pair overrides a neighbor-mask exclusion but loses to
        # causal masking, matching ops/neighbors.select_neighbors)
        col0 = src_owner * nl
        if nm_rows is not None:
            nm_blk = jax.lax.dynamic_slice_in_dim(nm_rows, col0, nl, axis=2)
            d = jnp.where(nm_blk, d, FINF)
        if sp_rows is not None:
            sp_blk = jax.lax.dynamic_slice_in_dim(sp_rows, col0, nl, axis=2)
            # a bond to a masked-out (padded) source must not resurrect
            # it at rank 0 — the never-select-masked contract above wins
            sp_blk = sp_blk & m_src[:, None, :]
            d = jnp.where(sp_blk, 0., d)
        if causal:
            # self-excluded dense layout masks exactly source > query
            # (reference :1267 via neighbors.select_neighbors)
            future = src_global[None, :] > q_global[:, None]
            d = jnp.where(future[None], FINF, d)

        cand_d = jnp.concatenate([best_r, d], axis=-1)
        cand_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(src_global[None, None], d.shape)],
            axis=-1)
        new_r, sel = _top_k_smallest(cand_d, k)
        new_i = jnp.take_along_axis(cand_i, sel, axis=-1)
        return new_r, new_i

    best_r, best_i = ring_scan(score, (best_r, best_i),
                               (coors_src, mask_src), axis_name,
                               overlap=overlap)
    return _unsquare_rank(best_r), best_i


def ring_knn(coors: jnp.ndarray, k: int, mesh: Mesh,
             axis_name: str = 'sp',
             mask: Optional[jnp.ndarray] = None,
             neighbor_mask: Optional[jnp.ndarray] = None,
             sparse_mask: Optional[jnp.ndarray] = None,
             causal: bool = False,
             overlap: bool = True
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact kNN (self excluded) over a node-sharded coordinate tensor,
    with the dense path's full ranking semantics.

    coors [b, n, 3] with n divisible by mesh.shape[axis_name]; optional
    mask [b, n] excludes padded nodes from ever being selected as
    sources. neighbor_mask/sparse_mask are optional FULL-width per-pair
    predicates [b, n, n] (query-row sharded over the sp axis by
    construction; the column axis stays local — they are the
    user-supplied O(N^2) inputs of the adjacency configs, so holding a
    row shard is the natural cost). causal masks future sources
    (source id > query id), reference :1267. overlap double-buffers the
    ring's ppermutes so ICI hides under the score compute (bit-exact
    either way — `ring_scan`).

    Returns (rank [b, n, k], idx [b, n, k]) sharded the same way;
    indices are global node ids. `rank` is the dense path's MODIFIED
    ranking (bonded pairs 0, exclusions FINF): validity is
    `rank <= valid_radius`, and the true geometry is recomputed from
    `coors[idx]` by the caller. Plain-kNN callers can keep reading it
    as a distance (invalid slots carry FINF).

    INTENTIONAL divergence from the dense path on `mask`: masked-out
    sources are FINF'd in the ranking here (never selected), while
    select_neighbors lets them win slots by raw distance and only
    invalidates them afterwards — so on padded inputs the ring fills
    those slots with real farther neighbors instead of wasting them.
    Parity with the dense path is exact for full masks (the tests'
    contract); with padding the ring path strictly dominates.
    """
    n = coors.shape[1]
    sp = mesh.shape[axis_name]
    assert n % sp == 0, f'n={n} must divide over {axis_name}={sp}'
    if mask is None:
        mask = jnp.ones(coors.shape[:2], bool)

    spec = P(None, axis_name, None)
    mspec = P(None, axis_name)
    in_specs = [spec, spec, mspec]
    args = [coors, coors, mask]
    # rows sharded like the queries, columns full: P(None, sp, None)
    for pred in (neighbor_mask, sparse_mask):
        if pred is not None:
            assert pred.shape[-2:] == (n, n), pred.shape
            in_specs.append(spec)
            args.append(pred)
    nm_pos = 3 if neighbor_mask is not None else None
    sp_pos = (3 + (neighbor_mask is not None)) \
        if sparse_mask is not None else None

    def body(*ops):
        return _ring_knn_local(
            ops[0], ops[1], ops[2],
            ops[nm_pos] if nm_pos is not None else None,
            ops[sp_pos] if sp_pos is not None else None,
            k=k, axis_name=axis_name, causal=causal, overlap=overlap)

    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=(spec, spec))
    # scope the ring (scan of score/merge/ppermute) for xprof attribution
    # (observability.timing.MODEL_SCOPES)
    with jax.named_scope('ring_knn'):
        return fn(*args)


def dense_knn(coors: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device reference: full [b, n, n] distances + top-k.

    Scores on squared distances with one safe sqrt at the end — the same
    formulation as the ring merge, so differentiating through the
    selection distances is NaN-free at coincident points (jnp.linalg.norm's
    gradient at zero distance is NaN; the model paths use safe_norm for
    the same reason)."""
    diff = coors[:, :, None] - coors[:, None, :]
    d = jnp.sum(diff * diff, axis=-1)
    n = coors.shape[1]
    d = jnp.where(jnp.eye(n, dtype=bool)[None], FINF, d)
    rank_sq, idx = _top_k_smallest(d, k)
    return _unsquare_rank(rank_sq), idx.astype(jnp.int32)
