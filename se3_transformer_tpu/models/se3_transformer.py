"""SE3Transformer — the flagship model / user API.

TPU-native rework of reference SE3Transformer
(/root/reference/se3_transformer_pytorch/se3_transformer_pytorch.py:936-1375)
reproducing its full constructor surface (:937-982) and forward conventions
(:1124-1134) as a flax.linen module with static shapes throughout:

  * every data-dependent quantity of the reference (`.item()` topk sizes,
    dynamic neighbor counts, boolean masked_select) becomes static config +
    fixed-K top-k with validity masks — the jit/pjit-safe formulation;
  * `reversible=True` maps to jax.checkpoint (rematerialized blocks) rather
    than RevNet inverse math (same activation-memory class, exact
    determinism through explicit PRNG keys — reference reversible.py);
  * the basis is computed in-trace (polynomial SH) with Q_J constants baked
    at trace time; `differentiable_coors` honestly gates coordinate
    gradients via stop_gradient.

A thin eager wrapper (`SE3Transformer`) holds params and mimics the
reference's call signature; the functional module (`SE3TransformerModule`)
is what you jit / pjit / shard.
"""
from __future__ import annotations

import re
from functools import partial
from typing import Dict, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..basis import get_basis
from ..ops.conv import (
    BackendSpec, ConvSE3, basis_layout, resolve_conv_backend,
)
from ..ops.trunk import SequentialTrunk
from ..ops.core import LinearSE3, NormSE3
from ..ops.egnn import EGnnNetwork
from ..ops.fiber import Fiber
from ..ops.neighbors import (
    Neighborhood, exclude_self_indices, expand_adjacency, remove_self,
    select_neighbors, sparse_neighbor_mask,
)
from ..ops.rotary import sinusoidal_embeddings
from ..utils.helpers import (
    batched_index_select, cast_tuple, masked_mean, safe_cat, safe_norm,
    to_order,
)
from ..observability import named_scope

Features = Dict[str, jnp.ndarray]

# Degree-1 features are Cartesian (x, y, z) at the user boundary — the
# reference's contract (tests rotate them with the raw 3x3 matrix). Our
# real-SH irrep ordering is m = (-1, 0, 1) ~ (y, z, x), so convert on the
# way in/out; D_1 = P R P^T makes type-1 outputs transform exactly as
# Cartesian vectors (see tests/test_wigner.py::
# test_degree_one_is_cartesian_conjugate).
_CART_TO_IRREP = (1, 2, 0)
_IRREP_TO_CART = (2, 0, 1)


def _permute_degree1(features: Features, perm) -> Features:
    if '1' not in features:
        return features
    t = features['1']
    return {**features, '1': t[..., jnp.asarray(perm)]}


class SE3TransformerModule(nn.Module):
    """Functional SE(3)-Transformer. Field-for-field parity with the
    reference constructor (se3_transformer_pytorch.py:937-982)."""
    dim: Union[int, Tuple[int, ...]]
    heads: int = 8
    dim_head: int = 24
    depth: int = 2
    input_degrees: int = 1
    num_degrees: Optional[int] = None
    output_degrees: int = 1
    valid_radius: float = 1e5
    reduce_dim_out: bool = False
    num_tokens: Optional[int] = None
    num_positions: Optional[int] = None
    num_edge_tokens: Optional[int] = None
    edge_dim: Optional[int] = None
    reversible: bool = False
    # reversible remat policy: None = recompute everything (O(1)
    # activations), 'save_conv_outputs' = store the ConvSE3 results so
    # the backward replay skips the dominant radial contraction
    # (ops/trunk.py::_resolve_remat_policy)
    remat_policy: Optional[str] = None
    attend_self: bool = True
    use_null_kv: bool = False
    differentiable_coors: bool = False
    fourier_encode_dist: bool = False
    rel_dist_num_fourier_features: int = 4
    num_neighbors: Union[int, float] = float('inf')
    attend_sparse_neighbors: bool = False
    num_adj_degrees: Optional[int] = None
    adj_dim: int = 0
    max_sparse_neighbors: Union[int, float] = float('inf')
    dim_in: Optional[Union[int, Tuple[int, ...]]] = None
    dim_out: Optional[int] = None
    norm_out: bool = False
    num_conv_layers: int = 0
    causal: bool = False
    global_feats_dim: Optional[int] = None
    linear_proj_keys: bool = False
    one_headed_key_values: bool = False
    tie_key_values: bool = False
    rotary_position: bool = False
    rotary_rel_dist: bool = False
    norm_gated_scale: bool = False
    use_egnn: bool = False
    egnn_hidden_dim: int = 32
    egnn_weights_clamp_value: Optional[float] = None
    egnn_feedforward: bool = False
    hidden_fiber_dict: Optional[Dict[int, int]] = None
    out_fiber_dict: Optional[Dict[int, int]] = None
    # contraction backend per conv layer (ops.conv.CONV_BACKENDS):
    # 'dense' (default — the CG tensor product) or 'so2' (the banded
    # SO(2) reduction, se3_transformer_tpu.so2 — the higher-degree
    # path), applied to every ConvSE3; or first-match-wins
    # (layer-name regex, backend) pairs to MIX backends per layer,
    # e.g. (('to_[vk]', 'so2'), ('.*', 'dense')). Layer names:
    # 'conv_in', 'preconv{i}', 'attn_block{i}/to_v',
    # 'attn_block{i}/to_k', 'conv_out'. Dense basis tensors are built
    # only for layers that need them; so2 edge frames likewise — an
    # all-so2 model never pays the O(P*Q*F) per-edge basis at all.
    conv_backend: BackendSpec = 'dense'
    # streaming flash-style attention (kernels.pallas_flash): route a
    # block's k/v + attention through ONE kernel that rebuilds the
    # pairwise contraction per VMEM tile with an online softmax — the
    # per-edge basis, the gathered/keyed features, and the [b, h, n, J]
    # scores never exist in HBM, and the recompute-in-backward
    # custom_vjp composes with reversible=True for near-O(1) activation
    # memory. True/False applies to every attention block; or
    # first-match-wins (block-name regex, 'flash'|'xla') pairs mirror
    # conv_backend's per-layer selection, e.g.
    # (('attn_block[01]', 'flash'), ('.*', 'xla')). Block names:
    # 'attn_block{i}'. The dense CG arm and the so2 banded arm are
    # selected by conv_backend per to_v/to_k layer as usual. Implies
    # the shared-radial grouped parameter layout for the fused blocks'
    # kv convs (checkpoint-compatible with shared_radial_hidden=True).
    # Unsupported alongside rotary embeddings, linear_proj_keys, and
    # sequence_parallel.
    fuse_pairwise: Union[bool, Tuple[Tuple[str, str], ...]] = False
    flash_interpret: bool = False  # tests: interpreter-mode flash kernel
    # None -> auto (Pallas fused pairwise kernel on TPU, XLA elsewhere)
    pallas: Optional[bool] = None
    # contract the angular basis inside the pairwise kernel (forward):
    # the V2 intermediate never touches HBM (kernels.pallas_pairwise, bxf)
    fuse_basis: bool = False
    # bf16 radial trunk/matmul (rotation-invariant inputs: preserves
    # equivariance, MXU-native speed — see ops.conv.radial_hidden)
    radial_bf16: bool = False
    pallas_interpret: bool = False  # tests: interpreter-mode conv kernel
    # None -> auto: fused per-degree attention kernel on TPU (sim/softmax/
    # weighted-sum in VMEM, one kv pass — kernels.pallas_attention)
    pallas_attention: Optional[bool] = None
    pallas_attention_interpret: bool = False  # tests: interpreter-mode kernel
    # matmul precision policy: None = backend default (bf16 MXU on TPU,
    # fastest), 'float32'/'highest' = strict (equivariance < 1e-4 on TPU;
    # see scripts/tpu_checks.py). The basis itself is always full precision.
    matmul_precision: Optional[str] = None
    # share one radial hidden trunk across degree pairs (perf option)
    shared_radial_hidden: bool = False
    # stream the node axis through the pairwise contraction in N remat'd
    # chunks (memory ceiling for huge channel counts; composes with the
    # Pallas kernel, which then bounds VMEM within each chunk)
    edge_chunks: Optional[int] = None
    # 'ring' = sequence-parallel neighbor selection: exact kNN via a ring
    # of ppermutes over `mesh`'s sp axis (parallel.ring), so the O(N^2)
    # distance/top-k tensors of the dense path (reference :1222) never
    # exist on any device. Requires `mesh`; plain-kNN semantics only.
    sequence_parallel: Optional[str] = None
    mesh: Optional[jax.sharding.Mesh] = None
    # ring comm knobs (parallel/ring.py, parallel/exchange.py). Both are
    # bit-exact off-switches kept for A/B measurement:
    #   ring_overlap   double-buffer the ring's ppermutes so ICI hides
    #                  under the score/select compute (identical results
    #                  either way — parallel.ring.ring_scan);
    #   ring_exchange  neighbor-sparse feature exchange: gather coors/
    #                  mask/edges/adjacency AND the trunk's neighbor
    #                  features by rotating owned value blocks instead of
    #                  a GSPMD global gather (which all-gathers the full
    #                  [b, N, ...] operand onto every device). Off = the
    #                  dense batched_index_select path, exact parity.
    ring_overlap: bool = True
    ring_exchange: bool = True
    # attention_mode='global': the kNN-free large-assembly mode. No
    # neighbor selection, no get_basis, no exchange_index_select — every
    # node attends to every node, with rel_pos/rel_dist, the radial
    # hidden and the SH/frames payload rebuilt per VMEM tile from raw
    # coordinates inside the streaming kernel (kernels.pallas_flash
    # global mode): activation memory is O(n) at O(n^2) compute, the
    # regime where n=4k-32k assemblies become admissible at all. The
    # input projection becomes a LinearSE3 lift (zero-filled for hidden
    # degrees the input lacks), the trunk runs the same attention blocks
    # in global mode (dense or so2 arm per conv_backend), and the output
    # projection is a LinearSE3 over the hidden degrees. Composes with
    # reversible=True and with sequence_parallel='ring' (queries stay
    # pinned, kv blocks rotate by ppermute — no full-width all-gather;
    # the ring exchange scope is live on this path).
    attention_mode: str = 'knn'
    # the O(n^2)-memory control arm for A/B (bench --assembly /
    # assembly_smoke): identical params and math, per-edge tensors
    # fully materialized, plain autodiff
    global_materialize: bool = False

    # checkpoint/capability family stamp (no annotation: NOT a flax
    # field). training/checkpoint.py guards restores on it — a v1
    # checkpoint must never be silently keyed into the v2 family
    # (se3_transformer_tpu/v2) or vice versa — and serving surfaces it
    # next to the precision mixes for family-aware placement.
    model_family = 'se3_v1'

    def __post_init__(self):
        # fiber dicts arrive as {degree: channels} with INT keys — the
        # reference's constructor surface. flax registers submodule
        # attributes through serialization.to_state_dict, which asserts
        # string keys on any dict-typed attribute, so module.init/clone
        # crashed on the raw dict (the seed-inherited tier-1 failure).
        # Normalize to a hashable tuple of (degree, channels) pairs at
        # construction; Fiber() accepts the pair form directly.
        for field in ('hidden_fiber_dict', 'out_fiber_dict'):
            val = getattr(self, field)
            if val is not None and not isinstance(val, tuple):
                object.__setattr__(
                    self, field,
                    tuple(sorted((int(d), int(c)) for d, c in val.items())))
        # per-layer backend rules may arrive as {pattern: backend} or a
        # list of pairs — normalize to a hashable tuple of pairs
        # (ORDER-PRESERVING: first match wins, so never sort)
        cb = self.conv_backend
        if not isinstance(cb, (str, tuple)):
            items = cb.items() if hasattr(cb, 'items') else cb
            object.__setattr__(
                self, 'conv_backend',
                tuple((str(p), str(b)) for p, b in items))
        fp = self.fuse_pairwise
        if not isinstance(fp, (bool, tuple)):
            items = fp.items() if hasattr(fp, 'items') else fp
            object.__setattr__(
                self, 'fuse_pairwise',
                tuple((str(p), str(v)) for p, v in items))
        super().__post_init__()

    # ------------------------------------------------------------------ #
    # static configuration helpers (resolved at trace time)
    # ------------------------------------------------------------------ #
    def _resolved(self):
        assert self.num_degrees is not None or self.hidden_fiber_dict is not None, \
            'either num_degrees or hidden_fiber_dict must be specified'
        num_degrees = self.num_degrees if self.num_degrees is not None \
            else (max(d for d, _ in self.hidden_fiber_dict) + 1)

        dim_in = self.dim_in if self.dim_in is not None else self.dim
        fiber_in = Fiber.create(self.input_degrees,
                                cast_tuple(dim_in, self.input_degrees))

        if self.hidden_fiber_dict is not None:
            fiber_hidden = Fiber(self.hidden_fiber_dict)
        else:
            fiber_hidden = Fiber.create(num_degrees, self.dim)

        output_degrees = self.output_degrees if not self.use_egnn else None
        dim_out = self.dim_out if self.dim_out is not None else self.dim
        if self.out_fiber_dict is not None:
            fiber_out = Fiber(self.out_fiber_dict)
            output_degrees = max(d for d, _ in self.out_fiber_dict) + 1
        elif output_degrees is not None:
            fiber_out = Fiber.create(output_degrees, dim_out)
        else:
            fiber_out = None
        return num_degrees, fiber_in, fiber_hidden, fiber_out, output_degrees

    @nn.compact
    def __call__(self, feats, coors, mask=None, adj_mat=None, edges=None,
                 return_type=None, return_pooled=False, neighbor_mask=None,
                 global_feats=None, neighbors=None):
        if self.matmul_precision is not None:
            with jax.default_matmul_precision(self.matmul_precision):
                return self._forward(
                    feats, coors, mask, adj_mat, edges, return_type,
                    return_pooled, neighbor_mask, global_feats, neighbors)
        return self._forward(feats, coors, mask, adj_mat, edges, return_type,
                             return_pooled, neighbor_mask, global_feats,
                             neighbors)

    def _forward(self, feats, coors, mask, adj_mat, edges, return_type,
                 return_pooled, neighbor_mask, global_feats, neighbors=None):
        precomputed_neighbors = neighbors
        del neighbors
        num_degrees, fiber_in, fiber_hidden, fiber_out, output_degrees = \
            self._resolved()

        assert not (self.accept_global_feats ^ (global_feats is not None)), \
            'global features must be passed iff global_feats_dim is set'
        assert not (self.causal and not self.attend_self), \
            'attend_self must be on in causal (autoregressive) mode'
        assert not (self.attend_sparse_neighbors and adj_mat is None), \
            'adjacency matrix must be passed in when attending to sparse neighbors'
        assert not (self.has_edges and edges is None), \
            'edge tokens/features must be supplied when edge_dim is set'
        if any(self._attention_fused()):
            assert self.sequence_parallel is None, \
                'fuse_pairwise streams its own gathers and does not ' \
                'compose with the sequence-parallel ring exchange yet'
            assert not (self.rotary_position or self.rotary_rel_dist), \
                'fuse_pairwise does not support rotary embeddings'
            assert not self.linear_proj_keys, \
                'fuse_pairwise needs conv keys (linear_proj_keys is ' \
                'the gathered node-projection variant)'

        if output_degrees == 1:
            return_type = 0

        # ------------------------------------------------------------- #
        # embeddings (reference :1143-1158)
        # ------------------------------------------------------------- #
        if self.num_tokens is not None:
            feats = nn.Embed(self.num_tokens, self._scalar_dim(),
                             name='token_emb')(feats)
        if self.num_positions is not None:
            n_ = feats.shape[1]
            assert n_ <= self.num_positions, \
                'sequence length exceeds num_positions'
            pos = nn.Embed(self.num_positions, self._scalar_dim(),
                           name='pos_emb')(jnp.arange(n_))
            feats = feats + pos[None]

        if not isinstance(feats, dict):
            feats = {'0': feats[..., None]}
        feats = _permute_degree1(feats, _CART_TO_IRREP)
        if global_feats is not None and not isinstance(global_feats, dict):
            global_feats = {'0': global_feats[..., None]}

        b, n = feats['0'].shape[0], feats['0'].shape[1]
        assert feats['0'].shape[2] == fiber_in[0], \
            f"feature dim {feats['0'].shape[2]} != configured {fiber_in[0]}"
        assert set(map(int, feats.keys())) == set(range(self.input_degrees)), \
            f'input must have degrees 0..{self.input_degrees - 1}'

        # ------------------------------------------------------------- #
        # kNN-free global attention (attention_mode='global'): branch
        # before any neighbor budget / O(n^2) index construction — none
        # of it exists on this path (see the field comment)
        # ------------------------------------------------------------- #
        if self.attention_mode == 'global':
            return self._global_forward(
                feats, coors, mask, global_feats, return_type,
                return_pooled, fiber_in, fiber_hidden, fiber_out, b, n)
        assert self.attention_mode == 'knn', \
            f'unknown attention_mode {self.attention_mode!r} ' \
            f"(want 'knn' or 'global')"

        # static neighbor budget (reference :1277-1281, made static)
        num_neighbors = self.num_neighbors
        assert self.attend_sparse_neighbors or num_neighbors > 0 \
            or precomputed_neighbors is not None, \
            'either attend to sparse neighbors or use num_neighbors > 0'
        num_neighbors = int(min(num_neighbors, n - 1))

        # sequence-parallel ring kNN: neighbor selection runs under
        # shard_map over the sp mesh axis (peak memory O(n_local^2), ICI
        # ppermute ring) — all in one traced program, no host round-trip.
        # Carries the FULL dense-path ranking semantics (VERDICT r4 next
        # #3): sparse-adjacency bonded priority, N-hop expansion + ring
        # embeddings, causal future-masking, user neighbor_mask, edges —
        # the per-pair predicates ride as query-row-sharded [b, nl, N]
        # tensors into the ring merge (parallel/ring.py).
        if precomputed_neighbors is None and self.sequence_parallel is not None:
            assert self.sequence_parallel == 'ring', \
                f"unknown sequence_parallel mode {self.sequence_parallel!r}"
            assert self.mesh is not None, \
                'sequence_parallel requires a mesh (jax.sharding.Mesh)'
            import contextlib

            from ..parallel.exchange import (
                bonded_priority_mask, exchange_scope, neighbor_gather,
                rowwise_gather,
            )
            from ..parallel.ring import ring_knn

            # row-local bonded-mask construction (exchange.py): the
            # dense scatter+top-k build would cost a full-width
            # [b, n, n] all-gather under GSPMD
            sp_size = self.mesh.shape.get('sp', 1)
            bonded_fn = partial(bonded_priority_mask, mesh=self.mesh) \
                if self.ring_exchange and n % sp_size == 0 else None
            adj_mat, adj_ind_full, sp_full, num_sparse = \
                self._adjacency_predicates(adj_mat, b, n,
                                           bonded_fn=bonded_fn)
            total_neighbors = int(min(num_neighbors + num_sparse, n - 1))
            assert total_neighbors > 0, 'must fetch at least 1 neighbor'

            rank, idx = ring_knn(
                coors, total_neighbors, self.mesh, mask=mask,
                neighbor_mask=neighbor_mask, sparse_mask=sp_full,
                causal=self.causal, overlap=self.ring_overlap)
            # the dense validity rule on the MODIFIED ranking: bonded
            # slots (rank 0) stay valid beyond the radius, masked/future
            # slots (rank FINF) never validate (neighbors.py:150)
            valid_radius = self.valid_radius if num_neighbors > 0 else 0.
            valid = rank <= valid_radius
            # neighbor-sparse exchange (parallel/exchange.py): the ids
            # are GLOBAL, so a plain gather over the node-sharded
            # operands would make GSPMD all-gather the full [b, N, ...]
            # tensor onto every device — the exchange rotates owned
            # blocks instead (O(n_local) resident, overlap-capable).
            # ring_exchange=False keeps the dense gathers (bit-exact A/B
            # control arm).
            if self.ring_exchange:
                gather_nodes = partial(neighbor_gather, mesh=self.mesh,
                                       overlap=self.ring_overlap)
                gather_cols = partial(rowwise_gather, mesh=self.mesh)
            else:
                gather_nodes = partial(batched_index_select, axis=1)
                gather_cols = partial(batched_index_select, axis=2)
            coors_j = gather_nodes(coors, idx)
            nbr_rel_pos = coors[:, :, None, :] - coors_j
            nbr_rel_dist = safe_norm(nbr_rel_pos, axis=-1)
            if mask is not None:
                valid = valid & gather_nodes(mask, idx)
                valid = valid & mask[:, :, None]
            hood = Neighborhood(idx, valid, nbr_rel_pos, nbr_rel_dist)

            # edges gather by the GLOBAL neighbor ids (the dense path's
            # remove_self + nearest-gather composed; reference
            # :1231-1239). The [b, n, N, ...] operands are row-sharded
            # with full columns, so the column selection is zero-comm —
            # rowwise_gather pins it local under shard_map. Token edges
            # gather FIRST and embed the [b, n, k] selection — embedding
            # the full [b, n, n] layout would materialize the
            # O(n^2 * edge_dim) tensor this path exists to avoid (Embed
            # is pointwise, so the values match)
            if edges is not None:
                if self.num_edge_tokens is not None:
                    edges = gather_cols(edges, idx)
                    edges = nn.Embed(self.num_edge_tokens, self.edge_dim,
                                     name='edge_emb')(edges)
                else:
                    edges = gather_cols(edges, idx)
            if self.num_adj_degrees is not None and self.adj_dim > 0:
                adj_sel = gather_cols(adj_ind_full, idx)
                adj_emb = nn.Embed(self.num_adj_degrees + 1, self.adj_dim,
                                   name='adj_emb')(adj_sel)
                edges = jnp.concatenate((edges, adj_emb), axis=-1) \
                    if edges is not None else adj_emb

            # the trunk's per-layer neighbor feature gathers (ConvSE3 /
            # attention / EGNN select values at hood.indices) route
            # through the same sparse exchange while the scope is active
            scope = exchange_scope(self.mesh, overlap=self.ring_overlap) \
                if self.ring_exchange else contextlib.nullcontext()
            with scope:
                return self._body(feats, hood, edges, mask, global_feats,
                                  return_type, return_pooled, num_degrees,
                                  fiber_in, fiber_hidden, fiber_out, b, n)

        # precomputed neighborhoods (host C++ kNN via native.knn_graph, or
        # ring kNN via parallel.ring) replace the O(n^2) on-device
        # selection entirely — handled before any O(n^2) index tensors are
        # even constructed
        if precomputed_neighbors is not None:
            assert not (self.attend_sparse_neighbors or self.causal
                        or neighbor_mask is not None
                        or self.num_adj_degrees is not None
                        or edges is not None), \
                'precomputed neighbors support plain kNN semantics only'
            nbr_idx, nbr_mask = precomputed_neighbors
            # clamp external indices: jnp gathers fill out-of-bounds with
            # NaN, which would silently poison outputs
            nbr_idx = jnp.clip(jnp.asarray(nbr_idx), 0, n - 1)
            coors_j = batched_index_select(coors, nbr_idx, axis=1)
            nbr_rel_pos = coors[:, :, None, :] - coors_j
            nbr_rel_dist = safe_norm(nbr_rel_pos, axis=-1)
            valid = nbr_rel_dist <= self.valid_radius
            # guard against self-inclusive conventions (e.g. sklearn
            # kneighbors returns the query itself as neighbor 0) and
            # sentinel-padded indices that clamping mapped onto real nodes
            valid = valid & (nbr_idx != jnp.arange(n)[None, :, None])
            if nbr_mask is not None:
                valid = valid & jnp.asarray(nbr_mask)
            if mask is not None:
                valid = valid & batched_index_select(mask, nbr_idx, axis=1)
                valid = valid & mask[:, :, None]
            hood = Neighborhood(nbr_idx, valid, nbr_rel_pos, nbr_rel_dist)
            return self._body(feats, hood, edges, mask, global_feats,
                              return_type, return_pooled, num_degrees,
                              fiber_in, fiber_hidden, fiber_out, b, n)

        self_excl = exclude_self_indices(n)
        adj_mat, adj_ind_full, sp_full, num_sparse = \
            self._adjacency_predicates(adj_mat, b, n)
        adj_indices = remove_self(adj_ind_full, self_excl) \
            if adj_ind_full is not None else None
        # the self-excluded view of the SAME full-layout bonded mask the
        # ring branch consumes (one source of truth for the jittered
        # selection — see _adjacency_predicates)
        sparse_mask = remove_self(sp_full, self_excl) \
            if sp_full is not None else None

        # pairwise geometry, self-excluded by construction (reference
        # :1221-1229); the O(n^2) tensors the selection below consumes
        with named_scope('neighbors'):
            rel_pos_full = coors[:, :, None, :] - coors[:, None, :, :]
            rel_pos = remove_self(rel_pos_full, self_excl)
            indices = jnp.broadcast_to(self_excl[None], (b, n, n - 1))

            pair_mask = None
            if mask is not None:
                pm = mask[:, :, None] & mask[:, None, :]
                pair_mask = remove_self(pm, self_excl)

        # edges (reference :1231-1239)
        if edges is not None:
            if self.num_edge_tokens is not None:
                edges = nn.Embed(self.num_edge_tokens, self.edge_dim,
                                 name='edge_emb')(edges)
            edges = remove_self(edges, self_excl)
        if self.num_adj_degrees is not None and self.adj_dim > 0:
            adj_emb = nn.Embed(self.num_adj_degrees + 1, self.adj_dim,
                               name='adj_emb')(adj_indices)
            edges = jnp.concatenate((edges, adj_emb), axis=-1) \
                if edges is not None else adj_emb

        if neighbor_mask is not None:
            neighbor_mask = remove_self(neighbor_mask, self_excl)

        # fixed-K neighbor selection (reference :1241-1294)
        valid_radius = self.valid_radius if num_neighbors > 0 else 0.
        total_neighbors = int(min(num_neighbors + num_sparse, n - 1))
        assert total_neighbors > 0, 'must fetch at least 1 neighbor'

        with named_scope('neighbors'):
            hood, nearest = select_neighbors(
                rel_pos, indices, total_neighbors, valid_radius,
                pair_mask=pair_mask, neighbor_mask=neighbor_mask,
                sparse_mask=sparse_mask, causal=self.causal)

        if edges is not None:
            edges = batched_index_select(edges, nearest, axis=2)

        return self._body(feats, hood, edges, mask, global_feats,
                          return_type, return_pooled, num_degrees,
                          fiber_in, fiber_hidden, fiber_out, b, n)

    def _adjacency_predicates(self, adj_mat, b, n, bonded_fn=None):
        """Full-[b, n, n]-layout adjacency products shared by the dense
        and ring branches: (expanded adj_mat, N-hop ring labels, bonded
        sparse-priority mask, num_sparse). Reference :1177-1217.

        The tie-break jitter is drawn in the dense path's self-excluded
        [b, n, n-1] layout and SCATTERED to full width, so both branches
        see identical noise from the same rng stream — the bonded subset
        a jittered top-k picks when a row has more bonds than the cap is
        then bit-identical between ring and dense. Fresh per call when
        the caller threads an rng (apply(..., rngs={'neighbor_noise':
        key}), matching the reference's per-forward draw :1211);
        deterministic seed-0 otherwise so plain inference stays
        reproducible.

        bonded_fn(adj_mat, noise_n1, num_sparse) -> sp_full, when given,
        replaces the dense scatter+top-k construction — the ring branch
        passes parallel.exchange.bonded_priority_mask so the build stays
        row-local (GSPMD's scatter partitioner otherwise re-materializes
        the full [b, n, n] operand per device; same rng draw, exact
        parity)."""
        # 'adjacency' scope (observability.timing.MODEL_SCOPES): the
        # jittered scatter + top-k below lowers to whiles that dominate
        # toy CPU traces — without the label, profile attribution
        # (`make profile-smoke`) loses half its device time
        with named_scope('adjacency'):
            if adj_mat is not None and adj_mat.ndim == 2:
                adj_mat = jnp.broadcast_to(adj_mat[None], (b, n, n))
            adj_ind_full = None
            if self.num_adj_degrees is not None:
                assert self.num_adj_degrees >= 1, \
                    'num_adj_degrees must be at least 1'
                adj_mat, adj_ind_full = expand_adjacency(
                    adj_mat, self.num_adj_degrees)
            num_sparse = 0
            sp_full = None
            if self.attend_sparse_neighbors:
                num_sparse = int(min(self.max_sparse_neighbors, n - 1))
                noise_key = self.make_rng('neighbor_noise') \
                    if self.has_rng('neighbor_noise') \
                    else jax.random.PRNGKey(0)
                noise_n1 = jax.random.uniform(
                    noise_key, (b, n, n - 1), minval=-0.01, maxval=0.01)
                if bonded_fn is not None:
                    sp_full = bonded_fn(adj_mat, noise_n1, num_sparse)
                else:
                    self_excl = exclude_self_indices(n)
                    noise_full = jnp.zeros((b, n, n), noise_n1.dtype).at[
                        :, jnp.arange(n)[:, None], self_excl].set(noise_n1)
                    adj_noself = adj_mat.astype(bool) \
                        & ~jnp.eye(n, dtype=bool)[None]
                    # the diagonal carries value 0 (+0 noise) and the
                    # >0.5 bonded threshold filters it, so the
                    # full-layout selection equals remove_self of the
                    # dense one exactly
                    sp_full = sparse_neighbor_mask(adj_noself, num_sparse,
                                                   noise_full)
            return adj_mat, adj_ind_full, sp_full, num_sparse

    def _global_forward(self, feats, coors, mask, global_feats,
                        return_type, return_pooled, fiber_in, fiber_hidden,
                        fiber_out, b, n):
        """attention_mode='global' (see the field comment): LinearSE3
        lift in -> global-attention trunk -> LinearSE3 out, with
        coordinates riding the basis dict's reserved keys. Shares the
        output conventions tail with _body verbatim."""
        import contextlib

        assert not (self.attend_sparse_neighbors or self.causal
                    or self.num_adj_degrees is not None or self.has_edges
                    or self.use_egnn), \
            "attention_mode='global' is plain all-pairs attention: " \
            'sparse/causal/adjacency/edge/egnn semantics presume a ' \
            'neighbor list'
        assert not (self.rotary_position or self.rotary_rel_dist), \
            'global attention does not support rotary embeddings'
        assert not self.linear_proj_keys, \
            'global attention needs conv keys (linear_proj_keys is the ' \
            'gathered node-projection variant)'
        assert not self.fourier_encode_dist, \
            'global attention consumes raw distances only (rebuilt from ' \
            'coordinates per tile)'
        assert self.num_conv_layers == 0, \
            'global mode has no per-edge convs (preconvs are ConvSE3)'
        assert not any(self._attention_fused()), \
            "fuse_pairwise is subsumed by attention_mode='global' (this " \
            'path always streams); leave it False'
        assert self.remat_policy is None, \
            "remat_policy='save_conv_outputs' tags ConvSE3 outputs, " \
            'which the global trunk never materializes — it would ' \
            'silently no-op'
        assert not (self.reversible and self.accept_global_feats), \
            'reversibility and global features are not compatible'
        if fiber_out is not None:
            hidden_degrees = {d for d, _ in fiber_hidden}
            assert all(d in hidden_degrees for d, _ in fiber_out), \
                'global mode projects out with a LinearSE3 (no per-edge ' \
                'conv_out), so every output degree must exist in the ' \
                'hidden fiber'

        backends = self._layer_backends(None)
        value_backends = tuple(backends.get(f'attn_block{i}/to_v', 'dense')
                               for i in range(self.depth))
        key_backends = tuple(backends.get(f'attn_block{i}/to_k', 'dense')
                             for i in range(self.depth))

        # coordinates (+ node mask) ride the basis dict's reserved keys —
        # the only "basis" the global kernel consumes. differentiable_coors
        # gates coordinate gradients exactly like get_basis does.
        basis = {'global_coords': coors if self.differentiable_coors
                 else jax.lax.stop_gradient(coors)}
        if mask is not None:
            basis['global_mask'] = mask

        # sequence-parallel composition: an ACTIVE exchange scope is the
        # trace-time signal that routes every attention block to the
        # ring-sharded global kernel (parallel/exchange.py — the scope
        # the kNN flash gather used to bypass)
        scope = contextlib.nullcontext()
        if self.sequence_parallel is not None:
            assert self.sequence_parallel == 'ring', \
                f'unknown sequence_parallel mode {self.sequence_parallel!r}'
            assert self.mesh is not None, \
                'sequence_parallel requires a mesh (jax.sharding.Mesh)'
            from ..parallel.exchange import exchange_scope
            scope = exchange_scope(self.mesh, overlap=self.ring_overlap)

        # lift in: LinearSE3 emits only degrees present in BOTH fibers —
        # zero-fill the hidden degrees the input lacks (there is no
        # per-edge conv_in to synthesize them; the first attention block
        # populates them through the pairwise SH payload)
        with named_scope('conv_in'):
            x = dict(LinearSE3(fiber_in, fiber_hidden,
                               name='lift_in')(feats))
            dtype = feats['0'].dtype
            for degree, c in fiber_hidden:
                if str(degree) not in x:
                    x[str(degree)] = jnp.zeros(
                        (b, n, c, to_order(degree)), dtype)

        with scope:
            with named_scope('trunk'):
                x = SequentialTrunk(
                    fiber_hidden, depth=self.depth, heads=self.heads,
                    dim_head=self.dim_head, attend_self=self.attend_self,
                    value_backends=value_backends,
                    key_backends=key_backends,
                    attention_mode='global',
                    global_materialize=self.global_materialize,
                    flash_interpret=self.flash_interpret,
                    use_null_kv=self.use_null_kv,
                    global_feats_dim=self.global_feats_dim,
                    tie_key_values=self.tie_key_values,
                    one_headed_key_values=self.one_headed_key_values,
                    norm_gated_scale=self.norm_gated_scale,
                    reversible=self.reversible,
                    pallas=self.pallas,
                    radial_bf16=self.radial_bf16,
                    name='trunk')(x, (None, None, None), None, basis,
                                  global_feats, None, mask)

        if fiber_out is not None:
            with named_scope('conv_out'):
                x = LinearSE3(fiber_hidden, fiber_out, name='lift_out')(x)

        if (self.norm_out or self.reversible) and fiber_out is not None:
            x = NormSE3(fiber_out, gated_scale=self.norm_gated_scale,
                        nonlin=lambda t: t, name='norm_out')(x)

        final_fiber = fiber_out if fiber_out is not None else fiber_hidden
        if self.reduce_dim_out:
            x = LinearSE3(final_fiber, final_fiber.to(1),
                          name='linear_out')(x)
            x = {k: v[..., 0, :] for k, v in x.items()}

        x = _permute_degree1(x, _IRREP_TO_CART)

        if return_pooled:
            pool = (lambda t: masked_mean(t, mask, axis=1)) \
                if mask is not None else (lambda t: t.mean(axis=1))
            x = {k: pool(v) for k, v in x.items()}
        if '0' in x:
            x = {**x, '0': x['0'][..., 0]}
        if return_type is not None:
            return x[str(return_type)]
        return x

    def _attention_fused(self):
        """Per-block streaming-attention resolution from the
        fuse_pairwise spec (bool, or first-match-wins (pattern,
        'flash'|'xla') pairs on 'attn_block{i}' — the conv_backend
        idiom). EGNN trunks have no SE3 attention blocks."""
        if self.use_egnn:
            return tuple()
        spec = self.fuse_pairwise
        out = []
        for i in range(self.depth):
            name = f'attn_block{i}'
            if isinstance(spec, bool):
                out.append(spec)
                continue
            val = 'xla'
            for pat, v in spec:
                if re.search(pat, name):
                    val = v
                    break
            assert val in ('flash', 'xla'), \
                f'fuse_pairwise rule value {val!r} (want flash|xla)'
            out.append(val == 'flash')
        return tuple(out)

    def _layer_backends(self, fiber_out):
        """Resolve the conv_backend spec per conv layer (first-match-wins
        on the layer name — ops.conv.resolve_conv_backend). The dict
        drives which per-edge payloads _body builds: dense basis tensors
        only when a layer consumes them, so2 edge frames likewise."""
        names = ['conv_in']
        names += [f'preconv{i}' for i in range(self.num_conv_layers)]
        if not self.use_egnn:
            for i in range(self.depth):
                names.append(f'attn_block{i}/to_v')
                if not (self.linear_proj_keys or self.tie_key_values):
                    names.append(f'attn_block{i}/to_k')
        if fiber_out is not None:
            names.append('conv_out')
        return {n: resolve_conv_backend(self.conv_backend, n)
                for n in names}

    def _body(self, feats, hood, edges, mask, global_feats, return_type,
              return_pooled, num_degrees, fiber_in, fiber_hidden, fiber_out,
              b, n):
        # rotary embeddings (reference :1298-1325)
        pos_emb = self._rotary_embeddings(b, n, hood)

        backends = self._layer_backends(fiber_out)
        fused_blocks = self._attention_fused()
        # a FUSED attention block's kv convs consume the flash payloads
        # (SH stack / so2 frames) instead of materialized basis tensors
        fused_conv_names = set()
        for i, fused in enumerate(fused_blocks):
            if fused:
                fused_conv_names.add(f'attn_block{i}/to_v')
                fused_conv_names.add(f'attn_block{i}/to_k')
        need_dense = any(b == 'dense' for name, b in backends.items()
                         if name not in fused_conv_names)
        need_flash_sh = any(backends[name] == 'dense'
                            for name in fused_conv_names
                            if name in backends)
        extra_backends = sorted(set(backends.values()) - {'dense'})

        # basis, in-trace (reference :1329). The basis-fused kernels
        # take the flat (p,f,q) layout: one padded minor axis (~1.1x)
        # instead of the structured form's (Q,F)->(8,128) tile pad (up
        # to ~60x HBM inflation at num_degrees=4); ops.conv.contract_pair
        # relays a basis that reaches it in the other layout.
        # Non-dense backends get their payload under their reserved key
        # instead — an all-so2 model skips the CG basis entirely (at
        # degree 6 that is 49 per-edge [P, Q, F] tensors never built).
        layout = basis_layout(self.fuse_basis, self.pallas,
                              self.pallas_interpret)
        basis = {}
        with named_scope('basis'):
            if need_dense:
                basis = get_basis(hood.rel_pos, num_degrees - 1,
                                  differentiable=self.differentiable_coors,
                                  layout=layout)
            if need_flash_sh:
                # dense-arm flash blocks: the raw SH stack (O(S) floats
                # per edge) replaces the per-pair basis tensors — an
                # all-flash dense model never materializes a basis
                from ..kernels.pallas_flash import flash_sh_payload
                basis['flash_sh'] = flash_sh_payload(
                    hood.rel_pos, num_degrees - 1,
                    differentiable=self.differentiable_coors)
            if 'so2' in extra_backends:
                from ..so2.frames import edge_frames
                basis['so2'] = edge_frames(
                    hood.rel_pos, num_degrees - 1,
                    differentiable=self.differentiable_coors)

        edge_info = (hood.indices, hood.mask, edges)
        x = feats

        conv_kwargs = dict(
            edge_dim=(edges.shape[-1] if edges is not None else 0),
            fourier_encode_dist=self.fourier_encode_dist,
            num_fourier_features=self.rel_dist_num_fourier_features,
            pallas=self.pallas,
            shared_radial_hidden=self.shared_radial_hidden,
            edge_chunks=self.edge_chunks,
            fuse_basis=self.fuse_basis,
            radial_bf16=self.radial_bf16,
            pallas_interpret=self.pallas_interpret)

        # project in + pre-convs (reference :1338-1344)
        with named_scope('conv_in'):
            x = ConvSE3(fiber_in, fiber_hidden, name='conv_in',
                        backend=backends['conv_in'],
                        **conv_kwargs)(x, edge_info, hood.rel_dist, basis)
        for i in range(self.num_conv_layers):
            x = NormSE3(fiber_hidden, gated_scale=self.norm_gated_scale,
                        name=f'preconv_norm{i}')(x)
            x = ConvSE3(fiber_hidden, fiber_hidden, name=f'preconv{i}',
                        backend=backends[f'preconv{i}'],
                        **conv_kwargs)(x, edge_info, hood.rel_dist, basis)

        # trunk (reference :1096-1109, :1348)
        with named_scope('trunk'):
            x = self._trunk(x, fiber_hidden, edge_info, hood.rel_dist,
                            basis, global_feats, pos_emb, mask, conv_kwargs,
                            backends)

        # project out (reference :1352-1363)
        if fiber_out is not None:
            with named_scope('conv_out'):
                x = ConvSE3(fiber_hidden, fiber_out, name='conv_out',
                            backend=backends['conv_out'],
                            **conv_kwargs)(x, edge_info, hood.rel_dist,
                                           basis)

        if (self.norm_out or self.reversible) and fiber_out is not None:
            x = NormSE3(fiber_out, gated_scale=self.norm_gated_scale,
                        nonlin=lambda t: t, name='norm_out')(x)

        final_fiber = fiber_out if fiber_out is not None else fiber_hidden
        with named_scope('readout'):
            if self.reduce_dim_out:
                x = LinearSE3(final_fiber, final_fiber.to(1),
                              name='linear_out')(x)
                x = {k: v[..., 0, :] for k, v in x.items()}

            x = _permute_degree1(x, _IRREP_TO_CART)

            # output conventions (reference :1365-1375)
            if return_pooled:
                pool = (lambda t: masked_mean(t, mask, axis=1)) \
                    if mask is not None else (lambda t: t.mean(axis=1))
                x = {k: pool(v) for k, v in x.items()}
        if '0' in x:
            x = {**x, '0': x['0'][..., 0]}
        if return_type is not None:
            return x[str(return_type)]
        return x

    # ------------------------------------------------------------------ #
    @property
    def accept_global_feats(self) -> bool:
        return self.global_feats_dim is not None

    @property
    def has_edges(self) -> bool:
        return self.edge_dim is not None and self.edge_dim > 0

    def _scalar_dim(self) -> int:
        dim_in = self.dim_in if self.dim_in is not None else self.dim
        return cast_tuple(dim_in, self.input_degrees)[0]

    def _rotary_embeddings(self, b, n, hood):
        if not (self.rotary_position or self.rotary_rel_dist):
            return None
        num_rotaries = int(self.rotary_position) + int(self.rotary_rel_dist)
        rot_dim = self.dim_head // num_rotaries

        key_pos_emb = None
        query_pos_emb = None

        if self.rotary_position:
            seq_emb = sinusoidal_embeddings(jnp.arange(n), rot_dim)  # [n, r]
            idx_with_self = jnp.concatenate(
                (jnp.broadcast_to(jnp.arange(n)[None, :, None],
                                  (b, n, 1)).astype(hood.indices.dtype),
                 hood.indices), axis=2)
            key_pos_emb = seq_emb[idx_with_self]           # [b, n, 1+k, r]
            query_pos_emb = jnp.broadcast_to(seq_emb[None], (b, n, rot_dim))

        if self.rotary_rel_dist:
            dist_with_self = jnp.pad(
                hood.rel_dist, ((0, 0), (0, 0), (1, 0))) * 1e2
            rel_emb = sinusoidal_embeddings(dist_with_self, rot_dim)
            key_pos_emb = safe_cat(key_pos_emb, rel_emb, axis=-1)
            q_emb = sinusoidal_embeddings(jnp.zeros((n,)), rot_dim)
            query_pos_emb = safe_cat(
                query_pos_emb, jnp.broadcast_to(q_emb[None], (b, n, rot_dim)),
                axis=-1)

        return (query_pos_emb, key_pos_emb)

    def _trunk(self, x, fiber_hidden, edge_info, rel_dist, basis,
               global_feats, pos_emb, mask, conv_kwargs, backends=None):
        backends = backends or {}
        if self.use_egnn:
            # the EGNN trunk has no ConvSE3 tags — a policy here would be
            # a silent no-op claimed by the config
            assert self.remat_policy is None, \
                'remat_policy applies to the conv-attention trunk only'
            return EGnnNetwork(
                fiber=fiber_hidden, depth=self.depth,
                edge_dim=conv_kwargs['edge_dim'],
                hidden_dim=self.egnn_hidden_dim,
                coor_weights_clamp_value=self.egnn_weights_clamp_value,
                feedforward=self.egnn_feedforward,
                reversible=self.reversible, name='egnn_net')(
                    x, edge_info, rel_dist, basis=basis,
                    global_feats=global_feats, pos_emb=pos_emb, mask=mask)

        assert not (self.reversible and self.accept_global_feats), \
            'reversibility and global features are not compatible'

        value_backends = tuple(
            backends.get(f'attn_block{i}/to_v', 'dense')
            for i in range(self.depth))
        key_backends = tuple(
            backends.get(f'attn_block{i}/to_k', 'dense')
            for i in range(self.depth))
        return SequentialTrunk(
            fiber_hidden, depth=self.depth, heads=self.heads,
            dim_head=self.dim_head, attend_self=self.attend_self,
            value_backends=value_backends, key_backends=key_backends,
            fused_attention=self._attention_fused(),
            flash_interpret=self.flash_interpret,
            edge_dim=conv_kwargs['edge_dim'],
            use_null_kv=self.use_null_kv,
            fourier_encode_dist=self.fourier_encode_dist,
            rel_dist_num_fourier_features=self.rel_dist_num_fourier_features,
            global_feats_dim=self.global_feats_dim,
            linear_proj_keys=self.linear_proj_keys,
            tie_key_values=self.tie_key_values,
            one_headed_key_values=self.one_headed_key_values,
            norm_gated_scale=self.norm_gated_scale,
            reversible=self.reversible, remat_policy=self.remat_policy,
            pallas=self.pallas,
            pallas_attention=self.pallas_attention,
            pallas_attention_interpret=self.pallas_attention_interpret,
            shared_radial_hidden=self.shared_radial_hidden,
            edge_chunks=self.edge_chunks, fuse_basis=self.fuse_basis,
            radial_bf16=self.radial_bf16,
            pallas_interpret=self.pallas_interpret, name='trunk')(
                x, edge_info, rel_dist, basis, global_feats, pos_emb, mask)


class SE3Transformer:
    """Eager convenience wrapper mirroring the reference's call style:

        model = SE3Transformer(dim=64, depth=2, num_degrees=2)
        out = model(feats, coors, mask, return_type=0)

    Parameters are initialized lazily on first call (seeded). For
    production TPU use, jit `model.module.apply` (or use
    se3_transformer_tpu.training) — this wrapper is for parity tests and
    interactive exploration.
    """

    model_family = 'se3_v1'

    def __init__(self, *, seed: int = 0, **kwargs):
        self.module = SE3TransformerModule(**kwargs)
        self.seed = seed
        self.params = None
        self._apply = jax.jit(
            self.module.apply,
            static_argnames=('return_type', 'return_pooled'))

    def init(self, rng, *args, **kwargs):
        self.params = self.module.init(rng, *args, **kwargs)['params']
        return self.params

    def __call__(self, feats, coors, mask=None, adj_mat=None, edges=None,
                 return_type=None, return_pooled=False, neighbor_mask=None,
                 global_feats=None, neighbors=None):
        kwargs = dict(mask=mask, adj_mat=adj_mat, edges=edges,
                      return_type=return_type, return_pooled=return_pooled,
                      neighbor_mask=neighbor_mask, global_feats=global_feats,
                      neighbors=neighbors)
        if self.params is None:
            init_fn = jax.jit(
                self.module.init,
                static_argnames=('return_type', 'return_pooled'))
            self.params = init_fn(jax.random.PRNGKey(self.seed), feats,
                                  coors, **kwargs)['params']
        return self._apply({'params': self.params}, feats, coors, **kwargs)
