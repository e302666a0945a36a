"""SE3TransformerV2: the eSCN-direct model family.

A sibling of models/se3_transformer.py — deliberately NOT checkpoint
compatible with v1 (the radial parameterization is per-m banded blocks,
see v2/conv.py; CheckpointManager's family guard makes cross-loading
fail loud instead of with a flax key error). The USER contract is
identical to v1's:

    module.apply({'params': p}, feats, coors, mask=mask,
                 adj_mat=adj, return_type=1)

with the same feats normalization (tokens -> Embed, arrays -> {'0'}),
the same cartesian<->irrep degree-1 permutation, the same
``output_degrees == 1 -> return_type = 0`` and '0'-squeeze output
conventions and the same return_pooled masked mean — so the
InferenceEngine AOT buckets, the trainer and the serving stack all
plug in unchanged. ``adj_mat`` is accepted and unused, matching the
v1 default path's semantics (it only matters under v1's
attend_sparse_neighbors machinery, which v2 does not grow).

Architecture: conv_in -> depth x (SeparableS2Activation -> V2ConvSE3
+ residual) -> SeparableS2Activation -> conv_out, all on the per-m
radial path with the edge-frames payload as the only geometry — no
basis tensors anywhere.
"""
from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability import named_scope
from ..ops.core import LinearSE3, residual_se3
from ..ops.fiber import Fiber
from ..ops.neighbors import exclude_self_indices, remove_self, \
    select_neighbors
from ..utils.helpers import masked_mean
from .conv import DEFAULT_V2_MID_DIM, V2ConvSE3
from .s2act import SeparableS2Activation

# cartesian <-> irrep component permutations for degree-1 features —
# same convention as v1 (models/se3_transformer.py)
_CART_TO_IRREP = (1, 2, 0)
_IRREP_TO_CART = (2, 0, 1)


def _permute_degree1(features, perm):
    if '1' not in features:
        return features
    return {**features,
            '1': features['1'][..., jnp.asarray(perm)]}


class SE3TransformerV2Module(nn.Module):
    """flax module for the v2 family (see module docstring; the eager
    wrapper below mirrors v1's SE3Transformer call style)."""
    dim: int
    depth: int = 2
    num_degrees: int = 4
    output_degrees: int = 1
    input_degrees: int = 1
    dim_in: Optional[int] = None
    dim_out: Optional[int] = None
    num_tokens: Optional[int] = None
    num_neighbors: int = 12
    valid_radius: float = 1e5
    reduce_dim_out: bool = False
    edge_dim: int = 0
    # v2 knobs (v2/conv.py, v2/s2act.py)
    mid_dim: int = DEFAULT_V2_MID_DIM
    max_m: Optional[int] = None
    s2_grid_nonlin: bool = True
    s2_resolution: Optional[int] = None
    # spine passthroughs, same meaning as v1
    differentiable_coors: bool = False
    matmul_precision: Optional[str] = 'highest'
    pallas: Optional[bool] = None
    pallas_interpret: bool = False
    edge_chunks: Optional[int] = None
    radial_bf16: bool = False

    # the checkpoint/capability family stamp (training/checkpoint.py
    # guards restores on it; serving surfaces it)
    model_family = 'se3_v2'

    @nn.compact
    def __call__(self, feats, coors, mask=None, adj_mat=None, edges=None,
                 return_type=None, return_pooled=False,
                 neighbor_mask=None):
        if self.matmul_precision is not None:
            with jax.default_matmul_precision(self.matmul_precision):
                return self._forward(feats, coors, mask, edges,
                                     return_type, return_pooled,
                                     neighbor_mask)
        return self._forward(feats, coors, mask, edges, return_type,
                             return_pooled, neighbor_mask)

    def _forward(self, feats, coors, mask, edges, return_type,
                 return_pooled, neighbor_mask):
        assert self.input_degrees == 1, \
            'v2 takes scalar (degree-0) inputs'
        dim_in = self.dim_in if self.dim_in is not None else self.dim
        dim_out = self.dim_out if self.dim_out is not None else self.dim
        fiber_in = Fiber.create(1, dim_in)
        fiber_hidden = Fiber.create(self.num_degrees, self.dim)
        fiber_out = Fiber.create(self.output_degrees, dim_out)

        if self.output_degrees == 1:
            return_type = 0

        if self.num_tokens is not None:
            feats = nn.Embed(self.num_tokens, dim_in,
                             name='token_emb')(feats)
        if not isinstance(feats, dict):
            feats = {'0': feats[..., None]}
        feats = _permute_degree1(feats, _CART_TO_IRREP)

        b, n = feats['0'].shape[0], feats['0'].shape[1]
        assert feats['0'].shape[2] == dim_in, \
            f"feature dim {feats['0'].shape[2]} != configured {dim_in}"

        num_neighbors = int(min(self.num_neighbors, n - 1))
        assert num_neighbors > 0, 'must fetch at least 1 neighbor'

        # fixed-K neighbor selection, self-excluded — the v1 dense path
        self_excl = exclude_self_indices(n)
        rel_pos_full = coors[:, :, None, :] - coors[:, None, :, :]
        rel_pos = remove_self(rel_pos_full, self_excl)
        indices = jnp.broadcast_to(self_excl[None], (b, n, n - 1))
        pair_mask = None
        if mask is not None:
            pm = mask[:, :, None] & mask[:, None, :]
            pair_mask = remove_self(pm, self_excl)
        if edges is not None:
            edges = remove_self(edges, self_excl)
        if neighbor_mask is not None:
            neighbor_mask = remove_self(neighbor_mask, self_excl)

        with named_scope('neighbors'):
            hood, nearest = select_neighbors(
                rel_pos, indices, num_neighbors, self.valid_radius,
                pair_mask=pair_mask, neighbor_mask=neighbor_mask)
        if edges is not None:
            from ..utils.helpers import batched_index_select
            edges = batched_index_select(edges, nearest, axis=2)

        # the ONLY geometry payload: edge frames (so2/frames.py) — v2
        # has no basis tensors at any degree
        with named_scope('frames'):
            from ..so2.frames import edge_frames
            frames = edge_frames(hood.rel_pos, self.num_degrees - 1,
                                 differentiable=self.differentiable_coors)

        edge_info = (hood.indices, hood.mask, edges)
        conv_kwargs = dict(
            mid_dim=self.mid_dim, max_m=self.max_m,
            edge_dim=(edges.shape[-1] if edges is not None else 0),
            pallas=self.pallas, pallas_interpret=self.pallas_interpret,
            edge_chunks=self.edge_chunks, radial_bf16=self.radial_bf16)

        with named_scope('conv_in'):
            x = V2ConvSE3(fiber_in, fiber_hidden, name='conv_in',
                          **conv_kwargs)(feats, edge_info,
                                         hood.rel_dist, frames)
        for i in range(self.depth):
            y = SeparableS2Activation(
                fiber_hidden, grid_nonlin=self.s2_grid_nonlin,
                resolution=self.s2_resolution, name=f'act{i}')(x)
            y = V2ConvSE3(fiber_hidden, fiber_hidden, name=f'block{i}',
                          **conv_kwargs)(y, edge_info, hood.rel_dist,
                                         frames)
            x = residual_se3(y, x)
        x = SeparableS2Activation(
            fiber_hidden, grid_nonlin=self.s2_grid_nonlin,
            resolution=self.s2_resolution, name='act_out')(x)
        with named_scope('conv_out'):
            x = V2ConvSE3(fiber_hidden, fiber_out, name='conv_out',
                          **conv_kwargs)(x, edge_info, hood.rel_dist,
                                         frames)

        if self.reduce_dim_out:
            x = LinearSE3(fiber_out, fiber_out.to(1),
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


class SE3TransformerV2:
    """Eager convenience wrapper mirroring v1's SE3Transformer:

        model = SE3TransformerV2(dim=8, depth=1, num_degrees=7)
        out = model(feats, coors, mask, return_type=1)

    Parameters initialize lazily on first call (seeded)."""

    model_family = 'se3_v2'

    def __init__(self, *, seed: int = 0, **kwargs):
        self.module = SE3TransformerV2Module(**kwargs)
        self.seed = seed
        self.params = None
        self._apply = jax.jit(
            self.module.apply,
            static_argnames=('return_type', 'return_pooled'))

    def init(self, rng, *args, **kwargs):
        self.params = self.module.init(rng, *args, **kwargs)['params']
        return self.params

    def __call__(self, feats, coors, mask=None, adj_mat=None, edges=None,
                 return_type=None, return_pooled=False,
                 neighbor_mask=None):
        kwargs = dict(mask=mask, edges=edges, return_type=return_type,
                      return_pooled=return_pooled,
                      neighbor_mask=neighbor_mask)
        if self.params is None:
            init_fn = jax.jit(
                self.module.init,
                static_argnames=('return_type', 'return_pooled'))
            self.params = init_fn(jax.random.PRNGKey(self.seed), feats,
                                  coors, **kwargs)['params']
        return self._apply({'params': self.params}, feats, coors,
                           **kwargs)
