"""Trunk execution strategies (the reference's L3 layer).

Reference reversible.py provides two ways to run the stack of
(attention, feedforward) blocks: SequentialSequence (:189-198) and
ReversibleSequence (:200-220), the latter a hand-rolled RevNet with RNG
state capture/replay for O(1) activation memory.

TPU-native equivalents:

  * SequentialTrunk — plain unrolled loop (XLA fuses across blocks).
  * reversible=True -> the same trunk with every block wrapped in
    jax.checkpoint (flax nn.remat): activations are rematerialized in the
    backward pass, giving the same activation-memory class as RevNet with
    no inverse math and exact determinism (JAX PRNG keys are explicit, so
    the reference's Deterministic RNG fork at reversible.py:59-89 has no
    analogue to port — determinism is free).
"""
from __future__ import annotations

from typing import Dict, Optional

import flax.linen as nn
import jax.numpy as jnp

from .attention import AttentionBlockSE3
from .core import FeedForwardBlockSE3
from .fiber import Fiber

Features = Dict[str, jnp.ndarray]


def _resolve_remat_policy(name: Optional[str]):
    """Map the string knob to a jax.checkpoint policy (None = remat
    everything). Strings keep the flax module dataclass hashable and the
    knob serializable in configs."""
    if name is None:
        return None
    import jax
    if name == 'save_conv_outputs':
        return jax.checkpoint_policies.save_only_these_names('conv_out')
    raise ValueError(f'unknown remat_policy {name!r}; '
                     f"expected None or 'save_conv_outputs'")


class SequentialTrunk(nn.Module):
    """depth x (AttentionBlockSE3 -> FeedForwardBlockSE3); reversible=True
    rematerializes each block (reference ReversibleSequence replacement)."""
    fiber: Fiber
    depth: int
    heads: int = 8
    dim_head: int = 24
    attend_self: bool = False
    edge_dim: int = 0
    use_null_kv: bool = False
    fourier_encode_dist: bool = False
    rel_dist_num_fourier_features: int = 4
    global_feats_dim: Optional[int] = None
    linear_proj_keys: bool = False
    tie_key_values: bool = False
    one_headed_key_values: bool = False
    norm_gated_scale: bool = False
    reversible: bool = False
    # remat policy for reversible=True. None = full per-block remat (the
    # O(1)-activation default, step cost ~4x fwd). 'save_conv_outputs' =
    # jax.checkpoint_policies.save_only_these_names('conv_out'): the
    # ConvSE3 results (tagged in ops/conv.py) are stored instead of
    # recomputed, so the backward replay skips the radial contraction —
    # ~95% of flagship FLOPs — and re-runs only the cheap glue. Costs
    # ~sum-over-blocks of the conv output tensors (~1.7 GB at flagship
    # dim=64/n=1024/k=32: 2 convs x 6 blocks x [n, k+1, 64, 16] f32)
    # for an expected ~4x -> ~3.1x step-multiplier cut.
    remat_policy: Optional[str] = None
    pallas: Optional[bool] = None
    pallas_attention: Optional[bool] = None
    pallas_attention_interpret: bool = False
    shared_radial_hidden: bool = False
    edge_chunks: Optional[int] = None
    fuse_basis: bool = False
    pallas_interpret: bool = False
    radial_bf16: bool = False
    # per-block conv backends for the attention value/key ConvSE3 paths
    # (resolved by the model from its conv_backend spec; None = dense
    # everywhere — ops.conv.CONV_BACKENDS)
    value_backends: Optional[tuple] = None
    key_backends: Optional[tuple] = None
    # per-block streaming-attention selection (resolved by the model
    # from its fuse_pairwise spec; None = unfused everywhere). A fused
    # block routes k/v + attention through kernels.pallas_flash.
    fused_attention: Optional[tuple] = None
    flash_interpret: bool = False
    # 'global' = the kNN-free large-assembly mode (every block; see
    # ops.attention.AttentionSE3.attention_mode)
    attention_mode: str = 'knn'
    global_materialize: bool = False

    @nn.compact
    def __call__(self, x: Features, edge_info, rel_dist, basis,
                 global_feats=None, pos_emb=None, mask=None) -> Features:
        # validate unconditionally: a typo'd or inapplicable policy must
        # raise, not silently no-op while configs/bench labels claim it
        policy = _resolve_remat_policy(self.remat_policy)
        if self.remat_policy is not None and not self.reversible:
            raise ValueError(
                f'remat_policy={self.remat_policy!r} requires '
                f'reversible=True (the policy governs what the '
                f'reversible backward stores vs recomputes)')
        attn_cls, ff_cls = AttentionBlockSE3, FeedForwardBlockSE3
        if self.reversible:
            attn_cls = nn.remat(AttentionBlockSE3, policy=policy)
            ff_cls = nn.remat(FeedForwardBlockSE3, policy=policy)

        for i in range(self.depth):
            x = attn_cls(
                self.fiber, heads=self.heads, dim_head=self.dim_head,
                backend_v=(self.value_backends[i]
                           if self.value_backends else 'dense'),
                backend_k=(self.key_backends[i]
                           if self.key_backends else 'dense'),
                attend_self=self.attend_self, edge_dim=self.edge_dim,
                use_null_kv=self.use_null_kv,
                fourier_encode_dist=self.fourier_encode_dist,
                rel_dist_num_fourier_features=self.rel_dist_num_fourier_features,
                global_feats_dim=self.global_feats_dim,
                linear_proj_keys=self.linear_proj_keys,
                tie_key_values=self.tie_key_values,
                one_headed_key_values=self.one_headed_key_values,
                norm_gated_scale=self.norm_gated_scale,
                pallas=self.pallas,
                pallas_attention=self.pallas_attention,
                pallas_attention_interpret=self.pallas_attention_interpret,
                shared_radial_hidden=self.shared_radial_hidden,
                edge_chunks=self.edge_chunks,
                fuse_basis=self.fuse_basis,
                radial_bf16=self.radial_bf16,
                pallas_interpret=self.pallas_interpret,
                fuse_pairwise=(self.fused_attention[i]
                               if self.fused_attention else False),
                flash_interpret=self.flash_interpret,
                attention_mode=self.attention_mode,
                global_materialize=self.global_materialize,
                name=f'attn_block{i}')(
                    x, edge_info, rel_dist, basis, global_feats, pos_emb,
                    mask)
            x = ff_cls(self.fiber, norm_gated_scale=self.norm_gated_scale,
                       name=f'ff_block{i}')(x)
        return x
