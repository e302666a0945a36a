"""A causal token decoder whose layers follow a pattern string, each layer
one pre-normed mixer with its residual:

    h <- h + Mixer(RMSNorm(h)),   Mixer by the layer's letter:
      M   a Mamba-2 state-space mixer          (ops/state_space.py)
      E   an expert layer that holds a share   (ops/expert_layer.py)
      *   grouped-query causal attention       (ops/grouped_attention.py)

then a final RMSNorm and an untied head. No layer has attention AND a
feed-forward; no rotation is applied (the state-space layers carry
position); there is no prediction block.

The fields carry the names a published `config.json` gives them;
`experts_held`, `expert_rank` and `vocab_rows` say what this chip holds of an
expert-parallel deployment, as in `models/token_decoder.py`, whose
interface this shares: `hidden_states` stops before the head and returns
(main, None, stats), `expert_layer_names` lists the expert layers' subtrees,
so `training/lm_loss.py` trains both. `hybrid_override_pattern` is the
layers built here.
"""
from __future__ import annotations

import flax.linen as nn

from ..observability import named_scope
from ..ops.expert_layer import ExpertLayer
from ..ops.grouped_attention import GroupedQueryAttention
from ..ops.latent_attention import RMSNorm
from ..ops.state_space import Mamba2Mixer

# letter -> (the mixer's module, its name in the parameter tree)
MIXERS = {'M': (Mamba2Mixer, 'ssm'), 'E': (ExpertLayer, 'moe'),
          '*': (GroupedQueryAttention, 'attn')}


class MixerBlock(nn.Module):
    kind: str                  # a key of MIXERS
    mixer: dict                # the mixer's fields
    eps: float

    @nn.compact
    def __call__(self, h):
        """h [B, T, d] -> (h, the expert layer's stats or None)."""
        with named_scope('norm'):
            u = RMSNorm(self.eps, name='pre_norm')(h)
        module, name = MIXERS[self.kind]
        if self.kind != 'E':
            return h + module(**self.mixer, name=name)(u), None
        b, t, d = u.shape
        out, stats = module(**self.mixer, name=name)(u.reshape(b * t, d))
        return h + out.reshape(b, t, d), stats


class HybridDecoder(nn.Module):
    vocab_rows: int
    hidden_size: int
    hybrid_override_pattern: str
    # M
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    # E
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    experts_held: int
    # *
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    expert_rank: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    mlp_hidden_act: str = 'relu2'
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    # execution, not architecture (every block is recomputed in the
    # backward pass: its input alone is saved)
    attention_block: int = 512       # ops/latent_attention.py
    bf16_operands: bool = True       # ops/expert_layer.py

    def setup(self):
        assert set(self.hybrid_override_pattern) <= set(MIXERS), \
            self.hybrid_override_pattern
        eps = self.layer_norm_epsilon
        fields = {
            'M': dict(
                dim=self.hidden_size, num_heads=self.mamba_num_heads,
                head_dim=self.mamba_head_dim, state_size=self.ssm_state_size,
                n_groups=self.n_groups, conv_kernel=self.conv_kernel,
                chunk_size=self.chunk_size, use_conv_bias=self.use_conv_bias,
                time_step_min=self.time_step_min,
                time_step_max=self.time_step_max,
                time_step_floor=self.time_step_floor, eps=eps),
            'E': dict(
                width=self.moe_intermediate_size,
                n_experts=self.n_routed_experts,
                top_k=self.num_experts_per_tok,
                experts_held=self.experts_held, expert_rank=self.expert_rank,
                shared_width=self.moe_shared_expert_intermediate_size,
                hidden_act=self.mlp_hidden_act,
                routed_scale=self.routed_scaling_factor,
                norm_topk=self.norm_topk_prob,
                bf16_operands=self.bf16_operands),
            '*': dict(
                dim=self.hidden_size, heads=self.num_attention_heads,
                kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                block=self.attention_block)}
        block = nn.remat(MixerBlock)
        self.embedding = nn.Embed(self.vocab_rows, self.hidden_size)
        self.blocks = [block(kind, fields[kind], eps)
                       for kind in self.hybrid_override_pattern]
        self.final_norm = RMSNorm(eps)
        self.head = nn.Dense(self.vocab_rows, use_bias=False)

    def expert_layer_names(self):
        """The parameter subtrees with an expert layer, in `stats` order."""
        return [f'blocks_{i}' for i, kind in
                enumerate(self.hybrid_override_pattern) if kind == 'E']

    def hidden_states(self, tokens):
        """tokens [B, T] -> (main [B, T, d], None, stats): the head's normed
        input (`main[t]` predicts token t + 1), no second head, one entry of
        `stats` per expert layer."""
        with named_scope('embed'):
            h = self.embedding(tokens)
        stats = []
        for block in self.blocks:
            h, s = block(h)
            stats += [s] if s is not None else []
        with named_scope('norm'):
            return self.final_norm(h), None, stats

    def __call__(self, tokens):
        """The logits [B, T, vocab_rows] (float32) and the stats: for small
        sizes and `init`; training goes through `hidden_states`."""
        main, _, stats = self.hidden_states(tokens)
        with named_scope('lm_head'):
            return self.head(main), stats
