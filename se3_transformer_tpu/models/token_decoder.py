"""A causal token decoder: latent attention, one or more leading dense
blocks, then blocks whose feed-forward is an expert layer that holds a share
of the experts, and a depth-1 next-next-token prediction block after the
last.

    block:  h <- h + Attn(RMSNorm(h));  h <- h + FFN(RMSNorm(h))
    FFN:    SwiGLU in the first `first_k_dense_replace` blocks, the expert
            layer (ops/expert_layer.py) after them
    prediction block (depth 1, as DeepSeek-V3 defines it): with h_t the last
            block's output before the final norm,
            u_t = [RMSNorm(Emb(x_{t+1})) ; RMSNorm(h_t)] We, one expert-layer
            block on u, its own final RMSNorm, then the model's own head:
            it predicts x_{t+2}. Embedding and head are shared.

The fields carry the names a published `config.json` gives them;
`experts_held`, `expert_rank` and `vocab_rows` say what this chip holds of an
expert-parallel deployment (the router keeps `n_routed_experts` outputs, ids
and logits are over the `vocab_rows` rows held). `num_hidden_layers` is the
number of blocks built here.

`hidden_states` stops before the head: the loss (training/lm_loss.py) takes
the logits chunk by chunk, so that no [tokens, vocab_rows] array is held.
"""
from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..observability import named_scope
from ..ops.expert_layer import ExpertLayer, SwiGLU
from ..ops.latent_attention import (
    SAVE_ATTN_CORE, LatentAttention, RMSNorm,
)


class DecoderBlock(nn.Module):
    attention: dict            # LatentAttention's fields
    eps: float
    dense: Optional[dict] = None     # SwiGLU's fields
    experts: Optional[dict] = None   # else ExpertLayer's

    @nn.compact
    def __call__(self, h):
        """h [B, T, d] -> (h, the expert layer's stats or None)."""
        with named_scope('norm'):
            a = RMSNorm(self.eps, name='attn_norm')(h)
        h = h + LatentAttention(**self.attention, eps=self.eps,
                                name='attn')(a)
        with named_scope('norm'):
            f = RMSNorm(self.eps, name='ff_norm')(h)
        if self.experts is None:
            with named_scope('dense_ff'):
                return h + SwiGLU(**self.dense, name='mlp')(f), None
        b, t, d = f.shape
        out, stats = ExpertLayer(**self.experts, name='moe')(
            f.reshape(b * t, d))
        return h + out.reshape(b, t, d), stats


class TokenDecoder(nn.Module):
    vocab_rows: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    experts_held: int
    expert_rank: int = 0
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    # execution, not architecture (every block is recomputed in the
    # backward pass: its input is saved, and the streaming attention core's
    # output and softmax statistics, so the replay launches no forward)
    attention_block: int = 512       # ops/latent_attention.py
    bf16_operands: bool = True       # ops/expert_layer.py

    def setup(self):
        assert self.num_nextn_predict_layers in (0, 1)
        attention = dict(
            dim=self.hidden_size, heads=self.num_attention_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            block=self.attention_block)
        experts = dict(
            width=self.moe_intermediate_size,
            n_experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            experts_held=self.experts_held, expert_rank=self.expert_rank,
            shared_width=self.n_shared_experts * self.moe_intermediate_size,
            routed_scale=self.routed_scaling_factor,
            norm_topk=self.norm_topk_prob, bf16_operands=self.bf16_operands)
        dense = dict(width=self.intermediate_size,
                     bf16_operands=self.bf16_operands)
        block = nn.remat(DecoderBlock, policy=SAVE_ATTN_CORE)
        eps = self.rms_norm_eps
        self.embedding = nn.Embed(self.vocab_rows, self.hidden_size)
        self.blocks = [
            block(attention, eps, dense=dense)
            if i < self.first_k_dense_replace
            else block(attention, eps, experts=experts)
            for i in range(self.num_hidden_layers)]
        self.final_norm = RMSNorm(eps)
        self.head = nn.Dense(self.vocab_rows, use_bias=False)
        if self.num_nextn_predict_layers:
            self.mtp_token_norm = RMSNorm(eps)
            self.mtp_hidden_norm = RMSNorm(eps)
            self.mtp_proj = nn.Dense(self.hidden_size, use_bias=False)
            self.mtp_block = block(attention, eps, experts=experts)
            self.mtp_final_norm = RMSNorm(eps)

    def expert_layer_names(self):
        """The parameter subtrees with an expert layer, in `stats` order."""
        return [f'blocks_{i}' for i in range(self.first_k_dense_replace,
                                             self.num_hidden_layers)] \
            + ['mtp_block'] * self.num_nextn_predict_layers

    def head_kernel(self, params):
        """The head's matrix [d, vocab_rows] from a parameter tree."""
        return params['head']['kernel']

    def hidden_states(self, tokens):
        """tokens [B, T] -> (main [B, T, d], next [B, T, d] or None, stats):
        the two heads' normed inputs. `main[t]` predicts token t + 1 and
        `next[t]` token t + 2; position T - 1 of the prediction block is fed
        token 0 for the token that does not exist (causal: no earlier
        position sees it; the loss masks it). `stats` has one entry per
        expert layer, the prediction block's last."""
        with named_scope('embed'):
            h = self.embedding(tokens)
        stats = []
        for block in self.blocks:
            h, s = block(h)
            stats += [s] if s is not None else []
        with named_scope('norm'):
            main = self.final_norm(h)
        if not self.num_nextn_predict_layers:
            return main, None, stats
        with named_scope('embed'):
            ahead = self.embedding(jnp.roll(tokens, -1, axis=1))
        with named_scope('mtp_merge'):
            u = self.mtp_proj(jnp.concatenate(
                (self.mtp_token_norm(ahead), self.mtp_hidden_norm(h)),
                axis=-1))
        u, s = self.mtp_block(u)
        with named_scope('norm'):
            return main, self.mtp_final_norm(u), stats + [s]

    def __call__(self, tokens):
        """Both heads' logits [B, T, vocab_rows] (float32) and the stats:
        for small sizes and `init`; training goes through `hidden_states`."""
        main, ahead, stats = self.hidden_states(tokens)
        with named_scope('lm_head'):
            return (self.head(main),
                    None if ahead is None else self.head(ahead), stats)
