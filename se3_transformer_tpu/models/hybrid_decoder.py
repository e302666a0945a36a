"""A causal token decoder whose layers follow a pattern string, each letter
one pre-normed mixer with its residual:

    h <- h + Mixer(RMSNorm(h)),   Mixer by the letter:
      M   a Mamba-2 state-space mixer          (ops/state_space.py)
      C   a gated short convolution            (ops/short_conv.py)
      *   grouped-query causal attention       (ops/grouped_attention.py),
          with per-head q/k norms (`qk_norm`) and rotation (`rope_theta`;
          None: none) where the model has them; over the two streams of a
          block-diffusion pass (`hidden_states(tokens, noised,
          block_length)`) by `ops/block_diffusion.py`'s rule instead
      W   the same attention under a sliding window: a query sees the
          `sliding_window_size` keys that end with itself, rotated at
          `sliding_rope_theta`. Window and rotation are the kind's, not the
          model's: `*EWEWEWE` is a global layer without rotation
          (`rope_theta` None) and three sliding layers with it
      E   an expert layer that holds a share   (ops/expert_layer.py),
          routed by `scoring_func` ('sigmoid' or 'softmax'), its experts of
          the form `mlp_hidden_act` ('silu', 'relu' or 'relu2'). With
          `moe_enable_early_router` (the router placed before attention) it
          is routed by the normed input of the attention step before it
          (`*` or `W`), which that step hands on, while its experts read
          its own normed input
      F   a dense gated feed-forward           (SwiGLU, ops/expert_layer.py)

then a final RMSNorm and the head: a matrix of its own, or with
`tie_word_embeddings` the embedding's transpose (no `head` subtree; the
embedding then takes both gradients). A published layer is one letter (a
layer of one mixer: `MEMEM*EME`) or two (an operator, then a feed-forward:
`CF*ECECECE` is a convolution layer with a dense feed-forward, an attention
layer and three convolution layers with experts); there is no prediction
block. Only the fields of the mixers the pattern uses need be given.

With `sandwich_norm` a second norm sits on every mixer's output, h <- h +
RMSNorm(Mixer(RMSNorm(h))). With `total_ut_steps` above 1 the stack is
looped: the pattern's blocks run that many times on ONE set of weights, the
final norm closes every pass and its output is what the next pass reads,
every pass's normed state is an exit of its own (the one head reads each) and
an `exit_gate` reads it too (`training/lm_loss.py::make_looped_lm_loss` is
the objective over them).

The fields carry the names a published `config.json` gives them;
`experts_held`, `expert_rank` and `vocab_rows` say what this chip holds of an
expert-parallel deployment, as in `models/token_decoder.py`, whose
interface this shares: `hidden_states` stops before the head and returns
(main, None, stats), `expert_layer_names` lists the expert layers' subtrees,
`head_kernel` gives the head's matrix from a parameter tree, so
`training/lm_loss.py` trains both. `hybrid_override_pattern` is the
layers built here.
"""
from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..observability import named_scope
from ..ops.expert_layer import ExpertLayer, SwiGLU
from ..ops.grouped_attention import GroupedQueryAttention
from ..ops.latent_attention import SAVE_ATTN_CORE, RMSNorm
from ..ops.short_conv import ShortConvMixer
from ..ops.state_space import Mamba2Mixer

# letter -> (the mixer's module, its name in the parameter tree)
MIXERS = {'M': (Mamba2Mixer, 'ssm'), 'E': (ExpertLayer, 'moe'),
          '*': (GroupedQueryAttention, 'attn'),
          'W': (GroupedQueryAttention, 'attn'),
          'C': (ShortConvMixer, 'conv'), 'F': (SwiGLU, 'mlp')}
ATTENTION = '*W'


class MixerBlock(nn.Module):
    kind: str                  # a key of MIXERS
    mixer: dict                # the mixer's fields
    eps: float
    early_router: bool = False     # an attention step hands its normed
    #                                input on, for the next step's router
    sandwich_norm: bool = False    # a norm on the mixer's output too

    @nn.compact
    def __call__(self, h, positions=None, block_length: int = 0,
                 routing_input=None):
        """h [B, T, d] -> (h, the expert layer's stats or None; with
        `early_router` an attention step's normed input [B, T, d] in their
        place). `positions` and `block_length` (static) are the attention
        mixer's: the two streams of a block-diffusion pass
        (`hidden_states`). `routing_input` [B, T, d] is the expert layer's:
        what its router reads where that is not the step's own normed
        input."""
        with named_scope('norm'):
            u = RMSNorm(self.eps, name='pre_norm')(h)
        module, name = MIXERS[self.kind]
        mixer = module(**self.mixer, name=name)

        def residual(out):
            if self.sandwich_norm:
                with named_scope('norm'):
                    out = RMSNorm(self.eps, name='post_norm')(out)
            return h + out

        if self.kind == 'E':
            b, t, d = u.shape
            out, stats = mixer(
                u.reshape(b * t, d), None if routing_input is None
                else routing_input.reshape(b * t, d))
            return residual(out.reshape(b, t, d)), stats
        if self.kind == 'F':       # SwiGLU writes no scope of its own
            with named_scope('dense_ff'):
                return residual(mixer(u)), None
        if self.kind in ATTENTION:
            return residual(mixer(u, positions, block_length)), \
                u if self.early_router else None
        return residual(mixer(u)), None


class HybridDecoder(nn.Module):
    vocab_rows: int
    hidden_size: int
    hybrid_override_pattern: str
    # M
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # C
    conv_L_cache: int = 3
    # E
    moe_intermediate_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    experts_held: int = 0
    expert_rank: int = 0
    mlp_hidden_act: str = 'relu2'
    routed_scaling_factor: float = 1.0
    scoring_func: str = 'sigmoid'
    norm_topk_prob: bool = True
    norm_topk_eps: float = 1e-20
    moe_enable_early_router: bool = False
    # F
    intermediate_size: int = 0
    # *
    num_attention_heads: int = 0
    num_key_value_heads: int = 0
    head_dim: int = 0
    qk_norm: bool = False
    rope_theta: Optional[float] = None
    # W: `*`'s heads under a window, with a rotation of its own
    sliding_window_size: int = 0
    sliding_rope_theta: Optional[float] = None
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    # a looped stack: passes over the one set of blocks, and a second norm
    # on every mixer's output (a published model has both or neither)
    total_ut_steps: int = 1
    sandwich_norm: bool = False
    # execution, not architecture (every block is recomputed in the
    # backward pass: its input is saved, and the streaming attention core's
    # output and softmax statistics, so the replay launches no forward)
    attention_block: int = 512       # ops/latent_attention.py
    bf16_operands: bool = True       # ops/expert_layer.py

    def setup(self):
        assert set(self.hybrid_override_pattern) <= set(MIXERS), \
            self.hybrid_override_pattern
        eps = self.layer_norm_epsilon
        attention = dict(
            dim=self.hidden_size, heads=self.num_attention_heads,
            kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            block=self.attention_block, qk_norm=self.qk_norm, eps=eps)
        fields = {
            'M': dict(
                dim=self.hidden_size, num_heads=self.mamba_num_heads,
                head_dim=self.mamba_head_dim, state_size=self.ssm_state_size,
                n_groups=self.n_groups, conv_kernel=self.conv_kernel,
                chunk_size=self.chunk_size, use_conv_bias=self.use_conv_bias,
                time_step_min=self.time_step_min,
                time_step_max=self.time_step_max,
                time_step_floor=self.time_step_floor, eps=eps),
            'C': dict(dim=self.hidden_size, taps=self.conv_L_cache),
            'E': dict(
                width=self.moe_intermediate_size,
                n_experts=self.n_routed_experts,
                top_k=self.num_experts_per_tok,
                experts_held=self.experts_held, expert_rank=self.expert_rank,
                shared_width=self.moe_shared_expert_intermediate_size,
                hidden_act=self.mlp_hidden_act,
                routed_scale=self.routed_scaling_factor,
                scoring_func=self.scoring_func,
                norm_topk=self.norm_topk_prob,
                norm_topk_eps=self.norm_topk_eps,
                bf16_operands=self.bf16_operands),
            'F': dict(width=self.intermediate_size,
                      bf16_operands=self.bf16_operands),
            '*': dict(attention, rope_theta=self.rope_theta),
            'W': dict(attention, rope_theta=self.sliding_rope_theta,
                      window=self.sliding_window_size)}
        # `block_length` is static: argument 3 of `__call__`, self counted
        block = nn.remat(MixerBlock, policy=SAVE_ATTN_CORE,
                         static_argnums=(3,))
        self.embedding = nn.Embed(self.vocab_rows, self.hidden_size)
        self.blocks = [
            block(kind, fields[kind], eps,
                  self.moe_enable_early_router and kind in ATTENTION,
                  self.sandwich_norm)
            for kind in self.hybrid_override_pattern]
        self.final_norm = RMSNorm(eps)
        if not self.tie_word_embeddings:
            self.head = nn.Dense(self.vocab_rows, use_bias=False)
        if self.total_ut_steps > 1:
            self.exit_gate = nn.Dense(1)

    def expert_layer_names(self):
        """The parameter subtrees with an expert layer, in `stats` order."""
        return [f'blocks_{i}' for i, kind in
                enumerate(self.hybrid_override_pattern) if kind == 'E']

    def head_kernel(self, params):
        """The head's matrix [d, vocab_rows] from a parameter tree."""
        if self.tie_word_embeddings:
            return params['embedding']['embedding'].T
        return params['head']['kernel']

    def hidden_states(self, tokens, noised=None, block_length: int = 0):
        """tokens [B, T] -> (main [B, T, d], None, stats): the head's normed
        input (`main[t]` predicts token t + 1), no second head, one entry of
        `stats` per expert layer.

        With `noised` [B, T] (the tokens with some replaced by the mask id)
        and a `block_length`, the pass of a decoder trained by diffusion over
        blocks: both streams in one sequence of 2 T positions, noised first,
        the two copies of a token at one rotary position, attention by
        `ops/block_diffusion.py`'s rule (only `*` mixers look across tokens
        here: a pattern with `M` or `C` has no such pass). `main` is the
        noised stream's T positions alone (`main[t]` predicts token t, in
        place); the clean stream's last layer feeds nothing. The expert
        layers' stats are over all 2 T positions.

        A looped stack (`total_ut_steps` P above 1) returns `main` [P, B, T,
        d], every pass's normed state, and an entry of `stats` per expert
        layer and pass, pass by pass. A pass is no module (flax gives the P
        calls of `blocks_0` one name), so each runs under a path component
        `ut_<t>` of its own, t = 0 .. P - 1: the trace reducer's `pass_s`
        reads it. Unrolled in Python: inside a scanned, checkpointed body the
        operations lose their scopes."""
        positions = None
        if noised is not None:
            assert block_length and self.total_ut_steps == 1 and not set(
                self.hybrid_override_pattern) & {'M', 'C'}, \
                (block_length, self.total_ut_steps,
                 self.hybrid_override_pattern)
            with named_scope('bd_streams'):
                t = tokens.shape[1]
                tokens = jnp.concatenate((noised, tokens), axis=1)
                positions = jnp.tile(jnp.arange(t), 2)
        with named_scope('embed'):
            h = self.embedding(tokens)
        stats = []

        def blocks(h):      # the pattern's blocks, once
            handed = None
            for kind, block in zip(self.hybrid_override_pattern,
                                   self.blocks):
                h, s = block(h, positions, block_length,
                             handed if kind == 'E' else None)
                if kind == 'E':
                    stats.append(s)
                else:       # an attention step's normed input, or None
                    handed = s
            return h

        if self.total_ut_steps == 1:
            h = blocks(h)
            if noised is not None:
                with named_scope('bd_streams'):
                    h = h[:, :h.shape[1] // 2]
            with named_scope('norm'):
                return self.final_norm(h), None, stats
        passes = []
        for t in range(self.total_ut_steps):
            with named_scope(f'ut_{t}'):
                h = blocks(h)
                with named_scope('norm'):
                    h = self.final_norm(h)
            passes.append(h)
        return jnp.stack(passes), None, stats

    def exit_logits(self, main):
        """A looped stack's `main` [P, B, T, d] -> the gate's logits
        [P - 1, B, T] on the normed states of every pass but the last, which
        takes the mass the others leave."""
        with named_scope('exit_gate'):
            return self.exit_gate(main[:-1])[..., 0]

    def __call__(self, tokens):
        """The logits [B, T, vocab_rows] (float32; a looped stack's last
        pass's) and the stats: for small sizes and `init`; training goes
        through `hidden_states`."""
        main, _, stats = self.hidden_states(tokens)
        if self.total_ut_steps > 1:
            self.exit_logits(main)      # so that `init` makes the gate
            main = main[-1]
        with named_scope('lm_head'):
            if self.tie_word_embeddings:
                return self.embedding.attend(main), stats
            return self.head(main), stats
