"""Parameters, operations and bytes of the decoder whose pattern's letters are
`C` a gated short convolution, `*` grouped-query attention with q/k norms,
`F` a dense gated feed-forward and `E` an expert layer of three-matrix
experts with no shared one; embedding and head are one matrix. From a
configuration's `model` sizes and a count of (token, expert) pairs. A
training step is priced at 3x its forward operations (forward plus a
backward of twice the forward): the replay of a recomputed block is never
counted. Causal attention is priced at half the square, by the hybrid
decoder's counts: they go by the query heads and their own width, so here
they are the unpadded work at heads of 64, whatever the kernel is fed."""
from .hybrid_counts import (  # noqa: F401  (the readers take them from here)
    attention_core_bytes, attention_core_flops, attention_core_train_flops,
)


def layers(m, kind):
    return m['hybrid_override_pattern'].count(kind)


def expert_layers(m):
    return layers(m, 'E')


def conv_matrix_params(m):
    """W_in [d, 3 d] and W_out [d, d]."""
    return 4 * m['hidden_size'] ** 2


def conv_vector_params(m):
    """The taps, one set a channel; no bias."""
    return m['conv_L_cache'] * m['hidden_size']


def attention_matrix_params(m):
    d, dh = m['hidden_size'], m['head_dim']
    return d * dh * 2 * (m['num_attention_heads']
                         + m['num_key_value_heads'])


def attention_vector_params(m):
    """The q and k norms' scales, one of head_dim each."""
    return 2 * m['head_dim']


def dense_ff_params(m):
    return 3 * m['hidden_size'] * m['intermediate_size']


def expert_params(m):
    """One routed expert: gate, up and down."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def router_params(m):
    return m['hidden_size'] * m['n_routed_experts']


def matrix_params(m):
    """{part: parameters in matrices, one operator of each kind}: the table
    of the configuration's cut."""
    return {
        'embedding': m['vocab_rows'] * m['hidden_size'],     # tied: once
        'conv': conv_matrix_params(m),
        'attention': attention_matrix_params(m),
        'dense_ff': dense_ff_params(m),
        'expert_layer': m['experts_held'] * expert_params(m)
        + router_params(m),
    }


def vector_params(m):
    """Every residual step's norm and the final one, the taps, the q/k
    norms."""
    return ((len(m['hybrid_override_pattern']) + 1) * m['hidden_size']
            + layers(m, 'C') * conv_vector_params(m)
            + layers(m, '*') * attention_vector_params(m))


def bias_entries(m):
    """The routers' correction biases: buffers the parameter tree holds."""
    return layers(m, 'E') * m['n_routed_experts']


def total_params(m):
    """Without the correction biases (`bias_entries`)."""
    p = matrix_params(m)
    return (p['embedding'] + layers(m, 'C') * p['conv']
            + layers(m, '*') * p['attention'] + layers(m, 'F') * p['dense_ff']
            + layers(m, 'E') * p['expert_layer'] + vector_params(m))


def sconv_core_flops(m, tokens):
    """One convolution operator's core, forward: B * X, the taps' multiply-
    adds, C * z."""
    return tokens * m['hidden_size'] * (2 + 2 * m['conv_L_cache'])


def sconv_core_train_flops(m, tokens, launches):
    return 3 * sconv_core_flops(m, tokens) * launches


def sconv_core_bytes(m, tokens, launches):
    """B, C, X read and the output written forward; they and the output's
    cotangent read and three cotangents written backward; float32."""
    return launches * 4 * tokens * m['hidden_size'] * (4 + 7)


def forward_flops(m, seq, pairs):
    """One sequence's forward. `pairs`: the (token, expert) pairs computed
    here, over all expert layers."""
    per_token = (
        layers(m, 'C') * 2 * conv_matrix_params(m)
        + layers(m, '*') * 2 * attention_matrix_params(m)
        + layers(m, 'F') * 2 * dense_ff_params(m)
        + layers(m, 'E') * 2 * router_params(m)
        + 2 * m['hidden_size'] * m['vocab_rows'])
    return (seq * per_token + 2 * pairs * expert_params(m)
            + layers(m, 'C') * sconv_core_flops(m, seq)
            + layers(m, '*') * attention_core_flops(m, seq))


def train_step_flops(m, seq, pairs):
    return 3 * forward_flops(m, seq, pairs)
