"""Parameters, operations and bytes of the decoder trained by diffusion over
blocks: the pattern's letters are `*` grouped-query attention with q/k
norms and `E` an expert layer of three-matrix experts with no shared one;
embedding and head are two matrices. From a configuration's `model` sizes,
the data's sequence length L, the block length and a count of (position,
expert) pairs.

A step reads every sequence twice (a noised copy and the clean one: 2 L
positions through every layer) and takes the head over the noised L alone.
The attention core is priced at its visible pairs, L^2 + L block_length a
sequence and head (the rule of `sdar_reference.py`'s docstring), 4
operations a pair and channel. A training step is priced at 3x its forward
operations (forward plus a backward of twice the forward): the replay of a
recomputed block is never counted, nor the kernel's own recomputation of the
scores."""


def layers(m, kind):
    return m['hybrid_override_pattern'].count(kind)


def expert_layers(m):
    return layers(m, 'E')


def attention_matrix_params(m):
    """q and out [d, H dh], k and v [d, KV dh]."""
    d, dh = m['hidden_size'], m['head_dim']
    return d * dh * 2 * (m['num_attention_heads']
                         + m['num_key_value_heads'])


def attention_vector_params(m):
    """The q and k norms' scales, one of head_dim each."""
    return 2 * m['head_dim']


def expert_params(m):
    """One routed expert: gate, up and down."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def router_params(m):
    return m['hidden_size'] * m['n_routed_experts']


def matrix_params(m):
    """{part: parameters in matrices, one of each kind}: the table of the
    configuration's cut."""
    return {
        'embedding': m['vocab_rows'] * m['hidden_size'],
        'head': m['vocab_rows'] * m['hidden_size'],
        'attention': attention_matrix_params(m),
        'expert_layer': m['experts_held'] * expert_params(m)
        + router_params(m),
    }


def vector_params(m):
    """Every residual step's norm and the final one, the q/k norms."""
    return ((len(m['hybrid_override_pattern']) + 1) * m['hidden_size']
            + layers(m, '*') * attention_vector_params(m))


def bias_entries(m):
    """The routers' correction biases: buffers the parameter tree holds."""
    return layers(m, 'E') * m['n_routed_experts']


def total_params(m):
    """Without the correction biases (`bias_entries`)."""
    p = matrix_params(m)
    return (p['embedding'] + p['head'] + layers(m, '*') * p['attention']
            + layers(m, 'E') * p['expert_layer'] + vector_params(m))


def visible_pairs(seq, block_length):
    """(query, key) pairs the two streams of one sequence compute, a head."""
    return seq * seq + seq * block_length


def bd_core_flops(m, seq, block_length):
    """Scores and weighted sum of one layer's forward over one sequence's
    two streams, every query head."""
    return m['num_attention_heads'] * visible_pairs(seq, block_length) \
        * 4 * m['head_dim']


def bd_core_train_flops(m, seq, block_length, launches):
    return 3 * bd_core_flops(m, seq, block_length) * launches


def bd_core_bytes(m, seq, launches):
    """As the kernel is fed, over 2 seq positions, each tensor once, in
    bfloat16 but the float32 statistics: q and o at the query heads, k and v
    at the key-value heads, the log-sum-exp forward; q, k, v, o, do and the
    log-sum-exp read, dq, dk, dv written backward."""
    t, dh = 2 * seq, m['head_dim']
    h, kv = m['num_attention_heads'], m['num_key_value_heads']
    forward = 2 * t * dh * (2 * h + 2 * kv) + 4 * t * h
    backward = 2 * t * dh * (4 * h + 4 * kv) + 4 * t * h
    return launches * (forward + backward)


def forward_flops(m, seq, pairs, block_length):
    """One sequence's forward. `pairs`: the (position, expert) pairs computed
    here, over all expert layers and both streams."""
    per_position = (layers(m, '*') * 2 * attention_matrix_params(m)
                    + layers(m, 'E') * 2 * router_params(m))
    return (2 * seq * per_position + 2 * pairs * expert_params(m)
            + layers(m, '*') * bd_core_flops(m, seq, block_length)
            + seq * 2 * m['hidden_size'] * m['vocab_rows'])


def train_step_flops(m, seq, pairs, block_length):
    return 3 * forward_flops(m, seq, pairs, block_length)
