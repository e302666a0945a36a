"""Parameters, operations and bytes of the hybrid decoder (layers by a
pattern string: `M` a Mamba-2 mixer, `E` an expert layer of two-matrix
experts, `*` grouped-query attention), from a configuration's `model` sizes
and a count of (token, expert) pairs. A training step is priced at 3x its
forward operations (forward plus a backward of twice the forward): the replay
of a recomputed block is never counted. Causal attention and the inside of a
scan's chunk are priced at half the square."""


def layers(m, kind):
    return m['hybrid_override_pattern'].count(kind)


def expert_layers(m):
    return layers(m, 'E')


def _ssm_widths(m):
    """(inner width H P, the convolution's channels H P + 2 G N)."""
    inner = m['mamba_num_heads'] * m['mamba_head_dim']
    return inner, inner + 2 * m['n_groups'] * m['ssm_state_size']


def mamba_matrix_params(m):
    """W_in [d, 2 H P + 2 G N + H] and W_out [H P, d]."""
    inner, conv = _ssm_widths(m)
    return m['hidden_size'] * (inner + conv + m['mamba_num_heads']
                               + inner)


def mamba_vector_params(m):
    """The convolution's taps and bias, dt_bias, A_log and D, the gated
    norm's scale."""
    inner, conv = _ssm_widths(m)
    return (conv * (m['conv_kernel'] + int(m['use_conv_bias']))
            + 3 * m['mamba_num_heads'] + inner)


def attention_params(m):
    d, dh = m['hidden_size'], m['head_dim']
    return d * dh * 2 * (m['num_attention_heads']
                         + m['num_key_value_heads'])


def expert_params(m):
    """One routed expert: up and down."""
    return 2 * m['hidden_size'] * m['moe_intermediate_size']


def shared_params(m):
    return 2 * m['hidden_size'] * m['moe_shared_expert_intermediate_size']


def matrix_params(m):
    """{part: parameters in matrices, one layer of each kind}: the table of
    the configuration's cut."""
    d = m['hidden_size']
    return {
        'embedding_and_head': 2 * m['vocab_rows'] * d,
        'mamba_layer': mamba_matrix_params(m),
        'expert_layer': (m['experts_held'] * expert_params(m)
                         + shared_params(m) + d * m['n_routed_experts']),
        'attention_layer': attention_params(m),
    }


def vector_params(m):
    """Every layer's norm and the final one, the state-space layers' vectors,
    the routers' correction biases."""
    return ((len(m['hybrid_override_pattern']) + 1) * m['hidden_size']
            + layers(m, 'M') * mamba_vector_params(m)
            + layers(m, 'E') * m['n_routed_experts'])


def total_params(m):
    p = matrix_params(m)
    return (p['embedding_and_head'] + layers(m, 'M') * p['mamba_layer']
            + layers(m, 'E') * p['expert_layer']
            + layers(m, '*') * p['attention_layer'] + vector_params(m))


def scan_flops(m, seq):
    """One state-space layer's scan, forward, over one sequence: inside a
    chunk of Q tokens the scores C B^T (per group) and their product with
    dt x (per head), at half the square, T Q (N G + P H); then 2 T N P H to
    build the chunk states and 2 T N P H to read them. The same count
    whatever implements the scan."""
    h, p, n, g = (m['mamba_num_heads'], m['mamba_head_dim'],
                  m['ssm_state_size'], m['n_groups'])
    return seq * m['chunk_size'] * (n * g + p * h) + 4 * seq * n * p * h


def scan_train_flops(m, seq, launches):
    return 3 * scan_flops(m, seq) * launches


def scan_bytes(m, seq, launches):
    """x, B, C, dt and y once each forward, they and their cotangents once
    each backward, in float32."""
    inner, conv = _ssm_widths(m)
    row = inner + conv + m['mamba_num_heads']      # x, B, C | y | dt
    return launches * 4 * seq * row * 3


def attention_core_flops(m, seq):
    """Scores and weighted sum of one layer's forward over one sequence,
    causal (half the square), every query head."""
    return m['num_attention_heads'] * seq * seq * 2 * m['head_dim']


def attention_core_train_flops(m, seq, launches):
    """Forward plus a backward of twice the forward (the kernel's own
    recomputation of the scores is not counted)."""
    return 3 * attention_core_flops(m, seq) * launches


def attention_core_bytes(m, seq, launches):
    """As the kernel is fed, the key-value heads repeated to the query
    heads': q, k, v and the output once forward; q, k, v, output, its
    cotangent and the three gradients once backward, in bfloat16."""
    return launches * 2 * seq * m['num_attention_heads'] * m['head_dim'] \
        * (4 + 8)


def forward_flops(m, seq, pairs):
    """One sequence's forward. `pairs`: the (token, expert) pairs computed
    here, over all expert layers."""
    d = m['hidden_size']
    _, conv = _ssm_widths(m)
    per_token = (
        layers(m, 'M') * 2 * (mamba_matrix_params(m)
                              + m['conv_kernel'] * conv)
        + layers(m, 'E') * 2 * (shared_params(m) + d * m['n_routed_experts'])
        + layers(m, '*') * 2 * attention_params(m)
        + 2 * d * m['vocab_rows'])
    return (seq * per_token + 2 * pairs * expert_params(m)
            + layers(m, 'M') * scan_flops(m, seq)
            + layers(m, '*') * attention_core_flops(m, seq))


def train_step_flops(m, seq, pairs):
    return 3 * forward_flops(m, seq, pairs)
