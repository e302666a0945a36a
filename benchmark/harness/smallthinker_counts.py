"""Parameters, operations and bytes of the decoder whose attention layers
are global (`*`: causal, no rotation) or sliding (`W`: a window, with
rotation), each followed by an expert layer (`E`) of three-matrix experts
with no shared one; embedding and head are two matrices. From a
configuration's `model` sizes, the sequence length T and a count of (token,
expert) pairs.

Each attention core is priced at its visible pairs: the causal triangle
T (T + 1) / 2 a head for a global layer, T w - w (w - 1) / 2 for a sliding
one (w = min(window, T): a row sees min(i + 1, w) keys), 4 operations a pair
and channel, the same work whatever implements it. A training step is priced
at 3x its forward operations (forward plus a backward of twice the forward):
the replay of a recomputed block is never counted, nor a kernel's own
recomputation of the scores, nor what it computes of a tile's masked pairs.
"""

ATTENTION = '*W'


def layers(m, kinds):
    return sum(m['hybrid_override_pattern'].count(k) for k in kinds)


def expert_layers(m):
    return layers(m, 'E')


def attention_matrix_params(m):
    """q and out [d, H dh], k and v [d, KV dh]."""
    d, dh = m['hidden_size'], m['head_dim']
    return d * dh * 2 * (m['num_attention_heads']
                         + m['num_key_value_heads'])


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
    """Every residual step's norm and the final one (no q/k norms)."""
    return (len(m['hybrid_override_pattern']) + 1) * m['hidden_size']


def bias_entries(m):
    """The routers' correction biases: buffers the parameter tree holds."""
    return layers(m, 'E') * m['n_routed_experts']


def total_params(m):
    """Without the correction biases (`bias_entries`)."""
    p = matrix_params(m)
    return (p['embedding'] + p['head'] + layers(m, ATTENTION) * p['attention']
            + layers(m, 'E') * p['expert_layer'] + vector_params(m))


def visible_pairs(seq, window=0):
    """(query, key) pairs a head computes; `window` 0: the causal
    triangle."""
    w = min(window, seq) if window else seq
    return seq * w - w * (w - 1) // 2


def _window(m, kind):
    return m['sliding_window_size'] if kind == 'W' else 0


def core_flops(m, seq, kind):
    """Scores and weighted sum of one layer's forward over one sequence,
    every query head."""
    return m['num_attention_heads'] * visible_pairs(seq, _window(m, kind)) \
        * 4 * m['head_dim']


def core_train_flops(m, seq, kind, launches):
    return 3 * core_flops(m, seq, kind) * launches


def core_bytes(m, seq, launches):
    """Each tensor once over `seq` positions, in bfloat16 but the float32
    statistics: q and o at the query heads, k and v at the key-value heads,
    the log-sum-exp forward; q, k, v, o, do and the log-sum-exp read, dq, dk,
    dv written backward. The same for either kind of layer."""
    dh = m['head_dim']
    h, kv = m['num_attention_heads'], m['num_key_value_heads']
    forward = 2 * seq * dh * (2 * h + 2 * kv) + 4 * seq * h
    backward = 2 * seq * dh * (4 * h + 4 * kv) + 4 * seq * h
    return launches * (forward + backward)


def forward_flops(m, seq, pairs):
    """One sequence's forward. `pairs`: the (token, expert) pairs computed
    here, over all expert layers."""
    per_token = (layers(m, ATTENTION) * 2 * attention_matrix_params(m)
                 + layers(m, 'E') * 2 * router_params(m)
                 + 2 * m['hidden_size'] * m['vocab_rows'])
    return (seq * per_token + 2 * pairs * expert_params(m)
            + sum(layers(m, kind) * core_flops(m, seq, kind)
                  for kind in ATTENTION))


def train_step_flops(m, seq, pairs):
    return 3 * forward_flops(m, seq, pairs)
