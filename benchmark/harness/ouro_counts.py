"""Parameters, operations and bytes of the looped decoder: layers of
grouped-query attention (`*`) and a dense SwiGLU (`F`), every mixer between
two norms, the stack run `total_ut_steps` times on one set of weights, the
final norm and the one head after every pass, an exit gate. From a
configuration's `model` sizes and the sequence length T.

The parameters are counted once whatever the passes; the operations once a
pass. The attention core is priced at the causal triangle T (T + 1) / 2 a
head, 4 operations a pair and channel, the same work whatever implements it.
A training step is priced at 3x its forward operations (forward plus a
backward of twice the forward): the replay of a recomputed block is never
counted, nor a kernel's own recomputation of the scores, nor what it
computes of a tile's masked pairs.
"""


def layers(m, kinds):
    return sum(m['hybrid_override_pattern'].count(k) for k in kinds)


def attention_matrix_params(m):
    """q and out [d, H dh], k and v [d, KV dh]."""
    d, dh = m['hidden_size'], m['head_dim']
    return d * dh * 2 * (m['num_attention_heads']
                         + m['num_key_value_heads'])


def ff_params(m):
    """gate, up and down."""
    return 3 * m['hidden_size'] * m['intermediate_size']


def layer_params(m):
    """One published layer: attention, SwiGLU and their four norms."""
    return attention_matrix_params(m) + ff_params(m) + 4 * m['hidden_size']


def gate_params(m):
    """The exit gate: one output with a bias."""
    return m['hidden_size'] + 1


def total_params(m):
    """Every residual step has two norms; embedding and head are two
    matrices; the final norm; the gate."""
    d = m['hidden_size']
    return (2 * m['vocab_rows'] * d
            + layers(m, '*') * attention_matrix_params(m)
            + layers(m, 'F') * ff_params(m)
            + 2 * len(m['hybrid_override_pattern']) * d + d + gate_params(m))


def visible_pairs(seq):
    """(query, key) pairs a head computes: the causal triangle."""
    return seq * (seq + 1) // 2


def core_flops(m, seq):
    """Scores and weighted sum of one layer's forward over one sequence,
    every query head, one pass."""
    return m['num_attention_heads'] * visible_pairs(seq) * 4 * m['head_dim']


def core_train_flops(m, seq, launches):
    """`launches`: forward launches, one a layer and pass."""
    return 3 * core_flops(m, seq) * launches


def core_bytes(m, seq, launches):
    """Each tensor once over `seq` positions, in bfloat16 but the float32
    statistics: q and o at the query heads, k and v at the key-value heads,
    the log-sum-exp forward; q, k, v, o, do and the log-sum-exp read, dq, dk,
    dv written backward."""
    dh = m['head_dim']
    h, kv = m['num_attention_heads'], m['num_key_value_heads']
    forward = 2 * seq * dh * (2 * h + 2 * kv) + 4 * seq * h
    backward = 2 * seq * dh * (4 * h + 4 * kv) + 4 * seq * h
    return launches * (forward + backward)


def pass_flops(m, seq):
    """One pass of the stack over one sequence, forward: the layers'
    products and cores."""
    products = 2 * (layers(m, '*') * attention_matrix_params(m)
                    + layers(m, 'F') * ff_params(m))
    return seq * products + layers(m, '*') * core_flops(m, seq)


def exit_flops(m, seq):
    """One pass's exit, forward: the head over the rows held (the gate's
    one column is left out)."""
    return seq * 2 * m['hidden_size'] * m['vocab_rows']


def forward_flops(m, seq):
    """One sequence's forward: every pass and its exit."""
    return m['total_ut_steps'] * (pass_flops(m, seq) + exit_flops(m, seq))


def train_step_flops(m, seq):
    return 3 * forward_flops(m, seq)
