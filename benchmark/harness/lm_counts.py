"""Parameters, operations and bytes of the token decoder, from a
configuration's `model` sizes and a count of (token, expert) pairs. A
training step is priced at 3x its forward operations (forward plus a backward
of twice the forward): the replay of a recomputed block is never counted.
Causal attention is priced at half the square."""


def attention_params(m):
    d, h = m['hidden_size'], m['num_attention_heads']
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    return (d * m['q_lora_rank'] + m['q_lora_rank'] * h * qk
            + d * (m['kv_lora_rank'] + m['qk_rope_head_dim'])
            + m['kv_lora_rank'] * h * (m['qk_nope_head_dim']
                                       + m['v_head_dim'])
            + h * m['v_head_dim'] * d)


def expert_params(m):
    """One SwiGLU of the routed width."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def expert_layers(m):
    """Expert layers of a step: the decoder's and the prediction block's."""
    return (m['num_hidden_layers'] - m['first_k_dense_replace']
            + m['num_nextn_predict_layers'])


def matrix_params(m):
    """{part: parameters in matrices}, norms and the correction bias left
    out: the table of the configuration's cut."""
    d = m['hidden_size']
    expert_block = (attention_params(m)
                    + m['n_shared_experts'] * expert_params(m)
                    + d * m['n_routed_experts']
                    + m['experts_held'] * expert_params(m))
    return {
        'embedding_and_head': 2 * m['vocab_rows'] * d,
        'dense_block': attention_params(m) + 3 * d * m['intermediate_size'],
        'expert_block': expert_block,
        'prediction_block': m['num_nextn_predict_layers']
        * (2 * d * d + expert_block),
    }


def vector_params(m):
    """Norm scales and the routers' correction biases."""
    d, mtp = m['hidden_size'], m['num_nextn_predict_layers']
    blocks = m['num_hidden_layers'] + mtp
    return (blocks * (2 * d + m['q_lora_rank'] + m['kv_lora_rank'])
            + expert_layers(m) * m['n_routed_experts'] + d + mtp * 3 * d)


def total_params(m):
    p = matrix_params(m)
    return (p['embedding_and_head']
            + m['first_k_dense_replace'] * p['dense_block']
            + (m['num_hidden_layers'] - m['first_k_dense_replace'])
            * p['expert_block'] + p['prediction_block'] + vector_params(m))


def attention_core_flops(m, seq):
    """Scores and weighted sum of one layer's forward over one sequence of
    `seq` tokens, causal (half the square)."""
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    return m['num_attention_heads'] * seq * seq * (qk + m['v_head_dim'])


def forward_flops(m, seq, pairs):
    """One sequence's forward. `pairs`: the (token, expert) pairs computed
    here, over all expert layers."""
    d = m['hidden_size']
    dense = m['first_k_dense_replace']
    blocks = m['num_hidden_layers'] + m['num_nextn_predict_layers']
    per_token = (
        blocks * 2 * attention_params(m)
        + dense * 2 * 3 * d * m['intermediate_size']
        + expert_layers(m) * 2 * (m['n_shared_experts'] * expert_params(m)
                                  + d * m['n_routed_experts'])
        + m['num_nextn_predict_layers'] * 2 * 2 * d * d
        + (1 + m['num_nextn_predict_layers']) * 2 * d * m['vocab_rows'])
    return (seq * per_token + 2 * pairs * expert_params(m)
            + blocks * attention_core_flops(m, seq))


def train_step_flops(m, seq, pairs):
    return 3 * forward_flops(m, seq, pairs)


def grouped_flops(m, pairs):
    """The grouped products of `pairs` pairs, forward, dX and dW: the same
    count whatever implements them."""
    return 3 * 2 * pairs * expert_params(m)


def grouped_bytes(m, pairs, layer_steps):
    """Each tensor of the grouped products once, at the width it is read or
    written with (bfloat16 operands, float32 results), forward and backward,
    over `layer_steps` launches of an expert layer."""
    d, w = m['hidden_size'], m['moe_intermediate_size']
    weights = layer_steps * m['experts_held'] * expert_params(m)
    forward = pairs * (2 * d + 2 * 4 * w + 2 * w + 4 * d) + 2 * weights
    backward = pairs * (2 * d + 4 * w + 2 * 2 * w + 4 * d) \
        + (2 + 4) * weights
    return forward + backward


def attention_core_train_flops(m, seq, layer_sequences):
    """Forward plus a backward of twice the forward (the kernel's own
    recomputation of the scores is not counted), over `layer_sequences`
    launches."""
    return 3 * attention_core_flops(m, seq) * layer_sequences


def attention_core_bytes(m, seq, layer_sequences):
    """q, k, v and the output once forward; q, k, v, output, its cotangent
    and the three gradients once backward, in bfloat16."""
    h = m['num_attention_heads']
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    v = m['v_head_dim']
    return layer_sequences * 2 * seq * h * (2 * qk + 2 * v
                                            + 2 * qk + 3 * v + 2 * qk + v)
