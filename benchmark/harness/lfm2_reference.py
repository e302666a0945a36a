"""The plain reference of the decoder whose layers are an operator and a
feed-forward (gated short convolutions or grouped-query attention with q/k
norms and rotation; a dense or an expert feed-forward; tied head): forward,
loss and gradient in straightforward `jax.numpy`, float32, every product at
`highest` precision. It imports nothing of the program; from
`lm_reference.py` it takes what is the same mathematics (RMSNorm, the
rotation of half-split pairs, SwiGLU, the chunked cross-entropy, Adam, the
operand rounding of the control). It is given the program's seeded parameter
tree (the names are flax's: one subtree a residual step) and the same tokens.

    step        h <- h + Op(RMSNorm(h)), by the subtree's one operator
    conv        [B ; C ; X] = u W_in (thirds, in this order);
                z_t = sum_{j=0..K-1} w[j] (B * X)_{t-(K-1)+j}, zeros before
                t = 0, no bias, no activation; out = (C * z) W_out
    attn        q = u Wq (H heads), k, v = u Wk, u Wv (KV heads, each shared by
                H / KV query heads); q, k <- RMSNorm over each head's channels
                (one scale each); q, k <- rotation(0..T-1, rope_theta, all
                channels, pairs (i, i + d/2)); softmax(q k^T / sqrt(d)),
                causal; out = o Wo
    mlp         (silu(u W1) * (u W3)) W2
    moe         s = sigmoid(u Wr); top-k of s + b; w = scale s / (sum + 1e-6)
                (the published normaliser); sum over the experts HELD HERE of
                w_e (silu(u W1_e) * (u W3_e)) W2_e; no shared expert
    loss        CE(RMSNorm(h) E^T, token t + 1), E the embedding (tied head),
                a mean over valid positions

The convolution is written as K shifted products; attention is taken in
blocks of queries against every key at or before them, each block recomputed
in the backward pass, so that 32 heads at 8,192 tokens fit.
"""
import jax
import jax.numpy as jnp

from . import lm_reference as lm

FP8_E4M3 = lm.FP8_E4M3
ROUTER_NORM_EPS = 1e-6


def short_conv(p, u, R):
    """u [T, d] -> [T, d]."""
    t, d = u.shape
    bcx = u @ R(p['in_proj']['kernel'])
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    taps = R(p['conv']['kernel'])                       # [K, d]
    k = taps.shape[0]
    bx = b * x
    z = jnp.zeros_like(bx)
    for lag in range(k):       # tap K - 1 - lag reads the token `lag` back
        back = jnp.concatenate((jnp.zeros((lag, d), bx.dtype), bx[:t - lag]))
        z = z + taps[k - 1 - lag] * back
    return (c * z) @ R(p['out_proj']['kernel'])


def attention(p, x, m, R, block):
    """x [T, d] -> [T, d]."""
    t = x.shape[0]
    h, kv, dh = (m['num_attention_heads'], m['num_key_value_heads'],
                 m['head_dim'])
    eps = m['layer_norm_epsilon']
    q = (x @ R(p['q']['kernel'])).reshape(t, h, dh)
    k = (x @ R(p['k']['kernel'])).reshape(t, kv, dh)
    v = (x @ R(p['v']['kernel'])).reshape(t, kv, dh)
    pos = jnp.arange(t, dtype=jnp.float32)
    q = lm.rotate(lm.rms_norm(q, R(p['q_norm']['scale']), eps), pos,
                  m['rope_theta'])
    k = lm.rotate(lm.rms_norm(k, R(p['k_norm']['scale']), eps), pos,
                  m['rope_theta'])
    q = q.reshape(t, kv, h // kv, dh)

    @jax.checkpoint
    def rows(q_i, k_j, v_j, q0):
        s = jnp.einsum('qgrd,kgd->grqk', q_i, k_j) * dh ** -0.5
        allowed = jnp.arange(k_j.shape[0])[None, :] \
            <= q0 + jnp.arange(q_i.shape[0])[:, None]
        s = jnp.where(allowed[None, None], s, -jnp.inf)
        return jnp.einsum('grqk,kgd->qgrd', jax.nn.softmax(s, axis=-1), v_j)

    bq = min(block, t)
    out = jnp.concatenate(
        [rows(q[i:i + bq], k[:i + bq], v[:i + bq], i)
         for i in range(0, t, bq)], axis=0)
    return out.reshape(t, h * dh) @ R(p['out']['kernel'])


def dense_ff(p, x, R):
    return lm.swiglu(R(p['gate']['kernel']), R(p['up']['kernel']),
                     R(p['down']['kernel']), x)


def route(p, x, m, R):
    """(chosen [N, k], weights [N, k]) over all the router's outputs."""
    s = jax.nn.sigmoid(x @ R(p['router']['kernel']))
    _, chosen = jax.lax.top_k(
        s + jax.lax.stop_gradient(p['correction_bias']),
        m['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m['norm_topk_prob']:
        w = w / (w.sum(axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    return chosen, m['routed_scaling_factor'] * w


def expert_layer(p, x, m, R, held=None):
    """x [N, d] -> (out, chosen). `held`: the global ids of the experts whose
    part is computed, p['experts_*'][i] being expert held[i]; by default the
    share of m['expert_rank']."""
    chosen, w = route(p, x, m, R)
    if held is None:
        first = m['expert_rank'] * m['experts_held']
        held = range(first, first + m['experts_held'])

    @jax.checkpoint
    def one(gate, up, down, w_e):
        return w_e[:, None] * lm.swiglu(R(gate), R(up), R(down), x)

    out = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = out + one(p['experts_gate'][i], p['experts_up'][i],
                        p['experts_down'][i], w_e)
    return out, chosen


def step(p, h, m, R, attn_block):
    """One residual step on h [T, d] -> (h, chosen or None)."""
    u = lm.rms_norm(h, R(p['pre_norm']['scale']), m['layer_norm_epsilon'])
    if 'conv' in p:
        return h + short_conv(p['conv'], u, R), None
    if 'attn' in p:
        return h + attention(p['attn'], u, m, R, attn_block), None
    if 'mlp' in p:
        return h + dense_ff(p['mlp'], u, R), None
    out, chosen = expert_layer(p['moe'], u, m, R)
    return h + out, chosen


def hidden_states(params, tokens, m, R, attn_block, remat=True):
    """tokens [T] -> (the head's normed input [T, d], [chosen per expert
    layer])."""
    one = jax.checkpoint(step, static_argnums=(2, 3, 4)) if remat else step
    m = lm._Static(m)
    h = R(params['embedding']['embedding'])[tokens]
    chosen = []
    for i in range(len(m['hybrid_override_pattern'])):
        h, c = one(params[f'blocks_{i}'], h, m, R, attn_block)
        chosen += [c] if c is not None else []
    return lm.rms_norm(h, R(params['final_norm']['scale']),
                       m['layer_norm_epsilon']), chosen


def loss(params, tokens, m, attn_block=512, chunk=1024, remat=True,
         operand_bits=None):
    """tokens [B, T] -> (loss, chosen [layers, B * T, k])."""
    R = lm._rounder(operand_bits)
    with jax.default_matmul_precision('highest'):
        b, t = tokens.shape
        rows = [hidden_states(params, tokens[i], m, R, attn_block, remat)
                for i in range(b)]
        cat = lambda xs: jnp.concatenate(xs, axis=0)   # noqa: E731
        total = lm.cross_entropy(
            cat([r[0] for r in rows]), R(params['embedding']['embedding']).T,
            jnp.roll(tokens, -1, axis=1).reshape(-1),
            jnp.tile(jnp.arange(t), b) < t - 1, chunk)
        chosen = jnp.stack([cat([r[1][i] for r in rows])
                            for i in range(len(rows[0][1]))]) \
            if rows[0][1] else None
    return total, chosen


adam_update = lm.adam_update
