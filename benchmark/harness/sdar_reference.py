"""The plain reference of the decoder trained by diffusion over blocks
(layers of grouped-query attention with q/k norms and rotation, then
softmax-routed experts; untied head): forward, loss and gradient in
straightforward `jax.numpy`, float32, every product at `highest` precision.
It imports nothing of the program; from `lm_reference.py` it takes what is
the same mathematics (RMSNorm, the rotation of half-split pairs, SwiGLU,
Adam, the operand rounding of the control). It is given the program's seeded
parameter tree (the names are flax's: one subtree a residual step), the same
tokens and the same noise.

    input       a sequence x of L tokens, its noised copy x~ (MASK where m_i)
                and the weights m_i / t; the model reads [x~ ; x], 2 L
                positions, at rotary positions [0..L-1 ; 0..L-1]
    mask        b(i) = i // block_length; query q sees key k exactly when
                  noised -> noised:  b(q) = b(k)
                  noised -> clean:   b(k) < b(q)
                  clean  -> clean:   b(k) <= b(q)
                  clean  -> noised:  never
                written here from each position's (stream, block) pair
    step        h <- h + Op(RMSNorm(h)), by the subtree's one operator
    attn        q = u Wq (H heads), k, v = u Wk, u Wv (KV heads, each shared
                by H / KV query heads); q, k <- RMSNorm over each head's
                channels (one scale each); q, k <- rotation(positions,
                rope_theta, all channels, pairs (i, i + d/2));
                softmax(q k^T / sqrt(d)) over the visible keys; out = o Wo
    moe         p = softmax(u Wr) over all the router's outputs; top-k of
                p + b; w = scale p / (sum of the chosen + 1e-20); sum over the
                experts HELD HERE of w_e (silu(u W1_e) * (u W3_e)) W2_e; no
                shared expert
    loss        (1 / (B L)) sum_i (m_i / t) [logsumexp(RMSNorm(h~_i) W) -
                (RMSNorm(h~_i) W)[x_i]] over the noised stream's L positions,
                in place (no shift); the clean stream's last layer feeds
                nothing

Attention is taken in blocks of queries, one after another, against every
key of both streams under the mask, each block recomputed in the backward
pass, so that 32 heads at 16,384 positions fit.
"""
import jax
import jax.numpy as jnp

from . import lm_reference as lm

FP8_E4M3 = lm.FP8_E4M3
ROUTER_NORM_EPS = 1e-20


def stream_and_block(length, block_length):
    """Of each of the 2 L positions: (1 for the clean stream else 0, the
    block of its token)."""
    i = jnp.arange(2 * length)
    return i // length, (i % length) // block_length


def visible(length, block_length):
    """[2L, 2L] bool: row q sees column k."""
    stream, block = stream_and_block(length, block_length)
    sq, sk = stream[:, None], stream[None, :]
    bq, bk = block[:, None], block[None, :]
    return jnp.where(
        sk == 0, (sq == 0) & (bq == bk),
        jnp.where(sq == 0, bk < bq, bk <= bq))


def attention(p, x, m, R, block, positions, seen):
    """x [T, d] -> [T, d], T the 2 L positions; seen [T, T] bool."""
    t = x.shape[0]
    h, kv, dh = (m['num_attention_heads'], m['num_key_value_heads'],
                 m['head_dim'])
    eps = m['layer_norm_epsilon']
    q = (x @ R(p['q']['kernel'])).reshape(t, h, dh)
    k = (x @ R(p['k']['kernel'])).reshape(t, kv, dh)
    v = (x @ R(p['v']['kernel'])).reshape(t, kv, dh)
    q = lm.rotate(lm.rms_norm(q, R(p['q_norm']['scale']), eps), positions,
                  m['rope_theta'])
    k = lm.rotate(lm.rms_norm(k, R(p['k_norm']['scale']), eps), positions,
                  m['rope_theta'])
    q = q.reshape(t, kv, h // kv, dh)

    @jax.checkpoint
    def rows(q_i, seen_i, k, v):
        s = jnp.einsum('qgrd,kgd->grqk', q_i, k) * dh ** -0.5
        s = jnp.where(seen_i[None, None], s, -jnp.inf)
        return jnp.einsum('grqk,kgd->qgrd', jax.nn.softmax(s, axis=-1), v)

    # one block of queries after another (`lax.map`): written as a Python
    # loop the compiler may hold every block's [heads, block, T] scores at
    # once, 1 GiB each at 16,384 positions
    bq = min(block, t)
    out = jax.lax.map(
        lambda xs: rows(xs[0], xs[1], k, v),
        (q.reshape(t // bq, bq, kv, h // kv, dh),
         seen.reshape(t // bq, bq, t)))
    return out.reshape(t, h * dh) @ R(p['out']['kernel'])


def route(p, x, m, R):
    """(chosen [N, k], weights [N, k]) over all the router's outputs."""
    s = jax.nn.softmax(x @ R(p['router']['kernel']), axis=-1)
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


def step(p, h, m, R, attn_block, positions, seen):
    """One residual step on h [T, d] -> (h, chosen or None)."""
    u = lm.rms_norm(h, R(p['pre_norm']['scale']), m['layer_norm_epsilon'])
    if 'attn' in p:
        return h + attention(p['attn'], u, m, R, attn_block, positions,
                             seen), None
    out, chosen = expert_layer(p['moe'], u, m, R)
    return h + out, chosen


def hidden_states(params, tokens, noised, m, R, block_length, attn_block,
                  remat=True):
    """tokens, noised [L] -> (the head's normed input over the noised
    stream [L, d], [chosen [2 L, k] per expert layer])."""
    one = jax.checkpoint(step, static_argnums=(2, 3, 4)) if remat else step
    m = lm._Static(m)
    length = tokens.shape[0]
    positions = jnp.tile(jnp.arange(length, dtype=jnp.float32), 2)
    seen = visible(length, block_length)
    h = R(params['embedding']['embedding'])[jnp.concatenate((noised, tokens))]
    chosen = []
    for i in range(len(m['hybrid_override_pattern'])):
        h, c = one(params[f'blocks_{i}'], h, m, R, attn_block, positions,
                   seen)
        chosen += [c] if c is not None else []
    return lm.rms_norm(h[:length], R(params['final_norm']['scale']),
                       m['layer_norm_epsilon']), chosen


def weighted_nll(h, kernel, targets, weight, chunk):
    """sum over rows of weight * (logsumexp(h kernel) - (h kernel)[target])."""
    @jax.checkpoint
    def one(hc, tc, wc):
        logits = hc @ kernel
        nll = jax.nn.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(wc * nll)

    n = h.shape[0]
    return sum(one(h[i:i + chunk], targets[i:i + chunk], weight[i:i + chunk])
               for i in range(0, n, chunk))


def loss(params, tokens, noised, weight, m, block_length, attn_block=512,
         chunk=1024, remat=True, operand_bits=None):
    """tokens, noised [B, L] int, weight [B, L] float32 (m / t) -> (loss,
    chosen [layers, B * 2 L, k])."""
    R = lm._rounder(operand_bits)
    with jax.default_matmul_precision('highest'):
        b, length = tokens.shape
        rows = [hidden_states(params, tokens[i], noised[i], m, R,
                              block_length, attn_block, remat)
                for i in range(b)]
        cat = lambda xs: jnp.concatenate(xs, axis=0)   # noqa: E731
        total = weighted_nll(
            cat([r[0] for r in rows]), R(params['head']['kernel']),
            tokens.reshape(-1), weight.reshape(-1).astype(jnp.float32),
            chunk) / (b * length)
        chosen = jnp.stack([cat([r[1][i] for r in rows])
                            for i in range(len(rows[0][1]))]) \
            if rows[0][1] else None
    return total, chosen


adam_update = lm.adam_update
