"""The plain reference of the decoder whose global attention layers carry no
rotation and whose other layers slide a window with one, each followed by
ReGLU experts routed by the ATTENTION step's input: forward, loss and
gradient in straightforward `jax.numpy`, float32, every product at `highest`
precision. It imports nothing of the program; from `lm_reference.py` it takes
what is the same mathematics (RMSNorm, the rotation of half-split pairs, the
chunked cross-entropy, Adam, the operand rounding of the control). It is
given the program's seeded parameter tree (the names are flax's: one subtree
a residual step, a published layer two of them) and the same tokens.

    layer       u  = RMSNorm_1(h)
                r  = u Wr                       the router's logits, from the
                                                attention step's input
                h' = h + Attn(u)
                x  = RMSNorm_2(h')
                h''= h' + sum over the chosen experts HELD HERE of
                     w_e (relu(x Wg_e) * (x Wu_e)) Wd_e      no shared expert
    attn        q = u Wq (H heads), k, v = u Wk, u Wv (KV heads, each shared
                by H / KV query heads); no q/k norms, no biases;
                  global  (the pattern's `*`): no rotation; key j visible to
                          query i iff j <= i
                  sliding (`W`): q, k <- rotation(0..T-1, rope_theta, all
                          channels, pairs (i, i + d/2)); key j visible iff
                          0 <= i - j < window (the token itself counts)
                softmax(q k^T / sqrt(d)) over the visible keys; out = o Wo
    route       p = softmax(r) over all the router's outputs; top-k of p + b
                (b the correction bias: zero is the published arithmetic);
                w = the chosen p divided by their sum, which equals the
                published softmax over the k chosen logits
    loss        CE(RMSNorm(h) W_head, token t + 1) over the vocabulary rows
                held here, a mean over valid positions

Departures from the published description, each for the chip's share and
stated in the configuration file: the experts held here are a slice of the
router's outputs and the others' part is left out (the partial sum of an
expert-parallel rank); embedding, head and loss are over a slice of the
vocabulary; the correction bias (published: none) enters the choice only.

Attention is an explicit mask [block, T] over blocks of queries, one after
another (`lax.map`: written as a Python loop the compiler may hold every
block's scores at once), each block against every key and recomputed in the
backward pass, so that 28 heads at 16,384 positions fit.
"""
import jax
import jax.numpy as jnp

from . import lm_reference as lm

FP8_E4M3 = lm.FP8_E4M3
ATTENTION = '*W'


def visible(rows, t, window):
    """[len(rows), T] bool: query `rows[i]` sees key j; `window` 0: every
    key at or before it."""
    i, j = rows[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    return seen & (i - j < window) if window else seen


def attention(p, x, m, R, block, sliding):
    """x [T, d] -> [T, d]."""
    t = x.shape[0]
    h, kv, dh = (m['num_attention_heads'], m['num_key_value_heads'],
                 m['head_dim'])
    q = (x @ R(p['q']['kernel'])).reshape(t, h, dh)
    k = (x @ R(p['k']['kernel'])).reshape(t, kv, dh)
    v = (x @ R(p['v']['kernel'])).reshape(t, kv, dh)
    if sliding:
        pos = jnp.arange(t, dtype=jnp.float32)
        q = lm.rotate(q, pos, m['sliding_rope_theta'])
        k = lm.rotate(k, pos, m['sliding_rope_theta'])
    window = m['sliding_window_size'] if sliding else 0
    q = q.reshape(t, kv, h // kv, dh)

    @jax.checkpoint
    def rows(q_i, rows_i, k, v):
        s = jnp.einsum('qgrd,kgd->grqk', q_i, k) * dh ** -0.5
        s = jnp.where(visible(rows_i, t, window)[None, None], s, -jnp.inf)
        return jnp.einsum('grqk,kgd->qgrd', jax.nn.softmax(s, axis=-1), v)

    bq = min(block, t)
    out = jax.lax.map(
        lambda xs: rows(xs[0], xs[1], k, v),
        (q.reshape(t // bq, bq, kv, h // kv, dh),
         jnp.arange(t).reshape(t // bq, bq)))
    return out.reshape(t, h * dh) @ R(p['out']['kernel'])


def reglu(gate, up, down, x):
    return (jax.nn.relu(x @ gate) * (x @ up)) @ down


def route(p, routed_by, m, R):
    """(chosen [N, k], weights [N, k]) over all the router's outputs, from
    the rows `routed_by`."""
    s = jax.nn.softmax(routed_by @ R(p['router']['kernel']), axis=-1)
    _, chosen = jax.lax.top_k(
        s + jax.lax.stop_gradient(p['correction_bias']),
        m['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / w.sum(axis=-1, keepdims=True)


def expert_layer(p, x, routed_by, m, R, held=None):
    """x [N, d] -> (out, chosen). `held`: the global ids of the experts whose
    part is computed, p['experts_*'][i] being expert held[i]; by default the
    share of m['expert_rank']."""
    chosen, w = route(p, routed_by, m, R)
    if held is None:
        first = m['expert_rank'] * m['experts_held']
        held = range(first, first + m['experts_held'])

    @jax.checkpoint
    def one(gate, up, down, w_e):
        return w_e[:, None] * reglu(R(gate), R(up), R(down), x)

    out = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = out + one(p['experts_gate'][i], p['experts_up'][i],
                        p['experts_down'][i], w_e)
    return out, chosen


def step(p, h, routed_by, m, R, attn_block, kind):
    """One residual step on h [T, d] -> (h, its normed input, chosen or
    None); an expert step is routed by `routed_by`, the normed input of the
    attention step before it."""
    u = lm.rms_norm(h, R(p['pre_norm']['scale']), m['layer_norm_epsilon'])
    if kind in ATTENTION:
        return h + attention(p['attn'], u, m, R, attn_block, kind == 'W'), \
            u, None
    out, chosen = expert_layer(p['moe'], u, routed_by, m, R)
    return h + out, u, chosen


def hidden_states(params, tokens, m, R, attn_block, remat=True):
    """tokens [T] -> (the head's normed input [T, d], [chosen per expert
    layer])."""
    one = jax.checkpoint(step, static_argnums=(3, 4, 5, 6)) if remat else step
    m = lm._Static(m)
    h = R(params['embedding']['embedding'])[tokens]
    chosen, routed_by = [], None
    for i, kind in enumerate(m['hybrid_override_pattern']):
        h, u, c = one(params[f'blocks_{i}'], h,
                      None if kind in ATTENTION else routed_by, m, R,
                      attn_block, kind)
        if kind in ATTENTION:
            routed_by = u
        else:
            chosen.append(c)
    return lm.rms_norm(h, R(params['final_norm']['scale']),
                       m['layer_norm_epsilon']), chosen


def loss(params, tokens, m, attn_block=256, chunk=1024, remat=True,
         operand_bits=None):
    """tokens [B, T] -> (loss, chosen [layers, B * T, k])."""
    R = lm._rounder(operand_bits)
    with jax.default_matmul_precision('highest'):
        b, t = tokens.shape
        rows = [hidden_states(params, tokens[i], m, R, attn_block, remat)
                for i in range(b)]
        cat = lambda xs: jnp.concatenate(xs, axis=0)   # noqa: E731
        total = lm.cross_entropy(
            cat([r[0] for r in rows]), R(params['head']['kernel']),
            jnp.roll(tokens, -1, axis=1).reshape(-1),
            jnp.tile(jnp.arange(t), b) < t - 1, chunk)
        chosen = jnp.stack([cat([r[1][i] for r in rows])
                            for i in range(len(rows[0][1]))])
    return total, chosen


adam_update = lm.adam_update
