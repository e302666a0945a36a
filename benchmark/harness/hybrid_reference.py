"""The plain reference of the hybrid decoder (layers by a pattern string:
Mamba-2 state-space mixers, two-matrix held experts, grouped-query
attention): forward, loss and gradient in straightforward `jax.numpy`,
float32, every product at `highest` precision. It imports nothing of the
program; from `lm_reference.py` it takes what is the same mathematics
(RMSNorm, the router, the chunked cross-entropy, Adam, the operand rounding
of the control). It is given the program's seeded parameter tree (the names
are flax's) and the same tokens.

    layer       h <- h + Mixer(RMSNorm(h)), by the pattern's letter
    M           [z | xBC | dt] = u W_in; xBC <- silu(conv4(xBC) + b);
                [x | B | C] = xBC; dt = softplus(dt + dt_bias);
                A = -exp(A_log); per head, B and C of the head's group:
                y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s
                      + D x_t;
                y <- GroupRMSNorm(y silu(z)); out = y W_out
    E           s = sigmoid(x Wr); top-k of s + b; w = scale s / (sum + 1e-20);
                sum over the experts HELD HERE of w_e relu(x U_e)^2 D_e
                + relu(x U_s)^2 D_s
    *           q = u Wq (H heads), k, v = u Wk, u Wv (KV heads, each shared by
                H / KV query heads); softmax(q k^T / sqrt(head_dim)), causal,
                no rotation; out = o Wo
    loss        CE(head(RMSNorm(h)), token t + 1), a mean over valid positions

The scan is NOT the program's chunked algorithm: it is the recurrence written
out as one masked product over the whole sequence (`scan_masked`), taken in
blocks of queries, each recomputed in the backward pass, so that 8,192 tokens
fit; the exponent is a difference of cumulative sums over the sequence
(float32: about 1e-4 of a decay at 8,192 tokens, far under what the cell's
limits hold). `scan_recurrence` is the recurrence itself, step by step, for
the tests that hold both forms to it.
"""
import jax
import jax.numpy as jnp

from . import lm_reference as lm

FP8_E4M3 = lm.FP8_E4M3


def scan_recurrence(x, dt, a, b, c, d):
    """x [T, H, P], dt [T, H], a [H], b, c [T, G, N], d [H] -> y [T, H, P]:
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + d x_t, one
    step at a time from S = 0."""
    h, p = x.shape[1:]
    rep = h // b.shape[1]

    def step(state, row):
        x_t, dt_t, b_t, c_t = row
        b_t, c_t = (jnp.repeat(v, rep, axis=0) for v in (b_t, c_t))  # [H, N]
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum('hpn,hn->hp', state, c_t) + d[:, None] * x_t

    zero = jnp.zeros((h, p, b.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (x, dt, b, c))[1]


def scan_masked(x, dt, a, b, c, d, block):
    """The same as one masked product, by blocks of `block` queries against
    every token at or before them."""
    t, h, _ = x.shape
    rep = h // b.shape[1]
    cum = jnp.cumsum(dt * a, axis=0)                      # [T, H]
    xd = x * dt[..., None]

    bq = min(block, t)

    # the whole arrays go in and are cut inside: what a recomputed block
    # keeps for the backward pass is then a reference, not a copy of a prefix
    def rows(c, cum, b, xd, q0):
        hi = min(q0 + bq, t)
        c_i, cum_i = c[q0:hi], cum[q0:hi]
        b_j, cum_j, xd_j = b[:hi], cum[:hi], xd[:hi]
        s = jnp.repeat(jnp.einsum('ign,jgn->gij', c_i, b_j), rep, axis=0)
        allowed = jnp.arange(hi)[None, :] <= jnp.arange(q0, hi)[:, None]
        decay = jnp.exp(jnp.where(
            allowed[None], cum_i.T[:, :, None] - cum_j.T[:, None, :],
            -jnp.inf))                                    # [H, q, keys]
        return jnp.einsum('hij,jhp->ihp', s * decay, xd_j)

    rows = jax.checkpoint(rows, static_argnums=(4,))
    y = jnp.concatenate(
        [rows(c, cum, b, xd, i) for i in range(0, t, bq)], axis=0)
    return y + d[:, None] * x


def causal_conv(x, kernel, bias):
    """x [T, C], kernel [K, C]: tap K - 1 reads the token itself, tap
    K - 1 - lag the token `lag` places back (zero before the first)."""
    t, c = x.shape
    k = kernel.shape[0]
    y = jnp.zeros_like(x) + bias
    for lag in range(k):
        back = jnp.concatenate((jnp.zeros((lag, c), x.dtype), x[:t - lag]))
        y = y + kernel[k - 1 - lag] * back
    return y


def mamba(p, u, m, R, block):
    """u [T, d] -> [T, d]."""
    t = u.shape[0]
    h, hp, n, g = (m['mamba_num_heads'], m['mamba_head_dim'],
                   m['ssm_state_size'], m['n_groups'])
    inner, gn = h * hp, g * n
    zxbcdt = u @ R(p['in_proj']['kernel'])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * gn],
                  zxbcdt[:, 2 * inner + 2 * gn:])
    bias = R(p['conv']['bias']) if m['use_conv_bias'] else 0.0
    xbc = jax.nn.silu(causal_conv(xbc, R(p['conv']['kernel']), bias))
    x = xbc[:, :inner].reshape(t, h, hp)
    b = xbc[:, inner:inner + gn].reshape(t, g, n)
    c = xbc[:, inner + gn:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + R(p['dt_bias']))
    y = scan_masked(x, dt, -jnp.exp(R(p['A_log'])), b, c, R(p['D']), block)
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + m['layer_norm_epsilon'])
    return (y.reshape(t, inner) * R(p['gate_norm']['scale'])) \
        @ R(p['out_proj']['kernel'])


def attention(p, x, m, R, block):
    """x [T, d] -> [T, d]; no rotation."""
    t = x.shape[0]
    h, kv, dh = (m['num_attention_heads'], m['num_key_value_heads'],
                 m['head_dim'])
    q = (x @ R(p['q']['kernel'])).reshape(t, kv, h // kv, dh)
    k = (x @ R(p['k']['kernel'])).reshape(t, kv, dh)
    v = (x @ R(p['v']['kernel'])).reshape(t, kv, dh)

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


def squared_relu(up, down, x):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def expert_layer(p, x, m, R, held=None):
    """x [N, d] -> (out, chosen). `held`: the global ids of the experts whose
    part is computed, p['experts_*'][i] being expert held[i]; by default the
    share of m['expert_rank']."""
    chosen, w = lm.route(p, x, m, R)
    if held is None:
        first = m['expert_rank'] * m['experts_held']
        held = range(first, first + m['experts_held'])

    @jax.checkpoint
    def one(up, down, w_e):
        return w_e[:, None] * squared_relu(R(up), R(down), x)

    out = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = out + one(p['experts_up'][i], p['experts_down'][i], w_e)
    if 'shared' in p:
        s = p['shared']
        out = out + squared_relu(R(s['up']['kernel']), R(s['down']['kernel']),
                                 x)
    return out, chosen


def layer(p, h, m, R, attn_block, ssm_block):
    """One layer on h [T, d] -> (h, chosen or None)."""
    u = lm.rms_norm(h, R(p['pre_norm']['scale']), m['layer_norm_epsilon'])
    if 'ssm' in p:
        return h + mamba(p['ssm'], u, m, R, ssm_block), None
    if 'attn' in p:
        return h + attention(p['attn'], u, m, R, attn_block), None
    out, chosen = expert_layer(p['moe'], u, m, R)
    return h + out, chosen


def hidden_states(params, tokens, m, R, attn_block, ssm_block, remat=True):
    """tokens [T] -> (the head's normed input [T, d], [chosen per expert
    layer])."""
    one = jax.checkpoint(layer, static_argnums=(2, 3, 4, 5)) if remat \
        else layer
    m = lm._Static(m)
    h = R(params['embedding']['embedding'])[tokens]
    chosen = []
    for i in range(len(m['hybrid_override_pattern'])):
        h, c = one(params[f'blocks_{i}'], h, m, R, attn_block, ssm_block)
        chosen += [c] if c is not None else []
    return lm.rms_norm(h, R(params['final_norm']['scale']),
                       m['layer_norm_epsilon']), chosen


def loss(params, tokens, m, attn_block=1024, ssm_block=256, chunk=1024,
         remat=True, operand_bits=None):
    """tokens [B, T] -> (loss, chosen [layers, B * T, k])."""
    R = lm._rounder(operand_bits)
    with jax.default_matmul_precision('highest'):
        b, t = tokens.shape
        rows = [hidden_states(params, tokens[i], m, R, attn_block, ssm_block,
                              remat) for i in range(b)]
        cat = lambda xs: jnp.concatenate(xs, axis=0)   # noqa: E731
        total = lm.cross_entropy(
            cat([r[0] for r in rows]), R(params['head']['kernel']),
            jnp.roll(tokens, -1, axis=1).reshape(-1),
            jnp.tile(jnp.arange(t), b) < t - 1, chunk)
        chosen = jnp.stack([cat([r[1][i] for r in rows])
                            for i in range(len(rows[0][1]))]) \
            if rows[0][1] else None
    return total, chosen


adam_update = lm.adam_update
