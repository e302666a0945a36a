"""The plain reference of the looped decoder: a stack of sandwich-normed
layers run several times on one set of weights, every pass closed by the
final norm and read by the one head and by an exit gate, trained over all
its exits. Forward, loss and gradient in straightforward `jax.numpy`,
float32, every product at `highest` precision. It imports nothing of the
program; from `lm_reference.py` it takes what is the same mathematics
(RMSNorm, the rotation of half-split pairs, Adam, the operand rounding of
the control). It is given the program's seeded parameter tree (the names are
flax's: one subtree a residual step, a published layer two of them) and the
same tokens.

    h_0 = Embed(x)
    for t = 1..P:                      one pass; the SAME parameters each t
        a = h_{t-1}
        for each layer:
            a <- a + N2( Attn( N1(a) ) )
            a <- a + N4( SwiGLU( N3(a) ) )
        g_t = N_f(a);  h_t = g_t       the final norm closes every pass and
                                       its output is what the next pass reads
        z_t = g_t W_head               this pass's exit
        lam_t = sigmoid(g_t . w_e + b_e)          t = 1..P-1
    Attn(u): q, k, v = u Wq, u Wk, u Wv (no bias, no q/k norms); q, k rotated
        at positions 0..T-1 (pairs (i, i + d/2)); softmax(q k^T / sqrt(d))
        over the keys at or before the query; o Wo
    SwiGLU(u) = (silu(u Wg) * (u Wu)) Wd
    N(u) = u / sqrt(mean(u^2) + eps) * scale

    a token n with a next token:
        l_t[n] = logsumexp(z_t[n]) - z_t[n][x_{n+1}]
        p_t = lam_t prod_{s<t} (1 - lam_s)  (t < P)
        p_P = prod_{s<P} (1 - lam_s)
        loss = mean_n [ sum_t p_t[n] l_t[n]  -  beta H(p[n]) ],
        H(p) = - sum_t p_t log p_t     (log p from log-sigmoids)

The layers are taken as a list a pass (`passes`: by default the tree's one
set, P times), so that a test can hand it P untied copies and see that the
shared layers' gradient is the sum of theirs.

Attention is an explicit mask [block, T] over blocks of queries, one after
another (`lax.map`: written as a Python loop the compiler may hold every
block's scores at once), each block against every key and recomputed in the
backward pass; every residual step is recomputed too, and the head goes by
chunks of tokens, so that four passes at 8,192 positions fit.
"""
import jax
import jax.numpy as jnp

from . import lm_reference as lm

FP8_E4M3 = lm.FP8_E4M3


def attention(p, x, m, R, block):
    """x [T, d] -> [T, d]."""
    t = x.shape[0]
    h, kv, dh = (m['num_attention_heads'], m['num_key_value_heads'],
                 m['head_dim'])
    pos = jnp.arange(t, dtype=jnp.float32)
    q = lm.rotate((x @ R(p['q']['kernel'])).reshape(t, h, dh), pos,
                  m['rope_theta'])
    k = lm.rotate((x @ R(p['k']['kernel'])).reshape(t, kv, dh), pos,
                  m['rope_theta'])
    v = (x @ R(p['v']['kernel'])).reshape(t, kv, dh)
    q = q.reshape(t, kv, h // kv, dh)

    @jax.checkpoint
    def rows(q_i, rows_i, k, v):
        s = jnp.einsum('qgrd,kgd->grqk', q_i, k) * dh ** -0.5
        seen = jnp.arange(t)[None, :] <= rows_i[:, None]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum('grqk,kgd->qgrd', jax.nn.softmax(s, axis=-1), v)

    bq = min(block, t)
    out = jax.lax.map(
        lambda xs: rows(xs[0], xs[1], k, v),
        (q.reshape(t // bq, bq, kv, h // kv, dh),
         jnp.arange(t).reshape(t // bq, bq)))
    return out.reshape(t, h * dh) @ R(p['out']['kernel'])


def step(p, h, m, R, attn_block, kind):
    """One residual step on h [T, d], its mixer between two norms."""
    eps = m['layer_norm_epsilon']
    u = lm.rms_norm(h, R(p['pre_norm']['scale']), eps)
    if kind == '*':
        out = attention(p['attn'], u, m, R, attn_block)
    else:
        f = p['mlp']
        out = lm.swiglu(R(f['gate']['kernel']), R(f['up']['kernel']),
                        R(f['down']['kernel']), u)
    return h + lm.rms_norm(out, R(p['post_norm']['scale']), eps)


def shared_passes(params, m):
    """The tree's one set of residual steps, once a pass."""
    one = [params[f'blocks_{i}']
           for i in range(len(m['hybrid_override_pattern']))]
    return [one] * m['total_ut_steps']


def hidden_states(params, passes, tokens, m, R, attn_block, remat=True):
    """tokens [T] -> [every pass's normed state [T, d]]. `passes`: a list a
    pass of the residual steps' subtrees."""
    one = jax.checkpoint(step, static_argnums=(2, 3, 4, 5)) if remat else step
    m = lm._Static(m)
    assert set(m['hybrid_override_pattern']) <= set('*F'), m
    h = R(params['embedding']['embedding'])[tokens]
    exits = []
    for steps in passes:
        for kind, p in zip(m['hybrid_override_pattern'], steps):
            h = one(p, h, m, R, attn_block, kind)
        h = lm.rms_norm(h, R(params['final_norm']['scale']),
                        m['layer_norm_epsilon'])
        exits.append(h)
    return exits


def row_nll(h, kernel, targets, chunk):
    """h [N, d] -> logsumexp(h kernel) - (h kernel)[target], a row [N]."""
    @jax.checkpoint
    def one(hc, tc):
        logits = hc @ kernel
        return jax.nn.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]

    return jnp.concatenate([one(h[i:i + chunk], targets[i:i + chunk])
                            for i in range(0, h.shape[0], chunk)])


def exit_log_probabilities(gate):
    """gate [P - 1, N], the logits of lam_1..lam_{P-1} -> log p [P, N]: a
    token leaves at pass t if it has not left before and the gate says so;
    the last pass takes what is left."""
    log_p, stayed = [], jnp.zeros_like(gate[0])
    for g in gate:
        log_p.append(stayed + jax.nn.log_sigmoid(g))
        stayed = stayed + jax.nn.log_sigmoid(-g)
    return jnp.stack(log_p + [stayed])


def loss(params, tokens, m, beta=0.1, attn_block=256, chunk=1024, remat=True,
         operand_bits=None, passes=None):
    """tokens [B, T] -> (loss, {'loss_ut' [P], 'exit_share' [P],
    'exit_entropy'}). `passes`: the layers a pass, where they are not the
    tree's own (`shared_passes`)."""
    R = lm._rounder(operand_bits)
    with jax.default_matmul_precision('highest'):
        b, t = tokens.shape
        if passes is None:
            passes = shared_passes(params, m)
        exits = [hidden_states(params, passes, tokens[i], m, R, attn_block,
                               remat) for i in range(b)]
        # [P, B * T, d]
        g = jnp.stack([jnp.concatenate([e[i] for e in exits])
                       for i in range(len(passes))])
        gate = g[:-1] @ R(params['exit_gate']['kernel'])[:, 0] \
            + R(params['exit_gate']['bias'])[0]
        log_p = exit_log_probabilities(gate)
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        kernel = R(params['head']['kernel'])
        targets = jnp.roll(tokens, -1, axis=1).reshape(-1)
        nll = jnp.stack([row_nll(g_t, kernel, targets, chunk) for g_t in g])
        valid = jnp.tile(jnp.arange(t), b) < t - 1
        n_valid = jnp.maximum(jnp.sum(valid), 1)

        def mean(rows):     # over the tokens that have a next token
            return jnp.sum(jnp.where(valid, rows, 0.0), axis=-1) / n_valid

        total = mean(jnp.sum(p * nll, axis=0) - beta * entropy)
        aux = dict(loss_ut=mean(nll), exit_share=mean(p),
                   exit_entropy=mean(entropy))
    return total, aux


adam_update = lm.adam_update
