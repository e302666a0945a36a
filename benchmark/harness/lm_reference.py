"""The plain reference of the token decoder: forward, loss, gradient and Adam
in straightforward `jax.numpy`, float32, every product at `highest`
precision. It imports nothing of the program and nothing of the harness (the
tier-1 tests load this file by path). It is given the program's seeded
parameter tree (the names are flax's) and the same tokens.

    block       h <- h + Attn(RMSNorm(h));  h <- h + FFN(RMSNorm(h))
    attention   cq = RMSNorm(x Wqa); q = cq Wqb -> [qn ; qr] per head;
                [ckv ; kr] = x Wkva; ckv <- RMSNorm(ckv); [kn ; v] = ckv Wkvb;
                rotation (half-split pairs, base rope_theta) of qr and of the
                one kr shared by the heads; (qn.kn + qr.kr) / sqrt(dn + dr),
                causal, softmax; (softmax.v) Wo
    experts     s = sigmoid(x Wr); top-k of s + b; w = scale s / (sum + 1e-20);
                sum over the experts HELD HERE of w_e Expert_e(x) + Shared(x)
    prediction  u = [RMSNorm(Emb(x_{t+1})) ; RMSNorm(h_t)] We, one expert
                block, its own final norm, the shared head, target x_{t+2}
    loss        CE_main + mtp_weight CE_mtp, each a mean over valid positions

Chosen to fit a chip beside 16 bytes a parameter, not to be fast: the experts
as a loop over those held with a mask over all tokens, attention by blocks of
queries, the loss by chunks of tokens, every block recomputed in the backward
pass. `operand_bits=(exponent, mantissa)` rounds every learned operand through
`jax.lax.reduce_precision` where it is used (gradient straight through): the
control one precision below what a configuration states.
"""
import jax
import jax.numpy as jnp

FP8_E4M3 = (4, 3)


def _rounder(operand_bits):
    if operand_bits is None:
        return lambda w: w

    def rnd(w):
        q = jax.lax.reduce_precision(w, *operand_bits)
        return w + jax.lax.stop_gradient(q - w)
    return rnd


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rotate(x, theta, base):
    """x [T, ..., d] at positions 0 .. T - 1; pairs (x_i, x_{i + d/2})."""
    d = x.shape[-1]
    inv = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = theta[:, None] * inv
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), d // 2)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate((x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)), axis=-1)


def attention(p, x, m, R, block):
    """x [T, d] -> [T, d]."""
    t = x.shape[0]
    h, dn, dr, dv, r = (m['num_attention_heads'], m['qk_nope_head_dim'],
                        m['qk_rope_head_dim'], m['v_head_dim'],
                        m['kv_lora_rank'])
    eps = m['rms_norm_eps']
    cq = rms_norm(x @ R(p['q_a']['kernel']), R(p['q_a_norm']['scale']), eps)
    q = (cq @ R(p['q_b']['kernel'])).reshape(t, h, dn + dr)
    ckv_kr = x @ R(p['kv_a']['kernel'])
    ckv = rms_norm(ckv_kr[:, :r], R(p['kv_a_norm']['scale']), eps)
    kv = (ckv @ R(p['kv_b']['kernel'])).reshape(t, h, dn + dv)
    pos = jnp.arange(t, dtype=jnp.float32)
    qn, qr = q[..., :dn], rotate(q[..., dn:], pos, m['rope_theta'])
    kn, v = kv[..., :dn], kv[..., dn:]
    kr = rotate(ckv_kr[:, r:], pos, m['rope_theta'])          # [T, dr]
    scale = (dn + dr) ** -0.5

    @jax.checkpoint
    def rows(qn_i, qr_i, kn_j, kr_j, v_j, q0):
        s = (jnp.einsum('qhd,khd->hqk', qn_i, kn_j)
             + jnp.einsum('qhd,kd->hqk', qr_i, kr_j)) * scale
        allowed = jnp.arange(kn_j.shape[0])[None, :] \
            <= q0 + jnp.arange(qn_i.shape[0])[:, None]
        s = jnp.where(allowed[None], s, -jnp.inf)
        return jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v_j)

    bq = min(block, t)
    out = jnp.concatenate(
        [rows(qn[i:i + bq], qr[i:i + bq], kn[:i + bq], kr[:i + bq],
              v[:i + bq], i) for i in range(0, t, bq)], axis=0)
    return out.reshape(t, h * dv) @ R(p['out']['kernel'])


def swiglu(gate, up, down, x):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(p, x, m, R):
    """(chosen [N, k], weights [N, k]) over all the router's outputs."""
    s = jax.nn.sigmoid(x @ R(p['router']['kernel']))
    _, chosen = jax.lax.top_k(
        s + jax.lax.stop_gradient(p['correction_bias']),
        m['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m['norm_topk_prob']:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
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
        return w_e[:, None] * swiglu(R(gate), R(up), R(down), x)

    out = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = out + one(p['experts_gate'][i], p['experts_up'][i],
                        p['experts_down'][i], w_e)
    if 'shared' in p:
        out = out + shared_expert(p, x, R)
    return out, chosen


def shared_expert(p, x, R):
    s = p['shared']
    return swiglu(R(s['gate']['kernel']), R(s['up']['kernel']),
                  R(s['down']['kernel']), x)


def block(p, h, m, R, attn_block):
    """One decoder block on h [T, d] -> (h, chosen or None)."""
    eps = m['rms_norm_eps']
    h = h + attention(p['attn'], rms_norm(h, R(p['attn_norm']['scale']), eps),
                      m, R, attn_block)
    f = rms_norm(h, R(p['ff_norm']['scale']), eps)
    if 'mlp' in p:
        d = p['mlp']
        return h + swiglu(R(d['gate']['kernel']), R(d['up']['kernel']),
                          R(d['down']['kernel']), f), None
    out, chosen = expert_layer(p['moe'], f, m, R)
    return h + out, chosen


def hidden_states(params, tokens, m, R, attn_block, remat=True):
    """tokens [T] -> (main [T, d], next [T, d] or None, [chosen per expert
    layer])."""
    blk = jax.checkpoint(block, static_argnums=(2, 3, 4)) if remat else block
    m = _Static(m)
    emb = R(params['embedding']['embedding'])
    h = emb[tokens]
    chosen = []
    for i in range(m['num_hidden_layers']):
        h, c = blk(params[f'blocks_{i}'], h, m, R, attn_block)
        chosen += [c] if c is not None else []
    eps = m['rms_norm_eps']
    main = rms_norm(h, R(params['final_norm']['scale']), eps)
    if not m['num_nextn_predict_layers']:
        return main, None, chosen
    ahead = emb[jnp.roll(tokens, -1)]
    u = jnp.concatenate(
        (rms_norm(ahead, R(params['mtp_token_norm']['scale']), eps),
         rms_norm(h, R(params['mtp_hidden_norm']['scale']), eps)), axis=-1) \
        @ R(params['mtp_proj']['kernel'])
    u, c = blk(params['mtp_block'], u, m, R, attn_block)
    return (main, rms_norm(u, R(params['mtp_final_norm']['scale']), eps),
            chosen + [c])


class _Static(dict):
    """The sizes as a hashable static argument of `jax.checkpoint`."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def cross_entropy(h, kernel, targets, valid, chunk):
    """Mean over valid rows of logsumexp(h kernel) - (h kernel)[target]."""
    @jax.checkpoint
    def one(hc, tc, vc):
        logits = hc @ kernel
        nll = jax.nn.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vc, nll, 0.0))

    n = h.shape[0]
    total = sum(one(h[i:i + chunk], targets[i:i + chunk], valid[i:i + chunk])
                for i in range(0, n, chunk))
    return total / jnp.maximum(jnp.sum(valid), 1)


def loss(params, tokens, m, mtp_weight=0.3, attn_block=1024, chunk=1024,
         remat=True, operand_bits=None):
    """tokens [B, T] -> (loss, chosen [layers, B * T, k]): the mean over the
    batch's valid positions of both heads' cross-entropies."""
    R = _rounder(operand_bits)
    with jax.default_matmul_precision('highest'):
        b, t = tokens.shape
        rows = [hidden_states(params, tokens[i], m, R, attn_block, remat)
                for i in range(b)]
        cat = lambda xs: jnp.concatenate(xs, axis=0)   # noqa: E731
        pos = jnp.tile(jnp.arange(t), b)
        kernel = R(params['head']['kernel'])
        total = cross_entropy(cat([r[0] for r in rows]), kernel,
                              jnp.roll(tokens, -1, axis=1).reshape(-1),
                              pos < t - 1, chunk)
        if rows[0][1] is not None:
            total = total + mtp_weight * cross_entropy(
                cat([r[1] for r in rows]), kernel,
                jnp.roll(tokens, -2, axis=1).reshape(-1), pos < t - 2, chunk)
        chosen = jnp.stack([cat([r[2][i] for r in rows])
                            for i in range(len(rows[0][2]))]) \
            if rows[0][2] else None
    return total, chosen


def adam_update(params, grads, mu, nu, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Plain Adam (Kingma & Ba), step t = 1, 2, ... (may be traced)."""
    tm = jax.tree_util.tree_map
    mu = tm(lambda a, g: b1 * a + (1 - b1) * g, mu, grads)
    nu = tm(lambda a, g: b2 * a + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = tm(lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
                params, mu, nu)
    return params, mu, nu
