"""The plain reference: the SE(3)-Transformer this repo trains, in
straightforward NumPy (the geometry) and jax.numpy (the learned part), one
structure at a time.

It imports nothing of the program. It follows the published architecture
(Fuchs et al. 2020, as lucidrains/se3-transformer-pytorch builds it) in the
parameterisation the program's module uses, so that it can be given the same
seeded weights: kNN graph, one shared radial trunk per convolution, radial
profile R = h W3 + b3 applied to the basis-contracted neighbour features,
mean-pooled convolutions with self-interaction, multi-head attention over
the neighbours plus the node itself, norm-gated nonlinearities, and
feed-forward blocks. No kernels, no bucketing, no reversible remat: the
per-edge tensors are simply streamed in blocks of nodes so that the radial
profile of one block fits the device.

Departures from the published description, all shared with the program:
the radial trunk is shared by the degree pairs of a convolution
(`shared_radial_hidden`), and neighbours are a fixed k with a validity mask.

`dtype=float32` runs every contraction at `highest` precision: that is the
reference. Lower precisions serve as controls; the geometry is not touched by
them, so that the graph is the same. `dtype=bfloat16` computes the
learned part in bfloat16 (weights, activations, accumulators).
`dtype=float8_e4m3fn` is float32 arithmetic whose every
learned operand (weights, and the activations entering a contraction) is
rounded to fp8, with a straight-through gradient. 'bfloat16_operands' rounds
the same operands to bfloat16: the precision the configurations state for the
program, as plain arithmetic (read on the CPU only, PERF.md).
"""
import jax
import jax.numpy as jnp
import numpy as np

from . import refbasis

_IRREP_TO_CART = (2, 0, 1)
MID = 128


def _order(d):
    return 2 * d + 1


def _exact(x):
    return x


def _rounded_to(dtype):
    """x -> x rounded to `dtype` and back, gradient passed straight through."""
    def rnd(x):
        q = x.astype(dtype).astype(x.dtype)
        return x + jax.lax.stop_gradient(q - x)
    return rnd


_ROUND = _exact     # set by forward() while it traces
# a `dtype` name for float32 arithmetic with rounded learned operands
OPERANDS = {'bfloat16_operands': jnp.bfloat16}


def _safe_norm(x, keepdims=False):
    sq = jnp.sum(x * x, axis=-1, keepdims=keepdims)
    zero = sq == 0
    return jnp.where(zero, 0.0, jnp.sqrt(jnp.where(zero, 1.0, sq)))


def neighbours(coors, mask, k):
    """Exact k nearest other real nodes of every node, on the host in float32:
    (idx [n,k], valid [n,k], rel_pos [n,k,3], rel_dist [n,k])."""
    coors, mask = np.asarray(coors, np.float32), np.asarray(mask, bool)
    n = coors.shape[0]
    rel = coors[:, None, :] - coors[None, :, :]
    dist = np.sqrt(np.sum(rel * rel, axis=-1))
    rank = np.where(np.eye(n, dtype=bool) | ~mask[None, :], np.inf, dist)
    idx = np.argsort(rank, axis=1, kind='stable')[:, :k]
    rel_pos = coors[:, None, :] - coors[idx]
    valid = mask[:, None] & mask[idx] & (idx != np.arange(n)[:, None])
    return (idx.astype(np.int32), valid, rel_pos,
            np.sqrt(np.sum(rel_pos * rel_pos, axis=-1)))


def pair_basis(rel_pos, max_degree):
    """{(d_in, d_out): [..., P, Q, F]} equivariant kernel bases, on the host
    in float64, handed over as float32."""
    rel_pos = np.asarray(rel_pos, np.float64)
    sq = np.sum(rel_pos ** 2, axis=-1, keepdims=True)
    rhat = rel_pos / np.sqrt(np.maximum(sq, 1e-16))
    Ys = refbasis.spherical_harmonics_all(2 * max_degree, rhat, xp=np)
    out = {}
    for d_in in range(max_degree + 1):
        for d_out in range(max_degree + 1):
            Ks = []
            for J in range(abs(d_in - d_out), d_in + d_out + 1):
                Q = np.asarray(refbasis.q_j(J, d_in, d_out), np.float64)
                K = np.einsum('...j,kj->...k', Ys[J], Q)
                Ks.append(K.reshape(*K.shape[:-1], _order(d_out),
                                    _order(d_in)))
            out[(d_in, d_out)] = np.stack(Ks, axis=-1).astype(np.float32)
    return out


def geometry(coors, mask, num_neighbors, num_degrees):
    """What the model sees of the coordinates: the kNN graph and the bases.
    None of it is learned and no gradient flows through it, so it is worked
    out on the host, once per set of coordinates, and the compiled reference
    holds the learned part alone (a fifth of the program to compile)."""
    k = int(min(num_neighbors, np.shape(coors)[0] - 1))
    idx, valid, rel_pos, rel_dist = neighbours(coors, mask, k)
    return dict(idx=idx, valid=valid, rel_dist=rel_dist.astype(np.float32),
                basis=pair_basis(rel_pos, num_degrees - 1))


def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p['scale'] + p['bias']


def _radial_hidden(p, rel_dist):
    x = rel_dist[..., None].astype(p['Dense_0']['kernel'].dtype)
    for i in (0, 1):
        d = p[f'Dense_{i}']
        x = x @ d['kernel'] + d['bias']
        x = jax.nn.gelu(_layer_norm(x, p[f'LayerNorm_{i}']))
    return x


def _linear(p, x):
    return {d: jnp.einsum('...cm,ce->...em', _ROUND(t), p[f'w{d}'])
            for d, t in x.items() if f'w{d}' in p}


def _norm(p, x, nonlin=jax.nn.gelu):
    out = {}
    for d, t in x.items():
        norm = jnp.clip(_safe_norm(t, keepdims=True), 1e-12, None)
        scale = p[f'scale{d}'].reshape(-1)
        out[d] = nonlin(norm[..., 0] * scale)[..., None] * (t / norm)
    return out


def _residual(x, res):
    return {d: t + res[d] if d in res else t for d, t in x.items()}


def conv(p, x, graph, basis, deg_out, block, pool):
    """ConvSE3 over the kNN graph. x {d: [n, c, 2d+1]} -> {d_out:
    [n, c_out, P]} pooled over the neighbours, or [n, k, c_out, P]."""
    idx, valid, rel_dist = graph
    n, k = idx.shape
    deg_in = sorted(x)
    dtype = x[deg_in[0]].dtype

    def one_block(args):
        idx_b, valid_b, dist_b, basis_b = args
        h = _ROUND(_radial_hidden(p, dist_b))              # [nb, k, MID]
        xg = {di: _ROUND(x[di][idx_b]) for di in deg_in}   # [nb, k, c, Q]
        out = {}
        for do in deg_out:
            # the input degrees side by side: one radial profile and one
            # contraction per output degree, sum_di V2_di . R_di
            v2 = jnp.concatenate([
                jnp.einsum('...pqf,...cq->...pcf',
                           basis_b[(di, do)].astype(dtype), xg[di])
                .reshape(*xg[di].shape[:-2], _order(do), -1)
                for di in deg_in], axis=-1)                # [nb, k, P, sum cF]
            w3 = jnp.concatenate([p[f'w3_{di}_{do}'] for di in deg_in], 1)
            b3 = jnp.concatenate([p[f'b3_{di}_{do}'] for di in deg_in], 0)
            R = jnp.einsum('...m,mio->...io', h, w3) + b3
            acc = jnp.einsum('...pi,...io->...op', v2, R)  # [nb, k, O, P]
            if pool:
                w = valid_b[..., None, None]
                cnt = valid_b.sum(-1)
                mean = jnp.where(w, acc, 0.).sum(1) \
                    / jnp.clip(cnt, 1, None).astype(acc.dtype)[:, None, None]
                acc = jnp.where((cnt == 0)[:, None, None], 0., mean)
            out[do] = acc
        return out

    nb = -(-n // block)
    pad = nb * block - n

    def split(a):
        if pad:
            a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(nb, block, *a.shape[1:])

    args = jax.tree_util.tree_map(
        split, (idx, valid, rel_dist,
                {key: basis[key] for key in basis
                 if key[0] in deg_in and key[1] in deg_out}))
    out = jax.lax.map(jax.checkpoint(one_block), args)
    out = {d: t.reshape(nb * block, *t.shape[2:])[:n] for d, t in out.items()}
    if pool and 'self_interact' in p:
        out = _residual(out, _linear(p['self_interact'], x))
    return out


def attention_block(p, x, graph, basis, heads, dim_head, block, kv_heads):
    """`kv_heads` is `heads`, or 1 where one head of keys and values is shared
    by every head of the queries (`one_headed_key_values`)."""
    idx, valid, _ = graph
    n, k = idx.shape
    res = x
    x = _norm(p['prenorm'], x)
    a = p['attn']
    degs = sorted(x)
    q = _linear(a['to_q'], x)
    v = conv(a['to_v'], x, graph, basis, degs, block, pool=False)
    kk = conv(a['to_k'], x, graph, basis, degs, block, pool=False)
    self_k = _linear(a['to_self_k'], x)
    self_v = _linear(a['to_self_v'], x)
    slot_mask = jnp.concatenate((jnp.ones((n, 1), bool), valid), axis=1)
    out = {}
    for d in degs:
        m = _order(d)
        qd = _ROUND(q[d]).reshape(n, heads, dim_head, m)
        kd = jnp.concatenate((self_k[d][:, None], kk[d]), axis=1)
        vd = jnp.concatenate((self_v[d][:, None], v[d]), axis=1)
        kd = _ROUND(kd).reshape(n, k + 1, kv_heads, dim_head, m)
        vd = _ROUND(vd).reshape(n, k + 1, kv_heads, dim_head, m)
        if kv_heads != heads:       # one head, seen by every query head
            kd = jnp.broadcast_to(kd, (n, k + 1, heads, dim_head, m))
            vd = jnp.broadcast_to(vd, (n, k + 1, heads, dim_head, m))
        sim = jnp.einsum('ihdm,ijhdm->ihj', qd, kd) * dim_head ** -0.5
        sim = jnp.where(slot_mask[:, None, :], sim, jnp.finfo(sim.dtype).min)
        attn = jax.nn.softmax(sim, axis=-1)
        od = jnp.einsum('ihj,ijhdm->ihdm', attn, vd)
        out[d] = od.reshape(n, heads * dim_head, m)
    return _residual(_linear(a['to_out'], out), res)


def ff_block(p, x):
    res = x
    x = _norm(p['prenorm'], x)
    f = p['feedforward']
    x = _linear(f['project_in'], x)
    x = _norm(f['nonlin'], x)
    x = _linear(f['project_out'], x)
    return _residual(x, res)


def forward(params, feats, geom, *, depth, num_degrees, heads, dim_head,
            output_degrees, kv_heads=None, block=64, dtype=jnp.float32,
            remat=False):
    """One structure: feats [n] int tokens or [n, dim] floats and its
    `geometry` -> the type-1 output [n, 3] (float32)."""
    global _ROUND
    operands = OPERANDS.get(dtype)
    if operands is None and jnp.dtype(dtype) == jnp.float8_e4m3fn:
        operands = jnp.float8_e4m3fn
    _ROUND = _rounded_to(operands) if operands else _exact
    try:
        return _forward(params, feats, geom, depth, num_degrees, heads,
                        dim_head, kv_heads or heads, output_degrees, block,
                        jnp.float32 if operands else dtype, remat)
    finally:
        _ROUND = _exact


def _forward(params, feats, geom, depth, num_degrees, heads, dim_head,
             kv_heads, output_degrees, block, dtype, remat):
    basis = {key: jnp.asarray(b) for key, b in geom['basis'].items()}
    graph = tuple(jnp.asarray(geom[k]) for k in ('idx', 'valid', 'rel_dist'))
    params = jax.tree_util.tree_map(lambda a: _ROUND(a.astype(dtype)),
                                    params)
    if 'token_emb' in params:
        feats = params['token_emb']['embedding'][feats]
    x = {0: feats.astype(dtype)[..., None]}
    hidden = list(range(num_degrees))

    x = conv(params['conv_in'], x, graph, basis, hidden, block, pool=True)
    x = {d: x[d] for d in hidden}
    # the trunk's blocks are alike: stack their weights and scan one body,
    # so that one block is compiled, whatever the depth
    t = params['trunk']
    blocks = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[{'attn': t[f'attn_block{i}'], 'ff': t[f'ff_block{i}']}
          for i in range(depth)])

    def body(x, p):
        x = attention_block(p['attn'], x, graph, basis, heads, dim_head,
                            block, kv_heads)
        return ff_block(p['ff'], x), None

    x, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, x, blocks)
    x = conv(params['conv_out'], x, graph, basis,
             list(range(output_degrees)), block, pool=True)
    x = _norm(params['norm_out'], x, nonlin=lambda t: t)
    x = _linear(params['linear_out'], x)
    out = x[1][..., 0, :][..., jnp.asarray(_IRREP_TO_CART)]
    return out.astype(jnp.float32)


def denoise_loss(params, feats, noised, coors, geom, **model):
    """The denoising objective of the training cell, one structure: `geom` is
    the geometry of the noised coordinates."""
    out = forward(params, feats, geom, **model)
    return (((noised + out) - coors) ** 2).sum(-1).mean()


def adam_update(params, grads, m, v, t, lr=1e-4, b1=0.9, b2=0.999,
                eps=1e-8):
    """Plain Adam (Kingma & Ba), step t = 1, 2, ... (a traced number, so that
    one program serves every step)."""
    tm = jax.tree_util.tree_map
    m = tm(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = tm(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = tm(lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
                params, m, v)
    return params, m, v
