"""Operations and bytes the algorithm needs, computed from shapes.

Copied from `se3_transformer_tpu/utils/flops.py` (`conv_flops`,
`linear_flops`: sound shape arithmetic) and corrected where that file counts
more than the algorithm needs: a training step is priced at 3x its forward
operations (forward plus a backward of twice the forward), never at the 4x
that counts the reversible replay. Fibers are lists of (degree, channels).
"""

MID = 128   # width of the radial trunk


def order(d):
    return 2 * d + 1


def conv_terms(fiber_in, fiber_out, edges):
    """One ConvSE3 over `edges` edges with a shared radial trunk, as a dict
    of multiply-add counts (x2): the trunk MLP, the radial apply h @ W3, the
    basis contraction B . x, and the output contraction V2 . R."""
    t = {'trunk': 2 * edges * 2 * MID * MID, 'radial_apply': 0.0,
         'basis_contract': 0.0, 'out_contract': 0.0}
    for d_out, c_out in fiber_out:
        P = order(d_out)
        for d_in, c_in in fiber_in:
            Q, F = order(d_in), order(min(d_in, d_out))
            t['radial_apply'] += 2 * edges * MID * c_in * F * c_out
            t['basis_contract'] += 2 * edges * P * Q * F * c_in
            t['out_contract'] += 2 * edges * P * c_in * F * c_out
    return t


def conv_flops(fiber_in, fiber_out, edges):
    return sum(conv_terms(fiber_in, fiber_out, edges).values())


def linear_flops(fiber_in, fiber_out, nodes):
    fo = dict(fiber_out)
    return sum(2 * nodes * c_in * fo[d] * order(d)
               for d, c_in in fiber_in if d in fo)


def attention_einsum_flops(num_degrees, heads, dim_head, nodes, slots):
    """Similarity and weighted sum over `slots` key/value slots per node."""
    return sum(4 * nodes * slots * heads * dim_head * order(d)
               for d in range(num_degrees))


def model_shapes(model):
    """The fibers of the model family from a configuration's `model`: input,
    hidden, queries, keys and values, output. With `one_headed_key_values`
    keys and values have one head's channels."""
    dim, nd = model['dim'], model['num_degrees']
    hidden = [(d, dim) for d in range(nd)]
    q = [(d, model['heads'] * model['dim_head']) for d in range(nd)]
    kv_heads = 1 if model.get('one_headed_key_values') else model['heads']
    kv = [(d, kv_heads * model['dim_head']) for d in range(nd)]
    f_in = [(0, dim)]
    f_out = [(d, dim) for d in range(model['output_degrees'])]
    return f_in, hidden, q, kv, f_out


def _convs(model):
    """conv_in, the key and the value convolution of every block, conv_out."""
    f_in, hidden, _, kv, f_out = model_shapes(model)
    return ([(f_in, hidden)] + [(hidden, kv)] * (2 * model['depth'])
            + [(hidden, f_out)])


def forward_flops(model, nodes):
    """Forward operations of one structure of `nodes` nodes."""
    f_in, hidden, q, kv, f_out = model_shapes(model)
    k = min(model['num_neighbors'], nodes - 1)
    edges = nodes * k
    total = sum(conv_flops(a, b, edges) for a, b in _convs(model))
    ff_hidden = [(d, 4 * c) for d, c in hidden]
    per_block = (linear_flops(hidden, q, nodes)           # to_q
                 + linear_flops(hidden, kv, nodes) * 2    # self k, self v
                 + linear_flops(q, hidden, nodes)         # to_out
                 + attention_einsum_flops(model['num_degrees'],
                                          model['heads'], model['dim_head'],
                                          nodes, k + 1)
                 + linear_flops(hidden, ff_hidden, nodes)
                 + linear_flops(ff_hidden, hidden, nodes))
    return total + model['depth'] * per_block


def train_step_flops(model, nodes):
    """Forward plus backward, no replay: 3x the forward."""
    return 3.0 * forward_flops(model, nodes)


def kernel_flops(model, nodes, backward):
    """What the fused pairwise kernels compute for one structure: the radial
    apply, the basis contraction and the output contraction of every
    convolution. With `backward`, the gradients of the radial apply and of the
    output contraction as well (twice their forward each); the replayed radial
    apply inside the backward kernel is recomputation and is not counted, and
    the basis contraction's backward runs outside the kernels."""
    k = min(model['num_neighbors'], nodes - 1)
    edges = nodes * k
    total = 0.0
    for a, b in _convs(model):
        t = conv_terms(a, b, edges)
        fwd = t['radial_apply'] + t['basis_contract'] + t['out_contract']
        total += fwd
        if backward:
            total += 2 * (t['radial_apply'] + t['out_contract'])
    return total


def kernel_bytes(model, nodes, backward):
    """The least HBM traffic of the same kernels, float32: per convolution
    and degree pair, read the radial hidden, the weights, the basis and the
    gathered features, write the output; the backward reads them again with
    the output's cotangent and writes the four gradients."""
    k = min(model['num_neighbors'], nodes - 1)
    edges = nodes * k
    total = 0.0
    for fiber_in, fiber_out in _convs(model):
        for d_out, c_out in fiber_out:
            P = order(d_out)
            for d_in, c_in in fiber_in:
                Q, F = order(d_in), order(min(d_in, d_out))
                w = MID * c_in * F * c_out + c_in * F * c_out
                read = edges * (MID + P * Q * F + c_in * Q) + w
                write = edges * P * c_out
                total += 4 * (read + write)
                if backward:
                    # reads the forward operands (V2 in place of B and x)
                    # and the cotangent; writes dh, dV2 and dW3, db3
                    total += 4 * (edges * (MID + P * c_in * F + P * c_out) + w
                                  + edges * (MID + P * c_in * F) + w)
    return total
