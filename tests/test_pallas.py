"""Numerics gate: Pallas fused pairwise kernel vs the XLA einsum path.

Runs the kernel in interpreter mode on CPU (tests/conftest.py forces the
CPU backend); the same comparison runs on real TPU hardware via
scripts/tpu_checks.py.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.basis import get_basis
from se3_transformer_tpu.kernels.pallas_pairwise import fused_pairwise_conv
from se3_transformer_tpu.ops.conv import PairwiseConvSE3


def test_fused_kernel_matches_einsum():
    rng = np.random.RandomState(0)
    E, mid, I, F, O, P = 37, 16, 5, 3, 12, 7
    h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(mid, I * F, O)), jnp.float32)
    b3 = jnp.asarray(rng.normal(size=(I * F, O)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(E, P, I * F)), jnp.float32)

    out = fused_pairwise_conv(h, w3, v2, b3=b3, interpret=True)
    R = jnp.einsum('em,mko->eko', h, w3) + b3
    ref = jnp.einsum('epk,eko->epo', v2, R)
    assert jnp.abs(out - ref).max() < 1e-4

    # b3 omitted == zero bias
    out0 = fused_pairwise_conv(h, w3, v2, interpret=True)
    ref0 = jnp.einsum('epk,eko->epo', v2, jnp.einsum('em,mko->eko', h, w3))
    assert jnp.abs(out0 - ref0).max() < 1e-4


@pytest.mark.parametrize('d_in,d_out', [(0, 1), (1, 1), (2, 1)])
def test_pairwise_conv_pallas_path_matches_xla(d_in, d_out):
    rng = np.random.RandomState(1)
    b, n, k, ci, co = 1, 6, 3, 4, 5
    edge = jnp.asarray(rng.normal(size=(b, n, k, 2)), jnp.float32)
    rel = jnp.asarray(rng.normal(size=(b, n, k, 3)), jnp.float32)
    basis = get_basis(rel, max(d_in, d_out))[f'{d_in},{d_out}']
    x = jnp.asarray(rng.normal(size=(b, n, k, ci, 2 * d_in + 1)), jnp.float32)

    xla_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False)
    params = xla_mod.init(jax.random.PRNGKey(0), edge, basis, x)
    out_xla = xla_mod.apply(params, edge, basis, x)

    pl_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                             pallas_interpret=True)
    out_pl = pl_mod.apply(params, edge, basis, x)

    assert out_pl.shape == out_xla.shape == (b, n, k, co, 2 * d_out + 1)
    assert jnp.abs(out_pl - out_xla).max() < 1e-4


def test_edge_chunks_composes_with_pallas():
    """Node-axis streaming through the Pallas kernel (the dim-512-class
    memory path: chunks bound HBM, the kernel bounds VMEM) must match the
    dense XLA path in values and gradients."""
    rng = np.random.RandomState(3)
    d_in, d_out, ci, co = 1, 2, 3, 4
    b, n, k = 1, 8, 3
    edge = jnp.asarray(rng.normal(size=(b, n, k, 2)), jnp.float32)
    rel = jnp.asarray(rng.normal(size=(b, n, k, 3)), jnp.float32)
    basis = get_basis(rel, 2)[f'{d_in},{d_out}']
    x = jnp.asarray(rng.normal(size=(b, n, k, ci, 2 * d_in + 1)), jnp.float32)

    xla_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False)
    params = xla_mod.init(jax.random.PRNGKey(0), edge, basis, x)
    ch_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                             pallas_interpret=True, edge_chunks=4)

    out_ref = xla_mod.apply(params, edge, basis, x)
    out_ch = ch_mod.apply(params, edge, basis, x)
    assert jnp.abs(out_ch - out_ref).max() < 1e-4

    def loss(mod):
        return lambda p: (mod.apply(p, edge, basis, x) ** 2).sum()

    g_ref = jax.grad(loss(xla_mod))(params)
    g_ch = jax.grad(loss(ch_mod))(params)
    for a, b2 in zip(jax.tree_util.tree_leaves(g_ref),
                     jax.tree_util.tree_leaves(g_ch)):
        assert jnp.abs(a - b2).max() < 1e-3


def test_pallas_path_gradients():
    """The custom-VJP (pallas fwd / einsum bwd) agrees with XLA gradients."""
    rng = np.random.RandomState(2)
    d_in, d_out, ci, co = 1, 1, 3, 4
    edge = jnp.asarray(rng.normal(size=(1, 4, 2, 2)), jnp.float32)
    rel = jnp.asarray(rng.normal(size=(1, 4, 2, 3)), jnp.float32)
    basis = get_basis(rel, 1)['1,1']
    x = jnp.asarray(rng.normal(size=(1, 4, 2, ci, 3)), jnp.float32)

    xla_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False)
    params = xla_mod.init(jax.random.PRNGKey(0), edge, basis, x)
    pl_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                             pallas_interpret=True)

    def loss(mod):
        def inner(p, xx):
            return (mod.apply(p, edge, basis, xx) ** 2).sum()
        return inner

    g1p, g1x = jax.grad(loss(xla_mod), argnums=(0, 1))(params, x)
    g2p, g2x = jax.grad(loss(pl_mod), argnums=(0, 1))(params, x)
    assert jnp.abs(g1x - g2x).max() < 1e-3
    for a, b2 in zip(jax.tree_util.tree_leaves(g1p),
                     jax.tree_util.tree_leaves(g2p)):
        assert jnp.abs(a - b2).max() < 1e-3


RDT = pytest.mark.parametrize('rdt', ['f32', 'radial_bf16'])


def _bwd_case(seed, E, mid, IF, O, P, rdt):
    """Seeded operands of one backward case: h and w3 in the radial dtype
    (`radial_bf16` hands the kernels bfloat16 ones), the rest float32."""
    rng = np.random.RandomState(seed)
    dt = jnp.bfloat16 if rdt == 'radial_bf16' else jnp.float32
    h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32).astype(dt)
    w3 = jnp.asarray(rng.normal(size=(mid, IF, O)), jnp.float32).astype(dt)
    b3 = jnp.asarray(rng.normal(size=(IF, O)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(E, P, IF)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(E, P, O)), jnp.float32)
    return h, w3, b3, v2, g


def _bwd_einsum(h, w3, b3, v2, g, round_dr=False):
    """(dh, dw3, dv2, db3) by einsums in float32 on the values h and w3
    hold. `round_dr` is the quantized oracle of the bfloat16 path: dR goes
    into the two products rounded to bfloat16, as the kernels feed it to
    the MXU; dV2 and dB3 never see the rounding."""
    h, w3 = h.astype(jnp.float32), w3.astype(jnp.float32)
    with jax.default_matmul_precision('highest'):
        R = jnp.einsum('em,mko->eko', h, w3) + b3  # dV2 needs R WITH bias
        dv2 = jnp.einsum('epo,eko->epk', g, R)
        dR = jnp.einsum('epk,epo->eko', v2, g)
        dRq = dR.astype(jnp.bfloat16).astype(jnp.float32) if round_dr \
            else dR
        return (jnp.einsum('eko,mko->em', dRq, w3),
                jnp.einsum('em,eko->mko', h, dRq), dv2, dR.sum(0))


def _rel(a, b):
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-9)


def _assert_bwd_matches(case, rdt, tol=1e-5):
    """The fused backward against the einsum VJP. float32 operands: every
    cotangent to `tol`. bfloat16 h / w3: dV2 and dB3 (float32 reductions)
    to `tol`; dH and dW3 to 5e-4 of the quantized oracle (a dR element
    that the kernel's summation order rounds to the other bfloat16
    neighbour moves one term of hundreds by 2^-9) and, loosely, to 1e-2
    of the unrounded float32 VJP."""
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv_bwd,
    )
    h, w3, b3, v2, g = case
    got = fused_pairwise_conv_bwd(h, w3, v2, g, b3=b3, interpret=True)
    assert all(t.dtype == jnp.float32 for t in got)
    exact = _bwd_einsum(*case)
    names = ('dh', 'dw3', 'dv2', 'db3')
    if rdt == 'f32':
        for n, a, b in zip(names, got, exact):
            assert _rel(a, b) < tol, (n, _rel(a, b))
        return
    oracle = _bwd_einsum(*case, round_dr=True)
    for n, a, q, b in zip(names, got, oracle, exact):
        if n in ('dv2', 'db3'):
            assert _rel(a, b) < tol, (n, _rel(a, b))
        else:
            assert _rel(a, q) < 5e-4, (n, _rel(a, q))
            assert _rel(a, b) < 1e-2, (n, _rel(a, b))


@RDT
@pytest.mark.parametrize('shape', [
    # (E, mid, IF, O, P). One program each (E 41 padded to a 128 block, the
    # full IF axis): the stacked-dR scratch is IF*O rows, a multiple of 8
    # or (O = 5) not
    (41, 16, 15, 12, 7),
    (41, 16, 15, 24, 7),
    (41, 16, 15, 64, 7),
    (41, 16, 15, 5, 7),
    # two e-blocks by four if-chunks, neither axis a multiple of its block:
    # both accumulations revisit (the wider sweep of such shapes,
    # test_fused_kernels_multichunk_if_axis, is in the slow tier)
    (300, 16, 100, 24, 7),
])
def test_fused_bwd_kernel_matches_einsum(shape, rdt):
    _assert_bwd_matches(_bwd_case(3, *shape, rdt), rdt, tol=2e-5)


@RDT
@pytest.mark.parametrize('shape', [
    # (E, mid, IF, O, P): IF above the unroll cap forces n_if > 1 and IF is
    # no multiple of block_if; E above 128 and no multiple of block_e
    # gives several e-blocks, so kernel A's accumulation over e and
    # kernel B's over if both revisit their output block
    (17, 8, 280, 20, 5),
    (600, 8, 280, 24, 5),
    (300, 16, 100, 5, 7),
    (260, 8, 72, 64, 7),
])
def test_fused_kernels_multichunk_if_axis(shape, rdt):
    """Exercises the partial-sum output path (the TPU-correctness-critical
    case the block revisit rules forbid accumulating in place)."""
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        _pick_blocks, fused_pairwise_conv,
    )
    E, mid, IF, O, P = shape
    block_e, block_if = _pick_blocks(E, IF, O, P, mid, bwd=True)
    assert IF > block_if and IF % block_if
    assert E <= 128 or (E > block_e and E % block_e)
    case = h, w3, b3, v2, g = _bwd_case(4, *shape, rdt)

    out = fused_pairwise_conv(h, w3, v2, b3=b3, interpret=True)
    R = jnp.einsum('em,mko->eko', h.astype(jnp.float32),
                   w3.astype(jnp.float32)) + b3
    assert _rel(out, jnp.einsum('epk,eko->epo', v2, R)) < 1e-5
    _assert_bwd_matches(case, rdt)


@RDT
@pytest.mark.parametrize('shape', [
    # (E, mid, IF, O, P) — edge cases: singleton axes, non-multiples,
    # IF > 128 (multi-chunk), E smaller than any block size
    (1, 8, 1, 1, 1),
    (3, 16, 2, 5, 3),
    (130, 16, 7, 9, 7),
    (8, 8, 200, 16, 5),
    (257, 24, 130, 3, 1),
    (130, 16, 40, 24, 7),
    (9, 8, 37, 64, 3),
])
def test_fused_kernels_shape_fuzz(shape, rdt):
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv,
    )
    case = h, w3, b3, v2, g = _bwd_case(sum(shape), *shape, rdt)
    R = jnp.einsum('em,mko->eko', h.astype(jnp.float32),
                   w3.astype(jnp.float32)) + b3
    out = fused_pairwise_conv(h, w3, v2, b3=b3, interpret=True)
    assert _rel(out, jnp.einsum('epk,eko->epo', v2, R)) < 1e-5, shape
    _assert_bwd_matches(case, rdt)


# ------------------------------------------------------------------ #
# fused multi-degree attention kernel
# ------------------------------------------------------------------ #

def test_fused_attention_matches_reference():
    from se3_transformer_tpu.kernels.pallas_attention import (
        attention_reference, fused_attention,
    )
    rng = np.random.RandomState(0)
    for B, h, kv_h, n, J, D in ((2, 4, 4, 40, 9, 24), (1, 4, 1, 16, 5, 8),
                                (1, 4, 2, 33, 12, 16), (1, 1, 1, 8, 3, 40)):
        q = jnp.asarray(rng.normal(size=(B * h, n, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B * kv_h, n, J, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B * kv_h, n, J, D)), jnp.float32)
        mask = jnp.asarray(rng.rand(B, n, J) > 0.3)
        # guarantee at least one valid slot per row
        mask = mask.at[:, :, 0].set(True)
        scale = D ** -0.5
        ref = attention_reference(q, k, v, mask, scale)
        out = fused_attention(q, k, v, mask, h, scale, True)
        assert np.abs(np.asarray(ref) - np.asarray(out)).max() < 1e-5, \
            (B, h, kv_h, n, J, D)
        # no mask
        ref = attention_reference(q, k, v, None, scale)
        out = fused_attention(q, k, v, None, h, scale, True)
        assert np.abs(np.asarray(ref) - np.asarray(out)).max() < 1e-5


def test_fused_attention_gradients():
    from se3_transformer_tpu.kernels.pallas_attention import (
        attention_reference, fused_attention,
    )
    rng = np.random.RandomState(1)
    # (h, kv_h): group=1 and the multi-query group>1 accumulation branch;
    # ragged mask exercises the masked-slot gradient path
    for h, kv_h in ((2, 2), (4, 1)):
        B, n, J, D = 1, 12, 6, 8
        q = jnp.asarray(rng.normal(size=(B * h, n, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B * kv_h, n, J, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B * kv_h, n, J, D)), jnp.float32)
        mask = jnp.asarray(rng.rand(B, n, J) > 0.3).at[:, :, 0].set(True)
        scale = D ** -0.5

        g_f = jax.grad(lambda q, k, v: (fused_attention(
            q, k, v, mask, h, scale, True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(lambda q, k, v: (attention_reference(
            q, k, v, mask, scale) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_f, g_r):
            assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-4, \
                (h, kv_h)


def test_model_with_fused_attention_matches_einsum_path():
    """Model-level: pallas_attention (interpreter) output identical to the
    einsum path, across the kv-slot variants (self/null/multi-query) and
    with masking."""
    from se3_transformer_tpu import SE3TransformerModule

    rng = np.random.RandomState(2)
    feats = jnp.asarray(rng.normal(size=(1, 20, 8)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, 20, 3)), jnp.float32)
    mask = np.ones((1, 20), bool)
    mask[:, 17:] = False
    mask = jnp.asarray(mask)

    for kwargs in (dict(), dict(use_null_kv=True),
                   dict(one_headed_key_values=True),
                   dict(linear_proj_keys=True)):
        base = dict(dim=8, depth=1, attend_self=True, num_neighbors=6,
                    num_degrees=2, output_degrees=2, heads=2, dim_head=4,
                    **kwargs)
        xla = SE3TransformerModule(**base, pallas_attention=False)
        fused = SE3TransformerModule(**base, pallas_attention=False,
                                     pallas_attention_interpret=True)
        params = xla.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                          return_type=1)['params']
        o1 = xla.apply({'params': params}, feats, coors, mask=mask,
                       return_type=1)
        o2 = fused.apply({'params': params}, feats, coors, mask=mask,
                         return_type=1)
        assert np.abs(np.asarray(o1) - np.asarray(o2)).max() < 2e-5, kwargs


def test_attention_block_picker_respects_vmem_budget():
    """The block picker must account for the REAL tile pads (lane dim ->
    128, sublane -> 8) and Pallas double buffering: the first guess
    didn't and OOM'd scoped VMEM at the flagship shapes on hardware
    (round-3 session log: 40 MiB against the 16 MiB limit)."""
    from se3_transformer_tpu.kernels.pallas_attention import (
        _VMEM_LIMIT, _block_row_bytes, _pick_block_n,
    )
    # flagship (n=1024, J=k+1=33) at every dim_head*m the trunk produces,
    # plus the shapes the round-3 session actually OOM'd on
    for J, D in [(33, 8), (33, 24), (33, 40), (33, 56), (33, 64),
                 (17, 24), (9, 8), (64, 64)]:
        for bwd in (False, True):
            b = _pick_block_n(1024, J, D, bwd=bwd)
            assert b * _block_row_bytes(J, D, bwd) <= _VMEM_LIMIT, \
                (J, D, bwd, b)


def test_fused_attention_big_j_falls_back(monkeypatch):
    """An over-budget slot axis must dispatch to the XLA path, not
    surface a Mosaic VMEM error (VERDICT r2 weak #4). Simulated by
    shrinking the VMEM budget so the tiny test config is over-budget:
    with the guard working, pallas_attention=True silently uses the XLA
    path (which runs on CPU); without it, the non-interpret pallas_call
    would fail on the CPU backend."""
    from se3_transformer_tpu import SE3TransformerModule
    from se3_transformer_tpu.kernels import pallas_attention as pa

    assert not pa.fused_attention_fits(J=452, D=64)   # the real ceiling
    monkeypatch.setattr(pa, '_VMEM_LIMIT', 1024)      # force over-budget
    assert not pa.fused_attention_fits(J=8, D=4)

    rng = np.random.RandomState(3)
    feats = jnp.asarray(rng.normal(size=(1, 16, 8)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, 16, 3)), jnp.float32)
    model = SE3TransformerModule(dim=8, depth=1, attend_self=True,
                                 num_neighbors=6, num_degrees=2,
                                 output_degrees=2, heads=2, dim_head=4,
                                 pallas_attention=True)
    params = model.init(jax.random.PRNGKey(0), feats, coors,
                        return_type=1)['params']
    out = model.apply({'params': params}, feats, coors, return_type=1)
    assert np.isfinite(np.asarray(out)).all()


def test_shared_radial_group_path():
    """ConvSE3(shared_radial_hidden=True) fuses all (d_in -> d_out) pairs
    of an output degree into one contraction. Gate (a) the group math
    against a per-pair loop over the same params and (b) the Pallas
    interpreter path against the XLA path."""
    from se3_transformer_tpu.basis import get_basis
    from se3_transformer_tpu.ops import ConvSE3, Fiber
    from se3_transformer_tpu.ops.conv import radial_hidden
    from se3_transformer_tpu.utils import batched_index_select
    import flax.linen as nn

    rng = np.random.RandomState(7)
    n, k, dim, degrees = 24, 6, 6, 3
    fiber = Fiber.create(degrees, dim)
    feats = {str(d): jnp.asarray(rng.normal(size=(1, n, dim, 2 * d + 1)),
                                 jnp.float32) for d in range(degrees)}
    coors = jnp.asarray(rng.normal(size=(1, n, 3)) * 2, jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (1, n, k)), jnp.int32)
    mask = jnp.ones((1, n, k), bool)
    coors_j = batched_index_select(coors, idx, axis=1)
    rel = coors[:, :, None, :] - coors_j
    rd = jnp.linalg.norm(rel, axis=-1)
    basis = get_basis(rel, degrees - 1)
    args = (feats, (idx, mask, None), rd, basis)

    conv = ConvSE3(fiber, fiber, shared_radial_hidden=True, pallas=False,
                   pool=False, self_interaction=False)
    params = conv.init(jax.random.PRNGKey(0), *args)
    out = conv.apply(params, *args)

    conv_i = ConvSE3(fiber, fiber, shared_radial_hidden=True, pallas=False,
                     pallas_interpret=True, pool=False,
                     self_interaction=False)
    out_i = conv_i.apply(params, *args)

    # per-pair reference over the very same params
    p = params['params']
    ef = rd[..., None]

    class Trunk(nn.Module):
        @nn.compact
        def __call__(self, x):
            return radial_hidden(x, 128)

    trunk_params = {'params': {k2: v for k2, v in p.items()
                               if k2.startswith(('Dense_', 'LayerNorm_'))}}
    hid = Trunk().apply(trunk_params, ef)
    for d_out in range(degrees):
        P = 2 * d_out + 1
        acc = None
        for d_in in range(degrees):
            F = 2 * min(d_in, d_out) + 1
            x = batched_index_select(feats[str(d_in)], idx, axis=1)
            v2 = jnp.einsum('...pqf,...cq->...pcf',
                            basis[f'{d_in},{d_out}'], x)
            v2 = v2.reshape(*v2.shape[:-2], dim * F)
            R = jnp.einsum('...m,mko->...ko', hid,
                           p[f'w3_{d_in}_{d_out}']) + p[f'b3_{d_in}_{d_out}']
            y = jnp.einsum('...pk,...ko->...po', v2, R)
            acc = y if acc is None else acc + y
        ref = jnp.swapaxes(acc, -1, -2)
        assert np.abs(np.asarray(out[str(d_out)]) - np.asarray(ref)).max() \
            < 1e-4
        assert np.abs(np.asarray(out_i[str(d_out)])
                      - np.asarray(out[str(d_out)])).max() < 1e-4


# ------------------------------------------------------------------ #
# basis-fused pairwise kernel (V2 in VMEM only)
# ------------------------------------------------------------------ #

@pytest.mark.parametrize('shape', [
    # (E, mid, C, Q, F, O, P) — incl. C not a multiple of the c-chunk,
    # E off the block grid, and the degree-0 singleton axes
    (37, 16, 4, 3, 3, 5, 7),
    (130, 8, 9, 5, 3, 4, 5),
    (8, 8, 1, 1, 1, 3, 1),
    (257, 24, 16, 7, 7, 8, 7),
])
def test_fused_bx_kernel_matches_einsum(shape):
    """A structured basis, flattened and contracted in the kernel, equals
    the einsum."""
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv_bxf,
    )
    from se3_transformer_tpu.ops.conv import flatten_basis
    E, mid, C, Q, F, O, P = shape
    rng = np.random.RandomState(sum(shape))
    h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(mid, C * F, O)), jnp.float32)
    b3 = jnp.asarray(rng.normal(size=(C * F, O)), jnp.float32)
    basis = jnp.asarray(rng.normal(size=(E, P, Q, F)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(E, C, Q)), jnp.float32)

    out = fused_pairwise_conv_bxf(h, w3, flatten_basis(basis), x, (P, Q, F),
                                  b3=b3, interpret=True)
    v2 = jnp.einsum('epqf,ecq->epcf', basis, x).reshape(E, P, C * F)
    R = jnp.einsum('em,mko->eko', h, w3) + b3
    ref = jnp.einsum('epk,eko->epo', v2, R)
    scale = float(jnp.abs(ref).max()) + 1e-9
    assert jnp.abs(out - ref).max() / scale < 1e-5, shape


# the one door of the dense contraction: ops.conv.contract_pair at the
# (2, 1) pair, (P, Q, F) = (3, 5, 3), so a (q, f) mix-up in either
# relayout cannot pass
_PAIR_PQF = (3, 5, 3)


def _pair_operands(seed, differentiable=False):
    from se3_transformer_tpu.ops.conv import flatten_basis
    rng = np.random.RandomState(seed)
    (P, Q, F), (b, n, k, mid, C, O) = _PAIR_PQF, (1, 6, 3, 8, 4, 5)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape),  # noqa: E731
                                     jnp.float32)
    rel = f32(b, n, k, 3)
    basis = get_basis(rel, 2, differentiable=differentiable)['2,1']
    return dict(h=f32(b, n, k, mid), w3=f32(mid, C * F, O), b3=f32(C * F, O),
                x=f32(b, n, k, C, Q), rel=rel, structured=basis,
                flat=flatten_basis(basis))


def _pair_einsum(h, w3, b3, basis, x):
    v2 = jnp.einsum('...pqf,...cq->...pcf', basis, x)
    v2 = v2.reshape(*v2.shape[:-2], -1)
    R = jnp.einsum('...m,mko->...ko', h, w3) + b3
    return jnp.einsum('...pk,...ko->...po', v2, R)


@pytest.mark.parametrize('route', ['interpret', 'xla'])
@pytest.mark.parametrize('fuse_basis', [True, False],
                         ids=['fused', 'unfused'])
@pytest.mark.parametrize('layout', ['flat', 'structured'])
def test_contract_pair_every_route_matches_einsum(layout, fuse_basis, route):
    """Whatever layout the basis arrives in, contract_pair takes the
    basis-fused kernels exactly when fuse_basis meets the Pallas path,
    the V2-given kernel on the Pallas path without it, XLA otherwise,
    and every combination is the float32 einsum."""
    from se3_transformer_tpu.ops.conv import contract_pair
    ops = _pair_operands(5)
    interpret = route == 'interpret'

    def run(basis):
        out, v2 = contract_pair(
            ops['h'], ops['w3'], ops['b3'], basis, ops['x'], _PAIR_PQF,
            pallas=False, pallas_interpret=interpret, edge_chunks=None,
            fuse_basis=fuse_basis)
        assert v2 is None
        return out

    launches = set(re.findall(r'fused_pairwise_conv\w*',
                              str(jax.make_jaxpr(run)(ops[layout]))))
    want = set() if not interpret else \
        {'fused_pairwise_conv_bxf'} if fuse_basis else {'fused_pairwise_conv'}
    assert launches == want, launches
    ref = _pair_einsum(ops['h'], ops['w3'], ops['b3'], ops['structured'],
                       ops['x'])
    assert _rel(run(ops[layout]), ref) < 1e-5


@pytest.mark.parametrize('differentiable', [False, True])
def test_contract_pair_structured_fused_gradients(differentiable):
    """A structured basis on the fused route: every cotangent comes out of
    the basis-fused backward kernels, dbasis through the flatten's own
    transpose, and equals jax.grad of the einsum form (down to the
    coordinates the basis was built from, when it is differentiable)."""
    from se3_transformer_tpu.ops.conv import contract_pair
    ops = _pair_operands(6)

    def loss(contract):
        def f(h, w3, b3, x, rel):
            basis = get_basis(rel, 2, differentiable=differentiable)['2,1']
            return (contract(h, w3, b3, basis, x) ** 2).sum()
        return f

    def fused(h, w3, b3, basis, x):
        return contract_pair(h, w3, b3, basis, x, _PAIR_PQF, pallas=False,
                             pallas_interpret=True, edge_chunks=None,
                             fuse_basis=True)[0]

    args = tuple(ops[k] for k in ('h', 'w3', 'b3', 'x', 'rel'))
    text = str(jax.make_jaxpr(jax.grad(loss(fused), argnums=(0, 1, 2, 3, 4)))(
        *args))
    assert set(re.findall(r'fused_pairwise_conv\w*', text)) == {
        'fused_pairwise_conv_bxf', 'fused_pairwise_conv_bwd_bxf',
        'fused_pairwise_conv_bwd_a', 'fused_pairwise_conv_bwd_b'}
    got = jax.grad(loss(fused), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(_pair_einsum), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(('dh', 'dw3', 'db3', 'dx', 'drel'), got, want):
        if name == 'drel' and not differentiable:
            assert not np.asarray(a).any() and not np.asarray(b).any()
        else:
            assert _rel(a, b) < 2e-5, (name, _rel(a, b))


def test_contract_pair_group_returns_the_v2_segment():
    """ConvSE3's shared trunk asks with group=True: the fused route still
    answers with the pair's output, the V2-given routes with the V2
    segment to concatenate, from either layout."""
    from se3_transformer_tpu.ops.conv import contract_pair
    ops = _pair_operands(7)
    kw = dict(pallas=False, edge_chunks=None, group=True)
    v2_ref = jnp.einsum('...pqf,...cq->...pcf', ops['structured'], ops['x'])
    v2_ref = v2_ref.reshape(*v2_ref.shape[:-2], -1)
    for layout in ('flat', 'structured'):
        args = (ops['h'], ops['w3'], ops['b3'], ops[layout], ops['x'],
                _PAIR_PQF)
        out, v2 = contract_pair(*args, pallas_interpret=True,
                                fuse_basis=True, **kw)
        assert v2 is None and out.shape == (1, 6, 3, 3, 5)
        for interpret, fuse in ((True, False), (False, True)):
            out, v2 = contract_pair(*args, pallas_interpret=interpret,
                                    fuse_basis=fuse, **kw)
            assert out is None and _rel(v2, v2_ref) < 1e-6


@pytest.mark.parametrize('d_in,d_out', [(0, 1), (1, 1), (2, 1), (1, 2)])
def test_pairwise_conv_fuse_basis_matches_xla(d_in, d_out):
    """Module level: fuse_basis forward and ALL gradients (params, x, and
    the basis itself — the differentiable-coors path) match the XLA
    path."""
    rng = np.random.RandomState(11)
    b, n, k, ci, co = 1, 6, 3, 4, 5
    edge = jnp.asarray(rng.normal(size=(b, n, k, 2)), jnp.float32)
    rel = jnp.asarray(rng.normal(size=(b, n, k, 3)), jnp.float32)
    basis = get_basis(rel, max(d_in, d_out))[f'{d_in},{d_out}']
    x = jnp.asarray(rng.normal(size=(b, n, k, ci, 2 * d_in + 1)), jnp.float32)

    xla_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False)
    params = xla_mod.init(jax.random.PRNGKey(0), edge, basis, x)
    bx_mod = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                             pallas_interpret=True, fuse_basis=True)

    out_ref = xla_mod.apply(params, edge, basis, x)
    out_bx = bx_mod.apply(params, edge, basis, x)
    assert out_bx.shape == out_ref.shape
    assert jnp.abs(out_bx - out_ref).max() < 1e-4

    def loss(mod):
        return lambda p, bb, xx: (mod.apply(p, edge, bb, xx) ** 2).sum()

    g1 = jax.grad(loss(xla_mod), argnums=(0, 1, 2))(params, basis, x)
    g2 = jax.grad(loss(bx_mod), argnums=(0, 1, 2))(params, basis, x)
    for a, b2 in zip(jax.tree_util.tree_leaves(g1),
                     jax.tree_util.tree_leaves(g2)):
        s = float(jnp.abs(a).max()) + 1e-9
        assert jnp.abs(a - b2).max() / s < 1e-4, (d_in, d_out)


def test_convse3_fuse_basis_group_path():
    """ConvSE3(shared_radial_hidden=True, fuse_basis=True) — one
    basis-fused launch per pair over the SAME param tree as the group
    concat path — matches it in values and parameter gradients."""
    from se3_transformer_tpu.ops import ConvSE3, Fiber
    from se3_transformer_tpu.utils import batched_index_select

    rng = np.random.RandomState(13)
    n, k, dim, degrees = 12, 4, 6, 3
    fiber = Fiber.create(degrees, dim)
    feats = {str(d): jnp.asarray(rng.normal(size=(1, n, dim, 2 * d + 1)),
                                 jnp.float32) for d in range(degrees)}
    coors = jnp.asarray(rng.normal(size=(1, n, 3)) * 2, jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (1, n, k)), jnp.int32)
    mask = jnp.ones((1, n, k), bool)
    coors_j = batched_index_select(coors, idx, axis=1)
    rel = coors[:, :, None, :] - coors_j
    rd = jnp.linalg.norm(rel, axis=-1)
    basis = get_basis(rel, degrees - 1)
    args = (feats, (idx, mask, None), rd, basis)

    group = ConvSE3(fiber, fiber, shared_radial_hidden=True, pallas=False,
                    pool=False, self_interaction=False)
    params = group.init(jax.random.PRNGKey(0), *args)
    bx = ConvSE3(fiber, fiber, shared_radial_hidden=True, pallas=False,
                 pallas_interpret=True, fuse_basis=True,
                 pool=False, self_interaction=False)

    out_g = group.apply(params, *args)
    out_b = bx.apply(params, *args)
    for d in out_g:
        assert np.abs(np.asarray(out_g[d]) - np.asarray(out_b[d])).max() \
            < 1e-4, d

    def loss(mod):
        return lambda p: sum((mod.apply(p, *args)[d] ** 2).sum()
                             for d in map(str, range(degrees)))

    g1 = jax.grad(loss(group))(params)
    g2 = jax.grad(loss(bx))(params)
    for a, b2 in zip(jax.tree_util.tree_leaves(g1),
                     jax.tree_util.tree_leaves(g2)):
        s = float(jnp.abs(a).max()) + 1e-9
        assert jnp.abs(a - b2).max() / s < 1e-4


# ------------------------------------------------------------------ #
# basis-fused backward (V2 and dx in VMEM only)
# ------------------------------------------------------------------ #

@RDT
@pytest.mark.parametrize('d_in,d_out,O,cb', [
    (i, o, 6, 8) for i in range(4) for o in range(4)] + [
    # one chunk of 16 channels: two sublane tiles to a row group of the
    # stack, at P = 1 (F = 1) and at the widest pair
    (1, 0, 6, 16), (3, 3, 6, 16),
    # the cell's keys and values: O = 24, nothing padded
    (2, 3, 24, 8),
    # kernel A's rolled o loop: two passes of _O_PER_PASS and a shorter one
    (0, 1, 72, 8),
])
def test_fused_bwd_bxf_kernel_matches_einsum(d_in, d_out, O, cb, rdt,
                                             monkeypatch):
    """Both launches of the basis-fused backward against the einsum VJP,
    at every (P, Q, F) of degrees 0..3: three e-blocks of 128 for 300
    edges and two c-chunks of 8 for 13 channels (the pick is pinned, so
    both accumulations revisit and both axes are padded), O = 6 padded to
    the sublane tile. Kernel A's stacks lie in tiles of 8 channels, so a
    chunk of 16 is two tiles to a row group. dx and dbasis are float32 reductions of dV2
    and hold the float32 tolerance under bfloat16 h as well."""
    import functools
    from se3_transformer_tpu.kernels import pallas_pairwise as pp
    P, Q, F = 2 * d_out + 1, 2 * d_in + 1, 2 * min(d_in, d_out) + 1
    E, mid, C = 300, 16, 13
    h, w3, b3, _, g = _bwd_case(5 + 4 * d_in + d_out, E, mid, C * F, O, P,
                                rdt)
    rng = np.random.RandomState(7)
    basis = jnp.asarray(rng.normal(size=(E, P * F * Q)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(E, C, Q)), jnp.float32)

    monkeypatch.setattr(pp, '_pick_blocks_bxf_bwd',
                        lambda *a, **k: (128, cb))
    got = jax.jit(functools.partial(
        pp._fused_pairwise_conv_bwd_bxf_impl, pqf=(P, Q, F),
        interpret=True, precision=None))(h, w3, b3, basis, x, g)
    assert all(t.dtype == jnp.float32 for t in got)

    b4 = basis.reshape(E, P, F, Q)

    def exact(round_dr):
        with jax.default_matmul_precision('highest'):
            v2 = jnp.einsum('epfq,ecq->epcf', b4, x).reshape(E, P, C * F)
            dh, dw3, dv2, db3 = _bwd_einsum(h, w3, b3, v2, g, round_dr)
            dv2 = dv2.reshape(E, P, C, F)
            return (dh, dw3, db3,
                    jnp.einsum('ecq,epcf->epfq', x, dv2).reshape(E, -1),
                    jnp.einsum('epfq,epcf->ecq', b4, dv2))

    names = ('dh', 'dw3', 'db3', 'dbasis', 'dx')
    for n, a, q, b in zip(names, got, exact(rdt != 'f32'), exact(False)):
        assert a.shape == b.shape, (n, a.shape, b.shape)
        if rdt == 'f32' or n in ('db3', 'dbasis', 'dx'):
            assert _rel(a, b) < 2e-5, (n, _rel(a, b))
        else:  # see _assert_bwd_matches
            assert _rel(a, q) < 5e-4, (n, _rel(a, q))
            assert _rel(a, b) < 1e-2, (n, _rel(a, b))


@pytest.mark.parametrize('F,O,cb', [(1, 6, 8), (3, 5, 8), (7, 24, 16)])
def test_bxf_stack_order_round_trips(F, O, cb):
    """The wrapper's stack order for kernel A, w3 -> rows of the R / dR
    stacks -> dw3: with t = c // 8, row ((f*(cb/8) + t)*O + o)*8 + c % 8
    of chunk n holds w3[:, (n*cb + c)*F + f, o], so that the 8 channels
    of one (f, t, o) are one sublane tile, and _unstack_rows puts every
    row back where it came from (a permutation got wrong is a gradient on
    the wrong weight, which a norm would not show)."""
    from se3_transformer_tpu.kernels import pallas_pairwise as pp
    n_c, mid = 2, 3
    w3 = jnp.arange(mid * n_c * cb * F * O, dtype=jnp.float32).reshape(
        mid, n_c * cb * F, O)
    rows = pp._stack_rows(w3.transpose(1, 2, 0), cb, F)
    assert rows.shape == (n_c, F * O * cb, mid)
    got, want = np.asarray(rows), np.asarray(w3)
    for n, f, o, c in [(0, 0, 0, 0), (1, F - 1, O - 1, cb - 1),
                       (1, F // 2, 2, 5), (0, F - 1, 1, 7)]:
        row = ((f * (cb // 8) + c // 8) * O + o) * 8 + c % 8
        assert (got[n, row] == want[:, (n * cb + c) * F + f, o]).all()
    back = pp._unstack_rows(rows, cb, F, O).transpose(2, 0, 1)
    assert back.shape == w3.shape and (np.asarray(back) == want).all()
    # the bias column takes the same order
    b3 = w3[0]
    assert (np.asarray(pp._stack_rows(b3, cb, F)) == got[..., 0]).all()


def test_bxf_backward_pick_fits_and_is_recorded():
    """The block pick of the basis-fused backward: within its own VMEM
    model at the cell's sixteen pairs (C 64, O 24) and at conv_in /
    conv_out's O 64; whole tiles of 8 channels; written to the consult
    log under a kind of its own, once per launch pair."""
    from se3_transformer_tpu.kernels import pallas_pairwise as pp, tuning
    snap = tuning.snapshot()
    for O in (24, 64):
        for d_in in range(4):
            for d_out in range(4):
                pqf = (2 * d_out + 1, 2 * d_in + 1,
                       2 * min(d_in, d_out) + 1)
                be, cb = pp._pick_blocks_bxf_bwd(32768, 64, O, *pqf, 128)
                assert be in (128, 256, 512) and 64 % cb == 0 \
                    and cb % 8 == 0
                assert pp._vmem_bxf_bwd(be, cb, O, *pqf, 128) \
                    <= 18 * 2 ** 20
    new = tuning.consults_since(snap)
    assert {c['kernel'] for c in new} == {'bxf_bwd'}
    assert sum(c['count'] for c in new) == 32
    assert pp._pick_blocks_bxf_bwd(32768, 64, 24, 7, 7, 7, 128) == (512, 8)
    assert pp._pick_blocks_bxf_bwd(32768, 64, 24, 7, 1, 1, 128) == (512, 64)
    assert pp._pick_blocks_bxf_bwd(32768, 64, 64, 7, 7, 7, 128) == (128, 8)
    # a budget nothing fits gives the smallest legal blocks, not a loop
    assert pp._pick_blocks_bxf_bwd(300, 16, 8, 7, 7, 7, 16,
                                   vmem_budget=1) == (128, 8)


@pytest.mark.parametrize('differentiable_coors', [False, True])
def test_convse3_bxf_gradients_match_xla(differentiable_coors):
    """ConvSE3 on a flat basis with fuse_basis (one bxf launch per pair,
    the basis-fused backward behind each) against the XLA group path on
    the same parameter tree: values, parameter and feature gradients, and
    with a differentiable basis the gradient of the coordinates too."""
    from se3_transformer_tpu.ops import ConvSE3, Fiber
    from se3_transformer_tpu.utils import batched_index_select

    rng = np.random.RandomState(17)
    n, k, dim, degrees = 10, 4, 5, 3
    fiber = Fiber.create(degrees, dim)
    feats = {str(d): jnp.asarray(rng.normal(size=(1, n, dim, 2 * d + 1)),
                                 jnp.float32) for d in range(degrees)}
    coors = jnp.asarray(rng.normal(size=(1, n, 3)) * 2, jnp.float32)
    # no node is its own neighbour: the zero vector has no direction
    idx = jnp.asarray((np.arange(n)[:, None] + 1 + rng.randint(
        0, n - 1, (n, k))) % n, jnp.int32)[None]
    mask = jnp.ones((1, n, k), bool)

    def call(mod, layout, params, feats, coors):
        rel = coors[:, :, None, :] - batched_index_select(coors, idx, axis=1)
        basis = get_basis(rel, degrees - 1,
                          differentiable=differentiable_coors, layout=layout)
        args = (feats, (idx, mask, None), jnp.linalg.norm(rel, axis=-1),
                basis)
        if params is None:
            return mod.init(jax.random.PRNGKey(0), *args)
        out = mod.apply(params, *args)
        return sum((out[d] ** 2).sum() for d in out)

    kw = dict(shared_radial_hidden=True, pool=False, self_interaction=False)
    group = ConvSE3(fiber, fiber, pallas=False, **kw)
    bxf = ConvSE3(fiber, fiber, pallas=False, pallas_interpret=True,
                  fuse_basis=True, **kw)
    params = call(group, 'pqf', None, feats, coors)

    from se3_transformer_tpu.kernels import tuning
    argnums = (0, 1, 2) if differentiable_coors else (0, 1)
    v1, g1 = jax.value_and_grad(
        lambda *a: call(group, 'pqf', *a), argnums)(params, feats, coors)
    tuning.clear_kernel_caches()  # picks are logged when a launch traces
    snap = tuning.snapshot()
    v2, g2 = jax.value_and_grad(
        lambda *a: call(bxf, 'pfq_flat', *a), argnums)(params, feats, coors)
    # the counter: every pair's forward is the bxf launch, and every one
    # of them took the basis-fused backward
    picks = {kind: sum(c['count'] for c in tuning.consults_since(snap)
                       if c['kernel'] == kind)
             for kind in ('bxf', 'bxf_bwd', 'plain')}
    assert picks == {'bxf': degrees ** 2, 'bxf_bwd': degrees ** 2,
                     'plain': 0}
    assert abs(float(v1) - float(v2)) < 1e-4 * abs(float(v1))
    leaves1, tree1 = jax.tree_util.tree_flatten(g1)
    leaves2, tree2 = jax.tree_util.tree_flatten(g2)
    assert tree1 == tree2
    for a, b2 in zip(leaves1, leaves2):
        s = float(jnp.abs(a).max()) + 1e-9
        assert float(jnp.abs(a - b2).max()) / s < 1e-4
    if differentiable_coors:
        assert float(jnp.abs(g2[2]).max()) > 0


def test_flat_basis_layout_equivalence():
    """get_basis(layout='pfq_flat') holds exactly the structured values,
    (p, f, q)-ordered; unflatten_basis gives the reference [P, Q, F]
    shape back and flatten_basis is its inverse, both ways round."""
    from se3_transformer_tpu.ops.conv import flatten_basis, unflatten_basis

    rng = np.random.RandomState(3)
    rel = jnp.asarray(rng.normal(size=(2, 6, 4, 3)), jnp.float32)
    deg = 2
    structured = get_basis(rel, deg)
    flat = get_basis(rel, deg, layout='pfq_flat')
    for d_in in range(deg + 1):
        for d_out in range(deg + 1):
            key = f'{d_in},{d_out}'
            P, Q = 2 * d_out + 1, 2 * d_in + 1
            F = 2 * min(d_in, d_out) + 1
            assert flat[key].shape == (2, 6, 4, P * F * Q)
            back = unflatten_basis(flat[key], P, Q, F)
            assert np.array_equal(np.asarray(back),
                                  np.asarray(structured[key]))
            assert np.array_equal(np.asarray(flatten_basis(back)),
                                  np.asarray(flat[key]))
            assert np.array_equal(
                np.asarray(flatten_basis(structured[key])),
                np.asarray(flat[key]))


def test_model_flat_basis_matches_structured():
    """Model-level: the fuse_basis model (which now feeds the flat basis
    layout into the bxf kernel) is numerically identical to the same
    params on the plain path, including coordinate gradients
    (differentiable_coors exercises dbasis)."""
    from se3_transformer_tpu import SE3TransformerModule

    rng = np.random.RandomState(11)
    feats = jnp.asarray(rng.normal(size=(1, 12, 8)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, 12, 3)), jnp.float32)
    mask = jnp.ones((1, 12), bool)
    base = dict(dim=8, depth=1, attend_self=True, num_neighbors=4,
                num_degrees=3, output_degrees=2, heads=2, dim_head=4,
                shared_radial_hidden=True, differentiable_coors=True)
    plain = SE3TransformerModule(**base, pallas=False)
    fused = SE3TransformerModule(**base, pallas=False,
                                 pallas_interpret=True, fuse_basis=True)
    params = plain.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                        return_type=1)['params']
    o1 = plain.apply({'params': params}, feats, coors, mask=mask,
                     return_type=1)
    o2 = fused.apply({'params': params}, feats, coors, mask=mask,
                     return_type=1)
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() < 2e-5

    gc1 = jax.grad(lambda c: (plain.apply(
        {'params': params}, feats, c, mask=mask, return_type=1) ** 2
    ).sum())(coors)
    gc2 = jax.grad(lambda c: (fused.apply(
        {'params': params}, feats, c, mask=mask, return_type=1) ** 2
    ).sum())(coors)
    s = float(jnp.abs(gc1).max()) + 1e-9
    assert np.abs(np.asarray(gc1) - np.asarray(gc2)).max() / s < 1e-4


def test_model_fuse_basis_matches_base():
    """Full model wiring: fuse_basis=True (interpreter kernels) output
    identical to the plain path, shared and unshared radial trunks."""
    from se3_transformer_tpu import SE3TransformerModule

    rng = np.random.RandomState(5)
    feats = jnp.asarray(rng.normal(size=(1, 16, 8)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, 16, 3)), jnp.float32)
    mask = jnp.ones((1, 16), bool)

    for shared in (False, True):
        base = dict(dim=8, depth=1, attend_self=True, num_neighbors=5,
                    num_degrees=3, output_degrees=2, heads=2, dim_head=4,
                    shared_radial_hidden=shared)
        plain = SE3TransformerModule(**base, pallas=False)
        fused = SE3TransformerModule(**base, pallas=False,
                                     pallas_interpret=True, fuse_basis=True)
        params = plain.init(jax.random.PRNGKey(0), feats, coors, mask=mask,
                            return_type=1)['params']
        o1 = plain.apply({'params': params}, feats, coors, mask=mask,
                         return_type=1)
        o2 = fused.apply({'params': params}, feats, coors, mask=mask,
                         return_type=1)
        assert np.abs(np.asarray(o1) - np.asarray(o2)).max() < 2e-5, shared


def test_fuse_basis_composes_with_edge_chunks_and_radial_bf16():
    """All three conv perf knobs at once (basis-fused kernel, node-axis
    streaming, bf16 radial): matches the plain XLA path, grads finite."""
    rng = np.random.RandomState(17)
    d_in, d_out, ci, co = 1, 1, 4, 5
    b, n, k = 1, 8, 3
    edge = jnp.asarray(rng.normal(size=(b, n, k, 2)), jnp.float32)
    rel = jnp.asarray(rng.normal(size=(b, n, k, 3)), jnp.float32)
    basis = get_basis(rel, 1)[f'{d_in},{d_out}']
    x = jnp.asarray(rng.normal(size=(b, n, k, ci, 3)), jnp.float32)

    plain = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False)
    params = plain.init(jax.random.PRNGKey(0), edge, basis, x)
    out_ref = plain.apply(params, edge, basis, x)

    combo = PairwiseConvSE3(d_in, ci, d_out, co, pallas=False,
                            pallas_interpret=True, fuse_basis=True,
                            edge_chunks=4, radial_bf16=True)
    out = combo.apply(params, edge, basis, x)
    rel_err = float(jnp.abs(out - out_ref).max()
                    / (jnp.abs(out_ref).max() + 1e-9))
    assert rel_err < 3e-2, rel_err  # bf16 value noise only

    g = jax.grad(lambda p: (combo.apply(p, edge, basis, x) ** 2).sum())(
        params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert bool(jnp.isfinite(leaf).all())


def test_pairwise_block_picker_production_validated_picks():
    """Pin the picker outputs the step validated: the conservative
    flagship's chunked plain contraction runs at (512, 8) — a
    sweep-derived flip to (256, 32) ran 2.7x slower in the step
    although the kernel alone ranks those blocks the other way around.
    Changing these picks needs a run of the benchmark's cell on the
    chip, not a kernel-level sweep; see the _pick_blocks docstring."""
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        _pick_blocks, _pick_blocks_bx,
    )
    # conservative flagship fwd, chunked (E=4096/chunk) and unchunked:
    # (512, 16) benched +13.5% over (512, 8); block_if=32 benched 2.7x
    # SLOWER — the pick is a measured local optimum, not a monotone knob
    assert _pick_blocks(4096, 1024, 64, 7, 128) == (512, 16)
    assert _pick_blocks(32768, 1024, 64, 7, 128) == (512, 16)
    # the backward keeps the 6 MiB budget and the (512, 8) pick the
    # winning A/B arms actually ran with. The stacked-dR scratch of both
    # backward kernels (PR 25) is bif*O*block_e*4 bytes, 1 MiB here: the
    # model already counts two such tiles and the backward held one (R),
    # so no pick moved. With a term of its own this one would read 6.47
    # MiB and fall to (256, 8), which no end-to-end run has validated
    assert _pick_blocks(4096, 1024, 64, 7, 128, bwd=True) == (512, 8)
    # d4_onehead_train's keys and values at the (3,3) pair, one head of
    # 24: 16 * 24 = 384 stacked rows, three full MXU tiles
    assert _pick_blocks(32768, 448, 24, 7, 128, bwd=True) == (512, 16)
    # flagship_fast bxf shape (within 2% of the sweep's best override)
    assert _pick_blocks_bx(32768, 64, 64, 7, 7, 7, 128) == (128, 8)
    # tiny shapes keep the full-axis fast path
    assert _pick_blocks(128, 16, 8, 3, 32) == (128, 16)
