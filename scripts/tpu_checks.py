"""On-TPU validation: equivariance + Pallas numerics + kernel speedup.

Runs on the real chip (the pytest suite runs on a simulated CPU mesh).
Checks:
  1. model equivariance at f32 matmul precision (<1e-4, the reference's
     acceptance bound) — TPU's default bf16 matmuls are also measured for
     reference;
  2. Pallas fused pairwise kernel vs XLA einsum path numerics;
  3. wall-clock of the pallas path vs the XLA path on a conv-heavy config.

Usage: python scripts/tpu_checks.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
from se3_transformer_tpu.utils.helpers import fetch_sync_tail
import jax.numpy as jnp
import numpy as np

from se3_transformer_tpu.models.se3_transformer import SE3TransformerModule


def check_equivariance(precision: str, radial_bf16: bool = False):
    from se3_transformer_tpu.utils.validation import equivariance_l2

    module = SE3TransformerModule(
        dim=16, depth=1, attend_self=True, num_neighbors=8, num_degrees=3,
        output_degrees=2, fourier_encode_dist=True,
        radial_bf16=radial_bf16)
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.normal(size=(1, 32, 16)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, 32, 3)), jnp.float32)
    mask = jnp.ones((1, 32), bool)
    # jit the init: eager init dispatches thousands of tiny ops one by
    # one (minutes); one compiled program is seconds
    init_fn = jax.jit(module.init, static_argnames=('return_type',))
    with jax.default_matmul_precision(precision):
        params = init_fn(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         return_type=1)['params']
    err = equivariance_l2(module, params, feats, coors, mask,
                          precision=precision)
    apply_fn = jax.jit(module.apply, static_argnames=('return_type',))
    scale = float(np.abs(np.asarray(apply_fn(
        {'params': params}, feats, coors, mask=mask, return_type=1))).max())
    return err, err / max(scale, 1e-12)


def check_equivariance_sparse_only(precision: str = 'float32'):
    """The sparse-neighbors-only config: the reference runs its analogue in
    float64 (tests/test_equivariance.py:234-260); on TPU there is no x64,
    so this config needs its own f32 tolerance check on chip."""
    from se3_transformer_tpu.utils.validation import equivariance_l2

    module = SE3TransformerModule(
        dim=16, depth=1, attend_self=True, num_degrees=2, output_degrees=2,
        num_neighbors=0, attend_sparse_neighbors=True, num_adj_degrees=2,
        adj_dim=4)
    rng = np.random.RandomState(0)
    n = 32
    feats = jnp.asarray(rng.normal(size=(1, n, 16)), jnp.float32)
    coors = jnp.asarray(rng.normal(size=(1, n, 3)), jnp.float32)
    mask = jnp.ones((1, n), bool)
    seq = np.arange(n)
    adj = jnp.asarray((seq[:, None] >= seq[None, :] - 1)
                      & (seq[:, None] <= seq[None, :] + 1))
    init_fn = jax.jit(module.init, static_argnames=('return_type',))
    with jax.default_matmul_precision(precision):
        params = init_fn(jax.random.PRNGKey(0), feats, coors, mask=mask,
                         adj_mat=adj, return_type=1)['params']
    return equivariance_l2(module, params, feats, coors, mask,
                           precision=precision, adj_mat=adj)


def bench_conv(pallas: bool, n=512, k=24, dim=32, degrees=3, iters=10,
               fuse_basis=False, radial_bf16=False):
    from se3_transformer_tpu.basis import get_basis
    from se3_transformer_tpu.ops import ConvSE3, Fiber
    from se3_transformer_tpu.utils import batched_index_select

    rng = np.random.RandomState(0)
    fiber = Fiber.create(degrees, dim)
    feats = {str(d): jnp.asarray(rng.normal(size=(1, n, dim, 2 * d + 1)),
                                 jnp.float32) for d in range(degrees)}
    coors = jnp.asarray(rng.normal(size=(1, n, 3)) * 3, jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (1, n, k)), jnp.int32)
    mask = jnp.ones((1, n, k), bool)

    conv = ConvSE3(fiber, fiber, pallas=pallas, fuse_basis=fuse_basis,
                   radial_bf16=radial_bf16)

    # jit the input prep: eager gathers/basis would dispatch thousands of
    # tiny ops one by one (minutes). fuse_basis
    # measures the FLAT basis layout — what the model actually feeds the
    # bxf kernel since round 4 (docs/DESIGN.md §2a)
    layout = 'pfq_flat' if fuse_basis else 'pqf'

    @jax.jit
    def prep(coors):
        coors_j = batched_index_select(coors, idx, axis=1)
        rel_pos = coors[:, :, None, :] - coors_j
        rel_dist = jnp.linalg.norm(rel_pos, axis=-1)
        basis = get_basis(rel_pos, degrees - 1, layout=layout)
        return rel_dist, basis

    rel_dist, basis = prep(coors)
    args = (feats, (idx, mask, None), rel_dist, basis)
    params = jax.jit(conv.init)(jax.random.PRNGKey(0), *args)
    fwd = jax.jit(lambda p, a: conv.apply(p, *a))
    out = jax.block_until_ready(fwd(params, args))
    fetch_sync_tail(out)  # warm the gating fetch (its own tiny program)

    t0 = time.time()
    for _ in range(iters):
        out = fwd(params, args)
    fetch_sync_tail(out)  # one-element host fetch gates completion
    dt = (time.time() - t0) / iters

    # numerics comparison at f32 precision (timing above uses the default
    # policy both paths share)
    with jax.default_matmul_precision('float32'):
        out = jax.jit(lambda p, a: conv.apply(p, *a))(params, args)
    return dt, jax.block_until_ready(out)


def check_fused_backward(n=256, k=16, dim=24, degrees=3,
                         interpret=False):
    """Pallas fwd+bwd vs XLA gradients on-chip (the interpret-mode tests
    cover logic; this covers Mosaic lowering)."""
    from se3_transformer_tpu.basis import get_basis
    from se3_transformer_tpu.ops import ConvSE3, Fiber
    from se3_transformer_tpu.utils import batched_index_select

    rng = np.random.RandomState(0)
    fiber = Fiber.create(degrees, dim)
    feats = {str(d): jnp.asarray(rng.normal(size=(1, n, dim, 2 * d + 1)),
                                 jnp.float32) for d in range(degrees)}
    coors = jnp.asarray(rng.normal(size=(1, n, 3)) * 3, jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (1, n, k)), jnp.int32)
    mask = jnp.ones((1, n, k), bool)
    @jax.jit
    def prep(coors):
        coors_j = batched_index_select(coors, idx, axis=1)
        rel = coors[:, :, None, :] - coors_j
        rd = jnp.linalg.norm(rel, axis=-1)
        return rd, get_basis(rel, degrees - 1)

    rd, basis = prep(coors)

    conv_pl = ConvSE3(fiber, fiber, pallas=False,
                      pallas_interpret=True) if interpret \
        else ConvSE3(fiber, fiber, pallas=True)
    conv_x = ConvSE3(fiber, fiber, pallas=False)
    params = jax.jit(conv_x.init)(jax.random.PRNGKey(0), feats,
                                  (idx, mask, None), rd, basis)

    def loss(conv):
        return lambda p: sum(
            (conv.apply(p, feats, (idx, mask, None), rd, basis)[d] ** 2).sum()
            for d in map(str, range(degrees)))

    # gate gradients at f32 matmul precision (the policy the equivariance
    # bound is stated at); the default-policy path is timed in bench_conv
    with jax.default_matmul_precision('float32'):
        g_pl = jax.jit(jax.grad(loss(conv_pl)))(params)
        g_x = jax.jit(jax.grad(loss(conv_x)))(params)
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(g_pl),
                    jax.tree_util.tree_leaves(g_x)):
        scale = float(jnp.abs(b).max()) + 1e-9
        worst = max(worst, float(jnp.abs(a - b).max()) / scale)
    return worst


def bench_attention(variant: str, B=1, h=8, n=1024, J=33, D=56, iters=20):
    """Attention path comparison at a flagship per-degree shape
    (D = dim_head*(2*deg+1) with dim_head=8 -> 8/24/40/56; J = k+1 kv
    slots) — the model dispatches one kernel per degree. Variants:
    'xla' einsum path, 'fused' D-on-lanes kernel (the J-on-lanes
    experiment was retired round 4 — decision table in
    kernels/pallas_attention.py)."""
    from se3_transformer_tpu.kernels.pallas_attention import (
        attention_reference, fused_attention,
    )
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.normal(size=(B * h, n, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B * h, n, J, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B * h, n, J, D)), jnp.float32)
    mask = jnp.asarray(rng.rand(B, n, J) > 0.2)
    mask = mask.at[:, :, 0].set(True)
    scale = D ** -0.5

    impl = dict(
        xla=lambda q, k, v: attention_reference(q, k, v, mask, scale),
        fused=lambda q, k, v: fused_attention(q, k, v, mask, h, scale),
    )[variant]
    fn = jax.jit(impl)
    out = jax.block_until_ready(fn(q, k, v))
    fetch_sync_tail(out)  # warm the gating fetch (its own tiny program)
    t0 = time.time()
    for _ in range(iters):
        out = fn(q, k, v)
    fetch_sync_tail(out)  # one-element host fetch gates completion
    return (time.time() - t0) / iters, out


def main():
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    enable_compilation_cache()
    print(f'backend: {jax.default_backend()}')

    for prec in ('float32', 'bfloat16'):
        err, rel = check_equivariance(prec)
        status = 'PASS' if (prec != 'float32' or err < 1e-4) else 'FAIL'
        print(f'equivariance @ matmul_precision={prec}: abs={err:.2e} '
              f'rel={rel:.2e} [{status if prec == "float32" else "info"}]')

    err_rb, rel_rb = check_equivariance('float32', radial_bf16=True)
    print(f'equivariance @ f32 + radial_bf16: abs={err_rb:.2e} '
          f'rel={rel_rb:.2e} [{"PASS" if err_rb < 1e-4 else "FAIL"}]')

    err_sp = check_equivariance_sparse_only()
    print(f'equivariance sparse-only @ f32: abs={err_sp:.2e} '
          f'[{"PASS" if err_sp < 1e-4 else "FAIL"}]')

    gworst = check_fused_backward()
    print(f'fused bwd vs XLA grads: rel={gworst:.2e} '
          f'[{"PASS" if gworst < 1e-4 else "FAIL"}]')

    t_xla, out_xla = bench_conv(pallas=False)
    t_pl, out_pl = bench_conv(pallas=True)
    diff = max(float(jnp.abs(out_xla[d] - out_pl[d]).max())
               for d in out_xla)
    print(f'ConvSE3 fwd: xla {t_xla*1e3:.1f} ms, pallas {t_pl*1e3:.1f} ms '
          f'({t_xla/t_pl:.2f}x), max|diff|={diff:.2e} '
          f'[{"PASS" if diff < 1e-3 else "FAIL"}]')

    t_bx, out_bx = bench_conv(pallas=True, fuse_basis=True)
    diff = max(float(jnp.abs(out_xla[d] - out_bx[d]).max())
               for d in out_xla)
    print(f'ConvSE3 fwd fuse_basis: {t_bx*1e3:.1f} ms '
          f'({t_xla/t_bx:.2f}x vs xla, {t_pl/t_bx:.2f}x vs pallas), '
          f'max|diff|={diff:.2e} [{"PASS" if diff < 1e-3 else "FAIL"}]')

    t_rb, out_rb = bench_conv(pallas=True, fuse_basis=True,
                              radial_bf16=True)
    scale = max(float(jnp.abs(out_xla[d]).max()) for d in out_xla)
    diff = max(float(jnp.abs(out_xla[d] - out_rb[d]).max())
               for d in out_xla) / scale
    print(f'ConvSE3 fwd fuse_basis+radial_bf16: {t_rb*1e3:.1f} ms '
          f'({t_xla/t_rb:.2f}x vs xla), rel diff={diff:.2e} '
          f'[{"PASS" if diff < 3e-2 else "FAIL"}]')

    # attention numerics + wall-clock at every flagship per-degree
    # shape. Layout DECIDED round 4 (retirement table in
    # kernels/pallas_attention.py): XLA is the attention path; the
    # D-on-lanes kernel stays the validated opt-in.
    for D in (8, 24, 40, 56):
        t_ax, out_ax = bench_attention('xla', D=D)
        t_af, out_af = bench_attention('fused', D=D)
        adiff = float(jnp.abs(out_ax - out_af).max())
        ok = adiff < 1e-3
        print(f'attention fwd D={D}: xla {t_ax*1e3:.2f} ms, '
              f'fused(D-lanes) {t_af*1e3:.2f} ms ({t_ax/t_af:.2f}x), '
              f'max|diff| fused={adiff:.2e} '
              f'[{"PASS" if ok else "FAIL"}]')


if __name__ == '__main__':
    main()
