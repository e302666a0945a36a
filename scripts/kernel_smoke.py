"""Fast on-chip smoke test of the Pallas kernels (Mosaic lowering + numerics).

Small shapes so compiles are quick; the full validation lives in
scripts/tpu_checks.py. Exits nonzero on any failure.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def check(name, a, b, tol=1e-4):
    scale = float(jnp.abs(b).max()) + 1e-9
    rel = float(jnp.abs(a - b).max()) / scale
    ok = rel < tol
    print(f'{name}: rel={rel:.2e} [{"PASS" if ok else "FAIL"}]')
    return ok


def main():
    from se3_transformer_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    enable_compilation_cache()
    print('backend:', jax.default_backend())
    rng = np.random.RandomState(0)
    ok = True

    # --- pairwise conv kernel, a few shape classes ---
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv, fused_pairwise_conv_bwd,
    )
    # mid=128 is the production value since the bias un-folding (the
    # bias is a [S, 1] operand now, not a 129th contraction row); the
    # smoke MUST cover the in-kernel lane-broadcast add and the db3
    # lane-reduce on real Mosaic
    # (64, 100, ...) keeps one deliberately sublane-UNALIGNED mid in the
    # on-chip gate: mid_dim is user-settable and the mid % 8 != 0 padding
    # path must stay covered on real Mosaic
    for (E, mid, IF, O, P) in [(300, 128, 24, 8, 5), (64, 100, 280, 20, 7),
                               (1000, 128, 56, 8, 7)]:
        h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
        w3 = jnp.asarray(rng.normal(size=(mid, IF, O)), jnp.float32)
        b3 = jnp.asarray(rng.normal(size=(IF, O)), jnp.float32)
        v2 = jnp.asarray(rng.normal(size=(E, P, IF)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(E, P, O)), jnp.float32)

        with jax.default_matmul_precision('highest'):
            ref = jnp.einsum('epk,eko->epo', v2,
                             jnp.einsum('em,mko->eko', h, w3) + b3)
        out = fused_pairwise_conv(h, w3, v2, b3=b3, precision='highest')
        ok &= check(f'pairwise fwd E={E} IF={IF} O={O} P={P}', out, ref)

        def f(h, w3, b3, v2):
            r = jnp.einsum('em,mko->eko', h, w3) + b3
            return (jnp.einsum('epk,eko->epo', v2, r) * g).sum()

        with jax.default_matmul_precision('highest'):
            dh_r, dw3_r, db3_r, dv2_r = jax.grad(
                f, argnums=(0, 1, 2, 3))(h, w3, b3, v2)
        dh, dw3, dv2, db3 = fused_pairwise_conv_bwd(h, w3, v2, g, b3=b3,
                                                    precision='highest')
        ok &= check(f'pairwise bwd dh  E={E}', dh, dh_r)
        ok &= check(f'pairwise bwd dw3 E={E}', dw3, dw3_r)
        ok &= check(f'pairwise bwd dv2 E={E}', dv2, dv2_r)
        ok &= check(f'pairwise bwd db3 E={E}', db3, db3_r)

    # --- radial_bf16 operands under an fp32 context precision: Mosaic
    # rejects contract_precision<fp32> on bf16 lhs ("Bad lhs type"); the
    # kernel must force DEFAULT (bf16 multiply, f32 accumulate) ---
    E, mid, IF, O, P = 300, 128, 24, 8, 5
    h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(mid, IF, O)), jnp.float32)
    b3 = jnp.asarray(rng.normal(size=(IF, O)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(E, P, IF)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        ref = jnp.einsum('epk,eko->epo', v2,
                         jnp.einsum('em,mko->eko', h, w3) + b3)
    with jax.default_matmul_precision('float32'):
        out = fused_pairwise_conv(h.astype(jnp.bfloat16),
                                  w3.astype(jnp.bfloat16), v2, b3=b3,
                                  precision='float32')
    ok &= check('pairwise fwd bf16-radial @ f32 ctx', out, ref, tol=3e-2)

    # --- basis-fused pairwise kernel, forward: the flat [E, P*F*Q]
    # basis the fast path feeds (Mosaic must lower the 2D-transposed bt) ---
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv_bxf,
    )
    for (E, mid, C, Q, F, O, P) in [(300, 128, 8, 3, 3, 8, 5),
                                    (64, 128, 9, 5, 3, 4, 5),
                                    (1000, 128, 8, 7, 7, 8, 7)]:
        h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
        w3 = jnp.asarray(rng.normal(size=(mid, C * F, O)), jnp.float32)
        b3 = jnp.asarray(rng.normal(size=(C * F, O)), jnp.float32)
        bas = jnp.asarray(rng.normal(size=(E, P, Q, F)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(E, C, Q)), jnp.float32)
        with jax.default_matmul_precision('highest'):
            v2 = jnp.einsum('epqf,ecq->epcf', bas, x).reshape(E, P, C * F)
            ref = jnp.einsum('epk,eko->epo', v2,
                             jnp.einsum('em,mko->eko', h, w3) + b3)
        flat = jnp.swapaxes(bas, -1, -2).reshape(E, P * F * Q)
        outf = fused_pairwise_conv_bxf(h, w3, flat, x, (P, Q, F), b3=b3,
                                       precision='highest')
        ok &= check(f'pairwise bxf fwd E={E} C={C} Q={Q} F={F}', outf, ref)

    # --- MXU one-hot gather vs jnp.take at a flagship-shaped gather:
    # the auto heuristic only fires on TPU, so CPU tests never see the
    # on-chip numerics of the matmul path ---
    from se3_transformer_tpu.utils.helpers import (
        _onehot_gather, _use_onehot_gather,
    )
    vals = jnp.asarray(rng.normal(size=(1, 1024, 64, 7)), jnp.float32)
    gidx = jnp.asarray(rng.randint(0, 1024, (1, 1024 * 33)), jnp.int32)
    if _use_onehot_gather(vals, gidx, 1):
        oh = jax.jit(_onehot_gather)(vals, gidx)
        tk = jax.jit(lambda v, i: jax.vmap(
            lambda vv, ii: jnp.take(vv, ii, axis=0))(v, i))(vals, gidx)
        ok &= check('onehot gather vs take (flagship shape)', oh, tk,
                    tol=1e-6)
    else:
        # run-everything contract: never abort the remaining canaries
        from se3_transformer_tpu.utils.helpers import is_tpu_backend
        print('onehot gather heuristic OFF at flagship shape '
              f'(backend={jax.default_backend()}) [FAIL]')
        ok &= not is_tpu_backend()

    # --- attention kernel ---
    from se3_transformer_tpu.kernels.pallas_attention import (
        attention_reference, fused_attention,
    )
    # the last two rows are FLAGSHIP-SHAPED (n=1024, J=33): round 3's
    # first session OOM'd scoped VMEM exactly there while the small
    # smoke shapes passed — the canary must cover the shapes the model
    # actually runs
    for (BH, BKV, n, J, D, masked) in [(8, 8, 100, 17, 24, True),
                                       (8, 1, 64, 33, 56, True),
                                       (4, 4, 128, 9, 8, False),
                                       (8, 8, 1024, 33, 64, True),
                                       (2, 2, 1024, 33, 8, True)]:
        q = jnp.asarray(rng.normal(size=(BH, n, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(BKV, n, J, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(BKV, n, J, D)), jnp.float32)
        B = 1
        heads = BH // B
        mask = None
        if masked:
            mask = jnp.asarray(rng.rand(B, n, J) > 0.2)
            mask = mask.at[:, :, 0].set(True)
        scale = D ** -0.5
        with jax.default_matmul_precision('highest'):
            ref = attention_reference(q, k, v, mask, scale)
        out = fused_attention(q, k, v, mask, heads, scale)
        ok &= check(f'attention BH={BH} BKV={BKV} J={J} D={D} '
                    f'mask={masked}', out, ref)

        gco = jnp.asarray(rng.normal(size=out.shape), jnp.float32)

        def f_ref(q, k, v):
            return (attention_reference(q, k, v, mask, scale) * gco).sum()

        def f_fused(q, k, v):
            return (fused_attention(q, k, v, mask, heads, scale)
                    * gco).sum()

        with jax.default_matmul_precision('highest'):
            refg = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        outg = jax.grad(f_fused, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip(('dq', 'dk', 'dv'), outg, refg):
            ok &= check(f'attention bwd {name} BH={BH} BKV={BKV} '
                        f'mask={masked}', a, b)

    print('ALL PASS' if ok else 'FAILURES')
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
