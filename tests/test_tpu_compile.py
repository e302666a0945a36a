"""The main path's kernels, asked of the TPU's own compiler without a TPU.

libtpu compiles for a chip that is DESCRIBED, not attached
(`jax.experimental.topologies`), so each case here raises what the chip's
compiler would raise — tile-illegal block specs, scoped-VMEM overflows,
ops Mosaic does not lower — at real widths, in a second or a few, at no
chip time. Interpret mode accepts all of that silently.

Nothing runs: these cases say a kernel COMPILES, never that it is right
or fast (the interpret-mode parity tests hold the numerics; a chip run
holds the rest). Code that asks `jax.default_backend()` still sees the
CPU here, so every case calls the kernel entry point itself.

Two processes cannot hold the TPU compiler plug-in at once (libtpu's
multi-process lockfile: "ABORTED: Internal error when accessing libtpu
multi-process lockfile") unless ALLOW_MULTIPLE_LIBTPU_LOAD is set, which
this file does before libtpu loads: a parallel test run (pytest-xdist)
spreads these cases over its workers, and a compile-only use never
touches a chip. The persistent compilation cache is off around the
cases — a deviceless executable can be written to it but not read back
without a chip.
"""
import base64
import json
import math
import os
import re

os.environ.setdefault('TPU_LOG_DIR', 'disabled')  # else libtpu logs to /tmp
os.environ.setdefault('ALLOW_MULTIPLE_LIBTPU_LOAD', '1')

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from se3_transformer_tpu.kernels import pallas_flash as pf  # noqa: E402
from se3_transformer_tpu.kernels.pallas_attention import (  # noqa: E402
    fused_attention,
)
from se3_transformer_tpu.kernels.pallas_pairwise import (  # noqa: E402
    fused_pairwise_conv, fused_pairwise_conv_bwd, fused_pairwise_conv_bwd_bxf,
    fused_pairwise_conv_bxf,
)

# the flagship shape tuples (tests/test_kernel_tuning.py pins the block
# picks at the same ones): dim=64, n=1024, k=32, degree 4
E, MID, O, P, Q, F, C = 32768, 128, 64, 7, 7, 7, 64
IF = 1024
ATT_N, ATT_J, ATT_D, ATT_HEADS = 1024, 33, 56, 8


@pytest.fixture(scope='module')
def v5e_2x2():
    """The four devices of a described v5e 2x2; skipped where libtpu
    cannot describe it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f'cannot describe a v5e topology here: {e}')
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update('jax_enable_compilation_cache', True)
    compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def v5e(v5e_2x2):
    """One described chip."""
    return SingleDeviceSharding(v5e_2x2[0])


def compile_for(device, fn, *shapes):
    """Lower `fn` at (shape, dtype) pairs placed on the described device
    and compile; returns the number of Mosaic calls in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=device) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('tpu_custom_call')


f32, bf16, i32, b1 = jnp.float32, jnp.bfloat16, jnp.int32, jnp.bool_

# (h, w3, bias) dtypes: f32, and the bf16 radial operands radial_bf16
# hands the kernels (ops/conv.py: bias and equivariant operands stay f32)
RADIAL = pytest.mark.parametrize('rdt', [f32, bf16],
                                 ids=['f32', 'radial_bf16'])


@RADIAL
def test_pairwise_forward_compiles(v5e, rdt):
    calls = compile_for(
        v5e, lambda h, w3, v2, b3: fused_pairwise_conv(h, w3, v2, b3),
        ((E, MID), rdt), ((MID, IF, O), rdt), ((E, P, IF), f32),
        ((IF, O), f32))
    assert calls > 0


@RADIAL
@pytest.mark.parametrize('p,n_if,o', [
    (P, IF, O),      # the flagship's trunk convolution
    # d4_onehead_train's keys / values (one head of 24) at the degree
    # pairs (3,3) and (0,3): the stacked-dR scratch is 384 rows there
    (7, 448, 24),
    (7, 64, 24),
])
def test_pairwise_backward_compiles(v5e, rdt, p, n_if, o):
    calls = compile_for(
        v5e,
        lambda h, w3, v2, g, b3: fused_pairwise_conv_bwd(h, w3, v2, g, b3),
        ((E, MID), rdt), ((MID, n_if, o), rdt), ((E, p, n_if), f32),
        ((E, p, o), f32), ((n_if, o), f32))
    assert calls == 2  # kernel A (dV2, dW3, dB3) and kernel B (dH)


@RADIAL
def test_pairwise_bxf_compiles(v5e, rdt):
    calls = compile_for(
        v5e,
        lambda h, w3, basis, x, b3: fused_pairwise_conv_bxf(
            h, w3, basis, x, (P, Q, F), b3),
        ((E, MID), rdt), ((MID, C * F, O), rdt), ((E, P * F * Q), f32),
        ((E, C, Q), f32), ((C * F, O), f32))
    assert calls > 0


@RADIAL
@pytest.mark.parametrize('d_in,d_out,o', [
    # d4_onehead_train's extremes, C = 64: keys / values (one head of 24)
    # at the widest pair, where R and dR are 1344 rows of 512 edges each,
    # and conv_out's 64 channels from degree 3, where Q is 7 and P*F 9
    (3, 3, 24),
    (3, 1, 64),
    # degree 0 in: Q = 1, and all 64 channels in one program
    (0, 3, 24),
    # the flagship recipe's widest pair (8 heads of 8): R and dR are 3584
    # rows, and only the smallest blocks fit
    (3, 3, 64),
])
def test_pairwise_bxf_backward_compiles(v5e, rdt, d_in, d_out, o):
    p, q, f = 2 * d_out + 1, 2 * d_in + 1, 2 * min(d_in, d_out) + 1
    calls = compile_for(
        v5e,
        lambda h, w3, basis, x, g, b3: fused_pairwise_conv_bwd_bxf(
            h, w3, basis, x, g, (p, q, f), b3),
        ((E, MID), rdt), ((MID, C * f, o), rdt), ((E, p * f * q), f32),
        ((E, C, q), f32), ((E, p, o), f32), ((C * f, o), f32))
    assert calls == 2  # A (dW3, dB3, dV2, dx) and B (dH), V2 built in both


def test_launches_are_named_by_role(v5e):
    """`name=` on pl.pallas_call is the innermost component of the op's
    name stack, and the chip's compiler takes that as the custom call's
    instruction name: the name a device trace shows. Forward, backward A
    (dV2, dW3, dB3) and backward B (dH) are three names, the old prefixes
    kept, and the degree pair rides in the op_name, not in the name."""
    import re
    e, c, o, p, q, f = 1024, 8, 8, 3, 3, 3

    def fn(h, w3, basis, x, v2, g, b3):
        with jax.named_scope('pair_1_1'):
            out = fused_pairwise_conv_bxf(h, w3, basis, x, (p, q, f), b3)
            # the basis-fused backward as a step without coordinate
            # gradients leaves it: the basis' cotangent dropped
            dh, dw3, db3, _, dx = fused_pairwise_conv_bwd_bxf(
                h, w3, basis, x, g, (p, q, f), b3)
            return (out, fused_pairwise_conv_bwd(h, w3, v2, g, b3),
                    dh, dw3, db3, dx)

    args = [jax.ShapeDtypeStruct(s, f32, sharding=v5e) for s in (
        (e, MID), (MID, c * f, o), (e, p * f * q), (e, c, q),
        (e, p, c * f), (e, p, o), (c * f, o))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = re.findall(r'%([\w.]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    names = sorted(re.sub(r'\.\d+$', '', n) for n, _ in calls)
    # the plain and the basis-fused backward share the two role names
    assert names == ['fused_pairwise_conv_bwd_a'] * 2 \
        + ['fused_pairwise_conv_bwd_b'] * 2 + ['fused_pairwise_conv_bxf']
    for name, op_name in calls:
        comps = op_name.split('/')
        assert comps[-1] == 'pallas_call' and name.startswith(comps[-2])
        assert 'pair_1_1' in comps and 'pairwise_layout' not in comps
    # the wrappers' relayouts are under their own leaf
    assert 'pairwise_layout/transpose' in text \
        or 'pairwise_layout/reshape' in text
    # V2, dV2 and dx never pass through XLA: with no gradient into the
    # basis nothing at all is left under the contraction's own leaf
    assert 'basis_contract' not in text


ATT_SHAPES = (((ATT_HEADS, ATT_N, ATT_D), f32),
              ((ATT_HEADS, ATT_N, ATT_J, ATT_D), f32),
              ((ATT_HEADS, ATT_N, ATT_J, ATT_D), f32),
              ((1, ATT_N, ATT_J), b1))


def test_fused_attention_forward_compiles(v5e):
    calls = compile_for(
        v5e, lambda q, k, v, m: fused_attention(
            q, k, v, m, ATT_HEADS, ATT_D ** -0.5), *ATT_SHAPES)
    assert calls > 0


def test_fused_attention_backward_compiles(v5e):
    def grads(q, k, v, m):
        return jax.grad(lambda q, k, v: fused_attention(
            q, k, v, m, ATT_HEADS, ATT_D ** -0.5).sum(),
            argnums=(0, 1, 2))(q, k, v)
    # the dq/dk/dv kernel alone: nothing reads the forward's output, so
    # XLA drops the forward call
    assert compile_for(v5e, grads, *ATT_SHAPES) > 0


def test_so2_contraction_compiles(v5e):
    """conv backend 'so2' for one degree-3 pair at flagship n, k, width:
    rotate-in, banded z, the radial apply through the plain kernel,
    rotate-out."""
    from se3_transformer_tpu.so2.contract import so2_pair_contract
    n, k, d = 1024, 32, 3
    edge = (1, n, k)

    def fn(h, w3, b3, x, ca, sa, cb, sb):
        frames = dict(cos_a=ca, sin_a=sa, cos_b=cb, sin_b=sb)
        return so2_pair_contract(h, w3, b3, frames, x, d_in=d, d_out=d,
                                 pallas=True, pallas_interpret=False,
                                 edge_chunks=None)
    calls = compile_for(
        v5e, fn, ((*edge, MID), f32), ((MID, C * F, O), f32),
        ((C * F, O), f32), ((*edge, C, Q), f32),
        *[((*edge, d + 1), f32)] * 4)
    assert calls > 0


@pytest.mark.xfail(strict=True, reason='INVALID_ARGUMENT: Custom emitter '
                   'for CustomSPMDPartitioning not found')
def test_pairwise_kernel_partitions_over_a_mesh(v5e_2x2):
    """A Pallas kernel inside a program jitted over the described 2x2
    (dp=2 x tp=2 operands). The kernels partition through
    jax.experimental.custom_partitioning, and this installation's TPU
    compiler (jax 0.9.0, libtpu 0.0.34) neither resolves that custom
    call nor can emit it — the chip answered `chip_smoke.py --chips 4`
    with the same words (PR 21), so it is the installation's limit, not
    the deviceless compile's. The same call partitions correctly on
    virtual CPU devices (tests/test_sharding.py). Strict: when this
    compiles, chip_smoke.py's mesh phase can have its kernels back."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps
    mesh = Mesh(np.asarray(v5e_2x2).reshape(2, 2), ('dp', 'tp'))
    e, i = 4096, 256

    def on(shape, *spec):
        return jax.ShapeDtypeStruct(shape, f32,
                                    sharding=NamedSharding(mesh, Ps(*spec)))
    text = jax.jit(
        lambda h, w3, v2, b3: fused_pairwise_conv(h, w3, v2, b3)).lower(
        on((e, MID), 'dp', None), on((MID, i, O), None, None, 'tp'),
        on((e, P, i), 'dp', None, None), on((i, O), None, 'tp'),
    ).compile().as_text()
    assert 'tpu_custom_call' in text and 'all-gather(' not in text


# --------------------------------------------------------------------- #
# the flash kernel: block specs are tile-legal; the body does not lower
# --------------------------------------------------------------------- #
FL_N, FL_K, FL_HEADS, FL_KVH, FL_DIM_HEAD, FL_S0 = 256, 16, 2, 1, 8, 2
FL_PAIRS, FL_DOUT, FL_L = ((0, 16), (1, 16)), 1, 2
FL_DH = FL_DIM_HEAD * (2 * FL_DOUT + 1)
FL_IF = sum(c * (2 * min(d, FL_DOUT) + 1) for d, c in FL_PAIRS)
FL_O = FL_KVH * FL_DIM_HEAD


def _flash_cfg(arm, mode):
    return pf.FlashConfig(
        pairs=FL_PAIRS, d_out=FL_DOUT, heads=FL_HEADS, kv_heads=FL_KVH,
        scale=FL_DIM_HEAD ** -0.5, arm_v=arm, arm_k=arm,
        prefix=FL_S0 if mode == 'knn' else 0, has_mask=True, mode=mode,
        exclude_self=mode == 'global', use_pallas=True)


def _compile_flash_knn(v5e, arm):
    n, k = FL_N, FL_K
    names = ['q', 'x0', 'x1', 'idx', 'nmask', 'h_v', 'h_k', 'wv', 'bv',
             'wk', 'bk', 'prefix_k', 'prefix_v']
    shapes = [((1, n, FL_HEADS, FL_DH), f32), ((1, n, 16, 1), f32),
              ((1, n, 16, 3), f32), ((1, n, k), i32), ((1, n, k), b1),
              ((1, n, k, MID), f32), ((1, n, k, MID), f32),
              ((MID, FL_IF, FL_O), f32), ((FL_IF, FL_O), f32),
              ((MID, FL_IF, FL_O), f32), ((FL_IF, FL_O), f32),
              ((1, n, FL_S0, FL_KVH * FL_DH), f32),
              ((1, n, FL_S0, FL_KVH * FL_DH), f32)]
    if arm == 'dense':
        names.append('sh')
        shapes.append(((1, n, k, (2 * FL_L + 1) ** 2), f32))
    else:
        names.append('fr')
        shapes.append(((1, n, k, 4 * (FL_L + 1)), f32))

    def fn(*arrays):
        ops = dict(zip(names, arrays))
        ops['xs'] = (ops.pop('x0'), ops.pop('x1'))
        return pf._flash_fwd_impl(_flash_cfg(arm, 'knn'), ops)
    return compile_for(v5e, fn, *shapes)


def _compile_flash_global(v5e):
    n = 512
    rp = [((1, MID), f32)] + [((1, MID), f32)] * 3 \
        + [((MID, MID), f32)] + [((1, MID), f32)] * 3

    def fn(q, x0, x1, coords, nodemask, wv, bv, *rp_v):
        ops = dict(q=q, xs=(x0, x1), coords=coords, nodemask=nodemask,
                   wv=wv, bv=bv, rp_v=tuple(rp_v))
        return pf._flash_fwd_impl(
            _flash_cfg('dense', 'global')._replace(tie=True), ops)
    return compile_for(
        v5e, fn, ((1, n, FL_HEADS, FL_DH), f32), ((1, n, 16, 1), f32),
        ((1, n, 16, 3), f32), ((1, n, 3), f32), ((1, n), b1),
        ((MID, FL_IF, FL_O), f32), ((FL_IF, FL_O), f32), *rp)


# strict: the day an arm compiles, its xfail fails, and the arm's entry
# in pallas_flash.MOSAIC_REFUSES (the selector's rule) goes with it
@pytest.mark.xfail(strict=True, reason=pf.MOSAIC_REFUSES['knn'])
@pytest.mark.parametrize('arm', ['dense', 'so2'])
def test_flash_knn_arm_compiles(v5e, arm):
    assert _compile_flash_knn(v5e, arm) > 0


@pytest.mark.xfail(strict=True, reason=pf.MOSAIC_REFUSES['global'])
def test_flash_global_arm_compiles(v5e):
    assert _compile_flash_global(v5e) > 0


@pytest.mark.parametrize('which', ['dense', 'so2', 'global'])
def test_flash_block_specs_are_tile_legal(v5e, which):
    """The repair this file was written for: the lowering used to stop
    at the idx / nmask / nodemask block specs ("the last two dimensions
    of your block shape are divisible by 8 and 128 ..."). Whatever the
    compiler refuses now, it is past the block specs."""
    with pytest.raises(Exception) as err:
        if which == 'global':
            _compile_flash_global(v5e)
        else:
            _compile_flash_knn(v5e, which)
    assert 'block shape' not in str(err.value)


@pytest.mark.parametrize('mode', ['knn', 'global'])
def test_flash_selector_is_an_explicit_rule(mode):
    """Unset, the selector picks the XLA stream (no compiler error is
    caught to get there); asked for outright, the kernel is refused with
    the compiler's message; interpret mode still runs it."""
    assert pf._resolve_pallas(None, False, mode) is False
    assert pf._resolve_pallas(None, True, mode) is True
    with pytest.raises(NotImplementedError) as err:
        pf._resolve_pallas(True, False, mode)
    assert pf.MOSAIC_REFUSES[mode] in str(err.value)


@pytest.mark.slow
def test_flagship_train_step_compiles_and_fits(v5e, monkeypatch):
    """chip_smoke.py's train program — value_and_grad + adam of
    recipes.flagship_fast(dim=64) at n=1024, k=32, degree 4, depth 6 —
    compiled for the chip (minutes): the Pallas kernels are in it and
    arguments + temporaries fit the v5e's 16 GiB."""
    import optax
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training import recipes
    from se3_transformer_tpu.utils import helpers

    # the auto-dispatch asks the default backend, which is the CPU here
    monkeypatch.setattr(helpers, 'is_tpu_backend', lambda: True)
    n, dim = 1024, 64
    module = recipes.flagship_fast(dim=dim, output_degrees=2,
                                   reduce_dim_out=True)

    def loss_fn(params, data, key):
        noise = jax.random.normal(key, data['coords'].shape)
        noised = data['coords'] + noise
        out = module.apply({'params': params}, data['seqs'], noised,
                           mask=data['masks'], return_type=1)
        return (((noised + out) - data['coords']) ** 2).sum(-1).mean(), {}

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    data = dict(seqs=jnp.zeros((1, n, dim)), coords=jnp.zeros((1, n, 3)),
                masks=jnp.ones((1, n), bool))
    optimizer = optax.adam(1e-4)
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), data['seqs'],
                            data['coords'], mask=data['masks'],
                            return_type=1)['params'])
    opt_state = jax.eval_shape(optimizer.init, params)
    compiled = make_sharded_train_step(loss_fn, optimizer).lower(
        on_chip(params), on_chip(opt_state), on_chip(data),
        on_chip(jax.random.PRNGKey(1))).compile()
    assert compiled.as_text().count('tpu_custom_call') > 0
    _assert_product_front_ends_agree(compiled)
    mem = compiled.memory_analysis()
    # donated state aliases its outputs
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16 * 2 ** 30, mem


# ------------------------------------------------------------------ #
# the token decoder's kernels, at the widths of the benchmark's cell
# ------------------------------------------------------------------ #
def test_streaming_attention_compiles_at_the_decoder_cells_size(v5e):
    """JAX's streaming kernel, causal, 20 heads of 256 over 8,192 tokens,
    blocks of 512, forward and both backward launches."""
    from se3_transformer_tpu.ops.latent_attention import (
        causal_attention_flash,
    )

    def loss(q, k, v):
        return causal_attention_flash(q, k, v, 1 / 16.0, 512).sum()

    assert compile_for(v5e, jax.grad(loss, argnums=(0, 1, 2)),
                       *[((1, 20, 8192, 256), jnp.float32)] * 3) == 3


def test_the_latent_core_compiles_at_the_decoder_cells_size(v5e, capsys):
    """The repo's two launches under the fourth rule, ('latent', 0), at the
    GLM cell's size: 20 heads of 256 (two lane rows a head, groups of one)
    over 8,192 positions, tiles of 512, token-major with the scale and the
    rounding `rounded_attention` leaves to XLA. Both lower for the chip
    within the VMEM they ask for, one launch each named `latent_core_*`,
    over the causal triangle's 136 tiles a head; no pass is launched, no
    head is laid out; the windows' VMEM is printed (the backward holds a
    head's dk and dv [8192, 256] float32 in two buffers each)."""
    from se3_transformer_tpu.kernels import pallas_block_attention as kernels

    t, heads, d, tile = 8192, 20, 256, 512
    assert kernels.launches_run(t, tile, heads, heads, d)
    assert not kernels.launches_run(4 * t, tile, heads, heads, d)

    def loss(q, k, v):
        return kernels.rounded_attention(
            q, k, v, d, d ** -0.5, ('latent', 0), tile).astype(f32).sum()

    x = jax.ShapeDtypeStruct((1, t, heads * d), f32, sharding=v5e)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    roles = re.findall(r'%(\w+_core_\w+|qk_pass_\w+)[.\d]* = [^\n]*'
                       r'tpu_custom_call', text)
    assert sorted(roles) == ['latent_core_bwd', 'latent_core_fwd'], roles
    assert text.count('s32[7,136]') >= 2 and 'flash' not in text
    assert f'f32[1,{heads},1,{t}]' in text
    assert not re.search(rf'\[1,{heads},{t},{d}\]|\[1,{t},{heads},{d}\]',
                         text)
    assert not re.search(r' (copy|transpose)\(', text[text.index('ENTRY'):])
    # two buffers a window: operand tiles in bfloat16, dq, dk, dv float32
    fwd = 2 * (4 * tile * d * 2 + tile * 4) \
        + (2 * tile * kernels.LANES + tile * d) * 4
    bwd = 2 * (5 * tile * d * 2 + tile * 4 + tile * d * 4
               + 2 * t * d * 4) + tile * 4
    with capsys.disabled():
        print(f'\nlatent core at 20 heads of 256 over 8,192: windows and '
              f'scratch {fwd / 2**20:.1f} MiB forward, {bwd / 2**20:.1f} MiB '
              f'backward, of {kernels.VMEM_LIMIT / 2**20:.0f} MiB')
    assert bwd < 40 * 2 ** 20 < kernels.VMEM_LIMIT


def test_grouped_products_are_native_on_the_chip(v5e):
    """`jax.lax.ragged_dot` and its two cotangents lower to the TPU's own
    grouped matrix product (one custom call each and one for the tile
    metadata), not to a dense product per group: the worst case, every
    pair held here, 32,768 rows over 8 experts."""
    from se3_transformer_tpu.ops.expert_layer import grouped_dot

    def loss(lhs, rhs, sizes):
        return grouped_dot(lhs, rhs, sizes, jnp.bfloat16).sum()

    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in (
        ((32768, 2048), jnp.float32), ((8, 2048, 1536), jnp.float32),
        ((8,), jnp.int32))]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('ragged-dot') >= 2 and 'while' not in text
    dense = 2 * 32768 * 2048 * 1536
    assert compiled.cost_analysis()['flops'] < 2.5 * dense


def _computations(text):
    """{name: body} of the computations of compiled HLO text."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r'^(?:ENTRY\s+)?%([\w.\-]+)\s+\(.*\{\s*$', line)
        if m:
            name = m.group(1)
            out[name] = []
        elif line.startswith('}'):
            name = None
        elif name:
            out[name].append(line)
    return {k: '\n'.join(v) for k, v in out.items()}


def _with_callees(comps, name):
    """The body of `name` and of everything it calls."""
    seen, todo = set(), [name]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [n for n in re.findall(r'%([\w.\-]+)', comps[c])
                     if n in comps]
    return '\n'.join(comps[c] for c in sorted(seen))


def _expert_layer_grad(v5e, n, d, on_a_tpu=True, **fields):
    """The gradient of an expert layer over x [n, d] as a recomputed block
    runs it, lowered for the chip, the layer's rules seeing a TPU or not."""
    from se3_transformer_tpu.ops import expert_layer
    layer = expert_layer.ExpertLayer(**fields)
    x = jax.ShapeDtypeStruct((n, d), f32, sharding=v5e)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)['params'])

    def loss(params, x):
        return jnp.square(jax.checkpoint(
            lambda p, x: layer.apply({'params': p}, x)[0])(params, x)).sum()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(expert_layer, 'is_tpu_backend', lambda: on_a_tpu)
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)


HYBRID_EXPERTS = dict(width=1856, n_experts=128, top_k=6, experts_held=8,
                      shared_width=3712, hidden_act='relu2', routed_scale=2.5)
GLM_EXPERTS = dict(width=1536, n_experts=64, top_k=4, experts_held=8,
                   shared_width=1536, hidden_act='silu', routed_scale=1.8)


@pytest.fixture(scope='module')
def hybrid_expert_layer(v5e):
    """The hybrid cell's expert layer, compiled: 8,192 tokens of 2,688."""
    return _expert_layer_grad(v5e, 8192, 2688,
                              **HYBRID_EXPERTS).compile().as_text()


def test_the_expert_layer_works_on_the_held_row_bound_at_the_hybrid_cells_size(
        hybrid_expert_layer):
    """The hybrid cell's expert layer as its recomputed block runs it, forward
    and backward: N * k = 49,152 pairs, `held_row_bound` 6,144. Each direction
    is one `conditional`; the forward one hands on its output alone (no
    branch's residuals, which the untaken one would fill with zeros); in the
    bounded branch of both the grouped products walk 6,144 rows, at the
    widths the chip's product wants (2,688 and 1,856 padded to 3,072 and
    2,048), and nothing has 49,152 rows but vectors (sort keys, permutations,
    a weight a pair)."""
    from se3_transformer_tpu.ops.expert_layer import held_row_bound
    n, k, d, width = 8192, 6, '3072', '2048'
    assert held_row_bound(n * k, 8, 128) == 6144
    assert held_row_bound(8192 * 4, 8, 64) == 8192       # the GLM cell's
    text = hybrid_expert_layer
    comps = _computations(text)
    conds = re.findall(
        r'= (\(.*?\)) conditional\(.*?branch_computations=\{%([\w.\-]+), '
        r'%([\w.\-]+)\}', text)
    # out [N, d]; the cotangents of x, the weights and the two matrices
    outputs = [len(re.findall(r'\w+\[[\d,]*\]', r)) for r, _, _ in conds]
    assert sorted(outputs) == [1, 4], conds
    for n_out, (_, full, bounded) in zip(outputs, conds):   # cond(fits, ...)
        n_products = {1: 2, 4: 6}[n_out]     # 2 forward; 2 again and 4 back
        for name, rows in ((bounded, 6144), (full, 49152)):
            body = _with_callees(comps, name)
            products = re.findall(
                r'%ragged-dot-none[\w.]* = f32\[(\d+),(\d+)', body)
            assert len(products) == n_products, (name, products)
            assert set(products) <= {(str(rows), width), (str(rows), d),
                                     ('8', width), ('8', d)}, (name, products)
            assert bool(re.search(r'\[49152,\d', body)) == (rows == 49152)
            assert re.search(r's32\[49152\]', body)


def _grouped_product_tiles(text):
    """{instruction: the operand dimensions of every `tpu.matmul` in its
    body} over the grouped products of compiled HLO text: the tiles XLA
    picked for the call, which it writes into the custom call's own Mosaic
    module."""
    tiles = {}
    for line in text.splitlines():
        m = re.match(r'\s*(?:ROOT )?%(ragged-dot-none[\w.]*) = .*'
                     r'backend_config=(\{.*\})\s*$', line)
        if m:
            body = base64.b64decode(json.loads(m.group(2))[
                'custom_call_config']['body']).decode()
            tiles[m.group(1)] = [
                int(v) for dims in re.findall(
                    r'tpu\.matmul.*: vector<(\d+)x(\d+)x\w+>, '
                    r'vector<(\d+)x(\d+)x\w+>,', body) for v in dims]
            assert tiles[m.group(1)], line[:200]
    return tiles


def test_grouped_products_run_at_tiles_of_256_and_more_at_the_hybrid_cells_size(
        hybrid_expert_layer):
    """XLA tiles a width of a grouped product by the largest of 512 / 256 /
    128 that divides it, and at 128 (2,688 = 21 x 128, 1,856 = 14.5 x 128) a
    call is its programs' overhead: with the widths padded, every one of the
    16 calls of the layer (2 + 6 a branch) multiplies tiles of 256 or more,
    in both branches of both `conditional`s."""
    tiles = _grouped_product_tiles(hybrid_expert_layer)
    assert len(tiles) == 16, sorted(tiles)
    assert min(min(dims) for dims in tiles.values()) >= 256, tiles
    # the pads are passes of their own (XLA fuses none into a cast), each
    # after the cast, at the operands' two bytes
    pads = re.findall(r' = (b?f\d+)\[[\d,]*\][^\n]* pad\(',
                      hybrid_expert_layer)
    assert pads and set(pads) == {'bf16'}, pads


def test_padded_widths_leave_the_glm_cells_expert_layer_as_it_was(v5e):
    """2,048 and 1,536 divide by 512: at the GLM cell's size (the
    short-convolution cell's widths too) the rule pads nothing, the layer
    lowers to the same StableHLO whether its rules see a TPU or not, and
    the products' tiles are 512 by 512."""
    def lowered(on_a_tpu):
        return _expert_layer_grad(v5e, 8192, 2048, on_a_tpu, **GLM_EXPERTS)

    def stripped(text):           # of source locations
        return re.sub(r'loc\(.*?\)|#loc.*', '', text)

    on_a_tpu = lowered(True)
    assert stripped(on_a_tpu.as_text()) == stripped(lowered(False).as_text())
    assert 'stablehlo.pad' not in on_a_tpu.as_text()
    tiles = _grouped_product_tiles(on_a_tpu.compile().as_text())
    assert len(tiles) == 24, sorted(tiles)               # 3 + 9 a branch
    assert {d for dims in tiles.values() for d in dims} == {512}, tiles


def _program_digests(lowered):
    """(the StableHLO of a lowered step outside its Mosaic bodies, its
    Mosaic modules), each as the first 16 hex digits of a sha256, source
    locations stripped from both: what the program asks of the compiler,
    whatever lines its source stands on."""
    import hashlib

    from jax._src.lib.mlir import ir
    bodies = []

    def body(match):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            bodies.append(ir.Module.parse(base64.b64decode(
                match.group(1))).operation.get_asm(enable_debug_info=False))
        return f'body <{len(bodies)}>'

    text = re.sub(r'loc\(.*?\)|#loc.*', '', lowered.as_text(debug_info=True))
    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
    text = re.sub(r'\n+', '\n', text)
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (text, '\n'.join(bodies)))


# The three causal decoders' steps as the tree before the one pass
# (`kernels/pallas_qk_pass.py`, PR 42) lowered them, by `_program_digests`:
# that PR changed `GroupedQueryAttention`, which two of them run, and held
# all three to what they were. A PR that means to change one of these
# programs says so and pins what it made.
LOWERED_BEFORE_THE_ONE_PASS = {
    # PR 46 meant to change this one and pins what it made: the latent
    # layer's core is the repo's two launches under ('latent', 0), q, k and
    # v written token-major by products, where the library's kernel, its
    # statistics and the head-major layout were
    # ('fbc6acb3f2404bb1', 'cbb0f31268823c36' before it)
    'token_decoder': ('1f972078eb188eba', 'f888f352ae0041fc'),
    # PR 45 meant to change this one and pins what it made: the hybrid's
    # global layer (32 heads over 2 of 128) takes the repo's two launches
    # under ('mha', 0) and the one pass, where the library's kernel, the
    # repeat of its key-value heads and four transposes were
    # ('83999f68a01618b1', '163f4d791ee9e1c7' before it)
    'hybrid': ('1d7574a62a328266', '7db51920a835d127'),
    'lfm2': ('0690edcb2e6f7ca7', '390b269ab0cf6096'),
}
# the same of the block-diffusion cell's step, lowered from the tree before
# its kernels took a second rule (a sliding window) and `ExpertLayer` a third
# form and a routing input: that step is this one, launch for launch
LOWERED_BEFORE_THE_WINDOW = {
    'sdar': ('22b4f6dde5632b50', '46d73dc846680a68'),
}


def _assert_one_forward_core_a_layer(text, layers, leaf):
    """The streaming kernel's launches in a compiled step: `layers` of each
    of the three and no forward in a block's replay (the blocks save its
    output and statistics), each under the scope `leaf`, which the per-layer
    metrics read, forward and backward alike."""
    launches = re.findall(
        r'%(flash_\w+)[.\d]* = [^\n]*tpu_custom_call[^\n]*op_name="([^"]*)"',
        text)
    by_role = {}
    for name, path in launches:
        assert f'/{leaf}/jit(flash_attention)/' in path, path
        assert 'rematted_computation' not in path, path
        assert ('transpose(' in path) == (name != 'flash_attention'), path
        by_role.setdefault(name.split('_block_')[0], []).append(path)
    assert {role: len(paths) for role, paths in by_role.items()} == {
        'flash_attention': layers, 'flash_mha_bwd_dkv': layers,
        'flash_mha_bwd_dq': layers}, by_role


def _assert_the_latent_cores_are_the_repos_launches(text, layers):
    """In a compiled step: one `latent_core_fwd` and one `latent_core_bwd`
    a layer of `layers`, each under the leaf `latent_core`, which the
    per-layer metrics read, the forward in the forward pass alone (a
    block's replay launches none: it saves o and the log-sum-exp), the
    backward under `transpose(`; no launch of the library's kernel and no
    pass: XLA's products write the operands."""
    from se3_transformer_tpu.observability import profiling
    assert 'flash' not in text and 'qk_pass' not in text
    by_role = {}
    for role, path in re.findall(
            r'%(latent_core_(?:fwd|bwd))[.\d]* = .*?'
            r'metadata=\{op_name="([^"]*)"', text, flags=re.S):
        assert '/attn/latent_core/' in path, path
        key = role, profiling.scope_phase(path)
        by_role[key] = by_role.get(key, 0) + 1
    assert by_role == {('latent_core_fwd', 'forward'): layers,
                       ('latent_core_bwd', 'backward'): layers}, by_role


def _assert_product_front_ends_agree(compiled):
    """The trace reducer counts an instruction's products from the
    `HloModuleProto` the chip's profiler stores beside a trace, through
    fields of `hlo.proto` it declares by hand. The check of those is the
    same rule on compiled HLO text (tests/hlo_text_reference.py): on one
    compiled step the two agree on every instruction, to the operation; the
    text prints an asynchronous pair under its own opcode (`slice-start`)
    and the computation it wraps inline, which is all the proto has beyond
    it."""
    from hlo_text_reference import hlo_text_computations
    from se3_transformer_tpu.observability import profiling
    text = profiling.product_counts(hlo_text_computations(compiled.as_text()))
    proto = profiling.hlo_proto_class()()
    proto.hlo_module.ParseFromString(
        compiled.runtime_executable().hlo_modules()[0]
        .as_serialized_hlo_module_proto())
    module = profiling.product_counts(
        profiling.hlo_proto_computations(proto.hlo_module))
    assert set(text) <= set(module)
    for name, row in text.items():
        other = module[name]
        assert (row['flops'], row['products'], row['kind']) == (
            other['flops'], other['products'], other['kind']), name
        assert row['opcode'] == other['opcode'] or \
            other['opcode'].startswith('async-'), name
    assert all(module[name]['products'] == 0
               for name in set(module) - set(text))
    products = sum(row['products'] for name, row in text.items()
                   if row['opcode'] != 'fusion')
    assert products > 100
    return text


def _assert_gated_backward_products_are_plain(text, tokens, widths):
    """The gated feed-forwards of a compiled step (`expert_layer.gated_ff`,
    one a width of `widths`, under `dense_ff` or `shared_expert`): no product
    of the backward holds SiLU's chain or reads a float32 [tokens, width]
    tensor (dh is written by one, and read by the pass alone); the chain is
    computed in one fusion a feed-forward outside the forward, and the six
    products after it read what it wrote, in bfloat16."""
    comps = _computations(text)
    fused = set(re.findall(r'calls=%([\w.\-]+)', text))
    chains, plain = [], 0
    for name, body in comps.items():
        if name in fused:
            continue
        for line in body.splitlines():
            m = re.match(r'\s*(?:ROOT )?%[\w.\-]+ = .*? (fusion|convolution)'
                         r'\((.*?)\), ', line)
            path = re.search(r'op_name="([^"]*)"', line)
            if not m or not path or not re.search(
                    r'/(dense_ff|shared_expert)/', path.group(1)):
                continue
            path = path.group(1)
            if 'transpose(' not in path:       # the forward
                continue
            called = re.search(r'calls=%([\w.\-]+)', line)
            inside = _with_callees(comps, called.group(1)) if called else line
            chain = 'exponential(' in inside or 'logistic(' in inside
            if 'rematted_computation' in path or 'convolution(' not in inside:
                chains += [path] * chain        # the replay's, or no product
                continue
            assert not chain, line[:300]
            read = [comps[name].split(f'%{o} = ', 1)[1].split(' ', 1)[0]
                    for o in re.findall(r'%([\w.\-]+)', m.group(2))
                    if f'%{o} = ' in comps[name]]
            for r in read:
                dims = [int(d) for d in re.findall(r'\d+', r.split('{')[0])]
                assert not (r.startswith('f32[') and dims[-1] in widths
                            and math.prod(dims[:-1]) == tokens), (line[:300], r)
            plain += any(r.startswith('bf16[') for r in read)
    assert len(chains) == len(widths), chains
    assert plain == 5 * len(widths), plain


@pytest.mark.slow
def test_token_decoder_step_compiles_and_fits(v5e, monkeypatch, capsys):
    """The benchmark's decoder cell: the published widths of its
    configuration file on the one step factory, compiled for the chip (under
    a minute): the attention core is the repo's two launches, one forward
    and one backward a block under `latent_core` and none in a replay, no
    launch of the library's kernel is left, the grouped products are in it,
    and state plus temporaries fit with the six blocks' saved attention
    outputs and log-sum-exps."""
    import optax
    from se3_transformer_tpu.ops import expert_layer, latent_attention
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES

    monkeypatch.setattr(latent_attention, 'is_tpu_backend', lambda: True)
    monkeypatch.setattr(expert_layer, 'is_tpu_backend', lambda: True)
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'benchmark', 'configs', 'glm47-flash-ep8-train.json')))
    module = RECIPES[cfg['recipe']](**cfg['model'], **cfg['overrides'])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-4)
    lowered = make_sharded_train_step(make_lm_loss(module), optimizer).lower(
        on_chip(params), on_chip(jax.eval_shape(optimizer.init, params)),
        on_chip(dict(tokens=tokens)),
        on_chip(jax.random.PRNGKey(1)))
    digests = _program_digests(lowered)
    assert digests == LOWERED_BEFORE_THE_ONE_PASS['token_decoder'], digests
    compiled = lowered.compile()
    text = compiled.as_text()
    assert 'ragged-dot' in text
    _assert_the_latent_cores_are_the_repos_launches(text, 6)
    _assert_product_front_ends_agree(compiled)
    _assert_gated_backward_products_are_plain(
        text, 8192, [cfg['model']['intermediate_size']]
        + [cfg['model']['moe_intermediate_size']] * 5)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    with capsys.disabled():
        print(f'\ntoken decoder step for a v5e: arguments '
              f'{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries '
              f'{mem.temp_size_in_bytes / 2**30:.2f} GiB, in all '
              f'{total / 2**30:.2f} GiB of 15.75')
    assert 11.5 * 2 ** 30 < total < 12 * 2 ** 30, mem


# ------------------------------------------------------------------ #
# the hybrid decoder, at the widths of the benchmark's cell
# ------------------------------------------------------------------ #
def test_streaming_attention_compiles_at_the_hybrid_cells_size(v5e):
    """The same kernel at 32 heads of 128 over 8,192 tokens (the two
    key-value heads already repeated), forward and both backward launches."""
    from se3_transformer_tpu.ops.latent_attention import (
        causal_attention_flash,
    )

    def loss(q, k, v):
        return causal_attention_flash(q, k, v, 128 ** -0.5, 512).sum()

    assert compile_for(v5e, jax.grad(loss, argnums=(0, 1, 2)),
                       *[((1, 32, 8192, 128), jnp.float32)] * 3) == 3


def test_the_scans_kernels_compile_at_the_hybrid_cells_size(v5e):
    """`kernels/pallas_scan.py` at 8,192 tokens in chunks of 128, 64 heads of
    64 in 8 groups, state 128: the forward launch alone, and under a
    gradient the forward that saves the entering states and the backward,
    each within the VMEM the launch asks for."""
    from se3_transformer_tpu.kernels import pallas_scan
    from se3_transformer_tpu.ops.state_space import scan_kernels
    t, h, p, n, g, q = 8192, 64, 64, 128, 8, 128
    assert pallas_scan.can_run(h, p, g, n, q)
    shapes = [((1, t, h, p), f32), ((1, t, h), f32), ((h,), f32),
              ((1, t, g, n), f32), ((1, t, g, n), f32), ((h,), f32)]

    def loss(x, *rest):
        return (scan_kernels(x, *rest, q) * x).sum()

    assert compile_for(v5e, lambda *v: scan_kernels(*v, q), *shapes) == 1
    assert compile_for(v5e, jax.grad(loss, argnums=tuple(range(6))),
                       *shapes) == 2


def _assert_the_scan_is_the_repos_kernels(text, layers, big):
    """In a compiled step: a forward launch a state-space layer in the
    forward pass and one in its block's replay, a backward launch a layer,
    each under `ssm_scan`; and XLA keeps no float32 buffer of `big`
    elements (a chunk's scores or decays over all heads) or more there."""
    from se3_transformer_tpu.observability import profiling
    launches = re.findall(
        r'%(ssm_scan_\w+?)[.\d]* = [^\n]*tpu_custom_call[^\n]*'
        r'op_name="([^"]*)"', text)
    by_role = {}
    for name, path in launches:
        assert '/ssm_scan/' in path and f'/{name}/pallas_call' in path, path
        phase = profiling.scope_phase(path)
        by_role[name, phase] = by_role.get((name, phase), 0) + 1
    assert by_role == {('ssm_scan_fwd', 'forward'): layers,
                       ('ssm_scan_fwd', 'replay'): layers,
                       ('ssm_scan_bwd', 'backward'): layers}, by_role
    for shape, path in re.findall(
            r' = (f32\[[\d,]*\])[^\n]*op_name="([^"]*/ssm_scan/[^"]*)"', text):
        if 'pallas_call' in path:       # the launches' own x, y and states
            continue
        assert math.prod(int(d) for d in shape[4:-1].split(',') if d) < big, (
            shape, path)


@pytest.mark.slow
def test_hybrid_decoder_step_compiles_and_fits(v5e, monkeypatch, capsys):
    """The benchmark's hybrid cell: the published widths of its configuration
    file on the one step factory, compiled for the chip (under a minute):
    the global layer's core is the repo's two launches under `mha_core`
    (32 heads over 2 of 128: one forward, none in a replay, one backward),
    the one pass on either side of them under `mha_qkv`, no launch of the
    library's kernel, no repeated key-value head and nothing laid out again
    around them; the grouped products and the scan's two kernels are in
    it, and state plus temporaries fit; its memory is printed."""
    import optax
    from se3_transformer_tpu.ops import (
        expert_layer, latent_attention, sliding_window, state_space,
    )
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES

    for ops in (latent_attention, sliding_window, state_space, expert_layer):
        monkeypatch.setattr(ops, 'is_tpu_backend', lambda: True)
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'benchmark', 'configs', 'nemotron-twotower-ep16-train.json')))
    module = RECIPES[cfg['recipe']](**cfg['model'], **cfg['overrides'])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-6)
    lowered = make_sharded_train_step(
        make_lm_loss(module, **cfg['loss']), optimizer).lower(
        on_chip(params), on_chip(jax.eval_shape(optimizer.init, params)),
        on_chip(dict(tokens=tokens)),
        on_chip(jax.random.PRNGKey(1)))
    assert _program_digests(lowered) == LOWERED_BEFORE_THE_ONE_PASS['hybrid']
    compiled = lowered.compile()
    text = compiled.as_text()
    assert 'ragged-dot' in text
    _assert_the_cores_are_the_repos_launches(text, 1, 0)
    _assert_no_relayout_around_the_core(text, 8192 * 32 * 128,
                                        ('mha_core',), 1)
    # 64 chunks x 64 heads x 128 x 128: what the einsum form wrote a layer
    _assert_the_scan_is_the_repos_kernels(text, 4, 64 * 64 * 128 * 128)
    _assert_product_front_ends_agree(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    with capsys.disabled():
        print(f'\nhybrid step for a v5e: arguments '
              f'{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries '
              f'{mem.temp_size_in_bytes / 2**30:.2f} GiB, in all '
              f'{total / 2**30:.2f} GiB of 15.75')
    assert 10 * 2 ** 30 < total < 13.5 * 2 ** 30, mem


# ------------------------------------------------------------------ #
# the decoder with gated short convolutions, at the widths of its cell
# ------------------------------------------------------------------ #
def test_streaming_attention_compiles_at_heads_of_64(v5e):
    """The same kernel at 32 heads of 64 over two sequences of 8,192 tokens
    (the eight key-value heads already repeated), half a lane row a head,
    unpadded: forward and both backward launches."""
    from se3_transformer_tpu.ops.latent_attention import (
        causal_attention_flash,
    )

    def loss(q, k, v):
        return causal_attention_flash(q, k, v, 64 ** -0.5, 512).sum()

    assert compile_for(v5e, jax.grad(loss, argnums=(0, 1, 2)),
                       *[((2, 32, 8192, 64), jnp.float32)] * 3) == 3


@pytest.mark.slow
def test_lfm2_decoder_step_compiles_and_fits(v5e, monkeypatch, capsys):
    """The benchmark's short-convolution cell: the published widths of its
    configuration file on the one step factory at two sequences of 8,192
    tokens, compiled for the chip (under a minute): the attention kernel
    and the grouped products are in it, the gates and taps are XLA's, and
    state plus temporaries fit; its memory is printed."""
    import optax
    from se3_transformer_tpu.ops import expert_layer, latent_attention
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES

    monkeypatch.setattr(latent_attention, 'is_tpu_backend', lambda: True)
    monkeypatch.setattr(expert_layer, 'is_tpu_backend', lambda: True)
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'benchmark', 'configs', 'lfm2-24b-a2b-ep8-train.json')))
    module = RECIPES[cfg['recipe']](**cfg['model'], **cfg['overrides'])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-6)
    lowered = make_sharded_train_step(
        make_lm_loss(module, **cfg['loss']), optimizer).lower(
        on_chip(params), on_chip(jax.eval_shape(optimizer.init, params)),
        on_chip(dict(tokens=tokens)),
        on_chip(jax.random.PRNGKey(1)))
    assert _program_digests(lowered) == LOWERED_BEFORE_THE_ONE_PASS['lfm2']
    compiled = lowered.compile()
    text = compiled.as_text()
    assert 'flash_mha_bwd_dkv' in text and 'ragged-dot' in text
    _assert_one_forward_core_a_layer(text, 1, 'mha_core')
    _assert_product_front_ends_agree(compiled)
    _assert_gated_backward_products_are_plain(
        text, 2 * 8192, [cfg['model']['intermediate_size']])
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    with capsys.disabled():
        print(f'\nlfm2 step for a v5e: arguments '
              f'{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries '
              f'{mem.temp_size_in_bytes / 2**30:.2f} GiB, in all '
              f'{total / 2**30:.2f} GiB of 15.75')
    assert 9 * 2 ** 30 < total < 12.5 * 2 ** 30, mem


# ------------------------------------------------------------------ #
# the block-diffusion core (ops/block_diffusion.py)
# ------------------------------------------------------------------ #
def _core_launches(text):
    """(role, op_name) of every launch of the block-diffusion core and of
    the pass before and after it in a compiled program. A launch's line
    breaks inside its `kernel_metadata`, so the path is the first `op_name`
    after the instruction's name."""
    return re.findall(
        r'%((?:bd_core|qk_pass)_(?:fwd|bwd))[.\d]* = .*?'
        r'metadata=\{op_name="([^"]*)"', text, flags=re.S)


def test_the_block_diffusion_core_compiles_and_visits_288_tiles_a_head(v5e):
    """The repo's kernels (`kernels/pallas_block_attention.py`,
    `kernels/pallas_qk_pass.py`) at the block-diffusion cell's size (32
    query and 4 key-value heads of 128, 2 x 8,192 positions, blocks of 4
    tokens, tiles of 512), in the projections' own layout: the pass and the
    core lower for the chip forward and backward, one launch each, and the
    table the core's grid is taken from holds 288 of a head's 1,024 tiles,
    none idle: a noised tile meets itself and the clean prefix's i + 1
    tiles, a clean tile i + 1 (sum of (i + 2) + (i + 1) over 16), where a
    causal core over 16,384 positions would visit 528; 48 of them evaluate
    the rule. No operand or result is laid out again around the launches."""
    from se3_transformer_tpu.kernels import pallas_block_attention as kernels
    from se3_transformer_tpu.ops import block_diffusion as bd

    assert kernels.can_run(8192, 4, 512, 32, 4, 128)
    assert bd.visited_tiles(8192, 4, 512) == 288 \
        == sum((i + 2) + (i + 1) for i in range(16))
    assert bd.boundary_tiles(8192, 4, 512) == 48
    assert 32 * 33 // 2 == 528 and 32 * 32 == 1024

    def loss(q, k, v, norms, rotary):
        return kernels.block_attention(
            q, k, v, norms, rotary, 128, 128 ** -0.5, 1e-6, ('bd', 4),
            512).astype(
            f32).sum()

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=v5e)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        on_chip(1, 16384, 32 * 128), on_chip(1, 16384, 4 * 128),
        on_chip(1, 16384, 4 * 128), (on_chip(128), on_chip(128)),
        (on_chip(16384, 128), on_chip(16384, 128))).compile()
    text = compiled.as_text()
    roles = [role for role, _ in _core_launches(text)]
    assert sorted(roles) == ['bd_core_bwd', 'bd_core_fwd', 'qk_pass_bwd',
                             'qk_pass_fwd'], roles
    # the grid is the table: sequences x key-value heads x 288 entries
    assert text.count('s32[7,288]') >= 2 and 'splash' not in text
    # the log-sum-exp leaves as one float32 a row; q, o and dq stay [T, H D]
    assert 'f32[1,32,1,16384]' in text and 'f32[1,16384,4096]{' in text
    assert not re.search(r'\[1,32,16384,128\]|\[1,16384,32,128\]', text)
    assert not re.search(r' (copy|transpose)\(', text[text.index('ENTRY'):])


def _assert_no_relayout_around_the_core(text, big, cores=('bd_core',),
                                        layers=5):
    """In a compiled step no instruction under `mha_qkv`, a core's leaf
    (`cores`) or `mha_out` (forward, replay or backward) that computes
    nothing passes over a tensor of `big` elements or more (q, o, do or dq:
    [16384, 32, 128] in the block-diffusion cell): no `copy`, no
    `transpose`, no fusion of converts and layout changes alone. The
    products and the launches are all that read and write them; what is
    left of XLA's own is named here (remat rounds the saved o to its own
    width, `reduce-precision`, one pass a layer of `layers`)."""
    comps = _computations(text)
    moves = {'copy', 'transpose', 'convert', 'bitcast', 'bitcast-convert',
             'reshape', 'parameter', 'broadcast', 'slice', 'concatenate',
             'tuple', 'get-tuple-element'}
    left = {}
    for line in text[text.index('ENTRY'):].splitlines():
        m = re.match(r'\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(',
                     line)
        path = re.search(r'op_name="([^"]*)"', line)
        if not m or not path or not re.search(
                rf'/attn/(mha_qkv|{"|".join(cores)}|mha_out)(/|$)',
                path.group(1)):
            continue
        shape, opcode = m.groups()
        sizes = [math.prod(int(d) for d in dims.split(',') if d)
                 for dims in re.findall(r'\w+\[([\d,]*)\]', shape)]
        if max(sizes, default=0) < big or opcode in (
                'custom-call', 'get-tuple-element', 'bitcast'):
            continue
        called = re.search(r'calls=%([\w.\-]+)', line)
        inside = _with_callees(comps, called.group(1)) if called else line
        opcodes = set(re.findall(r' = (?:\(.*?\)|\S+) ([\w\-]+)\(', inside))
        assert not opcodes <= moves, (opcodes, line[:300])
        if 'convolution' not in opcodes:
            left[opcode] = left.get(opcode, 0) + 1
    assert left == {'reduce-precision': layers}, left


@pytest.mark.slow
def test_sdar_decoder_step_compiles_and_fits(v5e, monkeypatch, capsys):
    """The benchmark's block-diffusion cell: the published widths of its
    configuration file on the one step factory at one sequence of 8,192
    tokens read twice, compiled for the chip (a minute): one forward and
    one backward launch of the core a layer, the repo's own, and none in a
    block's replay (the blocks save its output and log-sum-exp), every
    launch under `bd_core`, no `splash` anywhere; the one pass before the
    core forward and in the replay and after it backward, under `mha_qkv`,
    and nothing laid out again on either side of them; the grouped
    products are in it; state plus temporaries fit; its memory is
    printed."""
    import optax
    from se3_transformer_tpu.ops import (
        block_diffusion, expert_layer, latent_attention,
    )
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_block_diffusion_loss
    from se3_transformer_tpu.training.recipes import RECIPES

    for mod in (block_diffusion, latent_attention, expert_layer):
        monkeypatch.setattr(mod, 'is_tpu_backend', lambda: True)
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'benchmark', 'configs', 'sdar-30b-a3b-ep8-train.json')))
    module = RECIPES[cfg['recipe']](**cfg['model'], **cfg['overrides'])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    batch = dict(tokens=tokens, noised=tokens,
                 weight=jax.ShapeDtypeStruct((1, 8192), jnp.float32))
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-6)
    lowered = make_sharded_train_step(
        make_block_diffusion_loss(module, **cfg['loss']), optimizer).lower(
        on_chip(params), on_chip(jax.eval_shape(optimizer.init, params)),
        on_chip(batch), on_chip(jax.random.PRNGKey(1)))
    assert _program_digests(lowered) == LOWERED_BEFORE_THE_WINDOW['sdar']
    compiled = lowered.compile()
    text = compiled.as_text()
    assert 'ragged-dot' in text and 'flash_attention' not in text
    assert 'splash' not in text
    from se3_transformer_tpu.observability import profiling
    by_role = {}
    for role, path in _core_launches(text):
        leaf = '/attn/bd_core/' if role.startswith('bd_core') \
            else '/attn/mha_qkv/'
        assert leaf in path, path
        key = role, profiling.scope_phase(path)
        by_role[key] = by_role.get(key, 0) + 1
    # the core once a layer each way and none in a replay; the pass before
    # it again in the replay (its outputs are what the backward launch reads)
    assert by_role == {('bd_core_fwd', 'forward'): 5,
                       ('bd_core_bwd', 'backward'): 5,
                       ('qk_pass_fwd', 'forward'): 5,
                       ('qk_pass_fwd', 'replay'): 5,
                       ('qk_pass_bwd', 'backward'): 5}, by_role
    _assert_no_relayout_around_the_core(text, 16384 * 32 * 128)
    _assert_product_front_ends_agree(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    with capsys.disabled():
        print(f'\nsdar step for a v5e: arguments '
              f'{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries '
              f'{mem.temp_size_in_bytes / 2**30:.2f} GiB, in all '
              f'{total / 2**30:.2f} GiB of 15.75')
    assert 11.5 * 2 ** 30 < total < 13.5 * 2 ** 30, mem


# ------------------------------------------------------------------ #
# the sliding-window core (ops/sliding_window.py) and its decoder
# ------------------------------------------------------------------ #
def _window_launches(text):
    """(role, op_name) of every launch of the sliding-window core, of the
    causal core under its own rule and of the pass before and after them in
    a compiled program."""
    return re.findall(
        r'%((?:swa_core|mha_core|qk_pass)_(?:fwd|bwd))[.\d]* = .*?'
        r'metadata=\{op_name="([^"]*)"', text, flags=re.S)


def test_the_sliding_window_core_compiles_and_visits_252_tiles_a_head(v5e):
    """The repo's kernels under the window's rule at the new cell's size (28
    query heads over 4 key-value heads of 128, groups of 7, 16,384
    positions, a window of 4,096, tiles of 512), in the projections' own
    layout with rotation and no norms: the pass and the core lower for the
    chip forward and backward, one launch each, and the table the core's
    grid is taken from holds 252 of a head's 1,024 tiles where the causal
    triangle has 528: a query tile meets itself, the seven whole tiles
    before it and the far edge's (fewer at the sequence's start), 56 of
    them on a boundary (32 diagonals, 24 far edges). No tile wholly outside
    the window is launched, and nothing is laid out again around the
    launches."""
    from se3_transformer_tpu.kernels import pallas_block_attention as kernels
    from se3_transformer_tpu.ops import sliding_window as sw

    assert kernels.launches_run(16384, 512, 28, 4, 128)
    assert sw.visited_tiles(16384, 4096, 512) == 252 \
        == sum(min(i, 8) + 1 for i in range(32))
    assert sw.boundary_tiles(16384, 4096, 512) == 56 == 32 + 24
    assert sw.visited_tiles(16384, 16384, 512) == 528
    assert sw.visible_pairs(16384, 4096) == 58_722_304
    assert sw.visible_pairs(16384, 16384) == 134_225_920

    def loss(q, k, v, rotary):
        return kernels.block_attention(
            q, k, v, None, rotary, 128, 128 ** -0.5, 1e-6, ('swa', 4096),
            512).astype(f32).sum()

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=v5e)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip(1, 16384, 28 * 128), on_chip(1, 16384, 4 * 128),
        on_chip(1, 16384, 4 * 128),
        (on_chip(16384, 128), on_chip(16384, 128))).compile()
    text = compiled.as_text()
    roles = [role for role, _ in _window_launches(text)]
    assert sorted(roles) == ['qk_pass_bwd', 'qk_pass_fwd', 'swa_core_bwd',
                             'swa_core_fwd'], roles
    # the grid is the table: sequences x key-value heads x 252 entries
    assert text.count('s32[7,252]') >= 2 and 'bd_core' not in text
    assert 'f32[1,28,1,16384]' in text and 'f32[1,16384,3584]{' in text
    assert not re.search(r'\[1,28,16384,128\]|\[1,16384,28,128\]', text)
    assert not re.search(r' (copy|transpose)\(', text[text.index('ENTRY'):])


def _assert_the_cores_are_the_repos_launches(text, global_layers,
                                             sliding_layers):
    """In a compiled step: one `mha_core_fwd` and one `mha_core_bwd` a
    global layer under `mha_core`, one `swa_core_*` pair a sliding layer
    under `swa_core`, the forward in the forward pass alone (a block's
    replay launches none: it saves o and the log-sum-exp), the backward
    under `transpose(`; the one pass before each forward, replayed, and
    after each backward under `mha_qkv`; and nothing of the library's
    kernel."""
    from se3_transformer_tpu.observability import profiling
    assert 'flash' not in text and 'splash' not in text
    by_role = {}
    for role, path in _window_launches(text):
        leaf = 'mha_qkv' if role.startswith('qk_pass') \
            else role.rsplit('_', 1)[0]         # `<leaf>_fwd`, `<leaf>_bwd`
        assert f'/attn/{leaf}/' in path, path
        if role.endswith('_core_fwd'):
            assert 'rematted_computation' not in path, path
        if role.endswith('_bwd'):
            assert 'transpose(' in path, path
        key = role, profiling.scope_phase(path)
        by_role[key] = by_role.get(key, 0) + 1
    layers = global_layers + sliding_layers
    want = {('mha_core_fwd', 'forward'): global_layers,
            ('mha_core_bwd', 'backward'): global_layers,
            ('swa_core_fwd', 'forward'): sliding_layers,
            ('swa_core_bwd', 'backward'): sliding_layers,
            ('qk_pass_fwd', 'forward'): layers,
            ('qk_pass_fwd', 'replay'): layers,
            ('qk_pass_bwd', 'backward'): layers}
    assert by_role == {k: n for k, n in want.items() if n}, by_role


@pytest.mark.parametrize('t,heads,kv,tiles,diagonal', [
    (16384, 28, 4, 528, 32), (8192, 32, 2, 136, 16)],
    ids=['groups of 7 at 16k', 'groups of 16 at 8k'])
def test_the_causal_core_compiles_at_both_cells_global_layers(
        v5e, t, heads, kv, tiles, diagonal):
    """The same kernels under the third rule, ('mha', 0), at the global
    layers' shapes of the two cells that have heads of 128: the
    sliding-window cell's 28 query heads over 4 (groups of 7) at 16,384
    positions and the hybrid cell's 32 over 2 (groups of 16: a program's q,
    o and do blocks are 512 rows of 2,048 lanes, the pass's 256 rows of
    them in float32) at 8,192, tiles of 512, no norms and no rotation as
    both layers have it. The pass and the core lower for the chip forward
    and backward within the VMEM each asks for (the pass the default), one
    launch each named `mha_core_*`, over the causal triangle's table, the
    diagonal alone on a boundary; the key-value heads are never repeated
    and nothing is laid out again around the launches."""
    from se3_transformer_tpu.kernels import pallas_block_attention as kernels
    from se3_transformer_tpu.ops import sliding_window as sw

    assert kernels.launches_run(t, 512, heads, kv, 128)
    n = t // 512
    assert sw.visited_tiles(t, t, 512) == tiles == n * (n + 1) // 2
    assert sw.boundary_tiles(t, t, 512) == diagonal == n

    def loss(q, k, v):
        return kernels.block_attention(
            q, k, v, None, None, 128, 128 ** -0.5, 1e-6, ('mha', 0),
            512).astype(f32).sum()

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=v5e)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip(1, t, heads * 128), on_chip(1, t, kv * 128),
        on_chip(1, t, kv * 128)).compile()
    text = compiled.as_text()
    roles = [role for role, _ in _window_launches(text)]
    assert sorted(roles) == ['mha_core_bwd', 'mha_core_fwd', 'qk_pass_bwd',
                             'qk_pass_fwd'], roles
    assert text.count(f's32[7,{tiles}]') >= 2
    assert 'swa_core' not in text and 'flash' not in text
    assert f'f32[1,{heads},1,{t}]' in text \
        and f'f32[1,{t},{heads * 128}]{{' in text
    assert not re.search(rf'\[1,{heads},{t},128\]|\[1,{t},{heads},128\]',
                         text)
    assert not re.search(r' (copy|transpose)\(', text[text.index('ENTRY'):])


@pytest.mark.slow
def test_smallthinker_decoder_step_compiles_and_fits(v5e, monkeypatch,
                                                     capsys):
    """The benchmark's sliding-window cell: the published widths of its
    configuration file on the one step factory at one sequence of 16,384
    tokens, compiled for the chip: the global layer and each of the three
    sliding layers one forward and one backward launch of the repo's own
    and none in a replay, under `mha_core` and `swa_core` by the layer's
    rule, the one pass before them forward, replayed and backward under
    `mha_qkv`, no launch of the library's kernel, and nothing laid out
    again on either side of any of the four cores; the grouped products
    are in it; state plus temporaries fit; its memory is printed."""
    import optax
    from se3_transformer_tpu.ops import (
        expert_layer, latent_attention, sliding_window,
    )
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES

    for mod in (sliding_window, latent_attention, expert_layer):
        monkeypatch.setattr(mod, 'is_tpu_backend', lambda: True)
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'benchmark', 'configs', 'smallthinker-21b-a3b-swa-train.json')))
    module = RECIPES[cfg['recipe']](**cfg['model'], **cfg['overrides'])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    optimizer = optax.adam(1e-6)
    compiled = make_sharded_train_step(
        make_lm_loss(module, **cfg['loss']), optimizer).lower(
        on_chip(params), on_chip(jax.eval_shape(optimizer.init, params)),
        on_chip(dict(tokens=tokens)), on_chip(jax.random.PRNGKey(1))).compile()
    text = compiled.as_text()
    assert 'ragged-dot' in text
    _assert_the_cores_are_the_repos_launches(text, 1, 3)
    _assert_no_relayout_around_the_core(text, 16384 * 28 * 128,
                                        ('mha_core', 'swa_core'), 4)
    _assert_product_front_ends_agree(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    with capsys.disabled():
        print(f'\nsmallthinker step for a v5e: arguments '
              f'{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries '
              f'{mem.temp_size_in_bytes / 2**30:.2f} GiB, in all '
              f'{total / 2**30:.2f} GiB of 15.75')
    assert 12 * 2 ** 30 < total < 14.75 * 2 ** 30, mem


@pytest.mark.slow
def test_ouro_decoder_step_compiles_and_fits(v5e, monkeypatch, capsys):
    """The benchmark's looped cell: the published widths of its
    configuration file on the one step factory at one sequence of 8,192
    tokens, the objective over the four exits, compiled for the chip. The
    four layers run four times: 16 forward and 16 backward launches of the
    repo's own causal core under `mha_core` (16 heads of 128 in groups of
    one) and none in a replay, the one pass before them (rotation, no norms)
    forward, replayed and backward under `mha_qkv`, no launch of the
    library's kernel and nothing laid out again around a core; every pass's
    launches carry its `ut_<t>`, four of each role a pass; the gate and the
    mix are in it; state plus temporaries fit; its memory is printed."""
    import optax
    from se3_transformer_tpu.observability import profiling
    from se3_transformer_tpu.ops import (
        expert_layer, latent_attention, sliding_window,
    )
    from se3_transformer_tpu.parallel.sharding import make_sharded_train_step
    from se3_transformer_tpu.training.lm_loss import make_looped_lm_loss
    from se3_transformer_tpu.training.recipes import RECIPES

    for mod in (sliding_window, latent_attention, expert_layer):
        monkeypatch.setattr(mod, 'is_tpu_backend', lambda: True)
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'benchmark', 'configs', 'ouro-2.6b-loop4-train.json')))
    module = RECIPES[cfg['recipe']](**cfg['model'], **cfg['overrides'])
    assert sliding_window.kernels_run(8192, 512, 16, 16, 128)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)['params']
    assert sum(math.prod(a.shape) for a in
               jax.tree_util.tree_leaves(params)) == 406_884_353
    optimizer = optax.adam(1e-6)
    compiled = make_sharded_train_step(
        make_looped_lm_loss(module, **cfg['loss']), optimizer).lower(
        on_chip(params), on_chip(jax.eval_shape(optimizer.init, params)),
        on_chip(dict(tokens=tokens)), on_chip(jax.random.PRNGKey(1))).compile()
    text = compiled.as_text()
    passes, layers = 4, 4
    _assert_the_cores_are_the_repos_launches(text, passes * layers, 0)
    by_pass = {}
    for role, path in _window_launches(text):
        key = profiling.scope_pass(path), role, profiling.scope_phase(path)
        by_pass[key] = by_pass.get(key, 0) + 1
    assert by_pass == {
        (f'ut_{t}', role, phase): layers for t in range(passes)
        for role, phase in (
            ('mha_core_fwd', 'forward'), ('mha_core_bwd', 'backward'),
            ('qk_pass_fwd', 'forward'), ('qk_pass_fwd', 'replay'),
            ('qk_pass_bwd', 'backward'))}, by_pass
    _assert_no_relayout_around_the_core(text, 8192 * 16 * 128,
                                        ('mha_core',), passes * layers)
    for leaf in ('exit_gate', 'exit_mix'):
        assert f'/{leaf}/' in text, leaf
    _assert_product_front_ends_agree(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    with capsys.disabled():
        print(f'\nouro step for a v5e: arguments '
              f'{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries '
              f'{mem.temp_size_in_bytes / 2**30:.2f} GiB, in all '
              f'{total / 2**30:.2f} GiB of 15.75')
    assert 8 * 2 ** 30 < total < 14.75 * 2 ** 30, mem


def test_the_causal_path_lowers_as_it_did_before_the_two_streams(v5e):
    """`GroupedQueryAttention` at the short-convolution cell's widths, for
    the chip: called as the causal decoders call it and called with the new
    arguments at their defaults (no positions, no block length) it lowers to
    one StableHLO, the library's causal kernel under `mha_core` and no
    `bd_core` anywhere. (The three accepted cells' whole steps were lowered
    from the parent's tree and from this one when the path was added: the
    same text outside the Mosaic bodies, the same Mosaic modules.)"""
    from se3_transformer_tpu.ops import latent_attention
    from se3_transformer_tpu.ops.grouped_attention import (
        GroupedQueryAttention,
    )
    attn = GroupedQueryAttention(dim=2048, heads=32, kv_heads=8, head_dim=64,
                                 qk_norm=True, rope_theta=1e6)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), f32, sharding=v5e)
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)['params']
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        params)

    def stripped(text):           # of source locations and their lines
        return re.sub(r'\n+', '\n', re.sub(r'loc\(.*?\)|#loc.*', '', text))

    keep = latent_attention.is_tpu_backend
    latent_attention.is_tpu_backend = lambda: True
    try:
        plain = jax.jit(lambda p, x: attn.apply({'params': p}, x)).lower(
            params, x).as_text(debug_info=True)
        defaults = jax.jit(lambda p, x: attn.apply(
            {'params': p}, x, None, 0)).lower(params, x).as_text(
            debug_info=True)
    finally:
        latent_attention.is_tpu_backend = keep
    assert stripped(plain) == stripped(defaults)
    assert 'mha_core' in plain and 'bd_core' not in plain
    assert 'flash_attention' in plain and 'splash' not in plain
