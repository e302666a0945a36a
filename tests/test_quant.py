"""Quantized mixed-precision serving (se3_transformer_tpu.quant).

Contracts pinned here:
  * per-rule-class quantize->dequant round-trip error bounds (int8
    per-channel <= amax/254, bf16 relative <= 2^-8, fp32 exact);
  * the QuantTensor pytree leaf ORDER (q first) that flax's param
    shape check rides on;
  * an int8/fp8 rule matched to an l>0 (equivariant) weight raises
    LOUDLY — never a silent accuracy cliff;
  * the fused dequant epilogues (LinearSE3 / _QuantDense /
    _radial_contract XLA + Pallas interpret / flash) all agree with
    the fp32 evaluation of the dequantized weights to roundoff;
  * the engine quantizes at RESTORE time (int8 storage on device, the
    fp32 degree-0 weights never materialize), one checkpoint serves
    fp32 / bf16 / int8-mix engines unchanged, argument bytes drop
    under the 0.6x ceiling, and rolling swaps re-quantize with zero
    recompiles;
  * weight-only quantization preserves equivariance at degrees 2/4.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from se3_transformer_tpu import quant
from se3_transformer_tpu.quant import EquivariantPrecisionError, QuantTensor


# --------------------------------------------------------------------- #
# unit: quantize / dequantize / pytree contracts
# --------------------------------------------------------------------- #
def test_int8_roundtrip_error_bound_per_output_channel():
    rng = np.random.RandomState(0)
    w = rng.normal(size=(16, 8, 4)).astype(np.float32) * 3.0
    w[:, 2, 1] = 0.0   # an all-zero channel must survive exactly
    qt = quant.quantize(w, contract_axes=(0,), storage='int8')
    assert qt.q.dtype == np.int8
    assert qt.scale.shape == (1, 8, 4)          # contracted axis kept 1
    # symmetric round-to-nearest on a 127-level grid: per-channel error
    # <= scale/2 = amax/254
    bound = np.abs(w).max(axis=0, keepdims=True) / 254.0
    err = np.abs(quant.dequantize(qt) - w)
    assert (err <= bound + 1e-7).all()
    assert np.abs(quant.dequantize(qt)[:, 2, 1]).max() == 0.0


def test_bf16_cast_bound_and_fp32_passthrough():
    rng = np.random.RandomState(1)
    w = rng.normal(size=(32, 8)).astype(np.float32)
    qp, report = quant.quantize_params(
        {'w1': w}, ((r'(^|/)w1$', 'bf16'), (r'.*', 'fp32')))
    back = np.asarray(qp['w1'], np.float32)
    assert qp['w1'].dtype == jnp.bfloat16
    # bf16 has 8 mantissa bits: relative error <= 2^-9 of the magnitude
    assert (np.abs(back - w) <= np.abs(w) * 2 ** -8 + 1e-12).all()
    qp2, _ = quant.quantize_params({'w1': w}, 'fp32')
    assert qp2['w1'] is w                        # untouched passthrough
    assert report['params_bytes_quantized'] < report['params_bytes_fp32']


def test_qtensor_leaf_order_pins_flax_shape_check():
    # flax's Scope.param zips tree_leaves(value) against the abstract
    # init output PAIRWISE — the stored QuantTensor passes only because
    # q (the weight-shaped leaf) flattens FIRST; a reorder would break
    # every quantized apply
    qt = quant.quantize(np.ones((4, 2), np.float32))
    leaves = jax.tree_util.tree_leaves(qt)
    assert len(leaves) == 2
    assert leaves[0] is qt.q and leaves[1] is qt.scale
    # tree_map rebuilds the node (the engine's abstract-params path)
    mapped = jax.tree_util.tree_map(lambda x: x, qt)
    assert isinstance(mapped, QuantTensor)
    assert mapped.shape == (4, 2) and mapped.ndim == 2


def test_unknown_mix_and_bad_precision_raise():
    with pytest.raises(KeyError):
        quant.resolve_mix('int4_mix')
    with pytest.raises(ValueError):
        quant.resolve_mix(((r'.*', 'int4'),))
    if quant.fp8_dtype() is None:
        with pytest.raises(ValueError):
            quant.resolve_mix('fp8_mix')


def test_int8_rule_on_equivariant_weight_raises():
    # the negative test the ISSUE pins: an l>0 LinearSE3 weight matched
    # by an int8 rule must raise, not silently quantize
    rng = np.random.RandomState(2)
    tree = {'to_q': {'w0': rng.normal(size=(4, 4)).astype(np.float32),
                     'w1': rng.normal(size=(4, 4)).astype(np.float32)}}
    with pytest.raises(EquivariantPrecisionError) as e:
        quant.quantize_params(
            tree, ((r'(^|/)w[01]$', 'int8'), (r'.*', 'fp32')))
    assert 'to_q/w1' in str(e.value)
    # the shipped mix routes the same tree cleanly: w0 int8, w1 bf16
    qp, _ = quant.quantize_params(tree, 'int8_mix')
    assert isinstance(qp['to_q']['w0'], QuantTensor)
    assert qp['to_q']['w1'].dtype == jnp.bfloat16


def test_w3_mixer_rank_guard():
    # a num_degrees >= 4 model's LinearSE3 creates a 2-d `w3` CHANNEL
    # MIXER (an l>0 equivariant-path weight) that shares its name with
    # the 3-d radial weights — the rank guard must route it to the
    # bf16 passthrough, never silently int8 (review finding, pinned)
    rng = np.random.RandomState(10)
    tree = {'to_v': {'project': {'w3': rng.normal(size=(8, 8))
                                 .astype(np.float32)}},
            'pair_3_3': {'w3': rng.normal(size=(16, 8, 4))
                         .astype(np.float32)}}
    qp, _ = quant.quantize_params(tree, 'int8_mix')
    assert not isinstance(qp['to_v']['project']['w3'], QuantTensor)
    assert qp['to_v']['project']['w3'].dtype == jnp.bfloat16
    assert isinstance(qp['pair_3_3']['w3'], QuantTensor)
    # and an EXPLICIT unguarded int8 rule on the 2-d mixer raises
    with pytest.raises(EquivariantPrecisionError):
        quant.quantize_params(
            {'to_v': {'w3': tree['to_v']['project']['w3']}},
            ((r'(^|/)w3$', 'int8'), (r'.*', 'fp32')))


def test_quantize_params_stays_on_host():
    # the quantization pass must never touch a device: the engine's
    # single device_put is the only transfer (bf16 casts included)
    rng = np.random.RandomState(11)
    tree = {'w0': rng.normal(size=(4, 4)).astype(np.float32),
            'w1': rng.normal(size=(4, 4)).astype(np.float32)}
    qp, _ = quant.quantize_params(tree, 'int8_mix')
    assert isinstance(qp['w1'], np.ndarray)          # host bf16
    assert isinstance(qp['w0'].q, np.ndarray)
    assert isinstance(qp['w0'].scale, np.ndarray)


def test_concat_weights_quantized_and_mixed():
    rng = np.random.RandomState(3)
    a = quant.quantize(rng.normal(size=(8, 4, 2)).astype(np.float32))
    b = quant.quantize(rng.normal(size=(8, 6, 2)).astype(np.float32))
    cat = quant.concat_weights([a, b], axis=1)
    assert isinstance(cat, QuantTensor)
    assert cat.shape == (8, 10, 2) and cat.scale.shape == (1, 10, 2)
    ref = np.concatenate([quant.dequantize(a), quant.dequantize(b)],
                         axis=1)
    np.testing.assert_allclose(quant.dequantize(cat), ref, rtol=0,
                               atol=0)
    # mixed group falls back to dequantized fp32 concat, never a crash
    plain = rng.normal(size=(8, 3, 2)).astype(np.float32)
    mixed = quant.concat_weights([a, jnp.asarray(plain)], axis=1)
    assert not isinstance(mixed, QuantTensor)
    np.testing.assert_allclose(
        np.asarray(mixed),
        np.concatenate([quant.dequantize(a), plain], axis=1), atol=1e-7)


# --------------------------------------------------------------------- #
# kernel: the Pallas scale-column epilogue (interpret mode)
# --------------------------------------------------------------------- #
def test_fused_pairwise_conv_scale_epilogue_interpret():
    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv,
    )
    rng = np.random.RandomState(4)
    E, mid, IF, O, P = 24, 16, 12, 8, 3
    h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
    w3 = rng.normal(size=(mid, IF, O)).astype(np.float32)
    b3 = jnp.asarray(rng.normal(size=(IF, O)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(E, P, IF)), jnp.float32)
    qt = quant.quantize(w3, contract_axes=(0,))
    out = fused_pairwise_conv(h, jnp.asarray(qt.q), v2, b3=b3,
                              interpret=True,
                              w3_scale=jnp.asarray(qt.scale))
    # XLA reference on the dequantized weight: the in-tile epilogue is
    # the same math reassociated once
    R = jnp.einsum('em,mko->eko', h,
                   jnp.asarray(quant.dequantize(qt))) + b3
    ref = jnp.einsum('epk,eko->epo', v2, R)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


# --------------------------------------------------------------------- #
# model-level: fused epilogues vs the dequantized-weights oracle
# --------------------------------------------------------------------- #
@pytest.fixture(scope='module')
def toy():
    from se3_transformer_tpu.native.loader import chain_adjacency
    from se3_transformer_tpu.training.denoise import DenoiseConfig
    cfg = DenoiseConfig(num_tokens=24, dim=8, dim_head=8, heads=2,
                        depth=2, num_degrees=2, max_sparse_neighbors=4)
    module = cfg.build_module()
    rng = np.random.RandomState(0)
    L = 12
    batch = dict(
        tokens=jnp.asarray(rng.randint(0, 24, size=(1, L))),
        coords=jnp.asarray(rng.normal(size=(1, L, 3)).astype(np.float32)),
        mask=jnp.ones((1, L), bool),
        adj=jnp.asarray(chain_adjacency(L)))
    params = jax.jit(module.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), batch['tokens'], batch['coords'],
        mask=batch['mask'], adj_mat=batch['adj'],
        return_type=1)['params']
    host = jax.tree_util.tree_map(np.asarray, params)
    return cfg, module, host, batch


def _dequant_tree(qtree):
    """fp32 reference of a quantized tree (dequantize QuantTensors,
    upcast bf16 casts) — the oracle every fused epilogue must match."""
    return jax.tree_util.tree_map(
        lambda x: quant.dequantize(x) if isinstance(x, QuantTensor)
        else (np.asarray(x, np.float32)
              if getattr(x, 'dtype', None) == jnp.bfloat16 else x),
        qtree, is_leaf=lambda x: isinstance(x, QuantTensor))


def _apply(module, params, batch):
    return np.asarray(module.apply(
        {'params': params}, batch['tokens'], batch['coords'],
        mask=batch['mask'], adj_mat=batch['adj'], return_type=1))


def test_quantized_apply_matches_dequant_oracle(toy):
    cfg, module, host, batch = toy
    qtree, report = quant.quantize_params(host, 'int8_mix')
    assert report['bytes_ratio'] < 0.6
    out_q = _apply(module, qtree, batch)
    out_ref = _apply(module, _dequant_tree(qtree), batch)
    # the fused epilogues are the oracle's math with ONE multiply
    # reassociated — roundoff, nothing more
    assert np.abs(out_q - out_ref).max() < 1e-5
    # and the quantization error proper is visible but bounded (the
    # banked tradeoff, NOT a 1e-4 quantity — int8 grids cannot do that)
    out_fp32 = _apply(module, host, batch)
    assert 0 < np.abs(out_q - out_fp32).max() < 0.1


def test_so2_backend_quantized_matches_dequant_oracle():
    # the so2 path's radial matmul rides the SAME _radial_contract
    # epilogue — one checkpoint, any backend mix, quantized or not
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    rng = np.random.RandomState(5)
    n, dim = 24, 8
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                        jnp.float32)
    mask = jnp.ones((1, n), bool)
    mod = SE3TransformerModule(
        dim=dim, depth=1, num_degrees=2, output_degrees=2,
        reduce_dim_out=True, attend_self=True, num_neighbors=6,
        heads=2, dim_head=8, tie_key_values=True, conv_backend='so2')
    params = jax.jit(mod.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coors, mask=mask,
        return_type=1)['params']
    host = jax.tree_util.tree_map(np.asarray, params)
    qtree, _ = quant.quantize_params(host, 'int8_mix')
    out_q = mod.apply({'params': qtree}, feats, coors, mask=mask,
                      return_type=1)
    out_ref = mod.apply({'params': _dequant_tree(qtree)}, feats, coors,
                        mask=mask, return_type=1)
    assert float(jnp.abs(out_q - out_ref).max()) < 1e-5


def test_flash_fused_pairwise_quantized_matches_unfused():
    # the flash kernel's in-tile scale epilogue vs the unfused grouped
    # path, SAME quantized params (the 'one checkpoint serves fused and
    # unfused' guarantee must survive quantization)
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    rng = np.random.RandomState(6)
    n, k, dim = 32, 8, 8
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                        jnp.float32)
    mask = jnp.ones((1, n), bool)
    kw = dict(dim=dim, depth=1, num_degrees=2, output_degrees=2,
              reduce_dim_out=True, attend_self=True, use_null_kv=True,
              num_neighbors=k, heads=2, dim_head=8,
              tie_key_values=True, shared_radial_hidden=True)
    unfused = SE3TransformerModule(**kw)
    fused = SE3TransformerModule(fuse_pairwise=True, **kw)
    params = jax.jit(fused.init, static_argnames=('return_type',))(
        jax.random.PRNGKey(0), feats, coors, mask=mask,
        return_type=1)['params']
    qtree, _ = quant.quantize_params(
        jax.tree_util.tree_map(np.asarray, params), 'int8_mix')
    out_u = unfused.apply({'params': qtree}, feats, coors, mask=mask,
                          return_type=1)
    out_f = fused.apply({'params': qtree}, feats, coors, mask=mask,
                        return_type=1)
    assert float(jnp.abs(out_u - out_f).max()) < 1e-4


def test_quantized_equivariance_degrees_2_4():
    # weight-only quantization restricted to invariant-input matmuls
    # must preserve equivariance to roundoff — at the degrees where
    # rotation error would compound if a rule leaked
    from se3_transformer_tpu.models.se3_transformer import (
        SE3TransformerModule,
    )
    from se3_transformer_tpu.utils.validation import equivariance_l2
    rng = np.random.RandomState(7)
    n, k, dim = 48, 8, 8
    feats = jnp.asarray(rng.normal(size=(1, n, dim)), jnp.float32)
    coors = jnp.asarray(np.cumsum(rng.normal(size=(1, n, 3)), axis=1),
                        jnp.float32)
    mask = jnp.ones((1, n), bool)
    for d in (2, 4):
        mod = SE3TransformerModule(
            dim=dim, depth=1, num_degrees=d + 1, output_degrees=2,
            reduce_dim_out=True, attend_self=True, num_neighbors=k,
            heads=2, dim_head=8, tie_key_values=True)
        params = jax.jit(mod.init, static_argnames=('return_type',))(
            jax.random.PRNGKey(0), feats, coors, mask=mask,
            return_type=1)['params']
        host = jax.tree_util.tree_map(np.asarray, params)
        for mix in ('int8_mix', 'bf16'):
            qtree, _ = quant.quantize_params(host, mix)
            eq = equivariance_l2(mod, qtree, feats, coors, mask)
            assert eq < 1e-4, (d, mix, eq)


# --------------------------------------------------------------------- #
# engine: restore-time quantization, parity gates, swaps
# --------------------------------------------------------------------- #
def test_engine_restore_time_quantization_and_mix_parity(toy, tmp_path):
    from se3_transformer_tpu.inference import InferenceEngine
    from se3_transformer_tpu.native.loader import pad_to_bucket
    from se3_transformer_tpu.training.checkpoint import CheckpointManager
    cfg, module, host, batch = toy
    buckets = (12, 24)

    # one checkpoint serves fp32, bf16, and int8-mix engines unchanged
    mgr = CheckpointManager(str(tmp_path / 'ckpt'))
    mgr.save(0, (host, None, 0))
    engines = {
        mix: InferenceEngine.from_checkpoint(
            module, str(tmp_path / 'ckpt'), buckets=buckets,
            batch_size=2, precision=None if mix == 'fp32' else mix)
        for mix in ('fp32', 'bf16', 'int8_mix')}

    e8 = engines['int8_mix']
    # restore-time quantization, test-pinned: the device tree holds the
    # int8 STORAGE (and its scales) for every matched class — the fp32
    # degree-0 weights never materialized on device
    w3 = e8.params['conv_in']['pair_0_0']['w3']
    assert isinstance(w3, QuantTensor)
    assert jnp.asarray(w3.q).dtype == jnp.int8
    dk = e8.params['conv_in']['pair_0_0']['Dense_0']['kernel']
    assert isinstance(dk, QuantTensor)
    w0 = e8.params['conv_in']['self_interact']['w0']
    assert isinstance(w0, QuantTensor)
    # executables keyed apart from the fp32 engine's
    assert all(k[2] == 'float32+int8_mix' for k in e8.executables)

    # the memory claim off the cost ledger: args <= 0.6x fp32
    arg8 = e8.cost_payloads[e8._key(24)]['memory']['argument_bytes']
    arg32 = engines['fp32'].cost_payloads[
        engines['fp32']._key(24)]['memory']['argument_bytes']
    assert arg8 / arg32 <= 0.6

    # implementation parity: every mix's engine vs the fp32 engine fed
    # that mix's dequantized tree, padded AND unpadded rows
    rng = np.random.RandomState(8)
    tok12 = rng.randint(0, cfg.num_tokens, size=12)
    crd12 = rng.normal(size=(12, 3)).astype(np.float32)
    for mix in ('bf16', 'int8_mix'):
        qtree, _ = quant.quantize_params(host, mix)
        ref = InferenceEngine(module, _dequant_tree(qtree),
                              buckets=buckets, batch_size=2)
        e = engines[mix]
        # unpadded: exact-length bucket; padded: same rows forced into
        # the larger bucket (the padded-vs-unpadded serving semantics)
        out_u = np.asarray(e.predict(tok12, crd12))
        ref_u = np.asarray(ref.predict(tok12, crd12))
        t, c, m = pad_to_bucket([tok12], [crd12], 24, batch_size=2)
        out_p = np.asarray(e.run(24, t, c, m))[0, :12]
        ref_p = np.asarray(ref.run(24, t, c, m))[0, :12]
        assert np.abs(out_u - ref_u).max() < 1e-4, mix
        assert np.abs(out_p - ref_p).max() < 1e-4, mix
        # padded-vs-unpadded within the quantized engine itself, at the
        # existing serving gate
        assert np.abs(out_u - out_p).max() < 1e-4, mix

    # rolling-swap re-quantization: raw fp32 params in, the setter
    # re-quantizes at the engine's own mix — same executables, zero
    # recompiles, identical outputs
    compiled_before = dict(e8.compile_seconds)
    out_before = np.asarray(e8.predict(tok12, crd12))
    e8.params = host
    assert isinstance(e8.params['conv_in']['pair_0_0']['w3'],
                      QuantTensor)
    assert e8.compile_seconds == compiled_before
    out_after = np.asarray(e8.predict(tok12, crd12))
    assert np.abs(out_after - out_before).max() == 0.0

    # the stats/telemetry surface names the mix + the byte delta
    stats = e8.stats()
    assert stats['precision'] == 'int8_mix'
    assert stats['quant']['params_bytes_quantized'] < \
        stats['quant']['params_bytes_fp32']


@pytest.mark.parametrize('mix', ['int8_mix', 'fp8_mix'])
def test_engine_from_params_mix_parity_and_argument_bytes(toy, mix):
    """An engine built from a param tree (no checkpoint): its compiled
    buckets take at most 0.6x the fp32 engine's argument bytes, and it
    adds nothing to quantization itself — padded and unpadded rows
    agree with the fp32 evaluation of the same quantized weights."""
    if mix == 'fp8_mix' and quant.fp8_dtype() is None:
        pytest.skip('no fp8-e4m3 dtype in this jax build')
    from se3_transformer_tpu.inference import InferenceEngine
    from se3_transformer_tpu.native.loader import pad_to_bucket
    cfg, module, host, batch = toy
    kw = dict(buckets=(12, 24), batch_size=2)
    e = InferenceEngine(module, host, precision=mix, **kw)
    fp32 = InferenceEngine(module, host, **kw)
    qtree, _ = quant.quantize_params(host, mix)
    ref = InferenceEngine(module, _dequant_tree(qtree), **kw)

    def argument_bytes(engine):
        return engine.cost_payloads[engine._key(24)]['memory'][
            'argument_bytes']
    e.warmup()
    fp32.warmup()
    assert argument_bytes(e) <= 0.6 * argument_bytes(fp32)

    rng = np.random.RandomState(9)
    tok = rng.randint(0, cfg.num_tokens, size=12)
    crd = rng.normal(size=(12, 3)).astype(np.float32)
    out_u = np.asarray(e.predict(tok, crd))
    ref_u = np.asarray(ref.predict(tok, crd))
    t, c, m = pad_to_bucket([tok], [crd], 24, batch_size=2)
    out_p = np.asarray(e.run(24, t, c, m))[0, :12]
    ref_p = np.asarray(ref.run(24, t, c, m))[0, :12]
    assert np.abs(out_u - ref_u).max() < 1e-4
    assert np.abs(out_p - ref_p).max() < 1e-4
    assert np.abs(out_u - out_p).max() < 1e-4
