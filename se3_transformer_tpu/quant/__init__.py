"""Post-training quantization as a first-class serving precision layer.

ROADMAP item 3: per-replica HBM is the binding constraint on replica
count, and the fp32 param tree is the largest argument. This package
converts a restored checkpoint into a quantized pytree — int8 (or
fp8-e4m3) weights with per-output-channel fp32 scales for the
invariant-input matmuls, bf16 passthrough for higher-degree channel
mixers — selected by first-match-wins (param-path regex, precision)
rules mirroring the `conv_backend` / `partition_rules` idiom. Dequant
fuses into the consumers (LinearSE3's einsum, the radial-contract
Pallas/XLA paths, the flash kernel's in-tile radial matmul), so the
full-precision weights never materialize on device; every shipped mix
is gated on the equivariance-L2 harness + quantized-vs-fp32 parity
(tests/test_quant.py).

    from se3_transformer_tpu import quant
    qparams, report = quant.quantize_params(params, 'int8_mix')
    engine = InferenceEngine(module, params, precision='int8_mix')
"""
from .qtensor import (
    QuantTensor, concat_weights, dequantize, fp8_dtype, is_quantized,
    quantize, weight_or_none,
)
from .rules import (
    MIXES, PRECISIONS, EquivariantPrecisionError, mix_name,
    quantize_params, resolve_mix, resolve_precision,
)

__all__ = [
    'MIXES', 'PRECISIONS', 'EquivariantPrecisionError', 'QuantTensor',
    'concat_weights', 'dequantize', 'fp8_dtype', 'is_quantized',
    'mix_name', 'quantize', 'quantize_params', 'resolve_mix',
    'resolve_precision', 'weight_or_none',
]
