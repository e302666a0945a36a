"""The grouped-query attention core's share of its roofline: max(operations
/ 197 TFLOP/s, bytes / 819 GB/s) over the device seconds under the leaf
`mha_core` (on the TPU the streaming Pallas kernel's three launches, fed as
many key-value heads as query heads, and their glue). Causal at half the
square, forward plus a backward of twice the forward; neither the kernel's
own recomputation of the scores nor the block's replay is counted, so the
share reads low, never high."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    seconds = lm.leaf_seconds(ctx, __file__, ('mha_core',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import hybrid_counts
    m, mix = ctx['model'], ctx['traffic']
    launches = steps * mix['batch'] * hybrid_counts.layers(m, '*')
    return lm.roofline_share(
        ctx, seconds,
        hybrid_counts.attention_core_train_flops(m, mix['seq'], launches),
        hybrid_counts.attention_core_bytes(m, mix['seq'], launches),
        'mha_core')


read = lm.guarded(_read)
