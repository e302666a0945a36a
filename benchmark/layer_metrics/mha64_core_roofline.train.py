"""The grouped-query attention core's share of its roofline at the model's
own head width (32 heads of 64 in the cell that reads it): max(operations /
197 TFLOP/s, bytes / 819 GB/s) over the device seconds under the leaf
`mha_core`. The counts (`harness/lfm2_counts.py`) are of the unpadded work,
whatever the kernel is fed: causal at half the square, forward plus a
backward of twice the forward; neither the kernel's own recomputation of the
scores nor the block's replay is counted, so the share reads low, never
high. Nothing in a cell whose model has no `C` layers (`mha_core_roofline
.train` reads those)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    m, mix = ctx['model'], ctx['traffic']
    if 'C' not in m.get('hybrid_override_pattern', ''):
        return None
    seconds = lm.leaf_seconds(ctx, __file__, ('mha_core',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import lfm2_counts
    launches = steps * mix['batch'] * lfm2_counts.layers(m, '*')
    return lm.roofline_share(
        ctx, seconds,
        lfm2_counts.attention_core_train_flops(m, mix['seq'], launches),
        lfm2_counts.attention_core_bytes(m, mix['seq'], launches),
        'mha_core at its own head width')


read = lm.guarded(_read)
