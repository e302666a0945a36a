"""The global attention layers' core's share of its roofline in the decoder
that mixes them with sliding-window layers: max(operations / 197 TFLOP/s,
bytes / 819 GB/s) over the device seconds under the leaf `mha_core` (28 query
heads over 4 key-value heads of 128 at 16,384 positions in the cell; on the
TPU the streaming Pallas kernel's three launches, fed the key-value heads
repeated, and their glue). The counts (`harness/smallthinker_counts.py`) are
of the causal triangle, forward plus a backward of twice the forward, each
tensor once at the heads the model has; neither the kernel's own
recomputation of the scores nor the block's replay is counted, so the share
reads low, never high. Nothing in a cell whose model has no window (the
other decoders' `mha_core` is read by `mha_core_roofline.train` and
`mha64_core_roofline.train`)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    m, mix = ctx['model'], ctx['traffic']
    if not m.get('sliding_window_size'):
        return None
    seconds = lm.leaf_seconds(ctx, __file__, ('mha_core',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import smallthinker_counts as counts
    launches = steps * mix['batch'] * counts.layers(m, '*')
    return lm.roofline_share(
        ctx, seconds,
        counts.core_train_flops(m, mix['seq'], '*', launches),
        counts.core_bytes(m, mix['seq'], launches),
        'mha_core at the causal triangle')


read = lm.guarded(_read)
