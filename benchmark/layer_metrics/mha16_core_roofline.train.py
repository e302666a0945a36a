"""The attention core's share of its roofline in the looped decoder:
max(operations / 197 TFLOP/s, bytes / 819 GB/s) over the device seconds under
the leaf `mha_core` (16 query heads over 16 key-value heads of 128, groups of
one, at 8,192 positions in the cell; on the TPU the repo's own two launches
under the rule ('mha', 0), one forward and one backward a layer and pass: 16
each way a step). The counts (`harness/ouro_counts.py`) are of the causal
triangle, forward plus a backward of twice the forward, each tensor once;
neither the kernel's own recomputation of the scores nor what it computes of
a diagonal tile's masked pairs is counted, so the share reads low, never
high. Nothing in a cell whose model is not looped (the other decoders'
`mha_core` is read by `mha_core_roofline.train`, `mha64_core_roofline.train`
and `mha28_core_roofline.train`)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    m, mix = ctx['model'], ctx['traffic']
    if m.get('total_ut_steps', 1) < 2:
        return None
    seconds = lm.leaf_seconds(ctx, __file__, ('mha_core',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import ouro_counts as counts
    launches = steps * mix['batch'] * counts.layers(m, '*') \
        * m['total_ut_steps']
    return lm.roofline_share(
        ctx, seconds, counts.core_train_flops(m, mix['seq'], launches),
        counts.core_bytes(m, mix['seq'], launches),
        'mha_core at the causal triangle, every pass')


read = lm.guarded(_read)
