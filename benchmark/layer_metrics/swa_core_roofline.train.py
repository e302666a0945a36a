"""The sliding-window attention core's share of its roofline: max(operations
/ 197 TFLOP/s, bytes / 819 GB/s) over the device seconds under the leaf
`swa_core`. The counts (`harness/smallthinker_counts.py`) are of the visible
pairs alone (T w - w (w - 1) / 2 a head), forward plus a backward of twice
the forward, each tensor once; neither the kernel's own recomputation of the
scores, nor what it computes of a tile's masked pairs, nor XLA's passes under
the leaf are counted, so the share reads low, never high. Nothing in a cell
whose model has no window or whose trace has no such leaf."""
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
    seconds = lm.leaf_seconds(ctx, __file__, ('swa_core',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import smallthinker_counts as counts
    launches = steps * mix['batch'] * counts.layers(m, 'W')
    return lm.roofline_share(
        ctx, seconds,
        counts.core_train_flops(m, mix['seq'], 'W', launches),
        counts.core_bytes(m, mix['seq'], launches),
        'swa_core at its visible pairs')


read = lm.guarded(_read)
