"""The short convolution's core's share of its roofline: max(operations /
197 TFLOP/s, bytes / 819 GB/s) over the device seconds under the leaf
`sconv_core` (B * X, the taps, C * z: elementwise passes in XLA). The counts
(`harness/lfm2_counts.py`): forward plus a backward of twice the forward;
B, C, X, the output and their cotangents once each, float32. The seconds hold
the replayed forward and every intermediate XLA writes, so the share reads
low, never high."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    seconds = lm.leaf_seconds(ctx, __file__, ('sconv_core',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import lfm2_counts
    m, mix = ctx['model'], ctx['traffic']
    launches = steps * lfm2_counts.layers(m, 'C')
    tokens = mix['batch'] * mix['seq']
    return lm.roofline_share(
        ctx, seconds,
        lfm2_counts.sconv_core_train_flops(m, tokens, launches),
        lfm2_counts.sconv_core_bytes(m, tokens, launches), 'sconv_core')


read = lm.guarded(_read)
