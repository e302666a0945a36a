"""The block-diffusion attention core's share of its roofline: max(operations
/ 197 TFLOP/s, bytes / 819 GB/s) over the device seconds under the leaf
`bd_core`. The counts (`harness/sdar_counts.py`) are of the visible pairs
alone (L^2 + L block_length a sequence and head), forward plus a backward of
twice the forward, each tensor once; neither the kernel's own recomputation
of the scores, nor what it computes of a tile's masked pairs, nor the casts
under the leaf are counted, so the share reads low, never high. Nothing in a
cell whose run has no block length (another decoder's cell) or whose trace
has no such leaf."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    m, mix = ctx['model'], ctx['traffic']
    block_length = (ctx.get('loss') or {}).get('block_length')
    if not block_length:
        return None
    seconds = lm.leaf_seconds(ctx, __file__, ('bd_core',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import sdar_counts
    launches = steps * mix['batch'] * sdar_counts.layers(m, '*')
    return lm.roofline_share(
        ctx, seconds,
        sdar_counts.bd_core_train_flops(m, mix['seq'], block_length,
                                        launches),
        sdar_counts.bd_core_bytes(m, mix['seq'], launches),
        'bd_core at its visible pairs')


read = lm.guarded(_read)
