"""The scan's share of its roofline: max(operations / 197 TFLOP/s, bytes /
819 GB/s) over the device seconds under the leaf `ssm_scan`. The counts
(`harness/hybrid_counts.py`) are the same whatever implements the scan:
inside a chunk at half the square, the chunk states built and read, forward
plus a backward of twice the forward; x, B, C, dt, y and their cotangents once
each. The seconds hold the replayed forward and every intermediate the XLA
form writes, so the share reads low, never high."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    seconds = lm.leaf_seconds(ctx, __file__, ('ssm_scan',))
    steps = ctx['counters'].get('steps')
    if not seconds or not steps:
        return None
    from harness import hybrid_counts
    m, mix = ctx['model'], ctx['traffic']
    launches = steps * mix['batch'] * hybrid_counts.layers(m, 'M')
    return lm.roofline_share(
        ctx, seconds,
        hybrid_counts.scan_train_flops(m, mix['seq'], launches),
        hybrid_counts.scan_bytes(m, mix['seq'], launches), 'ssm_scan')


read = lm.guarded(_read)
