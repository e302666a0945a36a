"""The grouped products' share of their roofline: max(operations / 197 TFLOP/s,
bytes / 819 GB/s) over the device seconds under the leaf `moe_experts`.
Operations and bytes come from the step's own count of (token, expert) pairs
computed here (`moe_local_pairs`) through `harness/lm_counts.py`: forward,
dX and dW, each tensor once, no replay, the same count whatever implements
the products. The seconds hold the replayed forward too, so the share reads
low, never high."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    from harness import lm_counts
    c = ctx['counters']
    if not c.get('moe_local_pairs') or not c.get('expert_layer_steps'):
        return None
    return lm.roofline_share(
        ctx, lm.leaf_seconds(ctx, __file__, ('moe_experts',)),
        lm_counts.grouped_flops(ctx['model'], c['moe_local_pairs']),
        lm_counts.grouped_bytes(ctx['model'], c['moe_local_pairs'],
                                c['expert_layer_steps']), 'moe_experts')


read = lm.guarded(_read)
