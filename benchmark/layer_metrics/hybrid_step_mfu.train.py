"""Model-FLOP utilization of the hybrid decoder's whole step:
`hybrid_counts.train_step_flops` (3x forward, causal attention and the
chunks' inside at half, the pairs really computed here, no replay) x steps
over the window's seconds, against the bf16 peak. Nothing without a pattern
string in the model's sizes (another decoder's cell)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    c, mix, trace = ctx['counters'], ctx['traffic'], ctx.get('trace')
    if not trace or not c.get('steps') or 'moe_local_pairs' not in c \
            or 'hybrid_override_pattern' not in ctx['model']:
        return None
    from harness import hybrid_counts
    per_sequence = c['moe_local_pairs'] / c['steps'] / mix['batch']
    flops = c['steps'] * mix['batch'] * hybrid_counts.train_step_flops(
        ctx['model'], mix['seq'], per_sequence)
    return 100.0 * flops / trace['window_s'] / ctx['peaks']['bf16_flops']


read = lm.guarded(_read)
