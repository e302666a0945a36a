"""Model-FLOP utilization of the whole step of the decoder that mixes global
and sliding-window attention layers: `smallthinker_counts.train_step_flops`
(3x forward, each core at its visible pairs, the head over the rows held,
the pairs really computed here, no replay) x steps over the window's
seconds, against the bf16 peak. Nothing in a cell whose model has no
window (another decoder's cell)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    c, mix, trace = ctx['counters'], ctx['traffic'], ctx.get('trace')
    if not trace or not c.get('steps') or 'moe_local_pairs' not in c \
            or not ctx['model'].get('sliding_window_size'):
        return None
    from harness import smallthinker_counts as counts
    per_sequence = c['moe_local_pairs'] / c['steps'] / mix['batch']
    flops = c['steps'] * mix['batch'] * counts.train_step_flops(
        ctx['model'], mix['seq'], per_sequence)
    return 100.0 * flops / trace['window_s'] / ctx['peaks']['bf16_flops']


read = lm.guarded(_read)
