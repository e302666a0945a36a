"""Model-FLOP utilization of the whole step of the looped decoder:
`ouro_counts.train_step_flops` (3x forward over `total_ut_steps` passes, the
core at the causal triangle, the head once a pass, no replay) x steps over
the window's seconds, against the bf16 peak. A token counts once a step in
the rate and `total_ut_steps` times here. Nothing in a cell whose model is
not looped (another decoder's cell)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    c, mix, trace = ctx['counters'], ctx['traffic'], ctx.get('trace')
    if not trace or not c.get('steps') \
            or ctx['model'].get('total_ut_steps', 1) < 2:
        return None
    from harness import ouro_counts as counts
    flops = c['steps'] * mix['batch'] * counts.train_step_flops(
        ctx['model'], mix['seq'])
    return 100.0 * flops / trace['window_s'] / ctx['peaks']['bf16_flops']


read = lm.guarded(_read)
