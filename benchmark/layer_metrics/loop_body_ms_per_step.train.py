"""Device ms a step under any `ut_<t>` component of a looped stack: the
pattern's blocks and the final norm of every pass, forward, replayed and
backward (the reducer's `pass_s`, which files an operation by the pass its
path goes through, whatever its leaf). The four passes are printed apart,
once a trace. Nothing in a cell whose model is not looped, nor from a
program whose reducer has no such table."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    if ctx['model'].get('total_ut_steps', 1) < 2:
        return None
    red = lm.profile(ctx, __file__)
    passes = red and red.get('pass_s')
    if not passes:
        return None
    steps = ctx['counters'].get('steps') or 1
    if not red.get('passes_said'):      # the readers' own kept copy
        red['passes_said'] = True
        from se3_transformer_tpu.observability import profiling
        print(f'passes of the looped stack, a step of {steps}:\n'
              + profiling.format_passes(red, steps), flush=True)
    return lm.prog.per_step_ms(
        ctx, sum(sum(phases.values()) for phases in passes.values()))


read = lm.guarded(_read)
