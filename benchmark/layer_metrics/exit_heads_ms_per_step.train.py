"""Device ms a step in a looped stack's exits: the leaves `lm_head` (every
pass's logits and cross-entropy, chunk by chunk, forward, replayed and
backward), `exit_gate` (the gate's logits) and `exit_mix` (the passes'
probabilities, their entropy, the sums). Nothing in a cell whose model is
not looped (the other decoders' `lm_head` has no metric of its own)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    if ctx['model'].get('total_ut_steps', 1) < 2:
        return None
    return lm.leaf_ms_per_step(ctx, __file__,
                               ('lm_head', 'exit_gate', 'exit_mix'))


read = lm.guarded(_read)
