"""Device ms a step under the state-space layers' five leaves: `ssm_in`
(input projection), `ssm_conv`, `ssm_scan`, `ssm_gate` (gated group norm)
and `ssm_out` (output projection), forward, backward and replay."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(
        ctx, __file__,
        ('ssm_in', 'ssm_conv', 'ssm_scan', 'ssm_gate', 'ssm_out'))


read = lm.guarded(_read)
