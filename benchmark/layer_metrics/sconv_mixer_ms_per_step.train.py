"""Device ms a step under the gated short convolutions' three leaves:
`sconv_in` (input projection and its split), `sconv_core` (B * X, the taps,
C * z) and `sconv_out` (output projection), forward, backward and replay."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(ctx, __file__,
                               ('sconv_in', 'sconv_core', 'sconv_out'))


read = lm.guarded(_read)
