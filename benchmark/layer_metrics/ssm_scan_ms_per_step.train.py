"""Device ms a step under the leaf `ssm_scan`: from dt, A, x, B, C to y (the
chunked scan, D included), forward, backward and replay."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(ctx, __file__, ('ssm_scan',))


read = lm.guarded(_read)
