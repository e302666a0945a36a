"""Device ms a step under the leaf `swa_core`: the sliding-window attention
core's launches (on a TPU `swa_core_fwd` and `swa_core_bwd` over the window's
tile table; a replay launches none) and whatever XLA runs beside them.
Nothing in a program or a cell without the leaf."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(ctx, __file__, ('swa_core',))


read = lm.guarded(_read)
