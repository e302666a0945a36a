"""Device ms a step under the leaf `bd_core`: the block-diffusion attention
core's launches (forward, dkv and dq; a replay launches none) and the casts
around them. Nothing in a program or a cell without the leaf."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(ctx, __file__, ('bd_core',))


read = lm.guarded(_read)
