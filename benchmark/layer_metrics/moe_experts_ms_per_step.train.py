"""Device ms a step under the leaf `moe_experts`: the grouped products over
the experts held and their activation, forward, backward and replay."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(ctx, __file__, ('moe_experts',))


read = lm.guarded(_read)
