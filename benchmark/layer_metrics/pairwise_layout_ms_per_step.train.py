"""Device ms a step under the leaf `pairwise_layout` (the pads, transposes and
reshapes the pairwise kernels' wrappers issue), all phases."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
try:
    import _program_profile as prog  # noqa: E402
except ImportError:   # a checkout that lacks the helper reads nothing
    prog = None


def _read(ctx):
    red = prog.profile(ctx, __file__)
    if red is None:
        return None
    return prog.per_step_ms(ctx, red['leaf_s'].get('pairwise_layout', 0.0))


def read(ctx):
    return prog and prog.or_nothing(_read, ctx)
