"""Device ms a step in the attention core: the leaves `attn_core` (XLA) and
`pallas_attention*` (the fused kernel, where a cell turns it on)."""
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
    return prog.per_step_ms(ctx, sum(
        s for leaf, s in red['leaf_s'].items()
        if leaf == 'attn_core' or leaf.startswith('pallas_attention')))


def read(ctx):
    return prog and prog.or_nothing(_read, ctx)
