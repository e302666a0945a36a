"""Device ms a step in XLA's own instructions that hold no product and are
no launch (casts, copies, norms, gates, reductions, gathers): the reducer's
`glue_s` over every leaf and the unlabelled. With `product_s` and `launch_s`
it sums to the device's busy time."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _dense_products as dp  # noqa: E402


def _read(ctx):
    red = dp.reduction(ctx, __file__)
    if red is None:
        return None
    return dp.lm.prog.per_step_ms(ctx, dp.total(red['glue_s']) or None)


read = dp.lm.guarded(_read)
