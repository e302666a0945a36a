"""Device ms a step in XLA's dense products: the reducer's `product_s` over
every leaf but the cores that have a roofline (`_dense_products.CORES`),
forward, replay and backward."""
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
    return dp.lm.prog.per_step_ms(ctx, dp.dense(red, 'product_s') or None)


read = dp.lm.guarded(_read)
