"""How fast the MXU ran XLA's dense products: the reducer's `product_flops`
over their `product_s`, all phases, against the bf16 peak. Replayed products
count on both sides: this is no model-FLOP utilization."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _dense_products as dp  # noqa: E402


def _read(ctx):
    return dp.dense_peak_share(ctx, __file__)


read = dp.lm.guarded(_read)
