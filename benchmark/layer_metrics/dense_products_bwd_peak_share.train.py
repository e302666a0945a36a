"""`dense_products_peak_share.train` over the phase `backward` alone: the
cotangents' and the weights' products, against the bf16 peak."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _dense_products as dp  # noqa: E402


def _read(ctx):
    return dp.dense_peak_share(ctx, __file__, ('backward',))


read = dp.lm.guarded(_read)
