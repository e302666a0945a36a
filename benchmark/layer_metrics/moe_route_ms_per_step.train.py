"""Device ms a step around the grouped products: the leaves `moe_router`
(logits, sigmoid, top-k, weights), `moe_dispatch` (sort and gather into
expert order) and `moe_combine` (back to token order, the weighted sum)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(
        ctx, __file__, ('moe_router', 'moe_dispatch', 'moe_combine'))


read = lm.guarded(_read)
