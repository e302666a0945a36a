"""Device ms a step in latent attention: the leaves `latent_qkv` (down- and
up-projections, norms, rotation), `latent_core` (scores, softmax, weighted
sum) and `latent_out`."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    return lm.leaf_ms_per_step(
        ctx, __file__, ('latent_qkv', 'latent_core', 'latent_out'))


read = lm.guarded(_read)
