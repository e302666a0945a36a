"""The attention core's share of its roofline: max(operations / 197 TFLOP/s,
bytes / 819 GB/s) over the device seconds under the leaf `latent_core` (on
the TPU the streaming Pallas kernel's three launches and their glue).
Causal at half the square, forward plus a backward of twice the forward;
neither the kernel's own recomputation of the scores nor the block's replay
is counted, so the share reads low, never high."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402


def _read(ctx):
    from harness import lm_counts
    m, mix, steps = ctx['model'], ctx['traffic'], ctx['counters'].get('steps')
    if not steps:
        return None
    launches = steps * mix['batch'] * (m['num_hidden_layers']
                                       + m['num_nextn_predict_layers'])
    return lm.roofline_share(
        ctx, lm.leaf_seconds(ctx, __file__, ('latent_core',)),
        lm_counts.attention_core_train_flops(m, mix['seq'], launches),
        lm_counts.attention_core_bytes(m, mix['seq'], launches),
        'latent_core')


read = lm.guarded(_read)
