"""What each device operation of a traced run is and how much it computes:

    python scripts/product_table.py <trace dir or .xplane.pb> [--steps N]
        [--device-kind 'TPU v5 lite']

prints, per leaf of MODEL_SCOPES and forward | replay | backward, the ms, TFLOP
and share of the bf16 peak of the products XLA compiled, the leaf's other ms
by its two heaviest categories, and its launches' ms
(`observability.profiling.format_products`, docs/OBSERVABILITY.md), and, for
a looped stack, its passes apart (`format_passes`: ms under each `ut_<t>`).
Reads the trace alone; needs no device."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == '__main__':
    from se3_transformer_tpu.observability.profiling import main
    main()
