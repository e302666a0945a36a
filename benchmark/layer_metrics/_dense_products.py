"""For the four readers of XLA's dense products beside this file: what the
program's reducer says of each device instruction (`product_s`,
`product_flops`, `launch_s`, `glue_s`, the keys a reducer has since PR 36;
`product_bytes` in the table it prints), through `_lm_leaves.profile`, so by
scopes alone as every decoder reader.

"Dense" is every product XLA compiled itself (a `dot` or a `convolution`,
alone or in a fusion) under any leaf but the cores, which have rooflines of
their own: projections, dense and shared feed-forwards, routers, heads. The
operations are those of the instructions that ran, counted by the reducer from
the compiled module the profiler stores in the trace, and the seconds are
theirs: a replayed product counts on both sides, so the share says how fast
the MXU ran these instructions, not model-FLOP utilization. A reducer without
the keys (the parent of PR 36) gives None everywhere."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import _lm_leaves as lm  # noqa: E402

# leaves whose products have a roofline metric already
CORES = ('latent_core', 'mha_core', 'ssm_scan', 'sconv_core', 'moe_experts')
PHASES = ('forward', 'replay', 'backward')


def reduction(ctx, reader_file):
    """The reduction if it has the product tables, else None; prints the
    operator's table once per trace."""
    red = lm.profile(ctx, reader_file)
    if red is None or not red.get('product_s'):
        return None
    if not red.get('products_said'):     # the readers' own kept copy
        red['products_said'] = True
        _say(ctx, red)
    return red


def hand_count(ctx):
    """3 x (the cell's `forward_flops` at no pairs, less its core terms) x
    sequences x steps: what `harness/*_counts.py` make of the dense
    products' forward and backward, replay left out."""
    m, mix = ctx['model'], ctx['traffic']
    seq, pattern = mix['seq'], m.get('hybrid_override_pattern')
    if pattern is None:
        from harness import lm_counts as c
        cores = (m['num_hidden_layers'] + m['num_nextn_predict_layers']) \
            * c.attention_core_flops(m, seq)
    elif 'C' in pattern:
        from harness import lfm2_counts as c
        cores = c.layers(m, 'C') * c.sconv_core_flops(m, seq) \
            + c.layers(m, '*') * c.attention_core_flops(m, seq)
    else:
        from harness import hybrid_counts as c
        cores = c.layers(m, 'M') * c.scan_flops(m, seq) \
            + c.layers(m, '*') * c.attention_core_flops(m, seq)
    return 3 * ctx['counters']['steps'] * mix['batch'] * (
        c.forward_flops(m, seq, 0) - cores)


def _say(ctx, red):
    from se3_transformer_tpu.observability import profiling
    steps = ctx['counters'].get('steps') or 1
    print(f'products by leaf and phase (operations from '
          f'{red.get("flops_source")}), a step of {steps}:\n'
          + profiling.format_products(red, ctx['peaks']['bf16_flops'],
                                      ctx['peaks']['hbm_bytes_per_s'], steps),
          flush=True)
    parts = {name: total(red[name])
             for name in ('product_s', 'launch_s', 'glue_s')}
    print(f'device seconds {red["device_s"]:.6f} = products '
          f'{parts["product_s"]:.6f} + launches {parts["launch_s"]:.6f} + '
          f'glue {parts["glue_s"]:.6f} (sum {sum(parts.values()):.6f})',
          flush=True)
    counted = dense(red, 'product_flops', ('forward', 'backward'))
    try:
        by_hand = hand_count(ctx)
        print(f'dense operations, forward + backward: {counted:.6g} in the '
              f'instructions that ran, {by_hand:.6g} by harness/*_counts.py '
              f'({counted / by_hand:.4f})', flush=True)
    except Exception as e:    # a cell whose counts are shaped otherwise
        print(f'dense operations, forward + backward: {counted:.6g}; no '
              f'hand count ({type(e).__name__}: {e})', flush=True)


def dense(red, key, phases=PHASES):
    """The sum of `red[key]` over the dense leaves and `phases`."""
    return sum(by_phase.get(phase, 0.0)
               for leaf, by_phase in red[key].items() if leaf not in CORES
               for phase in phases)


def dense_peak_share(ctx, reader_file, phases=PHASES):
    """100 x the dense products' operations / their seconds / the bf16
    peak, or None."""
    red = reduction(ctx, reader_file)
    if red is None:
        return None
    seconds = dense(red, 'product_s', phases)
    if not seconds:
        return None
    return 100.0 * dense(red, 'product_flops', phases) / seconds \
        / ctx['peaks']['bf16_flops']


def total(table):
    return sum(v if isinstance(v, float) else sum(v.values())
               for v in table.values())
