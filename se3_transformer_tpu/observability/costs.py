"""HLO cost ledger: compiled-program cost/memory introspection -> the
schema'd `cost` record.

Every perf claim this repo makes is ultimately a claim about flops,
bytes, or peak HBM. This module turns any lowered/AOT executable into
a machine-checkable
`cost` record body (observability.schema kind='cost'):

  * `flops` / `bytes_accessed` — XLA's `compiled.cost_analysis()`,
    falling back to a dot-product FLOP estimate parsed out of the
    compiled HLO text on backends where cost_analysis returns None
    (the `source` field says which path produced the numbers, so a
    fallback estimate can never masquerade as the real analysis).
    NOTE the known blindness: Pallas-kernel FLOPs are invisible to
    BOTH paths, and lax.map bodies count once instead of trip-count
    times — `cost` records measure the XLA-visible program; the
    benchmark counts the whole step analytically
    (`benchmark/harness/counts.py`).
  * `memory` / `peak_bytes` — `compiled.memory_analysis()` split into
    argument/output/temp (the per-shard footprint estimate
    scripts/width_table.py has used since PR 5's weak-scaling rows;
    SPMD emits one per-device program, so these ARE per-chip numbers).
    `peak_bytes` is XLA's static argument+output+temp estimate, not a
    runtime high-water mark — the RetraceWatchdog's
    `peak_bytes_in_use` remains the measured figure where the backend
    exposes one.
  * `collectives` — the per-class {count, bytes} accounting reused
    verbatim from PR 5's `parallel.exchange.analyze_hlo_comm`, so a
    cost record of a sharded program also ledgers its communication.

Consumers: `InferenceEngine.warmup` (one
record per shape bucket — serving capacity planning reads
memory-per-bucket off the stream), `DenoiseTrainer` (the training step
factories' compiled program), scripts/width_table.py, and
scripts/perf_gate.py which enforces budgets over the resulting stream.
"""
from __future__ import annotations

import re
from typing import Optional

# cost_analysis property names differ across jax versions; these two are
# stable since 0.4.x
_FLOPS_KEYS = ('flops',)
_BYTES_KEYS = ('bytes accessed', 'bytes_accessed')

_MEMORY_FIELDS = (
    ('argument_bytes', 'argument_size_in_bytes'),
    ('output_bytes', 'output_size_in_bytes'),
    ('temp_bytes', 'temp_size_in_bytes'),
    ('alias_bytes', 'alias_size_in_bytes'),
    ('generated_code_bytes', 'generated_code_size_in_bytes'),
)

# dot lines in compiled HLO text carry operand shapes inline:
#   %dot.44 = f32[256,64]{1,0} dot(f32[256,256]{1,0} %a, f32[256,64]{1,0}
#       %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}, ...
_DOT_RE = re.compile(
    r'=\s*\S*?(?P<out>\w+\[[\d,]*\])\S*\s+dot\('
    r'\s*\S*?(?P<lhs>\w+\[[\d,]*\])[^)]*\).*?'
    r'lhs_contracting_dims=\{(?P<lc>[\d,]*)\}')
_SHAPE_DIMS_RE = re.compile(r'\[([\d,]*)\]')


def _dims(shape_token: str):
    m = _SHAPE_DIMS_RE.search(shape_token)
    return [int(d) for d in m.group(1).split(',') if d] if m else []


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def hlo_dot_flops(hlo_text: str) -> float:
    """Fallback FLOP estimate from the compiled HLO text: 2 * |output| *
    |contraction| summed over every dot. Elementwise/reduce flops are
    omitted (dots dominate every program this repo compiles), which is
    why records produced this way carry source='hlo_estimate'."""
    total = 0.0
    for m in _DOT_RE.finditer(hlo_text):
        out_dims = _dims(m.group('out'))
        lhs_dims = _dims(m.group('lhs'))
        contract = [int(d) for d in m.group('lc').split(',') if d]
        k = _prod(lhs_dims[d] for d in contract if d < len(lhs_dims))
        total += 2.0 * _prod(out_dims) * k
    return total


def _first(d: dict, keys):
    for k in keys:
        if k in d:
            return d[k]
    return None


def executable_cost_analysis(compiled) -> Optional[dict]:
    """`compiled.cost_analysis()` normalized to one dict, or None when
    the backend returns nothing (some plugin backends do) or raises."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - introspection is best-effort
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return dict(cost) if cost else None


def executable_memory(compiled) -> Optional[dict]:
    """`compiled.memory_analysis()` split into the schema's named byte
    fields, or None when the backend exposes no analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return None
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return None
    out = {}
    for name, attr in _MEMORY_FIELDS:
        v = getattr(ma, attr, None)
        if v is not None:
            out[name] = int(v)
    return out or None


def cost_payload(compiled, *, label: str, hlo_text: Optional[str] = None,
                 ) -> dict:
    """The schema'd `cost` record body (observability.schema kind='cost',
    minus run_id) for one compiled executable.

    `hlo_text` is reused when the caller already fetched it (a flagship
    program's `as_text()` runs to megabytes); otherwise it is read from
    the executable only when actually needed — for the fallback flops
    estimate, or for the collective ledger on hosts where collectives
    are even possible (device_count > 1). A single-device host never
    pays the multi-MB serialization just to ledger an empty dict.
    """
    from ..parallel.exchange import analyze_hlo_comm

    def text():
        nonlocal hlo_text
        if hlo_text is None:
            try:
                hlo_text = compiled.as_text()
            except Exception:  # noqa: BLE001
                hlo_text = ''
        return hlo_text

    cost = executable_cost_analysis(compiled)
    if cost is not None:
        source = 'cost_analysis'
        flops = float(_first(cost, _FLOPS_KEYS) or 0.0)
        bytes_accessed = _first(cost, _BYTES_KEYS)
        bytes_accessed = float(bytes_accessed) \
            if bytes_accessed is not None else None
    elif text():
        source = 'hlo_estimate'
        flops = hlo_dot_flops(text())
        bytes_accessed = None
    else:
        source = 'unavailable'
        flops = None
        bytes_accessed = None

    memory = executable_memory(compiled)
    if memory is None:
        # REFUSE to fabricate a zero split: a peak_bytes=0 record
        # passes every memory ceiling vacuously, silently disarming
        # the exact budgets scripts/perf_gate.py exists to enforce.
        # Callers guard this call — a missing record is loud
        # (width_table's memory_analysis_error field, a failed
        # perf-gate fresh-cost arm), a zeroed one is a lie.
        raise RuntimeError(
            'memory_analysis unavailable on this executable/backend — '
            'refusing to emit a zero-memory cost record')
    for name, _ in _MEMORY_FIELDS[:3]:
        memory.setdefault(name, 0)
    peak = (memory['argument_bytes'] + memory['output_bytes']
            + memory['temp_bytes'])

    if hlo_text is None:
        try:
            import jax
            parse_collectives = jax.device_count() > 1
        except Exception:  # noqa: BLE001 - no backend: parse anyway
            parse_collectives = True
    else:
        parse_collectives = True   # text already in hand — free
    collectives = {}
    if parse_collectives:
        try:
            collectives = analyze_hlo_comm(text())['collectives']
        except Exception:  # noqa: BLE001 - the ledger survives a
            pass           # parse fail

    return dict(label=label, source=source, flops=flops,
                bytes_accessed=bytes_accessed, memory=memory,
                peak_bytes=peak, collectives=collectives)


def step_cost_payload(step_fn, *args, label: str) -> dict:
    """`cost_payload` for a jitted-but-not-yet-introspectable step
    function: lower+compile against `args` (shapes only — nothing
    executes, so donation marks are harmless) and ledger the result.
    With the persistent compilation cache enabled this is warm whenever
    the same program already compiled in-process."""
    compiled = step_fn.lower(*args).compile()
    return cost_payload(compiled, label=label)
