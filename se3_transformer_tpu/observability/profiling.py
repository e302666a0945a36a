"""Per-scope device-time profiling: jax.profiler traces -> the schema'd
`profile` record.

Supersedes the ad-hoc `scripts/trace_summary.py` / `stage_timings.py`
pair (trace_summary is now a thin CLI shim over this module;
stage_timings is retired — per-scope attribution of ONE traced step
replaces re-jitting each stage as its own upper-bound program). The
pipeline:

  1. `capture_step_profile` runs an already-warm callable a few times
     under `jax.profiler` trace capture.
  2. The Chrome trace (trace.json.gz) is parsed WITHOUT tensorboard /
     xprof: device-side events are those carrying an `hlo_op` arg (the
     XLA:CPU thunk tracer) or living on an accelerator-named process
     track (TPU/TensorCore). Nested events double-count their children
     (a `call` wraps its fusion), so durations are made EXCLUSIVE with
     a per-thread interval stack before any aggregation.
  3. Device time is attributed onto the model's `named_scope` labels
     (`MODEL_SCOPES` — the authoritative list in observability.timing)
     by joining trace op names against the compiled HLO's
     `metadata={op_name="jit(...)/<scope>/..."}` paths: the INNERMOST
     matching scope wins, `.clone`/fusion-suffix variants are folded.
     Without HLO text a substring fallback scans the op paths the trace
     itself carries.
  4. `profile_payload` emits the record body: per-scope
     {time_ms, share}, total device time, attribution coverage, the
     top unattributed ops (so a coverage miss is diagnosable from the
     record alone), and a roofline utilization figure when the caller
     supplies the program's flops (observability.costs) — meaningful
     on chip, reported-but-decorative on CPU hosts.

`make profile-smoke` gates a toy run on coverage >= 80% plus schema
validity; docs/PERFORMANCE.md covers how to read the output.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .timing import MODEL_SCOPES, profile_trace

__all__ = [
    'find_trace_file', 'load_trace_events', 'device_events',
    'exclusive_durations', 'fold_name', 'op_scope_map',
    'attribute_scopes', 'device_time_by_op', 'capture_step_profile',
    'profile_payload',
]


# --------------------------------------------------------------------- #
# trace loading (the old scripts/trace_summary.py logic, consolidated)
# --------------------------------------------------------------------- #
def find_trace_file(d: str) -> str:
    pats = [os.path.join(d, 'plugins', 'profile', '*', '*.trace.json.gz'),
            os.path.join(d, '**', '*.trace.json.gz'),
            os.path.join(d, '*.trace.json.gz')]
    hits = []
    for p in pats:
        hits += glob.glob(p, recursive=True)
    if not hits:
        raise FileNotFoundError(f'no *.trace.json.gz under {d}')
    return max(hits, key=os.path.getmtime)


def load_trace_events(path: str) -> List[dict]:
    """Events from a trace.json.gz file, or the newest one under a
    directory."""
    if os.path.isdir(path):
        path = find_trace_file(path)
    with gzip.open(path, 'rt') as f:
        data = json.load(f)
    return data.get('traceEvents', [])


def _track_names(events) -> Tuple[Dict[int, str], Dict[tuple, str]]:
    pnames, tnames = {}, {}
    for ev in events:
        if ev.get('ph') != 'M':
            continue
        if ev.get('name') == 'process_name':
            pnames[ev['pid']] = ev.get('args', {}).get('name', '')
        elif ev.get('name') == 'thread_name':
            tnames[(ev['pid'], ev.get('tid'))] = \
                ev.get('args', {}).get('name', '')
    return pnames, tnames


def device_events(events) -> Tuple[List[dict], dict]:
    """The device-side complete (ph='X') events of a trace.

    CPU traces (XLA:CPU thunk tracer) mark every executed HLO with an
    `hlo_op` arg — when any event carries one, exactly those are the
    device events. TPU/accelerator traces instead put ops on device-
    named process tracks (TPU / TensorCore / /device:...), the old
    trace_summary heuristic. Returns (events, info) where info names
    the tracks used."""
    pnames, tnames = _track_names(events)
    xs = [ev for ev in events if ev.get('ph') == 'X']
    hlo = [ev for ev in xs if (ev.get('args') or {}).get('hlo_op')]
    if hlo:
        tracks = sorted({tnames.get((ev['pid'], ev.get('tid')),
                                    str(ev.get('tid'))) for ev in hlo})
        return hlo, dict(selector='hlo_op', tracks=tracks)
    dev = {pid for pid, n in pnames.items()
           if re.search(r'tpu|tensorcore|/device|gpu|accelerator', n,
                        re.IGNORECASE)}
    if not dev:
        dev = {pid for pid, n in pnames.items()
               if not re.search(r'python|host|plugin|runtime', n,
                                re.IGNORECASE)}
    sel = [ev for ev in xs if ev.get('pid') in dev]
    return sel, dict(selector='device_pids',
                     tracks=sorted(pnames.get(p, str(p)) for p in dev))


def exclusive_durations(events) -> List[Tuple[dict, float]]:
    """(event, exclusive_us) pairs: each event's duration minus the time
    of events nested inside it on the same thread. Without this, a
    wrapping `call` and its fusion body would both be counted and every
    aggregate would double."""
    out = []
    by_thread: Dict[tuple, list] = {}
    for ev in events:
        by_thread.setdefault((ev.get('pid'), ev.get('tid')), []).append(ev)
    for evs in by_thread.values():
        # parents first on ties: longer duration wins the outer slot
        evs.sort(key=lambda e: (float(e.get('ts', 0.0)),
                                -float(e.get('dur', 0.0))))
        stack: list = []   # entries [end_ts, child_time, event]
        for ev in evs:
            ts = float(ev.get('ts', 0.0))
            dur = float(ev.get('dur', 0.0))
            while stack and ts >= stack[-1][0] - 1e-9:
                end, child, parent = stack.pop()
                out.append((parent, float(parent.get('dur', 0.0)) - child))
            if stack:
                stack[-1][1] += dur
            stack.append([ts + dur, 0.0, ev])
        while stack:
            end, child, parent = stack.pop()
            out.append((parent, float(parent.get('dur', 0.0)) - child))
    return out


def fold_name(name: str) -> str:
    """fusion.123 / copy.5 / reduce.21.clone -> family name."""
    return re.sub(r'(\.\d+)*(\.clone)?(\.\d+)*$', '', name)


# --------------------------------------------------------------------- #
# scope attribution
# --------------------------------------------------------------------- #
_METADATA_RE = re.compile(
    r'%?([\w.\-]+)\s*=\s.*metadata=\{[^}]*op_name="([^"]*)"')


def _scope_of_path(op_name: str, scopes: Sequence[str],
                   by_len: Sequence[str]) -> Optional[str]:
    """Innermost MODEL_SCOPES label on an op_name path. Exact component
    match wins; a substring pass (longest scope first, so 'attention'
    can never swallow a 'pallas_attention' component) covers wrapped
    components like 'transpose(jvp(attention))'."""
    comps = op_name.split('/')
    scope_set = set(scopes)
    for comp in reversed(comps):
        if comp in scope_set:
            return comp
    for comp in reversed(comps):
        for scope in by_len:
            if scope in comp:
                return scope
    return None


def op_scope_map(hlo_text: str,
                 scopes: Sequence[str] = MODEL_SCOPES) -> Dict[str, str]:
    """instruction-name -> scope label, from the compiled HLO's op_name
    metadata. Keys cover both the literal instruction name (what CPU
    trace events use, '.clone' included) and its folded family."""
    by_len = sorted(scopes, key=len, reverse=True)
    out: Dict[str, str] = {}
    for m in _METADATA_RE.finditer(hlo_text):
        scope = _scope_of_path(m.group(2), scopes, by_len)
        if scope is None:
            continue
        name = m.group(1)
        out[name] = scope
        out.setdefault(name.replace('.clone', ''), scope)
    return out


def _event_scope(ev: dict, op_to_scope: Dict[str, str],
                 scopes: Sequence[str], by_len: Sequence[str]
                 ) -> Optional[str]:
    args = ev.get('args') or {}
    candidates = [args.get('hlo_op'), ev.get('name')]
    for c in candidates:
        if not c:
            continue
        for key in (c, c.replace('.clone', ''), fold_name(c)):
            if key in op_to_scope:
                return op_to_scope[key]
    # no HLO mapping: some tracers carry the full op path in the args
    # (TPU xprof: 'tf_op' / 'long_name')
    for v in args.values():
        if isinstance(v, str) and '/' in v:
            scope = _scope_of_path(v, scopes, by_len)
            if scope:
                return scope
    return None


def attribute_scopes(events, op_to_scope: Dict[str, str],
                     scopes: Sequence[str] = MODEL_SCOPES,
                     pairs=None) -> dict:
    """Fold a trace's device events onto scope labels.

    Returns {scope_us: {scope: us}, total_us, attributed_us,
    unattributed: [(folded op name, us) hottest first]}. `pairs` lets
    a caller reuse an exclusive_durations() result instead of paying
    the per-thread interval stacks twice on a multi-MB trace."""
    by_len = sorted(scopes, key=len, reverse=True)
    scope_us: Dict[str, float] = {}
    unattr: Dict[str, float] = {}
    total = 0.0
    attributed = 0.0
    for ev, excl_us in (pairs if pairs is not None
                        else exclusive_durations(events)):
        if excl_us <= 0:
            continue
        total += excl_us
        scope = _event_scope(ev, op_to_scope, scopes, by_len)
        if scope is not None:
            scope_us[scope] = scope_us.get(scope, 0.0) + excl_us
            attributed += excl_us
        else:
            key = fold_name(ev.get('name', '?'))
            unattr[key] = unattr.get(key, 0.0) + excl_us
    return dict(scope_us=scope_us, total_us=total,
                attributed_us=attributed,
                unattributed=sorted(unattr.items(), key=lambda kv: -kv[1]))


def device_time_by_op(events, raw: bool = False,
                      match: Optional[str] = None,
                      pairs=None) -> List[Tuple[str, float]]:
    """Total exclusive device ms per (folded) op name, hottest first —
    the `scripts/trace_summary.py` table. `pairs` reuses a precomputed
    exclusive_durations() result."""
    agg: Dict[str, float] = {}
    for ev, excl_us in (pairs if pairs is not None
                        else exclusive_durations(events)):
        if excl_us <= 0:
            continue
        name = ev.get('name', '?')
        if match and match not in name:
            continue
        key = name if raw else fold_name(name)
        agg[key] = agg.get(key, 0.0) + excl_us / 1e3
    return sorted(agg.items(), key=lambda kv: -kv[1])


# --------------------------------------------------------------------- #
# capture + record body
# --------------------------------------------------------------------- #
def capture_step_profile(fn, args=(), *, log_dir: str, steps: int = 3):
    """Run `fn(*args)` `steps` times under trace capture (the callable
    must already be warm — a compile inside the window would swamp the
    attribution) and block on the last result. Returns log_dir."""
    import jax
    out = None
    with profile_trace(log_dir):
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
    return log_dir


def profile_payload(trace_dir: str, *, label: str,
                    hlo_text: Optional[str] = None,
                    scopes: Sequence[str] = MODEL_SCOPES,
                    flops_per_step: Optional[float] = None,
                    steps: int = 1, top_unattributed: int = 8,
                    device_kind: Optional[str] = None) -> dict:
    """The schema'd `profile` record body (kind='profile', minus
    run_id): per-scope device-time shares + attribution coverage for
    one captured trace, and the roofline figure when the caller
    supplies the program's per-step flops (observability.costs).
    `device_kind` names the accelerator the trace was captured on
    (`jax.devices()[0].device_kind`); utilization is priced against
    that device's published peak and omitted without it."""
    events = load_trace_events(trace_dir)
    dev, info = device_events(events)
    op_map = op_scope_map(hlo_text, scopes) if hlo_text else {}
    att = attribute_scopes(dev, op_map, scopes)
    total_us = att['total_us']
    scope_stats = {
        scope: dict(time_ms=round(us / 1e3, 3),
                    share=round(us / total_us, 4) if total_us else 0.0)
        for scope, us in sorted(att['scope_us'].items(),
                                key=lambda kv: -kv[1])}
    body = dict(
        label=label,
        scopes=scope_stats,
        device_time_ms=round(total_us / 1e3, 3),
        coverage=round(att['attributed_us'] / total_us, 4)
        if total_us else 0.0,
        steps=steps,
        tracks=info,
        unattributed_top=[
            dict(op=op, time_ms=round(us / 1e3, 3))
            for op, us in att['unattributed'][:top_unattributed]],
    )
    if flops_per_step and total_us:
        flops_per_sec = flops_per_step * steps / (total_us / 1e6)
        body['roofline'] = dict(
            flops_per_step=flops_per_step,
            device_flops_per_sec=round(flops_per_sec, 1))
        if device_kind is not None:
            # against the peak of the device the trace was taken on; a
            # CPU trace (device_kind=None) carries no utilization
            from ..utils.flops import device_peaks
            body['roofline']['utilization_vs_bf16_peak'] = round(
                flops_per_sec / device_peaks(device_kind)['bf16_flops'], 6)
    return body
