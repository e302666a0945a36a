"""Compile/retrace watchdog + device runtime snapshots.

A silent retrace is the classic TPU perf cliff: one leaked dynamic
shape and every "hot" step pays a multi-minute XLA compile. The
watchdog snapshots each tracked jitted function's `_cache_size()` at
every flush; after warmup, any growth raises a loud structured
`RetraceWarning` and rides the flush record so the JSONL stream
carries the evidence. A process-wide `jax.monitoring` compile-event
counter travels alongside as forensic data: warnings key off cache
sizes only, but `compile_events_delta > 0` in a post-warmup flush record
is the tell-tale that SOMETHING compiled inside the window — including
functions the watchdog does not track.

The same listener keeps the compile log: per event the kind (jaxpr
trace, lowering, backend compile, cache retrieval, compile time saved),
the function's name, the seconds and the wall-clock span, as JAX hands
them over (`compile_log`, reduced by `compile_seconds`).

`device_memory_stats` snapshots the accelerator allocator
(bytes_in_use / peak_bytes_in_use) when the backend exposes it; CPU
returns None and the schema allows it.
"""
from __future__ import annotations

import time
import warnings
from typing import Callable, Dict, List, Optional

import jax

from .profiling import union_length


class RetraceWarning(UserWarning):
    """A tracked step function retraced after warmup."""


# jax.monitoring listeners are global and cannot be unregistered, so ONE
# listener feeds every watchdog (each baselines the counter at arm time) and
# keeps the compile log
_COMPILE_EVENTS = [0]
_COMPILE_LOG: List[dict] = []
_LISTENER_INSTALLED = [False]
# entries kept. Tracing the flagship step alone reports 57,755 `jaxpr_trace`
# events (every inner jit, einsum and `where`), all but a few hundred of
# them inside another function's trace: those are folded into the outer
# entry as it closes (`_on_time_span`). A process that retraces without end
# stops growing here
_LOG_CAPACITY = 100000
_LOG_DROPPED = [0]

# what JAX reports per compiled function (jax 0.9,
# dispatch.py::LogElapsedTimeContextManager and compiler.py), by kind
COMPILE_EVENT_KINDS = {
    '/jax/core/compile/jaxpr_trace_duration': 'jaxpr_trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'backend_compile',
    '/jax/compilation_cache/cache_retrieval_time_sec': 'cache_retrieval',
    '/jax/compilation_cache/compile_time_saved_sec': 'compile_time_saved',
}


def install_compile_listener():
    """Start counting compile events and keeping the compile log; called
    by every RetraceWatchdog and by `enable_compilation_cache()`, so a
    process that turns the cache on has the log from its first compile."""
    if _LISTENER_INSTALLED[0]:
        return
    _LISTENER_INSTALLED[0] = True
    # no guard: a listener that failed to install would make every
    # "zero post-warmup compiles" gate pass without counting anything
    from jax import monitoring

    def _plain(fun_name):
        # the trace is reported under `f`, lowering and load under `jit(f)`
        if fun_name and fun_name.startswith('jit(') and fun_name[-1] == ')':
            return fun_name[4:-1]
        return fun_name

    def _log(kind, fun_name, seconds, start, end):
        if len(_COMPILE_LOG) >= _LOG_CAPACITY:
            _LOG_DROPPED[0] += 1
            return
        _COMPILE_LOG.append(dict(kind=kind, fun_name=_plain(fun_name),
                                 seconds=float(seconds),
                                 start=float(start), end=float(end)))

    def _on_event(event: str, **kwargs):
        if 'compil' in event:
            _COMPILE_EVENTS[0] += 1

    def _on_duration(event: str, duration: float, **kwargs):
        if 'compil' in event:
            _COMPILE_EVENTS[0] += 1
        # the cache's two events come with neither a function nor a span:
        # they fire inside the `backend_compile` span of the function
        # being loaded, which names them when it closes (below)
        if event.startswith('/jax/compilation_cache/') \
                and event in COMPILE_EVENT_KINDS:
            now = time.time()
            _log(COMPILE_EVENT_KINDS[event], None, duration,
                 now - max(duration, 0.0), now)

    def _on_time_span(event: str, start: float, end: float, **kwargs):
        kind = COMPILE_EVENT_KINDS.get(event)
        if kind is None:
            return
        fun_name = kwargs.get('fun_name')
        if kind == 'backend_compile':
            for entry in reversed(_COMPILE_LOG):
                if entry['fun_name'] is not None or entry['end'] < start:
                    break
                entry['fun_name'] = _plain(fun_name)
        elif kind == 'jaxpr_trace':
            # a span closes after the spans nested in it: the traces that
            # began inside this one are its own seconds, and the three
            # set-up metrics count nested spans once anyway
            i = len(_COMPILE_LOG)
            while i and _COMPILE_LOG[i - 1]['start'] >= start:
                i -= 1
            _COMPILE_LOG[i:] = [e for e in _COMPILE_LOG[i:]
                                if e['kind'] != 'jaxpr_trace']
        _log(kind, fun_name, end - start, start, end)

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_time_span_listener(_on_time_span)


def compile_log() -> List[dict]:
    """What JAX reported of every compile since the listener was installed,
    in order: {kind, fun_name, seconds, start, end} with `kind` one of
    COMPILE_EVENT_KINDS' values and start/end on `time.time()`'s clock; a
    function traced inside another's trace (an inner jit) has no entry of
    its own. A
    `backend_compile` entry spans the cache lookup too: with a
    `cache_retrieval` entry of the same function inside it the executable
    was loaded, not compiled."""
    return [dict(e) for e in _COMPILE_LOG]


def compile_seconds(fun_name: str,
                    log: Optional[List[dict]] = None) -> Optional[dict]:
    """Set-up seconds by cause, from the compile log: `trace_s` (jaxpr
    trace + lowering to MLIR of `fun_name`), `load_s` (its backend compile,
    or cache retrieval on a hit: the `backend_compile` span covers both),
    `cache_hit` (whether a retrieval lies inside that span), `saved_s` (the
    compile time the cache reports saved) and `other_s`: wall-clock seconds
    inside any other function's trace, lowering or load that began before
    `fun_name`'s load ended, counted once where they nest and not at all
    where they lie inside `fun_name`'s own spans (the kernels' inner jits
    are traced inside the step's trace); `entries` and `dropped` say how
    much of the log there is. None if `fun_name` never compiled."""
    log = _COMPILE_LOG if log is None else log
    mine = [e for e in log if e['fun_name'] == fun_name]
    loads = [e for e in mine if e['kind'] == 'backend_compile']
    if not loads:
        return None
    load_end = max(e['end'] for e in loads)
    own = [(e['start'], e['end']) for e in mine
           if e['kind'] in ('jaxpr_trace', 'lower', 'backend_compile')]
    before = [e for e in log
              if e['fun_name'] != fun_name and e['start'] < load_end
              and e['kind'] in ('jaxpr_trace', 'lower', 'backend_compile')]
    others = [(e['start'], e['end']) for e in before]
    heaviest: Dict[tuple, float] = {}
    for e in before:
        if not any(s <= e['start'] and e['end'] <= t for s, t in own):
            key = (e['fun_name'], e['kind'])
            heaviest[key] = heaviest.get(key, 0.0) + e['seconds']
    return dict(
        entries=len(log), dropped=_LOG_DROPPED[0],
        trace_s=sum(e['seconds'] for e in mine
                    if e['kind'] in ('jaxpr_trace', 'lower')),
        load_s=sum(e['seconds'] for e in loads),
        cache_hit=any(e['kind'] == 'cache_retrieval' for e in mine),
        saved_s=sum(e['seconds'] for e in mine
                    if e['kind'] == 'compile_time_saved'),
        other_s=union_length(others, holes=own),
        # where `other_s` went: [function, kind, seconds], heaviest first
        other_top=[[f, k, v] for (f, k), v in sorted(
            heaviest.items(), key=lambda kv: -kv[1])[:8]])


def device_memory_stats() -> Optional[dict]:
    """Allocator byte counters of device 0, or None (CPU / no support).

    Only byte-valued keys are kept so flush records stay small."""
    try:
        dev = jax.local_devices()[0]
        stats = dev.memory_stats()
        if not stats:
            return None
        out = {k: int(v) for k, v in stats.items()
               if 'bytes' in k and isinstance(v, (int, float))}
        return out or None
    except Exception:  # noqa: BLE001
        return None


class RetraceWatchdog:
    """Tracks jitted functions' trace-cache sizes across flushes.

        wd = RetraceWatchdog({'train_step': step_fn})
        ... warmup step(s) ...
        wd.check()   # first check ARMS (baselines cache sizes)
        ... hot steps ...
        snap = wd.check()   # retrace after warmup -> RetraceWarning
                            # + snap['retraced'] entries

    Each check re-baselines, so one retrace warns exactly once. The
    `on_warn` callback (e.g. MetricLogger.log_record) receives the
    retraced payload for the JSONL stream.
    """

    def __init__(self, fns: Optional[Dict[str, Callable]] = None,
                 on_warn: Optional[Callable[[list], None]] = None,
                 use_monitoring: bool = True):
        self._fns: Dict[str, Callable] = {}
        self._on_warn = on_warn
        self._armed = False
        self._baseline: Dict[str, int] = {}
        self._compile_seen = _COMPILE_EVENTS[0]
        self.warnings_total = 0
        if use_monitoring:
            install_compile_listener()
        for name, fn in (fns or {}).items():
            self.track(name, fn)

    def track(self, name: str, fn: Callable):
        """Track a function. Functions without `_cache_size` (e.g. AOT
        compiled executables, which cannot retrace) are recorded as
        static."""
        self._fns[name] = fn

    def cache_sizes(self) -> Dict[str, int]:
        out = {}
        for name, fn in self._fns.items():
            size = getattr(fn, '_cache_size', None)
            try:
                out[name] = int(size()) if callable(size) else -1
            except Exception:  # noqa: BLE001
                out[name] = -1
        return out

    def arm(self):
        """Baseline current cache sizes; growth after this warns."""
        self._armed = True
        self._baseline = self.cache_sizes()
        self._compile_seen = _COMPILE_EVENTS[0]

    def check(self) -> dict:
        """Snapshot for the flush record. First call arms (warmup);
        later calls compare against the baseline and warn on growth.
        compile_events_delta counts process-wide compile events since
        the previous check — forensic only (unattributable), but >0
        after warmup means some function compiled inside the window."""
        sizes = self.cache_sizes()
        events = _COMPILE_EVENTS[0]
        snap = dict(cache_sizes=sizes,
                    compile_events=events,
                    compile_events_delta=events - self._compile_seen,
                    retraced=[],
                    warnings_total=self.warnings_total,
                    memory=device_memory_stats())
        self._compile_seen = events
        if not self._armed:
            self.arm()
            snap['armed'] = True
            return snap
        for name, size in sizes.items():
            prev = self._baseline.get(name)
            if prev is not None and prev >= 0 and size > prev:
                snap['retraced'].append(
                    dict(fn=name, cache_size=size, was=prev))
        if snap['retraced']:
            self.warnings_total += len(snap['retraced'])
            snap['warnings_total'] = self.warnings_total
            detail = ', '.join(
                f"{r['fn']}: trace cache {r['was']} -> {r['cache_size']}"
                for r in snap['retraced'])
            warnings.warn(
                f'step function retraced after warmup ({detail}) — a '
                f'leaked dynamic shape is recompiling the hot path',
                RetraceWarning, stacklevel=2)
            if self._on_warn is not None:
                try:
                    self._on_warn(snap['retraced'])
                except Exception:  # noqa: BLE001 - logging must not kill
                    pass
        # re-baseline: each retrace warns once, steady state stays silent
        self._baseline = sizes
        return snap
