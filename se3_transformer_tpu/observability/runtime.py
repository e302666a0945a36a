"""Compile/retrace watchdog + device runtime snapshots.

A silent retrace is the classic TPU perf cliff: one leaked dynamic
shape and every "hot" step pays a multi-minute XLA compile. The
watchdog snapshots each tracked jitted function's `_cache_size()` at
every flush; after warmup, any growth raises a loud structured
`RetraceWarning` and rides the flush record so the JSONL stream
carries the evidence. A process-wide `jax.monitoring` compile-event
counter travels alongside as forensic data: warnings key off cache
sizes only (the counter cannot attribute a compile to a function), but
`compile_events_delta > 0` in a post-warmup flush record is the
tell-tale that SOMETHING compiled inside the window — including
functions the watchdog does not track.

`device_memory_stats` snapshots the accelerator allocator
(bytes_in_use / peak_bytes_in_use) when the backend exposes it; CPU
returns None and the schema allows it.
"""
from __future__ import annotations

import warnings
from typing import Callable, Dict, Optional

import jax


class RetraceWarning(UserWarning):
    """A tracked step function retraced after warmup."""


# module-level compile-event counter: jax.monitoring listeners are
# global and cannot be unregistered individually, so ONE listener feeds
# every watchdog (each baselines the counter at arm time)
_COMPILE_EVENTS = [0]
_LISTENER_INSTALLED = [False]


def _install_compile_listener():
    if _LISTENER_INSTALLED[0]:
        return
    _LISTENER_INSTALLED[0] = True
    # no guard: a listener that failed to install would make every
    # "zero post-warmup compiles" gate pass without counting anything
    from jax import monitoring

    def _on_event(event: str, **kwargs):
        if 'compil' in event:
            _COMPILE_EVENTS[0] += 1

    def _on_duration(event: str, duration: float, **kwargs):
        if 'compil' in event:
            _COMPILE_EVENTS[0] += 1

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


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
            _install_compile_listener()
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
