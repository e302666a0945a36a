"""Host spans around the benchmark's own calls into each layer, kept in
memory. In a traced run every span is also a `jax.profiler.TraceAnnotation`,
so that it lies on the profiler's clock beside the device operations and an
idle gap can be given to what the host was doing."""
import contextlib
import time


class Spans:
    def __init__(self, annotate=False):
        self.annotate = annotate
        self.durations = {}       # name -> [seconds]
        self.compiles = []        # compile events seen while armed
        self.armed = False

    @contextlib.contextmanager
    def span(self, name):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    def add(self, name, seconds):
        self.durations.setdefault(name, []).append(float(seconds))

    # -- a compile inside the measured window is a harness fault ---------- #
    def watch_compiles(self):
        import jax.monitoring

        def on_duration(event, duration, **kw):
            if self.armed and 'backend_compile' in event:
                self.compiles.append((event, round(duration, 3), kw))

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def check_no_compiles(self):
        if self.compiles:
            raise SystemExit(
                'benchmark: compiled inside the measured window (a shape was '
                f'not warmed up): {self.compiles[:3]}')
