"""The compile log (observability.runtime): what JAX reports of every trace,
lowering, compile and cache load, by function, kept by the one listener that
RetraceWatchdog counts from; and PhaseTimer's spans on the profiler's clock."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from se3_transformer_tpu.observability import PhaseTimer, profile_trace
from se3_transformer_tpu.observability import runtime


def _probe():
    # a fresh function object under one name: the same HLO module, so the
    # second compile of a process finds the first's cache entry
    def compile_log_probe(x):
        return jnp.tanh(x @ x).sum()
    return jax.jit(compile_log_probe)


@pytest.fixture
def cache_everything():
    """The persistent cache takes entries of any size and compile time."""
    keep = (jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    yield
    jax.config.update('jax_persistent_cache_min_compile_time_secs', keep[0])
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', keep[1])


def test_log_holds_three_phases_by_function_and_a_retrieval_when_warm(
        cache_everything):
    # tests/conftest.py turned the cache on, which installed the listener
    assert runtime._LISTENER_INSTALLED[0]
    counted = runtime._COMPILE_EVENTS[0]
    x = jnp.ones((32, 32))
    marks = []
    for _ in range(2):
        marks.append(len(runtime.compile_log()))
        _probe()(x).block_until_ready()
    first = [e for e in runtime.compile_log()[marks[0]:marks[1]]
             if e['fun_name'] == 'compile_log_probe']
    second = [e for e in runtime.compile_log()[marks[1]:]
              if e['fun_name'] == 'compile_log_probe']
    for entries in (first, second):
        kinds = [e['kind'] for e in entries]
        for kind in ('jaxpr_trace', 'lower', 'backend_compile'):
            assert kinds.count(kind) == 1, kinds
        for e in entries:
            assert e['end'] >= e['start'] and e['seconds'] == \
                pytest.approx(e['end'] - e['start'], abs=1e-6) \
                or e['kind'] == 'compile_time_saved'
        spans = {e['kind']: e for e in entries}
        assert spans['jaxpr_trace']['end'] <= spans['lower']['start'] \
            <= spans['backend_compile']['start']
    # the second compile of the same module is a cache hit: the retrieval
    # is named after the function whose load it lies in
    kinds = [e['kind'] for e in second]
    assert 'cache_retrieval' in kinds and 'compile_time_saved' in kinds
    load = next(e for e in second if e['kind'] == 'backend_compile')
    hit = next(e for e in second if e['kind'] == 'cache_retrieval')
    assert load['start'] <= hit['start'] and hit['end'] <= load['end'] + 1e-3
    secs = runtime.compile_seconds('compile_log_probe',
                                   runtime.compile_log()[marks[1]:])
    assert secs['cache_hit'] and secs['load_s'] == \
        pytest.approx(load['seconds'])
    assert secs['trace_s'] > 0
    # RetraceWatchdog's counter is fed by the same listener
    assert runtime._COMPILE_EVENTS[0] > counted
    assert runtime.compile_seconds('never_compiled') is None


def test_a_trace_inside_a_trace_has_no_entry_of_its_own():
    """Inner jits are traced inside the outer function's trace: their
    seconds are the outer entry's, and the log stays a few entries a
    compile (the flagship step reports 57,755 such events)."""
    def compile_log_inner(x):
        return jnp.sin(x) * 2

    def compile_log_outer(x):
        return jax.jit(compile_log_inner)(x) + 1

    def traced(since):
        return [e['fun_name'] for e in runtime.compile_log()[since:]
                if e['kind'] == 'jaxpr_trace']

    a, b = jnp.ones(3), jnp.ones(5)    # eager ops compile too: before
    mark = len(runtime.compile_log())
    jax.jit(compile_log_outer)(a).block_until_ready()
    assert traced(mark) == ['compile_log_outer']
    kinds = [e['kind'] for e in runtime.compile_log()[mark:]]
    assert kinds.count('lower') == 1 and kinds.count('backend_compile') == 1
    # traced on its own it has its entry
    mark = len(runtime.compile_log())
    jax.jit(compile_log_inner)(b).block_until_ready()
    assert traced(mark) == ['compile_log_inner']


def test_compile_seconds_on_a_fabricated_log():
    def e(kind, fun, start, end):
        return dict(kind=kind, fun_name=fun, seconds=end - start,
                    start=start, end=end)
    log = [
        e('jaxpr_trace', 'fill', 0.0, 2.0),
        e('lower', 'fill', 2.0, 3.0),
        e('backend_compile', 'fill', 3.0, 7.0),
        # the step: the kernels' inner jits are traced inside its trace
        e('jaxpr_trace', 'fused_pairwise_conv_bxf', 11.0, 12.0),
        e('jaxpr_trace', 'fused_pairwise_conv_bwd', 13.0, 15.0),
        e('jaxpr_trace', 'train_step', 10.0, 40.0),
        e('lower', 'train_step', 40.0, 60.0),
        e('compile_time_saved', 'train_step', 88.0, 88.0),
        e('cache_retrieval', 'train_step', 61.0, 88.0),
        e('backend_compile', 'train_step', 60.5, 88.5),
        # nested: counted once
        e('jaxpr_trace', '_where', 8.2, 8.4),
        e('jaxpr_trace', 'init', 8.0, 9.0),
        # began after the step's load ended: not set-up before the step
        e('jaxpr_trace', 'reference_step', 100.0, 150.0),
    ]
    log[7]['seconds'] = 200.0
    secs = runtime.compile_seconds('train_step', log)
    assert secs['trace_s'] == pytest.approx(30.0 + 20.0)
    assert secs['load_s'] == pytest.approx(28.0)
    assert secs['cache_hit'] is True and secs['saved_s'] == 200.0
    # fill 7 s + init 1 s; the kernels' traces lie inside the step's own
    assert secs['other_s'] == pytest.approx(8.0)
    assert runtime.compile_seconds('step', log) is None


def test_phase_timer_spans_lie_on_the_profilers_clock(tmp_path):
    """PhaseTimer.phase also opens a TraceAnnotation: in a captured trace
    the span is an event of `/host:CPU`, named as the phase."""
    timer = PhaseTimer()
    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(8)).block_until_ready()
    with profile_trace(str(tmp_path)):
        for _ in range(2):
            with timer.phase('probe_step'):
                f(jnp.ones(8)).block_until_ready()
    assert timer.total_count('probe_step') == 2
    path = glob.glob(os.path.join(str(tmp_path), 'plugins', 'profile', '*',
                                  '*.xplane.pb'))[0]
    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == '/host:CPU')
    spans = [e for line in host.lines for e in line.events
             if e.name == 'probe_step']
    assert len(spans) == 2
    # the host's clock and the profiler's agree on how long they were
    assert sum(e.duration_ns for e in spans) * 1e-9 == pytest.approx(
        timer.total_seconds('probe_step'), rel=0.5)
    # and the reducer can keep them beside the device events
    from se3_transformer_tpu.observability import profiling
    kept = profiling.read_xplane(path, ['probe_step'])['host']
    assert [h[1] for h in kept] == ['probe_step', 'probe_step']
