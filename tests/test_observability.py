"""Telemetry subsystem tests (observability package): accumulator-under-
jit numerics vs a numpy reference, the one-sync-per-flush contract,
retrace watchdog behaviour, logger schema/context-manager/mirror fixes,
and the run summary obs_report renders. All CPU-only and cheap (tiny
jitted fns — the one model-level test uses the smallest trainable
config)."""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from se3_transformer_tpu.observability import (
    MetricAccumulator, MetricLogger, PhaseTimer, RetraceWarning,
    RetraceWatchdog,
)
from se3_transformer_tpu.observability import metrics as obs_metrics
from se3_transformer_tpu.observability.report import (
    load_jsonl, summarize, summarize_telemetry,
)
from se3_transformer_tpu.observability.schema import (
    SchemaError, validate_record, validate_stream,
)


# --------------------------------------------------------------------- #
# MetricAccumulator
# --------------------------------------------------------------------- #
def test_accumulator_under_jit_matches_numpy():
    @jax.jit
    def step(acc, x):
        return acc.update(loss=x.mean(), grad_norm=x.sum())

    acc = MetricAccumulator.zero(('loss', 'grad_norm'))
    rng = np.random.RandomState(0)
    vals = rng.normal(size=(17, 5)).astype(np.float32)
    for row in vals:
        acc = step(acc, jnp.asarray(row))
    window, fresh = acc.flush()

    means = vals.mean(axis=1)
    sums = vals.sum(axis=1)
    assert window['loss']['count'] == 17
    np.testing.assert_allclose(window['loss']['mean'], means.mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(window['loss']['min'], means.min(),
                               rtol=1e-5)
    np.testing.assert_allclose(window['loss']['max'], means.max(),
                               rtol=1e-5)
    np.testing.assert_allclose(window['grad_norm']['max'], sums.max(),
                               rtol=1e-5)
    # the fresh accumulator starts a clean window
    w2, _ = fresh.flush()
    assert w2['loss']['count'] == 0 and w2['loss']['mean'] is None


def test_accumulator_vector_metric_counts_elements():
    # per-micro-step loss vectors fold in element-wise (honest min/max)
    acc = MetricAccumulator.zero(('loss',))
    acc = jax.jit(lambda a, v: a.update(loss=v))(
        acc, jnp.asarray([1.0, 5.0, 3.0]))
    window, _ = acc.flush()
    assert window['loss'] == dict(count=3, mean=3.0, min=1.0, max=5.0)


def test_accumulator_rejects_undeclared_metric():
    acc = MetricAccumulator.zero(('loss',))
    with pytest.raises(KeyError):
        acc.update(never_declared=jnp.float32(1.0))


def test_one_host_fetch_per_flush_interval(monkeypatch):
    """The acceptance contract: hot steps do ZERO device-to-host
    transfers; flush() does exactly one."""
    fetches = []
    real = obs_metrics._host_fetch
    monkeypatch.setattr(obs_metrics, '_host_fetch',
                        lambda tree: (fetches.append(1), real(tree))[1])

    @jax.jit
    def step(acc, x):
        return acc.update(loss=x)

    acc = MetricAccumulator.zero(('loss',))
    flush_every = 6
    flushes = 0
    for i in range(2 * flush_every):
        acc = step(acc, jnp.float32(i))
        assert len(fetches) == flushes, 'hot step triggered a host fetch'
        if (i + 1) % flush_every == 0:
            window, acc = acc.flush()
            flushes += 1
            assert window['loss']['count'] == flush_every
            assert len(fetches) == flushes, 'flush must fetch exactly once'
    assert len(fetches) == 2  # one per flush interval, nothing else


def test_telemetry_step_signature_grows_only_by_accumulator():
    """make_sharded_train_step(telemetry=True) threads the accumulator
    pytree and nothing else; numerics match the plain step exactly."""
    import optax
    from se3_transformer_tpu.parallel import make_sharded_train_step

    def loss_fn(params, batch, rng):
        pred = batch['x'] * params['w']
        return ((pred - batch['y']) ** 2).mean(), {}

    opt = optax.sgd(0.1)
    batch = {'x': jnp.ones((8,)), 'y': 2 * jnp.ones((8,))}
    rng = jax.random.PRNGKey(0)

    plain = make_sharded_train_step(loss_fn, opt, donate=False)
    p1, s1, l1, _ = plain({'w': jnp.asarray(0.0)},
                          opt.init({'w': jnp.asarray(0.0)}), batch, rng)

    tele = make_sharded_train_step(loss_fn, opt, donate=False,
                                   telemetry=True)
    acc = MetricAccumulator.zero(('loss', 'grad_norm'))
    p2, s2, l2, _, acc = tele({'w': jnp.asarray(0.0)},
                              opt.init({'w': jnp.asarray(0.0)}),
                              batch, rng, acc)
    assert float(l1) == float(l2)
    assert float(p1['w']) == float(p2['w'])
    window, _ = acc.flush()
    assert window['loss']['count'] == 1
    np.testing.assert_allclose(window['loss']['mean'], float(l1),
                               rtol=1e-6)
    assert window['grad_norm']['mean'] > 0


# --------------------------------------------------------------------- #
# RetraceWatchdog
# --------------------------------------------------------------------- #
def test_watchdog_silent_on_steady_state_fires_on_shape_change():
    f = jax.jit(lambda x: x * 2)
    wd = RetraceWatchdog({'f': f}, use_monitoring=False)
    f(jnp.ones((4,)))
    snap = wd.check()            # warmup: arms
    assert snap.get('armed') and snap['cache_sizes']['f'] == 1

    f(jnp.ones((4,)))            # steady state: same trace
    with warnings.catch_warnings():
        warnings.simplefilter('error', RetraceWarning)
        snap = wd.check()
    assert snap['retraced'] == []

    f(jnp.ones((8,)))            # shape change: retrace
    with pytest.warns(RetraceWarning, match='retraced after warmup'):
        snap = wd.check()
    assert snap['retraced'] == [dict(fn='f', cache_size=2, was=1)]
    assert wd.warnings_total == 1

    # re-baselined: one retrace warns exactly once
    with warnings.catch_warnings():
        warnings.simplefilter('error', RetraceWarning)
        snap = wd.check()
    assert snap['retraced'] == []


def test_watchdog_on_warn_callback_feeds_logger():
    got = []
    f = jax.jit(lambda x: x + 1)
    wd = RetraceWatchdog({'f': f}, on_warn=got.append,
                         use_monitoring=False)
    f(jnp.ones((2,)))
    wd.check()
    f(jnp.ones((3,)))
    with pytest.warns(RetraceWarning):
        wd.check()
    assert got and got[0][0]['fn'] == 'f'


# --------------------------------------------------------------------- #
# PhaseTimer
# --------------------------------------------------------------------- #
def test_phase_timer_percentiles_and_window_reset():
    t = PhaseTimer()
    samples = [0.010, 0.020, 0.030, 0.040, 0.100]
    for s in samples:
        t.record('step', s)
    t.record('data', 0.005)
    win = t.window_summary()
    ref = np.asarray(samples) * 1e3
    assert win['step']['count'] == 5
    assert win['step']['p50_ms'] == pytest.approx(
        np.percentile(ref, 50), rel=1e-6)
    assert win['step']['p95_ms'] == pytest.approx(
        np.percentile(ref, 95), rel=1e-6)
    assert win['step']['max_ms'] == pytest.approx(100.0)
    assert win['data']['count'] == 1
    # window reset; cumulative survives
    assert t.window_summary() == {}
    cum = t.cumulative_summary()
    assert cum['step']['count'] == 5
    assert cum['step']['total_s'] == pytest.approx(sum(samples), rel=1e-6)
    assert t.total_seconds('step') == pytest.approx(sum(samples))


# --------------------------------------------------------------------- #
# MetricLogger
# --------------------------------------------------------------------- #
def test_metric_logger_schema_and_context_manager(tmp_path):
    path = str(tmp_path / 'metrics.jsonl')
    with MetricLogger(path, mirror=None, run_meta=dict(tool='test')) as lg:
        lg.log(1, loss=0.5)
        lg.log_record(
            'flush', step=1,
            window={'loss': dict(count=1, mean=0.5, min=0.5, max=0.5)},
            timing={'step': dict(count=1, p50_ms=1.0, p95_ms=1.0,
                                 max_ms=1.0, mean_ms=1.0)},
            runtime={})
    assert lg._fh is None  # closed by __exit__
    info = validate_stream(path)
    assert info['kinds'] == {'run_meta': 1, 'step': 1, 'flush': 1}
    head = json.loads(open(path).readline())
    assert head['kind'] == 'run_meta'
    assert head['run_id'] == lg.run_id
    assert 'backend' in head and 'code_rev' in head
    assert head['host']['pid'] == os.getpid()
    assert head['tool'] == 'test'


def test_metric_logger_closes_on_exception(tmp_path):
    path = str(tmp_path / 'metrics.jsonl')
    with pytest.raises(RuntimeError):
        with MetricLogger(path, mirror=None) as lg:
            lg.log(0, loss=1.0)
            raise RuntimeError('boom')
    assert lg._fh is None  # the old logger leaked the handle here


def test_metric_logger_mirror_fixed_precision():
    lines = []
    lg = MetricLogger(None, mirror=lines.append)
    rec = lg.log(3, loss=0.123456789012345)
    # mirror: readable fixed precision; record: full precision
    assert 'loss=0.1235' in lines[-1]
    assert '0.123456789012345' not in lines[-1]
    assert rec['loss'] == 0.123456789012345


# --------------------------------------------------------------------- #
# schema
# --------------------------------------------------------------------- #
def test_schema_rejects_malformed_records():
    with pytest.raises(SchemaError, match='unknown kind'):
        validate_record(dict(kind='nope'))
    with pytest.raises(SchemaError, match='missing required'):
        validate_record(dict(kind='step', run_id='x'))
    with pytest.raises(SchemaError, match='p50'):
        validate_record(dict(kind='flush', run_id='x', step=1,
                             window={}, runtime={},
                             timing={'step': dict(count=1)}))
    with pytest.raises(SchemaError, match='open with run_meta'):
        validate_stream([json.dumps(dict(kind='step', run_id='x',
                                         step=1, t=0.0))])


# --------------------------------------------------------------------- #
# report / obs_report
# --------------------------------------------------------------------- #
def test_summarize_telemetry_run_record_shape(tmp_path):
    path = str(tmp_path / 'tele.jsonl')
    with MetricLogger(path, mirror=None) as lg:
        lg.log_record(
            'flush', step=5,
            window={'loss': dict(count=5, mean=2.0, min=1.5, max=3.0)},
            timing={'step': dict(count=5, p50_ms=10.0, p95_ms=12.0,
                                 max_ms=13.0, mean_ms=10.5)},
            runtime={}, nodes_steps_per_sec=480.0)
        lg.log_record(
            'flush', step=10,
            window={'loss': dict(count=5, mean=1.0, min=0.5, max=1.6)},
            timing={'step': dict(count=5, p50_ms=9.0, p95_ms=11.0,
                                 max_ms=12.0, mean_ms=9.5)},
            runtime={}, nodes_steps_per_sec=505.0)
        lg.log_record(
            'summary', steps=10, label='denoise,test',
            metrics={'loss': dict(count=10, mean=1.5, min=0.5, max=3.0)},
            timing={'step': dict(count=10, p50_ms=9.5, p95_ms=12.0,
                                 max_ms=13.0, mean_ms=10.0)},
            retrace_warnings_total=0, nodes_steps_per_sec=500.0,
            loss_first=3.0, loss_last=0.5, loss_decreased=True)
    validate_stream(path)
    runs = summarize_telemetry(load_jsonl(path))
    assert len(runs) == 1
    r = runs[0]
    assert r['metric'].startswith('denoise_train_nodes_steps_per_sec')
    assert 'backend=' in r['metric'] and 'denoise,test' in r['metric']
    assert r['value'] == 500.0
    assert r['unit'].startswith('nodes*steps/sec/')
    assert r['vs_baseline'] == 1.0
    assert r['window_rates'] == [480.0, 505.0]
    assert r['steps_trained'] == 10
    assert r['step_ms'] == 10.0 and r['step_ms_p95'] == 12.0
    assert r['loss_decreased'] is True and r['retrace_warnings'] == 0
    # vs an anchor
    anchored = summarize_telemetry(load_jsonl(path), anchor=250.0)[0]
    assert anchored['vs_baseline'] == 2.0
    # summarize() unwraps the single run
    assert summarize(load_jsonl(path))['value'] == 500.0


# --------------------------------------------------------------------- #
# trainer end-to-end
# --------------------------------------------------------------------- #
def test_trainer_telemetry_end_to_end(tmp_path, monkeypatch):
    """Telemetry through the real DenoiseTrainer (smallest trainable
    config): schema-valid stream, per-phase p50/p95 in every flush, zero
    post-warmup retraces, and exactly one accumulator fetch per flush
    interval on the hot path."""
    from se3_transformer_tpu.training import DenoiseConfig, DenoiseTrainer

    fetches = []
    real = obs_metrics._host_fetch
    monkeypatch.setattr(obs_metrics, '_host_fetch',
                        lambda tree: (fetches.append(1), real(tree))[1])

    cfg = DenoiseConfig(num_nodes=12, dim=4, dim_head=4, heads=1, depth=1,
                        num_degrees=2, max_sparse_neighbors=2,
                        num_adj_degrees=1, adj_dim=2,
                        telemetry=True, flush_every=2)
    trainer = DenoiseTrainer(cfg)
    path = str(tmp_path / 'tele.jsonl')
    with MetricLogger(path, mirror=None) as lg:
        history = trainer.train(4, log=lambda *_: None, metric_logger=lg)
    assert len(fetches) == 2  # steps 2 and 4; close() sees no residual

    info = validate_stream(path)
    assert info['kinds']['flush'] == 2 and info['kinds']['summary'] == 1
    recs = [json.loads(l) for l in open(path)]
    flushes = [r for r in recs if r['kind'] == 'flush']
    for f in flushes:
        assert 'p50_ms' in f['timing']['step'] \
            or 'p50_ms' in f['timing']['warmup']
        assert f['runtime']['retraced'] == []
        assert f['window']['loss']['count'] == 2
    summary = [r for r in recs if r['kind'] == 'summary'][0]
    assert summary['retrace_warnings_total'] == 0
    assert summary['steps'] == 4
    assert 'p95_ms' in summary['timing']['step']
    assert np.isfinite(summary['loss_first'])
    assert history[-1]['kind'] == 'summary'
