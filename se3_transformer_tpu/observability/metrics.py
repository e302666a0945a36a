"""On-device metric accumulation + the schema'd JSONL metric stream.

`MetricAccumulator` is the hot-path half: a pytree of running
(sum, count, min, max) per metric that rides INSIDE the jitted train
step, so per-step instrumentation costs a handful of scalar VPU ops and
zero host syncs. The host half (`MetricLogger`) fetches the whole tree
once per flush interval (`flush()` — one device-to-host transfer) and
writes one structured JSONL record.
"""
from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp

from .schema import SCHEMA_VERSION

_STAT_FIELDS = ('sum', 'count', 'min', 'max')


def _host_fetch(tree):
    """The ONE device-to-host transfer per flush. Module-level so tests
    can count invocations (the no-sync-on-hot-steps contract)."""
    return jax.device_get(tree)


@jax.tree_util.register_pytree_node_class
class MetricAccumulator:
    """Running sum/count/min/max per metric as an on-device pytree.

    Usage inside a jitted step (structure is static — declare the metric
    names up front with `zero`):

        acc = MetricAccumulator.zero(('loss', 'grad_norm'))
        # ... inside jit:
        acc = acc.update(loss=loss, grad_norm=gnorm)
        # ... on the host, once per flush interval:
        window, acc = acc.flush()   # ONE device->host sync

    `update` accepts scalars or arrays (an array counts element-wise, so
    per-micro-step loss vectors fold in with honest min/max).
    """

    __slots__ = ('stats',)

    def __init__(self, stats: Dict[str, Dict[str, jnp.ndarray]]):
        self.stats = stats

    # -- pytree protocol ------------------------------------------------ #
    def tree_flatten(self):
        names = tuple(sorted(self.stats))
        children = tuple(self.stats[n][f] for n in names
                         for f in _STAT_FIELDS)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        it = iter(children)
        stats = {}
        for n in names:
            stats[n] = {f: next(it) for f in _STAT_FIELDS}
        return cls(stats)

    # -- construction / traced updates ---------------------------------- #
    @classmethod
    def zero(cls, names: Iterable[str]) -> 'MetricAccumulator':
        f32 = jnp.float32
        return cls({str(n): dict(sum=jnp.zeros((), f32),
                                 count=jnp.zeros((), f32),
                                 min=jnp.full((), jnp.inf, f32),
                                 max=jnp.full((), -jnp.inf, f32))
                    for n in names})

    @property
    def names(self):
        return tuple(sorted(self.stats))

    def update(self, **metrics) -> 'MetricAccumulator':
        unknown = set(metrics) - set(self.stats)
        if unknown:
            raise KeyError(
                f'metrics {sorted(unknown)} were not declared at zero() '
                f'time (jit needs a static metric set); declared: '
                f'{sorted(self.stats)}')
        new = {}
        for name, st in self.stats.items():
            if name in metrics:
                v = jnp.asarray(metrics[name], jnp.float32)
                new[name] = dict(sum=st['sum'] + v.sum(),
                                 count=st['count'] + float(v.size),
                                 min=jnp.minimum(st['min'], v.min()),
                                 max=jnp.maximum(st['max'], v.max()))
            else:
                new[name] = dict(st)
        return MetricAccumulator(new)

    def merge(self, other: 'MetricAccumulator') -> 'MetricAccumulator':
        assert set(self.stats) == set(other.stats), 'metric sets differ'
        return MetricAccumulator({
            n: dict(sum=a['sum'] + b['sum'], count=a['count'] + b['count'],
                    min=jnp.minimum(a['min'], b['min']),
                    max=jnp.maximum(a['max'], b['max']))
            for n, (a, b) in
            ((n, (self.stats[n], other.stats[n])) for n in self.stats)})

    # -- host side ------------------------------------------------------- #
    def flush(self):
        """Fetch the window to host (one transfer) and reset.

        Returns (window, fresh) where window maps each metric to
        {count, mean, min, max} (None stats when the window saw no
        updates) and fresh is a zeroed accumulator with the same names.
        """
        host = _host_fetch(self.stats)
        window = {}
        for name, st in host.items():
            c = float(st['count'])
            window[name] = dict(
                count=int(c),
                mean=(float(st['sum']) / c) if c else None,
                min=float(st['min']) if c else None,
                max=float(st['max']) if c else None)
        return window, MetricAccumulator.zero(self.stats)


def merge_windows(cum: Optional[dict], window: dict) -> dict:
    """Host-side running merge of flushed windows (for the run summary)."""
    if cum is None:
        return {k: dict(v) for k, v in window.items()}
    out = dict(cum)
    for name, w in window.items():
        if not w['count']:
            continue
        c = out.get(name)
        if not c or not c['count']:
            out[name] = dict(w)
            continue
        n = c['count'] + w['count']
        out[name] = dict(
            count=n,
            mean=(c['mean'] * c['count'] + w['mean'] * w['count']) / n,
            min=min(c['min'], w['min']),
            max=max(c['max'], w['max']))
    return out


def _code_rev() -> Optional[str]:
    """Package-tree fingerprint: the env pin a session sets wins (it is
    the code actually in memory); else a best-effort git lookup."""
    rev = os.environ.get('SE3_TPU_CODE_REV')
    if rev:
        return rev
    try:
        import subprocess
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        out = subprocess.run(
            ['git', 'rev-parse', 'HEAD:se3_transformer_tpu'],
            cwd=root, capture_output=True, text=True, timeout=5)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:  # noqa: BLE001 - metadata is best-effort
        return None


def collect_run_meta(extra: Optional[dict] = None) -> dict:
    """Host/backend/build metadata stamped at the head of every stream.

    Queried lazily (first log), after the caller has already touched the
    backend, so metadata collection is never the call that initializes
    it. A backend that cannot be named raises: a stream whose header
    says `backend: null` hides which device its numbers came from.
    """
    import platform
    import sys
    backend = jax.default_backend()
    devs = jax.devices()
    device_count = len(devs)
    device_kind = devs[0].device_kind if backend != 'cpu' else None
    meta = dict(
        kind='run_meta',
        schema_version=SCHEMA_VERSION,
        time_utc=time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime()),
        code_rev=_code_rev(),
        backend=backend,
        device_kind=device_kind,
        device_count=device_count,
        host=dict(hostname=platform.node(), pid=os.getpid(),
                  python=sys.version.split()[0], jax=jax.__version__),
    )
    if extra:
        meta.update(extra)
    return meta


def _round_floats(obj, ndigits=4):
    if isinstance(obj, float):
        return round(obj, ndigits)
    if isinstance(obj, dict):
        return {k: _round_floats(v, ndigits) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_round_floats(v, ndigits) for v in obj]
    return obj


class MetricLogger:
    """Structured JSONL metric stream + stdout mirror.

    Every record carries `kind` and `run_id`; the first record of a
    stream is a `run_meta` header (backend, code_rev, host metadata),
    emitted lazily at the first log so backend discovery never runs
    before the caller has initialized it. Context-manager support closes
    the file handle on ANY exit path (the old logger leaked it on
    exceptions).
    """

    def __init__(self, path: Optional[str] = None, mirror=print,
                 run_meta: Optional[dict] = None):
        self.path = path
        self.mirror = mirror
        self.run_id = uuid.uuid4().hex[:12]
        self._extra_meta = dict(run_meta) if run_meta else {}
        self._meta_written = False
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
        self._fh = open(path, 'a') if path else None
        self._t0 = time.time()
        # reentrant: _ensure_meta writes the header while already inside
        # the locked region. Multiple serve-loop threads may share one
        # logger (the in-process fleet smokes do) — without the lock the
        # lazy run_meta header loses the race and a serve record lands
        # first, which validate_stream rejects.
        self._lock = threading.RLock()

    # -- plumbing -------------------------------------------------------- #
    def _write(self, rec: dict):
        with self._lock:
            if self._fh:
                self._fh.write(json.dumps(rec) + '\n')
                self._fh.flush()

    def _ensure_meta(self):
        with self._lock:
            if self._meta_written:
                return
            meta = collect_run_meta(self._extra_meta)
            meta['run_id'] = self.run_id
            self._write(meta)
            self._meta_written = True
        if self.mirror:
            self.mirror(f'run {self.run_id} backend={meta.get("backend")} '
                        f'code_rev={meta.get("code_rev")}')

    @staticmethod
    def _fmt(v):
        # fixed precision in the stdout mirror: the full repr of
        # bf16-noise floats made the logs unreadable (the JSONL keeps
        # full precision)
        if isinstance(v, float):
            return f'{v:.4g}'
        if isinstance(v, dict):
            return json.dumps(_round_floats(v), separators=(',', ':'))
        return str(v)

    # -- logging API ----------------------------------------------------- #
    def log(self, step: int, **metrics) -> dict:
        """One per-step record (kind='step'). Returns the record."""
        self._ensure_meta()
        rec = dict(kind='step', run_id=self.run_id, step=step,
                   t=round(time.time() - self._t0, 3))
        rec.update({k: (float(v) if hasattr(v, 'item') else v)
                    for k, v in metrics.items()})
        self._write(rec)
        if self.mirror:
            shown = {k: v for k, v in rec.items()
                     if k not in ('kind', 'run_id')}
            self.mirror(' '.join(f'{k}={self._fmt(v)}'
                                 for k, v in shown.items()))
        return rec

    def log_record(self, kind: str, mirror: bool = True, **fields) -> dict:
        """One structured record of an arbitrary kind (flush /
        retrace_warning / summary / ...). Returns the record."""
        self._ensure_meta()
        rec = dict(kind=kind, run_id=self.run_id,
                   t=round(time.time() - self._t0, 3))
        rec.update(fields)
        self._write(rec)
        if self.mirror and mirror:
            shown = {k: v for k, v in rec.items() if k != 'run_id'}
            self.mirror(' '.join(f'{k}={self._fmt(v)}'
                                 for k, v in shown.items()))
        return rec

    # -- lifecycle ------------------------------------------------------- #
    def close(self):
        if self._fh:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False
