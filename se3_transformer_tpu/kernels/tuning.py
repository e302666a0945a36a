"""Shape-keyed kernel block-config autotuner table.

The fused pairwise-conv and attention Pallas kernels carry most of the
flagship step (root PERF.md section 5), but their block sizes
historically came from a static VMEM-budget heuristic validated only at the flagship shape
— `_pick_blocks` itself warns that non-flagship shapes inherit the 7 MiB
forward budget unvalidated, and that standalone-sweep rankings were
measured OPPOSITE to end-to-end rankings (the d0cd10d regression:
294.97 -> 107.51 nodes*steps/s). This module gives every pick function a
measured-config table consulted BEFORE the heuristic:

    precedence:  forced candidate  >  cache  >  heuristic

(the so2 and flash pick functions alone still read an environment
variable of their own first, and log it as source 'env')

  * `force(kind, blocks)` is the tuner's in-process candidate mechanism
    (scripts/tune_kernels.py): a pending table entry under measurement,
    in the tuner's own process;
  * the cache is a versioned on-disk JSON table (same durability pattern
    as the Q_J `.npz` cache in basis.py: atomic rename, corrupt file =
    miss, version bump = invalidation) keyed on
    (kernel kind, shape tuple, dtype, device_kind, cache version), with
    per-entry provenance (code_rev, benched nodes*steps/s, timestamp);
  * with an empty cache every pick is bit-identical to the heuristic
    (regression-pinned in tests/test_kernel_tuning.py).

Entries enter the cache ONLY through `promote()`, and the supported
promoter (scripts/tune_kernels.py) measures candidates END-TO-END
through the real train step — never the standalone kernel — and
requires a win over the incumbent across alternating A/B pairs. Every
consult (cache hit, forced candidate, 'env', or heuristic fallback) is
recorded in an in-process log that the serving engine's AOT warmup
and the run report surface, so an adopted pick is always
distinguishable from a heuristic one in telemetry.

Unlike basis.CACHE_PATH (frozen at import), the cache directory env var
is read per call: tests and the tuner retarget `SE3_TPU_CACHE_PATH`
without re-importing the package.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

CACHE_VERSION = 1

# kernel kinds with tunable picks. 'plain'/'bxf' are the pairwise
# forward kernels (the backward ALWAYS runs its own bwd-model heuristic
# — table entries never reach it, see _pick_blocks; the
# basis-fused backward logs its picks under 'bxf_bwd', a kind of the
# consult log only, so a step's log counts the launches of that form);
# 'attention' is the fused attention forward block_n and
# 'attention_bwd' the fused attention BACKWARD block_n (its working set
# is ~2x the forward's, so it keys its own measured entries —
# previously the bwd ran the heuristic only and the tuner could never
# promote a measured bwd block); 'so2' is the banded SO(2)
# contraction's node-axis streaming chunk count
# (so2/contract.py::_pick_so2_chunks — blocks = (chunks,), 1 =
# unchunked); 'flash' is the streaming equivariant-attention kernel's
# (block_n, block_j) tile pair (kernels/pallas_flash.py),
# 'flash_stream' its XLA fallback's node-axis chunk count
# (blocks = (chunks,), 1 = unchunked), and 'flash_global' the same
# chunk-count pick for the graph-free global variant — its own kind
# because its per-chunk working set is O(rows * n) not O(rows * K),
# so a small-n kNN-calibrated entry must never steer an assembly-n
# global step (the Pallas block pick stays kind 'flash': global
# shapes key K=0 there).
KINDS = ('plain', 'bxf', 'attention', 'attention_bwd', 'so2', 'flash',
         'flash_stream', 'flash_global')

# Mosaic's scoped-vmem stack limit is ~16 MiB; 12 MiB leaves slack for
# compiler temporaries (same constant, same hard-won reason, as
# pallas_attention._VMEM_LIMIT). Used as the admission ceiling for
# candidates whose kind has no stricter production budget.
MOSAIC_SCOPED_VMEM = 12 * 2 ** 20

_lock = threading.Lock()
# kind -> (shape-or-None, dtype-or-None, blocks): None wildcards match
# every pick of the kind (test convenience); the tuner always pins the
# target shape+dtype so a candidate under measurement cannot leak into
# the OTHER same-kind picks of the traced program (whose admissible
# sets differ — and whose picks revert to the heuristic at deployment,
# which would invalidate the end-to-end promotion evidence)
_forced: Dict[str, Tuple[Optional[Tuple[int, ...]], Optional[str],
                         Tuple[int, ...]]] = {}
# consult log: (kind, shape, dtype, source, blocks) -> count. Bounded by
# construction (picks happen at trace time; distinct keys are few).
_consults: Dict[Tuple, int] = {}
# file memo: path -> ((mtime_ns, size), entries)
_loaded: Dict[str, Tuple[Tuple[int, int], dict]] = {}


# --------------------------------------------------------------------- #
# cache file
# --------------------------------------------------------------------- #

def cache_dir() -> str:
    """Read per call (NOT frozen at import like basis.CACHE_PATH) so the
    tuner and tests can retarget without re-importing."""
    from ..utils.compilation_cache import CHECKOUT_CACHE_DIR
    return os.environ.get('SE3_TPU_CACHE_PATH', CHECKOUT_CACHE_DIR)


def cache_file() -> str:
    # version in the NAME: a bump orphans the old file instead of
    # migrating it (same invalidation mechanism as basis._qj_cache_file)
    return os.path.join(cache_dir(), f'kernel_blocks_v{CACHE_VERSION}.json')


def _key(kind: str, shape: Sequence[int], dtype: str,
         device_kind: str) -> str:
    return f'{kind}|{",".join(str(int(s)) for s in shape)}' \
           f'|{dtype}|{device_kind}'


def current_device_kind() -> str:
    """Device identity for the cache key: a v5e's measured winner must
    not silently steer a v4 (or the CPU interpret tests). A backend
    that fails to initialize raises: an entry keyed 'unknown' would
    match nothing and every pick would quietly be the heuristic."""
    import jax
    if jax.default_backend() == 'cpu':
        return 'cpu'
    return jax.devices()[0].device_kind


def _load_entries(path: str) -> dict:
    """Parse the table; ANY failure (missing, truncated, corrupt JSON,
    wrong in-file version) is a plain cache miss, never an error."""
    try:
        st = os.stat(path)
    except OSError:
        return {}
    sig = (st.st_mtime_ns, st.st_size)
    with _lock:
        cached = _loaded.get(path)
        if cached is not None and cached[0] == sig:
            return cached[1]
    entries: dict = {}
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict) and data.get('version') == CACHE_VERSION \
                and isinstance(data.get('entries'), dict):
            entries = data['entries']
    except Exception:  # noqa: BLE001 - corrupt/truncated file: miss
        entries = {}
    with _lock:
        _loaded[path] = (sig, entries)
    return entries


def entries() -> dict:
    """The raw on-disk table ({key: {blocks, provenance}})."""
    return dict(_load_entries(cache_file()))


def lookup(kind: str, shape: Sequence[int], *, dtype: str = 'float32',
           device_kind: Optional[str] = None
           ) -> Optional[Tuple[Tuple[int, ...], str]]:
    """Measured blocks for (kind, shape, dtype, device) or None.

    Returns (blocks, source) with source 'forced' (a tune_kernels
    candidate under measurement) or 'cache'. The caller (the pick
    function) still validates tile legality and the VMEM model before
    adopting — a hand-edited or stale entry must degrade to the
    heuristic with a warning, not to an opaque Mosaic compile error.
    """
    with _lock:
        forced = _forced.get(kind)
    if forced is not None:
        fshape, fdtype, fblocks = forced
        if (fshape is None
                or fshape == tuple(int(s) for s in shape)) \
                and (fdtype is None or fdtype == dtype):
            return tuple(fblocks), 'forced'
    ents = _load_entries(cache_file())
    if not ents:
        return None
    if device_kind is None:
        device_kind = current_device_kind()
    ent = ents.get(_key(kind, shape, dtype, device_kind))
    if not isinstance(ent, dict):
        return None
    blocks = ent.get('blocks')
    if (not isinstance(blocks, (list, tuple)) or not blocks
            or not all(isinstance(b, int) for b in blocks)):
        return None  # malformed entry: miss
    return tuple(blocks), 'cache'


def promote(kind: str, shape: Sequence[int], blocks: Sequence[int], *,
            dtype: str = 'float32', device_kind: Optional[str] = None,
            provenance: Optional[dict] = None) -> dict:
    """Write a measured winner into the table (read-modify-write under a
    file lock, atomic rename — the basis.py Q_J pattern). Returns the
    stored entry. Callers other than scripts/tune_kernels.py should have
    an equally end-to-end justification for what they write."""
    assert kind in KINDS, f'unknown kernel kind {kind!r} (known: {KINDS})'
    if device_kind is None:
        device_kind = current_device_kind()
    prov = dict(provenance or {})
    prov.setdefault('time_utc',
                    time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime()))
    if 'code_rev' not in prov:
        try:
            from ..observability.metrics import _code_rev
            prov['code_rev'] = _code_rev()
        except Exception:  # noqa: BLE001 - provenance is best-effort
            prov['code_rev'] = None
    entry = dict(blocks=[int(b) for b in blocks], provenance=prov)
    path = cache_file()
    os.makedirs(cache_dir(), exist_ok=True)
    lock_path = os.path.join(cache_dir(), 'kernel_blocks.lock')
    with open(lock_path, 'w') as lock_fh:
        try:
            import fcntl
            fcntl.flock(lock_fh, fcntl.LOCK_EX)
        except (ImportError, OSError):
            pass  # best-effort mutex, like the Q_J cache
        existing = _read_raw_entries(path)
        existing[_key(kind, shape, dtype, device_kind)] = entry
        tmp = f'{path}.{os.getpid()}.tmp'
        with open(tmp, 'w') as f:
            json.dump(dict(version=CACHE_VERSION, entries=existing), f,
                      indent=1, sort_keys=True)
        os.replace(tmp, path)
    with _lock:
        _loaded.pop(path, None)  # next lookup re-reads
    return entry


def _read_raw_entries(path: str) -> dict:
    """Re-read inside the write lock (the memo could be stale against a
    concurrent writer). Corrupt file: rebuild from scratch."""
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict) and data.get('version') == CACHE_VERSION \
                and isinstance(data.get('entries'), dict):
            return dict(data['entries'])
    except Exception:  # noqa: BLE001
        pass
    return {}


@contextlib.contextmanager
def force(kind: str, blocks: Sequence[int], *,
          shape: Optional[Sequence[int]] = None,
          dtype: Optional[str] = None):
    """Pin a candidate for one kind — the tuner's in-process measurement
    path (precedence: above the cache). Pass the target `shape` (and
    `dtype`) so ONLY that pick takes the candidate:
    a same-kind pick at another shape was never admitted for these
    blocks and must keep resolving cache/heuristic, or the measured A/B
    would not be the program that deploys. shape=None applies to every
    pick of the kind. Clears the kernel jit caches on entry AND exit:
    the pick runs at trace time, so a stale traced kernel would silently
    measure the wrong program (the lesson the old subprocess sweep
    learned the hard way)."""
    assert kind in KINDS, f'unknown kernel kind {kind!r}'
    with _lock:
        prior = _forced.get(kind)
        _forced[kind] = (
            None if shape is None else tuple(int(s) for s in shape),
            dtype, tuple(int(b) for b in blocks))
    clear_kernel_caches()
    try:
        yield
    finally:
        with _lock:
            if prior is None:
                _forced.pop(kind, None)
            else:
                _forced[kind] = prior
        clear_kernel_caches()


def clear_kernel_caches() -> int:
    """Drop every kernel jit/trace cache whose pick this table steers.
    Returns the number of caches cleared; raises if NOTHING was cleared
    (a silent no-op would let an A/B measure the same program twice)."""
    cleared = 0
    from . import pallas_attention as pa, pallas_flash as pf, \
        pallas_pairwise as pp
    for mod, names in (
            (pp, ('fused_pairwise_conv', 'fused_pairwise_conv_bxf',
                  'fused_pairwise_conv_bwd',
                  'fused_pairwise_conv_bwd_bxf')),
            (pa, ('_fused_attention_fwd_impl',
                  '_fused_attention_bwd_impl')),
            (pf, ('_flash_fwd_impl',))):
        for nm in names:
            f = getattr(mod, nm, None)
            if f is not None and hasattr(f, 'clear_cache'):
                f.clear_cache()
                cleared += 1
    for mod, names in (
            (pp, ('_fwd_partitioned', '_bxf_partitioned',
                  '_bwd_partitioned', '_bwd_bxf_partitioned')),
            (pa, ('_att_partitioned',))):
        for nm in names:
            f = getattr(mod, nm, None)
            if f is not None and hasattr(f, 'cache_clear'):
                f.cache_clear()
                cleared += 1
    if cleared == 0:
        raise RuntimeError(
            'clear_kernel_caches cleared nothing — kernel jit wrapper '
            'cache API changed; block A/Bs would be invalid')
    return cleared


# --------------------------------------------------------------------- #
# consult telemetry
# --------------------------------------------------------------------- #

def record_consult(kind: str, shape: Sequence[int], dtype: str,
                   source: str, blocks: Sequence[int]) -> None:
    """Called by the pick functions on every resolution. source is one
    of 'forced' / 'cache' / 'heuristic', or 'env' from the so2 and flash
    picks' own overrides."""
    key = (kind, tuple(int(s) for s in shape), dtype, source,
           tuple(int(b) for b in blocks))
    with _lock:
        _consults[key] = _consults.get(key, 0) + 1


def reset_consults() -> None:
    with _lock:
        _consults.clear()


def consults() -> List[dict]:
    """Every distinct pick resolution since the last reset, as dicts
    ({kernel, shape, dtype, source, blocks, count}) — the payload
    the serving warmup attaches to its records."""
    with _lock:
        items = sorted(_consults.items())
    return [dict(kernel=k, shape=list(s), dtype=d, source=src,
                 blocks=list(b), count=n)
            for (k, s, d, src, b), n in items]


def snapshot() -> Dict[Tuple, int]:
    """Opaque marker for consults_since — lets concurrent consumers
    (serving warmup, the tuner) report their own deltas without
    resetting the shared log out from under each other."""
    with _lock:
        return dict(_consults)


def consults_since(snap: Dict[Tuple, int]) -> List[dict]:
    """The consults recorded after `snap = snapshot()`."""
    with _lock:
        items = sorted(_consults.items())
    out = []
    for key, n in items:
        d = n - snap.get(key, 0)
        if d > 0:
            k, s, dt, src, b = key
            out.append(dict(kernel=k, shape=list(s), dtype=dt, source=src,
                            blocks=list(b), count=d))
    return out


def consult_summary(consult_list: Optional[List[dict]] = None) -> dict:
    """Compact adopted-vs-heuristic view for records: total counts per
    source plus the non-heuristic resolutions spelled out."""
    cs = consults() if consult_list is None else consult_list
    by_source: Dict[str, int] = {}
    for c in cs:
        by_source[c['source']] = by_source.get(c['source'], 0) + c['count']
    adopted = [c for c in cs if c['source'] != 'heuristic']
    return dict(by_source=by_source, adopted=adopted,
                cache_entries=len(entries()))


# --------------------------------------------------------------------- #
# candidate admission (the tuner's enumeration)
# --------------------------------------------------------------------- #

def admissible_candidates(kind: str, shape: Sequence[int]
                          ) -> List[Tuple[int, ...]]:
    """Tile-legal, VMEM-model-admissible candidate blocks for a shape —
    what scripts/tune_kernels.py is allowed to measure. Admission is
    model-based and conservative ON PURPOSE: over-budget settings fail
    in Mosaic's VMEM allocation at compile time (bxf (512, 16) did), so
    those configs are excluded here up front.

    Per kind:
      * 'plain': forward working set within the production 7 MiB budget
        (the same model `_pick_blocks` enforces). bwd-awareness is
        structural: the backward NEVER runs candidate blocks — it keeps
        its own 6 MiB bwd-model heuristic pick — so a forward candidate
        cannot regress the backward's VMEM fit.
      * 'bxf': forward model within MOSAIC_SCOPED_VMEM (12 MiB) —
        the model already sits above the 6 MiB paper budget at the
        production-validated flagship default (~7.5 MiB), so the real
        ceiling with slack is the admission line. Same backward note.
      * 'attention': block_n ladder admitted against the BACKWARD row
        model (`_block_row_bytes(J, D, bwd=True)`): training
        differentiates attention with the same block size family, so a
        forward-only fit would still OOM end-to-end.
      * 'attention_bwd': the backward's own block_n ladder, admitted
        against the same bwd row model (the bwd IS the bwd program —
        forward entries never steer it and vice versa).
      * 'flash': (block_n, block_j) for the streaming
        equivariant-attention kernel, admitted against its bwd-aware
        VMEM row model (pallas_flash._flash_vmem_bytes).
      * 'flash_stream': node-axis chunk count for the kernel's XLA
        streaming fallback (1 = unchunked), the so2-kind pattern.
    """
    out: List[Tuple[int, ...]] = []
    if kind == 'plain':
        from .pallas_pairwise import _round_up, _vmem_plain
        E, IF, O, P, mid = (int(s) for s in shape)
        budget = 7 * 2 ** 20
        for be in (128, 256, 512):
            if be > _round_up(E, 128):
                continue
            for bif in _second_axis_candidates(IF):
                # same in-kernel unroll (Mosaic compile time) bound as
                # _pick_blocks' max_unroll: the in-process tuner has no
                # per-candidate timeout, so admitting a pathological
                # unroll would spend the whole chip call compiling
                if P * bif > 256:
                    continue
                if _vmem_plain(be, min(bif, IF), IF, O, P, mid) <= budget:
                    out.append((be, bif))
    elif kind == 'bxf':
        from .pallas_pairwise import _round_up, _vmem_bx
        E, C, O, P, Q, F, mid = (int(s) for s in shape)
        for be in (128, 256, 512):
            if be > _round_up(E, 128):
                continue
            for cb in _second_axis_candidates(_round_up(C, 8)):
                if P * F * cb > 512:  # _pick_blocks_bx's max_unroll —
                    # see the plain-kind note above
                    continue
                if _vmem_bx(be, cb, O, P, Q, F, mid) \
                        <= MOSAIC_SCOPED_VMEM:
                    out.append((be, cb))
    elif kind in ('attention', 'attention_bwd'):
        from .pallas_attention import (
            _VMEM_LIMIT, _block_row_bytes, _round_up,
        )
        n, J, D = (int(s) for s in shape)
        # both kinds admit against the BACKWARD row model: a forward
        # entry still has to coexist with the bwd program end-to-end,
        # and the bwd kind's working set IS the bwd model
        row_bwd = _block_row_bytes(J, D, bwd=True)
        cap = max(8, _round_up(n, 8))
        for bn in (512, 256, 128, 64, 32, 16, 8):
            if bn <= cap and bn * row_bwd <= _VMEM_LIMIT:
                out.append((bn,))
    elif kind == 'flash':
        from .pallas_flash import flash_admissible_blocks
        out = flash_admissible_blocks(shape)
    elif kind == 'flash_stream':
        # node-axis chunk count for the XLA streaming fallback
        # (pallas_flash._flash_stream). The ladder must cover the
        # heuristic's own operating region (n // 16 — e.g. 64 chunks at
        # flagship n=1024), or the tuner could never measure it and
        # validate_entry would reject larger measured entries as corrupt
        n = int(shape[0])
        out = [(c,) for c in (1, 2, 4, 8, 16, 32, 64, 128) if c <= n]
    elif kind == 'flash_global':
        # the global variant's chunk count: same mechanism, ladder
        # extended through the assembly regime (n // 16 is 2048 chunks
        # at n=32768 — the heuristic's operating point must stay
        # admissible or validate_entry rejects measured entries there)
        n = int(shape[0])
        out = [(c,) for c in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512,
                              1024, 2048) if c <= n]
    elif kind == 'so2':
        # node-axis streaming chunk count for the banded SO(2)
        # contraction (so2/contract.py): 1 = unchunked (the heuristic
        # default — its working set is small), higher counts trade
        # overhead for a lax.map memory ceiling. Always legal when the
        # count does not exceed the node axis.
        n = int(shape[0])
        out = [(c,) for c in (1, 2, 4, 8) if c <= n]
    else:
        raise ValueError(f'unknown kernel kind {kind!r} (known: {KINDS})')
    return out


def _second_axis_candidates(full: int) -> List[int]:
    """Sublane-quantum-legal sizes for the if/c chunk axis: multiples of
    8 below the full axis, plus the full axis itself."""
    sizes = [s for s in (8, 16, 32, 64, 128) if s < full and s % 8 == 0]
    sizes.append(full)
    return sizes


def validate_entry(kind: str, shape: Sequence[int],
                   blocks: Sequence[int]) -> bool:
    """Tile-quantum + VMEM-model gate applied by the pick functions to a
    table hit before adopting it: a cache entry exists to be trusted
    silently, so anything the admission model rejects is treated as
    corrupt — warn and fall back to the heuristic."""
    ok = tuple(int(b) for b in blocks) in \
        set(admissible_candidates(kind, shape))
    if not ok:
        warnings.warn(
            f'kernel tuning table entry {kind}{tuple(shape)} -> '
            f'{tuple(blocks)} is not tile-legal/VMEM-admissible; '
            f'ignoring it (heuristic pick used). Re-run '
            f'scripts/tune_kernels.py or delete {cache_file()}',
            stacklevel=3)
    return ok
