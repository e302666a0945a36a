"""Step/phase wall-clock reservoirs + device-side trace attribution.

Two complementary views of where time goes:

  * `PhaseTimer` — HOST wall clock, percentile reservoirs per phase
    ('data', 'step', 'checkpoint', ...). In a steady async-dispatch
    pipeline the host loop converges onto device step time via queue
    backpressure, so windowed p50/p95/max of the 'step' phase tracks
    real step time without forcing a per-step sync.
  * `named_scope` / `profile_trace` — DEVICE attribution: scopes label
    the HLO, and `MODEL_SCOPES` is the closed list of leaves the trace
    reducer (observability.profiling) files device time under.
"""
from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from typing import Dict, Optional

import jax

# The closed list of leaves. Every `named_scope` the package writes is one of
# these (tests/test_scopes.py scans the sources), and the trace reducer
# (observability.profiling) files each device operation under the INNERMOST
# component of its `op_name` path that is on the list. Flax writes module
# names into the same path (`.../attn_block2/attention/attn/to_k/...`), so a
# scope is written by hand only where no module boundary exists. Forward,
# backward and replay are read from the path (`transpose(jvp(...))`,
# `rematted_computation`), never labelled.
MODEL_SCOPES = (
    'neighbors',          # models/se3_transformer.py: pairwise geometry +
    #                       kNN selection
    'adjacency',          # models/se3_transformer.py: adjacency expansion
    #                       + jittered bonded top-k
    'basis',              # models/se3_transformer.py: SH basis
    'conv_in',            # models/se3_transformer.py
    'trunk',              # models/se3_transformer.py
    'conv_out',           # models/se3_transformer.py
    'readout',            # models/se3_transformer.py: linear_out, degree-1
    #                       permutation, pooling
    'frames',             # v2/model.py: edge frames
    'gather',             # ops/conv.py ConvSE3: neighbour gather
    'radial',             # ops/conv.py: the radial trunk that makes h
    'pair',               # ops/conv.py: written `pair_<d_in>_<d_out>`
    #                       (`pair_all_<d_out>` for a grouped launch)
    #                       around each pairwise contraction; the kernels'
    #                       launches carry the degree pair here, not in
    #                       their names
    'basis_contract',     # ops/conv.py: V2 = basis . x and its cotangents
    #                       where XLA computes them: every path but the
    #                       flat-basis fused pair, which keeps only the
    #                       reduction to dbasis here (and that only under
    #                       differentiable_coors)
    'pairwise_layout',    # kernels/pallas_pairwise.py: the pads, transposes
    #                       and reshapes on either side of each launch
    'norm',               # ops/core.py NormSE3
    'ff',                 # ops/core.py FeedForwardBlockSE3
    'attention',          # ops/attention.py: whole attention call
    'attn_qkv',           # ops/attention.py: q/k/v projections + convs
    'attn_core',          # ops/attention.py: sim/softmax/weighted sum
    'pallas_attention',   # kernels/pallas_attention.py: fused kernel
    'pallas_attention_bwd',  # ... and its backward
    'flash_attention',    # kernels/pallas_flash.py: streaming kernel
    'flash_global_attention',          # ... kNN-free mode
    'flash_global_attention_sharded',  # ... under sequence parallelism
    'global_attention_materialized',   # ... its XLA oracle
    'ring_knn',           # parallel/ring.py: sequence-parallel kNN
    'ici_wait',           # parallel/ring.py ring_scan: the ppermute hop;
    #                       in an overlapped trace its exclusive time is
    #                       the NON-hidden remainder of the transfer
    'exchange',           # parallel/exchange.py: neighbor-sparse value
    #                       rotation + select (and the zero-comm rowwise
    #                       column select)
    # the token decoder (models/token_decoder.py); its block norms and final
    # norms are filed under `norm`
    'embed',              # models/token_decoder.py: token embedding rows
    'latent_qkv',         # ops/latent_attention.py: down- and
    #                       up-projections, their norms, the rotation;
    #                       the scale and the rounding of q, k and v
    'latent_core',        # ops/latent_attention.py: scores, softmax,
    #                       weighted sum (on a TPU the launches
    #                       `latent_core_fwd` / `latent_core_bwd` of
    #                       kernels/pallas_block_attention.py)
    'latent_out',         # ops/latent_attention.py: output projection
    'moe_router',         # ops/expert_layer.py: logits, sigmoid, top-k,
    #                       weights
    'moe_dispatch',       # ops/expert_layer.py: sort / gather into expert
    #                       order
    'moe_experts',        # ops/expert_layer.py: the grouped products and
    #                       the activation
    'moe_combine',        # ops/expert_layer.py: back to token order, the
    #                       weighted sum over a token's slots
    'shared_expert',      # ops/expert_layer.py
    'dense_ff',           # models/token_decoder.py: a dense block's SwiGLU
    'mtp_merge',          # models/token_decoder.py: the prediction block's
    #                       two norms, concatenation and projection
    'lm_head',            # training/lm_loss.py: both heads' logits and
    #                       cross-entropies, chunk by chunk
    # the hybrid decoder (models/hybrid_decoder.py) shares `embed`, `norm`,
    # the expert layer's leaves and `lm_head`
    'ssm_in',             # ops/state_space.py: the input projection and its
    #                       split into z, xBC, dt
    'ssm_conv',           # ops/state_space.py: the causal depthwise
    #                       convolution and its activation
    'ssm_scan',           # ops/state_space.py: from dt, A, x, B, C to y
    #                       (the chunked scan, D included: on a TPU the
    #                       launches `ssm_scan_fwd` and `ssm_scan_bwd` of
    #                       kernels/pallas_scan.py, elsewhere einsums)
    'ssm_gate',           # ops/state_space.py: the gated group norm
    'ssm_out',            # ops/state_space.py: output projection
    'mha_qkv',            # ops/grouped_attention.py: q, k, v projections,
    #                       q/k norms and rotation where the model has
    #                       them; before the repo's own core launches
    #                       (any leaf below) norm, rotation, scale and
    #                       rounding are the launches `qk_pass_fwd` and
    #                       `qk_pass_bwd` of kernels/pallas_qk_pass.py,
    #                       before the library's kernel XLA's passes and
    #                       the key-value heads repeated
    'mha_core',           # ops/grouped_attention.py: scores, softmax,
    #                       weighted sum of a global layer (every key at or
    #                       before the query): on a TPU at heads of whole
    #                       lane rows the two launches of
    #                       kernels/pallas_block_attention.py over the
    #                       causal triangle's table, `mha_core_fwd` and
    #                       `mha_core_bwd`; at heads of 64 the library's
    #                       streaming kernel's three; blocks of queries
    #                       off the TPU
    'mha_out',            # ops/grouped_attention.py: output projection
    # a pattern's `F` layers (models/hybrid_decoder.py) are filed under
    # `dense_ff`
    'sconv_in',           # ops/short_conv.py: the input projection and its
    #                       split into B, C, X
    'sconv_core',         # ops/short_conv.py: B * X, the taps, C * z
    'sconv_out',          # ops/short_conv.py: output projection
    # a decoder trained by diffusion over blocks (two streams in one pass)
    'bd_core',            # ops/grouped_attention.py: the block-diffusion
    #                       core (ops/block_diffusion.py: on a TPU the two
    #                       launches of kernels/pallas_block_attention.py,
    #                       `bd_core_fwd` and `bd_core_bwd`, di = sum(o do)
    #                       inside the second), apart from `mha_core`
    'swa_core',           # ops/grouped_attention.py: the core of a layer
    #                       with a sliding window (ops/sliding_window.py:
    #                       on a TPU the same two launches under the
    #                       window's table, `swa_core_fwd` and
    #                       `swa_core_bwd`), apart from `mha_core`, which a
    #                       decoder's global layers keep whoever runs them
    'bd_streams',         # models/hybrid_decoder.py, training/lm_loss.py:
    #                       building the two streams and their positions,
    #                       cutting the noised one out, the weights
    # a looped stack (models/hybrid_decoder.py `total_ut_steps`): its passes
    # are no leaves but path components `ut_<t>` (`PASS_SCOPE`), read into
    # the reducer's `pass_s` beside whatever leaf the operation is under
    'exit_gate',          # models/hybrid_decoder.py: the gate's logits on
    #                       each pass's normed state
    'exit_mix',           # training/lm_loss.py: the gate's log-sigmoids,
    #                       the passes' probabilities, their entropy and
    #                       the sums over tokens
    'loss',               # parallel/sharding.py train_step: what the
    #                       model's scopes do not claim inside the
    #                       differentiated loss
    'optimizer',          # parallel/sharding.py train_step: the update
)

# `pair_<d_in>_<d_out>` / `pair_all_<d_out>` -> the leaf `pair`
PAIR_SCOPE = re.compile(r'^pair_(\d+|all)_(\d+)$')
# `ut_<t>`: pass t of a looped stack. A component of the path, as a flax
# module's name is, and no leaf
PASS_SCOPE = re.compile(r'^ut_(\d+)$')


def named_scope(name: str):
    """Label a region for profilers; no-op cost under jit."""
    return jax.named_scope(name)


@contextlib.contextmanager
def profile_trace(log_dir: str, enabled: bool = True):
    """Capture a jax.profiler trace (tensorboard/perfetto-compatible)."""
    if not enabled:
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _percentiles(samples) -> dict:
    import numpy as np
    a = np.asarray(samples, dtype=float) * 1e3  # -> ms
    return dict(count=int(a.size),
                p50_ms=round(float(np.percentile(a, 50)), 3),
                p95_ms=round(float(np.percentile(a, 95)), 3),
                # serving SLOs quote p99; training flush records simply
                # carry it along (schema requires it only for `serve`)
                p99_ms=round(float(np.percentile(a, 99)), 3),
                max_ms=round(float(a.max()), 3),
                mean_ms=round(float(a.mean()), 3))


class PhaseTimer:
    """Host wall-clock reservoirs per phase with windowed percentiles.

        timer = PhaseTimer()
        with timer.phase('step'):
            ...dispatch the train step...
        stats = timer.window_summary()   # {phase: {p50_ms, p95_ms, ...}}

    `window_summary` reports and resets the current window (call it at
    the flush interval); `cumulative_summary` covers the whole run (its
    reservoir is capped at `capacity` samples — count/sum/max stay
    exact beyond that, percentiles come from the first `capacity`).
    """

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._window: Dict[str, list] = {}
        self._all: Dict[str, list] = {}
        self._totals: Dict[str, dict] = {}
        # recorders and the flush reader may live on different threads
        # (serving's async-dispatch replicas all record into ONE shared
        # timer while the main loop flushes): the count/total
        # read-modify-writes and the window swap must not race
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def phase(self, name: str):
        # also a TraceAnnotation of the phase's name: while a profiler
        # trace is being taken the span lies on the profiler's clock
        # (`/host:CPU`) beside the device operations; otherwise it costs
        # a fraction of a microsecond
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.record(name, time.perf_counter() - t0)

    def record(self, name: str, seconds: float):
        with self._lock:
            self._window.setdefault(name, []).append(seconds)
            full = self._all.setdefault(name, [])
            if len(full) < self.capacity:
                full.append(seconds)
            tot = self._totals.setdefault(
                name, dict(count=0, total_s=0.0, max_s=0.0))
            tot['count'] += 1
            tot['total_s'] += seconds
            tot['max_s'] = max(tot['max_s'], seconds)

    def window_summary(self, reset: bool = True) -> dict:
        with self._lock:
            window = self._window
            if reset:
                self._window = {}
            else:
                window = {k: list(v) for k, v in window.items()}
        return {name: _percentiles(samples)
                for name, samples in window.items() if samples}

    def cumulative_summary(self) -> dict:
        with self._lock:
            snap = {name: (list(samples), dict(self._totals[name]))
                    for name, samples in self._all.items() if samples}
        out = {}
        for name, (samples, tot) in snap.items():
            stats = _percentiles(samples)
            stats.update(count=tot['count'],
                         total_s=round(tot['total_s'], 4),
                         max_ms=round(tot['max_s'] * 1e3, 3))
            out[name] = stats
        return out

    def total_seconds(self, name: str) -> float:
        tot = self._totals.get(name)
        return tot['total_s'] if tot else 0.0

    def total_count(self, name: str) -> int:
        tot = self._totals.get(name)
        return tot['count'] if tot else 0
