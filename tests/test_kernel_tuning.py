"""Coverage for the shape-keyed kernel autotuner table
(se3_transformer_tpu/kernels/tuning.py) and its consult points in the
pick functions (_pick_blocks / _pick_blocks_bx / _pick_block_n).

Load-bearing contracts (ISSUE 4 acceptance):
  * with no cache file, every pick is BIT-IDENTICAL to
    the heuristic (the production-validated flagship picks are pinned);
  * a promoted entry round-trips persistence and demonstrably changes
    the pick, and the consult is logged for telemetry;
  * corrupt/truncated cache files and version bumps are plain misses;
  * entries that fail the tile-quantum/VMEM admission model are
    rejected with a warning, never handed to Mosaic;
  * candidate enumeration is bwd-aware and excludes the configs the
    round-4 standalone sweep measured as Mosaic VMEM compile failures
    (bxf (256,16)/(512,16)).

Everything runs on CPU; the end-to-end check uses interpreter-mode
kernels at tiny shapes.
"""
import json
import os

import numpy as np
import pytest

from se3_transformer_tpu.kernels import tuning
from se3_transformer_tpu.kernels.pallas_attention import _pick_block_n
from se3_transformer_tpu.kernels.pallas_pairwise import (
    _pick_blocks, _pick_blocks_bx,
)

# the flagship shape tuples (BASELINE.md)
PLAIN_FLAGSHIP = (32768, 1024, 64, 7, 128)
PLAIN_CHUNKED = (4096, 1024, 64, 7, 128)
BX_FLAGSHIP = (32768, 64, 64, 7, 7, 7, 128)
ATT_FLAGSHIP = (1024, 33, 56)


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    """Every test gets an empty cache dir and a clean consult log
    (tuning reads SE3_TPU_CACHE_PATH per call, unlike basis.py)."""
    monkeypatch.setenv('SE3_TPU_CACHE_PATH', str(tmp_path))
    tuning.reset_consults()
    yield tmp_path


def test_empty_cache_picks_bit_identical_to_heuristic():
    # the production-validated heuristic picks, pinned (test_pallas
    # pins them too; here the point is: WITH tuning integrated and an
    # empty table, nothing moved)
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)
    assert _pick_blocks(*PLAIN_FLAGSHIP) == (512, 16)
    assert _pick_blocks(*PLAIN_CHUNKED, bwd=True) == (512, 8)
    assert _pick_blocks_bx(*BX_FLAGSHIP) == (128, 8)
    assert _pick_blocks(128, 16, 8, 3, 32) == (128, 16)
    assert _pick_block_n(*ATT_FLAGSHIP) == 128
    assert _pick_block_n(*ATT_FLAGSHIP, bwd=True) == 64
    # and the consult log says every forward pick was heuristic
    summary = tuning.consult_summary()
    assert summary['adopted'] == []
    assert set(summary['by_source']) == {'heuristic'}


def test_promote_roundtrip_changes_pick_and_logs_consult(isolated_cache):
    entry = tuning.promote(
        'plain', PLAIN_CHUNKED, (256, 16),
        provenance=dict(benched_nodes_steps_per_sec=123.0))
    assert entry['blocks'] == [256, 16]
    # persisted with version + provenance
    with open(tuning.cache_file()) as f:
        data = json.load(f)
    assert data['version'] == tuning.CACHE_VERSION
    (key, stored), = data['entries'].items()
    assert key.startswith('plain|4096,1024,64,7,128|float32|')
    assert stored['provenance']['benched_nodes_steps_per_sec'] == 123.0
    assert 'time_utc' in stored['provenance']
    # the pick changed, and telemetry can tell
    assert _pick_blocks(*PLAIN_CHUNKED) == (256, 16)
    adopted = tuning.consult_summary()['adopted']
    assert adopted == [dict(kernel='plain', shape=list(PLAIN_CHUNKED),
                            dtype='float32', source='cache',
                            blocks=[256, 16], count=1)]
    # other shapes and the backward are untouched
    assert _pick_blocks(*PLAIN_FLAGSHIP) == (512, 16)
    assert _pick_blocks(*PLAIN_CHUNKED, bwd=True) == (512, 8)


def test_attention_promote_changes_pick():
    tuning.promote('attention', ATT_FLAGSHIP, (32,))
    assert _pick_block_n(*ATT_FLAGSHIP) == 32
    # a FORWARD entry never steers the backward ('attention_bwd' is its
    # own kind): bwd stays heuristic
    assert _pick_block_n(*ATT_FLAGSHIP, bwd=True) == 64


def test_attention_bwd_is_its_own_kind():
    """ISSUE 11 satellite: the attention backward consults kind
    'attention_bwd' — the tuner can promote a measured bwd block, and
    it never leaks into the forward (or the f32 pick from a bf16
    entry: dtype is threaded)."""
    tuning.promote('attention_bwd', ATT_FLAGSHIP, (16,))
    assert _pick_block_n(*ATT_FLAGSHIP, bwd=True) == 16
    assert _pick_block_n(*ATT_FLAGSHIP) == 128  # fwd untouched
    # dtype keys the entry
    tuning.promote('attention_bwd', ATT_FLAGSHIP, (8,), dtype='bfloat16')
    assert _pick_block_n(*ATT_FLAGSHIP, bwd=True) == 16
    assert _pick_block_n(*ATT_FLAGSHIP, bwd=True, dtype='bfloat16') == 8
    # every bwd consult is recorded under its own kind
    adopted = tuning.consult_summary()['adopted']
    assert any(c['kernel'] == 'attention_bwd' and c['source'] == 'cache'
               for c in adopted)
    assert not any(c['kernel'] == 'attention' for c in adopted)


def test_attention_bwd_invalid_entry_degrades_with_warning():
    tuning.promote('attention_bwd', ATT_FLAGSHIP, (512,))  # bwd-model
    # inadmissible at this shape (the ~2x row model rejects 512)
    import pytest as _pytest
    with _pytest.warns(UserWarning, match='not tile-legal'):
        assert _pick_block_n(*ATT_FLAGSHIP, bwd=True) == 64


def test_dtype_and_device_key_the_entry():
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16), dtype='bfloat16')
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)  # f32 pick untouched
    assert tuning.lookup('plain', PLAIN_CHUNKED, dtype='bfloat16') == (
        (256, 16), 'cache')
    tuning.promote('plain', PLAIN_FLAGSHIP, (256, 16),
                   device_kind='TPU v5e')
    assert _pick_blocks(*PLAIN_FLAGSHIP) == (512, 16)  # we are 'cpu'


def test_corrupt_cache_is_a_miss(isolated_cache):
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16))
    with open(tuning.cache_file(), 'w') as f:
        f.write('this is not json{{{')
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)


def test_truncated_cache_is_a_miss(isolated_cache):
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16))
    path = tuning.cache_file()
    raw = open(path).read()
    with open(path, 'w') as f:
        f.write(raw[:len(raw) // 2])
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)
    # and a later promote rebuilds a valid file over the debris
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16))
    assert _pick_blocks(*PLAIN_CHUNKED) == (256, 16)


def test_version_bump_invalidates(isolated_cache, monkeypatch):
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16))
    assert _pick_blocks(*PLAIN_CHUNKED) == (256, 16)
    monkeypatch.setattr(tuning, 'CACHE_VERSION', tuning.CACHE_VERSION + 1)
    # the versioned filename changes, so the old table is simply not read
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)


def test_wrong_in_file_version_is_a_miss(isolated_cache):
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16))
    path = tuning.cache_file()
    with open(path) as f:
        data = json.load(f)
    data['version'] = tuning.CACHE_VERSION + 99
    with open(path, 'w') as f:
        json.dump(data, f)
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)


def test_tile_quantum_illegal_entry_rejected_with_warning():
    tuning.promote('plain', PLAIN_CHUNKED, (300, 12))  # not 128/8-legal
    with pytest.warns(UserWarning, match='not tile-legal'):
        assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)


def test_vmem_illegal_entry_rejected_with_warning():
    # (512, 64) at the flagship plain shape blows the 7 MiB model
    tuning.promote('plain', PLAIN_FLAGSHIP, (512, 64))
    with pytest.warns(UserWarning, match='not tile-legal|VMEM'):
        assert _pick_blocks(*PLAIN_FLAGSHIP) == (512, 16)


def test_forced_candidate_beats_cache():
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16))
    with tuning.force('plain', (256, 32)):
        assert _pick_blocks(*PLAIN_CHUNKED) == (256, 32)
    assert _pick_blocks(*PLAIN_CHUNKED) == (256, 16)


def test_shape_pinned_force_does_not_leak_to_other_shapes():
    # the tuner pins shape+dtype: the candidate under measurement must
    # steer ONLY the target pick — a same-kind pick at another shape
    # keeps its deployed resolution (its admissible set differs, and it
    # reverts to the heuristic after promotion, so leaking it into the
    # A/B would measure a program that never deploys)
    with tuning.force('plain', (256, 32), shape=PLAIN_CHUNKED,
                      dtype='float32'):
        assert _pick_blocks(*PLAIN_CHUNKED) == (256, 32)
        assert _pick_blocks(*PLAIN_FLAGSHIP) == (512, 16)  # heuristic
        assert tuning.lookup('plain', PLAIN_CHUNKED,
                             dtype='bfloat16') is None
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 16)


def test_admissible_candidates_exclude_measured_mosaic_failures():
    # the round-4 sweep's Mosaic VMEM compile failures must be
    # excluded up front
    bxf = tuning.admissible_candidates('bxf', BX_FLAGSHIP)
    assert (256, 16) not in bxf and (512, 16) not in bxf
    assert (128, 8) in bxf  # the production-validated default
    plain = tuning.admissible_candidates('plain', PLAIN_FLAGSHIP)
    assert (512, 16) in plain  # the measured end-to-end winner
    assert all(be % 128 == 0 and bif % 8 == 0 for be, bif in plain)


def test_attention_candidates_are_bwd_aware():
    from se3_transformer_tpu.kernels.pallas_attention import (
        _VMEM_LIMIT, _block_row_bytes,
    )
    cands = tuning.admissible_candidates('attention', ATT_FLAGSHIP)
    row_bwd = _block_row_bytes(ATT_FLAGSHIP[1], ATT_FLAGSHIP[2], bwd=True)
    assert cands, 'no admissible attention candidates at the flagship'
    for (bn,) in cands:
        # training differentiates with the same block family, so a
        # forward-only fit must not be admitted
        assert bn * row_bwd <= _VMEM_LIMIT
    # the fwd heuristic's 128 does NOT fit the bwd row model here
    assert (128,) not in cands


def test_seeded_entry_is_numerically_inert_end_to_end():
    """A tuned pick changes the schedule, never the math: interpret-mode
    kernel output under the seeded entry matches to accumulation-order
    tolerance (different blocking reassociates the f32 sums)."""
    import jax.numpy as jnp

    from se3_transformer_tpu.kernels.pallas_pairwise import (
        fused_pairwise_conv,
    )
    rng = np.random.RandomState(0)
    E, mid, IF, O, P = 40, 32, 16, 8, 3
    h = jnp.asarray(rng.normal(size=(E, mid)), jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(mid, IF, O)), jnp.float32)
    b3 = jnp.asarray(rng.normal(size=(IF, O)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(E, P, IF)), jnp.float32)
    shape = (E, IF, O, P, mid)
    baseline_blocks = _pick_blocks(*shape)
    out_ref = np.asarray(fused_pairwise_conv(h, w3, v2, b3=b3,
                                             interpret=True))
    seeded = (128, 8)
    assert seeded != baseline_blocks
    assert seeded in tuning.admissible_candidates('plain', shape)
    tuning.promote('plain', shape, seeded,
                   provenance=dict(note='test seed'))
    tuning.clear_kernel_caches()  # the jit cache keys on shapes, not
    # the table — same trap as the env overrides
    assert _pick_blocks(*shape) == seeded
    out_seeded = np.asarray(fused_pairwise_conv(h, w3, v2, b3=b3,
                                                interpret=True))
    np.testing.assert_allclose(out_seeded, out_ref, rtol=1e-4, atol=1e-4)
    tuning.clear_kernel_caches()


def test_promote_is_read_modify_write():
    tuning.promote('plain', PLAIN_CHUNKED, (256, 16))
    tuning.promote('bxf', BX_FLAGSHIP, (256, 8))
    tuning.promote('plain', PLAIN_CHUNKED, (512, 8))  # overwrite by key
    ents = tuning.entries()
    assert len(ents) == 2
    assert _pick_blocks(*PLAIN_CHUNKED) == (512, 8)
    assert _pick_blocks_bx(*BX_FLAGSHIP) == (256, 8)


def test_tune_record_schema_roundtrip():
    """The tune record kind the tuner emits validates, and malformed
    ones fail loudly."""
    from se3_transformer_tpu.observability.schema import (
        SchemaError, validate_record,
    )
    rec = dict(kind='tune', run_id='tune-abc', kernel='plain',
               shape=[4096, 1024, 64, 7, 128], candidate=[256, 16],
               blocks=[256, 16], step_ms=12.3, verdict='promoted',
               promoted=True)
    validate_record(rec)
    with pytest.raises(SchemaError, match='verdict'):
        validate_record({**rec, 'verdict': 'sideways'})
    with pytest.raises(SchemaError, match='promoted'):
        validate_record({**rec, 'promoted': False})
    with pytest.raises(SchemaError, match='candidate'):
        validate_record({**rec, 'candidate': 'big'})
    with pytest.raises(SchemaError, match='missing'):
        validate_record({k: v for k, v in rec.items() if k != 'blocks'})


def test_report_surfaces_tune_records():
    from se3_transformer_tpu.observability.report import (
        summarize_tune_records,
    )
    recs = [
        dict(kind='tune', kernel='plain', shape=[1, 2], candidate=[256, 8],
             blocks=[256, 8], verdict='promoted', promoted=True,
             step_ms=1.0, nodes_steps_per_sec=300.0,
             pairs=[dict(incumbent=1.0, candidate=2.0)]),
        dict(kind='tune', kernel='plain', shape=[1, 2], candidate=[512, 8],
             blocks=[256, 8], verdict='rejected', promoted=False),
        dict(kind='tune', kernel='plain', shape=[1, 2], candidate=[256, 8],
             blocks=[256, 8], verdict='consulted', promoted=True),
    ]
    out = summarize_tune_records(recs)
    assert out['candidates'] == 3
    assert out['verdicts'] == dict(promoted=1, rejected=1, consulted=1)
    assert out['promoted'][0]['candidate'] == [256, 8]
    assert out['consulted'] == [dict(kernel='plain', shape=[1, 2],
                                     blocks=[256, 8])]
