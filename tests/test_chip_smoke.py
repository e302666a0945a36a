"""CPU rehearsal of chip_smoke.py: its phases at a tiny size, its checks
on facts a chip would and would not produce, and its refusal to run
without a TPU.

The script has no CPU branch and no size option; the rehearsal steers it
from here — phases are called with tiny sizes, and the device check is
the one thing never reached (`main` is only ever shown a CPU, which it
must refuse). What only a chip can show (Mosaic calls in the program,
the loss falling at flagship width) is checked on the facts directly.
"""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

TINY = dict(dim=8, depth=1, num_neighbors=4)


@pytest.fixture(scope='module')
def cache():
    return cs.CacheCounter()


def test_refuses_to_run_without_a_tpu(tmp_path):
    """`python chip_smoke.py` on a CPU: non-zero exit, no result line —
    from the repo, and from a directory holding the script alone."""
    alone = tmp_path / 'chip_smoke.py'
    alone.write_text(open(os.path.join(REPO, 'chip_smoke.py')).read())
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    for script in (os.path.join(REPO, 'chip_smoke.py'), str(alone)):
        proc = subprocess.run([sys.executable, script], env=env,
                              cwd=os.path.dirname(script),
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert 'needs a TPU' in proc.stderr
    with pytest.raises(SystemExit):
        cs.require_tpu(1)


def test_train_phase_rehearsal(cache):
    facts = cs.train_phase(cache, nodes=16, steps=3, **TINY)
    assert len(facts['losses']) == 3
    cs.check_finite('train', facts['losses'])
    # on a CPU the auto-dispatch takes the XLA path, and the check that
    # keeps an XLA path from passing for the kernel path says so
    assert facts['mosaic_calls'] == 0
    with pytest.raises(cs.SmokeFailure, match='tpu_custom_call'):
        cs.check_train(facts)


def test_check_train_on_chip_shaped_facts():
    good = dict(mosaic_calls=600, losses=[149709.1, 150971.5, 107368.2])
    cs.check_train(good)
    with pytest.raises(cs.SmokeFailure, match='did not decrease'):
        cs.check_train(dict(good, losses=[3.0, 2.0, 3.5]))
    with pytest.raises(cs.SmokeFailure, match='non-finite'):
        cs.check_train(dict(good, losses=[3.0, float('nan'), 1.0]))


def test_serve_phase_rehearsal(cache):
    facts = cs.serve_phase(cache, buckets=(8, 16), batch_size=2,
                           requests=5, **TINY)
    assert len(facts['pending']) == 5 and facts['rejected'] == ['oversize']
    cs.check_serve(facts)
    # each fact the check reads can fail it
    with pytest.raises(cs.SmokeFailure, match='compile events'):
        cs.check_serve(dict(facts, post_warmup_compiles=1))
    with pytest.raises(cs.SmokeFailure, match='equivariance'):
        cs.check_serve(dict(facts, equivariance=2e-3))
    with pytest.raises(cs.SmokeFailure, match='oversize'):
        cs.check_serve(dict(facts, rejected=[]))


def test_compile_cache_hits_are_counted(cache, tmp_path):
    """The line a second chip_smoke.py run prints for the train step:
    the same program compiled twice is one miss, then one hit."""
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update('jax_compilation_cache_dir', str(tmp_path))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    compilation_cache.reset_cache()
    try:
        counts = []
        for _ in range(2):
            hits, misses = cache.hits, cache.misses
            jax.jit(lambda x: jnp.tanh(x) @ x + 21.5).lower(
                np.ones((9, 9), np.float32)).compile()
            counts.append((cache.hits - hits, cache.misses - misses))
        assert counts == [(0, 1), (1, 0)]
    finally:
        for name, value in zip(
                ('jax_compilation_cache_dir',
                 'jax_persistent_cache_min_compile_time_secs',
                 'jax_persistent_cache_min_entry_size_bytes'), saved):
            jax.config.update(name, value)
        compilation_cache.reset_cache()


@pytest.mark.slow
def test_four_chip_phases_rehearsal(cache):
    """`--chips 4` on four virtual devices: replicas on four different
    devices, state spread over the mesh, mesh losses equal to one
    device's."""
    devices = jax.devices()[:4]
    facts = cs.replica_phase(cache, devices)
    cs.check_replicas(facts, devices)
    for engine in facts['engines'][1:]:      # placement is what is checked
        engine.mesh = facts['engines'][0].mesh
        engine.params = engine.params
    with pytest.raises(cs.SmokeFailure, match='four different devices'):
        cs.check_replicas(facts, devices)

    facts = cs.mesh_phase(cache, devices, nodes=32, parity_nodes=16,
                          steps=3, **TINY)
    assert facts['full']['collectives'] > 0
    facts['full']['losses'] = [3.0, 2.0, 1.0]     # learning is a chip's
    cs.check_mesh(facts)
    with pytest.raises(cs.SmokeFailure, match='differ from one device'):
        cs.check_mesh(dict(facts, on_one=[x * 1.01 for x in facts['on_one']]))
