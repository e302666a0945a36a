"""Nothing on the main path may hide which device it runs on, or move its
caches: the platform check the kernel dispatch asks, the peaks a
utilization is priced against, the device identity of the tuning table,
and where the compile cache lives.
"""
import os
import subprocess
import sys

import jax
import pytest

from se3_transformer_tpu.kernels import tuning
from se3_transformer_tpu.utils import compilation_cache, flops, helpers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# is_tpu_backend / current_device_kind
# --------------------------------------------------------------------- #

@pytest.mark.parametrize('platform, expected',
                         [('tpu', True), ('cpu', False), ('gpu', False)])
def test_is_tpu_backend_is_the_platform_check(monkeypatch, platform,
                                              expected):
    monkeypatch.setattr(jax, 'default_backend', lambda: platform)
    assert helpers.is_tpu_backend() is expected


@pytest.mark.parametrize('fn', [helpers.is_tpu_backend,
                                tuning.current_device_kind])
def test_a_backend_that_fails_to_start_raises(monkeypatch, fn):
    """No `except: return False` / `return 'unknown'`: a dead backend
    used to turn every kernel off, or key the tuning table so that
    nothing matched, without a word."""
    def dead():
        raise RuntimeError('Unable to initialize backend')
    monkeypatch.setattr(jax, 'default_backend', dead)
    with pytest.raises(RuntimeError, match='Unable to initialize'):
        fn()


def test_current_device_kind_on_cpu():
    assert tuning.current_device_kind() == 'cpu'


# --------------------------------------------------------------------- #
# peaks keyed by device_kind
# --------------------------------------------------------------------- #

def test_v5e_peaks_are_the_published_ones():
    peaks = flops.device_peaks('TPU v5 lite')
    assert peaks['bf16_flops'] == 197e12
    assert peaks['hbm_bytes_per_sec'] == 819e9


@pytest.mark.parametrize('kind', ['cpu', 'TPU v4', 'unknown', ''])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match='no published peaks'):
        flops.device_peaks(kind)


def test_profile_roofline_needs_a_known_device_kind(tmp_path):
    """observability.profiling prices utilization against the peak of
    the device the trace names; a CPU trace names none and carries
    none, an unknown device raises."""
    from xplane_fixture import write_xplane

    from se3_transformer_tpu.observability import profiling
    write_xplane(
        str(tmp_path / 'plugins' / 'profile' / 'run' / 'host.xplane.pb'),
        {'device': {'/device:TPU:0': [['dot.1', 0.0, 100e3, None, None]]}})
    kw = dict(label='x', flops_per_step=1e9, steps=1)
    body = profiling.profile_payload(str(tmp_path), **kw)
    assert 'utilization_vs_bf16_peak' not in body['roofline']
    body = profiling.profile_payload(str(tmp_path), device_kind='TPU v5 lite',
                                     **kw)
    assert body['roofline']['utilization_vs_bf16_peak'] == pytest.approx(
        1e9 / 100e-6 / 197e12, rel=1e-4)
    with pytest.raises(KeyError, match='no published peaks'):
        profiling.profile_payload(str(tmp_path), device_kind='TPU v9', **kw)


# --------------------------------------------------------------------- #
# the compile cache is placed from outside, or at one fixed path
# --------------------------------------------------------------------- #

@pytest.fixture
def restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update('jax_compilation_cache_dir', saved)


def test_env_var_places_the_cache_and_code_sets_no_directory(
        monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'outside'))
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, 'update',
        lambda name, value: (updates.append(name), real_update(name, value)))
    returned = compilation_cache.enable_compilation_cache(
        str(tmp_path / 'ignored'))
    assert returned == str(tmp_path / 'outside')
    assert 'jax_compilation_cache_dir' not in updates
    assert updates == ['jax_persistent_cache_min_compile_time_secs']
    assert not (tmp_path / 'ignored').exists()


_PRINT_CACHE_DIR = (
    'from se3_transformer_tpu.utils.compilation_cache import '
    'enable_compilation_cache as e; import jax; '
    'print(e()); print(jax.config.jax_compilation_cache_dir)')


def test_unset_the_cache_is_one_fixed_path_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    fixed = os.path.join(REPO, '.jax_cache', 'jit')
    # two calls, and two other processes: always the same directory
    assert compilation_cache.enable_compilation_cache() == fixed
    assert compilation_cache.enable_compilation_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, '-c', _PRINT_CACHE_DIR], cwd=REPO,
            env=dict(env, JAX_PLATFORMS='cpu'), capture_output=True,
            text=True, timeout=120, check=True).stdout.split()
        assert out == [fixed, fixed]
    # no home directory, temp name, pid or time in any default path
    for path in (fixed, compilation_cache.CHECKOUT_CACHE_DIR):
        assert path.startswith(REPO + os.sep)
        assert '~' not in path and str(os.getpid()) not in path
