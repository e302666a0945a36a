"""The device a run is on: chip or fail, peaks, memory."""
import jax

from .peaks import peaks_for


def require_accelerator(chips):
    """Exit non-zero, printing no result, without `chips` TPU chips. A
    number from a CPU run is never written under a device metric's name."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f'benchmark: JAX found no accelerator: {e}')
    if devices[0].platform != 'tpu':
        raise SystemExit(f'benchmark: platform is {devices[0].platform!r}, '
                         f'not a TPU; this benchmark does not fall back')
    if len(devices) < chips:
        raise SystemExit(f'benchmark: the cell asks for {chips} chips, '
                         f'JAX sees {len(devices)}')
    kind = devices[0].device_kind
    return devices[:chips], kind, peaks_for(kind)


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest chip. `peak_bytes_in_use` leaves out a
    program's temporaries on this backend, `peak_bytes_reserved` has them, so
    the larger of the two is what the chip had to hold."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get('peak_bytes_in_use', 0)),
                   int(s.get('peak_bytes_reserved', 0)))
    return peak


def device_record(devices, kind):
    return {'platform': devices[0].platform, 'kind': kind,
            'count': len(devices),
            'memory_peak_bytes': memory_peak_bytes(devices)}
