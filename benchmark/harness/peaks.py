"""Published per-chip peaks, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" system architecture table:
197 TFLOP/s in bf16 and 819 GB/s of HBM bandwidth per chip (16 GB of HBM).
There is no float32 peak here on purpose: the MXU runs float32 matrix
multiplications as several bf16 passes, and no published figure prices them.
A device that is not in the table is an error, never a default.
"""

PEAKS = {
    'TPU v5 lite': {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9,
                    'source': 'cloud.google.com/tpu/docs/v5e'},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f'benchmark: no published peaks for device_kind {device_kind!r} '
            f'(known: {sorted(PEAKS)}); add it to harness/peaks.py with its '
            f'source') from None
