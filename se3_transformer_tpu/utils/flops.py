"""Published peaks of the devices the repo measures on: what a
utilization or a roofline share is a share of."""
from __future__ import annotations

# Published per-chip peaks, keyed by jax's `device_kind`. Source: Google
# Cloud documentation, "TPU v5e" system architecture table (197 TFLOP/s
# bf16, 819 GB/s HBM bandwidth, 16 GB HBM). f32 matmuls run as multi-pass
# bf16 on the MXU; the repo's utilization figures have always priced
# them at a quarter of the bf16 rate.
DEVICE_PEAKS = {
    'TPU v5 lite': dict(bf16_flops=197e12, f32_flops=197e12 / 4,
                        hbm_bytes_per_sec=819e9),
}


def device_peaks(device_kind: str) -> dict:
    """Peaks of the device a number was measured on. A kind that is not
    in the table raises: a utilization against another chip's peak (or
    a CPU's wall clock against any) is a fabricated figure."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f'no published peaks for device_kind {device_kind!r} '
            f'(known: {sorted(DEVICE_PEAKS)}); add it to '
            f'utils.flops.DEVICE_PEAKS with its source') from None
