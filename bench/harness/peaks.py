"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is missing is an error, never a
default: a share of a peak is only as true as the peak.

Sources: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. The on-chip
VMEM bandwidths are those the TPU profiler itself states for the device
(``peak_vmem_rd_bw_gigabytes_per_second`` 18432.00247463936 and
``peak_vmem_wr_bw_gigabytes_per_second`` 6144.004404019201 in the device
plane of a trace taken on a TPU v5 lite).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "vmem_read_bytes_per_s": 18432.00247463936e9,
        "vmem_write_bytes_per_s": 6144.004404019201e9,
        "source": "Google Cloud documentation, TPU v5e; TPU profiler "
                  "device plane (VMEM)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"bench: no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/harness/"
                         f"peaks.py with their source") from None
