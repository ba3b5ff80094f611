"""The table of peaks, keyed by ``device_kind``, and what JAX reports of
the device. A device that is not in the table is an error, never a
default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9, "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
    "TPU v5e": {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9, "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's table "
            f"of peaks ({sorted(PEAKS)}): add it with its source"
        ) from None


def report(devices) -> dict:
    """``platform``, ``kind``, ``count`` as JAX reports them, and the
    peak bytes held on the fullest of ``devices``. Call it while the
    program's state is alive, after the window. The allocator's
    ``peak_bytes_in_use`` counts live arrays only: on this runtime the
    compiled programs' temporaries (activations) live in an arena it
    reports apart as ``bytes_reserved`` (a ResNet-50 step at batch 128
    reads 0.3 GB in use beside 4.5 GB reserved). So the peak is the
    larger of ``peak_bytes_in_use`` and what is held at this instant,
    ``bytes_in_use + bytes_reserved``; the two peaks are never added,
    because they need not fall together."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        held = int(stats.get("bytes_in_use", 0)) + int(
            stats.get("bytes_reserved", 0))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)), held)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
