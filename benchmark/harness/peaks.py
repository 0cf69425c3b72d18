"""The chips the benchmark knows, and the look for one.

One table, keyed by ``device_kind`` as JAX reports it. A device that is
not in it is an error, not a default, and there is no row for a CPU: a
number from a CPU run is never a device metric. Nothing here reads the
program's ``chip_specs()``, which a calibration file can move.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


class NoChip(SystemExit):
    pass


def look_for_chips(need, steer=None):
    """The device as JAX reports it, or a refusal: platform, kind, count.
    Only a test's steering lets a run go on without a TPU."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (steer and steer.allow_cpu):
        raise NoChip(f"benchmark: JAX found platform {platform!r}, not a "
                     f"TPU: no result (there is no fallback)")
    if len(devices) < need:
        raise NoChip(f"benchmark: the cell needs {need} chips, JAX found "
                     f"{len(devices)}")
    kind = devices[0].device_kind
    if platform == "tpu" and kind not in PEAKS:
        raise NoChip(f"benchmark: no peaks known for device kind {kind!r}")
    return {"platform": platform, "kind": kind, "count": len(devices)}


def peaks_of(device):
    """The row of ``PEAKS`` for a device line; ``None`` off the TPU, so
    that a reader of a share of a peak finds nothing to read there."""
    return PEAKS.get(device["kind"]) if device["platform"] == "tpu" else None


def memory_peak_bytes(chips):
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))
