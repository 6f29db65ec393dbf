"""The device a run is on: the check that it is the chip the cell asks for,
the table of peaks, and the memory reading. A run that finds no TPU, or fewer
chips than the cell needs, fails here; it never falls back to the CPU."""

from __future__ import annotations

import sys

# Peaks of one chip, keyed by JAX's exact ``device_kind``. A kind that is not
# here is an error, not a default. Source for "TPU v5 lite": Google Cloud
# documentation, "TPU v5e" system architecture (197 TFLOP/s bf16, 16 GB HBM2e
# at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it (a fixed
    path inside the checkout, or `JAX_COMPILATION_CACHE_DIR`), and every
    program in it, also those that compile in under a second: a second run
    has to find all of them."""
    import jax
    from tpudml.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class NoChip(RuntimeError):
    pass


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add a row with its "
            f"source to benchmarks/device.py PEAKS (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def require_chips(chips: int, platform: str = "tpu") -> list:
    """The first ``chips`` devices, or NoChip when JAX's default backend is
    not ``platform`` or sees fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < chips:
        raise NoChip(
            f"cell needs {chips} {platform} device(s); JAX found "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})")
    return devices[:chips]


def describe(devices: list) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices: list) -> int:
    """Peak bytes held on the fullest of ``devices``; 0 where the backend
    keeps no statistics (the CPU, in tests).

    The TPU allocator counts a program's temporaries apart from the buffers
    the process holds: `peak_bytes_in_use` is arguments and results only
    (4.93 GB for the GPT-2-medium step, whose compiled program has 10.39 GB of
    temporaries), and the temporaries are `peak_bytes_reserved` (10.24 GB
    there; my chip run, PR 26). A step holds both at once, so the peak is
    their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats(devices: list) -> dict:
    """Everything the first device's allocator reports, for the info line."""
    return dict(devices[0].memory_stats() or {})


def fail(message: str) -> None:
    print(f"benchmarks/run.py: {message}", file=sys.stderr)
    sys.exit(1)
