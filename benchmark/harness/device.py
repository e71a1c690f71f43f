"""The device as JAX reports it, the table of peaks, and the compile cache."""

from __future__ import annotations

import os
import sys

# Published peaks of one chip, keyed by `device_kind`. Source: Google
# Cloud documentation, "TPU v5e" system architecture page (197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise SystemExit(
            f"no published peaks for device kind {kind!r}: add it to "
            "benchmark/harness/device.py PEAKS with its source")
    return PEAKS[kind]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path: where
    JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache. Every
    program is cached, however short its compile."""
    import jax

    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return ""  # a rehearsal: nothing worth keeping
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int, allow_cpu: bool = False) -> dict:
    """Bring the backend up; exit 3 with no result unless it is a TPU
    with at least `chips` devices. `allow_cpu` is for rehearsals and
    tests, whose numbers are printed under no device metric's name."""
    import jax

    devices = jax.devices()
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if report["platform"] != "tpu" and not allow_cpu:
        print(f"benchmark: JAX found {report}, not a TPU; nothing is "
              "measured off the chip", file=sys.stderr)
        raise SystemExit(3)
    if report["platform"] == "tpu" and len(devices) < chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX found "
              f"{report}", file=sys.stderr)
        raise SystemExit(3)
    return report


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest device, None off the chip."""
    import jax

    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.local_devices()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def free_device():
    """Delete every array the process still holds on the device, whoever
    holds the reference: the program's state goes before the reference
    runs, so that the reference fits and sets no peak."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()
