"""Backend selection and the compile cache's place — one helper each.

Every entry point that touches a device (``tdn``, ``benchmark/``, the
tools, the tests, the multi-chip dry run) goes through here, so there is
exactly one rule for each question:

* **Which platform?** JAX resolves it. An explicit choice is asserted
  after the backend comes up (:func:`require_platform`) — the process
  fails rather than running somewhere other than where it was told to.
  Nothing probes, retries or downgrades.
* **Where does compiled code go?** Where ``JAX_COMPILATION_CACHE_DIR``
  says; otherwise ``<checkout>/.jax_cache`` (:func:`enable_compile_cache`).
  The directory is part of the cache key, so it never contains a user
  name, pid, time or temp dir.
"""

from __future__ import annotations

import hashlib
import os

from tpu_dist_nn.utils.errors import UnavailableError

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _cpu_fingerprint() -> str:
    """Stable digest of the host's CPU feature flags.

    XLA:CPU cache entries compiled for other vector extensions abort
    when loaded, so CPU caches are kept apart per feature set."""
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((ln for ln in f if ln.startswith("flags")), "")
    except OSError:
        return "nofp"
    return hashlib.sha1(flags.encode()).hexdigest()[:8]


def compile_cache_dir() -> str:
    """The persistent compile cache directory for this process.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set. Otherwise the fixed
    ``<checkout>/.jax_cache``, with a ``cpu-<features>`` subdirectory
    when the process is pinned to the host platform."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        path = os.path.join(path, f"cpu-{_cpu_fingerprint()}")
    return path


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on at :func:`compile_cache_dir`.

    Call after any platform pin and before the first compile. With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and no
    directory is set here."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_report() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the devices."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def param_devices(tree) -> list[int]:
    """Ids of the devices that hold any leaf of ``tree``."""
    import jax

    return sorted({
        d.id for leaf in jax.tree.leaves(tree)
        for d in leaf.sharding.device_set
    })


def device_memory() -> list[dict]:
    """Per local device, what its allocator reports holding now and at
    its peak (``None`` where the backend keeps no such statistics)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def require_platform(choice: str) -> dict:
    """Bring the backend up and hold it to ``choice``.

    ``auto`` accepts whatever JAX resolved. ``cpu``/``tpu`` raise
    :class:`~tpu_dist_nn.utils.errors.UnavailableError` naming the
    backend JAX found instead. Returns :func:`device_report`."""
    import jax

    found = jax.default_backend()
    if choice != "auto" and found != choice:
        raise UnavailableError(
            f"platform {choice!r} was required but JAX resolved {found!r} "
            f"(jax_platforms={jax.config.jax_platforms!r}, devices="
            f"{[str(d) for d in jax.devices()]})"
        )
    return device_report()
