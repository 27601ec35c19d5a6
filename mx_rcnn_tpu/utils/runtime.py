"""What this process is running on, as jax reports it.

Every record a tool writes (BENCH_serving / BENCH_soak lines, the chip
smoke's result, a re-recorded golden) carries :func:`device_record`, so a
number can never be read without the device it came from — a CPU run of a
serving tool is a count of requests, not a latency of the chip.
"""

from __future__ import annotations

from importlib import metadata


def device_record() -> dict:
    """``{"platform", "device_kind", "n_devices"}`` of the default backend
    (initialises it)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
    }


def runtime_versions() -> dict:
    """Installed jax / jaxlib / libtpu versions (provenance of a golden
    or a bring-up record; libtpu is the on-chip compiler)."""
    out = {}
    for name in ("jax", "jaxlib", "libtpu"):
        try:
            out[name] = metadata.version(name)
        except metadata.PackageNotFoundError:
            out[name] = "not installed"
    return out
