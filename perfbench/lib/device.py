"""What JAX reports of the devices a run used."""

from __future__ import annotations


def require(chips: int) -> None:
    """Refuse to measure anywhere but on ``chips`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"perfbench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) != chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX found {len(devs)}")


def record() -> dict:
    """Platform, kind and count of the devices, and the peak bytes in use on
    the fullest of them."""
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks) if peaks else None}
