"""Device monitor: HBM occupancy gauges from ``memory_stats()``.

Sampled at metric-emission boundaries (not per step). The CPU backend
reports no memory stats: the first empty sample there latches the
monitor silent for the rest of the process. On any other backend an
empty or failing ``memory_stats()`` is an error — the HBM gauges are
what the trainers' memory-fit check and the serve sizing rest on, and a
TPU run without them must not look like a healthy one.
"""

from trlx_tpu.telemetry.registry import MetricsRegistry

_available = True  # latches False on the CPU backend's first empty sample

_GAUGES = {
    "bytes_in_use": "device/hbm_in_use_gb",
    "peak_bytes_in_use": "device/hbm_peak_gb",
    "bytes_limit": "device/hbm_limit_gb",
}


def sample_device_stats(registry: MetricsRegistry) -> None:
    global _available
    if not _available:
        return
    import jax

    device = jax.local_devices()[0]
    stats = device.memory_stats()
    if not stats:
        if device.platform != "cpu":
            raise RuntimeError(
                f"{device.platform} device '{device.device_kind}' reports "
                f"no memory_stats(); the device/hbm_* gauges cannot be "
                f"sampled"
            )
        _available = False
        return
    for key, gauge in _GAUGES.items():
        if key in stats:
            registry.set_gauge(gauge, stats[key] / 2**30)
    if stats.get("bytes_limit"):
        registry.set_gauge(
            "device/hbm_utilization",
            stats.get("bytes_in_use", 0) / stats["bytes_limit"],
        )
