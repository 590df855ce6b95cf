"""The chip a run measures, and what JAX itself reports about compiling."""
from __future__ import annotations

from typing import Dict


class DeviceError(Exception):
    """The run is not on the accelerator the cell asks for."""


def require_tpu(chips: int) -> Dict:
    """JAX's devices as the result line reports them; raises unless the
    first device is a TPU and there are at least ``chips`` of them."""
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        raise DeviceError(f"JAX's first device is {dev['platform']!r}, "
                          f"not a TPU")
    if len(devices) < chips:
        raise DeviceError(f"{len(devices)} TPU device(s), the cell asks "
                          f"for {chips}")
    return dev


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events (a cache hit still records a short compile)."""

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "persistent_cache_hits": self.cache_hits}
