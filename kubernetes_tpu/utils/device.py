"""Which device a run used, and the rule that a measurement names it.

A bench row, the chip smoke and every probe that reports a rate call
`require_device()` before building anything: a host where JAX found no
TPU is an error there, unless the CPU was asked for by name
(`JAX_PLATFORMS=cpu`, as the tests and CPU dry runs do). The returned
dict rides every result, so no row can read `"backend": "tpu"` without
saying what it ran on.
"""

from __future__ import annotations

import threading
from typing import Dict


class NoAccelerator(RuntimeError):
    """JAX found no TPU and the CPU was not asked for by name."""


def device_info() -> Dict:
    """The device as JAX reports it: platform, kind, count."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def row_fields(info: Dict) -> Dict:
    """device_info() under the names bench rows carry."""
    return {"platform": info["platform"], "device_kind": info["kind"],
            "device_count": info["count"]}


def cpu_requested() -> bool:
    """True when the process was pinned to the CPU by name (the
    JAX_PLATFORMS env var / jax_platforms config) — not when JAX merely
    fell back to it because no accelerator initialised."""
    import jax

    platforms = jax.config.jax_platforms or ""
    return platforms.split(",")[0].strip().lower() == "cpu"


def require_device(allow_cpu: bool = True) -> Dict:
    """device_info(), or NoAccelerator when the platform is not a TPU.
    With allow_cpu (the bench entry points' rule) a CPU asked for by
    name passes and the caller's rows say `platform: "cpu"`; the chip
    smoke passes allow_cpu=False."""
    info = device_info()
    if info["platform"] == "tpu":
        return info
    if allow_cpu and info["platform"] == "cpu" and cpu_requested():
        return info
    raise NoAccelerator(
        f"no TPU: jax.devices() reports {info['count']} x "
        f"{info['platform']} ({info['kind']}); a measurement on the CPU "
        f"must ask for it by name with JAX_PLATFORMS=cpu"
    )


class CompileMeter:
    """Executable builds seen by this process, from jax.monitoring: every
    XLA/Mosaic compile request (AOT `.compile()` and jit cache misses
    alike, on any thread) fires one backend-compile duration event, and a
    request served from the persistent cache also fires a cache-hit
    event. `requests - cache_hits` is what the compiler actually built;
    `seconds` is wall time inside those requests (retrieval time for
    hits), summed over threads."""

    _COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    _HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._COMPILE_EVENT:
            with self._lock:
                self.requests += 1
                self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def read(self) -> Dict:
        with self._lock:
            return {"requests": self.requests, "cache_hits": self.cache_hits,
                    "seconds": self.seconds}


_meter = None


def compile_meter() -> CompileMeter:
    """The process-wide CompileMeter, registered with jax.monitoring on
    first use (listeners cannot be scoped to a run; callers diff two
    read()s around the window they care about)."""
    global _meter
    if _meter is None:
        import jax.monitoring

        m = CompileMeter()
        jax.monitoring.register_event_duration_secs_listener(m._on_duration)
        jax.monitoring.register_event_listener(m._on_event)
        _meter = m
    return _meter
