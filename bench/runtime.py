"""The process around a run: caches, the chip check, compile counting."""
from __future__ import annotations

import os
from pathlib import Path

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def configure(state_dir: Path) -> str:
    """Set up JAX's persistent compilation cache before anything compiles:
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else a fixed directory
    in the checkout's benchmark state.  Every program is cached, however
    quick its compile, so a second run compiles nothing.  Returns the
    directory in use."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(state_dir / "jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def device_record(chips: int) -> dict:
    """Platform, kind and count of the devices; raises ``NoChip`` unless
    there is a TPU with at least ``chips`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} TPU chip(s), the cell needs {chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where it is reported."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts programs XLA compiled between ``start`` and ``stop`` (a
    program loaded from the persistent cache is not a compile)."""

    def __init__(self):
        import jax
        self._built = self._hits = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self._on and event == COMPILE_EVENT:
            self._built += 1

    def _event(self, event, **kw):
        if self._on and event == CACHE_HIT_EVENT:
            self._hits += 1

    def start(self):
        self._on = True

    def stop(self):
        self._on = False

    @property
    def count(self) -> int:
        return self._built - self._hits
