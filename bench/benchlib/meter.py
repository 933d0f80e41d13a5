"""Compilations and device memory, as the benchmark reports them."""

from __future__ import annotations


class CompileMeter:
    """Counts XLA compilations, persistent-cache hits and persistent-cache
    writes (JAX records a miss only when it writes the entry) via
    ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits, self.writes)

    def since(self, snap) -> dict:
        s, c, h, w = (a - b for a, b in zip(self.snapshot(), snap))
        return {"compile_s": s, "compiles": c, "cache_hits": h,
                "cache_writes": w}


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    does not report it)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0
