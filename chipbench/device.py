"""The chip: finding it, naming it, its memory peak, and the compiles
JAX makes."""

from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def open_chips(n: int) -> list:
    """The TPU devices of this process; raises :class:`NoChip` unless
    JAX finds at least ``n``.  Never falls back to another platform."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise NoChip(f"JAX found no device: {exc}") from exc
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def describe(devices: list) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices: list) -> int | None:
    """Peak bytes in use on the fullest device, where it reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileEvents:
    """JAX monitoring listener: programs built by the backend (each
    records a compile duration, also when it was loaded from the
    persistent cache) and persistent-cache hits of this process."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.hits = 0
        self.compile_s: list[float] = []
        import jax.monitoring

        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == self._HIT:
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == self._COMPILE:
            self.compile_s.append(secs)

    def snapshot(self) -> tuple[int, int, float]:
        """(programs built, of them loaded from the cache, seconds spent
        building) so far."""
        return len(self.compile_s), self.hits, sum(self.compile_s)
