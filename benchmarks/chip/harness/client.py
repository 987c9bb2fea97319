"""What the traffic drivers share: host spans, counter deltas, the server
settings a cell names, its input pool, compile counting and the window
record each driver returns."""

from __future__ import annotations

import dataclasses
import gc

import numpy as np

from harness import data


def span(name: str):
    """A host span in the profiler's trace (cheap when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def counter_delta(before: dict, after: dict) -> dict:
    """``after - before`` for every integer counter of ``stats()`` (the
    fractions and percentiles it also holds do not subtract)."""
    return {key: after[key] - before[key] for key, val in after.items()
            if isinstance(val, int) and not isinstance(val, bool)
            and key in before}


def server_settings(cell) -> dict:
    """``EeiServer`` keyword arguments: the configuration's, then the
    traffic mix's on top."""
    return {**cell.config.get("server", {}), **cell.traffic.get("server", {})}


def make_pool(config: dict, seed: int, count: int) -> np.ndarray:
    """``count`` input matrices of the configuration's kind, host float32."""
    m = config["matrices"]
    if m["kind"] == "spiked_wishart":
        return data.spiked_wishart_pool(seed, count, config["n"],
                                        m["samples"], m["spikes"])
    raise ValueError(f"unknown matrix kind {m['kind']!r}")


def device_peak_bytes() -> int:
    """The device's peak bytes in use so far (see ``device.py``)."""
    import jax

    from harness import device

    return device.memory_peak_bytes(jax.devices())


def release(server) -> None:
    """Close a server and free what it holds on the device."""
    server.close()
    gc.collect()


class CompileMeter:
    """XLA compilations (or fetches of a compiled program from the
    persistent cache) and their seconds, while active."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _active: list = []
    _registered = False

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    @classmethod
    def _listen(cls, event: str, duration: float, **_) -> None:
        if event == cls._EVENT:
            for meter in cls._active:
                meter.count += 1
                meter.seconds += duration

    def __enter__(self) -> "CompileMeter":
        import jax

        if not CompileMeter._registered:
            jax.monitoring.register_event_duration_secs_listener(
                CompileMeter._listen)
            CompileMeter._registered = True
        CompileMeter._active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        CompileMeter._active.remove(self)


@dataclasses.dataclass
class WindowRecord:
    """What one measured window produced, for the end-to-end metrics, the
    per-layer readers and the check."""

    t_start: float
    t_end: float
    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value, as the driver measured it
    counters: dict = dataclasses.field(default_factory=dict)  # stats() delta
