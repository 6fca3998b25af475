"""What the benchmark asks of the device: that it is the chip the cell
needs, how much memory the run peaked at, a count of compilations, and a
profiler trace of the measured window."""

from __future__ import annotations

import contextlib
import threading
from pathlib import Path


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def identify(chips: int) -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX found; raises
    :class:`NoChip` unless they are at least ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"the benchmark runs on a TPU; JAX found platform "
                     f"{platform!r} ({len(devices)} device(s))")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts XLA backend compilations while it is open (JAX's monitoring
    events; a cached executable loaded from disk is not a compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self._on = False
        self._lock = threading.Lock()

    def _listen(self, event, duration, **_):
        if self._on and event == self.EVENT:
            with self._lock:
                self.count += 1

    @contextlib.contextmanager
    def counting(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True
        try:
            yield self
        finally:
            self._on = False


@contextlib.contextmanager
def profiled(directory, on: bool):
    """The JAX profiler over the block when ``on``; yields the directory
    its trace lands in (``None`` when off)."""
    if not on:
        yield None
        return
    import jax

    Path(directory).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(directory))
    try:
        yield Path(directory)
    finally:
        jax.profiler.stop_trace()
