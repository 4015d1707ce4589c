"""The device a run is on, and what the process did to get ready.

``require_devices`` is the gate: a measurement path that finds no TPU, or
another number of chips than the cell asks for, ends the process before a
phase runs and prints no result. ``CompileClock`` counts what JAX traced,
lowered and compiled (or loaded from the persistent cache) from
``jax.monitoring`` events, so ``compiles_in_window`` can be held to 0.
Both are copied from ``chip_smoke.py`` (PR 21), which stays the start-up
check; the copy is what later PRs cannot change.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, Sequence, Tuple


def device_summary(devices: Sequence[Any]) -> Dict[str, Any]:
    """The device as JAX reports it (the result line's ``device`` object)."""
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_devices(devices: Sequence[Any], chips: int, rehearsal: bool) -> None:
    """Exit non-zero, saying what was found, unless the devices are what the
    cell asks for: ``chips`` TPU chips, or in a rehearsal ``chips`` CPU
    devices."""
    found = device_summary(devices)
    want = "cpu" if rehearsal else "tpu"
    if found["platform"] != want or found["count"] != chips:
        sys.exit(
            f"benchmark: the cell needs {chips} {want} device(s), found "
            f"platform={found['platform']!r} kind={found['kind']!r} "
            f"count={found['count']}: no phase run, no result printed"
            + ("" if rehearsal else " (a CPU rehearsal is --rehearse-cpu)")
        )


def memory_peak_bytes(devices: Sequence[Any]) -> int:
    """Peak bytes held on the fullest chip: the allocator's peak of live
    buffers plus what the runtime reserves for the loaded programs'
    temporaries. On the TPU the two are kept apart: ``peak_bytes_in_use``
    counts arrays only, and a program's scratch space is ``bytes_reserved``
    (0.5 GB of arrays beside 6.3 GB reserved at the small training cell,
    read off the allocator's own events in a trace: PERF.md, PR 22).
    ``memory_stats()`` is ``None`` on the CPU, where this is 0."""
    peak = 0
    for dev in devices:
        stats = dev.memory_stats()
        if stats:
            reserved = max(
                int(stats.get("peak_bytes_reserved", 0)),
                int(stats.get("bytes_reserved", 0)),
            )
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)) + reserved)
    return peak


def memory_stats(devices: Sequence[Any]) -> Dict[str, Any]:
    """The allocator's whole record on the first chip, for the run's log."""
    return dict(devices[0].memory_stats() or {})


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's own record
    (``/proc/self/stat`` start time against the boot clock), so set-up time
    counts the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        # the command name (field 2) may hold spaces: split after its ")"
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")   # field 22
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), how many programs it built, and its cache hits
    and misses, summed over every thread of the process."""

    _TIMED = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )
    _BUILT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_: Any) -> None:
        if event in self._TIMED:
            with self._lock:
                self.seconds += duration
                if event == self._BUILT:
                    self.programs += 1

    def _on_event(self, event: str, **_: Any) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def read(self) -> Tuple[float, int, int, int]:
        """(seconds, programs built or loaded, cache hits, cache misses)."""
        with self._lock:
            return self.seconds, self.programs, self.hits, self.misses
