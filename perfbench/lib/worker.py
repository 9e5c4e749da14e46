"""What the process that holds the chip needs around the program: the
device check, jax's own compile events, and the benchmark's spans."""

from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, List


def device_report(chips: int, rehearsal: bool) -> dict:
    """Fails, before any work, unless this process was given `chips` TPU
    chips. A rehearsal (CPU, tiny sizes) says so in its report."""
    import jax

    devs = jax.devices()
    if rehearsal:
        devs = devs[:chips]  # the CPU backend shows every virtual device
    rep = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "pid": os.getpid(), "rehearsal": rehearsal}
    if not rehearsal and rep["platform"] != "tpu":
        raise RuntimeError(f"perfbench needs a TPU; this worker found "
                           f"platform={rep['platform']!r} ({rep['kind']})")
    if rep["count"] != chips:
        raise RuntimeError(f"the cell asks for {chips} chips; this worker "
                           f"sees {rep['count']}")
    return rep


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does not
    report it, as on the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """jax's own compile events in this process: how many programs were
    lowered, how the persistent cache answered, and the seconds spent
    tracing, lowering and compiling (or fetching from the cache)."""

    _TIMED = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.n = {"lowerings": 0, "cache_hits": 0, "cache_misses": 0,
                  "compile_s": 0.0}
        self.times = []  # wall time of every lowering
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name.endswith("/compilation_cache/cache_hits"):
            self.n["cache_hits"] += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.n["cache_misses"] += 1

    def _duration(self, name, secs, **_):
        if name in self._TIMED:
            self.n["compile_s"] += secs
        if name.endswith("/compile/jaxpr_to_mlir_module_duration"):
            self.n["lowerings"] += 1
            self.times.append(time.time())

    def snapshot(self) -> Dict[str, float]:
        return dict(self.n)


class Spans:
    """The benchmark's spans: wall-clock start and end kept in memory, and
    the same name written into the profiler's trace, so that an idle gap of
    the device can be named by what the host was doing."""

    def __init__(self):
        self.rows: Dict[str, List[tuple]] = {}

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        import jax

        t0 = time.time()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.rows.setdefault(name, []).append((t0, time.time(), fields))

    def durations_ms(self, name: str, t_from: float = 0.0,
                     t_to: float = float("inf")) -> List[float]:
        return [1e3 * (b - a) for a, b, _ in self.rows.get(name, [])
                if a >= t_from and b <= t_to]
