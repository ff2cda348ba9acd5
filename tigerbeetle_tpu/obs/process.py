"""What no stage can name: the process's own pauses.

A stall of seconds inside one stage (`<stage>_us.max`) says where the
loop stood, not why.  Three things outside the program's own code can
hold it there, and each leaves a number on the server's registry:

- the cyclic collector: histogram `server.gc.pause_us` and counters
  `server.gc.collections.gen0/1/2`, from one `gc.callbacks` hook (it
  runs at a collection, never at a commit); a pause over
  GC_PAUSE_NOTE_US also leaves an instant `gc_pause` in the flight
  ring, which `start` writes on SIGTERM;
- page faults: gauges `server.minflt`, `server.majflt`;
- descheduling: gauge `server.nivcsw` (involuntary context switches),
  and `server.cpu_us` (user + system): over a window, against
  `server.uptime_us`, the cores the process held.

The gauges read `resource.getrusage(RUSAGE_SELF)` at scrape time.  No
collector setting is changed here.
"""

from __future__ import annotations

import gc
import resource
import time

GC_PAUSE_NOTE_US = 50_000


def _usage():
    return resource.getrusage(resource.RUSAGE_SELF)


def _cpu_us() -> int:
    usage = _usage()
    return int((usage.ru_utime + usage.ru_stime) * 1e6)


class ProcessWatch:
    def __init__(self, registry, tracer, clock=time.perf_counter_ns) -> None:
        self._tracer = tracer
        self._clock = clock
        self._t0 = None
        self._h_pause = registry.histogram("server.gc.pause_us")
        self._c_gen = [
            registry.counter(f"server.gc.collections.gen{g}")
            for g in range(3)
        ]
        registry.gauge_fn("server.cpu_us", _cpu_us)
        registry.gauge_fn("server.minflt", lambda: _usage().ru_minflt)
        registry.gauge_fn("server.majflt", lambda: _usage().ru_majflt)
        registry.gauge_fn("server.nivcsw", lambda: _usage().ru_nivcsw)
        # TB_METRICS=0: no histogram to feed, no hook.
        self._hooked = self._h_pause.live
        if self._hooked:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = self._clock()
            return
        t0, self._t0 = self._t0, None
        if t0 is None:
            return  # hooked between a collection's start and its stop
        us = (self._clock() - t0) / 1e3
        self._h_pause.observe(us)
        self._c_gen[info["generation"]].inc()
        if us > GC_PAUSE_NOTE_US:
            self._tracer.instant(
                "gc_pause", generation=info["generation"], us=int(us)
            )

    def close(self) -> None:
        """Unhook (the list is the process's: a closed server must not
        keep counting).  Idempotent."""
        if self._hooked:
            self._hooked = False
            gc.callbacks.remove(self._on_gc)
