"""BeatWorker: the forest's beats run in commit order on one thread.

A beat (reference: src/lsm/compaction.zig beats) is the LSM work one
commit owes: spill the oldest rows of the state machine's RAM tail
into the trees, seal what overflowed, advance pending merges by a
block budget.  None of it decides the commit's reply, so the commit
only hands it over: the loop's thread copies the rows, computes the
budget and submits one job; the `lsm-beat` SerialWorker runs the jobs
FIFO.  Same jobs, same order, one thread: the forest sees the calls it
saw when the beat ran inside the commit, so blocks, manifest events
and free-set decisions stay a function of the commit count.

Single owner: between barriers the forest, its grid's cache and free
set, and the manifest log are touched by the worker alone.  Whoever
else wants them (a read of a spilled row, a checkpoint, a restore, the
scrubber) calls `barrier()` first, which is one deque check when the
worker is idle.

Only where the storage declares `supports_async_writeback`
(FileStorage: every served replica), the switch the grid's writer
uses; on MemoryStorage (tests, fuzzers, the VOPR) `submit` runs the
job in place, single-threaded and deterministic.
"""

from __future__ import annotations

import collections
import weakref

from tigerbeetle_tpu.utils import tracer as tracer_mod
from tigerbeetle_tpu.utils.worker import SerialWorker

# Beats handed over and not yet finished, at most: the hand-over of a
# third waits for the oldest.  Bounds the rows held twice (tail copy +
# memtable), the length of a checkpoint's drain, and keeps the commit
# rate honest when the worker is the slower side.
BEATS_QUEUED_MAX = 2


class BeatWorker:
    # Audited sharing with the lsm-beat SerialWorker (tbcheck
    # worker-shared): `_run` (worker) is the only writer of `_error`,
    # once, before its job reads as done; the loop's thread reads it
    # after joining that job.  Everything else here is the loop's.
    _WORKER_SHARED = frozenset({"_error"})

    def __init__(self, registry, threaded: bool) -> None:
        self.tracer = tracer_mod.NULL
        # On the worker: annotated on its own thread, out of the
        # loop's sums.
        self._st_work = tracer_mod.Stage(
            registry.histogram("beat.work_us"), "lsm.beat.work", tid=3
        )
        self._c_bound_waits = registry.counter("beat.bound_waits")
        self._h_bound_wait = registry.histogram("beat.bound_wait_us")
        self._c_joins = registry.counter("barrier.joins")
        self._h_join_wait = registry.histogram("barrier.wait_us")
        registry.gauge_fn("beat.queued", self.queued)
        self._jobs: collections.deque = collections.deque()
        self._error: BaseException | None = None
        self._worker = None
        if threaded:
            self._worker = SerialWorker("lsm-beat")
            # Discarded forests (crash-recovery loops) reclaim their
            # thread instead of leaking it.
            weakref.finalize(self, self._worker.close)

    def queued(self) -> int:
        return sum(1 for job in list(self._jobs) if not job.done())

    def idle(self) -> bool:
        return not self._jobs or self._jobs[-1].done()

    def submit(self, fn, *args) -> None:
        """Hand one beat over (the loop's thread, in commit order)."""
        if self._worker is None:
            fn(*args)
            return
        jobs = self._jobs
        while jobs and jobs[0].done():
            jobs.popleft()
        if len(jobs) >= BEATS_QUEUED_MAX:
            self._c_bound_waits.inc()
            with self._h_bound_wait.time():
                jobs.popleft().result()
        self._raise_failed()
        jobs.append(self._worker.submit(self._run, fn, args))

    def barrier(self) -> None:
        """Join every beat handed over.  Before anything but the
        worker reads or writes the forest."""
        jobs = self._jobs
        if jobs:
            if not jobs[-1].done():
                self._c_joins.inc()
                with self._h_join_wait.time():
                    jobs[-1].result()  # FIFO: the last done, all done
            jobs.clear()
        self._raise_failed()

    def close(self) -> None:
        """Drain, stop the thread, and say so if a beat failed.
        Idempotent."""
        try:
            self.barrier()
        finally:
            if self._worker is not None:
                self._worker.close()

    def _raise_failed(self) -> None:
        # STICKY, as Grid.flush_writes' write error: a forest that
        # lost a beat midway is not one to build on, so every later
        # hand-over and barrier raises what the beat raised (GridFull
        # above all) on the loop's thread.
        if self._error is not None:
            raise self._error

    def _run(self, fn, args) -> None:
        if self._error is not None:
            return  # beats behind a failed one do not run
        try:
            with self.tracer.stage(self._st_work):
                fn(*args)
        # tbcheck: allow(broad-except): whatever the beat raised is
        # kept and re-raised on the loop's thread (_raise_failed).
        except BaseException as e:
            self._error = e
