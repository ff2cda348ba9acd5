"""Tracer: span tree with slot-based start/end discipline.

reference: src/tracer.zig:1-70 — events are started/ended on fixed
slots (so nesting bugs assert immediately), and emitted to a backend
selected at init: `none` (no-op, zero overhead) or `json` (Chrome
trace-event format, loadable in chrome://tracing / Perfetto — the
tracy backend analog for this build).

Hooked in the hot paths (the reference hooks tracer.zig directly in
src/state_machine.zig:610-614,1124-1143 and src/io/linux.zig:31-33):
replica commit stages, checkpoint, journal writes, LSM spill/seal, and
the device flush — see Replica.tracer.  Backend "none" costs one
attribute check per site.

Beyond spans, the tracer carries counter series (`count()`, Chrome
"C" events: queue depths, batch sizes, repair counts) and instant
markers (`instant()`).  The buffer is bounded: oldest spans drop first
and the drop total is reported in the dump, so a long-running server
can leave tracing on.

A measured site is a STAGE (`Stage` + `Tracer.stage()`): one context
manager that, from one pair of clock reads, feeds the stage's
`<name>_us` histogram in its owner's registry, the JSON span (backend
"json"), and — for a leaf — an annotation `tb.<name>` through the
sink the server injects (`Tracer.annotate`; jax.profiler's
TraceAnnotation when the engine is the device one, so that the stage
shares the device trace's clock; this module imports no JAX).  Leaf
stages tile a thread's time: a leaf that opens inside another leaf
suspends the outer one until it closes, so no microsecond is counted
twice and the sums of the leaves inside a span never pass the span.
Enclosing stages (`leaf=False`: commit, checkpoint) keep histogram and
JSON span and emit no annotation.
"""

from __future__ import annotations

import collections
import json
import threading
import time

BUFFER_MAX = 200_000  # events kept before oldest-first dropping


class Stage:
    """One measured site: its histogram, its name (the scrape key
    less `_us`, registry prefix included), and whether it is a leaf.
    Made once by the site's owner; opened through `Tracer.stage()`."""

    __slots__ = ("hist", "name", "label", "tid", "timed")

    def __init__(self, hist, name: str, leaf: bool = True,
                 tid: int = 0) -> None:
        self.hist = hist
        self.name = name
        self.label = "tb." + name if leaf else None
        # Row of the JSON trace: 0 is the loop's thread, a stage that
        # runs on a worker names another.
        self.tid = tid
        # TB_METRICS=0 hands out the no-op histogram: no clock read.
        self.timed = hist.live


class Tracer:
    def __init__(self, backend: str = "none", process_id: int = 0,
                 clock=time.perf_counter_ns,
                 buffer_max: int = BUFFER_MAX) -> None:
        assert backend in ("none", "json")
        self.backend = backend
        self.enabled = backend != "none"
        self.process_id = process_id
        self.clock = clock
        self.buffer_max = buffer_max
        # Optional obs.flight.FlightRecorder sink: instants (and span
        # ends) are mirrored into its bounded ring EVEN when the
        # backend is "none" — the flight recorder is the always-on
        # postmortem buffer, the backend the opt-in full trace.
        self.flight = None
        # Annotation sink for leaf stages: a callable name -> context
        # manager, injected by the process that has JAX (runtime/
        # server.py sets jax.profiler.TraceAnnotation); None = off.
        self.annotate = None
        # Tests of a path whose leaves are meant never to nest set
        # this: a leaf that opens inside a leaf then asserts instead
        # of suspending the outer one.
        self.strict_leaves = False
        self._local = threading.local()   # .leaf: the thread's open leaf
        self._runs: dict[int, "_StageRun"] = {}   # open, backend json
        self._open: dict[tuple[str, int], tuple[int, dict | None]] = {}
        # deque(maxlen) drops oldest in O(1); a list shift per event
        # would make every traced hot-path op O(buffer_max) once full.
        self._spans: collections.deque[dict] = collections.deque(
            maxlen=buffer_max
        )
        self.dropped = 0

    # -- spans ---------------------------------------------------------

    def start(self, event: str, slot: int = 0, **args) -> None:
        """Open span `event` on `slot`.  One slot holds one open span
        of a given name — double-start asserts immediately (the
        reference's slot discipline); concurrent same-name spans use
        distinct slots (e.g. op number % k)."""
        if not self.enabled:
            return
        key = (event, slot)
        assert key not in self._open, f"span {event}[{slot}] already open"
        self._open[key] = (self.clock(), args or None)

    def stop(self, event: str, slot: int = 0) -> None:
        if not self.enabled:
            return
        key = (event, slot)
        # Unbalanced end asserts immediately (the reference's slot
        # discipline), instead of surfacing as a bare KeyError.
        assert key in self._open, f"span {event}[{slot}] not open"
        begin, args = self._open.pop(key)
        self._push(
            _span_event(event, self.process_id, slot, begin, self.clock(), args)
        )

    def span(self, event: str, slot: int = 0, **args):
        if not self.enabled:
            return _NOOP_SPAN
        return _Span(self, event, slot, args)

    def stage(self, stage: Stage, **args):
        """Open `stage` (a context manager; `as run` gives the run,
        for `run.split(n)` and `run.t0`).  With TB_METRICS=0, backend
        "none" and no sink it is the shared no-op: no clock read."""
        if stage.timed or self.enabled or (
            self.annotate is not None and stage.label is not None
        ):
            return _StageRun(self, stage, args)
        return _NOOP_SPAN

    def stamp(self, hist) -> int | None:
        """The clock now, for a wait that one site starts and another
        ends (`hist.observe((tracer.clock() - stamp) / 1e3)`); None
        where `hist` is the TB_METRICS=0 no-op."""
        return self.clock() if hist.live else None

    # -- counters + instants -------------------------------------------

    def count(self, series: str, value: float, **extra) -> None:
        """Counter sample (Chrome 'C' event): queue depth, batch size,
        repair totals — graphed as a time series by the viewer."""
        if not self.enabled:
            return
        values = {"value": value}
        values.update(extra)
        self._push(
            {
                "name": series, "ph": "C", "pid": self.process_id,
                "tid": 0, "ts": self.clock() / 1e3, "args": values,
            }
        )

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker (view change, crash recovery, …).
        Mirrored into the flight ring even with backend "none" — the
        postmortem buffer must not depend on full tracing being on."""
        if self.flight is not None:
            self.flight.note(name, **args)
        if not self.enabled:
            return
        ev = {
            "name": name, "ph": "i", "s": "p", "pid": self.process_id,
            "tid": 0, "ts": self.clock() / 1e3,
        }
        if args:
            ev["args"] = args
        self._push(ev)

    # -- output --------------------------------------------------------

    def _push(self, event: dict) -> None:
        if len(self._spans) == self.buffer_max:
            self.dropped += 1
        self._spans.append(event)

    def dump(self) -> str:
        """The trace so far.  Spans and stages still open (a dump from
        the SIGTERM handler interrupts the loop wherever it stands) are
        closed at now and marked `open_at_dump`; the tracer's state is
        left as it was."""
        events = list(self._spans)
        now = self.clock()
        for (name, slot), (begin, args) in list(self._open.items()):
            events.append(_span_event(
                name, self.process_id, slot, begin, now,
                {**(args or {}), "open_at_dump": True},
            ))
        for run in list(self._runs.values()):
            if run.t0 is not None:
                events.append(_span_event(
                    run.stage.name, self.process_id, run.stage.tid,
                    run.t0, now, {**run.args, "open_at_dump": True},
                ))
        return json.dumps(
            {
                "traceEvents": events,
                "otherData": {"dropped_events": self.dropped},
            }
        )

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.dump())

    @classmethod
    def from_env(cls, process_id: int = 0) -> "Tracer":
        """Backend from the TB_TRACE knob (envcheck-validated)."""
        from tigerbeetle_tpu import envcheck

        return cls(envcheck.trace_backend(), process_id=process_id)


def _span_event(name: str, pid: int, tid: int, begin: int, end: int,
                args: dict | None) -> dict:
    span = {
        "name": name, "ph": "X", "pid": pid, "tid": tid,
        "ts": begin / 1e3, "dur": (end - begin) / 1e3,
    }
    if args:
        span["args"] = args
    return span


class _StageRun:
    """One opening of a Stage.  `t0` is the clock at the last start or
    resume (None while suspended or untimed); `_ns` what earlier
    segments took."""

    __slots__ = ("tracer", "stage", "args", "t0", "_ns", "_n", "_ann",
                 "_outer")

    def __init__(self, tracer: Tracer, stage: Stage, args: dict) -> None:
        self.tracer = tracer
        self.stage = stage
        self.args = args
        self.t0 = None
        self._ns = 0
        self._n = 1
        self._ann = None
        self._outer = None

    def split(self, n: int) -> None:
        """The run produced `n` units (prepares of one drain): its
        histogram gets `n` samples of a share each, 0 adds the time to
        the sum alone."""
        self._n = n

    def __enter__(self):
        tracer = self.tracer
        if self.stage.label is not None:
            local = tracer._local
            outer = getattr(local, "leaf", None)
            if outer is not None:
                assert not tracer.strict_leaves, (
                    f"leaf {self.stage.name} opened inside leaf "
                    f"{outer.stage.name}"
                )
                outer._stop()
            self._outer = outer
            local.leaf = self
        if tracer.enabled:
            tracer._runs[id(self)] = self
        self._start()
        return self

    def __exit__(self, *exc):
        tracer = self.tracer
        self._stop()
        if self.stage.timed:
            self.stage.hist.observe_split(self._ns / 1e3, self._n)
        if tracer.enabled:
            tracer._runs.pop(id(self), None)
        if self.stage.label is not None:
            tracer._local.leaf = self._outer
            if self._outer is not None:
                self._outer._start()
        return False

    def _start(self) -> None:
        tracer, stage = self.tracer, self.stage
        if stage.label is not None and tracer.annotate is not None:
            self._ann = tracer.annotate(stage.label)
            self._ann.__enter__()
        if stage.timed or tracer.enabled:
            self.t0 = tracer.clock()

    def _stop(self) -> None:
        """End of a segment: the run's own end, or a leaf opening
        inside it.  One clock read serves histogram and JSON span."""
        tracer = self.tracer
        if self.t0 is not None:
            now = tracer.clock()
            self._ns += now - self.t0
            if tracer.enabled:
                tracer._push(_span_event(
                    self.stage.name, tracer.process_id, self.stage.tid,
                    self.t0, now, self.args or None,
                ))
            self.t0 = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


class _Span:
    __slots__ = ("_tracer", "_event", "_slot", "_args")

    def __init__(self, tracer: Tracer, event: str, slot: int, args: dict):
        self._tracer = tracer
        self._event = event
        self._slot = slot
        self._args = args

    def __enter__(self):
        self._tracer.start(self._event, self._slot, **self._args)

    def __exit__(self, *exc):
        self._tracer.stop(self._event, self._slot)
        return False


class _NoopSpan:
    __slots__ = ()
    t0 = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def split(self, n: int) -> None:
        pass


# One shared no-op context manager: disabled-tracer spans on the hot
# path cost an attribute check and this constant return.
_NOOP_SPAN = _NoopSpan()

# Shared no-op instance for call sites whose owner never enabled
# tracing (enabled=False short-circuits every method).
NULL = Tracer("none")
