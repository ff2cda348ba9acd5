"""Tracer: stages, instants, and the trace they leave.

reference: src/tracer.zig:1-70 — events are emitted to a backend
selected at init: `none` (no-op, zero overhead) or `json` (Chrome
trace-event format, loadable in chrome://tracing / Perfetto — the
tracy backend analog for this build).

A measured site is a STAGE (`Stage` + `Tracer.stage()`): one context
manager that, from one pair of clock reads, feeds the stage's
`<name>_us` histogram in its owner's registry, the JSON span (backend
"json"), and an annotation `tb.<name>` through the sink the server
injects (`Tracer.annotate`; jax.profiler's TraceAnnotation when the
engine is the device one, so that the stage shares the device trace's
clock; this module imports no JAX).  A stage is one of three things:

A LEAF (the default) tiles its thread's time.  A leaf that opens
inside another leaf suspends the outer one, clock and annotation,
until it closes, so no microsecond is counted twice and the sums of
the leaves inside a span never pass the span.  `strict_leaves` turns
that nesting into an assertion on the paths where none is meant.

A PART (`part=True`) is a piece of whichever leaf is open on its
thread: the spill and the seals are the same parts inside
`vsr.commit.beat`, `lsm.beat.work` and `vsr.ckpt.freeze`.  The leaf's
clock keeps running, so its histogram and JSON span stay inclusive;
its annotation is closed while a part is open and reopened after it,
so the profiler's host line still tiles and a gap takes the part's
name.  Parts tile their leaf as leaves tile a thread: a part inside a
part suspends the outer one, and a leaf that opens inside a part
suspends part and leaf together.  So a leaf's parts never sum past
it, and the leaf's SELF time is its sum less its parts' sums.  A run
that alternates between parts (a merge's read, merge, write, block
after block) opens once: `run.switch(stage)` moves it on, clock and
annotation, to the next part (a plan's few steps); `run.add(stage,
run.mark())` hands a stretch of the run to another part on two clock
reads and leaves the annotation alone (a block's read inside a merge).
Either way each part gets one sample a run, its total.  With no leaf
open a part measures nothing (code shared with paths that run under
no leaf: the host engine's, a lookup's), and asserts under
`strict_leaves`.

An ENCLOSING stage (`leaf=False`: `vsr.commit`) keeps histogram and
JSON span, emits no annotation and suspends nothing: the leaves inside
it account for it (`commit_attributed_pct`).

Beyond stages the tracer carries instant markers (`instant()`: view
changes, demotions, a collector pause), mirrored into the flight ring.
The buffer is bounded: oldest events drop first and the drop total is
reported in the dump, so a long-running server can leave tracing on.
Backend "none" with TB_METRICS=0 and no sink costs one check per site.
"""

from __future__ import annotations

import collections
import json
import threading
import time

BUFFER_MAX = 200_000  # events kept before oldest-first dropping


class Stage:
    """One measured site: its histogram, its name (the scrape key
    less `_us`, registry prefix included), and what it is: a leaf, a
    part of the leaf open around it, or an enclosing stage.  Made once
    by the site's owner; opened through `Tracer.stage()`."""

    __slots__ = ("hist", "name", "label", "tid", "timed", "part")

    def __init__(self, hist, name: str, leaf: bool = True,
                 tid: int = 0, part: bool = False) -> None:
        assert leaf or not part, "an enclosing stage is no part"
        self.hist = hist
        self.name = name
        self.label = "tb." + name if leaf else None
        self.part = part
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
        # Optional obs.flight.FlightRecorder sink: instants are
        # mirrored into its bounded ring EVEN when the backend is
        # "none" — the flight recorder is the always-on postmortem
        # buffer, the backend the opt-in full trace.
        self.flight = None
        # Annotation sink for leaves and parts: a callable name ->
        # context manager, injected by the process that has JAX
        # (runtime/server.py sets jax.profiler.TraceAnnotation); None
        # = off.
        self.annotate = None
        # Tests of a path whose leaves are meant never to nest set
        # this: a leaf that opens inside a leaf, a part inside a part,
        # or a part with no leaf around it then asserts.
        self.strict_leaves = False
        self._local = threading.local()   # .leaf: the thread's open leaf
        self._runs: dict[int, "_StageRun"] = {}   # open, backend json
        # deque(maxlen) drops oldest in O(1); a list shift per event
        # would make every traced hot-path op O(buffer_max) once full.
        self._spans: collections.deque[dict] = collections.deque(
            maxlen=buffer_max
        )
        self.dropped = 0

    # -- stages --------------------------------------------------------

    def stage(self, stage: Stage, **args):
        """Open `stage` (a context manager; `as run` gives the run,
        for `run.split(n)`, `run.switch(stage)`, `run.add(stage,
        run.mark())` and `run.t0`).  With TB_METRICS=0, backend "none"
        and no sink it is the shared no-op: no clock read.  So is a
        part with no leaf open."""
        if stage.timed or self.enabled or (
            self.annotate is not None and stage.label is not None
        ):
            if not stage.part:
                return _StageRun(self, stage, args)
            if getattr(self._local, "leaf", None) is not None:
                return _PartRun(self, stage, args)
            assert not self.strict_leaves, (
                f"part {stage.name} opened with no leaf open"
            )
        return NOOP_RUN

    def stamp(self, hist) -> int | None:
        """The clock now, for a wait that one site starts and another
        ends (`hist.observe((tracer.clock() - stamp) / 1e3)`); None
        where `hist` is the TB_METRICS=0 no-op."""
        return self.clock() if hist.live else None

    # -- instants ------------------------------------------------------

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
        """The trace so far.  Stages still open (a dump from the
        SIGTERM handler interrupts the loop wherever it stands) are
        closed at now and marked `open_at_dump`; the tracer's state is
        left as it was."""
        events = list(self._spans)
        now = self.clock()
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
    """One opening of a leaf or an enclosing Stage.  `t0` is the clock
    at the last start or resume (None while suspended or untimed);
    `_ns` what earlier segments took; `_part` the part open inside it
    (a leaf's alone)."""

    __slots__ = ("tracer", "stage", "args", "t0", "_ns", "_n", "_ann",
                 "_outer", "_part", "_sums")

    def __init__(self, tracer: Tracer, stage: Stage, args: dict) -> None:
        self.tracer = tracer
        self.stage = stage
        self.args = args
        self.t0 = None
        self._ns = 0
        self._n = 1
        self._ann = None
        self._outer = None
        self._part = None
        self._sums = None

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
                outer._suspend()
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
                self._outer._resume()
        return False

    def _start(self) -> None:
        self._annotate()
        if self.stage.timed or self.tracer.enabled:
            self.t0 = self.tracer.clock()

    def _stop(self) -> None:
        """End of a segment: the run's own end, a leaf opening inside
        it, a part moving on.  One clock read serves histogram and
        JSON span."""
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
        self._unannotate()

    def _annotate(self) -> None:
        tracer, stage = self.tracer, self.stage
        if stage.label is not None and tracer.annotate is not None:
            self._ann = tracer.annotate(stage.label)
            self._ann.__enter__()

    def _unannotate(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def _suspend(self) -> None:
        """A leaf opens inside this one: it stops, and with it the part
        it has open."""
        if self._part is not None:
            self._part._stop()
        self._stop()

    def _resume(self) -> None:
        if self._part is None:
            self._start()
            return
        if self.stage.timed or self.tracer.enabled:
            self.t0 = self.tracer.clock()   # the annotation is the part's
        self._part._start()


class _PartRun(_StageRun):
    """One opening of a part, inside the leaf open on its thread: the
    leaf's clock runs on, its annotation gives way to the part's."""

    __slots__ = ("_leaf",)

    def switch(self, stage: Stage) -> None:
        """The run goes on as `stage`, another part of the same leaf:
        ONE clock read ends the one and starts the other.  Each part
        the run visited gets one sample when it ends: the run's total
        for it."""
        if stage is self.stage:
            return
        tracer = self.tracer
        ns = self._ns
        t0 = self.t0
        if t0 is not None:
            self.t0 = now = tracer.clock()
            ns += now - t0
            if tracer.enabled:
                tracer._push(_span_event(
                    self.stage.name, tracer.process_id, self.stage.tid,
                    t0, now, self.args or None,
                ))
        sums = self._sums
        if sums is None:
            sums = self._sums = {}
        sums[self.stage] = ns
        self.stage = stage
        self._ns = sums.pop(stage, 0)
        if tracer.annotate is not None:
            self._unannotate()
            self._annotate()

    def mark(self) -> int | None:
        """The clock now, for `add`; None where the run reads none."""
        return self.tracer.clock() if self.t0 is not None else None

    def add(self, stage: Stage, since: int | None) -> None:
        """What ran since `since` (a `mark()`) was `stage`'s and not
        the run's own: two clock reads a stretch, no annotation, no
        span; `stage` gets one sample when the run ends."""
        if since is None:
            return
        ns = self.tracer.clock() - since
        if self._sums is None:
            self._sums = {}
        self._sums[stage] = self._sums.get(stage, 0) + ns
        self._ns -= ns

    def __enter__(self):
        tracer = self.tracer
        self._leaf = leaf = tracer._local.leaf
        outer = leaf._part
        if outer is None:
            leaf._unannotate()
        else:
            assert not tracer.strict_leaves, (
                f"part {self.stage.name} opened inside part "
                f"{outer.stage.name}"
            )
            outer._stop()
        self._outer = outer
        leaf._part = self
        if tracer.enabled:
            tracer._runs[id(self)] = self
        self._start()
        return self

    def __exit__(self, *exc):
        tracer = self.tracer
        self._stop()
        if self._sums is not None:
            for stage, ns in self._sums.items():
                if stage.timed:
                    stage.hist.observe(ns / 1e3)
        if self.stage.timed:
            self.stage.hist.observe(self._ns / 1e3)
        if tracer.enabled:
            tracer._runs.pop(id(self), None)
        leaf, outer = self._leaf, self._outer
        leaf._part = outer
        if outer is None:
            leaf._annotate()
        else:
            outer._start()
        return False


class _NoopRun:
    __slots__ = ()
    t0 = None
    stage = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def split(self, n: int) -> None:
        pass

    def switch(self, stage: Stage) -> None:
        pass

    def mark(self) -> None:
        return None

    def add(self, stage: Stage, since: None) -> None:
        pass


# One shared no-op run: an untimed stage on the hot path costs an
# attribute check and this constant return.  Also what code shared
# with paths that open no run takes as its default (`part=NOOP_RUN`).
NOOP_RUN = _NoopRun()

# Shared no-op instance for call sites whose owner never enabled
# tracing (enabled=False short-circuits every method).
NULL = Tracer("none")
