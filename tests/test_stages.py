"""The stage primitive (`utils/tracer.py` Stage / Tracer.stage) and the
leaf stages it puts over the path of a prepare.

One site, read three ways: the stage's `<name>_us` histogram, the JSON
span, and a `tb.<name>` annotation through the injected sink — from one
pair of clock reads.  Leaves tile a thread's time: one that opens
inside another suspends it, and `strict_leaves` turns that into an
assertion for the paths whose leaves are meant never to nest.  A part
is a piece of the leaf open around it: the leaf's clock runs on, its
annotation gives way to the part's, and parts tile the leaf as leaves
tile the thread.
"""

import ast
import gc
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

from tigerbeetle_tpu.obs.registry import _NOOP_HIST, Registry
from tigerbeetle_tpu.utils.tracer import NOOP_RUN, Stage, Tracer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(_REPO, "tigerbeetle_tpu")


class Clock:
    """Counts its reads; every read is 1,000 ns after the last."""

    def __init__(self) -> None:
        self.reads = 0

    def __call__(self) -> int:
        self.reads += 1
        return self.reads * 1000


class Sink:
    """Stands in for jax.profiler.TraceAnnotation."""

    def __init__(self) -> None:
        self.events: list = []

    def __call__(self, name: str):
        sink = self

        class _Ann:
            def __enter__(self):
                sink.events.append(("enter", name))

            def __exit__(self, *exc):
                sink.events.append(("exit", name))

        return _Ann()


def make(backend="json", enabled=True):
    clock = Clock()
    tracer = Tracer(backend, clock=clock)
    tracer.annotate = sink = Sink()
    return tracer, Registry(enabled=enabled), clock, sink


def test_one_pair_of_clock_reads_feeds_histogram_span_and_sink():
    tracer, reg, clock, sink = make()
    stage = Stage(reg.histogram("commit.reply_us"), "vsr.commit.reply")
    with tracer.stage(stage, op=7):
        pass
    assert clock.reads == 2
    hist = reg.histogram("commit.reply_us")
    assert hist.count == 1 and hist.total == 1.0          # 1,000 ns
    (span,) = json.loads(tracer.dump())["traceEvents"]     # dump reads once
    assert span["name"] == "vsr.commit.reply" and span["dur"] == 1.0
    assert span["ts"] == 1.0 and span["args"] == {"op": 7}
    assert sink.events == [("enter", "tb.vsr.commit.reply"),
                           ("exit", "tb.vsr.commit.reply")]


def test_no_clock_read_with_metrics_off_and_backend_none():
    clock = Clock()
    tracer = Tracer("none", clock=clock)
    reg = Registry(enabled=False)
    stage = Stage(reg.histogram("plan_us"), "sm.plan")
    assert stage.hist is _NOOP_HIST and not stage.timed
    assert tracer.stage(stage) is NOOP_RUN
    with tracer.stage(stage) as run:
        run.split(3)
        assert run.t0 is None
    assert tracer.stamp(stage.hist) is None
    assert clock.reads == 0
    # With the sink on, the annotation is made (the profiler has its
    # own clock) and still none of ours is read.
    tracer.annotate = sink = Sink()
    with tracer.stage(stage):
        pass
    assert clock.reads == 0 and len(sink.events) == 2


def test_an_enclosing_stage_keeps_histogram_and_span_and_emits_no_annotation():
    tracer, reg, _clock, sink = make()
    commit = Stage(reg.histogram("commit_us"), "vsr.commit", leaf=False)
    leaf = Stage(reg.histogram("plan_us"), "sm.plan")
    assert commit.label is None and leaf.label == "tb.sm.plan"
    with tracer.stage(commit):
        with tracer.stage(leaf):
            pass
    assert [name for _what, name in sink.events] == ["tb.sm.plan"] * 2
    names = [e["name"] for e in json.loads(tracer.dump())["traceEvents"]]
    assert names == ["sm.plan", "vsr.commit"]
    assert reg.histogram("commit_us").count == 1


def test_a_leaf_inside_a_leaf_suspends_the_outer_one():
    """No microsecond is counted twice: the sums of the leaves are the
    wall time, and the annotations never overlap."""
    tracer, reg, clock, sink = make()
    outer = Stage(reg.histogram("plan_us"), "sm.plan")
    inner = Stage(reg.histogram("dev.finish_us"), "sm.dev.finish")
    with tracer.stage(outer):          # t=1
        with tracer.stage(inner):      # outer stops at 2, inner runs 3..4
            pass
    # outer resumed at 5, stopped at 6.
    assert clock.reads == 6
    assert reg.histogram("plan_us").count == 1
    assert reg.histogram("plan_us").total == 2.0           # (2-1) + (6-5) us
    assert reg.histogram("dev.finish_us").total == 1.0
    assert sink.events == [
        ("enter", "tb.sm.plan"), ("exit", "tb.sm.plan"),
        ("enter", "tb.sm.dev.finish"), ("exit", "tb.sm.dev.finish"),
        ("enter", "tb.sm.plan"), ("exit", "tb.sm.plan"),
    ]
    spans = json.loads(tracer.dump())["traceEvents"]
    assert [(s["name"], s["ts"], s["dur"]) for s in spans] == [
        ("sm.plan", 1.0, 1.0), ("sm.dev.finish", 3.0, 1.0), ("sm.plan", 5.0, 1.0)]


def test_strict_leaves_asserts_when_a_leaf_opens_inside_a_leaf():
    tracer, reg, _clock, _sink = make()
    tracer.strict_leaves = True
    outer = Stage(reg.histogram("a_us"), "a")
    inner = Stage(reg.histogram("b_us"), "b")
    enclosing = Stage(reg.histogram("c_us"), "c", leaf=False)
    with tracer.stage(enclosing), tracer.stage(outer):
        with tracer.stage(enclosing):       # an enclosing stage may
            pass
        with pytest.raises(AssertionError, match="leaf b opened inside leaf a"):
            with tracer.stage(inner):
                pass


def test_leaves_of_another_thread_do_not_suspend_the_loops():
    tracer, reg, _clock, _sink = make()
    tracer.strict_leaves = True
    loop = Stage(reg.histogram("gc.sync_us"), "vsr.gc.sync")
    worker = Stage(reg.histogram("journal.sync_us"), "vsr.journal.sync", tid=1)
    with tracer.stage(loop):
        th = threading.Thread(target=lambda: tracer.stage(worker).__enter__())
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    assert reg.histogram("gc.sync_us").count == 1


@pytest.mark.parametrize("n,count,total", [(1, 1, 1.0), (4, 4, 1.0), (0, 0, 1.0)])
def test_split_shares_a_run_among_what_it_produced(n, count, total):
    tracer, reg, _clock, _sink = make()
    stage = Stage(reg.histogram("prepare_us", unit_scale=16), "vsr.prepare")
    with tracer.stage(stage) as run:
        run.split(n)
    hist = reg.histogram("prepare_us", unit_scale=16)
    assert hist.count == count and hist.total == pytest.approx(total)
    assert reg.snapshot()["prepare_us.sum"] == pytest.approx(total)


def test_dump_closes_stages_the_signal_found_open_and_marks_them():
    tracer, reg, _clock, _sink = make()
    commit = Stage(reg.histogram("commit_us"), "vsr.commit", leaf=False)
    leaf = Stage(reg.histogram("plan_us"), "sm.plan")
    with tracer.stage(commit, op=3):
        with tracer.stage(leaf):
            doc = json.loads(tracer.dump())
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["vsr.commit"]["args"] == {"op": 3, "open_at_dump": True}
    assert by_name["sm.plan"]["args"] == {"open_at_dump": True}
    assert all(e["dur"] > 0 for e in doc["traceEvents"])
    # The run went on and closed as it should: once each, unmarked.
    spans = json.loads(tracer.dump())["traceEvents"]
    assert sorted(e["name"] for e in spans) == ["sm.plan", "vsr.commit"]
    assert not any("open_at_dump" in e.get("args", {}) for e in spans)


def test_stamp_is_the_primitives_clock():
    tracer, reg, clock, _sink = make()
    assert tracer.stamp(reg.histogram("request_wait_us")) == 1000
    assert clock.reads == 1


# ----------------------------------------------------------------------
# Parts.


def parts_of(reg, leaf="plan", parts=("plan.decode", "plan.pack")):
    return (Stage(reg.histogram(leaf + "_us"), "sm." + leaf),
            *(Stage(reg.histogram(p + "_us"), "sm." + p, part=True)
              for p in parts))


def test_a_part_keeps_its_leafs_clock_and_closes_its_annotation():
    """The leaf's histogram and JSON span stay inclusive; on the
    profiler's host line the leaf gives way to the part and comes
    back, so the line still tiles and a gap takes the part's name."""
    tracer, reg, clock, sink = make()
    leaf, decode, _pack = parts_of(reg)
    with tracer.stage(leaf):            # 1
        with tracer.stage(decode):      # 2..3: no read of the leaf's
            pass
    assert clock.reads == 4             # the leaf ends at 4
    assert reg.histogram("plan_us").total == 3.0          # 4 - 1: inclusive
    assert reg.histogram("plan.decode_us").total == 1.0
    assert reg.histogram("plan_us").count == 1
    assert sink.events == [
        ("enter", "tb.sm.plan"), ("exit", "tb.sm.plan"),
        ("enter", "tb.sm.plan.decode"), ("exit", "tb.sm.plan.decode"),
        ("enter", "tb.sm.plan"), ("exit", "tb.sm.plan"),
    ]
    spans = json.loads(tracer.dump())["traceEvents"]
    assert [(e["name"], e["ts"], e["dur"]) for e in spans] == [
        ("sm.plan.decode", 2.0, 1.0), ("sm.plan", 1.0, 3.0)]


def test_parts_tile_inside_a_leaf_and_never_sum_past_it():
    """A part inside a part suspends the outer one, clock and
    annotation, as leaves do to each other; a leaf that opens inside a
    part suspends part and leaf together.  Whatever nests, the parts'
    sums stay within their leaf's and no two annotations overlap."""
    tracer, reg, clock, sink = make()
    leaf, pending, cold = parts_of(reg, parts=("plan.pending", "plan.join_cold"))
    inner = Stage(reg.histogram("dev.launch_us"), "sm.dev.launch")
    with tracer.stage(leaf):                    # leaf from 1
        with tracer.stage(pending):             # pending from 2
            with tracer.stage(cold):            # pending stops 3; cold 4..5
                pass
            with tracer.stage(inner):           # pending resumed 6, stops 7;
                pass                            # leaf stops 8; inner 9..10
        # leaf resumed 11, pending resumed 12 and stops 13; leaf ends 14.
    assert clock.reads == 14
    h = {k: reg.histogram(k + "_us").total for k in (
        "plan", "plan.pending", "plan.join_cold", "dev.launch")}
    assert h == {"plan": (8 - 1) + (14 - 11), "plan.pending": 1 + 1 + 1,
                 "plan.join_cold": 1.0, "dev.launch": 1.0}
    assert h["plan.pending"] + h["plan.join_cold"] <= h["plan"]
    assert all(reg.histogram(k + "_us").count == 1 for k in h)
    # The host line: enters and exits alternate, never two open.
    assert [what for what, _name in sink.events] == ["enter", "exit"] * (
        len(sink.events) // 2)
    assert [name for what, name in sink.events if what == "enter"] == [
        "tb.sm.plan", "tb.sm.plan.pending", "tb.sm.plan.join_cold",
        "tb.sm.plan.pending", "tb.sm.dev.launch", "tb.sm.plan.pending",
        "tb.sm.plan"]


def test_a_run_moves_on_from_part_to_part_and_each_gets_one_sample():
    """`run.switch`: block after block a merge reads, merges, writes;
    the run opens once, and each part it visited gets its total."""
    tracer, reg, clock, sink = make()
    leaf, read, merge, write = parts_of(
        reg, "beat", ("compact.read", "compact.merge", "compact.write"))
    with tracer.stage(leaf):
        with tracer.stage(merge) as run:
            for _block in range(3):
                run.switch(read)
                run.switch(merge)
                run.switch(merge)       # where it stands: nothing
                run.switch(write)
                run.switch(merge)
    for key, total in (("compact.read", 3.0), ("compact.write", 3.0),
                       ("compact.merge", 7.0)):
        hist = reg.histogram(key + "_us")
        assert (hist.count, hist.total) == (1, total), key
    assert reg.histogram("compact.read_us").total + reg.histogram(
        "compact.write_us").total + reg.histogram(
        "compact.merge_us").total <= reg.histogram("beat_us").total
    names = [name for what, name in sink.events if what == "enter"]
    assert names[1:5] == ["tb.sm.compact.merge", "tb.sm.compact.read",
                          "tb.sm.compact.merge", "tb.sm.compact.write"]
    assert NOOP_RUN.switch(read) is None


def test_a_run_hands_stretches_to_other_parts_and_keeps_its_annotation():
    """`run.add(stage, run.mark())`: a merge's reads and writes, block
    after block, come off the merge's own time on two clock reads each;
    the annotation stays the merge's, every part gets one sample."""
    tracer, reg, clock, sink = make()
    leaf, read, merge, write = parts_of(
        reg, "beat", ("compact.read", "compact.merge", "compact.write"))
    with tracer.stage(leaf):                    # 1
        with tracer.stage(merge) as run:        # 2
            for _block in range(3):
                since = run.mark()              # 3, 7, 11
                run.add(read, since)            # 4, 8, 12
                since = run.mark()              # 5, 9, 13
                run.add(write, since)           # 6, 10, 14
        # the merge ends at 15, the leaf at 16
    assert clock.reads == 16
    totals = {k: (reg.histogram(k + "_us").count, reg.histogram(k + "_us").total)
              for k in ("compact.read", "compact.write", "compact.merge", "beat")}
    assert totals == {"compact.read": (1, 3.0), "compact.write": (1, 3.0),
                      "compact.merge": (1, 13.0 - 6.0), "beat": (1, 15.0)}
    assert [name for what, name in sink.events if what == "enter"] == [
        "tb.sm.beat", "tb.sm.compact.merge", "tb.sm.beat"]
    spans = [e["name"] for e in json.loads(tracer.dump())["traceEvents"]]
    assert spans == ["sm.compact.merge", "sm.beat"]
    # Where no clock is read there is nothing to hand over.
    assert NOOP_RUN.mark() is None and NOOP_RUN.add(read, None) is None
    off = Tracer("none")
    off.annotate = Sink()
    _leaf, _read, quiet, _write = parts_of(
        Registry(enabled=False), "beat",
        ("compact.read", "compact.merge", "compact.write"))
    with off.stage(_leaf), off.stage(quiet) as run:
        assert run.mark() is None
        run.add(_read, run.mark())


def test_a_part_with_no_leaf_open_measures_nothing_and_asserts_when_strict():
    """Code shared with paths that run under no leaf (the host
    engine's, a lookup's) opens its parts there too: not measured.
    Where the leaves are meant to be there, `strict_leaves` says so."""
    tracer, reg, clock, sink = make()
    _leaf, decode, pack = parts_of(reg)
    assert tracer.stage(decode) is NOOP_RUN
    with tracer.stage(decode) as run:
        run.switch(pack)
    assert clock.reads == 0 and sink.events == []
    assert reg.histogram("plan.decode_us").count == 0
    tracer.strict_leaves = True
    with pytest.raises(AssertionError, match="part sm.plan.decode opened with no leaf"):
        tracer.stage(decode)


def test_strict_leaves_asserts_when_a_part_opens_inside_a_part():
    tracer, reg, _clock, _sink = make()
    tracer.strict_leaves = True
    leaf, decode, pack = parts_of(reg)
    with tracer.stage(leaf), tracer.stage(decode):
        with pytest.raises(AssertionError,
                           match="part sm.plan.pack opened inside part sm.plan.decode"):
            with tracer.stage(pack):
                pass
    with tracer.stage(leaf):            # one after the other they may
        with tracer.stage(decode):
            pass
        with tracer.stage(pack):
            pass
    assert reg.histogram("plan.pack_us").count == 1


def test_a_part_on_a_workers_thread_finds_the_workers_leaf():
    """The spill and the seals are the same parts in the commit's beat
    and on the beat worker: a part belongs to the leaf open on ITS
    thread, and leaves the other thread's alone."""
    tracer, reg, _clock, _sink = make()
    tracer.strict_leaves = True
    loop = Stage(reg.histogram("commit.beat_us"), "vsr.commit.beat")
    work = Stage(reg.histogram("beat.work_us"), "lsm.beat.work", tid=3)
    seal = Stage(reg.histogram("seal.encode_us"), "lsm.seal.encode", part=True)
    found = []

    def worker():
        with tracer.stage(work) as leaf:
            with tracer.stage(seal) as part:
                found.append((part._leaf is leaf, leaf._part is part))
            found.append(leaf._part is None)

    with tracer.stage(loop) as mine:
        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=10)
        assert mine._part is None
    assert found == [(True, True), True]
    assert reg.histogram("seal.encode_us").count == 1


def test_a_part_with_metrics_off_reads_no_clock():
    clock = Clock()
    tracer = Tracer("none", clock=clock)
    reg = Registry(enabled=False)
    leaf, decode, pack = parts_of(reg)
    with tracer.stage(leaf):
        assert tracer.stage(decode) is NOOP_RUN
        with tracer.stage(decode) as run:
            run.switch(pack)
    assert clock.reads == 0
    # With the sink on the annotations are made, and still no clock.
    tracer.annotate = sink = Sink()
    with tracer.stage(leaf):
        with tracer.stage(decode) as run:
            run.switch(pack)
    assert clock.reads == 0
    assert [name for what, name in sink.events if what == "enter"] == [
        "tb.sm.plan", "tb.sm.plan.decode", "tb.sm.plan.pack", "tb.sm.plan"]


def test_a_part_costs_under_three_microseconds():
    """With no sink (`--trace 0`): two clock reads and one observe.
    The best of five rounds, so that a busy machine cannot fail it."""
    tracer, reg = Tracer("none"), Registry(enabled=True)
    leaf, decode, pack = parts_of(reg)
    n, best, best_switch, best_add = 20_000, 1.0, 1.0, 1.0
    with tracer.stage(leaf):
        for _round in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with tracer.stage(decode):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
            with tracer.stage(decode) as run:
                t0 = time.perf_counter()
                for _ in range(n // 2):
                    run.switch(pack)
                    run.switch(decode)
                best_switch = min(best_switch, (time.perf_counter() - t0) / n)
                t0 = time.perf_counter()
                for _ in range(n):
                    run.add(pack, run.mark())
                best_add = min(best_add, (time.perf_counter() - t0) / n)
    assert best < 3e-6, f"{best * 1e9:.0f} ns a part"
    assert best_switch < 3e-6, f"{best_switch * 1e9:.0f} ns a switch"
    assert best_add < 3e-6, f"{best_add * 1e9:.0f} ns a stretch handed over"
    print(f"part {best * 1e9:.0f} ns, switch {best_switch * 1e9:.0f} ns, "
          f"add {best_add * 1e9:.0f} ns")


def test_the_collectors_pauses_are_counted_on_the_servers_registry():
    """obs/process.py: one `gc.callbacks` hook; a forced collection
    moves gen2 and the pause histogram by one; a pause over 50 ms
    leaves an instant for the flight ring; closed, it counts no more;
    with TB_METRICS=0 it is never hooked."""
    from tigerbeetle_tpu.obs import process
    from tigerbeetle_tpu.obs.flight import FlightRecorder

    tracer, reg = Tracer("none"), Registry(enabled=True)
    tracer.flight = flight = FlightRecorder(8)
    hooks = len(gc.callbacks)
    watch = process.ProcessWatch(reg, tracer)
    assert len(gc.callbacks) == hooks + 1
    before = reg.snapshot()
    gc.collect()
    after = reg.snapshot()
    assert after["server.gc.collections.gen2"] == before[
        "server.gc.collections.gen2"] + 1
    assert after["server.gc.pause_us.count"] == before[
        "server.gc.pause_us.count"] + 1
    assert after["server.gc.pause_us.sum"] > before["server.gc.pause_us.sum"]
    assert {"server.gc.collections.gen0", "server.gc.collections.gen1"} <= set(after)
    assert after["server.cpu_us"] > 0 and after["server.minflt"] > 0
    assert after["server.majflt"] >= 0 and after["server.nivcsw"] >= 0
    # A long pause (the clock says 60 ms) is noted, a short one (10 ms)
    # is not.  (The forced collection above may be either: a process
    # that has imported JAX holds a million objects.)
    noted = len(flight.events())
    ticks = iter([0, 10_000_000, 20_000_000, 80_000_000])
    slow = process.ProcessWatch(Registry(enabled=True), tracer,
                                clock=lambda: next(ticks))
    for _pause in range(2):
        slow._on_gc("start", {"generation": 2})
        slow._on_gc("stop", {"generation": 2})
    slow.close()
    (note,) = flight.events()[noted:]
    assert note["name"] == "gc_pause" and note["args"] == {
        "generation": 2, "us": 60_000}
    watch.close()
    watch.close()                       # idempotent
    assert len(gc.callbacks) == hooks
    gc.collect()
    assert reg.snapshot()["server.gc.collections.gen2"] == after[
        "server.gc.collections.gen2"]
    off = process.ProcessWatch(Registry(enabled=False), tracer)
    assert len(gc.callbacks) == hooks
    off.close()


# ----------------------------------------------------------------------
# The stages in the program.


def stage_kinds() -> dict[str, str]:
    """name -> "leaf", "part" or "enclosing", of every `Stage(...)` the
    package makes; a part is made with `part=True`, or by an owner's
    helper `part("<name>")`."""
    found = {}
    for root, _dirs, files in os.walk(PKG):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(root, f)).read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = getattr(node.func, "attr", getattr(node.func, "id", ""))
                at = {"Stage": 1, "part": 0}.get(func)
                if at is None:
                    continue
                name = next((a.value for a in node.args[at:at + 1]
                             if isinstance(a, ast.Constant)), None)
                if name is None:
                    continue

                def says(key, value):
                    return any(k.arg == key and k.value.value is value
                               for k in node.keywords)

                kind = ("part" if func == "part" or says("part", True)
                        else "enclosing" if says("leaf", False) else "leaf")
                # (vsr.journal.sync is made twice: a leaf on the WAL
                # worker, enclosed on the loop's thread.)
                had = found.setdefault(name, kind)
                assert "part" not in (had, kind) or had == kind, name
                if kind == "leaf":
                    found[name] = kind
    return found


def stage_names() -> dict[str, bool]:
    """name -> leaf, of the leaves and the enclosing stages."""
    return {n: k == "leaf" for n, k in stage_kinds().items() if k != "part"}


LEAVES = {
    "server.poll_wait", "server.ingress", "vsr.admit", "vsr.prepare",
    "vsr.journal.write", "vsr.gc.sync", "vsr.commit.prefetch", "sm.plan",
    "sm.dev.scrub.cost", "sm.dev.launch", "sm.dev.dispatch",
    "sm.dev.commit.update", "sm.dev.link.fetch_wait",
    "sm.dev.link.fetch_copy", "sm.dev.finish", "vsr.commit.reply",
    "vsr.commit.beat", "vsr.reply_send", "vsr.tick", "vsr.ckpt.freeze",
    "vsr.ckpt.finalize", "vsr.journal.sync", "vsr.replicate.send",
    "vsr.backup.accept", "lsm.beat.work",
}
# The parts, by the leaf whose question they answer (the LSM's open in
# the commit's beat, on the beat worker and in a checkpoint's freeze).
PLAN_PARTS = {"sm.plan." + p for p in (
    "decode", "ids", "id_dir", "accounts", "route", "pending", "pack",
    "join_cold")}
FINISH_PARTS = {"sm.finish." + p for p in (
    "codes", "mirror", "twin", "store", "ids", "native_ids", "status", "reply")}
LSM_PARTS = {"sm.spill.take", "sm.spill.objects", "sm.spill.index",
             "lsm.seal.concat", "lsm.seal.encode", "lsm.compact.read",
             "lsm.compact.merge", "lsm.compact.write"}
FREEZE_PARTS = {"sm.ckpt.drain", "sm.ckpt.verify_device",
                "sm.ckpt.verify_host", "sm.ckpt.encode",
                "vsr.ckpt.freeze.wrap", "vsr.ckpt.freeze.root",
                "vsr.ckpt.freeze.write", "vsr.ckpt.freeze.checksum"}
PARTS = PLAN_PARTS | FINISH_PARTS | LSM_PARTS | FREEZE_PARTS
# Counters at the parts' boundaries, and the process's own pauses
# (obs/process.py) on a server's registry.
PART_COUNTERS = {"lsm.seal.bytes", "lsm.compact.blocks_read",
                 "lsm.compact.blocks_written",
                 "sm.ids.runs_filed", "sm.ids.hashed", "sm.ids.runs"}
PROCESS_KEYS = {
    "server.gc.pause_us.count", "server.gc.pause_us.sum",
    "server.gc.pause_us.max", "server.gc.collections.gen0",
    "server.gc.collections.gen1", "server.gc.collections.gen2",
    "server.cpu_us", "server.minflt", "server.majflt", "server.nivcsw",
}
# What runs on a worker's thread: annotated there, out of the loop's
# sums (`server_loop_attributed_pct`, `commit_attributed_pct`).
WORKER_LEAVES = {"vsr.ckpt.finalize", "vsr.journal.sync", "lsm.beat.work"}
# The beat worker's instruments (lsm/beats.py), "lsm." on the scrape.
BEAT_KEYS = {
    "lsm.beat.work_us.count", "lsm.beat.work_us.sum", "lsm.beat.bound_waits",
    "lsm.beat.bound_wait_us.count", "lsm.beat.bound_wait_us.sum",
    "lsm.barrier.joins", "lsm.barrier.wait_us.sum", "lsm.beat.queued",
}
# What compaction and point reads did, over the forest's trees
# (lsm/tree.py TreeStats): six counters and a gauge.
COMPACT_KEYS = {
    "lsm.compact.jobs", "lsm.compact.moves", "lsm.compact.entries_in",
    "lsm.compact.entries_out", "lsm.tree.runs_peak",
    "lsm.lookup.runs_consulted", "lsm.lookup.runs_skipped",
}
# The posted groove (state_machine/spill.py): statuses written to it,
# rows a read asked it about, and of them found.
POSTED_KEYS = {
    "sm.store.status_overwrites", "sm.store.posted_lookups",
    "sm.store.posted_hits",
}
# What only a cluster's replicas open: the primary's hand-over of a
# prepare to the backups' connections, a backup's run of prepares.
REPLICATION_LEAVES = {"vsr.replicate.send", "vsr.backup.accept"}


def test_the_stage_names_live_once_in_code():
    kinds = stage_kinds()
    assert {n for n, k in kinds.items() if k == "leaf"} == LEAVES
    assert {n for n, k in kinds.items() if k == "enclosing"} == {"vsr.commit"}
    assert {n for n, k in kinds.items() if k == "part"} == PARTS
    src = open(os.path.join(PKG, "utils", "tracer.py")).read()
    assert "EVENTS" not in src
    # One system: the slot spans and the counter series are gone.
    for gone in ("def span", "def start", "def stop", "def count", "_Span"):
        assert gone not in src, gone
    used = [
        os.path.join(root, f)
        for root, _dirs, files in os.walk(PKG) for f in files
        if f.endswith(".py") and re.search(
            r"tracer\.(span|start|stop|count)\(", open(os.path.join(root, f)).read())]
    assert used == []


def test_perf_md_has_a_row_for_every_stage():
    """PERF.md section 3's stage table is held to the code: every
    stage has its row under its scrape key, and no row names a stage
    the code has not."""
    text = open(os.path.join(_REPO, "PERF.md")).read()
    table = text[text.index("### Stages"):]
    table = table[:table.index("\n### Parts")]
    rows = set(re.findall(r"^\| `([a-z_.]+)_us` \|", table, re.M))
    assert rows == set(stage_names())


def _parts_table() -> list[list[str]]:
    text = open(os.path.join(_REPO, "PERF.md")).read()
    table = text[text.index("### Parts"):]
    table = table[:table.index("\n## ")]
    return [[cell.strip() for cell in line.strip("|").split("|")]
            for line in table.splitlines() if line.startswith("| `")]


def test_perf_md_has_a_row_for_every_part_counter_and_gauge():
    """PERF.md section 3's parts table is held to the code too: a row a
    part under its scrape key, its leaves ones the code has, and in the
    last column the metric file that reads that key; then the counters
    at the parts' boundaries and the process's own keys."""
    rows = _parts_table()
    keys_of = [re.findall(r"`([a-z_.0-9]+)`", row[0]) for row in rows]
    keys = [k for ks in keys_of for k in ks]
    assert len(keys) == len(set(keys))
    process = {k.removesuffix(".count") for k in PROCESS_KEYS
               if not k.endswith((".sum", ".max"))}
    assert set(keys) == {p + "_us" for p in PARTS} | PART_COUNTERS | process
    for row, row_keys in zip(rows, keys_of):
        is_part = row_keys[0][:-3] in PARTS
        leaves = re.findall(r"`([a-z_.]+)`", row[2])
        assert set(leaves) <= LEAVES and bool(leaves) == is_part, row_keys
        files = re.findall(r"`([a-z_0-9]+)`", row[3])
        assert len(files) == 1 or not is_part, row_keys
        for name in files:
            spec = json.load(open(os.path.join(
                _REPO, "benchmarks", "layer_metrics", name + ".json")))
            assert any(read == k or read.startswith(k + ".")
                       for read in spec["keys"] for k in row_keys), (row_keys, name)


@pytest.mark.parametrize("sub", ["vsr", "lsm", "utils", "obs"])
def test_no_jax_below_the_state_machine(sub):
    for root, _dirs, files in os.walk(os.path.join(PKG, sub)):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(root, f)).read())
            for node in ast.walk(tree):
                names = []
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    names = [node.module or ""]
                assert not any(n == "jax" or n.startswith("jax.") for n in names), \
                    os.path.join(root, f)


def _device_server(tmp_path, trace_path=None):
    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu.runtime.server import ReplicaServer, format_data_file
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    path = str(tmp_path / "0_0.tigerbeetle")
    format_data_file(path, cluster=1, config=cfg.TEST_MIN)
    return ReplicaServer(
        path, cluster=1, addresses=["127.0.0.1:0"], replica_index=0,
        state_machine_factory=lambda: TpuStateMachine(
            cfg.TEST_MIN, engine="device", account_capacity=1 << 10,
            transfer_capacity=1 << 12),
        config=cfg.TEST_MIN, trace_path=trace_path,
    )


COMMIT_LEAVES = (
    "vsr.commit.prefetch", "sm.plan", "sm.dev.scrub.cost", "sm.dev.launch",
    "sm.dev.dispatch", "sm.dev.commit.update", "sm.dev.link.fetch_wait",
    "sm.dev.link.fetch_copy", "sm.dev.finish", "vsr.commit.reply",
    "vsr.commit.beat")


def test_on_the_plain_served_path_leaves_tile_the_loop_and_fill_the_commit(
        tmp_path):
    """The benchmark's path (plain create_transfers through a served
    device-engine replica).  No two leaf spans of the loop's thread
    overlap, which is what lets `trace_reduce.label_gaps` name a gap by
    a leaf; inside the commit span no leaf was ever suspended (they do
    not nest there at all); and they account for most of the span."""
    from tigerbeetle_tpu.client import Client

    server = _device_server(tmp_path, str(tmp_path / "trace.json"))
    assert server.tracer.annotate is not None     # the engine is the device one
    # Inside the commit: no leaf inside a leaf, no part inside a part,
    # no part without its leaf (with join_cold a part, one rule covers
    # all below a leaf).  Outside it the loop's leaves do nest.
    commit = server.replica._commit_prepare

    def strict_commit(*args, **kwargs):
        server.tracer.strict_leaves = True
        try:
            return commit(*args, **kwargs)
        finally:
            server.tracer.strict_leaves = False

    server.replica._commit_prepare = strict_commit
    stop, failed = [], []

    def loop():
        try:
            while not stop:
                server.poll_once(1)
        except BaseException as exc:  # noqa: BLE001 — reported below
            failed.append(exc)
            raise

    thread = threading.Thread(target=loop, daemon=True)
    thread.start()
    c = Client(f"127.0.0.1:{server.port}", 1, client_id=11, timeout_ms=60_000)
    assert c.create_accounts(
        [{"id": i, "ledger": 1, "code": 1} for i in (1, 2, 3)]) == []
    for at in range(12):
        assert c.create_transfers([
            {"id": 100 + at * 4 + j, "debit_account_id": 1 + j % 3,
             "credit_account_id": 1 + (j + 1) % 3, "amount": 5,
             "ledger": 1, "code": 1} for j in range(4)]) == []
    c.close()
    stop.append(1)
    thread.join(timeout=30)
    assert not thread.is_alive() and not failed, failed
    snap = server.registry.snapshot()
    server.close()
    # (12 small requests: the tail never reaches the 16,384 rows a
    # spill waits for, so no beat is handed to the worker and no join
    # meets a row that has left the tail.)
    on_the_path = LEAVES - REPLICATION_LEAVES - WORKER_LEAVES - {
        "vsr.ckpt.freeze"}
    assert snap["sm.plan.join_cold_us.count"] == 0
    assert snap["sm.store.join_cold_rows"] == 0
    assert BEAT_KEYS | COMPACT_KEYS | POSTED_KEYS <= set(snap)
    # Every part, counter and gauge of PR 37 is on the scrape, opened
    # or not; what this path opens are the plan's parts of a plain
    # batch and the finish's, once a prepare.
    assert {p + "_us.count" for p in PARTS} | PART_COUNTERS | PROCESS_KEYS <= set(snap)
    opened = {p for p in PARTS if snap[p + "_us.count"]}
    assert opened == (PLAN_PARTS - {"sm.plan.pending", "sm.plan.join_cold"}
                      ) | FINISH_PARTS
    for p in opened:
        assert snap[p + "_us.count"] == 12, p
    for leaf, parts in (("sm.plan", PLAN_PARTS), ("sm.dev.finish", FINISH_PARTS)):
        inside = sum(snap[p + "_us.sum"] for p in parts)
        assert 0.7 * snap[leaf + "_us.sum"] <= inside <= snap[leaf + "_us.sum"], leaf
    assert snap["server.gc.collections.gen0"] + snap[
        "server.gc.collections.gen1"] + snap["server.gc.collections.gen2"] == snap[
        "server.gc.pause_us.count"]
    assert 0 < snap["server.cpu_us"]
    assert not any(snap[key] for key in COMPACT_KEYS | POSTED_KEYS)   # nothing sealed or cold
    assert snap["lsm.beat.work_us.count"] == snap["lsm.beat.queued"] == 0
    for name in REPLICATION_LEAVES:
        assert snap[name + "_us.count"] == 0, name
    assert snap["vsr.quorum_wait_us.count"] == 0      # a quorum of one
    # The forest's blocks, as the data file's storage limit gives them.
    assert snap["vsr.grid.blocks_total"] == 8189
    assert 0 <= snap["vsr.grid.blocks_acquired"] <= snap[
        "vsr.grid.blocks_acquired_peak"] < 8189
    for name in sorted(on_the_path):
        assert snap[name + "_us.count"] > 0, name
    assert snap["sm.dev.compile.count"] >= 0 and snap["server.uptime_us"] > 0
    assert snap["vsr.requests_committed"] >= snap["vsr.commits"] >= 13
    assert snap["vsr.request_wait_us.count"] >= 13
    inside = sum(snap[k + "_us.sum"] for k in COMMIT_LEAVES)
    assert 0.8 * snap["vsr.commit_us.sum"] < inside <= snap["vsr.commit_us.sum"]
    # A prepare crosses the link once each way: its one fetch has a
    # sample in both leaves and brings home its own 512-byte row; up go
    # the packed buffer and the digest's two arrays.
    fetched = snap["sm.dev.fetches"]
    assert fetched == 12
    assert snap["sm.dev.link.fetch_wait_us.count"] == fetched
    assert snap["sm.dev.link.fetch_copy_us.count"] == fetched
    assert snap["sm.dev.link.fetch_bytes"] == 512 * fetched
    assert snap["sm.dev.link.fetch_start_us.count"] == fetched
    assert 3 * fetched <= snap["sm.dev.link.puts"] < 4 * fetched + 8

    doc = json.load(open(tmp_path / "trace.json"))
    # A part's span lies inside a span of its leaf (the JSON trace
    # keeps the leaf whole; the profiler's line has it give way).
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X" and e["tid"] == 0]
    for leaf, parts in (("sm.plan", PLAN_PARTS), ("sm.dev.finish", FINISH_PARTS)):
        around = [(e["ts"], e["ts"] + e["dur"]) for e in xs if e["name"] == leaf]
        for e in xs:
            if e["name"] in parts:
                assert any(a - 0.002 <= e["ts"] and e["ts"] + e["dur"] <= b + 0.002
                           for a, b in around), e
    leaves = stage_names()
    # (vsr.journal.sync is a leaf on the WAL worker only: on the loop's
    # thread the covering sync's leaf encloses it.)
    spans = sorted((e for e in doc["traceEvents"] if e.get("ph") == "X"
                    and e["tid"] == 0 and leaves.get(e["name"])
                    and e["name"] != "vsr.journal.sync"),
                   key=lambda e: e["ts"])
    assert on_the_path <= {e["name"] for e in spans}
    assert "vsr.commit" in {e["name"] for e in doc["traceEvents"]}
    for a, b in zip(spans, spans[1:]):
        # (timestamps are us with three decimals: allow their rounding)
        assert a["ts"] + a["dur"] <= b["ts"] + 0.002, (a, b)
    for name in COMMIT_LEAVES:
        segments = sum(1 for e in spans if e["name"] == name)
        assert segments == snap[name + "_us.count"], name


def test_the_beat_stage_is_the_hand_over_and_the_work_is_the_workers(tmp_path):
    """Full batches over FileStorage with the beat worker: every commit
    hands a beat to the `lsm-beat` thread.  `vsr.commit.beat` stays a leaf of the commit on
    the loop's thread (it tiles the span with the others, as
    `commit_attributed_pct` sums them) and measures the hand-over;
    `lsm.beat.work` runs on the worker's thread, annotated there, on
    its own row of the trace, and lies outside the commit span: a beat
    held on the worker does not hold the commit that handed it over."""
    import numpy as np

    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
    from tigerbeetle_tpu.testing.harness import account, pack
    from tigerbeetle_tpu.vsr import replica as vsr_replica
    from tigerbeetle_tpu.vsr.storage import FileStorage, ZoneLayout

    storage = FileStorage(str(tmp_path / "0_0.tigerbeetle"),
                          ZoneLayout(config=cfg.PRODUCTION), create=True)
    vsr_replica.format(storage, 30)
    r = vsr_replica.Replica(storage, 30, TpuStateMachine(
        cfg.PRODUCTION, account_capacity=1 << 10, transfer_capacity=1 << 16))
    # (A lone replica keeps its beats in place; a cluster's gets the
    # worker.  Given by hand here.)
    from tigerbeetle_tpu.lsm.beats import BeatWorker

    r.forest.beats = BeatWorker(r.forest.metrics, threaded=True)
    r.open()
    tracer = Tracer("json")
    tracer.strict_leaves = True          # per thread: the worker's leaf is its own
    on_thread = []

    class ThreadSink(Sink):
        def __call__(self, name):
            on_thread.append((name, threading.current_thread().name))
            return super().__call__(name)

    tracer.annotate = ThreadSink()
    r.set_tracer(tracer)
    Op = types.Operation
    assert r.on_request(int(Op.create_accounts), pack(
        [account(i) for i in (1, 2, 3)])) == b""

    def commit(op):
        rows = np.zeros(8190, types.TRANSFER_DTYPE)
        rows["id_lo"] = np.arange(1 + op * 8190, 1 + (op + 1) * 8190)
        rows["debit_account_id_lo"] = 1 + rows["id_lo"] % 3
        rows["credit_account_id_lo"] = 1 + (rows["id_lo"] + 1) % 3
        rows["amount_lo"] = rows["ledger"] = rows["code"] = 1
        assert r.on_request(int(Op.create_transfers), rows.tobytes()) == b""

    for op in range(5):
        commit(op)
    r.forest.barrier()
    # One more, its beat held on the worker while the commit returns.
    work, entered, release = r._beat_work, threading.Event(), threading.Event()
    r._beat_work = lambda *a: (entered.set(), release.wait(30), work(*a))
    waits = r.forest.metrics.snapshot()["beat.bound_waits"]
    commit(5)
    # The commit is back and its beat is still held: by order, no clock.
    assert entered.wait(30) and not release.is_set()
    release.set()
    r.forest.barrier()
    vsr, lsm = r.metrics.snapshot(), r.forest.metrics.snapshot()
    sm = r.sm.metrics.snapshot()
    # A checkpoint: its freeze spills the tail and seals every tree,
    # through the same parts, on the loop's thread now.
    r.checkpoint()
    vsr2, lsm2, sm2 = (r.metrics.snapshot(), r.forest.metrics.snapshot(),
                       r.sm.metrics.snapshot())
    r.close()
    storage.close()

    def total(snaps, parts, prefix):
        return sum(snap[p[len(prefix):] + "_us.sum"] for snap in snaps
                   for p in parts if p.startswith(prefix))

    lsm_parts = LSM_PARTS - {"sm.spill.take"}
    # The worker's leaf: objects, index entries, seals, compaction.
    work = total([lsm], lsm_parts, "lsm.") + total([sm], lsm_parts, "sm.")
    assert 0.7 * lsm["beat.work_us.sum"] <= work <= lsm["beat.work_us.sum"]
    assert sm["spill.objects_us.count"] == sm["spill.index_us.count"] == 4
    # (Four beats of 8,190 rows stay under the 32,768 a seal waits for.)
    assert lsm["seal.bytes"] == lsm["seal.encode_us.count"] == 0
    # The hand-over's one part is the copy of the rows out of the tail.
    assert sm["spill.take_us.count"] == 4
    assert 0 < sm["spill.take_us.sum"] <= vsr["commit.beat_us.sum"]
    # The freeze: the LSM's parts again, the state machine's (a host
    # engine drains and encodes; it verifies only where asked) and the
    # replica's own.
    assert vsr2["ckpt.freeze_us.count"] == 1
    freeze = (
        total([lsm2], lsm_parts, "lsm.") - total([lsm], lsm_parts, "lsm.")
        + total([sm2], lsm_parts | FREEZE_PARTS, "sm.")
        - total([sm], lsm_parts | FREEZE_PARTS, "sm.")
        + total([vsr2], FREEZE_PARTS, "vsr."))
    assert 0.7 * vsr2["ckpt.freeze_us.sum"] <= freeze <= vsr2["ckpt.freeze_us.sum"]
    for key in ("wrap", "root", "write", "checksum"):
        assert vsr2[f"ckpt.freeze.{key}_us.count"] == 1, key
    assert sm2["ckpt.drain_us.count"] == sm2["ckpt.encode_us.count"] == 1
    assert lsm2["seal.encode_us.count"] == lsm2["seal.concat_us.count"] >= 3
    assert lsm2["seal.bytes"] > 32_760 * 100

    commits = vsr["commit_us.count"]
    assert vsr["commit.beat_us.count"] == commits >= 7
    beats = lsm["beat.work_us.count"]
    assert beats == 4                   # commits 2 to 5: the tail past 16,384
    assert lsm["beat.bound_waits"] == waits and lsm["barrier.joins"] >= 1
    # The commit's leaves (host engine: prefetch, reply, beat) tile it.
    inside = sum(vsr[k + "_us.sum"] for k in (
        "commit.prefetch", "commit.reply", "commit.beat"))
    assert 0 < vsr["commit.beat_us.sum"] < inside <= vsr["commit_us.sum"]
    # Annotated on the worker's thread, and only there; its parts too;
    # the freeze's seals run on the loop's.
    where = {t for name, t in on_thread if name == "tb.lsm.beat.work"}
    assert where == {"lsm-beat"}
    assert {t for name, t in on_thread if name == "tb.sm.spill.objects"} == {
        "lsm-beat"}
    assert {t for name, t in on_thread if name == "tb.lsm.seal.encode"} == {
        threading.current_thread().name}
    assert {t for name, t in on_thread if name == "tb.sm.spill.take"} == {
        threading.current_thread().name}
    assert {t for name, t in on_thread if name == "tb.vsr.commit.beat"} == {
        threading.current_thread().name}
    # Its own row in the JSON trace; the loop's leaves still never overlap.
    events = [e for e in json.loads(tracer.dump())["traceEvents"] if e.get("ph") == "X"]
    assert {e["tid"] for e in events if e["name"] == "lsm.beat.work"} == {3}
    assert len([e for e in events if e["name"] == "lsm.beat.work"]) == beats
    loop = sorted((e for e in events if e["tid"] == 0 and e["name"] in LEAVES),
                  key=lambda e: e["ts"])
    assert {"vsr.commit.beat", "vsr.commit.reply"} <= {e["name"] for e in loop}
    for a, b in zip(loop, loop[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 0.002, (a, b)


@pytest.mark.parametrize("metrics", ["1", "0"])
def test_three_served_replicas_feed_the_replication_stages(
        tmp_path, monkeypatch, metrics):
    """`vsr.replicate.send` on the primary, `vsr.backup.accept` on the
    backups, `vsr.quorum_wait_us` a sample a prepare on the primary:
    in the scrape's registry, in the `start --trace` file, and (the two
    leaves) through the annotation sink.  With TB_METRICS=0 no
    histogram is fed and no quorum clock is read; the spans stay."""
    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.runtime.server import ReplicaServer, format_data_file
    from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine

    monkeypatch.setenv("TB_METRICS", metrics)
    servers, sinks = [], []
    addresses = ["127.0.0.1:0"] * 3
    for i in range(3):
        path = str(tmp_path / f"r{i}.tigerbeetle")
        format_data_file(path, cluster=5, replica_index=i, replica_count=3,
                         config=cfg.TEST_MIN)
        s = ReplicaServer(
            path, cluster=5, addresses=list(addresses), replica_index=i,
            state_machine_factory=lambda: CpuStateMachine(cfg.TEST_MIN),
            config=cfg.TEST_MIN, trace_path=str(tmp_path / f"trace{i}.json"))
        addresses[i] = f"127.0.0.1:{s.port}"
        s.tracer.annotate = sink = Sink()
        servers.append(s)
        sinks.append(sink)
    for s in servers:
        s.bus.addresses = list(addresses)
    stop = []

    def loop():
        while not stop:
            for s in servers:
                s.poll_once(timeout_ms=1)

    thread = threading.Thread(target=loop, daemon=True)
    thread.start()
    c = Client(",".join(addresses), 5, client_id=12, timeout_ms=60_000)
    assert c.create_accounts(
        [{"id": i, "ledger": 1, "code": 1} for i in (1, 2)]) == []
    for at in range(10):
        assert c.create_transfers([
            {"id": 50 + at, "debit_account_id": 1, "credit_account_id": 2,
             "amount": 1, "ledger": 1, "code": 1}]) == []
    c.close()
    deadline = time.time() + 20
    while time.time() < deadline and len(
            {s.replica.commit_min for s in servers}) > 1:
        time.sleep(0.05)
    stop.append(1)
    thread.join(timeout=30)
    snaps = [s.registry.snapshot() for s in servers]
    pipeline_left = [e.written_at for s in servers
                     for e in s.replica.pipeline.values()]
    lead = next(i for i, s in enumerate(servers) if s.replica.is_primary)
    prepared = servers[lead].replica.op
    assert prepared >= 12
    for s in servers:
        s.close()
    for i, snap in enumerate(snaps):
        # The process's gauges and the collector's counters are on every
        # server's scrape, the pause histogram where histograms are fed.
        # (A CpuStateMachine has no forest and no parts.)
        assert {"server.cpu_us", "server.minflt", "server.majflt",
                "server.nivcsw", "server.gc.collections.gen2"} <= set(snap)
        assert (PROCESS_KEYS <= set(snap)) == (metrics == "1")
        assert not any(snap.get(p + "_us.count") for p in PARTS)
        if metrics == "0":
            assert snap["server.gc.collections.gen0"] == 0      # never hooked
            assert not any(k.startswith(("vsr.replicate.send_us", "vsr.quorum_wait_us",
                                         "vsr.backup.accept_us")) and v
                           for k, v in snap.items())
            continue
        primary = i == lead
        assert (snap["vsr.replicate.send_us.count"] >= prepared - 1) == primary
        assert (snap["vsr.quorum_wait_us.count"] >= prepared - 1) == primary
        assert (snap["vsr.backup.accept_us.count"] >= prepared - 1) == (not primary)
        if primary:
            # A quorum waits at least for what it takes to hand the
            # prepare over.
            assert snap["vsr.quorum_wait_us.sum"] > snap["vsr.replicate.send_us.sum"] > 0
    assert all(x is None for x in pipeline_left) or metrics == "1"
    for i in range(3):
        names = {e["name"] for e in json.load(
            open(tmp_path / f"trace{i}.json"))["traceEvents"]}
        labels = {name for _what, name in sinks[i].events}
        mine = "vsr.replicate.send" if i == lead else "vsr.backup.accept"
        other = (REPLICATION_LEAVES - {mine}).pop()
        assert mine in names and "tb." + mine in labels
        assert other not in names and "tb." + other not in labels
        assert "vsr.quorum_wait" not in names       # a histogram, not a span


def test_sigterm_on_a_served_start_with_trace_leaves_a_loadable_file(tmp_path):
    """`start --trace=<path>` killed by SIGTERM: the one handler writes
    the flight record and the trace, then the process dies of the
    signal as before."""
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.obs.scrape import scrape_stats

    data = str(tmp_path / "0_0.tigerbeetle")
    trace = str(tmp_path / "trace.json")
    env = dict(os.environ, PYTHONPATH=_REPO, PYTHONUNBUFFERED="1",
               TB_FLIGHT_PATH=str(tmp_path / "flight.json"))
    subprocess.run([sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster=3",
                    "--replica=0", "--replica-count=1", data],
                   check=True, env=env, cwd=_REPO, capture_output=True, timeout=120)
    log = open(tmp_path / "server.log", "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tigerbeetle_tpu", "start", "--addresses=127.0.0.1:0",
         "--replica=0", "--cpu", f"--trace={trace}", data],
        stdout=log, stderr=subprocess.STDOUT, env=env, cwd=_REPO)
    try:
        port = None
        deadline = time.monotonic() + 120
        while port is None and time.monotonic() < deadline:
            assert proc.poll() is None, open(tmp_path / "server.log").read()[-2000:]
            m = re.search(r"listening on port (\d+)",
                          open(tmp_path / "server.log").read())
            port = int(m.group(1)) if m else None
            time.sleep(0.05)
        assert port is not None
        c = Client(f"127.0.0.1:{port}", 3, client_id=5, timeout_ms=60_000)
        assert c.create_accounts(
            [{"id": 1, "ledger": 1, "code": 1}, {"id": 2, "ledger": 1, "code": 1}]) == []
        assert c.create_transfers([{"id": 9, "debit_account_id": 1,
                                    "credit_account_id": 2, "amount": 1,
                                    "ledger": 1, "code": 1}]) == []
        c.close()
        assert scrape_stats(f"127.0.0.1:{port}", 3)["vsr.commit_us.count"] >= 3
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log.close()
    assert rc in (0, -signal.SIGTERM), rc
    doc = json.load(open(trace))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"vsr.commit", "vsr.journal.write", "vsr.commit.reply",
            "server.poll_wait"} <= names
    # The signal found the loop in its poll: that stage is closed at
    # the dump and says so.
    assert any(e.get("args", {}).get("open_at_dump") for e in doc["traceEvents"])
    assert os.path.exists(tmp_path / "flight.json")
