"""The stage primitive (`utils/tracer.py` Stage / Tracer.stage) and the
leaf stages it puts over the path of a prepare.

One site, read three ways: the stage's `<name>_us` histogram, the JSON
span, and a `tb.<name>` annotation through the injected sink — from one
pair of clock reads.  Leaves tile a thread's time: one that opens
inside another suspends it, and `strict_leaves` turns that into an
assertion for the paths whose leaves are meant never to nest.
"""

import ast
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
from tigerbeetle_tpu.utils.tracer import _NOOP_SPAN, Stage, Tracer

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
    assert tracer.stage(stage) is _NOOP_SPAN
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
# The stages in the program.


def stage_names() -> dict[str, bool]:
    """name -> leaf, of every `Stage(...)` the package makes."""
    found = {}
    for root, _dirs, files in os.walk(PKG):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(root, f)).read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and getattr(
                        node.func, "attr", getattr(node.func, "id", "")) == "Stage"):
                    continue
                name = next((a.value for a in node.args[1:2]
                             if isinstance(a, ast.Constant)), None)
                if name is None:
                    continue
                leaf = not any(k.arg == "leaf" and k.value.value is False
                               for k in node.keywords)
                found[name] = found.get(name, False) or leaf
    return found


LEAVES = {
    "server.poll_wait", "server.ingress", "vsr.admit", "vsr.prepare",
    "vsr.journal.write", "vsr.gc.sync", "vsr.commit.prefetch", "sm.plan",
    "sm.plan.join_cold", "sm.dev.scrub.cost", "sm.dev.launch", "sm.dev.dispatch",
    "sm.dev.commit.update", "sm.dev.link.fetch_wait",
    "sm.dev.link.fetch_copy", "sm.dev.finish", "vsr.commit.reply",
    "vsr.commit.beat", "vsr.reply_send", "vsr.tick", "vsr.ckpt.freeze",
    "vsr.ckpt.finalize", "vsr.journal.sync", "vsr.replicate.send",
    "vsr.backup.accept", "lsm.beat.work",
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
    stages = stage_names()
    assert {n for n, leaf in stages.items() if leaf} == LEAVES
    assert {n for n, leaf in stages.items() if not leaf} == {"vsr.commit"}
    src = open(os.path.join(PKG, "utils", "tracer.py")).read()
    assert "EVENTS" not in src


def test_perf_md_has_a_row_for_every_stage():
    """PERF.md section 3's stage table is held to the code: every
    stage has its row under its scrape key, and no row names a stage
    the code has not."""
    text = open(os.path.join(_REPO, "PERF.md")).read()
    table = text[text.index("### Stages"):]
    table = table[:table.index("\n## ")]
    rows = set(re.findall(r"^\| `([a-z_.]+)_us` \|", table, re.M))
    assert rows == set(stage_names())


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
        "vsr.ckpt.freeze", "sm.plan.join_cold"}
    assert snap["sm.plan.join_cold_us.count"] == 0
    assert snap["sm.store.join_cold_rows"] == 0
    assert BEAT_KEYS | COMPACT_KEYS | POSTED_KEYS <= set(snap)
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
    r.close()
    storage.close()

    commits = vsr["commit_us.count"]
    assert vsr["commit.beat_us.count"] == commits >= 7
    beats = lsm["beat.work_us.count"]
    assert beats == 4                   # commits 2 to 5: the tail past 16,384
    assert lsm["beat.bound_waits"] == waits and lsm["barrier.joins"] >= 1
    # The commit's leaves (host engine: prefetch, reply, beat) tile it.
    inside = sum(vsr[k + "_us.sum"] for k in (
        "commit.prefetch", "commit.reply", "commit.beat"))
    assert 0 < vsr["commit.beat_us.sum"] < inside <= vsr["commit_us.sum"]
    # Annotated on the worker's thread, and only there.
    where = {t for name, t in on_thread if name == "tb.lsm.beat.work"}
    assert where == {"lsm-beat"}
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
        if metrics == "0":
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
