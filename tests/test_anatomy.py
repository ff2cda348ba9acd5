"""Request anatomy: wire trace context, per-request stage timelines,
tail exemplars, and the flight recorder (obs/anatomy.py, obs/flight.py,
vsr/wire.py trace fields)."""

import json

import pytest

from tigerbeetle_tpu import obs, types
from tigerbeetle_tpu.obs.anatomy import (
    AnatomyRecorder,
    exemplar_trace_events,
)
from tigerbeetle_tpu.obs.flight import FlightRecorder
from tigerbeetle_tpu.utils.tracer import Stage, Tracer
from tigerbeetle_tpu.vsr import wire

# ----------------------------------------------------------------------
# Wire trace context.


def test_trace_context_header_roundtrip():
    h = wire.make_header(
        command=wire.Command.request, operation=130, cluster=7,
        client=99, request=3, trace_id=0xDEAD, trace_ts=123_456,
        trace_flags=wire.TRACE_SAMPLED,
    )
    wire.finalize_header(h, b"ab")
    assert wire.verify_header(h, b"ab")
    back = wire.header_from_bytes(h.tobytes())
    assert int(back["trace_id"]) == 0xDEAD
    assert int(back["trace_ts"]) == 123_456
    assert wire.trace_sampled(back) == 0xDEAD


def test_trace_context_copy_propagates():
    req = wire.make_header(
        command=wire.Command.request, trace_id=5, trace_ts=9,
        trace_flags=wire.TRACE_SAMPLED,
    )
    prep = wire.make_header(command=wire.Command.prepare, op=4)
    wire.copy_trace(prep, req)
    wire.finalize_header(prep, b"")
    assert wire.verify_header(prep, b"")
    assert wire.trace_sampled(prep) == 5
    assert int(prep["trace_ts"]) == 9


def test_unsampled_and_zero_id_are_untraced():
    h = wire.make_header(command=wire.Command.request, trace_id=7)
    assert wire.trace_sampled(h) == 0  # flag clear
    h2 = wire.make_header(
        command=wire.Command.request, trace_flags=wire.TRACE_SAMPLED
    )
    assert wire.trace_sampled(h2) == 0  # id zero


def test_untraced_header_is_bit_identical_to_legacy():
    # Zero trace fields leave the header bytes exactly as the
    # all-reserved layout produced them (wire compat).
    h = wire.make_header(command=wire.Command.prepare, op=1)
    raw = h.tobytes()
    assert raw[156:173] == bytes(17)


# ----------------------------------------------------------------------
# AnatomyRecorder.


class _Clock:
    def __init__(self):
        self.now = 1_000_000

    def __call__(self):
        return self.now


def _recorder(ring=8, **kw):
    clock = _Clock()
    reg = obs.Registry(enabled=True)
    rec = AnatomyRecorder(
        reg.scope("anatomy"), exemplar_ring=ring, clock=clock, **kw
    )
    return rec, clock, reg


def test_stage_timeline_and_e2e():
    rec, clock, reg = _recorder()
    clock.now = 1000
    rec.stage(42, "ingress", origin_ts=400)
    clock.now = 2000
    rec.stage(42, "prepare")
    clock.now = 3400
    rec.finish(42, "reply")
    assert len(rec.exemplars) == 1
    ex = rec.exemplars[0]
    assert [s[0] for s in ex["stages"]] == ["ingress", "prepare", "reply"]
    assert ex["e2e_us"] == pytest.approx((3400 - 400) / 1e3)
    snap = reg.snapshot()
    assert snap["anatomy.finished"] == 1
    assert snap["anatomy.e2e_us.count"] == 1


def test_exemplars_keep_only_top_buckets_after_warmup():
    rec, clock, _ = _recorder(ring=64)

    def run(tid, e2e_ns):
        rec.stage(tid, "a", origin_ts=clock.now)
        clock.now += e2e_ns
        rec.finish(tid)

    # Mixed population: mostly fast, a 10% slow tail (interleaved).
    tid = 1
    for i in range(100):
        run(tid, 10_000_000 if i % 10 == 9 else 100_000)
        tid += 1
    # Past warmup: a FAST request is not exemplar-worthy...
    kept_before = len(rec.exemplars)
    run(tid, 100_000)
    tid += 1
    assert len(rec.exemplars) == kept_before
    # ...a tail request is.
    run(tid, 20_000_000)
    assert len(rec.exemplars) == kept_before + 1
    assert rec.exemplars[-1]["e2e_us"] == pytest.approx(20_000)


def test_exemplar_ring_is_bounded():
    rec, clock, _ = _recorder(ring=4)
    for tid in range(1, 40):
        rec.stage(tid, "a", origin_ts=clock.now)
        clock.now += 1_000_000 * tid  # ever slower: all exemplar-worthy
        rec.finish(tid)
    assert len(rec.exemplars) == 4
    assert len(rec.exemplar_snapshot()) == 4


def test_open_records_bounded_with_eviction_counter():
    clock = _Clock()
    reg = obs.Registry(enabled=True)
    rec = AnatomyRecorder(
        reg.scope("anatomy"), exemplar_ring=4, open_max=8, clock=clock
    )
    for tid in range(1, 30):
        rec.stage(tid, "a")  # never finished
    assert len(rec._open) == 8
    assert reg.snapshot()["anatomy.open_evicted"] == 30 - 1 - 8
    # The oldest were evicted; finishing one of them is a no-op.
    rec.finish(1)
    assert reg.snapshot()["anatomy.finished"] == 0


def test_disabled_registry_disables_recorder():
    reg = obs.Registry(enabled=False)
    rec = AnatomyRecorder(reg.scope("anatomy"), exemplar_ring=4)
    assert not rec.enabled
    rec.stage(1, "a")
    rec.finish(1, "reply")
    assert not rec._open and not rec.exemplars


def test_stage_many_shares_one_timestamp():
    rec, clock, _ = _recorder()
    rec.stage(1, "journal_write")
    rec.stage(2, "journal_write")
    clock.now += 777
    rec.stage_many([1, 2], "gc_covering_sync")
    assert rec._open[1]["stages"][-1] == ["gc_covering_sync", clock.now]
    assert rec._open[2]["stages"][-1] == ["gc_covering_sync", clock.now]


def test_exemplar_trace_events_render_stage_spans():
    rec, clock, _ = _recorder()
    clock.now = 10_000
    rec.stage(9, "prepare", origin_ts=9_000)
    clock.now = 12_000
    rec.stage(9, "journal_write")
    clock.now = 15_000
    rec.finish(9, "reply")
    events = exemplar_trace_events(rec.exemplar_snapshot(), pid=3)
    names = [e["name"] for e in events]
    assert "prepare" in names and "journal_write" in names
    assert "reply" in names
    assert all(e["pid"] == 3 for e in events)
    jw = next(e for e in events if e["name"] == "journal_write")
    assert jw["dur"] == pytest.approx(2.0)  # 12_000 - 10_000 ns = 2 µs


# ----------------------------------------------------------------------
# Flight recorder.


def test_flight_ring_bounded_and_dump_parseable(tmp_path):
    fl = FlightRecorder(16, process_id=2)
    for i in range(50):
        fl.note("tick", i=i)
    assert fl.dropped == 34
    path = str(tmp_path / "flight.json")
    fl.write(path, reason="unit")
    data = json.load(open(path))
    assert data["otherData"]["flight_recorder"] is True
    assert data["otherData"]["reason"] == "unit"
    assert data["otherData"]["dropped_events"] == 34
    assert len(data["traceEvents"]) == 16
    assert all(e["pid"] == 2 for e in data["traceEvents"])
    # Oldest dropped first.
    assert data["traceEvents"][0]["args"]["i"] == 34


def test_flight_trigger_event_auto_dumps(tmp_path):
    path = str(tmp_path / "flight.json")
    fl = FlightRecorder(32, dump_path=path)
    fl.note("commit", op=1)
    assert not (tmp_path / "flight.json").exists()
    fl.note("device_demoted", error="FatalLinkError")
    data = json.load(open(path))
    assert data["otherData"]["reason"] == "device_demoted"
    names = [e["name"] for e in data["traceEvents"]]
    assert names == ["commit", "device_demoted"]


def test_tracer_instants_mirror_into_flight_even_when_disabled(tmp_path):
    t = Tracer("none")
    fl = FlightRecorder(8)
    t.flight = fl
    t.instant("device_demoted", error="x")
    t.instant("view_change", view=3)
    assert [ev[1] for ev in fl._ring] == ["device_demoted", "view_change"]
    # Backend "none" still emitted nothing to the trace buffer itself.
    assert len(json.loads(t.dump())["traceEvents"]) == 0


def test_flight_dump_merges_into_perfetto_timeline(tmp_path):
    from tigerbeetle_tpu.testing.cluster import merge_traces

    fl = FlightRecorder(8, process_id=0)
    fl.note("shed", client=1)
    p1 = str(tmp_path / "flight0.json")
    fl.write(p1)
    t = Tracer("json", process_id=0)
    with t.stage(Stage(obs.Registry(enabled=False).histogram("commit_us"),
                       "commit", leaf=False), op=1):
        pass
    p2 = str(tmp_path / "trace0.json")
    t.write(p2)
    merged = merge_traces([p1, p2], labels=["flight", "trace"])
    names = {e["name"] for e in merged["traceEvents"]}
    assert {"shed", "commit"} <= names
    assert {e["pid"] for e in merged["traceEvents"]} == {0, 1}


# ----------------------------------------------------------------------
# End-to-end propagation through a deterministic 2-replica cluster.


def test_cluster_propagates_trace_context_end_to_end():
    """SimClient stamps a wire trace context; with group commit live
    the PRIMARY's exemplar timeline spans queued/prepare ->
    journal_write -> gc_covering_sync -> prepare_ok -> commit ->
    reply, and the BACKUP holds its own partial record for the same
    request (journal_write -> commit)."""
    from tigerbeetle_tpu.testing.cluster import Cluster
    from tigerbeetle_tpu.testing.harness import account, pack, transfer
    from tigerbeetle_tpu.vsr.storage import MemoryStorage

    had = MemoryStorage.supports_deferred_sync
    MemoryStorage.supports_deferred_sync = True
    try:
        cluster = Cluster(replica_count=2, seed=11)
        client = cluster.client(1000)
        client.register()
        cluster.run_until(lambda: client.registered)
        assert cluster.run_request(
            client, types.Operation.create_accounts,
            pack([account(1), account(2)]),
        ) == b""
        assert cluster.run_request(
            client, types.Operation.create_transfers,
            pack([transfer(100, debit_account_id=1, credit_account_id=2,
                           amount=1)]),
        ) == b""
        cluster.settle()
        primary = cluster.replicas[0]
        backup = cluster.replicas[1]
        prim_ex = primary.anatomy.exemplar_snapshot()
        assert prim_ex, "primary retained no exemplars"
        stage_sets = [{s[0] for s in ex["stages"]} for ex in prim_ex]
        assert any(
            {"prepare", "journal_write", "gc_covering_sync",
             "prepare_ok", "commit", "reply"} <= stages
            for stages in stage_sets
        ), stage_sets
        # The backup recorded the same requests' replication hops.
        back_ex = backup.anatomy.exemplar_snapshot()
        assert any(
            {"journal_write", "commit"} <= {s[0] for s in ex["stages"]}
            for ex in back_ex
        ), back_ex
        # Stage timestamps are monotone within each record.
        for ex in prim_ex + back_ex:
            ts = [s[1] for s in ex["stages"]]
            assert ts == sorted(ts)
        # And the trace ids line up across replicas (wire-propagated,
        # not independently minted).
        prim_ids = {ex["trace_id"] for ex in prim_ex}
        back_ids = {ex["trace_id"] for ex in back_ex}
        assert prim_ids & back_ids
    finally:
        MemoryStorage.supports_deferred_sync = had


def test_shed_runs_below_the_dedupe_gate():
    """Admission control must never busy a RETRANSMISSION of a
    committed request (the stored reply wins), and a shed fresh
    request recovers once the queue has room."""
    from tigerbeetle_tpu.testing.cluster import Cluster
    from tigerbeetle_tpu.testing.harness import account, pack

    c = Cluster(replica_count=1)
    r = c.replicas[0]
    client = c.client(1000)
    client.register()
    c.run_until(lambda: client.registered)
    body = pack([account(1)])
    assert c.run_request(
        client, types.Operation.create_accounts, body
    ) == b""

    # Zero-bound the queue: anything that would queue is shed.
    r.admit_queue = 0
    sheds = []
    r.on_shed = lambda h, tenant=None: sheds.append(int(h["request"]))

    # Retransmit of the COMMITTED request: replayed from the stored
    # reply, never shed (the dedupe gate runs first).
    h = wire.make_header(
        command=wire.Command.request,
        operation=types.Operation.create_accounts,
        cluster=c.cluster_id, client=client.id,
        request=client.request_number,
    )
    wire.finalize_header(h, body)
    r.on_message(h, body)
    for _ in range(20):
        c.step()
    # Dedupe replayed the stored reply; the shed path never fired
    # (SimClient drops replies with nothing in flight, so the absence
    # of busy/shed IS the observable contract here).
    assert client.busy_replies == 0 and not sheds

    # A FRESH request while every prepare path is gated (anchor
    # repair pending) must queue — and with the zero bound, shed.
    r._anchor_pending = True
    client.request(types.Operation.create_accounts, pack([account(2)]))
    c.run_until(lambda: client.busy_replies > 0, 200)
    assert sheds and sheds[-1] == client.request_number
    # Lift the gate and the bound: the client's retransmission cadence
    # recovers the shed request — busy was typed, not fatal.
    r._anchor_pending = False
    r.admit_queue = None
    c.run_until(lambda: not client.busy())
    assert client.reply == b""


def test_vsr_drops_unknown_command_without_crashing():
    from tigerbeetle_tpu.testing.cluster import Cluster

    c = Cluster(replica_count=1)
    r = c.replicas[0]
    c.run_until(lambda: r.status == "normal")
    busy = wire.make_header(
        command=wire.Command.client_busy, cluster=c.cluster_id, client=5,
    )
    wire.finalize_header(busy, b"")
    r.on_message(busy, b"")  # must not raise
    # And a genuinely unknown byte is equally harmless.
    junk = wire.make_header(command=200, cluster=c.cluster_id)
    wire.finalize_header(junk, b"")
    r.on_message(junk, b"")
