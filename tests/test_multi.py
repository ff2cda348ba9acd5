"""WAL group commit (deferred-sync mode, vsr/multi.py): the
durability-before-ack contract, fsync batching, the backup
double-fsync regression, and crash-at-fsync chaos.

Group commit is forced onto the deterministic MemoryStorage clusters
here (production gating keys off storage.supports_deferred_sync,
which the fault-injecting backend leaves False so every other seeded
test keeps the synchronous path)."""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.testing.harness import account, ids_bytes, pack, transfer
from tigerbeetle_tpu.vsr import storage as storage_mod
from tigerbeetle_tpu.vsr.storage import FsyncCrash
from tigerbeetle_tpu.vsr.wire import Command


@pytest.fixture
def gc_cluster(monkeypatch):
    monkeypatch.setattr(
        storage_mod.MemoryStorage, "supports_deferred_sync", True,
        raising=False,
    )
    c = Cluster(3, seed=11)
    for r in c.replicas:
        assert r._gc_enabled
    return c


def _register(c, client_id):
    cl = c.client(client_id)
    cl.register()
    c.run_until(lambda: cl.registered)
    return cl


def _setup_accounts(c, cl, ids=(1, 2)):
    reply = c.run_request(
        cl, types.Operation.create_accounts, pack([account(i) for i in ids])
    )
    assert reply == b""


def _instrument_ack_ordering(c):
    """Record a violation whenever a prepare_ok or client reply for op
    N leaves a replica whose WAL write for N is not yet covered by a
    completed sync — the exact contract group commit must not weaken."""
    violations = []
    for r, st in zip(c.replicas, c.storages):
        state = {"seq": 0, "synced": 0, "wseq": {}}

        orig_write = r.journal.write_prepare

        def write_prepare(header, body, sync=True, *, _s=state, _w=orig_write):
            _s["seq"] += 1
            _s["wseq"][int(header["op"])] = _s["seq"]
            _w(header, body, sync=sync)
            if sync:
                _s["synced"] = _s["seq"]

        r.journal.write_prepare = write_prepare

        orig_sync = st.sync

        def sync(*, _s=state, _o=orig_sync):
            _o()  # raises (FsyncCrash) before anything counts as synced
            _s["synced"] = _s["seq"]

        st.sync = sync

        orig_send = r.bus.send

        def send(dst, header, body, *, _s=state, _r=r, _o=orig_send):
            cmd = int(header["command"])
            if cmd == int(Command.prepare_ok):
                w = _s["wseq"].get(int(header["op"]))
                if w is not None and w > _s["synced"]:
                    violations.append(("prepare_ok", _r.replica, int(header["op"])))
            if cmd in (int(Command.prepare), int(Command.commit)):
                # A commit number riding heartbeats / prepare headers
                # is an ack too: the sender vouches the op is quorum
                # -durable, which includes its OWN copy — its covering
                # sync must have completed (the self-vote leak class).
                commit = int(header["commit"])
                w = _s["wseq"].get(commit)
                if w is not None and w > _s["synced"]:
                    violations.append(("commit_leak", _r.replica, commit))
            _o(dst, header, body)

        r.bus.send = send

        orig_send_client = r.bus.send_client

        def send_client(client, header, body, *, _s=state, _r=r,
                        _o=orig_send_client):
            if int(header["command"]) == int(Command.reply):
                w = _s["wseq"].get(int(header["op"]))
                if w is not None and w > _s["synced"]:
                    violations.append(("reply", _r.replica, int(header["op"])))
            _o(client, header, body)

        r.bus.send_client = send_client
    return violations


def test_group_commit_never_acks_before_covering_sync(gc_cluster):
    c = gc_cluster
    violations = _instrument_ack_ordering(c)
    cl = _register(c, 100)
    _setup_accounts(c, cl)
    others = [_register(c, 101 + k) for k in range(3)]
    done = [0]

    def drive(client, base):
        sent = {"n": 0}

        def step_one():
            if client.busy():
                return False
            if sent["n"] >= 8:
                return True
            sent["n"] += 1
            client.request(
                types.Operation.create_transfers,
                pack([
                    transfer(base + sent["n"], debit_account_id=1,
                             credit_account_id=2, amount=1)
                ]),
            )
            return False

        return step_one

    steppers = [drive(cl, 1000)] + [
        drive(o, 2000 + 100 * k) for k, o in enumerate(others)
    ]
    for _ in range(4000):
        if all(s() for s in steppers):
            break
        c.step()
    c.settle()
    c.check_convergence()
    assert violations == [], violations[:10]


def test_group_commit_batches_fsyncs_under_pipelined_load(gc_cluster):
    """Concurrent sessions fill the prepare pipeline; a backup's one
    flush per step then covers several prepares — strictly fewer
    fsyncs than prepares (`journal_fsyncs_per_req` reads the same
    ratio from a served cluster's scrape)."""
    c = gc_cluster
    cl = _register(c, 100)
    _setup_accounts(c, cl)
    sessions = [_register(c, 101 + k) for k in range(4)]
    fsyncs0 = [st.stat_fsyncs for st in c.storages]
    prepares0 = [r.stat_prepares_written for r in c.replicas]
    pending = []
    next_id = [1000]
    for _ in range(1200):
        for s in sessions:
            if not s.busy():
                next_id[0] += 1
                s.request(
                    types.Operation.create_transfers,
                    pack([
                        transfer(next_id[0], debit_account_id=1,
                                 credit_account_id=2, amount=1)
                    ]),
                )
        c.step()
        if next_id[0] >= 1080:
            break
    c.settle()
    c.check_convergence()
    # The backup (replica 1 or 2) journals a whole delivered batch per
    # step and flushes once: covered prepares > flushes.
    gains = [
        (r.stat_prepares_written - p0) - (st.stat_fsyncs - f0)
        for r, st, p0, f0 in zip(
            c.replicas, c.storages, prepares0, fsyncs0
        )
    ]
    assert any(g > 0 for g in gains), (
        "no replica ever covered >1 prepare per fsync", gains
    )


def test_scrub_repair_costs_one_covering_sync(gc_cluster):
    """The backup double-cost regression: a scrub-repaired prepare
    (prepare-ring write + redundant-header coverage) folds into ONE
    covering sync in deferred-sync mode — it used to pay an fdatasync
    for the WAL write and another for the header-sector rewrite."""
    c = gc_cluster
    cl = _register(c, 100)
    _setup_accounts(c, cl)
    for i in range(5):
        reply = c.run_request(
            cl, types.Operation.create_transfers,
            pack([transfer(10 + i, debit_account_id=1,
                           credit_account_id=2, amount=1)]),
        )
        assert reply == b""
    c.settle()
    r = c.replicas[1]
    st = c.storages[1]
    op = r.commit_min - 1
    slot = r.journal.slot_for_op(op)
    st.corrupt_sector(st.layout.prepare_slot_offset(slot))
    assert r.journal.read_prepare(op) is None
    before = st.stat_fsyncs
    r._wal_scrub_probe(op)
    c.run_until(lambda: r.journal.read_prepare(op) is not None, 200)
    c.step()  # final flush point
    assert st.stat_fsyncs - before == 1, (
        "repair must cost exactly one covering sync",
        st.stat_fsyncs - before,
    )


def test_scrub_header_heal_rides_covering_sync(gc_cluster):
    """Header-ring damage self-heals from memory; in deferred-sync
    mode the rewrite rides the next covering flush (and flushes the
    WAL file only — never the grid)."""
    c = gc_cluster
    cl = _register(c, 100)
    _setup_accounts(c, cl)
    reply = c.run_request(
        cl, types.Operation.create_transfers,
        pack([transfer(10, debit_account_id=1, credit_account_id=2,
                       amount=1)]),
    )
    assert reply == b""
    c.settle()
    r = c.replicas[2]
    st = c.storages[2]
    op = r.commit_min
    slot = r.journal.slot_for_op(op)
    # Damage ONLY the redundant header sector (prepare intact).
    sector = st.layout.wal_headers_offset + (
        slot // 16 * storage_mod.SECTOR_SIZE
    )
    st.corrupt_sector(sector)
    assert r.journal.read_prepare(op) is not None
    assert not r.journal.header_sector_intact(slot)
    before = st.stat_fsyncs
    r._wal_scrub_probe(op)
    assert r.journal.header_sector_intact(slot)
    c.step()  # covering flush
    assert st.stat_fsyncs - before == 1
    assert r.journal.unsynced_writes == 0


def test_crash_at_fsync_no_acked_op_lost(gc_cluster):
    """Chaos: the primary dies INSIDE a covering fsync.  Nothing that
    sync would have covered was acked (the flush held the sends), so
    after failover + recovery every reply any client ever observed
    must be durable cluster-wide."""
    c = gc_cluster
    violations = _instrument_ack_ordering(c)
    cl = _register(c, 100)
    _setup_accounts(c, cl)
    acked_ids = []
    next_id = [100]

    def send_next():
        next_id[0] += 1
        cl.request(
            types.Operation.create_transfers,
            pack([transfer(next_id[0], debit_account_id=1,
                           credit_account_id=2, amount=1)]),
        )

    for _ in range(6):
        send_next()
        c.run_until(lambda: not cl.busy())
        assert cl.reply == b""
        acked_ids.append(next_id[0])

    # Arm the fault: the primary's 2nd sync from now never completes.
    c.storages[0].crash_at_fsync = 2
    send_next()
    crashed = False
    for _ in range(400):
        try:
            c.step()
        except FsyncCrash:
            crashed = True
            c.crash_replica(0)
            break
        if not cl.busy():
            acked_ids.append(next_id[0])
            send_next()
    assert crashed, "seeded crash_at_fsync never fired"

    # Failover: the remaining replicas elect a new primary; the client
    # retransmits the in-flight request and eventually gets its reply.
    c.run_until(lambda: not cl.busy(), 4000)
    acked_ids.append(next_id[0])
    c.restart_replica(0)
    c.settle(6000)
    c.check_linearized()
    c.check_convergence()
    assert violations == [], violations[:10]

    # Every acked transfer survives: balance == number of acked ops.
    out = c.run_request(cl, types.Operation.lookup_accounts, ids_bytes([1]))
    row = np.frombuffer(out, types.ACCOUNT_DTYPE)[0]
    assert types.u128_get(row, "debits_posted") == len(acked_ids)
