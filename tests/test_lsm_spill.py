"""LSM spill tier: the state machine's durable state scales past RAM.

VERDICT r1 item 2's acceptance test: commit more transfer state than
the memtable holds across several checkpoints, restart from disk, and
answer every query class from the LSM tier — with checkpoint blobs
O(RAM tail), not O(history).  The CPU oracle (dict-backed, no forest)
replays the same stream as the semantic reference.
"""

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import obs, types
from tigerbeetle_tpu.lsm.forest import Forest
from tigerbeetle_tpu.lsm.tree import CompactionJob, TreeStats
from tigerbeetle_tpu.state_machine import CpuStateMachine
from tigerbeetle_tpu.state_machine import spill as spill_mod
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing.harness import account, ids_bytes, pack, transfer
from tigerbeetle_tpu.vsr import replica as vsr_replica
from tigerbeetle_tpu.vsr.storage import MemoryStorage, ZoneLayout

CLUSTER = 11
N_ACCOUNTS = 40
BATCH = 500
N_BATCHES = 24  # 12k transfers >> forest memtable (8192)

Op = types.Operation
TF = types.TransferFlags
AF = types.AccountFlags

# test_min's 4KiB messages cap batches at 30 events; this scenario
# needs batches big enough to outgrow the forest memtable quickly.
CONF = cfg.Config(
    name="test_spill",
    message_size_max=1 << 16,
    lsm_batch_multiple=4,
    pipeline_prepare_queue_max=4,
    journal_slot_count=64,
    clients_max=4,
)


def layout():
    return ZoneLayout(config=CONF)


def make_tpu_replica(storage):
    r = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(CONF))
    r.open()
    return r


def build_stream():
    """[(op, body, checkpoint_after)] — accounts, posted transfers,
    one pending/post pair crossing a checkpoint, history accounts."""
    rng = np.random.default_rng(7)
    ops = []
    accounts = [
        # History on a few accounts exercises the history spill.
        account(i, flags=int(AF.history) if i <= 4 else 0)
        for i in range(1, N_ACCOUNTS + 1)
    ]
    ops.append((Op.create_accounts, pack(accounts), False))

    next_id = 1
    pending_id = None
    for b in range(N_BATCHES):
        rows = []
        for _ in range(BATCH):
            dr = int(rng.integers(1, N_ACCOUNTS + 1))
            cr = dr % N_ACCOUNTS + 1
            rows.append(
                transfer(
                    next_id, debit_account_id=dr, credit_account_id=cr,
                    amount=int(rng.integers(1, 50)),
                )
            )
            next_id += 1
        # A live pending created BEFORE a checkpoint and posted well
        # after: the checkpoint spills it (live pendings spill too —
        # a stuck pending must not pin RAM), so the post finalizes a
        # SPILLED pending via the LSM status update path.
        if b == 4:
            rows[-1] = transfer(
                next_id - 1, debit_account_id=5, credit_account_id=6,
                amount=17, flags=int(TF.pending),
            )
            pending_id = next_id - 1
        if b == 9:
            rows[0] = transfer(
                next_id - BATCH, amount=0,
                flags=int(TF.post_pending_transfer), pending_id=pending_id,
            )
        ops.append(
            (Op.create_transfers, pack(rows), b % 6 == 5)
        )
    return ops, next_id - 1


def replay(r, ops, *, checkpoint=True, restart_at=None, storage=None):
    replies = []
    blob_sizes = []
    for i, (op, body, ckpt) in enumerate(ops):
        replies.append(r.on_request(int(op), body))
        if ckpt and checkpoint:
            r.checkpoint()
            blob_sizes.append(
                int(r.superblock.working["checkpoint_size"])
            )
        if restart_at is not None and i == restart_at:
            r = make_tpu_replica(storage)
    return r, replies, blob_sizes


def query_suite(r, max_tid):
    """Wire-level bytes for every query class."""
    out = []
    ids = list(range(1, N_ACCOUNTS + 1))
    out.append(r.on_request(int(Op.lookup_accounts), ids_bytes(ids)))
    # Old (spilled), middle, and recent transfer ids.
    sample = [1, 2, 3, max_tid // 2, max_tid - 1, max_tid, max_tid + 999]
    out.append(r.on_request(int(Op.lookup_transfers), ids_bytes(sample)))
    for acct in (1, 5, 17):
        for flags, rev in ((3, 0), (1, 0), (2, 0), (3, 4)):
            f = np.zeros(1, types.ACCOUNT_FILTER_DTYPE)
            f[0]["account_id_lo"] = acct
            f[0]["limit"] = 100
            f[0]["flags"] = flags | rev
            out.append(
                r.on_request(int(Op.get_account_transfers), f.tobytes())
            )
    # Historical balances on a history-flagged account.
    f = np.zeros(1, types.ACCOUNT_FILTER_DTYPE)
    f[0]["account_id_lo"] = 2
    f[0]["limit"] = 50
    f[0]["flags"] = 3
    out.append(r.on_request(int(Op.get_account_balances), f.tobytes()))
    return out


def test_spill_across_checkpoints_restart_and_queries():
    ops, max_tid = build_stream()

    # TPU replica with LSM forest over (sparse) memory storage.
    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r_tpu = make_tpu_replica(storage)
    assert r_tpu.forest is not None
    r_tpu, replies_tpu, blob_sizes = replay(r_tpu, ops)

    # Oracle: plain CPU replica, no forest, same stream.
    storage_cpu = MemoryStorage(layout())
    vsr_replica.format(storage_cpu, CLUSTER)
    r_cpu = vsr_replica.Replica(
        storage_cpu, CLUSTER, CpuStateMachine(CONF)
    )
    r_cpu.open()
    assert r_cpu.forest is None
    r_cpu, replies_cpu, _ = replay(r_cpu, ops, checkpoint=False)

    assert replies_tpu == replies_cpu

    # Spill actually happened, and most rows left RAM.
    sm = r_tpu.sm
    assert sm._store.base > 8_000, sm._store.base
    assert sm._store.ram.count < 5_000
    assert sm._hspill.base > 0

    # Checkpoint blobs are O(tail): raw transfer state is ~1.5MB+ by
    # the last checkpoint; blobs must stay far below it and must not
    # grow with history.
    raw_state = max_tid * 128
    assert raw_state > 1_500_000
    assert max(blob_sizes) < 600_000, blob_sizes
    assert blob_sizes[-1] < blob_sizes[0] + 200_000

    # Every query class answers identically from LSM + RAM tail.
    q_tpu = query_suite(r_tpu, max_tid)
    q_cpu = query_suite(r_cpu, max_tid)
    assert q_tpu == q_cpu

    # Restart from disk: recovery opens the forest from its manifest.
    r_tpu2 = make_tpu_replica(storage)
    assert r_tpu2.sm._store.base == sm._store.base
    q2 = query_suite(r_tpu2, max_tid)
    assert q2 == q_cpu

    # Duplicate-id resubmission of a long-spilled transfer still hits
    # the exists ladder (duplicate detection spans the LSM tier).
    dup = pack(
        [transfer(1, debit_account_id=1, credit_account_id=2, amount=1)]
    )
    rep_t = r_tpu2.on_request(int(Op.create_transfers), dup)
    rep_c = r_cpu.on_request(int(Op.create_transfers), dup)
    assert rep_t == rep_c
    arr = np.frombuffer(rep_t, types.CREATE_RESULT_DTYPE)
    assert len(arr) == 1  # some exists_* / exists code, not success


def test_state_sync_ships_spilled_blocks():
    """A deeply-lagged TPU replica rejoins via state sync: the sync
    payload must carry the sender's live LSM grid blocks, or the
    installed manifest would reference blocks the receiver never had
    (reference: src/vsr/grid_blocks_missing.zig)."""
    from tigerbeetle_tpu.testing.cluster import Cluster
    from tigerbeetle_tpu.testing.harness import pack as hpack

    c = Cluster(
        replica_count=3, seed=77,
        state_machine_factory=lambda: TpuStateMachine(cfg.TEST_MIN),
    )
    client = c.client(1000)
    client.register()
    c.run_until(lambda: client.registered)
    c.run_request(client, Op.create_accounts, hpack([account(1), account(2)]))
    c.network.partition(2)
    interval = c.replicas[0].config.vsr_checkpoint_interval
    for k in range(3 * interval):
        c.run_request(
            client, Op.create_transfers,
            hpack(
                [
                    transfer(
                        1000 + k, debit_account_id=1, credit_account_id=2,
                        amount=1,
                    )
                ]
            ),
        )
    assert c.replicas[0].checkpoint_op > 0
    assert c.replicas[0].sm._store.base > 0  # sender actually spilled
    assert c.replicas[2].commit_min < c.replicas[0].commit_min
    c.network.heal()
    c.settle(max_steps=20000)
    for _ in range(50):
        c.step()
    c.check_convergence()
    lagged = c.replicas[2].sm
    assert lagged._store.base > 0
    # The synced replica answers queries over rows it only ever
    # received as shipped grid blocks.
    assert lagged.transfer_timestamp(1000) is not None
    assert lagged.transfer_timestamp(1000 + 3 * interval - 1) is not None


def test_spill_restart_midstream():
    """Restart between checkpoints: WAL replay on top of a spilled
    checkpoint must reconverge with the oracle."""
    ops, max_tid = build_stream()
    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r = make_tpu_replica(storage)
    r, replies_tpu, _ = replay(
        r, ops, restart_at=len(ops) // 2, storage=storage
    )

    storage_cpu = MemoryStorage(layout())
    vsr_replica.format(storage_cpu, CLUSTER)
    r_cpu = vsr_replica.Replica(
        storage_cpu, CLUSTER, CpuStateMachine(CONF)
    )
    r_cpu.open()
    r_cpu, replies_cpu, _ = replay(r_cpu, ops, checkpoint=False)

    q_tpu = query_suite(r, max_tid)
    q_cpu = query_suite(r_cpu, max_tid)
    assert q_tpu == q_cpu


# ----------------------------------------------------------------------
# Finalisers of cold pendings (PR 33) and the posted groove (PR 34).

S = types.TransferPendingStatus
R = types.CreateTransferResult
PER, BATCHES, N_ACCT = 400, 4, 8
STAGES = ("memtable", "sealed", "compacted")
READS = ("scalar", "gather_many", "pending_status", "lookup_transfers")


def _engine_replica(storage, engine):
    r = vsr_replica.Replica(
        storage, CLUSTER,
        TpuStateMachine(CONF, account_capacity=1 << 12, engine=engine),
    )
    r.open()
    return r


def _cpu_replica():
    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(storage, CLUSTER, CpuStateMachine(CONF))
    r.open()
    return r


def _finalisers(first_id, targets, void_every):
    return pack([
        transfer(first_id + k, pending_id=int(p), amount=0,
                 flags=int(TF.void_pending_transfer if k % void_every == 0
                           else TF.post_pending_transfer))
        for k, p in enumerate(targets)])


def _pendings(both, rng):
    both(Op.create_accounts, pack([account(i) for i in range(1, N_ACCT + 1)]))
    pend_ids = []
    for b in range(BATCHES):
        rows = []
        for k in range(PER):
            tid = 1000 + b * PER + k
            dr = int(rng.integers(1, N_ACCT + 1))
            rows.append(transfer(tid, debit_account_id=dr,
                                 credit_account_id=dr % N_ACCT + 1,
                                 amount=int(rng.integers(1, 500)),
                                 flags=int(TF.pending)))
            pend_ids.append(tid)
        assert both(Op.create_transfers, pack(rows)) == b""
    return pend_ids


def _settle(r):
    """A spill of the whole tail and a seal of every memtable: what
    beats do over a longer run."""
    r.checkpoint()
    for groove in r.forest.grooves.values():
        groove.object_tree.seal_memtable()


def _merge_level0(tree):
    """Level 0's runs into level 1, however few they are."""
    tree.seal_memtable()
    assert tree._job is None and tree.levels[0]
    job = CompactionJob(tree, 0)
    while not job.done:
        job.step(1 << 30)


def _status_reads(r, r_cpu, ids):
    """The status of `ids` through each door of the store, and the
    oracle's."""
    sm = r.sm
    ids = [int(i) for i in ids]
    rows = np.array([sm._transfer_row(i) for i in ids], np.int64)
    assert (rows < sm._store.base).all()
    want = [int(r_cpu.sm.pending_status(i) or 0) for i in ids]
    few = slice(0, len(ids), 9)
    body = ids_bytes(ids[few])
    return {
        "want": want,
        "scalar": ([int(sm._store["status"][int(row)]) for row in rows[few]], want[few]),
        "gather_many": (sm._store.gather_many(["status"], rows)["status"].tolist(), want),
        "pending_status": ([int(sm.pending_status(i) or 0) for i in ids[few]], want[few]),
        "lookup_transfers": (r.on_request(int(Op.lookup_transfers), body),
                             r_cpu.on_request(int(Op.lookup_transfers), body)),
    }


@pytest.fixture(scope="module", params=["device", "host"])
def cold(request):
    """The scenario, once an engine: pendings that all go cold, a
    round of finalisers, a checkpoint, a second round (a quarter of it
    naming pendings of the first), a restart from the data file, a
    compaction of both trees.  Every reply against the CPU oracle as
    it goes; what the cases below judge is kept in `seen`."""
    engine = request.param
    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r = _engine_replica(storage, engine)
    r_cpu = _cpu_replica()
    rng = np.random.default_rng(33)

    def both(op, body):
        got = r.on_request(int(op), body)
        assert got == r_cpu.on_request(int(op), body)
        return got

    pend_ids = np.array(_pendings(both, rng))
    _settle(r)
    sm = r.sm
    assert sm._store.base >= BATCHES * PER      # every pending is cold
    objects = r.forest.grooves["transfers"].object_tree
    posted = r.forest.grooves["transfers_posted"].object_tree
    # Its own count of what compaction does to the object tree (the
    # forest's is every tree's, the index trees' merges among them).
    objects.stats = TreeStats(obs.Registry(enabled=False))
    snap = lambda: sm.metrics.snapshot() | r.forest.metrics.snapshot()  # noqa: E731
    seen = {"engine": engine, "snap0": snap()}

    first = rng.permutation(pend_ids)[:PER]
    assert both(Op.create_transfers, _finalisers(5000, first, 3)) == b""
    seen["snap1"] = snap()
    seen["posted_memtable1"] = posted.memtable_count
    seen["memtable"] = _status_reads(r, r_cpu, pend_ids)

    _settle(r)      # the posted entries, and the finalisers' own rows
    seen["posted_runs1"] = sum(len(level) for level in posted.levels)
    seen["sealed"] = _status_reads(r, r_cpu, pend_ids)

    rest = [p for p in pend_ids if p not in set(first.tolist())]
    again = np.concatenate([first[:100], rng.permutation(rest)[:PER - 100]])
    seen["snap2"] = snap()
    seen["reply2"] = np.frombuffer(
        both(Op.create_transfers, _finalisers(7000, again, 2)),
        types.CREATE_RESULT_DTYPE,
    )
    seen["snap3"] = snap()

    # The data file: the checkpoint (round 1 in the posted tree's run)
    # and, behind it in the WAL, round 2.
    restarted = _engine_replica(storage, engine)
    rows = np.array([sm._transfer_row(int(i)) for i in pend_ids], np.int64)
    seen["restart"] = {
        "base": (restarted.sm._store.base, sm._store.base),
        "status": (
            restarted.sm._store.gather_many(["status"], rows)["status"].tolist(),
            sm._store.gather_many(["status"], rows)["status"].tolist(),
        ),
        "state_root": (restarted.sm.state_root(), sm.state_root()),
        "posted_memtable": (
            restarted.forest.grooves["transfers_posted"].object_tree.memtable_count,
            posted.memtable_count,
        ),
    }
    del restarted

    _merge_level0(posted)
    seen["compacted"] = _status_reads(r, r_cpu, pend_ids)
    _merge_level0(objects)
    seen["object_runs"] = [
        (run.key_min, run.key_max, run.count) for run in objects._runs_newest_first()
    ]
    seen["object_base"] = sm._store.base
    seen["object_stats"] = objects.stats
    seen["compacted_objects"] = _status_reads(r, r_cpu, pend_ids)

    both(Op.lookup_accounts, ids_bytes(list(range(1, N_ACCT + 1))))
    both(Op.lookup_transfers, ids_bytes(
        [int(x) for x in first[:50]] + list(range(5000, 5050))
        + list(range(7000, 7120))))
    sm.verify_device_mirror()
    return seen


def test_finalisers_of_cold_pendings_reach_the_posted_tree(cold):
    """A payments switch's posts and voids arrive after their pendings
    have left the RAM tail (PR 33): the join reads them through the
    object tree, the finalise writes ONE posted-tree entry each (PR
    34) and touches no object."""
    s0, s1 = cold["snap0"], cold["snap1"]
    assert s1["store.join_cold_rows"] - s0["store.join_cold_rows"] == PER
    assert s1["store.status_overwrites"] == PER
    assert cold["posted_memtable1"] == PER and cold["posted_runs1"] == 1
    # Every joined row was stored as `pending`; none was finalised yet.
    assert s1["store.posted_lookups"] - s0["store.posted_lookups"] == PER
    assert s1["store.posted_hits"] == 0
    assert s1["fallback_events"] == 0
    # The join is a part of sm.plan: timed where that leaf is open (the
    # device engine's plan), counted alone on the host engine's path.
    assert (s1["plan.join_cold_us.count"] >= 1) == (cold["engine"] == "device")
    if cold["engine"] == "device":
        assert s1["dev.kind.two_phase_lo.batches"] == 1
        assert s1["dev.fallback_batches"] == 0


def test_a_second_finalise_answers_from_the_posted_tree(cold):
    got, s2, s3 = cold["reply2"], cold["snap2"], cold["snap3"]
    assert len(got) == 100 and (got["index"] == np.arange(100)).all()
    want = [R.pending_transfer_already_voided if k % 3 == 0
            else R.pending_transfer_already_posted for k in range(100)]
    assert got["result"].tolist() == [int(x) for x in want]
    assert s3["store.posted_lookups"] - s2["store.posted_lookups"] == PER
    assert s3["store.posted_hits"] - s2["store.posted_hits"] == 100
    assert s3["store.status_overwrites"] == 2 * PER - 100
    assert s3["fallback_events"] == 0
    if cold["engine"] == "device":
        assert s3["dev.kind.two_phase_lo.batches"] == 2
        assert s3["dev.fallback_batches"] == 0
        assert s3["host_semantic_events"] == 0


@pytest.mark.parametrize("read", READS)
@pytest.mark.parametrize("stage", STAGES + ("compacted_objects",))
def test_status_reads_back_through_every_door(cold, stage, read):
    """Before the posted tree's seal, after it, after its compaction
    and after the object tree's."""
    got, want = cold[stage][read]
    assert got == want
    statuses = set(cold[stage]["want"])
    assert {int(S.pending), int(S.posted), int(S.voided)} <= statuses


def test_the_object_tree_holds_each_row_once_and_its_runs_move(cold):
    runs = sorted(cold["object_runs"])
    assert len(runs) >= 1
    for (_, prev_max, _), (cur_min, _, _) in zip(runs, runs[1:]):
        assert prev_max < cur_min
    assert sum(count for _, _, count in runs) == cold["object_base"]
    stats = cold["object_stats"]
    assert stats.jobs.value == 1 and stats.moves.value == 1
    assert stats.entries_in.value == 0 and stats.entries_out.value == 0


@pytest.mark.parametrize("what", ["base", "status", "state_root", "posted_memtable"])
def test_a_restart_from_checkpoint_and_wal_ends_where_the_replica_is(cold, what):
    """`Forest.open` on the checkpoint's blob brings the posted tree's
    run back, the WAL's replay writes round 2's entries again."""
    restarted, running = cold["restart"][what]
    assert restarted == running


def _as_the_parent(monkeypatch):
    """The store as PR 33 left it: no posted groove on the forest (no
    tree id, nothing in a checkpoint), and a finalise that reads the
    cold object back and `put_batch`es it under its old row key."""
    from tigerbeetle_tpu.lsm.groove import Groove

    declare = Forest.groove

    def groove(self, name, **kw):
        if name != "transfers_posted":
            return declare(self, name, **kw)
        self.grooves[name] = Groove(self.grid, name, **kw)    # never one of its trees
        return self.grooves[name]

    def update_status(self, rows, statuses):
        obj = self._lookup_raw(rows)
        obj[:, 136] = np.asarray(statuses, np.uint8)
        self.groove.object_tree.put_batch(spill_mod._row_keys(rows), obj)

    monkeypatch.setattr(Forest, "groove", groove)
    monkeypatch.setattr(spill_mod.TransferSpill, "update_status", update_status)


@pytest.mark.parametrize("engine", ["device", "host"])
def test_a_data_file_of_overwritten_objects_still_reads_the_newest_status(
        engine, monkeypatch):
    """A data file checkpointed BEFORE the posted groove: finalised
    pendings are overwritten row keys of the object tree, no posted
    entry names them.  It opens, answers the same statuses, takes new
    finalisers the new way, and a merge of the overlapping runs keeps
    the younger object."""
    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r_cpu = _cpu_replica()
    rng = np.random.default_rng(34)

    with monkeypatch.context() as parent:
        _as_the_parent(parent)
        r = _engine_replica(storage, engine)

        def both(op, body):
            got = r.on_request(int(op), body)
            assert got == r_cpu.on_request(int(op), body)
            return got

        pend_ids = np.array(_pendings(both, rng))
        _settle(r)
        first = rng.permutation(pend_ids)[:PER]
        assert both(Op.create_transfers, _finalisers(5000, first, 3)) == b""
        assert len(r.forest._trees) == 6
        _settle(r)
        assert r.forest.grooves["transfers_posted"].object_tree.memtable_count == 0

    r = _engine_replica(storage, engine)
    assert len(r.forest._trees) == 8
    for stage in ("opened", "finalised", "merged"):
        if stage == "finalised":
            rest = [p for p in pend_ids if p not in set(first.tolist())]
            again = np.concatenate([first[:100], rng.permutation(rest)[:PER - 100]])
            s0 = r.sm.metrics.snapshot()
            got = np.frombuffer(
                both(Op.create_transfers, _finalisers(7000, again, 2)),
                types.CREATE_RESULT_DTYPE,
            )
            assert len(got) == 100 and set(got["result"].tolist()) == {
                int(R.pending_transfer_already_voided),
                int(R.pending_transfer_already_posted),
            }
            s = r.sm.metrics.snapshot()
            # The overwritten objects say so themselves: no second read.
            assert s["store.posted_lookups"] - s0["store.posted_lookups"] == PER - 100
            assert s["store.posted_hits"] == 0
            assert s["store.status_overwrites"] == PER - 100
        if stage == "merged":
            _settle(r)
            objects = r.forest.grooves["transfers"].object_tree
            before = r.forest.stats.entries_in.value
            _merge_level0(objects)
            assert r.forest.stats.entries_in.value > before    # overlapping: a real merge
            _merge_level0(r.forest.grooves["transfers_posted"].object_tree)
        reads = _status_reads(r, r_cpu, pend_ids)
        for read in READS:
            got, want = reads[read]
            assert got == want, (stage, read)
    r.sm.verify_device_mirror()
