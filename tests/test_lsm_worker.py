"""The LSM beat leaves the commit (ISSUE 30): `lsm/beats.py`.

Where a forest has the beat worker (a cluster's replica over
FileStorage), a commit only hands its beat over (the rows copied out
of the tail, the block budget); one `lsm-beat` worker runs the beats in
commit order.  Held to the inline path here: the same stream ends on
the same bytes, every reader of the spill tier joins the worker first,
at most two beats are queued, a failure on the worker kills the replica
at its next commit, a crash between a commit and its beat is replayed
from the WAL, and `close()` leaves no thread.  A lone replica keeps the
beat in its commit (PERF.md section 6, PR 30: there the hand-over
costs), so these tests give their replicas the worker by hand.
"""

import dataclasses
import filecmp
import threading
import time

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.lsm.beats import BEATS_QUEUED_MAX, BeatWorker
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing.harness import account, ids_bytes, pack, transfer
from tigerbeetle_tpu.vsr import replica as vsr_replica
from tigerbeetle_tpu.vsr.free_set import GridFull
from tigerbeetle_tpu.vsr.storage import BLOCK_SIZE, FileStorage, ZoneLayout

CLUSTER = 30
N_ACCOUNTS = 64
PER = 8190                  # upstream's batch: a full beat a commit
Op = types.Operation
TF = types.TransferFlags
CONFIG = cfg.PRODUCTION


def open_replica(path, config=CONFIG, *, inline=False, create=False):
    storage = FileStorage(str(path), ZoneLayout(config=config), create=create)
    if create:
        vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(
        config, account_capacity=1 << 10, transfer_capacity=1 << 16))
    assert r.forest.beats._worker is None            # a lone replica: in place
    r.forest.beats = BeatWorker(r.forest.metrics, threaded=not inline)
    r.open()
    return storage, r


@pytest.mark.parametrize("replica_count,file_backed,threaded", [
    (1, True, False), (3, True, True), (3, False, False)])
def test_a_clusters_replica_over_a_file_gets_the_worker(
        tmp_path, replica_count, file_backed, threaded):
    """The worker exists where the loop has peers to wait for (the
    beat runs in that wait) and the storage takes writes from a thread;
    a lone replica and every MemoryStorage replica run beats in place."""
    from tigerbeetle_tpu.vsr.storage import MemoryStorage

    layout = ZoneLayout(config=CONFIG)
    storage = (FileStorage(str(tmp_path / "r.tigerbeetle"), layout, create=True)
               if file_backed else MemoryStorage(layout))
    r = vsr_replica.Replica(
        storage, CLUSTER, TpuStateMachine(CONFIG, account_capacity=1 << 10,
                                          transfer_capacity=1 << 12),
        replica=0, replica_count=replica_count)
    worker = r.forest.beats._worker
    assert (worker is not None) == threaded
    if threaded:
        assert worker._thread.name == "lsm-beat" and worker._thread.is_alive()
    r.close()
    assert worker is None or stopped([worker._thread])
    if file_backed:
        storage.close()


def shut(storage, r):
    r.close()
    storage.close()


def accounts(r):
    assert r.on_request(int(Op.create_accounts), pack(
        [account(i) for i in range(1, N_ACCOUNTS + 1)])) == b""


def batch(op: int, *, pending_at: int | None = None) -> bytes:
    """Commit `op`'s 8,190 transfers, ids in sequence."""
    rng = np.random.default_rng(3000 + op)
    rows = np.zeros(PER, types.TRANSFER_DTYPE)
    rows["id_lo"] = np.arange(1 + op * PER, 1 + (op + 1) * PER)
    debit = rng.integers(1, N_ACCOUNTS + 1, PER)
    rows["debit_account_id_lo"] = debit
    rows["credit_account_id_lo"] = debit % N_ACCOUNTS + 1
    rows["amount_lo"] = rng.integers(1, 1000, PER)
    rows["ledger"] = rows["code"] = 1
    if pending_at is not None:
        rows["flags"][pending_at] = int(TF.pending)
    return rows.tobytes()


def commit(r, op: int, **kw) -> None:
    assert r.on_request(int(Op.create_transfers), batch(op, **kw)) == b""


def checkpoint(r) -> None:
    """A checkpoint whose flip has landed: `checkpoint_op` feeds the
    beats' budget, and the async flip publishes it at a wall time."""
    r.checkpoint()
    r._ckpt_join()


def lsm(r) -> dict:
    return r.forest.metrics.snapshot()


class Gate:
    """A test double around `Replica._beat_work`: beats wait on the
    worker until `open()`; `drop` makes them return undone."""

    def __init__(self, r) -> None:
        self.event = threading.Event()
        self.drop = False
        self.ran = 0
        work = r._beat_work

        def held(spill, budget):
            assert threading.current_thread().name == "lsm-beat"
            self.event.wait(timeout=60)
            if not self.drop:
                work(spill, budget)
                self.ran += 1

        r._beat_work = held

    def open(self) -> None:
        self.event.set()

    def open_in(self, seconds: float) -> threading.Timer:
        timer = threading.Timer(seconds, self.open)
        timer.start()
        return timer


# ----------------------------------------------------------------------
# (a) equivalence.


@pytest.mark.parametrize("commits,checkpoints,index_memtable", [
    (50, (16, 33), None), (34, (16,), PER)],
    ids=["production", "index-trees-merge"])
def test_worker_and_inline_end_byte_identical(
        tmp_path, commits, checkpoints, index_memtable):
    """50 commits of 8,190 (over 40 full beats), two checkpoints: the same
    forest, the same state root, the same free set, the same bytes in
    the `.grid` file.  The second case seals the two index trees every
    beat (in production every eighth), so level 0 overflows three times
    and the merges that append to level 1 run under it."""
    ends = {}
    for name, inline in (("worker", False), ("inline", True)):
        path = tmp_path / f"{name}.tigerbeetle"
        storage, r = open_replica(path, inline=inline, create=True)
        indexes = r.forest.grooves["transfers"].indexes.values()
        for tree in indexes:
            tree.memtable_max = index_memtable or tree.memtable_max
        accounts(r)
        beats = 0
        for op in range(commits):
            full = r.sm._store.tail_count() + PER - 16_384 >= PER
            beats += full
            commit(r, op)
            if op in checkpoints:
                checkpoint(r)
        r.forest.barrier()
        snap = lsm(r)
        ends[name] = {
            "manifest": r.forest.manifest_blob(),
            "root": r.sm.state_root(),
            "free_set": r.forest.grid.free_set.encode(),
            "spill_base": r.sm._store.spill.base,
            "commit_min": r.commit_min,
            "merges": snap["compact.jobs"] - snap["compact.moves"],
            "level_1": [len(tree.levels[1]) for tree in indexes],
        }
        shut(storage, r)
        threaded = not inline
        assert (snap["beat.work_us.count"] >= beats) == threaded
        assert threaded or snap["beat.work_us.count"] == 0
    assert beats >= commits - 10
    assert ends["worker"]["spill_base"] >= (commits - 5) * PER
    if index_memtable:
        assert ends["worker"]["merges"] >= 6
        assert ends["worker"]["level_1"] == [3, 3]
    for key in ends["worker"]:
        assert ends["worker"][key] == ends["inline"][key], key
    assert filecmp.cmp(tmp_path / "worker.tigerbeetle.grid",
                       tmp_path / "inline.tigerbeetle.grid", shallow=False)


# ----------------------------------------------------------------------
# (b) every barrier site, with a beat held on the worker.


class Pair:
    """Two replicas after the same 6 commits (3 beats spilled), a
    pending transfer among the first rows; one threaded, one inline."""

    def __init__(self, tmp) -> None:
        self.made = []
        for name, inline in (("worker", False), ("inline", True)):
            storage, r = open_replica(tmp / f"{name}.tigerbeetle",
                                      inline=inline, create=True)
            accounts(r)
            for op in range(6):
                commit(r, op, pending_at=5 if op == 0 else None)
            self.made.append((storage, r))
        self.worker, self.inline = self.made[0][1], self.made[1][1]
        self.next_op = 6


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    pair = Pair(tmp_path_factory.mktemp("barrier"))
    yield pair
    for storage, r in pair.made:
        shut(storage, r)


def lookup(ids):
    return int(Op.lookup_transfers), ids_bytes(ids)


def account_transfers(acct: int):
    f = np.zeros(1, types.ACCOUNT_FILTER_DTYPE)
    f[0]["account_id_lo"] = acct
    f[0]["limit"] = 8000
    f[0]["flags"] = 3
    return int(Op.get_account_transfers), f.tobytes()


def create_one(**fields):
    return int(Op.create_transfers), pack([transfer(**fields)])


# case -> the request, given the id of a row the held beat carries.
BARRIER_CASES = {
    # Spilled long ago, handed over by the held beat, still in the tail.
    "lookup_transfers": lambda handed_over: lookup(
        [1, 2, PER + 7, handed_over, handed_over + 2 * PER]),
    "get_account_transfers": lambda handed_over: account_transfers(3),
    # Transfer 6 (row 5) was created pending and has spilled: the post
    # reads it and rewrites its status byte in the object tree.
    "post_of_a_spilled_pending": lambda handed_over: create_one(
        id=10_000_001, pending_id=6, flags=int(TF.post_pending_transfer)),
    # Transfer 101 spilled long ago; the same id with other fields.
    "duplicate_id_below_base": lambda handed_over: create_one(
        id=101, debit_account_id=1, credit_account_id=2, amount=1),
}


@pytest.mark.parametrize("case", BARRIER_CASES)
def test_a_reader_of_the_spill_tier_joins_the_held_beat(pair, case):
    worker, inline = pair.worker, pair.inline
    worker.forest.barrier()
    # The id of a row the next beat will carry: 17 rows into the tail.
    handed_over = int(worker.sm._store.col("id_lo")[17])
    spilled = worker.sm._store.spill.base
    gate = Gate(worker)
    for r in (worker, inline):
        commit(r, pair.next_op)
    pair.next_op += 1
    # The loop's side has handed the rows over; the trees lack them.
    assert worker.forest.beats.queued() == 1 and gate.ran == 0
    assert worker.sm._store.base == inline.sm._store.base > spilled + 17
    assert worker.sm._store.spill.base == spilled
    operation, body = BARRIER_CASES[case](handed_over)
    before = lsm(worker)
    timer = gate.open_in(0.3)
    t0 = time.monotonic()
    got = worker.on_request(operation, body)
    waited = time.monotonic() - t0
    timer.join()
    del worker._beat_work                   # the double goes
    assert got == inline.on_request(operation, body)
    assert gate.ran >= 1 and waited >= 0.25
    after = lsm(worker)
    assert after["barrier.joins"] > before["barrier.joins"]
    assert after["barrier.wait_us.sum"] - before["barrier.wait_us.sum"] >= 250_000
    if case == "lookup_transfers":
        rows = np.frombuffer(got, types.TRANSFER_DTYPE)
        assert rows["id_lo"].tolist() == [
            1, 2, PER + 7, handed_over, handed_over + 2 * PER]
    if case == "get_account_transfers":
        ids = np.frombuffer(got, types.TRANSFER_DTYPE)["id_lo"]
        # Rows of every age, the held beat's among them.
        assert ids.min() < PER and ids.max() > handed_over + PER
        assert ((ids >= handed_over) & (ids < handed_over + 4000)).any()
    if case == "post_of_a_spilled_pending":
        assert got == b""                   # posted: no failure row
        for r in (worker, inline):
            assert r.sm.pending_status(6) == types.TransferPendingStatus.posted
    if case == "duplicate_id_below_base":
        (result,) = np.frombuffer(got, types.CREATE_RESULT_DTYPE)
        assert types.CreateTransferResult(
            int(result["result"])).name.startswith("exists_with_different")
    assert worker.sm.state_root() == inline.sm.state_root()
    assert worker.forest.manifest_blob() == inline.forest.manifest_blob()


# ----------------------------------------------------------------------
# (c) the bound.


def test_a_third_hand_over_waits_for_the_first_beat(tmp_path):
    storage, r = open_replica(tmp_path / "b.tigerbeetle", create=True)
    accounts(r)
    for op in range(3):
        commit(r, op)                        # the tail fills; first beat
    r.forest.barrier()
    assert BEATS_QUEUED_MAX == 2
    gate = Gate(r)
    commit(r, 3)
    commit(r, 4)
    assert r.forest.beats.queued() == 2 and gate.ran == 0
    assert lsm(r)["beat.queued"] == 2
    before = lsm(r)
    assert before["beat.bound_waits"] == 0
    ran_when_opened = []
    timer = threading.Timer(0.3, lambda: (ran_when_opened.append(gate.ran),
                                          gate.open()))
    timer.start()
    t0 = time.monotonic()
    commit(r, 5)                             # blocks on the oldest beat
    waited = time.monotonic() - t0
    timer.join()
    assert ran_when_opened == [0] and waited >= 0.25 and gate.ran >= 1
    after = lsm(r)
    assert after["beat.bound_waits"] == 1
    assert after["beat.bound_wait_us.count"] == 1
    assert after["beat.bound_wait_us.sum"] >= 250_000
    # The commit's own stage measured the hand-over, wait included.
    assert r.metrics.snapshot()["commit.beat_us.max"] >= 250_000
    r.forest.barrier()
    assert gate.ran == 3 and r.forest.beats.queued() == 0
    assert r.sm._store.spill.base == r.sm._store.base
    shut(storage, r)


# ----------------------------------------------------------------------
# (d) a failure on the worker.


def test_grid_full_on_the_worker_kills_the_replica_at_its_next_commit(tmp_path):
    """As tests/test_grid_capacity.py has it inline: the commit that
    needs more blocks than the limit gives stops the replica, and the
    error names both counts.  Here the beat raises on the worker; the
    commit that handed it over was acknowledged (it is in the WAL),
    the next hand-over raises on the loop's thread, and so does every
    one after it."""
    offset = ZoneLayout(config=CONFIG).forest_offset
    small = dataclasses.replace(CONFIG, name="lsm_worker_small",
                                storage_size_limit=offset + 48 * BLOCK_SIZE)
    storage, r = open_replica(tmp_path / "full.tigerbeetle", small, create=True)
    accounts(r)
    main = threading.current_thread()
    raised_on = []
    work = r._beat_work

    def watched(spill, budget):
        try:
            work(spill, budget)
        except GridFull:
            raised_on.append(threading.current_thread().name)
            raise

    r._beat_work = watched
    committed, acknowledged = 0, r.commit_min
    with pytest.raises(GridFull) as failure:
        for op in range(200):
            commit(r, op)
            committed, acknowledged = op + 1, r.commit_min
    assert threading.current_thread() is main
    assert raised_on == ["lsm-beat"]
    text = str(failure.value)
    assert "gives the forest 48 blocks" in text and "grid full" in text
    # What was acknowledged before the stop is in the journal; the
    # commit that met the error was prepared and never acknowledged.
    assert committed >= 3 and r.commit_min == acknowledged == r.op - 1
    assert r.journal.read_prepare(acknowledged) is not None
    # Sticky: the next commit, a read of the spill tier, a checkpoint
    # and close() all die of the same error.
    with pytest.raises(GridFull):
        commit(r, committed + 1)
    with pytest.raises(GridFull):
        r.on_request(*lookup([1]))
    with pytest.raises(GridFull):
        r.checkpoint()
    thread = r.forest.beats._worker._thread
    with pytest.raises(GridFull):
        r.close()
    assert stopped([thread])
    storage.close()


# ----------------------------------------------------------------------
# (e) a crash between an acknowledged commit and its beat.


def test_a_crash_before_the_beat_is_replayed_from_the_wal(tmp_path):
    """Two replicas commit the same stream past a checkpoint.  On one
    the beats of the last five commits never run (the process is gone
    before the worker reaches them); the other runs them all.  Reopened
    from disk, both replay the journal from the checkpoint and re-run
    the beats: the same forest, the same root, every transfer there."""
    n_ops, ends = 16, {}
    for name, lost in (("crashed", 5), ("whole", 0)):
        path = tmp_path / f"{name}.tigerbeetle"
        storage, r = open_replica(path, create=True)
        accounts(r)
        gate = None
        for op in range(n_ops):
            if lost and op == n_ops - lost:
                r.forest.barrier()
                gate = Gate(r)
                gate.drop = True
                gate.open()                  # the queue is dropped
            commit(r, op)
            if op == 7:
                checkpoint(r)
        if gate is not None:
            assert gate.ran == 0
            assert r.sm._store.base - r.sm._store.spill.base == lost * PER
        acknowledged = r.commit_min
        shut(storage, r)
        storage, r = open_replica(path)      # from disk: checkpoint + WAL
        assert r.commit_min == acknowledged
        r.forest.barrier()
        assert r.sm._store.spill.base == r.sm._store.base > 0
        ends[name] = (r.forest.manifest_blob(), r.sm.state_root(),
                      r.forest.grid.free_set.encode())
        if lost:
            for op in (0, 9, n_ops - lost, n_ops - 1):
                ids = np.zeros((PER, 2), "<u8")
                ids[:, 0] = np.arange(1 + op * PER, 1 + (op + 1) * PER)
                got = np.frombuffer(
                    r.on_request(int(Op.lookup_transfers), ids.tobytes()),
                    types.TRANSFER_DTYPE)
                assert len(got) == PER and (got["id_lo"] == ids[:, 0]).all(), op
        shut(storage, r)
    assert ends["crashed"] == ends["whole"]


# ----------------------------------------------------------------------
# (f) close() drains and stops the thread.


def stopped(threads, seconds: float = 10.0) -> bool:
    deadline = time.monotonic() + seconds
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    return not any(t.is_alive() for t in threads)


def test_close_drains_and_leaves_no_beat_thread(tmp_path):
    path = tmp_path / "rounds.tigerbeetle"
    storage, r = open_replica(path, create=True)
    accounts(r)
    for op in range(4):
        commit(r, op)
    r.forest.barrier()
    gate = Gate(r)
    commit(r, 4)
    gate.open_in(0.2)
    assert r.forest.beats.queued() == 1
    threads = [r.forest.beats._worker._thread]
    shut(storage, r)                         # close() waited for the beat
    assert gate.ran == 1 and r.sm._store.spill.base == r.sm._store.base
    path = tmp_path / "empty.tigerbeetle"    # nothing to replay: fast rounds
    shut(*open_replica(path, create=True))
    for _ in range(50):
        storage, r = open_replica(path)
        threads.append(r.forest.beats._worker._thread)
        assert threads[-1].is_alive() and threads[-1].name == "lsm-beat"
        shut(storage, r)
    assert len(set(threads)) == 51 and stopped(threads)
    # Closed is closed: idempotent, and no hand-over after it.
    r.close()
    with pytest.raises(AssertionError, match="closed SerialWorker"):
        r.forest.beats.submit(lambda: None)


# ----------------------------------------------------------------------
# The run encode the worker makes with the interpreter lock released.


@pytest.mark.parametrize("value_size,sparse,n", [
    (144, True, 32_760), (144, True, 1), (160, True, 5_000), (8, False, 65_520),
    (8, True, 4_099), (1, False, 777), (144, False, 900)])
def test_the_native_run_encode_is_block_payload_byte_for_byte(value_size, sparse, n):
    """`Tree._block_payload` defines a block's bytes; the one-pass
    native encode of a whole run (`fastpath.encode_run`, what a seal
    calls) gives the same payloads, block for block."""
    from tigerbeetle_tpu.lsm.runs import pack_u128
    from tigerbeetle_tpu.lsm.tree import Tree
    from tigerbeetle_tpu.runtime import fastpath
    from tigerbeetle_tpu.vsr.grid import Grid
    from tigerbeetle_tpu.vsr.storage import MemoryStorage

    rng = np.random.default_rng(value_size * 1000 + n)
    grid = Grid(MemoryStorage(ZoneLayout(config=cfg.TEST_MIN)), block_count=8)
    tree = Tree(grid, "t", value_size=value_size, sparse_values=sparse)
    keys = pack_u128(np.arange(n, dtype=np.uint64) * 3, rng.integers(0, 9, n).astype(np.uint64))
    flags = rng.integers(0, 2, n).astype(np.uint8)
    vals = rng.integers(0, 256, (n, value_size)).astype(np.uint8)
    # Runs of zero words, as the spilled objects have (the sparse codec's case).
    vals[rng.random((n, value_size)) < 0.6] = 0
    if value_size >= 16:
        vals[:, 8:16] = 0
    per_block = tree._per_block()
    got = fastpath.encode_run(keys, flags, vals, value_size, per_block,
                              tree.sparse_values)
    assert got is not None, "the native library is built on first use"
    want = [tree._block_payload(keys[at:at + per_block], flags[at:at + per_block],
                                vals[at:at + per_block])
            for at in range(0, n, per_block)]
    assert len(got) == len(want) == -(-n // per_block)
    assert got == want
    assert all(len(p) <= grid.payload_size for p in got)
