"""LSM spill tier: the state machine's durable state scales past RAM.

VERDICT r1 item 2's acceptance test: commit more transfer state than
the memtable holds across several checkpoints, restart from disk, and
answer every query class from the LSM tier — with checkpoint blobs
O(RAM tail), not O(history).  The CPU oracle (dict-backed, no forest)
replays the same stream as the semantic reference.
"""

import numpy as np

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine import CpuStateMachine
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


def _device_replica():
    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(
        storage, CLUSTER,
        TpuStateMachine(CONF, account_capacity=1 << 12, engine="device"),
    )
    r.open()
    return r


def test_finalisers_of_cold_pendings_on_the_device_engine():
    """A payments switch's posts and voids arrive after their pendings
    have left the RAM tail (PR 33): the two-phase kernel's host join
    reads them from the object tree, the finalise rewrites their
    status there, and a second finalise, after a seal and a compaction
    have merged the overwritten keys, reads the NEW status back.
    Every reply, balance and stored row against the CPU oracle."""
    r = _device_replica()
    storage_cpu = MemoryStorage(layout())
    vsr_replica.format(storage_cpu, CLUSTER)
    r_cpu = vsr_replica.Replica(storage_cpu, CLUSTER, CpuStateMachine(CONF))
    r_cpu.open()
    rng = np.random.default_rng(33)
    n_acct, per, batches = 8, 400, 4

    def both(op, body):
        got = r.on_request(int(op), body)
        assert got == r_cpu.on_request(int(op), body)
        return got

    both(Op.create_accounts, pack([account(i) for i in range(1, n_acct + 1)]))
    pend_ids = []
    for b in range(batches):
        rows = []
        for k in range(per):
            tid = 1000 + b * per + k
            dr = int(rng.integers(1, n_acct + 1))
            rows.append(transfer(tid, debit_account_id=dr,
                                 credit_account_id=dr % n_acct + 1,
                                 amount=int(rng.integers(1, 500)),
                                 flags=int(TF.pending)))
            pend_ids.append(tid)
        assert both(Op.create_transfers, pack(rows)) == b""

    def settle():
        """A spill of the whole tail, a seal of every memtable and a
        full compaction: what beats do over a longer run."""
        r.checkpoint()
        for groove in r.forest.grooves.values():
            groove.object_tree.seal_memtable()
        r.forest.compact()

    sm = r.sm
    settle()
    assert sm._store.base >= batches * per      # every pending is cold
    snap = lambda: sm.metrics.snapshot()  # noqa: E731
    cold0 = snap()["store.join_cold_rows"]

    def finalisers(first_id, targets, void_every):
        return pack([
            transfer(first_id + k, pending_id=int(p), amount=0,
                     flags=int(TF.void_pending_transfer if k % void_every == 0
                               else TF.post_pending_transfer))
            for k, p in enumerate(targets)])

    first = rng.permutation(pend_ids)[:per]
    assert both(Op.create_transfers, finalisers(5000, first, 3)) == b""
    s = snap()
    assert s["store.join_cold_rows"] - cold0 == per
    assert s["store.status_overwrites"] == per
    assert s["plan.join_cold_us.count"] >= 1
    assert s["dev.kind.two_phase_lo.batches"] == 1
    assert s["dev.fallback_batches"] == 0 and s["fallback_events"] == 0

    # The overwritten keys now sit in a younger run than the rows they
    # overwrite; a compaction has to keep the younger.
    settle()
    again = np.concatenate([first[:100], rng.permutation(
        [p for p in pend_ids if p not in set(first.tolist())])[:per - 100]])
    reply = both(Op.create_transfers, finalisers(7000, again, 2))
    got = np.frombuffer(reply, types.CREATE_RESULT_DTYPE)
    assert len(got) == 100 and (got["index"] == np.arange(100)).all()
    R = types.CreateTransferResult
    want = [R.pending_transfer_already_voided if k % 3 == 0
            else R.pending_transfer_already_posted for k in range(100)]
    assert got["result"].tolist() == [int(x) for x in want]
    s = snap()
    assert s["store.status_overwrites"] == 2 * per - 100
    assert s["dev.kind.two_phase_lo.batches"] == 2
    assert s["dev.fallback_batches"] == 0 and s["fallback_events"] == 0
    assert s["host_semantic_events"] == 0

    both(Op.lookup_accounts, ids_bytes(list(range(1, n_acct + 1))))
    both(Op.lookup_transfers, ids_bytes(
        [int(x) for x in first[:50]] + list(range(5000, 5050))
        + list(range(7000, 7120))))
    sm.verify_device_mirror()
