"""Durability: wire headers, journal recovery, superblock quorum,
single-replica crash/restart round-trips."""

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine import CpuStateMachine
from tigerbeetle_tpu.testing.harness import account, ids_bytes, pack, transfer
from tigerbeetle_tpu.vsr import replica as vsr_replica
from tigerbeetle_tpu.vsr import wire
from tigerbeetle_tpu.vsr.journal import Journal
from tigerbeetle_tpu.vsr.storage import FileStorage, MemoryStorage, ZoneLayout
from tigerbeetle_tpu.vsr.superblock import SuperBlock

CLUSTER = 7


def layout():
    return ZoneLayout(config=cfg.TEST_MIN)


def fresh_replica(storage=None, sm=None):
    storage = storage or MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(storage, CLUSTER, sm or CpuStateMachine(cfg.TEST_MIN))
    r.open()
    return storage, r


def reopen(storage):
    r = vsr_replica.Replica(storage, CLUSTER, CpuStateMachine(cfg.TEST_MIN))
    r.open()
    return r


# ----------------------------------------------------------------------
# Wire.


def test_header_roundtrip_and_checksum():
    h = wire.make_header(
        command=wire.Command.prepare, operation=types.Operation.create_transfers,
        cluster=CLUSTER, op=3, timestamp=99, parent=(1 << 100) + 5,
    )
    body = b"x" * 128
    wire.finalize_header(h, body)
    assert wire.verify_header(h, body)
    h2 = wire.header_from_bytes(h.tobytes())
    assert wire.verify_header(h2, body)
    assert wire.u128(h2, "parent") == (1 << 100) + 5
    # Any flipped byte must fail verification.
    raw = bytearray(h.tobytes())
    raw[40] ^= 0xFF
    assert not wire.verify_header(wire.header_from_bytes(bytes(raw)), body)
    assert not wire.verify_header(h, body + b"y")


def test_root_prepare_deterministic():
    a = vsr_replica.wire.root_prepare(5)
    b = vsr_replica.wire.root_prepare(5)
    assert a.tobytes() == b.tobytes()
    assert a["op"] == 0 and wire.verify_header(a, b"")


# ----------------------------------------------------------------------
# Journal.


def make_prepare(op, parent, body=b"", timestamp=None):
    h = wire.make_header(
        command=wire.Command.prepare, operation=types.Operation.create_accounts,
        cluster=CLUSTER, op=op, timestamp=timestamp or op * 10, parent=parent,
    )
    return wire.finalize_header(h, body)


def test_journal_write_read_recover():
    storage = MemoryStorage(layout())
    j = Journal(storage, CLUSTER)
    root = wire.root_prepare(CLUSTER)
    j.write_prepare(root, b"")
    parent = wire.u128(root, "checksum")
    for op in range(1, 6):
        h = make_prepare(op, parent, body=bytes([op]) * 100)
        j.write_prepare(h, bytes([op]) * 100)
        parent = wire.u128(h, "checksum")

    j2 = Journal(storage, CLUSTER)
    rec = j2.recover(commit_min=0)
    assert rec.op_head == 5
    assert not rec.faulty_ops and not rec.truncated_ops
    h, body = j2.read_prepare(3)
    assert body == b"\x03" * 100


def test_journal_torn_head_truncated():
    storage = MemoryStorage(layout())
    j = Journal(storage, CLUSTER)
    root = wire.root_prepare(CLUSTER)
    j.write_prepare(root, b"")
    parent = wire.u128(root, "checksum")
    for op in range(1, 4):
        h = make_prepare(op, parent)
        j.write_prepare(h, b"", sync=(op < 3))
        parent = wire.u128(h, "checksum")
    storage.crash()  # op 3 unsynced: prepare+header sectors revert

    rec = Journal(storage, CLUSTER).recover(commit_min=0)
    assert rec.op_head == 2
    assert rec.faulty_ops == []


def test_journal_corrupt_prepare_below_head_is_faulty():
    storage = MemoryStorage(layout())
    j = Journal(storage, CLUSTER)
    root = wire.root_prepare(CLUSTER)
    j.write_prepare(root, b"")
    parent = wire.u128(root, "checksum")
    for op in range(1, 5):
        h = make_prepare(op, parent)
        j.write_prepare(h, b"")
        parent = wire.u128(h, "checksum")
    storage.corrupt_sector(storage.layout.prepare_slot_offset(2))

    rec = Journal(storage, CLUSTER).recover(commit_min=0)
    assert rec.faulty_ops == [2]
    assert rec.op_head == 4


def test_journal_ring_wrap():
    slots = cfg.TEST_MIN.journal_slot_count
    storage = MemoryStorage(layout())
    j = Journal(storage, CLUSTER)
    root = wire.root_prepare(CLUSTER)
    j.write_prepare(root, b"")
    parent = wire.u128(root, "checksum")
    last = slots + 10
    for op in range(1, last + 1):
        h = make_prepare(op, parent)
        j.write_prepare(h, b"")
        parent = wire.u128(h, "checksum")

    rec = Journal(storage, CLUSTER).recover(commit_min=last - 5)
    assert rec.op_head == last


def _recover_both_ways(storage, commit_min, window=3):
    """Run recover() with the windowed prepares scan and with the full
    scan on identical storage; return both (Recovery, headers-ring,
    prepare-reads) triples."""
    out = []
    for probe_all in (False, True):
        j = Journal(storage, CLUSTER)
        j.RECOVER_HEAD_WINDOW = window
        j.RECOVER_PROBE_ALL = probe_all
        reads0 = storage.reads
        rec = j.recover(commit_min=commit_min)
        out.append((rec, j.headers.tobytes(), storage.reads - reads0))
    return out


def _assert_equivalent(windowed, full):
    (rec_w, ring_w, _), (rec_f, ring_f, _) = windowed, full
    assert rec_w.op_head == rec_f.op_head
    assert rec_w.faulty_ops == rec_f.faulty_ops
    assert rec_w.truncated_ops == rec_f.truncated_ops
    assert sorted(rec_w.headers) == sorted(rec_f.headers)
    for op in rec_w.headers:
        assert rec_w.headers[op].tobytes() == rec_f.headers[op].tobytes()
    assert ring_w == ring_f


def test_journal_windowed_recover_equivalence():
    """The windowed prepares scan (skip slots settled by the redundant
    ring) must classify every adversarial state exactly like the full
    scan — wraps, corruption below/above the checkpoint, an unsynced
    crash tail, and a stale wrapped redundant header — while reading
    fewer prepare slots."""
    slots = cfg.TEST_MIN.journal_slot_count

    def build(n_ops):
        storage = MemoryStorage(layout())
        j = Journal(storage, CLUSTER)
        root = wire.root_prepare(CLUSTER)
        j.write_prepare(root, b"")
        parent = wire.u128(root, "checksum")
        for op in range(1, n_ops + 1):
            h = make_prepare(op, parent, body=bytes([op & 0xFF]) * 64)
            j.write_prepare(h, bytes([op & 0xFF]) * 64)
            parent = wire.u128(h, "checksum")
        return storage

    # Clean wrapped ring: equivalence AND strictly fewer prepare reads.
    storage = build(slots + 12)
    w, f = _recover_both_ways(storage, commit_min=slots + 4)
    _assert_equivalent(w, f)
    assert w[2] < f[2]

    # Latent corruption below the checkpoint (settled region): both
    # scans must ignore it.
    storage = build(slots + 12)
    storage.corrupt_sector(storage.layout.prepare_slot_offset(
        (slots + 12 - 20) % slots))
    w, f = _recover_both_ways(storage, commit_min=slots + 4)
    _assert_equivalent(w, f)

    # Corruption above the checkpoint: both must report it faulty.
    storage = build(slots + 12)
    storage.corrupt_sector(storage.layout.prepare_slot_offset(
        (slots + 6) % slots))
    w, f = _recover_both_ways(storage, commit_min=slots + 4)
    _assert_equivalent(w, f)
    assert slots + 6 in w[0].faulty_ops

    # Crash with an unsynced tail.
    storage = build(slots + 8)
    j = Journal(storage, CLUSTER)
    rec = j.recover(commit_min=slots)  # fills j.headers
    parent = wire.u128(rec.headers[rec.op_head], "checksum")
    for op in range(slots + 9, slots + 12):
        h = make_prepare(op, parent, body=b"t" * 32)
        j.write_prepare(h, b"t" * 32, sync=(op < slots + 11))
        parent = wire.u128(h, "checksum")
    storage.crash()
    w, f = _recover_both_ways(storage, commit_min=slots + 2)
    _assert_equivalent(w, f)

    # Stale wrapped redundant: the prepare holds a NEW op but the
    # redundant sector still shows the old wrapped op (crash landed
    # between the two writes).  The slot sits below max_op, inside the
    # backward head window.
    storage = build(slots + 12)
    j = Journal(storage, CLUSTER)
    j.recover(commit_min=slots + 4)
    new_op = slots + 13
    stale_slot = new_op % slots
    h = make_prepare(
        new_op,
        wire.u128(j.headers[(slots + 12) % slots], "checksum"),
        body=b"n" * 48,
    )
    from tigerbeetle_tpu.vsr.storage import _sectors

    msg = h.tobytes() + b"n" * 48
    storage.write(
        storage.layout.prepare_slot_offset(stale_slot),
        msg.ljust(_sectors(len(msg)), b"\x00"),
    )
    storage.sync()  # prepare persisted, redundant sector NOT updated
    w, f = _recover_both_ways(storage, commit_min=slots + 5)
    _assert_equivalent(w, f)
    assert w[0].op_head == new_op

    # BACKWARD window: a LATER op's redundant persisted across the
    # crash while this op's did not, so the stale-redundant slot sits
    # BELOW max_op — only the backward branch of the head window
    # rescues it from being settled as its old wrapped op.
    storage = build(slots + 12)
    j = Journal(storage, CLUSTER)
    j.recover(commit_min=slots + 4)
    parent = wire.u128(j.headers[(slots + 12) % slots], "checksum")
    h13 = make_prepare(slots + 13, parent, body=b"a" * 48)
    msg = h13.tobytes() + b"a" * 48
    storage.write(
        storage.layout.prepare_slot_offset((slots + 13) % slots),
        msg.ljust(_sectors(len(msg)), b"\x00"),
    )
    h14 = make_prepare(
        slots + 14, wire.u128(h13, "checksum"), body=b"b" * 48
    )
    msg = h14.tobytes() + b"b" * 48
    storage.write(
        storage.layout.prepare_slot_offset((slots + 14) % slots),
        msg.ljust(_sectors(len(msg)), b"\x00"),
    )
    j.headers[(slots + 14) % slots] = h14
    j._write_header_sector((slots + 14) % slots)
    storage.sync()
    w, f = _recover_both_ways(storage, commit_min=slots + 5)
    _assert_equivalent(w, f)
    assert w[0].op_head == slots + 14


# ----------------------------------------------------------------------
# SuperBlock.


def test_superblock_quorum_and_sequence():
    storage = MemoryStorage(layout())
    sb = SuperBlock(storage, CLUSTER)
    sb.format(replica=0, replica_count=1)
    sb.checkpoint(
        commit_min=24, commit_min_checksum=123, commit_max=24,
        checkpoint_offset=storage.layout.grid_offset, checkpoint_size=100,
        checkpoint_checksum=9,
    )

    sb2 = SuperBlock(storage, CLUSTER)
    h = sb2.open()
    assert int(h["sequence"]) == 2
    assert int(h["commit_min"]) == 24

    # Corrupt two of four copies: quorum (2) still holds.
    storage.corrupt_sector(0)
    storage.corrupt_sector(4096)
    assert int(SuperBlock(storage, CLUSTER).open()["sequence"]) == 2

    # Three corrupt: no quorum.
    storage.corrupt_sector(2 * 4096)
    with pytest.raises(RuntimeError, match="no quorum"):
        SuperBlock(storage, CLUSTER).open()


# ----------------------------------------------------------------------
# Replica end-to-end.


def test_replica_basic_and_restart_replay():
    storage, r = fresh_replica()
    reply = r.on_request(types.Operation.create_accounts,
                         pack([account(1), account(2)]))
    assert reply == b""
    reply = r.on_request(
        types.Operation.create_transfers,
        pack([transfer(10, debit_account_id=1, credit_account_id=2, amount=100)]),
    )
    assert reply == b""

    # Restart from a fresh state machine: WAL replay must rebuild state.
    r2 = reopen(storage)
    assert r2.op == r.op
    out = r2.on_request(types.Operation.lookup_accounts, ids_bytes([1, 2]))
    rows = np.frombuffer(out, types.ACCOUNT_DTYPE)
    assert types.u128_get(rows[0], "debits_posted") == 100
    assert types.u128_get(rows[1], "credits_posted") == 100


def test_replica_crash_loses_unsynced_tail_only():
    storage, r = fresh_replica()
    r.on_request(types.Operation.create_accounts, pack([account(1), account(2)]))
    r.on_request(
        types.Operation.create_transfers,
        pack([transfer(10, debit_account_id=1, credit_account_id=2, amount=7)]),
    )
    storage.crash()  # everything synced: no loss

    r2 = reopen(storage)
    out = r2.on_request(types.Operation.lookup_accounts, ids_bytes([1]))
    assert types.u128_get(np.frombuffer(out, types.ACCOUNT_DTYPE)[0],
                          "debits_posted") == 7


def test_replica_checkpoint_and_wal_wrap():
    storage, r = fresh_replica()
    r.on_request(types.Operation.create_accounts, pack([account(1), account(2)]))
    # Push ops past several checkpoint intervals + full ring wraps.
    n_ops = cfg.TEST_MIN.journal_slot_count * 3
    for i in range(n_ops):
        r.on_request(
            types.Operation.create_transfers,
            pack([transfer(100 + i, debit_account_id=1, credit_account_id=2,
                           amount=1)]),
        )
    assert r.checkpoint_op > 0

    r2 = reopen(storage)
    assert r2.commit_min == r.commit_min
    out = r2.on_request(types.Operation.lookup_accounts, ids_bytes([1]))
    assert types.u128_get(np.frombuffer(out, types.ACCOUNT_DTYPE)[0],
                          "debits_posted") == n_ops


def test_replica_dedupe_replays_reply():
    storage, r = fresh_replica()
    r.register_client(42)
    b1 = r.on_request(types.Operation.create_accounts, pack([account(1)]),
                      client=42, request=1)
    assert b1 == b""
    # Same request again: no re-execution (account already exists would
    # return `exists`, so identical empty reply proves dedupe).
    b2 = r.on_request(types.Operation.create_accounts, pack([account(1)]),
                      client=42, request=1)
    assert b2 == b""
    # New request number does execute (and reports exists).
    b3 = r.on_request(types.Operation.create_accounts, pack([account(1)]),
                      client=42, request=2)
    arr = np.frombuffer(b3, types.CREATE_RESULT_DTYPE)
    assert types.CreateAccountResult(int(arr[0]["result"])).name == "exists"


def test_replica_two_phase_expiry_survives_restart(tmp_path):
    path = str(tmp_path / "data.tb")
    storage = FileStorage(path, layout(), create=True)
    vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(storage, CLUSTER, CpuStateMachine(cfg.TEST_MIN))
    r.open()
    r.on_request(types.Operation.create_accounts, pack([account(1), account(2)]))
    r.on_request(
        types.Operation.create_transfers,
        pack([transfer(10, debit_account_id=1, credit_account_id=2, amount=50,
                       timeout=1, flags=types.TransferFlags.pending)]),
    )
    storage.close()

    storage = FileStorage(path, layout())
    r2 = vsr_replica.Replica(storage, CLUSTER, CpuStateMachine(cfg.TEST_MIN))
    r2.open()
    # Advance realtime past expiry: pulse fires, pending releases.
    out = r2.on_request(types.Operation.lookup_accounts, ids_bytes([1]),
                        realtime=10 * types.NS_PER_S)
    row = np.frombuffer(out, types.ACCOUNT_DTYPE)[0]
    assert types.u128_get(row, "debits_pending") == 0
    ts = r2.sm.transfer_timestamp(10)
    assert r2.sm.pending_status(10) == types.TransferPendingStatus.expired
    assert ts is not None
    storage.close()


def test_crash_at_fsync_request_never_acked():
    """The crash-at-fsync fault point: an op whose WAL sync dies
    mid-call is never acked (on_request raises instead of returning a
    reply), and recovery shows no trace of it."""
    from tigerbeetle_tpu.vsr.storage import FsyncCrash

    storage, r = fresh_replica()
    r.on_request(types.Operation.create_accounts, pack([account(1), account(2)]))
    r.on_request(
        types.Operation.create_transfers,
        pack([transfer(10, debit_account_id=1, credit_account_id=2, amount=5)]),
    )
    op_before = r.op
    storage.crash_at_fsync = 1
    with pytest.raises(FsyncCrash):
        r.on_request(
            types.Operation.create_transfers,
            pack([transfer(11, debit_account_id=1, credit_account_id=2,
                           amount=900)]),
        )
    storage.crash()  # power loss: the unsynced op's sectors are lost

    r2 = reopen(storage)
    assert r2.op == op_before
    out = r2.on_request(types.Operation.lookup_accounts, ids_bytes([1]))
    assert types.u128_get(np.frombuffer(out, types.ACCOUNT_DTYPE)[0],
                          "debits_posted") == 5


def test_mid_async_checkpoint_crash_recovers_previous_superblock():
    """Crash between an async checkpoint's FREEZE (spill + snapshot +
    buffered blob write) and its background flip: the new superblock
    never landed, so recovery must come up from the PREVIOUS one and
    replay the WAL tail to the same state."""
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(cfg.TEST_MIN))
    r.open()
    r.on_request(types.Operation.create_accounts, pack([account(1), account(2)]))
    # Cross one full (synchronous) checkpoint so a durable previous
    # superblock exists, then commit a tail beyond it.
    n_ops = cfg.TEST_MIN.vsr_checkpoint_interval + 7
    for i in range(n_ops):
        r.on_request(
            types.Operation.create_transfers,
            pack([transfer(100 + i, debit_account_id=1, credit_account_id=2,
                           amount=2)]),
        )
    assert r.checkpoint_op > 0
    seq_before = int(r.superblock.working["sequence"])
    commit_before = r.commit_min

    # The async split's freeze half only: spill + snapshot + blob
    # write land in the page cache (unsynced); the flip never runs —
    # exactly the state a crash inside the background window leaves.
    r._checkpoint_freeze()
    storage.crash()

    r2 = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(cfg.TEST_MIN))
    r2.open()
    assert int(r2.superblock.working["sequence"]) == seq_before
    assert r2.checkpoint_op == r.checkpoint_op
    assert r2.commit_min == commit_before  # WAL replay covers the tail
    out = r2.on_request(types.Operation.lookup_accounts, ids_bytes([1, 2]))
    rows = np.frombuffer(out, types.ACCOUNT_DTYPE)
    assert types.u128_get(rows[0], "debits_posted") == 2 * n_ops
    assert types.u128_get(rows[1], "credits_posted") == 2 * n_ops


def test_free_set_quarantines_released_blocks_until_flip():
    """Blocks released by a frozen checkpoint become free (the blob
    encodes them free) but must not be REUSED while the previous
    superblock — which may reference them — is still the durable
    recovery root (async flip window)."""
    from tigerbeetle_tpu.vsr.free_set import FreeSet

    fs = FreeSet(8)
    res = fs.reserve(3)
    a, b, c = fs.acquire(res), fs.acquire(res), fs.acquire(res)
    fs.forfeit(res)
    fs.release(a)
    fs.release(b)
    fs.checkpoint()  # freeze: free again, but quarantined
    assert fs.is_free(a) and fs.is_free(b)
    res2 = fs.reserve(5)
    got = {fs.acquire(res2) for _ in range(5)}
    fs.forfeit(res2)
    assert a not in got and b not in got, "reused a quarantined block"
    # The blob must encode quarantined blocks as FREE (it is only read
    # once its own flip is durable).
    decoded = FreeSet.decode(fs.encode(), 8)
    assert decoded.is_free(a) and decoded.is_free(b)
    # The NEXT freeze releases the previous quarantine (deterministic
    # in the commit stream; the replica's checkpoint join guarantees
    # it postdates the durable flip).
    fs.checkpoint()
    res3 = fs.reserve(2)
    got3 = {fs.acquire(res3) for _ in range(2)}
    fs.forfeit(res3)
    assert got3 == {a, b}
    # Explicit early release stays available for standalone harnesses.
    fs.release(c)
    fs.checkpoint()
    fs.release_quarantine()
    res4 = fs.reserve(1)
    assert fs.acquire(res4) == c
    fs.forfeit(res4)


def test_replica_tpu_state_machine_checkpoint_restart():
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    storage = MemoryStorage(layout())
    vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(cfg.TEST_MIN))
    r.open()
    r.on_request(types.Operation.create_accounts, pack([account(1), account(2)]))
    n_ops = cfg.TEST_MIN.vsr_checkpoint_interval + 5  # cross one checkpoint
    for i in range(n_ops):
        r.on_request(
            types.Operation.create_transfers,
            pack([transfer(100 + i, debit_account_id=1, credit_account_id=2,
                           amount=2)]),
        )
    assert r.checkpoint_op > 0

    r2 = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(cfg.TEST_MIN))
    r2.open()
    assert r2.commit_min == r.commit_min
    out = r2.on_request(types.Operation.lookup_accounts, ids_bytes([1, 2]))
    rows = np.frombuffer(out, types.ACCOUNT_DTYPE)
    assert types.u128_get(rows[0], "debits_posted") == 2 * n_ops
    assert types.u128_get(rows[1], "credits_posted") == 2 * n_ops
