"""The grid is sized by the data file's storage limit (ISSUE 28, M1).

One number, `Config.storage_size_limit`, decides how many blocks the
LSM forest may hold; `format` records it in the superblock and `open`
reads it back.  A replica with room commits several times what a small
limit holds, across checkpoints and a restart, and reads every transfer
back; running out is a stop that names itself; a file formatted before
the limit existed (4,096 blocks in its checkpointed free set, no limit
in its superblock) opens grown.
"""

import dataclasses
import os

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing.harness import account, ids_bytes, pack, transfer
from tigerbeetle_tpu.vsr import replica as vsr_replica
from tigerbeetle_tpu.vsr.free_set import FreeSet, GridFull
from tigerbeetle_tpu.vsr.grid import Grid
from tigerbeetle_tpu.vsr.storage import (
    BLOCK_SIZE,
    SNAPSHOT_SPAN,
    FileStorage,
    MemoryStorage,
    ZoneLayout,
)

CLUSTER = 28
N_ACCOUNTS = 40
BATCH = 500
PARENT_BLOCKS = 1 << 12      # the parent's FOREST_BLOCK_COUNT
Op = types.Operation

# test_min's 4 KiB messages cap a batch at 30 events; a batch of 500
# fills blocks fast enough for a test.
BASE = cfg.Config(
    name="test_grid", message_size_max=1 << 16, lsm_batch_multiple=4,
    pipeline_prepare_queue_max=4, journal_slot_count=64, clients_max=4,
)


def with_blocks(blocks: int, base: cfg.Config = BASE) -> cfg.Config:
    """`base` with the storage limit that gives the forest `blocks`."""
    offset = ZoneLayout(config=base).forest_offset
    return dataclasses.replace(
        base, storage_size_limit=offset + blocks * BLOCK_SIZE)


def open_replica(storage, config):
    r = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(config))
    r.open()
    return r


def fresh(config):
    storage = MemoryStorage(ZoneLayout(config=config))
    vsr_replica.format(storage, CLUSTER)
    r = open_replica(storage, config)
    assert r.on_request(int(Op.create_accounts), pack(
        [account(i) for i in range(1, N_ACCOUNTS + 1)])) == b""
    return storage, r


def batch_of(first_id: int) -> bytes:
    return pack([
        transfer(first_id + j, debit_account_id=1 + (first_id + j) % N_ACCOUNTS,
                 credit_account_id=1 + (first_id + j + 1) % N_ACCOUNTS,
                 amount=1 + (first_id + j) % 7)
        for j in range(BATCH)])


def commit_batches(r, start: int, n: int, checkpoint_every: int = 6) -> int:
    """`n` batches from batch number `start`; -> checkpoints taken."""
    taken = 0
    for b in range(start, start + n):
        assert r.on_request(int(Op.create_transfers), batch_of(1 + b * BATCH)) == b""
        if b % checkpoint_every == checkpoint_every - 1:
            r.checkpoint()
            taken += 1
    return taken


def read_back(r, n_transfers: int) -> None:
    """Every transfer, by id, from wherever it lives now."""
    for at in range(1, n_transfers + 1, 400):
        ids = list(range(at, min(at + 400, n_transfers + 1)))
        rows = np.frombuffer(
            r.on_request(int(Op.lookup_transfers), ids_bytes(ids)),
            types.TRANSFER_DTYPE)
        assert rows["id_lo"].tolist() == ids
        assert rows["amount_lo"].tolist() == [1 + i % 7 for i in ids]


@pytest.mark.parametrize("config", [cfg.PRODUCTION, cfg.TEST_MIN, with_blocks(77)],
                         ids=lambda c: c.name)
def test_block_count_follows_the_configs_limit(config):
    layout = ZoneLayout(config=config)
    want = (config.storage_size_limit - layout.grid_offset
            - 2 * SNAPSHOT_SPAN) // BLOCK_SIZE
    assert layout.forest_block_count() == want
    storage = MemoryStorage(layout)
    vsr_replica.format(storage, CLUSTER)
    r = open_replica(storage, config)
    assert r.forest.grid.block_count == want
    snap = r.metrics.snapshot()
    assert snap["grid.blocks_total"] == want
    assert snap["grid.blocks_acquired"] == snap["grid.blocks_acquired_peak"] == 0


def test_the_presets_limits():
    """PRODUCTION: room for upstream's 10M transfers several times over
    (256 MiB of blocks held 0.95M).  TEST_MIN: twice the parent's count,
    a free set a test can fill."""
    assert ZoneLayout(config=cfg.PRODUCTION).forest_block_count() > 50 * PARENT_BLOCKS
    small = ZoneLayout(config=cfg.TEST_MIN).forest_block_count()
    assert PARENT_BLOCKS < small < 3 * PARENT_BLOCKS
    with pytest.raises(ValueError, match="leaves the\\s+forest no block"):
        ZoneLayout(config=dataclasses.replace(
            cfg.TEST_MIN, storage_size_limit=1 << 20)).forest_block_count()


def test_format_records_the_limit_and_open_reads_it_back():
    """The data file's own limit sizes the grid, whatever the
    configuration of the build that opens it says by then."""
    formatted, opened = with_blocks(96), with_blocks(300)
    storage = MemoryStorage(ZoneLayout(config=formatted))
    vsr_replica.format(storage, CLUSTER)
    storage.layout = ZoneLayout(config=opened)
    r = open_replica(storage, opened)
    assert int(r.superblock.working["storage_size_limit"]) == formatted.storage_size_limit
    assert r.forest.grid.block_count == 96
    assert r.forest.grid.free_set.block_count == 96
    assert r.metrics.snapshot()["grid.blocks_total"] == 96


def test_a_replica_commits_several_times_what_a_small_limit_holds():
    """32 blocks stop a replica after a few thousand transfers.  With
    eight times the room it commits over four times as many, across
    checkpoints and a restart, reads each back, and never holds the
    blocks the limit gives."""
    _storage, small = fresh(with_blocks(32))
    held = 0
    with pytest.raises(GridFull):
        for b in range(200):
            commit_batches(small, b, 1)
            held = (b + 1) * BATCH
    assert 2 * BATCH <= held <= 40 * BATCH

    roomy = with_blocks(8 * 32)
    storage, r = fresh(roomy)
    n_batches = 4 * held // BATCH + 6
    first = n_batches // 2
    checkpoints = commit_batches(r, 0, first)
    r = open_replica(storage, roomy)             # restart from the data file
    # What came after the last checkpoint is replayed from the journal.
    read_back(r, first * BATCH)
    checkpoints += commit_batches(r, first, n_batches - first)
    assert checkpoints >= 2
    read_back(r, n_batches * BATCH)
    fs = r.forest.grid.free_set
    assert 32 < fs.acquired_peak < fs.block_count == 256
    assert fs.acquired == fs.block_count - fs.count_free()
    snap = r.metrics.snapshot()
    assert snap["grid.blocks_acquired_peak"] == fs.acquired_peak
    assert snap["grid.blocks_acquired"] == fs.acquired <= fs.acquired_peak


def test_running_out_names_itself_on_test_min():
    """TEST_MIN's own limit, its free set all but full: the commit that
    needs more stops, and says what the limit gave and what is held."""
    storage = MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))
    vsr_replica.format(storage, CLUSTER)
    r = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(cfg.TEST_MIN))
    r.open()
    fs = r.forest.grid.free_set
    total = ZoneLayout(config=cfg.TEST_MIN).forest_block_count()
    hold = fs.reserve(total - 2)
    for _ in range(total - 2):
        fs.acquire(hold)
    fs.forfeit(hold)
    assert r.on_request(int(Op.create_accounts), pack(
        [account(i) for i in range(1, 5)])) == b""
    per = cfg.TEST_MIN.batch_max_create_transfers
    with pytest.raises(GridFull) as failure:
        for b in range(40):
            assert r.on_request(int(Op.create_transfers), pack([
                transfer(1 + b * per + j, debit_account_id=1,
                         credit_account_id=2, amount=1)
                for j in range(per)])) == b""
            if b % 4 == 3:
                r.checkpoint()
    text = str(failure.value)
    assert f"gives the forest {total} blocks" in text
    assert f"{total - 2} are held" in text or f"{total - 1} are held" in text \
        or f"{total} are held" in text
    assert "grid full" in text and isinstance(failure.value, RuntimeError)


def test_a_parent_formatted_file_opens_grown():
    """A data file as the parent commit left it: 4,096 blocks in the
    checkpointed free set, no limit in the superblock.  It opens at the
    configuration's limit: the held blocks stay held, the rest is free,
    everything reads back, and commits go on."""
    old = with_blocks(PARENT_BLOCKS)
    storage, r = fresh(old)
    commit_batches(r, 0, 12)
    held = r.forest.grid.free_set.acquired
    assert held > 0 and r.forest.grid.block_count == PARENT_BLOCKS
    # The parent's superblock has zeroes where the limit now lives.
    sb = r.superblock
    h = sb.working.copy()
    h["sequence"] = int(h["sequence"]) + 1
    h["storage_size_limit"] = 0
    sb._write(h)

    new = with_blocks(5 * PARENT_BLOCKS)
    storage.layout = ZoneLayout(config=new)
    r = open_replica(storage, new)
    fs = r.forest.grid.free_set
    assert r.forest.grid.block_count == fs.block_count == 5 * PARENT_BLOCKS
    assert fs.acquired == held and fs.free[PARENT_BLOCKS:].all()
    read_back(r, 12 * BATCH)
    commit_batches(r, 12, 12)
    read_back(r, 24 * BATCH)
    # And a checkpoint that spans more blocks than the limit gives is
    # refused by name, not read past the end.
    blob = r.forest.manifest_blob()
    storage.layout = ZoneLayout(config=old)
    small = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(old))
    with pytest.raises(RuntimeError, match="free set spans 20480 blocks"):
        small.forest.open(blob)


def test_a_production_replica_commits_past_what_4096_blocks_held(tmp_path):
    """The parent's served replica died of `grid full` after ~0.95M
    transfers: its forest had 4,096 blocks whatever the data file.  At
    the production configuration, in upstream's own batches of 8,190
    over its 10,000 accounts, a replica now commits 1.5M across three
    checkpoints and a restart, holds more than 4,096 blocks on the way,
    and reads every transfer back."""
    config, per, n_ops = cfg.PRODUCTION, 8190, 184
    path = str(tmp_path / "0_0.tigerbeetle")
    storage = FileStorage(path, ZoneLayout(config=config), create=True)
    vsr_replica.format(storage, CLUSTER)

    def start():
        r = vsr_replica.Replica(storage, CLUSTER, TpuStateMachine(
            config, account_capacity=1 << 14, transfer_capacity=1 << 24))
        r.open()
        return r

    r = start()
    for at in range(1, 10_001, 5000):
        assert r.on_request(int(Op.create_accounts), pack(
            [account(i) for i in range(at, at + 5000)])) == b""
    rng = np.random.default_rng(28)
    checkpoints = 0
    for op in range(n_ops):
        rows = np.zeros(per, types.TRANSFER_DTYPE)
        rows["id_lo"] = np.arange(1 + op * per, 1 + (op + 1) * per)
        debit = rng.integers(1, 10_001, per)
        rows["debit_account_id_lo"] = debit
        rows["credit_account_id_lo"] = (debit + rng.integers(0, 9_999, per)) % 10_000 + 1
        rows["amount_lo"] = rng.integers(1, 1000, per)
        rows["ledger"] = rows["code"] = 1
        assert r.on_request(int(Op.create_transfers), rows.tobytes()) == b""
        if op % 60 == 59:
            r.checkpoint()
            checkpoints += 1
        if op == 89:                        # 30 ops past a checkpoint
            r.close()
            r = start()                     # replays them from the journal
    r.forest.barrier()                      # the free set is the beat worker's
    fs = r.forest.grid.free_set
    assert checkpoints == 3
    assert PARENT_BLOCKS < fs.acquired_peak < fs.block_count == 236_539
    assert r.metrics.snapshot()["grid.blocks_acquired_peak"] == fs.acquired_peak
    for op in range(n_ops):
        ids = np.zeros((per, 2), "<u8")
        ids[:, 0] = np.arange(1 + op * per, 1 + (op + 1) * per)
        got = np.frombuffer(r.on_request(int(Op.lookup_transfers), ids.tobytes()),
                            types.TRANSFER_DTYPE)
        assert len(got) == per and (got["id_lo"] == ids[:, 0]).all(), op
    r.close()
    storage.close()
    for name in os.listdir(tmp_path):       # ~0.5 GB of journal and grid
        os.unlink(tmp_path / name)


def naive_reserve(fs: FreeSet, n: int) -> np.ndarray:
    """The parent's reservation: a pass over the whole set."""
    return np.flatnonzero(fs.free & ~fs._reserved_mask & ~fs.quarantine)[:n]


def test_a_reservation_looks_where_the_parents_did_at_a_fraction_of_the_walk():
    """Same windows as a pass over the whole set, whatever was reserved,
    forfeited, released or checkpointed before: the cursor only skips
    what cannot be reserved."""
    rng = np.random.default_rng(28)
    fs = FreeSet(20_000)
    held: list[int] = []
    for step in range(600):
        roll = rng.random()
        if roll < 0.6:
            n = int(rng.integers(0, 40))
            want = naive_reserve(fs, n)
            res = fs.reserve(n)
            assert np.array_equal(res.blocks, want), step
            take = int(rng.integers(0, n + 1))
            held += [fs.acquire(res) for _ in range(take)]
            fs.forfeit(res)
        elif roll < 0.85 and held:
            for _ in range(int(rng.integers(1, 30))):
                if held:
                    fs.release(held.pop(int(rng.integers(len(held)))))
        else:
            fs.checkpoint()
            if roll > 0.95:
                fs.release_quarantine()
        assert fs.acquired == fs.block_count - fs.count_free()
    assert fs.acquired_peak >= fs.acquired > 0
    # At the production count a reservation in a set whose front is
    # held reads a few thousand flags, not a quarter of a million.
    big = FreeSet(ZoneLayout(config=cfg.PRODUCTION).forest_block_count())
    res = big.reserve(100_000)
    for _ in range(100_000):
        big.acquire(res)
    big.forfeit(res)
    res = big.reserve(8)
    assert res.blocks.tolist() == list(range(100_000, 100_008))
    assert big._scan_from == 100_008
    big.forfeit(res)
    back = FreeSet.decode(big.encode(), big.block_count)
    assert np.array_equal(back.free, big.free) and back.acquired == 100_000
    with pytest.raises(GridFull, match="236539 blocks, 100000 are held"):
        big.reserve(big.block_count)


def test_a_block_at_the_far_end_costs_its_own_bytes(tmp_path):
    """The data file stays sparse: a block never acquired costs no disk,
    wherever the limit puts the end of the grid."""
    layout = ZoneLayout(config=cfg.PRODUCTION)
    storage = FileStorage(str(tmp_path / "0_0.tigerbeetle"), layout, create=True)
    count = layout.forest_block_count()
    grid = Grid(storage, block_count=count, base_offset=layout.forest_offset)
    payload = os.urandom(5000)
    grid.write_block(count, payload)
    grid.flush_writes()
    grid._cache = type(grid._cache)(capacity=4, ways=4)
    assert grid.read_block(count) == payload
    stat = os.stat(str(tmp_path / "0_0.tigerbeetle.grid"))
    assert stat.st_size > 14 << 30 and stat.st_blocks * 512 < 1 << 20
    storage.close()
