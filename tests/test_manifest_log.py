"""Manifest log: append-only across checkpoints, self-compacting,
replay-exact (reference: src/lsm/manifest_log.zig:1-40)."""

import numpy as np

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu.lsm.forest import Forest
from tigerbeetle_tpu.lsm.runs import pack_u128
from tigerbeetle_tpu.vsr.storage import MemoryStorage, ZoneLayout


def make_forest(storage=None):
    storage = storage or MemoryStorage(
        ZoneLayout(config=cfg.TEST_MIN)
    )
    f = Forest(storage, block_count=1 << 12, memtable_max=64)
    f.groove("things", object_size=32, index_fields=["field"],
             index_value_size=8)
    return storage, f


def put_batch(groove, start, n):
    ids = np.arange(start, start + n, dtype=np.uint64)
    groove.insert_batch(
        ids, np.zeros(n, np.uint64), ids * 10,
        np.full((n, 32), 7, np.uint8),
        {"field": ids % 5},
    )


def test_checkpoint_appends_only_delta():
    storage, f = make_forest()
    g = f.grooves["things"]
    put_batch(g, 1, 200)
    blob1 = f.checkpoint()
    blocks_after_1 = list(f.mlog.blocks)
    assert blocks_after_1, "first checkpoint writes log blocks"

    put_batch(g, 201, 200)
    f.checkpoint()
    blocks_after_2 = list(f.mlog.blocks)
    # Append-only: with this small workload compaction must not have
    # triggered, the first checkpoint's blocks remain a prefix, and the
    # delta rides in newly appended blocks.
    assert len(blocks_after_2) >= len(blocks_after_1), (
        blocks_after_1, blocks_after_2,
    )
    assert blocks_after_2[: len(blocks_after_1)] == blocks_after_1


def test_replay_matches_live_state():
    storage, f = make_forest()
    g = f.grooves["things"]
    for k in range(6):
        put_batch(g, 1 + k * 300, 300)
        f.checkpoint()
    blob = f.checkpoint()

    storage2 = storage  # same blocks
    _, f2 = make_forest(storage2)
    f2.open(blob)
    g2 = f2.grooves["things"]
    ids = np.array([1, 500, 1200, 1799], np.uint64)
    found1, ts1 = g.lookup_ids(ids, np.zeros(4, np.uint64))
    found2, ts2 = g2.lookup_ids(ids, np.zeros(4, np.uint64))
    np.testing.assert_array_equal(found1, found2)
    np.testing.assert_array_equal(ts1, ts2)
    # Tree levels identical (same runs, same order).
    for t1, t2 in zip(f._trees, f2._trees):
        m1 = [[(r.id, [b.address for b in r.blocks]) for r in lvl]
              for lvl in t1.levels]
        m2 = [[(r.id, [b.address for b in r.blocks]) for r in lvl]
              for lvl in t2.levels]
        assert m1 == m2


def test_self_compaction_bounds_log():
    storage, f = make_forest()
    g = f.grooves["things"]
    # Many overwrites of the same keys: compactions churn runs, dead
    # events accumulate, and the log must keep compacting itself.
    for round_ in range(30):
        put_batch(g, 1, 128)
        f.checkpoint()
    live_runs = sum(
        len(lvl) for t in f._trees for lvl in t.levels
    )
    # Log events are bounded by ~2x live runs (+ flush slack), far
    # below the ~hundreds of events 30 churn rounds generated.
    assert f.mlog._events_total <= 2 * max(live_runs, 8) + 16, (
        f.mlog._events_total, live_runs,
    )
    blob = f.checkpoint()
    _, f2 = make_forest(storage)
    f2.open(blob)
    ids = np.arange(1, 129, dtype=np.uint64)
    found, _ts = f2.grooves["things"].lookup_ids(
        ids, np.zeros(len(ids), np.uint64)
    )
    assert found.all()


def test_mid_interval_snapshot_carries_tail():
    """A pure snapshot between checkpoints includes unflushed tail
    events; open() must replay them."""
    storage, f = make_forest()
    g = f.grooves["things"]
    put_batch(g, 1, 200)
    f.checkpoint()
    put_batch(g, 201, 200)  # seals mid-interval (memtable_max=64)
    blob = f.manifest_blob()  # NOT a checkpoint: tail unflushed
    assert len(f.mlog._tail) > 0 or f.mlog.blocks

    _, f2 = make_forest(storage)
    f2.open(blob)
    ids = np.array([1, 250, 400], np.uint64)
    found, _ = f2.grooves["things"].lookup_ids(
        ids, np.zeros(3, np.uint64)
    )
    assert found.all()


def test_oversized_run_splits_to_block_capacity():
    """A run with more block refs than fit one grid block must split
    into OP_ADD + continuation records sized from grid.payload_size —
    regression: a fixed 1024-ref split crashed checkpoint on 4KiB
    blocks."""
    from tigerbeetle_tpu.lsm.manifest_log import ManifestLog
    from tigerbeetle_tpu.vsr.grid import Grid

    st = MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))
    grid = Grid(st, block_size=4096, block_count=1 << 9)
    mlog = ManifestLog(grid)
    refs = [
        (1000 + i, 7, b"\x01" * 16, b"\x02" * 16) for i in range(300)
    ]
    mlog.run_add(5, 0, 1, refs)
    addresses = mlog.checkpoint()

    replayed = ManifestLog(grid).open(addresses)
    assert list(replayed.keys()) == [(5, 0, 1)]
    got = replayed[(5, 0, 1)]
    assert len(got) == 300
    assert [r[0] for r in got] == [1000 + i for i in range(300)]
