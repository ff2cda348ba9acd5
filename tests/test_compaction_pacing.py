"""Paced compaction: bounded per-beat work, correct reads mid-merge.

reference: src/lsm/compaction.zig:1-32 (beats of a bar),
src/lsm/forest.zig:846 (CompactionPipeline) — merge debt is spread
across commits instead of stalling checkpoints.
"""

import numpy as np
import pytest

from tigerbeetle_tpu.lsm.runs import KEY_DTYPE, pack_u128
from tigerbeetle_tpu.lsm.tree import GROWTH, Tree
from tigerbeetle_tpu.vsr.storage import MemoryStorage, ZoneLayout
from tigerbeetle_tpu.vsr.grid import Grid
from tigerbeetle_tpu import constants as cfg


def make_tree(memtable_max=64, value_size=8):
    layout = ZoneLayout(config=cfg.TEST_MIN)
    storage = MemoryStorage(layout)
    grid = Grid(storage, block_size=1 << 12, block_count=1 << 10)
    return Tree(grid, "t", value_size=value_size, memtable_max=memtable_max)


def put_range(tree, lo, hi, tag):
    keys = pack_u128(
        np.arange(lo, hi, dtype=np.uint64), np.zeros(hi - lo, np.uint64)
    )
    vals = np.full(hi - lo, tag, np.uint64)
    tree.put_batch(keys, vals)


def check_values(tree, expect: dict):
    ids = np.fromiter(expect.keys(), np.uint64)
    keys = pack_u128(ids, np.zeros(len(ids), np.uint64))
    found, vals = tree.lookup_batch(np.asarray(keys, KEY_DTYPE))
    assert found.all()
    got = vals.view(np.uint64).reshape(-1)
    want = np.fromiter(expect.values(), np.uint64)
    assert (got == want).all()


def test_beats_are_bounded_and_reads_stay_correct():
    tree = make_tree(memtable_max=64)
    expect = {}
    # Create deep merge debt: many seals, overlapping key ranges so
    # merges actually dedupe (newest tag wins).
    for round_ in range(GROWTH * 3):
        lo = (round_ % 4) * 100
        put_range(tree, lo, lo + 64, tag=round_)
        for k in range(lo, lo + 64):
            expect[k] = round_
        tree.seal_memtable()
    assert tree.compaction_pending()
    budget = 4
    beats = 0
    while tree.compaction_pending():
        used = tree.compact_beat(budget)
        assert used <= budget
        beats += 1
        assert beats < 10_000
        # Reads must be correct at EVERY intermediate state.
        if beats % 7 == 0:
            check_values(tree, expect)
    check_values(tree, expect)
    # The level shape invariant holds after draining.
    for level in range(len(tree.levels) - 1):
        assert len(tree.levels[level]) <= tree._level_run_max(level)


def test_seals_during_job_survive():
    tree = make_tree(memtable_max=64)
    expect = {}
    for round_ in range(GROWTH + 1):
        put_range(tree, 0, 64, tag=round_)
        expect.update({k: round_ for k in range(64)})
        tree.seal_memtable()
    assert tree.compaction_pending()
    # Advance the job partially, then seal NEW data mid-job.
    tree.compact_beat(2)
    put_range(tree, 1000, 1064, tag=77)
    expect.update({k: 77 for k in range(1000, 1064)})
    tree.seal_memtable()
    # Newer version of an existing key, mid-job.
    put_range(tree, 0, 8, tag=99)
    expect.update({k: 99 for k in range(8)})
    tree.seal_memtable()
    while tree.compaction_pending():
        tree.compact_beat(3)
    check_values(tree, expect)


def test_tombstones_drop_only_at_last_level():
    tree = make_tree(memtable_max=32)
    put_range(tree, 0, 32, tag=1)
    tree.seal_memtable()
    keys = pack_u128(np.arange(0, 16, dtype=np.uint64), np.zeros(16, np.uint64))
    tree.remove_batch(np.asarray(keys, KEY_DTYPE))
    tree.seal_memtable()
    for _ in range(GROWTH):
        put_range(tree, 100, 132, tag=2)
        tree.seal_memtable()
    while tree.compaction_pending():
        tree.compact_beat(4)
    found, _ = tree.lookup_batch(np.asarray(keys, KEY_DTYPE))
    assert not found.any()
    check_values(tree, {k: 1 for k in range(16, 32)})


def _forest_fixture():
    from tigerbeetle_tpu.lsm.forest import Forest

    layout = ZoneLayout(config=cfg.TEST_MIN)
    storage = MemoryStorage(layout)
    forest = Forest(storage, block_size=1 << 12, block_count=1 << 10,
                    memtable_max=64)
    forest.groove("obj", object_size=16, index_fields=[])
    return storage, forest


def _fill(forest, rounds, rng):
    g = forest.grooves["obj"]
    objs_by_id = {}
    for round_ in range(rounds):
        ids = np.arange(1 + round_ * 64, 1 + round_ * 64 + 64, dtype=np.uint64)
        objs = rng.integers(0, 2**63, (64, 2), np.uint64)
        # Interleaved timestamps across rounds: object-tree key ranges
        # OVERLAP, so its merges are real (disjoint inputs would take
        # the metadata move path and finish instantly).
        ts = (np.arange(64, dtype=np.uint64) + np.uint64(1)) * np.uint64(
            1000
        ) + np.uint64(round_)
        g.insert_batch(ids, np.zeros(64, np.uint64), ts,
                       objs.view(np.uint8), {})
        for i, v in zip(ids, objs):
            objs_by_id[int(i)] = v
    return objs_by_id


def _check_objects(forest, objs_by_id):
    g = forest.grooves["obj"]
    ids = np.fromiter(objs_by_id.keys(), np.uint64)
    found, ts = g.lookup_ids(ids, np.zeros(len(ids), np.uint64))
    assert found.all()
    found2, objs = g.get_objects(ts)
    assert found2.all()
    want = np.stack([objs_by_id[int(i)] for i in ids])
    assert (objs.view(np.uint64).reshape(len(ids), 2) == want).all()


def test_checkpoint_drains_active_jobs_only():
    """Checkpoints finish ACTIVE merge jobs (deterministic blobs — no
    job state crosses a checkpoint) but do not start merges for other
    over-full levels; those wait for the next interval's beats."""
    storage, forest = _forest_fixture()
    rng = np.random.default_rng(3)
    objs_by_id = _fill(forest, GROWTH * 2, rng)
    forest.compact_beat(4)  # starts (at least) one job
    assert any(t._job is not None for t in forest._trees)
    forest.checkpoint()
    assert all(t._job is None for t in forest._trees)
    _check_objects(forest, objs_by_id)
    while forest.compaction_pending():
        forest.compact_beat(8)
    _check_objects(forest, objs_by_id)


def test_midinterval_snapshot_orphan_reclaim():
    """A mid-interval snapshot (state sync path) taken with a merge in
    flight records the job's output blocks as orphans; a restore
    reclaims them, cancels the stale job, and the restarted merge
    reaches the same served state."""
    from tigerbeetle_tpu.lsm.forest import Forest

    storage, forest = _forest_fixture()
    rng = np.random.default_rng(3)
    objs_by_id = _fill(forest, GROWTH * 2, rng)
    forest.compact_beat(4)
    assert any(t._job is not None for t in forest._trees)
    blob = forest.manifest_blob()  # NOT a checkpoint: job in flight
    forest2 = Forest(storage, block_size=1 << 12, block_count=1 << 10,
                     memtable_max=64)
    forest2.groove("obj", object_size=16, index_fields=[])
    forest2.open(blob)
    assert all(t._job is None for t in forest2._trees)
    _check_objects(forest2, objs_by_id)
    while forest2.compaction_pending():
        forest2.compact_beat(8)
    forest2.checkpoint()  # activates the staged orphan releases
    _check_objects(forest2, objs_by_id)
