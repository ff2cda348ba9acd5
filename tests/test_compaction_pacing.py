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


# ----------------------------------------------------------------------
# A merge leaves the level it lands in alone (ISSUE 32): level L's runs
# become ONE new run of level L+1; only the last level merges in place.


def put_striped(tree, round_, n=64, stride=1000):
    """`n` keys that no other round writes, spread over the whole key
    range: every run overlaps every other and nothing dedupes, as an
    index tree's (slot, timestamp) keys."""
    ids = np.arange(n, dtype=np.uint64) * np.uint64(stride) + np.uint64(round_)
    tree.put_batch(pack_u128(ids, np.zeros(n, np.uint64)),
                   np.full(n, round_, np.uint64))
    return {int(k): round_ for k in ids}


def addresses(run):
    return [b.address for b in run.blocks]


def drain(tree, budget, expect, absent=None, every=3):
    """Beats of `budget` blocks until nothing is pending, reads checked
    at intermediate states (`absent`: keys that must stay not found);
    -> entries the merges read (counted at the block reads, so the
    count means the same on any tree)."""
    read = [0]
    inner = tree._read_run_block

    def counted(block):
        out = inner(block)
        read[0] += len(out[0])
        return out

    beats = 0
    while tree.compaction_pending():
        tree._read_run_block = counted
        try:
            assert tree.compact_beat(budget) <= budget
        finally:
            del tree._read_run_block
        beats += 1
        assert beats < 10_000
        if beats % every == 0 or not tree.compaction_pending():
            check_values(tree, expect)
            if absent is not None:
                assert not tree.lookup_batch(absent)[0].any()
    return read[0]


def test_a_merge_appends_one_run_and_leaves_the_levels_runs_alone():
    tree = make_tree(memtable_max=64)
    expect = {}
    for round_ in range(GROWTH + 1):
        expect.update(put_striped(tree, round_))
        tree.seal_memtable()
    drain(tree, 4, expect)
    assert [len(level) for level in tree.levels[:3]] == [0, 1, 0]
    first = tree.levels[1][0]
    held = addresses(first)
    # The second overflow, with new versions of some keys the first
    # run holds: the new run shadows them, the old run stays as it is.
    for round_ in range(GROWTH + 1, 2 * GROWTH + 2):
        expect.update(put_striped(tree, round_))
        if round_ % 3 == 0:
            put_range(tree, 0, 8, tag=round_)   # 0 is round 0's first key
            expect.update({k: round_ for k in range(8)})
        tree.seal_memtable()
    taken = sum(r.count for r in tree.levels[0])
    before = tree.stats.entries_in.value
    assert drain(tree, 4, expect) == taken
    assert tree.stats.entries_in.value - before == taken
    assert [len(level) for level in tree.levels[:3]] == [0, 2, 0]
    assert tree.levels[1][0] is first and addresses(first) == held
    assert tree.levels[1][1].id > first.id
    assert not set(addresses(tree.levels[1][1])) & set(held)
    assert not any(tree.grid.free_set.is_free(a) for a in held)
    assert tree.stats.jobs.value == 2 and tree.stats.moves.value == 0
    assert tree.stats.runs_peak.value == GROWTH + 2


def test_entries_through_merges_stay_linear_in_entries_ingested():
    """40 seals of keys that all overlap: every entry is read by one
    merge a level it descends.  Rewriting level 1 whole at each merge
    of level 0 (the tree before ISSUE 32) reads 9 + 18 + 27 + 36 seals'
    worth for the 36 merged here, and more with every merge after."""
    tree = make_tree(memtable_max=64)
    expect = {}
    read = 0
    for round_ in range(40):
        expect.update(put_striped(tree, round_))
        tree.seal_memtable()
        read += drain(tree, 6, expect)
    ingested = 40 * 64
    in_use = sum(1 for level in tree.levels if level)
    assert in_use == 2 and len(tree.levels[1]) == 4
    assert read <= (in_use - 1) * ingested < (in_use + 1) * ingested
    assert read == tree.stats.entries_in.value == tree.stats.entries_out.value


def run_flags(tree, run):
    return tree._read_run_all(run)[1]


def test_a_tombstone_outlives_every_older_run_and_no_longer():
    tree = make_tree(memtable_max=32)
    expect = {}
    put_range(tree, 0, 32, tag=1)
    expect.update({k: 1 for k in range(32)})
    tree.seal_memtable()
    rounds = iter(range(100, 10_000))
    dead = None

    def overflow(seals):
        for _ in range(seals):
            expect.update(put_striped(tree, next(rounds), n=32))
            tree.seal_memtable()
        drain(tree, 4, expect, absent=dead, every=1)

    overflow(GROWTH)
    assert len(tree.levels[1]) == 1          # holds keys 0..32, live
    dead = np.asarray(pack_u128(
        np.arange(16, dtype=np.uint64), np.zeros(16, np.uint64)), KEY_DTYPE)
    tree.remove_batch(dead)
    for k in range(16):
        del expect[k]
    tree.seal_memtable()
    # Merged into level 1 beside the run with the live keys: kept.
    overflow(GROWTH)
    assert len(tree.levels[1]) == 2
    assert run_flags(tree, tree.levels[1][0]).sum() == 0
    assert run_flags(tree, tree.levels[1][1]).sum() == 16
    # Level 1 overflows into an empty level 2 with nothing below: the
    # tombstones and the keys they hid both end there.
    while not tree.levels[2]:
        overflow(GROWTH + 1)
    assert [len(level) for level in tree.levels[2:]] == [1, 0, 0, 0, 0]
    assert run_flags(tree, tree.levels[2][0]).sum() == 0
    assert not any(run_flags(tree, r).any() for r in tree.levels[1])
    assert tree.levels[2][0].count == len(expect) - sum(
        r.count for r in tree.levels[1])


def test_the_last_level_merges_in_place():
    from tigerbeetle_tpu.lsm.tree import LEVELS

    tree = make_tree(memtable_max=64)
    expect = {}
    last = LEVELS - 1

    def file_run(level, round_, dead=()):
        expect.update(put_striped(tree, round_))
        if len(dead):
            tree.remove_batch(np.asarray(pack_u128(
                np.asarray(dead, np.uint64), np.zeros(len(dead), np.uint64)),
                KEY_DTYPE))
            for k in dead:
                del expect[k]
        tree.seal_memtable()
        tree.levels[level].append(tree.levels[0].pop())

    for round_ in range(2):                  # two runs, as moves leave them
        file_run(last, round_)
    old = [a for r in tree.levels[last] for a in addresses(r)]
    for round_ in range(2, GROWTH + 3):
        file_run(last - 1, round_, dead=[0, 1001] if round_ == 5 else ())
    assert tree._over_full_level() == last - 1
    drain(tree, 4, expect, absent=np.asarray(pack_u128(
        np.array([0, 1001], np.uint64), np.zeros(2, np.uint64)), KEY_DTYPE))
    assert [len(level) for level in tree.levels] == [0] * last + [1]
    out = tree.levels[last][0]
    assert out.count == len(expect) and not run_flags(tree, out).any()
    assert not set(addresses(out)) & set(old)
    assert tree.stats.entries_in.value == len(expect) + 4   # 2 dead, 2 hidden


def test_a_restore_through_the_manifest_log_keeps_a_multi_run_level():
    from tigerbeetle_tpu.lsm.forest import Forest

    storage, forest = _forest_fixture()
    rng = np.random.default_rng(32)
    ids = np.arange(1, 65, dtype=np.uint64)

    def write(forest, round_, newest):
        # The same 64 ids every round: the id tree's runs all overlap
        # and only the newest version of an id may be served.
        objs = rng.integers(0, 2**63, (64, 2), np.uint64)
        ts = np.arange(64, dtype=np.uint64) + np.uint64(1 + round_ * 64)
        forest.grooves["obj"].insert_batch(
            ids, np.zeros(64, np.uint64), ts, objs.view(np.uint8), {})
        newest.update(zip(ids.tolist(), objs))
        forest.compact_beat(8)

    newest = {}
    rounds = 3 * (GROWTH + 1) + 2
    for round_ in range(rounds):
        write(forest, round_, newest)
    blob = forest.checkpoint()
    id_tree = forest.grooves["obj"].id_tree
    shape = [[r.id for r in level] for level in id_tree.levels]
    assert len(shape[1]) == 3 and shape[1] == sorted(shape[1])
    for round_ in range(rounds, rounds + 4):   # lost with the crash
        write(forest, round_, {})
    forest2 = Forest(storage, block_size=1 << 12, block_count=1 << 10,
                     memtable_max=64)
    g2 = forest2.groove("obj", object_size=16, index_fields=[])
    forest2.open(blob)
    assert [[r.id for r in level] for level in g2.id_tree.levels] == shape
    _check_objects(forest2, newest)
    for round_ in range(rounds, rounds + GROWTH):
        write(forest2, round_, newest)
        _check_objects(forest2, newest)
    assert len(g2.id_tree.levels[1]) == 4


@pytest.mark.parametrize("seed", [3, 32])
def test_reads_match_a_model_through_paced_merges(seed):
    """The tree fuzzer at a length that crosses merges (its smoke tier,
    60 operations, seals six times and opens none): puts that
    overwrite, removes, seals and beats of 1-5 blocks against a dict,
    lookups landing mid-merge.  A merge that let level L+1's version of
    a key win over level L's (the tree before ISSUE 32) fails it."""
    from tigerbeetle_tpu.testing.fuzz import fuzz_tree

    fuzz_tree(seed, 400)
