"""LSM engine: EWAH codec, FreeSet, Grid, Tree, Groove, Forest."""

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu.lsm import ewah
from tigerbeetle_tpu.lsm.runs import KEY_DTYPE, pack_u128
from tigerbeetle_tpu.lsm.tree import Tree, k_way_merge_flags
from tigerbeetle_tpu.lsm.forest import Forest
from tigerbeetle_tpu.vsr.free_set import FreeSet
from tigerbeetle_tpu.vsr.grid import Grid
from tigerbeetle_tpu.vsr.storage import MemoryStorage, ZoneLayout


def storage():
    return MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))


def grid(block_size=4096, block_count=1 << 10):
    return Grid(storage(), block_size=block_size, block_count=block_count)


# ----------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ewah_roundtrip(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 300))
    words = np.zeros(n, np.uint64)
    # Mix of runs of zeros, ones, and literals.
    for _ in range(10):
        at = int(rng.integers(n))
        ln = int(rng.integers(1, 30))
        kind = rng.integers(3)
        if kind == 0:
            words[at : at + ln] = 0
        elif kind == 1:
            words[at : at + ln] = np.uint64(0xFFFFFFFFFFFFFFFF)
        else:
            words[at : at + ln] = rng.integers(
                1, 1 << 63, min(ln, n - at), dtype=np.uint64
            )
    encoded = ewah.encode(words)
    np.testing.assert_array_equal(ewah.decode(encoded, n), words)
    # Compressible input compresses.
    uniform = np.zeros(1000, np.uint64)
    assert len(ewah.encode(uniform)) == 8


def test_free_set_reserve_acquire_forfeit():
    fs = FreeSet(64)
    r1 = fs.reserve(4)
    r2 = fs.reserve(4)
    a = [fs.acquire(r1), fs.acquire(r2), fs.acquire(r1)]
    assert len(set(a)) == 3
    fs.forfeit(r1)
    fs.forfeit(r2)
    assert fs.count_free() == 61
    # Release is staged until checkpoint.
    fs.release(a[0])
    assert not fs.is_free(a[0])
    fs.checkpoint()
    assert fs.is_free(a[0])
    # Round-trips through EWAH.
    fs2 = FreeSet.decode(fs.encode(), 64)
    np.testing.assert_array_equal(fs2.free, fs.free)


def test_grid_blocks_checksummed():
    g = grid()
    fs = g.free_set
    r = fs.reserve(2)
    a1, a2 = fs.acquire(r), fs.acquire(r)
    fs.forfeit(r)
    g.write_block(a1, b"hello world")
    g.write_block(a2, b"x" * 1000)
    assert g.read_block(a1) == b"hello world"
    assert g.verify_block(a2)
    # Corrupt the sector behind a2: verify fails (it probes the DISK,
    # leaving the cache alone), and a disk read raises.
    g.storage.corrupt_sector(g._offset(a2))
    assert not g.verify_block(a2)
    assert g.read_block(a2) == b"x" * 1000  # cache still serves RAM copy
    g._cache.remove(a2)
    with pytest.raises(RuntimeError):
        g.read_block(a2)


# ----------------------------------------------------------------------


def keys_of(ids):
    ids = np.asarray(ids, np.uint64)
    return pack_u128(ids, np.zeros(len(ids), np.uint64))


def test_tree_put_lookup_across_seals():
    t = Tree(grid(), "t", value_size=8, memtable_max=64)
    rng = np.random.default_rng(0)
    all_ids = rng.permutation(np.arange(1, 2001, dtype=np.uint64))
    for at in range(0, 2000, 50):
        chunk = all_ids[at : at + 50]
        t.put_batch(keys_of(chunk), chunk.astype("<u8").view("V8"))
        t.maybe_seal()
    assert any(t.levels[i] for i in range(7))  # actually spilled

    probe = rng.permutation(np.arange(1, 3001, dtype=np.uint64))
    found, values = t.lookup_batch(keys_of(probe))
    expect = probe <= 2000
    np.testing.assert_array_equal(found, expect)
    got = values.view("<u8").reshape(-1)[expect]
    np.testing.assert_array_equal(got, probe[expect])


def test_tree_overwrite_newest_wins():
    t = Tree(grid(), "t", value_size=8, memtable_max=16)
    ids = np.arange(1, 101, dtype=np.uint64)
    t.put_batch(keys_of(ids), ids.astype("<u8").view("V8"))
    t.seal_memtable()
    t.put_batch(keys_of(ids), (ids * 7).astype("<u8").view("V8"))
    t.seal_memtable()
    found, values = t.lookup_batch(keys_of(ids))
    assert found.all()
    np.testing.assert_array_equal(values.view("<u8").reshape(-1), ids * 7)


def test_tree_tombstones():
    t = Tree(grid(), "t", value_size=8, memtable_max=16)
    ids = np.arange(1, 101, dtype=np.uint64)
    t.put_batch(keys_of(ids), ids.astype("<u8").view("V8"))
    t.seal_memtable()
    t.remove_batch(keys_of(ids[:50]))
    t.seal_memtable()
    found, _ = t.lookup_batch(keys_of(ids))
    np.testing.assert_array_equal(found, ids > 50)
    # Compactions drop tombstones at the last populated level.
    for _ in range(20):
        t.put_batch(keys_of(ids[50:]), ids[50:].astype("<u8").view("V8"))
        t.seal_memtable()
    found, _ = t.lookup_batch(keys_of(ids))
    np.testing.assert_array_equal(found, ids > 50)


def test_tree_scan_range():
    t = Tree(grid(), "t", value_size=8, memtable_max=32)
    ids = np.arange(1, 301, dtype=np.uint64)
    t.put_batch(keys_of(ids), ids.astype("<u8").view("V8"))
    t.seal_memtable()
    t.put_batch(keys_of(np.array([500], np.uint64)),
                np.array([500], "<u8").view("V8"))
    lo = keys_of([100]).tobytes()
    hi = keys_of([200]).tobytes()
    keys, values = t.scan_range(lo, hi)
    assert len(keys) == 101
    np.testing.assert_array_equal(
        values.view("<u8").reshape(-1), np.arange(100, 201)
    )


def test_k_way_merge_newest_first():
    k1 = keys_of([1, 2, 3])
    k2 = keys_of([2, 3, 4])
    v = lambda a: np.asarray(a, "<u8").view(np.uint8).reshape(-1, 8)
    newest = (k1, np.zeros(3, np.uint8), v([10, 20, 30]))
    oldest = (k2, np.zeros(3, np.uint8), v([99, 99, 40]))
    keys, flags, vals = k_way_merge_flags([newest, oldest], 8)
    np.testing.assert_array_equal(
        vals.view("<u8").reshape(-1), [10, 20, 30, 40]
    )


# ----------------------------------------------------------------------


def test_groove_end_to_end_with_forest_checkpoint():
    st = storage()
    f = Forest(st, block_size=4096, block_count=1 << 10, memtable_max=64)
    g = f.groove("transfers", object_size=128, index_fields=["ledger", "code"])

    n = 500
    ids = np.arange(1, n + 1, dtype=np.uint64)
    ts = ids * 10
    objects = np.zeros((n, 128), np.uint8)
    objects[:, 0] = (ids & 0xFF).astype(np.uint8)
    ledgers = np.where(ids % 2 == 0, 7, 8).astype(np.uint64)
    codes = np.full(n, 3, np.uint64)
    g.insert_batch(ids, np.zeros(n, np.uint64), ts, objects,
                   {"ledger": ledgers, "code": codes})

    found, got_ts = g.lookup_ids(ids[:10], np.zeros(10, np.uint64))
    assert found.all()
    np.testing.assert_array_equal(got_ts, ts[:10])

    found, objs = g.get_objects(ts[:10])
    assert found.all()
    np.testing.assert_array_equal(objs[:, 0], ids[:10] & 0xFF)

    scan = g.index_scan("ledger", 7)
    np.testing.assert_array_equal(scan, ts[ids % 2 == 0])
    both = g.index_intersect([g.index_scan("ledger", 7), g.index_scan("code", 3)])
    np.testing.assert_array_equal(both, ts[ids % 2 == 0])

    # Checkpoint -> new forest over same storage -> identical reads.
    blob = f.checkpoint()
    f2 = Forest(st, block_size=4096, block_count=1 << 10, memtable_max=64)
    f2.groove("transfers", object_size=128, index_fields=["ledger", "code"])
    f2.open(blob)
    g2 = f2.grooves["transfers"]
    found, got_ts = g2.lookup_ids(ids, np.zeros(n, np.uint64))
    assert found.all()
    np.testing.assert_array_equal(got_ts, ts)
    np.testing.assert_array_equal(g2.index_scan("ledger", 8), ts[ids % 2 == 1])


def test_tree_scales_past_memtable():
    """State far exceeding the memtable spills and stays queryable."""
    t = Tree(grid(block_count=1 << 12), "big", value_size=8, memtable_max=256)
    rng = np.random.default_rng(3)
    ids = rng.permutation(np.arange(1, 20_001, dtype=np.uint64))
    for at in range(0, len(ids), 256):
        chunk = ids[at : at + 256]
        t.put_batch(keys_of(chunk), chunk.astype("<u8").view("V8"))
        t.maybe_seal()
    probe = rng.choice(ids, 1000, replace=False)
    found, values = t.lookup_batch(keys_of(probe))
    assert found.all()
    np.testing.assert_array_equal(values.view("<u8").reshape(-1), probe)


# ----------------------------------------------------------------------
# lookup_batch passes over a memtable batch or a run whose key span
# misses every unresolved key (PR 34).


def _lookup_every_source(tree, keys):
    """lookup_batch as it was without the span check: every memtable
    batch and every run is searched for every unresolved key."""
    n = len(keys)
    found = np.zeros(n, bool)
    resolved = np.zeros(n, bool)
    values = np.zeros((n, tree.value_size), np.uint8)
    for bkeys, bflags, bvals in reversed(tree.memtable):
        todo = np.flatnonzero(~resolved)
        pos = np.minimum(np.searchsorted(bkeys, keys[todo]), len(bkeys) - 1)
        hit = bkeys[pos] == keys[todo]
        resolved[todo[hit]] = True
        live = hit & (bflags[pos] == 0)
        found[todo[live]] = True
        values[todo[live]] = bvals[pos[live]]
    for run in tree._runs_newest_first():
        todo = np.flatnonzero(~resolved)
        if len(todo):
            tree._run_lookup(run, keys, todo, found, resolved, values)
    return found, values


def _fuzzed_tree(seed):
    """A tree as testing/fuzz.py fuzz_tree grows them: overlapping
    runs on several levels, overwrites, tombstones, a job in flight."""
    rng = np.random.default_rng(seed)
    t = Tree(grid(), "fuzz", value_size=8, memtable_max=64)
    lo, hi = 1000, 1500
    for _ in range(400):
        roll = rng.random()
        if roll < 0.6:
            ids = rng.integers(lo, hi, int(rng.integers(1, 40))).astype(np.uint64)
            t.put_batch(keys_of(ids), rng.integers(0, 1 << 62, len(ids)).astype(np.uint64))
        elif roll < 0.8:
            ids = rng.integers(lo, hi, int(rng.integers(1, 20))).astype(np.uint64)
            t.remove_batch(keys_of(ids))
        elif roll < 0.9:
            t.seal_memtable()
        else:
            t.maybe_seal()
        if rng.random() < 0.3:
            t.compact_beat(int(rng.integers(1, 6)))
    return t, rng, lo, hi


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("where", ["below", "inside", "above", "across"])
def test_lookup_with_the_span_check_answers_as_without(seed, where):
    t, rng, lo, hi = _fuzzed_tree(seed)
    assert sum(len(level) for level in t.levels) >= 3
    probe = {
        "below": rng.integers(0, lo, 64),
        "inside": rng.integers(lo, hi, 64),
        "above": rng.integers(hi, 2 * hi, 64),
        "across": rng.integers(0, 2 * hi, 64),
    }[where].astype(np.uint64)
    # Narrow batches too: they miss some runs and meet others.
    for keys in (keys_of(probe), keys_of(np.sort(probe)[:5]), keys_of(probe[:1])):
        found, values = t.lookup_batch(keys)
        want_found, want_values = _lookup_every_source(t, keys)
        np.testing.assert_array_equal(found, want_found)
        np.testing.assert_array_equal(values, want_values)
    if where in ("below", "above"):
        assert not found.any()


def test_lookup_reads_no_block_of_a_run_it_passes_over():
    """Row-keyed runs (each seal covers the rows above the one before,
    as the spill tier's object trees do): a batch of old rows meets one
    run, the rest are passed over on their spans, none of their blocks
    read; a key no run holds visits none at all."""
    t = Tree(grid(), "rows", value_size=8, memtable_max=1 << 20)
    per_run = 500
    for r in range(6):
        rows = np.arange(r * per_run, (r + 1) * per_run, dtype=np.uint64)
        t.put_batch(keys_of(rows), rows * 3)
        t.seal_memtable()
    tail = np.arange(6 * per_run, 6 * per_run + 100, dtype=np.uint64)
    t.put_batch(keys_of(tail), tail * 3)       # a memtable batch above all
    runs = list(t._runs_newest_first())
    assert len(runs) == 6
    read = []
    read_block = t._read_run_block
    t._read_run_block = lambda block: (read.append(block.address), read_block(block))[1]

    rows = np.arange(per_run + 10, per_run + 200, dtype=np.uint64)   # in run 1
    found, values = t.lookup_batch(keys_of(rows))
    assert found.all()
    np.testing.assert_array_equal(values.view("<u8").reshape(-1), rows * 3)
    assert t.stats.runs_consulted.value == 1
    assert t.stats.runs_skipped.value == 4     # runs 5..2; run 0 is never reached
    assert set(read) <= {b.address for b in t.levels[0][1].blocks}

    read.clear()
    found, _ = t.lookup_batch(keys_of(np.array([10 * per_run], np.uint64)))
    assert not found.any() and not read
    assert t.stats.runs_consulted.value == 1 and t.stats.runs_skipped.value == 10


# ----------------------------------------------------------------------
# Scan builder (lsm/scan_builder.py): condition trees over indexes.


def _scan_fixture(seed=0, n=500):
    """Groove of objects with two indexed fields; returns (groove,
    fields-as-arrays) for brute-force comparison."""
    from tigerbeetle_tpu.lsm.forest import Forest

    rng = np.random.default_rng(seed)
    f = Forest(storage(), block_size=4096, block_count=1 << 12)
    g = f.groove("things", object_size=16, index_fields=["color", "size"])
    ts = np.arange(1, n + 1, dtype=np.uint64)
    color = rng.integers(1, 5, n).astype(np.uint64)
    size = rng.integers(1, 4, n).astype(np.uint64)
    objects = np.zeros((n, 16), np.uint8)
    objects[:, 0] = color
    objects[:, 1] = size
    objects[:, 2:10] = ts.astype("<u8").view(np.uint8).reshape(n, 8)
    g.insert_batch(ts, np.zeros(n, np.uint64), ts, objects,
                   {"color": color, "size": size})
    return g, ts, color, size


def test_scan_builder_eq_matches_bruteforce():
    from tigerbeetle_tpu.lsm.scan_builder import ScanBuilder

    g, ts, color, size = _scan_fixture()
    b = ScanBuilder(g)
    got = b.evaluate(b.eq("color", 3))
    want = ts[color == 3]
    np.testing.assert_array_equal(got, want)


def test_scan_builder_union_intersect_range_direction_limit():
    from tigerbeetle_tpu.lsm.scan_builder import ScanBuilder, ScanLookup

    g, ts, color, size = _scan_fixture(seed=1)
    b = ScanBuilder(g)
    # (color==1 OR color==2) AND size==3, ts in [100, 400], newest
    # first, limit 7 — the get_account_transfers query shape
    # (reference: src/state_machine.zig:931-996).
    expr = b.intersect(
        b.union(b.eq("color", 1), b.eq("color", 2)),
        b.eq("size", 3),
    )
    got = b.evaluate(expr, ts_min=100, ts_max=400, reversed=True, limit=7)
    mask = ((color == 1) | (color == 2)) & (size == 3) & (ts >= 100) & (ts <= 400)
    want = ts[mask][::-1][:7]
    np.testing.assert_array_equal(got, want)

    rows = ScanLookup(g).fetch(got)
    assert rows.shape == (len(got), 16)
    got_ts = rows[:, 2:10].copy().view("<u8").reshape(-1)
    np.testing.assert_array_equal(got_ts, want)


def test_scan_builder_survives_seal_and_compaction():
    from tigerbeetle_tpu.lsm.scan_builder import ScanBuilder

    g, ts, color, size = _scan_fixture(seed=2, n=300)
    for t in (g.id_tree, g.object_tree, *g.indexes.values()):
        t.seal_memtable()
        t.compact()
    b = ScanBuilder(g)
    got = b.evaluate(b.union(b.eq("color", 4), b.eq("size", 2)))
    want = ts[(color == 4) | (size == 2)]
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------------------
# Set-associative cache (utils/cache.py —
# reference: src/lsm/set_associative_cache.zig).


def test_set_associative_cache_basics():
    from tigerbeetle_tpu.utils.cache import SetAssociativeCache

    c = SetAssociativeCache(capacity=16, ways=4)
    for k in range(8):
        c.put(k, k * 10)
    for k in range(8):
        assert c.get(k) == k * 10
    c.put(3, 999)
    assert c.get(3) == 999
    c.remove(3)
    assert c.get(3) is None and 3 not in c


def test_set_associative_cache_bounded_with_clock_eviction():
    from tigerbeetle_tpu.utils.cache import SetAssociativeCache

    c = SetAssociativeCache(capacity=16, ways=4)
    # Overfill 8x: stays bounded, recently-touched keys survive longer.
    for k in range(128):
        c.put(k, k)
    live = sum(1 for k in range(128) if k in c)
    assert live <= 16
    # Values that survive are always the correct ones, and the hit
    # counter tracks successful lookups (clock eviction is an LRU
    # APPROXIMATION — survival of any one key is not guaranteed).
    survivors = [k for k in range(128) if k in c]
    hits_before = c.hits
    for k in survivors:
        assert c.get(k) == k
    assert c.hits == hits_before + len(survivors)


def test_grid_cache_is_set_associative():
    g = grid()
    fs = g.free_set
    res = fs.reserve(4)
    addrs = [fs.acquire(res) for _ in range(4)]
    fs.forfeit(res)
    for a in addrs:
        g.write_block(a, bytes([a]) * 50)
    before = g._cache.misses
    for a in addrs:
        assert g.read_block(a) == bytes([a]) * 50
    assert g._cache.misses == before  # warm from write-through
