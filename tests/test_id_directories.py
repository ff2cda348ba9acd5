"""The id directories (PR 38): `utils/hashindex.py RunIndex` and its
native mirror, `native/tb_fastpath.cpp IdDir`, file a batch as the runs
it is made of.  Both are driven with the same batches against a plain
`dict`: every key answers as the dict does, in both, and both file the
same batch the same way (the same runs, or the same hash).
"""

import time

import numpy as np
import pytest

from tigerbeetle_tpu.runtime import fastpath
from tigerbeetle_tpu.utils import RunIndex
from tigerbeetle_tpu.utils.hashindex import RUN_LIST_FREE

N = 8190
U64_MAX = 2**64 - 1


def _u64(vals):
    return np.array(list(vals), np.uint64)


class Pair:
    """A RunIndex, a native transfer-id directory and a dict, in
    lockstep: values are a batch's contiguous rows, as `tpu.py
    _index_created` files them."""

    def __init__(self):
        self.ix = RunIndex()
        self.native = fastpath.NativeFastpath(16)
        self.want: dict = {}
        self.row = 0

    def insert(self, lo, hi=None) -> int:
        lo = _u64(lo)
        hi = np.zeros(len(lo), np.uint64) if hi is None else _u64(hi)
        keys = list(zip(lo.tolist(), hi.tolist()))
        assert len(set(keys)) == len(keys) and not set(keys) & set(self.want)
        rows = np.arange(self.row, self.row + len(lo), dtype=np.uint64)
        filed = self.ix.insert(lo, hi, rows)
        self.native.add_transfer_ids(lo, hi, self.row)
        self.want.update(zip(keys, rows.tolist()))
        # A failed row leaves no row behind it, a session's next
        # request finds other sessions' rows in between: both happen.
        self.row += len(lo) + (len(lo) % 3 == 0)
        self.check()
        return filed

    def remove(self, keys) -> None:
        lo, hi = _u64(k[0] for k in keys), _u64(k[1] for k in keys)
        self.ix.remove(lo, hi)
        self.native.remove_transfer_ids(lo, hi)
        for k in keys:
            del self.want[k]
        self.check()

    def check(self) -> None:
        held = list(self.want)
        # Absent keys where a wrong run would answer: either side of
        # every held key, in its group and in another.
        near = {((lo + d) % 2**64, hi) for lo, hi in held[:: max(1, len(held) // 500)]
                for d in (-1, 1)} | {(lo, (hi + 1) % 2**64) for lo, hi in held[:50]}
        asked = held + [k for k in near if k not in self.want]
        lo, hi = _u64(k[0] for k in asked), _u64(k[1] for k in asked)
        want_found = np.array([k in self.want for k in asked])
        want_val = _u64(self.want.get(k, 0) for k in asked)
        found, val = self.ix.lookup(lo, hi)
        assert (found == want_found).all() and (val == want_val).all()
        n_found, n_val, n_runs, n_hashed = self.native.peek_transfer_ids(lo, hi)
        assert (n_found == want_found).all() and (n_val == want_val).all()
        assert self.ix.count == len(self.want)
        assert (self.ix.runs, self.ix.hashed) == (n_runs, n_hashed)
        assert self.ix.runs == sum(g.shape[1] for g in self.ix._runs.values())


def _gapped(rng, start, n, gaps):
    ids = np.arange(start, start + n, dtype=np.uint64)
    keep = np.ones(n, bool)
    keep[rng.choice(np.arange(1, n), gaps, replace=False)] = False
    return ids[keep]


# shape -> (rng, the next free id) -> the batches of one round.
def _clean(rng, at):
    return [np.arange(at, at + N, dtype=np.uint64)]


def _gaps(count):
    def shape(rng, at):
        return [_gapped(rng, at, N, count)]
    return shape


def _singles(rng, at):
    # A request of one transfer; then a prepare of three sessions' ones.
    return [[at], [at + 10**6, at + 1, at + 2 * 10**6]]


def _random(rng, at):
    return [rng.integers(2**40, 2**63, 500).astype(np.uint64) + np.uint64(at)]


def _interleaved(rng, at):
    a = np.arange(at, at + 300, dtype=np.uint64)
    b = np.arange(at + 10**7, at + 10**7 + 200, dtype=np.uint64)
    return [np.concatenate([a[:100], b[:50], a[100:], b[50:]])]


def _descending(rng, at):
    return [np.arange(at, at + 64, dtype=np.uint64)[::-1]]


SHAPES = {
    "clean": _clean, "gaps_1": _gaps(1), "gaps_41": _gaps(41),
    "gaps_half": _gaps(N // 2), "singles": _singles, "random": _random,
    "interleaved": _interleaved, "descending": _descending,
}
# Whether a round of the shape lands in the runs (its pieces are few) or
# in the hash (its ids are scattered).
IN_RUNS = {"clean": True, "gaps_1": True, "gaps_41": True, "gaps_half": False,
           "singles": True, "random": False, "interleaved": True,
           "descending": False}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_both_directories_answer_as_a_dict_does(shape, seed):
    rng = np.random.default_rng(seed)
    pair, at = Pair(), 1
    for round_ in range(4):
        for batch in SHAPES[shape](rng, at):
            filed = pair.insert(batch)
            assert (filed > 0) == IN_RUNS[shape], (round_, filed)
        at += 2 * N
        # Clean batches between, one that continues the last clean one's
        # ids and rows where nothing came between.
        pair.insert(np.arange(at, at + 100, dtype=np.uint64))
        at += 100 if rng.random() < 0.5 else 200
    assert (pair.ix.hashed == 0) == IN_RUNS[shape]


def test_a_clean_batch_that_follows_joins_its_run():
    pair = Pair()
    assert pair.insert(range(1, 101)) == 1          # 100 rows, then row 100
    assert pair.insert(range(101, 201)) == 1
    assert pair.ix.runs == 1
    # Ids that follow under rows that do not: a run of its own.
    pair.row += 5
    pair.insert(range(201, 301))
    assert pair.ix.runs == 2
    # Rows that follow under ids that do not: another.
    pair.insert(range(400, 500))
    assert pair.ix.runs == 3
    # A batch that fills the gap between two runs joins both where the
    # rows fit too: ids 10..19 and 30..39 under rows 0..9 and 20..29.
    ix = RunIndex()
    zeros = np.zeros(10, np.uint64)
    ix.insert(np.arange(10, 20, dtype=np.uint64), zeros, np.arange(10, dtype=np.uint64))
    ix.insert(np.arange(30, 40, dtype=np.uint64), zeros, np.arange(20, 30, dtype=np.uint64))
    assert ix.runs == 2
    ix.insert(np.arange(20, 30, dtype=np.uint64), zeros, np.arange(10, 20, dtype=np.uint64))
    assert ix.runs == 1 and ix.count == 30
    found, val = ix.lookup(np.arange(9, 41, dtype=np.uint64), np.zeros(32, np.uint64))
    assert found.tolist() == [False] + [True] * 30 + [False]
    assert val[1:31].tolist() == list(range(30))


def test_a_high_limb_splits_a_batch_and_keeps_its_group():
    pair = Pair()
    lo = list(range(10, 40)) + list(range(40, 70)) + list(range(100, 130))
    hi = [0] * 30 + [7] * 30 + [0] * 30
    assert pair.insert(lo, hi) == 3
    assert sorted(pair.ix._runs) == [0, 7]
    found, val = pair.ix.lookup(_u64([10, 40, 40, 100]), _u64([7, 0, 7, 0]))
    assert found.tolist() == [False, False, True, True]
    assert val.tolist() == [0, 0, 30, 60]
    pair.insert(range(100, 120), [2**64 - 1] * 20)


def test_a_run_does_not_cross_the_u64_wrap():
    pair = Pair()
    lo = [U64_MAX - 2, U64_MAX - 1, U64_MAX, 0, 1, 2]
    assert pair.insert(lo, [3] * 6) == 2
    found, val = pair.ix.lookup(_u64(lo), _u64([3] * 6))
    assert found.all() and val.tolist() == [0, 1, 2, 3, 4, 5]
    assert pair.ix._runs[3][0].tolist() == [0, U64_MAX - 2]


def test_remove_out_of_the_middle_of_a_split_run():
    rng = np.random.default_rng(7)
    pair = Pair()
    # Ten failed rows: pieces 1..99, 101..299, ..., 1901..2000.
    pair.insert([i for i in range(1, 2001) if i % 200 != 100])
    assert pair.ix.runs == 11
    pair.remove([(902, 0)])                 # splits the piece 901..1099
    assert pair.ix.runs == 12
    pair.remove([(901, 0)])                 # empties the one-id head
    assert pair.ix.runs == 11
    pair.remove([(2000, 0), (1, 0)])        # a tail, a head
    assert pair.ix.runs == 11
    # What was removed can come back, under new rows.
    assert pair.insert([902, 2000]) == 2
    assert pair.ix.runs == 13
    # A random batch is in the hash, and leaves it by the same door.
    ids = rng.integers(2**40, 2**63, 100).astype(np.uint64)
    assert pair.insert(ids) == 0
    pair.remove([(int(i), 0) for i in ids[:10]])
    assert pair.ix.hashed == 90


@pytest.mark.parametrize("cell, failed_rows, rows_a_failure", [
    ("bench1r-tpcc-pay-c4", 41, 2),    # a keying error fails both legs
    ("bench1r-chains2p-c4", 55, 3),    # 2% of chains of 1-7 fail whole
])
def test_the_hash_stays_empty_under_the_cells_gapped_batches(
        cell, failed_rows, rows_a_failure):
    """Four sessions, each its own id range; a request's created rows
    have a gap wherever a payment or a chain failed: `sm.ids.hashed`
    stays 0, and a lookup of the next request's ids (all absent, the
    plan's duplicate check) never asks the hash."""
    rng = np.random.default_rng(38)
    pair = Pair()
    nxt = [1 + s * 10**9 for s in range(4)]
    filed = []
    for req in range(12):
        if req == 1:
            pair.ix._hash.lookup = None    # a probe of the hash would raise
        s = req % 4
        ids = np.arange(nxt[s], nxt[s] + N, dtype=np.uint64)
        nxt[s] += N
        found, _ = pair.ix.lookup(ids, np.zeros(N, np.uint64))
        assert not found.any()
        keep = np.ones(N, bool)
        for at in rng.choice(N - rows_a_failure, failed_rows, replace=False):
            keep[at : at + rows_a_failure] = False
        rows = np.arange(pair.row, pair.row + keep.sum(), dtype=np.uint64)
        filed.append(pair.ix.insert(ids[keep], np.zeros(keep.sum(), np.uint64), rows))
        pair.native.add_transfer_ids(ids[keep], np.zeros(keep.sum(), np.uint64), pair.row)
        pair.row += int(keep.sum())
    assert pair.ix.hashed == 0 and pair.ix.count == pair.row
    assert all(failed_rows // 2 < f <= failed_rows + 1 for f in filed), filed
    *_, n_runs, n_hashed = pair.native.peek_transfer_ids([], [])
    assert (n_runs, n_hashed) == (pair.ix.runs, 0)


def test_singly_sent_scattered_ids_stop_growing_the_run_list():
    """Ids that come one a batch and never follow each other are runs
    of one while the list is short, and the hash's once it is long and
    averages under RUN_PIECES ids a run: the list cannot grow by a run,
    and a shift, an id."""
    ix = RunIndex()
    ids = np.random.default_rng(5).permutation(2 * RUN_LIST_FREE)[:RUN_LIST_FREE + 8]
    lo = (ids.astype(np.uint64) + np.uint64(1)) * np.uint64(3)
    # The list's first RUN_LIST_FREE runs, in one call each side of it.
    zero = np.zeros(1, np.uint64)
    ix._runs[0] = np.array([np.sort(lo[:RUN_LIST_FREE]), np.ones(RUN_LIST_FREE, np.uint64),
                   np.arange(RUN_LIST_FREE, dtype=np.uint64) * np.uint64(2)])
    ix.runs = ix._run_count = RUN_LIST_FREE
    for i in range(RUN_LIST_FREE, RUN_LIST_FREE + 8):
        assert ix.insert(lo[i : i + 1], zero, np.array([2 * i], np.uint64)) == 0
    assert (ix.runs, ix.hashed) == (RUN_LIST_FREE, 8)
    found, _ = ix.lookup(lo, np.zeros(len(lo), np.uint64))
    assert found.all()
    # A batch whose ids follow each other is still runs, gaps and all.
    assert ix.insert(np.arange(10**12, 10**12 + 100, dtype=np.uint64),
                     np.zeros(100, np.uint64), np.arange(100, dtype=np.uint64)) == 1
    gapped = _gapped(np.random.default_rng(6), 2 * 10**12, 1000, 20)
    assert 15 <= ix.insert(gapped, np.zeros(980, np.uint64),
                           np.arange(10**6, 10**6 + 980, dtype=np.uint64)) <= 21


def _best_ms(call, times=3) -> float:
    best = float("inf")
    for i in range(times):
        t0 = time.perf_counter()
        call(i)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def test_a_directory_of_500000_runs_stays_cheap():
    """The source's scale: 10M transfers at 1% failures are ~50,000
    runs, `small-c4`'s traffic there ~450,000 (one a request).  At
    500,000 runs a batch of 42 runs is filed with ONE shift of the list
    (it costs what filing one run costs, not 42 times that), and 8,190
    ids are looked up by as many binary searches.  Measured here, best
    of three, ms: RunIndex insert 2.9-4.8 (of one run 2.5-4.5: a fresh
    12 MB array either way), lookup of 8,190 present ids at random 4.0-4.6
    and of a batch's own absent ids 0.26; native insert 1.0-2.7, lookup
    3.3-4.3.  The limits are many times that, against a machine busy
    with other tests' work, and the ratio is what holds the mechanism."""
    runs = 500_000
    ix = RunIndex()
    starts = np.arange(runs, dtype=np.uint64) * np.uint64(16) + np.uint64(1)
    lens = np.full(runs, 8, np.uint64)
    ix._runs[0] = np.array([starts, lens, np.arange(runs, dtype=np.uint64) * np.uint64(9)])
    ix.runs, ix._run_count = runs, 8 * runs
    native = fastpath.NativeFastpath(16)
    # The native list is built through its own door, 50,000 runs a
    # batch: every run a piece of it, so one call files them all.
    ids = (starts[:, None] + np.arange(8, dtype=np.uint64)[None, :]).ravel()
    for at in range(0, len(ids), 400_000):
        part = ids[at : at + 400_000]
        native.add_transfer_ids(part, np.zeros(len(part), np.uint64), at * 2)
    assert native.peek_transfer_ids([], [])[2:] == (runs, 0)

    def batch_of(pieces, i):
        # Gapped batches in the middle of the list (another session's
        # range): each run lands between two runs that stand.
        base = int(starts[runs // 2 + 1000 * i + 100 * pieces]) + 8
        lo = np.concatenate([
            np.arange(base + 16 * j, base + 16 * j + 8, dtype=np.uint64)
            for j in range(pieces)])
        return lo, np.zeros(len(lo), np.uint64), 10**9 * (i + 1) + 10**6 * pieces

    def py_insert(pieces):
        def call(i):
            lo, hi, row = batch_of(pieces, i)
            assert ix.insert(lo, hi, np.arange(row, row + len(lo), dtype=np.uint64)) == pieces
        return call

    def native_insert(pieces):
        def call(i):
            lo, hi, row = batch_of(pieces, i)
            native.add_transfer_ids(lo, hi, row)
        return call

    one, many = _best_ms(py_insert(1)), _best_ms(py_insert(42))
    n_one, n_many = _best_ms(native_insert(1)), _best_ms(native_insert(42))
    assert ix.runs == runs + 3 * 43
    assert many < 8 * max(one, 0.5) and many < 250, (one, many)
    assert n_many < 8 * max(n_one, 0.5) and n_many < 100, (n_one, n_many)

    rng = np.random.default_rng(1)
    asked = np.concatenate([rng.choice(ids, N - 336), batch_of(42, 0)[0]])
    zeros = np.zeros(N, np.uint64)
    found = n_found = None

    def py_lookup(i):
        nonlocal found
        found, _ = ix.lookup(asked, zeros)

    def native_lookup(i):
        nonlocal n_found
        n_found, _, n_runs, n_hashed = native.peek_transfer_ids(asked, zeros)
        assert (n_runs, n_hashed) == (ix.runs, 0)

    assert _best_ms(py_lookup) < 100 and _best_ms(native_lookup) < 100
    assert found.all() and n_found.all()
    absent = np.arange(10**15, 10**15 + N, dtype=np.uint64)
    assert _best_ms(lambda i: ix.lookup(absent, zeros)) < 100


def test_the_state_machine_files_a_batch_with_failed_rows_as_runs():
    """Through `TpuStateMachine`: a batch in which some rows fail is
    filed as the runs its created rows make, in both directories; the
    counters say so; every created id is found again and answers
    `exists`, every failed id is still free."""
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
    from tigerbeetle_tpu.testing.harness import SingleNodeHarness, account, transfer

    sm = TpuStateMachine()
    h = SingleNodeHarness(sm)
    assert h.create_accounts([account(1), account(2)]) == []

    def batch(ids, bad):
        return [transfer(i, debit_account_id=1, credit_account_id=9 if i in bad else 2,
                         amount=1) for i in ids]

    bad = {110, 111, 150, 199}
    failed = h.create_transfers(batch(range(100, 200), bad))
    assert sorted(index + 100 for index, _ in failed) == sorted(bad)
    snap = sm.metrics.snapshot()
    assert snap["ids.runs_filed"] == 3 and snap["ids.hashed"] == 0   # 100.., 112.., 151..198
    assert snap["ids.runs"] == sm._tdir.runs == 3
    if sm._native is not None:
        assert sm._native.peek_transfer_ids([], [])[2:] == (3, 0)
    good = [i for i in range(100, 200) if i not in bad]
    assert [types.u128_get(r, "id") for r in h.lookup_transfers(range(100, 200))] == good
    # A second request over the same ids: the created ones exist, the
    # failed ones are created now, each a run of its own or joined.
    again = h.create_transfers(batch(range(100, 200), set()))
    assert sorted(index + 100 for index, _ in again) == good
    assert {int(result) for _, result in again} == {int(types.CreateTransferResult.exists)}
    snap = sm.metrics.snapshot()
    assert snap["ids.hashed"] == 0 and snap["ids.runs_filed"] == 6
    assert len(h.lookup_transfers(range(100, 200))) == 100
