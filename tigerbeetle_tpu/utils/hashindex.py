"""Vectorized open-addressing hash index: u128 key -> u64 value.

The host-side id directories (account id -> slot, transfer id -> row;
the reference's IdTree role, src/lsm/groove.zig:136-176) sit on the
commit hot path with 3 batch lookups + 1 batch insert per commit.
Sorted-run searches over 16-byte void keys are memcmp-bound; this
table keeps keys as native uint64 limb pairs and does linear probing
with whole-batch numpy steps — each probe round is a handful of SIMD
ops over the still-unresolved lanes, and rounds shrink geometrically
(load factor is capped at ~0.5).

Deletions (create_accounts chain rollback only) leave tombstones:
lookups probe through them, inserts do not reuse them (rare enough
that reclaiming happens on the next growth rehash).
"""

from __future__ import annotations

import numpy as np

_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xC2B2AE3D27D4EB4F)
_M3 = np.uint64(0xFF51AFD7ED558CCD)


class HashIndex:
    def __init__(self, capacity: int = 1 << 16) -> None:
        assert capacity & (capacity - 1) == 0
        self._cap = capacity
        self._mask = np.uint64(capacity - 1)
        self.k_lo = np.zeros(capacity, np.uint64)
        self.k_hi = np.zeros(capacity, np.uint64)
        self.val = np.zeros(capacity, np.uint64)
        self.used = np.zeros(capacity, bool)
        self.dead = np.zeros(capacity, bool)
        self.count = 0
        self._tombstones = 0

    def _hash(self, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
        h = lo * _M1 + hi * _M2
        h ^= h >> np.uint64(33)
        h *= _M3
        h ^= h >> np.uint64(29)
        return h & self._mask

    def _grow(self, need: int) -> None:
        # Grow 4x: rehash work is the dominant insert cost, and quadrupling
        # keeps total rehash work ~1.33N instead of ~2N.
        while (self.count + self._tombstones + need) * 2 >= self._cap:
            self._cap *= 4
        live = np.flatnonzero(self.used & ~self.dead)
        k_lo, k_hi, val = self.k_lo[live], self.k_hi[live], self.val[live]
        self._mask = np.uint64(self._cap - 1)
        self.k_lo = np.zeros(self._cap, np.uint64)
        self.k_hi = np.zeros(self._cap, np.uint64)
        self.val = np.zeros(self._cap, np.uint64)
        self.used = np.zeros(self._cap, bool)
        self.dead = np.zeros(self._cap, bool)
        self.count = 0
        self._tombstones = 0
        self.insert(k_lo, k_hi, val)

    def insert(self, lo: np.ndarray, hi: np.ndarray, values: np.ndarray) -> None:
        """Batch insert; keys must be unique and not already present."""
        n = len(lo)
        if n == 0:
            return
        if (self.count + self._tombstones + n) * 2 >= self._cap:
            self._grow(n)
        lo = np.asarray(lo, np.uint64)
        hi = np.asarray(hi, np.uint64)
        values = np.asarray(values, np.uint64)
        pos = self._hash(lo, hi)
        pending = np.arange(n)
        one = np.uint64(1)
        while len(pending):
            p = pos[pending]
            occ = self.used[p]
            free = pending[~occ]
            if len(free):
                # Scatter all candidates; colliding writes resolve
                # last-writer-wins, and a read-back identifies the one
                # winner per bucket (keys are unique) — no sort needed.
                fp = pos[free]
                self.used[fp] = True
                self.k_lo[fp] = lo[free]
                self.k_hi[fp] = hi[free]
                self.val[fp] = values[free]
                placed = (self.k_lo[fp] == lo[free]) & (self.k_hi[fp] == hi[free])
                losers = free[~placed]
            else:
                losers = free
            stepped = np.concatenate([pending[occ], losers])
            pos[stepped] = (pos[stepped] + one) & self._mask
            pending = stepped
        self.count += n

    def lookup(self, lo: np.ndarray, hi: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Batch get -> (found bool array, values uint64)."""
        n = len(lo)
        found = np.zeros(n, bool)
        values = np.zeros(n, np.uint64)
        if n == 0 or self.count == 0:
            return found, values
        lo = np.asarray(lo, np.uint64)
        hi = np.asarray(hi, np.uint64)
        pos = self._hash(lo, hi)
        active = np.arange(n)
        one = np.uint64(1)
        while len(active):
            p = pos[active]
            occ = self.used[p]
            match = (
                occ
                & ~self.dead[p]
                & (self.k_lo[p] == lo[active])
                & (self.k_hi[p] == hi[active])
            )
            hit = active[match]
            found[hit] = True
            values[hit] = self.val[p[match]]
            cont = occ & ~match
            active = active[cont]
            pos[active] = (pos[active] + one) & self._mask
        return found, values

    def remove(self, lo: np.ndarray, hi: np.ndarray) -> None:
        """Tombstone existing keys (chain-rollback un-create)."""
        n = len(lo)
        if n == 0:
            return
        lo = np.asarray(lo, np.uint64)
        hi = np.asarray(hi, np.uint64)
        pos = self._hash(lo, hi)
        active = np.arange(n)
        one = np.uint64(1)
        removed = 0
        while len(active):
            p = pos[active]
            occ = self.used[p]
            match = (
                occ
                & ~self.dead[p]
                & (self.k_lo[p] == lo[active])
                & (self.k_hi[p] == hi[active])
            )
            mp = p[match]
            self.dead[mp] = True
            removed += len(mp)
            cont = occ & ~match
            active = active[cont]
            pos[active] = (pos[active] + one) & self._mask
        self.count -= removed
        self._tombstones += removed


# How a batch is filed (the ONE rule, in two copies: RunIndex here and
# native/tb_fastpath.cpp IdDir, which must agree on where an id lives).
# A batch is split wherever its ids stop following each other; it goes
# to the run list, whole, where its pieces average at least RUN_PIECES
# ids or are at most RUN_PIECES (a prepare of a few sessions' requests,
# a single id), and to the hash, whole, otherwise.  8: a run costs
# three u64s and a step of the binary search where a hashed id costs
# 52 bytes (26 a slot, at most half of them used) and a cache miss, so
# a list of runs that short is no smaller and no faster than the hash; the cells' gapped batches
# average 50-200 ids a piece, scattered ids 1, nothing lies between.
RUN_PIECES = 8
# The "at most RUN_PIECES" door holds the LIST to the same mean once it
# is longer than this: ids that arrive one a batch and never follow
# each other (a client that sends random ids singly) would otherwise
# grow it by a run, and an O(runs) shift, each.
RUN_LIST_FREE = 1 << 16
_NO_RUNS = np.empty((3, 0), np.uint64)


class RunIndex:
    """Id directory with run-length compression over sequential ids.

    TigerBeetle recommends (and its benchmark default generates)
    sequential ids (reference: src/tigerbeetle/cli.zig:80-101
    `id_order=sequential`; docs/coding/data-modeling.md time-based ids).
    Rows in the columnar stores are assigned in insert order, so a batch
    of contiguous ids maps to a contiguous row range — representable as
    one (start_id, len, start_val) run instead of 8190 hash entries; a
    batch with gaps (a created batch has one wherever a row failed) is
    as many runs as it has pieces.

    Same contract as HashIndex (insert keys unique & absent; remove keys
    present). Scattered batches (RUN_PIECES) fall back to the hash;
    lookups ask the runs first and the hash for what they did not
    answer. Runs are grouped by the high limb (virtually always a
    single group, id_hi == 0 or a fixed template prefix) and kept sorted
    by start for a vectorized searchsorted probe.
    """

    def __init__(self, capacity: int = 1 << 16) -> None:
        self._hash = HashIndex(capacity)
        # hi (int) -> (3, R) u64: starts (sorted), lens, vals
        self._runs: dict[int, np.ndarray] = {}
        self._run_count = 0  # ids the runs hold
        self.runs = 0  # runs, over all groups

    @property
    def count(self) -> int:
        return self._hash.count + self._run_count

    @property
    def hashed(self) -> int:
        return self._hash.count

    def insert(self, lo: np.ndarray, hi: np.ndarray, values: np.ndarray) -> int:
        """Batch insert -> the runs the batch was filed as, 0 where it
        went to the hash."""
        n = len(lo)
        if n == 0:
            return 0
        lo = np.asarray(lo, np.uint64)
        hi = np.asarray(hi, np.uint64)
        values = np.asarray(values, np.uint64)
        one = np.uint64(1)
        # A piece starts where the id, its high limb or the value does
        # not follow the one before; an id of 0 follows nothing (the
        # modular +1 of 2^64 - 1).
        head = np.empty(n, bool)
        head[0] = True
        np.not_equal(lo[1:], lo[:-1] + one, out=head[1:])
        head[1:] |= values[1:] != values[:-1] + one
        head[1:] |= hi[1:] != hi[:-1]
        head[1:] |= lo[1:] == 0
        at = np.flatnonzero(head)
        k = len(at)
        if k > n // RUN_PIECES and (
            k > RUN_PIECES
            or self.runs + k
            > max((self._run_count + n) // RUN_PIECES, RUN_LIST_FREE)
        ):
            self._hash.insert(lo, hi, values)
            return 0
        # (3, k): the pieces' starts, lengths, values.
        pieces = np.empty((3, k), np.uint64)
        pieces[0] = lo[at]
        pieces[1, :-1] = at[1:] - at[:-1]
        pieces[1, -1] = n - at[-1]
        pieces[2] = values[at]
        p_hi = hi[at]
        if k == 1 or (p_hi == p_hi[0]).all():
            self._file(int(p_hi[0]), pieces)
        else:
            for h in np.unique(p_hi):
                self._file(int(h), pieces[:, p_hi == h])
        self._run_count += n
        return k

    def _file(self, h: int, pieces: np.ndarray) -> None:
        """Place one group's new runs in ONE pass over its array (every
        stretch between them moves once, as a block), then join what
        abuts (ids and values both) across the boundaries a new run
        touches."""
        k = pieces.shape[1]
        if k > 1:
            pieces = pieces[:, np.argsort(pieces[0])]
        old = self._runs.get(h, _NO_RUNS)
        pos = np.searchsorted(old[0], pieces[0])
        new = pos + np.arange(k)
        g = np.empty((3, old.shape[1] + k), np.uint64)
        g[:, new] = pieces
        prev = 0
        for j, p in enumerate(pos.tolist()):
            g[:, prev + j : p + j] = old[:, prev:p]
            prev = p
        g[:, prev + k :] = old[:, prev:]
        self.runs += k
        b = np.concatenate((new - 1, new))
        if k > 1:
            b = np.unique(b)
        b = b[(b >= 0) & (b < g.shape[1] - 1)]
        starts, lens, vals = g
        end = lens[b]
        join = b[
            (starts[b] + end == starts[b + 1]) & (vals[b] + end == vals[b + 1])
        ]
        if len(join):
            # Run join[i] + 1 folds into the run before it; a chain of
            # them folds into the chain's first.
            first = np.ones(len(join), bool)
            first[1:] = join[1:] != join[:-1] + 1
            into = join[first][np.cumsum(first) - 1]
            np.add.at(lens, into, lens[join + 1])
            g = np.delete(g, join + 1, axis=1)
            self.runs -= len(join)
        self._runs[h] = g

    def lookup(self, lo: np.ndarray, hi: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        n = len(lo)
        if n == 0 or not self._runs:
            return self._hash.lookup(lo, hi)
        lo = np.asarray(lo, np.uint64)
        hi = np.asarray(hi, np.uint64)
        if (hi == hi[0]).all():
            found, values = self._probe(int(hi[0]), lo)
        else:
            found = np.zeros(n, bool)
            values = np.zeros(n, np.uint64)
            for h in np.unique(hi):
                lane = np.flatnonzero(hi == h)
                found[lane], values[lane] = self._probe(int(h), lo[lane])
        # The hash is asked only for what the runs did not answer, and
        # not at all while it is empty.
        if self._hash.count:
            if not found.any():
                return self._hash.lookup(lo, hi)
            miss = np.flatnonzero(~found)
            found[miss], values[miss] = self._hash.lookup(lo[miss], hi[miss])
        return found, values

    def _probe(self, h: int, lo: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        g = self._runs.get(h)
        if g is None:
            return np.zeros(len(lo), bool), np.zeros(len(lo), np.uint64)
        starts, lens, vals = g
        idx = np.searchsorted(starts, lo, side="right") - 1
        ic = np.maximum(idx, 0)
        off = lo - starts[ic]
        hit = (idx >= 0) & (off < lens[ic])
        if not hit.any():
            return hit, np.zeros(len(lo), np.uint64)
        return hit, np.where(hit, vals[ic] + off, np.uint64(0))

    def remove(self, lo: np.ndarray, hi: np.ndarray) -> None:
        n = len(lo)
        if n == 0:
            return
        lo = np.asarray(lo, np.uint64)
        hi = np.asarray(hi, np.uint64)
        in_hash, _ = self._hash.lookup(lo, hi)
        if in_hash.any():
            self._hash.remove(lo[in_hash], hi[in_hash])
        # Run splitting: rare (create_accounts chain rollback only).
        for k in np.flatnonzero(~in_hash):
            h = int(hi[k])
            g = self._runs.get(h)
            assert g is not None, "remove of absent key"
            starts, lens, vals = g
            i = int(np.searchsorted(starts, lo[k], side="right")) - 1
            off = lo[k] - starts[i]
            assert 0 <= off < lens[i], "remove of absent key"
            tail = lens[i] - off - np.uint64(1)
            if off == 0 and tail == 0:
                if g.shape[1] == 1:
                    del self._runs[h]
                else:
                    self._runs[h] = np.delete(g, i, axis=1)
                self.runs -= 1
            elif off == 0:
                starts[i] += np.uint64(1)
                vals[i] += np.uint64(1)
                lens[i] = tail
            elif tail == 0:
                lens[i] = off
            else:
                rest = (lo[k] + np.uint64(1), tail, vals[i] + off + np.uint64(1))
                lens[i] = off
                self._runs[h] = np.insert(g, i + 1, rest, axis=1)
                self.runs += 1
            self._run_count -= 1
