"""Hot/cold account tiering: an HBM-resident hot set over the Zipf head.

Every device path used to be capacity-bound to one HBM-resident table
(kernel_fast.DeviceTable, device_engine.DeviceEngine) while the
reference serves unbounded state from an LSM forest.  Reddio's shape
(arXiv:2503.04595) decouples execution from state residency: compute
the batch's touched-account set up front, prefetch the cold rows into
the device table BEFORE the execution step, and let HBM act as a cache
over the logical table instead of a hard ceiling on it.

This module owns the host-side tier state shared by both engine modes:

- ``HotTier``: the logical<->hot slot maps, LRU admission/eviction over
  a fixed hot-row budget, and the hit/miss/evict/prefetch counters the
  obs layer reads.
- The shared growth-policy helpers (``grow_zero_host`` /
  ``grow_zero_device``) behind the three previously near-identical
  ``grow()`` implementations (kernel_fast / mirror / device_engine) —
  tiering hooks ONE resize path, not three.
- ``mirror_hot_table8``: the hot-shaped upload/compare image built from
  the host mirror (the COLD TIER: the full logical table always lives
  in BalanceMirror host-side, persisted by the same checkpoint/LSM
  machinery as before — tiering changes which rows the DEVICE holds,
  never where the truth lives).

Protocol invariants (DESIGN.md "Hot/cold account tiering"):

- The hot map only changes against a QUIESCED device pipeline: every
  admission first drains in-flight windows and flushes the write-behind
  lane, so evicted rows are clean by construction (their bytes already
  landed on the mirror through the same lane that wrote them) and every
  packed batch launches under the map it was translated with.
- The 16-byte state root keeps covering the WHOLE logical table:
  the host commitment twin is logical-capacity-shaped and unchanged;
  the device maintains the HOT PARTIAL (per-row hashes bound to
  LOGICAL row ids), and ``fold(hot_partial, cold_partial) == root``
  because the r15 fold is an order-independent per-lane sum
  (commitment.HostCommitment.partial gives the host-side hot partial;
  cold_partial = digest - hot_partial).

``TB_HOT_CAPACITY`` (envcheck.hot_capacity) sizes the hot set; the
default 0 means all-resident — ``from_env`` returns None and every
caller's tiering branch is dead, bit-for-bit today's behavior.
"""

from __future__ import annotations

import numpy as np

from tigerbeetle_tpu import envcheck


def grow_zero_host(array: np.ndarray, capacity: int) -> np.ndarray:
    """Zero-widen a host (rows, ...) array to `capacity` rows.

    Returns the input unchanged when already wide enough.  All-zero
    rows hash to 0 under the commitment formula, so growth through
    this helper can never move a state root.
    """
    if capacity <= len(array):
        return array
    out = np.zeros((capacity,) + array.shape[1:], array.dtype)
    out[: len(array)] = array
    return out


def grow_zero_device(table, capacity: int, sharding, place):
    """Zero-widen a device (rows, C) table to `capacity` rows.

    Dense tables concatenate on-device (async — growth must not
    introduce a host round-trip on the commit path); sharded tables
    reshard through the host via `place` (row boundaries move between
    devices).  `table` may be a host array already fetched by the
    caller (the engine's was-sharded grow path).
    """
    import jax
    import jax.numpy as jnp

    have = table.shape[0]
    if capacity <= have:
        return table
    extra = jnp.zeros((capacity - have,) + table.shape[1:], table.dtype)
    if sharding is None:
        return jnp.concatenate([table, extra])
    return place(jnp.concatenate([jax.device_get(table), extra]))


def mirror_hot_table8(mirror, logical_of: np.ndarray) -> np.ndarray:
    """Hot-shaped (hot_rows, 8) device-layout image of the mirror:
    row i holds logical row logical_of[i], zeros for free hot slots —
    the upload/health-compare image for a TIERED device table (the
    tiered twin of BalanceMirror.table8)."""
    out = np.zeros((len(logical_of), 8), np.uint64)
    occ = logical_of >= 0
    rows = logical_of[occ]
    out[occ, 0::2] = mirror.lo[rows]
    out[occ, 1::2] = mirror.hi[rows]
    return out


def from_env(logical_capacity: int) -> "HotTier | None":
    """Build the tier for a table of `logical_capacity` rows, or None
    when TB_HOT_CAPACITY leaves the table all-resident (0/unset, or a
    budget that already covers every row).  Read at CONSTRUCTION time
    (the envcheck knob discipline), so one test process can build
    machines under different settings."""
    budget = envcheck.hot_capacity()
    if budget <= 0 or budget >= logical_capacity:
        return None
    return HotTier(logical_capacity, budget)


class HotTier:
    """Logical<->hot maps + LRU admission over a fixed hot-row budget.

    Counters are plain host ints (readable in both engine modes with
    zero obs dependency); when the owning state machine binds a
    ``stats`` sink (device_engine.make_tier_stats), mutations also land
    on the machine's metrics registry as dev_tier.* counters.
    """

    def __init__(self, logical_capacity: int, hot_rows: int) -> None:
        assert 0 < hot_rows < logical_capacity
        self.hot_rows = hot_rows
        self.logical_capacity = logical_capacity
        # logical row -> hot slot (-1 = cold).
        self.hot_of = np.full(logical_capacity, -1, np.int64)
        # hot slot -> logical row (-1 = free).
        self.logical_of = np.full(hot_rows, -1, np.int64)
        # LRU stamps: one monotone clock tick per batch keeps victim
        # selection frequency/recency-ordered over the Zipf head.
        self._stamp = np.zeros(hot_rows, np.int64)
        self._clock = 0
        self.hits = 0
        self.misses = 0
        self.evicts = 0
        self.prefetches = 0
        self.prefetch_stall_us = 0.0
        self.stats = None  # optional dev_tier.* registry sink

    # -- planning ------------------------------------------------------

    def plan(self, slots) -> tuple[np.ndarray, np.ndarray]:
        """(unique_logical, missing_logical) of a batch's touched set
        (negative entries — not-found joins — are ignored)."""
        uniq = np.unique(np.asarray(slots, np.int64))
        uniq = uniq[uniq >= 0]
        if len(uniq) == 0:
            return uniq, uniq
        return uniq, uniq[self.hot_of[uniq] < 0]

    def record_use(self, rows: np.ndarray, hits: int, misses: int) -> None:
        """Stamp the batch's (now-resident) rows for LRU and count the
        hit/miss split; one clock tick per batch."""
        self._clock += 1
        hot = self.hot_of[rows]
        self._stamp[hot[hot >= 0]] = self._clock
        self.hits += hits
        self.misses += misses
        if self.stats is not None:
            if hits:
                self.stats["hit"].inc(hits)
            if misses:
                self.stats["miss"].inc(misses)

    # -- admission -----------------------------------------------------

    def admit(self, missing: np.ndarray, protect: np.ndarray,
              partial: bool = False):
        """Assign hot slots to cold `missing` rows, reusing free slots
        first and then evicting the least-recently-used occupants whose
        logical rows are not in `protect` (the batch's own touched
        set).  Returns (admitted_logical, hot_slots, evicted_logical);
        None when the batch cannot fit and partial=False (caller takes
        the host path).  With partial=True a prefix of `missing` is
        admitted and the rest stays cold (host-mode write-behind, where
        the mirror is authoritative and cold deltas are simply
        dropped).  The CALLER holds the pipeline quiesced."""
        need = len(missing)
        free = np.flatnonzero(self.logical_of < 0)
        take_free = free[:need]
        n_evict = need - len(take_free)
        victims = np.zeros(0, np.int64)
        if n_evict > 0:
            occupied = np.flatnonzero(self.logical_of >= 0)
            evictable = occupied[
                ~np.isin(self.logical_of[occupied], protect)
            ]
            if len(evictable) < n_evict:
                if not partial:
                    return None
                n_evict = len(evictable)
            if n_evict > 0:
                order = np.argsort(self._stamp[evictable], kind="stable")
                victims = evictable[order[:n_evict]]
        hot_slots = np.concatenate([take_free, victims])
        admitted = missing[: len(hot_slots)]
        evicted = self.logical_of[victims]
        if len(evicted):
            self.hot_of[evicted] = -1
        self.hot_of[admitted] = hot_slots
        self.logical_of[hot_slots] = admitted
        self._stamp[hot_slots] = self._clock
        self.evicts += len(evicted)
        self.prefetches += 1
        if self.stats is not None:
            if len(evicted):
                self.stats["evict"].inc(len(evicted))
            self.stats["prefetch"].inc()
        return admitted, hot_slots, evicted

    def note_stall(self, seconds: float) -> None:
        """Account one admission barrier's wall time (the drain+flush+
        upload the batch waited on before its device step)."""
        us = seconds * 1e6
        self.prefetch_stall_us += us
        if self.stats is not None:
            self.stats["prefetch_stall_us"].inc(us)
            self.stats["prefetch_us"].observe(us)

    # -- geometry ------------------------------------------------------

    def occupied(self) -> np.ndarray:
        """Logical rows currently resident (any order)."""
        return self.logical_of[self.logical_of >= 0]

    def grow_logical(self, capacity: int) -> None:
        """Widen the logical address space; the hot-row budget is a
        fixed HBM allowance and stays put (that is the point: growth
        of the LOGICAL table no longer implies HBM growth)."""
        self.hot_of = grow_zero_host(self.hot_of, capacity)
        if capacity > self.logical_capacity:
            # grow_zero_host zero-fills; new rows are cold, not slot 0.
            self.hot_of[self.logical_capacity : capacity] = -1
            self.logical_capacity = capacity

    def translate(self, arr: np.ndarray) -> np.ndarray:
        """Hot-space copy of a logical slot array; negative entries
        (not-found joins) pass through unchanged.  Callers prefetch
        first, so mapped entries are never -1."""
        out = np.asarray(arr, np.int64).copy()
        m = out >= 0
        out[m] = self.hot_of[out[m]]
        return out
