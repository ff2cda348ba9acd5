"""Host-side exact mirror of the account-balance table.

The device (HBM) table is the authoritative balance store, but a
round-trip to it costs ~wire latency, so the commit hot path must never
wait on the device. The host keeps a bit-exact mirror of the four u128
balance columns and uses it for:

- fast-path admission: the monotone-overflow check (see
  kernel_fast.py) runs against the mirror, so no device sync is needed
  to decide fast vs exact-scan routing;
- serving lookup/query balance reads without draining the device queue.

The mirror is maintained by the same deltas the device applies, in the
same commit order, so mirror == device table at every flush boundary
(tests assert this via the device-reading debug API).

Columns are (A, 4) uint64 limb pairs: dp, dpo, cp, cpo — matching the
device layout in kernel.py (reference balance fields:
src/tigerbeetle.zig:8-12).
"""

from __future__ import annotations

import numpy as np

from tigerbeetle_tpu.utils.tracer import NOOP_RUN

_MASK32 = np.uint64(0xFFFFFFFF)


def _add_u128(a_lo, a_hi, b_lo, b_hi):
    """Vectorized (a + b) mod 2^128 plus overflow flag."""
    lo = a_lo + b_lo
    carry = (lo < a_lo).astype(np.uint64)
    hi_partial = a_hi + b_hi
    ov1 = hi_partial < a_hi
    hi = hi_partial + carry
    ov2 = hi < hi_partial
    return lo, hi, ov1 | ov2


def _sub_u128(a_lo, a_hi, b_lo, b_hi):
    """Vectorized (a - b) mod 2^128 plus borrow flag."""
    lo = a_lo - b_lo
    borrow = (a_lo < b_lo).astype(np.uint64)
    hi = a_hi - b_hi - borrow
    under = (a_hi < b_hi) | ((a_hi == b_hi) & (borrow == 1))
    return lo, hi, under


def digest_columns(table):
    """Order-sensitive digest of a (rows, C) unsigned table: per-column
    u64 sums plus golden-ratio row-mixed sums, 2C words total — the
    same family as device_kernels.checksum.  ONE implementation feeds
    every integrity compare (checkpoint parity, healthy-mode scrub,
    re-promotion handshake, account-meta digest) so the formula cannot
    drift between the host and device sides.  Works on numpy and jnp
    arrays alike (the latter lets the device compute its own digest so
    only 2C words cross the link)."""
    if isinstance(table, np.ndarray):
        xp = np
    else:
        import jax.numpy as xp
    m = table.astype(xp.uint64)
    col_sums = m.sum(axis=0, dtype=xp.uint64)
    rows = xp.arange(m.shape[0], dtype=xp.uint64)[:, None]
    mixed = (
        m * (rows * xp.uint64(0x9E3779B97F4A7C15) + xp.uint64(1))
    ).sum(axis=0, dtype=xp.uint64)
    return xp.concatenate([col_sums, mixed])


def compact_deltas(slots, cols, amt_lo, amt_hi):
    """Group (slot, col, amount) contributions into exact u128 sums.

    Returns (uniq_slots, uniq_cols, sum_lo, sum_hi, limb_overflow).
    Amounts are accumulated as 4x32-bit limbs in uint64 lanes: each
    limb sum stays < 2^32 * count, so scatter-adds cannot wrap for any
    realistic batch, and one carry pass recombines exact sums.
    """
    assert len(slots) < 1 << 21, "limb sums must stay exact in float64"
    key = slots.astype(np.int64) * 4 + cols.astype(np.int64)
    uniq, inv = np.unique(key, return_inverse=True)
    # Exact limb sums via float64 bincount: each 32-bit limb summed
    # over <= 2^21 entries stays < 2^53, so float64 is exact.
    k = len(uniq)
    c0 = np.bincount(inv, (amt_lo & _MASK32).astype(np.float64), k).astype(np.uint64)
    c1_ = np.bincount(inv, (amt_lo >> np.uint64(32)).astype(np.float64), k).astype(
        np.uint64
    )
    c2_ = np.bincount(inv, (amt_hi & _MASK32).astype(np.float64), k).astype(np.uint64)
    c3_ = np.bincount(inv, (amt_hi >> np.uint64(32)).astype(np.float64), k).astype(
        np.uint64
    )
    c1 = c1_ + (c0 >> np.uint64(32))
    c2 = c2_ + (c1 >> np.uint64(32))
    c3 = c3_ + (c2 >> np.uint64(32))
    lo = (c0 & _MASK32) | ((c1 & _MASK32) << np.uint64(32))
    hi = (c2 & _MASK32) | ((c3 & _MASK32) << np.uint64(32))
    overflow = (c3 >> np.uint64(32)) != 0
    return (uniq // 4).astype(np.int64), (uniq % 4).astype(np.int64), lo, hi, overflow


class BalanceMirror:
    """Exact host copy of the (A, 4)-column u128 balance table.

    ``version`` is a cheap monotonic mutation stamp: every mutating
    method bumps it (the native fast path mutates lo/hi in place, so
    DeviceEngine.enqueue — which every native commit feeds — bumps it
    too).  Consumers use it as a cache key, e.g. the degraded-mode
    read() table (device_engine.py) that would otherwise rebuild a
    (capacity, 8) array per call.
    """

    def __init__(self, capacity: int) -> None:
        self.lo = np.zeros((capacity, 4), np.uint64)
        self.hi = np.zeros((capacity, 4), np.uint64)
        self.version = 0
        # Optional incremental state commitment (commitment.py): when
        # attached, every mutating method re-hashes exactly the rows
        # it touched, so the 16-byte state root is always current
        # without a full-table pass.  None = disabled (TB_STATE_COMMIT
        # =0), zero overhead.
        self.commitment = None
        # The part (utils/tracer.py Stage) the twin's re-hash is timed
        # as, where a caller hands its open run in (`part=`): the
        # owning machine's sm.finish.twin.
        self.twin_part = None

    def _touch(self, slots, part=NOOP_RUN) -> None:
        if self.commitment is None:
            return
        was = part.stage  # None: no run handed in
        if was is None or self.twin_part is None:
            self.commitment.refresh(slots, self)
            return
        part.switch(self.twin_part)
        self.commitment.refresh(slots, self)
        part.switch(was)

    def grow(self, capacity: int) -> None:
        if capacity <= len(self.lo):
            return
        from tigerbeetle_tpu.state_machine.hot_tier import grow_zero_host

        self.lo = grow_zero_host(self.lo, capacity)
        self.hi = grow_zero_host(self.hi, capacity)
        self.version += 1
        # All-zero rows hash to 0: growth never moves the root (the
        # twin widens its per-row hash store lazily on next refresh).

    def rows8(self, slots: np.ndarray) -> np.ndarray:
        """(k, 8) interleaved rows matching the device layout."""
        out = np.empty((len(slots), 8), np.uint64)
        out[:, 0::2] = self.lo[slots]
        out[:, 1::2] = self.hi[slots]
        return out

    def table8(self, capacity: int) -> np.ndarray:
        """Full (capacity, 8) device-layout table (zero-padded past the
        mirror's rows) — the re-upload image for demoted engines."""
        table = np.zeros((capacity, 8), np.uint64)
        n = min(len(self.lo), capacity)
        table[:n, 0::2] = self.lo[:n]
        table[:n, 1::2] = self.hi[:n]
        return table

    def checksum8(self, capacity: int) -> np.ndarray:
        """Host-side digest of the first `capacity` rows in device
        layout, matching device_kernels.checksum word-for-word.  Used
        by the checkpoint parity tripwire, the healthy-mode scrub, and
        the re-promotion handshake."""
        return digest_columns(self.table8(capacity))

    def set_rows8(self, slots: np.ndarray, rows: np.ndarray) -> None:
        """Overwrite rows from (k, 8) device-layout snapshots.

        Duplicate slots resolve to the LAST occurrence (commit order).
        """
        rev = slots[::-1]
        uniq, first = np.unique(rev, return_index=True)
        pick = len(slots) - 1 - first
        self.lo[uniq] = rows[pick][:, 0::2]
        self.hi[uniq] = rows[pick][:, 1::2]
        self.version += 1
        self._touch(uniq)

    def try_apply_adds(
        self, dr_slot, cr_slot, amt_lo, amt_hi, is_pending, mask,
        commit: bool = True, part=NOOP_RUN,
    ):
        """Fast-path admission + commit.

        Applies non-negative balance additions (pending -> dp/cp,
        posted -> dpo/cpo) iff no touched account's final column sum or
        combined debit/credit total overflows u128. Returns the compact
        (slot, col, delta_lo, delta_hi) arrays to enqueue to the device
        when committed, or None — meaning the caller must take the
        exact scan path (reference overflow codes:
        src/state_machine.zig:1531-1545).

        With commit=False this is a pure admission dry-run: nothing is
        mutated; a non-None return proves that applying ANY SUBSET of
        the masked additions cannot overflow (deltas are non-negative,
        so every prefix state is bounded by the all-applied state) —
        the superset guarantee the linked-batch resolver relies on.
        """
        m = mask
        if not m.any():
            z = np.zeros(0, np.int64)
            return (z, z.copy(), np.zeros(0, np.uint64), np.zeros(0, np.uint64))
        if not m.all():
            dr_slot, cr_slot = dr_slot[m], cr_slot[m]
            amt_lo, amt_hi = amt_lo[m], amt_hi[m]
            is_pending = is_pending[m]

        # Exact sums over the batch's OWN (slot, column) keys: the cost
        # follows the batch's rows, not the highest slot it names (a
        # float64 bin a column up to that slot cost 0.2 s a batch on a
        # table of 2^20 rows, PERF.md section 6, PR 35).
        u_slot, u_col, d_lo, d_hi, limb_ov = compact_deltas(
            np.concatenate([dr_slot, cr_slot]),
            np.concatenate(
                [np.where(is_pending, 0, 1), np.where(is_pending, 2, 3)]
            ),
            np.concatenate([amt_lo, amt_lo]),
            np.concatenate([amt_hi, amt_hi]),
        )
        if limb_ov.any():
            return None  # column delta alone exceeds u128
        # A key whose amounts are all nought moves nothing.
        moved = (d_lo | d_hi) != 0
        if not moved.all():
            u_slot, u_col = u_slot[moved], u_col[moved]
            d_lo, d_hi = d_lo[moved], d_hi[moved]
        if not self._admit_commit(u_slot, u_col, d_lo, d_hi, commit, part):
            return None
        return (u_slot, u_col, d_lo, d_hi)

    def _admit_commit(self, u_slot, u_col, d_lo, d_hi, commit: bool,
                      part=NOOP_RUN) -> bool:
        """Shared admission tail: per-column u128 overflow + combined
        dp+dpo / cp+cpo totals of every touched account, checked
        against the all-applied upper bound; mutates only when BOTH
        pass and commit=True."""
        old_lo = self.lo[u_slot, u_col]
        old_hi = self.hi[u_slot, u_col]
        new_lo, new_hi, add_ov = _add_u128(old_lo, old_hi, d_lo, d_hi)
        if add_ov.any():
            return False
        touched = np.unique(u_slot)
        cand_lo = self.lo[touched].copy()
        cand_hi = self.hi[touched].copy()
        pos = np.searchsorted(touched, u_slot)
        cand_lo[pos, u_col] = new_lo
        cand_hi[pos, u_col] = new_hi
        _, _, dr_tot_ov = _add_u128(
            cand_lo[:, 0], cand_hi[:, 0], cand_lo[:, 1], cand_hi[:, 1]
        )
        _, _, cr_tot_ov = _add_u128(
            cand_lo[:, 2], cand_hi[:, 2], cand_lo[:, 3], cand_hi[:, 3]
        )
        if dr_tot_ov.any() or cr_tot_ov.any():
            return False
        if commit:
            self.lo[u_slot, u_col] = new_lo
            self.hi[u_slot, u_col] = new_hi
            self.version += 1
            self._touch(touched, part)
        return True

    def try_apply_deltas(self, slots, cols, amt_lo, amt_hi, part=NOOP_RUN):
        """General checked addition over explicit (slot, col) targets
        (the two-phase resolver's mixed dp/dpo/cp/cpo adds).  Same
        admission rules as try_apply_adds, checked BEFORE any
        mutation.  Returns compact device deltas or None (caller falls
        back to the exact path, mirror untouched)."""
        if len(slots) == 0:
            z = np.zeros(0, np.int64)
            return (z, z.copy(), np.zeros(0, np.uint64), np.zeros(0, np.uint64))
        u_slot, u_col, d_lo, d_hi, limb_ov = compact_deltas(
            np.asarray(slots, np.int64), np.asarray(cols, np.int64),
            amt_lo, amt_hi,
        )
        if limb_ov.any():
            return None
        if not self._admit_commit(u_slot, u_col, d_lo, d_hi, True, part):
            return None
        return (u_slot, u_col, d_lo, d_hi)

    def apply_subs(self, slots, cols, amt_lo, amt_hi, part=NOOP_RUN) -> None:
        """Release amounts (pending expiry): column -= amount, exact."""
        u_slot, u_col, d_lo, d_hi, limb_ov = compact_deltas(
            slots, cols, amt_lo, amt_hi
        )
        assert not limb_ov.any()
        new_lo, new_hi, under = _sub_u128(
            self.lo[u_slot, u_col], self.hi[u_slot, u_col], d_lo, d_hi
        )
        assert not under.any(), "pending release underflow"
        self.lo[u_slot, u_col] = new_lo
        self.hi[u_slot, u_col] = new_hi
        self.version += 1
        self._touch(u_slot, part)
