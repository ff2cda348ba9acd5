"""Incremental state commitments over the account table.

Every integrity compare in the system used to re-digest the whole
table: the healthy-mode scrub, the demote/re-promote checksum
handshake, and `verify_device_mirror` each paid a full-table pass (and
a d2h crossing, ~105 ms on the link of an earlier round) per check, which is
why scrub cadence was throttled and why checkpoints carried no state
root.  AlDBaran's lesson (arXiv:2508.10493) is that a state commitment
can be maintained *incrementally*, decoupled from execution: hash each
row once, fold the row hashes with an order-independent operator, and
update the fold from just the rows a step touched.

Construction
------------
- **Per-row hash** `h(i, row)`: the row index and the 10 row columns
  (8 u64 balance limbs in device layout ``dp_lo, dp_hi, dpo_lo,
  dpo_hi, cp_lo, cp_hi, cpo_lo, cpo_hi`` + 2 u32 account-meta columns
  ``flags, ledger``) are mixed lane-wise through a splitmix64-style
  finalizer, xor-combined across columns, and finalized into two u64
  lanes.  An ALL-ZERO row hashes to exactly (0, 0), so the digest is
  **capacity-independent**: zero padding, table growth, and
  mirror-vs-device capacity mismatches cannot move the root, and a
  row-sharded table's shard-local partial digests fold to the same
  value as the dense table's.
- **Fold**: per-lane sum mod 2^64 over all row hashes — order
  independent, and invertible per row: ``fold' = fold - h(old) +
  h(new)``.  The 16-byte fold is THE state root.
- **Incremental update**: keep the per-row hashes; to absorb a step,
  re-hash just the touched rows, subtract the stored hashes, add the
  new ones.  O(touched), not O(table).

The same formula runs bit-identically on numpy (the host twin on
BalanceMirror, the CPU oracle, recovery recompute) and on device under
jit (the engine's on-device accumulator, GSPMD-sharded on row-sharded
engines — XLA inserts the ICI all-reduce for the fold).  A pinned
golden digest in tests/test_commitment.py fails tier-1 on any silent
drift of the formula.

What the root proves (and does not)
-----------------------------------
The root commits to the *balances + account-meta table contents by
slot*.  Two states with equal roots are equal tables with overwhelming
probability (128-bit fold of 64-bit mixed lanes; adversarial collision
resistance is NOT claimed — this is an integrity/divergence check, not
a cryptographic accumulator).  It does not cover transfer history,
pending-transfer state, or session tables — those are covered by the
checkpoint blob checksum; the root is the cheap always-on commitment
the table-shaped state lacked.  Full Merkle paths (per-row inclusion
proofs) are a deliberate scope cut: nothing in the system needs
point proofs yet, and a flat fold updates ~30x cheaper.
"""

from __future__ import annotations

import numpy as np

# Hash-stream constants.  GAMMA binds the row index, K_COL the column
# index; M1/M2 are the splitmix64 finalizer multipliers; C_LO/C_HI
# split the combined column accumulator into two independent lanes.
# PINNED by the golden-digest test: changing any of these is a state
# -root format change and must break tier-1 loudly.
GAMMA = 0x9E3779B97F4A7C15
K_COL = 0xC2B2AE3D27D4EB4F
M1 = 0xFF51AFD7ED558CCD
M2 = 0xC4CEB9FE1A85EC53
C_LO = 0x8BADF00D5CA1AB1E
C_HI = 0xFACEFEED0DDBA11D

ROOT_BYTES = 16  # (2,) u64 little-endian

_MASK64 = (1 << 64) - 1


def _xp_of(array):
    if isinstance(array, np.ndarray):
        return np
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)  # u64 lanes throughout
    return jnp


def _mix64(x, xp):
    """splitmix64-style finalizer, lane-wise on u64 arrays.  Works on
    numpy and jnp alike (both wrap u64 multiplies mod 2^64)."""
    x = x ^ (x >> xp.uint64(33))
    x = x * xp.uint64(M1)
    x = x ^ (x >> xp.uint64(29))
    x = x * xp.uint64(M2)
    return x ^ (x >> xp.uint64(32))


def rows_hash(rows, bal8, meta2, xp=None):
    """Per-row hash lanes: (k,) lo and (k,) hi u64 for global row
    indices `rows` with (k, 8) u64 balances and (k, 2) meta columns.
    All-zero rows hash to (0, 0) — see the module docstring."""
    if xp is None:
        xp = _xp_of(bal8)
    r = rows.astype(xp.uint64)
    rstream = r * xp.uint64(GAMMA)
    acc = xp.zeros(r.shape, xp.uint64)
    nonzero = xp.zeros(r.shape, bool)
    cols = [bal8[:, j] for j in range(8)] + [meta2[:, j] for j in range(2)]
    for j, col in enumerate(cols):
        v = col.astype(xp.uint64)
        nonzero = nonzero | (v != 0)
        acc = acc ^ _mix64(
            v ^ rstream ^ xp.uint64(((j + 1) * K_COL) & _MASK64), xp
        )
    lo = _mix64(acc ^ rstream ^ xp.uint64(C_LO), xp)
    hi = _mix64(acc ^ rstream ^ xp.uint64(C_HI), xp)
    zero = xp.uint64(0)
    return xp.where(nonzero, lo, zero), xp.where(nonzero, hi, zero)


def fold(lo, hi, xp=None):
    """(2,) u64 order-independent fold (per-lane sum mod 2^64)."""
    if xp is None:
        xp = _xp_of(lo)
    if xp is np:
        return np.array(
            [np.add.reduce(lo, dtype=np.uint64),
             np.add.reduce(hi, dtype=np.uint64)],
            np.uint64,
        )
    return xp.stack([lo.sum(dtype=xp.uint64), hi.sum(dtype=xp.uint64)])


def table_digest(bal8, meta2, start_row: int = 0):
    """From-scratch digest of a (n, 8) balance table + (n, 2) meta
    table whose first row has global index `start_row`.  The oracle
    every incremental path must match."""
    xp = _xp_of(bal8)
    n = bal8.shape[0]
    rows = xp.arange(n, dtype=xp.uint64) + xp.uint64(start_row)
    lo, hi = rows_hash(rows, bal8, meta2, xp)
    return fold(lo, hi, xp)


def root_bytes(digest) -> bytes:
    """16-byte little-endian state root of a (2,) u64 digest."""
    return np.asarray(digest, dtype="<u8").tobytes()


def root_int(digest) -> int:
    return int.from_bytes(root_bytes(digest), "little")


def digest_of_root(root: bytes) -> np.ndarray:
    assert len(root) == ROOT_BYTES, len(root)
    return np.frombuffer(root, "<u8").copy()


# ----------------------------------------------------------------------
# Cluster commitment: fold per-shard roots into one deterministic
# 16-byte value.  The shard INDEX is mixed into each shard's
# contribution (shards own disjoint account ranges, so two shards
# swapping state must move the cluster root), then lanes sum — the
# same fold algebra as rows, one level up.


def fold_cluster(roots: list[bytes]) -> bytes:
    acc = np.zeros(2, np.uint64)
    lane_c = np.array([C_LO, C_HI], np.uint64)
    for index, root in enumerate(roots):
        d = digest_of_root(root)
        stream = np.uint64((((index + 1) * GAMMA) & _MASK64))
        acc = acc + _mix64(d ^ stream ^ lane_c, np)
    return root_bytes(acc)


# ----------------------------------------------------------------------
# Wire codec for the read-only `state_root` query (rides the
# sessionless stats-op shape; obs/scrape.py is the client).  Fixed
# little-endian layout, safe to decode from untrusted bytes:
#   shard reply:   root[16] + commit_min u64          (24 bytes)
#   cluster reply: root[16] + n_shards u64            (24 bytes)

_ROOT_BODY = np.dtype([("root", "V16"), ("aux", "<u8")])


def root_body(root: bytes, aux: int) -> bytes:
    row = np.zeros(1, _ROOT_BODY)[0]
    row["root"] = root
    row["aux"] = aux
    return row.tobytes()


def parse_root_body(body: bytes) -> tuple[bytes, int]:
    if len(body) != _ROOT_BODY.itemsize:
        raise ValueError(f"state_root body: {len(body)} bytes")
    row = np.frombuffer(body, _ROOT_BODY)[0]
    return bytes(row["root"]), int(row["aux"])


# At-op query (round 19): a `state_root` REQUEST may carry an 8-byte
# little-endian op — "give me your root as of op N" — answered from
# the replica's root ring (vsr/replica.py enable_root_ring).  An empty
# request body keeps the legacy meaning (current root + commit_min);
# a server without the requested op in its ring answers current too,
# and the caller detects the op mismatch (unverifiable-at-N, not an
# error).  The follower attestation loop is the primary client.


def root_query_body(op: int) -> bytes:
    return int(op).to_bytes(8, "little")


def parse_root_query(body: bytes) -> int | None:
    """Requested op of a state_root query body, or None for the
    legacy empty (current-root) shape / any unknown shape."""
    if len(body) != 8:
        return None
    return int.from_bytes(body, "little")


# ----------------------------------------------------------------------
# Host twin: the incrementally-maintained digest of the BalanceMirror
# + account-meta columns.  Bit-identical to the device accumulator
# (same formula, numpy lanes), so healthy/degraded/recovery modes all
# agree on the root.


class HostCommitment:
    """Per-row hashes + running fold over the host mirror.

    `meta_fn(slots) -> (k, 2) uint32` supplies the account-meta
    columns (flags, ledger) — owned by the state machine's attribute
    store, which outlives mirror re-pointing (native rebuilds) and
    restores.  Balance bytes are always read live from the mirror the
    caller passes, so array swaps (native fast path re-pointing
    mirror.lo/hi) need no re-registration.
    """

    def __init__(self, capacity: int, meta_fn) -> None:
        self.row_lo = np.zeros(capacity, np.uint64)
        self.row_hi = np.zeros(capacity, np.uint64)
        self.digest = np.zeros(2, np.uint64)
        self.meta_fn = meta_fn

    def _ensure(self, capacity: int) -> None:
        if capacity <= len(self.row_lo):
            return
        from tigerbeetle_tpu.state_machine.hot_tier import grow_zero_host

        self.row_lo = grow_zero_host(self.row_lo, capacity)
        self.row_hi = grow_zero_host(self.row_hi, capacity)

    def refresh(self, slots, mirror) -> None:
        """Re-hash `slots` (any order, duplicates fine) from current
        mirror + meta content and roll the fold forward.  Idempotent:
        refreshing an untouched row is a no-op."""
        slots = np.unique(np.asarray(slots, np.int64))
        slots = slots[slots >= 0]
        if len(slots) == 0:
            return
        self._ensure(len(mirror.lo))
        slots = slots[slots < len(self.row_lo)]
        if len(slots) == 0:
            return
        bal8 = np.empty((len(slots), 8), np.uint64)
        bal8[:, 0::2] = mirror.lo[slots]
        bal8[:, 1::2] = mirror.hi[slots]
        lo, hi = rows_hash(slots, bal8, self.meta_fn(slots), np)
        delta = np.array(
            [
                np.add.reduce(lo - self.row_lo[slots], dtype=np.uint64),
                np.add.reduce(hi - self.row_hi[slots], dtype=np.uint64),
            ],
            np.uint64,
        )
        self.digest = self.digest + delta
        self.row_lo[slots] = lo
        self.row_hi[slots] = hi

    def rebuild(self, mirror) -> None:
        """From-scratch recompute (restore, divergence repair)."""
        cap = len(mirror.lo)
        self.row_lo = np.zeros(cap, np.uint64)
        self.row_hi = np.zeros(cap, np.uint64)
        self.digest = np.zeros(2, np.uint64)
        self.refresh(np.arange(cap, dtype=np.int64), mirror)

    def partial(self, rows) -> np.ndarray:
        """(2,) u64 fold of the STORED hashes of `rows` (any order,
        duplicates collapsed) — the host-side view of a tiered device
        engine's hot partial.  Because the fold is an order-independent
        per-lane sum, ``digest == partial(hot) + partial(cold)`` for
        any split of the table, and the cold partial is just
        ``digest - partial(hot)`` — no cold-row hashing needed."""
        rows = np.unique(np.asarray(rows, np.int64))
        rows = rows[(rows >= 0) & (rows < len(self.row_lo))]
        return np.array(
            [
                np.add.reduce(self.row_lo[rows], dtype=np.uint64),
                np.add.reduce(self.row_hi[rows], dtype=np.uint64),
            ],
            np.uint64,
        )

    def root_bytes(self) -> bytes:
        return root_bytes(self.digest)


# ----------------------------------------------------------------------
# Device-side kernels (lazy: importing this module must not initialize
# a JAX backend — mirror.py's host paths import it too).  The jitted
# callables are built once per process and dispatched by the engine
# through its DeviceLink seam; on a row-sharded engine the (capacity,)
# inputs carry NamedSharding and GSPMD partitions the hash lane-wise,
# all-reducing the fold over ICI.


_DEVICE_FNS: dict | None = None


def device_fns() -> dict:
    global _DEVICE_FNS
    if _DEVICE_FNS is not None:
        return _DEVICE_FNS
    import jax

    jax.config.update("jax_enable_x64", True)  # u64 lanes throughout
    import jax.numpy as jnp

    # Every kernel takes an explicit `rows` binding — the LOGICAL row
    # id hashed into each table row.  A dense (untiered) engine passes
    # arange / the slot array itself; a TIERED engine's hot-shaped
    # tables pass logical_of / the logical rows behind its hot slots,
    # so the device digest is the HOT PARTIAL of the logical table's
    # fold and fold(hot_partial, cold_partial) == root.  Free hot slots
    # are all-zero rows, which hash to (0, 0) regardless of binding.

    def _rebuild(balances, meta, rows):
        lo, hi = rows_hash(rows, balances, meta, jnp)
        return jnp.stack([lo, hi], axis=-1), fold(lo, hi, jnp)

    def _update(balances, meta, row_hash, digest, slots, rows):
        """Incremental absorb of (deduplicated) touched `slots`
        (indices into the device tables) hashed under logical ids
        `rows`; -1 slot entries are padding and contribute nothing."""
        A = balances.shape[0]
        valid = slots >= 0
        idx = jnp.where(valid, slots, 0)
        r = jnp.where(valid, rows, 0)
        lo, hi = rows_hash(r, balances[idx], meta[idx], jnp)
        zero = jnp.uint64(0)
        lo = jnp.where(valid, lo, zero)
        hi = jnp.where(valid, hi, zero)
        old = jnp.where(valid[:, None], row_hash[idx], zero)
        new = jnp.stack([lo, hi], axis=-1)
        digest = digest + (new - old).sum(axis=0, dtype=jnp.uint64)
        scatter = jnp.where(valid, idx, A)
        row_hash = row_hash.at[scatter].set(new, mode="drop")
        return row_hash, digest

    def _admit(row_hash, digest, slots, new_lo, new_hi):
        """Tiered admission/eviction in one step: replace the hashes
        at hot `slots` (the victims' — or zero for free slots) with
        the admitted rows' host-twin hashes `new_lo`/`new_hi`, rolling
        the hot-partial digest by (new - old).  Exact because admitted
        device content is uploaded from the very mirror rows the twin
        hashed; -1 slots are padding."""
        A = row_hash.shape[0]
        valid = slots >= 0
        idx = jnp.where(valid, slots, 0)
        zero = jnp.uint64(0)
        new = jnp.stack(
            [jnp.where(valid, new_lo, zero), jnp.where(valid, new_hi, zero)],
            axis=-1,
        )
        old = jnp.where(valid[:, None], row_hash[idx], zero)
        digest = digest + (new - old).sum(axis=0, dtype=jnp.uint64)
        scatter = jnp.where(valid, idx, A)
        row_hash = row_hash.at[scatter].set(new, mode="drop")
        return row_hash, digest

    def _probe(balances, meta, digest, rows):
        """(2, 2): [maintained digest, from-scratch digest] — ONE
        dispatch + one 32-byte fetch covers both the drift check and
        the memory-corruption check."""
        lo, hi = rows_hash(rows, balances, meta, jnp)
        return jnp.stack([digest, fold(lo, hi, jnp)])

    _DEVICE_FNS = {
        "rebuild": jax.jit(_rebuild),
        "update": jax.jit(_update),
        "admit": jax.jit(_admit),
        "probe": jax.jit(_probe),
    }
    return _DEVICE_FNS


def pad_slots(slots: np.ndarray, minimum: int = 256) -> np.ndarray:
    """Pad a deduplicated slot array to a power-of-two bucket (-1
    fill) so the update kernel compiles O(log max-batch) shapes, not
    one per touched-set size."""
    n = max(int(len(slots)), 1)
    bucket = minimum
    while bucket < n:
        bucket <<= 1
    out = np.full(bucket, -1, np.int64)
    out[: len(slots)] = slots
    return out
