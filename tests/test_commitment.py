"""Incremental state commitments (state_machine/commitment.py).

Codec pinning (golden digest, numpy/JAX bit-identity, fold algebra),
the host twin vs from-scratch differential under fuzz, the device
engine's incremental digest across kernel/wave/grow/remove/demote/
re-promote interleavings on dense AND row-sharded engines, cheap-scrub
fetch-count assertions, corruption catch-and-heal, and checkpoint
state-root recording/recompute through superblock recovery.
"""

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)  # u64 lanes (kernel.py does this)

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine import CpuStateMachine
from tigerbeetle_tpu.state_machine import commitment as cm
from tigerbeetle_tpu.testing.harness import (
    SingleNodeHarness,
    account,
    ids_bytes,
    pack,
    transfer,
)
from tigerbeetle_tpu.types import AccountFlags, Operation, TransferFlags

TF = TransferFlags


# ----------------------------------------------------------------------
# Codec: golden pin, platform bit-identity, fold algebra.


def _fixture_table():
    bal = np.arange(64, dtype=np.uint64).reshape(8, 8) * np.uint64(
        0x0123456789ABCDEF
    )
    meta = np.arange(16, dtype=np.uint32).reshape(8, 2) + np.uint32(1)
    return bal, meta


def test_golden_digest_pinned():
    """Silent drift of the hash formula (constants, mixing, fold) is a
    state-root FORMAT change: recorded checkpoint roots and
    cross-version scrub compares would all mismatch.  This pin makes
    it fail tier-1 instead."""
    bal, meta = _fixture_table()
    d = cm.table_digest(bal, meta)
    assert int(d[0]) == 0xB84D53B618D40315, hex(int(d[0]))
    assert int(d[1]) == 0x924D31B47961A88B, hex(int(d[1]))
    assert cm.root_bytes(d).hex() == "1503d418b6534db88ba86179b4314d92"


def test_numpy_jax_bit_identical():
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    for rows in (1, 8, 257):
        bal = rng.integers(0, 1 << 63, (rows, 8), dtype=np.uint64)
        bal |= rng.integers(0, 2, (rows, 8), dtype=np.uint64) << np.uint64(63)
        meta = rng.integers(0, 1 << 32, (rows, 2), dtype=np.uint64).astype(
            np.uint32
        )
        d_np = cm.table_digest(bal, meta)
        d_j = np.asarray(cm.table_digest(jnp.asarray(bal), jnp.asarray(meta)))
        assert (d_np == d_j).all(), rows


def test_dtype_stability():
    """Meta columns hash by VALUE, not storage dtype: uint16 flags
    (the attrs store) and uint32 flags (the engine's meta table) must
    digest identically."""
    bal, meta = _fixture_table()
    base = cm.table_digest(bal, meta)
    for dt in (np.uint16, np.uint64, np.int64):
        assert (cm.table_digest(bal, meta.astype(dt)) == base).all(), dt
    assert (cm.table_digest(bal.astype(np.uint64), meta) == base).all()


def test_zero_rows_capacity_invariance():
    """All-zero rows contribute exactly nothing, so zero padding,
    growth, and capacity mismatches never move the root."""
    bal, meta = _fixture_table()
    base = cm.table_digest(bal, meta)
    for pad in (1, 9, 100):
        bal2 = np.zeros((8 + pad, 8), np.uint64)
        meta2 = np.zeros((8 + pad, 2), np.uint32)
        bal2[:8], meta2[:8] = bal, meta
        assert (cm.table_digest(bal2, meta2) == base).all(), pad
    assert (
        cm.table_digest(np.zeros((5, 8), np.uint64), np.zeros((5, 2), np.uint32))
        == 0
    ).all()


def test_fold_order_independence_fuzz():
    """The fold is a per-lane modular sum of index-bound row hashes:
    any permutation of rows (hashed AT their true indices) folds to
    the same digest, and incremental subtract/add replays an arbitrary
    mutation order to the same result as from-scratch."""
    rng = np.random.default_rng(42)
    for trial in range(20):
        n = int(rng.integers(2, 200))
        bal = rng.integers(0, 1 << 64, (n, 8), dtype=np.uint64)
        meta = rng.integers(0, 1 << 32, (n, 2), dtype=np.uint64).astype(
            np.uint32
        )
        rows = np.arange(n, dtype=np.uint64)
        lo, hi = cm.rows_hash(rows, bal, meta, np)
        perm = rng.permutation(n)
        assert (
            cm.fold(lo[perm], hi[perm], np) == cm.table_digest(bal, meta)
        ).all()
        # Incremental replay: mutate random rows in random order.
        digest = cm.table_digest(bal, meta).copy()
        for _ in range(10):
            k = int(rng.integers(1, min(n, 16) + 1))
            slots = rng.choice(n, size=k, replace=False)
            old_lo, old_hi = cm.rows_hash(
                slots.astype(np.uint64), bal[slots], meta[slots], np
            )
            bal[slots] ^= rng.integers(0, 1 << 64, (k, 8), dtype=np.uint64)
            new_lo, new_hi = cm.rows_hash(
                slots.astype(np.uint64), bal[slots], meta[slots], np
            )
            digest = digest + np.array(
                [
                    np.add.reduce(new_lo - old_lo, dtype=np.uint64),
                    np.add.reduce(new_hi - old_hi, dtype=np.uint64),
                ],
                np.uint64,
            )
        assert (digest == cm.table_digest(bal, meta)).all(), trial


def test_swapped_rows_change_digest():
    """Row index is bound into the hash: two rows trading places (a
    divergence the plain column-sum digest family is blind to at the
    per-column level) must move the root."""
    bal, meta = _fixture_table()
    base = cm.table_digest(bal, meta)
    bal2 = bal.copy()
    bal2[[2, 5]] = bal2[[5, 2]]
    assert not (cm.table_digest(bal2, meta) == base).all()


def test_device_update_matches_scratch():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    bal = rng.integers(0, 1 << 64, (128, 8), dtype=np.uint64)
    meta = rng.integers(0, 1 << 32, (128, 2), dtype=np.uint64).astype(
        np.uint32
    )
    fns = cm.device_fns()
    arange = jnp.arange(128, dtype=jnp.uint64)
    row_hash, digest = fns["rebuild"](
        jnp.asarray(bal), jnp.asarray(meta), arange
    )
    assert (np.asarray(digest) == cm.table_digest(bal, meta)).all()
    for _ in range(5):
        k = int(rng.integers(1, 40))
        slots = np.unique(rng.integers(0, 128, k))
        bal[slots] ^= rng.integers(0, 1 << 64, (len(slots), 8), dtype=np.uint64)
        padded = jnp.asarray(cm.pad_slots(slots))
        row_hash, digest = fns["update"](
            jnp.asarray(bal), jnp.asarray(meta), row_hash, digest,
            padded, padded,
        )
        assert (np.asarray(digest) == cm.table_digest(bal, meta)).all()
        pair = np.asarray(
            fns["probe"](jnp.asarray(bal), jnp.asarray(meta), digest, arange)
        )
        assert (pair[0] == pair[1]).all()


def _mk_twin(rng, n):
    """HostCommitment over a random fake mirror (lo/hi column pairs)."""
    meta = rng.integers(0, 1 << 32, (n, 2), dtype=np.uint64).astype(np.uint32)

    class _M:
        pass

    m = _M()
    m.lo = rng.integers(0, 1 << 64, (n, 4), dtype=np.uint64)
    m.hi = rng.integers(0, 1 << 64, (n, 4), dtype=np.uint64)
    twin = cm.HostCommitment(n, meta_fn=lambda s: meta[s])
    twin.refresh(np.arange(n, dtype=np.int64), m)
    return twin, m


def test_partial_fold_hot_cold_split_fuzz():
    """Tiering's root invariant: for ANY hot/cold split of the table,
    partial(hot) + partial(cold) == digest per lane (mod 2^64) — the
    cold partial never needs hashing, it is digest - partial(hot).
    Duplicates collapse and out-of-range/negative rows are ignored, so
    a hot set handed in admission order folds the same as sorted."""
    rng = np.random.default_rng(7)
    for trial in range(10):
        n = int(rng.integers(2, 200))
        twin, _ = _mk_twin(rng, n)
        k = int(rng.integers(0, n + 1))
        hot = rng.choice(n, size=k, replace=False)
        cold = np.setdiff1d(np.arange(n), hot)
        assert (
            twin.partial(hot) + twin.partial(cold) == twin.digest
        ).all(), trial
        messy = np.concatenate(
            [rng.permutation(hot), hot, [-1, n, n + 17]]
        )
        assert (twin.partial(messy) == twin.partial(hot)).all(), trial


def test_partial_fold_degenerate_splits():
    """Empty cold tier: the hot partial IS the root (all-resident
    collapses to today's compare).  Empty hot set: partial is the
    zero lane pair and the cold partial is the whole digest."""
    rng = np.random.default_rng(8)
    twin, _ = _mk_twin(rng, 64)
    assert (twin.partial(np.arange(64)) == twin.digest).all()
    assert (twin.partial(np.zeros(0, np.int64)) == 0).all()


def test_device_admit_tracks_hot_partial():
    """Tiered device digest lifecycle against the host twin: an empty
    hot table folds to zero; every admission (free slots), mid-
    residency mutation (update kernel), and eviction-with-replacement
    (admit kernel over occupied victim slots) leaves the maintained
    device digest equal to twin.partial(occupied) — so
    fold(hot_partial, cold_partial) == twin.digest throughout."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    N, H = 32, 8  # logical rows, hot budget
    twin, m = _mk_twin(rng, N)
    fns = cm.device_fns()

    bal_hot = np.zeros((H, 8), np.uint64)
    meta_hot = np.zeros((H, 2), np.uint32)
    logical_of = np.full(H, -1, np.int64)

    def rows_binding():
        # Free slots bind to row 0; their all-zero content hashes to
        # (0, 0) regardless of the binding (the engine's _commit_rows).
        return jnp.asarray(
            np.where(logical_of >= 0, logical_of, 0).astype(np.uint64)
        )

    row_hash, digest = fns["rebuild"](
        jnp.asarray(bal_hot), jnp.asarray(meta_hot), rows_binding()
    )
    assert (np.asarray(digest) == 0).all()  # empty hot set

    def admit(rows, slots):
        rows = np.asarray(rows, np.int64)
        slots = np.asarray(slots, np.int64)
        bal_hot[slots, 0::2] = m.lo[rows]
        bal_hot[slots, 1::2] = m.hi[rows]
        meta_hot[slots] = twin.meta_fn(rows)
        logical_of[slots] = rows
        padded = cm.pad_slots(slots)
        k = len(slots)
        new_lo = np.zeros(len(padded), np.uint64)
        new_hi = np.zeros(len(padded), np.uint64)
        new_lo[:k] = twin.row_lo[rows]
        new_hi[:k] = twin.row_hi[rows]
        return fns["admit"](
            row_hash, digest, jnp.asarray(padded),
            jnp.asarray(new_lo), jnp.asarray(new_hi),
        )

    def check(step):
        occupied = logical_of[logical_of >= 0]
        assert (np.asarray(digest) == twin.partial(occupied)).all(), step
        pair = np.asarray(
            fns["probe"](
                jnp.asarray(bal_hot), jnp.asarray(meta_hot), digest,
                rows_binding(),
            )
        )
        assert (pair[0] == pair[1]).all(), step

    # Admission into free slots.
    row_hash, digest = admit([3, 9, 20], [0, 1, 2])
    check("admit-free")
    row_hash, digest = admit([4, 5, 6, 7, 8], [3, 4, 5, 6, 7])
    check("admit-fill")

    # Mid-residency mutation: the mirror (and twin) move first, then
    # the device row is rewritten and the update kernel rolls the
    # partial — same order as a write-behind flush.
    touched = np.array([9, 5], np.int64)
    m.lo[touched] ^= rng.integers(0, 1 << 64, (2, 4), dtype=np.uint64)
    twin.refresh(touched, m)
    hot_slots = np.array(
        [np.flatnonzero(logical_of == r)[0] for r in touched], np.int64
    )
    bal_hot[hot_slots, 0::2] = m.lo[touched]
    bal_hot[hot_slots, 1::2] = m.hi[touched]
    padded = cm.pad_slots(hot_slots)
    rows_pad = np.where(
        padded >= 0, logical_of[np.maximum(padded, 0)], 0
    ).astype(np.uint64)
    row_hash, digest = fns["update"](
        jnp.asarray(bal_hot), jnp.asarray(meta_hot), row_hash, digest,
        jnp.asarray(padded), jnp.asarray(rows_pad),
    )
    check("update-mid-residency")

    # Eviction with replacement: new rows land on occupied victim
    # slots; the admit kernel rolls out the victims' hashes.
    row_hash, digest = admit([25, 26], [0, 3])
    check("evict-readmit")

    # The digest is the hot PARTIAL, not the table digest: with a
    # non-empty cold tier they differ, and the cold partial closes
    # the fold.
    occupied = logical_of[logical_of >= 0]
    cold = np.setdiff1d(np.arange(N), occupied)
    assert len(cold) and not (np.asarray(digest) == twin.digest).all()
    assert (np.asarray(digest) + twin.partial(cold) == twin.digest).all()


def test_fold_cluster_deterministic_and_index_bound():
    r1 = cm.root_bytes(np.array([1, 2], np.uint64))
    r2 = cm.root_bytes(np.array([3, 4], np.uint64))
    assert cm.fold_cluster([r1, r2]) == cm.fold_cluster([r1, r2])
    # Shards swapping state must move the cluster root.
    assert cm.fold_cluster([r1, r2]) != cm.fold_cluster([r2, r1])


def test_root_body_roundtrip_and_rejects_garbage():
    root = bytes(range(16))
    body = cm.root_body(root, 77)
    assert len(body) == 24
    assert cm.parse_root_body(body) == (root, 77)
    with pytest.raises(ValueError):
        cm.parse_root_body(body + b"x")


# ----------------------------------------------------------------------
# Host twin + state machines.


def _scratch_root(sm) -> bytes:
    """From-scratch root over the TPU build's mirror + attrs — the
    oracle every incremental path must match."""
    n = len(sm._mirror.lo)
    bal8 = np.empty((n, 8), np.uint64)
    bal8[:, 0::2] = sm._mirror.lo
    bal8[:, 1::2] = sm._mirror.hi
    meta = sm._commit_meta_cols(np.arange(n, dtype=np.int64))
    return cm.root_bytes(cm.table_digest(bal8, meta))


def _fuzz_ops(h, rng, n_accounts, tid_start, batches=12):
    """Mixed batches: plain, pending+post/void, linked chains with
    failures, duplicate ids, timeouts — every routing class."""
    tid = tid_start
    for b in range(batches):
        kind = b % 5
        rows = []
        if kind == 0:  # plain order-free
            for _ in range(int(rng.integers(1, 24))):
                rows.append(transfer(
                    tid, debit_account_id=int(rng.integers(1, n_accounts + 1)),
                    credit_account_id=int(rng.integers(1, n_accounts + 1)),
                    amount=int(rng.integers(1, 100)),
                ))
                tid += 1
        elif kind == 1:  # pending + post/void
            p1, p2 = tid, tid + 1
            rows.append(transfer(p1, debit_account_id=1, credit_account_id=2,
                                 amount=5, flags=int(TF.pending), timeout=1000))
            rows.append(transfer(p2, debit_account_id=3, credit_account_id=4,
                                 amount=6, flags=int(TF.pending), timeout=2))
            tid += 2
            h.create_transfers(rows)
            rows = [
                transfer(tid, pending_id=p1, amount=5,
                         flags=int(TF.post_pending_transfer)),
                transfer(tid + 1, pending_id=p2,
                         flags=int(TF.void_pending_transfer)),
            ]
            tid += 2
        elif kind == 2:  # linked chain with a failing member (rollback)
            rows.append(transfer(tid, debit_account_id=1, credit_account_id=2,
                                 amount=1, flags=int(TF.linked)))
            rows.append(transfer(tid, debit_account_id=2, credit_account_id=3,
                                 amount=1))  # duplicate id: chain fails
            tid += 1
        elif kind == 3:  # duplicates + mixed amounts (off-kernel shapes)
            a = int(rng.integers(1, n_accounts + 1))
            for _ in range(6):
                rows.append(transfer(
                    tid, debit_account_id=a,
                    credit_account_id=(a % n_accounts) + 1,
                    amount=int(rng.integers(1, 10)),
                ))
                tid += 1
            rows.append(rows[-1])  # retransmitted duplicate row
        else:  # balancing / limit flags interplay
            rows.append(transfer(
                tid, debit_account_id=n_accounts + 1, credit_account_id=1,
                amount=int(rng.integers(1, 50)),
                flags=int(TF.balancing_debit),
            ))
            tid += 1
        if rows:
            h.create_transfers(rows)
    return tid


def test_host_twin_matches_scratch_and_cpu_oracle():
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    rng = np.random.default_rng(11)
    sm = TpuStateMachine(account_capacity=1 << 12)
    cpu = CpuStateMachine()
    h, hc = SingleNodeHarness(sm), SingleNodeHarness(cpu)
    n_acct = 24
    accts = [account(i + 1) for i in range(n_acct)] + [
        account(n_acct + 1,
                flags=int(AccountFlags.debits_must_not_exceed_credits))
    ]
    h.create_accounts(accts)
    hc.create_accounts(accts)
    assert sm._commitment is not None
    assert sm._commitment.root_bytes() == _scratch_root(sm)
    tid = _fuzz_ops(h, np.random.default_rng(11), n_acct, 1000)
    _fuzz_ops(hc, np.random.default_rng(11), n_acct, 1000)
    assert sm._commitment.root_bytes() == _scratch_root(sm)
    # Pending expiry (apply_subs path) via a pulse.
    h.create_transfers([transfer(tid, debit_account_id=5, credit_account_id=6,
                                 amount=3, flags=int(TF.pending), timeout=1)])
    hc.create_transfers([transfer(tid, debit_account_id=5, credit_account_id=6,
                                  amount=3, flags=int(TF.pending), timeout=1)])
    far = 20_000_000_000
    h.lookup_accounts([1])
    h.submit(Operation.lookup_accounts, ids_bytes([1]), realtime=far)
    hc.submit(Operation.lookup_accounts, ids_bytes([1]), realtime=far)
    assert sm._commitment.root_bytes() == _scratch_root(sm)
    # The CPU oracle computes the identical root for the same stream.
    assert sm.state_root() == cpu.state_root()


def test_linked_account_rollback_keeps_twin_current():
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    sm = TpuStateMachine(account_capacity=1 << 12)
    h = SingleNodeHarness(sm)
    h.create_accounts([account(1), account(2)])
    before = sm.state_root()
    # Linked chain whose tail fails (duplicate id): every slot the
    # chain allocated rolls back — the root must return exactly.
    res = h.create_accounts([
        account(50, flags=int(AccountFlags.linked)),
        account(1),  # exists -> chain fails
    ])
    assert any(code != 0 for _i, code in res)
    assert sm.state_root() == before == _scratch_root(sm)
    # And a successful chain moves it.
    h.create_accounts([account(60, flags=int(AccountFlags.linked)),
                       account(61)])
    assert sm.state_root() != before
    assert sm._commitment.root_bytes() == _scratch_root(sm)


def test_state_root_matches_with_commitment_disabled(monkeypatch):
    """TB_STATE_COMMIT=0 disables the incremental machinery, not the
    root: the from-scratch value must be identical."""
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    def drive(sm):
        h = SingleNodeHarness(sm)
        h.create_accounts([account(i + 1) for i in range(8)])
        h.create_transfers([
            transfer(1, debit_account_id=1, credit_account_id=2, amount=7),
        ])
        return sm.state_root()

    on = drive(TpuStateMachine(account_capacity=1 << 12))
    monkeypatch.setenv("TB_STATE_COMMIT", "0")
    sm_off = TpuStateMachine(account_capacity=1 << 12)
    assert sm_off._commitment is None
    assert sm_off._mirror.commitment is None
    assert drive(sm_off) == on


# ----------------------------------------------------------------------
# Device engine: incremental digest as a by-product of every execution
# path, cheap scrub/handshake with fetch-count assertions.


def _device_sm(capacity, link=None):
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    return TpuStateMachine(
        engine="device", account_capacity=capacity, device_link=link
    )


def _assert_device_consistent(sm):
    sm._dev.drain()
    sm._dev.flush()
    d = sm._dev
    assert d.dev_digest is not None
    pair = np.asarray(d.commit_probe())
    twin = sm._commitment.digest
    assert (pair[0] == pair[1]).all(), "incremental != from-scratch on device"
    assert (pair[1] == twin).all(), "device digest != host twin"
    assert sm.state_root() == _scratch_root(sm)


@pytest.mark.parametrize(
    "capacity",
    [
        1 << 10,  # 1024 % 8 == 0: row-sharded over the forced 8-dev mesh
        1012,     # 1012 % 8 != 0: dense single-device placement
    ],
    ids=["sharded", "dense"],
)
def test_device_digest_differential_fuzz(capacity):
    sm = _device_sm(capacity)
    if capacity % 8 == 0:
        assert sm._dev.sharding is not None, "expected a row-sharded engine"
    else:
        assert sm._dev.sharding is None
    h = SingleNodeHarness(sm)
    n_acct = 32
    h.create_accounts([account(i + 1) for i in range(n_acct)])
    _assert_device_consistent(sm)
    rng = np.random.default_rng(23)
    tid = _fuzz_ops(h, rng, n_acct, 5000, batches=10)
    _assert_device_consistent(sm)
    # Growth: push the account count past the engine capacity.
    extra = [account(10_000 + i) for i in range(capacity - n_acct + 8)]
    for i in range(0, len(extra), 1024):
        h.create_accounts(extra[i : i + 1024])
    assert sm._dev.capacity > capacity
    _assert_device_consistent(sm)
    _fuzz_ops(h, rng, n_acct, tid, batches=5)
    _assert_device_consistent(sm)


def test_cheap_scrub_no_full_fetch_and_corruption_healed():
    import jax.numpy as jnp

    sm = _device_sm(1 << 10)
    h = SingleNodeHarness(sm)
    h.create_accounts([account(i + 1) for i in range(16)])
    h.create_transfers([
        transfer(1, debit_account_id=1, credit_account_id=2, amount=9),
    ])
    d = sm._dev
    d.drain()
    d.flush()
    # Happy path: cheap scrubs only — the full-table fetch counter
    # must stay at ZERO.
    for _ in range(3):
        assert d.scrub() is True
    assert d.stat_scrub_cheap == 3
    assert d.stat_full_fetches == 0
    assert d.stat_scrub_fallback == 0
    # Corrupt one device row out of band (an HBM bit flip no step
    # touched): the NEXT cheap scrub must catch it (from-scratch vs
    # maintained digest), localize it with exactly one full fetch,
    # and heal through the existing re-upload path.
    d.balances = d.balances.at[7, 2].add(jnp.uint64(1))
    assert d.scrub() is False
    assert d.stat_scrub_fallback == 1
    assert d.stat_full_fetches == 1
    assert d.stat_scrub_heals == 1
    _assert_device_consistent(sm)
    assert d.scrub() is True
    assert d.stat_full_fetches == 1  # healed: back to cheap
    # Meta corruption is as detectable as balance corruption.
    d.meta = d.meta.at[3, 1].add(jnp.uint32(1))
    assert d.scrub() is False
    assert d.stat_scrub_heals == 2
    _assert_device_consistent(sm)


def test_deep_scrub_cadence(monkeypatch):
    """TB_DEV_SCRUB_FALLBACK=2: every 2nd scrub runs the full-fetch
    localization even when the cheap compare matched — and a clean
    deep scrub heals nothing."""
    monkeypatch.setenv("TB_DEV_SCRUB_FALLBACK", "2")
    sm = _device_sm(1 << 10)
    h = SingleNodeHarness(sm)
    h.create_accounts([account(1), account(2)])
    d = sm._dev
    d.drain()
    base_scrubs = d.stat_scrubs
    for _ in range(4):
        assert d.scrub() is True
    deep = sum(
        1 for k in range(base_scrubs + 1, d.stat_scrubs + 1) if k % 2 == 0
    )
    assert d.stat_full_fetches == deep > 0
    assert d.stat_scrub_heals == 0


def test_demote_repromote_handshake_cheap():
    from tigerbeetle_tpu.testing.chaos import ChaosLink

    link = ChaosLink(seed=1)
    sm = _device_sm(1 << 10, link=link)
    h = SingleNodeHarness(sm)
    h.create_accounts([account(i + 1) for i in range(8)])
    h.create_transfers([
        transfer(1, debit_account_id=1, credit_account_id=2, amount=4),
    ])
    sm._dev.drain()
    sm._dev.flush()
    # Fatal loss -> demote; degraded commits keep the twin current.
    link.kill()
    h.create_transfers([
        transfer(2, debit_account_id=2, credit_account_id=3, amount=5),
    ])
    d = sm._dev
    assert d.state is types.EngineState.degraded
    assert sm._commitment.root_bytes() == _scratch_root(sm)
    link.heal()
    full_before = d.stat_full_fetches
    assert d.try_repromote() is True
    assert d.state is types.EngineState.healthy
    # The handshake compared 16-byte roots: no full-table fetch.
    assert d.stat_full_fetches == full_before
    _assert_device_consistent(sm)
    # A twin the mirror does NOT back must fail the handshake closed.
    link.kill()
    h.create_transfers([
        transfer(3, debit_account_id=1, credit_account_id=4, amount=2),
    ])
    assert d.state is types.EngineState.degraded
    sm._commitment.digest = sm._commitment.digest + np.uint64(1)
    link.heal()
    assert d.try_repromote() is False
    assert d.state is types.EngineState.degraded
    sm._commitment.rebuild(sm._mirror)
    assert d.try_repromote() is True
    _assert_device_consistent(sm)


def test_verify_device_mirror_catches_twin_drift():
    sm = _device_sm(1 << 10)
    h = SingleNodeHarness(sm)
    h.create_accounts([account(1), account(2)])
    h.create_transfers([
        transfer(1, debit_account_id=1, credit_account_id=2, amount=3),
    ])
    sm.verify_device_mirror()  # clean
    sm._commitment.digest = sm._commitment.digest + np.uint64(5)
    with pytest.raises(AssertionError, match="commitment divergence"):
        sm.verify_device_mirror()


def test_commitment_disabled_engine_uses_legacy_scrub(monkeypatch):
    monkeypatch.setenv("TB_STATE_COMMIT", "0")
    sm = _device_sm(1 << 10)
    h = SingleNodeHarness(sm)
    h.create_accounts([account(1), account(2)])
    d = sm._dev
    d.drain()
    assert d.dev_digest is None
    assert d.scrub() is True
    assert d.stat_scrub_cheap == 0  # legacy full-digest compare ran
    sm.verify_device_mirror()


# ----------------------------------------------------------------------
# Checkpoint state roots: superblock recording + recovery recompute.


def _layout():
    from tigerbeetle_tpu.vsr.storage import ZoneLayout

    return ZoneLayout(config=cfg.TEST_MIN)


def test_checkpoint_state_root_roundtrip():
    from tigerbeetle_tpu.vsr import replica as vsr_replica
    from tigerbeetle_tpu.vsr.storage import MemoryStorage

    storage = MemoryStorage(_layout())
    vsr_replica.format(storage, 7)
    r = vsr_replica.Replica(storage, 7, CpuStateMachine(cfg.TEST_MIN))
    r.open()
    r.on_request(Operation.create_accounts, pack([account(1), account(2)]))
    r.on_request(
        Operation.create_transfers,
        pack([transfer(10, debit_account_id=1, credit_account_id=2,
                       amount=100)]),
    )
    r.checkpoint()
    r.close()
    recorded = int(r.superblock.working["state_root_lo"]) | (
        int(r.superblock.working["state_root_hi"]) << 64
    )
    assert recorded == int.from_bytes(r.sm.state_root(), "little") != 0

    # Restart: open() recomputes the root from the restored snapshot
    # and asserts it against the superblock.
    r2 = vsr_replica.Replica(storage, 7, CpuStateMachine(cfg.TEST_MIN))
    r2.open()
    assert r2.sm.state_root() == r.sm.state_root()
    r2.close()

    # A superblock whose recorded root contradicts the snapshot dies
    # at open, not at the next cross-replica divergence.
    sb = r2.superblock
    hdr = sb.working.copy()
    hdr["state_root_lo"] = int(hdr["state_root_lo"]) ^ 1
    hdr["sequence"] = int(hdr["sequence"]) + 1
    sb._write(hdr)
    r3 = vsr_replica.Replica(storage, 7, CpuStateMachine(cfg.TEST_MIN))
    with pytest.raises(RuntimeError, match="state root mismatch"):
        r3.open()


def test_cluster_convergence_compares_roots():
    """The VOPR convergence checker now asserts one root across
    replicas — and a deliberately drifted state machine trips it."""
    from tigerbeetle_tpu.testing.cluster import Cluster

    cluster = Cluster(replica_count=2, seed=3)
    client = cluster.client(100)
    client.register()
    cluster.run_until(lambda: client.registered)
    assert cluster.run_request(
        client, Operation.create_accounts, pack([account(1), account(2)])
    ) == b""
    assert cluster.run_request(
        client, Operation.create_transfers,
        pack([transfer(5, debit_account_id=1, credit_account_id=2, amount=3)]),
    ) == b""
    cluster.settle()
    cluster.check_convergence()
    roots = {r.sm.state_root() for r in cluster.replicas}
    assert len(roots) == 1 and next(iter(roots)) != bytes(16)
