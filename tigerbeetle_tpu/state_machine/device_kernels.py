"""Device-resident semantic kernels: result codes computed ON the TPU.

Round 3 kept all create_transfers semantics on the host and used the
device as a write-behind balance replica.  Round 4 inverts that
authority for the three vectorizable batch classes (order-free,
linked-chain, two-phase): the kernels below read the authoritative
HBM balance/meta tables, run the full precedence ladder + the
order-dependent resolution, apply balance effects, and emit result
codes — the host's role shrinks to joins (id-directory probes, durable
row gathers), routing, and bookkeeping derived from the codes.

reference: src/state_machine.zig:1220-1306 (execute loop),
:1462-1741 (create_transfer + post/void), src/tigerbeetle.zig:31-39
(limit formulas).  The semantics ported here are the same ones the
vectorized host resolvers (resolve.py) implement; differential fuzz in
tests/test_device_engine.py pins all three kernels to the CPU oracle.

Link contract (v5e, PERF.md sections 6 and 7.8): a d2h of 4 KB costs
~0.5 ms and of 512 KB ~2.4 ms, every separate upload or Python-scalar
argument ~0.25 ms of host time, a kernel's dispatch ~0.3 ms.  So a
batch crosses ONCE each way.  Up: its scalars (n, ts_base) ride in
the packed buffer's last row (SCALAR_ROW) — no scalar argument, no
convert program.  Down: each kernel returns, beside the new table, a
fixed-size FAILURE-SPARSE summary row (60 failure slots + status
flags, 512 bytes) as an output of its own; the engine starts its copy
home at dispatch and fetches nothing else, unless the row counts more
failures than its slots hold: the batch's dense result codes, a
third output that otherwise never leaves the device, then come home
too (32 KB at B = 8192), so the sparse encoding never loses
information.  A batch that hits an overflow/precondition edge raises
a flag and is re-executed exactly on the host engine (the fallback
path).

Cost contract (PERF.md section 6, PR 36): a kernel's work follows the
rows its batch touches, not the table.  The legs' slots are sorted
into DENSE RANKS (_touch: at most 2B touched rows, 4B in two_phase);
the exact sums are one one-hot product over (ranks, legs)
(_sum_legs), admission and the release run over the gathered rows
(_admit, _release), and the write-back scatters the touched rows
alone (_write_back).  The one cost that grows with the table is the
copy the write-back starts from (the engine never donates its
table).  Restricting the overflow flags to touched rows changes none
of them as long as the table holds the invariant the admission
itself maintains (no account's dp+dpo or cp+cpo total passes u128):
an untouched row's delta is zero (_admit's docstring).  On the v5e a
sort of 2^14 keys is ~10 us, a gather of as many 0.06-0.14 ms, a
scatter of as many 0.5-1.5 ms and a binary search of as many 1.75 ms:
so the kernels sort (compaction, inverse permutations, merge counts)
where they used to scatter and search.

Input marshaling split (who computes what): the host packs raw event
columns and *stateless byte predicates* (id == 0, id == maxInt,
debit id == credit id, ...) plus join booleans (duplicate-id found,
pending target found) into one u64 matrix per batch — pure wire
decoding and directory probes.  Every *decision* — precedence ladder
order, balance math, limit fixpoint, two-phase winner resolution,
overflow admission — happens on device against device state.
"""

from __future__ import annotations

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

from tigerbeetle_tpu.types import CreateTransferResult as CTR

# ---------------------------------------------------------------------------
# Batch geometry.

# Fixed event bucket (batches pad up to this; larger batches take the
# host path).  Tests shrink it via TB_DEV_B — CPU-backend matmuls at
# the production size would dominate the suite's runtime.
from tigerbeetle_tpu import envcheck as _envcheck

# Upper bound 8192: the linked kernel packs (event << 1 | side) into 14
# key bits and masks events with B-1, and f32 partial sums of 8-bit
# pieces over 4B rows (the two_phase add matmul) must stay below 2^24.
B = _envcheck.env_int("TB_DEV_B", 8192, minimum=1, maximum=8192)
if B & (B - 1) != 0:
    raise _envcheck.EnvVarError(
        f"TB_DEV_B={B} invalid: must be a power of 2 <= 8192"
    )
assert 4 * B * 255 < (1 << 24), "TB_DEV_B too large for exact f32 sums"
# A packed input is (ROWS, ncols): B event rows, then one row that
# carries the batch's scalars up with them (seal_scalars below).
SCALAR_ROW = B
ROWS = B + 1
SUMMARY_WORDS = 64
FAIL_CAP = SUMMARY_WORDS - 4   # failure entries per batch summary

# Summary flag bits (word [1]).
FLAG_OVERFLOW = 1 << 0     # balance-overflow admission failed
FLAG_PRECOND = 1 << 2      # kernel precondition (u64-safety, a long chain)
FLAG_SETTLED = 1 << 3      # linked: the exact pass by blocks ran
ITERS_SHIFT = 16           # linked fixpoint iterations (16 bits)
SETTLE_SHIFT = 32          # linked: the pass's rounds, over all its blocks

# The linked fixpoint's cap: no batch runs more full-width rounds
# (_linked).
FIXPOINT_CAP = 64
# Events one block of the exact pass by blocks decides at once
# (_settle_blocks); chosen on the v5e by a sweep of 32 to 1,024
# (PERF.md section 6).
SETTLE_BLOCK = 128
# Blocks of that pass one full-width round costs, measured on the v5e
# (PERF.md section 6): the fixpoint leaves for the pass once its rounds
# have cost what the pass over the unsettled rest would (_linked).
ROUND_BLOCKS = 4

# Packed input columns (u64 each, B rows).
COL_BITS = 0
COL_SLOTS = 1      # (dr_slot+1) u32 | (cr_slot+1) << 32 ; 0 = not found
COL_AMT_LO = 2
COL_AMT_HI = 3
COL_MISC = 4       # flags u16 | code u16 << 16 | ledger u32 << 32
COL_TIMEOUT = 5    # timeout u32 | (p_tgt+1) u32 << 32
N_COLS = 6
# two-phase extension columns:
COL_TP_JOIN = 6    # p_flags u16 | p_code u16 << 16 | p_ledger u32 << 32
COL_TP_SLOTS = 7   # (p_dr_slot+1) u32 | (p_cr_slot+1) u32 << 32  (durable)
COL_TP_AMT_LO = 8  # durable target amount
COL_TP_AMT_HI = 9
COL_TP_REF = 10    # (tgt_ev+1) u32 | dstat_init u32 << 32
N_COLS_TP = 11

# COL_BITS bits (host-marshaled stateless predicates + join booleans).
BIT_TS_NONZERO = 1 << 0
BIT_ID_ZERO = 1 << 1
BIT_ID_MAX = 1 << 2
BIT_DR_ZERO = 1 << 3
BIT_DR_MAX = 1 << 4
BIT_CR_ZERO = 1 << 5
BIT_CR_MAX = 1 << 6
BIT_SAME_ACCT = 1 << 7
BIT_PEND_NONZERO = 1 << 8
BIT_PEND_MAX = 1 << 9
BIT_PEND_SELF = 1 << 10
BIT_E_FOUND = 1 << 11
BIT_P_FOUND = 1 << 12
BIT_T_DR_SET = 1 << 13   # event names a debit account (pv ladder)
BIT_T_CR_SET = 1 << 14
BIT_DR_EQ_P = 1 << 15    # event dr id == target's dr id
BIT_CR_EQ_P = 1 << 16
BIT_LEDGER_EQ_P = 1 << 17  # unused (ledger compare runs on device)

# TransferFlags bits (reference: src/tigerbeetle.zig:127-140).
F_LINKED = 1 << 0
F_PENDING = 1 << 1
F_POST = 1 << 2
F_VOID = 1 << 3
F_BAL_DR = 1 << 4
F_BAL_CR = 1 << 5

# AccountFlags bits (reference: src/tigerbeetle.zig:42-63).
AF_DR_LIMIT = 1 << 1
AF_CR_LIMIT = 1 << 2

S_PENDING, S_POSTED, S_VOIDED, S_EXPIRED = 1, 2, 3, 4

NS_PER_S = jnp.uint64(1_000_000_000)
_MASK8 = jnp.uint64(0xFF)
_MASK16 = jnp.uint64(0xFFFF)
_MASK32 = jnp.uint64(0xFFFFFFFF)
# u64-exactness bound for the linked fixpoint (see resolve.py).
_U64_SAFE = np.uint64(1) << np.uint64(61)


def _sort(operand):
    """lax.sort by the first operand, not stable: every sort in this
    module is of distinct keys, or of keys whose ties are told apart
    by nothing that is read (a stable sort of 2^15 keys compiles for
    the v5e in 17 s, this one in 3 s, and runs no slower)."""
    return jax.lax.sort(operand, num_keys=1, is_stable=False)


def _first_nonzero(*pairs):
    r = jnp.uint32(0)
    for cond, code in pairs:
        r = jnp.where((r == 0) & cond, jnp.uint32(code), r)
    return r


def _unpack(pk):
    """Split the packed (B, C) u64 matrix into named columns."""
    bits = pk[:, COL_BITS]
    slots = pk[:, COL_SLOTS]
    misc = pk[:, COL_MISC]
    return {
        "bits": bits,
        "dr_slot": (slots & _MASK32).astype(jnp.int64) - 1,
        "cr_slot": (slots >> jnp.uint64(32)).astype(jnp.int64) - 1,
        "amt_lo": pk[:, COL_AMT_LO],
        "amt_hi": pk[:, COL_AMT_HI],
        "flags": (misc & _MASK16).astype(jnp.uint32),
        "code": ((misc >> jnp.uint64(16)) & _MASK16).astype(jnp.uint32),
        "ledger": (misc >> jnp.uint64(32)).astype(jnp.uint32),
        "timeout": (pk[:, COL_TIMEOUT] & _MASK32),
        "p_tgt": (pk[:, COL_TIMEOUT] >> jnp.uint64(32)).astype(jnp.int64) - 1,
    }


def _bit(bits, mask):
    return (bits & jnp.uint64(mask)) != 0


def _static_ladder_normal(ev, meta, active):
    """Static precedence ladder for non-post/void transfers, evaluated
    on device (reference ladder: src/state_machine.zig:1465-1504).
    `meta` is the (A, 2) u32 device table [flags, ledger]."""
    bits = ev["bits"]
    flags = ev["flags"]
    A = meta.shape[0]
    drc = jnp.clip(ev["dr_slot"], 0, A - 1)
    crc = jnp.clip(ev["cr_slot"], 0, A - 1)
    dr_found = ev["dr_slot"] >= 0
    cr_found = ev["cr_slot"] >= 0
    dr_ledger = jnp.where(dr_found, meta[drc, 1], 0)
    cr_ledger = jnp.where(cr_found, meta[crc, 1], 0)
    not_pending = (flags & F_PENDING) == 0
    not_balancing = (flags & (F_BAL_DR | F_BAL_CR)) == 0
    amount_zero = (ev["amt_lo"] == 0) & (ev["amt_hi"] == 0)
    r = _first_nonzero(
        (_bit(bits, BIT_TS_NONZERO), CTR.timestamp_must_be_zero),
        ((flags & ~jnp.uint32(0x3F)) != 0, CTR.reserved_flag),
        (_bit(bits, BIT_ID_ZERO), CTR.id_must_not_be_zero),
        (_bit(bits, BIT_ID_MAX), CTR.id_must_not_be_int_max),
        (_bit(bits, BIT_DR_ZERO), CTR.debit_account_id_must_not_be_zero),
        (_bit(bits, BIT_DR_MAX), CTR.debit_account_id_must_not_be_int_max),
        (_bit(bits, BIT_CR_ZERO), CTR.credit_account_id_must_not_be_zero),
        (_bit(bits, BIT_CR_MAX), CTR.credit_account_id_must_not_be_int_max),
        (_bit(bits, BIT_SAME_ACCT), CTR.accounts_must_be_different),
        (_bit(bits, BIT_PEND_NONZERO), CTR.pending_id_must_be_zero),
        (
            not_pending & (ev["timeout"] != 0),
            CTR.timeout_reserved_for_pending_transfer,
        ),
        (not_balancing & amount_zero, CTR.amount_must_not_be_zero),
        (ev["ledger"] == 0, CTR.ledger_must_not_be_zero),
        (ev["code"] == 0, CTR.code_must_not_be_zero),
        (~dr_found, CTR.debit_account_not_found),
        (~cr_found, CTR.credit_account_not_found),
        (dr_ledger != cr_ledger, CTR.accounts_must_have_the_same_ledger),
        (
            ev["ledger"] != dr_ledger,
            CTR.transfer_must_have_the_same_ledger_as_accounts,
        ),
    )
    # Inactive (padding) rows: poisoned so they never apply.
    return jnp.where(active, r, jnp.uint32(CTR.linked_event_failed))


# A leg that names no table row sorts last and matches no touched row.
_NO_SLOT = np.int32(0x7FFFFFFF)
# Rows a write-back scatters per turn of its loop (see _write_back).
_SCATTER_CHUNK = 512


def _touch(table, slot_rows):
    """Dense ranks of a batch's legs over the table rows they name.

    `slot_rows` is one table slot per leg (2B legs, 4B in two_phase);
    a leg whose slot is not a row of the table (-1: account not found)
    gets the key _NO_SLOT, which sorts last.  Sorts of i32 keys do it
    all (slots are below 2^31; a u64 sort is a variadic (u32, u32)
    sort on the TPU, and a sort of 2^14 keys is microseconds where a
    scatter or a binary search of as many is a millisecond): the
    first puts equal slots side by side, so the first leg of each run
    is that row's HEAD; the second packs the heads' slots to the
    front, in order.  A row's RANK is its place there: dense (below
    `rows` at any table size) and monotone in the slot.

    Returns a dict of
      key    (rows,) i32  the legs' keys, in leg order
      rank   (rows,) i32  each leg's row's rank, in leg order (n, a
                          rank no row has, for a leg that names none)
      n      () i32       how many rows the batch touches
      uslots (U,) i32     U = rows.  u < n: the slot of the row of
                          rank u; else a value past the table, which
                          matches no key and which a scatter drops
      hit    (U,) bool    u < n
      old    (U, 8) u64   table[uslots] where hit (else a clipped row,
                          never read: every use is under `hit`)

    Everything downstream (the sums, admission, the release, the
    write-back) is over these U <= 4B ranks; nothing but the gather
    here and the final scatter sees the table."""
    A = table.shape[0]
    rows = slot_rows.shape[0]
    assert A + rows < int(_NO_SLOT), (A, rows)
    found = (slot_rows >= 0) & (slot_rows < A)
    key = jnp.where(found, slot_rows, _NO_SLOT).astype(jnp.int32)
    iota = jnp.arange(rows, dtype=jnp.int32)
    key_s, perm = _sort((key, iota))
    named_s = key_s != _NO_SLOT
    head = jnp.concatenate(
        [jnp.ones(1, bool), key_s[1:] != key_s[:-1]]
    ) & named_s
    n = head.sum(dtype=jnp.int32)
    uslots = _sort(jnp.where(head, key_s, A + iota))
    # The ranks in key order are a running count of the heads; sorting
    # them by `perm` undoes the key sort.
    rank_s = jnp.where(named_s, jnp.cumsum(head.astype(jnp.int32)) - 1, n)
    _, rank = _sort((perm, rank_s))
    return {"key": key, "rank": rank, "n": n, "uslots": uslots,
            "hit": iota < n, "old": table[jnp.minimum(uslots, A - 1)]}


def _sum_legs(t, passes, lo_only=False):
    """Exact per-(touched row, column) u128 sums via ONE one-hot MXU
    matmul shared across several accumulation passes.

    `passes` is a list of (col_rows, amt_lo_rows, amt_hi_rows, valid)
    over the SAME legs; their 8-bit-piece payloads concatenate along
    the feature axis, so the (U, rows) one-hot of `_touch`'s ranks
    (position u against leg r: uslots[u] == key[r]) is generated once
    however many sums a kernel needs (linked: superset admission +
    final apply; two_phase: adds + releases).  It is (rows, rows) at
    any table size: the table's A is nowhere in it.  A row that takes
    512 legs costs what a row that takes one does.

    Amounts decompose into 8-bit pieces (each < 2^8); the one-hot
    bf16 matmul accumulates them in f32 — the CONTRACTION is over the
    legs, so sums stay below rows * 255 < 2^24 and every partial is
    exact — and a base-256 carry recombination rebuilds exact u128
    column deltas.  Invalid legs contribute ZERO payload (a leg that
    names no row matches no position at all).

    `lo_only` halves the payload (8 pieces) when every amount's high
    limb is zero — a trace-time specialization the host router
    selects (the high-limb sum is then just the carry chain's
    overflow).

    Returns one (d_lo, d_hi, limb_ov) of shape (U, 4) per pass; a
    rank no row has (u >= n) sums to zero.
    """
    rows = t["key"].shape[0]
    zero = jnp.uint64(0)
    npieces = 8 if lo_only else 16
    payloads = []
    for col_rows, amt_lo_rows, amt_hi_rows, valid in passes:
        lo = jnp.where(valid, amt_lo_rows, zero)
        pieces = [((lo >> jnp.uint64(s)) & _MASK8).astype(jnp.float32)
                  for s in range(0, 64, 8)]
        if not lo_only:
            hi = jnp.where(valid, amt_hi_rows, zero)
            pieces += [((hi >> jnp.uint64(s)) & _MASK8).astype(jnp.float32)
                       for s in range(0, 64, 8)]
        P = jnp.stack(pieces, axis=-1)  # (rows, npieces)
        colmask = jax.nn.one_hot(col_rows, 4, dtype=jnp.float32)
        payloads.append(
            (colmask[:, :, None] * P[:, None, :]).reshape(rows, 4 * npieces)
        )
    payload = jnp.concatenate(payloads, axis=-1)
    onehot = (t["uslots"][:, None] == t["key"][None, :]).astype(jnp.bfloat16)
    acc_all = jax.lax.dot_general(
        onehot, payload.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(rows, len(passes), 4, npieces).astype(jnp.uint64)

    out = []
    for p in range(len(passes)):
        acc = acc_all[:, p]
        c = acc[:, :, 0]
        d_lo = c & _MASK8
        carry = c >> jnp.uint64(8)
        for k in range(1, 8):
            c = acc[:, :, k] + carry
            d_lo = d_lo | ((c & _MASK8) << jnp.uint64(8 * k))
            carry = c >> jnp.uint64(8)
        if lo_only:
            out.append((d_lo, carry, jnp.zeros((rows, 4), bool)))
            continue
        c = acc[:, :, 8] + carry
        d_hi = c & _MASK8
        carry = c >> jnp.uint64(8)
        for k in range(1, 8):
            c = acc[:, :, 8 + k] + carry
            d_hi = d_hi | ((c & _MASK8) << jnp.uint64(8 * k))
            carry = c >> jnp.uint64(8)
        out.append((d_lo, d_hi, carry != 0))
    return out


def _restack(lo, hi):
    """(rows, 4) low and high limbs -> (rows, 8) in table layout."""
    return jnp.stack(
        [lo[:, 0], hi[:, 0], lo[:, 1], hi[:, 1],
         lo[:, 2], hi[:, 2], lo[:, 3], hi[:, 3]],
        axis=-1,
    )


def _admit(t, d_lo, d_hi, limb_ov):
    """Admission over the touched rows: `old + delta` per column, and
    whether ANY column's u128 add overflows or any account's combined
    dp+dpo / cp+cpo total overflows (mirrors
    BalanceMirror._admit_commit, which the host fast path proved
    bit-parity for).  Returns (new_rows, ov); the caller selects.

    The flags read touched rows only.  An untouched row has a zero
    delta: its adds cannot overflow, and its totals overflow only if
    they did before the batch, which no table holds, because this
    check is what admits every write to it (and `_release` only
    lowers a total).  So the restriction changes no flag."""
    old = t["old"]
    old_lo = old[:, 0::2]
    old_hi = old[:, 1::2]
    new_lo = old_lo + d_lo
    cy = (new_lo < old_lo).astype(jnp.uint64)
    hi_p = old_hi + d_hi
    add_ov1 = hi_p < old_hi
    new_hi = hi_p + cy
    add_ov = add_ov1 | (new_hi < hi_p)

    def tot_ov(lo_a, hi_a, lo_b, hi_b):
        # u128 (a + b) overflow flag.
        lo = lo_a + lo_b
        c = (lo < lo_a).astype(jnp.uint64)
        hp = hi_a + hi_b
        h = hp + c
        return (hp < hi_a) | (h < hp)

    dr_tot_ov = tot_ov(new_lo[:, 0], new_hi[:, 0], new_lo[:, 1], new_hi[:, 1])
    cr_tot_ov = tot_ov(new_lo[:, 2], new_hi[:, 2], new_lo[:, 3], new_hi[:, 3])
    row_ov = (limb_ov | add_ov).any(axis=1) | dr_tot_ov | cr_tot_ov
    return _restack(new_lo, new_hi), (row_ov & t["hit"]).any()


def _release(t, rows8, s_lo, s_hi, s_limb):
    """two_phase's releases over the touched rows: `rows8 - s` per
    column, and whether any column underflows (or a release sum
    overflowed its limbs).  Returns (new_rows, bad)."""
    old_lo = rows8[:, 0::2]
    old_hi = rows8[:, 1::2]
    n_lo = old_lo - s_lo
    borrow = (old_lo < s_lo).astype(jnp.uint64)
    n_hi = old_hi - s_hi - borrow
    under = (old_hi < s_hi) | ((old_hi == s_hi) & (old_lo < s_lo))
    bad = ((under | s_limb).any(axis=1) & t["hit"]).any()
    return _restack(n_lo, n_hi), bad


def _write_back(table, t, rows8, fallback):
    """The batch's new table: `rows8` scattered to the rows the batch
    touches, or, on `fallback`, the table as it was (nothing is
    scattered: no pass over the table selects).

    A scatter on the TPU is a loop over its indices, ~0.1 us each
    whether the index lands or is dropped, so the loop here runs over
    the n touched rows only, _SCATTER_CHUNK at a time (their slots are
    packed to the front of `uslots`, ascending and unique).  The table
    is not donated (a transient fault retries the batch from the same
    array): its one O(A) cost is the copy this loop starts from."""
    n = jnp.where(fallback, 0, t["n"])
    uslots = t["uslots"]
    size = min(_SCATTER_CHUNK, uslots.shape[0])
    assert uslots.shape[0] % size == 0, uslots.shape

    def chunk(state):
        at, table = state
        idx = jax.lax.dynamic_slice(uslots, (at,), (size,))
        upd = jax.lax.dynamic_slice(rows8, (at, jnp.int32(0)), (size, 8))
        table = table.at[idx].set(
            upd, mode="drop", unique_indices=True, indices_are_sorted=True
        )
        return at + size, table

    _, table = jax.lax.while_loop(
        lambda state: state[0] < n, chunk, (jnp.int32(0), table)
    )
    return table


def _summary(results, active, flags_word, last_applied):
    """A batch's answer, twice: the failure-sparse fixed-size summary
    row [n_fail, flags, last_applied+1, n_active, entries...] as
    (SUMMARY_WORDS,) u64, which is what crosses the link, and the
    dense (B,) u32 result codes, which stay on the device unless the
    row's n_fail says its FAIL_CAP entries do not hold them all: the
    engine then fetches the codes too (unpack_summary's `dense`)."""
    results = jnp.where(active, results, jnp.uint32(0))
    fail = results != 0
    n_fail = fail.sum().astype(jnp.uint64)
    # The first FAIL_CAP failing events, in order: a sort packs them
    # to the front (microseconds; a scatter of B updates is 0.5 ms).
    first = jnp.concatenate([
        _sort(
            jnp.where(fail, jnp.arange(B, dtype=jnp.uint32), jnp.uint32(B))
        ),
        jnp.full(FAIL_CAP, B, jnp.uint32),
    ])[:FAIL_CAP]
    entries = jnp.where(
        first < B,
        (first.astype(jnp.uint64) << jnp.uint64(32))
        | results[jnp.minimum(first, B - 1)].astype(jnp.uint64),
        jnp.uint64(0),
    )
    head = jnp.stack(
        [
            n_fail,
            flags_word,
            (last_applied + 1).astype(jnp.uint64),
            active.sum().astype(jnp.uint64),
        ]
    )
    return jnp.concatenate([head, entries]), results


def _split_scalars(pkx):
    """(pk, n, ts_base) of an uploaded (ROWS, ncols) buffer: the event
    rows, and the scalars seal_scalars wrote into row SCALAR_ROW."""
    tail = pkx[SCALAR_ROW]
    if pkx.dtype == jnp.uint32:
        ts_base = tail[1].astype(jnp.uint64) | (
            tail[2].astype(jnp.uint64) << jnp.uint64(32)
        )
    else:
        ts_base = tail[1]
    return pkx[:B], tail[0].astype(jnp.int64), ts_base


# ---------------------------------------------------------------------------
# Order-free kernel.


def _orderfree(table, meta, pkx, lo_only=False):
    """Order-independent batch: full static ladder + overflow admission
    + scatter apply + result codes, all on device.

    Host routing guarantees (same class the r3 host fast path took):
    no linked/post/void/balancing flags, unique fresh ids, no
    limit/history accounts touched.  Within that class the only
    dynamic codes are the overflow family — excluded wholesale by the
    total-sum admission check (amounts are non-negative, so any prefix
    is bounded by the all-applied total; reference:
    src/state_machine.zig:1531-1545) — and overflows_timeout, which is
    order-independent and computed per event here.
    """
    pk, n, ts_base = _split_scalars(pkx)
    return _orderfree_core(table, meta, _unpack(pk), n, ts_base, lo_only)


# Tight 20-byte/event format for the dominant order-free class:
# 5xu32 instead of 6xu64 is 2.4x fewer input bytes to upload (an h2d
# of 400 KB costs 1.03 ms on the v5e, PERF.md section 7.8).
# Host gating (exact facts, not predictions — no device re-check
# needed): every amount_hi == 0, amount_lo < 2^32, timeout == 0.
# Word 0 packs the predicate bits (low 18), the 6 transfer-flag bits,
# a code!=0 bit, and a reserved-flags bit; words 1/2 are slot+1;
# word 3 the u32 amount; word 4 the full ledger.
TIGHT_FLAGS_SHIFT = 18
TIGHT_CODE_BIT = 1 << 24
TIGHT_RESERVED_BIT = 1 << 25
N_COLS_TIGHT = 5


def _orderfree_tight(table, meta, pkx):
    pk32, n, ts_base = _split_scalars(pkx)
    w0 = pk32[:, 0]
    zero64 = jnp.zeros(B, jnp.uint64)
    # The reserved-flag predicate rides flag bit 6: the ladder's
    # (flags & ~0x3F) != 0 check then fires exactly for it.
    flags = (
        ((w0 >> jnp.uint32(TIGHT_FLAGS_SHIFT)) & jnp.uint32(0x3F))
        | (jnp.where(w0 & jnp.uint32(TIGHT_RESERVED_BIT), 1, 0) << 6)
    ).astype(jnp.uint32)
    ev = {
        "bits": w0.astype(jnp.uint64),
        "dr_slot": pk32[:, 1].astype(jnp.int64) - 1,
        "cr_slot": pk32[:, 2].astype(jnp.int64) - 1,
        "amt_lo": pk32[:, 3].astype(jnp.uint64),
        "amt_hi": zero64,
        "flags": flags,
        "code": jnp.where(
            w0 & jnp.uint32(TIGHT_CODE_BIT), jnp.uint32(1), jnp.uint32(0)
        ),
        "ledger": pk32[:, 4],
        "timeout": zero64,
        "p_tgt": jnp.full(B, -1, jnp.int64),
    }
    return _orderfree_core(table, meta, ev, n, ts_base, lo_only=True)


def _orderfree_core(table, meta, ev, n, ts_base, lo_only):
    iota = jnp.arange(B, dtype=jnp.int64)
    active = iota < n
    r = _static_ladder_normal(ev, meta, active)

    ts_i = ts_base + iota.astype(jnp.uint64)
    expires = ts_i + ev["timeout"] * NS_PER_S
    ov_timeout = (ev["timeout"] != 0) & (expires < ts_i)
    r = jnp.where((r == 0) & ov_timeout, jnp.uint32(CTR.overflows_timeout), r)

    ok = active & (r == 0)
    is_pending = (ev["flags"] & F_PENDING) != 0
    dcol = jnp.where(is_pending, 0, 1)
    ccol = jnp.where(is_pending, 2, 3)
    slot_rows = jnp.concatenate([ev["dr_slot"], ev["cr_slot"]])
    col_rows = jnp.concatenate([dcol, ccol])
    amt_lo2 = jnp.concatenate([ev["amt_lo"]] * 2)
    amt_hi2 = jnp.concatenate([ev["amt_hi"]] * 2)
    valid = jnp.concatenate([ok, ok])
    t = _touch(table, slot_rows)
    (sums,) = _sum_legs(
        t, [(col_rows, amt_lo2, amt_hi2, valid)], lo_only=lo_only
    )
    new_rows, ov = _admit(t, *sums)
    new_table = _write_back(table, t, new_rows, ov)

    applied_idx = jnp.where(ok, iota, -1)
    last_applied = applied_idx.max()
    flags_word = jnp.where(ov, jnp.uint64(FLAG_OVERFLOW), jnp.uint64(0))
    return (new_table, *_summary(r, active, flags_word, last_applied))


# ---------------------------------------------------------------------------
# Linked-chain kernel (port of resolve.linked_resolve to device).


def _linked(table, meta, pkx, small=False, block=SETTLE_BLOCK, gate=True):
    """Linked-chain batch of plain posted transfers; limit-flag
    accounts allowed.  Jacobi fixpoint over per-account segmented
    prefix sums converges to the exact sequential verdicts (see
    resolve.py for the correctness argument; reference:
    src/state_machine.zig:1220-1306, src/tigerbeetle.zig:31-39).

    A batch that has not settled in its full-width rounds is decided
    exactly in this same program: by blocks of `block` events in order
    (_settle_blocks), from the chain of the first event the last round
    changed.  The rounds stop at FIXPOINT_CAP, or with `gate` as soon
    as the rounds run (two at least) have cost what the pass over the
    rest would, ROUND_BLOCKS blocks a round: the cost of a batch that
    would settle in more rounds is then at most about twice the cheaper
    path's.  The gate stays shut for a batch with a chain longer than a
    block, which the pass leaves to the host.  Either way the verdicts
    are the exact ones; only the flags word's iterations and settle
    bits tell the paths apart.  `gate=False` is the cap alone.

    `small` is a trace-time specialization the host router selects
    when the batch's total amount contribution fits i32: each
    fixpoint prefix is then ONE i32 cumsum instead of four 16-bit
    pieces (the fixpoint's dominant per-iteration cost).  The device
    still verifies the bound and raises the precondition flag (exact
    host fallback) if the router's pick was wrong."""
    pk, n, _ts_base = _split_scalars(pkx)
    ev = _unpack(pk)
    A = table.shape[0]
    iota = jnp.arange(B, dtype=jnp.int64)
    active = iota < n
    static = _static_ladder_normal(ev, meta, active)

    linked = active & ((ev["flags"] & F_LINKED) != 0)
    # Chain structure: maximal runs of linked + following event.
    start = jnp.concatenate(
        [jnp.ones(1, bool), ~linked[:-1]]
    )
    chain_id, chain_start_ev, chain_last_ev = _chain_layout(start)

    # Unconditional per-event codes; chain_open overrides on the last
    # active event when it still carries the linked flag.
    code0 = static
    is_last = iota == (n - 1)
    code0 = jnp.where(
        is_last & linked, jnp.uint32(CTR.linked_event_chain_open), code0
    )
    static_ok = active & (code0 == 0)

    drc = jnp.clip(ev["dr_slot"], 0, A - 1)
    crc = jnp.clip(ev["cr_slot"], 0, A - 1)
    dr_flags = jnp.where(ev["dr_slot"] >= 0, meta[drc, 0], 0)
    cr_flags = jnp.where(ev["cr_slot"] >= 0, meta[crc, 0], 0)
    LIM = jnp.uint32(AF_DR_LIMIT | AF_CR_LIMIT)
    dlim = (dr_flags & AF_DR_LIMIT) != 0
    clim = (cr_flags & AF_CR_LIMIT) != 0

    # ---- preconditions (device-evaluated; violations -> host fallback)
    # The u64-safety of the rows the limit entries touch is read off
    # the entries' own gathered rows, below (ent_rows).
    precond_bad = (static_ok & (ev["amt_hi"] != 0)).any()
    ent_d = static_ok & ((dr_flags & LIM) != 0)
    ent_c = static_ok & ((cr_flags & LIM) != 0)
    contrib = jnp.where(static_ok, ev["amt_lo"], jnp.uint64(0))
    sum_bound = jnp.float64((1 << 31) - 1) if small else jnp.float64(_U64_SAFE)
    precond_bad = precond_bad | (
        contrib.astype(jnp.float64).sum() >= sum_bound
    )

    # ---- superset overflow admission legs (static_ok events, posted
    # cols); the sums themselves ride the SAME one-hot matmul as the
    # final apply below, and the fixpoint's keys the same rank pass.
    slot_rows = jnp.concatenate([ev["dr_slot"], ev["cr_slot"]])
    t = _touch(table, slot_rows)
    col_rows = jnp.concatenate(
        [jnp.ones(B, jnp.int32), jnp.full(B, 3, jnp.int32)]
    )
    amt_lo2 = jnp.concatenate([ev["amt_lo"]] * 2)
    amt_hi2 = jnp.concatenate([ev["amt_hi"]] * 2)
    sup_valid = jnp.concatenate([static_ok, static_ok])

    # ---- fixpoint over (slot, event)-sorted limit entries
    # (_limit_entries), a Jacobi round at a time (_limit_round).
    M = 2 * B
    e = _limit_entries(
        jnp.concatenate([ent_d, ent_c]), t["rank"], ev["amt_lo"],
        jnp.concatenate([dlim, clim]), chain_start_ev[chain_id], M,
    )
    ch = {"valid": active, "sok": static_ok,
          "start_of": chain_start_ev[chain_id],
          "last_of": chain_last_ev[chain_id]}

    # Each entry's row as the batch found it, out of the gathered
    # rows.  Precondition: a row a limit entry touches is u64-safe.
    ent_rows = t["old"][jnp.minimum(e["rank"], M - 1)]
    precond_bad = precond_bad | (
        e["valid"][:, None] & (
            (ent_rows[:, 1::2] != 0)
            | (ent_rows[:, 0::2] >= jnp.uint64(_U64_SAFE))
        )
    ).any()
    rows = {"dp": ent_rows[:, 0], "dpo": ent_rows[:, 2],
            "cp": ent_rows[:, 4], "cpo": ent_rows[:, 6]}

    def chain_state(pass_):
        fails = (~pass_ & active).astype(jnp.int32)
        F = jnp.cumsum(fails)
        base = (F - fails)[chain_start_ev][chain_id]
        applied_prefix = (F - base) == 0
        chain_ok = applied_prefix[chain_last_ev]
        # The first failing event of its chain: it fails and no event
        # of the chain before it does.
        first_fail = (fails != 0) & (F - fails == base)
        return applied_prefix, chain_ok, first_fail

    prefix = _prefix_i32 if small else _prefix_u64
    W = min(block, B)
    n32 = n.astype(jnp.int32)
    # The gate opens only where the pass decides every chain itself.
    longest = jnp.where(
        active, jnp.minimum(ch["last_of"], n32 - 1) - ch["start_of"] + 1, 0
    ).max()
    gate_ok = gate & (longest <= W)

    def body(state):
        pass_prev, _dr_fail, _cr_fail, it, _conv, _s = state
        pass_, dr_fail, cr_fail = _limit_round(pass_prev, ch, e, rows, prefix)
        changed = (pass_ != pass_prev) & active
        # Every event before the first that changed is final (its
        # verdict reads only earlier events'): s is its chain's start.
        s = ch["start_of"][jnp.argmax(changed)]
        return pass_, dr_fail, cr_fail, it + 1, ~changed.any(), s

    def cond(state):
        _p, _d, _c, it, conv, s = state
        pass_blocks = (n32 - s + W - 1) // W
        gated = gate_ok & (it >= 2) & (it * ROUND_BLOCKS >= pass_blocks)
        return (~conv) & (it < FIXPOINT_CAP) & ~gated

    init = (
        static_ok,
        jnp.zeros(B, bool),
        jnp.zeros(B, bool),
        jnp.int32(0),
        jnp.bool_(False),
        jnp.int32(0),
    )
    # One unconditional iteration then loop to convergence: matches the
    # host resolver's "verdict of event 0 is unconditional" induction.
    state = body(init)
    pass_, dr_fail, cr_fail, iters, conv, s0 = jax.lax.while_loop(
        cond, body, state
    )

    # ---- unsettled: the exact pass by blocks (a branch that a batch
    # settled in the rounds never executes).
    settle = ~conv & ~precond_bad

    def by_blocks(_):
        return _settle_blocks(
            W, prefix, ch, n32, start, chain_start_ev,
            ent_d, ent_c, t["rank"], ev["amt_lo"], dlim, clim, t["old"],
            pass_, dr_fail, cr_fail, s0,
        )

    def as_is(_):
        return pass_, dr_fail, cr_fail, jnp.int32(0), jnp.bool_(False)

    pass_, dr_fail, cr_fail, settle_rounds, long_chain = jax.lax.cond(
        settle, by_blocks, as_is, None
    )

    applied_prefix, chain_ok, is_ff = chain_state(pass_)

    # ---- result codes.
    results = jnp.zeros(B, jnp.uint32)
    bad_chain = ~chain_ok
    member_bad = bad_chain[chain_id] & active
    own_code = jnp.where(
        code0 != 0,
        code0,
        jnp.where(
            dr_fail,
            jnp.uint32(CTR.exceeds_credits),
            jnp.uint32(CTR.exceeds_debits),
        ),
    )
    results = jnp.where(
        member_bad, jnp.uint32(CTR.linked_event_failed), results
    )
    results = jnp.where(is_ff, own_code, results)
    results = jnp.where(
        is_last & linked & member_bad,
        jnp.uint32(CTR.linked_event_chain_open),
        results,
    )

    # ---- superset admission + apply in ONE shared-one-hot matmul
    # (events with results == 0 are exactly the members of
    # fully-passing chains).
    okev = active & (results == 0)
    ap_valid = jnp.concatenate([okev, okev])
    sup, ap = _sum_legs(
        t,
        [
            (col_rows, amt_lo2, amt_hi2, sup_valid),
            (col_rows, amt_lo2, amt_hi2, ap_valid),
        ],
        lo_only=True,
    )
    _, sup_ov = _admit(t, *sup)
    # The pass decides the batch unless it met a chain longer than a
    # block (or the batch broke a precondition anyway).
    undecided = precond_bad | long_chain
    fallback = sup_ov | undecided
    # The applied sums are the superset's or less: they overflow only
    # where sup_ov has already flagged.
    new_rows, _ov2 = _admit(t, *ap)
    new_table = _write_back(table, t, new_rows, fallback)

    last_applied = jnp.where(applied_prefix & active, iota, -1).max()
    flags_word = (
        jnp.where(sup_ov, jnp.uint64(FLAG_OVERFLOW), jnp.uint64(0))
        | jnp.where(undecided, jnp.uint64(FLAG_PRECOND), jnp.uint64(0))
        | jnp.where(settle, jnp.uint64(FLAG_SETTLED), jnp.uint64(0))
        | (iters.astype(jnp.uint64) << jnp.uint64(ITERS_SHIFT))
        | (settle_rounds.astype(jnp.uint64) << jnp.uint64(SETTLE_SHIFT))
    )
    return (new_table, *_summary(results, active, flags_word, last_applied))


def _chain_layout(start):
    """(chain_id, chain_start_ev, chain_last_ev) of E events from their
    chain-start flags: chain c starts at the c-th start event (a sort
    packs them to the front; E where there is no chain c) and ends
    where the next one starts."""
    E = start.shape[0]
    chain_id = jnp.cumsum(start.astype(jnp.int32)) - 1
    chain_start_ev = _sort(
        jnp.where(start, jnp.arange(E, dtype=jnp.int32), jnp.int32(E))
    )
    chain_last_ev = jnp.concatenate(
        [chain_start_ev[1:], jnp.full(1, E, jnp.int32)]
    ) - 1
    return chain_id, chain_start_ev, chain_last_ev


def _limit_entries(ent, rank, amt, lim, start_of, no_rank):
    """The limit entries of E events (a batch, or a block of one),
    sorted by (row, event).  `ent`, `rank` and `lim` are over the 2E
    legs, debit legs first: whether the leg is an entry (its row
    carries a limit), its row's dense rank (_touch: monotone in the
    slot, so the order is the order by slot), and whether the limit
    checks the leg's side; `start_of` is each event's chain's first
    event, `no_rank` a rank no row has.

    The TPU sort's cost scales with operand count, so the order is ONE
    packed u32 key — rank << (log2 E + 1) | event << 1 | side, 28 bits
    at E = B — and the per-entry columns are recovered arithmetically
    from the sorted keys (events are distinct within a row because
    dr != cr, so the side bit never affects the required event order);
    invalid legs get sentinel keys that sort to the end.  `at` undoes
    the sort: where each leg's entry sorted to, so that a round gathers
    its events' verdicts."""
    E = amt.shape[0]
    M = 2 * E
    sh = jnp.uint32(E.bit_length())
    jpos = jnp.arange(M, dtype=jnp.int32)
    iota = jnp.arange(E, dtype=jnp.uint32)
    sentinel = jnp.uint32(0xFFFFFFFF)
    key = jnp.where(
        ent,
        (rank.astype(jnp.uint32) << sh)
        | (jnp.concatenate([iota, iota]) << jnp.uint32(1))
        | jnp.concatenate([jnp.zeros(E, jnp.uint32), jnp.ones(E, jnp.uint32)]),
        sentinel,
    )
    key_s, perm = _sort((key, jpos))
    _, at = _sort((perm, jpos))
    valid = key_s != sentinel
    evs = jnp.where(
        valid, (key_s >> jnp.uint32(1)) & jnp.uint32(E - 1), jnp.uint32(0)
    ).astype(jnp.int32)
    erank = jnp.where(valid, key_s >> sh, jnp.uint32(no_rank)).astype(jnp.int32)
    deb = valid & ((key_s & jnp.uint32(1)) == 0)
    eamt = amt[evs]
    seg_new = jnp.concatenate(
        [jnp.ones(1, bool), erank[1:] != erank[:-1]]
    ) & valid
    seg_first = jax.lax.associative_scan(
        jnp.maximum, jnp.where(seg_new, jpos, 0)
    )
    # Chain-start boundary per entry, in the SAME packed-key encoding
    # and dtype (side bit 0 sorts before either side of the start
    # event).  bpos: how many entries sort before each boundary.  The
    # boundaries are in order themselves (within a row the chain starts
    # rise with the events), so ONE merge sort of both lists, tagged in
    # the low bit (a boundary before its equal entry), a running count
    # of the entries, and a second sort that packs the boundaries'
    # counts to the front do what 2E binary searches did.
    bkey = jnp.where(
        valid,
        (erank.astype(jnp.uint32) << sh)
        | (start_of[evs].astype(jnp.uint32) << jnp.uint32(1)),
        sentinel,
    )
    merged = _sort(jnp.concatenate([
        jnp.where(valid, bkey << jnp.uint32(1), jnp.uint32(0xFFFFFFFE)),
        jnp.where(valid, (key_s << jnp.uint32(1)) | jnp.uint32(1), sentinel),
    ]))
    is_entry = (merged & jnp.uint32(1)) != 0
    bpos = _sort(
        jnp.where(is_entry, sentinel, jnp.cumsum(is_entry.astype(jnp.uint32)))
    )[:M].astype(jnp.int32)
    view = valid & lim[perm]
    zero = jnp.uint64(0)
    return {
        "valid": valid, "deb": deb, "ev": evs, "rank": erank, "amt": eamt,
        "seg_first": seg_first, "bpos": bpos, "at": at,
        "view_d": view & deb, "view_c": view & ~deb,
        "amt_d": jnp.where(deb, eamt, zero),
        "amt_c": jnp.where(valid & ~deb, eamt, zero),
    }


def _prefix_i32(v):
    """Exclusive prefix sums of amounts whose total fits i32 (linked
    `small`: the device verifies the bound), as i32: ONE cumsum, and a
    gather of it is one 32-bit word where a u64 is two."""
    v = v.astype(jnp.int32)
    return jnp.cumsum(v) - v


def _prefix_u64(v):
    """Exact u64 exclusive prefix sums.  A direct u64 cumsum lowers to
    a variadic (u32, u32) reduce-window that blows XLA:TPU's scoped
    vmem inside while_loop bodies (tests/test_tpu_compile.py compiles
    this for the v5e): four 16-bit-piece i32 cumsums instead (totals
    < 2^61 by the precondition; piece sums < 2B * 2^16 < 2^31)."""
    cs = jnp.uint64(0)
    for k in range(4):
        p = ((v >> jnp.uint64(16 * k)) & _MASK16).astype(jnp.int32)
        cs = cs + (jnp.cumsum(p).astype(jnp.uint64) << jnp.uint64(16 * k))
    return cs - v


def _chain_verdicts(pass_, ch):
    """(applied_prefix, chain_ok) a event: whether every event of its
    chain up to it passes, and whether its whole chain does."""
    fails = (~pass_ & ch["valid"]).astype(jnp.int32)
    F = jnp.cumsum(fails)
    applied_prefix = (F - (F - fails)[ch["start_of"]]) == 0
    return applied_prefix, applied_prefix[ch["last_of"]]


def _limit_round(pass_prev, ch, e, rows, prefix):
    """One Jacobi round over limit entries (_limit_entries): each
    entry's row as the verdicts `pass_prev` give the events before it
    leave it — `rows` plus an earlier chain's legs where the whole
    chain passes, its own chain's where every event of it so far does
    — and the verdicts that follow.  -> (pass_, dr_fail, cr_fail)"""
    applied_prefix, chain_ok = _chain_verdicts(pass_prev, ch)
    # Both bits of an entry's event in one gather.
    w = ((chain_ok.astype(jnp.int32) << 1)
         | applied_prefix.astype(jnp.int32))[e["ev"]]
    wce, wie = (w & 2) != 0, (w & 1) != 0
    zero = jnp.uint64(0)
    Pdc = prefix(jnp.where(wce, e["amt_d"], zero))
    Pcc = prefix(jnp.where(wce, e["amt_c"], zero))
    Pdi = prefix(jnp.where(wie, e["amt_d"], zero))
    Pci = prefix(jnp.where(wie, e["amt_c"], zero))

    def before(Pc, Pi):
        # Exclusive prefixes: P[b] - P[a] sums entries [a, b).  The
        # earlier chains' legs are [seg_first, bpos), the own chain's
        # [bpos, here); exact in wrapping arithmetic.
        return (
            (Pc - Pi)[e["bpos"]] - Pc[e["seg_first"]] + Pi
        ).astype(jnp.uint64)

    deb_before = before(Pdc, Pdi)
    cred_before = before(Pcc, Pci)
    bad_d = e["view_d"] & (
        rows["dp"] + rows["dpo"] + deb_before + e["amt"]
        > rows["cpo"] + cred_before
    )
    bad_c = e["view_c"] & (
        rows["cp"] + rows["cpo"] + cred_before + e["amt"]
        > rows["dpo"] + deb_before
    )
    # An event's debit entry is where its debit leg sorted to, its
    # credit entry where its credit leg did.
    E = pass_prev.shape[0]
    dr_fail = bad_d[e["at"][:E]]
    cr_fail = bad_c[e["at"][E:]]
    return ch["sok"] & ~dr_fail & ~cr_fail, dr_fail, cr_fail


def _settle_blocks(W, prefix, ch, n, start, chain_start_ev, ent_d, ent_c,
                   rank, amt, dlim, clim, old, pass_, dr_fail, cr_fail, s0):
    """The exact pass after the linked fixpoint's rounds: Gauss-Seidel by
    blocks.  The batch is taken in order, in blocks of at most W
    events that never split a chain; each block runs the same Jacobi
    round (_limit_round) over its own limit entries alone (arrays of
    2W, where a full-width round is over 2B), starting from the rows
    as the earlier blocks' applied chains left them.  A block of w
    events settles within w + 1 rounds (event k's verdict reads only
    earlier events', so it is final after k + 1 rounds), so no block
    needs a cap or a split: the dependency depth is paid a block at a
    time.

    The full-width rounds already run are not wasted: every event
    before the first whose verdict changed in the last of them is
    final (its verdict reads only earlier events', which did not
    change), so the pass starts at that event's chain, `s0`.

    Returns (pass_, dr_fail, cr_fail, rounds, long_chain): the exact
    verdicts, the blocks' rounds summed, and whether the pass met a
    chain longer than a block, which it leaves to the host (the
    verdicts are then not final)."""
    Bn = pass_.shape[0]
    M = 2 * Bn
    iota = jnp.arange(Bn, dtype=jnp.int32)
    rank_d, rank_c = rank[:Bn], rank[Bn:]

    # Each rank's applied debits and credits from the final chains.
    _ap, chain_ok = _chain_verdicts(pass_, ch)
    applied = chain_ok & ch["valid"] & (iota < s0)
    dc = jnp.zeros((M, 2), jnp.uint64)
    dc = dc.at[jnp.where(ent_d & applied, rank_d, M), 0].add(amt, mode="drop")
    dc = dc.at[jnp.where(ent_c & applied, rank_c, M), 1].add(amt, mode="drop")
    rows_lo = old[:, 0::2]      # dp, dpo, cp, cpo

    def pad(x, fill):
        return jnp.concatenate([x, jnp.full(W, fill, x.dtype)])

    cols = {
        "sok": pad(ch["sok"], False), "start": pad(start, True),
        "ent_d": pad(ent_d, False), "ent_c": pad(ent_c, False),
        "rank_d": pad(rank_d, M), "rank_c": pad(rank_c, M),
        "amt": pad(amt, 0), "dlim": pad(dlim, False), "clim": pad(clim, False),
    }
    li = jnp.arange(W, dtype=jnp.int32)

    def solve(s, valid, guess, dc):
        """The block of events s + li where `valid`: -> (pass, dr_fail,
        cr_fail, rounds, settled, the block's applied legs as (rank,
        column, amount))."""
        c = {k: jax.lax.dynamic_slice(v, (s,), (W,)) for k, v in cols.items()}
        both = jnp.concatenate([valid, valid])
        st = c["start"] | (li == 0) | ~valid
        cid, cstart, clast = _chain_layout(st)
        bch = {"valid": valid, "sok": c["sok"] & valid,
               "start_of": cstart[cid], "last_of": clast[cid]}
        e = _limit_entries(
            jnp.concatenate([c["ent_d"], c["ent_c"]]) & both,
            jnp.concatenate([c["rank_d"], c["rank_c"]]), c["amt"],
            jnp.concatenate([c["dlim"], c["clim"]]), bch["start_of"], M,
        )
        # Each entry's row as the earlier blocks left it: the batch's
        # row, plus what their applied chains posted.
        erc = jnp.minimum(e["rank"], M - 1)
        r = rows_lo[erc]
        rows = {"dp": r[:, 0], "dpo": r[:, 1] + dc[erc, 0],
                "cp": r[:, 2], "cpo": r[:, 3] + dc[erc, 1]}

        def body(state):
            pass_prev, _d, _c, it, _conv = state
            p, d, cr = _limit_round(pass_prev, bch, e, rows, prefix)
            return p, d, cr, it + 1, (p == pass_prev).all()

        init = (guess & bch["sok"], jnp.zeros(W, bool), jnp.zeros(W, bool),
                jnp.int32(0), jnp.bool_(False))
        pass_l, dr_f, cr_f, rounds, conv = jax.lax.while_loop(
            lambda st: ~st[4] & (st[3] <= W + 1), body, body(init)
        )
        _ap, chain_ok = _chain_verdicts(pass_l, bch)
        app = e["valid"] & chain_ok[e["ev"]]
        adds = (jnp.where(e["valid"], e["rank"], M),
                jnp.where(e["deb"], 0, 1),
                jnp.where(app, e["amt"], jnp.uint64(0)))
        return pass_l, dr_f, cr_f, rounds, conv, adds

    def step(state):
        s, pass_p, dr_p, cr_p, dc, rounds, _long = state
        # The block: whole chains from s, W events at most.
        lim = s + W
        k = (chain_start_ev <= lim).sum(dtype=jnp.int32)
        e_ = jnp.where(n <= lim, n, chain_start_ev[k - 1])
        long_chain = e_ <= s
        e_ = jnp.maximum(e_, s)
        valid = li < (e_ - s)

        def put(x_p, v):
            keep = jax.lax.dynamic_slice(x_p, (s,), (W,))
            return jax.lax.dynamic_update_slice(
                x_p, jnp.where(valid, v, keep), (s,))

        guess = jax.lax.dynamic_slice(pass_p, (s,), (W,))
        pass_l, dr_f, cr_f, r, conv, (rk, col, add) = solve(s, valid, guess, dc)
        dc = dc.at[rk, col].add(add, mode="drop")
        return (e_, put(pass_p, pass_l), put(dr_p, dr_f), put(cr_p, cr_f),
                dc, rounds + r, long_chain | ~conv)

    state = (s0, pad(pass_, False), pad(dr_fail, False), pad(cr_fail, False),
             dc, jnp.int32(0), jnp.bool_(False))
    _s, pass_p, dr_p, cr_p, _dc, rounds, long_chain = jax.lax.while_loop(
        lambda st: (st[0] < n) & ~st[6], step, state
    )
    return pass_p[:Bn], dr_p[:Bn], cr_p[:Bn], rounds, long_chain


# ---------------------------------------------------------------------------
# Two-phase kernel (port of resolve.two_phase_resolve to device).


def _two_phase(table, meta, pkx, lo_only=False):
    """Pending-create + post/void batch with balance-independent
    verdicts (router preconditions: no linked/balancing, all timeouts
    zero, no limit/history accounts, unique fresh ids).  Closed-form:
    vectorized ladder + first-wins winner reduction, then scatter
    apply of adds and releases (reference:
    src/state_machine.zig:1608-1741)."""
    pk, n, _ts_base = _split_scalars(pkx)
    ev = _unpack(pk)
    iota = jnp.arange(B, dtype=jnp.int64)
    active = iota < n
    bits = ev["bits"]
    flags = ev["flags"]
    is_pv = (flags & (F_POST | F_VOID)) != 0
    pend_flag = (flags & F_PENDING) != 0

    # --- static ladders (normal for creates, pv prefix for post/void).
    static_n = _static_ladder_normal(ev, meta, active)
    post = (flags & F_POST) != 0
    void = (flags & F_VOID) != 0
    pv_excl = (
        (post & void)
        | (is_pv & ((flags & F_PENDING) != 0))
        | (is_pv & ((flags & F_BAL_DR) != 0))
        | (is_pv & ((flags & F_BAL_CR) != 0))
    )
    static_pv = _first_nonzero(
        (_bit(bits, BIT_TS_NONZERO), CTR.timestamp_must_be_zero),
        ((flags & ~jnp.uint32(0x3F)) != 0, CTR.reserved_flag),
        (_bit(bits, BIT_ID_ZERO), CTR.id_must_not_be_zero),
        (_bit(bits, BIT_ID_MAX), CTR.id_must_not_be_int_max),
        (pv_excl, CTR.flags_are_mutually_exclusive),
        (~_bit(bits, BIT_PEND_NONZERO), CTR.pending_id_must_not_be_zero),
        (_bit(bits, BIT_PEND_MAX), CTR.pending_id_must_not_be_int_max),
        (_bit(bits, BIT_PEND_SELF), CTR.pending_id_must_be_different),
        (ev["timeout"] != 0, CTR.timeout_reserved_for_pending_transfer),
    )
    static_pv = jnp.where(
        active, static_pv, jnp.uint32(CTR.linked_event_failed)
    )
    code = jnp.where(is_pv, static_pv, static_n)

    # --- pv dynamic ladder.
    tp_join = pk[:, COL_TP_JOIN]
    p_flags_d = (tp_join & _MASK16).astype(jnp.uint32)
    p_code_d = ((tp_join >> jnp.uint64(16)) & _MASK16).astype(jnp.uint32)
    p_ledger_d = (tp_join >> jnp.uint64(32)).astype(jnp.uint32)
    tp_slots = pk[:, COL_TP_SLOTS]
    p_dr_slot_d = (tp_slots & _MASK32).astype(jnp.int64) - 1
    p_cr_slot_d = (tp_slots >> jnp.uint64(32)).astype(jnp.int64) - 1
    p_amt_lo_d = pk[:, COL_TP_AMT_LO]
    p_amt_hi_d = pk[:, COL_TP_AMT_HI]
    tp_ref = pk[:, COL_TP_REF]
    tgt_ev = (tp_ref & _MASK32).astype(jnp.int64) - 1
    dstat_init = (tp_ref >> jnp.uint64(32)).astype(jnp.uint32)
    p_found = _bit(bits, BIT_P_FOUND)

    pv = is_pv & (code == 0)
    tgt_c = jnp.clip(tgt_ev, 0, B - 1)
    in_batch = pv & (tgt_ev >= 0) & (tgt_ev < iota)
    tgt_created = in_batch & (code[tgt_c] == 0)
    durable = pv & p_found & ~in_batch
    found = tgt_created | durable

    def app(c, cond, v):
        return jnp.where((c == 0) & cond & is_pv, jnp.uint32(v), c)

    code = app(code, pv & ~found, CTR.pending_transfer_not_found)
    p_flags = jnp.where(in_batch, flags[tgt_c], p_flags_d)
    code = app(
        code,
        found & ((p_flags & F_PENDING) == 0),
        CTR.pending_transfer_not_pending,
    )
    # Account-id mismatches: host ships equality predicates (u128 id
    # compares are stateless byte predicates); validity gating here.
    code = app(
        code,
        found & _bit(bits, BIT_T_DR_SET) & ~_bit(bits, BIT_DR_EQ_P),
        CTR.pending_transfer_has_different_debit_account_id,
    )
    code = app(
        code,
        found & _bit(bits, BIT_T_CR_SET) & ~_bit(bits, BIT_CR_EQ_P),
        CTR.pending_transfer_has_different_credit_account_id,
    )
    p_ledger = jnp.where(in_batch, ev["ledger"][tgt_c], p_ledger_d)
    p_code_t = jnp.where(in_batch, ev["code"][tgt_c], p_code_d)
    code = app(
        code,
        found & (ev["ledger"] > 0) & (ev["ledger"] != p_ledger),
        CTR.pending_transfer_has_different_ledger,
    )
    code = app(
        code,
        found & (ev["code"] > 0) & (ev["code"] != p_code_t),
        CTR.pending_transfer_has_different_code,
    )
    p_amt_lo = jnp.where(in_batch, ev["amt_lo"][tgt_c], p_amt_lo_d)
    p_amt_hi = jnp.where(in_batch, ev["amt_hi"][tgt_c], p_amt_hi_d)
    t_amt_set = (ev["amt_lo"] != 0) | (ev["amt_hi"] != 0)
    res_amt_lo = jnp.where(t_amt_set, ev["amt_lo"], p_amt_lo)
    res_amt_hi = jnp.where(t_amt_set, ev["amt_hi"], p_amt_hi)

    def gt128(a_lo, a_hi, b_lo, b_hi):
        return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo > b_lo))

    code = app(
        code,
        found & gt128(res_amt_lo, res_amt_hi, p_amt_lo, p_amt_hi),
        CTR.exceeds_pending_transfer_amount,
    )
    code = app(
        code,
        found & void & gt128(p_amt_lo, p_amt_hi, res_amt_lo, res_amt_hi),
        CTR.pending_transfer_has_different_amount,
    )
    dstat_ev = jnp.where(durable, dstat_init, jnp.uint32(S_PENDING))
    code = app(code, durable & (dstat_ev == S_POSTED),
               CTR.pending_transfer_already_posted)
    code = app(code, durable & (dstat_ev == S_VOIDED),
               CTR.pending_transfer_already_voided)
    code = app(code, durable & (dstat_ev == S_EXPIRED),
               CTR.pending_transfer_expired)

    # --- first-wins winner per target.
    cand = pv & (code == 0)
    p_tgt = ev["p_tgt"]
    tkey = jnp.where(
        cand,
        jnp.where(in_batch, tgt_c, B + jnp.clip(p_tgt, 0, B - 1)),
        2 * B,
    )
    first_idx = jax.ops.segment_min(
        jnp.where(cand, iota, B), tkey, num_segments=2 * B + 1
    )
    winner = cand & (iota == first_idx[tkey])
    loser = cand & ~winner
    win_ev = jnp.clip(first_idx[tkey], 0, B - 1)
    code = jnp.where(
        loser,
        jnp.where(
            post[win_ev],
            jnp.uint32(CTR.pending_transfer_already_posted),
            jnp.uint32(CTR.pending_transfer_already_voided),
        ),
        code,
    )

    ok = active & (code == 0)

    # --- apply.  Unified target slots for pv rows.
    p_drs = jnp.where(in_batch, ev["dr_slot"][tgt_c], p_dr_slot_d)
    p_crs = jnp.where(in_batch, ev["cr_slot"][tgt_c], p_cr_slot_d)
    pend_ok = ok & pend_flag
    plain_ok = ok & ~pend_flag & ~is_pv
    post_win = ok & winner & post

    # Adds: pending -> dp/cp, plain -> dpo/cpo, post -> dpo/cpo at
    # target slots.  4B rows.
    add_slots = jnp.concatenate([
        ev["dr_slot"], ev["cr_slot"], p_drs, p_crs,
    ])
    add_cols = jnp.concatenate([
        jnp.where(pend_flag, 0, 1), jnp.where(pend_flag, 2, 3),
        jnp.ones(B, jnp.int32), jnp.full(B, 3, jnp.int32),
    ])
    add_amt_lo = jnp.concatenate(
        [ev["amt_lo"], ev["amt_lo"], res_amt_lo, res_amt_lo]
    )
    add_amt_hi = jnp.concatenate(
        [ev["amt_hi"], ev["amt_hi"], res_amt_hi, res_amt_hi]
    )
    add_valid = jnp.concatenate(
        [pend_ok | plain_ok, pend_ok | plain_ok, post_win, post_win]
    )
    # Releases: winners subtract the pending amount from dp/cp (cannot
    # underflow: each live pending's amount is contained by invariant).
    # They ride the SAME 4B-leg one-hot as the adds — the release rows
    # are the [p_drs, p_crs] halves with their own columns and
    # validity; the [dr, cr] halves contribute zero.
    falseB = jnp.zeros(B, bool)
    win = ok & winner
    sub_cols = jnp.concatenate(
        [
            jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.int32), jnp.full(B, 2, jnp.int32),
        ]
    )
    sub_amt_lo = jnp.concatenate([p_amt_lo] * 4)
    sub_amt_hi = jnp.concatenate([p_amt_hi] * 4)
    sub_valid = jnp.concatenate([falseB, falseB, win, win])
    t = _touch(table, add_slots)
    adds, subs = _sum_legs(
        t,
        [
            (add_cols, add_amt_lo, add_amt_hi, add_valid),
            (sub_cols, sub_amt_lo, sub_amt_hi, sub_valid),
        ],
        lo_only=lo_only,
    )
    mid_rows, ov = _admit(t, *adds)
    final_rows, bad_release = _release(t, mid_rows, *subs)
    fallback = ov | bad_release
    new_table = _write_back(table, t, final_rows, fallback)

    last_applied = jnp.where(ok, iota, -1).max()
    flags_word = jnp.where(fallback, jnp.uint64(FLAG_OVERFLOW), jnp.uint64(0))
    return (new_table, *_summary(code, active, flags_word, last_applied))


# ---------------------------------------------------------------------------
# Auxiliary device ops.


def _lookup(table, slots):
    """Gather balance rows for lookup_accounts: slot < 0 -> zeros."""
    A = table.shape[0]
    rows = table[jnp.clip(slots, 0, A - 1)]
    return jnp.where(slots[:, None] >= 0, rows, jnp.uint64(0))


def _apply_deltas(table, packed):
    """Compact unique (slot, col, delta) modular adds — the exact-path
    write-behind lane (mirrors kernel_fast._flush_impl)."""
    A = table.shape[0]
    slots = packed[0].astype(jnp.int32)
    cols = packed[1].astype(jnp.int32)
    dense_lo = (
        jnp.zeros((A, 4), jnp.uint64)
        .at[slots, cols]
        .set(packed[2], mode="drop", unique_indices=True)
    )
    dense_hi = (
        jnp.zeros((A, 4), jnp.uint64)
        .at[slots, cols]
        .set(packed[3], mode="drop", unique_indices=True)
    )
    old_lo = table[:, 0::2]
    old_hi = table[:, 1::2]
    new_lo = old_lo + dense_lo
    carry = (new_lo < old_lo).astype(jnp.uint64)
    new_hi = old_hi + dense_hi + carry
    return _restack(new_lo, new_hi)


def _meta_update(meta, slots, acct_flags, acct_ledger):
    m = meta.at[slots, 0].set(acct_flags, mode="drop")
    return m.at[slots, 1].set(acct_ledger, mode="drop")


def _checksum(table):
    """Order-independent table digest: per-column modular sums plus a
    position-mixed sum (catches transposed rows)."""
    col_sums = table.sum(axis=0)
    rows = jnp.arange(table.shape[0], dtype=jnp.uint64)[:, None]
    mixed = (table * (rows * jnp.uint64(0x9E3779B97F4A7C15) + jnp.uint64(1))).sum(
        axis=0
    )
    return jnp.concatenate([col_sums, mixed])


import functools as _ft

def _jit_as(name: str, fn):
    """jax.jit(fn) under a program name of its own: a profiler trace
    and a compile log call the program `jit_<name>`, where a closure
    would be `jit_run` and a functools.partial `jit__unknown`.  What
    is compiled is the same."""

    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named)


orderfree = jax.jit(_orderfree)
orderfree_lo = _jit_as("orderfree_lo", _ft.partial(_orderfree, lo_only=True))
orderfree_tight = jax.jit(_orderfree_tight)
linked = jax.jit(_linked)
linked_small = _jit_as("linked_small", _ft.partial(_linked, small=True))
two_phase = jax.jit(_two_phase)
two_phase_lo = _jit_as("two_phase_lo", _ft.partial(_two_phase, lo_only=True))


# Scanned dispatch: G same-kind batches per device LAUNCH, read from
# one uploaded (G, ROWS, ncols) stack; lax.scan spreads an upload's
# and a dispatch's fixed host cost (~0.25 and 0.3-0.7 ms on the v5e:
# PERF.md section 6, PR 27) over the chunk.  The G summary rows come back
# as ONE (G, SUMMARY_WORDS) output, in record order, and the dense
# codes as one (G, B) output beside it.

def _scan_of(kind, fn, G):
    def run(table, meta, stack):
        def step(table, pkx):
            table, row, dense = fn(table, meta, pkx)
            return table, (row, dense)

        table, (rows, dense) = jax.lax.scan(step, table, stack)
        return table, rows, dense

    return _jit_as(f"scan_{kind}_g{G}", run)


_BASE_FNS = {
    "orderfree": _orderfree,
    "orderfree_lo": _ft.partial(_orderfree, lo_only=True),
    "orderfree_tight": _orderfree_tight,
    "linked": _linked,
    "linked_small": _ft.partial(_linked, small=True),
    "two_phase": _two_phase,
    "two_phase_lo": _ft.partial(_two_phase, lo_only=True),
}

# Packed-input geometry per kernel kind (host pack + prewarm shapes).
PK_SPEC = {
    "orderfree": (N_COLS, np.uint64),
    "orderfree_lo": (N_COLS, np.uint64),
    "orderfree_tight": (N_COLS_TIGHT, np.uint32),
    "linked": (N_COLS, np.uint64),
    "linked_small": (N_COLS, np.uint64),
    "two_phase": (N_COLS_TP, np.uint64),
    "two_phase_lo": (N_COLS_TP, np.uint64),
}
# Batches per scan launch, largest first (exact decomposition in the
# engine's chunk planner).  lax.scan compile time is
# length-independent, so the only cost of a big tier is its stack.
def _scan_sizes() -> tuple[int, ...]:
    from tigerbeetle_tpu import envcheck

    raw = envcheck.env_str("TB_DEV_SCAN_SIZES", "16,4")
    try:
        sizes = {int(x) for x in raw.split(",") if x.strip()}
    except ValueError:
        sizes = set()
    sizes = {g for g in sizes if g > 0}
    # Greedy exact decomposition needs descending tiers; an empty or
    # invalid override falls back to the default rather than hanging
    # the chunk planner (G=0) or crashing import (trailing comma).
    return tuple(sorted(sizes, reverse=True)) if sizes else (16, 4)


SCAN_SIZES = _scan_sizes()
# kind -> {G: jitted scan}; compiled lazily per (kind, G) actually used.
scan_kernels = {
    kind: {G: _scan_of(kind, fn, G) for G in SCAN_SIZES}
    for kind, fn in _BASE_FNS.items()
}


lookup = jax.jit(_lookup)
apply_deltas = jax.jit(_apply_deltas)
meta_update = jax.jit(_meta_update)
checksum = jax.jit(_checksum)


# ---------------------------------------------------------------------------
# Host-side packing (wire decoding + stateless predicates + joins).


def _predicate_bits(dtype, n, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
                    pend_lo, pend_hi, ts_nonzero):
    """The stateless wire predicates every packed format ships (one
    implementation — pack_base and pack_tight must never diverge)."""
    U64M = np.uint64(0xFFFFFFFFFFFFFFFF)
    bits = np.zeros(n, dtype)

    def setbit(mask, cond):
        np.bitwise_or(bits, np.where(cond, dtype(mask), dtype(0)), out=bits)

    setbit(BIT_TS_NONZERO, ts_nonzero)
    setbit(BIT_ID_ZERO, (id_lo == 0) & (id_hi == 0))
    setbit(BIT_ID_MAX, (id_lo == U64M) & (id_hi == U64M))
    setbit(BIT_DR_ZERO, (dr_lo == 0) & (dr_hi == 0))
    setbit(BIT_DR_MAX, (dr_lo == U64M) & (dr_hi == U64M))
    setbit(BIT_CR_ZERO, (cr_lo == 0) & (cr_hi == 0))
    setbit(BIT_CR_MAX, (cr_lo == U64M) & (cr_hi == U64M))
    setbit(BIT_SAME_ACCT, (dr_lo == cr_lo) & (dr_hi == cr_hi))
    setbit(BIT_PEND_NONZERO, (pend_lo != 0) | (pend_hi != 0))
    setbit(BIT_PEND_MAX, (pend_lo == U64M) & (pend_hi == U64M))
    setbit(BIT_PEND_SELF, (pend_lo == id_lo) & (pend_hi == id_hi))
    return bits


def pack_base(
    n, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi, pend_lo, pend_hi,
    amount_lo, amount_hi, flags, ledger, code, timeout, ts_nonzero,
    dr_slot, cr_slot, e_found, p_found=None, p_tgt=None,
    n_cols: int = N_COLS,
):
    """Build the packed (ROWS, n_cols) u64 input matrix on the host.

    Everything here is wire decoding, stateless byte predicates, and
    join results — no result-code decisions (those live on device)."""
    pk = np.zeros((ROWS, n_cols), np.uint64)
    bits = _predicate_bits(
        np.uint64, n, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
        pend_lo, pend_hi, ts_nonzero,
    )
    if e_found is not None:
        np.bitwise_or(
            bits,
            np.where(e_found, np.uint64(BIT_E_FOUND), np.uint64(0)),
            out=bits,
        )
    if p_found is not None:
        np.bitwise_or(
            bits,
            np.where(p_found, np.uint64(BIT_P_FOUND), np.uint64(0)),
            out=bits,
        )
    pk[:n, COL_BITS] = bits
    pk[:n, COL_SLOTS] = (
        (dr_slot.astype(np.int64) + 1).astype(np.uint64)
        | ((cr_slot.astype(np.int64) + 1).astype(np.uint64) << np.uint64(32))
    )
    pk[:n, COL_AMT_LO] = amount_lo
    pk[:n, COL_AMT_HI] = amount_hi
    pk[:n, COL_MISC] = (
        flags.astype(np.uint64)
        | (code.astype(np.uint64) << np.uint64(16))
        | (ledger.astype(np.uint64) << np.uint64(32))
    )
    tcol = timeout.astype(np.uint64)
    if p_tgt is not None:
        tcol = tcol | (
            (p_tgt.astype(np.int64) + 1).astype(np.uint64) << np.uint64(32)
        )
    pk[:n, COL_TIMEOUT] = tcol
    return pk


def pack_tight(
    n, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi, pend_lo, pend_hi,
    amount_lo, flags, ledger, code, ts_nonzero, dr_slot, cr_slot,
):
    """Tight (ROWS, 5) u32 order-free input (see _orderfree_tight).

    Caller-guaranteed facts: amount_hi == 0, amount_lo < 2^32,
    timeout == 0 for every event."""
    pk = np.zeros((ROWS, N_COLS_TIGHT), np.uint32)
    bits = _predicate_bits(
        np.uint32, n, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
        pend_lo, pend_hi, ts_nonzero,
    )
    np.bitwise_or(
        bits, np.where(code != 0, np.uint32(TIGHT_CODE_BIT), np.uint32(0)),
        out=bits,
    )
    np.bitwise_or(
        bits,
        np.where(
            (flags & ~np.uint32(0x3F)) != 0,
            np.uint32(TIGHT_RESERVED_BIT), np.uint32(0),
        ),
        out=bits,
    )
    np.bitwise_or(
        bits,
        (flags.astype(np.uint32) & np.uint32(0x3F))
        << np.uint32(TIGHT_FLAGS_SHIFT),
        out=bits,
    )
    pk[:n, 0] = bits
    pk[:n, 1] = (dr_slot.astype(np.int64) + 1).astype(np.uint32)
    pk[:n, 2] = (cr_slot.astype(np.int64) + 1).astype(np.uint32)
    pk[:n, 3] = amount_lo.astype(np.uint32)
    pk[:n, 4] = ledger
    return pk


def pack_two_phase_ext(
    pk, n, bits_extra_mask,
    p_flags, p_code, p_ledger, p_dr_slot, p_cr_slot,
    p_amt_lo, p_amt_hi, tgt_ev, dstat_init_ev,
):
    """Fill the two-phase join columns (durable target fields) and OR
    extra predicate bits into COL_BITS."""
    pk[:n, COL_BITS] |= bits_extra_mask
    pk[:n, COL_TP_JOIN] = (
        p_flags.astype(np.uint64)
        | (p_code.astype(np.uint64) << np.uint64(16))
        | (p_ledger.astype(np.uint64) << np.uint64(32))
    )
    pk[:n, COL_TP_SLOTS] = (
        (p_dr_slot.astype(np.int64) + 1).astype(np.uint64)
        | ((p_cr_slot.astype(np.int64) + 1).astype(np.uint64) << np.uint64(32))
    )
    pk[:n, COL_TP_AMT_LO] = p_amt_lo
    pk[:n, COL_TP_AMT_HI] = p_amt_hi
    pk[:n, COL_TP_REF] = (
        (tgt_ev.astype(np.int64) + 1).astype(np.uint64)
        | (dstat_init_ev.astype(np.uint64) << np.uint64(32))
    )
    return pk


def seal_scalars(pk: np.ndarray, n: int, ts_base: int) -> np.ndarray:
    """Write a batch's scalars into its packed buffer's last row (in
    place), so they cross the link inside the one upload: word 0 is
    n, then ts_base as one u64 or as two u32 halves, low first
    (_split_scalars is the device-side inverse)."""
    assert pk.shape[0] == ROWS, pk.shape
    tail = pk[SCALAR_ROW]
    tail[0] = n
    if pk.dtype == np.uint32:
        tail[1] = ts_base & 0xFFFFFFFF
        tail[2] = ts_base >> 32
    else:
        tail[1] = ts_base
    return pk


def unpack_summary(row: np.ndarray, dense: np.ndarray | None = None) -> dict:
    """Decode one (SUMMARY_WORDS,) u64 summary row; a batch with more
    than FAIL_CAP failures needs its `dense` (B,) u32 codes too."""
    n_fail = int(row[0])
    flags = int(row[1])
    if n_fail > FAIL_CAP:
        idx = np.flatnonzero(dense)
        codes = dense[idx].astype(np.uint32)
        assert len(idx) == n_fail, (len(idx), n_fail)
    else:
        entries = row[4 : 4 + n_fail]
        idx = (entries >> np.uint64(32)).astype(np.int64)
        codes = (entries & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return {
        "n_fail": n_fail,
        "overflow": bool(flags & FLAG_OVERFLOW),
        "precond": bool(flags & FLAG_PRECOND),
        "iters": (flags >> ITERS_SHIFT) & 0xFFFF,
        "settled": bool(flags & FLAG_SETTLED),
        "settle_rounds": flags >> SETTLE_SHIFT,
        "last_applied": int(row[2]) - 1,
        "n_active": int(row[3]),
        "fail_idx": idx,
        "fail_codes": codes,
    }
