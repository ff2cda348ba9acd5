"""Smoke-test the device kernels on CPU against hand-computed cases."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import jax.numpy as jnp

from tigerbeetle_tpu.state_machine import device_kernels as dk
from tigerbeetle_tpu.types import CreateTransferResult as CTR

A = 64
Bk = dk.B


def mk_tables(n_acct=8, ledger=1, acct_flags=None):
    table = jnp.zeros((A, 8), jnp.uint64)
    meta = np.zeros((A, 2), np.uint32)
    meta[:n_acct, 1] = ledger
    if acct_flags is not None:
        meta[: len(acct_flags), 0] = acct_flags
    return table, jnp.asarray(meta)


def base_pack(n, dr_slot, cr_slot, amt, flags=None, ids=None, pend=None,
              ledger=None, code=None, timeout=None, n_cols=dk.N_COLS,
              p_found=None, p_tgt=None, e_found=None):
    z = np.zeros(n, np.uint64)
    ids = np.arange(1, n + 1, dtype=np.uint64) if ids is None else ids
    pend = z if pend is None else pend
    dr_s = np.asarray(dr_slot, np.int64)
    cr_s = np.asarray(cr_slot, np.int64)
    return dk.pack_base(
        n,
        id_lo=ids, id_hi=z,
        dr_lo=np.where(dr_s < 0, 0, dr_s + 100).astype(np.uint64), dr_hi=z,
        cr_lo=np.where(cr_s < 0, 0, cr_s + 100).astype(np.uint64), cr_hi=z,
        pend_lo=pend, pend_hi=z,
        amount_lo=np.asarray(amt, np.uint64), amount_hi=z,
        flags=np.zeros(n, np.uint32) if flags is None else np.asarray(flags, np.uint32),
        ledger=np.ones(n, np.uint32) if ledger is None else ledger,
        code=np.ones(n, np.uint32) if code is None else code,
        timeout=np.zeros(n, np.uint32) if timeout is None else timeout,
        ts_nonzero=np.zeros(n, bool),
        dr_slot=np.asarray(dr_slot, np.int64),
        cr_slot=np.asarray(cr_slot, np.int64),
        e_found=np.zeros(n, bool) if e_found is None else e_found,
        p_found=p_found, p_tgt=p_tgt,
        n_cols=n_cols,
    )


ring = jnp.zeros((4, dk.SUMMARY_WORDS), jnp.uint64)

# --- orderfree: 3 ok transfers + 1 bad (same account)
table, meta = mk_tables()
pk = base_pack(4, [0, 1, 2, 3], [1, 2, 3, 3], [10, 20, 30, 40])
t2, r2 = dk.orderfree(table, meta, ring, 0, jnp.asarray(pk), 4,
                      jnp.uint64(1000))
s = dk.unpack_summary(np.asarray(r2)[0])
assert s["n_fail"] == 1 and s["fail_idx"][0] == 3, s
assert s["fail_codes"][0] == CTR.accounts_must_be_different
assert not s["overflow"] and s["last_applied"] == 2
tbl = np.asarray(t2)
assert tbl[0, 2] == 10 and tbl[1, 2] == 20 and tbl[1, 6] == 10
assert tbl[3, 6] == 30 and tbl[3, 2] == 0
print("orderfree ok")

# --- orderfree: pending create
table, meta = mk_tables()
pk = base_pack(2, [0, 1], [1, 2], [5, 7],
               flags=np.array([dk.F_PENDING, 0], np.uint32),
               timeout=np.array([3, 0], np.uint32))
t2, r2 = dk.orderfree(table, meta, ring, 1, jnp.asarray(pk), 2,
                      jnp.uint64(1000))
s = dk.unpack_summary(np.asarray(r2)[1])
assert s["n_fail"] == 0, s
tbl = np.asarray(t2)
assert tbl[0, 0] == 5 and tbl[1, 4] == 5 and tbl[1, 2] == 7
print("orderfree pending ok")

# --- linked: chain of 3 with middle failing statically -> all fail
table, meta = mk_tables()
pk = base_pack(3, [0, 1, 2], [1, 1, 0], [10, 20, 30],
               flags=np.array([dk.F_LINKED, dk.F_LINKED, 0], np.uint32))
t2, r2 = dk.linked(table, meta, ring, 0, jnp.asarray(pk), 3,
                   jnp.uint64(1000))
s = dk.unpack_summary(np.asarray(r2)[0])
assert s["n_fail"] == 3, s
codes = dict(zip(s["fail_idx"].tolist(), s["fail_codes"].tolist()))
assert codes[1] == CTR.accounts_must_be_different
assert codes[0] == CTR.linked_event_failed
assert codes[2] == CTR.linked_event_failed
assert np.asarray(t2).sum() == 0
print("linked static-fail ok")

# --- linked with limit account: acct0 has debits_must_not_exceed_credits,
# funded with 50 credits; chain1 debits 40 (ok), chain2 debits 40 (fails).
table, meta = mk_tables(acct_flags=np.array([2, 0, 0], np.uint32))
table = table.at[0, 6].set(50)  # cpo=50
pk = base_pack(2, [0, 0], [1, 2], [40, 40])
t2, r2 = dk.linked(table, meta, ring, 1, jnp.asarray(pk), 2,
                   jnp.uint64(1000))
s = dk.unpack_summary(np.asarray(r2)[1])
assert s["n_fail"] == 1 and s["fail_idx"][0] == 1, s
assert s["fail_codes"][0] == CTR.exceeds_credits
tbl = np.asarray(t2)
assert tbl[0, 2] == 40 and tbl[1, 6] == 40
print("linked limit ok")

# --- linked: chain rolls back on limit failure
table, meta = mk_tables(acct_flags=np.array([2, 0, 0], np.uint32))
table = table.at[0, 6].set(50)
pk = base_pack(3, [1, 0, 2], [2, 1, 0], [10, 60, 5],
               flags=np.array([dk.F_LINKED, dk.F_LINKED, 0], np.uint32))
t2, r2 = dk.linked(table, meta, ring, 2, jnp.asarray(pk), 3,
                   jnp.uint64(1000))
s = dk.unpack_summary(np.asarray(r2)[2])
assert s["n_fail"] == 3, s
codes = dict(zip(s["fail_idx"].tolist(), s["fail_codes"].tolist()))
assert codes[1] == CTR.exceeds_credits
assert codes[0] == CTR.linked_event_failed
tbl = np.asarray(t2)
assert tbl.sum() == 50, tbl.sum()  # only the funding credit remains
print("linked rollback ok")

# --- two_phase: pending + post pair (in-batch), second post loses
table, meta = mk_tables()
n = 3
ids = np.array([10, 11, 12], np.uint64)
pend = np.array([0, 10, 10], np.uint64)
flags = np.array([dk.F_PENDING, dk.F_POST, dk.F_POST], np.uint32)
pk = base_pack(
    n, [0, -1, -1], [1, -1, -1], [30, 0, 0], flags=flags, ids=ids,
    pend=pend, n_cols=dk.N_COLS_TP,
    p_found=np.zeros(n, bool), p_tgt=np.full(n, -1, np.int64),
)
# in-batch refs: tgt_ev = creator event of pending id (event 0)
pk = dk.pack_two_phase_ext(
    pk, n,
    bits_extra_mask=np.zeros(n, np.uint64),
    p_flags=np.zeros(n, np.uint16), p_code=np.zeros(n, np.uint16),
    p_ledger=np.zeros(n, np.uint32),
    p_dr_slot=np.full(n, -1, np.int64), p_cr_slot=np.full(n, -1, np.int64),
    p_amt_lo=np.zeros(n, np.uint64), p_amt_hi=np.zeros(n, np.uint64),
    tgt_ev=np.array([-1, 0, 0], np.int64),
    dstat_init_ev=np.zeros(n, np.uint32),
)
t2, r2 = dk.two_phase(table, meta, ring, 0, jnp.asarray(pk), n,
                      jnp.uint64(1000))
s = dk.unpack_summary(np.asarray(r2)[0])
assert s["n_fail"] == 1 and s["fail_idx"][0] == 2, s
assert s["fail_codes"][0] == CTR.pending_transfer_already_posted
tbl = np.asarray(t2)
# pending released, post applied: dp back to 0, dpo=30
assert tbl[0, 0] == 0 and tbl[0, 2] == 30 and tbl[1, 4] == 0 and tbl[1, 6] == 30, tbl[:2]
print("two_phase in-batch ok")

# --- two_phase: durable void with partial amount -> different_amount err
table, meta = mk_tables()
table = table.at[0, 0].set(30).at[1, 4].set(30)  # live pending 30
n = 1
pk = base_pack(
    n, [-1], [-1], [10],
    flags=np.array([dk.F_VOID], np.uint32),
    ids=np.array([20], np.uint64), pend=np.array([10], np.uint64),
    n_cols=dk.N_COLS_TP,
    p_found=np.ones(n, bool), p_tgt=np.zeros(n, np.int64),
)
pk = dk.pack_two_phase_ext(
    pk, n, bits_extra_mask=np.zeros(n, np.uint64),
    p_flags=np.full(n, dk.F_PENDING, np.uint16),
    p_code=np.ones(n, np.uint16), p_ledger=np.ones(n, np.uint32),
    p_dr_slot=np.zeros(n, np.int64), p_cr_slot=np.ones(n, np.int64),
    p_amt_lo=np.full(n, 30, np.uint64), p_amt_hi=np.zeros(n, np.uint64),
    tgt_ev=np.full(n, -1, np.int64),
    dstat_init_ev=np.full(n, dk.S_PENDING, np.uint32),
)
t2, r2 = dk.two_phase(table, meta, ring, 1, jnp.asarray(pk), n,
                      jnp.uint64(2000))
s = dk.unpack_summary(np.asarray(r2)[1])
assert s["n_fail"] == 1, s
assert s["fail_codes"][0] == CTR.pending_transfer_has_different_amount, s
print("two_phase durable partial-void ok")

# --- two_phase: durable void full -> releases pending
pk2 = base_pack(
    n, [-1], [-1], [0],
    flags=np.array([dk.F_VOID], np.uint32),
    ids=np.array([21], np.uint64), pend=np.array([10], np.uint64),
    n_cols=dk.N_COLS_TP,
    p_found=np.ones(n, bool), p_tgt=np.zeros(n, np.int64),
)
pk2 = dk.pack_two_phase_ext(
    pk2, n, bits_extra_mask=np.zeros(n, np.uint64),
    p_flags=np.full(n, dk.F_PENDING, np.uint16),
    p_code=np.ones(n, np.uint16), p_ledger=np.ones(n, np.uint32),
    p_dr_slot=np.zeros(n, np.int64), p_cr_slot=np.ones(n, np.int64),
    p_amt_lo=np.full(n, 30, np.uint64), p_amt_hi=np.zeros(n, np.uint64),
    tgt_ev=np.full(n, -1, np.int64),
    dstat_init_ev=np.full(n, dk.S_PENDING, np.uint32),
)
t3, r3 = dk.two_phase(table, meta, ring, 2, jnp.asarray(pk2), n,
                      jnp.uint64(2001))
s = dk.unpack_summary(np.asarray(r3)[2])
assert s["n_fail"] == 0, s
tbl = np.asarray(t3)
assert tbl[0, 0] == 0 and tbl[1, 4] == 0 and tbl[0, 2] == 0, tbl[:2]
print("two_phase durable void ok")

print("ALL SMOKE TESTS PASSED")
