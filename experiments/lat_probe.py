"""Microbenchmark: per-dispatch latency of a vectorized order-free
semantic kernel on the real TPU, to size the authority
inversion (VERDICT r3 item 1).

Shapes mirror the bench hot path: B=8190 events, A=4096 accounts.
The candidate kernel does: static-ladder-scale elementwise work,
dense per-(slot,col) delta accumulation, u128 overflow admission
against the live table, conditional apply, and returns packed
results + the new table.
"""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

print("devices:", jax.devices(), file=sys.stderr)

A = 4096
B = 8190
MASK32 = jnp.uint64(0xFFFFFFFF)


def kernel(table, acct, dr_slot, cr_slot, amt_lo, amt_hi, flags, ledger,
           code, id_zero, id_max, pend_nz, timeout, ts_nonzero):
    # --- static ladder (subset, representative op count)
    dr_ok = dr_slot >= 0
    cr_ok = cr_slot >= 0
    drc = jnp.clip(dr_slot, 0, A - 1)
    crc = jnp.clip(cr_slot, 0, A - 1)
    a_dr = acct[drc]
    a_cr = acct[crc]
    dr_ledger = jnp.where(dr_ok, a_dr[:, 1], 0)
    cr_ledger = jnp.where(cr_ok, a_cr[:, 1], 0)
    amount_zero = (amt_lo == 0) & (amt_hi == 0)
    r = jnp.zeros(B, jnp.uint32)

    def app(r, cond, code_v):
        return jnp.where((r == 0) & cond, jnp.uint32(code_v), r)

    r = app(r, ts_nonzero, 3)
    r = app(r, id_zero, 4)
    r = app(r, id_max, 5)
    r = app(r, ~dr_ok, 42)
    r = app(r, ~cr_ok, 43)
    r = app(r, dr_slot == cr_slot, 12)
    r = app(r, pend_nz, 13)
    r = app(r, timeout != 0, 14)
    r = app(r, amount_zero, 20)
    r = app(r, ledger == 0, 21)
    r = app(r, code == 0, 22)
    r = app(r, dr_ledger != cr_ledger, 30)
    r = app(r, ledger != dr_ledger, 31)
    ok = r == 0
    is_pending = (flags & 2) != 0

    # --- dense delta accumulation as 4x32-bit limbs (exact sums)
    l0 = amt_lo & MASK32
    l1 = amt_lo >> jnp.uint64(32)
    l2 = amt_hi & MASK32
    l3 = amt_hi >> jnp.uint64(32)
    zero = jnp.uint64(0)
    dcol = jnp.where(is_pending, 0, 1)
    ccol = jnp.where(is_pending, 2, 3)
    acc = jnp.zeros((A, 4, 4), jnp.uint64)
    sel = lambda v: jnp.where(ok, v, zero)
    acc = acc.at[drc, dcol, 0].add(sel(l0), mode="drop")
    acc = acc.at[drc, dcol, 1].add(sel(l1), mode="drop")
    acc = acc.at[drc, dcol, 2].add(sel(l2), mode="drop")
    acc = acc.at[drc, dcol, 3].add(sel(l3), mode="drop")
    acc = acc.at[crc, ccol, 0].add(sel(l0), mode="drop")
    acc = acc.at[crc, ccol, 1].add(sel(l1), mode="drop")
    acc = acc.at[crc, ccol, 2].add(sel(l2), mode="drop")
    acc = acc.at[crc, ccol, 3].add(sel(l3), mode="drop")
    c0 = acc[:, :, 0]
    c1 = acc[:, :, 1] + (c0 >> jnp.uint64(32))
    c2 = acc[:, :, 2] + (c1 >> jnp.uint64(32))
    c3 = acc[:, :, 3] + (c2 >> jnp.uint64(32))
    d_lo = (c0 & MASK32) | ((c1 & MASK32) << jnp.uint64(32))
    d_hi = (c2 & MASK32) | ((c3 & MASK32) << jnp.uint64(32))
    limb_ov = (c3 >> jnp.uint64(32)) != 0

    old_lo = table[:, 0::2]
    old_hi = table[:, 1::2]
    new_lo = old_lo + d_lo
    carry = (new_lo < old_lo).astype(jnp.uint64)
    new_hi = old_hi + d_hi + carry
    add_ov = (new_hi < old_hi) | ((new_hi == old_hi) & (new_lo < old_lo))
    # combined totals
    tot_lo = new_lo[:, 0] + new_lo[:, 1]
    tc = (tot_lo < new_lo[:, 0]).astype(jnp.uint64)
    tot_hi = new_hi[:, 0] + new_hi[:, 1] + tc
    dr_tot_ov = (tot_hi < new_hi[:, 0])
    overflow = limb_ov.any() | add_ov.any() | dr_tot_ov.any()

    new_table = jnp.where(
        overflow,
        table,
        jnp.stack(
            [new_lo[:, 0], new_hi[:, 0], new_lo[:, 1], new_hi[:, 1],
             new_lo[:, 2], new_hi[:, 2], new_lo[:, 3], new_hi[:, 3]],
            axis=-1,
        ),
    )
    results = jnp.where(overflow, jnp.uint32(0xFFFFFFFF), r)
    return new_table, results


jk = jax.jit(kernel, donate_argnums=(0,))

rng = np.random.default_rng(0)
table = jnp.zeros((A, 8), jnp.uint64)
acct = jnp.ones((A, 2), jnp.uint32)

def mk_inputs():
    dr = rng.integers(0, 1000, B).astype(np.int32)
    cr = ((dr + 1) % 1000).astype(np.int32)
    return dict(
        dr_slot=jnp.asarray(dr), cr_slot=jnp.asarray(cr),
        amt_lo=jnp.asarray(rng.integers(1, 100, B, np.uint64)),
        amt_hi=jnp.zeros(B, jnp.uint64),
        flags=jnp.zeros(B, jnp.uint32),
        ledger=jnp.ones(B, jnp.uint32),
        code=jnp.ones(B, jnp.uint32),
        id_zero=jnp.zeros(B, bool), id_max=jnp.zeros(B, bool),
        pend_nz=jnp.zeros(B, bool),
        timeout=jnp.zeros(B, jnp.uint64),
        ts_nonzero=jnp.zeros(B, bool),
    )

inp = mk_inputs()
t0 = time.perf_counter()
table, res = jk(table, acct, **inp)
np.asarray(res)
print(f"compile+first: {time.perf_counter()-t0:.3f}s", file=sys.stderr)

# --- synchronous per-call latency (fetch results every call)
N = 30
t0 = time.perf_counter()
for _ in range(N):
    table, res = jk(table, acct, **inp)
    res_np = np.asarray(res)
sync_ms = (time.perf_counter() - t0) / N * 1e3
print(f"sync per-call: {sync_ms:.2f} ms -> {B/(sync_ms/1e3):,.0f} ev/s")

# --- dispatch-only (no result fetch until the end)
t0 = time.perf_counter()
reses = []
for _ in range(N):
    table, res = jk(table, acct, **inp)
    reses.append(res)
jax.block_until_ready(reses[-1])
async_ms = (time.perf_counter() - t0) / N * 1e3
print(f"pipelined per-call: {async_ms:.2f} ms -> {B/(async_ms/1e3):,.0f} ev/s")

# --- host->device transfer cost for the input set alone
t0 = time.perf_counter()
for _ in range(N):
    arrs = [jnp.asarray(np.zeros(B, np.uint64)) for _ in range(8)]
    jax.block_until_ready(arrs)
xfer_ms = (time.perf_counter() - t0) / N * 1e3
print(f"8x u64(B) h2d: {xfer_ms:.2f} ms")

# --- depth-2 software pipeline: fetch res[k-1] after dispatch k
t0 = time.perf_counter()
prev = None
for _ in range(N):
    table, res = jk(table, acct, **inp)
    if prev is not None:
        np.asarray(prev)
    prev = res
np.asarray(prev)
pipe_ms = (time.perf_counter() - t0) / N * 1e3
print(f"depth-2 pipeline per-call: {pipe_ms:.2f} ms -> {B/(pipe_ms/1e3):,.0f} ev/s")
