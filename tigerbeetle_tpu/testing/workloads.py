"""Event streams of the five batch classes, for differential tests.

One generator per configuration of BASELINE.json:
  simple     unlinked posted transfers over 1k accounts, one ledger
  linked     chains (avg len 4) + must_not_exceed balance constraints
  two_phase  pending -> post/void mix (30% void), in-batch pairs
  zipf       transfers Zipf-skewed over 100 accounts (contention)
  mixed      create_accounts + create_transfers + lookup_accounts
             interleaved over 4 ledgers

Each returns (setup_ops, stream_ops, sizing): ops are [(Operation,
bytes)] of at most `batch` events each, sizing = (account_cap,
transfer_cap) for the machine that replays them.  The streams are a
pure function of (n_events, batch): every generator seeds its own rng.
Setup ends with transfer batches whose ids start at WARM0, so the
stream proper (ids from TID0) runs against tables that already hold
rows.
"""

from __future__ import annotations

import hashlib

import numpy as np

from tigerbeetle_tpu.testing.harness import account, ids_bytes, pack
from tigerbeetle_tpu.types import (
    TRANSFER_DTYPE,
    AccountFlags,
    Operation,
    TransferFlags,
)

TF = TransferFlags
AF = AccountFlags

TID0 = 1  # first transfer id of the stream
WARM0 = 50_000_000  # transfer ids of setup's batches


def accounts_bytes(ids, ledger=1, flags=None) -> bytes:
    flags = [0] * len(ids) if flags is None else flags
    return pack(
        [
            account(int(i), ledger=ledger, flags=int(f))
            for i, f in zip(ids, flags)
        ]
    )


def lookup_bytes(ids) -> bytes:
    return ids_bytes([int(i) for i in ids])


def transfers_bytes(
    ids, dr, cr, amount, *, ledger=1, flags=None, pending_id=None
) -> bytes:
    """Column-wise twin of harness.transfer for whole batches."""
    arr = np.zeros(len(ids), dtype=TRANSFER_DTYPE)
    arr["id_lo"] = ids
    arr["debit_account_id_lo"] = dr
    arr["credit_account_id_lo"] = cr
    arr["amount_lo"] = amount
    arr["ledger"] = ledger
    arr["code"] = 1
    if flags is not None:
        arr["flags"] = flags
    if pending_id is not None:
        arr["pending_id_lo"] = pending_id
    return arr.tobytes()


def batched(cols, batch):
    """Split one per-event column dict into create_transfers batches."""
    out = []
    n = len(cols["ids"])
    for at in range(0, n, batch):
        sl = slice(at, min(at + batch, n))
        out.append(
            (
                Operation.create_transfers,
                transfers_bytes(
                    cols["ids"][sl],
                    cols["dr"][sl],
                    cols["cr"][sl],
                    cols["amount"][sl],
                    ledger=cols.get("ledger", 1),
                    flags=cols["flags"][sl] if "flags" in cols else None,
                    pending_id=cols["pending_id"][sl]
                    if "pending_id" in cols
                    else None,
                ),
            )
        )
    return out


def gen_simple(n_events: int, batch: int):
    rng = np.random.default_rng(42)
    n_acct = 1_000
    setup = [(Operation.create_accounts, accounts_bytes(range(1, n_acct + 1)))]
    warm_n = min(batch, n_events)
    dr = rng.integers(1, n_acct + 1, warm_n, np.uint64)
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + warm_n, dtype=np.uint64),
            "dr": dr,
            "cr": dr % np.uint64(n_acct) + np.uint64(1),
            "amount": rng.integers(1, 100, warm_n, np.uint64),
        },
        batch,
    )
    dr = rng.integers(1, n_acct + 1, n_events, np.uint64)
    stream = batched(
        {
            "ids": np.arange(TID0, TID0 + n_events, dtype=np.uint64),
            "dr": dr,
            "cr": dr % np.uint64(n_acct) + np.uint64(1),
            "amount": rng.integers(1, 100, n_events, np.uint64),
        },
        batch,
    )
    return setup, stream, (1 << 12, n_events + 2 * batch + 1024)


def gen_linked(n_events: int, batch: int):
    """Chains avg len 4, half the accounts debit-limited (funded in
    setup so most chains succeed while some trip the limit and roll
    back whole chains)."""
    rng = np.random.default_rng(43)
    n_acct = 1_000
    limited = np.arange(1, n_acct // 2 + 1, dtype=np.uint64)
    flags = np.zeros(n_acct, np.uint16)
    flags[: n_acct // 2] = int(AF.debits_must_not_exceed_credits)
    setup = [
        (
            Operation.create_accounts,
            accounts_bytes(range(1, n_acct + 1), flags=flags),
        )
    ]
    # Fund the limited accounts: credit each from the last plain account.
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + len(limited), dtype=np.uint64),
            "dr": np.full(len(limited), n_acct, np.uint64),
            "cr": limited,
            "amount": np.full(len(limited), 50_000, np.uint64),
        },
        batch,
    )
    warm = _chain_events(rng, 2 * batch, batch, n_acct, WARM0 + 1_000_000)
    setup += _chain_batches(warm, batch)

    stream = _chain_batches(
        _chain_events(rng, n_events, batch, n_acct, TID0), batch
    )
    n_total = sum(len(b) // TRANSFER_DTYPE.itemsize for _op, b in stream)
    return setup, stream, (1 << 12, n_total + 4 * batch + len(limited) + 1024)


def _chain_events(rng, n_events, batch, n_acct, id0):
    lens = rng.integers(1, 8, size=n_events // 2 + batch)  # avg 4
    ends = np.cumsum(lens)
    n_chains = int(np.searchsorted(ends, n_events, side="left")) + 1
    lens = lens[:n_chains]
    total = int(lens.sum())
    # linked flag on every chain member except the last.
    last_idx = np.cumsum(lens) - 1
    flags = np.full(total, int(TF.linked), np.uint16)
    flags[last_idx] = 0
    dr = rng.integers(1, n_acct + 1, total, np.uint64)
    cr = rng.integers(1, n_acct + 1, total, np.uint64)
    clash = cr == dr
    cr[clash] = dr[clash] % np.uint64(n_acct) + np.uint64(1)
    return {
        "ids": np.arange(id0, id0 + total, dtype=np.uint64),
        "dr": dr,
        "cr": cr,
        "amount": rng.integers(1, 200, total, np.uint64),
        "flags": flags,
        "chain_ends": np.cumsum(lens),
    }


def _chain_batches(ev, batch):
    """Batch without splitting a chain across batches (an open chain at
    the end of a batch fails with linked_event_chain_open)."""
    out = []
    ends = ev["chain_ends"]
    total = len(ev["ids"])
    start = 0
    while start < total:
        # Last chain end fitting within `batch` events of `start`.
        hi = int(np.searchsorted(ends, start + batch, side="right"))
        if hi == 0 or ends[hi - 1] <= start:
            break
        stop = int(ends[hi - 1])
        sl = slice(start, stop)
        out.append(
            (
                Operation.create_transfers,
                transfers_bytes(
                    ev["ids"][sl], ev["dr"][sl], ev["cr"][sl],
                    ev["amount"][sl], flags=ev["flags"][sl],
                ),
            )
        )
        start = stop
    return out


def gen_two_phase(n_events: int, batch: int):
    """Adjacent (pending, post|void) pairs; 30% void, amount inherited
    (zero-means-inherit, reference: src/state_machine.zig:1743-1804)."""
    rng = np.random.default_rng(44)
    n_acct = 1_000
    setup = [(Operation.create_accounts, accounts_bytes(range(1, n_acct + 1)))]
    n_pairs = n_events // 2

    def pairs(n, id0):
        ids = np.arange(id0, id0 + 2 * n, dtype=np.uint64)
        flags = np.zeros(2 * n, np.uint16)
        flags[0::2] = int(TF.pending)
        void = rng.random(n) < 0.30
        flags[1::2] = np.where(
            void, int(TF.void_pending_transfer), int(TF.post_pending_transfer)
        ).astype(np.uint16)
        pending_id = np.zeros(2 * n, np.uint64)
        pending_id[1::2] = ids[0::2]
        dr = np.zeros(2 * n, np.uint64)
        cr = np.zeros(2 * n, np.uint64)
        dr[0::2] = rng.integers(1, n_acct + 1, n, np.uint64)
        cr[0::2] = dr[0::2] % np.uint64(n_acct) + np.uint64(1)
        amount = np.zeros(2 * n, np.uint64)
        amount[0::2] = rng.integers(1, 100, n, np.uint64)
        return {
            "ids": ids, "dr": dr, "cr": cr, "amount": amount,
            "flags": flags, "pending_id": pending_id,
        }

    setup += batched(pairs(batch // 2, WARM0), batch)
    stream = batched(pairs(n_pairs, TID0), batch)
    return setup, stream, (1 << 12, 2 * n_pairs + 4 * batch + 1024)


def gen_zipf(n_events: int, batch: int):
    rng = np.random.default_rng(45)
    n_acct = 100
    ranks = np.arange(1, n_acct + 1, dtype=np.float64)
    p = (1.0 / ranks) / (1.0 / ranks).sum()
    setup = [(Operation.create_accounts, accounts_bytes(range(1, n_acct + 1)))]
    warm_n = min(batch, n_events)

    def draw(n):
        dr = rng.choice(n_acct, size=n, p=p).astype(np.uint64) + np.uint64(1)
        cr = rng.choice(n_acct, size=n, p=p).astype(np.uint64) + np.uint64(1)
        clash = cr == dr
        cr[clash] = dr[clash] % np.uint64(n_acct) + np.uint64(1)
        return dr, cr

    dr, cr = draw(warm_n)
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + warm_n, dtype=np.uint64),
            "dr": dr, "cr": cr,
            "amount": rng.integers(1, 100, warm_n, np.uint64),
        },
        batch,
    )
    dr, cr = draw(n_events)
    stream = batched(
        {
            "ids": np.arange(TID0, TID0 + n_events, dtype=np.uint64),
            "dr": dr, "cr": cr,
            "amount": rng.integers(1, 100, n_events, np.uint64),
        },
        batch,
    )
    return setup, stream, (1 << 12, n_events + 2 * batch + 1024)


def gen_mixed(n_events: int, batch: int):
    """Interleaved create_accounts / create_transfers / lookup_accounts
    over 4 ledgers (BASELINE.json config 5)."""
    rng = np.random.default_rng(46)
    n_ledgers = 4
    per_ledger = [list(range(led * 100_000 + 1, led * 100_000 + 501))
                  for led in range(1, n_ledgers + 1)]
    setup = []
    for led in range(1, n_ledgers + 1):
        setup.append(
            (
                Operation.create_accounts,
                accounts_bytes(per_ledger[led - 1], ledger=led),
            )
        )
    led_accts = per_ledger[0]
    dr = rng.choice(led_accts, batch).astype(np.uint64)
    cr = rng.choice(led_accts, batch).astype(np.uint64)
    clash = cr == dr
    cr[clash] = np.where(
        dr[clash] == led_accts[-1], led_accts[0], dr[clash] + 1
    )
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + batch, dtype=np.uint64),
            "dr": dr, "cr": cr,
            "amount": rng.integers(1, 100, batch, np.uint64),
            "ledger": 1,
        },
        batch,
    )

    stream = []
    next_tid = TID0
    next_acct = {led: led * 100_000 + 501 for led in range(1, n_ledgers + 1)}
    events = 0
    k = 0
    while events < n_events:
        r = k % 10
        if r == 3:
            # New accounts on a rotating ledger.
            led = (k // 10) % n_ledgers + 1
            n_new = 500
            ids = list(range(next_acct[led], next_acct[led] + n_new))
            next_acct[led] += n_new
            per_ledger[led - 1].extend(ids)
            stream.append(
                (Operation.create_accounts, accounts_bytes(ids, ledger=led))
            )
            events += n_new
        elif r == 7:
            led = rng.integers(1, n_ledgers + 1)
            ids = rng.choice(per_ledger[int(led) - 1], 2_000)
            stream.append((Operation.lookup_accounts, lookup_bytes(ids)))
            events += len(ids)
        else:
            led = int(rng.integers(1, n_ledgers + 1))
            accts = np.asarray(per_ledger[led - 1], np.uint64)
            n = min(batch, n_events - events)
            dr = rng.choice(accts, n)
            cr = rng.choice(accts, n)
            clash = cr == dr
            cr[clash] = np.where(
                dr[clash] == accts[-1], accts[0], dr[clash] + 1
            )
            stream += batched(
                {
                    "ids": np.arange(next_tid, next_tid + n, dtype=np.uint64),
                    "dr": dr, "cr": cr,
                    "amount": rng.integers(1, 100, n, np.uint64),
                    "ledger": led,
                },
                batch,
            )
            next_tid += n
            events += n
        k += 1
    return setup, stream, (1 << 15, (next_tid - TID0) + 4 * batch + 1024)


CONFIGS = {
    "simple": gen_simple,
    "linked": gen_linked,
    "two_phase": gen_two_phase,
    "zipf": gen_zipf,
    "mixed": gen_mixed,
}


def config_account_ids(name):
    """Every account id a configuration's setup creates, and for
    `mixed` the first 3,000 of each ledger (the stream creates more)."""
    if name == "zipf":
        return np.arange(1, 101, dtype=np.uint64)
    if name == "mixed":
        ids = []
        for led in range(1, 5):
            ids.extend(range(led * 100_000 + 1, led * 100_000 + 3_001))
        return np.asarray(ids, np.uint64)
    return np.arange(1, 1_001, dtype=np.uint64)


def state_digest(h, account_ids, transfer_ids, batch) -> str:
    """Wire-level digest through harness `h`: the lookup replies for
    every given account and transfer id, `batch` ids a request."""
    hasher = hashlib.sha256()
    for op, ids in (
        (Operation.lookup_accounts, account_ids),
        (Operation.lookup_transfers, transfer_ids),
    ):
        for at in range(0, len(ids), batch):
            hasher.update(h.submit(op, lookup_bytes(ids[at : at + batch])))
    return hasher.hexdigest()
