"""What decides `correct`: every number compared, beside its limit.

All comparisons are exact, so every limit is 0.  The served side is
what the timed path itself answered: every reply of every request the
sessions sent (warm-up and window), and, once the window has closed,
`lookup_accounts` over all accounts and `lookup_transfers` over a
sample of the requests drawn from the seed.  The reference side is the traffic kind's plain
reference, fed the same requests drawn again from the seed; it takes
nothing from the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import wire

LIMITS = {
    "requests_failed": 0,
    "replies_differing": 0,
    "account_rows_differing": 0,
    "transfer_rows_differing": 0,
    "events_not_on_device": 0,
    "events_unaccounted": 0,
    "engine_faults": 0,
    "replicas_disagreeing": 0,
    "servers_exited_badly": 0,
}


def last_write(records: list):
    """The request acknowledged last (the write most likely to be lost)."""
    acked = [r for r in records if r.reply is not None]
    return max(acked, key=lambda r: r.t_reply) if acked else None


def sample_requests(records: list, seed: int, k: int) -> list:
    """`k` acknowledged requests drawn from the seed, the last one
    acknowledged among them."""
    acked = sorted((r for r in records if r.reply is not None),
                   key=lambda r: (r.session, r.index))
    if not acked:
        return []
    rng = np.random.default_rng([seed, 0xC0FFEE])
    picks = {int(i) for i in rng.choice(len(acked), size=min(k, len(acked)),
                                        replace=False)}
    picks.add(acked.index(last_write(acked)))
    return [acked[i] for i in sorted(picks)]


def rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of `want` that `got` does not hold at the same place, plus
    any surplus of `got`."""
    n = min(len(got), len(want))
    differ = int((got[:n] != want[:n]).sum()) if n else 0
    return differ + abs(len(got) - len(want))


def reference_side(gen, ref, records: list, sample: list,
                   drop: object | None = None) -> dict:
    """Run the reference over every acknowledged request, each
    session's in the order it sent them (sessions commute, in every
    kind; one session's requests need not).  `drop` names one record
    the control leaves out: an acknowledged write that is then not
    read back."""
    replies = {}
    for r in sorted(records, key=lambda r: (r.session, r.index)):
        if r.reply is None or r is drop:
            continue
        replies[(r.session, r.index)] = ref.apply(gen.request(r.session, r.index))
    stored = [ref.stored_rows(gen.request(r.session, r.index))
              for r in sample if r is not drop]
    transfers = (np.concatenate(stored) if stored
                 else np.zeros(0, wire.TRANSFER))
    transfers = transfers.copy()
    transfers["timestamp"] = 0
    return {"replies": replies, "accounts": ref.account_rows(),
            "transfers": transfers}


def answered_by(records: list, replies: dict) -> list:
    """The records as they would stand had `replies` (the control's)
    been served in the program's place; a request the control left out
    keeps the reply it got."""
    return [dataclasses.replace(r, reply=replies.get((r.session, r.index), r.reply))
            for r in records]


def numbers(served: dict, want: dict, records: list, health: dict) -> dict:
    """-> {name: value} for every key of LIMITS."""
    failed = sum(1 for r in records if r.reply is None)
    differing = sum(
        1 for r in records
        if r.reply is not None
        and r.reply != want["replies"].get((r.session, r.index))
    )
    return {
        "requests_failed": failed,
        "replies_differing": differing,
        "account_rows_differing": rows_differing(served["accounts"],
                                                 want["accounts"]),
        "transfer_rows_differing": rows_differing(served["transfers"],
                                                  want["transfers"]),
        **health,
    }


def verdict(values: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}), in LIMITS' order."""
    table = {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}
    return all(v["value"] <= v["limit"] for v in table.values()), table
