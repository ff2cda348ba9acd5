"""Generator kind `plain`: unflagged, posted transfers.

Every request has `request_events` transfers with distinct ids, debit
and credit accounts uniform over the configuration's accounts and
never equal, amounts 1..`amount_max`.  `request_events` is one size,
or a list: then each session sends the list's sizes over and over,
every turn in an order of its own drawn from the seed, so that every
seed sends the same sizes.  Such requests commute, so the
order in which concurrent sessions commit does not change any answer.
A request is a function of (seed, session, index) alone, so that the
reference draws the same rows again after the window has closed.

What every kind gives the harness: `accounts()`, `request(session,
index)` (the rows of a `create_transfers` body) and `n_accounts`; and
from `reference(gen)`: `apply(rows)` (the reply's bytes), and
`account_rows()` and `stored_rows(rows)` for the read-back.

`bad_rows` (0 in every benchmark traffic file) plants that many rows
per request which must be refused, one fault each in rotation; the
tests use it to show that the reference follows the state machine's
order of precedence and is not an "all ok" stub.
"""

from __future__ import annotations

import numpy as np

from .. import wire

SESSION_ID_BITS = 40   # ids in sequence, a range to each session

_FAULTS = ("same_accounts", "unknown_debit", "unknown_credit", "zero_amount",
           "zero_id", "wrong_ledger", "zero_code", "zero_debit")


class Plain:
    def __init__(self, params: dict, config: dict, seed: int) -> None:
        self.seed = int(seed)
        self.n_accounts = int(config["accounts"])
        self.ledger = int(config["ledger"])
        sizes = params["request_events"]
        self.sizes = [int(n) for n in (sizes if isinstance(sizes, list) else [sizes])]
        self.amount_max = int(params["amount_max"])
        self.bad_rows = int(params.get("bad_rows", 0))
        if not self.sizes or not all(
                0 < n <= wire.REQUEST_EVENTS_MAX for n in self.sizes):
            raise ValueError(f"request_events {sizes} outside 1..8190")
        if self.n_accounts < 2:
            raise ValueError("plain traffic needs two accounts or more")

    def accounts(self) -> np.ndarray:
        a = np.zeros(self.n_accounts, wire.ACCOUNT)
        a["id_lo"] = np.arange(1, self.n_accounts + 1, dtype=np.uint64)
        a["ledger"] = self.ledger
        a["code"] = 10
        return a

    def events(self, session: int, index: int) -> int:
        turn, at = divmod(index, len(self.sizes))
        if len(self.sizes) == 1:
            return self.sizes[0]
        order = np.random.default_rng([self.seed, session, turn, 2]).permutation(
            len(self.sizes))
        return self.sizes[order[at]]

    def first_id(self, session: int, index: int) -> int:
        return ((session + 1) << SESSION_ID_BITS) + index * max(self.sizes) + 1

    def request(self, session: int, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, session, index])
        n = self.events(session, index)
        t = np.zeros(n, wire.TRANSFER)
        t["id_lo"] = np.arange(n, dtype=np.uint64) + np.uint64(
            self.first_id(session, index))
        dr = rng.integers(0, self.n_accounts, n)
        cr = (dr + rng.integers(1, self.n_accounts, n)) % self.n_accounts
        t["debit_account_id_lo"] = dr + 1
        t["credit_account_id_lo"] = cr + 1
        t["amount_lo"] = rng.integers(1, self.amount_max + 1, n)
        t["ledger"] = self.ledger
        t["code"] = 7
        if self.bad_rows:
            at = rng.choice(n, size=min(self.bad_rows, n), replace=False)
            for k, i in enumerate(at):
                fault = _FAULTS[(k + index) % len(_FAULTS)]
                if fault == "same_accounts":
                    t["credit_account_id_lo"][i] = t["debit_account_id_lo"][i]
                elif fault == "unknown_debit":
                    t["debit_account_id_lo"][i] = self.n_accounts + 7 + k
                elif fault == "unknown_credit":
                    t["credit_account_id_lo"][i] = self.n_accounts + 7 + k
                elif fault == "zero_amount":
                    t["amount_lo"][i] = 0
                elif fault == "zero_id":
                    t["id_lo"][i] = 0
                elif fault == "wrong_ledger":
                    t["ledger"][i] = self.ledger + 1
                elif fault == "zero_code":
                    t["code"][i] = 0
                elif fault == "zero_debit":
                    t["debit_account_id_lo"][i] = 0
        return t


def make(params: dict, config: dict, seed: int) -> Plain:
    return Plain(params, config, seed)


class PlainReference:
    """The plain reference for this kind: result codes by the state
    machine's order of precedence, balances by addition.  It holds no
    transfer store: a plain transfer that was accepted reads back as
    the row that was sent (the timestamp masked), and the rows are
    drawn again from the seed.  Valid only for what this kind sends:
    unflagged rows, ids that never repeat, accounts without limits."""

    def __init__(self, gen: Plain) -> None:
        self.gen = gen
        n = gen.n_accounts
        # Amounts are under 2**16 and a run sends under 2**33 events, so
        # the sums stay far below 2**64 and the high limbs stay nought.
        self.debits = np.zeros(n + 1, np.uint64)
        self.credits = np.zeros(n + 1, np.uint64)
        self.events_accepted = 0

    def codes(self, t: np.ndarray) -> np.ndarray:
        n_acc = self.gen.n_accounts
        dr, cr = t["debit_account_id_lo"], t["credit_account_id_lo"]
        for f in ("id_hi", "debit_account_id_hi", "credit_account_id_hi",
                  "amount_hi", "pending_id_lo", "pending_id_hi", "timeout",
                  "flags", "timestamp"):
            if t[f].any():
                raise ValueError(f"plain reference: field {f} is set")
        checks = (
            (t["id_lo"] == 0, wire.ID_MUST_NOT_BE_ZERO),
            (dr == 0, wire.DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO),
            (cr == 0, wire.CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO),
            (dr == cr, wire.ACCOUNTS_MUST_BE_DIFFERENT),
            (t["amount_lo"] == 0, wire.AMOUNT_MUST_NOT_BE_ZERO),
            (t["ledger"] == 0, wire.LEDGER_MUST_NOT_BE_ZERO),
            (t["code"] == 0, wire.CODE_MUST_NOT_BE_ZERO),
            (dr > n_acc, wire.DEBIT_ACCOUNT_NOT_FOUND),
            (cr > n_acc, wire.CREDIT_ACCOUNT_NOT_FOUND),
            (t["ledger"] != self.gen.ledger,
             wire.TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS),
        )
        codes = np.zeros(len(t), np.uint32)
        for cond, code in reversed(checks):      # the first in order wins
            codes[cond] = code
        return codes

    def apply(self, t: np.ndarray) -> bytes:
        """Commit one request; -> the reply's bytes (failures only)."""
        codes = self.codes(t)
        ok = codes == wire.OK
        amount = t["amount_lo"][ok]
        np.add.at(self.debits, t["debit_account_id_lo"][ok].astype(np.int64), amount)
        np.add.at(self.credits, t["credit_account_id_lo"][ok].astype(np.int64), amount)
        self.events_accepted += int(ok.sum())
        bad = np.flatnonzero(~ok)
        reply = np.zeros(len(bad), wire.CREATE_RESULT)
        reply["index"] = bad
        reply["result"] = codes[bad]
        return reply.tobytes()

    def account_rows(self) -> np.ndarray:
        a = self.gen.accounts()
        a["debits_posted_lo"] = self.debits[1:]
        a["credits_posted_lo"] = self.credits[1:]
        return a

    def stored_rows(self, t: np.ndarray) -> np.ndarray:
        """The rows of request `t` that a lookup_transfers must return."""
        return t[self.codes(t) == wire.OK]


def reference(gen: Plain) -> PlainReference:
    return PlainReference(gen)
