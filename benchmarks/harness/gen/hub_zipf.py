"""Generator kind `hub_zipf`: a payment hub's participants under
hot-account contention (`BASELINE.json` configs[3], Zipf-skewed
transfers over 100 accounts, under configs[1]'s
`debits_must_not_exceed_credits`).

The accounts are laid out in one share to each session (`accounts //
sessions`; a remainder is created and never used), and each share is
a ledger of its own (`ledger + session`).  A share's first account is
the hub's SETTLEMENT account, with no flag; the others are the
PARTICIPANTS, each under `debits_must_not_exceed_credits`: a position
that may never go below zero.  A session keeps to its own share, its
own ledger and its own range of ids, so sessions commute and the
reference may replay each session by itself.

A request is a function of (seed, session, index) alone and always
has `request_events` plain posted rows:

  index 0   prefunding: rows from the settlement account to the
            participants in rotation, each participant's rows summing
            to the configuration's `prefund` (20,000 where it states
            none).  All accepted.
  then      payments: payer and payee drawn Zipf(`zipf_s`) by rank
            over the share's participants (rank 1 is the share's first
            participant), the payee drawn again while it equals the
            payer; amounts uniform over 1..`amount_max`.  A payment
            that would take its payer's position below zero answers
            `exceeds_credits` and moves nothing: which ones do is
            decided by the order of the rows, so the reference decides.

What every kind gives the harness: see `plain.py`.  The reference here
is the state machine's rule for a debit limit, event by event over
Python integers, with the codes of every request it applied kept, so
that `stored_rows` can say afterwards what a `lookup_transfers` must
return.
"""

from __future__ import annotations

import numpy as np

from .. import wire
from .plain import SESSION_ID_BITS

PREFUND = 20_000        # a participant's liquidity where the config states none
CODE = 7


class HubZipf:
    def __init__(self, params: dict, config: dict, seed: int) -> None:
        self.seed = int(seed)
        self.n_accounts = int(config["accounts"])
        self.ledger = int(config["ledger"])
        self.prefund = int(config.get("prefund", PREFUND))
        self.sessions = int(params["sessions"])
        self.n = int(params["request_events"])
        self.amount_max = int(params["amount_max"])
        self.zipf_s = float(params["zipf_s"])
        if not 0 < self.n <= wire.REQUEST_EVENTS_MAX:
            raise ValueError(f"request_events {self.n} outside 1..8190")
        self.share = self.n_accounts // self.sessions
        self.participants = self.share - 1
        if not 2 <= self.participants <= self.n:
            raise ValueError(
                f"{self.n_accounts} accounts make {self.participants} "
                f"participants a session: 2..{self.n} are needed")
        if self.prefund < -(-self.n // self.participants):
            raise ValueError(f"prefund {self.prefund}: a prefunding row "
                             "would move nothing")
        weights = np.arange(1, self.participants + 1, dtype=np.float64) ** -self.zipf_s
        self.cdf = np.cumsum(weights / weights.sum())

    # -- the layout --------------------------------------------------------

    def settlement_id(self, session: int) -> int:
        return session * self.share + 1

    def participant_ids(self, session: int) -> np.ndarray:
        return np.arange(self.participants, dtype=np.uint64) + np.uint64(
            self.settlement_id(session) + 1)

    def ledger_of(self, session: int) -> int:
        return self.ledger + session

    def accounts(self) -> np.ndarray:
        a = np.zeros(self.n_accounts, wire.ACCOUNT)
        a["id_lo"] = np.arange(1, self.n_accounts + 1, dtype=np.uint64)
        a["code"] = 10
        a["ledger"] = self.ledger + np.minimum(
            np.arange(self.n_accounts) // self.share, self.sessions - 1)
        for s in range(self.sessions):
            at = self.settlement_id(s)
            a["flags"][at:at + self.participants] = (
                wire.ACCOUNT_DEBITS_MUST_NOT_EXCEED_CREDITS)
        return a

    # -- a request ---------------------------------------------------------

    def first_id(self, session: int, index: int) -> int:
        return ((session + 1) << SESSION_ID_BITS) + index * self.n + 1

    def ranks(self, rng, n: int) -> np.ndarray:
        """`n` participant ranks from 0, Zipf by rank."""
        return np.minimum(np.searchsorted(self.cdf, rng.random(n), side="right"),
                          self.participants - 1)

    def request(self, session: int, index: int) -> np.ndarray:
        t = np.zeros(self.n, wire.TRANSFER)
        t["id_lo"] = np.arange(self.n, dtype=np.uint64) + np.uint64(
            self.first_id(session, index))
        t["ledger"] = self.ledger_of(session)
        t["code"] = CODE
        ids = self.participant_ids(session)
        if index == 0:
            to = np.arange(self.n) % self.participants
            rows = np.bincount(to, minlength=self.participants)
            # Each participant's rows sum to the prefund: every row the
            # quotient, its last row the remainder besides.
            last = np.zeros(self.n, bool)
            last[self.n - self.participants:] = True
            t["debit_account_id_lo"] = self.settlement_id(session)
            t["credit_account_id_lo"] = ids[to]
            t["amount_lo"] = self.prefund // rows[to] + np.where(
                last, self.prefund % rows[to], 0)
            return t
        rng = np.random.default_rng([self.seed, session, index])
        payer = self.ranks(rng, self.n)
        payee = self.ranks(rng, self.n)
        same = np.flatnonzero(payee == payer)
        while len(same):
            payee[same] = self.ranks(rng, len(same))
            same = same[payee[same] == payer[same]]
        t["debit_account_id_lo"] = ids[payer]
        t["credit_account_id_lo"] = ids[payee]
        t["amount_lo"] = rng.integers(1, self.amount_max + 1, self.n)
        return t


def make(params: dict, config: dict, seed: int) -> HubZipf:
    return HubZipf(params, config, seed)


_FIELDS = ("id_lo", "debit_account_id_lo", "credit_account_id_lo", "amount_lo",
           "ledger", "code")


class HubZipfReference:
    """The plain reference for this kind: every event through the state
    machine's order of precedence, one after another, and a debit that
    would take a participant's debits past its credits refused
    (src/tigerbeetle.zig:31-39, `debits_must_not_exceed_credits`).
    Valid only for what this kind sends: plain posted rows with no
    flag, ids that never repeat, amounts and ids under 2**64, accounts
    whose only flag is `debits_must_not_exceed_credits`."""

    def __init__(self, gen: HubZipf) -> None:
        self.gen = gen
        a = gen.accounts()
        # Python integers: exact at any size, and fast enough one by one.
        self.debits = [0] * (gen.n_accounts + 1)
        self.credits = [0] * (gen.n_accounts + 1)
        self.limited = [False] + [
            bool(f & wire.ACCOUNT_DEBITS_MUST_NOT_EXCEED_CREDITS)
            for f in a["flags"].tolist()]
        self.ledgers = [0] + a["ledger"].tolist()
        self._codes: dict[int, np.ndarray] = {}     # by a request's first id

    def _code(self, event: tuple) -> int:
        ident, dr, cr, amount, ledger, code = event
        n = self.gen.n_accounts
        if ident == 0:
            return wire.ID_MUST_NOT_BE_ZERO
        if dr == 0:
            return wire.DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO
        if cr == 0:
            return wire.CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO
        if dr == cr:
            return wire.ACCOUNTS_MUST_BE_DIFFERENT
        if amount == 0:
            return wire.AMOUNT_MUST_NOT_BE_ZERO
        if ledger == 0:
            return wire.LEDGER_MUST_NOT_BE_ZERO
        if code == 0:
            return wire.CODE_MUST_NOT_BE_ZERO
        if dr > n:
            return wire.DEBIT_ACCOUNT_NOT_FOUND
        if cr > n:
            return wire.CREDIT_ACCOUNT_NOT_FOUND
        if self.ledgers[dr] != self.ledgers[cr]:
            raise ValueError("hub_zipf reference: accounts of two ledgers")
        if ledger != self.ledgers[dr]:
            return wire.TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS
        if self.limited[dr] and self.debits[dr] + amount > self.credits[dr]:
            return wire.EXCEEDS_CREDITS
        return wire.OK

    def codes(self, t: np.ndarray) -> np.ndarray:
        """Apply the request's events in order; -> their result codes."""
        for f in ("id_hi", "debit_account_id_hi", "credit_account_id_hi",
                  "amount_hi", "pending_id_lo", "pending_id_hi", "timeout",
                  "flags", "timestamp"):
            if t[f].any():
                raise ValueError(f"hub_zipf reference: field {f} is set")
        debits, credits = self.debits, self.credits
        codes = []
        for event in zip(*(t[f].tolist() for f in _FIELDS)):
            code = self._code(event)
            if code == wire.OK:
                _, dr, cr, amount, _, _ = event
                debits[dr] += amount
                credits[cr] += amount
            codes.append(code)
        return np.array(codes, np.uint32)

    def apply(self, t: np.ndarray) -> bytes:
        """Commit one request; -> the reply's bytes (failures only)."""
        codes = self.codes(t)
        self._codes[int(t["id_lo"][0])] = codes
        bad = np.flatnonzero(codes != wire.OK)
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
        """The rows of request `t`, applied before, that a
        lookup_transfers must return: the accepted ones."""
        return t[self._codes[int(t["id_lo"][0])] == wire.OK]


def reference(gen: HubZipf) -> HubZipfReference:
    return HubZipfReference(gen)
