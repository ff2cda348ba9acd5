"""Generator kind `tpcc_payment`: TPC-C's Payment transaction (revision
5.11, clause 2.5) on a ledger, as a wholesale supplier's receivables.

The accounts (clause 4.3's scaling) are laid out warehouse by
warehouse, ids 1..n with no gap: a warehouse's own account (`code` 1),
then its `districts_per_warehouse` district accounts (`code` 2), then
its customers, district by district (`code` 3).  At 10 districts of
3,000 customers that is 30,011 accounts a warehouse.  A configuration
that states no warehouses (the other deployments' files) gets one a
session, of 10 districts, with as many customers a district as its
`accounts` hold; accounts beyond the layout are created and never
used.  No account carries a flag, and all open at zero.

A payment is a linked chain of two transfers for H_AMOUNT, uniform
over `amount_min`..`amount_max` cents: customer -> district (`linked`),
district -> warehouse.  The two rows are the payment's History row.
The home warehouse is uniform over the session's own (`session k` is
the terminals of warehouses k*W/S+1 .. (k+1)*W/S), the district uniform
over the home warehouse's; the customer is of the home district, or,
with probability `remote_share`, of a district drawn uniformly of
ANOTHER warehouse drawn uniformly (any session's: remote customers are
shared between sessions, and since nothing carries a limit, sessions
still commute).  The customer's number is NURand(A, 1, C) of clause
2.1.6, ((random(0, A) | random(1, C)) + c) % C + 1, with A the largest
2**k - 1 not over half of C (1023 at C = 3,000, the clause's) and the
run-time constant c drawn from the seed alone.

With probability `keying_error_share` a payment carries a keying
error: its second leg credits a warehouse id that does not exist.
Leg 1 then answers `linked_event_failed`, leg 2
`credit_account_not_found`, nothing is stored and no balance moves.
The fault is static (no balance decides it), so sessions still
commute.  A session's first request carries none: the harness holds
every kind to a first request that is accepted whole
(`tests/benchmarks/test_manifest.py`).

A request is a function of (seed, session, index) alone and always has
`request_events` rows, `request_events // 2` payments, ids in sequence
with a range to each session.  From the kernel's side: 8,190 legs of
mean 2,500.50 sum to about 2.05e9 cents, 4.6% (5.4 standard deviations
of the sum) under the 2**31 - 1 at which the planner leaves
`linked_small` for `linked`.

What every kind gives the harness: see `plain.py`.  The reference
here is the state machine's order of precedence event by event over
Python integers, with a chain's moves taken back when a leg fails.
"""

from __future__ import annotations

import numpy as np

from .. import wire
from .plain import SESSION_ID_BITS

CODE_WAREHOUSE, CODE_DISTRICT, CODE_CUSTOMER = 1, 2, 3
CODE_PAYMENT = 25       # the transfers' `code`: clause 2.5


def nurand_a(customers: int) -> int:
    """The largest 2**k - 1 not over half the range."""
    return (1 << (max(2, customers // 2).bit_length() - 1)) - 1


class TpccPayment:
    def __init__(self, params: dict, config: dict, seed: int) -> None:
        self.seed = int(seed)
        self.n_accounts = int(config["accounts"])
        self.ledger = int(config["ledger"])
        self.sessions = int(params["sessions"])
        self.n = int(params["request_events"])
        self.amount_min = int(params["amount_min"])
        self.amount_max = int(params["amount_max"])
        self.remote_share = float(params["remote_share"])
        self.keying_error_share = float(params["keying_error_share"])
        if not 0 < self.n <= wire.REQUEST_EVENTS_MAX or self.n % 2:
            raise ValueError(f"request_events {self.n}: even, 2..8190")
        if not 1 <= self.amount_min <= self.amount_max:
            raise ValueError(f"amounts {self.amount_min}..{self.amount_max}")
        self.warehouses = int(config.get("warehouses", self.sessions))
        self.districts = int(config.get("districts_per_warehouse", 10))
        self.customers = int(config.get("customers_per_district") or (
            self.n_accounts // self.warehouses - 1 - self.districts)
            // self.districts)
        self.per_warehouse = 1 + self.districts * (1 + self.customers)
        self.per_session = self.warehouses // self.sessions
        if (self.warehouses < 2 or self.per_session < 1 or self.customers < 2
                or self.warehouses * self.per_warehouse > self.n_accounts):
            raise ValueError(
                f"{self.n_accounts} accounts do not hold {self.warehouses} "
                f"warehouses of {self.districts} districts of {self.customers} "
                f"customers for {self.sessions} sessions")
        self.nurand_a = nurand_a(self.customers)
        self.nurand_c = int(np.random.default_rng(
            [self.seed, 0x7CC]).integers(0, self.nurand_a + 1))

    # -- the layout: warehouse, district and customer numbers from 0 -----

    def warehouse_id(self, w):
        return w * self.per_warehouse + 1

    def district_id(self, w, d):
        return w * self.per_warehouse + 2 + d

    def customer_id(self, w, d, c):
        return (w * self.per_warehouse + 1 + self.districts
                + d * self.customers + c + 1)

    def accounts(self) -> np.ndarray:
        a = np.zeros(self.n_accounts, wire.ACCOUNT)
        a["id_lo"] = np.arange(1, self.n_accounts + 1, dtype=np.uint64)
        a["ledger"] = self.ledger
        a["code"] = CODE_CUSTOMER
        for w in range(self.warehouses):
            at = w * self.per_warehouse
            a["code"][at] = CODE_WAREHOUSE
            a["code"][at + 1:at + 1 + self.districts] = CODE_DISTRICT
        return a

    # -- a request --------------------------------------------------------

    def first_id(self, session: int, index: int) -> int:
        return ((session + 1) << SESSION_ID_BITS) + index * self.n + 1

    def nurand(self, rng, n: int) -> np.ndarray:
        """`n` customer numbers from 0 (clause 2.1.6, less one)."""
        return ((rng.integers(0, self.nurand_a + 1, n)
                 | rng.integers(1, self.customers + 1, n))
                + self.nurand_c) % self.customers

    def payments(self, session: int, index: int) -> dict:
        """The request's payments, a column each: home warehouse and
        district, the customer's warehouse, district and number (from
        0), the amount, and whether it is remote or carries a keying
        error."""
        rng = np.random.default_rng([self.seed, session, index])
        p = self.n // 2
        home_w = session * self.per_session + rng.integers(0, self.per_session, p)
        home_d = rng.integers(0, self.districts, p)
        remote = rng.random(p) < self.remote_share
        other_w = (home_w + rng.integers(1, self.warehouses, p)) % self.warehouses
        other_d = rng.integers(0, self.districts, p)
        error = rng.random(p) < self.keying_error_share
        if index == 0:
            error[:] = False
        return {
            "home_w": home_w, "home_d": home_d, "remote": remote,
            "cust_w": np.where(remote, other_w, home_w),
            "cust_d": np.where(remote, other_d, home_d),
            "cust": self.nurand(rng, p),
            "amount": rng.integers(self.amount_min, self.amount_max + 1, p),
            "keying_error": error,
        }

    def request(self, session: int, index: int) -> np.ndarray:
        p = self.payments(session, index)
        t = np.zeros(self.n, wire.TRANSFER)
        t["id_lo"] = np.arange(self.n, dtype=np.uint64) + np.uint64(
            self.first_id(session, index))
        t["ledger"] = self.ledger
        t["code"] = CODE_PAYMENT
        district = self.district_id(p["home_w"], p["home_d"])
        leg1, leg2 = t[0::2], t[1::2]
        leg1["debit_account_id_lo"] = self.customer_id(
            p["cust_w"], p["cust_d"], p["cust"])
        leg1["credit_account_id_lo"] = district
        leg1["flags"] = wire.TRANSFER_LINKED
        leg2["debit_account_id_lo"] = district
        # A keying error names warehouse W + 1 + w: no such account.
        leg2["credit_account_id_lo"] = np.where(
            p["keying_error"], self.n_accounts + 1 + p["home_w"],
            self.warehouse_id(p["home_w"]))
        leg1["amount_lo"] = leg2["amount_lo"] = p["amount"]
        return t


def make(params: dict, config: dict, seed: int) -> TpccPayment:
    return TpccPayment(params, config, seed)


_FIELDS = ("id_lo", "debit_account_id_lo", "credit_account_id_lo", "amount_lo",
           "ledger", "code", "flags")


class TpccPaymentReference:
    """The plain reference for this kind: every event through the
    state machine's order of precedence, one after another, a chain's
    moves taken back when one of its legs fails.  Valid only for what
    this kind sends: ids that never repeat, ids and amounts under
    2**64, `linked` as the only flag, one ledger, accounts 1..n
    without flags.  No balance decides a code, so `codes` holds no
    state and `stored_rows` may ask it again."""

    def __init__(self, gen: TpccPayment) -> None:
        self.gen = gen
        # Python integers: exact at any size.
        self.debits = [0] * (gen.n_accounts + 1)
        self.credits = [0] * (gen.n_accounts + 1)

    def _code(self, event: tuple) -> int:
        ident, dr, cr, amount, ledger, code, _flags = event
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
        if ledger != self.gen.ledger:
            return wire.TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS
        return wire.OK

    def _walk(self, t: np.ndarray, move: bool) -> np.ndarray:
        """The request's events in order; -> their result codes.  With
        `move` the accepted ones move the balances."""
        for f in ("id_hi", "debit_account_id_hi", "credit_account_id_hi",
                  "amount_hi", "pending_id_lo", "pending_id_hi", "timeout",
                  "timestamp"):
            if t[f].any():
                raise ValueError(f"tpcc_payment reference: field {f} is set")
        if (t["flags"] & ~np.uint16(wire.TRANSFER_LINKED)).any():
            raise ValueError("tpcc_payment reference: a flag other than linked")
        n = len(t)
        codes = [wire.OK] * n
        chain: list[int] = []           # the open chain's events so far
        broken = False
        events = list(zip(*(t[f].tolist() for f in _FIELDS)))
        for i, event in enumerate(events):
            linked = bool(event[-1] & wire.TRANSFER_LINKED)
            if linked and i == n - 1:
                code = wire.LINKED_EVENT_CHAIN_OPEN
            elif broken:
                code = wire.LINKED_EVENT_FAILED
            else:
                code = self._code(event)
            codes[i] = code
            if code != wire.OK and not broken:
                # The chain falls whole: its earlier legs answer for it.
                broken = True
                for j in chain:
                    codes[j] = wire.LINKED_EVENT_FAILED
            chain.append(i)
            if not linked or i == n - 1:
                if move and not broken:
                    for j in chain:
                        _, dr, cr, amount, _, _, _ = events[j]
                        self.debits[dr] += amount
                        self.credits[cr] += amount
                chain, broken = [], False
        return np.array(codes, np.uint32)

    def codes(self, t: np.ndarray) -> np.ndarray:
        return self._walk(t, move=False)

    def apply(self, t: np.ndarray) -> bytes:
        """Commit one request; -> the reply's bytes (failures only)."""
        codes = self._walk(t, move=True)
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
        """The rows of request `t` that a lookup_transfers must return:
        nothing of a failed chain."""
        return t[self.codes(t) == wire.OK]


def reference(gen: TpccPayment) -> TpccPaymentReference:
    return TpccPaymentReference(gen)
