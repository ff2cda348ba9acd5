"""Generator kind `chains2p`: a payments switch's traffic.  Linked
chains over debit-limited accounts, and two-phase transfers: pendings,
then the posts and voids that finalise them two requests later.

The accounts are laid out in one share to each session (`accounts //
sessions`; a remainder is created and never used).  In a share the
first half carry `debits_must_not_exceed_credits`; the last `max(2,
share // 50)` of that half are POOR: nothing ever credits them, so a
debit on one always answers `exceeds_credits`.  The rest of the half
is funded by the session's first request.  The other half carries no
flag.  A session keeps to its own share and its own range of ids, so
sessions commute and the reference may replay each session by itself.

A request is a function of (seed, session, index) alone and always
has `request_events` rows, all of one class:

  index 0   funding: plain transfers of `FUND_AMOUNT` from the share's
            unflagged accounts to its funded limit accounts in rotation.
  then the cycle P, C, F, entered at a place rotated by the session's
  number (so that the requests in flight are not all of one class):

  P  pendings: `flags.pending`, timeout 0, between two distinct
     unflagged accounts, amounts 1..`amount_max`.  All accepted.
  C  chains: lengths uniform over `chain_len` (the last cut to fit),
     `flags.linked` on every leg but a chain's last, plain posted legs
     between two distinct accounts that are not poor.  With probability
     `chain_fail_share` ONE leg of a chain, at a place drawn from the
     seed, debits a poor account: it answers `exceeds_credits`, every
     other leg `linked_event_failed`, and nothing of the chain is
     stored.  (A funded account that ran dry would fail chains too, and
     the reference decides; over seeds 1, 2 and 3, 100 C requests a
     session at the cell's size, the share of chains failed that way
     was 0: a funded account holds 6 to 7 million and a C request moves
     under two thousand through it, as much in as out.)
  F  finalisers of the session's latest P request, each pending named
     at most once, in an order drawn from the seed: `void_share` of
     them `void_pending_transfer`, the others `post_pending_transfer`;
     `pending_id` set, amount, accounts, ledger and code 0 (inherited).
     A share `refinalize_share` of the rows instead name a pending that
     the session's PREVIOUS F request finalised, with either verb, and
     answer `pending_transfer_already_posted` or `_voided`; the
     pendings they displace stay pending, as a switch's stragglers do.
     An F slot with no P behind it yet is a C request.

What every kind gives the harness: see `plain.py`.  The reference here
keeps a table of the pendings (accounts, amount, ledger, code, status)
for the inherit rules and the already-finalised codes, and the result
codes of every request it applied, so that `stored_rows` can say
afterwards what a `lookup_transfers` must return.
"""

from __future__ import annotations

import bisect

import numpy as np

from .. import wire
from .plain import SESSION_ID_BITS

FUND_AMOUNT = 1_000_000
CODE = 7
_CYCLE = "PCF"
_PENDING, _POSTED, _VOIDED = 1, 2, 3


class Chains2p:
    def __init__(self, params: dict, config: dict, seed: int) -> None:
        self.seed = int(seed)
        self.n_accounts = int(config["accounts"])
        self.ledger = int(config["ledger"])
        self.sessions = int(params["sessions"])
        self.n = int(params["request_events"])
        self.amount_max = int(params["amount_max"])
        self.len_lo, self.len_hi = (int(x) for x in params["chain_len"])
        self.chain_fail_share = float(params["chain_fail_share"])
        self.void_share = float(params["void_share"])
        self.refinalize_share = float(params["refinalize_share"])
        if not 0 < self.n <= wire.REQUEST_EVENTS_MAX:
            raise ValueError(f"request_events {self.n} outside 1..8190")
        if not 1 <= self.len_lo <= self.len_hi:
            raise ValueError(f"chain_len {params['chain_len']}")
        self.share = self.n_accounts // self.sessions
        self.limited = self.share // 2
        self.poor = max(2, self.share // 50)
        self.funded = self.limited - self.poor
        if self.funded < 1 or self.share - self.limited < 2:
            raise ValueError(
                f"{self.n_accounts} accounts are too few for "
                f"{self.sessions} sessions of chains2p traffic")

    # -- the layout: account ids of a session's share -------------------

    def _first(self, session: int) -> int:
        return session * self.share + 1

    def funded_ids(self, session: int) -> np.ndarray:
        return np.arange(self.funded, dtype=np.uint64) + np.uint64(
            self._first(session))

    def poor_ids(self, session: int) -> np.ndarray:
        return np.arange(self.funded, self.limited, dtype=np.uint64) + np.uint64(
            self._first(session))

    def free_ids(self, session: int) -> np.ndarray:
        return np.arange(self.limited, self.share, dtype=np.uint64) + np.uint64(
            self._first(session))

    def accounts(self) -> np.ndarray:
        a = np.zeros(self.n_accounts, wire.ACCOUNT)
        a["id_lo"] = np.arange(1, self.n_accounts + 1, dtype=np.uint64)
        a["ledger"] = self.ledger
        a["code"] = 10
        for s in range(self.sessions):
            at = self._first(s) - 1
            a["flags"][at:at + self.limited] = (
                wire.ACCOUNT_DEBITS_MUST_NOT_EXCEED_CREDITS)
        return a

    # -- the classes -----------------------------------------------------

    def klass(self, session: int, index: int) -> str:
        """`fund`, `P`, `C` or `F`."""
        if index == 0:
            return "fund"
        k = _CYCLE[(index - 1 + session) % 3]
        if k == "F" and index - 2 < 1:
            return "C"              # no P behind it yet
        return k

    def first_id(self, session: int, index: int) -> int:
        return ((session + 1) << SESSION_ID_BITS) + index * self.n + 1

    def _rows(self, session: int, index: int) -> np.ndarray:
        t = np.zeros(self.n, wire.TRANSFER)
        t["id_lo"] = np.arange(self.n, dtype=np.uint64) + np.uint64(
            self.first_id(session, index))
        return t

    @staticmethod
    def _pairs(rng, ids: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
        """`n` debit and credit accounts, uniform over `ids`, never equal."""
        dr = rng.integers(0, len(ids), n)
        cr = (dr + rng.integers(1, len(ids), n)) % len(ids)
        return ids[dr], ids[cr]

    def request(self, session: int, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, session, index])
        k = self.klass(session, index)
        if k == "F":
            return self._finalisers(rng, session, index)
        t = self._rows(session, index)
        t["ledger"] = self.ledger
        t["code"] = CODE
        if k == "fund":
            free = self.free_ids(session)
            t["debit_account_id_lo"] = free[rng.integers(0, len(free), self.n)]
            t["credit_account_id_lo"] = self.funded_ids(session)[
                np.arange(self.n) % self.funded]
            t["amount_lo"] = FUND_AMOUNT
            return t
        t["amount_lo"] = rng.integers(1, self.amount_max + 1, self.n)
        if k == "P":
            dr, cr = self._pairs(rng, self.free_ids(session), self.n)
            t["flags"] = wire.TRANSFER_PENDING
        else:
            dr, cr = self._chains(rng, session, t)
        t["debit_account_id_lo"] = dr
        t["credit_account_id_lo"] = cr
        return t

    def _chains(self, rng, session: int, t: np.ndarray):
        n = self.n
        lens = rng.integers(self.len_lo, self.len_hi + 1, n)
        ends = np.cumsum(lens)
        chains = int(np.searchsorted(ends, n)) + 1
        ends = np.minimum(ends[:chains], n)         # the last is cut to fit
        starts = np.concatenate([[0], ends[:-1]])
        linked = np.ones(n, bool)
        linked[ends - 1] = False
        t["flags"] = np.where(linked, wire.TRANSFER_LINKED, 0)
        good = np.concatenate([self.funded_ids(session), self.free_ids(session)])
        dr, cr = self._pairs(rng, good, n)
        fails = np.flatnonzero(rng.random(chains) < self.chain_fail_share)
        at = starts[fails] + rng.integers(0, ends[fails] - starts[fails])
        poor = self.poor_ids(session)
        dr[at] = poor[rng.integers(0, len(poor), len(at))]
        return dr, cr

    def _order(self, session: int, index: int) -> np.ndarray:
        """The order in which F request `index` names the pendings of
        its P request (offsets into that request's rows); the last
        `_refinalised(...)` of them are displaced and stay pending."""
        return np.random.default_rng(
            [self.seed, session, index, 1]).permutation(self.n)

    def _refinalised(self, session: int, index: int) -> int:
        """Rows of F request `index` that name an older pending."""
        before = index - 3
        if before < 1 or self.klass(session, before) != "F":
            return 0
        return int(round(self.refinalize_share * self.n))

    def _finalisers(self, rng, session: int, index: int) -> np.ndarray:
        n = self.n
        t = self._rows(session, index)
        k = self._refinalised(session, index)
        fresh = np.uint64(self.first_id(session, index - 2)) + self._order(
            session, index)[:n - k].astype(np.uint64)
        again = np.zeros(n, bool)
        if k:
            again[rng.choice(n, size=k, replace=False)] = True
            k_before = self._refinalised(session, index - 3)
            done = self._order(session, index - 3)[:n - k_before]
            t["pending_id_lo"][again] = np.uint64(
                self.first_id(session, index - 5)) + rng.choice(
                    done, size=k, replace=False).astype(np.uint64)
        t["pending_id_lo"][~again] = fresh
        void = rng.random(n) < self.void_share
        t["flags"] = np.where(void, wire.TRANSFER_VOID, wire.TRANSFER_POST)
        return t


def make(params: dict, config: dict, seed: int) -> Chains2p:
    return Chains2p(params, config, seed)


_FIELDS = ("id_lo", "debit_account_id_lo", "credit_account_id_lo", "amount_lo",
           "pending_id_lo", "ledger", "code", "flags")


def _events(t: np.ndarray) -> list[tuple]:
    """The rows as tuples of Python integers, in `_FIELDS`' order (a
    numpy row read field by field costs ten times as much)."""
    return list(zip(*(t[f].tolist() for f in _FIELDS)))


class _Pendings:
    """The pendings one request created: ids ascending, and per id the
    event as it was sent and its status."""

    def __init__(self, t: np.ndarray) -> None:
        self.ids = t["id_lo"].copy()
        self.events = _events(t)
        self.status = [_PENDING] * len(t)


class Chains2pReference:
    """The plain reference for this kind: the state machine's order of
    precedence, event by event.  Valid only for what this kind sends:
    ids that never repeat and rise inside a request, amounts and ids
    under 2**64, no timeout, no balancing flag, one ledger, and
    `debits_must_not_exceed_credits` as the only account flag."""

    def __init__(self, gen: Chains2p) -> None:
        self.gen = gen
        n = gen.n_accounts + 1
        # Python integers: exact at any size, and fast enough one by one.
        self.debits_pending = [0] * n
        self.debits_posted = [0] * n
        self.credits_pending = [0] * n
        self.credits_posted = [0] * n
        self.limited = [False] + [
            bool(f & wire.ACCOUNT_DEBITS_MUST_NOT_EXCEED_CREDITS)
            for f in gen.accounts()["flags"].tolist()]
        self._firsts: list[int] = []        # of the pending tables, ascending
        self._tables: list[_Pendings] = []
        self._codes: dict[int, np.ndarray] = {}     # by a request's first id

    # -- the table of pendings -------------------------------------------

    def _pending(self, pending_id: int):
        """-> (table, place) or None."""
        at = bisect.bisect_right(self._firsts, pending_id) - 1
        if at < 0:
            return None
        table = self._tables[at]
        place = int(np.searchsorted(table.ids, np.uint64(pending_id)))
        if place == len(table.ids) or int(table.ids[place]) != pending_id:
            return None
        return table, place

    def _keep_pendings(self, t: np.ndarray, codes: np.ndarray) -> None:
        kept = t[(codes == wire.OK) & ((t["flags"] & wire.TRANSFER_PENDING) != 0)]
        if len(kept):
            at = bisect.bisect_right(self._firsts, int(kept["id_lo"][0]))
            self._firsts.insert(at, int(kept["id_lo"][0]))
            self._tables.insert(at, _Pendings(kept))

    # -- one event ---------------------------------------------------------

    def _create(self, event: tuple, undo: list | None) -> int:
        ident, dr, cr, amount, pending_id, ledger, code, flags = event
        g = self.gen
        if ident == 0:
            return wire.ID_MUST_NOT_BE_ZERO
        if dr == 0:
            return wire.DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO
        if cr == 0:
            return wire.CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO
        if dr == cr:
            return wire.ACCOUNTS_MUST_BE_DIFFERENT
        if pending_id != 0:
            return wire.PENDING_ID_MUST_BE_ZERO
        if amount == 0:
            return wire.AMOUNT_MUST_NOT_BE_ZERO
        if ledger == 0:
            return wire.LEDGER_MUST_NOT_BE_ZERO
        if code == 0:
            return wire.CODE_MUST_NOT_BE_ZERO
        if dr > g.n_accounts:
            return wire.DEBIT_ACCOUNT_NOT_FOUND
        if cr > g.n_accounts:
            return wire.CREDIT_ACCOUNT_NOT_FOUND
        if ledger != g.ledger:
            return wire.TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS
        if self.limited[dr] and (
                self.debits_pending[dr] + self.debits_posted[dr] + amount
                > self.credits_posted[dr]):
            return wire.EXCEEDS_CREDITS
        if flags & wire.TRANSFER_PENDING:
            moved = (self.debits_pending, dr, self.credits_pending, cr, amount)
        else:
            moved = (self.debits_posted, dr, self.credits_posted, cr, amount)
        moved[0][dr] += amount
        moved[2][cr] += amount
        if undo is not None:
            undo.append(moved)
        return wire.OK

    def _finalise(self, event: tuple) -> int:
        ident, dr, cr, amount, pending_id, ledger, code, flags = event
        post = bool(flags & wire.TRANSFER_POST)
        void = bool(flags & wire.TRANSFER_VOID)
        if ident == 0:
            return wire.ID_MUST_NOT_BE_ZERO
        if (post and void) or flags & wire.TRANSFER_PENDING:
            return wire.FLAGS_ARE_MUTUALLY_EXCLUSIVE
        if pending_id == 0:
            return wire.PENDING_ID_MUST_NOT_BE_ZERO
        if pending_id == ident:
            return wire.PENDING_ID_MUST_BE_DIFFERENT
        found = self._pending(pending_id)
        if found is None:
            return wire.PENDING_TRANSFER_NOT_FOUND
        table, place = found
        _, p_dr, p_cr, held, _, p_ledger, p_code, _ = table.events[place]
        if dr not in (0, p_dr):
            return wire.PENDING_TRANSFER_HAS_DIFFERENT_DEBIT_ACCOUNT_ID
        if cr not in (0, p_cr):
            return wire.PENDING_TRANSFER_HAS_DIFFERENT_CREDIT_ACCOUNT_ID
        if ledger not in (0, p_ledger):
            return wire.PENDING_TRANSFER_HAS_DIFFERENT_LEDGER
        if code not in (0, p_code):
            return wire.PENDING_TRANSFER_HAS_DIFFERENT_CODE
        amount = amount or held
        if amount > held:
            return wire.EXCEEDS_PENDING_TRANSFER_AMOUNT
        if void and amount < held:
            return wire.PENDING_TRANSFER_HAS_DIFFERENT_AMOUNT
        if table.status[place] == _POSTED:
            return wire.PENDING_TRANSFER_ALREADY_POSTED
        if table.status[place] == _VOIDED:
            return wire.PENDING_TRANSFER_ALREADY_VOIDED
        table.status[place] = _POSTED if post else _VOIDED
        self.debits_pending[p_dr] -= held
        self.credits_pending[p_cr] -= held
        if post:
            self.debits_posted[p_dr] += amount
            self.credits_posted[p_cr] += amount
        return wire.OK

    # -- one request ---------------------------------------------------------

    def codes(self, t: np.ndarray) -> np.ndarray:
        """Apply the request's events in order; -> their result codes."""
        for f in ("id_hi", "debit_account_id_hi", "credit_account_id_hi",
                  "amount_hi", "pending_id_hi", "timeout", "timestamp"):
            if t[f].any():
                raise ValueError(f"chains2p reference: field {f} is set")
        finalise = wire.TRANSFER_POST | wire.TRANSFER_VOID
        n = len(t)
        codes = [wire.OK] * n
        chain_from: int | None = None       # the open chain's first event
        broken = False
        undo: list = []
        for i, event in enumerate(_events(t)):
            flags = event[-1]
            linked = bool(flags & wire.TRANSFER_LINKED)
            if linked and chain_from is None:
                chain_from, broken, undo = i, False, []
            if linked and i == n - 1:
                code = wire.LINKED_EVENT_CHAIN_OPEN
            elif broken:
                code = wire.LINKED_EVENT_FAILED
            elif flags & finalise:
                if chain_from is not None:
                    raise ValueError("chains2p reference: a finaliser in a chain")
                code = self._finalise(event)
            else:
                code = self._create(event, undo if chain_from is not None else None)
            codes[i] = code
            if code != wire.OK and chain_from is not None and not broken:
                # The chain falls whole: what its legs moved goes back.
                broken = True
                for a, dr, b, cr, amount in reversed(undo):
                    a[dr] -= amount
                    b[cr] -= amount
                codes[chain_from:i] = [wire.LINKED_EVENT_FAILED] * (i - chain_from)
            if chain_from is not None and (
                    not linked or code == wire.LINKED_EVENT_CHAIN_OPEN):
                chain_from = None
                broken = False
        return np.array(codes, np.uint32)

    def apply(self, t: np.ndarray) -> bytes:
        """Commit one request; -> the reply's bytes (failures only)."""
        codes = self.codes(t)
        self._codes[int(t["id_lo"][0])] = codes
        self._keep_pendings(t, codes)
        bad = np.flatnonzero(codes != wire.OK)
        reply = np.zeros(len(bad), wire.CREATE_RESULT)
        reply["index"] = bad
        reply["result"] = codes[bad]
        return reply.tobytes()

    def account_rows(self) -> np.ndarray:
        a = self.gen.accounts()
        a["debits_pending_lo"] = self.debits_pending[1:]
        a["debits_posted_lo"] = self.debits_posted[1:]
        a["credits_pending_lo"] = self.credits_pending[1:]
        a["credits_posted_lo"] = self.credits_posted[1:]
        return a

    def stored_rows(self, t: np.ndarray) -> np.ndarray:
        """The rows of request `t`, applied before, that a
        lookup_transfers must return: nothing of a failed chain; a post
        or a void with what it inherits from its pending."""
        kept = t[self._codes[int(t["id_lo"][0])] == wire.OK].copy()
        for row in kept:
            if row["flags"] & (wire.TRANSFER_POST | wire.TRANSFER_VOID):
                table, place = self._pending(int(row["pending_id_lo"]))
                _, dr, cr, held, _, ledger, code, _ = table.events[place]
                row["debit_account_id_lo"] = dr
                row["credit_account_id_lo"] = cr
                row["ledger"] = ledger
                row["code"] = code
                if row["amount_lo"] == 0:
                    row["amount_lo"] = held
        return kept


def reference(gen: Chains2p) -> Chains2pReference:
    return Chains2pReference(gen)
