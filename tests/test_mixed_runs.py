"""A prepare of mixed classes, cut into runs that one kernel each takes
(`state_machine/tpu.py _cut_runs`, `_plan_runs`; the engine's
`PrepareReply`).

Every case runs the device engine beside `CpuStateMachine` and holds
it to it: each reply byte for byte, the account table, and the stored
transfers.  Where the cut applies, nothing goes to wave dispatch or to
the host; where it does not, the batch takes the path it took before.
"""

import os
import sys

import numpy as np
import pytest

import tigerbeetle_tpu.state_machine.device_engine as de
from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine import tpu as tpu_mod
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.types import Operation, TransferFlags

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from benchmarks.harness.gen import chains2p  # noqa: E402

TF = TransferFlags
AF = types.AccountFlags
CTR = types.CreateTransferResult
LIMIT = int(AF.debits_must_not_exceed_credits)
PARAMS = {"sessions": 4, "request_events": 100, "amount_max": 999,
          "chain_len": [1, 7], "chain_fail_share": 0.1, "void_share": 0.3,
          "refinalize_share": 0.05}


def pair():
    # Odd capacity: an unsharded engine on the suite's 8-device mesh.
    sm = TpuStateMachine(engine="device", account_capacity=(1 << 12) + 1)
    return hz.SingleNodeHarness(sm), hz.SingleNodeHarness(CpuStateMachine())


def both(h_d, h_c, op, body) -> bytes:
    got, want = h_d.submit(op, body), h_c.submit(op, body)
    assert got == want, op
    return got


def counters(h_d) -> dict:
    snap = h_d.sm.metrics.snapshot()
    return {k: snap[k] for k in (
        "plan.runs", "plan.cut_prepares", "dev.kind.waves.batches",
        "host_semantic_events", "dev_wave.batches")}


def same_state(h_d, h_c, account_ids, transfer_ids) -> None:
    assert (h_d.lookup_accounts(account_ids)
            == h_c.lookup_accounts(account_ids)).all()
    got = h_d.lookup_transfers(transfer_ids)
    want = h_c.lookup_transfers(transfer_ids)
    assert len(got) == len(want) and (got == want).all()


def codes_of(reply: bytes, n: int) -> np.ndarray:
    out = np.zeros(n, np.uint32)
    got = np.frombuffer(reply, types.CREATE_RESULT_DTYPE)
    out[got["index"]] = got["result"]
    return out


def setup_accounts(h_d, h_c, plain=range(1, 9), limited=range(9, 13)):
    rows = [hz.account(i) for i in plain] + [
        hz.account(i, flags=LIMIT) for i in limited]
    assert both(h_d, h_c, Operation.create_accounts, hz.pack(rows)) == b""
    # Fund the limited accounts (a linked-class batch of its own).
    fund = [hz.transfer(900 + i, debit_account_id=1, credit_account_id=i,
                        amount=1000) for i in limited]
    assert both(h_d, h_c, Operation.create_transfers, hz.pack(fund)) == b""


def pending(tid, dr=1, cr=2, amount=10, **kw):
    return hz.transfer(tid, debit_account_id=dr, credit_account_id=cr,
                       amount=amount, flags=int(TF.pending), **kw)


def post(tid, pending_id, void=False):
    flag = TF.void_pending_transfer if void else TF.post_pending_transfer
    return hz.transfer(tid, pending_id=pending_id, flags=int(flag))


def chain(tid, legs, dr=9, cr=10, amount=5):
    return [hz.transfer(tid + k, debit_account_id=dr, credit_account_id=cr,
                        amount=amount,
                        flags=int(TF.linked) if k < legs - 1 else 0)
            for k in range(legs)]


# ---------------------------------------------------------------------------
# Coalesced prepares of the switch's traffic, held to the CPU oracle.


@pytest.mark.parametrize("seed", [5, 44, 2**31 + 4401])
def test_coalesced_prepares_of_mixed_classes_equal_the_oracle(seed):
    """Four sessions' requests of 100 rows, a prepare each round of
    four, coalesced in an order drawn from the seed: pendings, chains
    (a tenth failing) and finalisers (a twentieth naming a pending
    already finalised), in every mix a prepare can hold."""
    gen = chains2p.make(PARAMS, {"accounts": 256, "ledger": 1}, seed)
    h_d, h_c = pair()
    both(h_d, h_c, Operation.create_accounts, gen.accounts().tobytes())
    rng = np.random.default_rng(seed)
    at = [0] * 4
    ids, codes = [], []
    for _round in range(12):
        rows = []
        for s in rng.permutation(4):
            rows.append(gen.request(int(s), at[s]))
            at[s] += 1
        batch = np.concatenate(rows)
        reply = both(h_d, h_c, Operation.create_transfers, batch.tobytes())
        ids.extend(batch["id_lo"].tolist())
        codes.append(codes_of(reply, len(batch)))
    c = counters(h_d)
    assert c["plan.cut_prepares"] >= 8
    assert c["plan.runs"] > 12 + c["plan.cut_prepares"]
    assert c["dev.kind.waves.batches"] == c["dev_wave.batches"] == 0
    assert c["host_semantic_events"] == 0
    codes = np.concatenate(codes)
    # Failed chains and second finalises are in it.
    assert (codes == int(CTR.linked_event_failed)).any()
    assert np.isin(codes, [int(CTR.pending_transfer_already_posted),
                           int(CTR.pending_transfer_already_voided)]).any()
    same_state(h_d, h_c, list(range(1, 257)), ids[::7] + ids[-50:])
    h_d.sm.verify_device_mirror()


def test_pipelined_prepares_of_mixed_classes_equal_the_oracle(monkeypatch):
    """The same traffic with the prepares pipelined (a window of four
    records): runs of later prepares queue behind earlier ones."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    gen = chains2p.make(PARAMS, {"accounts": 256, "ledger": 1}, 9)
    h_d, h_c = pair()
    both(h_d, h_c, Operation.create_accounts, gen.accounts().tobytes())
    rng = np.random.default_rng(9)
    at = [0] * 4
    bodies = []
    for _round in range(10):
        rows = []
        for s in rng.permutation(4):
            rows.append(gen.request(int(s), at[s]))
            at[s] += 1
        bodies.append(np.concatenate(rows).tobytes())
    futures = [h_d.submit_async(Operation.create_transfers, b) for b in bodies]
    for body, fut in zip(bodies, futures):
        assert fut.result() == h_c.submit(Operation.create_transfers, body)
    assert counters(h_d)["plan.cut_prepares"] >= 6
    assert counters(h_d)["host_semantic_events"] == 0
    same_state(h_d, h_c, list(range(1, 257)), [])


# ---------------------------------------------------------------------------
# Where the cut falls, and where it does not apply.


def test_a_prepare_of_one_class_takes_no_cut():
    h_d, h_c = pair()
    setup_accounts(h_d, h_c)
    was = counters(h_d)
    rows = [pending(100 + i, amount=1 + i) for i in range(20)]
    both(h_d, h_c, Operation.create_transfers, hz.pack(rows))
    now = counters(h_d)
    assert now["plan.runs"] == was["plan.runs"] + 1
    assert now["plan.cut_prepares"] == was["plan.cut_prepares"]


def test_the_cut_falls_right_after_a_chains_last_leg():
    """A chain of three legs on accounts under a limit, then pendings:
    the linked kernel takes the chain, orderfree the pendings, and the
    cut falls after the chain's last leg, never inside it."""
    legs = chain(200, 3)
    rows = legs + [pending(300 + i) for i in range(4)]
    events = np.frombuffer(hz.pack(rows), types.TRANSFER_DTYPE)
    n = len(events)
    acct = np.zeros(n, np.uint32)
    acct[:3] = LIMIT
    runs = tpu_mod._cut_runs(
        np.asarray(events["flags"]), acct, np.asarray(events["amount_hi"]),
        events["timeout"].astype(np.uint64))
    assert runs == [(0, 3, "linked"), (3, 7, "orderfree")]
    h_d, h_c = pair()
    setup_accounts(h_d, h_c)
    was = counters(h_d)
    assert both(h_d, h_c, Operation.create_transfers, hz.pack(rows)) == b""
    now = counters(h_d)
    assert now["plan.runs"] - was["plan.runs"] == 2
    assert now["plan.cut_prepares"] - was["plan.cut_prepares"] == 1
    assert now["host_semantic_events"] == 0
    same_state(h_d, h_c, list(range(1, 13)), [200, 201, 202, 300, 303])


def test_a_chain_is_never_split_and_an_open_chain_stays_last():
    """Chains whose legs alternate what they hold: a chain with a
    pending inside is no kernel's (the batch keeps today's path); an
    open chain at the end stays in the last run and answers
    linked_event_chain_open as it would whole."""
    rows = [pending(10)] + chain(20, 2) + [post(30, 10)] + chain(40, 2)[:1]
    events = np.frombuffer(hz.pack(rows), types.TRANSFER_DTYPE)
    n = len(events)
    acct = np.zeros(n, np.uint32)
    runs = tpu_mod._cut_runs(
        np.asarray(events["flags"]), acct, np.asarray(events["amount_hi"]),
        events["timeout"].astype(np.uint64))
    assert runs == [(0, 1, "orderfree"), (1, 3, "linked"),
                    (3, 4, "two_phase"), (4, 5, "linked")]
    h_d, h_c = pair()
    setup_accounts(h_d, h_c)
    reply = both(h_d, h_c, Operation.create_transfers, hz.pack(rows))
    assert codes_of(reply, n)[-1] == int(CTR.linked_event_chain_open)
    assert counters(h_d)["host_semantic_events"] == 0
    # A pending inside a chain: no kernel takes that chain even alone.
    inside = chain(50, 2)
    inside[0]["flags"] |= int(TF.pending)
    events = np.frombuffer(hz.pack(inside + [post(60, 10)]), types.TRANSFER_DTYPE)
    assert tpu_mod._cut_runs(
        np.asarray(events["flags"]), np.zeros(3, np.uint32),
        np.asarray(events["amount_hi"]),
        events["timeout"].astype(np.uint64)) is None


def test_a_finaliser_run_waits_for_the_run_that_made_its_pending(monkeypatch):
    """Pendings, a chain, then posts and voids of those pendings, in
    one prepare: the finalisers' run is planned once the pendings' run
    has resolved (the hazard probe drains), so its join finds them."""
    h_d, h_c = pair()
    setup_accounts(h_d, h_c)
    drains = []
    engine_drain = h_d.sm._engine_drain
    monkeypatch.setattr(h_d.sm, "_engine_drain",
                        lambda: (drains.append(1), engine_drain())[1])
    rows = ([pending(500 + i, amount=20 + i) for i in range(4)] + chain(600, 3)
            + [post(700, 500), post(701, 501, void=True), post(702, 502),
               post(703, 500)])
    was = counters(h_d)
    reply = both(h_d, h_c, Operation.create_transfers, hz.pack(rows))
    codes = codes_of(reply, len(rows))
    assert list(codes[-4:]) == [0, 0, 0, int(CTR.pending_transfer_already_posted)]
    now = counters(h_d)
    assert now["plan.runs"] - was["plan.runs"] == 3
    assert drains and now["host_semantic_events"] == 0
    same_state(h_d, h_c, list(range(1, 13)), [500, 501, 502, 503, 700, 701, 703])


def test_a_duplicate_id_across_two_runs_keeps_todays_path():
    """An id that a pending of one run and a chain leg of another both
    carry: the batch is not unique, so no cut; the leg answers as the
    oracle says (an `exists` code) through today's path."""
    h_d, h_c = pair()
    setup_accounts(h_d, h_c)
    rows = [pending(800)] + chain(810, 2) + [post(820, 800)]
    rows[2] = hz.transfer(800, debit_account_id=9, credit_account_id=10, amount=5)
    was = counters(h_d)
    reply = both(h_d, h_c, Operation.create_transfers, hz.pack(rows))
    assert codes_of(reply, len(rows))[2] in {
        int(c) for c in CTR if c.name.startswith("exists")}
    assert counters(h_d)["plan.cut_prepares"] == was["plan.cut_prepares"]
    same_state(h_d, h_c, list(range(1, 13)), [800, 810, 820])


def test_a_pending_on_an_account_with_a_limit_keeps_todays_path():
    """A run only the host can take (a pending on an account under a
    limit): the whole batch takes the path it took before the cut."""
    h_d, h_c = pair()
    setup_accounts(h_d, h_c)
    rows = [pending(900, dr=9, cr=1)] + chain(910, 2) + [post(920, 900)]
    was = counters(h_d)
    both(h_d, h_c, Operation.create_transfers, hz.pack(rows))
    now = counters(h_d)
    assert now["plan.cut_prepares"] == was["plan.cut_prepares"]
    assert now["plan.runs"] == was["plan.runs"]
    same_state(h_d, h_c, list(range(1, 13)), [900, 910, 911, 920])


def test_a_later_run_no_kernel_plans_leaves_the_rest_to_todays_path():
    """A post of a durable pending that carries a timeout: two_phase
    declines that run's join, so the runs before it stay on the chip
    and the rest of the batch goes as a prepare of its own would."""
    h_d, h_c = pair()
    setup_accounts(h_d, h_c)
    both(h_d, h_c, Operation.create_transfers,
         hz.pack([pending(1000, timeout=3600)]))
    rows = chain(1010, 3) + [post(1020, 1000), pending(1021)]
    was = counters(h_d)
    both(h_d, h_c, Operation.create_transfers, hz.pack(rows))
    now = counters(h_d)
    assert now["plan.cut_prepares"] - was["plan.cut_prepares"] == 1
    assert now["plan.runs"] - was["plan.runs"] == 1
    assert now["host_semantic_events"] - was["host_semantic_events"] <= 2
    same_state(h_d, h_c, list(range(1, 13)), [1000, 1010, 1012, 1020, 1021])


def test_a_prepare_reply_moves_each_runs_indexes_on():
    def reply(*pairs):
        out = np.zeros(len(pairs), types.CREATE_RESULT_DTYPE)
        out["index"] = [i for i, _c in pairs]
        out["result"] = [c for _i, c in pairs]
        return out.tobytes()

    runs = [(0, de.ReplyFuture(value=reply((1, 7)))),
            (5, de.ReplyFuture(value=b"")),
            (9, de.ReplyFuture(value=reply((0, 3), (2, 4))))]
    got = de.PrepareReply(runs)
    assert got.done()
    assert got.result() == reply((1, 7), (9, 3), (11, 4))
    assert not de.PrepareReply([(0, de.ReplyFuture())]).done()
