"""The linked kernels past their fixpoint's rounds: batches whose limit
checks have not settled when the rounds stop, at FIXPOINT_CAP or at the
gate that weighs them against the pass, are decided exactly on the
device by the pass by blocks (`device_kernels._settle_blocks`), and
batches that settle in their rounds take the path they always took.

The served cases are differentials against `CpuStateMachine`: the
replies, then every account row and every transfer the batches named.
The kernel's own cases hold the gate to the cap alone: the same table,
codes and summary, but for the flags word's iterations and settle bits.
"""

import functools
import json
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from benchmarks.harness.gen import hub_zipf  # noqa: E402
from tigerbeetle_tpu.state_machine import device_engine  # noqa: E402
from tigerbeetle_tpu.state_machine import device_kernels as dk  # noqa: E402
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine  # noqa: E402
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine  # noqa: E402
from tigerbeetle_tpu.testing import harness as hz  # noqa: E402
from tigerbeetle_tpu.types import (  # noqa: E402
    ACCOUNT_DTYPE, TRANSFER_DTYPE, AccountFlags, Operation, TransferFlags)

AF, TF = AccountFlags, TransferFlags
LIMIT = int(AF.debits_must_not_exceed_credits)
FREE = 9_000          # an account with no flag, the fillers' debtor
SINK = 9_001


def _relay(depth: int, chain_len: int = 1, fillers: int = 2, linked=(),
           filler_amount: int = 3):
    """A relay of `depth` limit accounts, none funded: event k of the
    relay debits participant k and credits participant k + 1, so the
    first fails and each then fails only because the one before it did.
    Jacobi rounds learn one link a round: depth + 1 of them.  Between
    the relay's events, `fillers` plain transfers between accounts
    without limits, which pass.  With `chain_len` > 1 the relay's and
    the fillers' rows go in linked chains of that length, laid across
    every block boundary; the rows in `linked` carry the flag besides.
    Fillers of 2^40 take the batch past 2^31 in all, to `linked`.
    -> (the accounts' body, the transfers' body)"""
    accounts = ([hz.account(k + 1, flags=LIMIT) for k in range(depth + 1)]
                + [hz.account(FREE), hz.account(SINK)])
    rows, tid = [], 1000
    for k in range(depth):
        rows.append(dict(id=tid, debit_account_id=k + 1,
                         credit_account_id=k + 2, amount=1))
        tid += 1
        for _ in range(fillers):
            rows.append(dict(id=tid, debit_account_id=FREE,
                             credit_account_id=SINK, amount=filler_amount))
            tid += 1
    rows = rows[:dk.B]
    for i, r in enumerate(rows):
        if i in linked or (chain_len > 1 and i % chain_len != chain_len - 1
                           and i != len(rows) - 1):
            r["flags"] = int(TF.linked)
    return hz.pack(accounts), hz.pack([hz.transfer(**r) for r in rows])


def _served(accounts, bodies, kernel=None, monkeypatch=None,
            kind="linked_small"):
    """-> (device harness, replies): the accounts and then every batch
    committed on the device engine and on the oracle, one at a time,
    every reply equal; then every account and transfer looked up on
    both, equal too."""
    if kernel is not None:
        monkeypatch.setitem(device_engine._KERNELS, kind, kernel)
    h_d = hz.SingleNodeHarness(
        TpuStateMachine(engine="device", account_capacity=1 << 12))
    h_c = hz.SingleNodeHarness(CpuStateMachine())
    replies = []
    for op, body in [(Operation.create_accounts, accounts)] + [
            (Operation.create_transfers, b) for b in bodies]:
        got = h_d.submit(op, body)
        assert got == h_c.submit(op, body)
        replies.append(got)
    assert replies.pop(0) == b""
    ids = [hz.ids_bytes(np.frombuffer(body, ACCOUNT_DTYPE)["id_lo"].tolist())]
    ids += [hz.ids_bytes(np.frombuffer(b, TRANSFER_DTYPE)["id_lo"].tolist())
            for b in bodies]
    for op, body in zip([Operation.lookup_accounts] + [Operation.lookup_transfers]
                        * len(bodies), ids):
        assert h_d.submit(op, body) == h_c.submit(op, body)
    h_d.sm.verify_device_mirror()
    return h_d, replies


def _snap(h_d) -> dict:
    snap = h_d.sm.metrics.snapshot()
    return {k: snap.get(f"dev.{k}", 0) for k in (
        "linked.settle_batches", "linked.settle_rows", "linked.gate_exits",
        "linked.settle_rounds.count", "linked.settle_rounds.sum",
        "linked.iters.count", "linked.iters.max", "fallback_batches",
        "kind.linked_small.batches", "kind.linked.batches")}


@functools.lru_cache(maxsize=None)
def _blocks(block, kind="linked_small", gate=True):
    name = f"{kind}_b{block}" + ("" if gate else "_cap")
    return dk._jit_as(name, functools.partial(
        dk._linked, small=kind == "linked_small", block=block, gate=gate))


def _packed(accounts, body):
    """The batch `body` over `accounts` (in slots 0, 1, ...) packed as
    the engine packs it.  -> (table, meta, packed input)"""
    a = np.frombuffer(accounts, ACCOUNT_DTYPE)
    t = np.frombuffer(body, TRANSFER_DTYPE)
    slot = {int(i): k for k, i in enumerate(a["id_lo"])}
    dr = np.array([slot[int(i)] for i in t["debit_account_id_lo"]], np.int64)
    cr = np.array([slot[int(i)] for i in t["credit_account_id_lo"]], np.int64)
    n = len(t)
    zero = np.zeros(n, np.uint64)
    ones = np.ones(n, np.uint32)
    pk = dk.seal_scalars(dk.pack_base(
        n, t["id_lo"].copy(), zero, (dr + 1).astype(np.uint64), zero,
        (cr + 1).astype(np.uint64), zero, zero, zero, t["amount_lo"].copy(),
        zero, t["flags"].astype(np.uint32), ones, ones,
        np.zeros(n, np.uint32), np.zeros(n, bool), dr, cr,
        np.zeros(n, bool)), n, 1 << 40)
    table = np.zeros((512, 8), np.uint64)
    meta = np.zeros((512, 2), np.uint32)
    meta[:, 1] = 1                  # the ledger
    meta[:len(a), 0] = a["flags"]
    return table, meta, pk


def _both_ways(block, kind, table, meta, pk):
    """-> (gate on, cap alone): each (table, summary row, codes)."""
    import jax

    return [jax.device_get(_blocks(block, kind, gate)(table, meta, pk))
            for gate in (True, False)]


@pytest.mark.parametrize("depth,chain_len,block,kind", [
    (65, 1, dk.SETTLE_BLOCK, "linked_small"),
    (300, 1, dk.SETTLE_BLOCK, "linked_small"),
    (300, 1, 64, "linked_small"),
    (120, 5, 128, "linked_small"),
    (512, 7, 64, "linked_small"),
    (300, 3, 32, "linked_small"),
    (120, 1, dk.SETTLE_BLOCK, "linked"),
    (120, 5, 64, "linked"),
])
def test_a_relay_past_the_cap_is_decided_on_the_device(
        monkeypatch, depth, chain_len, block, kind):
    accounts, body = _relay(depth, chain_len, fillers=0 if depth > 150 else 2,
                            filler_amount=1 << 40 if kind == "linked" else 3)
    h_d, (reply,) = _served(
        accounts, [body],
        None if block == dk.SETTLE_BLOCK else _blocks(block, kind), monkeypatch,
        kind)
    rows = np.frombuffer(body, TRANSFER_DTYPE)
    s = _snap(h_d)
    assert reply    # the relay's failures, as the oracle's
    assert s[f"kind.{kind}.batches"] == 1
    assert s["fallback_batches"] == 0 and h_d.sm.stat_fallback_events == 0
    assert s["linked.settle_batches"] == 1
    assert s["linked.settle_rows"] == len(rows)
    assert s["linked.iters.max"] < dk.FIXPOINT_CAP
    assert s["linked.gate_exits"] == s["linked.settle_batches"]
    assert s["linked.settle_rounds.count"] == 1
    assert s["linked.settle_rounds.sum"] > 0
    assert h_d.sm._dev.stat_semantic_events == len(rows)


def test_a_chain_longer_than_a_block_leaves_the_batch_to_the_host(monkeypatch):
    """The pass meets a chain it cannot fit in one block: the batch is
    flagged and re-executed on the host, exactly, as every batch past
    the cap was before the pass."""
    accounts, body = _relay(300, fillers=0, linked=range(250, 289))
    h_d, _replies = _served(accounts, [body], _blocks(32), monkeypatch)
    s = _snap(h_d)
    assert s["fallback_batches"] == 1 and s["linked.settle_batches"] == 0


def test_a_chain_longer_than_a_block_keeps_the_gate_shut(monkeypatch):
    """A relay of 40 among fillers with a chain of 41 events in its
    middle, blocks of 32: the gate would hand it to a pass that leaves
    the chain to the host, so the rounds run on and settle it on the
    device, as the cap alone does: the same flags word."""
    accounts, body = _relay(40, linked=range(30, 70))
    h_d, _replies = _served(accounts, [body], _blocks(32), monkeypatch)
    s = _snap(h_d)
    assert s["linked.iters.max"] > 2 and s["fallback_batches"] == 0
    assert s["linked.settle_batches"] == s["linked.gate_exits"] == 0
    on, cap = _both_ways(32, "linked_small", *_packed(accounts, body))
    for a, b in zip(on, cap):
        assert (a == b).all()
    assert dk.unpack_summary(on[1], on[2])["iters"] == s["linked.iters.max"]


def _zipf_batches(requests: int):
    """The `hub_zipf` generator at the cell's rehearsal size."""
    root = os.path.join(_REPO, "benchmarks")
    config = json.load(open(os.path.join(root, "configs", "hub100-zipf-1r.json")))
    traffic = json.load(open(os.path.join(root, "traffic", "zipf-c4.json")))
    config = {**config, **config["rehearsal"]}
    traffic = {**traffic, **traffic["rehearsal"]}
    gen = hub_zipf.make(traffic, config, 2**31 + 41)
    return gen.accounts().tobytes(), [gen.request(0, i).tobytes()
                                      for i in range(requests)]


def test_zipf_batches_of_the_cells_rehearsal_are_decided_on_the_device():
    accounts, batches = _zipf_batches(6)
    h_d, replies = _served(accounts, batches)
    s = _snap(h_d)
    assert replies[0] == b""                  # the prefunding
    assert all(len(r) for r in replies[1:])   # exceeds_credits in each
    assert s["fallback_batches"] == 0
    assert s["linked.settle_batches"] >= 1
    assert s["linked.gate_exits"] == s["linked.settle_batches"]
    assert s["linked.settle_rows"] == s["linked.settle_batches"] * len(
        np.frombuffer(batches[1], TRANSFER_DTYPE))


@pytest.mark.parametrize("block", [dk.SETTLE_BLOCK, 32])
def test_a_batch_that_settles_within_the_cap_takes_the_path_it_took(
        monkeypatch, block):
    """A relay of 1 among 119 fillers, in chains of 3, settles in 2
    rounds, before the gate is consulted.  Its summary carries the
    iterations alone (no settle bit, no settle rounds, no
    precondition), the pass's counters stay at 0, and the block size,
    which only the pass and the gate read, changes nothing the batch
    returns."""
    accounts, body = _relay(1, chain_len=3, fillers=119)
    h_d, _r = _served(accounts, [body],
                      None if block == dk.SETTLE_BLOCK else _blocks(block),
                      monkeypatch)
    s = _snap(h_d)
    assert s["linked.iters.max"] == 2 and s["linked.iters.count"] == 1
    assert s["linked.settle_batches"] == s["linked.settle_rows"] == 0
    assert s["linked.gate_exits"] == 0
    assert s["linked.settle_rounds.count"] == 0 and s["fallback_batches"] == 0


@pytest.mark.parametrize("block", [dk.SETTLE_BLOCK, 32])
def test_a_relay_of_40_leaves_its_rounds_at_the_gate(monkeypatch, block):
    """A relay of 40 in chains of 3 would settle in 41 rounds; after 2
    the pass over the 117 events left is one block of 128 (four of 32),
    which costs less than the 2 rounds run, so the gate hands it over:
    the oracle's replies and rows, and the codes and table of the cap
    alone."""
    accounts, body = _relay(40, chain_len=3)
    h_d, _r = _served(accounts, [body],
                      None if block == dk.SETTLE_BLOCK else _blocks(block),
                      monkeypatch)
    s = _snap(h_d)
    assert s["linked.iters.max"] == 2 and s["linked.iters.count"] == 1
    assert s["linked.settle_batches"] == s["linked.gate_exits"] == 1
    assert s["fallback_batches"] == 0
    on, cap = _both_ways(block, "linked_small", *_packed(accounts, body))
    assert dk.unpack_summary(on[1], on[2])["iters"] == 2
    assert dk.unpack_summary(cap[1], cap[2])["iters"] == 41
    assert (on[0] == cap[0]).all() and (on[2] == cap[2]).all()


def test_the_gate_returns_what_the_cap_returned():
    """One packed batch of 120 that settles in 2 rounds (one limit
    account debited, unfunded) through the kernel at two block sizes:
    the same table, summary row and codes, and the flags word holds
    the iterations alone."""
    import jax

    n = 120
    slots = np.arange(n, dtype=np.int64)
    dr = np.where(slots == 0, 0, 200)
    cr = np.where(slots == 0, 1, 201)
    zero = np.zeros(n, np.uint64)
    ids = np.arange(1, n + 1, dtype=np.uint64)
    pk = dk.seal_scalars(dk.pack_base(
        n, ids, zero, (dr + 1).astype(np.uint64), zero,
        (cr + 1).astype(np.uint64), zero, zero, zero,
        np.ones(n, np.uint64), zero, np.zeros(n, np.uint32),
        np.ones(n, np.uint32), np.ones(n, np.uint32), np.zeros(n, np.uint32),
        np.zeros(n, bool), dr, cr, np.zeros(n, bool)), n, 1 << 40)
    table = np.zeros((256, 8), np.uint64)
    meta = np.ones((256, 2), np.uint32)
    meta[:, 0] = np.where(np.arange(256) < 200, dk.AF_DR_LIMIT, 0)
    outs = [jax.device_get(_blocks(b)(table, meta, pk)) for b in (32, 256)]
    for a, b in zip(*outs):
        assert (a == b).all()
    row = dk.unpack_summary(outs[0][1], outs[0][2])
    assert row["iters"] == 2 and not row["settled"] and not row["precond"]
    assert int(outs[0][1][1]) == 2 << dk.ITERS_SHIFT
    assert row["n_fail"] == 1


# The flags word's bits that only say which path the batch took: the
# settle flag, and every bit from the iterations up (the pass's rounds
# lie above them).
_PATH_BITS = dk.FLAG_SETTLED | -(1 << dk.ITERS_SHIFT)


@pytest.mark.parametrize("block", [32, 128])
@pytest.mark.parametrize("kind", ["linked_small", "linked"])
@pytest.mark.parametrize("chain_len", [1, 3, 5])
@pytest.mark.parametrize("depth", [3, 40, 65, 300])
def test_the_gate_and_the_cap_alone_decide_alike(depth, chain_len, kind, block):
    """The relay family through the kernel with the gate and with the
    cap alone: the same table, codes, failure entries and last applied
    event; the flags words differ in the iterations and settle bits
    alone, and the gate never runs more rounds."""
    accounts, body = _relay(depth, chain_len, fillers=0 if depth > 150 else 2,
                            filler_amount=1 << 40 if kind == "linked" else 3)
    on, cap = _both_ways(block, kind, *_packed(accounts, body))
    assert (on[0] == cap[0]).all() and (on[2] == cap[2]).all()
    assert on[1][0] == cap[1][0] and (on[1][2:] == cap[1][2:]).all()
    flags_on, flags_cap = int(on[1][1]), int(cap[1][1])
    assert (flags_on ^ flags_cap) & ~_PATH_BITS == 0
    assert not flags_on & (dk.FLAG_PRECOND | dk.FLAG_OVERFLOW)
    got, want = dk.unpack_summary(on[1], on[2]), dk.unpack_summary(cap[1], cap[2])
    assert got["n_fail"] > 0
    # The gate opens by the round the pass over the whole batch costs.
    rows = len(np.frombuffer(body, TRANSFER_DTYPE))
    assert got["iters"] <= min(want["iters"], max(2, -(-rows // (block * dk.ROUND_BLOCKS))))
