"""Device-authoritative engine: differential + hazard + fallback tests.

The engine (state_machine/device_engine.py) computes create_transfers
result codes ON the device via the semantic kernels and materializes
replies from failure-sparse summaries.  These tests pin it to the CPU
oracle across the five batch classes and adversarial cases:
cross-batch hazards, fallback recovery, pulse interaction, and the
checkpoint checksum tripwire.
"""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.testing import workloads
from tigerbeetle_tpu.types import (
    AccountFlags,
    CreateTransferResult,
    Operation,
    TransferFlags,
)

AF = AccountFlags
TF = TransferFlags
CTR = CreateTransferResult


def mk_pair():
    sm_d = TpuStateMachine(engine="device", account_capacity=1 << 12)
    sm_c = CpuStateMachine()
    return hz.SingleNodeHarness(sm_d), hz.SingleNodeHarness(sm_c)


def replay_both(h_d, h_c, ops):
    futs = [h_d.submit_async(op, body) for op, body in ops]
    replies_d = [f.result() for f in futs]
    replies_c = [h_c.submit(op, body) for op, body in ops]
    for i, (a, b) in enumerate(zip(replies_d, replies_c)):
        assert a == b, f"reply {i} differs: {ops[i][0]!r}"
    return replies_d


def accounts(ids, flags=0, ledger=1):
    return hz.pack([hz.account(i, flags=flags, ledger=ledger) for i in ids])


def transfers(rows):
    return hz.pack([hz.transfer(**r) for r in rows])


@pytest.mark.parametrize("name", list(workloads.CONFIGS))
def test_config_differential(name):
    """A scaled-down stream of each batch class, multi-fetch: every
    reply and the final state digest against the oracle."""
    batch = 400
    setup, stream, sizing = workloads.CONFIGS[name](4000, batch)
    ops = setup + stream
    sm_d = TpuStateMachine(
        account_capacity=sizing[0], transfer_capacity=sizing[1],
        engine="device",
    )
    h_d = hz.SingleNodeHarness(sm_d)
    futs = [h_d.submit_async(op, body) for op, body in ops]
    replies_d = [f.result() for f in futs]
    h_c = hz.SingleNodeHarness(CpuStateMachine())
    for i, (op, body) in enumerate(ops):
        assert replies_d[i] == h_c.submit(op, body), f"op {i}"
    acct_ids = workloads.config_account_ids(name)
    tids = np.arange(workloads.TID0, workloads.TID0 + 2000).astype(np.uint64)
    assert workloads.state_digest(
        h_d, acct_ids, tids, batch
    ) == workloads.state_digest(h_c, acct_ids, tids, batch)
    assert sm_d._dev.stat_semantic_events > 0


def test_cross_batch_pending_reference_hazard():
    """A post in batch k+1 referencing a pending created in batch k
    (still in flight) must drain and resolve exactly."""
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts([1, 2]))]
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=10, debit_account_id=1, credit_account_id=2,
                         amount=50, flags=int(TF.pending)),
                ]
            ),
        )
    )
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=11, pending_id=10,
                         flags=int(TF.post_pending_transfer)),
                    dict(id=12, pending_id=10,
                         flags=int(TF.post_pending_transfer)),
                ]
            ),
        )
    )
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2])))
    replay_both(h_d, h_c, ops)


def test_cross_batch_duplicate_id_hazard():
    """A duplicate id against an in-flight batch must not be treated
    as fresh."""
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts([1, 2]))]
    t = dict(id=10, debit_account_id=1, credit_account_id=2, amount=5)
    ops.append((Operation.create_transfers, transfers([t])))
    ops.append((Operation.create_transfers, transfers([t])))  # exact dup
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [dict(id=10, debit_account_id=1, credit_account_id=2,
                      amount=6)]
            ),
        )
    )
    replay_both(h_d, h_c, ops)


def _overflow_ops():
    big = (1 << 127) + 5
    ops = [(Operation.create_accounts, accounts([1, 2, 3]))]
    # Two debits of ~2^127 on the same account: the second overflows
    # debits_posted, so total-sum admission must refuse the batch.
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=10, debit_account_id=1, credit_account_id=2,
                         amount=big),
                    dict(id=11, debit_account_id=1, credit_account_id=3,
                         amount=big),
                ]
            ),
        )
    )
    # Later clean batch must still be exact after recovery.
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [dict(id=12, debit_account_id=1, credit_account_id=3,
                      amount=7)]
            ),
        )
    )
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2, 3])))
    return ops


def test_fallback_overflow_orderfree():
    """Amounts near 2^128 trip the admission check -> exact host
    fallback, still bit-identical to the oracle."""
    h_d, h_c = mk_pair()
    replay_both(h_d, h_c, _overflow_ops())
    assert h_d.sm._dev.stat_fallback_batches >= 1


def test_fallback_recovery_redispatches_inflight(monkeypatch):
    """Batches dispatched AFTER one that falls back are re-executed
    against the corrected table."""
    import tigerbeetle_tpu.state_machine.device_engine as de

    monkeypatch.setattr(de, "_WINDOW", 64)
    h_d, h_c = mk_pair()
    big = (1 << 127) + 5
    ops = [(Operation.create_accounts, accounts([1, 2, 3]))]
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=10, debit_account_id=1, credit_account_id=2,
                         amount=big),
                    dict(id=11, debit_account_id=1, credit_account_id=3,
                         amount=big),
                ]
            ),
        )
    )
    for k in range(4):
        ops.append(
            (
                Operation.create_transfers,
                transfers(
                    [dict(id=20 + k, debit_account_id=1,
                          credit_account_id=3, amount=3 + k)]
                ),
            )
        )
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2, 3])))
    replay_both(h_d, h_c, ops)
    assert h_d.sm._dev.stat_fallback_batches >= 1


def test_fallback_recovery_reentrant_drain(monkeypatch):
    """Recovery's host fallback re-enters drain() via table reads
    (JAX host path, no native fastpath); the recovering window must
    not be visible as launched to the nested rotate, or its futures
    double-resolve and mirror bookkeeping double-applies — the
    code-review repro for the _recovering detach."""
    import tigerbeetle_tpu.state_machine.device_engine as de

    monkeypatch.setattr(de, "_WINDOW", 64)
    big = (1 << 127) + 5
    h_d, h_c = mk_pair()
    h_d.sm._native = None  # fallbacks take the JAX host path -> read()
    ops = [(Operation.create_accounts, accounts([1, 2, 3]))]
    for k in range(3):
        ops.append(
            (
                Operation.create_transfers,
                transfers(
                    [dict(id=10 + k, debit_account_id=1,
                          credit_account_id=2, amount=big)]
                ),
            )
        )
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2, 3])))
    replay_both(h_d, h_c, ops)
    assert h_d.sm._dev.stat_fallback_batches >= 1


def test_recovery_with_pending_window_stays_ordered(monkeypatch):
    """A full PENDING window queued behind a dirty one must not be
    launched by the recovery fallback's re-entrant drain — it would
    execute out of submission order against a table recovery is about
    to rebuild (and a nested dirty rotation would clobber the
    recovery slot)."""
    import tigerbeetle_tpu.state_machine.device_engine as de

    monkeypatch.setattr(de, "_WINDOW", 4)
    big = (1 << 127) + 5
    h_d, h_c = mk_pair()
    h_d.sm._native = None  # fallbacks take the JAX host path -> read()
    ops = [(Operation.create_accounts, accounts([1, 2, 3]))]
    amounts = [big, big] + [3 + k for k in range(9)]
    for k, amount in enumerate(amounts):
        ops.append(
            (
                Operation.create_transfers,
                transfers(
                    [dict(id=10 + k, debit_account_id=1,
                          credit_account_id=2 + k % 2, amount=amount)]
                ),
            )
        )
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2, 3])))
    replay_both(h_d, h_c, ops)
    assert h_d.sm._dev.stat_fallback_batches >= 1
    assert h_d.sm._dev.stat_demotions == 0
    h_d.sm.verify_device_mirror()


def _cap_ops():
    ops = [(Operation.create_accounts, accounts([1, 2]))]
    rows = [
        dict(id=100 + i, debit_account_id=1, credit_account_id=1, amount=1)
        for i in range(100)  # accounts_must_be_different x100 > cap 60
    ]
    ops.append((Operation.create_transfers, transfers(rows)))
    return ops


def test_more_failures_than_the_row_holds_come_home_dense():
    """More failures than the summary row's 60 entries -> the batch's
    dense result codes cross too (B x u32) and the device's verdicts
    stand: no host re-execution, no rebuilt table."""
    from tigerbeetle_tpu.state_machine import device_kernels as dk

    h_d, h_c = mk_pair()
    dev = h_d.sm._dev
    bytes0 = dev.stat_fetch_bytes
    replay_both(h_d, h_c, _cap_ops())
    assert dev.stat_fallback_batches == 0 and h_d.sm.stat_fallback_events == 0
    assert dev.stat_dense_fetches == 1
    assert dev.stat_fetch_bytes - bytes0 == 512 + 4 * dk.B
    assert dev.stat_semantic_events == 100
    h_d.sm.verify_device_mirror()


def _chain_rows(first_id: int, chains: int, poor_every: int):
    """`chains` chains of three legs over accounts 2..9; every
    `poor_every`th chain debits the never-funded limit account 1 in
    its middle leg: that leg answers exceeds_credits, the others
    linked_event_failed."""
    rows, tid = [], first_id
    for c in range(chains):
        for leg in range(3):
            poor = c % poor_every == poor_every - 1 and leg == 1
            rows.append(dict(
                id=tid, debit_account_id=1 if poor else 2 + (c + leg) % 8,
                credit_account_id=2 + (c + leg + 1) % 8, amount=1 + leg,
                flags=int(TF.linked) if leg < 2 else 0))
            tid += 1
    return rows


def _many_failures_linked(chains_failing: int):
    ops = [(Operation.create_accounts,
            accounts([1], flags=int(AF.debits_must_not_exceed_credits))
            + accounts(range(2, 10)))]
    ops.append((Operation.create_transfers, transfers(
        _chain_rows(100, 4 * chains_failing, 4) if chains_failing
        else _chain_rows(100, 8, 10**9))))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 10)))))
    ops.append((Operation.lookup_transfers, hz.ids_bytes(list(range(100, 160)))))
    return ops


def _many_failures_two_phase(again: int):
    """`again` pendings, all posted, then posted or voided a second
    time in one batch: every row of it answers already_posted."""
    ops = [(Operation.create_accounts, accounts([1, 2]))]
    ops.append((Operation.create_transfers, transfers(
        [dict(id=100 + i, debit_account_id=1, credit_account_id=2,
              amount=5 + i, flags=int(TF.pending)) for i in range(again)])))
    ops.append((Operation.create_transfers, transfers(
        [dict(id=300 + i, pending_id=100 + i,
              flags=int(TF.post_pending_transfer)) for i in range(again)])))
    ops.append((Operation.create_transfers, transfers(
        [dict(id=500 + i, pending_id=100 + i,
              flags=int(TF.void_pending_transfer if i % 3 else
                        TF.post_pending_transfer)) for i in range(again)]
        + [dict(id=900, debit_account_id=1, credit_account_id=2, amount=1)])))
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2])))
    return ops


@pytest.mark.parametrize("ops_of,failures,kind", [
    (lambda: _many_failures_linked(30), 90, "linked_small"),
    (lambda: _many_failures_two_phase(80), 80, "two_phase_lo"),
    (_cap_ops, 100, "orderfree_tight"),
])
def test_a_batch_of_many_failures_resolves_on_the_device(ops_of, failures, kind):
    """`_summary` is one function: a linked, a two-phase and an
    order-free batch with more than FAIL_CAP failures each resolve
    from their dense codes, replies identical to the oracle's."""
    from tigerbeetle_tpu.state_machine import device_kernels as dk

    assert failures > dk.FAIL_CAP
    h_d, h_c = mk_pair()
    ops = ops_of()
    replies = replay_both(h_d, h_c, ops)
    assert max(len(r) // 8 for (op, _body), r in zip(ops, replies)
               if op == Operation.create_transfers) >= failures
    dev = h_d.sm._dev
    assert dev.stat_fallback_batches == 0 and h_d.sm.stat_fallback_events == 0
    assert dev.stat_dense_fetches == 1
    snap = h_d.sm.metrics.snapshot()
    assert snap[f"dev.kind.{kind}.batches"] >= 1
    assert sum(v for k, v in snap.items()
               if k.startswith("dev.kind.") and k.endswith(".events")
               ) == dev.stat_semantic_events
    h_d.sm.verify_device_mirror()


@pytest.mark.parametrize("failing", [0, 1, 20])
def test_a_batch_of_few_failures_crosses_512_bytes_as_before(failing):
    """Up to FAIL_CAP failures (20 failing chains of 3 legs = 60) the
    sparse row is the only crossing."""
    h_d, h_c = mk_pair()
    dev = h_d.sm._dev
    ops = _many_failures_linked(failing)
    replay_both(h_d, h_c, ops[:1])
    bytes0 = dev.stat_fetch_bytes
    replies = replay_both(h_d, h_c, ops[1:2])
    assert len(replies[0]) // 8 == 3 * failing
    assert dev.stat_fetch_bytes - bytes0 == 512
    assert dev.stat_dense_fetches == 0 and dev.stat_fallback_batches == 0
    assert h_d.sm.metrics.snapshot()["dev.linked.iters.count"] == 1


def _precond_ops():
    huge = 1 << 62
    ops = [
        (
            Operation.create_accounts,
            accounts([1], flags=int(AF.debits_must_not_exceed_credits))
            + accounts([2, 3]),
        )
    ]
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [dict(id=5, debit_account_id=2, credit_account_id=1,
                      amount=huge)]
            ),
        )
    )
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=10, debit_account_id=1, credit_account_id=2,
                         amount=10, flags=int(TF.linked)),
                    dict(id=11, debit_account_id=1, credit_account_id=3,
                         amount=20),
                ]
            ),
        )
    )
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2, 3])))
    return ops


def test_linked_precondition_fallback():
    """Limit accounts with u128-scale balances exceed the fixpoint's
    u64-safety precondition -> device flags, host decides."""
    h_d, h_c = mk_pair()
    replay_both(h_d, h_c, _precond_ops())


@pytest.mark.parametrize("flag,ops_of", [
    ("FLAG_OVERFLOW", _overflow_ops),
    ("FLAG_PRECOND", _precond_ops),
])
def test_a_flagged_window_recovers_from_its_own_rows(monkeypatch, flag, ops_of):
    """The flag reaches the host in the window's own fetched row (an
    output of the kernel: no ring to index), and that row is what
    sends the window through _resolve_recovery."""
    from tigerbeetle_tpu.state_machine import device_engine as de
    from tigerbeetle_tpu.state_machine import device_kernels as dk

    seen = []
    real = de.DeviceEngine._resolve_recovery

    def spy(self, covered):
        for rec in covered:
            if rec.kind in de._SEMANTIC_KINDS:
                assert rec.out.rows.shape[1:] == (dk.SUMMARY_WORDS,)
                seen.append(int(rec.out.rows[rec.row][1]))
        return real(self, covered)

    monkeypatch.setattr(de.DeviceEngine, "_resolve_recovery", spy)
    h_d, h_c = mk_pair()
    replay_both(h_d, h_c, ops_of())
    assert any(flags & getattr(dk, flag) for flags in seen), seen
    assert h_d.sm._dev.stat_fallback_batches >= 1


def test_a_window_of_many_failures_is_clean(monkeypatch):
    """The row's n_fail, not a flag, says that the dense codes are
    needed: the window stays on _resolve_clean and the batch behind
    it is not re-dispatched."""
    from tigerbeetle_tpu.state_machine import device_engine as de
    from tigerbeetle_tpu.state_machine import device_kernels as dk

    assert not hasattr(dk, "FLAG_CAP")
    monkeypatch.setattr(
        de.DeviceEngine, "_resolve_recovery",
        lambda self, covered: pytest.fail("a clean window went to recovery"))
    h_d, h_c = mk_pair()
    ops = _cap_ops()
    ops.append((Operation.create_transfers, transfers(
        [dict(id=900, debit_account_id=1, credit_account_id=2, amount=7)])))
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2])))
    replay_both(h_d, h_c, ops)
    assert h_d.sm._dev.stat_dense_fetches == 1


def test_linked_fixpoint_multi_iteration():
    """Interleaved chains contending on limited accounts force the
    Jacobi fixpoint past one iteration; verdicts stay exact."""
    rng = np.random.default_rng(7)
    n_acct = 6
    h_d, h_c = mk_pair()
    ops = [
        (
            Operation.create_accounts,
            accounts(
                range(1, n_acct + 1),
                flags=int(AF.debits_must_not_exceed_credits),
            )
            + accounts([99]),
        )
    ]
    # Fund tightly so later chain members trip limits depending on
    # earlier verdicts.
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=100 + i, debit_account_id=99,
                         credit_account_id=i + 1, amount=30)
                    for i in range(n_acct)
                ]
            ),
        )
    )
    rows = []
    tid = 200
    for _chain in range(40):
        ln = int(rng.integers(1, 5))
        for j in range(ln):
            dr = int(rng.integers(1, n_acct + 1))
            cr = int(rng.integers(1, n_acct + 1))
            if cr == dr:
                cr = dr % n_acct + 1
            rows.append(
                dict(
                    id=tid, debit_account_id=dr, credit_account_id=cr,
                    amount=int(rng.integers(1, 25)),
                    flags=int(TF.linked) if j < ln - 1 else 0,
                )
            )
            tid += 1
    ops.append((Operation.create_transfers, transfers(rows)))
    ops.append(
        (Operation.lookup_accounts, hz.ids_bytes(list(range(1, n_acct + 1))))
    )
    replay_both(h_d, h_c, ops)


def test_pulse_with_inflight_timeout_pending():
    """A timeout pending created through the device path must still
    expire on schedule (pulse drains the pipeline first)."""
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts([1, 2]))]
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=10, debit_account_id=1, credit_account_id=2,
                         amount=50, flags=int(TF.pending), timeout=1),
                ]
            ),
        )
    )
    futs = [h_d.submit_async(op, body) for op, body in ops]
    replies_c = [h_c.submit(op, body) for op, body in ops]
    # Advance realtime past the expiry on both engines.
    later = int(2e9) + h_d.sm.prepare_timestamp
    # First submit advances prepare_timestamp past the expiry; the
    # second one's tick_pulses fires the pulse (prepare-time decision,
    # reference: src/vsr/replica.zig:3126-3143).
    for _ in range(2):
        a = h_d.submit_async(
            Operation.lookup_accounts, hz.ids_bytes([1, 2]), realtime=later
        )
        b = h_c.submit(
            Operation.lookup_accounts, hz.ids_bytes([1, 2]), realtime=later
        )
    for f, r in zip(futs, replies_c):
        assert f.result() == r
    assert a.result() == b
    acc = np.frombuffer(a.result(), dtype=types.ACCOUNT_DTYPE)
    assert int(acc[0]["debits_pending_lo"]) == 0  # expired and released


def test_checkpoint_checksum_catches_divergence():
    sm = TpuStateMachine(engine="device")
    h = hz.SingleNodeHarness(sm)
    h.submit(Operation.create_accounts, accounts([1, 2]))
    h.submit(
        Operation.create_transfers,
        transfers(
            [dict(id=10, debit_account_id=1, credit_account_id=2, amount=5)]
        ),
    )
    sm.verify_device_mirror()  # clean
    sm._mirror.lo[0, 1] += 1  # corrupt the mirror
    with pytest.raises(AssertionError, match="divergence"):
        sm.verify_device_mirror()
    sm._mirror.lo[0, 1] -= 1
    sm.snapshot()  # checkpoint barrier runs the verify


def test_lookup_accounts_sees_inflight_batches(monkeypatch):
    """Device-side balance gather reflects batches that have not
    materialized yet (no drain)."""
    import tigerbeetle_tpu.state_machine.device_engine as de

    monkeypatch.setattr(de, "_WINDOW", 1000)
    sm = TpuStateMachine(engine="device")
    h = hz.SingleNodeHarness(sm)
    h.submit(Operation.create_accounts, accounts([1, 2]))
    f1 = h.submit_async(
        Operation.create_transfers,
        transfers(
            [dict(id=10, debit_account_id=1, credit_account_id=2, amount=5)]
        ),
    )
    f2 = h.submit_async(Operation.lookup_accounts, hz.ids_bytes([1, 2]))
    assert not f1.done()  # still in flight
    acc = np.frombuffer(f2.result(), dtype=types.ACCOUNT_DTYPE)
    assert int(acc[0]["debits_posted_lo"]) == 5
    assert int(acc[1]["credits_posted_lo"]) == 5
    assert f1.result() == b""


def test_pipelined_double_finalize_same_pending(monkeypatch):
    """Two pipelined one-event batches posting the SAME durable
    pending: the second must drain on the recorded pending-ref key of
    the first (not just its transfer id) and fail with
    already_posted — the code-review repro for the id_keys hazard."""
    import tigerbeetle_tpu.state_machine.device_engine as de

    monkeypatch.setattr(de, "_WINDOW", 64)
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts([1, 2]))]
    ops.append(
        (
            Operation.create_transfers,
            transfers(
                [
                    dict(id=10, debit_account_id=1, credit_account_id=2,
                         amount=50, flags=int(TF.pending)),
                    dict(id=11, debit_account_id=1, credit_account_id=2,
                         amount=500, flags=int(TF.pending)),
                ]
            ),
        )
    )
    futs1 = [h_d.submit_async(op, body) for op, body in ops]
    replies_1 = [f.result() for f in futs1]  # pendings land durably
    ops2 = [
        (
            Operation.create_transfers,
            transfers(
                [dict(id=30, pending_id=10,
                      flags=int(TF.post_pending_transfer))]
            ),
        ),
        (
            Operation.create_transfers,
            transfers(
                [dict(id=31, pending_id=10,
                      flags=int(TF.post_pending_transfer))]
            ),
        ),
        (Operation.lookup_accounts, hz.ids_bytes([1, 2])),
    ]
    futs2 = [h_d.submit_async(op, body) for op, body in ops2]
    replies_d = replies_1 + [f.result() for f in futs2]
    replies_c = [h_c.submit(op, body) for op, body in ops + ops2]
    assert replies_d == replies_c
    res = np.frombuffer(replies_d[-2], dtype=types.CREATE_RESULT_DTYPE)
    assert len(res) == 1
    assert res[0]["result"] == int(CTR.pending_transfer_already_posted)


def test_two_phase_cross_batch_durable_targets():
    """Pendings land durably (drained), then posts/voids reference them
    from later batches, including double-finalize races."""
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts([1, 2, 3]))]
    pends = [
        dict(id=10 + i, debit_account_id=1, credit_account_id=2,
             amount=10 + i, flags=int(TF.pending))
        for i in range(6)
    ]
    ops.append((Operation.create_transfers, transfers(pends)))
    finalize = [
        dict(id=30, pending_id=10, flags=int(TF.post_pending_transfer)),
        dict(id=31, pending_id=11, flags=int(TF.void_pending_transfer)),
        dict(id=32, pending_id=10, flags=int(TF.void_pending_transfer)),
        dict(id=33, pending_id=12, flags=int(TF.post_pending_transfer),
             amount=5),
        dict(id=34, pending_id=99, flags=int(TF.post_pending_transfer)),
    ]
    ops.append((Operation.create_transfers, transfers(finalize)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2])))
    ops.append(
        (Operation.lookup_transfers, hz.ids_bytes([30, 31, 32, 33, 34]))
    )
    replay_both(h_d, h_c, ops)


def test_hot_tail_store_equivalence():
    """The C wire->store decode tail in _finish_native_fast must write
    EXACTLY the columns the shared _post_process_transfers path does —
    the two implementations are pinned together here so a bookkeeping
    change landing in only one fails loudly (per _finish_fast's
    one-implementation invariant)."""
    from tigerbeetle_tpu.runtime import fastpath
    from tigerbeetle_tpu.state_machine.tpu import _STORE_FIELDS

    if fastpath._load() is None:
        pytest.skip("native library unavailable")

    results = {}
    for hot in (True, False):
        rng = np.random.default_rng(11)  # same stream both runs
        sm = TpuStateMachine(account_capacity=1 << 12)
        if sm._native is None:
            pytest.skip("native fastpath unavailable")
        if not hot:
            # Disabling the native fast path routes the same batch
            # through the Python fast path + the SHARED bookkeeping
            # (_finish_fast -> _post_process_transfers).
            sm._native = None
        h = hz.SingleNodeHarness(sm)
        h.submit(Operation.create_accounts, accounts(range(1, 51)))
        rows = []
        for i in range(400):
            dr = int(rng.integers(1, 51))
            cr = dr % 50 + 1
            flags = int(TF.pending) if i % 5 == 0 else 0
            rows.append(
                dict(id=1000 + i, debit_account_id=dr,
                     credit_account_id=cr,
                     amount=int(rng.integers(1, 90)), flags=flags)
            )
        h.submit(Operation.create_transfers, transfers(rows))
        store = sm._store
        results[hot] = {
            name: np.asarray(store.col(name)).copy()
            for name in _STORE_FIELDS
        }

    for name in results[True]:
        assert (results[True][name] == results[False][name]).all(), (
            f"store column {name} diverges between the hot tail and "
            "the shared bookkeeping path"
        )


def test_grow_with_window_in_flight(monkeypatch):
    """Capacity growth triggered by create_accounts while a transfer
    window is still in flight: grow() must drain the stream, widen the
    tables, and every reply (before and after) must stay exact."""
    import tigerbeetle_tpu.state_machine.device_engine as de

    monkeypatch.setattr(de, "_WINDOW", 64)
    sm_d = TpuStateMachine(engine="device", account_capacity=64)
    h_d = hz.SingleNodeHarness(sm_d)
    h_c = hz.SingleNodeHarness(CpuStateMachine())
    ops = [(Operation.create_accounts, accounts(range(1, 41)))]
    # In-flight transfers against the small table...
    for k in range(6):
        ops.append(
            (
                Operation.create_transfers,
                transfers(
                    [dict(id=100 + k, debit_account_id=1 + k % 40,
                          credit_account_id=1 + (k + 1) % 40,
                          amount=5 + k)]
                ),
            )
        )
    futs = [h_d.submit_async(op, body) for op, body in ops]
    cap_before = sm_d._dev.capacity
    assert sm_d._dev.has_inflight()
    # ...then an account burst that forces _ensure_balance_capacity ->
    # DeviceEngine.grow() mid-stream.
    grow_ops = [(Operation.create_accounts, accounts(range(41, 101)))]
    for k in range(4):
        grow_ops.append(
            (
                Operation.create_transfers,
                transfers(
                    [dict(id=200 + k, debit_account_id=90 + k,
                          credit_account_id=1 + k, amount=7 + k)]
                ),
            )
        )
    grow_ops.append(
        (Operation.lookup_accounts, hz.ids_bytes(list(range(1, 101))))
    )
    futs += [h_d.submit_async(op, body) for op, body in grow_ops]
    replies_d = [f.result() for f in futs]
    replies_c = [h_c.submit(op, body) for op, body in ops + grow_ops]
    assert replies_d == replies_c
    assert sm_d._dev.capacity > cap_before
    # The point is DEVICE-path coverage: a regression that demotes the
    # engine would still reply exactly (host fallback) — fail loudly
    # instead of passing vacuously.
    assert sm_d._dev.stat_demotions == 0
    assert sm_d._dev.state is types.EngineState.healthy
    sm_d.verify_device_mirror()


def test_remove_accounts_with_window_in_flight(monkeypatch):
    """A linked create_accounts chain that fails mid-chain rolls back
    its slots (DeviceEngine.remove_accounts) while transfer batches
    are still in flight; the meta zeroing must sequence with the
    stream and later replies stay exact."""
    import tigerbeetle_tpu.state_machine.device_engine as de

    monkeypatch.setattr(de, "_WINDOW", 64)
    h_d, h_c = mk_pair()
    setup = (Operation.create_accounts, accounts([1, 2]))
    h_d.submit(*setup)
    h_c.submit(*setup)
    futs = []
    ops = []
    for k in range(3):
        op = (
            Operation.create_transfers,
            transfers(
                [dict(id=10 + k, debit_account_id=1, credit_account_id=2,
                      amount=3 + k)]
            ),
        )
        ops.append(op)
        futs.append(h_d.submit_async(*op))
    assert h_d.sm._dev.has_inflight()
    # Linked chain: second member duplicates id 1 -> whole chain fails
    # -> rollback removes the chain's already-allocated slots while
    # the transfer window above is still in flight.
    chain = (
        Operation.create_accounts,
        hz.pack(
            [
                hz.account(50, flags=int(AF.linked)),
                hz.account(1),
            ]
        ),
    )
    ops.append(chain)
    futs.append(h_d.submit_async(*chain))
    # Transfers naming the rolled-back account must fail identically.
    post = (
        Operation.create_transfers,
        transfers(
            [dict(id=20, debit_account_id=50, credit_account_id=2,
                  amount=9),
             dict(id=21, debit_account_id=1, credit_account_id=2,
                  amount=11)]
        ),
    )
    ops.append(post)
    futs.append(h_d.submit_async(*post))
    look = (Operation.lookup_accounts, hz.ids_bytes([1, 2, 50]))
    ops.append(look)
    futs.append(h_d.submit_async(*look))
    replies_d = [f.result() for f in futs]
    replies_c = [h_c.submit(op, body) for op, body in ops]
    assert replies_d == replies_c
    # Device-path coverage must be real, not a silent host fallback.
    assert h_d.sm._dev.stat_demotions == 0
    assert h_d.sm._dev.state is types.EngineState.healthy
    h_d.sm.verify_device_mirror()


def test_tight_and_wide_inputs_agree(monkeypatch):
    """The tight (B, 5) u32 order-free input and the wide u64 format
    must produce byte-identical replies and final state for the same
    stream — the tight path is an ENCODING, not a semantics change.
    The wide run shrinks the router's amount gate to zero so the same
    small-amount stream routes through the u64 format."""
    import tigerbeetle_tpu.state_machine.tpu as tpu_mod

    def stream():
        rng = np.random.default_rng(11)
        ops = [(Operation.create_accounts, accounts(range(1, 40)))]
        tid = 100
        for _ in range(3):
            rows = []
            for _k in range(50):
                dr = int(rng.integers(1, 40))
                cr = dr % 39 + 1
                rows.append(
                    hz.transfer(tid, debit_account_id=dr,
                                credit_account_id=cr,
                                amount=int(rng.integers(1, 90)))
                )
                tid += 1
            ops.append((Operation.create_transfers, hz.pack(rows)))
        ops.append((Operation.lookup_accounts, hz.ids_bytes(range(1, 40))))
        return ops

    def run():
        sm = TpuStateMachine(engine="device", account_capacity=1 << 10)
        h = hz.SingleNodeHarness(sm)
        return [h.submit(op, body) for op, body in stream()], sm

    replies_tight, sm_t = run()
    assert sm_t.stat_device_semantic_events > 0

    monkeypatch.setattr(tpu_mod, "_TIGHT_AMOUNT_LIMIT", 0)
    replies_wide, sm_w = run()
    assert sm_w.stat_device_semantic_events > 0
    assert replies_tight == replies_wide


# ---------------------------------------------------------------------------
# Link-error classes: classification is MEASURED against the
# declarative marker table, not guessed — a new marker harvested from
# a real link fault is one table row plus one parametrized case here.


def _pjrt_style_message(marker: str) -> str:
    """A message shaped like what JAX/PJRT actually surfaces: gRPC
    status name + detail, wrapped in the XlaRuntimeError prefix."""
    return (
        f"jaxlib.xla_extension.XlaRuntimeError: {marker}: stream "
        "executor failure while transferring buffer d2h"
    )


from tigerbeetle_tpu.state_machine.device_engine import LINK_ERROR_MARKERS


@pytest.mark.parametrize("marker,expected", list(LINK_ERROR_MARKERS))
def test_link_error_marker_classification(marker, expected):
    from tigerbeetle_tpu.state_machine import device_engine as de

    exc = RuntimeError(_pjrt_style_message(marker))
    assert de.classify_link_error(exc) == expected


def test_link_error_first_match_wins_and_default_fatal():
    from tigerbeetle_tpu.state_machine import device_engine as de

    # Typed exceptions bypass the table entirely.
    assert de.classify_link_error(de.TransientLinkError("x")) == "transient"
    assert de.classify_link_error(de.FatalLinkError("x")) == "fatal"
    # Unknown messages default to fatal (demote, never spin retrying).
    assert de.classify_link_error(RuntimeError("segfault in plugin")) == "fatal"
    # Declaration order arbitrates multi-marker messages: UNAVAILABLE
    # precedes INTERNAL in the table, so the transient row wins.
    both = RuntimeError(_pjrt_style_message("UNAVAILABLE") + " INTERNAL")
    assert de.classify_link_error(both) == "transient"


def test_link_error_classes_are_declarative():
    """The table stays the single source of truth: every row
    classifies one way, and both classes are represented (a table
    with one class is a boolean, not a classification)."""
    from tigerbeetle_tpu.state_machine import device_engine as de

    kinds = {kind for _m, kind in de.LINK_ERROR_MARKERS}
    assert kinds == {"transient", "fatal"}
    markers = [m for m, _k in de.LINK_ERROR_MARKERS]
    assert len(markers) == len(set(markers)), "duplicate marker rows"


# ---------------------------------------------------------------------------
# Healthy-mode scrub jitter: a deterministic per-engine offset keeps
# TB_DEV_SCRUB_EVERY scrubs off the same fetch ordinal across engines
# (each scrub costs a ~105 ms checksum fetch on the real link).


def test_scrub_offset_deterministic_and_bounded(monkeypatch):
    import tigerbeetle_tpu.state_machine.device_engine as de
    from tigerbeetle_tpu.state_machine.mirror import BalanceMirror

    monkeypatch.setattr(de, "_SCRUB_EVERY", 256)
    monkeypatch.setattr(de, "_SCRUB_JITTER", -1)  # auto: every // 8

    def offset(seed):
        eng = de.DeviceEngine(64, BalanceMirror(64), seed=seed)
        return eng._scrub_offset

    a1, a2 = offset(7), offset(7)
    assert a1 == a2, "same seed must give the same offset"
    cap = de._scrub_jitter_cap(256, -1)
    assert cap == 32
    offsets = {offset(s) for s in range(40)}
    assert all(0 <= o <= cap for o in offsets)
    assert len(offsets) > 1, "offsets never vary: jitter is vacuous"
    # Default seeds mix in a per-process construction ordinal: a fleet
    # of SAME-capacity engines must not scrub in lockstep.
    defaults = {
        de.DeviceEngine(64, BalanceMirror(64))._scrub_offset
        for _ in range(8)
    }
    assert len(defaults) > 1, "same-capacity engines share one offset"


def test_scrub_jitter_shifts_first_scrub(monkeypatch):
    """The first scrub fires TB_DEV_SCRUB_EVERY - offset fetches in
    (phase-shifted), subsequent scrubs keep the full cadence."""
    import tigerbeetle_tpu.state_machine.device_engine as de
    from tigerbeetle_tpu.state_machine.mirror import BalanceMirror

    monkeypatch.setattr(de, "_SCRUB_EVERY", 16)
    monkeypatch.setattr(de, "_SCRUB_JITTER", 5)
    eng = de.DeviceEngine(64, BalanceMirror(64), seed=3)
    off = eng._scrub_offset
    assert 0 <= off <= 5
    scrubbed = []
    real_scrub = eng.scrub

    def counting_scrub():
        scrubbed.append(eng.stat_fetches)
        return real_scrub()

    eng.scrub = counting_scrub
    for fetch in range(1, 64):
        eng.stat_fetches = fetch
        eng.tick()
    assert scrubbed, "scrub never fired"
    assert scrubbed[0] == 16 - off
    if len(scrubbed) > 1:
        assert scrubbed[1] - scrubbed[0] == 16


def test_scrub_jitter_disabled_when_zero(monkeypatch):
    import tigerbeetle_tpu.state_machine.device_engine as de
    from tigerbeetle_tpu.state_machine.mirror import BalanceMirror

    monkeypatch.setattr(de, "_SCRUB_EVERY", 256)
    monkeypatch.setattr(de, "_SCRUB_JITTER", 0)
    eng = de.DeviceEngine(64, BalanceMirror(64), seed=12345)
    assert eng._scrub_offset == 0


# ---------------------------------------------------------------------------
# Engine set-up: a lost LINK may demote; a broken PROGRAM may not.  A
# kernel the chip's compiler refuses or a table that does not fit must
# fail the process at construction/prewarm instead of yielding a green
# run served by the host with the chip idle.


from tigerbeetle_tpu.state_machine.mirror import BalanceMirror  # noqa: E402


class _RefusingLink:
    """DeviceLink whose uploads fail the way the chip's allocator does."""

    def __init__(self, message: str) -> None:
        from tigerbeetle_tpu.state_machine.device_engine import DeviceLink

        self._real = DeviceLink()
        self.message = message

    def device_put(self, array, sharding=None):
        raise RuntimeError(self.message)

    def __getattr__(self, name):
        return getattr(self._real, name)


@pytest.mark.parametrize("message", [
    "RESOURCE_EXHAUSTED: Attempting to allocate 17.00G. That was not "
    "possible. There are 15.48G free.",
    "INTERNAL: Mosaic failed to compile TPU kernel",
    "an error no table row names",
])
def test_engine_construction_propagates_program_errors(message):
    from tigerbeetle_tpu.state_machine import device_engine as de

    with pytest.raises(RuntimeError, match=message.split(":")[0]) as info:
        de.DeviceEngine(64, BalanceMirror(64), link=_RefusingLink(message))
    assert not isinstance(info.value, de.DeviceLostError)


def test_engine_construction_on_a_dead_link_is_still_born_degraded():
    from tigerbeetle_tpu.state_machine import device_engine as de
    from tigerbeetle_tpu.testing.chaos import ChaosLink
    from tigerbeetle_tpu.types import EngineState

    link = ChaosLink(seed=1)
    link.kill()
    eng = de.DeviceEngine(64, BalanceMirror(64), link=link)
    assert eng.state is EngineState.degraded
    assert "device lost" in eng.last_demotion
    # A status a link produces, retries exhausted, demotes as well.
    eng = de.DeviceEngine(
        64, BalanceMirror(64),
        link=_RefusingLink("UNAVAILABLE: connection reset"),
    )
    assert eng.state is EngineState.degraded


def test_prewarm_propagates_a_compile_error_and_demotes_on_link_loss():
    from tigerbeetle_tpu.state_machine import device_engine as de
    from tigerbeetle_tpu.testing.chaos import ChaosLink
    from tigerbeetle_tpu.types import EngineState

    eng = de.DeviceEngine(64, BalanceMirror(64))

    def refuse(kinds):
        raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in "
                           "memory space hbm while compiling")

    eng._prewarm_inner = refuse
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.prewarm(["orderfree"])
    assert eng.state is EngineState.healthy

    link = ChaosLink(seed=2)
    eng = de.DeviceEngine(64, BalanceMirror(64), link=link)
    link.kill()
    eng.prewarm(["orderfree"])
    assert eng.state is EngineState.degraded


def test_resource_exhausted_is_not_retried():
    """What the allocator and the compiler say when a program does not
    fit is answered at once, not after three back-offs."""
    from tigerbeetle_tpu.state_machine import device_engine as de

    eng = de.DeviceEngine(64, BalanceMirror(64))
    calls = []

    def oom():
        calls.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    with pytest.raises(de.DeviceLostError):
        eng._retry(oom, "dispatch")
    assert len(calls) == 1 and eng.stat_retries == 0


# ----------------------------------------------------------------------
# A window crosses the link once each way (PR 27).

import jax  # noqa: E402

from tigerbeetle_tpu.state_machine.device_engine import DeviceLink  # noqa: E402


class _RecordingLink(DeviceLink):
    """DeviceLink that writes down every crossing, in order."""

    def __init__(self) -> None:
        self.log = []

    def device_put(self, array, sharding=None):
        self.log.append(("put", tuple(array.shape)))
        return super().device_put(array, sharding)

    def dispatch(self, fn, *args):
        name = getattr(fn, "__name__", None) or fn.__wrapped__.__name__
        # Every argument is already on the device: no Python scalar, no
        # numpy array, nothing the call itself would have to upload.
        flat = jax.tree_util.tree_leaves(args)
        assert all(isinstance(a, jax.Array) for a in flat), (name, args)
        self.log.append(("dispatch", name, len(args)))
        return super().dispatch(fn, *args)

    def copy_to_host_async(self, array):
        self.log.append(("copy_start", tuple(array.shape)))
        super().copy_to_host_async(array)

    def fetch(self, array):
        self.log.append(("fetch", array.nbytes))
        return super().fetch(array)


def _sealed_batch(dk, kind, n):
    """A packed batch of `n` events that touch rows 0 and 1 (their
    ladder verdicts do not matter here, only how they cross)."""
    ncols, dtype = dk.PK_SPEC[kind]
    pk = np.zeros((dk.ROWS, ncols), dtype)
    if kind == "orderfree_tight":
        pk[:n, 1], pk[:n, 2] = 1, 2
    else:
        pk[:n, dk.COL_SLOTS] = 1 | (2 << 32)
    return pk


_ALL_KINDS = (
    "orderfree", "orderfree_lo", "orderfree_tight", "linked",
    "linked_small", "two_phase", "two_phase_lo",
)


@pytest.mark.parametrize(
    "kind,G",
    [(k, 1) for k in _ALL_KINDS]
    + [("orderfree_tight", 4), ("linked_small", 4), ("two_phase_lo", 4)],
)
def test_a_window_crosses_the_link_once_each_way(monkeypatch, kind, G):
    """Per dispatch unit: ONE upload (the scalars ride in it), the
    kernel's dispatch on device arrays alone, one started copy of the
    unit's own summary rows, then the digest's two uploads and its
    dispatch; at the rotation one fetch of 512 bytes a record."""
    from tigerbeetle_tpu.state_machine import device_engine as de
    from tigerbeetle_tpu.state_machine import device_kernels as dk

    monkeypatch.setattr(de, "_WINDOW", G)
    link = _RecordingLink()
    eng = de.DeviceEngine(64, BalanceMirror(64), link=link)
    link.log.clear()
    puts0, bytes0 = eng.stat_puts, eng.stat_fetch_bytes
    got = []
    ts_base = (7 << 32) | 9           # both halves of the tight format
    futs = [
        eng.submit(
            kind, _sealed_batch(dk, kind, n=2 + g), 2 + g, ts_base,
            lambda s, g=g: got.append((g, s["n_active"])) or b"ok",
            lambda: b"host",
        )
        for g in range(G)
    ]
    ncols, _dtype = dk.PK_SPEC[kind]
    up = (dk.ROWS, ncols) if G == 1 else (G, dk.ROWS, ncols)
    down = (dk.SUMMARY_WORDS,) if G == 1 else (G, dk.SUMMARY_WORDS)
    # The window filled at the last submit: it is launched, not fetched.
    program = link.log[1][1]
    assert kind in program and (G == 1 or f"scan_{kind}_g{G}" == program)
    launched = [
        ("put", up),
        ("dispatch", program, 3),
        ("copy_start", down),
        ("dispatch", "_update", 6),
    ]
    assert link.log == launched
    eng.drain()
    assert link.log == launched + [("fetch", 512 * G)]
    assert [f.result() for f in futs] == [b"ok"] * G
    # n crossed inside the buffer, record by record, in order.
    assert got == [(g, 2 + g) for g in range(G)]
    assert eng.stat_puts - puts0 == 1 + 2
    assert eng.stat_fetch_bytes - bytes0 == 512 * G
    assert eng.stat_fallback_batches == 0 and eng.stat_demotions == 0


def test_the_scalars_cross_in_the_buffers_last_row():
    """seal_scalars on the host, _split_scalars on the device: n and a
    ts_base wider than 32 bits, through both packed formats."""
    import jax

    from tigerbeetle_tpu.state_machine import device_kernels as dk

    ts_base = (0x1234 << 32) | 0x89ABCDEF
    for kind in ("orderfree", "orderfree_tight"):
        ncols, dtype = dk.PK_SPEC[kind]
        pk = dk.seal_scalars(np.ones((dk.ROWS, ncols), dtype), 37, ts_base)
        assert (pk[: dk.B] == 1).all()
        rows, n, ts = jax.jit(dk._split_scalars)(pk)
        assert rows.shape == (dk.B, ncols)
        assert (int(n), int(ts)) == (37, ts_base)
    with pytest.raises(AssertionError):
        dk.seal_scalars(np.zeros((dk.B, 6), np.uint64), 1, 1)


@pytest.mark.parametrize("stage", ["h2d", "fetch_start", "fetch"])
def test_a_fault_at_each_crossing_of_a_prepare_demotes_once(stage):
    """A fatal fault at the upload, at the copy's start, and at its
    wait: the reply comes bit-identical from the host fallback, and the
    demotion is counted once."""
    from tigerbeetle_tpu.testing.chaos import ChaosLink
    from tigerbeetle_tpu.types import EngineState

    link = ChaosLink(seed=27)
    sm_d = TpuStateMachine(
        engine="device", account_capacity=1 << 12, device_link=link
    )
    h_d, h_c = hz.SingleNodeHarness(sm_d), hz.SingleNodeHarness(CpuStateMachine())
    setup = (Operation.create_accounts, accounts([1, 2, 3]))
    assert h_d.submit(*setup) == h_c.submit(*setup)
    dev = sm_d._dev
    assert dev.state is EngineState.healthy and dev.stat_demotions == 0
    link.fail_next(stage=stage, kind="fatal")
    batch = (
        Operation.create_transfers,
        transfers(
            [
                dict(id=10, debit_account_id=1, credit_account_id=2, amount=5),
                dict(id=11, debit_account_id=2, credit_account_id=2, amount=1),
                dict(id=12, debit_account_id=3, credit_account_id=1, amount=7),
            ]
        ),
    )
    assert h_d.submit(*batch) == h_c.submit(*batch)
    assert link.stat_fatal == 1 and dev.stat_demotions == 1
    assert dev.stat_degraded_events == 3 and not dev.has_inflight()
    look = (Operation.lookup_accounts, hz.ids_bytes([1, 2, 3]))
    assert h_d.submit(*look) == h_c.submit(*look)


# ----------------------------------------------------------------------
# The shape `bench1r-tpcc-pay-c4` brings (PR 35): two-leg chains
# customer -> district -> warehouse, one row party to every chain, a
# hundredth of the chains failing statically.

_HOT_WAREHOUSE, _HOT_DISTRICTS, _HOT_CUSTOMERS = 1, 5, 600


def _hot_row_accounts():
    n = 1 + _HOT_DISTRICTS + _HOT_CUSTOMERS
    return (Operation.create_accounts, accounts(range(1, n + 1)))


def _hot_row_batch(rng, first_id: int, chains: int, fail_share: float):
    """`chains` payments: customer -> district (`linked`), district ->
    warehouse 1; a share of them names a warehouse that does not exist
    in its second leg.  -> (op, body), chains failing"""
    t = np.zeros(2 * chains, types.TRANSFER_DTYPE)
    t["id_lo"] = first_id + np.arange(2 * chains)
    t["ledger"], t["code"] = 1, 25
    district = 2 + rng.integers(0, _HOT_DISTRICTS, chains)
    fails = rng.random(chains) < fail_share
    fails[rng.integers(0, chains)] = True
    leg1, leg2 = t[0::2], t[1::2]
    leg1["debit_account_id_lo"] = 2 + _HOT_DISTRICTS + rng.integers(
        0, _HOT_CUSTOMERS, chains)
    leg1["credit_account_id_lo"] = leg2["debit_account_id_lo"] = district
    leg1["flags"] = int(TF.linked)
    leg2["credit_account_id_lo"] = np.where(fails, 9_999, _HOT_WAREHOUSE)
    leg1["amount_lo"] = leg2["amount_lo"] = rng.integers(100, 500_001, chains)
    return (Operation.create_transfers, t.tobytes()), int(fails.sum())


@pytest.mark.parametrize("seed", [35, 2**31 + 35])
def test_chains_through_one_hot_row_across_a_checkpoint_and_a_restore(seed):
    """Codes, balances and the state root against the oracle, with a
    checkpoint in the middle of the stream and the rest of it served
    by a machine restored from that checkpoint; and the gauges and
    histograms PR 35 added read what the stream makes them read."""
    rng = np.random.default_rng(seed)
    chains, batches = 128, 6
    n_accounts = 1 + _HOT_DISTRICTS + _HOT_CUSTOMERS
    h_d, h_c = mk_pair()
    replay_both(h_d, h_c, [_hot_row_accounts()])
    snap = h_d.sm.metrics.snapshot()
    assert snap["accounts"] == n_accounts and snap["dev.table_rows"] == 1 << 12
    # Account creation samples the histograms too (the rows whose meta
    # changed), and so does a restore: the stream's are counted from it.
    failing = 0
    for b in range(batches):
        if b == batches // 2:
            assert h_d.sm.state_root() == h_c.sm.state_root()
            blob = h_d.sm.snapshot()            # the barrier runs the verify
            restored = TpuStateMachine(engine="device", account_capacity=1 << 12)
            restored.restore(blob)
            assert restored.state_root() == h_c.sm.state_root()
            op = h_d.op
            h_d = hz.SingleNodeHarness(restored)
            h_d.op = op
            at_restore = restored.metrics.snapshot()
        op_body, n_fail = _hot_row_batch(rng, 10_000 + b * 1_000, chains, 0.01)
        failing += n_fail
        (reply,) = replay_both(h_d, h_c, [op_body])
        codes = np.frombuffer(reply, types.CREATE_RESULT_DTYPE)
        assert len(codes) == 2 * n_fail
        assert set(codes["result"][0::2]) == {int(CTR.linked_event_failed)}
        assert set(codes["result"][1::2]) == {int(CTR.credit_account_not_found)}
    ids = list(range(1, n_accounts + 1))
    replay_both(h_d, h_c, [(Operation.lookup_accounts, hz.ids_bytes(ids))])
    assert h_d.sm.state_root() == h_c.sm.state_root()
    h_d.sm.verify_device_mirror()
    dev = h_d.sm._dev
    assert dev.stat_fallback_batches == 0 and h_d.sm.stat_fallback_events == 0
    assert dev.stat_semantic_events == (batches - batches // 2) * 2 * chains
    snap = h_d.sm.metrics.snapshot()
    assert snap["accounts"] == n_accounts and snap["dev.table_rows"] == 1 << 12
    seen = snap["plan.rows_touched.count"] - at_restore["plan.rows_touched.count"]
    assert seen == batches - batches // 2
    assert snap["plan.row_legs_max.count"] == snap["plan.rows_touched.count"]
    # The warehouse's row takes a leg of every chain that names it:
    # over 100 of the batch's 128; a batch touches it, the 5 districts
    # and at most 128 customers.
    assert 100 < snap["plan.row_legs_max.max"] <= chains
    # (The restored accounts' meta rows ride the first window's sample.)
    touched = snap["plan.rows_touched.sum"] - at_restore["plan.rows_touched.sum"]
    assert 100 * seen < touched <= n_accounts + seen * (1 + _HOT_DISTRICTS + chains)
    assert "dev.plan.rows_touched.count" not in snap
    assert failing >= batches


def _dense_adds(dr_slot, cr_slot, amt_lo, amt_hi, is_pending):
    """The mirror's limb sums as they were before PR 35: a float64 bin
    a (slot, column) up to the highest slot named."""
    mask32 = np.uint64(0xFFFFFFFF)
    K = (int(max(dr_slot.max(), cr_slot.max())) + 1) * 4
    idx_dr = dr_slot * 4 + np.where(is_pending, 0, 1)
    idx_cr = cr_slot * 4 + np.where(is_pending, 2, 3)
    acc = np.empty((4, K))
    for i, limb in enumerate((amt_lo & mask32, amt_lo >> np.uint64(32),
                              amt_hi & mask32, amt_hi >> np.uint64(32))):
        w = limb.astype(np.float64)
        acc[i] = np.bincount(idx_dr, weights=w, minlength=K)
        acc[i] += np.bincount(idx_cr, weights=w, minlength=K)
    at = np.flatnonzero(acc.any(axis=0))
    c = acc[:, at].astype(np.uint64)
    c1 = c[1] + (c[0] >> np.uint64(32))
    c2 = c[2] + (c1 >> np.uint64(32))
    c3 = c[3] + (c2 >> np.uint64(32))
    assert not (c3 >> np.uint64(32)).any()
    return ((at >> 2).astype(np.int64), (at & 3).astype(np.int64),
            (c[0] & mask32) | ((c1 & mask32) << np.uint64(32)),
            (c2 & mask32) | ((c3 & mask32) << np.uint64(32)))


@pytest.mark.parametrize("seed,rows,hot", [
    (1, 4096, 1), (2, 4096, 8), (3, 1 << 16, 3), (2**31 + 4, 600, 300),
])
def test_the_mirrors_adds_follow_the_batch_and_equal_the_dense_sums(
        seed, rows, hot):
    """`try_apply_adds` sums over the batch's own (slot, column) keys;
    the deltas it returns and the rows it leaves are those of the
    dense form, bit for bit: hot rows, high limbs, pendings, amounts
    of nought and masked events included."""
    rng = np.random.default_rng(seed)
    n = 500
    cr = rng.integers(0, hot, n).astype(np.int64)
    dr = (hot + rng.integers(0, rows - hot, n)).astype(np.int64)
    amt_lo = rng.integers(0, 1 << 63, n, dtype=np.uint64) * np.uint64(2)
    amt_lo[rng.random(n) < 0.1] = 0
    amt_hi = np.where(rng.random(n) < 0.2,
                      rng.integers(0, 1 << 40, n, dtype=np.uint64), np.uint64(0))
    amt_hi[amt_lo == 0] = 0
    pending = rng.random(n) < 0.3
    mask = rng.random(n) < 0.9
    mirror, twin = BalanceMirror(rows), BalanceMirror(rows)
    for _ in range(3):
        got = mirror.try_apply_adds(dr, cr, amt_lo, amt_hi, pending, mask)
        want = _dense_adds(dr[mask], cr[mask], amt_lo[mask], amt_hi[mask],
                           pending[mask])
        assert twin._admit_commit(*want, True)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and (g == w).all()
        assert (mirror.lo == twin.lo).all() and (mirror.hi == twin.hi).all()
    assert mirror.lo[:hot].any() and mirror.hi[:hot].any()
    # A dry run proves admission and moves nothing.
    before = mirror.lo.copy()
    assert mirror.try_apply_adds(dr, cr, amt_lo, amt_hi, pending, mask,
                                 commit=False) is not None
    assert (mirror.lo == before).all()
    # A column that would pass 2^128 is refused, mirror untouched.
    huge = np.full(n, np.uint64((1 << 64) - 1))
    assert mirror.try_apply_adds(dr, np.zeros(n, np.int64), huge, huge,
                                 np.zeros(n, bool), np.ones(n, bool)) is None
    assert (mirror.lo == before).all()
