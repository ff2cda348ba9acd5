"""Device-loss chaos: demote -> serve degraded -> re-promote, on CPU.

The chaos shim (testing/chaos.py) injects seeded faults into every
link crossing of the device-authoritative engine; these tests pin the
degraded-mode lifecycle (state_machine/device_engine.py) to the CPU
oracle: under ANY injected fault schedule, every reply is bit-identical
to the pure-host oracle, no future is ever left unresolved, and the
engine re-promotes through the checksum handshake once the link heals.
"""

import numpy as np
import pytest

import tigerbeetle_tpu.state_machine.device_engine as de
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.testing.chaos import ChaosLink
from tigerbeetle_tpu.testing.vopr import Vopr, Workload
from tigerbeetle_tpu.types import EngineState, Operation


@pytest.fixture(autouse=True)
def _fast_lifecycle(monkeypatch):
    """Small window + no backoff sleeps + tight probe cadence: the
    lifecycle spins fast enough for a `not slow` smoke."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    monkeypatch.setattr(de, "_BACKOFF_MS", 0.0)
    monkeypatch.setattr(de, "_PROBE_EVERY", 2)


def mk_chaos_pair(seed=0, **chaos_kw):
    link = ChaosLink(seed=seed, **chaos_kw)
    sm_d = TpuStateMachine(
        engine="device", account_capacity=1 << 12, device_link=link
    )
    sm_c = CpuStateMachine()
    return hz.SingleNodeHarness(sm_d), hz.SingleNodeHarness(sm_c), link


def accounts(ids, flags=0):
    return hz.pack([hz.account(i, flags=flags) for i in ids])


def transfers(rows):
    return hz.pack([hz.transfer(**r) for r in rows])


def simple_ops(n_batches=6, tid0=100):
    ops = [(Operation.create_accounts, accounts([1, 2, 3]))]
    tid = tid0
    for k in range(n_batches):
        rows = [
            dict(id=tid + j, debit_account_id=1 + (k + j) % 3,
                 credit_account_id=1 + (k + j + 1) % 3, amount=1 + j)
            for j in range(3)
        ]
        tid += 3
        ops.append((Operation.create_transfers, transfers(rows)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2, 3])))
    return ops


def replay_pipelined(h_d, h_c, ops):
    futs = [h_d.submit_async(op, body) for op, body in ops]
    replies_d = [f.result() for f in futs]
    replies_c = [h_c.submit(op, body) for op, body in ops]
    for i, (a, b) in enumerate(zip(replies_d, replies_c)):
        assert a == b, f"reply {i} differs under chaos: {ops[i][0]!r}"
    return replies_d


@pytest.mark.parametrize("stage", ["h2d", "dispatch", "fetch_start", "fetch"])
def test_demote_at_every_stage_resolves_inflight(stage):
    """Fatal loss at each pipeline stage (pre-upload, mid-dispatch, as a
    summary's copy home starts, at its fetch): every in-flight future
    resolves bit-identically via
    host replay, and the engine lands in degraded mode."""
    h_d, h_c, link = mk_chaos_pair()
    ops = simple_ops()
    # Arm the fault AFTER setup so the loss hits with batches in
    # flight at the targeted stage.
    h_d.submit(*ops[0])
    h_c.submit(*ops[0])
    link.fail_next(stage=stage, kind="fatal")
    replay_pipelined(h_d, h_c, ops[1:])
    dev = h_d.sm._dev
    assert dev.stat_demotions >= 1
    assert not dev.has_inflight()
    # The link is healthy again after the one scripted fault, so the
    # probe cadence may already have re-promoted — either way the
    # lifecycle must be in a legal steady state.
    if dev.state is EngineState.healthy:
        assert dev.stat_repromotions >= 1
    else:
        assert dev.state is EngineState.degraded


def test_transient_faults_retry_without_demotion():
    """A burst of transient errors below the retry budget costs
    backoff only: no demotion, exact replies."""
    h_d, h_c, link = mk_chaos_pair()
    ops = simple_ops()
    h_d.submit(*ops[0])
    h_c.submit(*ops[0])
    link.fail_next(kind="transient", count=2)
    replay_pipelined(h_d, h_c, ops[1:])
    dev = h_d.sm._dev
    assert dev.state is EngineState.healthy
    assert dev.stat_retries >= 2
    assert dev.stat_demotions == 0


def test_retry_budget_exhaustion_demotes(monkeypatch):
    monkeypatch.setattr(de, "_RETRIES", 2)
    h_d, h_c, link = mk_chaos_pair()
    ops = simple_ops()
    h_d.submit(*ops[0])
    h_c.submit(*ops[0])
    # More consecutive transients than the budget: the crossing turns
    # into a device loss.
    link.fail_next(kind="transient", count=10)
    replay_pipelined(h_d, h_c, ops[1:])
    # Three transients (initial + 2 retries) exhausted the budget ->
    # demotion; the probe cadence may then have healed the engine once
    # the scripted faults drained.
    assert h_d.sm._dev.stat_demotions >= 1
    assert h_d.sm._dev.stat_retries >= 2


def test_degraded_serves_then_repromotes_with_handshake():
    """Kill -> exact degraded service -> heal -> probe cadence
    re-promotes through the checksum handshake -> device authority
    resumes (semantic events start counting again)."""
    h_d, h_c, link = mk_chaos_pair()
    ops = simple_ops(n_batches=4)
    replay_pipelined(h_d, h_c, ops)  # healthy warm-up
    dev = h_d.sm._dev
    sem_before = dev.stat_semantic_events
    assert sem_before > 0

    link.kill()
    mid = simple_ops(n_batches=6, tid0=500)[1:]  # accounts already exist
    replay_pipelined(h_d, h_c, mid)
    assert dev.state is EngineState.degraded
    assert dev.stat_degraded_events > 0

    link.heal()
    tail = simple_ops(n_batches=8, tid0=900)[1:]
    replay_pipelined(h_d, h_c, tail)
    assert dev.state is EngineState.healthy
    assert dev.stat_repromotions == 1
    # Authority genuinely moved back: post-heal batches ran on device.
    assert dev.stat_semantic_events > sem_before
    h_d.sm.verify_device_mirror()


def test_failed_probe_stays_degraded():
    """While the link is down, probes fail and the engine must keep
    serving degraded — never half-promote."""
    h_d, h_c, link = mk_chaos_pair()
    link.kill()
    replay_pipelined(h_d, h_c, simple_ops(n_batches=8))
    dev = h_d.sm._dev
    assert dev.state is EngineState.degraded
    assert dev.stat_probe_failures >= 1
    assert dev.stat_repromotions == 0


def test_scrub_heals_seeded_divergence(monkeypatch):
    """The healthy-mode checksum scrub detects a device/mirror
    divergence and heals it by re-uploading from the mirror."""
    monkeypatch.setattr(de, "_SCRUB_EVERY", 1)
    h_d, h_c, _link = mk_chaos_pair()
    ops = simple_ops(n_batches=2)
    replay_pipelined(h_d, h_c, ops)
    dev = h_d.sm._dev
    # Corrupt the device table behind the engine's back (a bit flip in
    # HBM), then let the next tick's scrub find and heal it.
    dev.balances = dev.balances.at[0, 1].add(np.uint64(1))
    with pytest.raises(AssertionError, match="divergence"):
        h_d.sm.verify_device_mirror()
    replay_pipelined(h_d, h_c, simple_ops(n_batches=2, tid0=700)[1:])
    assert dev.stat_scrubs >= 1
    assert dev.stat_scrub_heals == 1
    h_d.sm.verify_device_mirror()
    assert dev.state is EngineState.healthy


def test_scrub_heals_meta_divergence(monkeypatch):
    """The scrub digest covers the account-META table too: the ladder
    verdicts read it, so silent meta corruption is as dangerous as a
    balance flip.  A flipped word heals by re-upload from the host
    copy."""
    monkeypatch.setattr(de, "_SCRUB_EVERY", 1)
    h_d, h_c, _link = mk_chaos_pair()
    replay_pipelined(h_d, h_c, simple_ops(n_batches=2))
    dev = h_d.sm._dev
    dev.meta = dev.meta.at[1, 1].add(np.uint32(7))
    replay_pipelined(h_d, h_c, simple_ops(n_batches=2, tid0=800)[1:])
    assert dev.stat_scrub_heals == 1
    assert (np.asarray(dev.meta) == dev._meta_host).all()
    assert dev.state is EngineState.healthy


def test_lookup_and_meta_resolve_under_loss():
    """Device-side lookups and account-meta records in flight when the
    link dies must resolve from the mirror, in stream order."""
    h_d, h_c, link = mk_chaos_pair()
    ops = [(Operation.create_accounts, accounts([1, 2]))]
    h_d.submit(*ops[0])
    h_c.submit(*ops[0])
    link.fail_next(stage="fetch", kind="fatal")
    mixed = [
        (Operation.create_transfers, transfers(
            [dict(id=10, debit_account_id=1, credit_account_id=2,
                  amount=5)])),
        (Operation.lookup_accounts, hz.ids_bytes([1, 2])),
        (Operation.create_accounts, accounts([7])),
        (Operation.create_transfers, transfers(
            [dict(id=11, debit_account_id=7, credit_account_id=2,
                  amount=9)])),
        (Operation.lookup_accounts, hz.ids_bytes([1, 2, 7])),
    ]
    replay_pipelined(h_d, h_c, mixed)
    assert h_d.sm._dev.stat_demotions >= 1


def test_grow_while_degraded_then_repromote():
    """Capacity growth during an outage defers the HBM widen; the
    re-promotion upload must rebuild BOTH tables at the grown capacity
    and still pass the checksum handshake."""
    link = ChaosLink()
    sm_d = TpuStateMachine(
        engine="device", account_capacity=64, device_link=link
    )
    h_d = hz.SingleNodeHarness(sm_d)
    h_c = hz.SingleNodeHarness(CpuStateMachine())
    first = (Operation.create_accounts, accounts(range(1, 33)))
    assert h_d.submit(*first) == h_c.submit(*first)
    link.kill()
    burst = (Operation.create_accounts, accounts(range(33, 161)))
    assert h_d.submit(*burst) == h_c.submit(*burst)
    dev = sm_d._dev
    tx = [
        (Operation.create_transfers, transfers(
            [dict(id=100 + k, debit_account_id=1 + k,
                  credit_account_id=150 - k, amount=2 + k)]))
        for k in range(4)
    ]
    replay_pipelined(h_d, h_c, tx)
    assert dev.state is EngineState.degraded
    grown = dev.capacity
    assert grown >= 160
    link.heal()
    tail = [
        (Operation.create_transfers, transfers(
            [dict(id=200 + k, debit_account_id=10 + k,
                  credit_account_id=120 + k, amount=3 + k)]))
        for k in range(6)
    ]
    tail.append(
        (Operation.lookup_accounts, hz.ids_bytes(list(range(1, 161))))
    )
    replay_pipelined(h_d, h_c, tail)
    assert dev.state is EngineState.healthy
    assert int(dev.balances.shape[0]) == grown
    sm_d.verify_device_mirror()


def test_close_terminates_every_future():
    """DeviceEngine.close() resolves (host replay) or fails (typed
    error) every outstanding future — no caller is ever stranded."""
    h_d, _h_c, link = mk_chaos_pair()
    h_d.submit(Operation.create_accounts, accounts([1, 2]))
    fut = h_d.submit_async(
        Operation.create_transfers,
        transfers([dict(id=10, debit_account_id=1, credit_account_id=2,
                        amount=5)]),
    )
    link.kill()
    h_d.sm._dev.close()
    assert fut.done()
    fut.result()  # resolved exactly via host replay, not an assert


def test_reply_future_fail_is_typed():
    fut = de.ReplyFuture(None)
    with pytest.raises(de.DeviceLostError):
        fut.result()
    fut2 = de.ReplyFuture(None)
    fut2.fail(de.DeviceLostError("close", "boom"))
    assert fut2.done()
    with pytest.raises(de.DeviceLostError, match="close"):
        fut2.result()


def test_chaos_smoke_differential():
    """CI smoke (tier-1, CPU-only): ~1k seeded workload events through
    the device engine under probabilistic chaos at every stage —
    kills, fatal and transient faults — differentially checked against
    the pure-host oracle.  Fails on any reply mismatch or any
    permanently unresolved future; ends by healing and proving
    re-promotion passes the checksum handshake."""
    wl = Workload(1234)
    h_d, h_c, link = mk_chaos_pair(
        seed=99,
        p_transient=0.02,
        p_fatal=0.004,
        p_kill=0.002,
        down_for=6,
    )
    sent_events = 0
    pending: list = []
    ops_log: list = []
    while sent_events < 1000:
        operation, body, _must = wl.next_request()
        n = 1 if not body else len(body) // 128
        sent_events += n
        ops_log.append((operation, body))
        pending.append(h_d.submit_async(operation, body))
    replies_d = [f.result() for f in pending]  # no future may strand
    for f in pending:
        assert f.done()
    replies_c = [h_c.submit(op, body) for op, body in ops_log]
    mismatches = [
        i for i, (a, b) in enumerate(zip(replies_d, replies_c)) if a != b
    ]
    assert not mismatches, f"replies diverge at {mismatches[:5]}"

    dev = h_d.sm._dev
    # The schedule must actually have exercised the lifecycle.
    assert dev.stat_demotions >= 1, "chaos never demoted: weak smoke"
    assert dev.stat_retries >= 1
    # Heal and force the handshake: the engine must come back.
    link.heal()
    link.p_transient = link.p_fatal = link.p_kill = 0.0
    assert dev.try_repromote()
    assert dev.state is EngineState.healthy
    h_d.sm.verify_device_mirror()
    # And serve exactly after re-promotion.
    tail = simple_ops(n_batches=4, tid0=10_000_000)
    replay_pipelined(h_d, h_c, tail)


def test_demote_during_speculation_releases_whole_batch_bound(monkeypatch):
    """Regression (r18): a speculative record's admission charge is the
    WHOLE-batch superset — the same bound the wave path charges.  Kill
    the link with speculative batches in flight (mid-validation): the
    demotion replays them through the exact host fallback, the bound
    releases exactly once, and a sibling batch admitted after the
    replay sees the true mirror headroom — replies stay
    oracle-identical, nothing over-applies."""
    monkeypatch.setenv("TB_WAVES_SPECULATE", "force")
    h_d, h_c, link = mk_chaos_pair()
    ops = [(Operation.create_accounts, accounts(range(1, 21)))]
    h_d.submit(*ops[0])
    h_c.submit(*ops[0])
    # Fatal loss at the dispatch stage: the speculative step (or
    # its validation fetch) dies with the record in flight.
    link.fail_next(stage="dispatch", kind="fatal")
    mixed = []
    tid = 100
    for k in range(6):
        rows = [
            dict(id=tid + j, debit_account_id=1 + (k + j) % 20,
                 credit_account_id=1 + (k + j + 1) % 20,
                 amount=(1 << 40) + j)
            for j in range(4)
        ]
        tid += 4
        mixed.append((Operation.create_transfers, transfers(rows)))
    mixed.append((Operation.lookup_accounts,
                  hz.ids_bytes(list(range(1, 21)))))
    replay_pipelined(h_d, h_c, mixed)
    dev = h_d.sm._dev
    assert dev.stat_demotions >= 1, "fault never hit a record in flight"
    assert dev.inflight_bound() == 0, (
        "speculative record leaked (or double-released) its "
        "admission bound across the demotion replay"
    )
    # Post-heal batches must re-admit against the true mirror state.
    link.heal()
    tail = [
        (Operation.create_transfers, transfers(
            [dict(id=900 + k, debit_account_id=1 + k,
                  credit_account_id=2 + k, amount=7)]))
        for k in range(4)
    ]
    replay_pipelined(h_d, h_c, tail)
    assert dev.inflight_bound() == 0
    h_d.sm.verify_device_mirror()


def test_vopr_device_loss_nemesis():
    """Whole-cluster VOPR with the device-loss nemesis: replicas run
    the device engine behind seeded chaos links that die and heal at
    different times; linearization, convergence, conservation, and
    restart-replay equivalence must all hold."""
    v = Vopr(
        21, requests=18, packet_loss=0.0, crash_probability=0.0,
        device_loss_probability=0.04,
    )
    v.run()
    assert v._chaos_links, "device-loss nemesis built no chaos links"
    kills = sum(link.stat_kills for link in v._chaos_links)
    demotions = sum(
        r.sm._dev.stat_demotions
        for r in v.cluster.replicas
        if getattr(r.sm, "engine", "") == "device"
    )
    assert kills >= 1, "nemesis never killed a link: weak seed"
    assert demotions >= 1, "kills never demoted an engine"
