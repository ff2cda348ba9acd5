"""Wave dispatch inside the device engine's window launch.

Three layers:

1. Partitioner microtests: the vectorized wavefront level assigner
   (waves._levels_wavefront) against the Python-walk oracle
   (plan_waves(use_walk=True)) over fuzzed metadata, and the
   <100 µs planning budget for an 8k fresh-ids batch.
2. Window acceptance shapes: a two_phase pending/finalize stream that
   previously drained to the host executes inside the device window
   as <=2 wave steps per batch, and a chain-dominated linked batch of
   independent chains executes in ~max_chain_len device steps (not
   ~B) — both with replies byte-identical to the CPU oracle.
3. Forced-on vs forced-off differential fuzz: full device-engine
   windows (mixed kinds, two-phase, chains, duplicate ids, timeouts,
   grow/remove interleavings) run with TB_DEV_WAVES=1 and
   TB_DEV_WAVES=0; replies, final wire state, and the authoritative
   device table must be byte-identical.  Plus a chaos smoke with wave
   dispatch forced on (the degraded-mode lifecycle must keep working).
"""

import time

import numpy as np
import pytest

import tigerbeetle_tpu.state_machine.device_engine as de
from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine import resolve, waves
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.testing.chaos import ChaosLink
from tigerbeetle_tpu.types import EngineState, Operation, TransferFlags

TF = TransferFlags
AF = types.AccountFlags


# ---------------------------------------------------------------------------
# Partitioner: vectorized wavefront vs the Python-walk oracle.


def _random_meta(rng, n):
    flags = np.zeros(n, np.uint32)
    flags[rng.random(n) < 0.2] |= int(TF.linked)
    flags[rng.random(n) < 0.1] |= int(TF.balancing_debit)
    pv = rng.random(n) < 0.25
    flags[pv] |= int(TF.post_pending_transfer)
    id_group = rng.integers(0, max(1, n // 2), n).astype(np.int64)
    p_group = np.where(
        pv & (rng.random(n) < 0.7), rng.integers(0, max(1, n // 2), n), -1
    ).astype(np.int32)
    p_found = pv & (p_group < 0) & (rng.random(n) < 0.5)
    p_tgt = np.where(
        p_found, rng.integers(0, max(1, n // 3), n), -1
    ).astype(np.int32)
    dr_flags = np.where(
        rng.random(n) < 0.15,
        np.uint32(AF.debits_must_not_exceed_credits),
        np.uint32(0),
    )
    return resolve.wave_dependency_metadata(
        n,
        flags,
        rng.integers(0, 6, n).astype(np.int64),
        rng.integers(6, 12, n).astype(np.int64),
        dr_flags,
        np.zeros(n, np.uint32),
        id_group,
        p_group,
        p_tgt,
        p_found,
        np.where(p_found, rng.integers(0, 6, n), -1).astype(np.int64),
        np.where(p_found, rng.integers(6, 12, n), -1).astype(np.int64),
    )


def _plans_equal(a, b):
    assert len(a.segments) == len(b.segments)
    for (ka, ia), (kb, ib) in zip(a.segments, b.segments):
        assert ka == kb
        assert np.array_equal(np.asarray(ia), np.asarray(ib))
    assert a.chain_steps == b.chain_steps
    assert np.array_equal(a.wave_mask, b.wave_mask)
    assert a.n_steps == b.n_steps


@pytest.mark.parametrize("seed", range(20))
def test_vectorized_partitioner_matches_walk_oracle(seed):
    """The wavefront level assigner and the per-event Python walk must
    emit IDENTICAL plans (segment kinds, index sets, step counts) for
    arbitrary dependency metadata."""
    rng = np.random.default_rng(1000 + seed)
    for _ in range(8):
        n = int(rng.integers(2, 120))
        meta = _random_meta(rng, n)
        _plans_equal(
            waves.plan_waves(n, meta),
            waves.plan_waves(n, meta, use_walk=True),
        )


def test_wavefront_cap_falls_back_to_walk():
    """A fully serial region (every event reads+writes one hot slot via
    balancing) exceeds the wavefront round cap; the fallback walk must
    yield the same (degenerate, one-event-per-wave) plan."""
    n = 80
    flags = np.full(n, int(TF.balancing_debit), np.uint32)
    meta = resolve.wave_dependency_metadata(
        n, flags,
        np.zeros(n, np.int64), np.ones(n, np.int64),
        np.zeros(n, np.uint32), np.zeros(n, np.uint32),
        np.arange(n), np.full(n, -1, np.int32), np.full(n, -1, np.int32),
        np.zeros(n, bool), np.full(n, -1, np.int64),
        np.full(n, -1, np.int64),
    )
    fast = waves.plan_waves(n, meta)
    walk = waves.plan_waves(n, meta, use_walk=True)
    _plans_equal(fast, walk)
    assert fast.n_steps == n  # true serial dependency chain


def test_plan_waves_8k_fresh_under_100us():
    """Planning an 8k fresh-ids batch (the dominant shape) must cost
    <100 µs — it runs inside every window launch."""
    n = 8192
    meta = resolve.wave_dependency_metadata(
        n, np.zeros(n, np.uint32),
        np.arange(n, dtype=np.int64),
        np.arange(n, 2 * n, dtype=np.int64),
        np.zeros(n, np.uint32), np.zeros(n, np.uint32),
        np.arange(n), np.full(n, -1, np.int32), np.full(n, -1, np.int32),
        np.zeros(n, bool), np.full(n, -1, np.int64),
        np.full(n, -1, np.int64),
    )
    waves.plan_waves(n, meta)  # warm any lazy imports
    best = float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        waves.plan_waves(n, meta)
        best = min(best, time.perf_counter() - t0)
    assert best < 100e-6, f"plan_waves took {best * 1e6:.0f} µs"


# ---------------------------------------------------------------------------
# Window acceptance shapes.


def accounts(ids, flags=0):
    return hz.pack([hz.account(i, flags=flags) for i in ids])


def mk_pair(**tpu_kw):
    # Odd capacity: the test mesh exposes 8 virtual CPU devices, and a
    # device-divisible capacity would shard the engine — these tests
    # pin the SINGLE-CHIP executors (the sharded tests below use
    # mk_pair_sharded, whose capacity divides the mesh).
    sm_d = TpuStateMachine(
        engine="device",
        account_capacity=tpu_kw.pop("account_capacity", (1 << 12) + 1),
        **tpu_kw,
    )
    assert sm_d._dev.sharding is None
    return hz.SingleNodeHarness(sm_d), hz.SingleNodeHarness(CpuStateMachine())


def mk_pair_sharded(**tpu_kw):
    # Device-divisible capacity on the 8-device test mesh: the engine
    # row-shards its tables and wave plans execute SPMD (shard_map
    # over the ("shard",) mesh).
    sm_d = TpuStateMachine(
        engine="device",
        account_capacity=tpu_kw.pop("account_capacity", 1 << 12),
        **tpu_kw,
    )
    assert sm_d._dev.sharding is not None
    assert sm_d._dev.wave_mesh() is not None
    return hz.SingleNodeHarness(sm_d), hz.SingleNodeHarness(CpuStateMachine())


def replay_both(h_d, h_c, ops):
    futs = [h_d.submit_async(op, body) for op, body in ops]
    replies_d = [f.result() for f in futs]
    replies_c = [h_c.submit(op, body) for op, body in ops]
    for i, (a, b) in enumerate(zip(replies_d, replies_c)):
        assert a == b, f"reply {i} differs: {ops[i][0]!r}"
    return replies_d


def _pv_balancing_batch(tid, accs, rng, bal_accs=None):
    """(pending, post) pairs plus balancing singles: has_bal falls off
    every semantic kernel, previously draining the whole batch to the
    host.  `bal_accs`: dedicated per-event account pairs for the
    balancing riders (disjoint slots keep their reads independent of
    the pairs' writes — the acceptance-shape variant); default samples
    from the shared pool (overlap allowed, fuzz variant)."""
    rows = []
    for _ in range(6):
        a, b = rng.choice(accs, 2, replace=False)
        rows.append(
            hz.transfer(tid, debit_account_id=int(a),
                        credit_account_id=int(b),
                        amount=int(rng.integers(1, 50)),
                        flags=int(TF.pending))
        )
        rows.append(
            hz.transfer(tid + 1, amount=0, pending_id=tid,
                        flags=int(TF.post_pending_transfer))
        )
        tid += 2
    for k in range(3):
        if bal_accs is not None:
            a, b = bal_accs[2 * k], bal_accs[2 * k + 1]
        else:
            a, b = rng.choice(accs, 2, replace=False)
        rows.append(
            hz.transfer(tid, debit_account_id=int(a),
                        credit_account_id=int(b),
                        amount=int(rng.integers(1, 20)),
                        flags=int(TF.balancing_debit))
        )
        tid += 1
    return rows, tid


def test_two_phase_stream_waves_in_window(monkeypatch):
    """Acceptance: a pending/finalize stream the semantic kernels
    cannot express executes INSIDE the device window as <=2 wave steps
    per batch — no host drain — with oracle-identical replies."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    rng = np.random.default_rng(7)
    h_d, h_c = mk_pair()
    setup = (Operation.create_accounts, accounts(range(1, 47)))
    ops = [setup]
    accs = np.arange(1, 41)
    tid = 100
    for _ in range(6):
        rows, tid = _pv_balancing_batch(
            tid, accs, rng, bal_accs=list(range(41, 47))
        )
        ops.append((Operation.create_transfers, hz.pack(rows)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 47)))))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm.stat_dev_wave_batches == 6, "wave dispatch did not engage"
    assert sm.stat_host_semantic_events == 0, "batch drained to the host"
    # Steps live on either side of the r18 speculation split: wave-plan
    # steps in dev_wave.steps, speculative + residue steps in
    # dev_wave.spec.steps — combined, pairs still collapse to <=2.
    steps = sm.stat_dev_wave_steps + sm._dev.spec_stats["steps"].value
    assert steps <= 2 * sm.stat_dev_wave_batches, (
        f"{steps} steps for {sm.stat_dev_wave_batches} "
        "batches — two_phase pairs must collapse to <=2 waves"
    )
    sm.verify_device_mirror()


def test_chain_batch_waves_in_window(monkeypatch):
    """Acceptance: a chain-dominated linked batch of independent
    chains (with pending members, so the device `linked` kernel
    declines it) executes in ~max_chain_len device steps, not ~B."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts(range(1, 101)))]
    tid = 100
    for _b in range(3):
        rows = []
        for c in range(16):  # 16 independent chains x 3 members
            for j in range(3):
                f = int(TF.linked) if j < 2 else 0
                if j == 0:
                    f |= int(TF.pending)
                rows.append(
                    hz.transfer(
                        tid, debit_account_id=1 + 2 * c,
                        credit_account_id=2 + 2 * c,
                        amount=3 + j, flags=f,
                    )
                )
                tid += 1
        ops.append((Operation.create_transfers, hz.pack(rows)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 101)))))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm.stat_dev_wave_batches == 3
    assert sm.stat_host_semantic_events == 0
    # 48 members/batch; the position-stepped executor pays the padded
    # max_chain_len bucket (8), nowhere near one step per member.
    assert sm.stat_dev_wave_steps == 3 * 8, (
        f"{sm.stat_dev_wave_steps} steps for 3 chain batches"
    )
    sm.verify_device_mirror()


def test_dev_waves_off_drains_to_host(monkeypatch):
    """TB_DEV_WAVES=0 keeps the r7 behavior: off-kernel batches drain
    and run host-side (the differential fuzz's control arm really is
    the old path)."""
    monkeypatch.setenv("TB_DEV_WAVES", "0")
    rng = np.random.default_rng(8)
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts(range(1, 41)))]
    rows, _ = _pv_balancing_batch(100, np.arange(1, 41), rng)
    ops.append((Operation.create_transfers, hz.pack(rows)))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm.stat_dev_wave_batches == 0
    assert sm.stat_host_semantic_events > 0


def test_degraded_admission_counts_inflight_bound(monkeypatch):
    """Near-overflow balances: a second wave batch planned while the
    first is still in flight must count the first's amount bound on
    top of the (lagging) mirror and decline — serving exactly via the
    host instead of executing an unsound plan."""
    monkeypatch.setattr(de, "_WINDOW", 64)
    h_d, h_c = mk_pair()
    big = (1 << 127) + 5
    ops = [(Operation.create_accounts, accounts([1, 2, 3, 4]))]
    # Two off-kernel batches (balancing rider) pushing the same column
    # toward 2^128 while pipelined in one window.
    for k, tid in ((0, 100), (1, 200)):
        ops.append(
            (
                Operation.create_transfers,
                hz.pack(
                    [
                        hz.transfer(tid, debit_account_id=1,
                                    credit_account_id=2, amount=big),
                        hz.transfer(tid + 1, debit_account_id=3,
                                    credit_account_id=4, amount=5,
                                    flags=int(TF.balancing_debit)),
                    ]
                ),
            )
        )
    ops.append((Operation.lookup_accounts, hz.ids_bytes([1, 2, 3, 4])))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    # First batch may wave (headroom exists); the second must decline
    # (mirror + in-flight bound exceeds u128 headroom).
    assert sm.stat_dev_wave_batches <= 1
    assert sm.stat_dev_wave_declined >= 1
    sm.verify_device_mirror()


def test_wave_records_across_exact_recovery(monkeypatch):
    """A window holding [wave batch, flagged semantic batch, wave
    batch]: recovery must resolve the first wave record from its
    already-computed output, host-re-execute the flagged batch, and
    RE-EXECUTE the second wave record against the rebuilt table — all
    replies oracle-identical, no bound leaked."""
    monkeypatch.setattr(de, "_WINDOW", 8)
    rng = np.random.default_rng(9)
    h_d, h_c = mk_pair()
    ops = [(Operation.create_accounts, accounts(range(1, 47))
            + accounts([47], flags=int(AF.debits_must_not_exceed_credits))
            + accounts([48]))]
    accs = np.arange(1, 41)
    rows1, tid = _pv_balancing_batch(100, accs, rng, bal_accs=list(range(41, 47)))
    ops.append((Operation.create_transfers, hz.pack(rows1)))
    # One transfer of 2^62 onto a limit account: the linked kernel's
    # u64-safe bound refuses it -> FLAG_PRECOND -> exact recovery (the
    # bound is far under the u128 headroom: later admissions stand).
    ops.append(
        (
            Operation.create_transfers,
            hz.pack(
                [
                    hz.transfer(500, debit_account_id=48,
                                credit_account_id=47, amount=1 << 62)
                ]
            ),
        )
    )
    rows3, _ = _pv_balancing_batch(700, accs, rng, bal_accs=list(range(41, 47)))
    ops.append((Operation.create_transfers, hz.pack(rows3)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 47)))))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm._dev.stat_fallback_batches >= 1, "recovery never ran"
    assert sm.stat_dev_wave_batches == 2, "wave records missing"
    assert sm._dev.inflight_bound() == 0, "in-flight bound leaked"
    sm.verify_device_mirror()


# ---------------------------------------------------------------------------
# Forced-on vs forced-off differential fuzz over full windows.


def _fuzz_stream(rng, n_accts=60):
    """Ops mixing every routing class: semantic-kernel batches, wave
    batches (pv pairs + balancing, chains with pendings, duplicate
    ids, timeouts), account creation mid-stream (grow), a failing
    linked account chain (remove), and lookups."""
    ops = [(Operation.create_accounts, accounts(range(1, n_accts + 1)))]
    accs = np.arange(1, n_accts + 1)
    tid = 1000
    ids = []
    for k in range(14):
        r = rng.random()
        rows = []
        if r < 0.2:
            # Plain fresh batch -> orderfree semantic kernel.
            for _ in range(8):
                a, b = rng.choice(accs, 2, replace=False)
                rows.append(
                    hz.transfer(tid, debit_account_id=int(a),
                                credit_account_id=int(b),
                                amount=int(rng.integers(1, 90)))
                )
                ids.append(tid)
                tid += 1
        elif r < 0.45:
            rows, tid0 = _pv_balancing_batch(tid, accs, rng)
            ids.extend(range(tid, tid0))
            tid = tid0
            if rng.random() < 0.4 and ids:
                # Duplicate id rider: ids_unique fails -> off-kernel.
                rows.append(
                    hz.transfer(int(rng.choice(ids)),
                                debit_account_id=1, credit_account_id=2,
                                amount=1)
                )
        elif r < 0.7:
            # Independent chains, some pending members, some timeouts.
            for c in range(6):
                clen = int(rng.integers(2, 5))
                for j in range(clen):
                    f = int(TF.linked) if j < clen - 1 else 0
                    timeout = 0
                    if rng.random() < 0.3:
                        f |= int(TF.pending)
                        if rng.random() < 0.3:
                            timeout = int(rng.integers(1, 4))
                    a, b = rng.choice(accs, 2, replace=False)
                    rows.append(
                        hz.transfer(tid, debit_account_id=int(a),
                                    credit_account_id=int(b),
                                    amount=int(rng.integers(1, 40)),
                                    timeout=timeout, flags=f)
                    )
                    ids.append(tid)
                    tid += 1
        elif r < 0.8:
            # Account burst (meta records + possible grow) and a
            # failing linked account chain (rollback -> remove).
            base = n_accts + 1 + k * 40
            ops.append(
                (Operation.create_accounts,
                 accounts(range(base, base + 30)))
            )
            ops.append(
                (
                    Operation.create_accounts,
                    hz.pack(
                        [
                            hz.account(base + 30, flags=int(AF.linked)),
                            hz.account(1),  # duplicate -> chain fails
                        ]
                    ),
                )
            )
            continue
        else:
            ops.append(
                (
                    Operation.lookup_accounts,
                    hz.ids_bytes(
                        [int(x) for x in rng.choice(accs, 10, replace=False)]
                    ),
                )
            )
            continue
        ops.append((Operation.create_transfers, hz.pack(rows)))
    ops.append(
        (Operation.lookup_accounts, hz.ids_bytes([int(x) for x in accs]))
    )
    if ids:
        ops.append(
            (Operation.lookup_transfers,
             hz.ids_bytes([int(x) for x in sorted(set(ids))]))
        )
    return ops


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_device_waves_forced_on_off_differential(monkeypatch, seed):
    """Full device-engine windows with wave dispatch forced ON vs
    forced OFF: every reply, the final wire state, and the
    authoritative device table must be byte-identical — the wave plan
    is an execution strategy, never a semantics change."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    replies = {}
    tables = {}
    for mode in ("1", "0"):
        monkeypatch.setenv("TB_DEV_WAVES", mode)
        rng = np.random.default_rng(seed)
        sm = TpuStateMachine(engine="device", account_capacity=65)
        h = hz.SingleNodeHarness(sm)
        ops = _fuzz_stream(rng)
        futs = [h.submit_async(op, body) for op, body in ops]
        replies[mode] = [f.result() for f in futs]
        sm.verify_device_mirror()
        tables[mode] = np.asarray(sm._dev.checksum())
        if mode == "1":
            assert sm.stat_dev_wave_batches > 0, "fuzz never waved: vacuous"
        else:
            assert sm.stat_dev_wave_batches == 0
        del sm, h
    for i, (a, b) in enumerate(zip(replies["1"], replies["0"])):
        assert a == b, f"seed {seed}: reply {i} diverges (waves on vs off)"
    assert (tables["1"] == tables["0"]).all(), (
        "authoritative table diverges between wave-on and wave-off"
    )


def test_chaos_smoke_with_waves_on(monkeypatch):
    """Probabilistic link chaos with wave dispatch forced on: demote /
    degraded-serve / re-promote must keep every reply oracle-identical
    — wave records replay through their exact host fallback like any
    other in-flight record."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    monkeypatch.setattr(de, "_BACKOFF_MS", 0.0)
    monkeypatch.setattr(de, "_PROBE_EVERY", 2)
    monkeypatch.setenv("TB_DEV_WAVES", "1")
    rng = np.random.default_rng(5)
    link = ChaosLink(
        seed=17, p_transient=0.05, p_fatal=0.0, p_kill=0.0
    )
    sm_d = TpuStateMachine(
        engine="device", account_capacity=(1 << 10) + 1, device_link=link
    )
    h_d = hz.SingleNodeHarness(sm_d)
    h_c = hz.SingleNodeHarness(CpuStateMachine())
    ops = _fuzz_stream(rng, n_accts=40)
    futs = []
    for k, (op, body) in enumerate(ops):
        if k in (len(ops) // 3, 2 * len(ops) // 3):
            # Deterministic mid-stream losses: wave records must be in
            # flight when the link dies, replaying via host fallback.
            link.fail_next(kind="fatal")
        futs.append(h_d.submit_async(op, body))
    replies_d = [f.result() for f in futs]
    for f in futs:
        assert f.done()
    replies_c = [h_c.submit(op, body) for op, body in ops]
    mismatches = [
        i for i, (a, b) in enumerate(zip(replies_d, replies_c)) if a != b
    ]
    assert not mismatches, f"replies diverge at {mismatches[:5]}"
    dev = sm_d.sm._dev if hasattr(sm_d, "sm") else sm_d._dev
    assert dev.stat_demotions >= 1, "chaos never demoted: weak smoke"
    link.heal()
    link.p_transient = link.p_fatal = link.p_kill = 0.0
    assert dev.try_repromote()
    assert dev.state is EngineState.healthy
    sm_d.verify_device_mirror()


# ---------------------------------------------------------------------------
# SPMD wave dispatch on the row-sharded engine (the conftest mesh
# exposes 8 virtual CPU devices; a device-divisible capacity shards
# the engine's tables with NamedSharding over a ("shard",) mesh and
# the wave plans execute through waves._execute_plan_sharded).


def test_sharded_two_phase_stream_waves_in_window(monkeypatch):
    """Acceptance: the off-kernel pending/finalize stream executes
    INSIDE the window of a ROW-SHARDED engine — no decline, every plan
    SPMD over the mesh, replies oracle-identical — and the pending
    wave records hold compact columns, >= 10x smaller than the padded
    event dicts they replace."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    rng = np.random.default_rng(7)
    h_d, h_c = mk_pair_sharded()
    setup = (Operation.create_accounts, accounts(range(1, 47)))
    ops = [setup]
    accs = np.arange(1, 41)
    tid = 100
    for _ in range(6):
        rows, tid = _pv_balancing_batch(
            tid, accs, rng, bal_accs=list(range(41, 47))
        )
        ops.append((Operation.create_transfers, hz.pack(rows)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 47)))))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm.stat_dev_wave_batches == 6, "sharded engine declined waves"
    assert sm.stat_dev_wave_declined == 0, (
        sm.stat_dev_wave_decline_reasons
    )
    assert sm.stat_host_semantic_events == 0, "batch drained to the host"
    assert sm._dev.stat_wave_sharded >= 6, "plans did not execute SPMD"
    assert sm.stat_dev_wave_steps <= 2 * sm.stat_dev_wave_batches
    assert sm._dev.stat_wave_window_bytes_peak > 0
    reduction = (
        sm._dev.stat_wave_window_padded_peak
        / sm._dev.stat_wave_window_bytes_peak
    )
    assert reduction >= 10, (
        f"pending wave records only {reduction:.1f}x smaller than the "
        "padded event dicts"
    )
    sm.verify_device_mirror()


def test_sharded_chain_batch_waves_in_window(monkeypatch):
    """The chain-wave scan (one lax.scan over chain position) also
    runs SPMD: per-position sharded row updates, ~max_chain_len steps,
    oracle-identical replies."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    h_d, h_c = mk_pair_sharded()
    ops = [(Operation.create_accounts, accounts(range(1, 101)))]
    tid = 100
    for _b in range(3):
        rows = []
        for c in range(16):
            for j in range(3):
                f = int(TF.linked) if j < 2 else 0
                if j == 0:
                    f |= int(TF.pending)
                rows.append(
                    hz.transfer(
                        tid, debit_account_id=1 + 2 * c,
                        credit_account_id=2 + 2 * c,
                        amount=3 + j, flags=f,
                    )
                )
                tid += 1
        ops.append((Operation.create_transfers, hz.pack(rows)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 101)))))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm.stat_dev_wave_batches == 3
    assert sm.stat_dev_wave_declined == 0
    assert sm._dev.stat_wave_sharded >= 3
    assert sm.stat_dev_wave_steps == 3 * 8
    sm.verify_device_mirror()


def test_sharded_chain_rollback_in_window(monkeypatch):
    """A failing chain member (debit == credit: static ladder) rolls
    its whole chain back through the SPMD trailing-subtraction repair
    while sibling chains apply — oracle-identical replies and mirror."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    h_d, h_c = mk_pair_sharded()
    ops = [(Operation.create_accounts, accounts(range(1, 41)))]
    rows = []
    tid = 100
    for c in range(8):
        for j in range(3):
            f = int(TF.linked) if j < 2 else 0
            if j == 0:
                f |= int(TF.pending)
            dr, cr = 1 + 2 * c, 2 + 2 * c
            if c == 3 and j == 1:
                cr = dr  # accounts_must_be_different -> chain fails
            rows.append(
                hz.transfer(tid, debit_account_id=dr,
                            credit_account_id=cr, amount=3 + j, flags=f)
            )
            tid += 1
    ops.append((Operation.create_transfers, hz.pack(rows)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 41)))))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm.stat_dev_wave_batches == 1, "chain batch did not wave"
    assert sm._dev.stat_wave_sharded >= 1
    sm.verify_device_mirror()


def test_sharded_plan_with_scan_segment_declines(monkeypatch):
    """Unsupported plan shapes DECLINE, never error: history-account
    events force exact scan segments, which have no SPMD executor —
    the sharded engine counts the decline by reason and drains to the
    host, replies still oracle-identical."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    monkeypatch.setenv("TB_DEV_WAVES", "1")
    rng = np.random.default_rng(13)
    h_d, h_c = mk_pair_sharded()
    ops = [
        (
            Operation.create_accounts,
            hz.pack(
                [hz.account(i) for i in range(1, 41)]
                + [
                    hz.account(41, flags=int(AF.history)),
                    hz.account(42, flags=int(AF.history)),
                ]
            ),
        )
    ]
    rows = []
    tid = 100
    for _ in range(20):
        a, b = rng.choice(np.arange(1, 41), 2, replace=False)
        rows.append(
            hz.transfer(tid, debit_account_id=int(a),
                        credit_account_id=int(b),
                        amount=int(rng.integers(1, 40)),
                        flags=int(TF.pending))  # off the orderfree route
        )
        tid += 1
    rows.append(
        hz.transfer(tid, debit_account_id=41, credit_account_id=42,
                    amount=5, flags=int(TF.pending))
    )
    ops.append((Operation.create_transfers, hz.pack(rows)))
    ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 43)))))
    replay_both(h_d, h_c, ops)
    sm = h_d.sm
    assert sm.stat_dev_wave_batches == 0
    assert sm.stat_dev_wave_decline_reasons.get("shard_plan", 0) >= 1, (
        sm.stat_dev_wave_decline_reasons
    )
    assert sm.stat_host_semantic_events > 0, "decline must drain to host"
    sm.verify_device_mirror()


@pytest.mark.parametrize("seed", [31, 32])
def test_sharded_waves_differential(monkeypatch, seed):
    """Three arms over the SAME fuzz stream — sharded waves forced on,
    sharded waves off (drain), unsharded waves forced on — must agree
    byte-for-byte on every reply; the two sharded arms must also agree
    on the authoritative table digest.  The SPMD executors are an
    execution strategy, never a semantics change."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    replies = {}
    tables = {}
    arms = (
        ("sharded_on", 1 << 10, "1"),
        ("sharded_off", 1 << 10, "0"),
        ("unsharded_on", (1 << 10) + 1, "1"),
    )
    for name, capacity, mode in arms:
        monkeypatch.setenv("TB_DEV_WAVES", mode)
        rng = np.random.default_rng(seed)
        sm = TpuStateMachine(engine="device", account_capacity=capacity)
        sharded = capacity % 8 == 0
        assert (sm._dev.sharding is not None) == sharded
        h = hz.SingleNodeHarness(sm)
        ops = _fuzz_stream(rng)
        futs = [h.submit_async(op, body) for op, body in ops]
        replies[name] = [f.result() for f in futs]
        sm.verify_device_mirror()
        if sharded:
            tables[name] = np.asarray(sm._dev.checksum())
        if mode == "1":
            assert sm.stat_dev_wave_batches > 0, f"{name}: never waved"
            if sharded:
                assert sm._dev.stat_wave_sharded > 0
        else:
            assert sm.stat_dev_wave_batches == 0
        del sm, h
    for arm in ("sharded_off", "unsharded_on"):
        for i, (a, b) in enumerate(zip(replies["sharded_on"], replies[arm])):
            assert a == b, (
                f"seed {seed}: reply {i} diverges (sharded_on vs {arm})"
            )
    assert (tables["sharded_on"] == tables["sharded_off"]).all(), (
        "authoritative table diverges between sharded wave-on and -off"
    )


def test_sharded_chaos_smoke_with_waves_on(monkeypatch):
    """Link chaos on the ROW-SHARDED engine with wave dispatch forced
    on: demote / degraded-serve / re-promote keep every reply
    oracle-identical — sharded wave records replay through their exact
    host fallback like any other in-flight record."""
    monkeypatch.setattr(de, "_WINDOW", 4)
    monkeypatch.setattr(de, "_BACKOFF_MS", 0.0)
    monkeypatch.setattr(de, "_PROBE_EVERY", 2)
    monkeypatch.setenv("TB_DEV_WAVES", "1")
    rng = np.random.default_rng(5)
    link = ChaosLink(seed=23, p_transient=0.05, p_fatal=0.0, p_kill=0.0)
    sm_d = TpuStateMachine(
        engine="device", account_capacity=1 << 10, device_link=link
    )
    assert sm_d._dev.sharding is not None
    h_d = hz.SingleNodeHarness(sm_d)
    h_c = hz.SingleNodeHarness(CpuStateMachine())
    ops = _fuzz_stream(rng, n_accts=40)
    futs = []
    for k, (op, body) in enumerate(ops):
        if k in (len(ops) // 3, 2 * len(ops) // 3):
            link.fail_next(kind="fatal")
        futs.append(h_d.submit_async(op, body))
    replies_d = [f.result() for f in futs]
    for f in futs:
        assert f.done()
    replies_c = [h_c.submit(op, body) for op, body in ops]
    mismatches = [
        i for i, (a, b) in enumerate(zip(replies_d, replies_c)) if a != b
    ]
    assert not mismatches, f"replies diverge at {mismatches[:5]}"
    dev = sm_d._dev
    assert dev.stat_demotions >= 1, "chaos never demoted: weak smoke"
    link.heal()
    link.p_transient = link.p_fatal = link.p_kill = 0.0
    assert dev.try_repromote()
    assert dev.state is EngineState.healthy
    sm_d.verify_device_mirror()


# ---------------------------------------------------------------------------
# Pending wave-record compaction (waves.pack_wave_record).


def _random_event_dict(rng, n, B):
    from tigerbeetle_tpu.state_machine import kernel

    ev = {}
    for name, dtype in kernel.EVENT_FIELDS:
        dt = np.dtype(dtype)
        if name == "i":
            ev[name] = np.arange(B, dtype=dt)
            continue
        arr = np.zeros(B, dt)
        style = rng.random()
        if style < 0.25:
            pass  # all-zero column
        elif style < 0.45:
            arr[:n] = np.asarray(7, dt)  # constant
        elif dt.kind == "b":
            arr[:n] = rng.random(n) < 0.3
        elif dt.kind == "i":
            arr[:n] = rng.integers(-1, 50, n)
        else:
            hi = int(rng.choice([40, 70_000, 1 << 40]))
            arr[:n] = rng.integers(0, hi, n).astype(dt)
        ev[name] = arr
    return ev


@pytest.mark.parametrize("seed", range(4))
def test_pending_wave_record_codec_roundtrip(seed):
    """The columnar compaction is LOSSLESS for arbitrary event dicts:
    unpack(pack(ev)) reproduces every column bit-for-bit, dtype and
    padding included."""
    rng = np.random.default_rng(400 + seed)
    n = int(rng.integers(1, 200))
    B = 256
    ev = _random_event_dict(rng, n, B)
    dstat = np.zeros(B, np.uint32)
    dstat[: int(rng.integers(0, 5))] = 2
    hist_fix = np.zeros(B, bool)
    hist_fix[:n] = rng.random(n) < 0.8
    pk = waves.pack_wave_record(ev, dstat, hist_fix, n)
    ev2, dstat2, hist2 = waves.unpack_wave_record(pk)
    assert set(ev2) == set(ev)
    for name, arr in ev.items():
        got = ev2[name]
        assert got.dtype == arr.dtype, name
        assert np.array_equal(got, arr), name
    assert np.array_equal(dstat2, dstat) and dstat2.dtype == dstat.dtype
    assert np.array_equal(hist2, hist_fix) and hist2.dtype == hist_fix.dtype
    assert pk.nbytes < pk.padded_nbytes


def test_pending_wave_record_nonzero_padding_is_lossless():
    """A column with nonzero bytes PAST the batch length (not a shape
    the router produces, but the codec must never corrupt) is stored
    verbatim."""
    from tigerbeetle_tpu.state_machine import kernel

    rng = np.random.default_rng(9)
    B = 64
    ev = _random_event_dict(rng, 10, B)
    ev["amount_lo"][B - 1] = 77  # poison the padding
    pk = waves.pack_wave_record(ev, np.zeros(B, np.uint32),
                                np.zeros(B, bool), 10)
    ev2, _, _ = waves.unpack_wave_record(pk)
    for name, arr in ev.items():
        assert np.array_equal(ev2[name], arr), name
    del kernel
