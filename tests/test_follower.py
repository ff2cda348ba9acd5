"""Root-attested follower serving (round 19).

Layers under test, bottom-up:
- AofTail: offset-resumable chunked tailing, torn-tail-then-heal,
  mid-record truncation, corrupt-vs-torn classification.
- AOF repair-on-open + recovery gap-fill (the writer-side half of the
  follower's gap-free-stream contract).
- FollowerCore: attestation gate (unattested / lagging / poisoned /
  corrupt / gap / overload / not_readable), byte-charged read
  admission, bit-identical serving.
- The deterministic sim (SimFollower) differential: every read op the
  follower serves is byte-identical to the primary's executor.
- Pinned FollowerVopr seeds: crash mid-tail, torn AOF via upstream
  crash (incl. crash-inside-fsync), corrupt tailed sector, partition,
  lag — refuse-not-lie asserted throughout.
"""

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.runtime.follower import (
    FollowerCore,
    FollowerRefusal,
    FollowerReply,
)
from tigerbeetle_tpu.state_machine import CpuStateMachine
from tigerbeetle_tpu.testing.harness import account, ids_bytes, pack, transfer
from tigerbeetle_tpu.vsr import aof as aof_mod
from tigerbeetle_tpu.vsr import replica as vsr_replica
from tigerbeetle_tpu.vsr import wire
from tigerbeetle_tpu.vsr.aof import AOF, AofTail, BytesSource
from tigerbeetle_tpu.vsr.storage import MemoryStorage, ZoneLayout
from tigerbeetle_tpu.vsr.wire import FollowerRefuse

CLUSTER = 5


def _record(op: int, body: bytes = b"x" * 64, operation: int = 129,
            timestamp: int | None = None) -> bytes:
    h = wire.make_header(
        command=wire.Command.prepare, cluster=CLUSTER, op=op,
        operation=operation,
        timestamp=timestamp if timestamp is not None else op,
    )
    wire.finalize_header(h, body)
    return h.tobytes() + body


# ----------------------------------------------------------------------
# AofTail


def test_tail_resumes_from_offset():
    buf = bytearray(_record(1) + _record(2) + _record(3))
    tail = AofTail(BytesSource(buf))
    got = tail.poll()
    assert [int(h["op"]) for h, _b in got] == [1, 2, 3]
    assert not tail.torn and not tail.corrupt
    saved = tail.offset
    buf += _record(4) + _record(5)
    assert [int(h["op"]) for h, _ in tail.poll()] == [4, 5]
    # A fresh tail constructed at the saved offset resumes exactly.
    resumed = AofTail(BytesSource(buf), offset=saved)
    assert [int(h["op"]) for h, _ in resumed.poll()] == [4, 5]


def test_tail_torn_then_heal():
    rec = _record(2)
    buf = bytearray(_record(1) + rec[: len(rec) - 17])  # mid-record cut
    tail = AofTail(BytesSource(buf))
    assert [int(h["op"]) for h, _ in tail.poll()] == [1]
    at = tail.offset
    assert tail.torn and not tail.corrupt
    assert tail.poll() == [] and tail.offset == at  # parked, resumable
    buf += rec[len(rec) - 17:]  # the writer completes the record
    assert [int(h["op"]) for h, _ in tail.poll()] == [2]
    assert not tail.torn


def test_tail_mid_header_truncation():
    buf = bytearray(_record(1) + _record(2)[:100])  # inside the header
    tail = AofTail(BytesSource(buf))
    assert [int(h["op"]) for h, _ in tail.poll()] == [1]
    assert tail.torn and not tail.corrupt


def test_tail_corrupt_mid_file_refuses():
    buf = bytearray(_record(1) + _record(2) + _record(3))
    rec1 = len(_record(1))
    buf[rec1 + 40] ^= 0xFF  # inside record 2's header, records follow
    tail = AofTail(BytesSource(buf))
    got = tail.poll()
    assert [int(h["op"]) for h, _ in got] == [1]
    assert tail.corrupt and tail.corrupt_reason
    assert tail.poll() == []  # latched: never skips ahead


def test_tail_corrupt_body_mid_file_refuses():
    buf = bytearray(_record(1) + _record(2) + _record(3))
    rec1 = len(_record(1))
    buf[rec1 + 256 + 5] ^= 0xFF  # inside record 2's body
    tail = AofTail(BytesSource(buf))
    assert [int(h["op"]) for h, _ in tail.poll()] == [1]
    assert tail.corrupt


def test_tail_corruption_at_eof_reads_as_torn():
    # A damaged FINAL record cannot be distinguished from a crash
    # artifact — the conservative read is torn (stall), never serving.
    buf = bytearray(_record(1) + _record(2))
    buf[len(_record(1)) + 300] ^= 0xFF  # final record's body
    tail = AofTail(BytesSource(buf))
    assert [int(h["op"]) for h, _ in tail.poll()] == [1]
    assert tail.torn and not tail.corrupt


def test_tail_shrink_below_offset_waits():
    buf = bytearray(_record(1) + _record(2))
    tail = AofTail(BytesSource(buf))
    assert len(tail.poll()) == 2
    del buf[len(_record(1)):]  # writer crashed + repaired below us
    assert tail.poll() == []
    assert tail.torn and not tail.corrupt
    buf += _record(2)  # gap-fill re-appends the identical bytes
    assert tail.poll() == []  # boundary restored, nothing new yet
    buf += _record(3)
    assert [int(h["op"]) for h, _ in tail.poll()] == [3]


def test_tail_chunked_reads_cross_boundaries():
    big = _record(1, body=b"A" * 5000)
    buf = bytearray(big * 1)
    for op in range(2, 40):
        buf += _record(op, body=bytes([op % 256]) * 700)
    tail = AofTail(BytesSource(buf), chunk_bytes=1 << 12)  # < one record
    ops = [int(h["op"]) for h, _ in tail.poll()]
    assert ops == list(range(1, 40))


# ----------------------------------------------------------------------
# AOF writer: repair-on-open + recovery gap-fill


def _fresh_replica(storage, path):
    sm = CpuStateMachine(cfg.TEST_MIN)
    r = vsr_replica.Replica(storage, CLUSTER, sm, aof=AOF(path))
    r.open()
    return r


def test_aof_repair_truncates_torn_tail(tmp_path):
    path = str(tmp_path / "log.aof")
    storage = MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))
    vsr_replica.format(storage, CLUSTER)
    r = _fresh_replica(storage, path)
    r.on_request(types.Operation.create_accounts,
                 pack([account(1), account(2)]))
    r.on_request(
        types.Operation.create_transfers,
        pack([transfer(9, debit_account_id=1, credit_account_id=2,
                       amount=11)]),
    )
    r.aof.sync()
    r.aof.close()
    whole = open(path, "rb").read()
    # Tear the final record mid-body.
    open(path, "wb").write(whole[:-20])
    repaired = AOF(path)
    size = len(open(path, "rb").read())
    assert size < len(whole) - 20  # truncated to a record boundary
    entries = list(aof_mod.iterate(path))
    assert entries  # verified prefix intact
    assert repaired.last_op == max(int(h["op"]) for h, _ in entries)
    repaired.close()


def test_recovery_gap_fill_restores_stream(tmp_path):
    """A crash that erases the AOF's unsynced tail while the WAL kept
    the ops: recovery replay re-appends exactly the missing records,
    so a replay of the AOF reaches the identical state."""
    path = str(tmp_path / "log.aof")
    storage = MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))
    vsr_replica.format(storage, CLUSTER)
    r = _fresh_replica(storage, path)
    r.on_request(types.Operation.create_accounts,
                 pack([account(1), account(2)]))
    for k in range(3):
        r.on_request(
            types.Operation.create_transfers,
            pack([transfer(100 + k, debit_account_id=1,
                           credit_account_id=2, amount=5)]),
        )
    final_snapshot = r.sm.snapshot()
    r.aof.close()
    # Crash model: the last two records never hit the disk.
    whole = open(path, "rb").read()
    entries = list(aof_mod.iterate(path))
    keep = sum(int(h["size"]) for h, _ in entries[:-2])
    open(path, "wb").write(whole[:keep])
    # Restart over the same (synced) storage: recovery replays the WAL
    # and must gap-fill the AOF's lost tail.
    r2 = _fresh_replica(storage, path)
    assert r2.sm.snapshot() == final_snapshot
    r2.aof.sync()
    fresh = CpuStateMachine(cfg.TEST_MIN)
    aof_mod.replay(path, fresh, cluster=CLUSTER)
    assert fresh.snapshot() == final_snapshot
    ops = [int(h["op"]) for h, _ in aof_mod.iterate(path)]
    assert ops == sorted(ops) and len(set(ops)) == len(ops)
    assert max(ops) == r2.commit_min


# ----------------------------------------------------------------------
# FollowerCore over a single-replica primary (SimAof interface)


class _Primary:
    """Single-replica primary whose AOF is an in-memory buffer the
    core tails — the smallest honest commit-stream producer."""

    def __init__(self, root_ring: int = 1 << 12):
        from tigerbeetle_tpu.testing.cluster import SimAof

        self.aof = SimAof()
        self.storage = MemoryStorage(
            ZoneLayout(config=cfg.TEST_MIN)
        )
        vsr_replica.format(self.storage, CLUSTER)
        self.replica = vsr_replica.Replica(
            self.storage, CLUSTER, CpuStateMachine(cfg.TEST_MIN),
            aof=self.aof,
        )
        self.replica.open()
        self.replica.enable_root_ring(root_ring)

    def seed_accounts(self, n: int = 4):
        self.replica.on_request(
            types.Operation.create_accounts,
            pack([account(i) for i in range(1, n + 1)]),
        )

    def transfer(self, tid: int, dr: int = 1, cr: int = 2, amount: int = 7):
        self.replica.on_request(
            types.Operation.create_transfers,
            pack([transfer(tid, debit_account_id=dr, credit_account_id=cr,
                           amount=amount)]),
        )

    def attest(self, core: FollowerCore, at: int | None = None):
        r = self.replica
        op = r.commit_min if at is None else at
        root = r.root_at(op)
        assert root is not None, op
        core.on_attestation(root, op)


def _core(primary: _Primary, **kw) -> FollowerCore:
    kw.setdefault("staleness_ops", 8)
    return FollowerCore(
        primary.aof.source(), cluster=CLUSTER,
        state_machine=CpuStateMachine(cfg.TEST_MIN), **kw,
    )


def test_core_refuses_unattested_then_serves():
    p = _Primary()
    p.seed_accounts()
    p.transfer(900)
    core = _core(p)
    assert core.pump() > 0
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1, 2]))
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.unattested
    p.attest(core)
    assert core.refuse_reason() is None
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1, 2]))
    assert isinstance(got, FollowerReply)
    assert got.commit_min == p.replica.commit_min
    assert got.root == p.replica.root_at(p.replica.commit_min)
    want = p.replica.sm.execute_read(
        types.Operation.lookup_accounts, ids_bytes([1, 2])
    )
    assert got.body == want


def test_core_lagging_refusal_is_a_redirect():
    p = _Primary()
    p.seed_accounts()
    core = _core(p, staleness_ops=4)
    core.pump()
    p.attest(core)
    assert core.refuse_reason() is None
    # Commits continue; the follower does NOT pump (lag injection),
    # but hears about the primary's head via attestation.
    for k in range(6):
        p.transfer(1000 + k)
    p.attest(core)  # current head: lag estimate refreshes
    assert core.lag_ops() > 4
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]))
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.lagging
    snap = core.registry.snapshot()
    assert snap["follower.redirects"] == 1  # lagging = transient
    assert snap["follower.refused"] == 0    # no integrity refusal here
    assert snap["follower.lag_ops"] == core.lag_ops()
    # Catching up clears it.
    core.pump()
    p.attest(core)
    assert core.refuse_reason() is None


def test_core_poisons_on_root_mismatch():
    p = _Primary()
    p.seed_accounts()
    core = _core(p)
    core.pump()
    wrong = bytes(range(16))
    core.on_attestation(wrong, core.commit_min)
    assert core.poisoned
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]))
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.poisoned
    # Terminal: a later GOOD attestation does not resurrect it.
    p.attest(core)
    assert core.refuse_reason() == FollowerRefuse.poisoned
    assert core.registry.snapshot()["follower.attest_mismatch"] == 1


def test_core_gap_refuses():
    p = _Primary()
    p.seed_accounts()
    p.transfer(900)
    # Splice a middle record out of the log: op discontinuity.
    buf = p.aof.buffer
    tail = AofTail(BytesSource(buf))
    entries = tail.poll()
    assert len(entries) >= 3
    first = int(entries[0][0]["size"])
    second = int(entries[1][0]["size"])
    spliced = bytearray(bytes(buf[:first]) + bytes(buf[first + second:]))
    core = FollowerCore(
        BytesSource(spliced), cluster=CLUSTER,
        state_machine=CpuStateMachine(cfg.TEST_MIN), staleness_ops=8,
    )
    core.pump()
    assert core.gapped
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]))
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.gap


def test_core_corrupt_refuses():
    p = _Primary()
    p.seed_accounts()
    core = _core(p)
    core.pump()
    p.attest(core)
    assert core.refuse_reason() is None
    # Corrupt a byte AHEAD of the follower, then commit more so the
    # bad record is mid-file (unambiguously corrupt, not torn).
    at = len(p.aof.buffer) + 40
    p.transfer(901)
    p.transfer(902)
    p.aof.buffer[at] ^= 0xFF
    core.pump()
    assert core.tail.corrupt
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]))
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.corrupt
    assert core.registry.snapshot()["follower.tail_corrupt"] == 1


def test_core_not_readable():
    p = _Primary()
    p.seed_accounts()
    core = _core(p)
    core.pump()
    p.attest(core)
    got = core.serve(int(types.Operation.create_transfers), b"")
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.not_readable


def test_core_read_admission_charges_bytes():
    from tigerbeetle_tpu.qos import TenantQos

    p = _Primary()
    p.seed_accounts()
    qos = TenantQos(rate_bytes=100.0)  # burst = 100 body bytes
    core = _core(p, qos=qos)
    core.pump()
    p.attest(core)
    body = ids_bytes([1, 2, 3, 4])  # 64 body bytes
    t0 = 1_000_000_000
    assert isinstance(
        core.serve(int(types.Operation.lookup_accounts), body, now_ns=t0),
        FollowerReply,
    )
    got = core.serve(int(types.Operation.lookup_accounts), body, now_ns=t0)
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.overload
    # ~1 second refills the byte budget.
    assert isinstance(
        core.serve(int(types.Operation.lookup_accounts), body,
                   now_ns=t0 + 10**9),
        FollowerReply,
    )


def test_core_serves_all_read_ops_bit_identically():
    p = _Primary()
    p.replica.on_request(
        types.Operation.create_accounts,
        pack([account(i, flags=int(types.AccountFlags.history))
              for i in range(1, 5)]),
    )
    for k in range(6):
        p.transfer(700 + k, dr=1 + k % 3, cr=2 + k % 3, amount=3 + k)
    core = _core(p)
    core.pump()
    p.attest(core)
    filt = np.zeros(1, types.ACCOUNT_FILTER_DTYPE)[0]
    types.u128_set(filt, "account_id", 1)
    filt["limit"] = 100
    filt["flags"] = (types.AccountFilterFlags.debits
                     | types.AccountFilterFlags.credits)
    cases = [
        (types.Operation.lookup_accounts, ids_bytes([1, 2, 3, 4])),
        (types.Operation.lookup_transfers, ids_bytes([700, 701, 999])),
        (types.Operation.get_account_transfers, filt.tobytes()),
        (types.Operation.get_account_balances, filt.tobytes()),
    ]
    for op, body in cases:
        got = core.serve(int(op), body)
        assert isinstance(got, FollowerReply), (op, got)
        assert got.body == p.replica.sm.execute_read(op, body), op


def test_execute_read_has_no_state_effects():
    p = _Primary()
    p.seed_accounts()
    p.transfer(700)
    sm = p.replica.sm
    before = sm.snapshot()
    ts_before = (sm.commit_timestamp, sm.prepare_timestamp,
                 sm.pulse_next_timestamp)
    sm.execute_read(types.Operation.lookup_accounts, ids_bytes([1, 2]))
    filt = np.zeros(1, types.ACCOUNT_FILTER_DTYPE)[0]
    types.u128_set(filt, "account_id", 1)
    filt["limit"] = 8
    filt["flags"] = types.AccountFilterFlags.debits
    sm.execute_read(types.Operation.get_account_transfers, filt.tobytes())
    assert sm.snapshot() == before
    assert (sm.commit_timestamp, sm.prepare_timestamp,
            sm.pulse_next_timestamp) == ts_before


# ----------------------------------------------------------------------
# Deterministic sim: crash mid-tail + resume-offset stability


def test_sim_follower_crash_restart_reattests():
    from tigerbeetle_tpu.testing.cluster import Cluster, SimFollower

    c = Cluster(replica_count=2, seed=3, aof_replicas=(0,),
                root_ring=1 << 16)
    f = SimFollower(c, 0, staleness_ops=64)
    cl = c.client(0x900)
    cl.register()
    c.run_until(lambda: not cl.busy())
    acc = pack([account(i) for i in range(1, 4)])
    c.run_request(cl, types.Operation.create_accounts, acc)
    c.run_request(
        cl, types.Operation.create_transfers,
        pack([transfer(9, debit_account_id=1, credit_account_id=2,
                       amount=11)]),
    )
    c.settle()
    for _ in range(40):
        c.step()
    assert f.core.refuse_reason() is None
    got = f.read(types.Operation.lookup_accounts, ids_bytes([1, 2]))
    assert isinstance(got, FollowerReply)
    # kill -9 mid-tail: everything volatile dies with the process.
    f.crash_restart()
    got = f.read(types.Operation.lookup_accounts, ids_bytes([1, 2]))
    assert isinstance(got, FollowerRefusal)  # unattested again
    for _ in range(60):
        c.step()
    got = f.read(types.Operation.lookup_accounts, ids_bytes([1, 2]))
    assert isinstance(got, FollowerReply)
    want = c.replicas[0].sm.execute_read(
        types.Operation.lookup_accounts, ids_bytes([1, 2])
    )
    assert got.body == want
    f.check_never_lied()


# ----------------------------------------------------------------------
# Pinned VOPR seeds (tier-1): each locks a nemesis scenario the sweep
# surfaced.  The coverage asserts keep the seed honest — a code change
# that silently defuses the nemesis fails here, not in a soak.


@pytest.mark.parametrize(
    "seed, expect",
    [
        # Torn tail (upstream crash) + partition + pause + a corrupt
        # byte behind the read head; heals to a serving follower.
        (0, {"upstream_crashes": 1, "corruptions": 1, "end_ok": True}),
        # Corruption lands AHEAD: latched refuse-not-lie, reads ride
        # the primary fallback for the rest of the run.
        (1, {"end_corrupt": True, "fallbacks": True}),
        # Follower crash/restart x6 mid-tail; ends serving.
        (2, {"follower_crashes": 6, "end_ok": True}),
        # Crash INSIDE a covering fsync + corruption: both torn-tail
        # producers in one run.
        (4, {"fsync_crashes": 1, "end_corrupt": True}),
    ],
)
def test_follower_vopr_pinned(seed, expect):
    from tigerbeetle_tpu.testing.vopr import FollowerVopr

    v = FollowerVopr(seed)
    v.run()  # runs check_never_lied + liveness-after-heal internally
    assert v.reads_attempted > 0
    if expect.get("end_ok"):
        assert not v.follower.core.tail.corrupt
        assert not v.follower.core.gapped
        assert v.reads_served > 0
    if expect.get("end_corrupt"):
        assert v.follower.core.tail.corrupt
    if expect.get("fallbacks"):
        assert v.reads_fallback > 0
    for key in ("upstream_crashes", "corruptions", "follower_crashes",
                "fsync_crashes"):
        if key in expect:
            assert getattr(v, key) == expect[key], key
    assert not v.follower.core.poisoned


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(8, 24))
def test_follower_vopr_sweep(seed):
    from tigerbeetle_tpu.testing.vopr import FollowerVopr

    v = FollowerVopr(seed)
    v.run()


def test_core_incompatible_record_refuses_not_crashes():
    """A checksum-valid record the follower's state machine rejects
    (config/software mismatch — here: a batch above the follower's
    batch_max) latches a typed refusal instead of killing the
    process."""
    p = _Primary()
    p.seed_accounts()
    core = _core(p)
    core.pump()
    p.attest(core)
    assert core.refuse_reason() is None
    # Forge an oversized-but-valid committed record past the follower
    # config's batch_max (TEST_MIN), appended to the tailed log.
    n = cfg.TEST_MIN.batch_max(
        types.TRANSFER_DTYPE.itemsize, types.CREATE_RESULT_DTYPE.itemsize
    ) + 1
    rows = np.zeros(n, types.TRANSFER_DTYPE)
    rows["id_lo"] = np.arange(1, n + 1)
    rows["ledger"] = 1
    body = rows.tobytes()
    h = wire.make_header(
        command=wire.Command.prepare, cluster=CLUSTER,
        op=core.commit_min + 1,
        operation=int(types.Operation.create_transfers),
        timestamp=10**15,
    )
    wire.finalize_header(h, body)
    p.aof.buffer += h.tobytes() + body
    core.pump()
    assert core.incompatible
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]))
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.incompatible
    assert core.registry.snapshot()["follower.incompatible"] == 1


def test_core_replays_logically_batched_prepares():
    """vsr/multi.py packs several clients' create requests into ONE
    prepare (context = sub count, demux trailer appended) — the
    follower must commit the event bytes like the replica commit path
    does.  Surfaced by the read_scale bench: concurrent sessions
    coalesce, and a follower treating the trailer as events latched
    `incompatible` on every batched stream."""
    from tigerbeetle_tpu.state_machine import demuxer

    p = _Primary()
    p.seed_accounts()
    # Forge the batched record exactly as _primary_prepare_batch does:
    # two sub-requests' transfers concatenated + trailer, context=2.
    t1 = pack([transfer(501, debit_account_id=1, credit_account_id=2,
                        amount=3)])
    t2 = pack([transfer(502, debit_account_id=2, credit_account_id=1,
                        amount=4)])
    subs = [(0xAAA, 1, 1), (0xBBB, 1, 1)]
    body = t1 + t2 + demuxer.encode_trailer(subs)
    ts = p.replica.sm.commit_timestamp + 10
    h = wire.make_header(
        command=wire.Command.prepare, cluster=CLUSTER,
        op=p.replica.commit_min + 1,
        operation=int(types.Operation.create_transfers),
        timestamp=ts, context=2,
    )
    wire.finalize_header(h, body)
    p.aof.buffer += h.tobytes() + body
    core = _core(p)
    core.pump()
    assert not core.incompatible
    assert core.commit_min == p.replica.commit_min + 1
    got = core.serve(int(types.Operation.lookup_transfers),
                     ids_bytes([501, 502]))
    # Unattested refusal is fine — check the STATE instead: both
    # batched transfers applied.
    rows = core.sm.execute_read(
        types.Operation.lookup_transfers, ids_bytes([501, 502])
    )
    out = np.frombuffer(rows, types.TRANSFER_DTYPE)
    assert len(out) == 2
    assert sorted(int(r["amount_lo"]) for r in out) == [3, 4]


def test_aof_replay_handles_batched_prepares():
    from tigerbeetle_tpu.state_machine import demuxer

    p = _Primary()
    p.seed_accounts()
    t1 = pack([transfer(601, debit_account_id=1, credit_account_id=2,
                        amount=7)])
    subs = [(0xCCC, 1, 1)]
    body = t1 + demuxer.encode_trailer(subs)
    ts = p.replica.sm.commit_timestamp + 10
    h = wire.make_header(
        command=wire.Command.prepare, cluster=CLUSTER,
        op=p.replica.commit_min + 1,
        operation=int(types.Operation.create_transfers),
        timestamp=ts, context=1,
    )
    wire.finalize_header(h, body)
    p.aof.buffer += h.tobytes() + body
    # replay() consumes a file path.
    import tempfile

    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(bytes(p.aof.buffer))
        path = f.name
    fresh = CpuStateMachine(cfg.TEST_MIN)
    aof_mod.replay(path, fresh, cluster=CLUSTER)
    rows = fresh.execute_read(
        types.Operation.lookup_transfers, ids_bytes([601])
    )
    out = np.frombuffer(rows, types.TRANSFER_DTYPE)
    assert len(out) == 1 and int(out[0]["amount_lo"]) == 7


def test_core_attestation_age_bound_refuses_frozen_partition():
    """A FULL partition (upstream and log both unreachable) freezes
    lag_ops at 0 — the attestation AGE bound is what keeps the
    staleness contract honest there: once the last verified
    attestation is older than the bound, reads refuse `lagging`
    instead of serving frozen state as fresh forever."""
    p = _Primary()
    p.seed_accounts()
    core = _core(p, attest_max_age_ns=1_000_000_000)  # 1 s bound
    core.pump()
    t0 = 5_000_000_000
    root = p.replica.root_at(p.replica.commit_min)
    core.on_attestation(root, p.replica.commit_min, now_ns=t0)
    assert isinstance(
        core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]),
                   now_ns=t0 + 500_000_000),
        FollowerReply,
    )
    # Partition: no attestations for > the bound.  lag_ops is still 0
    # (the high-water mark froze), but the age bound refuses.
    assert core.lag_ops() == 0
    got = core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]),
                     now_ns=t0 + 2_000_000_000)
    assert isinstance(got, FollowerRefusal)
    assert got.reason == FollowerRefuse.lagging
    # Heal: a fresh attestation restores serving.
    core.on_attestation(root, p.replica.commit_min,
                        now_ns=t0 + 3_000_000_000)
    assert isinstance(
        core.serve(int(types.Operation.lookup_accounts), ids_bytes([1]),
                   now_ns=t0 + 3_100_000_000),
        FollowerReply,
    )


def test_tail_chunk_cache_persists_across_polls():
    """The chunk cache survives poll() calls: a driver consuming a few
    records per poll must not re-read the chunk every time."""
    reads = []

    class CountingSource(BytesSource):
        def read_at(self, offset, n):
            reads.append((offset, n))
            return super().read_at(offset, n)

    buf = bytearray(b"".join(_record(op) for op in range(1, 33)))
    tail = AofTail(CountingSource(buf))
    got = 0
    while True:
        batch = tail.poll(limit=4)
        if not batch:
            break
        got += len(batch)
    assert got == 32
    # One chunk read covers the whole buffer (records are small).
    assert len(reads) == 1, reads
