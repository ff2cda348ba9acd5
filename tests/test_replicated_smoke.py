"""Tier-1 replicated smoke: a real 2-replica TCP cluster (in-process
ReplicaServers over the native bus) driven by BENCH_REPL_SESSIONS
concurrent client sessions — the group-commit spine exercised end to
end in pytest, so a regression surfaces here and not only in a
cell's run.  Small stream, TEST_MIN config, CPU state machine: seconds, not
minutes."""

import os
import socket
import threading
import time

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.runtime.native import native_available
from tigerbeetle_tpu.state_machine import CpuStateMachine

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native runtime not built"
)

CLUSTER = 9
N_REPLICAS = 2
TRANSFERS_PER_SESSION = 12


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class _Server:
    def __init__(self, path, addresses, index):
        from tigerbeetle_tpu.runtime.server import ReplicaServer

        self.server = ReplicaServer(
            path, cluster=CLUSTER, addresses=addresses, replica_index=index,
            state_machine_factory=lambda: CpuStateMachine(cfg.TEST_MIN),
            config=cfg.TEST_MIN,
        )
        self._stop = False
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def _loop(self):
        while not self._stop:
            self.server.poll_once(timeout_ms=1)

    def close(self):
        self._stop = True
        self.thread.join(timeout=5)
        self.server.close()


def test_two_replica_group_commit_smoke(tmp_path, monkeypatch):
    """Both ingest arms, one assertion set: the cluster runs once with
    the columnar fast path forced ON and once forced OFF.  The
    create_transfers reply BODIES (result pairs, including a
    deliberate failure per session) must be identical across arms —
    the wire contract does not move with the decode strategy — and
    the ON arm's scrape must show nonzero fastpath.batch_decode hits
    (bit-level reply-frame identity incl. headers is pinned by the
    pinned-clock differential in tests/test_fastpath_decode.py)."""
    replies_on = _run_cluster_once(tmp_path / "on", "1", monkeypatch)
    replies_off = _run_cluster_once(tmp_path / "off", "0", monkeypatch)
    assert replies_on == replies_off


def test_two_replica_native_pipeline_smoke(tmp_path, monkeypatch):
    """Native-pipeline arm (round 20): the same cluster smoke with the
    per-prepare hot loop in C (TB_NATIVE_PIPELINE=1) vs pure Python
    (=0) — reply bodies identical, both over the columnar ingest path
    (bit-level frame identity is pinned by the sim-cluster
    differential in tests/test_native_pipeline.py)."""
    from tigerbeetle_tpu.runtime import fastpath

    if not fastpath.pipeline_available():
        pytest.skip("libtb_fastpath pipeline symbols not built")
    monkeypatch.setenv("TB_NATIVE_PIPELINE", "1")
    replies_native = _run_cluster_once(tmp_path / "np_on", "1", monkeypatch)
    monkeypatch.setenv("TB_NATIVE_PIPELINE", "0")
    replies_python = _run_cluster_once(tmp_path / "np_off", "1", monkeypatch)
    assert replies_native == replies_python


def test_two_replica_native_drain_smoke(tmp_path, monkeypatch):
    """C-resident drain arm (round 22): the same cluster smoke with a
    whole poll's prepare->ack->commit-decision batched below Python
    (TB_NATIVE_DRAIN=1) vs the per-item loop over the same batch seams
    (=0) — reply bodies identical (bit-level frame identity is pinned
    by the batched-delivery differential in tests/test_native_drain.py),
    and the scrape proves which arm ran: batch C crossings only on the
    ON arm, and far fewer crossings than prepares+acks processed."""
    from tigerbeetle_tpu.runtime import fastpath

    if not fastpath.drain_available():
        pytest.skip("libtb_fastpath r22 drain symbols not built")
    monkeypatch.setenv("TB_NATIVE_PIPELINE", "1")
    monkeypatch.setenv("TB_NATIVE_DRAIN", "1")
    drain_scrapes.clear()
    replies_native = _run_cluster_once(tmp_path / "nd_on", "1", monkeypatch)
    on_snaps = list(drain_scrapes)
    monkeypatch.setenv("TB_NATIVE_DRAIN", "0")
    drain_scrapes.clear()
    replies_python = _run_cluster_once(tmp_path / "nd_off", "1", monkeypatch)
    off_snaps = list(drain_scrapes)
    assert replies_native == replies_python
    # The ON arm crossed into C per batch seam — on BOTH roles (the
    # primary's plan+ack drains, the backup's accept drains) — and the
    # OFF arm never did.  Crossings are per RUN, so they stay bounded
    # by the per-item work they replaced (native_calls <= items).
    for s in on_snaps:
        assert s["vsr.drain.native_calls"] > 0
    primary_on, backup_on = on_snaps[0], on_snaps[1]
    assert (
        primary_on["vsr.drain.native_calls"]
        <= primary_on["vsr.prepare_us.count"]
        + primary_on["vsr.prepares_written"] * 2
    )
    assert (
        backup_on["vsr.drain.native_calls"]
        <= backup_on["vsr.prepare_ok_us.count"]
    )
    for s in off_snaps:
        assert s["vsr.drain.native_calls"] == 0


def test_two_replica_hash_reuse_smoke(tmp_path, monkeypatch):
    """Hash-once arm (round 23): the same cluster smoke with
    drain-scoped digest reuse ON vs OFF, pinned to ONE client session
    so every prepare is a unit request — the coalesce finalize is a
    legitimate extra pass over freshly concatenated bytes and would
    muddy the per-byte ratio this test exists to pin.  Reply bodies
    identical across arms; per role the reuse-on arm SHA-256s each
    committed body byte at most once (bytes_hashed <=
    committed_body_bytes), the reuse-off primary strictly more for
    the same stream (the build rehash comes back), and only the
    primary's build seams ever consume cached digests."""
    monkeypatch.setenv("BENCH_REPL_SESSIONS", "1")
    monkeypatch.setenv("TB_HASH_REUSE", "1")
    drain_scrapes.clear()
    replies_on = _run_cluster_once(tmp_path / "hr_on", "1", monkeypatch)
    on_snaps = list(drain_scrapes)
    monkeypatch.setenv("TB_HASH_REUSE", "0")
    drain_scrapes.clear()
    replies_off = _run_cluster_once(tmp_path / "hr_off", "1", monkeypatch)
    off_snaps = list(drain_scrapes)
    assert replies_on == replies_off
    # The counters and the engine forensics reach the scrape on every
    # role in both arms (vsr.* graft for the replica counters, bare
    # names for the server-level engine gauges).
    for s in on_snaps + off_snaps:
        assert s["vsr.hash.committed_body_bytes"] > 0
        assert s["hash.engine_code"] in (1, 2, 3)
        assert s["hash.threads"] >= 0
        assert "server.verify_body_bytes" in s
        assert "hash.scalar_fallback" in s
    # Tentpole contract, numerically: with reuse ON no role spends
    # more than ONE SHA-256 pass per committed body byte.  A
    # retransmitted frame must be verified before it can be
    # recognized as a duplicate — that pass is unavoidable in any
    # design and lands in hash.dup_body_bytes, so the bound is exact,
    # not fuzzed with slack.
    for s in on_snaps:
        assert (
            s["vsr.hash.bytes_hashed"]
            <= s["vsr.hash.committed_body_bytes"]
            + s["vsr.hash.dup_body_bytes"]
        ), s
    primary_on, primary_off = on_snaps[0], off_snaps[0]
    assert primary_on["vsr.hash.reuse_hits"] > 0
    # ... and turning the knob OFF brings the build rehash back: the
    # primary hashes the same committed stream strictly more than
    # once per byte (net of duplicate deliveries), and strictly more
    # than the reuse-on arm did.
    assert primary_off["vsr.hash.reuse_hits"] == 0
    off_net = (
        primary_off["vsr.hash.bytes_hashed"]
        - primary_off["vsr.hash.dup_body_bytes"]
    )
    on_net = (
        primary_on["vsr.hash.bytes_hashed"]
        - primary_on["vsr.hash.dup_body_bytes"]
    )
    assert off_net > primary_off["vsr.hash.committed_body_bytes"], (
        primary_off
    )
    assert off_net > on_net


# Scrape snapshots stashed by _run_cluster_once for arm-level
# assertions that need both runs (the drain smoke above).
drain_scrapes: list = []


def _run_cluster_once(tmp_path, fastpath_flag, monkeypatch):
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.runtime.server import format_data_file

    monkeypatch.setenv("TB_FASTPATH_DECODE", fastpath_flag)
    tmp_path.mkdir(parents=True, exist_ok=True)
    n_sessions = max(1, int(os.environ.get("BENCH_REPL_SESSIONS", "2")))
    ports = _free_ports(N_REPLICAS)
    addresses = [f"127.0.0.1:{p}" for p in ports]
    paths = [str(tmp_path / f"r{i}.tb") for i in range(N_REPLICAS)]
    for i in range(N_REPLICAS):
        format_data_file(
            paths[i], cluster=CLUSTER, replica_index=i,
            replica_count=N_REPLICAS, config=cfg.TEST_MIN,
        )
    servers = [
        _Server(paths[i], addresses, i) for i in range(N_REPLICAS)
    ]
    clients = []
    reply_bodies: dict = {}
    try:
        for r in servers:
            # Group commit must be live on the real server storage.
            assert r.server.replica._gc_enabled
        addr = ",".join(addresses)
        setup = Client(addr, CLUSTER, client_id=50, timeout_ms=30_000)
        clients.append(setup)
        assert setup.create_accounts(
            [{"id": 1, "ledger": 1, "code": 1},
             {"id": 2, "ledger": 1, "code": 1}]
        ) == []

        errors = []

        def transfer_body(tid, dr, cr):
            row = np.zeros(1, types.TRANSFER_DTYPE)
            row["id_lo"] = tid
            row["debit_account_id_lo"] = dr
            row["credit_account_id_lo"] = cr
            row["amount_lo"] = 1
            row["ledger"] = 1
            row["code"] = 1
            return row.tobytes()

        def drive(s):
            try:
                c = Client(addr, CLUSTER, client_id=100 + s,
                           timeout_ms=30_000)
                clients.append(c)
                base = 1000 * (s + 1)
                bodies = []
                for k in range(TRANSFERS_PER_SESSION):
                    reply = c._native.request(
                        types.Operation.create_transfers,
                        transfer_body(base + k, 1, 2), 30_000,
                    )
                    assert reply == b"", reply
                    bodies.append(reply)
                # Deliberate failure so the compared reply bytes are
                # non-trivial: debit == credit must come back as
                # accounts_must_be_different, identically in both arms.
                reply = c._native.request(
                    types.Operation.create_transfers,
                    transfer_body(base + 900, 1, 1), 30_000,
                )
                res = np.frombuffer(reply, types.CREATE_RESULT_DTYPE)
                assert len(res) == 1 and int(res[0]["result"]) == int(
                    types.CreateTransferResult.accounts_must_be_different
                ), res
                bodies.append(reply)
                reply_bodies[s] = bodies
            except Exception as exc:  # noqa: BLE001
                errors.append(f"session {s}: {exc!r}")

        threads = [
            threading.Thread(target=drive, args=(s,), daemon=True)
            for s in range(n_sessions)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert errors == [], errors

        rows = setup.lookup_accounts([1, 2])
        total = n_sessions * TRANSFERS_PER_SESSION
        assert types.u128_get(rows[0], "debits_posted") == total
        assert types.u128_get(rows[1], "credits_posted") == total

        # Counter-verified group commit: the covering-sync machinery
        # ran on the primary, and the contract-side bookkeeping is
        # clean (nothing deferred forever, nothing left unsynced).
        primary = servers[0].server.replica
        backup = servers[1].server.replica
        assert primary.stat_gc_flushes > 0
        assert backup.stat_prepares_written >= total // 30  # batched
        for r in servers:
            assert r.server.replica.journal.unsynced_writes == 0
            assert not r.server.replica._gc_pending
        # Both replicas committed the full stream (backup learns via
        # piggybacked commit numbers/heartbeats within a tick or two).
        assert primary.commit_min >= backup.commit_min >= 0

        # Proof-of-state query (state_machine/commitment.py): both
        # replicas answer the sessionless `state_root` op with the
        # SAME nonzero 16-byte root once converged — the wire-level
        # rendering of the hash-log convergence claim.  Run BEFORE the
        # scrape so the stashed snapshots are quiescent on both roles
        # (the backup has committed the full tail; the r23 hash-ratio
        # smoke compares bytes_hashed against committed_body_bytes and
        # a mid-catch-up backup would under-count the denominator).
        from tigerbeetle_tpu.obs.scrape import scrape_state_root

        roots = {}
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            roots = {
                i: scrape_state_root(addresses[i], CLUSTER,
                                     timeout_ms=20_000)
                for i in range(len(servers))
            }
            if len({cm for _root, cm in roots.values()}) == 1:
                break
            time.sleep(0.2)  # backup still applying the tail
        assert len({root for root, _cm in roots.values()}) == 1, roots
        assert roots[0][0] != bytes(16)
        assert roots[0][0] == servers[0].server.replica.sm.state_root()

        # Live scrape (obs/scrape.py): the `stats` wire op answers
        # from the same registry the in-process handles feed, and the
        # fsync/prepare counters satisfy the r10 group-commit
        # contract — one covering sync amortized over many prepares,
        # never an ack-relevant prepare left uncovered.
        from tigerbeetle_tpu.obs.scrape import scrape_stats

        for i, server in enumerate(servers):
            snap = scrape_stats(addresses[i], CLUSTER, timeout_ms=20_000)
            drain_scrapes.append(snap)
            assert snap["replica"] == i
            # r22 drain forensics are always scrape-visible, whichever
            # arm ran (the smoke above asserts the arm-specific values).
            assert "vsr.drain.native_calls" in snap
            assert "vsr.drain.py_fallbacks" in snap
            r = server.server.replica
            # Quiescent counters must agree bit-for-bit with the
            # in-process registry (drain histograms keep moving with
            # heartbeats; durability counters do not).
            assert snap["vsr.prepares_written"] == r.stat_prepares_written
            assert snap["vsr.gc_flushes"] == r.stat_gc_flushes
            assert snap["storage.fsyncs"] == server.server.storage.stat_fsyncs
            assert snap["vsr.commit_min"] == r.commit_min
            assert snap["version"] > 0
            # Columnar-ingest contract: the forced arm is the arm that
            # actually ran — nonzero batch-decode hits when on, zero
            # when off — and a native-capable build never fell back.
            if fastpath_flag == "1":
                assert snap["fastpath.batch_decode_hits"] > 0
                if not snap["fastpath.native_unavailable"]:
                    assert snap["fastpath.batch_decode_fallbacks"] == 0
                assert snap["server.decode_us_per_event.count"] > 0
            else:
                assert snap["fastpath.batch_decode_hits"] == 0
            if i == 0:
                # r20 per-prepare instrument: the primary timed every
                # header-build + bookkeeping span, and the histogram
                # reaches the scrape under the replica registry's
                # "vsr." graft.
                assert snap["vsr.prepare_us.count"] > 0
                assert snap["vsr.prepare_us.p50"] > 0
                assert snap["vsr.gc_flushes"] > 0
                # r10 contract: group commit => fewer covering syncs
                # than WAL appends once load overlaps (each flush
                # covers a whole drain), and every sync accounted.
                assert snap["vsr.gc_flushes"] <= snap["vsr.prepares_written"]
                assert snap["storage.fsyncs"] > 0
            else:
                # Backup-side instrument: every accepted prepare timed
                # its prepare_ok build span.
                assert snap["vsr.prepare_ok_us.count"] > 0

        return reply_bodies
    finally:
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        for r in servers:
            r.close()
