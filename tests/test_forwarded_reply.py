"""A reply reaches its client through a backup (ISSUE 28, M2).

Three served replicas over real TCP on the CPU backend.  A client that
addresses a backup alone has its request forwarded to the primary; the
reply travels back the way the request came, over the peer connection
and through the backup, in one round, with no resend.  The primary
keeps knowing the peer connection as the peer's, and a retransmit of a
committed request through the backup gets the stored reply.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.client import Client
from tigerbeetle_tpu.runtime.native import EV_MESSAGE, NativeBus
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.vsr import wire
from tigerbeetle_tpu.vsr.wire import Command, VsrOperation

CLUSTER = 28
RESEND_S = 1.0          # native/tb_runtime.cpp: the client's resend cadence
HEADER = cfg.HEADER_SIZE


@pytest.fixture
def tcp_cluster(tmp_path):
    from tigerbeetle_tpu.runtime.server import ReplicaServer, format_data_file

    servers = []
    paths = [str(tmp_path / f"r{i}.tigerbeetle") for i in range(3)]
    addresses = ["127.0.0.1:0"] * 3
    for i in range(3):
        format_data_file(paths[i], cluster=CLUSTER, replica_index=i,
                         replica_count=3, config=cfg.TEST_MIN)
        s = ReplicaServer(
            paths[i], cluster=CLUSTER, addresses=list(addresses),
            replica_index=i,
            state_machine_factory=lambda: CpuStateMachine(cfg.TEST_MIN),
            config=cfg.TEST_MIN,
        )
        addresses[i] = f"127.0.0.1:{s.port}"
        servers.append(s)
    for s in servers:
        s.bus.addresses = list(addresses)
    stop = [False]

    def loop():
        while not stop[0]:
            for s in servers:
                s.poll_once(timeout_ms=1)

    thread = threading.Thread(target=loop, daemon=True)
    thread.start()
    # A clock window and a view: the first request then needs no retry.
    warm = Client(",".join(addresses), CLUSTER, client_id=700, timeout_ms=30_000)
    assert warm.create_accounts(
        [{"id": i, "ledger": 1, "code": 1} for i in (1, 2)]) == []
    warm.close()
    try:
        yield servers, addresses
    finally:
        stop[0] = True
        thread.join(timeout=5)
        for s in servers:
            s.close()


def roles(servers):
    primary = next(s for s in servers if s.replica.is_primary)
    backups = [s for s in servers if s is not primary]
    assert len(backups) == 2
    return primary, backups


def counters(server) -> dict:
    snap = server.registry.snapshot()
    return {k: snap[k] for k in ("vsr.requests_forwarded", "vsr.replies_relayed")}


def transfers(first_id: int, n: int = 3) -> list[dict]:
    return [{"id": first_id + j, "debit_account_id": 1, "credit_account_id": 2,
             "amount": 1, "ledger": 1, "code": 1} for j in range(n)]


def test_a_client_of_a_backup_is_answered_in_one_round(tcp_cluster):
    servers, addresses = tcp_cluster
    primary, backups = roles(servers)
    backup = backups[0]
    assert counters(backup) == {"vsr.requests_forwarded": 0,
                                "vsr.replies_relayed": 0}
    # The backup's address alone: the client can reach no other replica.
    c = Client(addresses[backup.replica.replica], CLUSTER, client_id=701,
               timeout_ms=30_000)
    took = []
    for at in range(6):
        t0 = time.perf_counter()
        assert c.create_transfers(transfers(1000 + 10 * at)) == []
        took.append(time.perf_counter() - t0)
    assert c.lookup_accounts([1])["debits_posted_lo"].tolist() == [18]
    c.close()
    # No request waited for the client's resend, the first (which also
    # registers the session) included.
    assert max(took) < RESEND_S / 2, took
    # Register, six writes and the lookup went up and came back.
    assert counters(backup) == {"vsr.requests_forwarded": 8,
                                "vsr.replies_relayed": 8}
    assert counters(primary) == {"vsr.requests_forwarded": 0,
                                 "vsr.replies_relayed": 0}
    assert counters(backups[1]) == {"vsr.requests_forwarded": 0,
                                    "vsr.replies_relayed": 0}
    # Nobody resent, so the primary prepared each request once.
    assert primary.replica.view == 0


def test_the_peer_connection_stays_the_peers(tcp_cluster):
    """prepare_ok, commit and repair traffic keep their route: the
    connection a forwarded request came in on is ("replica", p) before
    and after, and the client's route points at it."""
    servers, addresses = tcp_cluster
    primary, backups = roles(servers)
    backup = backups[1]
    process = backup.replica.replica
    conn = primary.bus.replica_conns[process]
    assert primary.bus._conn_peer[conn] == ("replica", process)
    c = Client(addresses[process], CLUSTER, client_id=702, timeout_ms=30_000)
    assert c.create_transfers(transfers(2000)) == []
    assert primary.bus._conn_peer[conn] == ("replica", process)
    assert primary.bus.replica_conns[process] == conn
    assert primary.bus.client_conns[702] == conn
    # The backup knows the client by a connection of its own.
    own = backup.bus.client_conns[702]
    assert backup.bus._conn_peer[own] == ("client", 702)
    # Replication went on over the same connections: every replica
    # commits what the client wrote after it.
    assert c.create_transfers(transfers(2010)) == []
    c.close()
    deadline = time.time() + 20
    while time.time() < deadline and len(
            {s.replica.commit_min for s in servers}) > 1:
        time.sleep(0.05)
    assert len({s.replica.commit_min for s in servers}) == 1
    assert all(s.replica.view == 0 for s in servers)
    # The client gone, its route goes with its connection on the backup;
    # the primary's peer connection is untouched by that.
    deadline = time.time() + 10
    while time.time() < deadline and 702 in backup.bus.client_conns:
        time.sleep(0.05)
    assert 702 not in backup.bus.client_conns
    assert primary.bus._conn_peer[conn] == ("replica", process)


class RawClient:
    """A session spoken frame by frame, so that a request can be sent
    twice under one number."""

    def __init__(self, address: str, client: int) -> None:
        self.bus = NativeBus(cfg.TEST_MIN.message_size_max)
        host, _, port = address.rpartition(":")
        self.conn = self.bus.connect(host, int(port))
        self.client = client

    def frame(self, operation: int, request: int, body: bytes = b"") -> bytes:
        h = wire.make_header(command=Command.request, operation=operation,
                             cluster=CLUSTER, client=self.client,
                             request=request)
        wire.finalize_header(h, body)
        return h.tobytes() + body

    def reply_to(self, request: int, timeout_s: float = 10.0):
        """-> (header, body) of the first reply to `request`."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            for ev_type, _conn, payload in self.bus.poll(20):
                if ev_type != EV_MESSAGE or len(payload) < HEADER:
                    continue
                h = wire.header_from_bytes(payload[:HEADER])
                if (int(h["command"]) == int(Command.reply)
                        and int(h["request"]) == request):
                    return h, payload[HEADER:]
        return None

    def close(self) -> None:
        self.bus.close()


def test_a_retransmit_through_a_backup_gets_the_stored_reply(tcp_cluster):
    servers, addresses = tcp_cluster
    primary, backups = roles(servers)
    backup = backups[0]
    raw = RawClient(addresses[backup.replica.replica], client=703)
    raw.bus.send(raw.conn, raw.frame(int(VsrOperation.register), 0))
    assert raw.reply_to(0) is not None
    # Two transfers, the second a duplicate id: one failure in the reply.
    rows = np.zeros(2, types.TRANSFER_DTYPE)
    for i in range(2):
        rows[i]["id_lo"] = 3000
        rows[i]["debit_account_id_lo"] = 1
        rows[i]["credit_account_id_lo"] = 2
        rows[i]["amount_lo"] = 5
        rows[i]["ledger"] = 1
        rows[i]["code"] = 1
    request = raw.frame(int(types.Operation.create_transfers), 1, rows.tobytes())
    raw.bus.send(raw.conn, request)
    first = raw.reply_to(1)
    assert first is not None and len(first[1]) == 8
    commits = primary.replica.commit_min
    before = counters(backup)
    # The same frame again, through the backup again.
    raw.bus.send(raw.conn, request)
    again = raw.reply_to(1)
    assert again is not None
    assert again[1] == first[1]
    assert again[0].tobytes() == first[0].tobytes()
    # Answered from the primary's client-replies zone: nothing was
    # prepared, and the backup stored nothing, it relayed.
    assert primary.replica.commit_min == commits
    after = counters(backup)
    assert after["vsr.requests_forwarded"] == before["vsr.requests_forwarded"] + 1
    assert after["vsr.replies_relayed"] == before["vsr.replies_relayed"] + 1
    raw.close()
