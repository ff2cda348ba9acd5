"""Tier-1 overload smoke: a real 2-replica TCP cluster driven OPEN
LOOP at ~120% of a quick measured closed-loop capacity for ~2 s.

Asserts the whole request-anatomy + admission-control contract end to
end: the primary's queue stays bounded at TB_ADMIT_QUEUE, at least one
typed Command.client_busy reaches the client, the scraped tail
exemplars carry a full prepare -> journal_write -> gc_covering_sync ->
commit -> reply stage timeline that round-trips into a merged Perfetto
view, and SIGTERM produces a parseable flight-recorder dump.

Subprocess servers (not threads): the SIGTERM flight dump needs a real
main-thread signal handler.  CpuStateMachine + TEST_MIN keeps it
seconds, inside the tier-1 budget."""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu.runtime.native import native_available
from tigerbeetle_tpu.types import TRANSFER_DTYPE, Operation

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native runtime not built"
)

CLUSTER = 21
N_REPLICAS = 2
ADMIT_QUEUE = 8
BATCH = 24  # transfers per request (fits TEST_MIN's 3840-byte body)

_RUNNER = """\
import sys
sys.path.insert(0, {here!r})
from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu.runtime.server import ReplicaServer
from tigerbeetle_tpu.state_machine import CpuStateMachine
s = ReplicaServer({path!r}, cluster={cluster}, addresses={addrs!r}.split(','),
    replica_index={i}, config=cfg.TEST_MIN,
    state_machine_factory=lambda: CpuStateMachine(cfg.TEST_MIN))
print('listening', flush=True)
s.serve_forever()
"""


def _transfers(tid0, n, rng):
    arr = np.zeros(n, dtype=TRANSFER_DTYPE)
    arr["id_lo"] = np.arange(tid0, tid0 + n, dtype=np.uint64)
    arr["debit_account_id_lo"] = rng.integers(1, 9, n, np.uint64)
    arr["credit_account_id_lo"] = rng.integers(9, 17, n, np.uint64)
    arr["amount_lo"] = 1
    arr["ledger"] = 1
    arr["code"] = 1
    return arr.tobytes()


def test_open_loop_overload_sheds_and_dumps(tmp_path):
    from tigerbeetle_tpu.client import Client, OpenLoopSession
    from tigerbeetle_tpu.obs.anatomy import exemplar_trace_events
    from tigerbeetle_tpu.obs.scrape import scrape_stats
    from tigerbeetle_tpu.runtime.server import format_data_file
    from tigerbeetle_tpu.testing.cluster import merge_traces

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    socks = [socket.socket() for _ in range(N_REPLICAS)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    addresses = ",".join(f"127.0.0.1:{p}" for p in ports)
    env = dict(os.environ)
    env["TB_ADMIT_QUEUE"] = str(ADMIT_QUEUE)
    env["TB_FLIGHT_PATH"] = str(tmp_path / "flight_r{replica}.json")
    env.pop("TB_METRICS", None)  # anatomy on
    procs = []
    logs = []
    clients = []
    sessions = []
    try:
        for i in range(N_REPLICAS):
            path = str(tmp_path / f"r{i}.tb")
            format_data_file(
                path, cluster=CLUSTER, replica_index=i,
                replica_count=N_REPLICAS, config=cfg.TEST_MIN,
            )
            log = open(tmp_path / f"replica{i}.log", "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _RUNNER.format(
                    here=here, path=path, cluster=CLUSTER,
                    addrs=addresses, i=i,
                )],
                stdout=log, stderr=subprocess.STDOUT, cwd=here, env=env,
            ))
        deadline = time.time() + 60
        for i in range(N_REPLICAS):
            lp = tmp_path / f"replica{i}.log"
            while time.time() < deadline:
                assert procs[i].poll() is None, (
                    f"replica {i} died:\n" + lp.read_text()[-2000:]
                )
                if "listening" in lp.read_text():
                    break
                time.sleep(0.2)
            else:
                raise AssertionError(f"replica {i} never listened")

        rng = np.random.default_rng(9)
        setup = Client(addresses, CLUSTER, client_id=70, timeout_ms=30_000)
        clients.append(setup)
        assert setup.create_accounts(
            [{"id": a, "ledger": 1, "code": 1} for a in range(1, 17)]
        ) == []

        # Quick closed-loop capacity probe (~0.6 s, one session).
        tid = 1_000
        t_end = time.perf_counter() + 0.6
        t0 = time.perf_counter()
        sent_events = 0
        while time.perf_counter() < t_end:
            body = _transfers(tid, BATCH, rng)
            tid += BATCH
            reply = setup._native.request(
                Operation.create_transfers, body, 30_000
            )
            assert reply == b""
            sent_events += BATCH
        capacity_eps = sent_events / (time.perf_counter() - t0)
        req_rate = max(2.0, 1.2 * capacity_eps / BATCH)  # 120% offered

        sessions.extend(
            OpenLoopSession(f"127.0.0.1:{ports[0]}", CLUSTER, 0xB0 + k)
            for k in range(2)
        )
        t_start = time.perf_counter()
        t_stop = t_start + 2.0
        next_arrival = t_start
        queue_depths = []
        rr = 0
        while time.perf_counter() < t_stop:
            now = time.perf_counter()
            while next_arrival <= now:
                sessions[rr % 2].submit(
                    Operation.create_transfers, _transfers(tid, BATCH, rng)
                )
                tid += BATCH
                rr += 1
                next_arrival += float(rng.exponential(1.0 / req_rate))
            for s in sessions:
                s.poll(0)
            if len(queue_depths) < 40 and rr % 5 == 0:
                try:
                    snap = scrape_stats(
                        f"127.0.0.1:{ports[0]}", CLUSTER, timeout_ms=3_000
                    )
                    queue_depths.append(int(snap["server.queue_depth"]))
                except (OSError, TimeoutError, ValueError):
                    pass
            time.sleep(0.002)
        # Deterministic overload spike: whatever the box's speed, a
        # back-to-back burst (30 requests vs pipeline 4 + queue 8)
        # must overflow the admit bound and shed — the Poisson phase
        # alone can be absorbed by a fast machine.
        for _ in range(3):
            for _ in range(30):
                sessions[rr % 2].submit(
                    Operation.create_transfers, _transfers(tid, BATCH, rng)
                )
                tid += BATCH
                rr += 1
            time.sleep(0.05)
            for s in sessions:
                s.poll(0)
            try:
                snap = scrape_stats(
                    f"127.0.0.1:{ports[0]}", CLUSTER, timeout_ms=3_000
                )
                queue_depths.append(int(snap["server.queue_depth"]))
            except (OSError, TimeoutError, ValueError):
                pass
        # Grace: drain what the bounded queue admitted.
        grace = time.perf_counter() + 20.0
        while time.perf_counter() < grace and any(
            s.inflight for s in sessions
        ):
            for s in sessions:
                s.poll(20)

        # 1) Bounded queue: every sampled depth within the admit bound.
        assert queue_depths, "no queue-depth samples scraped"
        assert max(queue_depths) <= ADMIT_QUEUE, queue_depths

        # 2) Typed busy surfaced to the client + shed counted.
        busy_total = sum(s.busy_replies for s in sessions)
        snap = scrape_stats(f"127.0.0.1:{ports[0]}", CLUSTER,
                            timeout_ms=10_000)
        assert busy_total >= 1, (
            f"no typed busy at 120% load (shed={snap.get('server.shed')})"
        )
        assert snap["server.shed"] >= busy_total
        assert snap["server.admit_queue"] == ADMIT_QUEUE

        # 3) Tail exemplars: full replicated-drain stage timeline.
        exemplars = snap["anatomy.exemplars"]
        assert exemplars, "no exemplars retained"
        want = {"prepare", "journal_write", "gc_covering_sync", "commit",
                "reply"}
        full = [
            ex for ex in exemplars
            if want <= {s[0] for s in ex["stages"]}
        ]
        assert full, [
            sorted({s[0] for s in ex["stages"]}) for ex in exemplars
        ]
        for ex in full:
            ts = [s[1] for s in ex["stages"]]
            assert ts == sorted(ts)
        assert snap["vsr.anatomy.e2e_us.count"] > 0

        # 4) SIGTERM -> parseable flight-recorder dump (replica 1).
        procs[1].send_signal(signal.SIGTERM)
        flight_path = tmp_path / "flight_r1.json"
        deadline = time.time() + 15
        while time.time() < deadline and not flight_path.exists():
            time.sleep(0.2)
        assert flight_path.exists(), "no flight dump on SIGTERM"
        procs[1].wait(timeout=15)
        flight = json.loads(flight_path.read_text())
        assert flight["otherData"]["flight_recorder"] is True
        assert flight["otherData"]["reason"] == "sigterm"
        assert flight["traceEvents"], "flight ring empty"

        # 4b) The PRIMARY's flight dump names WHO was shed (round 16):
        # every shed note carries the tenant (ledger 1 here, derived
        # from the body — these sessions don't stamp the header), and
        # a per-tenant `shed.t<ledger>` instant makes the per-tenant
        # timeline greppable without parsing note args.
        procs[0].send_signal(signal.SIGTERM)
        flight0_path = tmp_path / "flight_r0.json"
        deadline = time.time() + 15
        while time.time() < deadline and not flight0_path.exists():
            time.sleep(0.2)
        assert flight0_path.exists(), "no flight dump on SIGTERM (r0)"
        procs[0].wait(timeout=15)
        flight0 = json.loads(flight0_path.read_text())
        shed_notes = [
            e for e in flight0["traceEvents"] if e["name"] == "shed"
        ]
        assert shed_notes, "primary shed but recorded no flight notes"
        assert all(
            e.get("args", {}).get("tenant") == 1 for e in shed_notes
        ), shed_notes[:3]
        assert any(
            e["name"] == "shed.t1" for e in flight0["traceEvents"]
        ), "no per-tenant shed instant"

        # 5) Perfetto round-trip: exemplar spans + the flight dump
        # merge into one loadable timeline with all stage names.
        ex_path = tmp_path / "exemplars.json"
        ex_path.write_text(json.dumps({
            "traceEvents": exemplar_trace_events(full),
            "otherData": {},
        }))
        merged = merge_traces(
            [str(ex_path), str(flight_path)],
            str(tmp_path / "merged.json"),
            labels=["exemplars", "flight_r1"],
        )
        names = {e["name"] for e in merged["traceEvents"]}
        assert want <= names, sorted(names)
        assert json.load(open(tmp_path / "merged.json")) == merged
    finally:
        for s in sessions:
            try:
                s.close()
            except Exception:
                pass
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        for p in procs:
            try:
                p.kill()
            except Exception:
                pass
        for log in logs:
            log.close()
