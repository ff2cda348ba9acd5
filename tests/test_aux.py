"""Aux subsystems: tracer, statsd, AOF, grid scrubber."""

import json
import socket

import numpy as np

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import types
from tigerbeetle_tpu.state_machine import CpuStateMachine
from tigerbeetle_tpu.testing.harness import account, pack, transfer
from tigerbeetle_tpu.utils.statsd import StatsD
from tigerbeetle_tpu.obs.registry import Registry
from tigerbeetle_tpu.utils.tracer import Stage, Tracer
from tigerbeetle_tpu.vsr import aof as aof_mod
from tigerbeetle_tpu.vsr import replica as vsr_replica
from tigerbeetle_tpu.vsr.grid import Grid
from tigerbeetle_tpu.vsr.scrubber import GridScrubber
from tigerbeetle_tpu.vsr.storage import MemoryStorage, ZoneLayout


def _stages(reg):
    return (Stage(reg.histogram("commit_us"), "vsr.commit", leaf=False),
            Stage(reg.histogram("plan_us"), "sm.plan"))


def test_tracer_spans():
    """Nested stages leave nested spans, inner first; backend "none"
    feeds the histograms and leaves no span."""
    t, reg = Tracer(backend="json"), Registry(enabled=True)
    commit, plan = _stages(reg)
    with t.stage(commit):
        with t.stage(plan):
            pass
    doc = json.loads(t.dump())
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["sm.plan", "vsr.commit"]
    assert all(e["dur"] >= 0 for e in doc["traceEvents"])

    none = Tracer(backend="none")
    with none.stage(commit):
        pass
    assert json.loads(none.dump())["traceEvents"] == []
    assert reg.histogram("commit_us").count == 2


def test_tracer_instants_stage_rows_and_bound():
    t, reg = Tracer(backend="json", buffer_max=10), Registry(enabled=True)
    t.instant("view_change", view=2)
    # A stage names its row of the trace (a worker's) and carries args.
    work = Stage(reg.histogram("beat.work_us"), "lsm.beat.work", tid=3)
    with t.stage(work, op=77):
        pass
    doc = json.loads(t.dump())
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["view_change"]["ph"] == "i"
    assert by_name["view_change"]["args"] == {"view": 2}
    assert by_name["lsm.beat.work"]["ph"] == "X"
    assert by_name["lsm.beat.work"]["tid"] == 3
    assert by_name["lsm.beat.work"]["args"]["op"] == 77
    # Bounded buffer: oldest events drop, drop count reported.
    for i in range(50):
        t.instant("x", i=i)
    doc = json.loads(t.dump())
    assert len(doc["traceEvents"]) == 10
    assert doc["otherData"]["dropped_events"] == 42


def test_server_writes_trace(tmp_path):
    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu.runtime.native import native_available
    from tigerbeetle_tpu.state_machine import CpuStateMachine

    if not native_available():
        pytest.skip("native runtime not built")
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.runtime.server import (
        ReplicaServer,
        format_data_file,
    )

    path = str(tmp_path / "data.tigerbeetle")
    trace = str(tmp_path / "trace.json")
    format_data_file(path, cluster=1, config=cfg.TEST_MIN)
    server = ReplicaServer(
        path, cluster=1, addresses=["127.0.0.1:0"], replica_index=0,
        state_machine_factory=lambda: CpuStateMachine(cfg.TEST_MIN),
        config=cfg.TEST_MIN, trace_path=trace,
    )
    import threading

    stop = []
    thread = threading.Thread(
        target=lambda: [server.poll_once(1) for _ in iter(
            lambda: not stop, False)], daemon=True
    )
    thread.start()
    c = Client(f"127.0.0.1:{server.port}", 1, client_id=9)
    assert c.create_accounts([{"id": 1, "ledger": 1, "code": 1}]) == []
    c.close()
    stop.append(1)
    thread.join(timeout=5)
    server.close()
    doc = json.loads(open(trace).read())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"vsr.commit", "vsr.commit.prefetch", "vsr.commit.reply"} <= names
    assert "vsr.journal.write" in names


def test_statsd_lines():
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.settimeout(2)
    port = recv.getsockname()[1]
    s = StatsD(port=port, prefix="tb")
    s.gauge("tx_per_s", 100.5)
    s.count("batches")
    s.timing("batch_ms", 12.5)
    got = sorted(recv.recv(1024).decode() for _ in range(3))
    assert got == [
        "tb.batch_ms:12.5|ms", "tb.batches:1|c", "tb.tx_per_s:100.5|g",
    ]
    s.close()
    recv.close()


def test_aof_records_and_replays(tmp_path):
    path = str(tmp_path / "log.aof")
    storage = MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))
    vsr_replica.format(storage, 5)
    r = vsr_replica.Replica(
        storage, 5, CpuStateMachine(cfg.TEST_MIN), aof=aof_mod.AOF(path)
    )
    r.open()
    r.on_request(types.Operation.create_accounts, pack([account(1), account(2)]))
    r.on_request(
        types.Operation.create_transfers,
        pack([transfer(9, debit_account_id=1, credit_account_id=2, amount=11)]),
    )
    r.aof.sync()

    entries = list(aof_mod.iterate(path))
    assert len(entries) >= 2

    fresh = CpuStateMachine(cfg.TEST_MIN)
    applied = aof_mod.replay(path, fresh, cluster=5)
    assert applied >= 2
    assert fresh.snapshot() == r.sm.snapshot()

    # A torn tail entry truncates iteration, not crashes.
    with open(path, "ab") as f:
        f.write(b"\x01" * 100)
    assert len(list(aof_mod.iterate(path))) == len(entries)


def test_grid_scrubber_finds_corruption():
    storage = MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))
    grid = Grid(storage, block_size=4096, block_count=64)
    fs = grid.free_set
    res = fs.reserve(8)
    addrs = [fs.acquire(res) for _ in range(8)]
    fs.forfeit(res)
    for a in addrs:
        grid.write_block(a, bytes([a]) * 100)

    bad = addrs[3]
    storage.corrupt_sector(grid._offset(bad))

    scrubber = GridScrubber(grid, cycle_ticks=2, blocks_per_tick_max=4)
    found = []
    while scrubber.cycles == 0:
        found += scrubber.tick()
    assert set(found) == {bad}


def test_grid_scrubber_tour_semantics():
    """Tour machinery (reference: src/vsr/grid_scrubber.zig): a cycle
    walks a STABLE snapshot paced across cycle_ticks, skips blocks
    freed mid-tour instead of flagging their stale frames, and picks
    up new allocations on the next tour."""
    storage = MemoryStorage(ZoneLayout(config=cfg.TEST_MIN))
    grid = Grid(storage, block_size=4096, block_count=64)
    fs = grid.free_set
    res = fs.reserve(16)
    addrs = [fs.acquire(res) for _ in range(16)]
    fs.forfeit(res)
    for a in addrs:
        grid.write_block(a, bytes([a]) * 64)

    scrubber = GridScrubber(grid, cycle_ticks=4, blocks_per_tick_max=8)
    # Pacing: 16 blocks over 4 ticks -> 4 per tick, progress advances.
    assert scrubber.tick() == []
    assert 0.0 < scrubber.progress < 1.0
    # Release a not-yet-scrubbed block and stale its frame: the tour
    # must SKIP it — the block is leaving the live set and peers may
    # no longer serve it for repair.
    victim = addrs[-1]
    fs.release(victim)
    storage.corrupt_sector(grid._offset(victim))
    while scrubber.cycles == 0:
        assert scrubber.tick() == []
    assert scrubber.faults_found == 0

    # A block allocated after the first snapshot joins the NEXT tour:
    # corrupt it and the scrubber must find it on the following cycle.
    res = fs.reserve(1)
    fresh = fs.acquire(res)
    fs.forfeit(res)
    grid.write_block(fresh, b"fresh")
    storage.corrupt_sector(grid._offset(fresh))  # verify_block reads disk
    found = []
    start_cycles = scrubber.cycles
    while scrubber.cycles < start_cycles + 2:
        found += scrubber.tick()
    assert fresh in found
    assert victim not in found


# ---------------------------------------------------------------------------
# RunIndex: run-compressed id directory (utils/hashindex.py).


def _u64(*vals):
    return np.array(vals, np.uint64)


def test_runindex_sequential_batches_merge_and_lookup():
    from tigerbeetle_tpu.utils import RunIndex

    ix = RunIndex()
    ix.insert(np.arange(1, 8191, dtype=np.uint64), np.zeros(8190, np.uint64),
              np.arange(0, 8190, dtype=np.uint64))
    ix.insert(np.arange(8191, 16381, dtype=np.uint64), np.zeros(8190, np.uint64),
              np.arange(8190, 16380, dtype=np.uint64))
    assert ix.count == 16380
    found, vals = ix.lookup(_u64(1, 16380, 16381), _u64(0, 0, 0))
    assert found.tolist() == [True, True, False]
    assert vals[0] == 0 and vals[1] == 16379


def test_runindex_hash_fallback_and_mixed_lookup():
    from tigerbeetle_tpu.utils import RunIndex

    ix = RunIndex()
    ix.insert(np.arange(10, 20, dtype=np.uint64), np.zeros(10, np.uint64),
              np.arange(10, dtype=np.uint64))
    # Three ids that do not follow: three runs of one (a batch of at
    # most RUN_PIECES pieces is filed as the runs it is made of).
    ix.insert(_u64(500, 7, 99), _u64(0, 0, 0), _u64(100, 101, 102))
    assert (ix.runs, ix.hashed) == (4, 0)
    # Scattered ids, more than that: the hash, whole.
    scattered = np.arange(1000, 1400, 20, dtype=np.uint64)
    ix.insert(scattered, np.zeros(20, np.uint64), np.arange(200, 220, dtype=np.uint64))
    assert (ix.runs, ix.hashed) == (4, 20)
    found, vals = ix.lookup(_u64(12, 7, 8, 1020, 1021), _u64(0, 0, 0, 0, 0))
    assert found.tolist() == [True, True, False, True, False]
    assert vals[0] == 2 and vals[1] == 101 and vals[3] == 201


def test_runindex_remove_splits_and_empties_runs():
    from tigerbeetle_tpu.utils import RunIndex

    ix = RunIndex()
    ix.insert(np.arange(10, 15, dtype=np.uint64), np.zeros(5, np.uint64),
              np.arange(5, dtype=np.uint64))
    ix.remove(_u64(12), _u64(0))  # split middle
    found, vals = ix.lookup(np.arange(10, 15, dtype=np.uint64), np.zeros(5, np.uint64))
    assert found.tolist() == [True, True, False, True, True]
    assert vals[[0, 1, 3, 4]].tolist() == [0, 1, 3, 4]
    ix.remove(_u64(10), _u64(0))  # shrink head
    ix.remove(_u64(14), _u64(0))  # shrink tail
    ix.remove(_u64(11), _u64(0))  # empty first run
    ix.remove(_u64(13), _u64(0))  # empty last run -> group removed
    assert ix.count == 0
    found, _ = ix.lookup(_u64(13), _u64(0))  # must not crash on empty group
    assert not found.any()
    # Reinsert after emptying works.
    ix.insert(np.arange(10, 12, dtype=np.uint64), np.zeros(2, np.uint64),
              _u64(7, 8))
    found, vals = ix.lookup(_u64(11), _u64(0))
    assert found[0] and vals[0] == 8


def test_runindex_rejects_wraparound_run():
    from tigerbeetle_tpu.utils import RunIndex

    ix = RunIndex()
    lo = _u64(2**64 - 1, 0)
    ix.insert(lo, _u64(7, 7), _u64(0, 1))
    found, vals = ix.lookup(lo, _u64(7, 7))
    assert found.all() and vals.tolist() == [0, 1]
    assert ix.runs == 2  # two runs of one: 0 does not follow 2**64 - 1


# ---------------------------------------------------------------------------
# Binding generation (bindings.py — reference: src/*_bindings.zig).


def test_bindings_c_header_compiles_with_size_asserts(tmp_path):
    import shutil
    import subprocess

    import pytest

    if shutil.which("g++") is None:
        pytest.skip("no C++ compiler on this host")
    from tigerbeetle_tpu import bindings

    paths = bindings.generate(str(tmp_path))
    header = next(p for p in paths if p.endswith("tb_types.h"))
    # The _Static_asserts make the compiler verify every wire layout.
    src = tmp_path / "check.c"
    src.write_text(f'#include "{header}"\nint main(void) {{ return 0; }}\n')
    subprocess.run(
        ["g++", "-x", "c", "-std=c11", "-Wall", "-Werror", "-fsyntax-only",
         str(src)],
        check=True, capture_output=True,
    )
    # ABI consistency: compiling the header TOGETHER with the actual
    # native runtime makes any signature drift a compile error.
    import os

    runtime = os.path.join(os.path.dirname(__file__), "..", "native",
                           "tb_runtime.cpp")
    both = tmp_path / "abi_check.cpp"
    both.write_text(
        f'#include "{header}"\n#include "{os.path.abspath(runtime)}"\n'
    )
    subprocess.run(
        ["g++", "-std=c++17", "-fsyntax-only", str(both)],
        check=True, capture_output=True,
    )


def test_bindings_cover_all_enums_and_fields(tmp_path):
    from tigerbeetle_tpu import bindings

    bindings.generate(str(tmp_path))
    ts = (tmp_path / "types.ts").read_text()
    go = (tmp_path / "types.go").read_text()
    c = (tmp_path / "tb_types.h").read_text()
    # Every CreateTransferResult code appears in every language.
    for member in types.CreateTransferResult:
        assert f"  {member.name}: {member.value}," in ts
        camel = "".join(p.capitalize() for p in member.name.split("_"))
        assert f"CreateTransferResult{camel} CreateTransferResult = {member.value}" in go
        assert (
            f"TB_CREATE_TRANSFER_RESULT_{member.name.upper()} = {member.value},"
            in c
        )
    # u128 fields collapse to one logical field in TS/Go.
    assert "id: bigint;" in ts and "Id [2]uint64" in go
    # The C structs keep raw limb layout for ABI fidelity.
    assert "uint64_t id_lo;" in c and "uint64_t id_hi;" in c
    assert "tb_client_request" in c
