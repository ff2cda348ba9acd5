"""Observability spine: tracer stage discipline, registry/histogram
exactness, snapshot monotonicity under chaos, trace merging, scrape
rendering, and the hot-path overhead contract."""

import json
import math
import random

import numpy as np
import pytest

import tigerbeetle_tpu.state_machine.device_engine as de
from tigerbeetle_tpu import obs
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.testing.chaos import ChaosLink
from tigerbeetle_tpu.testing.vopr import Workload
from tigerbeetle_tpu.utils.tracer import NOOP_RUN, Stage, Tracer

# ----------------------------------------------------------------------
# Tracer stage discipline + buffer accounting.


def _leaf(name="vsr.commit.reply", **kw):
    return Stage(obs.Registry(enabled=True).histogram(name + "_us"), name, **kw)


def test_tracer_a_stage_open_twice_asserts_where_leaves_must_not_nest():
    """What the slot discipline held for spans, `strict_leaves` holds
    for stages: the same leaf opened again while it is open asserts at
    once.  On ANOTHER thread it is the documented concurrency (a
    worker's leaf is its own)."""
    import threading

    t = Tracer("json")
    t.strict_leaves = True
    commit = _leaf()
    with t.stage(commit):
        with pytest.raises(AssertionError,
                           match="leaf vsr.commit.reply opened inside leaf vsr.commit.reply"):
            t.stage(commit).__enter__()
        th = threading.Thread(target=lambda: t.stage(commit).__enter__().__exit__())
        th.start()
        th.join(timeout=10)
    assert commit.hist.count == 2       # the loop's and the worker's


def test_tracer_a_part_without_its_leaf_asserts():
    """An end without a start cannot be written with a context manager;
    what can go unbalanced is a part outside its leaf."""
    t = Tracer("json")
    t.strict_leaves = True
    part = _leaf("lsm.seal.encode", part=True)
    with pytest.raises(AssertionError, match="part lsm.seal.encode opened with no leaf"):
        t.stage(part)
    with t.stage(_leaf("vsr.commit.beat")):
        with t.stage(part):
            pass
    with pytest.raises(AssertionError, match="opened with no leaf"):
        t.stage(part)
    assert part.hist.count == 1


def test_tracer_dump_closes_open_stages_at_now_and_marks_them():
    """A dump from the SIGTERM handler finds the loop wherever it
    stands: what is open is closed at now and marked, never refused,
    and the tracer goes on as it was."""
    t = Tracer("json")
    with t.stage(_leaf("vsr.commit", leaf=False)):
        doc = json.loads(t.dump())
        (span,) = doc["traceEvents"]
        assert span["name"] == "vsr.commit" and span["args"]["open_at_dump"] is True
    (span,) = json.loads(t.dump())["traceEvents"]  # balanced: once, unmarked
    assert "args" not in span


def test_tracer_buffer_drop_accounting():
    t = Tracer("json", buffer_max=16)
    for i in range(50):
        t.instant("tick", i=i)
    assert t.dropped == 50 - 16
    data = json.loads(t.dump())
    assert len(data["traceEvents"]) == 16
    assert data["otherData"]["dropped_events"] == 34
    # Oldest dropped first: the survivors are the newest 16.
    assert data["traceEvents"][0]["args"]["i"] == 34


# ----------------------------------------------------------------------
# Histogram: exact nearest-rank bucket selection vs a sorted oracle.


def _oracle(sorted_samples, q):
    rank = min(len(sorted_samples), max(1, math.ceil(q * len(sorted_samples))))
    return obs.Histogram.quantize(sorted_samples[rank - 1])


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_histogram_percentiles_match_sorted_oracle(seed):
    rng = random.Random(seed)
    reg = obs.Registry(enabled=True)
    h = reg.histogram("lat_us")
    samples = []
    for _ in range(4000):
        # Mixed scales: sub-µs to minutes, plus exact bucket edges.
        scale = rng.choice([1, 1, 10, 1000, 1e6, 6e7])
        v = rng.random() * scale
        if rng.random() < 0.05:
            v = float(rng.choice([0, 1, 15, 16, 17, 31, 32, 1 << 20]))
        samples.append(v)
        h.observe(v)
    ss = sorted(samples)
    for q in (0.01, 0.25, 0.5, 0.9, 0.99, 0.999, 1.0):
        assert h.percentile(q) == _oracle(ss, q), q
    assert h.count == len(samples)
    assert h.max == max(samples)
    assert abs(h.total - sum(samples)) < 1e-6 * max(1.0, sum(samples))


def test_histogram_bucket_arithmetic_is_consistent():
    # Every representable int maps into a bucket whose [lower, upper)
    # range contains it, and bucket indices are monotone in value.
    prev_idx = -1
    for n in list(range(0, 4096)) + [1 << k for k in range(12, 31)]:
        idx = obs.Histogram.bucket_of(n)
        assert idx >= prev_idx
        prev_idx = max(prev_idx, idx)
        assert n < obs.Histogram.upper_of(idx)


def test_histogram_empty_and_single():
    h = obs.Registry(enabled=True).histogram("x_us")
    assert h.percentile(0.99) == 0.0
    h.observe(42)
    assert h.percentile(0.5) == obs.Histogram.quantize(42)


@pytest.mark.parametrize("seed", [5, 6])
def test_histogram_unit_scale_resolves_sub_unit_floor(seed):
    """unit_scale=16 (r22: vsr.prepare_us / prepare_ok_us): sub-µs
    samples land in 1/16-µs buckets instead of collapsing into bucket
    0, percentiles descale back to raw units and still match the
    sorted oracle quantized at the scaled resolution, and count/sum/
    max stay in raw units."""
    rng = random.Random(seed)
    reg = obs.Registry(enabled=True)
    h = reg.histogram("fine_us", unit_scale=16)
    coarse = reg.histogram("coarse_us")
    samples = [rng.random() * rng.choice([0.2, 1, 4, 50]) for _ in range(3000)]
    for v in samples:
        h.observe(v)
        coarse.observe(v)
    ss = sorted(samples)
    for q in (0.25, 0.5, 0.9, 0.99):
        rank = min(len(ss), max(1, math.ceil(q * len(ss))))
        oracle = obs.Histogram.quantize(ss[rank - 1] * 16) / 16
        assert h.percentile(q) == oracle, q
    # The widened floor actually resolves the sub-µs mass the unscaled
    # histogram collapses: its p50 sits below 1 µs (impossible for
    # unit_scale=1, whose smallest nonzero representative is 1).
    assert h.percentile(0.5) < 1.0 <= coarse.percentile(0.5)
    assert h.count == len(samples)
    assert h.max == max(samples)
    assert abs(h.total - sum(samples)) < 1e-6 * max(1.0, sum(samples))


def test_histogram_unit_scale_must_agree_across_registrations():
    reg = obs.Registry(enabled=True)
    reg.histogram("h_us", unit_scale=16)
    reg.histogram("h_us", unit_scale=16)  # idempotent re-registration
    with pytest.raises(AssertionError, match="unit_scale"):
        reg.histogram("h_us")


# ----------------------------------------------------------------------
# Registry: composition, compat properties, version-driven dedup.


def test_registry_scope_and_attach_compose_one_snapshot():
    parent = obs.Registry(enabled=True)
    child = obs.Registry(enabled=True)
    parent.attach("vsr", child)
    child.counter("prepares").inc(3)
    parent.scope("sm").counter("events").inc(7)
    parent.gauge_fn("queue", lambda: 11)
    snap = parent.snapshot()
    assert snap["vsr.prepares"] == 3
    assert snap["sm.events"] == 7
    assert snap["queue"] == 11
    # Child mutations bump the composed version.
    v0 = parent.version()
    child.counter("prepares").inc()
    assert parent.version() == v0 + 1


def test_registry_rejects_kind_confusion():
    reg = obs.Registry(enabled=True)
    reg.counter("x")
    with pytest.raises(AssertionError):
        reg.gauge("x")


def test_stat_property_compat_reads_and_resets():
    sm = TpuStateMachine(account_capacity=1 << 10, transfer_capacity=1 << 10)
    assert sm.stat_device_events == 0
    sm.stat_device_events += 5          # property routes to the handle
    assert sm.metrics.snapshot()["device_events"] == 5
    sm.stat_device_events = 0           # a reset through the setter
    assert sm.stat_device_events == 0
    # Version moved for every write: idle-dedup can't miss it.
    assert sm.metrics.version() >= 2


def test_snapshot_version_changes_with_any_counter():
    reg = obs.Registry(enabled=True)
    a = reg.counter("a")
    s0 = reg.snapshot()
    s1 = reg.snapshot()
    assert s0 == s1  # idle: identical snapshot, same version
    a.inc()
    s2 = reg.snapshot()
    assert s2["version"] > s1["version"]
    # A counter added AFTER the comparison baseline still shows up —
    # the failure mode of the old hand-picked tuple.
    reg.counter("later").inc()
    s3 = reg.snapshot()
    assert s3["version"] > s2["version"] and "later" in s3


# ----------------------------------------------------------------------
# Snapshot monotonicity across a chaos smoke run.


@pytest.fixture
def _fast_lifecycle(monkeypatch):
    monkeypatch.setattr(de, "_WINDOW", 4)
    monkeypatch.setattr(de, "_BACKOFF_MS", 0.0)
    monkeypatch.setattr(de, "_PROBE_EVERY", 2)


def test_registry_snapshot_monotonic_under_chaos(_fast_lifecycle):
    """Counters never decrease and the version strictly increases
    whenever values change, across a seeded chaos workload that
    demotes/re-promotes the device engine mid-stream."""
    link = ChaosLink(seed=31, p_transient=0.03, p_fatal=0.01, down_for=4)
    sm = TpuStateMachine(
        engine="device", account_capacity=1 << 12, device_link=link
    )
    h = hz.SingleNodeHarness(sm)
    wl = Workload(77)
    prev = sm.metrics.snapshot()
    sent = 0
    while sent < 300:
        operation, body, _must = wl.next_request()
        sent += 1 if not body else len(body) // 128
        h.submit(operation, body)
        snap = sm.metrics.snapshot()
        for key, value in snap.items():
            if ".p" in key:  # percentiles may move both ways
                continue
            if key in prev:
                assert value >= prev[key] - 1e-9, (key, prev[key], value)
        if snap != prev:
            assert snap["version"] > prev["version"]
        prev = snap
    # The run exercised the lifecycle counters it claims to cover.
    assert prev["dev.link.errors"] >= 1


# ----------------------------------------------------------------------
# Overhead contract: backend "none" / TB_METRICS=0 cost one check.


def test_disabled_tracer_stage_is_shared_noop():
    t = Tracer("none")
    assert not t.enabled
    off = obs.Registry(enabled=False)
    commit = Stage(off.histogram("commit_us"), "vsr.commit", leaf=False)
    write = Stage(off.histogram("journal.write_us"), "vsr.journal.write")
    # Identity: no per-site allocation on the disabled path.
    assert t.stage(commit, op=7) is NOOP_RUN
    assert t.stage(write) is NOOP_RUN
    t.instant("marker")   # a no-op too
    assert len(json.loads(t.dump())["traceEvents"]) == 0


def test_disabled_histogram_is_shared_noop():
    reg = obs.Registry(enabled=False)
    h1 = reg.histogram("a_us")
    h2 = reg.histogram("b_us")
    assert h1 is h2  # one shared no-op instance
    timer = h1.time()
    with timer:
        pass
    assert h1.count == 0


def test_traced_site_overhead_is_one_attribute_check():
    """A traced hot-path site on the disabled backend must cost on the
    order of a method call — generously bounded at 5 µs/site so a
    noisy CI box cannot flake this."""
    import time

    t = Tracer("none")
    commit = Stage(obs.Registry(enabled=False).histogram("commit_us"),
                   "vsr.commit", leaf=False)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with t.stage(commit):
            pass
    per_site = (time.perf_counter() - t0) / n
    assert per_site < 5e-6, f"{per_site * 1e9:.0f} ns/site"


@pytest.mark.slow
def test_metrics_overhead_simple_kernel_within_2pct(monkeypatch):
    """`simple` kernel bench throughput with metrics on vs off stays
    within 2% (median of 5 interleaved runs each)."""
    import time

    from tigerbeetle_tpu.types import Operation

    def run_stream(metrics_on: bool) -> float:
        monkeypatch.setenv("TB_METRICS", "1" if metrics_on else "0")
        sm = TpuStateMachine(
            account_capacity=1 << 12, transfer_capacity=1 << 16
        )
        h = hz.SingleNodeHarness(sm)
        h.submit(
            Operation.create_accounts,
            hz.pack([hz.account(i) for i in range(1, 65)]),
        )
        rng = np.random.default_rng(5)
        bodies = []
        tid = 1000
        for _ in range(6):
            rows = [
                dict(
                    id=tid + j,
                    debit_account_id=int(rng.integers(1, 65)),
                    credit_account_id=int(rng.integers(1, 65)),
                    amount=1,
                )
                for j in range(2048)
            ]
            tid += 2048
            bodies.append(hz.pack([hz.transfer(**r) for r in rows]))
        # Untimed warmup (JIT compiles), then the timed replay.
        h.submit(Operation.create_transfers, bodies[0])
        t0 = time.perf_counter()
        for body in bodies[1:]:
            h.submit(Operation.create_transfers, body)
        sm.sync()
        return (len(bodies) - 1) * 2048 / (time.perf_counter() - t0)

    on, off = [], []
    run_stream(True)  # process-level warmup
    for _ in range(5):
        on.append(run_stream(True))
        off.append(run_stream(False))
    ratio = float(np.median(on)) / float(np.median(off))
    assert 0.98 <= ratio, f"metrics-on throughput ratio {ratio:.4f}"


# ----------------------------------------------------------------------
# Trace merging + scrape rendering.


def test_merge_traces_builds_one_perfetto_timeline(tmp_path):
    from tigerbeetle_tpu.testing.cluster import merge_traces

    paths = []
    for i in range(2):
        t = Tracer("json", process_id=0)  # deliberately colliding pids
        with t.stage(_leaf("vsr.commit", leaf=False), op=i):
            t.instant("prepare_ok", op=i)
        p = tmp_path / f"r{i}.json"
        t.write(str(p))
        paths.append(str(p))
    merged = merge_traces(paths, str(tmp_path / "merged.json"))
    data = json.load(open(tmp_path / "merged.json"))
    assert data == merged
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1}  # re-keyed per input file
    meta = [e for e in merged["traceEvents"] if e.get("ph") == "M"]
    assert [m["args"]["name"] for m in meta] == ["replica0", "replica1"]


def test_trace_demo_produces_cross_replica_drain(tmp_path):
    from tigerbeetle_tpu.testing.cluster import trace_demo

    out = str(tmp_path / "merged.json")
    info = trace_demo(out, n_replicas=2, batches=3, transfers_per_batch=4)
    assert info["trace_path"] == out and info["ops_committed"] > 0
    data = json.load(open(out))
    names = {e["name"] for e in data["traceEvents"]}
    # The full replicated-drain timeline, across both process tracks.
    for required in (
        "prepare", "vsr.journal.write", "vsr.gc.sync", "prepare_ok",
        "vsr.commit", "reply", "vsr.commit.prefetch", "vsr.commit.reply",
    ):
        assert required in names, required
    assert {e["pid"] for e in data["traceEvents"]} == {0, 1}


def test_merge_traces_skips_bad_files_and_warns(tmp_path):
    """Empty, truncated, missing, and non-object inputs are skipped
    with a warning + otherData.skipped entry; the survivors still
    merge (a replica killed mid-dump must not void the postmortem)."""
    from tigerbeetle_tpu.testing.cluster import merge_traces

    good = tmp_path / "good.json"
    t = Tracer("json")
    t.instant("commit", op=1)
    t.write(str(good))
    empty = tmp_path / "empty.json"
    empty.write_text("")
    truncated = tmp_path / "truncated.json"
    truncated.write_text('{"traceEvents": [{"name": "comm')
    notdict = tmp_path / "notdict.json"
    notdict.write_text("[1, 2, 3]")
    missing = tmp_path / "missing.json"

    with pytest.warns(UserWarning, match="merge_traces: skipping"):
        merged = merge_traces(
            [str(empty), str(good), str(truncated), str(missing),
             str(notdict)],
            str(tmp_path / "merged.json"),
        )
    names = [e["name"] for e in merged["traceEvents"]]
    assert "commit" in names  # the good file survived
    skipped = merged["otherData"]["skipped"]
    assert {s["label"] for s in skipped} == {
        "replica0", "replica2", "replica3", "replica4"
    }
    # The written file parses and matches.
    assert json.load(open(tmp_path / "merged.json")) == merged


def test_merge_traces_many_replicas(tmp_path):
    """>2-replica merges keep every input on its own re-keyed track."""
    from tigerbeetle_tpu.testing.cluster import merge_traces

    paths = []
    for i in range(5):
        t = Tracer("json", process_id=0)
        t.instant("prepare", op=i)
        p = tmp_path / f"r{i}.json"
        t.write(str(p))
        paths.append(str(p))
    merged = merge_traces(paths)
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1, 2, 3, 4}
    meta = [e for e in merged["traceEvents"] if e.get("ph") == "M"]
    assert len(meta) == 5
    assert "skipped" not in merged["otherData"]


def test_stats_scrape_monotonic_under_concurrent_load(tmp_path):
    """Scrape while drains are mid-flight: counters in successive
    snapshots never decrease, the version strictly increases whenever
    values change, and the exemplar ring honors its bound —
    concurrency must not tear the snapshot."""
    import socket
    import threading

    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.obs.scrape import scrape_stats
    from tigerbeetle_tpu.runtime.native import native_available
    from tigerbeetle_tpu.runtime.server import (
        ReplicaServer,
        format_data_file,
    )
    from tigerbeetle_tpu.state_machine import CpuStateMachine

    if not native_available():
        pytest.skip("native runtime not built")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    address = f"127.0.0.1:{port}"
    path = str(tmp_path / "r0.tb")
    format_data_file(path, cluster=17, config=cfg.TEST_MIN)
    server = ReplicaServer(
        path, cluster=17, addresses=[address], replica_index=0,
        state_machine_factory=lambda: CpuStateMachine(cfg.TEST_MIN),
        config=cfg.TEST_MIN,
    )
    stop = threading.Event()
    loop = threading.Thread(
        target=lambda: [server.poll_once(1) for _ in iter(
            lambda: not stop.is_set(), False
        )],
        daemon=True,
    )
    loop.start()
    client = None
    try:
        client = Client(address, 17, client_id=91, timeout_ms=30_000)
        assert client.create_accounts(
            [{"id": 1, "ledger": 1, "code": 1},
             {"id": 2, "ledger": 1, "code": 1}]
        ) == []
        errors = []

        def drive():
            try:
                for k in range(60):
                    client.create_transfers([
                        {"id": 1000 + k, "debit_account_id": 1,
                         "credit_account_id": 2, "amount": 1,
                         "ledger": 1, "code": 1}
                    ])
            except Exception as exc:  # noqa: BLE001
                errors.append(repr(exc))

        driver = threading.Thread(target=drive, daemon=True)
        driver.start()
        ring = server.replica.anatomy.exemplar_ring
        prev = None
        scrapes = 0
        while driver.is_alive() or scrapes < 3:
            snap = scrape_stats(address, 17, timeout_ms=10_000)
            scrapes += 1
            assert len(snap["anatomy.exemplars"]) <= ring
            if prev is not None:
                for key, value in snap.items():
                    if ".p" in key or key in (
                        "server.queue_depth", "vsr.anatomy.open",
                        "anatomy.exemplars",
                    ):
                        continue  # gauges/percentiles move both ways
                    if key in prev and isinstance(value, (int, float)):
                        assert value >= prev[key] - 1e-9, (
                            key, prev[key], value
                        )
                if {k: v for k, v in snap.items()
                        if k != "anatomy.exemplars"} != {
                            k: v for k, v in prev.items()
                            if k != "anatomy.exemplars"}:
                    assert snap["version"] >= prev["version"]
            prev = snap
            if not driver.is_alive() and scrapes >= 3:
                break
        driver.join(timeout=30)
        assert errors == [], errors
        assert prev["vsr.commits"] >= 60
    finally:
        stop.set()
        loop.join(timeout=5)
        if client is not None:
            client.close()
        server.close()


def test_stats_reply_roundtrips_snapshot():
    from tigerbeetle_tpu.obs.scrape import SCRAPE_REQUEST, stats_reply
    from tigerbeetle_tpu.vsr import wire
    from tigerbeetle_tpu.vsr.wire import Command, VsrOperation

    request = wire.make_header(
        command=Command.request, operation=VsrOperation.stats,
        cluster=9, request=SCRAPE_REQUEST,
    )
    wire.finalize_header(request, b"")
    snap = {"vsr.prepares_written": 12, "storage.fsyncs": 4, "version": 99}
    reply, body = stats_reply(snap, request)
    assert wire.verify_header(reply, body)
    assert int(reply["command"]) == int(Command.reply)
    assert int(reply["operation"]) == int(VsrOperation.stats)
    assert int(reply["request"]) == SCRAPE_REQUEST
    assert json.loads(body.decode()) == snap


def test_server_stats_op_never_enters_consensus():
    """A stats request reaching a bare VsrReplica (no server layer in
    front) is dropped, not prepared — op 6 would otherwise hit the
    asserting state-machine dispatch at commit."""
    from tigerbeetle_tpu.testing.cluster import Cluster
    from tigerbeetle_tpu.vsr import wire
    from tigerbeetle_tpu.vsr.wire import Command, VsrOperation

    c = Cluster(replica_count=1)
    r = c.replicas[0]
    c.run_until(lambda: r.status == "normal")
    ops_before = r.op
    h = wire.make_header(
        command=Command.request, operation=VsrOperation.stats,
        cluster=c.cluster_id, request=1,
    )
    wire.finalize_header(h, b"")
    r.on_message(h, b"")
    assert r.op == ops_before


def test_tb_metrics_env_plumbs_to_state_machine(monkeypatch):
    monkeypatch.setenv("TB_METRICS", "0")
    sm = TpuStateMachine(account_capacity=1 << 10, transfer_capacity=1 << 10)
    assert not sm.metrics.enabled
    monkeypatch.setenv("TB_METRICS", "1")
    sm = TpuStateMachine(account_capacity=1 << 10, transfer_capacity=1 << 10)
    assert sm.metrics.enabled


def _drive_speculative_batches(monkeypatch):
    """One fresh-id stream forced through the speculative dispatcher;
    returns the machine after every future resolved."""
    from tigerbeetle_tpu.types import Operation

    monkeypatch.setattr(de, "_WINDOW", 2)
    monkeypatch.setenv("TB_WAVES_SPECULATE", "force")
    sm = TpuStateMachine(engine="device", account_capacity=(1 << 10) + 1)
    h = hz.SingleNodeHarness(sm)
    h.submit(
        Operation.create_accounts,
        hz.pack([hz.account(i) for i in range(1, 9)]),
    )
    futs = []
    for k in range(4):
        rows = [
            hz.transfer(100 + 4 * k + j, debit_account_id=1 + j,
                        credit_account_id=5 + j, amount=1 + j)
            for j in range(4)
        ]
        futs.append(h.submit_async(Operation.create_transfers, hz.pack(rows)))
    for f in futs:
        f.result()
    sm.sync()
    return sm


def test_spec_counters_in_registry_and_metrics_off_noop(monkeypatch):
    """dev_wave.spec.* rides the machine registry (the stats scrape and
    flight postmortem read the same snapshot): counters tick under
    TB_METRICS=1 with the validation histogram populated; under
    TB_METRICS=0 the histogram is the shared no-op (no clock-derived
    samples in the snapshot) while the routing counters stay live —
    routing and the scrape depend on them."""
    monkeypatch.setenv("TB_METRICS", "1")
    sm = _drive_speculative_batches(monkeypatch)
    snap = sm.metrics.snapshot()
    assert snap["dev_wave.spec.attempts"] == 4
    assert snap["dev_wave.spec.hits"] == 4
    assert snap["dev_wave.spec.plan_skipped"] == 4
    assert snap["dev_wave.spec.steps"] == 4
    assert snap["dev_wave.spec.validation_us.count"] == 4

    monkeypatch.setenv("TB_METRICS", "0")
    sm0 = _drive_speculative_batches(monkeypatch)
    assert not sm0.metrics.enabled
    hist = sm0._dev.spec_stats["validation_us"]
    assert hist is obs.Registry(enabled=False).histogram("x_us"), (
        "TB_METRICS=0 must hand the spec path the shared no-op histogram"
    )
    snap0 = sm0.metrics.snapshot()
    assert snap0["dev_wave.spec.attempts"] == 4  # counters stay live
    assert snap0["dev_wave.spec.hits"] == 4
    assert "dev_wave.spec.validation_us.count" not in snap0


def test_flight_dump_embeds_stats_snapshot(tmp_path):
    """A flight recorder wired with a stats provider embeds the full
    registry snapshot in every dump's otherData — the demotion
    postmortem carries the dev_wave.spec.* / link counters that
    explain it — and a provider failure degrades to a recorded error,
    never a voided postmortem (dumps run inside signal handlers)."""
    from tigerbeetle_tpu.obs.flight import FlightRecorder

    reg = obs.Registry(enabled=True)
    reg.counter("dev_wave.spec.attempts").inc(3)
    fr = FlightRecorder(capacity=8, stats_fn=reg.snapshot)
    fr.note("device_demoted", error="boom")
    dump = fr.dump(reason="test")
    assert dump["otherData"]["stats"]["dev_wave.spec.attempts"] == 3
    path = tmp_path / "flight.json"
    fr.write(str(path))
    assert json.load(open(path))["otherData"]["stats"][
        "dev_wave.spec.attempts"
    ] == 3

    def bad_stats():
        raise RuntimeError("registry gone")

    fr2 = FlightRecorder(capacity=8, stats_fn=bad_stats)
    fr2.note("assertion_failure")
    dump2 = fr2.dump()
    assert "stats" not in dump2["otherData"]
    assert "registry gone" in dump2["otherData"]["stats_error"]
    assert len(dump2["traceEvents"]) == 1  # the ring survived


def test_tb_trace_env_selects_backend(monkeypatch):
    monkeypatch.setenv("TB_TRACE", "json")
    assert Tracer.from_env(3).enabled
    monkeypatch.delenv("TB_TRACE")
    assert not Tracer.from_env().enabled
