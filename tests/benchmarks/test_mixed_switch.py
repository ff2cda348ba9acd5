"""The cell `bench1r-mixed-c16` (configuration `switch16-mixed-1r`,
traffic `mixed-c16`, kind `chains2p`): sixteen sessions whose requests
of one class each the primary coalesces into prepares of mixed classes.

The kind's reference, as it stands, held to `CpuStateMachine` with the
requests coalesced as the primary does it; the manifest's entries; the
three per-layer metrics' readers on a program without their keys; and
the cell as one traced rehearsal on the CPU backend, in which the
router cuts prepares into runs, the chip decides every event and no
compile falls inside the window.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import compare, load, manifest as mf, wire  # noqa: E402
from benchmarks.harness.gen import chains2p  # noqa: E402
from manifest_checks import manifest_lists  # noqa: E402
from test_reference import _HEALTHY, Oracle  # noqa: E402
from tigerbeetle_tpu import constants  # noqa: E402
from tigerbeetle_tpu.state_machine import demuxer  # noqa: E402
from tigerbeetle_tpu.types import Operation  # noqa: E402

RUN = os.path.join(_REPO, "benchmarks", "run.py")
CELL = "bench1r-mixed-c16"
M = mf.Manifest()
CONFIG = {"accounts": 1024, "ledger": 1}
PARAMS = {"sessions": 16, "request_events": 40, "amount_max": 999,
          "chain_len": [1, 7], "chain_fail_share": 0.2, "void_share": 0.3,
          "refinalize_share": 0.05}
SEEDS = [4, 44, 2**31 + 4402]
ROUTING = "state machine routing"
ENTRIES = [
    {"name": "mixed_runs_per_prepare", "unit": "count", "source": "program_counter"},
    {"name": "cut_prepares_per_prepare", "unit": "count", "source": "program_counter"},
    {"name": "plan_cut_us_per_prepare", "unit": "us", "source": "program_span"},
]
ENTRIES = [dict(e, better="lower", layer=ROUTING, moves="commit_events_per_s",
                workloads=[CELL]) for e in ENTRIES]


def _coalesced(seed: int, rounds: int = 5, per_prepare: int = 7):
    """A funding request a session, then `rounds` rounds of the sixteen
    sessions in an order drawn from the seed, packed `per_prepare`
    requests to a prepare; each prepare committed whole on the oracle,
    each request applied alone on the reference.
    -> (gen, ref, oracle, [[(session, index, rows, reply)] a prepare])"""
    gen = chains2p.make(PARAMS, CONFIG, seed)
    ref, oracle = chains2p.reference(gen), Oracle()
    assert oracle.submit(Operation.create_accounts, gen.accounts().tobytes()) == b""
    rng = np.random.default_rng(seed)
    order = [(int(s), i) for i in range(rounds + 1)
             for s in rng.permutation(gen.sessions)]
    prepares = []
    for at in range(0, len(order), per_prepare):
        requests = [(s, i, gen.request(s, i)) for s, i in order[at:at + per_prepare]]
        body = np.concatenate([rows for _s, _i, rows in requests])
        got = np.frombuffer(oracle.submit(Operation.create_transfers,
                                          body.tobytes()), wire.CREATE_RESULT)
        # The demuxer's split: each request's failures, from its first row.
        start, done = 0, []
        for s, i, rows in requests:
            mine = got[(got["index"] >= start) & (got["index"] < start + len(rows))].copy()
            mine["index"] -= start
            start += len(rows)
            done.append((s, i, rows, mine.tobytes()))
        prepares.append(done)
    return gen, ref, oracle, prepares


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_equals_the_oracle_on_coalesced_prepares(seed):
    gen, ref, oracle, prepares = _coalesced(seed)
    classes = set()
    for prepare in prepares:
        kinds = {gen.klass(s, i) for s, i, _rows, _reply in prepare}
        classes |= kinds
        for s, i, rows, reply in sorted(prepare, key=lambda x: x[:2]):
            assert ref.apply(rows) == reply, (s, i, gen.klass(s, i))
    assert classes == {"fund", "P", "C", "F"}
    # Most prepares after the funding hold more than one class.
    mixed = [p for p in prepares[3:]
             if len({gen.klass(s, i) for s, i, _r, _y in p}) > 1]
    assert len(mixed) >= len(prepares[3:]) - 1
    for prepare in prepares:
        for s, i, rows, _reply in prepare:
            stored = oracle.submit(Operation.lookup_transfers,
                                   wire.ids_body(rows["id_lo"]))
            assert (wire.masked(wire.TRANSFER, stored) == ref.stored_rows(rows)).all()
    ids = np.arange(1, CONFIG["accounts"] + 1, dtype=np.uint64)
    rows = wire.masked(wire.ACCOUNT, oracle.submit(Operation.lookup_accounts,
                                                  wire.ids_body(ids)))
    assert (rows == ref.account_rows()).all()


@pytest.mark.parametrize("seed", [3, 4_000_000_444])
def test_control_lost_ack_is_not_correct(seed):
    gen = chains2p.make(PARAMS, CONFIG, seed)
    ref = chains2p.reference(gen)
    records, t = [], 0.0
    for i in range(5):
        for s in range(gen.sessions):
            t += 1.0
            rows = gen.request(s, i)
            records.append(load.Record(s, i, len(rows), t, t + 0.5, ref.apply(rows)))
    sample = compare.sample_requests(records, seed, 4)
    want = compare.reference_side(gen, chains2p.reference(gen), records, sample)
    lost = compare.last_write(records)
    held = compare.reference_side(gen, chains2p.reference(gen), records, sample,
                                  drop=lost)
    ok, table = compare.verdict(compare.numbers(held, want, records, _HEALTHY))
    assert not ok and table["account_rows_differing"]["value"] > 0


def check_the_cell_is_the_manifests(m):
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "switch16-mixed-1r", "mixed-c16", 1)
    config, traffic = m.config(cell), m.traffic(cell)
    chains = m.config(m.cell("bench1r-chains2p-c4"))
    for key in ("replicas", "chips_per_replica", "cluster", "accounts", "ledger",
                "event_bytes", "request_events_max", "launcher", "server",
                "guarantees", "reduced"):
        assert config[key] == chains[key], key
    assert len(config["source"]) <= 200
    assert {"clients", "request_rows", "mix", "timeout", "settlement"} <= set(
        config["assumed"])
    base = m.traffic(m.cell("bench1r-chains2p-c4"))
    assert traffic["kind"] == base["kind"] == "chains2p"
    for key in ("loop", "chain_len", "chain_fail_share", "void_share",
                "refinalize_share", "amount_max", "request_timeout_ms", "trace"):
        assert traffic[key] == base[key], key
    assert traffic["sessions"] == 16 <= constants.PRODUCTION.clients_max // 4
    assert sum(traffic["warm_requests_per_session"]) >= 4
    # Seven requests and their demux records fill a prepare's body; an
    # eighth does not fit, and at one row more a request only six do.
    limit = constants.PRODUCTION.message_body_size_max
    sub = demuxer.TRAILER_DTYPE.itemsize

    def fit(rows):
        return limit // (rows * config["event_bytes"] + sub)

    assert fit(traffic["request_events"]) == 7
    assert fit(traffic["request_events"] + 1) == 6
    # One checkpoint in every window of 960 - offset to 1,920 - offset prepares.
    assert traffic["phase"]["cross_checkpoints_before"] == 1
    assert 0 < 960 - traffic["phase"]["offset_ops"] <= 100
    # The rehearsal mixes classes: its sessions' requests in flight
    # together fit the suite's bucket of 512.
    rehearsal = traffic["rehearsal"]
    assert rehearsal["sessions"] > 1
    assert rehearsal["sessions"] * rehearsal["request_events"] <= 512
    for e in ENTRIES:
        spec = manifest_lists(m, e)
        assert spec["reader"] == "scrape_delta_ratio"


def test_the_cell_is_the_manifests():
    check_the_cell_is_the_manifests(M)


@pytest.mark.parametrize("name", [e["name"] for e in ENTRIES])
def test_a_program_without_the_keys_gives_the_readers_nothing(name):
    """The parent's scrapes: each reader returns None and raises
    nothing, so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "vsr.commits": 5, "sm.plan.route_us.sum": 9.0}
    later = dict(old, **{"vsr.commit_us.count": 9, "vsr.commits": 9})
    ctx = {"before": [dict(old)], "after": [dict(later)],
           "at_close": [dict(later)], "requests": 7, "trace": None}
    spec = M.layer_spec(M.per_layer[name])
    assert mf.reader(spec).read(spec, ctx) is None


# ---------------------------------------------------------------------------
# The cell, as one traced rehearsal.


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """-> (the result line, the run's scrapes)"""
    run_dir = tmp_path_factory.mktemp("cell") / "run"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 441),
         "--seconds", "4", "--trace", "1", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return line, json.load(f)


def test_the_rehearsal_cuts_prepares_and_decides_every_event_on_the_device(rehearsed):
    line, scrapes = rehearsed
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert all(v == {"value": 0, "limit": 0} for v in line["compared"].values())
    got = line["metrics"]
    assert got["mixed_runs_per_prepare"]["value"] > 1
    assert got["cut_prepares_per_prepare"]["value"] > 0
    assert got["plan_cut_us_per_prepare"]["value"] > 0
    assert got["device_computed_pct"]["value"] == 100.0
    before, after = scrapes["before"][0], scrapes["after"][0]
    assert line["compared"]["events_not_on_device"]["value"] == 0
    assert after["sm.dev_wave.batches"] == after["sm.dev.kind.waves.batches"] == 0
    assert after["sm.host_semantic_events"] == 0
    ran = {k for k in ("orderfree_tight", "linked_small", "two_phase_lo")
           if after[f"sm.dev.kind.{k}.batches"] > before[f"sm.dev.kind.{k}.batches"]}
    assert ran == {"orderfree_tight", "linked_small", "two_phase_lo"}


def test_no_compile_falls_inside_the_rehearsed_window(rehearsed):
    _line, scrapes = rehearsed
    before, at_close = scrapes["before"][0], scrapes["at_close"][0]
    assert at_close["sm.dev.compile.count"] == before["sm.dev.compile.count"]
    assert at_close["vsr.commits"] > before["vsr.commits"]
