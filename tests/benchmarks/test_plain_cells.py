"""The two full-batch cells (PR 28): `bench1r-plain-c4` and
`bench3r-plain-c4`, each run as a traced rehearsal on the CPU backend.

Their five per-layer metrics have files (and two readers); `ENTRIES`
is their manifest entries' text, with the cells they were written for
(PR 40 appended them).  They are read here from the scrapes of the
served runs.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import manifest as mf  # noqa: E402
from manifest_checks import manifest_lists  # noqa: E402

RUN = os.path.join(_REPO, "benchmarks", "run.py")
ONE, THREE = "bench1r-plain-c4", "bench3r-plain-c4"
VSR = "VSR, journal, checkpoint"
ENTRIES = [
    {"name": "backup_lag_ops", "unit": "ops", "better": "lower",
     "source": "program_counter", "layer": VSR, "moves": "request_p95_ms",
     "workloads": [THREE]},
    {"name": "quorum_wait_us_per_prepare", "unit": "us", "better": "lower",
     "source": "program_span", "layer": VSR, "moves": "request_p50_ms",
     "workloads": [THREE]},
    {"name": "replicate_send_us_per_prepare", "unit": "us", "better": "lower",
     "source": "program_span", "layer": VSR, "moves": "commit_events_per_s",
     "workloads": [THREE]},
    {"name": "backup_accept_us_per_prepare", "unit": "us", "better": "lower",
     "source": "program_span", "layer": VSR, "moves": "commit_events_per_s",
     "workloads": [THREE]},
    {"name": "grid_blocks_peak_pct", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "LSM spill and compaction",
     "moves": "commit_events_per_s", "workloads": [ONE, THREE]},
]
UNITS = {e["name"]: e["unit"] for e in ENTRIES}
M = mf.Manifest()


def rehearse(cell: str, seed: int, run_dir) -> tuple[dict, dict]:
    """-> (the result line, the run's scrapes)"""
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", str(seed),
         "--seconds", "4", "--trace", "1", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return line, json.load(f)


@pytest.fixture(scope="module")
def one(tmp_path_factory):
    return rehearse(ONE, 2**31 + 281, tmp_path_factory.mktemp("one") / "run")


@pytest.fixture(scope="module")
def three(tmp_path_factory):
    return rehearse(THREE, 2**31 + 283, tmp_path_factory.mktemp("three") / "run")


def check_the_cells_are_the_manifests(m):
    for name, config, chips in ((ONE, "upstream-bench-1r", 1),
                                (THREE, "upstream-bench-3r", 4)):
        cell = m.cell(name)
        assert (cell["config"], cell["chips"]) == (config, chips)
        traffic = m.traffic(cell)
        # plain-c4 in all but its name and the rehearsal's traced slice.
        base = m.traffic({"traffic": "plain-c4"})
        for key in ("kind", "loop", "sessions", "request_events", "amount_max",
                    "warm_requests_per_session", "phase", "read_back",
                    "request_timeout_ms", "trace"):
            assert traffic[key] == base[key], key
    three = m.config(m.cell(THREE))
    assert three["replicas"] == 3 and three["reduced"] == ["transfer_count", "replicas"]
    # The entries resolve to files and readers.
    for e in ENTRIES:
        manifest_lists(m, e)


def test_the_cells_are_the_manifests():
    check_the_cells_are_the_manifests(M)


def test_one_replica_full_batches(one):
    line, scrapes = one
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert all(v == {"value": 0, "limit": 0} for v in line["compared"].values())
    got = line["metrics"]
    assert got["grid_blocks_peak_pct"]["unit"] == "%"
    assert 0 <= got["grid_blocks_peak_pct"]["value"] < 100
    # Nothing of the replication to read at one replica: left out.
    assert not set(got) & (set(UNITS) - {"grid_blocks_peak_pct"})
    after = scrapes["after"][0]
    assert after["vsr.grid.blocks_total"] == 236539
    assert after["vsr.grid.blocks_acquired_peak"] >= after["vsr.grid.blocks_acquired"]
    assert after["vsr.quorum_wait_us.count"] == 0
    assert after["vsr.requests_forwarded"] == after["vsr.replies_relayed"] == 0


def test_three_replicas_full_batches(three):
    line, scrapes = three
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert all(v == {"value": 0, "limit": 0} for v in line["compared"].values())
    roots = line["info"]["state_roots"]
    assert len(roots) == 3 and len({(r, op) for r, op in roots}) == 1
    assert any(int(x, 16) for x, _op in roots)
    got = line["metrics"]
    for name, unit in UNITS.items():
        assert got[name]["unit"] == unit, name
    for name in ("quorum_wait_us_per_prepare", "replicate_send_us_per_prepare",
                 "backup_accept_us_per_prepare"):
        assert got[name]["value"] > 0, name
    assert got["backup_lag_ops"]["value"] >= 0
    assert got["quorum_wait_us_per_prepare"]["value"] > got[
        "replicate_send_us_per_prepare"]["value"]
    assert "requests_resent" in line["info"]
    # (How many replies a backup relays follows the client's latest
    # route, not the requests that backup sent on: a request the client
    # sent twice is answered twice, both along the later way.  The exact
    # counts are tests/test_forwarded_reply.py's.)
    primary, *backups = scrapes["after"]
    assert primary["vsr.replicate.send_us.count"] > 0
    assert primary["vsr.backup.accept_us.count"] == 0
    for b in backups:
        assert b["vsr.backup.accept_us.count"] > 0
        assert b["vsr.replicate.send_us.count"] == 0
        assert b["vsr.replies_relayed"] >= 0 <= b["vsr.requests_forwarded"]
        assert b["vsr.grid.blocks_total"] == primary["vsr.grid.blocks_total"]


@pytest.mark.parametrize("name", sorted(UNITS))
def test_a_program_without_the_keys_gives_the_readers_nothing(name):
    """The parent's scrapes: each reader returns None and raises
    nothing, so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "storage.bytes_grid": 9}
    later = dict(old, **{"vsr.commit_us.count": 9})
    ctx = {"before": [dict(old)] * 3, "after": [dict(later)] * 3,
           "at_close": [dict(later)] * 3, "requests": 7, "trace": None}
    spec = M.layer_spec(M.per_layer[name])
    assert mf.reader(spec).read(spec, ctx) is None


def check_the_new_readers_read_what_their_files_say(m):
    a = {"vsr.backup.accept_us.count": 10, "vsr.backup.accept_us.sum": 100.0}
    slow = {"vsr.backup.accept_us.count": 30, "vsr.backup.accept_us.sum": 700.0}
    fast = {"vsr.backup.accept_us.count": 30, "vsr.backup.accept_us.sum": 300.0}
    grid = {"vsr.grid.blocks_acquired_peak": 50, "vsr.grid.blocks_total": 400}
    ctx = {"before": [a, a, a], "after": [dict(slow, **grid), fast, slow],
           "at_close": [grid, {}, {}], "requests": 1, "trace": None}
    got = mf.read_layer_metrics(m, THREE, ctx)
    # The primary's own histogram is not a backup's; of the backups, the slower.
    assert got["backup_accept_us_per_prepare"]["value"] == pytest.approx(30.0)
    assert got["grid_blocks_peak_pct"]["value"] == pytest.approx(12.5)
    one = mf.read_layer_metrics(m, ONE, dict(ctx, before=[a], after=[slow]))
    assert "backup_accept_us_per_prepare" not in one


def test_the_new_readers_read_what_their_files_say():
    check_the_new_readers_read_what_their_files_say(M)
