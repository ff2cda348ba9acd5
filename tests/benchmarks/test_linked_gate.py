"""The gate out of the linked fixpoint's full-width rounds, as the
benchmark sees it: the manifest entry `linked_gate_exits_per_req`
(`ENTRY`, with the cell it was written for) and its reader, on a
program with the counter and on one without; the zipf cell rehearsed
on the CPU backend, whose every batch that takes the pass by blocks
leaves its rounds at the gate; and the chains and payment cells,
whose batches settle in their first rounds and never reach it.
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
from tigerbeetle_tpu.state_machine import device_kernels as dk  # noqa: E402

RUN = os.path.join(_REPO, "benchmarks", "run.py")
CELL = "bench1r-zipf-c4"
KEY = "sm.dev.linked.gate_exits"
M = mf.Manifest()
ENTRY = {"name": "linked_gate_exits_per_req", "unit": "batches", "better": "higher",
         "source": "program_counter", "layer": "kernels",
         "moves": "commit_events_per_s", "workloads": [CELL]}


def _rehearse(tmp_path_factory, cell: str, seed: int, trace: int):
    """-> (the result line, the run's scrapes)"""
    run_dir = tmp_path_factory.mktemp("gate") / "run"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", str(seed),
         "--seconds", "4", "--trace", str(trace), "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return line, json.load(f)


def _delta(scrapes: dict, key: str):
    return scrapes["after"][0][key] - scrapes["before"][0].get(key, 0)


def test_the_entry_is_the_manifests():
    spec = manifest_lists(M, ENTRY)
    assert spec["reader"] == "scrape_delta_ratio"
    assert spec["keys"] == [KEY] and spec["over"] == "requests"


def _ctx(before: dict, after: dict, requests: int) -> dict:
    return {"before": [before], "after": [after], "at_close": [after],
            "requests": requests, "trace": None}


def test_a_program_without_the_gate_gives_the_reader_nothing():
    """The parent's scrapes: the reader returns None and raises nothing,
    so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "sm.dev.linked.settle_batches": 3}
    later = {"vsr.commit_us.count": 9, "sm.dev.linked.settle_batches": 7}
    spec = M.layer_spec(M.per_layer[ENTRY["name"]])
    assert mf.reader(spec).read(spec, _ctx(old, later, 7)) is None


def test_the_reader_counts_the_window_s_gate_exits_over_its_requests():
    spec = M.layer_spec(M.per_layer[ENTRY["name"]])
    read = mf.reader(spec).read
    assert read(spec, _ctx({KEY: 3}, {KEY: 10}, 7)) == 1.0
    assert read(spec, _ctx({KEY: 3}, {KEY: 3}, 7)) == 0.0
    assert read(spec, _ctx({KEY: 3}, {KEY: 10}, 0)) is None


@pytest.fixture(scope="module")
def zipf(tmp_path_factory):
    return _rehearse(tmp_path_factory, CELL, 2**31 + 413, 1)


def test_the_zipf_rehearsal_leaves_every_settled_batch_at_the_gate(zipf):
    """Read from the scrape deltas as the settle metrics are: every
    batch that took the pass stopped its rounds at the gate, so the
    metric is the settle batches over the requests, and the settle rows
    over the requests' rows."""
    line, scrapes = zipf
    got = line["metrics"]
    settled = _delta(scrapes, "sm.dev.linked.settle_batches")
    assert settled >= 1
    assert _delta(scrapes, KEY) == settled
    assert scrapes["after"][0]["sm.dev.linked.iters.max"] < dk.FIXPOINT_CAP
    assert got[ENTRY["name"]]["value"] > 0
    traffic = M.traffic(M.cell(CELL))
    rows = {**traffic, **traffic["rehearsal"]}["request_events"]
    assert got[ENTRY["name"]]["value"] == pytest.approx(
        got["linked_settle_rows_per_req"]["value"] / rows)
    assert got["device_computed_pct"]["value"] == 100.0


@pytest.mark.parametrize("cell,seed,rounds", [
    ("bench1r-chains2p-c4", 2**31 + 333, 2),
    ("bench1r-tpcc-pay-c4", 2**31 + 335, 1),
])
def test_batches_that_settle_in_their_rounds_never_reach_the_gate(
        tmp_path_factory, cell, seed, rounds):
    """The chains settle in two rounds, the first that finds the
    failures and the second that confirms them; the payments, whose
    accounts carry no limit, in one: iterations as before, and neither
    the gate nor the pass counts a batch."""
    _line, scrapes = _rehearse(tmp_path_factory, cell, seed, 0)
    count = _delta(scrapes, "sm.dev.linked.iters.count")
    assert count > 0
    assert _delta(scrapes, "sm.dev.linked.iters.sum") == rounds * count
    assert _delta(scrapes, KEY) == _delta(scrapes, "sm.dev.linked.settle_batches") == 0
