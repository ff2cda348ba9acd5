"""The id directories' metrics (PR 38): how the transfer-id directory
filed the window's created batches.  `id_dir_runs_filed_per_prepare`
(counter `sm.ids.runs_filed`) and `id_dir_hashed_ids_per_prepare`
(counter `sm.ids.hashed`) over the prepares committed in the window;
the gauge `sm.ids.runs` (the runs the directory holds) rides the scrape
beside them.  They say how often PR 38's mechanism engages: a batch
with a gap wherever a row failed is as many runs as it has pieces and
none of its ids reaches the hash.

`ENTRIES` is their manifest entries' text, with the cells they were
written for (PR 40 appended them).  They are read here from two
scrapes, and from ONE traced rehearsal of the chains cell, the cell
whose requests fail in part (its rehearsal: one session, requests of
500 rows).
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
M = mf.Manifest()
CELL = "bench1r-chains2p-c4"
# The cells the entries were written for; the manifest may list more.
CELLS = ["bench1r-small-c4", "bench1r-plain-c4", "bench3r-plain-c4",
         "bench1r-chains2p-c4", "bench1r-tpcc-pay-c4"]
KEY = {"id_dir_runs_filed_per_prepare": "sm.ids.runs_filed",
       "id_dir_hashed_ids_per_prepare": "sm.ids.hashed"}
ENTRIES = [
    {"name": name, "unit": "count", "better": better,
     "source": "program_counter", "layer": "state machine routing",
     "moves": "commit_events_per_s", "workloads": CELLS}
    for name, better in (("id_dir_runs_filed_per_prepare", "lower"),
                         ("id_dir_hashed_ids_per_prepare", "lower"))]
NAMES = [e["name"] for e in ENTRIES]


def check_the_entry_names_a_file_that_reads_its_counter(m, entry):
    spec = manifest_lists(m, entry)
    assert spec["reader"] == "scrape_delta_ratio"
    assert spec["keys"] == [KEY[entry["name"]]]
    assert spec["over"] == ["vsr.commit_us.count"]


@pytest.mark.parametrize("entry", ENTRIES, ids=NAMES)
def test_the_entry_names_a_file_that_reads_its_counter(entry):
    check_the_entry_names_a_file_that_reads_its_counter(M, entry)


@pytest.mark.parametrize("filed, hashed, prepares, want", [
    (156, 0, 156, (1.0, 0.0)),            # plain: every batch one run
    (6552, 0, 156, (42.0, 0.0)),          # 41 failed payments a request
    (0, 8190 * 4, 4, (0.0, 8190.0)),      # random ids: the hash, whole
])
def test_the_files_read_a_windows_deltas(filed, hashed, prepares, want):
    before = {"sm.ids.runs_filed": 7, "sm.ids.hashed": 3, "vsr.commit_us.count": 11}
    after = {"sm.ids.runs_filed": 7 + filed, "sm.ids.hashed": 3 + hashed,
             "vsr.commit_us.count": 11 + prepares}
    ctx = {"before": [before], "after": [after], "at_close": [after], "requests": 9}
    got = tuple(mf.reader(spec).read(spec, ctx) for spec in (
        M.layer_spec(M.per_layer[name]) for name in NAMES))
    assert got == want


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counters_gives_the_readers_nothing(name):
    """The parent's scrapes: each reader returns None and raises
    nothing, so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "sm.finish.ids_us.sum": 1.0}
    later = dict(old, **{"vsr.commit_us.count": 9})
    ctx = {"before": [dict(old)], "after": [dict(later)],
           "at_close": [dict(later)], "requests": 4}
    spec = M.layer_spec(M.per_layer[name])
    assert mf.reader(spec).read(spec, ctx) is None


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """-> (the result line, the run's scrapes) of one traced rehearsal."""
    run_dir = tmp_path_factory.mktemp("id_dir_run") / "run"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 381),
         "--seconds", "4", "--trace", "1", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return line, json.load(f)


def test_a_rehearsal_files_its_gapped_batches_as_runs(rehearsed):
    line, scrapes = rehearsed
    before, after = scrapes["before"][0], scrapes["after"][0]
    prepares = after["vsr.commit_us.count"] - before["vsr.commit_us.count"]
    assert prepares > 0
    for name in NAMES:
        got = line["metrics"][name]
        assert got["unit"] == "count"
        assert got["value"] == pytest.approx(
            (after[KEY[name]] - before[KEY[name]]) / prepares), name
    # Failed chains leave gaps in a request's created ids: more runs
    # than prepares, and not an id in the hash, in the run's whole life.
    assert line["metrics"]["id_dir_runs_filed_per_prepare"]["value"] > 1
    assert line["metrics"]["id_dir_hashed_ids_per_prepare"]["value"] == 0
    assert after["sm.ids.hashed"] == 0
    assert 0 < after["sm.ids.runs"] <= after["sm.ids.runs_filed"]
