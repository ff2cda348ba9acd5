"""The two per-layer metrics that read how a prepare crossed the link
(PR 27): `engine_fetch_bytes_per_prepare` and
`engine_link_puts_per_prepare`.  Their files are data of the reader the
benchmark has; `entry` writes their manifest entries' text, with the
cell they were written for (PR 40 appended them).  They are read here
from the scrapes of one served CPU run (untraced: the readers need no
profiler).
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
CELL = "bench1r-small-c4"
M = mf.Manifest()
LINK_METRICS = {
    "engine_fetch_bytes_per_prepare": "bytes",
    "engine_link_puts_per_prepare": "count",
}


def entry(name: str, unit: str) -> dict:
    return {"name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "device engine",
            "moves": "commit_events_per_s", "workloads": [CELL]}


@pytest.fixture(scope="module")
def scrapes(tmp_path_factory):
    """-> the primary's scrapes at both ends of a served window"""
    run_dir = tmp_path_factory.mktemp("link_run") / "run"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 27),
         "--seconds", "3", "--trace", "0", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=600, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return json.load(f)


def check_the_file_is_data_of_the_reader_the_benchmark_has(m, name):
    spec = manifest_lists(m, entry(name, LINK_METRICS[name]))
    assert spec["reader"] == "scrape_delta_ratio"
    assert spec["over"] == ["vsr.commit_us.count"]
    assert all(k.startswith("sm.dev.link.") for k in spec["keys"])


@pytest.mark.parametrize("name", LINK_METRICS)
def test_the_file_is_data_of_the_reader_the_benchmark_has(name):
    check_the_file_is_data_of_the_reader_the_benchmark_has(M, name)


def test_a_served_run_reads_one_row_and_three_uploads_a_prepare(scrapes):
    after = scrapes["after"][0]
    assert after["sm.dev.link.fetch_bytes"] == 512 * after["sm.dev.fetches"] > 0
    ctx = {"before": scrapes["before"], "after": scrapes["after"],
           "at_close": scrapes["after"], "requests": 1, "trace": None}
    got = mf.read_layer_metrics(M, CELL, ctx)
    assert {n: got[n]["unit"] for n in LINK_METRICS} == LINK_METRICS
    # Every prepare of the cell is one solo batch: one summary row home;
    # its packed buffer and the digest's two arrays up.
    assert got["engine_fetch_bytes_per_prepare"]["value"] == pytest.approx(512, rel=0.02)
    assert 3 <= got["engine_link_puts_per_prepare"]["value"] < 3.5


def test_a_program_without_the_counters_gives_them_nothing_to_read():
    """The parent's scrape: the reader returns None and raises nothing,
    so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "sm.dev.fetches": 5}
    ctx = {"before": [dict(old)], "after": [dict(old, **{"vsr.commit_us.count": 9})],
           "at_close": [old], "requests": 7, "trace": None}
    for name in LINK_METRICS:
        spec = M.layer_spec(M.per_layer[name])
        assert mf.reader(spec).read(spec, ctx) is None, name
