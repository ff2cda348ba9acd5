"""The per-layer metrics that read the program's stages (PR 26): one
served run on the CPU backend at the rehearsal size, whose scrape has
every key their files name; and a recorded trace in which a `tb.` leaf
names the idle gap, not the runtime event inside it.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness import trace_reduce as tr  # noqa: E402

RUN = os.path.join(_REPO, "benchmarks", "run.py")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "bench1r-small-c4"
M = mf.Manifest()
STAGE_METRICS = [
    "server_loop_wait_pct", "server_loop_attributed_pct",
    "request_queue_wait_us", "requests_per_prepare",
    "prepare_build_us_per_prepare", "journal_write_us_per_prepare",
    "gc_sync_us_per_prepare", "commit_plan_us_per_prepare",
    "engine_launch_us_per_prepare", "engine_dispatch_us_per_prepare",
    "engine_digest_us_per_prepare", "engine_fetch_wait_us_per_prepare",
    "engine_fetch_copy_us_per_prepare", "engine_finish_us_per_prepare",
    "commit_reply_us_per_prepare", "commit_beat_us_per_prepare",
    "commit_attributed_pct", "compiles_per_request",
]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """-> (the result line, the primary's scrape as the window closed)"""
    run_dir = tmp_path_factory.mktemp("stages") / "run"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 26),
         "--seconds", "4", "--trace", "1", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=600, cwd=_REPO, env=env)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        scrape = json.load(f)["after"][0]
    return line, scrape


def test_the_stage_metrics_are_the_manifests_last_entries():
    names = [m["name"] for m in M.doc["per_layer"]]
    assert names[-len(STAGE_METRICS):] == STAGE_METRICS
    for entry in M.doc["per_layer"][-len(STAGE_METRICS):]:
        assert entry["workloads"] == [CELL]
        assert entry["source"] in ("program_span", "program_counter")


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_a_served_run_scrapes_every_key_the_metric_reads(served, name):
    line, scrape = served
    spec = M.layer_spec(M.per_layer[name])
    assert spec["reader"] in ("scrape_delta_ratio", "scrape_hist_mean")
    keys = list(spec["keys"])
    if spec["reader"] == "scrape_hist_mean":
        keys = [keys[0] + ".count", keys[0] + ".sum"]
    elif spec["over"] != "requests":
        keys += spec["over"]
    for key in keys:
        assert key in scrape, key
    assert name in line["metrics"], sorted(line["metrics"])
    assert line["metrics"][name]["unit"] == M.per_layer[name]["unit"]
    assert line["metrics"][name]["value"] >= 0


def test_the_leaves_account_for_the_commit_and_never_pass_it(served):
    line, scrape = served
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert 80 <= value["commit_attributed_pct"] <= 101
    assert value["server_loop_attributed_pct"] <= 101
    assert value["server_loop_wait_pct"] <= value["server_loop_attributed_pct"]
    assert value["requests_per_prepare"] >= 1
    assert scrape["vsr.requests_committed"] >= scrape["vsr.commits"] > 0
    # "A prepare" is one commit span: the per-prepare stage metrics of
    # the commit's leaves add up to no more than the span's own mean.
    inside = sum(value[n] for n in (
        "commit_plan_us_per_prepare", "engine_launch_us_per_prepare",
        "engine_dispatch_us_per_prepare", "engine_digest_us_per_prepare",
        "engine_fetch_wait_us_per_prepare", "engine_fetch_copy_us_per_prepare",
        "engine_finish_us_per_prepare", "commit_reply_us_per_prepare",
        "commit_beat_us_per_prepare"))
    assert inside <= 1e3 * value["commit_span_ms_per_req"] * 1.001


def test_a_program_without_the_stages_gives_the_metrics_nothing_to_read():
    """The parent's scrape has none of the keys: every reader returns
    None and raises nothing, so the line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "vsr.commit_us.sum": 9.0, "vsr.commits": 5,
           "sm.dev.link.fetch_us.count": 5, "sm.dev.link.fetch_us.sum": 3.0}
    ctx = {"before": [dict(old)], "after": [dict(old, **{"vsr.commits": 9})],
           "at_close": [old], "requests": 7, "trace": None}
    for name in STAGE_METRICS:
        spec = M.layer_spec(M.per_layer[name])
        assert mf.reader(spec).read(spec, ctx) is None, name


# ----------------------------------------------------------------------
# A leaf names the gap.


@pytest.fixture(scope="module")
def prepare_trace():
    """40 ms of this PR's traced chip run of the cell (TPU v5e), as
    `read_trace` returns it."""
    with open(os.path.join(DATA, "prepare-40ms.json")) as f:
        return json.load(f)


def host_events(trace):
    return sorted((s, s + d, name) for p in trace["planes"]
                  if p["name"].startswith(tr.HOST_PLANE_PREFIX)
                  for line in p["lines"] for name, s, d in line["events"] if d > 0)


def test_a_tb_leaf_wins_a_gap_over_the_runtime_event_inside_it(prepare_trace):
    host = host_events(prepare_trace)
    leaves = [h for h in host if h[2].startswith("tb.")]
    assert len({h[2] for h in leaves}) >= 8
    # A runtime event that lies wholly inside a leaf, and a gap as long
    # as the leaf: both overlap it, the leaf longer.
    inside = next((h, leaf) for leaf in leaves for h in host
                  if not h[2].startswith("tb.") and leaf[0] <= h[0]
                  and h[1] <= leaf[1] and h[1] - h[0] > 1000)
    runtime, leaf = inside
    assert tr.label_gaps([(leaf[0], leaf[1])], host) == [leaf[2]]
    # Without the program's annotations the same gap reads as it did
    # before them: by a runtime event.
    bare = [h for h in host if not h[2].startswith("tb.")]
    (label,) = tr.label_gaps([(leaf[0], leaf[1])], bare)
    assert label in {h[2] for h in bare} and runtime in bare


def test_the_recorded_prepares_idle_is_named_by_leaves(prepare_trace):
    got = tr.reduce(prepare_trace)
    labels = dict(got["gaps"])
    named = sum(s for name, s in labels.items() if name.startswith("tb."))
    assert named >= 0.9 * sum(labels.values())
    assert len([n for n in labels if n.startswith("tb.")]) >= 3
    # No enclosing span's name can appear: they emit no annotation.
    assert not {"tb.vsr.commit", "tb.state_machine_commit",
                "tb.poll_drain"} & set(labels)
    # The leaves of the loop's thread never overlap.
    main = [h for h in host_events(prepare_trace) if h[2].startswith("tb.")
            and h[2] not in ("tb.vsr.journal.sync", "tb.vsr.ckpt.finalize")]
    for a, b in zip(main, main[1:]):
        assert a[1] <= b[0], (a, b)
