"""`trace_reduce.py`: busy union, idle share and the gap labels.

Two traces recorded on a TPU v5e in PR 25's first session, of a cell
since taken out (full batches of 8,190 sent in bursts of 16 requests):
`bursts-slice.xplane.pb.gz`, the whole traced slice as the profiler
wrote it, for the reader; `burst-head-40ms.json`, the first 40 ms of a
burst as `read_trace` returns it, for the reduction.
"""

import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def head():
    with open(os.path.join(DATA, "burst-head-40ms.json")) as f:
        return json.load(f)


def sweep_busy_ns(intervals):
    """Busy time by counting open intervals at every edge: another
    algorithm than `union`, for the same number."""
    edges = sorted([(s, 1) for s, e in intervals if e > s]
                   + [(e, -1) for s, e in intervals if e > s])
    busy, open_, since = 0, 0, None
    for t, step in edges:
        if open_ == 0 and step == 1:
            since = t
        open_ += step
        if open_ == 0:
            busy += t - since
    return busy


def device_intervals(trace):
    plane = next(p for p in trace["planes"] if p["name"].startswith("/device:TPU:"))
    return [(s, s + d) for line in plane["lines"] if line["name"] in tr.OPS_LINES
            for _n, s, d in line["events"]]


@pytest.mark.parametrize("intervals,want", [
    ([], []),
    ([(5, 5)], []),
    ([(0, 10), (10, 20)], [(0, 20)]),
    ([(0, 10), (2, 3), (12, 14)], [(0, 10), (12, 14)]),
    ([(7, 9), (0, 4), (3, 8)], [(0, 9)]),
])
def test_union(intervals, want):
    assert tr.union(intervals) == want


def test_busy_union_on_the_recorded_burst(head):
    got = tr.reduce(head)
    intervals = device_intervals(head)
    assert len(intervals) == got["device_ops"] == 624
    assert got["busy_s"] * 1e9 == pytest.approx(sweep_busy_ns(intervals), abs=1)
    assert got["busy_s"] == pytest.approx(0.009030004)
    assert got["device_planes"] == 1
    # Busy, gaps and the device's span add up: a gap is span less busy.
    gaps = sum(s for _n, s in tr.reduce({**head})["gaps"])
    assert got["busy_s"] + gaps == pytest.approx(got["device_span_s"], rel=1e-3)
    assert 0 < got["busy_s"] < got["device_span_s"]


def test_programs_are_named_as_the_trace_names_them(head):
    got = tr.reduce(head)
    names = [n for n, _s in got["programs"]]
    assert names[0].startswith("jit__orderfree_tight(")
    assert names[1].startswith("jit__update(")
    assert all(s > 0 for _n, s in got["programs"])
    assert [s for _n, s in got["programs"]] == sorted(
        (s for _n, s in got["programs"]), reverse=True)
    assert len(got["programs"]) <= tr.TOP and len(got["gaps"]) <= tr.TOP


def test_gap_labels_on_the_recorded_burst(head):
    got = tr.reduce(head)
    labels = dict(got["gaps"])
    # Between a burst's programs the engine waits in a fetch.
    assert got["gaps"][0][0] == "np.asarray(jax.Array)"
    assert "no_runtime_event" in labels
    assert labels["np.asarray(jax.Array)"] > 10 * labels["no_runtime_event"]


def test_gap_label_is_the_host_event_that_overlaps_longest():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_a(1)", 0, 100], ["jit_b(2)", 1000, 100]]},
            {"name": "XLA Ops", "events": [["%x", 0, 100], ["%y", 1000, 50]]},
            {"name": "Async XLA Ops", "events": [["%copy-start", 1040, 60]]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["short", 90, 30], ["long", 200, 700],
                                        ["after", 1500, 50]]},
        ]},
        {"name": "/host:metadata", "lines": []},
    ]}
    got = tr.reduce(trace)
    assert got["busy_s"] == pytest.approx(200e-9)        # the copy counts
    assert got["device_span_s"] == pytest.approx(1100e-9)
    assert got["gaps"] == [["long", pytest.approx(900e-9)]]
    assert got["programs"] == [["jit_a(1)", pytest.approx(100e-9)],
                               ["jit_b(2)", pytest.approx(100e-9)]]
    trace["planes"][1]["lines"][0]["events"] = [["after", 1500, 50]]
    assert tr.reduce(trace)["gaps"][0][0] == "no_runtime_event"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_sweep_labels_every_gap_as_a_search_of_all_host_events_would(seed):
    """Host events that nest, overlap and outlast many gaps; among
    them one that spans the whole trace."""
    import random

    rng = random.Random(seed)
    gaps, t = [], 0
    for _ in range(400):
        t += rng.randint(1, 50)
        gaps.append((t, t + rng.randint(1, 30)))
        t = gaps[-1][1]
    host = [(0, t, "whole")] if seed == 3 else []
    for thread in range(3):
        x = rng.randint(0, 40)
        while x < t:
            d = rng.randint(1, 400)
            host.append((x, x + d, f"h{thread}.{rng.randint(0, 5)}"))
            x += d + rng.randint(0, 60)
    host.sort()

    def search(gap):
        best, best_overlap = "no_runtime_event", 0
        for start, end, name in host:
            overlap = min(end, gap[1]) - max(start, gap[0])
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        return best

    got = tr.label_gaps(gaps, host)
    assert got == [search(g) for g in gaps]
    assert len(set(got)) > 3 or seed == 3
    assert tr.label_gaps([(t + 500, t + 600)], host) == ["no_runtime_event"]


def test_a_trace_with_no_device_plane_gives_nothing_not_zero():
    got = tr.reduce({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "main", "events": [["x", 0, 10]]}]}]})
    assert got["busy_s"] is None and got["device_planes"] == 0
    assert got["programs"] == [] and got["gaps"] == []


def test_reader_on_the_recorded_xplane(tmp_path):
    """The whole slice through `main`, as a run's child does it."""
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_09_30"
    where.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "bursts-slice.xplane.pb.gz")) as src, \
            open(where / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    out = tmp_path / "reduced.json"
    proc = subprocess.run(
        [sys.executable, tr.__file__, str(tmp_path / "trace"), str(out)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(out.read_text())
    assert got["device_planes"] == 1 and got["device_ops"] == 6756
    # 32 requests in 3.94 s: two bursts, some 48 ms of device time each.
    assert got["busy_s"] == pytest.approx(0.096466378)
    assert got["device_span_s"] == pytest.approx(2.461542753)
    assert got["programs"][0][0].startswith("jit__orderfree_tight(")
    assert got["programs"][0][1] == pytest.approx(0.06142572)
    assert tr.main([str(tmp_path / "nothing"), str(out)]) == 1
