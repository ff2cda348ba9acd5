"""`benchmarks/run.py` end to end on the CPU backend, at the rehearsal
size (the suite's TB_DEV_B=512 shape): one replica and three.

A rehearsal drives every step of a run but the look for a chip, prints
the result line, and exits 3: it fails as a measurement.  Without
`--rehearsal` the same machine gives no result line at all.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(_REPO, "benchmarks", "run.py")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "info", "compared"}
DOC = json.load(open(os.path.join(_REPO, "BENCHMARK.json")))


def run(*argv, cwd=_REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run([sys.executable, RUN, *argv], capture_output=True,
                          text=True, timeout=timeout, cwd=cwd, env=env)


def last_line(proc) -> dict:
    context = f"rc={proc.returncode}\nstdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    lines = proc.stdout.splitlines()
    assert lines, context
    try:
        return json.loads(lines[-1])
    except ValueError:
        raise AssertionError("last line is not JSON\n" + context)


def per_layer_names(cell: str) -> set:
    return {m["name"] for m in DOC["per_layer"]
            if cell in m.get("workloads", [cell])}


def three_replicas(tmp_path) -> str:
    """A manifest with one more entry: the cell's traffic against the
    three-replica configuration file.  Replica count is data."""
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "upstream-bench-3r", "source": "a test",
                           "file": "benchmarks/configs/upstream-bench-3r.json",
                           "reduced": ["transfer_count", "replicas"], "why": "a test"})
    doc["workloads"].append({"name": "rehearse-3r-small", "config": "upstream-bench-3r",
                             "traffic": "small-c4", "chips": 4, "why": "a test"})
    doc["per_layer"].append({"name": "backup_lag_ops", "unit": "ops", "better": "lower",
                             "source": "program_counter",
                             "layer": "VSR, journal, checkpoint",
                             "moves": "request_p95_ms",
                             "workloads": ["rehearse-3r-small"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


@pytest.mark.parametrize("cell,trace", [(w["name"], i % 2) for i, w in
                                        enumerate(DOC["workloads"])]
                         + [("rehearse-3r-small", 1)])
def test_rehearsal_prints_the_contracts_line_and_fails_as_a_measurement(
        tmp_path, cell, trace):
    argv = ["--workload", cell, "--seed", str(2**31 + 77), "--seconds", "4",
            "--trace", str(trace), "--rehearsal", "--run-dir", str(tmp_path / "run")]
    if cell == "rehearse-3r-small":
        argv += ["--manifest", three_replicas(tmp_path)]
    proc = run(*argv)
    line = last_line(proc)
    context = json.dumps(line)[:3000] + proc.stderr[-2000:]
    assert proc.returncode == 3, context
    assert set(line) == KEYS | ({"breakdown"} if trace else set()), context
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, context
    assert line["failed"] == 0 and line["attempted"] >= 8, context
    assert line["info"]["requests_in_window"] >= line["attempted"] - 4
    assert line["device"]["platform"] == "cpu"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    if trace:
        # No device plane in a CPU trace: the trace's readers find nothing
        # and their metrics are left out, never reported as 0.
        assert line["metrics"] and set(line["metrics"]) <= (
            per_layer_names(cell) | {"backup_lag_ops"}), context
        assert "device_idle_pct" not in line["metrics"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        if cell == "rehearse-3r-small":
            assert "backup_lag_ops" in line["metrics"], context
            assert len(line["info"]["state_roots"]) == 3
    else:
        assert set(line["metrics"]) == {m["name"] for m in DOC["end_to_end"]}, context
        assert all(v["value"] > 0 for v in line["metrics"].values()), context
    for name, entry in line["compared"].items():
        assert entry == {"value": 0, "limit": 0}, (name, context)
    # The numbers compared are the last lines of standard error too.
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [ln.split()[1].rstrip(":") for ln in tail] == list(line["compared"])
    assert not os.path.exists(tmp_path / "run"), \
        "a correct run leaves its directory behind"


def test_rehearsal_of_a_traffic_file_that_no_cell_has_yet(tmp_path):
    """`plain-c4`, upstream's full batches in a closed loop, through the
    whole of a run.  The cell is one manifest entry; nothing else is new."""
    doc = json.loads(json.dumps(DOC))
    doc["workloads"].append({"name": "rehearse-plain-c4", "config": "upstream-bench-1r",
                             "traffic": "plain-c4", "chips": 1, "why": "a test"})
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(doc))
    proc = run("--workload", "rehearse-plain-c4", "--seed", "4000000007",
               "--seconds", "4", "--trace", "0", "--rehearsal",
               "--manifest", str(manifest), "--run-dir", str(tmp_path / "run"))
    line = last_line(proc)
    context = json.dumps(line)[:3000] + proc.stderr[-2000:]
    assert proc.returncode == 3 and line["correct"] is True, context
    assert line["attempted"] >= 4 and line["failed"] == 0, context


def test_no_chip_no_result_line(tmp_path):
    """A measurement (no `--rehearsal`) on a machine without a chip.
    The configuration is a copy with the rehearsal's server settings,
    so that the server need not warm a B=8,192 kernel on every core of
    a CPU before it can say what it holds."""
    root = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(_REPO, "benchmarks", sub), root / sub)
    path = root / "configs" / "upstream-bench-1r.json"
    config = json.load(open(path))
    config["server"] = config["rehearsal"]["server"]
    path.write_text(json.dumps(config))
    proc = run("--workload", DOC["workloads"][0]["name"], "--seed", "5",
               "--seconds", "2", "--trace", "0", "--run-dir", str(tmp_path / "run"),
               "--data-root", str(root))
    assert proc.returncode not in (0, 3), proc.stderr[-2000:]
    assert proc.stdout.strip() == "", proc.stdout[-2000:]
    assert "platform is 'cpu', not 'tpu'" in proc.stderr


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    shutil.copy(os.path.join(_REPO, "BENCHMARK.json"), tmp_path)
    for p in DOC["paths"]:
        shutil.copytree(os.path.join(_REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"), "--workload",
         DOC["workloads"][0]["name"], "--seed", "5", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_workload_is_refused():
    proc = run("--workload", "no-such-cell", "--seed", "5", "--seconds", "2",
               "--trace", "0")
    assert proc.returncode == 2 and proc.stdout.strip() == ""
