"""The rest of a run, with the timed path broken underneath: `correct`
has to come out false.  Each case is a rehearsal (no look for a chip)
of a configuration whose launcher is `faulty_serve.py`, which wraps
the state machine's commit; nothing of the harness is told."""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(_REPO, "benchmarks", "run.py")
DATA = os.path.join(_REPO, "benchmarks")

FAULTS = [
    # fault, traffic, configuration, only on replica, a number that has to catch it
    ("state_unchanged", "small-c4", "upstream-bench-1r", None, "account_rows_differing"),
    ("half_batch", "small-c4", "upstream-bench-1r", None, "account_rows_differing"),
    ("answer_altered", "small-c4", "upstream-bench-1r", None, "replies_differing"),
    ("half_batch", "plain-c4", "upstream-bench-1r", None, "transfer_rows_differing"),
    ("commits_dropped", "small-c4", "upstream-bench-3r", "2", "replicas_disagreeing"),
]


@pytest.mark.parametrize("fault,traffic,config,replica,caught_by", FAULTS,
                         ids=[f"{f[0]}-{f[1]}" for f in FAULTS])
def test_fault_under_the_timed_path_is_not_correct(tmp_path, fault, traffic, config,
                                                    replica, caught_by):
    root = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(DATA, sub), root / sub)
    path = root / "configs" / f"{config}.json"
    body = json.load(open(path))
    body["launcher"] = "tests/benchmarks/faulty_serve.py"
    body["rehearsal"]["server"]["env"]["BENCH_FAULT"] = fault
    if replica is not None:
        body["rehearsal"]["server"]["env"]["BENCH_FAULT_REPLICA"] = replica
    path.write_text(json.dumps(body))
    doc = json.load(open(os.path.join(_REPO, "BENCHMARK.json")))
    doc["configs"] = [{"name": config, "source": "a test", "reduced": body["reduced"],
                       "file": f"benchmarks/configs/{config}.json", "why": "a test"}]
    cell = {"name": f"fault-{fault}", "config": config, "traffic": traffic,
            "chips": 4 if body["replicas"] > 1 else 1, "why": "a test"}
    doc["workloads"] = [cell]
    for m in doc["end_to_end"] + doc["per_layer"]:
        m.pop("workloads", None)
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(doc))
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell["name"], "--seed", "31",
         "--seconds", "3", "--trace", "0", "--rehearsal",
         "--manifest", str(manifest), "--data-root", str(root),
         "--run-dir", str(tmp_path / "run")],
        capture_output=True, text=True, timeout=600, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    context = f"rc={proc.returncode}\n{proc.stdout[-3000:]}\n{proc.stderr[-3000:]}"
    assert proc.returncode == 3, context
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is False, context
    entry = line["compared"][caught_by]
    assert entry["value"] > entry["limit"], context
