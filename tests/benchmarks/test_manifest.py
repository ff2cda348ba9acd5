"""`BENCHMARK.json` against the contract's rules that a file can be
held to, and the harness's promise that a configuration, a traffic mix
and a per-layer metric are each one new file plus one manifest entry."""

import copy
import json
import os
import re
import shutil
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import manifest as mf  # noqa: E402

DOC = json.load(open(os.path.join(_REPO, "BENCHMARK.json")))
M = mf.Manifest()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LINE = re.compile(r"^[^\t\n]{1,200}$")
FILE_NAME = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def test_top_level_keys_and_sizes():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(_REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    cells = len(DOC["workloads"])
    assert 1 <= cells <= 24 and 1 <= len(DOC["configs"]) <= 24
    # A full check with the full 24 cells has to fit the driver's limit.
    runs = 2 + 14 * 24
    assert runs * (DOC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(1 for w in DOC["workloads"] if w["chips"] == 4)
    assert four <= max(1, cells // 2)


def test_command_and_paths():
    assert 1 <= len(DOC["command"]) <= 32 and all(LINE.match(w) for w in DOC["command"])
    assert 1 <= len(DOC["paths"]) <= 16
    for p in DOC["paths"]:
        assert FILE_NAME.match(p) and len(p) <= 200 and not p.startswith("/")
        assert ".." not in p.split("/") and os.path.isdir(os.path.join(_REPO, p))
    script = DOC["command"][1]
    assert any(script.startswith(p + "/") for p in DOC["paths"])
    for p in DOC["paths"]:
        for root, dirs, files in os.walk(os.path.join(_REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert FILE_NAME.match(f), os.path.join(root, f)


@pytest.mark.parametrize("config", DOC["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert mf.NAME.match(config["name"])
    assert LINE.match(config["source"]) and LINE.match(config["why"])
    assert any(config["file"].startswith(p + "/") for p in DOC["paths"])
    assert len(config["reduced"]) <= 16 and all(mf.NAME.match(k) for k in config["reduced"])
    assert sum(1 for c in DOC["configs"] if c["file"] == config["file"]) == 1
    assert any(w["config"] == config["name"] for w in DOC["workloads"])
    with open(os.path.join(_REPO, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"]
    assert body["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in body, f"{key} is reduced but the file does not hold it"
    for key in ("replicas", "chips_per_replica", "accounts", "server",
                "launcher", "guarantees", "assumed", "source", "rehearsal"):
        assert key in body, key
    assert os.path.isfile(os.path.join(_REPO, body["launcher"]))


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_files_that_exist(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert mf.NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4) and LINE.match(cell["why"])
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1
    config = M.config(cell)
    traffic = M.traffic(cell)
    assert config["replicas"] * config["chips_per_replica"] <= cell["chips"]
    kind = mf.generator_kind(traffic)
    gen = kind.make(traffic, config, 1)
    sizes = traffic["request_events"]
    assert len(gen.request(0, 0)) in (sizes if isinstance(sizes, list) else [sizes])
    assert hasattr(kind, "reference")
    assert callable(mf.loop_kind(traffic).session)
    assert traffic["trace"]["lead_s"] + traffic["trace"]["slice_s"] < DOC["run_seconds"]
    reported = {m["name"] for m in M.end_to_end_for(cell["name"])}
    assert "setup_s" in reported and len(reported) >= 2
    assert M.per_layer_for(cell["name"])


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(mf.DATA_ROOT, "traffic"))))
def test_every_traffic_file_draws_requests_its_reference_takes(name):
    """Those that no cell has yet too: the cell is then one entry."""
    traffic = M.traffic({"traffic": name})
    assert traffic["name"] == name and mf.NAME.match(name)
    config = M.config(DOC["workloads"][0])
    kind = mf.generator_kind(traffic)
    gen = kind.make(traffic, config, 2**31 + 3)
    ref = kind.reference(gen)
    sizes = traffic["request_events"]
    sizes = sizes if isinstance(sizes, list) else [sizes]
    warm = sum(traffic["warm_requests_per_session"])
    sent = [len(gen.request(1, index)) for index in range(warm)]
    # The warm requests send every size the window sends.
    assert set(sent) == set(sizes)
    assert len(ref.apply(gen.request(1, 0))) == 0       # all accepted
    assert len(ref.account_rows()) == config["accounts"]
    assert callable(mf.loop_kind(traffic).session)


@pytest.mark.parametrize("metric", DOC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert mf.NAME.match(metric["name"]) and mf.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    assert 1 <= len(DOC["end_to_end"]) <= 16
    assert any(m["name"] == "setup_s" for m in DOC["end_to_end"])


@pytest.mark.parametrize("metric", DOC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    assert mf.NAME.match(metric["name"]) and mf.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    assert LINE.match(metric["layer"])
    moved = M.end_to_end[metric["moves"]]
    cells = metric.get("workloads", list(M.cells))
    for name in cells:
        assert name in M.cells
        assert name in moved.get("workloads", list(M.cells))
    spec = M.layer_spec(metric)
    assert callable(mf.reader(spec).read)
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in DOC[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(names) == len(set(names))


def test_new_files_are_picked_up_with_no_code_edited(tmp_path):
    """A three-replica configuration, a traffic mix and a per-layer
    metric, each one new file beside the others plus one entry."""
    root = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(mf.DATA_ROOT, sub), root / sub)
    config = json.load(open(root / "configs" / "upstream-bench-3r.json"))
    config.update(name="other-3r", accounts=5000)
    (root / "configs" / "other-3r.json").write_text(json.dumps(config))
    traffic = json.load(open(root / "traffic" / "plain-c4.json"))
    traffic.update(name="plain-c2", sessions=2, request_events=1000)
    (root / "traffic" / "plain-c2.json").write_text(json.dumps(traffic))
    (root / "layer_metrics" / "journal_write_us_mean.json").write_text(json.dumps(
        {"name": "journal_write_us_mean", "reader": "scrape_hist_mean",
         "keys": ["vsr.journal.write_us"]}))
    doc = copy.deepcopy(DOC)
    doc["configs"].append({"name": "other-3r", "source": "a test",
                           "file": "benchmarks/configs/other-3r.json",
                           "reduced": config["reduced"], "why": "a test"})
    doc["workloads"].append({"name": "other3r-plain-c2", "config": "other-3r",
                             "traffic": "plain-c2", "chips": 4, "why": "a test"})
    doc["per_layer"].append({"name": "journal_write_us_mean", "unit": "us",
                             "better": "lower", "source": "program_span",
                             "layer": "VSR, journal, checkpoint",
                             "moves": "request_p95_ms",
                             "workloads": ["other3r-plain-c2"]})
    # A metric that lists its cells is read in those alone; the new cell
    # brings an entry of its own for what only some cells can report.
    for name in ("backup_lag_ops", "backup_lag_ops.other3r"):
        doc["per_layer"].append({"name": name, "unit": "ops", "better": "lower",
                                 "source": "program_counter",
                                 "layer": "VSR, journal, checkpoint",
                                 "moves": "request_p95_ms",
                                 "workloads": ["other3r-plain-c2"]})
    shutil.copy(root / "layer_metrics" / "backup_lag_ops.json",
                root / "layer_metrics" / "backup_lag_ops.other3r.json")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    m = mf.Manifest(str(path), str(root))
    cell = m.cell("other3r-plain-c2")
    assert m.config(cell)["accounts"] == 5000 and m.config(cell)["replicas"] == 3
    gen = mf.generator_kind(m.traffic(cell)).make(m.traffic(cell), m.config(cell), 9)
    assert len(gen.request(1, 0)) == 1000
    snap = {"vsr.journal.write_us.count": 10, "vsr.journal.write_us.sum": 50.0,
            "vsr.commit_min": 7}
    later = {"vsr.journal.write_us.count": 30, "vsr.journal.write_us.sum": 250.0,
             "vsr.commit_min": 9}
    ctx = {"before": [snap] * 3, "after": [later] * 3,
           "at_close": [later, snap, later], "requests": 20, "trace": None}
    got = mf.read_layer_metrics(m, "other3r-plain-c2", ctx)
    assert got["journal_write_us_mean"] == {"value": 10.0, "unit": "us"}
    assert got["backup_lag_ops.other3r"]["value"] == got["backup_lag_ops"]["value"] == 2.0
    # A reader that finds nothing to read leaves its metric out.
    assert "device_idle_pct" not in got and "commit_roofline_pct" not in got
    assert "journal_write_us_mean" not in mf.read_layer_metrics(
        m, DOC["workloads"][0]["name"], ctx)
    with pytest.raises(mf.ManifestError):
        m.cell("no-such-cell")


def test_trace_readers_never_pass_the_roofline():
    from benchmarks.harness import roofline

    ctx = {"before": [{}], "after": [{}], "at_close": [{}], "requests": 100,
           "trace": {"busy_s": 0.5, "window_s": 4.0, "requests": 100,
                     "events": 819000, "device_kind": "TPU v5 lite"}}
    got = mf.read_layer_metrics(M, "bench1r-small-c4", ctx)
    assert got["device_idle_pct"]["value"] == pytest.approx(87.5)
    assert got["device_busy_ms_per_req"]["value"] == pytest.approx(5.0)
    least = 819000 * roofline.bytes_per_event() / 819e9
    assert got["commit_roofline_pct"]["value"] == pytest.approx(100 * least / 0.5)
    assert 0 < got["commit_roofline_pct"]["value"] < 100
