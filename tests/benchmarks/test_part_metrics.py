"""The parts' metrics (PR 37): one file a part of the beat, of
`sm.dev.finish`, of `sm.plan` and of the checkpoint's freeze, and three
on the server's process, each read from traced rehearsals of three of
the cells it lists: the small cell, the chains cell (every part of the
plan and the finish does work there) and the payment cell (its window
holds the checkpoint).  (`bench1r-plain-c4` and `bench3r-plain-c4` are
in every entry's `workloads` and are not rehearsed here: each
rehearsal more on this machine's eight cores starves the other files'
of the requests their windows need, and `test_plain_cells.py` runs
those two already.  On the chip both cells' result lines had all 34
through a copy of the manifest with these entries, `chiprun_out/pr37b`,
`pr37d`; parts on a beat worker's thread are `tests/test_stages.py`'s.)

`ENTRIES` is their manifest entries' text, with the cells they were
written for (PR 40 appended them); the rehearsals read them through
the manifest.
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
SMALL = "bench1r-small-c4"
FULL = ["bench1r-plain-c4", "bench3r-plain-c4", "bench1r-chains2p-c4",
        "bench1r-tpcc-pay-c4"]
CELLS = [SMALL] + FULL
REHEARSED = [SMALL, "bench1r-chains2p-c4", "bench1r-tpcc-pay-c4"]
LSM, ENGINE, ROUTING, VSR, SERVER = (
    "LSM spill and compaction", "device engine", "state machine routing",
    "VSR, journal, checkpoint", "ingress verify and decode")
BEAT = ["spill_take", "spill_objects", "spill_index", "seal_concat",
        "seal_encode", "compact_read", "compact_merge", "compact_write"]
FINISH = ["codes", "mirror", "twin", "store", "ids", "native_ids", "status",
          "reply"]
PLAN = ["decode", "ids", "id_dir", "accounts", "route", "pending", "pack"]
FREEZE = ["drain", "verify_device", "verify_host", "encode", "wrap", "root",
          "write", "checksum"]


def _span(name: str, layer: str, moves: str, cells: list) -> dict:
    return {"name": name, "unit": "us", "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves,
            "workloads": cells}


RATE, P95 = "commit_events_per_s", "request_p95_ms"
ENTRIES = (
    [_span(f"beat_{p}_us_per_prepare", LSM, RATE, CELLS) for p in BEAT]
    + [_span(f"finish_{p}_us_per_prepare", ENGINE, RATE, CELLS) for p in FINISH]
    + [_span(f"plan_{p}_us_per_prepare", ROUTING, RATE, CELLS) for p in PLAN]
    # A window of `small-c4` holds no checkpoint: nothing to read there.
    + [_span(f"ckpt_freeze_{p}_us_mean", VSR, P95, FULL) for p in FREEZE]
    + [_span("gc_pause_us_per_req", SERVER, P95, CELLS),
       {"name": "server_cpu_cores", "unit": "cores", "better": "lower",
        "source": "program_counter", "layer": SERVER, "moves": RATE,
        "workloads": CELLS},
       {"name": "server_majflt_per_req", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": SERVER, "moves": P95,
        "workloads": CELLS}])
NAMES = [e["name"] for e in ENTRIES]
# The scrape key each file reads, less the histogram's suffix.
KEY = dict(
    [(f"beat_{p}_us_per_prepare",
      ("sm." if p.startswith("spill") else "lsm.") + p.replace("_", ".", 1) + "_us")
     for p in BEAT]
    + [(f"finish_{p}_us_per_prepare", f"sm.finish.{p}_us") for p in FINISH]
    + [(f"plan_{p}_us_per_prepare", f"sm.plan.{p}_us") for p in PLAN]
    + [(f"ckpt_freeze_{p}_us_mean",
        (f"sm.ckpt.{p}_us" if p in FREEZE[:4] else f"vsr.ckpt.freeze.{p}_us"))
       for p in FREEZE]
    + [("gc_pause_us_per_req", "server.gc.pause_us"),
       ("server_cpu_cores", "server.cpu_us"),
       ("server_majflt_per_req", "server.majflt")])


def check_the_entries_name_files_that_read_the_parts_keys(m):
    assert len(NAMES) == len(set(NAMES)) == 8 + 8 + 7 + 8 + 3
    for e in ENTRIES:
        spec = manifest_lists(m, e)
        if e["name"].startswith("ckpt_freeze_"):
            assert spec["reader"] == "scrape_hist_mean"
            assert spec["keys"] == [KEY[e["name"]]]
        else:
            assert spec["reader"] == "scrape_delta_ratio"
            want = KEY[e["name"]] + (".sum" if e["unit"] == "us" else "")
            assert spec["keys"] == [want]
            assert spec["over"] in (["vsr.commit_us.count"], "requests",
                                    ["server.uptime_us"])


def test_the_entries_name_files_that_read_the_parts_keys():
    check_the_entries_name_files_that_read_the_parts_keys(M)


_RUNS: dict = {}


@pytest.fixture
def rehearsed(request, tmp_path_factory):
    """-> (the cell, the result line, the run's scrapes) of ONE traced
    rehearsal a cell, made when the first test asks for it."""
    cell = request.param
    if cell not in _RUNS:
        run_dir = tmp_path_factory.mktemp("parts_" + cell) / "run"
        proc = subprocess.run(
            [sys.executable, RUN, "--workload", cell, "--seed",
             str(2**31 + 370 + CELLS.index(cell)), "--seconds", "4",
             "--trace", "1", "--rehearsal", "--keep",
             "--run-dir", str(run_dir)],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 3, proc.stderr[-3000:]
        line = json.loads(proc.stdout.splitlines()[-1])
        assert line["correct"] is True, proc.stderr[-3000:]
        with open(run_dir / "scrapes.json") as f:
            _RUNS[cell] = cell, line, json.load(f)
    return _RUNS[cell]


@pytest.mark.parametrize("rehearsed", REHEARSED, indirect=True)
def test_a_rehearsal_reads_every_metric_its_cell_lists(rehearsed):
    cell, line, scrapes = rehearsed
    before, after = scrapes["before"][0], scrapes["after"][0]
    listed = [e for e in ENTRIES if cell in e["workloads"]]
    assert len(listed) == (34 if cell in FULL else 26)
    frozen = line["info"]["checkpoints_in_window"] == 1
    # (On this machine's CPU, busy with the other files' rehearsals, a
    # window may commit too few requests to reach the checkpoint that
    # the chip's holds: then the freeze's files have nothing to read,
    # and `test_the_freezes_files_read_a_window_with_a_checkpoint`
    # holds them.)
    assert not frozen or cell in FULL
    if not frozen:
        assert not any(e["name"] in line["metrics"] for e in listed
                       if e["name"].startswith("ckpt_freeze_"))
        listed = [e for e in listed if not e["name"].startswith("ckpt_freeze_")]
    for e in listed:
        got = line["metrics"][e["name"]]
        assert got["unit"] == e["unit"] and got["value"] >= 0, e["name"]
    value = {e["name"]: line["metrics"][e["name"]]["value"] for e in listed}
    prepares = after["vsr.commit_us.count"] - before["vsr.commit_us.count"]
    assert prepares > 0

    def per_prepare(key):
        return (after[key + ".sum"] - before[key + ".sum"]) / prepares

    # Each file reads its own key, a prepare.
    for name in listed:
        if name["name"].endswith("_us_per_prepare"):
            assert value[name["name"]] == pytest.approx(
                per_prepare(KEY[name["name"]])), name["name"]
    # Plan and finish ran every device batch of the window; a part never
    # passes its leaf, and the parts together never do.
    for leaf, parts, prefix in (("sm.plan_us", PLAN, "plan_"),
                                ("sm.dev.finish_us", FINISH, "finish_")):
        inside = sum(value[f"{prefix}{p}_us_per_prepare"] for p in parts)
        if prefix == "plan_":
            inside += per_prepare("sm.plan.join_cold_us")
        assert 0 < inside <= per_prepare(leaf) * 1.0001, leaf
    assert value["plan_decode_us_per_prepare"] > 0
    assert value["finish_mirror_us_per_prepare"] > 0
    assert (value["plan_pending_us_per_prepare"] > 0) == (
        cell == "bench1r-chains2p-c4")
    # The beat's parts lie in the commit's beat on a lone replica (the
    # freeze has its own share).  A tail spills once it passes 16,384
    # rows; a seal waits for four beats' rows, or for the freeze.
    beat = sum(value[f"beat_{p}_us_per_prepare"] for p in BEAT)
    spilled = after["sm.spill.objects_us.count"] > before["sm.spill.objects_us.count"]
    sealed = after["lsm.seal.bytes"] > before["lsm.seal.bytes"]
    assert (value["beat_spill_objects_us_per_prepare"] > 0) == spilled
    assert (value["beat_spill_index_us_per_prepare"] > 0) == spilled
    assert (value["beat_seal_encode_us_per_prepare"] > 0) == sealed
    assert spilled or cell == SMALL or prepares < 40
    assert after["lsm.beat.work_us.count"] == before["lsm.beat.work_us.count"]
    leaves = per_prepare("vsr.commit.beat_us") + per_prepare(
        "vsr.ckpt.freeze_us") + per_prepare("lsm.beat.work_us")
    assert beat <= leaves * 1.0001
    # The freeze: its parts, with the LSM's share of it, stay within it.
    if frozen:
        freeze = sum(value[f"ckpt_freeze_{p}_us_mean"] for p in FREEZE)
        whole = after["vsr.ckpt.freeze_us.sum"] - before["vsr.ckpt.freeze_us.sum"]
        assert 0 < freeze <= whole * 1.0001      # one checkpoint: sum = mean
        assert value["ckpt_freeze_verify_host_us_mean"] > 0
        assert value["ckpt_freeze_encode_us_mean"] > 0
    # The process: under one thread's worth of CPU and a bit (the
    # rehearsal's servers share this machine), pauses counted.
    assert 0 < value["server_cpu_cores"] < 4
    assert after["server.gc.pause_us.count"] == sum(
        after[f"server.gc.collections.gen{g}"] for g in range(3))
    assert value["gc_pause_us_per_req"] >= 0
    assert value["server_majflt_per_req"] >= 0


def check_the_freezes_files_read_a_window_with_a_checkpoint(m):
    """Two scrapes around one checkpoint: each of the eight files reads
    its own histogram's mean over the window, whatever stood before."""
    before, after = {}, {}
    for i, part in enumerate(FREEZE):
        key = KEY[f"ckpt_freeze_{part}_us_mean"]
        before.update({key + ".count": 2, key + ".sum": 50.0})
        after.update({key + ".count": 3, key + ".sum": 50.0 + 1000.0 * (i + 1)})
    ctx = {"before": [before], "after": [after], "at_close": [after], "requests": 9}
    for i, part in enumerate(FREEZE):
        spec = m.layer_spec(m.per_layer[f"ckpt_freeze_{part}_us_mean"])
        assert mf.reader(spec).read(spec, ctx) == 1000.0 * (i + 1), part
    # A window without one: nothing, never 0.
    ctx = {"before": [after], "after": [after], "at_close": [after], "requests": 9}
    for part in FREEZE:
        spec = m.layer_spec(m.per_layer[f"ckpt_freeze_{part}_us_mean"])
        assert mf.reader(spec).read(spec, ctx) is None, part


def test_the_freezes_files_read_a_window_with_a_checkpoint():
    check_the_freezes_files_read_a_window_with_a_checkpoint(M)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_parts_gives_the_readers_nothing(name):
    """The parent's scrapes: each reader returns None and raises
    nothing, so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "server.uptime_us": 10,
           "vsr.ckpt.freeze_us.count": 1, "vsr.ckpt.freeze_us.sum": 7.0}
    later = dict(old, **{"vsr.commit_us.count": 9, "server.uptime_us": 20})
    ctx = {"before": [dict(old)], "after": [dict(later)],
           "at_close": [dict(later)], "requests": 4}
    spec = M.layer_spec(M.per_layer[name])
    assert mf.reader(spec).read(spec, ctx) is None
