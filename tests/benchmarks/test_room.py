"""Room for the next cell (PR 40).  A copy of the manifest takes one more
configuration, one more cell and one per-layer entry at the end of the
list, as the next `model_config` PR adds them: its files are copies of
files that stand, under new names, so no code is new.  Every
manifest-level check of this directory then holds against the copy as
it holds against the manifest, with no edit: no check holds the set of
cells, their number or an entry's place in the list unless that is
what it tests.
"""

import copy
import json
import os
import shutil
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import manifest as mf  # noqa: E402
import test_chains2p as chains  # noqa: E402
import test_id_dir_metrics as id_dir  # noqa: E402
import test_link_metrics as link  # noqa: E402
import test_manifest as manifest  # noqa: E402
import test_part_metrics as parts  # noqa: E402
import test_plain_cells as plain  # noqa: E402
import test_stage_metrics as stages  # noqa: E402
import test_tpcc_payment as tpcc  # noqa: E402

CONFIG, TRAFFIC, CELL = "room-1r", "room-c4", "bench1r-room-c4"
METRIC = "finish_mirror_us_per_prepare.room"


def _copy_of(path, **changes) -> str:
    with open(path) as f:
        return json.dumps(dict(json.load(f), **changes), indent=1)


@pytest.fixture(scope="module")
def room(tmp_path_factory) -> mf.Manifest:
    tmp = tmp_path_factory.mktemp("room")
    root = tmp / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(mf.DATA_ROOT, sub), root / sub)
    (root / "configs" / f"{CONFIG}.json").write_text(
        _copy_of(root / "configs" / "upstream-bench-1r.json", name=CONFIG))
    (root / "traffic" / f"{TRAFFIC}.json").write_text(
        _copy_of(root / "traffic" / "plain-full-c4.json", name=TRAFFIC))
    (root / "layer_metrics" / f"{METRIC}.json").write_text(
        _copy_of(root / "layer_metrics" / "finish_mirror_us_per_prepare.json",
                 name=METRIC))
    doc = copy.deepcopy(manifest.DOC)
    config = next(c for c in doc["configs"] if c["name"] == "upstream-bench-1r")
    doc["configs"].append(dict(config, name=CONFIG,
                               file=f"benchmarks/configs/{CONFIG}.json"))
    doc["workloads"].append({"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                             "chips": 1, "why": "the next model_config PR's cell"})
    metric = next(m for m in doc["per_layer"]
                  if m["name"] == "finish_mirror_us_per_prepare")
    doc["per_layer"].append(dict(metric, name=METRIC, workloads=[CELL]))
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(doc, indent=1))
    return mf.Manifest(str(path), str(root))


def _each(check, items):
    return lambda m: [check(m, item) for item in items(m)]


CHECKS = {
    "manifest.top_level": manifest.check_top_level_keys_and_sizes,
    "manifest.command_and_paths": manifest.check_command_and_paths,
    "manifest.configs": _each(manifest.check_config_entry_and_file,
                              lambda m: m.doc["configs"]),
    "manifest.cells": _each(manifest.check_cell_resolves_to_files_that_exist,
                            lambda m: m.doc["workloads"]),
    "manifest.traffic": _each(manifest.check_traffic_file_draws_requests_its_reference_takes,
                              manifest.traffic_names),
    "manifest.end_to_end": _each(manifest.check_end_to_end_metric,
                                 lambda m: m.doc["end_to_end"]),
    "manifest.per_layer": _each(manifest.check_per_layer_metric_moves_what_its_cells_report,
                                lambda m: m.doc["per_layer"]),
    "manifest.names": manifest.check_names_are_unique,
    "manifest.roofline": manifest.check_trace_readers_never_pass_the_roofline,
    "id_dir.entries": _each(id_dir.check_the_entry_names_a_file_that_reads_its_counter,
                            lambda m: id_dir.ENTRIES),
    "stages.entries": stages.check_the_stage_metrics_are_in_the_manifest,
    "stages.parent": stages.check_a_program_without_the_stages_gives_the_metrics_nothing_to_read,
    "parts.entries": parts.check_the_entries_name_files_that_read_the_parts_keys,
    "parts.freeze": parts.check_the_freezes_files_read_a_window_with_a_checkpoint,
    "plain.cells": plain.check_the_cells_are_the_manifests,
    "plain.readers": plain.check_the_new_readers_read_what_their_files_say,
    "chains.cell": chains.check_the_cell_is_the_manifests,
    "tpcc.cell": tpcc.check_the_cell_is_the_manifests,
    "link.entries": _each(link.check_the_file_is_data_of_the_reader_the_benchmark_has,
                          lambda m: link.LINK_METRICS),
}


def test_the_room_is_the_manifest_and_one_more_of_each(room):
    base = manifest.M
    assert set(room.cells) == set(base.cells) | {CELL}
    assert set(room.configs) == set(base.configs) | {CONFIG}
    assert [m["name"] for m in room.doc["per_layer"]] == [
        m["name"] for m in base.doc["per_layer"]] + [METRIC]
    assert room.config(room.cell(CELL))["name"] == CONFIG
    assert room.traffic(room.cell(CELL))["name"] == TRAFFIC


@pytest.mark.parametrize("name", sorted(CHECKS))
def test_a_manifest_check_holds_in_the_room(room, name):
    CHECKS[name](room)


def test_the_appended_entry_is_read_in_its_cell_alone(room):
    before = {"sm.finish.mirror_us.sum": 100.0, "vsr.commit_us.count": 10,
              "vsr.commit_us.sum": 5000.0}
    after = {"sm.finish.mirror_us.sum": 700.0, "vsr.commit_us.count": 13,
             "vsr.commit_us.sum": 8000.0}
    ctx = {"before": [before], "after": [after], "at_close": [after],
           "requests": 3, "trace": None}
    got = mf.read_layer_metrics(room, CELL, ctx)
    assert got[METRIC] == {"value": 200.0, "unit": "us"}
    assert "finish_mirror_us_per_prepare" not in got
    plain_got = mf.read_layer_metrics(room, "bench1r-plain-c4", ctx)
    assert plain_got["finish_mirror_us_per_prepare"] == got[METRIC]
    assert METRIC not in plain_got
