"""What every test of a metric's manifest entry asks: the manifest
lists the entry as the test writes it, in the cells it was written for
and perhaps in more, each of them a cell the manifest has; and the
entry names a file whose reader the harness finds.

A test holds no set of cells, no count of them and no entry's place in
the list unless that is what it tests: a later PR adds a cell and
appends an entry without an edit (`test_room.py`).
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import manifest as mf  # noqa: E402


def manifest_lists(m: mf.Manifest, entry: dict) -> dict:
    """-> the data file of the manifest's entry for `entry`"""
    got = m.per_layer.get(entry["name"])
    assert got is not None, f"{entry['name']} has no manifest entry"
    for key, value in entry.items():
        if key != "workloads":
            assert got[key] == value, (entry["name"], key)
    cells = got.get("workloads", list(m.cells))
    assert set(entry["workloads"]) <= set(cells) <= set(m.cells), entry["name"]
    assert mf.NAME.match(got["name"]) and mf.UNIT.match(got["unit"])
    spec = m.layer_spec(got)
    assert spec["name"] == got["name"] and callable(mf.reader(spec).read)
    return spec
