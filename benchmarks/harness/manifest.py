"""`BENCHMARK.json` and the data files it names.

Whatever belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

  <data root>/configs/<config>.json
  <data root>/traffic/<traffic>.json         (its kind: harness/gen/,
                                              its loop: harness/loops/)
  <data root>/layer_metrics/<metric>.json   (its reader: harness/readers/)

A later PR adds a file and a manifest entry and edits nothing here.
"""

from __future__ import annotations

import importlib
import json
import os
import re

from .cluster import REPO

DATA_ROOT = os.path.join(REPO, "benchmarks")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(ValueError):
    pass


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as exc:
        raise ManifestError(f"{path}: {exc}") from exc


class Manifest:
    def __init__(self, path: str = MANIFEST, data_root: str = DATA_ROOT) -> None:
        self.path = path
        self.data_root = data_root
        self.doc = _load(path)
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(
                f"no workload {name!r} in {self.path}: {sorted(self.cells)}")
        return self.cells[name]

    def config(self, cell: dict) -> dict:
        entry = self.configs.get(cell["config"])
        if entry is None:
            raise ManifestError(f"cell {cell['name']!r}: no config "
                                f"{cell['config']!r} in the manifest")
        # The manifest's `file` is relative to the repo; a test's copy of
        # the data keeps the same layout under its own root.
        rel = os.path.relpath(os.path.join(REPO, entry["file"]), DATA_ROOT)
        return _load(os.path.join(self.data_root, rel))

    def traffic(self, cell: dict) -> dict:
        return _load(os.path.join(self.data_root, "traffic",
                                  cell["traffic"] + ".json"))

    def end_to_end_for(self, cell_name: str) -> list[dict]:
        return [m for m in self.doc["end_to_end"]
                if cell_name in m.get("workloads", [cell_name])]

    def per_layer_for(self, cell_name: str) -> list[dict]:
        return [m for m in self.doc["per_layer"]
                if cell_name in m.get("workloads", [cell_name])]

    def layer_spec(self, metric: dict) -> dict:
        return _load(os.path.join(self.data_root, "layer_metrics",
                                  metric["name"] + ".json"))


def reader(spec: dict):
    """The reader module a layer metric's file names."""
    if not NAME.match(spec["reader"]):
        raise ManifestError(f"bad reader name {spec['reader']!r}")
    return importlib.import_module(f"{__package__}.readers.{spec['reader']}")


def generator_kind(traffic: dict):
    """The generator module a traffic file's `kind` names."""
    if not NAME.match(traffic["kind"]):
        raise ManifestError(f"bad generator kind {traffic['kind']!r}")
    return importlib.import_module(f"{__package__}.gen.{traffic['kind']}")


def loop_kind(traffic: dict):
    """The pacing module a traffic file's `loop` names."""
    if not NAME.match(traffic["loop"]):
        raise ManifestError(f"bad loop {traffic['loop']!r}")
    return importlib.import_module(f"{__package__}.loops.{traffic['loop']}")


def read_layer_metrics(manifest: Manifest, cell_name: str, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something
    to read; one that finds nothing is left out, never reported as 0."""
    out = {}
    for metric in manifest.per_layer_for(cell_name):
        spec = manifest.layer_spec(metric)
        value = reader(spec).read(spec, ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
