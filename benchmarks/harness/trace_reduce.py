#!/usr/bin/env python3
"""From a profiler trace (`.xplane.pb`) to device-busy time, the top
device programs and the idle gaps by what the host was doing.

    JAX_PLATFORMS=cpu python trace_reduce.py <trace dir> <out.json>

Runs in a child of its own, after the servers have gone: it needs JAX
only to read the file (`jax.profiler.ProfileData`), never a chip.

Two steps, so that the second is checked on a small recorded trace
(`tests/benchmarks/test_trace_reduce.py`):

  read_trace(path)  -> {"planes": [{"name", "lines": [{"name",
                        "events": [[name, start_ns, duration_ns], ...]}]}]}
  reduce(trace)     -> busy union, window, programs, gaps

What counts as the device being busy: the union, per device plane, of
the intervals of its `XLA Ops` and `Async XLA Ops` lines (every
operation that ran on the chip, copies included, whichever program it
belongs to); where a trace has neither, of its `XLA Modules` line.  Programs are named as the `XLA
Modules` line names them.  A gap is a stretch of the device's own
span in which no operation ran; it is labelled by the host-plane event
(the runtime's own: execute, transfer, compile) that overlaps it
longest, or `no_runtime_event`.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_trace(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        name = plane.name
        if not (name.startswith(DEVICE_PLANE_PREFIX)
                or name.startswith(HOST_PLANE_PREFIX)):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": name, "lines": lines})
    return {"planes": planes}


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _line(plane: dict, name: str) -> list | None:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return None


def label_gaps(gaps: list[tuple[int, int]],
               host: list[tuple[int, int, str]]) -> list[str]:
    """For each gap the host event that overlaps it longest.  `gaps`
    ascending and disjoint, `host` ascending by start: one sweep, which
    keeps the host events still open at the gap (a closed loop's slice
    holds 100,000 gaps and more host events than that)."""
    labels = []
    open_: list[tuple[int, int, str]] = []
    at = 0
    for g0, g1 in gaps:
        while at < len(host) and host[at][0] < g1:
            open_.append(host[at])
            at += 1
        open_ = [h for h in open_ if h[1] > g0]
        best, best_overlap = "no_runtime_event", 0
        for start, end, name in open_:
            overlap = min(end, g1) - max(start, g0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        labels.append(best)
    return labels


def reduce(trace: dict) -> dict:
    devices = [p for p in trace["planes"]
               if p["name"].startswith(DEVICE_PLANE_PREFIX)]
    host = sorted(
        (start, start + dur, name)
        for p in trace["planes"] if p["name"].startswith(HOST_PLANE_PREFIX)
        for line in p["lines"] for name, start, dur in line["events"]
        if dur > 0
    )
    busy_ns = []
    span_ns = []
    programs: dict[str, int] = {}
    gaps: dict[str, int] = {}
    n_ops = 0
    for plane in devices:
        ops = [e for name in OPS_LINES for e in _line(plane, name) or []]
        if not ops:
            ops = _line(plane, MODULES_LINE) or []
        n_ops += len(ops)
        covered = union([(s, s + d) for _n, s, d in ops])
        if not covered:
            continue
        busy_ns.append(sum(e - s for s, e in covered))
        span_ns.append(covered[-1][1] - covered[0][0])
        for name, _s, d in _line(plane, MODULES_LINE) or []:
            programs[name] = programs.get(name, 0) + d
        idle = [(e0, s1) for (_s0, e0), (s1, _e1) in zip(covered, covered[1:])]
        for (g0, g1), label in zip(idle, label_gaps(idle, host)):
            gaps[label] = gaps.get(label, 0) + (g1 - g0)

    def top(table: dict[str, int]) -> list:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name[:80], ns / 1e9] for name, ns in ranked]

    return {
        "device_planes": len(busy_ns),
        "device_ops": n_ops,
        # Averaged over the chips that ran anything.
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else None,
        "device_span_s": max(span_ns) / 1e9 if span_ns else None,
        "programs": top(programs),
        "gaps": top(gaps),
    }


def main(argv: list[str]) -> int:
    trace_dir, out = argv
    path = find_xplane(trace_dir)
    if path is None:
        print(f"trace_reduce: no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    result = reduce(read_trace(path))
    result["xplane_bytes"] = os.path.getsize(path)
    with open(out, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
