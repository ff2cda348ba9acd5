#!/usr/bin/env python3
"""The semantic kernels as lone dispatches on the chip: device time a
batch, compile time, peak device memory, and a digest of what each
returned, for one checkout or for two side by side.

    chiprun -- python chip_kernels.py --out chiprun_out/<dir> [TREE ...]

Each TREE (default: this checkout) is a directory that holds a
`tigerbeetle_tpu` package; a parent commit unpacked with `git archive`
into `.parent/` is one.  Every tree runs in a child of its own, one
after the other (a chip belongs to one process at a time; this parent
never imports JAX), at the production bucket B = 8,192, for tables of
65,536 and 1,048,576 rows, on two batches made from `--seed`:

  full   a request shaped like `bench1r-tpcc-pay-c4`'s: 4,095 two-leg
         payments customer -> district -> warehouse over 8 warehouse
         rows (~512 legs each), 80 district rows (~102) and ~3,900
         customer rows; `linked_small` takes it as linked chains,
         `orderfree_tight` as plain transfers, `two_phase_lo` as 4,095
         pendings and the 4,095 posts and voids of them
  small  its first 37 events (`bench1r-small-c4`'s mean prepare)
  limits `linked_small` alone: the full batch over a table in which a
         seventh of the customers may not owe more than they are owed
         and owe already, so their payments fail `exceeds_credits`, the
         chains with them, and the failures outrun the summary row

Device time is the `XLA Modules` line of a profiler trace over RUNS
dispatches (median), wall time the host clock around a dispatch that
ends in `block_until_ready`.  The digest (the new table's checksum and
the summary row) of a tree must equal the other tree's: the last line
says whether it did.  Without a chip this is a rehearsal: run it with
`JAX_PLATFORMS=cpu TB_DEV_B=512 --rows 4096`; its times mean nothing.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

RUNS = 20
WAREHOUSES, DISTRICTS = 8, 10


def _payments(rng, np, n_pay, rows):
    """(customer, district, warehouse) slots of n_pay payments."""
    n_hot = WAREHOUSES * (1 + DISTRICTS)
    w = rng.integers(0, WAREHOUSES, n_pay)
    d = rng.integers(0, DISTRICTS, n_pay)
    # Customers: 3,000 favourites a district, the table's far end too.
    cust = n_hot + (w * DISTRICTS + d) * 3000 + rng.integers(0, 3000, n_pay)
    cust = np.where(rng.random(n_pay) < 0.05, rows - 1 - cust, cust) % rows
    cust = np.where(cust < n_hot, cust + n_hot, cust)
    return cust, WAREHOUSES + w * DISTRICTS + d, w


def _batches(dk, np, seed, rows):
    """{(kind, batch): packed input} for the three kinds, full and small."""
    rng = np.random.default_rng(seed)
    B = dk.B
    n_pay = (B - 2) // 2
    n = 2 * n_pay
    cust, dist, ware = _payments(rng, np, n_pay, rows)
    dr = np.stack([cust, dist], 1).reshape(n).astype(np.int64)
    cr = np.stack([dist, ware], 1).reshape(n).astype(np.int64)
    amount = np.repeat(rng.integers(100, 500_001, n_pay), 2).astype(np.uint64)
    zero = np.zeros(n, np.uint64)
    ids = np.arange(1, n + 1, dtype=np.uint64) + np.uint64(seed << 20)
    ledger = np.ones(n, np.uint32)
    code = np.ones(n, np.uint32)
    found = np.zeros(n, bool)
    out = {}
    # An account's id is its slot + 1; a payment's first leg is linked.
    out["linked_small"] = dk.pack_base(
        n, ids, zero, (dr + 1).astype(np.uint64), zero,
        (cr + 1).astype(np.uint64), zero, zero, zero, amount, zero,
        np.where(np.arange(n) % 2 == 0, dk.F_LINKED, 0).astype(np.uint32),
        ledger, code, np.zeros(n, np.uint32), np.zeros(n, bool), dr, cr,
        found,
    )
    out["orderfree_tight"] = dk.pack_tight(
        n, ids, zero, (dr + 1).astype(np.uint64), zero,
        (cr + 1).astype(np.uint64), zero, zero, zero, amount,
        np.zeros(n, np.uint32), ledger, code, np.zeros(n, bool), dr, cr,
    )
    # two_phase: the first half creates pendings, the second half posts
    # (even) or voids (odd) them, naming no accounts and no amount.
    half = n // 2
    is_pv = np.arange(n) >= half
    tgt = np.where(is_pv, np.arange(n) - half, -1)
    flags = np.where(
        is_pv, np.where(np.arange(n) % 2 == 0, dk.F_POST, dk.F_VOID),
        dk.F_PENDING,
    ).astype(np.uint32)
    none = np.full(n, -1, np.int64)
    pk = dk.pack_base(
        n, ids, zero, np.where(is_pv, 0, dr + 1).astype(np.uint64), zero,
        np.where(is_pv, 0, cr + 1).astype(np.uint64), zero,
        np.where(is_pv, ids[np.clip(tgt, 0, None)], 0).astype(np.uint64),
        zero, np.where(is_pv, 0, amount).astype(np.uint64), zero, flags,
        np.where(is_pv, 0, ledger).astype(np.uint32),
        np.where(is_pv, 0, code).astype(np.uint32), np.zeros(n, np.uint32),
        np.zeros(n, bool), np.where(is_pv, none, dr), np.where(is_pv, none, cr),
        found, p_found=np.zeros(n, bool), p_tgt=none, n_cols=dk.N_COLS_TP,
    )
    z32 = np.zeros(n, np.uint32)
    out["two_phase_lo"] = dk.pack_two_phase_ext(
        pk, n, np.zeros(n, np.uint64), z32, z32, z32, none, none, zero, zero,
        tgt, z32,
    )
    both = {}
    for kind, pk in out.items():
        both[kind, "full"] = dk.seal_scalars(pk.copy(), n, 1 << 40)
        # The rows past a batch's events are zero, as the engine packs
        # them.  The small two_phase batch: 19 pendings, 18 finalisers.
        small = pk.copy()
        if kind == "two_phase_lo":
            small[19:37] = pk[half:half + 18]
        small[37:] = 0
        both[kind, "small"] = dk.seal_scalars(small, 37, 1 << 40)
    both["linked_small", "limits"] = both["linked_small", "full"]
    return both


def _device_ms(trace_dir):
    """{program: [ms, ...]} from the device plane's `XLA Modules` line."""
    import glob

    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    programs: dict = {}
    ops: dict = {}
    if not found:
        return programs, ops
    for plane in ProfileData.from_file(found[-1]).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    name = ev.name.split("(")[0]
                    programs.setdefault(name, []).append(ev.duration_ns / 1e6)
            elif line.name == "XLA Ops":
                for ev in line.events:
                    ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns / 1e6
    return programs, ops


def child(tree: str, out_path: str, seed: int, tables: list[int]) -> None:
    sys.path.insert(0, os.path.abspath(tree))
    import numpy as np

    import jax

    from tigerbeetle_tpu.state_machine import device_kernels as dk

    dev = jax.devices()[0]
    result = {
        "tree": tree, "platform": dev.platform,
        "device_kind": dev.device_kind, "B": dk.B, "seed": seed, "cells": [],
    }
    trace_root = os.path.splitext(out_path)[0] + "_trace"
    for rows in tables:
        table_h = np.zeros((rows, 8), np.uint64)
        table_h[:, 0::2] = np.arange(rows, dtype=np.uint64)[:, None] + 7
        meta_h = np.zeros((rows, 2), np.uint32)
        meta_h[:, 1] = 1
        table = jax.device_put(table_h)
        plain = jax.device_put(meta_h)
        hot = WAREHOUSES * (1 + DISTRICTS)
        limits_h = meta_h.copy()   # the CPU backend's put may alias
        limits_h[hot:, 0] = np.where(
            np.arange(hot, rows) % 7 == 3, dk.AF_DR_LIMIT, 0)
        limits = jax.device_put(limits_h)
        for (kind, batch), pk_h in _batches(dk, np, seed, rows).items():
            meta = limits if batch == "limits" else plain
            fn = getattr(dk, kind)
            pk = jax.device_put(pk_h)
            t0 = time.perf_counter()
            outs = jax.block_until_ready(fn(table, meta, pk))
            compile_s = time.perf_counter() - t0
            for _ in range(3):
                jax.block_until_ready(fn(table, meta, pk))
            trace_dir = os.path.join(trace_root, f"{kind}_{batch}_{rows}")
            wall = []
            jax.profiler.start_trace(trace_dir)
            for _ in range(RUNS):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(table, meta, pk))
                wall.append((time.perf_counter() - t0) * 1e3)
            jax.profiler.stop_trace()
            programs, ops = _device_ms(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            name = next((p for p in programs if kind in p), None)
            new_table, row, dense = outs
            row_h = np.asarray(row)
            digest = hashlib.sha256(
                np.asarray(dk.checksum(new_table)).tobytes()
                + row_h.tobytes() + np.asarray(dense).tobytes()
            ).hexdigest()[:16]
            stats = dev.memory_stats() or {}
            result["cells"].append({
                "kind": kind, "batch": batch, "table_rows": rows,
                "device_ms": (
                    statistics.median(programs[name]) if name else None),
                "device_runs": len(programs.get(name, ())),
                "wall_ms": statistics.median(wall),
                "first_call_s": compile_s,
                "n_fail": int(row_h[0]), "flags": int(row_h[1]),
                "n_active": int(row_h[3]), "digest": digest,
                "peak_bytes_so_far": stats.get("peak_bytes_in_use"),
                "ops_ms": sorted(
                    ((round(ms / RUNS, 4), op[:160]) for op, ms in ops.items()),
                    reverse=True)[:60],
            })
            print(json.dumps({k: v for k, v in result["cells"][-1].items()
                              if k != "ops_ms"}), flush=True)
            del outs, new_table, row, dense, pk
        del table, plain, limits
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trees", nargs="*", default=["."])
    ap.add_argument("--out", required=True, help="directory for <tree>.json")
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--rows", default="65536,1048576",
                    help="table sizes, comma-separated")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(args.trees[0], args.child, args.seed,
              [int(r) for r in args.rows.split(",")])
        return 0
    os.makedirs(args.out, exist_ok=True)
    results = []
    for tree in args.trees:
        label = os.path.basename(os.path.abspath(tree)).strip(".") or "tree"
        path = os.path.join(args.out, f"{label}.json")
        rc = subprocess.call([
            sys.executable, os.path.abspath(__file__), tree, "--child", path,
            "--out", args.out, "--seed", str(args.seed),
            "--rows", args.rows,
        ])
        if rc != 0:
            print(f"{tree}: exit {rc}", file=sys.stderr)
            return rc
        with open(path) as f:
            results.append(json.load(f))
    first = results[0]
    print(f"\n{'kind':16} {'batch':6} {'rows':>8}  " + "  ".join(
        f"{os.path.basename(os.path.abspath(r['tree'])):>12}" for r in results))
    same = True
    for i, cell in enumerate(first["cells"]):
        cells = [r["cells"][i] for r in results]
        same &= len({c["digest"] for c in cells}) == 1
        print(f"{cell['kind']:16} {cell['batch']:6} {cell['table_rows']:8}  "
              + "  ".join(f"{c['device_ms'] or c['wall_ms']:12.3f}"
                          for c in cells))
    print(json.dumps({
        "device": first["device_kind"], "platform": first["platform"],
        "trees": [r["tree"] for r in results], "digests_equal": same,
        "peak_bytes": [r["cells"][-1]["peak_bytes_so_far"] for r in results],
    }))
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
