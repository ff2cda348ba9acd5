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
  zipf   the two linked kinds alone, on the first table only: a request
         of `bench1r-zipf-c4` (`benchmarks/harness/gen/hub_zipf.py`,
         one ledger's 100 participants) over the positions its
         reference holds after `--zipf-after` requests; its limit
         checks need over 64 Jacobi rounds, so the exact pass by blocks
         decides it after the rounds; with `--settle-blocks` once more
         for each block size given (a tree whose kernel takes no block
         size runs it once)
  chains the two linked kinds alone, on the first table only: the first
         request of chains of `bench1r-chains2p-c4` (session 0, after
         its funding and its pendings) over the positions its reference
         holds then

On the first table the linked batches (zipf, chains and the full
payment batch, as `linked_small` and as `linked`) run twice where the
tree's kernel has the gate: as served, and with the cap alone
(`<kind>_cap`); each prints the rounds it ran (`iters`), and the last
lines each kind's cost of a full-width round and of a block of the pass
on the zipf batch, from which `ROUND_BLOCKS` is read.  `--only zipf`
runs those linked batches alone.

`--only mixed` runs no lone kernel: it drives each tree's device-engine
state machine (a table of 65,536 rows) with `bench1r-mixed-c16`'s
traffic (`benchmarks/harness/gen/chains2p.py`, 16 sessions), its
requests coalesced seven to a prepare in session order, as the primary
packs them: MIXED_WARM prepares, then MIXED_PREPARES more whose
commits are timed one by one (host clock around the commit and its
reply; the device programs each ran, in order, from a profiler trace
of that commit alone).  A tree with the cut shows a prepare's runs,
one program each beside the digest's update; one without it, the path
the same prepare took there (a wave plan, or the drained host path).
The digest (every reply, the account table, the measured prepares'
stored transfers) must be the other tree's.  Device time is the `XLA
Modules` line of a profiler trace over RUNS dispatches (median), wall time the host clock around a dispatch that
ends in `block_until_ready`.  The digest (the new table's checksum, the
summary row with its flags word's verdict bits alone, and the codes) of
a tree must equal the other tree's, and with the cap alone the one the
gate gave: the last line says whether it did.  Without a chip this is a rehearsal: run it with
`JAX_PLATFORMS=cpu TB_DEV_B=512 --rows 16384`; its times mean nothing.
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
LINKED = ("linked_small", "linked")
WAREHOUSES, DISTRICTS = 8, 10
MIXED_WARM, MIXED_PREPARES, MIXED_PER_PREPARE = 12, 6, 7
REQUEST_MAX = 8190      # the most events or ids a request carries


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


def _config(name: str, cell_file: str) -> tuple[dict, dict]:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmarks", "configs", name)) as f:
        config = json.load(f)
    with open(os.path.join(here, "benchmarks", "traffic", cell_file)) as f:
        return config, json.load(f)


def _request(gen, t, positions: dict, np) -> dict:
    """A request `t` over `positions` (dp, dpo, cp, cpo: an account's
    debits and credits, pending and posted, by id from 1), as
    `_linked_batch` packs it."""
    z = {
        "dr": t["debit_account_id_lo"].astype(np.int64) - 1,
        "cr": t["credit_account_id_lo"].astype(np.int64) - 1,
        "amount": t["amount_lo"].copy(), "ids": t["id_lo"].copy(),
        "tflags": t["flags"].astype(np.uint32),
        "flags": gen.accounts()["flags"].astype(np.uint32),
    }
    for col, values in positions.items():
        z[col] = np.array(values[1:], np.uint64)
    return z


def zipf_request(after: int, seed: int) -> dict:
    """A `bench1r-zipf-c4` request of session 0 and the positions its
    reference holds before it (this checkout's generator: no JAX)."""
    import numpy as np

    from benchmarks.harness.gen import hub_zipf

    config, traffic = _config("hub100-zipf-1r.json", "zipf-c4.json")
    gen = hub_zipf.make(traffic, config, seed)
    ref = hub_zipf.reference(gen)
    for index in range(after):
        ref.apply(gen.request(0, index))
    t = gen.request(0, after)
    none = [0] * len(ref.debits)
    z = _request(gen, t, {"dp": none, "dpo": ref.debits, "cp": none,
                          "cpo": ref.credits}, np)
    z["codes"] = ref.codes(t)       # (applies the request)
    return z


def chains_request(seed: int) -> dict:
    """The first request of chains of `bench1r-chains2p-c4`'s session 0
    and the positions its reference holds before it."""
    import numpy as np

    from benchmarks.harness.gen import chains2p

    config, traffic = _config("chains2p-1r.json", "chains2p-c4.json")
    gen = chains2p.make(traffic, config, seed)
    ref = chains2p.reference(gen)
    index = 0
    while gen.klass(0, index) != "C":
        ref.apply(gen.request(0, index))
        index += 1
    t = gen.request(0, index)
    z = _request(gen, t, {"dp": ref.debits_pending, "dpo": ref.debits_posted,
                          "cp": ref.credits_pending, "cpo": ref.credits_posted}, np)
    z["codes"] = ref.codes(t)
    return z


def _linked_batch(dk, np, z, rows):
    """(table, meta, packed input) of a request from `_request`."""
    n = min(len(z["dr"]), dk.B)     # a rehearsal's bucket holds less
    z = {k: v[:n] if v.shape == z["dr"].shape else v for k, v in z.items()}
    zero = np.zeros(n, np.uint64)
    ones = np.ones(n, np.uint32)
    pk = dk.seal_scalars(dk.pack_base(
        n, z["ids"], zero, (z["dr"] + 1).astype(np.uint64), zero,
        (z["cr"] + 1).astype(np.uint64), zero, zero, zero, z["amount"], zero,
        z["tflags"], ones, ones * 7, np.zeros(n, np.uint32),
        np.zeros(n, bool), z["dr"], z["cr"], np.zeros(n, bool)), n, 1 << 40)
    table = np.zeros((rows, 8), np.uint64)
    accounts = len(z["flags"])
    for col, name in ((0, "dp"), (2, "dpo"), (4, "cp"), (6, "cpo")):
        table[:accounts, col] = z[name]
    meta = np.zeros((rows, 2), np.uint32)
    meta[:, 1] = 1
    meta[:accounts, 0] = z["flags"]
    return table, meta, pk


def _linked_kernels(dk, blocks):
    """{name: kernel} of the linked kinds beside the served ones: each
    with the cap alone, where the tree's kernel has the gate, and
    `linked_small` at each of `blocks`, where it takes a block size."""
    import functools
    import inspect

    params = inspect.signature(dk._linked).parameters
    out = {}
    if "gate" in params:
        for kind in LINKED:
            out[f"{kind}_cap"] = dk._jit_as(f"{kind}_cap", functools.partial(
                dk._linked, small=kind == "linked_small", gate=False))
    if "block" in params:
        for b in blocks:
            out[f"linked_small_b{b}"] = dk._jit_as(
                f"linked_small_b{b}", functools.partial(dk._linked, small=True, block=b))
    return out


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


def child(tree: str, out_path: str, seed: int, tables: list[int],
          requests_path: str | None = None, blocks: tuple = (),
          only: str | None = None) -> None:
    sys.path.insert(0, os.path.abspath(tree))
    import numpy as np

    import jax

    from tigerbeetle_tpu.state_machine import device_kernels as dk

    dev = jax.devices()[0]
    result = {
        "tree": tree, "platform": dev.platform,
        "device_kind": dev.device_kind, "B": dk.B, "seed": seed,
        "settle_block": getattr(dk, "SETTLE_BLOCK", None), "cells": [],
    }
    trace_root = os.path.splitext(out_path)[0] + "_trace"
    requests = {}
    if requests_path:
        with np.load(requests_path) as f:
            for key in f.files:
                batch, name = key.split(".", 1)
                requests.setdefault(batch, {})[name] = f[key]
    # The flags word's verdict bits: the rest says which path a batch
    # took, which the digest leaves out.
    verdict_bits = np.uint64(dk.FLAG_OVERFLOW | dk.FLAG_PRECOND)
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
        todo = {
            (kind, batch): (getattr(dk, kind), table,
                            limits if batch == "limits" else plain, pk_h)
            for (kind, batch), pk_h in _batches(dk, np, seed, rows).items()
            if only != "zipf" or (kind, batch) == ("linked_small", "full")}
        if requests_path and rows == tables[0]:
            extra = _linked_kernels(dk, blocks)
            pay = todo["linked_small", "full"][3]
            todo["linked", "full"] = (dk.linked, table, plain, pay)
            for name, fn in extra.items():
                if name.endswith("_cap"):
                    todo[name, "full"] = (fn, table, plain, pay)
            for batch, z in requests.items():
                t_h, m_h, pk_h = _linked_batch(dk, np, z, rows)
                t_d, m_d = jax.device_put(t_h), jax.device_put(m_h)
                for kind in LINKED:
                    todo[kind, batch] = (getattr(dk, kind), t_d, m_d, pk_h)
                for name, fn in extra.items():
                    if batch == "zipf" or name.endswith("_cap"):
                        todo[name, batch] = (fn, t_d, m_d, pk_h)
        for (kind, batch), (fn, tab, meta, pk_h) in todo.items():
            pk = jax.device_put(pk_h)
            t0 = time.perf_counter()
            outs = jax.block_until_ready(fn(tab, meta, pk))
            compile_s = time.perf_counter() - t0
            for _ in range(3):
                jax.block_until_ready(fn(tab, meta, pk))
            trace_dir = os.path.join(trace_root, f"{kind}_{batch}_{rows}")
            wall = []
            jax.profiler.start_trace(trace_dir)
            for _ in range(RUNS):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(tab, meta, pk))
                wall.append((time.perf_counter() - t0) * 1e3)
            jax.profiler.stop_trace()
            programs, ops = _device_ms(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            name = next((p for p in programs if kind in p), None)
            new_table, row, dense = outs
            row_h = np.asarray(row)
            verdict_row = row_h.copy()
            verdict_row[1] &= verdict_bits
            digest = hashlib.sha256(
                np.asarray(dk.checksum(new_table)).tobytes()
                + verdict_row.tobytes() + np.asarray(dense).tobytes()
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
            if kind.startswith("linked"):
                summary = dk.unpack_summary(row_h, np.asarray(dense))
                result["cells"][-1].update(
                    iters=summary["iters"], settled=summary.get("settled"),
                    settle_rounds=summary.get("settle_rounds"),
                    precond=summary["precond"])
                if batch in requests:
                    result["cells"][-1]["failed_want"] = int(
                        (requests[batch]["codes"][:dk.B] != 0).sum())
                print(json.dumps({k: result["cells"][-1].get(k) for k in (
                    "kind", "batch", "iters", "settled", "settle_rounds",
                    "precond", "n_fail", "failed_want")}), flush=True)
            del outs, new_table, row, dense, pk, tab
        del table, plain, limits, todo
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)


def mixed_prepares(seed: int, events_max: int):
    """(gen, [[(session, index), ...] a prepare]): `bench1r-mixed-c16`'s
    requests, every session's in its own order, seven to a prepare as
    the primary coalesces them (fewer where the bucket holds fewer:
    a rehearsal takes the traffic's rehearsal sizes)."""
    from benchmarks.harness.gen import chains2p

    config, traffic = _config("switch16-mixed-1r.json", "mixed-c16.json")
    if events_max < traffic["request_events"] * MIXED_PER_PREPARE:
        config = {**config, **config["rehearsal"]}
        traffic = {**traffic, **traffic["rehearsal"]}
    gen = chains2p.make(traffic, config, seed)
    per = max(1, min(MIXED_PER_PREPARE, events_max // gen.n))
    order = [(s, i) for i in range(MIXED_WARM + MIXED_PREPARES)
             for s in range(gen.sessions)]
    prepares = [order[at:at + per] for at in range(0, len(order), per)]
    return gen, prepares[:MIXED_WARM + MIXED_PREPARES]


def mixed_child(tree: str, out_path: str, seed: int) -> None:
    sys.path.insert(0, os.path.abspath(tree))
    import numpy as np

    import jax

    from tigerbeetle_tpu.state_machine import device_kernels as dk
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
    from tigerbeetle_tpu.testing import harness as hz
    from tigerbeetle_tpu.types import Operation

    dev = jax.devices()[0]
    gen, prepares = mixed_prepares(seed, dk.B)
    sm = TpuStateMachine(engine="device", account_capacity=1 << 16)
    h = hz.SingleNodeHarness(sm)
    digest = hashlib.sha256()
    accounts = gen.accounts()
    for at in range(0, len(accounts), REQUEST_MAX):
        digest.update(h.submit(Operation.create_accounts,
                               accounts[at:at + REQUEST_MAX].tobytes()))
    trace_root = os.path.splitext(out_path)[0] + "_trace"
    result = {"tree": tree, "platform": dev.platform,
              "device_kind": dev.device_kind, "B": dk.B, "seed": seed,
              "prepares": []}
    for at, prepare in enumerate(prepares):
        batch = np.concatenate([gen.request(s, i) for s, i in prepare])
        body = batch.tobytes()
        timed = at >= MIXED_WARM
        if timed:
            trace_dir = os.path.join(trace_root, str(at))
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        reply = h.submit(Operation.create_transfers, body)
        wall_ms = (time.perf_counter() - t0) * 1e3
        digest.update(reply)
        if not timed:
            continue
        jax.profiler.stop_trace()
        programs, _ops = _device_ms(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ran = sorted(((name, ms) for name, times in programs.items()
                      for ms in times), key=lambda x: x[0])
        stored = h.submit(Operation.lookup_transfers,
                          hz.ids_bytes(batch["id_lo"].tolist()))
        digest.update(stored)
        result["prepares"].append({
            "requests": [gen.klass(s, i) for s, i in prepare],
            "events": len(batch), "wall_ms": wall_ms,
            "programs": ran, "device_ms": sum(ms for _n, ms in ran),
            "failures": len(reply) // 8,
        })
        print(json.dumps(result["prepares"][-1]), flush=True)
    ids = list(range(1, gen.n_accounts + 1))
    for at in range(0, len(ids), REQUEST_MAX):
        digest.update(h.submit(Operation.lookup_accounts,
                               hz.ids_bytes(ids[at:at + REQUEST_MAX])))
    snap = sm.metrics.snapshot()
    result["counters"] = {k: snap[k] for k in (
        "plan.runs", "plan.cut_prepares", "host_semantic_events",
        "dev_wave.batches") if k in snap}
    result["digest"] = digest.hexdigest()[:16]
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)


def main_mixed(args) -> int:
    results = []
    for tree in args.trees:
        label = os.path.basename(os.path.abspath(tree)).strip(".") or "tree"
        path = os.path.join(args.out, f"{label}.json")
        rc = subprocess.call([
            sys.executable, os.path.abspath(__file__), tree, "--child", path,
            "--out", args.out, "--seed", str(args.seed), "--only", "mixed"])
        if rc != 0:
            print(f"{tree}: exit {rc}", file=sys.stderr)
            return rc
        with open(path) as f:
            results.append(json.load(f))
    for r in results:
        walls = [p["wall_ms"] for p in r["prepares"]]
        busy = [p["device_ms"] for p in r["prepares"]]
        print(json.dumps({"tree": r["tree"], "wall_ms_median": statistics.median(walls),
                          "device_ms_median": statistics.median(busy),
                          "counters": r["counters"]}))
    same = len({r["digest"] for r in results}) == 1
    print(json.dumps({
        "device": results[0]["device_kind"], "platform": results[0]["platform"],
        "trees": [r["tree"] for r in results], "digests_equal": same}))
    return 0 if same else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trees", nargs="*", default=["."])
    ap.add_argument("--out", required=True, help="directory for <tree>.json")
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--rows", default="65536,1048576",
                    help="table sizes, comma-separated")
    ap.add_argument("--zipf-after", type=int, default=120,
                    help="requests of the zipf batch's session before it")
    ap.add_argument("--settle-blocks", default="",
                    help="block sizes of the exact pass, comma-separated")
    ap.add_argument("--only", choices=("zipf", "mixed"))
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--requests", help=argparse.SUPPRESS)
    args = ap.parse_args()
    blocks = tuple(int(b) for b in args.settle_blocks.split(",") if b)
    if args.child and args.only == "mixed":
        mixed_child(args.trees[0], args.child, args.seed)
        return 0
    if args.child:
        child(args.trees[0], args.child, args.seed,
              [int(r) for r in args.rows.split(",")], args.requests, blocks,
              args.only)
        return 0
    os.makedirs(args.out, exist_ok=True)
    if args.only == "mixed":
        return main_mixed(args)
    import numpy as np

    requests_path = os.path.join(args.out, "requests.npz")
    np.savez(requests_path, **{
        f"{batch}.{k}": v for batch, z in (
            ("zipf", zipf_request(args.zipf_after, args.seed)),
            ("chains", chains_request(args.seed))) for k, v in z.items()})
    results = []
    for tree in args.trees:
        label = os.path.basename(os.path.abspath(tree)).strip(".") or "tree"
        path = os.path.join(args.out, f"{label}.json")
        rc = subprocess.call([
            sys.executable, os.path.abspath(__file__), tree, "--child", path,
            "--out", args.out, "--seed", str(args.seed),
            "--rows", args.rows, "--requests", requests_path,
            "--settle-blocks", args.settle_blocks,
            *(["--only", args.only] if args.only else []),
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
    keys = list(dict.fromkeys((c["kind"], c["batch"], c["table_rows"])
                              for r in results for c in r["cells"]))
    digests: dict = {}
    for key in keys:
        cells = [c for r in results for c in r["cells"]
                 if (c["kind"], c["batch"], c["table_rows"]) == key]
        # A kernel beside the served ones (the cap alone, a block size)
        # is in the trees that have it; every linked kernel answers a
        # batch as the others do.
        linked = key[0].startswith("linked")
        same &= (linked and key[0] not in LINKED) or len(cells) == len(results)
        family = ("linked" if linked else key[0],) + key[1:]
        digests.setdefault(family, set()).update(c["digest"] for c in cells)
        print(f"{key[0]:18} {key[1]:6} {key[2]:8}  " + "  ".join(
            f"{c['device_ms'] or c['wall_ms']:12.3f}" for c in cells))
    same &= all(len(d) == 1 for d in digests.values())
    for r in results:
        for kind in LINKED:
            costs = _round_costs(r, kind, np)
            if costs:
                print(json.dumps({"tree": r["tree"], "kind": kind, **costs}))
    print(json.dumps({
        "device": first["device_kind"], "platform": first["platform"],
        "trees": [r["tree"] for r in results], "digests_equal": same,
        "peak_bytes": [r["cells"][-1]["peak_bytes_so_far"] for r in results],
    }))
    return 0 if same else 1


def _round_costs(result: dict, kind: str, np) -> dict | None:
    """The zipf batch's cost of one full-width round and of a block of
    the pass, in ms, out of three runs of `kind`: the zipf batch
    with the gate and with the cap alone, and the full payment batch (one
    round, no pass).  Each is a fixed part, its rounds and its pass,
    whose cost is taken to follow the pass's rounds; the blocks are the
    most the batch holds (the pass may start later).  -> None where the
    tree ran no such three."""
    c: dict = {}
    for x in result["cells"]:
        c.setdefault((x["kind"], x["batch"]), x)     # the first table's
    on, cap, one = (c.get((kind, "zipf")), c.get((f"{kind}_cap", "zipf")),
                    c.get((kind, "full")))
    if not (on and cap and one) or cap["iters"] == on["iters"]:
        return None

    def ms(x):
        return x["device_ms"] or x["wall_ms"]

    rounds, pass_rounds = np.linalg.solve(
        [[cap["iters"] - on["iters"], cap["settle_rounds"] - on["settle_rounds"]],
         [on["iters"] - one["iters"], on["settle_rounds"]]],
        [ms(cap) - ms(on), ms(on) - ms(one)])
    blocks = -(-on["n_active"] // result["settle_block"])
    block_ms = pass_rounds * on["settle_rounds"] / blocks
    return {"exit_round": on["iters"], "cap_round": cap["iters"],
            "round_ms": rounds, "pass_ms": pass_rounds * on["settle_rounds"],
            "blocks_at_most": blocks, "block_ms_at_least": block_ms,
            "round_blocks_at_most": rounds / block_ms}


if __name__ == "__main__":
    sys.exit(main())
