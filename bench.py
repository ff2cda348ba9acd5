"""Driver benchmark: create_transfers commit throughput + oracle parity.

Runs ALL FIVE BASELINE.json configs through the TPU state machine:
  simple     1M unlinked posted transfers over 1k accounts, one ledger
  linked     chains (avg len 4) + must_not_exceed balance constraints
  two_phase  pending -> post/void mix (30% void), in-batch pairs
  zipf       1M transfers Zipf-skewed over 100 accounts (contention)
  mixed      create_accounts + create_transfers + lookup_accounts
             interleaved over 4 ledgers

and verifies parity against the CPU oracle (CpuStateMachine): per-batch
reply bytes must match exactly, and the final wire-level state (every
account row via lookup_accounts, a transfer sample via lookup_transfers)
must be bit-identical.  The simple config's parity replay covers the
full 1M stream (BASELINE.json north star: "bit-identical results ... on
the 1M replay"); the other configs replay a truncated stream because
the oracle is per-event Python (~17k tx/s) and runs unmetered.

Prints ONE JSON line.  `value`/`vs_baseline` is the simple config
(the graded metric, vs the reference's 800k tx/s AlphaBeetle headline,
reference: docs/about/README.md:78); the other configs, the parity
verdict, and the device/host work split ride along as extra keys.

Env knobs: BENCH_SMALL=1 (quick dev run: 100k events, no parity),
BENCH_PARITY=0 (skip parity), BENCH_FULL_PARITY=1 (full-stream parity
for every config), BENCH_TRANSFERS=N (simple-config event count).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tigerbeetle_tpu import types
from tigerbeetle_tpu.types import (
    ACCOUNT_DTYPE,
    TRANSFER_DTYPE,
    U128_PAIR_DTYPE,
    AccountFlags,
    Operation,
    TransferFlags,
)

BASELINE_TPS = 800_000.0
BATCH = int(os.environ.get("BENCH_BATCH", 8_190))
SMALL = os.environ.get("BENCH_SMALL") == "1"
N_SIMPLE = int(
    os.environ.get("BENCH_TRANSFERS", 100_000 if SMALL else 1_000_000)
)
N_OTHER = 100_000 if SMALL else 1_000_000
PARITY = os.environ.get("BENCH_PARITY", "0" if SMALL else "1") == "1"
# Full-stream parity for EVERY config is the default (VERDICT r2 item
# 9): the Python oracle costs ~1 unmetered minute per 1M-event config.
# BENCH_FULL_PARITY=0 falls back to a 200k truncated replay for the
# non-simple configs.
FULL_PARITY = os.environ.get("BENCH_FULL_PARITY", "1") == "1"
N_PARITY_OTHER = 200_000

TF = TransferFlags
AF = AccountFlags


def accounts_bytes(ids, ledger=None, flags=None) -> bytes:
    ids = np.asarray(ids, np.uint64)
    arr = np.zeros(len(ids), dtype=ACCOUNT_DTYPE)
    arr["id_lo"] = ids
    arr["ledger"] = 1 if ledger is None else ledger
    arr["code"] = 1
    if flags is not None:
        arr["flags"] = flags
    return arr.tobytes()


def lookup_bytes(ids) -> bytes:
    arr = np.zeros(len(ids), dtype=U128_PAIR_DTYPE)
    arr["lo"] = np.asarray(ids, np.uint64)
    return arr.tobytes()


def transfers_bytes(
    ids, dr, cr, amount, *, ledger=1, flags=None, pending_id=None, timeout=None
) -> bytes:
    n = len(ids)
    arr = np.zeros(n, dtype=TRANSFER_DTYPE)
    arr["id_lo"] = ids
    arr["debit_account_id_lo"] = dr
    arr["credit_account_id_lo"] = cr
    arr["amount_lo"] = amount
    arr["ledger"] = ledger
    arr["code"] = 1
    if flags is not None:
        arr["flags"] = flags
    if pending_id is not None:
        arr["pending_id_lo"] = pending_id
    if timeout is not None:
        arr["timeout"] = timeout
    return arr.tobytes()


def batched(ops_arrays, op=Operation.create_transfers):
    """Split one big per-event array dict into (op, bytes) batches."""
    out = []
    n = len(ops_arrays["ids"])
    for at in range(0, n, BATCH):
        sl = slice(at, min(at + BATCH, n))
        out.append(
            (
                op,
                transfers_bytes(
                    ops_arrays["ids"][sl],
                    ops_arrays["dr"][sl],
                    ops_arrays["cr"][sl],
                    ops_arrays["amount"][sl],
                    ledger=ops_arrays.get("ledger", 1),
                    flags=None
                    if "flags" not in ops_arrays
                    else ops_arrays["flags"][sl],
                    pending_id=None
                    if "pending_id" not in ops_arrays
                    else ops_arrays["pending_id"][sl],
                    timeout=None
                    if "timeout" not in ops_arrays
                    else ops_arrays["timeout"][sl],
                ),
            )
        )
    return out


# ---------------------------------------------------------------------------
# Config generators.  Each returns (setup_ops, timed_ops, sizing) where
# ops are [(Operation, bytes)] and sizing = (account_cap, transfer_cap).
# Setup includes one untimed warmup transfer batch (ids 50M+) so JIT
# compilation and flush-shape warmup stay out of the timed window for
# BOTH engines (the oracle replays the identical stream).

TID0 = 1  # first timed transfer id
WARM0 = 50_000_000  # warmup transfer ids


def gen_simple(n_events: int):
    rng = np.random.default_rng(42)
    n_acct = 1_000
    setup = [(Operation.create_accounts, accounts_bytes(range(1, n_acct + 1)))]
    warm_n = min(BATCH, n_events)
    dr = rng.integers(1, n_acct + 1, warm_n, np.uint64)
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + warm_n, dtype=np.uint64),
            "dr": dr,
            "cr": dr % np.uint64(n_acct) + np.uint64(1),
            "amount": rng.integers(1, 100, warm_n, np.uint64),
        }
    )
    dr = rng.integers(1, n_acct + 1, n_events, np.uint64)
    timed = batched(
        {
            "ids": np.arange(TID0, TID0 + n_events, dtype=np.uint64),
            "dr": dr,
            "cr": dr % np.uint64(n_acct) + np.uint64(1),
            "amount": rng.integers(1, 100, n_events, np.uint64),
        }
    )
    return setup, timed, (1 << 12, n_events + 2 * BATCH + 1024)


def gen_linked(n_events: int):
    """Chains avg len 4, half the accounts debit-limited (funded in
    setup so most chains succeed while some trip the limit and roll
    back whole chains)."""
    rng = np.random.default_rng(43)
    n_acct = 1_000
    limited = np.arange(1, n_acct // 2 + 1, dtype=np.uint64)
    flags = np.zeros(n_acct, np.uint16)
    flags[: n_acct // 2] = int(AF.debits_must_not_exceed_credits)
    setup = [
        (
            Operation.create_accounts,
            accounts_bytes(range(1, n_acct + 1), flags=flags),
        )
    ]
    # Fund the limited accounts: credit each from the last plain account.
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + len(limited), dtype=np.uint64),
            "dr": np.full(len(limited), n_acct, np.uint64),
            "cr": limited,
            "amount": np.full(len(limited), 50_000, np.uint64),
        }
    )
    # Warmup chains (exercise the exact engine's compile-free path).
    warm = _chain_events(rng, 2 * BATCH, n_acct, WARM0 + 1_000_000)
    setup += _chain_batches(warm)

    timed = _chain_batches(_chain_events(rng, n_events, n_acct, TID0))
    n_total = sum(
        len(b) // 128 for _op, b in timed
    )
    return setup, timed, (1 << 12, n_total + 4 * BATCH + len(limited) + 1024)


def _chain_events(rng, n_events, n_acct, id0):
    lens = rng.integers(1, 8, size=n_events // 2 + BATCH)  # avg 4
    ends = np.cumsum(lens)
    n_chains = int(np.searchsorted(ends, n_events, side="left")) + 1
    lens = lens[:n_chains]
    total = int(lens.sum())
    # linked flag on every chain member except the last.
    last_idx = np.cumsum(lens) - 1
    flags = np.full(total, int(TF.linked), np.uint16)
    flags[last_idx] = 0
    dr = rng.integers(1, n_acct + 1, total, np.uint64)
    cr = rng.integers(1, n_acct + 1, total, np.uint64)
    clash = cr == dr
    cr[clash] = dr[clash] % np.uint64(n_acct) + np.uint64(1)
    return {
        "ids": np.arange(id0, id0 + total, dtype=np.uint64),
        "dr": dr,
        "cr": cr,
        "amount": rng.integers(1, 200, total, np.uint64),
        "flags": flags,
        "chain_ends": np.cumsum(lens),
    }


def _chain_batches(ev):
    """Batch without splitting a chain across batches (an open chain at
    the end of a batch fails with linked_event_chain_open)."""
    out = []
    ends = ev["chain_ends"]
    total = len(ev["ids"])
    start = 0
    while start < total:
        # Last chain end fitting within BATCH events of `start`.
        hi = int(np.searchsorted(ends, start + BATCH, side="right"))
        if hi == 0 or ends[hi - 1] <= start:
            break
        stop = int(ends[hi - 1])
        sl = slice(start, stop)
        out.append(
            (
                Operation.create_transfers,
                transfers_bytes(
                    ev["ids"][sl], ev["dr"][sl], ev["cr"][sl],
                    ev["amount"][sl], flags=ev["flags"][sl],
                ),
            )
        )
        start = stop
    return out


def gen_two_phase(n_events: int):
    """Adjacent (pending, post|void) pairs; 30% void, amount inherited
    (zero-means-inherit, reference: src/state_machine.zig:1743-1804)."""
    rng = np.random.default_rng(44)
    n_acct = 1_000
    setup = [(Operation.create_accounts, accounts_bytes(range(1, n_acct + 1)))]
    n_pairs = n_events // 2

    def pairs(n, id0):
        ids = np.arange(id0, id0 + 2 * n, dtype=np.uint64)
        flags = np.zeros(2 * n, np.uint16)
        flags[0::2] = int(TF.pending)
        void = rng.random(n) < 0.30
        flags[1::2] = np.where(
            void, int(TF.void_pending_transfer), int(TF.post_pending_transfer)
        ).astype(np.uint16)
        pending_id = np.zeros(2 * n, np.uint64)
        pending_id[1::2] = ids[0::2]
        dr = np.zeros(2 * n, np.uint64)
        cr = np.zeros(2 * n, np.uint64)
        dr[0::2] = rng.integers(1, n_acct + 1, n, np.uint64)
        cr[0::2] = dr[0::2] % np.uint64(n_acct) + np.uint64(1)
        amount = np.zeros(2 * n, np.uint64)
        amount[0::2] = rng.integers(1, 100, n, np.uint64)
        return {
            "ids": ids, "dr": dr, "cr": cr, "amount": amount,
            "flags": flags, "pending_id": pending_id,
        }

    warm_pairs = BATCH // 2
    setup += batched(pairs(warm_pairs, WARM0))
    timed = batched(pairs(n_pairs, TID0))
    return setup, timed, (1 << 12, 2 * n_pairs + 4 * BATCH + 1024)


def gen_zipf(n_events: int):
    rng = np.random.default_rng(45)
    n_acct = 100
    ranks = np.arange(1, n_acct + 1, dtype=np.float64)
    p = (1.0 / ranks) / (1.0 / ranks).sum()
    setup = [(Operation.create_accounts, accounts_bytes(range(1, n_acct + 1)))]
    warm_n = min(BATCH, n_events)

    def draw(n):
        dr = rng.choice(n_acct, size=n, p=p).astype(np.uint64) + np.uint64(1)
        cr = rng.choice(n_acct, size=n, p=p).astype(np.uint64) + np.uint64(1)
        clash = cr == dr
        cr[clash] = dr[clash] % np.uint64(n_acct) + np.uint64(1)
        return dr, cr

    dr, cr = draw(warm_n)
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + warm_n, dtype=np.uint64),
            "dr": dr, "cr": cr,
            "amount": rng.integers(1, 100, warm_n, np.uint64),
        }
    )
    dr, cr = draw(n_events)
    timed = batched(
        {
            "ids": np.arange(TID0, TID0 + n_events, dtype=np.uint64),
            "dr": dr, "cr": cr,
            "amount": rng.integers(1, 100, n_events, np.uint64),
        }
    )
    return setup, timed, (1 << 12, n_events + 2 * BATCH + 1024)


def gen_mixed(n_events: int):
    """Interleaved create_accounts / create_transfers / lookup_accounts
    over 4 ledgers (BASELINE.json config 5)."""
    rng = np.random.default_rng(46)
    n_ledgers = 4
    per_ledger = [list(range(led * 100_000 + 1, led * 100_000 + 501))
                  for led in range(1, n_ledgers + 1)]
    setup = []
    for led in range(1, n_ledgers + 1):
        setup.append(
            (
                Operation.create_accounts,
                accounts_bytes(per_ledger[led - 1], ledger=led),
            )
        )
    warm_n = BATCH
    led_accts = per_ledger[0]
    dr = rng.choice(led_accts, warm_n).astype(np.uint64)
    cr = rng.choice(led_accts, warm_n).astype(np.uint64)
    clash = cr == dr
    cr[clash] = np.where(
        dr[clash] == led_accts[-1], led_accts[0], dr[clash] + 1
    )
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + warm_n, dtype=np.uint64),
            "dr": dr, "cr": cr,
            "amount": rng.integers(1, 100, warm_n, np.uint64),
            "ledger": 1,
        }
    )

    timed = []
    next_tid = TID0
    next_acct = {led: led * 100_000 + 501 for led in range(1, n_ledgers + 1)}
    events = 0
    k = 0
    while events < n_events:
        r = k % 10
        if r == 3:
            # New accounts on a rotating ledger.
            led = (k // 10) % n_ledgers + 1
            n_new = 500
            ids = list(range(next_acct[led], next_acct[led] + n_new))
            next_acct[led] += n_new
            per_ledger[led - 1].extend(ids)
            timed.append(
                (Operation.create_accounts, accounts_bytes(ids, ledger=led))
            )
            events += n_new
        elif r == 7:
            led = rng.integers(1, n_ledgers + 1)
            ids = rng.choice(per_ledger[int(led) - 1], 2_000)
            timed.append((Operation.lookup_accounts, lookup_bytes(ids)))
            events += len(ids)
        else:
            led = int(rng.integers(1, n_ledgers + 1))
            accts = np.asarray(per_ledger[led - 1], np.uint64)
            n = min(BATCH, n_events - events)
            dr = rng.choice(accts, n)
            cr = rng.choice(accts, n)
            clash = cr == dr
            cr[clash] = np.where(
                dr[clash] == accts[-1], accts[0], dr[clash] + 1
            )
            timed += batched(
                {
                    "ids": np.arange(next_tid, next_tid + n, dtype=np.uint64),
                    "dr": dr, "cr": cr,
                    "amount": rng.integers(1, 100, n, np.uint64),
                    "ledger": led,
                }
            )
            next_tid += n
            events += n
        k += 1
    return setup, timed, (1 << 15, (next_tid - TID0) + 4 * BATCH + 1024)


CONFIGS = {
    "simple": gen_simple,
    "simple_device": gen_simple,
    "linked": gen_linked,
    "two_phase": gen_two_phase,
    "zipf": gen_zipf,
    "mixed": gen_mixed,
}

# Execution engine per config (VERDICT r3 #1): the device-authoritative
# engine computes result codes ON the TPU for every config except the
# graded `simple` headline and the durable full-system config, which
# run the round-3 host fast path.  Rationale (a link measured in an
# earlier round, not re-measured since): its device->host fetch cost
# ~105 ms at ~15 MB/s serialized, so even failure-sparse summary
# readback capped the device-authoritative path well below the host
# path's 5M+ ev/s — the headline keeps the throughput architecture,
# the other four configs prove the device-authoritative one at full
# parity.  Override per-run with TB_ENGINE=host|device.
CONFIG_ENGINE = {
    "simple": "host",
    # The SAME workload on the device-authoritative engine, reported
    # alongside the graded host row (VERDICT r4 #3): the north star is
    # the commit loop on the TPU, so the flagship workload must
    # exercise the semantic kernels too.
    "simple_device": "device",
    "linked": "device",
    "two_phase": "device",
    "zipf": "device",
    "mixed": "device",
}


# ---------------------------------------------------------------------------
# Execution + parity.


# Device-kernel kinds each config's workload routes to: named so the
# engine prewarms their transfer plans + scan compiles during untimed
# setup (one-time ~1s/shape + XLA compile costs otherwise land inside
# the first timed window).
CONFIG_PREWARM = {
    "simple_device": "orderfree_tight,orderfree_lo",
    "linked": "linked_small,linked",
    "two_phase": "two_phase_lo",
    "zipf": "orderfree_tight,orderfree_lo",
    "mixed": "orderfree_tight,orderfree_lo",
}


def _make_tpu(sizing, engine="host", config_name=""):
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    engine = os.environ.get("TB_ENGINE", engine)
    prewarm = (
        CONFIG_PREWARM.get(config_name, "orderfree_lo")
        if engine == "device"
        else None
    )
    return TpuStateMachine(
        account_capacity=sizing[0], transfer_capacity=sizing[1],
        engine=engine, prewarm=prewarm,
    )


def replay(sm, ops, collect=False):
    """Run ops through a fresh harness (pipelined when the machine
    supports it); returns (elapsed, replies)."""
    from tigerbeetle_tpu.testing.harness import SingleNodeHarness

    h = SingleNodeHarness(sm)
    t0 = time.perf_counter()
    futs = [h.submit_async(op, body) for op, body in ops]
    replies = [f.result() for f in futs]
    if hasattr(sm, "sync"):
        sm.sync()
    return (
        time.perf_counter() - t0,
        replies if collect else None,
        h,
    )


def n_events_of(ops) -> int:
    total = 0
    for op, body in ops:
        size = (
            types.EVENT_DTYPE[op].itemsize if op in types.EVENT_DTYPE else 128
        )
        total += len(body) // size
    return total


def state_digest(h, account_ids, transfer_ids) -> str:
    """Wire-level digest: every account row + a transfer sample."""
    hasher = hashlib.sha256()
    ids = np.asarray(account_ids, np.uint64)
    for at in range(0, len(ids), BATCH):
        reply = h.submit(
            Operation.lookup_accounts, lookup_bytes(ids[at : at + BATCH])
        )
        hasher.update(reply)
    tids = np.asarray(transfer_ids, np.uint64)
    for at in range(0, len(tids), BATCH):
        reply = h.submit(
            Operation.lookup_transfers, lookup_bytes(tids[at : at + BATCH])
        )
        hasher.update(reply)
    return hasher.hexdigest()


def config_account_ids(name):
    if name == "zipf":
        return np.arange(1, 101, dtype=np.uint64)
    if name == "mixed":
        ids = []
        for led in range(1, 5):
            ids.extend(range(led * 100_000 + 1, led * 100_000 + 3_001))
        return np.asarray(ids, np.uint64)
    return np.arange(1, 1_001, dtype=np.uint64)


def run_durable(n_events: int) -> dict:
    """Same-session before/after: synchronous checkpoints (the r6
    behavior — the whole spill + fsync + flip stalls the commit loop)
    vs asynchronous checkpoints (TB_CKPT_ASYNC=1 default: only the
    freeze stalls; the disk half runs on the checkpoint worker).  The
    headline numbers are the AFTER run; the before run rides along
    under "before" so the win is a graded number, not a claim."""
    before = _run_durable_once(n_events, ckpt_async=False)
    after = _run_durable_once(n_events, ckpt_async=True)
    after["before"] = {
        k: before.get(k)
        for k in (
            "events_per_sec", "commit_p50_ms", "commit_p99_ms",
            "commit_p999_ms", "commit_p100_ms", "ckpt_stall_ms_p50",
            "ckpt_stall_ms_p100", "fsyncs", "ckpt_async",
        )
    }
    return after


def _run_durable_once(n_events: int, ckpt_async: bool = True) -> dict:
    """The FULL server path at scale: real data file on disk, WAL
    append per op, forest attached, LSM spill + paced compaction at
    checkpoints — nothing stubbed (VERDICT r2 item 2: benchmark the
    real system, not the standalone machine).

    Checkpoints fire every 24 create ops (~196k events) — far more
    often than production's 960-op interval would at this batch size,
    deliberately: each one spills the whole RAM tail and creates merge
    debt for the beat pacing to absorb, which is the cost this config
    prices.  Reports commit p50/p99/p999/p100 + checkpoint stall
    alongside throughput.
    """
    import shutil
    import tempfile

    from tigerbeetle_tpu.vsr import replica as vsr_replica
    from tigerbeetle_tpu.vsr.storage import FileStorage, ZoneLayout

    conf = __import__(
        "tigerbeetle_tpu.constants", fromlist=["PRODUCTION"]
    ).PRODUCTION
    layout = ZoneLayout(config=conf)
    tmp = tempfile.mkdtemp(prefix="tb_bench_durable_")
    path = os.path.join(tmp, "0_0.tigerbeetle")
    env_before = os.environ.get("TB_CKPT_ASYNC")
    os.environ["TB_CKPT_ASYNC"] = "1" if ckpt_async else "0"
    r = storage = None
    try:
        storage = FileStorage(path, layout, create=True)
        vsr_replica.format(storage, cluster=0xB, replica=0, replica_count=1)
        from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

        sm = TpuStateMachine(
            conf, account_capacity=1 << 12,
            transfer_capacity=n_events + 2 * BATCH + 1024,
        )
        r = vsr_replica.Replica(storage, 0xB, sm)
        r.open()

        setup, timed, _sizing = gen_simple(n_events)
        for op, body in setup:
            r.on_request(int(op), body)
        sm.sync()
        # Counter reset (and the final read below) must see a drained
        # grid write-behind queue — pending SerialWorker block writes
        # increment the counters only when they execute.
        sm._forest.barrier()  # beats handed to the lsm-beat worker
        sm._forest.grid.flush_writes()
        storage.stat_bytes_wal = 0
        storage.stat_bytes_grid = 0
        storage.stat_bytes_control = 0
        storage.stat_fsyncs = 0
        # Registry baseline for the timed window: counters delta by
        # value, histograms by bucket counts (obs.counts_delta) —
        # registry instruments are monotonic and never reset, and the
        # setup phase above (incl. first-commit JIT cold starts) must
        # not pollute the timed percentiles.
        from tigerbeetle_tpu import obs

        wal_writes_before = r.metrics.snapshot().get("journal.writes", 0)
        h_request = r.metrics.histogram("request_us")
        h_commit = r.metrics.histogram("commit_us")
        request_counts_before = dict(h_request.counts)
        commit_counts_before = dict(h_commit.counts)

        def _windowed_p(hist, before, q):
            return obs.percentile_of_counts(
                obs.counts_delta(dict(hist.counts), before), q
            )
        # ~5 checkpoints over the stream, min every 4 ops (small runs
        # must still exercise spill + compaction debt).
        ckpt_every = max(4, min(48, len(timed) // 3))
        lat = []
        ckpt_stall = []  # how long r.checkpoint() blocks the commit loop
        failed = 0
        n_ckpt = 0
        t0 = time.perf_counter()
        for k, (op, body) in enumerate(timed):
            b0 = time.perf_counter()
            reply = r.on_request(int(op), body)
            if (k + 1) % ckpt_every == 0:
                c0 = time.perf_counter()
                r.checkpoint()
                ckpt_stall.append(time.perf_counter() - c0)
                n_ckpt += 1
            lat.append(time.perf_counter() - b0)
            failed += len(reply) // 8
        r._ckpt_join()  # in-flight flip lands outside the timed window
        sm.sync()
        # Inside it: the (at most two) beats still on the lsm-beat
        # worker are work the timed commits caused.
        sm._forest.barrier()
        elapsed = time.perf_counter() - t0
        # Outside the timed window (metric continuity across rounds):
        # drain the write-behind queue so the byte counters are exact.
        sm._forest.grid.flush_writes()
        assert failed == 0, f"durable: {failed} transfers failed"
        n_timed = n_events_of(timed)
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        reg = r.metrics.snapshot()
        return {
            "events_per_sec": round(n_timed / elapsed, 1),
            "events": n_timed,
            "failed_events": failed,
            "vs_baseline": round(n_timed / elapsed / BASELINE_TPS, 4),
            "engine": sm.engine,
            "device_resolved_pct": round(
                100.0
                * sm.stat_device_events
                / max(1, sm.stat_device_events + sm.stat_exact_events),
                1,
            ),
            "device_semantic_pct": round(
                100.0
                * sm.stat_device_semantic_events
                / max(
                    1,
                    sm.stat_device_semantic_events
                    + sm.stat_host_semantic_events,
                ),
                1,
            ),
            "commit_p50_ms": round(float(lat_ms[len(lat_ms) // 2]), 2),
            "commit_p99_ms": round(float(lat_ms[int(len(lat_ms) * 0.99)]), 2),
            "commit_p999_ms": round(
                float(lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.999))]), 2
            ),
            "commit_p100_ms": round(float(lat_ms[-1]), 2),
            # Registry-sourced percentiles (obs/registry.py),
            # WINDOWED to the timed loop via bucket-count deltas so
            # the setup phase's cold-start outliers stay out.
            # request_us covers the full prepare -> WAL sync ->
            # commit chain (the registry counterpart of the
            # driver-side commit_p* timings above, which ride along
            # as the independent cross-check); commit_us isolates the
            # state-machine commit stage.
            "registry_request_p50_ms": round(
                _windowed_p(h_request, request_counts_before, 0.5) / 1e3, 2
            ),
            "registry_request_p99_ms": round(
                _windowed_p(h_request, request_counts_before, 0.99) / 1e3, 2
            ),
            "registry_request_p999_ms": round(
                _windowed_p(h_request, request_counts_before, 0.999) / 1e3,
                2,
            ),
            "registry_commit_p50_ms": round(
                _windowed_p(h_commit, commit_counts_before, 0.5) / 1e3, 2
            ),
            "registry_commit_p99_ms": round(
                _windowed_p(h_commit, commit_counts_before, 0.99) / 1e3, 2
            ),
            "registry_commit_p999_ms": round(
                _windowed_p(h_commit, commit_counts_before, 0.999) / 1e3, 2
            ),
            "registry_ckpt_freeze_ms_p100": round(
                reg.get("ckpt.freeze_us.max", 0.0) / 1e3, 2
            ),
            "checkpoints": n_ckpt,
            "ckpt_async": ckpt_async,
            "ckpt_stall_ms_p50": round(
                float(np.median(ckpt_stall) * 1e3), 2
            ) if ckpt_stall else 0.0,
            "ckpt_stall_ms_p100": round(
                float(max(ckpt_stall) * 1e3), 2
            ) if ckpt_stall else 0.0,
            "fsyncs": storage.stat_fsyncs,
            # Timed-window WAL appends from the registry: the durable
            # analog of the replicated config's scraped ratio.
            "prepares": int(reg.get("journal.writes", 0) - wal_writes_before),
            "fsyncs_per_prepare": round(
                storage.stat_fsyncs
                / max(1, reg.get("journal.writes", 0) - wal_writes_before),
                3,
            ),
            "spilled_rows": int(sm._store.base),
            "hot_tail_batches": sm.stat_hot_tail_batches,
            "slow_tail_batches": sm.stat_slow_tail_batches,
            # Write-amplification forensics (VERDICT r4 #5): payload is
            # 128 B/event; everything above that is WAL framing + LSM
            # spill/compaction re-writes.
            "bytes_per_event": round(
                (
                    storage.stat_bytes_wal
                    + storage.stat_bytes_grid
                    + storage.stat_bytes_control
                )
                / max(1, n_timed),
                1,
            ),
            "wal_bytes": storage.stat_bytes_wal,
            "grid_bytes": storage.stat_bytes_grid,
            "control_bytes": storage.stat_bytes_control,
        }
    finally:
        if env_before is None:
            os.environ.pop("TB_CKPT_ASYNC", None)
        else:
            os.environ["TB_CKPT_ASYNC"] = env_before
        if r is not None:
            r.close()  # before/after share one process: no leaked workers
        if storage is not None:
            storage.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_replicated(n_events: int) -> dict:
    """Same-session before/after (round 14): per-message ingest
    (TB_FASTPATH_DECODE=0 — per-frame decode, per-request in-flight
    scans, per-sub reply encode; NOTE both arms share the r14
    single-verify and send2 paths, so this "before" is already faster
    than the true pre-r14 server) vs the columnar ingest fast path
    (default: one arena drain + one batch checksum pass per poll,
    batched request intake, coalesced reply encode).  Group commit +
    async checkpoints (the r10 spine) are on in BOTH arms.  The
    headline numbers are the AFTER run; "before" rides along so the
    decode-µs/event and throughput deltas are graded numbers."""
    # This box's disk throughput varies ~2x run to run (see the r10
    # notes) — one pair of arms can invert on noise alone.  The arms
    # INTERLEAVE (off, on, off, on, ...) so slow-disk windows hit both
    # equally, and the reported run per arm is the events_per_sec
    # median.  BENCH_REPL_REPEATS=1 keeps the quick default.
    repeats = max(1, int(os.environ.get("BENCH_REPL_REPEATS", 1)))
    befores, afters = [], []
    for _ in range(repeats):
        # Round 22: the graded before/after axis is the C-resident
        # drain loop (TB_NATIVE_DRAIN=0/1); the columnar ingest fast
        # path (r14) AND the native commit pipeline (r20) are on in
        # BOTH arms, so the delta isolates batching the whole
        # prepare→ack→commit-decision drain into one Python→C call
        # vs N per-prepare calls over the same C kernels.
        befores.append(_run_replicated_once(
            n_events, fastpath=True, native_pipeline=True,
            native_drain=False,
        ))
        afters.append(_run_replicated_once(
            n_events, fastpath=True, native_pipeline=True,
            native_drain=True,
        ))

    def median_run(runs):
        good = [r for r in runs if "error" not in r]
        if not good:
            return runs[0]
        good.sort(key=lambda r: r["events_per_sec"])
        return good[len(good) // 2]

    before = median_run(befores)
    after = dict(median_run(afters))
    after["before"] = {
        k: before.get(k)
        for k in (
            "events_per_sec", "request_p50_ms", "request_p99_ms",
            "request_p100_ms", "fsyncs_total", "prepares_total",
            "fsyncs_per_prepare", "fastpath_decode", "native_pipeline",
            "native_drain",
            "decode_us_per_event_p50", "decode_us_per_event_p99",
            "reply_encode_us_p50", "fastpath_batch_decode_hits",
            "prepare_us_p50", "prepare_us_p99",
            "prepare_ok_us_p50", "prepare_ok_us_p99",
            "drain_native_calls", "drain_py_fallbacks",
            "error",
        )
        if k in before
    }
    if repeats > 1:
        after["repeats"] = repeats
        after["arm_events_per_sec"] = {
            "before": [r.get("events_per_sec") for r in befores],
            "after": [r.get("events_per_sec") for r in afters],
        }
    # Round 23 hash-once arm: the headline AFTER run already IS the
    # reuse-on configuration (TB_HASH_REUSE defaults on) and carries
    # the per-replica hash.* counters; one extra run pins reuse OFF so
    # the rehash-at-build cost is a graded same-session delta rather
    # than a cross-round comparison.
    reuse_off = _run_replicated_once(
        n_events, fastpath=True, native_pipeline=True,
        native_drain=True, hash_reuse=False,
    )
    after["hash_reuse_off"] = {
        k: reuse_off.get(k)
        for k in (
            "events_per_sec", "request_p50_ms", "request_p99_ms",
            "request_p100_ms", "hash_reuse", "hash_engine",
            "hash_threads", "per_replica_stats", "error",
        )
        if k in reuse_off
    }
    return after


def _run_replicated_once(n_events: int, group_commit: bool = True,
                         fastpath: bool = True,
                         native_pipeline: bool = True,
                         native_drain: bool = True,
                         hash_reuse: bool = True) -> dict:
    """3-replica TCP cluster, real ReplicaServer processes, driven by
    CONCURRENT client sessions (VERDICT r4 #1b): each VSR session keeps
    one request in flight (request numbers are strictly increasing,
    reference: src/vsr/client.zig), so filling the <=8-prepare commit
    pipeline (reference: src/config.zig:149) takes multiple sessions —
    this is how the reference's benchmark scales load too
    (src/tigerbeetle/benchmark_load.zig).  Prices ring replication +
    quorum prepare_oks + remote WAL sync on top of the durable
    single-replica path.

    Failure handling (the r4 lesson): the per-request timeout is 300 s
    (~90x the 3.3 s idle p100), and any failure returns an error dict
    carrying the replica log tails instead of raising — the graded JSON
    line must survive one bad config."""
    import shutil
    import socket
    import subprocess
    import tempfile
    import threading

    n_replicas = 3
    n_sessions = int(os.environ.get("BENCH_REPL_SESSIONS", 4))
    request_timeout_ms = int(os.environ.get("BENCH_REPL_TIMEOUT_MS", 300_000))
    tmp = tempfile.mkdtemp(prefix="tb_bench_repl_")
    ports = []
    socks = []
    for _ in range(n_replicas):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    addresses = ",".join(f"127.0.0.1:{p}" for p in ports)
    here = os.path.dirname(os.path.abspath(__file__))
    procs = []
    logs = []
    clients: list = []
    try:
        for i in range(n_replicas):
            path = os.path.join(tmp, f"0_{i}.tigerbeetle")
            subprocess.run(
                [
                    sys.executable, "-m", "tigerbeetle_tpu", "format",
                    "--cluster=12", f"--replica={i}",
                    f"--replica-count={n_replicas}", path,
                ],
                check=True, capture_output=True, cwd=here, timeout=120,
            )
        runner = (
            "import sys; sys.path.insert(0, {here!r})\n"
            "from tigerbeetle_tpu.runtime import affinity\n"
            "affinity.apply(slot={i})\n"
            "from tigerbeetle_tpu.runtime.server import ReplicaServer\n"
            "from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine\n"
            "s = ReplicaServer({path!r}, addresses={addrs!r}.split(','),\n"
            "    replica_index={i},\n"
            "    state_machine_factory=lambda: TpuStateMachine(\n"
            "        account_capacity=1 << 12,\n"
            "        transfer_capacity={cap}))\n"
            "print('listening', flush=True)\n"
            "s.serve_forever()\n"
        )
        log_paths = []
        server_env = dict(os.environ)
        if group_commit:
            server_env.pop("TB_GROUP_COMMIT_MAX_US", None)  # default (on)
            server_env["TB_CKPT_ASYNC"] = "1"
        else:
            # The r6 behavior: one fsync per prepare, synchronous
            # checkpoint flips.
            server_env["TB_GROUP_COMMIT_MAX_US"] = "0"
            server_env["TB_CKPT_ASYNC"] = "0"
        # Columnar ingest arm selector (round 14): 0 pins the legacy
        # per-message decode path for the differential "before" run.
        server_env["TB_FASTPATH_DECODE"] = "1" if fastpath else "0"
        # Native commit pipeline arm selector (round 20): 0 pins the
        # pure-Python per-prepare path for the "before" run.
        server_env["TB_NATIVE_PIPELINE"] = "1" if native_pipeline else "0"
        # C-resident drain arm selector (round 22): 0 pins the
        # per-item Python loop over the same batch seams, so the
        # differential isolates the one-call-per-drain batching.
        server_env["TB_NATIVE_DRAIN"] = "1" if native_drain else "0"
        # Hash-once arm selector (round 23): 0 pins the rehash-at-
        # build path so the reuse delta is a graded number.
        server_env["TB_HASH_REUSE"] = "1" if hash_reuse else "0"
        # Core pinning rides the environment into each replica's
        # runner (applied below via affinity.apply in-process); the
        # per-subprocess plan is recorded so regrades self-describe.
        from tigerbeetle_tpu.runtime import affinity

        pinned_cores = {
            f"replica{i}": affinity.plan(i) for i in range(n_replicas)
        }
        for i in range(n_replicas):
            path = os.path.join(tmp, f"0_{i}.tigerbeetle")
            # Output to FILES, not pipes: a replica chattering past the
            # ~64KiB pipe buffer during the run would block on write
            # and stall the whole cluster.
            log_path = os.path.join(tmp, f"replica{i}.log")
            log_paths.append(log_path)
            log = open(log_path, "w")
            logs.append(log)
            p = subprocess.Popen(
                [
                    sys.executable, "-c",
                    runner.format(
                        here=here, path=path, addrs=addresses, i=i,
                        cap=n_events + 2 * BATCH + 1024,
                    ),
                ],
                stdout=log, stderr=subprocess.STDOUT, cwd=here,
                env=server_env,
            )
            procs.append(p)
        deadline = time.time() + 120
        for i, lp in enumerate(log_paths):
            while time.time() < deadline:
                if procs[i].poll() is not None:
                    raise AssertionError(
                        f"replica {i} exited rc={procs[i].returncode}:\n"
                        + open(lp).read()[-2000:]
                    )
                try:
                    if "listening" in open(lp).read():
                        break
                except OSError:
                    pass
                time.sleep(0.5)
            else:
                raise AssertionError(
                    f"replica did not start: {lp}\n"
                    + open(lp).read()[-2000:]
                )

        from tigerbeetle_tpu.client import Client

        clients = [
            Client(addresses, 12, timeout_ms=request_timeout_ms)
            for _ in range(n_sessions)
        ]
        n_acct = 1_000
        ids = np.arange(1, n_acct + 1, dtype=np.uint64)
        acct = np.frombuffer(accounts_bytes(ids), dtype=ACCOUNT_DTYPE)
        reply = clients[0]._native.request(
            Operation.create_accounts, acct.tobytes(), request_timeout_ms
        )
        assert reply == b"", "replicated setup: account failures"

        rng = np.random.default_rng(47)
        dr = rng.integers(1, n_acct + 1, n_events, np.uint64)
        bodies = [
            b
            for _op, b in batched(
                {
                    "ids": np.arange(1, n_events + 1, dtype=np.uint64),
                    "dr": dr,
                    "cr": dr % np.uint64(n_acct) + np.uint64(1),
                    "amount": rng.integers(1, 100, n_events, np.uint64),
                }
            )
        ]
        # Deal batches round-robin across sessions: each session keeps
        # one request in flight, so n_sessions requests ride the VSR
        # pipeline concurrently (ctypes releases the GIL during the
        # blocking native call).
        lat_per = [[] for _ in range(n_sessions)]
        failed_per = [0] * n_sessions
        errors: list[str] = []

        def drive(s: int) -> None:
            client = clients[s]
            try:
                for body in bodies[s::n_sessions]:
                    b0 = time.perf_counter()
                    reply = client._native.request(
                        Operation.create_transfers, body, request_timeout_ms
                    )
                    lat_per[s].append(time.perf_counter() - b0)
                    failed_per[s] += len(reply) // 8
            except Exception as exc:  # noqa: BLE001
                errors.append(f"session {s}: {exc!r}")

        threads = [
            threading.Thread(target=drive, args=(s,), daemon=True)
            for s in range(n_sessions)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        # Let each server print its final TB_STATS line (the counters
        # are harvested from the log tail after the kill).
        time.sleep(2.5)
        failed = sum(failed_per)
        if errors or failed:
            tails = {}
            for i, lp in enumerate(log_paths):
                try:
                    tails[f"replica{i}"] = open(lp).read()[-1500:]
                except OSError:
                    pass
            return {
                "error": "; ".join(errors) or f"{failed} transfers failed",
                "events": n_events,
                "completed_batches": sum(len(v) for v in lat_per),
                "total_batches": len(bodies),
                "replica_log_tails": tails,
            }
        lat_ms = np.sort(np.concatenate([np.asarray(v) for v in lat_per])) * 1e3
        # Per-replica durability counters, scraped LIVE from each
        # server's registry over the `stats` wire op (obs/scrape.py) —
        # the TB_STATS log-tail parser survives only as the
        # counter-verified fallback for replicas that died (a kill -9
        # can't answer a scrape but did leave its last line behind).
        # When both sources exist they must agree: they render the
        # same registry.
        per_replica_stats, scrape_extra = _harvest_replica_stats(
            [f"127.0.0.1:{p}" for p in ports], log_paths, cluster=12
        )
        # .get(): a replica killed mid-print can leave a truncated
        # TB_STATS line — a missing key must not void the whole run.
        fsyncs_total = sum(
            s.get("fsyncs", 0) for s in per_replica_stats.values()
        )
        prepares_total = sum(
            s.get("prepares", 0) for s in per_replica_stats.values()
        )
        return {
            "events_per_sec": round(n_events / elapsed, 1),
            "events": n_events,
            "failed_events": failed,
            "vs_baseline": round(n_events / elapsed / BASELINE_TPS, 4),
            "engine": "host",
            "replicas": n_replicas,
            "client_sessions": n_sessions,
            "group_commit": group_commit,
            "fastpath_decode": fastpath,
            "native_pipeline": native_pipeline,
            "native_drain": native_drain,
            "hash_reuse": hash_reuse,
            "pinned_cores": pinned_cores,
            "per_replica_stats": per_replica_stats,
            **scrape_extra,
            "fsyncs_total": fsyncs_total,
            "prepares_total": prepares_total,
            "fsyncs_per_prepare": round(
                fsyncs_total / max(1, prepares_total), 3
            ),
            "device_semantic_pct": 0.0,
            "request_p50_ms": round(float(lat_ms[len(lat_ms) // 2]), 2),
            "request_p99_ms": round(float(lat_ms[int(len(lat_ms) * 0.99)]), 2),
            "request_p100_ms": round(float(lat_ms[-1]), 2),
            # Context for the absolute number: every replica executes
            # the full durable path (WAL fsync + LSM spill/compaction),
            # and this container exposes ONE CPU core (nproc=1), so
            # three replica processes + the clients serialize on it.
            "host_cores": os.cpu_count(),
        }
    finally:
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        for p in procs:
            p.kill()
        for log in logs:
            log.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _harvest_replica_stats(
    addresses: list[str], log_paths: list[str], cluster: int,
) -> tuple[dict, dict]:
    """Per-replica durability counters: registry scrape first (the
    `stats` wire op), TB_STATS log tail only as the fallback for dead
    replicas.  When both sources are available they MUST agree on the
    durability counters — they render the same registry; a mismatch
    means the observability spine itself is broken, which is exactly
    what this cross-check exists to catch.

    -> (per_replica_stats, extra_keys): per-replica dicts in the
    legacy key schema (fsyncs/prepares/gc_flushes/commit_min), plus
    top-level bench keys (stats_source, server-side commit
    percentiles from replica 0's scrape)."""
    from tigerbeetle_tpu.obs.scrape import scrape_stats

    per_replica: dict = {}
    sources: dict = {}
    extra: dict = {}
    for i, (addr, lp) in enumerate(zip(addresses, log_paths)):
        name = f"replica{i}"
        snap = None
        try:
            snap = scrape_stats(addr, cluster, timeout_ms=10_000)
        except (OSError, TimeoutError, ValueError):
            snap = None  # dead replica: log tail below
        if snap is not None:
            stats = {
                "fsyncs": int(snap.get("storage.fsyncs", 0)),
                "prepares": int(snap.get("vsr.prepares_written", 0)),
                "gc_flushes": int(snap.get("vsr.gc_flushes", 0)),
                "commit_min": int(snap.get("vsr.commit_min", 0)),
                "ckpt_async": int(snap.get("vsr.ckpt.async", 0)),
                # Round 23 hash forensics, per role: the reuse ratio
                # (bytes_hashed vs committed + dup) the TCP smoke
                # asserts, rendered here per bench row.
                "hash_bytes_hashed": int(
                    snap.get("vsr.hash.bytes_hashed", 0)
                ),
                "hash_reuse_hits": int(
                    snap.get("vsr.hash.reuse_hits", 0)
                ),
                "hash_committed_body_bytes": int(
                    snap.get("vsr.hash.committed_body_bytes", 0)
                ),
                "hash_dup_body_bytes": int(
                    snap.get("vsr.hash.dup_body_bytes", 0)
                ),
            }
            sources[name] = "scrape"
            # Cross-check vs the log tail (same registry, two
            # renderings).  The server prints at ~1 Hz on change, so
            # allow it a few beats to emit the final line.
            deadline = time.time() + 5.0
            log_stats = _parse_tb_stats(lp)
            while log_stats is not None and time.time() < deadline:
                if all(
                    log_stats.get(k, stats[k]) == stats[k]
                    for k in ("fsyncs", "prepares", "gc_flushes")
                ):
                    break
                time.sleep(1.0)
                log_stats = _parse_tb_stats(lp)
            if log_stats is not None:
                mismatch = {
                    k: (stats[k], log_stats[k])
                    for k in ("fsyncs", "prepares", "gc_flushes")
                    if k in log_stats and log_stats[k] != stats[k]
                }
                assert not mismatch, (
                    f"{name}: scrape and TB_STATS log tail disagree "
                    f"(scrape, log): {mismatch}"
                )
            if i == 0:
                extra["server_commit_p50_ms"] = round(
                    snap.get("vsr.commit_us.p50", 0.0) / 1e3, 2
                )
                extra["server_commit_p99_ms"] = round(
                    snap.get("vsr.commit_us.p99", 0.0) / 1e3, 2
                )
                extra["server_commit_p999_ms"] = round(
                    snap.get("vsr.commit_us.p999", 0.0) / 1e3, 2
                )
                extra["server_drain_msgs_p50"] = snap.get(
                    "server.drain_msgs.p50", 0.0
                )
                # Columnar ingest instruments (round 14): amortized
                # decode µs per 128B event, coalesced reply-encode µs,
                # and the batch-decode hit/fallback counters — the
                # graded "decode µs/event reported per config" numbers.
                extra["decode_us_per_event_p50"] = snap.get(
                    "server.decode_us_per_event.p50", 0.0
                )
                extra["decode_us_per_event_p99"] = snap.get(
                    "server.decode_us_per_event.p99", 0.0
                )
                extra["reply_encode_us_p50"] = snap.get(
                    "server.reply_encode_us.p50", 0.0
                )
                extra["fastpath_batch_decode_hits"] = int(
                    snap.get("fastpath.batch_decode_hits", 0)
                )
                extra["fastpath_batch_decode_fallbacks"] = int(
                    snap.get("fastpath.batch_decode_fallbacks", 0)
                )
                extra["fastpath_native_unavailable"] = int(
                    snap.get("fastpath.native_unavailable", 0)
                )
                # Round 23: which SHA-256 engine served this row (a
                # scalar-fallback number must never grade as SHA-NI)
                # and the lane configuration that produced it.
                extra["hash_engine"] = {
                    1: "evp", 2: "sha256-legacy", 3: "scalar",
                }.get(int(snap.get("hash.engine_code", 0)), "hashlib")
                extra["hash_scalar_fallback"] = int(
                    snap.get("hash.scalar_fallback", 0)
                )
                extra["hash_threads"] = int(snap.get("hash.threads", 0))
                extra["hash_lanes_busy"] = int(
                    snap.get("hash.lanes_busy", 0)
                )
                # Per-prepare Python wall time on the VSR hot path
                # (round 20): the spans the native pipeline replaces —
                # the primary's header build + checksum stamping +
                # pipeline bookkeeping.  The native arm is graded on
                # this collapsing vs the pure-Python arm (at heavy
                # group-commit coalescing the span is body-checksum
                # bound and converges; prepare_ok_us below is the
                # body-independent view).
                extra["prepare_us_p50"] = snap.get(
                    "vsr.prepare_us.p50", 0.0
                )
                extra["prepare_us_p99"] = snap.get(
                    "vsr.prepare_us.p99", 0.0
                )
            if i == 1:
                # Backup-side per-prepare instrument: the prepare_ok
                # build span — no body work at all, so this is the
                # purest Python-overhead-per-prepare number.
                extra["prepare_ok_us_p50"] = snap.get(
                    "vsr.prepare_ok_us.p50", 0.0
                )
                extra["prepare_ok_us_p99"] = snap.get(
                    "vsr.prepare_ok_us.p99", 0.0
                )
            # C-resident drain loop counters (round 22), summed across
            # replicas: native_calls counts whole drains retired in one
            # Python→C call, py_fallbacks counts per-item retreats to
            # the Python loop.  The drained arm is graded on
            # native_calls > 0 with py_fallbacks staying ~0.
            extra["drain_native_calls"] = extra.get(
                "drain_native_calls", 0
            ) + int(snap.get("vsr.drain.native_calls", 0))
            extra["drain_py_fallbacks"] = extra.get(
                "drain_py_fallbacks", 0
            ) + int(snap.get("vsr.drain.py_fallbacks", 0))
        else:
            stats = _parse_tb_stats(lp)
            sources[name] = "log_tail" if stats is not None else "missing"
        if stats is not None:
            per_replica[name] = stats
    extra["stats_source"] = sources
    return per_replica, extra


def _parse_tb_stats(log_path: str) -> dict | None:
    """Last TB_STATS counters line of a replica log (see
    runtime/server.py _print_stats), or None when the server never got
    far enough to print one."""
    try:
        lines = [
            ln for ln in open(log_path).read().splitlines()
            if ln.startswith("TB_STATS ")
        ]
    except OSError:
        return None
    if not lines:
        return None
    out = {}
    for part in lines[-1].split()[1:]:
        key, _, value = part.partition("=")
        try:
            out[key] = int(value)
        except ValueError:
            pass
    return out


def run_hash_only() -> dict:
    """SHA-256 engine x body-size x lane-count microbench grid (round
    23): GB/s through the REAL counted ingress path
    (tb_fp_verify_frames2 — the batch verify the server drain runs,
    which also opens a digest-table crossing per call), not a bare
    digest loop.  Every row records the engine that ACTUALLY served it
    (hash_engine_name() after configure): forcing "evp" on a box
    without libcrypto silently lands on "scalar", and a mislabeled
    engine would turn an 8x regression into a fake win.  The grid is
    the sizing evidence for TB_HASH_THREADS — lanes only pay above
    the per-job handoff cost, so small bodies should show lanes <=
    inline and 1MB bodies should show the fan-out."""
    from tigerbeetle_tpu.runtime import fastpath
    from tigerbeetle_tpu.vsr import wire

    if not fastpath.available():
        return {"error": "libtb_fastpath not built"}
    if fastpath.verify_frames2(
        np.zeros(256, np.uint8), np.zeros(1, np.uint64),
        np.zeros(1, np.uint32), 0,
    ) is None:
        return {"error": "libtb_fastpath lacks r23 hash symbols"}
    rng = np.random.default_rng(23)
    sizes = (128, 4096, 65536, 1 << 20)
    lanes_grid = (0, 2, 4)
    engines = ((1, "evp"), (2, "sha256-legacy"), (3, "scalar"))
    target = 24 << 20  # bytes hashed per timed rep
    rows = []
    try:
        for size in sizes:
            # One shared frame batch per size: k frames of `size`-byte
            # bodies, enough to amortize per-call setup and give the
            # lanes real fan-out (k >= 24 even at 1MB).
            k = max(24, min(512, target // max(size, 1)))
            frames = []
            for j in range(k):
                body = rng.bytes(size)
                h = wire.make_header(
                    command=wire.Command.prepare, cluster=23, op=j + 1,
                )
                wire.finalize_header(h, body)
                frames.append(h.tobytes() + body)
            blob = b"".join(frames)
            arena = np.frombuffer(blob, np.uint8)
            lens = np.array([len(f) for f in frames], np.uint32)
            offsets = np.zeros(k, np.uint64)
            np.cumsum(lens[:-1], out=offsets[1:])
            body_bytes = int(lens.sum()) - 256 * k
            for force, requested in engines:
                for lanes in lanes_grid:
                    assert fastpath.configure_hash(lanes, force)
                    actual = fastpath.hash_engine_name()
                    # Warm once (page-in + pool spin-up), then time
                    # enough reps for >= ~0.05 s of work.
                    ok, hashed = fastpath.verify_frames2(
                        arena, offsets, lens, k
                    )
                    assert int(np.asarray(ok).sum()) == k
                    assert hashed == body_bytes, (hashed, body_bytes)
                    reps = max(1, (8 << 20) // max(body_bytes, 1))
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        fastpath.verify_frames2(arena, offsets, lens, k)
                    dt = time.perf_counter() - t0
                    rows.append({
                        "engine_requested": requested,
                        "engine": actual,
                        "body_bytes": size,
                        "lanes": lanes,
                        "frames": k,
                        "reps": reps,
                        "gb_per_sec": round(
                            body_bytes * reps / dt / 1e9, 3
                        ),
                    })
    finally:
        # Back to the validated env config + auto engine — the grid
        # must not leak a forced scalar into later configs.
        fastpath.configure_hash(None, 0)
    stats = fastpath.hash_stats()
    return {
        "rows": rows,
        "engine_auto": fastpath.hash_engine_name(),
        "scalar_fallback": fastpath.hash_scalar_fallback(),
        "lane_jobs_total": stats["lane_jobs"],
        "host_cores": os.cpu_count(),
    }


def run_open_loop() -> dict:
    """Open-loop latency-under-load grading (ROADMAP "open-loop
    overload + multi-tenant scenario bench").

    Every closed-loop config waits for the last batch before sending
    the next, which hides queueing collapse; production traffic is
    open-loop and bursty.  This config measures a quick closed-loop
    capacity, then drives Poisson arrivals (plus per-second bursts at
    BENCH_OPEN_BURST x the rate and a BENCH_OPEN_HOT_PCT hot-account
    mix) at 50/80/95/120% of that capacity through OpenLoopSession
    clients (many requests in flight), grading p50/p99/p999 reply
    latency per sustained rate — the rate-vs-SLO curve — and, at 120%,
    that admission control sheds typed busy replies while the queue
    stays bounded (no unbounded tail growth)."""
    import shutil
    import socket
    import subprocess
    import tempfile
    import threading

    from tigerbeetle_tpu import envcheck

    phase_secs = envcheck.open_loop_secs()
    batch = envcheck.open_loop_batch()
    hot_pct = envcheck.open_loop_hot_pct()
    burst = envcheck.open_loop_burst()
    read_pct = envcheck.open_loop_read_pct()
    n_replicas = 2
    n_sessions = int(os.environ.get("BENCH_OPEN_SESSIONS", 4))
    tmp = tempfile.mkdtemp(prefix="tb_bench_open_")
    ports = []
    socks = []
    for _ in range(n_replicas):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    addresses = ",".join(f"127.0.0.1:{p}" for p in ports)
    here = os.path.dirname(os.path.abspath(__file__))
    procs = []
    logs = []
    sessions = []
    sync_clients = []
    try:
        for i in range(n_replicas):
            path = os.path.join(tmp, f"0_{i}.tigerbeetle")
            subprocess.run(
                [
                    sys.executable, "-m", "tigerbeetle_tpu", "format",
                    "--cluster=13", f"--replica={i}",
                    f"--replica-count={n_replicas}", path,
                ],
                check=True, capture_output=True, cwd=here, timeout=120,
            )
        runner = (
            "import sys; sys.path.insert(0, {here!r})\n"
            "from tigerbeetle_tpu.runtime.server import ReplicaServer\n"
            "from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine\n"
            "s = ReplicaServer({path!r}, addresses={addrs!r}.split(','),\n"
            "    replica_index={i},\n"
            "    state_machine_factory=lambda: TpuStateMachine(\n"
            "        account_capacity=1 << 12,\n"
            "        transfer_capacity=1 << 22))\n"
            "print('listening', flush=True)\n"
            "s.serve_forever()\n"
        )
        server_env = dict(os.environ)
        server_env.setdefault("TB_ADMIT_QUEUE", "64")
        admit_bound = int(server_env["TB_ADMIT_QUEUE"])
        log_paths = []
        for i in range(n_replicas):
            path = os.path.join(tmp, f"0_{i}.tigerbeetle")
            log_path = os.path.join(tmp, f"replica{i}.log")
            log_paths.append(log_path)
            log = open(log_path, "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [
                    sys.executable, "-c",
                    runner.format(here=here, path=path, addrs=addresses, i=i),
                ],
                stdout=log, stderr=subprocess.STDOUT, cwd=here,
                env=server_env,
            ))
        deadline = time.time() + 120
        for i, lp in enumerate(log_paths):
            while time.time() < deadline:
                if procs[i].poll() is not None:
                    raise AssertionError(
                        f"replica {i} exited rc={procs[i].returncode}:\n"
                        + open(lp).read()[-2000:]
                    )
                try:
                    if "listening" in open(lp).read():
                        break
                except OSError:
                    pass
                time.sleep(0.5)
            else:
                raise AssertionError(f"replica did not start: {lp}")

        from tigerbeetle_tpu.client import Client, OpenLoopSession
        from tigerbeetle_tpu.obs.scrape import scrape_stats

        n_acct = 1_000
        n_hot = 4  # celebrity accounts taking hot_pct% of transfers
        setup = Client(addresses, 13, timeout_ms=120_000)
        sync_clients.append(setup)
        ids = np.arange(1, n_acct + 1, dtype=np.uint64)
        reply = setup._native.request(
            Operation.create_accounts, accounts_bytes(ids), 120_000
        )
        assert reply == b"", "open-loop setup: account failures"
        rng = np.random.default_rng(53)
        tid_next = [1]

        def make_body(n: int) -> bytes:
            tids = np.arange(
                tid_next[0], tid_next[0] + n, dtype=np.uint64
            )
            tid_next[0] += n
            dr = rng.integers(n_hot + 1, n_acct + 1, n, np.uint64)
            cr = rng.integers(n_hot + 1, n_acct + 1, n, np.uint64)
            hot = rng.random(n) < hot_pct / 100.0
            cr[hot] = rng.integers(1, n_hot + 1, int(hot.sum()), np.uint64)
            same = dr == cr
            cr[same] = dr[same] % np.uint64(n_acct) + np.uint64(1)
            return transfers_bytes(
                tids, dr, cr, rng.integers(1, 100, n, np.uint64)
            )

        # Read-heavy mix (BENCH_OPEN_READ_PCT): lookup_accounts id
        # batches with the same hot-account skew, plus a sprinkle of
        # AccountFilter queries over the hot accounts (the committed
        # scan path) — interleaved with the transfer stream so the
        # rate-vs-SLO curves price a realistic read/write mix.
        def make_read() -> tuple:
            if rng.random() < 0.15:
                row = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
                types.u128_set(
                    row, "account_id", int(rng.integers(1, n_hot + 1))
                )
                row["limit"] = 128
                row["flags"] = (types.AccountFilterFlags.debits
                                | types.AccountFilterFlags.credits)
                return Operation.get_account_transfers, row.tobytes()
            n = max(1, batch // 4)
            ids = rng.integers(n_hot + 1, n_acct + 1, n, np.uint64)
            hot = rng.random(n) < hot_pct / 100.0
            ids[hot] = rng.integers(1, n_hot + 1, int(hot.sum()),
                                    np.uint64)
            arr = np.zeros(n, dtype=types.U128_PAIR_DTYPE)
            arr["lo"] = ids
            return Operation.lookup_accounts, arr.tobytes()

        def submit_one(session) -> None:
            # Reads ride ON TOP of the transfer stream (additive, not
            # substitutive): the write arrival rate — and therefore
            # achieved_eps vs offered_eps and comparability with prior
            # BENCH_r*.json open_loop rows — is unchanged; the read
            # mix adds BENCH_OPEN_READ_PCT% extra requests.
            session.submit(Operation.create_transfers, make_body(batch))
            if rng.random() < read_pct / 100.0:
                op, body = make_read()
                session.submit(op, body)

        # -- closed-loop capacity probe: two sync sessions, ~2 s ------
        # Untimed warmup first: JIT compiles and page-cache fill must
        # not depress the measured capacity (every open-loop rate is a
        # fraction of it).
        for _ in range(3):
            setup._native.request(
                Operation.create_transfers, make_body(batch), 120_000
            )
        cap_secs = float(os.environ.get("BENCH_OPEN_CAP_SECS", 2.0))
        done = []
        lock = threading.Lock()

        def cap_drive():
            c = Client(addresses, 13, timeout_ms=120_000)
            sync_clients.append(c)
            with lock:
                body = make_body(batch)
            t_end = time.perf_counter() + cap_secs
            n = 0
            while time.perf_counter() < t_end:
                c._native.request(Operation.create_transfers, body, 120_000)
                with lock:
                    body = make_body(batch)
                n += batch
            done.append(n)

        threads = [threading.Thread(target=cap_drive, daemon=True)
                   for _ in range(2)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        capacity_eps = sum(done) / (time.perf_counter() - t0)

        # -- open-loop phases -----------------------------------------
        phases = {}
        for frac in (0.5, 0.8, 0.95, 1.2):
            target_eps = capacity_eps * frac
            req_rate = max(0.5, target_eps / batch)
            for s in sessions:
                s.completed.clear()
            if not sessions:
                sessions.extend(
                    OpenLoopSession(f"127.0.0.1:{ports[0]}", 13, 0x0BE0 + k)
                    for k in range(n_sessions)
                )
            t_start = time.perf_counter()
            t_end = t_start + phase_secs
            next_arrival = t_start
            next_burst = t_start + 1.0
            next_scrape = t_start
            sent = 0
            queue_depth_max = 0
            rr = 0
            while time.perf_counter() < t_end:
                now = time.perf_counter()
                while next_arrival <= now:
                    submit_one(sessions[rr % n_sessions])
                    rr += 1
                    sent += 1
                    next_arrival += float(rng.exponential(1.0 / req_rate))
                if burst > 1.0 and now >= next_burst:
                    # Burst: 5% of a second's volume lands at once,
                    # (burst-1)x over the Poisson baseline.
                    next_burst += 1.0
                    extra = int((burst - 1.0) * req_rate * 0.05)
                    for _ in range(extra):
                        submit_one(sessions[rr % n_sessions])
                        rr += 1
                        sent += 1
                for s in sessions:
                    s.poll(0)
                if now >= next_scrape:
                    next_scrape = now + 0.3
                    try:
                        snap = scrape_stats(
                            f"127.0.0.1:{ports[0]}", 13, timeout_ms=5_000
                        )
                        queue_depth_max = max(
                            queue_depth_max,
                            int(snap.get("server.queue_depth", 0)),
                        )
                    except (OSError, TimeoutError, ValueError):
                        pass
                time.sleep(0.001)
            # Grace drain: let queued work finish (bounded).
            grace = time.perf_counter() + max(10.0, 2 * phase_secs)
            while time.perf_counter() < grace and any(
                s.inflight for s in sessions
            ):
                for s in sessions:
                    s.poll(10)
            elapsed = time.perf_counter() - t_start
            write_op = int(Operation.create_transfers)
            lats = sorted(
                lat for s in sessions
                for (_r, kind, lat, _b, _op, _t) in s.completed
                if kind == "reply"
            )
            write_lats = sorted(
                lat for s in sessions
                for (_r, kind, lat, _b, op, _t) in s.completed
                if kind == "reply" and op == write_op
            )
            read_lats = sorted(
                lat for s in sessions
                for (_r, kind, lat, _b, op, _t) in s.completed
                if kind == "reply" and op != write_op
            )
            busy = sum(
                1 for s in sessions
                for (_r, kind, _l, _b, _op, _t) in s.completed
                if kind == "busy"
            )
            replied = len(lats)
            unresolved = sum(len(s.inflight) for s in sessions)
            for s in sessions:
                s.inflight.clear()  # abandoned; report honestly

            def pct(q, xs=None):
                xs = lats if xs is None else xs
                if not xs:
                    return None
                return round(xs[min(len(xs) - 1,
                                    int(q * len(xs)))] * 1e3, 2)

            phases[f"{int(frac * 100)}pct"] = {
                "offered_eps": round(target_eps, 1),
                "achieved_eps": round(
                    len(write_lats) * batch / elapsed, 1
                ),
                "requests_sent": sent,
                "requests_replied": replied,
                "busy_replies": busy,
                "unresolved": unresolved,
                "p50_ms": pct(0.50),
                "p99_ms": pct(0.99),
                "p999_ms": pct(0.999),
                # Read/write split (BENCH_OPEN_READ_PCT mix): reads
                # ride the same sessions, so overload pricing covers
                # both sides of the mix.
                "reads_replied": len(read_lats),
                "read_p50_ms": pct(0.50, read_lats),
                "read_p99_ms": pct(0.99, read_lats),
                "write_p99_ms": pct(0.99, write_lats),
                "queue_depth_max": queue_depth_max,
            }

        # Post-run forensics from the primary's registry.
        extra = {}
        try:
            snap = scrape_stats(f"127.0.0.1:{ports[0]}", 13,
                                timeout_ms=10_000)
            extra = {
                "shed_total": int(snap.get("server.shed", 0)),
                "admit_queue": int(snap.get("server.admit_queue", 0)),
                "exemplars_scraped": len(
                    snap.get("anatomy.exemplars", [])
                ),
                "anatomy_e2e_p99_ms": round(
                    snap.get("vsr.anatomy.e2e_us.p99", 0.0) / 1e3, 2
                ),
                # Columnar ingest instruments (round 14) — the
                # open-loop mix is where small frames make the
                # per-drain amortization visible.
                "decode_us_per_event_p50": snap.get(
                    "server.decode_us_per_event.p50", 0.0
                ),
                "decode_us_per_event_p99": snap.get(
                    "server.decode_us_per_event.p99", 0.0
                ),
                "reply_encode_us_p50": snap.get(
                    "server.reply_encode_us.p50", 0.0
                ),
                "fastpath_batch_decode_hits": int(
                    snap.get("fastpath.batch_decode_hits", 0)
                ),
                "fastpath_batch_decode_fallbacks": int(
                    snap.get("fastpath.batch_decode_fallbacks", 0)
                ),
            }
        except (OSError, TimeoutError, ValueError):
            pass
        over = phases.get("120pct", {})
        return {
            "capacity_eps": round(capacity_eps, 1),
            "batch_events": batch,
            "hot_account_pct": hot_pct,
            "read_pct": read_pct,
            "burst_multiplier": burst,
            "phase_secs": phase_secs,
            "sessions": n_sessions,
            "replicas": n_replicas,
            "phases": phases,
            # The overload verdict: bounded queue + visible shedding.
            "queue_bounded_at_120": (
                over.get("queue_depth_max", 0) <= admit_bound
            ),
            "host_cores": os.cpu_count(),
            **extra,
        }
    finally:
        for s in sessions:
            try:
                s.close()
            except Exception:
                pass
        for c in sync_clients:
            try:
                c.close()
            except Exception:
                pass
        for p in procs:
            p.kill()
        for log in logs:
            log.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_read_scale() -> dict:
    """Read scale-out grading (round 19): read throughput vs follower
    count while write p99 stays flat.

    A 2-replica cluster (replica 0 writing an AOF) serves a fixed
    open-loop write stream; arms add 0 / 1 / 2 / 4 root-attested
    follower processes and point a saturating lookup driver at them
    (the 0-follower baseline drives the same reads at the primary).
    Per arm: read rows/s, write p99, the share of reads actually
    served by followers (attested tier from the reply carve-out), and
    follower redirect/refusal counters.  Grades:

    - read_scaling_4f: reads/s at 4 followers over the primary-only
      baseline (on this 2-core container every follower competes with
      the replicas for CPU — recorded honestly, multi-core re-grade
      rides the usual carry-over).
    - write_p99_flat: max over follower arms of write p99 / baseline
      write p99 <= 2.0.
    - attested: every follower-served completion carried a nonzero
      (root, commit_min) attestation.
    """
    import shutil
    import socket
    import subprocess
    import tempfile

    from tigerbeetle_tpu import envcheck

    phase_secs = envcheck.read_scale_secs()
    write_rps = float(os.environ.get("BENCH_READ_SCALE_WRITE_RPS", 6.0))
    batch = 128
    read_ids = 64
    inflight_per_session = 4
    n_replicas = 2
    tmp = tempfile.mkdtemp(prefix="tb_bench_rdscale_")
    here = os.path.dirname(os.path.abspath(__file__))
    ports = []
    socks = []
    for _ in range(n_replicas):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    addresses = ",".join(f"127.0.0.1:{p}" for p in ports)
    aof_path = os.path.join(tmp, "r0.aof")
    procs = []
    followers = []  # (proc, port, log_path)
    logs = []
    sessions = []
    sync_clients = []

    def _wait_listening(proc, log_path, marker, deadline_s=120):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"process exited rc={proc.returncode}:\n"
                    + open(log_path).read()[-2000:]
                )
            try:
                text = open(log_path).read()
            except OSError:
                text = ""
            if marker in text:
                return text
            time.sleep(0.2)
        raise AssertionError(f"no '{marker}' in {log_path}")

    def _spawn_follower(fid):
        log_path = os.path.join(tmp, f"follower{fid}.log")
        log = open(log_path, "w")
        logs.append(log)
        p = subprocess.Popen(
            [
                sys.executable, "-m", "tigerbeetle_tpu", "follower",
                "--listen=127.0.0.1:0", f"--aof={aof_path}",
                f"--upstream=127.0.0.1:{ports[0]}", "--cluster=13",
                f"--id={fid}",
            ],
            stdout=log, stderr=subprocess.STDOUT, cwd=here,
            # Generous staleness for the THROUGHPUT arms: scaling is
            # what this config grades; a follower a few hundred ops
            # behind serving attested-stale reads is the intended
            # under-load behavior (the refusal correctness story is
            # the VOPR's job, not the bench's).
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     TB_READ_STALENESS_OPS=os.environ.get(
                         "TB_READ_STALENESS_OPS", "65536")),
        )
        text = _wait_listening(p, log_path, "follower listening on port")
        port = int(text.rsplit("port", 1)[1].split()[0])
        followers.append((p, port, log_path))
        return port

    try:
        for i in range(n_replicas):
            path = os.path.join(tmp, f"0_{i}.tigerbeetle")
            subprocess.run(
                [
                    sys.executable, "-m", "tigerbeetle_tpu", "format",
                    "--cluster=13", f"--replica={i}",
                    f"--replica-count={n_replicas}", path,
                ],
                check=True, capture_output=True, cwd=here, timeout=120,
            )
        runner = (
            "import sys; sys.path.insert(0, {here!r})\n"
            "from tigerbeetle_tpu.runtime.server import ReplicaServer\n"
            "from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine\n"
            "s = ReplicaServer({path!r}, addresses={addrs!r}.split(','),\n"
            "    replica_index={i},\n"
            "    aof_path={aof!r} if {i} == 0 else None,\n"
            "    state_machine_factory=lambda: TpuStateMachine(\n"
            "        account_capacity=1 << 12,\n"
            "        transfer_capacity=1 << 22))\n"
            "print('listening', flush=True)\n"
            "s.serve_forever()\n"
        )
        for i in range(n_replicas):
            path = os.path.join(tmp, f"0_{i}.tigerbeetle")
            log_path = os.path.join(tmp, f"replica{i}.log")
            log = open(log_path, "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [
                    sys.executable, "-c",
                    runner.format(here=here, path=path, addrs=addresses,
                                  i=i, aof=aof_path),
                ],
                stdout=log, stderr=subprocess.STDOUT, cwd=here,
                env=dict(os.environ),
            ))
            _wait_listening(procs[-1], log_path, "listening")

        from tigerbeetle_tpu.client import Client, OpenLoopSession
        from tigerbeetle_tpu.obs.scrape import scrape_stats

        n_acct = 1_000
        setup = Client(addresses, 13, timeout_ms=120_000)
        sync_clients.append(setup)
        ids = np.arange(1, n_acct + 1, dtype=np.uint64)
        reply = setup._native.request(
            Operation.create_accounts, accounts_bytes(ids), 120_000
        )
        assert reply == b"", "read-scale setup: account failures"
        rng = np.random.default_rng(91)
        tid_next = [1]

        def write_body() -> bytes:
            tids = np.arange(tid_next[0], tid_next[0] + batch,
                             dtype=np.uint64)
            tid_next[0] += batch
            dr = rng.integers(1, n_acct + 1, batch, np.uint64)
            cr = rng.integers(1, n_acct + 1, batch, np.uint64)
            same = dr == cr
            cr[same] = dr[same] % np.uint64(n_acct) + np.uint64(1)
            return transfers_bytes(
                tids, dr, cr, rng.integers(1, 100, batch, np.uint64)
            )

        def read_body() -> bytes:
            arr = np.zeros(read_ids, dtype=types.U128_PAIR_DTYPE)
            arr["lo"] = rng.integers(1, n_acct + 1, read_ids, np.uint64)
            return arr.tobytes()

        # Warm the device path before any timed arm.
        for _ in range(3):
            setup._native.request(
                Operation.create_transfers, write_body(), 120_000
            )

        def _wait_attested(fport, log_path, deadline_s=120):
            """Wait until the follower has attested AND replayed the
            standing backlog (lag < 256) — an arm that starts against
            followers deep in catch-up measures replay contention,
            not read serving."""
            deadline = time.time() + deadline_s
            snap = {}
            while time.time() < deadline:
                try:
                    snap = scrape_stats(f"127.0.0.1:{fport}", 13,
                                        timeout_ms=5_000)
                    if snap.get("follower.attested_op", 0) > 0 and (
                        snap.get("follower.lag_ops", 1 << 30) < 256
                    ):
                        return snap
                except (OSError, TimeoutError, ValueError):
                    pass
                time.sleep(0.2)
            raise AssertionError(
                f"follower :{fport} never caught up; last snap "
                f"{ {k: v for k, v in snap.items() if k.startswith('follower.')} }; "
                "log tail:\n" + open(log_path).read()[-2000:]
            )

        def run_arm(read_ports: list[int], label: str) -> dict:
            """One arm: open-loop writes at the primary + saturating
            reads across `read_ports` (primary port = baseline)."""
            wsess = OpenLoopSession(f"127.0.0.1:{ports[0]}", 13,
                                    0xBE00 + len(read_ports))
            rsess = [
                OpenLoopSession(f"127.0.0.1:{p}", 13,
                                0xCE00 + 16 * len(read_ports) + k)
                for k, p in enumerate(read_ports)
            ]
            # Redirect target: follower refusals re-drive here.
            psess = OpenLoopSession(f"127.0.0.1:{ports[0]}", 13,
                                    0xDE00 + len(read_ports))
            sessions.extend([wsess, psess] + rsess)
            t_start = time.perf_counter()
            t_end = t_start + phase_secs
            next_write = t_start
            redirects = 0
            per_session_inflight = {id(s): 0 for s in rsess}
            while time.perf_counter() < t_end:
                now = time.perf_counter()
                while next_write <= now:
                    wsess.submit(Operation.create_transfers, write_body())
                    next_write += float(rng.exponential(1.0 / write_rps))
                for s in rsess:
                    while per_session_inflight[id(s)] < inflight_per_session:
                        s.submit(Operation.lookup_accounts, read_body())
                        per_session_inflight[id(s)] += 1
                wsess.poll(0)
                psess.poll(0)
                for s in rsess:
                    s.poll(0)
                    done = s.completed
                    if done:
                        per_session_inflight[id(s)] -= len(done)
                        for (_r, kind, _l, _b, _op, _t) in done:
                            if kind == "busy":
                                # Follower refusal: redirect to the
                                # primary (the router's fallback,
                                # driven client-side here).
                                redirects += 1
                                psess.submit(
                                    Operation.lookup_accounts,
                                    read_body(),
                                )
                        s.stats_bucket = getattr(s, "stats_bucket", [])
                        s.stats_bucket.extend(done)
                        s.completed = []
                time.sleep(0.0005)
            elapsed = time.perf_counter() - t_start
            # Drain stragglers (bounded).
            grace = time.perf_counter() + 10.0
            while time.perf_counter() < grace and (
                wsess.inflight or psess.inflight
                or any(s.inflight for s in rsess)
            ):
                wsess.poll(5)
                psess.poll(5)
                for s in rsess:
                    s.poll(5)
            read_done = [
                c for s in rsess for c in getattr(s, "stats_bucket", [])
            ] + [c for s in rsess for c in s.completed]
            read_ok = [c for c in read_done if c[1] == "reply"]
            follower_served = [
                c for c in read_ok if c[5][0] == "follower"
            ]
            # Non-vacuous attestation check: the tier classification
            # already requires a nonzero carve-out, so the real test
            # is verifying a SAMPLED claim against the primary's root
            # ring (what a verifying client would do).  None = the
            # primary no longer retained the op (recorded, not
            # graded); False = attestation mismatch (grade fails).
            attestation_verified = None
            if follower_served:
                _t, _fid, claim_op, claim_root = follower_served[-1][5]
                try:
                    from tigerbeetle_tpu.obs.scrape import (
                        scrape_state_root,
                    )

                    proot, pop = scrape_state_root(
                        f"127.0.0.1:{ports[0]}", 13,
                        timeout_ms=10_000, at_op=claim_op,
                    )
                    if pop == claim_op:
                        attestation_verified = proot == claim_root
                except (OSError, TimeoutError, ValueError):
                    pass
            unattested = [
                c for c in follower_served
                if c[5][2] <= 0 or c[5][3] == b""
            ]
            p_reads = [c for c in psess.completed if c[1] == "reply"]
            write_lats = sorted(
                lat for (_r, kind, lat, _b, _op, _t) in wsess.completed
                if kind == "reply"
            )
            for s in [wsess, psess] + rsess:
                s.inflight.clear()

            def pct(xs, q):
                if not xs:
                    return None
                return round(xs[min(len(xs) - 1, int(q * len(xs)))] * 1e3, 2)

            return {
                "label": label,
                "reads_per_sec": round(
                    (len(read_ok) + len(p_reads)) / elapsed, 1
                ),
                "read_rows_per_sec": round(
                    (len(read_ok) + len(p_reads)) * read_ids / elapsed, 1
                ),
                "follower_served": len(follower_served),
                "primary_served": (
                    len(read_ok) - len(follower_served) + len(p_reads)
                ),
                "redirects": redirects,
                "unattested_follower_replies": len(unattested),
                "attestation_verified": attestation_verified,
                "writes_replied": len(write_lats),
                "write_p50_ms": pct(write_lats, 0.50),
                "write_p99_ms": pct(write_lats, 0.99),
            }

        arms = {}
        arms["0f"] = run_arm([ports[0]], "primary_only")
        for fcount in (1, 2, 4):
            while len(followers) < fcount:
                fport = _spawn_follower(len(followers))
                _wait_attested(fport, followers[-1][2])
            for _p, fport, flog in followers[:fcount]:
                # Surviving followers lag by the previous arm's
                # writes: let them drain before the timed phase.
                _wait_attested(fport, flog)
            arms[f"{fcount}f"] = run_arm(
                [port for _p, port, _l in followers[:fcount]],
                f"{fcount}_followers",
            )
        # Post-run follower forensics (first follower's counters).
        extra = {}
        try:
            snap = scrape_stats(f"127.0.0.1:{followers[0][1]}", 13,
                                timeout_ms=5_000)
            extra = {
                "follower_lag_ops": int(snap.get("follower.lag_ops", 0)),
                "follower_served_total": int(
                    snap.get("follower.served", 0)
                ),
                "follower_redirects": int(
                    snap.get("follower.redirects", 0)
                ),
                "follower_refused": int(snap.get("follower.refused", 0)),
                "follower_attest_ok": int(
                    snap.get("follower.attest_ok", 0)
                ),
            }
        except (OSError, TimeoutError, ValueError):
            pass
        base = arms["0f"]
        f4 = arms["4f"]
        base_p99 = base.get("write_p99_ms") or 0.0
        worst_p99 = max(
            (arms[k].get("write_p99_ms") or 0.0) for k in ("1f", "2f", "4f")
        )
        # The grade: every follower arm actually served from a
        # follower, nothing unattested slipped through, AND at least
        # one arm's sampled claim verified against the primary's ring
        # (a regression that stops stamping attestations would drop
        # follower_share to 0 and fail here, not pass vacuously).
        attested = all(
            arms[k]["unattested_follower_replies"] == 0
            and arms[k]["follower_served"] > 0
            for k in ("1f", "2f", "4f")
        ) and any(
            arms[k]["attestation_verified"] is True
            for k in ("1f", "2f", "4f")
        )
        return {
            "phase_secs": phase_secs,
            "write_rps": write_rps,
            "batch_events": batch,
            "read_ids_per_lookup": read_ids,
            "arms": arms,
            "read_scaling_4f": round(
                f4["read_rows_per_sec"]
                / max(1.0, base["read_rows_per_sec"]), 2
            ),
            "write_p99_ratio_worst": (
                round(worst_p99 / base_p99, 2) if base_p99 else None
            ),
            "write_p99_flat": bool(
                base_p99 and worst_p99 / base_p99 <= 2.0
            ),
            "attested": attested,
            "follower_share_4f": round(
                f4["follower_served"]
                / max(1, f4["follower_served"] + f4["primary_served"]), 3
            ),
            "host_cores": os.cpu_count(),
            **extra,
        }
    finally:
        for s in sessions:
            try:
                s.close()
            except Exception:
                pass
        for c in sync_clients:
            try:
                c.close()
            except Exception:
                pass
        for p, _port, _log in followers:
            p.kill()
        for p in procs:
            p.kill()
        for log in logs:
            log.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_qos_suite() -> dict:
    """Adversarial multi-tenant QoS scenario suite (round 16).

    Three arms, each graded on ISOLATION: the victim tenant's p99
    with the adversary present must stay within 25% of its solo-run
    p99 at the same victim rate (the ROADMAP grade), while the
    adversary drives 5x its fair share.

    - noisy_neighbor: the hot tenant (ledger 1) drives 5x its fair
      share with a Zipf-hot account mix; the victim (ledger 2) runs
      at its share.  Per-tenant token buckets cap the hot tenant's
      admitted rate, the per-tenant queue bound caps its backlog, and
      the weighted-fair drain keeps the victim's queued requests from
      waiting behind the flood.
    - contention: the adversary (ledger 3) hammers ONE credit account
      — serial row-dependency chains, the pathological wave shape —
      while the victim (ledger 4) runs spread traffic at its share.
    - cross_shard: through the r13 2PC router — the adversary
      (ledger 1) is cross-shard-heavy (every transfer is a full 2PC),
      the victim (ledger 2) strictly shard-local; the ROUTER's
      tenant-keyed open-slot admission is the isolation mechanism.

    Per-arm JSON carries victim solo/combined p99, the isolation
    ratio + grade, and the per-tenant admit/shed counters scraped
    from the live registries (vsr.qos.t<ledger>.*, router.qos.*)."""
    import shutil
    import socket
    import subprocess
    import tempfile
    import threading

    from tigerbeetle_tpu import envcheck

    phase_secs = envcheck.qos_suite_secs()
    batch = int(os.environ.get("BENCH_QOS_BATCH", 64))
    cluster_id = 29
    tmp = tempfile.mkdtemp(prefix="tb_bench_qos_")
    here = os.path.dirname(os.path.abspath(__file__))
    procs: list = []
    logs: list = []
    clients: list = []
    sessions: list = []
    tid_next = [1]
    out: dict = {
        "phase_secs": phase_secs, "batch_events": batch,
        "hot_offered_x_share": 5.0, "isolation_bound": 1.25,
        "host_cores": os.cpu_count(),
    }

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def wait_listening(proc, log_path, what):
        deadline = time.time() + 120
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"{what} exited rc={proc.returncode}:\n"
                    + open(log_path).read()[-2000:]
                )
            try:
                if "listening" in open(log_path).read():
                    return
            except OSError:
                pass
            time.sleep(0.3)
        raise AssertionError(f"{what} did not start: {log_path}")

    def boot_replica(tag: str, port: int, extra_env: dict):
        path = os.path.join(tmp, f"{tag}.tigerbeetle")
        subprocess.run(
            [
                sys.executable, "-m", "tigerbeetle_tpu", "format",
                f"--cluster={cluster_id}", "--replica=0",
                "--replica-count=1", path,
            ],
            check=True, capture_output=True, cwd=here, timeout=120,
        )
        runner = (
            "import sys; sys.path.insert(0, {here!r})\n"
            "from tigerbeetle_tpu.runtime.server import ReplicaServer\n"
            "from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine\n"
            "s = ReplicaServer({path!r}, addresses=['127.0.0.1:{port}'],\n"
            "    replica_index=0,\n"
            "    state_machine_factory=lambda: TpuStateMachine(\n"
            "        account_capacity=1 << 12,\n"
            "        transfer_capacity=1 << 22))\n"
            "print('listening', flush=True)\n"
            "s.serve_forever()\n"
        ).format(here=here, path=path, port=port)
        env = dict(os.environ)
        env.update(extra_env)
        log_path = os.path.join(tmp, f"{tag}.log")
        log = open(log_path, "w")
        logs.append(log)
        p = subprocess.Popen(
            [sys.executable, "-c", runner], stdout=log,
            stderr=subprocess.STDOUT, cwd=here, env=env,
        )
        procs.append(p)
        wait_listening(p, log_path, tag)
        return p

    def make_spread(rng, pool, n):
        tids = np.arange(tid_next[0], tid_next[0] + n, dtype=np.uint64)
        tid_next[0] += n
        dr = rng.choice(pool, n)
        cr = rng.choice(pool, n)
        same = dr == cr
        cr[same] = np.where(dr[same] == pool[0], pool[1], pool[0])
        return tids, dr, cr

    def pct(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(q * len(xs)))] * 1e3, 2)

    def drive_open_loop(specs, secs):
        """specs: (session, ledger, req_rate, body_fn).  Poisson per
        spec; returns {ledger: {"lats": [...], "busy": n, "sent": n}}.
        `busy` counts typed busy REPLIES received (the backoff path
        retries them, so most never surface as completions)."""
        rng = np.random.default_rng(97)
        stats = {
            ledger: {"lats": [], "busy": 0, "sent": 0}
            for _s, ledger, _r, _f in specs
        }
        busy0 = {id(s): s.busy_replies for s, _l, _r, _f in specs}
        for s, _ledger, _r, _f in specs:
            s.completed.clear()
        t0 = time.perf_counter()
        t_end = t0 + secs
        arrivals = [t0 for _ in specs]
        while time.perf_counter() < t_end:
            now = time.perf_counter()
            for i, (s, ledger, rate, body_fn) in enumerate(specs):
                while arrivals[i] <= now:
                    s.submit(
                        Operation.create_transfers, body_fn(),
                        tenant=ledger,
                    )
                    stats[ledger]["sent"] += 1
                    arrivals[i] += float(rng.exponential(1.0 / rate))
            for s, _ledger, _r, _f in specs:
                s.poll(0)
            time.sleep(0.001)
        grace = time.perf_counter() + max(10.0, 2 * secs)
        while time.perf_counter() < grace and any(
            s.inflight for s, _l, _r, _f in specs
        ):
            for s, _l, _r, _f in specs:
                s.poll(10)
        # Settle: the phase's server-side backlog must not drain into
        # the NEXT phase's window (a combined phase's residue would
        # pollute the following solo baseline).
        settle = time.perf_counter() + 8.0
        while time.perf_counter() < settle:
            try:
                snap = scrape_stats(addr, cluster_id, timeout_ms=3_000)
                if int(snap.get("server.queue_depth", 0)) == 0:
                    break
            except (OSError, TimeoutError, ValueError):
                pass
            time.sleep(0.2)
        for s, ledger, _r, _f in specs:
            for (_req, kind, lat, _b, _op, _t) in s.completed:
                if kind == "reply":
                    stats[ledger]["lats"].append(lat)
            stats[ledger]["busy"] += s.busy_replies - busy0[id(s)]
            s.inflight.clear()
            s.completed.clear()
        return stats

    def tenant_counters(snap, scope, ledgers):
        return {
            f"t{ledger}": {
                "admit": int(snap.get(f"{scope}.t{ledger}.admit", 0)),
                "shed": int(snap.get(f"{scope}.t{ledger}.shed", 0)),
            }
            for ledger in ledgers
        }

    try:
        from tigerbeetle_tpu.client import Client, OpenLoopSession
        from tigerbeetle_tpu.obs.scrape import scrape_stats

        # -- capacity probe: unrated server, closed loop ~1.5 s -------
        port = free_port()
        probe = boot_replica("probe", port, {"TB_TENANT_QOS": "0"})
        addr = f"127.0.0.1:{port}"
        setup = Client(addr, cluster_id, timeout_ms=120_000)
        clients.append(setup)
        n_acct = 256
        pools = {}
        for ledger in (1, 2, 3, 4):
            ids = np.arange(
                ledger * 10_000 + 1, ledger * 10_000 + n_acct + 1,
                dtype=np.uint64,
            )
            reply = setup._native.request(
                Operation.create_accounts,
                accounts_bytes(ids, ledger=ledger), 120_000,
            )
            assert reply == b"", "qos setup: account failures"
            pools[ledger] = ids
        rng = np.random.default_rng(43)
        for _ in range(3):  # untimed warmup (JIT)
            tids, dr, cr = make_spread(rng, pools[1], batch)
            setup._native.request(
                Operation.create_transfers,
                transfers_bytes(tids, dr, cr,
                                rng.integers(1, 100, batch, np.uint64),
                                ledger=1),
                120_000,
            )
        cap_secs = float(os.environ.get("BENCH_QOS_CAP_SECS", 1.5))
        # Best of two windows: every rate below is a fraction of this
        # number, and a single window on this box can undershoot 5x+
        # when a scheduler stall lands inside it.
        capacity_eps = 0.0
        for _win in range(2):
            t_end = time.perf_counter() + cap_secs
            t0 = time.perf_counter()
            done = 0
            while time.perf_counter() < t_end:
                tids, dr, cr = make_spread(rng, pools[1], batch)
                setup._native.request(
                    Operation.create_transfers,
                    transfers_bytes(tids, dr, cr,
                                    rng.integers(1, 100, batch,
                                                 np.uint64),
                                    ledger=1),
                    120_000,
                )
                done += batch
            capacity_eps = max(
                capacity_eps, done / (time.perf_counter() - t0)
            )
        capacity_rps = capacity_eps / batch
        setup.close()
        clients.remove(setup)
        probe.kill()
        probe.wait(timeout=30)
        procs.remove(probe)
        out["capacity_eps"] = round(capacity_eps, 1)

        # Shares: a fair share is 0.25x measured capacity; every
        # tenant's bucket admits exactly ONE share (TB_TENANT_RATE is
        # per-tenant, so the victim's own bucket is the same size —
        # a bucket below the victim's rate sheds the VICTIM, measured
        # here inflating its p99 with busy-backoff retries).  The
        # victim runs at 0.7x its share — under its bucket, so its
        # Poisson bursts ride the burst credit and it is never shed —
        # while the hot tenant OFFERS 5x a share and is admitted at
        # 1x: the flood's excess lives in its shed stream, not in
        # shared queues, and aggregate admitted load (~0.43x
        # capacity) stays below the tail-latency knee.  Sizing the
        # bucket near the remaining headroom instead moves the
        # overload inside: at 1.3x-share admission (combined
        # utilization ~0.6 vs solo ~0.25) plain queueing put the
        # victim's combined p99 at 1.7-2x solo with ZERO victim
        # sheds — and fsync/checkpoint stall frequency scales with
        # admitted throughput on this box's one disk, which no
        # admission policy can remove.
        share_rps = 0.25 * capacity_rps
        victim_rate = max(0.5, 0.7 * share_rps)
        hot_rate = max(1.0, 5.0 * share_rps)
        rated_env = {
            "TB_TENANT_QOS": "1",
            "TB_TENANT_RATE": str(share_rps),
            "TB_ADMIT_QUEUE": "64",
            # Wide enough to absorb a scheduler/checkpoint stall at
            # the victim's rate without shedding it (48 requests at a
            # 0.25x-capacity share is ~640 ms of stall headroom on
            # this box); the flood's backlog is still bounded per
            # tenant, and the WFQ drain keeps the victim's requests
            # from waiting behind it.
            "TB_TENANT_QUEUE": "48",
        }
        out["tenant_rate_rps"] = round(share_rps, 2)

        # -- single-server arms: noisy_neighbor + contention ----------
        port = free_port()
        boot_replica("rated", port, rated_env)
        addr = f"127.0.0.1:{port}"
        setup = Client(addr, cluster_id, timeout_ms=120_000)
        clients.append(setup)
        for ledger in (1, 2, 3, 4):
            reply = setup._native.request(
                Operation.create_accounts,
                accounts_bytes(pools[ledger], ledger=ledger), 120_000,
            )
            assert reply == b"", "qos rated setup: account failures"
        for _ in range(3):  # warmup the fresh server
            tids, dr, cr = make_spread(rng, pools[1], batch)
            setup._native.request(
                Operation.create_transfers,
                transfers_bytes(tids, dr, cr,
                                rng.integers(1, 100, batch, np.uint64),
                                ledger=1),
                120_000,
            )

        def spread_body(ledger):
            def make():
                tids, dr, cr = make_spread(rng, pools[ledger], batch)
                return transfers_bytes(
                    tids, dr, cr,
                    rng.integers(1, 100, batch, np.uint64),
                    ledger=ledger,
                )
            return make

        def zipf_body(ledger):
            hot_ids = pools[ledger][:4]

            def make():
                tids, dr, cr = make_spread(rng, pools[ledger], batch)
                hot = rng.random(batch) < 0.5
                cr[hot] = rng.choice(hot_ids, int(hot.sum()))
                same = dr == cr
                dr[same] = pools[ledger][-1]
                return transfers_bytes(
                    tids, dr, cr,
                    rng.integers(1, 100, batch, np.uint64),
                    ledger=ledger,
                )
            return make

        def hammer_body(ledger):
            target = pools[ledger][0]

            def make():
                tids, dr, _cr = make_spread(rng, pools[ledger], batch)
                cr = np.full(batch, target, np.uint64)
                same = dr == cr
                dr[same] = pools[ledger][-1]
                return transfers_bytes(
                    tids, dr, cr,
                    rng.integers(1, 100, batch, np.uint64),
                    ledger=ledger,
                )
            return make

        import statistics

        repeats = max(1, int(os.environ.get("BENCH_QOS_REPEATS", 3)))
        out["repeats"] = repeats

        def med(xs):
            xs = [x for x in xs if x is not None]
            return round(statistics.median(xs), 2) if xs else None

        def single_server_arm(hot_ledger, victim_ledger, hot_fn):
            """Interleaved solo/combined repeats, per-phase median p99
            (the BENCH_r08 recipe: this box's wall-clock windows are
            noisy; medians keep one scheduler stall from deciding the
            grade)."""
            victim_s = OpenLoopSession(addr, cluster_id,
                                       0xA000 + victim_ledger)
            hot_s = OpenLoopSession(addr, cluster_id, 0xA100 + hot_ledger)
            sessions.extend([victim_s, hot_s])
            solo_p99s, comb_p99s, comb_p50s, hot_p99s = [], [], [], []
            replied = {"victim": 0, "hot": 0, "victim_solo": 0}
            busy = {"victim": 0, "hot": 0}
            pre = scrape_stats(addr, cluster_id, timeout_ms=10_000)
            for _rep in range(repeats):
                solo = drive_open_loop(
                    [(victim_s, victim_ledger, victim_rate,
                      spread_body(victim_ledger))],
                    phase_secs,
                )
                combined = drive_open_loop(
                    [
                        (victim_s, victim_ledger, victim_rate,
                         spread_body(victim_ledger)),
                        (hot_s, hot_ledger, hot_rate, hot_fn(hot_ledger)),
                    ],
                    phase_secs,
                )
                solo_p99s.append(pct(solo[victim_ledger]["lats"], 0.99))
                comb_p99s.append(
                    pct(combined[victim_ledger]["lats"], 0.99)
                )
                comb_p50s.append(
                    pct(combined[victim_ledger]["lats"], 0.5)
                )
                hot_p99s.append(pct(combined[hot_ledger]["lats"], 0.99))
                replied["victim_solo"] += len(solo[victim_ledger]["lats"])
                replied["victim"] += len(combined[victim_ledger]["lats"])
                replied["hot"] += len(combined[hot_ledger]["lats"])
                busy["victim"] += combined[victim_ledger]["busy"]
                busy["hot"] += combined[hot_ledger]["busy"]
            post = scrape_stats(addr, cluster_id, timeout_ms=10_000)
            solo_p99 = med(solo_p99s)
            comb_p99 = med(comb_p99s)
            # Median of PER-REP ratios: each combined window is judged
            # against its adjacent solo window, so a noisy-box stall
            # that lands on one pair cannot decide the grade alone.
            ratios = [
                round(c / s_, 3)
                for s_, c in zip(solo_p99s, comb_p99s) if s_ and c
            ]
            ratio = med(ratios)
            ctr = tenant_counters(
                post, "vsr.qos", (hot_ledger, victim_ledger)
            )
            pre_ctr = tenant_counters(
                pre, "vsr.qos", (hot_ledger, victim_ledger)
            )
            for k in ctr:  # per-arm deltas, not since-boot totals
                ctr[k] = {
                    f: ctr[k][f] - pre_ctr[k][f] for f in ("admit", "shed")
                }
            return {
                "victim_ledger": victim_ledger, "hot_ledger": hot_ledger,
                "victim_offered_rps": round(victim_rate, 2),
                "hot_offered_rps": round(hot_rate, 2),
                "victim_solo_p99_ms": solo_p99,
                "victim_solo_p99_ms_all": solo_p99s,
                "victim_p99_ms": comb_p99,
                "victim_p99_ms_all": comb_p99s,
                "victim_p50_ms": med(comb_p50s),
                "hot_p99_ms": med(hot_p99s),
                "victim_replied": replied["victim"],
                "hot_replied": replied["hot"],
                "victim_busy": busy["victim"],
                "hot_busy": busy["hot"],
                "isolation_ratio": ratio,
                "isolation_ratio_all": ratios,
                "isolation_ok": (
                    ratio is not None and ratio <= 1.25
                ),
                # Mechanism grade, wall-clock-insensitive: per-tenant
                # admission must discriminate — the flood eats the
                # sheds (>50% of its offered requests) while the
                # victim keeps >95% admitted AND its reply throughput
                # within 25% of solo.  On a loaded 1-2 core box the
                # p99 grade above also prices shared-CPU/fsync stalls
                # no admission policy can remove; this one does not.
                "victim_throughput_retained": round(
                    replied["victim"] / max(1, replied["victim_solo"]), 3
                ),
                "admission_isolation_ok": (
                    ctr[f"t{hot_ledger}"]["shed"]
                    > ctr[f"t{hot_ledger}"]["admit"]
                    and ctr[f"t{victim_ledger}"]["shed"]
                    <= 0.05 * max(1, ctr[f"t{victim_ledger}"]["admit"])
                    and replied["victim"]
                    >= 0.75 * replied["victim_solo"]
                ),
                "tenant_counters": ctr,
            }

        arms = {}
        arms["noisy_neighbor"] = single_server_arm(1, 2, zipf_body)
        arms["contention"] = single_server_arm(3, 4, hammer_body)
        for s in sessions:
            s.close()
        sessions.clear()
        setup.close()
        clients.remove(setup)
        for p in procs:
            p.kill()
            p.wait(timeout=30)
        procs.clear()

        # -- cross_shard arm: 2 shards behind the 2PC router ----------
        # The router keys OPEN SLOTS, not rates: a cross-shard-heavy
        # tenant costs ~4 shard sub-ops per request, so the isolation
        # mechanism is a tight per-tenant open-slot bound AT THE
        # ROUTER (2 of 64) — the aggressor's excess requests shed
        # typed busy while local tenants' slots stay free.  The
        # shards keep the relaxed bound (2PC legs must not churn
        # through shard-side shedding).
        shard_addrs = []
        shard_env = dict(rated_env)
        shard_env["TB_TENANT_RATE"] = "0"
        router_env = dict(shard_env)
        router_env["TB_ROUTER_QUEUE"] = "64"
        router_env["TB_TENANT_QUEUE"] = "2"
        for s in range(2):
            sport = free_port()
            shard_addrs.append(f"127.0.0.1:{sport}")
            boot_replica(f"shard{s}", sport, shard_env)
        rport = free_port()
        router_runner = (
            "import sys; sys.path.insert(0, {here!r})\n"
            "from tigerbeetle_tpu.runtime.router import RouterServer\n"
            "r = RouterServer('127.0.0.1:{port}', {shards!r},\n"
            "    cluster={cluster}, recover=False)\n"
            "print('listening', flush=True)\n"
            "r.serve_forever()\n"
        ).format(here=here, port=rport, shards=shard_addrs,
                 cluster=cluster_id)
        renv = dict(os.environ)
        renv.update(router_env)
        rlog_path = os.path.join(tmp, "router.log")
        rlog = open(rlog_path, "w")
        logs.append(rlog)
        rproc = subprocess.Popen(
            [sys.executable, "-c", router_runner], stdout=rlog,
            stderr=subprocess.STDOUT, cwd=here, env=renv,
        )
        procs.append(rproc)
        wait_listening(rproc, rlog_path, "router")
        router_addr = f"127.0.0.1:{rport}"

        from tigerbeetle_tpu.types import shard_of_account

        setup = Client(router_addr, cluster_id, timeout_ms=120_000)
        clients.append(setup)
        n_acct2 = 512
        rpools = {}
        for ledger in (1, 2):
            ids = np.arange(
                ledger * 10_000 + 1, ledger * 10_000 + n_acct2 + 1,
                dtype=np.uint64,
            )
            reply = setup._native.request(
                Operation.create_accounts,
                accounts_bytes(ids, ledger=ledger), 120_000,
            )
            assert reply == b"", "qos router setup: account failures"
            rpools[ledger] = ids
        by_shard = {
            ledger: {
                s: np.asarray(
                    [a for a in rpools[ledger]
                     if shard_of_account(int(a), 2) == s], np.uint64
                )
                for s in range(2)
            }
            for ledger in (1, 2)
        }

        lock = threading.Lock()

        def next_tids(n):
            with lock:
                t = tid_next[0]
                tid_next[0] += n
            return np.arange(t, t + n, dtype=np.uint64)

        xbatch = max(1, batch // 8)  # 2PC legs amplify per-event cost

        def local_body(trng):
            s = int(trng.integers(2))
            pool = by_shard[2][s]
            tids = next_tids(xbatch)
            dr = trng.choice(pool, xbatch)
            cr = trng.choice(pool, xbatch)
            same = dr == cr
            cr[same] = np.where(dr[same] == pool[0], pool[1], pool[0])
            return transfers_bytes(
                tids, dr, cr, trng.integers(1, 100, xbatch, np.uint64),
                ledger=2,
            )

        def cross_body(trng):
            tids = next_tids(xbatch)
            dr = trng.choice(by_shard[1][0], xbatch)
            cr = trng.choice(by_shard[1][1], xbatch)
            return transfers_bytes(
                tids, dr, cr, trng.integers(1, 100, xbatch, np.uint64),
                ledger=1,
            )

        def closed_loop(ledger, body_fn, secs, lats, k):
            trng = np.random.default_rng(1000 + k)
            c = Client(f"{router_addr},{router_addr}", cluster_id,
                       timeout_ms=120_000)
            clients.append(c)
            t_end = time.perf_counter() + secs
            while time.perf_counter() < t_end:
                body = body_fn(trng)
                t1 = time.perf_counter()
                c._native.request(
                    Operation.create_transfers, body, 120_000
                )
                lats.append((ledger, time.perf_counter() - t1))

        def router_phase(with_aggressor):
            lats: list = []
            threads = [threading.Thread(
                target=closed_loop,
                args=(2, local_body, phase_secs, lats, 0),
                daemon=True,
            )]
            if with_aggressor:
                threads.extend(
                    threading.Thread(
                        target=closed_loop,
                        args=(1, cross_body, phase_secs, lats, k),
                        daemon=True,
                    )
                    for k in range(1, 4)
                )
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=phase_secs + 120)
            return lats

        pre = scrape_stats(router_addr, cluster_id, timeout_ms=10_000)
        v_solo, v_comb, a_comb = [], [], []
        solo_p99s, comb_p99s = [], []
        for _rep in range(repeats):
            solo_lats = router_phase(with_aggressor=False)
            time.sleep(1.0)  # let 2PC residue settle between windows
            comb_lats = router_phase(with_aggressor=True)
            time.sleep(1.0)
            vs = [lat for ledger, lat in solo_lats if ledger == 2]
            vc = [lat for ledger, lat in comb_lats if ledger == 2]
            v_solo.extend(vs)
            v_comb.extend(vc)
            a_comb.extend(
                lat for ledger, lat in comb_lats if ledger == 1
            )
            solo_p99s.append(pct(vs, 0.99))
            comb_p99s.append(pct(vc, 0.99))
        post = scrape_stats(router_addr, cluster_id, timeout_ms=10_000)
        solo_p99 = med(solo_p99s)
        comb_p99 = med(comb_p99s)
        xratios = [
            round(c / s_, 3)
            for s_, c in zip(solo_p99s, comb_p99s) if s_ and c
        ]
        ratio = med(xratios)
        arms["cross_shard"] = {
            "victim_ledger": 2, "hot_ledger": 1,
            "victim_solo_requests": len(v_solo),
            "victim_requests": len(v_comb),
            "aggressor_requests": len(a_comb),
            "victim_solo_p99_ms": solo_p99,
            "victim_solo_p99_ms_all": solo_p99s,
            "victim_p99_ms": comb_p99,
            "victim_p99_ms_all": comb_p99s,
            "victim_solo_p50_ms": pct(v_solo, 0.5),
            "victim_p50_ms": pct(v_comb, 0.5),
            "aggressor_p99_ms": pct(a_comb, 0.99),
            "isolation_ratio": ratio,
            "isolation_ratio_all": xratios,
            "isolation_ok": ratio is not None and ratio <= 1.25,
            # The router's tenant slot bound throttles the 2PC
            # aggressor; the victim's throughput share is the
            # CPU-insensitive view of the same isolation (a 1-2 core
            # box serializes the 4 processes, so the victim's p99
            # tail picks up scheduler noise no admission policy can
            # remove — the ROADMAP multi-core carry-over applies).
            "victim_throughput_retained": (
                round(len(v_comb) / max(1, len(v_solo)), 3)
            ),
            "admission_isolation_ok": (
                len(v_comb) >= 0.5 * len(v_solo)
            ),
            "cpu_bound": (os.cpu_count() or 1) <= 2,
            "router_tenant_slots": 2,
            "router_shed": int(post.get("router.shed", 0))
            - int(pre.get("router.shed", 0)),
            "router_2pc": int(post.get("router.2pc_commits", 0))
            - int(pre.get("router.2pc_commits", 0)),
        }

        out["arms"] = arms
        out["isolation_grade"] = all(
            a.get("isolation_ok") for a in arms.values()
        )
        # The acceptance grade (noisy-neighbor victim within 25% while
        # the hot tenant drives 5x): single-server arms, where the
        # admission path — not host-core oversubscription — is what's
        # being measured.
        out["isolation_grade_single_server"] = all(
            arms[a].get("isolation_ok")
            for a in ("noisy_neighbor", "contention")
        )
        out["admission_isolation_grade"] = all(
            a.get("admission_isolation_ok") for a in arms.values()
        )
        return out
    finally:
        for s in sessions:
            try:
                s.close()
            except Exception:
                pass
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        for p in procs:
            try:
                p.kill()
            except Exception:
                pass
        for log in logs:
            log.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_sharded_cluster() -> dict:
    """Account-sharded multi-cluster scaling (runtime/router.py): K
    single-replica consensus groups behind the crash-safe 2PC router,
    measured at 1/2/4 shards on this box.  Graded on scaling
    efficiency vs shard count, cross-shard ratio, 2PC round trips per
    cross-shard transfer, and the in-doubt recovery count after a
    mid-run router kill -9 + restart (shards > 1)."""
    counts = [
        int(x) for x in os.environ.get(
            "BENCH_SHARD_COUNTS", "1,2,4"
        ).split(",")
    ]
    out: dict = {"shard_counts": counts}
    base_eps = None
    for n_shards in counts:
        row = _run_sharded_once(n_shards)
        out[f"shards_{n_shards}"] = row
        eps = row.get("events_per_sec")
        if eps and n_shards == counts[0]:
            base_eps = eps / counts[0]
        if eps and base_eps:
            # 1.0 = perfect linear scaling over the first configuration
            # (per-shard normalized).
            row["scaling_efficiency"] = round(
                eps / (n_shards * base_eps), 3
            )
    # Reference point for the ROADMAP target (>= 3x `replicated` at 4
    # shards): the newest graded replicated number on this box.
    try:
        import glob
        import re

        here = os.path.dirname(os.path.abspath(__file__))
        newest = max(
            glob.glob(os.path.join(here, "BENCH_r*.json")),
            key=lambda p: int(re.search(r"r(\d+)", p).group(1)),
        )
        ref = json.load(open(newest))["configs"]["replicated"][
            "events_per_sec"
        ]
        out["replicated_reference_eps"] = ref
        top = out.get(f"shards_{counts[-1]}", {}).get("events_per_sec")
        if top and ref:
            out["vs_replicated_reference"] = round(top / ref, 2)
    except (ValueError, KeyError, OSError, AttributeError):
        pass
    out["host_cores"] = os.cpu_count()
    return out


def _run_sharded_once(n_shards: int) -> dict:
    import shutil
    import socket
    import subprocess
    import tempfile
    import threading

    from tigerbeetle_tpu.types import shard_of_account

    n_events = int(os.environ.get("BENCH_SHARD_EVENTS", 40_000))
    batch = int(os.environ.get("BENCH_SHARD_BATCH", 4096))
    cross_pct = float(os.environ.get("BENCH_SHARD_CROSS_PCT", 10.0))
    n_sessions = int(os.environ.get("BENCH_SHARD_SESSIONS", 4))
    request_timeout_ms = int(
        os.environ.get("BENCH_SHARD_TIMEOUT_MS", 300_000)
    )
    kill_router = n_shards > 1 and os.environ.get(
        "BENCH_SHARD_KILL", "1"
    ) != "0"
    cluster_id = 21
    tmp = tempfile.mkdtemp(prefix="tb_bench_shard_")
    here = os.path.dirname(os.path.abspath(__file__))
    procs: list = []
    logs: list = []
    clients: list = []
    router_proc: list = [None]

    def free_ports(n):
        socks = [socket.socket() for _ in range(n)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        return ports

    def wait_listening(proc, log_path, what, n_marks=1):
        """Wait for the n_marks-th "listening" line: restarted routers
        APPEND to the same log, so counting (not mere presence) is
        what proves THIS incarnation is up."""
        deadline = time.time() + 120
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"{what} exited rc={proc.returncode}:\n"
                    + open(log_path).read()[-2000:]
                )
            try:
                if open(log_path).read().count("listening") >= n_marks:
                    return
            except OSError:
                pass
            time.sleep(0.3)
        raise AssertionError(f"{what} did not start: {log_path}")

    try:
        shard_addrs = []
        for s in range(n_shards):
            port = free_ports(1)[0]
            addr = f"127.0.0.1:{port}"
            shard_addrs.append(addr)
            path = os.path.join(tmp, f"s{s}.tigerbeetle")
            subprocess.run(
                [
                    sys.executable, "-m", "tigerbeetle_tpu", "format",
                    f"--cluster={cluster_id}", "--replica=0",
                    "--replica-count=1", path,
                ],
                check=True, capture_output=True, cwd=here, timeout=120,
            )
            runner = (
                "import sys; sys.path.insert(0, {here!r})\n"
                "from tigerbeetle_tpu.runtime import affinity\n"
                "affinity.apply(slot={slot})\n"
                "from tigerbeetle_tpu.runtime.server import ReplicaServer\n"
                "from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine\n"
                "s = ReplicaServer({path!r}, addresses=[{addr!r}],\n"
                "    replica_index=0,\n"
                "    state_machine_factory=lambda: TpuStateMachine(\n"
                "        account_capacity=1 << 12,\n"
                "        transfer_capacity={cap}))\n"
                "print('listening', flush=True)\n"
                "s.serve_forever()\n"
            ).format(here=here, path=path, addr=addr, slot=s,
                     cap=4 * n_events + (1 << 16))
            log_path = os.path.join(tmp, f"shard{s}.log")
            log = open(log_path, "w")
            logs.append(log)
            procs.append((subprocess.Popen(
                [sys.executable, "-c", runner], stdout=log,
                stderr=subprocess.STDOUT, cwd=here,
            ), log_path))
        for proc, log_path in procs:
            wait_listening(proc, log_path, "shard replica")

        router_port = free_ports(1)[0]
        router_runner = (
            "import sys; sys.path.insert(0, {here!r})\n"
            "from tigerbeetle_tpu.runtime.router import RouterServer\n"
            "r = RouterServer('127.0.0.1:{port}', {shards!r},\n"
            "    cluster={cluster}, recover={recover})\n"
            "print('listening', flush=True)\n"
            "r.serve_forever()\n"
        )

        router_starts = [0]

        def start_router(recover: bool):
            log_path = os.path.join(tmp, "router.log")
            log = open(log_path, "a")
            logs.append(log)
            p = subprocess.Popen(
                [
                    sys.executable, "-c",
                    router_runner.format(
                        here=here, port=router_port, shards=shard_addrs,
                        cluster=cluster_id, recover=recover,
                    ),
                ],
                stdout=log, stderr=subprocess.STDOUT, cwd=here,
            )
            router_proc[0] = p
            router_starts[0] += 1
            wait_listening(p, log_path, "router",
                           n_marks=router_starts[0])
            return p

        start_router(recover=False)
        router_addr = f"127.0.0.1:{router_port}"

        from tigerbeetle_tpu.client import Client
        from tigerbeetle_tpu.obs.scrape import scrape_stats

        # Accounts, grouped per shard by the deterministic mapping.
        n_acct = 1_024
        ids = np.arange(1, n_acct + 1, dtype=np.uint64)
        by_shard = [[] for _ in range(n_shards)]
        for v in ids:
            by_shard[shard_of_account(int(v), n_shards)].append(int(v))
        by_shard = [np.asarray(v, dtype=np.uint64) for v in by_shard]
        # The doubled router address keeps the native client's
        # retransmission rotating (and reconnecting) through the
        # router restart window.
        setup = Client(f"{router_addr},{router_addr}", cluster_id,
                       timeout_ms=request_timeout_ms)
        clients.append(setup)
        reply = setup._native.request(
            Operation.create_accounts, accounts_bytes(ids),
            request_timeout_ms,
        )
        assert reply == b"", "sharded setup: account failures"

        # Transfer batches: rows round-robin across home shards;
        # cross_pct% pair a debit on shard s with a credit on s+1.
        rng = np.random.default_rng(71)
        bodies = []
        tid = 1
        done = 0
        while done < n_events:
            n = min(batch, n_events - done)
            tids = np.arange(tid, tid + n, dtype=np.uint64)
            tid += n
            home = (np.arange(n) + len(bodies)) % n_shards
            dr = np.empty(n, np.uint64)
            cr = np.empty(n, np.uint64)
            cross = rng.random(n) < cross_pct / 100.0
            for s in range(n_shards):
                mask = home == s
                pool = by_shard[s]
                dr[mask] = rng.choice(pool, int(mask.sum()))
                peer = by_shard[(s + 1) % n_shards]
                cr_s = rng.choice(pool, int(mask.sum()))
                cr_x = rng.choice(peer, int(mask.sum()))
                cr[mask] = np.where(cross[mask], cr_x, cr_s)
            same = dr == cr
            if same.any():
                for i in np.flatnonzero(same):
                    pool = by_shard[shard_of_account(int(dr[i]), n_shards)]
                    cr[i] = pool[0] if pool[0] != dr[i] else pool[1]
            bodies.append(transfers_bytes(
                tids, dr, cr, rng.integers(1, 100, n, np.uint64)
            ))
            done += n

        lat: list = []
        acceptable_fail = [0]
        hard_fail = [0]
        errors: list = []
        expired = int(types.CreateTransferResult.pending_transfer_expired)
        lock = threading.Lock()

        def drive(s: int) -> None:
            c = Client(f"{router_addr},{router_addr}", cluster_id,
                       timeout_ms=request_timeout_ms)
            clients.append(c)
            try:
                for body in bodies[s::n_sessions]:
                    b0 = time.perf_counter()
                    reply = c._native.request(
                        Operation.create_transfers, body,
                        request_timeout_ms,
                    )
                    dt = time.perf_counter() - b0
                    codes = np.frombuffer(
                        reply, types.CREATE_RESULT_DTYPE
                    )["result"]
                    with lock:
                        lat.append(dt)
                        # A cross-shard transfer aborted by the router
                        # kill resolves as a typed expired — a clean
                        # abort, priced but not an error.
                        acceptable_fail[0] += int(
                            (codes == expired).sum()
                        )
                        hard_fail[0] += int((codes != expired).sum())
            except Exception as exc:  # noqa: BLE001
                errors.append(f"session {s}: {exc!r}")

        threads = [
            threading.Thread(target=drive, args=(s,), daemon=True)
            for s in range(n_sessions)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        killed_mid_run = False
        indoubt = 0
        if kill_router:
            # Coordinator crash mid-stream: kill -9, restart with
            # recovery; clients ride their retransmission loops.
            time.sleep(max(1.0, min(10.0, n_events / 20_000)))
            router_proc[0].kill()
            router_proc[0].wait()
            start_router(recover=True)
            killed_mid_run = True
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        if errors or hard_fail[0]:
            return {
                "error": "; ".join(errors)
                or f"{hard_fail[0]} hard transfer failures",
                "n_shards": n_shards,
                "router_log_tail": open(
                    os.path.join(tmp, "router.log")
                ).read()[-1500:],
            }
        stats = {}
        try:
            # The scrape is a single request/reply exchange with no
            # retransmission; retry a couple of times before declaring
            # the router unscrapable.
            snap = None
            for _attempt in range(3):
                try:
                    snap = scrape_stats(router_addr, cluster_id,
                                        timeout_ms=20_000)
                    break
                except (OSError, TimeoutError, ValueError):
                    if _attempt == 2:
                        raise
            cross = int(snap.get("router.cross_shard_transfers", 0))
            stats = {
                "cross_shard_transfers": cross,
                "local_transfers": int(
                    snap.get("router.local_transfers", 0)
                ),
                "cross_shard_ratio": round(
                    cross / max(1, n_events), 4
                ),
                "two_pc_roundtrips": int(
                    snap.get("router.2pc_roundtrips", 0)
                ),
                "two_pc_commits": int(snap.get("router.2pc_commits", 0)),
                "two_pc_aborts": int(snap.get("router.2pc_aborts", 0)),
                "two_pc_compensations": int(
                    snap.get("router.2pc_compensations", 0)
                ),
                "two_pc_conflicts": int(
                    snap.get("router.2pc_conflicts", 0)
                ),
                "indoubt_recovered": int(
                    snap.get("router.indoubt_recovered", 0)
                ),
                "router_retries": int(snap.get("router.retries", 0)),
            }
            indoubt = stats["indoubt_recovered"]
            # Cluster proof-of-state through the router's state_root
            # query (per-shard roots folded deterministically) — the
            # audit hook clients get, graded here end to end.
            try:
                from tigerbeetle_tpu.obs.scrape import scrape_state_root

                croot, n_folded = scrape_state_root(
                    router_addr, cluster_id, timeout_ms=20_000
                )
                stats["cluster_root"] = croot.hex()
                stats["cluster_root_shards"] = n_folded
            except (OSError, TimeoutError, ValueError):
                stats["cluster_root"] = None
        except (OSError, TimeoutError, ValueError):
            stats = {"scrape_error": True}
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        from tigerbeetle_tpu.runtime import affinity

        return {
            "n_shards": n_shards,
            "events": n_events,
            "pinned_cores": {
                f"shard{s}": affinity.plan(s) for s in range(n_shards)
            },
            "events_per_sec": round(n_events / elapsed, 1),
            "batch_events": batch,
            "client_sessions": n_sessions,
            "router_killed_mid_run": killed_mid_run,
            "aborted_by_kill": acceptable_fail[0],
            "indoubt_recovered": indoubt,
            "request_p50_ms": round(
                float(lat_ms[len(lat_ms) // 2]), 2
            ) if len(lat_ms) else None,
            "request_p99_ms": round(
                float(lat_ms[int(len(lat_ms) * 0.99)]), 2
            ) if len(lat_ms) else None,
            **stats,
        }
    finally:
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        if router_proc[0] is not None:
            router_proc[0].kill()
        for proc, _lp in procs:
            proc.kill()
        for log in logs:
            log.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _run_subprocess_config(flag: str, timeout_s: int | None = None) -> dict:
    """One config in a fresh subprocess; ANY failure (non-zero exit,
    timeout, unparseable output) yields an error dict, never an
    exception — the graded JSON line must print regardless (r4 lesson:
    bench.py:786's assert turned one config's timeout into a round
    with no recorded number; reference behavior is devhub's
    unconditional per-merge record, src/scripts/devhub.zig:36-41)."""
    import subprocess

    if timeout_s is None:
        timeout_s = int(os.environ.get("BENCH_CONFIG_TIMEOUT_S", 3600))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), flag],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        # A wedged accelerator can leave the child unkillable
        # (D-state); kill, wait briefly, and record the timeout
        # rather than block forever reaping it.
        proc.kill()
        try:
            _, stderr = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            stderr = ""
        return {
            "error": f"config subprocess exceeded {timeout_s}s",
            "tail": (stderr or "")[-2000:],
        }
    if proc.returncode != 0:
        return {
            "error": f"config subprocess rc={proc.returncode}",
            "tail": (stderr or "")[-2000:],
        }
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as exc:
        return {
            "error": f"unparseable config output: {exc}",
            "tail": (stdout or "")[-1000:] + (stderr or "")[-1000:],
        }


def _reset_dev_wave_stats(sm) -> None:
    """Zero every wave-forensics counter before a timed window — the
    ONE list, shared by the memory configs and the device_waves arms
    (a counter added in one place but not the other would report
    stale counts from the previous arm)."""
    sm.stat_dev_wave_batches = 0
    sm.stat_dev_wave_declined = 0
    sm.stat_dev_wave_steps = 0
    sm.stat_dev_wave_events = 0
    sm.stat_dev_wave_plan_s = 0.0
    sm.stat_dev_wave_decline_reasons = {}
    if sm.engine == "device":
        sm._dev.stat_wave_sharded = 0
        sm._dev.stat_wave_window_bytes_peak = 0
        sm._dev.stat_wave_window_padded_peak = 0
        spec = getattr(sm._dev, "spec_stats", None)
        if spec:
            for handle in spec.values():
                if hasattr(handle, "set"):  # counters; histograms window
                    handle.set(0)


def _run_memory_config(name, gen) -> dict:
    n_events = N_SIMPLE if name == "simple" else N_OTHER
    setup, timed, sizing = gen(n_events)
    engine = CONFIG_ENGINE[name]
    sm = _make_tpu(sizing, engine, name)
    _, _, h = replay(sm, setup)
    if hasattr(sm, "sync"):
        sm.sync()
    # Only the timed window counts toward the device/host split.
    sm.stat_device_events = 0
    sm.stat_exact_events = 0
    sm.stat_host_semantic_events = 0
    sm.stat_hot_tail_batches = 0
    sm.stat_slow_tail_batches = 0
    sm.stat_wave_batches = 0
    sm.stat_wave_steps = 0
    sm.stat_wave_events = 0
    sm.stat_wave_parallel_events = 0
    _reset_dev_wave_stats(sm)
    if sm.engine == "device":
        sm._dev.stat_semantic_events = 0
    failed = 0
    t0 = time.perf_counter()
    futs = [
        (op, h.submit_async(op, body)) for op, body in timed
    ]
    for op, fut in futs:
        reply = fut.result()
        if op == Operation.create_transfers:
            failed += len(reply) // 8  # CREATE_RESULT_DTYPE entries
    if hasattr(sm, "sync"):
        sm.sync()
    elapsed = time.perf_counter() - t0
    # linked/two_phase legitimately reject events (limit trips,
    # chain rollbacks); the all-success configs must stay clean —
    # a silently-failing engine must not benchmark as a fast one.
    if name in ("simple", "simple_device", "zipf", "mixed"):
        assert failed == 0, f"{name}: {failed} transfers failed"
    n_timed = n_events_of(timed)
    dev = sm.stat_device_events
    exact = sm.stat_exact_events
    dev_sem = sm.stat_device_semantic_events
    host_sem = sm.stat_host_semantic_events
    out = {
        "events_per_sec": round(n_timed / elapsed, 1),
        "events": n_timed,
        "failed_events": failed,
        "vs_baseline": round(n_timed / elapsed / BASELINE_TPS, 4),
        "engine": sm.engine,
        "device_resolved_pct": round(100.0 * dev / max(1, dev + exact), 1),
        # The honest number (VERDICT r3 #1e): % of create_transfers
        # events whose RESULT CODES were computed by a device
        # kernel (not merely whose balance deltas were applied).
        "device_semantic_pct": round(
            100.0 * dev_sem / max(1, dev_sem + host_sem), 1
        ),
    }
    # Which bookkeeping path ran (VERDICT r4 #4): the all-success hot
    # tail is ~2x the general path, so its engagement must be visible
    # in the graded output, not inferred from the throughput's mode.
    if sm.stat_hot_tail_batches or sm.stat_slow_tail_batches:
        out["hot_tail_batches"] = sm.stat_hot_tail_batches
        out["slow_tail_batches"] = sm.stat_slow_tail_batches
    # Conflict-aware wave execution (waves.py): how many batches the
    # JAX exact path ran as wave plans, the device-step equivalents
    # per batch (1 per wave + length per conflict group), and the
    # share of events that executed in parallel waves.
    if sm.stat_wave_batches:
        out["wave_batches"] = sm.stat_wave_batches
        out["waves_per_batch"] = round(
            sm.stat_wave_steps / sm.stat_wave_batches, 2
        )
        out["wave_parallelism_pct"] = round(
            100.0 * sm.stat_wave_parallel_events
            / max(1, sm.stat_wave_events),
            1,
        )
    # Device-engine wave dispatch (TB_DEV_WAVES): window batches
    # executed as wave plans against the authoritative HBM table vs
    # declined to the host, their step collapse, and the planning
    # wall time (must never show in the window-launch profile).
    if sm.stat_dev_wave_batches or sm.stat_dev_wave_declined:
        out["device_waves"] = {
            "batches": sm.stat_dev_wave_batches,
            "declined": sm.stat_dev_wave_declined,
            "declined_by_reason": dict(sm.stat_dev_wave_decline_reasons),
            "sharded": sm._dev.stat_wave_sharded,
            "steps_per_batch": round(
                sm.stat_dev_wave_steps
                / max(1, sm.stat_dev_wave_batches),
                2,
            ),
            "events": sm.stat_dev_wave_events,
            "plan_ms_total": round(1e3 * sm.stat_dev_wave_plan_s, 2),
            "pending_window_bytes": sm._dev.stat_wave_window_bytes_peak,
            "pending_window_bytes_padded": (
                sm._dev.stat_wave_window_padded_peak
            ),
        }
    # Link-robustness forensics (device_engine degraded-mode
    # lifecycle): retries, demotions/re-promotions, events served by
    # the degraded host path, and checksum scrubs.  Only reported when
    # something happened — an all-zero block would just be noise on a
    # healthy link.
    if sm.engine == "device":
        d = sm._dev
        health = {
            "state": d.state.value,
            "link_retries": d.stat_retries,
            "link_errors": d.stat_link_errors,
            "demotions": d.stat_demotions,
            "repromotions": d.stat_repromotions,
            "probe_failures": d.stat_probe_failures,
            "degraded_events": d.stat_degraded_events,
            "scrubs": d.stat_scrubs,
            "scrub_heals": d.stat_scrub_heals,
        }
        if health["state"] != "healthy" or any(
            v for k, v in health.items() if k != "state"
        ):
            out["engine_health"] = health
        # Incremental state commitment (commitment.py): digest-update
        # dispatches + their per-step cost, the cheap (16-byte) vs
        # fallback (full-fetch) scrub split, and the root itself —
        # the graded evidence for the "scrub is 16 bytes now" claim.
        if d._commit_enabled:
            hu = d._h_commit_update
            out["commitment"] = {
                "updates": d.stat_commit_updates,
                "update_us_p50": hu.percentile(0.50),
                "update_us_p99": hu.percentile(0.99),
                "scrub_cheap": d.stat_scrub_cheap,
                "scrub_fallback": d.stat_scrub_fallback,
                "full_fetches": d.stat_full_fetches,
                "state_root": sm.state_root().hex(),
            }
    del sm, h
    return out


def _run_parity(name, gen) -> str:
    """-> "ok(full)" / "ok(truncated)" / mismatch description."""
    from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine

    if name == "simple":
        n_parity = N_SIMPLE
    elif FULL_PARITY:
        n_parity = N_OTHER
    else:
        n_parity = min(N_OTHER, N_PARITY_OTHER)
    setup, timed, sizing = gen(n_parity)
    ops = setup + timed
    sm_t = _make_tpu(sizing, CONFIG_ENGINE[name], name)
    _, replies_t, h_t = replay(sm_t, ops, collect=True)
    sm_c = CpuStateMachine()
    _, replies_c, h_c = replay(sm_c, ops, collect=True)
    mismatch = None
    for i, (a, b) in enumerate(zip(replies_t, replies_c)):
        if a != b:
            mismatch = f"reply[{i}] differs"
            break
    if mismatch is None:
        acct_ids = config_account_ids(name)
        tid_sample = np.concatenate(
            [
                np.arange(TID0, TID0 + min(4_000, n_parity)),
                np.arange(
                    max(TID0, TID0 + n_parity - 4_000), TID0 + n_parity
                ),
            ]
        ).astype(np.uint64)
        if state_digest(h_t, acct_ids, tid_sample) != state_digest(
            h_c, acct_ids, tid_sample
        ):
            mismatch = "final state digest differs"
    full = name == "simple" or n_parity >= N_OTHER
    return mismatch or ("ok(full)" if full else "ok(truncated)")


def run_waves_compare() -> dict:
    """Conflict-aware wave execution vs the B-step scan: same session,
    same JAX backend, identical op streams.

    Each bench config's stream runs twice through the JAX exact path
    with the native engine disabled — TB_WAVES=exact (wave scheduler
    with its normal profitability/admission gates) and TB_WAVES=scan
    (identical routing, pure sequential lax.scan) — so the comparison
    isolates the kernel SHAPE (one step per wave vs one step per
    event) from link tenancy and host bookkeeping, which are shared.
    A config whose plans the scheduler declines (e.g. linked, where
    chains serialize nearly every event) honestly shows speedup ~1 and
    no waves_per_batch.  Replies and final wire state must be bit-identical
    (graded under `parity`); `speedup` is the wave path's throughput
    over the scan's on this hour's backend, and `waves_per_batch` the
    device-step-equivalent collapse the partitioner achieved."""
    waves_n = int(os.environ.get("BENCH_WAVES_N", 16_380 if SMALL else 65_520))
    out = {"events_per_config": waves_n}
    saved = os.environ.get("TB_WAVES")
    saved_commit = os.environ.get("TB_STATE_COMMIT")
    try:
        for name in ("simple", "linked", "two_phase", "zipf", "mixed"):
            setup, timed, sizing = CONFIGS[name](waves_n)
            n_timed = n_events_of(timed)
            runs = {}
            # Three same-session arms: wave vs scan isolates the
            # kernel shape (as before); wave vs wave_nodigest grades
            # the incremental-commitment overhead (TB_STATE_COMMIT
            # A/B) instead of asserting it — replies and final state
            # must stay bit-identical across ALL arms.
            for mode, env_val, commit_env in (
                ("wave", "exact", "1"),
                ("wave_nodigest", "exact", "0"),
                ("scan", "scan", "1"),
            ):
                os.environ["TB_WAVES"] = env_val
                os.environ["TB_STATE_COMMIT"] = commit_env
                # NOT _make_tpu: a TB_ENGINE=device override would
                # silently put BOTH arms on the device engine (which
                # TB_WAVES does not bypass) and grade a meaningless
                # speedup — this comparison is host-engine by design.
                from tigerbeetle_tpu.state_machine.tpu import (
                    TpuStateMachine,
                )

                sm = TpuStateMachine(
                    account_capacity=sizing[0],
                    transfer_capacity=sizing[1],
                    engine="host",
                )
                sm._native = None  # isolate the JAX exact path
                if mode in ("wave", "wave_nodigest"):
                    # Untimed compile of every (batch, segment) bucket
                    # pair: the setup warmup only hits simple-shaped
                    # full-batch waves, and e.g. two_phase's ~B/2-event
                    # waves (bucket 4096) would otherwise first-compile
                    # inside the timed window, deflating the speedup.
                    from tigerbeetle_tpu.state_machine import waves

                    waves.prewarm(sizing[0])
                _, _, h = replay(sm, setup)
                sm.stat_wave_batches = 0
                sm.stat_wave_steps = 0
                sm.stat_wave_events = 0
                sm.stat_wave_parallel_events = 0
                t0 = time.perf_counter()
                futs = [(op, h.submit_async(op, body)) for op, body in timed]
                replies = [f.result() for _op, f in futs]
                elapsed = time.perf_counter() - t0
                digest = state_digest(
                    h, config_account_ids(name),
                    np.arange(TID0, TID0 + waves_n, dtype=np.uint64),
                )
                runs[mode] = {
                    "elapsed": elapsed,
                    "replies": replies,
                    "digest": digest,
                    "wave_batches": sm.stat_wave_batches,
                    "wave_steps": sm.stat_wave_steps,
                    "wave_events": sm.stat_wave_events,
                    "wave_parallel": sm.stat_wave_parallel_events,
                }
                del sm, h
            parity = "ok"
            for other in ("scan", "wave_nodigest"):
                for i, (a, b) in enumerate(
                    zip(runs["wave"]["replies"], runs[other]["replies"])
                ):
                    if a != b:
                        parity = f"reply[{i}] differs vs {other}"
                        break
                if parity == "ok" and (
                    runs["wave"]["digest"] != runs[other]["digest"]
                ):
                    parity = f"state digest differs vs {other}"
                if parity != "ok":
                    break
            w, s = runs["wave"], runs["scan"]
            wn = runs["wave_nodigest"]
            row = {
                "events": n_timed,
                "scan_events_per_sec": round(n_timed / s["elapsed"], 1),
                "wave_events_per_sec": round(n_timed / w["elapsed"], 1),
                "speedup": round(s["elapsed"] / w["elapsed"], 2),
                "nodigest_events_per_sec": round(
                    n_timed / wn["elapsed"], 1
                ),
                # Measured cost of maintaining the incremental state
                # commitment on this stream (positive = digest arm
                # slower).
                "digest_overhead_pct": round(
                    (w["elapsed"] / wn["elapsed"] - 1.0) * 100.0, 1
                ),
                "parity": parity,
            }
            if w["wave_batches"]:
                row["waves_per_batch"] = round(
                    w["wave_steps"] / w["wave_batches"], 2
                )
                row["wave_parallelism_pct"] = round(
                    100.0 * w["wave_parallel"] / max(1, w["wave_events"]), 1
                )
            out[name] = row
    finally:
        if saved is None:
            os.environ.pop("TB_WAVES", None)
        else:
            os.environ["TB_WAVES"] = saved
        if saved_commit is None:
            os.environ.pop("TB_STATE_COMMIT", None)
        else:
            os.environ["TB_STATE_COMMIT"] = saved_commit
    return out


def gen_offkernel(n_events: int):
    """Window batches the semantic kernels cannot express — the
    wave-dispatch target classes, which before this round drained the
    device stream to the host once per batch:

    - (pending, post) pairs with balancing riders on a funded side
      pool (has_bal falls off every kernel route; the plan is 2 waves
      + 1 rider wave);
    - independent 3-member linked chains whose first member is a
      pending (linked+pending declines the device `linked` kernel;
      the plan is one position-stepped chain segment).
    """
    rng = np.random.default_rng(46)
    n_acct = 1_001  # odd: keeps the engine UNSHARDED on virtual
    # meshes, so the single-chip configuration really grades the
    # single-chip executors (the sharded configuration rounds the
    # capacity up to a device multiple itself)
    bal0 = 801
    n_bal = 200
    setup = [(Operation.create_accounts, accounts_bytes(range(1, n_acct)))]
    # Fund the balancing pool so riders usually apply.
    setup += batched(
        {
            "ids": np.arange(WARM0, WARM0 + n_bal, dtype=np.uint64),
            "dr": np.full(n_bal, 1, np.uint64),
            "cr": np.arange(bal0, bal0 + n_bal, dtype=np.uint64),
            "amount": np.full(n_bal, 1_000_000, np.uint64),
        }
    )

    def pvbal_batch(m, id0):
        riders = min(8, m // 4)
        n_pairs = (m - riders) // 2
        m = 2 * n_pairs + riders
        ids = np.arange(id0, id0 + m, dtype=np.uint64)
        flags = np.zeros(m, np.uint16)
        flags[0 : 2 * n_pairs : 2] = int(TF.pending)
        flags[1 : 2 * n_pairs : 2] = int(TF.post_pending_transfer)
        flags[2 * n_pairs :] = int(TF.balancing_debit)
        pending_id = np.zeros(m, np.uint64)
        pending_id[1 : 2 * n_pairs : 2] = ids[0 : 2 * n_pairs : 2]
        dr = np.zeros(m, np.uint64)
        cr = np.zeros(m, np.uint64)
        dr[0 : 2 * n_pairs : 2] = rng.integers(1, bal0, n_pairs, np.uint64)
        cr[0 : 2 * n_pairs : 2] = dr[0 : 2 * n_pairs : 2] % np.uint64(
            bal0 - 1
        ) + np.uint64(1)
        # Distinct funded accounts per rider: their limit reads stay
        # independent of each other and of the pairs' writes.
        pick = rng.choice(n_bal, 2 * riders, replace=False).astype(np.uint64)
        dr[2 * n_pairs :] = bal0 + pick[:riders]
        cr[2 * n_pairs :] = bal0 + pick[riders:]
        amount = np.zeros(m, np.uint64)
        amount[0 : 2 * n_pairs : 2] = rng.integers(1, 100, n_pairs, np.uint64)
        amount[2 * n_pairs :] = rng.integers(1, 50, riders, np.uint64)
        return {
            "ids": ids, "dr": dr, "cr": cr, "amount": amount,
            "flags": flags, "pending_id": pending_id,
        }, id0 + m

    def chain_batch(m, id0):
        n_chains = m // 3
        m = 3 * n_chains
        ids = np.arange(id0, id0 + m, dtype=np.uint64)
        flags = np.zeros(m, np.uint16)
        flags[0::3] = int(TF.linked | TF.pending)
        flags[1::3] = int(TF.linked)
        # Disjoint account pairs per chain (chains must be pairwise
        # independent to ride position-stepped).
        base = rng.permutation(bal0 - 2)[:n_chains].astype(np.uint64)
        dr = np.repeat(base + 1, 3)
        cr = np.repeat(base + 2, 3)
        amount = rng.integers(1, 60, m).astype(np.uint64)
        return {
            "ids": ids, "dr": dr, "cr": cr, "amount": amount,
            "flags": flags,
        }, id0 + m

    timed = []
    tid = TID0
    events = 0
    k = 0
    while events < n_events:
        m = min(BATCH, n_events - events)
        if m < 8:
            break
        arrs, tid = (
            chain_batch(m, tid) if k % 3 == 2 else pvbal_batch(m, tid)
        )
        timed += batched(arrs)
        events += len(arrs["ids"])
        k += 1
    return setup, timed, (n_acct + 1, (tid - TID0) + 4 * BATCH + 1024)


def _run_device_waves_arms(n: int, sharded: bool) -> dict:
    """The wave-vs-drain comparison body shared by the single-chip and
    sharded device_waves configurations: the SAME off-kernel stream
    runs TB_DEV_WAVES=1 (wave plans execute inside the window against
    the HBM table) and TB_DEV_WAVES=0 (drain + exact host path per
    batch); replies must be bit-identical.  `sharded=True` rounds the
    account capacity up to a device multiple so the engine row-shards
    its tables and the wave plans execute SPMD over the ("shard",)
    mesh — and asserts the engine really sharded."""
    import jax

    out = {"events": n}
    saved = os.environ.get("TB_DEV_WAVES")
    try:
        runs = {}
        for mode, env_val in (("wave", "1"), ("drain", "0")):
            os.environ["TB_DEV_WAVES"] = env_val
            setup, timed, sizing = gen_offkernel(n)
            account_capacity = sizing[0]
            if sharded:
                nd = len(jax.devices())
                if nd < 2:
                    return {
                        "error": "single-device backend: launcher "
                        "should have forced a host-platform mesh"
                    }
                account_capacity = -(-account_capacity // nd) * nd
            # NOT _make_tpu: this comparison is device-engine BY
            # DESIGN (a TB_ENGINE=host override — including the CPU
            # re-exec fallback's — would grade a meaningless
            # host-vs-host speedup); the engine runs on whatever JAX
            # backend this hour provides, honestly marked.
            from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

            sm = TpuStateMachine(
                account_capacity=account_capacity,
                transfer_capacity=sizing[1],
                engine="device",
                prewarm="waves" if mode == "wave" else None,
            )
            if sharded:
                assert sm._dev.sharding is not None, "engine did not shard"
                out["n_devices"] = len(jax.devices())
            elif sm._dev.sharding is not None:
                return {
                    "error": "engine sharded under the single-chip "
                    "configuration (capacity should be odd)"
                }
            _, _, h = replay(sm, setup)
            _reset_dev_wave_stats(sm)
            sm.stat_host_semantic_events = 0
            t0 = time.perf_counter()
            futs = [(op, h.submit_async(op, body)) for op, body in timed]
            replies = [f.result() for _op, f in futs]
            if hasattr(sm, "sync"):
                sm.sync()
            elapsed = time.perf_counter() - t0
            runs[mode] = {
                "elapsed": elapsed,
                "replies": replies,
                "wave_batches": sm.stat_dev_wave_batches,
                "declined": sm.stat_dev_wave_declined,
                "declined_by_reason": dict(
                    sm.stat_dev_wave_decline_reasons
                ),
                "steps": sm.stat_dev_wave_steps,
                "events": sm.stat_dev_wave_events,
                "plan_s": sm.stat_dev_wave_plan_s,
                "host_events": sm.stat_host_semantic_events,
                "sharded_batches": sm._dev.stat_wave_sharded,
                "window_bytes": sm._dev.stat_wave_window_bytes_peak,
                "window_bytes_padded": (
                    sm._dev.stat_wave_window_padded_peak
                ),
            }
            del sm, h
        parity = "ok"
        for i, (a, b) in enumerate(
            zip(runs["wave"]["replies"], runs["drain"]["replies"])
        ):
            if a != b:
                parity = f"reply[{i}] differs"
                break
        n_timed = n_events_of(timed)
        w, d = runs["wave"], runs["drain"]
        out.update(
            {
                "events": n_timed,
                "drain_events_per_sec": round(n_timed / d["elapsed"], 1),
                "wave_events_per_sec": round(n_timed / w["elapsed"], 1),
                "speedup": round(d["elapsed"] / w["elapsed"], 2),
                "parity": parity,
                "wave_batches": w["wave_batches"],
                "wave_declined": w["declined"],
                "declined_by_reason": w["declined_by_reason"],
                "steps_per_batch": round(
                    w["steps"] / max(1, w["wave_batches"]), 2
                ),
                "plan_ms_total": round(1e3 * w["plan_s"], 2),
                "wave_host_drained_events": w["host_events"],
                "sharded_batches": w["sharded_batches"],
                "pending_window_bytes": w["window_bytes"],
                "pending_window_bytes_padded": w["window_bytes_padded"],
                "pending_window_reduction": round(
                    w["window_bytes_padded"] / max(1, w["window_bytes"]),
                    1,
                ),
            }
        )
        if w["wave_batches"] == 0:
            out["error"] = "wave dispatch never engaged"
        elif sharded and w["sharded_batches"] != w["wave_batches"]:
            out["error"] = "wave batches did not all execute SPMD"
    finally:
        if saved is None:
            os.environ.pop("TB_DEV_WAVES", None)
        else:
            os.environ["TB_DEV_WAVES"] = saved
    return out


def run_device_waves_compare() -> dict:
    """Wave dispatch vs host drain for the device engine's off-kernel
    batches, single-chip AND row-sharded configurations.  `speedup` is
    the wave arm's throughput over the drain arm's on this hour's
    backend, `steps_per_batch` the collapse the partitioner achieved
    (a two_phase-pair batch is ~3 steps, a chain batch ~max_chain_len
    — vs one semantic drain per batch), and the `sharded` sub-record
    runs the same comparison with the engine's tables row-sharded
    (real multi-device backend when available, else a forced
    host-platform mesh in a subprocess — honestly marked)."""
    n = int(os.environ.get("BENCH_DEV_WAVES_N", 16_380 if SMALL else 65_520))
    out = _run_device_waves_arms(n, sharded=False)
    out["sharded"] = _run_device_waves_sharded()
    # Optimistic execution (r18): speculate-on/off/forced per config.
    out["speculate"] = run_speculate_compare()
    return out


def _run_device_waves_sharded() -> dict:
    """The sharded device_waves configuration: inline when this
    backend already exposes >= 2 devices (a real multi-chip link),
    else in a subprocess with a forced 4-device host-platform CPU mesh
    — the NamedSharding/shard_map code path is identical; only the
    interconnect is fake, and `forced_host_platform` says so."""
    import subprocess

    import jax

    n = int(os.environ.get("BENCH_DEV_WAVES_SHARDED_N", 16_380))
    if len(jax.devices()) >= 2:
        return _run_device_waves_arms(n, sharded=True)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    # The child deliberately runs the forced CPU mesh: skip its
    # accelerator probe (forced_host_platform marks the row).
    env["TB_BENCH_DEVICE_CHECKED"] = "cpu"
    env.setdefault("TB_DEV_B", "512")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--device-waves-sharded-only"],
            env=env, capture_output=True, text=True,
            timeout=float(os.environ.get("BENCH_CONFIG_TIMEOUT_S", 3600)),
        )
    except subprocess.TimeoutExpired:
        return {"error": "sharded subprocess timed out"}
    if proc.returncode != 0:
        return {
            "error": f"sharded subprocess rc={proc.returncode}",
            "tail": (proc.stderr or "")[-1000:],
        }
    try:
        got = json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as exc:
        return {
            "error": f"unparseable sharded output: {exc}",
            "tail": (proc.stdout or "")[-500:]
            + (proc.stderr or "")[-500:],
        }
    got["forced_host_platform"] = True
    return got


# Workload configs the speculation comparison grades (ISSUE r18): the
# BENCH_r06 shapes, so hit rates line up with the known wave structure
# (simple/zipf/mixed commit in ~1 wave, two_phase in 2, linked is
# serial-dominated).
SPECULATE_CONFIGS = ("simple", "zipf", "mixed", "two_phase", "linked")


def _spec_counter_values(sm) -> dict:
    return {
        name: handle.value
        for name, handle in sm._dev.spec_stats.items()
        if hasattr(handle, "value")
    }


def _run_speculate_config(name: str, n: int) -> dict:
    """Three same-session arms over ONE config's identical stream:

    - off:    TB_WAVES_SPECULATE=0 — production routing, pessimistic
              wave plans for whatever falls off the semantic kernels.
    - auto:   the default residue-cap-gated speculation.
    - forced: TB_WAVES_SPECULATE=force — EVERY window batch through
              the speculative dispatcher (the arm that measures
              speculation itself: hit rate, steps/batch, validation
              and residue-plan wall time).

    Replies must be bit-identical across arms; `forced` on a
    serial-dominated config (linked) is expected to LOSE — that loss
    is the number the auto gate exists to avoid, reported honestly."""
    import jax

    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

    gen = CONFIGS[name]
    saved = os.environ.get("TB_WAVES_SPECULATE")
    arms = {}
    try:
        for arm, mode in (("off", "0"), ("auto", "auto"),
                          ("forced", "force")):
            os.environ["TB_WAVES_SPECULATE"] = mode
            setup, timed, sizing = gen(n)
            cap = sizing[0]
            nd = len(jax.devices())
            if nd > 1 and cap % nd == 0:
                # Keep the engine DENSE: speculation declines on
                # row-sharded engines (scope cut, DESIGN.md r18) and a
                # sharded arm would silently grade the wave path.
                cap += 1
            # No kind-matrix prewarm: every generator's setup already
            # carries an untimed warm-up batch that compiles whichever
            # routing THIS arm uses for the workload's own shapes
            # (semantic kernels for off/auto, the speculative executor
            # + its residue path for forced) — a full waves prewarm
            # per arm (15 machines) would dominate the section's wall
            # time for shapes the stream never dispatches.
            sm = TpuStateMachine(
                account_capacity=cap, transfer_capacity=sizing[1],
                engine="device",
            )
            _, _, h = replay(sm, setup)
            _reset_dev_wave_stats(sm)
            sm.stat_host_semantic_events = 0
            t0 = time.perf_counter()
            futs = [(op, h.submit_async(op, body)) for op, body in timed]
            replies = [f.result() for _op, f in futs]
            sm.sync()
            elapsed = time.perf_counter() - t0
            arms[arm] = {
                "elapsed": elapsed,
                "replies": replies,
                "spec": _spec_counter_values(sm),
                "wave_batches": sm.stat_dev_wave_batches,
                "wave_steps": sm.stat_dev_wave_steps,
                "plan_s": sm.stat_dev_wave_plan_s,
                "host_events": sm.stat_host_semantic_events,
            }
            del sm, h
    finally:
        if saved is None:
            os.environ.pop("TB_WAVES_SPECULATE", None)
        else:
            os.environ["TB_WAVES_SPECULATE"] = saved
    parity = "ok"
    for other in ("auto", "forced"):
        for i, (a, b) in enumerate(
            zip(arms["off"]["replies"], arms[other]["replies"])
        ):
            if a != b:
                parity = f"{other} reply[{i}] differs"
                break
    n_timed = n_events_of(timed)

    def arm_row(a: dict) -> dict:
        st = a["spec"]
        attempts = st["attempts"]
        return {
            "events_per_sec": round(n_timed / a["elapsed"], 1),
            "spec_batches": attempts,
            "hit_rate": round(st["hits"] / attempts, 3) if attempts else None,
            "steps_per_batch": (
                round(st["steps"] / attempts, 2) if attempts else None
            ),
            "plan_skipped": st["plan_skipped"],
            "residue_events": st["residue_events"],
            "validation_ms": round(1e3 * st["validation_s"], 2),
            "residue_plan_ms": round(1e3 * st["residue_plan_s"], 2),
            # Host routing/admission time (decode+joins+admission, plus
            # the partitioner whenever it actually ran).
            "host_plan_ms": round(1e3 * a["plan_s"], 2),
            "wave_plan_batches": a["wave_batches"],
            "wave_plan_steps": a["wave_steps"],
        }

    return {
        "events": n_timed,
        "parity": parity,
        "off": arm_row(arms["off"]),
        "auto": arm_row(arms["auto"]),
        "forced": arm_row(arms["forced"]),
    }


def run_speculate_compare() -> dict:
    """Optimistic execution (TB_WAVES_SPECULATE) vs the pessimistic
    wave path, per workload config.  The `forced` arm's `hit_rate` and
    `steps_per_batch` are the acceptance numbers: simple/zipf batches
    must validate conflict-free and execute in ONE speculative device
    step with the partitioner never running (plan_skipped == batches);
    two_phase pairs miss and replay their finalizers as a one-wave
    residue (2 steps/batch); linked is serial-dominated — forced
    speculation loses there by design, and the `auto` arm shows the
    residue-cap gate refusing the bet."""
    n = int(os.environ.get("BENCH_SPECULATE_N", 16_380))
    out = {}
    for name in SPECULATE_CONFIGS:
        try:
            out[name] = _run_speculate_config(name, n)
        # tbcheck: allow(broad-except): one config's failure must not
        # void the others' rows — record it honestly and continue.
        except Exception as exc:
            out[name] = {"error": repr(exc)[:500]}
    return out


# ----------------------------------------------------------------------
# Hot/cold account tiering (TB_HOT_CAPACITY): forced-tiny hot set vs
# the all-resident oracle over one identical Zipf-head stream.


def _gen_tiering_stream(n_batches, batch, n_acct, head, tail_mass, tid0):
    """Zipf-head batches: near-uniform draws over a `head` that fits
    the hot budget plus a thin 1/rank tail over the other accounts.
    Hit accounting is per UNIQUE touched row per batch, so this is the
    shape where a residency cache can actually reach a >= 90% rate —
    a pure 1/rank draw concentrates on a handful of rows and caps the
    unique-hit numerator far below the budget."""
    rng = np.random.default_rng(45)
    p = np.zeros(n_acct)
    p[:head] = (1.0 - tail_mass) / head
    tail_rank = np.arange(1, n_acct - head + 1, dtype=np.float64)
    p[head:] = (1.0 / tail_rank) / (1.0 / tail_rank).sum() * tail_mass
    p /= p.sum()
    ops = []
    tid = tid0
    for _ in range(n_batches):
        dr = rng.choice(n_acct, size=batch, p=p).astype(np.uint64) + np.uint64(1)
        cr = rng.choice(n_acct, size=batch, p=p).astype(np.uint64) + np.uint64(1)
        clash = cr == dr
        cr[clash] = dr[clash] % np.uint64(n_acct) + np.uint64(1)
        ids = np.arange(tid, tid + batch, dtype=np.uint64)
        tid += batch
        ops.append((
            Operation.create_transfers,
            transfers_bytes(ids, dr, cr,
                            rng.integers(1, 100, batch, np.uint64)),
        ))
    return ops


def _run_tiering_arm(engine, hot, n_acct, warm_ops, timed_ops, sizing):
    """One arm: per-batch SYNCHRONOUS submits so the latency list is a
    true per-step distribution (the tiered arm's admission barrier —
    drain+flush+upload before the device step — lands inside the
    batch that paid it)."""
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
    from tigerbeetle_tpu.testing.harness import SingleNodeHarness

    if hot is None:
        os.environ.pop("TB_HOT_CAPACITY", None)
    else:
        os.environ["TB_HOT_CAPACITY"] = str(hot)
    sm = TpuStateMachine(
        engine=engine, account_capacity=sizing[0],
        transfer_capacity=sizing[1],
    )
    tier = sm._dev.hot
    assert (tier is not None) == (hot is not None)
    h = SingleNodeHarness(sm)
    h.submit(
        Operation.create_accounts, accounts_bytes(range(1, n_acct + 1))
    )
    for op, body in warm_ops:
        h.submit(op, body)
    if tier is not None:
        tier.hits = tier.misses = tier.evicts = 0
        tier.prefetch_stall_us = 0.0
    replies = []
    lat = []
    t0 = time.perf_counter()
    for op, body in timed_ops:
        t1 = time.perf_counter()
        replies.append(h.submit(op, body))
        lat.append(time.perf_counter() - t1)
    if hasattr(sm, "sync"):
        sm.sync()
    elapsed = time.perf_counter() - t0
    lat_ms = 1e3 * np.asarray(lat)
    n_events = sum(
        len(b) // types.TRANSFER_DTYPE.itemsize for _op, b in timed_ops
    )
    row = {
        "hot_capacity": 0 if hot is None else hot,
        "events": n_events,
        "events_per_sec": round(n_events / elapsed, 1),
        "step_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "step_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "state_root": sm.state_root().hex(),
    }
    if tier is not None:
        total = tier.hits + tier.misses
        row.update(
            hit_rate=round(tier.hits / total, 4) if total else None,
            evicts=tier.evicts,
            prefetch_stall_us=round(tier.prefetch_stall_us, 1),
            prefetch_stall_us_per_batch=round(
                tier.prefetch_stall_us / max(1, len(timed_ops)), 1
            ),
            tier_punts=sm.metrics.snapshot().get("dev_tier.punt", 0),
        )
    return row, replies


def run_tiering_compare() -> dict:
    """Device-resident hot set vs all-resident oracle (TB_HOT_CAPACITY,
    round 20): the tiered arm serves a 640-account Zipf-head stream
    from a 64-row hot window (logical touched set 10x the budget; the
    4096-row logical table is 64x), in BOTH engine modes.  Acceptance:
    hit_rate >= 0.90 and tiered step p99 within 2x the all-resident
    arm's, with replies and state roots bit-identical — the hot set is
    a residency optimization, never an observable behavior change."""
    from tigerbeetle_tpu.runtime import affinity

    n_acct, hot, head = 640, 64, 48
    batch = int(os.environ.get("BENCH_TIERING_BATCH", 256))
    n_batches = int(os.environ.get("BENCH_TIERING_BATCHES", 48))
    sizing = (1 << 12, (n_batches + 8) * batch + 1024)
    warm_ops = _gen_tiering_stream(4, batch, n_acct, head, 0.008, WARM0)
    timed_ops = _gen_tiering_stream(
        n_batches, batch, n_acct, head, 0.008, TID0
    )
    out = {
        "accounts_touched": n_acct,
        "hot_capacity": hot,
        "touched_over_hot": round(n_acct / hot, 1),
        "batch": batch,
        "events": n_batches * batch,
        "pinned_cores": {"replica0": affinity.plan(0)},
    }
    saved = os.environ.get("TB_HOT_CAPACITY")
    try:
        for engine in ("host", "device"):
            arms = {}
            parity = "ok"
            for arm, knob in (("all_resident", None), ("tiered", hot)):
                try:
                    arms[arm] = _run_tiering_arm(
                        engine, knob, n_acct, warm_ops, timed_ops, sizing
                    )
                # tbcheck: allow(broad-except): one arm's failure must
                # not void the other's row — record it and continue.
                except Exception as exc:
                    arms[arm] = ({"error": repr(exc)[:500]}, None)
            res_row, res_replies = arms["all_resident"]
            tier_row, tier_replies = arms["tiered"]
            if res_replies is not None and tier_replies is not None:
                for i, (a, b) in enumerate(zip(res_replies, tier_replies)):
                    if a != b:
                        parity = f"reply[{i}] differs"
                        break
                else:
                    if res_row["state_root"] != tier_row["state_root"]:
                        parity = "state roots differ"
            else:
                parity = "arm errored"
            row = {
                "all_resident": res_row,
                "tiered": tier_row,
                "parity": parity,
            }
            if "error" not in res_row and "error" not in tier_row:
                p99r = res_row["step_p99_ms"]
                row["p99_ratio"] = (
                    round(tier_row["step_p99_ms"] / p99r, 2) if p99r else None
                )
                row["pass_hit_rate"] = (tier_row.get("hit_rate") or 0) >= 0.90
                row["pass_p99_2x"] = (
                    row["p99_ratio"] is not None and row["p99_ratio"] <= 2.0
                )
                if engine == "host":
                    # Honest asymmetry marker: the host-mode oracle arm
                    # is write-behind with NO per-batch sync (flushes
                    # amortize across ~32 batches), while the tiered
                    # arm's admission barrier flushes on every miss
                    # batch — so its p99 carries a whole flush dispatch
                    # this link hides from the oracle.  The 2x step-
                    # latency acceptance targets the device engine
                    # (authoritative HBM table), graded above.
                    row["note"] = (
                        "oracle arm never syncs per batch in host mode;"
                        " 2x-p99 acceptance is the device-engine row"
                    )
            out[engine] = row
    finally:
        if saved is None:
            os.environ.pop("TB_HOT_CAPACITY", None)
        else:
            os.environ["TB_HOT_CAPACITY"] = saved
    return out


def run_memory_only(name: str) -> dict:
    """One in-memory config (+ its parity replay) for the
    --memory-only=NAME subprocess entry.  Parity rides along under
    __parity__ so the parent can split it out."""
    import traceback

    if name not in CONFIGS:
        return {"error": f"unknown config {name!r}"}
    gen = CONFIGS[name]
    try:
        out = _run_memory_config(name, gen)
    except Exception:  # noqa: BLE001
        out = {
            "error": "config raised",
            "tail": traceback.format_exc()[-2000:],
        }
    if PARITY:
        try:
            out["__parity__"] = _run_parity(name, gen)
        except Exception:  # noqa: BLE001
            out["__parity__"] = (
                "parity raised: " + traceback.format_exc()[-500:]
            )
    return out


def main() -> None:
    configs_out = {}

    # TOTAL-run budget: per-config timeouts alone cannot bound the
    # whole run (7 configs x 3600 s with a pathological device link —
    # d2h up to 25 s/round-trip was measured once — outlives any driver's
    # patience, and a driver-level kill loses the entire record, the
    # r4 failure mode at one remove).  Each config gets a share of
    # what remains (late configs inherit early configs' slack); when
    # the budget is gone, remaining configs are SKIPPED with an
    # honest row and the graded JSON line still prints in time.
    t_run0 = time.time()
    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET_S", 5400))
    # memory configs + waves compare + device-waves compare + durable
    # + replicated + open-loop + sharded-cluster + qos-suite
    # + read-scale + tiering + hash microbench
    n_configs_left = [len(CONFIGS) + 9]

    def next_timeout(cap_s: float) -> int | None:
        remaining = budget_s - (time.time() - t_run0)
        n = max(1, n_configs_left[0])
        n_configs_left[0] -= 1
        if remaining < 270:
            return None  # not enough left to learn anything: skip
        # The grant NEVER exceeds what remains (minus assembly
        # headroom): a floor or share factor that could overshoot
        # budget_s would reopen the driver-kill/lost-record hole this
        # budget exists to close.
        return int(min(cap_s, max(240, 1.5 * remaining / n), remaining - 30))

    _SKIP_ROW = {
        "error": "skipped: BENCH_TOTAL_BUDGET_S exhausted",
        "budget_skipped": True,
    }

    # EVERY config runs in a fresh subprocess with a timeout: durable/
    # replicated are disk/page-cache sensitive, the in-memory 1M
    # replays are heap-sensitive, and — decisive after this round's
    # wedge events — a mid-run accelerator hang inside ANY config must
    # cost that config its timeout, not the whole graded record (a
    # stuck JAX call cannot be interrupted in-process).  Per-config
    # engine prewarm is untimed and XLA compiles come from the
    # persistent cache, so isolation costs only setup seconds.
    # Errors are recorded, never raised.
    def run_isolated(flag: str, timeout_s: int | None = None) -> dict:
        res = _run_subprocess_config(flag, timeout_s=timeout_s)
        if (
            "error" in res
            and "exceeded" in res.get("error", "")
            and not _device_alive()
        ):
            # The accelerator wedged AFTER the startup probe passed:
            # every remaining device-touching config would burn its
            # full subprocess timeout on the same hang, and a record
            # finished on CPU-backed JAX is not a device record.
            print(
                "bench: accelerator wedged mid-run: refusing to degrade "
                "to CPU-backed numbers",
                file=sys.stderr,
            )
            raise SystemExit(2)
        return res

    parity_ok = True
    parity_detail = {}
    # The memory-only subprocess runs the config AND its full-stream
    # parity replay (the ~17k tx/s Python oracle), so it gets twice
    # the per-config budget cap.  Memory configs run FIRST so the
    # graded `simple` row lands before any slow disk/cluster config
    # can eat the budget.
    per_config_cap = float(os.environ.get("BENCH_CONFIG_TIMEOUT_S", 3600))
    for name in CONFIGS:
        t = next_timeout(2 * per_config_cap)
        if t is None:
            res = dict(_SKIP_ROW)
        else:
            res = run_isolated(f"--memory-only={name}", timeout_s=t)
        detail = res.pop("__parity__", None)
        configs_out[name] = res
        if PARITY:
            if detail is None:
                detail = "not run (config error: %s)" % res.get(
                    "error", "missing"
                )
            parity_detail[name] = detail
            if not detail.startswith("ok"):
                parity_ok = False

    # Wave-vs-scan same-session comparison (waves.py): both paths on
    # this hour's backend, bit-identical parity graded alongside.
    t = next_timeout(per_config_cap)
    waves_out = (
        dict(_SKIP_ROW) if t is None
        else run_isolated("--waves-only", timeout_s=t)
    )

    # Device-engine wave dispatch vs host drain for off-kernel window
    # batches (TB_DEV_WAVES), same-session, parity graded alongside.
    t = next_timeout(per_config_cap)
    device_waves_out = (
        dict(_SKIP_ROW) if t is None
        else run_isolated("--device-waves-only", timeout_s=t)
    )

    for cname, flag in (("durable", "--durable-only"),
                        ("replicated", "--replicated-only"),
                        ("open_loop", "--open-loop"),
                        ("sharded_cluster", "--sharded-cluster-only"),
                        ("qos_suite", "--qos-suite"),
                        ("read_scale", "--read-scale"),
                        ("tiering", "--tiering-only"),
                        ("hash_only", "--hash-only")):
        t = next_timeout(per_config_cap)
        configs_out[cname] = (
            dict(_SKIP_ROW) if t is None
            else run_isolated(flag, timeout_s=t)
        )

    simple = configs_out.get("simple", {})
    # Overall device-semantic share, event-weighted across every
    # config (incl. durable); errored configs contribute nothing.
    tot = sum(c.get("events", 0) for c in configs_out.values() if "error" not in c)
    dev_tot = sum(
        c.get("events", 0) * c.get("device_semantic_pct", 0.0) / 100.0
        for c in configs_out.values()
        if "error" not in c
    )
    out = {
        "metric": "create_transfers_commits_per_sec",
        "value": simple.get("events_per_sec"),
        "unit": "transfers/s",
        "vs_baseline": simple.get("vs_baseline"),
        "configs": configs_out,
        "waves": waves_out,
        "device_waves": device_waves_out,
        "device_semantic_pct_overall": round(100.0 * dev_tot / max(1, tot), 1),
        "parity": parity_ok if PARITY else None,
    }
    if PARITY and isinstance(waves_out, dict):
        for row in waves_out.values():
            if isinstance(row, dict) and row.get("parity", "ok") != "ok":
                parity_ok = False
                out["parity"] = False
    if PARITY and isinstance(device_waves_out, dict):
        if device_waves_out.get("parity", "ok") != "ok":
            parity_ok = False
            out["parity"] = False
        sharded_row = device_waves_out.get("sharded")
        if (
            isinstance(sharded_row, dict)
            and sharded_row.get("parity", "ok") != "ok"
        ):
            parity_ok = False
            out["parity"] = False
    try:
        # The hour's measured device->host round trip — context for
        # the device-engine numbers it caps.  Validated
        # at capture; a malformed externally-set value must not cost
        # the graded record.
        out["link_d2h_ms"] = float(os.environ["TB_BENCH_LINK_D2H_MS"])
    except (KeyError, ValueError):
        pass
    if PARITY:
        out["parity_detail"] = parity_detail
    try:
        out["regressions"] = trend_tripwire(configs_out)
    except Exception as exc:  # noqa: BLE001
        out["regressions"] = [f"tripwire failed: {exc!r}"]
    print(json.dumps(out))


def trend_tripwire(configs_out: dict) -> list[str]:
    """Per-merge trend check (VERDICT r3 #8, reference:
    src/scripts/devhub.zig:36-41): diff each config's throughput
    against the newest BENCH_r*.json and warn loudly on a >10% drop.
    The warning also lands in the output JSON so regressions can't
    ship unnoticed."""
    import glob
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    numbered = []
    for p in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.search(r"r(\d+)", os.path.basename(p))
        if m:
            numbered.append((int(m.group(1)), p))
    if not numbered:
        return []
    # Newest PARSEABLE record wins: a crashed round's file has
    # `"parsed": null` (r4), and comparing against nothing silently
    # disarms the tripwire — skip such files and fall back to the
    # newest round that actually recorded numbers (VERDICT r4 #1c).
    prev_cfgs = None
    prev_name = None
    for _n, p in sorted(numbered, reverse=True):
        try:
            with open(p) as f:
                prev = json.load(f)
            parsed = prev.get("parsed", prev)
            if not isinstance(parsed, dict):
                continue
            cfgs = parsed.get("configs")
            if isinstance(cfgs, dict) and cfgs:
                prev_cfgs = cfgs
                prev_name = os.path.basename(p)
                break
        except Exception:
            continue
    if prev_cfgs is None:
        return []
    warnings = []
    if prev_name:
        print(f"trend tripwire: comparing vs {prev_name}", file=sys.stderr)
    for name, cur in configs_out.items():
        old = prev_cfgs.get(name, {}).get("events_per_sec")
        new = cur.get("events_per_sec")
        if not old:
            continue
        if new is None:
            msg = f"{name}: {old:,.0f} ev/s -> ERROR ({cur.get('error')})"
            warnings.append(msg)
            print(f"BENCH REGRESSION {msg}", file=sys.stderr)
            continue
        if new < 0.9 * old:
            note = ""
            if (
                cur.get("engine") == "device"
                and prev_cfgs.get(name, {}).get("engine") != "device"
            ):
                note = (
                    " (expected: config moved to the device-authoritative "
                    "engine this round)"
                )
            msg = (
                f"{name}: {old:,.0f} -> {new:,.0f} ev/s "
                f"({100 * (new / old - 1):+.1f}%){note}"
            )
            warnings.append(msg)
            print(f"BENCH REGRESSION {msg}", file=sys.stderr)
    return warnings


def _device_alive(timeout_s: int | None = None) -> bool:
    """Probe the accelerator from a SUBPROCESS (a hang cannot infect
    this process).  A wedged driver can leave the child unkillable
    (D-state): kill, wait briefly, and report dead rather than block
    forever reaping it."""
    import subprocess

    proc = subprocess.Popen(
        [
            sys.executable, "-c",
            # Also time a small computed-array d2h round trip: the
            # graded throughput tracks it, so the hour's link health
            # is recorded alongside the numbers it explains.
            # "Alive" requires a NON-CPU backend: with no chip JAX
            # falls back to CpuDevice without a word, and a
            # responsive CPU must not count as a reachable
            # accelerator (the device-authoritative configs' one-hot
            # matmuls take hours there; r6 observed exactly this).
            "import time, jax, jax.numpy as jnp;"
            "assert any(d.platform != 'cpu' for d in jax.devices()),"
            " 'cpu-only backend';"
            "y = jax.jit(lambda a: a * 3 + 1)(jnp.zeros((256, 256)));"
            "jax.block_until_ready(y);"
            "t0 = time.perf_counter();"
            "_ = float(jnp.sum(y));"
            "print('ok', round((time.perf_counter() - t0) * 1000, 1))",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(
            timeout=timeout_s
            if timeout_s is not None
            else int(os.environ.get("BENCH_DEVICE_PROBE_S", 180))
        )
        if "ok" in (out or ""):
            try:
                os.environ["TB_BENCH_LINK_D2H_MS"] = str(
                    float(out.split()[1])
                )
            except (IndexError, ValueError):
                pass
            return True
        return False
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            pass
        return False


def ensure_device_responsive() -> None:
    """No chip, no record: probe the accelerator from a SUBPROCESS (a
    hang cannot infect this process) and exit 2 when it does not
    answer.  A benchmark that falls back to CPU-backed JAX writes CPU
    numbers under device names; this one refuses."""
    if os.environ.get("TB_BENCH_DEVICE_CHECKED"):
        return
    if _device_alive():
        os.environ["TB_BENCH_DEVICE_CHECKED"] = "tpu"
        return
    print(
        "bench: no responsive accelerator: refusing to record "
        "CPU-backed numbers",
        file=sys.stderr,
    )
    raise SystemExit(2)


if __name__ == "__main__":
    ensure_device_responsive()
    memory_only = [
        a.split("=", 1)[1] for a in sys.argv if a.startswith("--memory-only=")
    ]
    if "--waves-only" in sys.argv:
        print(json.dumps((run_waves_compare())))
    elif "--speculate-only" in sys.argv:
        print(json.dumps((run_speculate_compare())))
    elif "--device-waves-only" in sys.argv:
        print(json.dumps((run_device_waves_compare())))
    elif "--device-waves-sharded-only" in sys.argv:
        # Internal: the sharded configuration's forced-host-platform
        # subprocess entry (the parent stamps forced_host_platform).
        n = int(os.environ.get("BENCH_DEV_WAVES_SHARDED_N", 16_380))
        print(json.dumps(_run_device_waves_arms(n, sharded=True)))
    elif "--durable-only" in sys.argv:
        print(json.dumps((run_durable(N_OTHER))))
    elif "--replicated-only" in sys.argv:
        print(json.dumps((run_replicated(N_OTHER))))
    elif "--open-loop" in sys.argv:
        # Open-loop arrival mode: sustained-rate-vs-SLO curves
        # (p50/p99/p999 at 50/80/95/120% of measured capacity).
        print(json.dumps((run_open_loop())))
    elif "--sharded-cluster-only" in sys.argv:
        # Account-sharded multi-cluster scaling behind the 2PC router
        # (scaling efficiency vs shard count + in-doubt recovery).
        print(json.dumps((run_sharded_cluster())))
    elif "--qos-suite" in sys.argv:
        # Adversarial multi-tenant QoS arms (noisy-neighbor /
        # contention / cross-shard), graded on victim-tenant isolation.
        print(json.dumps((run_qos_suite())))
    elif "--read-scale" in sys.argv:
        # Root-attested follower read scale-out: read throughput vs
        # follower count with write p99 flat (round 19).
        print(json.dumps((run_read_scale())))
    elif "--tiering-only" in sys.argv:
        # Hot/cold account tiering (TB_HOT_CAPACITY): forced-tiny hot
        # set vs all-resident oracle, hit rate + step-latency ratio
        # + bit-identical parity (round 20).
        print(json.dumps((run_tiering_compare())))
    elif "--hash-only" in sys.argv:
        # SHA-256 engine x size x lane GB/s grid through the counted
        # ingress verify (round 23 hash-once commit path).
        print(json.dumps((run_hash_only())))
    elif memory_only:
        print(json.dumps((run_memory_only(memory_only[0]))))
    else:
        main()
