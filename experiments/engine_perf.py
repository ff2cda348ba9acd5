"""Real-TPU throughput of the device-authoritative engine at bench
scale (zipf-shaped workload), across stage/fetch tunings."""
import os
import sys
import time

sys.path.insert(0, "/root/repo")

import numpy as np

N = int(os.environ.get("PERF_N", "500000"))
BATCH = 8190


def main():
    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
    from tigerbeetle_tpu.testing.harness import SingleNodeHarness
    from tigerbeetle_tpu.types import Operation
    import bench

    rng = np.random.default_rng(45)
    n_acct = 100
    setup = [(Operation.create_accounts,
              bench.accounts_bytes(range(1, n_acct + 1)))]
    dr = rng.integers(1, n_acct + 1, N, np.uint64)
    timed = bench.batched({
        "ids": np.arange(1, N + 1, dtype=np.uint64),
        "dr": dr,
        "cr": dr % np.uint64(n_acct) + np.uint64(1),
        "amount": rng.integers(1, 100, N, np.uint64),
    })
    warm = bench.batched({
        "ids": np.arange(50_000_000, 50_000_000 + BATCH, dtype=np.uint64),
        "dr": dr[:BATCH], "cr": dr[:BATCH] % np.uint64(n_acct) + np.uint64(1),
        "amount": rng.integers(1, 100, BATCH, np.uint64),
    })

    sm = TpuStateMachine(
        engine="device", account_capacity=1 << 12,
        transfer_capacity=N + 3 * BATCH,
    )
    h = SingleNodeHarness(sm)
    for op, body in setup + warm:
        h.submit(op, body)
    sm.sync()
    eng0 = sm._dev
    eng0.stat_fetches = 0
    split = ("launch_us", "dispatch_us", "commit.update_us",
             "link.fetch_wait_us", "link.fetch_copy_us", "finish_us")
    # The engine's leaf stages are histograms, never reset: read the
    # sums as a window.
    before = {k: eng0.metrics.histogram(k).total for k in split}

    t0 = time.perf_counter()
    futs = [h.submit_async(op, body) for op, body in timed]
    t_submit = time.perf_counter() - t0
    replies = [f.result() for f in futs]
    sm.sync()
    dt = time.perf_counter() - t0
    print(f"  submit loop: {t_submit:.2f}s, resolve: {dt - t_submit:.2f}s")
    failed = sum(len(r) // 8 for r in replies)
    eng = sm._dev
    print(
        f"WINDOW={os.environ.get('TB_DEV_WINDOW', '96')}: "
        f"{N/dt:,.0f} ev/s  ({dt:.2f}s, failed={failed}, "
        f"fetches={eng.stat_fetches}, semantic={eng.stat_semantic_events})"
    )
    print("  split: " + " ".join(
        f"{k}={(eng.metrics.histogram(k).total - before[k]) / 1e6:.2f}s"
        for k in split
    ))


main()
