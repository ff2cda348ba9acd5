"""Differential check: TpuStateMachine(engine='device') vs CPU oracle
on scaled-down bench configs, running on the CPU backend."""
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=1"
).strip()

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, "/root/repo")
os.environ["BENCH_SMALL"] = "1"
os.environ["BENCH_BATCH"] = "500"

import numpy as np  # noqa: E402

import bench  # noqa: E402
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine  # noqa: E402
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine  # noqa: E402
from tigerbeetle_tpu.testing.harness import SingleNodeHarness  # noqa: E402

N = int(os.environ.get("DIFF_N", "6000"))

for name, gen in bench.CONFIGS.items():
    setup, timed, sizing = gen(N)
    ops = setup + timed
    sm_d = TpuStateMachine(
        account_capacity=sizing[0], transfer_capacity=sizing[1],
        engine="device",
    )
    h_d = SingleNodeHarness(sm_d)
    futs = [h_d.submit_async(op, body) for op, body in ops]
    replies_d = [f.result() for f in futs]

    sm_c = CpuStateMachine()
    h_c = SingleNodeHarness(sm_c)
    replies_c = [h_c.submit(op, body) for op, body in ops]

    bad = None
    for i, (a, b) in enumerate(zip(replies_d, replies_c)):
        if a != b:
            bad = i
            break
    if bad is not None:
        import numpy as np
        from tigerbeetle_tpu import types

        ra = np.frombuffer(replies_d[bad], dtype=types.CREATE_RESULT_DTYPE)
        rb = np.frombuffer(replies_c[bad], dtype=types.CREATE_RESULT_DTYPE)
        print(f"{name}: MISMATCH at op {bad} ({ops[bad][0]!r})")
        print("  device:", ra[:10])
        print("  oracle:", rb[:10])
        sys.exit(1)
    # state digest
    acct_ids = bench.config_account_ids(name)
    tids = np.arange(bench.TID0, bench.TID0 + min(2000, N)).astype(np.uint64)
    dg_d = bench.state_digest(h_d, acct_ids, tids)
    dg_c = bench.state_digest(h_c, acct_ids, tids)
    assert dg_d == dg_c, f"{name}: state digest mismatch"
    eng = sm_d._dev
    print(
        f"{name}: ok  semantic={eng.stat_semantic_events} "
        f"host={sm_d.stat_host_semantic_events} "
        f"fallback_batches={eng.stat_fallback_batches} "
        f"fetches={eng.stat_fetches}"
    )
print("ALL CONFIGS MATCH")
