#!/usr/bin/env python3
"""`benchmarks/harness/serve.py` with the timed path broken underneath.

A test's configuration names this file as its launcher and sets
`BENCH_FAULT` in the server's environment; `BENCH_FAULT_REPLICA` (an
index) limits the fault to one replica.  The state machine's `commit`
is wrapped for `create_transfers`:

  state_unchanged   every third request is answered "all ok" and the
                    state is left as it was
  half_batch        only the first half of each request is committed
  answer_altered    every third reply gets one result row it should
                    not have
  commits_dropped   (with BENCH_FAULT_REPLICA) that replica answers and
                    commits nothing: what it would have received
                    through the exchange between replicas is left out
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)


def plant(fault: str) -> None:
    import numpy as np

    from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
    from tigerbeetle_tpu.types import CREATE_RESULT_DTYPE, Operation

    original = TpuStateMachine.commit
    seen = [0]

    def commit(self, client, op, timestamp, operation, input_bytes):
        if operation != Operation.create_transfers:
            return original(self, client, op, timestamp, operation, input_bytes)
        seen[0] += 1
        third = seen[0] % 3 == 0
        if fault == "commits_dropped" or (fault == "state_unchanged" and third):
            return b""
        if fault == "half_batch":
            half = len(input_bytes) // 256 * 128
            return original(self, client, op, timestamp, operation,
                            input_bytes[:half])
        reply = original(self, client, op, timestamp, operation, input_bytes)
        if fault == "answer_altered" and third:
            row = np.zeros(1, CREATE_RESULT_DTYPE)
            row["index"], row["result"] = 0, 21
            reply = row.tobytes() + reply
        return reply

    TpuStateMachine.commit = commit


def main(argv: list[str]) -> None:
    fault = os.environ["BENCH_FAULT"]
    only = os.environ.get("BENCH_FAULT_REPLICA")
    replica = next((a.split("=", 1)[1] for a in argv if a.startswith("--replica=")), "0")
    if only is None or only == replica:
        plant(fault)
    from benchmarks.harness import serve

    serve.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
