#!/usr/bin/env python3
"""The benchmark's launcher for one replica.

    serve.py --ctl <dir> --trace 0|1 -- <arguments of `start`>

Runs `tigerbeetle_tpu.cli.main(["start", ...])` on its main thread,
unchanged: the server is the program as a user starts it.  Only the
process that holds a chip can say what lies on it, so one daemon
thread waits for the parent's requests in the control directory:

  mem.go          -> mem.json: the device's memory statistics
  trace_start.go  -> jax.profiler.start_trace; trace_start.json
  trace_stop.go   -> stop_trace; trace_stop.json (where the trace lies)

The trace requests are honoured only with `--trace 1`.  The thread
imports JAX when it is first asked something, which is after the
server has started: it changes nothing of the start-up.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

POLL_S = 0.02


def _write(path: str, obj: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def memory_stats() -> dict:
    import jax

    worst: dict = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use", -1) >= worst.get("peak_bytes_in_use", -1):
            worst = {k: int(v) for k, v in stats.items()
                     if isinstance(v, (int, float))}
    return worst


def control_loop(ctl: str, trace: bool) -> None:
    trace_dir = os.path.join(ctl, "trace")
    tracing = False
    while True:
        time.sleep(POLL_S)
        try:
            for name in os.listdir(ctl):
                if not name.endswith(".go"):
                    continue
                what = name[:-3]
                os.unlink(os.path.join(ctl, name))
                out = os.path.join(ctl, what + ".json")
                if what == "mem":
                    _write(out, memory_stats())
                elif what == "trace_start" and trace and not tracing:
                    import jax

                    options = jax.profiler.ProfileOptions()
                    # The runtime's own host events label the idle gaps;
                    # Python frames would only make the file larger.
                    options.python_tracer_level = 0
                    options.host_tracer_level = 2
                    jax.profiler.start_trace(trace_dir, profiler_options=options)
                    tracing = True
                    _write(out, {"t": time.time()})
                elif what == "trace_stop" and tracing:
                    import jax

                    t = time.time()
                    jax.profiler.stop_trace()
                    tracing = False
                    _write(out, {"t": t, "dir": trace_dir,
                                 "stop_took_s": time.time() - t})
                else:
                    _write(out, {"error": f"cannot {what!r} now"})
        except Exception as exc:  # noqa: BLE001 — the server must keep serving
            _write(os.path.join(ctl, "control_error.json"), {"error": repr(exc)})


def main(argv: list[str]) -> None:
    if "--" not in argv:
        sys.exit(__doc__)
    own, start_args = argv[:argv.index("--")], argv[argv.index("--") + 1:]
    opts = dict(zip(own[::2], own[1::2]))
    ctl = opts["--ctl"]
    trace = opts.get("--trace", "0") == "1"
    threading.Thread(target=control_loop, args=(ctl, trace), daemon=True,
                     name="bench-control").start()
    from tigerbeetle_tpu.cli import main as cli_main

    cli_main(["start", *start_args])


if __name__ == "__main__":
    main(sys.argv[1:])
