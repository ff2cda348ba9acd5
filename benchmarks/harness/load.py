"""The load generator: sessions over TCP, on the client's clock.

Each session is one thread with one `tigerbeetle_tpu.client.Client`
(the program's own wire client; its request call runs in native code
with the interpreter lock released), given the configuration's
addresses in their own order and kept from the warm requests to the
window's end.  How a session paces its requests inside the window is
the traffic's `loop`, a module of `harness/loops/` found by name.
Nothing here imports JAX.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from tigerbeetle_tpu.client import Client
from tigerbeetle_tpu.types import Operation

RESEND_S = 1.0      # the program's client resends after this long (native/tb_runtime.cpp)


@dataclass
class Record:
    session: int
    index: int
    events: int             # rows of the request's body
    t_send: float           # time.perf_counter()
    t_reply: float
    reply: bytes | None     # None: the request failed
    error: str | None = None

    @property
    def latency_ms(self) -> float:
        return 1e3 * (self.t_reply - self.t_send)


@dataclass
class Sessions:
    """`n` clients against one cluster; requests are numbered per
    session and never repeat, whatever phase sends them."""

    addresses: str
    cluster: int
    gen: object
    n: int
    timeout_ms: int
    clients: list = field(default_factory=list)
    next_index: list = field(default_factory=list)
    records: list = field(default_factory=list)

    def connect(self) -> None:
        self.clients = [
            Client(self.addresses, self.cluster, timeout_ms=self.timeout_ms)
            for _ in range(self.n)]
        self.next_index = [0] * self.n

    def set_timeout(self, timeout_ms: int) -> None:
        for c in self.clients:
            c.timeout_ms = timeout_ms

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.clients.clear()

    def send_one(self, k: int, out: list) -> None:
        index = self.next_index[k]
        self.next_index[k] = index + 1
        rows = self.gen.request(k, index)
        body = rows.tobytes()
        t_send = time.perf_counter()
        try:
            reply = self.clients[k].request(Operation.create_transfers, body)
            error = None
        except Exception as exc:  # noqa: BLE001 — a failed request is a result
            reply, error = None, repr(exc)
        out.append(Record(k, index, len(rows), t_send, time.perf_counter(),
                          reply, error))

    def _run(self, body) -> list[Record]:
        outs = [[] for _ in range(self.n)]
        threads = [threading.Thread(target=body, args=(k, outs[k]))
                   for k in range(self.n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        done = [r for out in outs for r in out]
        self.records.extend(done)
        return done

    def fixed(self, requests_per_session: int) -> list[Record]:
        """Every session sends that many requests, and all have been
        answered when this returns (one phase of the warm-up)."""
        def body(k: int, out: list) -> None:
            for _ in range(requests_per_session):
                self.send_one(k, out)
                if out[-1].reply is None:
                    return
        return self._run(body)

    def window(self, seconds: float, shape: dict, loop) -> "Window":
        """The measured window, all sessions from one instant, each
        paced by `loop.session`.  The caller is free meanwhile
        (scrapes, the trace's triggers) and collects with `join()`."""
        gate = threading.Barrier(self.n + 1)
        win = Window()

        def body(k: int, out: list) -> None:
            gate.wait()
            loop.session(self, k, out, win, shape)

        win.runner = threading.Thread(
            target=lambda: win.records.extend(self._run(body)))
        win.runner.start()
        win.t0 = time.perf_counter()
        win.t1 = win.t0 + seconds
        gate.wait()
        return win


class Window:
    t0 = 0.0
    t1 = 0.0
    stop = False            # set by the caller to end the load early
    runner: threading.Thread

    def __init__(self) -> None:
        self.records: list[Record] = []

    def join(self) -> list[Record]:
        self.runner.join()
        return self.records


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    if not sorted_values:
        raise ValueError("no sample")
    rank = max(1, -(-len(sorted_values) * q // 1))      # ceil
    return sorted_values[int(rank) - 1]
