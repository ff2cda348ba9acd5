"""Deterministic device-link chaos injection.

The device-authoritative engine funnels every host<->device crossing
through one seam (device_engine.DeviceLink: "h2d" uploads, "dispatch"
kernel launches, "fetch_start" d2h copies started at dispatch, "fetch"
d2h reads, "probe" health checks).  ChaosLink
interposes on that seam with a SEEDED fault plan, so CPU-only tests can
drive the full degraded-mode lifecycle — transient-retry, fatal loss,
demote, serve-degraded, re-promote + checksum handshake — with no TPU
and byte-reproducible schedules (the VOPR discipline applied to the
accelerator link; reference: src/testing/storage.zig fault injection).

Fault kinds per crossing:
- transient: raises TransientLinkError once (a retry succeeds);
- fatal: raises FatalLinkError (classification skips the retry budget);
- down: every crossing fails fatally until heal()/auto-heal — a lost
  link;
- delay: sleeps a bounded jittered time first (pacing, not failure).
"""

from __future__ import annotations

import time

import numpy as np

from tigerbeetle_tpu.state_machine.device_engine import (
    DeviceLink,
    FatalLinkError,
    TransientLinkError,
)

STAGES = ("h2d", "dispatch", "fetch_start", "fetch", "probe")


class ChaosLink(DeviceLink):
    """Fault-injecting DeviceLink shim, seeded and deterministic.

    Probabilistic faults (per crossing, only on stages in `stages`):
    `p_transient`, `p_fatal`, `p_kill` (goes down for `down_for`
    crossings, then auto-heals), `p_delay`/`delay_s`.  Scripted faults:
    `fail_next(stage=..., kind=..., count=...)` queues exact faults for
    the next matching crossings, and `kill()`/`heal()` toggle hard
    loss — both for tests that target one pipeline stage precisely.
    """

    def __init__(
        self,
        seed: int = 0,
        *,
        p_transient: float = 0.0,
        p_fatal: float = 0.0,
        p_kill: float = 0.0,
        down_for: int = 4,
        p_delay: float = 0.0,
        delay_s: float = 0.0,
        stages: tuple[str, ...] = STAGES,
    ) -> None:
        self.rng = np.random.default_rng(seed)
        self.p_transient = p_transient
        self.p_fatal = p_fatal
        self.p_kill = p_kill
        self.down_for = down_for
        self.p_delay = p_delay
        self.delay_s = delay_s
        self.stages = tuple(stages)
        self.down = False
        self._down_left = 0  # crossings left before auto-heal (0: manual)
        self._scripted: list[tuple[str | None, str]] = []
        # Forensics the tests assert on.
        self.crossings = 0
        self.stat_transient = 0
        self.stat_fatal = 0
        self.stat_kills = 0
        self.stat_delays = 0

    # -- fault controls -------------------------------------------------

    def kill(self, *, down_for: int = 0) -> None:
        """Hard link loss; heals after `down_for` crossings (0: only an
        explicit heal() brings it back)."""
        self.down = True
        self._down_left = down_for
        self.stat_kills += 1

    def heal(self) -> None:
        self.down = False
        self._down_left = 0

    def fail_next(
        self,
        stage: str | None = None,
        kind: str = "fatal",
        count: int = 1,
    ) -> None:
        """Queue `count` scripted faults for the next crossings that
        match `stage` (None: any stage).  kind: "transient"/"fatal"."""
        assert kind in ("transient", "fatal"), kind
        assert stage is None or stage in STAGES, stage
        self._scripted.extend([(stage, kind)] * count)

    # -- injection core -------------------------------------------------

    def _raise(self, kind: str, stage: str, why: str) -> None:
        message = f"chaos: {why} ({stage} crossing {self.crossings})"
        if kind == "transient":
            self.stat_transient += 1
            raise TransientLinkError(message)
        self.stat_fatal += 1
        raise FatalLinkError(message)

    def _cross(self, stage: str) -> None:
        self.crossings += 1
        if self.down:
            if self._down_left:
                self._down_left -= 1
                if self._down_left == 0:
                    self.down = False
            self._raise("fatal", stage, "link down")
        for i, (want_stage, kind) in enumerate(self._scripted):
            if want_stage is None or want_stage == stage:
                del self._scripted[i]
                self._raise(kind, stage, f"scripted {kind}")
        if stage not in self.stages:
            return
        # One rng draw per armed fault class, in a FIXED order, so a
        # schedule replays identically for a given seed regardless of
        # which faults fire.
        if self.p_kill and self.rng.random() < self.p_kill:
            self.kill(down_for=self.down_for)
            self._raise("fatal", stage, "link down")
        if self.p_fatal and self.rng.random() < self.p_fatal:
            self._raise("fatal", stage, "injected fatal")
        if self.p_transient and self.rng.random() < self.p_transient:
            self._raise("transient", stage, "injected transient")
        if self.p_delay and self.rng.random() < self.p_delay:
            self.stat_delays += 1
            if self.delay_s > 0:
                time.sleep(self.delay_s * float(self.rng.random()))

    # -- DeviceLink surface ---------------------------------------------

    def device_put(self, array, sharding=None):
        self._cross("h2d")
        return super().device_put(array, sharding)

    def block_until_ready(self, arrays):
        self._cross("h2d")
        return super().block_until_ready(arrays)

    def copy_to_host_async(self, array) -> None:
        self._cross("fetch_start")
        super().copy_to_host_async(array)

    def fetch(self, array) -> np.ndarray:
        self._cross("fetch")
        return super().fetch(array)

    def dispatch(self, fn, *args):
        self._cross("dispatch")
        return super().dispatch(fn, *args)

    def probe(self) -> None:
        self._cross("probe")
        super().probe()


def device_chaos_factory(
    seed: int,
    *,
    account_capacity: int = 1 << 12,
    **chaos_kw,
):
    """-> (state_machine_factory, links) for the cluster/VOPR harness.

    Each machine the factory builds (initial replicas, restarts,
    restart-replay copies) gets its own deterministically-seeded
    ChaosLink, collected in `links` so a nemesis can kill/heal them
    mid-run.  Faults hit replicas at DIFFERENT times, yet the
    degraded-mode lifecycle keeps every reply bit-identical — which the
    cluster's hash-log convergence checker then enforces for free.
    """
    links: list[ChaosLink] = []

    def factory():
        from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

        link = ChaosLink(seed=seed + 101 * len(links), **chaos_kw)
        links.append(link)
        return TpuStateMachine(
            engine="device",
            account_capacity=account_capacity,
            device_link=link,
        )

    return factory, links
