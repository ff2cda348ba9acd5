"""Device-authoritative execution pipeline for create_transfers.

Owns the authoritative HBM balance table + account-meta table and a
stream of semantic-kernel dispatches (device_kernels.py).  The host
submits packed batches and gets back *reply futures*; result codes are
computed on device, come home as failure-sparse summary rows (512
bytes a batch, an output of the kernel itself), and materialize once
per execution window.

Execution model: a window crosses the link once each way
---------------------------------------------------------
Measured on the v5e (PERF.md sections 6 and 7.8, PR 27's probe): an
upload costs the host ~0.25 ms (160 KB or a scalar alike), a kernel's
dispatch ~0.3 ms, a 512-byte d2h 0.35-0.5 ms when it starts at the
fetch and 0.03 ms when it started at dispatch and the host had other
work meanwhile; the chip runs a padded batch in ~1.9 ms.  The host's
crossings bounded a prepare, not the kernel, so a window's schedule
is:

  submit()  appends the packed batch to a host-side window; NOTHING
            is dispatched until the window fills (TB_DEV_WINDOW) or
            is drained (the served path drains every prepare).
  launch    per dispatch unit (one batch, or a same-kind scan chunk):
            ONE upload that carries the batch's scalars in its last
            row, the dispatch behind it (the runtime orders the h2d
            before the kernel by data flow: nothing is waited for),
            and the start of the copy home of the unit's own summary
            rows (512 bytes a batch).  Then the digest's host work,
            under the kernels and the copies.
  rotate    at the window boundary: (1) wait for the PREVIOUS
            window's summary copies; (2) launch the new window;
            (3) only then run the previous window's host bookkeeping
            (finish callbacks), overlapped with the device crunching
            the new window.

A batch whose row counts more failures than its 60 entries hold (a
request of linked chains of which a few in a hundred fail: every leg
of a failed chain answers a code) brings its dense result codes home
too, B x u32 that the kernel leaves on the device beside the row: a
second crossing for that batch alone, chosen by the n_fail in hand.

A batch whose summary carries a fallback flag (balance overflow in
play, precondition violated) triggers exact
recovery BEFORE the next window launches: the host re-executes that
batch through the host engine (``fallback`` callback, which updates
the mirror), re-uploads the corrected table, and re-dispatches every
later in-flight record.  Replies stay exact for ANY input; the flags
only cost latency.

The pipeline also carries the write-behind lane the host exact path
uses (``enqueue``/``flush``, same contract as kernel_fast.DeviceTable)
so host-resolved batches keep the device table current in stream
order, and a device-side ``lookup`` used to serve lookup_accounts
balances from the authoritative table (not the host mirror).
"""

from __future__ import annotations

import os as _os
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from tigerbeetle_tpu import envcheck
from tigerbeetle_tpu.obs import stat_property as obs_stat_property
from tigerbeetle_tpu.state_machine import device_kernels as dk
from tigerbeetle_tpu.types import CREATE_RESULT_DTYPE, EngineState
from tigerbeetle_tpu.utils import tracer as tracer_mod

_WINDOW = envcheck.env_int("TB_DEV_WINDOW", 96, minimum=1)

# Link-robustness knobs: bounded retry with exponential backoff on
# every link crossing, a health-probe cadence for re-promotion out of
# degraded mode, and a checksum-scrub cadence during healthy operation
# (0 disables the scrub).  All read at call time so tests can tighten
# them per engine.
_RETRIES = envcheck.env_int("TB_DEV_RETRIES", 3, minimum=0)
_BACKOFF_MS = envcheck.env_float("TB_DEV_BACKOFF_MS", 5.0, minimum=0.0)
_BACKOFF_CAP_MS = envcheck.env_float(
    "TB_DEV_BACKOFF_CAP_MS", 200.0, minimum=0.0
)
_PROBE_EVERY = envcheck.env_int("TB_DEV_PROBE_EVERY", 8, minimum=1)
# r15: the healthy-mode scrub is a 16-byte incremental-digest compare
# (state_machine/commitment.py) instead of a full-table digest pass,
# so the default cadence drops from 256 to every TB_DEV_PROBE_EVERY
# fetches (the full-fetch compare survives only as the divergence-
# localization fallback).  Each scrub still pays one d2h crossing's
# latency — dev.scrub.cheap_us/fallback_us record the real split for
# the next chip session to retune against.
# The tight default only makes sense for the CHEAP scrub: an engine
# with the commitment disabled (TB_STATE_COMMIT=0) still pays the
# legacy full-digest compare per scrub, so it keeps the legacy 256
# unless the operator set the cadence explicitly (per-engine choice
# in __init__).
_SCRUB_EVERY_SET = envcheck.env_is_set("TB_DEV_SCRUB_EVERY")
_SCRUB_EVERY = envcheck.env_int("TB_DEV_SCRUB_EVERY", _PROBE_EVERY, minimum=0)
_SCRUB_EVERY_LEGACY = 256
# Maximum deterministic per-engine offset applied to the scrub cadence
# so every engine's TB_DEV_SCRUB_EVERY-th fetch doesn't land on the
# same window rotation (each scrub costs one dispatch and one 32-byte
# fetch).  -1 = auto (an eighth of the cadence).
_SCRUB_JITTER = envcheck.env_int("TB_DEV_SCRUB_JITTER", -1, minimum=-1)


def _validate_scrub_jitter(every: int, jitter: int) -> None:
    if every and jitter >= every:
        raise envcheck.EnvVarError(
            f"TB_DEV_SCRUB_JITTER={jitter} / TB_DEV_SCRUB_EVERY={every} "
            "invalid: the jitter offset must stay below the scrub "
            "cadence (TB_DEV_SCRUB_JITTER < TB_DEV_SCRUB_EVERY)"
        )


_validate_scrub_jitter(_SCRUB_EVERY, _SCRUB_JITTER)

# Per-process engine construction ordinal: the default scrub-jitter
# seed mixes it in so same-capacity engines sharing the link (the
# normal fleet configuration) still derive DIFFERENT offsets —
# deterministic for a fixed construction order, which is what replay
# needs.
_ENGINE_SEQ = 0


def _scrub_jitter_cap(every: int, jitter: int) -> int:
    """Effective jitter bound: the explicit knob, or auto = every//8."""
    if jitter >= 0:
        return jitter
    return every // 8 if every else 0


class LinkError(RuntimeError):
    """A device-link crossing failed (base for injected faults)."""


class TransientLinkError(LinkError):
    """Retryable: the crossing may succeed if reissued."""


class FatalLinkError(LinkError):
    """Not retryable: the link (or device state behind it) is gone."""


class DeviceLostError(RuntimeError):
    """The device link is lost: a crossing failed fatally or exhausted
    its retry budget.  Raised to callers only when no exact host
    answer exists (a stranded future after ``close()``); everywhere
    else the engine catches it and demotes to the host path."""

    def __init__(self, stage: str, cause: object = None) -> None:
        self.stage = stage
        self.cause = cause
        detail = f": {cause}" if cause is not None else ""
        super().__init__(f"device lost at {stage}{detail}")


# Link-error classes: message markers -> classification, FIRST MATCH
# WINS in declaration order.  JAX/PJRT surface gRPC-style status names
# in their messages; the transient rows are statuses a reissued
# crossing can outlive (backpressure, link flaps, deadline races),
# the fatal rows are states no retry fixes (bad program, lost buffers,
# corrupt device state, a program or table that does not fit:
# RESOURCE_EXHAUSTED is what the chip's compiler and allocator say
# then, and retrying it only delays the answer).  The table is
# DECLARATIVE so a marker harvested from a real link fault is added as
# one measured row — tests/test_device_engine.py asserts the
# classification of every entry.
LINK_ERROR_MARKERS = (
    ("RESOURCE_EXHAUSTED", "fatal"),
    ("UNAVAILABLE", "transient"),
    ("DEADLINE_EXCEEDED", "transient"),
    ("ABORTED", "transient"),
    ("CANCELLED", "transient"),
    ("temporarily", "transient"),
    ("INVALID_ARGUMENT", "fatal"),
    ("FAILED_PRECONDITION", "fatal"),
    ("NOT_FOUND", "fatal"),
    ("UNIMPLEMENTED", "fatal"),
    ("INTERNAL", "fatal"),
    ("DATA_LOSS", "fatal"),
)


def classify_link_error(exc: BaseException) -> str:
    """-> "transient" (retry may succeed) or "fatal" (demote)."""
    if isinstance(exc, TransientLinkError):
        return "transient"
    if isinstance(exc, (FatalLinkError, DeviceLostError)):
        return "fatal"
    msg = str(exc)
    for marker, kind in LINK_ERROR_MARKERS:
        if marker in msg:
            return kind
    return "fatal"


def raise_unless_link_lost(exc: "DeviceLostError") -> None:
    """Engine construction and prewarm: only a lost LINK may demote.
    A typed link fault (LinkError) or a status a link produces
    (the transient rows above, retries exhausted) returns; anything
    else — a kernel the compiler refuses, an allocation that does not
    fit, a bug — is a broken program, and re-raises as itself so the
    process fails instead of serving from the host with the chip
    idle."""
    cause = exc.cause
    if not isinstance(cause, BaseException):
        return
    if isinstance(cause, LinkError):
        return
    if classify_link_error(cause) == "transient":
        return
    raise cause


class DeviceLink:
    """Every host<->device crossing the engine makes, behind one seam.

    The engine never calls jax transfer/dispatch APIs directly; it
    goes through this object so the chaos harness (testing/chaos.py)
    can interpose a seeded fault-injecting shim, and so retry/
    classification lives in exactly one place (DeviceEngine._retry).
    Stages: "h2d" (uploads), "dispatch" (kernel launches),
    "fetch_start" (a d2h copy started, not waited for), "fetch" (d2h
    reads), "probe" (health check).
    """

    def device_put(self, array, sharding=None):
        if sharding is not None:
            return jax.device_put(array, sharding)
        return jax.device_put(array)

    def block_until_ready(self, arrays):
        return jax.block_until_ready(arrays)

    def copy_to_host_async(self, array) -> None:
        """Start the d2h copy of `array` behind the program that
        produces it; fetch() then finds the bytes on the host."""
        array.copy_to_host_async()

    def fetch(self, array) -> np.ndarray:
        return np.asarray(array)

    def dispatch(self, fn, *args):
        return fn(*args)

    def probe(self) -> None:
        """Tiny h2d + d2h round trip; raises if the link is dead."""
        echo = self.fetch(self.device_put(np.arange(4, dtype=np.uint64)))
        if int(echo[3]) != 3:
            raise FatalLinkError("probe round trip corrupted")


class ReplyFuture:
    """Reply bytes that materialize at the batch's window rotation.

    A future always terminates: it resolves with exact reply bytes
    (device summary, or host replay after a demotion) or fails with a
    typed error — ``result()`` never strands the caller in an assert
    when the link dies mid-window.
    """

    __slots__ = ("_value", "_engine", "_exc")

    def __init__(self, engine=None, value: bytes | None = None) -> None:
        self._value = value
        self._engine = engine
        self._exc: BaseException | None = None

    def done(self) -> bool:
        return self._value is not None or self._exc is not None

    def resolve(self, value: bytes) -> None:
        self._value = value

    def fail(self, exc: BaseException) -> None:
        self._exc = exc

    def result(self) -> bytes:
        if self._value is None and self._exc is None and (
            self._engine is not None
        ):
            self._engine.drain()
        if self._exc is not None:
            raise self._exc
        if self._value is None:
            raise DeviceLostError(
                "drain", "reply never materialized and no host replay ran"
            )
        return self._value


class PrepareReply:
    """The reply of a prepare whose batch the router cut into runs
    (`tpu.py _plan_runs`), each submitted as a batch of its own: the
    runs' futures with their offsets in the batch, in order.  Done
    when every run's is; the reply is theirs in order, each failure's
    index moved on by its run's offset: the whole batch's reply, byte
    for byte."""

    __slots__ = ("_runs",)

    def __init__(self, runs: list) -> None:
        self._runs = runs   # [(offset, ReplyFuture)]

    def done(self) -> bool:
        return all(fut.done() for _at, fut in self._runs)

    def result(self) -> bytes:
        out = []
        for at, fut in self._runs:
            reply = np.frombuffer(fut.result(), CREATE_RESULT_DTYPE).copy()
            reply["index"] += np.uint32(at)
            out.append(reply.tobytes())
        return b"".join(out)


class _InFlight:
    """One stream entry, in submission order (ordering matters for
    exact fallback recovery): a semantic batch, a wave-dispatched
    batch, a lookup gather, or an account-meta update."""

    __slots__ = (
        "kind", "pk", "n", "ts_base", "finish", "fallback", "future",
        "out", "row", "id_keys", "handle", "slots", "rows", "meta_args",
        "wave_args", "bound", "touched", "hot_slots",
    )

    def __init__(self, kind, future, finish, *, pk=None, n=0, ts_base=0,
                 fallback=None, id_keys=None, handle=None,
                 slots=None, meta_args=None, wave_args=None, bound=0,
                 hot_slots=None):
        self.kind = kind
        self.pk = pk
        self.n = n
        self.ts_base = ts_base
        self.finish = finish
        self.fallback = fallback
        self.future = future
        # A semantic batch's summary: row `row` of its dispatch
        # unit's output (_UnitOut), set at dispatch.
        self.out = None
        self.row = 0
        self.id_keys = id_keys  # sorted u128-packed ids (hazard probes)
        self.handle = handle    # lookup gather / wave packed-output handle
        self.slots = slots      # lookup slots, LOGICAL (host replay reads
                                # them against the mirror)
        self.hot_slots = hot_slots  # tiered device translation of slots
        self.rows = None        # lookup rows / wave outputs fetched at rotation
        self.meta_args = meta_args  # (slots, flags, ledger) for "meta"
        # (waves.PackedColumns, plan): the compact columnar record —
        # NOT the (B,)-padded event dict — rebuilt at launch.
        self.wave_args = wave_args
        # Balance rows this record's execution can modify (wave
        # records fill it at launch) — the incremental-commitment
        # update's input (commitment.py).
        self.touched = None
        # Host-integer bound on the balance additions this record can
        # still contribute (wave admission's in-flight term); released
        # when the record's bookkeeping lands on the mirror.
        self.bound = bound


class _UnitOut:
    """One dispatch unit's output: the summary's device handle from
    dispatch to the window's fetch, then its (G, SUMMARY_WORDS) numpy
    rows (G = 1 for a solo batch); and the unit's dense result codes,
    which stay on the device (`dense`) unless a row counts more
    failures than it has room for: then `codes` is their (G, B)
    numpy copy."""

    __slots__ = ("handle", "rows", "dense", "codes")

    def __init__(self, handle, dense) -> None:
        self.handle = handle
        self.rows = None
        self.dense = dense
        self.codes = None


# Speculative-execution forensics (ISSUE r18): counters named
# dev_wave.spec.* so the owning state machine's registry (and the
# stats-op scrape / flight postmortem built from it) shows them next
# to the dev_wave.* routing stats.  Standalone engines lazily build
# them on their private registry under the same names; the owning
# machine binds machine-registry handles right after construction.
_SPEC_COUNTER_NAMES = (
    "attempts",        # speculative launches dispatched
    "hits",            # batches validated conflict-free (1 device step)
    "plan_skipped",    # partitioner runs avoided (== hits by design)
    "residue_events",  # events replayed through a residue plan
    "steps",           # device-step equivalents incl. residue plans
    "validation_s",    # wall time: speculative dispatch + flags fetch
    "residue_plan_s",  # wall time: plan_residue on misses
)


def make_spec_stats(registry) -> dict:
    st = {
        name: registry.counter("dev_wave.spec." + name)
        for name in _SPEC_COUNTER_NAMES
    }
    st["validation_us"] = registry.histogram("dev_wave.spec.validation_us")
    return st


def make_touch_stats(registry) -> tuple:
    """plan.rows_touched and plan.row_legs_max: the distinct account
    rows a device batch touches and the legs on its most-touched row,
    a sample where the digest update takes the batch's unique
    (_commit_update_rows).  Same owning-machine-binds-handles contract
    as make_spec_stats."""
    return (
        registry.histogram("plan.rows_touched"),
        registry.histogram("plan.row_legs_max"),
    )


def make_tier_stats(registry) -> dict:
    """dev_tier.* handles for the hot/cold tiering (hot_tier.py) —
    same owning-machine-binds-handles contract as make_spec_stats."""
    st = {
        name: registry.counter("dev_tier." + name)
        for name in ("hit", "miss", "evict", "prefetch", "prefetch_stall_us")
    }
    st["prefetch_us"] = registry.histogram("dev_tier.prefetch_us")
    return st


_KERNELS = {
    "orderfree": dk.orderfree,
    "orderfree_lo": dk.orderfree_lo,
    "orderfree_tight": dk.orderfree_tight,
    "linked": dk.linked,
    "linked_small": dk.linked_small,
    "two_phase": dk.two_phase,
    "two_phase_lo": dk.two_phase_lo,
}
_SEMANTIC_KINDS = tuple(_KERNELS)

_MASK32_NP = np.uint64(0xFFFFFFFF)


def _tier_set_rows(table, idx, rows):
    """Overwrite table[idx] = rows; padding entries carry DISTINCT
    out-of-range indices (dropped — duplicates would void the
    unique_indices promise even for dropped entries)."""
    return table.at[idx].set(rows, mode="drop", unique_indices=True)


# No donation: the link layer may retry a transiently-failed dispatch,
# which must not find its input buffer already consumed.
_TIER_SET = jax.jit(_tier_set_rows)


def _touched_of_pk(kind: str, pk, n: int) -> np.ndarray:
    """Balance rows a packed semantic batch can modify, extracted from
    the HOST copy of the packed columns (a superset is fine — the
    commitment refresh of an unmodified row is a no-op).  Two-phase
    kernels also write the durable pending target's accounts
    (COL_TP_SLOTS); in-batch targets resolve to the creator event's
    own dr/cr slots, which the batch already covers."""
    pk = np.asarray(pk)
    if kind == "orderfree_tight":
        s = np.concatenate(
            [pk[:n, 1].astype(np.int64), pk[:n, 2].astype(np.int64)]
        ) - 1
        return s[s >= 0]
    w = pk[:n, dk.COL_SLOTS]
    parts = [
        (w & _MASK32_NP).astype(np.int64) - 1,
        (w >> np.uint64(32)).astype(np.int64) - 1,
    ]
    if kind in ("two_phase", "two_phase_lo"):
        w2 = pk[:n, dk.COL_TP_SLOTS]
        parts.append((w2 & _MASK32_NP).astype(np.int64) - 1)
        parts.append((w2 >> np.uint64(32)).astype(np.int64) - 1)
    s = np.concatenate(parts)
    return s[s >= 0]


class DeviceEngine:
    """Authoritative device tables + windowed semantic dispatch."""

    def __init__(self, capacity: int, mirror, link: DeviceLink | None = None,
                 seed: int | None = None, metrics=None) -> None:
        self.capacity = capacity
        self.mirror = mirror  # host bookkeeping copy (recovery + parity)
        # Hot/cold account tiering (hot_tier.py, TB_HOT_CAPACITY): when
        # active, the device tables hold only `hot.hot_rows` rows; the
        # mirror (+ _meta_host) is the full-logical cold tier, and
        # every submit path prefetches its touched-account set into the
        # hot window first (tier_prefetch).  None = all-resident.
        from tigerbeetle_tpu.state_machine import hot_tier as _hot_tier

        self.hot = _hot_tier.from_env(capacity)
        device_rows = capacity if self.hot is None else self.hot.hot_rows
        self.window = _WINDOW
        self.link = link if link is not None else DeviceLink()
        # Lifecycle (types.EngineState): healthy -> degraded on fatal
        # link loss (host mirror becomes authoritative, every
        # outstanding future is replayed exactly on the host) ->
        # repromoting (probe + table re-upload + checksum handshake)
        # -> healthy.
        self.state = EngineState.healthy
        self.last_demotion: str | None = None
        self.last_probe_failure: str | None = None
        self._degraded_submits = 0
        # Healthy-mode scrub cadence, jittered by a deterministic
        # per-engine offset (seeded) so a fleet of engines sharing the
        # link doesn't scrub on the same fetch ordinal — and so the
        # scrub's own fetch doesn't ride the identical window
        # rotation every cycle.  The offset only ADVANCES the first
        # scrub; the steady-state period stays TB_DEV_SCRUB_EVERY.
        global _ENGINE_SEQ
        _ENGINE_SEQ += 1
        if seed is None:
            seed = capacity + 0x85EBCA6B * _ENGINE_SEQ
        # Commitment on => cheap 16-byte scrubs => the tight default
        # cadence; commitment off (and no explicit operator cadence)
        # => every scrub is the legacy full-digest compare, keep 256.
        self._commit_enabled = envcheck.state_commit() == 1
        self._scrub_every = (
            _SCRUB_EVERY
            if (self._commit_enabled or _SCRUB_EVERY_SET)
            else _SCRUB_EVERY_LEGACY
        )
        cap = _scrub_jitter_cap(self._scrub_every, _SCRUB_JITTER)
        self._scrub_offset = (seed * 0x9E3779B9) % (cap + 1) if cap else 0
        self._last_scrub_fetch = -self._scrub_offset
        self._closed = False
        # Metrics registry handles (obs/registry.py): the owning state
        # machine passes a scoped view of ITS registry ("dev." prefix)
        # so one snapshot covers the whole engine; standalone engines
        # get a private registry.  A restore-recreated engine re-binds
        # the same handles — counters are process-lifetime cumulative.
        # Initialized before the first _place below can retry.
        from tigerbeetle_tpu import obs

        self.metrics = metrics if metrics is not None else obs.Registry()
        # Span/instant tracer (utils/tracer.py): NULL unless the owner
        # shares one — demotions/re-promotions then land as instants
        # on the merged cross-replica timeline.
        self.tracer = tracer_mod.NULL
        _c = self.metrics.counter
        self._stats = {
            "stat_retries": _c("link.retries"),
            "stat_link_errors": _c("link.errors"),
            "stat_semantic_events": _c("semantic_events"),
            "stat_fallback_batches": _c("fallback_batches"),
            "stat_fetches": _c("fetches"),
            # Batches whose failures outran the summary row, so that
            # their dense result codes crossed too (B x u32 each).
            "stat_dense_fetches": _c("summary.dense_fetches"),
            # How a window crossed: arrays the engine uploaded (every
            # link.device_put and every host array handed to a
            # program), and bytes _fetch brought home.
            "stat_puts": _c("link.puts"),
            "stat_fetch_bytes": _c("link.fetch_bytes"),
            # Degraded-mode lifecycle.
            "stat_demotions": _c("demotions"),
            "stat_repromotions": _c("repromotions"),
            "stat_probe_failures": _c("probe_failures"),
            "stat_degraded_events": _c("degraded_events"),
            "stat_scrubs": _c("scrubs"),
            "stat_scrub_heals": _c("scrub_heals"),
            # Incremental state commitment (commitment.py): digest
            # updates dispatched, cheap (16-byte) vs fallback
            # (full-fetch localization) scrub passes, full-table
            # fetches actually paid, and accumulator repairs (tables
            # matched but a digest drifted — should stay 0 forever).
            "stat_commit_updates": _c("commit.updates"),
            "stat_scrub_cheap": _c("commit.scrub_cheap"),
            "stat_scrub_fallback": _c("commit.scrub_fallback"),
            "stat_full_fetches": _c("commit.full_fetches"),
            "stat_commit_repairs": _c("commit.repairs"),
            # Wave-record memory + sharded-execution forensics.
            "stat_wave_window_bytes_peak": _c("wave.window_bytes_peak"),
            "stat_wave_window_padded_peak": _c("wave.window_padded_peak"),
            "stat_wave_sharded": _c("wave.sharded"),
        }
        # The engine's leaf stages (utils/tracer.py), in the order a
        # window passes through them; scraped as sm.dev.<name>_us where
        # the owning machine scopes this registry under "dev".  The
        # link.<stage>_us histograms below time single crossings and
        # enclose parts of these.
        _h = self.metrics.histogram
        self._st_launch = tracer_mod.Stage(_h("launch_us"), "sm.dev.launch")
        self._st_dispatch = tracer_mod.Stage(
            _h("dispatch_us"), "sm.dev.dispatch"
        )
        self._st_commit_update = tracer_mod.Stage(
            _h("commit.update_us"), "sm.dev.commit.update"
        )
        self._st_fetch_wait = tracer_mod.Stage(
            _h("link.fetch_wait_us"), "sm.dev.link.fetch_wait"
        )
        self._st_fetch_copy = tracer_mod.Stage(
            _h("link.fetch_copy_us"), "sm.dev.link.fetch_copy"
        )
        self._st_finish = tracer_mod.Stage(_h("finish_us"), "sm.dev.finish")
        # Who resolved what: batches and events per kind, counted
        # where semantic_events is; and the linked kernels' Jacobi
        # iterations (their cost), from the summary's flags word.
        self._kind_stats = {
            kind: (_c(f"kind.{kind}.batches"), _c(f"kind.{kind}.events"))
            for kind in _SEMANTIC_KINDS + ("waves", "spec")
        }
        self._h_linked_iters = _h("linked.iters")
        # Unsettled by the fixpoint's rounds: batches the exact pass by
        # blocks decided on the chip, the rows they held, and the pass's
        # rounds (a sample a batch that took it); of those batches, the
        # ones whose rounds stopped at the gate before the cap.
        self._c_settle_batches = self.metrics.counter("linked.settle_batches")
        self._c_settle_rows = self.metrics.counter("linked.settle_rows")
        self._h_settle_rounds = _h("linked.settle_rounds")
        self._c_gate_exits = self.metrics.counter("linked.gate_exits")
        # Per-stage crossing-latency histograms, hoisted so _retry
        # pays one dict lookup per crossing (no string building; the
        # shared no-op instances when TB_METRICS=0).
        self._link_hists = {
            stage: self.metrics.histogram(f"link.{stage}_us")
            for stage in ("h2d", "dispatch", "fetch_start", "fetch", "probe")
        }
        # The device table's rows: the kernels' accumulation and apply
        # visit every one, used or not (sm.accounts says how many are).
        self.metrics.gauge_fn(
            "table_rows",
            lambda: self.capacity if self.hot is None else self.hot.hot_rows,
        )
        # Cadence first-guesses as pull gauges + measured per-scrub
        # cost (ROADMAP "scrub/probe cadence tuning" carry-over): the
        # next real-link session reads the actual digest-compare cost
        # out of the same scrape that shows the cadence it ran at,
        # instead of re-deriving both from guesses.
        self.metrics.gauge_fn("scrub.every", lambda: self._scrub_every)
        self.metrics.gauge_fn("probe.every", lambda: _PROBE_EVERY)
        self._st_scrub = tracer_mod.Stage(
            self.metrics.histogram("scrub.cost_us"), "sm.dev.scrub.cost"
        )
        # Split scrub costs: the 16-byte digest compare vs the
        # full-fetch localization fallback — the next chip session
        # reads both (and the per-step digest-update overhead) off one
        # scrape (ROADMAP "scrub/probe cadence tuning").
        self._h_scrub_cheap = self.metrics.histogram("scrub.cheap_us")
        self._h_scrub_fallback = self.metrics.histogram("scrub.fallback_us")
        # Multi-device: the authoritative tables shard ROW-WISE across
        # every visible device (NamedSharding over a 1-D "shard" mesh);
        # the semantic kernels then run SPMD with XLA-inserted
        # collectives — the same dispatch code path single-chip uses
        # (exercised by __graft_entry__.dryrun_multichip on a virtual
        # CPU mesh).
        self.sharding = None
        devices = jax.devices()
        if len(devices) > 1 and device_rows % len(devices) == 0:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from tigerbeetle_tpu.parallel.sharded import make_row_mesh

            self.sharding = NamedSharding(
                make_row_mesh(devices), P("shard", None)
            )
        self._meta_host = np.zeros((capacity, 2), np.uint32)
        # Incremental state commitment (commitment.py): a device-side
        # (capacity, 2) per-row-hash array + (2,) u64 fold, updated
        # from just the rows each launch touched, with a bit-identical
        # host twin on the mirror (self._commit_enabled decided with
        # the scrub cadence above).  Standalone engines (unit tests)
        # get a twin keyed to the engine's own meta table; the owning
        # state machine attaches an attrs-backed twin BEFORE
        # constructing the engine.
        self.dev_row_hash = None
        self.dev_digest = None
        if self._commit_enabled and getattr(mirror, "commitment", None) is None:
            from tigerbeetle_tpu.state_machine import commitment as _cm

            mirror.commitment = _cm.HostCommitment(
                capacity, meta_fn=self._twin_meta
            )
        try:
            self.balances = self._place(
                jnp.zeros((device_rows, 8), jnp.uint64)
            )
            self.meta = self._place(jnp.zeros((device_rows, 2), jnp.uint32))
            self._commit_rebuild()
        except DeviceLostError as exc:
            raise_unless_link_lost(exc)
            # Born degraded: the link was already dead at construction.
            # Placeholders come from plain jnp (default backend, not the
            # link) so degraded-mode accessors have well-typed handles;
            # re-promotion replaces them from the mirror.
            self.state = EngineState.degraded
            self.last_demotion = repr(exc)
            self.balances = jnp.zeros((device_rows, 8), jnp.uint64)
            self.meta = jnp.zeros((device_rows, 2), jnp.uint32)
        # Window pipeline: _pending accumulates host-side; _launched is
        # the window currently executing on device; _recovering holds a
        # window mid-exact-recovery — detached from _launched so a
        # re-entrant drain (host fallbacks read the table, which
        # drains) cannot re-rotate it, but still owned so a demotion
        # mid-recovery replays its unresolved futures in order.
        self._pending: list[_InFlight] = []
        self._pending_semantic = 0
        self._launched: list[_InFlight] = []
        self._recovering: list[_InFlight] = []
        # Write-behind lane for host-resolved batches (exact path).
        self._q: list[tuple] = []
        self._queued = 0
        self._suppress_enqueue = False
        # Sum of in-flight records' contribution bounds (wave admission
        # accounts for batches the mirror has not materialized yet).
        self._inflight_bound = 0
        # dev_wave.spec.* handles: the owning state machine binds
        # machine-registry counters right after construction (and
        # after restore); standalone engines build them lazily on the
        # private registry at first speculative launch.
        self.spec_stats: dict | None = None
        # plan.rows_touched / plan.row_legs_max, bound the same way.
        self.touch_stats: tuple | None = None
        # Degraded-mode read() cache: (mirror version, capacity) ->
        # CPU-placed (capacity, 8) table handle.
        self._degraded_cache = None
    # Compatibility properties: every stat_* above reads/writes its
    # registry handle (ROADMAP D13).
    stat_retries = obs_stat_property("stat_retries")
    stat_link_errors = obs_stat_property("stat_link_errors")
    stat_semantic_events = obs_stat_property("stat_semantic_events")
    stat_fallback_batches = obs_stat_property("stat_fallback_batches")
    stat_fetches = obs_stat_property("stat_fetches")
    stat_dense_fetches = obs_stat_property("stat_dense_fetches")
    stat_puts = obs_stat_property("stat_puts")
    stat_fetch_bytes = obs_stat_property("stat_fetch_bytes")
    stat_demotions = obs_stat_property("stat_demotions")
    stat_repromotions = obs_stat_property("stat_repromotions")
    stat_probe_failures = obs_stat_property("stat_probe_failures")
    stat_degraded_events = obs_stat_property("stat_degraded_events")
    stat_scrubs = obs_stat_property("stat_scrubs")
    stat_scrub_heals = obs_stat_property("stat_scrub_heals")
    stat_wave_window_bytes_peak = obs_stat_property(
        "stat_wave_window_bytes_peak"
    )
    stat_wave_window_padded_peak = obs_stat_property(
        "stat_wave_window_padded_peak"
    )
    stat_wave_sharded = obs_stat_property("stat_wave_sharded")
    stat_commit_updates = obs_stat_property("stat_commit_updates")
    stat_scrub_cheap = obs_stat_property("stat_scrub_cheap")
    stat_scrub_fallback = obs_stat_property("stat_scrub_fallback")
    stat_full_fetches = obs_stat_property("stat_full_fetches")
    stat_commit_repairs = obs_stat_property("stat_commit_repairs")

    # ------------------------------------------------------------------
    # Link crossings: bounded retry + transient/fatal classification.
    # Every h2d upload, kernel dispatch, and d2h fetch funnels through
    # _retry, so a flaky link costs backoff, and a dead one raises ONE
    # typed error (DeviceLostError) that the lifecycle guards catch.

    def _retry(self, fn, stage: str):
        delay_s = _BACKOFF_MS / 1e3
        attempt = 0
        # Per-stage crossing latency — handles hoisted in __init__;
        # the no-op histogram when TB_METRICS=0 (no clock reads).
        hist = self._link_hists.get(stage)
        if hist is None:
            hist = self.metrics.histogram("link." + stage + "_us")
        while True:
            try:
                with hist.time():
                    return fn()
            except Exception as exc:  # noqa: BLE001
                if isinstance(exc, DeviceLostError):
                    raise
                self._stats["stat_link_errors"].inc()
                if (
                    classify_link_error(exc) != "transient"
                    or attempt >= _RETRIES
                ):
                    raise DeviceLostError(stage, exc) from exc
                attempt += 1
                self._stats["stat_retries"].inc()
                if delay_s > 0:
                    _time.sleep(delay_s)
                delay_s = min(delay_s * 2, _BACKOFF_CAP_MS / 1e3)

    def _put(self, array):
        self._stats["stat_puts"].inc()
        return self._retry(lambda: self.link.device_put(array), "h2d")

    def _arg(self, array):
        """A host array handed to a program as an argument: an upload
        of its own, counted with the puts."""
        self._stats["stat_puts"].inc()
        return jnp.asarray(array)

    def _run(self, fn, *args):
        return self._retry(lambda: self.link.dispatch(fn, *args), "dispatch")

    def _place(self, table):
        self._stats["stat_puts"].inc()
        return self._retry(
            lambda: self.link.device_put(table, self.sharding), "h2d"
        )

    def prewarm(self, kinds) -> None:
        """Pay the one-time per-process costs OFF the hot path: XLA
        compiles each kernel on first call, and the first upload of
        a shape may set up its transfer.
        TB_DEV_PREWARM names the kinds; engine construction happens
        before serving.

        The pseudo-kind "waves" warms the HOST-fallback wave executor
        (waves.py) against this engine's table geometry: a batch the
        router punts to the host path re-executes there, and with no
        native engine built that means wave/scan kernels whose first
        compile must not land inside a timed window."""
        if self.state is not EngineState.healthy:
            return
        try:
            self._prewarm_inner(kinds)
        # tbcheck: allow(broad-except): every failure is inspected —
        # a lost link demotes to the host path (degraded service
        # beats dying at setup); a compile error or an out-of-memory
        # is not a lost link and re-raises.
        except Exception as exc:
            lost = (
                exc if isinstance(exc, DeviceLostError)
                else DeviceLostError("prewarm", exc)
            )
            raise_unless_link_lost(lost)
            self._demote(lost)

    def _prewarm_inner(self, kinds) -> None:
        kinds = list(kinds)
        if "waves" in kinds:
            from tigerbeetle_tpu.state_machine import waves as _waves

            _waves.prewarm(self.capacity)
            mesh = self.wave_mesh()
            if mesh is not None:
                # Row-sharded engine: the window launch dispatches the
                # SPMD executors — warm those against this mesh so
                # sharded wave dispatch never first-compiles inside a
                # timed window.  (Speculation declines on sharded
                # engines, so no spec warm here.)
                _waves.prewarm(self.capacity, mesh=mesh)
            else:
                # The window launch dispatches the NON-DONATING twins
                # (separate XLA executables) — warm those too so wave
                # dispatch never first-compiles inside a timed window;
                # the speculative executor rides along unless disabled.
                _waves.prewarm(
                    self.capacity, engine=True,
                    spec=_waves.spec_mode() != "0",
                )
        if self._commit_enabled and self.dev_row_hash is not None:
            # Compile the digest-update kernel's smallest slot bucket
            # (every launch dispatches it) off the timed path.  An
            # all-padding slot array contributes nothing, so the
            # warmed dispatch cannot move the digest.
            from tigerbeetle_tpu.state_machine import commitment as _cm

            fns = _cm.device_fns()
            warm_pad = jnp.asarray(_cm.pad_slots(np.zeros(0, np.int64)))
            self._retry(
                lambda: self.link.block_until_ready(
                    self.link.dispatch(
                        fns["update"], self.balances, self.meta,
                        self.dev_row_hash, self.dev_digest,
                        warm_pad, warm_pad,
                    )
                ),
                "dispatch",
            )
        kinds = [k for k in kinds if k in _KERNELS]
        if not kinds:
            return
        scans = [G for G in dk.SCAN_SIZES if G <= self.window]
        table = jnp.zeros_like(self.balances)
        meta = jnp.zeros_like(self.meta)
        outs = []
        for k in kinds:
            ncols, dtype = dk.PK_SPEC[k]
            # An all-zero buffer is a batch of n = 0: nothing applies.
            pk = self._put(np.zeros((dk.ROWS, ncols), dtype))
            outs.append(_KERNELS[k](table, meta, pk))
            for G in scans:
                stack = self._put(np.zeros((G, dk.ROWS, ncols), dtype))
                outs.append(dk.scan_kernels[k][G](table, meta, stack))
        for out in outs:
            # The copy home of each output shape, as a launch makes it.
            self.link.copy_to_host_async(out[1])
        self._retry(lambda: self.link.block_until_ready(outs), "h2d")

    # ------------------------------------------------------------------
    # Account meta maintenance (create_accounts path).  Rides the
    # record stream so updates sequence between the batches around
    # them without forcing a drain.

    def add_accounts(self, slots, acct_flags, acct_ledger) -> None:
        slots = np.asarray(slots, np.int64)
        self._meta_host[slots, 0] = acct_flags
        self._meta_host[slots, 1] = acct_ledger
        # Meta is part of the committed row content: refresh the host
        # twin (the queued "meta" record folds the device side in at
        # its launch).
        if self.mirror.commitment is not None:
            self.mirror.commitment.refresh(slots, self.mirror)
        if self.state is not EngineState.healthy:
            # The host copy above is authoritative while degraded;
            # re-promotion re-uploads the whole meta table from it.  A
            # queued record would force a doomed launch at next drain.
            return
        self._queue_meta(
            slots,
            np.broadcast_to(
                np.asarray(acct_flags, np.uint32), slots.shape
            ).copy(),
            np.broadcast_to(
                np.asarray(acct_ledger, np.uint32), slots.shape
            ).copy(),
        )

    def remove_accounts(self, slots) -> None:
        """Linked create_accounts rollback support."""
        slots = np.asarray(slots, np.int64)
        self._meta_host[slots] = 0
        if self.mirror.commitment is not None:
            self.mirror.commitment.refresh(slots, self.mirror)
        if self.state is not EngineState.healthy:
            return  # see add_accounts
        z = np.zeros(len(slots), np.uint32)
        self._queue_meta(slots, z, z)

    def _queue_meta(self, slots, flags_u32, ledger_u32) -> None:
        """Queue a device meta update.  Tiered, meta records carry HOT
        slots (the map is stable until the next admission, which drains
        first), and cold rows are dropped — _meta_host stays the
        authority and admission uploads their meta."""
        if self.hot is not None:
            h = self.hot.translate(slots)
            keep = h >= 0
            if not keep.any():
                return
            slots = h[keep]
            flags_u32 = flags_u32[keep]
            ledger_u32 = ledger_u32[keep]
        self._pending.append(
            _InFlight(
                "meta", None, None,
                meta_args=(slots, flags_u32, ledger_u32),
            )
        )

    def grow(self, capacity: int) -> None:
        if capacity <= self.capacity:
            return
        self.drain()
        self.flush()
        old_capacity = self.capacity
        from tigerbeetle_tpu.state_machine.hot_tier import grow_zero_host

        self._meta_host = grow_zero_host(self._meta_host, capacity)
        # Capacity is committed before any link work: a demotion mid-
        # widen serves from the mirror at the NEW capacity, and
        # re-promotion rebuilds both tables from the mirror at it.
        self.capacity = capacity
        if self.hot is not None:
            # Tiered: the device tables keep their fixed hot-row
            # geometry — logical growth widens only the host maps (the
            # new rows are cold-zero, so the hot partial is untouched).
            self.hot.grow_logical(capacity)
            return
        was_sharded = self.sharding is not None
        if was_sharded and capacity % self.sharding.mesh.devices.size != 0:
            self.sharding = None  # re-place replicated from here on
        extra = capacity - old_capacity
        if self.state is not EngineState.healthy:
            return

        def widen(table, width, dtype):
            # Previously-sharded tables come back through the host (row
            # boundaries move between devices on grow, and a dropped
            # sharding must not leave a committed sharded base behind).
            base = (
                self._retry(lambda: self.link.fetch(table), "fetch")
                if was_sharded
                else table
            )
            return self._place(
                self._run(
                    jnp.concatenate, [base, jnp.zeros((extra, width), dtype)]
                )
            )

        try:
            self.balances = widen(self.balances, 8, jnp.uint64)
            self.meta = widen(self.meta, 2, jnp.uint32)
            # Zero rows hash to 0, so the widened digest VALUE is
            # unchanged — but the per-row hash array must match the
            # new geometry (and possibly a dropped sharding): rebuild.
            self._commit_rebuild()
        except DeviceLostError as exc:
            self._demote(exc)

    # ------------------------------------------------------------------
    # Hot/cold tiering (hot_tier.py): the batch planner calls
    # tier_prefetch with a batch's LOGICAL touched-account set BEFORE
    # packing; packed records then carry translated HOT slots.  The hot
    # map only ever changes against a quiesced pipeline (admission
    # drains + flushes first), so every in-flight record executes under
    # the map it was translated with, and eviction is free: after the
    # drain the mirror already holds every finished batch's effects —
    # the write-behind lane IS the dirty write-back path.

    def tier_prefetch(self, slots) -> bool:
        """Make every LOGICAL row in `slots` device-resident (negative
        entries ignored).  Returns False when the batch cannot run on
        device — touched set wider than the hot window, engine not
        healthy, or the link died mid-admission — and the caller takes
        the exact host path."""
        if self.hot is None:
            return True
        import time as _time

        hot = self.hot
        uniq, missing = hot.plan(np.asarray(slots, np.int64))
        if len(missing) == 0:
            hot.record_use(uniq, len(uniq), 0)
            return True
        if len(uniq) > hot.hot_rows:
            return False
        if self.state is not EngineState.healthy:
            return False
        t0 = _time.perf_counter()
        # Quiesce before the map moves (see section comment); the
        # drain can itself demote — re-check before touching the map.
        self.drain()
        self.flush()
        if self.state is not EngineState.healthy:
            return False
        got = hot.admit(missing, protect=uniq)
        if got is None:
            return False
        admitted, hot_slots, _evicted = got
        try:
            self._tier_upload(admitted, hot_slots)
        except DeviceLostError as exc:
            self._demote(exc)
            return False
        hot.record_use(uniq, len(uniq) - len(missing), len(missing))
        hot.note_stall(_time.perf_counter() - t0)
        return True

    def _tier_upload(self, admitted, hot_slots) -> None:
        """Upload admitted rows (balances + meta, straight from the
        cold tier) into their hot slots, and roll the device digest by
        the swap: the commitment "admit" kernel replaces the victim
        slots' row hashes with the host twin's hashes for the admitted
        rows — the digest stays the exact hot partial throughout."""
        if len(admitted) == 0:
            return
        from tigerbeetle_tpu.state_machine import commitment as _cm

        k = len(admitted)
        padded = _cm.pad_slots(np.asarray(hot_slots, np.int64))
        H = self.balances.shape[0]
        idx = np.where(
            padded >= 0, padded, H + np.arange(len(padded), dtype=np.int64)
        )
        bal = np.zeros((len(padded), 8), np.uint64)
        bal[:k] = self.mirror.rows8(admitted)
        meta = np.zeros((len(padded), 2), np.uint32)
        meta[:k] = self._meta_host[admitted]
        idx_j = self._put(idx)
        self.balances = self._run(
            _TIER_SET, self.balances, idx_j, self._put(bal)
        )
        self.meta = self._run(_TIER_SET, self.meta, idx_j, self._put(meta))
        if self._commit_enabled and self.dev_row_hash is not None:
            twin = self.mirror.commitment
            new_lo = np.zeros(len(padded), np.uint64)
            new_hi = np.zeros(len(padded), np.uint64)
            new_lo[:k] = twin.row_lo[admitted]
            new_hi[:k] = twin.row_hi[admitted]
            fns = _cm.device_fns()
            self.dev_row_hash, self.dev_digest = self._run(
                fns["admit"], self.dev_row_hash, self.dev_digest,
                self._put(padded), self._put(new_lo), self._put(new_hi),
            )

    # ------------------------------------------------------------------
    # Semantic dispatch.

    def submit(self, kind, pk, n, ts_base, finish, fallback,
               id_keys=None, bound=0) -> ReplyFuture:
        """Queue one semantic batch; returns its reply future.

        `finish(summary) -> bytes` runs at materialization (device codes
        -> bookkeeping + reply).  `fallback() -> bytes` re-executes the
        batch exactly on the host engine against the mirror.  `bound`
        upper-bounds the balance additions the batch can make (the
        wave path's in-flight admission term).

        In degraded mode the batch never touches the link: it resolves
        immediately through the exact host path (bit-identical reply).
        """
        dk.seal_scalars(pk, n, ts_base)
        return self._submit_record(
            n, fallback,
            lambda fut: _InFlight(
                kind, fut, finish, pk=pk, n=n, ts_base=ts_base,
                fallback=fallback, id_keys=id_keys, bound=bound,
            ),
        )

    def submit_waves(self, ev, dstat_init, n, ts_base, plan, hist_fix,
                     finish, fallback, id_keys=None, bound=0) -> ReplyFuture:
        """Queue one WAVE-DISPATCHED batch: a batch the semantic
        kernels cannot express, executed inside the window as the wave
        plan's segments (one device step per wave / chain position —
        waves.run_plan_engine) against the authoritative HBM table
        instead of draining to the host mirror.

        `ev` is the host-side (B,)-array event dict (kernel.py
        contract), `plan` the admitted WavePlan, `hist_fix` the
        snapshot-rewrite mask; `finish(packed_np) -> bytes` runs the
        exact-path bookkeeping from the fetched packed output at
        materialization, `fallback()` the drained host re-execution.
        The caller PROVED admission against mirror + the engine's
        in-flight bound, so the plan is never wrong — a wave record
        has no failure flag and never triggers exact recovery itself.

        The record does NOT retain the (B,)-padded dict: it stores the
        lossless columnar compaction (waves.pack_wave_record) and
        rebuilds the padded arrays at launch — a full pending window
        of wave records holds compact columns, not ~3 MB per batch
        (pending_window_bytes / ROADMAP "Wave-dispatch batch memory").
        """
        from tigerbeetle_tpu.state_machine import waves as _waves

        packed = _waves.pack_wave_record(ev, dstat_init, hist_fix, n)
        return self._submit_wave_like(
            "waves", packed, plan, n, ts_base, finish, fallback,
            id_keys, bound,
        )

    def submit_speculative(self, ev, dstat_init, n, ts_base, spec_serial,
                           pv_serial, finish, fallback, id_keys=None,
                           bound=0) -> ReplyFuture:
        """Queue one SPECULATIVE batch: no wave plan exists yet — at
        launch the ENTIRE batch executes as one validated device step
        (waves.run_speculative_engine) and only a conflicted residue
        replays through plan_waves (waves.plan_residue), so the
        partitioner runs exactly when validation fails.

        Everything else about the record is a wave record: the compact
        columnar codec (waves.pack_spec_record), the hazard-probe id
        keys, exact recovery (no failure flag — admission proved the
        overflow bound, so the fetched packed output always resolves),
        and the degraded-mode host fallback.  `bound` MUST be the
        whole-batch superset the wave path would charge — NOT the
        committed subset: a demotion mid-speculation replays the whole
        batch through the exact host fallback, and a smaller charge
        would let a sibling admission plan against headroom that
        replay then consumes (over-apply).  `pv_serial` records the
        submit-time routing fact (a pending target may sit on a
        history account) the residue planner must reuse."""
        from tigerbeetle_tpu.state_machine import waves as _waves

        packed = _waves.pack_spec_record(ev, dstat_init, spec_serial, n)
        return self._submit_wave_like(
            "spec", packed, bool(pv_serial), n, ts_base, finish,
            fallback, id_keys, bound,
        )

    def _submit_wave_like(self, kind, packed, extra, n, ts_base, finish,
                          fallback, id_keys, bound) -> ReplyFuture:
        """The shared tail of wave/speculative submission: one compact
        record on the stream + the pending-window memory peaks.
        `extra` is the kind's launch payload (the WavePlan for a wave
        record, the pv_serial routing fact for a speculative one)."""
        if self.hot is not None:
            # v1 tiering scope cut: the wave/speculative executors
            # index the table by LOGICAL slot inside their event dicts;
            # the router declines them (dev_wave.decline.tier) before
            # reaching here, so this guard only covers direct engine
            # callers — resolve exactly on the host.
            fut = ReplyFuture(self)
            self.drain()
            self.flush()
            self.stat_fallback_batches += 1
            self._resolve_host_now(fut, fallback)
            return fut
        fut = self._submit_record(
            n, fallback,
            lambda f: _InFlight(
                kind, f, finish, n=n, ts_base=ts_base,
                fallback=fallback, id_keys=id_keys, bound=bound,
                wave_args=(packed, extra),
            ),
        )
        compact, padded = self.pending_window_bytes()
        self.stat_wave_window_bytes_peak = max(
            self.stat_wave_window_bytes_peak, compact
        )
        self.stat_wave_window_padded_peak = max(
            self.stat_wave_window_padded_peak, padded
        )
        return fut

    def _spec_st(self) -> dict:
        st = self.spec_stats
        if st is None:
            st = self.spec_stats = make_spec_stats(self.metrics)
        return st

    def pending_window_bytes(self) -> tuple:
        """(compact, padded) host bytes retained by queued/in-flight
        wave records — what the window actually holds vs what the old
        padded event dicts would have held."""
        compact = padded = 0
        for rec in self._pending + self._launched + self._recovering:
            if rec.kind in ("waves", "spec") and rec.wave_args is not None:
                pk = rec.wave_args[0]
                compact += pk.nbytes
                padded += pk.padded_nbytes
        return compact, padded

    def wave_mesh(self):
        """Capability probe for SPMD wave dispatch: the row mesh when
        this engine's sharded tables support it — a 1-D ("shard",)
        mesh whose shard count divides the capacity — else None.  An
        unsupported mesh makes the router DECLINE wave submission
        (drain + host path, the r7 behavior), never error."""
        if self.sharding is None:
            return None
        mesh = self.sharding.mesh
        if tuple(mesh.axis_names) != ("shard",):
            return None
        if self.capacity % mesh.devices.size != 0:
            return None
        return mesh

    def _submit_record(self, n, fallback, make_rec) -> ReplyFuture:
        """The ONE stream-entry protocol for semantic and wave batches:
        degraded check -> flush (earlier exact-path deltas must
        precede) -> degraded re-check (the flush itself may lose the
        link; a queued record would force a doomed launch) -> enqueue
        + window-rotation trigger."""
        if self.state is not EngineState.healthy:
            fut = ReplyFuture(self)
            self.stat_degraded_events += n
            self._resolve_host_now(fut, fallback)
            return fut
        self.flush()
        if self.state is not EngineState.healthy:
            fut = ReplyFuture(self)
            self.stat_degraded_events += n
            self._resolve_host_now(fut, fallback)
            return fut
        fut = ReplyFuture(self)
        rec = make_rec(fut)
        self._pending.append(rec)
        self._pending_semantic += 1
        self._inflight_bound += rec.bound
        if self._pending_semantic >= self.window:
            try:
                self._rotate()
            except DeviceLostError as exc:
                self._demote(exc)
        return fut

    def inflight_bound(self) -> int:
        """Upper bound on balance additions submitted but not yet
        reflected in the mirror — the wave admission's `extra` term."""
        return self._inflight_bound

    def _release_bound(self, rec: _InFlight) -> None:
        """The record's bookkeeping reached the mirror (finish ran, or
        its host fallback/replay did): its contributions are no longer
        'in flight'.  Idempotent — bound zeroes on first release."""
        if rec.bound:
            self._inflight_bound -= rec.bound
            rec.bound = 0

    def lookup(self, slots, finish) -> ReplyFuture:
        """Device-side balance gather for lookup_accounts: rides the
        record stream, so it sees every earlier batch's effects.
        `finish(rows)` builds the reply from the fetched (k, 8) rows
        at materialization."""
        slots = np.asarray(slots, np.int64)
        if self.state is not EngineState.healthy:
            fut = ReplyFuture(self)
            self._resolve_host_now(
                fut, lambda: finish(self.mirror.rows8(slots))
            )
            return fut
        # Tiered: the gather indexes the hot-shaped device table, so
        # every looked-up row must be resident first.  If the batch
        # can't be made resident, drain + flush and answer from the
        # mirror — exact, since the drain materialized every earlier
        # batch's bookkeeping there.
        if not self.tier_prefetch(slots):
            fut = ReplyFuture(self)
            self.drain()
            self.flush()
            self._resolve_host_now(
                fut, lambda: finish(self.mirror.rows8(slots))
            )
            return fut
        # Earlier host-resolved batches' write-behind deltas must be
        # visible to the gather (found by the wave-dispatch fuzz: a
        # lookup queued behind only meta records — no semantic submit,
        # whose flush would have covered this — read the table without
        # the still-queued exact-path deltas).
        self.flush()
        if self.state is not EngineState.healthy:
            fut = ReplyFuture(self)
            self._resolve_host_now(
                fut, lambda: finish(self.mirror.rows8(slots))
            )
            return fut
        fut = ReplyFuture(self)
        rec = _InFlight(
            "lookup", fut, finish, slots=slots,
            hot_slots=(
                self.hot.translate(slots) if self.hot is not None else None
            ),
        )
        self._pending.append(rec)
        return fut

    @staticmethod
    def _resolve_host_now(fut: ReplyFuture, produce) -> None:
        try:
            fut.resolve(produce())
        except Exception as exc:  # noqa: BLE001
            # A host-path failure must still terminate the future; the
            # caller sees the real error at result().
            fut.fail(exc)
            raise

    def _gather(self, slots):
        pad = ((len(slots) + 255) & ~255) or 256
        sl = np.full(pad, -1, np.int64)
        sl[: len(slots)] = slots
        return self._run(dk.lookup, self.balances, self._arg(sl))

    # ------------------------------------------------------------------
    # Window launch: per dispatch unit one upload, the dispatch behind
    # it, and the start of its summary's copy home.

    def _plan_chunks(self, recs):
        """Group records into dispatch units: maximal same-kind
        semantic runs split into scan chunks (largest SCAN_SIZES
        first, exact decomposition — no padding), with meta/lookup
        records as unit boundaries."""
        units = []
        run = []
        for rec in recs:
            if rec.kind in _SEMANTIC_KINDS and (
                not run or run[-1].kind == rec.kind
            ):
                run.append(rec)
                continue
            if run:
                units.extend(self._split_run(run))
                run = []
            if rec.kind in _SEMANTIC_KINDS:
                run.append(rec)
            else:
                units.append((rec.kind, [rec]))
        if run:
            units.extend(self._split_run(run))
        return units

    @staticmethod
    def _split_run(run):
        out = []
        at = 0
        for G in dk.SCAN_SIZES:
            while len(run) - at >= G:
                out.append(("scan", run[at : at + G]))
                at += G
        for rec in run[at:]:
            out.append(("solo", [rec]))
        return out

    def _launch(self, recs: list[_InFlight]) -> None:
        """A window crosses the link once each way per dispatch unit:
        its inputs go up in ONE buffer that carries the scalars too
        (an upload or a Python-scalar argument costs ~0.25 ms each on
        the v5e, and jnp.uint64(ts_base) ran a 0.37 ms program of its
        own: tests/benchmarks/data/prepare-40ms.json), nothing is
        waited for before the dispatch (the runtime orders the h2d
        before the kernel by data flow; the block that stood here
        cost 0.5 ms a prepare: PERF.md section 6, PR 27), and each
        unit's summary rows start their copy home as soon as it is
        dispatched — so the digest's host work below runs under the
        kernels and the copies, not in front of them.  Same-kind runs
        go G batches per dispatch via lax.scan (a dispatch costs
        0.3-0.7 ms of host time a kernel)."""
        if not recs:
            return
        with self.tracer.stage(self._st_launch):
            units = self._upload_window(recs)
        with self.tracer.stage(self._st_dispatch):
            self._dispatch_units(units)
        # Absorb the whole window's touched rows into the on-device
        # commitment: one extra dispatch per launch.
        self._commit_absorb(recs)

    def _upload_window(self, recs: list[_InFlight]):
        """The sm.dev.launch stage: one upload per semantic dispatch
        unit — the batch's sealed buffer, or a scan chunk's stack of
        them.  Not waited for: the dispatch that reads a buffer
        is ordered behind its h2d by the runtime.
        -> [(unit kind, records, uploaded buffer or None)]"""
        units = []
        for ukind, urecs in self._plan_chunks(recs):
            dev_pk = None
            if ukind == "solo":
                dev_pk = self._put(urecs[0].pk)
            elif ukind == "scan":
                dev_pk = self._put(np.stack([r.pk for r in urecs]))
            units.append((ukind, urecs, dev_pk))
        return units

    def _dispatch_units(self, units) -> None:
        """The sm.dev.dispatch stage: the window's programs, back to
        back, every argument already a device array; each unit's
        output starts its copy home behind its own dispatch."""
        for ukind, urecs, dev_pk in units:
            kind = urecs[0].kind
            if ukind == "solo":
                self._run_semantic(_KERNELS[kind], dev_pk, urecs)
            elif ukind == "scan":
                self._run_semantic(
                    dk.scan_kernels[kind][len(urecs)], dev_pk, urecs
                )
            else:
                self._dispatch_aux(urecs[0])

    def _run_semantic(self, fn, dev_pk, urecs) -> None:
        self.balances, rows, dense = self._run(
            fn, self.balances, self.meta, dev_pk
        )
        out = _UnitOut(rows, dense)
        for g, rec in enumerate(urecs):
            rec.out = out
            rec.row = g
        self._start_fetch(rows)

    def _dispatch_aux(self, rec: _InFlight) -> None:
        """Dispatch one non-semantic record (meta update, lookup
        gather, wave or speculative batch)."""
        if rec.kind == "meta":
            slots, flags, ledger = rec.meta_args
            self.meta = self._run(
                dk.meta_update,
                self.meta, self._arg(slots), self._arg(flags),
                self._arg(ledger),
            )
            return
        if rec.kind == "lookup":
            rec.handle = self._gather(
                rec.hot_slots if rec.hot_slots is not None else rec.slots
            )
        elif rec.kind == "waves":
            self._exec_waves(rec)
        else:
            self._exec_spec(rec)
        self._start_fetch(rec.handle)

    def _dispatch(self, rec: _InFlight) -> None:
        """Immediate single-record dispatch (recovery re-dispatch)."""
        if rec.kind in _SEMANTIC_KINDS:
            self._run_semantic(
                _KERNELS[rec.kind], self._put(rec.pk), [rec]
            )
        else:
            self._dispatch_aux(rec)

    def _start_fetch(self, array) -> None:
        """Start `array`'s copy home behind the program just
        dispatched; _fetch waits for it at the window's rotation."""
        self._retry(
            lambda: self.link.copy_to_host_async(array), "fetch_start"
        )

    def _exec_waves(self, rec: _InFlight) -> None:
        """Execute a wave record's plan against the authoritative
        table.  The WHOLE batch rides one "dispatch" link crossing and
        the executor never donates the engine's table handle
        (waves.run_plan_engine), so a transient fault mid-plan retries
        the entire batch idempotently from the same `self.balances`.
        The packed per-event output handle is fetched at rotation like
        a lookup gather.  On a row-sharded engine the plan runs SPMD
        over the ("shard",) mesh (the router only admitted shardable
        plans there), and the new table comes back under the same
        NamedSharding row partition."""
        from tigerbeetle_tpu.state_machine import waves as _waves

        packed_rec, plan = rec.wave_args
        ev, dstat_init, hist_fix = _waves.unpack_wave_record(packed_rec)
        if self._commit_enabled:
            rec.touched = _waves.touched_slots(ev, rec.n)
        mesh = self.wave_mesh()

        def run():
            return self.link.dispatch(
                _waves.run_plan_engine, self.balances, ev, dstat_init,
                rec.n, rec.ts_base, plan, hist_fix, mesh,
            )

        new_balances, packed = self._retry(run, "dispatch")
        # Counted only AFTER the dispatch succeeded: a fatally-failed
        # SPMD launch that ends up served by host fallback must not
        # report as sharded execution in the forensics.
        if mesh is not None:
            self.stat_wave_sharded += 1
        self.balances = new_balances
        rec.handle = packed

    def _exec_spec(self, rec: _InFlight) -> None:
        """Execute a speculative record: ONE whole-batch device step
        with on-device conflict validation, a small flags fetch (the
        validation sync), then — only on a miss — plan_waves over the
        conflicted residue and a carry-threaded replay.  The executor
        never donates the engine's table handle and `self.balances`
        is reassigned only after the whole closure succeeded, so a
        transient fault anywhere (dispatch, validation fetch, residue
        replay) retries the entire batch idempotently from the same
        authoritative handle — exactly _exec_waves' contract."""
        from tigerbeetle_tpu.state_machine import resolve as _resolve
        from tigerbeetle_tpu.state_machine import waves as _waves

        packed_rec, pv_serial = rec.wave_args
        ev, dstat_init, spec_serial = _waves.unpack_spec_record(packed_rec)
        if self._commit_enabled:
            rec.touched = _waves.touched_slots(ev, rec.n)
        n = rec.n
        B = len(ev["flags"])

        def run():
            t0 = _time.perf_counter()
            carry, confl = self.link.dispatch(
                _waves.run_speculative_engine, self.balances, ev,
                dstat_init, spec_serial, n, rec.ts_base,
            )
            # THE validation sync: a (K,) bool fetch.  Blocking here is
            # the speculation tax — later records in the window read
            # self.balances, so the hit/miss verdict cannot defer to
            # rotation (a miss would leave residue effects unapplied
            # underneath them).
            confl_np = np.asarray(self.link.fetch(confl))[:n]
            val_s = _time.perf_counter() - t0
            residue = np.flatnonzero(confl_np)
            hist = np.zeros(B, bool)
            if len(residue) == 0:
                hist[:n] = True
                out = self.link.dispatch(
                    _waves.finalize_engine, carry, hist
                )
                return out, 0, 1, val_s, 0.0
            t1 = _time.perf_counter()
            meta = _resolve.spec_meta_from_events(ev, n, pv_serial)
            plan = _waves.plan_residue(n, meta, residue)
            plan_s = _time.perf_counter() - t1
            # Snapshot-rewrite mask: committed events rode the wave
            # step (finals), residue wave/chain events likewise; scan
            # residues keep their sequential-exact snapshots.
            hist[:n] = ~confl_np
            hist[:n] |= plan.wave_mask
            out = self.link.dispatch(
                _waves.continue_plan_engine, carry, ev, n, rec.ts_base,
                plan, hist,
            )
            return out, len(residue), 1 + plan.n_steps, val_s, plan_s

        st = self._spec_st()
        st["attempts"].inc()
        (new_balances, packed), residue_n, steps, val_s, plan_s = (
            self._retry(run, "dispatch")
        )
        self.balances = new_balances
        rec.handle = packed
        if residue_n == 0:
            st["hits"].inc()
            st["plan_skipped"].inc()
        else:
            st["residue_events"].inc(residue_n)
            st["residue_plan_s"].inc(plan_s)
        st["steps"].inc(steps)
        st["validation_s"].inc(val_s)
        st["validation_us"].observe(val_s * 1e6)

    # ------------------------------------------------------------------
    # Hazard probe: does any probe id match an in-flight batch's ids?

    def inflight_ids_hit(self, keys: np.ndarray, is_sorted: bool = False) -> bool:
        """keys: u128-packed (V16) id probes, any order (`is_sorted`:
        ascending already)."""
        stream = self._launched + self._pending
        if not stream or len(keys) == 0:
            return False
        if not is_sorted:
            keys = np.sort(keys)
        # V16 keys order numerically by their bytes; scalar compares go
        # through .tobytes() (numpy void scalars lack ufunc ordering).
        lo = keys[0].tobytes()
        hi = keys[-1].tobytes()
        for rec in stream:
            ik = rec.id_keys
            if ik is None or len(ik) == 0:
                continue
            if hi < ik[0].tobytes() or lo > ik[-1].tobytes():
                continue
            pos = np.searchsorted(ik, keys)
            pos = np.minimum(pos, len(ik) - 1)
            if (ik[pos] == keys).any():
                return True
        return False

    def has_inflight(self) -> bool:
        return bool(self._launched or self._pending)

    # ------------------------------------------------------------------
    # Rotation + materialization.

    def _fetch_window(self, recs) -> None:
        """Bring a launched window's outputs home: each dispatch
        unit's summary rows (512 bytes a batch) and each lookup/wave
        handle.  Their copies started at dispatch; this waits.  A
        batch whose row counts more failures than its FAIL_CAP
        entries hold brings its unit's dense codes home too (B x u32
        a batch): chosen by the row in hand, so a batch of few
        failures crosses what it always crossed."""
        if any(r.kind in _SEMANTIC_KINDS for r in recs):
            self.stat_fetches += 1
        for rec in recs:
            if rec.kind in _SEMANTIC_KINDS:
                out = rec.out
                if out.rows is None:
                    out.rows = self._fetch(out.handle).reshape(
                        -1, dk.SUMMARY_WORDS
                    )
                    out.handle = None
                if int(out.rows[rec.row][0]) > dk.FAIL_CAP:
                    self.stat_dense_fetches += 1
                    if out.codes is None:
                        out.codes = self._fetch(out.dense).reshape(-1, dk.B)
            elif rec.kind in ("lookup", "waves", "spec") and (
                rec.handle is not None
            ):
                rec.rows = self._fetch(rec.handle)
                rec.handle = None

    def _fetch(self, array) -> np.ndarray:
        """One fetch crossing of a launched window, in two leaves: the
        exposed wait for the program that produces `array`, then what
        is left of its copy home (started at dispatch) and the numpy
        view.  The link sees one crossing, and link.fetch_us encloses
        both."""

        def cross():
            with self.tracer.stage(self._st_fetch_wait):
                jax.block_until_ready(array)
            with self.tracer.stage(self._st_fetch_copy):
                return self.link.fetch(array)

        got = self._retry(cross, "fetch")
        self._stats["stat_fetch_bytes"].inc(got.nbytes)
        return got

    @staticmethod
    def _window_clean(recs) -> bool:
        for rec in recs:
            if rec.kind not in _SEMANTIC_KINDS:
                continue
            flags = int(rec.out.rows[rec.row][1])
            if flags & (dk.FLAG_OVERFLOW | dk.FLAG_PRECOND):
                return False
        return True

    @staticmethod
    def _summary_of(rec: _InFlight) -> dict:
        """A fetched semantic record's decoded summary."""
        out = rec.out
        return dk.unpack_summary(
            out.rows[rec.row],
            None if out.codes is None else out.codes[rec.row],
        )

    def _count_resolved(self, rec: _InFlight, summary=None) -> None:
        """The device computed `rec`'s result codes."""
        self.stat_semantic_events += rec.n
        batches, events = self._kind_stats[rec.kind]
        batches.inc()
        events.inc(rec.n)
        if rec.kind in ("linked", "linked_small"):
            self._h_linked_iters.observe(summary["iters"])
            if summary["settled"]:
                self._c_settle_batches.inc()
                self._c_settle_rows.inc(rec.n)
                self._h_settle_rounds.observe(summary["settle_rounds"])
                if summary["iters"] < dk.FIXPOINT_CAP:
                    self._c_gate_exits.inc()

    def _resolve_clean(self, recs) -> None:
        with self.tracer.stage(self._st_finish):
            self._resolve_clean_impl(recs)

    def _resolve_clean_impl(self, recs) -> None:
        for rec in recs:
            if rec.kind == "meta":
                continue
            if rec.kind == "lookup":
                rec.future.resolve(rec.finish(rec.rows))
                continue
            if rec.kind in ("waves", "spec"):
                self._count_resolved(rec)
                rec.future.resolve(rec.finish(rec.rows))
                self._release_bound(rec)
                continue
            s = self._summary_of(rec)
            self._count_resolved(rec, s)
            rec.future.resolve(rec.finish(s))
            self._release_bound(rec)

    def _rotate(self) -> None:
        """Window boundary: fetch the launched window's outputs, and —
        when it is clean — launch the pending window while the host
        still holds the fetched results, then finish the old window's
        bookkeeping overlapped with the new window's device work.

        Raises DeviceLostError on unrecoverable link loss; records are
        reassigned between _launched/_pending only AFTER the crossing
        that covers them succeeded, so the _demote caller always sees
        every unresolved record still in the stream lists, in order.
        """
        prev = self._launched
        if prev:
            self._fetch_window(prev)
        if prev and self._window_clean(prev):
            nxt = self._pending
            self._launch(nxt)  # may raise: prev + nxt stay tracked
            self._launched = nxt
            self._pending = []
            self._pending_semantic = 0
            self._resolve_clean(prev)  # host-only, cannot lose
            return
        if prev:
            # Fallback in the window: serial exact recovery first.
            # Detach prev into the recovery slot: the host fallbacks it
            # runs re-enter drain() via table reads, and a nested
            # rotate must NOT see this window as launched (it would
            # re-resolve it).  On device loss mid-recovery the records
            # stay in _recovering for _demote; on success the slot
            # clears.
            self._launched = []
            self._recovering = prev
            self._resolve_recovery(prev)
            self._recovering = []
        self._launched = []
        nxt = self._pending
        self._launch(nxt)  # may raise: nxt still in _pending
        self._launched = nxt
        self._pending = []
        self._pending_semantic = 0

    def _resolve_recovery(self, covered) -> None:
        """Exact recovery, from the window's own fetched rows: resolve
        in order until the flagged batch, host re-execute it (mirror
        becomes current), rebuild the device table, re-dispatch
        everything after it, fetch those, repeat until done."""
        while covered:
            failed_at = None
            for i, rec in enumerate(covered):
                if rec.kind == "meta":
                    continue
                if rec.kind == "lookup":
                    rec.future.resolve(rec.finish(rec.rows))
                    continue
                if rec.kind in ("waves", "spec"):
                    # Wave/speculative records carry no failure flag:
                    # admission proved the plan exact, so the fetched
                    # packed output (computed against the stream prefix
                    # before any LATER batch's fallback) resolves.
                    self._count_resolved(rec)
                    rec.future.resolve(rec.finish(rec.rows))
                    self._release_bound(rec)
                    continue
                s = self._summary_of(rec)
                if s["overflow"] or s["precond"]:
                    failed_at = i
                    self.stat_fallback_batches += 1
                    rec.future.resolve(rec.fallback())
                    self._release_bound(rec)
                    break
                self._count_resolved(rec, s)
                rec.future.resolve(rec.finish(s))
                self._release_bound(rec)
            if failed_at is None:
                return
            # Mirror reflects every batch up to and including the
            # fallback; rebuild the device table from it and replay
            # the rest in order.
            self._upload_from_mirror()
            covered = covered[failed_at + 1 :]
            for rec in covered:
                self._dispatch(rec)
            # The re-dispatched suffix mutated the rebuilt table: fold
            # its touched rows back into the commitment.
            self._commit_absorb(covered)
            self._fetch_window(covered)

    def _mirror_table_np(self) -> np.ndarray:
        """Device-layout (capacity, 8) snapshot of the host mirror."""
        return self.mirror.table8(self.capacity)

    def _mirror_hot_table_np(self) -> np.ndarray:
        """Hot-shaped (hot_rows, 8) host image of the device balance
        table — what the DEVICE table should equal under tiering."""
        from tigerbeetle_tpu.state_machine.hot_tier import mirror_hot_table8

        return mirror_hot_table8(self.mirror, self.hot.logical_of)

    def _meta_hot_np(self) -> np.ndarray:
        """Hot-shaped (hot_rows, 2) host image of the device meta
        table (zeros for free hot slots)."""
        lof = self.hot.logical_of
        out = np.zeros((len(lof), 2), np.uint32)
        occ = np.flatnonzero(lof >= 0)
        out[occ] = self._meta_host[lof[occ]]
        return out

    @staticmethod
    def _cpu_device():
        try:
            return jax.devices("cpu")[0]
        except RuntimeError:
            return None

    def _degraded_table(self):
        """Mirror-built table handle for degraded/recovering reads,
        pinned to the CPU backend — a deployment whose DEFAULT JAX
        backend is the lost device must not re-dispatch degraded
        work at it — and cached behind the mirror's version stamp so
        degraded reads stop rebuilding (capacity, 8) bytes per call
        (ROADMAP "Pin degraded-mode host compute")."""
        key = (self.mirror.version, self.capacity)
        if self._degraded_cache is not None and self._degraded_cache[0] == key:
            handle = self._degraded_cache[1]
            # The host exact path DONATES the table it reads (scan /
            # wave executors): a donated cache entry is dead — rebuild.
            if not handle.is_deleted():
                return handle
        table_np = self._mirror_table_np()
        cpu = self._cpu_device()
        handle = (
            jax.device_put(table_np, cpu)
            if cpu is not None
            else jnp.asarray(table_np)
        )
        self._degraded_cache = (key, handle)
        return handle

    def _device_checksum(self) -> np.ndarray:
        """Round-trip the device-side balance-table digest (the ONE
        checksum crossing verify paths and the health digest share)."""
        return self._retry(
            lambda: self.link.fetch(
                self.link.dispatch(dk.checksum, self.balances)
            ),
            "fetch",
        )

    @staticmethod
    def _meta_digest(meta):
        """4-word digest of the (capacity, 2) account-meta table —
        the shared digest formula (mirror.digest_columns), so it can
        never drift from the balance-table compare."""
        from tigerbeetle_tpu.state_machine.mirror import digest_columns

        return digest_columns(meta)

    def _device_health_digest(self) -> np.ndarray:
        """Balances digest + meta digest from the DEVICE tables — what
        the scrub and the re-promotion handshake compare against the
        host's copy (meta corruption must be as detectable as balance
        corruption: the kernels' ladder verdicts read it)."""
        bal = self._device_checksum()
        meta = self._retry(
            lambda: self.link.fetch(
                self.link.dispatch(self._meta_digest, self.meta)
            ),
            "fetch",
        )
        return np.concatenate([bal, meta])

    def _host_health_digest(self) -> np.ndarray:
        # Tiered, the device tables are hot-shaped: digest the same
        # hot-shaped host images the device should hold (the logical
        # table is attested separately through the commitment fold).
        if self.hot is not None:
            from tigerbeetle_tpu.state_machine.mirror import digest_columns

            return np.concatenate(
                [
                    digest_columns(self._mirror_hot_table_np()),
                    self._meta_digest(self._meta_hot_np()),
                ]
            )
        return np.concatenate(
            [
                self.mirror.checksum8(self.capacity),
                self._meta_digest(self._meta_host),
            ]
        )

    def _upload_from_mirror(self) -> None:
        src = (
            self._mirror_hot_table_np()
            if self.hot is not None
            else self._mirror_table_np()
        )
        self.balances = self._place(jnp.asarray(src))
        # The device table just changed wholesale: re-derive the
        # on-device commitment from scratch (one dispatch — callers
        # are recovery/re-promotion/heal paths, never the hot path).
        # Reads the CURRENT device meta table, so callers that also
        # re-upload meta must do so BEFORE this.
        self._commit_rebuild()

    # ------------------------------------------------------------------
    # Incremental state commitment (state_machine/commitment.py): the
    # device maintains per-row hashes + a 16-byte fold of its
    # balances+meta tables as a by-product of every execution path —
    # each launch/flush/recovery re-dispatch absorbs exactly the rows
    # it touched — and the host twin on mirror.commitment tracks the
    # same value bit-identically.  Scrub and the re-promotion
    # handshake compare 16 bytes; the full-table fetch survives only
    # as _localize_divergence.

    def _twin_meta(self, slots: np.ndarray) -> np.ndarray:
        """Meta columns for a standalone engine's host twin (the
        owning state machine supplies an attrs-backed one instead)."""
        out = np.zeros((len(slots), 2), np.uint32)
        m = slots < len(self._meta_host)
        out[m] = self._meta_host[slots[m]]
        return out

    def _commit_rows(self):
        """Logical-row binding for the commitment kernels: identity
        when all-resident, logical_of tiered.  Free hot slots bind to
        row 0 — their all-zero content hashes to (0, 0) regardless of
        the binding, so the digest is exactly the hot PARTIAL of the
        logical table (fold(hot, cold) == the full root)."""
        if self.hot is None:
            return jnp.arange(self.balances.shape[0], dtype=jnp.uint64)
        lof = self.hot.logical_of
        return jnp.asarray(np.where(lof >= 0, lof, 0).astype(np.uint64))

    def _commit_rebuild(self) -> None:
        """From-scratch device digest (vectorized over the table ON
        DEVICE; on a row-sharded engine GSPMD computes shard-local
        partial folds and all-reduces them over ICI)."""
        if not self._commit_enabled:
            return
        from tigerbeetle_tpu.state_machine import commitment as _cm

        fns = _cm.device_fns()
        self.dev_row_hash, self.dev_digest = self._run(
            fns["rebuild"], self.balances, self.meta, self._commit_rows()
        )

    def _commit_update(self, slots) -> None:
        """Absorb the touched rows of one launch/flush into the
        on-device digest: ONE extra dispatch per window, O(touched).
        `slots` index the DEVICE table (hot slots under tiering)."""
        if not self._commit_enabled or self.dev_row_hash is None:
            return
        with self.tracer.stage(self._st_commit_update):
            self._commit_update_rows(slots)

    def _commit_absorb(self, recs) -> None:
        """_commit_update over every row `recs` can have modified; the
        stage covers collecting them too."""
        if not self._commit_enabled or self.dev_row_hash is None:
            return
        with self.tracer.stage(self._st_commit_update):
            touched = self._collect_touched(recs)
            if touched is not None:
                self._commit_update_rows(touched)

    def _commit_update_rows(self, slots) -> None:
        slots = np.asarray(slots, np.int64)
        slots = slots[(slots >= 0) & (slots < self.balances.shape[0])]
        if len(slots) == 0:
            return
        if self.metrics.enabled:
            slots, legs = np.unique(slots, return_counts=True)
            st = self.touch_stats
            if st is None:
                st = self.touch_stats = make_touch_stats(self.metrics)
            st[0].observe(len(slots))
            st[1].observe(int(legs.max()))
        else:
            slots = np.unique(slots)
        from tigerbeetle_tpu.state_machine import commitment as _cm

        fns = _cm.device_fns()
        padded = _cm.pad_slots(slots)
        if self.hot is None:
            rows = padded
        else:
            rows = np.where(
                padded >= 0, self.hot.logical_of[np.maximum(padded, 0)], 0
            )
        self.stat_commit_updates += 1
        self.dev_row_hash, self.dev_digest = self._run(
            fns["update"], self.balances, self.meta,
            self.dev_row_hash, self.dev_digest,
            self._arg(padded), self._arg(rows),
        )

    def _collect_touched(self, recs) -> np.ndarray | None:
        """Union of balance rows a record list can have modified."""
        touched = []
        for rec in recs:
            if rec.kind == "meta":
                touched.append(rec.meta_args[0])
            elif rec.kind in ("waves", "spec") and rec.touched is not None:
                touched.append(rec.touched)
            elif rec.kind in _SEMANTIC_KINDS:
                touched.append(_touched_of_pk(rec.kind, rec.pk, rec.n))
        if not touched:
            return None
        return np.concatenate(touched)

    def commit_probe(self) -> np.ndarray:
        """(2, 2) u64 [maintained digest, from-scratch digest] from
        the device — one dispatch + one 32-byte fetch.  Caller must
        hold the engine drained/flushed."""
        from tigerbeetle_tpu.state_machine import commitment as _cm

        fns = _cm.device_fns()
        return self._retry(
            lambda: self.link.fetch(
                self.link.dispatch(
                    fns["probe"], self.balances, self.meta,
                    self.dev_digest, self._commit_rows(),
                )
            ),
            "fetch",
        )

    def device_root(self) -> np.ndarray:
        """(2,) u64 maintained device digest (16-byte fetch)."""
        return self._retry(
            lambda: self.link.fetch(self.dev_digest), "fetch"
        )

    def _twin_expected_digest(self) -> np.ndarray:
        """What the host twin says the DEVICE digest should be: the
        full root all-resident, the hot partial under tiering (the
        cold partial is the twin's remainder — fold(hot, cold) stays
        the whole-logical-table root)."""
        twin = self.mirror.commitment
        if self.hot is None:
            return twin.digest
        return twin.partial(self.hot.occupied())

    def _localize_divergence(self) -> np.ndarray:
        """THE full-table-fetch path (counted in commit.full_fetches):
        pull both device tables and name the diverged rows vs the
        host's copies — runs only when a 16-byte compare already
        failed (or the TB_DEV_SCRUB_FALLBACK deep-scrub cadence
        forces it)."""
        self.stat_full_fetches += 1
        bal = self._retry(lambda: self.link.fetch(self.balances), "fetch")
        meta = self._retry(lambda: self.link.fetch(self.meta), "fetch")
        if self.hot is not None:
            # Compare hot-shaped tables, report LOGICAL row ids.
            diverged = (bal != self._mirror_hot_table_np()).any(axis=1) | (
                meta != self._meta_hot_np()
            ).any(axis=1)
            hot_rows = np.flatnonzero(diverged)
            return self.hot.logical_of[hot_rows]
        diverged = (bal != self._mirror_table_np()).any(axis=1) | (
            meta != self._meta_host
        ).any(axis=1)
        return np.flatnonzero(diverged)

    def _heal_from_mirror(self) -> None:
        """Re-upload both tables from the host copies (meta first: the
        commitment rebuild inside _upload_from_mirror hashes it)."""
        meta_src = (
            self._meta_hot_np() if self.hot is not None else self._meta_host
        )
        self.meta = self._place(jnp.asarray(meta_src))
        self._upload_from_mirror()

    def drain(self) -> None:
        # A drain nested inside exact recovery (host fallbacks read the
        # table, which drains) must NOT touch the stream: launching the
        # pending window mid-recovery would execute it out of
        # submission order against a table recovery is about to
        # rebuild, and a nested dirty rotation would clobber the
        # _recovering slot.  The outer recovery finishes the stream.
        while (self._launched or self._pending) and not self._recovering:
            try:
                self._rotate()
            except DeviceLostError as exc:
                self._demote(exc)

    def close(self) -> None:
        """End-of-life barrier: every outstanding future resolves (via
        drain, demoting to exact host replay if the link dies) or
        fails with a typed DeviceLostError — a caller blocked in
        result() is never stranded."""
        try:
            self.drain()
            self.flush()
        # tbcheck: allow(broad-except): end-of-life barrier — when even
        # the host replay fails, every stranded future must still be
        # terminated with a typed DeviceLostError (never a hang).
        except Exception as exc:
            for rec in self._recovering + self._launched + self._pending:
                if rec.future is not None and not rec.future.done():
                    rec.future.fail(DeviceLostError("close", exc))
            self._recovering = []
            self._launched = []
            self._pending = []
            self._pending_semantic = 0
            self._inflight_bound = 0
            self._q.clear()
            self._queued = 0
        self._closed = True

    # ------------------------------------------------------------------
    # Degraded-mode lifecycle: demote on fatal link loss, serve exact
    # replies from the host engine against the mirror, probe + re-upload
    # + checksum handshake to re-promote, and a periodic checksum scrub
    # while healthy.

    def _demote(self, exc: BaseException) -> None:
        """Fatal link loss: the host mirror becomes authoritative.
        Every outstanding future resolves IN SUBMISSION ORDER through
        the exact host path — bit-identical to what the device would
        have replied — and later submits route host-side until a
        re-promotion handshake passes."""
        self.state = EngineState.degraded
        self.stat_demotions += 1
        self.tracer.instant("device_demoted", error=repr(exc)[:200])
        self.last_demotion = repr(exc)
        self._degraded_submits = 0
        # The device commitment is as dead as the table it covers; the
        # host twin stays live (mirror mutations keep refreshing it)
        # and re-promotion rebuilds the device side from the upload.
        self.dev_row_hash = None
        self.dev_digest = None
        outstanding = self._recovering + self._launched + self._pending
        # Clear BEFORE replaying: the host path may drain/read this
        # engine re-entrantly, and must see an empty stream.
        self._recovering = []
        self._launched = []
        self._pending = []
        self._pending_semantic = 0
        # Write-behind deltas exist on the mirror already; the device
        # copy is abandoned (re-promotion re-uploads the whole table).
        self._q.clear()
        self._queued = 0
        for rec in outstanding:
            self._replay_record_on_host(rec)

    def _replay_record_on_host(self, rec: _InFlight) -> None:
        fut = rec.future
        if fut is None or fut.done():
            self._release_bound(rec)
            return
        try:
            if rec.kind == "lookup":
                fut.resolve(rec.finish(self.mirror.rows8(rec.slots)))
            else:
                self.stat_degraded_events += rec.n
                fut.resolve(rec.fallback())
        # tbcheck: allow(broad-except): the host replay itself failed —
        # fail THIS future with the real error and keep terminating the
        # rest of the stream (one bad record must not strand the rest).
        except Exception as exc:
            fut.fail(exc)
        finally:
            self._release_bound(rec)

    def tick(self) -> None:
        """Periodic lifecycle work, called once per committed
        operation by the state machine (tpu.commit_async): in degraded
        mode, a health probe + re-promotion attempt every _PROBE_EVERY
        operations; while healthy, the checksum scrub every
        _SCRUB_EVERY window fetches."""
        if self.state is EngineState.degraded:
            self._degraded_submits += 1
            if self._degraded_submits >= _PROBE_EVERY:
                self._degraded_submits = 0
                self.try_repromote()
            return
        if (
            self._scrub_every
            and self.state is EngineState.healthy
            and self.stat_fetches
            >= self._last_scrub_fetch + self._scrub_every
        ):
            try:
                self.scrub()
            except DeviceLostError as exc:
                self._demote(exc)

    def try_repromote(self) -> bool:
        """Health probe -> table re-upload from the mirror -> checksum
        handshake.  The device becomes authoritative again ONLY if the
        round-tripped digest matches the mirror's; any failure leaves
        the engine degraded (and counted), never half-promoted."""
        if self.state is EngineState.healthy:
            return True
        if self._closed:
            return False
        self.state = EngineState.repromoting
        try:
            self._retry(self.link.probe, "probe")
            self._heal_from_mirror()  # meta first, commitment rebuilt
            if self._commit_enabled and self.mirror.commitment is not None:
                # Cheap handshake: the device's freshly-rebuilt 16-byte
                # root vs the incrementally-maintained host twin — no
                # full-table fetch, no host-side full digest pass.
                # Tiered, the device root is the HOT PARTIAL of the
                # logical table, so compare the twin's matching partial.
                dev_sum = self.device_root()
                host_sum = self._twin_expected_digest()
            else:
                dev_sum = self._device_health_digest()
                host_sum = self._host_health_digest()
            if not (dev_sum == host_sum).all():
                raise FatalLinkError(
                    "re-promotion checksum handshake mismatch: "
                    f"device={dev_sum.tolist()} host={host_sum.tolist()}"
                )
        # tbcheck: allow(broad-except): re-promotion is opportunistic —
        # any failure (probe via the classifying _retry, upload, digest
        # handshake) leaves the engine degraded and counted, never
        # half-promoted; the next tick retries.
        except Exception as exc:
            self.state = EngineState.degraded
            self.stat_probe_failures += 1
            self.last_probe_failure = repr(exc)
            return False
        self.state = EngineState.healthy
        self.stat_repromotions += 1
        self.tracer.instant("device_repromoted")
        return True

    def scrub(self) -> bool:
        """Integrity-compare the device tables against the host while
        idle; heal divergence by re-uploading from the mirror.
        Returns True when the tables already matched.  Raises
        DeviceLostError if the link dies mid-scrub (caller demotes).

        Happy path (commitment enabled): ONE dispatch + one 32-byte
        fetch — the device's maintained digest, its from-scratch
        recompute (catches HBM corruption of rows no step touched),
        and the host twin must all agree.  Only a mismatch (or the
        TB_DEV_SCRUB_FALLBACK deep-scrub cadence) pays the full-table
        fetch, which then NAMES the diverged rows before the heal."""
        if (
            self.state is not EngineState.healthy
            or self.has_inflight()
            or self._queued
        ):
            return True
        self._last_scrub_fetch = self.stat_fetches
        self.stat_scrubs += 1
        cheap = (
            self._commit_enabled
            and self.dev_digest is not None
            and self.mirror.commitment is not None
        )
        with self.tracer.stage(self._st_scrub):
            if cheap:
                self.stat_scrub_cheap += 1
                with self._h_scrub_cheap.time():
                    pair = self.commit_probe()
                host = self._twin_expected_digest()
                clean = bool(
                    (pair[0] == pair[1]).all() and (pair[1] == host).all()
                )
                deep_every = envcheck.scrub_fallback_every()
                if clean and not (
                    deep_every and self.stat_scrubs % deep_every == 0
                ):
                    return True
            else:
                clean = bool(
                    (
                        self._device_health_digest()
                        == self._host_health_digest()
                    ).all()
                )
                if clean:
                    return True
            # Divergence localization (the demoted full-fetch path) +
            # heal.  A deep scrub that confirms the cheap verdict
            # returns clean without healing.
            self.stat_scrub_fallback += 1
            with self._h_scrub_fallback.time():
                rows = self._localize_divergence()
            if len(rows) == 0:
                if not clean:
                    # Tables match byte-for-byte yet a digest
                    # disagreed: incremental-accumulator drift.  Must
                    # never happen (fuzz-pinned); repaired loudly so a
                    # wedged digest cannot spam heals forever.
                    self.stat_commit_repairs += 1
                    if self.mirror.commitment is not None:
                        self.mirror.commitment.rebuild(self.mirror)
                    self._commit_rebuild()
                return True
            self.tracer.instant("scrub_divergence", rows=int(len(rows)))
            self.stat_scrub_heals += 1
            self._heal_from_mirror()
        return False

    # ------------------------------------------------------------------
    # Write-behind lane (host exact path) — kernel_fast.DeviceTable API.

    def enqueue(self, slots, cols, add_lo, add_hi,
                refresh_twin: bool = True) -> None:
        if len(slots) == 0:
            return
        # The native fast path mutates the shared mirror arrays in
        # place (its commits don't pass through BalanceMirror methods)
        # but ALWAYS feeds its deltas through here — bump the mutation
        # stamp so the degraded-read cache can never serve stale rows
        # (including suppressed re-execution enqueues, whose mirror
        # mutation already happened natively), and fold the touched
        # rows into the host commitment twin for the same reason.
        # Callers whose deltas came through the mirror's own Python
        # methods (whose _touch already refreshed the twin) pass
        # refresh_twin=False to skip the duplicate hashing.
        self.mirror.version += 1
        if refresh_twin and self.mirror.commitment is not None:
            self.mirror.commitment.refresh(
                np.asarray(slots, np.int64), self.mirror
            )
        if self._suppress_enqueue:
            return
        if self.state is not EngineState.healthy:
            # Degraded: the mirror (already updated by the host path)
            # is authoritative; re-promotion re-uploads the full table.
            return
        # Exact-path deltas only arrive after a drain (the host path
        # drains before running), so they can never overtake queued
        # semantic batches.
        assert self._pending_semantic == 0 and not self._launched, (
            "write-behind enqueue with in-flight semantic batches"
        )
        self._q.append(
            (
                np.asarray(slots, np.int64),
                np.asarray(cols, np.int64),
                np.asarray(add_lo, np.uint64),
                np.asarray(add_hi, np.uint64),
            )
        )
        self._queued += len(slots)

    def flush(self) -> None:
        if not self._queued:
            return
        if self.state is not EngineState.healthy:
            self._q.clear()
            self._queued = 0
            return
        try:
            self._flush_inner()
        except DeviceLostError as exc:
            self._demote(exc)

    def _flush_inner(self) -> None:
        from tigerbeetle_tpu.state_machine.mirror import compact_deltas

        slots = np.concatenate([e[0] for e in self._q])
        cols = np.concatenate([e[1] for e in self._q])
        a_lo = np.concatenate([e[2] for e in self._q])
        a_hi = np.concatenate([e[3] for e in self._q])
        self._q.clear()
        self._queued = 0
        chunk = (1 << 21) - 1
        if len(slots) > chunk:
            parts = [
                compact_deltas(
                    slots[i : i + chunk], cols[i : i + chunk],
                    a_lo[i : i + chunk], a_hi[i : i + chunk],
                )
                for i in range(0, len(slots), chunk)
            ]
            slots = np.concatenate([p[0] for p in parts])
            cols = np.concatenate([p[1] for p in parts])
            a_lo = np.concatenate([p[2] for p in parts])
            a_hi = np.concatenate([p[3] for p in parts])
        u_slot, u_col, d_lo, d_hi, _ = compact_deltas(slots, cols, a_lo, a_hi)
        if self.hot is not None:
            # Exact-path deltas arrive with LOGICAL slots; the device
            # table is hot-shaped.  Cold rows keep their deltas in the
            # mirror only (it already leads for host-resolved batches);
            # they upload whole on admission.
            h = self.hot.hot_of[u_slot]
            keep = h >= 0
            u_slot, u_col = h[keep], u_col[keep]
            d_lo, d_hi = d_lo[keep], d_hi[keep]
        at = 0
        CH = 32_768
        while at < len(u_slot):
            take = min(len(u_slot) - at, CH)
            packed = np.empty((4, CH), np.uint64)
            packed[0, :take] = u_slot[at : at + take].astype(np.uint64)
            packed[0, take:] = self.capacity + np.arange(
                CH - take, dtype=np.uint64
            )
            packed[1, :take] = u_col[at : at + take].astype(np.uint64)
            packed[1, take:] = 0
            packed[2, :take] = d_lo[at : at + take]
            packed[2, take:] = 0
            packed[3, :take] = d_hi[at : at + take]
            packed[3, take:] = 0
            self.balances = self._run(
                dk.apply_deltas, self.balances, self._arg(packed)
            )
            at += take
        # Flushed deltas must land before any later queued meta/lookup
        # records are dispatched — but those only dispatch at the next
        # launch, which follows this flush in program order.
        self._commit_update(u_slot)

    def read(self):
        """Drain barrier + table handle (DeviceTable API compat).  In
        degraded mode the authoritative bytes live in the host mirror;
        callers get a default-backend array built from it (NOT routed
        through the possibly-dead link).  During exact recovery the
        mirror is likewise the truth — it reflects exactly the stream
        prefix before the batch being re-executed, while the device
        table still holds the whole window's kernel effects."""
        if self._recovering:
            return self._degraded_table()
        self.drain()
        self.flush()
        if self.state is not EngineState.healthy:
            return self._degraded_table()
        if self.hot is not None:
            # Tiered: the device holds only hot rows; the full LOGICAL
            # table comes from the mirror, which the drain above made
            # current for every finished batch.
            return self._degraded_table()
        return self.balances

    def write_back(self, value) -> None:
        """Replace the device table from a full LOGICAL table image
        (the owning machine's `_balances` setter).  Tiered, the hot
        rows are gathered out of it and the digest rebuilt — the
        mirror (which the caller updates through the same code path)
        stays the cold-tier authority."""
        if self.hot is None:
            self.balances = value
            return
        lof = self.hot.logical_of
        img = np.asarray(jax.device_get(value))
        hot_np = np.zeros((len(lof), 8), np.uint64)
        occ = np.flatnonzero(lof >= 0)
        hot_np[occ] = img[lof[occ]]
        try:
            self.balances = self._place(jnp.asarray(hot_np))
            self._commit_rebuild()
        except DeviceLostError as exc:
            self._demote(exc)

    def checksum(self) -> np.ndarray:
        """Authoritative-table digest (drained + flushed first): the
        device table while healthy, the mirror (computed host-side,
        no device work at all) while degraded.  Tiered, the digest
        covers the LOGICAL table, so it always comes from the mirror —
        the drain just guaranteed it is current."""
        self.drain()
        self.flush()
        if self.state is not EngineState.healthy or self.hot is not None:
            return self.mirror.checksum8(self.capacity)
        try:
            return self._device_checksum()
        except DeviceLostError as exc:
            self._demote(exc)
            return self.mirror.checksum8(self.capacity)
