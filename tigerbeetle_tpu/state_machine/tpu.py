"""TPU-backed state machine: host orchestration around the JAX kernel.

Same external interface as ``CpuStateMachine`` (input_valid / prepare /
pulse_needed / prefetch / commit over wire bytes), so the two are
interchangeable under the test harness and diffable bit-for-bit.

State split (see kernel.py header):
- DEVICE: the account *balance* table, (A, 8) uint64 — four u128
  balances as limb pairs. This is the only mutable per-account state
  (reference: src/tigerbeetle.zig:7-29 — every other Account field is
  immutable after create_accounts).
- HOST: id directories (LSM-style sorted runs, vectorized lookup),
  immutable account attributes, the columnar transfer store + pending
  statuses + expires_at index + historical balances. All hot-path host
  work is numpy-vectorized; per-event Python runs only for
  create_accounts (not the benchmark's hot operation) and rare pulse
  bookkeeping.

The commit flow for create_transfers mirrors the reference pipeline
(reference: src/vsr/replica.zig:3746-3847 prefetch->commit):
host static ladder + joins ~ prefetch; kernel scan ~ execute; host
post-processing ~ the groove inserts the reference does inline.
"""

from __future__ import annotations

import contextlib
import functools
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from tigerbeetle_tpu import constants as cfg
from tigerbeetle_tpu import envcheck
from tigerbeetle_tpu import types
from tigerbeetle_tpu.lsm import pack_u128
from tigerbeetle_tpu.obs import stat_property as obs_stat_property
from tigerbeetle_tpu.utils import HashIndex, RunIndex
from tigerbeetle_tpu.utils.tracer import NOOP_RUN
from tigerbeetle_tpu.state_machine import kernel, kernel_fast, resolve, waves
from tigerbeetle_tpu.state_machine.mirror import BalanceMirror, _sub_u128
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.types import (
    ACCOUNT_BALANCE_DTYPE,
    ACCOUNT_DTYPE,
    ACCOUNT_FILTER_DTYPE,
    CREATE_RESULT_DTYPE,
    NS_PER_S,
    TIMESTAMP_MAX,
    TIMESTAMP_MIN,
    TRANSFER_DTYPE,
    U64_MAX,
    U128_MAX,
    AccountFilterFlags,
    AccountFlags,
    CreateAccountResult,
    CreateTransferResult,
    Operation,
    TransferFlags,
    TransferPendingStatus,
)

# Tight device-input gate: amounts must fit u32 (tests shrink this to
# force the wide format on the same stream).
_TIGHT_AMOUNT_LIMIT = 1 << 32

AF = AccountFlags
TF = TransferFlags
CAR = CreateAccountResult
CTR = CreateTransferResult

_BATCH_BUCKETS = kernel.BATCH_BUCKETS

# Columnar transfer-store fields.
_STORE_FIELDS = {
    "id_lo": np.uint64, "id_hi": np.uint64,
    "dr_slot": np.int32, "cr_slot": np.int32,
    "amount_lo": np.uint64, "amount_hi": np.uint64,
    "pending_lo": np.uint64, "pending_hi": np.uint64,
    "ud128_lo": np.uint64, "ud128_hi": np.uint64,
    "ud64": np.uint64, "ud32": np.uint32,
    "timeout": np.uint32, "ledger": np.uint32, "code": np.uint32,
    "flags": np.uint32, "timestamp": np.uint64,
    "status": np.uint8,  # TransferPendingStatus for pending transfers
}

_ATTR_FIELDS = {
    "id_lo": np.uint64, "id_hi": np.uint64,
    "ud128_lo": np.uint64, "ud128_hi": np.uint64,
    "ud64": np.uint64, "ud32": np.uint32,
    "ledger": np.uint32, "code": np.uint32, "flags": np.uint32,
    "timestamp": np.uint64,
}

_HISTORY_FIELDS = {
    "timestamp": np.uint64,
    "dr_id_lo": np.uint64, "dr_id_hi": np.uint64,
    "cr_id_lo": np.uint64, "cr_id_hi": np.uint64,
    "dr_bal": (np.uint64, 8), "cr_bal": (np.uint64, 8),
}


def _amount_bound_total(amount_lo: np.ndarray, amount_hi: np.ndarray) -> int:
    """Exact host-integer sum of (lo, hi) u128 amount bounds via 32-bit
    limb sums (each limb sum < 2^21 * 2^32 < 2^64) — the in-flight
    admission bookkeeping the device engine's wave dispatch keeps."""
    m32 = np.uint64(0xFFFFFFFF)
    return (
        int((amount_lo & m32).sum(dtype=np.uint64))
        + (int((amount_lo >> np.uint64(32)).sum(dtype=np.uint64)) << 32)
        + (int((amount_hi & m32).sum(dtype=np.uint64)) << 64)
        + (int((amount_hi >> np.uint64(32)).sum(dtype=np.uint64)) << 96)
    )


# What a batch holds, as the router reads it: a bit for each of the
# predicates that send a batch to a kernel, or keep it from one.
(_HAS_LINKED, _HAS_PENDING, _HAS_PV, _TOUCH_LIMH, _TOUCH_HIST, _AMOUNT_HI,
 _TIMEOUT) = (1 << i for i in range(7))
# What each kernel cannot take (two_phase needs a post or a void too).
_ORDERFREE_NOT = _HAS_LINKED | _HAS_PV | _TOUCH_LIMH
_LINKED_NOT = _HAS_PENDING | _HAS_PV | _TOUCH_HIST | _AMOUNT_HI
_TWO_PHASE_NOT = _HAS_LINKED | _TIMEOUT | _TOUCH_LIMH


def _kernel_class(bits: int) -> str | None:
    """The kernel a batch whose events hold `bits` goes to: orderfree
    takes plain transfers and pendings; linked, chains and plain
    transfers on accounts under a limit; two_phase, posts and voids
    with no chain.  None: no kernel takes the batch whole."""
    if not bits & _ORDERFREE_NOT:
        return "orderfree"
    if not bits & _LINKED_NOT:
        return "linked"
    if bits & _HAS_PV and not bits & _TWO_PHASE_NOT:
        return "two_phase"
    return None


def _cut_runs(flags16, acct_flags, amount_hi, timeout):
    """A batch that no kernel takes whole, cut into runs that one
    kernel each takes: -> [(start, end, kernel)], the runs in order,
    or None where some chain is taken by no kernel even alone.

    A cut falls only after an event whose `linked` flag is clear, so no
    chain is split, and an open chain at the batch's end stays in the
    last run.  What a kernel cannot take only accumulates as a run
    grows: the first chain a kernel cannot take ends its reach, and a
    run, formed greedily from the front, ends where the longest reach
    of the three ends (the fewest runs).  A run without a post or a
    void that two_phase reaches, orderfree reaches as far."""
    u8 = np.uint8
    linked = (flags16 & np.uint16(TF.linked)) != 0
    pv = np.uint16(TF.post_pending_transfer | TF.void_pending_transfer)
    limh = np.uint32(
        AF.debits_must_not_exceed_credits
        | AF.credits_must_not_exceed_debits
        | AF.history
    )
    ev = (
        linked.view(u8) * u8(_HAS_LINKED)
        | ((flags16 & np.uint16(TF.pending)) != 0).view(u8) * u8(_HAS_PENDING)
        | ((flags16 & pv) != 0).view(u8) * u8(_HAS_PV)
        | ((acct_flags & limh) != 0).view(u8) * u8(_TOUCH_LIMH)
        | ((acct_flags & np.uint32(AF.history)) != 0).view(u8) * u8(_TOUCH_HIST)
        | (amount_hi != 0).view(u8) * u8(_AMOUNT_HI)
        | (timeout != 0).view(u8) * u8(_TIMEOUT)
    )
    n = len(ev)
    ends = np.flatnonzero(~linked)
    if len(ends) == 0 or ends[-1] != n - 1:
        ends = np.append(ends, n - 1)
    starts = np.concatenate(([0], ends[:-1] + 1))
    chain = np.bitwise_or.reduceat(ev, starts)
    m = len(chain)
    # The chains each kernel cannot take, and a sentinel past the last.
    stops = [np.append(np.flatnonzero(chain & u8(cannot)), m)
             for cannot in (_ORDERFREE_NOT, _LINKED_NOT, _TWO_PHASE_NOT)]
    runs = []
    i = 0
    while i < m:
        j = max(int(stop[np.searchsorted(stop, i)]) for stop in stops)
        if j == i:
            return None
        kernel = _kernel_class(int(np.bitwise_or.reduce(chain[i:j])))
        runs.append((int(starts[i]), int(ends[j - 1]) + 1, kernel))
        i = j
    return runs


def _zeros_touched(shape, dtype) -> np.ndarray:
    """Zeroed array with pages faulted in up front: appends write into
    fresh pages, and eager sequential touching is ~4x cheaper than
    faulting page-by-page from scattered slice writes."""
    a = np.empty(shape, dtype)
    a.fill(0)
    return a


class Columns:
    """Growable columnar array store with vectorized batch append."""

    def __init__(self, fields: dict, capacity: int = 1024) -> None:
        self._fields = fields
        self.count = 0
        self._cap = capacity
        self._cols = {}
        for name, spec in fields.items():
            if isinstance(spec, tuple):
                dtype, width = spec
                self._cols[name] = _zeros_touched((capacity, width), dtype)
            else:
                self._cols[name] = _zeros_touched(capacity, spec)

    def _ensure(self, extra: int) -> None:
        need = self.count + extra
        if need <= self._cap:
            return
        while self._cap < need:
            self._cap *= 4
        for name, col in self._cols.items():
            shape = (self._cap,) + col.shape[1:]
            new = np.empty(shape, col.dtype)
            new[: self.count] = col[: self.count]
            new[self.count :].fill(0)
            self._cols[name] = new

    def append(self, **arrays) -> np.ndarray:
        n = len(next(iter(arrays.values())))
        self._ensure(n)
        lo, hi = self.count, self.count + n
        for name, arr in arrays.items():
            self._cols[name][lo:hi] = arr
        self.count = hi
        return np.arange(lo, hi)

    def truncate(self, count: int) -> None:
        assert count <= self.count
        self.count = count

    def col(self, name: str) -> np.ndarray:
        return self._cols[name][: self.count]

    def __getitem__(self, name: str) -> np.ndarray:
        return self._cols[name]


class _GlobalCol:
    """Indexing proxy translating GLOBAL rows to the RAM tail or the
    LSM spill tier (read-only below the spill base)."""

    __slots__ = ("_store", "_name")

    def __init__(self, store: "TailStore", name: str) -> None:
        self._store = store
        self._name = name

    def __getitem__(self, rows):
        return self._store.gather(self._name, rows)

    def __setitem__(self, rows, values) -> None:
        base = self._store.base
        if np.isscalar(rows) or isinstance(rows, (int, np.integer)):
            rows = np.array([rows], np.int64)
            values = np.asarray([values])
        else:
            rows = np.asarray(rows)
            values = np.broadcast_to(np.asarray(values), rows.shape)
        in_ram = rows >= base
        if in_ram.any():
            self._store.ram[self._name][
                rows[in_ram] - base + self._store._off
            ] = values[in_ram]
        if not in_ram.all():
            # Spilled objects are immutable: the status a post, a void
            # or an expiry gives a spilled pending goes to the posted
            # groove (spill.py), nothing else is ever written.
            assert self._name == "status", "write to spilled row"
            self._store.spill.update_status(rows[~in_ram], values[~in_ram])


class TailStore:
    """Columnar store whose rows [0, base) have spilled into an LSM
    groove (state_machine/spill.py) and whose tail [base, count) stays
    in RAM — the hot append path never touches the LSM.

    Global row numbers are stable across spills: the id directories,
    the expiry index, and the native library all keep global rows.
    """

    def __init__(self, fields: dict, capacity: int = 1024,
                 cold_join=None) -> None:
        self.ram = Columns(fields, capacity)
        self.base = 0
        # `cold_join(n)` -> a context manager around a join of `n`
        # rows that have spilled (the owning machine's sm.plan.join_cold
        # stage and sm.store.join_cold_rows counter).
        self.cold_join = cold_join or (lambda n: contextlib.nullcontext())
        # Dead physical rows at the front of `ram` (already spilled):
        # drop_prefix advances this offset in O(1) and compacts only
        # when dead rows dominate — per-beat spills must not pay an
        # O(tail) memmove on the commit path.
        self._off = 0
        self.spill = None  # TransferSpill once a forest is attached

    @property
    def count(self) -> int:
        return self.base + self.ram.count - self._off

    def append(self, **arrays) -> np.ndarray:
        return self.ram.append(**arrays) - self._off + self.base

    def col(self, name: str) -> np.ndarray:
        """Live RAM-tail view; index 0 corresponds to global row
        .base."""
        return self.ram.col(name)[self._off :]

    def tail_count(self) -> int:
        return self.ram.count - self._off

    def __getitem__(self, name: str) -> _GlobalCol:
        return _GlobalCol(self, name)

    def _phys(self, rows):
        return rows - self.base + self._off

    def gather(self, name: str, rows):
        from tigerbeetle_tpu.state_machine import spill as spill_mod

        if np.isscalar(rows) or isinstance(rows, (int, np.integer)):
            if rows >= self.base:
                return self.ram[name][self._phys(rows)]
            obj = self.spill.gather(np.array([rows], np.int64))
            return spill_mod.unpack_objects(obj)[name][0]
        rows = np.asarray(rows)
        if len(rows) == 0 or (self.base == 0 or (rows >= self.base).all()):
            return self.ram[name][self._phys(rows)]
        out = np.empty(len(rows), self.ram[name].dtype)
        in_ram = rows >= self.base
        out[in_ram] = self.ram[name][self._phys(rows[in_ram])]
        cold = ~in_ram
        obj = self.spill.gather(rows[cold])
        out[cold] = spill_mod.unpack_objects(obj)[name]
        return out

    def gather_many(self, names: list[str], rows: np.ndarray) -> dict:
        """One spill fetch for many columns (exact-path joins)."""
        from tigerbeetle_tpu.state_machine import spill as spill_mod

        rows = np.asarray(rows)
        in_ram = rows >= self.base
        if in_ram.all():
            phys = self._phys(rows)
            return {n: self.ram[n][phys] for n in names}
        cold_rows = rows[~in_ram]
        with self.cold_join(len(cold_rows)):
            cold = spill_mod.unpack_objects(self.spill.gather(cold_rows))
        phys = np.maximum(self._phys(rows), 0)
        out = {}
        for n in names:
            vals = self.ram[n][phys].copy()
            vals[~in_ram] = cold[n]
            out[n] = vals
        return out

    def drop_prefix(self, n: int) -> None:
        """Advance base after `n` rows spilled (caller already wrote
        them to the groove).  O(1); the physical compaction amortizes."""
        assert n <= self.tail_count()
        self._off += n
        self.base += n
        # Compact when dead >= live: the move cost (live rows) is then
        # bounded by the rows dropped since the last compaction, i.e.
        # amortized O(1) per spilled row.
        if self._off and self._off * 2 >= self.ram.count:
            keep = self.ram.count - self._off
            for _name, colarr in self.ram._cols.items():
                colarr[:keep] = colarr[self._off : self.ram.count]
            self.ram.count = keep
            self._off = 0


def _dir_capacity(entries: int) -> int:
    """Pow2 hash capacity holding `entries` at <=50% load (the hash is
    the RunIndex fallback for scattered ids: a batch whose ids follow
    each other in fewer than an eighth of its places, utils/hashindex.py
    RUN_PIECES; a batch with a gap wherever a row failed is runs and
    never reaches it; presizing it keeps random-id workloads from
    rehashing on the commit hot path)."""
    return max(1 << 16, 1 << (2 * max(entries, 1)).bit_length())


def _first_code(shape) -> np.ndarray:
    return np.zeros(shape, np.uint32)


def _apply_code(result: np.ndarray, cond: np.ndarray, code: int) -> None:
    np.copyto(result, np.uint32(code), where=(result == 0) & cond)


class TpuStateMachine:
    """Accounting state machine with a JAX/TPU create_transfers path."""

    def __init__(
        self,
        config: cfg.Config = cfg.PRODUCTION,
        account_capacity: int = 1 << 16,
        transfer_capacity: int = 1 << 16,
        engine: str | None = None,
        device_link=None,
    ) -> None:
        """Capacities follow the reference's static-allocation design:
        all large buffers are sized up front from operator-configured
        limits (reference: docs/DESIGN.md static allocation;
        src/config.zig storage limits), so the steady-state commit path
        never grows or faults fresh pages.

        `engine` selects the create_transfers execution authority:
        - "host" (default): host C++/numpy resolvers compute result
          codes; the device table is a write-behind replica
          (round-3 architecture — lowest latency on this link).
        - "device": result codes are computed ON the TPU by the
          semantic kernels (device_kernels.py) through the pipelined
          DeviceEngine; the host mirror is demoted to bookkeeping,
          recovery, and checkpoint parity.  Replies materialize
          asynchronously (commit_async); commit() drains.
        Override via TB_ENGINE env var.

        `device_link` (device mode only): the DeviceLink the engine
        crosses for every upload/dispatch/fetch — tests pass a seeded
        chaos shim (testing/chaos.py) to exercise the degraded-mode
        lifecycle with no real TPU.
        """
        import os as _os

        self.config = config
        from tigerbeetle_tpu import envcheck as _envcheck

        self.engine = engine or _envcheck.env_str("TB_ENGINE", "host")
        assert self.engine in ("host", "device"), self.engine
        self._device_link = device_link
        self.prepare_timestamp = 0
        self.commit_timestamp = 0
        self.pulse_next_timestamp = TIMESTAMP_MIN

        # Metrics registry (obs/registry.py): every stat_* forensics
        # counter below is a registry handle behind a `stat_*`
        # property (tests read and reset them by that name; ROADMAP
        # D13), the device engine's
        # counters graft in under "dev.", and the owning ReplicaServer
        # attaches the whole tree under "sm." for TB_STATS lines and
        # the `stats` wire scrape.
        from tigerbeetle_tpu import obs

        self.metrics = obs.Registry()
        _c = self.metrics.counter
        self._stats = {
            # Device/host work-split accounting:
            # events whose balance effects were admitted order-free and
            # applied via device scatter-adds vs events resolved by the
            # serial exact engine (host); device-SEMANTIC split (result
            # codes computed by a device kernel) vs host.
            "stat_device_events": _c("device_events"),
            "stat_exact_events": _c("exact_events"),
            "stat_host_semantic_events": _c("host_semantic_events"),
            "stat_fallback_events": _c("fallback_events"),
            # Vectorized order-dependent resolution (resolve.py):
            # batches routed + fixpoint iterations spent.
            "stat_linked_batches": _c("linked_batches"),
            "stat_two_phase_batches": _c("two_phase_batches"),
            "stat_resolve_iters": _c("resolve_iters"),
            # Which bookkeeping tail ran (VERDICT r4 #4): the
            # all-success one-C-pass hot tail is ~2x the general tail.
            "stat_hot_tail_batches": _c("hot_tail_batches"),
            "stat_slow_tail_batches": _c("slow_tail_batches"),
            # Conflict-aware wave execution (waves.py) on the JAX
            # exact path: wave batches, device-step equivalents, and
            # the event split (waves_per_batch / wave_parallelism_pct).
            "stat_wave_batches": _c("wave.batches"),
            "stat_wave_steps": _c("wave.steps"),
            "stat_wave_events": _c("wave.events"),
            "stat_wave_parallel_events": _c("wave.parallel_events"),
            # Device-engine wave dispatch (TB_DEV_WAVES): window
            # batches executed as wave plans against the authoritative
            # HBM table, declines, step equivalents, cumulative
            # plan+admission wall time.
            "stat_dev_wave_batches": _c("dev_wave.batches"),
            "stat_dev_wave_declined": _c("dev_wave.declined"),
            "stat_dev_wave_steps": _c("dev_wave.steps"),
            "stat_dev_wave_events": _c("dev_wave.events"),
            "stat_dev_wave_plan_s": _c("dev_wave.plan_s"),
        }
        # Per-batch wave plan wall time (the cumulative counter above
        # hides the tail; the histogram is scrapeable).
        self._h_dev_wave_plan = self.metrics.histogram("dev_wave.plan_us")
        # Stage tracer (utils/tracer.py): NULL until the owning replica
        # shares its own (set_tracer); engines this machine makes,
        # restores included, get the same one.
        from tigerbeetle_tpu.utils import tracer as tracer_mod

        self.tracer = tracer_mod.NULL
        # sm.plan: a create_transfers batch from the commit's entry to
        # the engine's submit (decode, id and account directory joins,
        # routing, packing).
        self._st_plan = tracer_mod.Stage(
            self.metrics.histogram("plan_us"), "sm.plan"
        )
        # Parts (utils/tracer.py) of the leaf open around them; under
        # no leaf (the host engine's paths, a lookup) they measure
        # nothing.  Of sm.plan, in the order a batch meets them: bytes
        # to columns and what the batch holds; the unique-id check and
        # the in-flight hazards; the id directory's lookup; the account
        # joins; the choice of a kernel and the tier translate; a
        # two-phase batch's join on its pendings, and inside it
        # join_cold, the rows of that join that have left the RAM tail
        # (point reads of the forest's object tree); the packing and
        # `created`.
        def part(name: str) -> tracer_mod.Stage:
            key = name.removeprefix("sm.") + "_us"
            return tracer_mod.Stage(
                self.metrics.histogram(key), name, part=True
            )

        self._st_plan_decode = part("sm.plan.decode")
        self._st_plan_ids = part("sm.plan.ids")
        self._st_plan_id_dir = part("sm.plan.id_dir")
        self._st_plan_accounts = part("sm.plan.accounts")
        self._st_plan_route = part("sm.plan.route")
        self._st_plan_pending = part("sm.plan.pending")
        self._st_plan_pack = part("sm.plan.pack")
        self._st_join_cold = part("sm.plan.join_cold")
        # A batch that no kernel takes whole: its events classified and
        # the cuts found that split it into runs a kernel each takes.
        self._st_plan_cut = part("sm.plan.cut")
        # Of sm.dev.finish, the closures the engine resolves a batch
        # with: result codes and masks; the mirror's adds, and inside
        # them the commitment twin's re-hash of the rows they touched;
        # the store's append with its columns; the id directory's
        # insert; the native id set; pending statuses, expiries,
        # pulses, history; the reply.
        self._st_finish_codes = part("sm.finish.codes")
        self._st_finish_mirror = part("sm.finish.mirror")
        self._st_finish_twin = part("sm.finish.twin")
        self._st_finish_store = part("sm.finish.store")
        self._st_finish_ids = part("sm.finish.ids")
        self._st_finish_native_ids = part("sm.finish.native_ids")
        self._st_finish_status = part("sm.finish.status")
        self._st_finish_reply = part("sm.finish.reply")
        # Of the beat (vsr.commit.beat, lsm.beat.work) and of the
        # checkpoint's freeze: the rows copied out of the tail, their
        # objects built, their index entries and the trees' put_batch.
        self._st_spill_take = part("sm.spill.take")
        self._st_spill_objects = part("sm.spill.objects")
        self._st_spill_index = part("sm.spill.index")
        # Of vsr.ckpt.freeze, inside `snapshot`: the engine's drain;
        # the device's digest pair fetched; the host's from-scratch
        # digest of the mirror; the blob's encode.
        self._st_ckpt_drain = part("sm.ckpt.drain")
        self._st_ckpt_verify_device = part("sm.ckpt.verify_device")
        self._st_ckpt_verify_host = part("sm.ckpt.verify_host")
        self._st_ckpt_encode = part("sm.ckpt.encode")
        self._c_join_cold_rows = _c("store.join_cold_rows")
        # How the transfer-id directory filed the created batches: the
        # runs they became, the ids that went to its hash instead
        # (scattered ids; 0 where ids follow each other), and the runs
        # it holds, set wherever the list moves.
        self._c_ids_runs_filed = _c("ids.runs_filed")
        self._c_ids_hashed = _c("ids.hashed")
        self._g_id_runs = self.metrics.gauge("ids.runs")
        # The device runs a create_transfers prepare was committed as (1
        # where a kernel took it whole), and the prepares that were cut.
        self._c_plan_runs = _c("plan.runs")
        self._c_cut_prepares = _c("plan.cut_prepares")
        # Account rows in use (of the capacity `--cache-accounts` gives
        # the directory, the mirror and the device table), set wherever
        # the count moves: a pull gauge would change the snapshot under
        # an unchanged version.
        self._g_accounts = self.metrics.gauge("accounts")
        # Per-request anatomy hook (obs/anatomy.py): the owning
        # Replica shares its recorder and stamps the current prepare's
        # trace id before each commit, so commit_async can attribute
        # the device-window dispatch hop to the request's timeline.
        from tigerbeetle_tpu.obs import anatomy as anatomy_mod

        self.anatomy = anatomy_mod.NULL
        self.anatomy_trace = 0

        # Account state. The device table is authoritative; the host
        # mirror serves routing decisions and balance reads without
        # blocking on the device link (see mirror.py / kernel_fast.py).
        self._acct_dir = RunIndex(_dir_capacity(account_capacity))
        self._attrs = Columns(_ATTR_FIELDS, capacity=max(1024, account_capacity))
        self._mirror = BalanceMirror(account_capacity)
        # Incremental state commitment (commitment.py): the host twin
        # rides the mirror — every mirror mutation re-hashes exactly
        # the rows it touched — with meta columns read live from the
        # attribute store (survives native re-pointing + restores).
        # Attached BEFORE the device engine so both sides share it.
        self._commitment = None
        if envcheck.state_commit() == 1:
            from tigerbeetle_tpu.state_machine import (
                commitment as commitment_mod,
            )

            self._commitment = commitment_mod.HostCommitment(
                account_capacity, meta_fn=self._commit_meta_cols
            )
            self._mirror.commitment = self._commitment
            self._mirror.twin_part = self._st_finish_twin
        if self.engine == "device":
            from tigerbeetle_tpu.state_machine.device_engine import (
                DeviceEngine,
            )

            self._dev = DeviceEngine(
                account_capacity, self._mirror, link=device_link,
                metrics=self.metrics.scope("dev"),
            )
            # Compiles of this process (device.py counts them from
            # JAX's own events): a window in which the count moves
            # compiled on the served path.
            from tigerbeetle_tpu import device as device_mod

            self.metrics.gauge_fn(
                "dev.compile.count",
                lambda: device_mod.compile_stats()["count"],
            )
            self.metrics.gauge_fn(
                "dev.compile.seconds",
                lambda: device_mod.compile_stats()["seconds"],
            )
            # Speculative-execution counters live on the MACHINE
            # registry (dev_wave.spec.*, next to the dev_wave.*
            # routing stats) so the stats scrape and flight postmortem
            # carry them; the engine increments the shared handles.
            from tigerbeetle_tpu.state_machine.device_engine import (
                make_spec_stats,
                make_touch_stats,
            )

            self._dev.spec_stats = make_spec_stats(self.metrics)
            self._dev.touch_stats = make_touch_stats(self.metrics)
            self._bind_tier_stats()
            # Off-hot-path warmup of the named kinds' transfer plans +
            # scan compiles (construction happens before serving).
            from tigerbeetle_tpu import envcheck as _envcheck

            warm_kinds = _envcheck.env_str("TB_DEV_PREWARM", "")
            if warm_kinds:
                self._dev.prewarm(warm_kinds.split(","))
        else:
            self._dev = kernel_fast.DeviceTable(account_capacity)
            self._dev.mirror = self._mirror
            self._bind_tier_stats()
        # Native C++ fast path (native/tb_fastpath.cpp): wire decode,
        # static ladder, account resolution, duplicate detection and
        # u128 overflow admission run natively; the balance mirror is
        # re-pointed at the native library's memory so both sides share
        # one copy.  The library is built from source on first use and
        # a failed build raises (runtime/native.py); the pure-Python
        # engines run only where asked for (TB_FASTPATH_DISABLE).
        self._native = None
        from tigerbeetle_tpu.runtime import fastpath

        if fastpath.available():
            self._native = fastpath.NativeFastpath(account_capacity)
            self._mirror.lo = self._native.lo
            self._mirror.hi = self._native.hi

        # Transfer state.
        self._tdir = RunIndex(_dir_capacity(transfer_capacity))
        self._store = TailStore(
            _STORE_FIELDS, capacity=max(1024, transfer_capacity),
            cold_join=self._cold_join,
        )
        # expires_at index: (expires_at, row, active).  Rows are GLOBAL
        # store rows; live pendings never spill, so active entries
        # always resolve in the RAM tail.
        self._exp = Columns(
            {"expires_at": np.uint64, "row": np.uint32, "active": np.bool_}
        )
        self._history = Columns(_HISTORY_FIELDS)

        # LSM spill tier (attach_forest): None in standalone mode —
        # everything stays in RAM, as under testing/harness.py.  The
        # replica attaches a Forest so state scales past host RAM.
        self._forest = None
        self._hspill = None

        self._expiry_rows: np.ndarray | None = None
        self._exp_dead = 0

        self._inflight_timeouts = False
        # Declines by reason ("plan" = admission/profitability, "mesh"
        # = unsupported sharding geometry, "shard_plan" = plan shape
        # the SPMD executors don't cover, "degraded" = engine lost the
        # link mid-probe): measured, not guessed.  The dict is what
        # tests read; the scrape's cumulative per-reason counters
        # ride under dev_wave.decline.*.
        self.stat_dev_wave_decline_reasons: dict = {}

    # Compatibility properties: migrated stat_* counters live in the
    # metrics registry (reads and writes route to handles; ROADMAP
    # D13).
    stat_device_events = obs_stat_property("stat_device_events")
    stat_exact_events = obs_stat_property("stat_exact_events")
    stat_host_semantic_events = obs_stat_property("stat_host_semantic_events")
    stat_fallback_events = obs_stat_property("stat_fallback_events")
    stat_linked_batches = obs_stat_property("stat_linked_batches")
    stat_two_phase_batches = obs_stat_property("stat_two_phase_batches")
    stat_resolve_iters = obs_stat_property("stat_resolve_iters")
    stat_hot_tail_batches = obs_stat_property("stat_hot_tail_batches")
    stat_slow_tail_batches = obs_stat_property("stat_slow_tail_batches")
    stat_wave_batches = obs_stat_property("stat_wave_batches")
    stat_wave_steps = obs_stat_property("stat_wave_steps")
    stat_wave_events = obs_stat_property("stat_wave_events")
    stat_wave_parallel_events = obs_stat_property("stat_wave_parallel_events")
    stat_dev_wave_batches = obs_stat_property("stat_dev_wave_batches")
    stat_dev_wave_declined = obs_stat_property("stat_dev_wave_declined")
    stat_dev_wave_steps = obs_stat_property("stat_dev_wave_steps")
    stat_dev_wave_events = obs_stat_property("stat_dev_wave_events")
    stat_dev_wave_plan_s = obs_stat_property("stat_dev_wave_plan_s")

    def _cold_join(self, rows: int):
        self._c_join_cold_rows.inc(rows)
        return self.tracer.stage(self._st_join_cold)

    def _spill_parts(self):
        """-> (the run `TransferSpill.spill` opens, the part it moves
        on to)."""
        return (
            self.tracer.stage(self._st_spill_objects), self._st_spill_index
        )

    def _transfer_spill(self):
        """The spill handle over the forest's grooves (attach_forest,
        and again after a restore reopened them)."""
        from tigerbeetle_tpu.state_machine import spill as spill_mod

        forest = self._forest
        return spill_mod.TransferSpill(
            forest.grooves["transfers"], forest.grooves["transfers_posted"],
            self.metrics.scope("store"),
            attrs_fn=lambda: self._attrs, barrier=forest.barrier,
            parts=self._spill_parts,
        )

    def set_tracer(self, tracer) -> None:
        self.tracer = tracer
        if hasattr(self._dev, "tracer"):
            self._dev.tracer = tracer

    def device_report(self) -> dict:
        """What this machine runs on and how its engine fares — the
        server's start-up line and the `device` key of its stats
        scrape, so a launcher that must stay off JAX can tell a chip
        from the CPU backend and a healthy engine from a demoted one."""
        from tigerbeetle_tpu import device

        state = getattr(self._dev, "state", None)
        return {
            **device.describe(),
            "engine": self.engine,
            "state": state.name if state is not None else "healthy",
            "last_demotion": getattr(self._dev, "last_demotion", None),
            "compile": device.compile_stats(),
        }

    @property
    def stat_device_semantic_events(self) -> int:
        """Events whose result codes were computed on device."""
        return (
            self._dev.stat_semantic_events if self.engine == "device" else 0
        )

    @property
    def _balances(self):
        """Current device table handle behind a flush barrier."""
        return self._dev.read()

    @_balances.setter
    def _balances(self, value) -> None:
        # write_back gathers hot rows under tiering (plain handle swap
        # all-resident) — never assign self._dev.balances directly.
        self._dev.write_back(value)

    def sync(self) -> None:
        """Drain the write-behind queue and wait for the device."""
        jax.block_until_ready(self._dev.read())

    def _engine_drain(self) -> None:
        if self.engine == "device":
            self._dev.drain()

    def _bind_tier_stats(self) -> None:
        """Bind MACHINE-registry dev_tier.* handles to the hot tier
        (both engine modes; no-op all-resident) — same contract as the
        dev_wave.spec.* binding above."""
        hot = getattr(self._dev, "hot", None)
        if hot is None:
            return
        from tigerbeetle_tpu.state_machine.device_engine import (
            make_tier_stats,
        )

        hot.stats = make_tier_stats(self.metrics)

    def _commit_meta_cols(self, slots: np.ndarray) -> np.ndarray:
        """(k, 2) uint32 account-meta columns (flags, ledger) for the
        state commitment — read live from the attribute store, zeros
        past the live account count (matching the engine's meta
        table, where rolled-back/unused slots are zero)."""
        slots = np.asarray(slots, np.int64)
        out = np.zeros((len(slots), 2), np.uint32)
        m = slots < self._attrs.count
        if m.any():
            out[m, 0] = self._attrs.col("flags")[slots[m]]
            out[m, 1] = self._attrs.col("ledger")[slots[m]]
        return out

    def _commit_touch_accounts(self, n0: int) -> None:
        """Fold accounts created since slot n0 (their meta columns
        just became nonzero) into the host commitment twin.  Device
        engines already refreshed these rows in
        DeviceEngine.add_accounts (via _sync_engine_meta, which runs
        first at both call sites) — re-hashing them here would be an
        idempotent double pay."""
        if self._commitment is None or self._attrs.count <= n0:
            return
        if self.engine == "device":
            return
        self._commitment.refresh(
            np.arange(n0, self._attrs.count, dtype=np.int64), self._mirror
        )

    def state_root(self) -> bytes:
        """16-byte state commitment of the account table (balances +
        meta), current to the last materialized commit: the
        incrementally-maintained twin when TB_STATE_COMMIT=1, a
        from-scratch digest of the same value otherwise.  Read-only —
        never touches the device link (healthy, degraded, and
        recovering engines all agree with the host by contract; the
        scrub/handshake/checkpoint tripwires enforce it)."""
        from tigerbeetle_tpu.state_machine import commitment as cm

        if self._commitment is not None:
            return self._commitment.root_bytes()
        n = self._attrs.count
        bal8 = np.empty((n, 8), np.uint64)
        bal8[:, 0::2] = self._mirror.lo[:n]
        bal8[:, 1::2] = self._mirror.hi[:n]
        meta = self._commit_meta_cols(np.arange(n, dtype=np.int64))
        return cm.root_bytes(cm.table_digest(bal8, meta))

    def verify_device_mirror(self, part=NOOP_RUN) -> None:
        """Compare the device balance table against the host mirror via
        an order-independent digest; crash loudly on divergence
        (VERDICT r3 #4).  Called from the checkpoint barrier.  In
        degraded mode the mirror IS the authoritative table, so there
        is nothing to compare (and no device work that could be done)
        — the handshake that matters there is re-promotion's
        (device_engine.try_repromote).

        With the incremental commitment live the compare is 32 fetched
        bytes (device maintained digest + from-scratch recompute vs
        the host twin); the full-table fetch runs only to NAME the
        diverged rows in the crash message.

        `part`: the snapshot's open run (sm.ckpt.verify_device as it
        comes), moved on to sm.ckpt.verify_host for the host's pass."""
        from tigerbeetle_tpu.state_machine import device_kernels as dk
        from tigerbeetle_tpu.state_machine.device_engine import (
            DeviceLostError,
        )

        dev = self._dev
        if getattr(dev, "state", None) is not None:
            if dev.state is not types.EngineState.healthy:
                return
            if (
                dev._commit_enabled
                and self._commitment is not None
                and dev.dev_digest is not None
            ):
                from tigerbeetle_tpu.state_machine import commitment as cm

                dev.drain()
                dev.flush()
                if dev.state is not types.EngineState.healthy:
                    return
                try:
                    pair = np.asarray(dev.commit_probe())
                except DeviceLostError as exc:
                    dev._demote(exc)
                    return
                twin = self._commitment.digest
                # Tiered, the device digest is the HOT PARTIAL of the
                # logical root: fold(hot, cold) == twin.digest by the
                # r15 order-independent algebra, so comparing the
                # partial attests the device AND (via twin ==
                # host_scratch below) the whole logical table.
                expected_dev = (
                    self._commitment.partial(dev.hot.occupied())
                    if dev.hot is not None
                    else twin
                )
                # Checkpoint tripwire = the strongest compare: the
                # device's maintained digest, its from-scratch
                # recompute, the incrementally-maintained host twin,
                # AND a from-scratch host digest of the mirror must
                # all agree — so device drift, HBM corruption, twin
                # drift, and out-of-band mirror mutation each die
                # here, four-way-attributed.  (The host pass costs
                # what the old checksum8 compare cost; the CHEAP
                # 16-byte compares are scrub's and the handshake's.)
                part.switch(self._st_ckpt_verify_host)
                n_rows = len(self._mirror.lo)
                bal8 = np.empty((n_rows, 8), np.uint64)
                bal8[:, 0::2] = self._mirror.lo
                bal8[:, 1::2] = self._mirror.hi
                host_scratch = cm.table_digest(
                    bal8,
                    self._commit_meta_cols(
                        np.arange(n_rows, dtype=np.int64)
                    ),
                )
                if (
                    (pair[0] == pair[1]).all()
                    and (pair[1] == expected_dev).all()
                    and (twin == host_scratch).all()
                ):
                    return
                try:
                    rows = dev._localize_divergence()
                    detail = (
                        f"{len(rows)} rows diverged"
                        f" (first: {rows[:8].tolist()})"
                    )
                except DeviceLostError as exc:
                    detail = f"localization fetch failed: {exc!r}"
                raise AssertionError(
                    "device/mirror commitment divergence at checkpoint: "
                    f"{detail}; device(maintained, scratch)={pair.tolist()} "
                    f"twin={twin.tolist()} "
                    f"host_scratch={host_scratch.tolist()}"
                )
            if dev.hot is not None:
                # Tiered without commitment: dev.checksum() answers
                # from the mirror (trivially equal) — compare the
                # hot-shaped device tables against the hot-shaped host
                # images instead.
                dev.drain()
                dev.flush()
                if dev.state is not types.EngineState.healthy:
                    return
                try:
                    dev_sum = dev._device_health_digest()
                except DeviceLostError as exc:
                    dev._demote(exc)
                    return
                host_sum = dev._host_health_digest()
            else:
                dev_sum = dev.checksum()  # drains + flushes internally
                if dev.state is not types.EngineState.healthy:
                    return  # the checksum crossing itself demoted
                host_sum = self._mirror.checksum8(dev.capacity)
        else:
            # Host-engine mode: _dev is a kernel_fast.DeviceTable.
            if dev.hot is not None:
                # Tiered: read() serves the logical table FROM the
                # mirror — compare the actual hot device table against
                # the mirror's hot-shaped image instead.
                from tigerbeetle_tpu.state_machine.hot_tier import (
                    mirror_hot_table8,
                )

                from tigerbeetle_tpu.state_machine.mirror import (
                    digest_columns,
                )

                dev.flush()
                dev_sum = digest_columns(np.asarray(dev.balances))
                host_sum = digest_columns(
                    mirror_hot_table8(self._mirror, dev.hot.logical_of)
                )
            else:
                table = dev.read()
                dev_sum = np.asarray(dk.checksum(table))
                host_sum = self._mirror.checksum8(int(table.shape[0]))
        if not (dev_sum == host_sum).all():
            raise AssertionError(
                "device/mirror balance divergence at checkpoint: "
                f"device={dev_sum.tolist()} host={host_sum.tolist()}"
            )

    # ------------------------------------------------------------------
    # LSM spill tier (replica mode).

    def attach_forest(self, forest) -> None:
        """Wire the LSM forest in: transfers + history grooves back the
        columnar stores so durable state scales past host RAM
        (reference: src/lsm/forest.zig:31, groove.zig:136-176)."""
        from tigerbeetle_tpu.state_machine import spill as spill_mod

        assert self._forest is None
        self._forest = forest
        transfers = forest.groove(
            "transfers",
            object_size=spill_mod.TRANSFER_OBJECT_SIZE,
            index_fields=["dr_slot", "cr_slot"],
            index_value_size=8,
        )
        # Index entries are 25B vs 161B objects; sealing them 8x less
        # often keeps their levels shallow (every index run overlaps —
        # (slot, ts) keys never move-optimize), cutting merge rewrite
        # volume on the commit path.
        for tree in transfers.indexes.values():
            tree.memtable_max *= 8
        # Object rows arrive one 8k spill beat at a time; sealing every
        # beat makes level-0 churn (and the GROWTH-way merge cascade)
        # the dominant durable-path cost.  4x fewer, 4x larger runs cut
        # the per-event seal+merge work at ~5MB of memtable RAM.
        transfers.object_tree.memtable_max *= 4
        history = forest.groove(
            "account_history",
            object_size=spill_mod.HISTORY_OBJECT_SIZE,
            index_fields=[],
        )
        # The status of a pending finalised after it spilled, keyed by
        # the pending's row.  Declared LAST: a tree's id is its place
        # in this order, and a data file's manifest log names trees by
        # id.
        forest.groove(
            "transfers_posted",
            object_size=spill_mod.POSTED_OBJECT_SIZE,
            index_fields=[],
        )
        self._store.spill = self._transfer_spill()
        self._hspill = spill_mod.HistorySpill(history, barrier=forest.barrier)

    def spill_beat(
        self, max_rows: int = 8192, keep_min: int | None = None
    ) -> tuple | None:
        """Paced spill, the loop's half: take at most `max_rows` of the
        OLDEST RAM-tail rows, keeping the most recent `keep_min` hot in
        RAM, and return them as the arguments of `spill_rows` (None:
        nothing to spill).  Called once per commit by the replica, so
        the spill cost (and the compaction debt it creates) amortizes
        across the interval instead of landing inside the checkpoint
        (reference: src/lsm/compaction.zig — data enters the LSM per
        beat, not per checkpoint).  Deterministic: state-dependent
        only.

        The rows leave the tail here and now, COPIED: `drop_prefix`
        moves memory in place and later commits append, while
        `spill_rows` runs behind the commit on the forest's beat
        worker (lsm/beats.py).  From here on they are below
        `_store.base`, and a read of them joins the worker first."""
        if self._forest is None:
            return None
        if keep_min is None:
            keep_min = max(self.config.spill_keep_rows, 16_384)
        st = self._store
        if st.tail_count() <= keep_min:
            return None
        take = min(max_rows, st.tail_count() - keep_min)
        with self.tracer.stage(self._st_spill_take):
            rows = np.arange(st.base, st.base + take, dtype=np.int64)
            cols = {
                name: st.col(name)[:take].copy() for name in _STORE_FIELDS
            }
            st.drop_prefix(take)
        # History spills at checkpoint only (checkpoint_spill): its
        # rows are append-only and bounded per interval, and a per-beat
        # prefix rebuild would cost more copying than it saves.
        return rows, cols

    def spill_rows(self, rows: np.ndarray, cols: dict) -> None:
        """The beat's half of `spill_beat`: objects, index entries and
        seals for the rows it took (on the beat worker where there is
        one)."""
        self._store.spill.spill(rows, cols, self._attrs)

    def checkpoint_spill(self) -> None:
        """Move the whole RAM tail into the LSM tier — including live
        pendings, whose later status goes to the posted groove through
        TransferSpill.update_status (a stuck pending must not pin every
        later row in RAM).  Called by the replica at checkpoint —
        deterministic across replicas (state-dependent only), keeping
        checkpoint snapshots O(RAM tail), not O(history)
        (reference: src/vsr/replica.zig:3886-4039 checkpoint_data)."""
        if self._forest is None:
            return
        # The beats handed over come first: the freeze sees a drained
        # forest, so blobs stay functions of the committed state.
        self._forest.barrier()
        st = self._store
        # Retain the hot tail across checkpoints when configured: the
        # snapshot blob carries it, so checkpoint cost is O(one beat's
        # residue) instead of O(interval).
        limit = max(0, st.tail_count() - self.config.spill_keep_rows)
        if limit > 0:
            rows = np.arange(st.base, st.base + limit, dtype=np.int64)
            cols = {
                name: st.col(name)[:limit] for name in _STORE_FIELDS
            }
            st.spill.spill(rows, cols, self._attrs)
            st.drop_prefix(limit)
        # History is append-only: spill everything.
        h = self._history
        if h.count:
            self._hspill.spill(
                {name: h.col(name) for name in _HISTORY_FIELDS}
            )
            h.truncate(0)
        self._forest.checkpoint()

    # ------------------------------------------------------------------
    # Introspection helpers shared with CpuStateMachine.

    def _transfer_row(self, id_value: int) -> int | None:
        found, row = self._tdir.lookup(
            np.array([id_value & 0xFFFFFFFFFFFFFFFF], np.uint64),
            np.array([id_value >> 64], np.uint64),
        )
        return int(row[0]) if found[0] else None

    def transfer_timestamp(self, id_value: int) -> int | None:
        row = self._transfer_row(id_value)
        return None if row is None else int(self._store["timestamp"][row])

    def pending_status(self, id_value: int) -> TransferPendingStatus | None:
        row = self._transfer_row(id_value)
        if row is None:
            return None
        status = int(self._store["status"][row])
        return None if status == 0 else TransferPendingStatus(status)

    @property
    def history_count(self) -> int:
        return self._history.count

    def account_balances_raw(self, id_value: int) -> tuple | None:
        """(debits_pending, debits_posted, credits_pending,
        credits_posted) without going through a commit."""
        slot = self._account_slot(id_value)
        if slot is None:
            return None
        row = np.asarray(self._balances[slot])
        u = lambda i: int(row[i]) | (int(row[i + 1]) << 64)
        return (u(0), u(2), u(4), u(6))

    # ------------------------------------------------------------------
    # Interface plumbing (mirrors CpuStateMachine).

    def input_valid(self, operation: Operation, input_bytes: bytes) -> bool:
        return CpuStateMachine.input_valid(self, operation, input_bytes)

    def prepare(self, operation: Operation, input_bytes: bytes) -> None:
        CpuStateMachine.prepare(self, operation, input_bytes)

    def pulse_needed(self) -> bool:
        # In device mode an in-flight batch may be about to create a
        # timeout-carrying pending, which would pull
        # pulse_next_timestamp earlier — drain before deciding so the
        # pulse schedule matches the oracle exactly.  Timeout batches
        # are routed to the host path anyway, so this only fires when
        # such a batch is genuinely in flight.
        if (
            self.engine == "device"
            and self._inflight_timeouts
            and self._dev.has_inflight()
        ):
            self._engine_drain()
        if self.engine == "device" and not self._dev.has_inflight():
            self._inflight_timeouts = False
        return self.pulse_next_timestamp <= self.prepare_timestamp

    def prefetch(
        self, operation: Operation, input_bytes: bytes, prefetch_timestamp: int
    ) -> None:
        if operation == Operation.pulse:
            self._engine_drain()
            self._expiry_rows = self._scan_expired(prefetch_timestamp)

    def commit(
        self,
        client: int,
        op: int,
        timestamp: int,
        operation: Operation,
        input_bytes: bytes,
    ) -> bytes:
        return self.commit_async(
            client, op, timestamp, operation, input_bytes
        ).result()

    def commit_async(
        self,
        client: int,
        op: int,
        timestamp: int,
        operation: Operation,
        input_bytes: bytes,
    ):
        """Dispatch one committed operation; returns a ReplyFuture.

        In host-engine mode every reply resolves synchronously.  In
        device mode create_transfers batches (and lookup_accounts
        balance gathers) resolve when their summary/gather rides the
        next ring fetch — the pipelined path the
        replica drives (reference: the reference client pipelines
        batches the same way, src/clients/c/tb_client/packet.zig).
        """
        from tigerbeetle_tpu.state_machine.device_engine import ReplyFuture

        assert op != 0
        assert self.input_valid(operation, input_bytes)
        assert timestamp > self.commit_timestamp
        if self.anatomy_trace:
            # The request's device-window hop: when its batch was
            # handed to the engine (window admit / host dispatch).
            self.anatomy.stage(self.anatomy_trace, "device_dispatch")
        if self.engine == "device":
            # Lifecycle tick on EVERY committed operation (not just
            # transfers): re-promotion probes while degraded must fire
            # even when the workload shifts to lookups/creates, and
            # the healthy-mode scrub cadence keeps being evaluated.
            self._dev.tick()
        if operation == Operation.create_transfers:
            if self.engine == "device":
                return self._commit_create_transfers_device(
                    timestamp, input_bytes
                )
            return ReplyFuture(
                value=self._commit_create_transfers(timestamp, input_bytes)
            )
        if operation == Operation.lookup_accounts:
            if self.engine == "device" and self._dev.has_inflight():
                return self._lookup_accounts_device(input_bytes)
            return ReplyFuture(value=self._lookup_accounts(input_bytes))
        if operation == Operation.pulse:
            return ReplyFuture(value=self._commit_expire(timestamp))
        if operation == Operation.create_accounts:
            return ReplyFuture(
                value=self._commit_create_accounts(timestamp, input_bytes)
            )
        # Store-reading queries: exact only against materialized state.
        self._engine_drain()
        if operation == Operation.lookup_transfers:
            return ReplyFuture(value=self._lookup_transfers(input_bytes))
        if operation == Operation.get_account_transfers:
            return ReplyFuture(value=self._get_account_transfers(input_bytes))
        if operation == Operation.get_account_balances:
            return ReplyFuture(value=self._get_account_balances(input_bytes))
        raise AssertionError(operation)

    # ------------------------------------------------------------------
    # Accounts (cold path: per-event, exact oracle semantics).

    def _account_slot(self, id_value: int) -> int | None:
        found, slot = self._acct_dir.lookup(
            np.array([id_value & 0xFFFFFFFFFFFFFFFF], np.uint64),
            np.array([id_value >> 64], np.uint64),
        )
        return int(slot[0]) if found[0] else None

    def _sync_engine_meta(self, n0: int) -> None:
        """Register accounts created since slot n0 with the device
        engine's meta table (device-mode ladder/limit inputs)."""
        if self.engine != "device" or self._attrs.count <= n0:
            return
        slots = np.arange(n0, self._attrs.count, dtype=np.int64)
        self._dev.add_accounts(
            slots,
            self._attrs.col("flags")[n0:],
            self._attrs.col("ledger")[n0:],
        )

    def _commit_create_accounts(self, timestamp: int, input_bytes: bytes) -> bytes:
        events = np.frombuffer(input_bytes, dtype=ACCOUNT_DTYPE)
        n = len(events)
        n0 = self._attrs.count

        reply = self._commit_create_accounts_fast(timestamp, events, n)
        if reply is not None:
            self._sync_engine_meta(n0)
            self._commit_touch_accounts(n0)
            return reply
        results: list[tuple[int, int]] = []

        chain: int | None = None
        chain_broken = False
        # Undo scope for linked chains: slots allocated in the open chain.
        scope_slots: list[int] = []

        committed: list[dict] = []  # attr rows staged this batch

        def exists_ladder(ev: dict, slot: int) -> int:
            a = self._attrs
            if ev["flags"] != int(a["flags"][slot]):
                return CAR.exists_with_different_flags
            if ev["ud128_lo"] != int(a["ud128_lo"][slot]) or ev["ud128_hi"] != int(
                a["ud128_hi"][slot]
            ):
                return CAR.exists_with_different_user_data_128
            if ev["ud64"] != int(a["ud64"][slot]):
                return CAR.exists_with_different_user_data_64
            if ev["ud32"] != int(a["ud32"][slot]):
                return CAR.exists_with_different_user_data_32
            if ev["ledger"] != int(a["ledger"][slot]):
                return CAR.exists_with_different_ledger
            if ev["code"] != int(a["code"][slot]):
                return CAR.exists_with_different_code
            return CAR.exists

        def rollback_scope() -> None:
            if not scope_slots:
                return
            self._acct_dir.remove(
                self._attrs["id_lo"][scope_slots],
                self._attrs["id_hi"][scope_slots],
            )
            if self._native is not None:
                self._native.remove_accounts(
                    self._attrs["id_lo"][scope_slots],
                    self._attrs["id_hi"][scope_slots],
                )
            self._attrs.truncate(min(scope_slots))
            scope_slots.clear()

        for index in range(n):
            row = events[index]
            ev = {
                "id": types.u128_get(row, "id"),
                "flags": int(row["flags"]),
                "ud128_lo": int(row["user_data_128_lo"]),
                "ud128_hi": int(row["user_data_128_hi"]),
                "ud64": int(row["user_data_64"]),
                "ud32": int(row["user_data_32"]),
                "ledger": int(row["ledger"]),
                "code": int(row["code"]),
            }
            linked = bool(ev["flags"] & AF.linked)

            result: int | None = None
            if linked:
                if chain is None:
                    chain = index
                    assert not chain_broken
                    scope_slots.clear()
                if index == n - 1:
                    result = CAR.linked_event_chain_open
            if result is None and chain_broken:
                result = CAR.linked_event_failed
            if result is None and int(row["timestamp"]) != 0:
                result = CAR.timestamp_must_be_zero

            if result is None:
                result = self._create_account_checked(row, ev, exists_ladder)
                if result == CAR.ok:
                    slot = self._attrs.count
                    self._attrs.append(
                        id_lo=np.array([row["id_lo"]]),
                        id_hi=np.array([row["id_hi"]]),
                        ud128_lo=np.array([row["user_data_128_lo"]]),
                        ud128_hi=np.array([row["user_data_128_hi"]]),
                        ud64=np.array([row["user_data_64"]]),
                        ud32=np.array([row["user_data_32"]]),
                        ledger=np.array([row["ledger"]]),
                        code=np.array([row["code"]]),
                        flags=np.array([row["flags"]]),
                        timestamp=np.array([timestamp - n + index + 1], np.uint64),
                    )
                    self._acct_dir.insert(
                        np.array([row["id_lo"]], np.uint64),
                        np.array([row["id_hi"]], np.uint64),
                        np.array([slot], np.uint64),
                    )
                    if self._native is not None:
                        # A capacity rebuild re-registers everything in
                        # _attrs (including this row) — only register
                        # explicitly when no rebuild happened.
                        native = self._native
                        self._ensure_balance_capacity(self._attrs.count)
                        if self._native is native:
                            native.add_accounts(
                                np.array([row["id_lo"]], np.uint64),
                                np.array([row["id_hi"]], np.uint64),
                                np.array([row["flags"]], np.uint32),
                                np.array([row["ledger"]], np.uint32),
                                base_slot=slot,
                            )
                    if chain is not None:
                        scope_slots.append(slot)
                    self.commit_timestamp = timestamp - n + index + 1

            if result != CAR.ok:
                if chain is not None:
                    if not chain_broken:
                        chain_broken = True
                        rollback_scope()
                        for chain_index in range(chain, index):
                            results.append((chain_index, CAR.linked_event_failed))
                results.append((index, int(result)))

            if chain is not None and (
                not linked or result == CAR.linked_event_chain_open
            ):
                scope_slots.clear()
                chain = None
                chain_broken = False

        self._ensure_balance_capacity(self._attrs.count)
        self._sync_engine_meta(n0)
        self._commit_touch_accounts(n0)

        out = np.zeros(len(results), dtype=CREATE_RESULT_DTYPE)
        for i, (index, result) in enumerate(results):
            out[i]["index"] = index
            out[i]["result"] = result
        return out.tobytes()

    def _commit_create_accounts_fast(
        self, timestamp: int, events: np.ndarray, n: int
    ) -> bytes | None:
        """Vectorized all-valid batch: no chains, no failures, no
        existing ids — else None routes to the exact per-event loop."""
        if n == 0:
            return b""
        flags = events["flags"].astype(np.uint32)
        if (flags & np.uint32(AF.linked)).any():
            return None
        id_lo = events["id_lo"].astype(np.uint64)
        id_hi = events["id_hi"].astype(np.uint64)
        invalid = (
            (events["timestamp"] != 0)
            | (events["reserved"] != 0)
            | ((flags & ~np.uint32(0xF)) != 0)
            | ((id_lo == 0) & (id_hi == 0))
            | ((id_lo == np.uint64(U64_MAX)) & (id_hi == np.uint64(U64_MAX)))
            | (
                ((flags & np.uint32(AF.debits_must_not_exceed_credits)) != 0)
                & ((flags & np.uint32(AF.credits_must_not_exceed_debits)) != 0)
            )
            | (events["ledger"] == 0)
            | (events["code"] == 0)
        )
        for field in ("debits_pending", "debits_posted", "credits_pending",
                      "credits_posted"):
            invalid |= (events[f"{field}_lo"] != 0) | (events[f"{field}_hi"] != 0)
        if invalid.any():
            return None
        if n > 1 and not (
            (id_hi[1:] == id_hi[:-1]).all() and (id_lo[1:] > id_lo[:-1]).all()
        ):
            mix = id_lo * np.uint64(0x9E3779B97F4A7C15) + id_hi * np.uint64(
                0xC2B2AE3D27D4EB4F
            )
            if len(np.unique(mix)) != n:
                return None
        found, _ = self._acct_dir.lookup(id_lo, id_hi)
        if found.any():
            return None

        base = self._attrs.count
        ts0 = np.uint64(timestamp - n + 1)
        rows = self._attrs.append(
            id_lo=id_lo, id_hi=id_hi,
            ud128_lo=events["user_data_128_lo"],
            ud128_hi=events["user_data_128_hi"],
            ud64=events["user_data_64"], ud32=events["user_data_32"],
            ledger=events["ledger"], code=events["code"], flags=flags,
            timestamp=ts0 + np.arange(n, dtype=np.uint64),
        )
        assert rows[0] == base
        self._acct_dir.insert(id_lo, id_hi, rows.astype(np.uint64))
        self.commit_timestamp = timestamp
        native = self._native
        self._ensure_balance_capacity(self._attrs.count)
        # A capacity rebuild already re-registered every account.
        if native is not None and self._native is native:
            native.add_accounts(
                id_lo, id_hi, flags, events["ledger"], base_slot=base
            )
        return b""

    def _create_account_checked(self, row, ev, exists_ladder) -> int:
        # reference: src/state_machine.zig:1421-1448
        if int(row["reserved"]) != 0:
            return CAR.reserved_field
        if ev["flags"] & ~0xF:
            return CAR.reserved_flag
        if ev["id"] == 0:
            return CAR.id_must_not_be_zero
        if ev["id"] == U128_MAX:
            return CAR.id_must_not_be_int_max
        if (ev["flags"] & AF.debits_must_not_exceed_credits) and (
            ev["flags"] & AF.credits_must_not_exceed_debits
        ):
            return CAR.flags_are_mutually_exclusive
        for field in ("debits_pending", "debits_posted", "credits_pending", "credits_posted"):
            if types.u128_get(row, field) != 0:
                return getattr(CAR, f"{field}_must_be_zero")
        if ev["ledger"] == 0:
            return CAR.ledger_must_not_be_zero
        if ev["code"] == 0:
            return CAR.code_must_not_be_zero
        slot = self._account_slot(ev["id"])
        if slot is not None:
            return exists_ladder(ev, slot)
        return CAR.ok

    def _ensure_balance_capacity(self, slots: int) -> None:
        """Called with the new account count by every path that moves
        it: the count goes to its gauge, the tables grow to hold it."""
        self._g_accounts.set(slots)
        # The engine's logical capacity, not the live array shape: a
        # degraded device engine defers widening its HBM tables until
        # re-promotion, but its committed capacity already grew.
        cap = getattr(self._dev, "capacity", None)
        if cap is None:
            cap = self._dev.balances.shape[0]
        if slots <= cap:
            return
        while cap < slots:
            cap *= 2
        self._dev.grow(cap)
        if self._native is not None:
            self._rebuild_native(cap)
        else:
            self._mirror.grow(cap)

    def _rebuild_native(self, capacity: int) -> None:
        """Recreate the native fast path at a new capacity (growth or
        restore): copy balances, re-point the shared mirror, and
        re-register the id directories."""
        from tigerbeetle_tpu.runtime import fastpath

        old_lo, old_hi = self._mirror.lo, self._mirror.hi
        native = fastpath.NativeFastpath(capacity)
        native.lo[: len(old_lo)] = old_lo
        native.hi[: len(old_hi)] = old_hi
        n_acct = self._attrs.count
        if n_acct:
            native.add_accounts(
                self._attrs.col("id_lo"), self._attrs.col("id_hi"),
                self._attrs.col("flags"), self._attrs.col("ledger"),
                base_slot=0,
            )
        if self._store.base:
            from tigerbeetle_tpu.state_machine import spill as spill_mod

            for rows, obj in self._store.spill.iter_objects():
                cols = spill_mod.unpack_objects(obj)
                native.add_transfer_ids(
                    cols["id_lo"], cols["id_hi"], int(rows[0])
                )
        if self._store.tail_count():
            native.add_transfer_ids(
                self._store.col("id_lo"), self._store.col("id_hi"),
                self._store.base,
            )
        self._native = native
        self._mirror.lo = native.lo
        self._mirror.hi = native.hi

    # ------------------------------------------------------------------
    # create_transfers (the hot path).

    # ------------------------------------------------------------------
    # Device-authoritative create_transfers (engine == "device").

    def _commit_create_transfers_device(self, timestamp: int, input_bytes: bytes):
        """Route a batch to a device semantic kernel; host does joins,
        the device computes result codes (VERDICT r3 #1).  Falls back
        to the (drained) host path for shapes outside the kernels'
        classes — the same residual classes the r3 fast paths punted.

        The sm.plan stage is the planning alone: it ends where the
        engine's submit, or the host path, takes over.
        """
        with self.tracer.stage(self._st_plan):
            with self.tracer.stage(self._st_plan_decode) as part:
                submit = self._plan_create_transfers_device(
                    timestamp, input_bytes, part
                )
        return submit()

    def _plan_create_transfers_device(self, timestamp: int,
                                      input_bytes: bytes, part):
        """Decode, directory joins, routing and packing.  -> what
        resolves the batch, to be called: the engine's submit with its
        arguments bound, or the host path.  `part`: the plan's open
        run, moved on from part to part as the batch goes."""
        from tigerbeetle_tpu.state_machine import device_kernels as dk

        events = np.frombuffer(input_bytes, dtype=TRANSFER_DTYPE)
        n = len(events)
        ts_base = timestamp - n + 1
        host_path = self._host_path(events, timestamp, input_bytes)

        # A degraded engine serves every batch through the exact host
        # path (bit-identical replies) until commit_async's lifecycle
        # tick re-promotes it through the checksum handshake.
        if self._dev.state is not types.EngineState.healthy:
            return host_path

        if n == 0 or n > dk.B:
            return host_path

        # Forced-optimistic routing (TB_WAVES_SPECULATE=force): every
        # window batch — including shapes the semantic kernels could
        # serve — goes through the speculative wave dispatcher, the
        # differential-fuzz arm that maximizes coverage of the
        # validate-and-residue machinery.
        if waves.spec_mode() == "force":
            return host_path

        id_lo = np.asarray(events["id_lo"])
        id_hi = np.asarray(events["id_hi"])
        flags16 = np.asarray(events["flags"])
        flags = flags16.astype(np.uint32)
        timeout = events["timeout"].astype(np.uint64)
        amount_hi = np.asarray(events["amount_hi"])

        has_linked = bool((flags16 & np.uint16(TF.linked)).any())
        has_pending = bool((flags16 & np.uint16(TF.pending)).any())
        pv16 = np.uint16(TF.post_pending_transfer | TF.void_pending_transfer)
        has_pv = bool((flags16 & pv16).any())
        has_bal = bool(
            (flags16 & np.uint16(TF.balancing_debit | TF.balancing_credit)).any()
        )

        # Unique-id check (shared with the host router): ascending ids
        # prove uniqueness; else a 64-bit key mix.
        part.switch(self._st_plan_ids)
        ascending = n == 1 or bool(
            (
                (id_hi[1:] > id_hi[:-1])
                | ((id_hi[1:] == id_hi[:-1]) & (id_lo[1:] > id_lo[:-1]))
            ).all()
        )
        if ascending:
            ids_unique = True
        else:
            mix = id_lo * np.uint64(0x9E3779B97F4A7C15) + id_hi * np.uint64(
                0xC2B2AE3D27D4EB4F
            )
            ids_unique = len(np.unique(mix)) == n
        if not ids_unique or has_bal:
            return host_path

        # In-flight hazards: this batch's ids (duplicate checks) and —
        # for pv batches — its pending references must not collide
        # with batches whose bookkeeping hasn't materialized yet.  A pv
        # batch also RECORDS its pending-reference keys so a later
        # pipelined finalize of the same durable pending drains instead
        # of reading a stale status join (double-finalize hazard).
        keys = pack_u128(id_lo, id_hi)
        probe = keys
        ref = None
        if has_pv:
            # Only real references: pending_id == 0 means "no
            # reference" and must not alias across batches.
            plo = np.asarray(events["pending_id_lo"])
            phi = np.asarray(events["pending_id_hi"])
            ref = (plo != 0) | (phi != 0)
            probe = np.concatenate([probe, pack_u128(plo[ref], phi[ref])])
        # (The order, not just the sorted keys: a cut batch picks each
        # run's keys out of it.)
        order = None
        keys_sorted = keys
        if has_pv or not ascending:
            order = np.argsort(probe)
            keys_sorted = probe[order]
        if self._dev.inflight_ids_hit(probe):
            self._engine_drain()

        part.switch(self._st_plan_id_dir)
        e_found, _e_row = self._tdir.lookup(id_lo, id_hi)
        if e_found.any():
            return host_path

        # Account joins (slots + flags for routing).
        part.switch(self._st_plan_accounts)
        dr_lo = np.asarray(events["debit_account_id_lo"])
        dr_hi = np.asarray(events["debit_account_id_hi"])
        cr_lo = np.asarray(events["credit_account_id_lo"])
        cr_hi = np.asarray(events["credit_account_id_hi"])
        dr_found, dr_slot_u = self._acct_dir.lookup(dr_lo, dr_hi)
        cr_found, cr_slot_u = self._acct_dir.lookup(cr_lo, cr_hi)
        dr_slot = np.where(dr_found, dr_slot_u.astype(np.int64), -1)
        cr_slot = np.where(cr_found, cr_slot_u.astype(np.int64), -1)
        attrs = self._attrs
        dr_flags = np.where(
            dr_found, attrs["flags"][np.clip(dr_slot, 0, None)], 0
        ).astype(np.uint32)
        cr_flags = np.where(
            cr_found, attrs["flags"][np.clip(cr_slot, 0, None)], 0
        ).astype(np.uint32)
        LIMH = np.uint32(
            AF.debits_must_not_exceed_credits
            | AF.credits_must_not_exceed_debits
            | AF.history
        )
        touch_limit_hist = bool(((dr_flags | cr_flags) & LIMH).any())
        touch_hist = bool(
            ((dr_flags | cr_flags) & np.uint32(AF.history)).any()
        )

        part.switch(self._st_plan_route)
        common = dict(
            events=events, n=n, ts_base=ts_base, id_lo=id_lo, id_hi=id_hi,
            dr_lo=dr_lo, dr_hi=dr_hi, cr_lo=cr_lo, cr_hi=cr_hi,
            flags=flags, timeout=timeout, dr_slot=dr_slot, cr_slot=cr_slot,
            keys_sorted=keys_sorted, timestamp=timestamp,
            input_bytes=input_bytes, part=part,
        )

        kernel = _kernel_class(
            _HAS_LINKED * has_linked | _HAS_PENDING * has_pending
            | _HAS_PV * has_pv | _TOUCH_LIMH * touch_limit_hist
            | _TOUCH_HIST * touch_hist | _AMOUNT_HI * bool(amount_hi.any())
            | _TIMEOUT * bool(timeout.any())
        )
        if kernel is None:
            # Mixed classes (a prepare that coalesced requests of
            # several): cut into runs, one kernel each.
            part.switch(self._st_plan_cut)
            runs = _cut_runs(flags16, dr_flags | cr_flags, amount_hi, timeout)
            if runs is None:
                return host_path
            return self._plan_runs(runs, common, order, ref, host_path)
        # Each packer returns the engine's submit, bound, or None when
        # the batch cannot run on device — under tiering, a
        # touched-account set the hot window cannot hold (tier_prefetch
        # declined) — and the exact host path takes over.
        submit = self._submitter(kernel)(**common)
        if submit is None:
            return host_path
        self._c_plan_runs.inc()
        return submit

    def _submitter(self, kernel: str):
        return {
            "orderfree": self._submit_device_orderfree,
            "linked": self._submit_device_linked,
            "two_phase": self._submit_device_two_phase,
        }[kernel]

    def _host_path(self, events, timestamp: int, input_bytes: bytes):
        """What resolves a batch no kernel takes: the engine's submit
        of a wave plan, or the drained host path."""
        from tigerbeetle_tpu.state_machine.device_engine import ReplyFuture

        def run() -> ReplyFuture:
            # Batches the semantic kernels cannot express first try
            # WAVE DISPATCH inside the device window (TB_DEV_WAVES):
            # the wave plan executes against the authoritative HBM
            # table instead of draining the stream to the host mirror.
            # On decline the decode/ladder work is handed to the host
            # path (it is drain-stale-proof: wire bytes + the
            # synchronously-maintained account attrs only), so a
            # persistently declining deployment does not pay it twice.
            fut, decoded = self._try_submit_device_waves(
                events, len(events), timestamp, input_bytes
            )
            if fut is not None:
                return fut
            self._engine_drain()
            return ReplyFuture(
                value=self._commit_create_transfers(
                    timestamp, input_bytes, decoded=decoded
                )
            )

        return run

    def _plan_runs(self, runs, common, order, ref, host_path):
        """A cut batch's runs, each planned and submitted as a batch of
        its own, in order: its events keep the timestamps they have in
        the whole batch, and its failures come home indexed from its
        first event, moved on by its offset (`PrepareReply`).  Exact by
        the argument that makes two consecutive prepares exact: a run's
        joins read what the runs before it left, because where one of
        its pending references names an id of a run still in flight the
        stream drains first (the hazard probe).  The first run is
        planned here, in the plan's open part `common["part"]`; the
        others once the runs before them were submitted, each in a
        sm.plan of its own.  -> what resolves the batch: the first
        run's plan failing (a tier punt, a join the kernel declines)
        leaves the whole batch to `host_path`; a later run's, the rest
        of the batch, as a prepare of its own would be."""
        from tigerbeetle_tpu.state_machine.device_engine import PrepareReply

        n, ts_base = common["n"], common["ts_base"]
        events, input_bytes = common["events"], common["input_bytes"]
        size = TRANSFER_DTYPE.itemsize
        keys_sorted = common["keys_sorted"]
        # Each run's sorted keys (the engine's hazard probes), picked
        # out of the batch's in their order: its events' ids, and for
        # a batch with posts and voids their pendings' ids after them.
        if order is None:
            run_keys = [keys_sorted[a:b] for a, b, _kernel in runs]
        else:
            owner = np.arange(n)
            if ref is not None:
                owner = np.concatenate([owner, np.flatnonzero(ref)])
            starts = np.array([a for a, _b, _kernel in runs])
            run_of = np.searchsorted(starts, owner[order], side="right") - 1
            run_keys = [keys_sorted[run_of == r] for r in range(len(runs))]
        sliced = [k for k, v in common.items()
                  if isinstance(v, np.ndarray) and k != "keys_sorted"]

        def plan(r: int, part):
            a, b, kernel = runs[r]
            args = {k: common[k][a:b] for k in sliced}
            args.update(
                n=b - a, ts_base=ts_base + a, keys_sorted=run_keys[r],
                timestamp=ts_base + b - 1,
                input_bytes=input_bytes[a * size:b * size], part=part,
            )
            return self._submitter(kernel)(**args)

        first = plan(0, common["part"])
        if first is None:
            return host_path

        def submit() -> PrepareReply:
            replies = [(0, first())]
            on_device = 1
            for r in range(1, len(runs)):
                a, _b, kernel = runs[r]
                if kernel == "two_phase" and self._dev.inflight_ids_hit(
                    run_keys[r], is_sorted=True
                ):
                    self._engine_drain()
                with self.tracer.stage(self._st_plan):
                    with self.tracer.stage(self._st_plan_route) as part:
                        go = plan(r, part)
                if go is None:
                    replies.append((a, self._host_path(
                        events[a:], common["timestamp"], input_bytes[a * size:]
                    )()))
                    break
                replies.append((a, go()))
                on_device += 1
            self._c_plan_runs.inc(on_device)
            self._c_cut_prepares.inc()
            return PrepareReply(replies)

        return submit

    def _device_pack_base(
        self, n, events, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
        flags, timeout, dr_slot, cr_slot, p_found=None, p_tgt=None,
        n_cols=None,
    ):
        from tigerbeetle_tpu.state_machine import device_kernels as dk

        return dk.pack_base(
            n, id_lo=id_lo, id_hi=id_hi,
            dr_lo=dr_lo, dr_hi=dr_hi, cr_lo=cr_lo, cr_hi=cr_hi,
            pend_lo=np.asarray(events["pending_id_lo"]),
            pend_hi=np.asarray(events["pending_id_hi"]),
            amount_lo=np.asarray(events["amount_lo"]),
            amount_hi=np.asarray(events["amount_hi"]),
            flags=flags, ledger=np.asarray(events["ledger"]),
            code=events["code"].astype(np.uint32),
            timeout=events["timeout"].astype(np.uint32),
            ts_nonzero=np.asarray(events["timestamp"] != 0),
            dr_slot=dr_slot, cr_slot=cr_slot,
            e_found=np.zeros(n, bool),  # router guarantees no dups
            p_found=p_found, p_tgt=p_tgt,
            n_cols=n_cols or dk.N_COLS,
        )

    def _device_fallback(self, timestamp, input_bytes):
        """Exact host re-execution for a flagged batch (engine has
        drained up to the batch before it; mirror is current)."""

        def run() -> bytes:
            self._stats["stat_fallback_events"].inc(
                len(input_bytes) // TRANSFER_DTYPE.itemsize
            )
            self._dev._suppress_enqueue = True
            try:
                return self._commit_create_transfers(timestamp, input_bytes)
            finally:
                self._dev._suppress_enqueue = False

        return run

    def _observe_plan_time(self, t0: float) -> None:
        """Record one wave-routing pass's host wall time (decode,
        joins, admission, and the partitioner whenever it ran)."""
        plan_dt = _time.perf_counter() - t0
        self._stats["stat_dev_wave_plan_s"].inc(plan_dt)
        self._h_dev_wave_plan.observe(plan_dt * 1e6)

    def _dev_wave_decline(self, reason: str) -> None:
        self._stats["stat_dev_wave_declined"].inc()
        # Cumulative per-reason registry counter (scrapeable) + the
        # dict tests read.
        self.metrics.counter("dev_wave.decline." + reason).inc()
        reasons = self.stat_dev_wave_decline_reasons
        reasons[reason] = reasons.get(reason, 0) + 1

    def _try_submit_device_waves(
        self, events, n, timestamp, input_bytes
    ):
        """Wave-dispatch one window batch that fell off the semantic
        kernels (mixed kinds, conflicting/duplicate ids, balancing,
        timeouts, two-phase edge shapes): host joins + overflow
        admission at submit time, then either OPTIMISTIC submission
        (TB_WAVES_SPECULATE: no plan — the whole batch speculates as
        one device step at launch and only a conflicted residue is
        planned, DeviceEngine._exec_spec) or the pessimistic wave plan
        (segment execution against the authoritative HBM table at
        window launch); exact-path bookkeeping runs from the
        fetched packed outputs at materialization either way.  Returns
        (reply_future, None), or (None, decoded) on decline
        (admission, profitability, TB_DEV_WAVES=0, degraded engine,
        unsupported sharding geometry, plan shapes the SPMD executors
        don't cover, oversize batch) — the caller drains to the host
        exactly as before, reusing the decode dict: the plan is never
        wrong, only occasionally slower.

        ROW-SHARDED engines submit too: the plan executes SPMD over
        the engine's ("shard",) mesh (waves._execute_plan_sharded) as
        long as the capability probe (DeviceEngine.wave_mesh) accepts
        the mesh and the plan carries only wave/chain segments
        (waves.plan_shardable) — anything else declines gracefully,
        counted by reason, never errors.

        Soundness of planning against a LAGGING mirror: the hazard
        probe drains on any id/pending-reference overlap with
        in-flight records (so the host joins here equal their
        post-drain values), and the overflow admission charges every
        in-flight record's amount bound on top of the mirror state
        (DeviceEngine.inflight_bound), so no execution order of the
        window can surface an ov_* code the plan assumed away."""
        dev = self._dev
        dm = waves.dev_mode()
        if dm == "0" or n == 0 or n > _BATCH_BUCKETS[-1]:
            return None, None
        if dev.state is not types.EngineState.healthy:
            return None, None
        if dev.hot is not None:
            # v1 tiering scope cut: wave/speculative event dicts index
            # the table by LOGICAL slot throughout (plan, executors,
            # residue replay) — decline and take the host path.
            self._dev_wave_decline("tier")
            return None, None
        sharded = dev.sharding is not None
        if sharded and dev.wave_mesh() is None:
            self._dev_wave_decline("mesh")
            return None, None
        t0 = _time.perf_counter()
        d = self._decode_static(events, n)
        ts_base = timestamp - n + 1

        # In-flight hazards: this batch's ids (duplicate/exists joins)
        # and real pending references must not collide with records
        # whose bookkeeping hasn't materialized yet.
        keys = pack_u128(d["id_lo"], d["id_hi"])
        probe = keys
        if d["is_pv"].any():
            ref = (d["pend_lo"] != 0) | (d["pend_hi"] != 0)
            probe = np.concatenate(
                [probe, pack_u128(d["pend_lo"][ref], d["pend_hi"][ref])]
            )
        if dev.inflight_ids_hit(probe):
            self._engine_drain()
            if dev.state is not types.EngineState.healthy:
                self._dev_wave_decline("degraded")
                return None, d

        e_found, e_row = self._tdir.lookup(d["id_lo"], d["id_hi"])
        id_lo, id_hi = d["id_lo"], d["id_hi"]
        ascending = n == 1 or bool(
            (
                (id_hi[1:] > id_hi[:-1])
                | ((id_hi[1:] == id_hi[:-1]) & (id_lo[1:] > id_lo[:-1]))
            ).all()
        )
        B = next(b for b in _BATCH_BUCKETS if b >= n)
        j = self._exact_joins(
            n, B, id_lo, id_hi, d["pend_lo"], d["pend_hi"], d["is_pv"],
            ascending, e_found, e_row,
        )
        meta, pv_serial = self._wave_metadata(
            n, d["flags"], d["dr_slot"], d["cr_slot"], d["dr_flags"],
            d["cr_flags"], j["id_group"], j["p_group"], j["p_tgt"],
            j["p_found"], j["gather_p"],
        )

        # Optimistic routing (TB_WAVES_SPECULATE): admitted batches on
        # a dense engine skip the partitioner entirely — the whole
        # batch executes as ONE speculative device step, validated on
        # device, with only the conflicted residue replayed through
        # plan_waves at launch (DeviceEngine._exec_spec).  The
        # residue-cap gate skips batches the host ALREADY knows are
        # residue-dominated (chain members, history events, serialized
        # post/voids) — a guaranteed-loss speculation; "force" takes
        # them anyway (differential routing).
        sm_mode = waves.spec_mode()
        speculate = sm_mode != "0" and not sharded
        if speculate and sm_mode != "force":
            speculate = (
                int(meta["chain_member"].sum())
                <= waves.spec_residue_cap() * n
            )
        # Both cheap pre-admission declines run before the per-column
        # bound accumulation pays for itself.
        if not speculate and self._chain_dominated(
            n, meta, force=(dm == "1")
        ):
            self._observe_plan_time(t0)
            self._dev_wave_decline("plan")
            return None, d
        adm = self._wave_admission(
            n, meta, d["flags"], j["p_found"], j["gather_p"],
            d["is_pv"], d["amount_lo"], d["amount_hi"],
            extra_bound=dev.inflight_bound(),
        )
        if adm is None:
            self._observe_plan_time(t0)
            self._dev_wave_decline("plan")
            return None, d
        inb_pairs, batch_bound = adm
        plan = None
        if not speculate:
            plan = self._grade_plan(
                n, meta, inb_pairs, batch_bound, force=(dm == "1")
            )
        self._observe_plan_time(t0)
        if not speculate:
            if plan is None:
                self._dev_wave_decline("plan")
                return None, d
            if sharded and not waves.plan_shardable(plan):
                # The plan needs a scan segment (history accounts,
                # serial conflict regions) — no SPMD executor covers
                # those, so the sharded engine declines to the drained
                # host path.
                self._dev_wave_decline("shard_plan")
                return None, d

        ev = self._build_scan_events(
            n, B, events, d["flags"], d["static"], d["amount_lo"],
            d["amount_hi"], d["pend_lo"], d["pend_hi"], d["timeout"],
            d["ledger"], d["code"], d["dr_slot"], d["cr_slot"],
            d["dr_flags"], d["cr_flags"], d["dr_zero"], d["cr_zero"],
            e_found, j,
        )
        if d["timeout"].any():
            self._inflight_timeouts = True
        flags, timeout = d["flags"], d["timeout"]
        uniq_rows, dstat_init = j["uniq_rows"], j["dstat_init"]

        def finish(packed_np) -> bytes:
            out = kernel.unpack_outputs(packed_np)
            return self._finish_exact_outputs(
                out, n, ts_base, id_lo, id_hi, flags, timeout,
                uniq_rows, dstat_init, True,
            )

        self.stat_dev_wave_batches += 1
        self.stat_dev_wave_events += n
        if speculate:
            # The in-flight charge is the WHOLE-batch superset — the
            # same bound the wave path charges — never the committed
            # subset: a mid-flight demotion replays the entire batch
            # through the host fallback, and a smaller charge could
            # let a sibling admission over-apply (tests/test_chaos.py
            # pins this window).
            return dev.submit_speculative(
                ev, dstat_init, n, ts_base, meta["chain_member"],
                pv_serial, finish,
                self._device_fallback(timestamp, input_bytes),
                id_keys=np.sort(probe), bound=batch_bound,
            ), None
        self.stat_dev_wave_steps += plan.n_steps
        return dev.submit_waves(
            ev, dstat_init, n, ts_base, plan, _pad(plan.wave_mask, B),
            finish, self._device_fallback(timestamp, input_bytes),
            id_keys=np.sort(probe), bound=plan.batch_bound,
        ), None

    def _tier_translate(self, *slot_arrays):
        """Batch planner front-door for the hot/cold tiering: compute
        the batch's LOGICAL touched-account set up front, prefetch it
        into the device hot window (DeviceEngine.tier_prefetch — rides
        the write-behind lane for eviction), and return each input
        array translated to HOT slots (negative entries pass through).
        Returns None when the batch cannot run on device — the caller
        takes the exact host path.  All-resident: identity."""
        hot = getattr(self._dev, "hot", None)
        if hot is None:
            return slot_arrays
        touched = np.concatenate(
            [np.asarray(a, np.int64).ravel() for a in slot_arrays]
        )
        if not self._dev.tier_prefetch(touched):
            self.metrics.counter("dev_tier.punt").inc()
            return None
        return tuple(
            hot.translate(np.asarray(a, np.int64)) for a in slot_arrays
        )

    def _submit_device_orderfree(
        self, events, n, ts_base, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
        flags, timeout, dr_slot, cr_slot, keys_sorted, timestamp, input_bytes,
        part,
    ):
        from tigerbeetle_tpu.state_machine import device_kernels as dk

        # Tiered prefetch + translation happens BEFORE packing; the
        # finish/bookkeeping closures keep the LOGICAL slots (the
        # mirror and attrs are logical-indexed).
        tr = self._tier_translate(dr_slot, cr_slot)
        if tr is None:
            return None
        t_dr_slot, t_cr_slot = tr
        part.switch(self._st_plan_pack)
        amount_lo = np.asarray(events["amount_lo"])
        amount_hi = np.asarray(events["amount_hi"])
        has_timeout = bool(timeout.any())
        has_hi = bool(amount_hi.any())
        # Tight 20-byte/event input when the batch's exact facts allow
        # (h2d bytes are the device engine's ceiling on this link).
        tight = (
            not has_timeout
            and not has_hi
            and (n == 0 or int(amount_lo.max()) < _TIGHT_AMOUNT_LIMIT)
        )
        if tight:
            pk = dk.pack_tight(
                n, id_lo=id_lo, id_hi=id_hi, dr_lo=dr_lo, dr_hi=dr_hi,
                cr_lo=cr_lo, cr_hi=cr_hi,
                pend_lo=np.asarray(events["pending_id_lo"]),
                pend_hi=np.asarray(events["pending_id_hi"]),
                amount_lo=amount_lo, flags=flags,
                ledger=np.asarray(events["ledger"]),
                code=events["code"].astype(np.uint32),
                ts_nonzero=np.asarray(events["timestamp"] != 0),
                dr_slot=t_dr_slot, cr_slot=t_cr_slot,
            )
        else:
            pk = self._device_pack_base(
                n, events, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
                flags, timeout, t_dr_slot, t_cr_slot,
            )
        if has_timeout:
            self._inflight_timeouts = True
        created = {
            "flags": flags,
            "dr_slot": dr_slot.astype(np.int32),
            "cr_slot": cr_slot.astype(np.int32),
            "amount_lo": amount_lo, "amount_hi": amount_hi,
            "pending_lo": np.asarray(events["pending_id_lo"]),
            "pending_hi": np.asarray(events["pending_id_hi"]),
            "ud128_lo": np.asarray(events["user_data_128_lo"]),
            "ud128_hi": np.asarray(events["user_data_128_hi"]),
            "ud64": np.asarray(events["user_data_64"]),
            "ud32": np.asarray(events["user_data_32"]),
            "timeout": timeout,
            "ledger": np.asarray(events["ledger"]),
            "code": events["code"].astype(np.uint32),
        }

        def finish(summary) -> bytes:
            with self.tracer.stage(self._st_finish_codes) as run:
                results = np.zeros(n, np.uint32)
                results[summary["fail_idx"]] = summary["fail_codes"]
                apply_mask = results == 0
                is_pending = (flags & np.uint32(TF.pending)) != 0
                # Mirror bookkeeping doubles as a free admission parity
                # check: the device admitted, so this can never refuse.
                run.switch(self._st_finish_mirror)
                deltas = self._mirror.try_apply_adds(
                    dr_slot, cr_slot, amount_lo, amount_hi, is_pending,
                    apply_mask, part=run,
                )
                assert deltas is not None, (
                    "device/mirror admission divergence"
                )
                return self._finish_fast(
                    n, ts_base, id_lo, id_hi, flags, timeout, results,
                    created, last_applied=summary["last_applied"], part=run,
                )

        if tight:
            kind = "orderfree_tight"
        else:
            kind = "orderfree" if has_hi else "orderfree_lo"
        return functools.partial(
            self._dev.submit, kind, pk, n, ts_base, finish,
            self._device_fallback(timestamp, input_bytes),
            id_keys=keys_sorted,
            bound=_amount_bound_total(amount_lo, amount_hi),
        )

    def _submit_device_linked(
        self, events, n, ts_base, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
        flags, timeout, dr_slot, cr_slot, keys_sorted, timestamp, input_bytes,
        part,
    ):
        tr = self._tier_translate(dr_slot, cr_slot)
        if tr is None:
            return None
        t_dr_slot, t_cr_slot = tr
        part.switch(self._st_plan_pack)
        pk = self._device_pack_base(
            n, events, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
            flags, timeout, t_dr_slot, t_cr_slot,
        )
        amount_lo = np.asarray(events["amount_lo"])
        amount_hi = np.asarray(events["amount_hi"])
        created = {
            "flags": flags,
            "dr_slot": dr_slot.astype(np.int32),
            "cr_slot": cr_slot.astype(np.int32),
            "amount_lo": amount_lo, "amount_hi": amount_hi,
            "pending_lo": np.zeros(n, np.uint64),
            "pending_hi": np.zeros(n, np.uint64),
            "ud128_lo": np.asarray(events["user_data_128_lo"]),
            "ud128_hi": np.asarray(events["user_data_128_hi"]),
            "ud64": np.asarray(events["user_data_64"]),
            "ud32": np.asarray(events["user_data_32"]),
            "timeout": timeout,
            "ledger": np.asarray(events["ledger"]),
            "code": events["code"].astype(np.uint32),
        }

        def finish(summary) -> bytes:
            with self.tracer.stage(self._st_finish_codes) as run:
                results = np.zeros(n, np.uint32)
                results[summary["fail_idx"]] = summary["fail_codes"]
                self.stat_linked_batches += 1
                self.stat_resolve_iters += summary["iters"]
                run.switch(self._st_finish_mirror)
                deltas = self._mirror.try_apply_adds(
                    dr_slot, cr_slot, amount_lo, amount_hi,
                    np.zeros(n, bool), results == 0, part=run,
                )
                assert deltas is not None, (
                    "device/mirror admission divergence"
                )
                return self._finish_fast(
                    n, ts_base, id_lo, id_hi, flags, timeout, results,
                    created, last_applied=summary["last_applied"], part=run,
                )

        # Small-amount specialization: a batch whose total contribution
        # fits i32 runs the one-cumsum-per-prefix fixpoint (the device
        # re-verifies the bound; a wrong pick just falls back exactly).
        kind = (
            "linked_small"
            if int(amount_lo.sum(dtype=np.uint64)) < (1 << 31)
            else "linked"
        )
        return functools.partial(
            self._dev.submit, kind, pk, n, ts_base, finish,
            self._device_fallback(timestamp, input_bytes),
            id_keys=keys_sorted,
            bound=_amount_bound_total(amount_lo, amount_hi),
        )

    def _submit_device_two_phase(
        self, events, n, ts_base, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
        flags, timeout, dr_slot, cr_slot, keys_sorted, timestamp, input_bytes,
        part,
    ):
        """Build two-phase join columns and dispatch; None -> host path
        (same residual class the r3 host router punted to the serial
        exact engine)."""
        from tigerbeetle_tpu.state_machine import device_kernels as dk

        part.switch(self._st_plan_pending)
        pend_lo = np.asarray(events["pending_id_lo"])
        pend_hi = np.asarray(events["pending_id_hi"])
        is_pv = (flags & np.uint32(TF.post_pending_transfer | TF.void_pending_transfer)) != 0

        # In-batch pending references (ids unique -> creator is the
        # unique event with that id).
        id_key = pack_u128(id_lo, id_hi)
        order = np.argsort(id_key, kind="stable")
        sorted_keys = id_key[order]
        pend_key = pack_u128(pend_lo, pend_hi)
        pos = np.searchsorted(sorted_keys, pend_key)
        pos_c = np.minimum(pos, n - 1)
        tgt_ev = np.where(
            is_pv & (sorted_keys[pos_c] == pend_key), order[pos_c], -1
        ).astype(np.int64)
        idx = np.arange(n)
        ib = is_pv & (tgt_ev >= 0) & (tgt_ev < idx)
        # Keep r3 routing parity: an in-batch reference to a
        # non-pending create goes to the serial exact engine.
        if (
            ib
            & ((flags[np.clip(tgt_ev, 0, None)] & np.uint32(TF.pending)) == 0)
        ).any():
            return None

        # Durable pending-target join.
        if is_pv.any():
            p_found, p_row = self._tdir.lookup(pend_lo, pend_hi)
            p_found = p_found & is_pv & ~ib
        else:
            p_found = np.zeros(n, bool)
            p_row = np.zeros(n, np.uint64)
        p_rows_valid = p_row[p_found].astype(np.int64)
        if len(p_rows_valid):
            uniq_rows, first_idx, tgt_inverse = np.unique(
                p_rows_valid, return_index=True, return_inverse=True
            )
            join = self._store.gather_many(
                [
                    "flags", "dr_slot", "cr_slot", "amount_lo", "amount_hi",
                    "ledger", "code", "ud128_lo", "ud128_hi", "ud64", "ud32",
                    "timeout", "status",
                ],
                uniq_rows,
            )
            if (join["timeout"] != 0).any():
                return None
            pj_dr_u = np.clip(join["dr_slot"].astype(np.int64), 0, None)
            pj_cr_u = np.clip(join["cr_slot"].astype(np.int64), 0, None)
            LIMH = np.uint32(
                AF.debits_must_not_exceed_credits
                | AF.credits_must_not_exceed_debits
                | AF.history
            )
            pj_acct_flags = (
                self._attrs["flags"][pj_dr_u] | self._attrs["flags"][pj_cr_u]
            ).astype(np.uint32)
            if (pj_acct_flags & LIMH).any():
                return None
            p_tgt = np.full(n, -1, np.int64)
            p_tgt[p_found] = tgt_inverse
            uniq_status = join["status"].astype(np.uint32)

            def jcol(name, dtype):
                out = np.zeros(n, dtype)
                out[p_found] = join[name][tgt_inverse].astype(dtype)
                return out

        else:
            uniq_rows = np.zeros(0, np.int64)
            uniq_status = np.zeros(0, np.uint32)
            p_tgt = np.full(n, -1, np.int64)

            def jcol(name, dtype):
                return np.zeros(n, dtype)

        pj_dr_slot = jcol("dr_slot", np.int64)
        pj_cr_slot = jcol("cr_slot", np.int64)
        # Tiered prefetch over the batch's WHOLE touched set up front
        # (event accounts + durable pending-target accounts — in-batch
        # targets resolve to event slots already covered).  Only the
        # packed device columns translate; ctx/finish keep LOGICAL
        # slots.  Non-found pj entries keep their 0 default — the
        # kernel reads them only under the p_found bit.
        part.switch(self._st_plan_route)
        tr = self._tier_translate(
            dr_slot, cr_slot,
            np.where(p_found, pj_dr_slot, -1),
            np.where(p_found, pj_cr_slot, -1),
        )
        if tr is None:
            return None
        t_dr_slot, t_cr_slot, t_pj_dr, t_pj_cr = tr
        part.switch(self._st_plan_pack)
        t_pj_dr = np.where(p_found, t_pj_dr, 0)
        t_pj_cr = np.where(p_found, t_pj_cr, 0)
        pk = self._device_pack_base(
            n, events, id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi,
            flags, timeout, t_dr_slot, t_cr_slot,
            p_found=p_found, p_tgt=p_tgt, n_cols=dk.N_COLS_TP,
        )
        # Target account-id equality predicates (host marshaling: u128
        # byte compares against in-batch events or durable attrs).
        tgt_c = np.clip(tgt_ev, 0, None)
        p_drs = np.where(ib, dr_slot[tgt_c], pj_dr_slot)
        p_crs = np.where(ib, cr_slot[tgt_c], pj_cr_slot)
        pd = np.clip(p_drs, 0, None)
        pc = np.clip(p_crs, 0, None)
        p_dr_id_lo = self._attrs["id_lo"][pd]
        p_dr_id_hi = self._attrs["id_hi"][pd]
        p_cr_id_lo = self._attrs["id_lo"][pc]
        p_cr_id_hi = self._attrs["id_hi"][pc]
        t_dr_set = (dr_lo != 0) | (dr_hi != 0)
        t_cr_set = (cr_lo != 0) | (cr_hi != 0)
        dr_eq = (dr_lo == p_dr_id_lo) & (dr_hi == p_dr_id_hi)
        cr_eq = (cr_lo == p_cr_id_lo) & (cr_hi == p_cr_id_hi)
        bits_extra = (
            np.where(t_dr_set, np.uint64(dk.BIT_T_DR_SET), np.uint64(0))
            | np.where(t_cr_set, np.uint64(dk.BIT_T_CR_SET), np.uint64(0))
            | np.where(dr_eq, np.uint64(dk.BIT_DR_EQ_P), np.uint64(0))
            | np.where(cr_eq, np.uint64(dk.BIT_CR_EQ_P), np.uint64(0))
        )
        p_amt_lo_d = jcol("amount_lo", np.uint64)
        p_amt_hi_d = jcol("amount_hi", np.uint64)
        dstat_ev = np.zeros(n, np.uint32)
        if len(uniq_rows):
            dstat_ev[p_found] = uniq_status[p_tgt[p_found]]
        pk = dk.pack_two_phase_ext(
            pk, n, bits_extra_mask=bits_extra,
            p_flags=jcol("flags", np.uint32).astype(np.uint16),
            p_code=jcol("code", np.uint32).astype(np.uint16),
            p_ledger=jcol("ledger", np.uint32),
            p_dr_slot=t_pj_dr, p_cr_slot=t_pj_cr,
            p_amt_lo=p_amt_lo_d, p_amt_hi=p_amt_hi_d,
            tgt_ev=tgt_ev, dstat_init_ev=dstat_ev,
        )
        amount_lo = np.asarray(events["amount_lo"])
        amount_hi = np.asarray(events["amount_hi"])
        p_amt_lo = np.where(ib, amount_lo[tgt_c], p_amt_lo_d)
        p_amt_hi = np.where(ib, amount_hi[tgt_c], p_amt_hi_d)
        ud128_lo = np.asarray(events["user_data_128_lo"])
        ud128_hi = np.asarray(events["user_data_128_hi"])
        ud64 = np.asarray(events["user_data_64"])
        ud32 = np.asarray(events["user_data_32"]).astype(np.uint32)
        ledger_arr = np.asarray(events["ledger"])
        code_arr = events["code"].astype(np.uint32)
        pend_flag = (flags & np.uint32(TF.pending)) != 0
        post = (flags & np.uint32(TF.post_pending_transfer)) != 0

        ctx = dict(
            n=n, ts_base=ts_base, is_pv=is_pv, ib=ib, tgt_ev=tgt_ev,
            p_drs=p_drs, p_crs=p_crs, p_amt_lo=p_amt_lo, p_amt_hi=p_amt_hi,
            p_ud128_lo=np.where(ib, ud128_lo[tgt_c], jcol("ud128_lo", np.uint64)),
            p_ud128_hi=np.where(ib, ud128_hi[tgt_c], jcol("ud128_hi", np.uint64)),
            p_ud64=np.where(ib, ud64[tgt_c], jcol("ud64", np.uint64)),
            p_ud32=np.where(ib, ud32[tgt_c], jcol("ud32", np.uint32)),
            p_ledger=np.where(
                ib, ledger_arr[tgt_c].astype(np.uint32), jcol("ledger", np.uint32)
            ),
            p_code=np.where(ib, code_arr[tgt_c], jcol("code", np.uint32)),
            uniq_rows=uniq_rows, uniq_status=uniq_status, p_tgt=p_tgt,
            pend_flag=pend_flag, post=post,
        )

        def finish(summary) -> bytes:
            with self.tracer.stage(self._st_finish_codes) as run:
                return self._finish_device_two_phase(
                    summary, events, id_lo, id_hi, flags, timeout,
                    amount_lo, amount_hi, pend_lo, pend_hi,
                    ud128_lo, ud128_hi, ud64, ud32, ledger_arr, code_arr,
                    dr_slot, cr_slot, ctx, run,
                )

        self.stat_two_phase_batches += 1
        kind = (
            "two_phase_lo"
            if not (amount_hi.any() or p_amt_hi.any())
            else "two_phase"
        )
        # In-flight bound: creates add their amount through two slots
        # (counted once per slot by the wave admission), finalizers at
        # most max(t.amount, pending.amount) — 2x amounts + the joined
        # pending amounts over-covers both.
        bound = 2 * _amount_bound_total(
            amount_lo, amount_hi
        ) + _amount_bound_total(p_amt_lo, p_amt_hi)
        return functools.partial(
            self._dev.submit, kind, pk, n, ts_base, finish,
            self._device_fallback(timestamp, input_bytes),
            id_keys=keys_sorted,
            bound=bound,
        )

    def _finish_device_two_phase(
        self, summary, events, id_lo, id_hi, flags, timeout,
        amount_lo, amount_hi, pend_lo, pend_hi,
        ud128_lo, ud128_hi, ud64, ud32, ledger_arr, code_arr,
        dr_slot, cr_slot, ctx, part,
    ) -> bytes:
        """Bookkeeping from device codes (mirrors the tail of
        _try_two_phase_fast, with verdicts arriving from the kernel).
        `part`: the closure's open run (sm.finish.codes as it comes)."""
        n = ctx["n"]
        ts_base = ctx["ts_base"]
        is_pv = ctx["is_pv"]
        results = np.zeros(n, np.uint32)
        results[summary["fail_idx"]] = summary["fail_codes"]
        ok = results == 0
        winner = ok & is_pv
        post = ctx["post"]
        pend_flag = ctx["pend_flag"]
        p_drs, p_crs = ctx["p_drs"], ctx["p_crs"]
        p_amt_lo, p_amt_hi = ctx["p_amt_lo"], ctx["p_amt_hi"]
        t_amt_set = (amount_lo != 0) | (amount_hi != 0)
        res_amt_lo = np.where(is_pv & ~t_amt_set, p_amt_lo, amount_lo)
        res_amt_hi = np.where(is_pv & ~t_amt_set, p_amt_hi, amount_hi)

        # Mirror bookkeeping (device already applied; these asserts are
        # the admission-parity tripwire).
        part.switch(self._st_finish_mirror)
        pend_ok = ok & pend_flag
        plain_ok = ok & ~pend_flag & ~is_pv
        post_win = winner & post
        add_slots = np.concatenate([
            dr_slot[pend_ok], cr_slot[pend_ok],
            dr_slot[plain_ok], cr_slot[plain_ok],
            p_drs[post_win], p_crs[post_win],
        ])
        n_pend = int(pend_ok.sum())
        n_plain = int(plain_ok.sum())
        n_post = int(post_win.sum())
        add_cols = np.concatenate([
            np.zeros(n_pend, np.int64), np.full(n_pend, 2, np.int64),
            np.ones(n_plain, np.int64), np.full(n_plain, 3, np.int64),
            np.ones(n_post, np.int64), np.full(n_post, 3, np.int64),
        ])
        add_lo = np.concatenate([
            amount_lo[pend_ok], amount_lo[pend_ok],
            amount_lo[plain_ok], amount_lo[plain_ok],
            res_amt_lo[post_win], res_amt_lo[post_win],
        ])
        add_hi = np.concatenate([
            amount_hi[pend_ok], amount_hi[pend_ok],
            amount_hi[plain_ok], amount_hi[plain_ok],
            res_amt_hi[post_win], res_amt_hi[post_win],
        ])
        deltas = self._mirror.try_apply_deltas(
            add_slots, add_cols, add_lo, add_hi, part=part
        )
        assert deltas is not None, "device/mirror admission divergence"
        n_win = int(winner.sum())
        if n_win:
            sub_slots = np.concatenate([p_drs[winner], p_crs[winner]])
            sub_cols = np.concatenate(
                [np.zeros(n_win, np.int64), np.full(n_win, 2, np.int64)]
            )
            self._mirror.apply_subs(
                sub_slots, sub_cols,
                np.concatenate([p_amt_lo[winner]] * 2),
                np.concatenate([p_amt_hi[winner]] * 2), part=part,
            )

        part.switch(self._st_finish_store)
        ud128_set = (ud128_lo != 0) | (ud128_hi != 0)
        created = {
            "flags": flags,
            "dr_slot": np.where(is_pv, p_drs, dr_slot).astype(np.int32),
            "cr_slot": np.where(is_pv, p_crs, cr_slot).astype(np.int32),
            "amount_lo": np.where(is_pv, res_amt_lo, amount_lo),
            "amount_hi": np.where(is_pv, res_amt_hi, amount_hi),
            "pending_lo": pend_lo, "pending_hi": pend_hi,
            "ud128_lo": np.where(is_pv & ~ud128_set, ctx["p_ud128_lo"], ud128_lo),
            "ud128_hi": np.where(is_pv & ~ud128_set, ctx["p_ud128_hi"], ud128_hi),
            "ud64": np.where(is_pv & (ud64 == 0), ctx["p_ud64"], ud64),
            "ud32": np.where(is_pv & (ud32 == 0), ctx["p_ud32"], ud32),
            "timeout": np.zeros(n, np.uint64),
            "ledger": np.where(is_pv, ctx["p_ledger"], ledger_arr).astype(np.uint32),
            "code": np.where(is_pv, ctx["p_code"], code_arr).astype(np.uint32),
        }
        inb_status = np.where(
            pend_ok, np.uint32(kernel.S_PENDING), np.uint32(0)
        )
        ib_win = winner & ctx["ib"]
        if ib_win.any():
            inb_status[ctx["tgt_ev"][ib_win]] = np.where(
                post[ib_win],
                np.uint32(kernel.S_POSTED),
                np.uint32(kernel.S_VOIDED),
            )
        uniq_rows = ctx["uniq_rows"]
        uniq_status = ctx["uniq_status"]
        dstat_init = uniq_status.copy()
        dstat = uniq_status.copy()
        dur_win = winner & ~ctx["ib"]
        if dur_win.any():
            dstat[ctx["p_tgt"][dur_win]] = np.where(
                post[dur_win],
                np.uint32(kernel.S_POSTED),
                np.uint32(kernel.S_VOIDED),
            )
        zeros_u64 = np.zeros(n, np.uint64)
        self._post_process_transfers(
            n, ts_base, id_lo, id_hi, flags, timeout,
            results, ok, created, inb_status,
            dstat_init, dstat, uniq_rows,
            np.zeros((n, 8), np.uint64), np.zeros((n, 8), np.uint64),
            summary["last_applied"], zeros_u64, zeros_u64,
            no_history=True, part=part,
        )
        part.switch(self._st_finish_reply)
        fail_idx = np.flatnonzero(results != 0)
        reply = np.zeros(len(fail_idx), dtype=CREATE_RESULT_DTYPE)
        reply["index"] = fail_idx.astype(np.uint32)
        reply["result"] = results[fail_idx]
        return reply.tobytes()

    def _lookup_accounts_device(self, input_bytes: bytes):
        """lookup_accounts with balances gathered from the DEVICE table
        (rides the dispatch stream, so in-flight batches are visible
        without draining) — VERDICT r3 #1d."""
        ids = np.frombuffer(input_bytes, dtype=types.U128_PAIR_DTYPE)
        found, slots = self._acct_dir.lookup(
            ids["lo"].astype(np.uint64), ids["hi"].astype(np.uint64)
        )
        hit = np.flatnonzero(found)
        if len(hit) == 0:
            from tigerbeetle_tpu.state_machine.device_engine import (
                ReplyFuture,
            )

            return ReplyFuture(value=b"")
        slots_hit = slots[hit].astype(np.int64)

        def finish(rows) -> bytes:
            balances = rows[: len(slots_hit)]
            out = np.zeros(len(hit), dtype=ACCOUNT_DTYPE)
            a = self._attrs
            out["id_lo"], out["id_hi"] = a["id_lo"][slots_hit], a["id_hi"][slots_hit]
            out["debits_pending_lo"], out["debits_pending_hi"] = balances[:, 0], balances[:, 1]
            out["debits_posted_lo"], out["debits_posted_hi"] = balances[:, 2], balances[:, 3]
            out["credits_pending_lo"], out["credits_pending_hi"] = balances[:, 4], balances[:, 5]
            out["credits_posted_lo"], out["credits_posted_hi"] = balances[:, 6], balances[:, 7]
            out["user_data_128_lo"] = a["ud128_lo"][slots_hit]
            out["user_data_128_hi"] = a["ud128_hi"][slots_hit]
            out["user_data_64"] = a["ud64"][slots_hit]
            out["user_data_32"] = a["ud32"][slots_hit]
            out["ledger"] = a["ledger"][slots_hit]
            out["code"] = a["code"][slots_hit]
            out["flags"] = a["flags"][slots_hit]
            out["timestamp"] = a["timestamp"][slots_hit]
            return out.tobytes()

        return self._dev.lookup(slots_hit, finish)

    def _commit_create_transfers(
        self, timestamp: int, input_bytes: bytes, decoded: dict | None = None
    ) -> bytes:
        """`decoded`: an already-computed _decode_static dict (the
        wave-dispatch decline path hands its work over; safe to reuse
        across the drain — decode + ladder depend only on the wire
        bytes and the synchronously-maintained account attrs)."""
        events = np.frombuffer(input_bytes, dtype=TRANSFER_DTYPE)
        n = len(events)
        if n == 0:
            return b""
        self.stat_host_semantic_events += n
        ts_base = timestamp - n + 1

        # Native C++ fast path: one call covers decode, static ladder,
        # account resolution, duplicate checks, and overflow admission
        # (native/tb_fastpath.cpp); Python only does the bookkeeping.
        # A None return means fallback — nothing was mutated.
        # TB_WAVES=1/exact/scan bypasses every native/host fast path so
        # the JAX exact path (wave executor or B-step scan) sees the
        # full stream (differential-test routing).
        if self._native is not None and waves.mode() not in (
            "1", "exact", "scan"
        ):
            native_out = self._native.commit_transfers(input_bytes, n, ts_base)
            if native_out is not None:
                self.stat_device_events += n
                return self._finish_native_fast(
                    events, n, ts_base, *native_out
                )
            # Order-dependent native resolvers (tb_linked.inc /
            # tb_two_phase.inc): serial C++ over the wire bytes with
            # exact ladders, feeding the same device scatter-add queue.
            nl = self._native.commit_linked(input_bytes, n, ts_base)
            if nl is not None:
                results, dr_slot, cr_slot, deltas, last_applied = nl
                self.stat_device_events += n
                self.stat_linked_batches += 1
                return self._finish_native_fast(
                    events, n, ts_base, results, dr_slot, cr_slot, deltas,
                    last_applied=last_applied,
                )
            reply = self._try_native_two_phase(input_bytes, events, n, ts_base)
            if reply is not None:
                self.stat_device_events += n
                self.stat_two_phase_batches += 1
                return reply

        d = decoded if decoded is not None else self._decode_static(events, n)
        return self._commit_transfers_resolved(
            n, ts_base, events, d["id_lo"], d["id_hi"], d["pend_lo"],
            d["pend_hi"], d["flags"], d["timeout"], d["dr_slot"],
            d["cr_slot"], d["amount_lo"], d["amount_hi"], d["ledger"],
            d["code"], d["static"], d["is_pv"], d["dr_flags"],
            d["cr_flags"], d["dr_zero"], d["cr_zero"],
        )

    def _decode_static(self, events: np.ndarray, n: int) -> dict:
        """Column decode + account resolution + the static precedence
        ladder — everything about a create_transfers batch that is
        independent of balances and durable joins.  Shared by the host
        exact path and the device engine's wave submission
        (_try_submit_device_waves), which must agree byte-for-byte."""
        # Same-width fields stay strided views into the 1 MiB wire
        # buffer (it lives in L2 after the first pass, so elementwise
        # ops on views beat paying a contiguous copy per column);
        # narrower wire fields still widen via astype.
        id_lo = np.asarray(events["id_lo"])
        id_hi = np.asarray(events["id_hi"])
        dr_lo = np.asarray(events["debit_account_id_lo"])
        dr_hi = np.asarray(events["debit_account_id_hi"])
        cr_lo = np.asarray(events["credit_account_id_lo"])
        cr_hi = np.asarray(events["credit_account_id_hi"])
        pend_lo = np.asarray(events["pending_id_lo"])
        pend_hi = np.asarray(events["pending_id_hi"])
        amount_lo = np.asarray(events["amount_lo"])
        amount_hi = np.asarray(events["amount_hi"])
        flags = events["flags"].astype(np.uint32)
        timeout = events["timeout"].astype(np.uint64)
        ledger = np.asarray(events["ledger"])
        code = events["code"].astype(np.uint32)

        is_pv = (flags & (kernel.F_POST | kernel.F_VOID)) != 0

        # Account resolution (immutable within this batch).
        dr_found, dr_slot_u = self._acct_dir.lookup(dr_lo, dr_hi)
        cr_found, cr_slot_u = self._acct_dir.lookup(cr_lo, cr_hi)
        dr_slot = np.where(dr_found, dr_slot_u.astype(np.int64), -1).astype(np.int32)
        cr_slot = np.where(cr_found, cr_slot_u.astype(np.int64), -1).astype(np.int32)
        dr_c = np.clip(dr_slot, 0, None)
        cr_c = np.clip(cr_slot, 0, None)
        attrs = self._attrs
        dr_flags = np.where(dr_found, attrs["flags"][dr_c], 0).astype(np.uint32)
        cr_flags = np.where(cr_found, attrs["flags"][cr_c], 0).astype(np.uint32)
        dr_ledger = np.where(
            dr_found, attrs["ledger"][dr_c], 0
        ).astype(np.uint32)
        cr_ledger = np.where(
            cr_found, attrs["ledger"][cr_c], 0
        ).astype(np.uint32)

        # Elementary predicates, shared by the all-valid short circuit
        # and the precedence ladder.
        id_zero = (id_lo == 0) & (id_hi == 0)
        id_max = (id_lo == np.uint64(U64_MAX)) & (id_hi == np.uint64(U64_MAX))
        reserved = (flags & ~np.uint32(0x3F)) != 0
        dr_zero = (dr_lo == 0) & (dr_hi == 0)
        dr_max = (dr_lo == np.uint64(U64_MAX)) & (dr_hi == np.uint64(U64_MAX))
        cr_zero = (cr_lo == 0) & (cr_hi == 0)
        cr_max = (cr_lo == np.uint64(U64_MAX)) & (cr_hi == np.uint64(U64_MAX))
        same_acct = (dr_lo == cr_lo) & (dr_hi == cr_hi)
        pend_zero = (pend_lo == 0) & (pend_hi == 0)
        not_pending_flag = (flags & kernel.F_PENDING) == 0
        not_balancing = (flags & (kernel.F_BAL_DR | kernel.F_BAL_CR)) == 0
        amount_zero = (amount_lo == 0) & (amount_hi == 0)

        def pack(static):
            return dict(
                id_lo=id_lo, id_hi=id_hi, dr_lo=dr_lo, dr_hi=dr_hi,
                cr_lo=cr_lo, cr_hi=cr_hi, pend_lo=pend_lo,
                pend_hi=pend_hi, amount_lo=amount_lo,
                amount_hi=amount_hi, flags=flags, timeout=timeout,
                ledger=ledger, code=code, is_pv=is_pv, dr_slot=dr_slot,
                cr_slot=cr_slot, dr_flags=dr_flags, cr_flags=cr_flags,
                dr_zero=dr_zero, cr_zero=cr_zero, static=static,
            )

        # Short circuit: the hot path (well-formed plain transfers) hits
        # ZERO ladder codes — one OR-reduction detects that and skips
        # the ~25 masked-copyto cascade entirely.
        if not is_pv.any():
            any_invalid = (
                reserved | id_zero | id_max | dr_zero | dr_max | cr_zero
                | cr_max | same_acct | ~pend_zero | ~dr_found | ~cr_found
                | (not_pending_flag & (timeout != 0))
                | (not_balancing & amount_zero)
                | (ledger == 0) | (code == 0)
                | (dr_ledger != cr_ledger) | (ledger != dr_ledger)
            ).any()
            if not any_invalid:
                return pack(_first_code(n))

        # Static precedence ladder (reference: src/state_machine.zig:
        # 1465-1504 normal, :1614-1624 post/void prefix).
        static = _first_code(n)
        _apply_code(static, reserved, CTR.reserved_flag)
        _apply_code(static, id_zero, CTR.id_must_not_be_zero)
        _apply_code(static, id_max, CTR.id_must_not_be_int_max)

        # Post/void static prefix.
        post = (flags & kernel.F_POST) != 0
        void = (flags & kernel.F_VOID) != 0
        pv_excl = (
            (post & void)
            | (is_pv & ((flags & kernel.F_PENDING) != 0))
            | (is_pv & ((flags & kernel.F_BAL_DR) != 0))
            | (is_pv & ((flags & kernel.F_BAL_CR) != 0))
        )
        pend_max = (pend_lo == np.uint64(U64_MAX)) & (pend_hi == np.uint64(U64_MAX))
        pend_self = (pend_lo == id_lo) & (pend_hi == id_hi)
        _apply_code(static, is_pv & pv_excl, CTR.flags_are_mutually_exclusive)
        _apply_code(static, is_pv & pend_zero, CTR.pending_id_must_not_be_zero)
        _apply_code(static, is_pv & pend_max, CTR.pending_id_must_not_be_int_max)
        _apply_code(static, is_pv & pend_self, CTR.pending_id_must_be_different)
        _apply_code(static, is_pv & (timeout != 0), CTR.timeout_reserved_for_pending_transfer)

        # Normal static ladder.
        nm = ~is_pv
        _apply_code(static, nm & dr_zero, CTR.debit_account_id_must_not_be_zero)
        _apply_code(static, nm & dr_max, CTR.debit_account_id_must_not_be_int_max)
        _apply_code(static, nm & cr_zero, CTR.credit_account_id_must_not_be_zero)
        _apply_code(static, nm & cr_max, CTR.credit_account_id_must_not_be_int_max)
        _apply_code(static, nm & same_acct, CTR.accounts_must_be_different)
        _apply_code(static, nm & ~pend_zero, CTR.pending_id_must_be_zero)
        _apply_code(
            static, nm & not_pending_flag & (timeout != 0),
            CTR.timeout_reserved_for_pending_transfer,
        )
        _apply_code(static, nm & not_balancing & amount_zero, CTR.amount_must_not_be_zero)
        _apply_code(static, nm & (ledger == 0), CTR.ledger_must_not_be_zero)
        _apply_code(static, nm & (code == 0), CTR.code_must_not_be_zero)
        _apply_code(static, nm & ~dr_found, CTR.debit_account_not_found)
        _apply_code(static, nm & ~cr_found, CTR.credit_account_not_found)
        _apply_code(
            static, nm & (dr_ledger != cr_ledger), CTR.accounts_must_have_the_same_ledger
        )
        _apply_code(
            static, nm & (ledger != dr_ledger),
            CTR.transfer_must_have_the_same_ledger_as_accounts,
        )

        return pack(static)

    def _commit_transfers_resolved(
        self, n, ts_base, events, id_lo, id_hi, pend_lo, pend_hi,
        flags, timeout, dr_slot, cr_slot, amount_lo, amount_hi,
        ledger, code, static, is_pv, dr_flags, cr_flags, dr_zero, cr_zero,
    ) -> bytes:
        """Fast-path routing + exact kernel dispatch, after account
        resolution and the static ladder."""
        wave_mode = waves.mode()
        # "1"/"exact"/"scan" all route the batch to the JAX exact
        # dispatch below (skipping the host fast paths); "1" further
        # forces the wave plan past its profitability gate.
        wave_force = wave_mode in ("1", "exact", "scan")
        # The JAX kernel needs shape buckets (compile cache); the native
        # exact engine takes any length — skip the ~50-array padding.
        if self._native is not None and not wave_force:
            B = n
        else:
            B = next(b for b in _BATCH_BUCKETS if b >= n)

        # Durable joins (vectorized hash-index probes).
        e_found, e_row = self._tdir.lookup(id_lo, id_hi)

        # Fast-path routing (see kernel_fast.py preconditions): no
        # order-dependent flags, no in-batch or durable id collisions,
        # no limit/history accounts anywhere in the batch.
        order_free = not (
            flags
            & np.uint32(
                TF.linked
                | TF.post_pending_transfer
                | TF.void_pending_transfer
                | TF.balancing_debit
                | TF.balancing_credit
            )
        ).any()
        # In-batch duplicate-id check: strictly-increasing ids (the
        # common encoder output) prove uniqueness without a sort; else
        # a 64-bit key mix + unique — a hash collision only costs a
        # detour through the exact scan path, which resolves true id
        # groups.  The lexicographic (hi, lo) ascending test is shared
        # with the exact-path grouping shortcut below.
        ascending = n == 1 or bool(
            (
                (id_hi[1:] > id_hi[:-1])
                | ((id_hi[1:] == id_hi[:-1]) & (id_lo[1:] > id_lo[:-1]))
            ).all()
        )
        # The resolver routes exclude only balancing batches (order_free
        # already implies no balancing flags).
        route_candidate = not (
            flags & np.uint32(TF.balancing_debit | TF.balancing_credit)
        ).any()
        if route_candidate:
            ids_unique = ascending
            if not ids_unique:
                id_mix = id_lo * np.uint64(0x9E3779B97F4A7C15) + id_hi * np.uint64(
                    0xC2B2AE3D27D4EB4F
                )
                ids_unique = len(np.unique(id_mix)) == n
        else:
            ids_unique = False
        if order_free and ids_unique and not e_found.any() and not wave_force:
            acct_flags = dr_flags | cr_flags
            if not (
                acct_flags
                & np.uint32(
                    AF.debits_must_not_exceed_credits
                    | AF.credits_must_not_exceed_debits
                    | AF.history
                )
            ).any():
                reply = self._commit_fast(
                    n, ts_base, events, id_lo, id_hi, pend_lo, pend_hi,
                    flags, timeout, dr_slot, cr_slot, amount_lo, amount_hi,
                    ledger, code, static,
                )
                if reply is not None:
                    self.stat_device_events += n
                    return reply

        # Linked-chain / limit-account resolution (resolve.py): plain
        # posted transfers — chains and balance-limit accounts allowed
        # (a chain-free batch is just all chains of length 1) — get
        # exact verdicts from a vectorized fixpoint, then scatter-add
        # apply.  Batches without limits or chains never reach here
        # (the order-free path above took them).
        if (
            ids_unique
            and not wave_force
            and not (
                flags
                & np.uint32(
                    TF.pending
                    | TF.post_pending_transfer
                    | TF.void_pending_transfer
                    | TF.balancing_debit
                    | TF.balancing_credit
                )
            ).any()
            and not e_found.any()
            and not ((dr_flags | cr_flags) & np.uint32(AF.history)).any()
        ):
            reply = self._commit_linked_fast(
                n, ts_base, events, id_lo, id_hi, flags, timeout,
                dr_slot, cr_slot, amount_lo, amount_hi, ledger, code,
                static, dr_flags, cr_flags,
            )
            if reply is not None:
                self.stat_device_events += n
                self.stat_linked_batches += 1
                return reply

        j = self._exact_joins(
            n, B, id_lo, id_hi, pend_lo, pend_hi, is_pv, ascending,
            e_found, e_row,
        )
        unique_ids = j["unique_ids"]
        id_group = j["id_group"]
        p_group = j["p_group"]
        p_found = j["p_found"]
        gather_e = j["gather_e"]
        gather_p = j["gather_p"]
        uniq_rows = j["uniq_rows"]
        uniq_status = j["uniq_status"]
        p_tgt = j["p_tgt"]
        dstat_init = j["dstat_init"]

        # Two-phase resolution (resolve.py): post/void batches whose
        # verdicts are balance-independent resolve in one vectorized
        # pass — pendings, first-wins finalization, scatter-add apply.
        if is_pv.any() and ids_unique and not e_found.any() and not wave_force:
            reply = self._try_two_phase_fast(
                n, ts_base, events, id_lo, id_hi, pend_lo, pend_hi, flags,
                timeout, dr_slot, cr_slot, amount_lo, amount_hi, ledger,
                code, static, is_pv, dr_flags, cr_flags,
                unique_ids, id_group, p_group, p_found, gather_p,
                uniq_rows, p_tgt, uniq_status,
            )
            if reply is not None:
                self.stat_device_events += n
                self.stat_two_phase_batches += 1
                return reply

        ev = self._build_scan_events(
            n, B, events, flags, static, amount_lo, amount_hi,
            pend_lo, pend_hi, timeout, ledger, code, dr_slot, cr_slot,
            dr_flags, cr_flags, dr_zero, cr_zero, e_found, j,
        )

        self.stat_exact_events += n
        if self._native is not None and not wave_force:
            # Serial exact engine in C++ (native/tb_exact.inc): same
            # inputs and packed-output contract as the scan kernel.
            # Sequential semantics are inherently serial (the reference
            # loop is single-core), so the host runs them at memory
            # speed; the shared mirror is mutated in place and the
            # deltas ride the async device queue.
            packed_np, deltas = self._native.commit_exact(
                ev, kernel.EVENT_FIELDS, dstat_init, B, n, ts_base
            )
            self._dev.enqueue(*[d.copy() for d in deltas])
            out = kernel.unpack_outputs(packed_np)
            mirror_from_hist = False  # C++ already updated the mirror
        else:
            # Conflict-aware wave execution (waves.py): when the batch
            # partitions into few mutually-independent waves, run one
            # vectorized device step per wave — chain waves for clean
            # linked runs, and the exact scan only over true conflict
            # groups — instead of the full B-step scan.  Bit-identical
            # outputs (tests/test_waves.py).  A degraded device engine
            # pins this JAX work at the CPU backend: the default
            # backend may be the lost device.
            wave_plan = None
            if wave_mode not in ("0", "scan"):
                wave_plan = self._plan_wave_execution(
                    n, flags, dr_slot, cr_slot, dr_flags, cr_flags,
                    id_group, p_group, p_tgt, p_found, gather_p, is_pv,
                    amount_lo, amount_hi, force=(wave_mode == "1"),
                )
            with self._host_jax_scope():
                if wave_plan is not None:
                    # Wave events' snapshots are rewritten to batch
                    # finals at finalize (history events never ride
                    # waves).
                    new_balances, packed = waves.run_create_transfers_waves(
                        self._balances, ev, dstat_init, n, ts_base,
                        wave_plan, _pad(wave_plan.wave_mask, B),
                    )
                    self.stat_wave_batches += 1
                    self.stat_wave_steps += wave_plan.n_steps
                    self.stat_wave_events += n
                    self.stat_wave_parallel_events += wave_plan.parallel_events
                else:
                    new_balances, packed = kernel.run_create_transfers(
                        self._balances,
                        {k: jnp.asarray(v) for k, v in ev.items()},
                        dstat_init, n, ts_base,
                    )
                self._balances = new_balances

                # ONE device->host transfer for every output: the
                # kernel packs them into a single u64 matrix because
                # the device link is high-latency and per-leaf fetches
                # each pay a full round trip.
                out = kernel.unpack_outputs(np.asarray(packed))
            mirror_from_hist = True

        return self._finish_exact_outputs(
            out, n, ts_base, id_lo, id_hi, flags, timeout,
            uniq_rows, dstat_init, mirror_from_hist,
        )

    def _host_jax_scope(self):
        """JAX placement scope for host exact-path execution: pins the
        work at the CPU backend while the device engine is degraded or
        recovering (ROADMAP "Pin degraded-mode host compute") — the
        process default backend may be the lost device, and
        jnp.asarray/jit dispatch would otherwise route there.  A no-op
        (null scope) in host-engine mode and on a healthy engine."""
        import contextlib

        dev = self._dev
        if self.engine == "device" and (
            getattr(dev, "state", None) is not types.EngineState.healthy
            or dev._recovering
        ):
            cpu = dev._cpu_device()
            if cpu is not None:
                return jax.default_device(cpu)
        return contextlib.nullcontext()

    def _finish_exact_outputs(
        self, out, n, ts_base, id_lo, id_hi, flags, timeout,
        uniq_rows, dstat_init, mirror_from_hist,
    ) -> bytes:
        """Exact-path bookkeeping tail from unpacked kernel outputs —
        shared by the synchronous host path and the device engine's
        wave-record finish (which runs it at materialization from the
        fetched packed matrix)."""
        results = out["results"][:n]
        created_mask = out["created_mask"][:n]
        created = {f: out["created"][f][:n] for f in kernel.CREATED_FIELDS}
        inb_status = out["inb_status"][:n]
        dstat = out["dstat"]
        hist_dr = out["hist_dr"][:n]
        hist_cr = out["hist_cr"][:n]

        # Mirror reconstruction: events whose effects persisted
        # (results == 0; rollback rewrote failed-chain members) carry
        # post-apply snapshots of both touched rows. Interleaved in
        # event order, last write wins -> final balances of every
        # touched slot (rolled-back-only slots net to no change).
        ok_idx = np.flatnonzero(results == 0)
        if mirror_from_hist and len(ok_idx):
            slots2 = np.empty(2 * len(ok_idx), np.int64)
            slots2[0::2] = created["dr_slot"][ok_idx]
            slots2[1::2] = created["cr_slot"][ok_idx]
            rows2 = np.empty((2 * len(ok_idx), 8), np.uint64)
            rows2[0::2] = hist_dr[ok_idx]
            rows2[1::2] = hist_cr[ok_idx]
            self._mirror.set_rows8(slots2, rows2)

        self._post_process_transfers(
            n, ts_base, id_lo, id_hi, flags, timeout,
            results, created_mask, created, inb_status,
            dstat_init, dstat, uniq_rows,
            hist_dr, hist_cr,
            int(out["last_applied"]),
            out["pulse_create"][:n],
            out["pulse_remove"][:n],
        )

        # Reply: failures only, in event order.
        fail_idx = np.flatnonzero(results != 0)
        reply = np.zeros(len(fail_idx), dtype=CREATE_RESULT_DTYPE)
        reply["index"] = fail_idx.astype(np.uint32)
        reply["result"] = results[fail_idx]
        return reply.tobytes()

    def _exact_joins(
        self, n, B, id_lo, id_hi, pend_lo, pend_hi, is_pv, ascending,
        e_found, e_row,
    ) -> dict:
        """Exact-path join bundle: compact id groups, in-batch pending
        reference groups, durable duplicate/pending-target gathers and
        the deduped durable-status seed — shared by the host exact
        path and the device engine's wave submission."""
        # Exact-path id groups: one compact index per distinct id value.
        id_key = pack_u128(id_lo, id_hi)
        if ascending:
            # Strictly ascending (the common sequential-id encoding):
            # identity grouping without the unique() sort.
            unique_ids = id_key
            id_group = np.arange(n)
        else:
            unique_ids, id_group = np.unique(id_key, return_inverse=True)
        pend_key = pack_u128(pend_lo, pend_hi)
        pos = np.searchsorted(unique_ids, pend_key)
        pos_c = np.minimum(pos, len(unique_ids) - 1)
        p_group = np.where(
            is_pv & (unique_ids[pos_c] == pend_key), pos_c, -1
        ).astype(np.int32)

        if is_pv.any():
            p_found, p_row = self._tdir.lookup(pend_lo, pend_hi)
            p_found = p_found & is_pv
        else:
            p_found = np.zeros(n, bool)
            p_row = np.zeros(n, np.uint64)
        er = np.clip(e_row, 0, None).astype(np.int64)
        pr = np.clip(p_row, 0, None).astype(np.int64)

        st = self._store

        # Durable joins: ONE batched fetch per referenced row set (the
        # rows may live in the LSM spill tier — per-column gathers
        # would re-read the objects 13 times), skipped entirely when
        # the batch references no durable duplicate/pending rows (the
        # common case for fresh-id batches).
        _JOIN_FIELDS = (
            "flags", "dr_slot", "cr_slot", "amount_lo", "amount_hi",
            "pending_lo", "pending_hi", "ud128_lo", "ud128_hi",
            "ud64", "ud32", "timeout", "ledger", "code", "timestamp",
            "status",
        )

        def _make_gather(found, rows):
            if not found.any():
                empty = {
                    f: np.zeros(n, np.dtype(_STORE_FIELDS[f]))
                    for f in _JOIN_FIELDS
                }
                return lambda col: empty[col]
            idx = np.flatnonzero(found)
            got = st.gather_many(
                list(_JOIN_FIELDS), rows[idx].astype(np.int64)
            )
            full = {}
            for f in _JOIN_FIELDS:
                arr = np.zeros(n, got[f].dtype)
                arr[idx] = got[f]
                full[f] = arr
            return lambda col: full[col]

        gather_e = _make_gather(e_found, er)
        gather_p = _make_gather(p_found, pr)

        # Durable-pending target dedupe + initial statuses (taken from
        # the already-gathered join columns — no second LSM fetch).
        p_rows_valid = p_row[p_found].astype(np.int64)
        if len(p_rows_valid):
            uniq_rows, first_idx, tgt_inverse = np.unique(
                p_rows_valid, return_index=True, return_inverse=True
            )
            rep_event = np.flatnonzero(p_found)[first_idx]
            uniq_status = gather_p("status")[rep_event].astype(np.uint32)
        else:
            uniq_rows = np.zeros(0, np.int64)
            tgt_inverse = np.zeros(0, np.int64)
            uniq_status = np.zeros(0, np.uint32)
        p_tgt = np.full(n, -1, np.int32)
        p_tgt[p_found] = tgt_inverse.astype(np.int32)
        dstat_init = np.zeros(B, np.uint32)
        dstat_init[: len(uniq_rows)] = uniq_status
        return dict(
            unique_ids=unique_ids, id_group=id_group, p_group=p_group,
            p_found=p_found, p_row=p_row, gather_e=gather_e,
            gather_p=gather_p, uniq_rows=uniq_rows,
            uniq_status=uniq_status, p_tgt=p_tgt, dstat_init=dstat_init,
        )

    def _build_scan_events(
        self, n, B, events, flags, static, amount_lo, amount_hi,
        pend_lo, pend_hi, timeout, ledger, code, dr_slot, cr_slot,
        dr_flags, cr_flags, dr_zero, cr_zero, e_found, j,
    ) -> dict:
        """The (B,)-padded host event-array dict per
        kernel.EVENT_FIELDS — the scan/wave executors' input contract,
        shared by the host exact path and the wave submission."""
        gather_e = j["gather_e"]
        gather_p = j["gather_p"]
        return {
            "i": np.arange(B, dtype=np.int32),
            "flags": _pad(flags, B),
            "ts_nonzero": _pad(events["timestamp"] != 0, B),
            "static_result": _pad(static, B),
            "amount_lo": _pad(amount_lo, B), "amount_hi": _pad(amount_hi, B),
            "pending_lo": _pad(pend_lo, B), "pending_hi": _pad(pend_hi, B),
            "ud128_lo": _pad(events["user_data_128_lo"].astype(np.uint64), B),
            "ud128_hi": _pad(events["user_data_128_hi"].astype(np.uint64), B),
            "ud64": _pad(events["user_data_64"].astype(np.uint64), B),
            "ud32": _pad(events["user_data_32"].astype(np.uint32), B),
            "timeout": _pad(timeout, B),
            "ledger": _pad(ledger, B), "code": _pad(code, B),
            "dr_slot": _pad(dr_slot, B), "cr_slot": _pad(cr_slot, B),
            "dr_flags": _pad(dr_flags, B), "cr_flags": _pad(cr_flags, B),
            "dr_id_zero": _pad(dr_zero, B), "cr_id_zero": _pad(cr_zero, B),
            "id_group": _pad(j["id_group"].astype(np.int32), B),
            "p_group": _pad(j["p_group"], B),
            "e_found": _pad(e_found, B),
            "e_flags": _pad(gather_e("flags").astype(np.uint32), B),
            "e_dr_slot": _pad(gather_e("dr_slot").astype(np.int32), B),
            "e_cr_slot": _pad(gather_e("cr_slot").astype(np.int32), B),
            "e_amount_lo": _pad(gather_e("amount_lo").astype(np.uint64), B),
            "e_amount_hi": _pad(gather_e("amount_hi").astype(np.uint64), B),
            "e_pending_lo": _pad(gather_e("pending_lo").astype(np.uint64), B),
            "e_pending_hi": _pad(gather_e("pending_hi").astype(np.uint64), B),
            "e_ud128_lo": _pad(gather_e("ud128_lo").astype(np.uint64), B),
            "e_ud128_hi": _pad(gather_e("ud128_hi").astype(np.uint64), B),
            "e_ud64": _pad(gather_e("ud64").astype(np.uint64), B),
            "e_ud32": _pad(gather_e("ud32").astype(np.uint32), B),
            "e_timeout": _pad(gather_e("timeout").astype(np.uint64), B),
            "e_code": _pad(gather_e("code").astype(np.uint32), B),
            "p_found": _pad(j["p_found"], B),
            "p_flags": _pad(gather_p("flags").astype(np.uint32), B),
            "p_dr_slot": _pad(gather_p("dr_slot").astype(np.int32), B),
            "p_cr_slot": _pad(gather_p("cr_slot").astype(np.int32), B),
            "p_amount_lo": _pad(gather_p("amount_lo").astype(np.uint64), B),
            "p_amount_hi": _pad(gather_p("amount_hi").astype(np.uint64), B),
            "p_ud128_lo": _pad(gather_p("ud128_lo").astype(np.uint64), B),
            "p_ud128_hi": _pad(gather_p("ud128_hi").astype(np.uint64), B),
            "p_ud64": _pad(gather_p("ud64").astype(np.uint64), B),
            "p_ud32": _pad(gather_p("ud32").astype(np.uint32), B),
            "p_timeout": _pad(gather_p("timeout").astype(np.uint64), B),
            "p_ledger": _pad(gather_p("ledger").astype(np.uint32), B),
            "p_code": _pad(gather_p("code").astype(np.uint32), B),
            "p_timestamp": _pad(gather_p("timestamp").astype(np.uint64), B),
            "p_tgt": _pad(j["p_tgt"], B),
        }

    def _plan_wave_execution(
        self, n, flags, dr_slot, cr_slot, dr_flags, cr_flags,
        id_group, p_group, p_tgt, p_found, gather_p, is_pv,
        amount_lo, amount_hi, force: bool = False, extra_bound: int = 0,
    ):
        """Wave routing decision for one exact-path batch: dependency
        metadata (_wave_metadata) -> cheap chain-dominance decline
        (_chain_dominated) -> per-column overflow admission
        (_wave_admission) -> level partition + profitability.
        Returns the plan or None — the scan path — and is always safe
        to decline (never a wrong answer, only a slower one).
        `extra_bound` is the device engine's in-flight contribution
        bound when planning a window batch (the mirror lags
        materialization there); zero on the drained host path."""
        meta, pv_serial = self._wave_metadata(
            n, flags, dr_slot, cr_slot, dr_flags, cr_flags,
            id_group, p_group, p_tgt, p_found, gather_p,
        )
        # Chain-dominance declines on a cheap metadata counter BEFORE
        # the per-column admission pays its bound accumulation.
        if self._chain_dominated(n, meta, force):
            return None
        adm = self._wave_admission(
            n, meta, flags, p_found, gather_p, is_pv,
            amount_lo, amount_hi, extra_bound=extra_bound,
        )
        if adm is None:
            return None
        inb_pairs, batch_bound = adm
        return self._grade_plan(n, meta, inb_pairs, batch_bound, force)

    def _grade_plan(self, n, meta, inb_pairs, batch_bound, force: bool):
        """Partition + profitability + bound attachment — the ONE copy
        shared by the drained host path and the window submission (a
        profitability change made in one and not the other would
        silently diverge the two routings)."""
        plan = waves.plan_waves(n, meta, inb_pairs=inb_pairs)
        if not (force or plan.profitable()):
            return None
        plan.batch_bound = batch_bound
        return plan

    def _wave_metadata(
        self, n, flags, dr_slot, cr_slot, dr_flags, cr_flags,
        id_group, p_group, p_tgt, p_found, gather_p,
    ):
        """Dependency metadata (resolve.py) + the pv_serial routing
        fact, shared by the pessimistic wave path and the speculative
        dispatcher — the cheap first stage every routing gate reads."""
        p_drs = gather_p("dr_slot").astype(np.int64)
        p_crs = gather_p("cr_slot").astype(np.int64)

        # History accounts force per-event-sequential snapshots: their
        # events read their own rows (wave_dependency_metadata), and a
        # post/void whose target could sit on one goes to the scan.
        hist_ev = ((dr_flags | cr_flags) & np.uint32(AF.history)) != 0
        pv_hist = False
        if p_found.any():
            pj = np.unique(
                np.concatenate([p_drs[p_found], p_crs[p_found]])
            )
            pj = pj[pj >= 0]
            pv_hist = bool(
                (self._attrs["flags"][pj] & np.uint32(AF.history)).any()
            )
        pv_serial = bool(hist_ev.any() or pv_hist)
        meta = resolve.wave_dependency_metadata(
            n, flags, dr_slot, cr_slot, dr_flags, cr_flags,
            id_group, p_group, p_tgt, p_found, p_drs, p_crs,
            pv_serial=pv_serial,
        )
        # Stash the durable pending-target slot arrays for the
        # admission stage — already gathered here, and this path's
        # host wall time is exactly what dev_wave.plan_s instruments.
        meta["p_drs"] = p_drs
        meta["p_crs"] = p_crs
        return meta, pv_serial

    def _wave_admission(
        self, n, meta, flags, p_found, gather_p, is_pv,
        amount_lo, amount_hi, extra_bound: int = 0,
    ):
        """Per-column overflow admission against the mirror, shared by
        the pessimistic wave path and the speculative dispatcher
        (which must prove the same overflow superset before executing
        the whole batch optimistically — the ov_* exactness argument
        is order-free, so it covers the one-step speculative apply and
        any residue replay identically).  Returns
        (inb_pairs, batch_bound) or None when the batch lacks provable
        u128 headroom."""
        p_drs = meta["p_drs"]
        p_crs = meta["p_crs"]

        # Per-column overflow admission (waves.admission_ok): per-event
        # amount upper bounds — balancing zero-amount means maxInt u64,
        # post/void apply at most max(t.amount, pending.amount), and an
        # in-batch inherit is bounded by the largest create bound.
        is_balancing = (
            flags & np.uint32(TF.balancing_debit | TF.balancing_credit)
        ) != 0
        amount_zero = (amount_lo == 0) & (amount_hi == 0)
        bound_lo = np.where(
            is_balancing & amount_zero, np.uint64(U64_MAX), amount_lo
        )
        bound_hi = np.where(is_balancing & amount_zero, np.uint64(0), amount_hi)
        p_amt_lo = gather_p("amount_lo").astype(np.uint64)
        p_amt_hi = gather_p("amount_hi").astype(np.uint64)
        p_bigger = is_pv & (
            (p_amt_hi > bound_hi)
            | ((p_amt_hi == bound_hi) & (p_amt_lo > bound_lo))
        )
        bound_lo = np.where(p_bigger, p_amt_lo, bound_lo)
        bound_hi = np.where(p_bigger, p_amt_hi, bound_hi)
        inb_inherit = is_pv & amount_zero & ~p_found
        if inb_inherit.any():
            nm = ~is_pv
            if nm.any():
                mx_hi = bound_hi[nm].max()
                at = bound_hi[nm] == mx_hi
                mx_lo = bound_lo[nm][at].max()
                bound_lo = np.where(inb_inherit, mx_lo, bound_lo)
                bound_hi = np.where(inb_inherit, mx_hi, bound_hi)
        # Per-contribution (slot, bound) pairs: each slot an event can
        # add a balance column through, charged with that event's
        # bound — dr/cr for creates, the durable target's accounts for
        # found finalizers, and the referenced group's slot union for
        # in-batch finalizers (the creator is whichever applied).
        inb_ev, inb_slot = waves._inb_pv_write_pairs(n, meta)
        slots = np.concatenate(
            [meta["ev_dr"], meta["ev_cr"],
             p_drs[p_found], p_crs[p_found], inb_slot]
        )
        bounds_lo = np.concatenate(
            [bound_lo, bound_lo, bound_lo[p_found], bound_lo[p_found],
             bound_lo[inb_ev]]
        )
        bounds_hi = np.concatenate(
            [bound_hi, bound_hi, bound_hi[p_found], bound_hi[p_found],
             bound_hi[inb_ev]]
        )
        # Admission runs BEFORE the per-event partition: the bound
        # arrays are vectorized numpy, so a persistently declining
        # deployment (no u128 headroom left) never pays the plan cost.
        if not waves.admission_ok(
            self._mirror.lo, self._mirror.hi, slots, bounds_lo, bounds_hi,
            extra=extra_bound,
        ):
            return None
        return (inb_ev, inb_slot), _amount_bound_total(bound_lo, bound_hi)

    @staticmethod
    def _chain_dominated(n, meta, force: bool) -> bool:
        """Cheap pre-admission decline: chain members cost one exact
        step each UNLESS they are chain-wave candidates (clean linked
        runs, waves.py) — decline chain-dominated batches before
        paying admission or the partition only when the chains could
        not ride position-stepped anyway."""
        n_chain = int(meta["chain_member"].sum())
        chain_wave_possible = (
            waves.chain_max() >= 2
            and not meta["chain_serial"].any()
            and not (meta["chain_linked"] & meta["is_pv"]).any()
        )
        return (
            not force
            and bool(n_chain)
            and not chain_wave_possible
            and n < waves.min_ratio() * n_chain
        )

    def _try_native_two_phase(
        self, input_bytes, events, n, ts_base
    ) -> bytes | None:
        """Two-phase batch via the native serial resolver
        (native/tb_two_phase.inc).  Python prefetches the durable
        pending targets' columns (they may live in the LSM spill tier)
        and finishes the store/expiry bookkeeping; the resolver owns
        decode, ladders, reference resolution, and balance effects."""
        flags16 = np.asarray(events["flags"])
        pv16 = np.uint16(TF.post_pending_transfer | TF.void_pending_transfer)
        pv_mask = (flags16 & pv16) != 0
        if not pv_mask.any():
            return None
        # Cheap shape gate before paying for the durable join (the
        # native pass-0 would reject these anyway, but only after the
        # tdir lookup + LSM gather below already ran).
        if (flags16 & np.uint16(TF.linked)).any():
            return None
        pv_idx = np.flatnonzero(pv_mask)
        pend_lo = np.asarray(events["pending_id_lo"])[pv_idx]
        pend_hi = np.asarray(events["pending_id_hi"])[pv_idx]
        found, rows = self._tdir.lookup(pend_lo, pend_hi)
        join = None
        if found.any():
            hit = pv_idx[found]
            hit_rows = rows[found].astype(np.int64)
            got = self._store.gather_many(
                [
                    "flags", "dr_slot", "cr_slot", "amount_lo", "amount_hi",
                    "ledger", "code", "ud128_lo", "ud128_hi", "ud64", "ud32",
                    "timeout", "status",
                ],
                hit_rows,
            )
            join = {"row": np.full(n, -1, np.int64)}
            join["row"][hit] = hit_rows
            for f, dt in (
                ("flags", np.uint32), ("dr_slot", np.int32),
                ("cr_slot", np.int32), ("amount_lo", np.uint64),
                ("amount_hi", np.uint64), ("ledger", np.uint32),
                ("code", np.uint32), ("ud128_lo", np.uint64),
                ("ud128_hi", np.uint64), ("ud64", np.uint64),
                ("ud32", np.uint32), ("timeout", np.uint32),
                ("status", np.uint32),
            ):
                arr = np.zeros(n, dt)
                arr[hit] = got[f].astype(dt)
                join[f] = arr
        r = self._native.commit_two_phase(input_bytes, n, ts_base, join)
        if r is None:
            return None
        d = r["deltas"]
        self._dev.enqueue(d[0].copy(), d[1].copy(), d[2].copy(), d[3].copy())
        # Durable finalizations: status byte updates (rows may be
        # spilled; referenced targets are timeout-free by the
        # resolver's contract, so no expiry-index deactivation).
        if len(r["dur_rows"]):
            self._store["status"][r["dur_rows"].copy()] = r[
                "dur_status"
            ].astype(np.uint8)
        flags = flags16.astype(np.uint32)
        timeout = np.asarray(events["timeout"]).astype(np.uint64)
        created = {
            "flags": flags,
            "dr_slot": r["row_dr"], "cr_slot": r["row_cr"],
            "amount_lo": r["amt_lo"], "amount_hi": r["amt_hi"],
            "pending_lo": np.asarray(events["pending_id_lo"]),
            "pending_hi": np.asarray(events["pending_id_hi"]),
            "ud128_lo": r["ud128_lo"], "ud128_hi": r["ud128_hi"],
            "ud64": r["ud64"], "ud32": r["ud32"],
            "timeout": timeout,
            "ledger": r["ledger"], "code": r["code"],
        }
        return self._finish_fast(
            n, ts_base, np.asarray(events["id_lo"]),
            np.asarray(events["id_hi"]), flags, timeout, r["results"],
            created, last_applied=r["last_applied"],
            inb_status=r["inb_status"],
        )

    def _finish_native_fast(
        self, events, n, ts_base, results, dr_slot, cr_slot, deltas,
        last_applied: int | None = None,
    ) -> bytes:
        """Bookkeeping after a native fast-path apply: device enqueue,
        store append, expiry/pulse updates, reply (mirrors
        _commit_fast's tail; results/slots are views into reusable
        native buffers, consumed before the next native call)."""
        dslot, dcol, dlo, dhi = deltas
        # Copies: the device queue holds these past this call, and the
        # native output buffers are reused per batch.
        self._dev.enqueue(
            dslot.copy(), dcol.copy(), dlo.copy(), dhi.copy()
        )

        # Hot tail: every event applied, no timeouts — ONE C pass
        # decodes the wire records straight into the store's column
        # buffers (replacing ~17 strided numpy gathers per batch),
        # then only the id-directory and commit_timestamp remain.
        if (
            not (results != 0).any()
            and not np.asarray(events["timeout"]).any()
        ):
            self.stat_hot_tail_batches += 1
            st = self._store
            st.ram._ensure(n)
            lo = st.ram.count
            from tigerbeetle_tpu.runtime import fastpath as fp_mod

            fp_mod.decode_store(events, n, ts_base, st.ram._cols, lo)
            st.ram._cols["dr_slot"][lo : lo + n] = dr_slot
            st.ram._cols["cr_slot"][lo : lo + n] = cr_slot
            st.ram.count = lo + n
            rows = np.arange(lo, lo + n) - st._off + st.base
            id_lo = st.ram._cols["id_lo"][lo : lo + n]
            id_hi = st.ram._cols["id_hi"][lo : lo + n]
            self._index_created(id_lo, id_hi, rows)
            self.commit_timestamp = ts_base + n - 1
            return b""

        self.stat_slow_tail_batches += 1
        flags = events["flags"].astype(np.uint32)
        timeout = np.asarray(events["timeout"]).astype(np.uint64)
        created = {
            "flags": flags,
            "dr_slot": dr_slot, "cr_slot": cr_slot,
            "amount_lo": np.asarray(events["amount_lo"]),
            "amount_hi": np.asarray(events["amount_hi"]),
            "pending_lo": np.asarray(events["pending_id_lo"]),
            "pending_hi": np.asarray(events["pending_id_hi"]),
            "ud128_lo": np.asarray(events["user_data_128_lo"]),
            "ud128_hi": np.asarray(events["user_data_128_hi"]),
            "ud64": np.asarray(events["user_data_64"]),
            "ud32": np.asarray(events["user_data_32"]),
            "timeout": timeout,
            "ledger": np.asarray(events["ledger"]),
            "code": events["code"].astype(np.uint32),
        }
        return self._finish_fast(
            n, ts_base, np.asarray(events["id_lo"]),
            np.asarray(events["id_hi"]), flags, timeout, results, created,
            last_applied=last_applied,
        )

    def _commit_fast(
        self, n, ts_base, events, id_lo, id_hi, pend_lo, pend_hi,
        flags, timeout, dr_slot, cr_slot, amount_lo, amount_hi, ledger, code,
        static,
    ) -> bytes | None:
        """Parallel scatter-add apply for order-independent batches.

        Returns None when a balance-overflow is possible, in which case
        the caller re-runs the exact scan kernel (a later event may
        legitimately apply after an earlier one fails with an overflow
        code — reference: src/state_machine.zig:1531-1545).
        """
        # Remaining per-event codes are all order-independent here:
        # timestamp_must_be_zero precedes the static ladder (reference:
        # src/state_machine.zig:1251-1256), overflows_timeout depends
        # only on the event's own timestamp.
        results = np.where(
            events["timestamp"] != 0,
            np.uint32(CTR.timestamp_must_be_zero),
            static,
        )
        ts_i = np.uint64(ts_base) + np.arange(n, dtype=np.uint64)
        expires = ts_i + timeout * np.uint64(NS_PER_S)
        ov_timeout = expires < ts_i
        if ov_timeout.any():
            # overflows_timeout ranks BELOW the balance-overflow codes
            # (reference ladder: src/state_machine.zig:1531-1545), and
            # such an event's amount wouldn't reach the mirror's
            # monotone check — only the exact path ranks them right.
            return None
        apply_mask = results == 0
        is_pending = (flags & np.uint32(TF.pending)) != 0

        # Host-mirror admission (monotone-overflow check) + async
        # device enqueue — the hot path never waits on the device.
        deltas = self._mirror.try_apply_adds(
            dr_slot.astype(np.int64), cr_slot.astype(np.int64),
            amount_lo, amount_hi, is_pending, apply_mask,
        )
        if deltas is None:
            return None
        self._dev.enqueue(*deltas, refresh_twin=False)

        created = {
            "flags": flags,
            "dr_slot": dr_slot.astype(np.int32),
            "cr_slot": cr_slot.astype(np.int32),
            "amount_lo": amount_lo, "amount_hi": amount_hi,
            "pending_lo": pend_lo, "pending_hi": pend_hi,
            "ud128_lo": np.asarray(events["user_data_128_lo"]),
            "ud128_hi": np.asarray(events["user_data_128_hi"]),
            "ud64": np.asarray(events["user_data_64"]),
            "ud32": np.asarray(events["user_data_32"]),
            "timeout": timeout,
            "ledger": ledger, "code": code,
        }
        return self._finish_fast(
            n, ts_base, id_lo, id_hi, flags, timeout, results, created
        )

    def _commit_linked_fast(
        self, n, ts_base, events, id_lo, id_hi, flags, timeout,
        dr_slot, cr_slot, amount_lo, amount_hi, ledger, code,
        static, dr_flags, cr_flags,
    ) -> bytes | None:
        """Linked-chain batch via the vectorized fixpoint resolver.

        Preconditions were checked by the router (plain posted
        transfers only, unique fresh ids, no history accounts).  The
        superset overflow admission below proves no overflow result
        code can fire for ANY subset of the batch (deltas are
        non-negative), which reduces the dynamic ladder to the limit
        checks that resolve.linked_resolve models exactly."""
        ts_nonzero = np.asarray(events["timestamp"] != 0)
        # Superset = every event that could conceivably apply (static
        # failures — including account-not-found, so slots here are
        # always valid — never touch balances).
        may_apply = (static == 0) & ~ts_nonzero
        if not may_apply.any():
            pass  # nothing can apply; resolver handles codes
        elif (
            self._mirror.try_apply_adds(
                dr_slot.astype(np.int64), cr_slot.astype(np.int64),
                amount_lo, amount_hi, np.zeros(n, bool), may_apply,
                commit=False,
            )
            is None
        ):
            return None
        r = resolve.linked_resolve(
            static, ts_nonzero, flags, dr_slot, cr_slot,
            amount_lo, amount_hi, dr_flags, cr_flags, self._mirror,
        )
        if r is None:
            return None
        results, last_applied, iters = r
        self.stat_resolve_iters += iters
        deltas = self._mirror.try_apply_adds(
            dr_slot.astype(np.int64), cr_slot.astype(np.int64),
            amount_lo, amount_hi, np.zeros(n, bool), results == 0,
        )
        assert deltas is not None  # subset of the admitted superset
        self._dev.enqueue(*deltas, refresh_twin=False)
        created = {
            "flags": flags,
            "dr_slot": dr_slot.astype(np.int32),
            "cr_slot": cr_slot.astype(np.int32),
            "amount_lo": amount_lo, "amount_hi": amount_hi,
            "pending_lo": np.zeros(n, np.uint64),
            "pending_hi": np.zeros(n, np.uint64),
            "ud128_lo": np.asarray(events["user_data_128_lo"]),
            "ud128_hi": np.asarray(events["user_data_128_hi"]),
            "ud64": np.asarray(events["user_data_64"]),
            "ud32": np.asarray(events["user_data_32"]),
            "timeout": timeout,
            "ledger": ledger, "code": code,
        }
        return self._finish_fast(
            n, ts_base, id_lo, id_hi, flags, timeout, results, created,
            last_applied=last_applied,
        )

    def _try_two_phase_fast(
        self, n, ts_base, events, id_lo, id_hi, pend_lo, pend_hi, flags,
        timeout, dr_slot, cr_slot, amount_lo, amount_hi, ledger, code,
        static, is_pv, dr_flags, cr_flags,
        unique_ids, id_group, p_group, p_found, gather_p,
        uniq_rows, p_tgt, uniq_status,
    ) -> bytes | None:
        """Two-phase batch via the closed-form resolver.

        Remaining preconditions (the router already checked unique
        fresh ids): no linked/balancing flags, zero timeouts
        everywhere (event timeouts AND durable targets'), no limit or
        history flags on any touched account including durable
        targets' accounts, and in-batch pending references that point
        at actual pending creates.  Anything else returns None — the
        serial exact engine owns it."""
        if (
            flags
            & np.uint32(TF.linked | TF.balancing_debit | TF.balancing_credit)
        ).any():
            return None
        if timeout.any():
            return None
        LIMH = np.uint32(
            AF.debits_must_not_exceed_credits
            | AF.credits_must_not_exceed_debits
            | AF.history
        )
        if ((dr_flags | cr_flags) & LIMH).any():
            return None
        attrs = self._attrs
        if p_found.any():
            if (gather_p("timeout") != 0).any():
                return None
            pj_dr = np.clip(gather_p("dr_slot").astype(np.int64), 0, None)
            pj_cr = np.clip(gather_p("cr_slot").astype(np.int64), 0, None)
            pj_flags = np.where(
                p_found,
                attrs["flags"][pj_dr] | attrs["flags"][pj_cr],
                0,
            ).astype(np.uint32)
            if (pj_flags & LIMH).any():
                return None

        # In-batch pending-reference resolution: creator event of each
        # distinct id (ids are unique, so this is a permutation).
        creator = np.empty(len(unique_ids), np.int64)
        creator[id_group] = np.arange(n)
        tgt_ev = np.where(
            p_group >= 0, creator[np.clip(p_group, 0, None)], -1
        )
        idx = np.arange(n)
        ib = is_pv & (tgt_ev >= 0) & (tgt_ev < idx)
        if (
            ib
            & (
                (flags[np.clip(tgt_ev, 0, None)] & np.uint32(TF.pending))
                == 0
            )
        ).any():
            # Reference resolution on a non-pending in-batch row would
            # couple pv verdicts to each other — exact engine decides.
            return None

        ts_nonzero = np.asarray(events["timestamp"] != 0)
        p_join = {
            f: gather_p(f)
            for f in (
                "flags", "dr_slot", "cr_slot", "amount_lo", "amount_hi",
                "ledger", "code", "ud128_lo", "ud128_hi", "ud64", "ud32",
            )
        }
        ud128_lo = np.asarray(events["user_data_128_lo"])
        ud128_hi = np.asarray(events["user_data_128_hi"])
        ud64 = np.asarray(events["user_data_64"])
        ud32 = np.asarray(events["user_data_32"]).astype(np.uint32)
        r = resolve.two_phase_resolve(
            static, ts_nonzero, flags, is_pv,
            np.asarray(events["debit_account_id_lo"]),
            np.asarray(events["debit_account_id_hi"]),
            np.asarray(events["credit_account_id_lo"]),
            np.asarray(events["credit_account_id_hi"]),
            amount_lo, amount_hi,
            ud128_lo, ud128_hi, ud64, ud32,
            np.asarray(events["ledger"]), code,
            tgt_ev, p_found, p_tgt, p_join, uniq_status, attrs,
        )
        if r is None:
            return None

        results = r["results"]
        ok = r["ok"]
        winner = r["winner"]
        post = r["post"]
        pend_flag = r["pend_flag"]
        tgt_c = np.clip(tgt_ev, 0, None)
        in_batch = r["in_batch"]
        # Unified target slots (in-batch event columns or durable join).
        p_drs = np.where(
            in_batch,
            dr_slot[tgt_c].astype(np.int64),
            np.clip(p_join["dr_slot"].astype(np.int64), 0, None),
        )
        p_crs = np.where(
            in_batch,
            cr_slot[tgt_c].astype(np.int64),
            np.clip(p_join["cr_slot"].astype(np.int64), 0, None),
        )

        # --- balance deltas.  Adds are admission-checked atomically;
        # pending releases can never underflow (each live pending's
        # amount is contained in dp/cp by invariant).
        pend_ok = ok & pend_flag
        plain_ok = ok & ~pend_flag & ~is_pv
        post_win = winner & post
        add_slots = np.concatenate([
            dr_slot[pend_ok].astype(np.int64), cr_slot[pend_ok].astype(np.int64),
            dr_slot[plain_ok].astype(np.int64), cr_slot[plain_ok].astype(np.int64),
            p_drs[post_win], p_crs[post_win],
        ])
        n_pend = int(pend_ok.sum())
        n_plain = int(plain_ok.sum())
        n_post = int(post_win.sum())
        add_cols = np.concatenate([
            np.zeros(n_pend, np.int64), np.full(n_pend, 2, np.int64),
            np.ones(n_plain, np.int64), np.full(n_plain, 3, np.int64),
            np.ones(n_post, np.int64), np.full(n_post, 3, np.int64),
        ])
        add_lo = np.concatenate([
            amount_lo[pend_ok], amount_lo[pend_ok],
            amount_lo[plain_ok], amount_lo[plain_ok],
            r["res_amt_lo"][post_win], r["res_amt_lo"][post_win],
        ])
        add_hi = np.concatenate([
            amount_hi[pend_ok], amount_hi[pend_ok],
            amount_hi[plain_ok], amount_hi[plain_ok],
            r["res_amt_hi"][post_win], r["res_amt_hi"][post_win],
        ])
        deltas = self._mirror.try_apply_deltas(
            add_slots, add_cols, add_lo, add_hi
        )
        if deltas is None:
            return None  # overflow codes in play — exact engine decides
        n_win = int(winner.sum())
        sub_slots = np.concatenate([p_drs[winner], p_crs[winner]])
        sub_cols = np.concatenate(
            [np.zeros(n_win, np.int64), np.full(n_win, 2, np.int64)]
        )
        sub_lo = np.concatenate([r["p_amt_lo"][winner]] * 2)
        sub_hi = np.concatenate([r["p_amt_hi"][winner]] * 2)
        if n_win:
            self._mirror.apply_subs(sub_slots, sub_cols, sub_lo, sub_hi)
            zero = np.zeros(2 * n_win, np.uint64)
            neg_lo, neg_hi, _ = _sub_u128(zero, zero, sub_lo, sub_hi)
            self._dev.enqueue(
                np.concatenate([deltas[0], sub_slots]),
                np.concatenate([deltas[1], sub_cols]),
                np.concatenate([deltas[2], neg_lo]),
                np.concatenate([deltas[3], neg_hi]),
                refresh_twin=False,
            )
        else:
            self._dev.enqueue(*deltas, refresh_twin=False)

        # --- durable store rows (zero-means-inherit resolution for
        # created pv rows; reference: src/state_machine.zig:1697-1720).
        ud128_set = (ud128_lo != 0) | (ud128_hi != 0)
        created = {
            "flags": flags,
            "dr_slot": np.where(is_pv, p_drs, dr_slot.astype(np.int64)).astype(np.int32),
            "cr_slot": np.where(is_pv, p_crs, cr_slot.astype(np.int64)).astype(np.int32),
            "amount_lo": np.where(is_pv, r["res_amt_lo"], amount_lo),
            "amount_hi": np.where(is_pv, r["res_amt_hi"], amount_hi),
            "pending_lo": pend_lo, "pending_hi": pend_hi,
            "ud128_lo": np.where(is_pv & ~ud128_set, r["p_ud128_lo"], ud128_lo),
            "ud128_hi": np.where(is_pv & ~ud128_set, r["p_ud128_hi"], ud128_hi),
            "ud64": np.where(is_pv & (ud64 == 0), r["p_ud64"], ud64),
            "ud32": np.where(is_pv & (ud32 == 0), r["p_ud32"], ud32),
            "timeout": np.zeros(n, np.uint64),
            "ledger": np.where(
                is_pv, r["p_ledger"], np.asarray(events["ledger"])
            ).astype(np.uint32),
            "code": np.where(is_pv, r["p_code"], code).astype(np.uint32),
        }
        inb_status = np.where(
            pend_ok, np.uint32(kernel.S_PENDING), np.uint32(0)
        )
        ib_win = winner & in_batch
        if ib_win.any():
            inb_status[tgt_ev[ib_win]] = np.where(
                post[ib_win],
                np.uint32(kernel.S_POSTED),
                np.uint32(kernel.S_VOIDED),
            )
        dstat_init = uniq_status.copy()
        dstat = uniq_status.copy()
        dur_win = winner & r["durable"]
        if dur_win.any():
            dstat[p_tgt[dur_win]] = np.where(
                post[dur_win],
                np.uint32(kernel.S_POSTED),
                np.uint32(kernel.S_VOIDED),
            )
        zeros_u64 = np.zeros(n, np.uint64)
        self._post_process_transfers(
            n, ts_base, id_lo, id_hi, flags, timeout,
            results, ok, created, inb_status,
            dstat_init, dstat, uniq_rows,
            np.zeros((n, 8), np.uint64), np.zeros((n, 8), np.uint64),
            r["last_applied"], zeros_u64, zeros_u64,
            no_history=True,
        )
        fail_idx = np.flatnonzero(results != 0)
        reply = np.zeros(len(fail_idx), dtype=CREATE_RESULT_DTYPE)
        reply["index"] = fail_idx.astype(np.uint32)
        reply["result"] = results[fail_idx]
        return reply.tobytes()

    def _index_created(self, id_lo, id_hi, rows, part=NOOP_RUN) -> None:
        """File a created batch's ids under its (contiguous) rows in
        the id directory and in the native duplicate-id set, kept in
        lockstep: the same batches, filed by the same rule."""
        part.switch(self._st_finish_ids)
        filed = self._tdir.insert(id_lo, id_hi, rows.astype(np.uint64))
        if filed:
            self._c_ids_runs_filed.inc(filed)
        else:
            self._c_ids_hashed.inc(len(id_lo))
        self._g_id_runs.set(self._tdir.runs)
        if self._native is not None:
            part.switch(self._st_finish_native_ids)
            self._native.add_transfer_ids(id_lo, id_hi, int(rows[0]))

    def _finish_fast(
        self, n, ts_base, id_lo, id_hi, flags, timeout, results, created,
        last_applied: int | None = None,
        inb_status: np.ndarray | None = None,
        part=NOOP_RUN,
    ) -> bytes:
        """Shared fast-path tail (native and Python admission paths):
        expiry/pulse signals, store bookkeeping, failure reply.  Must
        stay one implementation — every fast path\'s durable state
        depends on it being identical.  `inb_status` overrides the
        default created-pending statuses when the caller finalized
        pendings within the batch (two-phase resolver).  `part`: a
        finish closure's open run, moved on through sm.finish.*."""
        part.switch(self._st_finish_store)
        apply_mask = results == 0
        is_pending = (flags & np.uint32(TF.pending)) != 0
        ts_i = np.uint64(ts_base) + np.arange(n, dtype=np.uint64)
        expires = ts_i + timeout * np.uint64(NS_PER_S)
        if inb_status is None:
            inb_status = np.where(
                apply_mask & is_pending,
                np.uint32(kernel.S_PENDING),
                np.uint32(0),
            )
        if last_applied is None:
            applied_idx = np.flatnonzero(apply_mask)
            last_applied = int(applied_idx[-1]) if len(applied_idx) else -1
        pulse_create = np.where(
            apply_mask & is_pending & (timeout > 0), expires, np.uint64(0)
        )

        self._post_process_transfers(
            n, ts_base, id_lo, id_hi, flags, timeout,
            results, apply_mask, created, inb_status,
            np.zeros(0, np.uint32), np.zeros(0, np.uint32),
            np.zeros(0, np.int64),
            np.zeros((n, 8), np.uint64), np.zeros((n, 8), np.uint64),
            last_applied, pulse_create, np.zeros(n, np.uint64),
            no_history=True, part=part,
        )

        part.switch(self._st_finish_reply)
        fail_idx = np.flatnonzero(results != 0)
        reply = np.zeros(len(fail_idx), dtype=CREATE_RESULT_DTYPE)
        reply["index"] = fail_idx.astype(np.uint32)
        reply["result"] = results[fail_idx]
        return reply.tobytes()

    def _post_process_transfers(
        self, n, ts_base, id_lo, id_hi, flags, timeout,
        results, created_mask, created, inb_status,
        dstat_init, dstat, uniq_rows,
        hist_dr, hist_cr, last_applied, pulse_create, pulse_remove,
        no_history: bool = False, part=NOOP_RUN,
    ) -> None:
        part.switch(self._st_finish_store)
        ok = results == 0
        # 1. Insert created transfers into the columnar store.  When
        # the whole batch applied (the hot path), index with slices —
        # no per-column fancy-gather copies.
        cm = created_mask
        if cm.all():
            idx = np.arange(n)
            sel = lambda a: a  # noqa: E731
        elif cm.any():
            idx = np.flatnonzero(cm)
            sel = lambda a: a[idx]  # noqa: E731
        else:
            idx = None
        if idx is not None:
            ts = np.uint64(ts_base) + idx.astype(np.uint64)
            rows = self._store.append(
                id_lo=sel(id_lo), id_hi=sel(id_hi),
                dr_slot=sel(created["dr_slot"]), cr_slot=sel(created["cr_slot"]),
                amount_lo=sel(created["amount_lo"]), amount_hi=sel(created["amount_hi"]),
                pending_lo=sel(created["pending_lo"]), pending_hi=sel(created["pending_hi"]),
                ud128_lo=sel(created["ud128_lo"]), ud128_hi=sel(created["ud128_hi"]),
                ud64=sel(created["ud64"]), ud32=sel(created["ud32"]),
                timeout=sel(created["timeout"]).astype(np.uint32, copy=False),
                ledger=sel(created["ledger"]), code=sel(created["code"]),
                flags=sel(flags), timestamp=ts,
                status=sel(inb_status).astype(np.uint8),
            )
            self._index_created(sel(id_lo), sel(id_hi), rows, part)
            row_of_event = np.full(n, -1, np.int64)
            row_of_event[idx] = rows
        else:
            row_of_event = np.full(n, -1, np.int64)

        # 2. Durable pending-status updates (+ expires index removal),
        # batched: changed rows may live in the LSM spill tier.
        part.switch(self._st_finish_status)
        changed = np.flatnonzero(dstat[: len(uniq_rows)] != dstat_init[: len(uniq_rows)])
        if len(changed):
            ch_rows = uniq_rows[changed]
            self._store["status"][ch_rows] = dstat[changed].astype(np.uint8)
            # A pending with a timeout is one with an active entry in
            # the expires index, which is in RAM: no read of the store
            # (the rows may be cold) to learn which they are.
            if self._exp.count > self._exp_dead:
                timed = self._exp.col("row")[self._exp.col("active")]
                for row in ch_rows[np.isin(ch_rows, timed)]:
                    self._exp_deactivate(int(row))

        # 3. New expires entries for still-pending in-batch creations.
        pend_created = np.flatnonzero(
            cm & (inb_status == kernel.S_PENDING) & (timeout > 0)
        )
        if len(pend_created):
            exp_rows = row_of_event[pend_created]
            expires = (
                np.uint64(ts_base)
                + pend_created.astype(np.uint64)
                + timeout[pend_created] * np.uint64(NS_PER_S)
            )
            self._exp.append(
                expires_at=expires,
                row=exp_rows.astype(np.uint32),
                active=np.ones(len(exp_rows), bool),
            )
        # In-batch created-then-finished pendings: status already stored;
        # their expires entries were never added (create+remove nets out).

        # 4. pulse_next_timestamp replay from the kernel's apply-time
        # signals — these are recorded pre-rollback, matching the
        # reference's unscoped pulse_next mutations
        # (reference: src/state_machine.zig:1576-1580,1704-1708).
        for k in np.flatnonzero((pulse_create != 0) | (pulse_remove != 0)):
            create_at = int(pulse_create[k])
            remove_at = int(pulse_remove[k])
            if create_at:
                if create_at < self.pulse_next_timestamp:
                    self.pulse_next_timestamp = create_at
            if remove_at:
                if self.pulse_next_timestamp == remove_at:
                    self.pulse_next_timestamp = TIMESTAMP_MIN

        # 5. Historical balances (skipped when the fast-path admission
        # already proved no account in the batch has flags.history).
        applied = cm & ok
        if not no_history and applied.any():
            idx = np.flatnonzero(applied)
            drs = created["dr_slot"][idx]
            crs = created["cr_slot"][idx]
            dr_hist = (self._attrs["flags"][drs] & AF.history) != 0
            cr_hist = (self._attrs["flags"][crs] & AF.history) != 0
            want = dr_hist | cr_hist
            if want.any():
                sel = idx[want]
                drs, crs = drs[want], crs[want]
                dr_hist, cr_hist = dr_hist[want], cr_hist[want]
                zero8 = np.zeros((len(sel), 8), np.uint64)
                self._history.append(
                    timestamp=np.uint64(ts_base) + sel.astype(np.uint64),
                    dr_id_lo=np.where(dr_hist, self._attrs["id_lo"][drs], 0),
                    dr_id_hi=np.where(dr_hist, self._attrs["id_hi"][drs], 0),
                    cr_id_lo=np.where(cr_hist, self._attrs["id_lo"][crs], 0),
                    cr_id_hi=np.where(cr_hist, self._attrs["id_hi"][crs], 0),
                    dr_bal=np.where(dr_hist[:, None], hist_dr[sel], zero8),
                    cr_bal=np.where(cr_hist[:, None], hist_cr[sel], zero8),
                )

        # 6. commit_timestamp advances to the last event that reached
        # the apply point — including chain events later rolled back
        # (reference: src/state_machine.zig:1583; rollback never
        # reverts commit_timestamp).
        if last_applied >= 0:
            self.commit_timestamp = ts_base + last_applied

    def _exp_deactivate(self, row: int) -> None:
        exp_rows = self._exp.col("row")
        active = self._exp.col("active")
        matches = np.flatnonzero((exp_rows == row) & active)
        self._exp["active"][matches] = False
        self._exp_dead += len(matches)
        # Compact once tombstones dominate, keeping scans O(live).
        if self._exp_dead * 2 > self._exp.count and self._exp.count > 64:
            live = np.flatnonzero(self._exp.col("active"))
            cols = {
                name: self._exp.col(name)[live].copy()
                for name in ("expires_at", "row", "active")
            }
            self._exp.truncate(0)
            self._exp.append(**cols)
            self._exp_dead = 0

    # ------------------------------------------------------------------
    # Expiry pulse.

    def _scan_expired(self, expires_at_max: int) -> np.ndarray:
        limit = self.config.batch_max_create_transfers
        active = self._exp.col("active")
        exp_at = self._exp.col("expires_at")
        rows = self._exp.col("row")
        live = np.flatnonzero(active)
        if len(live) == 0:
            self.pulse_next_timestamp = TIMESTAMP_MAX
            return np.zeros(0, np.int64)
        ts = self._store["timestamp"][rows[live]]
        order = np.lexsort((ts, exp_at[live]))
        ordered = live[order]
        ordered_exp = exp_at[live][order]

        due = ordered_exp <= expires_at_max
        due_idx = np.flatnonzero(due)
        if len(due_idx) > limit:
            taken = ordered[due_idx[:limit]]
            # buffer_finished: next pulse rescans from the overflow point
            # (reference: src/state_machine.zig:2136-2140).
            self.pulse_next_timestamp = int(ordered_exp[due_idx[limit]])
        elif len(due_idx) == len(ordered_exp):
            taken = ordered[due_idx]
            self.pulse_next_timestamp = TIMESTAMP_MAX
        else:
            taken = ordered[due_idx]
            self.pulse_next_timestamp = int(ordered_exp[len(due_idx)])
        return rows[taken].astype(np.int64)

    def _commit_expire(self, timestamp: int) -> bytes:
        assert self._expiry_rows is not None
        rows, self._expiry_rows = self._expiry_rows, None
        if len(rows) == 0:
            return b""

        st = self._store
        # Release pending amounts: dp -= amount on the debit side,
        # cp -= amount on the credit side (sums are order-independent;
        # reference: src/state_machine.zig:1874-1929). Mirror applies
        # exactly; the device gets the same deltas as two's-complement
        # modular adds through the write-behind queue.
        slots = np.concatenate([st["dr_slot"][rows], st["cr_slot"][rows]]).astype(
            np.int64
        )
        cols = np.concatenate(
            [np.zeros(len(rows), np.int64), np.full(len(rows), 2, np.int64)]
        )
        amt_lo = np.concatenate([st["amount_lo"][rows]] * 2)
        amt_hi = np.concatenate([st["amount_hi"][rows]] * 2)
        self._mirror.apply_subs(slots, cols, amt_lo, amt_hi)
        zero = np.zeros(len(slots), np.uint64)
        neg_lo, neg_hi, _ = _sub_u128(zero, zero, amt_lo, amt_hi)
        self._dev.enqueue(slots, cols, neg_lo, neg_hi, refresh_twin=False)

        st["status"][rows] = np.uint8(TransferPendingStatus.expired)
        for row in rows:
            self._exp_deactivate(int(row))
        return b""

    # ------------------------------------------------------------------
    # Lookups & queries (cold path).

    def _lookup_accounts(self, input_bytes: bytes) -> bytes:
        ids = np.frombuffer(input_bytes, dtype=types.U128_PAIR_DTYPE)
        found, slots = self._acct_dir.lookup(
            ids["lo"].astype(np.uint64), ids["hi"].astype(np.uint64)
        )
        hit = np.flatnonzero(found)
        out = np.zeros(len(hit), dtype=ACCOUNT_DTYPE)
        if len(hit) == 0:
            return b""
        slots = slots[hit].astype(np.int64)
        balances = self._mirror.rows8(slots)
        a = self._attrs
        out["id_lo"], out["id_hi"] = a["id_lo"][slots], a["id_hi"][slots]
        out["debits_pending_lo"], out["debits_pending_hi"] = balances[:, 0], balances[:, 1]
        out["debits_posted_lo"], out["debits_posted_hi"] = balances[:, 2], balances[:, 3]
        out["credits_pending_lo"], out["credits_pending_hi"] = balances[:, 4], balances[:, 5]
        out["credits_posted_lo"], out["credits_posted_hi"] = balances[:, 6], balances[:, 7]
        out["user_data_128_lo"] = a["ud128_lo"][slots]
        out["user_data_128_hi"] = a["ud128_hi"][slots]
        out["user_data_64"] = a["ud64"][slots]
        out["user_data_32"] = a["ud32"][slots]
        out["ledger"] = a["ledger"][slots]
        out["code"] = a["code"][slots]
        out["flags"] = a["flags"][slots]
        out["timestamp"] = a["timestamp"][slots]
        return out.tobytes()

    def _transfer_rows_to_np(self, rows: np.ndarray) -> np.ndarray:
        st = self._store
        rows = np.asarray(rows, np.int64)
        out = np.zeros(len(rows), dtype=TRANSFER_DTYPE)
        if len(rows) == 0:
            return out
        cols = st.gather_many(
            [
                "id_lo", "id_hi", "dr_slot", "cr_slot", "amount_lo",
                "amount_hi", "pending_lo", "pending_hi", "ud128_lo",
                "ud128_hi", "ud64", "ud32", "timeout", "ledger", "code",
                "flags", "timestamp",
            ],
            rows,
        )
        out["id_lo"], out["id_hi"] = cols["id_lo"], cols["id_hi"]
        dr = cols["dr_slot"].astype(np.int64)
        cr = cols["cr_slot"].astype(np.int64)
        out["debit_account_id_lo"] = self._attrs["id_lo"][dr]
        out["debit_account_id_hi"] = self._attrs["id_hi"][dr]
        out["credit_account_id_lo"] = self._attrs["id_lo"][cr]
        out["credit_account_id_hi"] = self._attrs["id_hi"][cr]
        out["amount_lo"], out["amount_hi"] = cols["amount_lo"], cols["amount_hi"]
        out["pending_id_lo"], out["pending_id_hi"] = cols["pending_lo"], cols["pending_hi"]
        out["user_data_128_lo"] = cols["ud128_lo"]
        out["user_data_128_hi"] = cols["ud128_hi"]
        out["user_data_64"] = cols["ud64"]
        out["user_data_32"] = cols["ud32"]
        out["timeout"] = cols["timeout"]
        out["ledger"] = cols["ledger"]
        out["code"] = cols["code"]
        out["flags"] = cols["flags"]
        out["timestamp"] = cols["timestamp"]
        return out

    def _lookup_transfers(self, input_bytes: bytes) -> bytes:
        ids = np.frombuffer(input_bytes, dtype=types.U128_PAIR_DTYPE)
        found, rows = self._tdir.lookup(
            ids["lo"].astype(np.uint64), ids["hi"].astype(np.uint64)
        )
        hit = rows[found].astype(np.int64)
        return self._transfer_rows_to_np(hit).tobytes()

    def _parse_filter(self, input_bytes: bytes):
        row = np.frombuffer(input_bytes, dtype=ACCOUNT_FILTER_DTYPE)[0]
        return row

    def _filter_rows(self, filter_row) -> np.ndarray | None:
        """Validated filter -> matching store rows in timestamp order.

        reference: src/state_machine.zig:931-996.
        """
        account_id = types.u128_get(filter_row, "account_id")
        ts_min = int(filter_row["timestamp_min"])
        ts_max = int(filter_row["timestamp_max"])
        limit = int(filter_row["limit"])
        fflags = int(filter_row["flags"])
        valid = (
            account_id != 0
            and account_id != U128_MAX
            and ts_min != U64_MAX
            and ts_max != U64_MAX
            and (ts_max == 0 or ts_min <= ts_max)
            and limit != 0
            and (fflags & (AccountFilterFlags.debits | AccountFilterFlags.credits))
            and not (fflags & ~int(AccountFilterFlags._valid_mask))
            and bytes(filter_row["reserved"]) == b"\x00" * 24
        )
        if not valid:
            return None
        slot = self._account_slot(account_id)
        if slot is None:
            return np.zeros(0, np.int64)
        st = self._store
        lo = TIMESTAMP_MIN if ts_min == 0 else ts_min
        hi = TIMESTAMP_MAX if ts_max == 0 else ts_max
        # Spilled rows: the query composes through the ScanBuilder —
        # the same expression engine (eq / union / intersect over the
        # (slot, ts) index trees) the reference routes queries through
        # (reference: src/state_machine.zig:931-996 -> src/lsm/
        # scan_builder.zig:529).  Values mode yields row pointers.
        if st.base:
            from tigerbeetle_tpu.lsm.scan_builder import ScanBuilder

            self._forest.barrier()  # the index trees are the worker's
            sb = ScanBuilder(st.spill.groove)
            scans = []
            if fflags & AccountFilterFlags.debits:
                scans.append(sb.eq("dr_slot", slot))
            if fflags & AccountFilterFlags.credits:
                scans.append(sb.eq("cr_slot", slot))
            spilled = sb.evaluate(
                sb.union(*scans), ts_min=lo, ts_max=hi, return_values=True
            ).astype(np.int64)
        else:
            spilled = np.zeros(0, np.int64)
        # RAM tail: vectorized column scan.
        mask = np.zeros(st.tail_count(), bool)
        if fflags & AccountFilterFlags.debits:
            mask |= st.col("dr_slot") == slot
        if fflags & AccountFilterFlags.credits:
            mask |= st.col("cr_slot") == slot
        ts = st.col("timestamp")
        mask &= (ts >= lo) & (ts <= hi)
        tail_rows = np.flatnonzero(mask) + st.base
        # Spilled rows all precede the tail; concat keeps ts order.
        rows = np.concatenate([spilled, tail_rows])
        if fflags & AccountFilterFlags.reversed:
            rows = rows[::-1]
        return rows

    def _get_account_transfers(self, input_bytes: bytes) -> bytes:
        filter_row = self._parse_filter(input_bytes)
        rows = self._filter_rows(filter_row)
        if rows is None:
            return b""
        batch_max = self.config.batch_max(
            ACCOUNT_FILTER_DTYPE.itemsize, TRANSFER_DTYPE.itemsize
        )
        rows = rows[: min(int(filter_row["limit"]), batch_max)]
        return self._transfer_rows_to_np(rows).tobytes()

    def _get_account_balances(self, input_bytes: bytes) -> bytes:
        filter_row = self._parse_filter(input_bytes)
        account_id = types.u128_get(filter_row, "account_id")
        slot = self._account_slot(account_id)
        if slot is None or not (int(self._attrs["flags"][slot]) & AF.history):
            return b""
        rows = self._filter_rows(filter_row)
        if rows is None:
            return b""
        batch_max = self.config.batch_max(
            ACCOUNT_FILTER_DTYPE.itemsize, ACCOUNT_BALANCE_DTYPE.itemsize
        )
        rows = rows[: min(int(filter_row["limit"]), batch_max)]
        # Map transfer timestamps -> history rows (same timestamps;
        # history rows are store-ordered too).  The RAM tail serves
        # recent rows; older rows come from the LSM history groove.
        want_ts = np.asarray(self._store["timestamp"][rows], np.uint64)
        h = self._history
        h_ts = h.col("timestamp")
        id_lo = np.uint64(account_id & 0xFFFFFFFFFFFFFFFF)
        id_hi = np.uint64(account_id >> 64)
        bal = np.zeros((len(rows), 8), np.uint64)
        in_ram = np.zeros(len(rows), bool)
        if len(h_ts):
            pos = np.searchsorted(h_ts, want_ts)
            pos_c = np.minimum(pos, len(h_ts) - 1)
            in_ram = h_ts[pos_c] == want_ts
            pr = pos_c[in_ram]
            is_dr = (h["dr_id_lo"][pr] == id_lo) & (h["dr_id_hi"][pr] == id_hi)
            bal[in_ram] = np.where(
                is_dr[:, None], h["dr_bal"][pr], h["cr_bal"][pr]
            )
        cold = ~in_ram
        if cold.any():
            assert self._hspill is not None, "history row missing"
            found, got = self._hspill.gather_by_ts(want_ts[cold])
            assert found.all(), "history row missing from LSM tier"
            is_dr = (got["dr_id_lo"] == id_lo) & (got["dr_id_hi"] == id_hi)
            bal[cold] = np.where(
                is_dr[:, None], got["dr_bal"], got["cr_bal"]
            )
        out = np.zeros(len(rows), dtype=ACCOUNT_BALANCE_DTYPE)
        out["debits_pending_lo"], out["debits_pending_hi"] = bal[:, 0], bal[:, 1]
        out["debits_posted_lo"], out["debits_posted_hi"] = bal[:, 2], bal[:, 3]
        out["credits_pending_lo"], out["credits_pending_hi"] = bal[:, 4], bal[:, 5]
        out["credits_posted_lo"], out["credits_posted_hi"] = bal[:, 6], bal[:, 7]
        out["timestamp"] = want_ts
        return out.tobytes()


def _pad(arr: np.ndarray, size: int) -> np.ndarray:
    n = len(arr)
    if n == size:
        return np.ascontiguousarray(arr)
    out = np.zeros(size, arr.dtype)
    out[:n] = arr
    return out


# ----------------------------------------------------------------------
# Checkpoint snapshot (consumed by vsr.checkpointing).

def _tpu_snapshot(self) -> bytes:
    """Serialize durable state: columnar stores + the balance mirror
    (which exactly equals the device table after a queue drain —
    kernel_fast.py write-behind contract).  Fixed-layout binary
    encoding (utils/snapshot.py), NOT pickle: checkpoint blobs travel
    via state sync and must be safe to decode from untrusted bytes."""
    with self.tracer.stage(self._st_ckpt_drain) as part:
        return _tpu_snapshot_parts(self, part)


def _tpu_snapshot_parts(self, part) -> bytes:
    from tigerbeetle_tpu.utils import snapshot as snapcodec

    if self.engine == "device":
        self._dev.drain()
    self._dev.flush()  # queue drained; mirror == device content
    # Device<->mirror checksum at the checkpoint barrier (VERDICT r3
    # #4): in device mode the mirror is a demoted parity oracle, so a
    # silent divergence would otherwise surface only on a fallback.
    # Host mode pays a ~100ms fetch on this link, so it verifies only
    # when asked (TB_CKPT_VERIFY=1; tests and VOPR set it).
    from tigerbeetle_tpu import envcheck as _envcheck

    if self.engine == "device" or _envcheck.env_str("TB_CKPT_VERIFY") == "1":
        part.switch(self._st_ckpt_verify_device)
        self.verify_device_mirror(part)
    part.switch(self._st_ckpt_encode)
    count = self._attrs.count
    # prepare_timestamp is primary-only in-memory state, re-derived from
    # commit_timestamp after restore — see cpu.py snapshot note.
    # With a forest attached, the store section holds only the RAM tail
    # (everything older lives in LSM grid blocks referenced by the
    # manifest) — the blob is O(tail + accounts), not O(history).
    state = {
        "commit_timestamp": self.commit_timestamp,
        "pulse_next_timestamp": self.pulse_next_timestamp,
        "exp_dead": self._exp_dead,
        "store_base": self._store.base,
        "attrs": {k: self._attrs.col(k) for k in _ATTR_FIELDS},
        "store": {k: self._store.col(k) for k in _STORE_FIELDS},
        "exp": {k: self._exp.col(k) for k in ("expires_at", "row", "active")},
        "history": {k: self._history.col(k) for k in _HISTORY_FIELDS},
        "mirror_lo": self._mirror.lo[:count],
        "mirror_hi": self._mirror.hi[:count],
    }
    if self._forest is not None:
        state["history_base"] = self._hspill.base
        state["forest"] = self._forest.manifest_blob()
    return snapcodec.encode_tree(state)


def _tpu_restore(self, data: bytes) -> None:
    import jax.numpy as jnp

    from tigerbeetle_tpu.utils import snapshot as snapcodec

    state = snapcodec.decode_tree(data)
    if self._forest is not None:
        # Beats of the state this replaces still name its stores.
        self._forest.barrier()
    self.commit_timestamp = state["commit_timestamp"]
    self.pulse_next_timestamp = state["pulse_next_timestamp"]
    self._exp_dead = state["exp_dead"]
    self.prepare_timestamp = self.commit_timestamp

    self._attrs = Columns(_ATTR_FIELDS)
    self._attrs.append(**state["attrs"])
    self._g_accounts.set(self._attrs.count)
    self._store = TailStore(_STORE_FIELDS, cold_join=self._cold_join)
    self._store.append(**state["store"])
    self._exp = Columns(
        {"expires_at": np.uint64, "row": np.uint32, "active": np.bool_}
    )
    self._exp.append(**state["exp"])
    self._history = Columns(_HISTORY_FIELDS)
    self._history.append(**state["history"])

    base = state.get("store_base", 0)
    if "forest" in state:
        from tigerbeetle_tpu.state_machine import spill as spill_mod

        assert self._forest is not None, "snapshot requires a forest"
        # Reopen the LSM tier from its manifest, then re-point the
        # spill handles at the restored grooves.
        self._forest.open(state["forest"])
        self._store.spill = self._transfer_spill()
        self._store.spill.base = base
        self._store.base = base
        self._hspill = spill_mod.HistorySpill(
            self._forest.grooves["account_history"],
            barrier=self._forest.barrier,
        )
        self._hspill.base = state["history_base"]
    else:
        assert base == 0, "spilled snapshot but no forest attached"

    # Rebuild directories (derived state, never serialized).  Spilled
    # ids stream back from the object tree once; sequential-id runs
    # compress to O(1) ranges in the directories.
    n_acct = self._attrs.count
    self._acct_dir = RunIndex(_dir_capacity(n_acct))
    self._acct_dir.insert(
        self._attrs.col("id_lo"), self._attrs.col("id_hi"),
        np.arange(n_acct, dtype=np.uint64),
    )
    self._tdir = RunIndex(_dir_capacity(self._store.count))
    if base:
        from tigerbeetle_tpu.state_machine import spill as spill_mod

        for rows, obj in self._store.spill.iter_objects():
            cols = spill_mod.unpack_objects(obj)
            self._tdir.insert(
                cols["id_lo"], cols["id_hi"], rows.astype(np.uint64)
            )
    self._tdir.insert(
        self._store.col("id_lo"), self._store.col("id_hi"),
        np.arange(base, base + self._store.tail_count(), dtype=np.uint64),
    )
    self._g_id_runs.set(self._tdir.runs)

    cap = max(1 << 12, 1 << (n_acct - 1).bit_length() if n_acct else 1)
    self._mirror = BalanceMirror(cap)
    self._mirror.lo[:n_acct] = state["mirror_lo"]
    self._mirror.hi[:n_acct] = state["mirror_hi"]
    if self._native is not None:
        self._rebuild_native(cap)
    if self._commitment is not None:
        # Fresh twin over the restored mirror + attrs: recovery
        # recomputes the commitment from scratch (the replica asserts
        # it against the superblock's recorded state root).
        from tigerbeetle_tpu.state_machine import commitment as commitment_mod

        self._commitment = commitment_mod.HostCommitment(
            cap, meta_fn=self._commit_meta_cols
        )
        self._commitment.rebuild(self._mirror)
        self._mirror.commitment = self._commitment
        self._mirror.twin_part = self._st_finish_twin
    if self.engine == "device":
        from tigerbeetle_tpu.state_machine.device_engine import (
            DeviceEngine,
            DeviceLostError,
            make_spec_stats,
            make_touch_stats,
        )

        self._dev = DeviceEngine(
            cap, self._mirror, link=self._device_link,
            metrics=self.metrics.scope("dev"),
        )
        self._dev.tracer = self.tracer
        # Re-bind the machine-registry dev_wave.spec.* handles — the
        # counters are process-lifetime cumulative across restores.
        self._dev.spec_stats = make_spec_stats(self.metrics)
        self._dev.touch_stats = make_touch_stats(self.metrics)
        self._bind_tier_stats()
        try:
            if self._dev.state is types.EngineState.healthy:
                # Tiered, this uploads the hot-shaped image for the
                # FRESH engine's (empty) hot map — admissions refill
                # the window on demand from the restored mirror.
                self._dev._upload_from_mirror()
        except DeviceLostError as exc:
            # Restore must not die with the link: the mirror restored
            # above is authoritative until re-promotion.
            self._dev._demote(exc)
        if n_acct:
            self._dev.add_accounts(
                np.arange(n_acct, dtype=np.int64),
                self._attrs.col("flags"),
                self._attrs.col("ledger"),
            )
    else:
        self._dev = kernel_fast.DeviceTable(cap)
        self._dev.mirror = self._mirror
        self._bind_tier_stats()
        # write_back gathers hot rows under tiering (identity swap
        # all-resident; _place only applies to device-resident tables).
        full = jnp.asarray(
            self._mirror.rows8(np.arange(cap, dtype=np.int64))
        )
        if self._dev.hot is None:
            full = self._dev._place(full)
        self._dev.write_back(full)
    self._inflight_timeouts = False
    self._expiry_rows = None


TpuStateMachine.snapshot = _tpu_snapshot
TpuStateMachine.restore = _tpu_restore
