"""Conflict-aware wave execution: parallel apply for independent
transfers, exact scan only for true dependencies.

The sequential scan kernel (kernel.py) pays one device step per EVENT
— B steps per batch — even when almost every event touches disjoint
accounts.  This module collapses that to one step per *wave*: a
host-side partitioner (`plan_waves`) builds the batch's conflict graph
and assigns each event a topological LEVEL (one more than the highest
level among earlier events it conflicts with); each level executes as
ONE vectorized device step over its — possibly non-contiguous — index
set (`_wave_step_impl`, the scan body re-expressed over a (K,) event
axis with balance deltas combined by an exact u128 segment-sum
scatter, like kernel_fast._flush_impl), while true serial dependencies
— linked chains — run through the unchanged exact scan at their batch
position (kernel.scan_segment).  A two_phase batch of (pending,
finalize) pairs is exactly TWO waves; a fresh-ids batch is ONE.  The
segment kinds thread one carry, so outputs are bit-identical to the
full scan (enforced by tests/test_waves.py differential fuzz).

What makes two events DEPENDENT (same model as parallel-EVM conflict
graphs — arXiv:2503.04595 — specialized to the reference semantics):

- **id/pending references.**  A second event with the same transfer-id
  value must observe the first's create (exists ladder); a post/void
  whose pending_id names an in-batch id must observe that create and
  its status.  Tracked as compact id-group tokens (tpu.py's exact-path
  grouping): two events conflict when either's id_group or p_group was
  already claimed by the wave.
- **durable two-phase targets.**  Two finalizers of the same durable
  pending race first-wins; the second's verdict depends on the first.
  Tracked by p_tgt (the deduped durable-target index).
- **balance READS.**  Most transfers only *add* to balance columns —
  addition commutes and their result codes read no mutable state, so
  they share a wave even on the same hot account (the deltas sum).
  But balancing_debit/credit clamps and debits/credits_must_not_exceed
  limit checks *read* account balances: such an event conflicts with
  any wave-mate that writes one of its read slots (and its own writes
  conflict with wave-mates' reads).
- **linked chains & history accounts.**  Rollback couples every chain
  member (including the closing event), and an AF.history account's
  per-event snapshot must be sequential-exact (it feeds the history
  groove, while wave snapshots are rewritten to batch finals).
  History events always run in exact scan segments; chain runs whose
  chains are MUTUALLY INDEPENDENT (no pv/history members, ids claimed
  once batch-wide, no slot both touched by two chains and read by
  anyone) run position-stepped as CHAIN WAVES — one lax.scan over
  chain position (`_chain_wave_impl`), ~max_chain_len steps instead
  of one per member, with exact trailing-subtraction rollback —
  and everything else keeps the scan.

Overflow codes are the one read everyone performs implicitly: whether
`amount + dp` overflows u128 depends on prior events.  The executor
keeps them exact with the same superset admission the order-free fast
path uses (mirror.try_apply_adds): amounts are non-negative, so if the
ALL-APPLIED additions to a slot cannot overflow its columns or its
dp+dpo / cp+cpo pairs, no sequential prefix can either, and every
ov_* term is identically false in both orders.  `admission_ok` proves
that bound per touched slot on the host mirror (plus an `extra` term
covering in-flight window batches when the device engine plans
against its lagging mirror); a batch that fails it routes to the scan
path — never a wrong answer, only a slower one.

Two executors share the segment loop (`_execute_plan`): the host
exact path donates its table (run_create_transfers_waves), while the
device engine's window launch dispatches NON-DONATING twins
(run_plan_engine) so its authoritative handle survives mid-batch
retries (device_engine._exec_waves).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from tigerbeetle_tpu.ops import u128 as w
from tigerbeetle_tpu.state_machine import kernel
from tigerbeetle_tpu.state_machine.kernel import (
    CREATED_FIELDS,
    F_BAL_CR,
    F_BAL_DR,
    F_LINKED,
    F_PENDING,
    F_POST,
    F_VOID,
    NS_PER_S,
    R_ALREADY_POSTED,
    R_ALREADY_VOIDED,
    R_EXCEEDS_CREDITS,
    R_EXCEEDS_DEBITS,
    R_EXCEEDS_PENDING_AMOUNT,
    R_OVERFLOWS_CP,
    R_OVERFLOWS_CPO,
    R_OVERFLOWS_CREDITS,
    R_OVERFLOWS_DEBITS,
    R_OVERFLOWS_DP,
    R_OVERFLOWS_DPO,
    R_OVERFLOWS_TIMEOUT,
    R_PENDING_DIFF_AMOUNT,
    R_PENDING_DIFF_CODE,
    R_PENDING_DIFF_CR,
    R_PENDING_DIFF_DR,
    R_PENDING_DIFF_LEDGER,
    R_PENDING_EXPIRED,
    R_PENDING_NOT_FOUND,
    R_PENDING_NOT_PENDING,
    R_TIMESTAMP_MUST_BE_ZERO,
    S_PENDING,
    S_POSTED,
    S_VOIDED,
    U64_MAX,
    _E_FIELD_MAP,
    _EXISTS_SENTINEL,
    _P_FIELD_MAP,
    _exists_ladder_normal,
    _exists_ladder_post_void,
    _first_nonzero,
    _gather_created,
    _merge,
    AF_CR_LIMIT,
    AF_DR_LIMIT,
    CP_LO, CP_HI, CPO_LO, CPO_HI, DP_LO, DP_HI, DPO_LO, DPO_HI,
)

_MASK32 = jnp.uint64(0xFFFFFFFF)

# Wave/scan segment shape buckets (jit compile cache keys).
_SEG_BUCKETS = (16, 64, 256, 1024, 4096, 8192)

def min_ratio() -> float:
    """Minimum step-count reduction (batch length / executed steps)
    before the wave path beats the plain scan; below it the partition
    degrades toward per-event waves and the scan's single fused
    dispatch wins.  Read live (like mode()) so tests can toggle
    TB_WAVES_MIN_RATIO after import."""
    from tigerbeetle_tpu import envcheck

    return envcheck.env_float("TB_WAVES_MIN_RATIO", 2.0, minimum=0.0)


def mode() -> str:
    """TB_WAVES routing mode:

    - unset/"auto": wave plans considered whenever the JAX exact scan
      would otherwise run (native absent), profitability + admission
      gates apply.
    - "0": off — the exact path always runs the B-step scan.
    - "1": force — route every batch to the JAX exact path (bypassing
      the native engine and the order-free/linked/two-phase fast
      paths) and execute the wave plan even when unprofitable.
      Differential-test routing: maximizes wave-executor coverage.
    - "exact": route to the JAX exact path like "1", but keep the
      normal profitability/admission decision (what the scheduler
      would really do there).
    - "scan": route to the JAX exact path, never plan waves — the
      pure sequential scan on identical routing, the honest control
      for wave-vs-scan benchmarks."""
    from tigerbeetle_tpu import envcheck

    return envcheck.env_choice(
        "TB_WAVES", "auto", ("auto", "0", "1", "exact", "scan")
    )


def dev_mode() -> str:
    """TB_DEV_WAVES routing mode for the device engine's window launch
    (independent of TB_WAVES, which governs the host exact path):

    - unset/"auto": window batches that fall off the semantic kernels
      (mixed kinds, conflicting ids, balancing, timeouts, two-phase
      edge shapes) are wave-dispatched against the authoritative HBM
      table when the plan is admitted and profitable; declines keep
      the r7 behavior (drain + exact host path).
    - "0": off — off-kernel batches always drain to the host.
    - "1": force — execute every ADMITTED plan even when unprofitable
      (differential-test routing; admission is never bypassed, it is
      the correctness proof)."""
    from tigerbeetle_tpu import envcheck

    return envcheck.env_choice("TB_DEV_WAVES", "auto", ("auto", "0", "1"))


def spec_mode() -> str:
    """TB_WAVES_SPECULATE routing mode for the device wave dispatcher
    (see envcheck.waves_speculate for the full contract): "auto"/"1"
    speculate behind the residue-cap gate, "0" keeps the pessimistic
    plan-first path, "force" routes every window batch optimistically.
    Read live (like mode()) so tests can toggle it after import."""
    from tigerbeetle_tpu import envcheck

    return envcheck.waves_speculate()


def spec_residue_cap() -> float:
    """TB_WAVES_SPEC_RESIDUE_CAP, read live (envcheck-validated)."""
    from tigerbeetle_tpu import envcheck

    return envcheck.spec_residue_cap()


def chain_max() -> int:
    """TB_WAVES_CHAIN_MAX: longest chain (in positions) a chain-wave
    segment may carry — longer chains keep the exact scan, whose cost
    is one step per member.  0 disables chain waves entirely.  Read
    live so tests can toggle it after import."""
    from tigerbeetle_tpu import envcheck

    return envcheck.env_int(
        "TB_WAVES_CHAIN_MAX", 64, minimum=0, maximum=4096
    )


# ---------------------------------------------------------------------------
# Partitioner.


@dataclass
class WavePlan:
    """Execution plan: ordered segments whose index sets cover [0, n).

    Segment order is the EXECUTION order; a "wave" segment's indices
    need not be contiguous (topological-level scheduling), a "scan"
    segment is always a contiguous chain run executed at its batch
    position, and a "chains" segment is a contiguous run of mutually
    independent linked chains executed position-stepped (one device
    step per chain POSITION — `chain_steps` holds the padded step
    count per segment index).
    """

    n: int
    # (kind, idx): kind "wave" = one parallel step over idx (int
    # array, ascending), kind "scan" = len(idx) exact sequential
    # steps over a contiguous run, kind "chains" = chain_steps[k]
    # position steps over a contiguous run of independent chains.
    segments: list = field(default_factory=list)
    wave_mask: np.ndarray | None = None  # events whose snapshots are
    # rewritten to batch finals (wave + chain-wave events)
    chain_steps: dict = field(default_factory=dict)
    # Host-integer sum of the batch's per-event amount bounds — the
    # admission term a later window batch must count while this one is
    # in flight (set by tpu._plan_wave_execution).
    batch_bound: int = 0

    @property
    def n_waves(self) -> int:
        return sum(1 for k, _ in self.segments if k == "wave")

    @property
    def parallel_events(self) -> int:
        return sum(len(ix) for k, ix in self.segments if k == "wave")

    @property
    def n_steps(self) -> int:
        """Device-step equivalents: 1 per wave, length per scan run,
        padded position count per chain-wave run."""
        total = 0
        for k, (kind, ix) in enumerate(self.segments):
            if kind == "wave":
                total += 1
            elif kind == "chains":
                total += self.chain_steps[k]
            else:
                total += len(ix)
        return total

    @property
    def ratio(self) -> float:
        return self.n / max(1, self.n_steps)

    def profitable(self, ratio_floor: float | None = None) -> bool:
        return self.ratio >= (
            min_ratio() if ratio_floor is None else ratio_floor
        )


# How many wavefront rounds the vectorized level assigner runs before
# handing the region to the Python-walk fallback: profitable plans
# have FEW levels (the ratio gate needs n / steps >= min_ratio), so a
# region still unassigned after this many rounds is serial enough that
# the O(n) walk is the cheaper exact algorithm.
_WAVEFRONT_CAP = 24


def _inb_pv_write_pairs(n: int, meta: dict):
    """(event, slot) pairs for in-batch post/voids: the slot union of
    the id-group each finalizer's pending reference names (the creator
    is whichever group member applied, so the finalizer's static write
    set is the union).  Shared by the partitioner's conflict entries
    and the per-column overflow admission (tpu.py)."""
    inb = meta["inb_pv"]
    empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))
    if not inb.any():
        return empty
    id_group = meta["id_group"]
    ref = np.unique(meta["p_group"][inb])
    member = np.isin(id_group, ref)
    g2 = np.concatenate([id_group[member], id_group[member]])
    s2 = np.concatenate([meta["ev_dr"][member], meta["ev_cr"][member]])
    keep = s2 >= 0
    g2, s2 = g2[keep], s2[keep]
    if len(g2) == 0:
        return empty
    span = int(s2.max()) + 2
    key = np.unique(g2 * span + s2)
    pg, ps = key // span, key % span
    evs = np.flatnonzero(inb)
    lo = np.searchsorted(pg, meta["p_group"][evs], side="left")
    hi = np.searchsorted(pg, meta["p_group"][evs], side="right")
    cnt = hi - lo
    total = int(cnt.sum())
    if total == 0:
        return empty
    out_ev = np.repeat(evs, cnt)
    within = np.arange(total) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    out_slot = ps[np.repeat(lo, cnt) + within]
    return out_ev.astype(np.int64), out_slot.astype(np.int64)


def _levels_walk(lo: int, hi: int, meta: dict, group_slots) -> np.ndarray:
    """Per-event Python walk over region [lo, hi) — the REFERENCE
    level assignment (the vectorized wavefront must agree exactly;
    tests/test_device_waves.py fuzzes the two against each other) and
    the fallback for regions more serial than _WAVEFRONT_CAP levels.

    Level = 1 + max level of every earlier conflicting event: same-id
    claims (exists ladder), pending refs, first-wins finalize targets,
    then balance-slot RAW/WAR (a reader must see exactly the earlier
    writers' adds; later writers must apply after it reads).  Reads
    also serialize against earlier reads — a balancing/limit reader's
    own writes are data-dependent, and the greedy rule this
    generalizes kept reader pairs ordered.
    """
    id_group = meta["id_group"]
    p_group = meta["p_group"]
    p_tgt = meta["p_tgt"]
    writes0, writes1 = meta["writes0"], meta["writes1"]
    reads0, reads1 = meta["reads0"], meta["reads1"]
    inb_pv = meta["inb_pv"]
    group_level: dict[int, int] = {}
    ptgt_level: dict[int, int] = {}
    write_level: dict[int, int] = {}
    read_level: dict[int, int] = {}
    levels = np.zeros(hi - lo, np.int32)
    for e in range(lo, hi):
        g = int(id_group[e])
        pg = int(p_group[e])
        pt = int(p_tgt[e])
        ww = []
        if writes0[e] >= 0:
            ww.append(int(writes0[e]))
        if writes1[e] >= 0:
            ww.append(int(writes1[e]))
        if inb_pv[e]:
            ww.extend(group_slots.get(pg, ()))
        rr = []
        if reads0[e] >= 0:
            rr.append(int(reads0[e]))
        if reads1[e] >= 0:
            rr.append(int(reads1[e]))

        lvl = group_level.get(g, -1) + 1
        if pg >= 0:
            lvl = max(lvl, group_level.get(pg, -1) + 1)
        if pt >= 0:
            lvl = max(lvl, ptgt_level.get(pt, -1) + 1)
        for s in rr:
            lvl = max(
                lvl,
                write_level.get(s, -1) + 1,
                read_level.get(s, -1) + 1,
            )
        for s in ww:
            lvl = max(lvl, read_level.get(s, -1) + 1)

        levels[e - lo] = lvl
        if lvl > group_level.get(g, -1):
            group_level[g] = lvl
        if pg >= 0 and lvl > group_level.get(pg, -1):
            group_level[pg] = lvl
        if pt >= 0 and lvl > ptgt_level.get(pt, -1):
            ptgt_level[pt] = lvl
        for s in ww:
            if lvl > write_level.get(s, -1):
                write_level[s] = lvl
        for s in rr:
            if lvl > read_level.get(s, -1):
                read_level[s] = lvl
    return levels


def _levels_wavefront(
    lo: int, hi: int, meta: dict, inb_ev, inb_slot, cap: int = None
) -> np.ndarray | None:
    """Vectorized level assignment for region [lo, hi): Kahn's
    algorithm by level over the conflict DAG.  At round k every
    still-unassigned event with no unassigned predecessor takes level
    k — which equals the walk's greedy level exactly (a predecessor's
    level is strictly below its successors', so "all predecessors
    assigned" first becomes true at round 1 + max pred level).

    Per round the blocked test is a segmented min over sorted-by-token
    entry arrays: for a serial token (id/pending-group claim,
    first-wins target) only the minimum-index unassigned claimant is
    unblocked; for a balance slot a reader is unblocked only as the
    minimum-index unassigned toucher, a writer when no unassigned
    reader precedes it (commuting writers share a round).  Rounds cost
    O(entries) vectorized; plans worth executing have few levels, so a
    region still unassigned after `cap` rounds returns None and the
    caller uses the O(n) walk.
    """
    if cap is None:
        cap = _WAVEFRONT_CAP
    m = hi - lo
    if m <= 1:
        return np.zeros(m, np.int32)
    rel = np.arange(m, dtype=np.int64)
    # Serial tokens: even ids = id/pending groups, odd = durable
    # first-wins targets (namespaces never collide).
    id_group = meta["id_group"][lo:hi]
    s_tok = [2 * id_group]
    s_ev = [rel]
    pg = meta["p_group"][lo:hi]
    msk = pg >= 0
    s_tok.append(2 * pg[msk])
    s_ev.append(rel[msk])
    pt = meta["p_tgt"][lo:hi]
    msk = pt >= 0
    s_tok.append(2 * pt[msk] + 1)
    s_ev.append(rel[msk])
    ser_tok = np.concatenate(s_tok)
    ser_ev = np.concatenate(s_ev)
    _, ser_tok = np.unique(ser_tok, return_inverse=True)
    n_ser = int(ser_tok.max()) + 1

    # Slot entries: (slot, event, role).
    sl, se, sr = [], [], []
    for name, is_read in (
        ("reads0", True), ("reads1", True),
        ("writes0", False), ("writes1", False),
    ):
        a = meta[name][lo:hi]
        msk = a >= 0
        sl.append(a[msk])
        se.append(rel[msk])
        sr.append(np.full(int(msk.sum()), is_read))
    if len(inb_ev):
        msk = (inb_ev >= lo) & (inb_ev < hi)
        sl.append(inb_slot[msk])
        se.append(inb_ev[msk] - lo)
        sr.append(np.zeros(int(msk.sum()), bool))
    slot = np.concatenate(sl)
    sev = np.concatenate(se)
    sread = np.concatenate(sr)
    have_slots = len(slot) > 0
    if have_slots:
        _, slot = np.unique(slot, return_inverse=True)
        n_slot = int(slot.max()) + 1

    levels = np.full(m, -1, np.int32)
    un = np.ones(m, bool)
    big = np.int64(m)
    for lvl in range(cap):
        blk = np.zeros(m, bool)
        act = un[ser_ev]
        t_min = np.full(n_ser, big, np.int64)
        np.minimum.at(t_min, ser_tok[act], ser_ev[act])
        e_act = ser_ev[act]
        np.logical_or.at(blk, e_act, e_act > t_min[ser_tok[act]])
        if have_slots:
            sact = un[sev]
            a_min = np.full(n_slot, big, np.int64)
            np.minimum.at(a_min, slot[sact], sev[sact])
            r_min = np.full(n_slot, big, np.int64)
            ract = sact & sread
            np.minimum.at(r_min, slot[ract], sev[ract])
            es = sev[sact]
            lim = np.where(
                sread[sact], a_min[slot[sact]], r_min[slot[sact]]
            )
            np.logical_or.at(blk, es, es > lim)
        take = un & ~blk
        if not take.any():
            # The DAG is acyclic (edges point forward), so this is
            # unreachable while events remain — guard anyway.
            return None
        levels[take] = lvl
        un &= ~take
        if not un.any():
            return levels
    return None


def _chain_wave_steps(i: int, j: int, n: int, meta: dict, claims):
    """Chain-wave admission for the chain run [i, j): the padded
    position-step count when the run's chains may execute
    position-stepped, else None (keep the exact scan).

    Requirements — each guards a specific exactness argument:
    - no must-scan members (history snapshots are semantically read)
      and no post/void members (first-wins + rollback un-finalize
      would couple chains);
    - every member's id-group is claimed exactly once batch-wide
      (fresh-or-durable-dup ids, never referenced by another event:
      a rolled-back member's created-record registration can then
      never feed a later exists/pending merge);
    - chains are pairwise independent: a balance slot touched by two
      different chains must have NO reader (commuting adds may share;
      a read coupled to another chain's writes — or its rollback —
      would diverge from the sequential order);
    - the longest chain fits the TB_WAVES_CHAIN_MAX cap, and the
      padded step count actually beats the scan's one step/member.
    """
    cap = chain_max()
    if cap < 2:
        return None
    if meta["chain_serial"][i:j].any() or meta["is_pv"][i:j].any():
        return None
    if (claims[meta["id_group"][i:j]] != 1).any():
        return None
    linked = meta["linked"][i:j]
    m = j - i
    starts = np.empty(m, bool)
    starts[0] = True
    starts[1:] = ~linked[:-1]
    chain_rel = np.cumsum(starts) - 1
    n_chains = int(chain_rel[-1]) + 1
    if n_chains < 2:
        return None
    max_len = int(np.bincount(chain_rel).max())
    if max_len > cap:
        return None
    steps = _bucket_positions(max_len)
    if steps >= m:
        return None
    # Pairwise chain independence over balance slots.
    sl, ch, rd = [], [], []
    for name, is_read in (
        ("reads0", True), ("reads1", True),
        ("writes0", False), ("writes1", False),
    ):
        a = meta[name][i:j]
        msk = a >= 0
        sl.append(a[msk])
        ch.append(chain_rel[msk])
        rd.append(np.full(int(msk.sum()), is_read))
    slot = np.concatenate(sl)
    if len(slot):
        chain_of = np.concatenate(ch)
        isr = np.concatenate(rd)
        order = np.lexsort((chain_of, slot))
        slot, chain_of, isr = slot[order], chain_of[order], isr[order]
        seg_new = np.empty(len(slot), bool)
        seg_new[0] = True
        seg_new[1:] = slot[1:] != slot[:-1]
        seg_id = np.cumsum(seg_new) - 1
        n_seg = int(seg_id[-1]) + 1
        first_chain = chain_of[seg_new][seg_id]
        multi = np.zeros(n_seg, bool)
        np.logical_or.at(multi, seg_id, chain_of != first_chain)
        has_read = np.zeros(n_seg, bool)
        np.logical_or.at(has_read, seg_id, isr)
        if (multi & has_read).any():
            return None
    return steps


def plan_waves(
    n: int, meta: dict, use_walk: bool = False, inb_pairs=None,
    claims=None, group_slots_fn=None,
) -> WavePlan:
    """Partition a batch into wave/chain-wave/scan segments.

    Chain runs (contiguous spans of ``chain_member`` events) are
    barriers at their batch position: runs of mutually independent
    linked chains execute position-stepped as a "chains" segment
    (~max_chain_len device steps — see _chain_wave_steps for the
    admission), everything else stays an exact scan.  The chain-free
    REGIONS between them schedule like a parallel-EVM conflict graph
    (arXiv:2503.04595): each event's *level* is one more than the
    highest level of any earlier in-region event it conflicts with
    (shared id/pending token, first-wins target, or a read-write
    balance-slot overlap), and each level executes as ONE wave —
    commuting adds never conflict, so a two_phase batch of (pending,
    finalize) pairs collapses to exactly two waves.  Level order
    preserves sequential semantics for every conflicting pair;
    non-conflicting events commute, so any interleaving of levels is
    bit-identical to the scan.

    Levels come from the vectorized wavefront (_levels_wavefront,
    sorted-token segmented mins) and
    fall back to the per-event Python walk for regions more serial
    than _WAVEFRONT_CAP levels; ``use_walk=True`` forces the walk —
    the reference algorithm the fuzz pins the wavefront against.

    `meta` comes from resolve.wave_dependency_metadata — see there for
    the field contract; `inb_pairs` lets a caller that already built
    the in-batch finalizer write pairs (_inb_pv_write_pairs — the
    admission in tpu._plan_wave_execution needs them too) pass them
    in instead of recomputing.  Runs once per batch on the host, only
    when the wave path is a routing candidate.

    `claims` / `group_slots_fn` exist for SUBSET planning
    (plan_residue): when `meta` covers only a batch's conflicted
    residue, the chain-wave claims admission and the walk fallback's
    in-batch slot unions must still count the COMMITTED events outside
    the subset — the caller supplies full-batch claim counts and a
    full-batch group->slot-union factory, and the subset-local lazy
    builders are skipped.
    """
    chain_member = meta["chain_member"]
    id_group = meta["id_group"]
    p_group = meta["p_group"]
    p_tgt = meta["p_tgt"]
    reads0, reads1 = meta["reads0"], meta["reads1"]
    inb_pv = meta["inb_pv"]

    # Fast path for the dominant shape (fresh unique ids, no chains, no
    # finalizers, no balance readers): the whole batch is ONE wave —
    # skip level assignment entirely.  The arange test covers the
    # ascending-id encoding (tpu.py's identity grouping) without the
    # O(n log n) unique().
    if (
        not chain_member.any()
        and not inb_pv.any()
        and (reads0 < 0).all()
        and (reads1 < 0).all()
        and (p_tgt < 0).all()
        and (p_group < 0).all()
        and (
            (len(id_group) == n and id_group[0] == 0
             and bool((np.diff(id_group) == 1).all()))
            or len(np.unique(id_group)) == n
        )
    ):
        plan = WavePlan(n, segments=[("wave", np.arange(n))])
        plan.wave_mask = np.ones(n, bool)
        return plan

    inb_ev, inb_slot = (
        inb_pairs if inb_pairs is not None else _inb_pv_write_pairs(n, meta)
    )
    group_slots = None  # walk-fallback slot unions, built lazily

    plan = WavePlan(n)
    wave_mask = np.zeros(n, bool)
    segments = plan.segments

    def walk_group_slots():
        # In-batch pending references resolve to the creating event at
        # run time; statically, the finalizer may write the slots of
        # ANY event sharing that id-group, so its write set is the
        # group's slot union.
        nonlocal group_slots
        if group_slots is None:
            if group_slots_fn is not None:
                group_slots = group_slots_fn()
            else:
                group_slots = {}
                if inb_pv.any():
                    ev_dr, ev_cr = meta["ev_dr"], meta["ev_cr"]
                    for e in range(n):
                        g = int(id_group[e])
                        s = group_slots.setdefault(g, set())
                        if ev_dr[e] >= 0:
                            s.add(int(ev_dr[e]))
                        if ev_cr[e] >= 0:
                            s.add(int(ev_cr[e]))
        return group_slots

    def level_region(lo: int, hi: int) -> None:
        levels = None
        if not use_walk:
            levels = _levels_wavefront(lo, hi, meta, inb_ev, inb_slot)
        if levels is None:
            levels = _levels_walk(lo, hi, meta, walk_group_slots())
        for lvl in range(int(levels.max()) + 1 if hi > lo else 0):
            idx = lo + np.flatnonzero(levels == lvl)
            segments.append(("wave", idx))
            wave_mask[idx] = True

    i = 0
    while i < n:
        if chain_member[i]:
            j = i
            while j < n and chain_member[j]:
                j += 1
            if claims is None:
                span = int(max(id_group.max(), p_group.max())) + 1
                claims = np.bincount(id_group, minlength=span)
                pgv = p_group[p_group >= 0]
                if len(pgv):
                    claims = claims + np.bincount(pgv, minlength=span)
            steps = _chain_wave_steps(i, j, n, meta, claims)
            if steps is not None:
                segments.append(("chains", np.arange(i, j)))
                plan.chain_steps[len(segments) - 1] = steps
                wave_mask[i:j] = True
            else:
                segments.append(("scan", np.arange(i, j)))
            i = j
            continue
        j = i
        while j < n and not chain_member[j]:
            j += 1
        level_region(i, j)
        i = j

    plan.wave_mask = wave_mask
    return plan


def plan_residue(n: int, meta: dict, idx: np.ndarray) -> WavePlan:
    """Wave plan for the conflicted RESIDUE of a speculatively-executed
    batch: the level partition plan_waves builds, restricted to the
    ascending global indices `idx`, with every segment's index set in
    GLOBAL batch coordinates and `wave_mask` a (n,) global mask.

    Soundness of planning the subset in isolation: a committed
    (non-conflicted) event commutes with every residue event — a
    conflict in either direction would have blocked one of them at
    validation — so pre-applying all committed effects is sequentially
    equivalent, and only residue-internal order constraints remain.
    Two full-batch terms still leak into the subset plan and are
    supplied from the full metadata: the chain-wave admission's
    claimed-exactly-once-batch-wide counts (a committed claimant
    outside the subset must still decline the chain wave — its created
    record feeds the member's exists merge, which the chain-wave step
    does not model) and the walk fallback's in-batch finalizer slot
    unions (the committed creator's slots are part of a residue
    finalizer's static write set)."""
    idx = np.asarray(idx, np.int64)
    sub = {
        key: (val[idx] if isinstance(val, np.ndarray) else val)
        for key, val in meta.items()
    }
    inb_ev, inb_slot = _inb_pv_write_pairs(n, meta)
    if len(inb_ev):
        keep = np.isin(inb_ev, idx)
        local = np.searchsorted(idx, inb_ev[keep])
        inb_pairs = (local.astype(np.int64), inb_slot[keep])
    else:
        inb_pairs = (inb_ev, inb_slot)
    claims = None
    if sub["chain_member"].any():
        id_group, p_group = meta["id_group"], meta["p_group"]
        span = int(max(id_group.max(), p_group.max())) + 1
        claims = np.bincount(id_group, minlength=span)
        pgv = p_group[p_group >= 0]
        if len(pgv):
            claims = claims + np.bincount(pgv, minlength=span)

    def group_slots_full():
        out: dict = {}
        ev_dr, ev_cr = meta["ev_dr"], meta["ev_cr"]
        id_group = meta["id_group"]
        for e in range(n):
            s = out.setdefault(int(id_group[e]), set())
            if ev_dr[e] >= 0:
                s.add(int(ev_dr[e]))
            if ev_cr[e] >= 0:
                s.add(int(ev_cr[e]))
        return out

    local_plan = plan_waves(
        len(idx), sub, inb_pairs=inb_pairs, claims=claims,
        group_slots_fn=group_slots_full,
    )
    plan = WavePlan(len(idx))
    mask = np.zeros(n, bool)
    for k, (kind, seg) in enumerate(local_plan.segments):
        gseg = idx[np.asarray(seg)]
        plan.segments.append((kind, gseg))
        if kind == "chains":
            plan.chain_steps[len(plan.segments) - 1] = (
                local_plan.chain_steps[k]
            )
        if kind in ("wave", "chains"):
            mask[gseg] = True
    plan.wave_mask = mask
    return plan


# ---------------------------------------------------------------------------
# Overflow admission (host, against the balance mirror).


def admission_ok(
    mirror_lo: np.ndarray,
    mirror_hi: np.ndarray,
    slots: np.ndarray,
    bound_lo: np.ndarray,
    bound_hi: np.ndarray,
    extra: int = 0,
) -> bool:
    """Per-column superset overflow admission for the whole batch.

    `slots` / `bound_lo` / `bound_hi` are aligned per-CONTRIBUTION
    arrays: each (slot, bound) entry upper-bounds one balance-column
    addition the batch can make at that slot (slot < 0 entries are
    ignored; an event appears once per slot it can add through —
    dr/cr for a create, the target's slot union for a finalizer).

    True when, for every touched slot, (pre dp+dpo) + T and
    (pre cp+cpo) + T provably fit u128, where T = the slot's bound sum
    plus `extra` — a host-integer upper bound on contributions already
    in flight but not yet reflected in the mirror (the device engine's
    window pipelining; zero on the drained host path).  Then every
    per-event ov_* term is false in ANY execution order: amounts are
    non-negative, so each sequential prefix of any column (and either
    pair) is bounded by pre + all-applied additions to that slot, and
    releases only shrink it.  Per-column bounding (instead of the old
    whole-table "any nonzero hi limb declines" rule) admits u128-scale
    balances as long as their remaining headroom covers the batch —
    ROADMAP "Wave-path admission breadth".
    """
    valid = slots >= 0
    if not valid.all():
        slots = slots[valid]
        bound_lo = bound_lo[valid]
        bound_hi = bound_hi[valid]
    if len(slots) == 0:
        return True
    # float64 limb bincounts are exact below 2^53: < 2^21 entries of
    # 32-bit limbs (same bound compact_deltas relies on).
    assert len(slots) < (1 << 21)
    m32 = np.uint64(0xFFFFFFFF)
    top = int(slots.max()) + 1
    acc = [
        np.bincount(slots, limb.astype(np.float64), top).astype(np.uint64)
        for limb in (
            bound_lo & m32, bound_lo >> np.uint64(32),
            bound_hi & m32, bound_hi >> np.uint64(32),
        )
    ]
    c0, c1, c2, c3 = acc
    c1 = c1 + (c0 >> np.uint64(32))
    c2 = c2 + (c1 >> np.uint64(32))
    c3 = c3 + (c2 >> np.uint64(32))
    if ((c3 >> np.uint64(32)) != 0).any():
        return False  # one slot's bound sum alone exceeds u128
    t_lo = (c0 & m32) | ((c1 & m32) << np.uint64(32))
    t_hi = (c2 & m32) | ((c3 & m32) << np.uint64(32))
    touched = np.unique(slots)
    T_lo = t_lo[touched]
    T_hi = t_hi[touched]
    if extra:
        if extra >> 128:
            return False
        e_lo = np.uint64(extra & ((1 << 64) - 1))
        e_hi = np.uint64(extra >> 64)
        nl = T_lo + e_lo
        carry = (nl < T_lo).astype(np.uint64)
        nh = T_hi + e_hi
        ov = nh < T_hi
        nh2 = nh + carry
        if (ov | (nh2 < nh)).any():
            return False
        T_lo, T_hi = nl, nh2
    for a, b in ((0, 1), (2, 3)):
        # pre pair = column a + column b (cannot overflow u128: the
        # engine's own overflow codes maintain the pair invariant —
        # checked anyway, a corrupt mirror must decline, not admit).
        pl = mirror_lo[touched, a] + mirror_lo[touched, b]
        cy = (pl < mirror_lo[touched, a]).astype(np.uint64)
        ph_p = mirror_hi[touched, a] + mirror_hi[touched, b]
        p_ov = ph_p < mirror_hi[touched, a]
        ph = ph_p + cy
        p_ov = p_ov | (ph < ph_p)
        if p_ov.any():
            return False
        sl = pl + T_lo
        s_cy = (sl < pl).astype(np.uint64)
        sh_p = ph + T_hi
        s_ov = sh_p < ph
        s_ov = s_ov | ((sh_p + s_cy) < sh_p)
        if s_ov.any():
            return False
    return True


# ---------------------------------------------------------------------------
# The wave step: the scan body over a (K,) event axis.
#
# Table access goes through a small ops seam so ONE step body serves
# both executors: dense (single device owns the whole (A, 8) table)
# and SPMD (each device owns a row slice of the NamedSharding-sharded
# table inside shard_map — see _sharded_fns).  Everything else in the
# step is event-axis work on replicated arrays, which every device
# computes identically, so the sharded executor's outputs are
# bit-identical to the dense one's by construction.


def _apply_add_sub(table, adds, subs, localize=None):
    """table + segment-summed adds - segment-summed subs, exact u128
    per (row, column) — the ONE copy of the carry/borrow arithmetic
    both table-ops share (the sharded executor's bit-identical
    guarantee depends on it staying single-source).  Each spec is
    (slots, cols, lo, hi, valid) with slots pre-clipped into the
    GLOBAL row range; `localize` maps a spec onto this table's rows
    (identity for the dense whole table)."""
    if localize is None:
        localize = lambda spec: spec  # noqa: E731
    A = table.shape[0]
    t_lo = table[:, 0::2]
    t_hi = table[:, 1::2]
    if adds is not None:
        d_lo, d_hi = _accum_u128(*localize(adds), A)
        n_lo = t_lo + d_lo
        cy = (n_lo < t_lo).astype(jnp.uint64)
        t_lo, t_hi = n_lo, t_hi + d_hi + cy
    if subs is not None:
        s_lo, s_hi = _accum_u128(*localize(subs), A)
        n_lo = t_lo - s_lo
        bw = (t_lo < s_lo).astype(jnp.uint64)
        t_lo, t_hi = n_lo, t_hi - s_hi - bw
    return jnp.stack(
        [t_lo[:, 0], t_hi[:, 0], t_lo[:, 1], t_hi[:, 1],
         t_lo[:, 2], t_hi[:, 2], t_lo[:, 3], t_hi[:, 3]],
        axis=-1,
    )


class _DenseTableOps:
    """Whole-table access: the single-device executor's row gathers
    and u128 segment-sum applies (the pre-seam code verbatim)."""

    @staticmethod
    def nrows(table) -> int:
        return table.shape[0]

    @staticmethod
    def rows(table, slots):
        """(K,) pre-clipped global row indices -> (K, 8) rows."""
        return table[slots]

    @staticmethod
    def apply(table, adds=None, subs=None):
        return _apply_add_sub(table, adds, subs)


class _ShardTableOps:
    """Row-slice access inside a shard_map body over the 1-D ("shard",)
    mesh: reads recombine each row from its single owner
    (sharded.gather_rows — all_gather over ICI + exact sum), writes
    scatter only onto locally-owned rows (no collective at all).  Both
    resolve ownership through sharded.own_rows — the one definition of
    the row layout — and reproduce the dense per-row arithmetic
    exactly: a gathered row IS the owner's row, and a local segment
    sum over the shard's slot range equals the dense sum restricted to
    those rows."""

    def __init__(self, total_rows: int, local_rows: int) -> None:
        self.total_rows = total_rows
        self.local_rows = local_rows

    def nrows(self, table) -> int:
        return self.total_rows

    def rows(self, table, slots):
        from tigerbeetle_tpu.parallel import sharded

        return sharded.gather_rows(table, slots, self.local_rows)

    def _localize(self, spec):
        from tigerbeetle_tpu.parallel import sharded

        slots, cols, lo, hi, valid = spec
        local, rel = sharded.own_rows(slots, self.local_rows)
        return rel, cols, lo, hi, valid & local

    def apply(self, table, adds=None, subs=None):
        return _apply_add_sub(table, adds, subs, localize=self._localize)


_DENSE_OPS = _DenseTableOps()


def _accum_u128(slots_c, cols, amt_lo, amt_hi, valid, A):
    """Exact per-(slot, column) u128 sums via 32-bit-piece scatter-adds
    (duplicate slots accumulate — the segment-sum analogue of
    kernel_fast._flush_impl's unique-scatter).  Piece sums stay below
    lanes * 2^32 < 2^64, so recombination with base-2^32 carries is
    exact.  Invalid lanes contribute zero (their slot may be clip
    garbage; zero is harmless anywhere)."""
    zero = jnp.uint64(0)
    lo = jnp.where(valid, amt_lo, zero)
    hi = jnp.where(valid, amt_hi, zero)
    pieces = [
        lo & _MASK32, lo >> jnp.uint64(32),
        hi & _MASK32, hi >> jnp.uint64(32),
    ]
    acc = [
        jnp.zeros((A, 4), jnp.uint64).at[slots_c, cols].add(p)
        for p in pieces
    ]
    c0, c1, c2, c3 = acc
    c1 = c1 + (c0 >> jnp.uint64(32))
    c2 = c2 + (c1 >> jnp.uint64(32))
    c3 = c3 + (c2 >> jnp.uint64(32))
    d_lo = (c0 & _MASK32) | ((c1 & _MASK32) << jnp.uint64(32))
    d_hi = (c2 & _MASK32) | ((c3 & _MASK32) << jnp.uint64(32))
    return d_lo, d_hi


def _wave_step_impl(carry, ev, n, ts_base, ops=_DENSE_OPS, commit_mask=None):
    """Apply one wave — K mutually independent events — as a single
    vectorized step against the segment carry.

    Line-for-line port of kernel.make_body's event body with the
    (K,) axis vectorized and chain/rollback logic dropped (the
    partitioner never places chain members in waves).  Independence
    guarantees every gather sees pre-wave state equal to its
    sequential value, and the admission precondition makes every ov_*
    term false, so results and records are bit-identical to the scan.

    `ops` is the table-access seam: dense (whole table) by default,
    shard-local inside the SPMD executor — the body itself never
    indexes `carry["balances"]` directly.

    `commit_mask` (speculative executor only) deactivates lanes whose
    events failed conflict validation: a masked lane applies nothing,
    scatters nothing, and leaves its result slot untouched — exactly
    "not yet executed", so the conflicted residue replays later
    against this carry.
    """
    table = carry["balances"]
    created = carry["created"]
    group_creator = carry["group_creator"]
    B = carry["results"].shape[0]
    A = ops.nrows(table)

    i = ev["i"]  # (K,) global indices; padding lanes carry i == B
    active = i < n
    if commit_mask is not None:
        active = active & commit_mask
    flags = ev["flags"]
    is_pv = (flags & (F_POST | F_VOID)) != 0
    ts_i = ts_base + i.astype(jnp.uint64)

    # No chain terms: wave events are never chain members, so the
    # scan's chain_open/chain_broken preconditions are identically 0.
    pre = _first_nonzero((ev["ts_nonzero"], R_TIMESTAMP_MUST_BE_ZERO))
    pre = jnp.where(pre == 0, ev["static_result"], pre)

    # -- Exists resolution via the in-batch id directory.
    e_creator = group_creator[jnp.clip(ev["id_group"], 0, B - 1)]
    e_inb = e_creator >= 0
    e_dur = ev["e_found"]
    e_any = e_inb | e_dur
    e = _merge(~e_inb, _gather_created(created, e_creator, B), ev, _E_FIELD_MAP)

    # ==================== normal create_transfer ====================
    dr_row = ops.rows(table, jnp.clip(ev["dr_slot"], 0, A - 1))
    cr_row = ops.rows(table, jnp.clip(ev["cr_slot"], 0, A - 1))
    dr_dp = (dr_row[:, DP_LO], dr_row[:, DP_HI])
    dr_dpo = (dr_row[:, DPO_LO], dr_row[:, DPO_HI])
    dr_cpo = (dr_row[:, CPO_LO], dr_row[:, CPO_HI])
    cr_dpo = (cr_row[:, DPO_LO], cr_row[:, DPO_HI])
    cr_cp = (cr_row[:, CP_LO], cr_row[:, CP_HI])
    cr_cpo = (cr_row[:, CPO_LO], cr_row[:, CPO_HI])

    exists_rn = _exists_ladder_normal(ev, e)

    is_balancing = (flags & (F_BAL_DR | F_BAL_CR)) != 0
    amount = (ev["amount_lo"], ev["amount_hi"])
    amount = w.select(
        is_balancing & w.is_zero(amount),
        (jnp.full_like(amount[0], U64_MAX), jnp.zeros_like(amount[1])),
        amount,
    )
    dr_balance, _ = w.add(dr_dpo, dr_dp)
    bd_avail = w.sub_sat(dr_cpo, dr_balance)
    amount = w.select((flags & F_BAL_DR) != 0, w.minimum(amount, bd_avail), amount)
    bd_fail = ((flags & F_BAL_DR) != 0) & w.is_zero(amount)

    cr_balance, _ = w.add(cr_cpo, cr_cp)
    bc_avail = w.sub_sat(cr_dpo, cr_balance)
    amount_bc = w.minimum(amount, bc_avail)
    amount = w.select(((flags & F_BAL_CR) != 0) & ~bd_fail, amount_bc, amount)
    bc_fail = ((flags & F_BAL_CR) != 0) & w.is_zero(amount) & ~bd_fail

    is_pending = (flags & F_PENDING) != 0
    _, ov_dp = w.add(amount, dr_dp)
    _, ov_cp = w.add(amount, cr_cp)
    _, ov_dpo = w.add(amount, dr_dpo)
    _, ov_cpo = w.add(amount, cr_cpo)
    dr_total, _ = w.add(dr_dp, dr_dpo)
    _, ov_debits = w.add(amount, dr_total)
    cr_total, _ = w.add(cr_cp, cr_cpo)
    _, ov_credits = w.add(amount, cr_total)

    timeout_ns = ev["timeout"] * NS_PER_S
    ts_plus = ts_i + timeout_ns
    ov_timeout = ts_plus < ts_i

    dr_lhs, _ = w.add(dr_total, amount)
    exceeds_cr = ((ev["dr_flags"] & AF_DR_LIMIT) != 0) & w.gt(dr_lhs, dr_cpo)
    cr_lhs, _ = w.add(cr_total, amount)
    exceeds_dr = ((ev["cr_flags"] & AF_CR_LIMIT) != 0) & w.gt(cr_lhs, cr_dpo)

    rn = _first_nonzero(
        (e_any, _EXISTS_SENTINEL),
        (bd_fail, R_EXCEEDS_CREDITS),
        (bc_fail, R_EXCEEDS_DEBITS),
        (is_pending & ov_dp, R_OVERFLOWS_DP),
        (is_pending & ov_cp, R_OVERFLOWS_CP),
        (ov_dpo, R_OVERFLOWS_DPO),
        (ov_cpo, R_OVERFLOWS_CPO),
        (ov_debits, R_OVERFLOWS_DEBITS),
        (ov_credits, R_OVERFLOWS_CREDITS),
        (ov_timeout, R_OVERFLOWS_TIMEOUT),
        (exceeds_cr, R_EXCEEDS_CREDITS),
        (exceeds_dr, R_EXCEEDS_DEBITS),
    )
    rn = jnp.where(rn == _EXISTS_SENTINEL, exists_rn, rn)

    # ==================== post/void pending transfer ====================
    p_creator = group_creator[jnp.clip(ev["p_group"], 0, B - 1)]
    p_inb = (ev["p_group"] >= 0) & (p_creator >= 0)
    p_dur = ev["p_found"]
    p_any = p_dur | p_inb
    p = _merge(p_dur, _gather_created(created, p_creator, B), ev, _P_FIELD_MAP)
    p_timestamp = jnp.where(
        p_dur,
        ev["p_timestamp"],
        ts_base + jnp.clip(p_creator, 0, B - 1).astype(jnp.uint64),
    )
    p_amount = (p["amount_lo"], p["amount_hi"])

    pv_amount_raw = (ev["amount_lo"], ev["amount_hi"])
    pv_amount = w.select(w.is_zero(pv_amount_raw), p_amount, pv_amount_raw)
    is_void = (flags & F_VOID) != 0

    exists_rp = _exists_ladder_post_void(ev, e, p)

    st = jnp.where(
        p_dur,
        carry["dstat"][jnp.clip(ev["p_tgt"], 0, B - 1)],
        carry["inb_status"][jnp.clip(p_creator, 0, B - 1)],
    )

    rp_pre_insert = _first_nonzero(
        (~p_any, R_PENDING_NOT_FOUND),
        ((p["flags"] & F_PENDING) == 0, R_PENDING_NOT_PENDING),
        (~ev["dr_id_zero"] & (ev["dr_slot"] != p["dr_slot"]), R_PENDING_DIFF_DR),
        (~ev["cr_id_zero"] & (ev["cr_slot"] != p["cr_slot"]), R_PENDING_DIFF_CR),
        ((ev["ledger"] > 0) & (ev["ledger"] != p["ledger"]), R_PENDING_DIFF_LEDGER),
        ((ev["code"] > 0) & (ev["code"] != p["code"]), R_PENDING_DIFF_CODE),
        (w.gt(pv_amount, p_amount), R_EXCEEDS_PENDING_AMOUNT),
        (is_void & w.lt(pv_amount, p_amount), R_PENDING_DIFF_AMOUNT),
        (e_any, _EXISTS_SENTINEL),
        (st == S_POSTED, R_ALREADY_POSTED),
        (st == S_VOIDED, R_ALREADY_VOIDED),
        (st == kernel.S_EXPIRED, R_PENDING_EXPIRED),
    )
    rp_pre_insert = jnp.where(
        rp_pre_insert == _EXISTS_SENTINEL, exists_rp, rp_pre_insert
    )

    p_expires = p_timestamp + p["timeout"] * NS_PER_S
    overdue = (p["timeout"] > 0) & (p_expires <= ts_i)
    rp = jnp.where((rp_pre_insert == 0) & overdue, R_PENDING_EXPIRED, rp_pre_insert)

    # ==================== merge & apply ====================
    dyn_r = jnp.where(is_pv, rp, rn)
    gate = active & (pre == 0)
    r = jnp.where(gate, dyn_r, jnp.where(active, pre, 0))

    pv_inserted = gate & is_pv & (rp_pre_insert == 0)
    normal_applied = gate & ~is_pv & (rn == 0)
    pv_applied = gate & is_pv & (rp == 0)
    inserted = pv_inserted | normal_applied
    applied = pv_applied | normal_applied

    ud128_inherit = is_pv & (ev["ud128_lo"] == 0) & (ev["ud128_hi"] == 0)
    rec = {
        "flags": flags,
        "dr_slot": jnp.where(is_pv, p["dr_slot"], ev["dr_slot"]),
        "cr_slot": jnp.where(is_pv, p["cr_slot"], ev["cr_slot"]),
        "amount_lo": jnp.where(is_pv, pv_amount[0], amount[0]),
        "amount_hi": jnp.where(is_pv, pv_amount[1], amount[1]),
        "pending_lo": ev["pending_lo"],
        "pending_hi": ev["pending_hi"],
        "ud128_lo": jnp.where(ud128_inherit, p["ud128_lo"], ev["ud128_lo"]),
        "ud128_hi": jnp.where(ud128_inherit, p["ud128_hi"], ev["ud128_hi"]),
        "ud64": jnp.where(is_pv & (ev["ud64"] == 0), p["ud64"], ev["ud64"]),
        "ud32": jnp.where(is_pv & (ev["ud32"] == 0), p["ud32"], ev["ud32"]),
        "timeout": jnp.where(is_pv, jnp.uint64(0), ev["timeout"]),
        "ledger": jnp.where(is_pv, p["ledger"], ev["ledger"]),
        "code": jnp.where(is_pv, p["code"], ev["code"]),
    }

    # -- Balance effects as commuting u128 deltas, segment-summed.
    up_dr_slot = jnp.where(is_pv, p["dr_slot"], ev["dr_slot"])
    up_cr_slot = jnp.where(is_pv, p["cr_slot"], ev["cr_slot"])
    safe_dr = jnp.clip(up_dr_slot, 0, A - 1)
    safe_cr = jnp.clip(up_cr_slot, 0, A - 1)

    is_post = (flags & F_POST) != 0
    zi = jnp.zeros_like(i)
    # Add lanes: normal dr (dp|dpo), normal cr (cp|cpo), post dr dpo,
    # post cr cpo.  Sub lanes: pv release dr dp, pv release cr cp.
    add_slots = jnp.concatenate([safe_dr, safe_cr, safe_dr, safe_cr])
    add_cols = jnp.concatenate(
        [
            jnp.where(is_pending, zi, zi + 1),
            jnp.where(is_pending, zi + 2, zi + 3),
            zi + 1,
            zi + 3,
        ]
    )
    add_lo = jnp.concatenate([amount[0], amount[0], pv_amount[0], pv_amount[0]])
    add_hi = jnp.concatenate([amount[1], amount[1], pv_amount[1], pv_amount[1]])
    post_ap = pv_applied & is_post
    add_valid = jnp.concatenate(
        [normal_applied, normal_applied, post_ap, post_ap]
    )
    sub_slots = jnp.concatenate([safe_dr, safe_cr])
    sub_cols = jnp.concatenate([zi, zi + 2])
    sub_lo = jnp.concatenate([p_amount[0], p_amount[0]])
    sub_hi = jnp.concatenate([p_amount[1], p_amount[1]])
    sub_valid = jnp.concatenate([pv_applied, pv_applied])

    table = ops.apply(
        table,
        adds=(add_slots, add_cols, add_lo, add_hi, add_valid),
        subs=(sub_slots, sub_cols, sub_lo, sub_hi, sub_valid),
    )

    # -- Per-event post-apply snapshots (pre-wave row + own deltas).
    # They may miss wave-mates' commuting deltas to the same slot, but
    # wave events' snapshots only feed the mirror and are rewritten
    # with batch finals at finalize (history-account events, whose
    # snapshots are semantically read, never ride waves).
    o_dr = ops.rows(carry["balances"], safe_dr)
    o_cr = ops.rows(carry["balances"], safe_cr)
    o_dr_dp = (o_dr[:, DP_LO], o_dr[:, DP_HI])
    o_dr_dpo = (o_dr[:, DPO_LO], o_dr[:, DPO_HI])
    o_cr_cp = (o_cr[:, CP_LO], o_cr[:, CP_HI])
    o_cr_cpo = (o_cr[:, CPO_LO], o_cr[:, CPO_HI])
    n_dr_dp = w.select(
        is_pv,
        w.sub(o_dr_dp, p_amount)[0],
        w.select(is_pending, w.add(o_dr_dp, amount)[0], o_dr_dp),
    )
    n_dr_dpo = w.select(
        is_pv,
        w.select(is_post, w.add(o_dr_dpo, pv_amount)[0], o_dr_dpo),
        w.select(is_pending, o_dr_dpo, w.add(o_dr_dpo, amount)[0]),
    )
    n_cr_cp = w.select(
        is_pv,
        w.sub(o_cr_cp, p_amount)[0],
        w.select(is_pending, w.add(o_cr_cp, amount)[0], o_cr_cp),
    )
    n_cr_cpo = w.select(
        is_pv,
        w.select(is_post, w.add(o_cr_cpo, pv_amount)[0], o_cr_cpo),
        w.select(is_pending, o_cr_cpo, w.add(o_cr_cpo, amount)[0]),
    )
    new_dr_row = jnp.stack(
        [n_dr_dp[0], n_dr_dp[1], n_dr_dpo[0], n_dr_dpo[1],
         o_dr[:, CP_LO], o_dr[:, CP_HI], o_dr[:, CPO_LO], o_dr[:, CPO_HI]],
        axis=-1,
    )
    new_cr_row = jnp.stack(
        [o_cr[:, DP_LO], o_cr[:, DP_HI], o_cr[:, DPO_LO], o_cr[:, DPO_HI],
         n_cr_cp[0], n_cr_cp[1], n_cr_cpo[0], n_cr_cpo[1]],
        axis=-1,
    )

    # -- Scatter per-event state at own (unique) global indices; OOB
    # padding lanes drop.
    idx_i = jnp.where(active, i, B)
    idx_ins = jnp.where(inserted, i, B)
    created = {
        f: created[f]
        .at[idx_ins]
        .set(rec[f].astype(created[f].dtype), mode="drop")
        for f in CREATED_FIELDS
    }
    created_mask = carry["created_mask"].at[idx_i].set(inserted, mode="drop")
    gidx = jnp.where(inserted, jnp.clip(ev["id_group"], 0, B - 1), B)
    group_creator = group_creator.at[gidx].set(i, mode="drop")

    inb_status = carry["inb_status"].at[idx_i].set(
        jnp.where(normal_applied & is_pending, jnp.uint32(S_PENDING), 0),
        mode="drop",
    )
    new_status = jnp.where(is_post, jnp.uint32(S_POSTED), jnp.uint32(S_VOIDED))
    idx_t = jnp.where(pv_applied & p_dur, jnp.clip(ev["p_tgt"], 0, B - 1), B)
    dstat = carry["dstat"].at[idx_t].set(new_status, mode="drop")
    idx_pc = jnp.where(pv_applied & ~p_dur, jnp.clip(p_creator, 0, B - 1), B)
    inb_status = inb_status.at[idx_pc].set(new_status, mode="drop")

    hist_dr = carry["hist_dr"].at[idx_i].set(new_dr_row, mode="drop")
    hist_cr = carry["hist_cr"].at[idx_i].set(new_cr_row, mode="drop")
    results = carry["results"].at[idx_i].set(r, mode="drop")

    last_applied = jnp.maximum(
        carry["last_applied"], jnp.where(applied, i, -1).max()
    )
    pulse_create = carry["pulse_create"].at[idx_i].set(
        jnp.where(
            normal_applied & is_pending & (ev["timeout"] > 0),
            ts_i + timeout_ns,
            jnp.uint64(0),
        ),
        mode="drop",
    )
    pulse_remove = carry["pulse_remove"].at[idx_i].set(
        jnp.where(pv_applied & (p["timeout"] > 0), p_expires, jnp.uint64(0)),
        mode="drop",
    )

    return dict(
        carry,
        balances=table,
        results=results,
        created_mask=created_mask,
        created=created,
        group_creator=group_creator,
        inb_status=inb_status,
        dstat=dstat,
        hist_dr=hist_dr,
        hist_cr=hist_cr,
        last_applied=last_applied,
        pulse_create=pulse_create,
        pulse_remove=pulse_remove,
    )


_wave_step = jax.jit(_wave_step_impl, donate_argnums=(0,))
# Non-donating twin for the device engine's window launch: the engine
# passes its AUTHORITATIVE table handle into the executor and must be
# able to retry the whole batch from that same handle after a
# transient link fault — donation would invalidate it mid-flight.
_wave_step_keep = jax.jit(_wave_step_impl)


# ---------------------------------------------------------------------------
# Chain-wave step: a contiguous run of mutually independent linked
# chains executed as ONE lax.scan over chain POSITION — step p applies
# the p-th member of every chain as a vectorized lane batch (the
# device linked kernel's fixpoint shape), so a chain-dominated region
# costs ~max_chain_len device steps instead of one per member.


def _chain_wave_impl(carry, ev, n, ts_base, ops=_DENSE_OPS):
    """Execute one "chains" segment against the segment carry.

    `ev` is a dict of (P, C) stacked event arrays — position-major,
    one lane per chain, padding lanes carrying i == B — plus a
    ``chain_open`` bool plane (linked flag on the batch's last event).
    Admission (waves._chain_wave_steps) guarantees: plain creates only
    (no post/void), no history accounts, id-groups claimed exactly
    once batch-wide, and pairwise chain independence over balance
    slots — so each lane's gathers see exactly its own chain's prior
    effects plus commuting cross-chain adds to UNREAD slots, and the
    per-position body below (the _wave_step normal-create path plus
    the scan's chain machinery) reproduces the sequential scan's
    results bit-for-bit.  Chain failure semantics match make_body:
    the failing member keeps its own code, every other member reports
    linked_event_failed (chain_open on an open tail), applied members'
    balance effects are rolled back by an exact trailing subtraction,
    and — like the reference's unscoped pulse bookkeeping —
    pulse_create signals recorded at apply time survive the rollback
    while created_mask/inb_status/group_creator registrations do not.
    """
    B = carry["results"].shape[0]
    A = ops.nrows(carry["balances"])
    C = ev["i"].shape[1]

    def step(state, ev_p):
        cr, alive = state
        table = cr["balances"]
        created = cr["created"]
        group_creator = cr["group_creator"]
        i = ev_p["i"]
        active = i < n
        flags = ev_p["flags"]
        ts_i = ts_base + i.astype(jnp.uint64)

        pre = _first_nonzero(
            (ev_p["chain_open"], kernel.R_LINKED_EVENT_CHAIN_OPEN),
            (~alive, kernel.R_LINKED_EVENT_FAILED),
            (ev_p["ts_nonzero"], R_TIMESTAMP_MUST_BE_ZERO),
        )
        pre = jnp.where(pre == 0, ev_p["static_result"], pre)

        # Exists: id-groups are claimed exactly once batch-wide, so
        # only the durable duplicate can exist — no in-batch creator.
        e_any = ev_p["e_found"]
        e = {
            f: ev_p[nm].astype(created[f].dtype)
            for f, nm in _E_FIELD_MAP.items()
        }
        exists_rn = _exists_ladder_normal(ev_p, e)

        dr_row = ops.rows(table, jnp.clip(ev_p["dr_slot"], 0, A - 1))
        cr_row = ops.rows(table, jnp.clip(ev_p["cr_slot"], 0, A - 1))
        dr_dp = (dr_row[:, DP_LO], dr_row[:, DP_HI])
        dr_dpo = (dr_row[:, DPO_LO], dr_row[:, DPO_HI])
        dr_cpo = (dr_row[:, CPO_LO], dr_row[:, CPO_HI])
        cr_dpo = (cr_row[:, DPO_LO], cr_row[:, DPO_HI])
        cr_cp = (cr_row[:, CP_LO], cr_row[:, CP_HI])
        cr_cpo = (cr_row[:, CPO_LO], cr_row[:, CPO_HI])

        is_balancing = (flags & (F_BAL_DR | F_BAL_CR)) != 0
        amount = (ev_p["amount_lo"], ev_p["amount_hi"])
        amount = w.select(
            is_balancing & w.is_zero(amount),
            (jnp.full_like(amount[0], U64_MAX), jnp.zeros_like(amount[1])),
            amount,
        )
        dr_balance, _ = w.add(dr_dpo, dr_dp)
        bd_avail = w.sub_sat(dr_cpo, dr_balance)
        amount = w.select(
            (flags & F_BAL_DR) != 0, w.minimum(amount, bd_avail), amount
        )
        bd_fail = ((flags & F_BAL_DR) != 0) & w.is_zero(amount)
        cr_balance, _ = w.add(cr_cpo, cr_cp)
        bc_avail = w.sub_sat(cr_dpo, cr_balance)
        amount_bc = w.minimum(amount, bc_avail)
        amount = w.select(
            ((flags & F_BAL_CR) != 0) & ~bd_fail, amount_bc, amount
        )
        bc_fail = ((flags & F_BAL_CR) != 0) & w.is_zero(amount) & ~bd_fail

        is_pending = (flags & F_PENDING) != 0
        _, ov_dp = w.add(amount, dr_dp)
        _, ov_cp = w.add(amount, cr_cp)
        _, ov_dpo = w.add(amount, dr_dpo)
        _, ov_cpo = w.add(amount, cr_cpo)
        dr_total, _ = w.add(dr_dp, dr_dpo)
        _, ov_debits = w.add(amount, dr_total)
        cr_total, _ = w.add(cr_cp, cr_cpo)
        _, ov_credits = w.add(amount, cr_total)
        timeout_ns = ev_p["timeout"] * NS_PER_S
        ts_plus = ts_i + timeout_ns
        ov_timeout = ts_plus < ts_i
        dr_lhs, _ = w.add(dr_total, amount)
        exceeds_cr = ((ev_p["dr_flags"] & AF_DR_LIMIT) != 0) & w.gt(
            dr_lhs, dr_cpo
        )
        cr_lhs, _ = w.add(cr_total, amount)
        exceeds_dr = ((ev_p["cr_flags"] & AF_CR_LIMIT) != 0) & w.gt(
            cr_lhs, cr_dpo
        )

        rn = _first_nonzero(
            (e_any, _EXISTS_SENTINEL),
            (bd_fail, R_EXCEEDS_CREDITS),
            (bc_fail, R_EXCEEDS_DEBITS),
            (is_pending & ov_dp, R_OVERFLOWS_DP),
            (is_pending & ov_cp, R_OVERFLOWS_CP),
            (ov_dpo, R_OVERFLOWS_DPO),
            (ov_cpo, R_OVERFLOWS_CPO),
            (ov_debits, R_OVERFLOWS_DEBITS),
            (ov_credits, R_OVERFLOWS_CREDITS),
            (ov_timeout, R_OVERFLOWS_TIMEOUT),
            (exceeds_cr, R_EXCEEDS_CREDITS),
            (exceeds_dr, R_EXCEEDS_DEBITS),
        )
        rn = jnp.where(rn == _EXISTS_SENTINEL, exists_rn, rn)

        gate = active & (pre == 0)
        r = jnp.where(gate, rn, jnp.where(active, pre, 0))
        applied = gate & (rn == 0)
        fail = active & alive & (r != 0)
        alive = alive & ~fail

        # -- Balance adds (segment-summed; pairwise independence makes
        # same-slot duplicates commuting cross-chain adds).
        safe_dr = jnp.clip(ev_p["dr_slot"], 0, A - 1)
        safe_cr = jnp.clip(ev_p["cr_slot"], 0, A - 1)
        zi = jnp.zeros_like(i)
        add_slots = jnp.concatenate([safe_dr, safe_cr])
        add_cols = jnp.concatenate(
            [
                jnp.where(is_pending, zi, zi + 1),
                jnp.where(is_pending, zi + 2, zi + 3),
            ]
        )
        add_lo = jnp.concatenate([amount[0]] * 2)
        add_hi = jnp.concatenate([amount[1]] * 2)
        valid = jnp.concatenate([applied, applied])
        new_table = ops.apply(
            table, adds=(add_slots, add_cols, add_lo, add_hi, valid)
        )

        # -- Snapshots (pre-row + own delta; rewritten to batch finals
        # at finalize for surviving members, unused for failed ones).
        n_dr_dp = w.select(is_pending, w.add(dr_dp, amount)[0], dr_dp)
        n_dr_dpo = w.select(is_pending, dr_dpo, w.add(dr_dpo, amount)[0])
        n_cr_cp = w.select(is_pending, w.add(cr_cp, amount)[0], cr_cp)
        n_cr_cpo = w.select(is_pending, cr_cpo, w.add(cr_cpo, amount)[0])
        new_dr_row = jnp.stack(
            [n_dr_dp[0], n_dr_dp[1], n_dr_dpo[0], n_dr_dpo[1],
             dr_row[:, CP_LO], dr_row[:, CP_HI],
             dr_row[:, CPO_LO], dr_row[:, CPO_HI]],
            axis=-1,
        )
        new_cr_row = jnp.stack(
            [cr_row[:, DP_LO], cr_row[:, DP_HI],
             cr_row[:, DPO_LO], cr_row[:, DPO_HI],
             n_cr_cp[0], n_cr_cp[1], n_cr_cpo[0], n_cr_cpo[1]],
            axis=-1,
        )

        rec = {
            "flags": flags,
            "dr_slot": ev_p["dr_slot"],
            "cr_slot": ev_p["cr_slot"],
            "amount_lo": amount[0],
            "amount_hi": amount[1],
            "pending_lo": ev_p["pending_lo"],
            "pending_hi": ev_p["pending_hi"],
            "ud128_lo": ev_p["ud128_lo"],
            "ud128_hi": ev_p["ud128_hi"],
            "ud64": ev_p["ud64"],
            "ud32": ev_p["ud32"],
            "timeout": ev_p["timeout"],
            "ledger": ev_p["ledger"],
            "code": ev_p["code"],
        }
        idx_i = jnp.where(active, i, B)
        idx_ins = jnp.where(applied, i, B)
        created = {
            f: created[f]
            .at[idx_ins]
            .set(rec[f].astype(created[f].dtype), mode="drop")
            for f in CREATED_FIELDS
        }
        created_mask = cr["created_mask"].at[idx_i].set(applied, mode="drop")
        gidx = jnp.where(applied, jnp.clip(ev_p["id_group"], 0, B - 1), B)
        group_creator = group_creator.at[gidx].set(i, mode="drop")
        inb_status = cr["inb_status"].at[idx_i].set(
            jnp.where(applied & is_pending, jnp.uint32(S_PENDING), 0),
            mode="drop",
        )
        hist_dr = cr["hist_dr"].at[idx_i].set(new_dr_row, mode="drop")
        hist_cr = cr["hist_cr"].at[idx_i].set(new_cr_row, mode="drop")
        results = cr["results"].at[idx_i].set(r, mode="drop")
        last_applied = jnp.maximum(
            cr["last_applied"], jnp.where(applied, i, -1).max()
        )
        pulse_create = cr["pulse_create"].at[idx_i].set(
            jnp.where(
                applied & is_pending & (ev_p["timeout"] > 0),
                ts_i + timeout_ns,
                jnp.uint64(0),
            ),
            mode="drop",
        )

        cr = dict(
            cr,
            balances=new_table,
            results=results,
            created_mask=created_mask,
            created=created,
            group_creator=group_creator,
            inb_status=inb_status,
            hist_dr=hist_dr,
            hist_cr=hist_cr,
            last_applied=last_applied,
            pulse_create=pulse_create,
        )
        ys = (
            i, r, applied, safe_dr, safe_cr,
            amount[0], amount[1], is_pending,
            jnp.clip(ev_p["id_group"], 0, B - 1),
        )
        return (cr, alive), ys

    alive0 = jnp.ones(C, bool)
    (carry, alive), ys = jax.lax.scan(step, (carry, alive0), ev)
    (ys_i, ys_r, ys_ap, ys_dr, ys_cr,
     ys_alo, ys_ahi, ys_pend, ys_g) = ys

    # -- Chain-failure repair: exact rollback subtraction of every
    # applied member of a failed chain, result/registration rewrite.
    dead = ~alive
    rb = ys_ap & dead[None, :]
    flat = lambda a: a.reshape(-1)  # noqa: E731
    zi = jnp.zeros_like(flat(ys_i))
    sub_slots = jnp.concatenate([flat(ys_dr), flat(ys_cr)])
    pend_f = flat(ys_pend)
    sub_cols = jnp.concatenate(
        [jnp.where(pend_f, zi, zi + 1), jnp.where(pend_f, zi + 2, zi + 3)]
    )
    sub_lo = jnp.concatenate([flat(ys_alo)] * 2)
    sub_hi = jnp.concatenate([flat(ys_ahi)] * 2)
    sub_valid = jnp.concatenate([flat(rb)] * 2)
    table = ops.apply(
        carry["balances"],
        subs=(sub_slots, sub_cols, sub_lo, sub_hi, sub_valid),
    )
    fix = (ys_r == 0) & dead[None, :] & (ys_i < n)
    idxf = jnp.where(fix, ys_i, B).reshape(-1)
    results = carry["results"].at[idxf].set(
        jnp.uint32(kernel.R_LINKED_EVENT_FAILED), mode="drop"
    )
    created_mask = carry["created_mask"].at[idxf].set(False, mode="drop")
    inb_status = carry["inb_status"].at[idxf].set(
        jnp.uint32(0), mode="drop"
    )
    gidxf = jnp.where(fix, ys_g, B).reshape(-1)
    group_creator = carry["group_creator"].at[gidxf].set(
        jnp.int32(-1), mode="drop"
    )
    return dict(
        carry,
        balances=table,
        results=results,
        created_mask=created_mask,
        inb_status=inb_status,
        group_creator=group_creator,
    )


_chain_step = jax.jit(_chain_wave_impl, donate_argnums=(0,))
_chain_step_keep = jax.jit(_chain_wave_impl)


@functools.partial(jax.jit, donate_argnums=(0,))
def _init_carry(balances, dstat_init):
    return kernel.make_carry(balances, dstat_init, dstat_init.shape[0])


@jax.jit
def _init_carry_keep(balances, dstat_init):
    return kernel.make_carry(balances, dstat_init, dstat_init.shape[0])


def _finalize_body(carry, hist_fix, ops=_DENSE_OPS):
    """Pack outputs; rewrite wave events' balance snapshots with the
    BATCH-FINAL rows of their touched slots so the host's last-write-
    wins mirror reconstruction lands on exact finals (a wave event's
    own snapshot misses wave-mates' commuting deltas to the same slot,
    and a chain-wave member cross-chain commuting adds).  `hist_fix`
    is the wave mask (wave + chain-wave events): scan-segment events
    keep their sequential snapshots — history-account events always
    run there, so the history groove only ever sees sequential-exact
    rows."""
    table = carry["balances"]
    A = ops.nrows(table)
    fix = hist_fix & (carry["results"] == 0)
    dr = jnp.clip(carry["created"]["dr_slot"], 0, A - 1)
    cr = jnp.clip(carry["created"]["cr_slot"], 0, A - 1)
    hist_dr = jnp.where(fix[:, None], ops.rows(table, dr), carry["hist_dr"])
    hist_cr = jnp.where(fix[:, None], ops.rows(table, cr), carry["hist_cr"])
    return kernel.finalize_outputs(
        dict(carry, hist_dr=hist_dr, hist_cr=hist_cr)
    )


_finalize_impl = jax.jit(_finalize_body, donate_argnums=(0,))
_finalize_keep = jax.jit(_finalize_body)


# ---------------------------------------------------------------------------
# SPMD executors: the SAME step bodies run inside shard_map over the
# device engine's 1-D ("shard",) row mesh, so a row-sharded multi-chip
# engine executes wave plans in place instead of declining to the host
# drain.  The balance table stays a NamedSharding row slice per device
# end to end; per-step cross-shard row reads recombine over ICI
# (sharded.gather_rows), scatters land only on locally-owned rows, and
# every event-axis output (results, records, snapshots, packed matrix)
# is computed replicated — identically on every device — so admission
# and packed outputs agree across the mesh by determinism, and the
# whole pipeline is bit-identical to the dense executor (enforced by
# the sharded differential fuzz in tests/test_device_waves.py).


def plan_shardable(plan: WavePlan) -> bool:
    """True when every segment has an SPMD executor: "wave" and
    "chains" do; "scan" segments (kernel.make_body's sequential
    machinery) keep single-device scope — a sharded engine declines
    such plans gracefully and drains to the host instead."""
    return all(kind in ("wave", "chains") for kind, _ in plan.segments)


@jax.jit
def _make_rest(dstat_init):
    """The segment carry MINUS the balance table (which the sharded
    executors thread separately, under its own partition spec)."""
    carry = kernel.make_carry(
        jnp.zeros((1, 8), jnp.uint64), dstat_init, dstat_init.shape[0]
    )
    carry.pop("balances")
    return carry


_SHARDED_FNS: dict = {}


def _sharded_fns(mesh, total_rows: int):
    """(wave, chain, finalize) shard_map-wrapped jits for one
    (mesh, table geometry) — cached: the wrappers are shape-polymorphic
    via jit retracing, but the mesh closure is fixed."""
    key = (mesh, total_rows)
    hit = _SHARDED_FNS.get(key)
    if hit is not None:
        return hit
    from jax.sharding import PartitionSpec as P

    from tigerbeetle_tpu.parallel import sharded
    from tigerbeetle_tpu.parallel.sharded import shard_map

    n_shard = mesh.shape["shard"]
    assert total_rows % n_shard == 0, (total_rows, n_shard)
    ops = _ShardTableOps(total_rows, total_rows // n_shard)
    kw = sharded.shard_map_kwargs()
    t_spec = P("shard", None)

    def wave_body(table, rest, ev, n, ts_base):
        out = _wave_step_impl(
            dict(rest, balances=table), ev, n, ts_base, ops=ops
        )
        return out.pop("balances"), out

    def chain_body(table, rest, ev, n, ts_base):
        out = _chain_wave_impl(
            dict(rest, balances=table), ev, n, ts_base, ops=ops
        )
        return out.pop("balances"), out

    def fin_body(table, rest, hist_fix):
        return _finalize_body(
            dict(rest, balances=table), hist_fix, ops=ops
        )

    def wrap(body, n_rep_args):
        return jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(t_spec,) + (P(),) * n_rep_args,
                out_specs=(t_spec, P()),
                **kw,
            )
        )

    fns = (wrap(wave_body, 4), wrap(chain_body, 4), wrap(fin_body, 2))
    _SHARDED_FNS[key] = fns
    return fns


def _execute_plan_sharded(
    balances, ev: dict, dstat_init, n: int, ts_base: int, plan: WavePlan,
    hist_fix: np.ndarray, mesh,
):
    """Segment loop over the SPMD executors; the caller proved
    plan_shardable(plan).  Never donates — the engine retries from the
    same authoritative handle after transient link faults, exactly
    like the dense engine path."""
    B = ev["flags"].shape[0]
    wave, chain, fin = _sharded_fns(mesh, balances.shape[0])
    rest = _make_rest(jnp.asarray(np.asarray(dstat_init), jnp.uint32))
    table = balances
    n_j = jnp.int32(n)
    ts_j = jnp.uint64(ts_base)
    for k, (seg_kind, idx) in enumerate(plan.segments):
        if seg_kind == "chains":
            ev_seg = _gather_chain_events(
                ev, idx, plan.chain_steps[k], n, B
            )
            table, rest = chain(table, rest, ev_seg, n_j, ts_j)
            continue
        assert seg_kind == "wave", (
            "scan segments have no SPMD executor (plan_shardable)"
        )
        K = _bucket(len(idx))
        ev_seg = _gather_events(ev, idx, K, B)
        table, rest = wave(table, rest, ev_seg, n_j, ts_j)
    return fin(table, rest, jnp.asarray(hist_fix))


def _bucket(k: int) -> int:
    for b in _SEG_BUCKETS:
        if b >= k:
            return b
    return k


def _bucket_positions(p: int) -> int:
    """Chain-wave position bucket (compile cache key): the next power
    of two >= max chain length, floored at 8 — padding positions carry
    inactive lanes, so a coarse bucket costs compute, not correctness,
    and keeps the (P, C) compile-cache tractable."""
    b = 8
    while b < p:
        b *= 2
    return b


def _gather_events(ev: dict, idx: np.ndarray, K: int, B: int) -> dict:
    """Padded (K,) device gather of the host event arrays at batch
    indices `idx` (ascending, possibly non-contiguous for waves);
    padding lanes get i == B (inactive, and every per-event scatter
    drops OOB)."""
    k = len(idx)
    out = {}
    for name, arr in ev.items():
        buf = np.zeros(K, arr.dtype)
        buf[:k] = arr[idx]
        if name == "i":
            buf[k:] = B
        out[name] = jnp.asarray(buf)
    return out


# Event fields the chain-wave step consumes (the post/void join
# columns never ride a "chains" segment — smaller stacked xs).
_CHAIN_EV_FIELDS = (
    "i", "flags", "ts_nonzero", "static_result",
    "amount_lo", "amount_hi", "pending_lo", "pending_hi",
    "ud128_lo", "ud128_hi", "ud64", "ud32", "timeout", "ledger", "code",
    "dr_slot", "cr_slot", "dr_flags", "cr_flags", "id_group",
    "e_found", "e_flags", "e_dr_slot", "e_cr_slot",
    "e_amount_lo", "e_amount_hi", "e_pending_lo", "e_pending_hi",
    "e_ud128_lo", "e_ud128_hi", "e_ud64", "e_ud32", "e_timeout",
    "e_code",
)


def _gather_chain_events(
    ev: dict, idx: np.ndarray, P: int, n: int, B: int
) -> dict:
    """Stack a chain run's events position-major: (P, C) planes, one
    lane per chain, padding cells carrying i == B (inactive).  Chain
    boundaries re-derive from the linked flags, so the executor and
    the partitioner can never disagree on the layout."""
    flags = ev["flags"][idx]
    linked = (flags & F_LINKED) != 0
    m = len(idx)
    starts = np.empty(m, bool)
    starts[0] = True
    starts[1:] = ~linked[:-1]
    chain_rel = np.cumsum(starts) - 1
    pos = np.arange(m) - np.flatnonzero(starts)[chain_rel]
    C = _bucket(int(chain_rel[-1]) + 1)
    assert int(pos.max()) < P, "chain run exceeds its position bucket"
    mat = np.full((P, C), B, np.int64)
    mat[pos, chain_rel] = idx
    out = {}
    for name in _CHAIN_EV_FIELDS:
        arr = ev[name]
        if name == "i":
            out[name] = jnp.asarray(mat.astype(np.int32))
            continue
        src = np.concatenate([arr, np.zeros(1, arr.dtype)])
        out[name] = jnp.asarray(src[np.minimum(mat, len(arr))])
    open_np = np.zeros((P, C), bool)
    open_np[pos, chain_rel] = linked & (idx == n - 1)
    out["chain_open"] = jnp.asarray(open_np)
    return out


def _execute_plan(
    balances, ev: dict, dstat_init, n: int, ts_base: int, plan: WavePlan,
    hist_fix: np.ndarray, donate: bool,
):
    """Run a batch by the plan's segments in order; returns
    (new_balances, packed outputs) — identical contract to
    kernel.run_create_transfers."""
    B = ev["flags"].shape[0]
    init = _init_carry if donate else _init_carry_keep
    step = _wave_step if donate else _wave_step_keep
    chain = _chain_step if donate else _chain_step_keep
    scan = kernel.scan_segment if donate else kernel.scan_segment_keep
    fin = _finalize_impl if donate else _finalize_keep
    carry = init(balances, jnp.asarray(np.asarray(dstat_init), jnp.uint32))
    id_group_full = jnp.asarray(ev["id_group"])
    n_j = jnp.int32(n)
    ts_j = jnp.uint64(ts_base)
    for k, (seg_kind, idx) in enumerate(plan.segments):
        if seg_kind == "chains":
            ev_seg = _gather_chain_events(
                ev, idx, plan.chain_steps[k], n, B
            )
            carry = chain(carry, ev_seg, n_j, ts_j)
            continue
        K = _bucket(len(idx))
        ev_seg = _gather_events(ev, idx, K, B)
        if seg_kind == "wave":
            carry = step(carry, ev_seg, n_j, ts_j)
        else:
            carry = scan(carry, ev_seg, id_group_full, n_j, ts_j)
    return fin(carry, jnp.asarray(hist_fix))


def run_create_transfers_waves(
    balances, ev: dict, dstat_init, n: int, ts_base: int, plan: WavePlan,
    hist_fix: np.ndarray,
):
    """Execute a batch by the wave plan; same contract and bit-exact
    same outputs as kernel.run_create_transfers.

    `ev` is the HOST-side dict of (B,) numpy arrays per
    kernel.EVENT_FIELDS; `hist_fix` is a (B,) bool mask of events whose
    snapshots should be rewritten with batch finals (wave and
    chain-wave events off history accounts).  The input `balances`
    buffer is DONATED (host exact path: the caller replaces its
    handle).
    """
    return _execute_plan(
        balances, ev, dstat_init, n, ts_base, plan, hist_fix, donate=True
    )


def run_plan_engine(
    balances, ev: dict, dstat_init, n: int, ts_base: int, plan: WavePlan,
    hist_fix: np.ndarray, mesh=None,
):
    """Device-engine entry: execute a window batch's wave plan against
    the AUTHORITATIVE table handle without donating any caller buffer
    — the engine must be able to retry the whole batch from the same
    handle after a transient link fault, and its `self.balances` stays
    valid if execution dies partway (demotion re-uploads from the
    mirror regardless).  Returns (new_balances, packed outputs).

    `mesh` routes a ROW-SHARDED engine's plan through the SPMD
    executors (shard_map over the 1-D "shard" axis): the new balances
    come back under the same NamedSharding row partition the engine
    placed them with, and the packed outputs are replicated.  The
    caller must have checked plan_shardable(plan) first."""
    if mesh is not None:
        return _execute_plan_sharded(
            balances, ev, dstat_init, n, ts_base, plan, hist_fix, mesh
        )
    return _execute_plan(
        balances, ev, dstat_init, n, ts_base, plan, hist_fix, donate=False
    )


# ---------------------------------------------------------------------------
# Optimistic (speculative) execution — round 18.  Invert the wave
# pipeline's order for low-contention batches (the Reddio parallel-EVM
# recipe, arXiv:2503.04595): execute the ENTIRE batch as ONE
# speculative wave step against the authoritative table, detect
# read-write/write-write conflicts ON DEVICE with segmented-min passes
# over the same conflict tokens the partitioner levels by, commit the
# validated events, and replay only the conflicted residue through a
# plan_waves subset plan.  The partitioner leaves the hot path
# entirely: plan only on validation failure.
#
# The PREFIX-COMMIT rule (the subtle part): an event's speculative
# result is committable iff NO earlier event in the batch conflicts
# with it — the wavefront's round-0 unblocked test.  Its gathers then
# saw exactly the sequential pre-state (nothing it depends on ran
# before it), and committable events are pairwise non-conflicting (a
# conflict between two of them would have blocked the later one), so
# committing them as one wave is the wave executor's own exactness
# argument.  An event that merely FOLLOWS a conflicted event commits
# fine when they don't conflict — commuting adds reorder freely — so
# the residue is the conflicted set itself, not a positional suffix.
# The step is NON-DONATING: on validation failure nothing about the
# authoritative handle changed, so "rollback" of the un-committed
# lanes is a no-op by construction (their applies were masked out, not
# undone).


def _spec_conflicts(ev: dict, spec_serial, n, A: int, B: int):
    """Per-lane conflict flags for one speculative step — the
    wavefront's round-0 blocked test (_levels_wavefront) computed on
    device from the event columns alone:

    - serial tokens: only the minimum-index claimant of an id/pending
      group or a durable first-wins target is unblocked;
    - balance slots: a reader is unblocked only as the minimum-index
      toucher of its slot, a writer only when no earlier reader
      touches it (commuting writers share);
    - `spec_serial` force-conflicts events the wave step does not
      model (chain members, history-account events, serialized
      post/voids) — they always replay through the residue plan.

    The in-batch finalizer's WIDENED write set (its target group's
    slot union) needs no entries here: the finalizer shares its
    p_group token with any in-batch creator, so whenever the widened
    writes could matter the finalizer is already blocked, and a
    committed finalizer provably applied nothing to those slots (its
    reference was durable or unresolved).
    """
    i = ev["i"]
    active = i < n
    big = jnp.int32(B)
    flags = ev["flags"]
    is_pv = (flags & (F_POST | F_VOID)) != 0

    # Serial tokens, namespace 1: id-value groups (id_group claims +
    # post/void pending-reference claims share the group space).
    idg = jnp.clip(ev["id_group"], 0, B - 1)
    pg = ev["p_group"]
    pgm = active & (pg >= 0)
    pgc = jnp.clip(pg, 0, B - 1)
    tok_min = jnp.full(B + 1, big, jnp.int32)
    tok_min = tok_min.at[jnp.where(active, idg, B)].min(i)
    tok_min = tok_min.at[jnp.where(pgm, pgc, B)].min(i)
    blk = active & (i > tok_min[idg])
    blk = blk | (pgm & (i > tok_min[pgc]))
    # Namespace 2: durable first-wins finalize targets.
    pt = ev["p_tgt"]
    ptm = active & (pt >= 0)
    ptc = jnp.clip(pt, 0, B - 1)
    pt_min = jnp.full(B + 1, big, jnp.int32).at[
        jnp.where(ptm, ptc, B)
    ].min(i)
    blk = blk | (ptm & (i > pt_min[ptc]))

    # Balance-slot entries (the metadata contract of
    # resolve.wave_dependency_metadata, recomputed from the same
    # columns): reads = balancing clamps + limit checks on own
    # accounts; writes = own dr/cr for creates, the durable target's
    # accounts for found finalizers.
    dr_slot = ev["dr_slot"]
    cr_slot = ev["cr_slot"]
    read_dr = (
        active & ~is_pv & (dr_slot >= 0)
        & (((flags & F_BAL_DR) != 0)
           | ((ev["dr_flags"] & AF_DR_LIMIT) != 0))
    )
    read_cr = (
        active & ~is_pv & (cr_slot >= 0)
        & (((flags & F_BAL_CR) != 0)
           | ((ev["cr_flags"] & AF_CR_LIMIT) != 0))
    )
    pf = ev["p_found"]
    neg = jnp.int32(-1)
    w0 = jnp.where(is_pv, jnp.where(pf, ev["p_dr_slot"], neg), dr_slot)
    w1 = jnp.where(is_pv, jnp.where(pf, ev["p_cr_slot"], neg), cr_slot)
    wm0 = active & (w0 >= 0)
    wm1 = active & (w1 >= 0)
    dr_c = jnp.clip(dr_slot, 0, A - 1)
    cr_c = jnp.clip(cr_slot, 0, A - 1)
    w0_c = jnp.clip(w0, 0, A - 1)
    w1_c = jnp.clip(w1, 0, A - 1)
    a_min = (
        jnp.full(A + 1, big, jnp.int32)
        .at[jnp.where(read_dr, dr_c, A)].min(i)
        .at[jnp.where(read_cr, cr_c, A)].min(i)
        .at[jnp.where(wm0, w0_c, A)].min(i)
        .at[jnp.where(wm1, w1_c, A)].min(i)
    )
    r_min = (
        jnp.full(A + 1, big, jnp.int32)
        .at[jnp.where(read_dr, dr_c, A)].min(i)
        .at[jnp.where(read_cr, cr_c, A)].min(i)
    )
    blk = blk | (read_dr & (i > a_min[dr_c]))
    blk = blk | (read_cr & (i > a_min[cr_c]))
    blk = blk | (wm0 & (i > r_min[w0_c]))
    blk = blk | (wm1 & (i > r_min[w1_c]))
    return blk | (active & spec_serial)


def _spec_exec_impl(balances, ev, dstat_init, spec_serial, n, ts_base):
    """One speculative step: fresh carry -> on-device validation ->
    the wave-step body gated on the validated lanes.  Returns
    (carry, conflicted); the carry holds exactly the committed
    events' effects and registrations — nothing of a conflicted lane
    lands anywhere, so the residue replay resumes from it."""
    B = dstat_init.shape[0]
    A = balances.shape[0]
    conflicted = _spec_conflicts(ev, spec_serial, n, A, B)
    carry = kernel.make_carry(balances, dstat_init, B)
    carry = _wave_step_impl(
        carry, ev, n, ts_base, commit_mask=~conflicted
    )
    return carry, conflicted


_spec_exec = jax.jit(_spec_exec_impl)


def run_speculative_engine(balances, ev: dict, dstat_init, spec_serial,
                           n: int, ts_base: int):
    """Device-engine entry for one speculative step: the WHOLE batch
    as one validated wave against the authoritative table handle,
    never donating any caller buffer (a transient link fault retries
    the entire batch idempotently from the same handle — exactly
    run_plan_engine's contract).  Returns (carry, conflicted): fetch
    `conflicted`, then either finalize_engine (no conflicts — the
    speculation hit) or continue_plan_engine with the residue plan."""
    B = ev["flags"].shape[0]
    K = _bucket(n)
    ev_seg = _gather_events(ev, np.arange(n), K, B)
    ss = np.zeros(K, bool)
    ss[:n] = np.asarray(spec_serial)[:n]
    return _spec_exec(
        balances, ev_seg,
        jnp.asarray(np.asarray(dstat_init), jnp.uint32),
        jnp.asarray(ss), jnp.int32(n), jnp.uint64(ts_base),
    )


def continue_plan_engine(carry, ev: dict, n: int, ts_base: int,
                         plan: WavePlan, hist_fix: np.ndarray):
    """Replay the conflicted residue: thread the speculative step's
    carry — committed events' effects, created-record registrations,
    statuses — through the residue plan's segments (global indices,
    non-donating twins), then finalize.  Returns (new_balances,
    packed outputs), the run_plan_engine contract."""
    B = ev["flags"].shape[0]
    id_group_full = jnp.asarray(ev["id_group"])
    n_j = jnp.int32(n)
    ts_j = jnp.uint64(ts_base)
    for k, (seg_kind, idx) in enumerate(plan.segments):
        if seg_kind == "chains":
            ev_seg = _gather_chain_events(
                ev, idx, plan.chain_steps[k], n, B
            )
            carry = _chain_step_keep(carry, ev_seg, n_j, ts_j)
            continue
        ev_seg = _gather_events(ev, idx, _bucket(len(idx)), B)
        if seg_kind == "wave":
            carry = _wave_step_keep(carry, ev_seg, n_j, ts_j)
        else:
            carry = kernel.scan_segment_keep(
                carry, ev_seg, id_group_full, n_j, ts_j
            )
    return _finalize_keep(carry, jnp.asarray(hist_fix))


def finalize_engine(carry, hist_fix: np.ndarray):
    """Finalize a speculative carry with an empty residue (the hit
    path): pack outputs, rewrite committed events' snapshots to batch
    finals.  Returns (new_balances, packed outputs)."""
    return _finalize_keep(carry, jnp.asarray(hist_fix))


def prewarm(
    A: int, B_buckets=kernel.BATCH_BUCKETS, buckets=_SEG_BUCKETS,
    engine: bool = False, mesh=None, spec: bool = False,
) -> None:
    """Compile the wave step, the chain-wave step, and the paired scan
    segment for the given table geometry OFF the hot path: each
    kernel costs seconds of one-time XLA compile on the chip, which
    must not land inside a timed window (device_engine.prewarm
    forwards its "waves" kind here; TB_DEV_PREWARM=waves,... opts in).
    The jits are shape-keyed on BOTH the carry's batch bucket B and
    the segment bucket K, so the default warms every (B, K <= B) pair
    the router can produce — warming only the extremes would leave
    mid-size first-compiles (e.g. two_phase's ~B/2-event waves, bucket
    4096) inside timed windows.  `engine=True` additionally warms the
    non-donating twins the device engine's window launch dispatches
    (separate XLA executables); the chain-wave step warms at its
    smallest position bucket (deeper chains recompile once, off the
    common path).  `mesh` warms the SPMD executors instead — the
    row-sharded engine's wave dispatch path."""
    if mesh is not None:
        _prewarm_sharded(A, mesh, B_buckets, buckets)
        return
    step = _wave_step_keep if engine else _wave_step
    chainf = _chain_step_keep if engine else _chain_step
    scan = kernel.scan_segment_keep if engine else kernel.scan_segment
    fin = _finalize_keep if engine else _finalize_impl
    outs = []
    for B, K, ev, idx, chain_ev in _prewarm_shapes(B_buckets, buckets):
        carry = kernel.make_carry(
            jnp.zeros((A, 8), jnp.uint64), jnp.zeros(B, jnp.uint32), B
        )
        carry = step(
            carry, _gather_events(ev, idx, K, B),
            jnp.int32(0), jnp.uint64(1),
        )
        carry = scan(
            carry, _gather_events(ev, idx, K, B),
            jnp.asarray(ev["id_group"]), jnp.int32(0), jnp.uint64(1),
        )
        if chain_ev is not None:
            carry = chainf(carry, chain_ev, jnp.int32(0), jnp.uint64(1))
        outs.append(fin(carry, jnp.zeros(B, bool)))
        if spec:
            # The speculative executor (engine-only, non-donating) is
            # a separate XLA executable per (B, K): validation +
            # masked wave step — warm it so a speculative launch never
            # first-compiles inside a timed window.
            sc, confl = _spec_exec(
                jnp.zeros((A, 8), jnp.uint64),
                _gather_events(ev, idx, K, B),
                jnp.zeros(B, jnp.uint32), jnp.zeros(K, bool),
                jnp.int32(0), jnp.uint64(1),
            )
            outs.append(confl)
            outs.append(_finalize_keep(sc, jnp.zeros(B, bool)))
    jax.block_until_ready(outs)


def _prewarm_shapes(B_buckets, buckets):
    """Yield (B, K, ev, idx, chain_ev) for every (batch, segment)
    bucket pair the router can produce — the ONE definition of the
    synthetic warm-up shapes, so the dense and sharded prewarm loops
    can never warm different geometries.  `chain_ev` is None when
    chain waves are disabled."""
    for B in B_buckets:
        ev = {
            name: np.zeros(B, np.dtype(dtype))
            for name, dtype in kernel.EVENT_FIELDS
        }
        ev["i"] = np.arange(B, dtype=np.int32)
        for K in buckets:
            if K > max(_SEG_BUCKETS) or _bucket(min(K, B)) != K:
                continue
            idx = np.arange(min(K, B))
            chain_ev = None
            if chain_max() >= 2:
                chain_ev = {
                    name: jnp.zeros((8, K), jnp.asarray(ev[name]).dtype)
                    for name in _CHAIN_EV_FIELDS
                }
                chain_ev["i"] = jnp.full((8, K), B, jnp.int32)
                chain_ev["chain_open"] = jnp.zeros((8, K), bool)
            yield B, K, ev, idx, chain_ev


def _prewarm_sharded(A: int, mesh, B_buckets, buckets) -> None:
    """Compile the SPMD wave/chain/finalize executors for every (B, K)
    bucket pair the router can produce, with the table placed under
    the engine's exact NamedSharding (compile cache keys include input
    shardings) — first compiles must not land inside a timed window."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    wave, chain, fin = _sharded_fns(mesh, A)
    sharding = NamedSharding(mesh, P("shard", None))
    outs = []
    for B, K, ev, idx, chain_ev in _prewarm_shapes(B_buckets, buckets):
        table = jax.device_put(jnp.zeros((A, 8), jnp.uint64), sharding)
        rest = _make_rest(jnp.zeros(B, jnp.uint32))
        table, rest = wave(
            table, rest, _gather_events(ev, idx, K, B),
            jnp.int32(0), jnp.uint64(1),
        )
        if chain_ev is not None:
            table, rest = chain(
                table, rest, chain_ev, jnp.int32(0), jnp.uint64(1)
            )
        outs.append(fin(table, rest, jnp.zeros(B, bool)))
    jax.block_until_ready(outs)


# ---------------------------------------------------------------------------
# Pending wave-record compaction.  A queued "waves" record used to
# retain its full (B,)-padded host event dict until launch (~3 MB at
# B=8192; a 96-batch window ~300 MB of host RAM).  Most columns are
# all-zero, constant, or narrow for common batches, and padding past
# the batch length is zeros by construction — so pending records store
# a lossless columnar encoding and rebuild the padded dict at launch
# (DeviceEngine.submit_waves / _exec_waves).  The engine reports the
# retained bytes as `pending_window_bytes`.

_PER_COLUMN_OVERHEAD = 8  # name/tag bookkeeping, counted honestly


class PackedColumns:
    """Lossless columnar encoding of a dict of (B,) numpy arrays whose
    tails (beyond row `n`) are zeros — except full-length aranges
    ("i"), which re-derive.  Per column: all-zero -> nothing, constant
    -> one scalar, arange -> nothing, bool -> bit-packed, integers ->
    the narrowest dtype that holds the value range."""

    __slots__ = ("n", "B", "cols", "nbytes", "padded_nbytes")

    def __init__(self, cols: dict, n: int) -> None:
        self.n = n
        self.cols = {}
        self.nbytes = 0
        self.padded_nbytes = 0
        B = None
        for name, arr in cols.items():
            arr = np.asarray(arr)
            B = arr.shape[0] if B is None else B
            assert arr.shape == (B,), (name, arr.shape, B)
            self.padded_nbytes += arr.nbytes
            self.cols[name] = enc = self._encode(arr, n)
            payload = enc[2]
            self.nbytes += _PER_COLUMN_OVERHEAD + (
                payload.nbytes if isinstance(payload, np.ndarray) else 8
            )
        self.B = B

    @staticmethod
    def _encode(arr: np.ndarray, n: int):
        dt = arr.dtype
        if dt.kind in "iu" and arr[0] == 0 and bool(
            (np.diff(arr) == 1).all()
        ):
            return (dt, "arange", None)
        head, tail = arr[:n], arr[n:]
        if tail.any():
            # Unexpectedly nonzero padding: store verbatim — the codec
            # must be lossless for ANY input, compact for common ones.
            return (dt, "full", arr.copy())
        if not head.any():
            return (dt, "zero", None)
        if bool((head == head[0]).all()):
            return (dt, "const", head[0])
        if dt.kind == "b":
            return (dt, "bits", np.packbits(head))
        if dt.kind == "u":
            vmax = int(head.max())
            for nt in (np.uint8, np.uint16, np.uint32, np.uint64):
                if vmax <= int(np.iinfo(nt).max):
                    return (dt, "arr", head.astype(nt))
        if dt.kind == "i":
            vmin, vmax = int(head.min()), int(head.max())
            for nt in (np.int8, np.int16, np.int32, np.int64):
                ii = np.iinfo(nt)
                if ii.min <= vmin and vmax <= ii.max:
                    return (dt, "arr", head.astype(nt))
        return (dt, "arr", head.copy())

    def unpack(self) -> dict:
        out = {}
        for name, (dt, tag, payload) in self.cols.items():
            if tag == "arange":
                out[name] = np.arange(self.B, dtype=dt)
                continue
            if tag == "full":
                out[name] = payload.copy()
                continue
            arr = np.zeros(self.B, dt)
            if tag == "const":
                arr[: self.n] = payload
            elif tag == "bits":
                arr[: self.n] = np.unpackbits(
                    payload, count=self.n
                ).astype(bool)
            elif tag == "arr":
                arr[: self.n] = payload.astype(dt)
            out[name] = arr
        return out


def pack_wave_record(ev: dict, dstat_init, hist_fix, n: int) -> PackedColumns:
    """One compact bundle for everything a pending "waves" record must
    retain until launch: the event dict plus the dstat seed and the
    snapshot-rewrite mask (all (B,) columns, same codec)."""
    cols = dict(ev)
    cols["__dstat_init__"] = np.asarray(dstat_init)
    cols["__hist_fix__"] = np.asarray(hist_fix)
    return PackedColumns(cols, n)


def unpack_wave_record(pk: PackedColumns):
    """-> (ev, dstat_init, hist_fix), bit-identical to what was packed."""
    cols = pk.unpack()
    dstat_init = cols.pop("__dstat_init__")
    hist_fix = cols.pop("__hist_fix__")
    return cols, dstat_init, hist_fix


def pack_spec_record(ev: dict, dstat_init, spec_serial, n: int) -> PackedColumns:
    """Sibling codec for a pending SPECULATIVE record (same lossless
    columnar compaction, same admission/recovery treatment as a wave
    record): the event dict plus the dstat seed and the known-serial
    mask the on-device validator force-conflicts.  No hist_fix column
    — the snapshot-rewrite mask depends on the validation outcome and
    is derived at launch."""
    cols = dict(ev)
    cols["__dstat_init__"] = np.asarray(dstat_init)
    serial = np.zeros(len(cols["flags"]), bool)
    serial[:n] = np.asarray(spec_serial)[:n]
    cols["__spec_serial__"] = serial
    return PackedColumns(cols, n)


def unpack_spec_record(pk: PackedColumns):
    """-> (ev, dstat_init, spec_serial), bit-identical to what was
    packed."""
    cols = pk.unpack()
    dstat_init = cols.pop("__dstat_init__")
    spec_serial = cols.pop("__spec_serial__")
    return cols, dstat_init, spec_serial


def touched_slots(ev: dict, n: int | None = None) -> np.ndarray:
    """Balance rows a wave batch can modify — the event dict's own
    dr/cr slots plus the durable pending targets' (post/void writes
    land on the TARGET's accounts; in-batch targets resolve to the
    creator event's slots, already covered).  A superset is fine: the
    incremental-commitment refresh of an unmodified row is a no-op
    (device_engine._commit_update)."""
    parts = []
    for key in ("dr_slot", "cr_slot", "p_dr_slot", "p_cr_slot"):
        col = ev.get(key)
        if col is None:
            continue
        a = np.asarray(col).astype(np.int64).ravel()
        if n is not None:
            a = a[:n]
        parts.append(a[a >= 0])
    if not parts:
        return np.zeros(0, np.int64)
    return np.unique(np.concatenate(parts))
