"""The kind `hub_zipf`: the generator is a function of (seed,
session, index) and keeps each session to its own ledger; its plain
reference pinned to `CpuStateMachine` at the rehearsal's size, with the
sessions interleaved in an order drawn from the seed; the control,
which has to come out as not correct; and the cell `bench1r-zipf-c4`
as one traced rehearsal on the CPU backend, whose batches go past the
linked fixpoint's cap and are decided on the device (`ENTRIES` is the
text of its two manifest entries, with the cell they were written for).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import compare, load, manifest as mf, wire  # noqa: E402
from benchmarks.harness.gen import hub_zipf  # noqa: E402
from manifest_checks import manifest_lists  # noqa: E402
from test_reference import _HEALTHY, Oracle  # noqa: E402
from tigerbeetle_tpu.types import Operation  # noqa: E402

RUN = os.path.join(_REPO, "benchmarks", "run.py")
CELL = "bench1r-zipf-c4"
M = mf.Manifest()
CONFIG = M.config(M.cell(CELL))
TRAFFIC = M.traffic(M.cell(CELL))
# The rehearsal's ledger of a settlement account and 3 participants of
# 1,000, one to each of 4 sessions; requests of the rehearsal's 500 rows.
SMALL = {**CONFIG, **CONFIG["rehearsal"], "accounts": 4 * CONFIG["rehearsal"]["accounts"]}
PARAMS = {**TRAFFIC, **TRAFFIC["rehearsal"], "sessions": 4}
SEEDS = [1, 41, 2**31 + 4141]
ENTRIES = [dict(e, better="lower", source="program_counter", layer="kernels",
                moves="commit_events_per_s", workloads=[CELL]) for e in (
    {"name": "linked_settle_rows_per_req", "unit": "rows"},
    {"name": "linked_settle_rounds_mean", "unit": "count"},
)]


def _order(seed: int, per_session: int) -> list:
    order = [s for s in range(4) for _ in range(per_session)]
    np.random.default_rng([seed, 7]).shuffle(order)
    return order


def _replay(seed: int, order: list):
    """The sessions' requests in `order` (a session's own in its own
    order), on the reference and on the oracle: every reply equal.
    -> (gen, ref, oracle, [(session, index, rows, reply)])"""
    gen = hub_zipf.make(PARAMS, SMALL, seed)
    ref, oracle = hub_zipf.reference(gen), Oracle()
    assert oracle.submit(Operation.create_accounts, gen.accounts().tobytes()) == b""
    at = [0] * 4
    sent = []
    for s in order:
        i, at[s] = at[s], at[s] + 1
        rows = gen.request(s, i)
        want = oracle.submit(Operation.create_transfers, rows.tobytes())
        assert ref.apply(rows) == want, (s, i)
        sent.append((s, i, rows, want))
    return gen, ref, oracle, sent


def _accounts_of(oracle, n: int) -> np.ndarray:
    ids = np.arange(1, n + 1, dtype=np.uint64)
    return wire.masked(wire.ACCOUNT, oracle.submit(Operation.lookup_accounts,
                                                   wire.ids_body(ids)))


# ---------------------------------------------------------------------------
# The generator.


def test_the_layout_is_four_ledgers_of_a_settlement_account_and_100_participants():
    gen = hub_zipf.make(TRAFFIC, CONFIG, 2**31 + 41)
    assert (gen.share, gen.participants, gen.prefund) == (101, 100, 20_000)
    a = gen.accounts()
    assert len(a) == CONFIG["accounts"] == 404
    for s in range(4):
        share = a[101 * s:101 * (s + 1)]
        assert (share["ledger"] == 1 + s).all()
        assert share["flags"][0] == 0
        assert (share["flags"][1:] == wire.ACCOUNT_DEBITS_MUST_NOT_EXCEED_CREDITS).all()
        assert gen.settlement_id(s) == 101 * s + 1
        assert list(gen.participant_ids(s)) == list(range(101 * s + 2, 101 * s + 102))


@pytest.mark.parametrize("index", [0, 1, 5, 117])
def test_same_seed_same_rows_other_seed_other_rows(index):
    a = hub_zipf.make(TRAFFIC, CONFIG, 7).request(2, index)
    assert (a == hub_zipf.make(TRAFFIC, CONFIG, 7).request(2, index)).all()
    assert len(a) == TRAFFIC["request_events"] == 8190
    if index:
        assert (a != hub_zipf.make(TRAFFIC, CONFIG, 8).request(2, index)).any()
    b = hub_zipf.make(TRAFFIC, CONFIG, 7).request(3, index)
    assert not set(a["id_lo"].tolist()) & set(b["id_lo"].tolist())


@pytest.mark.parametrize("session", range(4))
def test_a_session_keeps_to_its_own_ledger(session):
    gen = hub_zipf.make(TRAFFIC, CONFIG, 2**31 + 41)
    ledger = gen.accounts()["ledger"]
    for index in range(4):
        t = gen.request(session, index)
        dr = t["debit_account_id_lo"].astype(np.int64)
        cr = t["credit_account_id_lo"].astype(np.int64)
        assert (t["ledger"] == 1 + session).all()
        assert (ledger[dr - 1] == 1 + session).all() and (ledger[cr - 1] == 1 + session).all()
        assert (dr != cr).all() and not t["flags"].any()


def test_the_first_request_prefunds_each_participant_and_then_zipf_by_rank():
    gen = hub_zipf.make(TRAFFIC, CONFIG, 2**31 + 41)
    t = gen.request(1, 0)
    assert (t["debit_account_id_lo"] == gen.settlement_id(1)).all()
    got = np.bincount(t["credit_account_id_lo"].astype(np.int64) - 103,
                      weights=t["amount_lo"].astype(np.float64))
    assert (got == 20_000).all() and len(got) == 100
    payers = np.concatenate([gen.request(1, i)["debit_account_id_lo"]
                             for i in range(1, 6)]).astype(np.int64) - 103
    share = np.bincount(payers, minlength=100) / len(payers)
    # Zipf(1) over 100 ranks: rank 1 pays 1 / H(100) = 19.3% of the rows.
    assert abs(share[0] - 0.193) < 0.01 and share[0] > share[1] > share[9] > share[99]
    amounts = np.concatenate([gen.request(1, i)["amount_lo"] for i in range(1, 3)])
    assert amounts.min() == 1 and amounts.max() == 99


# ---------------------------------------------------------------------------
# The reference against the state machine.


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_equals_cpu_state_machine(seed):
    gen, ref, oracle, sent = _replay(seed, _order(seed, 6))
    for s, i, rows, _reply in sent:
        stored = oracle.submit(Operation.lookup_transfers, wire.ids_body(rows["id_lo"]))
        assert (wire.masked(wire.TRANSFER, stored) == ref.stored_rows(rows)).all(), (s, i)
    want = ref.account_rows()
    assert (_accounts_of(oracle, SMALL["accounts"]) == want).all()
    # The limits decided: refusals in every session's payments, and no
    # participant's debits past its credits.
    codes = [np.frombuffer(reply, wire.CREATE_RESULT) for s, i, _r, reply in sent if i]
    assert all((c["result"] == wire.EXCEEDS_CREDITS).all() for c in codes)
    assert sum(len(c) for c in codes) > 0.05 * sum(len(r) for _s, i, r, _ in sent if i)
    limited = want["flags"] != 0
    assert (want["debits_posted_lo"][limited] <= want["credits_posted_lo"][limited]).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_sessions_commute(seed):
    """Another interleaving, and each session by itself as
    `harness/compare.py reference_side` replays them: the same replies,
    the same balances."""
    _gen, ref_a, _o, sent_a = _replay(seed, _order(seed, 4))
    _gen, ref_b, _o, sent_b = _replay(seed, sorted(_order(seed, 4)))
    assert {(s, i): r for s, i, _rows, r in sent_a} == {
        (s, i): r for s, i, _rows, r in sent_b}
    assert (ref_a.account_rows() == ref_b.account_rows()).all()


@pytest.mark.parametrize("seed", [5, 4_000_000_041, 79])
def test_control_lost_ack_is_not_correct(seed):
    gen = hub_zipf.make(PARAMS, SMALL, seed)
    ref = hub_zipf.reference(gen)
    records, t = [], 0.0
    for i in range(4):
        for s in range(4):
            t += 1.0
            rows = gen.request(s, i)
            records.append(load.Record(s, i, len(rows), t, t + 0.5, ref.apply(rows)))
    sample = compare.sample_requests(records, seed, 3)
    want = compare.reference_side(gen, hub_zipf.reference(gen), records, sample)
    sound = compare.reference_side(gen, hub_zipf.reference(gen), records, sample)
    ok, table = compare.verdict(compare.numbers(sound, want, records, _HEALTHY))
    assert ok and all(v["value"] == 0 for v in table.values())
    lost = compare.last_write(records)
    held = compare.reference_side(gen, hub_zipf.reference(gen), records, sample,
                                  drop=lost)
    ok, table = compare.verdict(compare.numbers(held, want, records, _HEALTHY))
    assert not ok
    assert table["account_rows_differing"]["value"] > 0
    assert table["transfer_rows_differing"]["value"] > 0


# ---------------------------------------------------------------------------
# The cell.


def check_the_cell_is_the_manifests(m):
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hub100-zipf-1r", "zipf-c4", 1)
    config, traffic = m.config(cell), m.traffic(cell)
    base = m.config(m.cell("bench1r-plain-c4"))
    for key in ("replicas", "chips_per_replica", "cluster", "ledger",
                "event_bytes", "request_events_max", "launcher"):
        assert config[key] == base[key], key
    assert config["server"]["flags"] == base["server"]["flags"]
    assert config["server"]["env"] == {"TB_ENGINE": "device",
                                       "TB_DEV_PREWARM": "linked,linked_small"}
    assert set(config["guarantees"]) == set(base["guarantees"]) | {"limits"}
    assert (config["accounts"], config["prefund"]) == (404, 20_000)
    assert len(config["source"]) <= 200 and config["reduced"] == ["transfer_count"]
    plain = m.traffic(m.cell("bench1r-plain-c4"))
    for key in ("loop", "sessions", "request_events", "warm_requests_per_session",
                "request_timeout_ms", "trace"):
        assert traffic[key] == plain[key], key
    assert (traffic["zipf_s"], traffic["amount_max"]) == (1.0, 99)
    assert traffic["phase"]["cross_checkpoints_before"] == 1
    for e in ENTRIES:
        spec = manifest_lists(m, e)
        assert spec["reader"] in ("scrape_delta_ratio", "scrape_hist_mean")


def test_the_cell_is_the_manifests():
    check_the_cell_is_the_manifests(M)


@pytest.mark.parametrize("name", [e["name"] for e in ENTRIES])
def test_a_program_without_the_keys_gives_the_readers_nothing(name):
    """The parent's scrapes: each reader returns None and raises
    nothing, so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "sm.dev.linked.iters.count": 3}
    later = dict(old, **{"vsr.commit_us.count": 9, "sm.dev.linked.iters.count": 7})
    ctx = {"before": [dict(old)], "after": [dict(later)],
           "at_close": [dict(later)], "requests": 7, "trace": None}
    spec = M.layer_spec(M.per_layer[name])
    assert mf.reader(spec).read(spec, ctx) is None


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """-> (the result line, the run's scrapes)"""
    run_dir = tmp_path_factory.mktemp("zipf") / "run"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 411),
         "--seconds", "4", "--trace", "1", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return line, json.load(f)


def test_the_rehearsal_decides_past_the_cap_on_the_device(rehearsed):
    line, scrapes = rehearsed
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert all(v == {"value": 0, "limit": 0} for v in line["compared"].values())
    got = line["metrics"]
    assert got["device_computed_pct"]["value"] == 100.0
    assert got["linked_settle_rows_per_req"]["value"] > 0
    assert got["linked_settle_rounds_mean"]["value"] > 0
    before, after = scrapes["before"][0], scrapes["after"][0]
    assert after["sm.dev.fallback_batches"] == 0 and after["sm.fallback_events"] == 0
    assert after["sm.host_semantic_events"] == 0
    settled = after["sm.dev.linked.settle_batches"] - before["sm.dev.linked.settle_batches"]
    assert settled > 0
    assert after["sm.dev.linked.settle_rows"] - before[
        "sm.dev.linked.settle_rows"] == settled * PARAMS["request_events"]
    ran = {k for k in after if k.startswith("sm.dev.kind.") and k.endswith(
        ".batches") and after[k] > before[k]}
    assert ran == {"sm.dev.kind.linked_small.batches"}
