"""The kind `tpcc_payment` (PR 35): the generator keeps the source's
shapes (TPC-C revision 5.11, clauses 2.5 and 4.3); its plain reference
pinned to `CpuStateMachine` at a small size, with the sessions
interleaved in an order drawn from the seed, and the same whatever the
order; the control, which has to come out as not correct; and the cell
`bench1r-tpcc-pay-c4` as one traced rehearsal on the CPU backend that
reads its five per-layer metrics (`ENTRIES` is their manifest entries'
text, with the cell they were written for; PR 40 appended them).
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
from benchmarks.harness.gen import tpcc_payment  # noqa: E402
from manifest_checks import manifest_lists  # noqa: E402
from test_reference import _HEALTHY, Oracle  # noqa: E402
from tigerbeetle_tpu.types import Operation  # noqa: E402

RUN = os.path.join(_REPO, "benchmarks", "run.py")
CELL = "bench1r-tpcc-pay-c4"
M = mf.Manifest()
CONFIG = M.config(M.cell(CELL))
TRAFFIC = M.traffic(M.cell(CELL))
# 4 warehouses of 3 districts of 12 customers: 160 accounts.
SMALL = {"accounts": 160, "ledger": 1, "warehouses": 4,
         "districts_per_warehouse": 3, "customers_per_district": 12}
PARAMS = {"sessions": 4, "request_events": 120, "amount_min": 100,
          "amount_max": 500000, "remote_share": 0.15, "keying_error_share": 0.05}
SEEDS = [1, 35, 2**31 + 3535]
ROUTING, VSR = "state machine routing", "VSR, journal, checkpoint"
ENTRIES = [dict(e, workloads=[CELL]) for e in (
    {"name": "touched_rows_per_prepare", "unit": "rows", "better": "lower",
     "source": "program_counter", "layer": ROUTING, "moves": "commit_events_per_s"},
    {"name": "hot_row_legs_max_mean", "unit": "count", "better": "lower",
     "source": "program_counter", "layer": ROUTING, "moves": "commit_events_per_s"},
    {"name": "ckpt_freeze_us_mean", "unit": "us", "better": "lower",
     "source": "program_span", "layer": VSR, "moves": "request_p95_ms"},
    {"name": "ckpt_blob_bytes_per_account", "unit": "bytes", "better": "lower",
     "source": "program_counter", "layer": VSR, "moves": "request_p95_ms"},
    {"name": "device_table_fill_pct", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "device", "moves": "commit_events_per_s"},
)]
NEW_METRICS = [e["name"] for e in ENTRIES]
UNITS = {e["name"]: e["unit"] for e in ENTRIES}


# ---------------------------------------------------------------------------
# The generator keeps the source's shapes.


@pytest.fixture(scope="module")
def drawn():
    """The cell's generator and over 100,000 of its payments: 7
    requests of each of the 4 sessions, a column each."""
    gen = tpcc_payment.make(TRAFFIC, CONFIG, 2**31 + 35)
    cols: dict = {}
    for s in range(4):
        for i in range(7):
            p = gen.payments(s, i)
            p["session"] = np.full(len(p["amount"]), s)
            p["index"] = np.full(len(p["amount"]), i)
            for k, v in p.items():
                cols.setdefault(k, []).append(v)
    return gen, {k: np.concatenate(v) for k, v in cols.items()}


def test_the_layout_is_clause_4_3s(drawn):
    gen, _ = drawn
    assert (gen.warehouses, gen.districts, gen.customers) == (32, 10, 3000)
    assert gen.per_warehouse == 30_011 and gen.n_accounts == 32 * 30_011 == 960_352
    assert gen.nurand_a == 1023 and 0 <= gen.nurand_c <= 1023
    a = gen.accounts()
    assert (a["id_lo"] == np.arange(1, 960_353)).all() and not a["flags"].any()
    assert [int((a["code"] == c).sum()) for c in (1, 2, 3)] == [32, 320, 960_000]
    assert a["code"][gen.warehouse_id(31) - 1] == 1
    assert a["code"][gen.district_id(31, 9) - 1] == 2
    assert gen.customer_id(31, 9, 2999) == 960_352
    assert gen.customer_id(0, 0, 0) == 12 and a["code"][11] == 3
    assert [tpcc_payment.nurand_a(c) for c in (3000, 100, 12)] == [1023, 31, 3]


def test_85_of_100_payments_are_home_and_15_remote(drawn):
    _, p = drawn
    assert len(p["remote"]) > 100_000
    assert abs(p["remote"].mean() - 0.15) < 0.01
    home = ~p["remote"]
    assert (p["cust_w"][home] == p["home_w"][home]).all()
    assert (p["cust_d"][home] == p["home_d"][home]).all()
    # A remote customer is of ANOTHER warehouse, any of the other 31,
    # and of any of its districts.
    assert (p["cust_w"][p["remote"]] != p["home_w"][p["remote"]]).all()
    assert len(np.unique(p["cust_w"][p["remote"]])) == 32
    assert len(np.unique(p["cust_d"][p["remote"]])) == 10


def test_nurand_keeps_its_bounds_and_its_skew(drawn):
    gen, p = drawn
    assert p["cust"].min() >= 0 and p["cust"].max() < 3000
    counts = np.sort(np.bincount(p["cust"], minlength=3000))[::-1]
    uniform = np.sort(np.bincount(np.random.default_rng(1).integers(
        0, 3000, len(p["cust"])), minlength=3000))[::-1]
    # The tenth of the customers drawn most often takes over half of
    # the payments (0.62 measured; 0.13 when drawn uniformly), and the
    # favourite over 40 times its share: (3/4)**10 of a block of 1,024.
    assert counts[:300].sum() / len(p["cust"]) > 0.5
    assert uniform[:300].sum() / len(p["cust"]) < 0.2
    assert counts[0] / counts.mean() > 40
    # Another seed, another constant C: other favourites.
    other = tpcc_payment.make(TRAFFIC, CONFIG, 2**31 + 36)
    assert other.nurand_c != gen.nurand_c


def test_every_payment_is_two_rows_linked_on_the_first_alone(drawn):
    gen, _ = drawn
    for s, i in ((0, 0), (1, 3), (3, 6)):
        t = gen.request(s, i)
        assert len(t) == 8190
        leg1, leg2 = t[0::2], t[1::2]
        assert (leg1["flags"] == wire.TRANSFER_LINKED).all() and not leg2["flags"].any()
        assert (leg2["debit_account_id_lo"] == leg1["credit_account_id_lo"]).all()
        assert (leg1["amount_lo"] == leg2["amount_lo"]).all()
        assert leg1["amount_lo"].min() >= 100 and leg1["amount_lo"].max() <= 500_000
        assert (t["ledger"] == 1).all() and (t["code"] != 0).all()
        codes = gen.accounts()["code"]
        assert (codes[leg1["debit_account_id_lo"] - 1] == 3).all()
        assert (codes[leg1["credit_account_id_lo"] - 1] == 2).all()
        # 8,190 legs stay under the bound at which the planner leaves
        # `linked_small`.
        assert int(t["amount_lo"].sum()) < 2**31 - 1


def test_home_warehouses_are_a_sessions_own(drawn):
    gen, p = drawn
    assert (p["home_w"] // 8 == p["session"]).all()
    assert len(np.unique(p["home_w"])) == 32 and len(np.unique(p["home_d"])) == 10
    t = gen.request(2, 1)
    ok = t[1::2]["credit_account_id_lo"] <= gen.n_accounts
    own = {gen.warehouse_id(w) for w in range(16, 24)}
    assert set(t[1::2]["credit_account_id_lo"][ok].tolist()) == own
    # The district is the home warehouse's.
    district = t[1::2]["debit_account_id_lo"][ok]
    assert ((district - t[1::2]["credit_account_id_lo"][ok] >= 1)
            & (district - t[1::2]["credit_account_id_lo"][ok] <= 10)).all()
    # What the cell's `why` says of the hot rows: 8 warehouse rows take
    # about 512 legs of a request, 80 district rows about 102.
    ids, legs = np.unique(np.concatenate(
        [t["debit_account_id_lo"], t["credit_account_id_lo"]]), return_counts=True)
    by_id = dict(zip(ids.tolist(), legs.tolist()))
    per_warehouse = [by_id[w] for w in sorted(own)]
    assert 400 < min(per_warehouse) and max(per_warehouse) < 640
    assert 3_700 < len(ids) < 4_400


def test_one_payment_in_a_hundred_carries_a_keying_error(drawn):
    gen, p = drawn
    later = p["index"] > 0
    assert not p["keying_error"][~later].any()      # a first request is clean
    assert abs(p["keying_error"][later].mean() - 0.01) < 0.002
    t = gen.request(1, 2)
    bad = t[1::2]["credit_account_id_lo"] > gen.n_accounts
    assert (bad == gen.payments(1, 2)["keying_error"]).all() and 20 < bad.sum() < 70
    codes = tpcc_payment.reference(gen).codes(t)
    assert (codes[0::2][bad] == wire.LINKED_EVENT_FAILED).all()
    assert (codes[1::2][bad] == wire.CREDIT_ACCOUNT_NOT_FOUND).all()
    assert not codes[0::2][~bad].any() and not codes[1::2][~bad].any()
    # Over 60 failed rows a request: the summary row does not hold
    # them and the dense codes come home.
    assert (codes != 0).sum() > 60


def test_ids_never_repeat(drawn):
    gen, _ = drawn
    seen = np.concatenate([gen.request(s, i)["id_lo"]
                           for s in range(4) for i in range(5)])
    assert len(np.unique(seen)) == len(seen) == 4 * 5 * 8190
    assert (np.diff(gen.request(3, 4)["id_lo"]) == 1).all()


def test_same_seed_same_rows_other_seed_other_rows():
    for index in range(4):
        a = tpcc_payment.make(PARAMS, SMALL, 7).request(2, index)
        assert (a == tpcc_payment.make(PARAMS, SMALL, 7).request(2, index)).all()
        assert (a != tpcc_payment.make(PARAMS, SMALL, 8).request(2, index)).any()
        assert len(a) == PARAMS["request_events"]


def test_a_configuration_without_warehouses_gets_one_a_session():
    gen = tpcc_payment.make(TRAFFIC, {"accounts": 10_000, "ledger": 1}, 1)
    assert (gen.warehouses, gen.districts, gen.customers) == (4, 10, 248)
    assert len(gen.accounts()) == 10_000
    used = gen.request(3, 1)
    assert used["debit_account_id_lo"].max() <= 4 * gen.per_warehouse
    with pytest.raises(ValueError):
        tpcc_payment.make(TRAFFIC, dict(SMALL, warehouses=2), 1)
    with pytest.raises(ValueError):
        tpcc_payment.make(dict(PARAMS, request_events=121), SMALL, 1)


# ---------------------------------------------------------------------------
# The reference against CpuStateMachine.


def _order(seed: int, per_session: int, salt: int = 0) -> list:
    order = [s for s in range(4) for _ in range(per_session)]
    np.random.default_rng([seed, salt]).shuffle(order)
    return order


def _replay(seed: int, order: list, plant: bool = False):
    """The four sessions' requests interleaved in `order` (a session's
    own in its own order), on the reference and on the oracle.
    -> (gen, ref, oracle, [(session, index, rows, reply)])"""
    gen = tpcc_payment.make(PARAMS, SMALL, seed)
    ref, oracle = tpcc_payment.reference(gen), Oracle()
    assert oracle.submit(Operation.create_accounts, gen.accounts().tobytes()) == b""
    at = [0] * 4
    sent = []
    for s in order:
        i, at[s] = at[s], at[s] + 1
        rows = gen.request(s, i)
        if plant:
            _plant_faults(rows, np.random.default_rng([seed, s, i, 9]))
        want = oracle.submit(Operation.create_transfers, rows.tobytes())
        assert ref.apply(rows) == want, (s, i)
        sent.append((s, i, rows, want))
    return gen, ref, oracle, sent


def _plant_faults(rows: np.ndarray, rng) -> None:
    """One fault of each kind the ladder can meet, on a leg drawn from
    the seed (a first leg takes its chain's second with it, a second
    its first), and a chain left open at the request's end."""
    places = rng.choice(len(rows) - 2, size=10, replace=False)
    rows["credit_account_id_lo"][places[0]] = rows["debit_account_id_lo"][places[0]]
    rows["debit_account_id_lo"][places[1]] = SMALL["accounts"] + 9
    rows["credit_account_id_lo"][places[2]] = SMALL["accounts"] + 9
    rows["amount_lo"][places[3]] = 0
    rows["id_lo"][places[4]] = 0
    rows["ledger"][places[5]] = 2
    rows["code"][places[6]] = 0
    rows["debit_account_id_lo"][places[7]] = 0
    rows["credit_account_id_lo"][places[8]] = 0
    rows["ledger"][places[9]] = 0
    rows["flags"][-1] = wire.TRANSFER_LINKED


def _accounts_of(oracle) -> np.ndarray:
    ids = np.arange(1, SMALL["accounts"] + 1, dtype=np.uint64)
    return wire.masked(wire.ACCOUNT, oracle.submit(
        Operation.lookup_accounts, wire.ids_body(ids)))


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_equals_cpu_state_machine(seed):
    gen, ref, oracle, sent = _replay(seed, _order(seed, 5))
    failed = 0
    for s, i, rows, reply in sent:
        failed += len(reply) // 8
        stored = oracle.submit(Operation.lookup_transfers,
                               wire.ids_body(rows["id_lo"]))
        assert (wire.masked(wire.TRANSFER, stored) == ref.stored_rows(rows)).all(), (s, i)
    assert failed > 0 and failed % 2 == 0       # a payment fails whole
    want = ref.account_rows()
    assert (_accounts_of(oracle) == want).all()
    assert want["debits_posted_lo"].sum() == want["credits_posted_lo"].sum() > 0
    # Money only moves up: customers pay, warehouses collect.
    code = want["code"]
    assert not want["credits_posted_lo"][code == 3].any()
    assert not want["debits_posted_lo"][code == 1].any()
    assert (want["debits_posted_lo"][code == 2]
            == want["credits_posted_lo"][code == 2]).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_follows_the_ladder_on_planted_faults(seed):
    gen, ref, oracle, sent = _replay(seed, _order(seed, 3), plant=True)
    met = set()
    for s, i, rows, reply in sent:
        got = np.frombuffer(reply, wire.CREATE_RESULT)
        met |= set(got["result"].tolist())
        stored = oracle.submit(Operation.lookup_transfers,
                               wire.ids_body(rows["id_lo"][rows["id_lo"] != 0]))
        assert (wire.masked(wire.TRANSFER, stored) == ref.stored_rows(rows)).all(), (s, i)
    assert met >= {
        wire.LINKED_EVENT_FAILED, wire.LINKED_EVENT_CHAIN_OPEN,
        wire.ID_MUST_NOT_BE_ZERO, wire.DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO,
        wire.CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO, wire.ACCOUNTS_MUST_BE_DIFFERENT,
        wire.AMOUNT_MUST_NOT_BE_ZERO, wire.LEDGER_MUST_NOT_BE_ZERO,
        wire.CODE_MUST_NOT_BE_ZERO, wire.DEBIT_ACCOUNT_NOT_FOUND,
        wire.CREDIT_ACCOUNT_NOT_FOUND,
        wire.TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS}
    assert (_accounts_of(oracle) == ref.account_rows()).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_sessions_commute(seed):
    """Another interleaving, and each session by itself as
    `harness/compare.py reference_side` replays them: the same
    replies, the same balances."""
    _gen, ref_a, _oracle, sent_a = _replay(seed, _order(seed, 5))
    _gen, ref_b, _oracle, sent_b = _replay(seed, _order(seed, 5, salt=1))
    _gen, ref_c, _oracle, sent_c = _replay(seed, sorted(_order(seed, 5)))
    assert [s for s, *_ in sent_a] != [s for s, *_ in sent_b]
    by_request = {(s, i): reply for s, i, _rows, reply in sent_a}
    for sent in (sent_b, sent_c):
        assert {(s, i): reply for s, i, _rows, reply in sent} == by_request
    assert (ref_a.account_rows() == ref_b.account_rows()).all()
    assert (ref_a.account_rows() == ref_c.account_rows()).all()


@pytest.mark.parametrize("seed", [5, 4_000_000_035, 79])
def test_control_lost_ack_is_not_correct(seed):
    gen = tpcc_payment.make(PARAMS, SMALL, seed)
    ref = tpcc_payment.reference(gen)
    records, t = [], 0.0
    for i in range(5):
        for s in range(4):
            t += 1.0
            rows = gen.request(s, i)
            records.append(load.Record(s, i, len(rows), t, t + 0.5, ref.apply(rows)))
    sample = compare.sample_requests(records, seed, 3)
    want = compare.reference_side(gen, tpcc_payment.reference(gen), records, sample)
    sound = compare.reference_side(gen, tpcc_payment.reference(gen), records, sample)
    ok, table = compare.verdict(compare.numbers(sound, want, records, _HEALTHY))
    assert ok and all(v["value"] == 0 for v in table.values())
    lost = compare.last_write(records)
    held = compare.reference_side(gen, tpcc_payment.reference(gen), records, sample,
                                  drop=lost)
    ok, table = compare.verdict(compare.numbers(held, want, records, _HEALTHY))
    assert not ok
    assert table["account_rows_differing"]["value"] > 0
    assert table["transfer_rows_differing"]["value"] > 0


# ---------------------------------------------------------------------------
# The cell, as one traced rehearsal.


def check_the_cell_is_the_manifests(m):
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpcc-payment-1r", "tpcc-pay-c4", 1)
    assert m.config(cell) == CONFIG and m.traffic(cell) == TRAFFIC
    base = m.config(m.cell("bench1r-chains2p-c4"))
    for key in ("replicas", "chips_per_replica", "cluster", "ledger",
                "event_bytes", "request_events_max", "launcher"):
        assert CONFIG[key] == base[key], key
    assert CONFIG["server"]["flags"] == {"cache-accounts": 2**20,
                                         "cache-transfers": 2**24}
    assert CONFIG["server"]["env"] == {"TB_ENGINE": "device",
                                       "TB_DEV_PREWARM": "linked,linked_small"}
    assert set(CONFIG["guarantees"]) == {"durability", "order", "read_back",
                                         "atomicity"}
    for key in ("durability", "order", "read_back"):
        assert CONFIG["guarantees"][key] == base["guarantees"][key]
    assert len(CONFIG["source"]) <= 200
    assert CONFIG["reduced"] == ["warehouses", "transfer_count"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert CONFIG["accounts"] == CONFIG["warehouses"] * (1 + CONFIG[
        "districts_per_warehouse"] * (1 + CONFIG["customers_per_district"]))
    plain = m.traffic(m.cell("bench1r-plain-c4"))
    for key in ("loop", "sessions", "request_events", "warm_requests_per_session",
                "request_timeout_ms", "trace", "read_back"):
        assert TRAFFIC[key] == plain[key], key
    assert (TRAFFIC["remote_share"], TRAFFIC["keying_error_share"]) == (0.15, 0.01)
    assert (TRAFFIC["amount_min"], TRAFFIC["amount_max"]) == (100, 500_000)
    assert TRAFFIC["phase"]["cross_checkpoints_before"] == 1
    for e in ENTRIES:
        spec = manifest_lists(m, e)
        assert spec["reader"] in ("scrape_hist_mean", "scrape_gauge_ratio")


def test_the_cell_is_the_manifests():
    check_the_cell_is_the_manifests(M)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """-> (the result line, the run's scrapes)"""
    run_dir = tmp_path_factory.mktemp("tpcc") / "run"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 351),
         "--seconds", "4", "--trace", "1", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return line, json.load(f)


def test_the_cell_rehearsed_is_correct_on_the_device_engine(rehearsed):
    line, scrapes = rehearsed
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert all(v == {"value": 0, "limit": 0} for v in line["compared"].values())
    assert line["info"]["checkpoints_in_window"] == 1
    assert line["metrics"]["device_computed_pct"]["value"] == 100.0
    before, after = scrapes["before"][0], scrapes["after"][0]
    assert after["sm.dev.fallback_batches"] == 0 and after["sm.fallback_events"] == 0
    assert after["sm.host_semantic_events"] == 0
    # Every batch of the window is the linked kernel's, and a fifth of
    # the rehearsal's payments fail: the dense codes come home.
    ran = {k for k in after if k.startswith("sm.dev.kind.") and k.endswith(
        ".batches") and after[k] > before[k]}
    assert ran == {"sm.dev.kind.linked_small.batches"}
    assert after["sm.dev.summary.dense_fetches"] > before[
        "sm.dev.summary.dense_fetches"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_rehearsal_reads_the_new_metric(rehearsed, name):
    line, scrapes = rehearsed
    after = scrapes["after"][0]
    assert line["metrics"][name]["unit"] == UNITS[name]
    value = line["metrics"][name]["value"]
    if name == "touched_rows_per_prepare":
        # 250 payments: 250 customers less repeats, 20 districts, 2 warehouses.
        assert 100 < value <= 272
    elif name == "hot_row_legs_max_mean":
        # Two warehouse rows share 250 payments' second legs.
        assert 100 <= value <= 250
        assert after["sm.plan.row_legs_max.count"] == after[
            "sm.plan.rows_touched.count"] > 0
    elif name == "ckpt_freeze_us_mean":
        assert value > 0
    elif name == "ckpt_blob_bytes_per_account":
        assert after["sm.accounts"] == 2022
        assert value == after["vsr.ckpt.blob_bytes"] / 2022 and value > 128
    else:
        assert after["sm.dev.table_rows"] == 4096
        assert value == pytest.approx(100 * 2022 / 4096)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_keys_gives_the_readers_nothing(name):
    """The parent's scrapes: each reader returns None and raises
    nothing, so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "sm.dev.link.fetch_bytes": 9}
    later = dict(old, **{"vsr.commit_us.count": 9})
    ctx = {"before": [dict(old)], "after": [dict(later)],
           "at_close": [dict(later)], "requests": 7, "trace": None}
    spec = M.layer_spec({"name": name})
    assert mf.reader(spec).read(spec, ctx) is None
