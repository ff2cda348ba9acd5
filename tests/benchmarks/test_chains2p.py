"""The kind `chains2p` (PR 33): its plain reference pinned to
`CpuStateMachine` at a small size; what a failed chain and a second
finalise answer; the control, which has to come out as not correct;
and the cell `bench1r-chains2p-c4` as one traced rehearsal on the CPU
backend, which reads its five per-layer metrics (`ENTRIES` is their
manifest entries' text, with the cell they were written for; PR 40
appended them).
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
from benchmarks.harness.gen import chains2p  # noqa: E402
from manifest_checks import manifest_lists  # noqa: E402
from test_reference import _HEALTHY, Oracle  # noqa: E402
from tigerbeetle_tpu.types import Operation  # noqa: E402

RUN = os.path.join(_REPO, "benchmarks", "run.py")
CELL = "bench1r-chains2p-c4"
M = mf.Manifest()
CONFIG = {"accounts": 256, "ledger": 1}
PARAMS = {"sessions": 4, "request_events": 120, "amount_max": 999,
          "chain_len": [1, 7], "chain_fail_share": 0.2, "void_share": 0.3,
          "refinalize_share": 0.05}
SEEDS = [1, 33, 2**31 + 4321]
ENGINE, ROUTING = "device engine", "state machine routing"
ENTRIES = [
    dict(e, better="lower", workloads=[CELL]) for e in (
        {"name": "fallback_batches_per_req", "unit": "count",
         "source": "program_counter", "layer": ENGINE, "moves": "commit_events_per_s"},
        {"name": "dense_fetches_per_req", "unit": "count",
         "source": "program_counter", "layer": ENGINE, "moves": "request_p50_ms"},
        {"name": "pending_join_cold_us_per_prepare", "unit": "us",
         "source": "program_span", "layer": ROUTING, "moves": "commit_events_per_s"},
        {"name": "pending_join_cold_rows_per_req", "unit": "rows",
         "source": "program_counter", "layer": ROUTING, "moves": "commit_events_per_s"},
        {"name": "linked_fixpoint_iters_mean", "unit": "count",
         "source": "program_counter", "layer": "kernels", "moves": "commit_events_per_s"},
    )]


def _replay(seed: int, cycles: int = 3):
    """A funding request a session, then `cycles` cycles of the four
    sessions interleaved in an order drawn from the seed (a session's
    own requests in its own order), on the reference and on the
    oracle.  -> (gen, ref, oracle, [(session, index, rows, reply)])"""
    gen = chains2p.make(PARAMS, CONFIG, seed)
    ref, oracle = chains2p.reference(gen), Oracle()
    assert oracle.submit(Operation.create_accounts, gen.accounts().tobytes()) == b""
    per_session = 1 + 3 * cycles
    order = [s for s in range(4) for _ in range(per_session)]
    np.random.default_rng(seed).shuffle(order)
    at = [0] * 4
    sent = []
    for s in order:
        i, at[s] = at[s], at[s] + 1
        rows = gen.request(s, i)
        want = oracle.submit(Operation.create_transfers, rows.tobytes())
        assert ref.apply(rows) == want, (s, i, gen.klass(s, i))
        sent.append((s, i, rows, want))
    return gen, ref, oracle, sent


@pytest.mark.parametrize("seed", SEEDS)
def test_chains2p_reference_equals_cpu_state_machine(seed):
    gen, ref, oracle, sent = _replay(seed)
    classes = {gen.klass(s, i) for s, i, _rows, _reply in sent}
    assert classes == {"fund", "P", "C", "F"}
    for s, i, rows, _reply in sent:
        stored = oracle.submit(Operation.lookup_transfers,
                               wire.ids_body(rows["id_lo"]))
        assert (wire.masked(wire.TRANSFER, stored) == ref.stored_rows(rows)).all(), (s, i)
    ids = np.arange(1, CONFIG["accounts"] + 1, dtype=np.uint64)
    rows = wire.masked(wire.ACCOUNT, oracle.submit(Operation.lookup_accounts,
                                                  wire.ids_body(ids)))
    want = ref.account_rows()
    assert (rows == want).all()
    # The displaced pendings stay pending, and the rows say so.
    assert want["debits_pending_lo"].sum() > 0
    assert want["debits_pending_lo"].sum() == want["credits_pending_lo"].sum()
    assert (want["flags"] != 0).sum() == 4 * 32


@pytest.mark.parametrize("seed", SEEDS)
def test_a_failed_chain_answers_every_leg_and_stores_nothing(seed):
    gen, ref, _oracle, sent = _replay(seed, cycles=2)
    failed_chains = 0
    for s, i, rows, reply in sent:
        if gen.klass(s, i) != "C":
            continue
        codes = np.zeros(len(rows), np.uint32)
        got = np.frombuffer(reply, wire.CREATE_RESULT)
        codes[got["index"]] = got["result"]
        linked = (rows["flags"] & wire.TRANSFER_LINKED) != 0
        assert not linked[-1]
        ends = np.flatnonzero(~linked) + 1
        for a, b in zip(np.concatenate([[0], ends[:-1]]), ends):
            leg_codes = codes[a:b]
            if not leg_codes.any():
                continue
            failed_chains += 1
            assert (leg_codes == wire.EXCEEDS_CREDITS).sum() == 1
            assert (leg_codes == wire.LINKED_EVENT_FAILED).sum() == b - a - 1
            at_fault = rows[a:b][leg_codes == wire.EXCEEDS_CREDITS][0]
            assert at_fault["debit_account_id_lo"] in gen.poor_ids(s)
        kept = ref.stored_rows(rows)
        assert len(kept) == (codes == 0).sum()
        assert not set(kept["id_lo"]) & set(rows["id_lo"][codes != 0])
    assert failed_chains > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_a_second_finalise_answers_already_posted_or_voided(seed):
    gen, ref, _oracle, sent = _replay(seed)
    by_id = {}
    again = 0
    for s, i, rows, reply in sent:
        if gen.klass(s, i) != "F":
            continue
        got = np.frombuffer(reply, wire.CREATE_RESULT)
        assert len(got) == gen._refinalised(s, i)
        for index, code in zip(got["index"], got["result"]):
            verb = by_id[int(rows["pending_id_lo"][index])]
            assert code == (wire.PENDING_TRANSFER_ALREADY_POSTED
                            if verb & wire.TRANSFER_POST
                            else wire.PENDING_TRANSFER_ALREADY_VOIDED)
            again += 1
        ok = np.ones(len(rows), bool)
        ok[got["index"]] = False
        assert len(set(rows["pending_id_lo"])) == len(rows)     # each named once
        by_id.update(zip(rows["pending_id_lo"][ok].tolist(),
                         rows["flags"][ok].tolist()))
        kept = ref.stored_rows(rows)
        assert (kept["amount_lo"] > 0).all() and (kept["ledger"] == 1).all()
        assert (kept["debit_account_id_lo"] != 0).all()
        voids = (rows["flags"][ok] & wire.TRANSFER_VOID) != 0
        assert 0 < voids.sum() < ok.sum()
    assert again > 0


def test_same_seed_same_rows_other_seed_other_rows():
    for index in range(1, 8):
        a = chains2p.make(PARAMS, CONFIG, 7).request(2, index)
        assert (a == chains2p.make(PARAMS, CONFIG, 7).request(2, index)).all()
        assert (a != chains2p.make(PARAMS, CONFIG, 8).request(2, index)).any()
        assert len(a) == PARAMS["request_events"]
        b = chains2p.make(PARAMS, CONFIG, 7).request(3, index)
        assert not set(a["id_lo"]) & set(b["id_lo"])
        # A session keeps to its own share of the accounts.
        used = np.concatenate([a["debit_account_id_lo"], a["credit_account_id_lo"]])
        used = used[used != 0]
        assert ((used > 2 * 64) & (used <= 3 * 64)).all()


def test_the_layout_is_a_function_of_accounts_and_sessions():
    gen = chains2p.make(dict(PARAMS, request_events=8190),
                        {"accounts": 10000, "ledger": 1}, 1)
    assert (gen.share, gen.limited, gen.funded, gen.poor) == (2500, 1250, 1200, 50)
    assert len(gen.free_ids(3)) == 1250 and gen.free_ids(3)[-1] == 10000
    small = chains2p.make(PARAMS, CONFIG, 1)
    assert (small.share, small.limited, small.funded, small.poor) == (64, 32, 30, 2)
    # Sessions enter the cycle at different places; an F with no P
    # behind it is a C.
    assert [small.klass(0, i) for i in range(5)] == ["fund", "P", "C", "F", "P"]
    assert [small.klass(1, i) for i in range(5)] == ["fund", "C", "C", "P", "C"]
    assert [small.klass(2, i) for i in range(5)] == ["fund", "C", "P", "C", "F"]
    with pytest.raises(ValueError):
        chains2p.make(PARAMS, {"accounts": 8, "ledger": 1}, 1)


@pytest.mark.parametrize("seed", [3, 4_000_000_019, 77])
def test_control_lost_ack_is_not_correct(seed):
    gen = chains2p.make(PARAMS, CONFIG, seed)
    ref = chains2p.reference(gen)
    records, t = [], 0.0
    for i in range(7):
        for s in range(4):
            t += 1.0
            rows = gen.request(s, i)
            records.append(load.Record(s, i, len(rows), t, t + 0.5, ref.apply(rows)))
    sample = compare.sample_requests(records, seed, 3)
    want = compare.reference_side(gen, chains2p.reference(gen), records, sample)
    sound = compare.reference_side(gen, chains2p.reference(gen), records, sample)
    ok, table = compare.verdict(compare.numbers(sound, want, records, _HEALTHY))
    assert ok and all(v["value"] == 0 for v in table.values())
    lost = compare.last_write(records)
    held = compare.reference_side(gen, chains2p.reference(gen), records, sample,
                                  drop=lost)
    ok, table = compare.verdict(compare.numbers(held, want, records, _HEALTHY))
    assert not ok
    assert table["account_rows_differing"]["value"] > 0
    assert table["transfer_rows_differing"]["value"] > 0


# ---------------------------------------------------------------------------
# The cell, as one traced rehearsal.


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """-> (the result line, the run's scrapes)"""
    run_dir = tmp_path_factory.mktemp("cell") / "run"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 331),
         "--seconds", "4", "--trace", "1", "--rehearsal", "--keep",
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    with open(run_dir / "scrapes.json") as f:
        return line, json.load(f)


def check_the_cell_is_the_manifests(m):
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "chains2p-1r", "chains2p-c4", 1)
    config, traffic = m.config(cell), m.traffic(cell)
    base = m.config(m.cell("bench1r-plain-c4"))
    for key in ("replicas", "chips_per_replica", "cluster", "accounts", "ledger",
                "event_bytes", "request_events_max", "launcher"):
        assert config[key] == base[key], key
    assert config["server"]["flags"] == base["server"]["flags"]
    assert set(config["guarantees"]) == set(base["guarantees"]) | {
        "atomicity", "reservation"}
    assert len(config["source"]) <= 200 and config["reduced"] == ["transfer_count"]
    plain = m.traffic(m.cell("bench1r-plain-c4"))
    for key in ("loop", "sessions", "request_events", "amount_max",
                "warm_requests_per_session", "request_timeout_ms", "trace"):
        assert traffic[key] == plain[key], key
    assert traffic["phase"]["cross_checkpoints_before"] == 1
    # One checkpoint in every window of 45 to 1,004 requests.
    assert 960 - traffic["phase"]["offset_ops"] == 45
    assert traffic["read_back"]["transfer_sample_requests"] == 9
    for e in ENTRIES:
        spec = manifest_lists(m, e)
        assert spec["reader"] in ("scrape_delta_ratio", "scrape_hist_mean")


def test_the_cell_is_the_manifests():
    check_the_cell_is_the_manifests(M)


def test_the_cell_rehearsed_is_correct_on_the_device_engine(rehearsed):
    line, scrapes = rehearsed
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert all(v == {"value": 0, "limit": 0} for v in line["compared"].values())
    got = line["metrics"]
    assert got["fallback_batches_per_req"]["value"] == 0
    assert got["dense_fetches_per_req"]["value"] > 0
    assert got["linked_fixpoint_iters_mean"]["value"] >= 1
    assert got["device_computed_pct"]["value"] == 100.0
    before, after = scrapes["before"][0], scrapes["after"][0]
    assert after["sm.dev.fallback_batches"] == 0
    assert after["sm.fallback_events"] == 0
    assert after["sm.dev.summary.dense_fetches"] > 0
    # All three classes ran in the window, each on its own kernel.
    ran = {k for k in ("orderfree_tight", "linked_small", "two_phase_lo")
           if after[f"sm.dev.kind.{k}.batches"] > before[f"sm.dev.kind.{k}.batches"]}
    assert ran == {"orderfree_tight", "linked_small", "two_phase_lo"}
    # The rehearsal's one session sends one class a prepare: nothing
    # is left to wave dispatch or to the host.
    assert after["sm.dev_wave.batches"] == 0 and after["sm.host_semantic_events"] == 0
    kinds = [k for k in after if k.startswith("sm.dev.kind.") and k.endswith(".events")]
    assert sum(after[k] for k in kinds) == after["sm.dev.semantic_events"]


@pytest.mark.parametrize("name", [e["name"] for e in ENTRIES])
def test_a_program_without_the_keys_gives_the_readers_nothing(name):
    """The parent's scrapes: each reader returns None and raises
    nothing, so a result line leaves the metric out."""
    old = {"vsr.commit_us.count": 5, "sm.dev.link.fetch_bytes": 9}
    later = dict(old, **{"vsr.commit_us.count": 9})
    ctx = {"before": [dict(old)], "after": [dict(later)],
           "at_close": [dict(later)], "requests": 7, "trace": None}
    spec = M.layer_spec(M.per_layer[name])
    assert mf.reader(spec).read(spec, ctx) is None
