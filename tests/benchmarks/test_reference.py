"""The benchmark's own reference, pinned to `CpuStateMachine` at a tiny
size; its copy of the wire rows, pinned to the program's; the control,
which has to come out as not correct; the roofline's byte count."""

import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from benchmarks.harness import compare, load, roofline, wire  # noqa: E402
from benchmarks.harness.gen import plain  # noqa: E402
from tigerbeetle_tpu import constants as cfg  # noqa: E402
from tigerbeetle_tpu import types  # noqa: E402
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine  # noqa: E402
from tigerbeetle_tpu.types import Operation  # noqa: E402

CONFIG = {"accounts": 64, "ledger": 1}


class Oracle:
    """CpuStateMachine behind the primary's prepare/prefetch/commit
    sequence (the loop of chip_smoke.py's Oracle)."""

    def __init__(self) -> None:
        self.sm = CpuStateMachine(cfg.PRODUCTION)
        self.op = 0

    def submit(self, operation, body: bytes) -> bytes:
        sm = self.sm
        sm.prepare_timestamp = max(sm.prepare_timestamp, sm.commit_timestamp) + 1
        sm.prepare(operation, body)
        timestamp = sm.prepare_timestamp
        self.op += 1
        sm.prefetch(operation, body, prefetch_timestamp=timestamp)
        return sm.commit(0, self.op, timestamp, operation, body)


@pytest.mark.parametrize("ours,theirs", [
    (wire.ACCOUNT, types.ACCOUNT_DTYPE),
    (wire.TRANSFER, types.TRANSFER_DTYPE),
    (wire.CREATE_RESULT, types.CREATE_RESULT_DTYPE),
    (wire.U128_PAIR, types.U128_PAIR_DTYPE),
])
def test_wire_copy_equals_the_programs_rows(ours, theirs):
    assert ours == theirs


def test_result_codes_copy_equals_the_programs():
    R = types.CreateTransferResult
    for name in ("id_must_not_be_zero", "debit_account_id_must_not_be_zero",
                 "credit_account_id_must_not_be_zero",
                 "accounts_must_be_different", "amount_must_not_be_zero",
                 "ledger_must_not_be_zero", "code_must_not_be_zero",
                 "debit_account_not_found", "credit_account_not_found",
                 "transfer_must_have_the_same_ledger_as_accounts",
                 "linked_event_failed", "linked_event_chain_open",
                 "flags_are_mutually_exclusive", "pending_id_must_be_zero",
                 "pending_id_must_not_be_zero", "pending_id_must_be_different",
                 "pending_transfer_not_found", "pending_transfer_not_pending",
                 "pending_transfer_has_different_debit_account_id",
                 "pending_transfer_has_different_credit_account_id",
                 "pending_transfer_has_different_ledger",
                 "pending_transfer_has_different_code",
                 "exceeds_pending_transfer_amount",
                 "pending_transfer_has_different_amount",
                 "pending_transfer_already_posted",
                 "pending_transfer_already_voided", "exceeds_credits"):
        assert getattr(wire, name.upper()) == int(getattr(R, name)), name
    TF, AF = types.TransferFlags, types.AccountFlags
    assert (wire.TRANSFER_LINKED, wire.TRANSFER_PENDING, wire.TRANSFER_POST,
            wire.TRANSFER_VOID) == (TF.linked, TF.pending, TF.post_pending_transfer,
                                    TF.void_pending_transfer)
    assert wire.ACCOUNT_DEBITS_MUST_NOT_EXCEED_CREDITS == AF.debits_must_not_exceed_credits


@pytest.mark.parametrize("seed", [1, 25, 2**31 + 12345])
@pytest.mark.parametrize("bad_rows", [0, 24])
def test_plain_reference_equals_cpu_state_machine(seed, bad_rows):
    params = {"request_events": 200, "amount_max": 999, "bad_rows": bad_rows}
    gen = plain.make(params, CONFIG, seed)
    ref = plain.reference(gen)
    oracle = Oracle()
    assert oracle.submit(Operation.create_accounts, gen.accounts().tobytes()) == b""
    # Four sessions' requests in an order drawn from the seed: they commute.
    order = [(s, i) for s in range(4) for i in range(5)]
    np.random.default_rng(seed).shuffle(order)
    refused = 0
    for s, i in order:
        rows = gen.request(s, i)
        want = oracle.submit(Operation.create_transfers, rows.tobytes())
        got = ref.apply(rows)
        assert got == want, (s, i)
        refused += len(got) // 8
        stored = oracle.submit(Operation.lookup_transfers,
                               wire.ids_body(rows["id_lo"][rows["id_lo"] != 0]))
        kept = ref.stored_rows(rows).copy()
        assert (wire.masked(wire.TRANSFER, stored) == kept).all()
    assert (refused > 0) == (bad_rows > 0)
    assert ref.events_accepted == 20 * 200 - refused
    ids = np.arange(1, CONFIG["accounts"] + 1, dtype=np.uint64)
    rows = wire.masked(wire.ACCOUNT, oracle.submit(Operation.lookup_accounts,
                                                  wire.ids_body(ids)))
    assert (rows == ref.account_rows()).all()


@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_a_list_of_request_sizes_is_sent_whole_in_every_turn(seed):
    sizes = [1, 1, 2, 4, 8, 16, 200]
    gen = plain.make({"request_events": sizes, "amount_max": 9}, CONFIG, seed)
    for s in range(3):
        for turn in range(3):
            sent = [len(gen.request(s, turn * len(sizes) + k)) for k in range(len(sizes))]
            assert sorted(sent) == sizes
    orders = {tuple(gen.events(s, k) for k in range(len(sizes))) for s in range(4)}
    assert len(orders) > 1                      # each session in an order of its own
    ids = np.concatenate([gen.request(1, i)["id_lo"] for i in range(14)])
    assert len(set(ids.tolist())) == len(ids)
    ref, oracle = plain.reference(gen), Oracle()
    oracle.submit(Operation.create_accounts, gen.accounts().tobytes())
    for i in range(14):
        rows = gen.request(0, i)
        assert ref.apply(rows) == oracle.submit(
            Operation.create_transfers, rows.tobytes())


def test_same_seed_same_rows_other_seed_other_rows():
    params = {"request_events": 100, "amount_max": 999}
    a = plain.make(params, CONFIG, 7).request(2, 3)
    assert (a == plain.make(params, CONFIG, 7).request(2, 3)).all()
    assert (a != plain.make(params, CONFIG, 8).request(2, 3)).any()
    assert (a["debit_account_id_lo"] != a["credit_account_id_lo"]).all()
    b = plain.make(params, CONFIG, 7).request(3, 3)
    assert not set(a["id_lo"]) & set(b["id_lo"])


def _acked(gen, ref, sessions=4, each=3):
    """Records as the load would keep them, answered by the reference."""
    out, t = [], 0.0
    for i in range(each):
        for s in range(sessions):
            t += 1.0
            rows = gen.request(s, i)
            out.append(load.Record(s, i, len(rows), t, t + 0.5, ref.apply(rows)))
    return out


_HEALTHY = {"events_not_on_device": 0, "events_unaccounted": 0, "engine_faults": 0,
            "replicas_disagreeing": 0, "servers_exited_badly": 0}


@pytest.mark.parametrize("seed", [3, 4_000_000_019, 77])
def test_control_lost_ack_is_not_correct(seed):
    """The control: the reference in the program's place, with one
    guarantee broken: the last acknowledged write is not read back."""
    gen = plain.make({"request_events": 300, "amount_max": 999}, CONFIG, seed)
    records = _acked(gen, plain.reference(gen))
    sample = compare.sample_requests(records, seed, 2)
    want = compare.reference_side(gen, plain.reference(gen), records, sample)
    sound = compare.reference_side(gen, plain.reference(gen), records, sample)
    ok, table = compare.verdict(compare.numbers(sound, want, records, _HEALTHY))
    assert ok and all(v["value"] == 0 for v in table.values())
    lost = compare.last_write(records)
    assert lost is max(records, key=lambda r: r.t_reply) and lost in sample
    held = compare.reference_side(gen, plain.reference(gen), records, sample,
                                  drop=lost)
    ok, table = compare.verdict(compare.numbers(held, want, records, _HEALTHY))
    assert not ok
    assert table["account_rows_differing"]["value"] > 0
    assert table["transfer_rows_differing"]["value"] >= 300


@pytest.mark.parametrize("name", sorted(compare.LIMITS))
def test_any_number_over_its_limit_is_not_correct(name):
    values = dict.fromkeys(compare.LIMITS, 0)
    assert compare.verdict(values)[0]
    values[name] = 1
    ok, table = compare.verdict(values)
    assert not ok and list(table) == list(compare.LIMITS)


def test_a_failed_or_wrong_reply_counts():
    gen = plain.make({"request_events": 50, "amount_max": 9}, CONFIG, 1)
    records = _acked(gen, plain.reference(gen), each=2)
    want = compare.reference_side(gen, plain.reference(gen), records, [])
    records[0].reply = None
    records[1].reply = b"\x00" * 8
    got = compare.numbers({"accounts": want["accounts"],
                           "transfers": want["transfers"]},
                          want, records, _HEALTHY)
    assert got["requests_failed"] == 1 and got["replies_differing"] == 1


def test_roofline_bytes_and_bound():
    assert roofline.transfer_bytes_needed() == 92
    assert roofline.account_balance_bytes() == 64
    assert roofline.bytes_per_event() == 352
    least, bound = roofline.least_seconds(8190, "TPU v5 lite")
    assert bound == "bytes"
    assert least == pytest.approx(8190 * 352 / 819e9)
    with pytest.raises(KeyError):
        roofline.least_seconds(1, "some other chip")


@pytest.mark.parametrize("n,q,want", [(1, 0.5, 0), (2, 0.5, 0), (100, 0.95, 94),
                                      (100, 0.5, 49), (21, 0.95, 19)])
def test_percentile_is_nearest_rank(n, q, want):
    assert load.percentile([float(i) for i in range(n)], q) == float(want)
