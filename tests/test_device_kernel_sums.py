"""The kernels' dense sums against plain Python integers.

`device_kernels._touch` ranks a batch's legs over the table rows they
name, `_sum_legs` sums their amounts per (touched row, column),
`_admit` and `_release` check and apply over the gathered rows and
`_write_back` scatters them home.  Every semantic kernel is these five
in a row; here they run alone, composed as the kernels compose them,
against a reference that walks the legs one by one with unbounded
integers.  The table is compared whole: a row no leg names must come
back as it went in.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tigerbeetle_tpu.state_machine import device_kernels as dk

A = 2048
LEGS = 2 * dk.B          # what orderfree and linked sum over
U64 = (1 << 64) - 1
U128 = 1 << 128


# -- the composition under test (orderfree's with one pass, linked's
# and two_phase's with two) ------------------------------------------------


def _passes(legs):
    return [
        (jnp.asarray(p["col"]), jnp.asarray(p["lo"]), jnp.asarray(p["hi"]),
         jnp.asarray(p["valid"]))
        for p in legs
    ]


@functools.partial(jax.jit, static_argnames=("lo_only", "release"))
def _run(table, slot_rows, passes, lo_only, release):
    t = dk._touch(table, slot_rows)
    sums = dk._sum_legs(t, passes, lo_only=lo_only)
    rows8, bad = dk._admit(t, *sums[0])
    if release:
        rows8, bad_release = dk._release(t, rows8, *sums[1])
        bad = bad | bad_release
    return dk._write_back(table, t, rows8, bad), bad


def run(table, slots, legs, lo_only=False, release=False):
    new, bad = _run(
        jnp.asarray(table), jnp.asarray(slots, dtype=jnp.int64),
        _passes(legs), lo_only=lo_only, release=release,
    )
    return np.asarray(new), bool(bad)


# -- the reference ---------------------------------------------------------


def _ints(table):
    """(A, 4) Python-int columns dp, dpo, cp, cpo of an (A, 8) table."""
    return [[int(r[2 * c]) | (int(r[2 * c + 1]) << 64) for c in range(4)]
            for r in table]


def _table(cols):
    out = np.zeros((len(cols), 8), np.uint64)
    for r, row in enumerate(cols):
        for c, v in enumerate(row):
            out[r, 2 * c] = v & U64
            out[r, 2 * c + 1] = (v >> 64) & U64
    return out


def _sums(slots, p):
    sums = {}
    for s, c, lo, hi, v in zip(slots, p["col"], p["lo"], p["hi"], p["valid"]):
        if v:
            assert 0 <= s < A, "a valid leg names a row"
            key = (int(s), int(c))
            sums[key] = sums.get(key, 0) + (int(lo) | (int(hi) << 64))
    return sums


def reference(table, slots, adds, release=None):
    """(new table, flagged): the adds admitted as a whole or not at
    all, then the releases; a flagged batch leaves the table as it was."""
    cols = _ints(table)
    bad = False
    for (s, c), d in _sums(slots, adds).items():
        bad |= d >= U128                      # the sum's own limbs
        cols[s][c] += d
        bad |= cols[s][c] >= U128             # the column's add
    touched = {s for s in map(int, slots) if 0 <= s < A}
    for s in touched:
        bad |= cols[s][0] % U128 + cols[s][1] % U128 >= U128
        bad |= cols[s][2] % U128 + cols[s][3] % U128 >= U128
    if release is not None and not bad:
        for (s, c), d in _sums(slots, release).items():
            bad |= d >= U128 or cols[s][c] < d
            cols[s][c] -= d
    if bad:
        return table.copy(), True
    return _table(cols), False


# -- inputs ----------------------------------------------------------------


def _base_table(rng):
    table = np.zeros((A, 8), np.uint64)
    table[:, 0::2] = rng.integers(0, 1 << 62, (A, 4), dtype=np.uint64)
    return table


def _legs(rng, n, valid, hi=False):
    return {
        "col": rng.integers(0, 4, n).astype(np.int32),
        "lo": rng.integers(0, 1 << 63, n, dtype=np.uint64) * np.uint64(2)
        + rng.integers(0, 2, n, dtype=np.uint64),
        "hi": (rng.integers(0, 1 << 40, n, dtype=np.uint64) if hi
               else np.zeros(n, np.uint64)),
        "valid": np.asarray(valid, bool),
    }


def _slots_one_row(rng):
    return np.full(LEGS, 77), np.ones(LEGS, bool)


def _slots_all_distinct(rng):
    return rng.permutation(A)[:LEGS], np.ones(LEGS, bool)


def _slots_ends(rng):
    return np.where(rng.random(LEGS) < 0.5, 0, A - 1), np.ones(LEGS, bool)


def _slots_all_invalid(rng):
    return np.full(LEGS, -1), np.zeros(LEGS, bool)


def _slots_invalid_collide(rng):
    """Legs that name no row (-1 and past the table: a clip would land
    them on rows 0 and A-1) among valid legs on exactly those rows."""
    valid = rng.random(LEGS) < 0.5
    slots = np.where(
        valid, np.where(rng.random(LEGS) < 0.5, 0, A - 1),
        np.where(rng.random(LEGS) < 0.5, -1, A + 5),
    )
    return slots, valid


def _slots_hot_and_cold(rng):
    """The payment cell's shape: a few rows take most legs."""
    slots = np.where(
        rng.random(LEGS) < 0.5, rng.integers(0, 8, LEGS),
        rng.integers(8, A, LEGS),
    )
    return slots, rng.random(LEGS) < 0.9


SHAPES = {
    "one_row": _slots_one_row,
    "all_distinct": _slots_all_distinct,
    "slots_0_and_last": _slots_ends,
    "all_invalid": _slots_all_invalid,
    "invalid_collides_with_valid": _slots_invalid_collide,
    "hot_and_cold": _slots_hot_and_cold,
}


# -- tests -----------------------------------------------------------------


@pytest.mark.parametrize("lo_only", [False, True], ids=["lo_hi", "lo_only"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_sums_and_apply_equal_the_integer_reference(shape, lo_only):
    rng = np.random.default_rng(list(SHAPES).index(shape))
    table = _base_table(rng)
    slots, valid = SHAPES[shape](rng)
    adds = _legs(rng, LEGS, valid, hi=not lo_only)
    # Amounts that a u64 holds 2 * LEGS of: nothing overflows here.
    adds["lo"] >>= np.uint64(16)
    want, flagged = reference(table, slots, adds)
    assert not flagged
    got, bad = run(table, slots, [adds], lo_only=lo_only)
    assert not bad
    np.testing.assert_array_equal(got, want)
    if not valid.any():
        np.testing.assert_array_equal(got, table)


@pytest.mark.parametrize("lo_only", [False, True], ids=["lo_hi", "lo_only"])
def test_two_passes_share_one_rank_pass(lo_only):
    """linked's superset and apply: two sums over the same legs, the
    second a subset of the first; each equals its own reference."""
    rng = np.random.default_rng(11)
    table = _base_table(rng)
    slots, valid = _slots_hot_and_cold(rng)
    first = _legs(rng, LEGS, valid, hi=not lo_only)
    first["lo"] >>= np.uint64(16)
    second = dict(first, valid=valid & (rng.random(LEGS) < 0.5))
    t = dk._touch(jnp.asarray(table), jnp.asarray(slots, dtype=jnp.int64))
    both = dk._sum_legs(t, _passes([first, second]), lo_only=lo_only)
    for legs, sums in zip((first, second), both):
        rows8, bad = dk._admit(t, *sums)
        got = np.asarray(dk._write_back(jnp.asarray(table), t, rows8, bad))
        want, flagged = reference(table, slots, legs)
        assert not flagged and not bool(bad)
        np.testing.assert_array_equal(got, want)


def _one_leg(slot, col, lo, hi=0):
    """LEGS legs, the first one valid."""
    valid = np.zeros(LEGS, bool)
    valid[0] = True
    slots = np.full(LEGS, -1)
    slots[0] = slot
    legs = {
        "col": np.full(LEGS, col, np.int32),
        "lo": np.full(LEGS, lo, np.uint64),
        "hi": np.full(LEGS, hi, np.uint64),
        "valid": valid,
    }
    return slots, legs


OVERFLOWS = {
    # (column the row holds near its ceiling, column the leg adds to)
    "column_add_overflows_u128": (1, 1),
    "dr_total_overflows": (0, 1),      # dp near 2^128, the add lands on dpo
    "cr_total_overflows": (3, 2),      # cpo near 2^128, the add lands on cp
}


@pytest.mark.parametrize("lo_only", [False, True], ids=["lo_hi", "lo_only"])
@pytest.mark.parametrize("case", list(OVERFLOWS))
def test_an_overflow_flags_and_returns_the_table_bit_for_bit(case, lo_only):
    held, added = OVERFLOWS[case]
    rng = np.random.default_rng(5)
    table = _base_table(rng)
    table[900] = 0
    table[900, 2 * held] = U64 - 10          # the column holds 2^128 - 11
    table[900, 2 * held + 1] = U64
    slots, legs = _one_leg(900, added, 11)
    assert reference(table, slots, legs)[1]
    got, bad = run(table, slots, [legs], lo_only=lo_only)
    assert bad
    np.testing.assert_array_equal(got, table)
    # One less and the batch is admitted: the flag is exact.
    slots, legs = _one_leg(900, added, 10)
    want, flagged = reference(table, slots, legs)
    assert not flagged
    got, bad = run(table, slots, [legs], lo_only=lo_only)
    assert not bad
    np.testing.assert_array_equal(got, want)


def test_a_sum_that_overflows_its_own_limbs_flags():
    """Three legs of 2^127 each on one (row, column): the SUM passes
    2^128 before it meets the row."""
    table = np.zeros((A, 8), np.uint64)
    slots = np.full(LEGS, -1)
    slots[:3] = 5
    valid = np.zeros(LEGS, bool)
    valid[:3] = True
    legs = {
        "col": np.zeros(LEGS, np.int32), "lo": np.zeros(LEGS, np.uint64),
        "hi": np.full(LEGS, 1 << 63, np.uint64), "valid": valid,
    }
    assert reference(table, slots, legs)[1]
    got, bad = run(table, slots, [legs])
    assert bad
    np.testing.assert_array_equal(got, table)


def test_a_flag_on_an_untouched_row_is_not_the_batchs():
    """The admission reads touched rows: a row that already breaks the
    total (which no table holds: the check is what admits every write)
    flags only a batch that names it."""
    table = np.zeros((A, 8), np.uint64)
    table[40, 0:4] = U64                     # dp = dpo = 2^128 - 1
    slots, legs = _one_leg(41, 1, 5)
    got, bad = run(table, slots, [legs])
    assert not bad and int(got[41, 2]) == 5
    slots[1] = 40                            # named, though it adds nothing
    got, bad = run(table, slots, [legs])
    assert bad
    np.testing.assert_array_equal(got, table)


@pytest.mark.parametrize("lo_only", [False, True], ids=["lo_hi", "lo_only"])
@pytest.mark.parametrize("short_by", [0, 1])
def test_two_phase_release_underflow(short_by, lo_only):
    """Adds, then releases out of what the adds left: a release of one
    more than a column holds flags and leaves the table as it was."""
    rng = np.random.default_rng(3)
    table = _base_table(rng)
    slots, valid = _slots_hot_and_cold(rng)
    adds = _legs(rng, LEGS, valid, hi=not lo_only)
    adds["lo"] >>= np.uint64(16)
    # Leg 0 releases from column 0 of row 3 exactly what the adds
    # leave there, plus short_by.
    slots[0] = 3
    adds["valid"][0] = False
    mid, _ = reference(table, slots, adds)
    amount = (int(mid[3, 0]) | (int(mid[3, 1]) << 64)) + short_by
    assert amount <= U64 or not lo_only
    rel_valid = np.zeros(LEGS, bool)
    rel_valid[0] = True
    release = {
        "col": np.zeros(LEGS, np.int32),
        "lo": np.full(LEGS, amount & U64, np.uint64),
        "hi": np.full(LEGS, amount >> 64, np.uint64),
        "valid": rel_valid,
    }
    want, flagged = reference(table, slots, adds, release)
    assert flagged == bool(short_by)
    got, bad = run(table, slots, [adds, release], lo_only=lo_only,
                      release=True)
    assert bad == flagged
    np.testing.assert_array_equal(got, want)
    if not flagged:
        assert int(got[3, 0]) == 0 and int(got[3, 1]) == 0


@pytest.mark.parametrize("shape", list(SHAPES))
def test_ranks_are_dense_and_monotone_in_the_slot(shape):
    """What linked's packed sort key leans on: a leg's rank is below
    the number of legs at any table size, equal for equal slots, and
    ordered as the slots are."""
    rng = np.random.default_rng(23)
    slots, _valid = SHAPES[shape](rng)
    table = jnp.zeros((A, 8), jnp.uint64)
    t = dk._touch(table, jnp.asarray(slots, dtype=jnp.int64))
    rank = np.asarray(t["rank"])
    named = (slots >= 0) & (slots < A)
    distinct = sorted(set(slots[named].tolist()))
    n = int(t["n"])
    assert n == len(distinct)
    # The rank of a leg is its slot's place among the distinct slots.
    assert (rank[~named] == n).all()
    assert [distinct[r] for r in rank[named]] == slots[named].tolist()
    uslots, hit = np.asarray(t["uslots"]), np.asarray(t["hit"])
    assert uslots[hit].tolist() == distinct
    assert (uslots[~hit] >= A).all()
    # Ascending and unique, the pads too: what the scatter is told.
    assert (np.diff(uslots.astype(np.int64)) > 0).all()
