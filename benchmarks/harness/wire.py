"""The wire rows, as the benchmark's own copy.

The generators and the reference build and read 128-byte rows without
importing the program (reference: src/tigerbeetle.zig, Account and
Transfer).  `tests/benchmarks/test_reference.py` holds this copy to
`tigerbeetle_tpu.types`, field for field.
"""

from __future__ import annotations

import numpy as np


def _u128(name: str) -> list:
    return [(name + "_lo", "<u8"), (name + "_hi", "<u8")]


ACCOUNT = np.dtype(
    _u128("id") + _u128("debits_pending") + _u128("debits_posted")
    + _u128("credits_pending") + _u128("credits_posted")
    + _u128("user_data_128")
    + [("user_data_64", "<u8"), ("user_data_32", "<u4"), ("reserved", "<u4"),
       ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"),
       ("timestamp", "<u8")]
)

TRANSFER = np.dtype(
    _u128("id") + _u128("debit_account_id") + _u128("credit_account_id")
    + _u128("amount") + _u128("pending_id") + _u128("user_data_128")
    + [("user_data_64", "<u8"), ("user_data_32", "<u4"), ("timeout", "<u4"),
       ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"),
       ("timestamp", "<u8")]
)

CREATE_RESULT = np.dtype([("index", "<u4"), ("result", "<u4")])
U128_PAIR = np.dtype([("lo", "<u8"), ("hi", "<u8")])

assert ACCOUNT.itemsize == 128 and TRANSFER.itemsize == 128

# Flags the generators set (src/tigerbeetle.zig:42-63, 127-140).
ACCOUNT_DEBITS_MUST_NOT_EXCEED_CREDITS = 1 << 1
TRANSFER_LINKED = 1 << 0
TRANSFER_PENDING = 1 << 1
TRANSFER_POST = 1 << 2
TRANSFER_VOID = 1 << 3

# create_transfers result codes that the generators' transfers can get,
# in the order of precedence (src/tigerbeetle.zig:185-265).
OK = 0
LINKED_EVENT_FAILED = 1
LINKED_EVENT_CHAIN_OPEN = 2
ID_MUST_NOT_BE_ZERO = 5
FLAGS_ARE_MUTUALLY_EXCLUSIVE = 7
DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO = 8
CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO = 10
ACCOUNTS_MUST_BE_DIFFERENT = 12
PENDING_ID_MUST_BE_ZERO = 13
PENDING_ID_MUST_NOT_BE_ZERO = 14
PENDING_ID_MUST_BE_DIFFERENT = 16
AMOUNT_MUST_NOT_BE_ZERO = 18
LEDGER_MUST_NOT_BE_ZERO = 19
CODE_MUST_NOT_BE_ZERO = 20
DEBIT_ACCOUNT_NOT_FOUND = 21
CREDIT_ACCOUNT_NOT_FOUND = 22
TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS = 24
PENDING_TRANSFER_NOT_FOUND = 25
PENDING_TRANSFER_NOT_PENDING = 26
PENDING_TRANSFER_HAS_DIFFERENT_DEBIT_ACCOUNT_ID = 27
PENDING_TRANSFER_HAS_DIFFERENT_CREDIT_ACCOUNT_ID = 28
PENDING_TRANSFER_HAS_DIFFERENT_LEDGER = 29
PENDING_TRANSFER_HAS_DIFFERENT_CODE = 30
EXCEEDS_PENDING_TRANSFER_AMOUNT = 31
PENDING_TRANSFER_HAS_DIFFERENT_AMOUNT = 32
PENDING_TRANSFER_ALREADY_POSTED = 33
PENDING_TRANSFER_ALREADY_VOIDED = 34
EXCEEDS_CREDITS = 54

# The most events one request can carry (1 MiB message less its header).
REQUEST_EVENTS_MAX = 8190


def ids_body(ids) -> bytes:
    arr = np.zeros(len(ids), U128_PAIR)
    arr["lo"] = ids
    return arr.tobytes()


def masked(dtype: np.dtype, reply: bytes) -> np.ndarray:
    """Lookup rows with the server-assigned `timestamp` zeroed: the
    server stamps its own clock, the reference none."""
    arr = np.frombuffer(reply, dtype).copy()
    arr["timestamp"] = 0
    return arr

