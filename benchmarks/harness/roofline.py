"""What a committed event has to move, and how fast the chip could.

`commit_roofline_pct` reads the work, not the implementation: the
least time the chip could take for the events committed in the traced
slice, over all the time in which any operation ran on the device in
that slice, whichever programs ran.

Bytes a commit of one plain transfer has to move, from the wire
layouts (`wire.TRANSFER`, `wire.ACCOUNT`):

  the transfer's fields that a commit needs (all but the three
  user_data fields, which are stored and never computed on, and the
  timestamp, which the server assigns)                      92 bytes
  two account rows, the four balance fields of each
  (debits/credits, pending/posted: 4 x 16 bytes), read     128 bytes
  the same two rows written back                           128 bytes
  one result code                                            4 bytes
                                                           ---------
                                                           352 bytes

Operations: two 128-bit additions and the comparisons of the ladder,
some 16 integer operations an event.  Against the peaks below that is
0.08 ps of arithmetic and 430 ps of memory traffic: the bytes set the
bound, by four orders of magnitude.
"""

from __future__ import annotations

from . import wire

# Peaks of one chip, keyed by the `device_kind` JAX reports.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
# HBM at 819 GB/s.  A device that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"bytes_per_s": 819e9, "ops_per_s": 197e12,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"bytes_per_s": 819e9, "ops_per_s": 197e12,
                "source": "Google Cloud documentation, TPU v5e"},
}

_NOT_COMPUTED_ON = ("user_data_128_lo", "user_data_128_hi", "user_data_64",
                    "user_data_32", "timestamp")
_BALANCES = ("debits_pending", "debits_posted", "credits_pending",
             "credits_posted")
RESULT_BYTES = 4
OPS_PER_EVENT = 16


def transfer_bytes_needed() -> int:
    return sum(wire.TRANSFER.fields[f][0].itemsize
               for f in wire.TRANSFER.names if f not in _NOT_COMPUTED_ON)


def account_balance_bytes() -> int:
    return sum(wire.ACCOUNT.fields[f + half][0].itemsize
               for f in _BALANCES for half in ("_lo", "_hi"))


def bytes_per_event() -> int:
    rows = 2 * account_balance_bytes()          # debit and credit account
    return transfer_bytes_needed() + rows + rows + RESULT_BYTES


def least_seconds(events: int, device_kind: str) -> tuple[float, str]:
    """-> (the least time the chip could take, which bound sets it)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    peak = PEAKS[device_kind]
    by_bytes = events * bytes_per_event() / peak["bytes_per_s"]
    by_ops = events * OPS_PER_EVENT / peak["ops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
