"""ctypes bindings for the native commit fast path (tb_fastpath.cpp).

The host side of the TPU commit pipeline — wire decode, static ladder,
account resolution, duplicate detection, u128 overflow admission — runs
in C++ at memcpy-like speed; Python keeps orchestration, the columnar
stores, and the device queue.  The balance mirror memory is OWNED by
the native library and wrapped zero-copy as numpy arrays, so exact-path
(JAX kernel) commits and expiry mutations are immediately visible to
the native admission checks and vice versa.

Built from the tracked sources on first use; a failed build raises
(runtime/native.py NativeBuildError).  TB_FASTPATH_DISABLE asks for
the pure-Python path by name.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
from tigerbeetle_tpu import envcheck

def _default_lib_path() -> str:
    # Shares the sanitizer-flavor knob with runtime/native.py: under
    # TB_NATIVE_SANITIZE=asan both libraries load their sanitized
    # builds from native/asan/.
    from tigerbeetle_tpu.runtime import native as native_mod

    return os.path.join(native_mod._lib_dir(), "libtb_fastpath.so")


_LIB_PATH = envcheck.env_str("TB_FASTPATH_LIB", _default_lib_path())

_lib = None
_lib_failed = False  # negative cache: never retry (or re-make) per call
_lib_lock = threading.Lock()

_U64P = ctypes.POINTER(ctypes.c_uint64)
_U32P = ctypes.POINTER(ctypes.c_uint32)
_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)
_U8P = ctypes.POINTER(ctypes.c_uint8)

FALLBACK = 1


def kway_merge(streams, value_size: int):
    """Native k-way merge of sorted-unique (keys V16, flags u8, vals
    (n, value_size) u8) streams, newest first.  Returns merged arrays
    or None when the native library is unavailable."""
    import numpy as np

    lib = _load()
    if lib is None:
        return None
    k = len(streams)
    total = sum(len(s[0]) for s in streams)
    keys_c = [np.ascontiguousarray(s[0]) for s in streams]
    flags_c = [np.ascontiguousarray(s[1]) for s in streams]
    vals_c = [np.ascontiguousarray(s[2]) for s in streams]
    # Plain addresses: a compaction beat makes eight of these calls
    # with three arrays a stream, and a `data_as` cast apiece cost as
    # much as the merges themselves.
    ptrs = ctypes.c_void_p * k
    key_ptrs = ptrs(*[a.ctypes.data for a in keys_c])
    flag_ptrs = ptrs(*[a.ctypes.data for a in flags_c])
    val_ptrs = ptrs(*[a.ctypes.data for a in vals_c])
    lens = (ctypes.c_int64 * k)(*[len(s[0]) for s in streams])
    out_keys = np.empty(total, dtype="V16")
    out_flags = np.empty(total, np.uint8)
    out_vals = np.empty((total, value_size), np.uint8)
    n = lib.tb_lsm_kway_merge(
        k, key_ptrs, flag_ptrs, val_ptrs, lens, value_size,
        out_keys.ctypes.data if total else None,
        out_flags.ctypes.data if total else None,
        out_vals.ctypes.data if total else None,
    )
    return out_keys[:n], out_flags[:n], out_vals[:n]


def encode_run(keys, flags, vals, value_size: int, per_block: int,
               sparse: bool):
    """Every block payload of one run in one native pass, the
    interpreter lock released (native/tb_lsm.inc tb_lsm_encode_run;
    the layout is lsm/tree.py Tree._block_payload's).  Returns the
    payloads as a list of bytes, or None when the native library is
    unavailable."""
    import numpy as np

    lib = _load()
    if lib is None or not hasattr(lib, "tb_lsm_encode_run"):
        return None
    n = len(keys)
    keys = np.ascontiguousarray(keys)
    flags = np.ascontiguousarray(flags, np.uint8)
    vals = np.ascontiguousarray(vals)
    n_blocks = (n + per_block - 1) // per_block
    out = np.empty(n_blocks * 4 + n * (16 + 1 + 4 + value_size), np.uint8)
    offsets = np.empty(n_blocks + 1, np.int64)
    lib.tb_lsm_encode_run(
        keys.ctypes.data_as(_U8P), flags.ctypes.data_as(_U8P),
        vals.ctypes.data_as(_U8P), n, value_size, per_block,
        1 if sparse else 0, out.ctypes.data_as(_U8P),
        offsets.ctypes.data_as(_I64P),
    )
    return [
        out[offsets[b] : offsets[b + 1]].tobytes() for b in range(n_blocks)
    ]


def _load():
    global _lib, _lib_failed
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _lib_failed:
            return None
        if envcheck.env_is_set("TB_FASTPATH_DISABLE"):
            return None
        # Always invoke make: a no-op when fresh, a rebuild when a
        # source changed, and NativeBuildError when the build fails —
        # on this call and on every later one (one attempt per
        # process, runtime/native.py _run_make), so the hot paths that
        # probe availability per call never fork a `make` each and
        # never serve pure-Python numbers as native.
        from tigerbeetle_tpu.runtime import native as native_mod

        native_mod._run_make()
        _lib_failed = True  # cleared on success below
        if not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None

        lib.tb_fp_create.restype = ctypes.c_void_p
        lib.tb_fp_create.argtypes = [ctypes.c_uint64]
        lib.tb_fp_destroy.argtypes = [ctypes.c_void_p]
        lib.tb_fp_balances_lo.restype = _U64P
        lib.tb_fp_balances_lo.argtypes = [ctypes.c_void_p]
        lib.tb_fp_balances_hi.restype = _U64P
        lib.tb_fp_balances_hi.argtypes = [ctypes.c_void_p]
        lib.tb_fp_add_accounts.argtypes = [
            ctypes.c_void_p, _U64P, _U64P, _U32P, _U32P,
            ctypes.c_uint32, ctypes.c_uint64,
        ]
        lib.tb_fp_remove_accounts.argtypes = [
            ctypes.c_void_p, _U64P, _U64P, ctypes.c_uint32,
        ]
        lib.tb_fp_add_transfer_ids.argtypes = [
            ctypes.c_void_p, _U64P, _U64P, ctypes.c_uint64, ctypes.c_uint32,
        ]
        lib.tb_fp_remove_transfer_ids.argtypes = [
            ctypes.c_void_p, _U64P, _U64P, ctypes.c_uint32,
        ]
        lib.tb_fp_peek_transfer_ids.argtypes = [
            ctypes.c_void_p, _U64P, _U64P, ctypes.c_uint32, _U8P, _U64P, _U64P,
        ]
        lib.tb_fp_commit_transfers.restype = ctypes.c_int
        lib.tb_fp_commit_transfers.argtypes = [
            ctypes.c_void_p, _U8P, ctypes.c_uint32, ctypes.c_uint64,
            _U32P, _I32P, _I32P, _I64P, _I64P, _U64P, _U64P, _U32P,
        ]
        lib.tb_fp_commit_linked.restype = ctypes.c_int
        lib.tb_fp_commit_linked.argtypes = [
            ctypes.c_void_p, _U8P, ctypes.c_uint32, ctypes.c_uint64,
            _U32P, _I32P, _I32P, _I64P, _I64P, _U64P, _U64P, _U32P,
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.tb_fp_commit_two_phase.restype = ctypes.c_int
        lib.tb_fp_commit_two_phase.argtypes = [
            ctypes.c_void_p, _U8P, ctypes.c_uint32, ctypes.c_uint64,
            # durable-target join
            _I64P, _U32P, _I32P, _I32P, _U64P, _U64P, _U32P, _U32P,
            _U64P, _U64P, _U64P, _U32P, _U32P, _U32P,
            # outputs
            _U32P, _I32P, _I32P, _U64P, _U64P, _U64P, _U64P, _U64P,
            _U32P, _U32P, _U32P, _U32P,
            _I64P, _U32P, _U32P,
            _I64P, _I64P, _U64P, _U64P, _U32P,
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.tb_fp_commit_exact.restype = ctypes.c_int
        lib.tb_fp_commit_exact.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_uint32, _U32P, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_uint64, ctypes.c_uint32,
            _U64P, _I64P, _I64P, _U64P, _U64P, _U32P,
        ]
        lib.tb_lsm_kway_merge.restype = ctypes.c_int64
        lib.tb_lsm_kway_merge.argtypes = [
            ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        if hasattr(lib, "tb_lsm_encode_run"):  # absent from a stale .so
            lib.tb_lsm_encode_run.restype = ctypes.c_int64
            lib.tb_lsm_encode_run.argtypes = [
                _U8P, _U8P, _U8P, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int32, _U8P, _I64P,
            ]
        lib.tb_fp_decode_store.argtypes = [
            _U8P, ctypes.c_uint32, ctypes.c_uint64,
            _U64P, _U64P, _U64P, _U64P, _U64P, _U64P,
            _U64P, _U64P, _U64P,
            _U32P, _U32P, _U32P, _U32P, _U32P, _U64P, _U8P,
        ]
        # Columnar ingest (absent from a stale prebuilt .so when the
        # rebuild failed: callers fall back per-call).
        try:
            lib.tb_fp_verify_frames.argtypes = [
                _U8P, ctypes.POINTER(ctypes.c_uint64), _U32P,
                ctypes.c_uint32, _U8P,
            ]
            lib.tb_fp_finalize_headers.argtypes = [
                _U8P, ctypes.c_uint32, ctypes.POINTER(_U8P), _U32P,
            ]
        except AttributeError:
            lib.tb_fp_verify_frames = None
            lib.tb_fp_finalize_headers = None
        # r23 hash family: counted verify + the hash-pool / engine
        # controls.  Absent from a stale prebuilt .so whose rebuild
        # failed: callers degrade to the r20 symbols (uncounted) or
        # the Python fallback — the pipeline ABI check reports the
        # staleness loudly either way.
        try:
            lib.tb_fp_verify_frames2.restype = ctypes.c_uint64
            lib.tb_fp_verify_frames2.argtypes = [
                _U8P, ctypes.POINTER(ctypes.c_uint64), _U32P,
                ctypes.c_uint32, _U8P,
            ]
            lib.tb_hash_configure.argtypes = [
                ctypes.c_int32, ctypes.c_int32,
            ]
            lib.tb_hash_engine.restype = ctypes.c_int32
            lib.tb_hash_engine.argtypes = []
            lib.tb_hash_stats.argtypes = [_U64P]
        except AttributeError:
            lib.tb_fp_verify_frames2 = None
            lib.tb_hash_configure = None
            lib.tb_hash_engine = None
            lib.tb_hash_stats = None
        # Native commit pipeline (round 20).  Absent symbols mean a
        # stale prebuilt .so whose rebuild failed: pipeline_available()
        # reports False with a rebuild hint instead of letting an
        # AttributeError fire mid-drain.
        try:
            lib.tb_pl_abi_version.restype = ctypes.c_uint32
            lib.tb_pl_abi_version.argtypes = []
            lib.tb_pl_create.restype = ctypes.c_void_p
            lib.tb_pl_create.argtypes = []
            lib.tb_pl_destroy.argtypes = [ctypes.c_void_p]
            lib.tb_pl_reset.argtypes = [ctypes.c_void_p]
            lib.tb_pl_size.restype = ctypes.c_uint32
            lib.tb_pl_size.argtypes = [ctypes.c_void_p]
            lib.tb_pl_build_prepare.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32,
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32, _U8P,
            ]
            lib.tb_pl_build_prepare_ok.argtypes = [
                ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint32, _U8P,
            ]
            lib.tb_pl_frame_prepare.restype = ctypes.c_uint64
            lib.tb_pl_frame_prepare.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
                _U8P, ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                _U8P, _U8P,
            ]
            lib.tb_pl_note_prepare.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                ctypes.c_uint32,
            ]
            lib.tb_pl_on_ack.restype = ctypes.c_int
            lib.tb_pl_on_ack.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.tb_pl_mark_all_synced.argtypes = [ctypes.c_void_p]
            lib.tb_pl_set_synced.restype = ctypes.c_int
            lib.tb_pl_set_synced.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
            ]
            lib.tb_pl_drop.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            lib.tb_pl_commit_ready.restype = ctypes.c_int
            lib.tb_pl_commit_ready.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32,
            ]
            lib.tb_pl_votes.restype = ctypes.c_uint32
            lib.tb_pl_votes.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            # C-resident drain loop (round 22; flags added r23, ABI
            # 3).  Grouped with the r20 symbols on purpose: a stale
            # .so missing ANY of them disables the whole pipeline (and
            # reports ABI != 3 anyway), never a mixed old/new symbol
            # set.
            lib.tb_pl_build_prepares.restype = ctypes.c_int64
            lib.tb_pl_build_prepares.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(_U8P),
                _U64P, _U64P, _U64P, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_int, ctypes.c_uint32, _U8P,
                _U8P, ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                _U8P, ctypes.c_uint64, _U64P, _U64P, _U64P, _U8P, _U64P,
            ]
            lib.tb_pl_accept_prepares.restype = ctypes.c_int64
            lib.tb_pl_accept_prepares.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(_U8P), _U64P,
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_int, _U8P,
                _U8P, ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                _U8P, ctypes.c_uint64, _U64P, _U64P, _U64P, _U8P, _U64P,
            ]
            lib.tb_pl_on_acks.restype = ctypes.c_int64
            lib.tb_pl_on_acks.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32, _I64P,
            ]
            lib.tb_pl_commit_ready_run.restype = ctypes.c_uint64
            lib.tb_pl_commit_ready_run.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32,
            ]
        except AttributeError:
            lib.tb_pl_abi_version = None
        _lib = lib
        _lib_failed = False
        # Push the envcheck-validated pool sizing down at load: C
        # never reads the environment itself (the tbcheck envcheck
        # rule), and every later crossing inherits the lanes.
        if lib.tb_hash_configure is not None:
            lib.tb_hash_configure(envcheck.hash_threads(), 0)
        return _lib


def decode_store(events: np.ndarray, n: int, ts_base: int,
                 cols: dict, lo: int) -> None:
    """One C pass: wire Transfer records -> contiguous store columns
    written in place at cols[name][lo:lo+n] (tpu.py _STORE_FIELDS
    minus dr/cr slots).  PRECONDITION: every event applied — callers
    with failures take the shared slow path.  `events` is the
    contiguous wire-record array (read-only frombuffer views are fine
    — the C side only reads)."""
    lib = _load()
    assert lib is not None
    assert events.flags["C_CONTIGUOUS"]

    def at(name, ptype):
        arr = cols[name]
        return ctypes.cast(
            arr.ctypes.data + lo * arr.dtype.itemsize, ptype
        )

    lib.tb_fp_decode_store(
        ctypes.cast(events.__array_interface__["data"][0], _U8P),
        n, ts_base,
        at("id_lo", _U64P), at("id_hi", _U64P),
        at("amount_lo", _U64P), at("amount_hi", _U64P),
        at("pending_lo", _U64P), at("pending_hi", _U64P),
        at("ud128_lo", _U64P), at("ud128_hi", _U64P), at("ud64", _U64P),
        at("ud32", _U32P), at("timeout", _U32P), at("ledger", _U32P),
        at("code", _U32P), at("flags", _U32P), at("timestamp", _U64P),
        at("status", _U8P),
    )


def _p(arr: np.ndarray, ptype):
    return arr.ctypes.data_as(ptype)


class _OwnedView(np.ndarray):
    """ndarray view that keeps its native owner alive (lifetime tie),
    propagated to any derived view via __array_finalize__."""

    _owner = None

    def __array_finalize__(self, obj):
        if obj is not None:
            self._owner = getattr(obj, "_owner", None)


class NativeFastpath:
    """One native fast-path instance per TpuStateMachine."""

    def __init__(self, account_capacity: int) -> None:
        lib = _load()
        assert lib is not None
        self._lib = lib
        self._fp = lib.tb_fp_create(account_capacity)
        self.capacity = account_capacity
        # Zero-copy numpy views over the native balance mirror.  The
        # views hold a reference back to this object so the native
        # buffers cannot be freed while any view (e.g. the Python
        # BalanceMirror) is still alive.
        self.lo = np.ctypeslib.as_array(
            lib.tb_fp_balances_lo(self._fp), shape=(account_capacity, 4)
        ).view(_OwnedView)
        self.lo._owner = self
        self.hi = np.ctypeslib.as_array(
            lib.tb_fp_balances_hi(self._fp), shape=(account_capacity, 4)
        ).view(_OwnedView)
        self.hi._owner = self
        # Reusable output buffers (sized for the largest batch).
        n_max = 8192
        self._results = np.empty(n_max, np.uint32)
        self._dr_slot = np.empty(n_max, np.int32)
        self._cr_slot = np.empty(n_max, np.int32)
        # Deltas are bounded both by touched columns (4/account) and by
        # 4 per event (a post/void touches dp+dpo and cp+cpo).
        d_max = min(4 * account_capacity, 4 * n_max) + 8
        self._dslot = np.empty(d_max, np.int64)
        self._dcol = np.empty(d_max, np.int64)
        self._dlo = np.empty(d_max, np.uint64)
        self._dhi = np.empty(d_max, np.uint64)
        self._ndeltas = ctypes.c_uint32(0)
        self._packed = None
        self._field_dtypes = None
        self._last_applied = ctypes.c_int32(-1)
        # Two-phase resolver outputs (reused per call).
        self._tp_amt_lo = np.empty(n_max, np.uint64)
        self._tp_amt_hi = np.empty(n_max, np.uint64)
        self._tp_ud128_lo = np.empty(n_max, np.uint64)
        self._tp_ud128_hi = np.empty(n_max, np.uint64)
        self._tp_ud64 = np.empty(n_max, np.uint64)
        self._tp_ud32 = np.empty(n_max, np.uint32)
        self._tp_ledger = np.empty(n_max, np.uint32)
        self._tp_code = np.empty(n_max, np.uint32)
        self._tp_inb = np.empty(n_max, np.uint32)
        self._tp_dur_rows = np.empty(n_max, np.int64)
        self._tp_dur_status = np.empty(n_max, np.uint32)
        self._tp_ndur = ctypes.c_uint32(0)
        self._tp_empty_u64 = np.zeros(n_max, np.uint64)
        self._tp_empty_u32 = np.zeros(n_max, np.uint32)
        self._tp_empty_i32 = np.full(n_max, -1, np.int32)
        self._tp_empty_i64 = np.full(n_max, -1, np.int64)

    def __del__(self):
        if getattr(self, "_fp", None):
            self._lib.tb_fp_destroy(self._fp)
            self._fp = None

    def add_accounts(self, id_lo, id_hi, flags, ledger, base_slot: int) -> None:
        id_lo = np.ascontiguousarray(id_lo, np.uint64)
        id_hi = np.ascontiguousarray(id_hi, np.uint64)
        flags = np.ascontiguousarray(flags, np.uint32)
        ledger = np.ascontiguousarray(ledger, np.uint32)
        self._lib.tb_fp_add_accounts(
            self._fp, _p(id_lo, _U64P), _p(id_hi, _U64P),
            _p(flags, _U32P), _p(ledger, _U32P), len(id_lo), base_slot,
        )

    def remove_accounts(self, id_lo, id_hi) -> None:
        id_lo = np.ascontiguousarray(id_lo, np.uint64)
        id_hi = np.ascontiguousarray(id_hi, np.uint64)
        self._lib.tb_fp_remove_accounts(
            self._fp, _p(id_lo, _U64P), _p(id_hi, _U64P), len(id_lo)
        )

    def add_transfer_ids(self, id_lo, id_hi, base_row: int) -> None:
        id_lo = np.ascontiguousarray(id_lo, np.uint64)
        id_hi = np.ascontiguousarray(id_hi, np.uint64)
        self._lib.tb_fp_add_transfer_ids(
            self._fp, _p(id_lo, _U64P), _p(id_hi, _U64P), base_row, len(id_lo)
        )

    def remove_transfer_ids(self, id_lo, id_hi) -> None:
        id_lo = np.ascontiguousarray(id_lo, np.uint64)
        id_hi = np.ascontiguousarray(id_hi, np.uint64)
        self._lib.tb_fp_remove_transfer_ids(
            self._fp, _p(id_lo, _U64P), _p(id_hi, _U64P), len(id_lo)
        )

    def peek_transfer_ids(self, id_lo, id_hi):
        """Read-only, for tests and counters: -> (found, values, runs
        the transfer-id directory holds, ids in its hash)."""
        id_lo = np.ascontiguousarray(id_lo, np.uint64)
        id_hi = np.ascontiguousarray(id_hi, np.uint64)
        found = np.zeros(len(id_lo), np.uint8)
        values = np.zeros(len(id_lo), np.uint64)
        counts = np.zeros(2, np.uint64)
        self._lib.tb_fp_peek_transfer_ids(
            self._fp, _p(id_lo, _U64P), _p(id_hi, _U64P), len(id_lo),
            _p(found, _U8P), _p(values, _U64P), _p(counts, _U64P),
        )
        return found.astype(bool), values, int(counts[0]), int(counts[1])

    def commit_exact(self, ev: dict, field_order, dstat_init, B: int,
                     n: int, ts_base: int):
        """Serial exact engine (native/tb_exact.inc): same inputs and
        packed-output layout as the JAX scan kernel, so the caller
        unpacks with kernel.unpack_outputs.  Mutates the shared mirror;
        returns (packed (B, N_COLS) u64, deltas views) — the packed
        buffer is reused per call (the engine fully overwrites rows
        [0, B))."""
        from tigerbeetle_tpu.state_machine import kernel

        dtypes = self._field_dtypes
        if dtypes is None:
            dtypes = self._field_dtypes = [
                np.dtype(dt) for _name, dt in field_order
            ]
        arrays = []
        ptrs = (ctypes.c_void_p * len(field_order))()
        for k, (name, _dt) in enumerate(field_order):
            a = np.ascontiguousarray(ev[name], dtypes[k])
            arrays.append(a)  # keep alive for the call
            ptrs[k] = a.ctypes.data

        dstat = np.ascontiguousarray(dstat_init, np.uint32)
        packed = self._packed
        if packed is None or packed.shape[0] < B:
            packed = self._packed = np.empty(
                (max(B, 8192), kernel.N_COLS), np.uint64
            )
        packed = packed[:B]
        rc = self._lib.tb_fp_commit_exact(
            self._fp, ptrs, len(field_order), _p(dstat, _U32P), B, n, ts_base,
            kernel.N_COLS,
            _p(packed, _U64P), _p(self._dslot, _I64P), _p(self._dcol, _I64P),
            _p(self._dlo, _U64P), _p(self._dhi, _U64P),
            ctypes.byref(self._ndeltas),
        )
        assert rc == 0, f"exact engine field-order skew ({rc})"
        k = self._ndeltas.value
        return packed, (
            self._dslot[:k], self._dcol[:k], self._dlo[:k], self._dhi[:k]
        )

    def commit_transfers(self, body: bytes, n: int, ts_base: int):
        """-> None (fallback) or (results, dr_slot, cr_slot,
        (dslot, dcol, dlo, dhi)) — views into reusable buffers, valid
        until the next call."""
        if n > len(self._results):
            return None  # oversized batch: take the exact path
        # Zero-copy pointer into the immutable bytes object (the C side
        # only reads).
        buf = ctypes.cast(ctypes.c_char_p(body), _U8P)
        rc = self._lib.tb_fp_commit_transfers(
            self._fp, buf, n, ts_base,
            _p(self._results, _U32P), _p(self._dr_slot, _I32P),
            _p(self._cr_slot, _I32P), _p(self._dslot, _I64P),
            _p(self._dcol, _I64P), _p(self._dlo, _U64P),
            _p(self._dhi, _U64P), ctypes.byref(self._ndeltas),
        )
        if rc != 0:
            return None
        k = self._ndeltas.value
        return (
            self._results[:n], self._dr_slot[:n], self._cr_slot[:n],
            (self._dslot[:k], self._dcol[:k], self._dlo[:k], self._dhi[:k]),
        )


    def commit_linked(self, body: bytes, n: int, ts_base: int):
        """Serial native resolver for linked-chain / limit-account
        batches (native/tb_linked.inc).  -> None (fallback) or
        (results, dr_slot, cr_slot, deltas, last_applied)."""
        if n > len(self._results):
            return None
        buf = ctypes.cast(ctypes.c_char_p(body), _U8P)
        rc = self._lib.tb_fp_commit_linked(
            self._fp, buf, n, ts_base,
            _p(self._results, _U32P), _p(self._dr_slot, _I32P),
            _p(self._cr_slot, _I32P), _p(self._dslot, _I64P),
            _p(self._dcol, _I64P), _p(self._dlo, _U64P),
            _p(self._dhi, _U64P), ctypes.byref(self._ndeltas),
            ctypes.byref(self._last_applied),
        )
        if rc != 0:
            return None
        k = self._ndeltas.value
        return (
            self._results[:n], self._dr_slot[:n], self._cr_slot[:n],
            (self._dslot[:k], self._dcol[:k], self._dlo[:k], self._dhi[:k]),
            int(self._last_applied.value),
        )

    def commit_two_phase(self, body: bytes, n: int, ts_base: int,
                         join: dict | None):
        """Serial native resolver for two-phase batches
        (native/tb_two_phase.inc).  `join` carries the durable pending
        targets' columns (None when the batch references none);
        -> None (fallback) or a dict of output views valid until the
        next native call."""
        if n > len(self._results):
            return None
        buf = ctypes.cast(ctypes.c_char_p(body), _U8P)
        if join is None:
            j_row = self._tp_empty_i64
            j_flags = j_ledger = j_code = j_ud32 = j_timeout = j_status = (
                self._tp_empty_u32
            )
            j_dr = j_cr = self._tp_empty_i32
            j_amt_lo = j_amt_hi = j_u128lo = j_u128hi = j_ud64 = (
                self._tp_empty_u64
            )
        else:
            j_row = np.ascontiguousarray(join["row"], np.int64)
            j_flags = np.ascontiguousarray(join["flags"], np.uint32)
            j_dr = np.ascontiguousarray(join["dr_slot"], np.int32)
            j_cr = np.ascontiguousarray(join["cr_slot"], np.int32)
            j_amt_lo = np.ascontiguousarray(join["amount_lo"], np.uint64)
            j_amt_hi = np.ascontiguousarray(join["amount_hi"], np.uint64)
            j_ledger = np.ascontiguousarray(join["ledger"], np.uint32)
            j_code = np.ascontiguousarray(join["code"], np.uint32)
            j_u128lo = np.ascontiguousarray(join["ud128_lo"], np.uint64)
            j_u128hi = np.ascontiguousarray(join["ud128_hi"], np.uint64)
            j_ud64 = np.ascontiguousarray(join["ud64"], np.uint64)
            j_ud32 = np.ascontiguousarray(join["ud32"], np.uint32)
            j_timeout = np.ascontiguousarray(join["timeout"], np.uint32)
            j_status = np.ascontiguousarray(join["status"], np.uint32)
        rc = self._lib.tb_fp_commit_two_phase(
            self._fp, buf, n, ts_base,
            _p(j_row, _I64P), _p(j_flags, _U32P), _p(j_dr, _I32P),
            _p(j_cr, _I32P), _p(j_amt_lo, _U64P), _p(j_amt_hi, _U64P),
            _p(j_ledger, _U32P), _p(j_code, _U32P), _p(j_u128lo, _U64P),
            _p(j_u128hi, _U64P), _p(j_ud64, _U64P), _p(j_ud32, _U32P),
            _p(j_timeout, _U32P), _p(j_status, _U32P),
            _p(self._results, _U32P), _p(self._dr_slot, _I32P),
            _p(self._cr_slot, _I32P), _p(self._tp_amt_lo, _U64P),
            _p(self._tp_amt_hi, _U64P), _p(self._tp_ud128_lo, _U64P),
            _p(self._tp_ud128_hi, _U64P), _p(self._tp_ud64, _U64P),
            _p(self._tp_ud32, _U32P), _p(self._tp_ledger, _U32P),
            _p(self._tp_code, _U32P), _p(self._tp_inb, _U32P),
            _p(self._tp_dur_rows, _I64P), _p(self._tp_dur_status, _U32P),
            ctypes.byref(self._tp_ndur),
            _p(self._dslot, _I64P), _p(self._dcol, _I64P),
            _p(self._dlo, _U64P), _p(self._dhi, _U64P),
            ctypes.byref(self._ndeltas), ctypes.byref(self._last_applied),
        )
        if rc != 0:
            return None
        k = self._ndeltas.value
        nd = self._tp_ndur.value
        return {
            "results": self._results[:n],
            "row_dr": self._dr_slot[:n],
            "row_cr": self._cr_slot[:n],
            "amt_lo": self._tp_amt_lo[:n],
            "amt_hi": self._tp_amt_hi[:n],
            "ud128_lo": self._tp_ud128_lo[:n],
            "ud128_hi": self._tp_ud128_hi[:n],
            "ud64": self._tp_ud64[:n],
            "ud32": self._tp_ud32[:n],
            "ledger": self._tp_ledger[:n],
            "code": self._tp_code[:n],
            "inb_status": self._tp_inb[:n],
            "dur_rows": self._tp_dur_rows[:nd],
            "dur_status": self._tp_dur_status[:nd],
            "deltas": (
                self._dslot[:k], self._dcol[:k], self._dlo[:k], self._dhi[:k]
            ),
            "last_applied": int(self._last_applied.value),
        }


def available() -> bool:
    return _load() is not None


# ----------------------------------------------------------------------
# Columnar ingest: batch frame verification + batch reply finalize
# (the server-drain half of the fast path — runtime/server.py).


def batch_verify_available() -> bool:
    lib = _load()
    return lib is not None and getattr(
        lib, "tb_fp_verify_frames", None
    ) is not None


def verify_frames(arena: np.ndarray, offsets: np.ndarray,
                  lens: np.ndarray, n: int):
    """One native pass over `n` frames packed in `arena`: header +
    body checksums, version, size — exactly wire.verify_header per
    frame.  -> u8 ok flags, or None when the native library lacks the
    symbol (caller takes the vectorized Python fallback).  The flag
    buffer is allocated per call: several buses poll concurrently in
    one process (in-process test clusters, router + shards) and
    ctypes releases the GIL during the C pass — a shared module
    buffer raced."""
    lib = _load()
    if lib is None or getattr(lib, "tb_fp_verify_frames", None) is None:
        return None
    ok = np.empty(n, np.uint8)
    offsets = np.ascontiguousarray(offsets[:n], np.uint64)
    lens = np.ascontiguousarray(lens[:n], np.uint32)
    lib.tb_fp_verify_frames(
        ctypes.cast(arena.ctypes.data, _U8P),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        _p(lens, _U32P), n, _p(ok, _U8P),
    )
    return ok


def verify_frames2(arena: np.ndarray, offsets: np.ndarray,
                   lens: np.ndarray, n: int):
    """Counted r23 verify: same contract as verify_frames, plus the
    call opens a new digest-table crossing (verified body digests are
    cached for the build seams, the previous drain's entries die) and
    returns the body bytes hashed.  -> (ok u8 flags, bytes_hashed), or
    None when the library lacks the r23 symbols."""
    lib = _load()
    if lib is None or getattr(lib, "tb_fp_verify_frames2", None) is None:
        return None
    ok = np.empty(n, np.uint8)
    offsets = np.ascontiguousarray(offsets[:n], np.uint64)
    lens = np.ascontiguousarray(lens[:n], np.uint32)
    bytes_hashed = lib.tb_fp_verify_frames2(
        ctypes.cast(arena.ctypes.data, _U8P),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        _p(lens, _U32P), n, _p(ok, _U8P),
    )
    return ok, int(bytes_hashed)


def verify_frames_py2(arena: np.ndarray, offsets: np.ndarray,
                      lens: np.ndarray, n: int,
                      hdrs: np.ndarray | None = None):
    """Pure-Python vectorized fallback: structural checks (version,
    size) in one numpy pass, checksums per frame via hashlib (C-speed
    SHA-256 — the same hashes the legacy path paid, minus its
    per-message numpy/dispatch churn).  Pass `hdrs` when the caller
    already gathered the header records (verify_and_gather) so the
    fallback arm doesn't pay the gather twice.  Returns (ok u8 flags,
    body bytes hashed) — the byte count matches the native pass by
    construction (a frame failing the header checksum never reaches
    its body hash)."""
    from tigerbeetle_tpu.vsr import wire

    if hdrs is None:
        hdrs = wire.headers_from_arena(arena, offsets, n)
    ok = (
        (hdrs["version"] == wire.VERSION)
        & (hdrs["size"] == lens[:n])
        & (lens[:n] >= np.uint32(256))
    )
    bytes_hashed = 0
    mv = memoryview(arena)  # zero-copy per-frame slices
    for i in np.nonzero(ok)[0]:
        off = int(offsets[i])
        size = int(lens[i])
        frame = mv[off : off + size]
        c = wire.checksum(frame[16:256])
        if (
            int(hdrs[i]["checksum_lo"]) != c & 0xFFFFFFFFFFFFFFFF
            or int(hdrs[i]["checksum_hi"]) != c >> 64
        ):
            ok[i] = False
            continue
        bytes_hashed += size - 256
        cb = wire.checksum(frame[256:])
        if (
            int(hdrs[i]["checksum_body_lo"]) != cb & 0xFFFFFFFFFFFFFFFF
            or int(hdrs[i]["checksum_body_hi"]) != cb >> 64
        ):
            ok[i] = False
    return ok.astype(np.uint8), bytes_hashed


def verify_frames_py(arena: np.ndarray, offsets: np.ndarray,
                     lens: np.ndarray, n: int,
                     hdrs: np.ndarray | None = None) -> np.ndarray:
    """verify_frames_py2 without the byte count (r20 signature)."""
    return verify_frames_py2(arena, offsets, lens, n, hdrs=hdrs)[0]


def verify_and_gather(arena: np.ndarray, moffs: np.ndarray,
                      mlens: np.ndarray):
    """The shared drain-decode sequence (server dispatch + open-loop
    client completions): one batch checksum pass over the message
    frames — native, or the vectorized Python fallback — plus one
    vectorized header gather.  -> (ok u8 flags, (n,) HEADER_DTYPE
    records, native bool, body bytes hashed).  bytes_hashed is None
    only on the stale-.so corner (old uncounted symbol present, new
    one absent) — callers skip the counter rather than guess."""
    from tigerbeetle_tpu.vsr import wire

    n = len(moffs)
    hdrs = wire.headers_from_arena(arena, moffs, n)
    res = verify_frames2(arena, moffs, mlens, n)
    if res is not None:
        ok, bytes_hashed = res
        return ok, hdrs, True, bytes_hashed
    ok = verify_frames(arena, moffs, mlens, n)
    if ok is not None:
        return ok, hdrs, True, None
    ok, bytes_hashed = verify_frames_py2(arena, moffs, mlens, n, hdrs=hdrs)
    return ok, hdrs, False, bytes_hashed


# ----------------------------------------------------------------------
# Native commit pipeline (round 20): per-prepare header construction,
# journal append framing, and the primary's in-flight slot table live
# in native/tb_pipeline.cpp; VsrReplica (vsr/multi.py) keeps view
# changes, checkpoints, and recovery.  The differential contract is
# absolute: TB_NATIVE_PIPELINE=0/1 must produce bit-identical frames.

# Expected tb_pl_abi_version().  Bump in lockstep with
# native/tb_pipeline.cpp whenever any tb_pl_* signature changes.
# ABI 2 = the r22 C-resident drain loop batch family
# (tb_pl_build_prepares / tb_pl_accept_prepares / tb_pl_on_acks /
# tb_pl_commit_ready_run).  ABI 3 = the r23 hash-once commit path:
# tb_pl_build_prepare / tb_pl_build_prepares grew a digest-reuse
# flags word, and the library carries the hash pool + counted verify
# (tb_fp_verify_frames2 / tb_hash_configure / tb_hash_engine /
# tb_hash_stats).
PIPELINE_ABI = 3

_PIPELINE_HINT = (
    "libtb_fastpath.so is stale (missing/mismatched tb_pl_* pipeline "
    "symbols) and the automatic rebuild did not replace it — run "
    "`make -C native` (or `make -C native asan` under "
    "TB_NATIVE_SANITIZE=asan) and check runtime/native.py build_error()"
)
_pipeline_warned = False


def pipeline_error() -> str | None:
    """Why the native pipeline is unavailable even though the fastpath
    library loaded (stale-.so forensics), else None."""
    lib = _load()
    if lib is None:
        return None  # no library at all: the normal pure-Python path
    if getattr(lib, "tb_pl_abi_version", None) is None:
        return _PIPELINE_HINT
    got = int(lib.tb_pl_abi_version())
    if got != PIPELINE_ABI:
        return (
            f"libtb_fastpath.so pipeline ABI {got} != expected "
            f"{PIPELINE_ABI} — {_PIPELINE_HINT}"
        )
    return None


def pipeline_available() -> bool:
    lib = _load()
    return lib is not None and pipeline_error() is None


def drain_error() -> str | None:
    """Why the r22 C-resident drain loop is unavailable even though
    the fastpath library loaded (stale-.so forensics extended to the
    batch symbols), else None.  A library missing any batch symbol
    also reports pipeline ABI != 3, so this usually collapses into
    pipeline_error(); the getattr probe is belt and braces."""
    err = pipeline_error()
    if err is not None:
        return err
    lib = _load()
    if lib is None:
        return None
    if getattr(lib, "tb_pl_build_prepares", None) is None:
        return _PIPELINE_HINT
    return None


def drain_available() -> bool:
    lib = _load()
    return lib is not None and drain_error() is None


def create_pipeline():
    """A NativePipeline for one VsrReplica, or None when the native
    library is absent (pure-Python fallback).  A LOADED-BUT-STALE
    library fails fast: RuntimeError with the rebuild hint when the
    operator explicitly demanded TB_NATIVE_PIPELINE=1, a one-shot
    RuntimeWarning + fallback when the knob was defaulted."""
    global _pipeline_warned
    lib = _load()
    if lib is None:
        return None
    err = pipeline_error()
    if err is not None:
        if envcheck.env_is_set("TB_NATIVE_PIPELINE"):
            raise RuntimeError(err)
        if not _pipeline_warned:
            _pipeline_warned = True
            import warnings

            warnings.warn(
                f"native pipeline unavailable ({err}); "
                "falling back to the Python per-prepare path",
                RuntimeWarning, stacklevel=2,
            )
        return None
    return NativePipeline(lib)


class NativePipeline:
    """One native in-flight slot table + header builder per replica.

    Headers cross the boundary as raw 256-byte buffers; built headers
    come back as fresh HEADER_DTYPE records (bit-identical to the
    wire.make_header/copy_trace/finalize_header sequence)."""

    def __init__(self, lib) -> None:
        from tigerbeetle_tpu.vsr.wire import HEADER_DTYPE

        self._lib = lib
        self._pl = lib.tb_pl_create()
        assert self._pl, "tb_pl_create failed"
        self._dtype = HEADER_DTYPE

    def __del__(self):  # noqa: D105
        try:
            if getattr(self, "_pl", None):
                self._lib.tb_pl_destroy(self._pl)
                self._pl = None
        # tbcheck: allow(broad-except): __del__ at interpreter
        # teardown — the lib handle may already be gone.
        except Exception:
            pass

    def build_prepare(self, request: np.void, body: bytes, *, cluster: int,
                      view: int, op: int, commit: int, timestamp: int,
                      parent: int, replica: int, context: int,
                      release: int, reuse: bool = False) -> np.void:
        out = np.empty(1, self._dtype)
        self._lib.tb_pl_build_prepare(
            request.tobytes(), body, len(body),
            cluster & 0xFFFFFFFFFFFFFFFF, cluster >> 64, view, op,
            commit, timestamp, parent & 0xFFFFFFFFFFFFFFFF, parent >> 64,
            replica, context, release, 1 if reuse else 0,
            ctypes.cast(out.ctypes.data, _U8P),
        )
        return out[0]

    def build_prepare_ok(self, prepare: np.void, view: int,
                         replica: int) -> np.void:
        out = np.empty(1, self._dtype)
        self._lib.tb_pl_build_prepare_ok(
            prepare.tobytes(), view, replica,
            ctypes.cast(out.ctypes.data, _U8P),
        )
        return out[0]

    def note_prepare(self, header: np.void, synced: bool,
                     self_replica: int) -> None:
        self._lib.tb_pl_note_prepare(
            self._pl, header.tobytes(), 1 if synced else 0, self_replica
        )

    def on_ack(self, header: np.void) -> int | None:
        """Vote count after recording the ack, or None when the op has
        no in-flight entry / the checksum names a stale sibling — the
        same cases _on_prepare_ok drops."""
        votes = self._lib.tb_pl_on_ack(self._pl, header.tobytes())
        return None if votes < 0 else int(votes)

    def on_acks(self, headers: np.ndarray, cluster: int,
                view: int) -> tuple[int, np.ndarray]:
        """Vote a contiguous run of prepare_ok headers in one call
        (r22).  Returns (accepted_count, verdicts) where verdicts[i]
        is the entry's vote count after ack i, or negative for the
        drops the per-ack path also takes: -4 foreign cluster, -3
        stale/future view, -1 unknown op, -2 stale-sibling checksum."""
        k = len(headers)
        assert headers.dtype.itemsize == 256
        out = np.empty(k, np.int64)
        accepted = self._lib.tb_pl_on_acks(
            self._pl, headers.tobytes(), k,
            cluster & 0xFFFFFFFFFFFFFFFF, cluster >> 64, view,
            _p(out, _I64P),
        )
        return int(accepted), out

    def commit_ready_run(self, commit_min: int, quorum: int) -> int:
        """Length of the contiguous commit-ready run above commit_min
        — tb_pl_commit_ready extended to the whole drain (r22)."""
        return int(
            self._lib.tb_pl_commit_ready_run(self._pl, commit_min, quorum)
        )

    def mark_all_synced(self) -> None:
        self._lib.tb_pl_mark_all_synced(self._pl)

    def set_synced(self, op: int, synced: bool) -> bool:
        return self._lib.tb_pl_set_synced(
            self._pl, op, 1 if synced else 0
        ) == 0

    def drop(self, op: int) -> None:
        self._lib.tb_pl_drop(self._pl, op)

    def commit_ready(self, commit_min: int, quorum: int) -> bool:
        return bool(self._lib.tb_pl_commit_ready(self._pl, commit_min, quorum))

    def votes(self, op: int) -> int:
        return int(self._lib.tb_pl_votes(self._pl, op))

    def reset(self) -> None:
        self._lib.tb_pl_reset(self._pl)

    def size(self) -> int:
        return int(self._lib.tb_pl_size(self._pl))


def frame_prepare(header: np.void, body: bytes, headers_ring: np.ndarray,
                  slot: int, headers_per_sector: int, sector_size: int,
                  out_prepare: np.ndarray, out_sector: np.ndarray) -> int:
    """Journal append framing in one C pass: builds the sector-padded
    prepare buffer into `out_prepare` (returns the padded length),
    writes `headers_ring[slot] = header` in place, and builds the
    slot's redundant-header sector into `out_sector` — byte-identical
    to journal.write_prepare's Python framing.  Caller guarantees the
    library is loaded (pipeline_available())."""
    lib = _load()
    assert headers_ring.flags["C_CONTIGUOUS"]
    return int(lib.tb_pl_frame_prepare(
        header.tobytes(), body, len(body),
        ctypes.cast(headers_ring.ctypes.data, _U8P), slot,
        headers_per_sector, sector_size,
        ctypes.cast(out_prepare.ctypes.data, _U8P),
        ctypes.cast(out_sector.ctypes.data, _U8P),
    ))


def _padded_total(body_lens: np.ndarray, sector_size: int) -> int:
    """Sum of sector-padded prepare sizes — sized exactly like the C
    side's capacity check so a successful allocation here can never
    overflow there."""
    msgs = body_lens + np.uint64(256 + sector_size - 1)
    return int((msgs // np.uint64(sector_size)).sum()) * sector_size


def build_prepares(pl: NativePipeline, req_hdrs: np.ndarray, bodies: list,
                   timestamps: np.ndarray, contexts: np.ndarray, *,
                   cluster: int, view: int, op0: int, commit: int,
                   parent: int, replica: int, release: int, synced: bool,
                   headers_ring: np.ndarray, slot_count: int,
                   headers_per_sector: int, sector_size: int,
                   reuse: bool = False):
    """One C call for a whole drain's prepare builds (r22): K headers
    chained parent->checksum, registered in the slot table with the
    self-vote, and framed for the journal.  Returns (prepares, frames)
    where `prepares` is a (K,) HEADER_DTYPE array and `frames` is the
    WAL write-descriptor tuple (wal_arena, wal_off, wal_len, slots,
    sector_arena, sector_index), or None on arena overflow (caller
    loops the per-prepare path; nothing was mutated)."""
    lib = _load()
    k = len(bodies)
    assert req_hdrs.dtype.itemsize == 256 and req_hdrs.flags["C_CONTIGUOUS"]
    assert headers_ring.flags["C_CONTIGUOUS"]
    ptrs = (_U8P * k)(
        *[ctypes.cast(ctypes.c_char_p(b), _U8P) for b in bodies]
    )
    blens = np.array([len(b) for b in bodies], np.uint64)
    ts = np.ascontiguousarray(timestamps, np.uint64)
    ctx = np.ascontiguousarray(contexts, np.uint64)
    from tigerbeetle_tpu.vsr.wire import HEADER_DTYPE

    prepares = np.empty(k, HEADER_DTYPE)
    wal_arena = np.zeros(_padded_total(blens, sector_size), np.uint8)
    sector_arena = np.zeros(k * sector_size, np.uint8)
    wal_off = np.empty(k, np.uint64)
    wal_len = np.empty(k, np.uint64)
    slots = np.empty(k, np.uint64)
    sector_index = np.empty(k, np.uint64)
    rc = lib.tb_pl_build_prepares(
        pl._pl, req_hdrs.tobytes(), ptrs, _p(blens, _U64P),
        _p(ts, _U64P), _p(ctx, _U64P), k,
        cluster & 0xFFFFFFFFFFFFFFFF, cluster >> 64, view, op0, commit,
        parent & 0xFFFFFFFFFFFFFFFF, parent >> 64, replica, release,
        1 if synced else 0, 1 if reuse else 0,
        ctypes.cast(prepares.ctypes.data, _U8P),
        ctypes.cast(headers_ring.ctypes.data, _U8P), slot_count,
        headers_per_sector, sector_size,
        ctypes.cast(wal_arena.ctypes.data, _U8P), len(wal_arena),
        _p(wal_off, _U64P), _p(wal_len, _U64P), _p(slots, _U64P),
        ctypes.cast(sector_arena.ctypes.data, _U8P),
        _p(sector_index, _U64P),
    )
    if rc < 0:
        return None
    return prepares, (wal_arena, wal_off, wal_len, slots, sector_arena,
                      sector_index)


def accept_prepares(hdrs: np.ndarray, bodies: list, *, view: int,
                    replica: int, build_oks: bool,
                    headers_ring: np.ndarray, slot_count: int,
                    headers_per_sector: int, sector_size: int):
    """One C call for a backup drain's accepted-prepare run (r22):
    frame K prepares for the journal and build their prepare_ok
    headers.  Returns (oks, frames) — `oks` a (K,) HEADER_DTYPE array
    (contents undefined when build_oks=False) and `frames` as in
    build_prepares — or None on arena overflow (nothing mutated)."""
    lib = _load()
    k = len(bodies)
    assert hdrs.dtype.itemsize == 256 and hdrs.flags["C_CONTIGUOUS"]
    assert headers_ring.flags["C_CONTIGUOUS"]
    ptrs = (_U8P * k)(
        *[ctypes.cast(ctypes.c_char_p(b), _U8P) for b in bodies]
    )
    blens = np.array([len(b) for b in bodies], np.uint64)
    from tigerbeetle_tpu.vsr.wire import HEADER_DTYPE

    oks = np.empty(k, HEADER_DTYPE)
    wal_arena = np.zeros(_padded_total(blens, sector_size), np.uint8)
    sector_arena = np.zeros(k * sector_size, np.uint8)
    wal_off = np.empty(k, np.uint64)
    wal_len = np.empty(k, np.uint64)
    slots = np.empty(k, np.uint64)
    sector_index = np.empty(k, np.uint64)
    rc = lib.tb_pl_accept_prepares(
        hdrs.tobytes(), ptrs, _p(blens, _U64P), k, view, replica,
        1 if build_oks else 0,
        ctypes.cast(oks.ctypes.data, _U8P),
        ctypes.cast(headers_ring.ctypes.data, _U8P), slot_count,
        headers_per_sector, sector_size,
        ctypes.cast(wal_arena.ctypes.data, _U8P), len(wal_arena),
        _p(wal_off, _U64P), _p(wal_len, _U64P), _p(slots, _U64P),
        ctypes.cast(sector_arena.ctypes.data, _U8P),
        _p(sector_index, _U64P),
    )
    if rc < 0:
        return None
    return oks, (wal_arena, wal_off, wal_len, slots, sector_arena,
                 sector_index)


def finalize_headers(headers: np.ndarray, bodies: list) -> bool:
    """Batch reply finalize: set size + checksum_body + checksum on
    each 256-byte header record in the contiguous `headers` array for
    its body in `bodies` — one C call instead of 2n hashlib calls.
    Returns False when the native symbol is unavailable (caller loops
    wire.finalize_header)."""
    lib = _load()
    if lib is None or getattr(lib, "tb_fp_finalize_headers", None) is None:
        return False
    n = len(headers)
    assert headers.dtype.itemsize == 256 and headers.flags["C_CONTIGUOUS"]
    assert len(bodies) == n
    ptrs = (_U8P * n)(
        *[ctypes.cast(ctypes.c_char_p(b), _U8P) for b in bodies]
    )
    blens = np.array([len(b) for b in bodies], np.uint32)
    lib.tb_fp_finalize_headers(
        ctypes.cast(headers.ctypes.data, _U8P), n, ptrs, _p(blens, _U32P)
    )
    return True


# ----------------------------------------------------------------------
# Hash-once commit path (round 23): pool configuration, engine
# identity, and the scalar-fallback forensics.

# tb_hash_engine() codes (native/sha256.h Sha256Engine).
HASH_ENGINE_NAMES = {1: "evp", 2: "sha256-legacy", 3: "scalar"}

_scalar_warned = False


def configure_hash(threads: int | None = None) -> bool:
    """(Re)apply the hash-pool lane count (default: the validated
    TB_HASH_THREADS); the SHA-256 engine tier auto-resolves.  Returns
    False when the library is absent or lacks the r23 symbols (inline
    hashlib/scalar hashing everywhere — nothing to configure)."""
    lib = _load()
    if lib is None or getattr(lib, "tb_hash_configure", None) is None:
        return False
    if threads is None:
        threads = envcheck.hash_threads()
    lib.tb_hash_configure(threads, 0)
    return True


def hash_engine_name() -> str:
    """Which SHA-256 implementation the native library dispatches to
    ("evp" = libcrypto EVP one-shot / SHA-NI, "sha256-legacy" =
    libcrypto's compat entry, "scalar" = the portable ~225 MB/s core),
    or "hashlib" when no native library serves the hot path (Python's
    hashlib — itself OpenSSL-backed).  The server gauges it as
    hash.engine_code so a number can never silently come from the
    wrong engine."""
    lib = _load()
    if lib is None or getattr(lib, "tb_hash_engine", None) is None:
        return "hashlib"
    return HASH_ENGINE_NAMES.get(int(lib.tb_hash_engine()), "unknown")


def hash_scalar_fallback() -> int:
    """1 when the native library resolved NEITHER libcrypto tier and
    every native checksum runs on the 225 MB/s scalar core — surfaced
    as the hash.scalar_fallback gauge plus a one-time RuntimeWarning
    (a silent 8x hash regression must never pass as a normal run)."""
    global _scalar_warned
    if hash_engine_name() != "scalar":
        return 0
    if not _scalar_warned:
        _scalar_warned = True
        import warnings

        warnings.warn(
            "native SHA-256 resolved neither libcrypto's EVP one-shot "
            "nor SHA256(): hashing runs on the ~225 MB/s scalar "
            "fallback core (expect ~8x slower checksums; install a "
            "libcrypto.so to restore SHA-NI dispatch)",
            RuntimeWarning, stacklevel=2,
        )
    return 1


def hash_stats() -> dict:
    """Process-global hash-pool counters: jobs executed on worker
    lanes (hash.lanes_busy), drain-scoped digest-table hits, and the
    configured lane count.  Zeros when the library lacks the r23
    symbols."""
    lib = _load()
    if lib is None or getattr(lib, "tb_hash_stats", None) is None:
        return {"lane_jobs": 0, "table_hits": 0, "threads": 0}
    out = np.zeros(3, np.uint64)
    lib.tb_hash_stats(_p(out, _U64P))
    return {
        "lane_jobs": int(out[0]),
        "table_hits": int(out[1]),
        "threads": int(out[2]),
    }
