"""ctypes bindings for the native host runtime (native/tb_runtime.cpp).

The C++ library provides the epoll event loop, the header-framed TCP
message bus, and the C-ABI client session (the reference's io /
message_bus / tb_client components, reference: src/io/linux.zig,
src/message_bus.zig, src/clients/c/tb_client.zig).  Python loads it
via ctypes; the library is built from the tracked sources on first
use (make -C native; no binary is tracked), and a failed build is an
error — never a quiet fall-back to a stale library or to pure Python.
Tests that want the pure-Python arms ask for them by name
(TB_FASTPATH_DISABLE, TB_NATIVE_PIPELINE=0, TB_NATIVE_DRAIN=0, ...).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
from tigerbeetle_tpu.envcheck import env_str as _env_str
from tigerbeetle_tpu.envcheck import native_sanitize as _native_sanitize

# Sanitizer flavor (TB_NATIVE_SANITIZE=asan): libraries load from
# native/asan/ (same basenames) and `make` targets the asan build —
# shared by this loader and runtime/fastpath.py so one knob flips
# BOTH libraries to their sanitized builds.
_SANITIZE = _native_sanitize()
_MAKE_TARGET = _SANITIZE or "all"


def _lib_dir() -> str:
    return (os.path.join(_NATIVE_DIR, _SANITIZE) if _SANITIZE
            else _NATIVE_DIR)


_LIB_PATH = _env_str(
    "TB_RUNTIME_LIB", os.path.join(_lib_dir(), "libtb_runtime.so")
)

_lib = None
_lib_failed = False  # negative cache: don't retry a failed dlopen
_lib_lock = threading.Lock()
# The make error tail, kept for build_error() and re-raised by every
# later load: the chip tool copies the tree as it stands on disk, so a
# library the sources did not just produce is a wrong program.
_build_error: str | None = None


class NativeBuildError(RuntimeError):
    """`make -C native` failed; the message carries the error tail."""


def build_error() -> str | None:
    """Tail of the native build failure, or None when the build was
    clean (or not attempted yet)."""
    return _build_error


_make_attempted = False


def _run_make() -> None:
    """Invoke make once per process and raise NativeBuildError on
    failure — then and on every later call.  One `make` covers both
    libraries (Makefile `all:`), so the runtime and fastpath loaders
    share a single attempt; the Makefile's dependency tracking makes
    it a no-op when the libraries are fresh."""
    global _build_error, _make_attempted
    if not _make_attempted:
        _make_attempted = True
        # The error names the sanitizer flavor attempted: a failing
        # `make asan` (no compiler-rt, say) must never read as a
        # failing release build — and vice versa.
        flavor = f"sanitizer={_SANITIZE or 'none'}"
        try:
            subprocess.run(
                ["make", "-C", _NATIVE_DIR, _MAKE_TARGET], check=True,
                capture_output=True, timeout=300,
            )
        except subprocess.CalledProcessError as exc:
            tail = (exc.stderr or exc.stdout or b"")[-800:].decode(
                "utf-8", "replace"
            )
            _build_error = (
                f"make -C native {_MAKE_TARGET} failed ({flavor}, "
                f"rc={exc.returncode}): {tail}"
            )
        except (OSError, subprocess.SubprocessError) as exc:
            _build_error = (
                f"make -C native {_MAKE_TARGET} failed ({flavor}): {exc!r}"
            )
    if _build_error is not None:
        raise NativeBuildError(_build_error)


class _Event(ctypes.Structure):
    _fields_ = [
        ("type", ctypes.c_int32),
        ("conn", ctypes.c_int32),
        ("data", ctypes.POINTER(ctypes.c_uint8)),
        ("len", ctypes.c_uint32),
    ]


EV_ACCEPTED, EV_CONNECTED, EV_MESSAGE, EV_CLOSED = 1, 2, 3, 4


def _load():
    global _lib, _lib_failed
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _lib_failed:
            return None
        # Always invoke make: a no-op when the library is fresh, a
        # rebuild when a source changed, NativeBuildError otherwise.
        _run_make()
        _lib_failed = True  # cleared on success below
        if not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None

        lib.tb_bus_create.restype = ctypes.c_void_p
        lib.tb_bus_create.argtypes = [ctypes.c_uint32]
        lib.tb_bus_destroy.argtypes = [ctypes.c_void_p]
        lib.tb_bus_listen.restype = ctypes.c_int
        lib.tb_bus_listen.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint16]
        lib.tb_bus_listen_port.restype = ctypes.c_int
        lib.tb_bus_listen_port.argtypes = [ctypes.c_void_p]
        lib.tb_bus_connect.restype = ctypes.c_int
        lib.tb_bus_connect.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint16]
        lib.tb_bus_send.restype = ctypes.c_int
        lib.tb_bus_send.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.tb_bus_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.tb_bus_poll.restype = ctypes.c_int
        lib.tb_bus_poll.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.tb_bus_next_event.restype = ctypes.c_int
        lib.tb_bus_next_event.argtypes = [ctypes.c_void_p, ctypes.POINTER(_Event)]
        lib.tb_client_init.restype = ctypes.c_void_p
        lib.tb_client_init.argtypes = [
            ctypes.c_char_p, ctypes.c_uint16, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.tb_client_deinit.argtypes = [ctypes.c_void_p]
        lib.tb_client_add_address.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint16,
        ]
        lib.tb_client_request.restype = ctypes.c_int64
        lib.tb_client_request.argtypes = [
            ctypes.c_void_p, ctypes.c_uint8, ctypes.c_char_p, ctypes.c_uint32,
            ctypes.c_char_p, ctypes.c_uint32, ctypes.c_int,
        ]
        lib.tb_checksum128.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64 * 2,
        ]
        # Columnar drain + scatter-gather send (may be absent from a
        # stale prebuilt .so when the rebuild failed — the bus then
        # reports unsupported and callers keep the per-event paths).
        try:
            lib.tb_bus_send2.restype = ctypes.c_int
            lib.tb_bus_send2.argtypes = [
                ctypes.c_void_p, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_char_p, ctypes.c_uint32,
            ]
        except AttributeError:
            lib.tb_bus_send2 = None
        try:
            lib.tb_bus_sendv.restype = ctypes.c_int
            lib.tb_bus_sendv.argtypes = [
                ctypes.c_void_p, ctypes.c_int,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint32,
            ]
        except AttributeError:
            lib.tb_bus_sendv = None
        try:
            lib.tb_bus_poll_drain.restype = ctypes.c_int
            lib.tb_bus_poll_drain.argtypes = [
                ctypes.c_void_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_int32,
            ]
        except AttributeError:
            lib.tb_bus_poll_drain = None
        _lib = lib
        _lib_failed = False
        return _lib


def native_available() -> bool:
    return _load() is not None


def native_checksum128(data: bytes) -> int:
    lib = _load()
    out = (ctypes.c_uint64 * 2)()
    lib.tb_checksum128(data, len(data), out)
    return int(out[0]) | (int(out[1]) << 64)


class NativeBus:
    """Event-loop TCP bus: listen/connect/send/poll."""

    def __init__(self, message_size_max: int = 1 << 20) -> None:
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native runtime unavailable")
        self._bus = self._lib.tb_bus_create(message_size_max)
        if not self._bus:
            raise RuntimeError("tb_bus_create failed")
        self._message_size_max = message_size_max
        self._drain_bufs = None

    @property
    def supports_drain(self) -> bool:
        return getattr(self._lib, "tb_bus_poll_drain", None) is not None

    def poll_drain(self, timeout_ms: int = 0, max_events: int = 4096):
        """Columnar drain: one C call copies every ready event into a
        reusable arena — `(n, types, conns, offsets, lens, arena)`
        numpy views, valid until the NEXT poll_drain/poll call.
        Message payloads are `arena[offsets[i]: offsets[i]+lens[i]]`;
        non-message events have len 0.  Returns None when the loaded
        library predates the symbol (callers keep the per-event poll
        path)."""
        import numpy as np

        if not self.supports_drain:
            return None
        bufs = self._drain_bufs
        if bufs is None or len(bufs[0]) < max_events:
            cap = max(
                4 << 20, 2 * (self._message_size_max + 256)
            )
            bufs = self._drain_bufs = (
                np.empty(max(max_events, 4096), np.int32),   # types
                np.empty(max(max_events, 4096), np.int32),   # conns
                np.empty(max(max_events, 4096), np.uint64),  # offsets
                np.empty(max(max_events, 4096), np.uint32),  # lens
                np.empty(cap, np.uint8),                     # arena
            )
        types, conns, offsets, lens, arena = bufs
        u8p = ctypes.POINTER(ctypes.c_uint8)
        n = self._lib.tb_bus_poll_drain(
            self._bus, timeout_ms,
            arena.ctypes.data_as(u8p), arena.nbytes,
            types.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            conns.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            min(max_events, len(types)),
        )
        return n, types, conns, offsets, lens, arena

    def listen(self, host: str, port: int) -> int:
        rc = self._lib.tb_bus_listen(self._bus, host.encode(), port)
        if rc != 0:
            raise OSError(f"listen {host}:{port} failed")
        return self._lib.tb_bus_listen_port(self._bus)

    def connect(self, host: str, port: int) -> int:
        conn = self._lib.tb_bus_connect(self._bus, host.encode(), port)
        if conn < 0:
            raise OSError(f"connect {host}:{port} failed")
        return conn

    def send(self, conn: int, data: bytes) -> None:
        self._lib.tb_bus_send(self._bus, conn, data, len(data))

    def send2(self, conn: int, head: bytes, body: bytes) -> None:
        """One queued message from two parts — no Python-side concat
        (a megabyte body saved one full copy per hop)."""
        if getattr(self._lib, "tb_bus_send2", None) is None:
            self.send(conn, head + body)
            return
        self._lib.tb_bus_send2(
            self._bus, conn, head, len(head), body, len(body)
        )

    def sendv(self, conn: int, frames: list[bytes]) -> None:
        """Queue a run of complete frames for one connection in a
        single crossing (r22 drain loop: the backup's per-drain
        prepare_ok burst).  Falls back to per-frame sends when the
        loaded library predates the symbol."""
        if getattr(self._lib, "tb_bus_sendv", None) is None:
            for f in frames:
                self.send(conn, f)
            return
        u8p = ctypes.POINTER(ctypes.c_uint8)
        k = len(frames)
        bufs = (u8p * k)(
            *[ctypes.cast(ctypes.c_char_p(f), u8p) for f in frames]
        )
        lens = (ctypes.c_uint32 * k)(*[len(f) for f in frames])
        self._lib.tb_bus_sendv(self._bus, conn, bufs, lens, k)

    def close_conn(self, conn: int) -> None:
        self._lib.tb_bus_close(self._bus, conn)

    def poll(self, timeout_ms: int = 0) -> list[tuple[int, int, bytes]]:
        """-> [(event_type, conn, payload)]; payload copied out."""
        self._lib.tb_bus_poll(self._bus, timeout_ms)
        events = []
        ev = _Event()
        while self._lib.tb_bus_next_event(self._bus, ctypes.byref(ev)):
            payload = b""
            if ev.type == EV_MESSAGE and ev.len:
                payload = ctypes.string_at(ev.data, ev.len)
            events.append((int(ev.type), int(ev.conn), payload))
        return events

    def close(self) -> None:
        if self._bus:
            self._lib.tb_bus_destroy(self._bus)
            self._bus = None

    def __del__(self):  # noqa: D105
        try:
            self.close()
        # tbcheck: allow(broad-except): __del__ during interpreter
        # teardown — the bus handle may already be torn down; any
        # raise here becomes an unraisable-exception warning storm.
        except Exception:
            pass


class NativeClient:
    """Synchronous C-ABI client session (the tb_client analog)."""

    def __init__(self, host: str, port: int, cluster: int, client_id: int,
                 reply_cap: int = 1 << 20) -> None:
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native runtime unavailable")
        self._client = self._lib.tb_client_init(
            host.encode(), port, cluster,
            client_id & 0xFFFFFFFFFFFFFFFF, client_id >> 64,
        )
        if not self._client:
            raise OSError(f"tb_client_init {host}:{port} failed")
        self._reply_buf = ctypes.create_string_buffer(reply_cap)

    def add_address(self, host: str, port: int) -> None:
        """Additional cluster replica: retransmissions rotate through
        every known address, so a view change (new primary without
        this client's connection) recovers."""
        self._lib.tb_client_add_address(
            self._client, host.encode(), port
        )

    def request(self, operation: int, body: bytes = b"",
                timeout_ms: int = 10_000) -> bytes:
        rc = self._lib.tb_client_request(
            self._client, operation, body, len(body),
            self._reply_buf, len(self._reply_buf), timeout_ms,
        )
        if rc < 0:
            raise OSError(
                {-2: "evicted", -3: "timeout", -4: "io error", -5: "reply too large"}
                .get(rc, f"error {rc}")
            )
        return self._reply_buf.raw[:rc]

    def close(self) -> None:
        if self._client:
            self._lib.tb_client_deinit(self._client)
            self._client = None

    def __del__(self):  # noqa: D105
        try:
            self.close()
        # tbcheck: allow(broad-except): same __del__-at-teardown story
        # as NativeBus above.
        except Exception:
            pass
