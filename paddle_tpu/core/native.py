"""ctypes bindings to the C++ runtime (runtime_cpp/libpaddle_tpu_runtime.so).

The reference's native runtime pieces we keep native: the feed-path blocking
queue (operators/reader/blocking_queue.h), TCPStore rendezvous
(distributed/store/tcp_store.cc), host event recorder
(platform/profiler/host_event_recorder.h) and the host staging allocator
(memory/allocation/*). Built on demand with `make` (g++); every consumer has
a pure-Python fallback so the framework works before the first build.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_RUNTIME_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "runtime_cpp")
_SO = os.path.join(_RUNTIME_DIR, "libpaddle_tpu_runtime.so")

_lib = None
_lock = threading.Lock()

# True when the loaded .so carries the profiler span ring
# (trace.cc ptt_span_record/ptt_span_drain); stale builds predate it.
HAS_SPANS = False

# True when the loaded .so carries the host-embedding PS kernels
# (embed.cc pte_unique/pte_gather_f32/...); stale builds predate them and
# the host-embedding table falls back to pure numpy.
HAS_EMBED = False


def _build():
    # the runtime library only: the default target also links the C inference
    # API against python3-config, which a host that can run this need not have
    subprocess.run(
        ["make", "-C", _RUNTIME_DIR, os.path.basename(_SO)],
        check=True, capture_output=True,
    )


def _stale() -> bool:
    """True when any runtime source is newer than the built .so."""
    try:
        so_m = os.path.getmtime(_SO)
        for name in os.listdir(_RUNTIME_DIR):
            if name.endswith((".cc", ".h")) and os.path.getmtime(
                    os.path.join(_RUNTIME_DIR, name)) > so_m:
                return True
    except OSError:
        return False
    return False


def lib():
    """Load (building if needed) the native runtime; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        try:
            if not os.path.exists(_SO) or _stale():
                # make's own mtime check keeps the rebuild a no-op when
                # nothing changed; calling it whenever a source is newer
                # means an upgraded checkout can't load a stale .so that
                # lacks newly added symbols
                _build()
            L = ctypes.CDLL(_SO)
        except Exception:
            return None
        # queue
        L.ptq_create.restype = ctypes.c_void_p
        L.ptq_create.argtypes = [ctypes.c_int64]
        L.ptq_push.restype = ctypes.c_int
        L.ptq_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        L.ptq_pop_size.restype = ctypes.c_int64
        L.ptq_pop_size.argtypes = [ctypes.c_void_p]
        L.ptq_pop_into.restype = ctypes.c_int64
        L.ptq_pop_into.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        L.ptq_close.argtypes = [ctypes.c_void_p]
        L.ptq_size.restype = ctypes.c_int64
        L.ptq_size.argtypes = [ctypes.c_void_p]
        L.ptq_destroy.argtypes = [ctypes.c_void_p]
        # store
        L.pts_server_create.restype = ctypes.c_void_p
        L.pts_server_create.argtypes = [ctypes.c_int]
        L.pts_server_destroy.argtypes = [ctypes.c_void_p]
        L.pts_client_create.restype = ctypes.c_void_p
        L.pts_client_create.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
        L.pts_client_destroy.argtypes = [ctypes.c_void_p]
        L.pts_request.restype = ctypes.c_int
        L.pts_request.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        # trace
        L.ptt_create.restype = ctypes.c_void_p
        L.ptt_create.argtypes = [ctypes.c_int64]
        L.ptt_destroy.argtypes = [ctypes.c_void_p]
        L.ptt_intern.restype = ctypes.c_uint32
        L.ptt_intern.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.ptt_now_ns.restype = ctypes.c_uint64
        L.ptt_record.argtypes = [ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint64]
        L.ptt_drain.restype = ctypes.c_int64
        L.ptt_drain.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        L.ptt_name.restype = ctypes.c_char_p
        L.ptt_name.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        L.ptt_reset.argtypes = [ctypes.c_void_p]
        # trace span ring (absent from pre-span builds of the .so)
        global HAS_SPANS
        try:
            L.ptt_span_record.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_uint64,
            ]
            L.ptt_span_drain.restype = ctypes.c_int64
            L.ptt_span_drain.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ]
            HAS_SPANS = True
        except AttributeError:
            HAS_SPANS = False
        # host-embedding PS kernels (absent from pre-embed builds)
        global HAS_EMBED
        try:
            L.pte_unique.restype = ctypes.c_int64
            L.pte_unique.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64,
            ]
            L.pte_gather_f32.restype = ctypes.c_int
            L.pte_gather_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_int64,
            ]
            L.pte_sgd_f32.restype = ctypes.c_int
            L.pte_sgd_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_float, ctypes.c_int64,
            ]
            L.pte_adagrad_f32.restype = ctypes.c_int
            L.pte_adagrad_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            L.pte_merge_f32.restype = ctypes.c_int64
            L.pte_merge_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64,
            ]
            HAS_EMBED = True
        except AttributeError:
            HAS_EMBED = False
        # arena
        L.pta_create.restype = ctypes.c_void_p
        L.pta_create.argtypes = [ctypes.c_int64]
        L.pta_destroy.argtypes = [ctypes.c_void_p]
        L.pta_alloc.restype = ctypes.c_void_p
        L.pta_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        L.pta_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        L.pta_bytes.restype = ctypes.c_int64
        L.pta_bytes.argtypes = [ctypes.c_void_p]
        L.pta_reused.restype = ctypes.c_int64
        L.pta_reused.argtypes = [ctypes.c_void_p]
        _lib = L
        return _lib


class NativeQueue:
    """Bounded blocking byte-buffer queue backed by C++ (GIL-free copies)."""

    def __init__(self, capacity: int):
        L = lib()
        if L is None:
            raise RuntimeError("native runtime unavailable")
        self._L = L
        self._q = L.ptq_create(capacity)

    def push(self, data: bytes) -> bool:
        return self._L.ptq_push(self._q, data, len(data)) == 0

    def pop(self):
        n = self._L.ptq_pop_size(self._q)
        if n <= 0:
            return None
        buf = ctypes.create_string_buffer(int(n))
        got = self._L.ptq_pop_into(self._q, buf, n)
        if got <= 0:
            return None
        return buf.raw[: int(got)]

    def close(self):
        self._L.ptq_close(self._q)

    def __len__(self):
        return int(self._L.ptq_size(self._q))

    def __del__(self):
        try:
            self._L.ptq_destroy(self._q)
        except Exception:
            pass


class TCPStore:
    """KV store for rendezvous (reference distributed/store/tcp_store.h)."""

    SET, GET, ADD, WAIT, DELETE = 0, 1, 2, 3, 4

    def __init__(self, host="127.0.0.1", port=23456, is_master=False, timeout=30):
        L = lib()
        if L is None:
            raise RuntimeError("native runtime unavailable")
        self._L = L
        self._server = None
        if is_master:
            self._server = L.pts_server_create(port)
            if not self._server:
                raise RuntimeError(f"TCPStore: cannot bind port {port}")
        self._client = L.pts_client_create(host.encode(), port, int(timeout * 1000))
        if not self._client:
            raise RuntimeError(f"TCPStore: cannot connect {host}:{port}")

    def _req(self, op, key, val=b"", max_bytes=None):
        # the C side drains the full reply off the socket before the copy-out
        # bounds check, so an undersized buffer LOSES the value (-2, not
        # retryable) — callers expecting large replies must size up front
        out = ctypes.create_string_buffer(max(1 << 20, int(max_bytes or 0)))
        out_len = ctypes.c_int64(0)
        status = self._L.pts_request(
            self._client, op, key.encode(), val, len(val), out, len(out), ctypes.byref(out_len)
        )
        if status < 0:
            raise RuntimeError("TCPStore request failed")
        return status, out.raw[: out_len.value]

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        self._req(self.SET, key, value)

    def get(self, key, max_bytes=None):
        status, val = self._req(self.GET, key, max_bytes=max_bytes)
        return val if status == 0 else None

    def add(self, key, amount=1):
        import struct

        _, val = self._req(self.ADD, key, struct.pack("<q", amount))
        return struct.unpack("<q", val)[0]

    def wait(self, key, max_bytes=None):
        status, val = self._req(self.WAIT, key, max_bytes=max_bytes)
        if status != 0:
            raise RuntimeError(f"TCPStore wait({key}) interrupted")
        return val

    def delete_key(self, key):
        self._req(self.DELETE, key)

    def close(self):
        if self._client:
            self._L.pts_client_destroy(self._client)
            self._client = None
        if self._server:
            self._L.pts_server_destroy(self._server)
            self._server = None
