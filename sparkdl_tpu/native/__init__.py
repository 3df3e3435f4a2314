"""ctypes bindings for the native (C++) runtime pieces.

``NativeLogSender`` wraps native/ctrl_plane.cc: a bounded, thread-
drained, drop-oldest log transport that guarantees log pressure never
blocks a training step (the reference's backpressure clause,
``runner_base.py:65-68``). The library is built on first use with the
in-tree Makefile, under a name that carries a hash of its source, so
only a build of THIS ``ctrl_plane.cc`` is ever loaded (``native/build``
is git-ignored: a copied tree may carry someone else's artefact);
absence of a compiler degrades gracefully to the pure-Python sender in
:mod:`sparkdl_tpu.horovod.control_plane`, whose client says which of
the two it got (``log_transport``).
"""

import ctypes
import hashlib
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "native"
)

# Must match sdl_abi_version() in native/ctrl_plane.cc. The version is
# part of the FILENAME: dlopen dedups by pathname process-wide, so a
# stale same-named .so could never be replaced by a rebuild within this
# process — a new ABI must land at a new path.
_ABI_VERSION = 2


def _lib_path():
    with open(os.path.join(_NATIVE_DIR, "ctrl_plane.cc"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(
        _NATIVE_DIR, "build",
        f"libsparkdl_ctrl.v{_ABI_VERSION}.{digest}.so")

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


def load_ctrl_lib():
    """Build (once) and load the native control-plane library; returns
    None when unavailable (no compiler / build failure)."""
    global _lib, _build_attempted
    with _lib_lock:
        if _lib is not None:
            return _lib
        try:
            lib_path = _lib_path()
        except OSError:
            return None  # installed without the native sources
        if not os.path.exists(lib_path) and not _build_attempted:
            _build_attempted = True
            # Concurrent first-use builds (e.g. a fresh gang's workers)
            # must not write the same .so: build into a process-unique
            # dir, then atomically rename into place.
            tmp_build = f"build.tmp.{os.getpid()}"
            try:
                subprocess.run(
                    ["make", "-C", _NATIVE_DIR, f"BUILD={tmp_build}"],
                    capture_output=True, timeout=120, check=True,
                )
                os.makedirs(os.path.dirname(lib_path), exist_ok=True)
                os.replace(
                    os.path.join(_NATIVE_DIR, tmp_build,
                                 "libsparkdl_ctrl.so"),
                    lib_path,
                )
            except (OSError, subprocess.SubprocessError):
                return None
            finally:
                import shutil

                shutil.rmtree(
                    os.path.join(_NATIVE_DIR, tmp_build),
                    ignore_errors=True,
                )
        if not os.path.exists(lib_path):
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError:
            return None
        if (not hasattr(lib, "sdl_abi_version")
                or lib.sdl_abi_version() != _ABI_VERSION):
            return None
        lib.sdl_sender_create.restype = ctypes.c_void_p
        lib.sdl_sender_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_uint32, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.sdl_sender_send.restype = ctypes.c_int
        lib.sdl_sender_send.argtypes = [
            ctypes.c_void_p, ctypes.c_uint8, ctypes.c_char_p,
            ctypes.c_uint32,
        ]
        lib.sdl_sender_dropped.restype = ctypes.c_uint64
        lib.sdl_sender_dropped.argtypes = [ctypes.c_void_p]
        lib.sdl_sender_flush.restype = ctypes.c_int
        lib.sdl_sender_flush.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.sdl_sender_close.restype = None
        lib.sdl_sender_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class NativeLogSender:
    """Bounded drop-oldest log transport (native backend)."""

    def __init__(self, host, port, rank, capacity_bytes=4 << 20,
                 preamble=b""):
        lib = load_ctrl_lib()
        if lib is None:
            raise RuntimeError("native control-plane library unavailable")
        self._lib = lib
        self._handle = lib.sdl_sender_create(
            host.encode(), int(port), int(rank), int(capacity_bytes),
            preamble, len(preamble),
        )
        # Serializes send/flush against close: the C++ Sender is
        # deleted by close, so a racing send would be use-after-free.
        # Sends are non-blocking, so the lock is uncontended in
        # practice.
        self._lock = threading.Lock()
        self._closed = False

    def send(self, msg_type, payload: bytes):
        """Enqueue a frame; returns True if anything was dropped to
        make room (backpressure signal, never blocks)."""
        with self._lock:
            if self._closed:
                return True
            return bool(self._lib.sdl_sender_send(
                self._handle, msg_type, payload, len(payload)
            ))

    @property
    def dropped(self):
        with self._lock:
            if self._closed:
                return 0
            return int(self._lib.sdl_sender_dropped(self._handle))

    def flush(self, timeout_ms=5000):
        with self._lock:
            if self._closed:
                return True
            return self._lib.sdl_sender_flush(self._handle, timeout_ms) == 0

    def close(self):
        with self._lock:
            if not self._closed:
                self._closed = True
                self._lib.sdl_sender_close(self._handle)
