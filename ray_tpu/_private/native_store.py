"""ctypes binding for the native (C++) library.

Loads ``src/librtpu_store.so`` (building it with make on first use if a
toolchain is present): the ``.obj`` writer and reader, the GCS's log store
(gcs_store.py), the scheduler (native_sched.py). ``RAY_TPU_NATIVE_STORE=0``
forces the Python paths.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Iterable, Optional, Tuple

logger = logging.getLogger(__name__)

_SRC_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "src",
)
_LIB_PATH = os.path.join(_SRC_DIR, "librtpu_store.so")

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


def _configure(lib):
    lib.rtpu_write_object.restype = ctypes.c_long
    lib.rtpu_write_object.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint64,
    ]
    lib.rtpu_open_object.restype = ctypes.c_void_p
    lib.rtpu_open_object.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.rtpu_release_object.restype = None
    lib.rtpu_release_object.argtypes = [ctypes.c_void_p]
    lib.rtpu_object_exists.restype = ctypes.c_int
    lib.rtpu_object_exists.argtypes = [ctypes.c_char_p, ctypes.c_char_p]

    # append-log KV store (GCS persistence; src/log_store.cpp). Optional:
    # a prebuilt .so without these symbols still serves the object store.
    u8pp = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))
    u64p = ctypes.POINTER(ctypes.c_uint64)
    try:
        lib.rtpu_log_open
    except AttributeError:
        lib._has_log_store = False
        return lib
    lib._has_log_store = True
    lib.rtpu_log_open.restype = ctypes.c_void_p
    lib.rtpu_log_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.rtpu_log_put.restype = ctypes.c_int
    lib.rtpu_log_put.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
    ]
    lib.rtpu_log_count.restype = ctypes.c_uint64
    lib.rtpu_log_count.argtypes = [ctypes.c_void_p]
    lib.rtpu_log_iter_start.restype = None
    lib.rtpu_log_iter_start.argtypes = [ctypes.c_void_p]
    lib.rtpu_log_iter_next.restype = ctypes.c_int
    lib.rtpu_log_iter_next.argtypes = [
        ctypes.c_void_p, u8pp, u64p, u8pp, u64p, u8pp, u64p,
    ]
    lib.rtpu_log_close.restype = None
    lib.rtpu_log_close.argtypes = [ctypes.c_void_p]
    return lib


def load_library() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _build_attempted
    if _lib is not None:
        return _lib
    if os.environ.get("RAY_TPU_NATIVE_STORE", "1") == "0":
        return None
    with _lib_lock:
        if _lib is not None:
            return _lib

        def _stale() -> bool:
            """A .so older than any source is from a previous build and may
            be missing newer symbols — rebuild rather than crash on
            AttributeError during _configure."""
            if not os.path.exists(_LIB_PATH):
                return True
            lib_mtime = os.path.getmtime(_LIB_PATH)
            for name in os.listdir(_SRC_DIR):
                if name.endswith((".cpp", ".h")) and os.path.getmtime(
                    os.path.join(_SRC_DIR, name)
                ) > lib_mtime:
                    return True
            return False

        if _stale() and not _build_attempted:
            _build_attempted = True
            try:
                # Cross-process file lock: many workers starting at once
                # must not run concurrent builds of the same output (the
                # Makefile links to a temp name + atomic mv, so already-
                # mapped processes are safe either way).
                import fcntl

                with open(os.path.join(_SRC_DIR, ".build.lock"), "w") as lk:
                    fcntl.flock(lk, fcntl.LOCK_EX)
                    if _stale():  # may have been built while we waited
                        subprocess.run(
                            ["make", "-C", _SRC_DIR, "-s", "-B"],
                            check=True, capture_output=True, timeout=120,
                        )
            except (OSError, subprocess.SubprocessError) as e:
                # no toolchain / build failure: the object store and the
                # scheduler run as their Python twins, and say so
                stderr = getattr(e, "stderr", None) or b""
                logger.warning(
                    "native store build failed (%s: %s) %s; using the "
                    "pure-Python object store and scheduler",
                    type(e).__name__, e,
                    stderr.decode(errors="replace").strip()[-500:])
        if not os.path.exists(_LIB_PATH):
            return None
        try:
            _lib = _configure(ctypes.CDLL(_LIB_PATH))
        except (OSError, AttributeError) as e:
            # AttributeError = stale .so missing newer symbols
            logger.warning("could not load native store: %s", e)
            return None
        return _lib


def available() -> bool:
    return load_library() is not None


def _buffer_pointers(metadata: bytes, buffers: Iterable):
    """(meta, bufs_array, lens_array, nbufs, keepalive) for a C call.

    Zero-copy for bytes and writable buffers; readonly non-bytes views are
    copied once (rare: big tensors expose writable buffers)."""
    keep = []
    ptrs = []
    lens = []
    for buf in buffers:
        if isinstance(buf, (bytes, bytearray)):
            ptrs.append(ctypes.cast(ctypes.c_char_p(bytes(buf) if isinstance(buf, bytearray) else buf), ctypes.c_void_p))
            keep.append(buf)
            lens.append(len(buf))
            continue
        mv = memoryview(buf)
        if mv.ndim != 1 or mv.format != "B":
            mv = mv.cast("B")
        if mv.readonly:
            b = bytes(mv)
            keep.append(b)
            ptrs.append(ctypes.cast(ctypes.c_char_p(b), ctypes.c_void_p))
            lens.append(len(b))
        else:
            c = (ctypes.c_char * len(mv)).from_buffer(mv)
            keep.append((mv, c))
            ptrs.append(ctypes.cast(ctypes.addressof(c), ctypes.c_void_p))
            lens.append(len(mv))
    n = len(ptrs)
    arr = (ctypes.c_void_p * n)(*ptrs)
    larr = (ctypes.c_uint64 * n)(*lens)
    return arr, larr, n, keep


def write_object(store_dir: str, oid_hex: str, metadata: bytes,
                 buffers: Iterable, total_data_len: int) -> int:
    lib = load_library()
    arr, larr, n, keep = _buffer_pointers(metadata, buffers)
    written = lib.rtpu_write_object(
        store_dir.encode(), oid_hex.encode(), metadata, len(metadata),
        arr, larr, n,
    )
    if written < 0:
        raise IOError(f"native write_object failed for {oid_hex}")
    return written


def open_object(store_dir: str, oid_hex: str
                ) -> Optional[Tuple[int, bytes, memoryview]]:
    """(handle, metadata, data_view) or None. Caller must release(handle)
    after the data view is no longer needed."""
    lib = load_library()
    meta_ptr = ctypes.c_void_p()
    meta_len = ctypes.c_uint64()
    data_ptr = ctypes.c_void_p()
    data_len = ctypes.c_uint64()
    handle = lib.rtpu_open_object(
        store_dir.encode(), oid_hex.encode(),
        ctypes.byref(meta_ptr), ctypes.byref(meta_len),
        ctypes.byref(data_ptr), ctypes.byref(data_len),
    )
    if not handle:
        return None
    metadata = ctypes.string_at(meta_ptr, meta_len.value)
    if data_len.value:
        carr = (ctypes.c_char * data_len.value).from_address(data_ptr.value)
        data = memoryview(carr)
    else:
        data = memoryview(b"")
    return handle, metadata, data


def release(handle: int):
    lib = load_library()
    lib.rtpu_release_object(ctypes.c_void_p(handle))


def object_exists(store_dir: str, oid_hex: str) -> bool:
    lib = load_library()
    return bool(lib.rtpu_object_exists(store_dir.encode(), oid_hex.encode()))
