"""Native runtime core: build + load the C++ support library.

The reference's runtime substrate (GStreamer's queueing/threading) is native
C; this package is the TPU framework's native layer.  The library is built
from source on first use with the toolchain's ``g++`` (no external deps) and
cached next to the source, keyed by the source's content hash — a stale
``_build/*.so`` copied from elsewhere is rebuilt, never loaded.  Set
``NNSTPU_COMMON_NATIVE_RUNTIME=off`` to choose the pure-Python twins.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "frame_queue.cpp")
_BUILD_DIR = os.path.join(_HERE, "_build")
_SO = os.path.join(_BUILD_DIR, "libnns_runtime.so")
_STAMP = _SO + ".stamp"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[Exception] = None

# status codes (keep in sync with frame_queue.cpp)
OK = 0
OK_DROPPED_OLDEST = 1
DROPPED_INCOMING = 2
SHUTDOWN = -1
TIMEOUT = -2

EVENT_BIT = 1 << 63


def _source_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _stamp_matches(key: str) -> bool:
    try:
        with open(_STAMP) as f:
            return f.read().strip() == key
    except OSError:
        return False


def _build(key: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # pid-unique tmp: two *processes* may build concurrently (_lock only
    # covers threads); os.replace keeps the publish atomic either way
    tmp = _SO + f".tmp.{os.getpid()}"
    cmd = [
        "g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
        _SRC, "-o", tmp,
    ]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, _SO)
    stamp_tmp = _STAMP + f".tmp.{os.getpid()}"
    with open(stamp_tmp, "w") as f:
        f.write(key)
    os.replace(stamp_tmp, _STAMP)  # lands after the library it vouches for


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.nns_queue_new.argtypes = [ctypes.c_uint64]
    lib.nns_queue_new.restype = ctypes.c_void_p
    lib.nns_queue_free.argtypes = [ctypes.c_void_p]
    lib.nns_queue_free.restype = None
    lib.nns_queue_shutdown.argtypes = [ctypes.c_void_p]
    lib.nns_queue_shutdown.restype = None
    lib.nns_queue_len.argtypes = [ctypes.c_void_p]
    lib.nns_queue_len.restype = ctypes.c_int64
    lib.nns_queue_push.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.nns_queue_push.restype = ctypes.c_int
    lib.nns_queue_pop.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.nns_queue_pop.restype = ctypes.c_int
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The loaded library, (re)building it when the source's content hash
    differs from the built library's stamp; None when unavailable."""
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    with _lock:
        if _lib is not None or _load_error is not None:
            return _lib
        try:
            key = _source_hash()
            if not (os.path.exists(_SO) and _stamp_matches(key)):
                _build(key)
            _lib = _bind(ctypes.CDLL(_SO))
        except (OSError, subprocess.CalledProcessError) as exc:
            _load_error = exc
    return _lib


def available() -> bool:
    from ..conf import conf

    if not conf.get_bool("common", "native_runtime", True):
        return False
    return load() is not None


def queue_backend() -> str:
    """``"native"`` or ``"python"``: the ``queue`` element's backend as
    configured.  Raises when ``[common] native_runtime`` is on but the
    library failed to build or load — ``chip_smoke.py`` calls this so the
    host dispatch layer cannot change under a measurement without a word."""
    from ..conf import conf

    if not conf.get_bool("common", "native_runtime", True):
        return "python"
    if load() is None:
        detail = getattr(_load_error, "stderr", None) or _load_error
        raise RuntimeError(
            "[common] native_runtime is on but libnns_runtime.so failed to "
            f"build or load: {detail}")
    return "native"
