"""Lazy cc build + ctypes binding for the native host-encode kernels."""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Dict, Optional

log = logging.getLogger(__name__)

_lock = threading.Lock()
_libs: dict = {}

_DIR = os.path.dirname(__file__)


def _build(src: str, so_path: str) -> bool:
    """Compile `src` beside `so_path`, then rename into place: a
    concurrent process never loads a half-written library."""
    tmp = f"{so_path}.{os.getpid()}.tmp"
    try:
        for cc in ("cc", "gcc", "clang"):
            try:
                res = subprocess.run(
                    [cc, "-O3", "-shared", "-fPIC", src, "-o", tmp],
                    capture_output=True, timeout=120)
            except (OSError, subprocess.TimeoutExpired):
                continue
            if res.returncode == 0:
                os.replace(tmp, so_path)
                return True
            log.warning("%s failed on %s: %s", cc, src,
                        res.stderr.decode()[:500])
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _so_path(stem: str) -> str:
    """The artifact is keyed by a hash of the committed `.c` source, so a
    library built from another revision (or copied in with a newer
    mtime) is never loaded."""
    with open(os.path.join(_DIR, stem + ".c"), "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_{stem}-{digest}.so")


def _load(stem: str, signatures) -> Optional[ctypes.CDLL]:
    """Compile-on-first-use + bind; None → callers use the python path
    (`native_active()` reports which one is live)."""
    with _lock:
        if stem in _libs:
            return _libs[stem]
        _libs[stem] = None
        # the lock deliberately covers the one-time hash + build: two
        # threads racing the first use must not both compile
        try:
            # conc-ok: C003 (one-time build under the load lock)
            so_path = _so_path(stem)
            if not os.path.exists(so_path):
                for stale in glob.glob(os.path.join(_DIR, f"_{stem}*.so")):
                    if stale != so_path:  # a racing process's fresh build
                        # conc-ok: C003 (one-time build under the load lock)
                        os.remove(stale)
                # conc-ok: C003 (one-time build under the load lock)
                if not _build(os.path.join(_DIR, stem + ".c"), so_path):
                    return None
            lib = ctypes.CDLL(so_path)
            for name, argtypes, restype in signatures:
                fn = getattr(lib, name)
                fn.argtypes = argtypes
                fn.restype = restype
            _libs[stem] = lib
        except Exception:
            log.exception("native %s unavailable; using python path", stem)
        return _libs[stem]


def native_active() -> Dict[str, bool]:
    """Build/load every native kernel now and say which are live."""
    return {"murmur3": get_murmur3() is not None,
            "csv_parse": get_csv_parser() is not None}


def get_murmur3() -> Optional[ctypes.CDLL]:
    p = ctypes.c_void_p
    return _load("murmur3", [
        ("murmur3_buckets_i32",
         [p, p, ctypes.c_int64, ctypes.c_uint32, ctypes.c_uint32, p], None),
        ("murmur3_buckets_i64",
         [p, p, ctypes.c_int64, ctypes.c_uint32, ctypes.c_uint32, p], None),
        ("murmur3_hash_counts_i32",
         [p, p, p, ctypes.c_int64, ctypes.c_uint32, ctypes.c_uint32, p],
         None),
    ])


def get_csv_parser() -> Optional[ctypes.CDLL]:
    p = ctypes.c_void_p
    return _load("csv_parse", [
        ("csv_numeric_fill",
         [p, ctypes.c_int64, ctypes.c_int32, p, ctypes.c_int32,
          ctypes.c_char, p, p, ctypes.c_int64], ctypes.c_int64),
    ])
