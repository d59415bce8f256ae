"""ctypes loader for the native runtime library.

Builds recordio.cc with g++ on first use (no pybind11 in the image — C
ABI + ctypes per the environment constraints). The built library is
named after a hash of the source AND the compiler line, so a binary left
over from another source, another flag set or another checkout is never
loaded: modification times prove nothing once a tree has been copied. A
failed build is logged once, loudly, and ``get()`` returns None — the
pure-Python codec then takes over and the caller keeps working.
"""

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

from paddle_tpu.utils.logger import get_logger

log = get_logger("runtime.native")

_lock = threading.Lock()
_lib = None
_tried = False

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "recordio.cc")
_CXX = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread"]
_LIBS = ["-lz"]


def _so_path() -> str:
    h = hashlib.sha256(" ".join(_CXX + _LIBS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"_librecordio.{h.hexdigest()[:16]}.so")


def _build(so: str):
    """Compile ``_SRC`` into ``so`` (atomic publish; concurrent builders
    each write their own temporary). Raises on a failed build."""
    tmp = f"{so}.tmp.{os.getpid()}"
    try:
        subprocess.run(_CXX + [_SRC, "-o", tmp] + _LIBS, check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, "_librecordio*.so")):
        if stale != so:
            try:
                os.unlink(stale)       # a binary of some other source
            except OSError:
                pass


def _bind(lib):
    c = ctypes
    lib.rio_index.restype = c.c_long
    lib.rio_index.argtypes = [c.c_char_p, c.POINTER(c.POINTER(c.c_longlong)),
                              c.POINTER(c.POINTER(c.c_uint))]
    lib.rio_read_chunk.restype = c.c_longlong
    lib.rio_read_chunk.argtypes = [c.c_char_p, c.c_longlong,
                                   c.POINTER(c.POINTER(c.c_uint8)),
                                   c.POINTER(c.c_uint)]
    lib.rio_write_chunk.restype = c.c_longlong
    lib.rio_write_chunk.argtypes = [c.c_char_p, c.c_char_p,
                                    c.POINTER(c.c_uint), c.c_uint]
    lib.rio_free.restype = None
    lib.rio_free.argtypes = [c.c_void_p]
    lib.loader_create.restype = c.c_void_p
    lib.loader_create.argtypes = [c.c_char_p, c.POINTER(c.c_longlong),
                                  c.c_long, c.c_int, c.c_long]
    lib.loader_next.restype = c.c_longlong
    lib.loader_next.argtypes = [c.c_void_p, c.POINTER(c.POINTER(c.c_uint8))]
    lib.loader_next_batch.restype = c.c_longlong
    lib.loader_next_batch.argtypes = [c.c_void_p, c.POINTER(c.c_uint8),
                                      c.c_long, c.c_longlong]
    lib.loader_destroy.restype = None
    lib.loader_destroy.argtypes = [c.c_void_p]
    return lib


def get():
    """The loaded native library, or None when it cannot be built or
    loaded here (logged once; the pure-Python codec is the caller's
    other path)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _so_path()
        try:
            if not os.path.exists(so):
                _build(so)
            _lib = _bind(ctypes.CDLL(so))
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            log.error(
                "NATIVE RECORDIO UNAVAILABLE — %s: %s %s; the "
                "pure-Python codec takes over (same bytes, slower "
                "reads and batch assembly)", type(e).__name__, e,
                detail.decode(errors="replace")[-2000:])
            _lib = None
        return _lib
