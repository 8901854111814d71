"""Native (C++) runtime components, bound via ctypes.

The reference's host runtime is native code it borrows from its frameworks
(torch DataLoader workers at reference pytorch/single_gpu.py:60-61, TF's C++
input executor, ChainerMN's MPI glue — SURVEY §2.3).  This package is the
framework's own: ``dtdl_native.cpp`` compiled on first use with the system
toolchain (g++ -O3 -pthread -lz) into a cached shared library.  Everything
has a pure-Python fallback — ``available()`` gates all call sites.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile

log = logging.getLogger("dtdl_tpu")

_SRC = os.path.join(os.path.dirname(__file__), "src", "dtdl_native.cpp")
_LIB = None
_TRIED = False


def _build_dir() -> str:
    d = os.environ.get("DTDL_NATIVE_CACHE")
    if not d:
        d = os.path.join(tempfile.gettempdir(),
                         f"dtdl_native_{os.getuid()}")
    os.makedirs(d, exist_ok=True)
    return d


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_build_dir(), f"libdtdl_native_{tag}.so")


def _compile(out: str) -> bool:
    # a name of this process's own: several processes may build at once (six
    # test workers importing this on a machine that has no library yet), and
    # with one shared name the first ``os.replace`` takes the file from
    # under the others
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp, "-lz"]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build failed to run: %s", e)
        return False
    if r.returncode != 0:
        log.warning("native build failed:\n%s", r.stderr[-2000:])
        return False
    os.replace(tmp, out)
    return True


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.dtdl_loader_create.restype = c.c_void_p
    lib.dtdl_loader_create.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int, c.c_int, c.c_int,
        c.c_int, c.c_int, c.c_int, c.c_int, c.c_uint64,
        c.c_void_p, c.c_void_p]
    lib.dtdl_loader_start_epoch.argtypes = [c.c_void_p, c.c_int]
    lib.dtdl_loader_start_epoch_indices.restype = c.c_int
    lib.dtdl_loader_start_epoch_indices.argtypes = [
        c.c_void_p, c.c_int, c.c_void_p, c.c_int64]
    lib.dtdl_loader_next.restype = c.c_int
    lib.dtdl_loader_next.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
    lib.dtdl_loader_n_batches.restype = c.c_int64
    lib.dtdl_loader_n_batches.argtypes = [c.c_void_p]
    lib.dtdl_loader_destroy.argtypes = [c.c_void_p]
    lib.dtdl_idx_header.restype = c.c_int
    lib.dtdl_idx_header.argtypes = [c.c_char_p, c.c_int, c.c_void_p]
    lib.dtdl_idx_read_f32.restype = c.c_int
    lib.dtdl_idx_read_f32.argtypes = [c.c_char_p, c.c_int, c.c_void_p,
                                      c.c_int64, c.c_float]
    lib.dtdl_idx_read_i32.restype = c.c_int
    lib.dtdl_idx_read_i32.argtypes = [c.c_char_p, c.c_int, c.c_void_p,
                                      c.c_int64]
    lib.dtdl_topology.restype = c.c_int
    lib.dtdl_topology.argtypes = [c.c_char_p, c.c_int]
    return lib


def load() -> ctypes.CDLL | None:
    """Compile (once) and load the native library; None if unavailable."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("DTDL_DISABLE_NATIVE"):
        return None
    path = _lib_path()
    if not os.path.exists(path) and not _compile(path):
        return None
    try:
        _LIB = _bind(ctypes.CDLL(path))
    except OSError as e:
        log.warning("native library load failed: %s", e)
        _LIB = None
    return _LIB


def available() -> bool:
    return load() is not None


def topology() -> dict:
    """Host topology probe (cpus, memory, hostname) for the launcher."""
    lib = load()
    if lib is None:
        import multiprocessing
        import socket
        return {"host": socket.gethostname(),
                "cpus": multiprocessing.cpu_count(), "mem_gb": None,
                "native": False}
    buf = ctypes.create_string_buffer(512)
    n = lib.dtdl_topology(buf, len(buf))
    if n < 0:
        return {"native": False}
    import json
    d = json.loads(buf.value.decode())
    d["native"] = True
    return d
