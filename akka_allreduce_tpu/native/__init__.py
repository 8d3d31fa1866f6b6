"""Native (C++) runtime components.

The reference's transport layer is JVM-native netty TCP under Akka remoting
(reference: application.conf:5-11); this package supplies the equivalent for
the TPU framework's host plane: a C++ framed TCP transport
(src/transport.cpp) loaded via ctypes, built on demand with the in-tree
Makefile (g++; no pybind11 in this environment).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_DIR = os.path.join(_DIR, "_lib")

_lib: ctypes.CDLL | None = None


def _source_digest() -> str:
    """Content hash of everything the library is built from: every file
    under ``src/`` plus the Makefile. The digest is part of the library's
    file name, so a tree that was copied with a ``_lib/`` from other
    sources (``_lib/`` is git-ignored but travels with a directory copy,
    and mtimes do not survive one meaningfully) never loads a library it
    did not build."""
    h = hashlib.sha256()
    paths = sorted(glob.glob(os.path.join(_DIR, "src", "*")))
    for path in paths + [os.path.join(_DIR, "Makefile")]:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    return h.hexdigest()[:16]


def build_library(force: bool = False) -> str:
    """Compile the shared library unless one built from exactly these
    sources exists. Concurrent-process safe: compiles to a per-pid temp
    file and atomically renames, so simultaneous cold starts (the
    multi-process cluster) never load a partially-written .so. Returns
    the .so path."""
    so = os.path.join(_LIB_DIR, f"libaatpu-{_source_digest()}.so")
    if force or not os.path.exists(so):
        os.makedirs(_LIB_DIR, exist_ok=True)
        tmp = f"{so}.tmp.{os.getpid()}"
        try:
            # Build through the in-tree Makefile so its CXX/CXXFLAGS
            # overrides apply on the automatic path too; OUT is redirected
            # to a per-pid file and atomically renamed so concurrent cold
            # starts never load a partially-written .so.
            subprocess.run(
                ["make", "-s", "-C", _DIR,
                 f"OUT={os.path.relpath(tmp, _DIR)}"],
                check=True, capture_output=True, text=True)
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return so


def load_library() -> ctypes.CDLL:
    """Load (building if needed) and configure the C ABI."""
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(build_library())

    lib.aat_create.restype = ctypes.c_void_p
    lib.aat_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.aat_port.restype = ctypes.c_int
    lib.aat_port.argtypes = [ctypes.c_void_p]
    lib.aat_connect.restype = ctypes.c_int
    lib.aat_connect.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_int, ctypes.c_int]
    lib.aat_send.restype = ctypes.c_int
    lib.aat_send.argtypes = [ctypes.c_void_p, ctypes.c_int,
                             ctypes.POINTER(ctypes.c_uint8),
                             ctypes.c_uint64]
    lib.aat_recv_len.restype = ctypes.c_int64
    lib.aat_recv_len.argtypes = [ctypes.c_void_p]
    lib.aat_recv_take.restype = ctypes.c_int64
    lib.aat_recv_take.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint8),
                                  ctypes.c_uint64,
                                  ctypes.POINTER(ctypes.c_int)]
    lib.aat_poll_disconnect.restype = ctypes.c_int
    lib.aat_poll_disconnect.argtypes = [ctypes.c_void_p]
    lib.aat_close_peer.restype = None
    lib.aat_close_peer.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.aat_send_drained.restype = ctypes.c_int
    lib.aat_send_drained.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.aat_num_connected.restype = ctypes.c_int
    lib.aat_num_connected.argtypes = [ctypes.c_void_p]
    lib.aat_destroy.restype = None
    lib.aat_destroy.argtypes = [ctypes.c_void_p]

    lib.aat_cluster_run.restype = ctypes.c_long
    lib.aat_cluster_run.argtypes = [
        ctypes.c_int, ctypes.c_long, ctypes.c_int, ctypes.c_int,
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_long)]

    lib.aat_cluster_run_timed.restype = ctypes.c_long
    lib.aat_cluster_run_timed.argtypes = [
        ctypes.c_int, ctypes.c_long, ctypes.c_int, ctypes.c_int,
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_double), ctypes.c_long]

    lib.aat_remote_worker_run.restype = ctypes.c_long
    lib.aat_remote_worker_run.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_double, ctypes.c_double, ctypes.c_int]

    lib.aat_remote_worker_run_seeds.restype = ctypes.c_long
    lib.aat_remote_worker_run_seeds.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_double,
        ctypes.c_double, ctypes.c_double, ctypes.c_int]

    lib.aat_remote_master_run.restype = ctypes.c_long
    lib.aat_remote_master_run.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_uint, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_uint, ctypes.c_double, ctypes.c_double,
        ctypes.c_double, ctypes.c_int64, ctypes.c_double,
        ctypes.c_double, ctypes.c_double, ctypes.c_int]

    lib.aat_remote_master_run_timed.restype = ctypes.c_long
    lib.aat_remote_master_run_timed.argtypes = \
        lib.aat_remote_master_run.argtypes + [
            ctypes.POINTER(ctypes.c_double), ctypes.c_long]

    _lib = lib
    return lib
