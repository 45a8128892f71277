"""Native (C++) helpers for the host-side hot path.

The reference gets its ingest throughput from Go's compiled parser and
per-worker goroutines; the analogous native tier here is a small C++
shared library driving the columnar batch parser (``dsd_parse.cpp``),
compiled on first import with the system g++ and loaded via ctypes.
If no toolchain is available the callers fall back to the pure-Python
per-line parser (slower, same behavior).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import time

log = logging.getLogger("veneur_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "dsd_parse.cpp")
_BUILD_DIR = os.path.join(_DIR, "_build")
_SO = os.path.join(_BUILD_DIR, "dsd_parse.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = _SO + f".tmp.{os.getpid()}"
    # -mtune (not -march): tuned for this host but ISA-portable — the
    # cached .so may be reused on a different CPU (image builds) where
    # -march=native code would SIGILL past the mtime freshness check
    cmd = ["g++", "-O3", "-mtune=native", "-shared", "-fPIC",
           "-std=c++17", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True,
                       timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native parser build failed (%s); "
                    "falling back to pure-Python parsing", e)
        return False
    os.replace(tmp, _SO)  # atomic: racing processes both succeed
    # reap unique-named retry copies from past processes (see load).
    # Unlinking a mapped library is fine on Linux (the mapping
    # survives), but a FRESH copy may sit in the window between
    # another process's copyfile and its dlopen — only reap copies
    # old enough to be past that window
    base = os.path.basename(_SO) + ".r"
    cutoff = time.time() - 300
    for f in os.listdir(_BUILD_DIR):
        if f.startswith(base):
            p = os.path.join(_BUILD_DIR, f)
            try:
                if os.path.getmtime(p) < cutoff:
                    os.unlink(p)
            except OSError:
                pass
    return True


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library, or None."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        fresh = (os.path.exists(_SO) and
                 os.path.getmtime(_SO) >= os.path.getmtime(_SRC))
        rebuilt = not fresh
        if not fresh and not _build():
            return None
        path = _SO
        while True:
            try:
                lib = ctypes.CDLL(path)
            except OSError as e:
                log.warning("native parser load failed: %s", e)
                return None
            try:
                _bind(lib)
            except AttributeError as e:
                # a cached .so can pass the mtime freshness check yet
                # predate a newly added symbol (clock skew, copied
                # build dirs); rebuild once rather than poisoning
                # every native path
                if rebuilt:
                    log.warning("native library missing symbol (%s); "
                                "falling back to pure Python", e)
                    return None
                log.warning("cached native library missing symbol "
                            "(%s); rebuilding", e)
                rebuilt = True
                if not _build():
                    return None
                # dlopen caches loaded objects by pathname: reloading
                # _SO would hand back the already-mapped STALE image
                # (the handle above is never dlclosed), so the fresh
                # build must enter the process under a unique name
                path = _SO + f".r{os.getpid()}"
                try:
                    import shutil
                    shutil.copyfile(_SO, path)
                except OSError as ce:
                    log.warning("retry copy failed: %s", ce)
                    return None
                continue
            _lib = lib
            return _lib


def _bind(lib: ctypes.CDLL) -> None:
    """Declare arg/restypes for every exported symbol; raises
    AttributeError if the loaded library predates one of them."""
    i64, u64p, u8p, f32p, f64p, i32p, i64p = (
        ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64))
    lib.vtpu_parse_batch.restype = i64
    lib.vtpu_parse_batch.argtypes = [
        u8p, i64, u64p, u8p, f64p, u64p, f32p, u8p, i64p, i32p, i64]
    lib.vtpu_hash_members.restype = None
    lib.vtpu_hash_members.argtypes = [u8p, i64p, i64p, i64, u64p]
    lib.vtpu_recv_drain.restype = i64
    lib.vtpu_recv_drain.argtypes = [
        ctypes.c_int32, u8p, i64, ctypes.c_int32, ctypes.c_int32,
        i32p, i32p]
    vp = ctypes.c_void_p
    lib.vtpu_index_new.restype = vp
    lib.vtpu_index_new.argtypes = [i64]
    lib.vtpu_index_free.restype = None
    lib.vtpu_index_free.argtypes = [vp]
    lib.vtpu_index_clear.restype = None
    lib.vtpu_index_clear.argtypes = [vp]
    lib.vtpu_index_insert.restype = None
    lib.vtpu_index_insert.argtypes = [vp, ctypes.c_uint64,
                                      ctypes.c_int32]
    lib.vtpu_index_count.restype = i64
    lib.vtpu_index_count.argtypes = [vp]
    lib.vtpu_index_readers.restype = i64
    lib.vtpu_index_readers.argtypes = [vp]
    lib.vtpu_index_lookup.restype = None
    lib.vtpu_index_lookup.argtypes = [vp, u64p, i64, i32p]
    lib.vtpu_rank.restype = None
    lib.vtpu_rank.argtypes = [i32p, i64, ctypes.c_int32, i32p,
                              i32p]
    lib.vtpu_dense_plane.restype = i64
    lib.vtpu_dense_plane.argtypes = [
        i32p, f32p, f32p, i64, ctypes.c_int32, ctypes.c_int32,
        f32p, f32p, i32p, i32p, f32p, f32p, f64p]
    lib.vtpu_hll_plane.restype = None
    lib.vtpu_hll_plane.argtypes = [
        i32p, i32p, i64, ctypes.c_int32, ctypes.c_int32, u8p]
    lib.vtpu_hll_union_dense.restype = i64
    lib.vtpu_hll_union_dense.argtypes = [
        u8p, i64, i64p, i32p, i64p, i64, i64, u8p, u8p]
    lib.vtpu_sb_gather_i32.restype = None
    lib.vtpu_sb_gather_i32.argtypes = [
        ctypes.POINTER(i32p), i64p, ctypes.c_int32, i32p, i64,
        ctypes.c_int32]
    lib.vtpu_hll_plane_stats.restype = None
    lib.vtpu_hll_plane_stats.argtypes = [
        i32p, i32p, i64, ctypes.c_int32, ctypes.c_int32, u8p, f64p,
        i32p]
    lib.vtpu_tier_split.restype = i64
    lib.vtpu_tier_split.argtypes = [i32p, i64, u8p, i32p, i32p,
                                    i32p]
    lib.vtpu_ingest.restype = None
    lib.vtpu_ingest.argtypes = [
        vp, u64p, u8p, f64p, u64p, f32p, i64, i64p, i64, i64,
        f64p, u8p, f32p, u8p, u8p,
        i32p, f32p, f32p, u8p,
        i32p, i32p, u8p,
        i64p, i64p]
    lib.vtpu_parse_ingest.restype = None
    lib.vtpu_parse_ingest.argtypes = [
        u8p, i64, vp, i64,
        f64p, u8p, f32p, u8p, u8p,
        i32p, f32p, f32p, u8p,
        i32p, i32p, u8p,
        u64p, u8p, f64p, u64p, f32p, i64p, i32p,
        i64p, i32p, u8p,
        i64p]
    # io_uring multishot ring ingest (stubs on non-Linux / old
    # toolchains: probe returns -ENOSYS, new fails — same symbols)
    lib.vtpu_uring_probe.restype = i64
    lib.vtpu_uring_probe.argtypes = []
    lib.vtpu_uring_new.restype = vp
    lib.vtpu_uring_new.argtypes = [
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, u8p, i64p]
    lib.vtpu_uring_free.restype = None
    lib.vtpu_uring_free.argtypes = [vp]
    lib.vtpu_uring_stats.restype = None
    lib.vtpu_uring_stats.argtypes = [vp, i64p]
    lib.vtpu_uring_drain.restype = i64
    lib.vtpu_uring_drain.argtypes = [
        vp, u8p, i64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p]
    lib.vtpu_uring_parse_ingest.restype = i64
    lib.vtpu_uring_parse_ingest.argtypes = [
        vp, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, vp, i64,
        f64p, u8p, f32p, u8p, u8p,
        i32p, f32p, f32p, u8p,
        i32p, i32p, u8p,
        u64p, u8p, f64p, u64p, f32p, i64p, i32p,
        i64p, i32p, u8p,
        i64p, i32p]
    lib.vtpu_uring_pending_copy.restype = i64
    lib.vtpu_uring_pending_copy.argtypes = [vp, u8p, i64]
    lib.vtpu_uring_release.restype = i64
    lib.vtpu_uring_release.argtypes = [vp]
    lib.vtpu_metriclist_decode.restype = i64
    lib.vtpu_metriclist_decode.argtypes = [
        u8p, i64, i64, i64, i64,
        i64p, i32p,
        u8p, i32p, i32p, f64p,
        f64p,
        i64p, i32p,
        f32p, f32p,
        i64p, i32p,
        i64p, i32p,
        i64p, i32p,
        i64p]
    lib.vtpu_gob_decode.restype = i64
    lib.vtpu_gob_decode.argtypes = [
        u8p, i64, i64,
        i64p, i64p, u8p,
        i64,
        f64p, f64p,
        i64p, i32p,
        f32p, f32p,
        u8p,
        i64p]
    lib.vtpu_metriclist_keyhash.restype = None
    lib.vtpu_metriclist_keyhash.argtypes = [
        u8p, i64,
        i64p, i32p,
        u8p, i32p, i32p,
        i64p, i32p,
        i64p, i32p,
        u64p]
    lib.vtpu_metriclist_spans.restype = i64
    lib.vtpu_metriclist_spans.argtypes = [
        u8p, i64, i64, i64p, i64p, i64p]
    lib.vtpu_proxy_keyhash.restype = None
    lib.vtpu_proxy_keyhash.argtypes = [
        u8p, i64,
        i64p, i32p,
        i32p,
        i64p, i32p,
        i64p, i32p,
        u64p, u8p]
