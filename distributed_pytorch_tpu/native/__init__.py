"""Native (C++) runtime components and their build glue.

The reference inherits all of its native capability from the ``torch`` wheel
(SURVEY.md §2a); this package is where our framework's own native runtime
lives. Sources are compiled on first use with ``g++`` into ``_build/`` next to
this file, so there is no separate install step (mirroring the zero-setup
character of the reference scripts). Each artifact's file name carries a
hash of the source it was built from, so a binary is only ever reused for
the exact source text that produced it — whatever the files' mtimes say
after a copy or a checkout.

Components:

* ``kvstore.cpp``   -> ``tpu_kvstore`` binary — TCP rendezvous/KV store
  (c10d TCPStore twin; reference ``slurm/sbatch_run.sh:21-22``).
* ``prefetch.cpp``  -> ``libtpu_prefetch.so`` — GIL-free batch-prefetch worker
  pool (torch ``DataLoader`` worker/pin-memory twin; reference
  ``multigpu.py:72-79``), driven via ctypes.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_LOCK = threading.Lock()


def _build_dir() -> str:
    """Output dir for compiled artifacts.

    Prefer ``_build/`` next to the sources (editable installs, repo
    checkouts); when the package dir is read-only (non-editable wheel in
    system site-packages) fall back to a per-user cache dir keyed by the
    source location, so distinct installs never share stale binaries.
    """
    preferred = os.path.join(_NATIVE_DIR, "_build")
    if os.access(_NATIVE_DIR, os.W_OK):
        return preferred
    key = hashlib.sha256(_NATIVE_DIR.encode()).hexdigest()[:16]
    cache_root = os.environ.get(
        "XDG_CACHE_HOME", os.path.join(os.path.expanduser("~"), ".cache")
    )
    return os.path.join(cache_root, "distributed_pytorch_tpu", key)


def _compile(src_name: str, out_name: str, *, shared: bool) -> str:
    """Compile ``src_name`` (in this dir) to ``_build/<out_name>`` keyed by
    the source's content hash, unless that exact build already exists."""
    src = os.path.join(_NATIVE_DIR, src_name)
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    stem, ext = os.path.splitext(out_name)
    build_dir = _build_dir()
    out = os.path.join(build_dir, f"{stem}-{digest}{ext}")
    with _BUILD_LOCK:
        if os.path.exists(out):
            return out
        os.makedirs(build_dir, exist_ok=True)
        cmd = ["g++", "-O2", "-std=c++17", "-pthread"]
        if shared:
            cmd += ["-fPIC", "-shared"]
        # Per-process temp name: the threading lock doesn't cover concurrent
        # *processes* (two agents cold-starting on one machine), so each must
        # link into its own file before the atomic rename.
        tmp = f"{out}.tmp.{os.getpid()}"
        cmd += [src, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)  # atomic: concurrent builders see old or new
    return out


def kvstore_binary() -> str:
    """Path to the ``tpu_kvstore`` server binary (building it if needed)."""
    return _compile("kvstore.cpp", "tpu_kvstore", shared=False)


_PREFETCH_LIB = None


def prefetch_library() -> ctypes.CDLL:
    """The batch-prefetch shared library, built on first use, with argtypes
    bound."""
    global _PREFETCH_LIB
    if _PREFETCH_LIB is not None:
        return _PREFETCH_LIB
    path = _compile("prefetch.cpp", "libtpu_prefetch.so", shared=True)
    lib = ctypes.CDLL(path)
    lib.prefetch_create.restype = ctypes.c_void_p
    lib.prefetch_create.argtypes = [
        ctypes.c_void_p,  # x rows
        ctypes.c_void_p,  # y rows
        ctypes.c_long,  # row_x bytes
        ctypes.c_long,  # row_y bytes
        ctypes.POINTER(ctypes.c_long),  # indices
        ctypes.c_long,  # n_indices
        ctypes.c_long,  # batch
        ctypes.c_int,  # depth
        ctypes.c_int,  # n_threads
    ]
    lib.prefetch_next.restype = ctypes.c_int
    lib.prefetch_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.prefetch_stop.restype = None
    lib.prefetch_stop.argtypes = [ctypes.c_void_p]
    lib.prefetch_destroy.restype = None
    lib.prefetch_destroy.argtypes = [ctypes.c_void_p]
    _PREFETCH_LIB = lib
    return lib
