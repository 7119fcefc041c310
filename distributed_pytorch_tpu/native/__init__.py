"""Native (C++) runtime components and their build glue.

The reference inherits all of its native capability from the ``torch`` wheel
(SURVEY.md §2a); this package is where our framework's own native runtime
lives. Sources are compiled on first use with ``g++`` into ``_build/`` next to
this file, so there is no separate install step (mirroring the zero-setup
character of the reference scripts). Each artifact's file name carries a
hash of the source it was built from, so a binary is only ever reused for
the exact source text that produced it — whatever the files' mtimes say
after a copy or a checkout.

One component: ``kvstore.cpp`` -> ``tpu_kvstore`` binary — TCP rendezvous/KV
store (c10d TCPStore twin; reference ``slurm/sbatch_run.sh:21-22``).
"""

from __future__ import annotations

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


def _compile(src_name: str, out_name: str) -> str:
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
    return _compile("kvstore.cpp", "tpu_kvstore")
