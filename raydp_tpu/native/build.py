"""On-demand build of the native libraries, keyed by a hash of their source.

``raydp_tpu/native/_lib/`` is not committed, but it rides along whenever a
working tree is copied, and a copy need not keep mtimes. So the library's file
name carries a hash of the source it was compiled from: a library built from
other source — stale or foreign — has another name and can never load, and a
missing one is compiled from ``csrc/`` on first use (under a file lock, so
concurrently-spawning actor processes don't race the compiler).
"""

from __future__ import annotations

import fcntl
import glob
import hashlib
import os
import subprocess
from typing import Sequence

from raydp_tpu.log import get_logger

logger = get_logger("native.build")

CSRC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc")
LIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_lib")


def library_path(src: str, stem: str) -> str:
    """``_lib/lib<stem>-<hash of src>.so`` — where ``src``'s build lives."""
    with open(src, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(LIB_DIR, f"lib{stem}-{digest}.so")


def build_library(src: str, stem: str, link: Sequence[str] = ()) -> str:
    """Path of the shared library compiled from ``src``, building it if this
    exact source has not been built here yet. Raises when the source or the
    compiler is missing — the callers decide what an absent library means."""
    path = library_path(src, stem)
    os.makedirs(LIB_DIR, exist_ok=True)
    with open(os.path.join(LIB_DIR, ".build.lock"), "w") as lock_file:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        try:
            if not os.path.exists(path):
                tmp = path + ".tmp"
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                     "-o", tmp, src, *link],
                    check=True, capture_output=True, text=True)
                os.replace(tmp, path)
                for stale in glob.glob(os.path.join(LIB_DIR,
                                                    f"lib{stem}-*.so")):
                    if stale != path:
                        os.unlink(stale)
                logger.info("built %s -> %s", os.path.basename(src), path)
        finally:
            fcntl.flock(lock_file, fcntl.LOCK_UN)
    return path
