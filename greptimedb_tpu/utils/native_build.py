"""Build-on-first-use for the C++ libraries under greptimedb_tpu/native/.

The built library is git-ignored and named by a hash of its source, so
a process only ever loads a library built from the `.cpp` it sits next
to: a copied tree that carries someone else's stale `.so` (mtimes do
not survive a copy in any useful order) rebuilds instead of loading it.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess

from . import atomic_publish

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


def build_native_library(stem: str) -> str:
    """Path of `native/libgdb<stem>-<source hash>.so`, compiling
    `native/<stem>.cpp` with g++ when that exact file is absent. Raises
    OSError / subprocess.SubprocessError when the toolchain cannot
    build it (callers fall back to their Python twin)."""
    src = os.path.join(NATIVE_DIR, f"{stem}.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    lib = os.path.join(NATIVE_DIR, f"libgdb{stem}-{digest}.so")
    if os.path.exists(lib):
        return lib
    tmp = f"{lib[:-3]}.{os.getpid()}.so.tmp"
    subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                    "-o", tmp, src, "-lpthread"],
                   check=True, capture_output=True, timeout=120)
    atomic_publish(tmp, lib, fsync=False)     # build artifact
    for old in glob.glob(os.path.join(NATIVE_DIR, f"libgdb{stem}-*.so")):
        if old != lib:
            try:
                os.unlink(old)     # built from a source that is gone
            except OSError:
                pass
    return lib
