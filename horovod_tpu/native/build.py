"""On-demand native build: g++ → cached shared library.

Reference parity: the reference compiles its C++ core at pip-install time
(setup.py + CMakeLists, SURVEY.md §2.5 'Build'). This repo is run from
source, so the library builds lazily on first use instead — same compiler
flags discipline (-O3, -fPIC, -pthread, C++17), cached by source hash so
rebuilds only happen when the source changes. CMakeLists.txt in this
directory builds the identical artifact for packaging workflows.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Optional

_SRC = os.path.join(os.path.dirname(__file__), "src", "hvd_runtime.cc")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")

CXX_FLAGS = ["-O3", "-fPIC", "-shared", "-std=c++17", "-pthread", "-Wall"]


def _source_hash() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(CXX_FLAGS).encode())
    return h.hexdigest()[:16]


def lib_path() -> str:
    return os.path.join(_BUILD_DIR, f"libhvd_runtime_{_source_hash()}.so")


def build() -> Optional[str]:
    """Compile (if needed) and return the .so path. None only when the
    machine has no C++ compiler at all; a compiler that is present and
    fails raises — a quietly slower numpy path is not a fallback anyone
    asked for."""
    out = lib_path()
    if os.path.exists(out):
        return out
    import shutil
    cxx = os.environ.get("CXX") or shutil.which("g++") or shutil.which("c++")
    if cxx is None:
        return None
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # per-process temp name: workers of one job may build concurrently
    tmp = f"{out}.{os.getpid()}.tmp.so"
    cmd = [cxx, *CXX_FLAGS, _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(
            f"native build could not run:\n{' '.join(cmd)}\n{e}") from e
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build failed:\n{' '.join(cmd)}\n{proc.stderr}")
    os.replace(tmp, out)
    return out
