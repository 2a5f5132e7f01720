"""Shared loader for the native (C++) runtime components.

Each component is a single .cc compiled on first use into a .so next to its
source (g++ -O2 -shared, same contract as the reference's cpp_extension JIT
build — python/paddle/utils/cpp_extension) and bound via ctypes.  Callers
keep a pure-Python fallback so the package works without a toolchain.

The .so is named by a hash of the source and flags it was built from, so a
library left in the tree by another checkout, or copied with a fresh mtime,
is never loaded for a source it does not match.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_NATIVE_DIR = os.path.dirname(__file__)
_locks: dict = {}
_libs: dict = {}
_guard = threading.Lock()


def load_native(name: str, extra_flags=()):
    """Compile (unless built from this very source) and dlopen
    lib<name>.<hash>.so from <name>.cc; returns the ctypes CDLL.  Raises on
    compile failure — callers catch and fall back."""
    with _guard:
        lock = _locks.setdefault(name, threading.Lock())
    with lock:
        if name in _libs:
            return _libs[name]
        src = os.path.join(_NATIVE_DIR, f"{name}.cc")
        cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
               src, *extra_flags]
        with open(src, "rb") as f:
            tag = hashlib.sha256(
                f.read() + "\0".join(cmd[1:]).encode()).hexdigest()[:16]
        so = os.path.join(_NATIVE_DIR, f"lib{name}.{tag}.so")
        if not os.path.exists(so):
            # build aside and rename: another process (an xdist worker)
            # racing this one sees no file or a whole one
            tmp = f"{so}.{os.getpid()}.tmp"
            subprocess.run([*cmd, "-o", tmp], check=True,
                           capture_output=True)
            os.replace(tmp, so)
            for stale in glob.glob(os.path.join(_NATIVE_DIR,
                                                f"lib{name}*.so")):
                if stale != so:
                    try:
                        os.remove(stale)
                    except FileNotFoundError:   # a racing builder did
                        pass
        _libs[name] = ctypes.CDLL(so)
        return _libs[name]
