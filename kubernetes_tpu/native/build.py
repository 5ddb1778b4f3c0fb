"""Lazy g++ builds for the native components (ctypes loading; the image
ships no pybind11, and the CPython API would be overkill for these C
surfaces). The library is named after its source's content hash, so what
gets loaded was built from exactly the committed `<name>.cc` — never a
stale or foreign binary left beside it (`*.so` is untracked). A build
failure is logged with the compiler's output and returns None; consumers
then run their python implementations and report which path is active
(WalWriter.native)."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

_lock = threading.Lock()
_cache: dict = {}

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Optional[ctypes.CDLL]:
    """Compile (once per source revision) and dlopen native/<name>.cc."""
    with _lock:
        if name in _cache:
            return _cache[name]
        src = os.path.join(_SRC_DIR, f"{name}.cc")
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so = os.path.join(_SRC_DIR, f"{name}-{digest}.so")
        lib: Optional[ctypes.CDLL] = None
        try:
            if not os.path.exists(so):
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, src],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            logging.getLogger("native").warning(
                "native/%s.cc did not build or load (%r) — the python "
                "implementation runs instead. %s", name, e,
                detail.decode(errors="replace")[-2000:])
        _cache[name] = lib
        return lib
