"""Native (C++) components, built on demand with the system toolchain.

The extension is compiled lazily with g++ the first time it's needed and
cached next to its source under a name keyed by the source's hash, so a
`.so` copied along with a tree built from another source is never loaded;
any environment without a compiler (or with
SPICEDB_TPU_NO_NATIVE=1) transparently falls back to the pure-Python
implementations, so the native layer is a pure accelerator, never a
requirement.  Differential tests assert native/Python parity.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fastparse.cpp")
_EXT = sysconfig.get_config_var("EXT_SUFFIX") or ".so"

_lock = threading.Lock()
_module = None
_tried = False


def _so_path() -> str:
    """Where the build of the current fastparse.cpp lives."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_fastparse-{digest}{_EXT}")


def _build(so: str) -> bool:
    include = sysconfig.get_paths()["include"]
    # compile to a process-unique temp path and rename into place so that
    # concurrent builders (pytest-xdist, bench + server) can't dlopen a
    # half-written file — rename on the same filesystem is atomic
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           f"-I{include}", _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            sys.stderr.write(f"native build failed (falling back to python): "
                             f"{proc.stderr[-2000:]}\n")
            return False
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def load() -> Optional[object]:
    """The compiled _fastparse module, or None (pure-Python fallback)."""
    global _module, _tried
    with _lock:
        if _module is not None or _tried:
            return _module
        _tried = True
        if os.environ.get("SPICEDB_TPU_NO_NATIVE"):
            return None
        try:
            so = _so_path()
            if not os.path.exists(so) and not _build(so):
                return None
            spec = importlib.util.spec_from_file_location(
                "spicedb_kubeapi_proxy_tpu.native._fastparse", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _module = mod
        except Exception as e:  # any load failure -> python fallback
            sys.stderr.write(f"native load failed (falling back): {e}\n")
            _module = None
        return _module
