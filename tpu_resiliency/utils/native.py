"""Builder and loader for the repo's native helpers (build-on-demand).

The binaries under ``native/`` are build products, never committed: shipped
binaries are unreviewable.  A binary is used only when the stamp beside it
(``<binary>.src``) names the sha256 of the source and flags it would be built
from now; otherwise it is compiled to a process-unique temp file and
atomically ``os.replace``d into place (concurrent ranks on one host may build
simultaneously; a torn half-written file must never be dlopen'd or exec'd).
A binary left behind by older source therefore never runs — an mtime or a
symbol probe cannot promise that.

:func:`load_native` returns ``None`` when there is no toolchain; callers then
run their pure-Python stand-in.  Entry points that must not (the chip smoke)
call :func:`build_all` first, which raises, and check :func:`loaded` after.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Optional, Sequence

from .logging import get_logger

log = get_logger("native")

NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native")
)

# name -> (source, compiler env var, default compiler, flags before -o,
# flags after the source); mirrors native/Makefile
TARGETS: Dict[str, tuple] = {
    "libtpurx-pending.so": (
        "pending_stamp.c", "CC", "cc", ("-O2", "-Wall", "-shared", "-fPIC"), ()),
    "libtpurx-opring.so": (
        "op_ring.c", "CC", "cc", ("-O2", "-Wall", "-shared", "-fPIC"), ("-lm",)),
    "libtpurx-beat.so": (
        "beat_thread.c", "CC", "cc",
        ("-O2", "-Wall", "-shared", "-fPIC", "-D_GNU_SOURCE"), ("-lpthread",)),
    "tpurx-store-server": (
        "store_server.cpp", "CXX", "g++", ("-O2", "-std=c++17", "-Wall"), ()),
}

_cache: dict = {}
_cache_lock = threading.Lock()


def _source_stamp(src: str, flags: Sequence[str]) -> str:
    h = hashlib.sha256(" ".join(flags).encode())
    with open(src, "rb") as f:
        h.update(f.read())
    return h.hexdigest()


def ensure_built(name: str) -> tuple:
    """Build ``native/<name>`` unless its stamp matches the current source.
    Returns ``(path, built)``; raises ``OSError``/``SubprocessError`` when
    the source cannot be compiled."""
    src_name, cc_var, cc_default, pre, post = TARGETS[name]
    path = os.path.join(NATIVE_DIR, name)
    src = os.path.join(NATIVE_DIR, src_name)
    want = _source_stamp(src, (*pre, *post))
    try:
        with open(path + ".src") as f:
            if f.read().strip() == want and os.path.exists(path):
                return path, False
    except OSError:
        pass
    tmp = f"{path}.build.{os.getpid()}.{threading.get_ident()}"
    try:
        subprocess.run(
            [os.environ.get(cc_var, cc_default), *pre, "-o", tmp, src, *post],
            check=True, capture_output=True, text=True, timeout=120,
        )
        os.replace(tmp, path)
        with open(tmp, "w") as f:
            f.write(want)
        os.replace(tmp, path + ".src")
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass
    log.info("built native/%s from %s", name, src_name)
    return path, True


def build_all() -> Dict[str, bool]:
    """Build every native target now; ``{name: built_in_this_call}``."""
    return {name: ensure_built(name)[1] for name in TARGETS}


def load_native(lib_name: str) -> Optional[ctypes.CDLL]:
    """``ctypes.CDLL`` of ``native/<lib_name>``, built first when missing or
    stale; ``None`` when it cannot be built or loaded."""
    with _cache_lock:
        if lib_name not in _cache:
            _cache[lib_name] = _build_and_open(lib_name)
        return _cache[lib_name]


def _build_and_open(lib_name: str) -> Optional[ctypes.CDLL]:
    try:
        path, built = ensure_built(lib_name)
        if not built:
            return ctypes.CDLL(path)
        # glibc dedupes dlopen by pathname: had this process already opened
        # an older file at ``path``, re-opening ``path`` would return the OLD
        # mapping.  A private link has a fresh name.
        private = f"{path}.load.{os.getpid()}"
        try:
            os.link(path, private)
            return ctypes.CDLL(private)
        finally:
            try:
                os.unlink(private)
            except OSError:
                pass
    except (OSError, subprocess.SubprocessError) as exc:
        log.warning("native %s unavailable (%s); callers fall back to "
                    "pure Python", lib_name, exc)
        return None


def loaded() -> Dict[str, bool]:
    """Which shared libraries this process asked for, and whether each is
    really loaded (False = its caller runs the pure-Python stand-in)."""
    return {name: lib is not None for name, lib in _cache.items()}
