"""On-demand cmake+ninja build of the native tier (native/).

Shared by all ctypes bindings: one cmake project produces every shared
library (scheduler, control-plane core). No packaging step, no pybind11
(not in the image) — the C ABI plus ctypes is the binding layer.
"""

from __future__ import annotations

import ctypes
import shutil
import subprocess
import threading
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent.parent
_NATIVE = _REPO / "native"
_BUILD = _NATIVE / "build"
_build_lock = threading.Lock()


def _foreign_build_dir() -> bool:
    """Whether native/build was configured for ANOTHER source tree: the
    directory is git-ignored, so a copy of the checkout at a different
    root carries the old root's absolute paths in its CMakeCache.txt and
    cmake refuses to reuse it."""
    cache = _BUILD / "CMakeCache.txt"
    if not cache.exists():
        return False
    for line in cache.read_text(errors="replace").splitlines():
        if line.startswith("CMAKE_HOME_DIRECTORY:"):
            home = line.partition("=")[2].strip()
            return Path(home).resolve() != _NATIVE.resolve()
    return True


def ensure_built(lib_name: str) -> Path:
    """Build (if stale) and return the path to native/build/<lib_name>.

    Nothing here depends on a pre-existing build directory: a fresh
    checkout builds from the committed sources, and a build directory
    carried over from another root is thrown away first."""
    lib = _BUILD / lib_name
    # _build_lock exists to serialize exactly these cmake invocations
    # (two racing builders corrupt the ninja state); the subprocess IS
    # the critical section, and nothing else ever takes this lock.
    with _build_lock:
        sources = list((_NATIVE / "src").glob("*.cc")) + [
            _NATIVE / "CMakeLists.txt"
        ]
        src_newest = max(p.stat().st_mtime for p in sources)
        if _foreign_build_dir():
            shutil.rmtree(_BUILD)
        if not lib.exists() or lib.stat().st_mtime < src_newest:
            subprocess.run(  # kftpu-lint: disable=blocking-under-lock
                ["cmake", "-S", str(_NATIVE), "-B", str(_BUILD), "-G",
                 "Ninja"],
                check=True, capture_output=True,
            )
            subprocess.run(  # kftpu-lint: disable=blocking-under-lock
                ["cmake", "--build", str(_BUILD)],
                check=True, capture_output=True,
            )
    return lib


_libs: dict[str, ctypes.CDLL] = {}
_libs_lock = threading.Lock()


def load(lib_name: str, configure) -> ctypes.CDLL:
    """Load a native library once per process; `configure(lib)` declares
    the C ABI (argtypes/restypes) on first load.

    The cmake build runs OUTSIDE `_libs_lock` (a cold-cache build takes
    seconds; holding the cache lock over it would stall every other
    library's `load`). Two racing first-loaders may both CDLL the same
    library; the insert is double-checked so exactly one wins, and a
    duplicate CDLL handle of the same .so is harmless."""
    with _libs_lock:
        cached = _libs.get(lib_name)
    if cached is not None:
        return cached
    built = ensure_built(lib_name)
    fresh = ctypes.CDLL(str(built))
    configure(fresh)
    with _libs_lock:
        cached = _libs.get(lib_name)
        if cached is None:
            _libs[lib_name] = cached = fresh
        return cached
