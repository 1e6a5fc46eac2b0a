"""Build-on-first-use for the native shm ring (g++ -> _shmring.so).

Many rank processes may import concurrently (the launcher spawns them in a
burst), so the compile is serialized with an exclusive flock and lands via
atomic rename; losers of the race find the finished .so.  The .so is cached
next to the source, keyed by a hash of shmring.cpp and the compiler flags
(stored beside it in ``<name>.so.key``): a .so whose key does not match —
say, an untracked build copied along with a newer tree — is rebuilt, and
file times play no part.

Sanitizer builds: ``MPI_TPU_SANITIZE=address|undefined|thread`` adds the
matching ``-fsanitize=`` flags and caches the result under a
mode-specific name (``_shmring.asan.so`` etc.) so sanitized and plain
builds never overwrite each other.  Loading an ASan build into an
un-instrumented python usually needs ``LD_PRELOAD=$(gcc
-print-file-name=libasan.so)`` — see tests/test_sanitize_native.py for
the working recipe.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "shmring.cpp")
_SO = os.path.join(_DIR, "_shmring.so")

# sanitizer mode -> (cache-name infix, -fsanitize= flag list); the env
# knob is read per build so one process builds exactly one mode
_SANITIZERS = {
    "address": ("asan", ["-fsanitize=address", "-fno-omit-frame-pointer",
                         "-g"]),
    "undefined": ("ubsan", ["-fsanitize=undefined",
                            "-fno-sanitize-recover=undefined", "-g"]),
    "thread": ("tsan", ["-fsanitize=thread", "-g"]),
}

_lib = None


class NativeBuildError(RuntimeError):
    pass


def sanitize_mode() -> str:
    """The MPI_TPU_SANITIZE env knob, validated ('' = plain build)."""
    mode = os.environ.get("MPI_TPU_SANITIZE", "").strip()
    if mode and mode not in _SANITIZERS:
        raise NativeBuildError(
            f"unknown MPI_TPU_SANITIZE={mode!r}; accepted: "
            f"{sorted(_SANITIZERS)} (or unset for a plain build)")
    return mode


def _so_path(mode: str) -> str:
    if not mode:
        return _SO
    return os.path.join(_DIR, f"_shmring.{_SANITIZERS[mode][0]}.so")


def _cmd(mode: str, out: str) -> list:
    # -lrt: shm_open/shm_unlink live in librt on pre-2.34 glibc (a stub
    # librt still exists on newer ones, so the flag is portable both ways)
    return ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
            *(_SANITIZERS[mode][1] if mode else []),
            "-o", out, _SRC, "-pthread", "-lrt"]


def _key(mode: str) -> str:
    """Hash of the source and the command the .so is built with."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update("\0".join(_cmd(mode, "")).encode())
    return h.hexdigest()


def _fresh(so: str, key: str) -> bool:
    try:
        with open(so + ".key") as f:
            return f.read() == key and os.path.exists(so)
    except OSError:
        return False


def ensure_built(force: bool = False) -> str:
    """Compile shmring.cpp if needed; return the path to the .so.

    ``force`` rebuilds even when the cached .so's key matches — the
    recovery path for a .so carried over from a host with a different
    glibc layout (dlopen fails with an unresolved symbol; see
    load_shmring)."""
    mode = sanitize_mode()
    so = _so_path(mode)
    key = _key(mode)
    if not force and _fresh(so, key):
        return so
    lock_path = os.path.join(_DIR, ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if not force and _fresh(so, key):
                return so  # another process built it while we waited
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
            os.close(fd)
            cmd = _cmd(mode, tmp)
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True)
            except FileNotFoundError as e:
                os.unlink(tmp)
                raise NativeBuildError(
                    "g++ not found; the shm backend needs the native "
                    "toolchain (fall back to backend=socket)") from e
            if proc.returncode != 0:
                os.unlink(tmp)
                raise NativeBuildError(
                    f"shmring.cpp failed to compile"
                    f"{f' (MPI_TPU_SANITIZE={mode})' if mode else ''}:\n"
                    f"{proc.stderr[-2000:]}")
            os.replace(tmp, so)
            with open(tmp + ".key", "w") as f:
                f.write(key)
            os.replace(tmp + ".key", so + ".key")
            return so
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)


def load_shmring() -> ctypes.CDLL:
    """Load (building if necessary) and type the native library."""
    global _lib
    if _lib is not None:
        return _lib
    try:
        lib = ctypes.CDLL(ensure_built())
    except OSError:
        # a cached .so from a host with a different glibc (e.g. shm_open
        # moved between librt and libc) fails at dlopen, not at build —
        # recompile against THIS toolchain and retry once
        lib = ctypes.CDLL(ensure_built(force=True))
    lib.shmring_create.restype = ctypes.c_void_p
    lib.shmring_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.shmring_open.restype = ctypes.c_void_p
    lib.shmring_open.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.shmring_avail.restype = ctypes.c_uint64
    lib.shmring_avail.argtypes = [ctypes.c_void_p]
    # buf params are c_void_p: accepts bytes, ctypes buffers, AND raw
    # integer addresses (ndarray.ctypes.data) — the zero-copy array path
    lib.shmring_write.restype = ctypes.c_int
    lib.shmring_write.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                  ctypes.c_uint64, ctypes.c_double]
    lib.shmring_read.restype = ctypes.c_int
    lib.shmring_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_uint64, ctypes.c_double]
    lib.shmring_read_some.restype = ctypes.c_int64
    lib.shmring_read_some.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_uint64, ctypes.c_double]
    lib.shmring_write_some.restype = ctypes.c_int64
    lib.shmring_write_some.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_uint64, ctypes.c_double]
    lib.shmring_close.restype = None
    lib.shmring_close.argtypes = [ctypes.c_void_p]
    lib.shmring_unlink.restype = ctypes.c_int
    lib.shmring_unlink.argtypes = [ctypes.c_char_p]
    lib.shmdb_create.restype = ctypes.c_void_p
    lib.shmdb_create.argtypes = [ctypes.c_char_p]
    lib.shmdb_open.restype = ctypes.c_void_p
    lib.shmdb_open.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.shmdb_read.restype = ctypes.c_uint32
    lib.shmdb_read.argtypes = [ctypes.c_void_p]
    lib.shmdb_ring.restype = None
    lib.shmdb_ring.argtypes = [ctypes.c_void_p]
    lib.shmdb_wait.restype = ctypes.c_uint32
    lib.shmdb_wait.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                               ctypes.c_double]
    lib.shmdb_close.restype = None
    lib.shmdb_close.argtypes = [ctypes.c_void_p]
    lib.shmdb_unlink.restype = ctypes.c_int
    lib.shmdb_unlink.argtypes = [ctypes.c_char_p]
    # collective arena (coll/sm): one segment per shm communicator, with
    # per-rank flag lines driven by the shmflag_* ops (mpi_tpu/coll_sm.py)
    lib.shmarena_create.restype = ctypes.c_void_p
    lib.shmarena_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.shmarena_open.restype = ctypes.c_void_p
    lib.shmarena_open.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.shmarena_addr.restype = ctypes.c_uint64
    lib.shmarena_addr.argtypes = [ctypes.c_void_p]
    lib.shmarena_size.restype = ctypes.c_uint64
    lib.shmarena_size.argtypes = [ctypes.c_void_p]
    lib.shmarena_close.restype = None
    lib.shmarena_close.argtypes = [ctypes.c_void_p]
    lib.shmarena_unlink.restype = ctypes.c_int
    lib.shmarena_unlink.argtypes = [ctypes.c_char_p]
    lib.shmflag_read.restype = ctypes.c_uint32
    lib.shmflag_read.argtypes = [ctypes.c_uint64]
    lib.shmflag_post.restype = None
    lib.shmflag_post.argtypes = [ctypes.c_uint64, ctypes.c_uint32]
    lib.shmflag_wait_ge.restype = ctypes.c_uint32
    lib.shmflag_wait_ge.argtypes = [ctypes.c_uint64, ctypes.c_uint32,
                                    ctypes.c_double]
    _lib = lib
    return lib
