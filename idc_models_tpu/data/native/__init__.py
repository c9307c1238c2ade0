"""ctypes binding for the native (C++/libpng) image loader.

Lazily builds `loader.cpp` into `_native_loader.<source digest>.so`
beside this file the first time it is needed, then exposes

    decode_batch(paths, size, threads=0) -> np.ndarray [n, size, size, 3]

`available()` reports whether the native path can be used; callers fall
back to the PIL thread pool (idc.py) when it cannot (no toolchain, no
libpng). The framework keeps the decode loop entirely outside Python —
the reference gets this from tf.data's C++ runtime (SURVEY.md §2c).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

_DIR = Path(__file__).parent
_SRC = _DIR / "loader.cpp"
_ABI = 2

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_error: str | None = None


def _so_path() -> Path:
    """The binary's name carries its source's digest, so a .so built
    from any other loader.cpp — an older checkout's, copied along with
    the tree; git does not track it — has a different name and is never
    loaded. (File times cannot tell: a copy resets them.)"""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:12]
    return _DIR / f"_native_loader.{digest}.so"


def _build(so: Path) -> None:
    """Compile to a per-process temp file and atomically rename into
    place — never truncate a .so another process may have mapped, and
    concurrent builders (e.g. multi-host workers sharing a checkout)
    cannot corrupt each other's half-written output."""
    tmp = so.with_name(f"{so.name}.{os.getpid()}.tmp")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", str(_SRC),
             "-lpng", "-lz", "-lpthread", "-o", str(tmp)],
            check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    finally:
        tmp.unlink(missing_ok=True)


def _open_checked(so: Path) -> ctypes.CDLL:
    lib = ctypes.CDLL(str(so))
    try:
        abi = lib.idc_loader_abi_version()
    except AttributeError:
        _dlclose(lib)
        raise OSError("native loader predates the ABI-version export")
    if abi != _ABI:
        # dlclose before raising: dlopen caches by pathname, so a kept
        # handle would shadow the rebuilt binary on the retry
        _dlclose(lib)
        raise OSError(f"native loader ABI {abi} != expected {_ABI}")
    return lib


def _dlclose(lib: ctypes.CDLL) -> None:
    import _ctypes

    try:
        _ctypes.dlclose(lib._handle)
    except OSError:
        pass


def _load() -> ctypes.CDLL | None:
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            so = _so_path()
            if not so.exists():
                _build(so)
            try:
                lib = _open_checked(so)
            except (OSError, AttributeError):
                # a binary under the right name that still cannot load
                # (torn file, a build against another ABI constant):
                # rebuild from the source sitting right next to it
                # rather than giving up
                _build(so)
                lib = _open_checked(so)
            lib.idc_decode_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_float), ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.idc_decode_batch.restype = ctypes.c_int
            _lib = lib
        except (OSError, subprocess.CalledProcessError, AttributeError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            _build_error = f"native loader unavailable: {detail}"
        return _lib


def available() -> bool:
    return _load() is not None


def build_error() -> str | None:
    _load()
    return _build_error


def decode_batch(paths: list[str], size: int, *,
                 threads: int = 0, on_error: str = "raise") -> np.ndarray:
    """Decode PNGs to a float32 [n, size, size, 3] batch in [0, 1].

    `on_error="raise"` (default) raises ValueError naming the files that
    failed to decode — the same loud behavior as the PIL backend, so
    `backend="auto"` cannot silently train on zero images with real
    labels attached. `on_error="zero"` keeps the lenient mode (failed
    slots stay zero images, with a warning) for callers that opt in.
    """
    if on_error not in ("raise", "zero"):
        raise ValueError(f"on_error must be raise|zero, got {on_error!r}")
    lib = _load()
    if lib is None:
        raise RuntimeError(_build_error or "native loader unavailable")
    n = len(paths)
    out = np.empty((n, size, size, 3), np.float32)
    if n == 0:
        return out
    arr = (ctypes.c_char_p * n)(*[os.fsencode(p) for p in paths])
    status = np.empty(n, np.uint8)
    failures = lib.idc_decode_batch(
        arr, n, size, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        threads, status.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if failures:
        bad = [paths[i] for i in np.flatnonzero(status == 0)]
        # even in lenient mode an entirely undecodable input must fail
        # loudly — an all-zero dataset with real labels is never useful
        if on_error == "raise" or failures >= n:
            shown = ", ".join(bad[:5])
            more = f" (+{len(bad) - 5} more)" if len(bad) > 5 else ""
            raise ValueError(
                f"{failures}/{n} files failed to decode: {shown}{more}")
        import warnings

        warnings.warn(f"{failures}/{n} files failed to decode; their "
                      f"slots are zero images", stacklevel=2)
    return out
