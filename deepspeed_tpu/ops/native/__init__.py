"""Native (C++) op loading.

The op-builder analog (reference ``op_builder/builder.py``: install-time
``DS_BUILD_*`` compile or runtime ``jit_load`` with ninja): here a single
shared library is built from ``csrc/`` on first use with ``g++`` and cached
beside the package under a name keyed on its sources, compile command
and host CPU; ``available()`` is the capability probe
(``is_compatible`` analog) surfaced by ``dstpu_report``.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
from functools import lru_cache
from typing import Optional

from ...utils.logging import logger

_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "..", "csrc")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_LIB_STEM = "libdstpu_native"
_SOURCES = ["cpu_adam.cpp", "aio.cpp"]
_BASE_CMD = ["g++", "-O3", "-march=native", "-ffast-math", "-fPIC", "-shared",
             "-std=c++17", "-pthread"]


def _host_cpu() -> str:
    """What ``-march=native`` resolves to: the machine type plus the
    CPU's feature flags (a library built on another host may use
    instructions this one lacks)."""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + line
    except OSError:
        pass
    return platform.machine() + platform.processor()


def _lib_path(srcs) -> str:
    """The library's path carries a hash of everything it is made from —
    the sources, the compile command and the host's CPU — so a file left
    on disk by another checkout, another compiler line or another host is
    never loaded: only a name that matches all three is reused."""
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as fh:
            h.update(fh.read())
    h.update(" ".join(_BASE_CMD).encode())
    h.update(_host_cpu().encode())
    return os.path.join(_BUILD_DIR, f"{_LIB_STEM}-{h.hexdigest()[:16]}.so")


def build(force: bool = False) -> Optional[str]:
    """Compile csrc/ into one shared lib (jit_load analog)."""
    srcs = [os.path.abspath(os.path.join(_CSRC, s)) for s in _SOURCES]
    if not all(os.path.isfile(s) for s in srcs):
        return None
    lib_path = _lib_path(srcs)
    if not force and os.path.isfile(lib_path):
        return lib_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    for stale in glob.glob(os.path.join(_BUILD_DIR, _LIB_STEM + "*.so")):
        if stale != lib_path:
            os.remove(stale)
    # OpenMP multithreads the optimizer kernels (reference
    # csrc/includes/cpu_adam.h:171); retry without it on toolchains that
    # lack libgomp
    for extra in (["-fopenmp"], []):
        cmd = _BASE_CMD + extra + [*srcs, "-o", lib_path]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
            return lib_path
        except (subprocess.CalledProcessError, FileNotFoundError) as e:
            detail = getattr(e, "stderr", str(e))
    logger.warning(f"native op build failed ({detail}); using numpy fallbacks")
    return None


@lru_cache(None)
def load() -> Optional[ctypes.CDLL]:
    path = build()
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    i64, f32p = ctypes.c_int64, ctypes.POINTER(ctypes.c_float)
    lib.ds_adam_step.argtypes = [f32p, f32p, f32p, f32p, i64] + \
        [ctypes.c_float] * 7 + [ctypes.c_int]
    lib.ds_adagrad_step.argtypes = [f32p, f32p, f32p, i64] + [ctypes.c_float] * 3
    lib.ds_sgd_step.argtypes = [f32p, f32p, f32p, i64] + [ctypes.c_float] * 3
    lib.aio_create.restype = ctypes.c_void_p
    lib.aio_create.argtypes = [ctypes.c_int]
    lib.aio_submit.restype = i64
    lib.aio_submit.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_void_p, i64, i64, ctypes.c_int]
    lib.aio_wait.argtypes = [ctypes.c_void_p, i64]
    lib.aio_wait_all.argtypes = [ctypes.c_void_p]
    lib.aio_destroy.argtypes = [ctypes.c_void_p]
    return lib


def available() -> bool:
    return load() is not None
