"""Loader for the native C++ libraries (_sweed_native.so, _sweed_turbo.so).

A library is (re)built with g++ whenever the one in ``build/`` was not made
from THIS source, with THESE flags, for THIS host's CPU — a stamp file
beside each ``.so`` records what it was made from. ``build/`` is untracked
and compiled with ``-march=native``, so a copy of the tree can carry a
``.so`` from a machine with other instructions (AVX-512/GFNI vs AVX2); an
mtime comparison cannot see that, and loading it dies with SIGILL inside the
codec every identity check uses as its reference. All callers must tolerate
ImportError and fall back to pure-Python/numpy paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "build")


def _host_cpu_flags() -> str:
    """The host's instruction-set flags: what ``-march=native`` keys on."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    import platform

    return platform.machine()


def _build_key(source: str) -> str:
    """What a .so must have been made from to be loadable here."""
    h = hashlib.sha256()
    for path in (source, os.path.join(_DIR, "Makefile")):
        with open(path, "rb") as f:
            h.update(f.read())
    for var in ("CXX", "CXXFLAGS"):
        h.update(f"{var}={os.environ.get(var, '')}\n".encode())
    h.update(_host_cpu_flags().encode())
    return h.hexdigest()


def ensure_built(so_name: str, source_name: str, timeout: int = 180) -> str:
    """Path of ``build/<so_name>``, rebuilt first unless its stamp says it
    was made from the current source and flags on a host with this CPU.
    A deployment that ships a .so without the source loads it as is.
    Raises ImportError when the build fails."""
    so = os.path.join(_BUILD, so_name)
    source = os.path.join(_DIR, source_name)
    if not os.path.exists(source) and os.path.exists(so):
        return so
    stamp = so + ".stamp"
    key = _build_key(source)
    try:
        with open(stamp) as f:
            fresh = os.path.exists(so) and f.read() == key
    except OSError:
        fresh = False
    if fresh:
        return so
    # build aside and rename into place: daemons start concurrently, and a
    # loader must never dlopen a half-written library
    tmp = f"build/.tmp-{os.getpid()}"
    try:
        subprocess.run(
            ["make", "-C", _DIR, "-s", f"B={tmp}", f"{tmp}/{so_name}"],
            check=True, capture_output=True, timeout=timeout,
        )
        os.makedirs(_BUILD, exist_ok=True)
        os.replace(os.path.join(_DIR, tmp, so_name), so)
        with open(f"{stamp}.{os.getpid()}", "w") as f:
            f.write(key)
        os.replace(f"{stamp}.{os.getpid()}", stamp)  # stamp after the .so
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        out = getattr(e, "stderr", b"") or b""
        raise ImportError(
            f"native build failed: {out.decode(errors='replace')}"
        ) from e
    finally:
        shutil.rmtree(os.path.join(_DIR, tmp), ignore_errors=True)
    return so


class _Lib:
    def __init__(self) -> None:
        self._c = ctypes.CDLL(ensure_built("_sweed_native.so", "sweed_native.cpp"))
        self._c.sweed_crc32c_update.restype = ctypes.c_uint32
        self._c.sweed_crc32c_update.argtypes = [
            ctypes.c_uint32,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        self._c.sweed_kernel_variant.restype = ctypes.c_char_p
        self._c.sweed_kernel_variant.argtypes = []
        self._c.sweed_rs_matmul.restype = None
        self._c.sweed_rs_matmul.argtypes = [
            ctypes.c_void_p,  # matrix
            ctypes.c_int,  # out_rows
            ctypes.c_int,  # k
            ctypes.c_size_t,  # n
            ctypes.c_void_p,  # in
            ctypes.c_void_p,  # out
        ]
        self._c.sweed_rs_prep_bytes.restype = ctypes.c_size_t
        self._c.sweed_rs_prep_bytes.argtypes = []
        self._c.sweed_rs_prep.restype = None
        self._c.sweed_rs_prep.argtypes = [
            ctypes.c_void_p,  # matrix
            ctypes.c_int,  # out_rows
            ctypes.c_int,  # k
            ctypes.c_void_p,  # prep out
        ]
        self._c.sweed_rs_matmul_prep.restype = None
        self._c.sweed_rs_matmul_prep.argtypes = [
            ctypes.c_void_p,  # prep
            ctypes.c_int,  # out_rows
            ctypes.c_int,  # k
            ctypes.c_size_t,  # n
            ctypes.c_void_p,  # in
            ctypes.c_void_p,  # out
        ]

    def crc32c_update(self, crc: int, data: bytes) -> int:
        return self._c.sweed_crc32c_update(crc, data, len(data))

    def kernel_variant(self) -> str:
        """Which rs_matmul path this build compiled in ('avx2'/'scalar')."""
        return self._c.sweed_kernel_variant().decode()

    def rs_prep(self, matrix: np.ndarray) -> np.ndarray:
        """Derive the kernel's per-coefficient multiply prep (GFNI affine
        qwords or PSHUFB nibble tables, depending on the build) for a whole
        matrix. Cache the returned blob per matrix and pass it back through
        ``rs_matmul(..., prep=blob)`` — the hot path then never touches the
        log/exp tables."""
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        out_rows, k = matrix.shape
        stride = self._c.sweed_rs_prep_bytes()
        prep = np.empty(out_rows * k * stride, dtype=np.uint8)
        self._c.sweed_rs_prep(matrix.ctypes.data, out_rows, k, prep.ctypes.data)
        return prep

    def rs_matmul(
        self,
        matrix: np.ndarray,
        data: np.ndarray,
        prep: "np.ndarray | None" = None,
        out: "np.ndarray | None" = None,
    ) -> np.ndarray:
        """(out_rows×k GF matrix) @ (k×n bytes) → (out_rows×n bytes).

        ``out`` reuses a caller-owned result buffer: a fresh np.empty of
        hundreds of MB is mmap'd, first-touch page-faulted, and returned to
        the OS on free — measured ~2× the kernel's own runtime at GFNI
        rates. Streaming callers that consume the parity before the next
        call should allocate once and pass it back in.
        """
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        data = np.ascontiguousarray(data, dtype=np.uint8)
        out_rows, k = matrix.shape
        k2, n = data.shape
        if k != k2:
            raise ValueError(f"matrix k={k} != data rows {k2}")
        if out is None:
            out = np.empty((out_rows, n), dtype=np.uint8)
        elif (
            out.shape != (out_rows, n)
            or out.dtype != np.uint8
            or not out.flags["C_CONTIGUOUS"]
        ):
            raise ValueError(
                f"out must be C-contiguous uint8 {(out_rows, n)}, "
                f"got {out.dtype} {out.shape}"
            )
        if prep is not None:
            self._c.sweed_rs_matmul_prep(
                prep.ctypes.data, out_rows, k, n,
                data.ctypes.data, out.ctypes.data,
            )
        else:
            self._c.sweed_rs_matmul(
                matrix.ctypes.data, out_rows, k, n,
                data.ctypes.data, out.ctypes.data,
            )
        return out


lib = _Lib()
