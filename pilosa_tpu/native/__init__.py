"""Native (C) runtime helpers.

The compute path is JAX/XLA; these are host-side runtime hot spots where
Python-level cost caps serving throughput (the reference spends the same
cycles in compiled Go).  Each helper is optional: the .so is built from
the checked-in C source with the system compiler on first import and every
caller keeps a pure-Python fallback, so a missing toolchain degrades to
the slow path rather than failing.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))


def _build_and_load(name: str):
    """dlopen the build of native/<name>.c, compiling it first unless an
    artefact of exactly this source exists: the .so carries the source's
    content hash in its name (_<name>.<sha>.so), so a copied tree whose
    mtimes lie, or an artefact left by another version of the source, is
    never taken for current.  Returns None when there is no toolchain or
    the build fails — callers must treat the native path as an
    optimization, never a requirement."""
    src = os.path.join(_DIR, f"{name}.c")
    with open(src, "rb") as f:
        sha = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_DIR, f"_{name}.{sha}.so")
    if os.path.exists(so):
        try:
            return ctypes.CDLL(so)
        except OSError:
            pass  # corrupt / wrong-arch artifact: rebuild below
    try:
        # build to a temp file + atomic rename: concurrent importers
        # (test workers, multi-server benches) must not dlopen a
        # half-written .so
        fd, tmp = tempfile.mkstemp(suffix=".so.tmp", dir=_DIR)
        os.close(fd)
        try:
            subprocess.run(
                ["cc", "-O3", "-shared", "-fPIC", "-o", tmp, src],
                check=True, capture_output=True, timeout=60)
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        lib = ctypes.CDLL(so)
    # the native extension is an optional accelerator: no cc / a failed
    # build falls back to the pure-python path, and callers treat None
    # as exactly that
    except (OSError, subprocess.SubprocessError):
        return None
    # artefacts of other versions of the source
    for old in glob.glob(os.path.join(_DIR, f"_{name}*.so")):
        if old != so:
            try:
                os.unlink(old)
            except OSError:
                pass
    return lib


_fp_lib = _build_and_load("fingerprint")
if _fp_lib is not None:
    _fp_lib.fingerprint_scan.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_long,
    ]
    _fp_lib.fingerprint_scan.restype = ctypes.c_long


def fingerprint_live() -> bool:
    """Whether the C fingerprint scanner built and loaded (the
    prepared-statement front end takes the Python scanner otherwise)."""
    return _fp_lib is not None


def fingerprint_native(query: str):
    """(template, values int64 ndarray) via the C scanner, or None when
    the native library is unavailable or the query needs the Python path
    (int64 overflow)."""
    if _fp_lib is None:
        return None
    if not query.isascii():
        # the regex's \w matches Unicode word chars in lookarounds; the C
        # scanner is byte-wise ASCII — non-ASCII queries (keys are quoted,
        # but be exact) take the Python path
        return None
    b = query.encode("utf-8")
    n = len(b)
    tmpl = ctypes.create_string_buffer(n + 1)
    vals = np.empty(n // 2 + 1, dtype=np.int64)
    out_len = ctypes.c_long()
    nv = _fp_lib.fingerprint_scan(
        b, n, tmpl, ctypes.byref(out_len),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), vals.size)
    if nv < 0:
        return None
    return tmpl.raw[:out_len.value].decode("utf-8"), vals[:nv]
